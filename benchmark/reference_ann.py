"""The plain reference of the exact-KNN configurations (``ann-sift-1m``):
NumPy only, nothing of ``redisson_tpu`` imported.

The data are seeded stand-ins for a dataset file that is not here: a mixture
of Gaussians rounded and clipped to integers 0-255, as SIFT descriptors are,
so every squared L2 distance is an integer below 2**24 — exact in float32
whatever the order of summation, and the reference needs no tolerance.

``topk`` walks the base in row blocks: one float32 matmul a block (exact on
integers of this size), a first block's partition for a bound on each
query's k-th distance, then only the few columns at or under that bound.
Ties go to the lower rowid.  1,000,000 x 10,000 takes a few tens of seconds
on a dozen cores, beside the server's boot.
"""
import numpy as np

DATA_STREAM = 0xA22


def _rng(seed: int, *where: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, DATA_STREAM, *where])


def make_points(seed: int, stream: int, lo: int, hi: int, dim: int,
                centres: int, spread: float) -> np.ndarray:
    """Points [lo, hi) of one stream (0: the base, 1: the queries) as uint8
    (hi - lo, dim): each draws one of the seed's ``centres`` centres and a
    Gaussian of ``spread`` around it, rounded and clipped to 0-255.  Made in
    blocks of 65,536 points, each a pure function of (seed, stream, block)."""
    block = 1 << 16
    mu = _rng(seed, 9).integers(0, 160, (centres, dim)).astype(np.float32)
    out = np.empty((hi - lo, dim), np.uint8)
    for b in range(lo // block, -(-hi // block)):
        g = _rng(seed, stream, b)
        which = g.integers(0, centres, block)
        pts = mu[which] + g.standard_normal((block, dim), np.float32) * np.float32(spread)
        a, z = max(lo, b * block), min(hi, (b + 1) * block)
        out[a - lo:z - lo] = np.clip(np.rint(pts[a - b * block:z - b * block]), 0, 255)
    return out


def sq_l2(queries: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """(M, d) against (M, d) integer vectors -> (M,) exact squared distances."""
    diff = queries.astype(np.int64) - rows.astype(np.int64)
    return (diff * diff).sum(axis=1)


def _sparse_nonzero(mask: np.ndarray):
    """np.nonzero of a 2-D bool array that is almost all False: the words
    with a set byte are found eight bytes at a time."""
    cols = mask.shape[1]
    words = np.flatnonzero(mask.reshape(-1).view(np.uint64))
    sub, at = np.nonzero(mask.reshape(-1, 8)[words])
    flat = words[sub] * 8 + at
    return flat // cols, flat % cols


class _Chunk:
    """The running k best of one chunk of queries, and its tile buffers."""

    def __init__(self, queries: np.ndarray, k: int, block: int):
        q = queries.astype(np.float32)
        self.qa = np.concatenate([q, (q * q).sum(axis=1, keepdims=True),
                                  np.ones((len(q), 1), np.float32)], axis=1)
        self.d = np.empty((len(q), block), np.float32)
        self.mask = np.empty((len(q), block), bool)
        self.best_d = np.full((len(q), k), np.inf, np.float32)
        self.best_i = np.zeros((len(q), k), np.int64)
        self.rows_of = np.repeat(np.arange(len(q)), k)

    def take(self, xa: np.ndarray, r0: int, k: int, skip, lower: bool) -> None:
        m, n_q = len(xa), len(self.qa)
        d, mask = self.d[:, :m], self.mask[:, :m]
        np.matmul(self.qa, xa.T, out=d)
        if lower:
            d[:] = (d.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)
        if skip is not None:
            d[:, skip[(skip >= r0) & (skip < r0 + m)] - r0] = np.inf
        if r0 == 0 and m > k:  # a bound to filter the other blocks by
            bound = np.partition(d, k - 1, axis=1)[:, k - 1]
        else:
            bound = self.best_d[:, -1]
        np.less_equal(d, bound[:, None], out=mask)
        qi, col = _sparse_nonzero(mask)
        cand_d = np.concatenate([self.best_d.ravel(), d[qi, col]])
        cand_i = np.concatenate([self.best_i.ravel(), col + r0])
        cand_q = np.concatenate([self.rows_of, qi])
        order = np.lexsort((cand_i, cand_d, cand_q))
        cand_d, cand_i, cand_q = cand_d[order], cand_i[order], cand_q[order]
        take = np.searchsorted(cand_q, np.arange(n_q))[:, None] + np.arange(k)
        self.best_d, self.best_i = cand_d[take], cand_i[take]


def _sparse_nonzero(mask: np.ndarray):
    """np.nonzero of a 2-D bool array that is almost all False: the words
    with a set byte are found eight bytes at a time."""
    cols = mask.shape[1]
    if mask.size % 8:
        return np.nonzero(mask)
    flat = np.ascontiguousarray(mask).reshape(-1)
    words = np.flatnonzero(flat.view(np.uint64))
    sub, at = np.nonzero(flat.reshape(-1, 8)[words])
    where = words[sub] * 8 + at
    return where // cols, where % cols


def topk(base: np.ndarray, queries: np.ndarray, k: int, block: int = 1 << 13,
         chunk: int = 1024, dead=None, lower: bool = False, threads: int = 4):
    """The ``k`` nearest rows of ``base`` (N, d) uint8 to each of ``queries``
    (Q, d) uint8 by squared L2: (ids int32 (Q, k), dists int64 (Q, k)),
    distance ascending, ties toward the lower rowid.  ``dead``: rowids left
    out.  ``lower``: distances rounded to bfloat16 before they are ranked —
    what the nearest precision below float32 gives; for the tests that show
    the comparison tells it apart, never for a run.

    A tile's distances are ONE float32 matmul of [q, |q|^2, 1] against
    [-2x, 1, |x|^2]: every partial sum is an integer below 2**24 in
    magnitude, so the result is exact in any order of summation.  The
    chunks of queries take a block side by side on ``threads`` threads
    (NumPy lets go of the GIL where the time is)."""
    from concurrent.futures import ThreadPoolExecutor

    n, dim = len(base), base.shape[1]
    block = min(block, n)
    skip = None if dead is None else np.asarray(dead, np.int64)
    chunks = [_Chunk(queries[c:c + chunk], k, block)
              for c in range(0, len(queries), chunk)]
    xa = np.empty((block, dim + 2), np.float32)
    with ThreadPoolExecutor(max(1, threads)) as pool:
        for r0 in range(0, n, block):
            x = base[r0:r0 + block].astype(np.float32)
            m = len(x)
            xa[:m, :dim], xa[:m, dim] = -2.0 * x, 1.0
            xa[:m, dim + 1] = (x * x).sum(axis=1)
            list(pool.map(lambda c: c.take(xa[:m], r0, k, skip, lower), chunks))
    ids = np.concatenate([c.best_i for c in chunks]).astype(np.int32)
    if lower:  # the ranks came of rounded distances; report the true ones
        dists = np.stack([sq_l2(queries, base[ids[:, j]]) for j in range(k)], axis=1)
    else:
        dists = np.concatenate([c.best_d for c in chunks]).astype(np.int64)
    return ids, dists


def reply_failures(base: np.ndarray, queries: np.ndarray, ref_dist: np.ndarray,
                   got_ids: np.ndarray, got_dist: np.ndarray, dead=()) -> np.ndarray:
    """Which of Q replies are wrong.  A reply is right when its k distances
    equal the reference's k — exactly: integers, and the reply's four
    decimals carry them whole — every returned id's true distance is the one
    it was returned with (so at most the reference's k-th: any member of a
    tie at the boundary is accepted), no id comes twice and none is dead.
    Exact float32 L2 is what the configuration states; distances rounded to
    bfloat16 before ranking fail this on most queries
    (benchmark/tests/test_ann.py), so it holds the stated precision."""
    k = ref_dist.shape[1]
    if got_ids.shape != (len(queries), k):
        return np.ones(len(queries), bool)
    safe = np.clip(got_ids, 0, len(base) - 1)
    true = np.stack([sq_l2(queries, base[safe[:, j]]) for j in range(k)], axis=1)
    bad = (got_ids < 0).any(axis=1) | (got_ids >= len(base)).any(axis=1)
    bad |= (got_dist != ref_dist).any(axis=1) | (true != got_dist).any(axis=1)
    srt = np.sort(got_ids, axis=1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    if len(dead):
        bad |= np.isin(got_ids, np.asarray(dead)).any(axis=1)
    return bad

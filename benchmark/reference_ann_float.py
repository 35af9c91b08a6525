"""The plain reference of the float KNN configuration (``ann-gist-1m``):
NumPy only, nothing of ``redisson_tpu`` imported.

The data are seeded stand-ins for a dataset file that is not here:
non-negative, non-integer float32 values in [0, 1), a mixture of clusters in
``dim`` dimensions (each centre's coordinates exponential around a small
mean, each cluster its own spread), so squared nearest-neighbour distances
are O(0.1-2) as GIST's are and no distance is an integer.

**The tolerance.**  The configuration states float32 L2: the distance is a
float32 evaluation of the sum over ``dim`` terms of (q_i - x_i)^2.  Each
difference is rounded once (relative error u = 2**-24, twice that in its
square), each square once more, and a sum of ``dim`` non-negative terms
carries at most (1 + u)**(dim - 1) - 1 of its value in rounding, so the
whole evaluation lies within

    EPS = gamma(dim + 2) = (dim + 2) u / (1 - (dim + 2) u)

of the true distance, relative to that distance (5.734e-5 at dim = 960),
whatever the order of summation.  That is the stated precision, and it is
what a reply is held to:

  * a returned id is ADMISSIBLE when its float64 distance is at most the
    query's k-th float64 distance times (1 + EPS): a float32 evaluation
    cannot tell those from the k-th, anything farther it can;
  * a printed score, parsed to a float32, lies within EPS x (that id's
    float64 distance) of it; a copy of the query must read exactly 0.

ANN-Benchmarks' own rule, +1e-3 on the (square-root) Euclidean distance of
the k-th neighbour, is not used: at a distance of ~0.6 it admits ~0.3 % on
the squared distance, 60 x EPS, which lets a ranking from bfloat16 operands
(2**-9 a value) through on most queries.  The stated precision is float32,
so the check is float32's.

**The tables.**  ``tables`` finds each query's candidates by one float32
pass over the base in row blocks (|q|^2 - 2 q.x + |x|^2, one matmul a
block; a query's running ``m`` best), then ranks those candidates by their
float64 distances.  A query whose m-th float32 candidate does not clear its
admissible bound by more than that pass's own worst-case rounding is ranked
again over the whole base in float64, so no admissible id is missed.  The
workers check replies against these (Q, m) tables, never against the base.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

DATA_STREAM = 0xA23
U32 = 2.0 ** -24  # float32's unit roundoff
POINT_BLOCK = 1 << 16


def eps(dim: int) -> float:
    """gamma(dim + 2): the relative rounding a float32 L2 of ``dim`` terms
    may carry (see the module's docstring)."""
    n = dim + 2
    return n * U32 / (1.0 - n * U32)


def _rng(seed: int, *where: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, DATA_STREAM, *where])


def make_points(seed: int, stream: int, lo: int, hi: int, dim: int, centres: int,
                centre_mean: float, spread: tuple, threads: int = 4) -> np.ndarray:
    """Points [lo, hi) of one stream (0: the base, 1: the queries) as float32
    (hi - lo, dim): each draws one of the seed's ``centres`` centres
    (coordinates exponential of mean ``centre_mean``) and a Gaussian around
    it whose deviation is the centre's own, log-uniform in ``spread``;
    folded to non-negative and capped below 1.  Made in blocks of 65,536
    points, each a pure function of (seed, stream, block)."""
    g0 = _rng(seed, 9)
    mu = np.minimum(g0.exponential(centre_mean, (centres, dim)), 0.9).astype(np.float32)
    sigma = np.exp(g0.uniform(np.log(spread[0]), np.log(spread[1]), centres)).astype(np.float32)
    out = np.empty((hi - lo, dim), np.float32)

    def fill(b: int) -> None:
        g = _rng(seed, stream, b)
        which = g.integers(0, centres, POINT_BLOCK)
        noise = g.standard_normal((POINT_BLOCK, dim), np.float32)
        a, z = max(lo, b * POINT_BLOCK), min(hi, (b + 1) * POINT_BLOCK)
        at = slice(a - b * POINT_BLOCK, z - b * POINT_BLOCK)
        pts = np.abs(mu[which[at]] + noise[at] * sigma[which[at]][:, None])
        out[a - lo:z - lo] = np.minimum(pts, np.float32(0.999))

    with ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(fill, range(lo // POINT_BLOCK, -(-hi // POINT_BLOCK))))
    return out


def sq_l2(queries: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """(M, d) against (M, d) -> (M,) float64 squared distances (the products
    of float32 values are exact in float64; the sum's rounding is ~1e-13
    of the distance)."""
    diff = queries.astype(np.float64) - rows.astype(np.float64)
    return np.einsum("md,md->m", diff, diff)


def bf16(a: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (to nearest, ties to even), kept as
    float32: the operands a DEFAULT-precision matmul multiplies."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def _candidates(parts, queries: np.ndarray, m: int, block: int, threads: int,
                lower: bool):
    """The ``m`` smallest float32 expanded distances of each query over the
    rows of ``parts`` ((rows, first id, dead ids) each): (ids (Q, m),
    distances (Q, m)), unordered, and the largest squared norm of a row.
    ``lower``: the dot products of bfloat16 operands, as DEFAULT precision
    computes them."""
    q = np.ascontiguousarray(queries, np.float32)
    qn = np.einsum("qd,qd->q", q, q, dtype=np.float32)
    qm = bf16(q) if lower else q
    chunks = [slice(c, min(len(q), c + 128)) for c in range(0, len(q), 128)]
    best_d = np.full((len(q), m), np.inf, np.float32)
    best_i = np.full((len(q), m), -1, np.int64)
    xn_max = 0.0
    with ThreadPoolExecutor(max(1, threads)) as pool:
        for rows, first, dead in parts:
            for r0 in range(0, len(rows), block):
                x = np.ascontiguousarray(rows[r0:r0 + block], np.float32)
                xn = np.einsum("nd,nd->n", x, x, dtype=np.float32)
                xn_max = max(xn_max, float(xn.max()))
                xm = bf16(x) if lower else x
                gone = None
                if dead is not None and len(dead):
                    rel = dead - first - r0
                    gone = rel[(rel >= 0) & (rel < len(x))]

                def take(c, x=x, xn=xn, xm=xm, r0=r0, first=first, gone=gone):
                    d = qn[c, None] - 2.0 * (qm[c] @ xm.T) + xn[None, :]
                    if gone is not None:
                        d[:, gone] = np.inf
                    ids = np.broadcast_to(np.arange(len(x)) + (first + r0), d.shape)
                    cd = np.concatenate([best_d[c], d], axis=1)
                    ci = np.concatenate([best_i[c], ids], axis=1)
                    keep = np.argpartition(cd, m - 1, axis=1)[:, :m]
                    best_d[c] = np.take_along_axis(cd, keep, axis=1)
                    best_i[c] = np.take_along_axis(ci, keep, axis=1)

                list(pool.map(take, chunks))
    return best_i, best_d, xn_max


def _rank(parts, queries: np.ndarray, ids: np.ndarray):
    """Candidate ids (Q, m) -> (ids, float64 distances), each row ascending
    by distance, ties toward the lower id."""
    dist = np.empty(ids.shape, np.float64)
    for j in range(ids.shape[1]):
        dist[:, j] = sq_l2(queries, _rows_of(parts, ids[:, j]))
    order = np.lexsort((ids, dist), axis=1)
    return np.take_along_axis(ids, order, axis=1), np.take_along_axis(dist, order, axis=1)


def _rows_of(parts, ids: np.ndarray) -> np.ndarray:
    out = np.empty((len(ids), parts[0][0].shape[1]), np.float32)
    for rows, first, _dead in parts:
        at = (ids >= first) & (ids < first + len(rows))
        out[at] = rows[ids[at] - first]
    return out


def tables(parts, queries: np.ndarray, k: int, m: int = 32, block: int = 1 << 15,
           threads: int = 4, lower: bool = False) -> dict:
    """The per-query tables a reply is checked against.  ``parts``: the live
    rows as ((rows (n, d) float32, first id, dead ids or None), ...).

    Returns ``ids`` (Q, m) int64 and ``dist`` (Q, m) float64, ascending by
    float64 distance, and ``bound`` (Q,) = k-th distance x (1 + EPS): the
    admissible ids are those of a row with ``dist <= bound``.  ``lower``:
    the candidates a ranking from bfloat16 operands would pick, in their
    own order (ids only are meaningful: the tests' mutation, never a run)."""
    dim = queries.shape[1]
    e = eps(dim)
    cand, cand_d, xn_max = _candidates(parts, queries, m, block, threads, lower)
    if lower:
        order = np.lexsort((cand, cand_d), axis=1)
        return {"ids": np.take_along_axis(cand, order, axis=1)}
    ids, dist = _rank(parts, queries, cand)
    bound = dist[:, k - 1] * (1.0 + e)
    # the float32 pass's own worst case: EPS of |q|^2 + 2|q.x| + |x|^2
    q64 = queries.astype(np.float64)
    qn = np.einsum("qd,qd->q", q64, q64)
    slack = 2.0 * e * (qn + xn_max) + 1e-6
    short = np.flatnonzero(cand_d.max(axis=1).astype(np.float64) - slack <= bound)
    for qi in short:  # the m candidates may not hold every admissible id
        ids[qi], dist[qi] = _exact_row(parts, queries[qi], m)
        bound[qi] = dist[qi, k - 1] * (1.0 + e)
    if (dist[:, -1] <= bound).any():
        raise RuntimeError("a query has more than m admissible ids: raise m")
    return {"ids": ids, "dist": dist, "bound": bound, "exact_rows": np.array([len(short)])}


def _exact_row(parts, query: np.ndarray, m: int):
    """One query ranked in float64 over every live row."""
    q = query.astype(np.float64)
    d_all, i_all = [], []
    for rows, first, dead in parts:
        for r0 in range(0, len(rows), 1 << 15):
            x = rows[r0:r0 + (1 << 15)].astype(np.float64) - q
            d = np.einsum("nd,nd->n", x, x)
            if dead is not None and len(dead):
                rel = dead - first - r0
                d[rel[(rel >= 0) & (rel < len(d))]] = np.inf
            d_all.append(d)
            i_all.append(np.arange(len(d)) + first + r0)
    d, i = np.concatenate(d_all), np.concatenate(i_all)
    order = np.lexsort((i, d))[:m]
    return i[order], d[order]


def parse_scores(texts) -> np.ndarray:
    """Reply score texts -> the float32 values they name, as float64."""
    return np.array([float(np.float32(float(t))) for t in texts], np.float64)


def deviations(table: dict, got_ids: np.ndarray, got_scores: np.ndarray, dim: int) -> tuple:
    """How near replies come to the limits, in units of EPS: the largest
    |score - distance| / distance of a hit, and the largest (distance -
    k-th distance) / k-th distance of a returned id (a reply passes at 1 or
    less in both).  Hits whose id is not in the tables are left out."""
    ids, dist, bound = table["ids"], table["dist"], table["bound"]
    e = eps(dim)
    hit = got_ids[:, :, None] == ids[:, None, :]
    found = hit.any(axis=2)
    true = (hit * dist[:, None, :]).sum(axis=2)
    kth = (bound / (1.0 + e))[:, None]
    live = found & (true > 0)
    score = np.abs(got_scores - true)[live] / true[live] / e
    rank = ((true - kth) / kth / e)[found]
    return (float(score.max()) if score.size else 0.0, float(rank.max()) if rank.size else 0.0)


def reply_failures(table: dict, got_ids: np.ndarray, got_scores: np.ndarray,
                   dim: int, dead=()) -> np.ndarray:
    """Which of Q replies are wrong.  ``table``: the queries' rows of
    ``tables`` (``ids``, ``dist``, ``bound``); ``got_ids`` (Q, k) int64, -1
    where a reply held fewer than k hits; ``got_scores`` (Q, k): the printed
    scores parsed to float32 (``parse_scores``).  A reply is right when its
    k ids are distinct, live and admissible, each score lies within EPS x
    that id's float64 distance of it, and the scores ascend."""
    ids, dist, bound = table["ids"], table["dist"], table["bound"]
    n_q, k = got_ids.shape
    e = eps(dim)
    hit = got_ids[:, :, None] == ids[:, None, :]              # (Q, k, m)
    found = hit.any(axis=2)
    true = np.where(found, (hit * dist[:, None, :]).sum(axis=2), np.inf)
    bad = ~found.all(axis=1) | (got_ids < 0).any(axis=1)
    bad |= (true > bound[:, None]).any(axis=1)
    with np.errstate(invalid="ignore"):
        bad |= ~(np.abs(got_scores - true) <= e * true).all(axis=1)
    bad |= (np.diff(got_scores, axis=1) < 0).any(axis=1)
    srt = np.sort(got_ids, axis=1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    if len(dead):
        bad |= np.isin(got_ids, np.asarray(dead)).any(axis=1)
    return bad

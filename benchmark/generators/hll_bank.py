"""Traffic against one bank of HyperLogLog counters (``HLLA.*`` through
``RemoteRedisson.get_hyper_log_log_array``): streaming adds, and every
``read_every``-th call one pipelined frame that merges counter pairs and
reads the pairs' union cardinalities.

The counters are of two kinds, as in a service that counts per time bucket
and rolls closed buckets up: the last ``closed`` counters were filled in
set-up and take no more adds (sources of merges); the others are live (adds
by Zipf popularity, destinations of merges).  A source never changes during
the run, so a destination's final registers are the max of its own keys and
its sources' whatever the order in which connections were served — which is
what lets the reference be exact under concurrent connections.

``HLLA.MADD64`` is acknowledged when its scatter is enqueued, not when it
has run.  So adds count only through a later read on their connection: each
connection ends its window with a read frame (``closing``), and the checks
below read the bank after that.

A stream client's unit of work is a CYCLE: ``read_every - 1`` add frames and
the read frame that makes them count.  With ``"latency_over": "cycle"`` in
the traffic file the judged latency is a cycle's wall time over its frames
(``benchmark/latency.py``); ``cycle_ends`` below says which frames end one.

Traffic parameters: pairs_per_add (100000), pairs_per_read (1000),
read_every (10), skew, latency_over.
"""
import numpy as np

from benchmark import datagen as D
from benchmark.reference import RefHll, hll_failures, hll_hard

NAME = "bench:hll"
KIND_ADD, KIND_READ = 0, 1
_STREAM = 0x4111
_SLOT = 1 << 24  # key numbers a counter's set-up keys may take


def _live(sizes) -> int:
    return sizes["counters"] - sizes["closed"]


def _prefill(seed: int, sizes: dict, counters):
    """(counter ids, keys) set-up gives to ``counters``: ``prefill_per`` keys
    each, and ``heavy_n`` more on the first ``heavy`` counters."""
    counters = np.asarray(counters, np.int64)
    per, heavy_n = sizes["prefill_per"], sizes["heavy_n"]
    ids = [np.repeat(counters, per)]
    keys = [D.keys(seed, D.POPULATED,
                   (counters[:, None] * _SLOT + np.arange(per)).ravel())]
    for c in counters[counters < sizes["heavy"]]:
        ids.append(np.full(heavy_n, c, np.int64))
        keys.append(D.keys(seed, D.POPULATED,
                           (sizes["counters"] + c) * _SLOT + np.arange(heavy_n)))
    return np.concatenate(ids).astype(np.int32), np.concatenate(keys)


def _prefill_counts(sizes) -> np.ndarray:
    n = np.full(sizes["counters"], float(sizes["prefill_per"]))
    n[: sizes["heavy"]] += sizes["heavy_n"]
    return n


def _sample(seed: int, sizes: dict, zipf) -> np.ndarray:
    """The live counters tracked register for register: two heavy ones and a
    seeded draw from those below the ``sample_skip`` most popular (whose keys
    of one run would be tens of millions to hash again)."""
    cold = zipf.items[sizes["sample_skip"]:]
    cold = cold[cold >= sizes["heavy"]]
    pick = D.rng(seed, _STREAM, 5).choice(cold, sizes["sample"] - 2, replace=False)
    return np.sort(np.concatenate([[0, sizes["heavy"] - 1], pick]))


def _zipf(sizes: dict, params: dict, seed: int):
    return D.Zipf(sizes["counters"], params["skew"], seed,
                  among=np.arange(_live(sizes)))


def cycle_ends(params: dict, ops) -> np.ndarray:
    """Which requests of one connection, given their operation counts, end a
    cycle: the read frames, the closing one too."""
    if params["pairs_per_read"] == params["pairs_per_add"]:
        raise ValueError("a read frame cannot be told from an add frame by its size")
    return np.asarray(ops) == params["pairs_per_read"]


def reference(sizes: dict, params: dict, seed: int) -> dict:
    zipf = _zipf(sizes, params, seed)
    lut = np.zeros(sizes["counters"], bool)
    lut[_sample(seed, sizes, zipf)] = True
    return {"sample_lut": lut}


def populate(client, sizes: dict, params: dict, seed: int) -> dict:
    hll = client.get_hyper_log_log_array(NAME)
    if not hll.try_init(sizes["counters"]):
        raise RuntimeError("HLLA.RESERVE refused")
    ids, keys = _prefill(seed, sizes, np.arange(sizes["counters"]))
    order = D.rng(seed, _STREAM, 6).permutation(len(ids))
    ids, keys = ids[order], keys[order]
    for lo in range(0, len(ids), sizes["populate_batch"]):
        hll.add(ids[lo:lo + sizes["populate_batch"]], keys[lo:lo + sizes["populate_batch"]])
    return {"prefilled_keys": int(len(ids))}


def after_window(client, sizes: dict, params: dict, seed: int, ref: dict, writes: dict):
    """With every connection's last read answered: the whole bank against the
    true cardinalities, the sample against exact registers, every union the
    run read against the bounds it must lie between."""
    closed0 = _live(sizes)
    prefill = _prefill_counts(sizes)
    truth = prefill.copy()
    merged = np.zeros((closed0, sizes["closed"]), bool)
    for w in writes.values():
        truth += w["counts"]
        for dst, src, _est in w["reads"]:
            merged[dst, src - closed0] = True
    truth[:closed0] += merged @ prefill[closed0:]
    hll = client.get_hyper_log_log_array(NAME)
    est = hll.estimate_all()
    failures = hll_failures(est, truth, "all counters")

    sample = np.flatnonzero(ref["sample_lut"])
    sources = closed0 + np.flatnonzero(merged[sample].any(axis=0))
    rh = RefHll(np.concatenate([sample, sources]))
    rh.add(*_prefill(seed, sizes, rh.rows))
    for w in writes.values():
        if len(w["sample_ids"]):
            rh.add(w["sample_ids"], w["sample_keys"])
    for dst in sample:
        for s in closed0 + np.flatnonzero(merged[dst]):
            rh.merge_rows([dst], [s])
    failures += hll_failures(est[sample], truth[sample], "sampled counters",
                             ref_est=rh.estimate()[: len(sample)])

    reads = 0
    for conn, w in writes.items():
        for dst, src, got in w["reads"]:
            reads += 1
            n_low, n_high = prefill[dst] + prefill[src], truth[dst] + prefill[src]
            hard = hll_hard(n_low, n_high)
            low, high = n_low * (1 - hard), n_high * (1 + hard)
            bad = ~np.isfinite(got) | (got < low) | (got > high)
            if bad.any():
                failures.append(f"conn {conn}: {int(bad.sum())} of {len(got)} "
                                "union estimates outside their bounds")
    return failures, {"checked_reads": np.array([reads]),
                      "sample_keys": np.array([sum(len(w["sample_ids"])
                                                   for w in writes.values())])}


class Stream:
    def __init__(self, ctx):
        self.ctx, self.sizes, p = ctx, ctx.sizes, ctx.params
        self.zipf = _zipf(self.sizes, p, ctx.seed)
        self.lut = np.asarray(ctx.ref("sample_lut"))
        self.counts = np.zeros(self.sizes["counters"], np.float64)
        self.sample_ids, self.sample_keys, self.reads = [], [], []

    def bind(self, client):
        self.client = client
        self.hll = client.get_hyper_log_log_array(NAME)

    def make(self, idx: int):
        p = self.ctx.params
        if idx >= 0 and idx % p["read_every"] == p["read_every"] - 1:
            return self._read(idx)
        g = D.rng(self.ctx.seed, _STREAM, self.ctx.conn, 2, idx & 0xFFFFFFFF)
        n = p["pairs_per_add"]
        ids = self.zipf.draw(g, n).astype(np.int32)
        base = (self.ctx.conn << 44) | ((idx & 0xFFFFFF) << 20)
        keys = D.keys(self.ctx.seed, D.ADDED,
                      np.uint64(base) + np.arange(n, dtype=np.uint64))
        hit = self.lut[ids]  # drawn off the timed path, with the frame
        return (KIND_ADD, ids, keys, np.bincount(ids, minlength=len(self.counts)),
                ids[hit], keys[hit])

    def _read(self, idx: int):
        g = D.rng(self.ctx.seed, _STREAM, self.ctx.conn, 3, idx & 0xFFFFFFFF)
        n = self.ctx.params["pairs_per_read"]
        dst = self.zipf.draw_distinct(g, n).astype(np.int32)
        src = (_live(self.sizes) + g.permutation(self.sizes["closed"])[:n]).astype(np.int32)
        return KIND_READ, dst, src

    def warmup(self):
        return [self.make(-1), self._read(-2)]

    def closing(self, idx: int):
        return self._read(idx)

    def ops(self, req) -> int:
        return len(req[1])

    def send(self, req):
        if req[0] == KIND_ADD:
            return self.hll.add(req[1], req[2])
        d = np.ascontiguousarray(req[1], "<i4").tobytes()
        s = np.ascontiguousarray(req[2], "<i4").tobytes()
        ok, est = self.client.execute_many([
            ("HLLA.MERGEROWS", NAME, d, s), ("HLLA.ESTPAIRS", NAME, d, s)])
        if ok not in (b"OK", "OK") or not isinstance(est, (bytes, bytearray)):
            raise RuntimeError(f"read frame answered {ok!r}, {type(est).__name__}")
        return np.frombuffer(est, "<f8")

    def keep(self, idx: int, req, reply):
        if req[0] == KIND_ADD:
            self.counts += req[3]
            self.sample_ids.append(req[4])
            self.sample_keys.append(req[5])
        else:
            self.reads.append((req[1], req[2], reply))

    def writes(self):
        cat = lambda xs, dt: np.concatenate(xs) if xs else np.zeros(0, dt)  # noqa: E731
        return {"counts": self.counts, "reads": self.reads,
                "sample_ids": cat(self.sample_ids, np.int32),
                "sample_keys": cat(self.sample_keys, np.int64)}

    def verify(self) -> dict:
        # the bank is read once, by the parent, when every connection is done
        return {"checked_full": 0, "checked": len(self.reads), "failures": []}

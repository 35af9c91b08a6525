"""Traffic against one exact (FLAT) vector index, served the way
ANN-Benchmarks' RediSearch module serves a dataset: a HASH a vector under a
key prefix, ``FT.CREATE … VECTOR FLAT … TYPE FLOAT32 DIM d DISTANCE_METRIC
L2``, and ``FT.SEARCH idx "*=>[KNN k @vector $BLOB]" NOCONTENT SORTBY
__vector_score LIMIT 0 k PARAMS 2 BLOB <d*4 bytes> DIALECT 2``, through the
raw-command surface of ``RemoteRedisson`` (``execute_many``: one pipelined
frame, one ``sendall``).

A request is one frame of ``searches_per_frame`` such commands, one query
vector each (the suite's batch mode on the wire); each connection walks its
own seeded permutation of the configuration's queries, around again when it
runs out.  The window is read-only, as the source is.  EVERY reply is
checked against the exact reference in the worker that got it
(``benchmark/reference_ann.py reply_failures``).

Set-up fails fast (``BenchFailure``) where the program does not index at the
write: after the first populate frame the server's
``rtpu_search_docs_indexed_total`` must have grown by that frame's
documents, and after the last ``FT.INFO`` must count every document with
``rtpu_search_scan_keys_total`` where FT.CREATE left it.  A tree without
that walks the keyspace at every search; it ends here in seconds.

After the window (outside it, so the window stays read-only) the write
guarantee is held: ``after_copies`` new documents that are copies of
queries, the reference's nearest neighbour of as many other queries deleted,
then those queries searched on the same connection against a reference made
again over the changed base.

Traffic parameters: searches_per_frame (64), k (10), dim (128) — the
request's shape; they must be the configuration's.
"""
import sys

import numpy as np

from benchmark import datagen as D
from benchmark import reference_ann as R

INDEX, PREFIX, FIELD = "idx", "doc:", "vector"
SCORE = b"__vector_score"
_STREAM = 0xA22F
_CACHE = {}  # the parent makes the base once for reference() and populate()


def _fail(msg: str):
    """run.py's BenchFailure (non-zero exit, no result line) where this runs
    under run.py; a RuntimeError anywhere else."""
    return getattr(sys.modules.get("__main__"), "BenchFailure", RuntimeError)(msg)


def _points(seed: int, sizes: dict, stream: int, count: int) -> np.ndarray:
    key = (seed, stream, count, sizes["dim"], sizes["centres"], sizes["spread"])
    if key not in _CACHE:
        _CACHE[key] = R.make_points(seed, stream, 0, count, sizes["dim"],
                                    sizes["centres"], sizes["spread"])
    return _CACHE[key]


def _blobs(points: np.ndarray) -> list:
    """One little-endian float32 blob a row."""
    width = points.shape[1] * 4
    buf = points.astype("<f4").tobytes()
    return [buf[i:i + width] for i in range(0, len(buf), width)]


def search_command(sizes: dict, blob: bytes) -> tuple:
    k = sizes["k"]
    return ("FT.SEARCH", INDEX, f"*=>[KNN {k} @{FIELD} $BLOB]", "NOCONTENT",
            "SORTBY", SCORE.decode(), "LIMIT", 0, k, "PARAMS", 2, "BLOB", blob,
            "DIALECT", 2)


def decode_reply(reply, k: int):
    """One FT.SEARCH NOCONTENT reply -> (ids int64 (k,), distances float64
    (k,)), -1 where it holds fewer than k."""
    if not isinstance(reply, list) or not reply or isinstance(reply[0], Exception):
        raise RuntimeError(f"FT.SEARCH answered {reply!r}"[:200])
    ids, dist = np.full(k, -1, np.int64), np.full(k, -1.0)
    for j in range(min(k, (len(reply) - 1) // 2)):
        ids[j] = int(bytes(reply[1 + 2 * j])[len(PREFIX):])
        dist[j] = float(reply[2 + 2 * j][1])
    return ids, dist


def search_frame(client, sizes: dict, commands) -> tuple:
    """One pipelined frame of searches -> (ids (Q, k), distances (Q, k))."""
    got = [decode_reply(r, sizes["k"]) for r in client.execute_many(commands)]
    return np.stack([g[0] for g in got]), np.stack([g[1] for g in got])


def _metric(client, name: str):
    for line in bytes(client.execute("METRICS")).decode().splitlines():
        if line.startswith(name + " "):
            return float(line.rpartition(" ")[2])
    return None


def _num_docs(client) -> int:
    info = client.execute("FT.INFO", INDEX)
    return int(info[info.index(b"num_docs") + 1])


def reference(sizes: dict, params: dict, seed: int) -> dict:
    if (params["k"], params["dim"]) != (sizes["k"], sizes["dim"]):
        raise _fail("the traffic's k and dim are not the configuration's")
    base = _points(seed, sizes, 0, sizes["n"])
    queries = _points(seed, sizes, 1, sizes["queries"])
    # one more than k: what a search must return once its nearest is deleted
    ids, dists = R.topk(base, queries, sizes["k"] + 1)
    return {"base": base, "queries": queries, "ref_ids": ids, "ref_dist": dists}


def populate(client, sizes: dict, params: dict, seed: int) -> dict:
    n, batch = sizes["n"], sizes["populate_batch"]
    base = _points(seed, sizes, 0, n)
    made = client.execute(
        "FT.CREATE", INDEX, "ON", "HASH", "PREFIX", 1, PREFIX, "SCHEMA", FIELD,
        "VECTOR", "FLAT", 6, "TYPE", "FLOAT32", "DIM", sizes["dim"],
        "DISTANCE_METRIC", "L2")
    if made not in (b"OK", "OK"):
        raise _fail(f"FT.CREATE answered {made!r}")
    indexed0 = _metric(client, "rtpu_search_docs_indexed_total")
    scanned0 = _metric(client, "rtpu_search_scan_keys_total")
    for lo in range(0, n, batch):
        hi = min(n, lo + batch)
        added = client.execute_many([
            ("HSET", f"{PREFIX}{lo + j}", FIELD, blob)
            for j, blob in enumerate(_blobs(base[lo:hi]))])
        if added != [1] * (hi - lo):
            raise _fail(f"HSET of documents {lo}..{hi} answered {added[:4]!r}…")
        if lo == 0:  # the mechanism the cell exists for, before any FT.* query
            indexed = _metric(client, "rtpu_search_docs_indexed_total")
            if indexed0 is None or indexed is None or indexed - indexed0 != hi:
                raise _fail(
                    f"after the first {hi} HSETs rtpu_search_docs_indexed_total went "
                    f"{indexed0} -> {indexed}: this program does not index at the "
                    "write, and would walk the keyspace at every search")
    docs, scanned = _num_docs(client), _metric(client, "rtpu_search_scan_keys_total")
    if docs != n or scanned != scanned0:
        raise _fail(f"after populate FT.INFO counts {docs} documents of {n}, and "
                    f"rtpu_search_scan_keys_total went {scanned0} -> {scanned}")
    return {"populated_docs": n, "populate_frames": -(-n // batch)}


def after_window(client, sizes: dict, params: dict, seed: int, ref: dict, writes: dict):
    """The write guarantee, outside the timed window: copies of queries come
    back at distance 0, deleted nearest neighbours do not come back, on the
    connection that wrote, against the reference over the changed base."""
    n, k, m = sizes["n"], sizes["k"], sizes["after_copies"]
    pick = D.rng(seed, _STREAM, 7).permutation(sizes["queries"])[:2 * m]
    copied, bereft = pick[:m], pick[m:]
    queries = ref["queries"]
    dead = np.unique(ref["ref_ids"][bereft, 0])
    added = client.execute_many(
        [("HSET", f"{PREFIX}{n + j}", FIELD, blob)
         for j, blob in enumerate(_blobs(queries[copied]))]
        + [("DEL", f"{PREFIX}{i}") for i in dead])
    failures = []
    if added != [1] * (m + len(dead)):
        failures.append(f"after-window writes answered {added[:4]!r}…")
    base = np.concatenate([ref["base"], queries[copied]])
    _ids, want = R.topk(base, queries[pick], k, dead=dead)
    frame = params["searches_per_frame"]
    commands = [search_command(sizes, b) for b in _blobs(queries[pick])]
    got = [search_frame(client, sizes, commands[a:a + frame])
           for a in range(0, len(commands), frame)]
    ids, dist = np.concatenate([g[0] for g in got]), np.concatenate([g[1] for g in got])
    bad = R.reply_failures(base, queries[pick], want, ids, dist, dead=dead)
    back = (ids[:m] == (n + np.arange(m))[:, None]).any(axis=1) & (dist[:m, 0] == 0)
    if bad.any() or not back.all():
        failures.append(f"after the window's writes: {int(bad.sum())} of {len(pick)} "
                        f"searches wrong, {int((~back).sum())} of {m} copies not found "
                        "at distance 0")
    scanned = _metric(client, "rtpu_search_scan_keys_total")
    return failures, {"after_window_searches": np.array([len(pick)]),
                      "scan_keys_total": np.array([-1.0 if scanned is None else scanned])}


class Stream:
    def __init__(self, ctx):
        self.ctx, self.sizes = ctx, ctx.sizes
        self.frame = ctx.params["searches_per_frame"]
        self.base = ctx.ref("base")
        self.queries = np.asarray(ctx.ref("queries"))
        self.ref_dist = np.asarray(ctx.ref("ref_dist"))[:, : self.sizes["k"]]
        self.order = D.rng(ctx.seed, _STREAM, ctx.conn).permutation(len(self.queries))
        self.commands = [search_command(self.sizes, b) for b in _blobs(self.queries)]
        self.checked, self.wrong, self.first_wrong = 0, 0, None

    def bind(self, client):
        self.client = client

    def make(self, idx: int):
        """Frame ``idx`` of this connection: the next ``searches_per_frame``
        queries of its permutation (a pure function of seed, connection, idx)."""
        at = (idx * self.frame + np.arange(self.frame)) % len(self.order)
        return self.order[at]

    def warmup(self):
        return [self.make(-1)]

    def closing(self, idx: int):
        return None

    def ops(self, req) -> int:
        return len(req)

    def send(self, req):
        return search_frame(self.client, self.sizes, [self.commands[q] for q in req])

    def keep(self, idx: int, req, reply):
        ids, dist = reply
        bad = R.reply_failures(self.base, self.queries[req], self.ref_dist[req], ids, dist)
        self.checked += len(req)
        if bad.any():
            self.wrong += int(bad.sum())
            if self.first_wrong is None:
                q = int(req[np.flatnonzero(bad)[0]])
                self.first_wrong = (f"conn {self.ctx.conn} frame {idx} query {q}: got "
                                    f"{ids[bad][0].tolist()} {dist[bad][0].tolist()}, the "
                                    f"reference's distances {self.ref_dist[q].tolist()}")

    def writes(self):
        return {}

    def verify(self) -> dict:
        failures = []
        if self.wrong:
            failures.append(f"{self.wrong} of {self.checked} searches wrong; {self.first_wrong}")
        return {"checked_full": self.checked, "checked": self.checked, "failures": failures}

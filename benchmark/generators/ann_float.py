"""Traffic against one exact (FLAT) vector index of float embeddings, served
as ANN-Benchmarks' RediSearch module serves ``gist-960-euclidean``: the
wire, the index and the request are ``ann_flat``'s (``FT.CREATE … VECTOR
FLAT … TYPE FLOAT32 DIM d DISTANCE_METRIC L2``, frames of
``searches_per_frame`` pipelined ``FT.SEARCH … KNN k … DIALECT 2``, each
connection walking its own seeded permutation of the queries); the data and
the check are float's (``benchmark/reference_ann_float.py``).

EVERY reply is checked in the worker that got it, against per-query tables
the parent made (admissible ids, their float64 distances, the bound), never
against the base: its k ids distinct, live and admissible, each printed
score a float32 within EPS of that id's distance, the scores ascending.

Set-up fails fast (``BenchFailure``) where the program does not index at the
write (``ann_flat``'s two checks, the same counters) or does not answer a
search with float32 distances in order: after the first populate frame,
``probe_queries`` searches over the documents written so far are checked
against tables of those documents, before the rest of the base is sent.

After the window (outside it, so the window stays read-only) the write
guarantee is held as ``ann_flat`` holds it: ``after_copies`` copies of
queries come back at distance exactly 0, the deleted nearest neighbours of
as many other queries do not come back, on the connection that wrote.

Traffic parameters: searches_per_frame (64), k (10), dim (960) — the
request's shape; they must be the configuration's.
"""
import numpy as np

from benchmark import datagen as D
from benchmark import reference_ann_float as F
from benchmark.generators.ann_flat import (FIELD, INDEX, PREFIX, _blobs, _fail, _metric,
                                           _num_docs, search_command, search_frame)

_STREAM = 0xA230
_CACHE = {}  # the parent makes the base once for reference(), populate(), after_window()


def _points(seed: int, sizes: dict, stream: int, count: int) -> np.ndarray:
    key = (seed, stream, count, sizes["dim"], sizes["centres"], sizes["centre_mean"],
           tuple(sizes["spread"]))
    if key not in _CACHE:
        _CACHE[key] = F.make_points(seed, stream, 0, count, sizes["dim"], sizes["centres"],
                                    sizes["centre_mean"], tuple(sizes["spread"]))
    return _CACHE[key]


def _search(client, sizes: dict, queries: np.ndarray, frame: int):
    """``queries`` searched in frames of ``frame`` -> (ids (Q, k), the
    printed scores as the float32 values they name (Q, k))."""
    commands = [search_command(sizes, b) for b in _blobs(queries)]
    got = [search_frame(client, sizes, commands[a:a + frame])
           for a in range(0, len(commands), frame)]
    ids = np.concatenate([g[0] for g in got])
    scores = np.concatenate([g[1] for g in got]).astype(np.float32).astype(np.float64)
    return ids, scores


def reference(sizes: dict, params: dict, seed: int) -> dict:
    if (params["k"], params["dim"]) != (sizes["k"], sizes["dim"]):
        raise _fail("the traffic's k and dim are not the configuration's")
    base = _points(seed, sizes, 0, sizes["n"])
    queries = _points(seed, sizes, 1, sizes["queries"])
    table = F.tables([(base, 0, None)], queries, sizes["k"])
    return {"queries": queries, "ids": table["ids"], "dist": table["dist"],
            "bound": table["bound"]}


def _probe(client, sizes: dict, base: np.ndarray, queries: np.ndarray, hi: int) -> None:
    """Searches over the first ``hi`` documents, checked against tables of
    those documents: a program whose replies do not carry float32
    distances in order ends here, before the rest of the base is sent."""
    probe = queries[:sizes["probe_queries"]]
    table = F.tables([(base[:hi], 0, None)], probe, sizes["k"])
    ids, scores = _search(client, sizes, probe, 1)
    bad = F.reply_failures(table, ids, scores, sizes["dim"])
    if bad.any():
        q = int(np.flatnonzero(bad)[0])
        raise _fail(
            f"over the first {hi} documents {int(bad.sum())} of {len(probe)} searches do "
            f"not answer float32 distances in order: got {ids[q].tolist()} "
            f"{scores[q].tolist()}, the admissible {table['ids'][q, :sizes['k']].tolist()} "
            f"at {table['dist'][q, :sizes['k']].tolist()}")


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
        if lo == 0:  # the mechanisms the cell exists for, before the rest is sent
            indexed = _metric(client, "rtpu_search_docs_indexed_total")
            if indexed0 is None or indexed is None or indexed - indexed0 != hi:
                raise _fail(
                    f"after the first {hi} HSETs rtpu_search_docs_indexed_total went "
                    f"{indexed0} -> {indexed}: this program does not index at the "
                    "write, and would walk the keyspace at every search")
            _probe(client, sizes, base, _points(seed, sizes, 1, sizes["queries"]), hi)
    docs, scanned = _num_docs(client), _metric(client, "rtpu_search_scan_keys_total")
    if docs != n or scanned != scanned0:
        raise _fail(f"after populate FT.INFO counts {docs} documents of {n}, and "
                    f"rtpu_search_scan_keys_total went {scanned0} -> {scanned}")
    return {"populated_docs": n, "populate_frames": -(-n // batch)}


def after_window(client, sizes: dict, params: dict, seed: int, ref: dict, writes: dict):
    """The write guarantee, outside the timed window: copies of queries come
    back at distance 0, deleted nearest neighbours do not come back, on the
    connection that wrote, against tables over the changed base."""
    n, m = sizes["n"], sizes["after_copies"]
    pick = D.rng(seed, _STREAM, 7).permutation(sizes["queries"])[:2 * m]
    copied, bereft = pick[:m], pick[m:]
    queries = ref["queries"]
    dead = np.unique(ref["ids"][bereft, 0])
    added = client.execute_many(
        [("HSET", f"{PREFIX}{n + j}", FIELD, blob)
         for j, blob in enumerate(_blobs(queries[copied]))]
        + [("DEL", f"{PREFIX}{i}") for i in dead])
    failures = []
    if added != [1] * (m + len(dead)):
        failures.append(f"after-window writes answered {added[:4]!r}…")
    base = _points(seed, sizes, 0, n)
    table = F.tables([(base, 0, dead), (queries[copied], n, None)], queries[pick],
                     sizes["k"])
    ids, scores = _search(client, sizes, queries[pick], params["searches_per_frame"])
    failures += written_failures(table, ids, scores, n, m, sizes["dim"], dead)
    score_dev, rank_dev = F.deviations(table, ids, scores, sizes["dim"])
    scanned = _metric(client, "rtpu_search_scan_keys_total")
    return failures, {"after_window_searches": np.array([len(pick)]),
                      "after_window_score_dev_eps": np.array([score_dev]),
                      "after_window_rank_dev_eps": np.array([rank_dev]),
                      "scan_keys_total": np.array([-1.0 if scanned is None else scanned])}


def written_failures(table: dict, ids: np.ndarray, scores: np.ndarray, n: int, m: int,
                     dim: int, dead) -> list:
    """The after-window searches judged: the first ``m`` are of the queries
    copied into documents ``n``.., which must come back at exactly 0; none
    may return a deleted document; every reply as any other."""
    bad = F.reply_failures(table, ids, scores, dim, dead=dead)
    back = (ids[:m] == (n + np.arange(m))[:, None]).any(axis=1) & (scores[:m, 0] == 0)
    if bad.any() or not back.all():
        return [f"after the window's writes: {int(bad.sum())} of {len(ids)} searches "
                f"wrong, {int((~back).sum())} of {m} copies not found at distance 0"]
    return []


class Stream:
    def __init__(self, ctx):
        self.ctx, self.sizes = ctx, ctx.sizes
        self.frame = ctx.params["searches_per_frame"]
        self.queries = np.asarray(ctx.ref("queries"))
        self.k = self.sizes["k"]
        self.table = {name: np.asarray(ctx.ref(name)) for name in ("ids", "dist", "bound")}
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
        ids, dist = search_frame(self.client, self.sizes, [self.commands[q] for q in req])
        return ids, dist.astype(np.float32).astype(np.float64)

    def keep(self, idx: int, req, reply):
        ids, scores = reply
        rows = {name: arr[req] for name, arr in self.table.items()}
        bad = F.reply_failures(rows, ids, scores, self.sizes["dim"])
        self.checked += len(req)
        if bad.any():
            self.wrong += int(bad.sum())
            if self.first_wrong is None:
                j = int(np.flatnonzero(bad)[0])
                q = int(req[j])
                self.first_wrong = (
                    f"conn {self.ctx.conn} frame {idx} query {q}: got {ids[j].tolist()} "
                    f"{scores[j].tolist()}, the admissible "
                    f"{self.table['ids'][q, :self.k].tolist()} at "
                    f"{self.table['dist'][q, :self.k].tolist()}")

    def writes(self):
        return {}

    def verify(self) -> dict:
        failures = []
        if self.wrong:
            failures.append(f"{self.wrong} of {self.checked} searches wrong; {self.first_wrong}")
        return {"checked_full": self.checked, "checked": self.checked, "failures": failures}

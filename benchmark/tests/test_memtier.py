"""The cell ``bf-200c`` (``memtier-bf-10m`` x ``memtier-200c-p1``): the plain
reference against the program's hashing of byte items, the check's
tightness (a reference that drops a cell or truncates an item fails it), the
generator's streams as pure functions of the seed, the rehearsal, and the
manifest taking the cell by additions."""
import json
import os

import numpy as np
import pytest

from benchmark import loadgen, roofline_bf
from benchmark import reference_bf as R
from benchmark.generators import memtier_bf as G
from benchmark.tests.test_rehearse import ROOT, cells, rehearse

CELL = "bf-200c"
# a filter at its design load, so that false positives are common enough
# for a wrong reference to meet some: 2,000 of 4,000 keys populated in a
# filter reserved for 2,000
SIZES = {"capacity": 2000, "error_rate": 0.01, "m_bits": 19170, "k": 7,
         "key_prefix": "memtier-", "key_max": 4000, "populate_batch": 512,
         "populate_pipeline": 2, "sweep_keys": 4000, "sweep_chunk": 1024}
PARAMS = {"connections": 4, "exists_per_add": 10, "reprobe_after": 2,
          "key_prefix": "memtier-", "key_max": 4000, "k": 7}


# -- the reference against the program ---------------------------------------------


def _strings(seed: int, lengths) -> list:
    g = np.random.default_rng(seed)
    return [bytes(g.integers(0, 256, n, dtype=np.uint8)) for n in lengths]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_hashes_byte_items_as_the_program_does(seed):
    from redisson_tpu.utils import hashing as H

    items = _strings(seed, [n for n in range(1, 65) for _ in range(8)])
    h1, h2 = R.hash_pair(*R.pack(items))
    g1, g2 = H.hash_packed_bytes(*H.pack_keys(items))
    assert (h1 == g1).all() and (h2 == g2).all() and (h2 & 1).all()
    for m in (19170, 95850583):
        want = H.bloom_indexes(g1, g2, 7, m)
        assert (R.indexes(*R.pack(items), 7, m) == want).all()


def test_reference_agrees_with_the_device_kernels():
    import jax.numpy as jnp

    from redisson_tpu.core import kernels as K
    from redisson_tpu.utils import hashing as H

    m, k = 19170, 7
    items = [b"memtier-%d" % n for n in range(1, 401)]
    ref = R.RefFilter(m, k)
    bits = jnp.zeros((20480,), jnp.uint8)
    words, nbytes = H.pack_keys(items[:256])
    words = np.pad(words, ((0, 4 - words.shape[0]), (0, 0)))
    bits, newly = K.bloom_add_bytes_masked(bits, words, nbytes, np.int32(200), k, m)
    want = [ref.add(*map(lambda a: a[0], R.pack([b]))) for b in items[:200]]
    # the kernel judges a batch against the plane before it: equal while no
    # item's cells were all set by earlier items of the batch
    assert np.asarray(newly)[:200].tolist() == want and not np.asarray(newly)[200:].any()
    found = K.bloom_contains_bytes_masked(bits, words, nbytes, np.int32(256), k, m)
    assert (np.asarray(found) == ref.contains(*R.pack(items[:256]))).all()
    assert (np.flatnonzero(np.asarray(bits)) == np.flatnonzero(ref.cells)).all()


def test_numbered_items_are_the_formatted_strings():
    n = np.array([1, 9, 10, 99, 100, 4711, 999999, 1000000, 9999999, 10000000])
    rows, nbytes = R.numbered(b"memtier-", n)
    want_rows, want_nbytes = R.pack([b"memtier-%d" % v for v in n])
    assert (rows == want_rows).all() and (nbytes == want_nbytes).all()
    assert nbytes.min() == 9 and nbytes.max() == 16


def test_geometry_is_redissons():
    m = R.optimal_m(10_000_000, 0.01)
    assert (m, R.optimal_k(10_000_000, m)) == (95850583, 7)
    with open(os.path.join(ROOT, "benchmark", "configs", "memtier-bf-10m.json")) as fh:
        config = json.load(fh)
    assert (config["sizes"]["m_bits"], config["sizes"]["k"]) == (m, 7)
    small = dict(config["sizes"], **config["rehearse"])
    assert small["m_bits"] == R.optimal_m(small["capacity"], small["error_rate"])


# -- the check, against a server that is the true reference ---------------------------

_TRUE_INDEXES = R.indexes


class TrueServer:
    """Answers BF.* as the frozen arithmetic says, whatever the module's
    ``indexes`` has been patched to: the client of a correct server."""

    def __init__(self):
        self.cells, self.m, self.k = None, None, None

    def _at(self, items):
        return _TRUE_INDEXES(*R.pack([bytes(b) for b in items]), self.k, self.m)

    def execute(self, verb, name, *args):
        if verb == "BF.RESERVE":
            self.m = R.optimal_m(int(args[1]), float(args[0]))
            self.k = R.optimal_k(int(args[1]), self.m)
            self.cells, self.capacity = np.zeros(self.m, bool), int(args[1])
            return b"OK"
        if verb == "BF.INFO":
            return [b"Capacity", self.capacity, b"Size", self.m, b"Number of hashes", self.k]
        at = self._at(args)
        had = self.cells[at].all(axis=1)
        if verb in ("BF.ADD", "BF.MADD"):
            self.cells[at.ravel()] = True
            return int(not had[0]) if verb == "BF.ADD" else [int(not h) for h in had]
        return int(had[0]) if verb == "BF.EXISTS" else [int(h) for h in had]

    def execute_many(self, commands):
        return [self.execute(*c) for c in commands]

    def info(self):
        return "connected_clients:5\r\n"

    def ping(self):
        return True


def drive(tmp_path, seed: int, per_conn: int = 44) -> list:
    """The generator's whole life against a TrueServer; what failed, as text."""
    server = TrueServer()
    try:
        ref = G.reference(SIZES, PARAMS, seed)
        np.save(tmp_path / "plane.npy", ref["plane"])
        G.populate(server, SIZES, PARAMS, seed)
    except RuntimeError as e:
        return [f"set-up: {e}"]
    streams = [G.Stream(loadgen.StreamContext(SIZES, PARAMS, seed, c, PARAMS["connections"],
                                              str(tmp_path)))
               for c in range(PARAMS["connections"])]
    for s in streams:
        s.bind(server)
    for i in range(-2, per_conn):  # the connections take turns: one in flight each
        for s in streams:
            req = s.make(i)
            s.keep(i, req, s.send(req))
    failures, extra = G.after_window(server, SIZES, PARAMS, seed, ref,
                                     {s.ctx.conn: s.writes() for s in streams})
    for name, arr in extra.items():
        np.save(tmp_path / (name + ".npy"), arr)
    return failures + [f for s in streams for f in s.verify()["failures"]]


@pytest.mark.parametrize("seed", [11, 3500000000])
def test_a_correct_server_passes(tmp_path, seed):
    assert drive(tmp_path, seed) == []


def drop_a_cell(rows, nbytes, k, m):
    at = _TRUE_INDEXES(rows, nbytes, k, m)
    at[:, -1] = at[:, 0]  # k - 1 distinct cells an item
    return at


def truncate_the_item(rows, nbytes, k, m):
    rows = np.array(rows)
    last = np.asarray(nbytes, np.int64) - 1
    rows[np.arange(len(rows)), last] = 0  # the item without its last byte
    return _TRUE_INDEXES(rows, last.astype(np.uint32), k, m)


@pytest.mark.parametrize("broken", [drop_a_cell, truncate_the_item])
@pytest.mark.parametrize("seed", [11, 12])
def test_a_wrong_reference_fails_the_check(tmp_path, monkeypatch, broken, seed):
    monkeypatch.setattr(R, "indexes", broken)
    assert drive(tmp_path, seed) != []


def test_a_lost_add_and_a_false_negative_fail_the_check(tmp_path, monkeypatch):
    real = TrueServer.execute

    def forgetful(self, verb, name, *args):  # acknowledges every add, applies none
        if verb == "BF.ADD":
            return int(not self.cells[self._at(args)].all())
        return real(self, verb, name, *args)

    monkeypatch.setattr(TrueServer, "execute", forgetful)
    failures = drive(tmp_path, 11)
    assert any("false negatives" in f for f in failures)
    assert any("BF.MEXISTS answers differ" in f for f in failures)


# -- the streams -----------------------------------------------------------------------


def test_streams_are_pure_functions_of_the_seed():
    def stream(seed, conn):
        keys = G.Keys(SIZES, PARAMS, seed, conn)
        return [(keys.kind(i), keys.key(i)) for i in range(-2, 5000)]

    assert stream(7, 1) == stream(7, 1)
    assert stream(7, 1) != stream(8, 1) and stream(7, 1) != stream(7, 2)
    keys = G.Keys(SIZES, PARAMS, 7, 3)
    assert keys.key(4500) == G.Keys(SIZES, PARAMS, 7, 3).key(4500)  # out of order too
    kinds = np.array([keys.kind(i) for i in range(1100)])
    assert (kinds == G.KIND_ADD).sum() == 100  # exactly 1 : 10
    adds = [keys.key(i) for i in np.flatnonzero(kinds == G.KIND_ADD)]
    lo = 2 * 3 * keys.slice + 1
    assert len(set(adds)) == 100 and all(a % 2 == 1 and lo <= a < lo + 2 * keys.slice
                                         for a in adds)  # its own slice of the odd half
    for i in np.flatnonzero(kinds == G.KIND_ADD)[:-1]:
        assert keys.kind(i + 2) == G.KIND_REPROBE and keys.key(i + 2) == keys.key(i)
    plain = [keys.key(i) for i in np.flatnonzero(kinds == G.KIND_EXISTS)]
    assert (keys.probed(1100) == plain).all()
    assert 1 <= min(plain) and max(plain) <= SIZES["key_max"]
    assert 0.4 < np.mean(np.array(plain) % 2 == 0) < 0.6  # half present, half absent


def test_the_traffic_file_is_what_the_issue_names():
    with open(os.path.join(ROOT, "benchmark", "traffic", "memtier-200c-p1.json")) as fh:
        mix = json.load(fh)
    assert (mix["loop"], mix["connections"], mix["processes"]) == ("closed", 200, 8)
    assert (mix["exists_per_add"], mix["reprobe_after"], mix["key_max"]) == (10, 2, 10_000_000)
    assert "latency_over" not in mix  # judged over requests: one command a sample
    assert roofline_bf.mean_item_bytes("memtier-", 9) == 9.0
    assert 14.8 < roofline_bf.mean_item_bytes(mix["key_prefix"], mix["key_max"]) < 14.9
    assert roofline_bf.bloom_point_bytes(10, 1, 7, 15.0) == 10 * 7 + 14 + 11 * 16.0


# -- the cell ---------------------------------------------------------------------------


def test_the_manifest_took_the_cell_by_additions():
    m = cells()
    names = [w["name"] for w in m["workloads"]]
    cell = m["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "memtier-bf-10m", "memtier-200c-p1", 1)
    assert names.index(CELL) > names.index("ann-batch")  # appended after what was there
    assert [c["name"] for c in m["configs"]].index("memtier-bf-10m") == 4
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert CELL in e2e["ops_per_s"]["workloads"] and "workloads" not in e2e["req_p50_ms"]
    assert CELL not in e2e["req_p95_ms"]["workloads"]
    new = [x for x in m["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in new] == ["point.cmds_per_dispatch", "point.wait_ms",
                                        "point.padded_row_share", "bloom_point_roofline"]
    assert all(x["moves"] == "ops_per_s" for x in new)
    at = [x["name"] for x in m["per_layer"]]
    assert min(at.index(x["name"]) for x in new) > at.index("knn.cmds_per_dispatch")
    # an accepted metric with no list, whose reader finds nothing in this cell,
    # was given the list of the accepted cells (the builder's contract)
    readback = next(x for x in m["per_layer"] if x["name"] == "ioplane.readback_ms")
    assert readback["workloads"] == ["bank-bulk", "bank-point", "hll-stream", "fanout-4",
                                     "ann-batch"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_with_every_reply_checked(trace):
    last, detail = rehearse(ROOT, CELL, trace, seconds="3")
    assert detail["failures"] == [] and last["failed"] == 0 and last["attempted"] > 0
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    client = detail["client"]
    assert client["checked"] == client["checked_in_full"] >= last["attempted"]
    assert client["sweep_keys"] == [4000] and client["sweep_wrong"] == [0]
    assert client["connected_clients"][0] >= 8
    assert detail["setup"]["populated_items"] == 10000
    assert detail["setup"]["bf_info"] == {"m": 191701, "k": 7, "capacity": 20000}
    if trace:
        got = last["metrics"]
        assert got["point.cmds_per_dispatch"]["value"] == 1.0
        assert got["point.padded_row_share"]["value"] == 100.0 * 255 / 256
        assert got["point.wait_ms"]["value"] > 0 and got["bloom_point_roofline"]["value"] > 0
        assert {"device.idle_share", "executor.hop_ms", "dispatch.self_ms"} <= set(got)
        assert "ioplane.readback_ms" not in got
    else:
        assert set(last["metrics"]) == {"ops_per_s", "req_p50_ms", "setup_s"}

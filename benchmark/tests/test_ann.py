"""The exact-KNN configuration's yardstick: the NumPy reference against a
brute force and against the program at the rehearsal size, what tells the
stated precision from the one below it, the generator's frames, the readers
of the cell's per-layer metrics, the roofline's two functions, and the
cell's rehearsal."""
import json
import os

import numpy as np
import pytest

from benchmark import reference_ann as R
from benchmark import roofline
from benchmark.generators import ann_flat as G
from benchmark.loadgen import StreamContext
from benchmark.tests.test_rehearse import ROOT, cells, rehearse
from benchmark.tests.test_spans import Obs, reader

CELL = "ann-batch"


def sizes_and_params():
    with open(os.path.join(ROOT, "benchmark", "configs", "ann-sift-1m.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "traffic", "knn-batch-64.json")) as fh:
        params = json.load(fh)
    return {**config["sizes"], **config["rehearse"]}, params, config


@pytest.fixture(scope="module")
def data():
    sizes, _params, _config = sizes_and_params()
    base = R.make_points(7, 0, 0, sizes["n"], sizes["dim"], sizes["centres"], sizes["spread"])
    queries = R.make_points(7, 1, 0, sizes["queries"], sizes["dim"], sizes["centres"],
                            sizes["spread"])
    return sizes, base, queries


def test_the_configuration_states_the_sources_shapes():
    sizes, params, config = sizes_and_params()
    full = config["sizes"]
    assert (full["n"], full["dim"], full["dtype"], full["metric"], full["k"],
            full["queries"], full["algo"]) == (1_000_000, 128, "FLOAT32", "L2", 10,
                                               10_000, "FLAT")
    assert config["reduced"] == [] and config["server_flags"] == [] and config["chips"] == 1
    assert (config["rehearse"]["n"], config["rehearse"]["queries"]) == (4096, 256)
    assert (params["searches_per_frame"], params["connections"], params["k"],
            params["dim"]) == (64, 4, full["k"], full["dim"])
    assert len(config["guarantees"]) == 3 and "data" in config["assumed"]


def test_points_are_seeded_integers_in_blocks():
    a = R.make_points(5, 0, 0, 70_000, 16, 8, 24.0)
    assert a.dtype == np.uint8 and a.shape == (70_000, 16)
    # any range is the same points: a pure function of (seed, stream, position)
    assert np.array_equal(R.make_points(5, 0, 65_000, 66_000, 16, 8, 24.0), a[65_000:66_000])
    assert not np.array_equal(R.make_points(6, 0, 0, 100, 16, 8, 24.0), a[:100])
    assert not np.array_equal(R.make_points(5, 1, 0, 100, 16, 8, 24.0), a[:100])


def test_reference_is_the_brute_force_ties_to_the_lower_rowid(data):
    _sizes, base, queries = data
    base = base.copy()
    base[1::2] = base[0::2]  # every point twice
    ids, dists = R.topk(base, queries[:40], 11, block=512, chunk=16)
    for j in range(40):
        d = R.sq_l2(np.repeat(queries[j:j + 1], len(base), 0), base)
        order = np.lexsort((np.arange(len(base)), d))[:11]
        assert np.array_equal(ids[j], order) and np.array_equal(dists[j], d[order])
    dead = np.unique(ids[:, 0])
    ids2, _d2 = R.topk(base, queries[:40], 10, dead=dead)
    assert not np.isin(ids2, dead).any()


def test_the_precision_below_the_stated_one_is_not_correct(data):
    """Exact float32 L2 is what the configuration states.  Ranked by
    distances rounded to bfloat16 — the nearest precision below — most
    queries come out wrong; ranked exactly, none does.  (The matmul's own
    operand precision cannot be told apart on this data: integers 0-255 are
    exact in bfloat16, so a one-pass product of them is exact too — PERF.md
    section 6, PR 32.)"""
    _sizes, base, queries = data
    _ids, want = R.topk(base, queries, 10)
    ids, dists = R.topk(base, queries, 10)
    assert not R.reply_failures(base, queries, want, ids, dists).any()
    low_ids, low_d = R.topk(base, queries, 10, lower=True)
    assert R.reply_failures(base, queries, want, low_ids, low_d).mean() > 0.3


def test_reply_failures_names_each_kind_of_wrong_reply(data):
    _sizes, base, queries = data
    ids, want = R.topk(base, queries[:8], 10)
    got_i, got_d = ids.astype(np.int64).copy(), want.astype(np.float64).copy()
    got_i[1, 9] = got_i[1, 0]                      # an id twice
    got_d[2, 3] += 1                               # a distance off by one
    got_i[3, 0] = (got_i[3, 0] + 1) % len(base)    # an id that is not at that distance
    got_i[4, 5] = -1                               # a short reply
    bad = R.reply_failures(base, queries[:8], want, got_i, got_d)
    assert bad.tolist() == [False, True, True, True, True, False, False, False]
    assert R.reply_failures(base, queries[:8], want, ids, want, dead=[ids[6, 2]])[6]


def test_the_program_answers_what_the_reference_answers(data):
    """The embedded search service over the rehearsal's data: every id and
    distance of a stacked KNN equals the reference's."""
    from redisson_tpu.core.engine import Engine
    from redisson_tpu.services.search import SearchService

    sizes, base, queries = data
    svc = SearchService(Engine())
    svc.create_index("idx", {"vector": "VECTOR"},
                     vector={"vector": {"dim": sizes["dim"], "metric": "L2"}})
    for i, row in enumerate(base):
        svc.add_document("idx", f"doc:{i}", {"vector": row.astype(np.float32)})
    _ids, want = R.topk(base, queries, sizes["k"])
    device, finish = svc.knn("idx", "vector", queries[:64].astype(np.float32), sizes["k"])
    hits = finish(tuple(np.asarray(a) for a in device))
    got_i = np.array([[int(doc[4:]) for doc, _d in h] for h in hits])
    got_d = np.array([[d for _doc, d in h] for h in hits])
    assert not R.reply_failures(base, queries[:64], want[:64], got_i, got_d).any()


def test_a_frame_is_a_pure_function_of_seed_connection_and_number(tmp_path, data):
    sizes, base, queries = data
    _s, params, _c = sizes_and_params()
    ids, dists = R.topk(base, queries, sizes["k"] + 1)
    for name, arr in (("base", base), ("queries", queries), ("ref_dist", dists)):
        np.save(tmp_path / (name + ".npy"), arr)

    def stream(seed, conn):
        return G.Stream(StreamContext(sizes, params, seed, conn, 4, str(tmp_path)))

    a, b = stream(9, 0), stream(9, 0)
    for idx in (-1, 0, 1, 5, 1000):
        assert np.array_equal(a.make(idx), b.make(idx)) and len(a.make(idx)) == 64
    assert not np.array_equal(a.make(0), stream(9, 1).make(0))
    assert not np.array_equal(a.make(0), stream(10, 0).make(0))
    # a connection walks its whole permutation before a query comes again
    seen = np.concatenate([a.make(i) for i in range(len(queries) // 64)])
    assert sorted(seen.tolist()) == list(range(len(queries)))
    cmd = a.commands[3]
    assert cmd[:4] == ("FT.SEARCH", "idx", "*=>[KNN 10 @vector $BLOB]", "NOCONTENT")
    assert cmd[12] == queries[3].astype("<f4").tobytes() and len(cmd[12]) == 512
    # a checked frame: the reference's own answer passes, a shifted one does not
    req = a.make(0)
    a.keep(0, req, (ids[req, :10].astype(np.int64), dists[req, :10].astype(np.float64)))
    assert a.verify() == {"checked_full": 64, "checked": 64, "failures": []}
    a.keep(1, req, (ids[req, 1:11].astype(np.int64), dists[req, 1:11].astype(np.float64)))
    assert a.verify()["failures"] and a.wrong == 64


def test_decode_reply():
    reply = [2, b"doc:17", [b"__vector_score", b"1234.0000"], b"doc:3",
             [b"__vector_score", b"2000.0000"]]
    ids, dist = G.decode_reply(reply, 3)
    assert ids.tolist() == [17, 3, -1] and dist.tolist() == [1234.0, 2000.0, -1.0]
    with pytest.raises(RuntimeError):
        G.decode_reply(RuntimeError("ERR"), 3)


# -- the roofline's functions, by hand ------------------------------------------


def test_roofline_functions_by_hand():
    # 64 queries over 1,000,000 x 128: 2 * 64 * 1e6 * 128 operations
    assert roofline.knn_flat_flops(64, 1_000_000, 128) == 16_384_000_000.0
    # the bank 512,000,000 B, the bias plane 4,000,000, queries 32,768, replies 5,120
    assert roofline.knn_flat_bytes(64, 1_000_000, 128, 10) == 516_037_888.0
    peaks = roofline.the_peaks()
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    # memory-bound: 516,037,888 / 819e9 s against 16.384e9 / 197e12 s
    assert roofline.seconds(16_384_000_000.0, 516_037_888.0, peaks) == pytest.approx(
        630.08e-6, rel=1e-4)
    assert roofline.seconds(197e12, 1.0, peaks) == pytest.approx(1.0)


# -- the readers, on hand-made observations --------------------------------------


def kernel_frame(members, verb="FT.SEARCH"):
    return {"spans": [{"name": "kernel", "off_us": 0, "dur_us": 10,
                       "attrs": {"verb": verb, "members": members}}]}


def observed(frames=(), before=None, after=None, programs=None, slice_ops=0.0):
    obs = Obs()
    obs.frames, obs.slice_ops = list(frames), slice_ops
    obs.metrics_before, obs.metrics_after = before or {}, after or {}
    obs.device = None if programs is None else {"programs": programs}
    obs.params = {"dim": 128, "k": 10}
    return obs


Q, SLOTS, ROWS, SCAN = ("rtpu_knn_queries_total", "rtpu_knn_query_slots_total",
                        "rtpu_knn_rows_scored_total", "rtpu_search_scan_keys_total")


def test_roofline_share_by_hand():
    # 1,280 searches in the slice, 64 a dispatch: 20 dispatches of 630.08 us
    # of roofline time = 12.6016 ms, over 100 ms of the program: 12.6 %
    obs = observed([kernel_frame(64)] * 3, {Q: 0.0, ROWS: 0.0},
                   {Q: 6400.0, ROWS: 6400.0 * 1_000_000},
                   [["jit_knn_flat_topk", 0.1], ["jit_other", 5.0]], slice_ops=1280.0)
    assert reader("knn_flat_topk_roofline")(obs) == pytest.approx(12.6016, rel=1e-4)


@pytest.mark.parametrize("change", [
    lambda o: setattr(o, "device", None),                       # an untraced run
    lambda o: setattr(o, "device", {"programs": [["jit_x", 1.0]]}),  # the program not there
    lambda o: setattr(o, "frames", []),                         # no stacked dispatch
    lambda o: setattr(o, "frames", [kernel_frame(16, "BF.MEXISTS64")]),
    lambda o: setattr(o, "metrics_after", {}),                  # a program without the counters
    lambda o: setattr(o, "slice_ops", 0.0),
])
def test_roofline_share_is_none_where_there_is_nothing_to_read(change):
    obs = observed([kernel_frame(64)], {Q: 0.0, ROWS: 0.0}, {Q: 64.0, ROWS: 64e6},
                   [["jit_knn_flat_topk", 0.1]], slice_ops=64.0)
    assert reader("knn_flat_topk_roofline")(obs) is not None
    change(obs)
    assert reader("knn_flat_topk_roofline")(obs) is None


@pytest.mark.parametrize("name,before,after,want", [
    ("search.scan_keys_per_query", {SCAN: 5.0, Q: 10.0}, {SCAN: 5.0, Q: 110.0}, 0.0),
    ("search.scan_keys_per_query", {SCAN: 0.0, Q: 0.0}, {SCAN: 3e6, Q: 3.0}, 1e6),
    ("search.scan_keys_per_query", {}, {}, None),
    ("search.scan_keys_per_query", {SCAN: 1.0, Q: 4.0}, {SCAN: 1.0, Q: 4.0}, None),
    ("knn.padded_query_share", {SLOTS: 0.0, Q: 0.0}, {SLOTS: 640.0, Q: 640.0}, 0.0),
    ("knn.padded_query_share", {SLOTS: 0.0, Q: 0.0}, {SLOTS: 64.0, Q: 40.0}, 37.5),
    ("knn.padded_query_share", {Q: 1.0}, {Q: 2.0}, None),
    ("knn.padded_query_share", {SLOTS: 8.0, Q: 8.0}, {SLOTS: 8.0, Q: 8.0}, None),
])
def test_counter_readers(name, before, after, want):
    got = reader(name)(observed(before=before, after=after))
    assert got == want if want is None else got == pytest.approx(want)


def test_cmds_per_dispatch_reads_the_search_kernels_only():
    frames = [kernel_frame(64), kernel_frame(40), kernel_frame(16, "BF.MEXISTS64")]
    assert reader("knn.cmds_per_dispatch")(observed(frames)) == 52.0
    assert reader("knn.cmds_per_dispatch")(observed(frames[2:])) is None
    assert reader("knn.cmds_per_dispatch")(observed()) is None


# -- the cell ---------------------------------------------------------------------


def test_the_cell_is_in_the_manifest_by_additions():
    m = cells()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ann-sift-1m", "knn-batch-64", 1)
    assert [w["name"] for w in m["workloads"]][-1] == CELL  # appended, at the end
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1 and len(m["workloads"]) == 5
    new = [x for x in m["per_layer"] if x.get("workloads") == [CELL]]
    assert {x["name"] for x in new} == {"knn_flat_topk_roofline", "search.scan_keys_per_query",
                                        "knn.padded_query_share", "knn.cmds_per_dispatch"}
    assert all(x["moves"] == "ops_per_s" for x in new)
    assert m["per_layer"][-len(new):] == new


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_with_every_reply_checked(trace):
    last, detail = rehearse(ROOT, CELL, trace, seconds="3")
    assert detail["failures"] == [] and last["failed"] == 0 and last["attempted"] > 0
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert detail["client"]["checked"] == detail["client"]["checked_in_full"] > 0
    assert detail["client"]["after_window_searches"] == [16]
    assert detail["setup"]["populated_docs"] == 4096
    if trace:
        got = last["metrics"]
        assert got["knn.cmds_per_dispatch"]["value"] == 64.0
        assert got["search.scan_keys_per_query"]["value"] == 0.0
        assert got["knn.padded_query_share"]["value"] == 0.0
        assert {"device.idle_share", "wire.parse_ms", "kernel.device_ms_per_mop"} <= set(got)
    else:
        assert set(last["metrics"]) == {"ops_per_s", "req_p50_ms", "setup_s"}

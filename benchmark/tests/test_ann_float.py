"""The float exact-KNN configuration's yardstick (``ann-gist-1m``, cell
``gist-batch``): the float reference's data and tables against a float64
brute force, what its check refuses (four decimals, a ranking from bfloat16
operands, an unsorted reply, a reply that misses a written document), the
generator's frames, the cell's new reader, its manifest entries and its
rehearsal."""
import json
import os

import numpy as np
import pytest

from benchmark import reference_ann_float as F
from benchmark.generators import ann_float as G
from benchmark.loadgen import StreamContext
from benchmark.tests.test_rehearse import ROOT, cells, rehearse
from benchmark.tests.test_spans import Obs, reader

CELL = "gist-batch"


def sizes_and_params():
    with open(os.path.join(ROOT, "benchmark", "configs", "ann-gist-1m.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "traffic", "knn-gist-batch-64.json")) as fh:
        params = json.load(fh)
    return {**config["sizes"], **config["rehearse"]}, params, config


def points(sizes, stream, count, seed=7):
    return F.make_points(seed, stream, 0, count, sizes["dim"], sizes["centres"],
                         sizes["centre_mean"], tuple(sizes["spread"]))


@pytest.fixture(scope="module")
def data():
    sizes, _params, _config = sizes_and_params()
    base, queries = points(sizes, 0, sizes["n"]), points(sizes, 1, sizes["queries"])
    return sizes, base, queries, F.tables([(base, 0, None)], queries, sizes["k"])


def brute(base, queries, dead=()):
    """Every query's float64 distances to every live row."""
    d = np.stack([F.sq_l2(np.repeat(q[None], len(base), 0), base) for q in queries])
    d[:, np.asarray(dead, np.int64)] = np.inf
    return d


def test_the_configuration_states_the_sources_shapes():
    sizes, params, config = sizes_and_params()
    full = config["sizes"]
    assert (full["n"], full["dim"], full["dtype"], full["metric"], full["k"],
            full["queries"], full["algo"]) == (1_000_000, 960, "FLOAT32", "L2", 10, 1000,
                                               "FLAT")
    assert config["reduced"] == [] and config["server_flags"] == [] and config["chips"] == 1
    assert (sizes["n"], sizes["queries"], sizes["dim"]) == (4096, 256, 960)  # width uncut
    assert (params["searches_per_frame"], params["connections"], params["k"],
            params["dim"], params["generator"]) == (64, 4, 10, 960, "ann_float")
    assert "data" in config["assumed"] and len(config["guarantees"]) == 4


def test_points_are_seeded_non_integer_floats_in_blocks():
    a = F.make_points(5, 0, 0, 70_000, 24, 8, 0.035, (0.008, 0.03))
    assert a.dtype == np.float32 and a.shape == (70_000, 24)
    assert a.min() >= 0 and a.max() < 1 and not (a == np.round(a)).any()
    # any range is the same points: a pure function of (seed, stream, position)
    assert np.array_equal(F.make_points(5, 0, 65_000, 66_000, 24, 8, 0.035, (0.008, 0.03)),
                          a[65_000:66_000])
    assert not np.array_equal(F.make_points(6, 0, 0, 100, 24, 8, 0.035, (0.008, 0.03)), a[:100])
    assert not np.array_equal(F.make_points(5, 1, 0, 100, 24, 8, 0.035, (0.008, 0.03)), a[:100])


def test_the_data_have_gists_scale(data):
    _sizes, _base, _queries, table = data
    assert 0.05 < np.median(table["dist"][:, 0]) < 2.0  # squared, to the nearest


def test_eps_is_gamma_of_the_width():
    assert F.eps(960) == pytest.approx(962 * 2.0 ** -24 / (1 - 962 * 2.0 ** -24))
    assert F.eps(960) == pytest.approx(5.7343e-5, rel=1e-4)
    # a float32 evaluation of the L2 sum, in any order, lies within it
    rng = np.random.default_rng(3)
    q, x = rng.random((200, 960), np.float32), rng.random((200, 960), np.float32)
    true = F.sq_l2(q, x)
    diff = x - q
    for got in (np.einsum("md,md->m", diff, diff, dtype=np.float32),
                (diff * diff)[:, ::-1].cumsum(axis=1, dtype=np.float32)[:, -1]):
        assert (np.abs(got.astype(np.float64) - true) <= F.eps(960) * true).all()


def test_tables_are_the_float64_brute_force(data):
    sizes, base, queries, _table = data
    k, e = sizes["k"], F.eps(sizes["dim"])
    small, few = base[:1500], queries[:12]
    dead = np.array([3, 77, 1400])
    extra = few[:2] + np.float32(0.001)
    got = F.tables([(small, 0, dead), (extra, 5000, None)], few, k, m=16, block=512)
    d = np.concatenate([brute(small, few, dead), brute(extra, few)], axis=1)
    ids = np.concatenate([np.arange(1500), 5000 + np.arange(2)])
    for j in range(len(few)):
        order = np.lexsort((ids, d[j]))[:16]
        assert np.array_equal(got["ids"][j], ids[order])
        assert np.allclose(got["dist"][j], d[j, order], rtol=1e-12)
        kth = d[j, order[k - 1]]
        assert got["bound"][j] == pytest.approx(kth * (1 + e))
        assert not np.isin(got["ids"][j], dead).any()
    assert got["ids"][0, 0] == 5000 and got["ids"][1, 0] == 5001


def test_a_query_short_of_candidates_is_ranked_in_full(data):
    """With m = k + 1 a query's (k+1)-th float32 candidate cannot clear the
    bound by the pass's own rounding: it is ranked again over every row."""
    sizes, base, queries, _table = data
    got = F.tables([(base[:2000], 0, None)], queries[:4], sizes["k"], m=sizes["k"] + 1)
    want = F.tables([(base[:2000], 0, None)], queries[:4], sizes["k"])
    assert got["exact_rows"][0] > 0
    assert np.array_equal(got["ids"], want["ids"][:, :sizes["k"] + 1])


def _canonical(base, queries, ids):
    """Replies as the program makes them: float32 L2 of each pair, ordered."""
    diff = base[ids.reshape(-1)] - np.repeat(queries, ids.shape[1], 0)
    s = np.einsum("md,md->m", diff, diff, dtype=np.float32).reshape(ids.shape)
    order = np.lexsort((ids, s), axis=1)
    return (np.take_along_axis(ids, order, axis=1),
            np.take_along_axis(s, order, axis=1).astype(np.float64))


def test_the_precision_below_the_stated_one_is_not_correct(data):
    """Exact float32 L2 is what the configuration states.  The ids a ranking
    from bfloat16 operands picks (what a DEFAULT-precision matmul computes),
    each then scored exactly, are not admissible on a large share of
    queries; the float32 ranking's are on every query."""
    sizes, base, queries, table = data
    k = sizes["k"]
    right = F.tables([(base, 0, None)], queries, k)["ids"][:, :k]
    assert not F.reply_failures(table, *_canonical(base, queries, right), sizes["dim"]).any()
    low = F.tables([(base, 0, None)], queries, k, lower=True)["ids"][:, :k]
    share = F.reply_failures(table, *_canonical(base, queries, low), sizes["dim"]).mean()
    assert share > 0.3, share


def test_four_decimals_and_an_unsorted_reply_are_refused(data):
    sizes, base, queries, table = data
    ids, scores = _canonical(base, queries, table["ids"][:, :sizes["k"]])
    assert not F.reply_failures(table, ids, scores, sizes["dim"]).any()
    four = F.parse_scores([b"%.4f" % s for s in scores.reshape(-1)]).reshape(scores.shape)
    assert F.reply_failures(table, ids, four, sizes["dim"]).mean() > 0.5
    swapped_i, swapped_s = ids.copy(), scores.copy()
    swapped_i[:, [0, 1]], swapped_s[:, [0, 1]] = ids[:, [1, 0]], scores[:, [1, 0]]
    assert F.reply_failures(table, swapped_i, swapped_s, sizes["dim"]).mean() > 0.95
    # printed in full, the scores read back as they were
    nine = F.parse_scores([b"%.9g" % s for s in scores.reshape(-1)]).reshape(scores.shape)
    assert np.array_equal(nine, scores)


def test_a_reply_that_misses_a_written_document_is_refused(data):
    """After the window copies of some queries are written and the nearest
    neighbours of others deleted (``after_window``): a reply that does not
    see the writes is refused; one that does passes, each copy first at
    exactly 0."""
    sizes, base, queries, _table = data
    k, n, m = sizes["k"], sizes["n"], 8
    copied, bereft = queries[:m], queries[m:2 * m]
    before = F.tables([(base, 0, None)], queries[:2 * m], k)
    dead = np.unique(before["ids"][m:, 0])
    after = F.tables([(base, 0, dead), (copied, n, None)], queries[:2 * m], k)
    stale = _canonical(base, queries[:2 * m], before["ids"][:, :k])
    assert G.written_failures(after, *stale, n, m, sizes["dim"], dead)
    assert F.reply_failures(after, *stale, sizes["dim"], dead=dead)[m:].all()
    rows = np.concatenate([base, copied])
    fresh = _canonical(rows, queries[:2 * m], after["ids"][:, :k])
    assert G.written_failures(after, *fresh, n, m, sizes["dim"], dead) == []
    assert (fresh[0][:m, 0] == n + np.arange(m)).all() and (fresh[1][:m, 0] == 0).all()
    assert not np.isin(fresh[0], dead).any() and len(bereft) == m


def test_deviations_read_how_near_a_reply_comes_to_the_limits(data):
    sizes, base, queries, table = data
    ids, scores = _canonical(base, queries, table["ids"][:, :sizes["k"]])
    score_dev, rank_dev = F.deviations(table, ids, scores, sizes["dim"])
    assert 0 < score_dev < 0.1 and rank_dev <= 0  # float32 L2 is far inside EPS
    off = scores * (1 + 3 * F.eps(sizes["dim"]))
    assert F.deviations(table, ids, off, sizes["dim"])[0] == pytest.approx(3, abs=0.1)
    low = F.tables([(base, 0, None)], queries, sizes["k"], lower=True)["ids"][:, :sizes["k"]]
    assert F.deviations(table, *_canonical(base, queries, low), sizes["dim"])[1] > 1


def test_reply_failures_names_each_kind_of_wrong_reply(data):
    sizes, base, queries, table = data
    k = sizes["k"]
    ids, scores = _canonical(base, queries[:6], table["ids"][:6, :k])
    rows = {name: arr[:6] for name, arr in table.items() if name != "exact_rows"}
    got_i, got_s = ids.copy(), scores.copy()
    got_i[1, 9] = got_i[1, 0]                        # an id twice
    got_s[2, 3] *= 1 + 4 * F.eps(sizes["dim"])      # a score off by more than EPS
    got_i[3, 9] = table["ids"][3, -1]                # an id beyond the bound
    got_i[4, 5] = -1                                 # a short reply
    bad = F.reply_failures(rows, got_i, got_s, sizes["dim"])
    assert bad.tolist() == [False, True, True, True, True, False]


def test_a_frame_is_a_pure_function_of_seed_connection_and_number(tmp_path, data):
    sizes, base, queries, table = data
    _s, params, _c = sizes_and_params()
    for name in ("ids", "dist", "bound"):
        np.save(tmp_path / (name + ".npy"), table[name])
    np.save(tmp_path / "queries.npy", queries)

    def stream(seed, conn):
        return G.Stream(StreamContext(sizes, params, seed, conn, 4, str(tmp_path)))

    a, b = stream(9, 0), stream(9, 0)
    for idx in (-1, 0, 1, 5, 1000):
        assert np.array_equal(a.make(idx), b.make(idx)) and len(a.make(idx)) == 64
    assert not np.array_equal(a.make(0), stream(9, 1).make(0))
    assert not np.array_equal(a.make(0), stream(10, 0).make(0))
    seen = np.concatenate([a.make(i) for i in range(len(queries) // 64)])
    assert sorted(seen.tolist()) == list(range(len(queries)))
    cmd = a.commands[3]
    assert cmd[:4] == ("FT.SEARCH", "idx", "*=>[KNN 10 @vector $BLOB]", "NOCONTENT")
    assert cmd[12] == queries[3].astype("<f4").tobytes() and len(cmd[12]) == 3840
    req = a.make(0)
    right = _canonical(base, queries[req], table["ids"][req, :sizes["k"]])
    a.keep(0, req, right)
    assert a.verify() == {"checked_full": 64, "checked": 64, "failures": []}
    a.keep(1, req, (right[0], np.round(right[1], 4)))
    assert a.verify()["failures"] and a.wrong > 32


# -- the reader -------------------------------------------------------------------

HITS, MOVED = "rtpu_knn_hits_total", "rtpu_knn_hits_reordered_total"


@pytest.mark.parametrize("before,after,want", [
    ({HITS: 10.0, MOVED: 1.0}, {HITS: 6410.0, MOVED: 33.0}, 0.5),
    ({HITS: 0.0, MOVED: 0.0}, {HITS: 640.0, MOVED: 0.0}, 0.0),
    ({HITS: 5.0, MOVED: 0.0}, {HITS: 5.0, MOVED: 0.0}, None),   # no hit in the window
    ({}, {}, None),                                             # a program without them
])
def test_reordered_hit_share_by_hand(before, after, want):
    obs = Obs()
    obs.metrics_before, obs.metrics_after = before, after
    got = reader("knn.reordered_hit_share")(obs)
    assert got == want if want is None else got == pytest.approx(want)


# -- the cell ---------------------------------------------------------------------


def test_the_cell_is_in_the_manifest_by_additions():
    m = cells()
    assert m["workloads"][-1] == {
        "name": CELL, "config": "ann-gist-1m", "traffic": "knn-gist-batch-64", "chips": 1,
        "why": m["workloads"][-1]["why"]}
    assert m["configs"][-1]["name"] == "ann-gist-1m" and m["configs"][-1]["reduced"] == []
    assert m["per_layer"][-1] == {
        "name": "knn.reordered_hit_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "search", "moves": "ops_per_s",
        "workloads": [CELL]}
    # every list ann-batch is on, but the host's gc/stall tails, names the cell last
    for metric in m["per_layer"] + m["end_to_end"]:
        lst = metric.get("workloads")
        if lst and "ann-batch" in lst:
            assert lst[-1] == CELL, metric["name"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    last, detail = rehearse(ROOT, CELL, trace)
    m = cells()
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert detail["failures"] == [] and last["failed"] == 0 and last["attempted"] > 0
    assert detail["client"]["checked"] >= 64 * last["attempted"]
    kind = "per_layer" if trace else "end_to_end"
    allowed = {x["name"] for x in m[kind] if CELL in x.get("workloads", [CELL])}
    assert set(last["metrics"]) <= allowed
    if trace:
        assert {"knn.reordered_hit_share", "knn.cmds_per_dispatch",
                "ioplane.readback_ms", "device.idle_share"} <= set(last["metrics"])
    else:
        assert set(last["metrics"]) == allowed

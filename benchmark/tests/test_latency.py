"""``benchmark/latency.py``: what one judged latency sample is.  Cycle samples
of a synthetic window against hand-computed values; a mix without
``latency_over`` is judged bit for bit as before PR 31."""
import json
import os

import numpy as np
import pytest

from benchmark import latency
from benchmark.loadgen import load_generator
from benchmark.tests.test_spans import Obs, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ADD, READ = 100000, 1000
PARAMS = {"pairs_per_add": ADD, "pairs_per_read": READ, "read_every": 3,
          "latency_over": "cycle"}


def rows(*frames):
    """(t_send, t_done, ops[, ok]) a frame, idx in order; a closed loop's
    t_ref is its t_send."""
    return np.array([(i, f[0], f[0], f[1], f[0], f[2], f[3] if len(f) > 3 else 1)
                     for i, f in enumerate(frames)], np.float64).reshape(-1, 7)


# connection 0: two whole cycles of three frames, then the cycle the window's
# end cut: one add and the closing read
CONN0 = rows((0.000, 0.004, ADD), (0.005, 0.009, ADD), (0.010, 0.030, READ),     # 30 ms / 3
             (0.031, 0.035, ADD), (0.036, 0.050, ADD), (0.051, 0.091, READ),     # 60 ms / 3
             (0.092, 0.096, ADD), (0.097, 0.120, READ))                          # cut: left out
# connection 1: a whole cycle, a cycle with a failed add, a whole cycle, the closing read alone
CONN1 = rows((0.000, 0.010, ADD), (0.011, 0.020, ADD), (0.021, 0.045, READ),     # 45 ms / 3
             (0.046, 0.050, ADD, 0), (0.051, 0.055, ADD), (0.056, 0.080, READ),  # failed: left out
             (0.081, 0.085, ADD), (0.086, 0.090, ADD), (0.091, 0.105, READ),     # 24 ms / 3
             (0.106, 0.130, READ))                                               # closing: left out


def reports():
    return [{"conn": 0, "rows": CONN0}, {"conn": 1, "rows": CONN1}]


def test_a_cycle_sample_is_its_wall_time_over_its_frames():
    gen = load_generator("hll_bank")
    got = latency.cycle_ms(CONN0, gen.cycle_ends(PARAMS, CONN0[:, 5]))
    assert got == pytest.approx([10.0, 20.0])
    got = latency.cycle_ms(CONN1, gen.cycle_ends(PARAMS, CONN1[:, 5]))
    assert got == pytest.approx([15.0, 8.0])
    judged = latency.judged_ms(reports(), PARAMS, gen)
    assert judged == pytest.approx([10.0, 20.0, 15.0, 8.0])
    assert float(np.median(judged)) == pytest.approx(12.5)
    # the per-frame median of the same 17 answered frames (seven of them 4 ms adds)
    assert float(np.median(latency.request_ms(np.concatenate([CONN0, CONN1])))) \
        == pytest.approx(10.0)


def test_a_window_too_short_for_a_cycle_gives_no_sample():
    gen = load_generator("hll_bank")
    cut = rows((0.0, 0.004, ADD), (0.005, 0.030, READ))  # an add and the closing read
    assert len(latency.cycle_ms(cut, gen.cycle_ends(PARAMS, cut[:, 5]))) == 0
    assert len(latency.cycle_ms(cut[:0], np.zeros(0, bool))) == 0


def test_without_the_key_the_judged_samples_are_every_answered_request_as_before():
    """What run.py computed until PR 31, spelled out, against the module:
    bit for bit, for every mix that does not name ``latency_over``."""
    rng = np.random.default_rng(5)
    reps = []
    for conn in range(3):
        n = 40 + conn
        t_ref = np.sort(rng.uniform(0, 20, n))
        r = np.column_stack([np.arange(n), t_ref, t_ref + rng.uniform(0, 1e-3, n),
                             t_ref + rng.uniform(2e-3, 9e-2, n), t_ref,
                             np.full(n, 16.0), rng.uniform(size=n) > 0.1])
        reps.append({"conn": conn, "rows": r})
    every = np.concatenate([r["rows"] for r in reps]).reshape(-1, 7)
    ok = every[:, 6] == 1
    before = (every[ok, 3] - every[ok, 1]) * 1e3
    got = latency.judged_ms(reps, {}, gen=None)  # no generator is asked
    assert got.tobytes() == before.tobytes()
    assert float(np.median(got)) == float(np.median(before))
    assert float(np.percentile(got, 95)) == float(np.percentile(before, 95))
    for unknown in ("cycles", "request"):  # no key means requests: there is no second spelling
        with pytest.raises(ValueError):
            latency.judged_ms(reps, {"latency_over": unknown}, gen=None)


def test_only_the_stream_mix_is_judged_over_cycles():
    over = {}
    for name in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        with open(os.path.join(BENCH, "traffic", name)) as fh:
            over[name[:-5]] = json.load(fh).get("latency_over")
    assert over == {"bulk-flush-100k": None, "fanout-64-by-verb": None, "point-16key": None,
                    "stream-add-merge": "cycle"}


def observed(params, latency_ms, judged):
    obs = Obs()
    obs.params, obs.latency_ms = params, np.array(latency_ms, np.float64)
    obs.judged_ms = np.array(judged, np.float64)
    return obs


def test_frame_p50_reads_single_frames_only_where_cycles_are_judged():
    frames, cycles = [4.0, 4.0, 20.0, 4.0, 14.0, 40.0], [10.0, 20.0]
    obs = observed(PARAMS, frames, cycles)
    assert reader("client.frame_p50_ms")(obs) == 9.0
    assert reader("client.traced_req_p50_ms")(obs) == 15.0  # what req_p50_ms is there
    same = observed({}, frames, frames)  # judged over requests: it would repeat traced_req_p50_ms
    assert reader("client.frame_p50_ms")(same) is None
    assert reader("client.traced_req_p50_ms")(same) == 9.0
    assert reader("client.frame_p50_ms")(observed(PARAMS, [], [])) is None
    assert reader("client.traced_req_p50_ms")(observed(PARAMS, [], [])) is None

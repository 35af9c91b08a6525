"""The readers of the event loop's account (PR 37): ``loop.wake_ms`` on a
synthetic slice whose values are computed by hand, the seven ratios of the
always-on series on two hand-made scrapes, and a traced rehearsal of the two
cells whose requests are mostly the loop's."""
import pytest

from benchmark import loop_account, spans
from benchmark.tests.test_rehearse import ROOT, cells, rehearse
from benchmark.tests.test_spans import Obs, frame, reader

NEW = ("loop.wake_ms", "loop.busy_share", "loop.turn_ms", "loop.turns_per_frame",
       "loop.busy_us_per_frame", "host.loop_cpu_share", "host.worker_cpu_share",
       "host.process_cpu_share")

# frame 1 (overlapped readback): wake 200 after its dispatch, reply.wake 300
#   inside reply.wait: 500 us of waiting for the loop
# frame 2 (a serial segment of two jobs): wakes of 100 and 150: 250 us
# frame 3 (the blocking force): wake 400 from dispatch, 600 from force: 1,000 us
# frame 4 has no wake at all (a fully shed frame): it is in no median
REPLY = [
    frame(1, 10000, [
        (b"hop", 200, 100, [b"to", b"dispatch"]), (b"dispatch", 300, 1000, []),
        (b"wake", 1300, 200, [b"frm", b"dispatch"]),
        (b"hop", 1500, 100, [b"to", b"force"]), (b"readback", 1600, 7000, []),
        (b"reply.wait", 1500, 7400, []), (b"reply.wake", 8600, 300, [b"frm", b"force"]),
        (b"reply", 1500, 8500, [])]),
    frame(2, 3000, [
        (b"hop", 100, 100, [b"to", b"dispatch"]), (b"dispatch", 200, 500, []),
        (b"wake", 700, 100, [b"frm", b"dispatch"]),
        (b"hop", 800, 100, [b"to", b"dispatch"]), (b"dispatch", 900, 500, []),
        (b"wake", 1400, 150, [b"frm", b"dispatch"]), (b"reply", 1600, 1400, [])]),
    frame(3, 9000, [
        (b"hop", 100, 100, [b"to", b"dispatch"]), (b"dispatch", 200, 2000, []),
        (b"wake", 2200, 400, [b"frm", b"dispatch"]),
        (b"hop", 2600, 100, [b"to", b"force"]), (b"readback", 2700, 5000, []),
        (b"wake", 7700, 600, [b"frm", b"force"]), (b"reply", 8300, 700, [])]),
    frame(4, 500, [(b"parse", 0, 100, []), (b"reply", 100, 400, [])]),
]

BEFORE = {loop_account.TURNS: 1_000.0, loop_account.BUSY_S: 2.0,
          loop_account.FRAMES: 500.0, loop_account.LOOP_CPU_S: 1.5,
          loop_account.WORKER_CPU_S: 4.0, loop_account.PROCESS_CPU_S: 30.0,
          loop_account.UPTIME_S: 100.0}
# a 20 s window: 16 s busy in 40,000 turns over 10,000 frames; the loop's
# thread ran 12 s, the pools' threads 6 s, the process 50 s (2.5 cores)
AFTER = {loop_account.TURNS: 41_000.0, loop_account.BUSY_S: 18.0,
         loop_account.FRAMES: 10_500.0, loop_account.LOOP_CPU_S: 13.5,
         loop_account.WORKER_CPU_S: 10.0, loop_account.PROCESS_CPU_S: 80.0,
         loop_account.UPTIME_S: 120.0}
BY_HAND = {"loop.busy_share": 80.0, "loop.turn_ms": 0.4, "loop.turns_per_frame": 4.0,
           "loop.busy_us_per_frame": 1_600.0, "host.loop_cpu_share": 60.0,
           "host.worker_cpu_share": 30.0, "host.process_cpu_share": 250.0}
READS = {"loop.busy_share": (loop_account.BUSY_S, loop_account.UPTIME_S),
         "loop.turn_ms": (loop_account.BUSY_S, loop_account.TURNS),
         "loop.turns_per_frame": (loop_account.TURNS, loop_account.FRAMES),
         "loop.busy_us_per_frame": (loop_account.BUSY_S, loop_account.FRAMES),
         "host.loop_cpu_share": (loop_account.LOOP_CPU_S, loop_account.UPTIME_S),
         "host.worker_cpu_share": (loop_account.WORKER_CPU_S, loop_account.UPTIME_S),
         "host.process_cpu_share": (loop_account.PROCESS_CPU_S, loop_account.UPTIME_S)}


def observed(reply=(), before=None, after=None):
    obs = Obs()
    obs.frames = spans.parse_frames(list(reply))
    obs.metrics_before = dict(BEFORE if before is None else before)
    obs.metrics_after = dict(AFTER if after is None else after)
    return obs


def test_wake_ms_sums_a_frames_wakes_and_takes_the_median_of_frames_that_have_one():
    assert reader("loop.wake_ms")(observed(REPLY)) == 0.5  # of 0.25, 0.5, 1.0
    assert reader("loop.wake_ms")(observed(REPLY[:2])) == 0.375


def test_wake_ms_is_none_on_a_program_that_records_no_wake():
    old = [frame(1, 5000, [(b"hop", 100, 100, [b"to", b"dispatch"]),
                           (b"dispatch", 200, 3000, []), (b"reply", 3300, 1700, [])])]
    assert reader("loop.wake_ms")(observed(old)) is None
    assert reader("loop.wake_ms")(observed()) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_ratio_by_hand(name):
    assert reader(name)(observed()) == pytest.approx(BY_HAND[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_ratio_is_none_without_its_series_or_its_denominator(name):
    over, under = READS[name]
    for missing in (over, under):
        # the parent's server has neither series: no value, and no raise
        before = {k: v for k, v in BEFORE.items() if k != missing}
        assert reader(name)(observed(before=before)) is None
        assert reader(name)(observed(before={}, after={})) is None
    # a window that added nothing to the denominator
    assert reader(name)(observed(after={**AFTER, under: BEFORE[under]})) is None
    # nothing added to the numerator is a value: 0
    assert reader(name)(observed(after={**AFTER, over: BEFORE[over]})) == 0.0


# a share over the uptime between the two scrapes reads true only where the
# second scrape follows the window closely: not in `bank-bulk` and `fanout-4`,
# whose profile takes 19-29 s to stop (PERF.md section 7)
SHARES = ("loop.busy_share", "host.loop_cpu_share", "host.worker_cpu_share",
          "host.process_cpu_share")


def test_every_new_entry_has_its_file_and_lists_its_cells():
    import os

    m = cells()
    six = ["bank-bulk", "bank-point", "hll-stream", "fanout-4", "ann-batch", "bf-200c"]
    at = [x["name"] for x in m["per_layer"]]
    entries = {x["name"]: x for x in m["per_layer"]}
    for name in NEW:
        x = entries[name]
        assert at.count(name) == 1
        assert at.index(name) > at.index("point.wait_ms")  # after the parent's
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
        assert x["moves"] == "req_p50_ms" and x["better"] == "lower"
        # an explicit list, so that a later cell is not tripped; one may be
        # appended to it
        listed = [c for c in six if name not in SHARES or c not in ("bank-bulk", "fanout-4")]
        assert x["workloads"][:len(listed)] == listed
        assert x["layer"] == ("dispatch" if name == "loop.wake_ms" else "host")
        assert x["source"] == ("program_span" if name == "loop.wake_ms" else "program_counter")


@pytest.mark.parametrize("cell", ["bank-point", "bf-200c"])
def test_a_traced_rehearsal_reports_all_eight(cell):
    last, detail = rehearse(ROOT, cell, 1, seconds="3")
    assert detail["failures"] == [] and last["failed"] == 0 and last["attempted"] > 0
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    assert 0 < got["loop.wake_ms"] < got["client.traced_req_p50_ms"]
    assert 0 < got["loop.busy_share"] <= 100 and 0 < got["host.loop_cpu_share"] <= 100
    assert got["loop.turn_ms"] > 0 and got["loop.busy_us_per_frame"] > 0
    assert got["loop.turns_per_frame"] > 0.5
    assert got["host.worker_cpu_share"] > 0 and got["host.process_cpu_share"] > 0
    # the way back is named: what no span covers is a sliver of the frame
    assert got["frame.unspanned_ms"] < 0.25 * (
        got["client.traced_req_p50_ms"] - got["client.overhead_ms"])

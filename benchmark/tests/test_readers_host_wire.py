"""The readers of the closed frame waterfall (``recv``, ``hop``, the inside
of ``reply``) and of the host's pauses, on a synthetic slice whose values are
computed by hand."""
import pytest

from benchmark import spans
from benchmark.tests.test_spans import Obs, frame, reader

# frame 1, a readback frame (total 10,000 us):
#   recv [-4000, 0) | parse [0, 100) | qos [150, 200) | hop [200, 300) |
#   dispatch [300, 1300) with stage [320, 420) | hop [1400, 1600) to force |
#   readback [1600, 8600) | reply [1400, 10000) with reply.wait [1400, 9000),
#   reply.encode [9000, 9100), reply.write [9200, 9900)
#   in no span: [100, 150) + [1300, 1400) = 150 us
# frame 2, no readback (total 2,000 us):
#   recv [0, 0) | parse [0, 50) | hop [100, 400) | dispatch [400, 1400) |
#   reply [1500, 2000) with reply.encode [1500, 1520), reply.wait [1520, 1600),
#   reply.write [1650, 1950) | host.stall [0, 2000) covers nothing
#   in no span: [50, 100) + [1400, 1500) = 150 us
# frame 3 (total 4,000 us): as frame 2 with every gap doubled
#   in no span: [100, 300) + [1500, 1600) = 300 us
REPLY = [
    frame(1, 10000, [
        (b"parse", 0, 100, []),
        (b"recv", -4000, 4000, [b"reads", 19, b"nbytes", 1200000, b"feed_us", 300]),
        (b"qos", 150, 50, [b"shed", 0]),
        (b"hop", 200, 100, [b"to", b"dispatch"]),
        (b"stage", 320, 100, [b"device", 0]),
        (b"dispatch", 300, 1000, []),
        (b"hop", 1400, 200, [b"to", b"force"]),
        (b"readback", 1600, 7000, [b"grouped", 1, b"blocking", 1]),
        (b"reply.wait", 1400, 7600, []),
        (b"reply.encode", 9000, 100, []),
        (b"reply.write", 9200, 700, [b"nbytes", 12500, b"batch", 1]),
        (b"reply", 1400, 8600, [])]),
    frame(2, 2000, [
        (b"parse", 0, 50, []),
        (b"recv", 0, 0, [b"reads", 1, b"nbytes", 200, b"feed_us", 0]),
        (b"hop", 100, 300, [b"to", b"dispatch"]),
        (b"dispatch", 400, 1000, []),
        (b"reply.encode", 1500, 20, []),
        (b"reply.wait", 1520, 80, []),
        (b"reply.write", 1650, 300, [b"nbytes", 7, b"batch", 2]),
        (b"reply", 1500, 500, []),
        (b"host.stall", 0, 2000, [])]),
    frame(3, 4000, [
        (b"parse", 0, 100, []),
        (b"recv", -1000, 1000, [b"reads", 2, b"nbytes", 70000, b"feed_us", 10]),
        (b"hop", 300, 200, [b"to", b"dispatch"]),
        (b"dispatch", 500, 1000, []),
        (b"reply.encode", 1600, 100, []),
        (b"reply.wait", 1700, 300, []),
        (b"reply.write", 2000, 1900, [b"nbytes", 7, b"batch", 1]),
        (b"reply", 1600, 2400, [])]),
]
# what PR 22's server sends: none of the new spans, none of the new series
OLD_REPLY = [
    frame(1, 5000, [(b"parse", 0, 100, []), (b"dispatch", 200, 3000, []),
                    (b"readback", 2500, 1500, [b"grouped", 1, b"blocking", 1]),
                    (b"reply", 3200, 1800, [])]),
]
SERIES = {"gc_pause_seconds": "rtpu_host_gc_pause_seconds_total",
          "gc_long": "rtpu_host_gc_long_pauses_total",
          "stall_seconds": "rtpu_host_loop_stall_seconds_total",
          "stall_long": "rtpu_host_loop_long_stalls_total"}


def observed(reply, before, after):
    obs = Obs()
    obs.frames = spans.parse_frames(reply)
    obs.metrics_before = {"rtpu_qos_shed_ops": 0.0, **before}
    obs.metrics_after = {"rtpu_qos_shed_ops": 0.0, **after}
    return obs


NEW = observed(
    REPLY,
    {SERIES["gc_pause_seconds"]: 1.5, SERIES["gc_long"]: 2.0,
     SERIES["stall_seconds"]: 0.25, SERIES["stall_long"]: 1.0},
    {SERIES["gc_pause_seconds"]: 1.75, SERIES["gc_long"]: 5.0,
     SERIES["stall_seconds"]: 0.75, SERIES["stall_long"]: 3.0})
OLD = observed(OLD_REPLY, {}, {})
EMPTY = observed([], {}, {})


@pytest.mark.parametrize("name,value", [
    ("wire.recv_ms", 1.0),             # median of 4000, 0, 1000 us
    ("executor.hop_ms", 0.3),          # per-frame sums 300, 300, 200 us
    ("wire.reply_wait_ms", 0.3),       # 7600, 80, 300 us
    ("wire.reply_send_ms", 0.8),       # 100 + 700, 20 + 300, 100 + 1900 us
    ("frame.unspanned_ms", 0.15),      # 150, 150, 300 us
    ("host.gc_pause_ms", 250.0),
    ("host.gc_long_pauses", 3.0),
    ("host.loop_stall_ms", 500.0),
    ("host.loop_long_stalls", 2.0),
])
def test_reader_gives_the_value_computed_by_hand(name, value):
    assert reader(name)(NEW) == pytest.approx(value, abs=1e-9)


@pytest.mark.parametrize("name", [
    "wire.recv_ms", "executor.hop_ms", "wire.reply_wait_ms", "wire.reply_send_ms",
    "host.gc_pause_ms", "host.gc_long_pauses", "host.loop_stall_ms",
    "host.loop_long_stalls"])
def test_reader_reports_nothing_of_a_program_without_its_span_or_series(name):
    assert reader(name)(OLD) is None
    assert reader(name)(EMPTY) is None


def test_unspanned_ignores_recv_before_zero_and_counts_reply_once():
    read = reader("frame.unspanned_ms")
    f1, f2, f3 = NEW.frames
    # frame 1: recv's 4,000 us lie before offset 0 and cover nothing; reply
    # [1400, 10000) and its three children cover 8,600 us, once
    one = observed([REPLY[0]], {}, {})
    assert read(one) == 0.15
    without_children = dict(f1, spans=[s for s in f1["spans"]
                                       if not s["name"].startswith("reply.")])
    one.frames = [without_children]
    assert read(one) == 0.15
    # a span that crosses offset 0 covers only its part inside the frame
    crossing = dict(f3, spans=f3["spans"] + [
        {"name": "recv", "off_us": -500, "dur_us": 700, "attrs": {}}])
    one.frames = [crossing]
    assert read(one) == 0.2  # [100, 200) of the 300 is now covered
    # host.* annotate a pause: frame 2's stall spans the frame, covers nothing
    one.frames = [f2]
    assert read(one) == 0.15
    # PR 22's spans leave [100, 200) of 5,000 us uncovered; no frames, no value
    assert read(OLD) == 0.1
    assert read(EMPTY) is None


def test_new_spans_parse_unchanged_and_leave_the_old_readers_alone():
    f1 = NEW.frames[0]
    recv = next(s for s in f1["spans"] if s["name"] == "recv")
    assert recv["off_us"] == -4000 and recv["attrs"]["reads"] == 19
    assert reader("wire.parse_ms")(NEW) == 0.1
    assert reader("wire.reply_ms")(NEW) == 2.4      # 8600, 500, 2400 us
    assert reader("dispatch.self_ms")(NEW) == 1.0   # 900, 1000, 1000 us
    assert reader("ioplane.readback_ms")(NEW) == 7.0

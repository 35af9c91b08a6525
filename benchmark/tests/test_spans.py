"""Span reducers on a synthetic TRACE GET reply with known self times."""
import importlib.util
import os

import numpy as np

from benchmark import spans

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(name):
    spec = importlib.util.spec_from_file_location("m", os.path.join(
        BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def frame(tid, total, spans_, verb=b"BFA.MEXISTS64", ms=1000):
    return [tid, ms, total, verb, 1, b"interactive", b"default",
            [[n, off, dur, attrs] for n, off, dur, attrs in spans_]]


REPLY = [
    frame(1, 5000, [(b"parse", 0, 100, []), (b"qos", 110, 40, [b"shed", 0]),
                    (b"dispatch", 200, 3000, []),
                    (b"stage", 300, 500, [b"device", 0]),
                    (b"kernel", 900, 1000, [b"verb", b"BF.MEXISTS64", b"members", 4]),
                    (b"kernel.member", 900, 1000, [b"key", b"a"]),
                    (b"readback", 2500, 1500, [b"grouped", 1, b"blocking", 1]),
                    (b"reply", 3200, 1800, [])]),
    frame(2, 3000, [(b"parse", 0, 300, []), (b"dispatch", 400, 1000, []),
                    (b"readback", 1500, 500, [b"grouped", 1, b"blocking", 0]),
                    (b"reply", 1400, 1600, [])]),
    frame(3, 999, [(b"parse", 0, 5, []), (b"dispatch", 10, 50, [])], verb=b"trace"),
]


class Obs:
    pass


def test_parse_drops_control_frames_and_decodes_attrs():
    frames = spans.parse_frames(REPLY)
    assert [f["id"] for f in frames] == [1, 2]
    kernel = next(s for s in frames[0]["spans"] if s["name"] == "kernel")
    assert kernel["attrs"] == {"verb": "BF.MEXISTS64", "members": 4}


def test_self_time_is_span_minus_what_children_cover():
    f1, f2 = spans.parse_frames(REPLY)
    # dispatch [200, 3200); stage [300, 800), kernel [900, 1900), readback
    # overlaps it on [2500, 3200): 3000 - 500 - 1000 - 700
    assert spans.self_us(f1, "dispatch", ("stage", "kernel", "readback")) == 800
    assert spans.self_us(f2, "dispatch", ("stage", "kernel", "readback")) == 1000
    assert spans.stage_us(f1, "readback") == 1500
    assert spans.stage_median_ms([f1, f2], "parse") == 0.2
    assert spans.stage_median_ms([f1, f2], "stage") == 0.5  # only frames that have it
    assert spans.stage_median_ms([f1, f2], "promote") is None


def test_readers_on_the_synthetic_slice():
    obs = Obs()
    obs.frames = spans.parse_frames(REPLY)
    obs.slice_latency_ms = np.array([6.0, 4.0])
    obs.slice_requests = 2
    assert reader("dispatch.self_ms")(obs) == 0.9
    assert reader("wire.reply_ms")(obs) == 1.7
    assert reader("qos.wait_ms")(obs) == 0.04
    assert reader("coalesce.cmds_per_kernel")(obs) == 4.0
    assert reader("ioplane.blocking_syncs_per_frame")(obs) == 0.5
    assert reader("ioplane.readback_ms")(obs) == 1.0
    assert reader("wire.frames_per_request")(obs) == 1.0
    assert reader("client.overhead_ms")(obs) == 5.0 - 4.0  # median 5 ms - median total 4 ms
    obs.frames = []
    for name in ("dispatch.self_ms", "coalesce.cmds_per_kernel", "client.overhead_ms",
                 "wire.parse_ms", "ioplane.blocking_syncs_per_frame"):
        assert reader(name)(obs) is None  # nothing to read: nothing reported


def test_device_readers():
    obs = Obs()
    obs.device = {"busy_s": [0.5, 0.1], "window_s": 2.0}
    obs.slice_ops = 4e6
    assert reader("device.idle_share")(obs) == 85.0
    assert reader("device.idle_share_min")(obs) == 75.0
    assert reader("device.idle_share_max")(obs) == 95.0
    assert reader("kernel.device_ms_per_mop")(obs) == 150.0
    obs.device = None
    assert reader("device.idle_share")(obs) is None

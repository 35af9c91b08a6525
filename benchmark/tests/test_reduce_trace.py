"""The reduction from a profiler trace to busy time, per-program time and
idle gaps: exact on a synthetic trace, and sane on recorded ones."""
import glob
import os

import pytest

from benchmark import reduce_trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


class E:
    def __init__(self, name, start_ms, dur_ms):
        self.name, self.start_ns, self.duration_ns = name, start_ms * MS, dur_ms * MS


class L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def synthetic():
    return [
        P("/host:CPU", [L("bench-control", [E(T.MARK, 100, 1)])]),
        P("/device:TPU:0", [
            L("XLA Modules", [E("jit_probe(123)", 110, 40), E("jit_probe(123)", 300, 40),
                              E("jit_add(9)", 600, 100)]),
            # the op before the mark is clipped away; two ops overlap
            L("XLA Ops", [E("fusion.1", 50, 20), E("fusion.1", 110, 30), E("copy.2", 130, 20),
                          E("fusion.1", 300, 40), E("scatter.3", 600, 100)]),
            L("Steps", [E("ignored", 0, 1000)])]),
        P("/device:TPU:1", [
            L("XLA Modules", [E("jit_probe(77)", 900, 50)]),
            L("XLA Ops", [E("fusion.1", 900, 50)])]),
    ]


def test_busy_idle_programs_and_gaps_on_a_synthetic_trace():
    wall0 = 1_700_000_000 * 1_000_000_000
    out = T.reduce_planes(synthetic(), "tpu", mark_wall_ns=wall0, stop_wall_ns=wall0 + 900 * MS)
    assert out["devices"] == 2 and out["clock"] == "wall"
    assert out["window_s"] == pytest.approx(0.9)          # mark at 100 ms, stop at 1000 ms
    assert out["busy_s"] == pytest.approx([0.18, 0.05])   # 40 + 40 + 100 ms; 50 ms
    assert dict(out["programs"]) == pytest.approx({"jit_probe": 0.13, "jit_add": 0.1})
    assert dict(out["ops"]) == pytest.approx(
        {"fusion.1": 0.12, "scatter.3": 0.1, "copy.2": 0.02})
    top = out["gaps"][0]
    assert (top["device"], top["seconds"]) == (1, pytest.approx(0.8))  # 100 -> 900 ms
    assert top["start_wall_ns"] == wall0
    dev0 = [g for g in out["gaps"] if g["device"] == 0]
    assert [round(g["seconds"], 3) for g in dev0] == [0.3, 0.26, 0.15, 0.01]
    assert dev0[0]["start_wall_ns"] == wall0 + 600 * MS   # after scatter.3 ends at 700 ms


def test_without_a_mark_the_window_is_first_to_last_event():
    planes = synthetic()[1:]
    out = T.reduce_planes(planes, "tpu")
    assert out["clock"] == "trace" and out["window_s"] == pytest.approx(0.9)  # 50 -> 950 ms
    assert out["busy_s"][0] == pytest.approx(0.2)
    assert all(g["start_wall_ns"] is None for g in out["gaps"])


def test_a_trace_with_no_device_says_so():
    assert T.reduce_planes(synthetic()[:1], "tpu")["devices"] == 0


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*.xplane.pb"))))
def test_recorded_trace(path):
    """A slice recorded by the harness: tpu-*.xplane.pb on the chip (PR 22),
    cpu-*.xplane.pb by the CPU rehearsal."""
    platform = os.path.basename(path).split("-")[0]
    out = T.reduce_file(path, platform)
    assert out["devices"] >= 1 and out["window_s"] > 0
    assert 0 < sum(out["busy_s"]) <= out["devices"] * out["window_s"]
    assert out["ops"] and all(s > 0 for _n, s in out["ops"])
    assert len(out["gaps"]) <= T.TOP
    assert all(a["seconds"] >= b["seconds"] for a, b in zip(out["gaps"], out["gaps"][1:]))
    if platform == "tpu":
        assert out["programs"] and out["program_seconds"] > 0
        names = [d["plane"] for d in T.describe(path)]
        assert "/device:TPU:0" in names


def test_the_recorded_chip_slice_reduces_to_what_was_seen():
    """benchmark/tests/data/tpu-bank-bulk-slice.xplane.pb: 300 ms after the
    mark of a traced bank-bulk run on the v5e (PR 22, seed 103), the device
    lines and the mark only.  The chip ran the bank probe back to back: one
    13.6 ms gap while the slice began, then 7 ms programs 7 us apart."""
    path = os.path.join(DATA, "tpu-bank-bulk-slice.xplane.pb")
    wall0 = 1_790_000_000_000_000_000
    out = T.reduce_file(path, "tpu", wall0, wall0 + 300 * MS)
    assert out["clock"] == "wall" and out["window_s"] == pytest.approx(0.3)
    assert out["busy_s"] == pytest.approx([0.285810125])
    name, seconds = out["programs"][0]
    assert name == "jit_bloom_bank_contains_packed_bits" and seconds == pytest.approx(0.285665765)
    assert out["ops"][0][0].startswith("%fusion = u8[802816]")  # the gather over the plane
    first, second = out["gaps"][:2]
    assert first["seconds"] == pytest.approx(0.013579456) and first["start_wall_ns"] == wall0
    assert second["seconds"] < 1e-5

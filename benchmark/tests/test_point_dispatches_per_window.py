"""``point.dispatches_per_window``: the reader on hand-made scrapes, on a live
server's one window, and in the traced rehearsal of ``bf-200c``, the one
cell its manifest entry lists."""
import pytest

from benchmark.tests.test_rehearse import ROOT, cells, rehearse
from benchmark.tests.test_spans import Obs, reader

NAME = "point.dispatches_per_window"
DISPATCHES, WINDOWS = "rtpu_point_dispatches_total", "rtpu_point_windows_total"


def scrapes(before, after):
    obs = Obs()
    obs.metrics_before, obs.metrics_after = before, after
    return obs


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                                   # a program without the series
    ({DISPATCHES: 5.0}, {DISPATCHES: 9.0}),                     # without the windows' series
    ({DISPATCHES: 5.0, WINDOWS: 5.0}, {DISPATCHES: 5.0, WINDOWS: 5.0}),  # no window served
])
def test_nothing_to_read_is_no_value(before, after):
    assert reader(NAME)(scrapes(before, after)) is None


def test_it_is_what_the_window_added():
    obs = scrapes({DISPATCHES: 100.0, WINDOWS: 100.0}, {DISPATCHES: 101.0, WINDOWS: 101.0})
    assert reader(NAME)(obs) == 1.0
    obs = scrapes({DISPATCHES: 0.0, WINDOWS: 0.0}, {DISPATCHES: 38.0, WINDOWS: 20.0})
    assert reader(NAME)(obs) == pytest.approx(1.9)


def _metrics(conn) -> dict:
    out = {}
    for line in bytes(conn.execute("METRICS")).decode().splitlines():
        if line and not line.startswith("#"):
            name, _, val = line.rpartition(" ")
            out[name] = float(val)
    return out


def test_one_window_of_a_live_server_reads_one():
    from redisson_tpu.net.client import Connection
    from redisson_tpu.server.server import ServerThread

    with ServerThread(port=0, workers=2) as st:
        conn = Connection(st.server.host, st.server.port, timeout=60.0)
        try:
            conn.execute("BF.RESERVE", "bf", "0.01", 1000)
            before = _metrics(conn)
            assert conn.execute("BF.ADD", "bf", b"memtier-1") == 1
            after = _metrics(conn)
        finally:
            conn.close()
    assert after[WINDOWS] - before[WINDOWS] == 1.0
    assert reader(NAME)(scrapes(before, after)) == 1.0


def test_the_entry_lists_the_cell_and_its_rehearsal_reports_it():
    entry = next(m for m in cells()["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "dispatches", "better": "lower",
                     "source": "program_counter", "layer": "dispatch", "moves": "ops_per_s",
                     "workloads": ["bf-200c"]}
    last, detail = rehearse(ROOT, "bf-200c", 1, seconds="2")
    assert detail["failures"] == [] and last["failed"] == 0
    assert last["metrics"][NAME]["value"] == 1.0

"""``coalesce.fused_cmd_share``: the reader on hand-made scrapes, its entry in
the manifest, and the rehearsal of ``fanout-4`` reporting it."""
import pytest

from benchmark.tests.test_rehearse import ROOT, cells, rehearse
from benchmark.tests.test_spans import Obs, reader

NAME = "coalesce.fused_cmd_share"
FUSED, OFFERED = "rtpu_coalesce_cmds_fused_total", "rtpu_coalesce_cmds_offered_total"


def scrapes(before, after):
    obs = Obs()
    obs.metrics_before, obs.metrics_after = before, after
    return obs


def test_the_share_is_what_the_window_added():
    # before the window: the populate frames (their SETBITSB create their
    # bitsets: per record); inside it: 100 frames of 326 commands, 70 fused
    obs = scrapes({FUSED: 1_024.0, OFFERED: 4_096.0},
                  {FUSED: 1_024.0 + 7_000.0, OFFERED: 4_096.0 + 32_600.0})
    assert reader(NAME)(obs) == pytest.approx(100.0 * 70 / 326)
    obs = scrapes({FUSED: 0.0, OFFERED: 0.0}, {FUSED: 326.0, OFFERED: 326.0})
    assert reader(NAME)(obs) == 100.0


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                                     # the parent: no such series
    ({OFFERED: 5.0}, {OFFERED: 9.0}),                             # one of the two
    ({FUSED: 5.0, OFFERED: 8.0}, {FUSED: 5.0, OFFERED: 8.0}),     # a window that offered nothing
])
def test_nothing_to_read_is_no_value(before, after):
    assert reader(NAME)(scrapes(before, after)) is None


def test_the_manifest_lists_it_for_fanout_4_and_the_rehearsal_reports_it():
    entry = next(m for m in cells()["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "coalescer", "moves": "ops_per_s", "workloads": ["fanout-4"]}
    assert entry in cells()["per_layer"]  # later PRs append after it
    last, detail = rehearse(ROOT, "fanout-4", 1, seconds="3")
    assert detail["failures"] == [] and last["failed"] == 0
    share = last["metrics"][NAME]
    # the by-verb frame's 326 commands all have a stacked form
    assert share["unit"] == "%" and 90.0 <= share["value"] <= 100.0

"""``kernel.padded_row_share``: the reader on hand-made scrapes, and the
rehearsal of every cell — reported where the manifest lists it (``bank-bulk``,
``hll-stream``), absent where it does not (``bank-point``)."""
import pytest

from benchmark.tests.test_rehearse import ROOT, cells, rehearse
from benchmark.tests.test_spans import Obs, reader

NAME = "kernel.padded_row_share"
VALID, ISSUED = "rtpu_kernel_rows_valid_total", "rtpu_kernel_rows_issued_total"


def scrapes(before, after):
    obs = Obs()
    obs.metrics_before, obs.metrics_after = before, after
    return obs


def test_the_share_is_what_the_window_added():
    # before the window: a 10 M-key fill in one-shot buckets; inside it: 40
    # flushes of 100,000 keys in 49 chunks of 2,048
    obs = scrapes({VALID: 10_000_000.0, ISSUED: 11_468_800.0},
                  {VALID: 14_000_000.0, ISSUED: 11_468_800.0 + 40 * 100_352})
    assert reader(NAME)(obs) == pytest.approx(100.0 * 352 / 100_352)
    # one-shot: the bucket
    obs = scrapes({VALID: 0.0, ISSUED: 0.0}, {VALID: 100_000.0, ISSUED: 114_688.0})
    assert reader(NAME)(obs) == pytest.approx(12.807, abs=1e-3)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                                     # a program without the series
    ({VALID: 5.0}, {VALID: 9.0}),                                 # one of the two
    ({VALID: 5.0, ISSUED: 8.0}, {VALID: 5.0, ISSUED: 8.0}),       # a window that issued no row
])
def test_nothing_to_read_is_no_value(before, after):
    assert reader(NAME)(scrapes(before, after)) is None


@pytest.mark.parametrize("cell", ["bank-bulk", "hll-stream", "bank-point"])
def test_the_rehearsal_reports_it_where_the_manifest_lists_it(cell):
    entry = next(m for m in cells()["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "ops_per_s",
                     "workloads": ["bank-bulk", "hll-stream"]}
    last, detail = rehearse(ROOT, cell, 1)
    assert detail["failures"] == [] and last["failed"] == 0
    if cell in entry["workloads"]:
        share = last["metrics"][NAME]
        assert share["unit"] == "%" and 0.0 <= share["value"] < 100.0
    else:
        assert NAME not in last["metrics"]

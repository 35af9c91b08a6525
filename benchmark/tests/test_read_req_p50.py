"""``client.read_req_p50_ms``: the reader on hand-made requests, its entry in
the manifest, and a traced rehearsal of ``hll-stream`` reporting it."""
import numpy as np
import pytest

from benchmark.tests.test_rehearse import ROOT, cells, rehearse
from benchmark.tests.test_spans import Obs, reader

NAME = "client.read_req_p50_ms"


def requests(latency_ms, ops, params):
    obs = Obs()
    obs.latency_ms = np.array(latency_ms, np.float64)
    obs.request_ops = np.array(ops, np.float64)
    obs.params = params
    return obs


def test_it_is_the_median_of_the_read_frames_alone():
    # nine adds of 100,000 pairs at 8 ms, reads of 1,000 pairs at 30, 50 and 90 ms
    obs = requests([8.0] * 9 + [30.0, 90.0, 50.0], [100000] * 9 + [1000] * 3,
                   {"pairs_per_add": 100000, "pairs_per_read": 1000})
    assert reader(NAME)(obs) == 50.0
    assert float(np.median(obs.latency_ms)) == 8.0  # what req_p50_ms reads of the same window


@pytest.mark.parametrize("obs", [
    requests([5.0, 6.0], [16, 16], {"keys_per_request": 16}),             # a mix with no reads
    requests([8.0, 9.0], [100000, 100000], {"pairs_per_read": 1000}),     # a window with none
    requests([], [], {"pairs_per_read": 1000}),                           # nothing answered
])
def test_nothing_to_read_is_no_value(obs):
    assert reader(NAME)(obs) is None


def test_the_manifest_lists_it_for_hll_stream_and_the_traced_rehearsal_reports_it():
    entry = next(m for m in cells()["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "ms", "better": "lower", "source": "host_clock",
                     "layer": "client", "moves": "req_p50_ms", "workloads": ["hll-stream"]}
    last, detail = rehearse(ROOT, "hll-stream", 1, seconds="3")
    # every merged pair and every counter verified, as in an untraced run
    assert detail["failures"] == [] and last["failed"] == 0 and last["attempted"] > 0
    assert detail["client"]["checked_reads"][0] > 0
    read = last["metrics"][NAME]
    assert read["unit"] == "ms" and read["value"] > 0

"""`fanout-4` from the real manifest, rehearsed on the CPU with four forced
host devices: the 8-master mixed fan-out deployment (`cluster-mixed-8m`)
under `fanout-64-by-verb`, traced.  The cell is in `BENCHMARK.json` since the
server runs a bounded, pre-compiled set of programs whatever a frame's
composition: the rehearsal must end with no failure at all — above all no
program compiled inside the window."""
import json
import os

from test_rehearse import ROOT, rehearse

WAITED = ("coalesce.cmds_per_kernel", "ioplane.stage_wait_ms", "wire.frames_per_request",
          "device.idle_share_min", "device.idle_share_max")
NEW = ("coalesce.padded_plane_share", "ioplane.padded_fetch_share")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_fanout_4_is_a_cell_of_the_manifest():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == "fanout-4")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cluster-mixed-8m", "fanout-64-by-verb", 4)
    config = next(c for c in m["configs"] if c["name"] == "cluster-mixed-8m")
    assert config["file"] == "benchmark/configs/cluster-mixed-8m.json"
    assert config["reduced"] == ["masters"]
    with open(os.path.join(ROOT, config["file"])) as fh:
        assert json.load(fh)["server_flags"] == ["--devices", "all", "--workers", "8"]
    e2e = {x["name"] for x in m["end_to_end"] if "fanout-4" in x.get("workloads", ["fanout-4"])}
    assert e2e == {"ops_per_s", "req_p50_ms", "setup_s"}
    listed = {x["name"]: x for x in m["per_layer"] if "fanout-4" in x.get("workloads", [])}
    for name in WAITED + NEW:
        assert listed[name]["moves"] == "ops_per_s" and listed[name]["workloads"] == ["fanout-4"]


def test_fanout_4_rehearses_with_no_program_compiled_in_the_window():
    last, detail = rehearse(ROOT, "fanout-4", 1, seconds="3")
    assert last["correct"] is False and last["device"] == {**last["device"], "platform": "cpu",
                                                           "count": 4}
    assert last["failed"] == 0 and last["attempted"] > 0
    assert detail["failures"] == [], detail["failures"]
    assert detail["client"]["checked_in_full"] > 0  # sampled tenants, bit for bit
    m = manifest()
    allowed = {x["name"] for x in m["per_layer"] if "fanout-4" in x.get("workloads", ["fanout-4"])}
    assert set(last["metrics"]) <= allowed
    # the CPU's profile has one plane for all host devices: no idlest and
    # busiest chip, so those two readers return nothing here (the chip's do)
    assert set(WAITED[:3] + NEW) <= set(last["metrics"])
    assert not set(WAITED[3:]) & set(last["metrics"])
    assert "breakdown" in last and last["device"]["busy_s"] > 0
    assert last["metrics"]["coalesce.cmds_per_kernel"]["value"] >= 1
    assert last["metrics"]["wire.frames_per_request"]["value"] > 0
    for name in NEW:
        assert 0 <= last["metrics"][name]["value"] < 100
    # every metric that moves what the cell reports and lists no cells (the
    # CPU backend reports no memory statistics: no HBM peak here)
    unscoped = {x["name"] for x in m["per_layer"] if "workloads" not in x}
    unscoped.discard("device.hbm_peak_mb")
    assert unscoped <= set(last["metrics"]), unscoped - set(last["metrics"])

"""``--rehearse-cpu`` for every cell, and a further cell added by entries alone.

The rehearsal is the script at tiny size on the CPU (four forced host
devices for a four-chip cell): it must run to its end, check every reply,
and say plainly that it is not a measurement."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def rehearse(root, cell, trace, seconds="2"):
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", seconds, "--trace", str(trace), "--rehearse-cpu"],
        capture_output=True, text=True, timeout=600, cwd=root)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    detail = json.loads(next(ln for ln in lines if ln.startswith("DETAIL "))[7:])
    return json.loads(lines[-1]), detail


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("cell,trace", [("bank-bulk", 1), ("bank-point", 1),
                                        ("hll-stream", 0)])
def test_cell_rehearses(cell, trace):
    last, detail = rehearse(ROOT, cell, trace)
    m = cells()
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert detail["failures"] == [] and last["failed"] == 0 and last["attempted"] > 0
    chips = next(w["chips"] for w in m["workloads"] if w["name"] == cell)
    assert last["device"]["count"] == chips
    kind = "per_layer" if trace else "end_to_end"
    allowed = {x["name"] for x in m[kind] if cell in x.get("workloads", [cell])}
    assert set(last["metrics"]) <= allowed and last["metrics"]
    if trace:
        assert "breakdown" in last and last["device"]["busy_s"] > 0
        assert {"device.idle_share", "dispatch.self_ms", "client.traced_req_p50_ms"} \
            <= set(last["metrics"])
    else:
        assert set(last["metrics"]) == allowed  # every end-to-end metric of the cell


def test_a_further_cell_is_entries_in_the_manifest(tmp_path):
    """What a later PR does, shown on the cell that waits for the program
    (PERF.md section 7, first row): `fanout-4`, whose configuration, traffic
    file, generator and readers are here already.  Its entries in a copy of
    the manifest — one in ``configs[]``, one in ``workloads[]``, its readers
    in ``per_layer[]``, its name on the scoped metrics it reports — and no
    edit to any file there."""
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for name in ("redisson_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), root / name)
    m = cells()
    m["configs"].append({"name": "cluster-mixed-8m", "source": "test", "reduced": ["masters"],
                         "file": "benchmark/configs/cluster-mixed-8m.json", "why": "test"})
    m["workloads"].append({"name": "fanout-4", "config": "cluster-mixed-8m",
                           "traffic": "fanout-64-by-verb", "chips": 4, "why": "test"})
    for x in m["end_to_end"] + m["per_layer"]:
        if "workloads" in x and "bank-bulk" in x["workloads"]:
            x["workloads"].append("fanout-4")
    waiting = ("coalesce.cmds_per_kernel", "ioplane.stage_wait_ms", "wire.frames_per_request",
               "device.idle_share_min", "device.idle_share_max")
    m["per_layer"] += [{"name": n, "unit": "", "better": "lower", "source": "program_span",
                        "layer": n.split(".")[0], "moves": "ops_per_s",
                        "workloads": ["fanout-4"]} for n in waiting]
    with open(root / "BENCHMARK.json", "w") as fh:
        json.dump(m, fh)
    last, detail = rehearse(str(root), "fanout-4", 1)
    assert last["device"]["count"] == 4 and last["failed"] == 0 and last["attempted"] > 0
    # (the CPU's profile has one plane for all host devices: no min and max)
    assert set(waiting[:3]) <= set(last["metrics"]) <= {x["name"] for x in m["per_layer"]}
    assert last["metrics"]["coalesce.cmds_per_kernel"]["value"] > 1
    # every reply is right; what keeps the cell out of the manifest is that the
    # server compiles a program for every new composition of a frame
    assert all("compiled inside the window" in f for f in detail["failures"]), detail["failures"]


def test_a_checkout_without_the_program_gives_no_result(tmp_path):
    root = tmp_path / "bare"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    out = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload", "bank-bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=str(root),
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_without_a_tpu_there_is_no_result():
    """Not rehearsing, on a machine with no chip: non-zero exit, no line."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "bank-bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""

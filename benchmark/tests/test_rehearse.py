"""``--rehearse-cpu`` for every cell, and a further cell added by entries and a data file alone.

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
    """What a later PR does, shown on a cell the manifest does not have: a
    traffic mix that is a new data file (the stream mix with three
    connections that read every fifth frame), one entry in ``workloads[]``,
    its name on the scoped metrics it reports — and no edit to any file
    there."""
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for name in ("redisson_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), root / name)
    m = cells()
    assert "hll-stream-3c" not in {w["name"] for w in m["workloads"]}
    with open(root / "benchmark" / "traffic" / "stream-add-merge.json") as fh:
        mix = json.load(fh)
    mix["rehearse"].update(connections=3, processes=3)
    mix.update(read_every=5)
    with open(root / "benchmark" / "traffic" / "stream-3c-read-5th.json", "w") as fh:
        json.dump(mix, fh)
    m["workloads"].append({"name": "hll-stream-3c", "config": "hll-10k",
                           "traffic": "stream-3c-read-5th", "chips": 1, "why": "test"})
    for x in m["end_to_end"] + m["per_layer"]:
        if "hll-stream" in x.get("workloads", []):
            x["workloads"].append("hll-stream-3c")
    with open(root / "BENCHMARK.json", "w") as fh:
        json.dump(m, fh)
    for trace, kind in ((0, "end_to_end"), (1, "per_layer")):
        last, detail = rehearse(str(root), "hll-stream-3c", trace, seconds="3")
        assert detail["cell"] == "hll-stream-3c" and last["device"]["count"] == 1
        assert detail["failures"] == [] and last["failed"] == 0 and last["attempted"] > 0
        allowed = {x["name"] for x in m[kind] if "hll-stream-3c" in x.get("workloads",
                                                                          ["hll-stream-3c"])}
        assert set(last["metrics"]) <= allowed
        if trace:
            assert {"client.read_req_p50_ms", "kernel.device_ms_per_mop"} <= set(last["metrics"])
        else:
            assert set(last["metrics"]) == allowed
            # three connections, each closing on a read after reads every fifth frame
            assert detail["client"]["checked_reads"][0] >= 3


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

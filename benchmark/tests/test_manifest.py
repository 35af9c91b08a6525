"""BENCHMARK.json against the rules a later PR's entry must keep too."""
import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_keys_and_names():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark"] and 1 <= m["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in m[k]]
    assert all(NAME.fullmatch(n) for n in names), names
    assert len(set(names)) == len(names)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    for part in m["command"]:
        assert not part.startswith("/") and ".." not in part
    for c in m["configs"]:  # the driver refuses a longer source or why before any run
        assert 1 <= len(c["source"]) <= 200 and c["source"].isprintable(), c["name"]
        assert len(c["why"]) <= 200, c["name"]


def test_every_workloads_files_exist_and_why_fits():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    pairs = set()
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cfg = configs[w["config"]]
        assert cfg["file"].startswith("benchmark/") and os.path.isfile(os.path.join(ROOT, cfg["file"]))
        traffic = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        assert os.path.isfile(traffic), traffic
        with open(traffic) as fh:
            params = json.load(fh)
        assert os.path.isfile(os.path.join(BENCH, "generators", params["generator"] + ".py"))
        with open(os.path.join(ROOT, cfg["file"])) as fh:
            assert json.load(fh)["chips"] == w["chips"]
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(m["workloads"])
    assert {w["config"] for w in m["workloads"]} == set(configs)  # every config has a cell
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 2)


def test_metrics_are_reported_where_what_they_move_is():
    m = manifest()
    cells = [w["name"] for w in m["workloads"]]
    e2e = {x["name"]: set(x.get("workloads", cells)) for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == set(cells)
    for x in m["end_to_end"]:
        assert x["source"] in ("host_clock", "device_trace") and 0.01 <= x["bound"] <= 0.25
    for cell in cells:  # setup_s and at least one other end-to-end metric
        assert sum(cell in where for where in e2e.values()) >= 2
    for x in m["per_layer"]:
        assert x["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]
        assert x["moves"] in e2e, x
        # the contract's rule: reported only where what it moves is.  A metric
        # whose prediction differs by cell lists the cells where it holds.
        assert set(x.get("workloads", cells)) <= e2e[x["moves"]], x
    for cell in cells:
        assert any(cell in x.get("workloads", cells) for x in m["per_layer"])
    # `moves` is a prediction: one value for every metric would predict nothing
    assert {x["moves"] for x in m["per_layer"]} == set(e2e)

#!/usr/bin/env python3
"""Repeated runs of cells in one call, and what they say of the spread.

    python benchmark/tools/measure.py --cell bank-bulk --sets 2 --runs 6 [--traced 1]
    python benchmark/tools/measure.py --cell bank-point --sweep 1000,2000,4000

Runs ``benchmark/run.py`` as the driver does — one process a run, a new
``--seed`` each run of a set, the same seeds in every set — and prints, for
every end-to-end metric, each set's median and spread (distance between the
quartiles of ``statistics.quantiles(values, n=4)`` over the median) the way
the builder's contract measures them; bounds are set from the wider spread.
``--sweep`` runs an open-loop cell once at each total rate (the knee sweep:
p50, p99, how late the generator ran, backlog at the end).  Everything is
also written to ``chiprun_out/benchmark/measure-<cell>-<tag>.json``.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def one_run(cell: str, seed: int, seconds: float, trace: int, extra=()) -> dict:
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace), *extra]
    t0 = time.monotonic()
    out = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
    wall = time.monotonic() - t0
    lines = out.stdout.strip().splitlines()
    if out.returncode != 0 or not lines:
        print(out.stderr[-3000:], file=sys.stderr)
        return {"seed": seed, "rc": out.returncode, "wall_s": wall}
    last = json.loads(lines[-1])
    detail = next((json.loads(ln[len("DETAIL "):]) for ln in lines
                   if ln.startswith("DETAIL ")), {})
    row = {"seed": seed, "rc": 0, "wall_s": wall, "trace": trace, "last": last,
           "setup": detail.get("setup"), "client": detail.get("client"),
           "failures": detail.get("failures")}
    print(f"  seed {seed} trace {trace}: wall {wall:.1f}s correct={last['correct']} "
          f"failed={last['failed']}/{last['attempted']} "
          + " ".join(f"{k}={v['value']:.6g}" for k, v in last["metrics"].items()),
          flush=True)
    if detail.get("failures"):
        print("    failures:", detail["failures"][:4], flush=True)
    return row


def spread(values) -> float:
    if len(values) < 2:
        return float("nan")
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return float((q3 - q1) / med) if med else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=0, help="traced runs after the sets")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--sweep", default=None, help="comma-separated total rates")
    ap.add_argument("--tag", default="run")
    ap.add_argument("--extra", action="append", default=[],
                    help="passed through to run.py (e.g. --extra=--keep-trace)")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    seconds = args.seconds or manifest["run_seconds"]
    record = {"cell": args.cell, "seconds": seconds, "sets": [], "traced": [], "sweep": []}
    seed = args.seed0
    if args.sweep:
        for rate in args.sweep.split(","):
            print(f"rate {rate}/s", flush=True)
            row = one_run(args.cell, seed, seconds, 0, ["--set", f"rate={rate}", *args.extra])
            row["rate"] = float(rate)
            record["sweep"].append(row)
            seed += 1
    else:
        for s in range(args.sets):
            print(f"set {s + 1}", flush=True)
            rows, seed = [], args.seed0
            for _ in range(args.runs):
                rows.append(one_run(args.cell, seed, seconds, 0, args.extra))
                seed += 1
            record["sets"].append(rows)
        seed = args.seed0 + args.runs
        for _ in range(args.traced):
            record["traced"].append(one_run(args.cell, seed, seconds, 1, args.extra))
            seed += 1
        names = sorted({k for rows in record["sets"] for r in rows if r["rc"] == 0
                        for k in r["last"]["metrics"]})
        summary = {}
        for name in names:
            per_set = []
            for rows in record["sets"]:
                vals = [r["last"]["metrics"][name]["value"] for r in rows if r["rc"] == 0]
                # the first run of a checkout compiles: its set-up is recorded apart
                if name == "setup_s" and rows is record["sets"][0]:
                    vals = vals[1:]
                if vals:
                    per_set.append({"median": statistics.median(vals), "spread": spread(vals),
                                    "values": vals})
            summary[name] = per_set
            print(f"{name}: " + "; ".join(
                f"set {i + 1} median {p['median']:.6g} spread {100 * p['spread']:.2f}%"
                for i, p in enumerate(per_set)), flush=True)
        record["summary"] = summary
    out = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"measure-{args.cell}-{args.tag}.json"), "w") as fh:
        json.dump(record, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

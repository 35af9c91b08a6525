"""Perf regression gate (ISSUE 2 satellite): compare a fresh bench.py run
against a recorded one, per config.

A headline that slides a few percent a round goes unnoticed until someone
is forced to look; this gate makes the slide impossible to miss.  It is the
pre-commit perf ritual (README "Performance"): run bench.py on the chip,
feed the JSON here, commit only when the gate is green or the miss is
explicitly traded out in ROADMAP.

Usage:
  python tools/perf_gate.py --fresh out.json --baseline prev.json
  python bench.py | tee out.txt; python tools/perf_gate.py --fresh out.txt --baseline prev.json
  python tools/perf_gate.py --run --baseline prev.json   # runs bench.py itself

Inputs accept either the raw bench.py JSON line (possibly embedded in other
stdout) or a recorded wrapper ({"parsed": {...}}).  No record of current
code is checked in, so --baseline is required in practice (without it the
gate looks for BENCH_r*.json in the repo root and exits when none exists).

Gate rule: exit nonzero on a >5% drop (--threshold) in any GATED metric:
the HEADLINE (windowed bank contains/s), CONFIG5 (cluster mixed ops/s),
CONFIG2 flush p99 ms (lower is better — the latency floor the overlap
plane of ISSUE 3 attacks), and CONFIG4 cold entries/s.  Every other
tracked metric prints in the regression table and flags WARN on a drop —
visible, but advisory (run-to-run variance on the secondary configs is
real; the gated numbers are windowed/best-of or percentile-stable).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (label, extractor-path, higher_is_better, gated)
# Gated set (exit nonzero on a >threshold regression): the windowed headline,
# config5, and — since the overlap plane (ISSUE 3) attacked the flush-latency
# floor — config2 flush p99 and the config4 COLD rate, so the latency the
# plane recovered cannot silently regress either.
METRICS = [
    ("headline bank contains/s", ("value",), True, True),
    ("config5 cluster mixed ops/s", ("details", "config5_cluster_mixed_ops_per_sec"), True, True),
    # config5p (ISSUE 6): the multi-process 8-master number — the only
    # cluster metric with no shared GIL.  Gated; on its FIRST appearance
    # (baseline has no config5p) the row reads n/a and passes — the fresh
    # run becomes the recorded baseline for the next round to defend.
    ("config5p cluster-proc mixed ops/s", ("details", "config5p_cluster_proc_ops_per_sec"), True, True),
    # config5d (ISSUE 8): ONE server owning the local device mesh — the
    # device-sharded throughput AND the 1-vs-N-device speedup ratio are both
    # gated (n/a-pass on first sight, >threshold relative drop after): a
    # regression in the ratio means the per-device lanes stopped
    # overlapping even if raw throughput moved for other reasons.
    ("config5d device-sharded ops/s", ("details", "config5d_device_sharded_ops_per_sec"), True, True),
    ("config5d speedup vs 1 device", ("details", "config5d_speedup_vs_1dev"), True, True),
    ("config1 single contains/s", ("details", "config1_single_filter_contains_per_sec"), True, False),
    ("config2 flush p99 ms", ("details", "config2_flush_p99_ms"), False, True),
    ("config3 hll add/s", ("details", "config3_hll_add_per_sec"), True, False),
    ("config3 hll merge pairs/s", ("details", "config3_hll_merge_pairs_per_sec"), True, False),
    ("config4 mapreduce entries/s", ("details", "config4_mapreduce_entries_per_sec"), True, False),
    ("config4 mapreduce COLD entries/s", ("details", "config4_mapreduce_cold_entries_per_sec"), True, True),
    # config6 (ISSUE 7): the tracking plane's server-op reduction at a 99%
    # read ratio.  Gated relative to baseline AND against an ABSOLUTE floor
    # (FLOORS below): reads must cost >=10x fewer server ops with tracking
    # on, every round, not merely "no worse than last round".
    ("config6 server-op reduction", ("details", "config6_server_op_reduction"), True, True),
    ("config6 tracked read ops/s", ("details", "config6_tracked_read_ops_per_sec"), True, False),
    # config6r (ISSUE 17): the read-scaling plane — 4-replica-vs-1-replica
    # read QPS ratio under the config5d CPU-replica occupancy convention
    # (auto-disarmed on a real TPU).  Gated relative (n/a-pass on first
    # sight) AND bound absolutely below: replicas must deliver >= 2.5x at
    # 4 replicas, and the p99 replica staleness under write traffic must
    # stay inside the CEILING — read scaling bought by serving stale data
    # is not read scaling.
    ("config6r read qps scaling", ("details", "config6r_read_qps_scaling"), True, True),
    ("config6r staleness p99 ms", ("details", "config6r_staleness_p99_ms"), False, False),
    # config2q (ISSUE 10): interactive tail latency under the hostile
    # mixed-tenant flood with the QoS scheduler armed, and the p99 fairness
    # ratio between equal-budget tenants.  Both gated relative to baseline
    # (n/a-pass on first sight) AND bound absolutely from first sight: the
    # fairness ratio by a 2x CEILING, the armed-vs-disarmed speedup by a
    # 1.2x floor (the scheduler must land interactive p99 materially below
    # the disarmed baseline on the same container, every round).
    ("config2q interactive p99 ms", ("details", "config2q_interactive_p99_ms"), False, True),
    ("config2q fairness p99 ratio", ("details", "config2q_fairness_p99_ratio"), False, True),
    ("config2q speedup vs no-qos", ("details", "config2q_interactive_speedup_vs_noqos"), True, False),
    # ISSUE 18: interactive p99 while a bulk tenant occupies the DEVICE
    # LANE, preemptible sub-windows + the per-class stream armed (gated
    # relative, lower-better); the armed-vs-no-preempt speedup and the
    # 2-node fleet fairness/admitted-ratio bind absolutely below.
    ("config2q preempt interactive p99", ("details", "config2q_preempt_interactive_p99_ms"), False, True),
    ("config2q cluster fairness ratio", ("details", "config2q_cluster_fairness_p99_ratio"), False, True),
    # config7 (ISSUE 11): device KNN throughput — gated relative
    # (n/a-pass on first sight, like every new config); the recall QUALITY
    # axis binds as an absolute floor below, not a relative row.
    ("config7 knn qps", ("details", "config7_knn_qps"), True, True),
    # config7 IVF leg (ISSUE 14): sub-linear cell-scored KNN at N=50k/d=128
    # — qps gated relative like the FLAT leg; its recall and its
    # speedup-vs-FLAT bind as absolute floors below, and the INT8 bank's
    # compression ratio as a ceiling (quality axes never gate relatively).
    ("config7 ivf knn qps", ("details", "config7_ivf_knn_qps"), True, True),
    # config7s (ISSUE 15): mesh-sharded KNN — row-parallel shard legs +
    # on-device top-k merge.  qps gated relative (n/a-pass first sight);
    # the recall floor and the 1-vs-n speedup floor bind absolutely below
    # (the speedup runs under the config5d CPU-replica occupancy model,
    # auto-disarmed on a real TPU).
    ("config7 sharded knn qps", ("details", "config7_sharded_knn_qps"), True, True),
    # config8 (ISSUE 20): tiered-HBM overcommit — zipf tenants at >=4x the
    # device budget served through demote-to-host + fault-in-on-first-touch.
    # Throughput gated relative (n/a-pass first sight); the hot-hit floor
    # and fault-in p99 ceiling bind absolutely below (the residency plane
    # may never buy throughput by thrashing or stalling).
    ("config8 overcommit ops/s", ("details", "config8_overcommit_ops_per_sec"), True, True),
    ("config8 hot hit ratio", ("details", "config8_hot_hit_ratio"), True, False),
    # observability (ISSUE 12): armed-vs-disarmed tracing throughput ratio
    # from tools/obs_overhead_bench.py — advisory relative row (n/a-pass
    # first sight); the binding bound is the ABSOLUTE floor below (armed
    # tracing may cost at most 3% on the config5-shaped mixed workload).
    ("obs armed tracing ratio", ("details", "obs_armed_overhead_ratio"), True, False),
]

# (label, extractor-path, minimum) — ABSOLUTE floors checked on the FRESH
# run alone: unlike the relative gate, a floor holds from the metric's first
# appearance (n/a only while the fresh run doesn't emit the metric at all).
FLOORS = [
    ("config6 server-op reduction >= 10x",
     ("details", "config6_server_op_reduction"), 10.0),
    ("config2q speedup vs no-qos >= 1.2x",
     ("details", "config2q_interactive_speedup_vs_noqos"), 1.2),
    # ISSUE 18: sub-windows + the per-class device stream must land the
    # interactive p99 materially below the whole-window no-preempt baseline
    # on the same container (the A/B runs under the config5d CPU-replica
    # occupancy model, auto-disarmed on a real TPU)
    ("config2q preempt speedup vs no-preempt >= 1.2x",
     ("details", "config2q_preempt_speedup_vs_nopreempt"), 1.2),
    # config7 recall@10 vs the float64 brute-force oracle: FLAT scoring is
    # exact in f32, so only rounding ties may differ — binding from first
    # sight (a recall drop means the kernel, not the workload, changed)
    ("config7 recall@10 >= 0.99",
     ("details", "config7_recall_at_10"), 0.99),
    # ISSUE 14: the sub-linear/compressed legs are only admissible while
    # their recall holds — floors bind from first sight so the speedup can
    # never be bought by silently giving up result quality
    ("config7 ivf recall@10 >= 0.97",
     ("details", "config7_ivf_recall_at_10"), 0.97),
    ("config7 ivf speedup vs FLAT >= 2x",
     ("details", "config7_ivf_speedup_vs_flat"), 2.0),
    ("config7 int8 recall@10 >= 0.95",
     ("details", "config7_int8_recall_at_10"), 0.95),
    # ISSUE 15: FLAT sharding is exact — the merge may cost ties only, so
    # the recall floor binds at the FLAT level from first sight; and the
    # row-parallel fan-out must actually WIN under the occupancy model
    # (>= 1.5x vs the same corpus on 1 shard) or the plane is overhead
    ("config7 sharded recall@10 >= 0.99",
     ("details", "config7_sharded_recall_at_10"), 0.99),
    ("config7 sharded speedup vs 1 shard >= 1.5x",
     ("details", "config7_sharded_speedup_vs_1shard"), 1.5),
    # armed tracing overhead (ISSUE 12): obs_overhead_bench.py's
    # armed/disarmed ops ratio — binds from first sight, n/a while absent
    ("obs armed tracing ratio >= 0.97",
     ("details", "obs_armed_overhead_ratio"), 0.97),
    # ISSUE 17: 4 replicas must actually absorb reads — >= 2.5x the
    # 1-replica read QPS on the zipf blob-read mix, from first sight
    ("config6r read qps scaling >= 2.5x",
     ("details", "config6r_read_qps_scaling"), 2.5),
    # ISSUE 20: the LRU clock must keep the zipf head device-resident —
    # >=90% of probe calls under 4x overcommit served with no fault-in
    ("config8 hot hit ratio >= 0.9",
     ("details", "config8_hot_hit_ratio"), 0.9),
    ("config8 overcommit ratio >= 4x",
     ("details", "config8_overcommit_ratio"), 4.0),
]

# (label, extractor-path, maximum) — ABSOLUTE ceilings, same first-sight
# discipline as FLOORS but bounding from above (lower is better).
CEILINGS = [
    ("config2q fairness p99 ratio <= 2x",
     ("details", "config2q_fairness_p99_ratio"), 2.0),
    # ISSUE 18: the fleet rebalance loop's two defended numbers on the
    # 2-node hostile mix — a tenant spraying every node is held to ~1x its
    # GLOBAL budget (without the loop the ratio sits near the node count),
    # and re-splitting the sprayer's budget must not starve either node's
    # interactive tenant (worst/best cross-node interactive p99)
    ("config2q cluster admitted ratio <= 1.5x",
     ("details", "config2q_cluster_admitted_ratio"), 1.5),
    ("config2q cluster fairness p99 <= 2x",
     ("details", "config2q_cluster_fairness_p99_ratio"), 2.0),
    # ISSUE 14: an INT8 bank must actually be compressed — quantized
    # device bytes at most 0.35x what f32 storage of the same rows costs
    ("config7 int8 bytes ratio <= 0.35x",
     ("details", "config7_int8_bytes_ratio"), 0.35),
    # ISSUE 17: p99 replica staleness (REPLSTATE receipt clock) through
    # the 4-replica read window with the writer active — replicas serving
    # reads must stay within the bounded-staleness contract's ballpark
    # (client-side bound in the bench is 2000ms; the sweep cadence plus
    # heartbeat keeps a healthy replica an order of magnitude fresher)
    ("config6r staleness p99 ms <= 1500",
     ("details", "config6r_staleness_p99_ms"), 1500.0),
    # ISSUE 20: a fault-in is one packed H2D plus (COLD) one verified spill
    # read — p99 must stay a bounded hiccup; anything near this ceiling
    # means promotion is rebuilding kernels or fighting the lane gate
    ("config8 fault-in p99 ms <= 250",
     ("details", "config8_fault_in_p99_ms"), 250.0),
]


def _extract(doc: dict, path: Tuple[str, ...]) -> Optional[float]:
    cur = doc
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    try:
        return float(cur)
    except (TypeError, ValueError):
        return None


def load_bench_doc(text: str) -> dict:
    """Parse a bench result from raw text: a BENCH_rNN wrapper, the bare
    bench.py JSON object, or stdout containing the JSON line."""
    try:
        doc = json.loads(text)
        if isinstance(doc, dict):
            if "parsed" in doc and isinstance(doc["parsed"], dict):
                return doc["parsed"]
            if "metric" in doc:
                return doc
    except json.JSONDecodeError:
        pass
    # scan line-wise for the bench JSON object (bench.py logs to stderr, but
    # callers often tee both streams into one file)
    for line in reversed(text.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            doc = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(doc, dict) and "metric" in doc:
            return doc
    raise SystemExit("no bench.py JSON result found in input")


def latest_baseline_path() -> str:
    paths = glob.glob(os.path.join(REPO, "BENCH_r*.json"))
    if not paths:
        raise SystemExit("no BENCH_r*.json baseline found in repo root")

    def round_no(p: str) -> int:
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    return max(paths, key=round_no)


def compare(baseline: dict, fresh: dict, threshold: float) -> Tuple[list, bool]:
    """Per-metric rows + overall gate verdict."""
    rows = []
    ok = True
    for label, path, higher, gated in METRICS:
        b = _extract(baseline, path)
        f = _extract(fresh, path)
        if b is None or f is None or b == 0:
            rows.append((label, b, f, None, "n/a"))
            continue
        delta = (f - b) / b if higher else (b - f) / b
        regressed = delta < -threshold
        status = "OK"
        if regressed:
            status = "FAIL" if gated else "WARN"
            if gated:
                ok = False
        elif delta < 0:
            status = "fail(soft)" if gated else "warn(soft)"
        rows.append((label, b, f, delta, status))
    for label, path, floor in FLOORS:
        f = _extract(fresh, path)
        if f is None:
            rows.append((label, floor, f, None, "n/a"))
            continue
        passed = f >= floor
        rows.append((label, floor, f, None, "OK" if passed else "FAIL"))
        if not passed:
            ok = False
    for label, path, ceiling in CEILINGS:
        f = _extract(fresh, path)
        if f is None:
            rows.append((label, ceiling, f, None, "n/a"))
            continue
        passed = f <= ceiling
        rows.append((label, ceiling, f, None, "OK" if passed else "FAIL"))
        if not passed:
            ok = False
    return rows, ok


def render(rows, threshold: float) -> str:
    out = [
        f"{'metric':<34} {'baseline':>14} {'fresh':>14} {'delta':>8}  verdict",
        "-" * 82,
    ]
    for label, b, f, delta, status in rows:
        bs = f"{b:,.0f}" if isinstance(b, float) else "-"
        fs = f"{f:,.0f}" if isinstance(f, float) else "-"
        ds = f"{delta*+100:+.1f}%" if delta is not None else "-"
        out.append(f"{label:<34} {bs:>14} {fs:>14} {ds:>8}  {status}")
    out.append("-" * 82)
    out.append(
        f"gate: >{threshold:.0%} regression in headline, config5, config5p, "
        "config5d (ops/s AND 1-vs-N speedup), config2 flush p99, config4 "
        "cold, config6 reduction, config6r read scaling, config2q "
        "interactive p99, config2q fairness, config2q preempt p99, "
        "config2q cluster fairness, config7 knn qps, config7 ivf "
        "qps, config7 sharded qps, or config8 overcommit ops/s fails; "
        "other drops are advisory "
        "(WARN); a metric absent from the baseline reads n/a and passes "
        "(recorded on first sight).  Absolute floors (config6 reduction "
        ">= 10x, config6r read scaling >= 2.5x, config2q speedup vs "
        "no-qos >= 1.2x, config2q preempt speedup vs no-preempt >= 1.2x, "
        "config7 recall@10 >= 0.99, ivf recall >= 0.97 + "
        "ivf speedup >= 2x, int8 recall >= 0.95, sharded recall >= 0.99 + "
        "sharded speedup vs 1 shard >= 1.5x, armed tracing ratio >= 0.97, "
        "config8 hot-hit >= 0.9 + overcommit >= 4x) "
        "and ceilings (config2q fairness <= 2x, config2q cluster admitted "
        "ratio <= 1.5x + cluster fairness <= 2x, int8 bytes ratio <= "
        "0.35x, config6r staleness p99 <= 1500ms, config8 fault-in p99 <= "
        "250ms) bind from first sight."
    )
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="bench.py regression gate")
    ap.add_argument("--fresh", help="file holding a fresh bench.py result")
    ap.add_argument("--run", action="store_true", help="run bench.py now")
    ap.add_argument("--baseline", help="baseline file (default: latest BENCH_r*.json)")
    ap.add_argument("--threshold", type=float, default=0.05)
    args = ap.parse_args(argv)

    if bool(args.fresh) == bool(args.run):
        ap.error("exactly one of --fresh/--run is required")
    if args.run:
        import subprocess

        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            stdout=subprocess.PIPE, text=True,
        )
        if p.returncode != 0:
            raise SystemExit(f"bench.py failed rc={p.returncode}")
        fresh = load_bench_doc(p.stdout)
    else:
        with open(args.fresh) as fh:
            fresh = load_bench_doc(fh.read())

    bpath = args.baseline or latest_baseline_path()
    with open(bpath) as fh:
        baseline = load_bench_doc(fh.read())

    rows, ok = compare(baseline, fresh, args.threshold)
    print(f"baseline: {os.path.basename(bpath)}")
    print(render(rows, args.threshold))
    print("GATE:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())

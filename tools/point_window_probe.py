"""What the host pays for one window of point commands, on one thread.

A window is what ``server/verbs/sketch.py point_window`` answers: the
single-item ``BF.EXISTS`` / ``BF.ADD`` commands of different connections
that waited for one record, here ``memtier-<n>`` items (uniform over
``--key-max``) against one filter reserved for ``--capacity`` items, as in
the ``bf-200c`` cell.  Two windows: ``--probes`` probes with ``--adds`` adds
(29 members by default), and one lone add.  Each is called directly, in
this thread, with nothing else running; over ``--rounds`` calls, the mean
of the thread's CPU time and the median of the wall clock are printed, whole
and by piece: the upload and dispatch, and the grouped fetch.  On a tree
that answers a window a verb at a time (no ``answer_window_async``), the
upload and dispatch piece is ``contains_each_async`` with
``add_in_order_async``, so the same file reads the tree before the window
became one program.  Host-Python costs: a CPU run reads the host, never the
device.

Run:  python tools/point_window_probe.py [--rounds 1000] [--capacity 10000000]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import types

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from redisson_tpu.client.objects.bloom import BloomFilter
from redisson_tpu.core.engine import Engine
from redisson_tpu.server.registry import LazyReply, gather_lazy_device_results
from redisson_tpu.server.verbs.sketch import point_window

NAME = "probe:bf"


def timed(fn, rounds: int, before) -> dict:
    """Over `rounds` calls of fn(*before()), in microseconds: the MEAN of
    the thread's CPU (a host may tick its thread clocks far coarser than a
    call; the mean of many calls is still right) and the median of the wall
    clock; `before` is not timed."""
    cpu, wall = [], []
    for _ in range(rounds):
        args = before()
        c0, w0 = time.thread_time(), time.perf_counter()
        fn(*args)
        cpu.append((time.thread_time() - c0) * 1e6)
        wall.append((time.perf_counter() - w0) * 1e6)
    return {"cpu_us": statistics.fmean(cpu), "wall_us": statistics.median(wall)}


def probe(engine, probes: int, adds: int, rounds: int, key_max: int, rng) -> list:
    """[(piece, {cpu_us, wall_us})] of a window of `probes` probes and
    `adds` adds, fresh items every call."""
    import jax

    server = types.SimpleNamespace(engine=engine)
    bf = BloomFilter(engine, NAME)
    verbs = ["BF.EXISTS"] * probes + ["BF.ADD"] * adds

    def items():
        return [b"memtier-%d" % n for n in rng.integers(1, key_max + 1, len(verbs))]

    def dispatch(its):
        if hasattr(bf, "answer_window_async"):
            return [bf.answer_window_async(its, [v == "BF.ADD" for v in verbs])]
        out = [bf.contains_each_async(its[:probes])] if probes else []
        return out + (bf.add_in_order_async(its[probes:]) if adds else [])

    def dispatched():
        flags = dispatch(items())
        jax.block_until_ready([f for f, _n in flags])
        return (flags,)

    def fetch(flags):
        gather_lazy_device_results([LazyReply(device=(f,), owed=n) for f, n in flags])

    for _ in range(3):  # every program of the window is compiled
        point_window(server, NAME, verbs, items())
    rows = [("point_window whole", timed(
        lambda its: point_window(server, NAME, verbs, its), rounds, lambda: (items(),)))]
    pieces = ("answer_window_async" if hasattr(bf, "answer_window_async")
              else "contains_each_async + add_in_order_async")
    rows.append((f"  upload + dispatch ({pieces})",
                 timed(dispatch, rounds, lambda: (items(),))))
    rows.append(("  grouped fetch of the flags", timed(fetch, rounds, dispatched)))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rounds", type=int, default=1000)
    ap.add_argument("--capacity", type=int, default=10_000_000)
    ap.add_argument("--key-max", type=int, default=10_000_000)
    ap.add_argument("--probes", type=int, default=26)
    ap.add_argument("--adds", type=int, default=3)
    args = ap.parse_args()
    import jax

    rng = np.random.default_rng(39)
    engine = Engine()
    try:
        assert BloomFilter(engine, NAME).try_init(args.capacity, 0.01)
        windows = {f"{args.probes} probes + {args.adds} adds": (args.probes, args.adds),
                   "1 add": (0, 1)}
        out = {w: probe(engine, p, a, args.rounds, args.key_max, rng)
               for w, (p, a) in windows.items()}
    finally:
        engine.shutdown()
    print(f"# one window, {args.rounds} calls, one thread, "
          f"device {jax.devices()[0].platform}")
    print("| window | piece | thread CPU us | wall us |")
    print("|---|---|---|---|")
    for w, rows in out.items():
        for name, t in rows:
            print(f"| {w} | {name} | {t['cpu_us']:.1f} | {t['wall_us']:.1f} |")
    print("PROBE " + json.dumps({w: dict(rows) for w, rows in out.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

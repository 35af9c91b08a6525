"""What one map task's word extraction costs on the device.

One task of the ``wc-live`` cell: ``--lines`` consecutive lines (15,625 =
1,000,000 entries / 64 tasks) drawn as ``benchmark/generators/wordcount.py``
draws the ``wordcount-1m`` corpus from ``--seed``, joined and space-padded as
``services/mapreduce.py _wc_map`` joins a task, in a buffer of the bucketed
size the map gives it (1,310,720 bytes, 196,608 word rows at the cell's
size).  ``kernels.wc_extract_words`` is compiled once, then called
``--rounds`` times on the staged buffer, each call waited for with
``block_until_ready``; the median and mean wall clock of a call are
printed, with the compile and the task's word count (checked against the
host's).  On a chip that is the task's device time plus one dispatch; a CPU
run times XLA's CPU backend and says nothing of the chip.  The file reads
any tree that has ``wc_extract_words``: copy it into that tree's ``tools/``.

Run:  python tools/wc_extract_probe.py [--rounds 200] [--seed 3] [--lines 15625]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import numpy as np

from benchmark.generators import wordcount as G


def task_text(seed: int, lines: int) -> bytes:
    """The first task's text of the seeded corpus, as _wc_map joins it."""
    with open(os.path.join(ROOT, "benchmark", "configs", "wordcount-1m.json")) as fh:
        sizes = json.load(fh)["sizes"]
    vocab = G.vocabulary(seed, sizes)
    ranks, at = G.draw_lines(np.random.default_rng(seed), sizes, G._zipf_cdf(sizes), lines)
    return " ".join(G.render(vocab, ranks, at)).encode() + b" "


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--lines", type=int, default=15625)
    args = ap.parse_args(argv)

    import jax

    from redisson_tpu.core import kernels as K

    text = task_text(args.seed, args.lines)
    b = K.bucket_size(len(text))
    words = len(text.split())
    eb = K.bucket_size(words)
    buf = np.full(b, 32, np.uint8)
    buf[: len(text)] = np.frombuffer(text, np.uint8)
    staged, base = K.stage(buf), K.valid_n(0)
    t0 = time.perf_counter()
    rows, count = jax.block_until_ready(K.wc_extract_words(staged, eb, base))
    compile_s = time.perf_counter() - t0
    if int(count) != words:
        raise SystemExit(f"the task's count {int(count)} is not the host's {words}")
    wall = []
    for _ in range(args.rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(K.wc_extract_words(staged, eb, base))
        wall.append((time.perf_counter() - t0) * 1e3)
    dev = jax.devices()[0]
    out = {"b": b, "eb": eb, "words": words, "rounds": args.rounds,
           "first_call_s": round(compile_s, 3),
           "median_ms": round(statistics.median(wall), 4),
           "mean_ms": round(statistics.fmean(wall), 4),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()

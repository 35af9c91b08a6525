"""The FLAT KNN kernel at each matmul precision, at the full size of the
``ann-gist-1m`` configuration, against that configuration's check.

The base (1,000,000 x 960 float32) and the 1,000 queries are drawn as
``benchmark/generators/ann_float.py`` draws them from ``--seed``; the
per-query tables are ``benchmark/reference_ann_float.py``'s.  The bank is
put on the device once, padded to the program's capacity (1,048,576 rows),
with its squared-norm plane; then, for each of ``HIGHEST`` (what the
program runs), ``HIGH`` and ``DEFAULT``, ``kernels.knn_flat_topk`` is
compiled afresh with that precision in place of ``kernels._EXACT`` (this
script's process only: the program is not switched), called over the
queries in stacked batches of ``--batch`` (the cell's 64), and each batch
timed with ``block_until_ready`` over ``--rounds`` calls.  Each query's ids
are then scored as a reply is (float32 L2 of each pair, ordered by that
score and the row) and judged by ``reference_ann_float.reply_failures``: the
share of queries refused, how near the replies came to the two limits (in
units of EPS, ``deviations``) and the share of hits whose place the
ordering changed are printed beside the median ms a call.

Beside them, the same check of a ranking from bfloat16 operands computed in
NumPy on the host (``tables(..., lower=True)``), which needs no chip.

On a chip the times are the program's device time plus a dispatch; a CPU run
(``JAX_PLATFORMS=cpu``, use ``--n`` to make it small) times XLA's CPU
backend and says nothing of the chip, and on the CPU the three precisions
are one.  The last line is a JSON object.

Run:  python tools/knn_precision_probe.py [--seed 3] [--batch 64] [--rounds 10] [--n N]
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

from benchmark import reference_ann_float as F
from benchmark.generators import ann_float as G


def replies(base: np.ndarray, queries: np.ndarray, ids: np.ndarray):
    """Each query's ids as a reply carries them: (ids, float32 scores as
    float64) ordered by (score, id), and how many hits that ordering moved."""
    diff = base[ids.reshape(-1)] - np.repeat(queries, ids.shape[1], 0)
    s = np.einsum("md,md->m", diff, diff, dtype=np.float32).reshape(ids.shape)
    order = np.lexsort((ids, s), axis=1)
    moved = int((order != np.arange(ids.shape[1])).sum())
    return (np.take_along_axis(ids, order, axis=1),
            np.take_along_axis(s, order, axis=1).astype(np.float64), moved)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--n", type=int, default=None, help="rows (default: the configuration's)")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "benchmark", "configs", "ann-gist-1m.json")) as fh:
        sizes = json.load(fh)["sizes"]
    if args.n:
        sizes["n"] = args.n
    k, dim = sizes["k"], sizes["dim"]
    out = {"seed": args.seed, "n": sizes["n"], "dim": dim, "queries": sizes["queries"]}
    t0 = time.monotonic()
    base = G._points(args.seed, sizes, 0, sizes["n"])
    queries = G._points(args.seed, sizes, 1, sizes["queries"])
    out["data_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    table = F.tables([(base, 0, None)], queries, k)
    out["reference_s"] = time.monotonic() - t0
    out["exact_rows"] = int(table["exact_rows"][0])
    t0 = time.monotonic()
    low = F.tables([(base, 0, None)], queries, k, lower=True)["ids"][:, :k]
    ids, scores, _moved = replies(base, queries, low)
    out["numpy_bf16_operands"] = {
        "refused_share": float(F.reply_failures(table, ids, scores, dim).mean()),
        "rank_dev_eps": F.deviations(table, ids, scores, dim)[1],
        "seconds": time.monotonic() - t0}

    import jax
    import jax.numpy as jnp

    from redisson_tpu.core import kernels as K

    dev = jax.devices()[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
    cap = 1 << max(16, int(np.ceil(np.log2(sizes["n"]))))
    host = np.zeros((cap, dim), np.float32)  # padded on the host: one 4 GB copy crosses
    host[: sizes["n"]] = base
    bank = jax.device_put(host)
    del host
    norms = jnp.sum(bank * bank, axis=1)
    bias = jnp.zeros((cap,), jnp.float32)
    n_rows = jnp.int32(sizes["n"])
    nb = -(-len(queries) // args.batch)
    padded = np.zeros((nb * args.batch, dim), np.float32)
    padded[: len(queries)] = queries
    batches = [jnp.asarray(padded[b * args.batch:(b + 1) * args.batch]) for b in range(nb)]
    exact = K._EXACT
    out["precisions"] = {}
    try:
        for name in ("HIGHEST", "HIGH", "DEFAULT"):
            K._EXACT = getattr(jax.lax.Precision, name)

            def fresh(*a):  # a new function: jit keys its traces by the function
                return K.knn_flat_topk.__wrapped__(*a)

            fn = jax.jit(fresh, static_argnums=(7, 8))
            t0 = time.monotonic()
            jax.block_until_ready(fn(bank, None, bias, norms, None, batches[0], n_rows, k, "L2"))
            compile_s = time.monotonic() - t0
            got, times = [], []
            for q in batches:
                res = fn(bank, None, bias, norms, None, q, n_rows, k, "L2")
                got.append(np.asarray(res[1]))
            for _ in range(args.rounds):
                t0 = time.monotonic()
                jax.block_until_ready(fn(bank, None, bias, norms, None, batches[0], n_rows,
                                         k, "L2"))
                times.append((time.monotonic() - t0) * 1e3)
            picked = np.concatenate(got)[: len(queries)].astype(np.int64)
            ids, scores, moved = replies(base, queries, picked)
            score_dev, rank_dev = F.deviations(table, ids, scores, dim)
            out["precisions"][name] = {
                "score_dev_eps": score_dev, "rank_dev_eps": rank_dev,
                "ms_per_call": statistics.median(times), "ms_min": min(times),
                "compile_s": compile_s,
                "refused_share": float(F.reply_failures(table, ids, scores, dim).mean()),
                "reordered_hit_share": moved / picked.size}
    finally:
        K._EXACT = exact
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()

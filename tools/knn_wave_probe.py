"""What the host pays for one frame of KNN searches, piece by piece, on one
thread (ISSUE 33 was sized with this).

An embedded ``ServerThread`` holds ``--docs`` documents of ``--dim`` floats
in a FLAT index; the frame is ``--frame`` commands in the wording of the
``ann-batch`` cell (``benchmark/generators/ann_flat.py search_command``).
The pieces are called directly, in this thread, with nothing else running,
and the median of ``--rounds`` is printed as one table: the RESP parse, the
plans, ``coalesce_knn_run`` whole, ``_force_lazies`` whole (after the device
is done: its wait is not the host's), the grouped fetch, ``finish``, the
encoders and ``_encode_frame``.  Host-Python costs: they do not depend on
the index's size, and a CPU run reads the host, never the device.  A row
whose function the tree does not have is left out, so the same file reads a
tree from before the wave shared its plan.

Run:  python tools/knn_wave_probe.py [--docs 4096] [--rounds 30]
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from redisson_tpu.net import resp
from redisson_tpu.net.client import Connection
from redisson_tpu.server import server as S
from redisson_tpu.server.registry import CommandContext, gather_lazy_device_results
from redisson_tpu.server.verbs import modules as M

INDEX, FIELD, K = "idx", "vector", 10


def search_command(blob: bytes) -> tuple:
    return ("FT.SEARCH", INDEX, f"*=>[KNN {K} @{FIELD} $BLOB]", "NOCONTENT",
            "SORTBY", f"__{FIELD}_score", "LIMIT", 0, K, "PARAMS", 2, "BLOB",
            blob, "DIALECT", 2)


def populate(conn, docs: int, dim: int, rng) -> None:
    made = conn.execute(
        "FT.CREATE", INDEX, "ON", "HASH", "PREFIX", 1, "doc:", "SCHEMA", FIELD,
        "VECTOR", "FLAT", 6, "TYPE", "FLOAT32", "DIM", dim,
        "DISTANCE_METRIC", "L2")
    if made not in (b"OK", "OK"):
        raise RuntimeError(f"FT.CREATE answered {made!r}")
    base = rng.standard_normal((docs, dim)).astype("<f4")
    for lo in range(0, docs, 512):
        conn.execute_many([("HSET", f"doc:{i}", FIELD, base[i].tobytes())
                           for i in range(lo, min(lo + 512, docs))])


def median_ms(fn, rounds: int, before=None) -> float:
    """Median over `rounds` calls of fn(before()), in ms; `before` is not
    timed."""
    out = []
    for _ in range(rounds):
        args = () if before is None else (before(),)
        t0 = time.perf_counter()
        fn(*args)
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def probe(server, frame: bytes, rounds: int) -> list:
    """[(piece, median ms)] for one frame's bytes; the last row sums the
    pieces a frame pays whole (the others are parts of them)."""
    import jax

    ctx = CommandContext(server)
    cmds = resp.RespParser().feed(frame)
    rows = [("RESP parse of the %d-byte frame" % len(frame),
             median_ms(lambda: resp.RespParser().feed(frame), rounds))]
    whole = [0]  # indexes of the rows the last one sums

    def plans():
        return [M._ft_knn_plan(server, ctx, c[1:], False) for c in cmds]

    rows.append((f"{len(cmds)} x _ft_knn_plan", median_ms(plans, rounds)))
    if hasattr(M, "_ft_wave_plans"):
        rows.append(("_ft_wave_plans (one plan a wave)", median_ms(
            lambda: M._ft_wave_plans(server, ctx, cmds), rounds)))

    def dispatched():
        replies = M.coalesce_knn_run(server, ctx, cmds)[0]
        jax.block_until_ready(replies[0].device)
        return list(replies)

    M.coalesce_knn_run(server, ctx, cmds)  # the query buckets' programs
    whole += [len(rows), len(rows) + 1]
    rows.append(("coalesce_knn_run whole (plans + queries + lazies + knn_async)",
                 median_ms(lambda: M.coalesce_knn_run(server, ctx, cmds), rounds)))
    rows.append(("_force_lazies whole (fetch + finish + encoders)", median_ms(
        lambda results: S._force_lazies(results, server), rounds, dispatched)))
    rows.append(("  grouped fetch of the frame's lazies",
                 median_ms(gather_lazy_device_results, rounds, dispatched)))

    plan = plans()[0]
    queries = np.concatenate([p["q"] for p in plans()])
    svc = M._ft(server)

    def knn(**kw):
        device, finish = svc.knn(INDEX, FIELD, queries, K, warm=True, **kw)
        return finish, tuple(np.asarray(v) for v in device)

    finish, vals = knn()
    per_query = finish(vals)
    rows.append(("  finish: rows -> doc ids -> scores -> hit lists",
                 median_ms(lambda: finish(vals), rounds)))
    rows.append((f"  {len(cmds)} x encode_search (a nested list a command)", median_ms(
        lambda: [plan["encode"](per_query[i:i + 1]) for i in range(len(cmds))],
        rounds)))
    if hasattr(M, "_ft_wave_encode"):
        columns, vals = knn(columns=True)
        cols = columns(vals)
        rows.append(("  finish: rows -> doc ids -> scores, as columns",
                     median_ms(lambda: columns(vals), rounds)))
        rows.append(("  _ft_wave_encode (the wave's answers as bytes)", median_ms(
            lambda: M._ft_wave_encode(plan, *cols, range(len(cmds))), rounds)))

    def forced():
        results = dispatched()
        S._force_lazies(results, server)
        return results

    nbytes = len(S._encode_frame(forced(), 2))
    whole.append(len(rows))
    rows.append((f"_encode_frame ({nbytes} bytes)", median_ms(
        lambda results: S._encode_frame(results, 2), rounds, forced)))
    rows.append(("sum: parse + coalesce_knn_run + _force_lazies + _encode_frame",
                 sum(rows[i][1] for i in whole)))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--docs", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--frame", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=30)
    args = ap.parse_args()
    rng = np.random.default_rng(33)
    with S.ServerThread(port=0, workers=4) as st:
        conn = Connection(st.server.host, st.server.port, timeout=180.0)
        try:
            populate(conn, args.docs, args.dim, rng)
        finally:
            conn.close()
        queries = rng.standard_normal((args.frame, args.dim)).astype("<f4")
        frame = b"".join(resp.encode_command(*search_command(q.tobytes()))
                         for q in queries)
        rows = probe(st.server, frame, args.rounds)
    import jax

    print(f"# a {args.frame}-search frame over {args.docs} documents of "
          f"{args.dim}-d, median of {args.rounds}, one thread, "
          f"device {jax.devices()[0].platform}")
    print("| piece | ms |")
    print("|---|---|")
    for name, ms in rows:
        print(f"| {name} | {ms:.3f} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())

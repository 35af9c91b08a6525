#!/usr/bin/env python3
"""chip_smoke.py — does today's tree still start, serve and answer correctly
on the chip?  The quickest proof the repo carries.

Drives the system's main path once through the entry points a user calls, at
the sizes BASELINE.json publishes, and checks every answer against a plain
NumPy reference made from ``--seed`` (utils/hashing.py runs under NumPy):

  served    ``python -m redisson_tpu.server --prewarm`` as a child process,
            driven from THIS jax-free parent over RESP with RemoteRedisson:
            config 2 (1,000-tenant bloom bank, 10 M keys, 100k-key flushes),
            config 1 (one 1e7/0.01 filter), config 3 (10,000 HLL counters),
            a 64-command coalesced frame, BITOP OR/XOR — then SIGTERM with
            the client connection still open: exit 0 within a bound.
  embedded  ``redisson_tpu.create()`` in one child: the config 2 bank through
            Batch, the fused add+contains pair, config 4 word count (device
            pipeline must answer), FLAT / IVF / INT8 KNN recall@10 against a
            float64 oracle, one residency demote -> fault-in cycle,
            Engine.prewarm().
  --chips 4 additionally: one ``tpu-server --devices all`` owning all four
            chips (four owners, every device holding bytes, a cross-device
            PFCOUNT union with zero host colocations), then the
            __graft_entry__.multichip_step body on the real mesh.

One process per chip: this parent never imports jax; the phases run as
sequential children, each the only holder of the chip while it lives.

Fails (non-zero exit, no result line) when any child's platform is not
``tpu``, any phase raises, any answer differs from the reference, or a
must-be-zero counter is not.  ``--rehearse-cpu`` is the ONLY way onto the
CPU: tiny sizes, prints ``"platform": "cpu"`` and ``"ok": false`` — a
rehearsal of the script, never a pass, and never what happens when no chip
is found.

stdout: a ``REPORT {...}`` line with the full report (also written to
chiprun_out/chip_smoke/report.json), then — last line — one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import argparse
import contextlib
import json
import os
import select
import signal
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

READY_TIMEOUT_S = 300.0   # server boot: jax import + backend init + g++
CLIENT_TIMEOUT_S = 180.0  # first compile of a bank kernel takes tens of s
SIGTERM_BOUND_S = 60.0    # told-to-stop server must exit 0 within this
                          # (2.5-4 s holding one chip, ~16 s holding four:
                          # the TPU runtime's own shutdown)
CHILD_TIMEOUT_S = 900.0

SIZES = {
    # BASELINE.json widths; nothing here is cut
    "full": dict(
        tenants=1000, per_tenant=10_000, fpp=0.01, populate_batch=500_000,
        flush=100_000, windows=6, single_n=10_000_000, single_batch=1 << 20,
        hll_counters=10_000, hll_per=200, hll_heavy=8, hll_heavy_n=100_000,
        hll_merges=100, run_filters=64, run_keys=100, bitset_bits=1 << 20,
        bitset_set=50_000, wc_entries=1_000_000, vec_n=50_000, vec_d=128,
        vec_nlist=1536, vec_centers=512,
    ),
    # the rehearsal: same code, toy sizes, CPU only
    "tiny": dict(
        tenants=16, per_tenant=1000, fpp=0.01, populate_batch=4000,
        flush=2048, windows=5, single_n=100_000, single_batch=1 << 14,
        hll_counters=64, hll_per=200, hll_heavy=2, hll_heavy_n=20_000,
        hll_merges=8, run_filters=8, run_keys=100, bitset_bits=1 << 16,
        bitset_set=2000, wc_entries=2000, vec_n=2000, vec_d=32,
        vec_nlist=32, vec_centers=32,
    ),
}
HLL_P = 14
HLL_BOUND = 3 * 1.04 / np.sqrt(1 << HLL_P)


class SmokeFailure(Exception):
    """A check failed; the run exits non-zero and prints no result."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


# -- the plain reference (NumPy over utils/hashing.py) ------------------------


def ref_bloom_idx(keys, k: int, m: int):
    from redisson_tpu.utils import hashing as H

    lo, hi = H.int_keys_to_u32_pair(keys)
    h1, h2 = H.hash_u64_pair(lo, hi, np)
    return H.bloom_indexes(h1, h2, k, m, np).astype(np.int64)


class RefBank:
    """(tenants, m) bit plane with the bank kernels' batch semantics:
    ``add`` reports newly-added against the plane as it stood BEFORE the
    batch, then sets."""

    def __init__(self, tenants: int, m: int, k: int):
        self.m, self.k = m, k
        self.bits = np.zeros(tenants * m, np.uint8)

    def _flat(self, tenant, keys):
        return np.asarray(tenant, np.int64)[:, None] * self.m + ref_bloom_idx(
            keys, self.k, self.m
        )

    def add(self, tenant, keys):
        g = self._flat(tenant, keys)
        newly = (self.bits[g] == 0).any(axis=1)
        self.bits[g.ravel()] = 1
        return newly

    def contains(self, tenant, keys):
        return self.bits[self._flat(tenant, keys)].all(axis=1)


class RefHllBank:
    """(counters, 2**p) HyperLogLog registers and the estimator of
    ops/hll.py, in float64."""

    def __init__(self, counters: int, p: int = HLL_P):
        self.m = 1 << p
        self.regs = np.zeros((counters, self.m), np.uint8)

    def add(self, tenant, keys):
        from redisson_tpu.utils import hashing as H

        h1, h2 = H.hash_u64_pair(*H.int_keys_to_u32_pair(keys), np)
        idx = (h1 & np.uint32(self.m - 1)).astype(np.int64)
        # clz32(h2) + 1; frexp's exponent of an integer is its bit length
        rho = (33 - np.frexp(h2.astype(np.float64))[1]).astype(np.uint8)
        np.maximum.at(self.regs, (np.asarray(tenant, np.int64), idx), rho)

    def merge_rows(self, dst, src):
        self.regs[dst] = np.maximum(self.regs[dst], self.regs[src])

    def estimate(self):
        m = self.m
        e = (0.7213 / (1.0 + 1.079 / m)) * m * m / np.exp2(
            -self.regs.astype(np.float64)).sum(axis=1)
        zeros = (self.regs == 0).sum(axis=1)
        lin = m * (np.log(m) - np.log(np.maximum(zeros, 1)))
        return np.where((e <= 2.5 * m) & (zeros > 0), lin, e)


def check_hll(est, ref_est, truth, what: str) -> dict:
    """Device estimates against the register-exact reference (float32 vs
    float64 arithmetic apart) and, statistically, against the true
    cardinalities: 3 sigma holds for 99% of counters (all but one of a
    small bank), 6 sigma for all."""
    check(est.shape == truth.shape and np.isfinite(est).all(), f"{what}: bad estimates")
    drift = np.abs(est - ref_est) / ref_est
    check(drift.max() <= 2e-3,
          f"{what}: estimate differs from the reference by {drift.max():.5f} "
          f"(counter {int(drift.argmax())})")
    rel = np.abs(est - truth) / truth
    inside = float((rel <= HLL_BOUND).mean())
    outliers = int((rel > HLL_BOUND).sum())
    check(outliers <= max(1, len(rel) // 100) and rel.max() <= 2 * HLL_BOUND,
          f"{what}: {outliers} of {len(rel)} counters beyond {HLL_BOUND:.4f} "
          f"of the truth, worst {rel.max():.4f}")
    return {"max_vs_reference": round(float(drift.max()), 6),
            "share_within_3sigma": round(inside, 4),
            "max_rel_err": round(float(rel.max()), 5)}


def fpp_ceiling(p: float, probes: int) -> float:
    """The banks are probed filled to exactly their design load, where the
    false-positive rate IS the configured p: allow the 3-sigma sampling
    error of `probes` absent keys above it, no more."""
    return p + 3 * np.sqrt(p * (1 - p) / probes)


def make_keys(rng, n: int, absent: bool = False):
    """Seeded int64 keys; present and absent keys come from disjoint halves
    of the key space, so an absent key is absent by construction."""
    base = (1 << 62) if absent else 0
    return (rng.integers(0, 1 << 61, n, dtype=np.int64) + base).astype(np.int64)


@contextlib.contextmanager
def timed(walls: dict, name: str):
    """Wall seconds of one step into `walls` — set-up facts (first calls
    include compilation), never rates."""
    t0 = time.monotonic()
    yield
    walls[name] = round(time.monotonic() - t0, 3)


def bank_workload(rng, sz: dict, m: int, k: int, add, contains, walls: dict,
                  what: str):
    """BASELINE config 2 against one (tenants, m) bank, however it is
    reached: populate every key through `add(tenants, keys) -> newly`, then
    `windows` flushes of half present / half absent keys through
    `contains(tenants, keys) -> found`.  Every flag is compared with the
    reference; >= 4 consecutive windows reuse both StagingPool slots."""
    T, per = sz["tenants"], sz["per_tenant"]
    ref = RefBank(T, m, k)
    n_keys = T * per
    order = rng.permutation(n_keys)
    keys = make_keys(rng, n_keys)[order]
    tenant = np.repeat(np.arange(T, dtype=np.int32), per)[order]
    with timed(walls, "config2_populate_s"):
        for s in range(0, n_keys, sz["populate_batch"]):
            sl = slice(s, s + sz["populate_batch"])
            got, want = add(tenant[sl], keys[sl]), ref.add(tenant[sl], keys[sl])
            check(np.array_equal(got, want),
                  f"{what} populate @{s}: newly-added flags differ from the "
                  f"reference in {int((got != want).sum())} places")
    fp = fn = 0
    half = sz["flush"] // 2
    with timed(walls, "config2_flush_windows_s"):
        for w in range(sz["windows"]):
            pick = rng.integers(0, n_keys, half)
            qk = np.empty(2 * half, np.int64)
            qt = np.empty(2 * half, np.int32)
            qk[0::2], qt[0::2] = keys[pick], tenant[pick]
            qk[1::2] = make_keys(rng, half, absent=True)
            qt[1::2] = rng.integers(0, T, half)
            got, want = np.asarray(contains(qt, qk), bool), ref.contains(qt, qk)
            check(np.array_equal(got, want),
                  f"{what} window {w}: found vector differs from the "
                  f"reference in {int((got != want).sum())} of {len(got)}")
            fn += int((~got[0::2]).sum())
            fp += int(got[1::2].sum())
    probes = half * sz["windows"]
    check(fn == 0, f"{what}: {fn} false negatives")
    check(fp / probes < fpp_ceiling(sz["fpp"], probes),
          f"{what}: FPP {fp / probes:.5f} on {probes} absent keys")
    row = {"m": m, "k": k, "keys": n_keys, "windows": sz["windows"],
           "flush_keys": sz["flush"], "false_negatives": fn,
           "fpp_absent": round(fp / probes, 5)}
    return row, (tenant[:4096], keys[:4096])  # + keys known to be present


def pair_workload(rng, sz: dict, m: int, k: int):
    """BASELINE config 1's add-then-probe pair on one filter: the keys, and
    what the reference says of them (newly-added flags, found flags)."""
    b = sz["single_batch"]
    addk = make_keys(rng, b)
    probe = np.concatenate([addk[: b // 2], make_keys(rng, b // 2, absent=True)])
    ref, z = RefBank(1, m, k), np.zeros(b, np.int32)
    return addk, probe, ref.add(z, addk), ref.contains(z, probe)


# -- child processes ----------------------------------------------------------


def child_env(args) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()
    return env


class Children:
    """Every process this script starts, so that it also stops them."""

    def __init__(self):
        self.procs = []

    def popen(self, *a, **kw):
        p = subprocess.Popen(*a, **kw)
        self.procs.append(p)
        return p

    def kill_all(self):
        for p in self.procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)


def start_server(children: Children, args, extra: list, tag: str):
    """Spawn one tpu-server child; returns (proc, address, boot_seconds)."""
    os.makedirs(OUT_DIR, exist_ok=True)
    rfd, wfd = os.pipe()
    cmd = [sys.executable, "-m", "redisson_tpu.server", "--port", "0",
           "--ready-fd", str(wfd), "--prewarm", *extra]
    t0 = time.monotonic()
    with open(os.path.join(OUT_DIR, f"{tag}.log"), "wb") as logf:
        proc = children.popen(
            cmd, stdout=logf, stderr=subprocess.STDOUT, pass_fds=(wfd,),
            env=child_env(args), cwd=HERE,
        )
    os.close(wfd)
    buf = b""
    try:
        while b"\n" not in buf:
            left = READY_TIMEOUT_S - (time.monotonic() - t0)
            check(left > 0, f"{tag}: no READY line in {READY_TIMEOUT_S:.0f}s")
            if select.select([rfd], [], [], min(left, 0.5))[0]:
                chunk = os.read(rfd, 4096)
                check(chunk, f"{tag}: server exited before READY "
                             f"(rc={proc.poll()}); see {OUT_DIR}/{tag}.log")
                buf += chunk
            else:
                check(proc.poll() is None,
                      f"{tag}: server died before READY (rc={proc.poll()}); "
                      f"see {OUT_DIR}/{tag}.log")
    finally:
        os.close(rfd)
    _ready, host, port, _pid = buf.split(b"\n", 1)[0].decode().split()
    return proc, f"tpu://{host}:{port}", time.monotonic() - t0


def parse_info(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#") and ":" in line:
            k, v = line.split(":", 1)
            out[k] = v
    return out


def device_of_info(info: dict) -> dict:
    return {
        "platform": info["platform"],
        "kind": info["device_kind"],
        "count": int(info["local_device_count"]),
    }


def require_platform(device: dict, args, who: str) -> None:
    want = "cpu" if args.rehearse_cpu else "tpu"
    check(device["platform"] == want,
          f"{who} runs on platform {device['platform']!r}, not {want!r}: "
          "no accelerator, no smoke (use --rehearse-cpu to rehearse the "
          "script itself on the CPU)")


def stop_server_with_client_open(proc, client, tag: str) -> float:
    """SIGTERM while `client` still holds its connection; the server must
    let go of the device and exit 0 inside the bound."""
    check(client.ping(), f"{tag}: client connection is not open")
    t0 = time.monotonic()
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=SIGTERM_BOUND_S)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            f"{tag}: server still alive {SIGTERM_BOUND_S:.0f}s after SIGTERM "
            "with a client connected"
        ) from None
    check(rc == 0, f"{tag}: server exited {rc} on SIGTERM, not 0")
    return time.monotonic() - t0


def server_counters(client) -> dict:
    """The server-side facts a wire client cannot observe itself, off INFO's
    # Device section, INFO commandstats and METRICS."""
    info = parse_info(client.info())
    stats = parse_info(bytes(client.execute("INFO", "commandstats")).decode())
    metrics = {}
    for line in bytes(client.execute("METRICS")).decode().splitlines():
        if line and not line.startswith("#"):
            name, _, val = line.rpartition(" ")
            metrics[name] = float(val)
    occ = info["replica_occupancy"]
    return {
        "wire_plane": info["wire_plane"],
        "native_build": info["native_build"],
        "compile_cache_dir": info["compile_cache_dir"] or None,
        "compile_cache_hits": int(info["compile_cache_hits"]),
        "compile_cache_writes": int(info["compile_cache_writes"]),
        "compiled_programs": int(info["compiled_programs"]),
        "compile_seconds_setup": float(info["compile_seconds"]),
        "staging_reuses": int(info["staging_reuses"]),
        "staging_oneoffs": int(info["staging_oneoffs"]),
        "d2d_colocations": int(info["d2d_colocations"]),
        "host_colocations": int(info["host_colocations"]),
        "merge_fallbacks": int(info["merge_fallbacks"]),
        "lane_faults": int(info["lane_faults"]),
        "lanes_quarantined": int(info["lanes_quarantined"]),
        "replica_occupancy": None if occ == "none" else float(occ),
        "error_replies": int(info["errors"]),
        "command_errors": int(metrics.get("rtpu_commands_errors", 0)),
        "devices": {
            k: dict(kv.split("=") for kv in v.split(","))
            for k, v in info.items()
            if k.startswith("device") and k[6:].isdigit()
        },
        "coalesced_calls": {
            k[len("cmdstat_"):]: int(v.split(",")[0].split("=")[1])
            for k, v in stats.items() if k.endswith(".coalesced")
        },
    }


def check_native(status: str, who: str) -> None:
    """Serving from the Python wire plane is legal; a native build that was
    attempted and failed is not."""
    check(status not in ("build_failed", "load_failed"),
          f"{who}: the native wire build was attempted and failed ({status})")


def check_counters(c: dict, args, tag: str) -> None:
    check(c["host_colocations"] == 0, f"{tag}: host_colocations={c['host_colocations']}")
    check(c["merge_fallbacks"] == 0, f"{tag}: merge_fallbacks={c['merge_fallbacks']}")
    check(c["lane_faults"] == 0, f"{tag}: lane_faults={c['lane_faults']}")
    check(c["lanes_quarantined"] == 0, f"{tag}: quarantined lanes")
    check(c["error_replies"] == 0 and c["command_errors"] == 0,
          f"{tag}: server counted error replies "
          f"({c['error_replies']}/{c['command_errors']}; -TRYAGAIN included)")
    check(c["replica_occupancy"] is None,
          f"{tag}: the sleep model is armed ({c['replica_occupancy']} ns/item)")
    check_native(c["native_build"], tag)
    if not args.rehearse_cpu:  # pooled staging is gated off on CPU jax
        check(c["staging_reuses"] > 0, f"{tag}: StagingPool never reused a slot")


# -- served phase --------------------------------------------------------------


def served_workload(client, sz: dict, seed: int) -> dict:
    """The main path over the wire; every reply checked.  Returns per-step
    wall seconds (set-up facts: first calls include compilation)."""
    from redisson_tpu.client.remote import int64_blob

    rng = np.random.default_rng(seed)
    walls, out = {}, {}

    # -- config 2: tenant bank, populate everything, windows of flushes ------
    bank = client.get_bloom_filter_array("smoke:bank")
    check(bank.try_init(sz["tenants"], sz["per_tenant"], sz["fpp"]), "BFA.RESERVE refused")
    m, k = int(bank.get_size()), int(bank.get_hash_iterations())
    if sz is SIZES["full"]:
        check(m == 96_256, f"bank m={m}, BASELINE geometry is 96,256 bits/tenant")
    out["config2"], _ = bank_workload(rng, sz, m, k, bank.add_each,
                                      bank.contains, walls, "config2")

    # -- config 1: one big filter, add+probe pair in ONE pipelined frame ------
    bf = client.get_bloom_filter("smoke:single")
    check(bf.try_init(sz["single_n"], sz["fpp"]), "BF.RESERVE refused")
    bi = client.execute("BF.INFO", "smoke:single")
    m1, k1 = int(bi[bi.index(b"Size") + 1]), int(bi[bi.index(b"Number of hashes") + 1])
    addk, probe, want_newly, want_found = pair_workload(rng, sz, m1, k1)
    with timed(walls, "config1_pair_s"):
        newly_b, found_b = client.execute_many([
            ("BF.MADD64", "smoke:single", int64_blob(addk)),
            ("BF.MEXISTS64", "smoke:single", int64_blob(probe)),
        ])
    found = np.frombuffer(found_b, np.uint8).astype(bool)
    check(np.array_equal(np.frombuffer(newly_b, np.uint8).astype(bool), want_newly),
          "config1: newly-added flags differ")
    check(np.array_equal(found, want_found),
          "config1: found vector differs from the reference")
    check(found[: len(addk) // 2].all(), "config1: false negatives")
    out["config1"] = {"m": m1, "k": k1, "pair_keys": len(addk),
                      "fpp_absent": round(float(found[len(addk) // 2:].mean()), 5)}

    # -- config 3: HLL bank — add, estimate, merge rows, estimate ------------
    C, per_c = sz["hll_counters"], sz["hll_per"]
    hll = client.get_hyper_log_log_array("smoke:hll")
    check(hll.try_init(C), "HLLA.RESERVE refused")
    hk = make_keys(rng, C * per_c)
    ht = np.repeat(np.arange(C, dtype=np.int32), per_c)
    heavy_k = make_keys(rng, sz["hll_heavy"] * sz["hll_heavy_n"])
    heavy_t = np.repeat(np.arange(sz["hll_heavy"], dtype=np.int32), sz["hll_heavy_n"])
    truth = np.full(C, float(per_c))
    truth[: sz["hll_heavy"]] += sz["hll_heavy_n"]
    rhll = RefHllBank(C)
    with timed(walls, "config3_add_s"):
        for s in range(0, len(hk), 1 << 20):
            hll.add(ht[s:s + (1 << 20)], hk[s:s + (1 << 20)])
        hll.add(heavy_t, heavy_k)
    rhll.add(ht, hk)
    rhll.add(heavy_t, heavy_k)
    with timed(walls, "config3_estimate_s"):
        est = hll.estimate_all()
    out["config3"] = {"counters": C, "p": HLL_P, "bound_3sigma": round(float(HLL_BOUND), 5),
                      "added": check_hll(est, rhll.estimate(), truth, "config3")}
    M = sz["hll_merges"]
    dst = np.arange(sz["hll_heavy"], sz["hll_heavy"] + M, dtype=np.int32)
    src = dst + M
    with timed(walls, "config3_merge_s"):
        hll.merge_rows(dst, src)
        est2 = hll.estimate_all()
    rhll.merge_rows(dst, src)
    truth[dst] += truth[src]  # disjoint key sets by construction
    out["config3"]["merged"] = check_hll(est2, rhll.estimate(), truth, "config3 merged")
    check((est2[dst] >= est[dst]).all() and (est2[dst] >= est[src]).all(),
          "config3: a union estimates below one of its sides")
    untouched = np.setdiff1d(np.arange(C), dst)
    check(np.array_equal(est2[untouched], est[untouched]),
          "config3: MERGEROWS changed rows it was not given")

    # -- the coalesced run: one frame, F same-geometry filters ---------------
    F, rk = sz["run_filters"], sz["run_keys"]
    names = [f"smoke:run:{i}" for i in range(F)]
    for n in names:
        client.execute("BF.RESERVE", n, repr(sz["fpp"]), 10_000)
    bi = client.execute("BF.INFO", names[0])
    mr, kr = int(bi[bi.index(b"Size") + 1]), int(bi[bi.index(b"Number of hashes") + 1])
    run_add = [make_keys(rng, rk) for _ in names]
    run_probe = [np.concatenate([a[: rk // 2], make_keys(rng, rk // 2, absent=True)])
                 for a in run_add]
    with timed(walls, "coalesced_frames_s"):
        added = client.execute_many(
            [("BF.MADD64", n, int64_blob(a)) for n, a in zip(names, run_add)])
        probed = client.execute_many(
            [("BF.MEXISTS64", n, int64_blob(q)) for n, q in zip(names, run_probe)])
    for i in range(F):
        r = RefBank(1, mr, kr)
        zi = np.zeros(rk, np.int32)
        check(np.array_equal(np.frombuffer(added[i], np.uint8).astype(bool),
                             r.add(zi, run_add[i])), f"coalesced add {i} differs")
        check(np.array_equal(np.frombuffer(probed[i], np.uint8).astype(bool),
                             r.contains(zi, run_probe[i])), f"coalesced probe {i} differs")
    out["coalesced"] = {"filters": F, "keys_per_command": rk}

    # -- BITOP OR / XOR over two bitsets --------------------------------------
    nb = sz["bitset_bits"]
    ia = rng.choice(nb, sz["bitset_set"], replace=False).astype(np.int32)
    ib = rng.choice(nb, sz["bitset_set"], replace=False).astype(np.int32)
    ia[0] = ib[0] = nb - 1  # both planes span the full size
    ra, rb = np.zeros(nb, bool), np.zeros(nb, bool)
    ra[ia] = True
    rb[ib] = True
    with timed(walls, "bitop_s"):
        client.execute("SETBITSB", "smoke:bits:a", ia.astype("<i4").tobytes())
        client.execute("SETBITSB", "smoke:bits:b", ib.astype("<i4").tobytes())
        client.execute("BITOP", "OR", "smoke:bits:a", "smoke:bits:a", "smoke:bits:b")
        client.execute("BITOP", "XOR", "smoke:bits:b", "smoke:bits:b", "smoke:bits:a")
        ra = ra | rb
        rb = rb ^ ra
        ca = int(client.execute("BITCOUNT", "smoke:bits:a"))
        cb = int(client.execute("BITCOUNT", "smoke:bits:b"))
        sample = rng.integers(0, nb, 4096).astype("<i4")
        ga = np.frombuffer(client.execute("GETBITSB", "smoke:bits:a", sample.tobytes()), np.uint8)
        gb = np.frombuffer(client.execute("GETBITSB", "smoke:bits:b", sample.tobytes()), np.uint8)
    check(ca == int(ra.sum()) and cb == int(rb.sum()),
          f"BITOP: BITCOUNT {ca}/{cb} != reference {int(ra.sum())}/{int(rb.sum())}")
    check(np.array_equal(ga.astype(bool), ra[sample]) and
          np.array_equal(gb.astype(bool), rb[sample]), "BITOP: sampled bits differ")
    check(ca >= sz["bitset_set"], "BITOP OR: union smaller than a side")
    out["bitop"] = {"bits": nb, "or_count": ca, "xor_count": cb}
    out["wall_seconds_setup"] = walls
    return out


def served_phase(children, args, sz, report) -> dict:
    proc, addr, boot_s = start_server(children, args, _platform_args(args), "served")
    log(f"served: server READY at {addr} after {boot_s:.1f}s")
    from redisson_tpu.client.remote import RemoteRedisson

    client = RemoteRedisson(addr, timeout=CLIENT_TIMEOUT_S)
    try:
        device = device_of_info(parse_info(client.info()))
        require_platform(device, args, "the server")
        t0 = time.monotonic()
        phase = served_workload(client, sz, args.seed)
        phase["wall_seconds"] = round(time.monotonic() - t0, 3)
        phase["boot_seconds_setup"] = round(boot_s, 3)
        phase["server"] = c = server_counters(client)
        check_counters(c, args, "served")
        for verb in ("bf.madd64.coalesced", "bf.mexists64.coalesced"):
            check(c["coalesced_calls"].get(verb, 0) >= 1,
                  f"served: no {verb} dispatch — the coalesced run never ran")
        phase["sigterm_exit_seconds"] = round(
            stop_server_with_client_open(proc, client, "served"), 3)
    finally:
        client.shutdown()
    log(f"served: OK in {phase['wall_seconds']:.1f}s; server exited 0 "
        f"{phase['sigterm_exit_seconds']:.2f}s after SIGTERM")
    report["device"] = device
    return phase


def _platform_args(args) -> list:
    return ["--platform", "cpu"] if args.rehearse_cpu else []


# -- four chips, one server ----------------------------------------------------


def served4_phase(children, args, sz, report) -> dict:
    from redisson_tpu.client.remote import RemoteRedisson, int64_blob
    from redisson_tpu.utils.crc16 import calc_slot

    n = args.chips
    proc, addr, boot_s = start_server(
        children, args,
        _platform_args(args) + ["--devices", "all", "--workers", "8"], "served4")
    client = RemoteRedisson(addr, timeout=CLIENT_TIMEOUT_S)
    try:
        device = device_of_info(parse_info(client.info()))
        require_platform(device, args, "the --devices all server")
        check(device["count"] == n,
              f"--chips {n}: the server sees {device['count']} devices")
        t0 = time.monotonic()
        phase = served_workload(client, sz, args.seed + 1)
        rows = client.execute("CLUSTER", "DEVICES")
        check(rows[0] == n and len(rows) == n + 1,
              f"CLUSTER DEVICES names {rows[0]} owners, not {n}")
        owners = {int(r[0]): int(r[1]) for r in rows[1:]}
        check(all(s > 0 for s in owners.values()) and sum(owners.values()) == 16384,
              f"CLUSTER DEVICES slot counts {owners}")
        faults = {int(r[0]): [int(x) for x in r[4][1:4]] for r in rows[1:]}
        check(all(f == [0, 0, 0] for f in faults.values()),
              f"CLUSTER DEVICES fault ledger {faults}")
        # one HLL per device, named so its slot lands there; the PFCOUNT
        # union has to cross every device boundary
        per_dev = 16384 // n
        names, i = {}, 0
        while len(names) < n:
            nm = f"smoke:pf:{i}"
            names.setdefault(calc_slot(nm.encode()) // per_dev, nm)
            i += 1
        rng = np.random.default_rng(args.seed + 2)
        each = 50_000 if sz is SIZES["full"] else 5000
        for nm in names.values():
            client.execute("PFADD64", nm, int64_blob(make_keys(rng, each)))
        union = int(client.execute("PFCOUNT", *names.values()))
        rel = abs(union - n * each) / (n * each)
        check(rel <= HLL_BOUND, f"cross-device PFCOUNT off by {rel:.4f}")
        phase["wall_seconds"] = round(time.monotonic() - t0, 3)
        phase["boot_seconds_setup"] = round(boot_s, 3)
        phase["cluster_devices"] = {"owners": owners}
        phase["pfcount_union"] = {"devices": n, "estimate": union,
                                  "truth": n * each, "rel_err": round(rel, 5)}
        phase["server"] = c = server_counters(client)
        check_counters(c, args, "served4")
        check(c["d2d_colocations"] > 0,
              "served4: the union never moved a value between devices")
        if not args.rehearse_cpu:  # CPU devices report no allocator stats
            share = {d: int(v["bytes_in_use"]) for d, v in c["devices"].items()}
            check(len(share) == n and all(b > (1 << 20) for b in share.values()),
                  f"served4: a device holds no share of the state: {share}")
            phase["bytes_in_use_per_device"] = share
        phase["sigterm_exit_seconds"] = round(
            stop_server_with_client_open(proc, client, "served4"), 3)
    finally:
        client.shutdown()
    log(f"served4: OK in {phase['wall_seconds']:.1f}s")
    return phase


# -- phases that hold jax themselves (run as children of this script) ----------


def run_child(children, args, which: str) -> dict:
    os.makedirs(OUT_DIR, exist_ok=True)
    cmd = [sys.executable, os.path.abspath(__file__), "--child", which,
           "--seed", str(args.seed), "--chips", str(args.chips)]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    with open(os.path.join(OUT_DIR, f"{which}.log"), "wb") as logf:
        proc = children.popen(cmd, stdout=subprocess.PIPE, stderr=logf,
                              env=child_env(args), cwd=HERE)
        try:
            stdout, _ = proc.communicate(timeout=CHILD_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{which}: child still running after "
                               f"{CHILD_TIMEOUT_S:.0f}s") from None
    tail = ""
    if proc.returncode != 0:
        with open(os.path.join(OUT_DIR, f"{which}.log"), "rb") as fh:
            tail = fh.read()[-3000:].decode(errors="replace")
    check(proc.returncode == 0, f"{which}: child exited {proc.returncode}\n{tail}")
    for line in stdout.decode().splitlines():
        if line.startswith("@@RESULT "):
            return json.loads(line[len("@@RESULT "):])
    raise SmokeFailure(f"{which}: child printed no result")


def child_main(args) -> int:
    """Body of a jax-holding child: the only place this file imports jax."""
    import jax

    import redisson_tpu

    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    require_platform(device, args, f"the {args.child} child")
    sz = SIZES["tiny" if args.rehearse_cpu else "full"]
    t0 = time.monotonic()
    if args.child == "embedded":
        out = embedded_workload(sz, args.seed)
    elif args.child == "mesh":
        check(len(d) >= args.chips, f"mesh: {len(d)} devices, need {args.chips}")
        import __graft_entry__

        redisson_tpu.enable_compile_cache()
        __graft_entry__.multichip_step(args.chips)
        out = mesh_workload(args.chips, sz, args.seed)
        out["multichip_step"] = "ok"
    else:
        raise SmokeFailure(f"unknown child {args.child!r}")
    out["wall_seconds"] = round(time.monotonic() - t0, 3)
    out["device"] = device
    out["jax_version"] = jax.__version__
    cache = redisson_tpu.compile_cache_stats()
    out["compile"] = {
        "cache_dir": redisson_tpu.compile_cache_dir(),
        "cache_hits": cache["hits"], "cache_writes": cache["writes"],
        "programs": cache["programs"],
        "compile_seconds_setup": round(cache["compile_s"], 3),
    }
    ms = d[0].memory_stats() or {}
    out["peak_bytes_in_use"] = ms.get("peak_bytes_in_use")
    print("@@RESULT " + json.dumps(out), flush=True)
    return 0


def embedded_workload(sz: dict, seed: int) -> dict:
    import redisson_tpu
    from redisson_tpu.client.codec import StringCodec
    from redisson_tpu.core import ioplane, residency
    from redisson_tpu.services import mapreduce as MR

    rng = np.random.default_rng(seed + 100)
    client = redisson_tpu.create()
    eng = client._engine
    out, walls = {}, {}
    try:
        # -- config 2 through the embedded handle + Batch --------------------
        bank = client.get_bloom_filter_array("smoke:bank")
        check(bank.try_init(sz["tenants"], sz["per_tenant"], sz["fpp"]),
              "bank try_init refused")
        with timed(walls, "prewarm_s"):
            warmed = eng.prewarm(buckets=(sz["flush"],))
        check(warmed > 0, "Engine.prewarm() warmed nothing for a live bank")

        def batch_contains(qt, qk):
            batch = client.create_batch()
            fut = batch.get_bloom_filter_array("smoke:bank").contains_async(qt, qk)
            batch.execute()
            return fut.get()

        out["config2"], (some_t, some_k) = bank_workload(
            rng, sz, bank.get_size(), bank.get_hash_iterations(),
            bank.add_each, batch_contains, walls, "embedded config2")

        # -- residency: demote the bank to host RAM, fault it back in --------
        mgr = eng.enable_residency()
        residency.set_tier(True)  # the getter guard, as bench config8 arms it
        with timed(walls, "residency_cycle_s"):
            check(mgr.demote("smoke:bank", force=True), "residency: demote refused")
            check(mgr.tier_of("smoke:bank") == "warm", "residency: bank not WARM")
            got = bank.contains(some_t, some_k)  # first touch faults it in
        check(mgr.tier_of("smoke:bank") == "hot" and mgr.promotions == 1,
              "residency: the touch did not fault the bank back in")
        check(got.all(), "residency: the bank lost keys across the H2D")
        out["residency"] = {"bank_bytes": sz["tenants"] * bank.get_size(),
                            "promotions": mgr.promotions}
        eng.disable_residency()
        residency.set_tier(False)

        # -- the fused add+contains pair (kernels.bloom_fused_add_contains) --
        bf = client.get_bloom_filter("smoke:single")
        check(bf.try_init(sz["single_n"], sz["fpp"]), "single try_init refused")
        m1, k1 = bf.get_size(), bf.get_hash_iterations()
        addk, probe, want_newly, want_found = pair_workload(rng, sz, m1, k1)
        with timed(walls, "fused_pair_s"):
            batch = client.create_batch()
            proxy = batch.get_bloom_filter("smoke:single")
            f_add, f_probe = proxy.add_async(addk), proxy.contains_async(probe)
            batch.execute()
        check(int(f_add.get()) == int(want_newly.sum()),
              "fused pair: newly-added count differs")
        check(np.array_equal(np.asarray(f_probe.get(), bool), want_found),
              "fused pair: found vector differs")
        out["fused_pair"] = {"m": m1, "k": k1, "keys": len(addk)}

        # -- config 4: word count; the DEVICE pipeline must answer ----------
        n_docs = sz["wc_entries"]
        vocab = np.array([f"w{i}" for i in range(1000)])
        draws = rng.integers(0, 1000, (n_docs, 8))
        wmap = client.get_map("smoke:wc", codec=StringCodec())
        wmap.put_all({f"doc-{i}": " ".join(row) for i, row in enumerate(vocab[draws])})
        want = {f"w{i}": int(c) for i, c in enumerate(np.bincount(draws.ravel(), minlength=1000)) if c}
        before = dict(MR.WC_ANSWERED)
        with timed(walls, "config4_word_count_s"):
            counts = MR.word_count(wmap, workers=64)
        check(counts == want, "config4: word counts differ from the reference")
        check(MR.WC_ANSWERED["device"] == before.get("device", 0) + 1
              and MR.WC_ANSWERED["host"] == before.get("host", 0),
              f"config4: answered by {dict(MR.WC_ANSWERED)} — not the device pipeline")
        out["config4"] = {"entries": n_docs, "distinct": len(counts),
                          "answered_by": "device"}
        wmap.delete()

        # -- vectors: FLAT, IVF, INT8 recall@10 vs a float64 oracle ----------
        out["vector"] = vector_legs(client, sz, rng, walls)

        st = ioplane.STATS.snapshot()
        pools = [eng.staging]
        out["io"] = {
            "staging_reuses": sum(p.reuses for p in pools),
            "host_colocations": st["host_colocations"],
            "merge_fallbacks": st["merge_fallbacks"],
            "replica_occupancy": ioplane.replica_occupancy(),
        }
        check(st["host_colocations"] == 0 and st["merge_fallbacks"] == 0,
              f"embedded: {st}")
        check(ioplane.replica_occupancy() is None, "embedded: sleep model armed")
        if ioplane.staging_reuse_safe():
            check(out["io"]["staging_reuses"] > 0, "embedded: staging never reused")
    finally:
        client.shutdown()
    out["wall_seconds_setup"] = walls
    return out


def mesh_workload(n: int, sz: dict, seed: int) -> dict:
    """The mesh-sharded objects at a density where the dp replicas really
    disagree before their all-reduce (multichip_step's handful of keys
    cannot tell a wrong collective from a right one), against the same
    references as the single-chip phases."""
    import redisson_tpu
    from redisson_tpu.config import Config

    rng = np.random.default_rng(seed + 200)
    cfg = Config()
    cfg.mesh.dp = 2 if n >= 4 else 1
    cfg.mesh.shard = n // cfg.mesh.dp
    client = redisson_tpu.create(cfg)
    try:
        T, per = 2 * n, sz["per_tenant"]
        bank = client.get_sharded_bloom_filter_array("smoke:mesh:bank")
        check(bank.try_init(T, per, sz["fpp"]), "mesh bank try_init refused")
        ref = RefBank(T, bank.get_size(), bank.get_hash_iterations())
        keys = make_keys(rng, T * per)
        tenant = rng.integers(0, T, T * per).astype(np.int32)
        for s in range(0, len(keys), 8192):
            sl = slice(s, s + 8192)
            check(np.array_equal(bank.add_each(tenant[sl], keys[sl]),
                                 ref.add(tenant[sl], keys[sl])),
                  f"mesh bank add @{s}: newly-added flags differ")
        absent = make_keys(rng, len(keys), absent=True)
        for probe in (keys, absent):
            got = bank.contains_each(tenant, probe)
            check(np.array_equal(got, ref.contains(tenant, probe)),
                  "mesh bank: found vector differs from the reference in "
                  f"{int((got != ref.contains(tenant, probe)).sum())} places")
        hll = client.get_sharded_hll_array("smoke:mesh:hll")
        check(hll.try_init(T), "mesh hll try_init refused")
        rh = RefHllBank(T)
        hk = make_keys(rng, T * 20 * per)
        ht = rng.integers(0, T, len(hk)).astype(np.int32)
        for s in range(0, len(hk), 1 << 16):
            hll.add_each(ht[s:s + (1 << 16)], hk[s:s + (1 << 16)])
        rh.add(ht, hk)
        hll_row = check_hll(hll.estimate_all(), rh.estimate(),
                            np.bincount(ht, minlength=T).astype(float), "mesh hll")
        nb = sz["bitset_bits"]
        bits = client.get_sharded_bit_set("smoke:mesh:bits")
        check(bits.try_init(nb), "mesh bitset try_init refused")
        rb = np.zeros(nb, bool)
        for value, count in ((True, nb // 2), (False, nb // 4)):  # pmax, then pmin
            idx = rng.choice(nb, count, replace=False)
            for s in range(0, count, 1 << 15):
                part = idx[s:s + (1 << 15)]
                check(np.array_equal(bits.set_each(part, value), rb[part]),
                      f"mesh bitset set({value}) @{s}: previous bits differ")
                rb[part] = value
        check(bits.cardinality() == int(rb.sum()),
              f"mesh bitset cardinality {bits.cardinality()} != {int(rb.sum())}")
        sample = rng.integers(0, nb, 1 << 15)
        check(np.array_equal(bits.get_each(sample), rb[sample]),
              "mesh bitset: sampled bits differ")
    finally:
        client.shutdown()
    return {"devices": n, "mesh": {"dp": cfg.mesh.dp, "shard": cfg.mesh.shard},
            "bank_keys": len(keys), "hll": hll_row, "bitset_bits": nb}


def vector_legs(client, sz, rng, walls) -> dict:
    N, d, K_ = sz["vec_n"], sz["vec_d"], 10
    Q = 64
    svc = client.get_search()
    centers = rng.standard_normal((sz["vec_centers"], d)).astype(np.float32)
    vecs = (centers[rng.integers(len(centers), size=N)]
            + 0.25 * rng.standard_normal((N, d))).astype(np.float32)
    queries = (vecs[rng.integers(N, size=Q)]
               + 0.1 * rng.standard_normal((Q, d))).astype(np.float32)
    q64, v64 = queries.astype(np.float64), vecs.astype(np.float64)
    dist = 1.0 - (q64 @ v64.T) / (
        np.linalg.norm(q64, axis=1)[:, None] * np.linalg.norm(v64, axis=1)[None, :])
    truth = [set(np.argsort(dist[i], kind="stable")[:K_].tolist()) for i in range(Q)]
    legs = {
        "flat": ({"dim": d, "metric": "COSINE"}, None, 0.99),
        "ivf": ({"dim": d, "metric": "COSINE", "algo": "IVF",
                 "nlist": sz["vec_nlist"]}, 4, 0.97),
        "int8": ({"dim": d, "metric": "COSINE", "dtype": "INT8"}, None, 0.95),
    }
    out = {"n": N, "dim": d, "k": K_, "q_batch": Q}
    for leg, (spec, nprobe, floor) in legs.items():
        name = f"smoke:vec:{leg}"
        with timed(walls, f"vector_{leg}_s"):
            svc.create_index(name, {"emb": "VECTOR"}, vector={"emb": spec})
            for i in range(N):
                svc.add_document(name, f"d{i}", {"emb": vecs[i]})
            dev, fin = svc.knn(name, "emb", queries, K_, nprobe=nprobe)
            got = fin(tuple(np.asarray(v) for v in dev))
        hits = sum(len(truth[i] & {int(doc[1:]) for doc, _s in got[i][:K_]})
                   for i in range(Q))
        recall = hits / (K_ * Q)
        scores = np.array([s for row in got for _doc, s in row[:K_]], float)
        check(np.isfinite(scores).all(), f"vector {leg}: non-finite distances")
        check(recall >= floor, f"vector {leg}: recall@10 {recall:.4f} < {floor}")
        out[leg] = {"recall_at_10": round(recall, 4), "floor": floor}
        svc.drop_index(name)
    return out


# -- parent --------------------------------------------------------------------


def cache_entries(path) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _r, _d, files in os.walk(path))


def write_report(report: dict, name: str) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, name), "w") as fh:
        json.dump(report, fh, indent=1)


def parent_main(args) -> int:
    try:
        import redisson_tpu
    except ImportError as e:
        print(f"chip_smoke: the program is not here ({e}); nothing to smoke",
              file=sys.stderr)
        return 2
    sz = SIZES["tiny" if args.rehearse_cpu else "full"]
    cache_dir = redisson_tpu.compile_cache_dir() if not args.rehearse_cpu else None
    report = {
        "seed": args.seed, "chips": args.chips,
        "size": "tiny (CPU rehearsal)" if args.rehearse_cpu else "full (BASELINE.json)",
        "compile_cache": {"dir": cache_dir, "entries_before": cache_entries(cache_dir)},
        "phases": {},
    }
    children = Children()
    try:
        report["phases"]["served"] = served_phase(children, args, sz, report)
        from redisson_tpu.net import _native

        # this parent IS the wire client: its own half of the native plane
        report["client_wire_plane"] = {
            "plane": "native" if _native.load() is not None else "python",
            "native_build": _native.build_status(),
        }
        check_native(_native.build_status(), "the client")
        report["compile_cache"]["entries_after_served"] = cache_entries(cache_dir)
        emb = run_child(children, args, "embedded")
        report["phases"]["embedded"] = emb
        report["jax_version"] = emb["jax_version"]
        report["compile_cache"]["entries_after_embedded"] = cache_entries(cache_dir)
        check(emb["device"] == report["device"],
              f"the phases saw different devices: {emb['device']} vs {report['device']}")
        log(f"embedded: OK in {emb['wall_seconds']:.1f}s "
            f"(cache hits {emb['compile']['cache_hits']})")
        if args.chips > 1:
            check(report["device"]["count"] == args.chips,
                  f"--chips {args.chips}: jax found {report['device']['count']} "
                  "device(s); fewer is a failure, not a skip")
            report["phases"]["served4"] = served4_phase(children, args, sz, report)
            report["phases"]["mesh"] = run_child(children, args, "mesh")
            log("mesh: OK")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        report["failed"] = str(e)
        write_report(report, "report.failed.json")  # for the post-mortem only
        return 1
    finally:
        children.kill_all()
    report["wall_seconds_total"] = round(time.monotonic() - _T0, 3)
    write_report(report, "report.json")
    print("REPORT " + json.dumps(report))
    last = {"ok": not args.rehearse_cpu, "device": report["device"]}
    if args.rehearse_cpu:
        last["rehearsal"] = "cpu: every check passed at tiny size; not a chip pass"
    print(json.dumps(last), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every key set and corpus (and so of the reference)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 adds the --devices all server and the mesh step; "
                         "fewer than four TPU devices then fails")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="rehearse the script on the CPU at tiny size; prints "
                         "platform cpu and ok:false — never a pass")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        try:
            return child_main(args)
        except SmokeFailure as e:
            print(f"chip_smoke[{args.child}]: FAILED — {e}", file=sys.stderr)
            return 1
    return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())

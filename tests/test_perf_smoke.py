"""Perf-contract smoke tier (ISSUE 2 satellite, `-m perf`): fast assertions
that pin the coalescing plane's correctness contracts and the zero-cost
chaos-hook guarantee — the properties bench.py measures but CI can't time.

  * fused add+contains kernel is BIT-IDENTICAL to the unfused pair;
  * the fault-plane DISABLED hot path allocates nothing (tracemalloc
    attribution against the exact guard lines in net/client.py);
  * coalesced cross-filter dispatch returns exactly what per-filter
    dispatch returns;
  * the overlap plane's structural win (ISSUE 3): N flush windows cost
    <= N+1 blocking device syncs overlapped vs 2N serial, bit-identically;
  * tools/perf_gate.py logic passes/fails on the recorded artifacts,
    including the two ISSUE 3-gated metrics.
"""
import socket
import threading

import numpy as np
import pytest

pytestmark = pytest.mark.perf


# -- fused kernel bit-identity ------------------------------------------------

def test_fused_add_contains_bit_identical_to_unfused_pair():
    """One fused program == add kernel then contains kernel, bit for bit:
    same new plane, same newly flags, same found flags."""
    from redisson_tpu.core import kernels as K
    from redisson_tpu.ops import bittensor as bt
    from redisson_tpu.utils import hashing as H

    m, k = 95_851, 7
    rng = np.random.default_rng(5)
    pre = rng.integers(0, 1 << 60, 500).astype(np.int64)
    add = rng.integers(0, 1 << 60, 300).astype(np.int64)
    probe = np.concatenate([add[:150], rng.integers(0, 1 << 60, 150).astype(np.int64)])

    def pack(keys):
        lo, hi = H.int_keys_to_u32_pair(keys)
        return K.pack_rows(lo, hi, size=K.bucket_size(keys.shape[0])), keys.shape[0]

    lh_pre, n_pre = pack(pre)
    lh_add, n_add = pack(add)
    lh_probe, n_probe = pack(probe)

    base, _ = K.bloom_add_packed(bt.make(m), lh_pre, K.valid_n(n_pre), k, m)
    base = np.asarray(base)  # host copy: both paths start from identical bits

    import jax.numpy as jnp

    bits_a, newly_a = K.bloom_add_packed(jnp.asarray(base), lh_add, K.valid_n(n_add), k, m)
    found_a = K.bloom_contains_packed(bits_a, lh_probe, K.valid_n(n_probe), k, m)

    bits_b, newly_b, found_b = K.bloom_fused_add_contains(
        jnp.asarray(base), lh_add, K.valid_n(n_add), lh_probe, K.valid_n(n_probe), k, m
    )
    np.testing.assert_array_equal(np.asarray(bits_a), np.asarray(bits_b))
    np.testing.assert_array_equal(np.asarray(newly_a), np.asarray(newly_b))
    np.testing.assert_array_equal(np.asarray(found_a), np.asarray(found_b))
    # the probe must observe the adds (read-your-writes inside the pair)
    assert np.asarray(found_b)[:150].all()


# -- zero-alloc disabled fault plane -----------------------------------------

def _guard_lines(mod=None):
    """Line numbers of every fault-plane guard in `mod` (default
    net/client.py) — the exact sites the zero-cost contract covers."""
    if mod is None:
        import redisson_tpu.net.client as mod

    path = mod.__file__
    lines = []
    with open(path) as fh:
        for no, line in enumerate(fh, 1):
            if "_fault_plane" in line and "def " not in line and "install" not in line:
                lines.append(no)
            if "plane is not None" in line or "plane.on_" in line:
                lines.append(no)
    return path, sorted(set(lines))


def test_fault_plane_disabled_path_allocates_nothing():
    """With no plane installed, send/recv through a real socket must not
    allocate ANYTHING attributable to the fault-plane guard lines — the
    'single `is None` branch' contract, asserted at the allocator level."""
    import tracemalloc

    from redisson_tpu.net import client as net

    assert net._fault_plane is None, "a fault plane leaked from another test"
    a, b = socket.socketpair()
    stop = threading.Event()

    def echo():
        # reply one RESP simple string per received chunk
        while not stop.is_set():
            try:
                data = b.recv(1 << 16)
            except OSError:
                return
            if not data:
                return
            b.sendall(b"+PONG\r\n" * max(1, data.count(b"PING")))

    t = threading.Thread(target=echo, daemon=True)
    t.start()
    conn = net.Connection.__new__(net.Connection)  # bypass connect handshake
    from collections import deque

    conn.host, conn.port = "local", 0
    conn.timeout = 5.0
    import redisson_tpu.net.resp as resp

    conn._parser = resp.RespParser()
    conn._pending = deque()
    conn.push_handler = None
    conn._sock = a
    conn.closed = False
    try:
        conn.execute("PING")  # warm every lazy path before tracing
        path, guards = _guard_lines()
        assert guards, "guard lines not found — contract comment drifted"
        tracemalloc.start(1)
        try:
            for _ in range(200):
                conn.execute("PING")
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        offenders = [
            (tb.lineno, stat.size)
            for stat in snap.statistics("lineno")
            for tb in [stat.traceback[0]]
            if tb.filename == path and tb.lineno in guards and stat.size > 0
        ]
        assert not offenders, (
            f"fault-plane guard lines allocated with the plane DISABLED: {offenders}"
        )
    finally:
        stop.set()
        conn.close()
        b.close()
        t.join(timeout=5)


def test_device_fault_guard_sites_discovered_and_zero_alloc_disarmed():
    """The device fault domain's chokepoints (dispatch, bank alloc, the
    two readback drains) follow the SAME one-global-load guard discipline
    as the transport sites: each hook module must contain discoverable
    guard lines, and the hottest one — the per-readback gate in
    core/ioplane.py — must allocate NOTHING at those lines with the plane
    disarmed and the lane watchdog off."""
    import tracemalloc

    import jax
    import jax.numpy as jnp

    import redisson_tpu.core.ioplane as iop
    import redisson_tpu.server.registry as reg
    import redisson_tpu.services.vector as vec
    from redisson_tpu.net import client as net

    for mod in (iop, reg, vec):
        _path, guards = _guard_lines(mod)
        assert guards, f"no fault-plane guard lines found in {mod.__name__}"

    assert net._fault_plane is None, "a fault plane leaked from another test"
    assert iop.lane_watchdog_ms() == 0, "a lane watchdog leaked"
    val = jnp.arange(8, dtype=jnp.int32)
    jax.block_until_ready(val)
    iop.ReadbackFuture((val,)).result()  # warm every lazy path
    path, guards = _guard_lines(iop)
    tracemalloc.start(1)
    try:
        for _ in range(200):
            iop.ReadbackFuture((val,)).result()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    offenders = [
        (tb.lineno, stat.size)
        for stat in snap.statistics("lineno")
        for tb in [stat.traceback[0]]
        if tb.filename == path and tb.lineno in guards and stat.size > 0
    ]
    assert not offenders, (
        f"device-fault guard lines allocated with the plane DISABLED: "
        f"{offenders}"
    )


def test_residency_guard_sites_discovered_and_zero_alloc_disarmed():
    """The tiered-residency getters (core/store.py) and the vector bank's
    record accessor (services/vector.py) follow the same one-global-load
    guard discipline: discoverable `plane is not None` / `plane.on_` lines,
    and the hottest site — the DeviceStore getter — allocates NOTHING at
    those lines with the tier plane disarmed (RTPU_NO_TIER semantics)."""
    import tracemalloc

    import redisson_tpu
    import redisson_tpu.core.store as store_mod
    import redisson_tpu.services.vector as vec
    from redisson_tpu.core import residency as _res

    for mod in (store_mod, vec):
        _path, guards = _guard_lines(mod)
        assert guards, f"no tier-plane guard lines found in {mod.__name__}"

    prev = _res.set_tier(False)
    client = redisson_tpu.create()
    try:
        eng = client._engine
        bf = client.get_bloom_filter("perf:res")
        assert bf.try_init(10_000, 0.01)
        bf.add("warm")
        eng.store.get("perf:res")  # warm every lazy path before tracing
        path, guards = _guard_lines(store_mod)
        tracemalloc.start(1)
        try:
            for _ in range(200):
                eng.store.get("perf:res")
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        offenders = [
            (tb.lineno, stat.size)
            for stat in snap.statistics("lineno")
            for tb in [stat.traceback[0]]
            if tb.filename == path and tb.lineno in guards and stat.size > 0
        ]
        assert not offenders, (
            f"tier-plane guard lines allocated with the plane DISARMED: "
            f"{offenders}"
        )
    finally:
        client.shutdown()
        _res.set_tier(prev)


# -- coalesced dispatch equivalence ------------------------------------------

def test_coalesced_run_matches_per_filter_dispatch():
    import redisson_tpu
    from redisson_tpu.core import coalesce as CO

    c = redisson_tpu.create()
    try:
        engine = c._engine
        rng = np.random.default_rng(7)
        names, keys_list = [], []
        for i in range(6):
            name = f"perf:co{i}"
            assert c.get_bloom_filter(name).try_init(20_000, 0.01)
            names.append(name)
            keys_list.append(rng.integers(0, 1 << 60, 200 + 40 * i).astype(np.int64))
        newly, lengths = CO.fused_bloom_add_async(engine, names, keys_list)
        flat = np.asarray(newly)
        off = 0
        for i, (name, keys) in enumerate(zip(names, keys_list)):
            seg = flat[off : off + lengths[i]]
            off += lengths[i]
            assert seg.all(), f"{name}: fused add lost keys"
            # per-filter ground truth sees exactly the fused writes
            assert c.get_bloom_filter(name).contains_each(keys).all()
        probes = [
            np.concatenate([keys[:50], rng.integers(0, 1 << 60, 50).astype(np.int64)])
            for keys in keys_list
        ]
        found, lengths = CO.fused_bloom_contains_async(engine, names, probes)
        flat = np.asarray(found)
        off = 0
        for i, (name, probe) in enumerate(zip(names, probes)):
            seg = flat[off : off + lengths[i]]
            off += lengths[i]
            expect = c.get_bloom_filter(name).contains_each(probe)
            np.testing.assert_array_equal(seg, expect)
    finally:
        c.shutdown()


def test_coalesce_ineligible_on_mixed_geometry():
    import redisson_tpu
    from redisson_tpu.core import coalesce as CO

    c = redisson_tpu.create()
    try:
        assert c.get_bloom_filter("perf:g1").try_init(10_000, 0.01)
        assert c.get_bloom_filter("perf:g2").try_init(90_000, 0.01)
        with pytest.raises(CO.CoalesceIneligible):
            CO.fused_bloom_add_async(
                c._engine,
                ["perf:g1", "perf:g2"],
                [np.arange(10, dtype=np.int64), np.arange(10, dtype=np.int64)],
            )
        # duplicate names in an ADD run: second group must see the first
        with pytest.raises(CO.CoalesceIneligible):
            CO.fused_bloom_add_async(
                c._engine,
                ["perf:g1", "perf:g1"],
                [np.arange(10, dtype=np.int64), np.arange(10, dtype=np.int64)],
            )
    finally:
        c.shutdown()


def test_malformed_frame_element_does_not_kill_connection():
    """Reviewer regression: a frame whose command carries a NON-BYTES
    element (nested array) must not crash the run scanner — the bad command
    gets 'ERR bad request frame' and the rest of the frame is served."""
    from redisson_tpu.server.server import ServerThread

    with ServerThread(port=0) as st:
        s = socket.create_connection((st.server.host, st.server.port), timeout=5)
        try:
            s.sendall(
                b"*2\r\n*1\r\n$2\r\nhi\r\n$1\r\nx\r\n"  # nested-array element
                b"*1\r\n$4\r\nPING\r\n"
            )
            s.settimeout(5)
            data = b""
            while b"PONG" not in data:
                chunk = s.recv(1 << 16)
                assert chunk, f"connection dropped; got only {data!r}"
                data += chunk
            assert b"ERR bad request frame" in data
        finally:
            s.close()


def test_coalesced_run_with_one_bad_blob_errors_only_that_command():
    """Reviewer regression: one malformed blob in a BF.MADD64 run makes the
    run ineligible (nothing dispatched yet) — per-command fallback errors
    ONLY the bad command, the other adds land."""
    from redisson_tpu.net.resp import RespError
    from redisson_tpu.server.server import ServerThread

    with ServerThread(port=0) as st:
        with st.client() as conn:
            for i in range(3):
                assert conn.execute("BF.RESERVE", f"bad:{i}", 0.01, 1000) in (b"OK", "OK")
            good = np.arange(100, dtype=np.int64).tobytes()
            replies = conn.execute_many([
                ("BF.MADD64", "bad:0", good),
                ("BF.MADD64", "bad:1", good[:7]),  # not a multiple of 8
                ("BF.MADD64", "bad:2", good),
            ], timeout=30.0)
            assert np.frombuffer(replies[0], np.uint8).all()
            assert isinstance(replies[1], RespError)
            assert np.frombuffer(replies[2], np.uint8).all()
            probe = conn.execute("BF.MEXISTS64", "bad:2", good, timeout=30.0)
            assert np.frombuffer(probe, np.uint8).all()


# -- overlap plane structural property (ISSUE 3) ------------------------------

def test_overlap_pipeline_sync_bound_and_bit_identity():
    """THE structural win of the overlap plane, pinned without a TPU: N
    flush windows through ioplane.FlushPipeline cost exactly 2N counted
    blocking device syncs serial (barrier + forced fetch per window) and
    <= N+1 overlapped (one demand-driven readback per window, plus at most
    one staging wait) — and the two modes return bit-identical results."""
    import redisson_tpu
    from redisson_tpu.core import ioplane
    from redisson_tpu.core import kernels as K

    c = redisson_tpu.create()
    try:
        arr = c.get_bloom_filter_array("perf:ov")
        assert arr.try_init(tenants=32, expected_insertions=2000,
                            false_probability=0.01)
        rng = np.random.default_rng(3)
        keys = rng.integers(0, 1 << 60, 4000).astype(np.int64)
        t = (keys % 32).astype(np.int32)
        arr.add_each(t, keys)
        n_win = 8
        windows = [
            (t[i * 500 : (i + 1) * 500], keys[i * 500 : (i + 1) * 500])
            for i in range(n_win)
        ]

        def window_fn(tt, kk):
            def fn():
                packed, n = arr.contains_async(tt, kk)
                return (packed,), (lambda host, n=n: K.unpack_found(host[0], n))

            return fn

        out, syncs = {}, {}
        for mode, overlap in (("serial", False), ("overlapped", True)):
            pipe = ioplane.FlushPipeline(overlap=overlap, depth=2)
            ioplane.STATS.reset()
            futs = [pipe.submit(window_fn(*w)) for w in windows]
            pipe.drain()
            out[mode] = [f.result() for f in futs]
            syncs[mode] = ioplane.STATS.snapshot()["blocking_syncs"]
        assert syncs["serial"] == 2 * n_win, syncs
        assert syncs["overlapped"] <= n_win + 1, syncs
        for a, b in zip(out["serial"], out["overlapped"]):
            np.testing.assert_array_equal(a, b)
        assert out["serial"][0].all()  # populated keys are all present
    finally:
        c.shutdown()


# -- perf gate logic ----------------------------------------------------------

def test_perf_gate_passes_self_and_fails_known_regression(tmp_path):
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(repo, "tools", "perf_gate.py")
    )
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)

    r5 = os.path.join(repo, "tests", "golden", "bench_gate_base.json")
    assert gate.main(["--fresh", r5, "--baseline", r5]) == 0
    r3 = os.path.join(repo, "tests", "golden", "bench_gate_prior.json")
    # a headline decline between two records (the motivating regression)
    # must FAIL
    assert gate.main(["--fresh", r5, "--baseline", r3]) == 1

    # synthetic: a 6% headline drop fails, 4% passes
    with open(r5) as fh:
        base = gate.load_bench_doc(fh.read())
    import copy
    import json

    for factor, want in ((0.94, 1), (0.96, 0)):
        doc = copy.deepcopy(base)
        doc["value"] = base["value"] * factor
        p = tmp_path / f"fresh_{factor}.json"
        p.write_text(json.dumps(doc))
        assert gate.main(["--fresh", str(p), "--baseline", r5]) == want

    # the two metrics gated by ISSUE 3: config2 flush p99 (LOWER is better —
    # a 6% slower p99 fails, 4% passes) and config4 cold entries/s
    for key, factor, want in (
        ("config2_flush_p99_ms", 1.06, 1),
        ("config2_flush_p99_ms", 1.04, 0),
        ("config4_mapreduce_cold_entries_per_sec", 0.94, 1),
        ("config4_mapreduce_cold_entries_per_sec", 0.96, 0),
    ):
        doc = copy.deepcopy(base)
        doc["details"][key] = base["details"][key] * factor
        p = tmp_path / f"fresh_{key}_{factor}.json"
        p.write_text(json.dumps(doc))
        assert gate.main(["--fresh", str(p), "--baseline", r5]) == want, (
            key, factor,
        )

    # config5p (ISSUE 6): absent from the r05 baseline — FIRST sight must
    # pass (n/a row, the fresh number becomes the next baseline) ...
    doc = copy.deepcopy(base)
    doc["details"]["config5p_cluster_proc_ops_per_sec"] = 515_000
    first = tmp_path / "fresh_5p_first.json"
    first.write_text(json.dumps(doc))
    assert gate.main(["--fresh", str(first), "--baseline", r5]) == 0
    # ... and once recorded, a >5% drop GATES
    for factor, want in ((0.94, 1), (0.96, 0)):
        doc2 = copy.deepcopy(doc)
        doc2["details"]["config5p_cluster_proc_ops_per_sec"] = 515_000 * factor
        p = tmp_path / f"fresh_5p_{factor}.json"
        p.write_text(json.dumps(doc2))
        assert gate.main(["--fresh", str(p), "--baseline", str(first)]) == want


# -- config6 tracking gate + bounded overflow (ISSUE 7) ------------------------


def test_perf_gate_config6_floor_and_relative(tmp_path):
    """config6_server_op_reduction: n/a-passes while absent, then gates BOTH
    relatively (>5% drop vs baseline) and absolutely (>=10x floor from
    first sight)."""
    import copy
    import importlib.util
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(repo, "tools", "perf_gate.py")
    )
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)
    r5 = os.path.join(repo, "tests", "golden", "bench_gate_base.json")
    with open(r5) as fh:
        base = gate.load_bench_doc(fh.read())

    # absent everywhere: n/a rows pass (first sight is next round's baseline)
    assert gate.main(["--fresh", r5, "--baseline", r5]) == 0
    # first sight ABOVE the floor passes; BELOW the floor fails even though
    # the baseline has no config6 at all
    for reduction, want in ((24.7, 0), (8.0, 1)):
        doc = copy.deepcopy(base)
        doc["details"]["config6_server_op_reduction"] = reduction
        p = tmp_path / f"fresh_c6_{reduction}.json"
        p.write_text(json.dumps(doc))
        assert gate.main(["--fresh", str(p), "--baseline", r5]) == want
    # once recorded: a >5% relative drop fails even while above the floor
    doc = copy.deepcopy(base)
    doc["details"]["config6_server_op_reduction"] = 24.7
    rec = tmp_path / "c6_recorded.json"
    rec.write_text(json.dumps(doc))
    for reduction, want in ((12.0, 1), (24.0, 0)):
        doc2 = copy.deepcopy(doc)
        doc2["details"]["config6_server_op_reduction"] = reduction
        p = tmp_path / f"fresh_c6_rel_{reduction}.json"
        p.write_text(json.dumps(doc2))
        assert gate.main(["--fresh", str(p), "--baseline", str(rec)]) == want


def test_tracking_table_overflow_stays_bounded():
    """The perf contract of the tracking table: a read stream over MORE
    distinct keys than tracking-table-max-keys keeps the table AT the
    bound (never beyond), with exactly (distinct - bound) synthetic
    overflow evictions — the counter the perf smoke tier asserts bounded."""
    from redisson_tpu.net.client import Connection
    from redisson_tpu.server.server import ServerThread

    with ServerThread(port=0) as st:
        srv = st.server
        srv.config_set("tracking-table-max-keys", "64")
        a = Connection(srv.host, srv.port, timeout=30.0)
        a.push_handler = lambda _p: None
        b = Connection(srv.host, srv.port, timeout=30.0)
        try:
            assert a.execute("CLIENT", "TRACKING", "ON") in (b"OK",)
            distinct = 200
            b.send_many([("SET", f"ovb:{i}", b"v") for i in range(distinct)])
            b.read_replies(distinct, timeout=30.0)
            high_water = 0
            for i in range(distinct):
                a.execute("GET", f"ovb:{i}")
                high_water = max(high_water, srv.tracking.tracked_key_count())
            assert high_water <= 64, high_water
            assert srv.tracking.stats["overflow_evictions"] == distinct - 64
        finally:
            a.close()
            b.close()


# -- per-device lane sync bound (ISSUE 8) -------------------------------------


def test_overlap_pipeline_per_device_lane_sync_bound():
    """The ISSUE 8 extension of the N-windows contract: with the slot table
    device-sharded, EACH device lane independently holds N windows <= N+1
    blocking syncs (per-device IOStats ledger), windows on different
    devices never count against each other's lane, and both lanes return
    bit-identical results to the serial reference."""
    import redisson_tpu
    from redisson_tpu.core import ioplane
    from redisson_tpu.core import kernels as K

    c = redisson_tpu.create()
    try:
        engine = c._engine
        placement = engine.enable_placement()
        # two filter-array names owned by DIFFERENT devices
        names, seen = [], set()
        for i in range(4000):
            n = f"perf:lane{i}"
            d = placement.device_id_for_name(n)
            if d not in seen:
                seen.add(d)
                names.append((n, d))
            if len(names) == 2:
                break
        assert len(names) == 2
        rng = np.random.default_rng(9)
        arrs = {}
        for name, _d in names:
            arr = c.get_bloom_filter_array(name)
            assert arr.try_init(tenants=16, expected_insertions=1000,
                                false_probability=0.01)
            keys = rng.integers(0, 1 << 60, 2000).astype(np.int64)
            t = (keys % 16).astype(np.int32)
            arr.add_each(t, keys)
            arrs[name] = (arr, t, keys)

        def window_fn(arr, tt, kk):
            def fn():
                packed, n = arr.contains_async(tt, kk)
                return (packed,), (lambda host, n=n: K.unpack_found(host[0], n))
            return fn

        n_win = 6
        out = {}
        ioplane.reset_device_stats()
        pipes = {
            name: ioplane.FlushPipeline(overlap=True, depth=2)
            for name, _d in names
        }
        futs = {name: [] for name, _d in names}
        for w in range(n_win):
            for name, _d in names:
                arr, t, keys = arrs[name]
                lo = w * 300
                futs[name].append(pipes[name].submit(
                    window_fn(arr, t[lo : lo + 300], keys[lo : lo + 300])
                ))
        for name, _d in names:
            pipes[name].drain()
            out[name] = [f.result() for f in futs[name]]
        per_dev = ioplane.device_stats_snapshot()
        for name, d in names:
            syncs = per_dev[d]["blocking_syncs"]
            assert 0 < syncs <= n_win + 1, (name, d, per_dev)
        # bit-identity against the direct (serial) path
        for name, _d in names:
            arr, t, keys = arrs[name]
            for w in range(n_win):
                lo = w * 300
                expect = arr.contains(t[lo : lo + 300], keys[lo : lo + 300])
                np.testing.assert_array_equal(out[name][w], np.asarray(expect))
    finally:
        c.shutdown()


# -- config5d gate logic (ISSUE 8) --------------------------------------------


def test_perf_gate_config5d_first_sight_and_relative(tmp_path):
    """config5d_device_sharded_ops_per_sec AND the 1-vs-N speedup ratio:
    n/a-pass while absent from the baseline, then BOTH gate a >5% relative
    drop once recorded."""
    import copy
    import importlib.util
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(repo, "tools", "perf_gate.py")
    )
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)
    r5 = os.path.join(repo, "tests", "golden", "bench_gate_base.json")
    with open(r5) as fh:
        base = gate.load_bench_doc(fh.read())

    # first sight: absent from the baseline -> n/a rows, gate passes
    doc = copy.deepcopy(base)
    doc["details"]["config5d_device_sharded_ops_per_sec"] = 300_000
    doc["details"]["config5d_speedup_vs_1dev"] = 3.5
    first = tmp_path / "fresh_5d_first.json"
    first.write_text(json.dumps(doc))
    assert gate.main(["--fresh", str(first), "--baseline", r5]) == 0
    # once recorded, each metric independently gates a >5% drop
    for key, factor, want in (
        ("config5d_device_sharded_ops_per_sec", 0.94, 1),
        ("config5d_device_sharded_ops_per_sec", 0.96, 0),
        ("config5d_speedup_vs_1dev", 0.94, 1),
        ("config5d_speedup_vs_1dev", 0.96, 0),
    ):
        doc2 = copy.deepcopy(doc)
        doc2["details"][key] = doc["details"][key] * factor
        p = tmp_path / f"fresh_5d_{key}_{factor}.json"
        p.write_text(json.dumps(doc2))
        assert gate.main(
            ["--fresh", str(p), "--baseline", str(first)]
        ) == want, (key, factor)


# -- config6r read-scaling gate (ISSUE 17) -------------------------------------


def test_perf_gate_config6r_floor_ceiling_and_relative(tmp_path):
    """config6r: the read-QPS scaling ratio n/a-passes while absent, then
    gates BOTH relatively (>5% drop) and absolutely (>=2.5x floor from
    first sight); the staleness p99 binds only as an absolute ceiling
    (<=1500ms) — never relatively, since wall-clock staleness jitters with
    container load."""
    import copy
    import importlib.util
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(repo, "tools", "perf_gate.py")
    )
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)
    r5 = os.path.join(repo, "tests", "golden", "bench_gate_base.json")
    with open(r5) as fh:
        base = gate.load_bench_doc(fh.read())

    # absent everywhere: n/a rows pass
    assert gate.main(["--fresh", r5, "--baseline", r5]) == 0
    # first sight: the 2.5x scaling floor and the 1500ms staleness ceiling
    # bind even though the baseline has no config6r rows at all
    for scaling, stale, want in (
        (3.1, 260.0, 0),   # healthy
        (2.2, 260.0, 1),   # replicas not absorbing reads
        (3.1, 2400.0, 1),  # scaling bought with stale serving
    ):
        doc = copy.deepcopy(base)
        doc["details"]["config6r_read_qps_scaling"] = scaling
        doc["details"]["config6r_staleness_p99_ms"] = stale
        p = tmp_path / f"fresh_c6r_{scaling}_{stale}.json"
        p.write_text(json.dumps(doc))
        assert gate.main(["--fresh", str(p), "--baseline", r5]) == want, (
            scaling, stale,
        )
    # once recorded: scaling gates a >5% relative drop even above the
    # floor; staleness p99 does NOT gate relatively (advisory row only)
    doc = copy.deepcopy(base)
    doc["details"]["config6r_read_qps_scaling"] = 3.6
    doc["details"]["config6r_staleness_p99_ms"] = 100.0
    rec = tmp_path / "c6r_recorded.json"
    rec.write_text(json.dumps(doc))
    for scaling, stale, want in (
        (3.3, 100.0, 1),    # >5% scaling drop, still above the 2.5x floor
        (3.5, 100.0, 0),    # <5% drop passes
        (3.6, 1400.0, 0),   # staleness 14x worse but under the ceiling: OK
    ):
        doc2 = copy.deepcopy(doc)
        doc2["details"]["config6r_read_qps_scaling"] = scaling
        doc2["details"]["config6r_staleness_p99_ms"] = stale
        p = tmp_path / f"fresh_c6r_rel_{scaling}_{stale}.json"
        p.write_text(json.dumps(doc2))
        assert gate.main(["--fresh", str(p), "--baseline", str(rec)]) == want, (
            scaling, stale,
        )

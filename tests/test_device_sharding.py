"""Device-sharded serving (ISSUE 8): slot -> device placement over the
local mesh inside ONE server process.

Covers the tentpole contracts:

  * the 16384-slot table maps contiguously and completely onto
    ``jax.local_devices()``; records commit their banks to the owner device
    at EVERY install chokepoint (create / put / migration import);
  * device moves are FENCED slot handoffs riding the migration epoch
    discipline — kill-at-every-phase journaled rebalance property test,
    STALEEPOCH on a stale coordinator, bit-identical banks after resume;
  * the per-device warm pool: ``Engine.prewarm`` compiles every local
    device's kernels, and a device move re-hits the pool with ZERO rebuilds;
  * cross-device HLL / BitSet / MapReduce merges stay on-device
    (``IOStats.host_colocations`` == 0 — the zero-host-gather contract);
  * a coalesced run whose planes span devices falls back to per-record
    dispatch (CoalesceIneligible), never a host-side gather;
  * the wire surface: CLUSTER DEVICES / DEVMOVE (fenced, STALEEPOCH), and
    pipelined frames through the per-device dispatch plan preserve reply
    order across sharded/serial segment boundaries.
"""
import numpy as np
import pytest

from redisson_tpu.core.engine import Engine
from redisson_tpu.server.migration import (
    CoordinatorKilled,
    rebalance_devices,
    resume_device_rebalances,
)
from redisson_tpu.server.migration_journal import MigrationJournal
from redisson_tpu.server.placement import PlacementStaleEpoch, SlotPlacement
from redisson_tpu.utils.crc16 import MAX_SLOT, calc_slot


@pytest.fixture()
def engine():
    eng = Engine()
    eng.enable_placement()
    yield eng
    eng.shutdown()


def _names_on_distinct_devices(placement, n, prefix="dv"):
    """First `n` key names whose slots land on pairwise-distinct devices."""
    out, seen = [], set()
    i = 0
    while len(out) < n and i < 10_000:
        name = f"{prefix}{i}"
        d = placement.device_id_for_name(name)
        if d not in seen:
            seen.add(d)
            out.append(name)
        i += 1
    assert len(out) == n, f"only {len(out)} distinct devices reachable"
    return out


# -- placement table ----------------------------------------------------------


def test_owner_table_contiguous_and_complete():
    p = SlotPlacement()
    assert p.n_devices == 8  # conftest forces 8 host devices
    counts = p.slot_counts()
    assert sum(counts) == MAX_SLOT
    assert all(c == MAX_SLOT // 8 for c in counts)
    # contiguity: owner never decreases over the slot range
    owners = p.owner_snapshot()
    assert (np.diff(owners) >= 0).all()
    assert owners[0] == 0 and owners[-1] == 7


def test_spread_plan_4_8_4_shape():
    p = SlotPlacement()
    move_to_4 = p.spread_plan(4)
    assert move_to_4  # half the table moves off devices 4..7
    assert set(move_to_4.values()) <= set(range(4))
    for slot, dev in move_to_4.items():
        p.assign(slot, dev)
    assert p.slot_counts()[4:] == [0, 0, 0, 0]
    assert sum(p.slot_counts()) == MAX_SLOT
    move_back = p.spread_plan(8)
    for slot, dev in move_back.items():
        p.assign(slot, dev)
    assert p.slot_counts() == [MAX_SLOT // 8] * 8
    with pytest.raises(ValueError):
        p.spread_plan(0)
    with pytest.raises(ValueError):
        p.spread_plan(9)


def test_fence_stale_epoch_rejected_idempotent_accepted():
    p = SlotPlacement()
    assert p.assign(100, 3, epoch=5)
    assert p.epoch_of(100) == 5
    # same-epoch re-issue (the resume path) is accepted and idempotent
    assert not p.assign(100, 3, epoch=5)
    # a stale coordinator is fenced out loudly
    with pytest.raises(PlacementStaleEpoch, match="STALEEPOCH"):
        p.assign(100, 1, epoch=4)
    assert p.device_id_for_slot(100) == 3
    # a newer epoch supersedes; epoch-less manual moves stay unfenced
    assert p.assign(100, 2, epoch=6)
    assert p.assign(100, 4)
    # other slots are unaffected by slot 100's fence
    assert p.assign(101, 1, epoch=1)


def test_plan_frame_partitions_and_barriers():
    p = SlotPlacement()
    names = _names_on_distinct_devices(p, 3)
    cmds = [
        [b"SET", names[0].encode(), b"a"],
        [b"SET", names[1].encode(), b"b"],
        [b"DEL", names[0].encode()],          # not whitelisted: barrier
        [b"GET", names[1].encode()],
        [b"GET", names[2].encode()],
    ]
    plan = p.plan_frame(cmds)
    kinds = [k for k, _ in plan]
    assert kinds == ["buckets", "serial", "buckets"]
    first, barrier, second = (seg for _k, seg in plan)
    assert sorted(i for idxs in first.values() for i in idxs) == [0, 1]
    assert barrier == [2]
    assert sorted(i for idxs in second.values() for i in idxs) == [3, 4]
    # every bucket is single-device and indexes stay in frame order
    for seg in (first, second):
        for idxs in seg.values():
            assert idxs == sorted(idxs)


def test_plan_frame_plans_every_frame():
    """Whatever a frame's composition it gets a plan, so that one code
    dispatches it: a lone command and a one-device frame are a bucket, a
    frame with nothing laneable is one serial segment."""
    p = SlotPlacement()
    one = _names_on_distinct_devices(p, 1)[0].encode()
    dev = p.device_index_for_command([b"SET", one, b"x"])
    assert p.plan_frame([[b"SET", one, b"x"]]) == [("buckets", {dev: [0]})]
    assert p.plan_frame([[b"SET", one, b"x"], [b"GET", one]]) == [
        ("buckets", {dev: [0, 1]})
    ]
    assert p.plan_frame([[b"PING"], [b"PING"]]) == [("serial", [0, 1])]
    assert p.plan_frame([]) == []
    # a shed position is in no segment and ends the one before it
    cmds = [[b"SET", one, b"x"], [b"GET", one], [b"GET", one], [b"PING"]]
    assert p.plan_frame(cmds, [False, True, False, False]) == [
        ("buckets", {dev: [0]}), ("buckets", {dev: [2]}), ("serial", [3]),
    ]
    assert p.plan_frame(cmds, [True] * 4) == []


def test_cross_device_multikey_command_is_barrier():
    p = SlotPlacement()
    a, b = _names_on_distinct_devices(p, 2)
    cmds = [
        [b"SET", a.encode(), b"1"],
        [b"BITOP", b"OR", a.encode(), a.encode(), b.encode()],  # spans devices
        [b"SET", b.encode(), b"2"],
    ]
    assert p.device_index_for_command(cmds[1]) is None
    plan = p.plan_frame(cmds)
    assert [k for k, _ in plan] == ["buckets", "serial", "buckets"]


# -- record placement ---------------------------------------------------------


def test_records_commit_to_owner_device(engine):
    from redisson_tpu.client.objects.hyperloglog import HyperLogLog
    from redisson_tpu.core import ioplane

    p = engine.placement
    names = _names_on_distinct_devices(p, 4, prefix="own")
    for name in names:
        HyperLogLog(engine, name).add_all([f"{name}:{j}" for j in range(20)])
    for name in names:
        rec = engine.store.get(name)
        got = ioplane.device_of(rec.arrays["regs"])
        assert got == p.device_for_name(name), name


def test_put_unguarded_places_like_migration_import(engine):
    """The migration/replication import chokepoint places too: a record
    installed via put_unguarded lands on its slot's owner device."""
    import jax.numpy as jnp

    from redisson_tpu.core import ioplane
    from redisson_tpu.core.store import StateRecord

    p = engine.placement
    name = "imp0"
    rec = StateRecord(
        kind="bitset", meta={}, arrays={"bits": jnp.zeros(64, jnp.uint8)}
    )
    engine.store.put_unguarded(name, rec)
    got = ioplane.device_of(engine.store.get(name).arrays["bits"])
    assert got == p.device_for_name(name)


def test_move_slot_records_fenced_and_bit_identical(engine):
    from redisson_tpu.client.objects.hyperloglog import HyperLogLog
    from redisson_tpu.core import ioplane

    p = engine.placement
    name = "mv0"
    h = HyperLogLog(engine, name)
    h.add_all([f"k{j}" for j in range(500)])
    before = np.asarray(engine.store.get(name).arrays["regs"]).copy()
    count_before = h.count()
    slot = calc_slot(name.encode())
    src = p.device_id_for_slot(slot)
    dst = (src + 3) % p.n_devices
    moved = engine.move_slot_records(slot, dst, epoch=10)
    assert moved >= 1
    rec = engine.store.get(name)
    assert ioplane.device_of(rec.arrays["regs"]) == p.devices[dst]
    np.testing.assert_array_equal(np.asarray(rec.arrays["regs"]), before)
    assert h.count() == count_before
    # the losing coordinator is fenced out
    with pytest.raises(PlacementStaleEpoch, match="STALEEPOCH"):
        engine.move_slot_records(slot, src, epoch=9)
    assert ioplane.device_of(engine.store.get(name).arrays["regs"]) == p.devices[dst]


# -- per-device warm pool (satellite) -----------------------------------------


def test_prewarm_warms_every_device_and_move_hits_pool(engine):
    """--prewarm with placement on compiles every device's kernels (one
    pool entry per device per geometry), and a later device move finds its
    target already warm: ZERO rebuilds."""
    from redisson_tpu.client.objects.hyperloglog import HyperLogLog

    p = engine.placement
    name = "warm:hll:devshard"
    HyperLogLog(engine, name).add_all(["seed"])
    first = engine.prewarm(names=[name])
    assert first >= p.n_devices  # at least one program set per device
    # everything is warm now: a second pass costs nothing
    assert engine.prewarm(names=[name]) == 0
    # a device move lands on an already-warm device: still zero rebuilds,
    # whichever device the slot hops to
    slot = calc_slot(name.encode())
    for dst in range(p.n_devices):
        engine.move_slot_records(slot, dst)
        assert engine.prewarm(names=[name], all_devices=False) == 0, dst


def test_prewarm_without_placement_keeps_historical_keys():
    from redisson_tpu.client.objects.hyperloglog import HyperLogLog
    from redisson_tpu.core.warmpool import POOL

    eng = Engine()
    try:
        name = "warm:hll:classic"
        HyperLogLog(eng, name).add_all(["seed"])
        eng.prewarm(names=[name])
        # single-device engines key on device id -1 (the default device)
        assert any(
            k[0] == "hll" and k[-1] == -1
            for k in list(POOL._entries)
        )
    finally:
        eng.shutdown()


# -- journaled device rebalance: kill-at-every-phase (satellite) ---------------


def test_device_rebalance_kill_at_every_phase(engine, tmp_path):
    """For EVERY journal phase of a device rebalance, killing the
    coordinator right after that phase's entry and resuming ends with the
    slots on their target devices, banks bit-identical, journal terminal,
    and a stale coordinator fenced out with STALEEPOCH."""
    from redisson_tpu.client.objects.hyperloglog import HyperLogLog
    from redisson_tpu.core import ioplane

    p = engine.placement
    jd = str(tmp_path / "journal")
    names = [f"reb{i}" for i in range(6)]
    for name in names:
        HyperLogLog(engine, name).add_all([f"{name}:{j}" for j in range(50)])
    baseline = {
        n: np.asarray(engine.store.get(n).arrays["regs"]).copy()
        for n in names
    }
    slots = sorted({calc_slot(n.encode()) for n in names})
    for phase in ("PLANNED", "DRAINING:1", "STABLE"):
        target_dev = {
            s: (p.device_id_for_slot(s) + 1) % p.n_devices for s in slots
        }
        with pytest.raises(CoordinatorKilled):
            rebalance_devices(
                engine, target_dev, journal_dir=jd, crash_after=phase
            )
        results = resume_device_rebalances(engine, jd)
        if phase == "STABLE":
            # the kill landed AFTER the terminal entry: the rebalance is
            # already complete, nothing is in flight to resume
            assert results == [], (phase, results)
            epoch = max(j.epoch for j in MigrationJournal.scan(jd))
        else:
            assert [r["action"] for r in results] == ["completed"], (
                phase, results,
            )
            epoch = results[0]["epoch"]
        assert not MigrationJournal.in_flight(jd), phase
        for name in names:
            slot = calc_slot(name.encode())
            rec = engine.store.get(name)
            assert (
                ioplane.device_of(rec.arrays["regs"])
                == p.devices[target_dev[slot]]
            ), (phase, name)
            np.testing.assert_array_equal(
                np.asarray(rec.arrays["regs"]), baseline[name]
            )
        # the losing (stale) coordinator cannot un-move any slot
        with pytest.raises(PlacementStaleEpoch, match="STALEEPOCH"):
            engine.move_slot_records(slots[0], 0, epoch=epoch - 1)


def test_rebalance_resume_skips_slots_a_newer_rebalance_owns(engine, tmp_path):
    """A crashed rebalance whose slots were since re-fenced HIGHER by a
    newer rebalance resumes without clobbering them (stale slots counted,
    not replayed)."""
    jd = str(tmp_path / "journal")
    slot = calc_slot(b"reb-stale")
    from redisson_tpu.client.objects.hyperloglog import HyperLogLog

    HyperLogLog(engine, "reb-stale").add_all(["x"])
    with pytest.raises(CoordinatorKilled):
        rebalance_devices(
            engine, {slot: 2}, journal_dir=jd, crash_after="PLANNED"
        )
    # a NEWER rebalance moves the slot to device 5 and completes
    moved = rebalance_devices(engine, {slot: 5}, journal_dir=jd)
    assert moved >= 1
    results = resume_device_rebalances(engine, jd)
    assert [r["action"] for r in results] == ["completed"]
    assert results[0]["stale_slots"] == 1
    assert engine.placement.device_id_for_slot(slot) == 5
    assert resume_device_rebalances(engine, jd) == []  # idempotent


# -- cross-device merges stay on-device ---------------------------------------


def test_hll_union_across_devices_matches_single_device_and_stays_on_device():
    from redisson_tpu.client.objects.hyperloglog import HyperLogLog
    from redisson_tpu.core import ioplane

    sharded = Engine()
    sharded.enable_placement()
    plain = Engine()
    try:
        names = _names_on_distinct_devices(sharded.placement, 4, prefix="hu")
        rng = np.random.default_rng(3)
        for name in names:
            keys = [f"{name}:{int(k)}" for k in rng.integers(0, 1 << 40, 300)]
            HyperLogLog(sharded, name).add_all(keys)
            HyperLogLog(plain, name).add_all(keys)
        ioplane.STATS.reset()
        got = HyperLogLog(sharded, names[0]).count_with(*names[1:])
        want = HyperLogLog(plain, names[0]).count_with(*names[1:])
        assert got == want
        snap = ioplane.STATS.snapshot()
        assert snap["host_colocations"] == 0
        assert snap["d2d_colocations"] > 0  # the merge really crossed devices
        # PFMERGE: destination keeps its committed owner device
        HyperLogLog(sharded, names[0]).merge_with(*names[1:])
        rec = sharded.store.get(names[0])
        assert ioplane.device_of(rec.arrays["regs"]) == (
            sharded.placement.device_for_name(names[0])
        )
        assert HyperLogLog(sharded, names[0]).count() == want
        assert ioplane.STATS.snapshot()["host_colocations"] == 0
    finally:
        sharded.shutdown()
        plain.shutdown()


def test_bitset_bitop_across_devices_stays_on_device():
    from redisson_tpu.client.objects.bitset import BitSet
    from redisson_tpu.core import ioplane

    eng = Engine()
    eng.enable_placement()
    try:
        a, b = _names_on_distinct_devices(eng.placement, 2, prefix="bo")
        BitSet(eng, a).set_each(np.array([1, 5, 9]))
        BitSet(eng, b).set_each(np.array([2, 5, 100]))
        ioplane.STATS.reset()
        BitSet(eng, a).or_(b)
        snap = ioplane.STATS.snapshot()
        assert snap["host_colocations"] == 0
        assert snap["d2d_colocations"] > 0
        got = np.asarray(BitSet(eng, a).get_each(np.arange(128)))
        assert sorted(np.nonzero(got)[0].tolist()) == [1, 2, 5, 9, 100]
    finally:
        eng.shutdown()


def test_wordcount_spreads_chunks_and_merges_without_host_gather():
    """The cross-device MapReduce acceptance: chunk extraction fans out
    across the local mesh and the merge back to the reduce device is d2d —
    ZERO host-side gathers (asserted via IOStats)."""
    import redisson_tpu
    from redisson_tpu.client.codec import StringCodec
    from redisson_tpu.core import ioplane
    from redisson_tpu.services.mapreduce import word_count

    c = redisson_tpu.create()
    try:
        c._engine.enable_placement()
        m = c.get_map("ds:wc", codec=StringCodec())
        rng = np.random.default_rng(5)
        vocab = [f"w{i}" for i in range(40)]
        entries = {
            f"d{i}": " ".join(vocab[j] for j in rng.integers(0, 40, 6))
            for i in range(3000)
        }
        m.put_all(entries)
        ioplane.STATS.reset()
        counts = word_count(m, workers=8)
        assert sum(counts.values()) == 3000 * 6
        snap = ioplane.STATS.snapshot()
        assert snap["host_colocations"] == 0
        assert snap["d2d_colocations"] > 0  # chunks really spread + merged
    finally:
        c.shutdown()


# -- coalescing stays per-device ----------------------------------------------


def test_coalesce_rejects_run_spanning_devices():
    """A fused run whose planes live on different devices is INELIGIBLE —
    the caller falls back to per-record dispatch; a cross-device stack
    through host memory must never happen."""
    import redisson_tpu
    from redisson_tpu.core import coalesce as CO

    c = redisson_tpu.create()
    try:
        engine = c._engine
        engine.enable_placement()
        names = _names_on_distinct_devices(engine.placement, 2, prefix="cx")
        for name in names:
            assert c.get_bloom_filter(name).try_init(20_000, 0.01)
        with pytest.raises(CO.CoalesceIneligible, match="span"):
            CO.fused_bloom_add_async(
                engine, names,
                [np.arange(10, dtype=np.int64)] * len(names),
            )
        # per-filter fallback works and lands on each record's own device
        for name in names:
            bf = c.get_bloom_filter(name)
            bf.add_all(np.arange(10, dtype=np.int64))
            assert bf.contains_each(np.arange(10, dtype=np.int64)).all()
    finally:
        c.shutdown()


def test_coalesce_same_device_run_still_fuses():
    import redisson_tpu
    from redisson_tpu.core import coalesce as CO

    c = redisson_tpu.create()
    try:
        engine = c._engine
        engine.enable_placement()
        p = engine.placement
        # names sharing ONE owner device
        home = p.device_id_for_name("sd0")
        names = [
            n for n in (f"sd{i}" for i in range(2000))
            if p.device_id_for_name(n) == home
        ][:4]
        assert len(names) == 4
        for name in names:
            assert c.get_bloom_filter(name).try_init(20_000, 0.01)
        keys = [np.arange(50, dtype=np.int64) * (i + 1) for i in range(4)]
        newly, lengths = CO.fused_bloom_add_async(engine, names, keys)
        flat = np.asarray(newly)
        off = 0
        for name, k, n in zip(names, keys, lengths):
            assert flat[off : off + n].all(), name  # valid region (padded)
            off += n
            assert c.get_bloom_filter(name).contains_each(k).all()
    finally:
        c.shutdown()


# -- per-device d2h gather ----------------------------------------------------


def test_gather_device_results_buckets_per_device():
    """Results spanning devices fetch as one merged transfer PER DEVICE
    (counted on that device's ledger), bit-identically."""
    import jax

    from redisson_tpu.core import ioplane

    devs = jax.local_devices()
    rng = np.random.default_rng(11)
    host_vals = [rng.integers(0, 255, 97).astype(np.uint8) for _ in range(6)]
    groups = [
        (jax.device_put(v, devs[i % 3]),) for i, v in enumerate(host_vals)
    ]
    ioplane.reset_device_stats()
    before = ioplane.STATS.snapshot()["blocking_syncs"]
    out = ioplane.gather_device_results(groups)
    for got, want in zip(out, host_vals):
        np.testing.assert_array_equal(got[0], want)
    after = ioplane.STATS.snapshot()["blocking_syncs"]
    assert after - before == 3  # one sync per touched device, not per group
    per_dev = ioplane.device_stats_snapshot()
    touched = [d for d, s in per_dev.items() if s["blocking_syncs"]]
    assert len(touched) == 3


# -- the wire surface ---------------------------------------------------------


@pytest.fixture(scope="module")
def sharded_server():
    from redisson_tpu.server import ServerThread

    with ServerThread(devices="all", workers=8) as st:
        yield st


def test_cluster_devices_and_devmove_wire(sharded_server):
    from redisson_tpu.net.resp import RespError

    st = sharded_server
    with st.client() as conn:
        reply = conn.execute("CLUSTER", "DEVICES")
        assert int(reply[0]) == 8
        assert sum(int(row[1]) for row in reply[1:]) == MAX_SLOT
        conn.execute("SET", "wired", "v")
        conn.execute("PFADD", "wired:hll", "a", "b", "c")  # device-array record
        slot = calc_slot(b"wired:hll")
        moved = conn.execute("CLUSTER", "DEVMOVE", 3, "EPOCH", 50, slot)
        assert int(moved) >= 1  # the HLL's regs actually hopped devices
        assert int(conn.execute("PFCOUNT", "wired:hll")) == 3
        assert bytes(conn.execute("GET", "wired")) == b"v"
        # stale coordinator over the wire: STALEEPOCH, nothing moves
        reply = conn.execute("CLUSTER", "DEVMOVE", 1, "EPOCH", 49, slot)
        assert isinstance(reply, RespError)
        assert str(reply).startswith("STALEEPOCH")
        assert st.server.engine.placement.device_id_for_slot(slot) == 3
        # placement state is visible in CONFIG GET
        view = st.server.config_view()
        assert view["placement-devices"] == 8


def test_sharded_frame_preserves_reply_order(sharded_server):
    st = sharded_server
    with st.client() as conn:
        n = 24
        sets = conn.execute_many(
            [("SET", f"ord{i}", f"v{i}") for i in range(n)]
        )
        assert all(bytes(r) == b"OK" for r in sets)
        # mixed frame: sharded segments around a serial barrier (DEL)
        replies = conn.execute_many(
            [("GET", f"ord{i}") for i in range(n)]
            + [("DEL", "ord0")]
            + [("GET", f"ord{i}") for i in range(n)]
        )
        assert [bytes(r) for r in replies[:n]] == [
            f"v{i}".encode() for i in range(n)
        ]
        assert int(replies[n]) == 1
        assert replies[n + 1] is None  # the barrier ordered the delete
        assert [bytes(r) for r in replies[n + 2 :]] == [
            f"v{i}".encode() for i in range(1, n)
        ]


def test_sharded_frame_bloom_runs_fuse_per_device(sharded_server):
    """Same-verb blob runs inside one frame still coalesce per device
    bucket, and the replies are correct and ordered."""
    st = sharded_server
    with st.client() as conn:
        names = [f"fr{i}" for i in range(8)]
        for name in names:
            assert conn.execute("BF.RESERVE", name, 0.01, 2000) in (b"OK", "OK")
        blob = np.arange(200, dtype="<i8").tobytes()
        adds = conn.execute_many(
            [("BF.MADD64", n, blob) for n in names], timeout=60.0
        )
        for r in adds:
            assert np.frombuffer(r, np.uint8).all()
        probes = conn.execute_many(
            [("BF.MEXISTS64", n, blob) for n in names], timeout=60.0
        )
        for r in probes:
            assert np.frombuffer(r, np.uint8).all()


def test_single_device_server_unchanged():
    """devices=None (the default) keeps the historical single-device
    server: no placement, no lanes, byte-identical dispatch path."""
    from redisson_tpu.server import ServerThread

    with ServerThread(port=0) as st:
        assert st.server.engine.placement is None
        assert st.server.engine.lanes is None
        with st.client() as conn:
            conn.execute("SET", "plain", "x")
            assert bytes(conn.execute("GET", "plain")) == b"x"
            assert conn.execute("CLUSTER", "DEVICES") == [0]


def test_mixed_journal_dir_resume_paths_never_cross(engine, tmp_path):
    """Device rebalances share the journal directory's epoch allocator
    with slot migrations, but each resume path settles ONLY its own kind:
    resume_migrations must not dial a device rebalance as a node address,
    and resume_device_rebalances must ignore slot-migration journals."""
    from redisson_tpu.server.migration import resume_migrations

    jd = str(tmp_path / "journal")
    from redisson_tpu.client.objects.hyperloglog import HyperLogLog

    HyperLogLog(engine, "mix0").add_all(["x"])
    slot = calc_slot(b"mix0")
    with pytest.raises(CoordinatorKilled):
        rebalance_devices(
            engine, {slot: 4}, journal_dir=jd, crash_after="PLANNED"
        )
    # a slot-migration journal in the SAME directory (unreachable node:
    # the wire resume path would fail loudly if it tried the rebalance)
    j = MigrationJournal.create(jd, "127.0.0.1:1", "127.0.0.1:2")
    j.append("PLANNED", source="127.0.0.1:1", target="127.0.0.1:2",
             slots=[slot], epoch=j.epoch, old_view=[], new_view=[])
    # both journals share one monotonic epoch sequence
    assert j.epoch > MigrationJournal.scan(jd)[0].epoch
    # the device-rebalance resume settles only its own journal
    results = resume_device_rebalances(engine, jd)
    assert [r["action"] for r in results] == ["completed"]
    assert engine.placement.device_id_for_slot(slot) == 4
    # the wire resume sees only the slot-migration journal; it fails on the
    # unreachable node (expected here) but never touches the rebalance
    wire = resume_migrations(jd)
    assert len(wire) == 1 and wire[0]["id"] == j.migration_id


def test_plan_frame_aborts_on_in_frame_multi():
    """MULTI arms transaction queueing mid-frame: every later command must
    append to the queue in frame order, which concurrent buckets cannot
    guarantee — the planner makes the whole frame serial."""
    p = SlotPlacement()
    a, b = (n.encode() for n in _names_on_distinct_devices(p, 2))
    cmds = [
        [b"SET", a, b"1"],
        [b"MULTI"],
        [b"SET", b, b"2"],
        [b"EXEC"],
    ]
    assert p.plan_frame(cmds) == [("serial", [0, 1, 2, 3])]
    assert p.plan_frame(cmds, [True, False, False, False]) == [
        ("serial", [1, 2, 3])
    ]


def test_transaction_in_one_frame_on_sharded_server(sharded_server):
    """MULTI..EXEC pipelined in ONE frame against a device-sharded server
    queues and executes in order (the planner hands the frame to the
    sequential path)."""
    st = sharded_server
    with st.client() as conn:
        replies = conn.execute_many([
            ("SET", "tx:a", "1"),
            ("MULTI",),
            ("SET", "tx:a", "2"),
            ("SET", "tx:b", "3"),
            ("EXEC",),
            ("GET", "tx:a"),
            ("GET", "tx:b"),
        ])
        assert bytes(replies[0]) == b"OK"
        assert bytes(replies[1]) == b"OK"          # MULTI
        assert bytes(replies[2]) == b"QUEUED"
        assert bytes(replies[3]) == b"QUEUED"
        assert bytes(replies[5]) == b"2"
        assert bytes(replies[6]) == b"3"

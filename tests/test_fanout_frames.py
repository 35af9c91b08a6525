"""A pipelined frame over many tenants runs a bounded, pre-compiled set of
programs whatever its composition (ISSUE 26): the stacked runs of
core/coalesce.py, the grouped fetch of core/ioplane.py, and the laned frame
path of a device-sharded server.

Everything here runs on the CPU with four of the forced host devices and
small filters: it shows that replies equal the plain reference
(benchmark/reference.py), that fused runs of every length equal per-record
dispatch, that frames of any composition compile nothing once one frame has
warmed the server, that padding planes are never written back, that the
grouped fetch returns what it was given for any mix of parts, and what the
new METRICS series count.  How fast any of it is, only a chip run says."""
import numpy as np
import pytest

import redisson_tpu
from benchmark.reference import RefBank, RefBitSet
from redisson_tpu.core import coalesce as CO
from redisson_tpu.core import ioplane

CAPACITY, FPP = 500, 0.01      # a small filter: m = 4,792 bits, k = 7
BITS = 1 << 14                 # a small bitset
F0 = CO.STACK_PLANES


def programs() -> int:
    return int(redisson_tpu.compile_cache_stats()["programs"])


def blob8(a) -> bytes:
    return np.ascontiguousarray(a, "<i8").tobytes()


def blob4(a) -> bytes:
    return np.ascontiguousarray(a, "<i4").tobytes()


def names(t: int):
    tag = "{t%d}" % t
    return "bf" + tag, "ba" + tag, "bb" + tag


# -- the planner ---------------------------------------------------------------


@pytest.mark.parametrize("lengths,want", [
    ([100] * 5, [(0, 5)]),
    ([100] * F0, [(0, F0)]),
    ([100] * (F0 + 1), [(0, F0), (F0, F0 + 1)]),
    ([100] * (2 * F0 + 3), [(0, F0), (F0, 2 * F0), (2 * F0, 2 * F0 + 3)]),
    ([9000, 9000, 100], [(0, 1), (1, 3)]),            # rows cut the run
    ([100, 20000, 100, 100], [(0, 1), (1, 2), (2, 4)]),  # too long: alone
    ([20000], [(0, 1)]),
    ([], []),
])
def test_plan_stacked_chunks(lengths, want):
    assert CO.plan_stacked_chunks(lengths) == want
    top = CO.STACK_ROW_BUCKETS[-1]
    for s, e in want:
        assert e - s <= F0 and (e - s == 1 or sum(lengths[s:e]) <= top)


def test_stacked_row_buckets_are_a_short_ladder():
    assert list(CO.STACK_ROW_BUCKETS) == sorted(CO.STACK_ROW_BUCKETS)
    assert len(CO.STACK_ROW_BUCKETS) <= 4
    assert CO.stacked_row_bucket(1) == CO.STACK_ROW_BUCKETS[0]
    for b in CO.STACK_ROW_BUCKETS:
        assert CO.stacked_row_bucket(b) == b
    assert CO.stacked_row_bucket(CO.STACK_ROW_BUCKETS[-1] + 1) is None


# -- fused runs against per-record dispatch --------------------------------------


@pytest.fixture(scope="module")
def embedded():
    c = redisson_tpu.create()
    try:
        for i in range(2 * F0 + 2):
            assert c.get_bloom_filter(f"fz:{i}").try_init(CAPACITY, FPP)
        yield c
    finally:
        c.shutdown()


def _fused(engine, add: bool, run_names, keys_list):
    """The run through the stacked path, cut as the callers cut it."""
    fn = CO.fused_bloom_add_async if add else CO.fused_bloom_contains_async
    out = []
    for s, e in CO.plan_stacked_chunks([len(k) for k in keys_list]):
        flags, lengths = fn(engine, run_names[s:e], keys_list[s:e])
        flat = np.asarray(flags)
        off = 0
        for n in lengths:
            out.append(flat[off:off + n])
            off += n
    return out


@pytest.mark.parametrize("f", list(range(1, 2 * F0 + 3)))
def test_fused_run_of_every_length_equals_per_record_dispatch(embedded, f):
    """F from 1 past two chunk edges (F0, 2 F0): newly-added flags and found
    vectors of the stacked path are the per-record path's, bit for bit."""
    c = embedded
    engine = c._engine
    rng = np.random.default_rng(1000 + f)
    run = [f"fz:{i}" for i in rng.permutation(2 * F0 + 2)[:f]]
    fresh = [rng.integers(0, 1 << 60, 20 + (i * 7) % 30).astype(np.int64) for i in range(f)]
    twins = [f"tw:{f}:{i}" for i in range(f)]  # per-record twins, same history
    for name, twin in zip(run, twins):
        assert c.get_bloom_filter(twin).try_init(CAPACITY, FPP)
        rec, trec = engine.store.get(name), engine.store.get(twin)
        trec.arrays["bits"] = rec.arrays["bits"] + 0  # a copy of the plane
    newly = _fused(engine, True, run, fresh)
    for name, twin, keys, got in zip(run, twins, fresh, newly):
        want = c.get_bloom_filter(twin).add_each(keys)
        np.testing.assert_array_equal(got, want, err_msg=f"newly {name}")
    probes = [np.concatenate([k[:10], rng.integers(0, 1 << 60, 15).astype(np.int64)])
              for k in fresh]
    found = _fused(engine, False, run, probes)
    for name, twin, keys, got in zip(run, twins, probes, found):
        want = c.get_bloom_filter(twin).contains_each(keys)
        np.testing.assert_array_equal(got, want, err_msg=f"found {name}")
        assert got[:10].all()  # an acknowledged add is found
    for twin in twins:
        c.get_bloom_filter(twin).delete()


def test_padding_planes_are_never_written_back(embedded):
    """A run of 3 stacks F0 planes, 13 of them the first filter's plane
    again: the first filter keeps ITS new plane, every record's version
    moves once, and no other record is touched."""
    c = embedded
    engine = c._engine
    run = ["fz:0", "fz:1", "fz:2"]
    others = [f"fz:{i}" for i in range(3, 8)]
    before = {n: (engine.store.get(n).version, np.asarray(engine.store.get(n).arrays["bits"]))
              for n in run + others}
    asked0, stacked0 = CO.planes_counted()
    keys = [np.arange(40, dtype=np.int64) + 10_000 * (i + 1) for i in range(3)]
    CO.fused_bloom_add_async(engine, run, keys)
    assert CO.planes_counted() == (asked0 + 3, stacked0 + F0)
    for name, k in zip(run, keys):
        rec = engine.store.get(name)
        assert rec.version == before[name][0] + 1
        assert c.get_bloom_filter(name).contains_each(k).all()  # fz:0 too
        assert (np.asarray(rec.arrays["bits"]) >= before[name][1]).all()
    for name in others:
        rec = engine.store.get(name)
        assert rec.version == before[name][0]
        np.testing.assert_array_equal(np.asarray(rec.arrays["bits"]), before[name][1])


def test_a_run_longer_than_one_dispatch_is_ineligible_not_wrong(embedded):
    engine = embedded._engine
    run = [f"fz:{i}" for i in range(F0 + 1)]
    with pytest.raises(CO.CoalesceIneligible, match="more filters"):
        CO.fused_bloom_contains_async(engine, run, [np.arange(5, dtype=np.int64)] * (F0 + 1))
    with pytest.raises(CO.CoalesceIneligible, match="more rows"):
        CO.fused_bloom_contains_async(
            engine, run[:2], [np.arange(CO.STACK_ROW_BUCKETS[-1], dtype=np.int64)] * 2)


def test_embedded_batch_cuts_long_runs(embedded):
    """The Batch layer cuts a run of more filters than one stacked dispatch
    holds, and every future gets its own answer."""
    c = embedded
    for i in range(2 * F0 + 2):
        assert c.get_bloom_filter(f"bz:{i}").try_init(CAPACITY, FPP)
    b = c.create_batch()
    futs = []
    for i in range(2 * F0 + 2):
        keys = np.arange(30, dtype=np.int64) + 77_000 + 100 * i
        futs.append((keys, b.get_bloom_filter(f"bz:{i}").add_async(keys)))
    b.execute()
    for i, (keys, fut) in enumerate(futs):
        assert fut.get() == 30
        assert c.get_bloom_filter(f"bz:{i}").contains_each(keys).all()


# -- the grouped fetch -------------------------------------------------------------


def _mixed_parts(rng, n, devices):
    import jax

    makers = [
        lambda: rng.integers(0, 2, 512).astype(bool),
        lambda: rng.integers(0, 2, 64).astype(bool),
        lambda: rng.integers(0, 255, 512).astype(np.uint8),
        lambda: np.int32(rng.integers(-5, 1 << 20)),
        lambda: rng.integers(0, 1 << 31, 8).astype(np.uint32),
        lambda: rng.random((3, 4)).astype(np.float32),
        lambda: rng.integers(0, 1 << 15, 16).astype(np.uint16),
    ]
    host = [makers[rng.integers(len(makers))]() for _ in range(n)]
    return host, [jax.device_put(h, devices[rng.integers(len(devices))]) for h in host]


@pytest.mark.parametrize("n,n_dev", [(1, 1), (2, 1), (3, 2), (5, 1), (17, 3), (70, 2),
                                     (150, 4), (64, 1), (65, 1)])
def test_grouped_fetch_returns_what_it_was_given(devices, n, n_dev):
    """Any number, order and dtype mix of parts, over any number of devices,
    some named by several groups, some already on the host."""
    rng = np.random.default_rng(n * 10 + n_dev)
    host, dev = _mixed_parts(rng, n, devices[:n_dev])
    groups, want = [], []
    for _ in range(n + 3):
        pick = rng.integers(0, n, rng.integers(1, 4))
        groups.append(tuple(dev[i] for i in pick))
        want.append([host[i] for i in pick])
    groups.append((np.arange(3, dtype=np.uint8), dev[0]))  # a host value passes through
    want.append([np.arange(3, dtype=np.uint8), host[0]])
    note = {}
    out = ioplane.gather_device_results(groups, None, note)
    assert len(out) == len(groups)
    for got, exp in zip(out, want):
        assert len(got) == len(exp)
        for g, w in zip(got, exp):
            assert isinstance(g, np.ndarray) and g.dtype == w.dtype and g.shape == np.shape(w)
            np.testing.assert_array_equal(g, w)
    assert note["parts"] == len({id(a) for g in groups for a in g if not isinstance(a, np.ndarray)})
    assert note["fetches"] <= note["parts"]
    # a second fetch of the same kinds, in another order and number: no program
    before = programs()
    order = rng.permutation(len(groups))
    again = ioplane.gather_device_results([groups[i] for i in order][: max(1, len(groups) // 2)])
    assert programs() == before
    np.testing.assert_array_equal(again[0][0], want[order[0]][0])


def test_grouped_fetch_counts_owed_and_fetched_bytes(devices):
    import jax

    shared = jax.device_put(np.arange(256) % 2 == 0, devices[0])   # 256 B, bool
    lone = jax.device_put(np.int32(9), devices[0])                 # 4 B
    more = [jax.device_put(np.int32(i), devices[0]) for i in range(5)]  # 5 x 4 B
    owed0, fetched0 = ioplane.gather_bytes_counted()
    groups = [(shared,), (shared,), (shared,), (lone,)] + [(m,) for m in more]
    out = ioplane.gather_device_results(groups, [10, 20, None, None] + [None] * 5)
    assert [int(o[0]) for o in out[3:]] == [9, 0, 1, 2, 3, 4]
    owed, fetched = ioplane.gather_bytes_counted()
    assert owed - owed0 == 10 + 20 + 256 + 4 + 5 * 4
    # shared crosses once; the six int32 ride one stack of 16
    assert fetched - fetched0 == 256 + 16 * 4


def test_one_part_fetch_runs_the_parents_programs(devices):
    """The one-part path is the historical one: the value as a uint8 stream,
    one transfer.  Shown by program identity: once the parent's expression
    has run for a kind of value, the fetch of another value of that kind
    compiles nothing, and the other way round."""
    import jax
    import jax.numpy as jnp

    def parent(a):  # gather_device_results as PR 25 left it, one part
        was_bool = a.dtype == jnp.bool_
        b = (a.astype(jnp.uint8) if was_bool else a if a.dtype == jnp.uint8
             else jax.lax.bitcast_convert_type(a, jnp.uint8))
        merged = np.asarray(jnp.ravel(b))
        v = np.ascontiguousarray(merged).view(
            np.dtype("uint8" if was_bool else a.dtype.name)).reshape(a.shape)
        return v.astype(bool) if was_bool else v

    rng = np.random.default_rng(5)
    kinds = [lambda: rng.integers(0, 2, 1280).astype(bool),
             lambda: rng.integers(0, 1 << 31, 40).astype(np.uint32),
             lambda: np.int32(rng.integers(0, 1 << 20)),
             lambda: rng.integers(0, 255, 96).astype(np.uint8)]
    for i, make in enumerate(kinds):
        first, second = make(), make()
        a, b = jax.device_put(first, devices[1]), jax.device_put(second, devices[1])
        if i % 2:
            np.testing.assert_array_equal(parent(a), first)
            before = programs()
            got = ioplane.gather_device_results([(b,)])[0][0]
        else:
            got = ioplane.gather_device_results([(b,)])[0][0]
            before = programs()
            np.testing.assert_array_equal(parent(a), first)
        assert programs() == before
        assert got.dtype == second.dtype and got.shape == second.shape
        np.testing.assert_array_equal(got, second)


# -- the served path, four lanes ----------------------------------------------------

TENANTS = 48


class Tenant:
    """The plain reference's copy of one tenant."""

    def __init__(self, m: int, k: int):
        self.bf = RefBank(1, m, k)
        self.a, self.b = RefBitSet(BITS), RefBitSet(BITS)
        self.added = 0


@pytest.fixture(scope="module")
def served():
    from redisson_tpu.server import ServerThread

    with ServerThread(devices=4, workers=8) as st:
        with st.client() as conn:
            tenants = {}
            for t in range(TENANTS):
                bf, ba, bb = names(t)
                fill_a, fill_b = (np.arange(0, BITS, 97 + t) % BITS), (np.arange(5, BITS, 89 + t) % BITS)
                replies = conn.execute_many([
                    ("BF.RESERVE", bf, repr(FPP), CAPACITY),
                    ("SETBITSB", ba, blob4(fill_a)), ("SETBITSB", bb, blob4(fill_b))],
                    timeout=120.0)
                assert not any(isinstance(r, Exception) for r in replies), replies
                if not tenants:
                    info = conn.execute("BF.INFO", bf)
                    geometry = (int(info[info.index(b"Size") + 1]),
                                int(info[info.index(b"Number of hashes") + 1]))
                ref = tenants[t] = Tenant(*geometry)
                ref.a.set_each(fill_a)
                ref.b.set_each(fill_b)
            yield st, conn, tenants


def by_verb_frame(rng, tenants, picked, n_add, keys_per=40, set_bits=60):
    """One frame as benchmark/generators/cluster_mixed.py groups it: the adds,
    the probes (half of each present once its tenant has adds), then per
    tenant SETBITSB, BITOP OR, BITOP XOR, BITCOUNT.  Returns (commands,
    checks): checks[i](reply) asserts reply i against the reference, and must
    be called in order."""
    cmds, checks = [], []

    def eq(what, t, want):
        def check(r):
            got = (np.frombuffer(r, np.uint8).astype(bool) if isinstance(want, np.ndarray)
                   else int(r))
            assert np.array_equal(got, want), f"{what} of tenant {t}"
        return check

    plan = []
    for j, t in enumerate(picked):
        ref = tenants[t]
        add = None
        if j < n_add:
            add = (t << 32) + ref.added + np.arange(keys_per, dtype=np.int64)
        probe = (t << 32) + (1 << 30) + rng.integers(0, 1 << 20, keys_per)
        if ref.added:
            probe[0::2] = (t << 32) + rng.integers(0, ref.added, (keys_per + 1) // 2)
        plan.append((t, add, probe.astype(np.int64),
                     rng.integers(0, BITS, set_bits).astype(np.int32)))
    zero = np.zeros
    for t, add, _p, _b in plan:
        if add is not None:
            cmds.append(("BF.MADD64", names(t)[0], blob8(add)))
            checks.append(eq("newly-added flags", t, tenants[t].bf.add(zero(len(add), np.int32), add)))
            tenants[t].added += len(add)
    for t, _a, probe, _b in plan:
        cmds.append(("BF.MEXISTS64", names(t)[0], blob8(probe)))
        checks.append(eq("found vector", t, tenants[t].bf.contains(zero(len(probe), np.int32), probe)))
    for t, _a, _p, bits in plan:
        ref = tenants[t]
        _f, a, b = names(t)
        cmds += [("SETBITSB", a, blob4(bits)), ("BITOP", "OR", a, a, b),
                 ("BITOP", "XOR", b, b, a), ("BITCOUNT", a)]
        checks.append(eq("previous bits", t, ref.a.set_each(bits)))
        ref.a.or_(ref.b)
        checks.append(eq("OR length", t, ref.a.byte_length()))
        ref.b.xor(ref.a)
        checks.append(eq("XOR length", t, ref.b.byte_length()))
        checks.append(eq("BITCOUNT", t, ref.a.count()))
    return cmds, checks


def send_in_pieces(conn, cmds, cuts):
    """The frame as the server meets it when a socket read cuts it: each
    piece is parsed, fused and fetched on its own."""
    replies, at = [], 0
    for cut in sorted(set(cuts)) + [len(cmds)]:
        if cut > at:
            replies += conn.execute_many(cmds[at:cut], timeout=120.0)
            at = cut
    for r in replies:
        assert not isinstance(r, Exception), r
    return replies


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_by_verb_frames_equal_the_plain_reference(served, seed):
    """Seeded frames over many tenants through four lanes, reply for reply."""
    st, conn, tenants = served
    rng = np.random.default_rng(seed)
    for _ in range(3):
        picked = [int(t) for t in rng.permutation(TENANTS)[: rng.integers(8, 40)]]
        cmds, checks = by_verb_frame(rng, tenants, picked, int(rng.integers(1, 7)))
        cuts = rng.integers(1, len(cmds), rng.integers(0, 3))
        for reply, check in zip(send_in_pieces(conn, cmds, cuts), checks):
            check(reply)
    info = st.server.info_text()
    for key in ("host_colocations:0", "merge_fallbacks:0", "lane_faults:0",
                "lanes_quarantined:0"):
        assert key in info


def test_frames_of_any_composition_compile_nothing_after_one(served):
    """One warm-up frame over every lane, then twenty frames whose tenant
    count a device, piece cuts and add/probe mix are random: the process
    builds no XLA program."""
    st, conn, tenants = served
    rng = np.random.default_rng(26)
    placement = st.server.engine.placement
    assert {placement.device_id_for_name(names(t)[0]) for t in range(TENANTS)} == \
        {d.id for d in placement.devices}
    warm, checks = by_verb_frame(rng, tenants, list(range(TENANTS)), 6)
    for reply, check in zip(send_in_pieces(conn, warm, []), checks):
        check(reply)
    before = programs()
    for i in range(20):
        picked = [int(t) for t in rng.permutation(TENANTS)[: rng.integers(1, TENANTS + 1)]]
        cmds, checks = by_verb_frame(rng, tenants, picked, int(rng.integers(0, 9)))
        cuts = rng.integers(1, len(cmds), rng.integers(0, 4))
        for reply, check in zip(send_in_pieces(conn, cmds, cuts), checks):
            check(reply)
        assert programs() == before, f"frame {i} ({len(picked)} tenants, cuts {sorted(cuts)})"


def test_every_lane_holds_its_records_committed(served):
    """The default device's lane too: a program is compiled for where its
    operands are committed, and one warm-up has to stand for all four."""
    st, conn, tenants = served
    engine = st.server.engine
    lanes = {d.id for d in engine.placement.devices}
    seen = set()
    for t in range(TENANTS):
        for name in names(t):
            for arr in engine.store.get(name).arrays.values():
                assert arr.committed, name
                seen |= {d.id for d in arr.devices()}
    assert seen == lanes


def _metrics(conn) -> dict:
    out = {}
    for line in bytes(conn.execute("METRICS")).decode().splitlines():
        if line and not line.startswith("#"):
            name, _, val = line.rpartition(" ")
            out[name] = float(val)
    return out


def test_metrics_count_what_the_fixed_shapes_pad(served):
    """A known sequence: one frame of five probes of 40 keys on ONE lane."""
    st, conn, tenants = served
    placement = st.server.engine.placement
    home = placement.device_id_for_name(names(0)[0])
    mine = [t for t in range(TENANTS)
            if placement.device_id_for_name(names(t)[0]) == home][:5]
    assert len(mine) == 5
    keys = np.arange(40, dtype=np.int64)
    m0 = _metrics(conn)
    replies = conn.execute_many([("BF.MEXISTS64", names(t)[0], blob8(keys)) for t in mine])
    assert all(len(r) == 40 for r in replies)
    m1 = _metrics(conn)

    def delta(name):
        return m1[name] - m0[name]

    assert delta("rtpu_coalesce_planes_asked_total") == 5
    assert delta("rtpu_coalesce_planes_stacked_total") == F0
    assert delta("rtpu_kernel_rows_valid_total") == 200
    assert delta("rtpu_kernel_rows_issued_total") == CO.stacked_row_bucket(200)
    # five replies of 40 rows each, cut from one found vector of the bucket
    assert delta("rtpu_gather_bytes_owed_total") == 200
    assert delta("rtpu_gather_bytes_fetched_total") == CO.stacked_row_bucket(200)


def test_kernel_and_readback_spans_say_what_they_rode(served):
    st, conn, tenants = served
    rng = np.random.default_rng(3)
    conn.execute("CONFIG", "SET", "trace-enabled", "yes")
    try:
        conn.execute("TRACE", "RESET")
        cmds, checks = by_verb_frame(rng, tenants, list(range(12)), 3)
        for reply, check in zip(send_in_pieces(conn, cmds, []), checks):
            check(reply)
        frames = conn.execute("TRACE", "GET", 50)
    finally:
        conn.execute("CONFIG", "SET", "trace-enabled", "no")
    spans = [(bytes(s[0]).decode(), {bytes(s[3][i]).decode(): s[3][i + 1]
                                     for i in range(0, len(s[3]) - 1, 2)})
             for f in frames if bytes(f[3]).upper().startswith(b"BF.") for s in f[7]]
    kernels = [a for n, a in spans if n == "kernel"]
    assert kernels and all(int(a["stacked"]) == F0 and 1 <= int(a["members"]) <= F0
                           for a in kernels)
    assert sum(int(a["members"]) for a in kernels) == 3 + 12
    grouped = [a for n, a in spans if n == "readback" and int(a.get("grouped", 0))]
    assert grouped and all({"parts", "fetches", "bucket"} <= set(a) for a in grouped)
    assert all(int(a["fetches"]) <= int(a["parts"]) <= int(a["grouped"]) for a in grouped)
    assert max(int(a["bucket"]) for a in grouped) in ioplane.GATHER_STACK_RUNGS



def test_a_frame_on_one_lane_is_one_job_not_a_hop_a_command(served):
    """What a socket read leaves at the end of a long frame — one tenant's
    last commands, all on one lane — is dispatched as one bucket by one
    worker: on a busy pool a hop a command was a second of queueing."""
    st, conn, tenants = served
    rng = np.random.default_rng(9)
    cmds, checks = by_verb_frame(rng, tenants, [5], 1)  # six commands, one tenant
    conn.execute("CONFIG", "SET", "trace-enabled", "yes")
    try:
        conn.execute("TRACE", "RESET")
        for reply, check in zip(send_in_pieces(conn, cmds, []), checks):
            check(reply)
        frames = conn.execute("TRACE", "GET", 20)
    finally:
        conn.execute("CONFIG", "SET", "trace-enabled", "no")
    frame = next(f for f in frames if int(f[4]) == len(cmds))
    hops = [{bytes(s[3][i]).decode(): s[3][i + 1] for i in range(0, len(s[3]) - 1, 2)}
            for s in frame[7] if bytes(s[0]) == b"hop"]
    assert [bytes(h["to"]).decode() for h in hops].count("dispatch") == 1, hops

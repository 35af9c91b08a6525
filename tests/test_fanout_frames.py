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


# -- the wave planner ------------------------------------------------------------------


def entry(cmd):
    from redisson_tpu.core.coalesce import wave_entry

    return wave_entry([a if isinstance(a, bytes) else str(a).encode() for a in cmd])


def tenant_cmds(t, n_bits=50):
    _f, a, b = names(t)
    return [("SETBITSB", a, blob4(np.arange(n_bits))), ("BITOP", "OR", a, a, b),
            ("BITOP", "XOR", b, b, a), ("BITCOUNT", a)]


def check_waves(cmds, waves):
    """What every plan must hold: each command in exactly one wave; a wave of
    one form, within the stacked shape; two commands on one key, one of them
    writing it, keep their frame order in wave order."""
    entries = [entry(c) for c in cmds]
    where = {}
    for w, (form, members) in enumerate(waves):
        assert members == sorted(members) and members
        assert form is not None or len(members) == 1
        assert len(members) <= F0
        assert len(members) == 1 or sum(entries[i][3] for i in members) <= CO.STACK_ROW_BUCKETS[-1]
        for i in members:
            assert i not in where and entries[i][0] == form
            where[i] = w
    assert sorted(where) == list(range(len(cmds)))
    for j, (_fj, wj, rj, _nj) in enumerate(entries):
        for i in range(j):
            _fi, wi, ri, _ni = entries[i]
            if set(wi) & (set(wj) | set(rj)) or set(ri) & set(wj):
                assert where[i] < where[j], (cmds[i][:2], cmds[j][:2])
    return where


def forms_of(waves):
    return [form[0].decode() + (" " + form[1].decode() if form[0] == b"BITOP" else "")
            if form else None for form, _m in waves]


def test_a_by_verb_frame_over_a_lane_is_six_waves():
    """The cell's frame, one lane's share: adds, probes, then per tenant set,
    or, xor, count, interleaved as a client flushing one batch writes them."""
    ts = list(range(12))
    cmds = [("BF.MADD64", names(t)[0], blob8(np.arange(40))) for t in ts[:3]]
    cmds += [("BF.MEXISTS64", names(t)[0], blob8(np.arange(40))) for t in ts]
    for t in ts:
        cmds += tenant_cmds(t)
    waves = CO.plan_waves([entry(c) for c in cmds])
    check_waves(cmds, waves)
    assert forms_of(waves) == ["BF.MADD64", "BF.MEXISTS64", "SETBITSB", "BITOP OR",
                               "BITOP XOR", "BITCOUNT"]
    assert [len(m) for _f, m in waves] == [3, 12, 12, 12, 12, 12]


@pytest.mark.parametrize("case", ["tenant twice", "shared keys", "bitop across tenants",
                                  "cut at 16", "ineligible between", "readers share"])
def test_wave_plans_keep_per_key_order(case):
    _f0, a0, b0 = names(0)
    _f1, a1, b1 = names(1)
    if case == "tenant twice":
        cmds = tenant_cmds(0) + tenant_cmds(1) + tenant_cmds(0)
        waves = CO.plan_waves([entry(c) for c in cmds])
        where = check_waves(cmds, waves)
        # the second visit's commands each run after the first visit's last
        assert where[8] > where[3] and len(waves) == 8
        assert where[4] == where[0]  # tenant 1 rides tenant 0's first waves
    elif case == "shared keys":
        cmds = [("SETBITSB", a0, blob4([1])), ("SETBITSB", a0, blob4([2])),
                ("BITCOUNT", a0), ("SETBITSB", a0, blob4([3])), ("BITCOUNT", a0)]
        waves = CO.plan_waves([entry(c) for c in cmds])
        check_waves(cmds, waves)
        assert [m for _f, m in waves] == [[0], [1], [2], [3], [4]]
    elif case == "bitop across tenants":
        cmds = [("SETBITSB", a0, blob4([1])), ("SETBITSB", a1, blob4([1])),
                ("BITOP", "OR", a0, a0, a1),    # reads a1, writes a0
                ("BITOP", "OR", a1, a1, b1),    # writes a1: after the wave that read it
                ("SETBITSB", b0, blob4([1])),   # touches nothing above: first set wave
                ("BITCOUNT", a1)]
        waves = CO.plan_waves([entry(c) for c in cmds])
        where = check_waves(cmds, waves)
        assert where[0] == where[1] == where[4] == 0
        assert where[2] < where[3] < where[5]
    elif case == "cut at 16":
        cmds = [("BITCOUNT", names(t)[1]) for t in range(2 * F0 + 3)]
        waves = CO.plan_waves([entry(c) for c in cmds])
        check_waves(cmds, waves)
        assert [len(m) for _f, m in waves] == [F0, F0, 3]
    elif case == "ineligible between":
        cmds = [("SETBITSB", a0, blob4([1])), ("BITOP", "AND", a0, a0, b0),
                ("SETBIT", a1, 5, 1), ("SETBITSB", a1, blob4([2])),
                ("BITOP", "NOT", b1, a1), ("GETBITSB", a0, blob4([1])),
                ("SETBITSB", b0, blob4([3]))]
        waves = CO.plan_waves([entry(c) for c in cmds])
        where = check_waves(cmds, waves)
        assert forms_of(waves).count(None) == 4
        assert where[6] == where[3] > where[1]  # the AND read b0: its set waits, then rides a1's
    else:  # two probes of one filter still share a wave, as a run held them
        cmds = [("BF.MEXISTS64", names(0)[0], blob8([1, 2])),
                ("BF.MEXISTS64", names(0)[0], blob8([3])),
                ("BF.MADD64", names(0)[0], blob8([4])),
                ("BF.MEXISTS64", names(0)[0], blob8([4]))]
        waves = CO.plan_waves([entry(c) for c in cmds])
        check_waves(cmds, waves)
        assert [m for _f, m in waves] == [[0, 1], [2], [3]]


@pytest.mark.parametrize("seed", range(8))
def test_random_buckets_plan_to_ordered_waves_and_bf_runs_no_shorter(seed):
    """Random buckets over few tenants (many shared keys): the invariants
    hold, and every run of consecutive BF commands on different filters that
    coalescible_frame_runs + plan_stacked_chunks dispatch together today
    still shares one wave."""
    rng = np.random.default_rng(seed)
    cmds = []
    for _ in range(int(rng.integers(5, 120))):
        t = int(rng.integers(0, 40))
        bf, a, b = names(t)
        kind = int(rng.integers(0, 9))
        n = int(rng.choice([3, 40, 300, 2000, 9000]))
        cmds.append([
            ("BF.MADD64", bf, blob8(np.arange(n))), ("BF.MEXISTS64", bf, blob8(np.arange(n))),
            ("SETBITSB", a, blob4(np.arange(n))), ("BITOP", "OR", a, a, b),
            ("BITOP", "XOR", b, b, a), ("BITCOUNT", a), ("BITOP", "AND", a, a, b),
            ("SETBIT", a, 3, 1), ("BITOP", "OR", a, b, names((t + 1) % 40)[1]),
        ][kind])
    enc = [[x if isinstance(x, bytes) else str(x).encode() for x in c] for c in cmds]
    waves = CO.plan_waves([entry(c) for c in cmds])
    where = check_waves(cmds, waves)
    for s, e in CO.coalescible_frame_runs(enc):
        for cs, ce in CO.plan_stacked_chunks([len(c[2]) // 8 for c in enc[s:e]]):
            chunk = range(s + cs, s + ce)
            keys = [enc[i][1] for i in chunk]
            if len(set(keys)) == len(keys) and not any(
                    k in {c[1] for c in enc[:s]} for k in keys):
                # different filters, untouched before the run: one wave
                assert len({where[i] for i in chunk}) == 1, (s, e, cs, ce)


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

TENANTS = 72   # 16+ a lane: a lane's waves are cut at STACK_PLANES


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


@pytest.mark.parametrize("how", ["pieces", "whole requests"])
def test_frames_of_any_composition_compile_nothing_after_one(served, monkeypatch, how):
    """One warm-up frame over every lane, then twenty frames whose tenant
    count a device, piece cuts and add/probe mix are random — and pieces of
    ONE command, what a socket read can leave of any frame: the process builds no
    XLA program.  Nor for whole requests of the benchmark's shape (64
    tenants over the lanes, 100 keys a probe, 500 bit indexes: 190 KB in one
    write), which the read loop takes as one frame (ISSUE 29): a lane's
    waves of 16 members and 1,600 rows, a grouped fetch of a whole request's
    parts."""
    st, conn, tenants = served
    rng = np.random.default_rng(26)
    placement = st.server.engine.placement
    assert {placement.device_id_for_name(names(t)[0]) for t in range(TENANTS)} == \
        {d.id for d in placement.devices}
    a_lane = np.bincount([placement.device_id_for_name(names(t)[0]) for t in range(TENANTS)])
    assert a_lane.max() > F0  # a lane's waves of the whole-population frame are cut
    warm, checks = by_verb_frame(rng, tenants, list(range(TENANTS)), 6)
    for reply, check in zip(send_in_pieces(conn, warm, []), checks):
        check(reply)
    before = programs()
    if how == "whole requests":
        planned = []
        plan = st.server._plan_frame
        monkeypatch.setattr(st.server, "_plan_frame",
                            lambda ctx, cmds, shed: planned.append(len(cmds)) or plan(ctx, cmds, shed))
        for i in range(12):
            picked = [int(t) for t in rng.permutation(TENANTS)[:64]]
            cmds, checks = by_verb_frame(rng, tenants, picked, 6, keys_per=100, set_bits=500)
            assert len(cmds) == 326
            for reply, check in zip(send_in_pieces(conn, cmds, []), checks):
                check(reply)
            assert programs() == before, f"request {i}"
        # 64 KiB reads made four frames of each; what a read loop that runs
        # before the last byte has landed leaves is a frame of its own
        assert sum(planned) == 12 * 326 and len(planned) <= 18, planned
        return
    for i in range(20):
        picked = [int(t) for t in rng.permutation(TENANTS)[: rng.integers(1, TENANTS + 1)]]
        cmds, checks = by_verb_frame(rng, tenants, picked, int(rng.integers(0, 9)))
        cuts = list(rng.integers(1, len(cmds), rng.integers(0, 4)))
        lone = int(rng.integers(0, len(cmds)))  # one command alone, of any verb
        cuts += [lone, lone + 1]
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


def _span_attrs(frames):
    return [(bytes(s[0]).decode(), {bytes(s[3][i]).decode(): s[3][i + 1]
                                    for i in range(0, len(s[3]) - 1, 2)})
            for f in frames if int(f[4]) > 1 for s in f[7]]


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
    spans = _span_attrs(frames)
    kernels = [a for n, a in spans if n == "kernel"]
    assert kernels and all(int(a["stacked"]) == F0 and 1 <= int(a["members"]) <= F0
                           for a in kernels)
    rode = {}
    for a in kernels:
        verb = bytes(a["verb"]).decode()
        rode[verb] = rode.get(verb, 0) + int(a["members"])
    # every command of the frame rode a stacked dispatch, wave by wave
    assert rode == {"BF.MADD64": 3, "BF.MEXISTS64": 12, "SETBITSB": 12, "BITOP OR": 12,
                    "BITOP XOR": 12, "BITCOUNT": 12}
    # at most one wave a form and lane: 12 tenants over 4 lanes
    assert len(kernels) <= 6 * 4
    assert not [n for n, _a in spans if n == "kernel.member"]
    members = [k for a in kernels for k in bytes(a["keys"]).decode().split(",")]
    assert sorted(members) == sorted(
        [names(t)[0] for t in range(3)] + [names(t)[0] for t in range(12)]
        + [names(t)[1] for t in range(12)] * 3 + [names(t)[2] for t in range(12)])
    grouped = [a for n, a in spans if n == "readback" and int(a.get("grouped", 0))]
    assert grouped and all({"parts", "fetches", "bucket"} <= set(a) for a in grouped)
    assert all(int(a["fetches"]) <= int(a["parts"]) <= int(a["grouped"]) for a in grouped)
    assert max(int(a["bucket"]) for a in grouped) in ioplane.GATHER_STACK_RUNGS
    # a lane owes a handful of device values, not three a tenant
    assert max(int(a["parts"]) for a in grouped) <= 6 * 4


def test_metrics_count_offered_and_fused_commands(served):
    """A known frame on ONE lane: five tenants' set, or, xor, count (20
    commands with a stacked form), one BITOP AND and one SETBIT (none)."""
    st, conn, tenants = served
    placement = st.server.engine.placement
    home = placement.device_id_for_name(names(0)[0])
    mine = [t for t in range(TENANTS)
            if placement.device_id_for_name(names(t)[0]) == home][:5]
    rng = np.random.default_rng(8)
    cmds, checks = by_verb_frame(rng, tenants, mine, 0)
    cmds, checks = cmds[len(mine):], checks[len(mine):]  # the bitset part alone
    _f, a, b = names(mine[0])
    cmds += [("BITOP", "AND", a, a, b), ("SETBIT", a, 7, 1)]
    m0 = _metrics(conn)
    replies = send_in_pieces(conn, cmds, [])
    m1 = _metrics(conn)
    for reply, check in zip(replies, checks):
        check(reply)
    ref = tenants[mine[0]]  # the two per-record commands, after the tenant's four
    ref.a.bits &= ref.b.bits
    assert int(replies[-2]) == ref.a.byte_length()
    assert int(replies[-1]) == int(ref.a.bits[7])
    ref.a.bits[7] = True

    def delta(name):
        return m1[name] - m0[name]

    assert delta("rtpu_coalesce_cmds_offered_total") == 22
    assert delta("rtpu_coalesce_cmds_fused_total") == 20
    assert delta("rtpu_coalesce_planes_asked_total") == 20
    assert delta("rtpu_coalesce_planes_stacked_total") == 4 * F0  # four waves


def _hops_of(conn, cmds, send):
    """The `to` of each `hop` span of the frame that carried `cmds`."""
    conn.execute("CONFIG", "SET", "trace-enabled", "yes")
    try:
        conn.execute("TRACE", "RESET")
        send()
        frames = conn.execute("TRACE", "GET", 20)
    finally:
        conn.execute("CONFIG", "SET", "trace-enabled", "no")
    verb = cmds[0][0].encode()
    frame = next(f for f in frames if int(f[4]) == len(cmds) and bytes(f[3]).startswith(verb))
    hops = [{bytes(s[3][i]).decode(): s[3][i + 1] for i in range(0, len(s[3]) - 1, 2)}
            for s in frame[7] if bytes(s[0]) == b"hop"]
    return [bytes(h["to"]).decode() for h in hops]


@pytest.mark.parametrize("case", ["one lane of four", "no placement, one command"])
def test_a_frame_on_one_lane_is_one_job_not_a_hop_a_command(served, three_servers, case):
    """What a socket read leaves at the end of a long frame — one tenant's
    last commands, all on one lane — is dispatched as one bucket by one
    worker: on a busy pool a hop a command was a second of queueing.  And
    the frame of the bulk cells, one command with no placement, is two
    hops: its dispatch and the force of its reply."""
    if case == "one lane of four":
        st, conn, tenants = served
        rng = np.random.default_rng(9)
        cmds, checks = by_verb_frame(rng, tenants, [5], 1)  # six commands, one tenant

        def send():
            for reply, check in zip(send_in_pieces(conn, cmds, []), checks):
                check(reply)

        assert _hops_of(conn, cmds, send).count("dispatch") == 1
        return
    with three_servers["no placement"].client() as conn:
        conn.execute("BF.RESERVE", "hop{x}", repr(FPP), CAPACITY)
        cmds = [("BF.MEXISTS64", "hop{x}", blob8(np.arange(16)))]
        assert _hops_of(conn, cmds, lambda: send_in_pieces(conn, cmds, [])) == ["dispatch", "force"]


# -- waves against per-record sequential dispatch ---------------------------------------


@pytest.fixture(scope="module")
def plain():
    """A server with no placement: its frames take the sequential loop, every
    bitset command its per-record handler."""
    from redisson_tpu.server import ServerThread

    with ServerThread() as st:
        with st.client() as conn:
            yield st, conn


MIXED = 24       # tenants {m0}..{m23}, made by the test on both servers
GROWN = 2 * BITS


def mixed_names(t: int):
    tag = "{m%d}" % t
    return "bf" + tag, "ba" + tag, "bb" + tag, "bc" + tag


def mixed_frame(rng, n_cmds: int):
    """Commands of every kind a bucket can hold, over few tenants: the four
    stacked forms among what they do not cover."""
    cmds = []
    for _ in range(n_cmds):
        t = int(rng.integers(0, MIXED))
        bf, a, b, c = mixed_names(t)
        idx = rng.integers(0, BITS, int(rng.choice([1, 30, 200, 700]))).astype(np.int32)
        cmds.append([
            ("SETBITSB", a, blob4(idx)), ("SETBITSB", b, blob4(idx[:20])),
            ("BITOP", "OR", a, a, b), ("BITOP", "XOR", b, b, a), ("BITOP", "OR", a, b),
            ("BITCOUNT", a), ("BITCOUNT", b),
            ("BITOP", "AND", a, a, b), ("BITOP", "NOT", b, a),
            ("SETBITSB", a, blob4([3, BITS + 77])),               # grows the plane
            ("SETBITSB", a, blob4([5, 2**31 - 1])),               # out of range
            ("SETBITSB", a, blob4([-4, 9])),                      # negative
            ("SETBITSB", a, b""),                                 # no index
            ("SETBITSB", a, b"\x01\x02\x03"),                     # not a blob of int32
            ("SETBIT", a, int(idx[0]), 1), ("GETBITSB", a, blob4(idx[:50])),
            ("BITCOUNT", c),                                      # may not exist yet
            ("BITCOUNT", bf),                                     # not a bitset
            ("BITOP", "OR", a, a, bf),                            # wrong-typed source
            ("BITOP", "OR", c, c, a),                             # dest may not exist yet
            ("BITOP", "XOR", a, a, c),                            # source may not exist yet
            ("BITOP", "OR", a, a, b, c),                          # two other sources
            ("BITOP", "XOR", a, a),                               # none
            ("BF.MADD64", bf, blob8(idx.astype(np.int64) + (t << 32))),
            ("BF.MEXISTS64", bf, blob8(idx.astype(np.int64) + (t << 32))),
        ][int(rng.integers(0, 25))])
    return cmds


def plain_reply(r):
    return ("error", str(r)) if isinstance(r, Exception) else r


@pytest.mark.parametrize("seed", [271, 272, 273, 274])
def test_buckets_of_random_composition_equal_per_record_dispatch(served, plain, seed):
    """The same frames to the four-lane server (waves) and to a server whose
    frames take the sequential loop: reply for reply — error texts too — and
    afterwards plane for plane and version for version."""
    st, conn, _tenants = served
    pst, pconn = plain
    rng = np.random.default_rng(seed)
    fill = []
    for t in range(MIXED):
        bf, a, b, c = mixed_names(t)
        fill += [("BF.RESERVE", bf, repr(FPP), CAPACITY),
                 ("SETBITSB", a, blob4(rng.integers(0, BITS, 150))),
                 ("SETBITSB", b, blob4(rng.integers(0, BITS, 150)))]
    frames = [fill] + [mixed_frame(rng, int(rng.integers(1, 90))) for _ in range(6)]
    offered0, fused0 = CO.cmds_counted()
    for k, frame in enumerate(frames):
        got = conn.execute_many(frame, timeout=180.0)
        want = pconn.execute_many(frame, timeout=180.0)
        for i, (g, w) in enumerate(zip(got, want)):
            assert plain_reply(g) == plain_reply(w), (k, i, frame[i][:3])
    offered, fused = CO.cmds_counted()
    n_cmds = sum(len(f) for f in frames)
    # both kinds were met: commands that rode a wave, commands left per record
    # (the counters are the process's: the plain server's BF runs count too)
    assert 0.3 * n_cmds < fused - fused0 < offered - offered0
    for t in range(MIXED):
        for name in mixed_names(t)[1:]:
            rec, prec = st.server.engine.store.get(name), pst.server.engine.store.get(name)
            assert (rec is None) == (prec is None), name
            if rec is not None:
                assert rec.meta["nbits"] == prec.meta["nbits"] and rec.version == prec.version, name
                np.testing.assert_array_equal(np.asarray(rec.arrays["bits"]),
                                              np.asarray(prec.arrays["bits"]), err_msg=name)


def _lane_mates(st, n):
    placement = st.server.engine.placement
    home = placement.device_id_for_name(names(0)[0])
    mine = [t for t in range(TENANTS) if placement.device_id_for_name(names(t)[0]) == home]
    assert len(mine) >= n
    return mine[:n]


def test_a_bitset_waves_padding_is_never_written_back(served):
    """A set wave and an or wave of three on one lane: the three records get
    their new planes, their versions move once a command, and no other record
    of the lane is touched — the thirteen padding planes are stand-ins."""
    st, conn, tenants = served
    engine = st.server.engine
    mine = _lane_mates(st, 8)
    keys = [n for t in mine for n in names(t)[1:]]
    before = {n: (engine.store.get(n).version, np.asarray(engine.store.get(n).arrays["bits"]))
              for n in keys}
    cmds = []
    for t in mine[:3]:
        cmds += [("SETBITSB", names(t)[1], blob4([1, 2, 3])), ("BITOP", "OR", names(t)[1], names(t)[2])]
    asked0, stacked0 = CO.planes_counted()
    replies = send_in_pieces(conn, cmds, [])
    assert CO.planes_counted() == (asked0 + 6, stacked0 + 2 * F0)
    for t, old, length in zip(mine[:3], replies[0::2], replies[1::2]):
        ref = tenants[t]
        np.testing.assert_array_equal(np.frombuffer(old, np.uint8).astype(bool),
                                      ref.a.set_each(np.array([1, 2, 3])))
        ref.a.or_(ref.b)
        assert int(length) == ref.a.byte_length()
    for t in mine:
        for which, name in zip("ab", names(t)[1:]):
            rec = engine.store.get(name)
            moved = 2 if (t in mine[:3] and which == "a") else 0
            assert rec.version == before[name][0] + moved, name
            np.testing.assert_array_equal(
                np.asarray(rec.arrays["bits"])[:BITS].astype(bool),
                getattr(tenants[t], which).bits, err_msg=name)
            if not moved:
                np.testing.assert_array_equal(np.asarray(rec.arrays["bits"]), before[name][1])


@pytest.mark.parametrize("form", ["SETBITSB", "BITOP"])
def test_a_failed_writing_wave_replies_errors_and_applies_nothing_twice(served, monkeypatch, form):
    """The stacked program fails under a writing wave: every member replies
    an error, nothing is dispatched a second time (no per-record retry), and
    the records are as they were."""
    from redisson_tpu.core import kernels as K

    st, conn, tenants = served
    engine = st.server.engine
    mine = _lane_mates(st, 4)
    calls = []

    def boom(*args, **kw):
        calls.append(1)
        raise ValueError("boom")

    def no_per_record(*args, **kw):
        raise AssertionError("a failed writing wave was dispatched again, per record")

    monkeypatch.setattr(K, "bitset_stack_set" if form == "SETBITSB" else "bitset_stack_op", boom)
    monkeypatch.setattr(K, "bitset_set", no_per_record)
    monkeypatch.setattr(K, "bitset_or", no_per_record)
    if form == "SETBITSB":
        cmds = [("SETBITSB", names(t)[1], blob4([11, 12])) for t in mine]
    else:
        cmds = [("BITOP", "OR", names(t)[1], names(t)[1], names(t)[2]) for t in mine]
    before = {names(t)[1]: (engine.store.get(names(t)[1]).version,
                            np.asarray(engine.store.get(names(t)[1]).arrays["bits"])) for t in mine}
    replies = conn.execute_many(cmds, timeout=60.0)
    assert len(calls) == 1
    assert [str(r) for r in replies] == ["ERR internal: ValueError: boom"] * len(mine)
    for name, (version, plane) in before.items():
        rec = engine.store.get(name)
        assert rec.version == version
        np.testing.assert_array_equal(np.asarray(rec.arrays["bits"]), plane)
    monkeypatch.undo()
    # the lane serves on: the same commands now ride
    replies = send_in_pieces(conn, cmds, [])
    for t, r in zip(mine, replies):
        ref = tenants[t]
        if form == "SETBITSB":
            np.testing.assert_array_equal(np.frombuffer(r, np.uint8).astype(bool),
                                          ref.a.set_each(np.array([11, 12])))
        else:
            ref.a.or_(ref.b)
            assert int(r) == ref.a.byte_length()


def test_a_failed_count_wave_falls_back_to_per_record(served, monkeypatch):
    from redisson_tpu.core import kernels as K

    st, conn, tenants = served
    mine = _lane_mates(st, 4)

    def boom(*args, **kw):
        raise ValueError("boom")

    monkeypatch.setattr(K, "bitset_stack_popcount", boom)
    replies = send_in_pieces(conn, [("BITCOUNT", names(t)[1]) for t in mine], [])
    assert [int(r) for r in replies] == [tenants[t].a.count() for t in mine]


def test_a_lane_that_refuses_a_bucket_replies_tryagain_in_frame_position(served):
    """A kernel-launch fault met at the lane's gate (ISSUE 19): none of the
    bucket's commands ran, each replies the retryable fault, the other
    lanes' buckets serve, and the connection lives."""
    from redisson_tpu.chaos.faults import FaultSchedule
    from redisson_tpu.net.client import install_fault_plane

    st, conn, tenants = served
    placement = st.server.engine.placement
    picked = list(range(8))
    cmds = [("BITCOUNT", names(t)[1]) for t in picked]
    sched = FaultSchedule(0)
    sched.add("device_kernel", after=0, count=1)  # the first lane to dispatch
    prev = install_fault_plane(sched.plane())
    try:
        replies = conn.execute_many(cmds, timeout=60.0)
    finally:
        install_fault_plane(prev)
    refused = {placement.device_id_for_name(names(t)[1])
               for t, r in zip(picked, replies) if isinstance(r, Exception)}
    assert len(refused) == 1  # one lane's bucket, whole
    for t, r in zip(picked, replies):
        if placement.device_id_for_name(names(t)[1]) in refused:
            assert str(r).startswith("TRYAGAIN device fault"), r
        else:
            assert int(r) == tenants[t].a.count()
    again = send_in_pieces(conn, cmds, [])
    assert [int(r) for r in again] == [tenants[t].a.count() for t in picked]


def test_a_kernel_launch_fault_is_the_same_tryagain_per_record_and_serial(served):
    """One translation of a failed dispatch: the fault replies the same
    retryable text from a member of a wave that went per record (armed, the
    chaos plane sends every bitset command there) and from a serial command
    refused at its lane's gate."""
    from redisson_tpu.chaos.faults import FaultSchedule
    from redisson_tpu.net.client import install_fault_plane

    st, conn, tenants = served
    mine = _lane_mates(st, 3)
    home = st.server.engine.placement.device_id_for_name(names(mine[0])[1])
    cmds = [("BITCOUNT", names(t)[1]) for t in mine]

    def faulted(after, send):
        sched = FaultSchedule(0)
        sched.add("device_kernel", port=home, after=after, count=1)
        prev = install_fault_plane(sched.plane())
        try:
            return send()
        finally:
            install_fault_plane(prev)

    def serial():  # after ASKING a frame is planned serial: one command, one job
        assert conn.execute("ASKING") == b"OK"
        return conn.execute_many(cmds[:1], timeout=60.0)

    streak = ioplane.set_quarantine_after(100)  # this file's faults in a row must not quarantine the lane
    try:
        # the lane's dispatch stream: 0 the bucket at its gate, 1 its first member
        in_bucket = faulted(1, lambda: conn.execute_many(cmds, timeout=60.0))
        alone = faulted(0, serial)
    finally:
        ioplane.set_quarantine_after(streak)
    assert isinstance(in_bucket[0], Exception)
    assert [int(r) for r in in_bucket[1:]] == [tenants[t].a.count() for t in mine[1:]]
    assert str(in_bucket[0]) == str(alone[0]) == "TRYAGAIN device fault during dispatch; retry"
    again = send_in_pieces(conn, cmds, [])  # a clean fetch ends the lane's streak
    assert [int(r) for r in again] == [tenants[t].a.count() for t in mine]


# -- one frame executor: the same frames, placed or not --------------------------------


@pytest.fixture(scope="module")
def three_servers():
    from contextlib import ExitStack

    from redisson_tpu.server import ServerThread

    with ExitStack() as stack:
        yield {name: stack.enter_context(ServerThread(workers=4, **kw))
               for name, kw in (("no placement", {}), ("one device", {"devices": 1}),
                                ("eight devices", {"devices": "all"}))}


def _converse(st, frames):
    """One connection; each frame sent whole, its replies' raw bytes read back."""
    import socket

    from redisson_tpu.net import resp

    out = []
    parser = resp.RespParser(use_native=False)
    with socket.create_connection((st.server.host, st.server.port), timeout=60) as s:
        for frame in frames:
            s.sendall(b"".join(c if isinstance(c, bytes) else resp.encode_command_python(*c)
                               for c in frame))
            got, n = b"", 0
            while n < len(frame):
                data = s.recv(1 << 16)
                assert data, "the server closed the connection"
                got += data
                n += len(parser.feed(data))
            assert n == len(frame)
            out.append(got)
    return out


def _frames_of(case):
    """The frames of one case, and read-backs of everything they touched."""
    tag = case.replace(" ", "")
    a, b, c = ("%s{%s}:%d" % (case[:2], tag, i) for i in range(3))
    bits, string = "bits{%s}" % tag, "str{%s}" % tag
    k = [blob8(np.arange(100) + 70 * i) for i in range(4)]  # overlapping key windows
    reserve = [[("BF.RESERVE", n, repr(FPP), CAPACITY)] for n in (a, b, c)]
    audit = [[("BF.MEXISTS64", n, blob8(np.arange(400))) for n in (a, b, c)]
             + [("BITCOUNT", bits), ("GET", string)]]
    if case == "a lone command":
        body = [[("BF.MADD64", a, k[0])], [("BF.MEXISTS64", a, k[1])],
                [("SETBITSB", bits, blob4([3, 9, 27]))], [("PING",)]]
    elif case == "a blob run naming a filter twice":
        body = [[("BF.MADD64", a, k[0]), ("BF.MADD64", b, k[1]), ("BF.MADD64", a, k[1]),
                 ("BF.MADD64", c, k[2]), ("BF.MEXISTS64", a, k[2]), ("BF.MEXISTS64", b, k[2]),
                 ("BF.MEXISTS64", a, k[0])]]
    elif case == "a run cut by another verb":
        body = [[("BF.MADD64", a, k[0]), ("BF.MADD64", b, k[1]), ("ECHO", "cut"),
                 ("BF.MADD64", c, k[2]), ("BF.MEXISTS64", a, k[1]), ("SETBITSB", bits, blob4([5, 6])),
                 ("BF.MEXISTS64", b, k[1]), ("BITCOUNT", bits), ("BF.MEXISTS64", c, k[3])]]
    elif case == "a malformed element":
        nested = b"*3\r\n$9\r\nBF.MADD64\r\n*1\r\n$1\r\nx\r\n$8\r\n12345678\r\n"
        body = [[("BF.MADD64", a, k[0]), nested, ("BF.MADD64", b, k[1]), ("PING",)]]
    elif case == "commands that fail":
        body = [[("SET", string, "v")],
                [("BF.MADD64", a, k[0]), ("BF.MADD64", "nofilter{x}", k[0]), ("BF.MEXISTS64", a),
                 ("NOSUCHVERB", a), ("BITCOUNT", string),
                 ("BITOP", "NAND", bits, bits), ("BF.MEXISTS64", a, b"123"),
                 ("BF.MEXISTS64", b, k[0])]]
    elif case == "MULTI met mid-frame":
        body = [[("BF.MADD64", a, k[0]), ("MULTI",), ("BF.MADD64", b, k[1]), ("BF.MADD64", a, k[1]),
                 ("BF.MADD64", b, k[2]), ("SET", string, "v"), ("EXEC",),
                 ("BF.MEXISTS64", a, k[1]), ("BF.MEXISTS64", b, k[1])]]
    elif case == "a partly shed frame":
        # 100 items a command against 250 tokens: two admitted, two shed
        body = [[("CONFIG", "SET", "qos-tenant-burst", "250"), ("CONFIG", "SET", "qos-tenant-rate", "1")],
                [("BF.MADD64", a, k[0]), ("BF.MADD64", b, k[1]), ("BF.MADD64", c, k[2]),
                 ("BF.MEXISTS64", a, k[0])],
                [("CONFIG", "SET", "qos-tenant-rate", "0")]]
    elif case == "sub-windows armed":
        body = [[("CLIENT", "QOS", "CLASS", "bulk"), ("CONFIG", "SET", "qos-bulk-subwindow-items", "128")],
                [("BF.MADD64", a, k[0]), ("BF.MADD64", b, k[1]), ("BF.MADD64", a, k[1]),
                 ("BF.MADD64", c, k[2]), ("ECHO", "x"), ("SETBITSB", bits, blob4(np.arange(200))),
                 ("BF.MEXISTS64", a, k[2]), ("BF.MEXISTS64", b, k[2]), ("BITCOUNT", bits)],
                [("CONFIG", "SET", "qos-bulk-subwindow-items", "0")]]
    return reserve + body + audit


@pytest.mark.parametrize("case", [
    "a lone command", "a blob run naming a filter twice", "a run cut by another verb",
    "a malformed element", "commands that fail", "MULTI met mid-frame", "a partly shed frame",
    "sub-windows armed"])
def test_one_executor_answers_the_same_bytes_placed_or_not(three_servers, case):
    """The same pipelined frames to a server with no placement, one placed
    on one device and one placed on eight: reply bytes and final state (the
    read-backs that close each conversation) are identical."""
    frames = _frames_of(case)
    try:
        said = {name: _converse(st, frames) for name, st in three_servers.items()}
    finally:
        ioplane.set_bulk_subwindow_items(0)
    plain = said.pop("no placement")
    assert all(len(f) > 0 for f in plain)
    if case == "a partly shed frame":
        assert plain[4].count(b"-BUSY") == 2
    if case == "a malformed element":
        assert b"-ERR bad request frame" in plain[3]
    for name, got in said.items():
        assert got == plain, name

"""Traffic is a pure function of the seed: the same seed gives the same
requests bit for bit, another seed gives others."""
import json
import os

import numpy as np
import pytest

from benchmark import datagen as D
from benchmark.loadgen import StreamContext, load_generator

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _cell(config: str, traffic: str):
    with open(os.path.join(BENCH, "configs", config + ".json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as fh:
        params = json.load(fh)
    params.update(params.get("rehearse", {}))
    return {**cfg["sizes"], **cfg["rehearse"]}, params


def _flat(x):
    """Every array and scalar inside a request, as bytes."""
    if isinstance(x, (tuple, list)):
        return b"|".join(_flat(v) for v in x)
    if isinstance(x, np.ndarray):
        return x.tobytes()
    return repr(x).encode()


CELLS = [("bloom-bank-1k", "bulk-flush-100k"), ("bloom-bank-1k", "point-16key"),
         ("hll-10k", "stream-add-merge"), ("cluster-mixed-8m", "fanout-64-by-verb")]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_same_seed_same_traffic(tmp_path, config, traffic):
    sizes, params = _cell(config, traffic)
    gen = load_generator(params["generator"])

    def requests(seed, conn):
        for name, arr in gen.reference(sizes, params, seed).items():
            if arr.size < 100000:  # the streams need the small arrays only
                np.save(tmp_path / (name + ".npy"), arr)
        s = gen.Stream(StreamContext(sizes, params, seed, conn, params["connections"],
                                     str(tmp_path)))
        reqs = s.warmup() + [s.make(i) for i in range(12)]
        return _flat(reqs), reqs

    a, _reqs = requests(7, 0)
    assert a == requests(7, 0)[0]
    assert a != requests(8, 0)[0]
    assert a != requests(7, 1)[0]


def test_keys_regions_are_disjoint_and_seeded():
    i = np.arange(100000)
    a, b, c = (D.keys(1, r, i) for r in (D.POPULATED, D.ADDED, D.ABSENT))
    assert len(np.unique(np.concatenate([a, b, c]))) == 300000
    assert (a >= 0).all() and (c >= (1 << 62)).all() and (b < (1 << 62)).all()
    assert np.array_equal(a, D.keys(1, D.POPULATED, i))
    assert not np.array_equal(a, D.keys(2, D.POPULATED, i))


def test_zipf_is_skewed_and_restrictable():
    z = D.Zipf(1000, 0.99, 3)
    draws = z.draw(D.rng(3, 1), 200000)
    counts = np.bincount(draws, minlength=1000)
    assert counts[z.items[0]] > 5 * counts[z.items[99]] > 0
    mine = np.arange(3, 1000, 8)
    zz = D.Zipf(1000, 0.99, 3, among=mine)
    assert np.isin(zz.draw(D.rng(3, 2), 5000), mine).all()
    d = zz.draw_distinct(D.rng(3, 3), 64)
    assert len(set(d.tolist())) == 64 and np.isin(d, mine).all()


def test_poisson_arrivals_rate():
    t = D.poisson_arrivals(D.rng(4, 1), 2000.0, 10.0)
    assert abs(len(t) - 20000) < 600 and (np.diff(t) >= 0).all() and t[-1] < 10.0
    assert np.array_equal(t, D.poisson_arrivals(D.rng(4, 1), 2000.0, 10.0))


def test_bloom_kinds_every_add_is_probed_back(tmp_path):
    sizes, params = _cell("bloom-bank-1k", "point-16key")
    gen = load_generator("bloom_bank")
    s = gen.Stream(StreamContext(sizes, params, 5, 0, params["connections"], str(tmp_path)))
    kinds = s.kinds[:5000]
    adds = np.flatnonzero(kinds == gen.KIND_ADD)
    assert 150 < len(adds) < 350
    assert (kinds[adds + 2] == gen.KIND_PROBE_BACK).all()
    _k, t, keys = s.make(int(adds[0]))
    _k2, t2, keys2 = s.make(int(adds[0]) + 2)
    assert np.array_equal(keys, keys2) and np.array_equal(t, t2)


def _bank_stream(tmp_path, traffic, seed=11):
    sizes, params = _cell("bloom-bank-1k", traffic)
    gen = load_generator("bloom_bank")
    ref = gen.reference(sizes, params, seed)
    np.save(tmp_path / "plane.npy", ref["plane"])
    s = gen.Stream(StreamContext(sizes, params, seed, 0, params["connections"], str(tmp_path)))
    return gen, sizes, params, ref, s


def test_bank_check_passes_right_replies_and_catches_a_wrong_flag(tmp_path):
    """The check itself is checked: replies made from the reference pass, one
    flipped flag (a false positive, then a false negative) does not."""
    from benchmark.reference import RefBank

    gen, sizes, params, ref, s = _bank_stream(tmp_path, "point-16key")
    bank = RefBank(sizes["tenants"], sizes["m_bits"], sizes["k"], bits=ref["plane"].copy())
    for idx in range(-2, 300):
        req = s.make(idx)
        kind, t, keys = req
        reply = bank.add(t, keys) if kind == gen.KIND_ADD else bank.contains(t, keys)
        s.keep(idx, req, reply)
    _f, extra = gen.after_window(None, sizes, params, 11, ref, {0: s.writes()})
    assert len(extra["extra_bits"]) > 0
    np.save(tmp_path / "extra_bits.npy", extra["extra_bits"])
    good = s.verify()
    assert good["failures"] == [] and good["checked"] == 302 and good["checked_full"] > 250
    at = next(i for i, (_idx, kind, _r) in enumerate(s.kept) if kind == gen.KIND_CONTAINS)
    idx, kind, reply = s.kept[at]
    absent = np.flatnonzero(~reply)[0]
    for flip in (absent, 0):  # an absent key found; a present key (position 0) missed
        wrong = reply.copy()
        wrong[flip] = ~wrong[flip]
        s.kept[at] = (idx, kind, wrong)
        assert s.verify()["failures"], flip
    s.kept[at] = (idx, kind, reply)
    assert s.verify()["failures"] == []


def test_fanout_check_passes_right_replies_and_catches_a_wrong_count(tmp_path):
    from benchmark.reference import RefBank, RefBitSet

    sizes, params = _cell("cluster-mixed-8m", "fanout-64-by-verb")
    gen = load_generator("cluster_mixed")
    seed = 13
    sizes["sample"] = sizes["tenants"]  # follow every tenant
    np.save(tmp_path / "sampled.npy", gen.reference(sizes, params, seed)["sampled"])
    s = gen.Stream(StreamContext(sizes, params, seed, 1, params["connections"], str(tmp_path)))
    state = {}
    for idx in range(6):
        cmds, slots, plan, ops = req = s.make(idx)
        assert len(cmds) == len(slots) and ops > 0
        replies = []
        for (j, what), cmd in zip(slots, cmds):
            t, add, probe, bits = plan[j]
            ref = state.setdefault(t, gen._Tenant(seed, sizes, t))
            if what == "add":
                replies.append(ref.bf.add(np.zeros(len(add), np.int32), add).astype(np.uint8).tobytes())
            elif what == "probe":
                replies.append(ref.bf.contains(np.zeros(len(probe), np.int32), probe)
                               .astype(np.uint8).tobytes())
            elif what == "set":
                replies.append(ref.a.set_each(bits).astype(np.uint8).tobytes())
            elif what == "or":
                ref.a.or_(ref.b)
                replies.append(ref.a.byte_length())
            elif what == "xor":
                ref.b.xor(ref.a)
                replies.append(ref.b.byte_length())
            else:
                replies.append(ref.a.count())
        s.keep(idx, req, replies)
    assert any(t in s.added for t in state)  # later frames probe added keys
    good = s.verify()
    assert good["failures"] == [] and good["checked_full"] > 100
    idx, slots, plan, replies = s.kept[3]
    at = next(i for i, (_j, what) in enumerate(slots) if what == "count")
    replies[at] += 1
    assert any("BITCOUNT" in f for f in s.verify()["failures"])


def test_hll_cycles_every_read_every_th_frame_reads_and_every_window_closes_on_a_read(tmp_path):
    sizes, params = _cell("hll-10k", "stream-add-merge")
    gen = load_generator("hll_bank")
    np.save(tmp_path / "sample_lut.npy", gen.reference(sizes, params, 17)["sample_lut"])
    s = gen.Stream(StreamContext(sizes, params, 17, 1, params["connections"], str(tmp_path)))
    reqs = [s.make(i) for i in range(25)]
    assert [i for i, r in enumerate(reqs) if r[0] == gen.KIND_READ] == [9, 19]
    assert [r[0] for r in s.warmup()] == [gen.KIND_ADD, gen.KIND_READ]
    closing = s.closing(25)  # whatever the last frame was, the window ends on a read
    assert closing[0] == gen.KIND_READ
    # a frame's operation count tells a cycle's end: the reads, the closing one too
    ops = [s.ops(r) for r in reqs + [closing]]
    assert np.flatnonzero(gen.cycle_ends(params, ops)).tolist() == [9, 19, 25]
    with pytest.raises(ValueError):
        gen.cycle_ends({**params, "pairs_per_read": params["pairs_per_add"]}, ops)

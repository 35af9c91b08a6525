"""The one place the frozen copy (benchmark/reference.py) is held against the
program: same keys, same answers, at tiny sizes on the CPU."""
import numpy as np
import pytest

from benchmark import datagen as D
from benchmark import reference as R


@pytest.fixture(scope="module")
def client():
    import redisson_tpu

    c = redisson_tpu.create()
    yield c
    c.shutdown()


def test_hash_pair_is_the_programs():
    from redisson_tpu.utils import hashing as H

    keys = D.keys(3, D.POPULATED, np.arange(5000))
    keys = np.concatenate([keys, [0, 1, -1, (1 << 62) + 5, np.iinfo(np.int64).max]])
    want = H.hash_u64_pair(*H.int_keys_to_u32_pair(keys), np)
    got = R.hash_pair(keys)
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])


def test_bloom_bank_equals_reference(client):
    bank = client.get_bloom_filter_array("ref:bank")
    assert bank.try_init(16, 1000, 0.01)
    ref = R.RefBank(16, bank.get_size(), bank.get_hash_iterations())
    rng = np.random.default_rng(0)
    for r in range(4):
        t = rng.integers(0, 16, 3000).astype(np.int32)
        k = D.keys(1, D.POPULATED, rng.integers(0, 5000, 3000))  # with repeats
        assert np.array_equal(bank.add_each(t, k), ref.add(t, k)), f"add round {r}"
    t = rng.integers(0, 16, 4000).astype(np.int32)
    k = np.concatenate([D.keys(1, D.POPULATED, rng.integers(0, 5000, 2000)),
                        D.keys(1, D.ABSENT, np.arange(2000))])
    found = bank.contains(t, k)
    assert np.array_equal(found, ref.contains(t, k))
    assert 0 < found.sum() < len(found)


def test_single_filter_equals_reference(client):
    bf = client.get_bloom_filter("ref:bf")
    assert bf.try_init(500, 0.01)
    ref = R.RefBank(1, bf.get_size(), bf.get_hash_iterations())
    k = D.keys(2, D.POPULATED, np.arange(500))
    z = np.zeros(len(k), np.int32)
    assert np.array_equal(bf.add_each(k), ref.add(z, k))
    probe = np.concatenate([k[:100], D.keys(2, D.ABSENT, np.arange(400))])
    assert np.array_equal(bf.contains_each(probe), ref.contains(np.zeros(500, np.int32), probe))


def test_hll_bank_equals_reference(client):
    hll = client.get_hyper_log_log_array("ref:hll")
    assert hll.try_init(12)
    ref = R.RefHll(np.arange(12))
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 12, 60000).astype(np.int32)
    ids[:30000] = 0  # one counter past the linear-counting range
    keys = D.keys(5, D.ADDED, np.arange(60000))
    hll.add(ids, keys)
    ref.add(ids, keys)
    assert np.allclose(hll.estimate_all(), ref.estimate(), rtol=2e-3)
    dst, src = np.array([1, 2, 3], np.int32), np.array([9, 10, 11], np.int32)
    want_union = ref.estimate_union(dst, src)
    assert np.allclose(hll.estimate_union_pairs(dst, src), want_union, rtol=2e-3)
    hll.merge_rows(dst, src)
    ref.merge_rows(dst, src)
    est = hll.estimate_all()
    assert np.allclose(est, ref.estimate(), rtol=2e-3)
    assert np.allclose(est[dst], want_union, rtol=2e-3)
    truth = np.bincount(ids, minlength=12).astype(float)
    truth[dst] += truth[src]
    assert R.hll_failures(est, truth, "test", ref_est=ref.estimate()) == []
    assert R.hll_failures(est * 1.01, truth, "test", ref_est=ref.estimate())


def test_bitset_ops_equal_reference(client):
    nb = 1 << 14
    a, b = client.get_bit_set("ref:a"), client.get_bit_set("ref:b")
    ra, rb = R.RefBitSet(nb), R.RefBitSet(nb)
    rng = np.random.default_rng(2)
    for bs, ref in ((a, ra), (b, rb)):
        idx = rng.integers(0, nb, 300)
        idx[0] = nb - 1
        idx[1] = idx[2]  # a repeat inside one call
        assert np.array_equal(bs.set_each(idx), ref.set_each(idx))
    for _ in range(3):
        idx = rng.integers(0, nb, 200)
        assert np.array_equal(a.set_each(idx), ra.set_each(idx))
        a.or_("ref:b")
        ra.or_(rb)
        b.xor("ref:a")
        rb.xor(ra)
        assert a.cardinality() == ra.count() and b.cardinality() == rb.count()
        assert (a.length() + 7) // 8 == ra.byte_length()
        assert (b.length() + 7) // 8 == rb.byte_length()


def test_hll_hard_limit_follows_the_estimators_bias():
    """The simulation the hard limit rests on: the classic estimator reads
    about 2 % high just past the end of linear counting (2.5 m keys) and is
    unbiased well below and above, so only that band gets 8 sigma."""
    m, trials = 1 << R.HLL_P, 24
    rng = np.random.default_rng(0)
    bias = {}
    for n in (int(1.8 * m), int(2.6 * m), int(6.0 * m)):
        ref = R.RefHll(np.arange(trials))
        h2 = rng.integers(1, 1 << 32, (trials, n)).astype(np.float64)
        rho = (33 - np.frexp(h2)[1]).astype(np.uint8)
        idx = rng.integers(0, m, (trials, n))
        np.maximum.at(ref.regs, (np.repeat(np.arange(trials), n), idx.ravel()), rho.ravel())
        bias[n] = float(np.mean(ref.estimate() / n - 1.0))
    low, mid, high = bias.values()
    assert abs(low) < 0.004 and abs(high) < 0.004 and 0.015 < mid < 0.03, bias
    sigma = 1.04 / np.sqrt(m)
    assert np.allclose(R.hll_hard([1.8 * m, 2.6 * m, 6.0 * m]), [6 * sigma, 8 * sigma, 6 * sigma])
    assert np.allclose(R.hll_hard([1.0 * m, 4.0 * m], [2.4 * m, 9.0 * m]), [8 * sigma, 6 * sigma])
    truth = np.full(100, 1.8 * m)
    truth[1] = 2.6 * m
    off = np.ones(100)
    off[:2] = 1.045, 1.06
    assert R.hll_failures(truth * off, truth, "test") == []
    off[0] = 1.055  # 6 sigma is 4.875 %
    assert "1 beyond the hard limit" in R.hll_failures(truth * off, truth, "test")[0]

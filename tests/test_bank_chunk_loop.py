"""The bank probe's chunk loop (core/kernels.py _map_valid_chunks): device
work follows n_valid, not the bucket — and nothing a caller can see changes.

Every case runs on the CPU: it shows that the looped program equals the NumPy
reference and the one-shot body bit for bit, that one program serves every n
of a bucket, that a bucket under the threshold is today's program, and what
the two row counters read.  The HLL bank's add is held to the same reference
at the same sizes and stays one-shot (the chip's trace decided: its looped
scatter was three times slower).  How fast either is, only a chip run says."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import redisson_tpu
from redisson_tpu.core import kernels as K
from redisson_tpu.utils import hashing as H

C = K.CHUNK
UNDER = 7 * C    # bucket_size(7 * C): the largest bucket the loop leaves alone
AT = 8 * C       # the smallest it takes
OVER = 10 * C    # bucket_size(AT + 1): the next one up
T, M, KH = 8, 4096, 7      # a small bloom bank: 8 tenants x 4,096 cells
HT, P = 16, 10             # a small HLL bank: 16 counters x 1,024 registers


def edge_ns(b):
    return [1, C - 1, C, C + 1, b - C + 1, b - 1, b]


CASES = [(b, n) for b in (UNDER, AT, OVER) for n in edge_ns(b)]


def rows(b, tenants, seed):
    """A packed (3, b) operand, every row a real (tenant, key): what lies past
    n_valid would show if the device answered or applied it."""
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, tenants, b), rng.integers(0, 2**32, b, dtype=np.uint64),
                     rng.integers(0, 2**32, b, dtype=np.uint64)]).astype(np.uint32)


def np_found(bits, tlh, n):
    h1, h2 = H.hash_u64_pair(tlh[1], tlh[2], np)
    idx = H.bloom_indexes(h1, h2, KH, M, np)
    got = bits[tlh[0].astype(np.int64)[:, None], idx]
    return (got != 0).all(axis=1) & (np.arange(tlh.shape[1]) < n)


def np_clz32(v):
    v = v.astype(np.uint32).copy()
    length = np.zeros(v.shape, np.int64)
    for s in (16, 8, 4, 2, 1):
        big = v >= (1 << s)
        length += s * big
        v = np.where(big, v >> s, v)
    return 32 - (length + (v > 0))


def np_hll_add(regs, tlh, n):
    h1, h2 = H.hash_u64_pair(tlh[1, :n], tlh[2, :n], np)
    idx = (h1 & np.uint32((1 << P) - 1)).astype(np.int64)
    rho = (np_clz32(h2) + 1).astype(np.uint8)
    out = regs.copy()
    np.maximum.at(out, (tlh[0, :n].astype(np.int64), idx), rho)
    return out


@pytest.fixture(scope="module")
def plane():
    """A bank that holds the AT keys of rows(AT, T, seed=1)."""
    tlh = rows(AT, T, seed=1)
    bits, _ = K.bloom_bank_add_packed(jnp.zeros((T, M), jnp.uint8), tlh, K.valid_n(AT), KH, M)
    return np.asarray(bits), tlh


@jax.jit
def bloom_oneshot_bits(bits, tlh, n_valid):
    t, lo, hi = K._unpack_tlh(tlh)
    return K._pack_bool_u32(K._bloom_bank_probe(bits.reshape(-1), M, t, lo, hi, n_valid, KH, M))


def test_the_threshold_is_read_from_the_bucket():
    assert C % 32 == 0
    assert [K.chunked(b) for b in (K.MIN_BUCKET, C, UNDER, AT, OVER)] == [False, False, False, True, True]
    assert K.bucket_size(UNDER) == UNDER and K.bucket_size(AT) == AT and K.bucket_size(AT + 1) == OVER
    # every bucket the ladder makes from 8 chunks up is a whole number of chunks
    assert all(K.bucket_size(n) % C == 0 for n in range(AT, 1 << 20, 997))
    assert not K.chunked(AT + 32)  # a shape the ladder never makes: one-shot
    assert K.bucket_size(100_000) == 114_688 and K.rows_issued(100_000, 114_688) == -(-100_000 // C) * C
    assert K.rows_issued(16, 256) == 256 and K.rows_issued(AT, AT) == AT and K.rows_issued(1, AT) == C


@pytest.mark.parametrize("b,n", CASES)
def test_bloom_bank_contains_follows_n_valid_and_nothing_else(plane, b, n):
    bits, added = plane
    tlh = rows(b, T, seed=b + n)
    half = n // 2  # the first half of the valid rows is present, and all the padding
    tlh[:, :half] = added[:, np.arange(half) % AT]
    tlh[:, n:] = added[:, np.arange(n, b) % AT]
    got = np.asarray(K.bloom_bank_contains_packed_bits(bits, tlh, K.valid_n(n), KH, M))
    found = K.unpack_found(got, b)
    want = np_found(bits, tlh, n)
    np.testing.assert_array_equal(found, want)
    assert found[:half].all() and not found[n:].any()
    np.testing.assert_array_equal(got, np.asarray(bloom_oneshot_bits(bits, tlh, K.valid_n(n))))
    # the unpacked-result and unpacked-operand entry points share the body
    np.testing.assert_array_equal(
        np.asarray(K.bloom_bank_contains_packed(bits, tlh, K.valid_n(n), KH, M)), want)
    np.testing.assert_array_equal(
        np.asarray(K.bloom_bank_contains_u64(bits, tlh[0].astype(np.int32), tlh[1], tlh[2],
                                             K.valid_n(n), KH, M)), want)


@pytest.mark.parametrize("b,n", CASES)
def test_hll_bank_add_follows_n_valid_and_nothing_else(b, n):
    tlh = rows(b, HT, seed=b - n)
    before = np.random.default_rng(7).integers(0, 3, (HT, 1 << P)).astype(np.uint8)
    want = np_hll_add(before, tlh, n)
    got = K.hll_bank_add_packed(jnp.asarray(before), tlh, K.valid_n(n), P)
    np.testing.assert_array_equal(np.asarray(got), want)
    # the same flush twice is the same flush once
    again = K.hll_bank_add_packed(got, tlh, K.valid_n(n), P)
    np.testing.assert_array_equal(np.asarray(again), want)
    np.testing.assert_array_equal(
        np.asarray(K.hll_bank_add_u64(jnp.asarray(before), tlh[0].astype(np.int32), tlh[1], tlh[2],
                                      K.valid_n(n), P)), want)


@pytest.mark.parametrize("b", [AT, OVER])
def test_hll_keys_repeated_along_the_flush_give_the_reference_registers(b):
    """The first C keys come again in every later C rows, to other and to the
    same counters: max does not care where they stand."""
    tlh = rows(b, HT, seed=11)
    for c in range(1, b // C):
        tlh[1:, c * C:(c + 1) * C] = tlh[1:, :C]
        if c % 2:
            tlh[0, c * C:(c + 1) * C] = tlh[0, :C]
    n = b - 5
    start = jnp.zeros((HT, 1 << P), jnp.uint8)
    got = np.asarray(K.hll_bank_add_packed(start, tlh, K.valid_n(n), P))
    np.testing.assert_array_equal(got, np_hll_add(np.zeros((HT, 1 << P), np.uint8), tlh, n))


def test_bloom_bank_add_stays_one_shot():
    """A key repeated across two chunks is new once per flush: the add body's
    flags come from a gather before its own scatter, so it is never cut."""
    tlh = rows(AT, T, seed=5)
    tlh[:, C:2 * C] = tlh[:, :C]  # chunk 1 repeats chunk 0
    _, newly = K.bloom_bank_add_packed(jnp.zeros((T, M), jnp.uint8), tlh, K.valid_n(AT), KH, M)
    newly = np.asarray(newly)
    np.testing.assert_array_equal(newly[C:2 * C], newly[:C])
    assert newly[:C].sum() > C // 2


@pytest.mark.parametrize("program,b", [("bloom", AT), ("bloom", OVER), ("hll", AT), ("hll", OVER)])
def test_twenty_sizes_of_one_bucket_are_one_program(program, b):
    """n_valid is a device scalar and the trip count is computed from it on
    the device: a new n compiles nothing (the 0-compiles-in-a-window rule)."""
    bits = jnp.zeros((T, M), jnp.uint8)
    regs = jnp.zeros((HT, 1 << P), jnp.uint8)
    sizes = np.linspace(1, b, 20).astype(int)
    tlh = jnp.asarray(rows(b, T, seed=3))
    if program == "bloom":
        fn, call = K.bloom_bank_contains_packed_bits, lambda n: fn(bits, tlh, K.valid_n(n), KH, M)
    else:
        fn, call = K.hll_bank_add_packed, lambda n: fn(jnp.copy(regs), tlh, K.valid_n(n), P)
    call(b)
    entries = fn._cache_size()
    for n in sizes:
        call(int(n))
    assert len(set(sizes.tolist())) == 20 and fn._cache_size() == entries


def _hlo(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()  # the module and its entry are named after fn
    return text.replace(fn.__name__, "fn")


@pytest.mark.parametrize("b", [K.MIN_BUCKET, UNDER])
def test_a_bucket_under_the_threshold_is_the_oneshot_program(b):
    """bank-point's 16-key calls pad to 256 rows: they must run the program
    they ran before the loop existed."""
    bits = jax.ShapeDtypeStruct((T, M), jnp.uint8)
    regs = jax.ShapeDtypeStruct((HT, 1 << P), jnp.uint8)
    tlh = jax.ShapeDtypeStruct((3, b), jnp.uint32)
    nv = jax.ShapeDtypeStruct((), jnp.int32)

    def looped(bits, tlh, n_valid):
        return K._pack_bool_u32(K._bloom_bank_contains_body(bits, *K._unpack_tlh(tlh), n_valid, KH, M))

    def hll(regs, tlh, n_valid):
        return K._hll_bank_add_body(regs, *K._unpack_tlh(tlh), n_valid, P)

    assert _hlo(looped, bits, tlh, nv) == _hlo(bloom_oneshot_bits.__wrapped__, bits, tlh, nv)
    assert "while" not in _hlo(looped, bits, tlh, nv)
    over = jax.ShapeDtypeStruct((3, AT), jnp.uint32)
    assert "while" in _hlo(looped, bits, over, nv)
    assert "while" not in _hlo(hll, regs, over, nv)  # one-shot whatever the bucket


def metrics_rows(conn):
    text = bytes(conn.execute("METRICS")).decode()
    got = dict(line.split() for line in text.splitlines() if line.startswith("rtpu_kernel_rows_"))
    return int(float(got["rtpu_kernel_rows_valid_total"])), int(float(got["rtpu_kernel_rows_issued_total"]))


def test_the_row_counters_after_a_known_sequence_of_dispatches():
    """One engaged dispatch, one under the threshold, one with n == B, an add
    (one-shot whatever its bucket) — counted where they are dispatched, read
    from a server's METRICS (the counters are the process's)."""
    from redisson_tpu.client.remote import RemoteRedisson
    from redisson_tpu.server.server import ServerThread

    rng = np.random.default_rng(0)

    def flush(n):
        return rng.integers(0, T, n).astype(np.int32), rng.integers(0, 1 << 60, n).astype(np.int64)

    client = redisson_tpu.create()
    with ServerThread(port=0) as st, RemoteRedisson(st.address) as remote:
        try:
            bank = client.get_bloom_filter_array("rows-bank")
            bank.try_init(tenants=T, expected_insertions=500, false_probability=0.01)
            hll = client.get_hyper_log_log_array("rows-hll")
            hll.try_init(tenants=T)
            valid0, issued0 = metrics_rows(remote)
            assert (valid0, issued0) == K.rows_counted()
            bank.contains(*flush(AT + 1))  # bucket 10 * C, engaged: ceil(n / C) * C = 9 * C rows
            bank.contains(*flush(16))      # bucket 256, not engaged: 256 rows
            bank.contains(*flush(AT))      # n == B, engaged: B rows
            bank.add(*flush(AT + 1))       # the add body is one-shot: its bucket, 10 * C
            hll.add(*flush(AT + 1))        # and so is the HLL bank's: 10 * C
            valid1, issued1 = metrics_rows(remote)
        finally:
            client.shutdown()
    assert valid1 - valid0 == (AT + 1) + 16 + AT + (AT + 1) + (AT + 1)
    assert issued1 - issued0 == 9 * C + 256 + AT + 10 * C + 10 * C

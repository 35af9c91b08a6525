"""The plain reference: what a correct server answers, in NumPy alone.

A FROZEN COPY of the hash arithmetic the sketches are defined by
("rtpu-mur32x2/1": two murmur3-x86-32 chains over the key's two 32-bit words,
h2 forced odd; bloom index i = (h1 + i*h2) mod m; HLL register = h1 & (2^p-1),
rank = clz32(h2) + 1) and of the sketches' semantics (bloom add reports
newly-added against the plane as it stood before the batch; HLL row merge is
a register-wise max read from the pre-call bank; the classic bias-corrected
estimator with linear counting below 2.5 m).  It imports nothing from
``redisson_tpu`` — the one place the copy is compared with the program is
``benchmark/tests/test_reference.py`` — so a change to the program's hashing
shows as wrong answers here, not as a silently moved yardstick.
"""
import numpy as np

SEED1 = 0x9747B28C
SEED2 = 0x3C6EF372
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_FM1 = np.uint32(0x85EBCA6B)
_FM2 = np.uint32(0xC2B2AE35)
_FIVE = np.uint32(5)
_ADD = np.uint32(0xE6546B64)
_EIGHT = np.uint32(8)

HLL_P = 14
_SIGMA = 1.04 / np.sqrt(1 << HLL_P)
HLL_BOUND = 3 * _SIGMA  # chip_smoke's bound for 95 % of counters
# Where the classic estimator leaves linear counting (2.5 m) it reads high:
# +2.3 % at 2.5 m keys, +0.5 % at 3.7 m, nothing outside 2.3-5 m
# (tests/test_reference.py holds the simulation).
HLL_BIASED = (2.3, 3.7)  # in units of m = 2^p keys


def hll_hard(n_low, n_high=None, p: int = HLL_P):
    """How far from the truth no estimate may be: chip_smoke's 6 sigma, and
    8 sigma where the true cardinality (anywhere in [n_low, n_high]) lies in
    the estimator's biased band."""
    n_low = np.asarray(n_low, np.float64)
    n_high = n_low if n_high is None else np.asarray(n_high, np.float64)
    m = float(1 << p)
    biased = (n_high >= HLL_BIASED[0] * m) & (n_low <= HLL_BIASED[1] * m)
    return np.where(biased, 8 * _SIGMA, 6 * _SIGMA)


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _round(h, k):
    k = _rotl(k * _C1, 15) * _C2
    return _rotl(h ^ k, 13) * _FIVE + _ADD


def _fmix(x):
    x = (x ^ (x >> np.uint32(16))) * _FM1
    x = (x ^ (x >> np.uint32(13))) * _FM2
    return x ^ (x >> np.uint32(16))


def hash_pair(keys):
    """int64 keys -> (h1, h2) uint32; h2 odd."""
    k = np.asarray(keys).astype(np.uint64)
    lo = (k & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (k >> np.uint64(32)).astype(np.uint32)
    with np.errstate(over="ignore"):
        out = []
        for seed in (SEED1, SEED2):
            h = np.full(lo.shape, seed, np.uint32)
            h = _round(_round(h, lo), hi)
            out.append(_fmix(h ^ _EIGHT))  # key length in bytes
    return out[0], out[1] | np.uint32(1)


def bloom_indexes(keys, k: int, m: int):
    """(n, k) int64 bit positions of each key in an m-bit filter."""
    h1, h2 = hash_pair(keys)
    i = np.arange(k, dtype=np.uint32)
    with np.errstate(over="ignore"):
        idx = (h1[:, None] + i * h2[:, None]) % np.uint32(m)
    return idx.astype(np.int64)


class RefBank:
    """(tenants, m) bloom bit plane, one byte a bit.  ``bits`` may be handed
    in (a plane another process built, mapped read-only for ``contains``)."""

    def __init__(self, tenants: int, m: int, k: int, bits=None):
        self.m, self.k = m, k
        self.bits = np.zeros(tenants * m, np.uint8) if bits is None else bits

    def _flat(self, tenant, keys):
        return (np.asarray(tenant, np.int64)[:, None] * self.m
                + bloom_indexes(keys, self.k, self.m))

    def add(self, tenant, keys):
        g = self._flat(tenant, keys)
        newly = (self.bits[g] == 0).any(axis=1)
        self.bits[g.ravel()] = 1
        return newly

    def contains(self, tenant, keys):
        return self.bits[self._flat(tenant, keys)].all(axis=1)


def hll_idx_rho(keys, p: int = HLL_P):
    h1, h2 = hash_pair(keys)
    idx = (h1 & np.uint32((1 << p) - 1)).astype(np.int64)
    # clz32(h2) + 1; frexp's exponent of an integer is its bit length
    rho = (33 - np.frexp(h2.astype(np.float64))[1]).astype(np.uint8)
    return idx, rho


class RefHll:
    """(counters, 2^p) HyperLogLog registers.  ``rows`` names the counters
    held (a sample); ids passed in are the bank's own."""

    def __init__(self, rows, p: int = HLL_P):
        self.p, self.m = p, 1 << p
        self.rows = np.asarray(rows, np.int64)
        self._order = np.argsort(self.rows)
        self.regs = np.zeros((len(self.rows), self.m), np.uint8)

    def local(self, ids):
        """Positions in ``regs`` of the counters ``ids`` (all held)."""
        at = np.searchsorted(self.rows[self._order], np.asarray(ids, np.int64))
        return self._order[at]

    def add(self, ids, keys):
        idx, rho = hll_idx_rho(keys, self.p)
        np.maximum.at(self.regs, (self.local(ids), idx), rho)

    def merge_rows(self, dst, src):
        """dst[i] = max(dst[i], src[i]), every source read before any write."""
        d, s = self.local(dst), self.local(src)
        self.regs[d] = np.maximum(self.regs[d], self.regs[s].copy())

    def estimate(self, regs=None):
        regs = self.regs if regs is None else regs
        m = self.m
        e = (0.7213 / (1.0 + 1.079 / m)) * m * m / np.exp2(
            -regs.astype(np.float64)).sum(axis=1)
        zeros = (regs == 0).sum(axis=1)
        lin = m * (np.log(m) - np.log(np.maximum(zeros, 1)))
        return np.where((e <= 2.5 * m) & (zeros > 0), lin, e)

    def estimate_union(self, a, b):
        la, lb = self.local(a), self.local(b)
        return self.estimate(np.maximum(self.regs[la], self.regs[lb]))


def hll_failures(est, truth, what: str, ref_est=None) -> list:
    """Device estimates, statistically, against the true cardinalities
    (3 sigma for 95 % of counters, ``hll_hard`` for all) and, where given,
    against the register-exact reference (float32 vs float64 arithmetic
    apart: 2e-3).  chip_smoke.check_hll's rule, but for the counters in the
    estimator's biased band, where a Zipf stream puts a few in a hundred and
    chip_smoke put none: a run on the chip read 4.90 % on one of them, past
    6 sigma (4.875 %).  Returns what failed, as text."""
    est, truth = np.asarray(est, np.float64), np.asarray(truth, np.float64)
    if est.shape != truth.shape or not np.isfinite(est).all():
        return [f"{what}: bad estimates"]
    out = []
    if ref_est is not None:
        drift = np.abs(est - ref_est) / np.maximum(ref_est, 1.0)
        if drift.max() > 2e-3:
            out.append(f"{what}: estimate differs from the reference by "
                       f"{drift.max():.5f} (position {int(drift.argmax())})")
    rel = np.abs(est - truth) / np.maximum(truth, 1.0)
    outliers = int((rel > HLL_BOUND).sum())
    too_far = rel > hll_hard(truth)
    if outliers > max(1, len(rel) // 20) or too_far.any():
        out.append(f"{what}: {outliers} of {len(rel)} counters beyond "
                   f"{HLL_BOUND:.4f} of the truth, {int(too_far.sum())} beyond the "
                   f"hard limit, worst {rel.max():.4f}")
    return out


class RefBitSet:
    """One RBitSet as a bool plane: SETBITSB, BITOP OR / XOR, BITCOUNT."""

    def __init__(self, nbits: int):
        self.bits = np.zeros(nbits, bool)

    def set_each(self, idx):
        """Sets the bits; returns their previous values (SETBITSB's reply;
        a duplicate index inside one call reads the pre-call value)."""
        idx = np.asarray(idx, np.int64)
        old = self.bits[idx].copy()
        self.bits[idx] = True
        return old

    def or_(self, other: "RefBitSet"):
        self.bits |= other.bits

    def xor(self, other: "RefBitSet"):
        self.bits ^= other.bits

    def count(self) -> int:
        return int(self.bits.sum())

    def byte_length(self) -> int:
        """Bytes up to the highest set bit: what BITOP replies for its
        destination."""
        on = np.flatnonzero(self.bits)
        return (int(on[-1]) + 8) // 8 if len(on) else 0

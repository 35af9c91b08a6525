"""Deterministic, versioned, vectorized hashing for sketch index computation.

Role parity: the reference computes sketch indexes *client-side* with
HighwayHash128 (``org/redisson/misc/Hash.java:28-75``,
``org/redisson/misc/HighwayHash.java``) and derives Bloom bit positions as
``(h1 + i*h2) % size`` (``org/redisson/RedissonBloomFilter.java:90-97,139-151``).

TPU-first re-design: instead of a scalar 64-bit hash per key on the host, we
hash *batches* of keys on-device with uint32-lane arithmetic (TPU has no native
64-bit integer path; a pair of independent 32-bit murmur-style hashes gives the
same double-hashing scheme without x64 emulation).  The same code runs under
numpy (host) and jax.numpy (device) — callers pick the namespace.

The scheme is part of the persisted format (bloom bit layouts are only
meaningful under the hash that produced them), so it is versioned:

    HASH_VERSION = 1  — "rtpu-mur32x2/1"
      * int keys: key split into (hi, lo) uint32 words, murmur3-x86-32 chain
        over the two words, seeds SEED1/SEED2; h2 forced odd.
      * byte keys: keys padded to W uint32 little-endian words; words beyond
        ceil(len/4) are masked out of the chain; length xored in finalization.

Any change to the mixing constants or word order MUST bump HASH_VERSION and be
treated as a new on-disk/in-HBM format.
"""
from __future__ import annotations

import numpy as np

HASH_VERSION = 1
HASH_NAME = "rtpu-mur32x2/1"

SEED1 = 0x9747B28C
SEED2 = 0x3C6EF372

_C1 = 0xCC9E2D51
_C2 = 0x1B873593
_FM1 = 0x85EBCA6B
_FM2 = 0xC2B2AE35


def _u32(xp, v):
    # np.uint32 scalars carry an explicit dtype, which keeps both numpy and
    # jax (x64 disabled — python ints > 2**31 would overflow weak int32) in
    # pure uint32 modular arithmetic.
    del xp
    return np.uint32(v)


def _rotl32(xp, x, r):
    return (x << r) | (x >> (32 - r))


def fmix32(x, xp=np):
    """Murmur3 finalizer. x: uint32 array."""
    x = x ^ (x >> 16)
    x = x * _u32(xp, _FM1)
    x = x ^ (x >> 13)
    x = x * _u32(xp, _FM2)
    x = x ^ (x >> 16)
    return x


def _mur_round(xp, h, k):
    k = k * _u32(xp, _C1)
    k = _rotl32(xp, k, 15)
    k = k * _u32(xp, _C2)
    h = h ^ k
    h = _rotl32(xp, h, 13)
    h = h * _u32(xp, 5) + _u32(xp, 0xE6546B64)
    return h


def hash_words(words, nbytes, seed, xp=np):
    """Murmur3-x86-32-style hash over uint32 word lanes.

    words: sequence of uint32 arrays (the key, one array per word position,
           all the same shape) — word j is masked out for keys with
           ceil(nbytes/4) <= j.
    nbytes: uint32 array, byte length of each key (0 => only finalization).
    seed: python int.
    Returns uint32 array of hashes.
    """
    h = xp.full_like(words[0], _u32(xp, seed)) if hasattr(words[0], "shape") else _u32(xp, seed)
    nwords = (nbytes + _u32(xp, 3)) >> 2
    for j, w in enumerate(words):
        hj = _mur_round(xp, h, w)
        h = xp.where(nwords > _u32(xp, j), hj, h)
    h = h ^ nbytes
    return fmix32(h, xp)


def hash_u64_pair(lo, hi, xp=np):
    """Hash 64-bit keys given as (lo, hi) uint32 arrays -> (h1, h2) uint32.

    h2 is forced odd so that the double-hashing stride (h1 + i*h2) visits
    distinct residues (same trick as the reference's Guava-style scheme,
    RedissonBloomFilter.java:90-97 keeps h2 as an independent stride).
    """
    eight = _u32(xp, 8)
    h1 = hash_words([lo, hi], xp.full_like(lo, eight), SEED1, xp)
    h2 = hash_words([lo, hi], xp.full_like(lo, eight), SEED2, xp)
    h2 = h2 | _u32(xp, 1)
    return h1, h2


def hash_packed_bytes(words, nbytes, xp=np):
    """Hash variable-length byte keys packed as uint32 word columns.

    words: uint32 array of shape (W, N) — column j holds word j of every key.
    nbytes: uint32 array (N,).
    Returns (h1, h2) uint32 arrays of shape (N,).
    """
    if words.shape[0] == 0:  # empty batch or zero-width packing
        z = xp.zeros(nbytes.shape, xp.uint32)
        return z, z
    cols = [words[j] for j in range(words.shape[0])]
    h1 = hash_words(cols, nbytes, SEED1, xp)
    h2 = hash_words(cols, nbytes, SEED2, xp) | _u32(xp, 1)
    return h1, h2


def pack_keys(keys):
    """Host-side: pack a list of bytes keys into (words[W,N] uint32, nbytes[N]).

    W is ceil(maxlen/4); little-endian word packing, zero padding.
    """
    n = len(keys)
    if n == 0:
        return np.zeros((0, 0), np.uint32), np.zeros((0,), np.uint32)
    nbytes = np.fromiter(map(len, keys), np.uint32, n)
    w = max(1, (int(nbytes.max()) + 3) // 4)
    # one join of the keys zero-padded to whole rows, viewed as words
    rows = np.frombuffer(b"".join(k.ljust(4 * w, b"\0") for k in keys), "<u4")
    return rows.reshape(n, w).T.copy(), nbytes  # (W, N)


def int_keys_to_u32_pair(keys):
    """Host-side: int64/uint64 numpy array -> (lo, hi) uint32 arrays."""
    k = np.asarray(keys).astype(np.uint64)
    lo = (k & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (k >> np.uint64(32)).astype(np.uint32)
    return lo, hi


def bloom_indexes(h1, h2, k, m_bits, xp=np):
    """Double-hashed bit positions: shape (..., k) int32; (h1 + i*h2) % m.

    Mirrors the reference's index derivation (RedissonBloomFilter.java:139-151)
    but on 32-bit lanes; m_bits must be < 2**31.
    """
    i = xp.arange(k, dtype=xp.uint32)
    idx = (h1[..., None] + i * h2[..., None]) % _u32(xp, m_bits)
    return idx.astype(xp.int32)

"""BloomFilter: the north-star object (BASELINE.md configs 1, 2, 5).

Parity target: ``org/redisson/RedissonBloomFilter.java`` —
  * geometry: optimalNumOfBits / optimalNumOfHashFunctions (:262-299, the
    Guava formulas), persisted config with optimistic concurrency (:203-213),
  * add/contains over k hashed bit positions (:90-196),
  * count() estimate from BITCOUNT.

TPU-first redesign: where the reference turns an N-key batch into k*N SETBIT/
GETBIT commands pipelined to Redis (SURVEY.md §3.4 — the hot loop), here the
whole batch is ONE kernel: hash on device, gather/scatter over the resident
bit plane, single boolean vector back.  Single-key calls ride the same path
with a 1-element batch (and are the slow path by design — batch or use
RBatch, exactly like the reference).
"""
from __future__ import annotations

import math
from typing import Iterable, Optional

import numpy as np

from redisson_tpu.client.objects.base import RExpirable
from redisson_tpu.core import kernels as K
from redisson_tpu.core.store import StateRecord
from redisson_tpu.ops import bittensor as bt
from redisson_tpu.utils import hashing as H


def optimal_num_of_bits(n: int, p: float) -> int:
    """RedissonBloomFilter.java:284-290 (Guava): m = -n ln p / (ln 2)^2."""
    if p == 0:
        p = 4.9e-324
    return int(-n * math.log(p) / (math.log(2) ** 2))


def optimal_num_of_hash_functions(n: int, m: int) -> int:
    """RedissonBloomFilter.java:292-298: k = max(1, round(m/n * ln 2))."""
    return max(1, round(m / max(1, n) * math.log(2)))


class BloomFilter(RExpirable):
    MAX_SIZE = 2**31 - 1024  # int32 index space minus plane padding

    # -- init / config ------------------------------------------------------

    def try_init(self, expected_insertions: int, false_probability: float) -> bool:
        """Create the filter config+plane; False if it already exists
        (RedissonBloomFilter.java:203-238 tryInit semantics)."""
        if not 0 < false_probability < 1:
            raise ValueError("false probability must be in (0, 1)")
        if expected_insertions <= 0:
            raise ValueError("expected insertions must be positive")
        m = optimal_num_of_bits(expected_insertions, false_probability)
        if m > self.MAX_SIZE:
            raise ValueError(f"bloom filter size {m} exceeds max {self.MAX_SIZE}")
        k = optimal_num_of_hash_functions(expected_insertions, m)
        with self._engine.locked(self._name):
            if self._engine.store.exists(self._name):
                return False

            def factory():
                return StateRecord(
                    kind="bloom",
                    meta={
                        "n": expected_insertions,
                        "p": false_probability,
                        "m": m,
                        "k": k,
                        "hash": H.HASH_NAME,
                    },
                    arrays={"bits": bt.make(m)},
                )

            self._engine.store.get_or_create(self._name, "bloom", factory)
            return True

    def _rec(self) -> StateRecord:
        rec = self._engine.store.get(self._name)
        if rec is None:
            raise RuntimeError(f"Bloom filter '{self._name}' is not initialized")
        if rec.meta.get("hash") != H.HASH_NAME:
            raise RuntimeError(
                f"Bloom filter '{self._name}' was built with hash "
                f"{rec.meta.get('hash')!r}, runtime is {H.HASH_NAME!r}"
            )
        return rec

    # -- geometry accessors (reference getter parity) -----------------------

    def get_expected_insertions(self) -> int:
        return self._rec().meta["n"]

    def get_false_probability(self) -> float:
        return self._rec().meta["p"]

    def get_size(self) -> int:
        return self._rec().meta["m"]

    def get_hash_iterations(self) -> int:
        return self._rec().meta["k"]

    # -- data plane ---------------------------------------------------------

    def add(self, obj) -> bool:
        """True iff the element was (probably) newly added."""
        return bool(self.add_all([obj] if not isinstance(obj, np.ndarray) else obj))

    def add_all(self, objs) -> int:
        """Batch add; returns the number of (probably) new elements
        (RedissonBloomFilter.java:105-137 contract)."""
        return int(self.add_all_async(objs))

    def add_all_async(self, objs):
        """Pipelined add: newly-added count as a DEVICE scalar (4-byte result
        path, no host sync) — streaming writers dispatch flush after flush and
        only the final int() waits."""
        kind, arrays, n = self._engine.pack_keys(objs, self._codec)
        if n == 0:
            return np.int32(0)
        with self._engine.locked(self._name):
            rec = self._rec()
            m, k = rec.meta["m"], rec.meta["k"]
            bits = rec.arrays["bits"]
            if kind == "u64":
                bits, count = K.bloom_add_packed_count(bits, arrays, K.valid_n(n), k, m)
            else:
                words, nbytes = arrays
                bits, newly = K.bloom_add_bytes_masked(bits, words, nbytes, n, k, m)
                count = newly.astype(np.int32).sum()
            rec.arrays["bits"] = bits
            self._touch_version(rec)
        return count

    def add_each(self, objs) -> np.ndarray:
        """Batch add; returns a per-key "was newly added" bool array aligned
        with objs (the BF.MADD reply shape)."""
        newly, n = self.add_each_async(objs)
        return np.asarray(newly)[:n]

    def add_each_async(self, objs):
        """Pipelined batch add: (device newly-added array, n_valid) with NO
        host sync — the mutation is dispatched; callers force later (the
        frame-level lazy-reply path in server/registry.py, and streaming
        writers that keep flushes in flight)."""
        kind, arrays, n = self._engine.pack_keys(objs, self._codec)
        if n == 0:
            return np.zeros((0,), bool), 0
        with self._engine.locked(self._name):
            rec = self._rec()
            m, k = rec.meta["m"], rec.meta["k"]
            bits = rec.arrays["bits"]
            if kind == "u64":
                bits, newly = K.bloom_add_packed(bits, arrays, K.valid_n(n), k, m)
            else:
                words, nbytes = arrays
                bits, newly = K.bloom_add_bytes_masked(bits, words, nbytes, n, k, m)
            rec.arrays["bits"] = bits
            self._touch_version(rec)
        return newly, n

    def answer_window_async(self, items, adds):
        """Probes and adds of byte items from different clients, answered
        together as ONE one-at-a-time execution — every probe, then every
        add in the order given — by ONE upload and ONE dispatch under the
        record's lock, nothing fetched: (device uint8 flags, n_valid).
        `adds[i]` says whether item i is added (BF.ADD) or probed
        (BF.EXISTS).  add_each_async answers a batch from one gather taken
        before its scatter, right for a caller's own batch; here an add
        whose every clear cell an EARLIER add sets reports 0, and a probe
        does not see the window's adds (kernels.bloom_window_bytes_masked;
        server/verbs/sketch.py point_window)."""
        words, nbytes = H.pack_keys(
            [o if isinstance(o, bytes) else self._codec.encode(o) for o in items])
        n = len(nbytes)
        w = K.pow2_bucket(words.shape[0], minimum=4)
        buf = np.zeros((w + 2, K.pow2_bucket(n)), np.uint32)
        buf[:words.shape[0], :n] = words
        buf[w, :n] = nbytes
        buf[w + 1, :n] = adds
        staged = K.stage(buf)
        with self._engine.locked(self._name):
            rec = self._rec()
            bits, flags = K.bloom_window_bytes_masked(
                rec.arrays["bits"], staged, K.valid_n(n), rec.meta["k"], rec.meta["m"])
            rec.arrays["bits"] = bits
            if any(adds):
                self._touch_version(rec)
        return flags, n

    def contains(self, obj) -> bool:
        if isinstance(obj, np.ndarray):
            raise TypeError("use contains_each / count_contains for batches")
        return bool(self.contains_each([obj])[0])

    def contains_each(self, objs) -> np.ndarray:
        """Vectorized membership: bool array aligned with objs."""
        found, n = self.contains_each_async(objs)
        arr = np.asarray(found)
        if arr.dtype == np.uint32:  # packed-bitmap fast path (u64 keys)
            return K.unpack_found(arr, n)
        return arr[:n]

    def contains_each_async(self, objs):
        """Pipelined membership with no host sync — the RBatch executeAsync
        analog (keep several flushes in flight, force later; see
        BloomFilterArray.contains_async).  For integer-key batches the result
        is a device uint32 bitmap (decode with kernels.unpack_found); for
        codec-encoded keys it is a device bool array."""
        kind, arrays, n = self._engine.pack_keys(objs, self._codec, cache_hot=True)
        if n == 0:
            return np.zeros((0,), np.uint32), 0
        # Dispatch under the record lock: a concurrent add() donates the bit
        # plane, which would invalidate the buffer between our read of
        # rec.arrays and the kernel call.  The device-side result fetch
        # happens outside the lock.
        with self._engine.locked(self._name):
            rec = self._rec()
            m, k = rec.meta["m"], rec.meta["k"]
            bits = rec.arrays["bits"]
            if kind == "u64":
                found = K.bloom_contains_packed_bits(bits, arrays, K.valid_n(n), k, m)
            else:
                words, nbytes = arrays
                found = K.bloom_contains_bytes_masked(bits, words, nbytes, n, k, m)
        return found, n

    def count_contains(self, objs) -> int:
        """Number of objs (probably) present — reference contains(Collection)."""
        return int(self.contains_each(objs).sum())

    def count(self) -> int:
        """Approximate cardinality from the fill ratio
        (RedissonBloomFilter.java count(): X = BITCOUNT; -m/k * ln(1 - X/m))."""
        with self._engine.locked(self._name):
            rec = self._rec()
            m, k = rec.meta["m"], rec.meta["k"]
            x = int(K.bitset_popcount(rec.arrays["bits"], m))
        if x == 0:
            return 0
        if x >= m:
            return rec.meta["n"]
        return int(round(-m / k * math.log1p(-x / m)))

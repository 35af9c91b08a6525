"""BloomFilterArray: multi-tenant bloom bank (BASELINE.md config 2 / §7.3-7).

The reference models "1000 tenant filters" as 1000 independent RBloomFilter
objects whose batched ops still execute per-key on the server.  The TPU-first
design packs all tenants of one family into a single (T, m) bit plane so a
mixed 100k-op flush spanning hundreds of tenants is STILL one kernel — the
tenant id is just another index column (SURVEY.md §7.3 item 7).

Per-tenant semantics preserved: clear_tenant drops one row, per-tenant counts
via row popcounts.  Geometry (m, k) is shared across tenants by construction
— the trade the reference cannot express.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

from redisson_tpu.client.objects.base import RExpirable
from redisson_tpu.client.objects.bloom import optimal_num_of_bits, optimal_num_of_hash_functions
from redisson_tpu.core import kernels as K
from redisson_tpu.core.store import StateRecord
from redisson_tpu.ops import bittensor as bt
from redisson_tpu.utils import hashing as H

import jax.numpy as jnp


class BloomFilterArray(RExpirable):
    def try_init(self, tenants: int, expected_insertions: int, false_probability: float) -> bool:
        """Create a (tenants, m) bank; m/k sized per tenant."""
        if tenants <= 0:
            raise ValueError("tenants must be positive")
        m = optimal_num_of_bits(expected_insertions, false_probability)
        m = bt.padded_size(m)  # row-align so the 2-D plane tiles cleanly
        k = optimal_num_of_hash_functions(expected_insertions, m)
        if tenants * m > K.BANK_MAX_CELLS:
            raise ValueError(
                f"bank of {tenants} x {m} bits = {tenants * m} cells exceeds the "
                f"single-chip flat-index limit ({K.BANK_MAX_CELLS}); use fewer/"
                "smaller tenants or the sharded mesh kernels (parallel.sharded)"
            )
        with self._engine.locked(self._name):
            if self._engine.store.exists(self._name):
                return False
            self._engine.store.put(
                self._name,
                StateRecord(
                    kind="bloom_array",
                    meta={
                        "tenants": tenants,
                        "n": expected_insertions,
                        "p": false_probability,
                        "m": m,
                        "k": k,
                        "hash": H.HASH_NAME,
                    },
                    arrays={"bits": jnp.zeros((tenants, m), jnp.uint8)},
                ),
            )
            return True

    def _rec(self) -> StateRecord:
        rec = self._engine.store.get(self._name)
        if rec is None:
            raise RuntimeError(f"BloomFilterArray '{self._name}' is not initialized")
        return rec

    def tenants(self) -> int:
        return self._rec().meta["tenants"]

    def get_size(self) -> int:
        return self._rec().meta["m"]

    def get_hash_iterations(self) -> int:
        return self._rec().meta["k"]

    def _validate_flush(self, tenant_ids, keys, allow_empty: bool = True):
        """Shared flush validation/conversion for the single-flush and
        window packers — ONE place for dtype/shape rules so the two transfer
        layouts can never drift."""
        t = np.ascontiguousarray(tenant_ids, np.int32)
        if not self._engine.is_int_batch(keys):
            raise TypeError(
                "BloomFilterArray is the vectorized fast path: keys must be an "
                "integer numpy array (use BloomFilter for codec-encoded objects)"
            )
        arr = np.ascontiguousarray(keys, np.int64)
        if t.shape != arr.shape or t.ndim != 1:
            raise ValueError("tenant_ids and keys must be aligned 1-D arrays")
        if not allow_empty and arr.shape[0] == 0:
            raise ValueError("window flushes must be non-empty")
        return t, arr

    def _pack(self, tenant_ids, keys, cache_hot: bool = False):
        """One flush -> ONE contiguous (3, B) uint32 transfer buffer
        (rows: tenant, key-lo, key-hi): one transfer per flush instead of
        three (core/kernels.py pack_rows note).

        Hot-set reuse (`cache_hot`, read paths only): the staged buffer is
        content-addressed (kernels query cache), so a serving loop
        re-probing the same working set skips the pack AND the upload — a
        sync flush then costs one computed-result fetch, i.e. the transport
        floor.  Write flushes never cache: one-shot operands would evict
        the hot set for zero hits."""
        t, arr = self._validate_flush(tenant_ids, keys)
        n = arr.shape[0]
        b = K.bucket_size(max(1, n))

        def build():
            lo, hi = H.int_keys_to_u32_pair(arr)
            return K.pack_rows(t, lo, hi, size=b, pool=self._engine.staging_pool())

        if cache_hot and n >= 4096:
            return K.cached_staged(build, t, arr, extra=b"bfa%d" % b), n
        return build(), n

    def add_each(self, tenant_ids, keys) -> np.ndarray:
        """Batch add across tenants; bool array: element was (probably) new."""
        newly, n = self.add_each_async(tenant_ids, keys)
        return np.asarray(newly)[:n]

    def add_each_async(self, tenant_ids, keys):
        """Pipelined add: (device newly-added array, n_valid), no host sync —
        callers (the server's lazy-reply frames, streaming writers) force
        once per batch of flushes."""
        tlh, n = self._pack(tenant_ids, keys)
        if n == 0:
            return np.zeros((0,), bool), 0
        with self._engine.locked(self._name):
            rec = self._rec()
            bits, newly = K.bloom_bank_add_packed(
                rec.arrays["bits"], tlh, K.valid_n(n), rec.meta["k"], rec.meta["m"]
            )
            K.count_rows(n, tlh.shape[1])  # one-shot: the bucket is walked
            rec.arrays["bits"] = bits
            self._touch_version(rec)
        return newly, n

    def add(self, tenant_ids, keys) -> int:
        """Batch add across tenants; returns # of (probably) new elements."""
        return int(self.add_async(tenant_ids, keys))

    def add_async(self, tenant_ids, keys):
        """Pipelined add: returns the newly-added count as a DEVICE scalar
        without forcing a host sync — streaming writers dispatch flush after
        flush and only the final int() conversion waits."""
        tlh, n = self._pack(tenant_ids, keys)
        if n == 0:
            return np.int32(0)
        with self._engine.locked(self._name):
            rec = self._rec()
            bits, count = K.bloom_bank_add_packed_count(
                rec.arrays["bits"], tlh, K.valid_n(n), rec.meta["k"], rec.meta["m"]
            )
            K.count_rows(n, tlh.shape[1])  # one-shot: the bucket is walked
            rec.arrays["bits"] = bits
            self._touch_version(rec)
        return count

    def contains(self, tenant_ids, keys) -> np.ndarray:
        """Vectorized membership across tenants: bool array aligned with keys."""
        packed, n = self.contains_async(tenant_ids, keys)
        return K.unpack_found(np.asarray(packed), n)

    def contains_async(self, tenant_ids, keys):
        """Pipelined variant: returns (device uint32 result bitmap, n_valid)
        WITHOUT forcing the device->host transfer — callers keep several
        flushes in flight, force later (jax.device_get / np.asarray), and
        decode with kernels.unpack_found(bitmap, n).  Results travel as
        bitmaps because B bool bytes per flush dominate the d2h path (the
        executeAsync analog of RBatch; dispatches overlap so dispatch
        latency amortizes away)."""
        tlh, n = self._pack(tenant_ids, keys, cache_hot=True)
        if n == 0:
            return np.zeros((0,), np.uint32), 0
        with self._engine.locked(self._name):
            rec = self._rec()
            found = K.bloom_bank_contains_packed_bits(
                rec.arrays["bits"], tlh, K.valid_n(n), rec.meta["k"], rec.meta["m"]
            )
            K.count_rows(n, K.rows_issued(n, tlh.shape[1]))
        return found, n

    # -- window submission (multi-flush, single transfer) --------------------

    def _pack_flush_window(self, flushes):
        """Pack R flushes into ONE contiguous (3, R*Bb) uint32 buffer staged
        to the device in a single async copy.

        The RBatch discipline taken one level further: the reference batches
        k*N SETBIT/GETBITs of one logical op into one CommandsData frame
        (command/CommandBatchService.java:87-151); a window submission
        batches R whole flushes into one frame: one large copy and one
        dispatch instead of R of each.

        Each flush gets a uniform Bb = bucket_size(max_len) slot; the slack
        is filled by REPEATING the flush's last entry, so the same packed
        buffer is valid for add (scatter-OR is idempotent; repeats set the
        same bits again) and for contains (repeat results are discarded at
        unpack).  Returns (device buffer, Bb, lengths)."""
        if not flushes:
            raise ValueError("empty window")
        # identity dedupe: window position -> unique-flush slot.  Keyed on the
        # CALLER's array objects (all alive in `flushes`, so ids are unique
        # among them) — exact, and costs nothing for all-distinct windows.
        slot_of: dict = {}
        first_pos: list = []
        idx = np.empty(len(flushes), np.int32)
        for i, (t, k) in enumerate(flushes):
            key = (id(t), id(k))
            s = slot_of.get(key)
            if s is None:
                s = slot_of[key] = len(first_pos)
                first_pos.append(i)
            idx[i] = s
        rows = [
            self._validate_flush(*flushes[i], allow_empty=False) for i in first_pos
        ]
        lengths = [rows[idx[i]][1].shape[0] for i in range(len(flushes))]
        bb = K.bucket_size(max(lengths))

        def fill(dst, t, arr):
            n = arr.shape[0]
            lo, hi = H.int_keys_to_u32_pair(arr)
            dst[0, :n] = t.view(np.uint32)
            dst[1, :n] = lo
            dst[2, :n] = hi
            if n < bb:  # repeat-pad: idempotent for add, ignored for contains
                dst[:, n:bb] = dst[:, n - 1 : n]

        if len(rows) == len(flushes):
            # all distinct: one flat buffer, no device-side composition.
            # The buffer comes from the engine's double-buffered staging
            # pool (overlap plane): packing window W+1 overlaps window W's
            # still-in-flight upload instead of waiting allocator + DMA.
            pool = self._engine.staging_pool()
            shape = (3, len(rows) * bb)
            if pool is None:
                buf, slot = np.zeros(shape, np.uint32), None
            else:
                buf, slot = pool.acquire(shape, np.uint32)
            try:
                for i, (t, arr) in enumerate(rows):
                    fill(buf[:, i * bb : (i + 1) * bb], t, arr)
                staged = K.stage(buf)
            except BaseException:
                if pool is not None:
                    pool.release(slot)  # never leak a busy slot on error
                raise
            if pool is not None:
                pool.commit(slot, staged)
            return staged, bb, lengths
        # repeated flushes: upload UNIQUE buffers once, compose the window
        # in HBM (kernels.window_from_unique) — R-x less h2d traffic for
        # hot-set workloads that re-submit the same query buffers
        uniq = np.zeros((len(rows), 3, bb), np.uint32)
        for s, (t, arr) in enumerate(rows):
            fill(uniq[s], t, arr)
        tlh = K.window_from_unique(K.stage(uniq), K.stage(idx))
        return tlh, bb, lengths

    def contains_flushes_async(self, flushes):
        """Submit R contains flushes as ONE upload + ONE kernel dispatch.

        Returns (device uint32 bitmap over R*Bb entries, Bb, lengths); decode
        flush i with kernels.unpack_found on the [i*Bb, i*Bb+lengths[i])
        slice (contains_flushes does this).  This is the throughput path for
        pipelined multi-flush workloads (BASELINE config 2)."""
        tlh, bb, lengths = self._pack_flush_window(flushes)
        total = tlh.shape[1]
        with self._engine.locked(self._name):
            rec = self._rec()
            packed = K.bloom_bank_contains_packed_bits(
                rec.arrays["bits"], tlh, K.valid_n(total), rec.meta["k"], rec.meta["m"]
            )
            K.count_rows(sum(lengths), total)  # n_valid covers the whole window
        return packed, bb, lengths

    def contains_flushes(self, flushes) -> list:
        """Sync window submission: list of bool arrays, one per flush."""
        packed, bb, lengths = self.contains_flushes_async(flushes)
        full = K.unpack_found(np.asarray(packed), len(lengths) * bb)
        return [full[i * bb : i * bb + n] for i, n in enumerate(lengths)]

    def add_flushes_async(self, flushes):
        """Submit R add flushes as ONE upload + ONE kernel dispatch; returns
        (device newly-added uint32 bitmap, Bb, lengths) without a host sync
        — the bulk-populate path (one transfer for a whole ingest window)."""
        tlh, bb, lengths = self._pack_flush_window(flushes)
        total = tlh.shape[1]
        with self._engine.locked(self._name):
            rec = self._rec()
            bits, newly = K.bloom_bank_add_packed_bits(
                rec.arrays["bits"], tlh, K.valid_n(total), rec.meta["k"], rec.meta["m"]
            )
            K.count_rows(sum(lengths), total)
            rec.arrays["bits"] = bits
            self._touch_version(rec)
        return newly, bb, lengths

    def add_flushes(self, flushes) -> list:
        """Sync window submission: newly-added count per flush.

        Positions past lengths[i] (the repeat-padding) are sliced off before
        counting, so padding never inflates counts.  "Newly" is evaluated
        against the bank state at WINDOW start (one batch-parallel dispatch):
        a key appearing in two flushes of the same window counts as new in
        both — identical to the existing semantics for duplicate keys inside
        a single flush."""
        newly, bb, lengths = self.add_flushes_async(flushes)
        full = K.unpack_found(np.asarray(newly), len(lengths) * bb)
        return [int(full[i * bb : i * bb + n].sum()) for i, n in enumerate(lengths)]

    def clear_tenant(self, tenant_id: int) -> None:
        with self._engine.locked(self._name):
            rec = self._rec()
            rec.arrays["bits"] = rec.arrays["bits"].at[tenant_id].set(jnp.uint8(0))
            self._touch_version(rec)

    def tenant_bit_counts(self) -> np.ndarray:
        """Per-tenant set-bit counts (fill monitoring / growth policy input)."""
        with self._engine.locked(self._name):
            rec = self._rec()
            return np.asarray(jnp.sum(rec.arrays["bits"].astype(jnp.int32), axis=1))

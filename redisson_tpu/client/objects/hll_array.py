"""HyperLogLogArray: a bank of HLL counters as one (T, m) register tensor.

Capability analog of running many RHyperLogLog objects (BASELINE.md config 3:
"10k counters, streaming add + pairwise mergeWith"): the reference issues
PFADD/PFMERGE per counter; here a mixed-tenant add batch is one scatter-max
kernel and a whole wave of pairwise merges is one row-gather + scatter-max —
per-counter semantics with bank-wide dispatch (SURVEY.md §7.3 item 7).
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from redisson_tpu.client.objects.base import RExpirable
from redisson_tpu.core import kernels as K
from redisson_tpu.core.store import StateRecord
from redisson_tpu.ops import hll as hll_ops
from redisson_tpu.utils import hashing as H


class HyperLogLogArray(RExpirable):
    def try_init(self, tenants: int, p: int = hll_ops.DEFAULT_P) -> bool:
        if tenants <= 0:
            raise ValueError("tenants must be positive")
        with self._engine.locked(self._name):
            if self._engine.store.exists(self._name):
                return False
            self._engine.store.put(
                self._name,
                StateRecord(
                    kind="hll_array",
                    meta={"tenants": tenants, "p": p, "hash": H.HASH_NAME},
                    arrays={"regs": hll_ops.make_bank(tenants, p)},
                ),
            )
            return True

    def _rec(self) -> StateRecord:
        rec = self._engine.store.get(self._name)
        if rec is None:
            raise RuntimeError(f"HyperLogLogArray '{self._name}' is not initialized")
        return rec

    def tenants(self) -> int:
        return self._rec().meta["tenants"]

    def add(self, tenant_ids, keys) -> None:
        """Mixed-tenant streaming add: one scatter-max kernel."""
        t = np.ascontiguousarray(tenant_ids, np.int32)
        if not self._engine.is_int_batch(keys):
            raise TypeError("HyperLogLogArray fast path requires integer numpy keys")
        arr = np.ascontiguousarray(keys, np.int64)
        if t.shape != arr.shape:
            raise ValueError("tenant_ids and keys must be aligned 1-D arrays")
        n = arr.shape[0]
        if n == 0:
            return
        b = K.bucket_size(n)
        lo, hi = H.int_keys_to_u32_pair(arr)
        tlh = K.pack_rows(t, lo, hi, size=b)  # one contiguous transfer buffer
        with self._engine.locked(self._name):
            rec = self._rec()
            rec.arrays["regs"] = K.hll_bank_add_packed(rec.arrays["regs"], tlh, K.valid_n(n), rec.meta["p"])
            K.count_rows(n, b)  # one-shot: the bucket is walked
            self._touch_version(rec)

    def merge_rows(self, dst_ids, src_ids) -> None:
        """Batched pairwise PFMERGE: counter[dst] |= counter[src] per pair.

        Each round ships ONE (P,) source map and dispatches ONE dense
        gather+max over the bank (kernels.hll_bank_merge_map) — the
        scatter-free shape that lifted config3 off the serialized
        row-scatter path.  Pairs sharing a dst split into successive
        unique-dst rounds; rounds past the first gather from a PRE-CALL
        snapshot of the bank (hll_bank_merge_map_from), so every source
        folds in with read-all-sources-from-old scatter-max semantics —
        a dst updated in round 1 cannot leak its new registers through a
        later round."""
        import jax.numpy as jnp

        dst = np.ascontiguousarray(dst_ids, np.int32)
        src = np.ascontiguousarray(src_ids, np.int32)
        if dst.shape != src.shape:
            raise ValueError("dst_ids and src_ids must be aligned")
        if dst.shape[0] == 0:
            return
        with self._engine.locked(self._name):
            rec = self._rec()
            P = rec.arrays["regs"].shape[0]
            if dst.size and (int(dst.min()) < 0 or int(dst.max()) >= P
                             or int(src.min()) < 0 or int(src.max()) >= P):
                raise ValueError(f"counter id out of range [0, {P})")
            multi_round = len(np.unique(dst)) != dst.shape[0]
            # duplicate dsts: later rounds must read sources from the
            # pre-call bank, which the first round's donation destroys
            orig = jnp.copy(rec.arrays["regs"]) if multi_round else None
            first_round = True
            pairs_d, pairs_s = dst, src
            while pairs_d.size:
                _vals, first = np.unique(pairs_d, return_index=True)
                take = np.zeros(pairs_d.shape[0], bool)
                take[first] = True
                src_map = np.arange(P, dtype=np.int32)
                src_map[pairs_d[take]] = pairs_s[take]
                if first_round:
                    rec.arrays["regs"] = K.hll_bank_merge_map(
                        rec.arrays["regs"], K.stage(src_map)
                    )
                    first_round = False
                else:
                    rec.arrays["regs"] = K.hll_bank_merge_map_from(
                        rec.arrays["regs"], orig, K.stage(src_map)
                    )
                pairs_d, pairs_s = pairs_d[~take], pairs_s[~take]
            self._touch_version(rec)

    def estimate_all(self) -> np.ndarray:
        """Per-tenant cardinality estimates (one fused reduce over the bank)."""
        return np.asarray(self.estimate_all_async())

    def estimate_all_async(self):
        """Pipelined estimate: the (T,) float64 result stays on DEVICE — the
        server's reply path rides it as a readback future (overlap plane),
        so an estimate sweep never blocks the frame that asked for it."""
        with self._engine.locked(self._name):
            rec = self._rec()
            return K.hll_estimate(rec.arrays["regs"])

    def estimate_union_pairs(self, a_ids, b_ids) -> np.ndarray:
        """PFCOUNT of union per (a, b) pair without mutating either row."""
        return np.asarray(self.estimate_union_pairs_async(a_ids, b_ids))

    def estimate_union_pairs_async(self, a_ids, b_ids):
        """Pipelined pairwise union estimate (device result, no host sync)."""
        a = np.ascontiguousarray(a_ids, np.int32)
        b = np.ascontiguousarray(b_ids, np.int32)
        with self._engine.locked(self._name):
            rec = self._rec()
            return K.hll_bank_estimate_union_pairs(rec.arrays["regs"], a, b)

"""Persistent kernel warm-pool: precompile the hot programs OUTSIDE any
request's latency budget (ISSUE 2 tentpole, part 2).

XLA compiles lazily: the first dispatch of every (kernel, shape-bucket,
dtype) combination pays trace + compile (~seconds for the big programs) or,
with the persistent compile cache, a program LOAD (~1-2s for the word-count
sort) — inside whatever request happened to arrive first.  That is exactly
the MapReduce cold-start miss (BENCH r3-r5: 2.3s vs the <2s target) and the
windowed-phase recompile stalls.  The reference keeps executor workers warm
for the same reason (executor/TasksRunnerService.java:54,192 warm pools);
here "warm" means the compiled program is resident in the in-process jit
cache before serving starts.

One process-global pool (jit caches are process-global), keyed by
``(verb, shape, dtype, epoch, geometry, device)``:

  * verb   — logical kernel family ("bloom.add", "hll.add", "wc", ...);
  * shape  — the padded shape bucket(s) the program was built for;
  * dtype  — operand dtype discriminator;
  * epoch  — mesh epoch for sharded programs (a reshard invalidates those
             builds; single-chip programs use epoch 0);
  * device — the PLACEMENT axis (ISSUE 8): jit specializes per committed
             device, so with the slot table device-sharded a program warmed
             on device 0 is cold on device 3.  ``prewarm_store`` therefore
             warms each geometry on every requested device (the whole local
             mesh under ``Engine.prewarm`` with placement on) — a slot
             handoff onto any device then hits the pool with ZERO rebuilds.
             Single-device engines use device id -1 (the default device),
             preserving every pre-placement key.

The pool only BOOKKEEPS which combinations are already warm (bounded LRU —
it never pins device memory; compiled executables live in jax's own cache);
``warm()`` runs the dummy-dispatch thunk exactly once per key, so engine
startup, mapper boot and repeated prewarm calls cannot duplicate compile
work.  ``prewarm_store`` walks an engine's live records and warms each
record kind's hot verbs at the requested batch buckets — the server-boot
ritual (TpuServer --prewarm / Engine.prewarm()).

The SHARDED warm pool (cross-epoch kernel reuse when a reshard returns to a
previous geometry) lives on parallel/manager.MeshManager; this module covers
the single-chip engine kernels and the MapReduce programs.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Iterable, Optional, Tuple


class KernelWarmPool:
    """Bounded bookkeeping of warmed (verb, shape, dtype, epoch) keys."""

    def __init__(self, max_entries: int = 512):
        self._entries: "OrderedDict[Tuple, float]" = OrderedDict()
        self._max = max_entries
        self._lock = threading.Lock()
        self.hits = 0    # warm() calls that found the key already warm
        self.warms = 0   # thunks actually executed

    def warmed(self, key: Tuple) -> bool:
        with self._lock:
            return key in self._entries

    def warm(self, key: Tuple, thunk) -> bool:
        """Run `thunk` once per key; True iff THIS call executed it.
        The thunk runs OUTSIDE the lock (it may compile for seconds); a
        concurrent warm of the same key at worst duplicates one compile —
        jax's jit cache dedupes the program itself."""
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self.hits += 1
                return False
        thunk()
        import time

        with self._lock:
            self._entries[key] = time.monotonic()
            self._entries.move_to_end(key)
            while len(self._entries) > self._max:
                self._entries.popitem(last=False)
            self.warms += 1
        return True

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries), "hits": self.hits, "warms": self.warms}


# process-global pool: the jit cache it mirrors is process-global too
POOL = KernelWarmPool()


def _dev_key(device) -> int:
    """Pool-key device axis: -1 = the default (pre-placement) device, so
    single-device engines keep their historical keys exactly."""
    return -1 if device is None else getattr(device, "id", 0)


def _on(device, arr):
    """Commit a throwaway warm plane to `device` (the kernel then compiles
    FOR that device); None keeps the default placement."""
    if device is None:
        return arr
    import jax

    return jax.device_put(arr, device)


def _warm_bloom(engine, rec, buckets: Iterable[int], device=None) -> int:
    import numpy as np

    import jax

    from redisson_tpu.core import kernels as K
    from redisson_tpu.ops import bittensor as bt

    m, k = rec.meta["m"], rec.meta["k"]
    n = 0
    for b in buckets:
        b = K.bucket_size(b)

        def thunk(b=b):
            lh = K.stage(np.zeros((2, b), np.uint32))
            lh2 = K.stage(np.zeros((2, b), np.uint32))
            nv = K.valid_n(1)
            # throwaway zeros plane of the record's geometry: add kernels
            # DONATE their state, so real record planes never warm directly
            bits = _on(device, bt.make(m))
            bits, _ = K.bloom_add_packed(bits, lh, nv, k, m)
            K.bloom_contains_packed_bits(bits, lh, nv, k, m)
            bits2 = _on(device, bt.make(m))
            bits2, _ = K.bloom_add_packed_count(bits2, lh, nv, k, m)
            out = K.bloom_fused_add_contains(bits2, lh, nv, lh2, nv, k, m)
            jax.block_until_ready(out[0])

        n += POOL.warm(("bloom", (b,), "u64", 0, (m, k), _dev_key(device)), thunk)
    return n


def _warm_bloom_array(engine, rec, buckets: Iterable[int], device=None) -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from redisson_tpu.core import kernels as K

    m, k, tenants = rec.meta["m"], rec.meta["k"], rec.meta["tenants"]
    n = 0
    for b in buckets:
        b = K.bucket_size(b)

        def thunk(b=b):
            tlh = K.stage(np.zeros((3, b), np.uint32))
            nv = K.valid_n(1)
            bank = _on(device, jnp.zeros((tenants, m), jnp.uint8))
            bank, _ = K.bloom_bank_add_packed_bits(bank, tlh, nv, k, m)
            out = K.bloom_bank_contains_packed_bits(bank, tlh, nv, k, m)
            jax.block_until_ready(out)

        n += POOL.warm(
            ("bloom_array", (tenants, b), "u64", 0, (m, k), _dev_key(device)),
            thunk,
        )
    return n


def _warm_hll(engine, rec, buckets: Iterable[int], device=None) -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from redisson_tpu.core import kernels as K

    p = rec.meta["p"]
    regs = rec.arrays["regs"]
    shape = regs.shape
    n = 0
    for b in buckets:
        b = K.bucket_size(b)

        def thunk(b=b):
            nv = K.valid_n(1)
            dummy = _on(device, jnp.zeros(shape, regs.dtype))
            if len(shape) == 2:
                tlh = K.stage(np.zeros((3, b), np.uint32))
                out = K.hll_bank_add_packed(dummy, tlh, nv, p)
            else:
                lh = K.stage(np.zeros((2, b), np.uint32))
                out = K.hll_add_packed(dummy, lh, nv, p)
            jax.block_until_ready(out)

        n += POOL.warm(
            ("hll", shape, str(regs.dtype), 0, (p, b), _dev_key(device)), thunk
        )
    return n


def _warm_vector_bank(engine, rec, buckets: Iterable[int], device=None) -> int:
    """Warm one embedding bank's KNN programs (ISSUE 15): the FLAT
    matmul-top-k (and the IVF routed gather when the record carries a
    trained coarse index) at the bank's exact plane geometry, per device.
    Sharded banks hit this once PER SHARD RECORD — each shard is a plain
    vector_bank record on its own device — and their cross-shard merge
    warms through the manifest warmer below."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from redisson_tpu.core import kernels as K

    bank = rec.arrays.get("bank")
    if bank is None:
        return 0  # never flushed: no geometry to warm yet
    meta = rec.meta
    metric = str(meta.get("metric", "COSINE"))
    dtype = str(meta.get("dtype", "FLOAT32"))
    cap, pwidth = bank.shape
    k = max(1, min(10, cap))
    cells = rec.arrays.get("cells")
    cents = rec.arrays.get("centroids")
    nprobe = int(meta.get("nprobe", 0) or 1)
    n = 0

    def thunk():
        q = K.stage(np.zeros((1, pwidth), np.float32))
        nv = K.valid_n(1)
        dummy = _on(device, jnp.zeros(bank.shape, bank.dtype))
        scale = rec.arrays.get("scale")
        dscale = (
            _on(device, jnp.ones((cap,), jnp.float32))
            if scale is not None else None
        )
        dbias = _on(device, jnp.zeros((cap,), jnp.float32))
        out = K.knn_flat_topk(dummy, dscale, dbias, dbias, None, q, nv, k,
                              metric)
        if cells is not None and cents is not None:
            dc = _on(device, jnp.zeros(cents.shape, jnp.float32))
            dl = _on(device, jnp.zeros(cells.shape, jnp.int32))
            np_eff = max(1, min(nprobe, cents.shape[0]))
            k_ivf = max(1, min(k, np_eff * cells.shape[1]))
            if dscale is not None:
                out = K.knn_ivf_topk_q(dummy, dscale, dbias, dc, dl, q,
                                       nv, k_ivf, np_eff, metric)
            else:
                out = K.knn_ivf_topk(dummy, dbias, dc, dl, q, nv,
                                     k_ivf, np_eff, metric)
        jax.block_until_ready(out[0])

    ivf_key = (
        (cents.shape, cells.shape, nprobe)
        if cells is not None and cents is not None else None
    )
    n += POOL.warm(
        ("ftvec_knn", bank.shape, str(bank.dtype), metric, k, dtype,
         ivf_key, _dev_key(device)),
        thunk,
    )
    return n


def _warm_vector_manifest(engine, rec, buckets: Iterable[int],
                          device=None) -> int:
    """Warm the sharded-KNN MERGE program for a bank constellation: the
    jit instance comes from MeshManager's geometry-keyed cross-epoch pool
    (knn_merge_kernel), so a 4->8->4 reshard re-enters prewarm with the
    already-built program — 0 rebuilds, 0 first-dispatch traces."""
    import jax
    import jax.numpy as jnp

    from redisson_tpu.parallel.manager import MeshManager

    names = rec.meta.get("shard_names") or ()
    n_legs = len(names)
    if n_legs < 2:
        return 0
    mm = MeshManager.of(engine)
    geom = mm.geometry()
    merge = mm.knn_merge_kernel(n_legs, geom=geom)
    k = 10

    def thunk():
        dists = tuple(
            _on(device, jnp.zeros((1, k), jnp.float32))
            for _ in range(n_legs)
        )
        idxs = tuple(
            _on(device, jnp.zeros((1, k), jnp.int32)) for _ in range(n_legs)
        )
        sop = _on(device, jnp.zeros((n_legs * k,), jnp.int32))
        out = merge(dists, idxs, sop, k)
        jax.block_until_ready(out[0])

    return POOL.warm(
        ("ftvec_merge", n_legs, k, mm._mesh_key(geom.mesh),
         _dev_key(device)),
        thunk,
    )


_KIND_WARMERS = {
    "bloom": _warm_bloom,
    "bloom_array": _warm_bloom_array,
    "hll": _warm_hll,
    "hll_array": _warm_hll,
    "vector_bank": _warm_vector_bank,
    "vector_bank_manifest": _warm_vector_manifest,
}


def prewarm_store(engine, names: Optional[Iterable[str]] = None,
                  buckets: Iterable[int] = (0,),
                  devices: Optional[Iterable] = None) -> int:
    """Warm the hot verbs of every (named) live record at the given batch
    buckets (0 = the minimum bucket).  Returns the number of programs this
    call actually compiled/loaded; everything already warm is free.  Run at
    server boot or before a timed serving phase — never on the hot path.

    ``devices``: the placement axis — warm each geometry ON EACH of these
    devices (Engine.prewarm passes the whole local mesh with placement on,
    so ``tpu-server --prewarm`` compiles every device's kernels, not just
    device 0's, and a later slot handoff re-hits the pool: 0 rebuilds).
    None warms each record on its CURRENT device (the owner with placement
    on, the default device otherwise)."""
    from redisson_tpu.core import kernels as K

    buckets = [K.bucket_size(max(1, b)) for b in buckets]
    warmed = 0
    for name in list(names) if names is not None else engine.store.keys():
        rec = engine.store.get(name)
        if rec is None:
            continue
        warmer = _KIND_WARMERS.get(rec.kind)
        if warmer is None:
            continue
        if devices is not None:
            devs = list(devices)
        else:
            devs = [engine.device_for_name(name)]  # None with placement off
        with engine.locked(name):
            rec = engine.store.get(name)
            if rec is None:
                continue
            for dev in devs:
                warmed += warmer(engine, rec, buckets, device=dev)
    return warmed


def prewarm_word_count_pooled(total_chars: int, total_words: int,
                              n_chunks: int = 2) -> bool:
    """services.mapreduce.prewarm_word_count through the pool: repeated
    boots / repeated jobs over same-bucket corpora skip the (re)warm
    entirely.  True iff this call did the work."""
    from redisson_tpu.core import kernels as K

    b = K.bucket_size(max(1, -(-total_chars // n_chunks)))
    eb = K.bucket_size(max(1, -(-total_words // n_chunks)))

    def thunk():
        from redisson_tpu.services.mapreduce import prewarm_word_count

        prewarm_word_count(total_chars, total_words, n_chunks=n_chunks)

    return POOL.warm(("wc", (b, eb, n_chunks), "uint8", 0, ()), thunk)

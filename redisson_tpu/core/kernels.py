"""Jitted kernel dispatch: shape bucketing + donation + compile cache.

This is the heart of the L2' execution core (SURVEY.md §7.1-2): the reference
amortizes per-command overhead by pipelining RESP frames over one connection
(``command/CommandBatchService.java:87-151`` — one CommandsData write per
shard); the TPU equivalent amortizes XLA dispatch (~10-100us) by packing a
whole batch of ops into fixed-shape tensors and dispatching ONE compiled
kernel per (op-kind, shape-bucket).

Shape discipline: batch arrays are padded up to power-of-two buckets so the
number of distinct compiled programs is O(log max_batch) per op, never
O(#batch-sizes).  A dynamic `n_valid` scalar masks padding *inside* the kernel
(padded rows index out of range -> dropped scatters / ignored gathers), so
padding never corrupts state.

State-mutating kernels donate their state argument: XLA writes the new state
into the same HBM buffer — in-place semantics without in-place ops.

Why there is no Pallas kernel here (measured decision, 2026-07): the hot ops
are random-access bit/register probes — per-key gathers/scatters over a
plane far larger than VMEM.  Pallas on TPU has no vectorized gather (only
`pl.ds` slice-style dynamic indexing), so a hand-written probe kernel
degenerates to a scalar loop or a one-hot matmul whose one-hot operand is
O(batch x plane_rows) — both strictly worse than XLA's native gather unit.
What the chip reads (v5e device trace; ledger PR 22/24, micro-benchmark
PR 25): the bank probe of a 100,000-key flush over a (1000, 96256) plane is
7.05 ms one-shot, 92 % of it the byte gather at 8.1 ns an index — the same
per index for one gather of 802,816 or 49 of 14,336.  The served bulk cell is
kernel-bound on it (chip busy 98.7 %), so rows cost device time, not h2d
bytes: see _map_valid_chunks.  (An earlier "~21 us, transfer-bound" figure
here was a host-clock timing of the enqueue through a remote transport.)
The elementwise hash chain fuses into the gather kernel under XLA already.
Pallas remains the right tool for the mesh collectives' custom overlap if
profiling ever shows XLA's psum/pmax lagging (see parallel/sharded.py), and
for a probe only as a different algorithm (VMEM-resident bit-packed rows, a
tenant-grouped matmul) — ROADMAP Queue 1, SK.
"""
from __future__ import annotations

import functools
import threading as _threading
from collections import OrderedDict as _OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from redisson_tpu.ops import bittensor as bt
from redisson_tpu.ops import hll as hll_ops
from redisson_tpu.utils import hashing as H

MIN_BUCKET = 256


def _tag_compile_failure(e):
    """Replace the JaxRuntimeError of a failed XLA compile with
    ioplane.KernelCompileError, so the serving layer can tell a kernel the
    compiler refuses (deterministic, fatal to the frame) from a transient
    device fault (retryable): both are worded ``INTERNAL: ...``.  The text
    is kept verbatim — message-matched paths (a compile-time
    RESOURCE_EXHAUSTED still degrades as -OOM) see what they saw before.
    jax calls registered handlers only from its backend-compile step."""
    from redisson_tpu.core.ioplane import KernelCompileError

    return KernelCompileError(str(e))


# jax has no public hook for this; the private one is pinned by
# tests/test_chip_bringup.py, which fails loudly if an upgrade moves it
from jax._src import compiler as _jax_compiler  # noqa: E402

_jax_compiler.register_xla_runtime_error_handler(_tag_compile_failure)


def pow2_bucket(n: int, minimum: int = MIN_BUCKET) -> int:
    b = minimum
    while b < n:
        b <<= 1
    return b


def bucket_size(n: int, minimum: int = MIN_BUCKET) -> int:
    """Padded batch size of a flush: one static operand shape, one h2d and
    one compiled program a bucket.

    1/8-octave steps: next multiple of (next_pow2(n) / 8) — at most 12.5%
    padding, at most 8 compiled programs per octave in the jit cache.  The
    padding's bytes are cheap (a 1.4 MB h2d); its ROWS are not, where a
    one-shot body walks them: 8.1 ns a gathered index, 0.85 ms of a 7.05 ms
    100,000-key probe (v5e trace; ledger PR 22/24).  Bodies that can, stop
    at n_valid instead (_map_valid_chunks); a finer ladder would buy the same
    for up to four times the programs an octave.

    NOT the ladder of the coalescer's stacked runs (core/coalesce.py): their
    row count is 100 x however many of a frame's tenants share a device, a
    different rung nearly every frame, on a device the host cannot keep busy
    — they pad to one of four buckets (STACK_ROW_BUCKETS), all compiled
    together.
    """
    if n <= minimum:
        return minimum
    step = max(minimum, (1 << (int(n - 1).bit_length())) >> 3)
    return ((n + step - 1) // step) * step


def pad_to(arr: np.ndarray, size: int, axis: int = 0) -> np.ndarray:
    """Zero-pad `arr` along `axis` up to `size`."""
    if arr.shape[axis] == size:
        return arr
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, size - arr.shape[axis])
    return np.pad(arr, pad)


def _valid_mask(n: int, n_valid) -> jax.Array:
    return jnp.arange(n, dtype=jnp.int32) < n_valid


# Device work follows n_valid, not the bucket.  A bucket fixes the operand's
# shape (one h2d, one compiled program); what it pads is still gathered by a
# one-shot body, and at 8.1 ns an index that is device time: a 100,000-key
# probe in its 114,688 bucket spent 12.8 % of its gathers on rows nobody
# sent.  Large buckets therefore run as a loop over CHUNK-row slices whose
# trip count the device computes from n_valid.  Chosen from the device trace
# (v5e, n = 100,000, one-shot 7.051 ms): 1,024 -> 6.287 ms, 2,048 -> 6.216,
# 4,096 -> 6.301, 8,192 -> 6.564; a step costs ~1 us of its own.
CHUNK = 2048            # a multiple of 32, so _pack_bool_u32 words line up
CHUNKED_MIN_CHUNKS = 8  # below 8 * CHUNK rows the one-shot body runs as ever


def chunked(b: int) -> bool:
    """Whether a bucket of `b` rows runs as the chunk loop.  Decided from the
    operand's static shape alone: every bucket_size bucket of 16,384 rows or
    more is a multiple of CHUNK."""
    return b >= CHUNKED_MIN_CHUNKS * CHUNK and b % CHUNK == 0


def rows_issued(n: int, b: int) -> int:
    """Rows the device walks for `n` valid rows in a bucket of `b`, in a
    body that runs the chunk loop."""
    return -(-n // CHUNK) * CHUNK if chunked(b) else b


_ROWS_LOCK = _threading.Lock()
_rows_valid = 0
_rows_issued = 0


def count_rows(n: int, issued: int) -> None:
    """One bank dispatch: `n` rows a caller sent, `issued` rows the device
    walks for them (rows_issued(n, b) where the body runs the chunk loop,
    the bucket where it is one-shot).  Two integer adds, always on; METRICS
    exports the sums (kernel_rows_valid_total, kernel_rows_issued_total)."""
    global _rows_valid, _rows_issued
    with _ROWS_LOCK:  # server worker threads dispatch side by side
        _rows_valid += n
        _rows_issued += issued


def rows_counted() -> tuple:
    """(valid, issued) row totals of this process's bank dispatches."""
    return _rows_valid, _rows_issued


# Point commands: a single-item BF.ADD / BF.EXISTS (server/verbs/sketch.py
# point_window).  Counted by what is done, whatever does it: commands
# answered by verb, device dispatches issued for them, and count_rows's rule
# at those dispatches — rows the bytes kernel was handed against rows
# somebody asked for.  A window of commands formed across connections
# (server/server.py _join_point_window) counts its members here, its one
# dispatch once and itself once; a lone command is one command, one window,
# one dispatch, one row of a bucket of MIN_BUCKET.  Always on; METRICS
# exports the sums (point_cmds_total with point_cmds_bf_add_total /
# point_cmds_bf_exists_total, point_windows_total, point_dispatches_total,
# point_rows_valid_total, point_rows_issued_total).
POINT_VERBS = ("BF.ADD", "BF.EXISTS")
_point_cmds = dict.fromkeys(POINT_VERBS, 0)
_point_windows = 0
_point_dispatches = 0
_point_rows_valid = 0
_point_rows_issued = 0


def count_point_dispatch(n: int, issued: int) -> None:
    """One device dispatch issued for `n` point commands, handed `issued`
    rows."""
    global _point_dispatches, _point_rows_valid, _point_rows_issued
    with _ROWS_LOCK:
        _point_dispatches += 1
        _point_rows_valid += n
        _point_rows_issued += issued


def count_point_cmds(verb: str, n: int = 1) -> None:
    """`n` point commands of `verb` answered."""
    with _ROWS_LOCK:
        _point_cmds[verb] += n


def count_point_window() -> None:
    """One window of point commands served."""
    global _point_windows
    with _ROWS_LOCK:
        _point_windows += 1


def point_counted() -> dict:
    """This process's point-command totals: {"cmds": {verb: n}, "windows",
    "dispatches", "rows_valid", "rows_issued"}."""
    return {"cmds": dict(_point_cmds), "windows": _point_windows,
            "dispatches": _point_dispatches, "rows_valid": _point_rows_valid,
            "rows_issued": _point_rows_issued}


def _map_valid_chunks(rows, n_valid, body):
    """THE one expression of the policy: found[c] = body(*rows[c], n_valid -
    start of c) for the CHUNK-row slices c of `rows` (parallel 1-D arrays of
    one bucket) that hold a valid row — ceil(n_valid / CHUNK) of them, a trip
    count the device reads from n_valid, so a new n in the same bucket
    compiles nothing.  `body` masks the tail of the last chunk itself; chunks
    past it are never visited and read back as zeros (not found).

    Only for bodies whose rows are independent of each other: the probes.
    NOT the bloom adds: their `newly` flags come from a gather taken before
    the flush's own scatter, and a key repeated across two chunks would see
    its first add — another reply, not a faster one.  Nor the HLL bank's
    scatter-max, though max would allow any cut: inside a loop XLA:TPU
    scatters at 86 ns a row against the one-shot's 1 ms + 9.5 ns (v5e trace:
    9.8 ms looped, 3.3 ms one-shot, 100,000 rows into the 164 MB bank).
    Both stay one-shot."""

    def one(i, found):
        start = i * CHUNK
        chunk = [jax.lax.dynamic_slice(r, (start,), (CHUNK,)) for r in rows]
        return jax.lax.dynamic_update_slice(found, body(*chunk, n_valid - start), (start,))

    return jax.lax.fori_loop(0, (n_valid + (CHUNK - 1)) // CHUNK, one,
                             jnp.zeros(rows[0].shape, jnp.bool_))


_N_CACHE: "_OrderedDict" = _OrderedDict()
_N_CACHE_MAX = 4096
_N_CACHE_LOCK = _threading.Lock()


def valid_n(n: int):
    """Device-resident int32 scalar for `n_valid` kernel args.

    A Python int argument costs a fresh tiny host->device upload on every
    call; flush sizes repeat, so a cached device scalar turns that into a
    one-time cost per distinct n.
    True LRU eviction: a workload cycling through >_N_CACHE_MAX distinct
    flush sizes must not silently thrash re-uploads of its hottest sizes.
    Locked: server worker threads share this cache, and the hit-path
    move_to_end would KeyError against a concurrent eviction."""
    with _N_CACHE_LOCK:
        a = _N_CACHE.get(n)
        if a is not None:
            _N_CACHE.move_to_end(n)  # touch: keep hot sizes resident
            return a
    device_scalar = jnp.asarray(np.int32(n))  # upload outside the lock
    with _N_CACHE_LOCK:
        if len(_N_CACHE) >= _N_CACHE_MAX:
            _N_CACHE.popitem(last=False)  # evict the LEAST-recently-used
        return _N_CACHE.setdefault(n, device_scalar)


# --------------------------------------------------------------------------
# Bloom filter kernels (state = expanded bit plane; k, m static per filter
# geometry — the compile cache key).  Reference behavior being replaced:
# RedissonBloomFilter.java:105-196 (k*N SETBIT/GETBIT per RBatch flush).
# --------------------------------------------------------------------------

def _bloom_add_body(bits, lo, hi, n_valid, k: int, m: int):
    h1, h2 = H.hash_u64_pair(lo, hi, jnp)
    idx = H.bloom_indexes(h1, h2, k, m, jnp)
    mask = _valid_mask(lo.shape[0], n_valid)
    # sentinel = physical plane size (m alone may land in the padding lanes,
    # which must stay zero for bit_not/length_hint to be correct)
    idx = jnp.where(mask[:, None], idx, bits.shape[0])  # out of range -> dropped
    new_bits, newly = bt.set_and_report(bits, idx)
    return new_bits, newly & mask


def _bloom_contains_body(bits, lo, hi, n_valid, k: int, m: int):
    h1, h2 = H.hash_u64_pair(lo, hi, jnp)
    idx = H.bloom_indexes(h1, h2, k, m, jnp)
    return bt.contains(bits, idx) & _valid_mask(lo.shape[0], n_valid)


bloom_add_u64_masked = jax.jit(_bloom_add_body, static_argnums=(4, 5), donate_argnums=(0,))
bloom_contains_u64_masked = jax.jit(_bloom_contains_body, static_argnums=(4, 5))


@functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(0,))
def bloom_add_bytes_masked(bits, words, nbytes, n_valid, k: int, m: int):
    with jax.named_scope("bloom_add_bytes_masked"):
        h1, h2 = H.hash_packed_bytes(words, nbytes, jnp)
        idx = H.bloom_indexes(h1, h2, k, m, jnp)
        mask = _valid_mask(h1.shape[0], n_valid)
        idx = jnp.where(mask[:, None], idx, bits.shape[0])
        new_bits, newly = bt.set_and_report(bits, idx)
        return new_bits, newly & mask


@functools.partial(jax.jit, static_argnums=(4, 5))
def bloom_contains_bytes_masked(bits, words, nbytes, n_valid, k: int, m: int):
    with jax.named_scope("bloom_contains_bytes_masked"):
        h1, h2 = H.hash_packed_bytes(words, nbytes, jnp)
        idx = H.bloom_indexes(h1, h2, k, m, jnp)
        return bt.contains(bits, idx) & _valid_mask(h1.shape[0], n_valid)


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0,))
def bloom_window_bytes_masked(bits, buf, n_valid, k: int, m: int):
    """A window of point commands in ONE program (server/verbs/sketch.py
    point_window).  `buf` is one (W + 2, B) uint32 upload: W rows of item
    words (hashing.pack_keys' layout), a row of byte lengths, a row that
    is 1 for an add.  The answers are those of one one-at-a-time execution,
    the probes then the adds in row order: a probe reads the plane before
    the window; an add is newly added where one of its cells is clear in
    that plane AND is no cell of an earlier add of the window.  The adds'
    cells are then set.  Returns (plane, uint8[B] flags, 0 past n_valid)."""
    with jax.named_scope("bloom_window_bytes_masked"):
        w = buf.shape[0] - 2
        h1, h2 = H.hash_packed_bytes(buf[:w], buf[w], jnp)
        idx = H.bloom_indexes(h1, h2, k, m, jnp)
        rows = jnp.arange(idx.shape[0], dtype=jnp.int32)
        valid = rows < n_valid
        add = (buf[w + 1] != 0) & valid
        old = bits.at[idx].get(mode="fill", fill_value=1)
        present = jnp.all(old != 0, axis=-1)
        # cell (i, j) is set before add i by an earlier add i' < i of the
        # window: an equality test of every cell against every other, (B*k)
        # squared.  On a v5e, windows dispatched back to back are paced by
        # the host (about 0.3 ms each) with this test, a flat one or a sort
        earlier = add[None, :] & (rows[None, :] < rows[:, None])
        meets = jnp.any(idx[:, :, None, None] == idx[None, None, :, :], axis=3)
        covered = jnp.any(meets & earlier[:, None, :], axis=2)
        newly = jnp.any((old == 0) & ~covered, axis=-1)
        flags = jnp.where(add, newly, present & valid)
        new_bits = bt.set_bits(bits, jnp.where(add[:, None], idx, bits.shape[0]), 1)
        return new_bits, flags.astype(jnp.uint8)


# --- multi-tenant bloom bank: (T, m) bit plane, ops carry a tenant row ------
# (BASELINE config 2: 1k tenants, one kernel for a mixed 100k-op flush.)
# Indexing is flattened to 1-D (tenant*m + idx): XLA lowers flat gathers/
# scatters to the fast single-dim path, ~3x faster than 2-D (row, col)
# indexing on TPU (measured on the config-2 workload).  Flat indexes are
# int32, so banks are capped at BANK_MAX_CELLS cells — enforced at try_init
# (BloomFilterArray) — beyond which the sharded mesh kernels
# (parallel/sharded.py) are the intended path.

BANK_MAX_CELLS = 2**31 - 2048  # int32 flat-index space minus sentinel headroom

def _bloom_bank_add_body(bits2d, tenant, lo, hi, n_valid, k: int, m: int):
    h1, h2 = H.hash_u64_pair(lo, hi, jnp)
    idx = H.bloom_indexes(h1, h2, k, m, jnp)
    mask = _valid_mask(lo.shape[0], n_valid)
    size = bits2d.shape[0] * bits2d.shape[1]
    flat = bits2d.reshape(-1)
    # row stride is the PHYSICAL row width: for BloomFilterArray banks it
    # equals m (rows are padded_size-aligned at init), and it makes the same
    # kernels serve the coalescing plane's stacked single-filter planes,
    # whose physical size exceeds the logical hash domain m (core/coalesce)
    g = jnp.where(mask[:, None], tenant[:, None] * bits2d.shape[1] + idx, size)
    old = flat.at[g].get(mode="fill", fill_value=1)
    newly = jnp.any(old == 0, axis=-1) & mask
    new_flat = flat.at[g.reshape(-1)].set(jnp.uint8(1), mode="drop")
    return new_flat.reshape(bits2d.shape), newly


def _bloom_bank_probe(flat, width: int, tenant, lo, hi, n_valid, k: int, m: int):
    """Probe every row against the flat plane (row stride `width`); rows at or
    past n_valid gather too and are masked in the result."""
    h1, h2 = H.hash_u64_pair(lo, hi, jnp)
    idx = H.bloom_indexes(h1, h2, k, m, jnp)
    g = tenant[:, None] * width + idx
    got = flat.at[g].get(mode="fill", fill_value=1)
    return jnp.all(got != 0, axis=-1) & _valid_mask(lo.shape[0], n_valid)


def _bloom_bank_contains_body(bits2d, tenant, lo, hi, n_valid, k: int, m: int):
    # the flat view is formed once, outside the loop
    probe = functools.partial(_bloom_bank_probe, bits2d.reshape(-1), bits2d.shape[1], k=k, m=m)
    if chunked(lo.shape[0]):
        return _map_valid_chunks((tenant, lo, hi), n_valid, probe)
    return probe(tenant, lo, hi, n_valid)


bloom_bank_add_u64 = jax.jit(_bloom_bank_add_body, static_argnums=(5, 6), donate_argnums=(0,))
bloom_bank_contains_u64 = jax.jit(_bloom_bank_contains_body, static_argnums=(5, 6))


# --- packed-row variants ----------------------------------------------------
# One flush = ONE contiguous uint32 buffer (rows: tenant?, lo, hi) = ONE
# host->device transfer instead of three — the kernels below are identical
# math to their unpacked forms, they only change the wire layout.


# -- hot-query staged-buffer cache -------------------------------------------
# A latency-sensitive serving loop re-probes the same hot working set (the
# bench's own "hot-set serving pattern"); re-uploading an identical query
# buffer pays its h2d cost every flush.  Content addressing (blake2b over the raw operand bytes, ~1ms/MB)
# makes the reuse EXACT: any mutation of the caller's arrays changes the
# digest, so this is never identity-cache guesswork.  Entries hold staged
# DEVICE buffers; kernels never donate their query operand, so a cached
# buffer survives any number of dispatches.
import hashlib as _hashlib

_QCACHE: "_OrderedDict[bytes, object]" = _OrderedDict()
_QCACHE_SLOTS = 8
_QCACHE_MAX_BYTES = 8 << 20  # don't pin giant one-off uploads in HBM
_QCACHE_LOCK = _threading.Lock()


def query_digest(*arrays, extra: bytes = b"") -> bytes:
    h = _hashlib.blake2b(digest_size=16)
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(memoryview(a).cast("B"))
    h.update(extra)
    return h.digest()


def query_cache_get(digest: bytes):
    with _QCACHE_LOCK:
        buf = _QCACHE.pop(digest, None)
        if buf is not None:
            _QCACHE[digest] = buf  # LRU refresh
        return buf


def query_cache_put(digest: bytes, buf) -> None:
    nbytes = getattr(buf, "nbytes", _QCACHE_MAX_BYTES + 1)
    if nbytes > _QCACHE_MAX_BYTES:
        return
    with _QCACHE_LOCK:
        _QCACHE[digest] = buf
        while len(_QCACHE) > _QCACHE_SLOTS:
            _QCACHE.popitem(last=False)


def cached_staged(build, *digest_arrays, extra: bytes = b""):
    """THE one expression of the hot-query policy: content-digest the raw
    operands, reuse the staged device buffer on a hit, else build+stage+
    cache.  `build()` runs only on a miss, so hits skip the pack AND the
    h2d upload.  Callers gate this to READ paths — caching one-shot write
    flushes would evict the hot working set for zero hits."""
    digest = query_digest(*digest_arrays, extra=extra)
    buf = query_cache_get(digest)
    if buf is None:
        buf = build()
        query_cache_put(digest, buf)
    return buf


def stage(arr):
    """Asynchronous host->device staging for kernel operands.

    Passing a raw numpy array into a jitted call makes the dispatch BLOCK on
    a synchronous transfer.  An explicit device_put is asynchronous: it
    returns immediately and the upload overlaps with in-flight compute, so
    pipelined flushes actually pipeline."""
    return jax.device_put(arr)


def pack_rows(*arrays, size: int, pool=None):
    """Stack 1-D arrays into one (R, size) uint32 transfer buffer, staged
    to the device asynchronously (see stage()) — ONE contiguous upload per
    flush instead of R small ones, and the dispatch never blocks on it.

    `pool` (core/ioplane.StagingPool) fills a double-buffered reusable host
    slot instead of a fresh allocation: refilling the next flush's buffer
    overlaps this one's in-flight upload (the overlap plane's H2D half).
    Callers pass a pool only where reuse is safe (Engine.staging_pool gates
    on the backend's copy semantics)."""
    shape = (len(arrays), size)
    if pool is None:
        out, slot = np.zeros(shape, np.uint32), None
    else:
        out, slot = pool.acquire(shape, np.uint32)
    try:
        for i, a in enumerate(arrays):
            out[i, : a.shape[0]] = a.view(np.uint32) if a.dtype == np.int32 else a
        staged = stage(out)
    except BaseException:
        if pool is not None:
            pool.release(slot)  # a leaked-busy slot would silently disable
        raise                   # the double-buffer for the pool's lifetime
    return staged if pool is None else pool.commit(slot, staged)


def _unpack_tlh(tlh):
    return tlh[0].astype(jnp.int32), tlh[1], tlh[2]


def _bloom_bank_add_packed(bits2d, tlh, n_valid, k: int, m: int):
    tenant, lo, hi = _unpack_tlh(tlh)
    return _bloom_bank_add_body(bits2d, tenant, lo, hi, n_valid, k, m)


bloom_bank_add_packed = jax.jit(
    _bloom_bank_add_packed, static_argnums=(3, 4), donate_argnums=(0,)
)


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0,))
def bloom_bank_add_packed_count(bits2d, tlh, n_valid, k: int, m: int):
    """Add variant returning only the newly-added COUNT — a 4-byte device
    scalar instead of a B-byte bool plane on the result path."""
    bits, newly = _bloom_bank_add_packed(bits2d, tlh, n_valid, k, m)
    return bits, jnp.sum(newly.astype(jnp.int32))


def _pack_bool_u32(found):
    """Device side: bool[B] -> uint32[B/32] little-bit-order bitmap.  The
    result path of a contains flush is B bool bytes otherwise; results
    travel as bitmaps (8x fewer bytes) and unpack host-side
    (unpack_found)."""
    w = found.reshape(-1, 32).astype(jnp.uint32)
    return (w << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(axis=1, dtype=jnp.uint32)


def unpack_found(packed, n: int) -> np.ndarray:
    """Host side: uint32 bitmap (from _pack_bool_u32) -> bool[n]."""
    b = np.unpackbits(np.ascontiguousarray(packed).view(np.uint8), bitorder="little")
    return b[:n].astype(bool)


def _bloom_bank_contains_impl(bits2d, tlh, n_valid, k: int, m: int):
    tenant, lo, hi = _unpack_tlh(tlh)
    return _bloom_bank_contains_body(bits2d, tenant, lo, hi, n_valid, k, m)


bloom_bank_contains_packed = jax.jit(_bloom_bank_contains_impl, static_argnums=(3, 4))


@functools.partial(jax.jit, static_argnums=(3, 4))
def bloom_bank_contains_packed_bits(bits2d, tlh, n_valid, k: int, m: int):
    return _pack_bool_u32(_bloom_bank_contains_impl(bits2d, tlh, n_valid, k, m))


# --- stacked-run variants (core/coalesce.py) ----------------------------------
# A fused run over several single-filter planes is a small bank.  The planes
# arrive as a TUPLE of equal 1-D planes and are stacked inside the program,
# so one dispatch does what jnp.stack + the bank kernel + one slice a plane
# did, and the program's shape is the tuple's length and the row bucket —
# both fixed by the coalescer (STACK_PLANES, STACK_ROW_BUCKETS), whatever
# the run's composition.  Nothing is donated: a padding plane repeats a real
# one.


@functools.partial(jax.jit, static_argnums=(3, 4))
def bloom_stack_contains_packed(planes, tlh, n_valid, k: int, m: int):
    return _bloom_bank_contains_impl(jnp.stack(planes), tlh, n_valid, k, m)


@functools.partial(jax.jit, static_argnums=(3, 4))
def bloom_stack_add_packed(planes, tlh, n_valid, k: int, m: int):
    """(one new plane an input plane, newly-added flags)."""
    bits2d, newly = _bloom_bank_add_packed(jnp.stack(planes), tlh, n_valid, k, m)
    return tuple(bits2d[i] for i in range(len(planes))), newly


@jax.jit
def window_from_unique(uniq, idx):
    """Compose a flush window on DEVICE from its unique flushes.

    uniq: (U, 3, Bb) packed unique flushes; idx: (R,) int32 mapping window
    position -> unique slot.  Returns (3, R*Bb) laid out exactly like a
    host-packed window (flush i occupies [i*Bb, (i+1)*Bb) of each row).

    Pipelined workloads re-submit the same flush buffers (hot query sets,
    re-validation sweeps); re-uploading R identical 1.4MB operands is pure
    h2d waste, while an HBM-side take of the same bytes is effectively
    free.  The dedupe is by object
    identity in _pack_flush_window — exact, zero hashing cost."""
    w = jnp.take(uniq, idx, axis=0)  # (R, 3, Bb)
    return jnp.swapaxes(w, 0, 1).reshape(3, -1)


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0,))
def bloom_bank_add_packed_bits(bits2d, tlh, n_valid, k: int, m: int):
    """Add variant returning the newly-added flags as a uint32 bitmap — the
    multi-flush (window) result path, where B bool bytes per entry would
    dominate d2h the same way they do for contains."""
    bits, newly = _bloom_bank_add_packed(bits2d, tlh, n_valid, k, m)
    return bits, _pack_bool_u32(newly)


def _bloom_add_packed(bits, lh, n_valid, k: int, m: int):
    return _bloom_add_body(bits, lh[0], lh[1], n_valid, k, m)


bloom_add_packed = jax.jit(_bloom_add_packed, static_argnums=(3, 4), donate_argnums=(0,))


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0,))
def bloom_add_packed_count(bits, lh, n_valid, k: int, m: int):
    new_bits, newly = _bloom_add_packed(bits, lh, n_valid, k, m)
    return new_bits, jnp.sum(newly.astype(jnp.int32))


def _bloom_contains_impl(bits, lh, n_valid, k: int, m: int):
    return _bloom_contains_body(bits, lh[0], lh[1], n_valid, k, m)


bloom_contains_packed = jax.jit(_bloom_contains_impl, static_argnums=(3, 4))


@functools.partial(jax.jit, static_argnums=(3, 4))
def bloom_contains_packed_bits(bits, lh, n_valid, k: int, m: int):
    return _pack_bool_u32(_bloom_contains_impl(bits, lh, n_valid, k, m))


# --- fused multi-verb hot pair ----------------------------------------------
# The bloom serving loop's hottest verb PAIR is add-then-probe on one filter
# (ingest acks + read-your-writes probes in the same pipeline window).  Run
# unfused that is two dispatches and an extra full-plane donation round trip
# through the jit boundary; fused it is ONE program — XLA keeps the bit plane
# resident in HBM between the scatter and the gather, and the probe sees the
# adds (submission order: the add group precedes the contains group, the
# same order the reference preserves inside a CommandsData frame).

def _bloom_fused_add_contains_body(bits, add_lh, n_add, probe_lh, n_probe,
                                   k: int, m: int):
    bits, newly = _bloom_add_body(bits, add_lh[0], add_lh[1], n_add, k, m)
    found = _bloom_contains_body(bits, probe_lh[0], probe_lh[1], n_probe, k, m)
    return bits, newly, found


bloom_fused_add_contains = jax.jit(
    _bloom_fused_add_contains_body, static_argnums=(5, 6), donate_argnums=(0,)
)


@functools.partial(jax.jit, static_argnums=(5, 6), donate_argnums=(0,))
def bloom_fused_add_contains_bits(bits, add_lh, n_add, probe_lh, n_probe,
                                  k: int, m: int):
    """Fused pair with bitmap result paths (the wire/window d2h discipline)."""
    bits, newly, found = _bloom_fused_add_contains_body(
        bits, add_lh, n_add, probe_lh, n_probe, k, m
    )
    return bits, _pack_bool_u32(newly), _pack_bool_u32(found)


# --------------------------------------------------------------------------
# HLL kernels (replaces server-side PFADD/PFMERGE/PFCOUNT,
# RedissonHyperLogLog.java:71-102).
# --------------------------------------------------------------------------

def _hll_add_body(regs, lo, hi, n_valid, p: int):
    h1, h2 = H.hash_u64_pair(lo, hi, jnp)
    idx, rho = hll_ops.idx_rho(h1, h2, p)
    idx = jnp.where(_valid_mask(lo.shape[0], n_valid), idx, regs.shape[-1])
    return hll_ops.add(regs, idx, rho)


def _hll_bank_add_body(regs2d, tenant, lo, hi, n_valid, p: int):
    h1, h2 = H.hash_u64_pair(lo, hi, jnp)
    idx, rho = hll_ops.idx_rho(h1, h2, p)
    m = regs2d.shape[1]
    size = regs2d.shape[0] * m
    mask = _valid_mask(lo.shape[0], n_valid)
    g = jnp.where(mask, tenant * m + idx, size)  # flat fast path (see bloom bank)
    # one-shot whatever the bucket: see _map_valid_chunks
    new_flat = regs2d.reshape(-1).at[g].max(rho, mode="drop")
    return new_flat.reshape(regs2d.shape)


hll_add_u64 = jax.jit(_hll_add_body, static_argnums=(4,), donate_argnums=(0,))
hll_bank_add_u64 = jax.jit(_hll_bank_add_body, static_argnums=(5,), donate_argnums=(0,))


@functools.partial(jax.jit, donate_argnums=(0,))
def hll_bank_merge_map(regs2d, src_map):
    """Batched pairwise PFMERGE as ONE dense gather + elementwise max:
    new[r] = max(old[r], old[src_map[r]]), src_map[r] = r for untouched
    rows.  A row-scatter-max (`.at[dst].max(rows[src])`) lowers to a slow
    serialized scatter on TPU; the dense-map form is a row gather + vmax —
    pure HBM-bandwidth, fused by XLA (~3 passes over the bank regardless of
    pair count).  Callers pre-build the (P,)-map host-side and split
    duplicate-dst pair lists into unique-dst rounds (hll_array.merge_rows),
    the PFMERGE role of RedissonHyperLogLog.java:71-102."""
    return jnp.maximum(regs2d, regs2d[src_map])


@functools.partial(jax.jit, donate_argnums=(0,))
def hll_bank_merge_map_from(regs2d, src_bank, src_map):
    """Round >= 2 of a duplicate-dst merge: sources gather from
    `src_bank` — the PRE-CALL snapshot — never from the partially merged
    `regs2d`, so every round folds in exactly the requested sources (a
    dst updated in round 1 must not leak ITS new sources into a later
    round's dst — scatter-max read-all-sources-from-old semantics)."""
    return jnp.maximum(regs2d, src_bank[src_map])


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def hll_bank_add_packed(regs2d, tlh, n_valid, p: int):
    tenant, lo, hi = _unpack_tlh(tlh)
    return _hll_bank_add_body(regs2d, tenant, lo, hi, n_valid, p)


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def hll_add_packed(regs, lh, n_valid, p: int):
    return _hll_add_body(regs, lh[0], lh[1], n_valid, p)


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0,))
def hll_add_bytes(regs, words, nbytes, n_valid, p: int):
    h1, h2 = H.hash_packed_bytes(words, nbytes, jnp)
    idx, rho = hll_ops.idx_rho(h1, h2, p)
    idx = jnp.where(_valid_mask(h1.shape[0], n_valid), idx, regs.shape[-1])
    return hll_ops.add(regs, idx, rho)


hll_merge = jax.jit(hll_ops.merge, donate_argnums=(0,))
hll_estimate = jax.jit(hll_ops.estimate)
hll_estimate_union = jax.jit(hll_ops.estimate_union)


@jax.jit
def hll_bank_estimate_union_pairs(regs2d, a, b):
    return hll_ops.estimate(jnp.maximum(regs2d[a], regs2d[b]))


# --------------------------------------------------------------------------
# BitSet kernels (RedissonBitSet.java surface).
# --------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def bitset_set(bits, idx, n_valid, value):
    mask = _valid_mask(idx.shape[0], n_valid)
    safe = jnp.where(mask, idx, bits.shape[0])
    old = bits.at[safe].get(mode="fill", fill_value=0)
    return bits.at[safe].set(value.astype(jnp.uint8), mode="drop"), old & mask.astype(jnp.uint8)


@jax.jit
def bitset_get(bits, idx):
    return bt.get_bits(bits, idx)


bitset_popcount = jax.jit(bt.popcount, static_argnums=(1,))
bitset_and = jax.jit(bt.bit_and, donate_argnums=(0,))
bitset_or = jax.jit(bt.bit_or, donate_argnums=(0,))
bitset_xor = jax.jit(bt.bit_xor, donate_argnums=(0,))
bitset_not = jax.jit(bt.bit_not, static_argnums=(1,), donate_argnums=(0,))
bitset_bitpos = jax.jit(bt.bitpos, static_argnums=(1, 2))
bitset_length = jax.jit(bt.length_hint)


# --- stacked-wave variants (core/coalesce.py) ---------------------------------
# One program for a wave of same-verb bitset commands on DIFFERENT planes of
# one shape.  The planes arrive as a tuple of the coalescer's fixed length and
# each is worked on in place: one scatter (or one elementwise pass) a plane
# inside the program, the written planes DONATED.  Chosen on the v5e against a
# flat bank (jnp.stack, one scatter at slot * stride + index, one slice a
# plane) and against the same body without donation, 16 planes of 2^20 cells,
# time to return: set 0.79-0.96 ms donated, 1.6-1.7 not, 1.6-1.7 flat; or/xor
# 0.31 ms donated, 1.1-1.2 not, 1.1-1.2 flat (PERF.md section 6, PR 27).  A
# donated tuple must not name one buffer twice, so the coalescer pads a
# writing wave with resident stand-ins, never with a repeated plane.

# pads a wave's index window: beyond every plane (BitSet.MAX_BIT is below it),
# so the scatter drops it and the gather reads 0 for it
NO_INDEX = np.int32(2**31 - 1)


@functools.partial(jax.jit, donate_argnums=(0,))
def bitset_stack_set(planes, window):
    """SETBITSB over a tuple of planes: row i of the (P, R) int32 `window`
    holds plane i's indexes, padded with NO_INDEX.  Returns (one new plane a
    plane, the previous bits as one (P, R) uint8 value).  The ones are a
    constant of the program."""
    old = jnp.stack([bt.get_bits(p, window[i]) for i, p in enumerate(planes)])
    return tuple(bt.set_bits(p, window[i], 1) for i, p in enumerate(planes)), old


_BIT_OPS = {"OR": bt.bit_or, "XOR": bt.bit_xor}


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def bitset_stack_op(dests, srcs, op: str):
    """BITOP OR / XOR over a tuple of (dest, src) plane pairs: (one new dest
    plane a pair, their length hints as one (P,) int32 value) — the reply's
    length comes out of the program that made the plane."""
    new = tuple(_BIT_OPS[op](d, s) for d, s in zip(dests, srcs))
    return new, jnp.stack([bt.length_hint(z) for z in new])


@jax.jit
def bitset_stack_popcount(planes):
    """BITCOUNT over a tuple of whole planes: one (P,) int32 value."""
    return jnp.stack([bt.popcount(p, p.shape[0]) for p in planes])


# --------------------------------------------------------------------------
# Text word-count kernels (MapReduce device path, SURVEY.md §3.5 / §7.3-6).
#
# The reference word-count iterates entries in a mapper and writes one
# multimap entry per emit (mapreduce/Collector.java:56-73, MapperTask.java:
# 50-78).  The TPU path hashes, shuffles and reduces the WHOLE text in
# compiled programs (services/mapreduce.py drives them):
#   1. wc_extract_words, once a map task (a chunk of entries): running
#      sums of the per-byte hash terms, word ends compacted by a sort of the
#      byte positions, each end's (sum_a, sum_b, last whitespace) read by
#      ONE gather of a (3, N) stack — a word's hash is its end's sum less
#      the previous word's —, and the task's word count.
#   2. wc_count_runs, once a job over every task's words: lexicographic
#      sort of the 64-bit word hashes (TPU sorts are fast) + run-boundary
#      compaction via a second sort — counts come out as diffs of run-start
#      positions, NO scatters.  Every distinct word has a row, whatever the
#      vocabulary: there is no distinct-word cap.
#   3. wc_rows_block: a fixed-size block of those rows, so the fetch is
#      proportional to the distinct words and one program serves any count.
#
# Measured design history (2026-07, v5e behind a remote transport, 1M docs /
# 8M words; premise gone — re-measure):
#   * Python threads (r2): 6.6s — GIL-serialized, "64 mappers" was fiction.
#   * Host C single-pass (str.split + Counter): 1.5-2.6s — the 1-core bound.
#   * Per-byte scatter kernel (6 table scatters over 42M bytes): 5.4s —
#     TPU scatter costs ~21ms per 1M updates; scatters CANNOT carry this.
#   * Dual-table count sketch (IBLT peeling, 4 scatters over 10.8M words):
#     ~1.9s — better, still scatter-bound.
#   * This sort-based pipeline: sorts + scans + one gather only.  One
#     task of the 64-task, 76.7 MB job (1,310,720 bytes, 196,608 rows;
#     tools/wc_extract_probe.py, v5e, 2026-10): 13.7 ms when the position
#     weights were a table lookup (a gather of every byte, 2.3 ms), the
#     sums two jnp.cumsum (1.4 ms) and a word was read by five gathers at
#     its end and its last whitespace (~1.9 ms each); 6.8 ms with sums
#     restarted at whitespace by a 21-step shifted scan over the 1-D text;
#     4.9 ms with the sums carried through a stable sort as payload (4.2
#     unstable); 3.1 ms now — weights multiplied up from cap's bits, sums
#     scanned in 128-lane rows (0.9 ms for both, timed alone), an unstable
#     sort of the end positions alone and ONE stacked gather.
# Hash identity: words are keyed by a 64-bit (2x u32) polynomial hash of
# byte+1 values with position weights p^min(pos,63) plus a length term —
# words longer than 63 bytes that share a 63-byte prefix, length, AND the
# sum of remaining bytes collide (documented bound; astronomically unlikely
# for natural tokens).
# --------------------------------------------------------------------------

_WC_POW = 64
_WC_SENTINEL = 0xFFFFFFFF  # hash and start of a padding row: sorts last


def _wc_pow_table(p: int) -> np.ndarray:
    out = np.zeros(_WC_POW, np.uint32)
    v = 1
    for i in range(_WC_POW):
        out[i] = v
        v = (v * p) & 0xFFFFFFFF
    return out


_WC_POW_A = _wc_pow_table(0x01000193)  # FNV-32 prime
_WC_POW_B = _wc_pow_table(40503)


def _wc_pow(cap, table):
    """table[cap] for a (N,) cap in [0, 64): p^cap mod 2^32, multiplied up
    from the table's p^(2^k) by cap's bits.  Elementwise: a lookup of the
    64-entry table lowers to a gather of every byte."""
    out = jnp.ones(cap.shape, jnp.uint32)
    for k in range(6):
        out = jnp.where((cap >> k) & 1 == 1, out * jnp.uint32(int(table[1 << k])), out)
    return out


_WC_LANES = 128


def _wc_cumsum(x):
    """Inclusive running sum of a (N,) uint32, mod 2^32: rows of 128 lanes
    summed by a log-step scan of shifted adds, then each row offset by the
    rows before it (a running sum of N / 128 totals)."""
    n = x.shape[0]
    pad = -n % _WC_LANES
    r = jnp.pad(x, (0, pad)).reshape(-1, _WC_LANES)
    d = 1
    while d < _WC_LANES:
        r = r + jnp.pad(r, ((0, 0), (d, 0)))[:, :_WC_LANES]
        d *= 2
    tot = r[:, -1]
    return (r + (jnp.cumsum(tot) - tot)[:, None]).reshape(-1)[:n]


def _wc_hash_prelude(buf):
    """Per byte: whether it is whitespace, the last whitespace at or before
    it, and the running sums of the hash terms (0 at whitespace) — mod
    2^32, so a word's hash is the sum at its last byte less the sum at the
    last byte of the word before it."""
    n = buf.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    ws = buf == 32
    last_ws = jax.lax.cummax(jnp.where(ws, idx, jnp.int32(-1)))
    cap = jnp.minimum(idx - last_ws - 1, _WC_POW - 1)
    b1 = jnp.where(ws, jnp.uint32(0), buf.astype(jnp.uint32) + 1)
    return (ws, idx, last_ws, _wc_cumsum(b1 * _wc_pow(cap, _WC_POW_A)),
            _wc_cumsum(b1 * _wc_pow(cap, _WC_POW_B)))


def _wc_after(x):
    """x[k] - x[k - 1] along the words, x[-1] taken as 0."""
    return x - jnp.concatenate([jnp.zeros((1,), x.dtype), x[:-1]])


@functools.partial(jax.jit, static_argnums=(1,))
def wc_extract_words(buf, eb: int, base):
    """One map task's words.  buf: (N,) uint8 text, whitespace normalized
    to 0x20 and ws-padded (the last word's end is visible).  eb: the static
    word rows of the output.  base: int32 scalar, the chunk's offset in the
    job's text.  Returns ((3, eb) uint32 rows of (hash_a, hash_b, global
    start) — padding rows all 0xFFFFFFFF, so they sort after every real
    word —, the chunk's word count as int32).  A count above eb means the
    rows were cut: the caller extracts again at a larger eb."""
    with jax.named_scope("wc_extract_words"):
        ws, idx, last_ws, cum_a, cum_b = _wc_hash_prelude(buf)
        end_mask = (~ws) & jnp.concatenate([ws[1:], jnp.ones((1,), bool)])
        n_words = jnp.sum(end_mask, dtype=jnp.int32)
        # word ends in order (ties are the non-ends, cut or masked: no
        # stable sort), then each end's running sums and last whitespace
        # in ONE gather; in the words' order, the sum at the whitespace
        # before a word is the sum at the previous word's end
        key = jnp.where(end_mask, idx, jnp.int32(0x7FFFFFFF))
        e = jnp.minimum(jax.lax.sort(key, is_stable=False)[:eb], buf.shape[0] - 1)
        ca, cb, lw = jnp.take(jnp.stack([cum_a, cum_b, last_ws.astype(jnp.uint32)]), e, axis=1)
        lw = lw.astype(jnp.int32)
        ln = (e - lw).astype(jnp.uint32)
        ha = _wc_after(ca) ^ (ln * jnp.uint32(2654435761))
        hb = _wc_after(cb) + (ln * jnp.uint32(0x9E3779B9))
        rows = jnp.stack([ha, hb, (lw + 1).astype(jnp.uint32) + base.astype(jnp.uint32)])
        valid = jnp.arange(eb, dtype=jnp.int32) < n_words
        return jnp.where(valid, rows, jnp.uint32(_WC_SENTINEL)), n_words


@jax.jit
def wc_count_runs(parts, counts):
    """Count words by sorting, over every map task's rows at once.
    parts: a tuple of (3, eb) wc_extract_words rows; counts: their word
    counts.  (hash_a, hash_b) sort lexicographically, so equal words become
    adjacent runs; a second sort compacts each run's first position to the
    front.  Returns ((2, R) int32 rows of (run start, the run's global text
    offset as uint32 bits) — the first `runs` of them real, padding's own
    run last —, int32[3] (runs, words, most words of one task))."""
    with jax.named_scope("wc_count_runs"):
        rows = jnp.concatenate(parts, axis=1)
        sh_a, sh_b, sh_off = jax.lax.sort((rows[0], rows[1], rows[2]), num_keys=2)
        prev_a = jnp.concatenate([jnp.full((1,), ~sh_a[0], sh_a.dtype), sh_a[:-1]])
        prev_b = jnp.concatenate([jnp.zeros((1,), sh_b.dtype), sh_b[:-1]])
        first = (sh_a != prev_a) | (sh_b != prev_b)
        idx = jnp.arange(rows.shape[1], dtype=jnp.int32)
        fp = jnp.where(first, idx, jnp.int32(0x7FFFFFFF))
        c_fp, c_off = jax.lax.sort((fp, sh_off), num_keys=1)
        words = jnp.stack(counts)
        stats = jnp.stack([jnp.sum(first, dtype=jnp.int32), jnp.sum(words),
                           jnp.max(words)])
        return jnp.stack([c_fp, jax.lax.bitcast_convert_type(c_off, jnp.int32)]), stats


@functools.partial(jax.jit, static_argnums=(2,))
def wc_rows_block(rows, at, size: int):
    """Columns [at, at + size) of wc_count_runs' rows (a start past the end
    is clamped to rows.shape[1] - size, as dynamic_slice does)."""
    with jax.named_scope("wc_rows_block"):
        return jax.lax.dynamic_slice(rows, (0, at), (2, size))


# --------------------------------------------------------------------------
# Vector-search kernels (FT VECTOR / KNN, ISSUE 11).
#
# FLAT (exact) KNN is a score matrix + a top-k: queries (Q, d) against a bank
# (C, d) is a (Q, d) x (d, C) matmul — the MXU's native shape — and
# jax.lax.top_k over the masked score rows.  The matrix is never whole: the
# bank is walked in row blocks, each block cut to its k best, the candidates
# merged (knn_flat_topk).  Same no-Pallas rationale as the probe kernels
# above: XLA already lowers dot_general to the systolic array and top_k to
# the tuned sort unit; a hand kernel could only re-derive them.
#
# Distance conventions (lower = better, the RediSearch FLAT shapes):
#   L2     — squared euclidean ||q - b||^2 (expanded form so the matmul
#            carries the whole cross term)
#   COSINE — 1 - cos(q, b)  (zero-norm rows score distance 1: orthogonal)
#   IP     — 1 - <q, b>
# Rows at index >= n_rows (padding / unfilled capacity) and rows whose
# `bias` is +inf (deleted docs, prefilter exclusions) never reach the top-k:
# bias adds into the distance row before selection, so a hybrid query's
# host-built mask is just an additive bias operand — no second kernel.
# Ties break toward the LOWER row index (lax.top_k is stable), which the
# NumPy fallback (services/vector.py) mirrors with a stable argsort: the
# armed and disarmed paths return identical orderings.
# --------------------------------------------------------------------------


# Scoring matmuls run at full float32 precision.  The TPU's DEFAULT matmul
# precision rounds f32 operands to bfloat16 (one MXU pass): measured on a
# v5e, FLAT recall@10 over the 50k x 128 clustered bank fell to 0.958
# against the float64 oracle — "FLAT is exact" (and the >= 0.99 recall
# floor) only holds with the multi-pass product.  CPU ignores the setting.
_EXACT = jax.lax.Precision.HIGHEST


def _bank_f32(bank, scale):
    """Decompress-in-kernel seam (ISSUE 14): quantized banks (FLOAT16, or
    INT8 + symmetric per-row scale) widen to float32 INSIDE the scoring
    program, so the MXU still sees one fused matmul and the decompressed
    plane never round-trips HBM as a separate buffer.  The trace
    specializes on the bank dtype — float32 banks pay nothing."""
    if bank.dtype == jnp.float32:
        return bank
    rows = bank.astype(jnp.float32)
    if scale is not None:
        rows = rows * scale[..., None]
    return rows


# Rows one step of the FLAT scan scores.  The (Q, capacity) distance matrix
# of a 1M-row bank is 256 MB a 64-query batch; a step holds (Q, KNN_BLOCK)
# (16 MB at Q = 64) and keeps k of it.  Chosen on the v5e over 1,048,576 x
# 128 f32 at Q = 64, k = 10 (my chip run, PR 32; PERF.md section 6): ms a
# call by block 1,024 / 2,048 / 4,096 / 8,192 / 16,384 / 32,768 / 65,536 =
# 40.3 / 21.9 / 12.7 / 7.7 / 5.4 / 4.2 / 3.5 — a step's fixed cost (the
# per-block top_k's) is what a larger block saves; the whole matrix in one
# top_k is 4.6.  Larger blocks were not probed.
KNN_BLOCK = 65536


def _block_topk(dist, k: int):
    """The k smallest of each row of one block, ascending, ties toward the
    lower column (lax.top_k is stable): (values, columns)."""
    neg, pos = jax.lax.top_k(-dist, k)
    return -neg, pos


def _knn_block_dists(tile, norms, q, q_norm, metric: str):
    """Distances of one (B, W) tile of the bank against the queries: the
    cross term on the MXU, the rows' squared norms from the plane kept
    beside the bank (rowbank_write_norms) — never summed again here."""
    dots = jnp.dot(q, tile.T, preferred_element_type=jnp.float32,
                   precision=_EXACT)  # (Q, B)
    if metric == "L2":
        return q_norm[:, None] - 2.0 * dots + norms[None, :]
    if metric == "COSINE":
        denom = q_norm[:, None] * jnp.sqrt(norms)[None, :]
        return 1.0 - jnp.where(denom > 0.0, dots / denom, 0.0)
    if metric == "IP":
        return 1.0 - dots
    raise ValueError(f"unknown metric {metric!r}")  # pragma: no cover


@functools.partial(jax.jit, static_argnums=(7, 8))
def knn_flat_topk(bank, scale, bias, norms, qmask, q, n_rows, k: int,
                  metric: str):
    """Exact FLAT top-k, blocked: the bank is walked in KNN_BLOCK-row tiles,
    each tile scored on the MXU and cut to its own k best, and the
    (Q, blocks * k) candidates merged by one more top_k.  The ids are those
    of a top_k over the whole (Q, capacity) matrix, ties included: a global
    winner is among its own block's k best, blocks are concatenated in row
    order and top_k is stable, so of equal distances the lower rowid wins
    both times.

    scale: the INT8 dequant column or None; qmask: a hybrid query's (C,)
    additive 0 / +inf plane or None; norms: (C,) squared row norms.  A
    capacity that is no multiple of the block ends on a tile that starts
    early and masks the rows the tile before it covered."""
    with jax.named_scope("knn_flat_topk"):
        cap, width = bank.shape
        block = min(KNN_BLOCK, cap)
        blocks = -(-cap // block)
        kb = min(k, block)
        rowid = jnp.arange(cap, dtype=jnp.int32)
        gate = jnp.where(rowid < n_rows, bias, jnp.inf)
        if qmask is not None:
            gate = gate + qmask
        q_norm = jnp.sum(q * q, axis=1, dtype=jnp.float32)
        if metric == "COSINE":
            q_norm = jnp.sqrt(q_norm)

        def score(start, first):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block)  # noqa: E731
            tile = _bank_f32(sl(bank), None if scale is None else sl(scale))
            dist = _knn_block_dists(tile, sl(norms), q, q_norm, metric)
            dist = dist + sl(gate)[None, :]
            local = start + jnp.arange(block, dtype=jnp.int32)
            dist = jnp.where((local >= first)[None, :], dist, jnp.inf)
            top, pos = _block_topk(dist, kb)
            return top, (pos + start).astype(jnp.int32)

        if blocks == 1:
            return score(jnp.int32(0), jnp.int32(0))

        def step(_, i):
            first = i * block
            return None, score(jnp.minimum(first, cap - block), first)

        _, (tops, ids) = jax.lax.scan(
            step, None, jnp.arange(blocks, dtype=jnp.int32)
        )
        nq = q.shape[0]
        tops = jnp.moveaxis(tops, 0, 1).reshape(nq, blocks * kb)
        ids = jnp.moveaxis(ids, 0, 1).reshape(nq, blocks * kb)
        top, pos = _block_topk(tops, min(k, cap))
        return top, jnp.take_along_axis(ids, pos, axis=1)


# -- IVF (inverted-file) KNN: sub-linear scoring (ISSUE 14) -------------------
#
# A coarse k-means quantizer routes each query through ONE small
# (Q, d) x (d, nlist) matmul; only the rows of the top-`nprobe` cells are
# then gathered and scored, so candidate work is O(nprobe * cell_cap) per
# query instead of O(N).  The per-cell row lists arrive as a CSR-style
# device index with a UNIFORM stride (`cells`: (nlist, cell_cap) int32,
# ragged rows padded with an out-of-range sentinel) — uniform stride keeps
# the candidate gather ONE fixed-shape XLA gather; the recall gate
# (config7 floors) keeps the approximation honest.  Ties break toward the
# earlier candidate position (probe order, then cell position), which the
# NumPy fallback in services/vector.py mirrors with a stable argsort.

# (padded `cells` entries carry services/vector._IVF_SENTINEL — any value
# >= n_rows works here, validity is the `cand < n_rows` mask below)


def _ivf_candidate_dists(rows_f32, q, metric: str):
    """Distances of gathered candidate rows (Q, M, W) against their own
    query (Q, W) — the _knn_distances conventions, batched per query."""
    dots = jnp.einsum(
        "qmw,qw->qm", rows_f32, q, preferred_element_type=jnp.float32,
        precision=_EXACT,
    )
    if metric == "L2":
        q_sq = jnp.sum(q * q, axis=1, dtype=jnp.float32)
        r_sq = jnp.sum(rows_f32 * rows_f32, axis=2, dtype=jnp.float32)
        return q_sq[:, None] - 2.0 * dots + r_sq
    if metric == "COSINE":
        qn = jnp.sqrt(jnp.sum(q * q, axis=1, dtype=jnp.float32))
        rn = jnp.sqrt(jnp.sum(rows_f32 * rows_f32, axis=2, dtype=jnp.float32))
        denom = qn[:, None] * rn
        return 1.0 - jnp.where(denom > 0.0, dots / denom, 0.0)
    if metric == "IP":
        return 1.0 - dots
    raise ValueError(f"unknown metric {metric!r}")  # pragma: no cover


def _ivf_route(centroids, q, nprobe: int, metric: str):
    """Top-`nprobe` coarse cells per query: ONE (Q, d) x (d, nlist) matmul
    + top_k — the sub-linear plane's whole routing cost."""
    cdots = jnp.dot(q, centroids.T, preferred_element_type=jnp.float32,
                    precision=_EXACT)
    if metric == "L2":
        cd = (
            jnp.sum(q * q, axis=1, dtype=jnp.float32)[:, None]
            - 2.0 * cdots
            + jnp.sum(centroids * centroids, axis=1,
                      dtype=jnp.float32)[None, :]
        )
    elif metric == "COSINE":
        qn = jnp.sqrt(jnp.sum(q * q, axis=1, dtype=jnp.float32))
        cn = jnp.sqrt(jnp.sum(centroids * centroids, axis=1,
                              dtype=jnp.float32))
        denom = qn[:, None] * cn[None, :]
        cd = 1.0 - jnp.where(denom > 0.0, cdots / denom, 0.0)
    else:  # IP
        cd = 1.0 - cdots
    _neg, probe = jax.lax.top_k(-cd, nprobe)
    return probe  # (Q, nprobe) cell ids


def _knn_ivf_body(bank, scale, bias, qmask, centroids, cells, q, n_rows,
                  k: int, nprobe: int, metric: str):
    probe = _ivf_route(centroids, q, nprobe, metric)
    cand = cells[probe].reshape(q.shape[0], -1)   # (Q, nprobe*cap) rowids
    valid = cand < n_rows                         # sentinel + padding out
    safe = jnp.where(valid, cand, 0)
    rows = _bank_f32(bank[safe], None if scale is None else scale[safe])
    dist = _ivf_candidate_dists(rows, q, metric) + bias[safe]
    if qmask is not None:  # hybrid prefilter: (C,) additive 0/+inf plane
        dist = dist + qmask[safe]
    dist = jnp.where(valid, dist, jnp.inf)
    neg, pos = jax.lax.top_k(-dist, k)
    idx = jnp.take_along_axis(cand, pos, axis=1)  # +inf rows carry garbage
    return -neg, idx.astype(jnp.int32)            # ids; callers drop them


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def knn_ivf_topk(bank, bias, centroids, cells, q, n_rows, k: int,
                 nprobe: int, metric: str):
    return _knn_ivf_body(bank, None, bias, None, centroids, cells, q,
                         n_rows, k, nprobe, metric)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def knn_ivf_topk_q(bank, scale, bias, centroids, cells, q, n_rows, k: int,
                   nprobe: int, metric: str):
    return _knn_ivf_body(bank, scale, bias, None, centroids, cells, q,
                         n_rows, k, nprobe, metric)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def knn_ivf_topk_masked(bank, bias, qmask, centroids, cells, q, n_rows,
                        k: int, nprobe: int, metric: str):
    return _knn_ivf_body(bank, None, bias, qmask, centroids, cells, q,
                         n_rows, k, nprobe, metric)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def knn_ivf_topk_masked_q(bank, scale, bias, qmask, centroids, cells, q,
                          n_rows, k: int, nprobe: int, metric: str):
    return _knn_ivf_body(bank, scale, bias, qmask, centroids, cells, q,
                         n_rows, k, nprobe, metric)


# -- mesh-sharded KNN merge (ISSUE 15) ----------------------------------------
#
# Row-parallel banks (services/vector.ShardedEmbeddingBank) reuse the whole
# knn_topk / knn_ivf_topk family above AS the per-shard variants — each shard
# is a full bank on its own device, so the per-shard leg is literally the
# single-device program.  What sharding adds is the REDUCE: every shard's
# (Q, k_s) local top-k d2d-colocates onto one device and this kernel picks
# the global top-k as concat + lax.top_k — the FAISS shard-then-merge shape
# on the repo's psum/merge discipline (never a host gather).  Ties break
# toward the earlier concatenated position: lower shard id first, then the
# shard's own tie order — which the NumPy fallback mirrors with a stable
# argsort over the identical concat layout.


def knn_sharded_merge(dists, idxs, shard_of_pos, k: int):
    """dists/idxs: tuples of per-shard (Q, k_s) top-k outputs (all on ONE
    device by the time this runs); shard_of_pos: (sum k_s,) int32 mapping a
    concat position to its shard id (static per constellation, staged
    once).  Returns (dist (Q, k), shard (Q, k), local_idx (Q, k)) — the
    host decodes (shard, local) back to global rowids off the readback
    path (resolve_hits), so no global-id plane ever ships to the device.

    Deliberately NOT jitted here: the serving jit instances are minted per
    mesh geometry by MeshManager.knn_merge_kernel, whose cross-epoch warm
    pool is what makes a 4->8->4 reshard land back on the already-built
    program — a module-level jit would be a second, unpooled compile path."""
    dist_cat = jnp.concatenate(list(dists), axis=1)
    idx_cat = jnp.concatenate(list(idxs), axis=1)
    neg, pos = jax.lax.top_k(-dist_cat, k)
    sid = shard_of_pos[pos]
    lidx = jnp.take_along_axis(idx_cat, pos, axis=1)
    return -neg, sid.astype(jnp.int32), lidx.astype(jnp.int32)


@jax.jit
def kmeans_step(points, weights, centroids):
    """One Lloyd iteration over the host mirror staged once per training
    run: L2 assignment (the classic IVF coarse quantizer, whatever the
    field's query metric) + weighted mean update.  `weights` zeroes dead
    rows out of both the assignment result (-1) and the centroid update;
    empty cells keep their previous centroid.  Returns
    (new_centroids f32 (L, W), assign int32 (N,))."""
    d = (
        jnp.sum(points * points, axis=1, dtype=jnp.float32)[:, None]
        - 2.0 * jnp.dot(points, centroids.T,
                        preferred_element_type=jnp.float32, precision=_EXACT)
        + jnp.sum(centroids * centroids, axis=1, dtype=jnp.float32)[None, :]
    )
    assign = jnp.argmin(d, axis=1).astype(jnp.int32)
    sums = jnp.zeros_like(centroids).at[assign].add(
        points * weights[:, None]
    )
    counts = jnp.zeros((centroids.shape[0],), jnp.float32).at[assign].add(
        weights
    )
    new_c = jnp.where(
        counts[:, None] > 0.0,
        sums / jnp.maximum(counts, 1.0)[:, None],
        centroids,
    )
    return new_c, jnp.where(weights > 0.0, assign, jnp.int32(-1))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def rowbank_write_packed(bank, bias, packed, n_valid):
    """Block-append/overwrite rows of a (C, W) f32 device bank from ONE
    packed uint32 transfer buffer — the embedding/numeric ingest path's
    single H2D per flush (ISSUE 11; the pack_rows bandwidth discipline).

    packed: (P, W+2) uint32 — col 0 = row index, col 1 = the row's new bias
    bits (f32: 0.0 live, +inf dead), cols 2.. = the row data bitcast to
    uint32.  Rows past n_valid scatter out of range (dropped)."""
    idx = packed[:, 0].astype(jnp.int32)
    newbias = jax.lax.bitcast_convert_type(packed[:, 1], jnp.float32)
    rows = jax.lax.bitcast_convert_type(packed[:, 2:], jnp.float32)
    mask = _valid_mask(packed.shape[0], n_valid)
    safe = jnp.where(mask, idx, bank.shape[0])
    return (
        bank.at[safe].set(rows, mode="drop"),
        bias.at[safe].set(newbias, mode="drop"),
    )


@functools.partial(jax.jit, donate_argnums=(0, 1))
def rowbank_write_packed_f16(bank, bias, packed, n_valid):
    """rowbank_write_packed for FLOAT16 banks: cols 2.. carry TWO f16 lanes
    per uint32 word (numpy ``.view(uint32)`` packing; XLA's bitcast orders
    the trailing lane dim from the least-significant bits, which matches) —
    the compressed upload is HALF the f32 transfer for the same rows."""
    idx = packed[:, 0].astype(jnp.int32)
    newbias = jax.lax.bitcast_convert_type(packed[:, 1], jnp.float32)
    halves = jax.lax.bitcast_convert_type(packed[:, 2:], jnp.float16)
    rows = halves.reshape(packed.shape[0], -1)
    mask = _valid_mask(packed.shape[0], n_valid)
    safe = jnp.where(mask, idx, bank.shape[0])
    return (
        bank.at[safe].set(rows, mode="drop"),
        bias.at[safe].set(newbias, mode="drop"),
    )


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def rowbank_write_packed_i8(bank, scale, bias, packed, n_valid):
    """rowbank_write_packed for INT8 banks: col 2 = the row's symmetric
    dequant scale (f32 bits), cols 3.. = FOUR int8 lanes per uint32 word —
    a quarter of the f32 transfer; the scoring kernels dequantize in-
    program (``_bank_f32``)."""
    idx = packed[:, 0].astype(jnp.int32)
    newbias = jax.lax.bitcast_convert_type(packed[:, 1], jnp.float32)
    newscale = jax.lax.bitcast_convert_type(packed[:, 2], jnp.float32)
    quads = jax.lax.bitcast_convert_type(packed[:, 3:], jnp.int8)
    rows = quads.reshape(packed.shape[0], -1)
    mask = _valid_mask(packed.shape[0], n_valid)
    safe = jnp.where(mask, idx, bank.shape[0])
    return (
        bank.at[safe].set(rows, mode="drop"),
        scale.at[safe].set(newscale, mode="drop"),
        bias.at[safe].set(newbias, mode="drop"),
    )


@functools.partial(jax.jit, donate_argnums=(2, 3))
def rowbank_grow(bank, bias, grown_bank, grown_bias):
    """Device-side capacity growth: copy the old bank into the zero-filled
    larger plane (HBM copy — growth never re-uploads host rows).  The grown
    planes are donated: XLA writes the copy into their buffers in place.
    dtype-agnostic: the jit re-specializes for f16/int8 banks."""
    c = bank.shape[0]
    return (
        grown_bank.at[:c].set(bank),
        grown_bias.at[:c].set(bias),
    )


@functools.partial(jax.jit, donate_argnums=(1,))
def rowbank_grow_plane(plane, grown):
    """Grow ONE auxiliary per-row plane (the INT8 scale column) the same
    HBM-copy way."""
    return grown.at[: plane.shape[0]].set(plane)


@functools.partial(jax.jit, donate_argnums=(0,))
def rowbank_write_norms(norms, bank, scale, packed, n_valid):
    """The squared norms of the rows one packed write just installed, into
    the (C,) plane kept beside the bank: read back from the bank as stored
    (dequantized, so a quantized bank's norms are those of the values it
    scores), whatever the bank's dtype.  A killed row is zeros: norm 0."""
    idx = packed[:, 0].astype(jnp.int32)
    mask = _valid_mask(packed.shape[0], n_valid)
    safe = jnp.where(mask, idx, bank.shape[0])
    rows = _bank_f32(
        bank.at[safe].get(mode="fill", fill_value=0),
        None if scale is None else scale.at[safe].get(mode="fill", fill_value=1),
    )
    return norms.at[safe].set(
        jnp.sum(rows * rows, axis=1, dtype=jnp.float32), mode="drop"
    )


@jax.jit
def rowbank_norms(bank, scale):
    """The whole norms plane of a bank that came without one (a record
    restored or shipped from before the plane existed): one pass, once."""
    rows = _bank_f32(bank, scale)
    return jnp.sum(rows * rows, axis=1, dtype=jnp.float32)

"""MapReduce service.

Parity target (SURVEY.md §2.6, §3.5): ``org/redisson/mapreduce/`` —
`RMap.mapReduce()` / `RCollection.mapReduce()` submit a CoordinatorTask to
the `redisson_mapreduce` executor; MapperTask iterates the source, emitting
via Collector into per-partition multimaps keyed by `hash64(key) % workers`
(``Collector.java:56-73``, ``MapperTask.java:50-78``); one ReducerTask per
partition folds value lists; optional CollatorTask folds the result map
(``CoordinatorTask.java:77-166``).

TPU-first redesign (BASELINE north star): the reference's per-emit Redis
write is the hot loop; here
  * the host path batches emissions into in-memory partition buffers (one
    lock touch per mapper chunk, not per emit),
  * the DISTRIBUTED path ships mapper chunks and reducer partitions as
    executor tasks claimable by WorkerNode OS processes (the GIL makes
    in-process "mapper threads" fiction — the reference's worker-JVM model,
    ``executor/TasksRunnerService.java:192-318``, is the right shape), and
  * the kernel path (`KernelMapReduce`, `word_count` device pipeline)
    compiles map+shuffle+reduce into jitted programs over packed arrays
    (SURVEY.md §7.3 item 6's "vmap-able kernel API with a host-executor
    fallback").
"""
from __future__ import annotations

import pickle
import re
import threading
import time
import uuid
from collections import Counter, defaultdict
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from redisson_tpu.services.executor import inject_client
from redisson_tpu.utils import hashing as H

import numpy as np


class Collector:
    """Per-mapper emission buffer (Collector.java analog, minus the per-emit
    network write)."""

    def __init__(self, n_partitions: int):
        self._parts: List[Dict[Any, List[Any]]] = [defaultdict(list) for _ in range(n_partitions)]
        self._n = n_partitions

    def emit(self, key, value) -> None:
        kb = key.encode() if isinstance(key, str) else repr(key).encode()
        words, nbytes = H.pack_keys([kb])
        h1, _ = H.hash_packed_bytes(words, nbytes, np)
        self._parts[int(h1[0]) % self._n][key].append(value)


def _part_name(job: str, chunk_idx: int, run: str, pi: int) -> str:
    return f"mr:{job}:c{chunk_idx}:r{run}:p{pi}"


def _mr_map_task(map_name, keys, mapper, n_parts, job, chunk_idx, codec, *, client):
    """Mapper chunk task (MapperTask.java:50-78 analog): read the chunk in
    ONE batched call, run the user mapper into an in-memory Collector, flush
    each partition buffer with ONE bulk multimap merge (vs the reference's
    per-emit write).

    Partition names are RUN-scoped (fresh uuid per execution): a requeued
    clone writes to its own names, so a stale slow worker can neither
    append duplicates to nor delete/clobber the winning run's output — the
    coordinator tells reducers exactly which run won (the acked one).
    Loser runs' partitions are unreferenced garbage reaped by the cleanup
    task.  `codec` is the source map's codec: the worker must encode lookup
    keys exactly as the writer did, or get_all matches nothing."""
    from redisson_tpu.client.codec import PickleCodec

    run = uuid.uuid4().hex[:8]
    source = client.get_map(map_name, codec=codec)
    entries = source.get_all(keys)
    c = Collector(n_parts)
    for k, v in entries.items():
        mapper(k, v, c)
    for pi, pmap in enumerate(c._parts):
        if pmap:
            mm = client.get_list_multimap(
                _part_name(job, chunk_idx, run, pi), codec=PickleCodec()
            )
            mm.put_all_entries(dict(pmap))
    return {"entries": len(entries), "run": run}


def _mr_reduce_task(job, pi, chunk_runs, reducer, result_name, result_codec, *, client):
    """Reducer partition task (ReducerTask.java analog): fold each key's
    value list across every WINNING mapper run's partition output
    (`chunk_runs` = [(chunk_idx, run), ...] from the acked map results),
    optionally write into the named result map, return the reduced dict so
    the coordinator can merge without re-reading.

    IDEMPOTENT: reads only — a requeued re-run (worker died mid-fold) sees
    every chunk again and the result-map write is a full overwrite of this
    partition's keys.  Partition cleanup belongs to the COORDINATOR
    (_mr_cleanup_task in its finally), never to the reducer: deleting as we
    read would make a re-run silently undercount the already-consumed
    chunks."""
    from redisson_tpu.client.codec import PickleCodec

    grouped: Dict[Any, List[Any]] = defaultdict(list)
    for ci, run in chunk_runs:
        mm = client.get_list_multimap(_part_name(job, ci, run, pi), codec=PickleCodec())
        for k, v in mm.entries():
            grouped[k].append(v)
    out = {k: reducer(k, vals) for k, vals in grouped.items()}
    if result_name and out:
        client.get_map(result_name, codec=result_codec).put_all(out)
    return out


def _wc_chunk_task(map_name, keys, codec, *, client):
    """word_count mapper chunk: one batched read + the shared C-speed
    Counter pass.  Returns the chunk's {word: count} dict (small —
    vocabulary-sized).  Idempotent by construction: no grid writes."""
    vals = client.get_map(map_name, codec=codec).get_all(keys)
    return _host_word_count([str(v) for v in vals.values()])


def _mr_cleanup_task(job, names=None, *, client):
    """Best-effort partition reaper.  `names` (the coordinator's known
    partition names — winning runs x partitions) deletes directly; names is
    None on FAILED jobs where winning runs are unknown, falling back to a
    `mr:{job}:*` pattern sweep.  The scan is the exception path only — a
    KEYS scan per successful job would cost O(total keyspace) every run.
    A stale clone that flushes after this sweep leaks until a failed-job
    sweep touches it; that residual is leak-shaped, never correctness-shaped
    (reducers only read run names the coordinator handed them)."""
    keys = client.get_keys()
    if names is None:
        try:
            names = list(keys.get_keys(f"mr:{job}:*"))
        except Exception:  # noqa: BLE001 — best-effort cleanup
            return 0
    n = 0
    for name in names:
        try:
            n += int(keys.delete(name))  # per-name: slot-routable
        except Exception:  # noqa: BLE001 — best-effort cleanup
            pass
    return n


# grid-aware tasks get the worker's client injected (the @RInject analog;
# WorkerNode._run_one and ExecutorService._run_task both honor the marker)
_mr_map_task = inject_client(_mr_map_task)
_mr_reduce_task = inject_client(_mr_reduce_task)
_mr_cleanup_task = inject_client(_mr_cleanup_task)
_wc_chunk_task = inject_client(_wc_chunk_task)


def _await_payload_task(executor, task_id: str, timeout: float):
    """Cross-process task wait that works for local ExecutorService handles
    AND wire proxies: poll task_state (cheap), fetch the result when done.
    Results submitted via submit_payload come back as pickled bytes from
    remote workers but as live objects from in-process worker threads —
    normalize both."""
    deadline = time.time() + timeout
    while True:
        state = executor.task_state(task_id)
        if state in ("finished", "failed", "cancelled"):
            raw = executor.await_task_result(task_id, 5.0)
            if isinstance(raw, (bytes, bytearray, memoryview)):
                return pickle.loads(bytes(raw))  # noqa: S301 — coordinator's own task
            return raw
        if state is None:
            raise KeyError(f"unknown task {task_id}")
        if time.time() > deadline:
            raise TimeoutError(f"task {task_id} not finished within {timeout}s")
        time.sleep(0.02)


class MapReduce:
    """Generic map-reduce over a Map or collection handle.

    mapper(key, value, collector)           — RMapper.map analog
    reducer(key, values) -> value           — RReducer.reduce analog
    collator(result_dict) -> Any (optional) — RCollator analog

    With `executor=` an ExecutorService handle (local or wire proxy), mapper
    chunks and reducer partitions ship as claimable tasks run by WorkerNode
    processes / registered workers (CoordinatorTask.java:77-136); without
    one, the in-process thread path runs (useful for small jobs and tests).
    mapper/reducer/collator must then be module-level picklable callables.
    """

    def __init__(
        self,
        engine,
        mapper: Callable,
        reducer: Callable,
        collator: Optional[Callable] = None,
        workers: int = 4,
        executor=None,
    ):
        self._engine = engine
        self._mapper = mapper
        self._reducer = reducer
        self._collator = collator
        self._workers = max(1, workers)
        self._executor = executor
        self._timeout: Optional[float] = None

    def timeout(self, seconds: float) -> "MapReduce":
        self._timeout = seconds
        return self

    def _entries(self, source) -> List[Tuple[Any, Any]]:
        if hasattr(source, "read_all_entry_set"):
            return source.read_all_entry_set()
        if hasattr(source, "read_all"):
            return [(None, v) for v in source.read_all()]
        return list(source)

    def execute(self, source, result_map=None):
        """Run the full pipeline; returns the reduced dict (or the collator
        output if a collator was set).  Writes into `result_map` if given
        (the reference's execute(resultMapName))."""
        if self._executor is not None:
            return self._execute_distributed(source, result_map)
        entries = self._entries(source)
        n_parts = self._workers
        chunk = max(1, (len(entries) + self._workers - 1) // self._workers)
        collectors: List[Collector] = []
        threads = []
        errors: List[BaseException] = []

        def run_mapper(chunk_entries):
            c = Collector(n_parts)
            try:
                for k, v in chunk_entries:
                    self._mapper(k, v, c)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
            collectors.append(c)

        # mapper wave (MapperTask fan-out; threads play the worker role)
        for i in range(0, len(entries), chunk):
            t = threading.Thread(target=run_mapper, args=(entries[i : i + chunk],))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(self._timeout)
        if errors:
            raise errors[0]

        # shuffle: merge per-mapper partition buffers (the multimap state)
        partitions: List[Dict[Any, List[Any]]] = [defaultdict(list) for _ in range(n_parts)]
        for c in collectors:
            for pi, pmap in enumerate(c._parts):
                for k, vals in pmap.items():
                    partitions[pi][k].extend(vals)

        # reducer wave (one ReducerTask per partition)
        result: Dict[Any, Any] = {}
        res_lock = threading.Lock()
        rthreads = []

        def run_reducer(pmap):
            out = {k: self._reducer(k, vals) for k, vals in pmap.items()}
            with res_lock:
                result.update(out)

        for pmap in partitions:
            if pmap:
                t = threading.Thread(target=run_reducer, args=(pmap,))
                t.start()
                rthreads.append(t)
        for t in rthreads:
            t.join(self._timeout)

        if result_map is not None:
            result_map.put_all(result)
        if self._collator is not None:
            return self._collator(result)
        return result

    def _execute_distributed(self, source, result_map=None):
        """Coordinator for the worker-process path (CoordinatorTask.java:
        77-136): mapper chunks fan out as executor tasks, then one reducer
        task per partition; every task is claim-fenced and orphan-requeued
        by the executor machinery, so a worker dying mid-chunk re-runs on a
        survivor (TasksService re-scheduling)."""
        ex = self._executor
        name = getattr(source, "_name", None)
        if name is None:
            raise TypeError("distributed MapReduce needs a named Map handle")
        codec = getattr(source, "_codec", None)
        keys = source.read_all_keys()
        job = uuid.uuid4().hex[:12]
        n_parts = self._workers
        timeout = self._timeout or 120.0
        chunk = max(1, (len(keys) + self._workers - 1) // self._workers)
        chunks = [keys[i : i + chunk] for i in range(0, len(keys), chunk)]
        try:
            tids = [
                ex.submit_payload(
                    pickle.dumps(
                        (_mr_map_task, (name, ck, self._mapper, n_parts, job, ci, codec), {}),
                        protocol=pickle.HIGHEST_PROTOCOL,
                    )
                )
                for ci, ck in enumerate(chunks)
            ]
            # the acked map result names the WINNING run per chunk — stale
            # clones wrote under other run ids nobody will ever read
            chunk_runs = [
                (ci, _await_payload_task(ex, tid, timeout)["run"])
                for ci, tid in enumerate(tids)
            ]
            result_name = getattr(result_map, "_name", None)
            result_codec = getattr(result_map, "_codec", None)
            rtids = [
                ex.submit_payload(
                    pickle.dumps(
                        (
                            _mr_reduce_task,
                            (job, pi, chunk_runs, self._reducer, result_name, result_codec),
                            {},
                        ),
                        protocol=pickle.HIGHEST_PROTOCOL,
                    )
                )
                for pi in range(n_parts)
            ]
            result: Dict[Any, Any] = {}
            for tid in rtids:
                result.update(_await_payload_task(ex, tid, timeout))
        except BaseException:
            # failed/abandoned job: winning runs unknown — pattern sweep
            self._submit_cleanup(ex, job, None)
            raise
        else:
            # success: delete exactly the winning runs' partition names
            # (no keyspace scan on the common path); stale-clone orphans
            # wait for a failed-job sweep — a leak, never a correctness
            # hazard, because reducers only read runs the coordinator named
            self._submit_cleanup(
                ex,
                job,
                [
                    _part_name(job, ci, run, pi)
                    for ci, run in chunk_runs
                    for pi in range(n_parts)
                ],
            )
        if self._collator is not None:
            return self._collator(result)
        return result

    @staticmethod
    def _submit_cleanup(ex, job: str, names) -> None:
        """Fire-and-forget cleanup task (rides the executor so it works from
        any coordinator — local handle or wire proxy)."""
        try:
            ex.submit_payload(
                pickle.dumps(
                    (_mr_cleanup_task, (job, names), {}),
                    protocol=pickle.HIGHEST_PROTOCOL,
                )
            )
        except Exception:  # noqa: BLE001 — best-effort cleanup
            pass


class KernelMapReduce:
    """Array-native map-reduce compiled to one jitted program.

    map_fn: vmap-able (value_row -> (key_id, mapped_value)) over packed arrays
    reduce: 'sum' | 'max' | 'min' — the shuffle+reduce runs as a single
    segment reduction on device (replacing per-emit multimap writes with one
    scatter — SURVEY.md §3.5's "compile mapper/reducer to jax.vmap kernels").
    """

    def __init__(self, map_fn: Callable, reduce: str = "sum", n_keys: int = 1024):
        import jax
        import jax.numpy as jnp

        if reduce not in ("sum", "max", "min"):
            raise ValueError(f"unsupported reduce {reduce!r}")
        self._n_keys = n_keys

        def pipeline(values):
            keys, mapped = jax.vmap(map_fn)(values)
            if reduce == "sum":
                return jnp.zeros((n_keys,), mapped.dtype).at[keys].add(mapped)
            if reduce == "max":
                init = jnp.full((n_keys,), jnp.iinfo(mapped.dtype).min if mapped.dtype.kind == "i" else -jnp.inf, mapped.dtype)
                return init.at[keys].max(mapped)
            init = jnp.full((n_keys,), jnp.iinfo(mapped.dtype).max if mapped.dtype.kind == "i" else jnp.inf, mapped.dtype)
            return init.at[keys].min(mapped)

        self._jitted = jax.jit(pipeline)

    def execute(self, values) -> np.ndarray:
        """values: (N, ...) array; returns (n_keys,) reduced vector."""
        return np.asarray(self._jitted(values))


# every ASCII codepoint str.isspace() considers whitespace (str.split's
# separator set): \t\n\x0b\x0c\r plus the \x1c-\x1f file/group/record/unit
# separators — miss one and the device path diverges from str.split()
_WS_TRANSLATE = bytes.maketrans(b"\t\n\x0b\x0c\r\x1c\x1d\x1e\x1f", b" " * 9)

# any whitespace OUTSIDE that ASCII set (NBSP, ideographic space, \x85, ...)
_UNICODE_WS_RE = re.compile(r"[^\S \t\n\x0b\x0c\r\x1c\x1d\x1e\x1f]")

# gc.disable() is a process-wide toggle: a depth counter makes the pause
# reentrant across overlapping scans (one scan finishing must not re-enable
# collection under another still running)
_gc_guard = threading.Lock()
_gc_depth = 0
_gc_was_enabled = False


class _gc_paused:
    def __enter__(self):
        import gc

        global _gc_depth, _gc_was_enabled
        with _gc_guard:
            if _gc_depth == 0:
                _gc_was_enabled = gc.isenabled()
                gc.disable()
            _gc_depth += 1

    def __exit__(self, *exc):
        import gc

        global _gc_depth
        with _gc_guard:
            _gc_depth -= 1
            if _gc_depth == 0 and _gc_was_enabled:
                gc.enable()
        return False


# which pipeline ANSWERED each word count of this process.  The host path is
# a semantic alternative only (text the byte kernel cannot tokenize, more
# distinct words than d_max) — a device error propagates, it never lands
# here, so "host" rising on ASCII text under d_max is a finding, not noise.
WC_ANSWERED: Counter = Counter()


def _host_word_count(vals: List[str]) -> Dict[str, int]:
    """Single-pass C-speed fallback: per-value split + Counter.update (both
    C loops).  Measured 2026-07: ~0.67M entries/s on one core — the r2
    '64 mapper threads' variant ran 4x SLOWER than this (GIL thrash)."""
    WC_ANSWERED["host"] += 1
    c: Counter = Counter()
    for v in vals:
        c.update(v.split())
    return dict(c)


# distinct-word capacity of the device reduce (2**bits); shared by every
# path so cached views and fresh builds can never disagree on the cutoff
_WC_D_MAX_BITS = 17


class _WcScanView:
    """Tokenized device view of a value set: hashed word streams resident in
    HBM plus the normalized byte blobs for decode/fallback.

    The TPU re-expression of "the data already lives server-side": the
    reference's mapper re-reads the source hash from Redis RAM on every
    execute (MapperTask.java:50-78); here the server-side store IS device
    memory, so repeated scans of an unchanged map should start from the
    staged token arrays, not from Python strings.  Validity is keyed by the
    record's (nonce, version) — any mutation (or delete/recreate) bumps it
    and the next scan rebuilds."""

    __slots__ = ("key", "ha", "hb", "st", "blobs", "padded", "nw")

    def __init__(self, key, ha, hb, st, blobs, padded, nw):
        self.key = key
        self.ha, self.hb, self.st = ha, hb, st
        self.blobs, self.padded, self.nw = blobs, padded, nw


class _WcViewCache:
    """At most `cap` staged views per engine (LRU) — each view holds ~3
    device words per source word, so an unbounded cache would eat HBM."""

    def __init__(self, cap: int = 2):
        self._cap = cap
        self._lock = threading.Lock()
        self._views: "dict[str, _WcScanView]" = {}

    def get(self, name: str, key) -> Optional[_WcScanView]:
        with self._lock:
            v = self._views.get(name)
            if v is None:
                return None
            if v.key != key:
                # known stale: drop NOW so its HBM token arrays free even if
                # the rebuild ends on the host path and never calls put()
                self._views.pop(name)
                return None
            # refresh recency so eviction is true LRU, not FIFO
            self._views.pop(name)
            self._views[name] = v
            return v

    def put(self, name: str, view: _WcScanView) -> None:
        with self._lock:
            self._views.pop(name, None)
            self._views[name] = view
            while len(self._views) > self._cap:
                self._views.pop(next(iter(self._views)))


def _wc_tokenize(vals: List[str], n_chunks: int, key=None,
                 devices=None) -> Optional[_WcScanView]:
    """Host tokenize + device staging; None means "use the host path"
    (non-ASCII whitespace or pathological token shapes).  Chunking overlaps
    host prep of chunk i+1 with device compute of chunk i (uploads are
    staged asynchronously).

    ``devices`` (device-sharded engines, ISSUE 8): chunk i commits to
    devices[i % D], so the extract kernels of all chunks run CONCURRENTLY
    across the local mesh; the per-chunk token streams then merge back onto
    devices[0] over d2d transfers (ioplane.colocate — never a host gather)
    before the sort."""
    import jax.numpy as jnp

    from redisson_tpu.core import kernels as K

    if devices is not None and len(devices) > 1:
        n_chunks = max(n_chunks, len(devices))
    csize = max(1, (len(vals) + n_chunks - 1) // n_chunks)
    blobs: List[bytes] = []
    padded: List[int] = []
    nw = 0
    parts = []
    base = 0
    for ci in range(0, len(vals), csize):
        joined = " ".join(vals[ci : ci + csize]) + " "
        # ASCII whitespace (incl. \x1c-\x1f) is normalized by _WS_TRANSLATE;
        # non-ASCII text may carry Unicode whitespace (NBSP, \x85, ...) the
        # byte kernel cannot see — diverging from str.split() silently is
        # worse than falling back (isascii() keeps the common case O(1)-ish)
        if not joined.isascii() and _UNICODE_WS_RE.search(joined):
            return None
        big = joined.encode().translate(_WS_TRANSLATE)
        b = K.bucket_size(len(big))
        buf = np.full(b, 32, np.uint8)
        buf[: len(big)] = np.frombuffer(big, np.uint8)
        # the host counts words (one vectorized pass) but ships ONLY the
        # text: end positions are rediscovered on device by
        # wc_extract_words_auto, killing the former (E,) u16 delta upload
        # (~16MB per 1M-doc scan)
        ws = buf == 32
        n_ends = int(np.count_nonzero(~ws[:-1] & ws[1:]))
        eb = K.bucket_size(max(1, n_ends))
        if devices is not None and len(devices) > 1:
            import jax

            staged = jax.device_put(buf, devices[len(parts) % len(devices)])
        else:
            staged = K.stage(buf)
        parts.append(
            K.wc_extract_words_auto(
                staged, K.valid_n(n_ends), eb, jnp.uint32(base)
            )
        )
        blobs.append(big)
        padded.append(b)
        nw += n_ends
        base += b
    if devices is not None and len(devices) > 1 and len(parts) > 1:
        # the cross-device MapReduce MERGE: every chunk's token stream hops
        # d2d onto devices[0] (counted, zero host gathers) and the sorted
        # reduce runs there
        from redisson_tpu.core import ioplane

        parts = [
            tuple(ioplane.colocate(a, devices[0]) for a in p) for p in parts
        ]
    ha = jnp.concatenate([p[0] for p in parts])
    hb = jnp.concatenate([p[1] for p in parts])
    st = jnp.concatenate([p[2] for p in parts])
    return _WcScanView(key, ha, hb, st, blobs, padded, nw)


def prewarm_word_count(
    total_chars: int,
    total_words: int,
    n_chunks: int = 2,  # word_count's device path always scans in 2 chunks
    d_max_bits: int = None,
) -> None:
    """Load (or compile) the word-count device programs for the shape
    buckets a corpus of ~total_chars/~total_words will use, so the first
    real scan pays neither the XLA compile (~50s) nor the persistent-cache
    program load (~1.6s) inside its own latency budget.

    The reference keeps executor workers warm for exactly this reason
    (executor/TasksRunnerService.java:54,192 warm pools); here "warm" means
    the compiled programs are resident in the in-process jit cache.  Shapes
    are pow2-bucketed, so an estimate within 2x of the real corpus lands in
    the same bucket; a miss only wastes this call, never affects results.
    Call at server boot / before a timed scan, off the serving path."""
    import jax
    import jax.numpy as jnp

    from redisson_tpu.core import kernels as K

    if d_max_bits is None:
        d_max_bits = _WC_D_MAX_BITS
    csize_chars = max(1, -(-total_chars // n_chunks))
    b = K.bucket_size(csize_chars)
    wper = max(1, -(-total_words // n_chunks))
    eb = K.bucket_size(wper)
    buf = np.full(b, 32, np.uint8)
    buf[:4] = np.frombuffer(b"abc ", np.uint8)  # one real token
    part = K.wc_extract_words_auto(
        K.stage(buf), K.valid_n(1), eb, jnp.uint32(0)
    )
    # the sort program's shape is the CONCATENATED stream: n_chunks * eb
    parts = [part] * n_chunks
    ha = jnp.concatenate([p[0] for p in parts])
    hb = jnp.concatenate([p[1] for p in parts])
    st = jnp.concatenate([p[2] for p in parts])
    # fetch to host too: a session's FIRST d2h costs ~5x the steady fetch
    # (transport path setup), and a first fetch issued right after the
    # job's 50MB token upload stalls even longer (measured: ~2s vs ~0.7s
    # clean) — paying it here, at boot, is the cheap side of the trade.
    np.asarray(K.wc_sort_runs(ha, hb, st, 1 << d_max_bits))


def _wc_reduce(view: _WcScanView, d_max: int) -> Optional[Dict[str, int]]:
    """Count runs of the sorted word stream; None = distinct words exceed
    d_max (caller falls back to the host path)."""
    import jax

    from redisson_tpu.core import kernels as K

    fused = K.wc_sort_runs(view.ha, view.hb, view.st, d_max)
    # drain compute BEFORE pulling results, so the fetch below is one
    # transfer of a finished value
    jax.block_until_ready(fused)
    host = np.asarray(fused)  # ONE fetch for both result rows
    fp = host[0]
    off = host[1].view(np.uint32)
    # padding ends carry sentinel hashes that sort AFTER every real word,
    # so positions [0, nw) of the sorted array are the real words
    nw = view.nw
    finite = fp < nw
    if bool(finite[-1]):
        return None  # every fp row is a real run start: distinct > d_max
    fps = fp[finite]
    counts = np.diff(np.concatenate([fps, [nw]]))
    out: Dict[str, int] = {}
    bounds = np.cumsum([0] + view.padded)
    for o, c in zip(off[finite], counts):
        ci = int(np.searchsorted(bounds, o, side="right")) - 1
        local = int(o - bounds[ci])
        bg = view.blobs[ci]
        end = local
        while end < len(bg) and bg[end] != 32:
            end += 1
        out[bg[local:end].decode(errors="replace")] = int(c)
    WC_ANSWERED["device"] += 1
    return out


def _host_word_count_blobs(blobs: List[bytes]) -> Dict[str, int]:
    """Host fallback over a view's normalized blobs (same text, already
    whitespace-normalized, so split() agrees with the original values)."""
    WC_ANSWERED["host"] += 1
    c: Counter = Counter()
    for b in blobs:
        c.update(b.decode(errors="replace").split())
    return dict(c)


def device_word_count(vals: List[str], d_max_bits: int = _WC_D_MAX_BITS, n_chunks: int = 2) -> Dict[str, int]:
    """Word-count compiled to the device (kernels.wc_extract_words +
    wc_sort_runs; design history in that module's header).

    Host does only C-speed passes: join values into one byte buffer,
    normalize whitespace (bytes.translate), find word-end positions with two
    vectorized comparisons; the device tokenizes/hashes via scans+gathers
    and counts via sorts.  Falls back to the host path when the
    distinct-word count exceeds 2**d_max_bits."""
    if not vals:
        return {}
    view = _wc_tokenize(vals, n_chunks)
    if view is None:
        return _host_word_count(vals)
    out = _wc_reduce(view, 1 << d_max_bits)
    return _host_word_count(vals) if out is None else out


def word_count(
    source_map, workers: int = 4, executor=None, timeout: float = 120.0
) -> Dict[str, int]:
    """The canonical example (and BASELINE config 4 workload): count words
    across all values of a map.

    Three paths, fastest applicable first:
      * `executor=` given — mapper chunks ship to WorkerNode processes (the
        reference's worker-JVM model; escapes the coordinator's GIL);
      * device — the wc_* kernel pipeline (sorts/scans/gathers on chip);
      * host — single-pass C Counter fallback.
    """
    if executor is not None:
        keys = source_map.read_all_keys()
        codec = getattr(source_map, "_codec", None)
        chunk = max(1, (len(keys) + workers - 1) // workers)
        tids = [
            executor.submit_payload(
                pickle.dumps(
                    (_wc_chunk_task, (source_map._name, keys[i : i + chunk], codec), {}),
                    protocol=pickle.HIGHEST_PROTOCOL,
                )
            )
            for i in range(0, len(keys), chunk)
        ]
        total: Counter = Counter()
        for tid in tids:
            total.update(_await_payload_task(executor, tid, timeout))
        return dict(total)
    # device scan-view fast path: an UNCHANGED map re-scans from its staged
    # token arrays in HBM (see _WcScanView) — no re-read, no re-tokenize
    engine = getattr(source_map, "_engine", None)
    name = getattr(source_map, "_name", None)
    cache = rec = None
    if not getattr(source_map, "_scan_view_safe", False):
        engine = name = None  # TTL'd maps: expiry is invisible to the version
    if engine is not None and name is not None:
        try:
            rec = engine.store.get(name)
            cache = engine.service("wc_scan_views", _WcViewCache)
        except Exception:  # noqa: BLE001 — wire-backed maps have no local store
            rec = cache = None
    # snapshot the validity key BEFORE reading values: store.get returns the
    # LIVE record (mutations bump version in place on it), so the key must be
    # captured as values, not re-read through the alias after the scan
    key0 = (rec.nonce, rec.version) if rec is not None else None
    if cache is not None and key0 is not None:
        view = cache.get(name, key0)
        if view is not None:
            out = _wc_reduce(view, 1 << _WC_D_MAX_BITS)
            return _host_word_count_blobs(view.blobs) if out is None else out
    # pause cyclic gc for the scan: the value read + tokenize allocate
    # millions of short-lived objects next to the map's own millions, and
    # collection passes triggered mid-scan cost hundreds of ms of pure
    # latency (nothing here creates cycles; gen0 pressure is the trigger)
    with _gc_paused():
        raw = source_map.read_all_values()
        from redisson_tpu.client.codec import StringCodec

        if isinstance(getattr(source_map, "_codec", None), StringCodec):
            vals = raw  # StringCodec decodes to str: skip the 1M-item copy
        else:
            vals = [v if type(v) is str else str(v) for v in raw]
        if not vals:
            return {}
        key = None
        if key0 is not None:
            # revalidate after the read: a mutation racing the value read
            # must not get its torn view cached under ANY version
            rec2 = engine.store.get(name)
            if rec2 is not None and (rec2.nonce, rec2.version) == key0:
                key = key0
        placement = getattr(engine, "placement", None) if engine is not None else None
        view = _wc_tokenize(
            vals, 2, key,
            devices=placement.devices if placement is not None else None,
        )
        if view is None:
            return _host_word_count(vals)
        out = _wc_reduce(view, 1 << _WC_D_MAX_BITS)
        if out is None:
            return _host_word_count(vals)
        if cache is not None and key is not None:
            cache.put(name, view)
        return out

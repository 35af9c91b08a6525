"""Overlapped device I/O plane: double-buffered H2D staging, dispatch-ahead,
demand-driven D2H readback (ISSUE 3 tentpole).

The flush path used to execute stage -> dispatch -> fetch strictly in series:
every window paid a blocking host->device staging barrier AND a blocking
computed-result fetch before the next window could even stage.  The
reference never serializes this way — every command is async at the
CommandAsyncExecutor boundary and the wire only waits on results the caller
demanded.  This module is the device-side analog of that contract:

  * **Staging** (`StagingPool`): flush packing fills one of `depth` reusable
    host buffers; the upload of buffer B overlaps the refill of buffer A.  A
    slot is only re-issued once its previous upload has materialized on
    device, so reuse can never corrupt an in-flight copy.
  * **Dispatch-ahead** (`FlushPipeline`): up to `depth` windows stay
    dispatched-but-unfetched; window i+1's upload and kernel overlap window
    i's readback.
  * **Readback futures** (`ReadbackFuture`): kernel outputs stay on device as
    lazy handles; the D2H transfer happens only when a result is actually
    demanded (`result()`), and co-pending futures can drain in ONE grouped
    transfer (`force_all` / `gather_device_results` — the server's
    `_force_lazies` seam generalized).

Disable with ``--no-overlap`` (tpu-server flag) or ``set_overlap(False)`` /
``RTPU_NO_OVERLAP=1`` for A/B measurement: the disabled plane reproduces the
serial stage/dispatch/fetch shape exactly, and results are bit-identical in
both modes (the plane reorders WAITS, never device work — the device stream
stays in-order).

Accounting (`STATS`) counts blocking device syncs and exposed readback time;
the structural contract CI pins (tests/test_perf_smoke.py) is: N flush
windows cost <= N+1 blocking syncs overlapped vs 2N serial.
"""
from __future__ import annotations

import functools
import os
import threading
import time
import weakref
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

# tracing plane (observe/trace.py, ISSUE 12): every site below guards on the
# process-global `_obs._tracer` — disarmed cost is one global load plus an
# `is None` branch (the chaos-hook zero-cost discipline, asserted at the
# allocator level by tests/test_observe.py)
from redisson_tpu.observe import trace as _obs

# device chaos plane (ISSUE 19): the lane dispatch/readback chokepoints
# consult the SAME process-global fault plane net/client.py hosts, under the
# same discipline — disarmed cost is one global load plus an `is None`
# branch, asserted at the allocator level by tests/test_perf_smoke.py
# against these guard lines too
from redisson_tpu.net import client as _net

# -- global switch ------------------------------------------------------------

_overlap = os.environ.get("RTPU_NO_OVERLAP", "") not in ("1", "true", "yes")


def overlap_enabled() -> bool:
    return _overlap


def set_overlap(on: bool) -> bool:
    """Flip the process-global overlap switch; returns the previous value
    (callers restore it — the A/B discipline of bench.py)."""
    global _overlap
    prev = _overlap
    _overlap = bool(on)
    return prev


# process-global flush-window deadline (ISSUE 10): FlushPipelines built
# without an explicit deadline_s follow this default, so CONFIG SET
# qos-interactive-deadline-ms arms the deadline-triggered window close for
# every pipeline constructed afterwards (same process-global discipline as
# set_overlap).  None = deadline trigger off (the historical shape).
_window_deadline_s: Optional[float] = None


def set_window_deadline(seconds: Optional[float]) -> Optional[float]:
    """Set the default FlushPipeline window deadline; returns the previous
    value (callers restore it — the A/B discipline)."""
    global _window_deadline_s
    prev = _window_deadline_s
    _window_deadline_s = seconds
    return prev


def window_deadline() -> Optional[float]:
    return _window_deadline_s


# -- bulk-window preemption (ISSUE 18) ----------------------------------------
#
# The PR 9 QoS plane bounds interactive latency at ADMISSION, but once a big
# coalesced bulk window is in a lane it holds the device stream end to end —
# the tracing plane shows the interactive wait sitting in `stage`, not `qos`.
# Two mechanisms close that gap, both behind this one switch:
#
#   * sub-windows: an oversized bulk run splits into bounded chunks (target
#     items via set_bulk_subwindow_items / CONFIG SET qos-bulk-subwindow-
#     items), each its own self-contained fused dispatch through the lane,
#     with a PREEMPTION POINT between chunks — a waiting interactive frame
#     jumps the inter-sub-window boundary instead of the whole drained
#     window (DeviceLane.preempt_point);
#   * per-class streams: an interactive dispatch occupies the lane's
#     INTERACTIVE stream (its own gate + staging slot + dispatch queue), so
#     its kernel launches without queuing behind the bulk stream's
#     occupancy gate at all.
#
# Disarm with RTPU_NO_PREEMPT=1 / set_preempt(False) / tpu-server
# --no-preempt: the disarmed plane reproduces the exact single-stream,
# unsplit-window PR 9 behavior, bit-identically (splitting moves only WHERE
# the lane gate is released; per-op results are computed by the same
# kernels either way).

_preempt = os.environ.get("RTPU_NO_PREEMPT", "") not in ("1", "true", "yes")


def preempt_enabled() -> bool:
    return _preempt


def set_preempt(on: bool) -> bool:
    """Flip the process-global preemption switch; returns the previous
    value (callers restore it — the A/B discipline of bench.py)."""
    global _preempt
    prev = _preempt
    _preempt = bool(on)
    return prev


# target device items per bulk sub-window (0 = splitting off, the
# historical whole-window dispatch).  CONFIG SET qos-bulk-subwindow-items
# pushes here so every lane's dispatch path shares one knob.
_bulk_subwindow_items = 0


def bulk_subwindow_items() -> int:
    return _bulk_subwindow_items


def set_bulk_subwindow_items(n: int) -> int:
    """Set the sub-window split target; returns the previous value."""
    global _bulk_subwindow_items
    prev = _bulk_subwindow_items
    _bulk_subwindow_items = max(0, int(n))
    return prev


# -- lane watchdog (ISSUE 19) --------------------------------------------------
#
# `ReadbackFuture.result()` historically blocked FOREVER on a transfer that
# never materializes (hung DMA, preempted device) — a wedged writer task
# holding a staging slot and a connection.  The watchdog bounds that wait:
# armed (CONFIG SET lane-watchdog-ms > 0) a readback that has not
# materialized within the bound raises `LaneWatchdogTimeout`, which the
# server dispatch layer converts to a clean retryable -TRYAGAIN and the
# lane's fault ledger counts toward quarantine.  0 = off, the historical
# unbounded-wait shape, bit-identical replies.

_lane_watchdog_s = 0.0


def lane_watchdog_ms() -> int:
    return int(_lane_watchdog_s * 1000)


def set_lane_watchdog_ms(ms: int) -> int:
    """Arm/disarm the readback lane watchdog (0 = off); returns the
    previous value in ms (callers restore it — the A/B discipline)."""
    global _lane_watchdog_s
    prev = int(_lane_watchdog_s * 1000)
    _lane_watchdog_s = max(0, int(ms)) / 1000.0
    return prev


# consecutive device faults/timeouts that flip a lane to QUARANTINED
_quarantine_after = 3


def quarantine_after() -> int:
    return _quarantine_after


def set_quarantine_after(n: int) -> int:
    """Set the consecutive-fault quarantine threshold; returns the
    previous value."""
    global _quarantine_after
    prev = _quarantine_after
    _quarantine_after = max(1, int(n))
    return prev


class LaneWatchdogTimeout(RuntimeError):
    """A device readback exceeded the armed lane-watchdog bound — the
    frame fails retryably (-TRYAGAIN) instead of wedging its writer."""


class KernelCompileError(RuntimeError):
    """XLA refused to COMPILE a kernel.  Raised at the first call of a
    program, before any dispatch is in flight, and deterministic: the same
    call fails the same way forever, so it is fatal to its frame (-ERR) and
    never a retryable device fault — even though the runtime words it
    ``INTERNAL: ...`` exactly like a failed launch.  core/kernels.py tags
    compile failures with this class where jax raises them."""


def is_retryable_device_fault(e: BaseException) -> bool:
    """Device-layer failure shapes the server dispatch layer converts to a
    clean retryable ``-TRYAGAIN``: the lane-watchdog timeout and the
    JaxRuntimeError transient-runtime prefixes (a failed kernel launch, a
    preempted/unavailable device).  Matched on the message, never the
    class, so the chaos plane's injected errors ride the same path.
    RESOURCE_EXHAUSTED is deliberately NOT here — HBM exhaustion takes the
    -OOM degradation path (services/vector.DeviceOomError) — and neither
    is a compile failure (KernelCompileError)."""
    if isinstance(e, LaneWatchdogTimeout):
        return True
    if not isinstance(e, RuntimeError) or isinstance(e, KernelCompileError):
        return False
    return str(e).lstrip().startswith(
        ("INTERNAL", "UNAVAILABLE", "ABORTED", "CANCELLED",
         "DEADLINE_EXCEEDED")
    )


# which lane stream the CURRENT THREAD's dispatch occupies ("interactive"
# while an interactive _LaneOccupancy is held): engine.staging_pool reads
# this to hand the interactive fast path its own staging slot without
# threading the QoS class through every pack call
_stream_tls = threading.local()


def current_stream() -> Optional[str]:
    return getattr(_stream_tls, "stream", None)


_staging_safe: Optional[bool] = None


def staging_reuse_safe() -> bool:
    """Pooled host-buffer reuse requires device_put to COPY.  CPU jax may
    zero-copy ALIAS suitably-aligned numpy memory, so refilling a slot would
    corrupt the "device" value it staged earlier; off-CPU the upload is a
    real DMA copy and reuse is safe.  Cached once per process."""
    global _staging_safe
    if _staging_safe is None:
        try:
            import jax

            _staging_safe = jax.default_backend() != "cpu"
        except Exception:  # noqa: BLE001 — no jax: nothing stages anyway
            _staging_safe = False
    return _staging_safe


# -- blocking-sync + readback accounting --------------------------------------


class IOStats:
    """Process-global counters for the plane's observable costs.

    ``blocking_syncs`` counts every host-side wait on device work the plane
    performs (staging barriers, forced readbacks, grouped gathers) — the
    quantity the structural smoke test bounds.  ``readback_exposed_s``
    accumulates ONLY the readback wall time spent while the device value was
    not yet ready (the un-hidden part); bench.py derives overlap efficiency
    as 1 - exposed/serial_total.

    ``d2d_colocations`` / ``host_colocations`` audit the cross-device merge
    discipline (ISSUE 8): moving a device value onto another device for an
    on-device merge must be a direct device transfer (``colocate``), never a
    host round trip — the soak/tests assert host_colocations stays 0."""

    __slots__ = ("_lock", "blocking_syncs", "readbacks", "readback_wait_s",
                 "readback_exposed_s", "staging_waits", "barrier_wait_s",
                 "d2d_colocations", "host_colocations", "sharded_knn_merges",
                 "merge_fallbacks")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        self.blocking_syncs = 0
        self.readbacks = 0
        self.readback_wait_s = 0.0
        self.readback_exposed_s = 0.0
        self.staging_waits = 0
        self.barrier_wait_s = 0.0
        self.d2d_colocations = 0
        self.host_colocations = 0
        self.sharded_knn_merges = 0
        self.merge_fallbacks = 0

    def count_sync(self, n: int = 1) -> None:
        with self._lock:
            self.blocking_syncs += n

    def add_barrier(self, wall_s: float) -> None:
        with self._lock:
            self.blocking_syncs += 1
            self.barrier_wait_s += wall_s

    def count_staging_wait(self) -> None:
        with self._lock:
            self.blocking_syncs += 1
            self.staging_waits += 1

    def add_readback(self, wall_s: float, was_ready: bool) -> None:
        with self._lock:
            self.blocking_syncs += 1
            self.readbacks += 1
            self.readback_wait_s += wall_s
            if not was_ready:
                self.readback_exposed_s += wall_s

    def count_colocation(self, via_host: bool) -> None:
        with self._lock:
            if via_host:
                self.host_colocations += 1
            else:
                self.d2d_colocations += 1

    def count_sharded_merge(self) -> None:
        """One on-device sharded-KNN top-k merge ran (ISSUE 15) — paired
        with host_colocations == 0 this proves the cross-shard reduce
        stayed on the interconnect (the vector soak asserts both)."""
        with self._lock:
            self.sharded_knn_merges += 1

    def count_merge_fallback(self) -> None:
        """A cross-device merge left the mesh-collective path for the d2d
        colocate chain (parallel/manager.merge_across_devices).  Still
        on-device and correct, but an error took it there: the chip smoke
        asserts this stays 0."""
        with self._lock:
            self.merge_fallbacks += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "blocking_syncs": self.blocking_syncs,
                "readbacks": self.readbacks,
                "readback_wait_s": self.readback_wait_s,
                "readback_exposed_s": self.readback_exposed_s,
                "staging_waits": self.staging_waits,
                "barrier_wait_s": self.barrier_wait_s,
                "d2d_colocations": self.d2d_colocations,
                "host_colocations": self.host_colocations,
                "sharded_knn_merges": self.sharded_knn_merges,
                "merge_fallbacks": self.merge_fallbacks,
            }


STATS = IOStats()

# -- per-device stats (ISSUE 8: IOStats split per device) ---------------------
# One IOStats per local device id, lazily created: the per-device serving
# lanes attribute their gathers/syncs here IN ADDITION to the global STATS
# (the global counters keep their exact historical semantics — every
# structural contract pinned against STATS is unchanged).

_DEVICE_STATS: dict = {}
_DEVICE_STATS_LOCK = threading.Lock()


def device_stats(dev_id: int) -> IOStats:
    with _DEVICE_STATS_LOCK:
        st = _DEVICE_STATS.get(dev_id)
        if st is None:
            st = _DEVICE_STATS[dev_id] = IOStats()
        return st


def device_stats_snapshot() -> dict:
    with _DEVICE_STATS_LOCK:
        stats = dict(_DEVICE_STATS)
    return {d: s.snapshot() for d, s in stats.items()}


def reset_device_stats() -> None:
    with _DEVICE_STATS_LOCK:
        for s in _DEVICE_STATS.values():
            s.reset()


def device_of(value):
    """Single committed device of a jax array, else None (numpy values,
    uncommitted arrays, multi-device sharded planes)."""
    devs = getattr(value, "devices", None)
    if devs is None:
        return None
    ds = devs()
    return next(iter(ds)) if len(ds) == 1 else None


def _device_id_of(value) -> Optional[int]:
    """Single committed device id of a jax array, else None (numpy values
    and multi-device sharded arrays)."""
    dev = device_of(value)
    return None if dev is None else dev.id


def colocate(value, device):
    """Move a device value onto `device` WITHOUT a host round trip: the
    cross-device merge primitive (HLL PFMERGE/PFCOUNT across slots,
    MapReduce chunk-merge, BITOP across records).  On TPU this is an ICI
    device-to-device copy — the same interconnect the parallel/ mesh
    collectives ride; the host fallback exists only for exotic transfer
    failures and is COUNTED so the zero-host-gather contract is auditable
    (STATS.host_colocations)."""
    if device is None:
        return value
    devs = getattr(value, "devices", None)
    if devs is None:
        return value  # host value: the dispatch will stage it where needed
    if devs() == {device}:
        return value
    import jax

    try:
        out = jax.device_put(value, device)
        STATS.count_colocation(via_host=False)
        return out
    except Exception:  # noqa: BLE001 — transfer path unavailable: go via host
        out = jax.device_put(np.asarray(value), device)
        STATS.count_colocation(via_host=True)
        return out


def _is_ready(x) -> bool:
    """True when a device value has materialized (forcing it costs only the
    transfer, no compute wait).  Non-jax values (numpy fallbacks) are always
    ready."""
    f = getattr(x, "is_ready", None)
    if f is None:
        return True
    try:
        return bool(f())
    except Exception:  # noqa: BLE001 — deleted/donated buffer: nothing to wait on
        return True


def barrier(values) -> None:
    """COUNTED blocking device sync: the serial path's explicit
    stage/dispatch drain before a fetch (the `--no-overlap` reference
    shape).  The overlapped path never calls this.  Wall time is recorded
    (STATS.barrier_wait_s) so bench's A/B can attribute the serial path's
    total readback cost: barrier wait + forced fetch."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(values)
    wall = time.perf_counter() - t0
    STATS.add_barrier(wall)
    for dev_id in {
        d for d in (_device_id_of(v) for v in values) if d is not None
    }:
        device_stats(dev_id).add_barrier(wall)


# -- readback futures ----------------------------------------------------------


class ReadbackFuture:
    """Demand-driven D2H readback handle (the RFuture of the device plane).

    Holds kernel outputs as device references; ``result()`` performs the
    host transfer on first demand (counted, exposed-time attributed) and
    caches.  ``force_all`` primes several futures with ONE grouped transfer
    instead — device references are released either way."""

    __slots__ = ("_device", "_finish", "_value", "_error", "_done")

    def __init__(self, device: Sequence[Any], finish: Optional[Callable] = None):
        self._device: tuple = tuple(device)
        self._finish = finish
        self._value = None
        self._error: Optional[BaseException] = None
        self._done = False

    def done(self) -> bool:
        return self._done

    def ready(self) -> bool:
        """True when result() would not block on device work."""
        return self._done or all(_is_ready(v) for v in self._device)

    def _deliver(self, host: tuple) -> None:
        try:
            self._value = self._finish(host) if self._finish is not None else (
                host[0] if len(host) == 1 else host
            )
        except BaseException as e:  # noqa: BLE001 — surfaced on result()
            self._error = e
        self._done = True
        self._device = ()  # release device memory references

    def _chaos_stall(self, plane, dev_ids, was_ready: bool) -> bool:
        """Apply an injected hung-transfer stall (device_hang).  With the
        watchdog armed a stall past the bound waits only the bound and
        trips; otherwise the transfer just takes `stall` seconds — the
        pre-watchdog shape, bounded so tests terminate.  Returns the
        (possibly demoted) was_ready flag."""
        stall = 0.0
        for d in dev_ids:
            s = plane.on_device_readback(d)
            if s > stall:
                stall = s
        if stall <= 0.0:
            return was_ready
        bound = _lane_watchdog_s
        if bound > 0.0 and stall > bound:
            time.sleep(bound)
            self._trip(dev_ids, bound)
        else:
            time.sleep(stall)
        return False

    def _wait_ready(self, bound: float) -> bool:
        """Bounded poll for device materialization (the armed watchdog's
        wait): True when every value is ready within `bound` seconds."""
        deadline = time.monotonic() + bound
        while not all(_is_ready(v) for v in self._device):
            left = deadline - time.monotonic()
            if left <= 0.0:
                return False
            time.sleep(min(0.002, left))
        return True

    def _trip(self, dev_ids, wall: float) -> None:
        """The watchdog fired: account the (bounded) wait, attribute a
        timeout fault to every involved lane, and fail this future with
        `LaneWatchdogTimeout` — retryable, never a wedged writer."""
        STATS.add_readback(wall, False)
        for d in dev_ids:
            device_stats(d).add_readback(wall, False)
            note_device_fault(d, "watchdog_timeout")
        if _obs._tracer is not None:
            cur = _obs.current_trace()
            if cur is not None:
                now = time.monotonic()
                cur.add_span(
                    "readback", now - wall, now,
                    blocking=1, grouped=0, timeout=1,
                )
        devs = ", ".join(str(d) for d in sorted(dev_ids)) or "?"
        self._error = LaneWatchdogTimeout(
            f"readback exceeded lane-watchdog bound "
            f"({lane_watchdog_ms()}ms) on device(s) {devs}"
        )
        self._done = True
        self._device = ()

    def _guard(self, plane, bound: float) -> None:
        """Armed-only detection gate shared by ``result()`` and
        ``force_all``: applies any injected hung-transfer stall, then
        enforces the lane-watchdog bound on the device wait.  Never called
        on the disarmed path (no plane, watchdog off)."""
        was_ready = all(_is_ready(v) for v in self._device)
        dev_ids = {
            d for d in (_device_id_of(v) for v in self._device)
            if d is not None
        }
        t0 = time.perf_counter()
        if plane is not None:
            was_ready = self._chaos_stall(plane, dev_ids, was_ready)
        if (not self._done and bound > 0.0 and not was_ready
                and not self._wait_ready(bound)):
            self._trip(dev_ids, time.perf_counter() - t0)

    def result(self):
        if not self._done:
            plane = _net._fault_plane
            bound = _lane_watchdog_s
            if plane is not None or bound > 0.0:
                self._guard(plane, bound)
        if not self._done:
            was_ready = all(_is_ready(v) for v in self._device)
            dev_ids = {
                d for d in (_device_id_of(v) for v in self._device)
                if d is not None
            }
            t0 = time.perf_counter()
            try:
                host = tuple(np.asarray(v) for v in self._device)
            except BaseException as e:  # noqa: BLE001
                STATS.add_readback(time.perf_counter() - t0, was_ready)
                for dev_id in dev_ids:
                    note_device_fault(dev_id, "readback_error")
                self._error = e
                self._done = True
                self._device = ()
            else:
                wall = time.perf_counter() - t0
                STATS.add_readback(wall, was_ready)
                for dev_id in dev_ids:  # per-lane sync ledger (ISSUE 8)
                    device_stats(dev_id).add_readback(wall, was_ready)
                    note_device_ok(dev_id)
                if _obs._tracer is not None:
                    cur = _obs.current_trace()
                    if cur is not None:
                        # this frame PAID a blocking sync iff the device
                        # value had not materialized when force hit it
                        now = time.monotonic()
                        cur.add_span(
                            "readback", now - wall, now,
                            blocking=int(not was_ready), grouped=0,
                        )
                self._deliver(host)
        if self._error is not None:
            raise self._error
        return self._value


_GATHER_POOL = None
_GATHER_POOL_LOCK = threading.Lock()


def _gather_pool():
    """Small shared pool for CONCURRENT per-device d2h fetches: with the
    slot table device-sharded (ISSUE 8), one frame's results live on
    several devices and cannot concatenate into one stream — fetching the
    per-device sub-streams in parallel overlaps their transfer latencies
    (serializing D fetches would pay D sync latencies back to back)."""
    global _GATHER_POOL
    with _GATHER_POOL_LOCK:
        if _GATHER_POOL is None:
            from concurrent.futures import ThreadPoolExecutor

            _GATHER_POOL = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="rtpu-d2h"
            )
        return _GATHER_POOL


def _readback_guard(dev_id: Optional[int], parts: Sequence[Any]) -> None:
    """Armed-only readback gate for the grouped per-device fetch (the
    serving path's ONE transfer per device): applies any injected
    hung-transfer stall and enforces the lane-watchdog bound before the
    blocking transfer starts.  Raises ``LaneWatchdogTimeout`` (retryable)
    with the fault attributed to the lane.  Disarmed cost: one global
    load + one float compare, then return."""
    plane = _net._fault_plane
    bound = _lane_watchdog_s
    if (plane is None and bound <= 0.0) or dev_id is None:
        return
    stall = 0.0
    if plane is not None:
        stall = plane.on_device_readback(dev_id)
    if stall > 0.0:
        if bound > 0.0 and stall > bound:
            time.sleep(bound)
            note_device_fault(dev_id, "watchdog_timeout")
            raise LaneWatchdogTimeout(
                f"readback exceeded lane-watchdog bound "
                f"({lane_watchdog_ms()}ms) on device(s) {dev_id}"
            )
        time.sleep(stall)
        return
    if bound > 0.0:
        deadline = time.monotonic() + bound
        while not all(_is_ready(p) for p in parts):
            left = deadline - time.monotonic()
            if left <= 0.0:
                note_device_fault(dev_id, "watchdog_timeout")
                raise LaneWatchdogTimeout(
                    f"readback exceeded lane-watchdog bound "
                    f"({lane_watchdog_ms()}ms) on device(s) {dev_id}"
                )
            time.sleep(min(0.002, left))


# -- the grouped fetch's fixed shapes ---------------------------------------------
# A frame over many tenants owes each device dozens of small values (previous
# bits, lengths, found vectors), their number and order the frame's
# composition.  Merging them with one concatenate was a new XLA program for
# every composition (and two eager ops a part before it: 25.7 ms for a
# 16-tenant frame's 50 parts on a v5e host).  Parts of one dtype and shape
# are stacked instead, by ONE jitted program whose operand count is padded to
# a rung below, and every stack and every lone part crosses with an
# asynchronous copy started before the first is waited for: 1.3 ms for the
# same 50 parts, against 4.2 ms for an asynchronous copy of every part and
# 1.1 ms for a program a composition (v5e, PERF.md section 6, PR 26).
GATHER_STACK_RUNGS = (4, 16, 64)
GATHER_STACK_MAX_BYTES = 1 << 20  # larger parts cross alone: the copy is the cost

_STACK_WARM: set = set()
_STACK_WARM_LOCK = threading.Lock()

_GATHER_BYTES_LOCK = threading.Lock()
_gather_bytes_owed = 0
_gather_bytes_fetched = 0


def gather_bytes_counted() -> tuple:
    """(owed, fetched) byte totals of this process's grouped fetches: bytes
    the replies are made from against bytes brought to the host for them (a
    stack's padding, the rest of a value several replies cut their rows
    from).  METRICS exports both (gather_bytes_owed_total,
    gather_bytes_fetched_total), always on."""
    return _gather_bytes_owed, _gather_bytes_fetched


@functools.lru_cache(maxsize=None)
def _stack_program():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda *xs: jnp.stack(xs))


def _stack_parts(*parts):
    return _stack_program()(*parts)


def warm_targets(value) -> list:
    """[(device, committed)] a warm-up for `value`'s kind has to cover:
    where `value` sits, placed as it is, and — committed, as placement
    commits a lane's records — every other device that serves a lane beside
    that one (none on an engine without placement).  A jitted program is
    compiled for where its operands are committed, so a warm-up call runs on
    stand-ins placed as the values they stand in for."""
    own = device_of(value)
    own_id = getattr(own, "id", None)
    beside = [
        lane.device for ls in list(_LANE_SETS) if own_id in ls._lanes
        for lane in ls.lanes() if lane.dev_id != own_id
    ]
    return [(own, bool(getattr(value, "committed", False)))] + [
        (dev, True) for dev in dict.fromkeys(beside)
    ]


def stand_in(value, device):
    """`value` itself where it sits, else a copy committed to `device`."""
    import jax

    return value if device == device_of(value) else jax.device_put(value, device)


def target_key(target, kind) -> tuple:
    device, committed = target
    return (getattr(device, "id", None), committed, kind)


def warm_stack_class(part) -> None:
    """Compile the stack program of `part`'s dtype and shape at every rung
    before any is needed, on every warm target (device-sharded serving:
    which lane first owes two values of a kind, and how many, is a frame's
    composition).  Called for every kind of value a several-part fetch
    meets, stacked or not, and by producers that know their results' shapes
    ahead (core/coalesce.py): a kind first met in warm-up traffic has its
    programs before the traffic that counts."""
    kind = (part.dtype.name, part.shape)
    targets = warm_targets(part)
    if all(target_key(t, kind) in _STACK_WARM for t in targets):
        return
    with _STACK_WARM_LOCK:
        for target in targets:
            key = target_key(target, kind)
            if key in _STACK_WARM:
                continue
            operand = stand_in(part, target[0])
            for rung in GATHER_STACK_RUNGS:
                _stack_parts(*([operand] * rung))
            _STACK_WARM.add(key)


def _fetch_one_part(part):
    """The one-part fetch, as it has always been: the value as a uint8
    stream (bool via uint8, other dtypes bitcast), one transfer, viewed back
    on the host."""
    import jax
    import jax.numpy as jnp

    was_bool = part.dtype == jnp.bool_
    if was_bool:
        b = part.astype(jnp.uint8)  # exact: values are 0/1
    elif part.dtype == jnp.uint8:
        b = part
    else:
        b = jax.lax.bitcast_convert_type(part, jnp.uint8)
    merged = np.asarray(jnp.ravel(b))
    if was_bool:
        return merged.reshape(part.shape).astype(bool)
    return merged.view(np.dtype(part.dtype.name)).reshape(part.shape)


def _fetch_parts(parts: Sequence[Any]) -> Tuple[list, int, int, int]:
    """Several values of one device -> (host arrays, bytes brought,
    transfers made, largest stack rung used)."""
    classes: "dict[tuple, List[int]]" = {}
    for i, part in enumerate(parts):
        classes.setdefault((part.dtype.name, part.shape), []).append(i)
    crossing = []  # (device value, the positions it carries, stacked?)
    top = GATHER_STACK_RUNGS[-1]
    widest = 0
    for members in classes.values():
        first = parts[members[0]]
        if first.nbytes > GATHER_STACK_MAX_BYTES:
            crossing += [(parts[i], [i], False) for i in members]
            continue
        warm_stack_class(first)
        if len(members) == 1:
            crossing.append((first, members, False))
            continue
        for at in range(0, len(members), top):
            some = members[at : at + top]
            rung = next(r for r in GATHER_STACK_RUNGS if len(some) <= r)
            stack = _stack_parts(
                *[parts[i] for i in some], *([first] * (rung - len(some)))
            )
            crossing.append((stack, some, True))
            widest = max(widest, rung)
    for value, _at, _stacked in crossing:
        value.copy_to_host_async()
    host: list = [None] * len(parts)
    fetched = 0
    for value, positions, stacked in crossing:
        got = np.asarray(value)
        fetched += got.nbytes
        for row, i in enumerate(positions):
            host[i] = got[row, ...] if stacked else got  # an array also where 0-d
    return host, fetched, len(crossing), widest


def gather_device_results(groups: Sequence[Sequence[Any]],
                          owed: Optional[Sequence[Optional[int]]] = None,
                          note: Optional[dict] = None) -> List[tuple]:
    """Fetch every device value of `groups`, each device's share with a
    number of transfers that does not grow with the number of values, and
    with a bounded set of XLA programs whatever their number, order and
    shapes.  A value named by several groups crosses once.  Per device:

      * one value, no lanes: bitcast to a uint8 stream, one transfer, viewed
        back on the host — the historical single-result fetch, unchanged
        (its two eager programs are a pair a kind of value AND a device:
        where lanes serve, a lane that owes one value is a frame's
        composition, and the value crosses as the lone ones below do);
      * several: values of one dtype and shape are stacked by one jitted
        program at a padded operand count (GATHER_STACK_RUNGS; the class's
        rungs are compiled together, on every lane, when it is first met),
        lone and large values cross as they are, and every crossing is an
        asynchronous copy started before the first is waited for.

    Devices fetch concurrently.  G groups at one blocking transfer each
    would pay G sync latencies (~0.4 ms each on a v5e host).  numpy values
    pass through.  `owed[i]` is the number of bytes group i's reply is made
    from where that is less than its values hold (rows of a shared result);
    it feeds gather_bytes_counted.  `note` (tracing armed) receives `parts`,
    `fetches` and the largest stack `bucket`."""
    global _gather_bytes_owed, _gather_bytes_fetched

    uniq: List[Any] = []     # device values, each once
    seen: "dict[int, int]" = {}
    index: List[List[Any]] = []  # per group: position in uniq, or the value
    owed_total = 0
    for gi, group in enumerate(groups):
        pos = []
        own = 0
        for arr in group:
            if not hasattr(arr, "copy_to_host_async"):  # already on the host
                pos.append(np.asarray(arr))
                continue
            at = seen.get(id(arr))
            if at is None:
                at = seen[id(arr)] = len(uniq)
                uniq.append(arr)
            pos.append(at)
            own += arr.nbytes
        index.append(pos)
        less = owed[gi] if owed is not None else None
        owed_total += own if less is None else min(less, own)
    if not uniq:
        return [tuple(pos) for pos in index]
    # bucket by committed device: cross-device values can neither stack nor
    # ride one transfer — each device fetches its own share (device-sharded
    # serving, ISSUE 8)
    buckets: "dict[Optional[int], List[int]]" = {}
    for ui, part in enumerate(uniq):
        buckets.setdefault(_device_id_of(part), []).append(ui)

    host: List[Any] = [None] * len(uniq)
    tally = [(0, 0, 0)] * len(buckets)  # (bytes, transfers, rung) a device

    def fetch_bucket(bi: int, dev_id, uis) -> None:
        parts = [uniq[ui] for ui in uis]
        _readback_guard(dev_id, parts)
        STATS.count_sync()
        if dev_id is not None:
            device_stats(dev_id).count_sync()
        if len(parts) == 1 and not any(dev_id in ls._lanes for ls in list(_LANE_SETS)):
            got = [_fetch_one_part(parts[0])]
            tally[bi] = (got[0].nbytes, 1, 0)
        else:
            got, *tally[bi] = _fetch_parts(parts)
        for ui, value in zip(uis, got):
            host[ui] = value

    items = list(buckets.items())
    if len(items) == 1:
        fetch_bucket(0, *items[0])
    else:
        futs = [
            _gather_pool().submit(fetch_bucket, bi, dev_id, uis)
            for bi, (dev_id, uis) in enumerate(items)
        ]
        for f in futs:
            f.result()  # surface the first failure (caller falls back)
    with _GATHER_BYTES_LOCK:
        _gather_bytes_owed += owed_total
        _gather_bytes_fetched += sum(t[0] for t in tally)
    if note is not None:
        note.update(parts=len(uniq), fetches=sum(t[1] for t in tally),
                    bucket=max(t[2] for t in tally))
    return [
        tuple(host[p] if isinstance(p, int) else p for p in pos)
        for pos in index
    ]


@functools.lru_cache(maxsize=256)
def _scatter_fn(sig: tuple):
    """Jitted on-device unpack for scatter_host_arrays: slice the merged
    uint8 stream at static offsets, bitcast each piece back to its dtype,
    reshape — one compile per layout signature (the exact inverse of the
    gather path's bitcast/concat)."""
    import jax
    import jax.numpy as jnp

    def unpack(stream):
        out = []
        for off, nbytes, dtype_name, shape, was_bool in sig:
            piece = jax.lax.slice_in_dim(stream, off, off + nbytes)
            dt = np.dtype(dtype_name)
            if was_bool:
                out.append(piece.astype(jnp.bool_).reshape(shape))
            elif dt == np.uint8:
                out.append(piece.reshape(shape))
            else:
                n = nbytes // dt.itemsize
                out.append(
                    jax.lax.bitcast_convert_type(
                        piece.reshape(n, dt.itemsize), dt
                    ).reshape(shape)
                )
        return tuple(out)

    return jax.jit(unpack)


def scatter_host_arrays(arrays: dict, device, pool: "Optional[StagingPool]" = None
                        ) -> dict:
    """Upload a dict of host arrays to `device` with ONE host->device
    transfer — the inverse of gather_device_results: view every array as a
    uint8 byte stream (bool via uint8, values 0/1), pack them into one
    merged host buffer (through the lane's double-buffered staging pool
    when one is armed), upload the merged stream once, then split/bitcast/
    reshape entirely on device (jitted, one compile per layout signature).
    Returns {key: committed jax.Array on `device`}.  Same constraint as
    the gather path: each dtype must round-trip via ``np.dtype(a.dtype
    .name)`` — callers fall back to per-array device_put on any raise."""
    import jax

    keys = sorted(arrays)
    sig = []
    chunks = []
    off = 0
    for k in keys:
        a = np.asarray(arrays[k])
        np.dtype(a.dtype.name)  # raises on non-round-tripping dtypes
        was_bool = a.dtype == np.bool_
        b = a.astype(np.uint8) if was_bool else a
        stream = np.ascontiguousarray(b).view(np.uint8).ravel()
        sig.append((off, int(stream.size), a.dtype.name, tuple(a.shape),
                    was_bool))
        chunks.append(stream)
        off += int(stream.size)
    if off == 0:  # nothing but empty planes: placement still applies
        return {k: jax.device_put(np.asarray(arrays[k]), device) for k in keys}
    if pool is not None:
        buf, slot = pool.acquire((off,), np.uint8)
    else:
        buf, slot = np.empty(off, np.uint8), None
    pos = 0
    for stream in chunks:
        buf[pos:pos + stream.size] = stream
        pos += stream.size
    merged = jax.device_put(buf, device)
    if pool is not None:
        pool.commit(slot, merged)
    parts = _scatter_fn(tuple(sig))(merged)
    return dict(zip(keys, parts))


def force_all(futures: Sequence[ReadbackFuture]) -> None:
    """Materialize several ReadbackFutures with ONE grouped transfer (the
    frame-level drain the server's reply path uses; the embedded Batch
    drains its pending groups through here too)."""
    todo = [f for f in futures if not f.done()]
    if not todo:
        return
    # the SAME detection gate result() applies: injected hung-transfer
    # stalls land here too, and the armed lane watchdog bounds the grouped
    # drain — a wedged device fails its futures with LaneWatchdogTimeout
    # instead of wedging the whole reply frame.  Disarmed cost: one global
    # load + one float compare.
    plane = _net._fault_plane
    bound = _lane_watchdog_s
    if plane is not None or bound > 0.0:
        for f in todo:
            f._guard(plane, bound)
        todo = [f for f in todo if not f.done()]  # tripped: error delivered
        if not todo:
            return
    try:
        host_groups = gather_device_results([f._device for f in todo])
    except Exception:  # noqa: BLE001 — grouped path failed; force singly
        for f in todo:
            try:
                f.result()
            except Exception:  # noqa: BLE001 — error lands on THAT future
                pass
        return
    for f, host in zip(todo, host_groups):
        f._deliver(host)


# -- double-buffered host staging ----------------------------------------------


class _StageSlot:
    __slots__ = ("buf", "staged", "busy")

    def __init__(self, buf: np.ndarray):
        self.buf = buf
        self.staged = None  # device handle last uploaded from this buffer
        self.busy = False


class StagingPool:
    """Double-buffered host staging buffers for flush packing.

    ``acquire(shape, dtype)`` hands out a zeroed host view backed by one of
    ``depth`` reusable slots; ``commit(slot, staged)`` pairs the slot with
    the device copy made from it and frees it.  A slot is re-issued only
    once its previous upload has materialized (a real wait is counted as a
    blocking sync) — refilling buffer A therefore overlaps buffer B's
    in-flight upload, and reuse can never scribble over bytes the DMA is
    still reading.  When every slot is checked out (deep concurrent
    fan-out) acquire degrades to a fresh one-off allocation (slot=None):
    correctness never depends on pool depth."""

    def __init__(self, depth: int = 2):
        self._lock = threading.Lock()
        self._slots: List[_StageSlot] = []
        self._depth = max(1, depth)
        self.reuses = 0  # observability (ResourceCensus-friendly gauges)
        self.oneoffs = 0

    def acquire(self, shape, dtype=np.uint32) -> Tuple[np.ndarray, Optional[_StageSlot]]:
        want = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        slot = None
        with self._lock:
            for s in self._slots:
                if not s.busy:
                    s.busy = True
                    slot = s
                    break
            if slot is None and len(self._slots) < self._depth:
                slot = _StageSlot(np.empty(max(want, 1), np.uint8))
                slot.busy = True
                self._slots.append(slot)
        if slot is None:
            self.oneoffs += 1
            return np.zeros(shape, dtype), None
        staged, slot.staged = slot.staged, None
        if staged is not None and not _is_ready(staged):
            # the double-buffer boundary: the slot's previous upload is
            # still in flight — wait (counted) before touching its bytes
            import jax

            STATS.count_staging_wait()
            jax.block_until_ready(staged)
        if slot.buf.nbytes < want:
            slot.buf = np.empty(want, np.uint8)
        self.reuses += 1
        view = slot.buf[:want].view(dtype).reshape(shape)
        view[...] = 0
        return view, slot

    def commit(self, slot: Optional[_StageSlot], staged):
        """Record the device handle uploaded from `slot` and free the slot.
        Returns `staged` for call-site chaining; slot=None (one-off buffer)
        is a no-op."""
        if slot is not None:
            with self._lock:
                slot.staged = staged
                slot.busy = False
        return staged

    def release(self, slot: Optional[_StageSlot]) -> None:
        """Abandon a slot without an upload (error paths)."""
        if slot is not None:
            with self._lock:
                slot.busy = False

    def clear(self) -> None:
        with self._lock:
            self._slots.clear()

    def slot_count(self) -> int:
        with self._lock:
            return len(self._slots)


# -- dispatch-ahead flush driver -----------------------------------------------


class FlushPipeline:
    """stage -> dispatch -> fetch driver for a stream of flush windows — the
    plane's A/B harness (bench.py's overlap sub-measurement and the CPU
    structural smoke both drive it).

    ``submit(fn)``: ``fn()`` stages + dispatches ONE window and returns
    ``(device_values, finish)`` with ``finish(host_tuple) -> result``.

      * overlap on: returns a ReadbackFuture immediately; at most ``depth``
        windows stay un-forced (the dispatch-ahead bound) — submitting
        window depth+1 forces the oldest, whose readback by then overlapped
        the younger windows' staging and dispatch.  N windows cost N counted
        readback syncs (+ at most one staging wait): the <= N+1 contract.
      * overlap off: the strict serial reference — a counted barrier on the
        window's device values (the stage/dispatch drain) then an immediate
        forced fetch: exactly 2 blocking syncs per window, the 2N shape.

    Deadline-aware window close (ISSUE 10, the QoS plane): size/arrival are
    no longer the ONLY flush triggers —

      * ``submit(fn, interactive=True)`` closes the window at the deadline
        class boundary: an interactive window's readback is forced as soon
        as its dispatch lands instead of parking un-forced behind up to
        ``depth`` bulk windows (laziness trades the bulk stream's
        throughput for the interactive result's latency, exactly the wrong
        trade for that class);
      * with ``deadline_s`` set, any window older than the deadline is
        forced by the next submit, bounding how long a result can sit
        dispatched-but-undelivered when traffic goes quiet.

    Neither trigger reorders device work — only WAITS move, so results stay
    bit-identical (the same contract as the overlap switch itself).
    """

    def __init__(self, *, overlap: Optional[bool] = None, depth: int = 2,
                 deadline_s: Optional[float] = None):
        self.overlap = overlap_enabled() if overlap is None else bool(overlap)
        self.depth = max(1, depth)
        # None = follow the process-global default (set_window_deadline,
        # armed by CONFIG SET qos-interactive-deadline-ms)
        self.deadline_s = (
            _window_deadline_s if deadline_s is None else deadline_s
        )
        self._ring: List[Tuple[ReadbackFuture, float]] = []

    @staticmethod
    def _force(fut: ReadbackFuture) -> None:
        try:
            fut.result()
        except Exception:  # noqa: BLE001 — error stays on the future
            pass

    def submit(self, fn: Callable[[], Tuple[Sequence[Any], Optional[Callable]]],
               interactive: bool = False) -> ReadbackFuture:
        device, finish = fn()
        fut = ReadbackFuture(device, finish)
        if not self.overlap:
            barrier(tuple(device))
            self._force(fut)
            return fut
        now = time.monotonic()
        # deadline-triggered close: windows older than deadline_s deliver
        # NOW — a quiet lane must not hold results hostage to the next
        # arrival or the depth overflow
        if self.deadline_s is not None:
            while self._ring and now - self._ring[0][1] > self.deadline_s:
                self._force(self._ring.pop(0)[0])
        if interactive:
            # deadline-class close: the interactive window never parks in
            # the dispatch-ahead ring — one readback sync, right here, at
            # the earliest point the device can deliver it
            self._force(fut)
            return fut
        self._ring.append((fut, now))
        if len(self._ring) > self.depth:
            self._force(self._ring.pop(0)[0])
        return fut

    def pending(self) -> int:
        return len(self._ring)

    def drain(self) -> None:
        """Force every still-pending window (end of the stream)."""
        ring, self._ring = self._ring, []
        for fut, _t in ring:
            self._force(fut)


# -- per-class QoS in-flight ledger (ISSUE 10) ---------------------------------


class QosLedger:
    """Per-deadline-class in-flight accounting: one global ledger on the
    server's WindowScheduler, one per DeviceLane.  Every ``enter`` must be
    paired with an ``exit`` — the in-flight rows are census gauges (the
    soak's flat-census assertion guards them), the cumulative rows feed the
    CLUSTER QOS / CLUSTER DEVICES wire views."""

    __slots__ = ("_lock", "frames", "ops", "nbytes", "waiting",
                 "dispatched_ops", "dispatched_frames",
                 "stream_inflight", "stream_dispatched")

    _CLASSES = ("interactive", "bulk")
    # device streams (ISSUE 18): which lane stream served a dispatch —
    # "interactive" only when the per-class fast path actually took it
    # (preemption armed AND the frame was interactive-class), "bulk"
    # otherwise, so disarmed runs book every dispatch on the bulk stream
    # exactly as the pre-stream ledger did
    _STREAMS = ("interactive", "bulk")

    def __init__(self):
        self._lock = threading.Lock()
        self.frames = {c: 0 for c in self._CLASSES}
        self.ops = {c: 0 for c in self._CLASSES}
        self.nbytes = {c: 0 for c in self._CLASSES}
        self.waiting = 0  # bulk frames parked at the admission gate
        self.dispatched_ops = {c: 0 for c in self._CLASSES}
        self.dispatched_frames = {c: 0 for c in self._CLASSES}
        self.stream_inflight = {s: 0 for s in self._STREAMS}
        self.stream_dispatched = {s: 0 for s in self._STREAMS}

    @classmethod
    def _cls(cls, qos_class: str) -> str:
        return qos_class if qos_class in cls._CLASSES else "bulk"

    def enter(self, qos_class: str, ops: int, nbytes: int = 0) -> None:
        c = self._cls(qos_class)
        with self._lock:
            self.frames[c] += 1
            self.ops[c] += ops
            self.nbytes[c] += nbytes
            self.dispatched_ops[c] += ops
            self.dispatched_frames[c] += 1

    def exit(self, qos_class: str, ops: int, nbytes: int = 0) -> None:
        c = self._cls(qos_class)
        with self._lock:
            self.frames[c] -= 1
            self.ops[c] -= ops
            self.nbytes[c] -= nbytes

    def wait_enter(self) -> None:
        with self._lock:
            self.waiting += 1

    def wait_exit(self) -> None:
        with self._lock:
            self.waiting -= 1

    def stream_enter(self, stream: str, ops: int) -> None:
        s = stream if stream in self._STREAMS else "bulk"
        with self._lock:
            self.stream_inflight[s] += ops
            self.stream_dispatched[s] += ops

    def stream_exit(self, stream: str, ops: int) -> None:
        s = stream if stream in self._STREAMS else "bulk"
        with self._lock:
            self.stream_inflight[s] -= ops

    def stream_rows(self) -> list:
        """``[b"STREAM", name, in-flight ops, dispatched ops]`` per device
        stream — appended to the CLUSTER QOS reply.  The leading b"STREAM"
        tag keeps the rows distinct from the per-class rows (whose row[0]
        is the class name) so pre-stream consumers' parsers — notably
        OccupancyLoadBalancer._qos_infl_ops — skip them unchanged."""
        with self._lock:
            return [
                [b"STREAM", s.encode(), self.stream_inflight[s],
                 self.stream_dispatched[s]]
                for s in self._STREAMS
            ]

    def census(self, prefix: str = "qos") -> dict:
        """Drain-to-zero gauges only (cumulative counters are exposed on the
        wire views instead, so flat-census assertions stay meaningful)."""
        with self._lock:
            out = {f"{prefix}_bulk_waiting": float(self.waiting)}
            for c in self._CLASSES:
                out[f"{prefix}_{c}_inflight_frames"] = float(self.frames[c])
                out[f"{prefix}_{c}_inflight_ops"] = float(self.ops[c])
                out[f"{prefix}_{c}_inflight_bytes"] = float(self.nbytes[c])
            for s in self._STREAMS:
                out[f"{prefix}_stream_{s}_inflight"] = float(
                    self.stream_inflight[s])
            return out

    def wire_row(self) -> list:
        """[in-flight ops i/b, in-flight bytes i/b, dispatched ops i/b] —
        the compact CLUSTER DEVICES per-lane projection."""
        with self._lock:
            return [
                self.ops["interactive"], self.ops["bulk"],
                self.nbytes["interactive"], self.nbytes["bulk"],
                self.dispatched_ops["interactive"],
                self.dispatched_ops["bulk"],
            ]


# -- per-device serving lanes (ISSUE 8: device-sharded slot ownership) --------
#
# With the slot table mapped onto the local device mesh, ONE flush lane is a
# structural bottleneck: frames routed to different devices would still
# serialize through a single StagingPool/FlushPipeline and a single IOStats
# ledger.  A DeviceLane is the per-chip lane — its own double-buffered
# staging pool, its own dispatch-ahead pipeline, its own stats — and LaneSet
# is the engine's registry of them, plus the cross-lane dispatch-concurrency
# accounting bench.py's config5d reports.

_replica_ns_per_item: Optional[float] = None


def set_replica_occupancy(ns_per_item: Optional[float]) -> Optional[float]:
    """Arm/disarm the CPU-replica device-occupancy model: with a value set,
    every ``DeviceLane.occupy(n_items)`` holds its lane for n_items *
    ns_per_item nanoseconds — modeling the per-chip compute time a real
    accelerator would serialize on its stream.  This exists ONLY for A/B
    measurement on chip-less containers (bench config5d; the same
    scaled-down-replica discipline as the PR 3 overlap-efficiency CPU
    number): the 1-device leg serializes the modeled occupancy through one
    lane, the N-device leg overlaps it across lanes, exactly as N chips
    would.  Disarmed (None, the default) a lane's occupy() costs one
    uncontended lock acquisition.  Returns the previous value."""
    global _replica_ns_per_item
    prev = _replica_ns_per_item
    _replica_ns_per_item = ns_per_item
    return prev


def replica_occupancy() -> Optional[float]:
    return _replica_ns_per_item


# every live LaneSet, weakly held: device-layer faults observed where no
# lane reference exists (ReadbackFuture) are attributed through here
_LANE_SETS: "weakref.WeakSet" = weakref.WeakSet()


def note_device_fault(dev_id: int, kind: str) -> bool:
    """Attribute one device fault to every registered lane for `dev_id`;
    returns True when any lane newly flipped to QUARANTINED."""
    tripped = False
    for ls in list(_LANE_SETS):
        lane = ls._lanes.get(dev_id)
        if lane is not None and lane.note_fault(kind):
            tripped = True
    return tripped


def note_device_ok(dev_id: int) -> None:
    """A readback on `dev_id` completed cleanly: reset its lanes'
    consecutive-fault streaks (quarantine itself clears only via probe)."""
    for ls in list(_LANE_SETS):
        lane = ls._lanes.get(dev_id)
        if lane is not None:
            lane.note_ok()


def quarantined_device_ids() -> set:
    """Device ids currently quarantined on ANY registered lane set."""
    out = set()
    for ls in list(_LANE_SETS):
        for dev_id, lane in ls._lanes.items():
            if lane.quarantined:
                out.add(dev_id)
    return out


class DeviceLane:
    """One device's serving lane: staging pool + flush pipeline + stats +
    the dispatch-occupancy gate (a mutex standing in for the device stream:
    dispatches bound for one device serialize, dispatches bound for
    different devices overlap)."""

    def __init__(self, device, laneset: "LaneSet", depth: int = 2):
        self.device = device
        self.dev_id = getattr(device, "id", 0)
        self.pool = StagingPool(depth=depth)
        self.pipeline = FlushPipeline(depth=depth)
        self.stats = device_stats(self.dev_id)
        # per-lane QoS ledger (ISSUE 10): queue depth / in-flight ops+bytes
        # per deadline class, read by CLUSTER DEVICES and the lane census
        self.qos = QosLedger()
        self._laneset = laneset
        self._gate = threading.Lock()
        # interactive device stream (ISSUE 18): its own gate + staging slot
        # + dispatch queue, so an armed interactive dispatch launches
        # without queuing behind the bulk stream's occupancy gate.  depth=1
        # on both — interactive windows never park (FlushPipeline forces
        # them at submit) and one staging slot matches one-at-a-time
        # latency-bound traffic.
        self._igate = threading.Lock()
        self.ipool = StagingPool(depth=1)
        self.ipipeline = FlushPipeline(depth=1)
        self._icond = threading.Condition(threading.Lock())
        self._iwaiting = 0  # interactive dispatches queued or in flight
        self.dispatches = 0
        self.preemptions = 0  # preempt points that actually yielded
        # device fault ledger (ISSUE 19): consecutive faults/timeouts trip
        # quarantine; a successful readback resets the streak, a probe
        # dispatch (server CLUSTER DEVPROBE) un-quarantines
        self.consec_faults = 0
        self.total_faults = 0
        self.quarantined = False
        self.quarantined_at = 0.0
        self.last_fault_kind = ""

    def note_fault(self, kind: str) -> bool:
        """Record one device-layer fault (kernel launch failure, readback
        timeout/error).  Trips QUARANTINED at the consecutive threshold;
        returns True when THIS call flipped the lane."""
        self.total_faults += 1
        self.consec_faults += 1
        self.last_fault_kind = kind
        if not self.quarantined and self.consec_faults >= _quarantine_after:
            self.quarantined = True
            self.quarantined_at = time.monotonic()
            if _obs._tracer is not None:
                cur = _obs.current_trace()
                if cur is not None:
                    now = time.monotonic()
                    cur.add_span("quarantine", now, now, device=self.dev_id)
            return True
        return False

    def note_ok(self) -> None:
        """A device operation completed cleanly: the consecutive-fault
        streak (NOT the quarantine flag — only a probe clears that) resets."""
        if self.consec_faults:
            self.consec_faults = 0

    def unquarantine(self) -> None:
        """Clear quarantine (the probe-passed path)."""
        self.quarantined = False
        self.consec_faults = 0

    def occupy(self, n_items: int = 0, qos_class: Optional[str] = None,
               nbytes: int = 0):
        """Context manager bounding one dispatch's device occupancy: holds
        the lane gate (per-device serialization) and, under the CPU-replica
        knob, the modeled per-chip compute time for `n_items` ops.  With
        `qos_class` given (the scheduler armed), the dispatch is accounted
        on the lane's per-class QoS ledger for its whole residency.  With
        preemption armed an interactive-class dispatch occupies the lane's
        INTERACTIVE stream (_igate) instead of the bulk gate."""
        return _LaneOccupancy(self, n_items, qos_class, nbytes)

    def submit(self, fn, interactive: bool = False):
        """Route one flush window to the serving stream's pipeline: armed
        interactive windows go through the interactive dispatch queue (so a
        parked bulk ring never delays forcing them), everything else —
        and everything when disarmed — through the bulk pipeline."""
        if interactive and _preempt:
            return self.ipipeline.submit(fn, interactive=True)
        return self.pipeline.submit(fn, interactive=interactive)

    def interactive_waiting(self) -> int:
        with self._icond:
            return self._iwaiting

    def _ienter(self) -> None:
        with self._icond:
            self._iwaiting += 1

    def _iexit(self) -> None:
        with self._icond:
            self._iwaiting -= 1
            if self._iwaiting <= 0:
                self._icond.notify_all()

    def preempt_point(self, timeout: float = 0.05) -> bool:
        """The inter-sub-window preemption point: with preemption armed and
        interactive dispatches queued or in flight on this lane, yield the
        (released) device for up to `timeout` seconds so their kernels
        launch before the next bulk sub-window re-occupies the stream.
        Called BETWEEN chunk dispatches — the caller holds no lane gate and
        no record locks here, and the wait is bounded, so the point can
        never deadlock the bulk stream against a stuck client.  Returns
        True when it actually yielded."""
        if not _preempt:
            return False
        yielded = False
        with self._icond:
            if self._iwaiting > 0:
                deadline = time.monotonic() + timeout
                while self._iwaiting > 0:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._icond.wait(left)
                yielded = True
        if yielded:
            self.preemptions += 1
        return yielded


class _LaneOccupancy:
    __slots__ = ("_lane", "_n", "_cls", "_nbytes", "_tcur", "_tmark",
                 "_stream", "_gate", "_prev_stream")

    def __init__(self, lane: DeviceLane, n_items: int,
                 qos_class: Optional[str] = None, nbytes: int = 0):
        self._lane = lane
        self._n = n_items
        self._cls = qos_class
        self._nbytes = nbytes
        self._tcur = None  # active FrameTrace (tracing armed only)
        self._tmark = 0.0
        # stream selection (ISSUE 18): interactive dispatches take the
        # lane's interactive stream only with preemption armed — disarmed,
        # everything serializes through the one bulk gate, the exact
        # pre-stream behavior
        if qos_class == "interactive" and _preempt:
            self._stream = "interactive"
            self._gate = lane._igate
        else:
            self._stream = "bulk"
            self._gate = lane._gate
        self._prev_stream = None

    def __enter__(self):
        # device dispatch chokepoint (ISSUE 19): consulted BEFORE any
        # ledger entry so an injected kernel-launch failure unwinds with
        # nothing to undo — __exit__ never runs when __enter__ raises
        plane = _net._fault_plane
        if plane is not None:
            try:
                plane.on_device_dispatch(self._lane.dev_id)
            except BaseException:
                self._lane.note_fault("kernel_launch")
                raise
        if self._cls is not None:
            self._lane.qos.enter(self._cls, self._n, self._nbytes)
        self._lane.qos.stream_enter(self._stream, self._n)
        if self._stream == "interactive":
            # visible to preempt_point from the moment the dispatch queues
            # on the interactive gate, not just once it holds it
            self._lane._ienter()
        if _obs._tracer is not None:
            self._tcur = _obs.current_trace()
        if self._tcur is not None:
            # `stage` = time queued behind the lane gate (ahead of the
            # chip); the occupancy hold itself becomes the `dispatch` span
            t0 = time.monotonic()
            self._gate.acquire()
            self._tmark = time.monotonic()
            self._tcur.add_span(
                "stage", t0, self._tmark,
                device=self._lane.dev_id, items=self._n,
                nbytes=self._nbytes, stream=self._stream,
            )
        else:
            self._gate.acquire()
        self._prev_stream = getattr(_stream_tls, "stream", None)
        _stream_tls.stream = self._stream
        self._lane._laneset._enter()
        self._lane.dispatches += 1
        return self._lane

    def __exit__(self, *exc):
        try:
            ns = _replica_ns_per_item
            if ns is not None and self._n > 0:
                time.sleep(self._n * ns * 1e-9)
        finally:
            if self._tcur is not None:
                self._tcur.add_span(
                    "dispatch", self._tmark, time.monotonic(),
                    device=self._lane.dev_id, items=self._n,
                    nbytes=self._nbytes, stream=self._stream,
                )
            self._lane._laneset._exit()
            _stream_tls.stream = self._prev_stream
            self._gate.release()
            if self._stream == "interactive":
                self._lane._iexit()
            self._lane.qos.stream_exit(self._stream, self._n)
            if self._cls is not None:
                self._lane.qos.exit(self._cls, self._n, self._nbytes)
        return False


class LaneSet:
    """The engine's per-device lane registry + cross-lane concurrency
    accounting (``peak_concurrent`` is bench config5d's dispatch-concurrency
    sub-metric: >1 proves frames routed to different devices actually
    dispatched in parallel)."""

    def __init__(self, devices: Sequence[Any], depth: int = 2):
        self._lanes = {
            getattr(d, "id", i): DeviceLane(d, self, depth=depth)
            for i, d in enumerate(devices)
        }
        self._lock = threading.Lock()
        self._active = 0
        self.peak_concurrent = 0
        # fault attribution registry (ISSUE 19): ReadbackFuture holds no
        # lane reference, so watchdog trips reach lanes through here
        _LANE_SETS.add(self)

    def lane(self, device) -> DeviceLane:
        dev_id = device if isinstance(device, int) else getattr(device, "id", 0)
        lane = self._lanes.get(dev_id)
        if lane is None:  # unknown device (placement grew): one-off lane
            with self._lock:
                lane = self._lanes.get(dev_id)
                if lane is None:
                    lane = self._lanes[dev_id] = DeviceLane(device, self)
        return lane

    def lanes(self) -> List[DeviceLane]:
        return list(self._lanes.values())

    def _enter(self) -> None:
        with self._lock:
            self._active += 1
            if self._active > self.peak_concurrent:
                self.peak_concurrent = self._active

    def _exit(self) -> None:
        with self._lock:
            self._active -= 1

    def active(self) -> int:
        with self._lock:
            return self._active

    def reset_concurrency(self) -> int:
        with self._lock:
            prev, self.peak_concurrent = self.peak_concurrent, 0
            return prev

    def census(self) -> dict:
        """Flat gauges for ResourceCensus: staging slots and in-flight
        dispatch count must return to baseline after a storm."""
        out = {"lanes": len(self._lanes), "active_dispatches": self.active()}
        for dev_id, lane in sorted(self._lanes.items()):
            out[f"lane{dev_id}_staging_slots"] = lane.pool.slot_count()
            out[f"lane{dev_id}_istaging_slots"] = lane.ipool.slot_count()
            out[f"lane{dev_id}_iwaiting"] = lane.interactive_waiting()
            # quarantine state (ISSUE 19): both must return to 0 after a
            # fault storm recovers (probe passed / evacuation complete)
            out[f"lane{dev_id}_quarantined"] = int(lane.quarantined)
            out[f"lane{dev_id}_consec_faults"] = lane.consec_faults
            # per-lane QoS in-flight (ISSUE 10): must drain to 0 at quiesce
            for k, v in lane.qos.census(prefix=f"lane{dev_id}_qos").items():
                out[k] = v
        return out

    def clear(self) -> None:
        for lane in self._lanes.values():
            lane.pool.clear()
            lane.ipool.clear()
            lane.pipeline.drain()
            lane.ipipeline.drain()

"""TpuServer: asyncio RESP server fronting one Engine ("the sidecar").

Role parity: the reference has no server (Redis is the server); the TPU
build's data plane lives in THIS process next to the accelerator, so the
server is the piece that takes the Redis role for remote clients while the
Engine takes the command-execution role (SURVEY.md §7.1 L4').

Connection discipline mirrors the reference's pipeline
(client/handler/RedisChannelInitializer.java:74-108): framed RESP in, ordered
execution per connection (the CommandsQueue FIFO guarantee), replies written
in arrival order, pubsub push frames interleaved from a writer queue.
Engine calls execute on a bounded thread pool so the event loop never blocks
on device dispatch.

Overlap plane (core/ioplane, ISSUE 3): a frame whose replies carry device
results no longer blocks its read loop on the D2H readback — the frame's
grouped force runs as a readback future drained by the per-connection writer
task's completion queue (FIFO: reply order and RESP framing are untouched),
while the read loop stages and dispatches the NEXT frame.  Frames without
device results flush immediately.  `--no-overlap` restores the serial
stage->dispatch->fetch shape for A/B measurement.

QoS plane (server/scheduler.py, ISSUE 10): between frame parsing and
dispatch, every frame is classified into a deadline class (interactive vs
bulk), charged against its tenant's token bucket (over-budget = -BUSY shed
before dispatch), and admitted class-aware — interactive on a reserved
worker slice, bulk behind a bounded admission gate.  `--no-qos` /
`RTPU_NO_QOS=1` restores pure arrival-order dispatch, bit-identically.
"""
from __future__ import annotations

import asyncio
import gc
import socket
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from contextlib import ExitStack
from typing import Any, Dict, List, Optional, Tuple

from redisson_tpu.core import ioplane
from redisson_tpu.core import coalesce as _coalesce
from redisson_tpu.core.coalesce import (
    COALESCIBLE_BLOB_VERBS, KNN_FORM, STACK_PLANES, plan_frame_runs,
    plan_subwindows, plan_waves, serial_plan, stacked_row_bucket, wave_entry,
)
from redisson_tpu.core.engine import Engine
from redisson_tpu.core.kernels import MIN_BUCKET
from redisson_tpu.net import client as _net
from redisson_tpu.net import resp
from redisson_tpu.net.resp import ProtocolError, RespError
from redisson_tpu.observe import trace as _obs
from redisson_tpu.server import scheduler as _sched
from redisson_tpu.server.registry import CommandContext, Encoded, LazyReply, REGISTRY


class _PendingFrame:
    """A frame whose readback is still in flight (overlap plane): the
    per-connection writer task awaits `fut` (the executor job forcing the
    frame's LazyReplies), then encodes and writes the replies — while the
    connection's read loop is already staging and dispatching the NEXT
    frame.  `proto` is the connection's negotiated protocol AT DISPATCH
    time: a later frame's HELLO must not re-encode earlier replies.
    `trace` is the frame's FrameTrace when tracing is armed (the writer
    task closes its `reply` span at write time), else None."""

    __slots__ = ("results", "fut", "proto", "trace")

    def __init__(self, results: list, fut, proto: int, trace=None):
        self.results = results
        self.fut = fut
        self.proto = proto
        self.trace = trace

    def encoded(self) -> bytes:
        return _encode_frame(self.results, self.proto)


class _TracedEncoded:
    """Pre-encoded frame bytes carrying their FrameTrace (tracing ARMED
    only — disarmed frames enqueue plain bytes, exactly as before): the
    writer task writes `data` and closes the trace's `reply` span, making
    the trace total the true client-observable latency.  Dispatch ends and
    `reply.encode` is recorded HERE; `put_at` (encode done, about to be
    queued) opens the frame's `reply.wait` for the writer task to close."""

    __slots__ = ("data", "trace", "put_at")

    def __init__(self, results: list, proto: int, trace):
        trace.mark_dispatched()
        self.data = _encode_frame(results, proto)
        self.put_at = time.monotonic()
        trace.add_span("reply.encode", trace.dispatched_at, self.put_at)
        self.trace = trace


# bound on the graceful drain after SIGTERM/SIGINT (serve_until_signal)
_STOP_DRAIN_S = 5.0

# The most bytes ONE frame takes of what has arrived on a connection.  A
# frame is what the stream has buffered when the read loop looks — one read
# of up to this many bytes, never a byte waited for — so a pipelined request
# that has landed is parsed, planned, dispatched and fetched once (fanout-4:
# 326 commands over 64 tenants, 198 KB in one sendall, were 3.8 frames at
# 64 KiB a read: PERF.md section 6, PR 29).  The cap is what one recv of
# asyncio's selector transport brings at most (256 KiB), so a burst that has
# landed whole is taken whole, and it bounds what a client that pipelines
# without end can make of one plan and how long its first reply is held: the
# parser returns whole commands only, so a longer pipeline is cut at a
# command boundary and the rest is the next frame.  One command larger than
# this (a 1.2 MB bulk flush) is buffered by the parser across reads, as it
# always was.  Also the stream's `limit`, so the transport is not paused
# under a burst the next read takes whole.
_FRAME_CAP = 256 * 1024

# The collector's thresholds for a server PROCESS (``main``; an embedded
# ServerThread leaves its host's collector alone).  What stays on this heap
# is records — one tracked object a document — and what comes and goes is a
# frame's lists, which live as long as the frame is in flight: tens of
# milliseconds, several young collections at CPython's (700, 10, 10) under
# four connections' frames, so each frame's objects were promoted to the
# oldest generation before they died, counted there as growth, and a
# quarter of the heap's worth of them set off a full collection — a walk
# over every record, every few seconds, in a window that writes nothing (a
# million documents: ~0.3 s a walk on the chip's host, the slowest frame
# of every ann-batch window; PERF.md section 6, PR 32).  The first threshold counts allocations less frees:
# at 50,000 the frames in flight never reach it, their objects die young by
# reference count, a young collection comes when that many objects have
# STAYED, and the heap's growth counts what stays; full collections still
# come, every 100 middle ones, when the heap has grown by a quarter.
GC_THRESHOLDS = (50_000, 20, 100)

# fixed -TRYAGAIN texts (ISSUE 19): byte-identical whichever layer detects
# the fault and whether the chaos plane is armed or not
_DEVICE_FAULT_TRYAGAIN = "TRYAGAIN device fault during dispatch; retry"


def _quarantined_tryagain(dev_id: int) -> str:
    return f"TRYAGAIN device {dev_id} quarantined; retry after evacuation"


def _on_worker(trace, to: str, fn, *args):
    """The one entry of a frame's work on a worker thread.  `trace` (tracing
    armed only) closes the `hop` its submit site opened and is this
    thread's current trace while `fn` runs, so the lane, kernel and
    readback spans recorded below land on the right frame; its last line
    opens the `wake` the loop closes after its await."""
    if trace is None:
        return fn(*args)
    trace.hopped(to)
    _obs.set_current(trace)
    try:
        return fn(*args)
    finally:
        _obs.clear_current()
        trace.left_at = time.monotonic()


# POINT commands — a single-item BF.ADD / BF.EXISTS — by the verb's bytes as
# clients write them (any other spelling takes the serial path, which ends
# in the same window function with one member), to the verb as the registry
# names it and as hooks and counters do.
_POINT_VERBS = {
    spelling: (name.encode(), name)
    for name in ("BF.ADD", "BF.EXISTS")
    for spelling in (name.encode(), name.lower().encode())
}
# the most members one window takes: the smallest bucket of a batch, so a
# window of any size runs the one program a lone command runs
_POINT_WINDOW_MAX = MIN_BUCKET


class _PointMember:
    """One point command waiting in its record's open window: the
    connection, the command, the future its frame awaits on the loop, and
    its FrameTrace (tracing armed) or None."""

    __slots__ = ("ctx", "cmd", "fut", "trace")

    def __init__(self, ctx, cmd, fut, trace):
        self.ctx = ctx
        self.cmd = cmd
        self.fut = fut
        self.trace = trace


def _resolve_point_window(members, replies, error=None) -> None:
    """On the loop, ONE call a window: every member's frame goes on with
    its reply, or dies with `error` (a stopping pool).  A member whose
    connection went away while it waited (its future cancelled) drops its
    answer."""
    if error is not None:
        for m in members:
            if not m.fut.done():
                m.fut.set_exception(error)
        return
    for m, reply in zip(members, replies):
        if not m.fut.done():
            m.fut.set_result(reply)


def _is_slow(cmd) -> bool:
    """Whether a parsed command may park its worker (_SLOW_COMMANDS)."""
    return (
        isinstance(cmd, list) and bool(cmd)
        and isinstance(cmd[0], (bytes, bytearray))
        and bytes(cmd[0]).upper() in _SLOW_COMMANDS
    )


class _Laneless:
    """What stands where a dispatch has no lane to occupy (no placement, or
    keys on several lanes): nothing is held; with tracing armed it records
    the `dispatch` span the lane gate records otherwise."""

    __slots__ = ("_cur", "_t0")

    def __enter__(self):
        self._cur = _obs.current_trace() if _obs._tracer is not None else None
        if self._cur is not None:
            self._t0 = time.monotonic()

    def __exit__(self, *exc):
        if self._cur is not None:
            self._cur.add_span("dispatch", self._t0, time.monotonic())
        return False


def _drain_index(search) -> None:
    """A writing frame's last worker job: what it left dirty under a search
    index is indexed (a `dispatch` span of its own on the frame's trace)."""
    with _Laneless():
        search.drain_all()


def _force_lazies(results: list, server) -> None:
    """Materialize every LazyReply of a frame in place.  Device-form lazies
    are fetched with one grouped fetch a device (the whole frame pays ~1
    device->host sync a lane); callable-form lazies force individually.  A
    fault surfacing at force time (watchdog timeout, kernel-launch failure)
    is that reply's error (_error_reply: retryable -TRYAGAIN), never a
    wedged writer (ISSUE 19)."""
    from redisson_tpu.server.registry import gather_lazy_device_results

    dev_idx = [
        i for i, r in enumerate(results)
        if isinstance(r, LazyReply) and r.device is not None
    ]
    if dev_idx:
        try:
            host_vals = gather_lazy_device_results([results[i] for i in dev_idx])
        except ioplane.LaneWatchdogTimeout as e:
            # the grouped drain tripped the armed lane watchdog: the
            # frame's device-form lazies rode ONE hung transfer — fail
            # them all retryable instead of re-forcing through the
            # same wedged device one by one
            for i in dev_idx:
                results[i] = server._error_reply(e)
            dev_idx, host_vals = [], None
        except Exception:  # noqa: BLE001 — grouped path failed; force singly
            host_vals = None
        if host_vals is not None:
            for i, vals in zip(dev_idx, host_vals):
                try:
                    results[i] = results[i].finish(vals)
                except Exception as e:  # noqa: BLE001 — per-reply isolation
                    results[i] = server._error_reply(e)
    for i, r in enumerate(results):
        if isinstance(r, LazyReply):
            try:
                results[i] = r.force()
            except Exception as e:  # noqa: BLE001 — per-reply isolation
                results[i] = server._error_reply(e)


# Commands whose handlers may PARK the worker thread (blocking verbs hold it
# for up to their timeout; OBJCALL runs arbitrary object methods incl.
# poll_blocking).  Dispatched on the wide slow pool so the per-connection
# fast pool never starves.
_SLOW_COMMANDS = frozenset(
    b.encode() for b in (
        "OBJCALL", "OBJCALLM", "OBJCALLMA", "OBJCALLV", "TXEXEC", "EXEC",
        "BLPOP", "BRPOP", "BLMOVE",
        "BRPOPLPUSH", "BZPOPMIN", "BZPOPMAX", "BLMPOP", "BZMPOP",
        "XREAD", "XREADGROUP", "WAIT",
    )
)


class TpuServer:
    def __init__(
        self,
        engine: Optional[Engine] = None,
        host: str = "127.0.0.1",
        port: int = 6390,
        password: Optional[str] = None,
        checkpoint_path: Optional[str] = None,
        mode: str = "standalone",
        workers: int = 4,
        tls_cert_file: Optional[str] = None,
        tls_key_file: Optional[str] = None,
        tls_ca_file: Optional[str] = None,
        users: Optional[Dict[str, str]] = None,
        overlap: Optional[bool] = None,
        devices: Optional[Any] = None,
        qos: Optional[bool] = None,
        dispatch_ahead: Optional[int] = None,
        journal_dir: Optional[str] = None,
        advertise_host: Optional[str] = None,
    ):
        self.engine = engine if engine is not None else Engine()
        # device-sharded serving (ISSUE 8): `devices` maps the 16384-slot
        # table onto the local device mesh — an int takes the first N local
        # devices, "all" takes every one.  None (default) = the historical
        # single-device server.  An engine whose placement is already
        # enabled (embedded callers) is left as configured.
        if devices is not None and self.engine.placement is None:
            n = None if devices in ("all", "ALL") else int(devices)
            self.engine.enable_placement(n_devices=n)
        # overlapped device I/O plane (core/ioplane): frames with device-form
        # lazy replies hand their readback to the per-connection writer task
        # instead of blocking the read loop — upload/kernel of frame N+1
        # overlaps the D2H readback of frame N.  None = follow the process-
        # global switch; False = the serial A/B reference (--no-overlap).
        from redisson_tpu.core import ioplane as _ioplane

        self.overlap = _ioplane.overlap_enabled() if overlap is None else bool(overlap)
        # dispatch-ahead bound: at most this many frames may sit between
        # "dispatched" and "replies written" per connection (bounds device
        # memory held by un-drained readbacks).  Configurable (ISSUE 10
        # satellite): tpu-server --dispatch-ahead N / CONFIG SET
        # dispatch-ahead — applied to connections opened AFTER the change
        # (each connection sizes its semaphore at accept time); default 2.
        self.readback_ahead = (
            2 if dispatch_ahead is None else max(1, int(dispatch_ahead))
        )
        # deadline-aware window scheduling + per-tenant QoS (ISSUE 10,
        # server/scheduler.py): classify frames interactive/bulk, charge
        # per-tenant token buckets, shed over-budget frames with -BUSY
        # before dispatch.  None = follow the process-global switch
        # (RTPU_NO_QOS=1 disarms); shedding itself is additionally opt-in
        # via CONFIG SET qos-tenant-rate (default unlimited).
        self.scheduler = _sched.WindowScheduler(enabled=qos)
        if self.scheduler.bulk_slots <= 0:
            # reserve one dispatch slot for interactive traffic: bulk-class
            # frames across ALL connections share workers-1 admission slots
            self.scheduler.bulk_slots = max(1, workers - 1)
        self._bulk_gate: Optional[asyncio.Semaphore] = None
        self._bulk_gate_n = 0
        self.host = host
        self.port = port
        # the address this node IS in cluster views (ISSUE 16): a cross-host
        # node binds 0.0.0.0 but is named in views/journals/READY by its
        # routable address — without the split, owns_slot never matches and
        # the node MOVED-bounces its own slots forever.  None = bind host
        # (the single-machine default, where they coincide).
        self.advertise_host = advertise_host
        self.password = password
        # ACL users (username -> password): the reference's AUTH user pass
        # (BaseConnectionHandler.java:59-122).  "default" aliases `password`.
        self.users: Dict[str, str] = dict(users or {})
        # TLS: cert+key enable the listener's TLS; ca_file additionally
        # REQUIRES client certificates (mTLS) and pins the trust root for
        # this node's OUTGOING links (migration/replication) so a TLS
        # cluster's bus speaks TLS end to end.
        self.tls_cert_file = tls_cert_file
        self.tls_key_file = tls_key_file
        self.tls_ca_file = tls_ca_file
        self.checkpoint_path = checkpoint_path
        self.mode = mode
        self.node_id = uuid.uuid4().hex
        self.started_at = time.time()
        self.stats = {"connections": 0, "commands": 0, "frames": 0,
                      "errors": 0, "sheds": 0,
                      # read-scaling plane (ISSUE 17): replica-served keyed
                      # reads, reads refused as too stale (REPLSTATE
                      # MAXSTALE), reads bounced to the master (missing
                      # READONLY / fenced slot)
                      "replica_reads": 0, "replica_redirects_stale": 0,
                      "replica_fallbacks": 0}
        # observability (utils/metrics.py): per-command timers + counters,
        # rendered by the METRICS command; hooks = NettyHook-analog SPI
        from redisson_tpu.utils.metrics import MetricsHook, MetricsRegistry

        self.metrics = MetricsRegistry()
        self.hooks = [MetricsHook(self.metrics)]
        self.metrics.gauge("keys", lambda: len(self.engine.store))
        self.metrics.gauge("connections", lambda: self.stats["connections"])
        # tracing plane (ISSUE 12, observe/trace.py): the process tracer —
        # disarmed by default (zero-cost guards); CONFIG SET trace-enabled /
        # RTPU_TRACE=1 arms it.  Stage-duration histograms feed THIS
        # registry (stage.* timers) so prometheus_text exports breakdowns.
        self.tracer = _obs.TRACER
        self.tracer.registry = self.metrics
        self.metrics.gauge(
            "trace_ring_entries",
            lambda: self.tracer.census()["trace_ring_entries"],
        )
        self.metrics.gauge(
            "trace_inflight",
            lambda: self.tracer.census()["trace_inflight"],
        )
        # the host's pauses (armed only; monotone, so a scraper's
        # after-minus-before is the window's): collections, and turns of
        # this server's event loop of 5 ms or more (LoopSelector.select)
        self.metrics.gauge(
            "host_gc_pause_seconds_total", lambda: self.tracer.gc_pause_s
        )
        self.metrics.gauge(
            "host_gc_long_pauses_total", lambda: self.tracer.gc_long_pauses
        )
        self.metrics.gauge(
            "host_loop_stall_seconds_total", lambda: self.tracer.loop_stall_s
        )
        self.metrics.gauge(
            "host_loop_long_stalls_total",
            lambda: self.tracer.loop_long_stalls,
        )
        # the event loop's account (always on; monotone): its turns and the
        # seconds it spent outside `select`, counted by the selector this
        # server hands its own loop (new_event_loop; a server inside
        # somebody else's loop reads 0), and the frames the loop served.
        # Whose CPU: the loop thread's, this server's three pools' threads'
        # and the whole process's (with the runtime's and XLA's threads)
        # since start_async, read from the threads' own clocks AT THE
        # SCRAPE — nothing on the hot path — beside the uptime a reader
        # divides them by.  Busy less CPU of the loop is time it held a
        # turn open without running: the interpreter lock, or a blocking
        # call.
        self._loop_selector: Optional[_obs.LoopSelector] = None
        self._own_loop = None
        self._loop_thread: Optional[threading.Thread] = None
        self._up_at = time.monotonic()
        self._loop_cpu0 = self._process_cpu0 = 0.0
        self._cpu_seen = {"loop": 0.0, "worker": 0.0}
        self._cpu_lock = threading.Lock()
        for series, field in (("turns", "turns"), ("busy_seconds", "busy_s")):
            self.metrics.gauge(
                f"host_loop_{series}_total",
                lambda field=field: getattr(self._loop_selector, field, 0),
            )
        self.metrics.gauge("frames_served_total", lambda: self.stats["frames"])
        for who in self._cpu_seen:
            self.metrics.gauge(
                f"host_{who}_cpu_seconds_total",
                lambda who=who: self._threads_cpu_s(who),
            )
        self.metrics.gauge(
            "host_process_cpu_seconds_total",
            lambda: time.process_time() - self._process_cpu0,
        )
        self.metrics.gauge(
            "host_uptime_seconds_total",
            lambda: time.monotonic() - self._up_at,
        )
        # rows the bank kernels were sent against rows the device walked for
        # them (core/kernels.py count_rows): what a bucket's padding costs
        from redisson_tpu.core import kernels as _K

        self.metrics.gauge(
            "kernel_rows_valid_total", lambda: _K.rows_counted()[0]
        )
        self.metrics.gauge(
            "kernel_rows_issued_total", lambda: _K.rows_counted()[1]
        )
        # what the fixed shapes of a multi-tenant frame pad (always on, as
        # the rows above): planes a fused run named against planes its
        # dispatch stacked (core/coalesce.py), and bytes the frame's replies
        # are made from against bytes the grouped fetch brought
        # (core/ioplane.py gather_device_results)
        self.metrics.gauge(
            "coalesce_planes_asked_total", lambda: _coalesce.planes_counted()[0]
        )
        self.metrics.gauge(
            "coalesce_planes_stacked_total", lambda: _coalesce.planes_counted()[1]
        )
        # how often the coalescer engages: commands offered to it (the
        # buckets' commands) against commands that rode a stacked dispatch
        self.metrics.gauge(
            "coalesce_cmds_offered_total", lambda: _coalesce.cmds_counted()[0]
        )
        self.metrics.gauge(
            "coalesce_cmds_fused_total", lambda: _coalesce.cmds_counted()[1]
        )
        # the search plane (always on): documents indexed at a write against
        # keys any keyspace scan walked for an index (services/search.py),
        # and the device KNN's query vectors, the bucket slots they were
        # padded to and live rows x queries (services/vector.py)
        from redisson_tpu.services import search as _search
        from redisson_tpu.services import vector as _vector

        self.metrics.gauge(
            "search_docs_indexed_total", lambda: _search.search_counted()[0]
        )
        self.metrics.gauge(
            "search_scan_keys_total", lambda: _search.search_counted()[1]
        )
        self.metrics.gauge("knn_queries_total", lambda: _vector.knn_counted()[0])
        self.metrics.gauge(
            "knn_query_slots_total", lambda: _vector.knn_counted()[1]
        )
        self.metrics.gauge(
            "knn_rows_scored_total", lambda: _vector.knn_counted()[2]
        )
        # members of stacked KNN dispatches, and those of them answered from
        # a plan the wave shares, as bytes (verbs/modules.py coalesce_knn_run)
        self.metrics.gauge(
            "knn_wave_cmds_total", lambda: _coalesce.knn_wave_counted()[0]
        )
        self.metrics.gauge(
            "knn_wave_shared_cmds_total", lambda: _coalesce.knn_wave_counted()[1]
        )
        # point commands (single-item BF.ADD / BF.EXISTS; always on): answered
        # by verb, windows served, device dispatches issued for them, rows
        # those were handed against rows asked (core/kernels.py count_point_*)
        self.metrics.gauge(
            "point_cmds_total", lambda: sum(_K.point_counted()["cmds"].values())
        )
        for verb in _K.POINT_VERBS:
            self.metrics.gauge(
                f"point_cmds_{verb.lower()}_total",
                lambda verb=verb: _K.point_counted()["cmds"][verb],
            )
        for series in ("windows", "dispatches", "rows_valid", "rows_issued"):
            self.metrics.gauge(
                f"point_{series}_total",
                lambda series=series: _K.point_counted()[series],
            )
        self.metrics.gauge(
            "gather_bytes_owed_total", lambda: ioplane.gather_bytes_counted()[0]
        )
        self.metrics.gauge(
            "gather_bytes_fetched_total",
            lambda: ioplane.gather_bytes_counted()[1],
        )
        # orphaned RESP3 pushes (ISSUE 12 satellite bugfix): the process-
        # global drop counter was census-only — a fleet scrape could never
        # see a desync-avoided push drop.  Now a first-class gauge.
        from redisson_tpu.net.client import dropped_push_count

        self.metrics.gauge("dropped_pushes", dropped_push_count)
        # QoS plane gauges (ISSUE 10): shed totals + per-class in-flight —
        # the census variants of the same numbers live in scheduler.census()
        self.metrics.gauge("qos_shed_ops", lambda: self.scheduler.shed_ops)
        self.metrics.gauge(
            "qos_shed_frames", lambda: self.scheduler.shed_frames
        )
        self.metrics.gauge(
            "qos_interactive_inflight_ops",
            lambda: self.scheduler.ledger.ops["interactive"],
        )
        self.metrics.gauge(
            "qos_bulk_inflight_ops",
            lambda: self.scheduler.ledger.ops["bulk"],
        )
        self.metrics.gauge(
            "qos_bulk_waiting", lambda: self.scheduler.ledger.waiting
        )
        # cluster_view: [(slot_from, slot_to, host, port, node_id)] when this
        # node is part of a cluster (set by the topology/launcher, L3')
        self.cluster_view: List[Tuple[int, int, str, int, str]] = []
        # highest accepted SETVIEW fencing token (coordinator-HA discipline:
        # a stale ex-leader's late view write carries a lower token and is
        # rejected; see registry.py CLUSTER SETVIEW TOKEN)
        self.view_epoch: int = 0
        # live resharding state (the MIGRATING/IMPORTING window of the
        # reference's slot-migration protocol, cluster/ClusterConnectionManager
        # .java:358-450 checkSlotsMigration + RedisExecutor ASK handling):
        #   migrating_slots: slot -> target "host:port" (this node drains it)
        #   importing_slots: slot -> source "host:port" (this node receives)
        self.migrating_slots: Dict[int, str] = {}
        self.importing_slots: Dict[int, str] = {}
        # crash-recovery fence (ISSUE 6): slots whose journaled migration
        # was in flight when THIS process last died (rearm_recovery at
        # boot).  Until resume_migrations settles the journal, every keyed
        # command in such a slot answers TRYAGAIN — serving the restored
        # (possibly stale) copies would fork the record lineage against the
        # copies the pre-crash drain already shipped to the target, and one
        # fork's acked writes would silently lose the version race when the
        # resumed drain reconciles them.  SETSLOT STABLE clears it.
        self.recovering_slots: Dict[int, str] = {}
        # per-slot migration fencing (ISSUE 4 journaled migrations): the
        # highest EPOCH this node accepted for each slot's SETSLOT/
        # MIGRATESLOTS traffic.  A resumed coordinator re-issues its
        # journaled epoch (== accepted: idempotent redo), while a STALE
        # coordinator resuming after a NEWER migration touched the slot
        # carries a lower epoch and is rejected (STALEEPOCH) — the fencing
        # that makes journal replay safe under coordinator races.
        self.slot_epochs: Dict[int, int] = {}
        # the ACTIVE journaled epoch per MIGRATING slot (set when SETSLOT
        # MIGRATING carries EPOCH, popped on STABLE): the drain stamps it
        # onto every outgoing IMPORTRECORDS so the target journals the
        # batch before acking.  Distinct from slot_epochs, which is the
        # fencing high-water mark and survives past STABLE — stamping from
        # it would mis-attribute a later unjournaled migration's batches
        # to a settled journal.
        self.migrating_epochs: Dict[int, int] = {}
        # import-side journal plane (ISSUE 13): the shared journal
        # directory (``--journal-dir`` / ClusterSupervisor) this node
        # writes its ImportJournals into, plus the OPEN journals by epoch —
        # settled on the migration's final SETSLOT STABLE, replayed by
        # migration.rearm_recovery after a crash
        self.journal_dir = journal_dir
        self._import_journals: Dict[int, Any] = {}
        self._import_journal_lock = threading.Lock()
        # read-scaling gauges (ISSUE 17): METRICS / METRICS CLUSTER rows +
        # ResourceCensus feed — replica-side attribution (the replica both
        # serves the read and refuses the stale/unarmed one)
        self.metrics.gauge(
            "replica_reads", lambda: self.stats["replica_reads"]
        )
        self.metrics.gauge(
            "replica_redirects_stale",
            lambda: self.stats["replica_redirects_stale"],
        )
        self.metrics.gauge(
            "replica_fallbacks", lambda: self.stats["replica_fallbacks"]
        )
        # -- cluster / replication role (server/replication.py) -------------
        self.role = "master"  # "master" | "replica"
        self.master_address: Optional[str] = None
        # bounded-staleness stamp (ISSUE 17): the highest sweep-cut offset
        # this REPLICA applied (REPLPUSH payload stamp or REPLPING), the
        # master wall-clock of that cut, and the LOCAL monotonic receipt
        # time — staleness_ms is measured against the local receipt so
        # cross-host clock skew can never fake freshness
        self.repl_applied_offset = 0
        self.repl_applied_ts = 0.0
        self.repl_applied_at: Optional[float] = None
        # set on REPLICAOF NO ONE promotion: the master this node replicated
        # before — the ROLE breadcrumb coordinators use to adopt
        # half-finished failovers (registry cmd_role / cmd_replicaof)
        self.promoted_from: Optional[str] = None
        self._replication = None  # lazy ReplicationSource (master side)
        self._repl_lock = threading.Lock()
        # REPLPUSHSEG staging: xfer_id -> [chunk slots, last-touch monotonic]
        # (verbs/admin.py cmd_replpushseg; census counts live entries)
        self._repl_xfers: Dict[str, list] = {}
        self._repl_xfers_lock = threading.Lock()
        # resumable REPLSNAPSHOT staging (ISSUE 16): xfer_id ->
        # [blob, chunk_bytes, last-touch monotonic] — one immutable
        # serialized cut a replica FETCHes by offset; reaped by staleness
        # (verbs/admin.py cmd_replsnapshot; census counts live entries)
        self._snap_stages: Dict[str, list] = {}
        self._snap_lock = threading.Lock()
        self._snap_seq = 0
        # chaos pause gate (SIGSTOP analog): cleared = every command handler
        # parks before dispatch, so the node stops answering (pings included)
        # WITHOUT closing connections — the hung-but-accepting failure mode
        # that only command-timeout detectors can catch
        self._pause_gate = threading.Event()
        self._pause_gate.set()
        self._client_ids = iter(range(1, 1 << 62))
        # server-assisted client tracking (tracking/table.py): per-connection
        # read-key memory + RESP3 invalidation pushes on write/expiry/
        # FLUSHALL/slot handoff.  Always constructed (cheap); the dispatch
        # hook costs one int load while no client has tracking on.
        from redisson_tpu.tracking.table import TrackingTable

        self.tracking = TrackingTable(self)
        self.metrics.gauge("tracking_keys", self.tracking.tracked_key_count)
        self.metrics.gauge(
            "tracking_overflow_evictions",
            lambda: self.tracking.stats["overflow_evictions"],
        )
        self.metrics.gauge(
            "tracking_pushes", lambda: self.tracking.stats["pushes"]
        )
        # expiry invalidation: a key the TTL reaper (or a lazy-expiry read)
        # drops must invalidate near caches exactly like a DEL would
        self.engine.store.on_expired = self.tracking.note_expired
        # embedding-bank residency gauges (ISSUE 11, the first HBM-ledger
        # brick): bank count + device bytes, 0 until FT.CREATE ... VECTOR
        # builds one (the search service is lazily constructed — don't
        # force it just to report zero)
        # ONE labeled gauge family for the whole embedding-bank census —
        # totals (ftvec_banks / ftvec_device_bytes / ftvec_index_bytes, the
        # ISSUE 11/14 rows) AND the per-device HBM-ledger labels
        # ftvec_*_bytes_dev<N> (ISSUE 15), which exist only while that
        # device holds bank bytes, so DROPINDEX zeroes every shard's row.
        # One family on purpose: the census walks every index/bank/shard,
        # and per-row scalar gauges would re-run that walk once per row
        # per scrape.
        self.metrics.multi_gauge("ftvec", self._ftvec_census)
        # per-device residency over ALL record kinds (ISSUE 19 satellite):
        # record_bytes_dev<N>[_<kind>] rows from one store scan per scrape —
        # same one-family discipline as ftvec, rows vanish with the bytes
        self.metrics.multi_gauge("devbytes", self._device_bytes_census)
        # tiered-HBM residency plane (ISSUE 20): per-device per-tier byte
        # ledgers (residency_bytes_dev<N>_{hot,warm,cold}) plus the
        # promotion/demotion/fault-in counters — rows exist only while the
        # manager is armed and the tier holds bytes, so DEL drains them
        self.metrics.multi_gauge("residency", self._residency_census)
        # OBJCALL handle cache (ordered for LRU eviction; see registry)
        from collections import OrderedDict

        self._objcall_handles: "OrderedDict" = OrderedDict()
        self._objcall_handles_lock = threading.Lock()
        self._pool = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="rtpu-srv")
        # reserved interactive dispatch capacity (ISSUE 10): frames the
        # scheduler classifies interactive run HERE, so a bulk flood holding
        # every shared worker can never queue ahead of them (the thread-pool
        # face of "interactive ops are admitted into the window first").
        # FULL --workers width on purpose: this is isolation, not a
        # reservation — with QoS armed by default, small-frame-heavy
        # deployments (and sharded interactive frames' per-device fan-out)
        # must keep their historical dispatch concurrency (threads spawn
        # lazily, so an all-bulk workload never pays for these)
        self._workers = workers
        self._qos_pool = ThreadPoolExecutor(
            max_workers=max(2, workers), thread_name_prefix="rtpu-qos"
        )
        # OBJCALL may run arbitrarily-blocking object methods (blocking
        # queues, latches); isolate them on a wide pool so parked callers
        # can't starve the data-plane workers (the reference marks such
        # commands isBlockingCommand and gives them dedicated connections)
        self._slow_pool = ThreadPoolExecutor(max_workers=64, thread_name_prefix="rtpu-slow")
        self._closing = False
        # open windows of point commands, by record name as the wire gave
        # it: the members that joined since the record's ONE job — submitted
        # or at work — took its window (_join_point_window); no entry, no job
        self._point_open: Dict[bytes, List[_PointMember]] = {}
        self._point_lock = threading.Lock()
        # EXEC transactions serialize (see cmd_exec: handlers may take record
        # locks beyond the precomputed key set)
        self._exec_mutex = threading.Lock()
        self._server: Optional[asyncio.AbstractServer] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._writers: set = set()
        self._local_client = None

    # -- registry support ----------------------------------------------------

    def config_view(self) -> Dict[str, Any]:
        """CONFIG GET surface: the node's live knob table (read side)."""
        ev = self.engine._eviction
        cfg = self.engine.config
        view = {
            "port": self.port,
            "mode": self.mode,
            "role": self.role,
            "node-id": self.node_id,
            "checkpoint-path": self.checkpoint_path or "",
            "tls": bool(self.tls_cert_file),
            # before the scheduler lazily starts, report what it WILL use
            "eviction-min-delay": ev.min_delay if ev else cfg.min_cleanup_delay,
            "eviction-max-delay": ev.max_delay if ev else cfg.max_cleanup_delay,
            "tracking-table-max-keys": self.tracking.max_keys,
            "placement-devices": (
                self.engine.placement.n_devices
                if self.engine.placement is not None else 0
            ),
            "dispatch-ahead": self.readback_ahead,
            # device fault domain (ISSUE 19): lane watchdog + quarantine
            "lane-watchdog-ms": ioplane.lane_watchdog_ms(),
            "lane-quarantine-after": ioplane.quarantine_after(),
            # tracing plane (ISSUE 12): arming + ring/slowlog knobs
            "trace-enabled": int(_obs.tracing_enabled()),
            "trace-ring-capacity": self.tracer.ring_capacity,
            "slowlog-log-slower-than": self.tracer.slowlog_slower_than_us,
            "slowlog-max-len": self.tracer.slowlog_max_len,
        }
        # vector-plane tuning (ISSUE 15 satellite): the IVF gather geometry
        # and the per-bank HBM budget must re-sweep on a chip WITHOUT a
        # code edit — live process-global knobs in services/vector.py
        from redisson_tpu.services import vector as _V

        view["ivf-cell-imbalance"] = _V.IVF_CELL_IMBALANCE
        view["ivf-cell-cap-max"] = _V.IVF_CELL_CAP_MAX
        view["ftvec-device-budget"] = _V.DEVICE_BYTES_BUDGET
        # tiered-HBM residency plane (ISSUE 20): the per-DEVICE byte budget
        # (the generalization of the per-bank ftvec knob above) + arming
        from redisson_tpu.core import residency as _res

        view["device-budget-bytes"] = _res.DEVICE_BUDGET_BYTES
        view["residency-enabled"] = int(
            self.engine.residency is not None and _res.tier_enabled()
        )
        view.update(self.scheduler.config_view())
        return view

    def config_set(self, key: str, value: str) -> bool:
        """CONFIG SET: the runtime-tunable subset (RedisNode.setConfig
        analog).  Structural knobs (port, TLS, mode) are read-only."""
        if key == "eviction-min-delay":
            self.engine.eviction.min_delay = float(value)
            return True
        if key == "eviction-max-delay":
            self.engine.eviction.max_delay = float(value)
            return True
        if key == "checkpoint-path":
            self.checkpoint_path = value or None
            return True
        if key == "tracking-table-max-keys":
            n = int(value)
            if n <= 0:
                return False
            self.tracking.max_keys = n
            return True
        if key == "dispatch-ahead":
            n = int(value)
            if n <= 0:
                return False
            # connections opened from now on size their per-connection
            # dispatch-ahead semaphore with this (see _handle)
            self.readback_ahead = n
            return True
        if key == "lane-watchdog-ms":
            # bounded readback wait (ISSUE 19): 0 disarms — the historical
            # unbounded-wait shape, bit-identical replies
            n = int(value)
            if n < 0:
                return False
            ioplane.set_lane_watchdog_ms(n)
            return True
        if key == "lane-quarantine-after":
            # consecutive device faults/timeouts that flip a lane to
            # QUARANTINED (CLUSTER DEVICES shows the state)
            n = int(value)
            if n <= 0:
                return False
            ioplane.set_quarantine_after(n)
            return True
        if key == "trace-enabled":
            # arm/disarm the per-frame tracing plane live (the chaos-hook
            # discipline: disarmed sites cost one load + is-None; armed
            # replies stay bit-identical — tests/test_observe.py pins both)
            _obs.set_tracing(
                value.lower() not in ("0", "false", "no", "off")
            )
            return True
        if key == "trace-ring-capacity":
            n = int(value)
            if n <= 0:
                return False
            self.tracer.set_ring_capacity(n)
            return True
        if key == "slowlog-log-slower-than":
            # Redis parity: µs threshold; negative disables slowlog
            # recording, 0 logs every frame
            self.tracer.slowlog_slower_than_us = int(value)
            return True
        if key == "slowlog-max-len":
            n = int(value)
            if n <= 0:
                return False
            self.tracer.set_slowlog_max_len(n)
            return True
        if key == "ivf-cell-imbalance":
            # cell_cap bound multiplier; applies at the next cell rebuild /
            # retrain (the chip-run gather-bandwidth sweep, ISSUE 15)
            v = float(value)
            if v < 1.0:
                return False
            from redisson_tpu.services import vector as _V

            _V.set_ivf_cell_imbalance(v)
            return True
        if key == "ivf-cell-cap-max":
            # hard gather-width ceiling (0 = unbounded)
            n = int(value)
            if n < 0:
                return False
            from redisson_tpu.services import vector as _V

            _V.set_ivf_cell_cap_max(n)
            return True
        if key == "ftvec-device-budget":
            # per-bank-per-device HBM budget in bytes (0 = unlimited)
            n = int(value)
            if n < 0:
                return False
            from redisson_tpu.services import vector as _V

            _V.set_device_bytes_budget(n)
            return True
        if key == "device-budget-bytes":
            # per-DEVICE HBM budget the residency sweeper demotes against
            # (0 = unlimited; demotion still available via explicit verbs)
            n = int(value)
            if n < 0:
                return False
            from redisson_tpu.core import residency as _res

            _res.set_device_budget_bytes(n)
            return True
        if key == "residency-enabled":
            # arm/disarm the tiered-HBM residency plane live (ISSUE 20).
            # Disarming promotes every demoted record back to HOT first so
            # replies stay bit-identical with the plane off.
            on = value.lower() not in ("0", "false", "no", "off")
            from redisson_tpu.core import residency as _res

            if on:
                self.enable_residency(sweep_interval=1.0)
            else:
                _res.set_tier(False)
                self.engine.disable_residency()
            return True
        if key.startswith("qos-"):
            if key == "qos-bulk-slots" and int(value) <= 0:
                # 0 means "re-derive from workers" exactly like construction
                # time — it must never silently disable the flood protection
                value = str(max(1, self._workers - 1))
            ok = self.scheduler.config_set(key, value)
            if ok and key == "qos-interactive-deadline-ms":
                # arm/disarm ioplane's deadline-triggered window close: live
                # lane pipelines update NOW, pipelines built later inherit
                # the process-global default
                s = self.scheduler.interactive_deadline_ms / 1000.0
                ioplane.set_window_deadline(s if s > 0 else None)
                if self.engine.lanes is not None:
                    for lane in self.engine.lanes.lanes():
                        lane.pipeline.deadline_s = s if s > 0 else None
            if ok and key == "qos-bulk-subwindow-items":
                # push the sub-window split target into the process-global
                # knob the lane dispatch paths read (ISSUE 18)
                ioplane.set_bulk_subwindow_items(
                    self.scheduler.bulk_subwindow_items
                )
            return ok
        return False

    def next_client_id(self) -> int:
        return next(self._client_ids)

    def local_client(self):
        """Embedded client over this server's engine (OBJCALL target)."""
        if self._local_client is None:
            from redisson_tpu.client.redisson import RedissonTpu

            self._local_client = RedissonTpu(self.engine)
        return self._local_client

    def cluster_slots(self) -> List[Any]:
        """CLUSTER SLOTS reply shape: [from, to, [host, port, id]]."""
        if not self.cluster_view:
            return [[0, 16383,
                     [self.public_host.encode(), self.port,
                      self.node_id.encode()]]]
        return [
            [lo, hi, [h.encode(), p, nid.encode()]]
            for (lo, hi, h, p, nid) in self.cluster_view
        ]

    # -- cluster routing / replication role ----------------------------------

    @property
    def public_host(self) -> str:
        """The host this node is KNOWN BY (views, journals, READY line):
        the advertised address when bind and routable addresses differ
        (cross-host nodes binding 0.0.0.0), else the bind host."""
        return self.advertise_host or self.host

    def address(self) -> str:
        return f"{self.public_host}:{self.port}"

    def owns_slot(self, slot: int) -> bool:
        if not self.cluster_view:
            return True
        for lo, hi, h, p, _nid in self.cluster_view:
            if lo <= slot <= hi:
                if (h, p) == (self.public_host, self.port):
                    return True
                # a replica serves READS for its master's range (the READONLY
                # connection mode of Redis cluster replicas); writes are
                # rejected separately by the role check in check_routing
                return self.role == "replica" and self.master_address == f"{h}:{p}"
        return False  # unassigned slot: treat as not owned

    def moved_target(self, slot: int) -> Optional[Tuple[str, int]]:
        for lo, hi, h, p, _nid in self.cluster_view:
            if lo <= slot <= hi:
                return h, p
        return None

    def check_routing(self, cmd: str, args: List[bytes], asking: bool = False,
                      readonly: bool = False) -> None:
        """MOVED/ASK + READONLY enforcement (the server half of the
        reference's redirect protocol, cluster/ClusterConnectionManager +
        command/RedisExecutor redirect handling).

        Migration window semantics (Redis slot-migration model):
          * slot MIGRATING here: keys still present serve locally; absent
            keys redirect ASK to the draining target (they either moved
            already or must be created there);
          * slot IMPORTING here: normally MOVED back to the source (the view
            still names it), but a command preceded by ASKING is served.

        Replica read admission (ISSUE 17, Redis parity): a CLUSTER replica
        serves keyed reads only to connections that armed READONLY —
        everyone else is MOVED to the master (writes keep the historical
        -READONLY refusal below).  Standalone replicated pairs (no cluster
        view) keep serving reads to every connection, as before.
        """
        from redisson_tpu.net import commands as C
        from redisson_tpu.net.resp import RespError
        from redisson_tpu.utils.crc16 import calc_slot

        if self.cluster_view:
            migrating_absent = migrating_present = 0
            ask_target = None
            replica_read = False
            for key in C.command_keys(cmd, args):
                slot = calc_slot(key)
                if slot in self.recovering_slots:
                    # interrupted-migration fence: neither the restored
                    # local copy nor an ASK hop is safe until the journal
                    # resume settles the slot (see recovering_slots above)
                    # — and a replica never serves a fenced slot either
                    raise RespError(
                        f"TRYAGAIN slot {slot} recovering from an "
                        "interrupted migration"
                    )
                if self.owns_slot(slot):
                    if self.role == "replica" and not C.is_write(cmd, args):
                        if not readonly:
                            # the Redis-parity refusal: keyed reads without
                            # READONLY bounce to the master (the client's
                            # fallback path counts the redirect)
                            self.stats["replica_fallbacks"] += 1
                            ma = self.master_address
                            if ma:
                                raise RespError(f"MOVED {slot} {ma}")
                        else:
                            replica_read = True
                    target = self.migrating_slots.get(slot)
                    if target is not None:
                        name = key.decode() if isinstance(key, bytes) else key
                        if self.engine.store.peek(name):
                            migrating_present += 1
                        else:
                            migrating_absent += 1
                            ask_target, ask_slot = target, slot
                    continue
                if asking and slot in self.importing_slots:
                    continue  # one-shot admission during the handoff window
                target = self.moved_target(slot)
                if target is not None:
                    raise RespError(f"MOVED {slot} {target[0]}:{target[1]}")
                raise RespError(f"CLUSTERDOWN Hash slot {slot} not served")
            if migrating_absent:
                if migrating_present:
                    # mixed present/absent across a migration window: neither
                    # node holds every key right now — the client must retry
                    # until the drain finishes (Redis returns TRYAGAIN for
                    # exactly this multi-key case)
                    raise RespError(
                        "TRYAGAIN Multiple keys request during rehashing of slot"
                    )
                raise RespError(f"ASK {ask_slot} {ask_target}")
            if replica_read:
                self.stats["replica_reads"] += 1
        if self.role == "replica" and C.is_write(cmd, args):
            raise RespError("READONLY You can't write against a read only replica.")

    # -- live slot migration (server side) -----------------------------------

    def _migration_absent_guard(self, name: str) -> None:
        """DeviceStore absent-name hook: any touch of an ABSENT record in a
        MIGRATING slot redirects to the target.  This closes the races the
        pre-dispatch ASK check cannot: a record the drain deletes between
        check_routing and the handler would otherwise be silently recreated
        here (lost acked write) or read as nil (read-your-writes violation)."""
        from redisson_tpu.utils.crc16 import calc_slot

        slot = calc_slot(name.encode())
        target = self.migrating_slots.get(slot)
        if target is not None:
            raise RespError(f"ASK {slot} {target}")

    def fence_slot_epoch(self, slot: int, epoch: Optional[int]) -> None:
        """Accept-or-reject a migration-control command's fencing epoch for
        one slot.  Epoch-less commands (legacy callers, manual admin) pass
        unfenced; an epoch below the highest accepted one is a stale
        coordinator's late write and is refused loudly."""
        if epoch is None:
            return
        cur = self.slot_epochs.get(slot, 0)
        if epoch < cur:
            raise RespError(
                f"STALEEPOCH slot {slot} fenced at epoch {cur}; got {epoch}"
            )
        self.slot_epochs[slot] = epoch

    def set_slot_migrating(self, slot: int, target: str,
                           epoch: Optional[int] = None) -> None:
        self.migrating_slots[slot] = target
        if epoch is not None:
            # journaled drain: outgoing IMPORTRECORDS carry this epoch so
            # the target journals each batch before acking (ISSUE 13)
            self.migrating_epochs[slot] = epoch
        self.engine.store.absent_guard = self._migration_absent_guard

    def set_slot_importing(self, slot: int, source: str) -> None:
        self.importing_slots[slot] = source

    def set_slot_recovering(self, slot: int, target: str,
                            epoch: Optional[int] = None) -> None:
        self.recovering_slots[slot] = target
        # fence-first invalidation (the case Redis gets wrong-by-config): a
        # RECOVERING slot's restored copies may be stale against what the
        # pre-crash drain already shipped — every near cache drops the
        # slot's keys BEFORE the slot serves anything again, stamped with
        # THIS handoff's fencing epoch (the caller's, NOT the recorded
        # slot_epochs high-water mark: an epoch-less handoff of a slot a
        # PREVIOUS journaled migration fenced would otherwise be deduped
        # against that stale record and emit nothing) so the resume
        # re-issue is idempotent
        # slot_names is a full store scan — only pay it when a tracking
        # client could actually hear the invalidation (rearm_recovery calls
        # this per in-flight slot BEFORE serving; with tracking idle the
        # boot path must stay O(1))
        self.tracking.invalidate_slot(
            slot, epoch,
            self.slot_names(slot) if self.tracking.active else None,
        )

    def set_slot_stable(self, slot: int, epoch: Optional[int] = None) -> None:
        migrated = slot in self.migrating_slots or slot in self.recovering_slots
        self.migrating_slots.pop(slot, None)
        self.importing_slots.pop(slot, None)
        self.recovering_slots.pop(slot, None)  # resume settled the journal
        self.migrating_epochs.pop(slot, None)
        if not self.migrating_slots:
            self.engine.store.absent_guard = None
        self._settle_import_journals(epoch)
        if migrated:
            # handoff finalized on the SOURCE: whatever the per-key drain
            # stream didn't already invalidate (keys read-but-absent, keys
            # registered after their ship) flushes here, stamped with THIS
            # command's epoch — None (unfenced legacy migration) always
            # emits, a journaled re-issue at its own epoch dedupes
            self.tracking.invalidate_slot(slot, epoch)

    # -- import-side journal (ISSUE 13: the target-kill durability gap) -------

    def journal_import_batch(self, epoch: int, source: Optional[str],
                             blob: bytes) -> None:
        """Make one accepted IMPORTRECORDS batch durable (fsync'd into this
        node's ImportJournal) BEFORE it is applied or acked — the source
        deletes a record only once its batch survives a SIGKILL here.  A
        batch arriving for an epoch whose journal is already terminal is a
        stale re-ship of a settled migration: applied (idempotent by
        version) but not re-journaled — terminal journals stay terminal."""
        from redisson_tpu.server.migration_journal import ImportJournal

        if self.journal_dir is None:
            return
        with self._import_journal_lock:
            j = self._import_journals.get(epoch)
            if j is None:
                j = ImportJournal.open_for(
                    self.journal_dir, self.address(), epoch, source=source
                )
                if j.is_terminal():
                    return
                self._import_journals[epoch] = j
            j.append_batch(blob)

    def adopt_import_journal(self, journal) -> None:
        """Boot-time re-adoption (migration.rearm_recovery): a replayed
        in-flight import journal stays open on the restarted node so the
        resumed migration's final SETSLOT STABLE settles it."""
        with self._import_journal_lock:
            self._import_journals[journal.epoch] = journal

    def import_journal_rows(self) -> List[Tuple[int, str, int, str]]:
        """(epoch, phase, batches journaled, source) per OPEN import
        journal — the CLUSTER WINDOWS rows that let an operator see an
        in-flight import from the receiving end."""
        with self._import_journal_lock:
            return [
                (epoch, j.phase or "", j.batch_count(), j.source or "")
                for epoch, j in sorted(self._import_journals.items())
            ]

    def _settle_import_journals(self, epoch: Optional[int]) -> None:
        """Terminalize the import journal for `epoch` once its migration's
        LAST window slot goes STABLE (no remaining MIGRATING/IMPORTING/
        RECOVERING slot fenced at that epoch) — after which gc may prune it
        and a restart no longer replays it."""
        if epoch is None or not self._import_journals:
            return

        def _settleable() -> bool:
            j = self._import_journals.get(epoch)
            if j is None:
                return False
            open_slots = (
                set(self.importing_slots) | set(self.migrating_slots)
                | set(self.recovering_slots)
            )
            # still a window in flight for this migration? not settleable
            return not any(
                self.slot_epochs.get(s) == epoch for s in open_slots
            )

        with self._import_journal_lock:
            if not _settleable():
                return
        # durability point OUTSIDE the lock: a concurrent drain's
        # journal-and-ack (journal_import_batch) must not stall behind a
        # full-store snapshot and time its source's link out
        if not self._checkpoint_import_state():
            return  # not durable yet: keep the journal for boot replay
        with self._import_journal_lock:
            if not _settleable():  # a re-opened window raced the save
                return
            self._import_journals.pop(epoch).append("STABLE", settled=True)

    def _checkpoint_import_state(self) -> bool:
        """Make the imported records as durable as this node's normal
        story BEFORE an import journal retires: the journal holds the only
        durable copy of batches whose source copies are already deleted,
        so it may only terminalize once a checkpoint covers them — else a
        SIGKILL after STABLE but before the next snapshot would restore a
        pre-import checkpoint with nothing left to replay.  A node with no
        checkpoint configured has no durability floor to wait for.
        Returns False (journal kept in flight, replayed at next boot) when
        the save fails."""
        if self.checkpoint_path is None:
            return True
        from redisson_tpu.core import checkpoint

        try:
            checkpoint.save(self.engine, self.checkpoint_path)
            self.__dict__["_lastsave"] = int(time.time())
            return True
        except Exception:  # noqa: BLE001 — keep the journal instead
            return False

    def slot_names(self, slot: int) -> List[str]:
        from redisson_tpu.utils.crc16 import calc_slot

        return [
            n for n in self.engine.store.keys() if calc_slot(n.encode()) == slot
        ]

    # records shipped per IMPORTRECORDS frame during drains: a journaled
    # target fsyncs ONCE per frame, so batch coalescing divides the
    # journal-before-ack cost by the batch width (ISSUE 14 satellite; the
    # r06 container measured ~2.7ms/record = -27% import throughput at
    # batch 1)
    DRAIN_BATCH_RECORDS = 32

    def migrate_slot_batch(self, slots, limit: int = 0,
                           batch: Optional[int] = None) -> int:
        """Drain MIGRATING slot(s) to their targets; limit<=0 drains fully.

        Records ship in BATCHES of `batch` (default DRAIN_BATCH_RECORDS)
        per IMPORTRECORDS frame, grouped by (target, epoch).  The whole
        batch's record locks are held (sorted order — deadlock-free) across
        serialize -> IMPORTRECORDS -> local delete, the same atomicity the
        per-record path had: every mutation path (object handles AND the
        store-level DEL/EXPIRE commands) takes these locks, so no client
        write, delete, or expire can interleave between the snapshot
        leaving and the local copies dying — the zero-lost-acked-writes
        contract holds for deletes too (a DEL either lands before the
        snapshot, keeping the record out of the batch, or blocks until the
        name is locally absent and then ASK-redirects to the target).
        Redis gets the same guarantee from MIGRATE's single-threaded
        blocking; we pay it per-batch instead of per-server.  A journaled
        target fsyncs its ImportJournal ONCE per frame (journal-before-ack
        and the pre-ack replica cover are per-frame contracts — both hold
        unchanged), so the batch width directly divides the durability
        overhead the ISSUE 13 plane added.
        """
        from redisson_tpu.net.client import NodeClient
        from redisson_tpu.server import replication
        from redisson_tpu.utils.crc16 import calc_slot

        if isinstance(slots, int):
            slots = [slots]
        targets: Dict[int, str] = {}
        for s in slots:
            t = self.migrating_slots.get(s)
            if t is None:
                raise RespError(f"ERR slot {s} is not MIGRATING")
            targets[s] = t
        wanted = set(targets)
        names = [
            (n, calc_slot(n.encode()))
            for n in self.engine.store.keys()
            if calc_slot(n.encode()) in wanted
        ]
        if limit and limit > 0:
            names = names[:limit]
        if not names:
            return 0
        if batch is None or batch <= 0:
            batch = self.DRAIN_BATCH_RECORDS
        # group by (target, epoch) preserving scan order: one frame may
        # carry records of MANY slots, but never records bound for
        # different targets or fenced at different epochs
        groups: Dict[Tuple[str, Optional[int]], List[str]] = {}
        for name, slot in names:
            key = (targets[slot], self.migrating_epochs.get(slot))
            groups.setdefault(key, []).append(name)
        moved = 0
        links: Dict[str, NodeClient] = {}
        try:
            for (target, ep), gnames in groups.items():
                link = links.get(target)
                if link is None:
                    link = links[target] = self.link_client(
                        target, ping_interval=0, retry_attempts=1
                    )
                for i in range(0, len(gnames), batch):
                    moved += self._drain_batch_locked(
                        link, ep, gnames[i : i + batch]
                    )
        finally:
            for link in links.values():
                link.close()
        return moved

    def _drain_batch_locked(self, link, ep: Optional[int],
                            names: List[str]) -> int:
        """Ship one drain batch under ALL its record locks (sorted
        acquisition; serialize_records re-enters each per-record RLock)."""
        from redisson_tpu.server import replication

        with self.engine.locked_many(names):
            present = [n for n in names if self.engine.store.peek(n)]
            if not present:
                return 0  # expired/deleted meanwhile
            blob, shipped = replication.serialize_records(
                self.engine, present, include_live=False
            )
            if not shipped:
                return 0
            if ep is not None:
                # journaled migration: the target fsyncs the whole frame
                # into its ImportJournal BEFORE this ack — the local
                # deletes below are then safe against a target SIGKILL
                # (ISSUE 13 target-kill gap), at ONE fsync per batch
                link.execute(
                    "IMPORTRECORDS", "EPOCH", ep, "SOURCE",
                    self.address(), blob, timeout=30.0,
                )
            else:
                link.execute("IMPORTRECORDS", blob, timeout=30.0)
            shipped_names = [n for n, _nonce, _ver in shipped]
            for name in shipped_names:
                self.engine.store.delete_unguarded(name)
            # drain-stream invalidation: the records just left this node —
            # a near cache serving them would miss every write the target
            # accepts from now on (push enqueue only, so holding the locks
            # here is fine); active-guarded like every other site so an
            # idle-tracking migration never touches the dispatch-shared
            # table lock
            if self.tracking.active:
                self.tracking.note_write(shipped_names, None)
            return len(shipped_names)

    # -- chaos hooks (fault plane, server layer) ------------------------------

    def pause(self) -> None:
        """Stop answering commands without dropping connections (the
        SIGSTOP/GC-pause analog).  Paused workers park on the gate; clients
        observe reply timeouts, feeding FailedCommandsTimeoutDetector."""
        self._pause_gate.clear()

    def resume(self) -> None:
        self._pause_gate.set()

    @property
    def paused(self) -> bool:
        return not self._pause_gate.is_set()

    def _await_resume(self) -> None:
        """A dispatch job's first line: park while the server is paused."""
        if not self._pause_gate.is_set():
            # bounded so a forgotten resume() degrades to a long stall, not
            # a permanently wedged worker pool
            self._pause_gate.wait(timeout=60.0)

    def _error_reply(self, e: BaseException, n: int = 1) -> Encoded:
        """THE translation of a failed dispatch into a reply, for `n`
        commands that share it.  A stopping worker pool drops the
        connection instead (ConnectionResetError): it never replies
        per-command errors.  Any other failure is a per-command one — reply
        an error, keep the connection (dropping it would kill every other
        pipelined command on this socket)."""
        if isinstance(e, ConnectionResetError):
            raise e
        if isinstance(e, RuntimeError) and "shutdown" in str(e):
            raise ConnectionResetError(str(e)) from e
        self.stats["errors"] += n
        if isinstance(e, RespError):
            text = str(e.args[0])
        elif ioplane.is_retryable_device_fault(e):
            # device-layer fault (kernel launch, watchdog timeout): a clean
            # retryable -TRYAGAIN, never an opaque internal error; what may
            # have applied is NEVER re-dispatched here — at-most-once is
            # the client's to spend (ISSUE 19)
            text = _DEVICE_FAULT_TRYAGAIN
        else:  # uninitialized object, state errors, handler bugs: sandboxed
            text = f"ERR internal: {type(e).__name__}: {e}"
        return Encoded(resp.encode_error(text))

    def _dispatch_one(self, ctx, cmd, qos_class: Optional[str] = None,
                      held: bool = False):
        """One command through its handler, whatever it raises a reply
        (_error_reply).  A serial command (one of a serial segment's worker
        job, _dispatch_serial; a frame that is ONE point command does not
        come here, it joins its record's window: _join_point_window) waits
        at the pause gate and occupies its lane when every key maps to ONE
        device — single-command frames (pipelined blobs bigger than one
        recv chunk arrive one command per parse batch) and transaction
        members still account their device occupancy against the owning
        lane: dispatches from CONCURRENT connections bound for different
        devices overlap, same-device ones serialize, exactly like N
        per-chip streams.  `held`: the caller is a bucket that holds the
        lane already — the gate is not re-entrant, so a per-record member
        never takes it again."""
        if not isinstance(cmd, list) or not all(
            isinstance(a, (bytes, bytearray)) for a in cmd
        ):
            return Encoded(resp.encode_error("ERR bad request frame"))
        try:
            if held:
                return REGISTRY.dispatch(self, ctx, cmd)
            self._await_resume()
            with self._occupancy(self._lane_of(cmd), (cmd,), qos_class):
                return REGISTRY.dispatch(self, ctx, cmd)
        except Exception as e:  # noqa: BLE001 — sandboxed per command
            return self._error_reply(e)

    def _dispatch_serial(self, ctx, cmds, qos_class: Optional[str] = None):
        """A serial segment's consecutive commands, each alone and in frame
        order, as one worker job."""
        return [self._dispatch_one(ctx, cmd, qos_class) for cmd in cmds]

    # -- windows of point commands (ISSUE 36) ----------------------------------
    # A frame that is ONE point command does not go to a worker alone: on
    # the loop it joins the open window of its record, and ONE worker job a
    # window answers every member with one dispatch and one fetch.
    # A record has one job at a time, so no timer and no size: a window is
    # what arrived while the one before it was served (group commit) — a
    # lone command on an idle server is taken at once, alone, and 200
    # waiting connections form windows of a hundred.  The members of a
    # window are all unanswered, hence concurrent, so any one order of them
    # is a legal one; it is the probes, then the adds as they arrived
    # (verbs/sketch.py point_window).  A connection has at most one member
    # anywhere: its read loop awaits the frame, so its next command is read
    # after this one's reply.

    def _point_windows_serve(self) -> bool:
        """Whether this server answers point commands by window: where
        Registry.dispatch does nothing a command that a window cannot do a
        member — no placement and no cluster view (routing, lanes), not a
        replica (READONLY redirects), the fault plane disarmed (its
        per-command device chokepoint)."""
        return (
            self.engine.placement is None and not self.cluster_view
            and self.role != "replica" and _net._fault_plane is None
        )

    def _join_point_window(self, ctx, cmd, loop, pool, trace):
        """On the loop: `cmd`, a point command, joins the open window of
        its record; where the record has no job, it opens one and submits
        the job that will take it.  Returns the future its frame awaits."""
        member = _PointMember(ctx, cmd, loop.create_future(), trace)
        key = cmd[1]
        with self._point_lock:
            waiting = self._point_open.get(key)
            if waiting is not None:
                waiting.append(member)
                return member.fut
            self._point_open[key] = [member]
        self._submit_point_job(loop, pool, key)
        return member.fut

    def _submit_point_job(self, loop, pool, key: bytes) -> None:
        """The record's next job; from a stopping pool, the end of whoever
        waits for it."""
        try:
            pool.submit(self._serve_point_window, loop, pool, key)
        except RuntimeError as e:  # nobody will take them
            with self._point_lock:
                stranded = self._point_open.pop(key)
            loop.call_soon_threadsafe(
                _resolve_point_window, stranded, None, ConnectionResetError(str(e))
            )

    def _serve_point_window(self, loop, pool, key: bytes) -> None:
        """THE worker job of a record's point commands: take what is
        waiting (at most _POINT_WINDOW_MAX members), answer it, hand every
        member's reply to the loop in ONE call, and — before returning —
        submit the record's next job if anybody joined meanwhile.  `hop`
        closes here, where the job takes the member; `dispatch` is taken ->
        answered, and `wake` opens where it ends."""
        self._await_resume()
        with self._point_lock:
            waiting = self._point_open[key]
            members = waiting[:_POINT_WINDOW_MAX]
            del waiting[:_POINT_WINDOW_MAX]
        traced = [m.trace for m in members if m.trace is not None]
        for tr in traced:
            tr.hopped("dispatch")
        t_taken = time.monotonic()
        replies, error = None, ConnectionResetError("the window's job died")
        try:
            replies = self._answer_point_window(key, members)
            error = None
        except Exception as e:  # noqa: BLE001 — a stopping pool: the frames die, as a serial command's does
            error = e if isinstance(e, ConnectionResetError) else ConnectionResetError(repr(e))
        finally:
            # whatever ended the window, its members are answered and the
            # record is let go of (or handed to its next job)
            t_done = time.monotonic()
            for tr in traced:
                tr.add_span("dispatch", t_taken, t_done)
                tr.left_at = t_done
            loop.call_soon_threadsafe(_resolve_point_window, members, replies, error)
            with self._point_lock:
                if not waiting:
                    del self._point_open[key]
            if waiting:  # only a job takes members away: still there
                self._submit_point_job(loop, pool, key)

    def _answer_point_window(self, key: bytes, members) -> list:
        """One window's replies, a member each: what Registry.dispatch does
        a command done a member — tracking (reads registered before the
        dispatch, writes invalidated after it, also where it failed:
        possibly applied), the command hooks (INFO commandstats counts
        every member) — around ONE call of verbs/sketch.py point_window.  A
        window that raises answers every member what a refused serial
        command answers (_error_reply) and dispatches nothing again."""
        from redisson_tpu.server.verbs.sketch import point_window
        from redisson_tpu.utils.metrics import run_hooks_end, run_hooks_start

        if not self._point_windows_serve():
            # the server changed while they waited (a cluster view, the
            # fault plane armed): each goes the way a command goes
            return [self._dispatch_one(m.ctx, m.cmd) for m in members]
        verbs = [_POINT_VERBS[m.cmd[0]] for m in members]
        track = self.tracking if self.tracking.active else None
        hooks = self.hooks
        tokens, error = [], None
        try:
            for m, (verb, name) in zip(members, verbs):
                if track is not None:
                    track.pre_dispatch(m.ctx, verb, m.cmd[1:])
                if hooks:
                    tokens.append(run_hooks_start(hooks, name, m.cmd[1:]))
            replies = point_window(
                self, key.decode(), [name for _v, name in verbs],
                [m.cmd[2] for m in members], [m.trace for m in members],
            )
        except Exception as e:  # noqa: BLE001 — the window's, so every member's
            error = e
        for tok, (_verb, name) in zip(tokens, verbs):
            run_hooks_end(tok, name, error)
        if track is not None:
            for m, (verb, _name) in zip(members, verbs):
                try:
                    track.post_dispatch(m.ctx, verb, m.cmd[1:])
                except Exception as e:  # noqa: BLE001 — never mask the primary error
                    error = error or e
        if error is not None:
            replies = [self._error_reply(error, len(members))] * len(members)
        return replies

    def _fused_add_error_invalidate(self, track, run_names) -> None:
        """A failed fused BF.MADD64 run may have PARTIALLY applied (that is
        why add runs never re-dispatch) — tracked near caches holding
        negative `contains` entries for these filters must still be
        invalidated or they serve stale membership forever.  writer_ctx is
        None deliberately: the writer's client-side wrapper aborted on the
        error reply, so even a NOLOOP writer needs the push."""
        if track is not None and run_names:
            try:
                track.note_write(run_names, None)
            except Exception:  # noqa: BLE001 — never mask the primary error
                pass

    def _dispatch_bloom_run(self, ctx, cmds):
        """ONE stacked-bank kernel dispatch for a wave of same-verb BF blob
        commands (the adaptive coalescing plane) instead of one per
        command, per-command LazyReplies riding the frame's grouped d2h
        gather.  A wave holds what one stacked dispatch holds
        (coalesce.plan_waves: up to 16 commands and 16,384 keys).
        Ineligible waves (and a wave of one command too long to stack) fall
        back to sequential per-command dispatch with identical semantics;
        an unexpected failure of the fused path falls back only for
        CONTAINS waves (read-only) — add waves reply per-command errors
        instead, so a possibly-applied mutation is never re-dispatched
        (at-most-once)."""
        from redisson_tpu.server.verbs.sketch import coalesce_bloom_run

        cur = _obs.current_trace() if _obs._tracer is not None else None
        k0 = time.monotonic() if cur is not None else 0.0
        is_add = bytes(cmds[0][0]).upper() == b"BF.MADD64"
        # tracking hooks for the fused path (the fallback below re-dispatches
        # through REGISTRY.dispatch, which carries its own hooks): probe runs
        # register their filter names PRE-dispatch, add runs invalidate after
        # the fused kernel applied
        track = self.tracking if self.tracking.active else None
        run_names = None
        if track is not None:
            seen = set()
            run_names = [
                n for n in (bytes(c[1]).decode() for c in cmds)
                if not (n in seen or seen.add(n))
            ]
            if not is_add:
                track.note_read(ctx, run_names)
        fused = None
        try:
            if len(cmds) > 1 or (
                len(cmds[0]) > 2
                and stacked_row_bucket(len(cmds[0][2]) // 8) is not None
            ):
                fused = coalesce_bloom_run(self, ctx, cmds)
        except Exception as e:  # noqa: BLE001 — per-run isolation
            # first, so that a stopping pool drops the connection whichever
            # verb failed; a failed probe wave counts no error of its own
            enc = self._error_reply(e, len(cmds) if is_add else 0)
            if is_add:
                self._fused_add_error_invalidate(track, run_names)
                return [enc for _ in cmds]
        if fused is not None:
            if cur is not None:
                self._stacked_kernel_span(
                    cur, k0, bytes(cmds[0][0]).upper().decode(), cmds
                )
            if track is not None and is_add:
                track.note_write(run_names, ctx)
            return fused
        return [self._dispatch_one(ctx, cmd, held=True) for cmd in cmds]

    @staticmethod
    def _stacked_kernel_span(cur, k0: float, verb: str, cmds, key_at: int = 1,
                             stacked: int = STACK_PLANES) -> None:
        """Coalescer fan-in: ONE kernel span for a stacked dispatch; `keys`
        names its member commands' keys (the first 32, so a 1000-command
        blob run cannot bloat the trace).  `stacked`: what the dispatch was
        padded to."""
        cur.add_span(
            "kernel", k0, time.monotonic(), verb=verb, members=len(cmds),
            stacked=stacked,
            keys=b",".join(bytes(c[key_at]) for c in cmds[:32]).decode(
                errors="replace"),
        )

    def _dispatch_bitset_wave(self, ctx, cmds):
        """ONE stacked dispatch for a wave of same-form SETBITSB, BITOP OR /
        XOR or BITCOUNT commands on different keys (coalesce.plan_waves),
        per-command LazyReplies riding the frame's grouped fetch.  A member
        the stacked form does not cover takes the per-record handler — the
        members of a wave share no key, so it may run after the others.  An
        unexpected failure falls back to per-record dispatch only for
        BITCOUNT (read-only); a writing wave replies per-command errors and
        is never re-dispatched (at-most-once, as add runs)."""
        from redisson_tpu.server.verbs.sketch import coalesce_bitset_wave

        cur = _obs.current_trace() if _obs._tracer is not None else None
        k0 = time.monotonic() if cur is not None else 0.0
        verb = bytes(cmds[0][0]).upper()
        writes = verb != b"BITCOUNT"
        # the tracking hooks Registry.dispatch runs a command: reads register
        # BEFORE the dispatch, writes invalidate after it (also where it
        # failed: possibly applied).  A member that ends per record runs
        # them again there: a spurious push costs one refetch
        track = self.tracking if self.tracking.active else None
        if track is not None:
            for c in cmds:
                track.pre_dispatch(ctx, verb, c[1:])
        fused = None
        try:
            fused = coalesce_bitset_wave(self, ctx, cmds)
        except Exception as e:  # noqa: BLE001 — per-wave isolation
            enc = self._error_reply(e, len(cmds) if writes else 0)
            if writes:
                if track is not None:
                    for c in cmds:
                        try:
                            track.post_dispatch(ctx, verb, c[1:])
                        except Exception:  # noqa: BLE001 — never mask the primary error
                            pass
                return [enc for _ in cmds]
        if fused is None:
            return [self._dispatch_one(ctx, cmd, held=True) for cmd in cmds]
        rode = [c for c, r in zip(cmds, fused) if r is not None]
        if cur is not None:
            if verb == b"BITOP":  # the operator is part of the form
                self._stacked_kernel_span(
                    cur, k0, "BITOP " + bytes(cmds[0][1]).upper().decode(), rode, 2
                )
            else:
                self._stacked_kernel_span(cur, k0, verb.decode(), rode)
        if track is not None:
            for c in rode:
                track.post_dispatch(ctx, verb, c[1:])
        return [
            r if r is not None else self._dispatch_one(ctx, cmd, held=True)
            for cmd, r in zip(cmds, fused)
        ]

    def _dispatch_knn_wave(self, ctx, cmds):
        """ONE stacked KNN dispatch for a wave of FT.SEARCH / FT.MSEARCH
        commands on one index and query text (coalesce.plan_waves), a
        LazyReply a command riding the frame's grouped fetch.  A lone search
        is dispatched as the command it is.  Read-only: a wave that cannot
        ride goes per command, which replies whatever a command alone
        replies; a stacked dispatch that fails with an error reply of its
        own (-OOM, a device fault's -TRYAGAIN) answers every member with
        it, as each would have met it alone."""
        from redisson_tpu.server.verbs.modules import coalesce_knn_run

        if len(cmds) == 1:
            return [self._dispatch_one(ctx, cmds[0], held=True)]
        cur = _obs.current_trace() if _obs._tracer is not None else None
        k0 = time.monotonic() if cur is not None else 0.0
        fused = None
        try:
            fused = coalesce_knn_run(self, ctx, cmds)
        except Exception as e:  # noqa: BLE001 — per-wave isolation
            worded = isinstance(e, RespError) or ioplane.is_retryable_device_fault(e)
            enc = self._error_reply(e, len(cmds) if worded else 0)
            if worded:
                return [enc for _ in cmds]
        if fused is None:
            return [self._dispatch_one(ctx, cmd, held=True) for cmd in cmds]
        replies, slots, shared = fused
        _coalesce.count_knn_fused(len(cmds), shared)
        if cur is not None:
            self._stacked_kernel_span(cur, k0, "FT.SEARCH", cmds, stacked=slots)
        return replies

    # -- device-sharded frame dispatch (ISSUE 8) ------------------------------

    def _ftvec_census(self) -> dict:
        """Embedding-bank residency rows ({ftvec_banks, ftvec_device_bytes})
        from the lazily-created search service; zeros while none exists."""
        svc = self.engine._services.get("search")
        zeros = {"ftvec_banks": 0.0, "ftvec_device_bytes": 0.0,
                 "ftvec_index_bytes": 0.0}
        if svc is None:
            return zeros
        try:
            # observe-only: a scrape must never fault a demoted bank back
            # onto the device (ISSUE 20) — a WARM bank reports 0 HBM bytes,
            # which is exactly what the ledger means
            from redisson_tpu.core import residency as _res

            with _res.no_promote():
                return svc.device_census()
        except Exception:  # noqa: BLE001 — a broken gauge must not kill scrape
            return zeros

    def _device_bytes_census(self) -> dict:
        """Per-device HBM residency over EVERY record kind (ISSUE 19
        satellite — the generalization of the ftvec_*_bytes_dev ledger):
        one store scan summing each record's committed device arrays by
        (device, kind).  Rows — ``record_bytes_dev<N>`` totals plus
        ``record_bytes_dev<N>_<kind>`` breakdowns — exist only while that
        device holds bytes, so DEL / FT.DROPINDEX drains them to absence
        == zero (the soak's flat-census assertion)."""
        from redisson_tpu.core.ioplane import _device_id_of

        by_dev: dict = {}
        by_kind: dict = {}
        try:
            records = self.engine.store.census_records()
        except Exception:  # noqa: BLE001 — a broken gauge must not kill scrape
            return {}
        for kind, rec in records:
            arrays = getattr(rec, "arrays", None)
            if not arrays:
                continue
            for arr in list(arrays.values()):
                d = _device_id_of(arr)
                if d is None:
                    continue
                n = float(getattr(arr, "nbytes", 0) or 0)
                if n <= 0.0:
                    continue
                by_dev[d] = by_dev.get(d, 0.0) + n
                by_kind[(d, kind)] = by_kind.get((d, kind), 0.0) + n
        out: dict = {}
        for d, v in sorted(by_dev.items()):
            out[f"record_bytes_dev{d}"] = v
        for (d, kind), v in sorted(by_kind.items()):
            out[f"record_bytes_dev{d}_{kind}"] = v
        return out

    def _residency_census(self) -> dict:
        """Per-tier residency rows (ISSUE 20): empty while the plane is
        disarmed so the gauge family contributes nothing to a scrape."""
        mgr = self.engine.residency
        if mgr is None:
            return {}
        try:
            return mgr.census()
        except Exception:  # noqa: BLE001 — a broken gauge must not kill scrape
            return {}

    def _residency_fence_check(self, name: str) -> bool:
        """True when ``name``'s slot is mid-migration on this node — the
        demoter must never touch a record the fenced journaled mover is
        about to snapshot (ISSUE 20 'fenced/migrating slots never demote')."""
        if not (self.migrating_slots or self.importing_slots
                or self.recovering_slots):
            return False
        from redisson_tpu.utils.crc16 import calc_slot

        slot = calc_slot(name.encode())
        return (slot in self.migrating_slots
                or slot in self.importing_slots
                or slot in self.recovering_slots)

    def enable_residency(self, **kw) -> None:
        """Arm the tiered-residency plane with the server's fences wired in
        (CONFIG SET residency-enabled yes / --residency boot path).  Under
        RTPU_NO_TIER=1 this is a refused no-op END TO END: set_tier(True)
        would be rejected, and a manager whose sweeper demotes while the
        getter guard stays disarmed would strand WARM records with no
        fault-in path."""
        from redisson_tpu.core import residency as _res

        if _res._NO_TIER:
            return
        self.engine.enable_residency(**kw)
        self.engine.residency.fence_check = self._residency_fence_check
        _res.set_tier(True)

    def _occupancy(self, lane, cmds, qos_class: Optional[str] = None):
        """The context one dispatch of `cmds` runs under: the occupancy of
        `lane` for their estimated device items, or where there is no lane
        the nothing that _Laneless is.  The sizing rule lives in
        server/scheduler.py (ISSUE 10) so lane accounting and tenant
        budgets cannot diverge."""
        if lane is None:
            return _Laneless()
        if lane.quarantined:
            # a QUARANTINED lane rejects new keyed work retryably while its
            # slots evacuate / await a probe — never a dispatch into a
            # faulted device stream (ISSUE 19)
            raise RespError(_quarantined_tryagain(lane.dev_id))
        return lane.occupy(
            _sched.estimate_device_items(cmds), qos_class=qos_class,
            nbytes=_sched._frame_nbytes(cmds) if qos_class is not None else 0,
        )

    def _lane_of(self, cmd):
        """The one device lane every key of `cmd` maps to, else None
        (laneless or mixed-device: nothing to occupy)."""
        eng = self.engine
        if eng.placement is None or eng.lanes is None:
            return None
        dev = eng.placement.device_index_for_command(cmd)
        if dev is None:
            return None
        return eng.lanes.lane(eng.placement.devices[dev])

    def _subwindow_target(self, qos_class: Optional[str]) -> int:
        """Effective bulk sub-window item target for one dispatch: >0 only
        with preemption armed, splitting configured, and a non-interactive
        dispatch (interactive frames ride the fast path whole)."""
        if qos_class == "interactive" or not ioplane.preempt_enabled():
            return 0
        return ioplane.bulk_subwindow_items()

    def _pool_for(self, adm):
        """Worker pool for one frame's dispatch: interactive-class frames
        (scheduler armed) run on the reserved interactive pool so a bulk
        flood occupying every shared worker can never queue ahead of them;
        everything else keeps the historical shared pool."""
        if adm is not None and adm.interactive:
            return self._qos_pool
        return self._pool

    def _dispatch_bucket(self, ctx, dev_index: Optional[int], items,
                         qos_class: Optional[str] = None):
        """One lane's ordered slice of a pipelined frame (a 'buckets'
        segment of the frame's plan), one worker job: it runs WHILE the
        other devices' buckets run on their workers — the per-chip dispatch
        lanes of device-sharded serving; with no placement (`dev_index`
        None) the one bucket is a run of blob commands and there is no lane
        to hold.  Inside the bucket, commands on different keys regroup
        into same-verb waves, one stacked dispatch each (all on this
        device).  Returns [(frame_index, result), ...]."""
        self._await_resume()
        eng = self.engine
        lane = (
            eng.lanes.lane(eng.placement.devices[dev_index])
            if dev_index is not None and eng.lanes is not None else None
        )
        cmds = [c for _i, c in items]
        out = []
        # The bucket's commands regrouped into same-form waves on disjoint
        # keys, per-key order kept (coalesce.plan_waves): each wave ONE
        # stacked dispatch, whatever order the client wrote the tenants'
        # commands in.  Lone commands too: which commands of a frame stand
        # alone on a device is the frame's composition, and a lone command
        # dispatched per record would be a program of its own on every lane.
        waves = [
            (form, members, [cmds[i] for i in members])
            for form, members in plan_waves([wave_entry(c) for c in cmds])
        ]
        _coalesce.count_offered(len(cmds))
        # preemptible sub-windows (ISSUE 18): an oversized bulk bucket splits
        # its ONE bucket-wide occupancy into segments of at most
        # qos-bulk-subwindow-items estimated device items, each under an
        # occupancy of its own, with ``lane.preempt_point()`` between them so
        # a waiting interactive frame jumps the boundary instead of the
        # drained bucket.  Waves are first cut at COMMAND boundaries, never
        # inside one command's key batch, each piece a SELF-CONTAINED stacked
        # dispatch with its own record locks: at-most-once survives because
        # a failed piece replies per-command errors and is never
        # re-dispatched, while earlier pieces already applied and replied
        # (the ``runs_within_admission`` sub-run shape).  Replies land by
        # frame index, so per-connection FIFO and reply bytes are identical
        # to the unsplit dispatch.
        target = self._subwindow_target(qos_class) if lane is not None else 0
        segs = [(0, len(waves))]
        if target > 0:
            waves = [
                (form, members[s:e], wave[s:e])
                for form, members, wave in waves
                for s, e in plan_subwindows(
                    [_sched.estimate_command_items(c) for c in wave], target
                )
            ]
            segs = plan_subwindows(
                [_sched.estimate_device_items(wave) for _f, _m, wave in waves],
                target,
            )
        for k, (lo, hi) in enumerate(segs):
            if k:
                lane.preempt_point()
            seg = waves[lo:hi]
            seg_cmds = [c for _f, _m, wave in seg for c in wave]
            with ExitStack() as held:
                try:
                    held.enter_context(
                        self._occupancy(lane, seg_cmds, qos_class)
                    )
                except Exception as e:  # noqa: BLE001
                    # a lane that refuses the dispatch at its gate
                    # (quarantined, a kernel-launch fault: ISSUE 19) has run
                    # none of them: they reply in frame position what a
                    # refused serial command replies, and the other devices'
                    # buckets still serve
                    enc = self._error_reply(e, len(seg_cmds))
                    out.extend(
                        (items[i][0], enc) for _f, members, _w in seg
                        for i in members
                    )
                    continue
                for form, members, wave in seg:
                    if form is None:
                        replies = [self._dispatch_one(ctx, wave[0], held=True)]
                    elif form[0] in COALESCIBLE_BLOB_VERBS:
                        replies = self._dispatch_bloom_run(ctx, wave)
                    elif form[0] == KNN_FORM:
                        replies = self._dispatch_knn_wave(ctx, wave)
                    else:
                        replies = self._dispatch_bitset_wave(ctx, wave)
                    out.extend(
                        (items[i][0], r) for i, r in zip(members, replies)
                    )
        return out

    def _plan_frame(self, ctx, commands, shed_mask):
        """The one plan a frame is run by (core/coalesce.py plan_frame_runs
        says what a plan is).  A connection in a state the grouped
        dispatchers do not serve — inside MULTI, unauthenticated, ASKING —
        gets the serial plan, as does a frame of one command with no
        placement (nothing to group WITHIN the frame: every frame of a
        bulk flush; a point command has left for its record's window
        before any plan, _run_frame) and a frame whose planning failed:
        planning must never break a frame."""
        placement = self.engine.placement
        if (
            (len(commands) > 1 or placement is not None)
            and ctx.multi_queue is None
            and ctx.authenticated
            and not ctx.asking
        ):
            try:
                if placement is None:
                    return plan_frame_runs(commands, shed_mask)
                # a frame that lands on ONE lane is planned too: its bucket
                # is one job for one worker, where a serial segment is a
                # hop a command — nothing on an idle pool, and a second of
                # queueing on a busy one.  Such frames are what is left of
                # a pipelined request when the read loop ran before its
                # last bytes had landed, or when the request is longer
                # than _FRAME_CAP (with 64 KiB reads every request ended on
                # one: PERF.md section 6, PR 26).  A frame of ONE command
                # too: what has arrived is the frame's composition, and in
                # a bucket the command rides the stacked program every
                # lane has compiled, where a serial one would run a
                # per-record program of its own.
                return placement.plan_frame(commands, shed_mask)
            except Exception:  # noqa: BLE001
                pass
        return serial_plan(len(commands), shed_mask)

    async def _run_frame(self, ctx, commands, loop, adm=None, trace=None):
        """Dispatch every command of one pipelined frame — the commands that
        had arrived whole when the read loop looked (_handle), up to
        _FRAME_CAP of bytes — under its plan and return the replies by
        frame index, whatever order they completed in.  Handlers may
        return LazyReply — device work enqueued, NOT
        forced: the frame's lazies are forced together afterwards
        (_finish_frame), one device->host sync a frame and lane instead of
        one a command.  A frame that is ONE point command (a single-item
        BF.ADD / BF.EXISTS) on a connection and a server the grouped paths
        serve is not planned: it joins the open window of its record and
        is answered with the other connections' commands waiting there, by
        one worker job (_join_point_window).  A 'serial' segment runs its
        commands in frame order as barriers, consecutive fast ones as one
        worker job (_dispatch_serial); a 'buckets' segment fans its
        per-lane buckets out on the worker pool CONCURRENTLY (each bucket
        FIFO on its device lane — per-key order is preserved because a key
        maps to exactly one device)."""
        qos_class = shed_mask = None
        results: list = [None] * len(commands)
        if adm is not None:
            qos_class, shed_mask = adm.qos_class, adm.shed_mask
        if shed_mask is not None:
            # load-shed: -BUSY in frame position, NO dispatch, no queue
            # residency (the reply FIFO is untouched — the error encodes
            # exactly where the command's reply goes).  QoS shed boundary
            # (ISSUE 10): no group of the plan spans a shed command — a
            # fused window covers ADMITTED ops only, so a partially-applied
            # coalesced add run can never be created by (or re-dispatched
            # after) a shed decision
            shed = Encoded(resp.encode_error(_sched.busy_error(adm.tenant)))
            for i, refused in enumerate(shed_mask):
                if refused:
                    results[i] = shed
        pool = self._pool_for(adm)
        plan = None
        if len(commands) == 1:
            # a frame of ONE command (every frame of a bulk flush is one):
            # a point command joins its record's window, anything else is
            # planned as it always was
            cmd = commands[0]
            if (
                type(cmd) is list and len(cmd) == 3
                and type(cmd[0]) is bytes and cmd[0] in _POINT_VERBS
                and results[0] is None  # not shed
                and ctx.authenticated and ctx.multi_queue is None
                and not ctx.asking
                and type(cmd[1]) is bytes and type(cmd[2]) is bytes
                and self._point_windows_serve()
            ):
                self.stats["commands"] += 1
                if trace is not None:
                    trace.hop_at = time.monotonic()
                results[0] = await self._join_point_window(
                    ctx, cmd, loop, pool, trace
                )
                if trace is not None:
                    trace.woke("dispatch")
                plan = ()
        if plan is None:
            plan = self._plan_frame(ctx, commands, shed_mask)
        for seg_kind, seg in plan:
            if seg_kind == "serial":
                self.stats["commands"] += len(seg)
                # consecutive fast commands are ONE worker job, run in frame
                # order (a hop a command was ~0.2 ms of one serial host: a
                # frame of 467 HSETs paid it 467 times).  OBJCALL (user
                # methods may park) and blocking verbs go one by one to the
                # wide slow pool: a parked handler must never starve the
                # small fast pool every connection shares
                at = 0
                while at < len(seg):
                    slow = _is_slow(commands[seg[at]])
                    end = at + 1
                    while not slow and end < len(seg) and not _is_slow(commands[seg[end]]):
                        end += 1
                    if trace is not None:
                        trace.hop_at = time.monotonic()
                    replies = await loop.run_in_executor(
                        self._slow_pool if slow else pool, _on_worker, trace,
                        "dispatch", self._dispatch_serial, ctx,
                        [commands[i] for i in seg[at:end]], qos_class,
                    )
                    if trace is not None:
                        trace.woke("dispatch")
                    for i, r in zip(seg[at:end], replies):
                        results[i] = r
                    at = end
                continue
            jobs = []
            if trace is not None:
                trace.hop_at = time.monotonic()  # the buckets share it
            for dev_index, idxs in seg.items():
                self.stats["commands"] += len(idxs)
                jobs.append(loop.run_in_executor(
                    pool, _on_worker, trace, "dispatch", self._dispatch_bucket,
                    ctx, dev_index, [(i, commands[i]) for i in idxs], qos_class,
                ))
            outs = await asyncio.gather(*jobs, return_exceptions=True)
            if trace is not None:
                trace.woke("dispatch")  # from the bucket that ended last
            err = next((o for o in outs if isinstance(o, BaseException)), None)
            if err is not None:
                raise err
            for out in outs:
                for i, r in out:
                    results[i] = r
        # index at the write: the keys this frame's writes left dirty under
        # a search index's prefix are indexed before the frame is answered
        # (services/search.py; with no index, one dict lookup a frame)
        search = self.engine._services.get("search")
        if search is not None and search.has_dirty():
            if trace is not None:
                trace.hop_at = time.monotonic()
            await loop.run_in_executor(
                pool, _on_worker, trace, "dispatch", _drain_index, search)
            if trace is not None:
                trace.woke("dispatch")
        return results

    async def _finish_frame(self, ctx, results, loop, write_q, readback_slots,
                            alive, adm=None, trace=None) -> bool:
        """The reply tail of a dispatched frame: force its lazies, encode,
        hand the bytes to the connection's writer task.  Returns False when
        the connection must stop reading (writer task dead)."""
        if any(isinstance(r, LazyReply) for r in results):
            if self.overlap:
                # overlap plane: hand the readback to the writer task
                # as a completion-queue entry and go straight back to
                # reading — frame N+1's upload/dispatch overlaps this
                # frame's D2H.  FIFO queue order preserves the reply
                # order; proto is snapshotted at dispatch time.
                await readback_slots.acquire()
                if not alive["writer"]:
                    return False  # connection is going down; stop dispatching
                if trace is not None:
                    trace.mark_dispatched()
                    trace.hop_at = trace.dispatched_at
                fut = loop.run_in_executor(
                    self._pool_for(adm), _on_worker, trace, "force",
                    _force_lazies, results, self,
                )
                write_q.put_nowait(
                    _PendingFrame(results, fut, ctx.proto, trace)
                )
                return True
            if trace is not None:
                trace.hop_at = time.monotonic()
            await loop.run_in_executor(
                self._pool_for(adm), _on_worker, trace, "force",
                _force_lazies, results, self,
            )
            if trace is not None:
                trace.woke("force")
        # one queue item per frame — the whole frame's replies
        # encode in one pass and write in one syscall batch
        if trace is not None:
            write_q.put_nowait(_TracedEncoded(results, ctx.proto, trace))
        else:
            write_q.put_nowait(_encode_frame(results, ctx.proto))
        return True

    def replication_source(self):
        """Lazy master-side record shipper (server/replication.py)."""
        from redisson_tpu.server.replication import ReplicationSource

        with self._repl_lock:
            if self._replication is None:
                self._replication = ReplicationSource(self)
            return self._replication

    def info_text(self) -> str:
        up = int(time.time() - self.started_at)
        return (
            "# Server\r\n"
            f"redis_version:7.2.0-rtpu\r\nrun_id:{self.node_id}\r\n"
            f"tcp_port:{self.port}\r\nuptime_in_seconds:{up}\r\nmode:{self.mode}\r\n"
            f"gc_thresholds:{','.join(map(str, gc.get_threshold()))}\r\n"
            "# Clients\r\n"
            f"connected_clients:{self.stats['connections']}\r\n"
            "# Stats\r\n"
            f"total_commands_processed:{self.stats['commands']}\r\n"
            f"errors:{self.stats['errors']}\r\n"
            "# Keyspace\r\n"
            f"db0:keys={len(self.engine.store)},expires=0\r\n"
            + self._device_info_text()
        )

    def _device_info_text(self) -> str:
        """``# Device`` INFO section: where this server runs (platform,
        device kind, per-device allocator bytes — the client/nodes.py
        memory() fields) and the I/O plane facts a wire client cannot see
        otherwise (which wire plane serves, compile cache, staging reuse,
        host-side colocations, lane faults, the occupancy model)."""
        import jax

        import redisson_tpu
        from redisson_tpu.net import _native

        devs = jax.local_devices()
        lines = [
            "# Device",
            f"platform:{devs[0].platform}",
            f"device_kind:{devs[0].device_kind}",
            f"local_device_count:{len(devs)}",
            f"jax_version:{jax.__version__}",
        ]
        for i, d in enumerate(devs):
            ms = d.memory_stats() or {}  # None on backends without stats
            row = [f"id={d.id}"] + [
                f"{k}={ms[k]}"
                for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                if k in ms
            ]
            lines.append(f"device{i}:" + ",".join(row))
        eng = self.engine
        lanes = eng.lanes.lanes() if eng.lanes is not None else []
        pools = [eng.staging] + [p for ln in lanes for p in (ln.pool, ln.ipool)]
        io = ioplane.STATS.snapshot()
        cache = redisson_tpu.compile_cache_stats()
        occ = ioplane.replica_occupancy()
        lines += [
            f"wire_plane:{'native' if _native.load() is not None else 'python'}",
            f"native_build:{_native.build_status()}",
            f"compile_cache_dir:{redisson_tpu.compile_cache_dir() or ''}",
            f"compile_cache_hits:{cache['hits']}",
            f"compile_cache_writes:{cache['writes']}",
            f"compiled_programs:{cache['programs']}",
            f"compile_seconds:{cache['compile_s']:.3f}",
            f"staging_reuses:{sum(p.reuses for p in pools)}",
            f"staging_oneoffs:{sum(p.oneoffs for p in pools)}",
            f"d2d_colocations:{io['d2d_colocations']}",
            f"host_colocations:{io['host_colocations']}",
            f"merge_fallbacks:{io['merge_fallbacks']}",
            f"lane_faults:{sum(ln.total_faults for ln in lanes)}",
            f"lanes_quarantined:{sum(int(ln.quarantined) for ln in lanes)}",
            f"replica_occupancy:{'none' if occ is None else occ}",
        ]
        return "\r\n".join(lines) + "\r\n"

    def commandstats_text(self) -> str:
        """INFO commandstats section (Redis parity): per-verb
        calls/usec/usec_per_call, sourced from the MetricsRegistry's
        ``command.<verb>`` timers (the MetricsHook records every dispatched
        command there already — no second accounting plane)."""
        lines = ["# Commandstats"]
        with self.metrics._lock:
            timers = sorted(self.metrics._timers.items())
        for name, t in timers:
            if not name.startswith("command."):
                continue
            verb = name[len("command."):]
            usec = int(t.total_s * 1e6)
            per = usec / t.count if t.count else 0.0
            lines.append(
                f"cmdstat_{verb}:calls={t.count},usec={usec},"
                f"usec_per_call={per:.2f}"
            )
        return "\r\n".join(lines) + "\r\n"

    # -- QoS admission (ISSUE 10: deadline classes + per-tenant budgets) ------

    def _bulk_gate_for(self, slots: int) -> Optional[asyncio.Semaphore]:
        """The server-wide bulk admission gate: at most `slots` bulk-class
        frames may be in dispatch at once across ALL connections, so a bulk
        flood can never occupy every worker ahead of interactive traffic.
        Rebuilt when CONFIG SET qos-bulk-slots changes the count (holders of
        the old gate release into the old gate — each frame releases exactly
        the object it acquired)."""
        if slots <= 0:
            return None
        gate = self._bulk_gate
        if gate is None or self._bulk_gate_n != slots:
            gate = self._bulk_gate = asyncio.Semaphore(slots)
            self._bulk_gate_n = slots
        return gate

    async def _serve_frame(self, ctx, commands, loop, write_q,
                           readback_slots, alive, trace=None) -> bool:
        """Admit + dispatch ONE parsed frame (the read loop's per-frame
        body).  Returns False when the connection must stop reading (writer
        task dead).  With the scheduler armed the frame is classified
        (interactive/bulk) and charged against its tenant's token bucket
        BEFORE anything dispatches: over-budget commands shed with -BUSY
        (never any queue residency), bulk frames pass the bounded bulk
        admission gate, and the frame's dispatch is accounted on the
        per-class in-flight ledger for its whole residency.  `trace`
        (tracing armed only) records admit + bulk-gate wait as the frame's
        `qos` span, annotated tenant/class/items/shed."""
        if not commands:
            return True  # a read that completed no frame
        self.stats["frames"] += 1
        sched = self.scheduler
        adm = None
        bulk_gate = None
        acquired = begun = False
        tq0 = time.monotonic() if trace is not None else 0.0
        if (
            sched.armed
            and ctx.authenticated
            and ctx.multi_queue is None
        ):
            adm = sched.admit(ctx, commands)
            if adm.shed_count:
                self.stats["sheds"] += adm.shed_count
        fully_shed = (
            adm is not None
            and adm.shed_mask is not None
            and all(adm.shed_mask)
        )
        try:
            if adm is not None:
                # a FULLY-refused frame never dispatches (its replies are
                # pure encodes), so it must not occupy a bulk admission
                # slot — holding one through the shed path would give the
                # over-budget tenant's refusals queue residency that delays
                # in-budget bulk tenants
                if not adm.interactive and not fully_shed:
                    bulk_gate = self._bulk_gate_for(sched.bulk_slots)
                    if bulk_gate is not None:
                        sched.ledger.wait_enter()
                        try:
                            await bulk_gate.acquire()
                            acquired = True
                        finally:
                            sched.ledger.wait_exit()
                sched.begin(adm)
                begun = True
                if trace is not None:
                    # classification + tenant charge + bulk-gate wait: the
                    # span that attributes "my frame sat behind admission"
                    trace.qos_class = adm.qos_class
                    trace.tenant = adm.tenant
                    trace.add_span(
                        "qos", tq0, time.monotonic(),
                        tenant=adm.tenant, cls=adm.qos_class,
                        items=adm.items, shed=adm.shed_count,
                    )
            ok = await self._finish_frame(
                ctx, await self._run_frame(ctx, commands, loop, adm, trace),
                loop, write_q, readback_slots, alive, adm, trace,
            )
        finally:
            if begun:
                sched.end(adm)
            if acquired:
                bulk_gate.release()
        if ok and fully_shed and sched.shed_penalty_ms > 0:
            # fully-refused frame: park THIS connection's read loop for the
            # shed penalty (replies already flushed, every gate/ledger hold
            # already released) — a client that spins on -BUSY cannot turn
            # the cheap shed path into a parse-plane DoS; nobody else's
            # traffic is delayed
            await asyncio.sleep(sched.shed_penalty_ms / 1000.0)
        return ok

    # -- asyncio plumbing ----------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        """One connection: the read loop and its writer task.  A FRAME is
        the whole commands among the bytes the stream has buffered when the
        loop looks: one read of up to _FRAME_CAP, never a byte waited for.
        Each frame is parsed once, admitted once (_serve_frame),
        planned and dispatched once (_run_frame) and answered in frame
        position; a command cut by the network stays in the parser until
        its rest comes."""
        self.stats["connections"] += 1
        self._writers.add(writer)
        ctx = CommandContext(self)
        self.tracking.register_conn(ctx)
        parser = resp.RespParser()
        loop = asyncio.get_running_loop()
        write_q: asyncio.Queue = asyncio.Queue()

        def push(msg) -> None:
            # pubsub listeners fire on engine threads; hop to the loop
            # (encoded with THIS connection's negotiated protocol)
            loop.call_soon_threadsafe(
                write_q.put_nowait, resp.encode_reply(msg, ctx.proto)
            )

        ctx.push = push

        # dispatch-ahead bound (overlap plane): the read loop may run at most
        # `readback_ahead` frames ahead of the slowest un-written readback
        # (snapshotted at accept time so a mid-connection CONFIG SET
        # dispatch-ahead cannot skew this connection's acquire/release pairing)
        readback_ahead = max(1, self.readback_ahead)
        readback_slots = asyncio.Semaphore(readback_ahead)
        # shared liveness flag (writer task -> read loop/_serve_frame)
        alive = {"writer": True}

        async def writer_task():
            # The completion queue drain: items are pre-encoded bytes (pubsub
            # pushes, readback-free frames — these flush immediately) or
            # _PendingFrame readback futures (awaited HERE, off the read
            # loop, so the next frame's upload and dispatch overlap this
            # frame's D2H readback).  The queue is FIFO and this task writes
            # strictly in pop order, so per-connection reply ordering and
            # RESP framing are preserved exactly.
            #
            # Aggregated writes: everything drained from one queue pass —
            # coalesced frames AND resolved readback frames — is joined and
            # written as a SINGLE transport.write (one syscall per drained
            # batch instead of per frame).  An unresolved readback only ever
            # delays bytes queued BEHIND it, never ones already collected.
            #
            # Tracing (armed only): traced items carry their FrameTrace;
            # once the batch's bytes are written+drained each trace closes
            # its `reply` span HERE — the trace total is therefore the true
            # client-observable latency, and the span's children say what
            # the tail was: `reply.wait` (the readback future, or the time
            # queued here; `reply.wake` is the end of it, the force job's
            # last line -> this task has its result), `reply.encode`,
            # `reply.write` (write -> drain returned, shared by the batch).
            # A trace whose bytes never reach the wire (pool death,
            # connection error) is abandoned so the inflight census row
            # still drains.
            held = None  # a _PendingFrame popped while coalescing bytes
            try:
                while True:
                    item = held if held is not None else await write_q.get()
                    held = None
                    if item is None:
                        return
                    parts: list = []
                    done_tr = None  # traces of this batch (armed only)
                    final = False
                    while True:
                        if isinstance(item, _PendingFrame):
                            if parts and not item.fut.done():
                                # flush what's ready; await this one next pass
                                held = item
                                break
                            try:
                                await item.fut  # the overlapped readback
                            except Exception:  # noqa: BLE001 — pool died mid-force
                                # tear the connection DOWN, like the serial
                                # path's in-loop exception would: a silent
                                # return leaves the read loop dispatching into
                                # a dead queue and the client blocked on recv
                                # with no EOF
                                if item.trace is not None:
                                    _obs.TRACER.abandon(item.trace)
                                if done_tr is not None:
                                    for t in done_tr:
                                        _obs.TRACER.abandon(t)
                                try:
                                    writer.close()
                                except Exception:  # noqa: BLE001
                                    pass
                                return
                            finally:
                                readback_slots.release()
                            if item.trace is not None:
                                tr = item.trace
                                t_got = time.monotonic()
                                parts.append(item.encoded())
                                tr.add_span("reply.wait", tr.dispatched_at,
                                            t_got)
                                tr.add_span("reply.wake", tr.left_at, t_got,
                                            frm="force")
                                tr.add_span("reply.encode", t_got,
                                            time.monotonic())
                                if done_tr is None:
                                    done_tr = []
                                done_tr.append(tr)
                            else:
                                parts.append(item.encoded())
                        elif isinstance(item, _TracedEncoded):
                            parts.append(item.data)
                            item.trace.add_span(
                                "reply.wait", item.put_at, time.monotonic()
                            )
                            if done_tr is None:
                                done_tr = []
                            done_tr.append(item.trace)
                        else:
                            parts.append(item)
                        if write_q.empty():
                            break
                        nxt = write_q.get_nowait()
                        if nxt is None:
                            final = True
                            break
                        item = nxt
                    if parts:
                        payload = parts[0] if len(parts) == 1 else b"".join(parts)
                        if done_tr is not None:
                            t_write = time.monotonic()
                        writer.write(payload)
                        try:
                            await writer.drain()
                        except ConnectionError:
                            if done_tr is not None:
                                for t in done_tr:
                                    _obs.TRACER.abandon(t)
                            return
                        if done_tr is not None:
                            for t in done_tr:
                                _obs.TRACER.finish_reply(
                                    t, t_write, len(payload), len(parts)
                                )
                    if final:
                        return
            finally:
                alive["writer"] = False
                # un-stick a read loop parked on the dispatch-ahead bound
                for _ in range(readback_ahead):
                    readback_slots.release()

        wt = asyncio.create_task(writer_task())
        # `recv` (tracing armed only): reads, first read's return, bytes and
        # fruitless-feed seconds of the frame now arriving; rx_n == 0 = the
        # next read brings a frame's first byte
        rx_n = rx_bytes = 0
        rx_t0 = rx_feed = 0.0
        try:
            while True:
                data = await reader.read(_FRAME_CAP)
                if not data:
                    break
                # tracing (observe/trace.py): frames are stamped AT PARSE
                # TIME — trace id + monotonic t0 — and the stamp rides the
                # frame through every chokepoint.  Disarmed cost: one
                # module-global load + `is not None` per read.  None (not
                # 0.0) is the disarmed sentinel: arming between this read
                # and the begin_frame guard must not anchor a trace at
                # monotonic zero (a garbage uptime-long total).
                t_parse0 = (
                    time.monotonic() if _obs._tracer is not None else None
                )
                if t_parse0 is None:
                    rx_n = 0
                else:
                    if rx_n == 0:
                        # bytes already buffered (armed mid-frame) are the
                        # frame's too
                        rx_t0, rx_bytes = t_parse0, parser.pending_bytes
                        rx_feed = 0.0
                    rx_n += 1
                    rx_bytes += len(data)
                try:
                    commands = parser.feed(data)
                except ProtocolError as e:
                    write_q.put_nowait(resp.encode_error(f"ERR protocol error: {e}"))
                    break
                trace = None
                if _obs._tracer is not None and commands:
                    trace = _obs._tracer.begin_frame(
                        ctx, commands, t0=t_parse0
                    )
                    if t_parse0 is not None:
                        # the frame's arrival lies BEFORE its t0 (the read
                        # that completed it): a negative offset, so `parse`,
                        # `reply` and the total keep their meaning
                        left = parser.pending_bytes
                        trace.add_span(
                            "recv", rx_t0, t_parse0, reads=rx_n,
                            nbytes=rx_bytes - left,
                            feed_us=int(rx_feed * 1e6),
                        )
                        # the next frame's first bytes rode this read too
                        rx_n = 1 if left else 0
                        rx_t0, rx_bytes, rx_feed = t_parse0, left, 0.0
                    if self.role == "replica":
                        # per-stage replica annotation (ISSUE 17): every
                        # span of a replica-served frame carries replica=1
                        trace.base_attrs = {"replica": 1}
                elif t_parse0 is not None and not commands:
                    # a feed that completed nothing is receive-side work
                    rx_feed += time.monotonic() - t_parse0
                try:
                    ok = await self._serve_frame(
                        ctx, commands, loop, write_q, readback_slots, alive,
                        trace,
                    )
                except BaseException:
                    # frame died before its replies were queued: close the
                    # trace's books so the inflight census row drains
                    if trace is not None and not trace.finished:
                        _obs.TRACER.abandon(trace)
                    raise
                if not ok:
                    if trace is not None and not trace.finished:
                        _obs.TRACER.abandon(trace)
                    break
        except (ConnectionResetError, asyncio.IncompleteReadError, BrokenPipeError):
            pass
        finally:
            # tracking disconnect-cleanup FIRST: the table must not leak this
            # conn's keys, and dependents redirecting here must break loudly
            self.tracking.unregister_conn(ctx)
            for ch, lid in list(ctx.subscriptions.items()):
                self.engine.pubsub.unsubscribe(ch, lid)
            for pat, lid in list(ctx.psubscriptions.items()):
                self.engine.pubsub.punsubscribe(pat, lid)
            write_q.put_nowait(None)
            await wt
            # traced frames still queued behind the writer's death never
            # reached the wire: abandon them so trace_inflight drains
            while not write_q.empty():
                leftover = write_q.get_nowait()
                t = getattr(leftover, "trace", None)
                if t is not None and not t.finished:
                    _obs.TRACER.abandon(t)
            self._writers.discard(writer)
            self.stats["connections"] -= 1
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:  # noqa: BLE001
                pass

    @property
    def tls_enabled(self) -> bool:
        return self.tls_cert_file is not None

    def _server_ssl_context(self):
        if not self.tls_enabled:
            return None
        import ssl

        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(self.tls_cert_file, self.tls_key_file)
        if self.tls_ca_file:
            ctx.load_verify_locations(self.tls_ca_file)
            ctx.verify_mode = ssl.CERT_REQUIRED  # mutual TLS
        return ctx

    def link_client(self, address: str, **kw):
        """NodeClient for this node's OUTGOING links (slot drains, replica
        sync): inherits the node's password and, when TLS is on, a client
        context trusting the cluster CA (hostname checks off — cluster
        peers are addressed by IP)."""
        from redisson_tpu.net.client import NodeClient, client_ssl_context

        kw.setdefault("password", self.password)
        if self.tls_enabled:
            kw.setdefault(
                "ssl_context",
                client_ssl_context(
                    # self-signed deployments (no separate CA) trust the
                    # shared node cert itself — same fallback as
                    # ServerThread.client(); without it REPLSNAPSHOT and
                    # IMPORTRECORDS links die on SSLCertVerificationError
                    ca_file=self.tls_ca_file or self.tls_cert_file,
                    cert_file=self.tls_cert_file,
                    key_file=self.tls_key_file,
                    verify_hostname=False,
                ),
            )
        return NodeClient(address, **kw)

    def new_event_loop(self) -> asyncio.AbstractEventLoop:
        """The loop this server runs on where it makes its own (`asyncio.run
        (..., loop_factory=server.new_event_loop)`: ServerThread, the CLI):
        a selector loop whose selector keeps the loop's account."""
        self._loop_selector = _obs.LoopSelector()
        self._own_loop = asyncio.SelectorEventLoop(self._loop_selector)
        return self._own_loop

    def _threads_cpu_s(self, who: str) -> float:
        """CPU seconds of the loop's thread since start_async (`loop`), or
        of this server's three pools' threads (`worker`).  A stopping
        server keeps its last reading: stop() closes under the same lock,
        before any of those threads can end, so the clock of a thread that
        is gone is never asked for and no total falls."""
        with self._cpu_lock:
            if not self._closing:
                if who == "loop":
                    now = _obs.thread_cpu_s(self._loop_thread) - self._loop_cpu0
                else:
                    now = sum(
                        _obs.thread_cpu_s(t)
                        for pool in (self._pool, self._qos_pool, self._slow_pool)
                        for t in tuple(pool._threads)
                    )
                self._cpu_seen[who] = now
            return self._cpu_seen[who]

    async def start_async(self):
        self._loop = asyncio.get_running_loop()
        if self._loop is not self._own_loop:
            self._loop_selector = None  # somebody else's loop: no account
        self._loop_thread = threading.current_thread()
        self._loop_cpu0 = _obs.thread_cpu_s(self._loop_thread)
        self._process_cpu0 = time.process_time()
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port, reuse_address=True,
            ssl=self._server_ssl_context(), limit=_FRAME_CAP,
        )
        if self.port == 0:
            self.port = self._server.sockets[0].getsockname()[1]
        return self

    async def serve_forever(self):
        await self.start_async()
        async with self._server:
            await self._server.serve_forever()

    async def serve_until_signal(self, ready_fd: Optional[int] = None,
                                 journal_dir: Optional[str] = None):
        """CLI serve loop: run until SIGTERM **or** SIGINT — both are the
        graceful path (supervisors send SIGTERM; only the SIGINT/Ctrl-C
        route used to reach the AutoCheckpointer flush-on-stop, which left
        SIGTERM'd deployments losing their last interval of writes).

        ``ready_fd``: once the listener is bound (port 0 resolved), write
        one line — ``READY <host> <port> <pid>`` — to this inherited file
        descriptor and close it.  The ClusterSupervisor awaits that line
        instead of sleep-polling the port (cluster/supervisor.py)."""
        import os
        import signal as _signal

        loop = asyncio.get_running_loop()
        stopped = asyncio.Event()
        installed = []
        for sig in (_signal.SIGTERM, _signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stopped.set)
                installed.append(sig)
            except (NotImplementedError, RuntimeError):  # non-main thread /
                pass                                     # exotic loop
        await self.start_async()
        if journal_dir is not None:
            # the node's import journals live here too (ISSUE 13): the
            # IMPORTRECORDS handler needs the dir armed before serving
            self.journal_dir = journal_dir
        if self.journal_dir is not None:
            # BEFORE the ready line goes out (supervised clients gate on
            # it): re-arm migration windows this node was a party to when
            # it last died — restored copies of mid-migration slots must
            # answer TRYAGAIN, not serve a forked lineage — and replay the
            # import journals whose batches this node acked but may have
            # lost with its memory (migration.rearm_recovery)
            from redisson_tpu.server.migration import rearm_recovery

            rearm_recovery(self, self.journal_dir)
        if ready_fd is not None:
            line = f"READY {self.public_host} {self.port} {os.getpid()}\n".encode()
            try:
                os.write(ready_fd, line)
            finally:
                try:
                    os.close(ready_fd)
                except OSError:
                    pass
        try:
            await stopped.wait()
        finally:
            for sig in installed:
                loop.remove_signal_handler(sig)
            # stop() closes the listener AND every client writer; only then
            # can wait_closed() finish — it waits for each connection's
            # handler, and an idle client would otherwise hold a
            # told-to-stop server (and its device) forever.  Bounded: a
            # handler wedged past the bound must not outlive the signal.
            self.stop()
            try:
                await asyncio.wait_for(
                    self._server.wait_closed(), timeout=_STOP_DRAIN_S
                )
            except asyncio.TimeoutError:
                pass

    def stop(self):
        # parked blocking verbs (_block_loop, WAIT) poll this to unpark:
        # a forever-blocked worker would otherwise survive pool shutdown
        # (wait=False) and hang interpreter exit via the futures atexit join
        with self._cpu_lock:  # no scrape is reading a thread's clock now
            self._closing = True
        self._pause_gate.set()  # release chaos-paused workers
        loop, server = self._loop, self._server
        if loop is not None and server is not None:
            def shutdown():
                server.close()
                # drop established connections too: clients must see a dead
                # node, not a half-alive one (failover tests depend on this)
                for w in list(self._writers):
                    try:
                        w.close()
                    except Exception:  # noqa: BLE001
                        pass

            try:
                loop.call_soon_threadsafe(shutdown)
            except RuntimeError:
                pass  # loop already closed (repeated stop): nothing to do
        if self._replication is not None:
            self._replication.close()
        self._pool.shutdown(wait=False)
        self._qos_pool.shutdown(wait=False)
        self._slow_pool.shutdown(wait=False)


def _encode_result(result, proto: int = 3) -> bytes:
    if isinstance(result, str) and result.startswith("+"):
        return resp.encode_simple(result[1:])
    if isinstance(result, list) and result and all(isinstance(r, resp.Push) for r in result):
        # subscribe-style confirmations: stream of push frames
        return b"".join(resp.encode_reply(r, proto) for r in result)
    return resp.encode_reply(result, proto)


def _encode_frame(results: list, proto: int) -> bytes:
    """Encode a whole frame's replies as ONE byte string.  Runs of plain
    values ride a single resp.encode_replies emit (one native arena write
    for the run — the aggregated-write path); pre-encoded errors and the
    two special result forms (`+simple` strings, push-frame lists) keep
    their _encode_result semantics, in place, in order."""
    parts: list = []
    run: list = []
    flush = parts.append
    for r in results:
        if isinstance(r, Encoded):
            if run:
                flush(resp.encode_replies(run, proto))
                run = []
            flush(r.data)
        elif isinstance(r, str) and r.startswith("+"):
            if run:
                flush(resp.encode_replies(run, proto))
                run = []
            flush(resp.encode_simple(r[1:]))
        elif isinstance(r, list) and r and isinstance(r[0], resp.Push):
            if run:
                flush(resp.encode_replies(run, proto))
                run = []
            flush(_encode_result(r, proto))
        else:
            run.append(r)
    if run:
        flush(resp.encode_replies(run, proto))
    if len(parts) == 1:
        return parts[0]
    return b"".join(parts)


class ServerThread:
    """In-process server on a daemon thread — the embedded-test harness
    (RedisRunner analog for hermetic tests, SURVEY.md §4 lesson)."""

    def __init__(self, engine: Optional[Engine] = None, port: int = 0, **kw):
        self.server = TpuServer(engine=engine, port=port, **kw)
        self._thread: Optional[threading.Thread] = None
        self._started = threading.Event()

    def __enter__(self) -> "ServerThread":
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def start(self) -> "ServerThread":
        def run():
            async def main():
                await self.server.start_async()
                self._started.set()
                async with self.server._server:
                    try:
                        await self.server._server.serve_forever()
                    except asyncio.CancelledError:
                        pass

            asyncio.run(main(), loop_factory=self.server.new_event_loop)

        self._thread = threading.Thread(target=run, daemon=True, name="rtpu-server")
        self._thread.start()
        if not self._started.wait(timeout=10):
            raise RuntimeError("server failed to start")
        return self

    @property
    def port(self) -> int:
        return self.server.port

    @property
    def address(self) -> str:
        scheme = "tpus" if self.server.tls_enabled else "tpu"
        return f"{scheme}://{self.server.host}:{self.server.port}"

    def stop(self):
        self.server.stop()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def client(self):
        """One-shot admin connection (context manager) to this node — speaks
        TLS when the node does (trusting the node's own CA/cert chain)."""
        from contextlib import closing

        from redisson_tpu.net.client import Connection, client_ssl_context

        ssl_ctx = None
        if self.server.tls_enabled:
            ssl_ctx = client_ssl_context(
                ca_file=self.server.tls_ca_file or self.server.tls_cert_file,
                cert_file=self.server.tls_cert_file if self.server.tls_ca_file else None,
                key_file=self.server.tls_key_file if self.server.tls_ca_file else None,
                verify_hostname=False,
            )
        return closing(
            Connection(
                self.server.host,
                self.server.port,
                timeout=120.0,
                password=self.server.password,
                ssl_context=ssl_ctx,
            )
        )


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description="redisson-tpu server")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=6390)
    ap.add_argument(
        "--advertise-host", default=None,
        help="the routable address this node is named by in cluster views, "
             "migration journals, and its READY line when it differs from "
             "the bind --host (cross-host nodes bind 0.0.0.0; without this "
             "a node would MOVED-bounce its own slots)",
    )
    ap.add_argument("--password", default=None)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--restore", action="store_true", help="load checkpoint on boot")
    ap.add_argument(
        "--checkpoint-interval", type=float, default=0.0,
        help="seconds between automatic snapshots (0 = manual SAVE only)",
    )
    ap.add_argument("--platform", default=None, help="force jax platform (cpu/tpu)")
    ap.add_argument(
        "--prewarm", action="store_true",
        help="precompile hot kernels for restored records at boot "
             "(core/warmpool — keeps the first request's latency clean)",
    )
    ap.add_argument(
        "--no-overlap", action="store_true",
        help="disable the overlapped device I/O plane (core/ioplane): "
             "flushes run strictly stage->dispatch->fetch and every frame's "
             "readback blocks its connection's read loop — the serial "
             "reference path for A/B measurement",
    )
    ap.add_argument(
        "--no-tier", action="store_true",
        help="disable the tiered-HBM residency plane (core/residency): "
             "every record stays HOT on its owner device, no demotion or "
             "fault-in ever runs — the reference path for A/B measurement "
             "(RTPU_NO_TIER=1 equivalent; replies are bit-identical)",
    )
    ap.add_argument(
        "--residency", action="store_true",
        help="arm the tiered-HBM residency plane at boot (cold records "
             "demote to host RAM / spill under the per-device "
             "device-budget-bytes budget and fault back in on first touch; "
             "also CONFIG SET residency-enabled yes)",
    )
    ap.add_argument(
        "--workers", type=int, default=4,
        help="data-plane worker threads (the per-connection dispatch pool)",
    )
    ap.add_argument(
        "--no-qos", action="store_true",
        help="disable the deadline-aware window scheduler / per-tenant QoS "
             "plane (server/scheduler.py): frames dispatch in pure arrival "
             "order with no classification, budgets, or load shedding — the "
             "reference path for A/B measurement (RTPU_NO_QOS=1 equivalent)",
    )
    ap.add_argument(
        "--no-preempt", action="store_true",
        help="disable the bulk-window preemption plane (ISSUE 18): no "
             "sub-window splitting, no per-class device streams — every "
             "dispatch serializes through the single per-lane gate exactly "
             "as PR 9 shipped, the reference path for A/B measurement "
             "(RTPU_NO_PREEMPT=1 equivalent)",
    )
    ap.add_argument(
        "--dispatch-ahead", type=int, default=None,
        help="per-connection dispatch-ahead bound: how many frames may sit "
             "between 'dispatched' and 'replies written' on one connection "
             "(bounds device memory held by un-drained readbacks; also "
             "CONFIG SET dispatch-ahead).  Default: 2.",
    )
    ap.add_argument(
        "--devices", default=None,
        help="device-sharded serving (ISSUE 8): map the 16384-slot table "
             "onto this many local devices ('all' = every jax.local_device); "
             "each object's banks live on the device owning its slot and "
             "frames dispatch down per-device lanes.  Default: one device "
             "(no placement).",
    )
    ap.add_argument(
        "--ready-fd", type=int, default=None,
        help="inherited fd to write one 'READY <host> <port> <pid>' line to "
             "once the listener is bound (the ClusterSupervisor's readiness "
             "protocol; with --port 0 this reports the kernel-chosen port)",
    )
    ap.add_argument(
        "--journal-dir", default=None,
        help="migration-journal directory to consult at boot: in-flight "
             "migrations naming this node re-arm their windows and fence "
             "their slots RECOVERING until resume_migrations settles them "
             "(the crashed-node restart discipline, migration.rearm_recovery)",
    )
    ap.add_argument(
        "--tls-cert", default=None,
        help="PEM certificate: enables TLS on the listener (with --tls-key) "
             "and on this node's OUTGOING cluster links (migration/"
             "replication) — the cross-host bus discipline: plaintext "
             "clients are refused at the handshake",
    )
    ap.add_argument("--tls-key", default=None,
                    help="PEM private key for --tls-cert")
    ap.add_argument(
        "--tls-ca", default=None,
        help="PEM CA bundle: additionally REQUIRE client certificates "
             "(mutual TLS) and pin the trust root for outgoing links",
    )
    ap.add_argument(
        "--retry-profile", default=None, choices=("lan", "wan"),
        help="link retry cadence for cluster-internal connections "
             "(net/retry.py LINK_PROFILES): 'lan' (default) keeps the "
             "historical tight schedules; 'wan' stretches backoff and "
             "deadlines for links that cross real networks.  Equivalent to "
             "RTPU_RETRY_PROFILE; the flag also exports the env var so "
             "coordinator code spawned from this process inherits it.",
    )
    args = ap.parse_args(argv)
    if bool(args.tls_cert) != bool(args.tls_key):
        ap.error("--tls-cert and --tls-key must be given together")
    if args.checkpoint_interval > 0 and not args.checkpoint:
        ap.error("--checkpoint-interval requires --checkpoint <path>")
    if args.platform:
        import os

        import jax

        # the flag WINS over an inherited JAX_PLATFORMS: a supervisor passes
        # --platform cpu precisely to keep N children off the one chip.  jax
        # is already imported (module imports above), so the env var alone
        # would be read too late — it is still exported for the processes
        # this one spawns.
        os.environ["JAX_PLATFORMS"] = args.platform
        jax.config.update("jax_platforms", args.platform)
    from redisson_tpu.core import ioplane as _iop

    if args.no_overlap:
        # flip the process-global switch too: the embedded Batch/pack paths
        # of THIS process must match the server's serial reply path
        _iop.set_overlap(False)
    if args.no_qos:
        _sched.set_qos(False)
    if args.no_preempt:
        _iop.set_preempt(False)
    if args.no_tier:
        from redisson_tpu.core import residency as _res_tier

        _res_tier.set_tier(False)
    if args.retry_profile:
        import os as _os

        from redisson_tpu.net import retry as _retry

        _os.environ["RTPU_RETRY_PROFILE"] = args.retry_profile
        _retry.set_retry_profile(args.retry_profile)
    gc.set_threshold(*GC_THRESHOLDS)
    engine = Engine()
    srv = TpuServer(
        engine,
        host=args.host,
        port=args.port,
        advertise_host=args.advertise_host,
        password=args.password,
        checkpoint_path=args.checkpoint,
        overlap=not args.no_overlap,
        workers=args.workers,
        devices=args.devices,
        qos=False if args.no_qos else None,
        dispatch_ahead=args.dispatch_ahead,
        tls_cert_file=args.tls_cert,
        tls_key_file=args.tls_key,
        tls_ca_file=args.tls_ca,
    )
    if args.restore and args.checkpoint:
        from redisson_tpu.core import checkpoint

        # a fresh boot has nothing to restore yet — the supervisor restart
        # path passes --restore unconditionally once a checkpoint dir exists
        import os as _os

        if _os.path.exists(args.checkpoint):
            checkpoint.load(engine, args.checkpoint)
    if args.residency and not args.no_tier:
        srv.enable_residency(sweep_interval=1.0)
    if args.prewarm:
        engine.prewarm()
    checkpointer = None
    if args.checkpoint and args.checkpoint_interval > 0:
        from redisson_tpu.core.checkpoint import AutoCheckpointer

        checkpointer = AutoCheckpointer(
            engine, args.checkpoint, args.checkpoint_interval
        ).start()
    try:
        # SIGTERM and SIGINT both land on the graceful path (the supervisor
        # stops nodes with SIGTERM; see serve_until_signal)
        asyncio.run(
            srv.serve_until_signal(
                ready_fd=args.ready_fd, journal_dir=args.journal_dir,
            ),
            loop_factory=srv.new_event_loop,
        )
    finally:
        if checkpointer is not None:
            # flush-on-stop: writes since the last interval tick reach disk
            # even on Ctrl-C / SIGTERM-driven exit
            checkpointer.stop()
    return 0


if __name__ == "__main__":
    main()

"""Per-frame stage-span tracing: the attribution plane (ISSUE 12 tentpole).

The serving path crosses five planes — parser, QoS scheduler, coalescer,
device lane (stage/dispatch/readback), reply writer — and until now the only
visibility was disjoint aggregates (IOStats sync counts, QosLedger in-flight,
MetricsRegistry command timers): a p99 regression could be *measured* but
never *attributed* to a stage.  This module is the Dapper-style answer
(PAPERS.md): every parsed frame is stamped with a trace id + monotonic t0,
and each chokepoint it crosses appends a **stage span**:

  ``recv``      — the read that brought the frame's first byte -> the read
                  that completed it (``reads``/``nbytes``/``feed_us``: a
                  1 MB command crosses several reads of at most the
                  server's _FRAME_CAP).  It lies BEFORE the
                  frame's t0, so its ``off_us`` is negative;
  ``parse``     — RESP bytes -> command list (read loop), from offset 0;
  ``qos``       — WindowScheduler classify/charge + bulk-gate wait
                  (tenant/class/items/shed annotated);
  ``hop``       — ``run_in_executor`` submit -> the worker's first line
                  (``to`` = ``dispatch`` or ``force``): the queue for a
                  pool thread plus the thread switch;
  ``wake``      — the mirror image of ``hop``: a worker's last line -> the
                  first line of the frame's coroutine after its ``await``
                  (``frm`` = ``dispatch`` or ``force``): ``call_soon_threadsafe``
                  plus the turns the loop took to come round.  A ``hop`` says
                  how long a worker was waited for, a ``wake`` how long the
                  loop was;
  ``dispatch``  — handler execution window for the whole frame;
  ``stage``     — device-lane gate wait (queueing ahead of the chip);
  ``kernel``    — ONE span per coalesced same-verb run (``members``, and
                  ``keys``: its first 32 members' keys, comma-joined); one
                  per single-item BF.ADD / BF.EXISTS too (``members`` 1);
  ``point.wait`` — such a point command's plan (its worker job's submit) ->
                  its device dispatch issued (``verb``): the queue for a
                  worker and the record's lock.  It lies over ``hop`` and
                  the head of ``dispatch``, so stage totals leave it out;
  ``readback``  — D2H force, annotated whether the frame PAID the blocking
                  sync (``blocking``) or rode a grouped fetch (``grouped``);
  ``reply``     — dispatch-done -> bytes written: the tail that makes the
                  trace total the true client-observable latency.  Its
                  children say what the tail was: ``reply.wait`` (for the
                  overlapped readback future, or in the writer's queue),
                  ``reply.wake`` (inside that wait: the force job's last
                  line -> the writer task has its result),
                  ``reply.encode`` and ``reply.write`` (``write`` + ``drain``;
                  ``nbytes``, ``batch`` = frames in that one write);
  ``host.gc`` / ``host.stall`` — on SLOW frames only (total at or over the
                  slowlog threshold): the part of the frame a host pause
                  overlapped (below).

Finished traces land in a **bounded, lock-light ring** (deque append is a
single GIL-atomic op), queryable over the wire (``TRACE GET/RESET/CONFIG``,
slowest-N by total or by stage), backing ``SLOWLOG`` (entries carry the
per-stage breakdown instead of Redis's flat duration) and ``LATENCY
HISTORY``; per-stage duration timers feed the server's MetricsRegistry so
``prometheus_text`` exports stage histograms.

**Host events** ride the same clock: while armed, a small bounded ring
records every garbage collection of a millisecond or more (``gc.callbacks``,
``gen`` annotated) and every turn of the server's event loop that lasted
5 ms or more (``stall``: for that long no socket was read and no hand-off
taken — a GC, a worker holding the GIL, a long synchronous call, or simply
more callbacks than a turn should carry; found where the loop selects,
``LoopSelector``, so a server inside somebody else's loop records none; a
pause that begins while the loop sleeps in ``select`` is sleep to that
clock — a collection on a worker thread is in the ring as ``gc``).
``TRACE EVENTS`` lists the ring, four monotone ``host_*`` totals ride
METRICS, and a slow frame is annotated with the events it overlapped — what
an operator asks of a slow log.

**The event loop's own account** is not tracing and is always on
(``LoopSelector``, below): the loop's turns and the seconds it spent outside
``select`` — METRICS ``host_loop_turns_total``,
``host_loop_busy_seconds_total`` — beside the CPU clocks of the loop's
thread, the pools' threads and the process, read at the scrape
(``thread_cpu_s``).

Arming follows the chaos-hook discipline (net/client.py ``_fault_plane``):

  * DISARMED (the default) every instrumentation site costs one module-
    global load plus an ``is None``/``is not None`` branch — no attribute
    chase, no call, no allocation (tests/test_observe.py asserts this at
    the allocator level against the discovered guard lines);
    the ``gc`` callback is not installed and the heartbeat wakes once a
    second to look at the guard;
  * ARMED (``RTPU_TRACE=1`` / ``set_tracing(True)`` / ``CONFIG SET
    trace-enabled yes``) replies are bit-identical to disarmed — the
    tracer only *observes* waits and work, it never reorders either.

One tracer per process (``TRACER``), same singleton discipline as
``ioplane.STATS``: production runs one server per process, so the ring IS
the per-server ring; in-process multi-server tests share it knowingly.
"""
from __future__ import annotations

import gc
import itertools
import os
import selectors
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

# span propagation across worker threads: the read loop stamps the frame,
# dispatch runs on pool threads, ioplane sites (lane gates, readbacks) are
# reached deep inside them — a thread-local carries the active FrameTrace
# so no kernel-adjacent signature needs to thread a trace argument through.
_tls = threading.local()


class Span:
    """One stage interval inside a frame: offsets are µs from the frame's
    t0, attrs is a small flat dict (tenant, device, blocking, ...)."""

    __slots__ = ("name", "off_us", "dur_us", "attrs")

    def __init__(self, name: str, off_us: int, dur_us: int,
                 attrs: Optional[dict] = None):
        self.name = name
        self.off_us = off_us
        self.dur_us = dur_us
        self.attrs = attrs


class FrameTrace:
    """One frame's trace: id, wall timestamp, monotonic t0, and the span
    list every chokepoint appends to.  Spans may be appended from several
    worker threads (device-sharded buckets); ``list.append`` is GIL-atomic,
    so the trace carries no lock — the lock-light half of the contract."""

    __slots__ = ("trace_id", "ts", "t0", "verbs", "n_cmds", "client_id",
                 "qos_class", "tenant", "spans", "dispatched_at", "hop_at",
                 "left_at", "total_us", "finished", "base_attrs")

    def __init__(self, trace_id: int, ts: float, t0: float, verbs: str,
                 n_cmds: int, client_id: int):
        self.trace_id = trace_id
        self.ts = ts          # wall-clock epoch seconds (SLOWLOG parity)
        self.t0 = t0          # monotonic anchor every span offsets from
        self.verbs = verbs
        self.n_cmds = n_cmds
        self.client_id = client_id
        self.qos_class: Optional[str] = None
        self.tenant: Optional[str] = None
        self.spans: List[Span] = []
        self.dispatched_at: Optional[float] = None
        # submit time of the executor hop in flight (one slot: a frame's
        # hops follow one another; a sharded plan's buckets, submitted in
        # one loop turn, share the stamp)
        self.hop_at = t0
        # the last line of the worker job that ended last (the same slot
        # discipline: a bucket frame's jobs each stamp it, the last to end
        # stamps it last)
        self.left_at = t0
        self.total_us = 0
        self.finished = False
        # attrs merged into EVERY span of this frame (replica-served frames
        # stamp replica=1 here, so per-stage breakdowns split by role)
        self.base_attrs: Optional[dict] = None

    def add_span(self, name: str, start: float, end: float,
                 **attrs) -> None:
        """Record one stage interval ([start, end] monotonic seconds).  An
        interval that began before t0 (``recv``) keeps its negative offset."""
        if self.base_attrs:
            attrs = {**self.base_attrs, **attrs}
        self.spans.append(Span(
            name,
            int((start - self.t0) * 1e6),
            max(0, int((end - start) * 1e6)),
            attrs or None,
        ))

    def mark_dispatched(self) -> None:
        """Dispatch finished; the remaining time to the reply write is the
        ``reply`` span (recorded by the writer task via finish_reply)."""
        self.dispatched_at = time.monotonic()

    def hopped(self, to: str) -> None:
        """A worker's first line: close the ``hop`` its submit site opened
        (``hop_at``) — the wait for a pool thread plus the thread switch."""
        self.add_span("hop", self.hop_at, time.monotonic(), to=to)

    def woke(self, frm: str) -> None:
        """The loop's first line after awaiting a worker: close the ``wake``
        the worker's last line opened (``left_at``) — the hand-off to the
        loop plus the turns it took to come round to this frame."""
        self.add_span("wake", self.left_at, time.monotonic(), frm=frm)

    def stage_totals(self) -> Dict[str, int]:
        """{stage: summed µs} — the SLOWLOG breakdown projection (child
        spans excluded: the ``reply.*`` children duplicate their ``reply``
        span's time, ``wave.plan`` its kernel span's, ``wave.answer`` its
        ``reply`` span's, and ``point.wait`` lies over the ``hop`` and the
        head of ``dispatch``)."""
        out: Dict[str, int] = {}
        for s in self.spans:
            if s.name.startswith(("reply.", "wave.", "point.")):
                continue
            out[s.name] = out.get(s.name, 0) + s.dur_us
        return out

    def stage_us(self, stage: str) -> int:
        return sum(s.dur_us for s in self.spans if s.name == stage)


class Tracer:
    """The process tracer: frame factory, bounded ring, SLOWLOG view,
    LATENCY samples, and the MetricsRegistry feed."""

    # LATENCY HISTORY depth (Redis keeps 160 samples per event)
    LATENCY_SAMPLES = 160
    # host-event ring: a collection enters it at GC_MIN_S (the young
    # generation collects thousands of times a minute in tens of µs — the
    # totals count those, the ring keeps what can explain a slow frame), a
    # turn of the loop at STALL_MIN_S; LONG_S is the size PERF.md gives
    # the stalls that make p99 unboundable
    HOST_EVENTS = 1024
    GC_MIN_S = 0.001
    STALL_MIN_S = 0.005
    LONG_S = 0.040

    def __init__(self, ring_capacity: int = 512,
                 slowlog_max_len: int = 128,
                 slowlog_slower_than_us: int = 10_000):
        self._ids = itertools.count(1)
        self._slowlog_ids = itertools.count(1)
        self._ring: deque = deque(maxlen=max(1, ring_capacity))
        self._slowlog: deque = deque(maxlen=max(1, slowlog_max_len))
        self.slowlog_slower_than_us = slowlog_slower_than_us
        self._lock = threading.Lock()   # inflight counter + reconfig only
        self._inflight = 0
        # per-stage (ts, ms) samples for LATENCY HISTORY
        self._latency: Dict[str, deque] = {}
        # MetricsRegistry receiving stage.<name> timers (server wires its
        # default registry here; None = no histogram feed)
        self.registry = None
        # host events (armed only): (kind, wall ts, monotonic start,
        # seconds, attrs) appended at the event's END, so the ring is in
        # end order; the monotone totals behind the host_* METRICS series
        self._host: deque = deque(maxlen=self.HOST_EVENTS)
        self._gc_t0: Optional[float] = None
        self.gc_pause_s = 0.0
        self.gc_long_pauses = 0
        self.loop_stall_s = 0.0
        self.loop_long_stalls = 0

    # -- frame lifecycle ------------------------------------------------------

    def begin_frame(self, ctx, commands, t0: Optional[float] = None
                    ) -> FrameTrace:
        now = time.monotonic()
        try:
            verb = bytes(commands[0][0]).upper().decode()
        except Exception:  # noqa: BLE001 — malformed frame still traces
            verb = "?"
        start = now if t0 is None else t0
        # the wall stamp is the anchor's, not the parse's end: a span's
        # wall position is ts + its offset
        tr = FrameTrace(
            next(self._ids), time.time() - (now - start), start,
            verb, len(commands), getattr(ctx, "client_id", 0),
        )
        if t0 is not None:
            tr.add_span("parse", t0, now)
        with self._lock:
            self._inflight += 1
        return tr

    def finish(self, trace: FrameTrace, end: Optional[float] = None) -> None:
        with self._lock:  # idempotent: abandon may race the writer's finish
            if trace.finished:
                return
            trace.finished = True
            self._inflight -= 1
        trace.total_us = max(
            0, int(((end if end is not None else time.monotonic())
                    - trace.t0) * 1e6)
        )
        self._ring.append(trace)
        thr = self.slowlog_slower_than_us
        slow = thr >= 0 and trace.total_us >= thr
        if slow:
            self._annotate_host_events(trace)
        totals = trace.stage_totals()
        if slow:
            self._slowlog.append((
                next(self._slowlog_ids), int(trace.ts), trace.total_us,
                trace, totals,
            ))
        reg = self.registry
        if reg is not None:
            reg.timer("stage.total").record(trace.total_us / 1e6)
            for stage, us in totals.items():
                reg.timer(f"stage.{stage}").record(us / 1e6)
        self._note_latency("total", trace.ts, trace.total_us / 1e3)
        for stage, us in totals.items():
            self._note_latency(stage, trace.ts, us / 1e3)

    def finish_reply(self, trace: FrameTrace, write_t0: float, nbytes: int,
                     batch: int) -> None:
        """Writer-task completion: close ``reply.write`` (the batch's
        ``write`` -> ``drain`` returned) and the ``reply`` span (dispatch-done
        -> bytes written), and finish the trace at the write timestamp —
        total therefore equals the client-observable latency."""
        now = time.monotonic()
        trace.add_span("reply.write", write_t0, now, nbytes=nbytes,
                       batch=batch)
        start = trace.dispatched_at if trace.dispatched_at is not None else now
        trace.add_span("reply", start, now)
        self.finish(trace, end=now)

    def abandon(self, trace: FrameTrace) -> None:
        """A frame whose replies never reached the wire (connection died
        mid-flight): close the books so the inflight census row drains."""
        self.finish(trace)

    def _note_latency(self, event: str, ts: float, ms: float) -> None:
        dq = self._latency.get(event)
        if dq is None:
            dq = self._latency.setdefault(
                event, deque(maxlen=self.LATENCY_SAMPLES)
            )
        dq.append((int(ts), ms))

    # -- host events ----------------------------------------------------------

    def note_stall(self, start: float, seconds: float) -> None:
        """A turn of the loop that began at ``start`` lasted ``seconds``,
        STALL_MIN_S or more (LoopSelector.select, armed only; one loop a
        server, so no two writers of these totals): a ``stall``."""
        self.loop_stall_s += seconds
        if seconds >= self.LONG_S:
            self.loop_long_stalls += 1
        self._host.append(("stall", time.time() - seconds, start, seconds,
                           None))

    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` entry (installed by set_tracing(True) only).
        Collections never nest and run under the GIL, so the start stamp is
        one slot; the callback takes no lock — it can fire between any two
        bytecodes of any thread, also under this tracer's own."""
        if phase == "start":
            self._gc_t0 = time.monotonic()
            return
        t0 = self._gc_t0
        if t0 is None:  # armed between a collection's start and its stop
            return
        self._gc_t0 = None
        seconds = time.monotonic() - t0
        self.gc_pause_s += seconds
        if seconds >= self.GC_MIN_S:
            if seconds >= self.LONG_S:
                self.gc_long_pauses += 1
            self._host.append(("gc", time.time() - seconds, t0, seconds,
                               {"gen": info.get("generation", -1)}))

    def _annotate_host_events(self, trace: FrameTrace) -> None:
        """A slow frame gets one ``host.gc`` / ``host.stall`` span for each
        ring event that overlapped it, clipped to the frame.  Newest first,
        and done at the first event that ended before the frame began: only
        slow frames pay, and no registry of frames in flight is needed.
        (A copy is walked: a collection may append to the ring meanwhile.)"""
        t0 = trace.t0
        end = t0 + trace.total_us / 1e6
        for kind, _ts, start, seconds, attrs in reversed(list(self._host)):
            if start + seconds <= t0:
                break
            if start < end:
                trace.add_span("host." + kind, max(start, t0),
                               min(start + seconds, end), **(attrs or {}))

    def host_events(self, n: Optional[int] = None) -> List[tuple]:
        """Newest-first: [(kind, wall ts, seconds, attrs), ...]."""
        items = [(k, ts, sec, attrs)
                 for k, ts, _start, sec, attrs in reversed(list(self._host))]
        return items if n is None else items[: max(0, n)]

    # -- queries --------------------------------------------------------------

    def entries(self) -> List[FrameTrace]:
        return list(self._ring)

    def slowest(self, n: int = 10, by: str = "total") -> List[FrameTrace]:
        """Slowest-N finished traces by total duration, or by one stage's
        summed duration (``by="qos"``, ``"readback"``, ...)."""
        traces = list(self._ring)
        if by in ("", "total"):
            key = lambda t: t.total_us  # noqa: E731
        else:
            key = lambda t: t.stage_us(by)  # noqa: E731
        traces.sort(key=key, reverse=True)
        return traces[: max(0, n)]

    def reset(self) -> None:
        self._ring.clear()
        self._host.clear()

    def set_ring_capacity(self, n: int) -> None:
        n = max(1, int(n))
        with self._lock:
            self._ring = deque(self._ring, maxlen=n)

    @property
    def ring_capacity(self) -> int:
        return self._ring.maxlen or 0

    # -- SLOWLOG view ---------------------------------------------------------

    def slowlog_get(self, n: Optional[int] = None) -> List[tuple]:
        """Newest-first (Redis order): [(id, ts, dur_us, trace,
        {stage: us}), ...]."""
        items = list(self._slowlog)
        items.reverse()
        return items if n is None else items[: max(0, n)]

    def slowlog_len(self) -> int:
        return len(self._slowlog)

    def slowlog_reset(self) -> None:
        self._slowlog.clear()

    def set_slowlog_max_len(self, n: int) -> None:
        with self._lock:
            self._slowlog = deque(self._slowlog, maxlen=max(1, int(n)))

    @property
    def slowlog_max_len(self) -> int:
        return self._slowlog.maxlen or 0

    # -- LATENCY view ---------------------------------------------------------

    def latency_events(self) -> List[str]:
        return sorted(self._latency)

    def latency_history(self, event: str) -> List[Tuple[int, float]]:
        dq = self._latency.get(event)
        return list(dq) if dq is not None else []

    def latency_reset(self, events=()) -> int:
        names = list(events) if events else list(self._latency)
        n = 0
        for ev in names:
            if self._latency.pop(ev, None) is not None:
                n += 1
        return n

    # -- census ---------------------------------------------------------------

    def census(self) -> Dict[str, float]:
        """Census rows: ring occupancy is BOUNDED by capacity; inflight
        must drain to 0 at quiesce (a begun frame whose reply never
        finished the books is a trace leak)."""
        return {
            "trace_ring_entries": float(len(self._ring)),
            "trace_inflight": float(self._inflight),
        }


# -- the event loop's account (always on) --------------------------------------


class LoopSelector(selectors.DefaultSelector):
    """The selector a server hands its own event loop
    (``asyncio.SelectorEventLoop(LoopSelector())``), counting where the loop
    selects: a TURN is what the loop runs between two calls of ``select`` —
    the callbacks of the events and hand-offs that were ready — and BUSY is
    the time it spent there, so 1 - busy / wall is the share of its life
    the loop slept waiting for work, and busy / turns what one more
    hand-off waits for the loop, on average, when it never sleeps.  Two
    clock reads, two adds and a comparison a turn; one loop, one writer, so
    no lock.  Readers on other threads see totals that only grow.  While
    tracing is armed a turn of ``Tracer.STALL_MIN_S`` or more is the
    tracer's ``stall`` host event."""

    def __init__(self):
        super().__init__()
        self.turns = 0
        self.busy_s = 0.0
        self._ran_at = time.monotonic()

    def select(self, timeout=None):
        ran = time.monotonic() - self._ran_at
        self.busy_s += ran
        self.turns += 1
        if ran >= Tracer.STALL_MIN_S:
            tracer = _tracer  # one read: another thread may disarm
            if tracer is not None:
                tracer.note_stall(self._ran_at, ran)
        ready = super().select(timeout)
        self._ran_at = time.monotonic()
        return ready


def thread_cpu_s(thread: Optional[threading.Thread]) -> float:
    """CPU seconds `thread` has run (user + system), read from its own
    clock: nothing is counted on the thread itself.  0.0 for no thread, or
    one that has ended (its clock goes with it)."""
    if thread is None or not thread.is_alive():
        return 0.0
    try:
        return time.clock_gettime(time.pthread_getcpuclockid(thread.ident))
    except OSError:  # it ended between the two lines
        return 0.0


# -- process-global arming (the chaos-hook discipline) -------------------------

TRACER = Tracer()

# THE guard every instrumentation site loads: None = disarmed (zero-cost),
# TRACER = armed.  Same shape as net/client.py `_fault_plane`.
_tracer: Optional[Tracer] = None


def tracing_enabled() -> bool:
    return _tracer is not None


def set_tracing(on: bool) -> bool:
    """Arm/disarm the process tracer; returns the previous armed state
    (callers restore it — the A/B discipline of RTPU_NO_QOS)."""
    global _tracer
    prev = _tracer is not None
    _tracer = TRACER if on else None
    # the gc callback exists only while armed: disarmed, a collection
    # calls nothing of ours
    if on and TRACER._on_gc not in gc.callbacks:
        gc.callbacks.append(TRACER._on_gc)
    elif not on and TRACER._on_gc in gc.callbacks:
        gc.callbacks.remove(TRACER._on_gc)
        TRACER._gc_t0 = None
    return prev


if os.environ.get("RTPU_TRACE", "") in ("1", "true", "yes"):
    set_tracing(True)


def current_trace() -> Optional[FrameTrace]:
    """The FrameTrace active on THIS thread (set by the dispatch wrappers),
    or None.  Only called from armed paths — disarmed sites branch on
    ``_tracer`` before reaching here."""
    return getattr(_tls, "trace", None)


def set_current(trace: FrameTrace) -> None:
    _tls.trace = trace


def clear_current() -> None:
    _tls.trace = None

"""Tracing plane tests (ISSUE 12): per-frame stage spans, TRACE/SLOWLOG/
LATENCY parity verbs, fleet scrape, and the two cost/safety contracts —

  * DISARMED guard sites allocate NOTHING (the chaos-hook zero-cost
    discipline, extended to every trace site by line discovery across
    server/server.py, core/ioplane.py, server/registry.py);
  * ARMED replies are BYTE-IDENTICAL to disarmed, including under the
    3-frames-in-flight overlapped-readback shape (the tracer observes
    waits and work, it never reorders either).
"""
import threading
import time

import numpy as np
import pytest

from redisson_tpu.net.client import Connection
from redisson_tpu.observe import trace as obs
from redisson_tpu.server.server import ServerThread


@pytest.fixture(autouse=True)
def _restore_tracing():
    """Every test leaves the process tracer exactly as it found it (ring
    drained): a leaked armed tracer would silently tax every later test."""
    prev = obs.tracing_enabled()
    yield
    obs.set_tracing(prev)
    obs.TRACER.reset()
    obs.TRACER.slowlog_reset()
    obs.TRACER.latency_reset()
    obs.TRACER.slowlog_slower_than_us = 10_000


def _conn(st, timeout=60.0):
    return Connection(st.server.host, st.server.port, timeout=timeout)


# -- zero-alloc disarmed guards (discovery across every instrumented file) ----


def _trace_guard_lines(mod):
    """Line numbers of every tracing guard in `mod` — the exact sites the
    zero-cost contract covers.  Guards are written in one of three shapes
    (enforced here by discovery, like the fault-plane test): a read of the
    process-global ``_tracer``, a ``trace is not None`` branch on the
    threaded-through frame trace, or the lane occupancy's ``_tcur`` slot."""
    path = mod.__file__
    tokens = ("_tracer", "trace is not None", "_tcur", "done_tr is not None",
              "cur is not None", "current_trace()")
    lines = []
    with open(path) as fh:
        for no, line in enumerate(fh, 1):
            if "def " in line or "import" in line:
                continue
            if any(tok in line for tok in tokens):
                lines.append(no)
    return path, sorted(set(lines))


def test_trace_disarmed_guard_sites_allocate_nothing():
    """With tracing disarmed, a full wire workload crossing every
    instrumented chokepoint (parse, qos, dispatch, coalesced run, grouped
    readback, reply writer) must not allocate ANYTHING attributable to the
    discovered guard lines — the same allocator-level contract the
    fault-plane hooks carry (tests/test_perf_smoke.py)."""
    import tracemalloc

    import redisson_tpu.core.ioplane as ioplane_mod
    import redisson_tpu.server.registry as registry_mod
    import redisson_tpu.server.server as server_mod

    assert not obs.tracing_enabled(), "tracing leaked armed from another test"
    guards = {}
    for mod, floor in ((server_mod, 8), (ioplane_mod, 2), (registry_mod, 1)):
        path, lines = _trace_guard_lines(mod)
        assert len(lines) >= floor, (
            f"{path}: found only {len(lines)} trace guards — discovery "
            "tokens drifted from the instrumentation idiom"
        )
        guards[path] = set(lines)

    with ServerThread(port=0, workers=2) as st:
        conn = _conn(st)
        try:
            blob = np.ascontiguousarray(
                np.arange(128, dtype=np.int64) * 2654435761, "<i8"
            ).tobytes()
            assert conn.execute("BF.RESERVE", "za:bf", 0.01, 10_000) in (
                b"OK", "OK",
            )
            frame = [
                ("SET", "za:k", b"v"),
                ("BF.MADD64", "za:bf", blob),
                ("BF.MADD64", "za:bf", blob),   # coalescible run
                ("BF.MEXISTS64", "za:bf", blob),  # grouped readback
                ("PING",),
            ]
            conn.execute_many(frame, timeout=60.0)  # warm every lazy path
            tracemalloc.start(1)
            try:
                for _ in range(60):
                    conn.execute_many(frame, timeout=60.0)
                snap = tracemalloc.take_snapshot()
            finally:
                tracemalloc.stop()
        finally:
            conn.close()
    offenders = [
        (tb.filename, tb.lineno, stat.size)
        for stat in snap.statistics("lineno")
        for tb in [stat.traceback[0]]
        if tb.filename in guards and tb.lineno in guards[tb.filename]
        and stat.size > 0
    ]
    assert not offenders, (
        f"trace guard lines allocated with tracing DISARMED: {offenders}"
    )


# -- armed/disarmed byte-identity under overlapped readbacks -------------------


def _inflight_replies(traced: bool):
    """10 mixed frames, at most 3 in flight (the overlapped-readback shape
    the dispatch-ahead bound allows), replies drained in FIFO order."""
    prev = obs.set_tracing(traced)
    try:
        with ServerThread(port=0, workers=4) as st:
            conn = _conn(st, timeout=120.0)
            try:
                assert conn.execute("BF.RESERVE", "bi:bf", 0.01, 50_000) in (
                    b"OK", "OK",
                )
                out = []
                inflight = []
                for f in range(10):
                    keys = (
                        np.arange(400, dtype=np.int64) + f * 1000
                    ) * 2654435761
                    blob = np.ascontiguousarray(keys, "<i8").tobytes()
                    cmds = [
                        ("ECHO", f"f{f}".encode()),
                        ("BF.MADD64", "bi:bf", blob),
                        ("BF.MEXISTS64", "bi:bf", blob),
                        ("INCR", "bi:ctr"),
                    ]
                    inflight.append(conn.execute_many_lazy(cmds))
                    if len(inflight) > 3:  # 3 frames in flight
                        out.extend(inflight.pop(0).get(timeout=120.0))
                for h in inflight:
                    out.extend(h.get(timeout=120.0))
                return out
            finally:
                conn.close()
    finally:
        obs.set_tracing(prev)
        obs.TRACER.reset()
        obs.TRACER.slowlog_reset()


def test_armed_replies_byte_identical_with_three_frames_in_flight():
    a = _inflight_replies(traced=True)
    b = _inflight_replies(traced=False)
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        assert x == y, f"reply {i} diverged between tracing armed/disarmed"


# -- bounded ring + census drain ----------------------------------------------


def test_trace_ring_bounded_and_census_drains():
    from redisson_tpu.chaos.census import ResourceCensus

    obs.set_tracing(True)
    obs.TRACER.reset()
    with ServerThread(port=0) as st:
        census = ResourceCensus()
        census.track_server("srv", st.server)
        snap = census.snapshot()
        assert "srv.trace_ring_entries" in snap
        assert "srv.trace_inflight" in snap
        conn = _conn(st)
        try:
            assert conn.execute(
                "CONFIG", "SET", "trace-ring-capacity", "16"
            ) in (b"OK", "OK")
            # sustained load far past the ring capacity
            for _ in range(20):
                conn.execute_many([("PING",)] * 5, timeout=30.0)
            deadline = time.time() + 5
            while time.time() < deadline:
                c = st.server.tracer.census()
                if c["trace_inflight"] == 0:
                    break
                time.sleep(0.02)
            c = st.server.tracer.census()
            assert 0 < c["trace_ring_entries"] <= 16, c
            assert c["trace_inflight"] == 0, (
                "begun frames did not close their books at quiesce"
            )
            # metrics gauges carry the same rows
            mets = st.server.metrics.snapshot()
            assert 0 < mets["trace_ring_entries"] <= 16
            assert mets["trace_inflight"] == 0
            assert conn.execute("TRACE", "RESET") in (b"OK", "OK")
            # the RESET frame is itself traced and finishes AFTER the reset
            # applied — at most that one entry may remain
            time.sleep(0.1)
            assert st.server.tracer.census()["trace_ring_entries"] <= 1
        finally:
            conn.close()


# -- the acceptance waterfall: qos wait vs readback, separately attributed ----


def test_trace_get_waterfall_attributes_qos_wait_and_readback():
    """Hostile config2q-style mix, traced end to end: over the wire,
    TRACE GET must show a bulk frame whose `qos` span carries the bulk-gate
    wait and an interactive frame whose `readback` span carries the D2H —
    the two attributions that were previously indistinguishable."""
    obs.set_tracing(True)
    obs.TRACER.reset()
    blob = np.ascontiguousarray(
        np.arange(20_000, dtype=np.int64) * 2654435761, "<i8"
    ).tobytes()
    probe = np.ascontiguousarray(
        np.arange(64, dtype=np.int64) * 40503, "<i8"
    ).tobytes()
    with ServerThread(port=0, workers=4) as st:
        assert st.server.scheduler.armed
        admin = _conn(st)
        try:
            assert admin.execute("CONFIG", "SET", "qos-bulk-slots", "1") in (
                b"OK", "OK",
            )
            for i in range(2):
                admin.execute("BF.RESERVE", f"wf:bulk{i}{{hog}}", 0.01, 40_000)
            admin.execute("BF.RESERVE", "wf:int{ta}", 0.01, 10_000)
            admin.execute("BF.MADD64", "wf:int{ta}", probe)
        finally:
            admin.close()
        stop = threading.Event()
        errors = []

        def hog(j):
            try:
                c = _conn(st, timeout=120.0)
                try:
                    c.execute("CLIENT", "QOS", "CLASS", "bulk", "TENANT", "hog")
                    frame = [
                        ("BF.MADD64", f"wf:bulk{i}{{hog}}", blob)
                        for i in range(2)
                    ]
                    while not stop.is_set():
                        c.execute_many(frame, timeout=120.0)
                finally:
                    c.close()
            except Exception as e:  # noqa: BLE001
                if not stop.is_set():
                    errors.append(e)

        def interactive():
            try:
                c = _conn(st, timeout=120.0)
                try:
                    c.execute(
                        "CLIENT", "QOS", "CLASS", "interactive", "TENANT", "ta"
                    )
                    while not stop.is_set():
                        c.execute("BF.MEXISTS64", "wf:int{ta}", probe,
                                  timeout=120.0)
                finally:
                    c.close()
            except Exception as e:  # noqa: BLE001
                if not stop.is_set():
                    errors.append(e)

        threads = [
            threading.Thread(target=hog, args=(j,), daemon=True)
            for j in range(3)
        ] + [threading.Thread(target=interactive, daemon=True)]
        for t in threads:
            t.start()
        time.sleep(2.5)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors

        wire = _conn(st)
        try:
            entries = wire.execute("TRACE", "GET", "200", timeout=30.0)
        finally:
            wire.close()
    assert entries, "trace ring empty after a traced run"

    def spans_of(entry):
        return {
            bytes(s[0]).decode(): s for s in entry[7]
        }

    bulk_qos_waits = [
        spans_of(e)["qos"][2]
        for e in entries
        if bytes(e[5]) == b"bulk" and "qos" in spans_of(e)
    ]
    interactive_readbacks = [
        spans_of(e)["readback"][2]
        for e in entries
        if bytes(e[5]) == b"interactive" and "readback" in spans_of(e)
    ]
    # with bulk-slots=1 and 3 hog connections, somebody's frame sat behind
    # the admission gate for at least a millisecond
    assert bulk_qos_waits and max(bulk_qos_waits) > 1_000, bulk_qos_waits
    assert interactive_readbacks, (
        "no interactive frame recorded a readback span"
    )
    # the two attributions are on DIFFERENT frames: an interactive frame's
    # qos span (when present) is admission work, not the gate wait
    int_qos = [
        spans_of(e)["qos"][2]
        for e in entries
        if bytes(e[5]) == b"interactive" and "qos" in spans_of(e)
    ]
    assert int_qos and max(int_qos) < max(bulk_qos_waits), (
        "interactive frames waited on the bulk gate — attribution is wrong"
    )
    # the frame's anchors keep their meaning under the closed waterfall:
    # `parse` starts at offset 0 (t0 = the read that completed the frame),
    # `recv` lies before it and ends there, and `reply`, `reply.write` and
    # the total all end at the write
    for e in entries:
        sp = spans_of(e)
        assert sp["parse"][1] == 0, e
        assert sp["recv"][1] <= 0 and abs(sp["recv"][1] + sp["recv"][2]) <= 2, e
        assert abs(sp["reply"][1] + sp["reply"][2] - e[2]) <= 2, e
        assert abs(sp["reply.write"][1] + sp["reply.write"][2] - e[2]) <= 2, e
    _assert_hops_lead_somewhere(entries)
    _assert_reply_children(entries)
    # coalesced bulk runs recorded ONE kernel span naming its members' keys
    kernel_entries = [
        e for e in entries
        if bytes(e[5]) == b"bulk" and "kernel" in spans_of(e)
    ]
    if kernel_entries:
        e = kernel_entries[0]
        assert not [s for s in e[7] if bytes(s[0]) == b"kernel.member"]
        kernels = [s for s in e[7] if bytes(s[0]) == b"kernel"]
        assert len(kernels) >= 1
        a = _attrs(kernels[0])
        assert len(bytes(a["keys"]).split(b",")) == a["members"] >= 2


# -- the closed waterfall: recv, hop, the inside of reply ----------------------


def _named(entry, name):
    return [s for s in entry[7] if bytes(s[0]) == name]


def _attrs(span):
    return {bytes(span[3][i]).decode(): span[3][i + 1]
            for i in range(0, len(span[3]), 2)}


def _assert_hops_lead_somewhere(entries):
    """Every hop names where it went and ends at or before the start of
    the `dispatch` (`to=force`: the `readback`) it leads to; a frame with a
    `dispatch` crossed an executor, so it has a hop."""
    for e in entries:
        hops = _named(e, b"hop")
        if _named(e, b"dispatch"):
            assert hops, e
        for h in hops:
            to = bytes(_attrs(h)["to"])
            assert to in (b"dispatch", b"force"), e
            end = h[1] + h[2]
            if to == b"dispatch":
                assert any(d[1] >= end for d in _named(e, b"dispatch")), e
            else:
                assert all(r[1] >= end for r in _named(e, b"readback")), e


def _assert_reply_children(entries):
    """`reply.wait`, `reply.encode`, `reply.write`: one each, inside
    `reply`, not overlapping (2 us for the integer offsets); `reply.wake`,
    where the writer task awaited the force job, is the end of the wait."""
    for e in entries:
        (reply,) = _named(e, b"reply")
        for wake in _named(e, b"reply.wake"):
            (wait,) = _named(e, b"reply.wait")
            assert wait[1] - 2 <= wake[1], e
            assert abs(wake[1] + wake[2] - wait[1] - wait[2]) <= 2, e
        kids = sorted(
            (s for s in e[7] if bytes(s[0]).startswith(b"reply.")
             and bytes(s[0]) != b"reply.wake"),
            key=lambda s: (s[1], s[1] + s[2]),
        )
        assert sorted(bytes(s[0]) for s in kids) == [
            b"reply.encode", b"reply.wait", b"reply.write"], e
        assert kids[0][1] >= reply[1] - 2, e
        assert kids[-1][1] + kids[-1][2] <= reply[1] + reply[2] + 2, e
        for x, y in zip(kids, kids[1:]):
            assert x[1] + x[2] <= y[1] + 2, e
        w = _attrs(_named(e, b"reply.write")[0])
        assert w["nbytes"] > 0 and w["batch"] >= 1, e


def _traced_mixed_frames(st):
    """A coalesced run, a lone laned command, readbacks (overlapped) and
    readback-free frames, traced: the shapes every dispatch wrapper and
    both writer-queue item kinds see."""
    blob = np.ascontiguousarray(
        np.arange(2_000, dtype=np.int64) * 2654435761, "<i8"
    ).tobytes()
    conn = _conn(st)
    try:
        conn.execute("BF.RESERVE", "cw:bf", 0.01, 50_000)
        conn.execute_many([
            ("SET", "cw:k", b"v"),
            ("BF.MADD64", "cw:bf", blob),
            ("BF.MADD64", "cw:bf", blob),
            ("BF.MEXISTS64", "cw:bf", blob),
            ("PING",),
        ], timeout=60.0)
        conn.execute("BF.MEXISTS64", "cw:bf", blob)
        conn.execute("PING")
        conn.execute("GET", "cw:k")
        time.sleep(0.1)
        return conn.execute("TRACE", "GET", "100", timeout=30.0)
    finally:
        conn.close()


def test_recv_span_covers_a_frame_that_arrives_in_three_writes():
    """A frame's arrival is a span of its own, BEFORE t0: the frame's total
    and `parse` keep their meaning, and the pauses between the sender's
    writes are in `recv`, not in nothing."""
    import socket

    from redisson_tpu.net import resp

    obs.set_tracing(True)
    obs.TRACER.reset()
    payload = resp.encode_command("SET", "rx:k", b"x" * 300_000)
    cuts = (0, 100_000, 200_000, len(payload))
    with ServerThread(port=0) as st:
        sock = socket.create_connection((st.server.host, st.server.port))
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            for lo, hi in zip(cuts, cuts[1:]):
                if lo:
                    time.sleep(0.15)
                sock.sendall(payload[lo:hi])
            got = b""
            while not got.endswith(b"\r\n"):
                got += sock.recv(64)
            assert got == b"+OK\r\n"
        finally:
            sock.close()
        wire = _conn(st)
        try:
            time.sleep(0.1)
            entries = wire.execute("TRACE", "GET", "20")
        finally:
            wire.close()
    (e,) = [e for e in entries if bytes(e[3]) == b"SET"]
    (recv,) = _named(e, b"recv")
    a = _attrs(recv)
    assert a["reads"] >= 3 and a["nbytes"] == len(payload), a
    assert a["feed_us"] >= 0
    # two pauses of 150 ms lie inside it (less what a busy host took to
    # make the first read); it ends where the frame begins
    assert recv[1] < 0 and recv[2] >= 240_000, recv
    assert abs(recv[1] + recv[2]) <= 2, recv
    (parse,) = _named(e, b"parse")
    assert parse[1] == 0
    # total_us is still completing-read -> reply written: no pause in it
    assert e[2] < 140_000, e
    assert all(s[1] >= 0 for s in e[7] if bytes(s[0]) != b"recv"), e


def test_every_executor_crossing_has_a_hop():
    obs.set_tracing(True)
    obs.TRACER.reset()
    with ServerThread(port=0, workers=4) as st:
        entries = _traced_mixed_frames(st)
    assert len(entries) >= 5
    _assert_hops_lead_somewhere(entries)
    tos = {bytes(_attrs(h)["to"]) for e in entries for h in _named(e, b"hop")}
    assert tos == {b"dispatch", b"force"}, tos
    # the pipelined frame crosses once for SET, once for the coalesced run,
    # once for the probe, once for PING and once more to force — if it
    # arrived whole; cut in two by the socket, its larger part still hops
    # once a dispatch
    big = max(entries, key=lambda e: e[4])
    assert big[4] >= 3 and len(_named(big, b"hop")) >= 2, big


def test_reply_children_lie_inside_reply_and_stage_totals_skip_them():
    obs.set_tracing(True)
    obs.TRACER.reset()
    obs.TRACER.slowlog_slower_than_us = 0  # every frame into SLOWLOG
    with ServerThread(port=0, workers=4) as st:
        assert st.server.overlap
        entries = _traced_mixed_frames(st)
        conn = _conn(st)
        try:
            slow = conn.execute("SLOWLOG", "GET", "50")
        finally:
            conn.close()
    _assert_reply_children(entries)
    with_readback = [e for e in entries if _named(e, b"readback")]
    without = [e for e in entries if not _named(e, b"readback")]
    assert with_readback and without
    for e in with_readback:
        # the overlapped readback is what the writer task waited for
        (wait,) = _named(e, b"reply.wait")
        (rb,) = _named(e, b"readback")
        assert wait[1] <= rb[1] and rb[1] + rb[2] <= wait[1] + wait[2] + 2, e
    # `reply` is counted once: the children are in no per-stage projection
    for tr in obs.TRACER.entries():
        totals = tr.stage_totals()
        assert "reply" in totals and "recv" in totals
        assert not [k for k in totals if k.startswith("reply.")], totals
    stages = {bytes(s[0]) for entry in slow for s in entry[4]}
    assert b"reply" in stages and b"hop" in stages
    assert not [s for s in stages if s.startswith(b"reply.")], stages


# -- the host's pauses: gc and a blocked event loop ----------------------------


_ARMED_HOST_SERIES = {
    "rtpu_host_gc_pause_seconds_total",
    "rtpu_host_gc_long_pauses_total",
    "rtpu_host_loop_stall_seconds_total",
    "rtpu_host_loop_long_stalls_total",
}


def _host_series(conn):
    """The host's pauses as METRICS has them: the armed-only series (the
    loop's account and the CPU clocks beside them are always on:
    tests/test_loop_account.py)."""
    return {
        line.split()[0]: float(line.split()[1])
        for line in bytes(conn.execute("METRICS")).decode().splitlines()
        if line.split()[0] in _ARMED_HOST_SERIES
    }


def _big_heap():
    """Enough containers that a full collection takes well over 40 ms."""
    return [[i] for i in range(2_500_000)]


def test_host_pauses_are_counted_listed_and_put_on_the_slow_frame():
    import gc

    obs.set_tracing(True)
    obs.TRACER.reset()
    assert obs.TRACER._on_gc in gc.callbacks
    junk = _big_heap()
    with ServerThread(port=0) as st:
        conn = _conn(st)
        waiter = _conn(st)
        try:
            before = _host_series(conn)
            assert set(before) == _ARMED_HOST_SERIES
            # a frame that is in flight (parked in BLPOP on a worker) while
            # the host pauses twice: a full collection, then a synchronous
            # sleep on the server's own loop
            parked = waiter.execute_many_lazy([("BLPOP", "hp:q", "20")])
            time.sleep(0.1)
            # (gc.collect() returns at once while another thread collects —
            # the big heap makes automatic passes long too: ask until a long
            # pause has ENDED with the frame parked)
            longs = obs.TRACER.gc_long_pauses
            deadline = time.monotonic() + 20.0
            while (obs.TRACER.gc_long_pauses == longs
                   and time.monotonic() < deadline):
                gc.collect()
            time.sleep(0.05)
            st.server._loop.call_soon_threadsafe(time.sleep, 0.06)
            time.sleep(0.15)
            conn.execute("RPUSH", "hp:q", b"x")
            assert parked.get(timeout=30.0) == [[b"hp:q", b"x"]]
            time.sleep(0.1)
            after = _host_series(conn)
            events = conn.execute("TRACE", "EVENTS")
            slowest = conn.execute("TRACE", "GET", "5", "BY", "host.stall")
        finally:
            conn.close()
            waiter.close()
    del junk
    for name in before:
        assert after[name] > before[name], (name, before, after)
    gcs = [ev for ev in events if bytes(ev[0]) == b"gc"]
    stalls = [ev for ev in events if bytes(ev[0]) == b"stall"]
    # [kind, unix_ms, dur_us, attrs], newest first
    assert any(ev[2] >= 40_000 and ev[3] == [b"gen", 2] for ev in gcs), gcs
    assert any(ev[2] >= 40_000 for ev in stalls), stalls
    assert all(ev[1] > 1_600_000_000_000 for ev in events)
    ends = [ev[1] + ev[2] / 1000 for ev in events]  # newest (by end) first
    assert all(x >= y - 2 for x, y in zip(ends, ends[1:])), events
    (frame,) = [e for e in slowest if bytes(e[3]) == b"BLPOP"]
    hgc = [s for s in _named(frame, b"host.gc") if s[2] >= 40_000]
    hst = [s for s in _named(frame, b"host.stall") if s[2] >= 40_000]
    assert hgc and hst, frame
    assert _attrs(hgc[0])["gen"] == 2
    for s in hgc + hst:  # clipped to the frame
        assert s[1] >= 0 and s[1] + s[2] <= frame[2], (s, frame[2])
    # TRACE RESET clears the event ring too
    assert obs.TRACER.host_events()
    obs.TRACER.reset()
    assert not obs.TRACER.host_events()


def test_disarmed_host_plane_installs_and_records_nothing():
    import gc

    obs.set_tracing(False)
    obs.TRACER.reset()
    assert obs.TRACER._on_gc not in gc.callbacks
    junk = _big_heap()
    with ServerThread(port=0) as st:
        conn = _conn(st)
        try:
            before = _host_series(conn)
            gc.collect()
            st.server._loop.call_soon_threadsafe(time.sleep, 0.06)
            conn.execute("PING")
            # nothing polls the guard: an idle server's loop takes no turns
            turns = st.server._loop_selector.turns
            time.sleep(1.0)
            assert st.server._loop_selector.turns - turns <= 3
            assert _host_series(conn) == before
            assert conn.execute("TRACE", "EVENTS") == []
        finally:
            conn.close()
    del junk
    assert not obs.TRACER.host_events()
    # arming installs the one callback, once; disarming takes it away
    obs.set_tracing(True)
    obs.set_tracing(True)
    assert gc.callbacks.count(obs.TRACER._on_gc) == 1
    obs.set_tracing(False)
    assert obs.TRACER._on_gc not in gc.callbacks


# -- SLOWLOG parity verbs ------------------------------------------------------


def test_slowlog_get_reset_len_with_threshold():
    obs.set_tracing(True)
    with ServerThread(port=0) as st:
        conn = _conn(st)
        try:
            # impossible threshold: nothing logs
            assert conn.execute(
                "CONFIG", "SET", "slowlog-log-slower-than", "-1"
            ) in (b"OK", "OK")
            st.server.tracer.slowlog_reset()
            conn.execute("PING")
            conn.execute("SET", "sl:k", b"v")
            time.sleep(0.1)
            assert conn.execute("SLOWLOG", "LEN") == 0
            # log-everything threshold
            conn.execute("CONFIG", "SET", "slowlog-log-slower-than", "0")
            conn.execute("SET", "sl:k2", b"v2")
            conn.execute("GET", "sl:k2")
            deadline = time.time() + 5
            while time.time() < deadline and conn.execute("SLOWLOG", "LEN") < 2:
                time.sleep(0.02)
            n = conn.execute("SLOWLOG", "LEN")
            assert n >= 2, n
            entries = conn.execute("SLOWLOG", "GET", "2")
            assert len(entries) == 2
            sid, ts, dur_us, cmd, stages = entries[0]
            assert sid > 0 and ts > 0 and dur_us >= 0
            # per-stage breakdown instead of Redis's flat duration
            stage_names = {bytes(s[0]) for s in stages}
            assert b"dispatch" in stage_names and b"reply" in stage_names
            # newest-first ordering (Redis parity)
            assert entries[0][0] > entries[1][0]
            assert conn.execute("SLOWLOG", "RESET") in (b"OK", "OK")
            # the RESET verb's own frame may re-log (threshold 0): raise it
            conn.execute(
                "CONFIG", "SET", "slowlog-log-slower-than", "10000000"
            )
            st.server.tracer.slowlog_reset()
            assert conn.execute("SLOWLOG", "LEN") == 0
        finally:
            conn.close()


# -- INFO commandstats + LATENCY ----------------------------------------------


def test_info_commandstats_section():
    with ServerThread(port=0) as st:
        conn = _conn(st)
        try:
            conn.execute("SET", "cs:k", b"v")
            conn.execute("GET", "cs:k")
            conn.execute("PING")
            text = bytes(conn.execute("INFO", "commandstats")).decode()
            assert text.startswith("# Commandstats")
            assert "cmdstat_set:calls=" in text
            assert "usec_per_call=" in text
            # plain INFO keeps its historical sections, commandstats-free
            plain = bytes(conn.execute("INFO")).decode()
            assert "cmdstat_" not in plain and "# Server" in plain
            # INFO all appends the section
            everything = bytes(conn.execute("INFO", "all")).decode()
            assert "# Server" in everything and "cmdstat_get:" in everything
        finally:
            conn.close()


def test_latency_history_and_reset_over_stage_histograms():
    obs.set_tracing(True)
    obs.TRACER.latency_reset()
    with ServerThread(port=0) as st:
        conn = _conn(st)
        try:
            for _ in range(5):
                conn.execute("PING")
            deadline = time.time() + 5
            while time.time() < deadline:
                if conn.execute("LATENCY", "HISTORY", "total"):
                    break
                time.sleep(0.02)
            hist = conn.execute("LATENCY", "HISTORY", "total")
            assert hist, "no total-latency samples after traced traffic"
            ts, ms = hist[-1]  # (unix ts, ms) — the Redis LATENCY contract
            assert ts > 0 and ms >= 1
            assert conn.execute("LATENCY", "HISTORY", "dispatch")
            latest = conn.execute("LATENCY", "LATEST")
            events = {bytes(row[0]) for row in latest}
            assert b"total" in events and b"dispatch" in events
            # disarm first: the RESET frame itself would otherwise re-seed
            # the event it just cleared when its reply span closes
            obs.set_tracing(False)
            time.sleep(0.05)
            n = conn.execute("LATENCY", "RESET", "total")
            assert n == 1
            assert conn.execute("LATENCY", "HISTORY", "total") == []
            # stage histograms also feed the MetricsRegistry exposition
            text = bytes(conn.execute("METRICS")).decode()
            assert "rtpu_stage_dispatch_count" in text
            assert "rtpu_stage_total_p99_seconds" in text
        finally:
            conn.close()


# -- exported gauges (the satellite bugfix) -----------------------------------


def test_dropped_pushes_and_shed_counters_in_prometheus_exposition():
    with ServerThread(port=0) as st:
        conn = _conn(st)
        try:
            text = bytes(conn.execute("METRICS")).decode()
        finally:
            conn.close()
    # dropped_pushes was census-only before ISSUE 12; the QoS cumulative
    # shed counters ride the same default registry
    assert "rtpu_dropped_pushes " in text
    assert "rtpu_qos_shed_ops " in text
    assert "rtpu_qos_shed_frames " in text
    assert "rtpu_trace_ring_entries " in text


# -- fleet-wide scrape ---------------------------------------------------------


def test_merge_prometheus_texts_labels_every_line():
    from redisson_tpu.utils.metrics import merge_prometheus_texts

    merged = merge_prometheus_texts({
        "h1:1": "rtpu_keys 3.0\nrtpu_up 1\n",
        "h2:2": 'rtpu_keys 5.0\nrtpu_lat{q="p99"} 0.2\n# comment\n',
    })
    lines = merged.strip().splitlines()
    assert 'rtpu_keys{node="h1:1"} 3.0' in lines
    assert 'rtpu_keys{node="h2:2"} 5.0' in lines
    # an existing label set keeps its labels, node appended
    assert 'rtpu_lat{q="p99",node="h2:2"} 0.2' in lines
    assert not any(line.startswith("#") for line in lines)


def test_metrics_cluster_aggregates_the_fleet():
    """The wire half of the one-pane-of-glass: METRICS CLUSTER on one node
    scrapes every master in its view and returns one labeled exposition."""
    with ServerThread(port=0) as a, ServerThread(port=0) as b:
        from redisson_tpu.utils.crc16 import calc_slot

        view = [
            ("0", "8191", a.server.host, str(a.server.port),
             a.server.node_id),
            ("8192", "16383", b.server.host, str(b.server.port),
             b.server.node_id),
        ]
        flat = [x for row in view for x in row]
        # a key whose slot the SECOND node owns
        key = next(
            f"mc:{i}" for i in range(500)
            if calc_slot(f"mc:{i}".encode()) >= 8192
        )
        ca = _conn(a)
        cb = _conn(b)
        try:
            assert ca.execute("CLUSTER", "SETVIEW", *flat) in (b"OK", "OK")
            assert cb.execute("CLUSTER", "SETVIEW", *flat) in (b"OK", "OK")
            assert cb.execute("SET", key, b"v") in (b"OK", "OK")
            text = bytes(ca.execute("METRICS", "CLUSTER")).decode()
        finally:
            ca.close()
            cb.close()
    la = f'node="{a.server.host}:{a.server.port}"'
    lb = f'node="{b.server.host}:{b.server.port}"'
    assert la in text and lb in text
    assert f"rtpu_keys{{{lb}}} 1.0" in text


def test_supervisor_scrape_merges_live_nodes():
    """ClusterSupervisor.scrape() — driven against in-process listeners
    (the supervisor half shares merge_prometheus_texts with the METRICS
    CLUSTER verb; real-process supervision is covered by
    tests/test_cluster_proc.py).  A dead node contributes nothing."""
    from redisson_tpu.cluster.supervisor import ClusterSupervisor

    class FakeNode:
        def __init__(self, host, port, up=True):
            self.host, self.port, self._up = host, port, up

        @property
        def address(self):
            return f"{self.host}:{self.port}"

        def alive(self):
            return self._up

    with ServerThread(port=0) as a, ServerThread(port=0) as b:
        sup = ClusterSupervisor(masters=1)  # construction only, never started
        sup.masters = [
            FakeNode(a.server.host, a.server.port),
            FakeNode(b.server.host, b.server.port),
            FakeNode("127.0.0.1", 1, up=False),  # dead: skipped silently
        ]
        text = sup.scrape()
    assert f'node="{a.server.host}:{a.server.port}"' in text
    assert f'node="{b.server.host}:{b.server.port}"' in text
    assert 'node="127.0.0.1:1"' not in text
    assert "rtpu_keys{" in text


# -- perf gate: armed-overhead row --------------------------------------------


def test_perf_gate_obs_overhead_row():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "perf_gate",
        os.path.join(os.path.dirname(__file__), "..", "tools", "perf_gate.py"),
    )
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)

    base = {"metric": "x", "value": 1000.0, "details": {}}

    def doc(ratio):
        return {
            "metric": "x", "value": 1000.0,
            "details": {"obs_armed_overhead_ratio": ratio},
        }

    # absent everywhere: n/a row, passes (first sight becomes the baseline)
    rows, ok = pg.compare(base, base, 0.05)
    assert ok
    # healthy ratio passes even vs an n/a baseline
    rows, ok = pg.compare(base, doc(0.995), 0.05)
    assert ok, rows
    # the 3% armed-overhead floor binds from first sight
    rows, ok = pg.compare(base, doc(0.90), 0.05)
    assert not ok
    assert any(
        "armed tracing" in r[0] and r[4] == "FAIL" for r in rows
    ), rows

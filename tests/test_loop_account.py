"""The event loop's account (ISSUE 37): the `wake` span — the way back from
a worker to the loop, the mirror image of `hop` — in every shape of frame
that awaits a worker, and the always-on series that say how busy the loop
thread is, how long one of its turns takes and whose CPU the host burns."""
import asyncio
import threading
import time

import numpy as np
import pytest

from redisson_tpu.net.client import Connection
from redisson_tpu.observe import trace as obs
from redisson_tpu.server.server import ServerThread, TpuServer


@pytest.fixture(autouse=True)
def _restore_tracing():
    prev = obs.tracing_enabled()
    yield
    obs.set_tracing(prev)
    obs.TRACER.reset()
    obs.TRACER.slowlog_reset()
    obs.TRACER.latency_reset()


def _conn(st, timeout=60.0):
    return Connection(st.server.host, st.server.port, timeout=timeout)


def _named(entry, *names):
    return [s for s in entry[7] if bytes(s[0]).decode() in names]


def _attrs(span):
    return {bytes(span[3][i]).decode(): span[3][i + 1]
            for i in range(0, len(span[3]), 2)}


def _blob(n=2_000):
    return np.ascontiguousarray(
        np.arange(n, dtype=np.int64) * 2654435761, "<i8").tobytes()


# -- the four shapes of frame that await a worker -------------------------------


def _serial(st):
    """Serial commands, one worker job each frame (and two in one frame)."""
    conn = _conn(st)
    try:
        conn.execute("SET", "wk:k", b"v")
        conn.execute("GET", "wk:k")
        # a slow verb between two fast ones: three jobs, one after another
        conn.execute("RPUSH", "wk:q", b"x")
        conn.execute_many([("INCR", "wk:n"), ("BLPOP", "wk:q", "1"),
                           ("INCR", "wk:n")])
    finally:
        conn.close()
    return {"SET", "GET", "INCR", "RPUSH"}


def _bucket(st):
    """A coalesced run and a probe: bucket segments fanned out on the pool,
    their lazies forced by the overlapped job the writer task awaits."""
    conn = _conn(st)
    try:
        conn.execute("BF.RESERVE", "wk:bf", 0.01, 50_000)
        for _ in range(3):
            conn.execute_many([
                ("BF.MADD64", "wk:bf", _blob()), ("BF.MADD64", "wk:bf", _blob()),
                ("BF.MEXISTS64", "wk:bf", _blob()),
            ], timeout=60.0)
            conn.execute("BF.MEXISTS64", "wk:bf", _blob())
    finally:
        conn.close()
    return {"BF.MADD64", "BF.MEXISTS64"}


def _window(st):
    """Point commands of eight connections at once: while the record's first
    job compiles, the others gather in its next window."""
    conn = _conn(st)
    try:
        conn.execute("BF.RESERVE", "wk:pt", 0.01, 50_000)
    finally:
        conn.close()
    conns = [_conn(st) for _ in range(8)]
    go = threading.Barrier(len(conns))
    errors = []

    def ask(i, c):
        try:
            go.wait(timeout=30)
            for j in range(4):
                item = f"item-{i}-{j}"
                assert c.execute("BF.ADD", "wk:pt", item) == 1
                assert c.execute("BF.EXISTS", "wk:pt", item) == 1
        except BaseException as e:  # noqa: BLE001 — raised below
            errors.append(e)

    threads = [threading.Thread(target=ask, args=(i, c)) for i, c in enumerate(conns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for c in conns:
        c.close()
    assert not errors and not any(t.is_alive() for t in threads), errors
    return {"BF.ADD", "BF.EXISTS"}


def _index(st):
    """Writes under a search index: the frame's serial job, then the job
    that indexes what it left dirty (`search.drain_all`)."""
    conn = _conn(st)
    try:
        assert conn.execute(
            "FT.CREATE", "wk:ix", "ON", "HASH", "PREFIX", "1", "wk:doc:",
            "SCHEMA", "vector", "VECTOR", "FLAT", "6", "TYPE", "FLOAT32",
            "DIM", "8", "DISTANCE_METRIC", "L2") == b"OK"
        for i in range(6):
            vec = np.full(8, i, np.float32).tobytes()
            assert conn.execute("HSET", f"wk:doc:{i}", "vector", vec) == 1
    finally:
        conn.close()
    return {"HSET"}


SHAPES = {
    "serial": (_serial, {}),
    "index": (_index, {}),
    "bucket": (_bucket, {}),
    "window": (_window, {}),
    # no overlap plane: the frame's own coroutine awaits the force job
    "force": (_bucket, {"overlap": False}),
}


@pytest.fixture(scope="module")
def traced():
    """{shape: the traced frames of its verbs}, one server a shape."""
    prev = obs.set_tracing(True)
    out = {}
    try:
        for shape, (drive, kw) in SHAPES.items():
            obs.TRACER.reset()
            with ServerThread(port=0, workers=4, **kw) as st:
                verbs = drive(st)
                time.sleep(0.1)
                conn = _conn(st)
                try:
                    entries = conn.execute("TRACE", "GET", "500", timeout=30.0)
                finally:
                    conn.close()
            out[shape] = [e for e in entries if bytes(e[3]).decode() in verbs]
            assert len(out[shape]) >= 3, (shape, entries)
    finally:
        obs.set_tracing(prev)
        obs.TRACER.reset()
    return out


# what a worker records before its last line
_WORKER_SPANS = ("dispatch", "readback", "kernel", "stage", "wave.answer")


@pytest.mark.parametrize("shape", list(SHAPES))
def test_every_await_of_a_worker_is_followed_by_a_wake(traced, shape):
    """A `hop` a worker job awaited on the loop, a `wake` (`reply.wake`
    where the writer task awaits it) with the hop's `to` as its `frm`; the
    wake begins where the worker's last span ends, and a window's members
    all wake from the one `dispatch` they share."""
    for e in traced[shape]:
        hops = sorted(bytes(_attrs(h)["to"]) for h in _named(e, "hop"))
        wakes = _named(e, "wake", "reply.wake")
        assert hops, e
        if shape == "bucket":
            # a fanned-out segment hops once a bucket and wakes once
            assert set(hops) == {bytes(_attrs(w)["frm"]) for w in wakes}, e
        else:
            assert hops == sorted(bytes(_attrs(w)["frm"]) for w in wakes), e
        for w in wakes:
            frm = bytes(_attrs(w)["frm"])
            assert frm in (b"dispatch", b"force"), e
            assert (bytes(w[0]) == b"reply.wake") == (
                frm == b"force" and shape != "force"), e
            ends = [s[1] + s[2] for s in _named(e, *_WORKER_SPANS)
                    if s[1] + s[2] <= w[1] + 1_000]
            assert ends, e
            # the worker's last line follows its last span by a return or two
            assert -1_000 <= w[1] - max(ends) <= 50_000, (w, max(ends), e)
            # and the hop it answers came before it
            assert any(h[1] + h[2] <= w[1] and bytes(_attrs(h)["to"]) == frm
                       for h in _named(e, "hop")), e
    if shape == "index":
        # the write's job and the index's: two hops, two wakes a frame
        assert all(len(_named(e, "wake")) == 2 for e in traced[shape])
    if shape == "window":
        # several connections' members rode one dispatch: its `members`
        assert max(_attrs(k)["members"] for e in traced[shape]
                   for k in _named(e, "kernel")) >= 2
    if shape == "force":
        assert not [w for e in traced[shape] for w in _named(e, "reply.wake")]
        assert [w for e in traced[shape] for w in _named(e, "wake")
                if bytes(_attrs(w)["frm"]) == b"force"]


def _unspanned_us(entry, without=()):
    """Total minus the union of the frame's spans inside [0, total]
    (benchmark/layer_metrics/frame.unspanned_ms.py's arithmetic)."""
    total = entry[2]
    cover = sorted(
        (max(0, s[1]), min(total, s[1] + s[2])) for s in entry[7]
        if not bytes(s[0]).startswith(b"host.")
        and bytes(s[0]).decode() not in without)
    covered, at = 0, 0
    for a, b in cover:
        if b > max(a, at):
            covered += b - max(a, at)
            at = b
    return total - covered


@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_traced_frame_is_spans_end_to_end(traced, shape):
    """With `wake` in, what no span covers is synchronous code on the loop
    between spans: under a fifth of the median frame here, on a CPU whose
    frames are a millisecond (on the chip: under 2 ms of bf-200c's 50), and
    less than it is with the wakes taken out again."""
    frames = traced[shape]
    share = np.median([_unspanned_us(e) / max(1, e[2]) for e in frames])
    assert share < 0.20, (shape, share)
    named = sum(_unspanned_us(e, ("wake", "reply.wake")) - _unspanned_us(e)
                for e in frames)
    assert named > 0, shape


def test_reply_wake_lies_inside_reply_wait_and_stage_totals_skip_it():
    obs.set_tracing(True)
    obs.TRACER.reset()
    with ServerThread(port=0, workers=4) as st:
        assert st.server.overlap
        _bucket(st)
        time.sleep(0.1)
        conn = _conn(st)
        try:
            entries = conn.execute("TRACE", "GET", "100", timeout=30.0)
        finally:
            conn.close()
    seen = 0
    for e in entries:
        for wake in _named(e, "reply.wake"):
            (wait,) = _named(e, "reply.wait")
            (reply,) = _named(e, "reply")
            assert wait[1] - 2 <= wake[1], e
            assert abs(wake[1] + wake[2] - (wait[1] + wait[2])) <= 2, e
            assert reply[1] - 2 <= wake[1] and (
                wake[1] + wake[2] <= reply[1] + reply[2] + 2), e
            seen += 1
    assert seen
    for tr in obs.TRACER.entries():
        totals = tr.stage_totals()
        assert not [k for k in totals if k.startswith("reply.")], totals
        if any(s.name == "wake" for s in tr.spans):
            assert totals["wake"] == tr.stage_us("wake") > 0  # a top-level stage


def test_trace_dump_renders_the_wake():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import trace_dump

    obs.set_tracing(True)
    obs.TRACER.reset()
    with ServerThread(port=0) as st:
        _window(st)
        time.sleep(0.1)
        conn = _conn(st)
        try:
            entries = conn.execute("TRACE", "GET", "100", "BY", "wake")
        finally:
            conn.close()
    assert all(len(e) == 8 for e in entries)  # TRACE GET's eight fields
    text = trace_dump.render_trace(entries[0])
    lines = [ln.split()[0] for ln in text.splitlines()[1:]]
    assert "wake" in lines and lines.index("hop") < lines.index("wake")
    assert "frm=dispatch" in text


def test_a_frames_wall_stamp_is_its_t0s():
    """`unix_ms` + a span's offset is the span's wall position: the stamp
    is taken where t0 was, not after the parse."""
    t0 = time.monotonic() - 0.5
    tr = obs.Tracer().begin_frame(None, [[b"PING"]], t0=t0)
    assert abs((time.time() - tr.ts) - (time.monotonic() - t0)) < 0.005
    (parse,) = tr.spans
    assert parse.name == "parse" and parse.off_us == 0
    assert 495_000 <= parse.dur_us <= 600_000
    late = obs.Tracer().begin_frame(None, [[b"PING"]])
    assert abs(time.time() - late.ts) < 0.05 and not late.spans


# -- the loop's account ---------------------------------------------------------

_ACCOUNT = ("rtpu_host_loop_turns_total", "rtpu_host_loop_busy_seconds_total",
            "rtpu_frames_served_total")
_CLOCKS = ("rtpu_host_loop_cpu_seconds_total", "rtpu_host_worker_cpu_seconds_total",
           "rtpu_host_process_cpu_seconds_total", "rtpu_host_uptime_seconds_total")


def _series(conn):
    return {
        line.split()[0]: float(line.split()[1])
        for line in bytes(conn.execute("METRICS")).decode().splitlines()
        if line.split()[0] in _ACCOUNT + _CLOCKS
    }


def test_loop_selector_counts_where_the_loop_selects():
    sel = obs.LoopSelector()
    loop = asyncio.SelectorEventLoop(sel)
    try:
        async def main():
            for _ in range(10):
                await asyncio.sleep(0.002)
            time.sleep(0.05)  # held a turn open without selecting: busy

        t0 = time.monotonic()
        loop.run_until_complete(main())
        wall = time.monotonic() - t0
    finally:
        loop.close()
    assert sel.turns >= 10
    assert 0.05 <= sel.busy_s <= wall + 0.01
    assert sel.busy_s < 0.05 + 0.5 * (wall - 0.05) + 0.02  # the sleeps are not


def test_a_long_turn_is_the_stall_and_only_while_armed():
    """The loop's lateness has one measure: a turn of STALL_MIN_S or more,
    found where the loop selects, is the tracer's `stall` host event — its
    start and length the turn's — and a disarmed loop records none."""
    def run_turns():
        loop = asyncio.SelectorEventLoop(obs.LoopSelector())
        try:
            async def main():
                await asyncio.sleep(0.002)
                t0 = time.monotonic()
                time.sleep(0.05)  # a long turn
                await asyncio.sleep(0.002)
                time.sleep(0.001)  # a short one
                await asyncio.sleep(0.002)
                return t0

            return loop.run_until_complete(main())
        finally:
            loop.close()

    obs.set_tracing(False)
    obs.TRACER.reset()
    before = (obs.TRACER.loop_stall_s, obs.TRACER.loop_long_stalls)
    run_turns()
    assert (obs.TRACER.loop_stall_s, obs.TRACER.loop_long_stalls) == before
    assert not obs.TRACER.host_events()
    obs.set_tracing(True)
    t0 = run_turns()
    stalls = [ev for ev in obs.TRACER._host if ev[0] == "stall"]
    (long,) = [ev for ev in stalls if ev[3] >= obs.Tracer.LONG_S]
    assert 0.05 <= long[3] < 0.2 and abs(long[2] - t0) < 0.01, (long, t0)
    assert abs(long[1] - (time.time() - (time.monotonic() - long[2]))) < 0.05
    assert obs.TRACER.loop_long_stalls == before[1] + 1
    assert obs.TRACER.loop_stall_s - before[0] >= long[3]
    assert all(ev[3] >= obs.Tracer.STALL_MIN_S for ev in stalls)


def test_the_account_grows_under_traffic_and_stays_inside_the_wall_clock():
    """Always on: disarmed here.  Turns, busy seconds and frames grow with
    traffic; the loop cannot be busy, nor burn CPU, for longer
    than the server is up; no series falls between two scrapes."""
    assert not obs.tracing_enabled()
    with ServerThread(port=0, workers=2) as st:
        conn = _conn(st)
        try:
            first = _series(conn)
            assert set(first) == set(_ACCOUNT + _CLOCKS)
            for i in range(200):
                conn.execute("SET", f"la:{i}", b"v")
            conn.execute_many([("GET", "la:1")] * 50)
            second = _series(conn)
            time.sleep(0.3)  # asleep in select: uptime grows, busy hardly
            third = _series(conn)
        finally:
            conn.close()
    # a scrape of a stopped server (a census, a test's teardown): its threads
    # and their clocks are gone, its totals are not
    st._thread.join(timeout=10)
    stopped = st.server.metrics.snapshot()
    for name in _ACCOUNT + _CLOCKS:
        assert stopped[name[len("rtpu_"):]] >= third[name], (name, stopped)
    for a, b in ((first, second), (second, third)):
        for name in a:
            assert b[name] >= a[name], (name, a, b)
    for name in _ACCOUNT:
        assert second[name] > first[name], (name, first, second)
    # 200 one-command frames, one pipelined frame (or a few), the scrape's own
    assert 201 <= second["rtpu_frames_served_total"] - first["rtpu_frames_served_total"] <= 252
    # a frame is read, answered and written: two turns or more
    assert second["rtpu_host_loop_turns_total"] - first["rtpu_host_loop_turns_total"] >= 400
    for snap in (first, second, third):
        up = snap["rtpu_host_uptime_seconds_total"]
        assert 0 < snap["rtpu_host_loop_busy_seconds_total"] <= up
        assert 0 <= snap["rtpu_host_loop_cpu_seconds_total"] <= up
        assert 0 <= snap["rtpu_host_worker_cpu_seconds_total"]
        assert 0 < snap["rtpu_host_process_cpu_seconds_total"]
    assert second["rtpu_host_loop_cpu_seconds_total"] > first["rtpu_host_loop_cpu_seconds_total"]
    assert second["rtpu_host_worker_cpu_seconds_total"] > first["rtpu_host_worker_cpu_seconds_total"]
    idle = third["rtpu_host_uptime_seconds_total"] - second["rtpu_host_uptime_seconds_total"]
    assert idle >= 0.3
    assert (third["rtpu_host_loop_busy_seconds_total"]
            - second["rtpu_host_loop_busy_seconds_total"]) < 0.5 * idle


def test_a_server_in_somebody_elses_loop_counts_no_turns():
    """`start_async` inside a loop the server did not make: the loop's
    account reads 0 (no selector of ours in it) and nothing raises; the
    CPU clocks and the frames served still read."""
    async def main():
        srv = TpuServer(port=0, workers=2)
        await srv.start_async()
        try:
            def ask():
                conn = Connection(srv.host, srv.port, timeout=30.0)
                try:
                    for i in range(20):
                        conn.execute("SET", f"fl:{i}", b"v")
                    return _series(conn)
                finally:
                    conn.close()

            return await asyncio.get_running_loop().run_in_executor(None, ask)
        finally:
            srv.stop()
            srv._server.close()

    got = asyncio.run(main())
    assert set(got) == set(_ACCOUNT + _CLOCKS)
    for name in _ACCOUNT[:2]:
        assert got[name] == 0, got
    assert got["rtpu_frames_served_total"] >= 20
    assert got["rtpu_host_uptime_seconds_total"] > 0
    assert 0 <= got["rtpu_host_loop_cpu_seconds_total"] <= got["rtpu_host_uptime_seconds_total"]

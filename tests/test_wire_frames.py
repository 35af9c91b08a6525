"""A frame is what has arrived on a connection when its read loop looks
(ISSUE 29): the stream's buffer, up to server._FRAME_CAP, never a byte that
has not come.  These tests count the
frames a pipelined request is served as and compare reply BYTES; they run on
the CPU and say nothing of how fast anything is.

A test that needs "everything has landed before the server looks" stalls the
server's event loop while the client writes: what the loop then finds is the
kernel's to say, as on a busy server."""
import socket
import threading
import time

import numpy as np
import pytest

from redisson_tpu.net import resp
from redisson_tpu.server import server as S

CAP = S._FRAME_CAP


@pytest.fixture(scope="module")
def st():
    with S.ServerThread(workers=4) as st:
        yield st


@pytest.fixture
def frames(st, monkeypatch):
    """The command count of every frame the server plans, in order."""
    seen = []
    plan = st.server._plan_frame

    def counted(ctx, commands, shed_mask):
        seen.append(len(commands))
        return plan(ctx, commands, shed_mask)

    monkeypatch.setattr(st.server, "_plan_frame", counted)
    return seen


def connect(st):
    return socket.create_connection((st.server.host, st.server.port), timeout=60)


def wire(cmds) -> bytes:
    return b"".join(resp.encode_command_python(*c) for c in cmds)


def read_replies(s, n: int) -> bytes:
    """The raw bytes of the next `n` replies."""
    parser = resp.RespParser(use_native=False)
    got = b""
    while n > 0:
        data = s.recv(1 << 16)
        assert data, "the server closed the connection"
        got += data
        n -= len(parser.feed(data))
    assert n == 0
    return got


def while_the_loop_stands(st, secs, act):
    """Run `act` while the server's event loop is held for `secs`: what
    `act` writes has landed in the kernel before any of it is read."""
    held = threading.Event()

    def hold():
        held.set()
        time.sleep(secs)

    st.server._loop.call_soon_threadsafe(hold)
    assert held.wait(10)
    act()


def pipeline(tag: str, n: int, value_bytes: int):
    """`n` commands whose replies depend on order: SET k, APPEND k, GET k."""
    cmds = []
    for i in range(n // 3):
        key = f"{tag}:{i % 7}"
        cmds += [("SET", key, "%06d" % i + "v" * value_bytes), ("APPEND", key, "+%d" % i),
                 ("GET", key)]
    return cmds


@pytest.mark.parametrize("value_bytes", [700, 1000])
def test_a_request_larger_than_one_old_read_is_one_frame(st, frames, value_bytes):
    """One sendall of more than 64 KiB (and less than the kernel's initial
    128 KiB receive window, so one recv can hold it): ONE frame, its
    replies byte for byte those of the same commands sent one a read."""
    cmds = pipeline(f"one{value_bytes}", 300, value_bytes)
    payload = wire(cmds)
    assert 1 << 16 < len(payload) < 124_000 < CAP
    with connect(st) as s:
        one_by_one = b""
        for c in cmds:
            s.sendall(wire([c]))
            one_by_one += read_replies(s, 1)
        assert frames == [1] * len(cmds)
        del frames[:]
        while_the_loop_stands(st, 0.2, lambda: s.sendall(payload))
        whole = read_replies(s, len(cmds))
    assert frames == [len(cmds)]
    assert whole == one_by_one


def test_a_request_of_several_recvs_is_answered_as_if_sent_one_a_read(st, frames):
    """200 KB (fanout-4's request) is more than a new connection's receive
    window: the first recv brings a part and the rest is the next frame or
    two (how many is the kernel's to say; a connection whose window has
    grown brings it whole).  Every command is in exactly one frame and the
    replies are byte for byte those of the same commands sent one a read."""
    cmds = pipeline("refill", 600, 880)
    payload = wire(cmds)
    assert 190_000 < len(payload) < CAP
    with connect(st) as s:
        while_the_loop_stands(st, 0.2, lambda: s.sendall(payload))
        whole = read_replies(s, len(cmds))
    assert sum(frames) == len(cmds) and len(frames) < len(cmds), frames
    with connect(st) as s:
        s.sendall(wire([("DEL",) + tuple(f"refill:{i}" for i in range(7))]))
        read_replies(s, 1)
        again = b""
        for c in cmds:
            s.sendall(wire([c]))
            again += read_replies(s, 1)
    assert whole == again


def test_a_pipeline_longer_than_the_cap_is_cut_at_a_command_boundary(st, frames):
    """Three caps' worth of small commands in one write: no frame holds more
    than the cap (and the command the cut fell in), every command is in
    exactly one frame, and the replies come in the order sent."""
    n = 3 * CAP // 30
    cmds = [("ECHO", "%011d" % i) for i in range(n)]
    payload = wire(cmds)
    each = len(payload) // n
    assert len(payload) > 3 * CAP
    parser = resp.RespParser(use_native=False)
    got = []
    with connect(st) as s:
        sender = threading.Thread(target=s.sendall, args=(payload,))
        while_the_loop_stands(st, 0.2, sender.start)
        while len(got) < n:
            data = s.recv(1 << 16)
            assert data
            got += parser.feed(data)
        sender.join(10)
        assert not sender.is_alive()
    assert [bytes(r) for r in got] == [c[1].encode() for c in cmds]
    assert sum(frames) == n and len(frames) >= 3
    assert max(frames) * each <= CAP + each, (max(frames), each)


def test_a_lone_command_is_answered_without_waiting_for_more(st, frames):
    """A silent socket: the command is dispatched on the read that brought
    it — no timer runs out first and nothing waits for more bytes."""
    with connect(st) as s:
        s.sendall(wire([("PING",)]))
        read_replies(s, 1)  # the connection's first command has compiled nothing since
        took = []
        for _ in range(20):
            t0 = time.monotonic()
            s.sendall(wire([("ECHO", "alone")]))
            assert read_replies(s, 1) == b"$5\r\nalone\r\n"
            took.append(time.monotonic() - t0)
    assert frames == [1] * 21
    assert sorted(took)[10] < 0.05, took  # a timer or a wait for more bytes would show here


def test_what_has_come_is_served_before_the_rest_of_a_cut_command_comes(st, frames):
    """The network cuts a frame in the middle of its third command, with a
    pause: the two whole commands are answered BEFORE the rest is written
    (a frame never waits for bytes that have not come), and the third
    parses from its two pieces."""
    cmds = [("SET", "cut:k", "a" * 300), ("APPEND", "cut:k", "b" * 300), ("GET", "cut:k"),
            ("STRLEN", "cut:k")]
    payload = wire(cmds)
    cut = len(wire(cmds[:2])) + 9  # inside GET's header
    with connect(st) as s:
        s.sendall(payload[:cut])
        first = read_replies(s, 2)
        assert first == b"+OK\r\n:600\r\n"
        time.sleep(0.05)
        s.sendall(payload[cut:])
        rest = read_replies(s, 2)
    assert rest == b"$600\r\n" + b"a" * 300 + b"b" * 300 + b"\r\n:600\r\n"
    assert frames == [2, 2]


def test_a_command_larger_than_the_cap_is_one_command(st, frames):
    """A bulk flush (1.2 MB in the bulk cells) crosses several reads of the
    cap and is buffered by the parser: one frame of one command."""
    value = bytes(range(256)) * (5 * CAP // 256)
    with connect(st) as s:
        s.sendall(wire([("SET", "big:k", value), ("STRLEN", "big:k")]))
        assert read_replies(s, 2) == b"+OK\r\n:%d\r\n" % len(value)
    assert sum(frames) == 2 and len(frames) <= 2


def test_an_over_budget_tenants_larger_frame_is_shed_in_frame_position(st, frames):
    """QoS admission sees ONE frame, only a larger one: 120 adds of 100 keys
    (100 KB in one write) against a budget of 50 of them are classed and
    charged once, the first 50 admitted and the rest answered -BUSY in frame
    position, byte for byte what the same commands sent one a read are
    answered, and no shed add reached its filter."""
    names = [f"shed:{i % 4}{{hog}}" for i in range(120)]
    blobs = [(1_000 * i + np.arange(100, dtype="<i8")).tobytes() for i in range(120)]
    cmds = [("BF.MADD64", n, b) for n, b in zip(names, blobs)]
    payload = wire(cmds)
    assert 1 << 16 < len(payload) < 124_000
    arm = [("CONFIG", "SET", "qos-tenant-burst", "5050"), ("CONFIG", "SET", "qos-tenant-rate", "0.001")]
    fresh = [("DEL",) + tuple(sorted(set(names)))] + [
        ("BF.RESERVE", n, "0.01", "10000") for n in sorted(set(names))]
    sheds0 = st.server.scheduler.shed_frames
    with connect(st) as s:
        try:
            s.sendall(wire(fresh + arm))
            read_replies(s, len(fresh) + len(arm))
            del frames[:]
            while_the_loop_stands(st, 0.2, lambda: s.sendall(payload))
            whole = read_replies(s, len(cmds))
            assert frames == [len(cmds)]
            assert st.server.scheduler.shed_frames == sheds0 + 1
            s.sendall(wire([("CONFIG", "SET", "qos-tenant-rate", "0")]))
            read_replies(s, 1)
            probe = wire([("BF.MEXISTS64", n, b) for n, b in zip(names, blobs)])
            s.sendall(probe)
            there = read_replies(s, len(cmds))
            s.sendall(wire(fresh + arm))
            read_replies(s, len(fresh) + len(arm))
            one_by_one = b""
            for c in cmds:
                s.sendall(wire([c]))
                one_by_one += read_replies(s, 1)
        finally:
            s.sendall(wire([("CONFIG", "SET", "qos-tenant-rate", "0"),
                            ("CONFIG", "SET", "qos-tenant-burst", "")]))
            read_replies(s, 2)
    parser = resp.RespParser(use_native=False)
    replies = parser.feed(whole)
    busy = [isinstance(r, resp.RespError) and str(r).startswith("BUSY") for r in replies]
    assert busy == [False] * 50 + [True] * 70
    assert whole == one_by_one
    found = [np.frombuffer(bytes(r), np.uint8) for r in parser.feed(there)]
    assert all(f.all() for f in found[:50])
    # a shed add never dispatched: at most the filter's false positives
    assert sum(int(f.sum()) for f in found[50:]) < 0.05 * 70 * 100

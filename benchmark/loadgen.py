"""The one general load generator: closed and open loops over the public
client API, in jax-free worker processes.

A traffic file names a ``generator`` (a module of ``benchmark/generators``)
and the loop's parameters.  The generator module supplies a ``Stream`` per
connection — what one request is, how it is sent through the client a user
calls, and how its reply is checked; this file supplies the loop, the clock
and the process discipline, the same for every mix:

  closed   each connection sends, waits for the decoded reply, sends the
           next; a request is timed from just before the API call.
  open     each connection has Poisson arrivals drawn from the seed at
           ``rate / connections``; a request is timed from when it was DUE,
           so a stall is charged to every request it delays, and how late
           the generator itself ran (send time minus the later of due time
           and the connection falling free) is reported beside it.

Workers are spawned processes (one Python thread cannot offer the rate);
CLOCK_MONOTONIC is system-wide, so the parent fixes the window as two
monotonic instants and every worker keeps to them.  Requests are drawn off
the timed path: all of them before the window in an open loop, by a
producer thread a few frames ahead in a closed one.
"""
import importlib
import multiprocessing as mp
import os
import queue
import threading
import time
import traceback

import numpy as np

CLIENT_TIMEOUT_S = 1200.0  # a first run compiles inside its warm-up
DRAIN_LIMIT_S = 30.0       # open loop: how far past the window a backlog may run
AHEAD = 4                  # closed loop: requests drawn ahead of need


def load_generator(name: str):
    return importlib.import_module(f"benchmark.generators.{name}")


class StreamContext:
    """What a generator's Stream is made from."""

    def __init__(self, sizes: dict, params: dict, seed: int, conn: int,
                 n_conns: int, ref_dir: str):
        self.sizes, self.params, self.seed = sizes, params, seed
        self.conn, self.n_conns, self.ref_dir = conn, n_conns, ref_dir

    def ref(self, name: str):
        """One array of the reference the parent built; a large one is mapped,
        so the workers share its pages."""
        path = os.path.join(self.ref_dir, name + ".npy")
        return np.load(path, mmap_mode="r" if os.path.getsize(path) > (1 << 20) else None)


class Recorder:
    """Per-request clock readings of one connection."""

    def __init__(self, conn: int):
        self.conn = conn
        self.rows = []  # (idx, t_ref, t_send, t_done, free_at, ops, ok)
        self.unsent = 0
        self.errors = []

    def fail(self, what: str):
        if len(self.errors) < 5:
            self.errors.append(what)


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(left)


def _send(stream, rec: Recorder, idx: int, req, t_ref, free_at) -> float:
    """One timed request; returns when it was done."""
    t0 = time.monotonic()
    try:
        reply = stream.send(req)
        ok = True
    except Exception as e:  # noqa: BLE001 — a refused/failed/unanswered
        reply, ok = None, False  # operation is a result, not a crash
        rec.fail(f"conn {rec.conn} request {idx}: {type(e).__name__}: {e}")
    t1 = time.monotonic()
    rec.rows.append((idx, t0 if t_ref is None else t_ref, t0, t1,
                     t0 if free_at is None else free_at,
                     stream.ops(req) if ok else 0, ok))
    if ok:
        stream.keep(idx, req, reply)
    return t1


def _closed_loop(stream, rec: Recorder, t_start: float, t_end: float) -> None:
    ahead: queue.Queue = queue.Queue(maxsize=AHEAD)
    stop = threading.Event()

    def produce():
        i = 0
        while not stop.is_set():
            req = stream.make(i)
            while not stop.is_set():
                try:
                    ahead.put((i, req), timeout=0.05)
                    break
                except queue.Full:
                    pass
            i += 1

    th = threading.Thread(target=produce, daemon=True)
    th.start()
    try:
        _sleep_until(t_start)
        idx = -1
        while time.monotonic() < t_end and not rec.errors:
            idx, req = ahead.get()
            _send(stream, rec, idx, req, None, None)
    finally:
        stop.set()
        th.join(timeout=10.0)
    closing = None if rec.errors else stream.closing(idx + 1)
    if closing is not None:
        _send(stream, rec, idx + 1, closing, None, None)


def _open_loop(stream, rec: Recorder, t_start: float, due, reqs) -> None:
    free_at = t_start
    for i, off in enumerate(due):
        t_due = t_start + float(off)
        if rec.errors or time.monotonic() > t_start + float(due[-1]) + DRAIN_LIMIT_S:
            rec.unsent = len(due) - i  # attempted, never answered
            rec.fail(f"conn {rec.conn}: gave up with {rec.unsent} requests unsent")
            return
        _sleep_until(t_due)
        free_at = _send(stream, rec, i, reqs[i], t_due, max(free_at, t_due))


def connect(addr: str):
    """The client every part of the benchmark uses: one socket, no retry (a
    failed request is a result), no keep-alive pings inside the window."""
    from redisson_tpu.client.remote import RemoteRedisson

    return RemoteRedisson(addr, timeout=CLIENT_TIMEOUT_S, pool_size=1,
                          retry_attempts=0, ping_interval=0)


def _connection(gen, spec: dict, conn: int, addr: str):
    client = connect(addr)
    ctx = StreamContext(spec["sizes"], spec["params"], spec["seed"], conn,
                        spec["params"]["connections"], spec["ref_dir"])
    stream = gen.Stream(ctx)
    stream.bind(client)
    return client, stream, Recorder(conn)


def _expect(pipe, word: str) -> None:
    got = pipe.recv()
    if got != word:
        raise RuntimeError(f"the parent said {got!r}, not {word!r}")


def worker_main(spec: dict, pipe) -> None:
    """Body of one worker process: its connections, their warm-up, the
    window, the check.  Talks to the parent over ``pipe``:
    ("connect", addr) -> "ready"; "warm" -> "warm"; ("go", t0, t1) ->
    ("ran", writes); "verify" -> ("done", report)."""
    try:
        gen = load_generator(spec["params"]["generator"])
        _msg, addr = pipe.recv()
        conns = [_connection(gen, spec, c, addr) for c in spec["conns"]]
        pipe.send("ready")
        _expect(pipe, "warm")
        for _client, stream, rec in conns:
            for j, req in enumerate(stream.warmup()):
                _send(stream, rec, -1 - j, req, None, None)
            rec.rows.clear()  # warm-up is set-up: acknowledged, checked, not timed
        pipe.send("warm")
        _go, t_start, t_end = pipe.recv()
        params = spec["params"]
        threads = []
        for _client, stream, rec in conns:
            if params["loop"] == "open":
                due = stream.arrivals(t_end - t_start)
                reqs = [stream.make(i) for i in range(len(due))]
                target, args = _open_loop, (stream, rec, t_start, due, reqs)
            else:
                target, args = _closed_loop, (stream, rec, t_start, t_end)
            threads.append(threading.Thread(target=target, args=args))
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        pipe.send(("ran", {rec.conn: stream.writes() for _c, stream, rec in conns}))
        _expect(pipe, "verify")
        report = []
        for client, stream, rec in conns:
            checked = stream.verify()
            rows = np.array(rec.rows, np.float64).reshape(-1, 7)
            report.append({"conn": rec.conn, "rows": rows, "unsent": rec.unsent,
                           "errors": rec.errors, **checked})
            client.shutdown()
        pipe.send(("done", report))
    except BaseException:  # noqa: BLE001 — the parent must hear of it
        pipe.send(("crashed", traceback.format_exc()))
        raise
    finally:
        pipe.close()


class Workers:
    """The worker processes of one run, seen from the parent."""

    def __init__(self, spec: dict):
        params = spec["params"]
        n_proc = min(params.get("processes", params["connections"]),
                     params["connections"])
        ctx = mp.get_context("spawn")
        self.procs, self.pipes = [], []
        for w in range(n_proc):
            mine = list(range(w, params["connections"], n_proc))
            here, there = ctx.Pipe()
            p = ctx.Process(target=worker_main,
                            args=({**spec, "conns": mine}, there), daemon=True)
            p.start()
            there.close()
            self.procs.append(p)
            self.pipes.append(here)

    def _recv(self, pipe, timeout: float):
        if not pipe.poll(timeout):
            raise RuntimeError(f"a load worker said nothing for {timeout:.0f}s")
        msg = pipe.recv()
        if isinstance(msg, tuple) and msg[0] == "crashed":
            raise RuntimeError("a load worker crashed:\n" + msg[1])
        return msg

    def connect(self, addr: str) -> None:
        for p in self.pipes:
            p.send(("connect", addr))
        for p in self.pipes:
            self._recv(p, 120.0)

    def warm(self) -> None:
        """The first worker alone, then the rest: a first run compiles each
        program once, not once a connection."""
        for group in (self.pipes[:1], self.pipes[1:]):
            for p in group:
                p.send("warm")
            for p in group:
                self._recv(p, CLIENT_TIMEOUT_S)

    def go(self, t_start: float, t_end: float) -> None:
        for p in self.pipes:
            p.send(("go", t_start, t_end))

    def ran(self, timeout: float) -> dict:
        writes = {}
        for p in self.pipes:
            writes.update(self._recv(p, timeout)[1])
        return writes

    def verify(self) -> list:
        for p in self.pipes:
            p.send("verify")
        out = []
        for p in self.pipes:
            out.extend(self._recv(p, 300.0)[1])
        return sorted(out, key=lambda r: r["conn"])

    def stop(self) -> None:
        for p in self.pipes:
            p.close()
        for p in self.procs:
            p.join(timeout=10.0)
            if p.is_alive():
                p.kill()
                p.join(timeout=10.0)

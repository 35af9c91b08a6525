"""ISSUE 35: point commands — a single-item ``BF.ADD`` / ``BF.EXISTS`` of a
string item — served to a fleet of connections that each keep ONE in flight
(memtier_benchmark's default traffic, the benchmark's cell ``bf-200c``).

Contracts pinned here:
  * 32 connections, one command in flight each, 10 ``BF.EXISTS`` to 1
    ``BF.ADD`` of ``memtier-<n>`` (9-13 bytes here, 9-16 in the cell) against
    a 20,000-item filter: every reply, and a ``BF.MEXISTS`` sweep of the
    plane afterwards, is what the plain reference (``benchmark/
    reference_bf.py``) says, by the cell's own rules — the generator
    (``benchmark/generators/memtier_bf.py``) run against an in-process
    server;
  * the always-on counters count what they say: ``point_cmds`` the commands
    answered (by verb), ``point_dispatches`` the device dispatches issued for
    them, rows valid <= rows issued (the rows asked of a bucket of
    ``MIN_BUCKET``);
  * with tracing armed a point command's frame carries a ``kernel`` span
    with ``verb`` and ``members`` and a ``point.wait`` span that ends where
    the ``kernel`` span ends; stage totals leave ``point.wait`` out;
  * the two bytes kernels carry their ``jax.named_scope``;
  * ``BF.INFO`` reports the capacity the filter was reserved with.

ISSUE 36: point commands of different connections are answered as one
WINDOW — what was waiting for its record when a worker took the job — with
one upload, one dispatch and one fetch.  Pinned here (``_window`` makes a window
on purpose: the server paused, the commands sent and joined, then resumed):
  * a window's answers are those of one one-at-a-time execution, the probes
    then the adds: a probe of an item the same window adds sees the plane
    before it; of two adds that share every unset cell exactly one reports
    newly added; an acknowledged add is seen by every later probe;
  * a lone command on an idle server is a window of one: one dispatch of
    one valid row; a connection never has two members in a window, and
    MULTI/EXEC and multi-command frames answer as before;
  * every member counts in ``INFO commandstats`` and ``stats["commands"]``,
    a tracking client is invalidated by a windowed ``BF.ADD``;
  * a window that raises answers every member an error and dispatches
    nothing twice; armed, every member's frame carries ``hop``,
    ``dispatch``, ``kernel`` (``verb`` = the window's verbs, ``members`` =
    the window), ``point.wait`` (``verb`` = its own) and ``readback``.
"""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import loadgen
from benchmark import reference_bf as R
from benchmark.generators import memtier_bf as G
from redisson_tpu.core import kernels as K
from redisson_tpu.client.objects.bloom import BloomFilter
from redisson_tpu.net.client import Connection
from redisson_tpu.net.resp import RespError
from redisson_tpu.observe import trace as obs
from redisson_tpu.server.server import ServerThread

SIZES = {"capacity": 20000, "error_rate": 0.01, "m_bits": 191701, "k": 7,
         "key_prefix": "memtier-", "key_max": 20000, "populate_batch": 1024,
         "populate_pipeline": 4, "sweep_keys": 4000, "sweep_chunk": 1024}
PARAMS = {"connections": 32, "exists_per_add": 10, "reprobe_after": 2,
          "key_prefix": "memtier-", "key_max": 20000, "k": 7}
SEED = 3535
PER_CONN = 33  # three cycles of 11: three adds, three re-probes, 27 probes


@pytest.fixture(scope="module")
def server():
    with ServerThread(port=0, workers=4) as st:
        yield st


@pytest.fixture()
def conn(server):
    c = Connection(server.server.host, server.server.port, timeout=60.0)
    yield c
    c.close()


def _metrics(c) -> dict:
    rows = dict(line.rsplit(" ", 1) for line in
                bytes(c.execute("METRICS")).decode().splitlines() if line)
    return {k[len("rtpu_point_"):-len("_total")]: int(float(v)) for k, v in rows.items()
            if k.startswith("rtpu_point_")}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


@pytest.fixture(scope="module")
def fleet(server, tmp_path_factory):
    """The generator's whole life against the in-process server: reference,
    set-up over the wire, 32 connections each with one command in flight,
    the parent's after-window pass, every connection's check."""
    ref_dir = tmp_path_factory.mktemp("ref")
    ref = G.reference(SIZES, PARAMS, SEED)
    np.save(ref_dir / "plane.npy", ref["plane"])
    parent = loadgen.connect(server.address)
    populated = G.populate(parent, SIZES, PARAMS, SEED)
    streams = []
    for c in range(PARAMS["connections"]):
        s = G.Stream(loadgen.StreamContext(SIZES, PARAMS, SEED, c, PARAMS["connections"],
                                           str(ref_dir)))
        s.bind(loadgen.connect(server.address))
        streams.append(s)
    before = _metrics(parent.node)
    errors = []

    def run(s):
        try:
            for idx, req in [(-1 - j, r) for j, r in enumerate(s.warmup())] + \
                    [(i, s.make(i)) for i in range(PER_CONN)]:
                s.keep(idx, req, s.send(req))
        except Exception as e:  # noqa: BLE001 — shown by the test
            errors.append(repr(e))

    threads = [threading.Thread(target=run, args=(s,)) for s in streams]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    counted = _delta(_metrics(parent.node), before)
    writes = {s.ctx.conn: s.writes() for s in streams}
    failures, extra = G.after_window(parent, SIZES, PARAMS, SEED, ref, writes)
    for name, arr in extra.items():
        np.save(ref_dir / (name + ".npy"), arr)
    reports = [s.verify() for s in streams]
    for s in streams:
        s.client.shutdown()
    parent.shutdown()
    return {"errors": errors, "failures": failures, "extra": extra, "reports": reports,
            "counted": counted, "populated": populated, "streams": streams, "ref": ref}


def test_every_reply_of_the_fleet_is_the_references(fleet):
    assert fleet["errors"] == [] and fleet["failures"] == []
    assert [r["failures"] for r in fleet["reports"]] == [[]] * PARAMS["connections"]
    sent = PARAMS["connections"] * (PER_CONN + 2)  # two warm-up commands each
    assert sum(r["checked"] for r in fleet["reports"]) == sent


def test_the_sweep_reads_the_planes_end_state(fleet):
    x = fleet["extra"]
    assert x["sweep_keys"][0] == SIZES["sweep_keys"] and x["sweep_wrong"][0] == 0
    adds = PARAMS["connections"] * (PER_CONN // 11 + 1)  # the warm-up's add too
    assert x["adds_acknowledged"][0] == adds == len(x["added_n"])
    assert x["sweep_touched"][0] >= adds  # every add is swept, then probed keys
    assert x["connected_clients"][0] >= PARAMS["connections"]
    # set-up added the even half; a false positive or two may have been there already
    assert fleet["populated"]["populated_items"] == 10000
    assert fleet["populated"]["bf_info"] == {"m": 191701, "k": 7, "capacity": 20000}


def test_the_mix_is_memtiers(fleet):
    kinds = np.concatenate([np.array(s.kept)[2:, 1] for s in fleet["streams"]])  # past warm-up
    n = PARAMS["connections"] * PER_CONN
    assert len(kinds) == n and (kinds == G.KIND_ADD).sum() * 11 == n  # exactly 1 : 10
    late = sum(s.keys.kind(i) == G.KIND_ADD for s in fleet["streams"]
               for i in (PER_CONN - 2, PER_CONN - 1))  # re-probed past the last command
    assert (kinds == G.KIND_REPROBE).sum() == (kinds == G.KIND_ADD).sum() - late
    for s in fleet["streams"]:
        kept = np.array(s.kept)[2:]
        for at in np.flatnonzero(kept[:, 1] == G.KIND_ADD):
            if at + 2 < len(kept):  # probed again two commands later, answered 1
                assert tuple(kept[at + 2, 1:4]) == (G.KIND_REPROBE, kept[at, 2], 1)
    lengths = {len(b"memtier-%d" % s.keys.key(i)) for s in fleet["streams"][:4]
               for i in range(PER_CONN)}
    assert lengths <= set(range(9, 17)) and len(lengths) >= 3


def test_counters_count_what_they_say(fleet):
    c = fleet["counted"]
    sent = PARAMS["connections"] * (PER_CONN + 2)
    adds = PARAMS["connections"] * (PER_CONN // 11 + 1)
    assert c["cmds"] == sent and c["cmds_bf_add"] == adds
    assert c["cmds_bf_exists"] == sent - adds
    # 32 connections against four workers: windows form, a window is one
    # dispatch whatever its mix, and each hands its kernel one bucket
    assert c["dispatches"] < c["cmds"] and c["windows"] == c["dispatches"]
    assert c["rows_valid"] == sent and c["rows_issued"] == c["dispatches"] * K.MIN_BUCKET


@pytest.mark.parametrize("verb", ["BF.ADD", "BF.EXISTS"])
def test_one_command_counts_once(conn, verb):
    conn.execute("BF.RESERVE", "cnt:" + verb, "0.01", 1000)
    before = _metrics(conn)
    assert conn.execute(verb, "cnt:" + verb, b"memtier-7") == (1 if verb == "BF.ADD" else 0)
    d = _delta(_metrics(conn), before)
    other = "cmds_bf_exists" if verb == "BF.ADD" else "cmds_bf_add"
    assert d == {"cmds": 1, "cmds_" + verb.lower().replace(".", "_"): 1, other: 0,
                 "windows": 1, "dispatches": 1, "rows_valid": 1, "rows_issued": K.MIN_BUCKET}


def test_the_batch_forms_count_nothing(conn):
    conn.execute("BF.RESERVE", "cnt:batch", "0.01", 1000)
    before = _metrics(conn)
    assert conn.execute("BF.MADD", "cnt:batch", b"a", b"b") == [1, 1]
    assert conn.execute("BF.MEXISTS", "cnt:batch", b"a", b"c") == [1, 0]
    assert _delta(_metrics(conn), before) == dict.fromkeys(before, 0)


def _window(server, sends):
    """``sends`` — [(connection, command)], a connection once — answered as
    ONE window: while the server is paused its jobs park before they take
    anything, so every command sent joins the window the first one opened;
    then it resumes.  The replies, in ``sends``' order."""
    srv = server.server
    base = srv.stats["commands"]
    srv.pause()
    try:
        for c, cmd in sends:
            c.send(*cmd)
        deadline = time.monotonic() + 60.0
        while srv.stats["commands"] - base < len(sends):  # counted as it joins
            assert time.monotonic() < deadline, "the commands never joined"
            time.sleep(0.001)
    finally:
        srv.resume()
    return [c.read_reply() for c, _cmd in sends]


@pytest.fixture()
def conns(server):
    cs = [Connection(server.server.host, server.server.port, timeout=60.0)
          for _ in range(8)]
    yield cs
    for c in cs:
        c.close()


def test_a_window_is_the_probes_then_the_adds(server, conns):
    """Rounds of one window each, eight connections: adds of new items,
    probes of items earlier rounds added, of items THIS window adds (they
    see the plane before it) and of strangers — each reply what the
    reference says, one at a time, in that order."""
    m, k = R.optimal_m(20000, 0.01), 7
    conns[0].execute("BF.RESERVE", "win:order", "0.01", 20000)
    ref = R.RefFilter(m, k)
    before = _metrics(conns[0])
    item = lambda n: b"win-%d" % n  # noqa: E731
    sent = 0
    for r in range(12):
        new = [item(100 * r + j) for j in range(3)]
        old = [item(100 * (r - 1) + j) for j in range(2)] if r else [item(7_000_000)]
        cmds = ([("BF.ADD", "win:order", it) for it in new]
                + [("BF.EXISTS", "win:order", it) for it in old]
                + [("BF.EXISTS", "win:order", new[0]), ("BF.EXISTS", "win:order", new[2])])
        cmds = cmds[r % len(cmds):] + cmds[:r % len(cmds)]  # who arrives first differs
        cmds = cmds[:len(conns)]
        got = _window(server, list(zip(conns, cmds)))
        probes = [c[2] for c in cmds if c[0] == "BF.EXISTS"]
        want = dict(zip(probes, ref.contains(*R.pack(probes)).tolist()))
        rows, nbytes = R.pack(new)
        cells = ref.indexes(rows, nbytes)
        assert len(np.unique(cells)) == cells.size  # no two of the round's adds meet
        newly = {it: ref.add(rows[j], int(nbytes[j])) for j, it in enumerate(new)}
        for (verb, _name, it), answer in zip(cmds, got):
            expected = want[it] if verb == "BF.EXISTS" else newly[it]
            assert answer == int(expected), (r, verb, it)
        sent += len(cmds)
    d = _delta(_metrics(conns[0]), before)
    assert d["cmds"] == sent == d["rows_valid"]
    assert d["dispatches"] == d["windows"] == 12  # a dispatch a window, whatever its size and mix


def _meeting_pair(m: int, k: int):
    """Two different items with a cell in common, and the cells only one of
    them has."""
    ref = R.RefFilter(m, k)
    items = [b"pair-%d" % n for n in range(4000)]
    cells = ref.indexes(*R.pack(items))
    owner = {}
    for i, row in enumerate(cells.tolist()):
        for cell in row:
            j = owner.setdefault(cell, i)
            if j != i:
                a, b = set(cells[j].tolist()), set(row)
                return items[j], items[i], sorted(a ^ b)
    raise AssertionError("no two items share a cell")


@pytest.mark.parametrize("pair", ["the same item", "two items that share every unset cell"])
def test_of_two_adds_that_meet_exactly_one_is_new(server, conns, pair):
    """Two connections' adds answered together: the second of them, in
    whichever order the window took them, finds every cell set.  The
    batched kernel alone would answer 1 to both."""
    m, k = R.optimal_m(2000, 0.01), 7
    eng = server.server.engine
    a, b, only_one = _meeting_pair(m, k)
    ones = 0
    for r in range(24):
        name = f"win:meet:{pair[:8]}:{r}"
        conns[0].execute("BF.RESERVE", name, "0.01", 2000)
        if pair == "the same item":
            first = second = b"same-%d" % r
        else:
            first, second = (a, b) if r % 2 else (b, a)
            with eng.locked(name):  # what is left unset of either is what both have
                rec = eng.store.get(name)
                rec.arrays["bits"] = rec.arrays["bits"].at[jnp.asarray(only_one)].set(1)
        sends = [(conns[0], ("BF.ADD", name, first)), (conns[1], ("BF.ADD", name, second))]
        if r < 12:
            got = _window(server, sends)
        else:  # and free-running: met in a window or not, the answer stands
            out = [None, None]
            threads = [threading.Thread(
                target=lambda i=i, c=c, cmd=cmd: out.__setitem__(i, c.execute(*cmd)))
                for i, (c, cmd) in enumerate(sends)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            got = out
        assert sorted(got) == [0, 1], (r, got)
        assert conns[2].execute("BF.MEXISTS", name, first, second) == [1, 1]
        ones += sum(got)
    assert ones == 24


def test_an_acknowledged_add_is_seen_by_every_later_probe(server, conns):
    conns[0].execute("BF.RESERVE", "win:seen", "0.01", 20000)
    stop = threading.Event()

    def company(c, base):  # other connections keep windows forming
        n = 0
        while not stop.is_set():
            c.execute("BF.EXISTS" if n % 4 else "BF.ADD", "win:seen", b"other-%d" % (base + n))
            n += 1

    threads = [threading.Thread(target=company, args=(c, 10_000 * i))
               for i, c in enumerate(conns[2:])]
    for t in threads:
        t.start()
    try:
        for r in range(100):
            assert conns[0].execute("BF.ADD", "win:seen", b"seen-%d" % r) in (0, 1)
            assert conns[1].execute("BF.EXISTS", "win:seen", b"seen-%d" % r) == 1, r
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_a_lone_command_is_a_window_of_one_and_waits_for_nobody(server, conn):
    """Nothing holds a command for company: with nobody else connected it is
    answered — one dispatch of one valid row — and no window stays open."""
    conn.execute("BF.RESERVE", "win:lone", "0.01", 1000)
    before = _metrics(conn)
    assert [conn.execute("BF.ADD", "win:lone", b"a"), conn.execute("BF.EXISTS", "win:lone", b"a"),
            conn.execute("bf.exists", "win:lone", b"b"), conn.execute("Bf.Add", "win:lone", b"a")] \
        == [1, 1, 0, 0]
    d = _delta(_metrics(conn), before)
    assert (d["cmds"], d["windows"], d["dispatches"], d["rows_valid"]) == (4, 4, 4, 4)
    assert d["rows_issued"] == 4 * K.MIN_BUCKET
    deadline = time.monotonic() + 30.0  # the job lets go of the record after it answered
    while server.server._point_open and time.monotonic() < deadline:
        time.sleep(0.001)
    assert server.server._point_open == {}


def test_a_connection_never_has_two_members_in_a_window(server, conn):
    """One connection writing single commands ahead of its replies: they are
    answered in the order written, each by a dispatch of its own — the next
    is taken only when the one before it is answered."""
    conn.execute("BF.RESERVE", "win:pipe", "0.01", 1000)
    cmds = [("BF.ADD" if i % 3 == 0 else "BF.EXISTS", "win:pipe", b"p-%d" % (i // 3 * 3))
            for i in range(30)]
    want = [1 if c[0] == "BF.ADD" else 1 for c in cmds]  # an add, then two probes of it
    before = _metrics(conn)
    for c in cmds:  # a send a command: frames of one, or of what had landed
        conn.send(*c)
    assert [conn.read_reply() for _ in cmds] == want
    assert conn.execute_many(cmds) == [0 if c[0] == "BF.ADD" else 1 for c in cmds]  # one frame
    d = _delta(_metrics(conn), before)
    assert d["cmds"] == d["dispatches"] == d["rows_valid"] == 60


def test_multi_exec_and_mixed_frames_answer_as_before(server, conn):
    conn.execute("BF.RESERVE", "win:tx", "0.01", 1000)
    assert conn.execute_many([("MULTI",), ("BF.ADD", "win:tx", b"t"), ("BF.EXISTS", "win:tx", b"t"),
                              ("BF.EXISTS", "win:tx", b"u"), ("EXEC",)]) \
        == [b"OK", b"QUEUED", b"QUEUED", b"QUEUED", [1, 1, 0]]
    assert conn.execute("MULTI") == b"OK"
    assert conn.execute("BF.ADD", "win:tx", b"v") == b"QUEUED"  # a frame of one, inside MULTI
    assert conn.execute("EXEC") == [1]
    assert conn.execute_many([("PING",), ("BF.EXISTS", "win:tx", b"v"), ("BF.MADD", "win:tx", b"w"),
                              ("BF.ADD", "win:tx", b"w"), ("BF.EXISTS", "win:nofilter", b"w")])[:4] \
        == [b"PONG", 1, [1], 0]
    missing = conn.execute("BF.EXISTS", "win:nofilter", b"w")
    assert isinstance(missing, RespError) and "not initialized" in str(missing)


def test_no_member_is_lost_under_a_short_switch_interval(server):
    """The open windows are shared by the loop and the workers: 24
    connections on three records, the interpreter switching threads every
    10 us — every command is answered, counted once, and no window stays
    open."""
    import sys

    admin = Connection(server.server.host, server.server.port, timeout=60.0)
    for r in range(3):
        admin.execute("BF.RESERVE", f"win:stress:{r}", "0.01", 20000)
    before = _metrics(admin)
    answered, errors = [0] * 24, []

    def run(i):
        c = Connection(server.server.host, server.server.port, timeout=60.0)
        try:
            for n in range(60):
                verb = "BF.ADD" if n % 5 == 0 else "BF.EXISTS"
                reply = c.execute(verb, f"win:stress:{i % 3}", b"s-%d-%d" % (i, n // 5 * 5))
                assert reply == 1, (i, n, reply)  # an add of a new item, then probes of it
                answered[i] += 1
        except Exception as e:  # noqa: BLE001 — shown by the test
            errors.append(repr(e))
        finally:
            c.close()

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(i,)) for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert not any(t.is_alive() for t in threads), "a command was never answered"
    finally:
        sys.setswitchinterval(was)
    assert errors == [] and answered == [60] * 24
    d = _delta(_metrics(admin), before)
    assert d["cmds"] == d["rows_valid"] == 24 * 60 and d["dispatches"] <= d["cmds"]
    deadline = time.monotonic() + 30.0
    while server.server._point_open and time.monotonic() < deadline:
        time.sleep(0.001)
    assert server.server._point_open == {}
    admin.close()


def _calls(c, verb: str) -> int:
    for line in bytes(c.execute("INFO", "commandstats")).decode().splitlines():
        if line.startswith(f"cmdstat_{verb.lower()}:"):
            return int(line.split("calls=")[1].split(",")[0])
    return 0


def test_every_member_is_counted_and_a_tracked_reader_is_invalidated(server, conns):
    srv = server.server
    conns[0].execute("BF.RESERVE", "win:stats", "0.01", 1000)
    pushes = []
    conns[7].push_handler = pushes.append
    conns[7].execute("CLIENT", "TRACKING", "ON")
    assert conns[7].execute("BF.EXISTS", "win:stats", b"s-1") == 0  # a tracked read
    calls = {v: _calls(conns[0], v) for v in ("BF.ADD", "BF.EXISTS")}
    commands = srv.stats["commands"]
    cmds = [("BF.ADD", "win:stats", b"s-%d" % i) for i in range(2)] + \
           [("BF.EXISTS", "win:stats", b"s-%d" % i) for i in range(4)]
    assert _window(server, list(zip(conns, cmds))) == [1, 1, 0, 0, 0, 0]
    assert srv.stats["commands"] - commands == 6
    assert _calls(conns[0], "BF.ADD") - calls["BF.ADD"] == 2
    assert _calls(conns[0], "BF.EXISTS") - calls["BF.EXISTS"] == 4
    conns[7].execute("PING")  # drains the push queued ahead of the reply
    assert pushes and bytes(pushes[0][0]) == b"invalidate" and pushes[0][1] == [b"win:stats"]


def test_a_window_that_raises_answers_every_member_and_dispatches_once(server, conns, monkeypatch):
    srv = server.server
    conns[0].execute("BF.RESERVE", "win:fail", "0.01", 1000)
    issued = []

    def failing(self, items, adds):
        issued.append(list(zip(items, adds)))
        raise RuntimeError("the window's dispatch failed")

    monkeypatch.setattr(BloomFilter, "answer_window_async", failing)
    errors = srv.stats["errors"]
    cmds = [("BF.EXISTS", "win:fail", b"f-0"), ("BF.ADD", "win:fail", b"f-1"),
            ("BF.ADD", "win:fail", b"f-2"), ("BF.EXISTS", "win:fail", b"f-3")]
    got = _window(server, list(zip(conns, cmds)))
    assert all(isinstance(r, RespError) and "the window's dispatch failed" in str(r) for r in got)
    assert len(issued) == 1 and sorted(issued[0]) == [  # one dispatch, never issued again
        (b"f-0", False), (b"f-1", True), (b"f-2", True), (b"f-3", False)]
    assert srv.stats["errors"] - errors == 4
    monkeypatch.undo()
    assert conns[0].execute("BF.MEXISTS", "win:fail", b"f-1", b"f-2") == [0, 0]
    assert conns[1].execute("BF.ADD", "win:fail", b"f-1") == 1  # the connections live on


def test_every_member_of_a_window_has_its_spans(server, conns):
    conns[0].execute("BF.RESERVE", "win:trace", "0.01", 1000)
    cmds = [("BF.EXISTS", "win:trace", b"t-%d" % i) for i in range(5)] + \
           [("BF.ADD", "win:trace", b"t-%d" % i) for i in range(2)]
    admin = conns[7]
    admin.execute("CONFIG", "SET", "trace-enabled", "yes")
    try:
        admin.execute("TRACE", "RESET")
        assert _window(server, list(zip(conns, cmds))) == [0] * 5 + [1] * 2
        frames = _point_spans(admin)
    finally:
        admin.execute("CONFIG", "SET", "trace-enabled", "no")
    assert sorted(v for v, _named in frames) == sorted(c[0] for c in cmds)
    for verb, named in frames:
        assert {"hop", "dispatch", "kernel", "point.wait", "readback"} <= set(named)
        (hop,), (dispatch,), (kernel,), (wait,), (readback,) = (
            named[n] for n in ("hop", "dispatch", "kernel", "point.wait", "readback"))
        assert bytes(wait["verb"]).decode() == verb  # the member's; the window's both
        assert bytes(kernel["verb"]).decode() == "BF.EXISTS+BF.ADD" and kernel["members"] == 7
        # submitted -> taken (the pause: not nothing), taken -> answered, and
        # inside that the member's dispatch issued and the window's one fetch
        assert hop["dur"] > 0 and bytes(hop["to"]) == b"dispatch"
        assert hop["off"] + hop["dur"] <= dispatch["off"] + 2
        for inner in (kernel, readback):
            assert dispatch["off"] <= inner["off"] + 2
            assert inner["off"] + inner["dur"] <= dispatch["off"] + dispatch["dur"] + 2
        assert abs(wait["off"] - hop["off"]) <= 2
        assert abs((wait["off"] + wait["dur"]) - (kernel["off"] + kernel["dur"])) <= 2
        assert readback["grouped"] == 1 and readback["parts"] == 1  # one flags array, one fetch


def _point_spans(c):
    """[(frame verb, {span name: [attrs]})] of the traced frames that hold a
    point command, in frame order."""
    out = []
    for _tid, _ms, _us, verb, _n, _cls, _tenant, spans in c.execute(
            "TRACE", "GET", 100, "BY", "nothing"):
        named = {}
        for name, off, dur, attrs in spans:
            kv = {bytes(attrs[i]).decode(): attrs[i + 1] for i in range(0, len(attrs), 2)}
            named.setdefault(bytes(name).decode(), []).append({**kv, "off": off, "dur": dur})
        if "point.wait" in named:
            out.append((bytes(verb).decode(), named))
    return out


@pytest.mark.parametrize("pipelined", [1, 5])
def test_point_wait_and_members_appear_in_trace_get(conn, pipelined):
    conn.execute("BF.RESERVE", f"tr:{pipelined}", "0.01", 1000)
    cmds = [("BF.ADD" if i % 2 == 0 else "BF.EXISTS", f"tr:{pipelined}", b"memtier-%d" % i)
            for i in range(pipelined)]
    conn.execute("CONFIG", "SET", "trace-enabled", "yes")
    try:
        conn.execute("TRACE", "RESET")
        conn.execute_many(cmds)
        frames = _point_spans(conn)
    finally:
        conn.execute("CONFIG", "SET", "trace-enabled", "no")
    kernels = [s for _v, named in frames for s in named["kernel"]]
    waits = [s for _v, named in frames for s in named["point.wait"]]
    assert [bytes(s["verb"]).decode() for s in kernels] == [c[0] for c in cmds]
    assert [s["members"] for s in kernels] == [1] * pipelined
    assert [bytes(s["verb"]).decode() for s in waits] == [c[0] for c in cmds]
    for k, w in zip(kernels, waits):  # planned -> dispatch issued: it ends with the kernel span
        assert abs((w["off"] + w["dur"]) - (k["off"] + k["dur"])) <= 2 and w["off"] <= k["off"]
    assert frames[0][0] == cmds[0][0]


def test_stage_totals_leave_point_wait_out():
    tr = obs.FrameTrace(1, 0.0, 10.0, "BF.EXISTS", 1, 0)
    tr.add_span("hop", 10.0, 10.2)
    tr.add_span("kernel", 10.2, 10.3, verb="BF.EXISTS", members=1)
    tr.add_span("point.wait", 10.0, 10.3, verb="BF.EXISTS")
    assert set(tr.stage_totals()) == {"hop", "kernel"}


@pytest.mark.parametrize("kernel", ["bloom_add_bytes_masked", "bloom_contains_bytes_masked"])
def test_the_bytes_kernels_carry_their_scope(kernel):
    bits = jnp.zeros((2048,), jnp.uint8)
    words, nbytes = jnp.zeros((4, 256), jnp.uint32), jnp.zeros((256,), jnp.uint32)
    text = getattr(K, kernel).lower(bits, words, nbytes, jnp.int32(1), 7, 2000).as_text(
        debug_info=True)
    assert f"jit({kernel})/{kernel}/" in text


def test_bf_info_reports_the_capacity(conn):
    conn.execute("BF.RESERVE", "info:bf", "0.01", 20000)
    info = conn.execute("BF.INFO", "info:bf")
    info = {bytes(info[i]).decode(): info[i + 1] for i in range(0, len(info), 2)}
    assert (info["Capacity"], info["Size"], info["Number of hashes"]) == (
        20000, R.optimal_m(20000, 0.01), 7)

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
    them (one a command on the path that stands), rows valid <= rows issued
    (one row asked of a bucket of ``MIN_BUCKET``);
  * with tracing armed a point command's frame carries a ``kernel`` span
    with ``verb`` and ``members`` and a ``point.wait`` span that ends where
    the ``kernel`` span ends; stage totals leave ``point.wait`` out;
  * the two bytes kernels carry their ``jax.named_scope``;
  * ``BF.INFO`` reports the capacity the filter was reserved with.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import loadgen
from benchmark import reference_bf as R
from benchmark.generators import memtier_bf as G
from redisson_tpu.core import kernels as K
from redisson_tpu.net.client import Connection
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
    assert c["dispatches"] == c["cmds"]  # one a command, on the path that stands
    assert c["rows_valid"] == sent and c["rows_issued"] == sent * K.MIN_BUCKET
    assert c["rows_valid"] <= c["rows_issued"]


@pytest.mark.parametrize("verb", ["BF.ADD", "BF.EXISTS"])
def test_one_command_counts_once(conn, verb):
    conn.execute("BF.RESERVE", "cnt:" + verb, "0.01", 1000)
    before = _metrics(conn)
    assert conn.execute(verb, "cnt:" + verb, b"memtier-7") == (1 if verb == "BF.ADD" else 0)
    d = _delta(_metrics(conn), before)
    other = "cmds_bf_exists" if verb == "BF.ADD" else "cmds_bf_add"
    assert d == {"cmds": 1, "cmds_" + verb.lower().replace(".", "_"): 1, other: 0,
                 "dispatches": 1, "rows_valid": 1, "rows_issued": K.MIN_BUCKET}


def test_the_batch_forms_count_nothing(conn):
    conn.execute("BF.RESERVE", "cnt:batch", "0.01", 1000)
    before = _metrics(conn)
    assert conn.execute("BF.MADD", "cnt:batch", b"a", b"b") == [1, 1]
    assert conn.execute("BF.MEXISTS", "cnt:batch", b"a", b"c") == [1, 0]
    assert _delta(_metrics(conn), before) == dict.fromkeys(before, 0)


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

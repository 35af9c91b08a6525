"""ISSUE 32: a hash-mode search index learns of a write AT THE WRITE, a
frame's run of KNN searches is one stacked dispatch, and the FLAT top-k is
blocked with the row norms kept beside the bank.

Contracts pinned here:
  * every way a key under an index's prefix can change (HSET, HDEL, DEL,
    UNLINK, a TTL passing, RENAME in and out, FLUSHALL, FT.CREATE over
    existing keys, two indexes on one prefix) is in what the next FT.SEARCH
    on the connection sees, and no search walks the keyspace
    (``search_scan_keys_total`` unmoved);
  * a frame of N FT.SEARCH answers what N single commands answer, byte for
    byte, rides ONE ``kernel`` span of N members, is cut by an interleaved
    HSET, and meets no cold program for any N once one run has warmed the
    query buckets;
  * the blocked top-k returns the ids and distances of the NumPy path
    (``knn_host``) — ties, dead rows, k above the live rows, a capacity
    that is no multiple of the block, a hybrid filter, INT8 / FLOAT16,
    sharded — on integer and Gaussian data;
  * the norms plane follows set_row / overwrite / delete / growth.

ISSUE 33: inside a stacked KNN wave host work is per wave.  Members that
differ from the wave's first in the query blob alone share its plan and are
answered as wire bytes by the wave's encoder — byte for byte what each
answers alone, under RESP2 and RESP3; any other member is planned alone and
rides the same dispatch; ``rtpu_knn_wave_cmds_total`` /
``rtpu_knn_wave_shared_cmds_total`` count both kinds.
"""
import socket
import time

import numpy as np
import pytest

from redisson_tpu.core import kernels as K
from redisson_tpu.core.engine import Engine
from redisson_tpu.net import resp
from redisson_tpu.net.client import Connection
from redisson_tpu.server.server import ServerThread
from redisson_tpu.services import search as S
from redisson_tpu.services import vector as V
from redisson_tpu.services.search import Range, SearchService

DIM = 8


@pytest.fixture()
def server():
    with ServerThread(port=0, workers=4) as st:
        yield st


@pytest.fixture()
def svc():
    return SearchService(Engine())


@pytest.fixture()
def small_block(monkeypatch):
    """A block small enough that tiny banks take the scan, the partial last
    tile and the merge."""
    monkeypatch.setattr(K, "KNN_BLOCK", 64)
    K.knn_flat_topk.clear_cache()
    yield 64
    K.knn_flat_topk.clear_cache()


def _conn(st):
    return Connection(st.server.host, st.server.port, timeout=60.0)


def _vecs(n, seed=0, integer=True):
    rng = np.random.default_rng(seed)
    if integer:
        return rng.integers(0, 256, (n, DIM)).astype(np.float32)
    return rng.standard_normal((n, DIM)).astype(np.float32)


def _create(c, idx="ix", prefix="doc:", extra=()):
    assert c.execute(
        "FT.CREATE", idx, "ON", "HASH", "PREFIX", "1", prefix, "SCHEMA", *extra,
        "vector", "VECTOR", "FLAT", "6", "TYPE", "FLOAT32", "DIM", str(DIM),
        "DISTANCE_METRIC", "L2") == b"OK"


def _search_cmd(q, idx="ix", k=3, *opts):
    return ("FT.SEARCH", idx, f"*=>[KNN {k} @vector $BLOB]", "NOCONTENT",
            "PARAMS", "2", "BLOB", np.asarray(q, np.float32).tobytes(), *opts)


def _ids(reply):
    return [bytes(x).decode() for x in reply[1::2]]


def _load(c, vecs, prefix="doc:"):
    for i, v in enumerate(vecs):
        assert c.execute("HSET", f"{prefix}{i}", "vector", v.tobytes()) == 1


# -- index at the write, over the wire ------------------------------------------


def _hset_new(c, vecs):
    c.execute("HSET", "doc:new", "vector", (vecs[0] + 1).tobytes())
    return "doc:new", True


def _hset_over(c, vecs):
    c.execute("HSET", "doc:5", "vector", (vecs[0] + 1).tobytes())
    return "doc:5", True


def _hdel(c, vecs):
    c.execute("HSET", "doc:0", "vector", (vecs[0] + 1).tobytes())
    assert c.execute("HDEL", "doc:0", "vector") == 1
    return "doc:0", False


def _del(c, vecs):
    c.execute("HSET", "doc:0", "vector", (vecs[0] + 1).tobytes())
    assert c.execute("DEL", "doc:0") == 1
    return "doc:0", False


def _unlink(c, vecs):
    c.execute("HSET", "doc:0", "vector", (vecs[0] + 1).tobytes())
    assert c.execute("UNLINK", "doc:0") == 1
    return "doc:0", False


def _expire(c, vecs):
    c.execute("HSET", "doc:0", "vector", (vecs[0] + 1).tobytes())
    assert c.execute("PEXPIRE", "doc:0", "40") == 1
    assert "doc:0" in _ids(c.execute(*_search_cmd(vecs[0] + 1)))  # not yet
    time.sleep(0.12)
    return "doc:0", False


def _rename_in(c, vecs):
    c.execute("HSET", "other:9", "vector", (vecs[0] + 1).tobytes())
    assert c.execute("RENAME", "other:9", "doc:moved") == b"OK"
    return "doc:moved", True


def _rename_out(c, vecs):
    c.execute("HSET", "doc:0", "vector", (vecs[0] + 1).tobytes())
    assert c.execute("RENAME", "doc:0", "other:9") == b"OK"
    return "doc:0", False


@pytest.mark.parametrize("change", [
    _hset_new, _hset_over, _hdel, _del, _unlink, _expire, _rename_in, _rename_out,
], ids=lambda f: f.__name__.strip("_"))
def test_write_is_in_what_the_next_search_sees(server, change):
    c = _conn(server)
    _create(c)
    vecs = _vecs(24, seed=3)
    _load(c, vecs)
    scanned = S.search_counted()[1]
    key, present = change(c, vecs)
    # the probe is the vector every change writes: at distance 0 if it is there
    hits = c.execute(*_search_cmd(vecs[0] + 1, "ix", 3))
    assert (key in _ids(hits)) is present, hits
    if present:
        assert _ids(hits)[0] == key and bytes(hits[2][1]) == b"0"
    assert S.search_counted()[1] == scanned  # no search walked the keyspace
    c.close()


def test_documents_are_indexed_before_the_frame_is_answered(server):
    c = _conn(server)
    _create(c)
    vecs = _vecs(40, seed=4)
    indexed, scanned = S.search_counted()
    c.execute_many([("HSET", f"doc:{i}", "vector", v.tobytes())
                    for i, v in enumerate(vecs)])
    # no FT.* yet: the counter moved at the write
    assert S.search_counted() == (indexed + 40, scanned)
    info = c.execute("FT.INFO", "ix")
    assert info[info.index(b"num_docs") + 1] == 40
    assert S.search_counted() == (indexed + 40, scanned)
    c.close()


def test_flushall_empties_the_index_and_it_serves_on(server):
    c = _conn(server)
    _create(c)
    vecs = _vecs(16, seed=5)
    _load(c, vecs)
    assert c.execute("FLUSHALL") == b"OK"
    scanned = S.search_counted()[1]
    assert c.execute(*_search_cmd(vecs[2]))[0] == 0
    c.execute("HSET", "doc:after", "vector", vecs[2].tobytes())
    assert _ids(c.execute(*_search_cmd(vecs[2]))) == ["doc:after"]
    assert S.search_counted()[1] == scanned
    c.close()


def test_create_over_existing_keys_scans_once(server):
    c = _conn(server)
    vecs = _vecs(12, seed=6)
    _load(c, vecs)
    c.execute("SET", "doc:string", "not a hash")
    scanned = S.search_counted()[1]
    _create(c)
    after_create = S.search_counted()[1]
    assert after_create > scanned  # FT.CREATE's one scan, counted
    hits = c.execute(*_search_cmd(vecs[7]))
    assert _ids(hits)[0] == "doc:7"
    assert S.search_counted()[1] == after_create
    c.close()


def test_dropindex_stops_the_hooks_and_a_new_index_starts_clean(server):
    c = _conn(server)
    _create(c)
    vecs = _vecs(8, seed=7)
    _load(c, vecs)
    assert c.execute("FT.DROPINDEX", "ix") == b"OK"
    eng = server.server.engine
    assert eng.ingest_hook is None and eng.store.on_change is None
    c.execute("DEL", "doc:0")
    _create(c)
    assert "doc:0" not in _ids(c.execute(*_search_cmd(vecs[0], "ix", 8)))
    assert eng.ingest_hook is not None
    c.close()


def test_two_indexes_on_one_prefix_both_follow(server):
    c = _conn(server)
    _create(c, "ia")
    _create(c, "ib", extra=("price", "NUMERIC"))
    vecs = _vecs(10, seed=8)
    for i, v in enumerate(vecs):
        c.execute("HSET", f"doc:{i}", "vector", v.tobytes(), "price", str(i))
    scanned = S.search_counted()[1]
    c.execute("DEL", "doc:3")
    for idx in ("ia", "ib"):
        got = _ids(c.execute(*_search_cmd(vecs[3], idx, 10)))
        assert "doc:3" not in got and len(got) == 9
    assert c.execute("FT.SEARCH", "ib", "@price:[2 4]", "NOCONTENT")[0] == 2
    assert S.search_counted()[1] == scanned
    c.close()


def test_a_write_with_no_index_pays_no_hook(server):
    eng = server.server.engine
    c = _conn(server)
    c.execute("HSET", "doc:1", "vector", b"x")
    assert eng.ingest_hook is None and eng.store.on_change is None
    assert "search" not in eng._services
    c.close()


def test_entry_mode_keeps_its_version_diffed_sync():
    eng = Engine()
    from redisson_tpu.client.objects.map import Map

    svc = SearchService(eng)
    svc.create_index("e", {"age": "NUMERIC"}, prefixes=["users:"])
    Map(eng, "users:eu").put("u1", {"age": 36})
    assert eng.ingest_hook is None  # an entry-mode index arms no hook
    assert svc.sync("e") == 1 and svc.sync("e") == 0
    assert svc.search("e", Range("age", 30, 40)).total == 1


# -- one stacked dispatch a frame's run -----------------------------------------


def _spans(c, verb="FT.SEARCH"):
    out = []
    for _tid, _ms, _us, _verb, _n, _cls, _tenant, spans in c.execute(
            "TRACE", "GET", 100, "BY", "nothing"):
        for name, _off, _dur, attrs in spans:
            kv = {bytes(attrs[i]).decode(): attrs[i + 1] for i in range(0, len(attrs), 2)}
            if bytes(name) == b"kernel" and bytes(kv.get("verb", b"")).decode() == verb:
                out.append({k: (int(v) if isinstance(v, int) else v) for k, v in kv.items()})
    return out


@pytest.fixture()
def loaded(server):
    c = _conn(server)
    _create(c, extra=("price", "NUMERIC"))
    vecs = _vecs(80, seed=9)
    for i, v in enumerate(vecs):
        c.execute("HSET", f"doc:{i}", "vector", v.tobytes(), "price", str(i))
    yield c, vecs
    c.close()


@pytest.mark.parametrize("n", [2, 3, 5, 17, 64, 70])
def test_a_frame_answers_what_single_commands_answer(loaded, n):
    c, vecs = loaded
    queries = _vecs(n, seed=100 + n)
    opts = [(), ("LIMIT", "1", "2"), ("SORTBY", "__vector_score", "DESC"),
            ("WITHCURSOR", "COUNT", "2"), ("DIALECT", "2")]
    cmds = [_search_cmd(q, "ix", 4, *opts[i % len(opts)]) for i, q in enumerate(queries)]
    alone = [c.execute(*cmd) for cmd in cmds]
    c.execute("CONFIG", "SET", "trace-enabled", "yes")
    c.execute("TRACE", "RESET")
    together = c.execute_many(cmds)
    spans = _spans(c)
    c.execute("CONFIG", "SET", "trace-enabled", "no")

    def shape(reply):  # cursor ids differ by design: a cursor a command
        if reply and isinstance(reply[0], list):
            return [reply[0], bool(reply[1])]
        return reply

    assert [shape(r) for r in together] == [shape(r) for r in alone]
    # every search rode a stacked dispatch: members add up to the frame
    assert sum(s["members"] for s in spans) == n
    assert len(spans) == -(-n // 64) or len(spans) <= 3  # the read loop may cut
    for s in spans:
        assert s["stacked"] in V.KNN_QUERY_BUCKETS and s["stacked"] >= s["members"]


def test_msearch_and_filtered_searches_ride_their_own_forms(loaded):
    c, vecs = loaded
    q = _vecs(6, seed=31)
    cmds = [
        _search_cmd(q[0], "ix", 3), _search_cmd(q[1], "ix", 3),
        ("FT.MSEARCH", "ix", "*=>[KNN 3 @vector $BLOB]", "PARAMS", "2", "BLOB",
         q[2:4].tobytes()),
        ("FT.SEARCH", "ix", "(@price:[10 30])=>[KNN 3 @vector $BLOB]", "NOCONTENT",
         "PARAMS", "2", "BLOB", q[4].tobytes()),
        ("FT.SEARCH", "ix", "(@price:[10 30])=>[KNN 3 @vector $BLOB]", "NOCONTENT",
         "PARAMS", "2", "BLOB", q[5].tobytes()),
        ("FT.SEARCH", "ix", "@price:[10 12]", "NOCONTENT"),
    ]
    alone = [c.execute(*cmd) for cmd in cmds]
    assert c.execute_many(cmds) == alone
    for i in _ids(alone[3]):
        assert 10 <= int(i.split(":")[1]) <= 30


def test_an_interleaved_write_cuts_the_run_and_is_seen(loaded):
    c, vecs = loaded
    probe = vecs[0] + 1
    cmds = [_search_cmd(probe), _search_cmd(probe),
            ("HSET", "doc:cut", "vector", probe.astype(np.float32).tobytes()),
            _search_cmd(probe), _search_cmd(probe)]
    c.execute("CONFIG", "SET", "trace-enabled", "yes")
    c.execute("TRACE", "RESET")
    out = c.execute_many(cmds)
    spans = _spans(c)
    c.execute("CONFIG", "SET", "trace-enabled", "no")
    assert "doc:cut" not in _ids(out[0]) + _ids(out[1])
    assert _ids(out[3])[0] == "doc:cut" and _ids(out[4])[0] == "doc:cut"
    assert [s["members"] for s in spans] == [2, 2]


def test_no_run_length_meets_a_cold_program(loaded):
    c, vecs = loaded
    queries = _vecs(64, seed=55)
    c.execute_many([_search_cmd(q) for q in queries[:2]])  # warms every bucket
    built = K.knn_flat_topk._cache_size()
    for n in (1, 2, 3, 4, 5, 9, 16, 17, 33, 64):
        c.execute_many([_search_cmd(q) for q in queries[:n]])
    assert K.knn_flat_topk._cache_size() == built


def test_knn_counters_count_queries_slots_and_rows(loaded):
    c, vecs = loaded
    q0, s0, r0 = V.knn_counted()
    c.execute_many([_search_cmd(q) for q in _vecs(5, seed=77)])
    q1, s1, r1 = V.knn_counted()
    assert (q1 - q0, s1 - s0, r1 - r0) == (5, 16, 5 * 80)
    c.execute("DEL", "doc:0")
    c.execute(*_search_cmd(vecs[1]))
    assert V.knn_counted() == (q1 + 1, s1 + 1, r1 + 79)


# -- host work is a wave's: one plan, one answer (ISSUE 33) ----------------------


def _raw(sock, cmds):
    """The bytes that answer `cmds`, sent as one frame."""
    sock.sendall(b"".join(resp.encode_command(*c) for c in cmds))
    parser, buf, got = resp.RespParser(), b"", 0
    while got < len(cmds):
        chunk = sock.recv(1 << 20)
        assert chunk, "the server hung up"
        buf += chunk
        got += len(parser.feed(chunk))
    return buf


def _wave_counters(c):
    rows = dict(line.rsplit(" ", 1) for line in
                bytes(c.execute("METRICS")).decode().splitlines() if line)
    return (int(float(rows["rtpu_knn_wave_cmds_total"])),
            int(float(rows["rtpu_knn_wave_shared_cmds_total"])))


def _wave_spans(c):
    """(name, attrs) of the kernel and wave.* spans of the traced frames, in
    frame order."""
    out = []
    for *_head, spans in c.execute("TRACE", "GET", 100, "BY", "nothing"):
        for name, _off, _dur, attrs in spans:
            if bytes(name) in (b"kernel", b"wave.plan", b"wave.answer"):
                out.append((bytes(name).decode(), {
                    bytes(attrs[i]).decode(): attrs[i + 1]
                    for i in range(0, len(attrs), 2)}))
    return out


@pytest.fixture()
def gone_meanwhile(server, monkeypatch):
    """hide(docs): those documents of "ix" are deleted between every
    dispatch and its fetch from then on (the rows score, the ids are gone
    when the reply is made) — for a command alone as for a wave."""
    from redisson_tpu.server import server as server_mod

    hidden = []
    force = server_mod._force_lazies

    def force_with_rows_gone(results, srv):
        idx = srv.engine._services["search"]._idx("ix")
        rows = [idx._rowid[d] for d in hidden]
        for row in rows:
            idx._rowdoc[row] = None
        try:
            return force(results, srv)
        finally:
            for row, d in zip(rows, hidden):
                idx._rowdoc[row] = d

    monkeypatch.setattr(server_mod, "_force_lazies", force_with_rows_gone)
    return hidden.extend


_SCORE = ("SORTBY", "__vector_score")
_WAVE_CASES = {
    # the wording of the ann-batch cell
    "limit_0_10": (10, _SCORE + ("LIMIT", "0", "10", "DIALECT", "2")),
    "limit_2_5": (10, ("LIMIT", "2", "5")),
    "sortby_desc": (6, _SCORE + ("DESC", "LIMIT", "1", "5")),
    "k_above_live": (100, ("LIMIT", "0", "100")),
    "empty": (5, ()),
    "deleted_meanwhile": (5, ()),
    "sharded": (7, _SCORE + ("LIMIT", "0", "7")),
}


@pytest.mark.parametrize("proto", [2, 3], ids=["hello2", "hello3"])
@pytest.mark.parametrize("case", list(_WAVE_CASES))
def test_a_shared_plan_frame_answers_the_single_commands_bytes(
        server, gone_meanwhile, case, proto):
    n = 12
    k, opts = _WAVE_CASES[case]
    c = _conn(server)
    vecs = _vecs(80, seed=9)
    if case == "sharded":
        assert c.execute(
            "FT.CREATE", "ix", "ON", "HASH", "PREFIX", "1", "doc:", "SCHEMA",
            "vector", "VECTOR", "FLAT", "8", "TYPE", "FLOAT32", "DIM", str(DIM),
            "DISTANCE_METRIC", "L2", "SHARDS", "2") == b"OK"
    else:
        _create(c)
    _load(c, vecs)
    if case == "empty":
        gone_meanwhile(f"doc:{i}" for i in range(len(vecs)))
    if case == "deleted_meanwhile":
        gone_meanwhile(["doc:3", "doc:4"])
    queries = np.concatenate([vecs[3:5] + 1, _vecs(n - 2, seed=200)])
    cmds = [_search_cmd(q, "ix", k, *opts) for q in queries]
    with socket.create_connection((server.server.host, server.server.port),
                                  timeout=60.0) as sock:
        _raw(sock, [("HELLO", str(proto))])
        alone = [_raw(sock, [cmd]) for cmd in cmds]
        before = _wave_counters(c)
        together = _raw(sock, cmds)
        after = _wave_counters(c)
    assert together == b"".join(alone)
    assert (after[0] - before[0], after[1] - before[1]) == (n, n)
    # and the bytes say what the case is about
    replies = resp.RespParser().feed(together)
    if case == "empty":
        assert replies == [[0]] * n
    elif case == "deleted_meanwhile":
        assert replies[0][0] == k - 1 and "doc:3" not in _ids(replies[0])
        assert replies[1][0] == k - 1 and "doc:4" not in _ids(replies[1])
        assert replies[2][0] == k
    elif case == "k_above_live":
        assert all(r[0] == len(vecs) and len(r) == 1 + 2 * len(vecs) for r in replies)
    elif case == "limit_2_5":
        assert all(r[0] == k and len(r) == 1 + 2 * 5 for r in replies)
    else:
        assert _ids(replies[0])[0 if "DESC" not in opts else -1] in ("doc:3", "doc:4")
    c.close()


def _mixed_wave(q, first_is_plain):
    """Searches of one index and query text that differ in more than the
    blob: (the frame's commands, how many of them share the first's plan)."""
    plain = [_search_cmd(v, "ix", 4) for v in q[:4]]
    other = [
        _search_cmd(q[4], "ix", 4, "WITHCURSOR", "COUNT", "2"),
        _search_cmd(q[5], "ix", 4, "LIMIT", "1", "2"),
        ("FT.MSEARCH", "ix", "*=>[KNN 4 @vector $BLOB]", "PARAMS", "2", "BLOB",
         q[6:8].tobytes()),
        ("FT.SEARCH", "ix", "*=>[KNN 4 @vector $BLOB]", "PARAMS", "2", "BLOB",
         q[8].tobytes()),  # returns content
    ]
    if first_is_plain:
        return plain[:2] + other[:2] + plain[2:3] + other[2:] + plain[3:], 4
    return other + plain, 0  # nobody is the cursor search's byte for byte


@pytest.mark.parametrize("first_is_plain", [True, False], ids=["plain_first", "other_first"])
def test_a_mixed_wave_rides_one_dispatch_and_answers_each_as_alone(loaded, first_is_plain):
    c, vecs = loaded
    cmds, shared = _mixed_wave(_vecs(9, seed=41), first_is_plain)
    alone = [c.execute(*cmd) for cmd in cmds]
    before = _wave_counters(c)
    c.execute("CONFIG", "SET", "trace-enabled", "yes")
    c.execute("TRACE", "RESET")
    together = c.execute_many(cmds)
    spans = _wave_spans(c)
    c.execute("CONFIG", "SET", "trace-enabled", "no")
    after = _wave_counters(c)

    def shape(reply):  # a cursor a command: the ids differ by design
        if reply and isinstance(reply[0], list):
            return [reply[0], bool(reply[1])]
        return reply

    assert [shape(r) for r in together] == [shape(r) for r in alone]
    content = next(i for i, cmd in enumerate(cmds)
                   if cmd[0] == "FT.SEARCH" and "NOCONTENT" not in cmd)
    assert b"vector" in together[content][2]
    assert (after[0] - before[0], after[1] - before[1]) == (len(cmds), shared)
    assert [name for name, _a in spans] == ["wave.plan", "kernel", "wave.answer"]
    assert spans[1][1]["members"] == len(cmds) and spans[1][1]["stacked"] == 16
    assert (spans[2][1]["members"], spans[2][1]["shared"]) == (len(cmds), shared)


def test_a_write_between_shared_plan_searches_cuts_the_wave(loaded):
    c, vecs = loaded
    probe = (vecs[0] + 1).astype(np.float32)
    half = [_search_cmd(probe + i, "ix", 3, "LIMIT", "0", "3") for i in range(3)]
    before = _wave_counters(c)
    c.execute("CONFIG", "SET", "trace-enabled", "yes")
    c.execute("TRACE", "RESET")
    out = c.execute_many(half + [("HSET", "doc:cut", "vector", probe.tobytes())] + half)
    spans = _wave_spans(c)
    c.execute("CONFIG", "SET", "trace-enabled", "no")
    assert all("doc:cut" not in _ids(r) for r in out[:3])
    assert all(_ids(r)[0] == "doc:cut" for r in out[4:])
    assert [a["members"] for name, a in spans if name == "kernel"] == [3, 3]
    assert [a["shared"] for name, a in spans if name == "wave.answer"] == [3, 3]
    after = _wave_counters(c)
    assert (after[0] - before[0], after[1] - before[1]) == (6, 6)


@pytest.mark.parametrize("blob", [b"", b"12345", b"\x00" * (4 * DIM + 4)],
                         ids=["empty", "five_bytes", "one_float_more"])
def test_a_malformed_blob_is_its_members_own_error(loaded, blob):
    c, vecs = loaded
    good = [_search_cmd(v + 1, "ix", 3) for v in vecs[:3]]
    bad = ("FT.SEARCH", "ix", "*=>[KNN 3 @vector $BLOB]", "NOCONTENT",
           "PARAMS", "2", "BLOB", blob)
    out = c.execute_many(good[:2] + [bad] + good[2:])
    assert isinstance(out[2], Exception) and "vector blob" in str(out[2])
    assert [_ids(r)[0] for r in out[:2] + out[3:]] == ["doc:0", "doc:1", "doc:2"]


def test_no_wave_of_either_kind_meets_a_cold_program(loaded):
    c, vecs = loaded
    q = _vecs(64, seed=56)
    c.execute_many([_search_cmd(v, "ix", 4) for v in q[:2]])  # warms every bucket
    built = K.knn_flat_topk._cache_size()
    for n in (2, 7, 33, 64):
        c.execute_many([_search_cmd(v, "ix", 4, "LIMIT", "0", "4") for v in q[:n]])
    for first_is_plain in (True, False):
        c.execute_many(_mixed_wave(q[:9], first_is_plain)[0])
    assert K.knn_flat_topk._cache_size() == built


# -- the blocked top-k against the NumPy path ------------------------------------


def _bank(svc, name, vecs, **spec):
    svc.create_index(name, {"emb": "VECTOR"},
                     vector={"emb": {"dim": vecs.shape[1], "metric": "L2", **spec}})
    for i, v in enumerate(vecs):
        svc.add_document(name, f"d{i}", {"emb": v})
    return svc._idx(name).vectors.banks["emb"]


def _same_as_host(bank, queries, k, allowed=None):
    dev = bank.knn_async(queries, k, allowed_rows=allowed)
    host = bank.knn_host(queries, k, allowed_rows=allowed)
    d_dev, i_dev = bank.resolve_hits(tuple(np.asarray(a) for a in dev[:-2]))
    d_host, i_host = host[0], host[1]
    nq = len(queries)
    finite = np.isfinite(d_host)
    assert (np.isfinite(d_dev[:nq]) == finite).all()
    assert (i_dev[:nq][finite] == i_host[finite]).all()
    assert np.allclose(d_dev[:nq][finite], d_host[finite], rtol=1e-5, atol=1e-3)
    return i_host


@pytest.mark.parametrize("integer", [True, False], ids=["integer", "gaussian"])
@pytest.mark.parametrize("case", [
    "plain", "ties", "dead_rows", "k_above_live", "partial_last_block", "masked",
    "INT8", "FLOAT16", "sharded", "cosine",
])
def test_blocked_topk_is_the_numpy_paths(svc, small_block, case, integer):
    n = {"partial_last_block": 300, "k_above_live": 70}.get(case, 256)
    vecs = _vecs(n, seed=11, integer=integer)
    queries = _vecs(5, seed=12, integer=integer)
    spec, k, allowed = {}, 10, None
    if case == "ties":
        vecs[1::2] = vecs[0::2]  # every row twice: the lower rowid must win
    if case in ("INT8", "FLOAT16"):
        spec["dtype"] = case
    if case == "sharded":
        spec["shards"] = 2
    if case == "cosine":
        spec["metric"] = "COSINE"
        vecs, queries = vecs + 1.0, queries + 1.0
    if case == "partial_last_block":
        # a capacity that is no multiple of the block: 75 * 2**n rows
        bank = V.EmbeddingBank(svc._engine, "odd", V.VectorFieldSpec(
            field="emb", dim=DIM, metric="L2"), block=75)
        for i, v in enumerate(vecs):
            bank.set_row(i, v)
        bank.flush_pending()
        assert bank._cap % small_block
    else:
        bank = _bank(svc, "b_" + case, vecs, **spec)
    if case == "dead_rows":
        for i in range(0, n, 3):
            bank.set_row(i, None)
    if case == "k_above_live":
        for i in range(5, n):
            bank.set_row(i, None)
        k = 9
    if case == "masked":
        allowed = np.arange(3, n, 7)
    got = _same_as_host(bank, queries, k, allowed)
    if case == "ties":
        assert (got[:, 0::2] % 2 == 0).all() and (got[:, 1::2] == got[:, 0::2] + 1).all()
    if case == "masked":
        assert np.isin(got, allowed).all()


def test_norms_plane_follows_the_rows(svc):
    vecs = _vecs(10, seed=13)
    bank = _bank(svc, "n", vecs)

    def norms():
        bank.flush_pending()
        return np.asarray(bank._get_norms())

    want = (vecs * vecs).sum(axis=1)
    assert np.array_equal(norms()[:10], want) and not norms()[10:].any()
    bank.set_row(3, vecs[0])  # overwrite
    bank.set_row(4, None)     # delete
    got = norms()
    assert got[3] == want[0] and got[4] == 0.0 and got[5] == want[5]
    more = _vecs(600, seed=14)  # growth: the plane is copied on the device
    for i, v in enumerate(more):
        bank.set_row(10 + i, v)
    got = norms()
    assert len(got) == bank._cap >= 610 and bank.grows >= 1
    assert got[3] == want[0] and np.array_equal(got[10:610], (more * more).sum(axis=1))
    # a record that came without the plane gets one in one pass
    bank._rec().arrays.pop("norms")
    assert np.array_equal(np.asarray(bank._norms_locked(*bank._get_planes()[::2]))[10:610],
                          (more * more).sum(axis=1))


def test_rowdocs_take_maps_rows_to_ids():
    rd = S._RowDocs()
    for i in range(600):
        rd.append(f"d{i}")
    rd[7] = None
    got = rd.take(np.array([[0, 7], [599, 600], [-1, 300]]))
    assert got.tolist() == [["d0", None], ["d599", None], [None, "d300"]]
    assert len(rd) == 600 and rd[599] == "d599" and rd[600] is None

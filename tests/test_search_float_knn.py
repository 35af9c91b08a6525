"""Exact KNN over float embeddings at GIST's width (960 dims), over the wire,
against the plain float64 reference of the benchmark's configuration
``ann-gist-1m`` (``benchmark/reference_ann_float.py``: NumPy, no program code).

Contracts pinned here:
  * every returned id is admissible (its float64 distance at most the k-th
    times 1 + EPS, EPS the rounding a float32 L2 of 960 terms may carry),
    every printed score is the canonical float32 of its pair
    (``vector.pair_scores``) written so that it reads back whole, and lies
    within EPS of the id's float64 distance;
  * a reply ascends in its printed scores, also where the scoring path
    found two neighbours in the other order: a constructed near-tie, which
    the always-on counters ``rtpu_knn_hits_total`` /
    ``rtpu_knn_hits_reordered_total`` count;
  * a search answers the same bytes in a frame's stacked wave as alone, and
    with the device plane armed as disarmed.
"""
import socket

import numpy as np
import pytest

from benchmark import reference_ann_float as F
from redisson_tpu.core import coalesce as C
from redisson_tpu.net import resp
from redisson_tpu.net.client import Connection
from redisson_tpu.server.server import ServerThread
from redisson_tpu.services import vector as V

DIM, K, N, Q = 960, 10, 3000, 24
SPREAD = (0.008, 0.03)


def _create(c, idx, prefix):
    assert c.execute(
        "FT.CREATE", idx, "ON", "HASH", "PREFIX", "1", prefix, "SCHEMA",
        "vector", "VECTOR", "FLAT", "6", "TYPE", "FLOAT32", "DIM", str(DIM),
        "DISTANCE_METRIC", "L2") == b"OK"


def _cmd(q, idx="idx", k=K):
    return ("FT.SEARCH", idx, f"*=>[KNN {k} @vector $BLOB]", "NOCONTENT",
            "SORTBY", "__vector_score", "LIMIT", "0", str(k), "PARAMS", "2", "BLOB",
            np.asarray(q, np.float32).tobytes(), "DIALECT", "2")


def _hits(reply):
    """(doc ids, score texts) of one FT.SEARCH NOCONTENT reply."""
    return ([bytes(d).decode() for d in reply[1::2]],
            [bytes(f[1]) for f in reply[2::2]])


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


@pytest.fixture(scope="module")
def data():
    base = F.make_points(11, 0, 0, N, DIM, 16, 0.035, SPREAD)
    queries = F.make_points(11, 1, 0, Q, DIM, 16, 0.035, SPREAD)
    return base, queries, F.tables([(base, 0, None)], queries, K)


@pytest.fixture(scope="module")
def served(data):
    base = data[0]
    with ServerThread(port=0, workers=4) as st:
        c = Connection(st.server.host, st.server.port, timeout=120.0)
        _create(c, "idx", "doc:")
        for lo in range(0, N, 500):
            assert c.execute_many([("HSET", f"doc:{i}", "vector", base[i].tobytes())
                                   for i in range(lo, min(N, lo + 500))]) == [1] * min(500, N - lo)
        yield st, c
        c.close()


def _bank(st, idx="idx"):
    found = st.server.engine._services["search"]._idx(idx)
    return found, found.vectors.banks["vector"]


@pytest.mark.parametrize("frame", [1, 8, Q], ids=["alone", "wave8", "wave24"])
def test_replies_hold_the_float32_contract(served, data, frame):
    st, c = served
    _base, queries, table = data
    cmds = [_cmd(q) for q in queries]
    replies = [r for a in range(0, Q, frame) for r in c.execute_many(cmds[a:a + frame])]
    ids = np.array([[int(d[4:]) for d in _hits(r)[0]] for r in replies])
    texts = [_hits(r)[1] for r in replies]
    scores = np.array([F.parse_scores(t) for t in texts])
    assert not F.reply_failures(table, ids, scores, DIM).any()
    # the text is the canonical float32 of the pair, whole
    idx, bank = _bank(st)
    rows = np.array([[idx._rowid[f"doc:{i}"] for i in row] for row in ids])
    canonical = bank.pair_scores(queries, np.repeat(np.arange(Q), K), rows.reshape(-1))
    assert np.array_equal(scores.reshape(-1).astype(np.float32), canonical)
    assert all(np.float32(float(t)) == np.float32(s)
               for row, srow in zip(texts, scores) for t, s in zip(row, srow))


def test_a_reply_to_a_ranking_in_lower_precision_is_refused(data):
    """What the check above holds the served path to: bfloat16 operands,
    four decimals and an unsorted reply each fail it."""
    base, queries, table = data
    picked = F.tables([(base, 0, None)], queries, K, lower=True)["ids"][:, :K]
    true = np.stack([F.sq_l2(queries, base[picked[:, j]]) for j in range(K)], axis=1)
    order = np.argsort(true, axis=1, kind="stable")
    lower = F.reply_failures(table, np.take_along_axis(picked, order, axis=1),
                             np.take_along_axis(true, order, axis=1), DIM)
    assert lower.any()
    right_ids = table["ids"][:, :K]
    right = table["dist"][:, :K].astype(np.float32).astype(np.float64)
    assert not F.reply_failures(table, right_ids, right, DIM).any()
    assert F.reply_failures(table, right_ids, np.round(right, 4), DIM).mean() > 0.5
    assert F.reply_failures(table, right_ids[:, ::-1], right[:, ::-1], DIM).all()


def test_a_near_tie_is_answered_in_canonical_order(served):
    """Two rows a few ulps apart at a magnitude the scoring path's
    |q|^2 - 2 q.x + |x|^2 cannot resolve: it finds them tied (lower rowid
    first), the canonical scores tell them apart, the reply follows those."""
    st, c = served
    centre = np.full(DIM, 32.0, np.float32)
    step = np.float32(2.0 ** -18)  # 32's ulp: the deltas are exact
    far, near, other = centre.copy(), centre.copy(), centre.copy()
    far[1] += 27 * step
    near[0] += 26 * step
    other[2] += 1.0
    _create(c, "tie", "tie:")
    for i, v in enumerate((far, near, other)):
        assert c.execute("HSET", f"tie:{i}", "vector", v.tobytes()) == 1
    before = C.knn_hits_counted()
    for frame in (1, 2):
        for reply in c.execute_many([_cmd(centre, "tie", 3)] * frame):
            docs, texts = _hits(reply)
            assert docs == ["tie:1", "tie:0", "tie:2"]
            assert [np.float32(float(t)) for t in texts] == [
                np.float32(676 * 2.0 ** -36), np.float32(729 * 2.0 ** -36), np.float32(1.0)]
    hits, moved = (a - b for a, b in zip(C.knn_hits_counted(), before))
    assert (hits, moved) == (9, 6)  # three searches, each moved its first two hits
    rows = dict(line.rsplit(" ", 1) for line in
                bytes(c.execute("METRICS")).decode().splitlines() if line)
    assert float(rows["rtpu_knn_hits_total"]) >= hits
    assert float(rows["rtpu_knn_hits_reordered_total"]) >= moved


@pytest.mark.parametrize("proto", [2, 3], ids=["hello2", "hello3"])
def test_a_wave_answers_a_lone_searchs_bytes(served, data, proto):
    st, _c = served
    cmds = [_cmd(q) for q in data[1][:12]]
    with socket.create_connection((st.server.host, st.server.port), timeout=120.0) as sock:
        _raw(sock, [("HELLO", str(proto))])
        alone = [_raw(sock, [cmd]) for cmd in cmds]
        together = _raw(sock, cmds)
    assert together == b"".join(alone)


def test_armed_and_disarmed_replies_are_the_same_bytes(served, data):
    st, _c = served
    cmds = [_cmd(q) for q in data[1][:6]]
    with socket.create_connection((st.server.host, st.server.port), timeout=120.0) as sock:
        armed = _raw(sock, cmds)
        prev = V.set_vector(False)
        try:
            disarmed = _raw(sock, cmds)
        finally:
            V.set_vector(prev)
    assert armed == disarmed

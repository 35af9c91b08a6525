"""Native wire plane property matrix (ISSUE 5): the C++ encoder/parser/LZ4
paths must be byte-for-byte (encoder), value-for-value (parser), and
round-trip (LZ4) interchangeable with the pure-Python fallbacks — across
the full RESP2/RESP3 surface, under ragged chunking, and with the
toolchain missing (`RTPU_NO_NATIVE=1`).
"""
import hashlib
import os
import random
import subprocess
import sys
import time

import pytest

from redisson_tpu.net import _native, resp
from redisson_tpu.net.resp import Push, RespError, RespParser
from redisson_tpu.utils import lz4block

HAS_NATIVE = _native.load() is not None

pytestmark = []


# -- encoder byte identity ----------------------------------------------------

ENCODE_MATRIX = [
    None, True, False, 0, 1, -1, 42, -(2**63), 2**63 - 1, 2**70, -(2**70),
    3.5, -0.0, 7.0, float("inf"), float("-inf"), 1e-9,
    b"", b"raw", b"embedded\r\nCRLF", b"x" * 5000, bytearray(b"ba"),
    memoryview(b"mv"), "text", "unicode-é中",
    RespError("ERR something bad"), RespError("MOVED 12 h:1"), RespError(),
    Push([b"message", b"chan", b"payload"]), Push([]),
    [], [1, 2, 3], [b"a"] * 64, list(range(100)), [[b"n", [1, [2.5, None]]]],
    [1, True, 3], [b"mixed", 1, None, True, 2.5, "s"],
    (1, 2), {}, {b"k": 1, b"j": [1, 2]}, {1: {2: {3: b"deep"}}},
    set(), {1, 2, 3}, frozenset([b"a", b"b"]), {b"x", 1},
    [b"bulk-run-%d" % i for i in range(32)] + [b""],
    [None] * 16, [2**70] * 10, [1.25] * 12,
]


def _rand_value(rng: random.Random, depth: int = 0):
    kinds = ["int", "bigint", "bytes", "str", "float", "none", "bool", "err"]
    if depth < 3:
        kinds += ["list", "intlist", "bulklist", "dict", "set", "push"] * 2
    k = rng.choice(kinds)
    if k == "int":
        return rng.randrange(-2**63, 2**63)
    if k == "bigint":
        return rng.randrange(2**63, 2**80) * rng.choice((1, -1))
    if k == "bytes":
        return bytes(rng.getrandbits(8) for _ in range(rng.randrange(0, 40)))
    if k == "str":
        return "".join(chr(rng.randrange(32, 500)) for _ in range(rng.randrange(0, 12)))
    if k == "float":
        return rng.choice([rng.uniform(-1e6, 1e6), float(rng.randrange(-50, 50))])
    if k == "none":
        return None
    if k == "bool":
        return rng.random() < 0.5
    if k == "err":
        return RespError(f"ERR code {rng.randrange(100)}")
    if k == "list":
        return [_rand_value(rng, depth + 1) for _ in range(rng.randrange(0, 12))]
    if k == "intlist":
        return [rng.randrange(-2**63, 2**63) for _ in range(rng.randrange(8, 40))]
    if k == "bulklist":
        return [b"m%d" % i for i in range(rng.randrange(8, 40))]
    if k == "dict":
        return {
            bytes(rng.getrandbits(8) for _ in range(4)): _rand_value(rng, depth + 1)
            for _ in range(rng.randrange(0, 6))
        }
    if k == "set":
        return {rng.randrange(1000) for _ in range(rng.randrange(0, 8))}
    return Push([_rand_value(rng, depth + 1) for _ in range(rng.randrange(0, 5))])


@pytest.mark.skipif(not HAS_NATIVE, reason="native lib unavailable")
@pytest.mark.parametrize("proto", [2, 3])
def test_encoder_byte_identity_matrix(proto):
    for v in ENCODE_MATRIX:
        assert resp.encode_reply(v, proto) == resp.encode_reply_python(v, proto), v


@pytest.mark.skipif(not HAS_NATIVE, reason="native lib unavailable")
def test_encoder_byte_identity_random_property():
    rng = random.Random(1234)
    for _ in range(300):
        v = _rand_value(rng)
        for proto in (2, 3):
            a = resp.encode_reply(v, proto)
            b = resp.encode_reply_python(v, proto)
            assert a == b, (proto, v)


@pytest.mark.skipif(not HAS_NATIVE, reason="native lib unavailable")
def test_encode_replies_frame_identity():
    rng = random.Random(77)
    for _ in range(50):
        frame = [_rand_value(rng) for _ in range(rng.randrange(1, 30))]
        for proto in (2, 3):
            assert resp.encode_replies(frame, proto) == b"".join(
                resp.encode_reply_python(v, proto) for v in frame
            )
    # homogeneous frames take the header-less run path
    for frame in ([b"OK"] * 64, list(range(64)), [b"v"] * 8):
        assert resp.encode_replies(frame, 3) == b"".join(
            resp.encode_reply_python(v, 3) for v in frame
        )


@pytest.mark.skipif(not HAS_NATIVE, reason="native lib unavailable")
def test_encode_command_identity():
    cases = [
        ("PING",),
        ("SET", b"k", 5),
        ("X", 3.5, True, 2**80, -(2**63), bytearray(b"zz"), memoryview(b"mm")),
        ("HSET", "h", *sum([[f"f{i}", b"v%d" % i] for i in range(40)], [])),
    ]
    for args in cases:
        assert resp.encode_command(*args) == resp.encode_command_python(*args)
    cmds = [("GET", b"key:%d" % i) for i in range(50)] + [("PING",)]
    assert resp.encode_commands(cmds) == b"".join(
        resp.encode_command_python(*c) for c in cmds
    )
    with pytest.raises(TypeError):
        resp.encode_command("SET", object())


def test_encoder_fallback_path(monkeypatch):
    """With the native handle gone (toolchain-missing simulation), every
    encode entry point still produces the same bytes via pure Python."""
    monkeypatch.setattr(resp, "_enc_lib", None)
    for v in ENCODE_MATRIX:
        for proto in (2, 3):
            assert resp.encode_reply(v, proto) == resp.encode_reply_python(v, proto)
    assert resp.encode_commands([("SET", "a", 1)]) == resp.encode_command_python(
        "SET", "a", 1
    )
    assert resp.encode_replies([b"x", 1], 3) == resp.encode_reply_python(
        b"x", 3
    ) + resp.encode_reply_python(1, 3)


# -- parser value identity ----------------------------------------------------

def _wire_frames(rng: random.Random) -> bytes:
    """Random well-formed RESP frames over the FULL marker set, including
    the decode-only surface (verbatim `=`, big number `(`, attribute `|`)."""

    def frame(depth=0):
        kinds = ["simple", "error", "int", "bignum", "bulk", "verbatim",
                 "null", "nullbulk", "bool", "double"]
        if depth < 3:
            kinds += ["array", "set", "map", "push", "attr", "nullarray"]
        k = rng.choice(kinds)
        if k == "simple":
            return b"+OK%d\r\n" % rng.randrange(100)
        if k == "error":
            return b"-ERR boom %d\r\n" % rng.randrange(100)
        if k == "int":
            return b":%d\r\n" % rng.randrange(-2**63, 2**63)
        if k == "bignum":
            return b"(%d\r\n" % (rng.randrange(2**63, 2**90) * rng.choice((1, -1)))
        if k == "bulk":
            p = bytes(rng.getrandbits(8) for _ in range(rng.randrange(0, 30)))
            return b"$%d\r\n" % len(p) + p + b"\r\n"
        if k == "verbatim":
            p = b"txt:" + bytes(rng.randrange(32, 127) for _ in range(8))
            return b"=%d\r\n" % len(p) + p + b"\r\n"
        if k == "null":
            return b"_\r\n"
        if k == "nullbulk":
            return b"$-1\r\n"
        if k == "nullarray":
            return b"*-1\r\n"
        if k == "bool":
            return rng.choice((b"#t\r\n", b"#f\r\n"))
        if k == "double":
            return rng.choice(
                (b",3.5\r\n", b",inf\r\n", b",-inf\r\n", b",%.6f\r\n" % rng.uniform(-9, 9))
            )
        n = rng.randrange(0, 5)
        if k == "array":
            return b"*%d\r\n" % n + b"".join(frame(depth + 1) for _ in range(n))
        if k == "set":
            return b"~%d\r\n" % n + b"".join(b":%d\r\n" % rng.randrange(99) for _ in range(n))
        if k == "map":
            return b"%%%d\r\n" % n + b"".join(
                frame(depth + 3) + frame(depth + 1) for _ in range(n)
            )
        if k == "push":
            return b">%d\r\n" % n + b"".join(frame(depth + 1) for _ in range(n))
        # attribute: n pairs, then the decorated value
        return (
            b"|%d\r\n" % n
            + b"".join(frame(depth + 3) + frame(depth + 3) for _ in range(n))
            + frame(depth + 1)
        )

    return b"".join(frame() for _ in range(rng.randrange(1, 30)))


def _norm(v):
    """Comparable form: RespError compares by identity and may appear as a
    map key, and map/set iteration order is not part of the contract."""
    if isinstance(v, RespError):
        return ("__err__", str(v))
    if isinstance(v, Push):
        return ("__push__", tuple(_norm(x) for x in v))
    if isinstance(v, list):
        return [_norm(x) for x in v]
    if isinstance(v, tuple):
        return tuple(_norm(x) for x in v)
    if isinstance(v, dict):
        items = [(repr(_norm(k)), _norm(val)) for k, val in v.items()]
        return ("__map__", sorted(items, key=lambda kv: kv[0]))
    if isinstance(v, (set, frozenset)):
        return ("__set__", sorted(repr(_norm(x)) for x in v))
    return v


@pytest.mark.skipif(not HAS_NATIVE, reason="native lib unavailable")
def test_parser_value_identity_random_streams():
    rng = random.Random(4321)
    for round_ in range(40):
        blob = _wire_frames(rng)
        pn, pp = RespParser(True), RespParser(False)
        out_n, out_p = [], []
        i = 0
        while i < len(blob):
            j = min(len(blob), i + rng.randrange(1, 97))
            out_n.extend(pn.feed(blob[i:j]))
            out_p.extend(pp.feed(blob[i:j]))
            i = j
        assert [_norm(v) for v in out_n] == [_norm(v) for v in out_p], round_
        assert pn.pending_bytes == pp.pending_bytes == 0


@pytest.mark.parametrize("native", [False] + ([True] if HAS_NATIVE else []))
def test_parser_attribute_and_bignum(native):
    p = RespParser(use_native=native)
    blob = (
        b"|1\r\n+key-popularity\r\n%1\r\n$1\r\na\r\n,0.19\r\n:42\r\n"
        b"(3492890328409238509324850943850943825024385\r\n"
        b"(-3492890328409238509324850943850943825024385\r\n"
        b"|0\r\n$2\r\nhi\r\n"
        b"*2\r\n|1\r\n+a\r\n#t\r\n:5\r\n=11\r\ntxt:hello x\r\n"
    )
    vals = p.feed(blob)
    assert vals[0] == 42  # attribute parsed + discarded
    assert vals[1] == 3492890328409238509324850943850943825024385
    assert vals[2] == -3492890328409238509324850943850943825024385
    assert vals[3] == b"hi"
    assert vals[4] == [5, b"txt:hello x"]
    assert p.pending_bytes == 0


@pytest.mark.parametrize("native", [False] + ([True] if HAS_NATIVE else []))
def test_parser_incomplete_attribute_not_consumed(native):
    p = RespParser(use_native=native)
    assert p.feed(b"|1\r\n+a\r\n:1\r\n") == []  # decorated value still missing
    assert p.feed(b":9\r\n") == [9]
    assert p.pending_bytes == 0


# -- O(n) partial-frame buffering (satellite) ---------------------------------

@pytest.mark.parametrize("native", [False] + ([True] if HAS_NATIVE else []))
def test_feed_large_bulk_in_small_chunks_is_linear(native):
    """A 4MB bulk arriving in 1KB chunks must cost O(n) total copying: the
    window buffer is appended in place (same bytearray object throughout —
    the old code rebuilt a bytes object per feed, O(n^2)) and the wall time
    stays far under what quadratic re-copying costs (>5s)."""
    payload = os.urandom(4 << 20)
    frame = b"$%d\r\n" % len(payload) + payload + b"\r\n"
    p = RespParser(use_native=native)
    buf_id = id(p._buf)
    got = []
    t0 = time.perf_counter()
    for i in range(0, len(frame), 1024):
        got.extend(p.feed(frame[i : i + 1024]))
    elapsed = time.perf_counter() - t0
    assert id(p._buf) == buf_id, "buffer was rebuilt — the O(n^2) pattern"
    assert got == [payload]
    assert p.pending_bytes == 0
    assert elapsed < 5.0, f"chunked feed took {elapsed:.1f}s — quadratic copying?"


@pytest.mark.parametrize("native", [False] + ([True] if HAS_NATIVE else []))
def test_feed_window_compacts_after_consumption(native):
    """The consumed prefix must not grow without bound: after draining many
    pipelined replies the window resets instead of retaining every byte
    ever received."""
    p = RespParser(use_native=native)
    frame = b"+OK\r\n" * 1000
    for _ in range(30):
        vals = p.feed(frame)
        assert len(vals) == 1000
        assert p.pending_bytes == 0
        assert p._pos == 0  # fully-consumed feeds compact immediately
    assert len(p._buf) <= len(frame)


# -- lz4: native <-> python cross round-trips ---------------------------------

LZ4_DATA = [
    b"",
    b"a",
    b"short",
    b"aaaaaaaaaaaa",
    b"a" * 1000,
    b"abcd" * 500,
    b"the quick brown fox " * 100,
    bytes(range(256)) * 64,
    b"x" * 14 + b"y",
    os.urandom(300) + b"q" * 100_000 + os.urandom(300),
    os.urandom(70_000),
]


@pytest.mark.skipif(not HAS_NATIVE, reason="native lib unavailable")
@pytest.mark.parametrize("i", range(len(LZ4_DATA)))
def test_lz4_native_python_cross_roundtrip(i):
    data = LZ4_DATA[i]
    native_stream = lz4block.compress(data)
    python_stream = lz4block.compress_python(data)
    # native stream decodes on BOTH decoders; python stream likewise
    assert lz4block.decompress(native_stream, len(data)) == data
    assert lz4block.decompress_python(native_stream, len(data)) == data
    assert lz4block.decompress(python_stream, len(data)) == data
    assert lz4block.decompress_python(python_stream, len(data)) == data


@pytest.mark.skipif(not HAS_NATIVE, reason="native lib unavailable")
def test_lz4_native_rejects_malformed():
    data = b"hello world " * 50
    packed = lz4block.compress(data)
    with pytest.raises(ValueError):
        lz4block.decompress(packed[:-3], len(data))
    with pytest.raises(ValueError):
        lz4block.decompress(packed, len(data) + 1)
    with pytest.raises(ValueError):
        lz4block.decompress(b"\x01\x41\x09\x00\xff\xff", 100)
    with pytest.raises(ValueError):
        lz4block.decompress(packed, -1)


def test_replication_wire_payload_roundtrip():
    """The LZ4-framed replication blob decodes transparently, and legacy
    bare-pickle blobs still pass through."""
    import pickle

    from redisson_tpu.server import replication as R

    records = [{"name": "r%d" % i, "data": b"z" * 500} for i in range(4)]
    blob = R._wire_payload(records, ["r0", "r1"])
    assert blob[:4] == R._WIRE_LZ4_MAGIC  # compressible payload got framed
    raw = R._unwire_payload(blob)
    doc = pickle.loads(raw)
    assert doc["records"] == records and doc["live"] == ["r0", "r1"]
    bare = pickle.dumps({"format": 1, "records": []}, protocol=4)
    assert R._unwire_payload(bare) is bare  # legacy pass-through


# -- calc_slots scratch reuse (satellite) -------------------------------------

def test_calc_slots_scratch_reuse_and_single_key():
    from redisson_tpu.utils.crc16 import calc_slot

    keysets = [
        [b"one-key"],
        [b"foo", b"bar{tag}baz", b"{user1000}.following", b"", b"{}", b"{x}"],
        [b"k%d" % i for i in range(300)],  # grows the scratch
        [b"single{h}"],
        [b"k%d" % i for i in range(40)],   # shrinking n reuses the big scratch
    ]
    for keys in keysets:
        assert resp.calc_slots(keys) == [calc_slot(k) for k in keys]
    assert resp.calc_slots([]) == []


# -- server reply digest: native vs RTPU_NO_NATIVE=1 --------------------------

_DIGEST_DRIVER = r"""
import hashlib, socket, sys
from redisson_tpu.net import resp
from redisson_tpu.server.server import ServerThread

CMDS = [
    ("HELLO", "3"),
    ("SET", "k1", "v1"), ("GET", "k1"), ("GET", "missing"),
    ("RPUSH", "l1", *[f"e{i}" for i in range(40)]),
    ("LRANGE", "l1", "0", "-1"),
    ("INCR", "ctr"), ("INCRBY", "ctr", "41"),
    ("ZADD", "z1", "1.5", "a", "2", "b"), ("ZSCORE", "z1", "a"),
    ("SADD", "s1", "x", "y", "z"), ("SMEMBERS", "s1"),
    ("HSET", "h1", "f1", "v1", "f2", "v2"), ("HGETALL", "h1"),
    ("TOTALLY-BOGUS-CMD",), ("TYPE", "k1"), ("EXISTS", "k1", "missing"),
]
with ServerThread(port=0) as st:
    s = socket.create_connection((st.server.host, st.server.port), timeout=30)
    parser = resp.RespParser(use_native=False)
    h = hashlib.sha256()
    n_replies = 0
    # wave 1: pre-HELLO (RESP2 projection), wave 2: post-HELLO 3
    for wave in (CMDS[1:], CMDS):
        s.sendall(b"".join(resp.encode_command_python(*c) for c in wave))
        want = len(wave)
        got = 0
        while got < want:
            data = s.recv(1 << 16)
            assert data, "server closed early"
            h.update(data)
            got += len(parser.feed(data))
    s.close()
print(h.hexdigest())
"""


# -- toolchain hygiene: the checked-in .so must match resp.cpp ----------------

@pytest.mark.skipif(not HAS_NATIVE, reason="native lib unavailable")
def test_makefile_rebuild_matches_checked_in_library(tmp_path):
    """Exercises `make -C native BUILD=<tmp>` and proves the library the
    loader built (content-keyed on resp.cpp) matches it: the fresh build
    exports the full entry-point set and behaves identically on scan,
    encode, lz4, and crc16 samples."""
    import ctypes
    import shutil

    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("build toolchain unavailable")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    native_dir = os.path.join(repo, "native")
    so = _native.so_path()
    assert so is not None and os.path.exists(so)  # HAS_NATIVE built it
    build = str(tmp_path / "build")
    subprocess.run(
        ["make", "-C", native_dir, f"BUILD={build}"],
        check=True, capture_output=True, timeout=240,
    )
    # _bind raises AttributeError when an entry point is missing — a stale
    # artifact cannot pass silently
    fresh = _native._bind(ctypes.CDLL(os.path.join(build, "librtpu.so")))
    checked = _native._bind(ctypes.CDLL(so))

    # scan parity
    blob = (
        b"*3\r\n$2\r\nhi\r\n:42\r\n%1\r\n+k\r\n#t\r\n"
        b"(99999999999999999999\r\n|1\r\n+a\r\n:1\r\n$2\r\nok\r\n"
    )
    for lib_a, lib_b in ((fresh, checked),):
        sa = resp._scan_native(lib_a, resp._TokenBuf(), blob)
        sb = resp._scan_native(lib_b, resp._TokenBuf(), blob)
        assert sa == sb

    # encode parity (both libs, against the pure reference)
    value = [b"x", 1, {b"k": [2.5, None, True]}, [b"r%d" % i for i in range(16)]]
    sc = resp._EncScratch()
    for lib in (fresh, checked):
        del sc.ops[:], sc.vals[:], sc.offs[:]
        del sc.pool[:]
        resp._flatten(value, 3, sc.ops, sc.vals, sc.offs, sc.pool)
        assert resp._emit_flat(lib, sc) == resp.encode_reply_python(value, 3)

    # lz4 parity: each lib's stream decodes on the other and on pure python
    data = (b"hygiene " * 400) + os.urandom(64)

    def compress_with(lib):
        cap = len(data) + len(data) // 255 + 16
        out = ctypes.create_string_buffer(cap)
        w = lib.rtpu_lz4_compress(data, len(data), out, cap)
        assert w > 0
        return ctypes.string_at(out, w)

    for stream in (compress_with(fresh), compress_with(checked)):
        assert lz4block.decompress_python(stream, len(data)) == data
        for lib in (fresh, checked):
            out = ctypes.create_string_buffer(len(data))
            produced = __import__("ctypes").c_uint64(0)
            rc = lib.rtpu_lz4_decompress(
                stream, len(stream), out, len(data), ctypes.byref(produced)
            )
            assert rc == 0 and ctypes.string_at(out, len(data)) == data

    # crc16 parity
    for key in (b"foo", b"bar{tag}baz", b""):
        assert fresh.rtpu_crc16(key, len(key)) == checked.rtpu_crc16(key, len(key))


@pytest.mark.skipif(not HAS_NATIVE, reason="native lib unavailable")
def test_server_reply_digest_identical_without_native():
    """ISSUE 5 acceptance: a tpu-server drives byte-identical reply streams
    with the native wire plane and with RTPU_NO_NATIVE=1 (pure Python)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digests = {}
    for label, extra_env in (("native", {}), ("fallback", {"RTPU_NO_NATIVE": "1"})):
        env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
        out = subprocess.run(
            [sys.executable, "-c", _DIGEST_DRIVER],
            capture_output=True, text=True, timeout=240, cwd=repo, env=env,
        )
        assert out.returncode == 0, (label, out.stdout, out.stderr)
        digests[label] = out.stdout.strip().splitlines()[-1]
    assert digests["native"] == digests["fallback"], digests
    assert len(digests["native"]) == 64  # a real sha256 came back

"""Device word-count pipeline (kernels.wc_extract_words / wc_count_runs):
correctness vs the host Counter reference across edge shapes."""
import numpy as np
import pytest

from redisson_tpu.services.mapreduce import (
    _host_word_count,
    device_word_count,
    word_count,
)


def _ref(vals):
    from collections import Counter

    c = Counter()
    for v in vals:
        c.update(v.split())
    return dict(c)


@pytest.mark.parametrize(
    "vals",
    [
        ["foo bar foo", "baz foo bar"],
        ["single"],
        ["  leading and  double   spaces ", "trailing spaces  "],
        ["tabs\tand\nnewlines\r\nmixed", "v\x0bv\x0cw"],
        ["", "", "only third has words"],
        ["a b c d e f g h i j" * 3],
        ["répé unicode répé", "naïve café"],
    ],
)
def test_device_word_count_matches_host(vals):
    assert device_word_count(vals) == _ref(vals)



def _identity_rows(buf: np.ndarray, eb: int, base: int):
    """NumPy reference of wc_extract_words: each word's (hash_a, hash_b,
    start + base) — the sum of (byte + 1) * p^min(pos, 63) mod 2^32 for
    each prime, mixed with the length —, sentinel rows after the words, and
    the word count."""
    from redisson_tpu.core import kernels as K

    mask = (1 << 32) - 1
    rows = []
    at = 0
    for word in bytes(buf).split(b" "):
        if word:
            pos = np.minimum(np.arange(len(word)), K._WC_POW - 1)
            terms = np.frombuffer(word, np.uint8).astype(np.uint64) + 1
            ha = int((terms * K._WC_POW_A[pos].astype(np.uint64)).sum()) & mask
            hb = int((terms * K._WC_POW_B[pos].astype(np.uint64)).sum()) & mask
            n = len(word)
            rows.append([ha ^ ((n * 2654435761) & mask), (hb + n * 0x9E3779B9) & mask,
                         (at + base) & mask])
        at += len(word) + 1
    want = np.full((3, eb), K._WC_SENTINEL, np.uint32)
    kept = np.array(rows[:eb], np.uint32).reshape(-1, 3)
    want[:, : len(kept)] = kept.T
    return want, len(rows)


def _text(*parts, size=1024):
    """`parts` (bytes) laid into a space-filled buffer of `size` bytes,
    each at the offset given before it."""
    buf = np.full(size, 32, np.uint8)
    for at, word in parts:
        buf[at: at + len(word)] = np.frombuffer(word, np.uint8)
    return buf


def _word(n, seed=0):
    g = np.random.default_rng(seed + n)
    return bytes(g.choice(np.r_[33:256], n).astype(np.uint8).tolist())


@pytest.mark.parametrize(
    "buf,eb,base",
    [
        (_text(*[(at, _word(n)) for at, n in zip(
            [0, 2, 6, 70, 134, 200, 300], [1, 2, 62, 63, 64, 65, 200])]), 16, 0),
        (_text((5, b"ab"), (12, b"c"), (13 + 40, b"de fg"), (200, b"h"), size=333), 8, 0),
        (_text((0, b"x" * 7), (1024 - 8, b"end word"), size=1024), 8, 0),
        (_text((3, _word(64)), (100, b"base"), size=256), 4, 1 << 20),
        (_text(*[(3 * i, b"ab") for i in range(40)], size=128), 16, 7),
        (np.full(64, 32, np.uint8), 4, 0),
    ],
    ids=["lengths-1-to-200", "runs-of-spaces", "word-on-last-byte", "base",
         "count-above-eb", "no-words"],
)
def test_extract_words_keeps_the_word_identity(buf, eb, base):
    """wc_extract_words' rows are the identity bit for bit: the words'
    hashes across the 63-byte cap, their starts with the task's base, the
    sentinel rows and the count (which may pass eb: the rows are then the
    first eb words)."""
    from redisson_tpu.core import kernels as K

    rows, count = K.wc_extract_words(K.stage(buf), eb, K.valid_n(base))
    want, n = _identity_rows(buf, eb, base)
    assert int(count) == n
    np.testing.assert_array_equal(np.asarray(rows), want)

def test_device_word_count_long_words_and_chunking():
    long_word = "x" * 200
    vals = [f"{long_word} short {long_word}", "short " + "y" * 80]
    out = device_word_count(vals, n_chunks=2)
    assert out[long_word] == 2
    assert out["short"] == 2
    assert out["y" * 80] == 1


def test_device_word_count_d_max_fallback():
    # 3000 distinct words, where a device cap of 2^8 once sent the job to the
    # host: the device answers every distinct word, with no host fallback
    from redisson_tpu.services import mapreduce as MR

    vals = [" ".join(f"w{i}" for i in range(j, j + 50)) for j in range(0, 3000, 50)]
    before = MR.mr_counted()
    out = device_word_count(vals)
    after = MR.mr_counted()
    assert out == _ref(vals)
    assert after["jobs_device"] - before["jobs_device"] == 1
    assert after["distinct"] - before["distinct"] == 3000


def test_device_word_count_large_random_corpus():
    rng = np.random.default_rng(9)
    vocab = [f"word{i}" for i in range(500)]
    vals = [
        " ".join(vocab[j] for j in rng.integers(0, 500, 12)) for i in range(5000)
    ]
    assert device_word_count(vals) == _ref(vals)


def test_word_count_facade_local_paths():
    import redisson_tpu
    from redisson_tpu.client.codec import StringCodec

    client = redisson_tpu.create()
    try:
        m = client.get_map("wc:facade", codec=StringCodec())
        m.put_all({f"d{i}": "alpha beta alpha" for i in range(100)})
        counts = word_count(m)
        assert counts == {"alpha": 200, "beta": 100}
        assert _host_word_count(["alpha beta alpha"] * 100) == {"alpha": 200, "beta": 100}
    finally:
        client.shutdown()


def test_device_word_count_unicode_whitespace_falls_back_consistently():
    """NBSP and ideographic space are str.split() separators; the byte
    kernel must not silently diverge — it falls back to the host path."""
    vals = ["a b c", "x　y"]
    assert device_word_count(vals) == _ref(vals)


def test_device_word_count_ascii_control_whitespace():
    """\\x1c-\\x1f are str.split() separators (str.isspace() is true for
    them); the byte kernel must treat them identically (reviewer repro)."""
    vals = ["alpha\x1cbeta", "alpha beta", "g\x1dh\x1ei\x1fj"]
    assert device_word_count(vals) == _ref(vals)


def test_word_count_scan_view_cache_invalidation():
    """Repeated word_count over an UNCHANGED map serves from the staged
    device view; ANY mutation (put / remove / delete+recreate) must
    invalidate it — stale counts would be a correctness bug, not a perf
    detail."""
    import redisson_tpu
    from redisson_tpu.client.codec import StringCodec
    from redisson_tpu.services.mapreduce import _WcViewCache

    client = redisson_tpu.create()
    try:
        m = client.get_map("wc:view", codec=StringCodec())
        m.put_all({f"d{i}": "alpha beta alpha" for i in range(50)})
        assert word_count(m) == {"alpha": 100, "beta": 50}
        cache = client._engine.service("wc_scan_views", _WcViewCache)
        assert cache._views.get("wc:view") is not None  # view was staged
        # second scan hits the view (key unchanged)
        rec = client._engine.store.get("wc:view")
        assert cache.get("wc:view", (rec.nonce, rec.version)) is not None
        assert word_count(m) == {"alpha": 100, "beta": 50}
        # mutation bumps version -> view miss -> fresh counts
        m.put("extra", "gamma gamma")
        assert word_count(m) == {"alpha": 100, "beta": 50, "gamma": 2}
        m.remove("extra")
        assert word_count(m) == {"alpha": 100, "beta": 50}
        # delete + recreate restarts versions but changes the nonce
        m.delete()
        m.put_all({"x": "delta"})
        assert word_count(m) == {"delta": 1}
    finally:
        client.shutdown()


def test_word_count_map_cache_ttl_not_stale():
    """MapCache TTL expiry removes entries without a version bump, so the
    scan-view fast path must not apply — counts must reflect expiry."""
    import time

    import redisson_tpu
    from redisson_tpu.client.codec import StringCodec

    client = redisson_tpu.create()
    try:
        mc = client.get_map_cache("wc:ttl", codec=StringCodec())
        mc.put("keep", "alpha")
        mc.put_with_ttl("gone", "beta", ttl=0.2)
        assert word_count(mc) == {"alpha": 1, "beta": 1}
        time.sleep(0.3)
        assert word_count(mc) == {"alpha": 1}  # stale view would keep beta
    finally:
        client.shutdown()


def test_word_count_loader_backed_map_not_stale():
    """Read-through loads insert values WITHOUT a version bump, so
    loader-configured maps must bypass the scan-view fast path."""
    import redisson_tpu
    from redisson_tpu.client.codec import StringCodec
    from redisson_tpu.client.objects.map import MapLoader, MapOptions

    class L(MapLoader):
        def load(self, key):
            return "gamma gamma"

        def load_all_keys(self):
            return []

    client = redisson_tpu.create()
    try:
        m = client.get_map("wc:loader", codec=StringCodec(), options=MapOptions(loader=L()))
        m.put("a", "alpha beta")
        assert word_count(m) == {"alpha": 1, "beta": 1}
        m.get("newkey")  # read-through load, no version bump
        assert word_count(m) == {"alpha": 1, "beta": 1, "gamma": 2}
    finally:
        client.shutdown()

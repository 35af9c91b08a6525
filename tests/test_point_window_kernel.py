"""A window of point commands — single-item ``BF.ADD`` / ``BF.EXISTS`` of one
record — is ONE upload, ONE program (``kernels.bloom_window_bytes_masked``)
and ONE fetch (``server/verbs/sketch.py point_window``).

Pinned here, against the plain reference (``benchmark/reference_bf.py``):
  * the answers are those of one one-at-a-time execution, the probes then the
    adds as they arrived, over seeded windows of 1-256 members: random mixes,
    adds that share cells with earlier adds of the window, a probe of an item
    the same window adds (it answers the plane before the window), an item
    added twice; the plane afterwards is the reference's;
  * a window is one upload, one dispatch and one entry of the record's lock,
    counted once as a window, whatever its mix;
  * windows of every size and mix run one program a width rung of the items
    (256 rows, W words), compiled with the record's first window of that
    width and never again.
"""
import types

import jax.numpy as jnp
import numpy as np
import pytest

import redisson_tpu
from benchmark import reference_bf as R
from redisson_tpu.client.objects.bloom import BloomFilter
from redisson_tpu.core import kernels as K
from redisson_tpu.core.engine import Engine
from redisson_tpu.server.verbs.sketch import point_window


@pytest.fixture(scope="module")
def engine():
    eng = Engine()
    yield eng
    eng.shutdown()


def _filter(engine, name: str, capacity: int):
    bf = BloomFilter(engine, name)
    assert bf.try_init(capacity, 0.01)
    return types.SimpleNamespace(engine=engine), R.RefFilter(bf.get_size(), bf.get_hash_iterations())


def _serial(ref, verbs, items) -> list:
    """The reference's answers: every probe against the plane before the
    window, then every add, one at a time, in the order given."""
    out = [0] * len(items)
    probes = [i for i, v in enumerate(verbs) if v == "BF.EXISTS"]
    if probes:
        for i, found in zip(probes, ref.contains(*R.pack([items[i] for i in probes])).tolist()):
            out[i] = int(found)
    for i, v in enumerate(verbs):
        if v == "BF.ADD":
            rows, nbytes = R.pack([items[i]])
            out[i] = int(ref.add(rows[0], int(nbytes[0])))
    return out


def _plane(engine, name: str, m: int) -> np.ndarray:
    return np.asarray(engine.store.get(name).arrays["bits"])[:m].astype(bool)


@pytest.mark.parametrize("seed", range(6))
def test_a_window_is_the_references_serial_order(engine, seed):
    """A small filter that fills up over the run, items drawn from a pool a
    little larger than a window: adds meet earlier adds' cells, items are
    added twice and probed in the window that adds them."""
    name = f"ref:{seed}"
    server, ref = _filter(engine, name, 300)
    rng = np.random.default_rng(3900 + seed)
    sizes = [1, 256, 2] + rng.integers(1, 257, 17).tolist()
    for at, n in enumerate(sizes):
        share = rng.choice([0.0, 0.1, 0.5, 0.9, 1.0])
        verbs = ["BF.ADD" if x < share else "BF.EXISTS" for x in rng.random(n)]
        items = [b"memtier-%d" % x for x in rng.integers(0, 400, n)]
        assert point_window(server, name, verbs, items) == _serial(ref, verbs, items), (seed, at)
    assert (_plane(engine, name, ref.m) == ref.cells).all()


def _meeting_pair(ref):
    """Two different items with a cell in common, and the cells only one of
    them has."""
    items = [b"pair-%d" % n for n in range(4000)]
    cells = ref.indexes(*R.pack(items))
    owner = {}
    for i, row in enumerate(cells.tolist()):
        for cell in row:
            j = owner.setdefault(cell, i)
            if j != i:
                return items[j], items[i], sorted(set(cells[j].tolist()) ^ set(row))
    raise AssertionError("no two items share a cell")


@pytest.mark.parametrize("first", [0, 1])
def test_of_two_adds_that_meet_exactly_the_first_is_new(engine, first):
    """Two items whose cells left clear are the same ones: in one window the
    first added is new, the second finds every cell set — whichever is
    first, and whatever probes stand between them."""
    name = f"meet:{first}"
    server, ref = _filter(engine, name, 2000)
    a, b, only_one = _meeting_pair(ref)
    with engine.locked(name):
        rec = engine.store.get(name)
        rec.arrays["bits"] = rec.arrays["bits"].at[jnp.asarray(only_one)].set(1)
    ref.cells[only_one] = True
    pair = (a, b) if first == 0 else (b, a)
    verbs = ["BF.ADD", "BF.EXISTS", "BF.EXISTS", "BF.ADD"]
    items = [pair[0], pair[1], b"stranger", pair[1]]
    assert point_window(server, name, verbs, items) == [1, 0, 0, 0] == _serial(ref, verbs, items)


def test_a_probe_sees_the_plane_before_its_window_and_a_repeat_is_not_new(engine):
    name = "order"
    server, ref = _filter(engine, name, 2000)
    verbs = ["BF.ADD", "BF.EXISTS", "BF.ADD", "BF.ADD", "BF.EXISTS"]
    items = [b"x", b"x", b"x", b"y", b"y"]
    assert point_window(server, name, verbs, items) == [1, 0, 0, 1, 0] == _serial(ref, verbs, items)
    assert point_window(server, name, ["BF.EXISTS"] * 3, [b"x", b"y", b"z"]) == [1, 1, 0]


@pytest.mark.parametrize("mix", ["probes", "adds", "mixed", "one"])
def test_a_window_is_one_upload_one_dispatch_one_lock(engine, monkeypatch, mix):
    name = f"once:{mix}"
    server, ref = _filter(engine, name, 2000)
    verbs = {"probes": ["BF.EXISTS"] * 29, "adds": ["BF.ADD"] * 29,
             "mixed": ["BF.EXISTS"] * 26 + ["BF.ADD"] * 3, "one": ["BF.ADD"]}[mix]
    items = [b"memtier-%d" % i for i in range(len(verbs))]
    assert point_window(server, name, verbs[:1], items[:1]) == _serial(ref, verbs[:1], items[:1])
    staged, locked = [], []
    stage, lock = K.stage, engine.locked
    monkeypatch.setattr(K, "stage", lambda a: staged.append(a.shape) or stage(a))
    monkeypatch.setattr(engine, "locked", lambda n: locked.append(n) or lock(n))
    before = K.point_counted()
    assert point_window(server, name, verbs, items) == _serial(ref, verbs, items)
    after = K.point_counted()
    assert staged == [(4 + 2, K.MIN_BUCKET)] and locked == [name]
    assert (after["windows"] - before["windows"], after["dispatches"] - before["dispatches"]) == (1, 1)
    assert after["rows_valid"] - before["rows_valid"] == len(verbs)
    assert after["rows_issued"] - before["rows_issued"] == K.MIN_BUCKET


def test_one_program_a_width_and_none_after_the_first_window(engine):
    name = "compiles"
    server, ref = _filter(engine, name, 3331)  # a geometry no other test has
    rng = np.random.default_rng(39)

    def window(n, width):
        verbs = ["BF.ADD" if x < 0.3 else "BF.EXISTS" for x in rng.random(n)]
        items = [b"%0*d" % (width, x) for x in rng.integers(0, 10 ** 6, n)]
        assert point_window(server, name, verbs, items) == _serial(ref, verbs, items)

    built = K.bloom_window_bytes_masked._cache_size()
    window(3, 15)  # the record's first window: items of up to 16 bytes, W = 4
    assert K.bloom_window_bytes_masked._cache_size() == built + 1
    programs = redisson_tpu.compile_cache_stats()["programs"]
    for n in (1, 2, 7, 29, 100, 200, 255, 256):
        window(n, 9 + n % 8)
    assert redisson_tpu.compile_cache_stats()["programs"] == programs
    window(5, 20)  # a wider rung: W = 8, one program more
    assert K.bloom_window_bytes_masked._cache_size() == built + 2
    programs = redisson_tpu.compile_cache_stats()["programs"]
    for n in (1, 64, 256):
        window(n, 17 + n % 16)
    assert redisson_tpu.compile_cache_stats()["programs"] == programs


def test_a_padded_row_answers_nothing_and_sets_nothing():
    """Rows at or past n_valid are masked, even where their add row is 1."""
    m, k = 2000, 7
    words = np.zeros((4, K.MIN_BUCKET), np.uint32)
    words[0] = np.arange(K.MIN_BUCKET)
    buf = np.concatenate([words, np.full((2, K.MIN_BUCKET), 1, np.uint32)])
    bits, flags = K.bloom_window_bytes_masked(jnp.zeros((2048,), jnp.uint8), buf,
                                              jnp.int32(3), k, m)
    flags = np.asarray(flags)
    assert flags.dtype == np.uint8 and flags.tolist() == [1, 1, 1] + [0] * (K.MIN_BUCKET - 3)
    ref = R.RefFilter(m, k)
    ref.add_many(words[:, :3].T.copy().view(np.uint8), np.ones(3, np.uint32))
    assert (np.asarray(bits)[:m].astype(bool) == ref.cells).all() and not np.asarray(bits)[m:].any()


def test_the_window_kernel_carries_its_scope():
    bits = jnp.zeros((2048,), jnp.uint8)
    buf = jnp.zeros((6, K.MIN_BUCKET), jnp.uint32)
    text = K.bloom_window_bytes_masked.lower(bits, buf, jnp.int32(1), 7, 2000).as_text(
        debug_info=True)
    assert "jit(bloom_window_bytes_masked)/bloom_window_bytes_masked/" in text

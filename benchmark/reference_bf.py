"""The plain reference of one RedisBloom-style filter over string items: what
a correct server answers to ``BF.ADD`` / ``BF.EXISTS`` / ``BF.MEXISTS``, in
NumPy alone.

A FROZEN COPY of the arithmetic a filter over byte items is defined by
("rtpu-mur32x2/1" over bytes: the item, zero-padded to whole little-endian
32-bit words, through two murmur3-x86-32 chains of one round a word —
``ceil(len / 4)`` rounds, the last word taken whole, no tail step — the
byte length xored in before the finalizer, h2 forced odd; index i =
(h1 + i * h2) mod m, i < k) and of the geometry Redisson derives from a
capacity and an error rate (m = floor(-n ln p / ln^2 2), k = max(1,
round(m / n * ln 2))).  It imports nothing from ``redisson_tpu`` and nothing
from ``reference.py``: the one place the copy meets the program is
``benchmark/tests/test_memtier.py``, so a change to the program's hashing
shows as wrong answers, not as a silently moved yardstick.

Items travel as a zero-padded uint8 matrix (one row an item, a multiple of
four bytes wide) with their byte lengths beside it: ``pack`` makes that from
any list of byte strings, ``numbered`` from a prefix and an array of numbers
(``memtier-<n>``) without a Python loop.  The plane is one boolean cell a bit
of the filter; ``add`` takes one item at a time (its answer is judged against
the plane as it stood before that item), ``add_many`` is set-up's bulk form
of it, ``contains`` probes any number of items.
"""
import math

import numpy as np

SEED1 = 0x9747B28C
SEED2 = 0x3C6EF372
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_FM1 = np.uint32(0x85EBCA6B)
_FM2 = np.uint32(0xC2B2AE35)
_FIVE = np.uint32(5)
_ADD = np.uint32(0xE6546B64)


def optimal_m(capacity: int, error_rate: float) -> int:
    return int(-capacity * math.log(error_rate) / (math.log(2) ** 2))


def optimal_k(capacity: int, m: int) -> int:
    return max(1, round(m / capacity * math.log(2)))


def pack(items) -> tuple:
    """Byte strings -> (rows (N, 4W) uint8 zero-padded, nbytes (N,) uint32)."""
    width = max(4, -(-max(len(b) for b in items) // 4) * 4)
    rows = np.zeros((len(items), width), np.uint8)
    for i, b in enumerate(items):
        rows[i, : len(b)] = np.frombuffer(b, np.uint8)
    return rows, np.array([len(b) for b in items], np.uint32)


def numbered(prefix: bytes, numbers) -> tuple:
    """The items ``prefix + str(n)`` for an array of non-negative numbers,
    packed as ``pack`` packs them."""
    n = np.asarray(numbers, np.int64).ravel()
    most = len(str(int(n.max()))) if len(n) else 1
    digits = np.ones(len(n), np.int64)
    for d in range(1, most):
        digits += n >= 10 ** d
    width = -(-(len(prefix) + most) // 4) * 4
    rows = np.zeros((len(n), width), np.uint8)
    rows[:, : len(prefix)] = np.frombuffer(prefix, np.uint8)
    for at in range(most):  # the digit `at` places from the left
        power = digits - 1 - at
        there = power >= 0
        rows[there, len(prefix) + at] = 48 + (n[there] // 10 ** power[there]) % 10
    return rows, (len(prefix) + digits).astype(np.uint32)


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _chain(words, nwords, nbytes, seed: int):
    h = np.full(nbytes.shape, seed, np.uint32)
    for j in range(words.shape[1]):
        k = _rotl(words[:, j] * _C1, 15) * _C2
        hj = _rotl(h ^ k, 13) * _FIVE + _ADD
        h = np.where(nwords > j, hj, h)
    h = h ^ nbytes
    h = (h ^ (h >> np.uint32(16))) * _FM1
    h = (h ^ (h >> np.uint32(13))) * _FM2
    return h ^ (h >> np.uint32(16))


def hash_pair(rows, nbytes):
    """Packed items -> (h1, h2) uint32; h2 odd."""
    words = np.ascontiguousarray(rows).view("<u4")
    nbytes = np.asarray(nbytes, np.uint32)
    nwords = (nbytes + np.uint32(3)) >> np.uint32(2)
    with np.errstate(over="ignore"):
        return (_chain(words, nwords, nbytes, SEED1),
                _chain(words, nwords, nbytes, SEED2) | np.uint32(1))


def indexes(rows, nbytes, k: int, m: int):
    """(N, k) int64 cell positions of each item in an m-cell filter."""
    h1, h2 = hash_pair(rows, nbytes)
    i = np.arange(k, dtype=np.uint32)
    with np.errstate(over="ignore"):
        return ((h1[:, None] + i * h2[:, None]) % np.uint32(m)).astype(np.int64)


class RefFilter:
    """One filter's plane, one boolean cell a bit.  ``cells`` may be handed
    in (a plane another process built, mapped read-only for ``contains``)."""

    def __init__(self, m: int, k: int, cells=None):
        self.m, self.k = m, k
        self.cells = np.zeros(m, bool) if cells is None else cells

    def indexes(self, rows, nbytes):
        return indexes(rows, nbytes, self.k, self.m)

    def add(self, row, nbytes: int) -> bool:
        """One item; True iff one of its cells was clear (``BF.ADD``'s 1)."""
        at = self.indexes(np.asarray(row)[None, :], np.array([nbytes]))[0]
        newly = not self.cells[at].all()
        self.cells[at] = True
        return bool(newly)

    def add_many(self, rows, nbytes) -> None:
        self.cells[self.indexes(rows, nbytes).ravel()] = True

    def contains(self, rows, nbytes):
        return self.cells[self.indexes(rows, nbytes)].all(axis=1)

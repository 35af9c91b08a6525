"""Seeded data: every key, index, tenant draw and arrival time of a run is a
pure function of ``--seed`` and a position, so the parent, each worker and
the reference can make the same datum without passing it around, and a frame
can be made again after the window from nothing but its number.

Keys live in disjoint regions of the 62-bit key space, so a key of one
region is absent from every set made of another by construction:

  region 0   keys populated in set-up              [0, 2^59)
  region 1   keys added during the run             [2^59, 2^60)
  region 2   keys never added (absent probes)      [2^62, 2^62 + 2^59)
"""
import numpy as np

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
REGION_BITS = 59
POPULATED, ADDED, ABSENT = 0, 1, 8  # region numbers (ABSENT sets bit 62)


def mix64(x):
    """splitmix64's finalizer: a bijection of uint64."""
    x = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * _M1
        x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


def keys(seed: int, region: int, index):
    """int64 key number ``index`` of ``region`` under ``seed``."""
    i = np.asarray(index, np.uint64)
    with np.errstate(over="ignore"):
        h = mix64(i * _GOLDEN + mix64(np.uint64(seed) + np.uint64(region) * _M1))
    return ((h >> np.uint64(64 - REGION_BITS))
            | (np.uint64(region) << np.uint64(REGION_BITS))).astype(np.int64)


def rng(seed: int, *where: int) -> np.random.Generator:
    """The generator of one position (stream, connection, frame, ...)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, *[int(w) & 0xFFFFFFFF
                                                            for w in where]])


class Zipf:
    """Zipf(s) popularity over ``n`` items; rank r is item ``perm[r]`` under
    a permutation drawn from the seed, so the hot items are not the low ids.
    ``among`` restricts the draw to those items (renormalised)."""

    def __init__(self, n: int, s: float, seed: int, among=None):
        perm = rng(seed, 0x21FF).permutation(n)
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
        if among is not None:
            keep = np.isin(perm, np.asarray(among))
            perm, w = perm[keep], w[keep]
        self.items = perm
        self.p = w / w.sum()
        self._cdf = np.cumsum(self.p)
        self._cdf[-1] = 1.0

    def draw(self, g: np.random.Generator, size: int):
        return self.items[np.searchsorted(self._cdf, g.random(size), side="right")]

    def draw_distinct(self, g: np.random.Generator, size: int):
        """``size`` different items, popular ones more often (Efraimidis-
        Spirakis keys: the top of u^(1/p))."""
        k = np.log(g.random(len(self.items))) / self.p
        return self.items[np.argpartition(-k, size - 1)[:size]]


def poisson_arrivals(g: np.random.Generator, rate: float, seconds: float):
    """Arrival offsets in [0, seconds) of a Poisson process of ``rate`` a
    second."""
    n = int(rate * seconds * 1.2 + 64)
    t = np.cumsum(g.exponential(1.0 / rate, n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(g.exponential(1.0 / rate, n))])
    return t[t < seconds]

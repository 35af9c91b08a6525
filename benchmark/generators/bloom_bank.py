"""Traffic against one multi-tenant bloom bank (``BFA.*`` through
``RemoteRedisson.get_bloom_filter_array``): bulk ``contains`` flushes and
small per-user calls with a share of ``add``, from one set of parameters.

Traffic parameters (``benchmark/traffic/*.json``):
  keys_per_request   keys in one call (100000 = the source's flush, 16 = a
                     user's handful)
  tenant_of          "key": every key draws its own tenant (a batch service's
                     flush); "request": one tenant a call (one user's request)
  skew               Zipf exponent of tenant popularity
  add_share          share of calls that are ``add`` of new keys; the call
                     two after an add on its connection probes those keys
  check_share        share of calls whose whole reply is compared with the
                     reference plane (the present-key flags always are)

Even positions of a call carry keys populated in set-up (present), odd
positions keys of a region nothing is ever added from (absent).

The bank only gains bits, so under concurrent adds a probe's right answer
lies between the plane as populated (lower) and that plane with every add
of the run (upper): a sampled reply must satisfy lower <= found <= upper,
exactly equal where the two agree — always, in a mix without adds.
"""
import numpy as np

from benchmark import datagen as D
from benchmark.reference import RefBank

NAME = "bench:bank"
KIND_CONTAINS, KIND_ADD, KIND_PROBE_BACK = 0, 1, 2
_KINDS_AHEAD = 1 << 18
_STREAM = 0xB10F


def _plane_shape(sizes):
    return sizes["tenants"], sizes["m_bits"], sizes["k"]


def _populated(seed: int, sizes: dict, lo: int, hi: int):
    """(tenants, keys) of populated positions [lo, hi): position j holds
    global key number (j * stride) mod N, so a batch mixes every tenant."""
    n = sizes["tenants"] * sizes["per_tenant"]
    i = (np.arange(lo, hi, dtype=np.int64) * sizes["populate_stride"]) % n
    return (i // sizes["per_tenant"]).astype(np.int32), D.keys(seed, D.POPULATED, i)


def reference(sizes: dict, params: dict, seed: int) -> dict:
    """The plane as set-up leaves it: every tenant's keys added."""
    t, m, k = _plane_shape(sizes)
    n = t * sizes["per_tenant"]
    ref = RefBank(t, m, k)
    for lo in range(0, n, 1 << 20):
        ref.add(*_populated(seed, sizes, lo, min(n, lo + (1 << 20))))
    return {"plane": ref.bits}


def populate(client, sizes: dict, params: dict, seed: int) -> dict:
    bank = client.get_bloom_filter_array(NAME)
    if not bank.try_init(sizes["tenants"], sizes["per_tenant"], sizes["fpp"]):
        raise RuntimeError("BFA.RESERVE refused")
    m, k = int(bank.get_size()), int(bank.get_hash_iterations())
    if (m, k) != (sizes["m_bits"], sizes["k"]):
        raise RuntimeError(f"bank geometry m={m} k={k}, the configuration "
                           f"states m={sizes['m_bits']} k={sizes['k']}")
    n = sizes["tenants"] * sizes["per_tenant"]
    newly = 0
    for lo in range(0, n, sizes["populate_batch"]):
        t, keys = _populated(seed, sizes, lo, min(n, lo + sizes["populate_batch"]))
        newly += int(bank.add_each(t, keys).sum())
    return {"populated_keys": n, "newly_added": newly}


def after_window(client, sizes: dict, params: dict, seed: int, ref: dict, writes: dict):
    """The run's adds as sorted flat bit positions: the overlay that turns
    the lower plane into the upper one."""
    t, m, k = _plane_shape(sizes)
    rows = [w for w in writes.values() if len(w[0])]
    if not rows:
        return [], {"extra_bits": np.zeros(0, np.int64)}
    bank = RefBank(t, m, k, bits=ref["plane"])
    flat = bank._flat(np.concatenate([w[0] for w in rows]),
                      np.concatenate([w[1] for w in rows]))
    return [], {"extra_bits": np.unique(flat)}


class Stream:
    def __init__(self, ctx):
        self.ctx, self.sizes, p = ctx, ctx.sizes, ctx.params
        self.n = p["keys_per_request"]
        self.zipf = D.Zipf(self.sizes["tenants"], p["skew"], ctx.seed)
        self.add_share = p.get("add_share", 0.0)
        self.check_share = p.get("check_share", 1.0)
        # kinds are a property of the position, fixed before any is sent:
        # the call two after an add probes its keys; no add follows an add
        # at that distance, so every added key is probed once
        u = D.rng(ctx.seed, _STREAM, ctx.conn, 1).random(_KINDS_AHEAD + 2)
        kinds = np.zeros(_KINDS_AHEAD + 2, np.int8)
        for i in np.flatnonzero(u < self.add_share):
            if i >= 2 and kinds[i - 2] == KIND_ADD:
                continue
            kinds[i] = KIND_ADD
        kinds[2:][kinds[:-2] == KIND_ADD] = KIND_PROBE_BACK
        self.kinds = kinds
        self.kept = []
        self.added_t, self.added_k = [], []

    def bind(self, client):
        self.bank = client.get_bloom_filter_array(NAME)

    # -- what a request is ----------------------------------------------------

    def _slot(self, idx: int) -> np.ndarray:
        """Positions of this call's keys in the per-run key regions."""
        base = (self.ctx.conn << 44) | ((idx & 0xFFFFFF) << 20)
        return np.uint64(base) + np.arange(self.n, dtype=np.uint64)

    def _kind(self, idx: int) -> int:
        if idx < 0:  # warm-up: -1 a probe, -2 an add
            return KIND_ADD if idx == -2 else KIND_CONTAINS
        if idx >= _KINDS_AHEAD:
            raise RuntimeError("more calls on one connection than kinds were drawn for")
        return int(self.kinds[idx])

    def make(self, idx: int):
        kind = self._kind(idx)
        if kind == KIND_PROBE_BACK:
            _k, t, keys = self.make(idx - 2)
            return kind, t, keys
        g = D.rng(self.ctx.seed, _STREAM, self.ctx.conn, 2, idx & 0xFFFFFFFF)
        if self.ctx.params["tenant_of"] == "request":
            t = np.full(self.n, self.zipf.draw(g, 1)[0], np.int32)
        else:
            t = self.zipf.draw(g, self.n).astype(np.int32)
        if kind == KIND_ADD:
            return kind, t, D.keys(self.ctx.seed, D.ADDED, self._slot(idx))
        keys = D.keys(self.ctx.seed, D.ABSENT, self._slot(idx))
        half = len(keys[0::2])
        j = g.integers(0, self.sizes["per_tenant"], half)
        keys[0::2] = D.keys(self.ctx.seed, D.POPULATED,
                            t[0::2].astype(np.int64) * self.sizes["per_tenant"] + j)
        return kind, t, keys

    def warmup(self):
        return [self.make(-1)] + ([self.make(-2)] if self.add_share else [])

    def arrivals(self, seconds: float):
        p = self.ctx.params
        return D.poisson_arrivals(D.rng(self.ctx.seed, _STREAM, self.ctx.conn, 3),
                                  p["rate"] / p["connections"], seconds)

    def closing(self, idx: int):
        return None

    def ops(self, req) -> int:
        return self.n

    # -- the public client API --------------------------------------------------

    def send(self, req):
        kind, t, keys = req
        if kind == KIND_ADD:
            return self.bank.add_each(t, keys)
        return self.bank.contains(t, keys)

    def keep(self, idx: int, req, reply):
        kind = req[0]
        if kind == KIND_ADD:
            self.added_t.append(req[1])
            self.added_k.append(req[2])
        self.kept.append((idx, kind, reply))

    def writes(self):
        if not self.added_t:
            return np.zeros(0, np.int32), np.zeros(0, np.int64)
        return np.concatenate(self.added_t), np.concatenate(self.added_k)

    # -- after the window ---------------------------------------------------------

    def verify(self) -> dict:
        t, m, k = _plane_shape(self.sizes)
        bank = RefBank(t, m, k, bits=self.ctx.ref("plane"))
        extra = np.asarray(self.ctx.ref("extra_bits"))
        pick = D.rng(self.ctx.seed, _STREAM, self.ctx.conn, 4)
        failures, false_neg, full = [], 0, 0
        for idx, kind, reply in self.kept:
            if len(reply) != self.n:
                failures.append(f"call {idx}: {len(reply)} flags for {self.n} keys")
                continue
            if kind == KIND_PROBE_BACK:
                false_neg += int((~reply).sum())  # an acknowledged add, read back
            elif kind == KIND_CONTAINS:
                false_neg += int((~reply[0::2]).sum())
            if pick.random() >= self.check_share or kind == KIND_PROBE_BACK:
                continue
            full += 1
            _kind, tn, keys = self.make(idx)
            g = bank._flat(tn, keys)
            lower = bank.bits[g].astype(bool)
            if kind == KIND_ADD:
                # newly-added means some bit was clear before the add, so it
                # was clear in the plane as populated too
                bad = reply & lower.all(axis=1)
            else:
                upper = lower
                if len(extra):
                    at = np.minimum(np.searchsorted(extra, g), len(extra) - 1)
                    upper = lower | (extra[at] == g)
                bad = (lower.all(axis=1) & ~reply) | (reply & ~upper.all(axis=1))
            if bad.any():
                failures.append(f"conn {self.ctx.conn} call {idx} (kind {kind}): "
                                f"{int(bad.sum())} of {self.n} flags outside the reference")
        if false_neg:
            failures.append(f"conn {self.ctx.conn}: {false_neg} false negatives")
        return {"checked_full": full, "checked": len(self.kept),
                "failures": failures[:8]}

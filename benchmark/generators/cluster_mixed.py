"""Multi-tenant fan-out over a slot-sharded keyspace: every tenant has its
own hash tag ``{tN}`` and under it one bloom filter (``bf``) and two bitsets
(``ba``, ``bb``).  One request is one pipelined frame (``execute_many``, the
client's one-flush batch) over ``tenants_per_frame`` different tenants:

  a run of BF.MADD64     ``adds_per_frame`` tenants, ``keys_per_add`` new keys
  a run of BF.MEXISTS64  every tenant, ``keys_per_probe`` keys, half present
                         (populated in set-up, or added by an earlier frame)
  per tenant             SETBITSB ba <set_bits indexes>, BITOP OR ba ba bb,
                         BITOP XOR bb bb ba, BITCOUNT ba

The commands are grouped by verb as above: the server's coalescer fuses
consecutive same-verb runs.

A tenant belongs to one connection (tenant mod connections), as a tenant's
own service instance would hold it: its commands are served in the order
they were sent, so the reference can follow a tenant exactly — XOR is not
monotone, and two connections mutating one bitset would leave the answer to
the scheduler.  ``sample`` tenants are followed bit for bit (newly-added
flags, found vectors, SETBITSB's previous bits, BITOP lengths, BITCOUNT);
for every tenant the present-key flags must all be set.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import datagen as D
from benchmark.reference import RefBank, RefBitSet

_STREAM = 0xFA40
_SLOT = 1 << 24


def names(t: int):
    tag = "{t%d}" % t
    return "bf" + tag, "ba" + tag, "bb" + tag


def _blob8(a) -> bytes:
    return np.ascontiguousarray(a, "<i8").tobytes()


def _blob4(a) -> bytes:
    return np.ascontiguousarray(a, "<i4").tobytes()


def _filter_keys(seed: int, sizes: dict, t: int):
    return D.keys(seed, D.POPULATED, t * _SLOT + np.arange(sizes["per_tenant"]))


def _bitset_fill(seed: int, sizes: dict, t: int, which: int):
    n = int(sizes["bitset_bits"] * sizes["bit_density"])
    idx = D.rng(seed, _STREAM, 7, t, which).integers(0, sizes["bitset_bits"], n)
    idx[0] = sizes["bitset_bits"] - 1  # both planes span the full size
    return idx.astype(np.int32)


def sampled_tenants(seed: int, sizes: dict) -> np.ndarray:
    return np.sort(D.rng(seed, _STREAM, 5).choice(
        sizes["tenants"], sizes["sample"], replace=False))


def reference(sizes: dict, params: dict, seed: int) -> dict:
    return {"sampled": sampled_tenants(seed, sizes)}


def _raise_errors(replies, what: str):
    for r in replies:
        if isinstance(r, Exception):
            raise RuntimeError(f"{what}: {r}")


POPULATE_THREADS = 8


def _populate_some(addr: str, sizes: dict, seed: int, tenants) -> None:
    from benchmark.loadgen import connect

    with connect(addr) as client:
        for t in tenants:
            bf, ba, bb = names(t)
            _raise_errors(client.execute_many([
                ("BF.RESERVE", bf, repr(sizes["fpp"]), sizes["per_tenant"]),
                ("BF.MADD64", bf, _blob8(_filter_keys(seed, sizes, t))),
                ("SETBITSB", ba, _blob4(_bitset_fill(seed, sizes, t, 0))),
                ("SETBITSB", bb, _blob4(_bitset_fill(seed, sizes, t, 1)))]), "populate")


def populate(client, sizes: dict, params: dict, seed: int) -> dict:
    """One frame a tenant — its filter reserved and filled, its bitsets set —
    over a few connections at once (tenants are independent).  A frame over
    many tenants would be fused and fetched in groups whose shapes follow
    from which tenants share a chip: new programs every frame.  The first
    tenants go alone, so a first run compiles each program once."""
    addr = client.node.address
    first = min(sizes["tenants"], 4 * POPULATE_THREADS)
    _populate_some(addr, sizes, seed, range(first))
    with ThreadPoolExecutor(POPULATE_THREADS) as pool:
        for done in [pool.submit(_populate_some, addr, sizes, seed,
                                 range(first + w, sizes["tenants"], POPULATE_THREADS))
                     for w in range(POPULATE_THREADS)]:
            done.result()
    info = client.execute("BF.INFO", names(0)[0])
    geometry = (int(info[info.index(b"Size") + 1]),
                int(info[info.index(b"Number of hashes") + 1]))
    if geometry != (sizes["m_bits"], sizes["k"]):
        raise RuntimeError(f"filter geometry {geometry}, the configuration "
                           f"states m={sizes['m_bits']} k={sizes['k']}")
    return {"records": 3 * sizes["tenants"]}


def after_window(client, sizes, params, seed, ref, writes):
    return [], {}


class _Tenant:
    """The reference's copy of one sampled tenant."""

    def __init__(self, seed: int, sizes: dict, t: int):
        self.bf = RefBank(1, sizes["m_bits"], sizes["k"])
        keys = _filter_keys(seed, sizes, t)
        self.bf.add(np.zeros(len(keys), np.int32), keys)
        self.a, self.b = RefBitSet(sizes["bitset_bits"]), RefBitSet(sizes["bitset_bits"])
        self.a.set_each(_bitset_fill(seed, sizes, t, 0))
        self.b.set_each(_bitset_fill(seed, sizes, t, 1))


class Stream:
    def __init__(self, ctx):
        self.ctx, self.sizes, self.p = ctx, ctx.sizes, ctx.params
        mine = np.arange(ctx.conn, self.sizes["tenants"], ctx.n_conns)
        self.zipf = D.Zipf(self.sizes["tenants"], self.p["skew"], ctx.seed, among=mine)
        self.added = {}  # tenant -> keys added so far (by frames already made)
        self.kept = []

    def bind(self, client):
        self.client = client

    def make(self, idx: int):
        """Frames are made in order: a frame probes keys its connection's
        earlier frames added."""
        p, seed = self.p, self.ctx.seed
        g = D.rng(seed, _STREAM, self.ctx.conn, 2, idx & 0xFFFFFFFF)
        ts = self.zipf.draw_distinct(g, p["tenants_per_frame"])
        plan = []  # per tenant: (t, add keys or None, probe keys, bit indexes)
        half = p["keys_per_probe"] // 2
        for j, t in enumerate(ts):
            t = int(t)
            had = self.added.get(t, 0)
            add = None
            if j < p["adds_per_frame"]:
                add = D.keys(seed, D.ADDED, t * _SLOT + had + np.arange(p["keys_per_add"]))
                self.added[t] = had + p["keys_per_add"]
            probe = D.keys(seed, D.ABSENT, (t * _SLOT + (idx & 0xFFFF) * 256
                                            + np.arange(p["keys_per_probe"])))
            probe[0::2] = D.keys(seed, D.POPULATED,
                                 t * _SLOT + g.integers(0, self.sizes["per_tenant"], half))
            if had:  # some of the present half from what earlier frames added
                n_back = min(p["probe_back"], half)
                probe[0:2 * n_back:2] = D.keys(
                    seed, D.ADDED, t * _SLOT + g.integers(0, had, n_back))
            bits = g.integers(0, self.sizes["bitset_bits"], p["set_bits"]).astype(np.int32)
            plan.append((t, add, probe, bits))
        cmds, slots = [], []  # slots[i] = (tenant position, what) of command i
        for j, (t, a, _pk, _b) in enumerate(plan):
            if a is not None:
                cmds.append(("BF.MADD64", names(t)[0], _blob8(a)))
                slots.append((j, "add"))
        for j, (t, _a, pk, _b) in enumerate(plan):
            cmds.append(("BF.MEXISTS64", names(t)[0], _blob8(pk)))
            slots.append((j, "probe"))
        for j, (t, _a, _pk, bits) in enumerate(plan):
            _f, a, b = names(t)
            cmds += [("SETBITSB", a, _blob4(bits)), ("BITOP", "OR", a, a, b),
                     ("BITOP", "XOR", b, b, a), ("BITCOUNT", a)]
            slots += [(j, "set"), (j, "or"), (j, "xor"), (j, "count")]
        ops = (sum(len(a) for _t, a, _p, _b in plan if a is not None)
               + sum(len(pk) for _t, _a, pk, _b in plan)
               + sum(len(b) + 3 for _t, _a, _p, b in plan))
        return cmds, slots, plan, ops

    def warmup(self):
        return [self.make(-1)]

    def closing(self, idx: int):
        return None

    def ops(self, req) -> int:
        return req[3]

    def send(self, req):
        replies = self.client.execute_many(req[0])
        _raise_errors(replies, "frame")
        return replies

    def keep(self, idx: int, req, reply):
        self.kept.append((idx, req[1], req[2], reply))

    def writes(self):
        return None

    def verify(self) -> dict:
        sampled = set(int(t) for t in self.ctx.ref("sampled"))
        refs = {}
        failures, false_neg, exact = [], 0, 0

        def differs(idx, t, what):
            failures.append(f"conn {self.ctx.conn} frame {idx} tenant {t}: {what} "
                            "differs from the reference")

        for idx, slots, plan, replies in self.kept:
            for (j, what), r in zip(slots, replies):
                t, add, probe, bits = plan[j]
                if what == "probe":
                    found = np.frombuffer(r, np.uint8).astype(bool)
                    if len(found) != len(probe):
                        differs(idx, t, "length of found vector")
                        continue
                    false_neg += int((~found[0::2]).sum())
                if t not in sampled:
                    continue
                if t not in refs:
                    refs[t] = _Tenant(self.ctx.seed, self.sizes, t)
                ref = refs[t]
                exact += 1
                if what == "add":
                    want = ref.bf.add(np.zeros(len(add), np.int32), add)
                    if not np.array_equal(np.frombuffer(r, np.uint8).astype(bool), want):
                        differs(idx, t, "newly-added flags")
                elif what == "probe":
                    if not np.array_equal(found, ref.bf.contains(
                            np.zeros(len(probe), np.int32), probe)):
                        differs(idx, t, "found vector")
                elif what == "set":
                    if not np.array_equal(np.frombuffer(r, np.uint8).astype(bool),
                                          ref.a.set_each(bits)):
                        differs(idx, t, "SETBITSB previous bits")
                elif what == "or":
                    ref.a.or_(ref.b)
                    if int(r) != ref.a.byte_length():
                        differs(idx, t, "BITOP OR length")
                elif what == "xor":
                    ref.b.xor(ref.a)
                    if int(r) != ref.b.byte_length():
                        differs(idx, t, "BITOP XOR length")
                elif int(r) != ref.a.count():
                    differs(idx, t, f"BITCOUNT {int(r)} vs {ref.a.count()}")
        if false_neg:
            failures.append(f"conn {self.ctx.conn}: {false_neg} false negatives")
        return {"checked_full": exact, "checked": len(self.kept),
                "failures": failures[:8]}

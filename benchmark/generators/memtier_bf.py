"""memtier_benchmark's default fleet against ONE RedisBloom-style filter of
string items, in its arbitrary-command mode (``--command "BF.ADD bf
__key__"`` / ``--command "BF.EXISTS bf __key__"``), through the raw-command
surface of ``RemoteRedisson`` (``execute``: one command, one reply).

What the README's tables would say of this family:

  traffic parameters (``benchmark/traffic/*.json``)
    connections      connections in all (memtier: threads x clients, 4 x 50);
                     each is a ``RemoteRedisson`` of its own, one socket
    processes        client processes they are spread over (memtier's C
                     threads would be as many Python GILs here)
    exists_per_add   ``BF.EXISTS`` to one ``BF.ADD`` (memtier ``--ratio
                     1:10``: 10); the ratio is exact, a cycle of
                     ``exists_per_add + 1`` commands that starts at a seeded
                     phase on every connection
    reprobe_after    a connection probes the key it added again this many
                     commands later (2): an acknowledged add, read back
    key_max, key_prefix, k   the request's shape: items are ``<key_prefix><n>``,
                     n in 1..key_max (memtier ``--key-prefix memtier-
                     --key-maximum 10000000``, 9-16 bytes), k cells an item;
                     they must be the configuration's
    loop             "closed": every connection has exactly ONE command in
                     flight (memtier ``--pipeline 1``): send, wait for the
                     decoded reply, send the next

  one request   ONE command — ``BF.EXISTS bf memtier-<n>`` with n uniform
    over the whole key space (memtier ``--key-pattern R:R``), or ``BF.ADD bf
    memtier-<n>`` with n the next of the connection's own seeded walk over
    its slice of the ODD numbers, each once — and its integer reply.  Set-up
    adds the EVEN numbers (half the key space), so a probe is present or
    absent with equal odds.  One operation = one command answered and found
    right; one latency sample = one command, send to decoded reply.  A
    request is a pure function of the seed, the connection and its position.

  how a reply is checked (``Stream.verify`` in the workers, ``after_window``
    in the parent; the reference is ``benchmark/reference_bf.py``)
    BF.EXISTS of a key populated in set-up, or whose BF.ADD was acknowledged
      (on ANY connection, by CLOCK_MONOTONIC) before this command was sent:
      must answer 1 — limit 0 false negatives;
    BF.EXISTS of any other key: what the reference says, wherever the
      reference over the set-up state and over the window's end state
      agree; where they differ (a false positive born inside the window, or
      an add in flight) either answer passes, and those are counted;
    BF.ADD: 0 if the set-up state already holds all its k cells, else 1
      unless the end state WITHOUT this item holds them all (either passes);
    the re-probe ``reprobe_after`` commands later: must answer 1;
    after the window the parent's connection sends ``BF.MEXISTS`` in chunks
      over ``sweep_keys`` seeded keys, up to half of them keys the window
      added or probed: every answer equals the reference with every
      acknowledged add applied — the plane's state, false positives too.

Set-up fails fast (``BenchFailure``) unless ``BF.INFO`` gives the
configuration's m, k and capacity after ``BF.RESERVE`` and a chunk of
``BF.MEXISTS`` over the populated filter equals the reference; a worker
refuses to open the window unless ``INFO`` reads ``connected_clients`` >=
``connections``, and the parent checks the same again before any connection
closes.  Nothing here reads a counter or span of the program: a program
without them runs the cell to its end.
"""
import sys
import time

import numpy as np

from benchmark import datagen as D
from benchmark import reference_bf as R

NAME = "bf"
KIND_EXISTS, KIND_ADD, KIND_REPROBE = 0, 1, 2
VERB = {KIND_EXISTS: "BF.EXISTS", KIND_ADD: "BF.ADD", KIND_REPROBE: "BF.EXISTS"}
EITHER = 2  # a BF.ADD whose 0 and 1 both pass
_STREAM = 0x3E37
_BLOCK = 4096  # probe keys are drawn a block at a time
_CACHE = {}    # the parent builds the set-up plane once, for reference() and populate()


def _fail(msg: str):
    """run.py's BenchFailure (non-zero exit, no result line) where this runs
    under run.py; a RuntimeError anywhere else."""
    return getattr(sys.modules.get("__main__"), "BenchFailure", RuntimeError)(msg)


def _items(sizes: dict, numbers) -> list:
    prefix = sizes["key_prefix"].encode()
    return [prefix + b"%d" % n for n in np.asarray(numbers).tolist()]


def _packed(sizes: dict, numbers) -> tuple:
    return R.numbered(sizes["key_prefix"].encode(), numbers)


class Keys:
    """What connection ``conn`` sends at each position: a pure function of
    the seed, the connection and the position, the same in a worker, in the
    parent after the window and in a test."""

    def __init__(self, sizes: dict, params: dict, seed: int, conn: int):
        self.seed, self.conn = seed, conn
        self.key_max = sizes["key_max"]
        self.cycle = params["exists_per_add"] + 1
        self.after = params["reprobe_after"]
        if not 0 < self.after < self.cycle:
            raise _fail("reprobe_after must lie inside the cycle")
        self.phase = int(D.rng(seed, _STREAM, conn, 1).integers(0, self.cycle))
        # the connection's own slice of the odd numbers, walked in seeded order
        self.slice = (self.key_max // 2) // params["connections"]
        self.walk = D.rng(seed, _STREAM, conn, 2).permutation(self.slice)
        self._block = (None, None)

    def _odd(self, at: int) -> int:
        return 2 * (self.conn * self.slice + int(self.walk[at])) + 1

    def kinds(self, idx) -> np.ndarray:
        """Kinds of the window's positions ``idx`` (>= 0): an add opens every
        cycle, counted from the connection's phase; ``after`` commands later
        its key is probed again."""
        idx = np.asarray(idx)
        at = (idx - self.phase) % self.cycle
        back = (at == self.after) & (idx >= self.phase + self.after)
        return np.where(at == 0, KIND_ADD, np.where(back, KIND_REPROBE, KIND_EXISTS))

    def kind(self, idx: int) -> int:
        if idx < 0:  # warm-up: -1 a probe, -2 an add
            return KIND_ADD if idx == -2 else KIND_EXISTS
        return int(self.kinds(idx))

    def probes(self, block: int) -> np.ndarray:
        """The uniform keys of positions [block * _BLOCK, (block + 1) * _BLOCK)."""
        if self._block[0] != block:
            g = D.rng(self.seed, _STREAM, self.conn, 3, block & 0xFFFFFFFF)
            self._block = (block, g.integers(1, self.key_max + 1, _BLOCK))
        return self._block[1]

    def key(self, idx: int) -> int:
        kind = self.kind(idx)
        if kind == KIND_REPROBE:
            return self.key(idx - self.after)
        if kind == KIND_EXISTS:
            return int(self.probes(idx // _BLOCK)[idx % _BLOCK])
        if idx < 0:
            return self._odd(self.slice - 1)  # the walk's last: warm-up's own
        nth = (idx - self.phase) // self.cycle
        if nth >= self.slice - 1:
            raise RuntimeError(f"connection {self.conn} ran out of keys to add")
        return self._odd(nth)

    def probed(self, sent: int) -> np.ndarray:
        """Keys of the plain probes among positions [0, sent)."""
        keys = np.concatenate([self.probes(b) for b in range(-(-sent // _BLOCK))]
                              or [np.zeros(0, np.int64)])[:sent]
        return keys[self.kinds(np.arange(sent)) == KIND_EXISTS]


def _geometry(sizes: dict, params: dict) -> tuple:
    m = R.optimal_m(sizes["capacity"], sizes["error_rate"])
    k = R.optimal_k(sizes["capacity"], m)
    if (m, k) != (sizes["m_bits"], sizes["k"]):
        raise _fail(f"the configuration states m={sizes['m_bits']} k={sizes['k']}; its "
                    f"capacity and error rate give m={m} k={k}")
    for key in ("k", "key_prefix", "key_max"):
        if params[key] != sizes[key]:
            raise _fail(f"the traffic's {key} is not the configuration's")
    return m, k


def _populated(sizes: dict) -> range:
    return range(2, sizes["key_max"] + 1, 2)


def reference(sizes: dict, params: dict, seed: int) -> dict:
    """The plane as set-up leaves it: every even number's item added."""
    m, k = _geometry(sizes, params)
    ref = R.RefFilter(m, k)
    evens = _populated(sizes)
    for lo in range(0, len(evens), 1 << 20):
        ref.add_many(*_packed(sizes, np.asarray(evens[lo:lo + (1 << 20)])))
    _CACHE["plane"] = ref.cells
    return {"plane": ref.cells}


def _mexists(client, sizes: dict, numbers) -> np.ndarray:
    """``BF.MEXISTS`` over the items of ``numbers``, a chunk a command, the
    commands in one pipelined frame."""
    chunk = sizes["sweep_chunk"]
    replies = client.execute_many([
        ("BF.MEXISTS", NAME, *_items(sizes, numbers[lo:lo + chunk]))
        for lo in range(0, len(numbers), chunk)])
    for r in replies:
        if not isinstance(r, list):
            raise _fail(f"BF.MEXISTS answered {r!r}"[:200])
    return np.array([f for r in replies for f in r], bool)


def populate(client, sizes: dict, params: dict, seed: int) -> dict:
    made = client.execute("BF.RESERVE", NAME, repr(sizes["error_rate"]), sizes["capacity"])
    if made not in (b"OK", "OK"):
        raise _fail(f"BF.RESERVE answered {made!r}")
    info = client.execute("BF.INFO", NAME)
    info = {bytes(info[i]).decode(): info[i + 1] for i in range(0, len(info), 2)}
    m, k, capacity = int(info["Size"]), int(info["Number of hashes"]), int(info["Capacity"])
    # a server that reports no capacity (0: the tree before PR 35 read a key
    # its records never had) is held to it through m and k, which follow
    # from the capacity and the error rate alone
    if (m, k) != (sizes["m_bits"], sizes["k"]) or capacity not in (sizes["capacity"], 0):
        raise _fail(f"BF.INFO gives m={m} k={k} capacity={capacity}; the configuration "
                    f"states m={sizes['m_bits']} k={sizes['k']} capacity={sizes['capacity']}")
    evens, batch = _populated(sizes), sizes["populate_batch"]
    frame = batch * sizes["populate_pipeline"]
    newly = 0
    for lo in range(0, len(evens), frame):
        part = evens[lo:lo + frame]
        replies = client.execute_many([("BF.MADD", NAME, *_items(sizes, part[a:a + batch]))
                                       for a in range(0, len(part), batch)])
        if sum(len(r) if isinstance(r, list) else -1 for r in replies) != len(part):
            raise _fail(f"BF.MADD of items {lo}..{lo + len(part)} answered {replies[0]!r}"[:200])
        newly += sum(sum(r) for r in replies)
    # the sweep's program, warmed on the populated filter and held to the reference
    some = D.rng(seed, _STREAM, 8).integers(1, sizes["key_max"] + 1, sizes["sweep_chunk"])
    want = R.RefFilter(m, k, cells=_CACHE["plane"]).contains(*_packed(sizes, some))
    got = _mexists(client, sizes, some)
    if not (got == want).all():
        raise _fail(f"after populate {int((got != want).sum())} of {len(some)} BF.MEXISTS "
                    "answers differ from the reference over the set-up state")
    return {"populated_items": len(evens), "newly_added": int(newly),
            "bf_info": {"m": m, "k": k, "capacity": capacity}}


def _connected(client) -> int:
    for line in client.info().splitlines():
        if line.startswith("connected_clients:"):
            return int(line.split(":", 1)[1])
    return -1


def after_window(client, sizes: dict, params: dict, seed: int, ref: dict, writes: dict):
    """With every load connection still open: the fleet is counted, the
    window's acknowledged adds become what the workers check against (who
    added what and when, what each add had to answer, the cells the adds
    set), and the plane's end state is swept."""
    failures = []
    connected = _connected(client)
    if connected < params["connections"]:
        failures.append(f"connected_clients {connected} with the load connections "
                        f"still open: fewer than the mix's {params['connections']}")
    m, k = sizes["m_bits"], sizes["k"]
    ref0 = R.RefFilter(m, k, cells=ref["plane"])
    added = np.concatenate([w["added"] for w in writes.values()] or [np.zeros(0, np.int64)])
    acked = np.concatenate([w["acked"] for w in writes.values()] or [np.zeros(0)])
    order = np.argsort(added)
    added, acked = added[order], acked[order]
    if len(np.unique(added)) != len(added) or (len(added) and not (added % 2).all()):
        failures.append("the connections' adds are not distinct odd numbers")
    at = ref0.indexes(*_packed(sizes, added)) if len(added) else np.zeros((0, k), np.int64)
    cells, times = np.unique(at, return_counts=True)
    # an add's cells in the end state WITHOUT it: set before the window, or
    # set by another add (its own repeats of one cell taken off)
    own = (at[:, :, None] == at[:, None, :]).sum(axis=2)
    others = times[np.searchsorted(cells, at)] - own
    had_all = ref0.cells[at].all(axis=1)
    expect = np.where(had_all, 0, np.where((ref0.cells[at] | (others > 0)).all(axis=1),
                                           EITHER, 1)).astype(np.int8)
    end = R.RefFilter(m, k, cells=ref0.cells.copy())
    end.cells[cells] = True
    probed = np.concatenate([Keys(sizes, params, seed, c).probed(w["sent"])
                             for c, w in writes.items()] or [np.zeros(0, np.int64)])
    rows = _packed(sizes, probed)
    either = int((ref0.contains(*rows) != end.contains(*rows)).sum()) if len(probed) else 0
    # the sweep: up to half its keys touched in the window (the adds first)
    g = D.rng(seed, _STREAM, 9)
    half = sizes["sweep_keys"] // 2
    touched = np.concatenate([added, g.permutation(probed)])[:half]
    sweep = np.concatenate([touched, g.integers(1, sizes["key_max"] + 1,
                                                sizes["sweep_keys"] - len(touched))])
    got = _mexists(client, sizes, sweep)
    wrong = int((got != end.contains(*_packed(sizes, sweep))).sum())
    if wrong:
        failures.append(f"after the window {wrong} of {len(sweep)} BF.MEXISTS answers "
                        "differ from the reference with every acknowledged add applied")
    one = lambda v: np.array([v])  # noqa: E731 — a size-1 array lands in DETAIL
    return failures, {
        "added_n": added, "added_ack": acked, "add_expect": expect, "extra_cells": cells,
        "connected_clients": one(connected), "adds_acknowledged": one(len(added)),
        "adds_either_answer": one(int((expect == EITHER).sum())),
        "probes_either_answer": one(either), "sweep_keys": one(len(sweep)),
        "sweep_touched": one(len(touched)), "sweep_wrong": one(wrong)}


class Stream:
    def __init__(self, ctx):
        self.ctx, self.sizes = ctx, ctx.sizes
        self.keys = Keys(ctx.sizes, ctx.params, ctx.seed, ctx.conn)
        self.prefix = ctx.sizes["key_prefix"].encode()
        self.kept = []   # (idx, kind, n, answer, t_send)
        self.added = []  # (n, when its reply had arrived)
        self.sent = 0

    def bind(self, client):
        self.client = client
        client.ping()  # the socket is open before the workers report ready

    def make(self, idx: int):
        return self.keys.kind(idx), self.keys.key(idx)

    def warmup(self):
        if self.ctx.conn == 0:  # every worker has connected: the fleet is whole
            connected = _connected(self.client)
            if connected < self.ctx.params["connections"]:
                raise _fail(f"connected_clients {connected} when the window opens: "
                            f"fewer than the mix's {self.ctx.params['connections']}")
        return [self.make(-1), self.make(-2)]

    def closing(self, idx: int):
        return None

    def ops(self, req) -> int:
        return 1

    def send(self, req):
        kind, n = req
        item = self.prefix + b"%d" % n
        t0 = time.monotonic()
        answer = self.client.execute(VERB[kind], NAME, item)
        return int(answer), t0, time.monotonic()

    def keep(self, idx: int, req, reply):
        kind, n = req
        answer, t_send, t_done = reply
        if kind == KIND_ADD:
            self.added.append((n, t_done))
        self.kept.append((idx, kind, n, answer, t_send))
        self.sent = max(self.sent, idx + 1)

    def writes(self):
        return {"added": np.array([a[0] for a in self.added], np.int64),
                "acked": np.array([a[1] for a in self.added], np.float64),
                "sent": self.sent}

    def verify(self) -> dict:
        kept = np.array(self.kept, np.float64).reshape(-1, 5)
        kind, n, answer, t_send = (kept[:, 1].astype(int), kept[:, 2].astype(np.int64),
                                   kept[:, 3].astype(int), kept[:, 4])
        ref0 = R.RefFilter(self.sizes["m_bits"], self.sizes["k"], cells=self.ctx.ref("plane"))
        added_n, added_ack = self.ctx.ref("added_n"), self.ctx.ref("added_ack")
        expect, extra = self.ctx.ref("add_expect"), self.ctx.ref("extra_cells")
        failures = []
        if not len(kept):
            return {"checked_full": 0, "checked": 0, "failures": failures}
        if not np.isin(answer, (0, 1)).all():
            failures.append(f"conn {self.ctx.conn}: an answer that is neither 0 nor 1")
        at = ref0.indexes(*_packed(self.sizes, n))
        low = ref0.cells[at]
        if len(extra):
            high = low | (extra[np.minimum(np.searchsorted(extra, at), len(extra) - 1)] == at)
        else:
            high = low
        low, high = low.all(axis=1), high.all(axis=1)
        where = np.minimum(np.searchsorted(added_n, n), max(len(added_n) - 1, 0))
        was_added = (added_n[where] == n) if len(added_n) else np.zeros(len(n), bool)
        acked_before = was_added & ((added_ack[where] < t_send) if len(added_n) else False)
        probe = kind != KIND_ADD
        must = probe & ((n % 2 == 0) | acked_before)
        false_neg = int((must & (answer != 1)).sum())
        free = probe & ~must
        wrong = int((free & (low == high) & (answer != low)).sum())
        adds = kind == KIND_ADD
        if adds.any() and not was_added[adds].all():
            failures.append(f"conn {self.ctx.conn}: an add the parent never heard of")
        want = expect[where[adds]] if len(added_n) else np.zeros(0, np.int8)
        wrong_adds = int(((want != EITHER) & (answer[adds] != want)).sum())
        back = kind == KIND_REPROBE
        if (back & ~acked_before).any():
            failures.append(f"conn {self.ctx.conn}: a re-probe whose add was not acknowledged")
        if false_neg:
            failures.append(f"conn {self.ctx.conn}: {false_neg} false negatives "
                            f"({int((back & (answer != 1)).sum())} of them re-probes)")
        if wrong or wrong_adds:
            failures.append(f"conn {self.ctx.conn}: {wrong} of {int(free.sum())} BF.EXISTS and "
                            f"{wrong_adds} of {int(adds.sum())} BF.ADD answers outside the "
                            "reference")
        return {"checked_full": len(kept), "checked": len(kept), "failures": failures[:8]}

"""DeviceStore: the registry of named device-resident states.

Role parity: in the reference, every RObject is a *stateless handle* and all
state lives in the Redis server keyed by name (SURVEY.md §1 L5).  Here the
"server state" is a process-local registry mapping object name -> a state
record holding device arrays plus metadata (kind, logical sizes, hash/format
version).  Handles stay stateless; compound mutations run under the engine's
per-record locks (core/engine.py `locked`/`locked_many`) for Lua-equivalent
atomicity — single writer per object name.

Mutation discipline: states are replaced wholesale (functional update) by
kernels jitted with donated arguments, so XLA reuses the HBM buffer in place —
the TPU analogue of Redis mutating its dict entry.
"""
from __future__ import annotations

import secrets
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

from redisson_tpu.core import residency as _res


@dataclass
class StateRecord:
    kind: str                       # "bloom" | "hll" | "bitset" | "bucket" | ...
    meta: Dict[str, Any] = field(default_factory=dict)
    arrays: Dict[str, Any] = field(default_factory=dict)  # name -> jax.Array
    host: Any = None                # host-side python state (dict/list/...)
    version: int = 0                # bumped on every mutation (optimistic cc)
    expire_at: Optional[float] = None  # epoch seconds, None = persistent
    # creation identity: versions restart at 0 when a name is deleted and
    # recreated, so replication compares (nonce, version), not version alone —
    # otherwise a recreate within one ship interval is invisible to replicas
    nonce: int = field(default_factory=lambda: secrets.randbits(63))
    # residency plane (ISSUE 20): HOT = arrays in HBM (the only state before
    # this PR), WARM = arrays released with the exact host bytes in `stash`,
    # COLD = stash spilled to the verified container at `cold_path`.  Tier
    # moves only under the record lock + the manager's transition lock;
    # version does NOT bump on a tier change (content is identical, so
    # replication/migration must not re-ship a demoted record).
    tier: str = _res.HOT
    stash: Optional[Dict[str, Any]] = None   # WARM host mirror (numpy)
    stash_dev: int = -1                      # device the arrays came off
    cold_path: Optional[str] = None          # COLD spill file
    cold_bytes: int = 0                      # spilled host bytes (census)

    def expired(self, now: Optional[float] = None) -> bool:
        return self.expire_at is not None and (now or time.time()) >= self.expire_at


class DeviceStore:
    """Thread-safe name -> StateRecord registry with TTL semantics.

    TTLs mirror RExpirable (``org/redisson/RedissonExpirable.java``): any
    object can carry an expiry; expired entries are treated as absent and
    reaped lazily on access plus periodically by the EvictionScheduler analog.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._states: Dict[str, StateRecord] = {}
        # Optional hook called whenever an ABSENT name is touched — created
        # (get_or_create / put) or read/deleted as missing (get / delete).
        # The slot-migration window installs one that ASK-redirects absent
        # names in MIGRATING slots: creations must happen on the target, and
        # a record the drain just moved must redirect rather than read as
        # nil (read-your-writes across the handoff).  This is the chokepoint
        # that makes drain-vs-access races lose no acked state
        # (server/server.py _migration_absent_guard).
        self.absent_guard: Optional[Callable[[str], None]] = None
        # Hook fired with the NAMES of expired records the store just
        # reaped (lazily on access or by reap_expired) — the client-tracking
        # plane invalidates near caches through it exactly like a DEL
        # (server/server.py wires it to TrackingTable.note_expired).
        # Contract: the callback must not reenter the store (lazy-expiry
        # sites fire while the store lock is held by reentrant callers).
        self.on_expired: Optional[Callable[[list], None]] = None
        # Device-placement hook (ISSUE 8): fired with (name, record) at
        # EVERY install chokepoint (get_or_create factory result, put,
        # put_unguarded) so a placement-enabled engine commits the record's
        # device arrays to the device owning its slot — creations, restores
        # (checkpoint.load goes through put) and migration/replication
        # imports (put_unguarded) all land on the right device through this
        # ONE seam.  None (the default) keeps today's default-device
        # behavior bit for bit.
        self.placement_hook: Optional[Callable[[str, StateRecord], None]] = None
        # Hook fired with the NAMES a call installed, deleted, renamed, gave
        # a TTL or reaped, and with None when the store was flushed — how a
        # search index learns of a key's fate at the write
        # (services/search.py; in-place mutations of a record's value report
        # through Engine.ingest_hook).  Same contract as on_expired: it runs
        # under the store lock and must not reenter the store.
        self.on_change: Optional[Callable[[Optional[list]], None]] = None
        # residency manager (ISSUE 20): set by Engine.enable_residency —
        # the armed `_res._tier_plane` guard routes getter touches here so
        # multiple engines in one process never cross-wire.  None = the
        # store has no tiering even while the process-global plane is armed.
        self.residency = None

    def _placed(self, name: str, rec: StateRecord) -> StateRecord:
        # a failed placement FAILS the install: swallowing it would leave
        # the record on the default device while CLUSTER DEVICES names
        # another owner — every device's share silently landing on device 0
        if self.placement_hook is not None:
            self.placement_hook(name, rec)
        return rec

    def _changed(self, names: Optional[list]) -> None:
        if self.on_change is not None:
            try:
                self.on_change(names)
            except Exception:  # noqa: BLE001 — an index must never fail a write
                pass

    def _reaped(self, name: str) -> None:
        self._changed([name])
        if self.on_expired is not None:
            try:
                self.on_expired([name])
            except Exception:  # noqa: BLE001 — expiry must never fail a read
                pass

    def _get_locked(self, name: str) -> Optional[StateRecord]:
        """get() body under self._lock (callers hold it) — shared by the
        public getters so the residency fault-in below fires exactly once,
        AFTER the lock is released."""
        rec = self._states.get(name)
        if rec is not None and rec.expired():
            del self._states[name]
            rec = None
            self._reaped(name)
        if rec is None and self.absent_guard is not None:
            self.absent_guard(name)
        return rec

    def get(self, name: str) -> Optional[StateRecord]:
        with self._lock:
            rec = self._get_locked(name)
        # fault-in chokepoint (ISSUE 20): a keyed command touching a
        # WARM/COLD record promotes it back to HOT *here*, OUTSIDE the
        # store lock — promotion takes the record lock and the owner
        # lane's gate, and holding the store lock across either would
        # invert every documented lock order.  Disarmed cost: one module-
        # global load + is-None (tests/test_perf_smoke.py pins it).
        plane = _res._tier_plane
        if plane is not None and rec is not None:
            plane.on_record_access(self, name, rec)
        return rec

    def get_or_create(self, name: str, kind: str, factory: Callable[[], StateRecord]) -> StateRecord:
        with self._lock:
            # raises via absent_guard in a migration window
            rec = self._get_locked(name)
            if rec is None:
                rec = factory()
                assert rec.kind == kind
                self._states[name] = self._placed(name, rec)
            elif rec.kind != kind:
                raise TypeError(
                    f"object '{name}' holds a {rec.kind}, requested {kind} "
                    "(WRONGTYPE in the reference)"
                )
        plane = _res._tier_plane
        if plane is not None and rec is not None:
            plane.on_record_access(self, name, rec)
        return rec

    def put(self, name: str, rec: StateRecord) -> None:
        with self._lock:
            # Expired entries are semantically absent: a put() recreating an
            # expired name in a MIGRATING slot must ASK-redirect exactly like
            # get/get_or_create would (same predicate peek() uses), or the
            # recreated record can slip in behind a completed drain.
            cur = self._states.get(name)
            if (cur is None or cur.expired()) and self.absent_guard is not None:
                self.absent_guard(name)
            self._states[name] = self._placed(name, rec)
            self._changed([name])

    def put_unguarded(self, name: str, rec: StateRecord) -> None:
        """Install bypassing the absent guard — ONLY for migration/replication
        transfer frames, which legitimately create records in windowed slots
        (the importing side) or overwrite during a drain."""
        with self._lock:
            self._states[name] = self._placed(name, rec)
            self._changed([name])

    def delete(self, name: str) -> bool:
        with self._lock:
            existed = self._states.pop(name, None) is not None
            if not existed and self.absent_guard is not None:
                self.absent_guard(name)
            if existed:
                self._changed([name])
            return existed

    def delete_unguarded(self, name: str) -> bool:
        """Delete bypassing the absent guard (the drain's own removal)."""
        with self._lock:
            existed = self._states.pop(name, None) is not None
            if existed:
                self._changed([name])
            return existed

    def exists(self, name: str) -> bool:
        return self.get(name) is not None

    def get_unguarded(self, name: str) -> Optional[StateRecord]:
        """get() without the absent guard — for transfer-frame appliers
        (replication/migration) that legitimately probe absent names."""
        with self._lock:
            rec = self._states.get(name)
            if rec is not None and rec.expired():
                del self._states[name]
                self._reaped(name)
                return None
            return rec

    def peek(self, name: str) -> bool:
        """Existence WITHOUT the absent guard — for routing decisions that
        must inspect both present and absent keys (TRYAGAIN vs ASK) and for
        the drain's own bookkeeping."""
        with self._lock:
            rec = self._states.get(name)
            return rec is not None and not rec.expired()

    def rename(self, old: str, new: str) -> bool:
        with self._lock:
            rec = self._get_locked(old)  # metadata op: no fault-in needed
            if rec is None:
                return False
            if new != old:
                self._states[new] = rec
                del self._states[old]
                self._changed([old, new])
            return True

    def expire(self, name: str, at: Optional[float]) -> bool:
        with self._lock:
            rec = self._get_locked(name)  # metadata op: no fault-in needed
            if rec is None:
                return False
            rec.expire_at = at
            self._changed([name])
            return True

    def ttl(self, name: str) -> Optional[float]:
        """Remaining TTL seconds; None if absent or persistent (pttl analog)."""
        rec = self.get(name)
        if rec is None or rec.expire_at is None:
            return None
        return max(0.0, rec.expire_at - time.time())

    def census_records(self):
        """Non-expired ``(kind, record)`` pairs in one consistent snapshot —
        the residency-ledger scan (server ``_device_bytes_census``): callers
        read each record's arrays WITHOUT the store lock, so a gauge scrape
        never serializes against the write path."""
        with self._lock:
            return [
                (r.kind, r) for r in list(self._states.values())
                if not r.expired()
            ]

    def keys(self, pattern: Optional[str] = None):
        """SCAN/KEYS analog (RedissonKeys.java:545 surface)."""
        import fnmatch

        with self._lock:
            names = [n for n, r in list(self._states.items()) if not r.expired()]
        if pattern is None or pattern == "*":
            return names
        return [n for n in names if fnmatch.fnmatchcase(n, pattern)]

    def reap_expired(self) -> int:
        now = time.time()
        reaped = []
        with self._lock:
            for name in [n_ for n_, r in self._states.items() if r.expired(now)]:
                del self._states[name]
                reaped.append(name)
        if reaped:
            self._changed(reaped)
        if reaped and self.on_expired is not None:
            try:
                self.on_expired(reaped)
            except Exception:  # noqa: BLE001 — sweep must survive hook bugs
                pass
        return len(reaped)

    def flushall(self) -> None:
        with self._lock:
            self._states.clear()
            self._changed(None)

    def __len__(self):
        with self._lock:
            return len(self._states)

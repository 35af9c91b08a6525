"""Embedded execution engine: the CommandAsyncExecutor analog.

The reference routes every object operation through `CommandAsyncExecutor`
(``command/CommandAsyncService.java:538-566`` -> RedisExecutor state machine);
object handles are stateless and share one executor.  Here, handles share one
`Engine`, which owns:

  * the DeviceStore (the "server state"),
  * key packing (codec bytes / int64 -> padded device index tensors),
  * the shape-bucketing policy (compile-cache discipline, core/kernels.py),
  * per-record mutual exclusion (the Lua-atomicity equivalent: every compound
    mutation of one object runs under its record lock — single-writer per
    object, SURVEY.md §7.1 item 5),
  * the in-process pub/sub hub used by synchronizer wakeups and topics.

Remote mode (server/) wraps the same Engine behind the RESP protocol.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

from redisson_tpu.client.codec import Codec, DEFAULT_CODEC
from redisson_tpu.core import kernels as K
from redisson_tpu.core.store import DeviceStore, StateRecord
from redisson_tpu.utils import hashing as H


class Engine:
    def __init__(self, config=None):
        import redisson_tpu
        from redisson_tpu.config import Config
        from redisson_tpu.core.pubsub import PubSubHub

        # engines are where device work starts: configure the persistent
        # XLA compile cache before the first kernel compiles (lazy — a
        # wire-only client never constructs an Engine and never pays the
        # jax import)
        redisson_tpu.enable_compile_cache()
        self.config = config if config is not None else Config()
        self.store = DeviceStore()
        self.pubsub = PubSubHub()
        self.default_codec: Codec = DEFAULT_CODEC
        # name -> [RLock, refcount]: entries exist only while someone holds or
        # waits on them, so object churn can't grow the registry unboundedly
        self._record_locks: dict[str, list] = {}
        self._locks_guard = threading.Lock()
        self._wait_entries: dict[str, "object"] = {}
        self._holder_override = threading.local()
        self._closed = False
        self._eviction = None
        self._timer = None
        self._timer_pool = None
        self._renewal_pool_ = None
        self._events_pool_ = None
        # (name, holder) -> Timeout: active lock-watchdog renewals, all on
        # the ONE shared wheel timer (ServiceManager's HashedWheelTimer role)
        self._renewals: dict[tuple, Any] = {}
        self._services: dict = {}
        # fired with a record's NAME after an object handle changed its
        # value in place (RObject._touch_version): how a search index learns
        # of a write at the write (services/search.py arms it while a
        # hash-mode index exists; None costs a write one load and is-None)
        self.ingest_hook = None
        # overlapped device I/O plane (core/ioplane): double-buffered host
        # staging shared by every flush packer of this engine
        from redisson_tpu.core import ioplane

        self.staging = ioplane.StagingPool()
        # device-sharded serving (ISSUE 8): slot -> local-device placement +
        # one serving lane per device.  None (the default) = single-device
        # behavior, bit for bit; enable_placement() opts in.
        self.placement = None
        self.lanes = None
        # tiered HBM residency (ISSUE 20): None until enable_residency()
        # arms the HOT/WARM/COLD plane for THIS engine's store
        self.residency = None

    def service(self, key: str, factory):
        """Engine-scoped lazy singleton (script cache, search indexes, ...)
        — one instance per engine regardless of which handle asks first."""
        with self._locks_guard:
            svc = self._services.get(key)
            if svc is None:
                svc = self._services[key] = factory()
            return svc

    @property
    def eviction(self):
        """Lazily-started EvictionScheduler (eviction/EvictionScheduler.java
        analog); the sweep thread only exists once something registers."""
        with self._locks_guard:
            if self._closed:
                raise RuntimeError("engine is shut down")
            if self._eviction is None:
                from redisson_tpu.core.eviction import EvictionScheduler

                self._eviction = EvictionScheduler(
                    min_delay=self.config.min_cleanup_delay,
                    max_delay=self.config.max_cleanup_delay,
                )
                # global TTL reaper: RExpirable whole-object expiries
                self._eviction.schedule("__store__", self.store.reap_expired)
            return self._eviction

    @contextmanager
    def impersonate(self, holder_id: Optional[str]):
        """Execute with an explicit synchronizer-holder identity — the server
        runs remote calls under the CLIENT's uuid:threadId (the reference's
        LockName travels from client to Lua the same way,
        RedissonBaseLock.getLockName)."""
        if holder_id is None:
            yield
            return
        prev = getattr(self._holder_override, "value", None)
        self._holder_override.value = holder_id
        try:
            yield
        finally:
            self._holder_override.value = prev

    def holder_override(self) -> Optional[str]:
        return getattr(self._holder_override, "value", None)

    def wait_entry(self, key: str):
        """Shared per-key wait latch (the RedissonLockEntry registry of
        pubsub/PublishSubscribeService — one latch per waiting object).

        Idle entries (no waiters, no buffered signal, untouched for 60s) are
        pruned by a background sweep; every park in the codebase is a bounded
        retry loop, so a signal lost to a prune costs one park timeout, never
        a hang."""
        from redisson_tpu.core.pubsub import WaitEntry

        with self._locks_guard:
            we = self._wait_entries.get(key)
            if we is None:
                we = self._wait_entries[key] = WaitEntry()
        we.touch()  # a fetched entry is in use: restart its idle clock
        # the sweep rides the shared eviction thread; first use starts it
        try:
            self.eviction.schedule("__wait_entry_gc__", self._gc_wait_entries)
        except RuntimeError:
            # engine shut down between the entry fetch and the schedule; the
            # caller's park loop is bounded, so skipping the GC is harmless
            pass
        return we

    def _gc_wait_entries(self, max_idle: float = 60.0) -> int:
        with self._locks_guard:
            stale = [
                k for k, we in self._wait_entries.items() if we.idle(max_idle)
            ]
            for k in stale:
                del self._wait_entries[k]
        return len(stale)

    # -- timers --------------------------------------------------------------

    @property
    def timer(self):
        """ONE shared wheel timer for all watchdogs/renewals — never a thread
        per timeout (connection/ServiceManager.java HashedWheelTimer role)."""
        with self._locks_guard:
            if self._closed:
                raise RuntimeError("engine is shut down")
            if self._timer is None:
                from redisson_tpu.utils.timer import HashedWheelTimer

                self._timer = HashedWheelTimer()
            return self._timer

    @property
    def timer_pool(self):
        """Small shared pool that RUNS timed tasks (the reference pairs its
        wheel timer with the ServiceManager executor the same way): wheel
        ticks only enqueue, so a task blocking on a contended record lock
        can never stall every other timeout in the process."""
        with self._locks_guard:
            if self._closed:
                raise RuntimeError("engine is shut down")
            if self._timer_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._timer_pool = ThreadPoolExecutor(
                    max_workers=4, thread_name_prefix="rtpu-timer-task"
                )
            return self._timer_pool

    def queue_wait_entry(self, name: str):
        """The wait entry blocking-queue-family consumers park on — the ONE
        authority for the __q_wait__ key format (paired with
        signal_queue_waiters; hand-built keys at park sites would silently
        strand waiters if the format ever moved)."""
        return self.wait_entry(f"__q_wait__:{name}")

    def signal_queue_waiters(self, name: str) -> None:
        """Wake queue-family waiters parked on `name` WITHOUT materializing
        a wait entry when nobody waits."""
        e = self._wait_entries.get(f"__q_wait__:{name}")
        if e is not None:
            e.signal(all_=True)

    def schedule_timeout(self, fn, delay: float):
        """Run `fn` ~`delay` seconds from now on the shared timer pool.
        Returns the wheel Timeout (cancellable until it fires)."""
        pool = self.timer_pool
        return self.timer.new_timeout(lambda: pool.submit(fn), delay)

    @property
    def events_pool(self):
        """SINGLE-worker pool delivering entry/eviction events
        (MapCache listeners etc.).  One worker on purpose: events for one
        object must arrive in mutation order (created before updated before
        removed), which a multi-worker pool cannot guarantee.  Deliveries
        are async so a mutator never runs user listeners while holding the
        record lock (the reference gets the same decoupling from Redis
        pubsub delivery)."""
        with self._locks_guard:
            if self._closed:
                raise RuntimeError("engine is shut down")
            if self._events_pool_ is None:
                from concurrent.futures import ThreadPoolExecutor

                self._events_pool_ = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="rtpu-events"
                )
            return self._events_pool_

    @property
    def _renewal_pool(self):
        """Dedicated pool for lease renewals.  Renewals are lease-CRITICAL:
        sharing a pool with arbitrary user work (MapWriter flushes,
        scheduled-task fires) would let a blocked writer starve renewals
        past lease expiry — two holders of a mutual-exclusion lock.
        Multiple workers for the same reason INTERNALLY: one renew() stuck
        on a contended record lock (held across a device sync or a
        migration serialize) must not delay every other lock's renewal
        tick past its lease."""
        with self._locks_guard:
            if self._closed:
                raise RuntimeError("engine is shut down")
            if self._renewal_pool_ is None:
                from concurrent.futures import ThreadPoolExecutor

                self._renewal_pool_ = ThreadPoolExecutor(
                    max_workers=4, thread_name_prefix="rtpu-renewal"
                )
            return self._renewal_pool_

    def start_renewal(self, name: str, holder: str, renew, interval: float) -> None:
        """Register a watchdog renewal for (lock name, holder) — the
        EXPIRATION_RENEWAL_MAP discipline of RedissonBaseLock.java:127-189:
        one renewal per (entry, holder) regardless of reentrancy; `renew()`
        returns True to keep renewing, False to stop."""
        key = (name, holder)

        def tick():
            # runs on the timer POOL (renew takes record locks and must not
            # block the wheel thread — schedule_timeout enforces the hop)
            try:
                keep = bool(renew())
            except Exception:  # noqa: BLE001 — a failing renew stops renewing
                keep = False
            with self._locks_guard:
                if key not in self._renewals or not keep or self._closed:
                    self._renewals.pop(key, None)
                    return
            nxt = self._schedule_renewal_tick(tick, interval)
            with self._locks_guard:
                if key in self._renewals:
                    self._renewals[key] = nxt
                else:
                    nxt.cancel()  # cancel_renewal raced the reschedule

        with self._locks_guard:
            if key in self._renewals:
                return  # reentrant re-acquire keeps the existing renewal
            self._renewals[key] = None  # claim the slot before scheduling
        first = self._schedule_renewal_tick(tick, interval)
        with self._locks_guard:
            if key in self._renewals:
                self._renewals[key] = first
            else:
                first.cancel()  # cancelled between claim and schedule

    def _schedule_renewal_tick(self, tick, interval: float):
        pool = self._renewal_pool
        return self.timer.new_timeout(lambda: pool.submit(tick), interval)

    def cancel_renewal(self, name: str, holder: Optional[str] = None) -> None:
        """Stop renewals for a lock (all holders when holder is None — the
        force_unlock path)."""
        with self._locks_guard:
            keys = [
                k
                for k in self._renewals
                if k[0] == name and (holder is None or k[1] == holder)
            ]
            for k in keys:
                t = self._renewals.pop(k)
                if t is not None:  # None = start_renewal's claim placeholder
                    t.cancel()

    # -- locking ------------------------------------------------------------

    @contextmanager
    def locked(self, name: str):
        with self._locks_guard:
            entry = self._record_locks.get(name)
            if entry is None:
                entry = self._record_locks[name] = [threading.RLock(), 0]
            entry[1] += 1
        try:
            with entry[0]:
                yield
        finally:
            with self._locks_guard:
                entry[1] -= 1
                if entry[1] == 0:
                    # nobody holds or waits: drop the registry entry (churny
                    # short-lived objects must not leak host memory)
                    self._record_locks.pop(name, None)

    def try_locked(self, name: str):
        """Non-blocking record lock: a held context manager, or None when
        some other thread holds the lock RIGHT NOW.  The residency demoter
        uses it so releasing cold arrays can never stall a serving path —
        a busy record simply stays HOT this sweep."""
        with self._locks_guard:
            entry = self._record_locks.get(name)
            if entry is None:
                entry = self._record_locks[name] = [threading.RLock(), 0]
            entry[1] += 1
        if not entry[0].acquire(blocking=False):
            with self._locks_guard:
                entry[1] -= 1
                if entry[1] == 0:
                    self._record_locks.pop(name, None)
            return None

        @contextmanager
        def _held():
            try:
                yield
            finally:
                entry[0].release()
                with self._locks_guard:
                    entry[1] -= 1
                    if entry[1] == 0:
                        self._record_locks.pop(name, None)

        return _held()

    @contextmanager
    def locked_many(self, names: Iterable[str]):
        """Acquire several record locks in sorted-name order (deadlock-free
        for concurrent multi-object ops like PFMERGE / BITOP)."""
        ordered = sorted(set(names))
        entries = []
        with self._locks_guard:
            for n in ordered:
                entry = self._record_locks.get(n)
                if entry is None:
                    entry = self._record_locks[n] = [threading.RLock(), 0]
                entry[1] += 1
                entries.append((n, entry))
        acquired = []
        try:
            for _n, entry in entries:
                entry[0].acquire()
                acquired.append(entry)
            yield
        finally:
            for entry in reversed(acquired):
                entry[0].release()
            with self._locks_guard:
                for n, entry in entries:
                    entry[1] -= 1
                    if entry[1] == 0:
                        self._record_locks.pop(n, None)

    # -- device-sharded placement (ISSUE 8) -----------------------------------

    def enable_placement(self, devices=None, n_devices: Optional[int] = None):
        """Map the 16384-slot table onto the local device mesh: every
        record created/installed from here on commits its device arrays to
        the device owning its slot, frames routed to different devices
        dispatch down per-device lanes (ioplane.LaneSet), and coalesced
        runs fuse PER DEVICE.  Returns the SlotPlacement (rebalanceable
        online via fenced slot handoffs — server/migration.rebalance_devices).
        """
        from redisson_tpu.core import ioplane
        from redisson_tpu.server.placement import SlotPlacement

        placement = SlotPlacement(devices=devices, n_devices=n_devices)
        with self._locks_guard:
            self.placement = placement
            self.lanes = ioplane.LaneSet(placement.devices)
        self.store.placement_hook = self._place_record
        return placement

    def device_for_name(self, name: str):
        """Owner device of `name`'s slot, or None with placement off."""
        p = self.placement
        return None if p is None else p.device_for_name(name)

    # -- tiered HBM residency (ISSUE 20) --------------------------------------

    def enable_residency(self, budget_bytes: Optional[int] = None,
                         spill_dir: Optional[str] = None,
                         sweep_interval: float = 0.0, **kw):
        """Arm the HOT/WARM/COLD residency plane for this engine's store:
        getters fault WARM/COLD records back in on first touch, and the
        (optional) background sweeper demotes least-recently-touched clean
        records whenever a device exceeds ``device-budget-bytes``.
        Idempotent; returns the ResidencyManager."""
        from redisson_tpu.core import residency as _residency

        if self.residency is None:
            self.residency = _residency.ResidencyManager(
                self, spill_dir=spill_dir, sweep_interval=sweep_interval,
                **kw,
            )
            self.store.residency = self.residency
        if budget_bytes is not None:
            _residency.set_device_budget_bytes(budget_bytes)
        return self.residency

    def disable_residency(self) -> None:
        """Detach the residency plane from this store.  Every WARM/COLD
        record is promoted back to HOT FIRST — once the getters stop
        routing to the manager nothing would ever fault a demoted record
        back in, and its (correct, host-side) state would read as empty."""
        mgr = self.residency
        if mgr is None:
            return
        with self.store._lock:
            demoted = [
                (n, r) for n, r in self.store._states.items()
                if r.tier != "hot"
            ]
        for name, rec in demoted:
            mgr.fault_in(name, rec)
        self.residency = None
        self.store.residency = None
        mgr.stop()

    def _place_record(self, name: str, rec) -> None:
        """DeviceStore placement hook: commit the record's single-device
        arrays to the slot's owner.  Multi-device (mesh-sharded) planes are
        never touched — the parallel/ layer owns their layout.  An array
        that merely sits on its owner (uncommitted: every new array starts
        on the default device) is committed there too, without a copy: a
        jitted program is compiled for where its operands are committed, so
        the default device's lane would otherwise run a set of programs of
        its own, which no warm-up made for the other lanes covers."""
        p = self.placement
        if p is None:
            return
        device = p.device_for_name(name)
        import jax

        for key, arr in list(rec.arrays.items()):
            devs = getattr(arr, "devices", None)
            if devs is not None:
                ds = devs()
                if len(ds) != 1 or (ds == {device} and arr.committed):
                    continue  # sharded plane, or already home
            elif not isinstance(arr, np.ndarray):
                continue  # host-side state (lists/dicts) never places
            rec.arrays[key] = jax.device_put(arr, device)

    @staticmethod
    def _move_record_to(rec, device) -> bool:
        """Commit a record's movable arrays to `device`; True iff anything
        actually hopped.  Sharded (multi-device) planes and host-side state
        never move; single-device jax arrays and numpy values do."""
        import jax

        changed = False
        for key, arr in list(rec.arrays.items()):
            devs = getattr(arr, "devices", None)
            if devs is None:
                if not isinstance(arr, np.ndarray):
                    continue
            else:
                ds = devs()
                if len(ds) != 1 or ds == {device}:
                    continue
            rec.arrays[key] = jax.device_put(arr, device)
            changed = True
        return changed

    def move_slots_records(self, targets: Dict[int, int],
                           epoch: Optional[int] = None,
                           skip_stale: bool = False) -> Tuple[int, int]:
        """BULK fenced slot -> device handoff: fence + repoint every slot
        in ``targets`` ({slot: device_index}), then move the affected
        records in ONE store scan (a full 8->4 rebalance repoints ~8192
        owners; per-slot scans would be O(slots x keys)).  Each record
        moves under its record lock: an in-flight dispatch holds the lock
        and finishes on the old device; the next dispatch finds the plane
        committed to the new one.  Returns (records_moved, stale_slots);
        a stale coordinator's epoch raises PlacementStaleEpoch unless
        ``skip_stale`` (the resume path) counts it instead."""
        from redisson_tpu.server.placement import PlacementStaleEpoch
        from redisson_tpu.utils.crc16 import calc_slot

        p = self.placement
        if p is None:
            raise RuntimeError("placement is not enabled on this engine")
        fenced: Dict[int, int] = {}
        stale = 0
        for slot, dev_index in targets.items():
            try:
                p.assign(slot, dev_index, epoch)  # fences + repoints routing
                fenced[slot] = dev_index
            except PlacementStaleEpoch:
                if not skip_stale:
                    raise
                stale += 1  # a newer rebalance owns this slot now
        if not fenced:
            return 0, stale
        moving = [
            (n, fenced[s])
            for n in self.store.keys()
            for s in (calc_slot(n.encode()),)
            if s in fenced
        ]
        moved = 0
        for name, dev_index in moving:
            device = p.devices[dev_index]
            with self.locked(name):
                rec = self.store.get_unguarded(name)
                if rec is not None and self._move_record_to(rec, device):
                    moved += 1
        return moved, stale

    def move_slot_records(self, slot: int, dev_index: int,
                          epoch: Optional[int] = None) -> int:
        """One fenced slot -> device handoff (CLUSTER DEVMOVE's unit);
        see move_slots_records for the bulk form and the contract."""
        moved, _stale = self.move_slots_records({slot: dev_index}, epoch)
        return moved

    # -- kernel warm pool ----------------------------------------------------

    @property
    def warm_pool(self):
        """The process-global kernel warm-pool (core/warmpool.py)."""
        from redisson_tpu.core import warmpool

        return warmpool.POOL

    def prewarm(self, names=None, buckets=(0,), all_devices: Optional[bool] = None) -> int:
        """Precompile the hot kernels of live records at the given batch
        buckets (TasksRunnerService warm-pool analog) — run at boot or
        before a timed serving phase, never on the hot path.  Returns the
        number of programs actually compiled/loaded this call.

        With placement enabled (device-sharded serving) the default warms
        every record's geometry on EVERY local device — jit specializes per
        device placement, so a slot handoff onto a cold device would
        otherwise pay a first-dispatch compile mid-serving.  Pass
        ``all_devices=False`` to warm only each record's current owner."""
        from redisson_tpu.core import warmpool

        if all_devices is None:
            all_devices = self.placement is not None
        return warmpool.prewarm_store(
            self, names=names, buckets=buckets,
            devices=(self.placement.devices
                     if (all_devices and self.placement is not None) else None),
        )

    # -- overlapped device I/O ----------------------------------------------

    def staging_pool(self, device=None):
        """The engine's double-buffered host staging pool — or None when the
        overlap plane is off (--no-overlap: serial A/B reference) or the
        backend zero-copy-aliases host memory (CPU jax), where slot reuse
        would corrupt a staged value (ioplane.staging_reuse_safe).

        With placement enabled and a `device` given, the DEVICE'S lane pool
        is returned instead of the shared one: each device's uploads double-
        buffer independently, so two lanes' flush packing never contends on
        one slot pair (the per-chip lane discipline, ISSUE 8)."""
        from redisson_tpu.core import ioplane

        if not (ioplane.overlap_enabled() and ioplane.staging_reuse_safe()):
            return None
        if device is not None and self.lanes is not None:
            lane = self.lanes.lane(device)
            # interactive device stream (ISSUE 18): the holding thread's
            # occupancy marks itself in TLS, so its flush packing stages
            # through the lane's interactive slot instead of contending on
            # the bulk stream's double buffer
            if ioplane.current_stream() == "interactive":
                return lane.ipool
            return lane.pool
        return self.staging

    # -- key packing --------------------------------------------------------

    @staticmethod
    def is_int_batch(objs) -> bool:
        if isinstance(objs, np.ndarray) and objs.dtype.kind in "iu":
            return True
        return False

    def pack_keys(self, objs, codec: Optional[Codec],
                  cache_hot: bool = False) -> Tuple[str, tuple, int]:
        """Normalize a key batch for the hash kernels.

        Returns (kind, padded_arrays, n_valid):
          kind="u64":   arrays = ONE (2, B) uint32 buffer (rows lo, hi) — a
                        single contiguous host->device transfer per flush
                        (kernels.pack_rows bandwidth note)
          kind="bytes": arrays = (words[W,N], nbytes[N]) padded on both axes

        Fast path: numpy integer arrays are hashed as int64 directly (no codec
        round-trip) — the vectorized analog of the reference's
        codec-encode-then-hash (RedissonBloomFilter.java:90-97), which this
        deliberately skips for machine-width keys.
        """
        codec = codec or self.default_codec
        if self.is_int_batch(objs):
            arr = np.ascontiguousarray(objs, dtype=np.int64)
            n = arr.shape[0]
            b = K.bucket_size(max(1, n))

            def build():
                lo, hi = H.int_keys_to_u32_pair(arr)
                return K.pack_rows(lo, hi, size=b, pool=self.staging_pool())

            if cache_hot and n >= 4096:
                # hot-set reuse, READ paths only (kernels.cached_staged): a
                # serving loop re-probing the same working set skips the
                # pack and the h2d upload entirely
                return "u64", K.cached_staged(build, arr, extra=b"u64%d" % b), n
            return "u64", build(), n
        if isinstance(objs, (bytes, str, int, float)) or not isinstance(objs, (list, tuple, np.ndarray)):
            objs = [objs]
        encoded = [o if isinstance(o, bytes) else codec.encode(o) for o in objs]
        n = len(encoded)
        words, nbytes = H.pack_keys(encoded)
        b = K.pow2_bucket(max(1, n))
        w = max(4, K.pow2_bucket(max(1, words.shape[0]), minimum=4))
        words = K.stage(K.pad_to(K.pad_to(words, b, axis=1), w, axis=0))
        nbytes = K.stage(K.pad_to(nbytes, b))
        return "bytes", (words, nbytes), n

    # -- lifecycle ----------------------------------------------------------

    def shutdown(self):
        with self._locks_guard:
            self._closed = True
            eviction, self._eviction = self._eviction, None
            timer, self._timer = self._timer, None
            pool, self._timer_pool = self._timer_pool, None
            rpool, self._renewal_pool_ = self._renewal_pool_, None
            epool, self._events_pool_ = self._events_pool_, None
            renewals = list(self._renewals.values())
            self._renewals.clear()
        for t in renewals:
            if t is not None:
                t.cancel()
        if timer is not None:
            timer.stop()
        for p in (pool, rpool, epool):
            if p is not None:
                p.shutdown(wait=False, cancel_futures=True)
        if eviction is not None:
            eviction.close()
        if self.residency is not None:
            self.residency.stop()
            self.residency = None
            self.store.residency = None
        self.pubsub.close()
        self.staging.clear()
        if self.lanes is not None:
            self.lanes.clear()
        self.store.flushall()


def require(rec: Optional[StateRecord], name: str) -> StateRecord:
    if rec is None:
        raise KeyError(f"object '{name}' does not exist")
    return rec

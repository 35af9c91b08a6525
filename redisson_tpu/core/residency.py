"""Tiered HBM residency: HOT on device, WARM in host RAM, COLD on disk
(ISSUE 20 tentpole).

The HBM capacity plane stopped at enforcement — ``ftvec-device-budget``
REFUSES over-budget growth (PR 15) and the per-device byte ledgers MEASURE
residency (PR 19) — but nothing managed it: an over-budget tenant got a
``VectorBudgetError``, not service.  This module treats HBM as a **cache**
over host RAM and checkpoint-backed storage, the working-set-tiering shape
every serving stack leans on (KV-cache offload, parameter paging):

  * **HOT**  — device arrays live in HBM (today's only state);
  * **WARM** — the record's device arrays are RELEASED; a host-RAM numpy
    mirror (``rec.stash``) holds the exact bytes.  Promotion is ONE packed
    H2D through the owner lane's staging path (``scatter_host_arrays``) —
    same geometry, same device, so the warm kernel pool re-hits with ZERO
    rebuilds;
  * **COLD** — the host mirror is spilled to a checkpoint-container file
    (MAGIC + CRC trailer, ``checkpoint.read_verified`` reads it back) and
    dropped; promotion adds exactly one verified generation read.

Fault-in on first touch: the DeviceStore getters fire
``plane.on_record_access`` AFTER releasing the store lock; a WARM/COLD
record promotes synchronously before the caller sees it, so handlers never
observe a tier.  Demotion is safe by construction — only clean state
demotes (dirty probes pin HOT; vector banks with pending rows register
one), fenced/migrating slots never demote (``fence_check``), records
touched within ``min_idle_s`` never demote (the touch clock closes the
get-then-read race), and sharded / host-only records are simply ineligible.

Arming follows the chaos-hook discipline (net/client.py ``_fault_plane``,
observe/trace.py ``_tracer``): ``_tier_plane`` is the ONE module global
every store-getter site loads — ``None`` (the default) costs one load plus
an ``is None`` branch and replies stay bit-identical; armed, the plane
routes to the store's own :class:`ResidencyManager`.  The plane arms only
when a manager is actually installed (``enable_residency`` /
``set_tier(True)``) — armed-with-no-manager would charge every getter a
method call plus two getattrs for nothing, a measured ~70% p99 hit on the
interactive QoS leg.  ``RTPU_NO_TIER=1`` is the hard kill-switch:
``set_tier(True)`` becomes a no-op, so even ``CONFIG SET
residency-enabled yes`` cannot arm the guard.

Lock discipline (the dispatch path's order is lane -> record):

  * promotion runs WITHOUT the store lock (getters fire the hook after
    release), takes the record lock first, then the per-record transition
    lock, then TRIES the owner lane's gate with a short timeout — a
    dispatch holding the gate while waiting on this record's lock would
    otherwise ABBA; on timeout the upload proceeds gateless (contention,
    not correctness: ``device_put`` needs no gate);
  * demotion try-acquires the record lock (never blocks a serving path)
    and snapshots + swaps arrays entirely under it, so a concurrent
    wholesale plane replacement can never be clobbered.
"""
from __future__ import annotations

import collections
import itertools
import os
import struct
import threading
import time
import zlib
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from redisson_tpu.core.ioplane import _device_id_of

# interned tier constants: guard sites compare with ``is``
HOT = "hot"
WARM = "warm"
COLD = "cold"

_SPILL_FMT = 1

# -- per-device byte budget (generalizes ftvec-device-budget) ------------------

DEVICE_BUDGET_BYTES = int(os.environ.get("RTPU_DEVICE_BUDGET_BYTES", "0"))
# per-DEVICE budget over ALL record kinds' device bytes (0 = unlimited) —
# the ledger PR 19 measures is what this bounds; the sweeper demotes the
# least-recently-touched clean records until each device fits.


def set_device_budget_bytes(value: int) -> int:
    """Set the per-device byte budget (0 = unlimited); returns previous."""
    global DEVICE_BUDGET_BYTES
    prev, DEVICE_BUDGET_BYTES = DEVICE_BUDGET_BYTES, max(0, int(value))
    return prev


# -- the disarm switch (RTPU_NO_TIER) ------------------------------------------


class _TierPlane:
    """Router the armed store-getter sites call: resolves the touched
    store's OWN manager (multiple engines in one test process must never
    cross-wire), so the module global stays a single is-None guard."""

    def on_record_access(self, store, name: str, rec) -> None:
        if getattr(_tls, "bypass", False):
            return  # census / serializer scan: observe, never promote
        mgr = getattr(store, "residency", None)
        if mgr is not None:
            mgr.on_access(name, rec)


_PLANE = _TierPlane()

# THE guard every getter site loads: None = disarmed (zero-cost).  Same
# shape as observe/trace.py `_tracer` / net/client.py `_fault_plane`.
# Starts disarmed — enable_residency()/set_tier(True) arms it when a
# manager exists to route to; RTPU_NO_TIER=1 pins it disarmed for good.
_NO_TIER = os.environ.get("RTPU_NO_TIER", "") in ("1", "true", "yes")
_tier_plane: Optional[_TierPlane] = None

_tls = threading.local()


def tier_enabled() -> bool:
    return _tier_plane is not None


def set_tier(on: bool) -> bool:
    """Arm/disarm the residency plane; returns the previous armed state
    (callers restore it — the A/B discipline of RTPU_NO_QOS).  Under
    RTPU_NO_TIER=1 arming is refused: the env var is the operator's
    bit-identity guarantee and must beat any in-process caller."""
    global _tier_plane
    prev = _tier_plane is not None
    _tier_plane = _PLANE if (on and not _NO_TIER) else None
    return prev


class no_promote:
    """Context: observe records without faulting them in — the census /
    serializer discipline (a metrics scrape or checkpoint cut walking every
    record must never drag the whole WARM set back into HBM)."""

    def __enter__(self):
        self._prev = getattr(_tls, "bypass", False)
        _tls.bypass = True
        return self

    def __exit__(self, *exc):
        _tls.bypass = self._prev
        return False


# -- residency-aware host views (work disarmed too) ----------------------------


def record_host_arrays(rec) -> Dict[str, Any]:
    """Host-side numpy view of a record's named arrays REGARDLESS of tier —
    the one seam checkpoint/replication/migration serializers read through,
    so a WARM/COLD record checkpoints and ships without promotion."""
    stash = getattr(rec, "stash", None)
    if stash is not None:
        return dict(stash)
    path = getattr(rec, "cold_path", None)
    if path is not None:
        return load_spill(path)
    import numpy as np

    return {k: np.asarray(v) for k, v in rec.arrays.items()}


def record_device_bytes(rec) -> int:
    """HBM bytes this record holds RIGHT NOW (0 for WARM/COLD)."""
    total = 0
    for a in rec.arrays.values():
        n = getattr(a, "nbytes", None)
        if n is not None:
            total += int(n)
    return total


def _host_bytes(arrays: Dict[str, Any]) -> int:
    return sum(int(getattr(a, "nbytes", 0)) for a in arrays.values())


# -- COLD spill container (checkpoint format: MAGIC + pickle + CRC) ------------


def write_spill(path: str, arrays: Dict[str, Any]) -> int:
    """One record's host arrays as a verified container file — the same
    MAGIC/CRC-trailer shape as checkpoints, read back by ``load_spill``
    through ``checkpoint.read_verified`` (COLD promotion = exactly one
    checkpoint-generation read).  Returns the payload byte count."""
    import pickle

    import numpy as np

    from redisson_tpu.core import checkpoint as ckpt

    payload = {
        "format": _SPILL_FMT,
        "arrays": {k: np.asarray(v) for k, v in arrays.items()},
    }
    body = ckpt.MAGIC + pickle.dumps(payload, protocol=4)
    data = body + ckpt.TRAILER_MAGIC + struct.pack(
        ">I", zlib.crc32(body) & 0xFFFFFFFF
    )
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return len(data)


def load_spill(path: str) -> Dict[str, Any]:
    """Read + CRC-verify one spill file back to host arrays (raises
    ``CheckpointCorruptError`` on a torn/forged file)."""
    from redisson_tpu.core import checkpoint as ckpt

    payload = ckpt.read_verified(path)
    if not isinstance(payload, dict) or payload.get("format") != _SPILL_FMT:
        raise ckpt.CheckpointCorruptError(f"not a residency spill: {path!r}")
    return dict(payload["arrays"])


# -- the manager ---------------------------------------------------------------


class ResidencyManager:
    """Per-engine tier manager: touch clock, fault-in, clock/LRU demotion
    against the per-device byte budget, COLD spill, and the census rows
    the ``CLUSTER RESIDENCY`` verb / METRICS multi-gauge render."""

    def __init__(self, engine, spill_dir: Optional[str] = None,
                 min_idle_s: float = 0.25, cold_after_s: float = 0.0,
                 sweep_interval: float = 0.0, gate_timeout_s: float = 0.25):
        self.engine = engine
        self._spill_dir = spill_dir
        self._owns_spill_dir = False
        self.min_idle_s = float(min_idle_s)
        # WARM records idle longer than this spill COLD (0 = never auto-COLD)
        self.cold_after_s = float(cold_after_s)
        self.gate_timeout_s = float(gate_timeout_s)
        # touch clock: name -> (monotonic seq, wall-ish monotonic seconds);
        # plain dict writes are GIL-atomic — the hot getter path takes no lock
        self._clock = itertools.count(1)
        self._touch: Dict[str, Tuple[int, float]] = {}
        # per-record transition locks (promote/demote mutual exclusion)
        self._tlocks: Dict[str, threading.Lock] = {}
        self._tguard = threading.Lock()
        # demotion pins: probes that flag a record DIRTY (pending vector
        # rows, mid-2PC state, ...) — dirty records pin HOT.  The vector
        # plane's pending-rows probe is always on: a bank mid-accumulation
        # must not demote between set_row and flush.
        self.pin_probes: List[Callable[[str, Any], bool]] = [
            self._vector_pending_probe,
        ]
        # slot-fence probe (server wires migrating/importing/recovering):
        # fenced slots never demote — their records are mid-handoff
        self.fence_check: Callable[[str], bool] = lambda name: False
        # counters (census + METRICS rows)
        self.promotions = 0
        self.demotions_warm = 0
        self.demotions_cold = 0
        self.cold_loads = 0
        self.fault_in_ms_total = 0.0
        self.fault_in_ms_max = 0.0
        # bounded per-promotion duration ring — percentile source for the
        # bench gate (config8_fault_in_p99_ms); a deque so an overcommitted
        # long run can't grow it unbounded
        self.fault_in_samples: Deque[float] = collections.deque(maxlen=4096)
        self._sweeper: Optional[threading.Thread] = None
        self._stop = threading.Event()
        if sweep_interval > 0:
            self.start_sweeper(sweep_interval)

    # -- plumbing -------------------------------------------------------------

    def _tlock(self, name: str) -> threading.Lock:
        with self._tguard:
            lk = self._tlocks.get(name)
            if lk is None:
                lk = self._tlocks[name] = threading.Lock()
            return lk

    def spill_dir(self) -> str:
        if self._spill_dir is None:
            import tempfile

            self._spill_dir = tempfile.mkdtemp(prefix="rtpu-residency-")
            self._owns_spill_dir = True
        else:
            os.makedirs(self._spill_dir, exist_ok=True)
        return self._spill_dir

    def _spill_path(self, name: str) -> str:
        import hashlib

        h = hashlib.sha256(name.encode()).hexdigest()[:32]
        return os.path.join(self.spill_dir(), f"{h}.spill")

    def _vector_pending_probe(self, name: str, rec) -> bool:
        if rec.kind not in ("vector_bank",):
            return False
        from redisson_tpu.services.vector import bank_has_pending

        return bank_has_pending(self.engine.store, name)

    def touch_age(self, name: str) -> float:
        t = self._touch.get(name)
        return float("inf") if t is None else time.monotonic() - t[1]

    # -- the getter hook (armed path) -----------------------------------------

    def on_access(self, name: str, rec) -> None:
        self._touch[name] = (next(self._clock), time.monotonic())
        if rec.tier is not HOT and rec.tier != HOT:
            self.fault_in(name, rec)

    # -- fault-in (promotion) -------------------------------------------------

    def fault_in(self, name: str, rec) -> None:
        """Promote a WARM/COLD record back to HOT: one packed H2D through
        the owner lane's staging path (COLD first pays one verified spill
        read).  Synchronous — the touching command proceeds only once the
        arrays are device-resident, so its QoS admission window charges the
        fault-in by construction."""
        eng = self.engine
        t0 = time.monotonic()
        from_tier = rec.tier
        with eng.locked(name):
            with self._tlock(name):
                if rec.tier == HOT:
                    return  # raced with another promoter
                stash = rec.stash
                if stash is None:
                    path = rec.cold_path
                    if path is None:
                        # nothing to restore (empty record demoted): just flip
                        rec.tier = HOT
                        return
                    stash = load_spill(path)
                    self.cold_loads += 1
                nbytes = _host_bytes(stash)
                device = eng.device_for_name(name)
                self._upload(name, rec, stash, device)
                rec.stash = None
                if rec.cold_path is not None:
                    try:
                        os.unlink(rec.cold_path)
                    except OSError:
                        pass
                    rec.cold_path = None
                rec.tier = HOT
                self.promotions += 1
        dt_ms = (time.monotonic() - t0) * 1e3
        self.fault_in_ms_total += dt_ms
        if dt_ms > self.fault_in_ms_max:
            self.fault_in_ms_max = dt_ms
        self.fault_in_samples.append(dt_ms)
        from redisson_tpu.observe import trace as _obs

        if _obs._tracer is not None:
            tr = _obs.current_trace()
            if tr is not None:
                from redisson_tpu.core.ioplane import current_stream

                tr.add_span(
                    "promote", t0, time.monotonic(), record=name,
                    tier=from_tier, bytes=nbytes,
                    stream=current_stream() or "bulk",
                )

    def _upload(self, name: str, rec, stash: Dict[str, Any], device) -> None:
        """ONE packed H2D of the stash onto `device` — the owner lane's
        gate is TRIED (not taken) so a dispatch already holding it while
        waiting on this record's lock can never ABBA; a promote fired from
        INSIDE a lane occupancy (current_stream set) already owns the gate
        and skips it."""
        from redisson_tpu.core import ioplane

        lane = None
        if device is not None and self.engine.lanes is not None:
            try:
                lane = self.engine.lanes.lane(device)
            except Exception:  # noqa: BLE001 — unknown device: gateless
                lane = None
        gate = None
        if lane is not None and ioplane.current_stream() is None:
            if lane._gate.acquire(timeout=self.gate_timeout_s):
                gate = lane._gate
        try:
            pool = self.engine.staging_pool(device)
            try:
                arrays = ioplane.scatter_host_arrays(stash, device, pool=pool)
            except TypeError:  # a dtype the byte-stream packing cannot
                import jax     # round-trip: per-array upload, same bytes

                arrays = {
                    k: (jax.device_put(v, device) if device is not None
                        else jax.device_put(v))
                    for k, v in stash.items()
                }
            rec.arrays.update(arrays)
        finally:
            if gate is not None:
                gate.release()

    # -- demotion -------------------------------------------------------------

    def _demotable(self, name: str, rec) -> bool:
        """Clean, single-device, unfenced, idle: the safe-by-construction
        predicate.  Anything ambiguous pins HOT."""
        if rec.tier != HOT or not rec.arrays or rec.expired():
            return False
        if self.touch_age(name) < self.min_idle_s:
            return False  # touched too recently: closes the get-read race
        if self.fence_check(name):
            return False  # migrating/importing/recovering slot
        for probe in self.pin_probes:
            try:
                if probe(name, rec):
                    return False  # dirty (e.g. pending vector rows)
            except Exception:  # noqa: BLE001 — a broken probe pins, never
                return False   # unpins: fail safe
        # host-side numpy planes have nothing to release; mesh-sharded
        # planes belong to parallel/ — only single-device arrays demote
        return all(
            _device_id_of(a) is not None for a in rec.arrays.values()
        )

    def demote(self, name: str, cold: bool = False,
               force: bool = False) -> bool:
        """Release one record's device arrays to its host stash (WARM), or
        spill the stash to disk (COLD).  Never blocks a serving path: the
        record lock is TRY-acquired; a busy record just stays HOT.  Returns
        True iff the tier actually changed."""
        eng = self.engine
        ctx = eng.try_locked(name)
        if ctx is None:
            return False
        with ctx:
            with self._tlock(name):
                rec = eng.store.get_unguarded(name)
                if rec is None:
                    return False
                if rec.tier == HOT:
                    if not force and not self._demotable(name, rec):
                        return False
                    if force and (not rec.arrays or self.fence_check(name)):
                        return False
                    import numpy as np

                    stash = {
                        k: np.asarray(v) for k, v in rec.arrays.items()
                    }
                    dev = next(
                        (d for d in map(_device_id_of, rec.arrays.values())
                         if d is not None), -1,
                    )
                    rec.arrays.clear()
                    rec.stash = stash
                    rec.stash_dev = dev
                    rec.tier = WARM
                    self.demotions_warm += 1
                    if not cold:
                        return True
                if cold and rec.tier == WARM and rec.stash is not None:
                    path = self._spill_path(name)
                    write_spill(path, rec.stash)
                    rec.cold_path = path
                    rec.cold_bytes = _host_bytes(rec.stash)
                    rec.stash = None
                    rec.tier = COLD
                    self.demotions_cold += 1
                    return True
        return False

    # -- pressure / budget ----------------------------------------------------

    def hot_bytes_by_device(self) -> Dict[int, int]:
        """HBM bytes by device id over every live record — the PR 19
        ledger scan, reused as the demotion pressure signal."""
        out: Dict[int, int] = {}
        with no_promote():
            for _kind, rec in self.engine.store.census_records():
                for a in rec.arrays.values():
                    d = _device_id_of(a)
                    if d is not None:
                        out[d] = out.get(d, 0) + int(a.nbytes)
        return out

    def _candidates_on(self, dev_id: int, exclude=()) -> List[Tuple[float, str, int]]:
        """(idle_age, name, device_bytes) of demotable records whose arrays
        live on `dev_id`, coldest (longest-idle) first."""
        cands: List[Tuple[float, str, int]] = []
        with self.engine.store._lock:
            items = list(self.engine.store._states.items())
        for name, rec in items:
            if name in exclude or rec.expired() or rec.tier != HOT:
                continue
            nbytes = 0
            on_dev = False
            for a in rec.arrays.values():
                if _device_id_of(a) == dev_id:
                    on_dev = True
                    nbytes += int(a.nbytes)
            if on_dev and self._demotable(name, rec):
                cands.append((self.touch_age(name), name, nbytes))
        cands.sort(reverse=True)  # longest idle first
        return cands

    def make_room(self, dev_id: int, need_bytes: int, exclude=()) -> int:
        """Demote longest-idle clean records off `dev_id` until
        `need_bytes` are freed (or candidates run out).  Returns freed."""
        freed = 0
        for _age, name, nbytes in self._candidates_on(dev_id, exclude):
            if freed >= need_bytes:
                break
            if self.demote(name):
                freed += nbytes
        return freed

    def admit_device_alloc(self, device, delta_bytes: int,
                           exclude=()) -> None:
        """Growth admission against ``device-budget-bytes``: demote colder
        records first, refuse (VectorBudgetError) only as the LAST resort
        — the ISSUE 20 bugfix for unsharded bank growth."""
        budget = DEVICE_BUDGET_BYTES
        if not budget or delta_bytes <= 0:
            return
        dev_id = getattr(device, "id", 0) if device is not None else 0
        hot = self.hot_bytes_by_device().get(dev_id, 0)
        over = hot + delta_bytes - budget
        if over <= 0:
            return
        freed = self.make_room(dev_id, over, exclude=exclude)
        if freed < over:
            from redisson_tpu.services.vector import VectorBudgetError

            raise VectorBudgetError(
                f"allocating {delta_bytes} bytes on device {dev_id} exceeds "
                f"the {budget}-byte device-budget-bytes and only {freed} of "
                f"the needed {over} bytes were demotable (the rest is hot, "
                f"dirty, or fenced)"
            )

    # -- sweeper --------------------------------------------------------------

    def sweep(self) -> Dict[str, int]:
        """One control-loop pass: (1) demote each over-budget device back
        under ``device-budget-bytes``; (2) spill long-idle WARM records
        COLD; (3) GC spill files of deleted records."""
        out = {"demoted": 0, "colded": 0, "freed_bytes": 0}
        budget = DEVICE_BUDGET_BYTES
        if budget:
            for dev_id, hot in self.hot_bytes_by_device().items():
                if hot > budget:
                    before = self.demotions_warm
                    out["freed_bytes"] += self.make_room(dev_id, hot - budget)
                    out["demoted"] += self.demotions_warm - before
        if self.cold_after_s > 0:
            with self.engine.store._lock:
                warm = [
                    n for n, r in self.engine.store._states.items()
                    if r.tier == WARM and not r.expired()
                ]
            for name in warm:
                if self.touch_age(name) >= self.cold_after_s:
                    if self.demote(name, cold=True):
                        out["colded"] += 1
        self._gc_spills()
        return out

    def _gc_spills(self) -> None:
        if self._spill_dir is None or not os.path.isdir(self._spill_dir):
            return
        with self.engine.store._lock:
            live = {
                r.cold_path for r in self.engine.store._states.values()
                if r.cold_path is not None
            }
        for fn in os.listdir(self._spill_dir):
            if not fn.endswith(".spill"):
                continue
            path = os.path.join(self._spill_dir, fn)
            if path not in live:
                try:
                    os.unlink(path)
                except OSError:
                    pass

    def start_sweeper(self, interval: float) -> None:
        if self._sweeper is not None:
            return
        self._sweep_interval = float(interval)

        def _run():
            while not self._stop.wait(self._sweep_interval):
                try:
                    self.sweep()
                except Exception:  # noqa: BLE001 — sweep must never die
                    pass

        self._sweeper = threading.Thread(
            target=_run, name="rtpu-residency", daemon=True
        )
        self._sweeper.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._sweeper
        if t is not None and t.is_alive():
            t.join(timeout=2.0)
        self._sweeper = None
        if self._owns_spill_dir and self._spill_dir is not None:
            import shutil

            shutil.rmtree(self._spill_dir, ignore_errors=True)
            self._spill_dir = None
            self._owns_spill_dir = False

    # -- census / observability -----------------------------------------------

    def census(self) -> Dict[str, float]:
        """Per-device per-tier byte rows (nonzero only — drain-to-absence
        on DEL/DROPINDEX) plus the monotonic counters."""
        hot: Dict[int, int] = {}
        warm: Dict[int, int] = {}
        cold: Dict[int, int] = {}
        with self.engine.store._lock:
            items = list(self.engine.store._states.items())
        with no_promote():
            for _name, rec in items:
                if rec.expired():
                    continue
                if rec.tier == WARM and rec.stash is not None:
                    d = rec.stash_dev
                    warm[d] = warm.get(d, 0) + _host_bytes(rec.stash)
                elif rec.tier == COLD:
                    d = rec.stash_dev
                    cold[d] = cold.get(d, 0) + int(rec.cold_bytes)
                else:
                    for a in rec.arrays.values():
                        d = _device_id_of(a)
                        if d is not None:
                            hot[d] = hot.get(d, 0) + int(a.nbytes)
        rows: Dict[str, float] = {}
        for tier, per in (("hot", hot), ("warm", warm), ("cold", cold)):
            for d, n in sorted(per.items()):
                if n:
                    rows[f"residency_bytes_dev{d}_{tier}"] = float(n)
        rows["residency_promotions"] = float(self.promotions)
        rows["residency_demotions_warm"] = float(self.demotions_warm)
        rows["residency_demotions_cold"] = float(self.demotions_cold)
        rows["residency_cold_loads"] = float(self.cold_loads)
        rows["residency_fault_in_ms_total"] = round(self.fault_in_ms_total, 3)
        rows["residency_fault_in_ms_max"] = round(self.fault_in_ms_max, 3)
        return rows

    def tier_of(self, name: str) -> Optional[str]:
        with no_promote():
            rec = self.engine.store.get_unguarded(name)
        return None if rec is None else rec.tier

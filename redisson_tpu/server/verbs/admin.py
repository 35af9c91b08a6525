"""Node admin/info, replication, checkpoint, script/function verbs (redisnode + RScript/RFunction surface).

Split from server/registry.py (round 5, no behavior change): one module per
verb family, shared preludes in verbs/common.py so numkeys/syntax validation
cannot diverge between families again.
"""

import threading
import time

from redisson_tpu.net.resp import RespError
from redisson_tpu.server.registry import register, _s, _int
from redisson_tpu.server.verbs.collections import cmd_lmpop, cmd_zmpop
from redisson_tpu.server.verbs.common import _block_loop, _exec_tls, _glob_match

# -- admin / node info (redisnode/* surface) ---------------------------------

@register("TIME")
def cmd_time(server, ctx, args):
    t = time.time()
    return [str(int(t)).encode(), str(int((t % 1) * 1e6)).encode()]


@register("INFO")
def cmd_info(server, ctx, args):
    """INFO [section] — the default sections, or one named section.
    ``INFO commandstats`` (ISSUE 12 satellite) renders per-verb
    calls/usec/usec_per_call from the MetricsRegistry command timers."""
    if args:
        section = _s(args[0]).lower()
        if section == "commandstats":
            return server.commandstats_text().encode()
        if section in ("all", "everything"):
            return (server.info_text() + server.commandstats_text()).encode()
    return server.info_text().encode()


@register("MEMORY")
def cmd_memory(server, ctx, args):
    sub = bytes(args[0]).upper() if args else b""
    if sub == b"USAGE":
        rec = server.engine.store.get(_s(args[1]))
        if rec is None:
            return None
        total = 0
        for arr in rec.arrays.values():
            total += int(getattr(arr, "nbytes", 0) or 0)
        import sys

        if rec.host is not None:
            total += sys.getsizeof(rec.host)
        return total
    if sub == b"STATS":
        return [b"keys.count", len(server.engine.store)]
    return "+OK"


@register("CLUSTER")
def cmd_cluster(server, ctx, args):
    sub = bytes(args[0]).upper() if args else b""
    if sub == b"SLOTS":
        return server.cluster_slots()
    if sub == b"MYID":
        return server.node_id.encode()
    if sub == b"INFO":
        state = "ok" if server.cluster_view else "ok"
        return f"cluster_enabled:{1 if server.cluster_view else 0}\r\ncluster_state:{state}\r\n".encode()
    if sub == b"SETVIEW":
        # SETVIEW [TOKEN <n>] <from> <to> <host> <port> <node_id> ...
        # (5-tuples) — the topology/launcher (harness.ClusterRunner,
        # server/monitor.py) installs the slot map on every node; the
        # reference's analog is each node's view from CLUSTER NODES gossip.
        # TOKEN carries the writing coordinator's FENCING token (its
        # FencedLock leadership token): a view stamped with a LOWER token
        # than the last accepted one is a stale ex-leader's late write and
        # is rejected — the fencing discipline that makes coordinator HA
        # safe (a paused leader resuming after its lease lapsed cannot
        # clobber its successor's topology).
        rest = args[1:]
        token = None
        if rest and bytes(rest[0]).upper() == b"TOKEN":
            token = _int(rest[1])
            rest = rest[2:]
        if len(rest) % 5 != 0:
            raise RespError("ERR SETVIEW expects 5-tuples")
        if token is not None:
            if token < server.view_epoch:
                raise RespError(
                    f"STALEVIEW token {token} < accepted epoch {server.view_epoch}"
                )
            server.view_epoch = token
        view = []
        for i in range(0, len(rest), 5):
            view.append(
                (
                    _int(rest[i]),
                    _int(rest[i + 1]),
                    _s(rest[i + 2]),
                    _int(rest[i + 3]),
                    _s(rest[i + 4]),
                )
            )
        server.cluster_view = view
        return "+OK"
    if sub == b"RESET":
        server.cluster_view = []
        return "+OK"
    # -- live slot migration (MIGRATING/IMPORTING window + drain) ------------
    if sub == b"SETSLOT":
        # SETSLOT <slot> MIGRATING <host:port> | IMPORTING <host:port> |
        #         STABLE | NODE <host:port> <node_id>   [EPOCH <n>]
        # EPOCH is the journaled coordinator's per-migration fencing token
        # (server.fence_slot_epoch): re-issue with the SAME epoch is the
        # idempotent resume path; a LOWER epoch is a stale coordinator and
        # replies STALEEPOCH before any state changes.
        slot = _int(args[1])
        mode = bytes(args[2]).upper()
        rest = list(args[3:])
        epoch = None
        if len(rest) >= 2 and bytes(rest[-2]).upper() == b"EPOCH":
            epoch = _int(rest[-1])
            rest = rest[:-2]
        server.fence_slot_epoch(slot, epoch)
        if mode == b"MIGRATING":
            server.set_slot_migrating(slot, _s(rest[0]), epoch)
            return "+OK"
        if mode == b"IMPORTING":
            server.set_slot_importing(slot, _s(rest[0]))
            return "+OK"
        if mode == b"STABLE":
            server.set_slot_stable(slot, epoch)
            return "+OK"
        if mode == b"NODE":
            # finalize locally: point the slot at its new owner in this
            # node's view and clear the window state (the orchestrator also
            # pushes a full SETVIEW; NODE keeps single-node finalization
            # correct even before that lands)
            addr, nid = _s(rest[0]), _s(rest[1])
            host, port = addr.rsplit(":", 1)
            new_view = []
            for lo, hi, h, p, vnid in server.cluster_view:
                if lo <= slot <= hi:
                    # split the range around the reassigned slot
                    if lo <= slot - 1:
                        new_view.append((lo, slot - 1, h, p, vnid))
                    new_view.append((slot, slot, host, int(port), nid))
                    if slot + 1 <= hi:
                        new_view.append((slot + 1, hi, h, p, vnid))
                else:
                    new_view.append((lo, hi, h, p, vnid))
            server.cluster_view = new_view
            server.set_slot_stable(slot, epoch)
            return "+OK"
        raise RespError("ERR SETSLOT expects MIGRATING|IMPORTING|STABLE|NODE")
    if sub == b"WINDOWS":
        # live migration-window state, over the wire: the cross-process
        # soak (chaos/soak.py ClusterProcSoakHarness) asserts "all slots
        # STABLE" on real server processes, where reaching into
        # server.migrating_slots directly is impossible by design.
        # Reply: [["MIGRATING", slot, target], ..., ["IMPORTING", slot, src]]
        out = [
            [b"MIGRATING", s, t.encode()]
            for s, t in sorted(server.migrating_slots.items())
        ]
        out += [
            [b"IMPORTING", s, src.encode()]
            for s, src in sorted(server.importing_slots.items())
        ]
        out += [
            [b"RECOVERING", s, t.encode()]
            for s, t in sorted(server.recovering_slots.items())
        ]
        # target-side import-journal state (ISSUE 13): an operator can see
        # an in-flight import from the RECEIVING end — epoch, phase,
        # batches made durable pre-ack, and the draining source.  Rows
        # disappear when the migration's last slot goes STABLE (the
        # journal terminalizes), so "no windows" keeps meaning "settled".
        out += [
            [b"IMPORTJOURNAL", epoch, phase.encode(), batches, src.encode()]
            for epoch, phase, batches, src in server.import_journal_rows()
        ]
        return out
    if sub == b"COUNTKEYSINSLOT":
        return len(server.slot_names(_int(args[1])))
    if sub == b"GETKEYSINSLOT":
        names = server.slot_names(_int(args[1]))
        limit = _int(args[2]) if len(args) > 2 else len(names)
        return [n.encode() for n in names[:limit]]
    if sub == b"MIGRATESLOT":
        # drain one MIGRATING slot (optional batch limit; <=0 = fully)
        limit = _int(args[2]) if len(args) > 2 else 0
        return server.migrate_slot_batch(_int(args[1]), limit)
    if sub == b"DEVICES":
        # device-sharded serving state (ISSUE 8), over the wire: per-device
        # slot counts + device labels so tooling (bench config5d, the
        # device-shard soak) can audit the placement without in-process
        # access.  Reply: [n_devices, [dev_id, slots_owned, label,
        # [QOS, infl_ops_i, infl_ops_b, infl_bytes_i, infl_bytes_b,
        #  dispatched_i, dispatched_b]]...] — the trailing QOS row is the
        # lane's per-deadline-class scheduler ledger (ISSUE 10; appended so
        # pre-QoS consumers indexing row[0..2] keep working).  A server
        # without placement replies [0].
        p = server.engine.placement
        if p is None:
            return [0]
        counts = p.slot_counts()
        lanes = server.engine.lanes
        out = [p.n_devices]
        for i, d in enumerate(p.devices):
            row = [getattr(d, "id", i), counts[i], str(d).encode()]
            if lanes is not None:
                lane = lanes.lane(d)
                row.append([b"QOS"] + lane.qos.wire_row())
                # device fault ledger (ISSUE 19) — appended AFTER the QOS
                # row, same discipline: pre-fault consumers indexing
                # row[0..3] keep working.  [FAULTS, quarantined,
                # consec_faults, total_faults, last_fault_kind]
                row.append([
                    b"FAULTS", int(lane.quarantined), lane.consec_faults,
                    lane.total_faults, lane.last_fault_kind.encode(),
                ])
            out.append(row)
        return out
    if sub == b"QOS":
        # CLUSTER QOS REBALANCE <tenant> <rate> [<burst>] (ISSUE 18): the
        # fleet budget actuator — a supervisor control loop pushes each
        # node's share of a tenant's GLOBAL rate here (cluster/qos_control
        # re-splits it proportional to observed per-node demand).  Applies
        # the override via the scheduler's per-tenant hook; a control-plane
        # push, not consensus.
        if len(args) > 1 and bytes(args[1]).upper() == b"REBALANCE":
            if len(args) < 4:
                raise RespError(
                    "ERR CLUSTER QOS REBALANCE <tenant> <rate> [<burst>] "
                    "[WEIGHT <w>]"
                )
            # WEIGHT <w> (ISSUE 19 satellite): the tenant's service-class
            # weight (gold=2.0 / silver=1.0 style) — stored on the bucket
            # state, consumed by the supervisor's demand split; the rate
            # retarget itself stays token-preserving regardless.
            rest = list(args[2:])
            weight = None
            if len(rest) >= 2 and bytes(rest[-2]).upper() == b"WEIGHT":
                try:
                    weight = float(rest[-1])
                except ValueError:
                    raise RespError("ERR value is not a valid float") from None
                rest = rest[:-2]
            tenant = _s(rest[0]) if rest else ""
            try:
                rate = float(rest[1])
                burst = float(rest[2]) if len(rest) > 2 else None
            except (IndexError, ValueError):
                raise RespError("ERR value is not a valid float") from None
            if weight is not None:
                server.scheduler.set_tenant_weight(tenant, weight)
            server.scheduler.set_tenant_rate(tenant, rate, burst)
            return b"OK"
        # global window-scheduler state (ISSUE 10): armed flag, shed
        # totals, per-class in-flight, the per-device-stream rows
        # (ISSUE 18), and the per-tenant bucket table.
        # Reply: [armed, shed_ops, shed_frames,
        #         [class, infl_frames, infl_ops, infl_bytes]...,
        #         [b"STREAM", name, infl_ops, dispatched_ops]...,
        #         [b"TENANT", name, bucket_level, admitted, shed_ops,
        #          shed_frames]...]
        # STREAM rows aggregate over the engine's device lanes; their
        # b"STREAM" tag keeps row[0] distinct from the class rows so
        # pre-stream parsers (OccupancyLoadBalancer._qos_infl_ops) skip
        # them unchanged.
        sched = server.scheduler
        led = sched.ledger
        out = [1 if sched.armed else 0, sched.shed_ops, sched.shed_frames]
        for cls in ("interactive", "bulk"):
            out.append([
                cls.encode(), led.frames[cls], led.ops[cls], led.nbytes[cls],
            ])
        lanes = server.engine.lanes
        if lanes is not None:
            agg = {}
            for lane in lanes.lanes():
                for tag, name, infl, disp in lane.qos.stream_rows():
                    cur = agg.setdefault(name, [0, 0])
                    cur[0] += infl
                    cur[1] += disp
            for name in (b"interactive", b"bulk"):
                if name in agg:
                    out.append([b"STREAM", name] + agg[name])
        for name, level, admitted, shed_ops, shed_frames, weight \
                in sched.tenant_table():
            # weight rides as a trailing element (ISSUE 19 satellite):
            # parse_tenant_table's len>=6 contract tolerates — and now
            # surfaces — it, so pre-weight consumers keep working.
            out.append([
                b"TENANT", name.encode(), int(level), admitted,
                shed_ops, shed_frames, f"{weight:g}".encode(),
            ])
        return out
    if sub == b"DEVMOVE":
        # DEVMOVE <dev_index> [EPOCH <n>] <slot>... — fenced slot -> device
        # handoff inside THIS process (the device-rebalance wire verb: a
        # move is just a placement handoff riding the migration fencing
        # epochs; a stale coordinator's lower epoch replies STALEEPOCH).
        # Returns the number of records whose banks actually moved.
        from redisson_tpu.server.placement import PlacementStaleEpoch

        if server.engine.placement is None:
            raise RespError("ERR placement is not enabled on this server")
        rest = list(args[1:])
        dev_index = _int(rest[0])
        rest = rest[1:]
        epoch = None
        if rest and bytes(rest[0]).upper() == b"EPOCH":
            epoch = _int(rest[1])
            rest = rest[2:]
        moved = 0
        try:
            for s in (_int(a) for a in rest):
                moved += server.engine.move_slot_records(s, dev_index, epoch)
        except PlacementStaleEpoch as e:
            raise RespError(str(e))
        except ValueError as e:
            raise RespError(f"ERR {e}")
        return moved
    if sub == b"DEVPROBE":
        # DEVPROBE <dev_index> (ISSUE 19) — one REAL tiny dispatch+readback
        # through the device's lane; both chaos chokepoints (occupancy
        # enter, readback) consult, so a still-faulted device stays
        # quarantined while a clean pass un-quarantines it.
        # Reply: [passed, quarantined] — tooling polls this for recovery.
        return _dev_probe(server, _int(args[1]))
    if sub == b"DEVEVACUATE":
        # DEVEVACUATE <dev_index> [DIR <journal_dir>] (ISSUE 19) — evacuate
        # every slot owned by <dev_index> onto the surviving non-quarantined
        # devices through the journaled device rebalance (kill-at-every-
        # phase resumable; keyed traffic on moving slots rides the existing
        # TRYAGAIN fence).  Reply: [moved_records, evacuated_slots, epoch]
        # (epoch -1 when unjournaled).
        from redisson_tpu.server import migration as mig

        if server.engine.placement is None:
            raise RespError("ERR placement is not enabled on this server")
        rest = list(args[1:])
        dev_index = _int(rest[0])
        journal_dir = None
        if len(rest) >= 3 and bytes(rest[1]).upper() == b"DIR":
            journal_dir = _s(rest[2])
        try:
            moved, targets, epoch = mig.evacuate_device(
                server.engine, dev_index, journal_dir=journal_dir
            )
        except ValueError as e:
            raise RespError(f"ERR {e}")
        return [moved, len(targets), -1 if epoch is None else epoch]
    if sub == b"MIGRATESLOTS":
        # MIGRATESLOTS [EPOCH <n>] <slot>... — drain MANY migrating slots
        # in one store scan (the orchestrator's bulk form: a reshard of
        # hundreds of slots must not pay a full keyspace scan per slot).
        # EPOCH fences every named slot like SETSLOT EPOCH does.
        rest = list(args[1:])
        epoch = None
        if rest and bytes(rest[0]).upper() == b"EPOCH":
            epoch = _int(rest[1])
            rest = rest[2:]
        slots = [_int(a) for a in rest]
        for s in slots:
            server.fence_slot_epoch(s, epoch)
        return server.migrate_slot_batch(slots)
    if sub == b"RESIDENCY":
        # Tiered-HBM residency plane (ISSUE 20), over the wire.
        #   CLUSTER RESIDENCY                      — the ledger table:
        #     [armed, budget_bytes,
        #      [b"DEV", dev, hot_bytes, warm_bytes, cold_bytes]...,
        #      [b"CTR", promotions, demotions_warm, demotions_cold,
        #       cold_loads, fault_in_ms_total, fault_in_ms_max]]
        #   CLUSTER RESIDENCY TIER <key>           — "hot"/"warm"/"cold"
        #   CLUSTER RESIDENCY DEMOTE <key> [COLD]  — force one demotion
        #   CLUSTER RESIDENCY SWEEP                — one on-demand sweep:
        #     [demoted, colded, freed_bytes]
        #   CLUSTER RESIDENCY SHED <dev> [COUNT n] [DIR d] — move up to n
        #     of <dev>'s slots onto the survivors through the journaled
        #     fenced device rebalance (the pressure-rebalancer's actuator):
        #     [records_moved, slots_moved]
        from redisson_tpu.core import residency as _res

        mgr = server.engine.residency
        if len(args) > 1:
            op = bytes(args[1]).upper()
            if op == b"TIER":
                if len(args) < 3:
                    raise RespError("ERR CLUSTER RESIDENCY TIER <key>")
                if mgr is None:
                    # disarmed plane: everything is HOT by construction
                    return _res.HOT.encode()
                t = mgr.tier_of(_s(args[2]))
                if t is None:
                    raise RespError("ERR no such key")
                return t.encode()
            if op == b"SHED":
                # a placement op, deliberately legal with the manager off —
                # an operator can pre-drain a device before arming tiers
                from redisson_tpu.server import migration as mig

                if server.engine.placement is None:
                    raise RespError(
                        "ERR placement is not enabled on this server"
                    )
                rest = list(args[2:])
                if not rest:
                    raise RespError(
                        "ERR CLUSTER RESIDENCY SHED <dev> [COUNT n] [DIR d]"
                    )
                dev_index = _int(rest[0])
                rest = rest[1:]
                count = 8
                journal_dir = None
                while rest:
                    word = bytes(rest[0]).upper()
                    if word == b"COUNT" and len(rest) >= 2:
                        count = _int(rest[1])
                        rest = rest[2:]
                    elif word == b"DIR" and len(rest) >= 2:
                        journal_dir = _s(rest[1])
                        rest = rest[2:]
                    else:
                        raise RespError(
                            "ERR CLUSTER RESIDENCY SHED <dev> "
                            "[COUNT n] [DIR d]"
                        )
                try:
                    targets = mig.shed_plan(
                        server.engine.placement, dev_index, count
                    )
                    moved = mig.rebalance_devices(
                        server.engine, targets, journal_dir=journal_dir
                    ) if targets else 0
                except ValueError as e:
                    raise RespError(f"ERR {e}")
                return [moved, len(targets)]
            if mgr is None:
                raise RespError(
                    "ERR residency plane is not enabled "
                    "(CONFIG SET residency-enabled yes)"
                )
            if op == b"DEMOTE":
                if len(args) < 3:
                    raise RespError(
                        "ERR CLUSTER RESIDENCY DEMOTE <key> [COLD]"
                    )
                cold = len(args) > 3 and bytes(args[3]).upper() == b"COLD"
                return 1 if mgr.demote(_s(args[2]), cold=cold,
                                       force=True) else 0
            if op == b"SWEEP":
                swept = mgr.sweep()
                return [swept["demoted"], swept["colded"],
                        int(swept["freed_bytes"])]
            raise RespError("ERR unknown CLUSTER RESIDENCY subcommand")
        armed = 1 if (mgr is not None and _res.tier_enabled()) else 0
        out = [armed, int(_res.DEVICE_BUDGET_BYTES)]
        if mgr is None:
            return out
        census = mgr.census()
        devs: dict = {}
        for k, v in census.items():
            if k.startswith("residency_bytes_dev"):
                num, _, tier = k[len("residency_bytes_dev"):].partition("_")
                devs.setdefault(int(num), {})[tier] = int(v)
        for d in sorted(devs):
            row = devs[d]
            out.append([b"DEV", d, row.get("hot", 0), row.get("warm", 0),
                        row.get("cold", 0)])
        out.append([
            b"CTR", mgr.promotions, mgr.demotions_warm, mgr.demotions_cold,
            mgr.cold_loads, f"{mgr.fault_in_ms_total:g}".encode(),
            f"{mgr.fault_in_ms_max:g}".encode(),
        ])
        return out
    raise RespError("ERR unknown CLUSTER subcommand")


def _dev_probe(server, dev_index: int):
    """One end-to-end probe dispatch on a device's lane (ISSUE 19): occupy
    the lane (the chaos kernel-launch chokepoint), run a trivial kernel on
    the device, read it back through ``ReadbackFuture`` (the hung-transfer /
    watchdog chokepoint).  Every fault path already attributes itself to the
    lane's quarantine ledger, so a failed probe only reports — it never
    double-counts.  A verified pass un-quarantines the lane."""
    from redisson_tpu.core import ioplane

    p = server.engine.placement
    lanes = server.engine.lanes
    if p is None or lanes is None:
        raise RespError("ERR placement is not enabled on this server")
    if not (0 <= dev_index < p.n_devices):
        raise RespError(f"ERR device index {dev_index} outside placement")
    device = p.devices[dev_index]
    lane = lanes.lane(device)
    try:
        with lane.occupy(1):
            import jax
            import jax.numpy as jnp

            val = jax.device_put(jnp.arange(8, dtype=jnp.int32), device) + 1
        out = ioplane.ReadbackFuture((val,)).result()
        import numpy as np

        # result() unwraps a single-output future to the array itself
        ok = int(np.asarray(out).sum()) == 36  # sum(1..8)
    except (KeyboardInterrupt, SystemExit):
        raise
    except BaseException:  # noqa: BLE001 — a failing probe is the answer
        return [0, int(lane.quarantined)]
    if ok:
        lane.unquarantine()
    return [1 if ok else 0, int(lane.quarantined)]


@register("ASKING")
def cmd_asking(server, ctx, args):
    """One-shot admission for the NEXT command on this connection into an
    IMPORTING slot (the redirect half of the ASK protocol)."""
    ctx.asking = True
    return "+OK"


def _tracking_invalidator(server):
    """apply_records on_applied hook: transfer frames (migration imports,
    replication pushes) mutate the keyspace exactly like writes, so tracked
    readers on this node must invalidate — the hole that would otherwise
    leave a near cache stale forever is a reader registered on the IMPORT
    side while the record's newer state arrives by drain, not by verb."""
    tracking = getattr(server, "tracking", None)
    if tracking is None or not tracking.active:
        return None
    return lambda names: tracking.note_write(list(names), None)


def _bank_resync(server, names) -> None:
    """Hydration-awareness seam (ISSUE 17, services/vector.py): a full-ship
    replaces a vector_bank record's arrays behind any service-level bank
    object bound to it — resync the bank's host mirror / row count so a
    later (e.g. post-promotion) query never scores a stale mirror."""
    services = getattr(server.engine, "_services", None)
    if not services or services.get("search") is None or not names:
        return
    try:
        from redisson_tpu.services.vector import sync_banks_from_records

        sync_banks_from_records(server.engine, names)
    except Exception:
        pass  # observability seam: never fail the apply


def _replica_on_applied(server):
    """Composite on_applied for replica-side apply_records: tracked readers
    invalidate (replica-side tracking tables stay coherent across the push
    stream) and service banks re-adopt externally installed records."""
    tracking_cb = _tracking_invalidator(server)

    def on_applied(names):
        if tracking_cb is not None:
            tracking_cb(names)
        _bank_resync(server, names)

    return on_applied


def _stamp_recorder(server):
    """apply_records on_payload hook: adopt the push's replication stamp
    (master sweep-cut offset + wall ts) AFTER the records applied — the
    bounded-staleness answer REPLSTATE gives must never run ahead of the
    state a replica read would actually see.  Receipt time is monotonic,
    so staleness_ms needs no cross-host clock agreement."""

    def on_payload(payload):
        off = payload.get("repl_offset")
        if off is None:
            return  # scoped cover-ship: carries records, not a sweep cut
        server.repl_applied_offset = int(off)
        server.repl_applied_ts = float(payload.get("repl_ts") or 0.0)
        server.repl_applied_at = time.monotonic()

    return on_payload


def _require_replica(server, verb: str) -> None:
    """Replication-stream verbs apply only on replicas (ISSUE 17 bugfix): a
    promoted master must NEVER apply a late push from its old master — the
    promoted hydrated plane would silently regress to pre-failover state.
    Rejecting here (instead of trusting the pusher to notice the
    promotion) closes the race between REPLICAOF NO ONE and the old
    master's next sweep; the rejected pusher marks the link unhealthy and
    stops treating this node as its replica."""
    if server.role != "replica":
        raise RespError(
            f"ERR {verb} rejected: node is a master (stale replication push)"
        )


@register("IMPORTRECORDS")
def cmd_importrecords(server, ctx, args):
    """IMPORTRECORDS [EPOCH <n> [SOURCE <addr>]] <blob> — install migrated
    records (slot-migration transfer frame; the blob carries records only —
    no live-list pruning, unlike REPLPUSH).

    With EPOCH (a journaled migration's fenced drain) and a configured
    journal dir, the batch is fsync'd into this node's
    :class:`~redisson_tpu.server.migration_journal.ImportJournal` BEFORE it
    is applied or acked — the source deletes only records this node has
    made durable, which closes the target-kill gap (ISSUE 13).  When
    replicas are attached, the applied records are additionally
    REPLPUSH-covered before the ack, so a dead target's promoted replica
    carries the in-flight import forward.

    A node started WITHOUT a journal dir accepts EPOCH frames but journals
    nothing — the pre-ISSUE-13 degraded mode, kept for the manual/legacy
    migration path.  The target-kill guarantee therefore requires the
    fleet to share a journal dir; the ClusterSupervisor enforces this by
    construction (``--journal-dir`` is passed to every node it spawns)."""
    from redisson_tpu.server import replication

    rest = list(args)
    epoch = source = None
    while len(rest) > 2:
        head = bytes(rest[0]).upper()
        if head == b"EPOCH":
            epoch = _int(rest[1])
        elif head == b"SOURCE":
            source = _s(rest[1])
        else:
            break
        rest = rest[2:]
    if len(rest) != 1:
        raise RespError("ERR IMPORTRECORDS [EPOCH n [SOURCE addr]] <blob>")
    blob = bytes(rest[0])
    if epoch is not None:
        # durability point FIRST: a SIGKILL after this line loses nothing
        # the source will delete (the reply below is what authorizes it)
        server.journal_import_batch(epoch, source, blob)
    applied_names: list = []
    tracking_cb = _tracking_invalidator(server)

    def on_applied(names):
        applied_names.extend(names)
        if tracking_cb is not None:
            tracking_cb(names)

    applied = replication.apply_records(
        server.engine, blob, on_applied=on_applied
    )
    repl = server._replication
    if epoch is not None and applied_names \
            and repl is not None and repl.replicas():
        # replica-covered target (journaled imports only — the legacy
        # epoch-less path never promised it): best-effort push of JUST the
        # applied records before the ack, so failover-by-promotion starts
        # from a caught-up replica (the journal remains the proof)
        repl.cover(applied_names)
    return applied


# -- replication (server/replication.py) -------------------------------------

def _promote_flush(server) -> None:
    """Promotion barrier (ISSUE 17 bugfix): the replica's hydrated device
    plane becomes MASTER state the instant the role flips, so everything
    that could let replica-stream staleness leak in afterwards is cut
    here — half-assembled segmented pushes are dropped (their remaining
    segments are role-gate rejected anyway), tracked readers invalidate
    across the live keyspace (their entries were registered against
    replica-served values and must refetch under the promoted epoch), the
    staleness clock resets (a master is authoritative, never 'stale'), and
    service-level banks re-adopt their records under the promoted role."""
    with server._repl_xfers_lock:
        server._repl_xfers.clear()
    server.repl_applied_at = None
    names = list(server.engine.store.keys())
    cb = _tracking_invalidator(server)
    if cb is not None and names:
        try:
            cb(names)
        except Exception:
            pass
    _bank_resync(server, names)
    server.stats["promotions"] = server.stats.get("promotions", 0) + 1


@register("REPLICAOF")
def cmd_replicaof(server, ctx, args):
    """REPLICAOF NO ONE -> become master; REPLICAOF <host> <port> -> full
    sync from master, then register for the push stream."""
    if len(args) == 2 and bytes(args[0]).upper() == b"NO" and bytes(args[1]).upper() == b"ONE":
        promoted = server.role == "replica"
        if promoted and server.master_address:
            # breadcrumb for successor coordinators: an orphaned master that
            # can name the dead master it was promoted FROM is a
            # half-finished failover; a restarted stale master cannot
            server.promoted_from = server.master_address
        # role flips FIRST: from here every in-flight/late push from the old
        # master is rejected by _require_replica, THEN the promotion barrier
        # scrubs what the replica stream staged (ISSUE 17 bugfix)
        server.role = "master"
        server.master_address = None
        if promoted:
            _promote_flush(server)
        return "+OK"
    if len(args) != 2:
        raise RespError("ERR REPLICAOF <host> <port> | NO ONE")
    host, port = _s(args[0]), _int(args[1])
    from redisson_tpu.server import replication

    # nodes of one grid share credentials AND transport security: the link
    # authenticates with this node's own password and speaks TLS when this
    # node does (cluster-wide convention; server.link_client), with
    # profile-driven cadence (net/retry: lan = legacy single-shot link)
    from redisson_tpu.net.retry import replica_link_kwargs

    master = server.link_client(f"{host}:{port}", **replica_link_kwargs())
    try:
        # resumable chunked pull (ISSUE 16): a dropped link resumes at the
        # offset it reached; the blob is CRC-gated before it can apply
        blob = replication.pull_snapshot(master, timeout=60.0)
        replication.apply_records(
            server.engine, blob,
            on_applied=_tracking_invalidator(server),
        )
        # register by the address this node is KNOWN BY (advertise split):
        # the master's push link must reach a routable address, not a
        # 0.0.0.0 bind
        master.execute("REPLREGISTER", server.public_host, server.port)
    finally:
        master.close()
    server.role = "replica"
    server.master_address = f"{host}:{port}"
    # stale stamps from a PREVIOUS master's stream must not answer fresh:
    # the staleness clock restarts at the new master's first push/heartbeat
    server.repl_applied_at = None
    return "+OK"


def _reap_stale_snaps(server, now: float, keep: str = "") -> None:
    """Drop staged snapshot cuts untouched past the stale window (caller
    holds server._snap_lock) — the same discipline as _reap_stale_xfers:
    a replica that died mid-pull must not pin its cut forever."""
    stages = server._snap_stages
    from redisson_tpu.server.replication import SNAP_STAGE_STALE_S

    for k in [k for k, (_b, _c, ts) in stages.items()
              if k != keep and now - ts > SNAP_STAGE_STALE_S]:
        del stages[k]


@register("REPLSNAPSHOT")
def cmd_replsnapshot(server, ctx, args):
    """Bare REPLSNAPSHOT -> the full serialized cut (legacy one-ship path).

    Subcommands (ISSUE 16, resumable full-sync — replication.pull_snapshot
    is the client half):

      * ``BEGIN [CHUNK n]`` — serialize ONE immutable cut, stage it, reply
        ``[xfer_id, total_bytes, crc32, chunk_bytes]``;
      * ``FETCH <id> <offset>`` — the staged bytes at ``offset`` (up to the
        stage's chunk size); an unknown/reaped id answers ``SNAPEXPIRED``
        so the puller restarts from a fresh BEGIN instead of assembling a
        mixed-cut blob;
      * ``END <id>`` — release the stage (idempotent)."""
    from redisson_tpu.server import replication

    if not args:
        blob, _shipped = replication.serialize_records(server.engine)
        return blob
    sub = bytes(args[0]).upper()
    now = time.monotonic()
    if sub == b"BEGIN":
        chunk = replication.SNAPSHOT_CHUNK_BYTES
        if len(args) >= 3 and bytes(args[1]).upper() == b"CHUNK":
            chunk = max(1, _int(args[2]))
        import zlib

        blob, _shipped = replication.serialize_records(server.engine)
        with server._snap_lock:
            _reap_stale_snaps(server, now)
            while len(server._snap_stages) >= replication.SNAP_STAGE_MAX:
                # backstop only: drop the least-recently-touched stage
                stages = server._snap_stages
                del stages[min(stages, key=lambda k: stages[k][2])]
            server._snap_seq += 1
            xfer_id = f"snap-{server.node_id[:8]}-{server._snap_seq}"
            server._snap_stages[xfer_id] = [blob, chunk, now]
        return [xfer_id, len(blob), zlib.crc32(blob), chunk]
    if sub == b"FETCH":
        xfer_id, offset = _s(args[1]), _int(args[2])
        with server._snap_lock:
            _reap_stale_snaps(server, now, keep=xfer_id)
            entry = server._snap_stages.get(xfer_id)
            if entry is None:
                raise RespError(
                    f"SNAPEXPIRED unknown snapshot transfer {xfer_id}"
                )
            blob, chunk, _ts = entry
            entry[2] = now
        if not (0 <= offset <= len(blob)):
            raise RespError(
                f"ERR snapshot offset {offset} outside 0..{len(blob)}"
            )
        return blob[offset:offset + chunk]
    if sub == b"END":
        with server._snap_lock:
            server._snap_stages.pop(_s(args[1]), None)
        return "+OK"
    raise RespError(
        "ERR REPLSNAPSHOT [BEGIN [CHUNK n] | FETCH <id> <offset> | END <id>]"
    )


@register("REPLREGISTER")
def cmd_replregister(server, ctx, args):
    host, port = _s(args[0]), _int(args[1])
    server.replication_source().register(f"{host}:{port}")
    return "+OK"


@register("REPLPUSH")
def cmd_replpush(server, ctx, args):
    from redisson_tpu.server import replication

    _require_replica(server, "REPLPUSH")
    # any live push proves the link is back: reap transfers its dead
    # predecessor abandoned mid-segment (a restarted master full-ships via
    # plain REPLPUSH, so seg-only sweeping would never fire here)
    with server._repl_xfers_lock:
        _reap_stale_xfers(server, time.monotonic())
    return replication.apply_records(
        server.engine, bytes(args[0]),
        on_applied=_replica_on_applied(server),
        on_payload=_stamp_recorder(server),
    )


# staging eviction knobs (cmd_replpushseg): a transfer untouched for
# REPL_XFER_STALE_S is abandoned (its pusher's per-segment timeout is 60s,
# so 120s of silence means the source died mid-transfer); REPL_XFER_MAX is
# the hard leak backstop — far above any sane concurrent-transfer count, so
# in-progress transfers are never spuriously dropped (ADVICE r5 low: the
# old keep-at-most-4-by-insertion-order cap dropped concurrent live ones).
REPL_XFER_STALE_S = 120.0
REPL_XFER_MAX = 64


def _reap_stale_xfers(server, now: float, keep: str = "") -> None:
    """Drop staged transfers untouched past the stale window.  Caller holds
    server._repl_xfers_lock.  Runs on EVERY replication push — not just a
    new transfer's first slice — so an abandoned transfer cannot linger
    (and read as a phantom leak in the resource census) just because no
    later segmented ship ever starts."""
    xfers = server._repl_xfers
    for k in [k for k, (_slots, ts) in xfers.items()
              if k != keep and now - ts > REPL_XFER_STALE_S]:
        del xfers[k]


@register("REPLPUSHSEG")
def cmd_replpushseg(server, ctx, args):
    """REPLPUSHSEG <xfer_id> <seq> <nsegs> <chunk> — one bounded slice of an
    oversized REPLPUSH blob (a 10M-key bloom plane is ~95MB; a single
    sendall of that stalls past socket timeouts, server/replication.py
    SEGMENT_BYTES).  The final slice reassembles and applies the blob;
    intermediates stage host-side and answer +OK.  Staging evicts by
    per-transfer staleness (last-touch timestamp), never insertion order."""
    from redisson_tpu.server import replication

    _require_replica(server, "REPLPUSHSEG")
    xfer_id, seq, nsegs = _s(args[0]), _int(args[1]), _int(args[2])
    chunk = bytes(args[3])
    now = time.monotonic()
    xfers = server._repl_xfers
    with server._repl_xfers_lock:
        _reap_stale_xfers(server, now, keep=xfer_id)
        if seq == 0:
            while len(xfers) >= REPL_XFER_MAX:
                # backstop only: drop the least-recently-touched transfer
                del xfers[min(xfers, key=lambda k: xfers[k][1])]
            xfers[xfer_id] = [[None] * nsegs, now]
        entry = xfers.get(xfer_id)
        if entry is None or len(entry[0]) != nsegs or not (0 <= seq < nsegs):
            raise RespError(f"ERR unknown replication transfer {xfer_id}/{seq}")
        entry[0][seq] = chunk
        entry[1] = now
        if any(s is None for s in entry[0]):
            return "+OK"
        del xfers[xfer_id]
        blob = b"".join(entry[0])
    return replication.apply_records(
        server.engine, blob,
        on_applied=_replica_on_applied(server),
        on_payload=_stamp_recorder(server),
    )


@register("REPLPING")
def cmd_replping(server, ctx, args):
    """REPLPING <offset> <ts> — master heartbeat on a clean sweep cut: the
    replica's applied offset advances without any payload, so bounded-
    staleness replica reads stay eligible while the keyspace is idle
    (otherwise an idle master would starve every MAXSTALE bound)."""
    _require_replica(server, "REPLPING")
    server.repl_applied_offset = _int(args[0])
    try:
        server.repl_applied_ts = float(_s(args[1]))
    except (ValueError, IndexError):
        server.repl_applied_ts = 0.0
    server.repl_applied_at = time.monotonic()
    return "+OK"


@register("REPLSTATE")
def cmd_replstate(server, ctx, args):
    """REPLSTATE [MAXSTALE <ms>] -> [role, applied_offset, staleness_ms,
    view_epoch] — the bounded-staleness contract's server half (ISSUE 17).

    staleness_ms is measured from the monotonic RECEIPT of the last applied
    push/heartbeat, so it needs no cross-host clock agreement; -1 means the
    replica has never synced (always too stale).  A master answers 0 — it
    is never stale with respect to itself.  The MAXSTALE form replies the
    same shape and additionally counts replica_redirects_stale when the
    answer exceeds the client's bound: the client pipelines REPLSTATE
    MAXSTALE ahead of its read and redirects to the master on the reply."""
    max_stale = None
    if args:
        if len(args) == 2 and bytes(args[0]).upper() == b"MAXSTALE":
            max_stale = _int(args[1])
        else:
            raise RespError("ERR REPLSTATE [MAXSTALE <ms>]")
    if server.role != "replica":
        stale_ms = 0
    elif server.repl_applied_at is None:
        stale_ms = -1
    else:
        stale_ms = int((time.monotonic() - server.repl_applied_at) * 1000.0)
    if max_stale is not None and server.role == "replica" \
            and (stale_ms < 0 or stale_ms > max_stale):
        server.stats["replica_redirects_stale"] += 1
    return [
        server.role.encode(),
        int(server.repl_applied_offset),
        stale_ms,
        int(server.view_epoch),
    ]


@register("REPLFLUSH")
def cmd_replflush(server, ctx, args):
    """Ship dirty records to all replicas NOW (WAIT / syncSlaves analog)."""
    if server._replication is None:
        return 0
    return server._replication.flush()


@register("ROLE")
def cmd_role(server, ctx, args):
    """Redis ROLE parity: master -> ["master", 0, [replica addrs]];
    replica -> ["slave", host, port, "connected", 0].  Failover
    coordinators probe this to DISCOVER a dead master's replicas when they
    started after the death (a successor coordinator has no poll history)."""
    if server.role == "replica" and server.master_address:
        host, _, port = server.master_address.rpartition(":")
        return [b"slave", host.encode(), int(port), b"connected", 0]
    reps = []
    if server._replication is not None:
        reps = [a.encode() for a in server._replication.replicas()]
    promoted_from = getattr(server, "promoted_from", None)
    # 4th element is our extension past Redis ROLE: the address this master
    # was promoted FROM (empty when it never was a replica) — coordinators
    # use it to adopt half-finished failovers without mistaking a restarted
    # stale master for one
    return [b"master", 0, reps, (promoted_from or "").encode()]


@register("REPLICAS")
def cmd_replicas(server, ctx, args):
    if server._replication is None:
        return []
    return [a.encode() for a in server._replication.replicas()]


@register("METRICS")
def cmd_metrics(server, ctx, args):
    """Prometheus text exposition of the node's metrics registry.

    ``METRICS CLUSTER`` (ISSUE 12): fan the scrape out to every master in
    this node's cluster view and merge the expositions with per-node
    ``node="host:port"`` labels — the wire half of the fleet-wide
    one-pane-of-glass (``ClusterSupervisor.scrape()`` is the supervisor
    half; both ride ``utils.metrics.merge_prometheus_texts``).  A dead
    peer contributes nothing rather than failing the whole scrape."""
    if args and bytes(args[0]).upper() == b"CLUSTER":
        from redisson_tpu.utils.metrics import merge_prometheus_texts

        texts = {server.address(): server.metrics.prometheus_text()}
        seen = {(server.host, server.port)}
        for _lo, _hi, host, port, _nid in server.cluster_view:
            if (host, port) in seen:
                continue
            seen.add((host, port))
            try:
                link = server.link_client(
                    f"{host}:{port}", ping_interval=0, retry_attempts=1
                )
                try:
                    texts[f"{host}:{port}"] = bytes(
                        link.execute("METRICS", timeout=10.0)
                    ).decode()
                finally:
                    link.close()
            except Exception:  # noqa: BLE001 — dead peer: scrape the rest
                continue
        return merge_prometheus_texts(texts).encode()
    return server.metrics.prometheus_text().encode()


# -- tracing plane verbs (ISSUE 12: TRACE / SLOWLOG / LATENCY) ----------------


def _attrs_wire(attrs) -> list:
    """A span's or a host event's flat attrs on the wire: [k, v, ...]."""
    out = []
    if attrs:
        for k, v in attrs.items():
            out.append(k.encode())
            out.append(v if isinstance(v, int) else str(v).encode())
    return out


def _span_wire(span) -> list:
    """One stage span on the wire: [name, off_us, dur_us, [k, v, ...]]."""
    return [span.name.encode(), span.off_us, span.dur_us,
            _attrs_wire(span.attrs)]


def _trace_wire(tr) -> list:
    """One frame trace on the wire: [id, unix_ms, total_us, verb, n_cmds,
    class, tenant, [span, ...]] — tools/trace_dump.py renders this as a
    per-stage waterfall."""
    return [
        tr.trace_id, int(tr.ts * 1000), tr.total_us, tr.verbs.encode(),
        tr.n_cmds, (tr.qos_class or "").encode(), (tr.tenant or "").encode(),
        [_span_wire(s) for s in tr.spans],
    ]


@register("TRACE")
def cmd_trace(server, ctx, args):
    """TRACE GET [n] [BY total|<stage>] | EVENTS [n] | RESET | CONFIG
    GET|SET k v — the per-frame span ring over the wire.  GET returns the
    slowest-n finished traces ordered by total duration (or by one stage's
    summed duration: BY qos / readback / dispatch / hop / recv / host.gc /
    ...), each a full span tree: [name, off_us, dur_us, attrs] a span,
    offsets from the read that completed the frame (`recv`, the frame's
    arrival, lies before it: a negative offset), `reply.wait` /
    `reply.encode` / `reply.write` inside `reply`, and on a slow frame one
    `host.gc` / `host.stall` for each host pause it overlapped.  EVENTS
    returns the host-event ring, newest first: [kind, unix_ms, dur_us,
    attrs] with kind `gc` (a collection of 1 ms or more, `gen`) or `stall`
    (a turn of the event loop of 5 ms or more).  RESET clears both rings.
    Empty while tracing is disarmed (CONFIG SET trace-enabled yes arms)."""
    sub = bytes(args[0]).upper() if args else b"GET"
    tracer = server.tracer
    if sub == b"GET":
        rest = list(args[1:])
        n = 10
        by = "total"
        if rest and bytes(rest[0]).upper() != b"BY":
            n = _int(rest[0])
            rest = rest[1:]
        if rest and bytes(rest[0]).upper() == b"BY":
            if len(rest) < 2:
                raise RespError("ERR TRACE GET ... BY needs a stage name")
            by = _s(rest[1])
        return [_trace_wire(t) for t in tracer.slowest(n, by=by)]
    if sub == b"EVENTS":
        n = _int(args[1]) if len(args) > 1 else None
        return [
            [kind.encode(), int(ts * 1000), int(seconds * 1e6),
             _attrs_wire(attrs)]
            for kind, ts, seconds, attrs in tracer.host_events(n)
        ]
    if sub == b"RESET":
        tracer.reset()
        return "+OK"
    if sub == b"CONFIG":
        mode = bytes(args[1]).upper() if len(args) > 1 else b"GET"
        if mode == b"GET":
            out = []
            view = server.config_view()
            for k in ("trace-enabled", "trace-ring-capacity",
                      "slowlog-log-slower-than", "slowlog-max-len"):
                out += [k.encode(), str(view[k]).encode()]
            return out
        if mode == b"SET":
            if len(args) < 4:
                raise RespError("ERR TRACE CONFIG SET <key> <value>")
            if not server.config_set(_s(args[2]), _s(args[3])):
                raise RespError(
                    f"ERR unknown TRACE CONFIG parameter '{_s(args[2])}'"
                )
            return "+OK"
        raise RespError("ERR TRACE CONFIG expects GET|SET")
    raise RespError("ERR TRACE expects GET|EVENTS|RESET|CONFIG")


@register("SLOWLOG")
def cmd_slowlog(server, ctx, args):
    """SLOWLOG GET [n] | RESET | LEN — Redis parity verbs backed by the
    trace ring (threshold: CONFIG SET slowlog-log-slower-than <µs>,
    negative disables, 0 logs everything).  Each entry carries the
    per-stage breakdown instead of Redis's flat duration:
    [id, unix_ts, total_us, [verb, ncmds], [[stage, dur_us], ...]]."""
    sub = bytes(args[0]).upper() if args else b"GET"
    tracer = server.tracer
    if sub == b"GET":
        n = _int(args[1]) if len(args) > 1 else 10
        out = []
        for sid, ts, dur_us, tr, stages in tracer.slowlog_get(n):
            out.append([
                sid, ts, dur_us,
                [tr.verbs.encode(), str(tr.n_cmds).encode()],
                [[st.encode(), us] for st, us in sorted(stages.items())],
            ])
        return out
    if sub == b"LEN":
        return tracer.slowlog_len()
    if sub == b"RESET":
        tracer.slowlog_reset()
        return "+OK"
    raise RespError("ERR SLOWLOG expects GET|RESET|LEN")


@register("LATENCY")
def cmd_latency(server, ctx, args):
    """LATENCY HISTORY <event> | RESET [event ...] | LATEST — Redis parity
    over the per-STAGE samples the tracer collects (events are stage names:
    total, recv, parse, qos, hop, dispatch, stage, kernel, readback, reply,
    and host.gc / host.stall from the slow frames that overlapped a pause)."""
    sub = bytes(args[0]).upper() if args else b""
    tracer = server.tracer
    if sub == b"HISTORY":
        if len(args) < 2:
            raise RespError("ERR LATENCY HISTORY <event>")
        return [
            # (unix ts, MILLISECONDS) pairs — the Redis LATENCY contract;
            # sub-ms durations round up to 1 so a recorded sample is never
            # indistinguishable from "no latency"
            [ts, max(1, int(round(ms)))]
            for ts, ms in tracer.latency_history(_s(args[1]))
        ]
    if sub == b"RESET":
        return tracer.latency_reset([_s(a) for a in args[1:]])
    if sub == b"LATEST":
        out = []
        for ev in tracer.latency_events():
            hist = tracer.latency_history(ev)
            if not hist:
                continue
            ts, ms = hist[-1]
            worst = max(m for _t, m in hist)
            out.append([
                ev.encode(), ts,
                max(1, int(round(ms))), max(1, int(round(worst))),
            ])
        return out
    raise RespError("ERR LATENCY expects HISTORY|RESET|LATEST")


# -- checkpoint (SAVE analog; full impl in core/checkpoint.py) ---------------

@register("SAVE")
def cmd_save(server, ctx, args):
    path = _s(args[0]) if args else server.checkpoint_path
    if path is None:
        raise RespError("ERR no checkpoint path configured")
    from redisson_tpu.core import checkpoint

    checkpoint.save(server.engine, path)
    return "+OK"


@register("BGSAVE")
def cmd_bgsave(server, ctx, args):
    """Checkpoint in the background (the RDB BGSAVE role); LASTSAVE reports
    the completion time of the most recent one."""
    path = _s(args[0]) if args else server.checkpoint_path
    if path is None:
        raise RespError("ERR no checkpoint path configured")
    from redisson_tpu.core import checkpoint

    def run():
        try:
            checkpoint.save(server.engine, path)
            server.__dict__["_lastsave"] = int(time.time())
        except Exception:  # noqa: BLE001 — background save: best-effort
            pass

    threading.Thread(target=run, daemon=True, name="rtpu-bgsave").start()
    return "+Background saving started"


@register("BGREWRITEAOF")
def cmd_bgrewriteaof(server, ctx, args):
    """No AOF exists: durability is checkpoint + replication, so the rewrite
    request degrades to a background checkpoint (documented in PARITY.md)."""
    cmd_bgsave(server, ctx, args)
    return "+Background append only file rewriting started"


@register("LASTSAVE")
def cmd_lastsave(server, ctx, args):
    return int(server.__dict__.get("_lastsave", 0))


@register("SHUTDOWN")
def cmd_shutdown(server, ctx, args):
    """SHUTDOWN [NOSAVE|SAVE]: optionally checkpoint, then stop the server.
    Like Redis, a successful shutdown never delivers a reply — the
    connection just dies; the stop runs on a side thread so this handler's
    worker can finish its frame."""
    mode = bytes(args[0]).upper() if args else b""
    if mode == b"SAVE" and not server.checkpoint_path:
        raise RespError("ERR no checkpoint path configured")
    if mode == b"SAVE" or (mode != b"NOSAVE" and server.checkpoint_path):
        from redisson_tpu.core import checkpoint

        try:
            checkpoint.save(server.engine, server.checkpoint_path)
            server.__dict__["_lastsave"] = int(time.time())
        except Exception as e:  # noqa: BLE001 — like Redis: a failed final
            # save ABORTS the shutdown (data would be lost silently)
            raise RespError(f"ERR shutdown save failed, aborting: {e}")
    threading.Thread(target=server.stop, daemon=True, name="rtpu-shutdown").start()
    return "+OK"


@register("RESTORESTATE")
def cmd_restorestate(server, ctx, args):
    path = _s(args[0]) if args else server.checkpoint_path
    if path is None:
        raise RespError("ERR no checkpoint path configured")
    from redisson_tpu.core import checkpoint

    n = checkpoint.load(server.engine, path)
    return n


# -- script / function / admin verbs (RScript + RFunction wire surface) ------

def _script_svc(server):
    from redisson_tpu.services.script import ScriptService

    return server.engine.service("script", lambda: ScriptService(server.engine))


def _function_svc(server):
    from redisson_tpu.services.script import FunctionService

    return server.engine.service("function", lambda: FunctionService(server.engine))


def _proc_keys_args(args, at):
    """numkeys keys... args... tail shared by EVALSHA/FCALL."""
    n = _int(args[at])
    if n < 0:
        raise RespError("ERR Number of keys can't be negative")
    if len(args) < at + 1 + n:
        raise RespError("ERR Number of keys is greater than number of args")
    keys = [_s(k) for k in args[at + 1 : at + 1 + n]]
    rest = [bytes(a) for a in args[at + 1 + n :]]
    return keys, rest


@register("EVALSHA")
def cmd_evalsha(server, ctx, args):
    """EVALSHA sha numkeys key... arg... — invokes a script REGISTERED
    SERVER-SIDE (embedded script_load).  Scripts here are Python callables,
    so source never ships over the wire: remote callers address by digest
    only, and a miss replies NOSCRIPT exactly like the reference's
    EVAL-fallback discipline expects."""
    from redisson_tpu.services.script import NoScriptError

    keys, rest = _proc_keys_args(args, 1)
    try:
        return _script_svc(server).eval_sha(_s(args[0]), keys, rest)
    except NoScriptError:
        raise RespError("NOSCRIPT No matching script. Please use EVAL.")


@register("EVAL")
def cmd_eval(server, ctx, args):
    raise RespError(
        "ERR EVAL with shipped source is not supported on this server: "
        "scripts are Python callables registered server-side (script_load); "
        "invoke by digest with EVALSHA, or FCALL a loaded function library"
    )


@register("SCRIPT")
def cmd_script(server, ctx, args):
    sub = bytes(args[0]).upper()
    svc = _script_svc(server)
    if sub == b"EXISTS":
        return [1 if ok else 0 for ok in svc.script_exists(*[_s(s) for s in args[1:]])]
    if sub == b"FLUSH":
        svc.script_flush()
        return "+OK"
    if sub == b"LOAD":
        raise RespError(
            "ERR SCRIPT LOAD over the wire is not supported (scripts are "
            "Python callables; register them server-side)"
        )
    raise RespError(f"ERR Unknown SCRIPT subcommand '{_s(args[0])}'")


def _fcall(server, args, read_only: bool):
    keys, rest = _proc_keys_args(args, 1)
    svc = _function_svc(server)
    # resolve OUTSIDE the invocation: a KeyError raised by the function's
    # own body must surface as the function's error, not "not found"
    try:
        fn = svc._resolve(_s(args[0]))
    except KeyError:
        raise RespError(f"ERR Function not found: {_s(args[0])}")
    from redisson_tpu.services.script import ScriptMode

    mode = ScriptMode.READ_ONLY if read_only else ScriptMode.READ_WRITE
    return svc._script.eval(fn, keys, rest, mode)


@register("FCALL")
def cmd_fcall(server, ctx, args):
    return _fcall(server, args, read_only=False)


@register("FCALL_RO")
def cmd_fcall_ro(server, ctx, args):
    return _fcall(server, args, read_only=True)


@register("FUNCTION")
def cmd_function(server, ctx, args):
    sub = bytes(args[0]).upper()
    if sub == b"LIST":
        out = []
        for lib, fns in sorted(_function_svc(server).list().items()):
            out.append([
                b"library_name", lib.encode(),
                b"functions", [f.encode() for f in fns],
            ])
        return out
    if sub == b"DUMP" or sub == b"LOAD":
        raise RespError(
            "ERR FUNCTION libraries are Python callables registered "
            "server-side; wire DUMP/LOAD is not supported"
        )
    raise RespError(f"ERR Unknown FUNCTION subcommand '{_s(args[0])}'")


@register("WAIT")
def cmd_wait(server, ctx, args):
    """WAIT numreplicas timeout(ms): flush dirty records to replicas now and
    report how many replicas are attached (record-level async replication:
    a returned count >= numreplicas means the flush was SHIPPED to that
    many replicas — the syncSlaves/REPLFLUSH semantics)."""
    import time as _t

    if len(args) < 2:
        raise RespError("ERR wrong number of arguments for 'wait' command")
    want = _int(args[0])
    timeout_ms = _int(args[1])
    if timeout_ms < 0:
        raise RespError("ERR timeout is negative")
    # Redis WAIT timeout 0 = block until the replica count is reached
    # (same convention as _block_loop's timeout<=0)
    deadline = None if timeout_ms == 0 else _t.time() + timeout_ms / 1000.0
    while True:
        n = 0
        if server._replication is not None:
            server._replication.flush()
            n = len(server._replication.replicas())
        if (
            n >= want
            or (deadline is not None and _t.time() >= deadline)
            or getattr(server, "_closing", False)
            or getattr(_exec_tls, "in_exec", False)  # no parking inside EXEC
        ):
            return n
        _t.sleep(0.02)  # parked, not spinning: this holds a pool worker


@register("CONFIG")
def cmd_config(server, ctx, args):
    """CONFIG GET pattern | CONFIG SET key value — the RedisNode.setConfig
    admin surface over the server's live knob table."""
    sub = bytes(args[0]).upper()
    if sub == b"GET":
        pattern = _s(args[1]) if len(args) > 1 else "*"
        out = []
        for k, v in sorted(server.config_view().items()):
            if _glob_match(pattern, k):
                out += [k.encode(), str(v).encode()]
        return out
    if sub == b"SET":
        if not server.config_set(_s(args[1]), _s(args[2])):
            raise RespError(f"ERR Unknown or read-only CONFIG parameter '{_s(args[1])}'")
        return "+OK"
    raise RespError(f"ERR Unknown CONFIG subcommand '{_s(args[0])}'")


def _bmpop_prelude(args):
    """Shared BLMPOP/BZMPOP validation: timeout + numkeys BEFORE any
    delegation, so malformed input replies a syntax error, never ERR
    internal."""
    import math as _math

    if len(args) < 4:
        raise RespError("ERR wrong number of arguments")
    try:
        timeout = float(args[0])
    except (TypeError, ValueError):
        raise RespError("ERR timeout is not a float or out of range")
    if not _math.isfinite(timeout) or timeout < 0:
        # NaN would make every deadline comparison False: park forever
        raise RespError("ERR timeout is not a float or out of range")
    rest = args[1:]
    n = _int(rest[0])
    if n <= 0:
        raise RespError("ERR numkeys should be greater than 0")
    if len(rest) < 1 + n + 1:
        raise RespError("ERR Number of keys is greater than number of args")
    return timeout, rest, _s(rest[1])


@register("BLMPOP")
def cmd_blmpop(server, ctx, args):
    """BLMPOP timeout numkeys key... LEFT|RIGHT [COUNT n]."""
    timeout, rest, first_key = _bmpop_prelude(args)

    def poll_once():
        return cmd_lmpop(server, ctx, rest)

    return _block_loop(server, first_key, poll_once, timeout)


@register("BZMPOP")
def cmd_bzmpop(server, ctx, args):
    """BZMPOP timeout numkeys key... MIN|MAX [COUNT n]."""
    timeout, rest, first_key = _bmpop_prelude(args)

    def poll_once():
        return cmd_zmpop(server, ctx, rest)

    return _block_loop(server, first_key, poll_once, timeout)


@register("DUMP")
def cmd_dump(server, ctx, args):
    """DUMP key — the portable record blob (core/checkpoint.dump_record;
    wire names are stored keys, so no handle/NameMapper indirection)."""
    from redisson_tpu.core import checkpoint

    try:
        return checkpoint.dump_record(server.engine, _s(args[0]))
    except KeyError:
        return None  # missing key dumps nil


@register("RESTORE")
def cmd_restore(server, ctx, args):
    """RESTORE key ttl(ms) blob [REPLACE] — BUSYKEY unless REPLACE."""
    from redisson_tpu.core import checkpoint

    name = _s(args[0])
    ttl_ms = _int(args[1])
    if ttl_ms < 0:
        raise RespError("ERR Invalid TTL value, must be >= 0")
    opts = {bytes(a).upper() for a in args[3:]}
    if opts - {b"REPLACE", b"PERSIST"}:
        raise RespError("ERR syntax error")
    try:
        # Redis semantics: ttl 0 == no expiry.  RObject.migrate ships the
        # remaining TTL as this explicit operand; the blob-carried TTL only
        # applies to direct restore_record calls (checkpoint files).
        checkpoint.restore_record(
            server.engine, name, bytes(args[2]),
            ttl_ms / 1000.0 if ttl_ms > 0 else None,
            b"REPLACE" in opts, persist=b"PERSIST" in opts or ttl_ms == 0,
        )
    except ValueError as e:
        msg = str(e)
        raise RespError(msg if msg.startswith("BUSYKEY") else f"ERR {msg}")
    return "+OK"

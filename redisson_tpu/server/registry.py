"""Server command registry: RESP command name -> handler over the Engine.

Parity target: ``client/protocol/RedisCommands.java`` (the ~447-command
registry) reimagined server-side: instead of 447 micro-commands, the wire
surface is (a) a compact set of compatible commands for keyspace admin,
strings, bits, sketches and pubsub, with **batched multi-key forms as the
primary citizens** (BF.MADD/BF.MEXISTS carry whole key batches — the RBatch
flush arrives as ONE command, one fused kernel dispatch), and (b) a generic
`OBJCALL` escape hatch that invokes any client-object method server-side
(pickled args), giving the full L5' object surface remote parity the way the
reference ships task classBody bytes (executor/TasksRunnerService.java).

Handlers run on the server's worker pool; per-connection order is preserved
by the connection loop (CommandsQueue FIFO discipline).
"""
from __future__ import annotations

import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from redisson_tpu.net import client as _net
from redisson_tpu.net.resp import Push, RespError
from redisson_tpu.observe import trace as _obs
from redisson_tpu.utils.metrics import run_hooks_end, run_hooks_start
from redisson_tpu.version import __version__ as VERSION


class LazyReply:
    """Deferred reply: the handler DISPATCHED device work but did not force
    the device->host sync.  The connection loop materializes every lazy
    reply of a pipelined frame together — and, for the (device, finish)
    form, fetches every device result of the frame in one grouped fetch a
    device (core/ioplane.gather_device_results: same-shaped values stacked,
    every crossing started before the first is waited for), so a
    32-command frame pays ~1 device->host sync instead of 32 (the
    reference's analog is CommandBatchService's single-flush discipline).
    A device value several replies name (the rows of one fused run) crosses
    once.  Constraint: a reply that is its device's ONLY value crosses as a
    uint8 stream, so its dtype must round-trip via
    ``np.dtype(a.dtype.name)`` — a dtype numpy can't name (e.g. bfloat16)
    cannot ride this path.

    Two forms:
      LazyReply(force=fn)              — fn() -> reply, forced individually;
      LazyReply(device=(arrs...), finish=fn) — fn(host_arrays) -> reply,
        host_arrays delivered by the frame-level grouped transfer.  `owed`:
        the bytes of them the reply is made from, where that is less than
        all (its rows of a shared result) — counted, never needed.
    """

    __slots__ = ("device", "finish", "owed", "_force")

    def __init__(self, force: Optional[Callable[[], Any]] = None,
                 device: Optional[tuple] = None,
                 finish: Optional[Callable[[tuple], Any]] = None,
                 owed: Optional[int] = None):
        self._force = force
        self.device = device
        self.finish = finish
        self.owed = owed

    def force(self) -> Any:
        if self._force is not None:
            return self._force()
        import numpy as np

        return self.finish(tuple(np.asarray(v) for v in self.device))


class Encoded:
    """A reply already in wire bytes: an error encoded where it was caught,
    or an answer its handler made as bytes (a KNN wave's, verbs/modules.py
    _ft_wave_encode).  The frame's encoder passes ``data`` through."""

    __slots__ = ("data",)

    def __init__(self, data: bytes):
        self.data = data


def gather_lazy_device_results(lazies: List["LazyReply"],
                               traces=None) -> List[tuple]:
    """Fetch every device value of `lazies` with one grouped fetch a device
    — the frame-level gather, THE shared primitive of the overlap plane
    (core/ioplane.gather_device_results): the server's reply path, the
    embedded Batch drain, and bench's A/B harness all force through it, so
    the fetch discipline cannot diverge between layers.  `traces` (tracing
    armed): the frames the fetch is made for where they are several — a
    window of point commands, one frame a member — else the thread's
    current one."""
    from redisson_tpu.core.ioplane import _is_ready, gather_device_results

    if _obs._tracer is not None:
        if traces is None:
            cur = _obs.current_trace()
            traces = () if cur is None else (cur,)
        if traces:
            # the frame rode the GROUPED fetch: one span covering the whole
            # gather, annotated whether any member still had to block on
            # device work (vs a pure-transfer ride)
            import time as _time

            was_ready = all(
                _is_ready(v) for lz in lazies for v in lz.device
            )
            t0 = _time.monotonic()
            note: dict = {}
            out = gather_device_results(
                [lz.device for lz in lazies], [lz.owed for lz in lazies], note
            )
            # parts: distinct device values the frame owed; fetches: the
            # transfers that brought them; bucket: the widest stack ridden
            t1 = _time.monotonic()
            for tr in traces:
                tr.add_span(
                    "readback", t0, t1,
                    grouped=len(lazies), blocking=int(not was_ready), **note,
                )
            return out
    return gather_device_results(
        [lz.device for lz in lazies], [lz.owed for lz in lazies]
    )


class CommandContext:
    """Per-connection state (db selection, auth, subscriptions)."""

    def __init__(self, server):
        self.server = server
        # auth required when a default password OR any ACL user is set
        self.authenticated = server.password is None and not getattr(server, "users", None)
        self.username: Optional[str] = None
        # negotiated protocol: this wire is RESP3-native (typed maps/sets/
        # push/null/bool/double frames); HELLO 2 downgrades the connection
        # to the strict RESP2 projection for compatibility clients
        self.proto: int = 3
        self.name: Optional[str] = None
        # stable connection identity: CLIENT ID / TRACKING REDIRECT address
        # this context for its whole life (the old per-call next_client_id
        # minted a fresh id every CLIENT ID — useless as a redirect target)
        self.client_id: int = server.next_client_id()
        # per-connection tracking state (tracking/table.py ConnTracking);
        # None until CLIENT TRACKING ON
        self.tracking = None
        # QoS plane (ISSUE 10, server/scheduler.py): the connection-declared
        # deadline class ("interactive"/"bulk"; None = heuristic by frame
        # size) and tenant (None = derive from the frame's key {hashtag})
        # — set by CLIENT QOS CLASS <c> [TENANT <t>]
        self.qos_class: Optional[str] = None
        self.tenant: Optional[str] = None
        self.subscriptions: Dict[str, int] = {}
        self.psubscriptions: Dict[str, int] = {}
        self.push: Optional[Callable[[Any], None]] = None  # wired by the server
        self.asking = False  # one-shot ASK admission (cleared per command)
        # READONLY connection state (Redis cluster parity, ISSUE 17): armed
        # by the READONLY verb, cleared by READWRITE.  A cluster replica
        # serves keyed reads only to readonly connections — everyone else
        # gets -MOVED to the master (server.check_routing).
        self.readonly = False
        # MULTI/EXEC/WATCH state (per-connection, like Redis): a non-None
        # multi_queue means queueing mode; watch_versions holds the record
        # versions observed at WATCH time (the optimistic precondition)
        self.multi_queue: Optional[List[List[bytes]]] = None
        self.multi_error = False
        self.watch_versions: Dict[str, int] = {}

    def subscription_count(self) -> int:
        return len(self.subscriptions) + len(self.psubscriptions)


class Registry:
    def __init__(self):
        self._handlers: Dict[bytes, Callable] = {}

    def register(self, name: str):
        def deco(fn):
            self._handlers[name.upper().encode()] = fn
            return fn

        return deco

    # commands served immediately even while a MULTI queue is open
    _TX_IMMEDIATE = frozenset(
        (b"MULTI", b"EXEC", b"DISCARD", b"WATCH", b"UNWATCH", b"RESET",
         b"QUIT", b"AUTH", b"HELLO")
    )

    def dispatch(self, server, ctx: CommandContext, args: List[bytes]):
        if not args:
            raise RespError("ERR empty command")
        cmd = bytes(args[0]).upper()
        handler = self._handlers.get(cmd)
        if handler is None:
            if ctx.multi_queue is not None:
                # Redis poisons the open transaction: EXEC replies EXECABORT
                ctx.multi_error = True
            raise RespError(f"ERR unknown command '{cmd.decode()}'")
        if not ctx.authenticated and cmd not in (b"AUTH", b"HELLO", b"QUIT", b"PING"):
            raise RespError("NOAUTH Authentication required.")
        # one-shot ASK admission: consumed by every command (the ASKING
        # handler re-arms it for the next one)
        asking, ctx.asking = ctx.asking, False
        if server.cluster_view or server.role == "replica":
            # queue-time MOVED/ASK replies match Redis cluster; EXEC rechecks
            # the whole group before applying anything
            server.check_routing(cmd.decode(), args[1:], asking=asking,
                                 readonly=ctx.readonly)
        if ctx.multi_queue is not None and cmd not in self._TX_IMMEDIATE:
            ctx.multi_queue.append([bytes(a) for a in args])
            return "+QUEUED"
        # device-dispatch chokepoint (ISSUE 19): with the chaos plane armed
        # a command routed to a faulted device fails HERE, with the same
        # XlaRuntimeError shape a real kernel launch raises, BEFORE the
        # handler applies anything.  Disarmed cost: one global load + an
        # `is None` branch (device resolution runs only when armed).
        plane = _net._fault_plane
        if plane is not None:
            _consult_device_dispatch(plane, server, args)
        # client-tracking hooks (tracking/table.py): `active` is an int load
        # + compare, so a server with no tracking clients pays ~nothing.
        # Reads register PRE-dispatch (a concurrent writer must see the
        # registration or apply before our read); writes invalidate
        # POST-dispatch (after the handler applied).
        track = getattr(server, "tracking", None)
        if track is not None and not track.active:
            track = None
        if track is not None:
            track.pre_dispatch(ctx, cmd, args[1:])
        hooks = getattr(server, "hooks", None)
        if not hooks:
            try:
                result = handler(server, ctx, args[1:])
            except BaseException:
                # a raising write verb may have PARTIALLY applied (e.g. a
                # multi-source merge that created its dest before a later
                # WRONGTYPE): other clients' tracked entries must still
                # invalidate — same possibly-applied discipline as the
                # fused-BF error path.  A spurious push for a not-applied
                # write costs one refetch; a skipped one is stale forever.
                if track is not None:
                    try:
                        track.post_dispatch(ctx, cmd, args[1:])
                    except Exception:
                        pass  # never mask the primary error
                raise
            if track is not None:
                track.post_dispatch(ctx, cmd, args[1:])
            return result
        name = cmd.decode()
        tokens = run_hooks_start(hooks, name, args[1:])
        try:
            result = handler(server, ctx, args[1:])
        except BaseException as e:
            run_hooks_end(tokens, name, e)
            if track is not None:  # possibly-applied (see no-hooks branch)
                try:
                    track.post_dispatch(ctx, cmd, args[1:])
                except Exception:
                    pass
            raise
        run_hooks_end(tokens, name, None)
        if track is not None:
            track.post_dispatch(ctx, cmd, args[1:])
        return result


def _consult_device_dispatch(plane, server, args) -> None:
    """Armed-only slow path: resolve the command's owning device (the
    single-device whitelisted verbs of SlotPlacement) and consult the chaos
    plane's per-device dispatch stream.  A raised fault is attributed to
    the lane's quarantine ledger before it surfaces."""
    eng = getattr(server, "engine", None)
    placement = getattr(eng, "placement", None)
    if placement is None:
        return
    try:
        dev_index = placement.device_index_for_command(
            [bytes(a) for a in args]
        )
    except Exception:  # noqa: BLE001 — unroutable: not a device command
        return
    if dev_index is None:
        return
    dev_id = getattr(placement.devices[dev_index], "id", dev_index)
    try:
        plane.on_device_dispatch(dev_id)
    except BaseException:
        from redisson_tpu.core import ioplane as _iop

        _iop.note_device_fault(dev_id, "kernel_launch")
        raise


REGISTRY = Registry()
register = REGISTRY.register


def _s(b: bytes) -> str:
    return b.decode() if isinstance(b, (bytes, bytearray)) else str(b)


def _int(b) -> int:
    try:
        return int(b)
    except (TypeError, ValueError):
        raise RespError("ERR value is not an integer or out of range")



# verb families live in server/verbs/*; importing the package registers
# every handler into REGISTRY (split r5: registry.py was a 4,702-line
# monolith; the families + shared prelude set now live per-module)
from redisson_tpu.server import verbs  # noqa: E402,F401  (registration side effect)

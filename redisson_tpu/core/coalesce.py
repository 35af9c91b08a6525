"""Adaptive cross-object coalescing plane (ISSUE 2 tentpole).

The batch layer (core/batch.py) and the server's pipelined frames
(server/server.py) both arrive at the same shape of work: a RUN of same-verb
bloom ops against DIFFERENT filters in one pipeline window — the config-5
fan-out (64 per-tenant filters, one BF.MADD64 + one BF.MEXISTS64 each).
Ungrouped that costs one device dispatch per (verb, object); each dispatch
pays the fixed XLA-dispatch overhead (~10-100us on-chip), so a 64-filter
wave pays it 64 times for work one kernel could do.

This module fuses such a run into ONE kernel call: filters that share
geometry (same m, k, hash, physical plane size) are stacked into a small
bank inside the program, every op's keys concatenate into one packed (3, B)
transfer buffer whose first row is the SEGMENT SLOT (which filter each key
probes), and the bank kernels' bodies (core/kernels.py — flat
`slot*stride + idx` indexing) execute the whole run.  Results scatter back
to each issuer by segment offset; adds write each filter's new plane back
under the same locked_many window that ordered the dispatch.

ONE SHAPE A GEOMETRY.  Which filters of a frame share a device, and where a
socket read cut the frame, change with every frame; a program whose shape
followed them (F planes, the 1/8-octave row bucket of 100 x F rows) was a
new XLA compile nearly every frame.  A stacked dispatch therefore always
stacks STACK_PLANES planes — the run's F, padded by repeating its first
plane; no row names a padding plane and none is written back — and pads its
rows to one of the four STACK_ROW_BUCKETS.  The stack is an HBM-side copy of
STACK_PLANES x S bytes (6 MB for 10,000-key filters), cheap next to F
dispatch overheads on a device the host cannot keep busy.  Longer runs are
cut at command boundaries into several dispatches (plan_stacked_chunks); a
command with more rows than the largest bucket is dispatched alone, as any
command outside a run is.  The first stacked dispatch of a geometry compiles
the whole set — two verbs x four row buckets — on every device of the
placement (_warm_geometry), so no later frame meets a cold program.

WAVES.  A client that flushes one batch over its tenants writes each
tenant's commands together (set, or, xor, count, then the next tenant), so
the same-verb runs of a frame have length 1.  But a device-sharded segment
promises per-key order only, so inside one device's bucket the commands on
DIFFERENT keys are regrouped into same-verb waves (plan_waves), per-key order
kept, and each wave is one stacked dispatch: the bloom runs above, and the
bitset forms SETBITSB, BITOP OR / XOR and BITCOUNT over STACK_PLANES planes
of one shape (the "bitset waves" section below).

Semantics preserved exactly:
  * per-issuer results: segment offsets are computed host-side from the
    submitted lengths, so every reply slices back to its op in order;
  * adds: "newly" is evaluated against the window-start plane — identical
    to the single-group semantics for duplicate keys inside one flush; a
    run with the SAME filter named twice under `add` is ineligible (the
    second group must see the first's bits, which one dispatch cannot do);
  * locking: the whole fused dispatch runs under engine.locked_many over
    the touched names (sorted order, deadlock-free), the same exclusion a
    per-group dispatch takes per name.

Ineligible runs (mixed geometry, codec keys, missing records, duplicate add
names, planes too large to stack, more planes or rows than one stacked
dispatch holds) raise CoalesceIneligible — callers fall back to the
per-group path, so coalescing is a pure fast path, never a semantics change.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from redisson_tpu.core import kernels as K
from redisson_tpu.utils import hashing as H


class CoalesceIneligible(Exception):
    """Run cannot fuse; caller must dispatch per group."""


# -- which commands have a stacked form, and the frame plans made from it ------

# blob sketch verbs whose commands ride one stacked-bank kernel dispatch
# (server/verbs/sketch.py coalesce_bloom_run).  A client that orders a
# shard's frame to keep same-verb commands adjacent (the natural order of a
# fan-out batch) gets maximal runs for free.
COALESCIBLE_BLOB_VERBS = frozenset((b"BF.MADD64", b"BF.MEXISTS64"))

_STACKED_BITOPS = (b"OR", b"XOR")

# FT.SEARCH / FT.MSEARCH with a KNN arm: the commands of a frame's run that
# name one index and one query text score as ONE stacked dispatch over the
# index's embedding bank (server/verbs/modules.py coalesce_knn_run), which
# reads the bank once for all of them.  A wave holds as many commands as the
# largest query bucket holds vectors (services/vector.py KNN_QUERY_BUCKETS).
KNN_VERBS = frozenset((b"FT.SEARCH", b"FT.MSEARCH"))
KNN_FORM = b"FT.KNN"
KNN_STACK_MAX = 64


def _frame_verb(cmd) -> Optional[bytes]:
    """The verb of one parsed command; None for a malformed one (an empty
    array, nested arrays, ints): it joins no group, and the per-command
    path replies its error."""
    if (
        isinstance(cmd, list)
        and cmd
        and all(isinstance(a, (bytes, bytearray)) for a in cmd)
    ):
        return bytes(cmd[0]).upper()
    return None


def _run_family(verb: Optional[bytes]) -> Optional[bytes]:
    """What consecutive commands must share to be one run: the verb of a BF
    blob command, KNN_FORM for either search verb, None for the rest."""
    if verb in COALESCIBLE_BLOB_VERBS:
        return verb
    return KNN_FORM if verb in KNN_VERBS else None


def _verb_runs(verbs: List[Optional[bytes]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    families = [_run_family(v) for v in verbs]
    i, n = 0, len(verbs)
    while i < n:
        family = families[i]
        if family is None:
            i += 1
            continue
        j = i + 1
        while j < n and families[j] == family:
            j += 1
        if j - i >= 2:
            out.append((i, j))
        i = j
    return out


def coalescible_frame_runs(cmds: List[Any]) -> List[Tuple[int, int]]:
    """Maximal [start, end) runs of two or more CONSECUTIVE same-verb
    coalescible blob commands, or search commands of either verb, in one
    pipelined frame.  Pure scan: each run is dispatched as one group;
    everything outside the runs dispatches per command, so frame order is
    untouched."""
    return _verb_runs([_frame_verb(c) for c in cmds])


def _admitted(lo: int, hi: int, shed_mask) -> List[int]:
    if shed_mask is None:
        return list(range(lo, hi))
    return [i for i in range(lo, hi) if not shed_mask[i]]


def serial_plan(n: int, shed_mask=None) -> List[Tuple[str, Any]]:
    """The frame plan that is right for every frame: each admitted command
    alone, in frame order (plan_frame_runs says what a plan is)."""
    admitted = _admitted(0, n, shed_mask)
    return [("serial", admitted)] if admitted else []


def plan_frame_runs(commands: List[Any], shed_mask=None) -> List[Tuple[str, Any]]:
    """The frame plan of an engine with NO placement (a placed one:
    server/placement.py plan_frame).  A plan is a list of segments the
    server runs one after the other:

        ("serial", [i, ...])            the commands in frame order
        ("buckets", {lane: [i, ...]})   a lane's commands are one job, the
                                        lanes' jobs run side by side; only
                                        per-key order is kept

    over the frame positions of the ADMITTED commands — a shed position
    (``shed_mask``, QoS) is answered in place, is in no segment, and no
    group spans one (runs_within_admission).  With no placement the one
    lane is None and a bucket is a run of two or more consecutive same-verb
    blob commands (coalescible_frame_runs); everything else is serial.  A
    frame that holds MULTI is serial throughout: every later command must
    append to the transaction queue in frame order, and a bucket regroups
    its commands."""
    n = len(commands)
    verbs = [_frame_verb(c) for c in commands]
    if b"MULTI" in verbs:
        return serial_plan(n, shed_mask)
    segments: List[Tuple[str, Any]] = []
    at = 0
    for s, e in runs_within_admission(_verb_runs(verbs), shed_mask) + [(n, n)]:
        between = _admitted(at, s, shed_mask)
        if between:
            segments.append(("serial", between))
        if e > s:
            segments.append(("buckets", {None: list(range(s, e))}))
        at = e
    return segments


def wave_entry(cmd):
    """(form, writes, reads, rows) of one bucket command for plan_waves:
    which stacked program the command can ride (None: per record) and the
    keys that order it against the bucket's others.  `cmd` is a list of
    bytes (placement.device_index_for_command, _frame_verb).  Forms: a BF
    blob verb (its rows add up in the wave's window); SETBITSB at the row
    bucket of its own indexes; BITOP OR / XOR; BITCOUNT.  What a record
    holds is looked at when the wave is dispatched (verbs/sketch.py
    coalesce_bitset_wave).  FT.SEARCH / FT.MSEARCH with a KNN arm: by index
    and query text (verbs/modules.py coalesce_knn_run)."""
    verb = bytes(cmd[0]).upper()
    n = len(cmd)
    if verb in COALESCIBLE_BLOB_VERBS and n >= 2:
        key = (bytes(cmd[1]),)
        rows = len(cmd[2]) // 8 if n > 2 else 0
        if verb == b"BF.MADD64":
            return (verb,), key, (), rows
        return (verb,), (), key, rows
    if verb == b"SETBITSB" and n == 3:
        bucket = stacked_row_bucket(len(cmd[2]) // 4)
        form = (verb, bucket) if bucket is not None and len(cmd[2]) >= 4 else None
        return form, (bytes(cmd[1]),), (), 0
    if verb == b"BITCOUNT" and n == 2:
        return (verb,), (), (bytes(cmd[1]),), 0
    if verb == b"BITOP" and n >= 4:
        op = bytes(cmd[1]).upper()
        form = (verb, op) if op in _STACKED_BITOPS else None
        return form, (bytes(cmd[2]),), tuple(bytes(a) for a in cmd[3:]), 0
    if verb in KNN_VERBS and n >= 3:
        # a search reads its index and writes no key; what differs in the
        # query text (filter, field, k) is another form
        index, query = bytes(cmd[1]), bytes(cmd[2])
        form = (KNN_FORM, index, query) if b"=>" in query else None
        return form, (), (b"__ftq__:" + index,), 0
    # any other verb, per record: every key counts as written
    from redisson_tpu.net import commands as C

    keys = C.command_keys(verb.decode(), cmd[1:])
    return None, tuple(bytes(k) for k in keys), (), 0


def runs_within_admission(runs, shed_mask) -> List[Tuple[int, int]]:
    """Split each [start, end) coalescible run at QoS shed boundaries
    (ISSUE 10): a shed command never dispatches, so a run spanning one would
    fuse commands the admission decision already refused — and, worse, a
    fused ADD run that partially applied could never be re-dispatched
    (at-most-once).  Runs therefore form per ADMITTED window only: each run
    is cut into its maximal admitted sub-runs, and sub-runs shorter than 2
    fall back to per-command dispatch.  ``shed_mask`` None (fully admitted
    frame) returns ``runs`` unchanged — the disarmed path costs nothing."""
    if shed_mask is None:
        return list(runs)
    out: List[Tuple[int, int]] = []
    for start, end in runs:
        i = start
        while i < end:
            if shed_mask[i]:
                i += 1
                continue
            j = i + 1
            while j < end and not shed_mask[j]:
                j += 1
            if j - i >= 2:
                out.append((i, j))
            i = j
    return out


def plan_subwindows(items: Sequence[int], target: int) -> List[Tuple[int, int]]:
    """Partition one coalescible run into preemptible sub-windows
    (ISSUE 18): given the per-command device-item counts of a run's
    commands, return [start, end) chunks (indices into the run) such that
    each chunk's total stays within ``target`` items — the bound on how
    long one sub-window can occupy its device lane before the next
    preemption point.

    Splits happen at COMMAND boundaries only, never inside one command's
    key batch: each chunk dispatches as a self-contained fused run with
    the standard add-run at-most-once discipline (a failed chunk errors
    per-command and is never re-dispatched; earlier chunks already applied
    and replied — exactly the sub-run semantics ``runs_within_admission``
    already establishes at shed boundaries).  A single command larger than
    ``target`` therefore forms its own oversized chunk: bounding it any
    tighter would require splitting a fused apply mid-batch, which the
    at-most-once contract forbids.

    ``target <= 0`` (splitting disarmed) or a run already within target
    returns the whole run as one chunk — the historical dispatch shape.
    """
    n = len(items)
    if n == 0:
        return []
    if target <= 0 or sum(items) <= target:
        return [(0, n)]
    out: List[Tuple[int, int]] = []
    start = 0
    acc = 0
    for i, it in enumerate(items):
        if i > start and acc + it > target:
            out.append((start, i))
            start = i
            acc = 0
        acc += it
    out.append((start, n))
    return out


# -- the stacked shape -----------------------------------------------------------
# Chosen on the v5e (PERF.md section 6, PR 26): the device is idle in every
# cell that reaches this path, so padding costs microseconds of an idle chip
# and each shape it saves is a compile a frame would have waited for.
STACK_PLANES = 16
STACK_ROW_BUCKETS = (256, 1024, 4096, 16384)
# planes larger than this are not stacked: STACK_PLANES copies of one would
# hold more HBM than the dispatch overhead they save is worth
STACK_MAX_PLANE_CELLS = (64 << 20) // STACK_PLANES


def stacked_row_bucket(n: int) -> Optional[int]:
    """The row bucket a stacked dispatch of `n` rows pads to; None when no
    bucket holds them."""
    return next((b for b in STACK_ROW_BUCKETS if n <= b), None)


def stacked_prefix(lengths: Sequence[int]) -> int:
    """How many leading commands of a run (rows a command in `lengths`) one
    stacked dispatch holds: at most STACK_PLANES planes and the largest row
    bucket.  At least 1 — a command too long for any bucket stands alone
    and is dispatched per record."""
    rows = 0
    for i, n in enumerate(lengths[:STACK_PLANES]):
        rows += n
        if rows > STACK_ROW_BUCKETS[-1]:
            return max(i, 1)
    return min(len(lengths), STACK_PLANES)


def plan_stacked_chunks(lengths: Sequence[int]) -> List[Tuple[int, int]]:
    """Cut one coalescible run into [start, end) chunks one stacked dispatch
    each can hold, at command boundaries and in order (stacked_prefix)."""
    out: List[Tuple[int, int]] = []
    start = 0
    while start < len(lengths):
        end = start + stacked_prefix(lengths[start:])
        out.append((start, end))
        start = end
    return out


def plan_waves(entries) -> List[Tuple[Any, List[int]]]:
    """Regroup one device bucket's commands into WAVES: [(form, positions)],
    waves to be run in list order, each wave of a form one stacked dispatch.

    `entries[i]` = (form, writes, reads, rows) of command i in frame order:
    `form` is what a stacked program needs its members to share (None: the
    command goes per record, a wave of its own), `writes` / `reads` its keys,
    `rows` what it adds to a wave's row window (0 where the form fixes it).

    The only order a sharded segment promises is per key
    (placement.PARALLEL_VERBS), so a command may run beside any command
    whose keys it does not share.  It joins the first wave of its form, with
    room, AFTER every wave it depends on — the last that touched a key it
    writes, the last that wrote a key it reads — else it opens a new wave at
    the end.  Two commands on one key therefore never swap, and never share
    a wave unless both only read it; a command with no key keeps its place
    against every other.  Room is the stacked shape's: STACK_PLANES members
    (KNN_STACK_MAX searches) and the largest row bucket (a command too long for any bucket stands
    alone), so consecutive same-verb commands on different keys form the
    chunks plan_stacked_chunks cuts."""
    waves: List[list] = []  # [form, positions, rows]
    last_write: dict = {}
    last_touch: dict = {}
    floor = -1  # the last keyless command's wave: nothing later runs before it
    top = STACK_ROW_BUCKETS[-1]
    for i, (form, writes, reads, rows) in enumerate(entries):
        keyless = not writes and not reads
        after = len(waves) - 1 if keyless else floor
        for k in writes:
            after = max(after, last_touch.get(k, -1))
        for k in reads:
            after = max(after, last_write.get(k, -1))
        at = None
        if form is not None:
            room = KNN_STACK_MAX if form[0] == KNN_FORM else STACK_PLANES
            at = next(
                (w for w in range(after + 1, len(waves))
                 if waves[w][0] == form and len(waves[w][1]) < room
                 and waves[w][2] + rows <= top),
                None,
            )
        if at is None:
            at = len(waves)
            waves.append([form, [], 0])
        waves[at][1].append(i)
        waves[at][2] += rows
        if keyless:
            floor = at
        for k in writes:
            last_write[k] = last_touch[k] = at
        for k in reads:
            last_touch[k] = max(last_touch.get(k, -1), at)
    return [(form, members) for form, members, _rows in waves]


_PLANES_LOCK = threading.Lock()
_planes_asked = 0
_planes_stacked = 0
_cmds_offered = 0
_knn_fused = 0
_knn_shared = 0


def planes_counted() -> tuple:
    """(asked, stacked) plane totals of this process's stacked dispatches:
    planes the runs named against planes the device stacked for them.
    METRICS exports both (coalesce_planes_asked_total,
    coalesce_planes_stacked_total), always on, as kernels.count_rows does
    for rows."""
    return _planes_asked, _planes_stacked


def _count_planes(asked: int) -> None:
    global _planes_asked, _planes_stacked
    with _PLANES_LOCK:  # server worker threads dispatch side by side
        _planes_asked += asked
        _planes_stacked += STACK_PLANES


def count_offered(n: int) -> None:
    """`n` commands reached the place where the server decides between a
    stacked dispatch and per-record dispatch: a bucket of a frame's plan."""
    global _cmds_offered
    with _PLANES_LOCK:
        _cmds_offered += n


def count_knn_fused(n: int, shared: int) -> None:
    """`n` search commands rode one stacked KNN dispatch (no plane is
    stacked for them: they share the index's bank), `shared` of them
    answered from the one plan they share, as bytes."""
    global _knn_fused, _knn_shared
    with _PLANES_LOCK:
        _knn_fused += n
        _knn_shared += shared


def knn_wave_counted() -> tuple:
    """(members, shared members) of this process's stacked KNN dispatches:
    search commands that rode one, and those of them that differed from
    their wave's first in the query blob alone, shared its plan and were
    answered by the wave's encoder (verbs/modules.py coalesce_knn_run).
    METRICS exports both (knn_wave_cmds_total, knn_wave_shared_cmds_total),
    always on."""
    return _knn_fused, _knn_shared


def cmds_counted() -> tuple:
    """(offered, fused) command totals: commands the server offered to the
    coalescer (count_offered) against commands that rode a stacked dispatch
    — a member of a stacked dispatch is one plane it was asked for, or one
    search of a stacked KNN.  METRICS
    exports both (coalesce_cmds_offered_total, coalesce_cmds_fused_total),
    always on."""
    return _cmds_offered, _planes_asked + _knn_fused


def _concat_segments(engine, keys_list) -> Tuple[np.ndarray, np.ndarray, List[int]]:
    """Concatenate per-op int-key arrays into one preallocated buffer plus an
    aligned segment-slot column.  Returns (slot, keys, lengths)."""
    arrs = []
    for ks in keys_list:
        a = np.asarray(ks)
        if not engine.is_int_batch(a):
            raise CoalesceIneligible("non-integer key batch")
        arrs.append(np.ascontiguousarray(a, np.int64).reshape(-1))
    lengths = [a.shape[0] for a in arrs]
    total = sum(lengths)
    if total == 0:
        raise CoalesceIneligible("empty run")
    keys = np.empty(total, np.int64)
    slot = np.empty(total, np.int32)
    off = 0
    for s, a in enumerate(arrs):
        n = a.shape[0]
        keys[off : off + n] = a
        slot[off : off + n] = s
        off += n
    return slot, keys, lengths


# ACTUAL committed device of a plane (None = uncommitted/host: stacks with
# anything) — the ONE device-detection rule, shared process-wide
from redisson_tpu.core.ioplane import device_of as _plane_device


def _validated_records(engine, names: Sequence[str]):
    """Fetch + geometry-check the run's records.  Caller holds the locks.

    Device check (device-sharded serving, ISSUE 8): every plane in the
    stack must live on ONE device — jnp.stack across committed devices
    would have to gather through the host, which the coalescing plane must
    never do.  The server splits runs per device BEFORE coalescing
    (placement.plan_frame), so a mixed group here only happens mid-slot-
    handoff — the run simply falls back to per-record dispatch (each record
    executes on its own current device), never a host-side gather."""
    recs = []
    m = k = shape = hname = None
    device = None
    for name in names:
        rec = engine.store.get(name)
        if rec is None or rec.kind != "bloom":
            raise CoalesceIneligible(f"'{name}' is not an initialized bloom filter")
        if m is None:
            m, k = rec.meta["m"], rec.meta["k"]
            hname = rec.meta.get("hash")
            shape = rec.arrays["bits"].shape
            device = _plane_device(rec.arrays["bits"])
        elif (
            rec.meta["m"] != m
            or rec.meta["k"] != k
            or rec.meta.get("hash") != hname
            or rec.arrays["bits"].shape != shape
        ):
            raise CoalesceIneligible("mixed filter geometry in run")
        else:
            d = _plane_device(rec.arrays["bits"])
            if d is not None and device is not None and d != device:
                raise CoalesceIneligible(
                    "planes span devices (slot handoff in flight)"
                )
            device = device if device is not None else d
        recs.append(rec)
    if shape[0] > STACK_MAX_PLANE_CELLS:
        raise CoalesceIneligible("planes too large to stack")
    return recs, m, k


_WARM: set = set()
_WARM_LOCK = threading.Lock()


def _warm_geometry(plane, k: int, m: int) -> None:
    """Compile every stacked program of this geometry before any is needed:
    both verbs at every row bucket, on every device that can own such a
    filter (ioplane.warm_targets: the plane's own, and the other lanes of a
    device-sharded engine) — and the grouped fetch's programs for their
    results.  Which bucket a run pads to and which device it lands on follow
    from a frame's composition, so a set compiled as met would have its
    rarer members met late, by a frame that then waits seconds for the
    compiler.  Run once a (device, geometry): empty windows over the plane
    itself (a copy of it on the other lanes), results dropped."""
    from redisson_tpu.core import ioplane

    kind = (plane.shape[0], k, m)
    targets = ioplane.warm_targets(plane)
    if all(ioplane.target_key(t, kind) in _WARM for t in targets):
        return
    with _WARM_LOCK:
        for target in targets:
            key = ioplane.target_key(target, kind)
            if key in _WARM:
                continue
            planes = (ioplane.stand_in(plane, target[0]),) * STACK_PLANES
            for b in STACK_ROW_BUCKETS:
                tlh = K.stage(np.zeros((3, b), np.uint32))
                found = K.bloom_stack_contains_packed(planes, tlh, K.valid_n(0), k, m)
                K.bloom_stack_add_packed(planes, tlh, K.valid_n(0), k, m)
                ioplane.warm_stack_class(found)  # newly-added flags alike
            _WARM.add(key)


def _pack_window(engine, slot: np.ndarray, keys: np.ndarray, device=None):
    """(slot, keys) -> staged (3, B) uint32 transfer buffer + n_valid.
    Staged through the engine's double-buffered pool (overlap plane): one
    wave's packing overlaps the previous wave's in-flight upload.  With
    placement on, `device` selects that device's LANE pool so two devices'
    waves never contend on one slot pair (ISSUE 8)."""
    n = keys.shape[0]
    b = stacked_row_bucket(n)
    lo, hi = H.int_keys_to_u32_pair(keys)
    return K.pack_rows(slot, lo, hi, size=b, pool=engine.staging_pool(device)), n


def _stacked_window(engine, names: Sequence[str], keys_list):
    """The packed window of one stacked dispatch, or CoalesceIneligible when
    the run is more than one holds (callers cut runs with
    plan_stacked_chunks before they get here)."""
    if len(names) > STACK_PLANES:
        raise CoalesceIneligible("more filters than one stacked dispatch holds")
    slot, keys, lengths = _concat_segments(engine, keys_list)
    if stacked_row_bucket(keys.shape[0]) is None:
        raise CoalesceIneligible("more rows than one stacked dispatch holds")
    tlh, n = _pack_window(
        engine, slot, keys, device=engine.device_for_name(names[0])
    )
    return tlh, n, lengths


def _stacked_planes(recs, k: int, m: int) -> tuple:
    """The run's planes padded to STACK_PLANES by repeating the first: a
    padding plane is read by no row (segment slots stop at the run's last
    filter) and changes no answer.  Counts the padding, and compiles the
    geometry's programs if this is its first stacked dispatch."""
    planes = [r.arrays["bits"] for r in recs]
    _warm_geometry(planes[0], k, m)
    _count_planes(len(planes))
    return tuple(planes) + (planes[0],) * (STACK_PLANES - len(planes))


def fused_bloom_contains_async(engine, names: Sequence[str], keys_list):
    """ONE dispatch for a contains run over at most STACK_PLANES
    same-geometry filters (kernels.bloom_stack_contains_packed).

    Returns (device bool array over the concatenated window, padded to its
    row bucket; lengths) — slice issuer i's reply at [sum(lengths[:i]),
    +lengths[i]).  No host sync: callers force on their own result path
    (frame-level gather on the server, np.asarray in the batch layer) and
    slice on the host — a device-side slice a reply would be a program an
    offset."""
    tlh, n, lengths = _stacked_window(engine, names, keys_list)
    with engine.locked_many(set(names)):
        recs, m, k = _validated_records(engine, names)
        planes = _stacked_planes(recs, k, m)
        found = K.bloom_stack_contains_packed(planes, tlh, K.valid_n(n), k, m)
        K.count_rows(n, K.rows_issued(n, tlh.shape[1]))
    return found, lengths


def fused_bloom_add_async(engine, names: Sequence[str], keys_list):
    """ONE dispatch for an add run over at most STACK_PLANES DISTINCT
    same-geometry filters (kernels.bloom_stack_add_packed); writes each
    filter's new plane back under the run's locks — the run's own planes
    only, never a padding plane's.  Returns (device newly-added bool array,
    lengths)."""
    if len(set(names)) != len(names):
        raise CoalesceIneligible(
            "duplicate filter in add run (second group must observe the first)"
        )
    tlh, n, lengths = _stacked_window(engine, names, keys_list)
    with engine.locked_many(set(names)):
        recs, m, k = _validated_records(engine, names)
        planes = _stacked_planes(recs, k, m)
        new_planes, newly = K.bloom_stack_add_packed(planes, tlh, K.valid_n(n), k, m)
        K.count_rows(n, tlh.shape[1])
        for rec, plane in zip(recs, new_planes):  # stops at the run's last
            rec.arrays["bits"] = plane
            rec.version += 1
    return newly, lengths


def fused_bloom_pair_async(engine, name: str, add_keys, probe_keys):
    """The hot add-then-probe PAIR on one filter as a single fused program
    (kernels.bloom_fused_add_contains): the probe observes the adds, the
    plane stays donated/resident between the scatter and the gather.
    Returns (device newly bool, n_add, device found bool, n_probe)."""
    add_arr = np.asarray(add_keys)
    probe_arr = np.asarray(probe_keys)
    if not (engine.is_int_batch(add_arr) and engine.is_int_batch(probe_arr)):
        raise CoalesceIneligible("non-integer key batch")
    if add_arr.size == 0 or probe_arr.size == 0:
        raise CoalesceIneligible("empty side of fused pair")
    kind_a, lh_a, n_a = engine.pack_keys(add_arr, None)
    kind_p, lh_p, n_p = engine.pack_keys(probe_arr, None)
    if kind_a != "u64" or kind_p != "u64":
        raise CoalesceIneligible("fused pair requires u64 key packing")
    with engine.locked(name):
        rec = engine.store.get(name)
        if rec is None or rec.kind != "bloom":
            raise CoalesceIneligible(f"'{name}' is not an initialized bloom filter")
        m, k = rec.meta["m"], rec.meta["k"]
        bits, newly, found = K.bloom_fused_add_contains(
            rec.arrays["bits"], lh_a, K.valid_n(n_a), lh_p, K.valid_n(n_p), k, m
        )
        rec.arrays["bits"] = bits
        rec.version += 1
    return newly, n_a, found, n_p


# -- bitset waves ------------------------------------------------------------------
# A wave (plan_waves) of SETBITSB, of BITOP OR / XOR or of BITCOUNT commands
# on different bitsets of one plane shape is ONE program over STACK_PLANES
# planes (kernels.bitset_stack_*), as a bloom run is.  A member the forms do
# not cover — a missing or wrong-typed record, a plane of another shape or
# device, an index past the plane (the per-record path grows it) — is left
# out and told to the caller, which dispatches it per record: the members of
# a wave share no key, so which of them runs first changes nothing.


def _bitset_rec(engine, name: str, like=None):
    """`name`'s record where it holds a bitset plane a stacked dispatch can
    take — of `like`'s shape and device, where given — else None.  Caller
    holds the lock."""
    rec = engine.store.get(name)
    if rec is None or rec.kind != "bitset":
        return None
    plane = rec.arrays["bits"]
    if plane.shape[0] > STACK_MAX_PLANE_CELLS or rec.meta["nbits"] != plane.shape[0]:
        return None  # a logical size short of the plane: per record masks it
    if like is not None and (
        plane.shape != like.shape or _plane_device(plane) != _plane_device(like)
    ):
        return None
    return rec


def _wave_guard(engine) -> None:
    """Conditions under which the per-record handlers do more than the
    stacked forms know of: inside a migration window a missing key redirects
    (store.absent_guard), under a name mapper a key is not its record's
    name."""
    if engine.store.absent_guard is not None:
        raise CoalesceIneligible("migration window open")
    if getattr(engine.config, "name_mapper", None) is not None:
        raise CoalesceIneligible("name mapper configured")


class _StandIns:
    """The resident padding of ONE (device, plane shape): STACK_PLANES planes
    a writing wave donates in the places its members leave free, replaced by
    what the program returns for them — what they hold is never read.  A
    donated tuple may not name one buffer twice, so padding cannot repeat a
    member's plane as a bloom run's does."""

    def __init__(self, like, device):
        import jax

        self.lock = threading.Lock()
        host = np.zeros(like.shape, like.dtype)
        self.planes = [jax.device_put(host, device) for _ in range(STACK_PLANES)]


# (device id, plane shape) -> _StandIns, least recently used first; bounded,
# since a store may hold bitsets of any number of sizes
_STAND_INS: "OrderedDict[tuple, _StandIns]" = OrderedDict()
_STAND_INS_MAX = 16
_STAND_INS_LOCK = threading.Lock()


def _donating(like, device, recs: Sequence[Any], call):
    """One writing wave: `call(stack)` -> (new planes, value) over the planes
    of `recs`, padded to STACK_PLANES with the stand-ins of `like`'s shape on
    `device`; each record gets its new plane and its version moves, the
    stand-ins are replaced by what came back in their places.  Returns
    `value`.  Caller holds the records' locks.  One wave at a time borrows a
    set; a call that fails may have consumed the buffers it was given, so
    the set is dropped and made anew by the next wave."""
    key = (getattr(device, "id", None), like.shape)
    with _STAND_INS_LOCK:
        pads = _STAND_INS.get(key)
        if pads is None:
            pads = _STAND_INS[key] = _StandIns(like, device)
            if len(_STAND_INS) > _STAND_INS_MAX:
                _STAND_INS.popitem(last=False)  # a wave that holds it keeps it alive
        _STAND_INS.move_to_end(key)
    free = STACK_PLANES - len(recs)
    with pads.lock:
        try:
            new, value = call(
                tuple(r.arrays["bits"] for r in recs) + tuple(pads.planes[:free])
            )
        except BaseException:
            with _STAND_INS_LOCK:
                _STAND_INS.pop(key, None)
            raise
        pads.planes[:free] = new[len(recs):]
    for rec, plane in zip(recs, new):  # stops at the wave's last member
        rec.arrays["bits"] = plane
        rec.version += 1
    return value


def _set_window(idx_list: Sequence[np.ndarray], rows: int, device):
    """The (STACK_PLANES, rows) index window of one SETBITSB wave, staged
    once, on the wave's device."""
    import jax

    window = np.full((STACK_PLANES, rows), K.NO_INDEX, np.int32)
    for i, idx in enumerate(idx_list):
        window[i, : idx.shape[0]] = idx
    return jax.device_put(window, device)


def _warm_bitset_shape(plane) -> None:
    """_warm_geometry's discipline for the bitset waves: the first wave of a
    plane shape compiles set (at every row bucket), or, xor and count on
    every device that can own such a bitset — on stand-ins, results dropped
    — and the grouped fetch's programs for their results."""
    import jax

    from redisson_tpu.core import ioplane

    kind = ("bitset", plane.shape[0])
    targets = ioplane.warm_targets(plane)
    if all(ioplane.target_key(t, kind) in _WARM for t in targets):
        return
    with _WARM_LOCK:
        for target in targets:
            key = ioplane.target_key(target, kind)
            if key in _WARM:
                continue
            device = target[0]
            for b in STACK_ROW_BUCKETS:
                window = _set_window((), b, device)
                ioplane.warm_stack_class(_donating(
                    plane, device, (), lambda stack: K.bitset_stack_set(stack, window)
                ))
            src = (jax.device_put(np.zeros(plane.shape, plane.dtype), device),)
            for op in K._BIT_OPS:
                lengths = _donating(
                    plane, device, (),
                    lambda stack: K.bitset_stack_op(stack, src * STACK_PLANES, op),
                )
            ioplane.warm_stack_class(lengths)  # the counts alike
            K.bitset_stack_popcount(src * STACK_PLANES)
            _WARM.add(key)


def fused_bitset_set_async(engine, names: Sequence[str], idx_list):
    """ONE dispatch for a SETBITSB wave over at most STACK_PLANES DISTINCT
    bitsets (kernels.bitset_stack_set): writes each member's new plane back
    under the wave's locks.  Returns (device previous bits, (STACK_PLANES,
    R) uint8; rows) — rows[i] is member i's row of it, None where the member
    is left to the per-record path (no such bitset, another shape or
    device, an index outside the plane)."""
    if len(names) > STACK_PLANES or len(set(names)) != len(names):
        raise CoalesceIneligible("more or repeated bitsets in a set wave")
    _wave_guard(engine)
    bucket = stacked_row_bucket(max(idx.shape[0] for idx in idx_list))
    if bucket is None:
        raise CoalesceIneligible("more rows than one stacked dispatch holds")
    rows: List[Optional[int]] = [None] * len(names)
    with engine.locked_many(set(names)):
        recs: list = []
        took: list = []
        for i, (name, idx) in enumerate(zip(names, idx_list)):
            like = recs[0].arrays["bits"] if recs else None
            rec = _bitset_rec(engine, name, like) if idx.shape[0] else None
            if rec is None or int(idx.min()) < 0 or int(idx.max()) >= rec.meta["nbits"]:
                continue
            rows[i] = len(recs)
            recs.append(rec)
            took.append(idx)
        if not recs:
            raise CoalesceIneligible("no member a stacked set can take")
        first = recs[0].arrays["bits"]
        device = _plane_device(first)
        _warm_bitset_shape(first)
        _count_planes(len(recs))
        window = _set_window(took, bucket, device)
        old = _donating(
            first, device, recs, lambda stack: K.bitset_stack_set(stack, window)
        )
    return old, rows


def fused_bitop_async(engine, op: str, pairs: Sequence[Tuple[str, str]]):
    """ONE dispatch for a wave of BITOP `op` (OR / XOR) over at most
    STACK_PLANES (dest, source) pairs, dest = dest `op` source
    (kernels.bitset_stack_op); no dest is named twice or as a source.
    Returns (device length hints, (STACK_PLANES,) int32; rows) as
    fused_bitset_set_async does; a member whose two bitsets are not both
    there, of one shape and on one device is left to the per-record path."""
    dests = [d for d, _s in pairs]
    if (
        len(pairs) > STACK_PLANES or len(set(dests)) != len(dests)
        or set(dests) & {s for _d, s in pairs}
    ):
        raise CoalesceIneligible("more or dependent pairs in a bitop wave")
    _wave_guard(engine)
    rows: List[Optional[int]] = [None] * len(pairs)
    with engine.locked_many({n for pair in pairs for n in pair}):
        recs: list = []
        srcs: list = []
        for i, (dest, src) in enumerate(pairs):
            like = recs[0].arrays["bits"] if recs else None
            rec = _bitset_rec(engine, dest, like)
            other = _bitset_rec(engine, src, rec.arrays["bits"]) if rec is not None else None
            if other is None:
                continue
            rows[i] = len(recs)
            recs.append(rec)
            srcs.append(other.arrays["bits"])
        if not recs:
            raise CoalesceIneligible("no member a stacked bitop can take")
        first = recs[0].arrays["bits"]
        _warm_bitset_shape(first)
        _count_planes(len(recs))
        srcs += srcs[:1] * (STACK_PLANES - len(srcs))  # read only: may repeat
        lengths = _donating(
            first, _plane_device(first), recs,
            lambda stack: K.bitset_stack_op(stack, tuple(srcs), op),
        )
    return lengths, rows


def fused_bitcount_async(engine, names: Sequence[str]):
    """ONE dispatch for a BITCOUNT wave over at most STACK_PLANES bitsets
    (kernels.bitset_stack_popcount): (device counts, (STACK_PLANES,) int32;
    rows) as fused_bitset_set_async does.  No host sync: the counts ride
    the frame's grouped fetch."""
    if len(names) > STACK_PLANES:
        raise CoalesceIneligible("more bitsets than one stacked dispatch holds")
    _wave_guard(engine)
    rows: List[Optional[int]] = [None] * len(names)
    with engine.locked_many(set(names)):
        planes: list = []
        for i, name in enumerate(names):
            rec = _bitset_rec(engine, name, planes[0] if planes else None)
            if rec is not None:
                rows[i] = len(planes)
                planes.append(rec.arrays["bits"])
        if not planes:
            raise CoalesceIneligible("no member a stacked count can take")
        _warm_bitset_shape(planes[0])
        _count_planes(len(planes))
        planes += planes[:1] * (STACK_PLANES - len(planes))
        counts = K.bitset_stack_popcount(tuple(planes))
    return counts, rows

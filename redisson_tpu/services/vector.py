"""Device-accelerated vector search: FT VECTOR fields + KNN banks.

Parity target: RediSearch's ``FT.CREATE ... SCHEMA f VECTOR FLAT 6 TYPE
FLOAT32 DIM d DISTANCE_METRIC {L2|COSINE|IP}`` and the ``(*)=>[KNN k @f $v]``
query arm of FT.SEARCH (RedissonSearch.java drives the same verbs).  The
reference scores every document per-query in the RediSearch C module; here an
index's embeddings live as ONE device-resident ``(capacity, dim)`` bank and a
FLAT KNN query is a single jitted matmul-(+norm)-top-k kernel
(core/kernels.knn_topk) — the MXU replaces the per-doc loop, exactly the
trade the numeric plane already made for range predicates.

Three scaling axes compose on top of FLAT (ISSUEs 14/15), all behind the
recall gate that keeps them honest:

  * **IVF** (``VECTOR IVF ... NLIST n [NPROBE p]``) — a coarse k-means
    centroid bank (kernels.kmeans_step over the host mirror, trained at a
    build threshold and retrained on growth drift) routes each query
    through one small (Q, d) x (d, nlist) matmul; only the rows of the
    top-``nprobe`` cells are gathered and scored
    (kernels.knn_ivf_topk).  Per-cell row lists ship as a CSR-style
    uniform-stride device index ((nlist, cell_cap) int32, sentinel-padded)
    that lives IN the bank's record — centroids + cells + bank move
    together through fenced rebalances and die together on DROPINDEX.
  * **FP16 / INT8 storage** (``TYPE FLOAT16|INT8``) — bank blocks compress
    at upload (two f16 / four int8 lanes per packed uint32 word; INT8
    carries a symmetric per-row scale) and decompress INSIDE the scoring
    kernel, so HBM holds 2-4x more rows per chip and the MXU still sees
    one fused program.  The host mirror stores the DEQUANTIZED values, so
    the disarmed path and the recall oracle score exactly what the device
    scores.
  * **Mesh sharding** (``SHARDS n``, ISSUE 15) — the bank splits ROW-WISE
    into n shard records, each pinned to a distinct local device through
    its own ``{hashtag}`` slot (ShardedEmbeddingBank), so N x d scales
    past one chip's HBM — the FAISS shard-then-merge pattern (Johnson et
    al. 2017) under this repo's record/placement discipline.  Ingest
    routes each new rowid to the least-full shard (one packed H2D per
    shard per flush through that shard device's lane staging pool); a
    query fans per-shard matmul/IVF-gather-score + local top-k legs out
    across the lanes and merges the per-shard winners ON DEVICE
    (kernels.knn_sharded_merge: concat + lax.top_k — a d2d colocate of
    (Q, k) tops, never a host gather; IOStats.host_colocations stays 0).
    Each shard is a full EmbeddingBank, so IVF and FP16/INT8 compose with
    sharding — all three axes multiply.

Bank layout (the bloom-bank discipline generalized to float rows):

  * **Block-appended, never re-uploaded** — ingested rows buffer host-side
    and flush to the device as ONE packed ``(P, cols)`` uint32 transfer
    (row index + bias bits [+ scale bits] + bitcast row lanes) through the
    engine's double-buffered staging pool; a stream of single-doc ingests
    costs O(N/block) H2D transfers, not O(N) full-bank uploads.
  * **Capacity growth is an HBM copy** — the grown plane is zero-filled on
    device and the old rows copy device-side (kernels.rowbank_grow); host
    rows are never re-staged.
  * **Record-backed, slot-placed** — each bank lives in a DeviceStore
    record named ``__ftvec__{<index>}:<field>`` (the ``{hashtag}`` pins the
    record to the INDEX's slot), so placement commits it to the slot-owner
    device, fenced journaled device rebalances move it like any record, and
    FT.DROPINDEX tears it down through the ordinary store path (census
    flat).
  * **Deletions are a bias, not a compaction** — every row carries an f32
    bias (0 live, +inf dead) added into the distance row inside the kernel;
    hybrid queries lower their host-side prefilter mask onto the score
    matrix as one more additive bias operand.

Results come back as demand-driven device handles: the server's FT verbs
wrap (dist, idx) in a LazyReply so M concurrent KNN frames drain through the
frame-grouped transfer (<= M+1 blocking syncs, the overlap-plane contract),
and dispatch holds the owning device's lane gate so KNN occupancy is
accounted like every other verb.

Disarm with ``RTPU_NO_VECTOR=1`` / ``set_vector(False)``: scoring runs a
pure-NumPy float32 path with the same formulas, the same canonical IVF
index (centroids, assignments and cell lists are HOST state — whichever
path trained them, both score through them) and the same stable tie-break,
so replies are identical with the device path off (the A/B discipline of
every plane in this repo).
"""
from __future__ import annotations

import os
import threading
import weakref
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from redisson_tpu.core import residency as _res

# device chaos plane (ISSUE 19): the bank create/grow allocation chokepoint
# consults the process-global fault plane net/client.py hosts — disarmed
# cost is one global load + `is None` (the zero-alloc guard discipline)
from redisson_tpu.net import client as _net
from redisson_tpu.net.resp import RespError

# -- global switch (same discipline as ioplane.set_overlap) -------------------

_vector = os.environ.get("RTPU_NO_VECTOR", "") not in ("1", "true", "yes")


def vector_enabled() -> bool:
    return _vector


def set_vector(on: bool) -> bool:
    """Flip the process-global device-KNN switch; returns the previous value
    (callers restore it — the A/B discipline of bench.py config 7)."""
    global _vector
    prev = _vector
    _vector = bool(on)
    return prev


VECTOR_METRICS = ("L2", "COSINE", "IP")
VECTOR_DTYPES = ("FLOAT32", "FLOAT16", "INT8")
VECTOR_ALGOS = ("FLAT", "IVF")
DEFAULT_BLOCK = 256  # rows buffered per H2D flush (the O(N/block) contract)
DEFAULT_NPROBE = 8
RETRAIN_GROWTH = 1.5   # retrain once the corpus grew this much past the
                       # last training set (the drift heuristic)
KMEANS_ITERS = 6

# -- live tuning knobs (ISSUE 15 satellite) ------------------------------------
# The next chip run must re-sweep the IVF gather geometry around REAL HBM
# gather bandwidth (ROADMAP chip-run note) — these must move via env /
# ``CONFIG SET``, never a code edit.  Read at use time, so a live SET takes
# effect at the next cell rebuild / capacity growth.

IVF_CELL_IMBALANCE = float(os.environ.get("RTPU_IVF_CELL_IMBALANCE", "3"))
# cell_cap bound = IVF_CELL_IMBALANCE x mean occupancy; rows past it spill
# to their next-nearest cell (recall-vs-gather-width trade, _rebuild_cells)

IVF_CELL_CAP_MAX = int(os.environ.get("RTPU_IVF_CELL_CAP_MAX", "0"))
# hard ceiling on cell_cap — the per-query candidate gather is
# O(nprobe x cell_cap), so this IS the gather-width dial; 0 = unbounded.
# Rows a capped cell cannot hold (even after spilling) drop from the cell
# table — the recall gate keeps that trade visible.

DEVICE_BYTES_BUDGET = int(os.environ.get("RTPU_FTVEC_DEVICE_BUDGET", "0"))
# per-bank-per-device HBM budget in bytes (0 = unlimited) — the first
# enforced brick of the ROADMAP HBM-capacity ledger: a single-device bank
# that would grow past it raises VectorBudgetError at flush, while a
# SHARDS n bank splits the same corpus into n under-budget shard banks
# (the config7s capacity demo).


def set_ivf_cell_imbalance(value: float) -> float:
    """Set the cell_cap imbalance bound; returns the previous value."""
    global IVF_CELL_IMBALANCE
    prev, IVF_CELL_IMBALANCE = IVF_CELL_IMBALANCE, max(1.0, float(value))
    return prev


def set_ivf_cell_cap_max(value: int) -> int:
    """Set the gather-width ceiling (0 = unbounded); returns the previous."""
    global IVF_CELL_CAP_MAX
    prev, IVF_CELL_CAP_MAX = IVF_CELL_CAP_MAX, max(0, int(value))
    return prev


def set_device_bytes_budget(value: int) -> int:
    """Set the per-bank device-bytes budget (0 = unlimited); returns prev."""
    global DEVICE_BYTES_BUDGET
    prev, DEVICE_BYTES_BUDGET = DEVICE_BYTES_BUDGET, max(0, int(value))
    return prev


class VectorBudgetError(RuntimeError):
    """A bank flush would grow one device's bank past DEVICE_BYTES_BUDGET —
    the corpus needs SHARDS (or a compressed TYPE) to fit the mesh."""


class DeviceOomError(RespError):
    """A device allocation failed (HBM ``RESOURCE_EXHAUSTED``) growing a
    bank.  Subclassing RespError makes every dispatch layer encode it as a
    clean retryable ``-OOM`` reply instead of a dead connection; the FIXED
    message keeps armed/disarmed (and RTPU_NO_NATIVE) replies
    byte-identical.  The rows that triggered the growth are KEPT pending
    (flush_pending restores them), so nothing acked is lost."""

    def __init__(self, name: str):
        super().__init__(
            f"OOM device out of memory growing vector bank '{name}'; "
            f"rows kept pending"
        )


def _is_resource_exhausted(e: BaseException) -> bool:
    """The HBM-exhaustion shape real JAX raises: an ``XlaRuntimeError`` /
    RuntimeError whose message leads with RESOURCE_EXHAUSTED.  Matched on
    the message, never the class, so the chaos plane's RuntimeError
    fallback exercises the same recovery path."""
    return (
        isinstance(e, RuntimeError)
        and str(e).lstrip().startswith("RESOURCE_EXHAUSTED")
    )

_IVF_SENTINEL = np.int32(0x3FFFFFFF)  # padded cells entry: never a live row


@dataclass
class VectorFieldSpec:
    """One FT VECTOR schema attribute.

    ``algo``   — FLAT (exact) or IVF (sub-linear, recall-gated).
    ``dtype``  — FLOAT32, or the compressed bank formats FLOAT16 / INT8
                 (symmetric per-row scale); compression composes with both
                 algorithms.
    ``nlist``  — IVF coarse-cell count (required for IVF).
    ``nprobe`` — default cells probed per query (queries may override);
                 0 resolves to min(nlist, 8).
    ``train_min`` — row count at which the coarse quantizer first trains;
                 0 resolves to max(4 * nlist, 256).  Below it IVF scores
                 FLAT (exact).
    ``shards`` — row-parallel mesh shards (ISSUE 15): 1 (default) keeps
                 the single-record bank; n > 1 splits rows across n shard
                 records pinned to distinct local devices.  IVF state and
                 compressed storage are PER SHARD, so all axes compose."""

    field: str
    dim: int
    metric: str = "COSINE"
    dtype: str = "FLOAT32"
    algo: str = "FLAT"
    nlist: int = 0
    nprobe: int = 0
    train_min: int = 0
    shards: int = 1

    def __post_init__(self):
        self.metric = str(self.metric).upper()
        self.algo = str(self.algo).upper()
        self.dtype = str(self.dtype).upper()
        self.dim = int(self.dim)
        self.nlist = int(self.nlist)
        self.nprobe = int(self.nprobe)
        self.train_min = int(self.train_min)
        self.shards = int(self.shards)
        if self.shards < 1:
            raise ValueError("SHARDS must be a positive shard count")
        if self.dim <= 0:
            raise ValueError("vector DIM must be positive")
        if self.metric not in VECTOR_METRICS:
            raise ValueError(f"unsupported DISTANCE_METRIC '{self.metric}'")
        if self.algo not in VECTOR_ALGOS:
            raise ValueError(f"unsupported vector algorithm '{self.algo}'")
        if self.dtype not in VECTOR_DTYPES:
            raise ValueError(f"unsupported vector TYPE '{self.dtype}'")
        if self.algo == "IVF":
            if self.nlist < 2:
                raise ValueError("IVF needs NLIST >= 2")
            if self.nprobe <= 0:
                self.nprobe = min(self.nlist, DEFAULT_NPROBE)
            self.nprobe = min(self.nprobe, self.nlist)
            if self.train_min <= 0:
                self.train_min = max(4 * self.nlist, 256)
        elif self.nlist or self.nprobe or self.train_min:
            raise ValueError("NLIST/NPROBE/TRAIN_MIN are IVF attributes")

    def to_meta(self) -> Dict[str, Any]:
        return {
            "field": self.field, "dim": self.dim, "metric": self.metric,
            "dtype": self.dtype, "algo": self.algo, "nlist": self.nlist,
            "nprobe": self.nprobe, "train_min": self.train_min,
            "shards": self.shards,
        }


def parse_vector_value(value, dim: int) -> Optional[np.ndarray]:
    """Decode one document's vector field into a (dim,) float32 row.

    Accepts the wire form (raw little-endian float32 bytes, the RediSearch
    HSET blob) and host forms (sequence of floats / numpy array).  Returns
    None for absent values; raises ValueError on a dimension mismatch."""
    if value is None:
        return None
    if isinstance(value, (bytes, bytearray, memoryview)):
        buf = bytes(value)
        if len(buf) != dim * 4:
            raise ValueError(
                f"vector blob is {len(buf)} bytes; DIM {dim} needs {dim * 4}"
            )
        return np.frombuffer(buf, dtype="<f4").astype(np.float32, copy=True)
    arr = np.asarray(value, dtype=np.float32).reshape(-1)
    if arr.shape[0] != dim:
        raise ValueError(f"vector has {arr.shape[0]} dims; schema says {dim}")
    return np.ascontiguousarray(arr)


def bank_record_name(index: str, field: str) -> str:
    """DeviceStore name of one index-field embedding bank.  The ``{index}``
    hashtag maps the record to the INDEX's keyspace slot, so SlotPlacement
    commits every bank of one index to that index's slot-owner device and
    indexes shard across the local mesh like any record."""
    return "__ftvec__{%s}:%s" % (index, field)


def shard_record_name(index: str, field: str, shard: int, salt: int) -> str:
    """DeviceStore name of ONE shard of a mesh-sharded bank.  The hashtag
    embeds the shard id + a salt, so each shard record owns its OWN
    keyspace slot: SlotPlacement commits it to that slot's device, fenced
    journaled rebalances / CLUSTER DEVMOVE move it like any record, and
    the constellation re-pins shard by shard — no bespoke migration
    machinery (the manifest record under bank_record_name lists these)."""
    return "__ftvec__{%s#s%d.%d}:%s" % (index, shard, salt, field)


def pick_shard_record_names(engine, index: str, field: str,
                            n: int) -> List[str]:
    """Shard record names whose slots land on DISTINCT devices: shard i
    targets device (owner(base) + i) % n_devices (SlotPlacement.device_span)
    and the hashtag salt is searched until the name's slot maps there —
    deterministic given the placement table, a few CRC16 probes per shard.
    Placement off: salt 0 (every record on the default device anyway)."""
    p = getattr(engine, "placement", None)
    if p is None:
        return [shard_record_name(index, field, i, 0) for i in range(n)]
    span = p.device_span(p.device_id_for_name(bank_record_name(index, field)),
                         n)
    names = []
    for i, want in enumerate(span):
        for salt in range(512):
            nm = shard_record_name(index, field, i, salt)
            if p.device_id_for_name(nm) == want:
                names.append(nm)
                break
        else:  # pragma: no cover — 512 probes over 16384 slots always hit
            names.append(shard_record_name(index, field, i, 0))
    return names


# The query counts a stacked KNN dispatch pads to.  A bounded set, compiled
# together the first time a frame's run reaches a bank (_warm_query_buckets),
# so what a frame holds never meets a cold program; a run longer than the
# largest is cut into several dispatches (core/coalesce.py KNN_STACK_MAX).
KNN_QUERY_BUCKETS = (1, 4, 16, 64)


def knn_query_bucket(n: int) -> int:
    """The query bucket `n` stacked queries pad to: the smallest of
    KNN_QUERY_BUCKETS that holds them, a power of two above the largest (one
    FT.MSEARCH blob of more vectors than a frame's run may stack)."""
    b = next((b for b in KNN_QUERY_BUCKETS if n <= b), KNN_QUERY_BUCKETS[-1])
    while b < n:
        b <<= 1
    return b


_KNN_LOCK = threading.Lock()
_knn_queries = 0
_knn_slots = 0
_knn_rows_scored = 0


def _count_knn(queries: int, slots: int, rows: int) -> None:
    global _knn_queries, _knn_slots, _knn_rows_scored
    with _KNN_LOCK:  # server worker threads dispatch side by side
        _knn_queries += queries
        _knn_slots += slots
        _knn_rows_scored += queries * rows


def knn_counted() -> tuple:
    """(queries, query slots, rows scored) of this process's device KNN
    dispatches: query vectors asked, the bucket slots their dispatches
    padded them to, and live rows x queries.  METRICS exports the three
    (knn_queries_total, knn_query_slots_total, knn_rows_scored_total),
    always on, as kernels.count_rows does for the sketch banks."""
    return _knn_queries, _knn_slots, _knn_rows_scored


# -- bank compression (FP16 / INT8 with symmetric per-row scale) --------------


def phys_width(dim: int, dtype: str) -> int:
    """Physical bank width: the logical dim rounded up so rows pack whole
    uint32 words in the staged upload (2 f16 / 4 int8 lanes per word).
    Padding lanes hold zeros — they add exact 0.0 to every dot product and
    norm, so scoring on the padded width equals scoring on the logical."""
    if dtype == "FLOAT16":
        return dim + (dim & 1)
    if dtype == "INT8":
        return (dim + 3) & ~3
    return dim


def quantize_row(row: np.ndarray, dtype: str, pwidth: int):
    """(stored row at physical width, scale f32, dequantized logical f32).

    The DEQUANTIZED values are what both scoring paths see: the device
    kernel widens the stored lanes in-program (kernels._bank_f32) and the
    host mirror records exactly those widened values — armed and disarmed
    scoring read the same numbers."""
    dim = row.shape[0]
    if dtype == "FLOAT16":
        stored = np.zeros(pwidth, np.float16)
        stored[:dim] = row.astype(np.float16)
        return stored, np.float32(1.0), stored[:dim].astype(np.float32)
    if dtype == "INT8":
        amax = float(np.max(np.abs(row))) if dim else 0.0
        if not np.isfinite(amax) or amax == 0.0:
            scale = np.float32(1.0)
        else:
            scale = np.float32(amax / 127.0)
        stored = np.zeros(pwidth, np.int8)
        with np.errstate(invalid="ignore"):
            q = np.clip(np.rint(row / scale), -127, 127)
        stored[:dim] = np.nan_to_num(q).astype(np.int8)
        return stored, scale, stored[:dim].astype(np.float32) * scale
    if pwidth == dim:  # float32 packs whole: nothing to pad, nothing to widen
        return row, np.float32(1.0), row
    stored = np.zeros(pwidth, np.float32)
    stored[:dim] = row
    return stored, np.float32(1.0), stored[:dim].copy()


_NP_DTYPES = {
    "FLOAT32": np.float32, "FLOAT16": np.float16, "INT8": np.int8,
}


def _pair_score_math(rows: np.ndarray, qs: np.ndarray,
                     metric: str) -> np.ndarray:
    """The per-pair score reduction shared by EVERY reply path (plain and
    sharded banks): (M, d) rows against (M, d) queries -> (M,) f32 scores.
    One routine on purpose — the armed/disarmed byte-identity contract
    hangs off these exact reductions."""
    dots = np.einsum("md,md->m", rows, qs, dtype=np.float32)
    if metric == "L2":
        q_sq = np.einsum("md,md->m", qs, qs, dtype=np.float32)
        r_sq = np.einsum("md,md->m", rows, rows, dtype=np.float32)
        return (q_sq - 2.0 * dots + r_sq).astype(np.float32)
    if metric == "COSINE":
        qn = np.sqrt(np.einsum("md,md->m", qs, qs, dtype=np.float32))
        rn = np.sqrt(np.einsum("md,md->m", rows, rows, dtype=np.float32))
        denom = qn * rn
        with np.errstate(invalid="ignore", divide="ignore"):
            cos = np.where(denom > 0.0, dots / denom, 0.0)
        return (1.0 - cos).astype(np.float32)
    return (1.0 - dots).astype(np.float32)  # IP


class DeviceRowBank:
    """Block-appended device-resident row bank (f32 / f16 / int8+scale).

    The shared substrate of the embedding banks AND the search service's
    numeric plane: rows are addressed by the index's doc rowid, mutations
    buffer host-side in ``_pending`` and flush as ONE packed upload +
    ONE scatter kernel per block (kernels.rowbank_write_packed*).  A host
    mirror is kept alongside — it feeds the pure-NumPy disarmed path, the
    recall oracle, and index rebuilds, and costs rows*width*4 host bytes
    (always f32: it stores the DEQUANTIZED values the device scores).

    This base class is STANDALONE (arrays held directly, default device) —
    the engine-free binding ``_NumericPlane`` uses.  ``RecordRowBank``
    overrides the plane seam to live inside a DeviceStore record."""

    def __init__(self, width: int, block: int = DEFAULT_BLOCK,
                 dtype: str = "FLOAT32"):
        self.width = int(width)          # logical dim
        self.dtype = str(dtype).upper()
        if self.dtype not in VECTOR_DTYPES:
            raise ValueError(f"unsupported bank dtype '{dtype}'")
        self.pwidth = phys_width(self.width, self.dtype)
        self.block = max(1, int(block))
        self.rows = 0            # logical row count (max rowid + 1)
        self.dead = 0            # rows of those whose bias is +inf (killed)
        self._cap = 0            # device capacity (rows)
        # rowid -> (bias, stored row at pwidth | None, scale)
        self._pending: Dict[int, Tuple[float, Optional[np.ndarray],
                                       np.float32]] = {}
        self._lock = threading.RLock()
        # host mirror (disarmed path / oracle): grown by doubling; always
        # f32 at the LOGICAL width, holding dequantized values
        self._host = np.zeros((0, self.width), np.float32)
        self._host_bias = np.zeros((0,), np.float32)
        # observability: the transfer discipline tests pin these
        self.h2d_flushes = 0     # packed uploads (ONE per flush)
        self.grows = 0           # device-side capacity copies
        self.dispatches = 0      # scatter kernels dispatched

    # -- packed upload geometry ----------------------------------------------

    def _packed_cols(self) -> int:
        if self.dtype == "FLOAT16":
            return 2 + self.pwidth // 2
        if self.dtype == "INT8":
            return 3 + self.pwidth // 4
        return 2 + self.pwidth

    # -- plane seam (overridden by RecordRowBank) -----------------------------

    def _get_planes(self):
        return (
            getattr(self, "_bank", None),
            getattr(self, "_bias", None),
            getattr(self, "_scale", None),
        )

    def _set_planes(self, bank, bias, scale) -> None:
        self._bank, self._bias, self._scale = bank, bias, scale

    # A bank that scores distances (EmbeddingBank) keeps its rows' squared
    # norms as one more (capacity,) plane, written with the rows and grown
    # with the bank, so no query sums the bank again.
    NORMS = False

    def _get_norms(self):
        return getattr(self, "_norms", None)

    def _set_norms(self, norms) -> None:
        self._norms = norms

    def _target_device(self):
        return None

    def _staging_pool(self):
        return None

    def _record_guard(self):
        """Mutual exclusion for device-plane mutation (record lock for the
        store-backed binding; the bank's own lock already covers standalone)."""
        return nullcontext()

    # -- host-side mutation ---------------------------------------------------

    def _mirror(self, rowid: int, bias: float, row: Optional[np.ndarray]) -> None:
        if rowid >= self._host.shape[0]:
            new_cap = max(self.block, self._host.shape[0] * 2)
            while new_cap <= rowid:
                new_cap *= 2
            grown = np.zeros((new_cap, self.width), np.float32)
            grown[: self._host.shape[0]] = self._host
            self._host = grown
            gbias = np.zeros((new_cap,), np.float32)
            gbias[: self._host_bias.shape[0]] = self._host_bias
            self._host_bias = gbias
        self._host[rowid] = 0.0 if row is None else row
        self._host_bias[rowid] = bias

    def _note_row_change(self, rowid: int) -> None:
        """Hook for derived index maintenance (EmbeddingBank's IVF plane);
        called under the bank lock on every set_row."""

    def set_row(self, rowid: int, row: Optional[np.ndarray]) -> None:
        """Install/overwrite one row.  ``row=None`` kills it: data goes to
        zeros and bias to +inf, so the row can never reach a top-k (zeros,
        not NaN — a NaN row would poison the whole distance column through
        the matmul; callers that WANT NaN semantics, like the numeric
        plane's cleared rows, pass an explicit NaN-filled row)."""
        if row is None:
            bias = np.float32(np.inf)
            stored, scale, deq = None, np.float32(1.0), None
        else:
            bias = np.float32(0.0)
            stored, scale, deq = quantize_row(
                np.asarray(row, np.float32), self.dtype, self.pwidth
            )
        with self._lock:
            was_dead = rowid < self.rows and np.isinf(self._host_bias[rowid])
            self.dead += int(row is None) - int(was_dead)
            self._mirror(rowid, float(bias), deq)
            self.rows = max(self.rows, rowid + 1)
            self._pending[rowid] = (float(bias), stored, scale)
            self._note_row_change(rowid)
            if vector_enabled() and len(self._pending) >= self.block:
                self.flush_pending()

    # -- device flush ---------------------------------------------------------

    BUDGETED = False  # RecordRowBank opts in: only device-resident banks
                      # charge the HBM ledger, never the numeric plane's
                      # engine-free standalone binding

    def _projected_device_bytes(self, cap: int) -> int:
        """Device bytes a `cap`-row bank holds: stored rows + bias plane
        (+ INT8 scale column) — the quantity DEVICE_BYTES_BUDGET bounds."""
        per_row = self.pwidth * np.dtype(_NP_DTYPES[self.dtype]).itemsize + 4
        if self.dtype == "INT8":
            per_row += 4
        return cap * per_row

    def _ensure_capacity_locked(self, needed: int) -> None:
        import jax
        import jax.numpy as jnp

        from redisson_tpu.core import kernels as K

        if needed <= self._cap:
            return
        new_cap = max(self.block, self._cap)
        while new_cap < needed:
            new_cap *= 2
        budget = DEVICE_BYTES_BUDGET
        if budget and self.BUDGETED:
            projected = self._projected_device_bytes(new_cap)
            if projected > budget:
                raise VectorBudgetError(
                    f"bank '{getattr(self, 'name', '?')}' would hold "
                    f"{projected} device bytes at capacity {new_cap} — over "
                    f"the {budget}-byte per-device budget; shard the index "
                    f"(SHARDS n) or compress its TYPE"
                )
        if self.BUDGETED:
            # residency-plane admission (ISSUE 20 bugfix): growth that would
            # push the OWNER DEVICE over device-budget-bytes first demotes
            # that device's colder clean records; VectorBudgetError is the
            # LAST resort (raised inside admit_device_alloc only when not
            # enough bytes were demotable).  Disarmed / no manager: no-op.
            eng = getattr(self, "_engine", None)
            mgr = getattr(eng, "residency", None) if eng is not None else None
            if mgr is not None and _res.tier_enabled():
                delta = (self._projected_device_bytes(new_cap)
                         - self._projected_device_bytes(self._cap))
                mgr.admit_device_alloc(
                    self._target_device(), delta,
                    exclude=(getattr(self, "name", ""),),
                )
        device = self._target_device()
        dev_id = getattr(device, "id", 0) if device is not None else 0
        # device allocation chokepoint (ISSUE 19): the injected and the
        # real RESOURCE_EXHAUSTED converge on ONE DeviceOomError below
        plane = _net._fault_plane
        if plane is not None:
            try:
                plane.on_device_alloc(
                    dev_id, self._projected_device_bytes(new_cap)
                )
            except RuntimeError as e:
                if _is_resource_exhausted(e):
                    self._oom(dev_id, e)
                raise
        jdt = {"FLOAT32": jnp.float32, "FLOAT16": jnp.float16,
               "INT8": jnp.int8}[self.dtype]
        ctx = jax.default_device(device) if device is not None else nullcontext()
        try:
            with ctx:
                grown = jnp.zeros((new_cap, self.pwidth), jdt)
                gbias = jnp.zeros((new_cap,), jnp.float32)
                gscale = (
                    jnp.ones((new_cap,), jnp.float32)
                    if self.dtype == "INT8" else None
                )
                gnorms = (
                    jnp.zeros((new_cap,), jnp.float32) if self.NORMS else None
                )
            if device is not None:
                grown = jax.device_put(grown, device)
                gbias = jax.device_put(gbias, device)
                if gscale is not None:
                    gscale = jax.device_put(gscale, device)
                if gnorms is not None:
                    gnorms = jax.device_put(gnorms, device)
            bank, bias, scale = self._get_planes()
            if bank is not None and self._cap > 0:
                if gnorms is not None:
                    gnorms = K.rowbank_grow_plane(
                        self._norms_locked(bank, scale), gnorms
                    )
                grown, gbias = K.rowbank_grow(bank, bias, grown, gbias)
                if gscale is not None and scale is not None:
                    gscale = K.rowbank_grow_plane(scale, gscale)
                self.grows += 1
        except RuntimeError as e:
            if _is_resource_exhausted(e):
                self._oom(dev_id, e)
            raise
        self._set_planes(grown, gbias, gscale)
        if gnorms is not None:
            self._set_norms(gnorms)
        self._cap = new_cap

    def _norms_locked(self, bank, scale):
        """The norms plane of `bank`; made in one pass where the record came
        without one (restored or shipped from before the plane existed)."""
        from redisson_tpu.core import kernels as K

        norms = self._get_norms()
        if norms is None or norms.shape[0] != bank.shape[0]:
            norms = K.rowbank_norms(bank, scale)
            self._set_norms(norms)
        return norms

    def _oom(self, dev_id: int, cause: BaseException) -> None:
        """HBM exhausted growing this bank: count the fault on the lane's
        quarantine ledger and surface the one fixed ``-OOM`` reply shape
        (never the raw XlaRuntimeError, never a dead connection)."""
        from redisson_tpu.core import ioplane as _iop

        _iop.note_device_fault(dev_id, "alloc_oom")
        raise DeviceOomError(getattr(self, "name", "?")) from cause

    def _pack_items(self, buf: np.ndarray, items) -> None:
        """Fill the packed upload buffer: col 0 rowid, col 1 bias bits,
        [col 2 scale bits for INT8,] remaining cols = row lanes bitcast."""
        n = len(items)
        buf[:n, 0] = np.fromiter((r for r, _v in items), np.uint32, count=n)
        buf[:n, 1] = np.fromiter(
            (b for _r, (b, _row, _s) in items), np.float32, count=n
        ).view(np.uint32)
        rows = np.zeros((n, self.pwidth), _NP_DTYPES[self.dtype])
        for i, (_r, (_b, row, _s)) in enumerate(items):
            if row is not None:
                rows[i] = row
        if self.dtype == "INT8":
            buf[:n, 2] = np.fromiter(
                (s for _r, (_b, _row, s) in items), np.float32, count=n
            ).view(np.uint32)
            buf[:n, 3:] = rows.view(np.uint32)
        else:
            buf[:n, 2:] = rows.view(np.uint32)

    def flush_pending(self) -> int:
        """Drain the pending rows to the device: ONE packed H2D + ONE
        scatter kernel regardless of how many rows accumulated.  Returns the
        number of rows flushed."""
        from redisson_tpu.core import kernels as K

        with self._lock:
            if not self._pending:
                return 0
            pending, self._pending = self._pending, {}
            try:
                with self._record_guard():
                    self._ensure_capacity_locked(self.rows)
            except (VectorBudgetError, DeviceOomError):
                # over-budget growth refused or HBM exhausted: the rows
                # stay PENDING (their mirror values are already installed),
                # so nothing is lost — a raised budget, a resharded index,
                # or a post-evacuation retry drains them later
                self._pending = pending
                raise
            with self._record_guard():
                n = len(pending)
                p = K.bucket_size(n, minimum=min(self.block, 256))
                shape = (p, self._packed_cols())
                pool = self._staging_pool()
                if pool is None:
                    buf, slot = np.zeros(shape, np.uint32), None
                else:
                    buf, slot = pool.acquire(shape, np.uint32)
                try:
                    self._pack_items(buf, sorted(pending.items()))
                    staged = K.stage(buf)
                except BaseException:
                    if pool is not None:
                        pool.release(slot)
                    raise
                if pool is not None:
                    pool.commit(slot, staged)
                bank, bias, scale = self._get_planes()
                nv = K.valid_n(n)
                if self.dtype == "INT8":
                    bank, scale, bias = K.rowbank_write_packed_i8(
                        bank, scale, bias, staged, nv
                    )
                elif self.dtype == "FLOAT16":
                    bank, bias = K.rowbank_write_packed_f16(
                        bank, bias, staged, nv
                    )
                else:
                    bank, bias = K.rowbank_write_packed(
                        bank, bias, staged, nv
                    )
                self._set_planes(bank, bias, scale)
                if self.NORMS:
                    self._set_norms(K.rowbank_write_norms(
                        self._norms_locked(bank, scale), bank, scale, staged, nv
                    ))
                self.h2d_flushes += 1
                self.dispatches += 1
            return n

    def device_planes(self) -> Tuple[Any, Any, Any, int]:
        """(bank, bias, scale, rows) with every pending row flushed — the
        kernel operand view (scale is None except for INT8 banks).  bank is
        None while the bank has never filled."""
        with self._lock:
            self.flush_pending()
            bank, bias, scale = self._get_planes()
            return bank, bias, scale, self.rows

    def host_planes(self) -> Tuple[np.ndarray, np.ndarray]:
        """(rows x width data, bias) host mirror — the disarmed scoring path
        and the brute-force oracle's input (dequantized f32)."""
        with self._lock:
            return (
                self._host[: self.rows].copy(),
                self._host_bias[: self.rows].copy(),
            )

    def device_bytes(self) -> int:
        bank, bias, scale = self._get_planes()
        total = 0
        for a in (bank, bias, scale):
            if a is not None:
                total += int(a.nbytes)
        return total

    def logical_f32_bytes(self) -> int:
        """What the same rows would cost uncompressed — the denominator of
        the compression-ratio gauge (config7_int8_bytes_ratio)."""
        return int(self._cap) * (self.width + 1) * 4

    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)


# live record-backed banks by (store identity, record name): the residency
# demoter's dirty probe consults this to pin banks with PENDING rows HOT —
# demoting mid-accumulation would still be correct (the mirror holds the
# rows) but would turn the next flush into a promote+flush double transfer.
# Weak values: a dropped index's bank unregisters itself by dying.
_LIVE_BANKS: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


def bank_has_pending(store, name: str) -> bool:
    """Lock-free dirty probe for the residency plane (len() of a dict is
    GIL-atomic; advisory — a racing flush re-touches the record and the
    touch clock pins it anyway)."""
    bank = _LIVE_BANKS.get((id(store), name))
    return bank is not None and len(getattr(bank, "_pending", ())) > 0


class RecordRowBank(DeviceRowBank):
    """DeviceRowBank whose planes live inside a DeviceStore StateRecord —
    placement commits them to the slot-owner device at creation, fenced
    journaled rebalances move them like any record, and deleting the record
    (FT.DROPINDEX) releases the device memory through the ordinary store
    teardown path."""

    KIND = "vector_bank"
    BUDGETED = True

    def __init__(self, engine, name: str, width: int,
                 block: int = DEFAULT_BLOCK, dtype: str = "FLOAT32",
                 meta: Optional[dict] = None, reset: bool = True):
        super().__init__(width, block, dtype=dtype)
        self._engine = engine
        self.name = name
        from redisson_tpu.core.store import StateRecord

        with engine.locked(name):
            if reset:
                # index definitions are host-side (engine services), so a
                # stale bank record from a dropped/rebuilt index must not
                # leak rows into the fresh one
                engine.store.delete_unguarded(name)
            rec = engine.store.get_unguarded(name)
            if rec is None:
                engine.store.put_unguarded(
                    name,
                    StateRecord(
                        kind=self.KIND,
                        meta=dict(meta or {}, rows=0, width=width,
                                  block=self.block, dtype=self.dtype),
                        arrays={},
                    ),
                )
        _LIVE_BANKS[(id(engine.store), name)] = self

    def _rec(self):
        rec = self._engine.store.get_unguarded(self.name)
        if rec is None:
            raise KeyError(f"vector bank '{self.name}' was dropped")
        # residency fault-in (ISSUE 20): EVERY bank plane read/write funnels
        # through here, so a demoted bank promotes before any caller can
        # observe its released arrays.  Same one-load disarm guard as the
        # store getters (tests/test_perf_smoke.py discovers these lines).
        plane = _res._tier_plane
        if plane is not None and rec.tier is not _res.HOT:
            plane.on_record_access(self._engine.store, self.name, rec)
        return rec

    def _get_planes(self):
        arrays = self._rec().arrays
        return arrays.get("bank"), arrays.get("bias"), arrays.get("scale")

    def _set_planes(self, bank, bias, scale) -> None:
        rec = self._rec()
        rec.arrays["bank"] = bank
        rec.arrays["bias"] = bias
        if scale is not None:
            rec.arrays["scale"] = scale
        rec.meta["rows"] = self.rows
        rec.version += 1

    def _get_norms(self):
        return self._rec().arrays.get("norms")

    def _set_norms(self, norms) -> None:
        self._rec().arrays["norms"] = norms

    def _target_device(self):
        from redisson_tpu.core.ioplane import device_of

        bank, _bias, _scale = self._get_planes()
        if bank is not None:
            dev = device_of(bank)
            if dev is not None:
                return dev
        return self._engine.device_for_name(self.name)

    def _staging_pool(self):
        return self._engine.staging_pool(self._target_device())

    def _record_guard(self):
        return self._engine.locked(self.name)

    def drop(self) -> None:
        with self._lock:
            self._pending.clear()
            self._engine.store.delete_unguarded(self.name)

    def sync_external(self) -> None:
        """Adopt record state installed BEHIND this object's back — a
        replication full-ship replacing rec.arrays, or a promoted replica
        re-binding an index over hydrated records (ISSUE 17).  Row count
        comes from rec.meta, the host mirror is re-dequantized from the
        device planes (one d2h), pending rows are dropped (the record is
        the newer truth), and any IVF plane resets so the next query
        retrains over the adopted rows instead of scoring stale cells."""
        with self._lock:
            rec = self._engine.store.get_unguarded(self.name)
            if rec is None:
                return
            bank, bias, scale = self._get_planes()
            rows = int(rec.meta.get("rows", 0))
            self._pending.clear()
            self.rows = rows
            self._cap = 0 if bank is None else int(bank.shape[0])
            if bank is None or rows <= 0:
                self.dead = 0
                self._host = np.zeros((0, self.width), np.float32)
                self._host_bias = np.zeros((0,), np.float32)
            else:
                stored = np.asarray(bank)[:rows]
                if self.dtype == "INT8" and scale is not None:
                    sc = np.asarray(scale)[:rows].astype(np.float32)
                    deq = stored.astype(np.float32) * sc[:, None]
                else:
                    deq = stored.astype(np.float32)
                self._host = np.ascontiguousarray(deq[:, : self.width])
                self._host_bias = (
                    np.asarray(bias)[:rows].astype(np.float32)
                    if bias is not None else np.zeros((rows,), np.float32)
                )
                self.dead = int(np.isinf(self._host_bias).sum())
                if self.NORMS:  # whatever plane came along is of other rows
                    from redisson_tpu.core import kernels as K

                    self._set_norms(K.rowbank_norms(bank, scale))
            ivf = getattr(self, "_ivf", None)
            if ivf is not None:
                self._ivf = type(ivf)(self.spec)


def sync_banks_from_records(engine, names) -> int:
    """Hydration-awareness seam (ISSUE 17): replication full-ships replace a
    vector_bank record's arrays WITHOUT the owning bank object seeing it,
    so a service bank bound to that record (an index def that outlived a
    REPLPUSH, or a promoted replica's rebuilt index) would keep serving a
    stale host mirror / row count.  Resync every plain record-backed bank
    whose record name is in `names`; sharded facades are skipped — their
    host-side routing tables are not record state, so adopting shard rows
    without routes would be worse than the stale mirror they replace."""
    svc = getattr(engine, "_services", {}).get("search")
    if svc is None or not names:
        return 0
    wanted = set(names)
    synced = 0
    for idx in list(getattr(svc, "_indexes", {}).values()):
        vectors = getattr(idx, "vectors", None)
        if not vectors:
            continue
        for bank in vectors.banks.values():
            if isinstance(bank, RecordRowBank) and bank.name in wanted:
                bank.sync_external()
                synced += 1
    return synced


class _IvfPlane:
    """Host-canonical IVF coarse index for one embedding bank: centroids,
    per-row cell assignments and the padded per-cell row lists.  BOTH
    scoring paths read this one state — whichever path trained it — so
    armed and disarmed replies stay identical.  The device copies
    (``centroids`` / ``cells`` arrays in the bank's record) are derived,
    stamped, and re-uploaded lazily when stale."""

    def __init__(self, spec: "VectorFieldSpec"):
        self.spec = spec
        self.centroids: Optional[np.ndarray] = None  # (nlist, dim) f32
        self.assign = np.full(0, -1, np.int32)       # rowid -> cell | -1
        self.cells: Optional[np.ndarray] = None      # (nlist, cap) i32
        self.cell_cap = 0
        self.trained_rows = 0
        self.trains = 0
        self.dirty_rows: set = set()
        self.cells_stale = False
        self.training = False    # a snapshot-train is in flight (off-lock)
        self.stamp = 0           # host index version
        self.uploaded_stamp = -1  # device copy version
        self.index_uploads = 0


class EmbeddingBank(RecordRowBank):
    """One index-field embedding bank + the KNN dispatch path.

    ``record_name`` overrides the canonical bank record name — the mesh-
    sharded facade (ShardedEmbeddingBank) constructs one EmbeddingBank per
    SHARD under a shard-salted hashtag, so each shard slot-places onto its
    own device and every per-shard axis (IVF plane, compressed storage,
    lane accounting) is exactly this class, unchanged."""

    NORMS = True

    def __init__(self, engine, index: str, spec: VectorFieldSpec,
                 block: int = DEFAULT_BLOCK, reset: bool = True,
                 record_name: Optional[str] = None):
        self.spec = spec
        self._ivf = _IvfPlane(spec) if spec.algo == "IVF" else None
        self._warm: set = set()  # (capacity, k, masked) with every bucket built
        self.counts = True       # False on a shard: its facade counts once
        super().__init__(
            engine, record_name or bank_record_name(index, spec.field),
            spec.dim, block=block, dtype=spec.dtype,
            meta=dict(spec.to_meta(), index=index), reset=reset,
        )

    # -- IVF host-canonical index maintenance ---------------------------------

    def _note_row_change(self, rowid: int) -> None:
        if self._ivf is not None:
            self._ivf.dirty_rows.add(rowid)

    def _centroid_l2(self, rows: np.ndarray) -> np.ndarray:
        """L2 assignment of rows (M, dim) to the canonical centroids —
        np.argmin ties toward the lower cell, matching kernels.kmeans_step."""
        c = self._ivf.centroids
        d = (
            np.sum(rows * rows, axis=1, dtype=np.float32)[:, None]
            - 2.0 * (rows @ c.T)
            + np.sum(c * c, axis=1, dtype=np.float32)[None, :]
        )
        return np.argmin(d, axis=1).astype(np.int32)

    def _needs_train_locked(self) -> bool:
        ivf = self._ivf
        n = self.rows
        return n >= ivf.spec.train_min and (
            ivf.centroids is None
            or n >= int(RETRAIN_GROWTH * ivf.trained_rows)
        )

    def _train_snapshot_locked(self):
        """(n, pts copy, weights, pre-snapshot dirty set) or None when too
        few live rows to seat nlist centroids."""
        ivf = self._ivf
        n = self.rows
        live = np.isfinite(self._host_bias[:n])
        if int(np.count_nonzero(live)) < ivf.spec.nlist:
            return None
        return (
            n,
            self._host[:n].copy(),
            live.astype(np.float32),
            frozenset(ivf.dirty_rows),
        )

    def _train_compute(self, n: int, pts: np.ndarray, w: np.ndarray):
        """The pure training computation — runs WITHOUT the bank lock:
        jitted kmeans_step iterations when the device plane is armed, the
        same NumPy formula when disarmed.  Either way the result
        (centroids + assignments) is plain host data the caller installs
        as the one canonical index."""
        nlist = self._ivf.spec.nlist
        live = w > 0.0
        # deterministic seeded init from live rows (pure host-side, so the
        # SAME init feeds whichever iteration path runs)
        rng = np.random.default_rng(0x1DF5EED ^ n)
        init = rng.choice(np.nonzero(live)[0], nlist, replace=False)
        cent = pts[np.sort(init)].astype(np.float32, copy=True)
        if vector_enabled():
            from redisson_tpu.core import kernels as K

            dp = K.stage(pts)
            dw = K.stage(w)
            dc = K.stage(cent)
            assign = None
            for _ in range(KMEANS_ITERS):
                dc, assign = K.kmeans_step(dp, dw, dc)
            cent = np.asarray(dc)
            assign = np.asarray(assign)
        else:
            assign = None
            for _ in range(KMEANS_ITERS):
                d = (
                    np.sum(pts * pts, axis=1, dtype=np.float32)[:, None]
                    - 2.0 * (pts @ cent.T)
                    + np.sum(cent * cent, axis=1, dtype=np.float32)[None, :]
                )
                assign = np.argmin(d, axis=1).astype(np.int32)
                sums = np.zeros_like(cent)
                np.add.at(sums, assign, pts * w[:, None])
                counts = np.zeros(cent.shape[0], np.float32)
                np.add.at(counts, assign, w)
                cent = np.where(
                    counts[:, None] > 0.0,
                    sums / np.maximum(counts, 1.0)[:, None],
                    cent,
                )
        return cent, np.where(live, assign, -1).astype(np.int32)

    def _train_now(self) -> None:
        """One training run: snapshot under the lock, ITERATE OUTSIDE IT
        (a 50k x 128 x nlist=1536 training is seconds of compute — holding
        the bank lock across it would stall every query and ingest on the
        field, a tail-latency cliff the QoS plane can't see), install the
        result under the lock.  Queries during the run score on the
        previous index (or FLAT while untrained); `training` keeps
        concurrent callers from duplicating the work."""
        ivf = self._ivf
        with self._lock:
            if ivf.training:
                return
            snap = self._train_snapshot_locked()
            if snap is None:
                return
            ivf.training = True
        try:
            n, pts, w, pre_dirty = snap
            cent, assign = self._train_compute(n, pts, w)
        finally:
            with self._lock:
                ivf.training = False
        with self._lock:
            ivf.centroids = cent
            if ivf.assign.shape[0] < max(n, self.rows):
                grown = np.full(
                    max(self.rows, n, 2 * max(1, ivf.assign.shape[0])),
                    -1, np.int32,
                )
                grown[: ivf.assign.shape[0]] = ivf.assign
                ivf.assign = grown
            ivf.assign[:n] = assign
            ivf.trained_rows = n
            ivf.trains += 1
            # rows dirty AT the snapshot are covered by this training; rows
            # dirtied DURING it keep their dirty mark (their mirror values
            # post-date the snapshot).  A row in both sets keeps its
            # snapshot-value assignment — one update behind, self-corrected
            # at its next write and bounded by the recall gate.
            ivf.dirty_rows -= pre_dirty
            ivf.cells_stale = True

    def _maybe_train(self) -> None:
        """Train/retrain gate, called by BOTH scoring paths BEFORE they
        take the bank lock for dispatch."""
        if self._ivf is None:
            return
        with self._lock:
            if not self._needs_train_locked() or self._ivf.training:
                return
        self._train_now()

    def _rebuild_cells(self) -> None:
        """Repack the per-cell row lists into the uniform-stride CSR table
        ((nlist, cell_cap) int32, sentinel-padded, rowids ascending within
        a cell — the tie-break order both scoring paths share).

        BALANCED: cell_cap is bounded at IVF_CELL_IMBALANCE x the mean
        occupancy (bucketed),
        because the kernel's candidate gather is O(nprobe * cell_cap) per
        query — one kmeans-imbalanced giant cell would silently inflate
        EVERY query's gather past the cache-friendly window.  An overfull
        cell keeps its centroid-closest rows and SPILLS the rest to their
        next-nearest cell with room (Faiss-style balanced assignment); a
        spilled row is still found through its second-best centroid, and
        the recall gate keeps the trade honest.  Both bounds are LIVE
        knobs (env / CONFIG SET, ISSUE 15): IVF_CELL_IMBALANCE and the
        hard gather-width ceiling IVF_CELL_CAP_MAX, re-read here so the
        chip-run sweep never needs a code edit."""
        from redisson_tpu.core import kernels as K

        ivf = self._ivf
        n = self.rows
        a = ivf.assign[:n].copy()
        live_rows = np.nonzero(a >= 0)[0]
        n_live = live_rows.shape[0]
        counts = np.bincount(a[live_rows], minlength=ivf.spec.nlist)
        avg = max(1, -(-n_live // ivf.spec.nlist))  # ceil
        imb = max(1.0, float(IVF_CELL_IMBALANCE))
        cap = K.bucket_size(max(4, int(round(imb * avg))), minimum=4)
        if IVF_CELL_CAP_MAX:
            cap = min(cap, max(4, int(IVF_CELL_CAP_MAX)))
        cent = ivf.centroids
        overfull = np.nonzero(counts > cap)[0]
        for c in overfull:
            members = live_rows[a[live_rows] == c]
            rows = self._host[members]
            d_own = np.sum((rows - cent[c][None, :]) ** 2, axis=1)
            order = np.argsort(d_own, kind="stable")
            spill = members[order[cap:]]
            # next-nearest cells with room, nearest-first (stable)
            srows = self._host[spill]
            d_all = (
                np.sum(srows * srows, axis=1, dtype=np.float32)[:, None]
                - 2.0 * (srows @ cent.T)
                + np.sum(cent * cent, axis=1, dtype=np.float32)[None, :]
            )
            pref = np.argsort(d_all, axis=1, kind="stable")
            for i, rowid in enumerate(spill):
                placed = False
                for cc in pref[i]:
                    if cc != c and counts[cc] < cap:
                        a[rowid] = cc
                        counts[cc] += 1
                        placed = True
                        break
                if not placed:  # pragma: no cover — nlist*cap >= 2*n_live
                    a[rowid] = int(np.argmin(counts))
                    counts[a[rowid]] += 1
            counts[c] = cap
        cells = np.full((ivf.spec.nlist, cap), _IVF_SENTINEL, np.int32)
        # vectorized repack (a per-query Python loop over the corpus would
        # dominate interleaved ingest/query workloads): sort live rows by
        # (cell, rowid) — lexsort's last key is primary — then each row's
        # slot is its rank within its cell's contiguous run
        if live_rows.size:
            order = np.lexsort((live_rows, a[live_rows]))
            srows = live_rows[order]
            scells = a[srows]
            starts = np.searchsorted(scells, np.arange(ivf.spec.nlist))
            rank = np.arange(srows.size) - starts[scells]
            keep = rank < cap  # post-balance this is all rows
            cells[scells[keep], rank[keep]] = srows[keep]
        ivf.assign[:n] = a
        ivf.cells = cells
        ivf.cell_cap = cap
        ivf.cells_stale = False
        ivf.stamp += 1

    def _ivf_sync(self) -> None:
        """Bring the canonical host index up to date with the mirror:
        incrementally assign rows ingested since the last sync and repack
        the cell lists.  Called under the bank lock from BOTH scoring
        paths, so whichever path queries first does the maintenance and
        the other reuses it.  (Training/retraining happens OFF the lock in
        _maybe_train, which the scoring entry points call first.)"""
        ivf = self._ivf
        n = self.rows
        if ivf.assign.shape[0] < n:
            grown = np.full(max(n, 2 * max(1, ivf.assign.shape[0])), -1,
                            np.int32)
            grown[: ivf.assign.shape[0]] = ivf.assign
            ivf.assign = grown
        if ivf.centroids is not None and ivf.dirty_rows:
            dirty = np.fromiter(
                (r for r in ivf.dirty_rows if r < n), np.int64
            )
            ivf.dirty_rows.clear()
            if dirty.size:
                live = np.isfinite(self._host_bias[dirty])
                cells = np.full(dirty.size, -1, np.int32)
                if np.any(live):
                    cells[live] = self._centroid_l2(self._host[dirty[live]])
                ivf.assign[dirty] = cells
                ivf.cells_stale = True
        if ivf.centroids is not None and (ivf.cells_stale or ivf.cells is None):
            self._rebuild_cells()

    def _ensure_index_device(self):
        """(device centroids (nlist, pwidth) f32, device cells) — uploaded
        into the bank's RECORD arrays when the host index moved past the
        uploaded stamp, so fenced rebalances move centroids + cells + bank
        as one record and DROPINDEX releases all three."""
        import jax

        ivf = self._ivf
        # record guard: a fenced rebalance moves these arrays under the
        # record lock — the upload must not interleave with the move
        with self._record_guard():
            rec = self._rec()
            if (
                ivf.uploaded_stamp == ivf.stamp
                and "centroids" in rec.arrays
                and "cells" in rec.arrays
            ):
                return rec.arrays["centroids"], rec.arrays["cells"]
            cent = ivf.centroids
            if self.pwidth != self.width:
                padded = np.zeros((cent.shape[0], self.pwidth), np.float32)
                padded[:, : self.width] = cent
                cent = padded
            device = self._target_device()
            dc = jax.device_put(np.ascontiguousarray(cent, np.float32),
                                device)
            dl = jax.device_put(np.ascontiguousarray(ivf.cells), device)
            rec.arrays["centroids"] = dc
            rec.arrays["cells"] = dl
            rec.version += 1
            ivf.uploaded_stamp = ivf.stamp
            ivf.index_uploads += 1
            return dc, dl

    def index_device_bytes(self) -> int:
        """Bytes the coarse index (centroids + cell table) holds on device —
        the census row that catches cell-index leaks on DROPINDEX."""
        try:
            arrays = self._rec().arrays
        except KeyError:
            return 0
        total = 0
        for k in ("centroids", "cells"):
            a = arrays.get(k)
            if a is not None:
                total += int(a.nbytes)
        return total

    def owner_device_id(self) -> int:
        """Device id the bank's planes sit on (-1 while unplaced/never
        flushed) — the label of the per-device HBM-ledger rows."""
        from redisson_tpu.core.ioplane import device_of

        try:
            bank, _bias, _scale = self._get_planes()
        except KeyError:
            return -1
        dev = device_of(bank) if bank is not None else None
        if dev is None:
            dev = self._target_device()
        return getattr(dev, "id", -1) if dev is not None else -1

    def device_bytes_by_device(self) -> Dict[int, int]:
        """{device id: bank bytes} — one entry for a plain bank; the
        sharded facade merges its shards' maps (per-device ledger rows)."""
        b = self.device_bytes()
        return {self.owner_device_id(): b} if b else {}

    def index_bytes_by_device(self) -> Dict[int, int]:
        b = self.index_device_bytes()
        return {self.owner_device_id(): b} if b else {}

    def ivf_ready(self) -> bool:
        return self._ivf is not None and self._ivf.centroids is not None

    def _resolve_nprobe(self, nprobe: Optional[int]) -> int:
        p = self.spec.nprobe if not nprobe else int(nprobe)
        return max(1, min(p, self.spec.nlist))

    def retrain(self) -> None:
        """Force a coarse-quantizer retrain now (tests / admin)."""
        if self._ivf is None:
            return
        self._train_now()
        with self._lock:
            if self._ivf.centroids is not None:
                self._rebuild_cells()

    # -- scoring --------------------------------------------------------------

    def _lane_gate(self, n_items: int):
        """Hold the owning device's serving lane for the dispatch — KNN
        occupancy is accounted per chip exactly like the whitelisted verbs
        (ioplane.DeviceLane; a no-op without placement)."""
        eng = self._engine
        if eng.lanes is None:
            return nullcontext()
        device = self._target_device()
        if device is None:
            return nullcontext()
        return eng.lanes.lane(device).occupy(n_items)

    def _pad_queries(self, q: np.ndarray, qb: int) -> np.ndarray:
        """Stack to the query bucket AND the physical bank width (the
        padding lanes are zeros, exact no-ops in every metric)."""
        out = np.zeros((qb, self.pwidth), np.float32)
        out[: q.shape[0], : self.width] = q
        return out

    def knn_async(self, queries: np.ndarray, k: int,
                  allowed_rows: Optional[np.ndarray] = None,
                  nprobe: Optional[int] = None, warm: bool = False):
        """Dispatch one stacked KNN: queries (Q, dim) float32 against every
        live row (FLAT) or the routed top-nprobe cells (IVF).  Returns
        (device_dist, device_idx, q_count, k_eff) WITHOUT forcing the
        readback — the server wraps it in a LazyReply so the frame-grouped
        transfer drains it; embedded callers np.asarray().

        ``allowed_rows`` (hybrid prefilter): int row ids that may score —
        everything else gets +inf distance via an additive (capacity,) plane.

        ``warm``: the caller stacks a frame's run, whose length the next
        frame changes — build the FLAT program at every query bucket now
        (once a capacity and k), so no later run meets a cold one.

        Falls back to the host path (knn_host) when the device plane is
        disarmed (RTPU_NO_VECTOR) — callers branch on vector_enabled()."""
        from redisson_tpu.core import kernels as K

        q = np.ascontiguousarray(queries, np.float32).reshape(-1, self.width)
        nq = q.shape[0]
        self._maybe_train()  # off-lock; queries meanwhile score the old index
        with self._lock:
            bank, bias, scale, rows = self.device_planes()
            if bank is None or rows == 0:
                return None
            if self._ivf is not None:
                self._ivf_sync()
            qb = knn_query_bucket(nq)
            staged = K.stage(self._pad_queries(q, qb))
            metric = self.spec.metric
            mask = None
            if allowed_rows is not None:
                m = np.full(self._cap, np.inf, np.float32)
                m[np.asarray(allowed_rows, np.int64)] = 0.0
                mask = K.stage(m)
            live = rows - self.dead
            nv = K.valid_n(rows)
            if self.ivf_ready():
                np_eff = self._resolve_nprobe(nprobe)
                dc, dl = self._ensure_index_device()
                cand = np_eff * self._ivf.cell_cap
                k_eff = max(1, min(int(k), cand))
                with self._lane_gate(nq * max(1, min(rows, cand))):
                    if scale is not None and mask is not None:
                        dist, idx = K.knn_ivf_topk_masked_q(
                            bank, scale, bias, mask, dc, dl, staged, nv,
                            k_eff, np_eff, metric,
                        )
                    elif scale is not None:
                        dist, idx = K.knn_ivf_topk_q(
                            bank, scale, bias, dc, dl, staged, nv,
                            k_eff, np_eff, metric,
                        )
                    elif mask is not None:
                        dist, idx = K.knn_ivf_topk_masked(
                            bank, bias, mask, dc, dl, staged, nv,
                            k_eff, np_eff, metric,
                        )
                    else:
                        dist, idx = K.knn_ivf_topk(
                            bank, bias, dc, dl, staged, nv,
                            k_eff, np_eff, metric,
                        )
                if self.counts:
                    _count_knn(nq, qb, min(live, cand))
                return dist, idx, nq, k_eff
            if nprobe and self._ivf is None:
                raise ValueError("NPROBE applies to an IVF field")
            k_eff = max(1, min(int(k), self._cap))
            norms = self._norms_locked(bank, scale)
            with self._lane_gate(nq * max(1, rows)):
                if warm:
                    self._warm_query_buckets(
                        bank, scale, bias, norms, mask, nv, k_eff
                    )
                dist, idx = K.knn_flat_topk(
                    bank, scale, bias, norms, mask, staged, nv, k_eff, metric
                )
            if self.counts:
                _count_knn(nq, qb, live)
        return dist, idx, nq, k_eff

    def _warm_query_buckets(self, bank, scale, bias, norms, mask, nv,
                            k_eff: int) -> None:
        """Build knn_flat_topk at every KNN_QUERY_BUCKETS size for this
        capacity, k and kind of query, on the planes themselves (it writes
        nothing).  jit keeps the programs; the set only spares the calls."""
        import jax

        from redisson_tpu.core import kernels as K

        key = (self._cap, k_eff, mask is not None)
        if key in self._warm:
            return
        for qb in KNN_QUERY_BUCKETS:
            zeros = K.stage(np.zeros((qb, self.pwidth), np.float32))
            jax.block_until_ready(K.knn_flat_topk(
                bank, scale, bias, norms, mask, zeros, nv, k_eff,
                self.spec.metric,
            ))
        self._warm.add(key)

    def _host_flat_dists(self, q: np.ndarray, host: np.ndarray) -> np.ndarray:
        dots = q @ host.T  # (Q, rows) f32
        metric = self.spec.metric
        if metric == "L2":
            q_sq = np.sum(q * q, axis=1, dtype=np.float32)
            b_sq = np.sum(host * host, axis=1, dtype=np.float32)
            return q_sq[:, None] - 2.0 * dots + b_sq[None, :]
        if metric == "COSINE":
            qn = np.sqrt(np.sum(q * q, axis=1, dtype=np.float32))
            bn = np.sqrt(np.sum(host * host, axis=1, dtype=np.float32))
            denom = qn[:, None] * bn[None, :]
            with np.errstate(invalid="ignore", divide="ignore"):
                cos = np.where(denom > 0.0, dots / denom, 0.0)
            return (1.0 - cos).astype(np.float32)
        return (1.0 - dots).astype(np.float32)  # IP

    def knn_host(self, queries: np.ndarray, k: int,
                 allowed_rows: Optional[np.ndarray] = None,
                 nprobe: Optional[int] = None):
        """Pure-NumPy KNN (the RTPU_NO_VECTOR reference): same float32
        formulas, same +inf bias discipline, same canonical IVF index and
        the same stable tie-break as the kernels — replies must be
        identical."""
        q = np.ascontiguousarray(queries, np.float32).reshape(-1, self.width)
        self._maybe_train()  # off-lock, same gate as the armed path
        with self._lock:
            host, hbias = self.host_planes()
            rows = host.shape[0]
            if rows == 0:
                return None
            if self._ivf is not None:
                self._ivf_sync()
            if self.ivf_ready():
                return self._knn_host_ivf(q, k, allowed_rows, nprobe,
                                          host, hbias)
            if nprobe and self._ivf is None:
                raise ValueError("NPROBE applies to an IVF field")
        dist = self._host_flat_dists(q, host) + hbias[None, :]
        if allowed_rows is not None:
            mask = np.full(rows, np.inf, np.float32)
            mask[np.asarray(allowed_rows, np.int64)] = 0.0
            dist = dist + mask[None, :]
        k_eff = max(1, min(int(k), rows))
        order = np.argsort(dist, axis=1, kind="stable")[:, :k_eff]
        top = np.take_along_axis(dist, order, axis=1)
        return top.astype(np.float32), order.astype(np.int32), q.shape[0], k_eff

    def pair_scores(self, q: np.ndarray, qis: np.ndarray,
                    rowids: np.ndarray) -> np.ndarray:
        """THE canonical reply-score routine (byte-identity contract): both
        scoring paths pick WHICH rows win (device kernel or NumPy), then
        the wire score of every (query, row) hit is recomputed here — one
        deterministic per-pair NumPy reduction over the dequantized mirror,
        identical bits whichever path chose the ids.  (Device-vs-host GEMMs
        disagree in the last ulp; at large score magnitudes that ulp
        crosses the reply's 4-decimal rounding boundary.)"""
        with self._lock:
            rows = self._host[np.asarray(rowids, np.int64)]       # (M, d)
        qs = np.ascontiguousarray(q, np.float32)[np.asarray(qis, np.int64)]
        return _pair_score_math(rows, qs, self.spec.metric)

    def resolve_hits(self, vals) -> Tuple[np.ndarray, np.ndarray]:
        """Host arrays of one armed dispatch -> (dist (Q,k), GLOBAL rowids
        (Q,k)).  Plain banks already address global rowids; the sharded
        facade overrides to decode its (dist, shard, local) triple."""
        return np.asarray(vals[0]), np.asarray(vals[1])

    def _knn_host_ivf(self, q, k, allowed_rows, nprobe, host, hbias):
        """NumPy mirror of kernels._knn_ivf_body over the SAME canonical
        centroids/cells: identical routing, identical candidate order
        (probe order then cell position), identical padding semantics."""
        ivf = self._ivf
        np_eff = self._resolve_nprobe(nprobe)
        nq = q.shape[0]
        rows = host.shape[0]
        cent = ivf.centroids
        metric = self.spec.metric
        # routing = the FLAT distance formula against the centroid bank
        cd = self._host_flat_dists(q, cent)
        probe = np.argsort(cd, axis=1, kind="stable")[:, :np_eff]
        cand = ivf.cells[probe].reshape(nq, -1)          # (Q, M)
        valid = cand < rows
        safe = np.where(valid, cand, 0)
        rvec = host[safe]                                 # (Q, M, dim)
        dots = np.einsum("qmw,qw->qm", rvec, q, dtype=np.float32)
        if metric == "L2":
            q_sq = np.sum(q * q, axis=1, dtype=np.float32)
            r_sq = np.sum(rvec * rvec, axis=2, dtype=np.float32)
            dist = q_sq[:, None] - 2.0 * dots + r_sq
        elif metric == "COSINE":
            qn = np.sqrt(np.sum(q * q, axis=1, dtype=np.float32))
            rn = np.sqrt(np.sum(rvec * rvec, axis=2, dtype=np.float32))
            denom = qn[:, None] * rn
            with np.errstate(invalid="ignore", divide="ignore"):
                dist = 1.0 - np.where(denom > 0.0, dots / denom, 0.0)
        else:
            dist = 1.0 - dots
        dist = dist + hbias[safe]
        if allowed_rows is not None:
            mask = np.full(rows, np.inf, np.float32)
            mask[np.asarray(allowed_rows, np.int64)] = 0.0
            dist = dist + mask[safe]
        dist = np.where(valid, dist, np.inf).astype(np.float32)
        cand_n = np_eff * ivf.cell_cap
        k_eff = max(1, min(int(k), cand_n))
        order = np.argsort(dist, axis=1, kind="stable")[:, :k_eff]
        top = np.take_along_axis(dist, order, axis=1)
        idx = np.take_along_axis(cand, order, axis=1)
        return top.astype(np.float32), idx.astype(np.int32), nq, k_eff


# -- mesh-sharded banks (ISSUE 15) --------------------------------------------

_FANOUT_POOL = None
_FANOUT_POOL_LOCK = threading.Lock()


def _gmap_decode(g: np.ndarray, local: np.ndarray) -> np.ndarray:
    """Shard-local rowids -> global rowids through one shard's gmap, with
    out-of-range entries (IVF padding sentinels, capacity padding) mapped
    to -1 — the ONE guarded lookup both reply paths share, so neither can
    dereference a sentinel the other would have masked."""
    local = np.asarray(local)
    ok = (local >= 0) & (local < g.shape[0])
    return np.where(ok, g[np.clip(local, 0, max(0, g.shape[0] - 1))], -1)


def _fanout_pool():
    """Shared worker pool for per-shard KNN legs: each leg stages its query
    onto its OWN shard's device and occupies that device's lane, so
    dispatching legs from concurrent threads is what lets N chips (or the
    CPU-replica occupancy model) overlap one sharded frame — the thread
    face of config5d's cross-lane dispatch."""
    global _FANOUT_POOL
    if _FANOUT_POOL is None:
        with _FANOUT_POOL_LOCK:
            if _FANOUT_POOL is None:
                from concurrent.futures import ThreadPoolExecutor

                _FANOUT_POOL = ThreadPoolExecutor(
                    max_workers=16, thread_name_prefix="rtpu-ftvec-shard"
                )
    return _FANOUT_POOL


class ShardedEmbeddingBank:
    """One index-field embedding bank split ROW-WISE across the local mesh
    (``SHARDS n``): n EmbeddingBank shards, each a full bank (own IVF
    plane, own compressed storage, own lane/staging accounting) under a
    shard-salted hashtag record pinned to its own slot-owner device — so
    the constellation's total N x d exceeds any ONE chip's HBM, and every
    existing per-record discipline (fenced rebalances, DEVMOVE, DROPINDEX
    teardown, census) applies shard by shard with zero new machinery.

    Routing: a global rowid is assigned once to the LEAST-FULL shard
    (``_route``/``_local``), and each shard keeps its local->global map
    (``_gmap``).  Queries fan per-shard ``knn_async`` legs out across the
    lanes (each leg charges ITS device's lane), then the per-shard (Q, k)
    tops d2d-colocate onto one shard's device and merge as ONE jitted
    top-k-of-top-ks (kernels.knn_sharded_merge) — never a host gather
    (IOStats.host_colocations unmoved; sharded_knn_merges counts).  The
    disarmed path mirrors the SAME shard legs + concat order with a stable
    argsort, and reply scores come from the one canonical
    ``_pair_score_math`` over the shard mirrors, so armed and disarmed
    replies stay byte-identical for every shards x algo x dtype cell."""

    KIND = "vector_bank_manifest"

    def __init__(self, engine, index: str, spec: VectorFieldSpec,
                 block: int = DEFAULT_BLOCK, reset: bool = True):
        from redisson_tpu.core.store import StateRecord

        self.spec = spec
        self._engine = engine
        self.index = index
        self.block = max(1, int(block))
        self.name = bank_record_name(index, spec.field)
        self._lock = threading.RLock()
        with engine.locked(self.name):
            old = engine.store.get_unguarded(self.name)
            if reset and old is not None:
                # a dropped/rebuilt index must not leak its old shard
                # records (their salted names may differ this time)
                for nm in old.meta.get("shard_names", ()):
                    engine.store.delete_unguarded(nm)
                engine.store.delete_unguarded(self.name)
                old = None
            if old is not None and old.meta.get("shard_names"):
                names = list(old.meta["shard_names"])
            else:
                names = pick_shard_record_names(
                    engine, index, spec.field, spec.shards
                )
                engine.store.put_unguarded(
                    self.name,
                    StateRecord(
                        kind=self.KIND,
                        meta=dict(spec.to_meta(), index=index,
                                  shard_names=list(names)),
                        arrays={},
                    ),
                )
        self.shard_names = names
        self.shards: List[EmbeddingBank] = [
            EmbeddingBank(engine, index, spec, block=block, reset=reset,
                          record_name=nm)
            for nm in names
        ]
        for sh in self.shards:
            sh.counts = False  # knn_async below counts a query once
        # global rowid -> (shard, shard-local rowid); -1 = never assigned
        self._route = np.full(0, -1, np.int32)
        self._local = np.full(0, -1, np.int32)
        # per shard: local rowid -> global rowid (append-only: a local slot
        # never re-routes, so readback-time decode needs no lock ordering)
        self._gmap: List[np.ndarray] = [
            np.full(0, -1, np.int32) for _ in names
        ]
        # local slots ASSIGNED per shard — the least-full/next-slot counter.
        # Kept here (not read off shard.rows) so slot minting stays correct
        # while the shard's own set_row runs OUTSIDE the facade lock.
        self._assigned: List[int] = [0 for _ in names]
        # round-robin cursor for the merge device (no fixed hot lane)
        self._merge_rr = 0
        # staged shard_of_pos operands, keyed by (leg shard ids, per-leg
        # k_s, merge device id): static per constellation geometry, so the
        # hot query path reuses the device buffer instead of paying one
        # tiny H2D per dispatch.  Bounded: geometries are few (k values x
        # merge-device rotation); a pathological sweep just clears it.
        self._sop_cache: Dict[Tuple, Any] = {}
        self.rows = 0

    # -- routing --------------------------------------------------------------

    def _grow_routing_locked(self, rowid: int) -> None:
        if rowid < self._route.shape[0]:
            return
        cap = max(self.block, 2 * max(1, self._route.shape[0]))
        while cap <= rowid:
            cap *= 2
        for attr in ("_route", "_local"):
            cur = getattr(self, attr)
            grown = np.full(cap, -1, np.int32)
            grown[: cur.shape[0]] = cur
            setattr(self, attr, grown)

    def _assign_locked(self, rowid: int) -> Tuple[int, int]:
        """Route one new rowid to the LEAST-FULL shard and mint its local
        slot (ties toward the lower shard — deterministic layout).  The
        fullness/next-slot source is the facade's own ``_assigned`` ledger,
        never ``shard.rows``: the shard write runs outside the facade lock,
        so its row count lags the minting and reading it here would hand
        two rowids the same slot."""
        s = int(np.argmin(self._assigned))
        loc = self._assigned[s]
        self._assigned[s] = loc + 1
        self._route[rowid] = s
        self._local[rowid] = loc
        g = self._gmap[s]
        if loc >= g.shape[0]:
            cap = max(DEFAULT_BLOCK, 2 * max(1, g.shape[0]))
            while cap <= loc:
                cap *= 2
            grown = np.full(cap, -1, np.int32)
            grown[: g.shape[0]] = g
            self._gmap[s] = g = grown
        g[loc] = rowid
        return s, loc

    def set_row(self, rowid: int, row: Optional[np.ndarray]) -> None:
        # routing under the facade lock; the shard write OUTSIDE it — a
        # shard whose pending block flushes (packed H2D + scatter) must not
        # stall ingest to every other shard or query leg-selection (the
        # shard's own lock already serializes its slots)
        with self._lock:
            self._grow_routing_locked(rowid)
            s = int(self._route[rowid])
            if s < 0:
                s, loc = self._assign_locked(rowid)
            else:
                loc = int(self._local[rowid])
            self.rows = max(self.rows, rowid + 1)
        self.shards[s].set_row(loc, row)

    # -- aggregate bank surface (the EmbeddingBank API, summed) ---------------

    @property
    def h2d_flushes(self) -> int:
        return sum(sh.h2d_flushes for sh in self.shards)

    @property
    def grows(self) -> int:
        return sum(sh.grows for sh in self.shards)

    def device_bytes(self) -> int:
        return sum(sh.device_bytes() for sh in self.shards)

    def index_device_bytes(self) -> int:
        return sum(sh.index_device_bytes() for sh in self.shards)

    def logical_f32_bytes(self) -> int:
        return sum(sh.logical_f32_bytes() for sh in self.shards)

    def pending_count(self) -> int:
        return sum(sh.pending_count() for sh in self.shards)

    def device_bytes_by_device(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for sh in self.shards:
            for d, b in sh.device_bytes_by_device().items():
                out[d] = out.get(d, 0) + b
        return out

    def index_bytes_by_device(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for sh in self.shards:
            for d, b in sh.index_bytes_by_device().items():
                out[d] = out.get(d, 0) + b
        return out

    def ivf_ready(self) -> bool:
        return any(sh.ivf_ready() for sh in self.shards)

    def retrain(self) -> None:
        for sh in self.shards:
            sh.retrain()

    def flush_pending(self) -> int:
        return sum(sh.flush_pending() for sh in self.shards)

    def drop(self) -> None:
        for sh in self.shards:
            sh.drop()
        with self._engine.locked(self.name):
            self._engine.store.delete_unguarded(self.name)

    def shard_rows(self) -> List[Dict[str, Any]]:
        """Per-shard FT.INFO / census rows: residency shard by shard."""
        out = []
        for i, sh in enumerate(self.shards):
            out.append({
                "shard": i, "record": sh.name, "rows": sh.rows,
                "device": sh.owner_device_id(),
                "device_bytes": sh.device_bytes(),
                "index_device_bytes": sh.index_device_bytes(),
            })
        return out

    # -- scoring --------------------------------------------------------------

    def _legs(self, allowed_rows: Optional[np.ndarray]):
        """[(shard, shard-local allowed | None)] — the ONE leg-selection
        routine both scoring paths share: ascending shard order (the merge
        tie-break), empty shards skipped, and a hybrid prefilter that
        covers no rows of a shard skips that shard's dispatch entirely."""
        with self._lock:
            if allowed_rows is None:
                return [
                    (s, None) for s in range(len(self.shards))
                    if self.shards[s].rows > 0
                ]
            al = np.asarray(allowed_rows, np.int64).reshape(-1)
            al = al[(al >= 0) & (al < self._route.shape[0])]
            rs = self._route[al]
            ls = self._local[al]
            legs = []
            for s in range(len(self.shards)):
                if self.shards[s].rows <= 0:
                    continue
                m = rs == s
                if np.any(m):
                    legs.append((s, ls[m].astype(np.int64)))
            return legs

    def _merge_kernel(self, n_legs: int):
        """The top-k-of-top-ks program, fetched through MeshManager's
        geometry-keyed cross-epoch warm pool — a 4->8->4 reshard lands back
        on the already-built program (0 rebuilds; the sharded-KNN half of
        the Engine.prewarm contract)."""
        from redisson_tpu.parallel.manager import MeshManager

        return MeshManager.of(self._engine).knn_merge_kernel(n_legs)

    def _merge_lane_gate(self, device, n_items: int):
        eng = self._engine
        if eng.lanes is None or device is None:
            return nullcontext()
        return eng.lanes.lane(device).occupy(n_items)

    def knn_async(self, queries: np.ndarray, k: int,
                  allowed_rows: Optional[np.ndarray] = None,
                  nprobe: Optional[int] = None, warm: bool = False):
        """Row-parallel KNN: fan the stacked queries out as one
        ``knn_async`` leg per live shard (concurrent, each under its own
        device lane), d2d-colocate the per-shard (Q, k) tops onto one
        shard's device and run ONE merged top-k kernel there.  Returns
        (dist, shard, local, q_count, k_eff) — resolve_hits decodes the
        (shard, local) pair back to global rowids host-side."""
        from redisson_tpu.core import ioplane
        from redisson_tpu.core import kernels as K

        q = np.ascontiguousarray(queries, np.float32).reshape(
            -1, self.spec.dim
        )
        nq = q.shape[0]
        legs = self._legs(allowed_rows)
        if not legs:
            return None
        pool = _fanout_pool()
        futs = [
            pool.submit(self.shards[s].knn_async, q, k, al, nprobe, warm)
            for s, al in legs
        ]
        outs = []
        for (s, _al), f in zip(legs, futs):
            o = f.result()
            if o is not None:
                outs.append((s, o))
        if not outs:
            return None
        # merge device rotates across the live legs per dispatch — a fixed
        # choice (always shard 0) would serialize EVERY bank's merges on
        # one lane while the other chips idle after their legs
        with self._lock:
            rr = self._merge_rr
            self._merge_rr = rr + 1
        dest = ioplane.device_of(outs[rr % len(outs)][1][0])
        dists, idxs = [], []
        for _s, (d, i, _nq, _k_s) in outs:
            dists.append(ioplane.colocate(d, dest))
            idxs.append(ioplane.colocate(i, dest))
        geom_key = (
            tuple(s for s, _o in outs),
            tuple(o[3] for _s, o in outs),
            getattr(dest, "id", None),
        )
        with self._lock:
            sop = self._sop_cache.get(geom_key)
        if sop is None:
            shard_of_pos = np.concatenate(
                [np.full(o[3], s, np.int32) for s, o in outs]
            )
            if dest is not None:
                import jax

                sop = jax.device_put(shard_of_pos, dest)
            else:
                sop = K.stage(shard_of_pos)
            with self._lock:
                if len(self._sop_cache) >= 64:
                    self._sop_cache.clear()
                self._sop_cache[geom_key] = sop
        total = sum(o[3] for _s, o in outs)
        k_out = max(1, min(int(k), total))
        merge = self._merge_kernel(len(outs))
        # the merge charges the MERGE device's lane on top of the per-shard
        # legs already charged — a sharded frame bills every lane it rides
        with self._merge_lane_gate(dest, nq * total):
            dist, sid, lidx = merge(tuple(dists), tuple(idxs), sop, k_out)
        ioplane.STATS.count_sharded_merge()
        _count_knn(nq, knn_query_bucket(nq),
                   sum(self.shards[s].rows - self.shards[s].dead for s, _o in outs))
        return dist, sid, lidx, nq, k_out

    def resolve_hits(self, vals) -> Tuple[np.ndarray, np.ndarray]:
        """(dist, shard, local) host arrays -> (dist, GLOBAL rowids); non-
        finite / unmapped entries resolve to rowid -1 (callers skip)."""
        dist = np.asarray(vals[0])
        sid = np.asarray(vals[1])
        lidx = np.asarray(vals[2])
        with self._lock:
            gmaps = list(self._gmap)
        glob = np.full(dist.shape, -1, np.int32)
        finite = np.isfinite(dist)
        if np.any(finite):
            for s in np.unique(sid[finite]):
                m = finite & (sid == s)
                glob[m] = _gmap_decode(gmaps[int(s)], lidx[m])
        return dist, glob

    def knn_host(self, queries: np.ndarray, k: int,
                 allowed_rows: Optional[np.ndarray] = None,
                 nprobe: Optional[int] = None):
        """Disarmed reference: the SAME per-shard legs (each shard's own
        ``knn_host`` — same IVF index, same tie-breaks), concatenated in
        the same ascending-shard order, merged by one stable argsort —
        mirrors the device merge position for position."""
        q = np.ascontiguousarray(queries, np.float32).reshape(
            -1, self.spec.dim
        )
        legs = self._legs(allowed_rows)
        if not legs:
            return None
        outs = []
        for s, al in legs:
            o = self.shards[s].knn_host(q, k, allowed_rows=al, nprobe=nprobe)
            if o is not None:
                outs.append((s, o))
        if not outs:
            return None
        with self._lock:
            gmaps = list(self._gmap)
        dist_cat = np.concatenate([o[0] for _s, o in outs], axis=1)
        # decode through the SAME guarded gmap lookup as resolve_hits: an
        # IVF shard leg's top-k may carry padding-sentinel candidates
        # (probed cells holding fewer than k live rows — common once rows
        # split n ways), whose +inf dist the caller drops but whose raw
        # index must never dereference the gmap
        glob_cat = np.concatenate(
            [_gmap_decode(gmaps[s], o[1]) for s, o in outs], axis=1
        )
        k_out = max(1, min(int(k), dist_cat.shape[1]))
        order = np.argsort(dist_cat, axis=1, kind="stable")[:, :k_out]
        top = np.take_along_axis(dist_cat, order, axis=1)
        idx = np.take_along_axis(glob_cat, order, axis=1)
        return (
            top.astype(np.float32), idx.astype(np.int32), q.shape[0], k_out
        )

    def pair_scores(self, q: np.ndarray, qis: np.ndarray,
                    rowids: np.ndarray) -> np.ndarray:
        """The canonical reply-score routine over the SHARD mirrors: global
        rowids gather their dequantized rows shard by shard, then the one
        shared per-pair reduction — identical bits to a plain bank holding
        the same rows."""
        rid = np.asarray(rowids, np.int64).reshape(-1)
        with self._lock:
            rs = self._route[rid]
            ls = self._local[rid]
        rows = np.zeros((rid.shape[0], self.spec.dim), np.float32)
        for s in np.unique(rs):
            if s < 0:  # pragma: no cover — winners are always routed
                continue
            m = rs == s
            sh = self.shards[int(s)]
            with sh._lock:
                rows[m] = sh._host[ls[m]]
        qs = np.ascontiguousarray(q, np.float32)[np.asarray(qis, np.int64)]
        return _pair_score_math(rows, qs, self.spec.metric)


class VectorPlane:
    """Per-index vector fields: field -> EmbeddingBank sharing the index's
    doc rowid space (the numeric plane's row discipline)."""

    def __init__(self, engine, index: str,
                 specs: Dict[str, VectorFieldSpec],
                 block: int = DEFAULT_BLOCK, reset: bool = True):
        self.index = index
        # SHARDS 1 constructs the plain single-record bank — the sharded
        # facade never sits in that path, so SHARDS=1 replies are the
        # unsharded plane's replies byte for byte (ISSUE 15 acceptance)
        self.banks: Dict[str, Any] = {
            f: (
                ShardedEmbeddingBank(engine, index, spec, block=block,
                                     reset=reset)
                if spec.shards > 1
                else EmbeddingBank(engine, index, spec, block=block,
                                   reset=reset)
            )
            for f, spec in specs.items()
        }

    def __bool__(self) -> bool:
        return bool(self.banks)

    def set_row(self, rowid: int, fields: Dict[str, Any]) -> None:
        for f, bank in self.banks.items():
            try:
                row = parse_vector_value(fields.get(f), bank.spec.dim)
            except ValueError:
                # malformed blob in an auto-ingested hash: the doc stays
                # text/tag/numeric-searchable, just never KNN-visible (the
                # RediSearch failed-attribute discipline)
                row = None
            bank.set_row(rowid, row)

    def clear_row(self, rowid: int) -> None:
        for bank in self.banks.values():
            bank.set_row(rowid, None)

    def drop(self) -> None:
        for bank in self.banks.values():
            bank.drop()

    def device_bytes(self) -> int:
        return sum(b.device_bytes() for b in self.banks.values())

    def index_device_bytes(self) -> int:
        return sum(b.index_device_bytes() for b in self.banks.values())

    def h2d_flushes(self) -> int:
        return sum(b.h2d_flushes for b in self.banks.values())

    def device_bytes_by_device(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for b in self.banks.values():
            for d, v in b.device_bytes_by_device().items():
                out[d] = out.get(d, 0) + v
        return out

    def index_bytes_by_device(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for b in self.banks.values():
            for d, v in b.index_bytes_by_device().items():
                out[d] = out.get(d, 0) + v
        return out

    def info_rows(self) -> List[Dict[str, Any]]:
        out = []
        for f, b in self.banks.items():
            row = {
                "field": f, "dim": b.spec.dim, "metric": b.spec.metric,
                "algo": b.spec.algo, "dtype": b.spec.dtype,
                "rows": b.rows, "device_bytes": b.device_bytes(),
            }
            if b.spec.algo == "IVF":
                row.update({
                    "nlist": b.spec.nlist, "nprobe": b.spec.nprobe,
                    "trained": b.ivf_ready(),
                    "index_device_bytes": b.index_device_bytes(),
                })
            if isinstance(b, ShardedEmbeddingBank):
                row["shards"] = b.spec.shards
                row["shard_rows"] = b.shard_rows()
            out.append(row)
        return out

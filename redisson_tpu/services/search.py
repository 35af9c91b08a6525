"""Search service: secondary indexes + queries + aggregations.

Parity target: RSearch (``RedissonSearch.java``, 906 LoC — FT.CREATE /
FT.SEARCH / FT.AGGREGATE over hashes selected by key prefix) and the
condition tree of LiveObjectSearch (``liveobject/LiveObjectSearch.java``,
``liveobject/condition/*``: EQ/GT/GE/LT/LE/IN/AND/OR).

TPU-first design: the reference evaluates numeric predicates per-document in
the RediSearch C module; here every NUMERIC field of an index is packed into
one dense (docs × fields) float32 device matrix, so a numeric filter over N
documents is a single vectorized compare-and-reduce on device — the MXU/VPU
replaces the per-doc loop.  TEXT (tokenized words) and TAG (exact values)
fields live in host-side inverted indexes: set intersection there is
hash-table work the device has no advantage on; mixed queries intersect the
host candidate set with the device numeric mask.

Auto-indexing: the reference indexes every hash whose key matches a prefix.
A hash-mode index (the FT.* wire verbs) learns of a write AT THE WRITE: every
mutation of a record and every delete, rename, expiry or flush of a key tells
the service the key's name (Engine.ingest_hook, DeviceStore.on_change), the
key joins the dirty set of each index whose prefix covers it, and the set is
drained — O(dirty), never the keyspace — at the end of the frame that wrote
and before any FT.* answers.  One scan of the keyspace is left: the one
FT.CREATE / FT.ALTER makes over keys that were there before the index.  An
entry-mode index (the embedded facade's) keeps `sync()`, the version-diffed
scan the caller asks for.
"""
from __future__ import annotations

import re
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

_COUNT_LOCK = threading.Lock()
_docs_indexed = 0
_scan_keys = 0


def _count(docs: int = 0, keys: int = 0) -> None:
    global _docs_indexed, _scan_keys
    with _COUNT_LOCK:
        _docs_indexed += docs
        _scan_keys += keys


def search_counted() -> tuple:
    """(documents indexed at a write, keys walked by a keyspace scan) of this
    process's search indexes.  METRICS exports both (search_docs_indexed_total,
    search_scan_keys_total), always on."""
    return _docs_indexed, _scan_keys


def hit_lists(docs, scores, ends) -> list:
    """A KNN's hits as columns (SearchService.knn: doc ids, distances and
    where each query's end) -> one ``[(doc_id, distance), ...]`` list a
    query."""
    flat = list(zip(docs.tolist(), scores.tolist()))
    ends = ends.tolist()
    return [flat[a:b] for a, b in zip([0] + ends[:-1], ends)]


class _RowDocs:
    """row -> doc id, as an object array that grows by doubling: a KNN
    reply's (Q, k) rows become doc ids by ONE lookup (take), where a list
    was a Python loop a hit."""

    def __init__(self):
        self._ids = np.empty(256, object)
        self._n = 0

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, row: int):
        return self._ids[row] if 0 <= row < self._n else None

    def __setitem__(self, row: int, doc_id) -> None:
        self._ids[row] = doc_id

    def append(self, doc_id) -> None:
        if self._n == len(self._ids):
            grown = np.empty(2 * self._n, object)
            grown[: self._n] = self._ids
            self._ids = grown
        self._ids[self._n] = doc_id
        self._n += 1

    def take(self, rows: np.ndarray) -> np.ndarray:
        """Doc ids of `rows` (any shape); None where a row is out of range
        or its document is gone."""
        ok = (rows >= 0) & (rows < self._n)
        out = self._ids[np.where(ok, rows, 0)]
        out[~ok] = None
        return out


# -- schema ------------------------------------------------------------------


class FieldType:
    TEXT = "TEXT"
    TAG = "TAG"
    NUMERIC = "NUMERIC"
    VECTOR = "VECTOR"  # device-resident embedding bank (services/vector.py)


_WORD = re.compile(r"[\w']+")


def tokenize(text: str) -> List[str]:
    return [w.lower() for w in _WORD.findall(str(text))]


# -- condition tree (liveobject/condition/* analog) --------------------------


@dataclass
class Condition:
    def and_(self, other: "Condition") -> "Condition":
        return And([self, other])

    def or_(self, other: "Condition") -> "Condition":
        return Or([self, other])


@dataclass
class Eq(Condition):
    field: str
    value: Any


@dataclass
class In(Condition):
    field: str
    values: Sequence[Any]


@dataclass
class Range(Condition):
    """lo <= field <= hi with open endpoints via inclusive flags."""

    field: str
    lo: float = float("-inf")
    hi: float = float("inf")
    lo_inc: bool = True
    hi_inc: bool = True


def Gt(field: str, v: float) -> Range:
    return Range(field, lo=v, lo_inc=False)


def Ge(field: str, v: float) -> Range:
    return Range(field, lo=v, lo_inc=True)


def Lt(field: str, v: float) -> Range:
    return Range(field, hi=v, hi_inc=False)


def Le(field: str, v: float) -> Range:
    return Range(field, hi=v, hi_inc=True)


@dataclass
class Text(Condition):
    """Full-text: all words must match (FT.SEARCH default AND semantics)."""

    field: str
    query: str


@dataclass
class And(Condition):
    parts: List[Condition] = field(default_factory=list)


@dataclass
class Or(Condition):
    parts: List[Condition] = field(default_factory=list)


# -- index -------------------------------------------------------------------


class _NumericPlane:
    """Dense (docs × numeric-fields) matrix on the block-appended device row
    bank (services/vector.DeviceRowBank).

    Historically this cached one whole-matrix device upload and re-staged
    the ENTIRE host matrix whenever the row count changed — O(docs) H2D per
    single-doc ingest.  Now appends/overwrites buffer host-side and flush as
    ONE packed upload + scatter per block (the embedding banks' discipline),
    so N single-doc ingests cost O(N/block) transfers; a query flushes at
    most the pending tail, never the full matrix."""

    def __init__(self, fields: List[str]):
        from redisson_tpu.services.vector import DeviceRowBank

        self.fields = fields
        self.col = {f: i for i, f in enumerate(fields)}
        self._count = 0
        self._bank = DeviceRowBank(len(fields)) if fields else None

    def __len__(self) -> int:
        return self._count

    @property
    def h2d_flushes(self) -> int:
        return self._bank.h2d_flushes if self._bank is not None else 0

    def _row(self, values: Dict[str, Any]) -> np.ndarray:
        row = np.full(len(self.fields), np.nan, np.float32)
        for f, v in values.items():
            if f in self.col and v is not None:
                try:
                    row[self.col[f]] = float(v)
                except (TypeError, ValueError):
                    pass  # non-numeric value in a NUMERIC column: unindexed
        return row

    def append(self, values: Dict[str, Any]) -> int:
        rowid = self._count
        self._count += 1
        if self._bank is not None:
            self._bank.set_row(rowid, self._row(values))
        return rowid

    def replace(self, rowid: int, values: Dict[str, Any]) -> None:
        if self._bank is not None:
            self._bank.set_row(rowid, self._row(values))

    def clear_row(self, rowid: int) -> None:
        # explicit NaN row (NOT the bank's zero-filled kill): NaN is the
        # "unindexed" sentinel every range compare already treats as False
        if self._bank is not None:
            self._bank.set_row(
                rowid, np.full(len(self.fields), np.nan, np.float32)
            )

    def matrix(self):
        import jax.numpy as jnp

        if self._bank is None:
            return jnp.zeros((0, 0), jnp.float32)
        bank, _bias, _scale, rows = self._bank.device_planes()
        if bank is None:
            return jnp.zeros((0, len(self.fields)), jnp.float32)
        return bank[:rows]

    def range_mask(self, cond: Range) -> np.ndarray:
        """One vectorized compare over all docs on device."""
        import jax.numpy as jnp

        m = self.matrix()
        if m.shape[0] == 0 or cond.field not in self.col:
            return np.zeros(self._count, bool)
        colv = m[:, self.col[cond.field]]
        lo_ok = colv >= cond.lo if cond.lo_inc else colv > cond.lo
        hi_ok = colv <= cond.hi if cond.hi_inc else colv < cond.hi
        mask = jnp.where(jnp.isnan(colv), False, lo_ok & hi_ok)
        return np.asarray(mask)


class SearchIndex:
    """One FT index: schema + doc table + inverted/tag/numeric planes."""

    def __init__(
        self,
        name: str,
        schema: Dict[str, str],
        prefixes: Sequence[str] = ("",),
        doc_mode: str = "entry",
        engine=None,
        vector_specs: Optional[Dict[str, Any]] = None,
    ):
        self.name = name
        self.schema = dict(schema)
        self.prefixes = list(prefixes)
        # device-resident embedding banks (FT VECTOR fields, ISSUE 11):
        # rowids shared with the numeric plane, banks record-backed so they
        # place/rebalance/tear down like every other record.  Requires the
        # engine; an engine-less index (unit-test construction) refuses
        # VECTOR fields rather than silently indexing nothing.
        self.vector_specs = dict(vector_specs or {})
        if self.vector_specs and engine is None:
            raise ValueError("VECTOR fields need an engine-bound index")
        if engine is not None and self.vector_specs:
            from redisson_tpu.services.vector import VectorPlane

            self.vectors = VectorPlane(engine, name, self.vector_specs)
        else:
            self.vectors = None
        # document model for auto-ingestion (SearchService.sync):
        #   "entry" — one doc per dict-valued map ENTRY, id "{map}:{key}"
        #             (the embedded facade's historical model)
        #   "hash"  — one doc per map RECORD, id = map name (RediSearch's
        #             ON HASH model, used by the FT.* wire verbs)
        # One model per index: the two disagree on doc identity, and mixing
        # them through the shared version stamps would suppress each other.
        if doc_mode not in ("entry", "hash"):
            raise ValueError(f"unknown doc_mode {doc_mode!r}")
        self.doc_mode = doc_mode
        self.docs: Dict[str, Dict[str, Any]] = {}          # doc_id -> fields
        self._rowid: Dict[str, int] = {}                   # doc_id -> numeric row
        self._rowdoc = _RowDocs()                          # row -> doc_id
        self._text: Dict[str, Dict[str, set]] = {
            f: {} for f, t in schema.items() if t == FieldType.TEXT
        }                                                   # field -> word -> ids
        self._tag: Dict[str, Dict[Any, set]] = {
            f: {} for f, t in schema.items() if t == FieldType.TAG
        }
        self._numeric = _NumericPlane(
            [f for f, t in schema.items() if t == FieldType.NUMERIC]
        )
        self._synced_versions: Dict[str, Any] = {}          # map name -> stamp
        # index-at-write (hash mode): keys written since the last drain (the
        # hooks append and the one drainer pops, lock-free: a deque's ends
        # are atomic, so no write is lost between the two), the flush count
        # the documents are of, and the keys that carry a TTL
        self._dirty: deque = deque()
        self._prefix_tuple = tuple(self.prefixes)
        self._flushes = 0
        self._expiring: Dict[str, float] = {}
        self._next_expiry = float("inf")
        self._drain_lock = threading.Lock()
        # synonym groups (FT.SYNUPDATE/SYNDUMP): group id -> lowercase terms,
        # and the reverse map consulted at query time
        self.synonyms: Dict[str, set] = {}
        self._syn_of: Dict[str, set] = {}
        self._lock = threading.RLock()

    # -- synonyms (RediSearch FT.SYNUPDATE / FT.SYNDUMP) ---------------------

    def syn_update(self, group_id: str, terms: Sequence[str]) -> None:
        with self._lock:
            g = self.synonyms.setdefault(group_id, set())
            for t in terms:
                t = str(t).lower()
                g.add(t)
                self._syn_of.setdefault(t, set()).add(group_id)

    def syn_dump(self) -> Dict[str, List[str]]:
        """term -> sorted group ids (the FT.SYNDUMP reply shape)."""
        with self._lock:
            return {t: sorted(gs) for t, gs in self._syn_of.items()}

    # -- document maintenance ------------------------------------------------

    def add(self, doc_id: str, fields: Dict[str, Any]) -> None:
        with self._lock:
            if doc_id in self.docs:
                self._unindex(doc_id)
                self.docs[doc_id] = dict(fields)
                self._index_inverted(doc_id, fields)
                row = self._rowid[doc_id]
                self._numeric.replace(row, fields)
            else:
                self.docs[doc_id] = dict(fields)
                self._index_inverted(doc_id, fields)
                row = self._numeric.append(fields)
                self._rowid[doc_id] = row
                self._rowdoc.append(doc_id)
            if self.vectors:
                self.vectors.set_row(row, fields)

    def remove(self, doc_id: str) -> bool:
        with self._lock:
            if doc_id not in self.docs:
                return False
            self._unindex(doc_id)
            del self.docs[doc_id]
            row = self._rowid.pop(doc_id)
            self._rowdoc[row] = None
            self._numeric.clear_row(row)
            if self.vectors:
                self.vectors.clear_row(row)
            return True

    def _index_inverted(self, doc_id: str, fields: Dict[str, Any]) -> None:
        for f, words in self._text.items():
            for w in tokenize(fields.get(f, "")):
                words.setdefault(w, set()).add(doc_id)
        for f, tags in self._tag.items():
            v = fields.get(f)
            if v is not None:
                tags.setdefault(v, set()).add(doc_id)

    def _unindex(self, doc_id: str) -> None:
        old = self.docs[doc_id]
        for f, words in self._text.items():
            for w in tokenize(old.get(f, "")):
                ids = words.get(w)
                if ids is not None:
                    ids.discard(doc_id)
        for f, tags in self._tag.items():
            v = old.get(f)
            if v is not None and v in tags:
                tags[v].discard(doc_id)

    # -- evaluation ----------------------------------------------------------

    def _eval(self, cond: Optional[Condition]) -> set:
        with self._lock:
            if cond is None:
                return set(self.docs)
            return self._eval_inner(cond)

    def _eval_inner(self, cond: Condition) -> set:
        if isinstance(cond, And):
            sets = [self._eval_inner(p) for p in cond.parts]
            return set.intersection(*sets) if sets else set(self.docs)
        if isinstance(cond, Or):
            out: set = set()
            for p in cond.parts:
                out |= self._eval_inner(p)
            return out
        if isinstance(cond, Text):
            words = tokenize(cond.query)
            plane = self._text.get(cond.field, {})
            sets = []
            for w in words:
                ids = set(plane.get(w, set()))
                # synonym expansion (FT.SYNUPDATE groups): a query term
                # matches docs containing ANY member of its groups —
                # RediSearch semantics, index-time groups applied query-side
                for g in self._syn_of.get(w, ()):
                    for w2 in self.synonyms.get(g, ()):
                        ids |= plane.get(w2, set())
                sets.append(ids)
            return set.intersection(*sets) if sets else set()
        if isinstance(cond, Eq):
            ftype = self.schema.get(cond.field)
            if ftype == FieldType.TAG:
                return set(self._tag.get(cond.field, {}).get(cond.value, set()))
            if ftype == FieldType.NUMERIC:
                v = float(cond.value)
                return self._mask_to_ids(self._numeric.range_mask(Range(cond.field, v, v)))
            if ftype == FieldType.TEXT:
                return self._eval_inner(Text(cond.field, str(cond.value)))
            return {d for d, f in self.docs.items() if f.get(cond.field) == cond.value}
        if isinstance(cond, In):
            out = set()
            for v in cond.values:
                out |= self._eval_inner(Eq(cond.field, v))
            return out
        if isinstance(cond, Range):
            return self._mask_to_ids(self._numeric.range_mask(cond))
        raise TypeError(f"unknown condition {cond!r}")

    def _mask_to_ids(self, mask: np.ndarray) -> set:
        return {
            self._rowdoc[i]
            for i in np.nonzero(mask)[0]
            if self._rowdoc[i] is not None
        }

    def __len__(self) -> int:
        return len(self.docs)


# -- results -----------------------------------------------------------------


@dataclass
class SearchResult:
    total: int
    docs: List[Tuple[str, Dict[str, Any]]]


# -- service -----------------------------------------------------------------


def _edit_distance_le(a: str, b: str, k: int) -> bool:
    """Levenshtein distance <= k (banded DP; FT.SPELLCHECK DISTANCE 1-4)."""
    if abs(len(a) - len(b)) > k:
        return False
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i] + [0] * len(b)
        best = i
        for j, cb in enumerate(b, 1):
            cur[j] = min(
                prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + (ca != cb)
            )
            best = min(best, cur[j])
        if best > k:
            return False
        prev = cur
    return prev[-1] <= k


class SearchService:
    """RSearch analog bound to one engine."""

    def __init__(self, engine):
        self._engine = engine
        self._indexes: Dict[str, SearchIndex] = {}
        self._aliases: Dict[str, str] = {}       # alias -> index name
        self._dicts: Dict[str, set] = {}         # FT.DICT* custom dictionaries
        # FT.CURSOR id -> (pending rows, expires_at): abandoned cursors are
        # pruned by idle timeout + a hard cap, like RediSearch's cursor
        # expiry — without it every undrained WITHCURSOR leaks its rows for
        # the server's lifetime
        self._cursors: Dict[int, Tuple[List[Any], float]] = {}
        self._next_cursor = 1
        self._lock = threading.Lock()
        # the hash-mode indexes the write hooks report to; a new tuple at
        # every FT.CREATE / DROPINDEX / ALTER, so the hooks read it unlocked
        self._hooked: Tuple[SearchIndex, ...] = ()
        self._flushes = 0  # FLUSHALLs the store has told of

    CURSOR_TTL = 300.0
    CURSOR_MAX = 128

    def _prune_cursors_locked(self) -> None:
        import time as _time

        now = _time.time()
        for cid in [c for c, (_r, exp) in self._cursors.items() if exp <= now]:
            del self._cursors[cid]
        while len(self._cursors) > self.CURSOR_MAX:
            del self._cursors[min(self._cursors)]  # oldest id first

    # -- FT.CREATE / DROPINDEX / _LIST ---------------------------------------

    @staticmethod
    def _vector_specs(schema: Dict[str, str], vector) -> Dict[str, Any]:
        """Normalize the `vector` argument ({field: VectorFieldSpec | spec
        kwargs}) and cross-check it against the schema's VECTOR fields."""
        from redisson_tpu.services.vector import VectorFieldSpec

        specs: Dict[str, Any] = {}
        for f, spec in (vector or {}).items():
            if not isinstance(spec, VectorFieldSpec):
                spec = VectorFieldSpec(field=f, **dict(spec))
            specs[f] = spec
        declared = {f for f, t in schema.items() if t == FieldType.VECTOR}
        if declared != set(specs):
            raise ValueError(
                f"VECTOR schema fields {sorted(declared)} need matching "
                f"vector specs (got {sorted(specs)})"
            )
        return specs

    def create_index(
        self,
        name: str,
        schema: Dict[str, str],
        prefixes: Sequence[str] = ("",),
        doc_mode: str = "entry",
        vector: Optional[Dict[str, Any]] = None,
    ) -> SearchIndex:
        specs = self._vector_specs(schema, vector)
        with self._lock:
            if name in self._indexes:
                raise ValueError(f"index '{name}' already exists")
            idx = SearchIndex(
                name, schema, prefixes, doc_mode,
                engine=self._engine, vector_specs=specs,
            )
            self._indexes[name] = idx
            self._rehook_locked()
        self._scan(idx)  # the hooks are armed: a write racing the scan is dirty
        return idx

    def create(
        self,
        name: str,
        schema: Dict[str, str],
        prefixes: Sequence[str] = ("",),
        doc_mode: str = "entry",
        vector: Optional[Dict[str, Any]] = None,
    ) -> bool:
        """Wire-friendly FT.CREATE (returns a plain bool so it survives the
        OBJCALL pickle boundary; `create_index` returns the live index)."""
        self.create_index(name, schema, prefixes, doc_mode, vector=vector)
        return True

    def drop_index(self, name: str) -> bool:
        with self._lock:
            idx = self._indexes.pop(name, None)
            self._rehook_locked()
        if idx is not None and idx.vectors:
            # bank records leave the store with the index — device memory is
            # released through the ordinary teardown path, so the census's
            # ftvec gauges return to baseline (the HBM-ledger brick)
            idx.vectors.drop()
        return idx is not None

    def index_names(self) -> List[str]:
        with self._lock:
            return sorted(self._indexes)

    def _idx(self, name: str) -> SearchIndex:
        with self._lock:
            name = self._aliases.get(name, name)
            idx = self._indexes.get(name)
        if idx is None:
            raise KeyError(f"no such index '{name}'")
        return idx

    def resolve(self, name: str) -> str:
        """Alias -> real index name (identity for real names)."""
        with self._lock:
            return self._aliases.get(name, name)

    # -- FT.ALTER ------------------------------------------------------------

    def alter(self, name: str, field: str, ftype: str) -> None:
        """FT.ALTER idx SCHEMA ADD field type: rebuild the index with the
        widened schema and re-add every stored doc (the numeric plane's
        column set is fixed at construction, so ALTER swaps the index the
        way RediSearch rescans)."""
        old = self._idx(name)
        if field in old.schema:
            raise ValueError(f"field '{field}' already exists")
        schema = dict(old.schema)
        schema[field] = ftype
        fresh = SearchIndex(
            old.name, schema, old.prefixes, old.doc_mode,
            engine=self._engine, vector_specs=old.vector_specs,
        )
        with old._lock:
            for doc_id, fields in old.docs.items():
                fresh.add(doc_id, fields)
        with self._lock:
            self._indexes[old.name] = fresh
            self._rehook_locked()
        self._scan(fresh)

    # -- FT.ALIAS* -----------------------------------------------------------

    def alias_add(self, alias: str, index: str) -> None:
        self._idx(index)  # KeyError if unknown
        with self._lock:
            if alias in self._aliases:
                raise ValueError(f"alias '{alias}' already exists")
            self._aliases[alias] = self._aliases.get(index, index)

    def alias_update(self, alias: str, index: str) -> None:
        self._idx(index)
        with self._lock:
            self._aliases[alias] = self._aliases.get(index, index)

    def alias_del(self, alias: str) -> None:
        with self._lock:
            if alias not in self._aliases:
                raise ValueError(f"alias '{alias}' does not exist")
            del self._aliases[alias]

    # -- FT.DICT* ------------------------------------------------------------

    def dict_add(self, name: str, *terms: str) -> int:
        with self._lock:
            d = self._dicts.setdefault(name, set())
            before = len(d)
            d.update(terms)
            return len(d) - before

    def dict_del(self, name: str, *terms: str) -> int:
        with self._lock:
            d = self._dicts.get(name, set())
            n = 0
            for t in terms:
                if t in d:
                    d.discard(t)
                    n += 1
            return n

    def dict_dump(self, name: str) -> List[str]:
        with self._lock:
            return sorted(self._dicts.get(name, ()))

    # -- FT.SPELLCHECK -------------------------------------------------------

    def spellcheck(
        self, index: str, query: str, include: Sequence[str] = (),
        exclude: Sequence[str] = (), distance: int = 1,
    ) -> Dict[str, List[Tuple[float, str]]]:
        """Suggestions for query terms absent from the index vocabulary
        (RediSearch FT.SPELLCHECK): candidates come from the index's TEXT
        terms plus INCLUDE dicts, minus EXCLUDE dicts; scored by the share
        of docs containing the suggestion (the RediSearch score shape)."""
        idx = self._idx(index)
        self.sync(self.resolve(index))
        vocab: Dict[str, int] = {}
        with idx._lock:
            ndocs = max(1, len(idx.docs))
            for words in idx._text.values():
                for w, ids in words.items():
                    if ids:
                        vocab[w] = max(vocab.get(w, 0), len(ids))
        with self._lock:
            included = set().union(*(self._dicts.get(d, set()) for d in include)) if include else set()
            excluded = set().union(*(self._dicts.get(d, set()) for d in exclude)) if exclude else set()
        known = (set(vocab) | included) - excluded
        out: Dict[str, List[Tuple[float, str]]] = {}
        for term in tokenize(query):
            if term in known:
                continue
            sugg = [
                (vocab.get(c, 0) / ndocs if c in vocab else 0.0, c)
                for c in known
                if _edit_distance_le(term, c, distance)
            ]
            sugg.sort(key=lambda t: (-t[0], t[1]))
            out[term] = sugg
        return out

    # -- FT.CURSOR -----------------------------------------------------------

    def cursor_create(self, rows: List[Any]) -> int:
        import time as _time

        with self._lock:
            cid = self._next_cursor
            self._next_cursor += 1
            self._cursors[cid] = (list(rows), _time.time() + self.CURSOR_TTL)
            self._prune_cursors_locked()  # after insert: cap includes the new one
            return cid

    def cursor_read(self, cid: int, count: int) -> Tuple[List[Any], int]:
        """Returns (rows, next_cursor_id); 0 = exhausted (and deleted).
        A read refreshes the cursor's idle deadline."""
        import time as _time

        with self._lock:
            self._prune_cursors_locked()
            entry = self._cursors.get(cid)
            if entry is None:
                raise KeyError(f"no such cursor {cid}")
            pending, _exp = entry
            rows, rest = pending[:count], pending[count:]
            if rest:
                self._cursors[cid] = (rest, _time.time() + self.CURSOR_TTL)
                return rows, cid
            del self._cursors[cid]
            return rows, 0

    def cursor_del(self, cid: int) -> None:
        with self._lock:
            if cid not in self._cursors:
                raise KeyError(f"no such cursor {cid}")
            del self._cursors[cid]

    def info(self, name: str) -> Dict[str, Any]:
        idx = self._idx(name)
        out = {
            "name": idx.name,
            "num_docs": len(idx),
            "schema": dict(idx.schema),
            "prefixes": list(idx.prefixes),
        }
        if idx.vectors:
            out["vector_fields"] = idx.vectors.info_rows()
            out["vector_device_bytes"] = idx.vectors.device_bytes()
            out["vector_index_bytes"] = idx.vectors.index_device_bytes()
        return out

    def device_census(self) -> Dict[str, float]:
        """Embedding-bank residency gauges — the first concrete brick of the
        ROADMAP HBM-ledger item: per-process bank count + device bytes (and
        per-index byte rows for FT.INFO).  Feeds MetricsRegistry gauges and
        ResourceCensus rows; the vector soak asserts these return to
        baseline after FT.DROPINDEX."""
        with self._lock:
            indexes = list(self._indexes.values())
        banks = 0
        total = 0
        index_bytes = 0
        by_dev: Dict[int, float] = {}
        idx_by_dev: Dict[int, float] = {}
        for idx in indexes:
            if idx.vectors:
                banks += len(idx.vectors.banks)
                total += idx.vectors.device_bytes()
                index_bytes += idx.vectors.index_device_bytes()
                for d, v in idx.vectors.device_bytes_by_device().items():
                    by_dev[d] = by_dev.get(d, 0.0) + float(v)
                for d, v in idx.vectors.index_bytes_by_device().items():
                    idx_by_dev[d] = idx_by_dev.get(d, 0.0) + float(v)
        out = {
            "ftvec_banks": float(banks),
            "ftvec_device_bytes": float(total),
            # the IVF coarse index (centroids + cell table) — its own row
            # so soaks catch a cell-index leak on DROPINDEX even when the
            # bank itself tears down correctly
            "ftvec_index_bytes": float(index_bytes),
        }
        # per-DEVICE breakdown (ISSUE 15 satellite — the HBM-capacity
        # ledger's first per-chip rows): which chip holds how many bank /
        # coarse-index bytes.  Rows exist only while a device holds bytes,
        # so DROPINDEX returns every shard's row to absence == zero (the
        # sharded soak pins that).
        for d, v in sorted(by_dev.items()):
            out[f"ftvec_device_bytes_dev{d}"] = v
        for d, v in sorted(idx_by_dev.items()):
            out[f"ftvec_index_bytes_dev{d}"] = v
        return out

    # -- tracking-plane integration (ISSUE 11) --------------------------------
    #
    # FT.* is keyless on the wire, so the generic key-based tracking hooks
    # never see it.  A tracked FT.SEARCH registers the index's synthetic
    # QUERY KEY instead, and the index's INGEST STREAM (writes landing under
    # its prefixes, index DDL) invalidates that key — hot query results
    # near-cache client-side and go stale the moment the index can change.

    @staticmethod
    def query_key(name: str) -> str:
        return f"__ftq__:{name}"

    def ingest_touched(self, written_names: Sequence[str]) -> List[str]:
        """Query keys of every hash-mode index whose prefixes cover any of
        the written key names (the write-side invalidation hook the server's
        TrackingTable calls post-dispatch)."""
        with self._lock:
            indexes = list(self._indexes.items())
        out = []
        for name, idx in indexes:
            if idx.doc_mode != "hash":
                continue
            if any(
                n.startswith(p)
                for p in idx.prefixes
                for n in written_names
            ):
                out.append(self.query_key(name))
        return out

    # -- KNN (FT VECTOR, services/vector.py) ----------------------------------

    def knn(self, index: str, field: str, queries, k: int,
            condition: Optional[Condition] = None,
            nprobe: Optional[int] = None, warm: bool = False,
            columns: bool = False):
        """One stacked KNN over the index's embedding bank (FLAT exact, or
        routed IVF once the field's coarse quantizer trained; ``nprobe``
        overrides the IVF field's probe width for this query; ``warm``: the
        queries are a frame's stacked run — EmbeddingBank.knn_async).

        Returns ``(device, finish)``: with the device plane armed, `device`
        is the (dist, idx) kernel-output pair — the caller wraps it in a
        LazyReply / ReadbackFuture and calls ``finish((dist, idx))`` with
        the fetched host arrays; disarmed (RTPU_NO_VECTOR), `device` is
        None and ``finish(None)`` scores on the NumPy path.  Either way
        ``finish`` maps rows back to doc ids and returns one
        ``[(doc_id, distance), ...]`` list per query (distance ascending,
        ties toward the lower rowid) — or, with ``columns``, the same hits
        as ``(doc ids (H,), distances (H,), ends (nq,))``, query after query,
        ``ends`` where each query's hits end (hit_lists makes the lists)."""
        from redisson_tpu.services import vector as V

        idx = self._idx(index)
        bank = idx.vectors.banks.get(field) if idx.vectors else None
        if bank is None:
            raise ValueError(f"'{field}' is not a VECTOR field of '{index}'")
        if nprobe and bank.spec.algo != "IVF":
            # validated HERE, before either scoring path dispatches: the
            # disarmed path resolves inside finish() — past the verb's
            # ValueError->RespError mapping — so a late raise would reply
            # 'ERR internal' disarmed but a clean error armed
            raise ValueError("NPROBE applies to an IVF field")
        q = np.ascontiguousarray(queries, np.float32).reshape(-1, bank.spec.dim)
        nq = q.shape[0]
        shape = (lambda *cols: cols) if columns else hit_lists
        none = (np.empty(0, object), np.empty(0), np.zeros(nq, np.int64))
        allowed = None
        if condition is not None:
            ids = idx._eval(condition)
            with idx._lock:
                allowed = np.fromiter(
                    (idx._rowid[d] for d in ids if d in idx._rowid),
                    np.int64,
                )
            if allowed.size == 0:
                return None, lambda _vals: shape(*none)
        armed = V.vector_enabled()
        out = (
            bank.knn_async(q, k, allowed_rows=allowed, nprobe=nprobe,
                           warm=warm)
            if armed else None
        )
        if armed and out is None:
            return None, lambda _vals: shape(*none)

        def finish(vals):
            if vals is None:  # disarmed: score now, on host
                host = bank.knn_host(q, k, allowed_rows=allowed,
                                     nprobe=nprobe)
                if host is None:
                    return shape(*none)
                dist_h, idx_h = host[0], host[1]
            else:
                # the bank decodes its own device outputs to GLOBAL rowids:
                # (dist, idx) for plain banks, (dist, shard, local) for the
                # mesh-sharded facade (gmap decode off the readback path)
                dist_h, idx_h = bank.resolve_hits(vals)
            dist_h, idx_h = dist_h[:nq], idx_h[:nq]  # the bucket's padding
            # rows -> doc ids in one lookup; a non-finite distance is a
            # padding entry (k exceeded the live rows), a None a document
            # deleted between dispatch and fetch
            docs = idx._rowdoc.take(idx_h)
            ok = np.isfinite(dist_h) & (docs != None)  # noqa: E711 — elementwise
            qis, _j = np.nonzero(ok)  # row-major: reply order
            if not len(qis):
                return shape(*none)
            # the kernel/NumPy paths choose WHICH rows win; the scores on
            # the wire come from ONE canonical per-pair routine so armed
            # and disarmed replies are byte-identical (vector.pair_scores)
            scores = bank.pair_scores(q, qis, idx_h[ok])
            return shape(docs[ok], scores, np.cumsum(ok.sum(axis=1)))

        if not armed:
            return None, finish
        # device arrays lead, (q_count, k_eff) trail: (dist, idx) for the
        # plain bank, (dist, shard, local) for the sharded facade — the
        # LazyReply grouped readback is tuple-length agnostic
        return tuple(out[:-2]), finish

    # -- document ingestion --------------------------------------------------

    def add_document(self, index: str, doc_id: str, fields: Dict[str, Any]) -> None:
        self._idx(index).add(doc_id, fields)

    def remove_document(self, index: str, doc_id: str) -> bool:
        return self._idx(index).remove(doc_id)

    # -- index at the write ----------------------------------------------------

    def _rehook_locked(self) -> None:
        """Point the engine's and the store's write hooks at the hash-mode
        indexes there are; with none, take the hooks off — a write then
        costs one attribute load and an is-None."""
        self._hooked = tuple(
            i for i in self._indexes.values() if i.doc_mode == "hash"
        )
        armed = bool(self._hooked)
        self._engine.ingest_hook = self._note_key if armed else None
        self._engine.store.on_change = self._note_keys if armed else None

    def _note_key(self, name: str) -> None:
        """A record of this name changed (Engine.ingest_hook).  Lock-free and
        store-free: it runs under the writer's record lock."""
        for idx in self._hooked:
            if name.startswith(idx._prefix_tuple):
                idx._dirty.append(name)

    def _note_keys(self, names) -> None:
        """Keys were installed, deleted, renamed, given a TTL or reaped
        (DeviceStore.on_change); None: the store was flushed."""
        if names is None:
            self._flushes += 1
            return
        for name in names:
            self._note_key(name)

    def has_dirty(self) -> bool:
        return any(i._dirty for i in self._hooked)

    def drain_all(self) -> int:
        """Bring every hash-mode index up to the writes applied so far (the
        server calls it at the end of a frame that left keys dirty)."""
        return sum(self._drain(i) for i in self._hooked if i._dirty)

    def current(self, name: str) -> SearchIndex:
        """The index an FT.* command answers from, up to date with every
        write applied before the call: a hash-mode index drains its dirty
        keys (and stands empty again after a FLUSHALL); an entry-mode one is
        as its last sync() left it."""
        idx = self._idx(name)
        if idx.doc_mode == "hash":
            self._drain(idx)
            idx = self._idx(name)  # a flush replaced it
        return idx

    def _after_flush(self, idx: SearchIndex) -> SearchIndex:
        """FLUSHALL took every document and the bank records with them: the
        definition stays, over an empty index with banks of its own, which
        is returned.  The dirty set goes along: it holds the keys written
        since the flush."""
        fresh = SearchIndex(
            idx.name, idx.schema, idx.prefixes, idx.doc_mode,
            engine=self._engine, vector_specs=idx.vector_specs,
        )
        fresh.synonyms, fresh._syn_of = idx.synonyms, idx._syn_of
        fresh._dirty = idx._dirty
        fresh._flushes = idx._flushes = self._flushes
        with self._lock:
            if self._indexes.get(idx.name) is not idx:
                return self._indexes.get(idx.name, idx)  # replaced meanwhile
            self._indexes[idx.name] = fresh
            self._rehook_locked()
        return fresh

    def _read_hash(self, idx: SearchIndex, key: str, rec) -> Dict[str, Any]:
        # wire hashes hold RAW bytes (typed HSET surface): a plain map's
        # host dict holds them as BytesCodec would hand them back; any other
        # kind is read through its handle.  Decoded to str below
        if rec.kind == "map":
            entries = list(rec.host.items())
        else:
            from redisson_tpu.client.codec import BytesCodec
            from redisson_tpu.client.objects.map import Map

            entries = Map(self._engine, key, codec=BytesCodec()).read_all_entry_set()
        fields = {}
        for k, v in entries:
            ks = k.decode() if isinstance(k, (bytes, bytearray)) else str(k)
            if idx.schema.get(ks) == FieldType.VECTOR:
                # raw float32 blob (the RediSearch HSET wire shape):
                # utf-8 decoding arbitrary vector bytes would throw
                fields[ks] = bytes(v) if isinstance(v, (bytes, bytearray)) else v
                continue
            vs = v.decode() if isinstance(v, (bytes, bytearray)) else v
            if idx.schema.get(ks) == FieldType.NUMERIC:
                try:
                    vs = float(vs)
                except (TypeError, ValueError):
                    pass
            fields[ks] = vs
        return fields

    def _ingest_hash(self, idx: SearchIndex, key: str) -> int:
        """One key of a hash-mode index against the store: 1 if the index
        changed.  A key that is gone, expired or no hash any more leaves the
        index; one with a TTL is looked at again when it has passed."""
        rec = self._engine.store.get_unguarded(key)
        if rec is None or rec.kind not in ("map", "map_cache"):
            idx._expiring.pop(key, None)
            idx._synced_versions.pop(key, None)
            return int(idx.remove(key))
        if rec.expire_at is not None:
            idx._expiring[key] = rec.expire_at
            idx._next_expiry = min(idx._next_expiry, rec.expire_at)
        else:
            idx._expiring.pop(key, None)
        stamp = (rec.nonce, rec.version)  # versions restart at a recreate
        if idx._synced_versions.get(key) == stamp:
            return 0
        idx.add(key, self._read_hash(idx, key, rec))
        idx._synced_versions[key] = stamp
        return 1

    def _drain(self, idx: SearchIndex) -> int:
        """Apply the writes the hooks reported since the last drain: O(dirty
        keys), whatever the keyspace holds."""
        import time as _time

        with idx._drain_lock:
            if idx._flushes == self._flushes:
                now = _time.time()
                if idx._next_expiry <= now:
                    idx._dirty.extend(
                        k for k, at in idx._expiring.items() if at <= now
                    )
                    idx._next_expiry = min(
                        (at for at in idx._expiring.values() if at > now),
                        default=float("inf"),
                    )
                dirty = set()
                while idx._dirty:
                    dirty.add(idx._dirty.popleft())
                n = sum(self._ingest_hash(idx, key) for key in dirty)
                _count(docs=n)
                return n
            fresh = self._after_flush(idx)
        return self._drain(fresh)

    def sync(self, name: str) -> int:
        """Bring an index up to date and say how many documents changed.  A
        hash-mode index drains what its write hooks reported; an entry-mode
        one pulls from every map whose name matches a prefix, as a
        version-diffed scan (maps whose version is unchanged are skipped)."""
        idx = self._idx(name)
        if idx.doc_mode == "hash":
            return self._drain(idx)
        return self._scan(idx)

    def _scan(self, idx: SearchIndex) -> int:
        """Walk the keyspace for the keys under the index's prefixes: what
        FT.CREATE / FT.ALTER do once over the keys that were there before
        the index, and what an entry-mode sync() is."""
        from redisson_tpu.client.objects.map import Map

        n = 0
        keys = self._engine.store.keys()
        _count(keys=len(keys))
        with idx._drain_lock:
            idx._flushes = self._flushes
            for key in keys:
                if not key.startswith(idx._prefix_tuple):
                    continue
                if idx.doc_mode == "hash":
                    n += self._ingest_hash(idx, key)
                    continue
                rec = self._engine.store.get(key)
                if rec is None or rec.kind not in ("map", "map_cache"):
                    continue
                if idx._synced_versions.get(key) == rec.version:
                    continue
                for k, v in Map(self._engine, key).read_all_entry_set():
                    if isinstance(v, dict):
                        idx.add(f"{key}:{k}", v)
                        n += 1
                idx._synced_versions[key] = rec.version
        return n

    # -- FT.SEARCH -----------------------------------------------------------

    def search(
        self,
        index: str,
        condition: Optional[Condition] = None,
        sort_by: Optional[str] = None,
        descending: bool = False,
        offset: int = 0,
        limit: int = 10,
    ) -> SearchResult:
        idx = self._idx(index)
        ids = idx._eval(condition)
        docs = [(d, idx.docs[d]) for d in ids]
        if sort_by is not None:
            docs.sort(
                key=lambda kv: (kv[1].get(sort_by) is None, kv[1].get(sort_by)),
                reverse=descending,
            )
        else:
            docs.sort(key=lambda kv: kv[0])
        return SearchResult(total=len(docs), docs=docs[offset : offset + limit])

    # -- FT.AGGREGATE ---------------------------------------------------------

    _REDUCERS = {
        "count": lambda xs: len(xs),
        "sum": lambda xs: float(np.sum(xs)) if len(xs) else 0.0,
        "avg": lambda xs: float(np.mean(xs)) if len(xs) else float("nan"),
        "min": lambda xs: float(np.min(xs)) if len(xs) else float("nan"),
        "max": lambda xs: float(np.max(xs)) if len(xs) else float("nan"),
    }

    def aggregate(
        self,
        index: str,
        condition: Optional[Condition] = None,
        group_by: Optional[str] = None,
        reducers: Optional[Dict[str, Tuple[str, Optional[str]]]] = None,
        sort_by: Optional[str] = None,
        descending: bool = False,
        offset: int = 0,
        limit: Optional[int] = None,
    ) -> List[Dict[str, Any]]:
        """GROUPBY + REDUCE [+ SORTBY + LIMIT].  `reducers` maps output
        name -> (op, field); ops: count/sum/avg/min/max (field ignored for
        count).  `sort_by` names any OUTPUT column (the group key or a
        reducer name), with offset/limit paging — the FT.AGGREGATE
        SORTBY/LIMIT pipeline stages (RedissonSearch.java aggregate)."""
        idx = self._idx(index)
        ids = idx._eval(condition)
        reducers = reducers or {"count": ("count", None)}
        groups: Dict[Any, List[Dict[str, Any]]] = {}
        for d in ids:
            fields = idx.docs[d]
            key = fields.get(group_by) if group_by else None
            groups.setdefault(key, []).append(fields)
        out = []
        for key, members in groups.items():
            row: Dict[str, Any] = {} if group_by is None else {group_by: key}
            for out_name, (op, f) in reducers.items():
                if op == "count":
                    row[out_name] = len(members)
                else:
                    xs = np.asarray(
                        [float(m[f]) for m in members if m.get(f) is not None],
                        np.float64,
                    )
                    row[out_name] = self._REDUCERS[op](xs)
            out.append(row)
        if sort_by is not None:
            # type-bucketed key: a column mixing numbers and strings must
            # sort deterministically, not raise int-vs-str TypeError
            def _key(r):
                v = r.get(sort_by)
                if v is None:
                    return (2, "", 0.0)
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    return (0, "", float(v))
                return (1, str(v), 0.0)

            out.sort(key=_key, reverse=descending)
        else:
            out.sort(key=lambda r: (str(r.get(group_by)) if group_by else ""))
        if offset or limit is not None:
            out = out[offset : None if limit is None else offset + limit]
        return out

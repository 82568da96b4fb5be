"""Inverted index: sorted term dictionary + segment bitmaps, per SST.

Mirrors reference `src/index/src/inverted_index` (format.rs:28: an FST of
tag values mapping to bitmaps of row-segment positions) stored in a puffin
container next to each SST (reference `src/puffin`), and mito2's applier
integration (sst/parquet/reader.rs:335-425 prune path; predicate kinds
Eq/In/Range/Regex per search/index_apply.rs:26-58).

Per SST file, one puffin blob per tag column holds:
  - the sorted distinct UTF-8 *values* present (the FST analog — binary
    search replaces FST lookup, an ordered slice replaces FST range scan),
  - one packed bitmap per value over fixed-size row segments
    (``segment_rows`` rows each, finer than parquet row groups).

Scan-time predicates (Eq/In from ``=``/``IN``, Range from comparisons and
BETWEEN, Regex from LIKE and PromQL ``=~``) intersect those bitmaps to
skip whole row groups — and whole files — before any Parquet page is
touched. Pruning is purely an IO reduction: the scan may still return rows
a predicate rejects; the device filter always runs afterwards.

Values (not per-file codes) key the index so it stays valid as the region
tag registry grows.

Blob binary layout (little-endian, blob type "gtpu-inverted-index-v1"):

    u32 n_terms | u32 n_segments | u32 segment_rows | u8 has_null | pad[3]
    u32 term_offsets[n_terms + 1]        # into the term byte stream
    term bytes (utf-8, concatenated)
    bitmaps: (n_terms + has_null) rows x ceil(n_segments/8) bytes,
             packbits(bitorder="little"); the NULL bitmap is last
"""

from __future__ import annotations

import hashlib
import logging
import os
import re
import struct
from dataclasses import dataclass
from typing import Optional, Sequence, Union

import numpy as np

from greptimedb_tpu.objectstore import default_store
from greptimedb_tpu.storage.puffin import PuffinReader, PuffinWriter

logger = logging.getLogger(__name__)

BLOB_TYPE = "gtpu-inverted-index-v1"
DEFAULT_SEGMENT_ROWS = 8192
#: terms x segments above which a tag's blob is not written (8 MiB packed)
MAX_BITMAP_CELLS = 1 << 26


# ---- predicates ------------------------------------------------------------


@dataclass(frozen=True)
class InSet:
    """value ∈ {…} — from ``tag = 'v'`` and ``tag IN (…)``."""

    values: tuple[str, ...]  # sorted

    @staticmethod
    def of(values) -> "InSet":
        return InSet(tuple(sorted(str(v) for v in values)))


@dataclass(frozen=True)
class Range:
    """lo (<|<=) value (<|<=) hi over the tag's string ordering — from
    comparisons and BETWEEN on tag columns. Either bound may be None."""

    lo: Optional[str]
    hi: Optional[str]
    lo_inc: bool = True
    hi_inc: bool = True


@dataclass(frozen=True)
class Regex:
    """value matches an anchored regular expression — from LIKE and
    PromQL ``=~`` matchers."""

    pattern: str


@dataclass(frozen=True, eq=False)
class CodeSet:
    """value's code in the region's tag registry is one of `codes` — an
    `=`-set the caller already resolved against the dictionary (the
    metric engine's label matchers: ten thousand label sets are ten
    thousand ints, not strings to sort and look up). Local to one
    region: it drives the scan's exact row filter, never an SST's index
    (which knows terms, not codes) and never the wire."""

    codes: np.ndarray  # sorted int64
    digest: str

    @staticmethod
    def of(codes) -> "CodeSet":
        codes = np.unique(np.asarray(codes, dtype=np.int64))
        return CodeSet(codes, hashlib.blake2b(
            codes.tobytes(), digest_size=12).hexdigest())

    def __repr__(self) -> str:  # the scan caches' key
        return f"CodeSet({len(self.codes)}:{self.digest})"


Predicate = Union[InSet, Range, Regex, CodeSet]

# A predicate map is tag name -> tuple of Predicates (ANDed), but a plain
# set of values (the historical form, still produced by callers like
# metric_engine and the Flight wire) is accepted anywhere and treated as
# one InSet.
PredicateMap = dict[str, object]


def _norm_preds(v) -> tuple[Predicate, ...]:
    if isinstance(v, (set, frozenset, list)) and not isinstance(v, tuple):
        return (InSet.of(v),)
    if isinstance(v, (InSet, Range, Regex, CodeSet)):
        return (v,)
    out = []
    for p in v:
        out.extend(_norm_preds(p))
    return tuple(out)


def normalize_predicates(preds: Optional[PredicateMap]) \
        -> dict[str, tuple[Predicate, ...]]:
    if not preds:
        return {}
    return {k: _norm_preds(v) for k, v in preds.items()}


def predicates_cache_key(preds: Optional[PredicateMap]):
    """Hashable, order-independent key for scan caches."""
    if not preds:
        return None
    return tuple(sorted(
        (k, tuple(sorted(map(repr, v))))
        for k, v in normalize_predicates(preds).items()
    ))


def serialize_predicates(preds: Optional[PredicateMap]) -> Optional[dict]:
    """JSON-able form for the Flight region-scan wire (reference ships
    these inside the QueryRequest alongside the substrait plan)."""
    if not preds:
        return None
    out: dict[str, list] = {}
    for k, pv in normalize_predicates(preds).items():
        ser = []
        for p in pv:
            if isinstance(p, InSet):
                ser.append({"in": list(p.values)})
            elif isinstance(p, Range):
                ser.append({"range": [p.lo, p.hi, p.lo_inc, p.hi_inc]})
            elif isinstance(p, Regex):
                ser.append({"regex": p.pattern})
        out[k] = ser
    return out


def serialize_predicates_legacy(preds: Optional[PredicateMap]) \
        -> Optional[dict]:
    """Bare value-list wire form — the only shape pre-Range/Regex peers
    parse. Tags whose predicates aren't a single InSet are DROPPED (losing
    pruning, never correctness: pruning is advisory, the scan-side filter
    still runs). Ship alongside serialize_predicates under a separate key
    so either end of a mixed-version pair finds a form it understands."""
    if not preds:
        return None
    out = {}
    for k, pv in normalize_predicates(preds).items():
        if len(pv) == 1 and isinstance(pv[0], InSet):
            out[k] = list(pv[0].values)
    return out or None


def deserialize_predicates(obj) -> Optional[dict]:
    if not obj:
        return None
    out: dict[str, tuple[Predicate, ...]] = {}
    for k, v in obj.items():
        preds: list[Predicate] = []
        if isinstance(v, list) and v and not isinstance(v[0], dict):
            # legacy wire form: bare list of values = one IN set
            preds.append(InSet.of(v))
        else:
            for p in v:
                if "in" in p:
                    preds.append(InSet.of(p["in"]))
                elif "range" in p:
                    lo, hi, li, hi_inc = p["range"]
                    preds.append(Range(lo, hi, li, hi_inc))
                else:
                    preds.append(Regex(p["regex"]))
        out[k] = tuple(preds)
    return out


# ---- build side ------------------------------------------------------------


def _index_path(sst_dir: str, file_id: str) -> str:
    return os.path.join(sst_dir, f"{file_id}.puffin")


class InvertedIndexWriter:
    """Build + persist the per-file index at SST write time (reference
    create/sort_create.rs role; here the values arrive already
    dictionary-encoded, so 'external sort' reduces to bincount over
    codes)."""

    def __init__(self, sst_dir: str, store=None,
                 segment_rows: int = DEFAULT_SEGMENT_ROWS):
        self.sst_dir = sst_dir
        self.store = default_store(store)
        self.segment_rows = int(segment_rows)

    def path(self, file_id: str) -> str:
        return _index_path(self.sst_dir, file_id)

    def write(
        self,
        file_id: str,
        tag_codes: dict[str, np.ndarray],  # tag -> int codes per row
        tag_dicts: dict[str, np.ndarray],  # tag -> value table
        row_group_size: int,
        num_rows: int,
    ) -> None:
        if not tag_codes or num_rows == 0:
            return
        seg = self.segment_rows
        n_segments = (num_rows + seg - 1) // seg
        w = PuffinWriter({"num_rows": num_rows,
                          "row_group_size": int(row_group_size)})
        for tag, codes in tag_codes.items():
            blob = self._build_blob(
                np.asarray(codes), np.asarray(tag_dicts[tag]), n_segments)
            if blob is None:
                logger.info(
                    "sst %s: no index blob for tag %s (terms x %d segments "
                    "> %d): scans prune nothing on it in this file",
                    file_id, tag, n_segments, MAX_BITMAP_CELLS)
            else:
                w.add_blob(BLOB_TYPE, blob, {"column": tag})
        self.store.write(self.path(file_id), w.finish())

    def _build_blob(self, codes: np.ndarray, values: np.ndarray,
                    n_segments: int) -> Optional[bytes]:
        seg = self.segment_rows
        n = len(codes)
        seg_ids = np.arange(n, dtype=np.int64) // seg
        null_rows = codes < 0
        has_null = bool(null_rows.any())

        # distinct codes present, mapped to their sorted-term order
        present = np.unique(codes[~null_rows]) if (~null_rows).any() \
            else np.empty(0, dtype=codes.dtype)
        if len(present) * n_segments > MAX_BITMAP_CELLS:
            # one bitmap row per term: a tag with a term per series (the
            # metric engine's `__labels`) would cost more to build, keep
            # and search than the row groups it could rule out. A file
            # without the blob prunes nothing on this tag (IndexApplier:
            # "tag not indexed in this file") and the scan's exact row
            # filter decides alone
            return None
        terms = np.asarray([str(values[c]) for c in present], dtype=object)
        order = np.argsort(terms, kind="stable")
        terms = terms[order]
        present = present[order]
        n_terms = len(terms)

        # bitmap matrix [n_terms (+null), n_segments]
        rank = np.full(int(values.shape[0]) + 1, -1, dtype=np.int64)
        rank[present] = np.arange(n_terms)
        bm = np.zeros((n_terms + (1 if has_null else 0), n_segments),
                      dtype=bool)
        if n_terms:
            rows = rank[np.where(null_rows, len(values), codes)]
            ok = rows >= 0
            bm[rows[ok], seg_ids[ok]] = True
        if has_null:
            bm[n_terms, seg_ids[null_rows]] = True
        packed = np.packbits(bm, axis=1, bitorder="little").tobytes() \
            if bm.size else b""

        term_bytes = [t.encode() for t in terms]
        offsets = np.zeros(n_terms + 1, dtype=np.uint32)
        offsets[1:] = np.cumsum([len(b) for b in term_bytes])
        return b"".join([
            struct.pack("<IIIB3x", n_terms, n_segments, seg,
                        1 if has_null else 0),
            offsets.tobytes(),
            b"".join(term_bytes),
            packed,
        ])

    def delete(self, file_id: str) -> None:
        path = self.path(file_id)
        if self.store.exists(path):
            self.store.delete(path)
        # remove a pre-puffin JSON sidecar if one exists (format upgrade)
        legacy = os.path.join(self.sst_dir, f"{file_id}.idx.json")
        if self.store.exists(legacy):
            self.store.delete(legacy)


# ---- search side -----------------------------------------------------------


class _TagIndex:
    """Parsed in-memory form of one tag's blob. Bitmaps stay *packed*
    (one byte row per 8 segments); only the term rows a predicate actually
    hits are unpacked — O(hits), not O(n_terms * n_segments)."""

    __slots__ = ("_terms", "_term_blob", "_term_offsets", "_packed",
                 "_n_terms", "_has_null", "n_segments", "segment_rows")

    def __init__(self, data: bytes):
        n_terms, n_segments, seg_rows, has_null = \
            struct.unpack_from("<IIIB", data, 0)
        off = 16
        offsets = np.frombuffer(data, dtype=np.uint32, count=n_terms + 1,
                                offset=off)
        off += 4 * (n_terms + 1)
        # decoded when a predicate first asks for terms: a scan that
        # selects on another tag (or by code: CodeSet) never pays for a
        # blob that holds a term per series
        self._terms = None
        self._term_offsets = offsets
        self._term_blob = data[off:off + int(offsets[-1])]
        off += int(offsets[-1])
        width = (n_segments + 7) // 8
        rows = n_terms + (1 if has_null else 0)
        self._packed = np.frombuffer(
            data, dtype=np.uint8, count=rows * width, offset=off
        ).reshape(rows, width)
        self._n_terms = n_terms
        self._has_null = bool(has_null)
        self.n_segments = n_segments
        self.segment_rows = seg_rows

    @property
    def terms(self) -> list:
        if self._terms is None:
            blob, offsets = self._term_blob, self._term_offsets
            self._terms = [blob[offsets[i]:offsets[i + 1]].decode()
                           for i in range(self._n_terms)]
        return self._terms

    # each evaluator returns a bool[n_segments] of segments that MAY match

    def eval(self, pred: Predicate) -> np.ndarray:
        if isinstance(pred, InSet):
            return self._eval_in(pred.values)
        if isinstance(pred, Range):
            return self._eval_range(pred)
        if isinstance(pred, Regex):
            return self._eval_regex(pred.pattern)
        return np.ones(self.n_segments, dtype=bool)  # CodeSet: no terms

    def _or_rows(self, rows: np.ndarray, with_null: bool) -> np.ndarray:
        idx = list(np.asarray(rows, dtype=np.int64))
        if with_null and self._has_null:
            idx.append(self._n_terms)
        if not idx:
            return np.zeros(self.n_segments, dtype=bool)
        merged = np.bitwise_or.reduce(self._packed[idx], axis=0)
        return np.unpackbits(merged, bitorder="little")[:self.n_segments] \
            .astype(bool)

    def _eval_in(self, values: Sequence[str]) -> np.ndarray:
        terms = self.terms
        lo = np.searchsorted(terms, list(values))
        hits = [
            i for v, i in zip(values, lo)
            if i < len(terms) and terms[i] == v
        ]
        # an absent tag is NULL here but the empty string in PromQL's
        # data model — `host=""` must keep NULL segments
        return self._or_rows(np.asarray(hits, dtype=np.int64),
                             with_null="" in values)

    def _eval_range(self, p: Range) -> np.ndarray:
        terms = self.terms
        lo = 0 if p.lo is None else \
            np.searchsorted(terms, p.lo, side="left" if p.lo_inc else "right")
        hi = len(terms) if p.hi is None else \
            np.searchsorted(terms, p.hi, side="right" if p.hi_inc else "left")
        return self._or_rows(np.arange(lo, max(lo, hi), dtype=np.int64),
                             with_null=False)

    def _eval_regex(self, pattern: str) -> np.ndarray:
        try:
            rx = re.compile(pattern)
        except re.error:
            return np.ones(self.n_segments, dtype=bool)  # can't prune
        hits = np.asarray(
            [i for i, t in enumerate(self.terms) if rx.fullmatch(t)],
            dtype=np.int64)
        return self._or_rows(hits, with_null=rx.fullmatch("") is not None)


@dataclass
class SegmentSelection:
    """Which fixed-size row segments of a file may contain matches."""

    mask: np.ndarray  # bool[n_segments]
    segment_rows: int

    @property
    def is_empty(self) -> bool:
        return not bool(self.mask.any())

    @property
    def all_set(self) -> bool:
        return bool(self.mask.all())

    def group_mask(self, group_row_counts: Sequence[int]) -> np.ndarray:
        """bool[groups]: which parquet row groups hold a surviving
        segment, given each group's row count (reference row-selection
        analog) — one pass over arrays, whatever the group count."""
        rows = np.asarray(group_row_counts, dtype=np.int64)
        start = np.cumsum(rows) - rows
        seg = self.segment_rows
        n = len(self.mask)
        s0 = np.minimum(start // seg, n)
        s1 = np.minimum(np.where(rows > 0,
                                 (start + rows - 1) // seg + 1, s0), n)
        covered = np.concatenate(([0], np.cumsum(self.mask)))
        return covered[s1] > covered[s0]

    def row_groups(self, group_row_counts: Sequence[int]) -> list[int]:
        """`group_mask` as the surviving groups' indices."""
        return np.flatnonzero(self.group_mask(group_row_counts)).tolist()


class IndexApplier:
    """Evaluate tag predicates against a file's index.

    Returns the allowed row-group indices, or None when the file has no
    index / nothing is pruned (scan everything), or [] when provably
    empty."""

    def __init__(self, sst_dir: str, store=None):
        self.sst_dir = sst_dir
        self.store = default_store(store)
        # the parsed index of every file asked about, until the file is
        # deleted (`invalidate`): a scan asks every file of the region,
        # so a cache of fewer slots than the region has files re-reads
        # them all on every scan
        self._cache: dict[str, Optional[dict]] = {}

    def _load(self, file_id: str) -> Optional[dict]:
        if file_id in self._cache:
            return self._cache[file_id]
        entry = None
        path = _index_path(self.sst_dir, file_id)
        if self.store.exists(path):
            reader = PuffinReader(self.store.open_input(path))
            entry = {"tags": {}, "props": reader.properties}
            for blob in reader.blobs_of_type(BLOB_TYPE):
                entry["tags"][blob.properties.get("column")] = \
                    _TagIndex(reader.read_blob(blob))
        self._cache[file_id] = entry
        return entry

    def select(self, file_id: str,
               predicates: Optional[PredicateMap]) -> Optional[SegmentSelection]:
        preds = normalize_predicates(predicates)
        if not preds:
            return None
        data = self._load(file_id)
        if data is None:
            return None
        mask = None
        for tag, plist in preds.items():
            tix: Optional[_TagIndex] = data["tags"].get(tag)
            if tix is None:
                continue  # tag not indexed in this file
            for p in plist:
                m = tix.eval(p)
                mask = m if mask is None else (mask & m)
                if not mask.any():
                    return SegmentSelection(mask, tix.segment_rows)
        if mask is None:
            return None
        seg_rows = next(iter(data["tags"].values())).segment_rows
        return SegmentSelection(mask, seg_rows)

    def apply(
        self, file_id: str, predicates: Optional[PredicateMap],
        group_row_counts: Optional[Sequence[int]] = None,
    ) -> Optional[list[int]]:
        """Row-group form of `select`. Without `group_row_counts` (parquet
        meta not opened yet) only the fully-empty answer is decidable."""
        sel = self.select(file_id, predicates)
        if sel is None:
            return None
        if sel.is_empty:
            return []
        if sel.all_set:
            return None
        if group_row_counts is None:
            props = self._load(file_id)["props"]
            rg = int(props.get("row_group_size", 0))
            num = int(props.get("num_rows", 0))
            if not rg or not num:
                return None
            group_row_counts = [min(rg, num - s) for s in range(0, num, rg)]
        return sel.row_groups(group_row_counts)

    def invalidate(self, file_id: str) -> None:
        self._cache.pop(file_id, None)


# ---- predicate extraction from SQL -----------------------------------------


def _sql_like_to_regex(pat: str) -> str:
    # inline (?is): the query-side LIKE filter compiles with
    # re.IGNORECASE | re.DOTALL (query/expr.py _like_to_regex) — index
    # pruning must never be stricter than the filter it serves
    out = ["(?is)"]
    for ch in pat:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "".join(out)


def extract_tag_predicates(where, schema) -> dict[str, tuple]:
    """Conservatively extract tag constraints from the top-level
    conjunction of a raw (pre-bind) WHERE AST: `tag = 'v'`, `tag IN (…)`,
    `tag  (<|<=|>|>=)  'v'`, `tag BETWEEN a AND b`, `tag LIKE 'p%'`.
    Anything not provably restrictive is ignored — pruning must never
    drop rows."""
    from greptimedb_tpu.sql import ast

    tags = {c.name for c in schema.tag_columns}
    out: dict[str, list] = {}

    def add(name: str, pred: Predicate):
        out.setdefault(name, []).append(pred)

    def tag_lit(e):
        """(column, literal) if e is `tag OP literal` in either order,
        plus whether the operands were swapped."""
        l, r = e.left, e.right
        swapped = False
        if isinstance(r, ast.Column) and isinstance(l, ast.Literal):
            l, r, swapped = r, l, True
        if isinstance(l, ast.Column) and l.name in tags \
                and isinstance(r, ast.Literal) and r.value is not None:
            return l.name, str(r.value), swapped
        return None

    def walk(e):
        if isinstance(e, ast.BinaryOp) and e.op == "and":
            walk(e.left)
            walk(e.right)
            return
        if isinstance(e, ast.BinaryOp) and e.op == "=":
            hit = tag_lit(e)
            if hit:
                add(hit[0], InSet.of([hit[1]]))
            return
        if isinstance(e, ast.BinaryOp) and e.op in ("<", "<=", ">", ">="):
            hit = tag_lit(e)
            if hit:
                name, v, swapped = hit
                op = e.op
                if swapped:  # 'v' < tag  ==  tag > 'v'
                    op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}[op]
                if op in ("<", "<="):
                    add(name, Range(None, v, hi_inc=(op == "<=")))
                else:
                    add(name, Range(v, None, lo_inc=(op == ">=")))
            return
        if isinstance(e, ast.BinaryOp) and e.op == "like":
            if isinstance(e.left, ast.Column) and e.left.name in tags \
                    and isinstance(e.right, ast.Literal) \
                    and e.right.value is not None:
                add(e.left.name, Regex(_sql_like_to_regex(str(e.right.value))))
            return
        if (
            isinstance(e, ast.Between)
            and not getattr(e, "negated", False)
            and isinstance(e.expr, ast.Column)
            and e.expr.name in tags
            and isinstance(e.low, ast.Literal)
            and isinstance(e.high, ast.Literal)
            and e.low.value is not None
            and e.high.value is not None
        ):
            add(e.expr.name, Range(str(e.low.value), str(e.high.value)))
            return
        if (
            isinstance(e, ast.InList)
            and not e.negated
            and isinstance(e.expr, ast.Column)
            and e.expr.name in tags
            and all(isinstance(i, ast.Literal) for i in e.items)
        ):
            add(e.expr.name,
                InSet.of([str(i.value) for i in e.items
                          if i.value is not None]))

    if where is not None:
        walk(where)
    return {k: tuple(v) for k, v in out.items()}

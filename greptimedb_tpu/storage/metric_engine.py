"""Metric engine: many logical tables over one physical region.

Mirrors reference src/metric-engine (engine.rs:57-98): Prometheus workloads
create one table per metric — thousands to millions of tiny tables — which
would drown a region-per-table design. The reference multiplexes logical
tables onto one physical mito region pair (data + metadata).

TPU-native re-design: the physical data region stores exactly two tag
columns — `__table` (logical table name) and `__labels` (the canonical
serialized label set, i.e. THE SERIES ID as one dictionary code) — plus
`greptime_timestamp` / `greptime_value`. Logical tag columns are virtual:
each label-set value of the region's `__labels` dictionary is parsed ONCE
per process into per-tag code columns over the dictionary (`_LabelCatalog`,
extended when the dictionary grows, never rebuilt), and a scan derives a
table's tag columns by one numpy gather through them. A scan of one
logical table therefore costs what that table's rows cost, whatever else
the region holds; `=` / `=~` predicates on virtual tags become a set of
`__labels` codes pushed to the physical scan beside `__table`. This keeps
the device kernel ABI identical to normal tables while the storage side
collapses arbitrary table counts into one LSM region.

Logical table metadata (the reference's metadata region) lives in the kv
backend under `__metric_engine/`.
"""

from __future__ import annotations

import json
import re
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from greptimedb_tpu.catalog.kv import KvBackend
from greptimedb_tpu.datatypes.recordbatch import RecordBatch
from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema
from greptimedb_tpu.datatypes.types import DataType, SemanticType
from greptimedb_tpu.datatypes.vector import DictVector
from greptimedb_tpu.storage.engine import RegionEngine
from greptimedb_tpu.storage.region import ScanData
from greptimedb_tpu.utils import tracing
from greptimedb_tpu.utils.metrics import (
    METRIC_ENGINE_LABEL_SETS_PARSED,
    METRIC_ENGINE_ROWS,
    METRIC_ENGINE_SCAN_SECONDS,
    METRIC_ENGINE_WRITE_ROWS,
)

TABLE_COL = "__table"
LABELS_COL = "__labels"
TS_COL = "greptime_timestamp"
VALUE_COL = "greptime_value"

META_PREFIX = "__metric_engine/"


def physical_schema() -> Schema:
    return Schema([
        ColumnSchema(TABLE_COL, DataType.STRING, SemanticType.TAG),
        ColumnSchema(LABELS_COL, DataType.STRING, SemanticType.TAG),
        ColumnSchema(TS_COL, DataType.TIMESTAMP_MILLISECOND,
                     SemanticType.TIMESTAMP, nullable=False),
        ColumnSchema(VALUE_COL, DataType.FLOAT64, SemanticType.FIELD),
    ])


def encode_labels(tags: dict[str, Optional[str]]) -> str:
    """Canonical series encoding: sorted k=v pairs, \\x1f-separated (tag
    values may contain commas; \\x1f cannot appear in Prometheus labels)."""
    items = sorted((k, v) for k, v in tags.items() if v is not None)
    return "\x1f".join(f"{k}={v}" for k, v in items)


def decode_labels(s: str) -> dict[str, str]:
    if not s:
        return {}
    out = {}
    for part in s.split("\x1f"):
        k, _, v = part.partition("=")
        out[k] = v
    return out


class _TagColumn:
    """One virtual tag over a region's label sets: `codes[label_code]` is
    the tag's value code in that label set (-1: the set lacks the tag),
    against an append-only value list (codes never move)."""

    def __init__(self, size: int):
        self.values: list[str] = []
        self.index: dict[str, int] = {}
        self.codes = np.full(size, -1, dtype=np.int32)
        self._array: Optional[np.ndarray] = None

    def intern(self, value: str) -> int:
        code = self.index.get(value)
        if code is None:
            code = self.index[value] = len(self.values)
            self.values.append(value)
        return code

    def values_array(self) -> np.ndarray:
        if self._array is None or len(self._array) != len(self.values):
            self._array = np.asarray(self.values, dtype=object)
        return self._array


class _LabelCatalog:
    """A physical region's `__labels` dictionary, parsed: per tag name
    one `_TagColumn` over the dictionary's codes. Registry codes are
    append-only, so the catalog is right for the prefix it has parsed
    and `sync` parses only what the dictionary has grown by."""

    def __init__(self):
        self._lock = threading.Lock()
        self.parsed = 0
        self.tags: dict[str, _TagColumn] = {}

    def sync(self, registry) -> tuple[str, int]:
        """Bring the catalog up to the dictionary. Returns (hit | extend
        | miss, label sets parsed by this call)."""
        if registry.cardinality(LABELS_COL) == self.parsed:
            return "hit", 0
        with self._lock:
            new = registry.values_from(LABELS_COL, self.parsed)
            if not new:
                return "hit", 0
            state = "extend" if self.parsed else "miss"
            self._parse(new)
            METRIC_ENGINE_LABEL_SETS_PARSED.inc(len(new))
            return state, len(new)

    def _parse(self, new: list) -> None:
        """decode_labels over `new`, column-wise (pyarrow's string
        kernels; Python touches distinct tag values only)."""
        import pyarrow as pa
        import pyarrow.compute as pc

        base, n = self.parsed, len(new)
        size = base + n
        for col in self.tags.values():
            col.codes = np.concatenate(
                [col.codes, np.full(n, -1, dtype=np.int32)])
        parts = pc.split_pattern(pa.array(new, type=pa.string()), "\x1f")
        owner = np.repeat(np.arange(n, dtype=np.int64),
                          np.diff(parts.offsets.to_numpy()))
        flat = parts.flatten()
        # "" (no tags at all) splits to one part without a pair
        pair = pc.match_substring(flat, "=")
        flat = flat.filter(pair)
        owner = owner[pair.to_numpy(zero_copy_only=False)]
        if len(flat):
            kv = pc.split_pattern(flat, "=", max_splits=1)
            keys = pc.list_element(kv, 0).dictionary_encode()
            vals = pc.list_element(kv, 1)
            key_codes = keys.indices.to_numpy()
            for kid, tag in enumerate(keys.dictionary.to_pylist()):
                col = self.tags.get(tag)
                if col is None:
                    col = self.tags[tag] = _TagColumn(size)
                sel = np.flatnonzero(key_codes == kid)
                enc = vals.take(pa.array(sel)).dictionary_encode()
                mapping = np.asarray(
                    [col.intern(v) for v in enc.dictionary.to_pylist()],
                    dtype=np.int32)
                col.codes[base + owner[sel]] = \
                    mapping[enc.indices.to_numpy()]
        self.parsed = size

    def column(self, tag: str) -> tuple[np.ndarray, np.ndarray]:
        """(codes over the dictionary, value dictionary) of a tag."""
        col = self.tags.get(tag)
        if col is None:
            return (np.full(self.parsed, -1, dtype=np.int32),
                    np.asarray([], dtype=object))
        return col.codes, col.values_array()

    def matching(self, tag: str, pred) -> Optional[np.ndarray]:
        """bool[label sets] for one `=`-set or regex predicate on a
        virtual tag (an absent tag is the empty string, as in PromQL's
        data model), or None for a predicate that cannot prune."""
        from greptimedb_tpu.storage.index import InSet, Regex

        codes, values = self.column(tag)
        lut = np.zeros(len(values) + 1, dtype=bool)  # slot -1: tag absent
        if isinstance(pred, InSet):
            index = self.tags[tag].index if tag in self.tags else {}
            lut[np.asarray([index[v] for v in pred.values if v in index],
                           dtype=np.int64)] = True
            lut[-1] = "" in pred.values
        elif isinstance(pred, Regex):
            try:
                rx = re.compile(pred.pattern)
            except re.error:
                return None
            lut[:-1] = [rx.fullmatch(v) is not None for v in values]
            lut[-1] = rx.fullmatch("") is not None
        else:
            return None
        return lut[codes]


#: physical Region instance -> its catalog (a TRUNCATE recreates the
#: region object, and with it the dictionary: the catalog goes with it)
_CATALOGS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_CATALOGS_LOCK = threading.Lock()


def _catalog_of(phys) -> _LabelCatalog:
    with _CATALOGS_LOCK:
        cat = _CATALOGS.get(phys)
        if cat is None:
            cat = _CATALOGS[phys] = _LabelCatalog()
        return cat


@dataclass
class LogicalTableMeta:
    name: str
    tag_names: list[str]
    physical_region: int
    logical_region: int
    ts_name: str = TS_COL
    value_name: str = VALUE_COL

    def to_json(self) -> str:
        return json.dumps(self.__dict__)

    @staticmethod
    def from_json(s: str) -> "LogicalTableMeta":
        return LogicalTableMeta(**json.loads(s))


class LogicalRegion:
    """Region-shaped view of one logical table over the physical region.

    Registered in the RegionEngine's region map under the logical region id
    so the entire query path (scan/put/flush) works unchanged."""

    def __init__(self, meta: LogicalTableMeta, engine: RegionEngine):
        self.meta = meta
        self.engine = engine
        self.region_id = meta.logical_region
        self.schema = logical_schema(meta.tag_names, meta.ts_name, meta.value_name)
        # (data_version, {tag: values}) of the last label-values answer
        self._label_values: Optional[tuple] = None

    # -- write: logical batch -> physical rows --
    def write(self, batch: RecordBatch, op: int) -> int:
        return self.write_many([(batch, op)])[0]

    def write_many(self, items: list[tuple[RecordBatch, int]]) -> list[int]:
        """Several mutations of this table as ONE group commit of the
        physical region (what the write workers hand a region)."""
        phys = self.engine.region(self.meta.physical_region)
        written = phys.write_many(
            [(self._physical_batch(batch), op) for batch, op in items])
        METRIC_ENGINE_WRITE_ROWS.inc(sum(written))
        if phys.memtable_bytes >= self.engine.config.flush_threshold_bytes:
            phys.flush()
            phys.compact()
        return written

    def _physical_batch(self, batch: RecordBatch) -> RecordBatch:
        n = batch.num_rows
        return RecordBatch(physical_schema(), {
            # one dictionary value, whatever the batch's size
            TABLE_COL: DictVector(np.zeros(n, dtype=np.int32),
                                  np.asarray([self.meta.name], dtype=object)),
            LABELS_COL: self._label_column(batch, n),
            TS_COL: np.asarray(batch.columns[self.meta.ts_name],
                               dtype=np.int64),
            VALUE_COL: np.asarray(batch.columns[self.meta.value_name],
                                  dtype=np.float64),
        })

    def _label_column(self, batch: RecordBatch, n: int) -> DictVector:
        """The batch's `__labels` column: the tag columns factorised into
        their distinct combinations, `encode_labels` once per
        combination (its string, key by sorted key), codes gathered."""
        key = np.zeros(n, dtype=np.int64)
        span = 1
        codes_of, pairs_of = [], []
        for t in self.meta.tag_names:  # sorted at creation
            col = batch.columns.get(t)
            if col is None:
                continue
            if not isinstance(col, DictVector):
                col = DictVector.encode(np.asarray(col, dtype=object))
            card = len(col.values) + 1
            if span * card >= 1 << 62:
                # keep the mixed radix inside int64
                _, key = np.unique(key, return_inverse=True)
                span = int(key.max()) + 1 if n else 1
            key = key * card + (col.codes.astype(np.int64) + 1)
            span *= card
            codes_of.append(col.codes)
            # slot 0 is NULL: encode_labels drops the pair
            pairs_of.append([None] + [f"{t}={v}" for v in col.values])
        if not codes_of or n == 0:
            return DictVector(np.zeros(n, dtype=np.int32),
                              np.asarray([""], dtype=object))
        _, first, inverse = np.unique(key, return_index=True,
                                      return_inverse=True)
        picked = [[pairs[c] for c in (codes[first] + 1).tolist()]
                  for codes, pairs in zip(codes_of, pairs_of)]
        labels = ["\x1f".join(p for p in combo if p is not None)
                  for combo in zip(*picked)]
        return DictVector(inverse.astype(np.int32),
                          np.asarray(labels, dtype=object))

    @property
    def memtable_bytes(self) -> int:
        return 0  # flush policy is owned by the physical region

    @property
    def registry(self):
        return _VirtualRegistry(self)

    @property
    def data_version(self) -> int:
        return self.engine.region(self.meta.physical_region).data_version

    def data_identity(self) -> tuple:
        """The physical region's (see Region.data_identity): every
        logical table moves with it, and its extent bounds theirs."""
        return self.engine.region(self.meta.physical_region).data_identity()

    def flush(self):
        self.engine.region(self.meta.physical_region).flush()

    def compact(self, strategy: str = "twcs"):
        return self.engine.region(self.meta.physical_region).compact(strategy)

    def drop(self):
        pass  # logical drop = metadata removal; physical data is shared

    # -- scan: physical rows -> virtual logical columns --
    def scan(
        self,
        ts_range: Optional[tuple[int, int]] = None,
        projection: Optional[Sequence[str]] = None,
        tag_predicates: Optional[dict[str, set]] = None,
        seq_min: Optional[int] = None,
        full_key: bool = True,
    ) -> Optional[ScanData]:
        if seq_min is not None:
            # logical regions share a physical region: a sequence
            # boundary over the shared store is not table-scoped, so
            # incremental consumers must fall back to full scans
            raise NotImplementedError(
                "seq_min scans are not supported on metric-engine "
                "logical regions")
        with tracing.span("metric_engine_scan",
                          table=self.meta.name) as attrs:
            return self._scan(ts_range, projection, tag_predicates, attrs)

    def _scan(self, ts_range, projection, tag_predicates,
              attrs: dict) -> Optional[ScanData]:
        from greptimedb_tpu.storage.index import CodeSet, normalize_predicates

        phys = self.engine.region(self.meta.physical_region)
        catalog = _catalog_of(phys)
        seconds = {"physical": 0.0, "labels": 0.0, "project": 0.0}
        attrs.update(physical_rows=0, logical_rows=0, label_sets_parsed=0,
                     label_cache="hit")

        def sync() -> None:
            t0 = time.perf_counter()
            state, parsed = catalog.sync(phys.registry)
            seconds["labels"] += time.perf_counter() - t0
            if parsed:
                attrs["label_sets_parsed"] += parsed
                attrs["label_cache"] = state

        # push the table selector down, and with it the label sets that
        # can match the `=` / `=~` predicates on virtual tags
        sync()
        phys_preds: dict = {TABLE_COL: {self.meta.name}}
        allowed = None
        for tag, preds in normalize_predicates(tag_predicates).items():
            if tag not in self.meta.tag_names:
                continue
            for p in preds:
                m = catalog.matching(tag, p)
                if m is not None:
                    allowed = m if allowed is None else (allowed & m)
        if allowed is not None and not allowed.all():
            phys_preds[LABELS_COL] = CodeSet.of(np.flatnonzero(allowed))
        t0 = time.perf_counter()
        scan = phys.scan(ts_range, None, phys_preds)
        tcodes = phys.registry.codes_of(TABLE_COL, [self.meta.name])
        if scan is None or not tcodes:
            return None
        attrs["physical_rows"] = decoded = \
            (scan.stats or {}).get("rows_prefilter", scan.num_rows)
        # the physical scan filters rows exactly on `=`-sets; whatever
        # else it may hand back (a snapshot without the row filter)
        # drops here
        keep = scan.columns[TABLE_COL] == tcodes[0]
        idx = None if keep.all() else np.flatnonzero(keep)

        def rows(arr):
            return arr if idx is None else arr[idx]

        label_codes = rows(scan.columns[LABELS_COL])
        seconds["physical"] = time.perf_counter() - t0
        if not len(label_codes):
            return None
        sync()  # label sets written between the first sync and the snapshot

        t0 = time.perf_counter()
        columns: dict[str, np.ndarray] = {}
        tag_dicts: dict[str, np.ndarray] = {}
        names = projection or self.schema.names
        # all tags always materialize, whatever `full_key` says: the
        # shared physical region is last-write-wins and is read whole
        # (projection None, so Region.scan's `full_key` default holds:
        # dedup needs the full primary key), and a virtual tag is one
        # numpy gather, not a decode
        for t in self.meta.tag_names:
            codes, tag_dicts[t] = catalog.column(t)
            columns[t] = codes[label_codes]
        columns[self.meta.ts_name] = rows(scan.columns[TS_COL])
        if self.meta.value_name in names:
            columns[self.meta.value_name] = rows(scan.columns[VALUE_COL])
        out = ScanData(
            schema=self.schema,
            columns=columns,
            seq=rows(scan.seq),
            op_type=rows(scan.op_type),
            tag_dicts=tag_dicts,
            num_rows=int(len(label_codes)),
            needs_dedup=scan.needs_dedup,
            region_id=self.region_id,
            data_version=scan.data_version,
            incarnation=scan.incarnation,
            scan_fingerprint=("metric", self.meta.name, ts_range,
                              tuple(names or ()), scan.scan_fingerprint),
        )
        seconds["project"] = time.perf_counter() - t0
        for phase, took in seconds.items():
            METRIC_ENGINE_SCAN_SECONDS.observe(took, phase=phase)
        METRIC_ENGINE_ROWS.inc(float(decoded), kind="physical_decoded")
        METRIC_ENGINE_ROWS.inc(float(out.num_rows), kind="logical_returned")
        attrs["logical_rows"] = out.num_rows
        return out


class _VirtualRegistry:
    """Registry-shaped accessor for label values (HTTP label-values API):
    per tag, the values the table's label sets carry, read off the
    region's label catalog."""

    def __init__(self, region: LogicalRegion):
        self._region = region

    @property
    def values(self) -> dict[str, list[str]]:
        region = self._region
        memo = region._label_values
        if memo is not None and memo[0] == region.data_version:
            return memo[1]
        version = region.data_version
        out: dict[str, list[str]] = {t: [] for t in region.meta.tag_names}
        scan = region.scan(projection=[region.meta.ts_name])
        if scan is not None:
            for t in region.meta.tag_names:
                used = np.unique(scan.columns[t])
                out[t] = scan.tag_dicts[t][used[used >= 0]].tolist()
        region._label_values = (version, out)
        return out


def logical_schema(tag_names: list[str], ts_name: str = TS_COL,
                   value_name: str = VALUE_COL) -> Schema:
    cols = [ColumnSchema(t, DataType.STRING, SemanticType.TAG) for t in tag_names]
    cols.append(ColumnSchema(ts_name, DataType.TIMESTAMP_MILLISECOND,
                             SemanticType.TIMESTAMP, nullable=False))
    cols.append(ColumnSchema(value_name, DataType.FLOAT64, SemanticType.FIELD))
    return Schema(cols)


class MetricEngine:
    """Logical-table multiplexer over a RegionEngine (engine.rs:57-98)."""

    def __init__(self, engine: RegionEngine, kv: KvBackend):
        self.engine = engine
        self.kv = kv
        self.engine.register_opener(self._open_logical)

    # physical region management: one data region per (db) group
    def _physical_region_id(self, db: str) -> int:
        key = f"{META_PREFIX}physical/{db}"
        existing = self.kv.get(key)
        if existing is not None:
            return int(existing)
        rid = (0x7FFF0000 << 32) | (self.kv.incr(META_PREFIX + "physical_seq") & 0xFFFFFFFF)
        if not self.kv.compare_and_put(key, None, str(rid)):
            return int(self.kv.get(key))
        return rid

    def create_logical_table(
        self, db: str, name: str, tag_names: list[str],
        ts_name: str = TS_COL, value_name: str = VALUE_COL,
    ) -> LogicalTableMeta:
        phys_rid = self._physical_region_id(db)
        try:
            self.engine.region(phys_rid)
        except KeyError:
            try:
                self.engine.open_region(phys_rid)
            except FileNotFoundError:
                self.engine.create_region(phys_rid, physical_schema())
        logical_rid = (0x7FFE0000 << 32) | (self.kv.incr(META_PREFIX + "logical_seq") & 0xFFFFFFFF)
        meta = LogicalTableMeta(
            name=name, tag_names=sorted(tag_names),
            physical_region=phys_rid, logical_region=logical_rid,
            ts_name=ts_name, value_name=value_name,
        )
        self.kv.put(f"{META_PREFIX}table/{db}/{name}", meta.to_json())
        self.kv.put(f"{META_PREFIX}region/{logical_rid}", meta.to_json())
        self.engine.regions[logical_rid] = LogicalRegion(meta, self.engine)
        return meta

    def drop_logical_table(self, db: str, name: str) -> None:
        raw = self.kv.get(f"{META_PREFIX}table/{db}/{name}")
        if raw is None:
            return
        meta = LogicalTableMeta.from_json(raw)
        self.kv.delete(f"{META_PREFIX}table/{db}/{name}")
        self.kv.delete(f"{META_PREFIX}region/{meta.logical_region}")
        self.engine.regions.pop(meta.logical_region, None)

    def list_logical_tables(self, db: str) -> list[str]:
        prefix = f"{META_PREFIX}table/{db}/"
        return [k[len(prefix):] for k, _ in self.kv.range(prefix)]

    def _open_logical(self, region_id: int):
        """Opener hook: rebuild a LogicalRegion from kv metadata when the
        engine is asked to open a logical region id (e.g. after restart)."""
        raw = self.kv.get(f"{META_PREFIX}region/{region_id}")
        if raw is None:
            return None
        meta = LogicalTableMeta.from_json(raw)
        try:
            self.engine.region(meta.physical_region)
        except KeyError:
            self.engine.open_region(meta.physical_region)
        return LogicalRegion(meta, self.engine)

"""File engine: external files served as read-only tables (mirrors
reference `src/file-engine`: `FileRegionEngine` over common/datasource
formats, src/file-engine/src/engine.rs).

A file region materializes its CSV/JSON/Parquet file into the same
`ScanData` contract the LSM regions produce (tags as dictionary codes,
zero seq/op_type sideband, `needs_dedup=False`), so the device kernels
treat external data exactly like native region scans. Registered as an
opener on the shared RegionEngine — region ids in the 0x7FFD0000 space
route here (the metric engine uses 0x7FFF/0x7FFE the same way).
"""

from __future__ import annotations

import json
import os
from typing import Optional, Sequence

import numpy as np

from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema
from greptimedb_tpu.datatypes.types import DataType, SemanticType
from greptimedb_tpu.storage.region import ScanData

META_PREFIX = "__file_engine/"
FILE_REGION_BASE = 0x7FFD0000 << 32


class FileEngineError(Exception):
    pass


class FileRegion:
    """Read-only region over one external file."""

    def __init__(self, region_id: int, path: str, fmt: str, schema: Schema):
        self.region_id = region_id
        self.path = path
        self.fmt = fmt
        self.schema = schema
        self._cache = None  # (mtime, columns, tag_dicts, nrows)

    # -- region engine contract (read side) ----------------------------------

    @property
    def data_version(self) -> int:
        try:
            return int(os.stat(self.path).st_mtime_ns)
        except OSError:
            return 0

    def scan(self, ts_range=None, projection: Optional[Sequence[str]] = None,
             tag_predicates=None, seq_min=None,
             full_key=True) -> Optional[ScanData]:
        # `full_key` (Region.scan) asks nothing here: a file's rows are
        # never merged by key, so only the named columns are returned
        if seq_min is not None:
            raise NotImplementedError(
                "seq_min scans are not supported on external tables")
        columns, tag_dicts, nrows = self._load()
        if nrows == 0:
            return None
        names = list(projection) if projection else self.schema.names
        ts_name = self.schema.time_index.name
        if ts_name not in names:
            names.append(ts_name)
        cols = {n: columns[n] for n in names}
        mask = None
        if ts_range is not None:
            ts = columns[ts_name]
            lo, hi = ts_range
            mask = (ts >= lo) & (ts <= hi)
        if mask is not None:
            cols = {n: c[mask] for n, c in cols.items()}
            nrows = int(mask.sum())
            if nrows == 0:
                return None
        return ScanData(
            schema=self.schema,
            columns=cols,
            seq=np.zeros(nrows, dtype=np.int64),
            op_type=np.zeros(nrows, dtype=np.int8),
            tag_dicts={k: v for k, v in tag_dicts.items() if k in cols},
            num_rows=nrows,
            needs_dedup=False,
            region_id=self.region_id,
            data_version=self.data_version,
        )

    # -- write side: read-only (reference file-engine rejects writes) --------

    def write(self, batch, op):
        raise FileEngineError("file engine tables are read-only")

    def flush(self):
        pass

    def compact(self, strategy=None):
        pass

    def drop(self):
        self._cache = None

    @property
    def memtable_bytes(self) -> int:
        return 0

    # -- load + coerce ---------------------------------------------------------

    def _load(self):
        from greptimedb_tpu.datasource import read_file
        from greptimedb_tpu.utils.time import coerce_ts_literal

        mtime = self.data_version
        if self._cache is not None and self._cache[0] == mtime:
            return self._cache[1], self._cache[2], self._cache[3]
        t = read_file(self.path, self.fmt)
        nrows = t.num_rows
        have = set(t.schema.names)
        columns: dict[str, np.ndarray] = {}
        tag_dicts: dict[str, np.ndarray] = {}
        for c in self.schema.columns:
            if c.name not in have:
                raise FileEngineError(
                    f"column {c.name!r} missing from {self.path!r}")
            vals = t.column(c.name).to_pylist()
            if c.semantic is SemanticType.TAG or c.dtype.is_string:
                # NULLs encode as code -1, same as native regions
                from greptimedb_tpu.datatypes.vector import DictVector
                dv = DictVector.encode(
                    [None if v is None else str(v) for v in vals])
                columns[c.name] = dv.codes
                tag_dicts[c.name] = dv.values
            elif c.dtype.is_timestamp:
                columns[c.name] = np.asarray(
                    [coerce_ts_literal(v, c.dtype) for v in vals],
                    dtype=np.int64)
            elif c.dtype.is_float:
                columns[c.name] = np.asarray(
                    [np.nan if v is None else float(v) for v in vals],
                    dtype=c.dtype.to_numpy())
            else:
                columns[c.name] = np.asarray(
                    [0 if v is None else int(v) for v in vals],
                    dtype=c.dtype.to_numpy())
        self._cache = (mtime, columns, tag_dicts, nrows)
        return columns, tag_dicts, nrows


class FileEngine:
    """Region-engine facade for external-file tables; persists region
    metadata in the catalog kv so regions reopen across restarts."""

    def __init__(self, region_engine, kv):
        self.engine = region_engine
        self.kv = kv
        region_engine.register_opener(self._open)

    def create_file_table(self, db: str, name: str, schema: Optional[Schema],
                          location: str, fmt: Optional[str]) -> tuple[int, Schema]:
        from greptimedb_tpu.datasource import infer_format, read_file

        fmt = infer_format(location, fmt)
        if schema is None:
            schema = self._infer_schema(read_file(location, fmt))
        rid = FILE_REGION_BASE | (self.kv.incr(META_PREFIX + "seq") & 0xFFFFFFFF)
        meta = {"path": location, "format": fmt,
                "schema": schema.to_dict(), "db": db, "table": name}
        self.kv.put(f"{META_PREFIX}region/{rid}", json.dumps(meta))
        self.engine.regions[rid] = FileRegion(rid, location, fmt, schema)
        return rid, schema

    def drop_file_table(self, region_id: int) -> None:
        self.kv.delete(f"{META_PREFIX}region/{region_id}")
        self.engine.regions.pop(region_id, None)

    def _open(self, region_id: int):
        if (region_id >> 32) != 0x7FFD0000:
            return None
        raw = self.kv.get(f"{META_PREFIX}region/{region_id}")
        if raw is None:
            return None
        meta = json.loads(raw)
        return FileRegion(region_id, meta["path"], meta["format"],
                          Schema.from_dict(meta["schema"]))

    @staticmethod
    def _infer_schema(t) -> Schema:
        """Schema inference (reference file-engine infers from the file):
        timestamp-typed (or ts-named int) column → time index, strings →
        tags, numerics → fields."""
        import pyarrow as pa

        cols: list[ColumnSchema] = []
        ts_col = None
        for field in t.schema:
            if pa.types.is_timestamp(field.type) and ts_col is None:
                ts_col = field.name
        if ts_col is None:
            for field in t.schema:
                if field.name.lower() in ("ts", "timestamp", "time") and (
                        pa.types.is_integer(field.type)):
                    ts_col = field.name
                    break
        if ts_col is None:
            raise FileEngineError(
                "cannot infer a time index column; declare the schema "
                "explicitly in CREATE EXTERNAL TABLE")
        for field in t.schema:
            if field.name == ts_col:
                dt = DataType.from_arrow(field.type) \
                    if pa.types.is_timestamp(field.type) \
                    else DataType.TIMESTAMP_MILLISECOND
                cols.append(ColumnSchema(field.name, dt,
                                         SemanticType.TIMESTAMP, False))
            elif pa.types.is_string(field.type) or \
                    pa.types.is_large_string(field.type):
                cols.append(ColumnSchema(field.name, DataType.STRING,
                                         SemanticType.TAG, True))
            else:
                dt = DataType.from_arrow(field.type)
                cols.append(ColumnSchema(field.name, dt, SemanticType.FIELD,
                                         True))
        return Schema(cols)

"""SST files: sorted Parquet with min/max pruning.

Mirrors the reference's parquet SST contract (mito2/src/sst/parquet/writer.rs:41-87,
reader row-group pruning at reader.rs:335-447): rows sorted by
(tags..., ts, seq); internal columns `__seq` (write sequence) and `__op_type`
(PUT/DELETE) ride alongside; region schema JSON is stored in the parquet
key-value metadata (analog of PARQUET_METADATA_KEY, sst/parquet.rs:37).

TPU-first delta from the reference: tags are stored as per-column parquet
dictionary columns (not one memcomparable key blob) because the kernel ABI
wants dense per-tag codes.

Row groups hold 32,768 rows (the reference writes 102,400; both were
measured, PERF.md PR 34): files sort by (pk, ts), so a pruned read decodes
the groups the inverted index selects and its cost follows the rows
selected. What a read needs from a file's footer — group row counts,
per-group time-index min / max — is parsed once per file (`_FilePlan`, kept
until the file is deleted), and the surviving groups are read in contiguous
batches of at most `READ_BATCH_ROWS` rows, so the fixed cost of a read is
per file and per batch, not per row group. A file's layout is read from its
own footer: files of any group size keep reading.
"""

from __future__ import annotations

import json
import os
import uuid
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from greptimedb_tpu.datatypes.schema import Schema
from greptimedb_tpu.datatypes.types import SemanticType
from greptimedb_tpu.datatypes.vector import DictVector
from greptimedb_tpu.objectstore import default_store

SEQ_COL = "__seq"
OP_COL = "__op_type"
METADATA_KEY = b"greptimedb_tpu:region_schema"
# sst format version stamp; files without it predate versioning (= v1)
FORMAT_KEY = b"greptimedb_tpu:sst_format"
DEFAULT_ROW_GROUP = 32_768  # mito2 writes 102,400: PERF.md, PR 34
#: most rows one read of a file's row groups brings in at once: a
#: pruned read holds no more rows it will drop, a stream no larger chunk
READ_BATCH_ROWS = 1 << 20
_I64 = np.iinfo(np.int64)


@dataclass
class FileMeta:
    """Catalog entry for one SST (reference sst/file.rs FileMeta)."""

    file_id: str
    num_rows: int
    ts_min: int
    ts_max: int
    max_seq: int
    level: int = 0
    size_bytes: int = 0
    # tag columns holding any NULL (-1) code in this file, or None when
    # unknown (files written before this field existed). The lastpoint
    # newest-first pruner needs it: NULL-tag rows form a group the
    # registry's cardinality cannot account for, so a file that might
    # hold them blocks early termination unless the NULL group already
    # has a newer candidate.
    null_tags: Optional[list] = None

    def to_dict(self) -> dict:
        return self.__dict__.copy()

    @staticmethod
    def from_dict(d: dict) -> "FileMeta":
        return FileMeta(**d)


class SstWriter:
    def __init__(self, sst_dir: str, schema: Schema,
                 row_group_size: int = DEFAULT_ROW_GROUP, store=None):
        self.sst_dir = sst_dir
        self.schema = schema
        self.row_group_size = row_group_size
        self.store = default_store(store)

    def write(
        self,
        columns: dict[str, np.ndarray],
        tag_dicts: dict[str, np.ndarray],
        seq: np.ndarray,
        op_type: np.ndarray,
        level: int = 0,
    ) -> FileMeta:
        """Write pre-sorted columns (tag columns as int32 codes against
        `tag_dicts`) to a new SST file. Caller guarantees sort order
        (tags..., ts, seq) — flush runs the device sort-dedup first."""
        ts_name = self.schema.time_index.name
        n = len(columns[ts_name])
        tag_cols = [c.name for c in self.schema.tag_columns]
        # parquet writes a DictionaryArray's WHOLE dictionary into every
        # row group's dictionary page (the metric engine's `__labels`: a
        # region-wide dictionary of every series, of which a group holds
        # a few thousand), so each group is written with the dictionary
        # of the values it uses: a read of some row groups decodes those
        # values alone. Where a group uses every value, that is the
        # whole dictionary as before
        def arrow_columns(lo: int, hi: int) -> list:
            out = []
            for c in self.schema.columns:
                col = columns[c.name][lo:hi]
                if c.semantic is SemanticType.TAG:
                    dv = DictVector(np.asarray(col, dtype=np.int32),
                                    tag_dicts[c.name])
                    out.append(dv.compact().to_arrow())
                else:
                    out.append(pa.array(col, type=c.dtype.to_arrow()))
            out.append(pa.array(np.asarray(seq[lo:hi], dtype=np.int64),
                                type=pa.int64()))
            out.append(pa.array(np.asarray(op_type[lo:hi], dtype=np.int8),
                                type=pa.int8()))
            return out

        fields = [pa.field(c.name,
                           pa.dictionary(pa.int32(), pa.string())
                           if c.semantic is SemanticType.TAG
                           else c.dtype.to_arrow(), nullable=c.nullable)
                  for c in self.schema.columns]
        fields.append(pa.field(SEQ_COL, pa.int64(), nullable=False))
        fields.append(pa.field(OP_COL, pa.int8(), nullable=False))

        from greptimedb_tpu.storage.format import FORMAT_VERSIONS

        meta = {METADATA_KEY: json.dumps(self.schema.to_dict()).encode(),
                FORMAT_KEY: str(FORMAT_VERSIONS["sst"]).encode()}
        pa_schema = pa.schema(fields, metadata=meta)

        file_id = uuid.uuid4().hex
        path = os.path.join(self.sst_dir, f"{file_id}.parquet")
        sink = pa.BufferOutputStream()
        # physical encodings tuned for the TSBS shape (readers are
        # format-agnostic — parquet self-describes, so old zstd/dict
        # files keep opening, test_compat.py):
        # - lz4 over zstd: scan decode is single-thread bound on the
        #   serving box; lz4 decompresses ~2.6x faster for ~14% more
        #   bytes
        # - BYTE_STREAM_SPLIT on float fields: sensor-range doubles have
        #   near-constant exponent bytes, so splitting byte planes lets
        #   lz4 find them (write 0.90->0.44s, 175->144MB per 2M rows)
        # - DELTA_BINARY_PACKED on ts/seq: repeated or incrementing
        #   int64s collapse to near-nothing
        # tag columns must be listed in use_dictionary explicitly:
        # use_dictionary=False would materialize their DictionaryArrays
        # as dense PLAIN strings (full hostname per row) — the listed
        # form keeps RLE_DICTIONARY on tags while column_encoding
        # applies to the rest.
        encodings = {c.name: "BYTE_STREAM_SPLIT"
                     for c in self.schema.field_columns
                     if c.dtype.is_float}
        encodings[ts_name] = "DELTA_BINARY_PACKED"
        encodings[SEQ_COL] = "DELTA_BINARY_PACKED"
        with pq.ParquetWriter(
                sink, pa_schema, compression="lz4", use_dictionary=tag_cols,
                column_encoding=encodings, write_statistics=True) as w:
            for lo in range(0, max(n, 1), self.row_group_size):
                hi = min(lo + self.row_group_size, n)
                w.write_table(
                    pa.Table.from_arrays(arrow_columns(lo, hi),
                                         schema=pa_schema),
                    row_group_size=self.row_group_size)
        self.store.write(path, sink.getvalue())  # pa.Buffer, zero extra copy
        # build the per-file inverted index (tag value -> row-group bitmap)
        from greptimedb_tpu.storage.index import (
            DEFAULT_SEGMENT_ROWS,
            InvertedIndexWriter,
        )

        InvertedIndexWriter(
            self.sst_dir, self.store,
            segment_rows=min(DEFAULT_SEGMENT_ROWS, self.row_group_size),
        ).write(
            file_id,
            {c.name: np.asarray(columns[c.name], dtype=np.int32)
             for c in self.schema.tag_columns},
            tag_dicts,
            self.row_group_size,
            n,
        )
        ts = np.asarray(columns[ts_name])
        null_tags = [
            c.name for c in self.schema.tag_columns
            if n and bool((np.asarray(columns[c.name],
                                      dtype=np.int32) < 0).any())
        ]
        return FileMeta(
            file_id=file_id,
            num_rows=n,
            ts_min=int(ts.min()) if n else 0,
            ts_max=int(ts.max()) if n else 0,
            max_seq=int(np.max(seq)) if n else 0,
            level=level,
            size_bytes=self.store.size(path),
            null_tags=null_tags,
        )


@dataclass
class _FilePlan:
    """What every read of one file needs from its footer. A file never
    changes once written, so this is parsed at the first plan and kept
    until the file is deleted: a request parses no footer and casts no
    statistic."""

    metadata: pq.FileMetaData
    schema_arrow: pa.Schema
    group_rows: np.ndarray  # int64[groups]
    # the time index per group, in its storage unit; a group without
    # statistics spans every instant, so no window prunes it
    ts_min: np.ndarray  # int64[groups]
    ts_max: np.ndarray  # int64[groups]


def cut_batches(groups: Sequence[int],
                group_rows: np.ndarray) -> list[list[int]]:
    """A plan's surviving row groups, in order, cut into contiguous
    batches of at most READ_BATCH_ROWS rows each; a group larger than
    that is a batch of its own. One read and one decode a batch: every
    read path bounds what it holds at once with this."""
    out: list[list[int]] = []
    cur: list[int] = []
    held = 0
    for g in groups:
        rows = int(group_rows[g])
        if cur and held + rows > READ_BATCH_ROWS:
            out.append(cur)
            cur, held = [], 0
        cur.append(int(g))
        held += rows
    if cur:
        out.append(cur)
    return out


class SstReader:
    def __init__(self, sst_dir: str, store=None):
        from greptimedb_tpu.storage.index import IndexApplier

        self.sst_dir = sst_dir
        self.store = default_store(store)
        self.index_applier = IndexApplier(sst_dir, self.store)
        # beside the applier's parsed indexes, and for as long: one
        # _FilePlan per file asked about, until `delete`
        self._plans: dict[str, _FilePlan] = {}

    def path(self, file_id: str) -> str:
        return os.path.join(self.sst_dir, f"{file_id}.parquet")

    def file_plan(self, file_id: str, ts_name: str) -> _FilePlan:
        """The file's kept footer, parsed on first use."""
        fp = self._plans.get(file_id)
        if fp is not None:
            return fp
        pf = pq.ParquetFile(self.store.open_input(self.path(file_id)))
        _check_sst_format(pf, file_id)
        md, schema_arrow = pf.metadata, pf.schema_arrow
        n = md.num_row_groups
        group_rows = np.empty(n, dtype=np.int64)
        ts_min = np.full(n, _I64.min, dtype=np.int64)
        ts_max = np.full(n, _I64.max, dtype=np.int64)
        ts_idx = schema_arrow.get_field_index(ts_name)
        ts_type = schema_arrow.field(ts_idx).type if ts_idx >= 0 else None
        for g in range(n):
            rg = md.row_group(g)
            group_rows[g] = rg.num_rows
            stats = rg.column(ts_idx).statistics if ts_idx >= 0 else None
            if stats is None or not stats.has_min_max:
                continue
            try:
                lo, hi = (_ts_stat(stats.min, ts_type),
                          _ts_stat(stats.max, ts_type))
            except (OverflowError, ValueError):
                continue  # an instant no datetime holds: never pruned
            ts_min[g], ts_max[g] = lo, hi
        fp = _FilePlan(md, schema_arrow, group_rows, ts_min, ts_max)
        self._plans[file_id] = fp
        return fp

    def open(self, file_id: str, fp: _FilePlan) -> pq.ParquetFile:
        """A handle on the file that parses no footer. Concurrent
        workers each open their own (pyarrow readers are not safe for
        concurrent reads on one handle)."""
        return pq.ParquetFile(self.store.open_input(self.path(file_id)),
                              metadata=fp.metadata)

    def plan_groups(
        self,
        meta: FileMeta,
        schema: Schema,
        ts_range: Optional[tuple[int, int]] = None,
        projection: Optional[Sequence[str]] = None,
        tag_predicates: Optional[dict[str, set]] = None,
    ) -> Optional[tuple]:
        """Pruning phase of every read: the row groups whose time range
        meets the window (one comparison over the kept per-group
        statistics, reference reader.rs:427-447) and whose segments the
        inverted index selects (reader.rs:335-425). Returns (the file's
        kept footer, surviving row-group indices in order, projected
        column names) or None when pruning rules the whole file out."""
        if ts_range is not None and (meta.ts_max < ts_range[0] or meta.ts_min >= ts_range[1]):
            return None
        # the index first: it may rule the file out with no parquet
        # metadata read at all
        sel = None
        if tag_predicates:
            sel = self.index_applier.select(meta.file_id, tag_predicates)
            if sel is not None and sel.is_empty:
                return None
        ts_name = schema.time_index.name
        fp = self.file_plan(meta.file_id, ts_name)
        keep = np.ones(len(fp.group_rows), dtype=bool)
        if ts_range is not None:
            keep &= (fp.ts_max >= ts_range[0]) & (fp.ts_min < ts_range[1])
        if sel is not None and not sel.all_set:
            keep &= sel.group_mask(fp.group_rows)
        groups = np.flatnonzero(keep).tolist()
        if not groups:
            return None
        cols = None
        if projection is not None:
            cols = list(dict.fromkeys(list(projection) + [ts_name, SEQ_COL, OP_COL]))
            # tolerate schema evolution: drop columns the file predates
            avail = set(fp.schema_arrow.names)
            cols = [c for c in cols if c in avail]
        return fp, groups, cols

    def read(
        self,
        meta: FileMeta,
        schema: Schema,
        ts_range: Optional[tuple[int, int]] = None,
        projection: Optional[Sequence[str]] = None,
        tag_predicates: Optional[dict[str, set]] = None,
    ) -> Optional[pa.Table]:
        """Read an SST's surviving row groups at once. Returns None if
        fully pruned. Internal columns are always materialized."""
        plan = self.plan_groups(meta, schema, ts_range, projection,
                                tag_predicates)
        if plan is None:
            return None
        fp, groups, cols = plan
        return self.open(meta.file_id, fp).read_row_groups(groups,
                                                           columns=cols)

    def iter_batches(self, meta: FileMeta, fp: _FilePlan,
                     batches: Sequence[Sequence[int]],
                     columns: Optional[Sequence[str]]):
        """Yield one table a batch of row groups (`cut_batches` of a
        `plan_groups` result, or a worker's share of them) through one
        handle of its own — the caller cuts each to the rows it keeps
        before the next is read, so a read never holds more than
        READ_BATCH_ROWS rows it will drop."""
        pf = self.open(meta.file_id, fp)
        for batch in batches:
            yield pf.read_row_groups(list(batch), columns=columns)

    def iter_chunks(
        self,
        meta: FileMeta,
        schema: Schema,
        ts_range: Optional[tuple[int, int]] = None,
        projection: Optional[Sequence[str]] = None,
        tag_predicates: Optional[dict[str, set]] = None,
    ):
        """Lazily yield row-group batches of an SST (reference
        sst/parquet/row_group.rs lazy InMemoryRowGroup + reader.rs
        FileRange streaming) — bounded memory for beyond-RAM scans. Same
        pruning as `read`; each yield decodes one batch of at most
        READ_BATCH_ROWS rows."""
        plan = self.plan_groups(meta, schema, ts_range, projection,
                                tag_predicates)
        if plan is None:
            return
        fp, groups, cols = plan
        yield from self.iter_batches(
            meta, fp, cut_batches(groups, fp.group_rows), cols)

    def delete(self, file_id: str) -> None:
        self.store.delete(self.path(file_id))
        from greptimedb_tpu.storage.index import InvertedIndexWriter

        InvertedIndexWriter(self.sst_dir, self.store).delete(file_id)
        self.invalidate(file_id)

    def invalidate(self, file_id: str) -> None:
        """Forget what is kept of a file (its parsed index, its plan)."""
        self.index_applier.invalidate(file_id)
        self._plans.pop(file_id, None)


def _check_sst_format(pf: pq.ParquetFile, file_id: str) -> None:
    """Refuse files stamped with a NEWER sst format (a v1 reader must
    not half-parse a v2 file); absent stamp = v1 (pre-versioning)."""
    from greptimedb_tpu.storage.format import FORMAT_VERSIONS, FormatError

    md = pf.schema_arrow.metadata or {}
    raw = md.get(FORMAT_KEY)
    if raw is not None and int(raw) > FORMAT_VERSIONS["sst"]:
        raise FormatError(
            f"sst {file_id} has format v{int(raw)}; this build reads "
            f"<= v{FORMAT_VERSIONS['sst']}")


def _ts_stat(v, ts_type) -> int:
    """Parquet timestamp stats come back as datetime — normalize to an int
    in the column's own storage unit."""
    if isinstance(v, (int, np.integer)):
        return int(v)
    return pa.scalar(v).cast(ts_type).cast(pa.int64()).as_py()


def schema_from_parquet(path: str) -> Schema:
    pf = pq.ParquetFile(path)
    md = pf.schema_arrow.metadata or {}
    if METADATA_KEY in md:
        return Schema.from_dict(json.loads(md[METADATA_KEY].decode()))
    raise ValueError(f"{path} has no region schema metadata")

"""Region: one LSM instance (mirrors reference `MitoRegion` +
`VersionControl`, mito2/src/region/version.rs:83-138).

Write path (reference worker/handle_write.rs:34): WAL append is the
durability boundary, then the memtable ingests and the committed sequence
advances. Scan path (reference read/scan_region.rs:148-279): collect
memtable chunks + SSTs overlapping the time predicate, remap file-local tag
dictionaries into the region registry, and hand the concatenated columns to
the device tier — sort-dedup and aggregation happen in kernels, not here.
Flush (worker/handle_flush.rs:34-170): memtable → sorted SST, manifest
edit, WAL truncation.
"""

from __future__ import annotations

import bisect
import contextlib
import itertools
import os
import threading
import time
import weakref
from collections import OrderedDict
from dataclasses import dataclass, field
from collections.abc import Mapping
from typing import NamedTuple, Optional, Sequence

import numpy as np
import pyarrow as pa

from greptimedb_tpu.datatypes.recordbatch import RecordBatch
from greptimedb_tpu.datatypes.schema import Schema
from greptimedb_tpu.datatypes.types import SemanticType
from greptimedb_tpu.datatypes.vector import DictVector
from greptimedb_tpu.storage.manifest import ManifestManager
from greptimedb_tpu.storage.memtable import Memtable, TagRegistry
from greptimedb_tpu.storage.sst import (
    OP_COL,
    SEQ_COL,
    FileMeta,
    SstReader,
    SstWriter,
    cut_batches,
)
from greptimedb_tpu.storage.wal import Wal
from greptimedb_tpu.utils import deadline as dl

OP_PUT = 0
OP_DELETE = 1


class RegionDroppedError(RuntimeError):
    """Write raced a DROP: the region is gone; the write did not happen."""


@dataclass
class _PartEntry:
    """One per-file decoded scan part: `part` is (cols, seq, op) for the
    rows an SST contributes under a (ts_range, names, predicates) shape,
    or None when the file prunes to nothing under that shape (cached too
    — re-proving emptiness costs a parquet footer read)."""

    part: Optional[tuple]
    nbytes: int
    #: rows of the file inside the scan's window, BEFORE the exact tag
    #: filter cut them to the selected series: what the read decoded
    rows_read: int = 0


def _scan_nbytes(sd: "ScanData") -> int:
    """Host bytes a whole-scan snapshot holds (column arrays + seq/op;
    for a snapshot still in parts, the parts it holds). Object columns
    undercount their string payload — the budget errs permissive there,
    like the part cache does."""
    parts = sd._parts
    if parts is not None:
        return sum(_part_nbytes(p) for p in parts.loaded if p is not None) \
            + (_part_nbytes(parts.mem) if parts.mem is not None else 0)
    n = 0
    for v in sd.columns.values():
        if isinstance(v, np.ndarray):
            n += v.nbytes
    if isinstance(sd.seq, np.ndarray):
        n += sd.seq.nbytes
    if isinstance(sd.op_type, np.ndarray):
        n += sd.op_type.nbytes
    return n


def _part_nbytes(part: Optional[tuple]) -> int:
    if part is None:
        return 64  # bookkeeping floor for cached pruned-empty entries
    cols, seq, op = part
    return sum(int(a.nbytes) for a in cols.values()) \
        + int(seq.nbytes) + int(op.nbytes)


def _concat_parts(chunks: list) -> Optional[tuple]:
    """Decoded chunks of one file, in row order, as one part."""
    if not chunks:
        return None
    if len(chunks) == 1:
        return chunks[0]
    return ({n: np.concatenate([c[0][n] for c in chunks])
             for n in chunks[0][0]},
            np.concatenate([c[1] for c in chunks]),
            np.concatenate([c[2] for c in chunks]))


class ScanExpired(RuntimeError):
    """A scan's bytes were asked for after its snapshot died: the region
    was dropped or truncated (which deletes SSTs whatever pins them), or
    a file left the purge queue while no pin held it. The rows the plan
    promised cannot be read any more; the consumer takes a fresh scan
    (query/physical.py re-runs the statement's scan) — never a partial
    answer."""


class _ReadCount(NamedTuple):
    """What reading one file cost. `in_window`: rows inside the window
    before the exact tag filter (`rows_prefilter`, `rows_decoded`).
    `read` and `batches`: the rows a PRUNED read's batches brought in
    (in_window plus what they dropped outside the window) and how many
    batches; a whole-file read leaves them 0."""

    in_window: int = 0
    read: int = 0
    batches: int = 0

    def plus(self, other: "_ReadCount") -> "_ReadCount":
        return _ReadCount(*(a + b for a, b in zip(self, other)))


#: per-thread scan IO tally: (SST parts fetched through the part cache,
#: rows decoded from SSTs, then of the pruned reads among them: rows
#: their batches read, rows they kept, batches). The executor reads the
#: difference around a statement to say what the scan really cost it
#: (the `scan` stage's rows_decoded / rows_read / rows_kept / batches,
#: greptimedb_tpu_agg_scan_total's mode); pool workers decode on other
#: threads, so the request thread counts for them in _cached_parts.
#: Then the tag columns outside a scan's projection: those it carried
#: for the primary key's sake and those it left unread (_scan_columns)
_SCAN_IO = threading.local()
_SCAN_IO_FIELDS = ("parts", "rows", "read", "kept", "batches",
                   "key_decoded", "key_skipped")


def scan_io_counters() -> tuple[int, ...]:
    return tuple(getattr(_SCAN_IO, f, 0) for f in _SCAN_IO_FIELDS)


def scan_io_since(before: tuple[int, ...]) -> dict:
    """What this thread's scans cost since `before` (an earlier
    `scan_io_counters()`), under the names a `scan` stage span carries:
    rows_decoded, and of the pruned reads among them rows_read,
    rows_kept and batches; where a scan named fewer tags than the
    table has, key_columns_decoded and key_columns_skipped beside them."""
    d = [a - b for a, b in zip(scan_io_counters(), before)]
    out = {"rows_decoded": d[1], "rows_read": d[2], "rows_kept": d[3],
           "batches": d[4]}
    if d[5] or d[6]:
        out.update(key_columns_decoded=d[5], key_columns_skipped=d[6])
    return out


def _scan_io_add(**by: int) -> None:
    for f, n in by.items():
        setattr(_SCAN_IO, f, getattr(_SCAN_IO, f, 0) + n)


def _note_key_columns(n: int, decoded: bool) -> None:
    """A scan's `n` tag columns outside its projection: carried for the
    primary key's sake (`decoded`), or left unread because the caller
    makes no last-write-wins mask."""
    from greptimedb_tpu.utils.metrics import SCAN_KEY_COLUMNS

    kind = "decoded" if decoded else "skipped"
    SCAN_KEY_COLUMNS.inc(float(n), kind=kind)
    _scan_io_add(**{"key_" + kind: n})


class _PlanPins:
    """The SST pins a scan plan holds from plan to fetch (the reference's
    FilePurger refcount held by a scan's file handles): compaction may
    swap the files out meanwhile, and the purge waits. Released exactly
    once — when every part has been fetched, on close(), or when the
    plan is garbage (weakref.finalize), so a plan nobody reads cannot
    hold a file for ever."""

    def __init__(self, region: "Region", metas: list):
        self._region = region
        self._metas = metas
        self._lock = threading.Lock()

    def release(self) -> None:
        with self._lock:
            metas, self._metas = self._metas, None
        if metas:
            self._region._unpin_files(metas)


class _ScanParts:
    """One scan snapshot BEFORE concatenation: its row segments in scan
    order — one per contributing SST, then the memtable slice. A segment
    of a window- or predicate-pruned scan is decoded when the scan is
    taken (its row count is only known then); a segment of a full scan
    is the whole immutable file, so its row count comes from FileMeta
    and its bytes are fetched — through the per-file part cache and the
    decode pool — only when a consumer asks for them. Fetched segments
    are not kept here (the part cache is the memo, within its budget);
    `hold`/`release` keep a working set for a consumer walking parts."""

    def __init__(self, region: "Region", names: list, pred_key,
                 metas: list, rows: list, loaded: list, mem, cache_key):
        self.region = region
        self.names = names
        self.pred_key = pred_key
        self.metas = metas
        self.loaded = loaded
        self.mem = mem
        self.cache_key = cache_key
        lens = list(rows) + ([len(mem[1])] if mem is not None else [])
        self.offsets = [0]
        for n in lens:
            self.offsets.append(self.offsets[-1] + int(n))
        self._lock = threading.Lock()
        self._held: dict[int, tuple] = {}
        self.pins: Optional[_PlanPins] = None
        # serializes ScanData.materialize (a parked snapshot is shared)
        self.build_lock = threading.Lock()
        # the owning ScanData's stats dict: fetches add what they cost
        self.stats: Optional[dict] = None

    def _segment(self, i: int) -> Optional[tuple]:
        if i == len(self.metas):
            return self.mem
        part = self.loaded[i]
        if part is None:
            with self._lock:
                part = self._held.get(i)
        return part

    def hold(self, idxs) -> None:
        """Fetch the not-yet-loaded segments among `idxs` in ONE parallel
        decode (part-cache hits are free) and keep them until release."""
        need = [i for i in dict.fromkeys(idxs)
                if i < len(self.metas) and self._segment(i) is None]
        if not need:
            return
        parts, cost = self.region._fetch_parts(
            [self.metas[i] for i in need], self.names, self.pred_key)
        st = self.stats
        if st is not None:
            with self._lock:
                st["part_hits"] += cost["part_hits"]
                st["files_decoded"] += cost["files_decoded"]
                st["decode_s"] = round(st["decode_s"] + cost["decode_s"], 4)
                st["decode_workers"] = max(st["decode_workers"],
                                           cost["decode_workers"])
        for i, part in zip(need, parts):
            want = self.offsets[i + 1] - self.offsets[i]
            if part is None or len(part[1]) != want:
                raise ScanExpired(
                    f"sst {self.metas[i].file_id} no longer holds the "
                    f"{want} rows its plan counted")
        with self._lock:
            self._held.update(zip(need, parts))

    def release(self, idxs=None) -> None:
        with self._lock:
            if idxs is None:
                self._held.clear()
            else:
                for i in idxs:
                    self._held.pop(i, None)

    def part(self, i: int) -> tuple:
        part = self._segment(i)
        if part is None:
            self.hold([i])
            part = self._segment(i)
            if part is None:  # released under us by another thread
                return self.part(i)
        return part

    def segment_of(self, start: int, end: int) -> Optional[int]:
        """Index of the one segment holding rows [start, end), or None
        when the range crosses a seam."""
        i = bisect.bisect_right(self.offsets, start) - 1
        if 0 <= i < len(self.offsets) - 1 and end <= self.offsets[i + 1]:
            return i
        return None

    def all_parts(self) -> list:
        """Every non-empty segment in order, fetched in one fan-out."""
        n = len(self.metas)
        self.hold(range(n))
        out = [self.part(i) for i in range(n)
               if self.offsets[i + 1] > self.offsets[i]]
        if self.mem is not None:
            out.append(self.mem)
        return out

    def has_delete(self) -> bool:
        """Whether any row of the snapshot is a tombstone, WITHOUT
        reading a file whose answer the region already knows: SSTs are
        immutable, so `Region._file_deletes` (noted when a file is
        written or first decoded whole) stands for the file's life.
        Unknown means read it."""
        if self.mem is not None and bool((self.mem[2] != OP_PUT).any()):
            return True
        unknown = []
        for i, meta in enumerate(self.metas):
            part = self._segment(i)
            if part is not None:
                if bool((part[2] != OP_PUT).any()):
                    return True
                continue
            known = self.region._file_deletes.get(meta.file_id)
            if known:
                return True
            if known is None:
                unknown.append(i)
        return bool(unknown) and self.region._files_have_delete(
            [self.metas[i] for i in unknown])

    def ts_extents(self, ts_name: str) -> list:
        """(min, max) timestamp of every non-empty segment: a whole
        file's from its FileMeta (exact: written from the same rows), a
        decoded segment's from its rows."""
        spans = []
        for i in range(len(self.offsets) - 1):
            if self.offsets[i + 1] <= self.offsets[i]:
                continue
            part = self._segment(i)
            if part is None:
                spans.append((self.metas[i].ts_min, self.metas[i].ts_max))
            else:
                ts = part[0][ts_name]
                spans.append((int(ts.min()), int(ts.max())))
        return spans

    def close(self) -> None:
        self.release()
        if self.pins is not None:
            self.pins.release()


class _LazyColumns(Mapping):
    """`ScanData.columns` of a snapshot still in parts: the names are
    known, a value is read by concatenating — which builds every column
    at once, fanned across the scan pool, exactly the whole-scan
    assembly `Region.scan` used to do up front."""

    def __init__(self, scan: "ScanData", names: list):
        self._scan = scan
        self._names = names

    def __getitem__(self, name):
        if name not in self._names:
            raise KeyError(name)
        return self._scan.materialize().columns[name]

    def __iter__(self):
        return iter(self._names)

    def __len__(self):
        return len(self._names)


@dataclass
class ScanData:
    """Host-side scan output: one consistent snapshot of a region's rows.

    A scan is a plan first and bytes on demand. What every consumer can
    read for free is the plan: row counts, part offsets and identities,
    tag dictionaries, version. `columns` / `seq` / `op_type` are the
    concatenated whole-scan arrays, ready for device blocks; on a
    snapshot still in parts (`_parts`) they are built at first touch —
    the same parallel decode, the same concatenation, the same snapshot
    cache as before, so a consumer of whole columns pays what it always
    paid. A consumer that walks parts (`rows`, `hold_rows`) never
    causes the concatenation, and reads only the parts it asks for.

    Tags are int32 codes against `tag_dicts`; rows are NOT yet deduplicated
    or exactly time-filtered — `seq`/`op_type` ride along so the device
    sort-dedup kernel can apply last-write-wins + tombstones (the analog of
    the reference's MergeReader output contract, read.rs:59-73)."""

    schema: Schema
    columns: Mapping = field(repr=False)
    seq: Optional[np.ndarray] = field(repr=False)
    op_type: Optional[np.ndarray] = field(repr=False)
    tag_dicts: dict[str, np.ndarray] = field(repr=False)
    num_rows: int
    needs_dedup: bool = True
    # identity for the device block cache: (region_id, incarnation,
    # data_version, scan_fingerprint) names an immutable column snapshot.
    # incarnation is the owning Region INSTANCE's id: TRUNCATE recreates
    # the region and resets data_version, so version alone could collide
    # with a pre-truncate snapshot (0 = unknown/remote/synthetic)
    region_id: int = -1
    data_version: int = 0
    incarnation: int = 0
    scan_fingerprint: tuple = ()
    # row offsets of the per-SST sorted segments inside `columns`:
    # rows [offsets[i], offsets[i+1]) are one flushed file's rows, sorted
    # by (tags..., ts, seq) (see Region._sort_order); rows past offsets[-1]
    # come from the memtable in arbitrary order. Lets first/last-class
    # aggregates gather per-series boundary rows instead of reducing the
    # whole scan (reference exploits the same order via per-file
    # last-row semantics in its merge reader, mito2/src/read/merge.rs).
    # () means "no sortedness information" (merged/remote scans).
    sorted_part_offsets: tuple = ()
    # per-SST-part identity aligned with sorted_part_offsets' segments:
    # (file_id, ts_range, pred_key) per contributing file, in row order.
    # The device hot set keys HBM column blocks by this, so a part's
    # uploads survive data-version bumps for the life of its file
    # (rows past offsets[-1] are memtable and carry no part identity).
    # () = no per-part identity (merged/synthetic/seq-sliced scans).
    part_keys: tuple = ()
    # observability: how this snapshot was built (ssts considered /
    # pruned, scan-cache reuse count, files decoded so far) — piggybacked
    # on the region wire protocol so distributed EXPLAIN ANALYZE shows
    # datanode-side IO. None for synthetic/merged scans. Mutated only
    # under the region lock (cache_hits bumps on each cached reuse).
    stats: Optional[dict] = None

    #: the snapshot's un-concatenated form, while it has one
    _parts = None

    @property
    def tag_cardinalities(self) -> dict[str, int]:
        return {k: len(v) for k, v in self.tag_dicts.items()}

    @property
    def materialized(self) -> bool:
        return self._parts is None

    def materialize(self) -> "ScanData":
        """Build the whole-scan columns (idempotent; returns self). Every
        missing part decodes in one fan-out across the scan pool and the
        columns concatenate in parallel; the snapshot then joins the
        region's snapshot cache, as a scan decoded up front always did."""
        parts = self._parts
        if parts is None:
            return self
        with parts.build_lock:
            if self._parts is None:
                return self
            try:
                segs = parts.all_parts()
            except BaseException:
                # a failed read leaves no file pinned behind it (a
                # retry pins again, or finds the snapshot expired)
                parts.close()
                raise
            if len(segs) == 1:
                # single part (one big SST, or memtable only): a concat
                # would copy ~the whole table for nothing — cold scans at
                # the TSBS 17M-row scale spend seconds here otherwise
                cols, seq, op = segs[0]
                columns = {n: cols[n] for n in parts.names}
            else:
                columns = parts.region._concat_columns(
                    parts.names, [p[0] for p in segs])
                seq = np.concatenate([p[1] for p in segs])
                op = np.concatenate([p[2] for p in segs])
            self.columns = columns
            self.seq = seq
            self.op_type = op
            self._parts = None
            parts.close()
            if parts.cache_key is not None:
                with parts.region._lock:
                    parts.region._scan_cache_put(parts.cache_key, self)
        return self

    def close(self) -> None:
        """Release what a plan holds for fetches to come (file pins, the
        held working set). Idempotent; the snapshot stays readable — a
        later fetch pins again, or raises ScanExpired."""
        parts = self._parts
        if parts is not None:
            parts.close()

    def rows(self, name: str, start: int, end: int) -> np.ndarray:
        """Rows [start, end) of one column. Inside one part of a
        snapshot still in parts this reads that part alone."""
        parts = self._parts
        if parts is not None:
            i = parts.segment_of(start, end)
            if i is not None:
                off = parts.offsets[i]
                return parts.part(i)[0][name][start - off:end - off]
        return self.columns[name][start:end]

    def hold_rows(self, ranges) -> Optional[list]:
        """Fetch, in one parallel decode, the parts holding the given
        (start, end) row ranges, and keep them until `release_rows`
        gets the returned handle. No-op on a materialized snapshot."""
        parts = self._parts
        if parts is None:
            return None
        idxs = [i for i in (parts.segment_of(s, e) for s, e in ranges)
                if i is not None]
        parts.hold(idxs)
        return idxs

    def fetch_width(self, n: int) -> int:
        """How many parts one `hold_rows` should ask for at a time: the
        decode fan-out the region's scan pool gives `n` files."""
        parts = self._parts
        if parts is None:
            return max(n, 1)
        from greptimedb_tpu.storage import scan_pool

        return scan_pool.resolve(parts.region.decode_threads, max(n, 1))

    def release_rows(self, handle) -> None:
        parts = self._parts
        if parts is not None and handle:
            parts.release(handle)

    def has_delete(self) -> bool:
        """Whether the snapshot holds any tombstone (memoized)."""
        memo = self.__dict__.get("_has_delete")
        if memo is None:
            parts = self._parts
            memo = parts.has_delete() if parts is not None \
                else bool((self.op_type != OP_PUT).any())
            self._has_delete = memo
        return memo

    def ts_extent(self, ts_name: str) -> tuple[int, int]:
        """(min, max) timestamp over the snapshot's rows."""
        spans = self.segment_ts_extents(ts_name)
        return (min(s[0] for s in spans), max(s[1] for s in spans))

    def segment_ts_extents(self, ts_name: str) -> list:
        """(min, max) timestamp per non-empty SST part, then the
        memtable tail's."""
        parts = self._parts
        if parts is not None:
            return parts.ts_extents(ts_name)
        offs = list(self.sorted_part_offsets) or [0]
        if offs[-1] < self.num_rows:
            offs.append(self.num_rows)  # memtable tail interval
        ts = self.columns[ts_name]
        spans = []
        for i in range(len(offs) - 1):
            s0, s1 = offs[i], offs[i + 1]
            if s1 > s0:
                seg = ts[s0:s1]
                spans.append((int(seg.min()), int(seg.max())))
        return spans


def _built_at_first_touch(attr: str) -> property:
    """`columns` / `seq` / `op_type` of a ScanData: plain attributes on
    a materialized snapshot; on one still in parts, `seq` / `op_type`
    materialize it and `columns` is a view that does so when a value is
    read (its names are free). The view is made per access and not
    kept, so a plan and its view form no reference cycle and a dropped
    plan releases its pins at once."""

    def get(self):
        if self._parts is not None:
            if attr == "_columns":
                return _LazyColumns(self, self._parts.names)
            self.materialize()
        return self.__dict__[attr]

    def put(self, value):
        self.__dict__[attr] = value

    return property(get, put)


ScanData.columns = _built_at_first_touch("_columns")
ScanData.seq = _built_at_first_touch("_seq")
ScanData.op_type = _built_at_first_touch("_op_type")


@dataclass
class ScanStream:
    """Lazy scan: metadata upfront, columns delivered as bounded chunks
    (reference streams lazy row groups with a page cache,
    sst/parquet/row_group.rs + reader.rs:335-447; here each chunk becomes
    one padded device block, so host memory stays flat regardless of scan
    size). Tag dictionaries come from the region's registry — complete
    without touching the data. Only append-mode (no-dedup) scans stream;
    last-write-wins needs the whole scan in one sort."""

    schema: Schema
    tag_dicts: dict[str, np.ndarray]
    region_id: int
    data_version: int
    est_rows: int
    ts_min: int  # over the pruned file set + memtable (chunk key planning)
    ts_max: int
    _chunks: object  # () -> Iterator[(cols dict, nrows)]
    _close: object = None  # idempotent; releases file pins
    incarnation: int = 0  # owning Region instance id (see ScanData)

    def chunks(self):
        return self._chunks()

    def close(self):
        """Release the snapshot's SST file pins. Idempotent, and required
        whenever the stream is abandoned before (or instead of) being
        iterated — a never-started generator's finally never runs."""
        if self._close is not None:
            self._close()


#: process-wide Region instance ids — TRUNCATE recreates a region with
#: the same region_id and a reset data_version, so snapshot identity
#: (device/snap cache keys) must also carry WHICH instance produced it
_REGION_INCARNATIONS = itertools.count(1)


class Region:
    def __init__(self, region_id: int, region_dir: str, schema: Schema, wal: Wal,
                 store=None, manifest: "ManifestManager" = None):
        self.region_id = region_id
        self.incarnation = next(_REGION_INCARNATIONS)
        self.region_dir = region_dir
        self.schema = schema
        self.wal = wal
        self.store = store
        self.manifest = manifest if manifest is not None else \
            ManifestManager(os.path.join(region_dir, "manifest"), store)
        self.sst_writer = SstWriter(os.path.join(region_dir, "sst"), schema,
                                    store=store)
        self.sst_reader = SstReader(os.path.join(region_dir, "sst"), store)
        tag_names = [c.name for c in schema.tag_columns]
        self.registry = TagRegistry(tag_names)
        # dictionary sizes as of the last manifest edit that held them
        self._recorded_dict_sizes: Optional[dict] = None
        self.memtable = Memtable(schema, self.registry)
        self.next_seq = 0
        self.files: dict[str, FileMeta] = {}
        # worker-model discipline (reference mito2 region worker,
        # worker.rs:110-650): one lock serializes this region's mutations;
        # scans take a consistent snapshot under it and decode outside
        self._lock = threading.RLock()
        # one compaction at a time per region (reference FlushScheduler /
        # CompactionScheduler serialize per region); the slow merge runs
        # outside the main lock so writes keep flowing
        self._compact_lock = threading.Lock()
        # set by drop(): late writers must fail, not resurrect WAL/SSTs
        self.dropped = False
        # compacted-away SSTs are purged only once no reader holds them —
        # scans pin their snapshot's files (the reference's FilePurger
        # refcount, mito2/src/sst/file_purger.rs)
        self._purge_queue: list[tuple[str, float]] = []
        self._file_refs: dict[str, int] = {}
        # bumped on every mutation; device cache keys include it
        self.data_version = 0
        # host scan cache: decoded-column snapshots keyed by
        # (data_version, ts_range, columns) — the analog of the reference's
        # decoded-page cache (mito2/src/cache.rs); repeated dashboard/TSBS
        # queries skip parquet decode entirely
        self._scan_cache: "OrderedDict[tuple, ScanData]" = OrderedDict()
        self.scan_cache_entries = 4  # overridden from EngineConfig
        # whole-scan snapshots and per-file parts draw on ONE shared
        # byte budget (part_cache_budget): the snapshot is a concat
        # COPY of the parts, so accounting them separately
        # double-counted host RAM (ROADMAP carry-over). The NEWEST
        # snapshot is exempt from the budget — refusing to cache the
        # working set of the current dashboard would trade a bounded
        # overshoot for re-decoding the table every query.
        self._scan_cache_sizes: dict[tuple, int] = {}
        self._scan_cache_bytes = 0
        # per-file decoded-part cache: (file_id, ts_range, names, preds)
        # -> _PartEntry, byte-budgeted LRU. SSTs are immutable, so an
        # entry stays valid for the file's whole life — a flush only
        # adds files, meaning a post-flush scan decodes ONLY the new
        # file and concats the rest from here (the monolithic
        # data_version-keyed cache above threw everything away on every
        # mutation). Entries die with their file: compaction swap,
        # retention expiry, and DROP/TRUNCATE call
        # _invalidate_file_parts.
        self._part_cache: "OrderedDict[tuple, _PartEntry]" = OrderedDict()
        self._part_cache_bytes = 0
        self.part_cache_budget = 1 << 30  # overridden from EngineConfig
        # file_id -> does the SST hold any non-PUT row? Noted when the
        # file is written (flush, compaction) or first decoded whole;
        # SSTs are immutable, so the answer stands until the file dies
        # (_invalidate_file_parts). Lets a scan plan answer "any
        # tombstone?" without reading op_type of files it will not
        # otherwise touch. Absent = unknown (files from before a
        # restart): the asker reads the file.
        self._file_deletes: dict[str, bool] = {}
        # SST decode fan-out cap; 0 = auto (storage/scan_pool.py)
        self.decode_threads = 0
        # ---- group-commit ingest pipeline (storage/group_commit.py) ----
        # attached by the engine when [ingest] group_commit is on; None
        # = the legacy serial write path (bit-for-bit differential tests
        # compare the two)
        self.committer = None
        # commit tickets order the WAL appends of concurrent group
        # commits: sequences are reserved under the region lock (fast),
        # but the append+fsync runs OUTSIDE it — the ticket turn keeps
        # the WAL file in sequence order anyway
        self._commit_tickets = itertools.count()
        self._wal_turn = 0
        self._wal_turn_cv = threading.Condition()
        # tickets reserved but not yet applied: flush/drop must wait for
        # these — a flush between reserve and apply would record a
        # flushed_seq past rows that are not yet in the memtable and
        # lose them on replay (acked-write loss)
        self._inflight_commits: set = set()
        self._commit_idle = threading.Condition(self._lock)
        # tickets abandoned before their turn (interrupt mid-wait): the
        # turn counter skips them instead of wedging every later commit
        self._dead_tickets: set = set()
        # flush/drop waiting for the commit pipeline to drain: while
        # nonzero, group_reserve holds new reservations back — without
        # the gate, overlapped commits under sustained ingest keep the
        # in-flight set nonempty and the quiesce would starve
        self._quiesce_waiters = 0

    # ---- lifecycle ---------------------------------------------------------

    @classmethod
    def create(cls, region_id: int, region_dir: str, schema: Schema, wal: Wal,
               store=None) -> "Region":
        region = cls(region_id, region_dir, schema, wal, store)
        region.manifest.record_schema(schema)
        return region

    @classmethod
    def open(cls, region_id: int, region_dir: str, wal: Wal, store=None) -> "Region":
        """Replay manifest (checkpoint + deltas), then WAL from flushed_seq
        (reference region/opener.rs:62-117)."""
        manifest = ManifestManager(os.path.join(region_dir, "manifest"), store)
        st = manifest.state
        if st.schema is None:
            raise FileNotFoundError(f"region {region_id} has no manifest at {region_dir}")
        region = cls(region_id, region_dir, st.schema, wal, store,
                     manifest=manifest)
        region.files = dict(st.files)
        # restore the tag registry snapshot taken at last flush; WAL replay
        # below re-adds any values seen since
        for name, values in st.tag_dicts.items():
            region.registry.restore(name, values)
        region._recorded_dict_sizes = {
            name: len(values) for name, values in st.tag_dicts.items()}
        region.next_seq = st.flushed_seq
        for entry in wal.replay(region_id, from_seq=st.flushed_seq):
            n = region.memtable.write(entry.batch, entry.seq, entry.op_type)
            region.next_seq = max(region.next_seq, entry.seq + n)
        return region

    def drop(self) -> None:
        with self._lock:
            self.dropped = True
            # in-flight group commits may still be appending to the WAL
            # this is about to delete; `dropped` blocks new reservations
            # and fails the in-flight ones at apply time
            self._quiesce_commits_locked()
            self._drain_purge(force=True)
            self.wal.delete_region(self.region_id)
            for fid in list(self.files):
                self.sst_reader.delete(fid)
            self._invalidate_file_parts(list(self.files))
            # snapshot-anchored hot-set entries must die too: TRUNCATE
            # recreates the region with the SAME region_id and resets
            # data_version, so a re-ingest could otherwise hit a
            # pre-truncate HBM block under a colliding version + shape
            self._notify_device_cache("invalidate_region")
            self.files.clear()
            self._scan_cache.clear()
            self._scan_cache_sizes.clear()
            self._scan_cache_bytes = 0

    def close(self) -> None:
        """Release deferred resources (deleted-but-grace-held SSTs)."""
        with self._lock:
            self._drain_purge(force=True)

    def _drain_purge(self, force: bool = False) -> None:
        """Delete deferred SSTs no reader pins (caller holds
        self._lock — drop/close/_unpin_files all enter under it)."""
        keep: list[tuple[str, float]] = []
        for fid, t in self._purge_queue:
            if self._file_refs.get(fid, 0) > 0 and not force:
                keep.append((fid, t))  # a reader still holds it
            else:
                self.sst_reader.delete(fid)
        self._purge_queue = keep

    def _pin_files(self, metas) -> None:
        for m in metas:
            self._file_refs[m.file_id] = self._file_refs.get(m.file_id, 0) + 1

    def _unpin_files(self, metas) -> None:
        with self._lock:
            for m in metas:
                n = self._file_refs.get(m.file_id, 0) - 1
                if n <= 0:
                    self._file_refs.pop(m.file_id, None)
                else:
                    self._file_refs[m.file_id] = n
            if self._purge_queue:
                self._drain_purge()

    # ---- per-file decoded-part cache + parallel decode ---------------------

    @property
    def _host_cache_bytes(self) -> int:
        """Bytes the part cache AND the whole-scan snapshots hold —
        the one number the shared budget bounds (caller holds
        self._lock; both put paths read it under the region lock)."""
        return self._part_cache_bytes + self._scan_cache_bytes

    def _part_cache_put(self, key: tuple, ent: _PartEntry) -> None:
        """Insert under the SHARED byte budget (caller holds self._lock):
        parts and whole-scan snapshots compete for the same bytes; a
        part insert evicts older parts, never snapshots (the snapshot is
        the hotter end product)."""
        from greptimedb_tpu.utils.metrics import SCAN_PART_CACHE_EVENTS

        # parts get whatever the resident snapshots leave over; when a
        # budget-exempt newest snapshot alone exceeds the budget there
        # is nothing left — refuse the insert instead of thrash-evicting
        # every part (including this one) on every decode
        avail = self.part_cache_budget - self._scan_cache_bytes
        if ent.nbytes > avail:
            return  # an entry that can never fit must not wipe the cache
        old = self._part_cache.pop(key, None)
        if old is not None:
            self._part_cache_bytes -= old.nbytes
        self._part_cache[key] = ent
        self._part_cache_bytes += ent.nbytes
        evicted = 0
        while self._part_cache_bytes > avail \
                and self._part_cache:
            _, e = self._part_cache.popitem(last=False)
            self._part_cache_bytes -= e.nbytes
            evicted += 1
        if evicted:
            SCAN_PART_CACHE_EVENTS.inc(float(evicted), event="evict")

    def _scan_cache_put(self, key: tuple, result: "ScanData") -> None:
        """Cache a whole-scan snapshot against the shared budget
        (caller holds self._lock): evict older snapshots beyond the
        entry-count limit, then cold parts, then older snapshots until
        the total fits — the newest snapshot itself always caches (it
        is live in the caller regardless; bounded overshoot beats
        re-decoding the active dashboard's table every query)."""
        nb = _scan_nbytes(result)
        old = self._scan_cache.pop(key, None)
        if old is not None:
            self._scan_cache_bytes -= self._scan_cache_sizes.pop(key, 0)
        from greptimedb_tpu.utils.metrics import SCAN_PART_CACHE_EVENTS

        self._scan_cache[key] = result
        self._scan_cache_sizes[key] = nb
        self._scan_cache_bytes += nb
        evicted = 0
        while len(self._scan_cache) > self.scan_cache_entries:
            k, _ = self._scan_cache.popitem(last=False)
            self._scan_cache_bytes -= self._scan_cache_sizes.pop(k, 0)
            evicted += 1
        while self._host_cache_bytes > self.part_cache_budget \
                and self._part_cache:
            _, e = self._part_cache.popitem(last=False)
            self._part_cache_bytes -= e.nbytes
            evicted += 1
        while self._host_cache_bytes > self.part_cache_budget \
                and len(self._scan_cache) > 1:
            k, _ = self._scan_cache.popitem(last=False)
            self._scan_cache_bytes -= self._scan_cache_sizes.pop(k, 0)
            evicted += 1
        if evicted:
            # snapshot evictions count here too: both caches spend the
            # ONE shared budget, so the operator's evict series must
            # show all of its churn, not just the part half
            SCAN_PART_CACHE_EVENTS.inc(float(evicted), event="evict")

    def _invalidate_file_parts(self, file_ids) -> None:
        """Drop part-cache entries for removed SSTs (compaction swap,
        retention expiry, DROP/TRUNCATE). Caller holds self._lock."""
        gone = set(file_ids)
        for k in [k for k in self._part_cache if k[0] in gone]:
            ent = self._part_cache.pop(k)
            self._part_cache_bytes -= ent.nbytes
        for fid in gone:
            self._file_deletes.pop(fid, None)
        # the HBM columnar hot set keys device blocks by the same file
        # identity — the seams that kill host parts kill device blocks
        self._notify_device_cache("invalidate_files", gone)

    def _notify_device_cache(self, fn_name: str, *args) -> None:
        """Best-effort invalidation fan-out to the query-layer caches
        keyed by file identity: the HBM columnar hot set AND the
        partial-aggregate cache (per-part [G, F] planes) die through
        the exact same seams that kill host parts. sys.modules lookup,
        not an import: a storage-only process that never ran a query
        has no caches to notify (and this runs under the region lock —
        the caches take only their own locks)."""
        import sys

        for modname in ("greptimedb_tpu.query.device_cache",
                        "greptimedb_tpu.query.partial_cache"):
            mod = sys.modules.get(modname)
            if mod is not None:
                try:
                    getattr(mod, fn_name)(self.region_id, *args)
                except Exception:  # noqa: BLE001 — upkeep must not fail the seam
                    pass

    def _decode_file_part(self, meta: FileMeta, ts_range, names,
                          tag_predicates, plan=None
                          ) -> tuple[Optional[tuple], _ReadCount]:
        """Read+decode one SST into host columns (the per-file body the
        old scan loop ran serially). Returns ((cols, seq, op) or None
        when pruning/filtering leaves nothing, what the read cost). A
        pruned read (a window or tag predicates) goes batch by batch of
        the row groups its plan left, each cut to the rows it keeps
        before the next is read (`_decode_batches`); a whole-file read
        keeps every row, so it reads them at once."""
        from greptimedb_tpu.utils.metrics import (
            SCAN_DECODE_BYTES,
            SCAN_DECODE_SECONDS,
        )

        with SCAN_DECODE_SECONDS.time():
            if ts_range is not None or tag_predicates:
                if plan is None:
                    plan = self.sst_reader.plan_groups(
                        meta, self.schema, ts_range, names,
                        tag_predicates=tag_predicates)
                if plan is None:
                    return None, _ReadCount()
                fp, groups, cols_proj = plan
                chunks, count = self._decode_batches(
                    meta, fp, cut_batches(groups, fp.group_rows),
                    cols_proj, ts_range, names, tag_predicates)
                part = _concat_parts(chunks)
            else:
                table = self.sst_reader.read(meta, self.schema, ts_range,
                                             names,
                                             tag_predicates=tag_predicates)
                if table is None or table.num_rows == 0:
                    return None, _ReadCount()
                part = self._decode_table_part(table, ts_range, names)
                count = _ReadCount(0 if part is None else len(part[1]))
        if part is None:
            return None, count
        SCAN_DECODE_BYTES.inc(float(_part_nbytes(part)))
        return part, count

    def _decode_batches(self, meta: FileMeta, fp, batches, cols_proj,
                        ts_range, names, tag_predicates
                        ) -> tuple[list, _ReadCount]:
        """Batches of one SST's planned row groups (`sst.cut_batches`),
        decoded one at a time and each cut — on the arrow table, before
        any column is converted — to the rows inside the window whose
        =/IN tag predicates hold. SSTs sort by (pk, ts), so the groups
        a point query needs a few hundred rows of hold tens of
        thousands each: the probe, the mask, the `take` and the
        conversion run once a batch, whatever it keeps, and a read
        never holds more than a batch of rows it will drop. Whole
        series keep/drop together, so LWW dedup and tombstones stay
        intact; the device WHERE still evaluates the predicate exactly.
        Returns (decoded chunks in batch order, the read's cost); rows
        and order are those of the whole-file decode."""
        ts_name = self.schema.time_index.name
        probe = [ts_name] if ts_range is not None else []
        probe += [t for t in (tag_predicates or {})
                  if t in names and t != ts_name]
        chunks: list = []
        in_window = rows_read = n_batches = 0
        for table in self.sst_reader.iter_batches(meta, fp, batches,
                                                  cols_proj):
            n_batches += 1
            rows_read += table.num_rows
            if table.num_rows == 0:
                continue
            keep = None
            if probe:
                # the time index rides along: it gives a column the
                # file lacks (backfilled) the batch's row count
                have = [n for n in dict.fromkeys([ts_name] + probe)
                        if n in table.column_names]
                pre = self._decode_sst(table.select(have), probe)
                if ts_range is not None:
                    tsv = pre[ts_name]
                    keep = (tsv >= ts_range[0]) & (tsv < ts_range[1])
                    if not keep.any():
                        continue
                in_window += table.num_rows if keep is None \
                    else int(keep.sum())
                tag_keep = self._tag_inset_mask(tag_predicates, pre) \
                    if tag_predicates else None
                if tag_keep is not None:
                    keep = tag_keep if keep is None else keep & tag_keep
            else:
                in_window += table.num_rows
            if keep is not None and not keep.all():
                table = table.take(np.flatnonzero(keep))
            part = self._decode_table_part(table, ts_range, names)
            if part is not None:
                chunks.append(part)
        return chunks, _ReadCount(in_window, rows_read, n_batches)

    def _decode_table_part(self, table, ts_range, names) -> Optional[tuple]:
        """Arrow table -> (cols, seq, op) with the exact ts row filter —
        the decode body shared by the whole-file and split-row-group
        paths (identical bytes either way; the split path just runs it
        per group chunk and concatenates in group order)."""
        ts_name = self.schema.time_index.name
        cols = self._decode_sst(table, names)
        seq_col = table.column(SEQ_COL).to_numpy(
            zero_copy_only=False).astype(np.int64)
        op_col = table.column(OP_COL).to_numpy(
            zero_copy_only=False).astype(np.int8)
        if ts_range is not None:
            # exact row filter: SSTs sort by (pk, ts), so a row
            # group from one large flush can span the whole time
            # range and row-group stats cannot prune it — drop
            # out-of-range rows here so downstream (device
            # transfer + kernels) only sees the queried window.
            # All versions/tombstones of an instant share its ts,
            # so LWW dedup still sees every candidate.
            tsv = cols[ts_name]
            # [lo, hi) — extract_ts_bounds emits half-open upper
            # bounds (ts <= v becomes hi = v+1), matching every
            # other pruner here (sst/memtable/scan_stream)
            m = (tsv >= ts_range[0]) & (tsv < ts_range[1])
            if not m.all():
                if not m.any():
                    return None
                cols = {n: v[m] for n, v in cols.items()}
                seq_col = seq_col[m]
                op_col = op_col[m]
        return (cols, seq_col, op_col)

    def _decode_file_part_split(self, meta: FileMeta, ts_range, names,
                                tag_predicates, threads: int
                                ) -> tuple[Optional[tuple], _ReadCount, int]:
        """One SST decoded by SEVERAL workers: the batches of its
        surviving row groups split into contiguous runs, each run read
        through its own parquet handle + decoded on the shared pool,
        reassembled in order — byte-for-byte the single-worker result
        (ISSUE 5 carry-over: one huge file used to serialize the decode
        stage). Returns (part or None, the read's cost as
        `_decode_file_part` counts it, workers observed)."""
        from greptimedb_tpu.storage import scan_pool
        from greptimedb_tpu.utils.metrics import (
            SCAN_DECODE_BYTES,
            SCAN_DECODE_SECONDS,
        )

        plan = self.sst_reader.plan_groups(meta, self.schema, ts_range,
                                           names,
                                           tag_predicates=tag_predicates)
        pruned = ts_range is not None or bool(tag_predicates)
        batches = [] if plan is None \
            else cut_batches(plan[1], plan[0].group_rows)
        k = min(threads, len(batches))
        if k <= 1:
            # nothing to split (pruned empty / one batch): the classic
            # whole-file path, so read()-level test spies and fault
            # seams see exactly the pre-split behavior
            if plan is None and pruned:
                return None, _ReadCount(), 1
            return (*self._decode_file_part(
                meta, ts_range, names, tag_predicates,
                plan if pruned else None), 1)
        fp, _groups, cols_proj = plan
        with SCAN_DECODE_SECONDS.time():
            # contiguous runs preserve row order under reassembly
            bounds = [len(batches) * i // k for i in range(k + 1)]
            runs = [batches[bounds[i]:bounds[i + 1]] for i in range(k)]
            pool = scan_pool.get(k)
            seen: set = set()

            def work(run):
                seen.add(threading.get_ident())
                if pruned:
                    return self._decode_batches(
                        meta, fp, run, cols_proj, ts_range, names,
                        tag_predicates)
                table = self.sst_reader.open(
                    meta.file_id, fp).read_row_groups(
                        [g for batch in run for g in batch],
                        columns=cols_proj)
                if table.num_rows == 0:
                    return [], _ReadCount()
                part = self._decode_table_part(table, ts_range, names)
                return ([], _ReadCount()) if part is None \
                    else ([part], _ReadCount(len(part[1])))

            from greptimedb_tpu.utils import tracing

            run_one = tracing.propagate(work)
            # scan_pool.submit re-adopts the query's CancelToken in the
            # worker: queued units for a dead query unwind typed
            futs = [scan_pool.submit(pool, run_one, run) for run in runs]
            chunks: list = []
            count = _ReadCount()
            first_err = None
            for f in futs:
                try:
                    got, n = dl.wait_future(f, "scan gather")
                    chunks.extend(got)
                    count = count.plus(n)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    if first_err is None:
                        first_err = e
            if first_err is not None:
                raise first_err
            part = _concat_parts(chunks)
        if part is None:
            return None, count, max(1, len(seen))
        SCAN_DECODE_BYTES.inc(float(_part_nbytes(part)))
        return part, count, max(1, len(seen))

    def _decode_parts(self, metas, ts_range, names,
                      tag_predicates) -> tuple[list, int]:
        """Decode several SSTs, fanning across the shared per-datanode
        pool (storage/scan_pool.py). Returns ((part, _ReadCount) pairs
        in `metas` order, distinct workers observed). decode_threads=1
        decodes inline, byte-for-byte the sequential path; a SINGLE
        file of several batches splits them across the pool
        instead of serializing on one worker (order-preserving
        reassembly).

        Fault discipline: every submitted future is WAITED ON before
        this returns or raises, so no worker touches SST bytes after
        the caller's unpin; the first error in file order propagates
        (typed FaultError/Unavailable from objectstore.read included),
        exactly as the serial loop raised it."""
        from greptimedb_tpu.storage import scan_pool

        # resolve against the CONFIGURED cap, not the file count: a
        # single huge SST gets its row groups split across the spare
        # workers instead of serializing on one (order-preserving —
        # see _decode_file_part_split)
        threads = scan_pool.resolve(self.decode_threads,
                                    max(len(metas), 1_000_000))
        if len(metas) == 1 and threads > 1:
            part, count, workers = self._decode_file_part_split(
                metas[0], ts_range, names, tag_predicates, threads)
            return [(part, count)], workers
        threads = min(threads, len(metas))
        if threads <= 1 or len(metas) <= 1:
            return ([self._decode_file_part(m, ts_range, names,
                                            tag_predicates)
                     for m in metas], 1)
        pool = scan_pool.get(threads)
        seen: set = set()

        def work(meta):
            seen.add(threading.get_ident())
            return self._decode_file_part(meta, ts_range, names,
                                          tag_predicates)

        # carry the request's trace/span/ledger context onto the pool
        # workers: per-file decode (and the objectstore_read spans
        # inside it) lands in the query's span tree
        from greptimedb_tpu.utils import tracing

        futs = [scan_pool.submit(pool, tracing.propagate(work), m)
                for m in metas]
        results: list = []
        first_err = None
        for f in futs:
            try:
                results.append(dl.wait_future(f, "decode gather"))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                results.append((None, _ReadCount()))
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err
        return results, max(1, len(seen))

    def _concat_columns(self, names, parts_cols) -> dict:
        """Assemble the whole-scan columns from per-file parts. Columns
        are independent, so the concat copies fan across the decode
        pool too (numpy releases the GIL for the memcpy) — on the
        incremental path this copy IS the remaining scan cost."""
        from greptimedb_tpu.storage import scan_pool

        threads = scan_pool.resolve(self.decode_threads, len(names))
        if threads <= 1 or len(names) <= 1:
            return {n: np.concatenate([p[n] for p in parts_cols])
                    for n in names}
        pool = scan_pool.get(threads)
        futs = {n: scan_pool.submit(
            pool, np.concatenate, [p[n] for p in parts_cols])
            for n in names}
        return {n: dl.wait_future(f, "concat gather")
                for n, f in futs.items()}

    def _cached_parts(self, file_list, ts_range, names, pred_key,
                      tag_predicates, insert: bool = True
                      ) -> tuple[list, dict]:
        """Per-file decoded parts for `file_list` (which the caller has
        pinned), through the part cache; misses decode in parallel.
        `insert=False` reuses hits but keeps misses out of the cache
        (compaction reads its soon-to-be-removed inputs once — caching
        them would evict warm query parts for zero retained value).
        Returns (list of _PartEntry aligned with file_list, stats)."""
        from greptimedb_tpu.utils.metrics import (
            SCAN_PART_CACHE_EVENTS,
            SCAN_ROWS,
        )

        keys = [(m.file_id, ts_range, tuple(names), pred_key)
                for m in file_list]
        parts: list = [None] * len(file_list)
        hits = 0
        with self._lock:
            for i, k in enumerate(keys):
                ent = self._part_cache.get(k)
                if ent is not None:
                    self._part_cache.move_to_end(k)
                    parts[i] = ent
                    hits += 1
        missing = [i for i in range(len(file_list)) if parts[i] is None]
        workers = 0
        t0 = time.perf_counter()
        if missing:
            decoded, workers = self._decode_parts(
                [file_list[i] for i in missing], ts_range, names,
                tag_predicates)
            whole = ts_range is None and not tag_predicates
            with self._lock:
                for i, (part, count) in zip(missing, decoded):
                    ent = _PartEntry(part, _part_nbytes(part),
                                     count.in_window)
                    parts[i] = ent
                    # a scan races compaction/expiry: its pinned files
                    # may have been removed (and invalidated) while it
                    # decoded — inserting then would strand dead
                    # entries in the budget forever
                    if file_list[i].file_id not in self.files:
                        continue
                    if insert:
                        self._part_cache_put(keys[i], ent)
                    if whole and part is not None:
                        self._file_deletes[file_list[i].file_id] = \
                            bool((part[2] != OP_PUT).any())
            total = _ReadCount()
            kept = 0
            for part, count in decoded:
                total = total.plus(count)
                if count.batches and part is not None:
                    kept += len(part[1])
            _scan_io_add(rows=total.in_window, read=total.read, kept=kept,
                         batches=total.batches)
            if total.batches:
                SCAN_ROWS.inc(float(total.read), kind="read")
                SCAN_ROWS.inc(float(kept), kind="kept")
        _scan_io_add(parts=len(file_list))
        from greptimedb_tpu.utils import ledger

        if hits:
            SCAN_PART_CACHE_EVENTS.inc(float(hits), event="hit")
            ledger.cache_event("scan_part", "hit", float(hits))
        if missing:
            SCAN_PART_CACHE_EVENTS.inc(float(len(missing)), event="miss")
            ledger.cache_event("scan_part", "miss", float(len(missing)))
            # decode-byte attribution on the request thread (the global
            # SCAN_DECODE_BYTES inc fires on pool workers, which don't
            # carry this request's contextvars)
            ledger.add("bytes_decoded",
                       float(sum(parts[i].nbytes for i in missing
                                 if parts[i] is not None)))
        return parts, {
            "part_hits": hits,
            "files_decoded": len(missing),
            "decode_workers": workers,
            "decode_s": round(time.perf_counter() - t0, 4),
        }

    @contextlib.contextmanager
    def _reading_planned(self, metas):
        """Pin a scan plan's files for a read made some time after the
        plan was taken under the region lock. They are readable if they
        still exist — live, or swapped out by compaction and waiting in
        the purge queue (the same rows the snapshot saw). Otherwise the
        snapshot is dead: ScanExpired, and the consumer scans afresh;
        likewise when DROP/TRUNCATE, which deletes files whatever pins
        them, lands under the read."""
        with self._lock:
            queued = {fid for fid, _ in self._purge_queue}
            if self.dropped or any(
                    m.file_id not in self.files and m.file_id not in queued
                    for m in metas):
                raise ScanExpired(
                    f"region {self.region_id}: a planned sst is gone")
            self._pin_files(metas)
        try:
            yield
        except Exception as e:
            if self.dropped:
                raise ScanExpired(
                    f"region {self.region_id} dropped under a scan") from e
            raise
        finally:
            self._unpin_files(metas)

    def _fetch_parts(self, metas, names, pred_key) -> tuple[list, dict]:
        """Bytes on demand for a scan plan: the whole-file parts of
        `metas`, through the part cache and the decode pool."""
        with self._reading_planned(metas):
            entries, cost = self._cached_parts(metas, None, names,
                                               pred_key, None)
        return [e.part for e in entries], cost

    def _files_have_delete(self, metas) -> bool:
        """Whether any of a plan's files holds a non-PUT row, for files
        `_file_deletes` does not know yet (written before this process
        opened the region): reads each file's op_type column alone —
        not the scan's projection — and notes the answer for good."""
        found = False
        with self._reading_planned(metas):
            for meta in metas:
                table = self.sst_reader.read(meta, self.schema, None, [])
                flag = table is not None and bool(
                    (table.column(OP_COL).to_numpy(zero_copy_only=False)
                     != OP_PUT).any())
                with self._lock:
                    if meta.file_id in self.files:
                        self._file_deletes[meta.file_id] = flag
                found = found or flag
        return found

    # ---- write -------------------------------------------------------------

    def write(self, batch: RecordBatch, op_type: int = OP_PUT) -> int:
        """Durable write: WAL first, then memtable (reference
        region_write_ctx.rs:92-144 + wal.rs:133). Returns affected rows."""
        return self.write_many([(batch, op_type)])[0]

    def write_many(self, items: list[tuple[RecordBatch, int]]) -> list[int]:
        """Apply several mutations with ONE WAL group commit (reference
        RegionWriteCtx batches all of a worker cycle's mutations into one
        WalWriter write, region_write_ctx.rs:92-144). Returns per-item
        affected rows.

        With the [ingest] group-commit pipeline attached, concurrent
        callers coalesce through the per-region bounded queue (one WAL
        append + one fsync + one memtable apply per drained group, the
        fsync OUTSIDE the region lock); otherwise the legacy serial path
        below runs — preserved bit-for-bit for differential tests."""
        if self.committer is not None:
            return self.committer.write_many(items)
        return self.write_many_serial(items)

    def write_many_serial(self, items: list[tuple[RecordBatch, int]]
                          ) -> list[int]:
        """The pre-pipeline write path: WAL append (and its fsync) and
        memtable apply under one region-lock hold."""
        counts = [b.num_rows for b, _ in items]
        live = [(b, op) for b, op in items if b.num_rows]
        if not live:
            return counts
        with self._lock:
            if self.dropped:
                # a write racing DROP must error, not silently append to
                # (and resurrect) the deleted region's WAL
                raise RegionDroppedError(
                    f"region {self.region_id} is dropped")
            seq = self.next_seq
            entries = []
            for batch, op_type in live:
                entries.append((seq, op_type, batch))
                seq += batch.num_rows
            self.wal.append_many(self.region_id, entries)
            for s, op_type, batch in entries:
                self.memtable.write(batch, s, op_type)
            self.next_seq = seq
            self.data_version += 1
        return counts

    # ---- group-commit hooks (storage/group_commit.py drives these) ---------

    def group_reserve(self, live: list[tuple[RecordBatch, int]]
                      ) -> tuple[int, list]:
        """Reserve the group's WAL sequences and a commit ticket under
        the region lock — metadata only, the slow encode/fsync work runs
        outside. Returns (ticket, [(seq, op_type, batch), ...])."""
        with self._lock:
            # a pending flush/DROP quiesce has priority: new
            # reservations wait so the in-flight set can actually drain
            while self._quiesce_waiters:
                self._commit_idle.wait(timeout=1.0)
            if self.dropped:
                raise RegionDroppedError(
                    f"region {self.region_id} is dropped")
            seq = self.next_seq
            entries = []
            for batch, op_type in live:
                entries.append((seq, op_type, batch))
                seq += batch.num_rows
            self.next_seq = seq
            ticket = next(self._commit_tickets)
            self._inflight_commits.add(ticket)
            return ticket, entries

    def group_commit(self, ticket: int, entries: list,
                     blob: Optional[bytes] = None) -> None:
        """Ticket-ordered durable commit: WAL append + fsync OUTSIDE the
        region lock (readers and other regions' writers never wait on
        the disk), then the memtable apply under it. `blob` is the
        pre-encoded WAL frame blob (encoded outside every lock, so the
        next group's encode overlaps this one's fsync); None falls back
        to the backend's own encode (remote WAL)."""
        from greptimedb_tpu.fault import FAULTS
        from greptimedb_tpu.utils.metrics import INGEST_WAL_FSYNC_SECONDS

        try:
            with self._wal_turn_cv:
                while self._wal_turn != ticket:
                    # bounded laps, never abandoned: the ticket MUST
                    # retire in sequence or every later commit wedges
                    self._wal_turn_cv.wait(timeout=1.0)
            # sole owner of this region's WAL tail until the turn
            # advances; a crash in here leaves at most a torn tail that
            # replay truncates (nothing in the group was acknowledged)
            FAULTS.fire("ingest.commit", op="append",
                        region=str(self.region_id))
            with INGEST_WAL_FSYNC_SECONDS.time():
                if blob is not None:
                    self.wal.append_blob(self.region_id, blob)
                else:
                    self.wal.append_many(self.region_id, entries)
            FAULTS.fire("ingest.commit", op="apply",
                        region=str(self.region_id))
            with self._lock:
                dropped = self.dropped
                if not dropped:
                    for s, op_type, batch in entries:
                        self.memtable.write(batch, s, op_type)
                    self.data_version += 1
            if dropped:
                # the rows are durable in a WAL that drop() is about to
                # delete — the write must not be acknowledged
                raise RegionDroppedError(
                    f"region {self.region_id} is dropped")
        finally:
            self._finish_commit(ticket)

    def group_abort(self, ticket: int) -> None:
        """Release a reserved ticket whose commit never started (encode
        failed, fault fired pre-append). Waits its WAL turn so the turn
        counter stays strictly sequential; the reserved sequences become
        a gap, which replay tolerates. The finally mirrors
        group_commit's: an interrupt landing mid-wait must still retire
        the ticket (as a dead one) or every later commit wedges."""
        try:
            with self._wal_turn_cv:
                while self._wal_turn != ticket:
                    self._wal_turn_cv.wait(timeout=1.0)
        finally:
            self._finish_commit(ticket)

    def _finish_commit(self, ticket: int) -> None:
        with self._wal_turn_cv:
            if self._wal_turn == ticket:
                self._wal_turn = ticket + 1
                while self._wal_turn in self._dead_tickets:
                    self._dead_tickets.discard(self._wal_turn)
                    self._wal_turn += 1
                self._wal_turn_cv.notify_all()
            elif self._wal_turn < ticket:
                # abandoned before its turn came up (interrupt during
                # the wait): let the predecessor's advance skip it
                self._dead_tickets.add(ticket)
        with self._lock:
            self._inflight_commits.discard(ticket)
            if not self._inflight_commits:
                self._commit_idle.notify_all()

    def _quiesce_commits_locked(self) -> None:
        """Wait (holding self._lock, released during the wait) until no
        group commit sits between reserve and apply: flush would record
        a flushed_seq past the reserved-but-unapplied rows and lose them
        on replay; drop would delete the WAL a commit is appending to.
        While waiting, group_reserve holds NEW reservations back (the
        _quiesce_waiters gate), so the drain is bounded by the already-
        reserved groups' fsyncs even under sustained overlapped ingest —
        commits always terminate via their finally."""
        self._quiesce_waiters += 1
        try:
            while self._inflight_commits:
                self._commit_idle.wait(timeout=5.0)
        finally:
            self._quiesce_waiters -= 1
            if not self._quiesce_waiters:
                self._commit_idle.notify_all()

    # ---- flush -------------------------------------------------------------

    def flush(self) -> Optional[FileMeta]:
        """Memtable → sorted SST; manifest edit; WAL truncate."""
        with self._lock:
            self._quiesce_commits_locked()
            return self._flush_locked()

    def _flush_locked(self) -> Optional[FileMeta]:
        self._drain_purge()
        data = self.memtable.concat()
        if data is None:
            return None
        cols, seq, op = data
        order = self._sort_order(cols, seq)
        sorted_cols = {k: v[order] for k, v in cols.items()}
        tag_dicts = {
            c.name: self.registry.dict_array(c.name) for c in self.schema.tag_columns
        }
        meta = self.sst_writer.write(sorted_cols, tag_dicts, seq[order], op[order])
        self.files[meta.file_id] = meta
        self._file_deletes[meta.file_id] = bool((op != OP_PUT).any())
        self.manifest.record_flush([meta], flushed_seq=self.next_seq,
                                   tag_dicts=self._dicts_to_record())
        self.memtable = Memtable(self.schema, self.registry)
        self.wal.obsolete(self.region_id, self.next_seq)
        self.data_version += 1
        return meta

    def _dicts_to_record(self) -> Optional[dict]:
        """The tag dictionaries for a manifest edit, or None where they
        have not grown since the last edit that carried them (replay
        keeps the last ones it saw): a region whose dictionary is a
        million label sets does not rewrite it with every flush."""
        sizes = {c.name: self.registry.cardinality(c.name)
                 for c in self.schema.tag_columns}
        if sizes == self._recorded_dict_sizes:
            return None
        self._recorded_dict_sizes = sizes
        return self.registry.snapshot()

    def _sort_order(self, cols: dict[str, np.ndarray], seq: np.ndarray) -> np.ndarray:
        keys = [seq, cols[self.schema.time_index.name]]
        for c in reversed(self.schema.tag_columns):
            keys.append(cols[c.name])
        return np.lexsort(keys)

    # ---- compaction (TWCS: merge within time windows) ----------------------

    def compact(self, strategy: str = "twcs") -> list[FileMeta]:
        """Compact SSTs. "twcs": time-window groups picked by TwcsPicker
        (reference compaction/twcs.rs); "full": everything into one file
        (manual strict-window analog, ADMIN compact_table). The merge is the
        computation of query-time dedup, persisted (SURVEY.md §7): the
        inputs are sorted runs, so the host merges them."""
        from greptimedb_tpu.storage.compaction import TwcsPicker

        with self._compact_lock:
            with self._lock:
                files = list(self.files.values())
            if strategy == "full":
                groups = [files] if len(files) > 1 else []
            else:
                groups = TwcsPicker().pick(files)
            out: list[FileMeta] = []
            for group in groups:
                meta = self._merge_files(group)
                if meta is not None:
                    out.append(meta)
            return out

    def _merge_files(self, group: list[FileMeta]) -> Optional[FileMeta]:
        """Read `group`'s SSTs, merge their sorted runs last-write-wins,
        rewrite as one L1 file, swap in the manifest (compaction/task.rs
        analog)."""
        names = self.schema.names
        from greptimedb_tpu.storage.index import predicates_cache_key

        # the merge reads full files with no range/predicates — exactly
        # the shape a full scan caches, so compaction REUSES warm scan
        # parts and decodes cold inputs in parallel; insert=False keeps
        # its one-shot inputs from evicting warm query entries
        with self._lock:
            self._pin_files(group)
        try:
            entries, _ = self._cached_parts(
                group, None, names, predicates_cache_key(None), None,
                insert=False)
        finally:
            self._unpin_files(group)
        parts_cols, parts_seq, parts_op = [], [], []
        for ent in entries:
            if ent.part is None:
                continue
            cols_p, seq_p, op_p = ent.part
            parts_cols.append(cols_p)
            parts_seq.append(seq_p)
            parts_op.append(op_p)
        if not parts_cols:
            return None
        columns = self._concat_columns(names, parts_cols)
        seq = np.concatenate(parts_seq)
        op = np.concatenate(parts_op)
        n_rows = len(seq)

        tag_names = [c.name for c in self.schema.tag_columns]
        # the inputs are (tags, ts, seq)-sorted runs, as flush wrote
        # them: the host's stable sort merges presorted runs in seconds
        # where a device sort of the whole group takes minutes at tens
        # of millions of rows; last write wins per (series, ts)
        order = self._sort_order(columns, seq)
        same = np.ones(max(n_rows - 1, 0), dtype=bool)
        for name in tag_names + [self.schema.time_index.name]:
            v = columns[name][order]
            same &= v[1:] == v[:-1]
        keep = np.ones(n_rows, dtype=bool)
        keep[:-1] = ~same
        if len(group) == len(self.files):
            # winning tombstones go only where the group is every file:
            # a partial (windowed) compaction retains them, since an
            # older shadowed PUT may live in a file outside the group
            keep &= op[order] != OP_DELETE
        order = order[keep]
        cols = {k: v[order] for k, v in columns.items()}
        tag_dicts = {n: self.registry.dict_array(n) for n in tag_names}
        meta = self.sst_writer.write(
            cols, tag_dicts, seq[order], op[order], level=1
        )
        removed = [f.file_id for f in group]
        import time as _time

        from greptimedb_tpu.fault import FAULTS

        # chaos seam: a crash HERE (new SST durable, manifest not yet
        # edited) must leave the pre-compaction file list authoritative —
        # the new file is an unreferenced orphan, never a half-swap
        FAULTS.fire("maintenance.job", op="compact", phase="swap")
        with self._lock:
            for fid in removed:
                self.files.pop(fid, None)
            self.files[meta.file_id] = meta
            self._file_deletes[meta.file_id] = \
                bool((op[order] != OP_PUT).any())
            # the inputs' decoded parts die with them — a later scan
            # must decode the merged output, never concat stale inputs
            self._invalidate_file_parts(removed)
            # flushed_seq=None: this edit persists NO memtable rows —
            # advancing it here would mark concurrent unflushed writes
            # replay-obsolete (acked-write loss on crash)
            self.manifest.record_flush(
                [meta], flushed_seq=None,
                tag_dicts=self._dicts_to_record(), removed=removed)
            # defer physical deletion: concurrent scans may still hold
            # the pre-compaction file list
            now = _time.monotonic()
            self._purge_queue.extend((fid, now) for fid in removed)
            self.data_version += 1
        return meta

    def _tag_inset_mask(self, tag_predicates, columns):
        """Row mask for the InSet (=/IN) and CodeSet parts of the tag
        predicates over global-code columns, or None when neither
        applies. Regex/Range predicates stay with the device filter."""
        from greptimedb_tpu.storage.index import (
            CodeSet,
            InSet,
            normalize_predicates,
        )

        keep = None
        for tag, preds in normalize_predicates(tag_predicates).items():
            if tag not in columns:
                continue
            allowed = None
            for p in preds:
                if isinstance(p, InSet):
                    codes = np.asarray(
                        self.registry.codes_of(tag, p.values), dtype=np.int64)
                elif isinstance(p, CodeSet):
                    codes = p.codes
                else:
                    continue
                allowed = codes if allowed is None \
                    else np.intersect1d(allowed, codes)
            if allowed is None:
                continue
            m = np.isin(columns[tag], allowed)
            keep = m if keep is None else (keep & m)
        return keep

    def _widen_covering_range(self, ts_range):
        """None when `ts_range` covers at least half of the region's
        data span (see scan: canonical-cache sharing), else unchanged."""
        if ts_range is None:
            return None
        lo, hi = ts_range
        with self._lock:
            # metadata-only snapshot under the lock: flush mutates
            # self.files and swaps self.memtable concurrently
            mins = [m.ts_min for m in self.files.values()]
            maxs = [m.ts_max for m in self.files.values()]
            mem = self.memtable
            mem_min, mem_max = mem.ts_min, mem.ts_max
        if mem_min is not None and mem_max is not None:
            mins.append(mem_min)
            maxs.append(mem_max)
        if not mins:
            return ts_range
        glo, ghi = min(mins), max(maxs)
        if lo <= glo and hi > ghi:
            return None  # covers everything: exactly the full scan
        covered = min(hi, ghi + 1) - max(lo, glo)
        return None if 2 * covered >= (ghi + 1 - glo) else ts_range

    # ---- scan --------------------------------------------------------------

    def scan(
        self,
        ts_range: Optional[tuple[int, int]] = None,
        projection: Optional[Sequence[str]] = None,
        tag_predicates: Optional[dict[str, set]] = None,
        seq_min: Optional[int] = None,
        full_key: bool = True,
    ) -> Optional[ScanData]:
        """Collect memtable + pruned SSTs into concatenated host columns.
        `tag_predicates` (tag -> allowed values) drives inverted-index
        row-group pruning; the scan result may then contain rows the
        predicate rejects — the device filter still runs, pruning is purely
        an IO reduction (never affects correctness).

        `full_key`: whether the table's whole primary key rides along
        with `projection` (every tag column; the time index always
        does). A last-write-wins merge (query/lww.py) and a cut into
        series runs need it, so that is the default. The CALLER decides,
        from the table's declared `append_mode` — the catalog holds it,
        a region's manifest does not: an append-mode table's statements
        make no mask, and with `full_key=False` their scans decode the
        columns they name and no other (`__seq` / `__op_type` stay in
        every part).

        `seq_min`: return only rows written AFTER that sequence — the
        incremental-consumer scan (flow ticks fold each row exactly
        once). Prunes whole SSTs by FileMeta.max_seq, so the IO cost is
        O(new data + files that straddle the boundary), not O(table)."""
        names = self._scan_columns(projection, full_key)
        from greptimedb_tpu.storage.index import predicates_cache_key
        pred_key = predicates_cache_key(tag_predicates)
        if seq_min is not None:
            return self._scan_since(seq_min, ts_range, names,
                                    tag_predicates)
        # wide windows (>= half the region's time span) serve the
        # CANONICAL full scan instead of a range-keyed copy: every
        # distinct ts_range otherwise caches its own host columns AND
        # its own HBM blocks (the fingerprint keys them), so a handful
        # of overlapping dashboards would hold several copies of the
        # table. Kernels mask exactly either way; narrow windows still
        # get a filtered copy (that is where filtering pays), and
        # tag-predicated scans keep their exact range — the inverted
        # index already shrank them, so the copy is cheap and computing
        # over the shared full rows would cost more than it saves.
        if not tag_predicates:
            ts_range = self._widen_covering_range(ts_range)
        # snapshot phase under the region lock: version + file list +
        # memtable rows form one consistent view (an acknowledged row is
        # in a listed file or in `mem`); SST decode (the slow part) runs
        # outside, on immutable pinned files
        with self._lock:
            version = self.data_version
            cache_key = (version, ts_range, tuple(names), pred_key)
            cached = self._scan_cache.get(cache_key)
            if cached is not None:
                self._scan_cache.move_to_end(cache_key)
                if cached.stats is not None:
                    cached.stats["cache_hits"] += 1
                return cached
            file_list = list(self.files.values())
            self._pin_files(file_list)
            mem = self.memtable.concat(ts_range)
        # a FULL scan (no window left after widening, no predicates) is
        # a plan: each part is a whole immutable file, so its row count
        # is FileMeta's and nothing has to be decoded to lay the
        # snapshot out. Its bytes come per part when a consumer asks
        # (_ScanParts), the files pinned until then. A pruned scan only
        # learns its row counts by decoding, so it decodes now — in
        # parallel through the per-file part cache: misses fan across
        # the shared pool, hits are free — and keeps the parts; neither
        # concatenates before a consumer reads whole columns. Part order
        # is the serial file order either way (so LWW dedup, the sorted
        # part_offsets contract, and fault propagation order all behave
        # as the old one-file-at-a-time loop did)
        lazy = ts_range is None and not tag_predicates
        if lazy:
            metas = [m for m in file_list if m.num_rows > 0]
            loaded: list = [None] * len(metas)
            decode_stats = {"part_hits": 0, "files_decoded": 0,
                            "decode_workers": 0, "decode_s": 0.0}
        else:
            try:
                part_entries, decode_stats = self._cached_parts(
                    file_list, ts_range, names, pred_key, tag_predicates)
            finally:
                self._unpin_files(file_list)
            metas = [m for m, e in zip(file_list, part_entries)
                     if e.part is not None]
            loaded = [e.part for e in part_entries if e.part is not None]
        if mem is not None:
            mcols, mseq, mop = mem
            mem = ({n: mcols[n] for n in names}, mseq, mop)
        # rows read before the exact tag filter (what index pruning
        # left to decode): the scan's IO, which a caller that pushes
        # predicates compares with the rows it got back
        if not lazy:
            decode_stats["rows_prefilter"] = \
                sum(e.rows_read for e in part_entries) \
                + (len(mem[1]) if mem is not None else 0)
        if tag_predicates and mem is not None:
            # exact row filter for equality/IN tag predicates: the
            # inverted index prunes row groups, but one row group holds
            # hundreds of series — dropping non-matching rows keeps the
            # cached scan (and device compute) proportional to the
            # SELECTED series. Whole series keep/drop together, so LWW
            # dedup and tombstones stay intact; the device WHERE still
            # evaluates the predicate exactly (incl. NULL semantics).
            # An SST part was cut when it was decoded, batch by batch
            # (_decode_batches; an emptied part keeps its place as a
            # zero-row segment); the memtable slice is cut here
            mem = self._inset_filter(tag_predicates, mem)
            if not len(mem[1]):
                mem = None
        rows = [m.num_rows for m in metas] if lazy \
            else [len(p[1]) for p in loaded]
        num_rows = sum(rows) + (len(mem[1]) if mem is not None else 0)
        if num_rows == 0:
            # preserve the "no rows" contract: consumers None-check,
            # they never expect a 0-row ScanData
            if lazy:
                self._unpin_files(file_list)
            return None
        parts = _ScanParts(self, names, pred_key, metas, rows, loaded, mem,
                           cache_key)
        tag_dicts = {
            c.name: self.registry.dict_array(c.name)
            for c in self.schema.tag_columns
            if c.name in names
        }
        result = ScanData(
            schema=self.schema,
            columns=None,
            seq=None,
            op_type=None,
            tag_dicts=tag_dicts,
            num_rows=num_rows,
            region_id=self.region_id,
            data_version=version,
            incarnation=self.incarnation,
            scan_fingerprint=(ts_range, tuple(names), pred_key),
            sorted_part_offsets=tuple(parts.offsets[:len(metas) + 1]),
            # device hot-set identity: a part's rows depend only on the
            # immutable file + the window/predicate key (the inset
            # filter keeps whole series deterministically)
            part_keys=tuple((m.file_id, ts_range, pred_key)
                            for m in metas),
            stats={"ssts": len(file_list),
                   "ssts_pruned": len(file_list) - len(metas),
                   "cache_hits": 0,
                   **decode_stats},
        )
        result._parts = parts
        parts.stats = result.stats
        if lazy:
            # the plan keeps its files pinned until its bytes are read
            # (or it is closed, or dropped as garbage): it is NOT parked
            # in the snapshot cache — a plan costs nothing to take
            # again, and a parked one would hold its pins for as long
            # as it stayed
            parts.pins = _PlanPins(self, file_list)
            weakref.finalize(parts, parts.pins.release)
        else:
            with self._lock:
                self._scan_cache_put(cache_key, result)
        return result

    def _inset_filter(self, tag_predicates, part: tuple) -> tuple:
        """One part's rows that pass the InSet tag predicates."""
        cols, seq, op = part
        keep = self._tag_inset_mask(tag_predicates, cols)
        if keep is None or keep.all():
            return part
        idx = np.flatnonzero(keep)
        return ({n: v[idx] for n, v in cols.items()}, seq[idx], op[idx])

    def scan_last(self, group_tag: str,
                  projection: Optional[Sequence[str]] = None,
                  full_key: bool = True,
                  ) -> Optional[ScanData]:
        """Lastpoint-pruned scan: visit SSTs NEWEST-FIRST (FileMeta
        ts_max order) and stop once every series grouped by `group_tag`
        provably holds its last row in the visited set — instead of
        decoding the whole table for a handful of winner rows (TSBS
        `lastpoint` is the user; the reference's merge reader gets the
        same effect from per-file last-row semantics).

        Termination argument: files are visited in descending ts_max,
        so every unvisited file only holds rows with ts <= the next
        file's ts_max. Once a series has a candidate with ts STRICTLY
        above that bound (strict: an equal ts in an older file could
        carry a higher seq and win LWW), no unvisited file can hold its
        winner — or any version of the winning instant, so the subset
        dedup picks the true row. The known-series set is the tag
        registry's value list (a superset of live values; codes with no
        surviving rows block early stop, which costs pruning, never
        correctness). NULL-tag rows form a group the registry cannot
        name: FileMeta.null_tags says which files may hold them
        (None = pre-upgrade file, assumed to), and termination also
        waits for the NULL group whenever an unvisited file might
        contribute to it.

        Returns None when the path cannot serve the query exactly —
        any DELETE tombstone in the visited rows or memtable (the
        newest row may be a tombstone, making an interior row the
        answer) — and the caller falls back to the full scan.
        `full_key` as in `scan`."""
        names = self._scan_columns(projection, full_key)
        tag_names = [c.name for c in self.schema.tag_columns]
        if group_tag not in tag_names or group_tag not in names:
            return None
        from greptimedb_tpu.storage.index import predicates_cache_key
        pred_key = predicates_cache_key(None)
        ts_name = self.schema.time_index.name
        with self._lock:
            version = self.data_version
            cache_key = ("lastpoint", version, group_tag, tuple(names))
            cached = self._scan_cache.get(cache_key)
            if cached is not None:
                self._scan_cache.move_to_end(cache_key)
                if cached.stats is not None:
                    cached.stats["cache_hits"] += 1
                return cached
            # deterministic newest-first order (ties broken by id so
            # parallel and serial runs visit identical prefixes)
            file_list = sorted(
                self.files.values(),
                key=lambda m: (m.ts_max, m.max_seq, m.file_id),
                reverse=True)
            self._pin_files(file_list)
            mem = self.memtable.concat(None)
            card = self.registry.cardinality(group_tag)
        # suffix_null[i]: may any of file_list[i:] hold NULL group_tag?
        suffix_null = [False] * (len(file_list) + 1)
        for i in range(len(file_list) - 1, -1, -1):
            m = file_list[i]
            has = m.null_tags is None or group_tag in m.null_tags
            suffix_null[i] = suffix_null[i + 1] or has
        # best[0] = newest ts seen for the NULL group, best[1 + code]
        # for each registry code; int64 min = "never seen"
        floor = np.iinfo(np.int64).min
        best = np.full(card + 1, floor, dtype=np.int64)

        def fold(codes: np.ndarray, ts: np.ndarray) -> None:
            nonlocal best
            if codes.size == 0:
                return
            slot = codes.astype(np.int64) + 1
            mx = int(slot.max())
            if mx >= best.size:
                # a file dictionary introduced values the registry
                # snapshot predates — grow; they were seen here, so
                # their termination entries are live
                best = np.concatenate(
                    [best, np.full(mx + 1 - best.size, floor,
                                   dtype=np.int64)])
            np.maximum.at(best, slot, ts.astype(np.int64))

        aborted = False
        if mem is not None:
            mcols, _mseq, mop = mem
            if bool((mop != OP_PUT).any()):
                aborted = True
            else:
                fold(np.asarray(mcols[group_tag]),
                     np.asarray(mcols[ts_name]))
        visited_entries: list = []
        visited = 0
        part_hits = files_decoded = 0
        workers = 1
        try:
            from greptimedb_tpu.storage import scan_pool

            stop = False
            while not (aborted or stop) and visited < len(file_list):
                # decode in waves of the pool width (parallelism inside
                # a wave), but take the wave's files one at a time and
                # test the stop condition after each: what the pruned
                # scan visits — and whether a tombstone voids it — must
                # not depend on how wide the pool is. Files the wave
                # decoded past the stop point are dropped unread (they
                # stay in the part cache)
                threads = scan_pool.resolve(self.decode_threads,
                                            len(file_list) - visited)
                wave = file_list[visited:visited + max(1, threads)]
                parts, st = self._cached_parts(wave, None, names,
                                               pred_key, None)
                part_hits += st["part_hits"]
                files_decoded += st["files_decoded"]
                workers = max(workers, st["decode_workers"])
                for ent in parts:
                    visited_entries.append(ent)
                    visited += 1
                    if ent.part is not None:
                        cols, _seq_col, op_col = ent.part
                        if bool((op_col != OP_PUT).any()):
                            aborted = True
                            break
                        fold(np.asarray(cols[group_tag]),
                             np.asarray(cols[ts_name]))
                    if visited >= len(file_list):
                        break
                    nxt = file_list[visited].ts_max
                    if bool((best[1:] > nxt).all()) and \
                            (not suffix_null[visited] or best[0] > nxt):
                        stop = True
                        break
        finally:
            self._unpin_files(file_list)
        if aborted:
            return None  # tombstones: caller runs the full scan
        parts_cols: list = []
        parts_seq: list = []
        parts_op: list = []
        sst_part_lens: list = []
        part_keys: list = []
        for meta, ent in zip(file_list, visited_entries):
            if ent.part is None:
                continue
            cols, seq_col, op_col = ent.part
            parts_cols.append(cols)
            parts_seq.append(seq_col)
            parts_op.append(op_col)
            sst_part_lens.append(len(seq_col))
            # full-file parts (no window, no predicates): these HBM
            # blocks are shared with full-scan keys of the same file
            part_keys.append((meta.file_id, None, pred_key))
        if mem is not None:
            mcols, mseq, mop = mem
            parts_cols.append({n: mcols[n] for n in names})
            parts_seq.append(mseq)
            parts_op.append(mop)
        if not parts_cols:
            return None
        if len(parts_cols) == 1:
            columns = dict(parts_cols[0])
            seq = parts_seq[0]
            op = parts_op[0]
        else:
            columns = self._concat_columns(names, parts_cols)
            seq = np.concatenate(parts_seq)
            op = np.concatenate(parts_op)
        part_offsets = np.cumsum([0] + sst_part_lens)
        tag_dicts = {
            c.name: self.registry.dict_array(c.name)
            for c in self.schema.tag_columns
            if c.name in names
        }
        result = ScanData(
            schema=self.schema,
            columns=columns,
            seq=seq,
            op_type=op,
            tag_dicts=tag_dicts,
            num_rows=len(seq),
            region_id=self.region_id,
            data_version=version,
            incarnation=self.incarnation,
            # distinct from any full scan: the row set is pruned, so
            # device blocks must never be shared with full-scan keys
            scan_fingerprint=("lastpoint", group_tag, tuple(names)),
            sorted_part_offsets=tuple(int(o) for o in part_offsets),
            part_keys=tuple(part_keys),
            stats={"ssts": len(file_list),
                   "ssts_pruned": len(file_list) - visited,
                   "cache_hits": 0,
                   "lastpoint_visited": visited,
                   "part_hits": part_hits,
                   "files_decoded": files_decoded,
                   "decode_workers": workers},
        )
        with self._lock:
            self._scan_cache_put(cache_key, result)
        return result

    def _scan_since(self, seq_min: int, ts_range, names,
                    tag_predicates) -> Optional[ScanData]:
        """The seq_min slice of scan(): rows with seq > seq_min only.
        The whole-scan result is uncached (each consumer's boundary
        differs and moves every tick), but the per-file decode rides
        the shared part cache + decode pool — a boundary-straddling
        file decodes once, not once per tick, and misses fan out in
        parallel exactly like scan(); SSTs whose max_seq <= seq_min
        never leave disk."""
        from greptimedb_tpu.storage.index import predicates_cache_key

        pred_key = predicates_cache_key(tag_predicates)
        with self._lock:
            version = self.data_version
            file_list = [m for m in self.files.values()
                         if m.max_seq > seq_min]
            self._pin_files(file_list)
            mem = self.memtable.concat(ts_range)
        parts_cols: list[dict] = []
        parts_seq: list[np.ndarray] = []
        parts_op: list[np.ndarray] = []
        sst_part_lens: list[int] = []
        try:
            part_entries, _stats = self._cached_parts(
                file_list, ts_range, names, pred_key, tag_predicates)
        finally:
            self._unpin_files(file_list)
        for ent in part_entries:
            if ent.part is None:
                continue
            # parts are ts-filtered already; the seq boundary applies on
            # COPIES — cached entries must stay whole for full scans
            cols, seq_col, op_col = ent.part
            m = seq_col > seq_min
            if not m.any():
                continue
            if not m.all():
                cols = {n: v[m] for n, v in cols.items()}
                seq_col = seq_col[m]
                op_col = op_col[m]
            parts_cols.append(cols)
            parts_seq.append(seq_col)
            parts_op.append(op_col)
            sst_part_lens.append(len(seq_col))
        if mem is not None:
            mcols, mseq, mop = mem
            m = mseq > seq_min
            if m.any():
                parts_cols.append({n: mcols[n][m] for n in names})
                parts_seq.append(mseq[m])
                parts_op.append(mop[m])
        if not parts_cols:
            return None
        if len(parts_cols) == 1:
            columns = dict(parts_cols[0])
            seq = parts_seq[0]
            op = parts_op[0]
        else:
            columns = self._concat_columns(names, parts_cols)
            seq = np.concatenate(parts_seq)
            op = np.concatenate(parts_op)
        part_offsets = np.cumsum([0] + sst_part_lens)
        tag_dicts = {
            c.name: self.registry.dict_array(c.name)
            for c in self.schema.tag_columns
            if c.name in names
        }
        return ScanData(
            schema=self.schema, columns=columns, seq=seq, op_type=op,
            tag_dicts=tag_dicts, num_rows=len(seq),
            region_id=self.region_id, data_version=version,
            incarnation=self.incarnation,
            scan_fingerprint=(ts_range, tuple(names), "seq", int(seq_min)),
            sorted_part_offsets=tuple(int(o) for o in part_offsets),
        )

    def scan_stream(
        self,
        ts_range: Optional[tuple[int, int]] = None,
        projection: Optional[Sequence[str]] = None,
        tag_predicates: Optional[dict[str, set]] = None,
        full_key: bool = True,
    ) -> Optional["ScanStream"]:
        """Lazy bounded-memory scan (see ScanStream). Returns None when the
        time range prunes everything. `full_key` as in `scan`."""
        names = self._scan_columns(projection, full_key)
        with self._lock:
            snapshot_files = list(self.files.values())
            self._pin_files(snapshot_files)
            mem = self.memtable.concat(ts_range)
            stream_version = self.data_version
        files = [
            meta for meta in snapshot_files
            if ts_range is None
            or (meta.ts_max >= ts_range[0] and meta.ts_min < ts_range[1])
        ]
        if not files and mem is None:
            self._unpin_files(snapshot_files)
            return None
        bounds = [(m.ts_min, m.ts_max) for m in files]
        if mem is not None and len(mem[1]):
            ts_name = self.schema.time_index.name
            bounds.append((int(mem[0][ts_name].min()),
                           int(mem[0][ts_name].max())))
        ts_min = min(b[0] for b in bounds)
        ts_max = max(b[1] for b in bounds)
        est = sum(m.num_rows for m in files) + (len(mem[1]) if mem else 0)

        unpinned = [False]

        def unpin_once():
            if not unpinned[0]:
                unpinned[0] = True
                self._unpin_files(snapshot_files)

        def gen():
            from greptimedb_tpu.storage import scan_pool

            workers = scan_pool.resolve(self.decode_threads, len(files))
            try:
                if workers <= 1 or len(files) <= 1:
                    # decode_threads=1: byte-for-byte the sequential
                    # pre-pipeline path (parity tests compare to it)
                    for meta in files:
                        for table in self.sst_reader.iter_chunks(
                                meta, self.schema, ts_range, names,
                                tag_predicates=tag_predicates):
                            if table.num_rows:
                                yield (self._decode_sst(table, names),
                                       table.num_rows)
                else:
                    yield from self._stream_files_parallel(
                        files, ts_range, names, tag_predicates, workers)
                if mem is not None and len(mem[1]):
                    yield {n: mem[0][n] for n in names}, len(mem[1])
            finally:
                unpin_once()

        return ScanStream(
            schema=self.schema,
            tag_dicts={
                c.name: self.registry.dict_array(c.name)
                for c in self.schema.tag_columns if c.name in names
            },
            region_id=self.region_id,
            data_version=stream_version,
            incarnation=self.incarnation,
            est_rows=est,
            ts_min=ts_min,
            ts_max=ts_max,
            _chunks=gen,
            _close=unpin_once,
        )

    def _stream_files_parallel(self, files, ts_range, names,
                               tag_predicates, workers: int):
        """Streaming-scan decode pipeline: up to `workers` files decode
        concurrently, each producing into its own small bounded queue;
        the consumer drains queues in file order, so chunks come out in
        EXACTLY the serial order (file order, chunk order within a file
        — the bit-for-bit parity contract) while later files decode in
        the background. Host memory stays bounded: workers x (queue of
        2 + 1 in-flight) chunks. Errors surface at the failing file's
        position in the consumption order, like the serial loop raised
        them.

        Producers run on a PER-STREAM executor, not the shared scan
        pool: a stream is consumer-paced — a client that pauses between
        chunks parks its producers against their full queues for
        arbitrarily long, and on the shared pool those parked workers
        would starve every other scan's decode on the datanode. The
        worker COUNT still honors the [scan] decode_threads sizing."""
        import queue as _queue
        from concurrent.futures import ThreadPoolExecutor
        from concurrent.futures import TimeoutError as _FutTimeout

        from greptimedb_tpu.storage import scan_pool

        pool = ThreadPoolExecutor(
            max_workers=workers,
            thread_name_prefix="gtpu-stream-decode")
        stop = threading.Event()

        def produce(meta, out):
            try:
                for table in self.sst_reader.iter_chunks(
                        meta, self.schema, ts_range, names,
                        tag_predicates=tag_predicates):
                    if stop.is_set():
                        return
                    if not table.num_rows:
                        continue
                    item = ("chunk",
                            (self._decode_sst(table, names),
                             table.num_rows))
                    while not stop.is_set():
                        try:
                            out.put(item, timeout=0.05)
                            break
                        except _queue.Full:
                            continue
            except BaseException as e:  # noqa: BLE001 — shipped in order
                while not stop.is_set():
                    try:
                        out.put(("error", e), timeout=0.05)
                        return
                    except _queue.Full:
                        continue
            finally:
                while not stop.is_set():
                    try:
                        out.put(("end", None), timeout=0.05)
                        return
                    except _queue.Full:
                        continue

        queues: dict[int, _queue.Queue] = {}
        futs = []
        nxt = 0
        try:
            for i in range(len(files)):
                while nxt < len(files) and nxt < i + workers:
                    q = _queue.Queue(maxsize=2)
                    queues[nxt] = q
                    futs.append(scan_pool.submit(pool, produce,
                                                 files[nxt], q))
                    nxt += 1
                q = queues.pop(i)
                while True:
                    try:
                        kind, payload = q.get(timeout=0.1)
                    except _queue.Empty:
                        # deadline checkpoint: a dead consumer unwinds
                        # typed; the finally stops the producers
                        dl.check("streaming scan wait")
                        continue
                    if kind == "end":
                        break
                    if kind == "error":
                        raise payload
                    yield payload
        finally:
            # producers poll `stop` on every put/iteration; wait for
            # every submitted future so no worker touches SST bytes
            # after the caller's unpin
            stop.set()
            for q in queues.values():
                try:
                    while True:
                        q.get_nowait()
                except _queue.Empty:
                    pass
            for f in futs:
                while True:
                    try:
                        f.result(timeout=30)
                        break
                    except _FutTimeout:
                        # a producer wedged in a slow read still holds
                        # SST handles — the caller's unpin MUST wait it
                        # out, or compaction could delete bytes mid-read
                        continue
                    except Exception:  # noqa: BLE001 — already surfaced
                        break
            pool.shutdown(wait=False)

    def _scan_columns(self, projection: Optional[Sequence[str]],
                      full_key: bool = True) -> list[str]:
        """The columns a scan decodes, in schema order: the projection
        and the time index, and with `full_key` every tag column beside
        them — a last-write-wins mask merges by the whole primary key,
        whatever the statement names. The caller asks for the key (see
        `scan`); without it the tags outside the projection are never
        read. Counts those tags, either way, on
        greptimedb_tpu_scan_key_columns_total."""
        if projection is None:
            return self.schema.names
        named = set(projection)
        named.add(self.schema.time_index.name)
        key_only = sum(1 for c in self.schema.tag_columns
                       if c.name not in named)
        if key_only:
            _note_key_columns(key_only, decoded=full_key)
            if full_key:
                named.update(c.name for c in self.schema.tag_columns)
        return [n for n in self.schema.names if n in named]

    def _decode_sst(self, table: pa.Table, names: list[str]) -> dict[str, np.ndarray]:
        cols: dict[str, np.ndarray] = {}
        n = table.num_rows
        for c in self.schema.columns:
            if c.name not in names:
                continue
            if c.name not in table.column_names:
                # column added by ALTER after this SST was written: backfill
                # with the declared default, else NULL (NaN / None / -1 code)
                if c.semantic is SemanticType.TAG:
                    cols[c.name] = np.full(n, -1, dtype=np.int32)
                elif c.dtype.is_string:
                    cols[c.name] = np.full(n, c.default, dtype=object)
                elif c.dtype.is_float:
                    fill = np.nan if c.default is None else float(c.default)
                    cols[c.name] = np.full(n, fill, dtype=c.dtype.to_numpy())
                else:
                    fill = c.default if c.default is not None else 0
                    cols[c.name] = np.full(n, fill, dtype=c.dtype.to_numpy())
                continue
            arr = table.column(c.name)
            if c.semantic is SemanticType.TAG:
                dv = DictVector.from_arrow(
                    arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
                )
                from greptimedb_tpu.datatypes.vector import remap_codes

                mapping = self.registry.remap_dict(c.name, dv.values)
                cols[c.name] = remap_codes(dv.codes, mapping)
            elif c.dtype.is_timestamp:
                cols[c.name] = arr.to_numpy(zero_copy_only=False).astype(np.int64)
            else:
                cols[c.name] = arr.to_numpy(zero_copy_only=False)
        return cols

    # ---- stats -------------------------------------------------------------

    @property
    def num_sst_rows(self) -> int:
        return sum(f.num_rows for f in self.files.values())

    def estimate_rows(self, ts_range=None) -> int:
        """Rows a scan of `ts_range` may return, from metadata only (the
        files and memtable whose extent overlaps it; what
        ScanStream.est_rows counts) — decides streaming without taking
        a snapshot."""
        with self._lock:
            metas = list(self.files.values())
            mem = self.memtable
            mem_rows, mem_lo, mem_hi = mem.num_rows, mem.ts_min, mem.ts_max
        if ts_range is not None:
            metas = [m for m in metas if m.ts_max >= ts_range[0]
                     and m.ts_min < ts_range[1]]
            if mem_lo is not None and (mem_hi < ts_range[0]
                                       or mem_lo >= ts_range[1]):
                mem_rows = 0
        return sum(m.num_rows for m in metas) + mem_rows

    def ts_extent(self) -> Optional[tuple[int, int]]:
        """(min, max) timestamp over SST metas + memtable, or None when
        the region is empty — metadata only, no data read (drives the
        bucket-top-k scan narrowing, physical.py)."""
        with self._lock:
            bounds = [(m.ts_min, m.ts_max) for m in self.files.values()]
            if self.memtable.ts_min is not None:
                bounds.append((self.memtable.ts_min, self.memtable.ts_max))
        if not bounds:
            return None
        return (min(b[0] for b in bounds), max(b[1] for b in bounds))

    def data_identity(self) -> tuple:
        """(incarnation, data_version, ts extent or None), read under
        the lock a scan takes its snapshot under: what a cache built
        from this region's rows is valid for, known without a scan. A
        write acknowledged before this call has moved the version."""
        with self._lock:
            return (self.incarnation, self.data_version, self.ts_extent())

    @property
    def memtable_bytes(self) -> int:
        return self.memtable.bytes_estimate

    @property
    def l0_count(self) -> int:
        """Unmerged flush outputs — the write-stall backpressure signal
        (the reference stalls writers on L0 pressure the same way)."""
        with self._lock:
            return sum(1 for f in self.files.values() if f.level == 0)

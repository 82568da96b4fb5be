"""Pipelined parallel scan (ISSUE 5): concurrent SST decode through the
shared pool, the per-file decoded-part cache under mutation
(flush/compaction/expiry/DELETE/TRUNCATE), typed degradation under
injected objectstore.read faults, upload prefetch double buffering, and
the lastpoint newest-first pruned scan."""

import os

import numpy as np
import pytest

from greptimedb_tpu.datatypes import (
    ColumnSchema,
    DataType,
    DictVector,
    RecordBatch,
    Schema,
    SemanticType,
)
from greptimedb_tpu.storage import RegionEngine, sst
from greptimedb_tpu.storage.engine import EngineConfig


def schema3():
    return Schema([
        ColumnSchema("ts", DataType.TIMESTAMP_MILLISECOND,
                     SemanticType.TIMESTAMP),
        ColumnSchema("host", DataType.STRING, SemanticType.TAG),
        ColumnSchema("v", DataType.FLOAT64),
    ])


def make_batch(schema, hosts, ts, vals):
    return RecordBatch(schema, {
        "ts": np.asarray(ts, dtype=np.int64),
        "host": DictVector.encode(hosts),
        "v": np.asarray(vals, dtype=np.float64),
    })


@pytest.fixture
def engine(tmp_path):
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    yield eng
    eng.close()


def fill_files(engine, rid, n_files=4, rows_per_file=300, hosts=6,
               t0=0):
    """n_files time-disjoint SSTs, every host in every file."""
    schema = engine.region(rid).schema
    for f in range(n_files):
        names = [f"h{i % hosts}" for i in range(rows_per_file)]
        ts = (t0 + f * 1_000_000
              + np.arange(rows_per_file, dtype=np.int64) * 10)
        vals = np.arange(rows_per_file, dtype=np.float64) + f * 1000
        engine.put(rid, make_batch(schema, names, ts, vals))
        engine.flush(rid)


def clear_scan_caches(region):
    with region._lock:
        region._scan_cache.clear()
        region._scan_cache_sizes.clear()
        region._scan_cache_bytes = 0
        region._part_cache.clear()
        region._part_cache_bytes = 0


def scans_equal(a, b) -> bool:
    if a.num_rows != b.num_rows:
        return False
    if a.sorted_part_offsets != b.sorted_part_offsets:
        return False
    for k in a.columns:
        if not np.array_equal(np.asarray(a.columns[k]),
                              np.asarray(b.columns[k])):
            return False
    return (np.array_equal(a.seq, b.seq)
            and np.array_equal(a.op_type, b.op_type))


class TestParallelDecode:
    def test_parallel_matches_sequential_bit_for_bit(self, engine,
                                                     monkeypatch):
        engine.create_region(1, schema3())
        fill_files(engine, 1)
        region = engine.region(1)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
        clear_scan_caches(region)
        seq = engine.scan(1).materialize()
        assert seq.stats["decode_workers"] == 1
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
        clear_scan_caches(region)
        par = engine.scan(1).materialize()
        assert scans_equal(seq, par)
        # ts-ranged and projected scans too
        for kwargs in ({"ts_range": (1_000_000, 2_000_500)},
                       {"projection": ["v"]}):
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
            clear_scan_caches(region)
            a = engine.scan(1, **kwargs)
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
            clear_scan_caches(region)
            b = engine.scan(1, **kwargs)
            assert scans_equal(a, b)

    @pytest.mark.parametrize("kwargs", [
        {}, {"ts_range": (1_000_000, 2_000_500)},
        {"tag_predicates": {"host": {"h1", "h4"}}, "projection": ["host"]},
    ])
    def test_a_scan_without_the_key_decodes_alike_serial_and_parallel(
            self, engine, monkeypatch, kwargs):
        """`full_key=False` (an append-mode table's scan): the parts
        hold the named columns only, the same bytes from one decode
        thread and from four, and they are the full-key scan's columns
        of those names."""
        engine.create_region(1, schema3())
        fill_files(engine, 1)
        region = engine.region(1)
        kwargs = {"projection": ["v"], **kwargs}
        got = []
        for threads in ("1", "4"):
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", threads)
            clear_scan_caches(region)
            got.append(engine.scan(1, full_key=False,
                                   **kwargs).materialize())
        assert "host" not in got[0].columns or "host" in kwargs["projection"]
        assert scans_equal(got[0], got[1])
        full = engine.scan(1, **kwargs).materialize()
        assert set(full.columns) == set(got[0].columns) | {"host"}
        assert all(np.array_equal(col, full.columns[name])
                   for name, col in got[0].columns.items())
        assert np.array_equal(got[0].seq, full.seq)

    def test_decode_pool_actually_exercised(self, engine, monkeypatch):
        """Tier-1 speed guard: a multi-SST region's cold scan must run
        on >1 pool worker — a refactor silently re-serializing the
        path fails here, not in a bench round."""
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=6)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
        region = engine.region(1)
        # a couple of attempts: tiny decodes can legitimately finish on
        # one worker before the second picks a task up
        for _ in range(5):
            clear_scan_caches(region)
            scan = engine.scan(1).materialize()
            if scan.stats["decode_workers"] > 1:
                break
        assert scan.stats["decode_workers"] > 1, scan.stats
        assert scan.stats["files_decoded"] == 6

    def test_single_huge_file_splits_row_groups(self, engine,
                                                monkeypatch):
        """ISSUE 7 carry-over: ONE multi-row-group SST must fan its row
        groups across the pool (order-preserving reassembly) instead of
        serializing the decode stage on a single worker — bit-for-bit
        the single-worker result, ranged/projected scans included."""
        engine.create_region(1, schema3())
        region = engine.region(1)
        region.sst_writer.row_group_size = 100  # 1 flush -> 9 groups
        # ... that a bound of 200 rows a batch cuts into 5 batches
        monkeypatch.setattr(sst, "READ_BATCH_ROWS", 200)
        fill_files(engine, 1, n_files=1, rows_per_file=900)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
        clear_scan_caches(region)
        seq = engine.scan(1).materialize()
        assert seq.stats["decode_workers"] == 1
        assert seq.num_rows == 900
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
        for _ in range(5):
            clear_scan_caches(region)
            par = engine.scan(1).materialize()
            if par.stats["decode_workers"] > 1:
                break
        assert par.stats["decode_workers"] > 1, par.stats
        assert scans_equal(seq, par)
        # ranged + projected parity through the split path too (the
        # exact ts row filter runs per chunk and must reassemble clean)
        for kwargs in ({"ts_range": (2_000, 5_005)},
                       {"projection": ["v"]}):
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
            clear_scan_caches(region)
            a = engine.scan(1, **kwargs)
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
            clear_scan_caches(region)
            b = engine.scan(1, **kwargs)
            assert scans_equal(a, b)

    def test_single_row_group_file_takes_classic_path(self, engine,
                                                      monkeypatch):
        """A one-row-group file has nothing to split: it must decode
        through the classic whole-file read (spies and fault seams on
        SstReader.read keep seeing the pre-split behavior)."""
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=1)
        region = engine.region(1)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
        calls = []
        orig = region.sst_reader.read

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(region.sst_reader, "read", spy)
        clear_scan_caches(region)
        scan = engine.scan(1).materialize()
        assert scan.num_rows == 300
        assert calls, "whole-file read() was bypassed"

    def test_compaction_reads_through_part_cache(self, engine):
        engine.create_region(1, schema3())
        fill_files(engine, 1)
        warm = engine.scan(1).materialize()  # fills per-file parts
        from greptimedb_tpu.utils.metrics import SCAN_PART_CACHE_EVENTS

        before = SCAN_PART_CACHE_EVENTS.get(event="hit")
        engine.compact(1)
        assert SCAN_PART_CACHE_EVENTS.get(event="hit") >= before + 4
        # merged output equals the pre-compaction rows (append region)
        after = engine.scan(1).materialize()
        assert after.num_rows == warm.num_rows


class TestPrunedReadByRowGroup:
    """A pruned read (a window, =/IN tag predicates) goes row group by
    row group and cuts each to the rows it keeps before the next is
    read: the rows, their order, the stats and the cached part are
    those of decoding the whole file and filtering after."""

    @staticmethod
    def region_of(engine, n_files=3):
        engine.create_region(1, schema3())
        region = engine.region(1)
        region.sst_writer.row_group_size = 100  # 900 rows -> 9 groups
        fill_files(engine, 1, n_files=n_files, rows_per_file=900)
        return region

    @staticmethod
    def expected(full, ts_range, hosts):
        names = np.asarray(full.tag_dicts["host"], dtype=object)[
            np.asarray(full.columns["host"])]
        ts = np.asarray(full.columns["ts"])
        keep = np.ones(len(ts), dtype=bool)
        if ts_range is not None:
            keep &= (ts >= ts_range[0]) & (ts < ts_range[1])
        in_window = int(keep.sum())
        if hosts is not None:
            keep &= np.isin(names, sorted(hosts))
        return keep, in_window

    @pytest.mark.parametrize("threads", ["1", "4"])
    @pytest.mark.parametrize("ts_range,hosts", [
        ((1_000_000, 1_004_005), None),
        (None, {"h1"}),
        (None, {"h0", "h5"}),
        ((500, 2_003_000), {"h2", "h3"}),
        ((1_002_000, 1_002_050), {"h4"}),
        ((0, 3_000_000), {"h1", "nobody"}),
    ])
    def test_rows_and_order_are_the_filtered_whole_decode(
            self, engine, monkeypatch, threads, ts_range, hosts):
        region = self.region_of(engine)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", threads)
        full = engine.scan(1).materialize()
        keep, _ = self.expected(full, ts_range, hosts)
        preds = None if hosts is None else {"host": hosts}
        clear_scan_caches(region)
        got = engine.scan(1, ts_range=ts_range, tag_predicates=preds)
        assert got.num_rows == int(keep.sum())
        for k in ("ts", "host", "v"):
            assert np.array_equal(np.asarray(got.columns[k]),
                                  np.asarray(full.columns[k])[keep]), k
        assert np.array_equal(got.seq, full.seq[keep])
        assert np.array_equal(got.op_type, full.op_type[keep])

    @pytest.mark.parametrize("threads", ["1", "4"])
    def test_one_file_splits_its_pruned_groups_alike(self, engine,
                                                     monkeypatch, threads):
        region = self.region_of(engine, n_files=1)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", threads)
        full = engine.scan(1).materialize()
        keep, _ = self.expected(full, (1_000, 8_000), {"h1", "h4"})
        clear_scan_caches(region)
        got = engine.scan(1, ts_range=(1_000, 8_000),
                          tag_predicates={"host": {"h1", "h4"}})
        assert np.array_equal(np.asarray(got.columns["v"]),
                              np.asarray(full.columns["v"])[keep])
        assert np.array_equal(got.seq, full.seq[keep])

    def test_one_batch_is_read_at_a_time_and_parts_cache_cut(
            self, engine, monkeypatch):
        region = self.region_of(engine)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
        # a bound of 100 rows: a batch is one of the 100-row groups
        monkeypatch.setattr(sst, "READ_BATCH_ROWS", 100)
        full = engine.scan(1).materialize()
        ts_range, hosts = (1_000_000, 2_000_000), {"h3"}
        keep, _ = self.expected(full, ts_range, hosts)
        seen = []
        orig = region.sst_reader.iter_batches

        def spy(*a, **k):
            for table in orig(*a, **k):
                seen.append(table.num_rows)
                yield table

        monkeypatch.setattr(region.sst_reader, "iter_batches", spy)
        clear_scan_caches(region)
        got = engine.scan(1, ts_range=ts_range,
                          tag_predicates={"host": hosts})
        assert seen and max(seen) <= 100
        assert got.num_rows == int(keep.sum()) == 150
        # what the read decoded inside the window, before the tag cut:
        # the index left the two 100-row groups that hold h3's 150 rows
        assert seen == [100, 100] and got.stats["rows_prefilter"] == 200
        # the part cache holds the cut parts, not the window's rows
        with region._lock:
            ents = [e for k, e in region._part_cache.items()
                    if k[1] == ts_range and e.part is not None]
        assert sum(len(e.part[1]) for e in ents) == 150
        assert sum(e.rows_read for e in ents) == 200
        # and a second scan of the same shape is served from them
        with region._lock:
            region._scan_cache.clear()
            region._scan_cache_sizes.clear()
            region._scan_cache_bytes = 0
        del seen[:]
        again = engine.scan(1, ts_range=ts_range,
                            tag_predicates={"host": hosts})
        assert not seen and scans_equal(got, again)
        assert again.stats["rows_prefilter"] == 200

    @pytest.mark.parametrize("bound,batches", [(1 << 20, [200]),
                                               (100, [100, 100])])
    def test_scan_rows_total_counts_what_batches_read_and_kept(
            self, engine, monkeypatch, bound, batches):
        """`scan_rows_total{kind}`: `read` = the rows of the batches a
        pruned read decoded, `kept` = the rows it returned; both equal
        the oracle's, whole reads and cache hits count nothing, and the
        `scan` stage's tally says the same."""
        from greptimedb_tpu.storage.region import (
            scan_io_counters,
            scan_io_since,
        )
        from greptimedb_tpu.utils.metrics import SCAN_ROWS

        region = self.region_of(engine)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
        monkeypatch.setattr(sst, "READ_BATCH_ROWS", bound)
        read0, kept0 = (SCAN_ROWS.get(kind=k) for k in ("read", "kept"))
        full = engine.scan(1).materialize()  # a whole read: not counted
        assert SCAN_ROWS.get(kind="read") == read0
        ts_range, hosts = (1_000_000, 2_000_000), {"h3"}
        keep, _ = self.expected(full, ts_range, hosts)
        seen = []
        orig = region.sst_reader.iter_batches

        def spy(*a, **k):
            for table in orig(*a, **k):
                seen.append(table.num_rows)
                yield table

        monkeypatch.setattr(region.sst_reader, "iter_batches", spy)
        clear_scan_caches(region)
        before = scan_io_counters()
        got = engine.scan(1, ts_range=ts_range,
                          tag_predicates={"host": hosts})
        # one file meets the window; the index leaves the two groups
        # that hold h3
        assert seen == batches
        read = SCAN_ROWS.get(kind="read") - read0
        kept = SCAN_ROWS.get(kind="kept") - kept0
        assert read == sum(seen) == 200
        assert kept == got.num_rows == int(keep.sum()) == 150
        assert read >= kept
        assert scan_io_since(before) == {
            "rows_decoded": 200, "rows_read": 200, "rows_kept": 150,
            "batches": len(batches)}
        # served again from the part cache: nothing read, nothing counted
        with region._lock:
            region._scan_cache.clear()
            region._scan_cache_sizes.clear()
            region._scan_cache_bytes = 0
        engine.scan(1, ts_range=ts_range, tag_predicates={"host": hosts})
        assert SCAN_ROWS.get(kind="read") - read0 == 200


class TestBatchedReadAcrossLayouts:
    """Files of today's group size (sst.DEFAULT_ROW_GROUP),
    files written with 2^20-row groups before it, and a region holding
    both: a read takes each file's layout from the file's own footer,
    returns the whole-file decode's rows in its order, never asks
    parquet for more than READ_BATCH_ROWS rows at once, and parses a
    file's footer once."""

    HOSTS = 5
    POINTS = 260_000  # a file: 1.3M rows, 2 groups of 2^20 or many small

    @classmethod
    def region_of(cls, engine, layout):
        engine.create_region(1, schema3())
        region = engine.region(1)
        sizes = {"new": [sst.DEFAULT_ROW_GROUP],
                 "old": [1 << 20],
                 "both": [1 << 20, sst.DEFAULT_ROW_GROUP]}[layout]
        schema = region.schema
        n = cls.HOSTS * cls.POINTS
        for f, size in enumerate(sizes):
            region.sst_writer.row_group_size = size
            ts = f * 10_000_000 + np.repeat(
                np.arange(cls.POINTS, dtype=np.int64) * 10, cls.HOSTS)
            engine.put(1, RecordBatch(schema, {
                "ts": ts,
                "host": DictVector(
                    np.tile(np.arange(cls.HOSTS, dtype=np.int32),
                            cls.POINTS),
                    np.asarray([f"h{i}" for i in range(cls.HOSTS)],
                               dtype=object)),
                "v": np.arange(n, dtype=np.float64) + f,
            }))
            engine.flush(1)
        # one far row: no window below is half the region's span, so
        # none is widened to a whole scan
        engine.put(1, make_batch(schema, ["h0"], [100_000_000], [0.5]))
        engine.flush(1)
        groups = [-(-n // size) for size in sizes] + [1]
        assert [region.sst_reader.file_plan(m.file_id, "ts").group_rows.size
                for m in region.files.values()] == groups
        return region

    @staticmethod
    def spy_reads(monkeypatch):
        """Rows every parquet read asks for, and every footer parse."""
        import pyarrow.parquet as pq

        asked, parsed = [], []
        orig_read = pq.ParquetFile.read_row_groups
        orig_init = pq.ParquetFile.__init__

        def read_row_groups(self, row_groups, *a, **k):
            asked.append(sum(self.metadata.row_group(g).num_rows
                             for g in row_groups))
            return orig_read(self, row_groups, *a, **k)

        def init(self, source, *a, metadata=None, **k):
            if metadata is None:
                parsed.append(1)
            return orig_init(self, source, *a, metadata=metadata, **k)

        monkeypatch.setattr(pq.ParquetFile, "read_row_groups",
                            read_row_groups)
        monkeypatch.setattr(pq.ParquetFile, "__init__", init)
        return asked, parsed

    @pytest.mark.parametrize("threads", ["1", "4"])
    @pytest.mark.parametrize("layout", ["new", "old", "both"])
    def test_rows_order_and_bound_hold_for_every_layout(
            self, engine, monkeypatch, layout, threads):
        region = self.region_of(engine, layout)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", threads)
        full = engine.scan(1).materialize()
        asked, _parsed = self.spy_reads(monkeypatch)
        for ts_range, hosts in [
                ((1_000, 2_500_000), None),        # window-only: > 2^20 rows
                (None, {"h1", "h3"}),              # the index's selection
                ((10_200_000, 10_900_000), {"h4"}),
                ((2_000_000, 12_000_000), {"h0", "nobody"})]:
            keep, _ = TestPrunedReadByRowGroup.expected(full, ts_range,
                                                        hosts)
            preds = None if hosts is None else {"host": hosts}
            clear_scan_caches(region)
            del asked[:]
            got = engine.scan(1, ts_range=ts_range, tag_predicates=preds)
            if not keep.any():
                assert got is None
                continue
            assert asked and max(asked) <= sst.READ_BATCH_ROWS, asked
            assert got.num_rows == int(keep.sum())
            for k in ("ts", "host", "v"):
                assert np.array_equal(
                    np.asarray(got.columns[k]),
                    np.asarray(full.columns[k])[keep]), (k, ts_range)
            assert np.array_equal(got.seq, full.seq[keep])

    @pytest.mark.parametrize("layout", ["new", "old", "both"])
    def test_stream_chunks_are_batches_of_at_most_the_bound(
            self, engine, monkeypatch, layout):
        region = self.region_of(engine, layout)
        full = engine.scan(1).materialize()
        asked, _parsed = self.spy_reads(monkeypatch)
        for threads in ("1", "4"):
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS",
                               threads)
            del asked[:]
            stream = engine.scan_stream(1, ts_range=(0, 200_000_000))
            chunks = list(stream.chunks())
            assert sum(n for _c, n in chunks) == full.num_rows
            assert sorted(asked) == sorted(n for _c, n in chunks)
            assert max(asked) <= sst.READ_BATCH_ROWS
            # a file's groups come in as few batches as the bound
            # allows, not eight groups (or one) at a time: two a large
            # file, one the far row's
            assert len(asked) == 2 * len(region.files) - 1
            assert np.array_equal(
                np.concatenate([c["v"] for c, _n in chunks]),
                np.asarray(full.columns["v"]))

    def test_a_second_plan_parses_nothing_and_delete_drops_the_plan(
            self, engine, monkeypatch):
        region = self.region_of(engine, "both")
        reader = region.sst_reader
        casts = []
        orig = sst._ts_stat
        monkeypatch.setattr(
            sst, "_ts_stat",
            lambda *a: casts.append(1) or orig(*a))
        _asked, parsed = self.spy_reads(monkeypatch)
        reader._plans.clear()
        first = engine.scan(1, ts_range=(0, 10_005_000),
                            tag_predicates={"host": {"h2"}})
        groups = sum(len(fp.group_rows) for fp in reader._plans.values())
        big = [m.file_id for m in region.files.values() if m.num_rows > 1]
        assert set(reader._plans) == set(big)
        # a footer a file, two statistics a group: once
        assert len(parsed) == 2 and len(casts) == 2 * groups > 4
        del parsed[:], casts[:]
        again = engine.scan(1, ts_range=(100, 10_007_000),
                            tag_predicates={"host": {"h2", "h3"}})
        stream = engine.scan_stream(1, ts_range=(100, 10_007_000))
        assert sum(n for _c, n in stream.chunks()) > 0
        assert first.num_rows == 260_000 + 500
        assert again.num_rows == 2 * (260_000 - 10) + 2 * 700
        assert not parsed and not casts
        # the kept plan lives as long as the file
        reader.delete(big[0])
        assert set(reader._plans) == {big[1]}
        assert big[0] not in reader.index_applier._cache


class TestPartCacheMutation:
    def test_parts_survive_unrelated_flush(self, engine):
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=3)
        region = engine.region(1)
        engine.scan(1).materialize()
        assert len(region._part_cache) == 3
        # unrelated flush: a NEW file appears, old entries stay
        engine.put(1, make_batch(region.schema, ["h0"], [99_000_000],
                                 [5.0]))
        engine.flush(1)
        scan = engine.scan(1).materialize()
        assert scan.stats["files_decoded"] == 1
        assert scan.stats["part_hits"] == 3
        # and the incremental assembly is correct
        assert scan.num_rows == 3 * 300 + 1

    @pytest.mark.parametrize("ts_range", [None, (0, 1_500_000)])
    def test_parts_without_the_key_are_entries_of_their_own(self, engine,
                                                            ts_range):
        """A `full_key=False` scan's parts are keyed by the columns
        they hold: the same projection with the key decodes its own
        parts and takes none of these, and each is hit again by its
        own scan."""
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=3)
        region = engine.region(1)
        files = 3  # every file is asked; the window leaves one no rows
        narrow = engine.scan(1, ts_range, ["v"], full_key=False)
        narrow.materialize()
        assert narrow.stats["files_decoded"] == files
        assert {k[2] for k in region._part_cache} == {("ts", "v")}
        wide = engine.scan(1, ts_range, ["v"]).materialize()
        assert wide.stats["files_decoded"] == files
        assert wide.stats["part_hits"] == 0
        assert {k[2] for k in region._part_cache} == {
            ("ts", "v"), ("host", "ts", "v")}
        with region._lock:
            region._scan_cache.clear()
            region._scan_cache_sizes.clear()
            region._scan_cache_bytes = 0
        again = engine.scan(1, ts_range, ["v"], full_key=False)
        again.materialize()
        assert again.stats["part_hits"] == files
        assert again.stats["files_decoded"] == 0
        assert set(again.columns) == {"ts", "v"}

    def test_compaction_invalidates_input_parts(self, engine):
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=3)
        region = engine.region(1)
        engine.scan(1).materialize()
        old_ids = set(region.files)
        engine.compact(1)  # full merge
        cached_files = {k[0] for k in region._part_cache}
        assert not (cached_files & old_ids)
        scan = engine.scan(1).materialize()
        assert scan.num_rows == 3 * 300

    def test_expiry_invalidates_parts(self, engine):
        from greptimedb_tpu.maintenance.retention import run_expiry

        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=3)
        region = engine.region(1)
        engine.scan(1).materialize()
        assert len(region._part_cache) == 3
        # cutoff between file 0 and file 1 (file ts in units of ms)
        ttl_ms = 1
        newest = max(m.ts_max for m in region.files.values())
        res = run_expiry(region, ttl_ms,
                         now_ms=newest - 1_000_000 + ttl_ms)
        assert res["removed"] >= 1
        cached_files = {k[0] for k in region._part_cache}
        assert cached_files <= set(region.files)
        scan = engine.scan(1).materialize()
        assert scan.stats["ssts"] == len(region.files)

    def test_delete_served_from_memtable_delta(self, engine):
        """DELETE writes tombstones to the memtable: cached per-file
        parts stay valid and the scan's memtable delta carries the
        tombstone (LWW dedup applies it downstream)."""
        from greptimedb_tpu.storage.region import OP_DELETE

        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=2)
        region = engine.region(1)
        engine.scan(1).materialize()
        engine.delete(1, make_batch(region.schema, ["h0"], [0], [0.0]))
        scan = engine.scan(1).materialize()
        assert scan.stats["files_decoded"] == 0  # parts reused
        assert (scan.op_type == OP_DELETE).sum() == 1

    def test_truncate_drop_clears_caches(self, engine):
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=2)
        region = engine.region(1)
        engine.scan(1).materialize()
        assert region._part_cache
        from greptimedb_tpu.storage.engine import RegionRequest, RequestType

        engine.handle_request(RegionRequest(RequestType.DROP, 1))
        assert not region._part_cache
        assert not region._scan_cache

    def test_byte_budget_evicts_lru(self, engine):
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=4)
        region = engine.region(1)
        full = engine.scan(1).materialize()
        one_part = region._part_cache[next(iter(region._part_cache))]
        # budget for ~2 parts: older entries must age out
        region.part_cache_budget = one_part.nbytes * 2 + 1
        from greptimedb_tpu.utils.metrics import SCAN_PART_CACHE_EVENTS

        before = SCAN_PART_CACHE_EVENTS.get(event="evict")
        clear_scan_caches(region)
        scan = engine.scan(1).materialize()
        assert SCAN_PART_CACHE_EVENTS.get(event="evict") > before
        assert region._part_cache_bytes <= region.part_cache_budget
        assert scan.num_rows == full.num_rows  # eviction never drops rows

    def test_snapshot_and_parts_share_one_budget(self, engine):
        """ISSUE-6 satellite (ROADMAP carry-over): the whole-scan
        snapshot is a concat COPY of the parts — accounting them
        separately double-counted host RAM. Both draw on
        part_cache_budget; when a snapshot lands, cold parts age out so
        the SHARED total fits (the newest snapshot itself is exempt:
        bounded overshoot beats re-decoding the live table)."""
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=4)
        region = engine.region(1)
        engine.scan(1).materialize()
        assert region._scan_cache_bytes > 0  # snapshots are accounted
        assert region._host_cache_bytes == (region._part_cache_bytes
                                            + region._scan_cache_bytes)
        # budget below one snapshot: every cold part must age out, the
        # newest snapshot (still exempt) is the only resident entry
        region.part_cache_budget = max(1, region._scan_cache_bytes // 2)
        clear_scan_caches(region)
        region._scan_cache_sizes.clear()
        region._scan_cache_bytes = 0
        scan = engine.scan(1).materialize()
        assert scan.num_rows == 1200
        assert not region._part_cache
        assert len(region._scan_cache) == 1
        # dropping the snapshot returns its bytes
        with region._lock:
            region._scan_cache.clear()
            region._scan_cache_sizes.clear()
            region._scan_cache_bytes = 0
        assert region._host_cache_bytes == 0


@pytest.mark.chaos
class TestFaultedDecode:
    def test_read_fault_degrades_typed_and_unpins(self, engine,
                                                  monkeypatch):
        from greptimedb_tpu.fault import FAULTS, Fault, FaultError

        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=4)
        region = engine.region(1)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
        clear_scan_caches(region)
        # retries exhaust: every read of one schedule's window fails
        FAULTS.arm("objectstore.read", Fault(kind="fail", prob=1.0))
        try:
            with pytest.raises(FaultError):
                engine.scan(1).materialize()
        finally:
            FAULTS.disarm("objectstore.read")
        # pin discipline: every worker finished before the unpin; no
        # file is left pinned by the failed scan
        assert not region._file_refs
        # disarmed: the same scan succeeds (and decodes all files)
        clear_scan_caches(region)
        scan = engine.scan(1).materialize()
        assert scan.stats["files_decoded"] == 4

    def test_latency_fault_keeps_results_identical(self, engine,
                                                   monkeypatch):
        from greptimedb_tpu.fault import FAULTS, Fault

        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=4)
        region = engine.region(1)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
        clear_scan_caches(region)
        oracle = engine.scan(1).materialize()
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
        FAULTS.arm("objectstore.read",
                   Fault(kind="latency", arg=0.01, prob=0.5, seed=7))
        try:
            clear_scan_caches(region)
            jittered = engine.scan(1).materialize()
        finally:
            FAULTS.disarm("objectstore.read")
        assert scans_equal(oracle, jittered)


class TestScanLast:
    def test_visits_only_newest_needed(self, engine, monkeypatch):
        # threads=1 -> decode waves of one file: the stop condition is
        # checked after every file, so exactly ONE file is visited
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=4)  # every host in every file
        scan = engine.scan_last(1, "host")
        assert scan is not None
        assert scan.stats["lastpoint_visited"] == 1
        assert scan.stats["ssts"] == 4

    def test_series_only_in_old_file_forces_deeper_visit(self, engine):
        engine.create_region(1, schema3())
        region = engine.region(1)
        s = region.schema
        engine.put(1, make_batch(s, ["h_old"], [100], [1.0]))
        engine.flush(1)
        fill_files(engine, 1, n_files=2, t0=1_000_000)
        scan = engine.scan_last(1, "host")
        # h_old only exists in the oldest file: every file visited
        assert scan.stats["lastpoint_visited"] == 3
        codes = np.asarray(scan.columns["host"])
        d = region.registry.dict_array("host")
        assert "h_old" in set(d[codes[codes >= 0]])

    def test_matches_full_scan_winners(self, engine):
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=3)
        region = engine.region(1)
        full = engine.scan(1).materialize()
        pruned = engine.scan_last(1, "host")
        ts_f = np.asarray(full.columns["ts"])
        ts_p = np.asarray(pruned.columns["ts"])
        for c in range(region.registry.cardinality("host")):
            mf = np.asarray(full.columns["host"]) == c
            mp = np.asarray(pruned.columns["host"]) == c
            assert ts_f[mf].max() == ts_p[mp].max()

    def test_tombstone_falls_back(self, engine):
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=2)
        region = engine.region(1)
        # delete the NEWEST instant of h0: the tombstone could BE the
        # winner, so the pruned path must refuse — from the memtable...
        newest = max(m.ts_max for m in region.files.values())
        engine.delete(1, make_batch(region.schema, ["h0"], [newest],
                                    [0.0]))
        assert engine.scan_last(1, "host") is None
        engine.flush(1)  # ...and from the (now newest) SST
        assert engine.scan_last(1, "host") is None

    def test_tombstone_in_irrelevant_old_file_keeps_pruning(self, engine):
        """A tombstone whose file the stop condition proves irrelevant
        (every series has a strictly newer candidate) does NOT void
        the pruned path."""
        engine.create_region(1, schema3())
        region = engine.region(1)
        s = region.schema
        engine.put(1, make_batch(s, ["h0", "h1"], [10, 20], [1.0, 2.0]))
        engine.delete(1, make_batch(s, ["h0"], [10], [1.0]))
        engine.flush(1)  # old file with a ts=10 tombstone
        fill_files(engine, 1, n_files=2, t0=1_000_000, hosts=2)
        scan = engine.scan_last(1, "host")
        assert scan is not None
        # terminated before reaching the tombstone file
        assert scan.stats["lastpoint_visited"] < scan.stats["ssts"]

    def test_null_tag_group_blocks_early_stop(self, engine):
        """A NULL-host row only in an OLD file: FileMeta.null_tags
        must force the visit deep enough that the NULL group's winner
        is in the result."""
        engine.create_region(1, schema3())
        region = engine.region(1)
        s = region.schema
        engine.put(1, make_batch(s, [None, "h0"], [100, 110],
                                 [1.0, 2.0]))
        engine.flush(1)
        fill_files(engine, 1, n_files=2, t0=1_000_000)
        scan = engine.scan_last(1, "host")
        assert scan.stats["lastpoint_visited"] == 3
        codes = np.asarray(scan.columns["host"])
        assert (codes < 0).any()  # the NULL row made it into the set


class TestUploadPrefetch:
    def test_prefetch_builds_and_get_joins(self):
        import time

        import jax.numpy as jnp

        from greptimedb_tpu.query.device_cache import DeviceCache

        cache = DeviceCache(budget_bytes=1 << 24)
        built = []

        def mk(i):
            def build():
                time.sleep(0.005)
                built.append(i)
                return jnp.arange(16) + i
            return build

        cache.prefetch(("blk", 1), mk(1))
        cache.prefetch(("blk", 1), mk(1))  # dedup: no double build
        a = cache.get(("blk", 1), mk(1))
        assert int(a[0]) == 1
        assert built == [1]
        assert cache.prefetch_issued == 1
        # a failing prefetch degrades to the inline build
        def boom():
            raise RuntimeError("prefetch build failed")

        cache.prefetch(("blk", 2), boom)
        b = cache.get(("blk", 2), mk(2))
        assert int(b[0]) == 2

    def test_prefetch_disabled_by_env(self, monkeypatch):
        from greptimedb_tpu.query.device_cache import (
            upload_prefetch_enabled,
        )

        assert upload_prefetch_enabled()
        monkeypatch.setenv("GREPTIMEDB_TPU_UPLOAD_PREFETCH", "0")
        assert not upload_prefetch_enabled()


class TestStreamAndSeqMinParallel:
    """ISSUE-6 satellite: scan_stream and the seq_min slice ride the
    decode pool too — bit-for-bit parity vs the serial path."""

    def _stream_chunks(self, engine, rid, **kwargs):
        stream = engine.scan_stream(rid, **kwargs)
        assert stream is not None
        out = []
        try:
            for cols, n in stream.chunks():
                out.append(({k: np.asarray(v).copy()
                             for k, v in cols.items()}, n))
        finally:
            stream.close()
        return out

    @staticmethod
    def _chunks_equal(a, b):
        if [n for _, n in a] != [n for _, n in b]:
            return False
        for (ca, _), (cb, _) in zip(a, b):
            if set(ca) != set(cb):
                return False
            for k in ca:
                if not np.array_equal(ca[k], cb[k]):
                    return False
        return True

    def test_scan_stream_parallel_matches_serial_bit_for_bit(
            self, engine, monkeypatch):
        """Chunk ORDER matters, not just content: the parallel pipeline
        must emit file order, chunk order within a file — exactly the
        serial loop's sequence."""
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=6)
        for kwargs in ({}, {"ts_range": (1_000_000, 4_000_500)},
                       {"projection": ["v"]}):
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
            serial = self._stream_chunks(engine, 1, **kwargs)
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
            par = self._stream_chunks(engine, 1, **kwargs)
            assert self._chunks_equal(serial, par), kwargs
        assert sum(n for _, n in serial) > 0

    def test_scan_stream_memtable_tail_after_parallel_files(
            self, engine, monkeypatch):
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=4)
        schema = engine.region(1).schema
        # unflushed rows ride the stream's tail chunk
        engine.put(1, make_batch(schema, ["h9"] * 3,
                                 [9_000_000, 9_000_010, 9_000_020],
                                 [1.0, 2.0, 3.0]))
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
        serial = self._stream_chunks(engine, 1)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
        par = self._stream_chunks(engine, 1)
        assert self._chunks_equal(serial, par)

    def test_scan_stream_abandoned_midway_unpins(self, engine,
                                                 monkeypatch):
        """Abandoning a parallel stream must stop the producers and
        release every file pin (the compaction path depends on it)."""
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=6)
        import time

        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
        region = engine.region(1)
        stream = engine.scan_stream(1)
        it = stream.chunks()
        next(it)  # consume one chunk, then walk away
        it.close()
        stream.close()
        deadline = time.time() + 10
        while time.time() < deadline:
            with region._lock:
                if not region._file_refs:
                    return
            time.sleep(0.01)
        raise AssertionError("file pins leaked after abandoned stream")

    def test_seq_min_parallel_matches_serial_bit_for_bit(
            self, engine, monkeypatch):
        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=5)
        region = engine.region(1)
        full = engine.scan(1).materialize()
        boundaries = [0, int(full.seq.min()),
                      int(np.median(full.seq)), int(full.seq.max())]
        for s in boundaries:
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "1")
            clear_scan_caches(region)
            serial = engine.scan(1, seq_min=s)
            monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
            clear_scan_caches(region)
            par = engine.scan(1, seq_min=s)
            if serial is None or par is None:
                assert serial is None and par is None, s
                continue
            assert scans_equal(serial, par), s

    def test_seq_min_rides_the_part_cache(self, engine, monkeypatch):
        """A boundary-straddling file decodes ONCE, not once per tick:
        the second seq_min scan over the same files is all part-cache
        hits, and the seq filter applies on copies (a later FULL scan
        still sees every row)."""
        from greptimedb_tpu.utils.metrics import SCAN_PART_CACHE_EVENTS

        engine.create_region(1, schema3())
        fill_files(engine, 1, n_files=3)
        monkeypatch.setenv("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "4")
        region = engine.region(1)
        clear_scan_caches(region)
        first = engine.scan(1, seq_min=1)
        hits0 = SCAN_PART_CACHE_EVENTS.get(event="hit")
        miss0 = SCAN_PART_CACHE_EVENTS.get(event="miss")
        again = engine.scan(1, seq_min=1)
        assert SCAN_PART_CACHE_EVENTS.get(event="hit") > hits0
        assert SCAN_PART_CACHE_EVENTS.get(event="miss") == miss0
        assert scans_equal(first, again)
        full = engine.scan(1).materialize()
        assert full.num_rows == 900  # cached parts stayed whole


@pytest.mark.chaos
def test_process_cluster_parallel_decode_parity(tmp_path):
    """Acceptance (ISSUE 5): over a live ProcessCluster with
    objectstore.read latency chaos injected in the datanode children,
    query results are bit-for-bit identical between decode_threads=1
    and the default parallel pool. The two clusters replay the same
    seeded fault schedule (GTPU_CHAOS_SEED)."""
    import time

    from greptimedb_tpu.cluster.process_cluster import ProcessCluster
    from greptimedb_tpu.meta.metasrv import MetasrvOptions

    def run(threads: str, root: str):
        old = {
            k: os.environ.get(k)
            for k in ("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", "GTPU_CHAOS",
                      "GTPU_CHAOS_SEED")
        }
        os.environ["GREPTIMEDB_TPU_SCAN_DECODE_THREADS"] = threads
        os.environ["GTPU_CHAOS"] = \
            "objectstore.read=latency,arg:0.005,prob:0.3"
        os.environ["GTPU_CHAOS_SEED"] = "1234"
        c = None
        try:
            c = ProcessCluster(root, num_datanodes=2,
                               opts=MetasrvOptions())
            c.beat_all(time.time() * 1000)
            c.sql("CREATE TABLE m (host STRING, v DOUBLE, ts TIMESTAMP "
                  "TIME INDEX, PRIMARY KEY(host))")
            for f in range(3):
                vals = ", ".join(
                    f"('h{i % 5}', {f * 100 + i}.5, {f * 10_000 + i})"
                    for i in range(50))
                c.sql(f"INSERT INTO m VALUES {vals}")
                info = c.catalog.table("public", "m")
                for rid in info.region_ids:
                    c.router.flush(rid)
            rows = c.sql(
                "SELECT host, count(*), sum(v), max(ts) FROM m "
                "GROUP BY host ORDER BY host").rows()
            raw = c.sql("SELECT host, v, ts FROM m "
                        "ORDER BY host, ts").rows()
            return rows, raw
        finally:
            if c is not None:
                c.close()
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    seq = run("1", str(tmp_path / "seq"))
    par = run("0", str(tmp_path / "par"))
    assert seq == par

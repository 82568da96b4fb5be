"""TWCS compaction + inverted index tests (reference compaction/twcs.rs and
index/inverted_index tests analog)."""

import numpy as np
import pytest

from greptimedb_tpu.catalog.catalog import Catalog
from greptimedb_tpu.catalog.kv import MemoryKv
from greptimedb_tpu.query.engine import QueryEngine
from greptimedb_tpu.storage.compaction import TwcsOptions, TwcsPicker, infer_time_window_ms
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine
from greptimedb_tpu.storage.index import IndexApplier, extract_tag_predicates
from greptimedb_tpu.storage.sst import FileMeta
from greptimedb_tpu.sql import parse_sql

HOUR_MS = 3_600_000


def fm(i, ts_min, ts_max, level=0):
    return FileMeta(file_id=f"f{i}", num_rows=100, ts_min=ts_min, ts_max=ts_max,
                    max_seq=i, level=level)


class TestTwcsPicker:
    def test_no_compaction_under_limits(self):
        picker = TwcsPicker(TwcsOptions(time_window_ms=HOUR_MS))
        files = [fm(1, 0, 100), fm(2, 100, 200)]  # 2 files, active window, limit 4
        assert picker.pick(files) == []

    def test_active_window_compacts_over_limit(self):
        picker = TwcsPicker(TwcsOptions(time_window_ms=HOUR_MS,
                                        max_active_window_files=2))
        files = [fm(i, 0, 1000 + i) for i in range(4)]
        groups = picker.pick(files)
        assert len(groups) == 1
        assert len(groups[0]) == 4

    def test_inactive_window_compacts_at_two(self):
        picker = TwcsPicker(TwcsOptions(time_window_ms=HOUR_MS))
        old = [fm(1, 0, 100), fm(2, 50, 200)]  # window 0
        active = [fm(3, 2 * HOUR_MS, 2 * HOUR_MS + 10)]  # window 2
        groups = picker.pick(old + active)
        assert len(groups) == 1
        assert {f.file_id for f in groups[0]} == {"f1", "f2"}

    def test_window_inference(self):
        files = [fm(1, 0, 30 * 60 * 1000)]  # 30min span -> 1h bucket
        assert infer_time_window_ms(files) == HOUR_MS
        files = [fm(1, 0, 5 * 24 * HOUR_MS)]  # 5d span -> 7d bucket
        assert infer_time_window_ms(files) == 7 * 24 * HOUR_MS


@pytest.fixture
def qe(tmp_path):
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path)))
    q = QueryEngine(Catalog(MemoryKv()), engine)
    q.execute_one(
        "CREATE TABLE cpu (host STRING, usage DOUBLE, ts TIMESTAMP TIME INDEX, "
        "PRIMARY KEY(host))"
    )
    yield q
    engine.close()


def region_of(qe, name="cpu"):
    info = qe.catalog.table("public", name)
    return qe.region_engine.region(info.region_ids[0])


class TestRegionCompaction:
    def test_twcs_merges_same_window(self, qe):
        # 3 flushes in the same hour window + overflow threshold
        for i in range(5):
            qe.execute_one(
                f"INSERT INTO cpu (host, usage, ts) VALUES ('h{i}', {i}.0, {1000 + i})"
            )
            region_of(qe).flush()
        region = region_of(qe)
        assert len(region.files) == 5
        out = region.compact()
        assert len(out) == 1
        assert len(region.files) == 1
        assert list(region.files.values())[0].level == 1
        res = qe.execute_one("SELECT count(*) FROM cpu")
        assert res.rows()[0][0] == 5

    def test_windowed_compaction_preserves_lww(self, qe):
        # same key written twice across files: winner must survive the merge
        qe.execute_one("INSERT INTO cpu (host, usage, ts) VALUES ('a', 1.0, 1000)")
        region_of(qe).flush()
        qe.execute_one("INSERT INTO cpu (host, usage, ts) VALUES ('a', 9.0, 1000)")
        region_of(qe).flush()
        for i in range(3):
            qe.execute_one(
                f"INSERT INTO cpu (host, usage, ts) VALUES ('b', {i}.0, {2000 + i})"
            )
            region_of(qe).flush()
        region_of(qe).compact()
        res = qe.execute_one("SELECT usage FROM cpu WHERE host = 'a'")
        assert res.rows() == [[9.0]]

    def test_partial_compaction_keeps_tombstones(self, qe):
        # put in file A (old window), delete in file B+C (new window);
        # compacting only B+C must not lose the tombstone
        qe.execute_one("INSERT INTO cpu (host, usage, ts) VALUES ('a', 1.0, 1000)")
        region = region_of(qe)
        region.flush()
        qe.execute_one("DELETE FROM cpu WHERE host = 'a'")
        region.flush()
        qe.execute_one("INSERT INTO cpu (host, usage, ts) VALUES ('b', 2.0, 2000)")
        region.flush()
        # merge only the last two files (partial group)
        group = sorted(region.files.values(), key=lambda f: f.max_seq)[1:]
        region._merge_files(group)
        res = qe.execute_one("SELECT host FROM cpu ORDER BY host")
        assert res.rows() == [["b"]]  # 'a' stays deleted

    def test_full_compaction_drops_tombstones(self, qe):
        qe.execute_one("INSERT INTO cpu (host, usage, ts) VALUES ('a', 1.0, 1000)")
        region = region_of(qe)
        region.flush()
        qe.execute_one("DELETE FROM cpu WHERE host = 'a'")
        region.flush()
        region.compact(strategy="full")
        assert len(region.files) == 1
        res = qe.execute_one("SELECT count(*) FROM cpu")
        assert res.rows()[0][0] == 0
        # the merged file physically contains no tombstone rows
        meta = list(region.files.values())[0]
        assert meta.num_rows == 0 or meta.num_rows == 1  # winner-only content


class TestMergeAgainstAModel:
    """The merge of a compaction's sorted runs on the host, held to a
    dict that applies the same writes in order: last write wins per
    (series, ts), a winning tombstone hides the row, and a partial
    group keeps its tombstones for the files outside it."""

    @pytest.mark.parametrize("partial", [False, True])
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_merge_is_last_write_wins(self, tmp_path, seed, partial):
        engine = RegionEngine(EngineConfig(data_dir=str(tmp_path)))
        q = QueryEngine(Catalog(MemoryKv()), engine)
        q.execute_one(
            "CREATE TABLE m (host STRING, dc STRING, v DOUBLE, "
            "ts TIMESTAMP TIME INDEX, PRIMARY KEY(host, dc))")
        try:
            rng = np.random.default_rng(seed)
            region = region_of(q, "m")
            model = {}
            for f in range(6):
                rows = {}
                for _ in range(40):
                    key = (f"h{rng.integers(5)}", f"d{rng.integers(3)}",
                           1000 + int(rng.integers(12)))
                    rows[key] = float(rng.integers(1000))
                q.execute_one(
                    "INSERT INTO m (host, dc, v, ts) VALUES " + ", ".join(
                        f"('{h}', '{d}', {v}, {ts})"
                        for (h, d, ts), v in rows.items()))
                model.update(rows)
                if f in (2, 4):
                    gone = f"h{rng.integers(5)}"
                    q.execute_one(f"DELETE FROM m WHERE host = '{gone}'")
                    model = {k: v for k, v in model.items() if k[0] != gone}
                region.flush()
            files = sorted(region.files.values(), key=lambda x: x.max_seq)
            assert len(files) == 6
            if partial:
                # the newest four only: the first two stay beside them
                region._merge_files(files[2:])
                assert len(region.files) == 3
            else:
                region.compact(strategy="full")
                assert len(region.files) == 1
                merged = list(region.files.values())[0]
                assert merged.num_rows == len(model)
            got = q.execute_one(
                "SELECT host, dc, ts, v FROM m ORDER BY host, dc, ts").rows()
            want = [[h, d, ts, v] for (h, d, ts), v in sorted(model.items())]
            assert [[r[0], r[1], int(r[2]), r[3]] for r in got] == want
        finally:
            engine.close()


class TestInvertedIndex:
    def test_index_prunes_row_groups(self, tmp_path):
        engine = RegionEngine(EngineConfig(data_dir=str(tmp_path)))
        qe = QueryEngine(Catalog(MemoryKv()), engine)
        qe.execute_one(
            "CREATE TABLE t (host STRING, v DOUBLE, ts TIMESTAMP TIME INDEX, "
            "PRIMARY KEY(host))"
        )
        region = region_of(qe, "t")
        region.sst_writer.row_group_size = 8  # force multiple row groups
        rows = []
        for h in range(4):
            for i in range(8):
                rows.append(f"('host{h}', 1.0, {h * 1_000_000 + i})")
        qe.execute_one("INSERT INTO t (host, v, ts) VALUES " + ",".join(rows))
        region.flush()
        meta = list(region.files.values())[0]
        applier = region.sst_reader.index_applier
        # host0 lives in exactly one of 4 row groups (data sorted by host)
        groups = applier.apply(meta.file_id, {"host": {"host0"}})
        assert groups == [0]
        assert applier.apply(meta.file_id, {"host": {"host3"}}) == [3]
        assert applier.apply(meta.file_id, {"host": {"nope"}}) == []
        # scan path returns the pruned subset but correct results
        scan = region.scan(tag_predicates={"host": {"host0"}})
        assert scan.num_rows == 8
        res = qe.execute_one("SELECT count(*) FROM t WHERE host = 'host0'")
        assert res.rows()[0][0] == 8
        engine.close()

    def test_extract_tag_predicates(self, qe):
        info = qe.catalog.table("public", "cpu")
        from greptimedb_tpu.storage.index import InSet

        sel = parse_sql("SELECT * FROM cpu WHERE host = 'a' AND ts > 5")[0]
        preds = extract_tag_predicates(sel.where, info.schema)
        assert preds == {"host": (InSet.of(["a"]),)}
        sel = parse_sql("SELECT * FROM cpu WHERE host IN ('a', 'b')")[0]
        preds = extract_tag_predicates(sel.where, info.schema)
        assert preds == {"host": (InSet.of(["a", "b"]),)}
        # OR is not restrictive -> no predicates
        sel = parse_sql("SELECT * FROM cpu WHERE host = 'a' OR usage > 1")[0]
        assert extract_tag_predicates(sel.where, info.schema) == {}

"""Incremental aggregation (ISSUE 13): the per-part partial-aggregate
cache — bit-for-bit parity vs the classic whole-scan paths, delta-only
folding after flushes and late writes, every invalidation seam
(compaction swap, TTL expiry, TRUNCATE incarnation reset, DELETE
tombstone fallback), the typed ineligibility fallbacks, the cluster
fragment-plane memo, the mesh placement, and a 2-dn ProcessCluster
failover run proving no stale partial is ever served."""

import os
import time

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import partial_cache as pc
from greptimedb_tpu.query.engine import QueryContext, QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig


@pytest.fixture(autouse=True)
def _fresh_cache():
    from greptimedb_tpu.query import physical as ph

    pc.global_cache().clear()
    ph._PARTIAL_DISABLED["flag"] = False
    yield
    pc.global_cache().clear()


@pytest.fixture
def db(tmp_path):
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    yield eng, qe
    eng.close()


CTX = QueryContext()


def mk(qe, name="cpu", append=True):
    extra = " WITH (append_mode='true')" if append else ""
    qe.execute_one(
        f"CREATE TABLE {name} (ts TIMESTAMP(3) TIME INDEX, host STRING, "
        f"v DOUBLE, w DOUBLE, PRIMARY KEY(host)){extra}", CTX)
    return qe.catalog.table("public", name).region_ids[0]


def fill(qe, eng, rid, name="cpu", files=3, rows=120, mem=40, t0=0,
         hosts=5, vbase=0.0):
    """files flushed SSTs with disjoint ts ranges + a memtable tail."""
    f = -1
    for f in range(files):
        vals = ", ".join(
            f"({t0 + f * 1_000_000 + i * 10}, 'h{i % hosts}', "
            f"{vbase + f * 100 + i}, {float(i % 7)})"
            for i in range(rows))
        qe.execute_one(f"INSERT INTO {name} VALUES {vals}", CTX)
        eng.flush(rid)
    if mem:
        vals = ", ".join(
            f"({t0 + (f + 1) * 1_000_000 + i * 10}, 'h{i % hosts}', "
            f"{vbase + i}, {float(i % 5)})"
            for i in range(mem))
        qe.execute_one(f"INSERT INTO {name} VALUES {vals}", CTX)


def run_both(qe, sql):
    """(classic result, incremental result, stats) — classic = partial
    cache disabled."""
    os.environ["GREPTIMEDB_TPU_PARTIAL_CACHE"] = "off"
    try:
        classic = qe.execute_one(sql, CTX)
    finally:
        os.environ.pop("GREPTIMEDB_TPU_PARTIAL_CACHE", None)
    inc = qe.execute_one(sql, CTX)
    return classic, inc, qe.executor.last_partial_stats


def assert_same(a, b):
    assert a.names == b.names
    for ca, cb in zip(a.columns, b.columns):
        ca, cb = np.asarray(ca), np.asarray(cb)
        if ca.dtype.kind == "f" or cb.dtype.kind == "f":
            np.testing.assert_array_equal(
                ca.astype(float), cb.astype(float))
        else:
            assert list(ca) == list(cb)


AGG_SQL = ("SELECT host, sum(v), count(v), avg(v), min(v), max(w) "
           "FROM cpu GROUP BY host ORDER BY host")


class TestParity:
    @pytest.mark.parametrize("sql", [
        AGG_SQL,
        "SELECT host, first(v), last(v) FROM cpu WHERE w >= 1 "
        "GROUP BY host ORDER BY host",
        "SELECT count(*), sum(v), stddev(v) FROM cpu",
        "SELECT date_bin(INTERVAL '1 second', ts) AS sec, max(v) "
        "FROM cpu WHERE host = 'h1' GROUP BY sec ORDER BY sec",
        "SELECT host, avg(v) FROM cpu WHERE ts >= 500000 "
        "GROUP BY host HAVING avg(v) > 0 ORDER BY host",
    ])
    def test_bitwise_vs_classic_and_warm(self, db, sql):
        """Cold incremental == classic == warm repeat, bit for bit, for
        the dense aggregate surface (sum/count/avg/min/max, first/last,
        global, bucketed + WHERE, HAVING)."""
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid)
        classic, cold, cold_stats = run_both(qe, sql)
        assert qe.executor.last_path == "incremental"
        assert cold_stats["part_misses"] == 3
        warm = qe.execute_one(sql, CTX)
        warm_stats = qe.executor.last_partial_stats
        assert warm_stats["part_hits"] == 3
        assert warm_stats["part_misses"] == 0
        assert_same(classic, cold)
        assert_same(cold, warm)

    def test_lww_disjoint_parts_eligible(self, db):
        """A non-append (LWW) table with disjoint part ts extents and
        in-part duplicate instants rides the cache: dedup is provably
        part-local, and the sliced mask reproduces LWW exactly."""
        eng, qe = db
        rid = mk(qe, name="lww", append=False)
        for f in range(3):
            vals = []
            for i in range(80):
                vals.append(f"({f * 100000 + i * 10}, 'h{i % 4}', "
                            f"{f * 100 + i}, 0.0)")
                if i % 9 == 0:  # duplicate instant: LWW must pick this
                    vals.append(f"({f * 100000 + i * 10}, 'h{i % 4}', "
                                f"{f * 100 + i + 5000}, 0.0)")
            qe.execute_one("INSERT INTO lww VALUES " + ", ".join(vals),
                           CTX)
            eng.flush(rid)
        sql = ("SELECT host, sum(v), max(v), last(v) FROM lww "
               "GROUP BY host ORDER BY host")
        classic, inc, stats = run_both(qe, sql)
        assert qe.executor.last_path == "incremental"
        assert_same(classic, inc)
        # a late write INSIDE an old part's extent voids disjointness:
        # the whole-scan mask is made first, and a part that lost a row
        # to it keys its partial by the rows it lost (PR 38) — still the
        # cache's path, still correct
        qe.execute_one("INSERT INTO lww VALUES (15, 'h0', 999, 0.0)",
                       CTX)
        classic2, inc2, _ = run_both(qe, sql)
        assert qe.executor.last_path == "incremental"
        assert_same(classic2, inc2)
        # the late write REPEATS an instant of the first file: that
        # part's cached partial must not answer for the row that lost
        qe.execute_one("INSERT INTO lww VALUES (10, 'h1', 777, 0.0)",
                       CTX)
        classic3, inc3, _ = run_both(qe, sql)
        assert qe.executor.last_path == "incremental"
        assert_same(classic3, inc3)
        assert classic3.rows() != classic2.rows()


class TestDeltaFold:
    def test_warm_folds_only_memtable(self, db):
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=40)
        qe.execute_one(AGG_SQL, CTX)
        warm = qe.execute_one(AGG_SQL, CTX)
        st = qe.executor.last_partial_stats
        assert st["part_hits"] == 3
        assert st["delta_rows"] == st["memtable_rows"] == 40
        assert st["cached_rows"] == st["total_rows"] - 40

    def test_post_flush_folds_only_new_file(self, db):
        """A flush turns the memtable into file 4; the next query must
        compute ONE new part and serve 3 from cache."""
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=40)
        classic0, _, _ = run_both(qe, AGG_SQL)
        eng.flush(rid)
        inc = qe.execute_one(AGG_SQL, CTX)
        st = qe.executor.last_partial_stats
        assert st["part_hits"] == 3
        assert st["part_misses"] == 1
        assert st["memtable_rows"] == 0
        assert st["delta_rows"] == 40
        assert_same(classic0, inc)  # flush must not change the answer

    def test_late_write_memtable_delta(self, db):
        """Late rows (new disjoint window) ride the memtable delta and
        never invalidate the cached parts."""
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=0)
        qe.execute_one(AGG_SQL, CTX)
        vals = ", ".join(f"(9{i:06d}, 'h{i % 5}', {i}, 1.0)"
                         for i in range(25))
        qe.execute_one(f"INSERT INTO cpu VALUES {vals}", CTX)
        classic, inc, st = run_both(qe, AGG_SQL)
        assert st["part_hits"] == 3
        assert st["delta_rows"] == 25
        assert_same(classic, inc)


class TestInvalidationSeams:
    def test_compaction_swap(self, db):
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=0)
        qe.execute_one(AGG_SQL, CTX)
        assert len(pc.global_cache().part_keys(rid)) == 3
        eng.compact(rid)
        # old files' partials died with their files
        assert pc.global_cache().part_keys(rid) == []
        classic, inc, st = run_both(qe, AGG_SQL)
        assert st["part_misses"] >= 1
        assert_same(classic, inc)

    def test_ttl_expiry(self, db):
        from greptimedb_tpu.maintenance.retention import run_expiry

        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=0)
        qe.execute_one(AGG_SQL, CTX)
        before = len(pc.global_cache().part_keys(rid))
        assert before == 3
        region = eng.region(rid)
        # expire everything older than the newest file's window
        newest = max(m.ts_max for m in region.files.values())
        horizon = int(time.time() * 1000) - newest + 500_000
        out = run_expiry(region, ttl_ms=horizon)
        assert out.get("removed", 0) >= 1
        keys_left = pc.global_cache().part_keys(rid)
        assert len(keys_left) < before
        classic, inc, _ = run_both(qe, AGG_SQL)
        assert_same(classic, inc)

    def test_truncate_incarnation_reset(self, db):
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=0)
        warm0 = qe.execute_one(AGG_SQL, CTX)
        assert qe.executor.last_partial_stats["parts"] == 3
        qe.execute_one("TRUNCATE TABLE cpu", CTX)
        info = qe.catalog.table("public", "cpu")
        rid2 = info.region_ids[0]
        # re-ingest DIFFERENT values into the recreated region
        fill(qe, eng, rid2, files=2, rows=60, mem=0, vbase=7777.0)
        classic, inc, _ = run_both(qe, AGG_SQL)
        assert_same(classic, inc)
        # a stale pre-truncate partial would leak the old sums
        assert not np.array_equal(np.asarray(inc.columns[1]),
                                  np.asarray(warm0.columns[1]))

    def test_delete_tombstone_fallback(self, db):
        """DELETE writes tombstones; like scan_last, any reachable
        tombstone voids the per-part decomposition — typed fallback to
        the classic fold, bit-for-bit correct."""
        eng, qe = db
        rid = mk(qe, name="lww", append=False)
        for f in range(2):
            vals = ", ".join(
                f"({f * 100000 + i * 10}, 'h{i % 4}', {f * 100 + i}, 0.0)"
                for i in range(60))
            qe.execute_one(f"INSERT INTO lww VALUES {vals}", CTX)
            eng.flush(rid)
        sql = "SELECT host, sum(v) FROM lww GROUP BY host ORDER BY host"
        qe.execute_one(sql, CTX)
        assert qe.executor.last_path == "incremental"
        qe.execute_one("DELETE FROM lww WHERE host = 'h1'", CTX)
        classic, inc, _ = run_both(qe, sql)
        assert qe.executor.last_path != "incremental"
        assert_same(classic, inc)
        assert "h1" not in list(np.asarray(inc.columns[0]))

    def test_drop_region_invalidates(self, db):
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=0)
        qe.execute_one(AGG_SQL, CTX)
        assert pc.global_cache().part_keys(rid)
        qe.execute_one("DROP TABLE cpu", CTX)
        assert pc.global_cache().part_keys(rid) == []


def _io_counters():
    """(part-cache misses, part-cache hits, decode bytes): what a scan
    costs the storage layer."""
    from greptimedb_tpu.utils.metrics import (
        SCAN_DECODE_BYTES,
        SCAN_PART_CACHE_EVENTS,
    )

    return (SCAN_PART_CACHE_EVENTS.get(event="miss"),
            SCAN_PART_CACHE_EVENTS.get(event="hit"),
            SCAN_DECODE_BYTES.get())


def _modes():
    from greptimedb_tpu.utils.metrics import AGG_SCAN

    return {m: AGG_SCAN.get(mode=m) for m in ("none", "parts", "whole")}


def _mode_delta(before):
    return {m: int(v - before[m]) for m, v in _modes().items()}


BUCKET_SQL = ("SELECT date_bin(INTERVAL '1 second', ts) AS sec, host, "
              "avg(v), max(w) FROM cpu WHERE ts >= 0 AND ts < 90000000 "
              "GROUP BY sec, host ORDER BY sec, host")


class TestScanOnDemand:
    """ISSUE 25: a scan is a plan first and bytes on demand — the
    aggregate probes the partial cache from region metadata and decodes
    only the parts it misses."""

    @pytest.mark.parametrize("append", [True, False],
                             ids=["append", "lww"])
    @pytest.mark.parametrize("sql", [AGG_SQL, BUCKET_SQL],
                             ids=["by_host", "double_groupby"])
    def test_all_hit_decodes_nothing(self, db, monkeypatch, append, sql):
        """(a) every partial cached: no SST part is fetched or decoded,
        nothing is concatenated, op_type is never read — and the answer
        equals the materialized one bit for bit."""
        from greptimedb_tpu.storage.region import Region, ScanData
        from greptimedb_tpu.utils.metrics import QUERY_TIER

        eng, qe = db
        rid = mk(qe, append=append)
        fill(qe, eng, rid, mem=0)
        classic, cold, _ = run_both(qe, sql)
        region = eng.region(rid)
        with region._lock:  # drop the classic run's parked snapshot
            region._scan_cache.clear()
            region._scan_cache_sizes.clear()
            region._scan_cache_bytes = 0
        built = []
        orig_mat, orig_cat = ScanData.materialize, Region._concat_columns
        monkeypatch.setattr(
            ScanData, "materialize",
            lambda self: built.append("materialize") or orig_mat(self))
        monkeypatch.setattr(
            Region, "_concat_columns",
            lambda self, *a: built.append("concat") or orig_cat(self, *a))
        io0, modes0 = _io_counters(), _modes()
        cache0 = QUERY_TIER.get(tier="cache")
        warm = qe.execute_one(sql, CTX)
        st = qe.executor.last_partial_stats
        assert st["part_hits"] == 3 and st["part_misses"] == 0
        assert _io_counters() == io0
        assert _mode_delta(modes0) == {"none": 1, "parts": 0, "whole": 0}
        assert QUERY_TIER.get(tier="cache") == cache0 + 1
        assert built == []
        assert not region._file_refs  # the plan gave its pins back
        assert_same(classic, cold)
        assert_same(classic, warm)

    def test_flush_decodes_only_the_new_file(self, db):
        """(b) + (c): a row acknowledged after the cache is warm is in
        the next answer straight from the memtable (nothing decoded),
        and after the flush only the new file is decoded."""
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=0)
        qe.execute_one(AGG_SQL, CTX)
        qe.execute_one("INSERT INTO cpu VALUES (77, 'h1', 1e6, 3.0), "
                       "(78, 'fresh', 5.0, 1.0)", CTX)
        io0, modes0 = _io_counters(), _modes()
        classic, inc, st = run_both(qe, AGG_SQL)
        assert st["part_hits"] == 3 and st["memtable_rows"] == 2
        assert "fresh" in list(np.asarray(inc.columns[0]))
        assert_same(classic, inc)
        # the classic twin decoded; the incremental run did not
        assert _mode_delta(modes0) == {"none": 1, "parts": 0, "whole": 1}
        eng.flush(rid)
        io0, modes0 = _io_counters(), _modes()
        after = qe.execute_one(AGG_SQL, CTX)
        st = qe.executor.last_partial_stats
        assert st["part_hits"] == 3 and st["part_misses"] == 1
        assert st["delta_rows"] == 2
        io1 = _io_counters()
        assert io1[0] == io0[0] + 1  # one part-cache miss: the new file
        assert io1[1] == io0[1]  # no other part was even looked up
        assert io1[2] > io0[2]
        assert _mode_delta(modes0) == {"none": 0, "parts": 1, "whole": 0}
        assert_same(classic, after)  # the flush does not change it

    def test_delete_after_warm_falls_back_exact(self, db):
        """(d) a DELETE acknowledged after the cache is warm is seen
        from the memtable slice, and after its flush from the new
        file's noted flag: typed fallback, whole columns, exact."""
        eng, qe = db
        rid = mk(qe, name="lww", append=False)
        for f in range(2):
            vals = ", ".join(
                f"({f * 100000 + i * 10}, 'h{i % 4}', {f * 100 + i}, 0.0)"
                for i in range(60))
            qe.execute_one(f"INSERT INTO lww VALUES {vals}", CTX)
            eng.flush(rid)
        sql = "SELECT host, sum(v) FROM lww GROUP BY host ORDER BY host"
        qe.execute_one(sql, CTX)
        qe.execute_one(sql, CTX)
        assert qe.executor.last_partial_stats["part_hits"] == 2
        qe.execute_one("DELETE FROM lww WHERE host = 'h1'", CTX)
        for where in ("memtable", "flushed"):
            modes0 = _modes()
            classic, inc, _ = run_both(qe, sql)
            assert qe.executor.last_path != "incremental", where
            assert _mode_delta(modes0)["whole"] == 2, where
            assert_same(classic, inc)
            assert "h1" not in list(np.asarray(inc.columns[0])), where
            eng.flush(rid)

    @pytest.mark.parametrize("seam", ["compact", "truncate"])
    def test_snapshot_dies_between_plan_and_fetch(self, db, monkeypatch,
                                                  seam):
        """(e) the files of a plan are swapped out (compaction) or
        deleted (TRUNCATE) after the plan is taken and before its bytes
        are read: the answer is a whole snapshot's, and nothing stays
        pinned — the purge queue drains."""
        import glob

        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=0)
        expected = qe.execute_one(AGG_SQL, CTX)
        pc.global_cache().clear()  # cold: every part has to be fetched
        region = eng.region(rid)
        orig, fired = eng.scan, []

        def scan_then_mutate(*a, **k):
            out = orig(*a, **k)
            if not fired:
                fired.append(1)
                assert not out.materialized  # a plan, nothing read yet
                if seam == "compact":
                    eng.compact(rid)
                else:
                    qe.execute_one("TRUNCATE TABLE cpu", CTX)
            return out

        monkeypatch.setattr(eng, "scan", scan_then_mutate)
        got = qe.execute_one(AGG_SQL, CTX)
        assert fired
        if seam == "compact":
            # the plan's pins kept the swapped-out files readable
            assert_same(expected, got)
        else:
            # the snapshot expired; the retaken one is the empty table
            assert got.num_rows == 0
        del got
        assert not region._file_refs
        assert not region._purge_queue
        live = set(eng.region(
            qe.catalog.table("public", "cpu").region_ids[0]).files)
        on_disk = {os.path.basename(f)[:-len(".parquet")]
                   for f in glob.glob(os.path.join(
                       eng.config.data_dir, "**", "sst", "*.parquet"),
                       recursive=True)}
        assert on_disk == live
        monkeypatch.setattr(eng, "scan", orig)
        again = qe.execute_one(AGG_SQL, CTX)
        if seam == "compact":
            assert_same(expected, again)

    @pytest.mark.parametrize("sql,flag", [
        (AGG_SQL, "on_demand"),
        ("SELECT host, median(v) FROM cpu GROUP BY host", "whole"),
    ], ids=["parts", "whole"])
    def test_decode_on_demand_is_scan_stage_time(self, db, sql, flag):
        """The plan's `scan` segment decodes nothing; the rows are
        decoded in a later `scan` segment — inside the fold for missed
        parts, before the classic kernels for whole columns — so the
        stage keeps meaning what it meant."""
        from greptimedb_tpu.utils import tracing

        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, mem=0)
        ctx = QueryContext()  # a trace of its own
        with tracing.request_span("test:scan_on_demand"):
            qe.execute_one(sql, ctx)
        segs = sorted((s for s in tracing.spans_for(ctx.trace_id)
                       if s.stage and s.name == "scan"),
                      key=lambda s: s.started_at)
        plan = segs[0]
        assert plan.attrs["rows"] == 360
        assert plan.attrs["rows_decoded"] == 0
        later = [s for s in segs[1:] if s.attrs.get(flag)]
        assert later, [s.attrs for s in segs]
        assert sum(s.attrs.get("rows_decoded", 0) for s in later) == 360

    @pytest.mark.parametrize("shape", ["host_agg", "multi_region"])
    def test_ineligible_shapes_materialize(self, db, shape):
        """(f) a shape the per-part decomposition cannot serve reads
        whole columns as before, and says so."""
        eng, qe = db
        if shape == "host_agg":
            rid = mk(qe)
            fill(qe, eng, rid)
            sql = "SELECT host, median(v) FROM cpu GROUP BY host " \
                  "ORDER BY host"
        else:
            qe.execute_one(
                "CREATE TABLE cpu (ts TIMESTAMP(3) TIME INDEX, host "
                "STRING, v DOUBLE, w DOUBLE, PRIMARY KEY(host)) "
                "PARTITION ON COLUMNS (host) (host < 'h2', host >= 'h2') "
                "WITH (append_mode='true')", CTX)
            rids = qe.catalog.table("public", "cpu").region_ids
            assert len(rids) == 2
            vals = ", ".join(f"({i * 10}, 'h{i % 5}', {i}.5, 1.0)"
                             for i in range(100))
            qe.execute_one(f"INSERT INTO cpu VALUES {vals}", CTX)
            for rid in rids:
                eng.flush(rid)
            # an order statistic over a partitioned table still gathers
            # (merge_scans): its scan has no region of its own
            sql = "SELECT host, median(v) FROM cpu GROUP BY host " \
                  "ORDER BY host"
        modes0 = _modes()
        first = qe.execute_one(sql, CTX)
        assert qe.executor.last_path != "incremental"
        second = qe.execute_one(sql, CTX)
        assert _mode_delta(modes0) == {"none": 0, "parts": 0, "whole": 2}
        assert_same(first, second)
        assert first.num_rows == 5


    def test_a_partitioned_table_takes_the_per_part_route(self, db):
        """Each region of a PARTITION ON COLUMNS table folds its own
        scan part by part, under its own identity: the second request
        fetches no part."""
        eng, qe = db
        qe.execute_one(
            "CREATE TABLE cpu (ts TIMESTAMP(3) TIME INDEX, host "
            "STRING, v DOUBLE, w DOUBLE, PRIMARY KEY(host)) "
            "PARTITION ON COLUMNS (host) (host < 'h2', host >= 'h2') "
            "WITH (append_mode='true')", CTX)
        rids = qe.catalog.table("public", "cpu").region_ids
        vals = ", ".join(f"({i * 10}, 'h{i % 5}', {i}.5, 1.0)"
                         for i in range(100))
        qe.execute_one(f"INSERT INTO cpu VALUES {vals}", CTX)
        for rid in rids:
            eng.flush(rid)
        modes0 = _modes()
        first = qe.execute_one(AGG_SQL, CTX)
        assert qe.executor.last_path == "fanout+incremental"
        second = qe.execute_one(AGG_SQL, CTX)
        assert _mode_delta(modes0) == {"none": 1, "parts": 1, "whole": 0}
        assert qe.executor.last_partial_stats["part_hits"] == 2
        assert_same(first, second)
        assert first.num_rows == 5


class TestEligibilityFallbacks:
    def test_host_agg_falls_back(self, db):
        from greptimedb_tpu.utils.metrics import PARTIAL_AGG_CACHE_EVENTS

        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid)
        before = PARTIAL_AGG_CACHE_EVENTS.get(event="fallback")
        qe.execute_one(
            "SELECT host, approx_percentile_cont(v, 0.5) FROM cpu "
            "GROUP BY host", CTX)
        assert qe.executor.last_path != "incremental"
        assert PARTIAL_AGG_CACHE_EVENTS.get(event="fallback") > before

    def test_disabled_by_option(self, db, monkeypatch):
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid)
        monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")
        qe.execute_one(AGG_SQL, CTX)
        assert qe.executor.last_path != "incremental"
        assert qe.executor.last_partial_stats is None

    def test_memtable_only_scan_falls_back(self, db):
        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid, files=0, mem=50)
        classic, inc, _ = run_both(qe, AGG_SQL)
        assert qe.executor.last_path != "incremental"
        assert_same(classic, inc)


class TestCacheMechanics:
    def test_budget_eviction(self):
        cache = pc.PartialAggCache(budget=4096)
        part = {"keys": [np.arange(8)],
                "planes": {"sum": np.zeros((8, 4))}}
        for i in range(64):
            cache.put(("part", 1, f"f{i}", None, None, ("fp",)), part)
        assert cache.bytes <= 4096
        assert len(cache.part_keys(1)) < 64

    def test_dead_file_put_refused(self):
        cache = pc.PartialAggCache(budget=1 << 20)
        key = ("part", 1, "file_a", None, None, ("fp",))
        cache.invalidate_files(1, ["file_a"])
        cache.put(key, {"keys": [], "planes": {}})
        assert cache.get(key) is None

    def test_epoch_put_refused_after_region_invalidate(self):
        cache = pc.PartialAggCache(budget=1 << 20)
        key = ("frag", 7, 0, 3, "{}")
        epoch = cache.epoch(7)
        cache.invalidate_region(7)  # TRUNCATE while the fold ran
        cache.put(key, {"keys": [], "planes": {}}, epoch=epoch)
        assert cache.get(key) is None

    def test_frag_generation_retirement(self):
        """Fragment keys embed (incarnation, data_version); writes bump
        the version with no invalidation seam, so stale-generation
        entries must retire on the next put instead of accumulating one
        dead entry per write."""
        cache = pc.PartialAggCache(budget=1 << 20)
        empty = {"keys": [], "planes": {}}
        for version in range(50):
            cache.put(("frag", 9, 0, version, "{frag-a}"), empty)
        # only the newest generation's entry survives
        with cache._lock:
            frags = [k for k in cache._lru if k[0] == "frag"]
        assert frags == [("frag", 9, 0, 49, "{frag-a}")]
        # distinct fragments at the SAME generation coexist
        cache.put(("frag", 9, 0, 49, "{frag-b}"), empty)
        with cache._lock:
            assert len([k for k in cache._lru if k[0] == "frag"]) == 2

    def test_budget_env_zero_means_auto(self, monkeypatch):
        monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE_BYTES", "0")
        assert pc.budget_bytes() == 256 << 20
        monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE_BYTES", "1024")
        assert pc.budget_bytes() == 1024

    def test_oversized_entry_never_wipes(self):
        cache = pc.PartialAggCache(budget=1024)
        small = {"keys": [], "planes": {"sum": np.zeros((4, 2))}}
        cache.put(("part", 1, "f0", None, None, ("fp",)), small)
        big = {"keys": [], "planes": {"sum": np.zeros((1024, 16))}}
        cache.put(("part", 1, "f1", None, None, ("fp",)), big)
        assert cache.get(("part", 1, "f0", None, None, ("fp",))) \
            is not None


class TestFailureLatch:
    def test_unexpected_failure_degrades_and_latches(self, db,
                                                     monkeypatch):
        """An infrastructure failure inside the incremental fold must
        answer THAT query via the classic kernels and latch the path
        off — degradation, never an error (the fused-latch contract)."""
        from greptimedb_tpu.query import physical as ph

        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid)
        monkeypatch.setattr(
            ph.PhysicalExecutor, "_incremental_partials",
            lambda self, *a, **k: (_ for _ in ()).throw(
                RuntimeError("boom")))
        try:
            res = qe.execute_one(AGG_SQL, CTX)
            assert res.num_rows == 5
            assert qe.executor.last_path != "incremental"
            assert ph._PARTIAL_DISABLED["flag"]
            # latched: later queries skip the broken path silently
            res2 = qe.execute_one(AGG_SQL, CTX)
            assert_same(res, res2)
        finally:
            ph._PARTIAL_DISABLED["flag"] = False


class TestDeviceHedge:
    def test_first_touch_serves_host_and_warms_background(self, db,
                                                          monkeypatch):
        """On a real accelerator in auto host-tier mode the FIRST
        incremental fold of a shape must not block on the device
        compile: it serves host-side, a background warm marks the shape
        device-warm, and later folds route to the device."""
        import time as _time

        from greptimedb_tpu.query import physical as ph

        eng, qe = db
        rid = mk(qe)
        fill(qe, eng, rid)
        ex = qe.executor
        monkeypatch.setattr(ph.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ex, "mesh", None)
        monkeypatch.setattr(
            ex, "tier_for",
            lambda agg, n, streaming=False: "device")
        res = qe.execute_one(AGG_SQL, CTX)
        assert qe.executor.last_path == "incremental"
        assert qe.executor.last_tier == "host"  # hedged: no compile stall
        for _ in range(100):  # the background warm lands
            if not ex.router.status()["warmup"]["warming"]:
                break
            _time.sleep(0.05)
        assert ex.router.status()["warmup"] == {
            "warm": 1, "warming": 0, "failed": 0}
        res2 = qe.execute_one(AGG_SQL, CTX)
        assert qe.executor.last_tier == "device"  # warm: device serves
        assert_same(res, res2)


class TestMeshTier:
    def test_mesh_tier_parity_and_placement(self, db, monkeypatch):
        """Force the mesh tier (8 virtual devices, low row floor): the
        incremental fold computes per-part partials on owning shards
        and matches the classic mesh path bit-for-bit."""
        eng, qe = db
        monkeypatch.setenv("GREPTIMEDB_TPU_MESH_MIN_ROWS", "1")
        rid = mk(qe)
        fill(qe, eng, rid, files=3, rows=200, mem=30)
        if qe.executor.mesh is None:
            pytest.skip("no virtual device mesh in this environment")
        classic, inc, st = run_both(qe, AGG_SQL)
        assert qe.executor.last_path == "incremental"
        assert qe.executor.last_tier == "mesh"
        assert st["part_misses"] == 3
        assert_same(classic, inc)
        warm = qe.execute_one(AGG_SQL, CTX)
        assert qe.executor.last_partial_stats["part_hits"] == 3
        assert_same(classic, warm)


class TestFlowDirtySpan:
    def test_dirty_span_tick_rides_partial_cache(self, db):
        """A flow that can't run the incremental (state-plane) path —
        post-aggregate projection — re-aggregates its dirty span through
        the executor, which now serves immutable parts from the cache."""
        from greptimedb_tpu.flow.engine import FlowEngine

        eng, qe = db
        rid = mk(qe, name="src")
        fill(qe, eng, rid, name="src", mem=20)
        fe = FlowEngine(qe)
        qe.execute_one(
            "CREATE FLOW f1 SINK TO snk AS "
            "SELECT host, max(v) * 2 FROM src GROUP BY host", CTX)
        infos = fe.list_flows("public")
        assert infos and not infos[0].incremental  # dirty-span flow
        fe.run_available("public")
        # source changed -> second tick re-runs the aggregate; parts
        # must come from the cache
        qe.execute_one(
            "INSERT INTO src VALUES (9000000, 'h0', 1.0, 0.0)", CTX)
        fe.run_available("public")
        st = (FlowEngine.last_tick_stats or {}).get("partial_cache")
        assert st is not None and st["part_hits"] >= 1


class TestClusterFragmentCache:
    def test_repeated_fragment_serves_cached_plane(self, tmp_path):
        """In a multi-region cluster, the SECOND identical aggregate
        must answer each region's PlanFragment from the cached plane —
        Region.scan is never called again — and a write invalidates
        (data_version key) so no stale plane is served."""
        from greptimedb_tpu.cluster import Cluster
        from greptimedb_tpu.meta.metasrv import MetasrvOptions

        c = Cluster(str(tmp_path), num_datanodes=2,
                    opts=MetasrvOptions())
        try:
            c.sql("CREATE TABLE cpu (host STRING, v DOUBLE, ts "
                  "TIMESTAMP(3) NOT NULL, TIME INDEX (ts), PRIMARY "
                  "KEY(host)) PARTITION ON COLUMNS (host) "
                  "(host < 'host3', host >= 'host3')")
            rows = [f"('host{h}', {float(10 * h + i)}, {1000 * i + h})"
                    for h in range(6) for i in range(20)]
            c.sql("INSERT INTO cpu VALUES " + ", ".join(rows))
            c.sql("ADMIN flush_table('cpu')")
            sql = ("SELECT host, sum(v), count(v) FROM cpu "
                   "GROUP BY host ORDER BY host")
            first = c.sql(sql)
            assert c.frontend.executor.last_path == "pushdown"

            from greptimedb_tpu.storage.region import Region

            calls = {"n": 0}
            orig = Region.scan

            def spy(self, *a, **k):
                calls["n"] += 1
                return orig(self, *a, **k)

            Region.scan = spy
            try:
                second = c.sql(sql)
            finally:
                Region.scan = orig
            assert calls["n"] == 0, "cached plane must not rescan"
            assert_same(first, second)

            # a write bumps data_version: the plane recomputes, fresh
            c.sql("INSERT INTO cpu VALUES ('host0', 1000.0, 999999)")
            third = c.sql(sql)
            h0 = np.asarray(third.columns[1])[0]
            assert h0 == np.asarray(first.columns[1])[0] + 1000.0
        finally:
            c.close()


@pytest.mark.chaos
class TestProcessClusterFailover:
    def test_no_stale_partial_after_failover_replay(self, tmp_path):
        """2-dn ProcessCluster: warm the fragment/partial caches, write
        UNFLUSHED rows, SIGKILL the owner, let failover re-open the
        region on the survivor from the shared WAL — the same aggregate
        must reflect every acked write (a stale partial would drop the
        unflushed delta)."""
        from greptimedb_tpu.cluster.process_cluster import ProcessCluster
        from greptimedb_tpu.meta.metasrv import MetasrvOptions

        c = ProcessCluster(str(tmp_path), num_datanodes=2,
                           opts=MetasrvOptions())
        try:
            t = 0.0
            for _ in range(5):
                c.beat_all(t)
                t += 3000.0
            c.sql("CREATE TABLE m (host STRING, v DOUBLE, ts "
                  "TIMESTAMP(3) NOT NULL, TIME INDEX (ts), PRIMARY "
                  "KEY(host)) PARTITION ON COLUMNS (host) "
                  "(host < 'h5', host >= 'h5')")
            rows = ", ".join(f"('h{i}', {float(i)}, {1000 * (i + 1)})"
                             for i in range(10))
            c.sql(f"INSERT INTO m VALUES {rows}")
            c.sql("ADMIN flush_table('m')")
            sql = "SELECT sum(v), count(v) FROM m"
            warm = c.sql(sql).rows()
            assert warm == [[45.0, 10]]
            c.sql(sql)  # second run: fragment planes now cached

            # acked but unflushed: lives only in the shared WAL
            c.sql("INSERT INTO m VALUES ('h0', 100.0, 999999)")
            assert c.sql(sql).rows() == [[145.0, 11]]

            info = c.catalog.table("public", "m")
            rid = info.region_ids[0]
            owner = c.metasrv.routes.get(
                str(rid >> 32)).regions[0].leader_node
            for _ in range(5):
                c.beat_all(t)
                t += 3000.0
            c.kill_datanode(owner)
            for _ in range(20):
                c.beat_all(t)
                t += 3000.0
            assert c.tick(t), "failover should start"
            c.beat_all(t)  # deliver OPEN_REGION to the survivor

            got = c.sql(sql).rows()
            assert got == [[145.0, 11]], (
                "stale partial served after failover replay")
        finally:
            c.close()

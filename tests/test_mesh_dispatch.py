"""Part-aligned mesh shard dispatch (ISSUE 12): the parity matrix
against the single-device oracle, the per-shard file-anchored hot set
(a flush uploads only its new file), measured mesh routing, and the
typed degradation contract.

Parity tests use integer-valued doubles so float sums are associativity-
free: the mesh path's per-shard fold + psum combine must be BIT-FOR-BIT
identical to the serial single-device result, not merely close."""

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig


@pytest.fixture(autouse=True)
def _classic_mesh_paths(monkeypatch):
    # this module pins the classic shard_map dispatch machinery (paths,
    # H2D accounting, dispatch counters); the partial-aggregate cache
    # would intercept eligible shapes before they reach it — its own
    # mesh-tier behavior is covered in test_partial_cache.py
    monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")


@pytest.fixture
def mesh_db(tmp_path, monkeypatch):
    monkeypatch.setenv("GREPTIMEDB_TPU_MESH", "8x1")
    monkeypatch.setenv("GREPTIMEDB_TPU_MESH_MIN_ROWS", "1")
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                       maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    assert qe.executor.mesh is not None
    yield qe
    engine.close()


def _off_oracle(qe, sql, monkeypatch):
    """Same SQL with the mesh disabled on a fresh executor (fresh device
    cache) — the serial single-device oracle."""
    from greptimedb_tpu.query.physical import PhysicalExecutor

    monkeypatch.setenv("GREPTIMEDB_TPU_MESH", "off")
    off = PhysicalExecutor(qe.region_engine)
    saved = qe.executor
    qe.executor = off
    try:
        return qe.execute_one(sql).rows()
    finally:
        qe.executor = saved
        monkeypatch.setenv("GREPTIMEDB_TPU_MESH", "8x1")


def _fill(qe, *, files=3, hosts=12, points=30, append=True, tail=True):
    """Integer-valued data across several SST files (+ an optional
    unflushed memtable delta)."""
    mode = " WITH (append_mode = 'true')" if append else ""
    qe.execute_one(
        "CREATE TABLE m (host STRING, v DOUBLE, w DOUBLE, ts TIMESTAMP(3)"
        " NOT NULL, TIME INDEX (ts), PRIMARY KEY (host))" + mode)
    rng = np.random.default_rng(7)
    for f in range(files):
        rows = []
        for p in range(points):
            for h in range(hosts):
                ts = (f * points + p) * 1000
                rows.append(f"('h{h:02d}', {int(rng.integers(0, 1000))}, "
                            f"{int(rng.integers(0, 50))}, {ts})")
        qe.execute_one("INSERT INTO m (host, v, w, ts) VALUES "
                       + ",".join(rows))
        qe.execute_one("ADMIN flush_table('m')")
    if tail:
        rows = [f"('h{h:02d}', {h + 1}, 7, {10_000_000 + h})"
                for h in range(hosts)]
        qe.execute_one("INSERT INTO m (host, v, w, ts) VALUES "
                       + ",".join(rows))
    return qe.catalog.table("public", "m").region_ids[0]


PARITY_SQLS = [
    # dense-prepared class: sum/count/min/max/avg over two fields
    "SELECT host, sum(v), count(v), min(v), max(w), avg(w) FROM m "
    "GROUP BY host ORDER BY host",
    # general sharded kernel: first/last ride the ts-paired combine
    "SELECT host, first(v), last(v), last(w) FROM m "
    "GROUP BY host ORDER BY host",
    # date_bin bucket key + tag key
    "SELECT host, date_bin(INTERVAL '10 seconds', ts) AS b, sum(v) "
    "FROM m GROUP BY host, b ORDER BY host, b",
]


class TestParityMatrix:
    @pytest.mark.parametrize("sql", PARITY_SQLS)
    def test_append_multipart_with_memtable_delta(self, mesh_db,
                                                  monkeypatch, sql):
        qe = mesh_db
        _fill(qe)
        got = qe.execute_one(sql).rows()
        # first/last may reduce through the boundary fast path first:
        # "boundary+sharded" still proves the mesh served the fold
        assert "sharded" in qe.executor.last_path, \
            qe.executor.last_path
        assert qe.executor.last_tier == "mesh"
        off = _off_oracle(qe, sql, monkeypatch)
        assert got == off  # bit-for-bit (integer-valued doubles)

    def test_dedup_lww_parity(self, mesh_db, monkeypatch):
        """Non-append table: LWW dedup survivors must shard identically
        (the dedup mask rides the shard plan's segment order)."""
        qe = mesh_db
        _fill(qe, append=False, files=2, tail=False)
        # overwrite some (host, ts) instants — dedup must pick these
        rows = [f"('h{h:02d}', {9000 + h}, 1, {p * 1000})"
                for h in range(6) for p in range(10)]
        qe.execute_one("INSERT INTO m (host, v, w, ts) VALUES "
                       + ",".join(rows))
        qe.execute_one("ADMIN flush_table('m')")
        sql = ("SELECT host, sum(v), count(v), last(v) FROM m "
               "GROUP BY host ORDER BY host")
        got = qe.execute_one(sql).rows()
        assert "sharded" in qe.executor.last_path
        off = _off_oracle(qe, sql, monkeypatch)
        assert got == off
        # the overwrites actually landed (guard against vacuous parity):
        # LWW must serve the 9000-valued rewrite of the ts=0 instant
        point = qe.execute_one(
            "SELECT v FROM m WHERE host = 'h00' AND ts = 0").rows()
        assert [list(r) for r in point] == [[9000.0]]

    def test_where_filter_parity(self, mesh_db, monkeypatch):
        qe = mesh_db
        _fill(qe)
        sql = ("SELECT host, sum(v), count(v) FROM m "
               "WHERE w < 25 AND host <> 'h03' GROUP BY host ORDER BY host")
        got = qe.execute_one(sql).rows()
        assert qe.executor.last_path.startswith("sharded")
        assert got == _off_oracle(qe, sql, monkeypatch)


class TestShardedHotSet:
    def _h2d(self):
        from greptimedb_tpu.utils.metrics import DEVICE_TRANSFER_BYTES

        return DEVICE_TRANSFER_BYTES.get(direction="h2d")

    def test_warm_repeat_zero_h2d_and_flush_uploads_only_new(
            self, mesh_db, monkeypatch):
        qe = mesh_db
        rid = _fill(qe, tail=False)
        sql = PARITY_SQLS[0]
        qe.execute_one(sql)
        assert qe.executor.last_path.startswith("sharded")
        cache = qe.executor.cache
        old_file_keys = {k for k in cache.file_keys(rid)
                         if "mshard" in k}
        assert old_file_keys, "no per-shard file-anchored uploads"
        before = self._h2d()
        want = qe.execute_one(sql).rows()
        assert self._h2d() == before, \
            "mesh-warm repeat re-uploaded shard buffers"
        # flush a new file: the old files' per-shard uploads survive the
        # data-version bump; only the new file's segments transfer
        qe.execute_one(
            "INSERT INTO m (host, v, w, ts) VALUES ('h00', 5, 5, 999000)")
        qe.execute_one("ADMIN flush_table('m')")
        before = self._h2d()
        got = qe.execute_one(sql).rows()
        delta = self._h2d() - before
        keys = {k for k in cache.file_keys(rid) if "mshard" in k}
        assert old_file_keys <= keys
        assert len(keys) > len(old_file_keys)
        # the incremental transfer is tiny relative to the working set:
        # one 1-row file's planes + the rebuilt mask, not the table
        full = sum(cache._lru[k].nbytes for k in old_file_keys)
        assert delta < full / 2, (delta, full)
        # and the result reflects the new row
        assert got != want

    def test_skew_and_dispatch_metrics(self, mesh_db):
        from greptimedb_tpu.utils.metrics import (
            MESH_DISPATCHES,
            MESH_SHARD_SKEW,
        )

        qe = mesh_db
        _fill(qe)
        before = MESH_DISPATCHES.get(path="sharded_prepared", shards="8")
        qe.execute_one(PARITY_SQLS[0])
        assert MESH_DISPATCHES.get(path="sharded_prepared",
                                   shards="8") > before
        skew = MESH_SHARD_SKEW.get()
        assert 1.0 <= skew < 4.0, skew


class TestRoutingAndDegradation:
    def test_host_aggregate_still_correct_with_mesh(self, mesh_db,
                                                    monkeypatch):
        """Order statistics compute host-side; the mesh may still serve
        the device planes (rows) — results must match the mesh-off
        oracle either way."""
        qe = mesh_db
        _fill(qe)
        sql = ("SELECT host, approx_percentile_cont(v, 0.5) FROM m "
               "GROUP BY host ORDER BY host")
        got = qe.execute_one(sql).rows()
        assert len(got) == 12
        assert got == _off_oracle(qe, sql, monkeypatch)

    def test_sparse_cardinality_shards_over_mesh(self, mesh_db,
                                                 monkeypatch):
        """Beyond the dense budget the sort-compact path no longer
        demotes to a single device: each shard compacts its own rows
        and the partials combine in gid space, bit-for-bit with the
        single-device sparse result."""
        monkeypatch.setenv("GREPTIMEDB_TPU_DENSE_GROUPS_MAX", "4")
        qe = mesh_db
        _fill(qe, files=1, tail=False)
        sql = "SELECT host, sum(v) FROM m GROUP BY host ORDER BY host"
        got = qe.execute_one(sql).rows()
        assert len(got) == 12
        assert qe.executor.last_path == "sparse_sharded"
        assert qe.executor.last_tier == "mesh"
        assert got == _off_oracle(qe, sql, monkeypatch)

    def test_small_scan_stays_single_device(self, mesh_db, monkeypatch):
        monkeypatch.setenv("GREPTIMEDB_TPU_MESH_MIN_ROWS", "1000000")
        qe = mesh_db
        _fill(qe)
        qe.execute_one(PARITY_SQLS[0])
        assert not qe.executor.last_path.startswith("sharded")
        assert qe.executor.last_tier == "device"

    def test_measured_routing_prefers_winner(self, mesh_db):
        """Feed the history rings directly: when the device tier
        measures faster for a size class, the router stops choosing the
        mesh (and explores it again every 16th decision)."""
        qe = mesh_db
        ex = qe.executor
        n = 200_000
        for _ in range(4):
            ex.router.note("mesh", n, 0.100)
            ex.router.note("device", n, 0.010)
        picks = {ex.tier_for(object(), n) for _ in range(15)}
        assert picks == {"device"}
        # the periodic exploration re-tries the loser eventually
        picks = [ex.tier_for(object(), n) for _ in range(16)]
        assert "mesh" in picks

    def test_mesh_ineligible_is_typed(self):
        from greptimedb_tpu.parallel.sharded_dispatch import (
            MeshIneligible,
            plan_shards,
        )
        from types import SimpleNamespace

        scan = SimpleNamespace(num_rows=10, sorted_part_offsets=[0, 10],
                               part_keys=(("f", None, None),))
        with pytest.raises(MeshIneligible):
            plan_shards(scan, 0)


class TestShardPlan:
    def test_prefix_stable_assignment(self):
        """Adding a new part must not move earlier segments between
        shards — the property that keeps file-anchored uploads valid
        across flushes."""
        from types import SimpleNamespace

        from greptimedb_tpu.parallel.sharded_dispatch import plan_shards

        def mk(parts):
            offs = [0]
            pkeys = []
            for i, rows in enumerate(parts):
                offs.append(offs[-1] + rows)
                pkeys.append((f"file{i}", None, None))
            return SimpleNamespace(num_rows=offs[-1],
                                   sorted_part_offsets=offs,
                                   part_keys=tuple(pkeys))

        p1 = plan_shards(mk([1000, 700, 300]), 4)
        p2 = plan_shards(mk([1000, 700, 300, 500]), 4)
        segs1 = {(seg.pkey, seg.start, seg.end, s)
                 for s, lst in enumerate(p1.segs) for seg in lst}
        segs2 = {(seg.pkey, seg.start, seg.end, s)
                 for s, lst in enumerate(p2.segs) for seg in lst}
        assert segs1 <= segs2
        # balance: every shard within 2x of the mean
        assert p2.skew < 2.0
        total = sum(p2.lens)
        assert total == 2500

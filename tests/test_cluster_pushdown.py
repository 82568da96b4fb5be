"""Cluster-mode rollup substitution, lastpoint pruning, and partition
scatter (ISSUE 12): the distributed frontend must
ship partial-aggregate planes — never raw rows — and return bit-for-bit
what the raw path returns."""

import numpy as np
import pytest

from greptimedb_tpu.cluster import Cluster
from greptimedb_tpu.meta.metasrv import MetasrvOptions
from greptimedb_tpu.partition.rule import (
    HashPartitionRule,
    PartitionBound,
    RangePartitionRule,
    rule_from_json,
)

CREATE = (
    "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) NOT NULL, "
    "TIME INDEX (ts), PRIMARY KEY(host))"
)


def host_rule(*splits):
    bounds = [PartitionBound((s,)) for s in splits] + [PartitionBound(())]
    return RangePartitionRule(["host"], bounds)


def make_cluster(tmp_path, n=3, wire=False):
    return Cluster(str(tmp_path), num_datanodes=n, opts=MetasrvOptions(),
                   wire_transport=wire)


def seed_minutes(cluster, hosts=6, minutes=3, per_minute=20):
    """Integer-valued rows spanning `minutes` one-minute buckets; the
    last bucket stays the ACTIVE window after a rollup."""
    rng = np.random.default_rng(3)
    rows = []
    for h in range(hosts):
        for m in range(minutes):
            for i in range(per_minute):
                ts = m * 60_000 + i * (60_000 // per_minute)
                rows.append(
                    f"('host{h}', {int(rng.integers(0, 1000))}, {ts})")
    cluster.sql("INSERT INTO cpu (host, v, ts) VALUES " + ", ".join(rows))


def roll_all(cluster, resolution_ms=60_000):
    """Give every datanode the rollup rule and roll every raw region —
    what the maintenance plane does on its tick, driven synchronously."""
    from greptimedb_tpu.maintenance.rollup import (
        ROLLUP_RID_FLAG,
        RollupRule,
        rule_slot,
        run_rollup_job,
    )

    rule = RollupRule(resolution_ms=resolution_ms)
    for dn in cluster.datanodes.values():
        dn.engine.maintenance.rollup_rules = [rule]
        for rid in list(dn.engine.regions):
            if rid & ROLLUP_RID_FLAG:
                continue
            run_rollup_job(dn.engine, rid, rule_slot(resolution_ms), rule)


ROLLUP_SQL = ("SELECT host, min(v), max(v), sum(v), count(v), avg(v) "
              "FROM cpu WHERE ts >= 0 AND ts < 120000 "
              "GROUP BY host ORDER BY host")


class TestClusterRollupSubstitution:
    def _run(self, c, monkeypatch):
        got = c.sql(ROLLUP_SQL).rows()
        path = c.frontend.executor.last_path
        # raw oracle: substitution disabled, same cluster
        monkeypatch.setenv("GTPU_ROLLUP_SUBSTITUTE", "0")
        try:
            want = c.sql(ROLLUP_SQL).rows()
        finally:
            monkeypatch.delenv("GTPU_ROLLUP_SUBSTITUTE")
        return got, want, path

    def test_substitution_ships_plane_fragments(self, tmp_path,
                                                monkeypatch):
        c = make_cluster(tmp_path)
        c.create_partitioned_table(CREATE, host_rule("host2", "host4"))
        seed_minutes(c)
        roll_all(c)
        got, want, path = self._run(c, monkeypatch)
        # served from the companion plane regions THROUGH the fragment
        # pushdown: partial [G, F] planes crossed the frontend boundary,
        # not raw rows — and bit-for-bit equal to the raw path
        assert path == "pushdown+rollup", path
        assert got == want
        assert len(got) == 6
        c.close()

    @pytest.mark.slow
    def test_substitution_over_wire(self, tmp_path, monkeypatch):
        c = make_cluster(tmp_path, n=2, wire=True)
        c.create_partitioned_table(CREATE, host_rule("host3"))
        seed_minutes(c, hosts=4)
        roll_all(c)
        got, want, path = self._run(c, monkeypatch)
        assert path == "pushdown+rollup", path
        assert got == want
        c.close()

    def test_late_write_disables_substitution(self, tmp_path,
                                              monkeypatch):
        """An out-of-order write into the covered span must flip the
        probe ineligible — the raw path serves (correctness beats the
        plane win) until the next roll re-covers."""
        c = make_cluster(tmp_path)
        c.create_partitioned_table(CREATE, host_rule("host2", "host4"))
        seed_minutes(c)
        roll_all(c)
        assert c.sql(ROLLUP_SQL)  # warm: substitution works
        assert c.frontend.executor.last_path == "pushdown+rollup"
        # a vacant instant inside the covered span (LWW must not merge it)
        c.sql("INSERT INTO cpu (host, v, ts) VALUES ('host0', 500, 30001)")
        got = c.sql(ROLLUP_SQL).rows()
        path = c.frontend.executor.last_path
        assert "rollup" not in (path or ""), path
        # the late row is IN the result (raw path sees it)
        by_host = {r[0]: r for r in got}
        assert by_host["host0"][4] == 41  # count picked up the new row
        c.close()

    def test_uncovered_window_falls_back(self, tmp_path, monkeypatch):
        c = make_cluster(tmp_path)
        c.create_partitioned_table(CREATE, host_rule("host2", "host4"))
        seed_minutes(c)
        roll_all(c)
        # window reaches into the active (raw-only) bucket
        sql = ("SELECT host, sum(v) FROM cpu WHERE ts >= 0 AND "
               "ts < 180000 GROUP BY host ORDER BY host")
        got = c.sql(sql).rows()
        assert "rollup" not in (c.frontend.executor.last_path or "")
        assert len(got) == 6
        c.close()


class TestClusterLastpoint:
    def test_lastpoint_fragment_prunes_and_matches(self, tmp_path,
                                                   monkeypatch):
        """Cluster lastpoint: the fragment carries the pruning hint,
        every region serves its partial from scan_last (spied), the
        frontend's last_path proves no raw-row gather, and the result is
        bit-for-bit the raw aggregate."""
        from greptimedb_tpu.storage.region import Region

        c = make_cluster(tmp_path)
        info = c.create_partitioned_table(CREATE,
                                          host_rule("host2", "host4"))
        # several files per region so newest-first pruning has work
        for gen in range(3):
            rows = [f"('host{h}', {100 * gen + h}, {gen * 10_000 + h})"
                    for h in range(6)]
            c.sql("INSERT INTO cpu (host, v, ts) VALUES " + ", ".join(rows))
            for rid in info.region_ids:
                c.router.flush(rid)
        calls = {"n": 0}
        orig = Region.scan_last

        def spy(self, *a, **k):
            calls["n"] += 1
            return orig(self, *a, **k)

        monkeypatch.setattr(Region, "scan_last", spy)
        sql = "SELECT host, last(v) FROM cpu GROUP BY host ORDER BY host"
        got = c.sql(sql).rows()
        assert c.frontend.executor.last_path == "lastfrag+pushdown"
        assert calls["n"] == len(info.region_ids)
        assert got == [(f"host{h}", float(200 + h)) for h in range(6)] or \
            [list(r) for r in got] == [[f"host{h}", float(200 + h)]
                                       for h in range(6)]
        # raw oracle: strip the hint by disabling scan_last
        monkeypatch.setattr(Region, "scan_last",
                            lambda self, *a, **k: None)
        want = c.sql(sql).rows()
        assert got == want
        c.close()


@pytest.mark.slow
class TestProcessClusterPushdown:
    def test_lastpoint_pushdown_across_processes(self, tmp_path):
        """Real child-process datanodes over Flight: cluster lastpoint
        returns exactly the per-series newest rows, and the frontend's
        last_path proves the partial-agg fragment (with the scan_last
        hint) served it — no raw-row gather."""
        from greptimedb_tpu.cluster.process_cluster import ProcessCluster

        c = ProcessCluster(str(tmp_path), num_datanodes=2)
        try:
            c.sql(
                "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
                "NOT NULL, TIME INDEX (ts), PRIMARY KEY(host)) "
                "PARTITION ON COLUMNS (host) (host < 'host3', "
                "host >= 'host3')")
            for gen in range(3):
                rows = [f"('host{h}', {100 * gen + h}, {gen * 10_000 + h})"
                        for h in range(6)]
                c.sql("INSERT INTO cpu (host, v, ts) VALUES "
                      + ", ".join(rows))
                c.sql("ADMIN flush_table('cpu')")
            sql = ("SELECT host, last(v) FROM cpu GROUP BY host "
                   "ORDER BY host")
            got = [list(r) for r in c.sql(sql).rows()]
            assert got == [[f"host{h}", float(200 + h)] for h in range(6)]
            assert c.frontend.executor.last_path == "lastfrag+pushdown"
        finally:
            c.close()


class TestPartitionScatter:
    def test_hash_rule_vectorized_and_stable(self):
        rule = HashPartitionRule(["host"], 4)
        hosts = np.asarray([f"h{i}" for i in range(1000)], dtype=object)
        r1 = rule.find_regions([hosts])
        r2 = rule.find_regions([hosts])
        assert (r1 == r2).all()
        assert r1.dtype == np.int32
        assert set(np.unique(r1)) <= set(range(4))
        # reasonable spread over 1000 distinct series
        counts = np.bincount(r1, minlength=4)
        assert counts.min() > 150, counts
        # split partitions the row set exactly
        parts = rule.split([hosts])
        all_rows = np.sort(np.concatenate(list(parts.values())))
        assert (all_rows == np.arange(1000)).all()
        # JSON round trip preserves assignment
        clone = rule_from_json(rule.to_json())
        assert (clone.find_regions([hosts]) == r1).all()

    def test_hash_rule_multi_column_and_numeric(self):
        rule = HashPartitionRule(["host", "dev"], 3)
        hosts = np.asarray(["a", "a", "b", "b"], dtype=object)
        devs = np.asarray([1, 2, 1, 2], dtype=np.int64)
        r = rule.find_regions([hosts, devs])
        assert len(r) == 4
        # same tuple -> same region (whole series stay together)
        r2 = rule.find_regions([hosts[:1], devs[:1]])
        assert r2[0] == r[0]

    def test_cluster_rows_land_where_find_regions_says(self, tmp_path):
        rule = HashPartitionRule(["host"], 3)
        c = make_cluster(tmp_path)
        info = c.create_partitioned_table(CREATE, rule)
        hosts = [f"host{h}" for h in range(12)]
        rows = [f"('{h}', 1, {i * 1000})"
                for i, h in enumerate(hosts) for _ in (0,)]
        c.sql("INSERT INTO cpu (host, v, ts) VALUES " + ", ".join(rows))
        expect = rule.find_regions(
            [np.asarray(hosts, dtype=object)])
        for idx, rid in enumerate(info.region_ids):
            scan = c.router.scan(rid)
            got_hosts = set()
            if scan is not None:
                d = scan.tag_dicts["host"]
                got_hosts = {d[code] for code in scan.columns["host"]}
            want_hosts = {h for h, r in zip(hosts, expect) if r == idx}
            assert got_hosts == want_hosts, (idx, got_hosts, want_hosts)
        # the aggregate over the scattered table is whole
        assert c.sql("SELECT count(*) FROM cpu").rows()[0][0] == 12
        c.close()

    def test_default_hash_regions_auto_partitions(self, tmp_path,
                                                  monkeypatch):
        monkeypatch.setenv("GREPTIMEDB_TPU_DEFAULT_HASH_REGIONS", "3")
        c = make_cluster(tmp_path)
        c.sql(CREATE)
        info = c.catalog.table("public", "cpu")
        assert len(info.region_ids) == 3
        assert info.partition_rules["type"] == "hash"
        assert info.partition_rules["columns"] == ["host"]
        rows = [f"('host{h}', {h}, {h * 1000})" for h in range(9)]
        c.sql("INSERT INTO cpu (host, v, ts) VALUES " + ", ".join(rows))
        assert c.sql("SELECT count(*) FROM cpu").rows()[0][0] == 9
        # more than one region actually holds rows
        occupied = sum(
            1 for rid in info.region_ids
            if c.router.scan(rid) is not None)
        assert occupied > 1
        c.close()

    def test_standalone_create_stays_single_region(self, tmp_path,
                                                   monkeypatch):
        """The [partition] default must not touch standalone engines."""
        from greptimedb_tpu.catalog import Catalog, MemoryKv
        from greptimedb_tpu.query import QueryEngine
        from greptimedb_tpu.storage import RegionEngine
        from greptimedb_tpu.storage.engine import EngineConfig

        monkeypatch.setenv("GREPTIMEDB_TPU_DEFAULT_HASH_REGIONS", "3")
        engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "d")))
        qe = QueryEngine(Catalog(MemoryKv()), engine)
        qe.execute_one(CREATE)
        assert len(qe.catalog.table("public", "cpu").region_ids) == 1
        engine.close()

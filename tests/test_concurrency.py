"""Write/scan/flush/compact under concurrency — the worker-model
discipline (reference mito2 region worker, worker.rs:110-650): mutations
serialize on the region lock, scans snapshot consistently, compacted
SSTs are purged on a grace delay so in-flight scans can finish."""

import threading

import numpy as np
import pytest

from greptimedb_tpu.catalog.catalog import Catalog
from greptimedb_tpu.catalog.kv import MemoryKv
from greptimedb_tpu.query.engine import QueryEngine
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine


@pytest.fixture
def qe(tmp_path):
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path)))
    q = QueryEngine(Catalog(MemoryKv()), engine)
    q.execute_one(
        "CREATE TABLE m (host STRING, v DOUBLE, ts TIMESTAMP TIME INDEX, "
        "PRIMARY KEY(host))")
    yield q
    engine.close()


def _run_threads(fns, timeout=120):
    errors = []

    def wrap(fn):
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(fn,)) for fn in fns]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    assert not errors, errors[:3]


class TestWriteScanRaces:
    ROUNDS = 30

    def test_writes_during_scans(self, qe):
        """Scans racing writes must never crash and every scan must see a
        consistent snapshot (full rows, monotonic count)."""
        counts = []

        def writer():
            for i in range(self.ROUNDS):
                qe.execute_one(
                    "INSERT INTO m VALUES " + ", ".join(
                        f"('h{j}', {i}.0, {i * 100 + j})" for j in range(20)))

        def scanner():
            for _ in range(self.ROUNDS):
                r = qe.execute_one("SELECT count(*), count(v) FROM m")
                total, non_null = r.rows()[0]
                # a torn scan would show count(*) != count(v) (a row with
                # ts appended but v missing) — snapshots forbid that
                assert total == non_null, (total, non_null)
                counts.append(total)

        _run_threads([writer, scanner, scanner])
        assert qe.execute_one("SELECT count(*) FROM m").rows()[0][0] == \
            self.ROUNDS * 20
        # each scanner saw monotonically non-decreasing counts
        # (counts interleave between scanners; global sortedness isn't
        # required — only that nothing went backwards catastrophically
        # below zero or above the final total)
        assert all(0 <= c <= self.ROUNDS * 20 for c in counts)

    def test_concurrent_writers_unique_seqs(self, qe):
        """Parallel INSERTs must not collide on WAL sequences (lost
        updates); every row must survive a restart replay."""
        def writer(base):
            def run():
                for i in range(self.ROUNDS):
                    qe.execute_one(
                        f"INSERT INTO m VALUES ('w{base}', {i}.0, "
                        f"{base * 1_000_000 + i})")
            return run

        _run_threads([writer(b) for b in range(4)])
        assert qe.execute_one("SELECT count(*) FROM m").rows()[0][0] == \
            4 * self.ROUNDS
        info = qe.catalog.table("public", "m")
        rid = info.region_ids[0]
        region = qe.region_engine.region(rid)
        # WAL seqs must be unique: replay and count
        seqs = [e.seq for e in region.wal.replay(rid)]
        assert len(seqs) == len(set(seqs))

    def test_scans_during_flush_and_compact(self, qe):
        """Flush + compaction racing scans: file swaps must not break an
        in-flight scan (grace-deferred purge)."""
        qe.execute_one(
            "INSERT INTO m VALUES " + ", ".join(
                f"('h{j}', 1.0, {j})" for j in range(50)))

        stop = threading.Event()

        def maintainer():
            for i in range(10):
                qe.execute_one(
                    "INSERT INTO m VALUES " + ", ".join(
                        f"('h{j}', 2.0, {10_000 + i * 100 + j})"
                        for j in range(20)))
                qe.execute_one("ADMIN flush_table('m')")
                qe.execute_one("ADMIN compact_table('m')")
            stop.set()

        def scanner():
            while not stop.is_set():
                r = qe.execute_one(
                    "SELECT host, count(*) FROM m GROUP BY host "
                    "ORDER BY host")
                assert r.num_rows >= 1

        _run_threads([maintainer, scanner, scanner])
        assert qe.execute_one("SELECT count(*) FROM m").rows()[0][0] == \
            50 + 10 * 20

    def test_on_demand_part_fetches_race_compaction(self, tmp_path):
        """ISSUE 25: aggregates whose scan is a plan fetch their parts
        AFTER the region lock is released — here with a cold partial
        cache every time, more threads than cores and a short switch
        interval, while a maintainer flushes new files and compacts
        them away. Every answer is one whole snapshot's (every row has
        v = 1, so sum(v) == count(*), never fewer than the rows
        acknowledged before it was asked), and when the dust settles no
        file is pinned and the purge queue drains."""
        import os
        import sys

        from greptimedb_tpu.query import partial_cache as pc

        engine = RegionEngine(EngineConfig(data_dir=str(tmp_path),
                                           maintenance_workers=0))
        q = QueryEngine(Catalog(MemoryKv()), engine)
        q.execute_one(
            "CREATE TABLE m (host STRING, v DOUBLE, ts TIMESTAMP TIME "
            "INDEX, PRIMARY KEY(host)) WITH (append_mode='true')")
        rid = q.catalog.table("public", "m").region_ids[0]
        acked = [0]

        def load(batch):
            q.execute_one("INSERT INTO m VALUES " + ", ".join(
                f"('h{j % 7}', 1.0, {batch * 1000 + j})"
                for j in range(40)))
            acked[0] += 40
            engine.flush(rid)

        for b in range(3):
            load(b)
        stop = threading.Event()

        def maintainer():
            for b in range(3, 11):
                load(b)
                if b % 2:
                    engine.compact(rid)
            stop.set()

        def scanner(k):
            # a text of its own: identical concurrent statements share
            # one execution (fast-lane single flight), and a follower's
            # answer is then as old as its leader's snapshot
            sql = f"SELECT count(*) AS c{k}, sum(v) FROM m"

            def run():
                while not stop.is_set():
                    scan_once(sql)
            return run

        def scan_once(sql):
            floor = acked[0]
            pc.global_cache().clear()  # every part must be fetched
            total, summed = q.execute_one(sql).rows()[0]
            assert total == summed, (total, summed)
            assert total >= floor, (total, floor)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)
        try:
            _run_threads([maintainer] + [
                scanner(k) for k in range((os.cpu_count() or 2) + 2)])
        finally:
            sys.setswitchinterval(old)
        assert q.execute_one("SELECT count(*) FROM m").rows()[0][0] == 440
        region = engine.region(rid)
        import gc

        gc.collect()
        assert not region._file_refs
        engine.close()
        assert not region._purge_queue

    def test_compacted_files_purged_on_close(self, qe, tmp_path):
        import glob

        maint = qe.region_engine.maintenance

        def admin(sql):
            # ADMIN is async job submission — wait for each job before
            # the next step: a flush still QUEUED when the second one
            # is submitted collapses with it into one job (one SST, so
            # the compaction below has nothing to merge and nothing to
            # purge), which is how this test failed on a loaded machine
            for row in qe.execute_one(sql).rows():
                maint.wait(int(row[0]), timeout=30)

        qe.execute_one("INSERT INTO m VALUES ('a', 1.0, 1000)")
        admin("ADMIN flush_table('m')")
        qe.execute_one("INSERT INTO m VALUES ('b', 2.0, 2000)")
        admin("ADMIN flush_table('m')")
        admin("ADMIN compact_table('m')")
        info = qe.catalog.table("public", "m")
        region = qe.region_engine.region(info.region_ids[0])
        # old files grace-held, not yet deleted
        assert region._purge_queue
        region.close()
        assert not region._purge_queue
        live = set(region.files)
        on_disk = {p.split("/")[-1].replace(".parquet", "")
                   for p in glob.glob(str(tmp_path) + "/**/sst/*.parquet",
                                      recursive=True)}
        assert on_disk == live

"""Frontend concurrency plane tests (concurrency/ package): the
shape-keyed parameterized plan cache and its invalidation under DDL and
rollup-state changes, bounded admission with per-tenant weighted fair
scheduling (a flooding tenant cannot starve a light one), typed
Overloaded rejection through the HTTP/MySQL error mapping. (Threaded
clients through the full frontend path, bit-for-bit with serial:
tests/test_serving_path.py.)"""

import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.catalog.catalog import Catalog
from greptimedb_tpu.catalog.kv import MemoryKv
from greptimedb_tpu.concurrency import (
    ConcurrencyConfig,
    ConcurrencyPlane,
    Overloaded,
)
from greptimedb_tpu.concurrency.admission import (
    AdmissionController,
    parse_weights,
)
from greptimedb_tpu.query.engine import QueryEngine
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine
from greptimedb_tpu.utils.metrics import (
    ADMISSION_EVENTS,
    PLAN_CACHE_EVENTS,
)


def make_qe(tmp_path, plane=None, **engine_cfg):
    engine_cfg.setdefault("maintenance_workers", 0)
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                       **engine_cfg))
    qe = QueryEngine(Catalog(MemoryKv()), engine, concurrency=plane)
    return engine, qe


def create_cpu(qe):
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
        "TIME INDEX, PRIMARY KEY(host))")


def ingest(qe, hosts=4, points=120, step_ms=1000, t0=0):
    rows = []
    for h in range(hosts):
        for i in range(points):
            rows.append(f"('h{h}', {float((h + 1) * (i % 7))}, "
                        f"{t0 + i * step_ms})")
    qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES " + ",".join(rows))


DASH_SQL = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, max(v), "
            "sum(v) FROM cpu WHERE host = '{host}' AND ts >= {lo} AND "
            "ts < {hi} GROUP BY minute")


def run_threads(fns, timeout=120):
    """Run fns concurrently; return per-fn results, raise on any error."""
    out = [None] * len(fns)
    errors = []
    barrier = threading.Barrier(len(fns))

    def wrap(i, fn):
        try:
            barrier.wait(timeout)
            out[i] = fn()
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(i, fn))
               for i, fn in enumerate(fns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    assert not errors, errors[:3]
    return out


# ---- plan cache ------------------------------------------------------------


class TestPlanCache:
    def test_shape_hit_rebinds_parameters(self, tmp_path):
        """2000 dashboard queries differing only in WHERE literals share
        ONE cache entry, and every rebind computes the RIGHT answer.
        (Fast lane off: the plan cache's own hit counter is asserted —
        with the lane on, repeats would be fast-lane hits instead.)"""
        engine, qe = make_qe(tmp_path, plane=ConcurrencyPlane(
            ConcurrencyConfig(fast_lane=False)))
        create_cpu(qe)
        ingest(qe)
        oracle = {}
        for host in ("h0", "h1", "h2"):
            for lo in (0, 60_000):
                sql = DASH_SQL.format(host=host, lo=lo, hi=lo + 60_000)
                oracle[sql] = qe.execute_one(sql).rows()
        assert len(qe.concurrency.plan_cache) == 1
        hits0 = PLAN_CACHE_EVENTS.get(event="hit")
        for sql, want in oracle.items():
            assert qe.execute_one(sql).rows() == want
        assert PLAN_CACHE_EVENTS.get(event="hit") - hits0 >= len(oracle)
        # distinct answers prove the rebind is real, not a stale replay
        assert len({repr(r) for r in oracle.values()}) > 1
        engine.close()

    def test_structural_values_are_distinct_shapes(self, tmp_path):
        """Literals OUTSIDE the WHERE clause (bucket width, LIMIT) change
        the plan structure — they must key separate entries."""
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe)
        a = ("SELECT date_bin(INTERVAL '1 minute', ts) AS b, max(v) "
             "FROM cpu WHERE ts >= 0 GROUP BY b")
        b = ("SELECT date_bin(INTERVAL '2 minutes', ts) AS b, max(v) "
             "FROM cpu WHERE ts >= 0 GROUP BY b")
        ra1, rb1 = qe.execute_one(a).rows(), qe.execute_one(b).rows()
        assert len(qe.concurrency.plan_cache) == 2
        assert qe.execute_one(a).rows() == ra1
        assert qe.execute_one(b).rows() == rb1
        assert ra1 != rb1
        engine.close()

    def test_capacity_eviction(self, tmp_path):
        plane = ConcurrencyPlane(ConcurrencyConfig(plan_cache_entries=2))
        engine, qe = make_qe(tmp_path, plane=plane)
        create_cpu(qe)
        ingest(qe, hosts=2, points=10)
        ev0 = PLAN_CACHE_EVENTS.get(event="evict")
        qe.execute_one("SELECT max(v) FROM cpu WHERE ts >= 0")
        qe.execute_one("SELECT min(v) FROM cpu WHERE ts >= 0")
        qe.execute_one("SELECT sum(v) FROM cpu WHERE ts >= 0")
        assert len(qe.concurrency.plan_cache) == 2
        assert PLAN_CACHE_EVENTS.get(event="evict") > ev0
        engine.close()

    @pytest.mark.parametrize("ddl", [
        "ALTER TABLE cpu ADD COLUMN extra DOUBLE",
        "TRUNCATE TABLE cpu",
        "DROP TABLE cpu",
    ])
    def test_ddl_invalidates_cached_shapes(self, tmp_path, ddl):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=30)
        sql = DASH_SQL.format(host="h0", lo=0, hi=60_000)
        qe.execute_one(sql)
        qe.execute_one(sql)
        assert len(qe.concurrency.plan_cache) == 1
        inv0 = PLAN_CACHE_EVENTS.get(event="invalidate")
        qe.execute_one(ddl)
        assert len(qe.concurrency.plan_cache) == 0
        assert PLAN_CACHE_EVENTS.get(event="invalidate") > inv0
        engine.close()

    def test_alter_star_expansion_not_stale(self, tmp_path):
        """A cached `SELECT *` shape must not survive ALTER ADD COLUMN:
        the post-DDL query expands the NEW column set."""
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=5)
        sql = "SELECT * FROM cpu WHERE ts >= 0 AND ts < 10000"
        before = qe.execute_one(sql)
        qe.execute_one(sql)
        qe.execute_one("ALTER TABLE cpu ADD COLUMN extra DOUBLE")
        after = qe.execute_one(sql)
        assert "extra" not in before.names
        assert "extra" in after.names
        engine.close()

    def test_truncate_then_drop_create_serve_fresh_plans(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=30)
        sql = "SELECT count(*) FROM cpu WHERE ts >= 0"
        assert qe.execute_one(sql).rows() == [[60]]
        qe.execute_one("TRUNCATE TABLE cpu")
        assert qe.execute_one(sql).rows() == [[0]]
        qe.execute_one("DROP TABLE cpu")
        # same name, different schema: the old shape must not rebind
        qe.execute_one(
            "CREATE TABLE cpu (host STRING, v DOUBLE, w DOUBLE, "
            "ts TIMESTAMP(3) TIME INDEX, PRIMARY KEY(host))")
        qe.execute_one(
            "INSERT INTO cpu (host, v, w, ts) VALUES ('h9', 1.0, 2.0, 5)")
        assert qe.execute_one(sql).rows() == [[1]]
        assert qe.execute_one(
            "SELECT w FROM cpu WHERE ts >= 0").rows() == [[2.0]]
        engine.close()

    def test_remote_ddl_caught_by_snapshot_comparison(self, tmp_path):
        """A DDL executed by ANOTHER engine over the same catalog (a
        peer frontend) never fires this engine's explicit invalidation —
        the per-hit TableInfo content check is the safety net."""
        engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "d"),
                                           maintenance_workers=0))
        catalog = Catalog(MemoryKv())
        qe1 = QueryEngine(catalog, engine)
        qe2 = QueryEngine(catalog, engine)
        create_cpu(qe1)
        ingest(qe1, hosts=2, points=5)
        sql = "SELECT * FROM cpu WHERE ts >= 0 AND ts < 10000"
        qe1.execute_one(sql)
        qe1.execute_one(sql)
        assert len(qe1.concurrency.plan_cache) == 1
        inv0 = PLAN_CACHE_EVENTS.get(event="invalidate")
        qe2.execute_one("ALTER TABLE cpu ADD COLUMN extra DOUBLE")
        after = qe1.execute_one(sql)  # qe1 never saw the ALTER
        assert "extra" in after.names
        assert PLAN_CACHE_EVENTS.get(event="invalidate") > inv0
        engine.close()

    def test_rollup_state_change_reprobes_substitution(self, tmp_path):
        """The cached entry memoizes 'substitution ineligible' — a
        finished roll must evict that memo, not keep serving raw scans
        for a now-substitutable shape."""
        engine, qe = make_qe(tmp_path, maintenance_workers=1,
                             rollup_rules=[{"resolution_ms": 60_000}])
        create_cpu(qe)
        ingest(qe, hosts=3, points=180)
        maint = qe.region_engine.maintenance
        for r in qe.execute_one("ADMIN flush_table('cpu')").rows():
            maint.wait(int(r[0]), timeout=30)
        sql = ("SELECT host, max(v), count(v) FROM cpu WHERE ts >= 0 AND "
               "ts < 120000 GROUP BY host ORDER BY host")
        # warm the shape BEFORE any rollup exists: memoizes skip-probe
        first = qe.execute_one(sql)
        qe.execute_one(sql)
        assert "+rollup" not in (qe.executor.last_path or "")
        jobs = [maint.wait(int(r[0]), timeout=30) for r in
                qe.execute_one("ADMIN rollup_table('cpu', '1m')").rows()]
        assert all(j.state == "done" for j in jobs), [j.error for j in jobs]
        got = qe.execute_one(sql)
        assert "+rollup" in (qe.executor.last_path or "")
        assert got.rows() == first.rows()
        engine.close()


# ---- admission control + fairness ------------------------------------------


class TestAdmission:
    def test_parse_weights(self):
        assert parse_weights("a=3, b=1,bad, c=x,=2") == {"a": 3, "b": 1}
        assert parse_weights("") == {}

    def test_queue_full_rejects_typed(self):
        ac = AdmissionController(1, queue_size=0)
        with ac.slot("t"):
            def blocked():
                # a second thread: the outer slot is thread-local
                def go():
                    with ac.slot("t"):
                        pass
                with pytest.raises(Overloaded):
                    go()
            run_threads([blocked])

    def test_queue_timeout_rejects_typed(self):
        """The queued request is rejected Overloaded after its timeout
        and before the holder releases. Asserted by the order of
        events: the holder holds until the rejection has been seen,
        however long a loaded machine takes to get there; the one
        clock read is a lower bound, which load cannot break."""
        timeout_s = 0.05
        ac = AdmissionController(1, queue_size=4,
                                 queue_timeout_s=timeout_s)
        held, release = threading.Event(), threading.Event()
        events = []

        def holder():
            with ac.slot("t"):
                events.append("held")
                held.set()
                release.wait()
            events.append("released")

        t = threading.Thread(target=holder)
        t.start()
        try:
            assert held.wait(120)
            before = {e: ADMISSION_EVENTS.get(event=e, tenant="t")
                      for e in ("queue", "reject_timeout")}
            t0 = time.perf_counter()
            with pytest.raises(Overloaded):
                with ac.slot("t"):
                    events.append("admitted")
            waited = time.perf_counter() - t0
            events.append("rejected")
            # rejected while the holder still held: the slot is taken,
            # the queue has let the waiter go
            assert ac.active == 1 and ac.queued == 0
            assert not release.is_set()
        finally:
            release.set()
            t.join(120)
        assert events == ["held", "rejected", "released"]
        assert waited >= timeout_s
        for e in ("queue", "reject_timeout"):
            assert ADMISSION_EVENTS.get(event=e, tenant="t") \
                == before[e] + 1, e

    def test_env_kill_switch_disables_the_plane(self, monkeypatch):
        monkeypatch.setenv("GTPU_CONCURRENCY", "0")
        plane = ConcurrencyPlane()
        assert not plane.admission.enabled
        assert not plane.plan_cache.enabled
        assert not plane.fast_lane.enabled

    def test_nested_statements_ride_the_outer_slot(self):
        ac = AdmissionController(1, queue_size=0)
        with ac.slot("t"):
            with ac.slot("t"):  # would deadlock if it re-acquired
                assert ac.depth() == 2
            assert ac.depth() == 1

    def test_slot_handoff_keeps_limit(self):
        ac = AdmissionController(2, queue_size=64)
        seen = []
        lock = threading.Lock()

        def worker():
            with ac.slot("t"):
                with lock:
                    seen.append(ac.active)
                time.sleep(0.002)

        run_threads([worker] * 16)
        assert max(seen) <= 2
        assert ac.active == 0 and ac.queued == 0

    def test_flooding_tenant_cannot_starve_light_tenant(self):
        """One slot, tenant `flood` parks a deep backlog, tenant `light`
        issues sequential queries: WRR must serve light after at most
        ~one turn, so light's p99 wait stays a small multiple of the
        work quantum while flood's backlog p99 is the whole drain."""
        ac = AdmissionController(1, queue_size=256, queue_timeout_s=60)
        quantum = 0.004
        flood_waits, light_waits = [], []
        lock = threading.Lock()

        def flood_one():
            t0 = time.perf_counter()
            with ac.slot("flood"):
                with lock:
                    flood_waits.append(time.perf_counter() - t0)
                time.sleep(quantum)

        def light_seq():
            # let the flood stack up first
            while ac.queued < 20:
                time.sleep(0.001)
            for _ in range(8):
                t0 = time.perf_counter()
                with ac.slot("light"):
                    light_waits.append(time.perf_counter() - t0)
                    time.sleep(quantum)

        run_threads([flood_one] * 40 + [light_seq])
        assert len(light_waits) == 8 and len(flood_waits) == 40
        p99_light = float(np.percentile(light_waits, 99))
        p99_flood = float(np.percentile(flood_waits, 99))
        # flood's tail waits the drain (~40 quanta); light never waits
        # more than a few quanta — assert a bounded ratio with slack
        assert p99_light < p99_flood / 3, (p99_light, p99_flood)

    def test_engine_overload_raises_typed(self, tmp_path):
        plane = ConcurrencyPlane(ConcurrencyConfig(
            max_concurrency=1, queue_size=0))
        engine, qe = make_qe(tmp_path, plane=plane)
        create_cpu(qe)
        ingest(qe, hosts=2, points=10)
        release = threading.Event()
        entered = threading.Event()

        def holder():
            with qe.concurrency.admission.slot("big"):
                entered.set()
                release.wait(10)

        t = threading.Thread(target=holder)
        t.start()
        entered.wait(10)
        try:
            with pytest.raises(Overloaded):
                qe.execute_one("SELECT count(*) FROM cpu")
        finally:
            release.set()
            t.join(10)
        # slot free again: the statement goes through
        assert qe.execute_one("SELECT count(*) FROM cpu").rows() == [[20]]
        engine.close()

    def test_http_maps_overload_to_503(self, tmp_path):
        from greptimedb_tpu.servers.http import HttpServer

        plane = ConcurrencyPlane(ConcurrencyConfig(
            max_concurrency=1, queue_size=0))
        engine, qe = make_qe(tmp_path, plane=plane)
        create_cpu(qe)
        ingest(qe, hosts=2, points=10)
        srv = HttpServer(qe, port=0)
        try:
            port = srv.start()
            release = threading.Event()
            entered = threading.Event()

            def holder():
                with qe.concurrency.admission.slot("big"):
                    entered.set()
                    release.wait(10)

            t = threading.Thread(target=holder)
            t.start()
            entered.wait(10)
            body = urllib.parse.urlencode(
                {"sql": "SELECT count(*) FROM cpu"}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/sql", data=body,
                headers={"X-Greptime-Tenant": "small"})
            try:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=30)
                assert ei.value.code == 503
            finally:
                release.set()
                t.join(10)
        finally:
            srv.stop()
        engine.close()

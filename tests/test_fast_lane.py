"""Parse-free serving fast lane (concurrency/fast_lane.py, ISSUE 14):
the literal scanner, probe-verified binders, byte-for-byte parity with
the slow lane across HTTP/MySQL/Postgres, DDL-invalidation races, the
typed fallback matrix, the sharded hot counters, the lock-light
admission fast path, and the columnar INSERT seam."""

import json
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.catalog.catalog import Catalog
from greptimedb_tpu.catalog.kv import MemoryKv
from greptimedb_tpu.concurrency import ConcurrencyConfig, ConcurrencyPlane
from greptimedb_tpu.concurrency import fast_lane as fl
from greptimedb_tpu.concurrency.admission import AdmissionController
from greptimedb_tpu.query.engine import QueryEngine
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine
from greptimedb_tpu.utils.metrics import FAST_LANE_EVENTS


def make_qe(tmp_path, plane=None, sub="a"):
    engine = RegionEngine(EngineConfig(
        data_dir=str(tmp_path / f"data_{sub}"), maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), engine, concurrency=plane)
    return engine, qe


def create_cpu(qe):
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
        "TIME INDEX, PRIMARY KEY(host))")


def ingest(qe, hosts=4, points=60):
    rows = []
    for h in range(hosts):
        for i in range(points):
            rows.append(f"('h{h}', {float((h + 1) * (i % 7))}, "
                        f"{i * 1000})")
    qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                   + ",".join(rows))


DASH = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, max(v), "
        "sum(v) FROM cpu WHERE host = '{host}' AND ts >= {lo} AND "
        "ts < {hi} GROUP BY minute")


def events():
    out: dict = {}
    for key, v in FAST_LANE_EVENTS._snapshot().items():
        e = dict(key)["event"]
        out[e] = out.get(e, 0) + v
    return out


# ---- scanner ----------------------------------------------------------------


class TestScanner:
    def test_rotating_literals_share_a_template(self):
        a, err = fl.scan("SELECT max(v) FROM cpu WHERE host = 'h1' "
                         "AND ts >= 1000 AND ts < 2000")
        b, err2 = fl.scan("SELECT max(v) FROM cpu WHERE host = 'h2' "
                          "AND ts >= 5000 AND ts < 9000")
        assert err is None and err2 is None
        assert a[0] == b[0]
        assert a[1] == ["h1", 1000, 2000]
        assert b[1] == ["h2", 5000, 9000]

    def test_value_types_match_the_parser(self):
        scanned, _ = fl.scan(
            "SELECT 1 WHERE a = 5 AND b = 5.5 AND c = 1e3 AND d = .5")
        assert scanned[1] == [1, 5, 5.5, 1000.0, 0.5]
        assert [type(v) for v in scanned[1]] \
            == [int, int, float, float, float]

    def test_identifier_digits_are_not_literals(self):
        scanned, _ = fl.scan("SELECT v2 FROM t1 WHERE host_1 = 3")
        assert scanned[1] == [3]

    def test_quoted_identifiers_stay_in_the_template(self):
        scanned, _ = fl.scan('SELECT "col2" FROM cpu WHERE "t5" = 7')
        assert scanned[1] == [7]
        assert '"col2"' in scanned[0] and '"t5"' in scanned[0]

    @pytest.mark.parametrize("sql,reason", [
        ("SELECT 1 -- trailing comment", "comment"),
        ("SELECT /* inline */ 1", "comment"),
        ("SELECT 'it''s' FROM cpu", "quoted_literal"),
        ("INSERT INTO cpu VALUES (1)", "non_select"),
        ("DROP TABLE cpu", "non_select"),
        ("SELECT 1; SELECT 2", "multi_statement"),
        ("SELECT '\x00'", "ambiguous"),
        ("SELECT " + "1," * 3000 + "2", "ambiguous"),
    ])
    def test_ambiguity_falls_back_typed(self, sql, reason):
        scanned, err = fl.scan(sql)
        assert scanned is None and err == reason

    def test_comment_marker_inside_string_is_fine(self):
        scanned, err = fl.scan("SELECT 1 WHERE a = '--not a comment'")
        assert err is None
        assert scanned[1] == [1, "--not a comment"]

    def test_trailing_semicolon_is_single_statement(self):
        scanned, err = fl.scan("SELECT max(v) FROM cpu ;")
        assert err is None


# ---- engine integration -----------------------------------------------------


class TestFastLaneServing:
    def test_hit_rebinds_and_matches_slow_lane(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe)
        sqls = [DASH.format(host=f"h{h}", lo=lo, hi=lo + 60_000)
                for h in range(3) for lo in (0, 10_000)]
        # first sighting marks the template, the second builds it
        for s in sqls:
            qe.execute_one(s)
        built = {s: qe.execute_one(s) for s in sqls}
        h0 = events().get("hit", 0)
        for s, want in built.items():
            got = qe.execute_one(s)
            slow = qe._execute_sql_slow(s, QueryContext())[-1]
            assert got.names == want.names == slow.names
            assert got.rows() == want.rows() == slow.rows()
        assert events().get("hit", 0) - h0 >= len(sqls)
        # distinct answers prove the rebind is real
        assert len({repr(r.rows()) for r in built.values()}) > 1
        engine.close()

    def test_negative_and_string_literals_bind(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       "('a', -5.0, 1000), ('b', 3.0, 2000)")
        q = "SELECT host FROM cpu WHERE v > -6.0 AND ts >= 0 ORDER BY host"
        assert qe.execute_one(q).rows() == [["a"], ["b"]]
        q2 = "SELECT host FROM cpu WHERE v > -4.0 AND ts >= 0 ORDER BY host"
        assert qe.execute_one(q2).rows() == [["b"]]  # hit: -4 rebinds
        engine.close()

    def test_structural_values_pin_per_variant(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe)
        ql = "SELECT host, max(v) FROM cpu GROUP BY host ORDER BY host LIMIT {n}"
        assert len(qe.execute_one(ql.format(n=2)).rows()) == 2
        assert len(qe.execute_one(ql.format(n=2)).rows()) == 2
        # same template, new LIMIT: must NOT serve the LIMIT-2 plan
        assert len(qe.execute_one(ql.format(n=3)).rows()) == 3
        assert len(qe.execute_one(ql.format(n=3)).rows()) == 3
        qi = ("SELECT date_bin(INTERVAL '{iv}', ts) AS m, count(v) "
              "FROM cpu GROUP BY m ORDER BY m LIMIT 2")
        minute = qe.execute_one(qi.format(iv="1 minute"))
        qe.execute_one(qi.format(iv="1 minute"))
        second = qe.execute_one(qi.format(iv="30 seconds"))
        assert minute.rows() != second.rows()
        slow = qe._execute_sql_slow(qi.format(iv="30 seconds"),
                                    QueryContext())[-1]
        assert second.rows() == slow.rows()
        engine.close()

    def test_boolean_literals_are_constant_params(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        qe.execute_one("CREATE TABLE flags (host STRING, ok BOOLEAN, ts "
                       "TIMESTAMP(3) TIME INDEX, PRIMARY KEY(host))")
        qe.execute_one("INSERT INTO flags (host, ok, ts) VALUES "
                       "('a', true, 1000), ('b', false, 2000)")
        q = "SELECT host FROM flags WHERE ok = true AND ts >= {lo}"
        assert qe.execute_one(q.format(lo=0)).rows() == [["a"]]
        assert qe.execute_one(q.format(lo=500)).rows() == [["a"]]
        assert qe.execute_one(q.format(lo=1500)).rows() == []
        engine.close()

    def test_ddl_invalidates_before_next_request(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=5)
        sql = "SELECT * FROM cpu WHERE ts >= 0 AND ts < 10000"
        qe.execute_one(sql)
        qe.execute_one(sql)  # fast-lane hit
        qe.execute_one("ALTER TABLE cpu ADD COLUMN extra DOUBLE")
        after = qe.execute_one(sql)
        assert "extra" in after.names
        engine.close()

    def test_remote_style_ddl_caught_by_info_check(self, tmp_path):
        """DDL that bypasses this engine's hooks (another frontend's
        ALTER) is caught by the per-hit TableInfo snapshot check."""
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=5)
        sql = "SELECT * FROM cpu WHERE ts >= 0 AND ts < 10000"
        qe.execute_one(sql)
        qe.execute_one(sql)
        # mutate the catalog behind the plane's back (no invalidation
        # hook fires): fast lane must notice via _info_matches
        from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema
        from greptimedb_tpu.datatypes.types import DataType, SemanticType
        info = qe.catalog.table("public", "cpu")
        new_schema = Schema(list(info.schema.columns) + [
            ColumnSchema("extra", DataType.FLOAT64, SemanticType.FIELD,
                         True)])
        for rid in info.region_ids:
            qe.region_engine.alter_region_schema(rid, new_schema)
        info.schema = new_schema
        qe.catalog.update_table(info)
        inv0 = events().get("invalidate", 0)
        after = qe.execute_one(sql)
        assert "extra" in after.names
        assert events().get("invalidate", 0) > inv0
        engine.close()

    def test_alter_race_between_hit_and_execute(self, tmp_path):
        """An ALTER landing after the template hit but before execute:
        the request must not crash, and the NEXT request serves the new
        schema — identical to the slow lane's plan-cache race window."""
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=5)
        sql = "SELECT host, v FROM cpu WHERE ts >= 0 AND ts < 10000"
        qe.execute_one(sql)
        qe.execute_one(sql)
        lane = qe.concurrency.fast_lane
        orig = lane._bind_execute
        fired = []

        def racing(qe_, entry, params):
            if not fired:
                fired.append(True)
                qe.execute_one("ALTER TABLE cpu ADD COLUMN extra DOUBLE")
            return orig(qe_, entry, params)

        lane._bind_execute = racing
        try:
            mid = qe.execute_one(sql)  # races the ALTER; must not crash
            assert mid.names == ["host", "v"]
        finally:
            lane._bind_execute = orig
        after = qe.execute_one("SELECT * FROM cpu WHERE ts >= 0 "
                               "AND ts < 10000")
        assert "extra" in after.names
        engine.close()

    def test_drop_and_recreate_serves_fresh(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=5)
        sql = "SELECT count(v) FROM cpu WHERE ts >= 0"
        assert qe.execute_one(sql).rows() == [[10]]
        assert qe.execute_one(sql).rows() == [[10]]
        qe.execute_one("DROP TABLE cpu")
        create_cpu(qe)
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       "('x', 1.0, 1000)")
        assert qe.execute_one(sql).rows() == [[1]]
        engine.close()

    def test_rollup_state_change_falls_back_until_reprobed(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe)
        sql = DASH.format(host="h0", lo=0, hi=60_000)
        qe.execute_one(sql)  # mark
        want = qe.execute_one(sql).rows()  # build
        h0 = events().get("hit", 0)
        assert qe.execute_one(sql).rows() == want  # hit
        assert events().get("hit", 0) == h0 + 1
        from greptimedb_tpu.maintenance import rollup

        rollup._bump_substitution_state()
        f0 = events().get("fallback", 0)
        assert qe.execute_one(sql).rows() == want  # slow lane re-probes
        assert events().get("fallback", 0) == f0 + 1
        # the re-probe re-stamped the shared plan-cache entry: hits resume
        assert qe.execute_one(sql).rows() == want
        assert events().get("hit", 0) == h0 + 2
        engine.close()

    def test_session_funcs_never_template(self, tmp_path):
        """database() depends on the session — the text cannot key the
        plan, so the template must go (and stay) uncacheable."""
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       "('a', 1.0, 1000)")
        sql = "SELECT database() AS db, host FROM cpu WHERE ts >= 0"
        r1 = qe.execute_one(sql)
        r2 = qe.execute_one(sql)
        assert r1.rows() == r2.rows() == [["public", "a"]]
        assert len(qe.concurrency.fast_lane) == 0
        engine.close()

    def test_session_timezone_binds_per_request(self, tmp_path):
        """Naive string timestamp literals coerce in the SESSION
        timezone at bind time: the same text from differently zoned
        sessions must produce different (correct) answers, and the
        single-flight must not share across zones."""
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        # rows at epoch 0h and 2h (UTC)
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       "('a', 1.0, 0), ('b', 2.0, 7200000)")
        sql = ("SELECT host FROM cpu WHERE ts >= '1970-01-01 01:00:00' "
               "ORDER BY host")
        for _ in range(2):  # second round: fast-lane hits
            assert qe.execute_sql(sql, QueryContext(
                timezone="UTC"))[-1].rows() == [["b"]]
            # 01:00 at +02:00 is 23:00Z the day before: both rows match
            assert qe.execute_sql(sql, QueryContext(
                timezone="+02:00"))[-1].rows() == [["a"], ["b"]]
        engine.close()

    def test_pinned_churn_marks_template_uncacheable(self, tmp_path):
        """A pinned slot rotating per request (ever-changing LIMIT)
        must not pay a probe rebuild forever — the churn guard marks
        the template uncacheable after the variant list saturates."""
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe)
        lane = qe.concurrency.fast_lane
        ql = "SELECT host, max(v) FROM cpu GROUP BY host ORDER BY host LIMIT {n}"
        for n in range(1, 50):
            r = qe.execute_one(ql.format(n=n))
            assert len(r.rows()) == min(n, 4)  # 4 hosts
        key = next(iter(lane._templates))
        assert lane._templates[key].uncacheable
        # still serves correctly through the slow lane
        assert len(qe.execute_one(ql.format(n=2)).rows()) == 2
        engine.close()

    def test_first_sighting_marks_second_builds(self, tmp_path):
        """A never-repeated ad-hoc statement must not pay the O(slots)
        probe build — entries appear on the SECOND sighting."""
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=5)
        sql = "SELECT count(v) FROM cpu WHERE ts >= 0"
        qe.execute_one(sql)
        assert len(qe.concurrency.fast_lane) == 0  # marked, not built
        qe.execute_one(sql)
        assert len(qe.concurrency.fast_lane) == 1  # built
        engine.close()

    def test_interceptor_chain_runs_exactly_once(self, tmp_path):
        """Auditing interceptors count invocations: the fast lane must
        not double-run the chain on misses/fallbacks, and a rewriting
        interceptor routes to the slow lane (one run, rewritten text)."""
        from greptimedb_tpu.plugins import Plugins

        engine, qe = make_qe(tmp_path)
        # a PRIVATE container: default_plugins() is a process-wide
        # singleton, and a registered rewriter would poison every
        # later test in this interpreter
        qe.plugins = Plugins()
        create_cpu(qe)
        ingest(qe, hosts=2, points=5)
        calls = []

        def audit(sql, ctx):
            calls.append(sql)
            return sql

        qe.plugins.register_sql_interceptor(audit)
        sql = "SELECT count(v) FROM cpu WHERE ts >= 0"
        for expected in (1, 2, 3, 4):  # mark, build, hit, hit
            qe.execute_one(sql)
            assert len(calls) == expected
        # non-SELECT fallback: still exactly one run
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       "('z', 1.0, 99000)")
        assert len(calls) == 5

        def rewrite(sql, ctx):
            calls.append(sql)
            return sql.replace("count(v)", "sum(v)")

        qe.plugins.register_sql_interceptor(rewrite)
        r = qe.execute_one(sql)
        # the rewritten text executed (sum, not count), chain ran once
        assert r.names == ["sum(v)"]
        assert calls[-2:] == [sql, sql]
        engine.close()

    def test_disabled_lane_is_inert(self, tmp_path):
        plane = ConcurrencyPlane(ConcurrencyConfig(fast_lane=False))
        engine, qe = make_qe(tmp_path, plane=plane)
        create_cpu(qe)
        ingest(qe, hosts=2, points=5)
        sql = "SELECT count(v) FROM cpu WHERE ts >= 0"
        qe.execute_one(sql)
        qe.execute_one(sql)
        assert len(qe.concurrency.fast_lane) == 0
        engine.close()


# ---- byte identity across protocols ----------------------------------------


class TestByteIdentity:
    def _twin_engines(self, tmp_path):
        """Two engines over identical data: one with the lane, one
        without — the oracle for byte-level response comparison."""
        fast = make_qe(tmp_path, plane=ConcurrencyPlane(
            ConcurrencyConfig()), sub="fast")
        slow = make_qe(tmp_path, plane=ConcurrencyPlane(
            ConcurrencyConfig(fast_lane=False)), sub="slow")
        for _, qe in (fast, slow):
            create_cpu(qe)
            ingest(qe)
        return fast, slow

    def test_http_payload_bytes_identical(self, tmp_path):
        from greptimedb_tpu.servers.encode import encode_sql_payload

        (ef, qf), (es, qs) = self._twin_engines(tmp_path)
        sqls = [DASH.format(host=f"h{h}", lo=lo, hi=lo + 60_000)
                for h in range(2) for lo in (0, 10_000)]
        for s in sqls * 3:  # round 1 marks, 2 builds, 3 hits
            bf = encode_sql_payload(qf.execute_sql(s, QueryContext()), 1.0)
            bs = encode_sql_payload(qs.execute_sql(s, QueryContext()), 1.0)
            assert bf == bs
        ef.close()
        es.close()

    def test_mysql_and_postgres_wire_parity(self, tmp_path):
        from greptimedb_tpu.servers.mysql import MysqlServer
        from greptimedb_tpu.servers.postgres import PostgresServer
        from tests.test_wire_protocols import MiniMysql, MiniPg

        (ef, qf), (es, qs) = self._twin_engines(tmp_path)
        servers, clients = [], []
        try:
            pairs = []
            for qe in (qf, qs):
                ms = MysqlServer(qe, port=0)
                ms.start()
                ps = PostgresServer(qe, port=0)
                ps.start()
                servers += [ms, ps]
                my = MiniMysql(ms.port)
                pg = MiniPg(ps.port)
                clients += [my, pg]
                pairs.append((my, pg))
            (my_f, pg_f), (my_s, pg_s) = pairs
            sqls = [DASH.format(host="h0", lo=0, hi=60_000),
                    "SELECT host, v FROM cpu WHERE ts >= 1000 AND "
                    "ts < 9000 ORDER BY host, ts"]
            for s in sqls * 2:
                assert my_f.query(s) == my_s.query(s)
                assert pg_f.query(s) == pg_s.query(s)
        finally:
            for c in clients:
                c.close()
            for srv in servers:
                srv.shutdown()
            ef.close()
            es.close()

    def test_threaded_50_client_parity(self, tmp_path):
        """50 concurrent HTTP clients on a fast-lane server: every
        response must equal the idle-server slow-lane response."""
        from greptimedb_tpu.servers.http import HttpServer

        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe)
        sqls = [DASH.format(host=f"h{h}", lo=lo, hi=lo + 60_000)
                for h in range(4) for lo in (0, 10_000, 20_000)]
        oracle = {}
        for s in sqls:
            r = qe._execute_sql_slow(s, QueryContext())[-1]
            oracle[s] = (list(r.names), r.rows())
        srv = HttpServer(qe, host="127.0.0.1", port=0)
        errors = []
        try:
            port = srv.start()
            url = f"http://127.0.0.1:{port}/v1/sql"

            def client(i):
                try:
                    for k in range(6):
                        s = sqls[(i + k) % len(sqls)]
                        body = urllib.parse.urlencode({"sql": s}).encode()
                        with urllib.request.urlopen(
                                urllib.request.Request(url, data=body),
                                timeout=120) as resp:
                            payload = json.loads(resp.read())
                        rec = payload["output"][0]["records"]
                        names = [c["name"]
                                 for c in rec["schema"]["column_schemas"]]
                        want_names, want_rows = oracle[s]
                        assert names == want_names
                        assert len(rec["rows"]) == len(want_rows)
                        for got, want in zip(rec["rows"], want_rows):
                            assert got == [
                                None if (isinstance(v, float) and v != v)
                                else v for v in want]
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(50)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(180)
        finally:
            srv.stop()
        assert not errors, errors[:3]
        hits = events().get("hit", 0)
        assert hits > 0
        engine.close()


# ---- sharded hot counters ---------------------------------------------------


class TestShardedCounters:
    def test_concurrent_incs_never_lose_counts(self):
        from greptimedb_tpu.utils.metrics import ShardedCounter

        c = ShardedCounter("greptimedb_tpu_test_shard_total", "test")
        n_threads, per = 16, 5000

        def work():
            for _ in range(per):
                c.inc(kind="a")
                c.inc(2.0, kind="b")

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.get(kind="a") == n_threads * per
        assert c.get(kind="b") == 2.0 * n_threads * per
        assert c.total() == 3.0 * n_threads * per

    def test_dead_thread_shard_folds_into_base(self):
        from greptimedb_tpu.utils.metrics import ShardedCounter

        c = ShardedCounter("greptimedb_tpu_test_fold_total", "test")
        t = threading.Thread(target=lambda: c.inc(5.0, kind="x"))
        t.start()
        t.join()
        del t
        import gc

        gc.collect()
        deadline = time.monotonic() + 5
        while c.shard_count() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert c.shard_count() == 0  # folded by the finalizer
        assert c.get(kind="x") == 5.0

    def test_render_merges_shards(self):
        from greptimedb_tpu.utils.metrics import ShardedCounter

        c = ShardedCounter("greptimedb_tpu_test_render_total", "test")
        c.inc(kind="a")
        lines = c.render()
        assert 'greptimedb_tpu_test_render_total{kind="a"} 1.0' in lines


# ---- admission fast path ----------------------------------------------------


class TestAdmissionFastPath:
    def test_uncontended_grab_and_release(self):
        ac = AdmissionController(4, queue_size=8)
        with ac.slot("t"):
            assert ac.active == 1
            with ac.slot("t"):  # re-entrant: same thread, same slot
                assert ac.active == 1
        assert ac.active == 0 and ac.queued == 0

    def test_contended_handoff_bounds_active(self):
        ac = AdmissionController(2, queue_size=64, queue_timeout_s=30)
        seen = []
        gate = threading.Semaphore(0)

        def work():
            with ac.slot("t"):
                seen.append(ac.active)
                time.sleep(0.005)
            gate.release()

        threads = [threading.Thread(target=work) for _ in range(12)]
        for t in threads:
            t.start()
        for _ in range(12):
            assert gate.acquire(timeout=30)
        for t in threads:
            t.join(10)
        assert max(seen) <= 2
        assert ac.active == 0 and ac.queued == 0

    def test_no_lost_wakeup_under_churn(self):
        """Hammer the enqueue/release race window: every waiter must be
        served long before the 5s timeout (a lost wakeup would eat the
        full timeout and fail the wall-clock bound)."""
        ac = AdmissionController(1, queue_size=256, queue_timeout_s=5.0)
        done = []

        def work():
            for _ in range(60):
                with ac.slot("t"):
                    pass
            done.append(1)

        threads = [threading.Thread(target=work) for _ in range(8)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert len(done) == 8
        assert time.monotonic() - t0 < 20
        assert ac.active == 0 and ac.queued == 0

    def test_queue_full_raises_typed_overloaded(self):
        from greptimedb_tpu.concurrency import Overloaded

        ac = AdmissionController(1, queue_size=0)
        hold = threading.Event()
        release = threading.Event()

        def holder():
            with ac.slot("t"):
                hold.set()
                release.wait(10)

        t = threading.Thread(target=holder)
        t.start()
        assert hold.wait(10)
        try:
            with pytest.raises(Overloaded):
                with ac.slot("other"):
                    pass
        finally:
            release.set()
            t.join(10)


# ---- encode header memos ----------------------------------------------------


class TestEncodeMemos:
    def test_sql_payload_matches_whole_document_dumps(self, tmp_path):
        from greptimedb_tpu.query.result import QueryResult
        from greptimedb_tpu.servers.encode import (
            encode_sql_payload,
            records_json,
        )
        from greptimedb_tpu.datatypes.types import DataType

        r = QueryResult(
            ["h", "v"], [DataType.STRING, DataType.FLOAT64],
            [np.asarray(["a", "b"], dtype=object),
             np.asarray([1.5, float("nan")])])
        aff = QueryResult.of_affected(3)
        got = encode_sql_payload([aff, r], 12.345)
        want = json.dumps({
            "code": 0,
            "output": [{"affectedrows": 3},
                       {"records": records_json(r)}],
            "execution_time_ms": 12.345}).encode()
        assert got == want
        # second call rides the memoized schema header — still identical
        assert encode_sql_payload([aff, r], 12.345) == want

    def test_mysql_header_packets_memoized_and_identical(self):
        from greptimedb_tpu.servers.encode import (
            _coldef,
            _eof,
            encode_mysql_rows,
            lenc_int,
            MYSQL_TYPE_VAR_STRING,
        )

        names = ["a", "b"]
        rows = [["x", 1], [None, 2.5]]
        got = encode_mysql_rows(names, rows)
        want = [lenc_int(2), _coldef("a", MYSQL_TYPE_VAR_STRING),
                _coldef("b", MYSQL_TYPE_VAR_STRING), _eof()]
        assert got[:4] == want
        assert got[4] == b"\x01x" + b"\x011"
        assert got[5] == b"\xfb" + b"\x032.5"
        assert encode_mysql_rows(names, rows) == got

    def test_postgres_row_description_memoized(self):
        from greptimedb_tpu.datatypes.types import DataType
        from greptimedb_tpu.servers.postgres import _row_description

        a = _row_description(["h", "v"], [DataType.STRING,
                                          DataType.FLOAT64])
        b = _row_description(["h", "v"], [DataType.STRING,
                                          DataType.FLOAT64])
        assert a is b  # memo, not a rebuild


# ---- columnar INSERT seam ---------------------------------------------------


class TestColumnarInsert:
    def test_parser_emits_columnar_values(self):
        from greptimedb_tpu.sql import parse_sql

        stmts = parse_sql("INSERT INTO cpu (host, v, ts) VALUES "
                          "('a', 1.5, 1000), ('b', NULL, 2000), "
                          "('c', true, 3000)" + " " * 40)
        assert len(stmts) == 1
        ins = stmts[0]
        assert ins.columnar_values == [
            ["a", "b", "c"], [1.5, None, True], [1000, 2000, 3000]]
        assert ins.rows == []

    def test_columnar_and_expression_inserts_agree(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        # literal fast path (columnar) — padded past the 64-char gate
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       "('a', 1.5, 1000), ('b', 2.5, 2000)" + " " * 30)
        # expression path (full parser, per-cell evaluation)
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       "('c', 1.0 + 0.5, 3000)")
        r = qe.execute_one("SELECT host, v FROM cpu WHERE ts >= 0 "
                           "ORDER BY host")
        assert r.rows() == [["a", 1.5], ["b", 2.5], ["c", 1.5]]
        engine.close()

    def test_arity_mismatch_still_typed_error(self, tmp_path):
        from greptimedb_tpu.query.expr import PlanError

        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        with pytest.raises(PlanError):
            qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                           "('a', 1.5)" + " " * 60)
        engine.close()

    def test_null_time_index_rejected(self, tmp_path):
        from greptimedb_tpu.query.expr import PlanError

        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        with pytest.raises(PlanError, match="time index"):
            qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                           "('a', 1.5, NULL)" + " " * 50)
        engine.close()

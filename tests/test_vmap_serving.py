"""The serving path's encode seam and its guards: the columnar
result-encode path (byte-identical responses from concurrent clients,
the single flight's shared memo, admission slot released at
execute-done), typed-Overloaded bounds under burst, plan-cache
skip-reason visibility, and runtime lockdep over admission, the fast
lane and the single flight's lock."""

import json
import os
import subprocess
import sys
import threading
import urllib.parse

import numpy as np

from greptimedb_tpu.catalog.catalog import Catalog
from greptimedb_tpu.catalog.kv import MemoryKv
from greptimedb_tpu.concurrency import (
    ConcurrencyConfig,
    ConcurrencyPlane,
)
from greptimedb_tpu.query.engine import QueryEngine
from greptimedb_tpu.query.result import QueryResult
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine
from greptimedb_tpu.utils.metrics import (
    FAST_LANE_EVENTS,
    PLAN_CACHE_EVENTS,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_qe(tmp_path, plane=None, **engine_cfg):
    engine_cfg.setdefault("maintenance_workers", 0)
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                       **engine_cfg))
    qe = QueryEngine(Catalog(MemoryKv()), engine, concurrency=plane)
    return engine, qe


def create_cpu(qe, two_tags=False):
    if two_tags:
        qe.execute_one(
            "CREATE TABLE cpu (host STRING, dc STRING, v DOUBLE, "
            "ts TIMESTAMP(3) TIME INDEX, PRIMARY KEY(host, dc))")
    else:
        qe.execute_one(
            "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
            "TIME INDEX, PRIMARY KEY(host))")


def ingest(qe, hosts=4, dcs=0, points=120, step_ms=1000, seed=7):
    rng = np.random.default_rng(seed)
    rows = []
    for h in range(hosts):
        for d in range(max(dcs, 1)):
            for i in range(points):
                v = rng.uniform(0.0, 100.0)
                if dcs:
                    rows.append(f"('h{h}','dc{d}',{v!r},{i * step_ms})")
                else:
                    rows.append(f"('h{h}',{v!r},{i * step_ms})")
    cols = "(host, dc, v, ts)" if dcs else "(host, v, ts)"
    qe.execute_one(f"INSERT INTO cpu {cols} VALUES " + ",".join(rows))


def run_threads(fns, timeout=120):
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


# ---- the result-encode path ------------------------------------------------


def _legacy_json_rows(r: QueryResult) -> list:
    """The pre-columnar per-value encoder — the parity oracle."""
    import math

    def safe(v):
        if isinstance(v, float) and (math.isnan(v) or math.isinf(v)):
            return None
        if isinstance(v, (np.integer,)):
            return int(v)
        if isinstance(v, (np.floating,)):
            return float(v)
        return v

    return [[safe(v) for v in row] for row in r.rows()]


class TestEncodePath:
    def test_columnar_json_rows_parity(self):
        from greptimedb_tpu.datatypes.types import DataType
        from greptimedb_tpu.servers.encode import json_rows

        r = QueryResult(
            ["f", "i", "s", "b", "t"],
            [DataType.FLOAT64, DataType.INT64, DataType.STRING,
             DataType.BOOL, DataType.TIMESTAMP_MILLISECOND],
            [np.asarray([1.5, float("nan"), float("inf"),
                         float("-inf"), -0.0, 1e300]),
             np.asarray([1, -2, 3, 0, 7, 9], dtype=np.int64),
             np.asarray(["a", None, "c", "", "e", "f"], dtype=object),
             np.asarray([True, False, True, False, True, False]),
             np.asarray([0, 1, 2, 3, 4, 5], dtype=np.int64)])
        fast = json_rows(r)
        assert fast == _legacy_json_rows(r)
        # and the JSON bytes agree too (the wire contract)
        assert json.dumps(fast) == json.dumps(_legacy_json_rows(r))

    def test_encode_memo_shares_materialization(self, tmp_path):
        """The memo as the single flight makes it: a fast-lane hit's
        result carries one, and what the first encoder wrote is what
        every later encoder of that result gets."""
        from greptimedb_tpu.servers.encode import (
            json_rows,
            memo_rows,
            rows_json,
        )

        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=10)
        sql = "SELECT host, max(v) FROM cpu WHERE ts >= {} GROUP BY host"
        hits = FAST_LANE_EVENTS.get(event="hit")
        for lo in (0, 1000, 2000):
            r = qe.execute_one(sql.format(lo))
        assert FAST_LANE_EVENTS.get(event="hit") > hits
        assert r.encode_memo == {}
        first = json_rows(r)
        assert json_rows(r) is first  # memoized, not rebuilt
        rows = memo_rows(r)
        assert memo_rows(r) is rows
        written = rows_json(r)
        assert rows_json(r) is written
        assert set(r.encode_memo) == {"json_rows", "rows", "rows_json"}
        engine.close()

    def test_http_50_clients_byte_identical_to_idle_serial(self, tmp_path):
        """Threaded clients, each response encoded on its own request
        thread, get responses byte-identical to the idle-server serial
        path (only execution_time_ms may differ)."""
        import http.client

        from greptimedb_tpu.servers.http import HttpServer

        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe)
        srv = HttpServer(qe, port=0)
        try:
            port = srv.start()

            def fetch(sql):
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=60)
                try:
                    body = urllib.parse.urlencode({"sql": sql}).encode()
                    conn.request(
                        "POST", "/v1/sql", body=body,
                        headers={"Content-Type":
                                 "application/x-www-form-urlencoded"})
                    resp = conn.getresponse()
                    data = resp.read()
                    assert resp.status == 200, data[:200]
                    payload = json.loads(data)
                    payload.pop("execution_time_ms", None)
                    return json.dumps(payload, sort_keys=True)
                finally:
                    conn.close()

            sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
                   "max(v), avg(v) FROM cpu WHERE host = 'h{h}' AND "
                   "ts >= {lo} AND ts < {hi} GROUP BY minute")
            sqls = [sql.format(h=i % 4, lo=(i % 2) * 30_000,
                               hi=90_000 + (i % 2) * 30_000)
                    for i in range(50)]
            serial = {s: fetch(s) for s in set(sqls)}
            got = run_threads([lambda s=s: fetch(s) for s in sqls])
            for s, body in zip(sqls, got):
                assert body == serial[s], s
        finally:
            srv.stop()
        engine.close()

    def test_burst_overloaded_rates_bounded(self, tmp_path):
        """Burst past the admission bound: every
        failure is the typed 503 (code 5003), never a stack trace, and
        the server keeps serving at least its configured concurrency —
        no starvation regression vs the PR 6 contract."""
        import http.client

        from greptimedb_tpu.servers.http import HttpServer

        plane = ConcurrencyPlane(ConcurrencyConfig(
            max_concurrency=2, queue_size=2, queue_timeout_s=0.5))
        engine, qe = make_qe(tmp_path, plane=plane)
        create_cpu(qe)
        ingest(qe, hosts=2, points=60)
        srv = HttpServer(qe, port=0)
        try:
            port = srv.start()
            sql = ("SELECT host, sum(v) FROM cpu WHERE ts >= 0 "
                   "GROUP BY host")
            statuses = []
            bodies = []
            lock = threading.Lock()

            def client(i):
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=60)
                try:
                    body = urllib.parse.urlencode({"sql": sql}).encode()
                    conn.request(
                        "POST", "/v1/sql", body=body,
                        headers={"Content-Type":
                                 "application/x-www-form-urlencoded",
                                 "X-Greptime-Tenant": f"t{i % 4}"})
                    resp = conn.getresponse()
                    data = resp.read()
                    with lock:
                        statuses.append(resp.status)
                        bodies.append((resp.status, data))
                finally:
                    conn.close()

            run_threads([lambda i=i: client(i) for i in range(24)])
            n200 = statuses.count(200)
            n503 = statuses.count(503)
            assert n200 + n503 == len(statuses), statuses
            assert n200 >= 4  # bounded rejection, not collapse
            for status, data in bodies:
                if status == 503:
                    assert json.loads(data)["code"] == 5003
        finally:
            srv.stop()
        engine.close()

    def test_mysql_rows_encode_parity(self):
        from greptimedb_tpu.servers.encode import encode_mysql_rows

        rows = [[1, "a", None], [2.5, "b", float("nan")]]
        inline = encode_mysql_rows(["x", "y", "z"], rows)
        # text rows: a length-prefixed string a value, 0xfb a NULL / NaN
        assert inline[-3:-1] == [b"\x011\x01a\xfb", b"\x032.5\x01b\xfb"]
        binary = encode_mysql_rows(["x", "y", "z"], rows, True)
        assert binary != inline  # binary protocol really is distinct
        assert binary[0] == inline[0]  # same column count header


# ---- plan-cache skip visibility ---------------------------------------------


class TestPlanCacheSkipReasons:
    def test_skip_reasons_counted(self, tmp_path):
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=10)

        def delta(reason, sql):
            before = PLAN_CACHE_EVENTS.get(event="skip", reason=reason)
            qe.execute_one(sql)
            return PLAN_CACHE_EVENTS.get(event="skip",
                                         reason=reason) - before

        assert delta("join", "SELECT a.v FROM cpu a JOIN cpu b ON "
                             "a.ts = b.ts AND a.host = b.host") >= 1
        assert delta("cte", "WITH w AS (SELECT v FROM cpu) "
                            "SELECT * FROM w") >= 1
        assert delta("subquery",
                     "SELECT * FROM (SELECT v FROM cpu) d") >= 1
        assert delta("window",
                     "SELECT host, row_number() OVER "
                     "(PARTITION BY host ORDER BY ts) FROM cpu") >= 1
        # since ISSUE 44 a RANGE statement is planned, cached and bound
        # as any aggregate: no skip, and its second sighting is a hit
        rng = ("SELECT ts, host, min(v) RANGE '5s' FROM cpu "
               "ALIGN '5s' BY (host)")
        assert delta("range_select", rng) == 0
        hits = PLAN_CACHE_EVENTS.get(event="hit")
        assert delta("range_select", rng) == 0
        assert PLAN_CACHE_EVENTS.get(event="hit") == hits + 1
        # the top-level reason wins, once: a CTE whose body joins must
        # count ONE skip (cte), not one per recursive _select entry
        before = {r: PLAN_CACHE_EVENTS.get(event="skip", reason=r)
                  for r in ("cte", "join")}
        qe.execute_one(
            "WITH w AS (SELECT a.v AS v FROM cpu a JOIN cpu b ON "
            "a.ts = b.ts AND a.host = b.host) SELECT * FROM w")
        assert PLAN_CACHE_EVENTS.get(event="skip", reason="cte") \
            == before["cte"] + 1
        assert PLAN_CACHE_EVENTS.get(event="skip", reason="join") \
            == before["join"]
        engine.close()

    def test_skip_reason_in_slow_query_surfaces(self, tmp_path,
                                                monkeypatch):
        from greptimedb_tpu.utils import slow_query

        monkeypatch.setenv("GTPU_SLOW_QUERY_MS", "0.0001")
        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=10)
        slow_query.clear()
        qe.execute_one("WITH w AS (SELECT v FROM cpu) SELECT * FROM w")
        recs = slow_query.records()
        assert recs and recs[0].plan_cache_skip == "cte"
        assert recs[0].to_dict()["plan_cache_skip"] == "cte"
        # the information_schema detail column
        r = qe.execute_one(
            "SELECT plan_cache_skip FROM information_schema.slow_queries")
        assert "cte" in {v for v in r.columns[0].tolist()}
        engine.close()


# ---- runtime lockdep over the serving locks -------------------------------------


_LOCKDEP_SCRIPT = """
import tempfile, threading
import greptimedb_tpu
from greptimedb_tpu.lint import lockdep
assert lockdep.enabled(), "GTPU_LOCKDEP=1 did not install"

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.concurrency import ConcurrencyConfig, ConcurrencyPlane
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.servers.encode import encode_sql_payload
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine

with tempfile.TemporaryDirectory() as d:
    eng = RegionEngine(EngineConfig(data_dir=d, maintenance_workers=0))
    plane = ConcurrencyPlane(ConcurrencyConfig(max_concurrency=2))
    qe = QueryEngine(Catalog(MemoryKv()), eng, concurrency=plane)
    ctx = QueryContext(db="public")
    qe.execute_sql("CREATE TABLE t (host STRING, ts TIMESTAMP TIME INDEX,"
                   " v DOUBLE, PRIMARY KEY(host))", ctx)
    vals = ",".join(f"('h{i % 4}', {1700000000000 + i * 1000}, {i * 0.5})"
                    for i in range(240))
    qe.execute_sql(f"INSERT INTO t VALUES {vals}", ctx)
    errs = []
    def worker(k):
        try:
            for j in range(3):
                r = qe.execute_sql(
                    "SELECT host, count(*), sum(v) FROM t WHERE "
                    f"host = 'h{(k + j) % 4}' AND ts >= 1700000000000 "
                    "GROUP BY host", ctx)
                encode_sql_payload(r, 0.0)
        except Exception as e:
            errs.append(e)
    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(6)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert not errs, errs

rep = lockdep.assert_acyclic()
repo_edges = [e for e in rep["edges"]
              if all("greptimedb_tpu" in s for s in e)]
assert repo_edges, "no repo lock nesting observed"
print(f"LOCKDEP_EDGES={len(repo_edges)}")
"""


def test_runtime_lockdep_covers_admission_fast_lane_and_single_flight():
    """GTPU_LOCKDEP=1 over the serving path: threaded repeat-shape
    queries queueing for two admission slots, served by the fast lane
    (identical ones sharing a flight), their results serialized on the
    thread that ran them; the observed lock nesting (admission, the
    lane's template and flight locks, plan cache, metrics) must stay
    acyclic."""
    res = subprocess.run(
        [sys.executable, "-c", _LOCKDEP_SCRIPT],
        capture_output=True, text=True, timeout=480, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "GTPU_LOCKDEP": "1",
             "GTPU_SLOW_QUERY_MS": "600000"})
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "LOCKDEP_EDGES=" in res.stdout


def test_lint_scope_covers_serving_modules():
    """The static lockdep/blocking checkers must include the encode
    seam (concurrency/ itself is scope-prefixed, which covers
    admission.py, plan_cache.py and fast_lane.py)."""
    from greptimedb_tpu.lint.lockgraph import SCOPE_FILES, _in_scope

    assert "greptimedb_tpu/servers/encode.py" in SCOPE_FILES
    for mod in ("admission", "plan_cache", "fast_lane"):
        assert _in_scope(f"greptimedb_tpu/concurrency/{mod}.py")

"""Continuous profiling plane (ISSUE 17): the always-on flame sampler
(stage/path attribution, bounded windows, profiler-thread exclusion),
the per-statement ledger stamps (span / slow-query / ANALYZE, keyed on
the serving stages), the /v1/profile endpoints (auth, content types),
deterministic cluster merge, heartbeat piggyback, and the OTLP log lane
riding the trace exporter.
"""

from __future__ import annotations

import json
import logging
import threading
import time
import urllib.error
import urllib.request

import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils import (flame, ledger, otlp_trace, profiling,
                                  slow_query, tracing)


@pytest.fixture
def qe(tmp_path):
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data")))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    yield qe
    engine.close()


def _seed(qe, rows=64):
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP TIME INDEX, "
        "PRIMARY KEY(host))")
    vals = ", ".join(f"('h{i % 4}', {float(i)}, {1000 * (i + 1)})"
                     for i in range(rows))
    qe.execute_one(f"INSERT INTO cpu VALUES {vals}")


@pytest.fixture
def sampler_off():
    """Every test leaves the process sampler stopped and windows empty."""
    flame.shutdown()
    flame.reset()
    yield
    flame.shutdown()
    flame.reset()


def _spin_ms(ms: float) -> float:
    """Busy CPU loop the sampler can land on (no sleeps: sleeps are
    idle-filtered)."""
    t0 = time.perf_counter()
    x = 0.0
    while (time.perf_counter() - t0) * 1000 < ms:
        x += sum(i * i for i in range(200))
    return x


# ---- continuous sampler -----------------------------------------------------


class TestContinuousSampler:
    def test_attributes_stage_and_path(self, sampler_off):
        flame.configure(enabled=True, hz=250.0, window_s=30.0)
        tracing.set_trace(None)
        with tracing.span("stmt:Select"):
            flame.note_path("dense_fused")
            _spin_ms(600)
        folded = flame.folded()
        assert folded.startswith("# flame:")
        body = [ln for ln in folded.splitlines()[1:] if ln]
        assert body, "sampler captured nothing in 600 ms @ 250 Hz"
        attributed = [ln for ln in body
                      if ln.startswith("stage:stmt:Select;path:dense_fused;")]
        assert attributed, f"no attributed stacks in:\n{folded[:500]}"
        # the ISSUE acceptance: >=90% of samples attribute to the busy
        # stage in a controlled single-busy-thread scenario
        summ = flame.summary()
        assert summ["samples"] > 0
        assert summ["attributed"] / summ["samples"] >= 0.9
        assert summ["stages"].get("stmt", 0) > 0
        assert summ["paths"].get("dense_fused", 0) > 0

    def test_stage_filter_and_speedscope_document(self, sampler_off):
        flame.configure(enabled=True, hz=250.0)
        with tracing.span("stmt:Select"):
            _spin_ms(300)
        only = flame.folded(stage="stmt")
        assert all(ln.startswith(("#", "stage:stmt"))
                   for ln in only.splitlines() if ln)
        doc = flame.speedscope()
        assert doc["$schema"].endswith("file-format-schema.json")
        prof, = doc["profiles"]
        assert prof["type"] == "sampled"
        assert len(prof["samples"]) == len(prof["weights"])
        assert prof["endValue"] == sum(prof["weights"])
        names = {f["name"] for f in doc["shared"]["frames"]}
        assert any(n.startswith("stage:stmt") for n in names)

    def test_sampler_excludes_itself(self, sampler_off):
        flame.configure(enabled=True, hz=250.0)
        _spin_ms(300)
        folded = flame.folded()
        assert "_tick" not in folded
        assert "gtpu-flame-sampler" not in folded

    def test_disabled_hooks_are_cheap_noops(self, sampler_off):
        assert not flame.enabled()
        flame.push_stage("x")  # must not record anything while off
        flame.pop_stage()
        flame.note_path("y")
        assert flame.summary()["samples"] == 0

    def test_configure_retunes_and_shutdown_stops(self, sampler_off):
        flame.configure(enabled=True, hz=200.0)
        assert flame.running()
        t = next(th for th in threading.enumerate()
                 if th.name == "gtpu-flame-sampler")
        flame.configure(enabled=True, hz=200.0)  # idempotent: same thread
        t2 = next(th for th in threading.enumerate()
                  if th.name == "gtpu-flame-sampler")
        assert t is t2
        flame.shutdown()
        assert not flame.running()
        t.join(timeout=2.0)
        assert not t.is_alive()

    def test_maybe_install_env_twins(self, sampler_off, monkeypatch):
        monkeypatch.setenv("GTPU_PROFILE", "off")
        flame.maybe_install()
        assert not flame.running()
        monkeypatch.setenv("GTPU_PROFILE", "1")
        monkeypatch.setenv("GTPU_PROFILE_HZ", "55")
        flame.maybe_install()
        assert flame.running()
        assert flame._SAMPLER.period == pytest.approx(1.0 / 55)

    @pytest.mark.slow
    def test_overhead_budget_2pct(self, sampler_off):
        """A/B the busy loop with the sampler on vs off: the always-on
        budget is <=2% (median of alternating rounds)."""
        def _round():
            t0 = time.perf_counter()
            _spin_ms(250)
            return time.perf_counter() - t0

        on, off = [], []
        for _ in range(5):
            flame.configure(enabled=True, hz=19.0)
            on.append(_round())
            flame.shutdown()
            off.append(_round())
        on.sort(), off.sort()
        overhead = on[2] / off[2] - 1.0
        assert overhead <= 0.02, f"sampler overhead {overhead:.1%} > 2%"


# ---- sample_cpu profiler-thread exclusion -----------------------------------


class TestSampleCpuExclusion:
    def test_own_sampler_thread_not_counted(self):
        out = {}

        def run():
            out["folded"] = profiling.sample_cpu(seconds=0.3, hz=200,
                                                 include_idle=True)

        t = threading.Thread(target=run)
        t.start()
        _spin_ms(300)
        t.join()
        # the fixed bug: sample_cpu counted its own sampling loop when
        # invoked off the serving thread
        assert "_sample_loop" not in out["folded"]
        assert "sample_cpu" not in out["folded"]

    def test_continuous_sampler_excluded_from_sample_cpu(self, sampler_off):
        flame.configure(enabled=True, hz=200.0)
        folded = profiling.sample_cpu(seconds=0.2, hz=100,
                                      include_idle=True)
        assert "_tick" not in folded


# ---- per-query stamps (engine / ANALYZE / slow query) -----------------------


class TestQueryStamps:
    """The statement's ledger slice — stamped on its span, printed by
    EXPLAIN ANALYZE, kept in the slow-query record — carries the same
    stage milliseconds the stage spans (and the histogram) recorded."""

    @staticmethod
    def _kv(line: str, head: str) -> dict:
        return dict(kv.split("=") for kv in line.split(head)[1].split())

    def test_analyze_ledger_agrees_with_stage_spans(self, qe):
        _seed(qe)
        r = qe.execute_one(
            "EXPLAIN ANALYZE SELECT host, avg(v) FROM cpu GROUP BY host")
        text = "\n".join(row[0] for row in r.rows())
        assert "roofline:" not in text
        led = self._kv(next(ln for ln in text.splitlines()
                            if "resource ledger:" in ln),
                       "resource ledger:")
        stage_keys = [k for k in led
                      if k.endswith("_ms") and k != "stages_ms"
                      and k[:-3] in tracing.STAGES]
        assert {"scan_ms", "device_ms", "assemble_ms"} <= set(stage_keys)
        assert float(led["stages_ms"]) == pytest.approx(
            sum(float(led[k]) for k in stage_keys), abs=0.01)
        # every stage in the ledger is a span of the printed tree
        for k in stage_keys:
            assert f"{k[:-3]}: " in text

    def test_statement_span_and_histogram_stamped(self, qe):
        from greptimedb_tpu.session import QueryContext
        from greptimedb_tpu.utils.metrics import STAGE_SECONDS

        _seed(qe)
        n0 = STAGE_SECONDS.count(stage="scan")
        ctx = QueryContext()
        qe.execute_sql("SELECT host, avg(v) FROM cpu GROUP BY host", ctx)
        spans = tracing.spans_for(ctx.trace_id)
        stmt = next(s for s in spans if s.name == "stmt:Select")
        led = self._kv("x " + stmt.attrs["ledger"], "x")
        scans = [s for s in spans if s.name == "scan" and s.stage]
        assert float(led["scan_ms"]) == pytest.approx(
            sum(s.duration_ms for s in scans), abs=0.01)
        assert "achieved_gbps" not in stmt.attrs
        assert STAGE_SECONDS.count(stage="scan") == n0 + len(scans)

    def test_slow_query_record_carries_stage_ledger(self, qe, monkeypatch):
        monkeypatch.setenv("GTPU_SLOW_QUERY_MS", "0.0001")
        slow_query.clear()
        try:
            _seed(qe)
            qe.execute_one("SELECT host, avg(v) FROM cpu GROUP BY host")
            rec = next(r for r in slow_query.records(50)
                       if r.query.startswith("SELECT"))
            assert rec.ledger.get("scan_ms", 0) > 0
            by_stage: dict = {}
            for _node, name, ms in rec.stages:
                by_stage[name] = by_stage.get(name, 0.0) + ms
            for key in ("parse", "scan", "device", "assemble"):
                assert rec.ledger[key + "_ms"] == pytest.approx(
                    by_stage[key], abs=0.01)
            d = rec.to_dict()
            assert "achieved_gbps" not in d
            assert "roofline_fraction" not in d
        finally:
            slow_query.clear()

    def test_information_schema_slow_queries_columns(self, qe, monkeypatch):
        monkeypatch.setenv("GTPU_SLOW_QUERY_MS", "0.0001")
        slow_query.clear()
        try:
            _seed(qe)
            qe.execute_one("SELECT count(*) FROM cpu")
            r = qe.execute_one(
                "SELECT stages, ledger "
                "FROM information_schema.slow_queries")
            assert any("scan=" in row[0] and "scan_ms=" in row[1]
                       for row in r.rows())
            with pytest.raises(Exception, match="achieved_gbps"):
                qe.execute_one("SELECT achieved_gbps "
                               "FROM information_schema.slow_queries")
        finally:
            slow_query.clear()


# ---- HTTP endpoints ---------------------------------------------------------


class TestProfileEndpoints:
    def _get(self, port, path, auth=None):
        req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
        if auth:
            import base64
            cred = base64.b64encode(auth.encode()).decode()
            req.add_header("Authorization", f"Basic {cred}")
        return urllib.request.urlopen(req, timeout=10)

    def test_flame_endpoint_auth_and_content_types(self, qe, sampler_off):
        from greptimedb_tpu.auth import StaticUserProvider
        from greptimedb_tpu.servers import HttpServer

        flame.configure(enabled=True, hz=250.0)
        with tracing.span("stmt:Select"):
            _spin_ms(400)
        srv = HttpServer(qe, port=0,
                         user_provider=StaticUserProvider({"u": "pw"}))
        port = srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(port, "/v1/profile/flame")
            assert ei.value.code == 401
            with self._get(port, "/v1/profile/flame", auth="u:pw") as resp:
                assert "text/plain" in resp.headers["Content-Type"]
                body = resp.read().decode()
            assert body.startswith("# flame:")
            assert "stage:stmt:Select;" in body
            with self._get(port, "/v1/profile/flame?format=speedscope",
                           auth="u:pw") as resp:
                assert "application/json" in resp.headers["Content-Type"]
                doc = json.loads(resp.read())
            assert doc["profiles"][0]["type"] == "sampled"
            with self._get(port, "/v1/profile/cluster",
                           auth="u:pw") as resp:
                view = json.loads(resp.read())
            assert view["merged"]["samples"] >= 1
        finally:
            srv.stop()

    def test_flame_endpoint_503_when_disabled(self, qe, sampler_off):
        from greptimedb_tpu.servers import HttpServer

        srv = HttpServer(qe, port=0)
        port = srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(port, "/v1/profile/flame")
            assert ei.value.code == 503
            assert "GTPU_PROFILE" in json.loads(ei.value.read())["error"]
        finally:
            srv.stop()

    def test_flame_dump_tool(self, qe, sampler_off):
        import os
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from tools.flame_dump import fetch, render_cluster

        from greptimedb_tpu.servers import HttpServer

        flame.configure(enabled=True, hz=250.0)
        with tracing.span("stmt:Select"):
            _spin_ms(300)
        srv = HttpServer(qe, port=0)
        port = srv.start()
        try:
            body, ctype = fetch(f"127.0.0.1:{port}", "/v1/profile/flame")
            assert "text/plain" in ctype
            assert body.decode().startswith("# flame:")
            body, _ = fetch(f"127.0.0.1:{port}", "/v1/profile/cluster")
            out = render_cluster(json.loads(body))
            assert "cluster profile:" in out
        finally:
            srv.stop()


# ---- cluster rollup ---------------------------------------------------------


def _digest(node, stages, paths=None, samples=None, top=None):
    total = samples if samples is not None else sum(stages.values())
    return {"node": node, "ts_ms": 1700000000000, "hz": 19.0,
            "window_s": 30.0, "samples": total,
            "attributed": sum(stages.values()),
            "stages": dict(stages), "paths": dict(paths or {}),
            "top": list(top or [])}


class TestClusterRollup:
    def test_merge_is_order_independent(self, sampler_off):
        a = _digest("dn-0", {"stmt": 30, "scan": 10},
                    top=[{"frame": "decode (sst.py:1)", "self": 25}])
        b = _digest("dn-1", {"stmt": 5, "flush": 7},
                    top=[{"frame": "decode (sst.py:1)", "self": 3},
                         {"frame": "fsync (wal.py:9)", "self": 6}])
        flame.note_node_summary("dn-0", a)
        flame.note_node_summary("dn-1", b)
        v1 = flame.cluster_view()
        flame.reset()
        flame.note_node_summary("dn-1", b)
        flame.note_node_summary("dn-0", a)
        v2 = flame.cluster_view()
        # deterministic merge: identical whatever order digests arrived
        # (only the local node's ts_ms may differ between calls)
        assert v1["merged"] == v2["merged"]
        assert sorted(v1["nodes"]) == sorted(v2["nodes"])
        assert v1["merged"]["stages"] == {"flush": 7, "scan": 10,
                                          "stmt": 35}
        assert v1["merged"]["top"][0] == {
            "frame": "decode (sst.py:1)", "self": 28}

    def test_rollup_bounded(self, sampler_off):
        for i in range(flame._CLUSTER_CAP + 40):
            flame.note_node_summary(f"dn-{i}", _digest(f"dn-{i}",
                                                       {"stmt": 1}))
        view = flame.cluster_view()
        # cap + the local node
        assert len(view["nodes"]) <= flame._CLUSTER_CAP + 1
        assert "dn-0" not in view["nodes"]  # oldest evicted first

    def test_heartbeat_carries_profile(self, sampler_off):
        from greptimedb_tpu.meta.heartbeat import HeartbeatTask
        from greptimedb_tpu.meta.metasrv import Metasrv

        flame.configure(enabled=True, hz=250.0)
        with tracing.span("stmt:Select"):
            _spin_ms(300)
        ms = Metasrv(MemoryKv())
        task = HeartbeatTask("dn-7", ms, stats_fn=lambda: [],
                             on_instruction=lambda inst: None)
        assert task.beat() is not None
        prof = ms.node_profiles().get("dn-7")
        assert prof is not None and prof["samples"] > 0
        # sampler stopped: the beat carries no profile, the last one
        # sticks (a restarting node must not blank the cluster view)
        flame.shutdown()
        assert task.beat() is not None
        assert ms.node_profiles().get("dn-7") == prof

    @pytest.mark.slow
    def test_process_cluster_flame_merge_deterministic(self, tmp_path,
                                                       sampler_off,
                                                       monkeypatch):
        """Real child-process datanodes: each samples itself (inherited
        GTPU_PROFILE*), digests ride the Flight piggyback, and the
        frontend's merged view is identical whatever order they
        arrived in."""
        from greptimedb_tpu.cluster.process_cluster import ProcessCluster

        monkeypatch.setenv("GTPU_PROFILE", "1")
        monkeypatch.setenv("GTPU_PROFILE_HZ", "500")
        c = ProcessCluster(str(tmp_path), num_datanodes=2)
        try:
            c.sql(
                "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
                "NOT NULL, TIME INDEX (ts), PRIMARY KEY(host)) "
                "PARTITION ON COLUMNS (host) (host < 'host3', "
                "host >= 'host3')")
            rows = [f"('host{h}', {float(h)}, {1000 + h})"
                    for h in range(6)]
            c.sql("INSERT INTO cpu (host, v, ts) VALUES " + ", ".join(rows))
            for _ in range(3):
                c.sql("SELECT host, avg(v) FROM cpu GROUP BY host")
            view = flame.cluster_view()
            remote = [n for n in view["nodes"] if n.startswith("datanode-")]
            assert len(remote) == 2, sorted(view["nodes"])
            # replay the same digests in reverse order: identical merge
            digests = {n: view["nodes"][n] for n in remote}
            flame.reset()
            for n in sorted(digests, reverse=True):
                flame.note_node_summary(n, digests[n])
            v2 = flame.cluster_view()
            assert {n: v2["nodes"][n] for n in remote} == digests
            assert v2["merged"]["stages"] == {
                k: v for k, v in view["merged"]["stages"].items()}
        finally:
            c.close()

    def test_information_schema_cluster_profile(self, qe, sampler_off):
        flame.configure(enabled=True, hz=250.0, node="frontend-0")
        with tracing.span("stmt:Select"):
            _spin_ms(400)
        flame.note_node_summary("dn-1", _digest("dn-1", {"scan": 12}))
        r = qe.execute_one(
            "SELECT node, stage, stage_samples, share "
            "FROM information_schema.cluster_profile ORDER BY node, stage")
        rows = r.rows()
        nodes = {row[0] for row in rows}
        assert {"frontend-0", "dn-1"} <= nodes
        dn1 = next(row for row in rows if row[0] == "dn-1")
        assert dn1[1] == "scan" and dn1[2] == 12 and dn1[3] == 1.0


# ---- OTLP log lane ----------------------------------------------------------


class _Sink:
    """OTLP/HTTP sink recording (path, payload) pairs."""

    def __init__(self):
        from http.server import BaseHTTPRequestHandler, HTTPServer

        self.posts: list = []
        outer = self

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                outer.posts.append(
                    (self.path, json.loads(self.rfile.read(n))))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *a):
                pass

        self.httpd = HTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture
def no_exporter():
    yield
    otlp_trace.configure(None)


class TestOtlpLogLane:
    def test_golden_log_payload(self):
        p = otlp_trace.log_payload([
            {"ts": 1700000000.5, "levelno": logging.WARNING,
             "logger": "greptimedb_tpu.fault", "body": "seam tripped",
             "trace_id": "feedbeefcafe0001"},
            {"ts": 1700000001.0, "levelno": logging.ERROR,
             "logger": "greptimedb_tpu.wal", "body": "fsync failed",
             "trace_id": ""},
        ], node="dn-0")
        rl, = p["resourceLogs"]
        attrs = {a["key"]: a["value"] for a in rl["resource"]["attributes"]}
        assert attrs["service.name"] == {"stringValue": "greptimedb_tpu"}
        assert attrs["service.instance.id"] == {"stringValue": "dn-0"}
        r0, r1 = rl["scopeLogs"][0]["logRecords"]
        assert r0["timeUnixNano"] == "1700000000500000000"
        assert r0["severityText"] == "WARN"
        assert r0["body"] == {"stringValue": "seam tripped"}
        assert r0["traceId"] == "feedbeefcafe0001".rjust(32, "0")
        assert r1["severityText"] == "ERROR"
        assert "traceId" not in r1  # uncorrelated record exports bare

    def test_warning_logs_export_with_trace_correlation(self, no_exporter):
        sink = _Sink()
        try:
            otlp_trace.configure(f"http://127.0.0.1:{sink.port}",
                                 flush_interval_s=0.05)
            tid = tracing.set_trace(None)
            with tracing.span("stmt:Select"):
                logging.getLogger("greptimedb_tpu.test_profile").warning(
                    "deliberate warning for export")
            assert otlp_trace.exporter().flush(timeout_s=5.0)
            deadline = time.time() + 5
            while time.time() < deadline and not any(
                    path.endswith("/v1/logs") for path, _ in sink.posts):
                time.sleep(0.02)
            logs = [p for path, p in sink.posts
                    if path.endswith("/v1/logs")]
            assert logs, f"no /v1/logs posts in {[p for p, _ in sink.posts]}"
            recs = [r for p in logs
                    for rl in p["resourceLogs"]
                    for sl in rl["scopeLogs"]
                    for r in sl["logRecords"]]
            mine = next(r for r in recs if "deliberate warning"
                        in r["body"]["stringValue"])
            assert mine["traceId"] == tid.rjust(32, "0")
        finally:
            sink.stop()

    def test_info_records_and_own_logger_skipped(self, no_exporter):
        sink = _Sink()
        try:
            exp = otlp_trace.configure(f"http://127.0.0.1:{sink.port}",
                                       flush_interval_s=0.05)
            logging.getLogger("greptimedb_tpu.x").info("below threshold")
            logging.getLogger("greptimedb_tpu.otlp_trace").warning(
                "export failed (must not feed back)")
            assert exp.flush(timeout_s=5.0)
            recs = [r for path, p in sink.posts
                    if path.endswith("/v1/logs")
                    for rl in p["resourceLogs"]
                    for sl in rl["scopeLogs"]
                    for r in sl["logRecords"]]
            assert not recs
        finally:
            sink.stop()

    def test_gate_env_disables_log_lane(self, no_exporter, monkeypatch):
        monkeypatch.setenv("GTPU_OTLP_LOGS", "off")
        otlp_trace.configure("http://127.0.0.1:1")
        handlers = logging.getLogger("greptimedb_tpu").handlers
        assert not any(isinstance(h, otlp_trace.OtlpLogHandler)
                       for h in handlers)
        monkeypatch.delenv("GTPU_OTLP_LOGS")
        otlp_trace.configure("http://127.0.0.1:1")
        handlers = logging.getLogger("greptimedb_tpu").handlers
        assert any(isinstance(h, otlp_trace.OtlpLogHandler)
                   for h in handlers)

    def test_token_bucket_throttles_storms(self, no_exporter):
        exp = otlp_trace.OtlpTraceExporter("http://127.0.0.1:1")
        exp._stop = True  # enqueue only; never actually post
        from greptimedb_tpu.utils.otlp_trace import OTLP_LOG_RECORDS

        t0 = OTLP_LOG_RECORDS.get(event="throttled")
        for i in range(200):
            exp.on_log({"ts": 0.0, "levelno": logging.WARNING,
                        "logger": "greptimedb_tpu.storm",
                        "body": f"warn {i}", "trace_id": ""})
        assert len(exp._logq) <= exp._log_rate + 1
        t1 = OTLP_LOG_RECORDS.get(event="throttled")
        assert t1 - t0 >= 150


# ---- options / config plumbing ----------------------------------------------


class TestProfilingOptions:
    def test_apply_observability_env_twins(self, sampler_off, monkeypatch):
        from greptimedb_tpu.options import (ProfilingOptions,
                                            StandaloneOptions,
                                            apply_observability)

        for k in ("GTPU_PROFILE", "GTPU_PROFILE_HZ",
                  "GTPU_PROFILE_WINDOW_S", "GTPU_PROFILE_WINDOWS"):
            monkeypatch.delenv(k, raising=False)
        opts = StandaloneOptions()
        opts.profiling = ProfilingOptions(enabled=False, hz=7.0)
        apply_observability(opts)
        import os
        assert os.environ.get("GTPU_PROFILE") == "off"
        assert os.environ.get("GTPU_PROFILE_HZ") == "7.0"
        assert not flame.running()
        opts.profiling = ProfilingOptions()  # defaults: on @ 19 Hz
        apply_observability(opts)
        assert os.environ.get("GTPU_PROFILE", "") == ""
        assert flame.running()

    def test_example_toml_documents_profiling(self):
        from greptimedb_tpu.options import example_toml

        toml = example_toml()
        assert "[profiling]" in toml
        assert "hz = 19.0" in toml


# ---- lint: exemplar rule ----------------------------------------------------


class TestExemplarLint:
    def _run(self, src):
        from greptimedb_tpu.lint import Repo, SourceFile
        from greptimedb_tpu.lint.metrics_options import check_exemplars

        return check_exemplars(Repo(files=[
            SourceFile.from_text("greptimedb_tpu/utils/metrics.py", src)]))

    def test_flags_hot_path_histogram_without_exemplars(self):
        findings = self._run(
            'X = REGISTRY.histogram("greptimedb_tpu_query_foo_seconds",\n'
            '                       "help")\n')
        assert len(findings) == 1
        assert "exemplars=True" in findings[0].message

    def test_accepts_exemplars_and_ignores_cold_paths(self):
        assert not self._run(
            'X = REGISTRY.histogram("greptimedb_tpu_statement_x",\n'
            '                       "help", exemplars=True)\n')
        assert not self._run(
            'X = REGISTRY.histogram("greptimedb_tpu_maintenance_x",\n'
            '                       "help")\n')

    def test_live_repo_clean(self):
        from greptimedb_tpu.lint import load_repo
        from greptimedb_tpu.lint.metrics_options import check_exemplars

        assert check_exemplars(load_repo()) == []

"""What is left of the profiling plane (ISSUE 17; the continuous sampler
went in PR 40): /debug/pprof/cpu leaves its own thread out, the
per-statement ledger stamps (span / slow-query / ANALYZE, keyed on the
serving stages), the OTLP log lane riding the trace exporter, and the
exemplar lint.
"""

from __future__ import annotations

import json
import logging
import threading
import time

import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils import (otlp_trace, profiling, slow_query,
                                  tracing)


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


def _spin_ms(ms: float) -> float:
    """Busy CPU loop a sampler can land on (no sleeps: sleeps are
    idle-filtered)."""
    t0 = time.perf_counter()
    x = 0.0
    while (time.perf_counter() - t0) * 1000 < ms:
        x += sum(i * i for i in range(200))
    return x


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

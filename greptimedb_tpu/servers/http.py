"""HTTP server (mirrors reference servers::http `HttpServer::make_app`,
src/servers/src/http.rs:625-801): /v1/sql, the Prometheus HTTP API,
InfluxDB/OpenTSDB write endpoints, /metrics, /health.

stdlib ThreadingHTTPServer — the host tier serves protocol traffic while
queries execute as device kernels; no framework dependencies.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import threading
import time
import traceback
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from greptimedb_tpu.catalog.catalog import CatalogError
from greptimedb_tpu.fault import FaultError, Unavailable
from greptimedb_tpu.fault.retry import Cancelled, DeadlineExceeded
from greptimedb_tpu.query.engine import QueryContext, QueryEngine
from greptimedb_tpu.query.result import QueryResult
from greptimedb_tpu.utils.metrics import (
    HTTP_REQUESTS,
    PROMQL_ENCODED_RESPONSES,
    QUERY_DURATION,
    REGISTRY,
)


class HttpServer:
    def __init__(self, query_engine: QueryEngine, host: str = "127.0.0.1",
                 port: int = 4000, user_provider=None,
                 timeout_s: Optional[float] = None):
        self.qe = query_engine
        self.host = host
        self.port = port
        self.user_provider = user_provider
        self.timeout_s = timeout_s
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> int:
        qe = self.qe

        provider = self.user_provider

        class Handler(_Handler):
            query_engine = qe
            user_provider = provider
            # socketserver honors this as the per-connection socket
            # timeout (http.timeout_s option)
            if self.timeout_s:
                timeout = self.timeout_s

        class Server(ThreadingHTTPServer):
            # default backlog (5) resets connections under benchmark-level
            # concurrency (50 clients connecting at once); daemon threads
            # so a hung handler can't block process exit
            request_queue_size = 128
            daemon_threads = True

        self._httpd = Server((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self.port

    def stop(self):
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._thread.join(timeout=5)


class _Handler(BaseHTTPRequestHandler):
    query_engine: QueryEngine = None  # injected
    user_provider = None  # injected
    protocol_version = "HTTP/1.1"
    # headers and body go out in separate send()s — without NODELAY,
    # Nagle holds the second segment for the peer's delayed ACK and
    # every keep-alive request eats a flat ~40 ms (round-5: single-
    # connection latency 44 ms with a 1.2 ms engine)
    disable_nagle_algorithm = True

    def log_message(self, fmt, *args):  # quiet
        pass

    # ---- plumbing ----------------------------------------------------------

    def _params(self) -> dict:
        parsed = urllib.parse.urlparse(self.path)
        params = {k: v[0] for k, v in urllib.parse.parse_qs(parsed.query).items()}
        return params

    def _body(self) -> bytes:
        length = int(self.headers.get("Content-Length") or 0)
        return self.rfile.read(length) if length else b""

    def _form_or_query(self) -> dict:
        params = self._params()
        body = self._body()
        ctype = (self.headers.get("Content-Type") or "").split(";")[0].strip()
        if body and ctype in ("application/x-www-form-urlencoded", ""):
            try:
                form = {k: v[0] for k, v in
                        urllib.parse.parse_qs(body.decode()).items()}
                params = {**form, **params}
            except UnicodeDecodeError:
                pass
        self._raw_body = body
        return params

    def _send(self, code: int, payload, content_type="application/json"):
        from greptimedb_tpu.utils import tracing

        # stages belong to a request with a root (/health, /metrics and
        # a refused login have none and stay out of the stage histogram)
        stage = tracing.stage if getattr(self, "_traceparent", None) \
            else (lambda name, **kw: contextlib.nullcontext())
        if isinstance(payload, bytes):
            data = payload
        else:
            with stage("encode"):
                data = json.dumps(payload).encode()
        with stage("send", bytes=len(data)):
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(data)))
            # W3C egress: echo the request's trace context so the
            # caller can join its spans to ours (set per traced
            # request in _route)
            tp = getattr(self, "_traceparent", None)
            if tp:
                self.send_header("traceparent", tp)
            self.end_headers()
            self.wfile.write(data)
        route = urllib.parse.urlparse(self.path).path
        HTTP_REQUESTS.inc(path=route, status=str(code))


    def _ctx(self, params: dict) -> QueryContext:
        from greptimedb_tpu.session import Channel
        # X-Greptime-Timezone: per-request session timezone (reference
        # servers/src/http — HTTP is stateless, so SET TIME ZONE can't
        # persist; clients pin it per request via this header)
        tz = self.headers.get("X-Greptime-Timezone") or \
            params.get("timezone")
        if tz:
            from greptimedb_tpu.utils.time import tzinfo_for

            tzinfo_for(tz)  # fail fast on a typo'd zone name
        user = getattr(self, "_user", None)
        # X-Greptime-Tenant: admission-control identity for fair
        # scheduling; falls back to the authenticated user, then the db
        tenant = self.headers.get("X-Greptime-Tenant") \
            or params.get("tenant") \
            or getattr(user, "username", None)
        # X-Greptime-Timeout: per-request deadline ("500ms", "5s", or a
        # bare millisecond count); absent = session/config default
        from greptimedb_tpu.utils import deadline

        timeout_ms = deadline.parse_timeout_ms(
            self.headers.get("X-Greptime-Timeout")
            or params.get("timeout"))
        from greptimedb_tpu.utils import tracing

        return QueryContext(db=params.get("db", "public"),
                            channel=Channel.HTTP,
                            timezone=tz or None,
                            tenant=tenant,
                            timeout_ms=timeout_ms,
                            user=user,
                            # the request trace installed by _route's
                            # ingress span (adopted from an incoming
                            # traceparent header, or freshly minted) —
                            # the engine joins the same trace
                            trace_id=tracing.current_trace_id())

    # ---- routing -----------------------------------------------------------

    def do_GET(self):
        self._route()

    def do_POST(self):
        self._route()

    def _route(self):
        path = urllib.parse.urlparse(self.path).path
        self._traceparent = None
        try:
            if path == "/health" or path == "/ready":
                return self._send(200, {})
            if path in ("/dashboard", "/dashboard/"):
                from greptimedb_tpu.servers.dashboard import PAGE

                return self._send(200, PAGE.encode(),
                                  "text/html; charset=utf-8")
            if path == "/metrics":
                # content negotiation: an OpenMetrics scraper gets the
                # exemplar-bearing exposition (trace_id exemplars on
                # histogram buckets + the spec's # EOF), classic
                # scrapers keep the byte-stable text format
                om = "application/openmetrics-text" in \
                    (self.headers.get("Accept") or "")
                ctype = ("application/openmetrics-text; version=1.0.0; "
                         "charset=utf-8") if om \
                    else "text/plain; version=0.0.4"
                return self._send(
                    200, REGISTRY.render(openmetrics=om).encode(), ctype)
            if self.user_provider is not None:
                # Basic auth on every data route (reference
                # servers/src/http/authorize.rs; /health and /metrics
                # stay open)
                from greptimedb_tpu.auth import AuthError
                try:
                    self._user = self.user_provider.authenticate_basic(
                        self.headers.get("Authorization") or "")
                except AuthError as e:
                    data = json.dumps({"code": 7002, "error": str(e)}).encode()
                    self.send_response(401)
                    self.send_header("WWW-Authenticate",
                                     'Basic realm="greptimedb"')
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    HTTP_REQUESTS.inc(path=path, status="401")
                    return
            from greptimedb_tpu.utils import tracing

            # every data route runs under a request root span: the
            # incoming W3C traceparent (if any) is adopted so our spans
            # join the caller's trace, and _send echoes the context back
            with tracing.request_span(f"http:{path}",
                                      traceparent=self.headers.get(
                                          "traceparent")):
                self._traceparent = tracing.to_traceparent()
                return self._route_traced(path)
        except DeadlineExceeded as e:
            # typed deadline expiry: the timeout shape (408), not 503 —
            # the client asked for the bound it just hit
            self._send(408, {"code": 3001, "error": str(e),
                             "execution_time_ms": 0})
        except Cancelled as e:
            # typed cancellation (KILL / DELETE-to-kill / disconnect):
            # nginx's 499 "client closed request" shape
            self._send(499, {"code": 3002, "error": str(e),
                             "execution_time_ms": 0})
        except Unavailable as e:
            # typed degradation (retries + route refresh exhausted): a
            # 503 the client should back off on, not a stack trace
            self._send(503, {"code": 5003, "error": str(e),
                             "execution_time_ms": 0})
        except Exception as e:  # noqa: BLE001 — wire boundary
            traceback.print_exc()
            self._send(400, {"code": 3000, "error": str(e),
                             "execution_time_ms": 0})

    def _route_traced(self, path: str):
        try:
            if path.startswith("/debug/pprof/"):
                # on-demand profiling (reference servers/src/http/pprof.rs
                # + mem_prof.rs) — folded CPU stacks / tracemalloc heap.
                # Sits BEHIND the auth gate: stack samples and heap
                # contents are sensitive (only /health and /metrics are
                # exempt, matching authorize.rs)
                from greptimedb_tpu.utils import profiling

                qs = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query)
                if path == "/debug/pprof/cpu":
                    secs = min(float(qs.get("seconds", ["5"])[0]), 60.0)
                    out = profiling.sample_cpu(seconds=secs)
                    return self._send(200, out.encode(), "text/plain")
                if path == "/debug/pprof/mem":
                    if qs.get("action", [""])[0] == "stop":
                        out = profiling.mem_profile_stop()
                    else:
                        out = profiling.mem_profile(
                            top=int(qs.get("top", ["50"])[0]))
                    return self._send(200, out.encode(), "text/plain")
                if path == "/debug/pprof/device":
                    # a jax.profiler session around the next n seconds,
                    # host tracer on: device operations and this
                    # program's spans on one clock, written under the
                    # data home (tools/trace_gaps.py reads it)
                    secs = min(float(qs.get("seconds", ["5"])[0]), 60.0)
                    data_dir = getattr(getattr(
                        self.query_engine.region_engine, "config", None),
                        "data_dir", None)
                    if data_dir is None:
                        return self._send(404, {
                            "error": "no local data home to write under"})
                    try:
                        out = profiling.device_trace(secs, os.path.join(
                            os.path.dirname(data_dir), "profiles"))
                    except profiling.ProfilerBusy as e:
                        return self._send(409, {"error": str(e)})
                    return self._send(200, out)
                return self._send(404, {"error": f"no route {path}"})
            if path == "/v1/faults":
                # chaos-state debug surface: armed points, partitions,
                # and per-series fire counts — what a red scenario run
                # pulls first to see which schedule actually hit
                from greptimedb_tpu.fault import FAULTS, chaos_seed
                from greptimedb_tpu.utils.metrics import FAULT_INJECTIONS

                return self._send(200, {
                    "chaos_seed": chaos_seed(),
                    "faults": FAULTS.describe(),
                    "partitions": FAULTS.partitions(),
                    "fired": [{"labels": labels, "count": count}
                              for labels, count in
                              FAULT_INJECTIONS.series()]})
            if path == "/v1/device":
                # which device this process serves from and whether
                # anything on the device path degraded (canaries,
                # latches, warm-up failures) — what chip_smoke.py and an
                # operator check before trusting a latency
                return self._send(
                    200, self.query_engine.executor.device_status())
            if path == "/v1/maintenance":
                # background maintenance plane debug surface: queue
                # depth + job list (newest first) + stall counters
                from greptimedb_tpu.utils.metrics import (
                    WRITE_STALL_SECONDS,
                )

                maint = getattr(self.query_engine.region_engine,
                                "maintenance", None)
                params = self._params()
                n = int(params.get("limit", "100"))
                return self._send(200, {
                    "enabled": maint is not None,
                    "queue_depth": maint.queue_depth() if maint else 0,
                    "rollup_rules": [
                        {"resolution_ms": r.resolution_ms,
                         "fields": list(r.fields), "auto": r.auto}
                        for r in (maint.rollup_rules if maint else [])],
                    "write_stall_seconds": WRITE_STALL_SECONDS.total(),
                    "jobs": [j.to_dict()
                             for j in (maint.jobs() if maint else [])[:n]],
                })
            if path == "/v1/slow_queries":
                # debug surface of the slow-query ring; behind the auth
                # gate (query text is sensitive, unlike /metrics)
                from greptimedb_tpu.utils import slow_query

                params = self._params()
                n = int(params.get("limit", "50"))
                return self._send(200, {
                    "slow_queries": [r.to_dict()
                                     for r in slow_query.records(n)],
                    "threshold_ms": slow_query.threshold_ms()})
            if path.startswith("/v1/traces/"):
                # one trace's span tree by id (auth-gated like
                # /v1/slow_queries — span attrs carry query shape);
                # tools/trace_dump.py renders it, and the stage-
                # histogram exemplars at /metrics point here
                from greptimedb_tpu.utils import tracing

                tid = path.rsplit("/", 1)[1].lower()
                # accept the zero-padded 32-hex form our own
                # traceparent egress emits for internally-minted ids
                # (same normalization as parse_traceparent)
                if len(tid) == 32 and tid.startswith("0" * 16):
                    tid = tid[16:]
                spans = tracing.spans_for(tid)
                if not spans:
                    return self._send(404, {"error": f"no spans for "
                                                     f"trace {tid!r}"})
                wire = tracing.spans_to_wire(spans)
                for w, s in zip(wire, spans):
                    w["node"] = s.node
                return self._send(200, {
                    "trace_id": tid,
                    "spans": wire,
                    "tree": tracing.render_tree(spans)})
            if path == "/v1/queries" or path.startswith("/v1/queries/"):
                return self._handle_queries(path)
            if path == "/v1/sql":
                return self._handle_sql()
            if path == "/v1/promql":
                return self._promql_response(self._handle_promql_range)
            if path.startswith("/v1/prometheus/api/v1/") or path.startswith("/api/v1/"):
                sub = path.split("/api/v1/", 1)[1]
                if sub == "query_range":
                    return self._promql_response(self._handle_promql_range)
                if sub == "query":
                    return self._promql_response(
                        self._handle_promql_instant)
                if sub == "labels":
                    return self._handle_labels()
                if sub.startswith("label/") and sub.endswith("/values"):
                    return self._handle_label_values(sub.split("/")[1])
                if sub == "series":
                    return self._handle_series()
                return self._send(404, _prom_err("unknown endpoint"))
            if path in ("/v1/influxdb/write", "/v1/influxdb/api/v2/write",
                        "/influxdb/write"):
                return self._handle_influx_write()
            if path in ("/v1/opentsdb/api/put", "/opentsdb/api/put"):
                return self._handle_opentsdb_put()
            if path in ("/v1/prometheus/write", "/v1/prometheus/api/v1/write"):
                return self._handle_prom_remote_write()
            if path in ("/v1/prometheus/read", "/v1/prometheus/api/v1/read"):
                return self._handle_prom_remote_read()
            if path in ("/v1/otlp/v1/metrics",):
                return self._handle_otlp_metrics()
            if path in ("/v1/otlp/v1/traces",):
                return self._handle_otlp_traces()
            if path == "/v1/scripts":
                return self._handle_scripts()
            if path == "/v1/run-script":
                return self._handle_run_script()
            return self._send(404, {"error": f"no route {path}"})
        except DeadlineExceeded as e:
            self._send(408, {"code": 3001, "error": str(e),
                             "execution_time_ms": 0})
        except Cancelled as e:
            self._send(499, {"code": 3002, "error": str(e),
                             "execution_time_ms": 0})
        except Unavailable as e:
            # typed degradation (retries + route refresh exhausted): a
            # 503 the client should back off on, not a stack trace
            self._send(503, {"code": 5003, "error": str(e),
                             "execution_time_ms": 0})
        except Exception as e:  # noqa: BLE001 — wire boundary
            traceback.print_exc()
            self._send(400, {"code": 3000, "error": str(e),
                             "execution_time_ms": 0})

    # ---- /v1/queries (running-queries surface) -----------------------------

    def do_DELETE(self):
        self._route()

    def _handle_queries(self, path: str):
        """GET /v1/queries lists live statements on this frontend;
        DELETE /v1/queries/<id> cancels one (the HTTP twin of
        KILL QUERY <id>)."""
        from greptimedb_tpu.utils import deadline

        if self.command == "DELETE":
            qid_s = path[len("/v1/queries/"):] \
                if path.startswith("/v1/queries/") else ""
            try:
                qid = int(qid_s)
            except ValueError:
                return self._send(400,
                                  {"error": f"bad query id {qid_s!r}"})
            if deadline.RUNNING.kill(qid, reason="DELETE /v1/queries"):
                return self._send(200, {"killed": qid})
            return self._send(404, {"error": f"no running query {qid}"})
        return self._send(200, {"queries": deadline.RUNNING.list()})

    # ---- /v1/sql (reference http.rs:724 sql handler) -----------------------

    def _handle_sql(self):
        from greptimedb_tpu.servers.encode import encode_sql_payload
        from greptimedb_tpu.utils import deadline, tracing

        params = self._form_or_query()
        sql = params.get("sql")
        if not sql:
            return self._send(400, {"code": 1004, "error": "missing sql"})
        ctx = self._ctx(params)
        # pre-create the statement token so a client that hangs up
        # mid-execution cancels the work it abandoned (the engine arms
        # the deadline and registers it in the running-queries table)
        token = deadline.CancelToken()
        ctx.cancel_token = token
        stop_watch = deadline.watch_disconnect(self.connection, token)
        t0 = time.perf_counter()
        try:
            with QUERY_DURATION.time(kind="sql"):
                results = self.query_engine.execute_sql(sql, ctx)
        finally:
            stop_watch()
        # the admission slot was released inside execute_sql (at
        # execute-done): serialization below never occupies an
        # execution slot
        elapsed = round((time.perf_counter() - t0) * 1000, 3)
        with tracing.stage("encode"):
            data = encode_sql_payload(results, elapsed)
        self._send(200, data)

    # ---- Prometheus API (reference http.rs:724-744) ------------------------

    def _promql_response(self, handle):
        """A PromQL request, every response to it counted once by how
        its body was written: an answer by `_send_promql`, an error
        that `_route` answers as `rows`."""
        try:
            handle()
        except Exception:
            PROMQL_ENCODED_RESPONSES.inc(path="rows")
            raise

    def _send_promql(self, code: int, payload):
        # counted before the bytes go out: a scrape that follows the
        # response has it. Only `_matrix_body` hands bytes over
        PROMQL_ENCODED_RESPONSES.inc(
            path="columnar" if isinstance(payload, bytes) else "rows")
        self._send(code, payload)

    def _handle_promql_range(self):
        from greptimedb_tpu.promql.engine import (
            PromqlEngine,
            SeriesMatrix,
            d2h,
        )

        params = self._form_or_query()
        query = params.get("query") or params.get("promql")
        if not query:
            return self._send_promql(400, _prom_err("missing query"))
        try:
            start = _prom_time(params["start"])
            end = _prom_time(params["end"])
            step = _prom_duration(params.get("step", "60"))
        except (KeyError, ValueError) as e:
            return self._send_promql(
                400, _prom_err(f"bad range params: {e}"))
        ctx = self._ctx(params)
        engine = PromqlEngine(self.query_engine)
        from greptimedb_tpu.utils import slow_query, tracing

        # the watch covers readback, encoding and the socket write too
        # (eval_matrix's own is a no-op inside it): a slow PromQL
        # request's record holds its whole stage tree
        with slow_query.watch("promql", query, ctx.db):
            with QUERY_DURATION.time(kind="promql_range"):
                times, result = engine.eval_matrix(query, start, end, step,
                                                   ctx)
            if isinstance(result, SeriesMatrix):
                return self._send_promql(200, _matrix_body(times, result))
            with tracing.stage("readback"):
                vals = np.broadcast_to(d2h(result, dtype=np.float64),
                                       times.shape)
            with tracing.stage("encode"):
                payload = {"resultType": "matrix",
                           "result": [{"metric": {},
                                       "values": _values_json(times, vals)}]}
            self._send_promql(200, {"status": "success", "data": payload})

    def _handle_promql_instant(self):
        from greptimedb_tpu.promql.engine import (
            PromqlEngine,
            SeriesMatrix,
            d2h,
        )
        from greptimedb_tpu.utils import slow_query, tracing

        params = self._form_or_query()
        query = params.get("query")
        if not query:
            return self._send_promql(400, _prom_err("missing query"))
        t = _prom_time(params.get("time", str(time.time())))
        ctx = self._ctx(params)
        engine = PromqlEngine(self.query_engine)
        with slow_query.watch("promql", query, ctx.db):
            with QUERY_DURATION.time(kind="promql_instant"):
                times, result = engine.eval_matrix(query, t, t, 1.0, ctx)
            with tracing.stage("readback"):
                vals = d2h(result.values
                            if isinstance(result, SeriesMatrix) else result)
            with tracing.stage("encode"):
                if isinstance(result, SeriesMatrix):
                    out = []
                    for i, lab in enumerate(result.labels):
                        v = vals[i, -1]
                        if math.isnan(v):
                            continue
                        metric = dict(lab)
                        if result.metric:
                            metric["__name__"] = result.metric
                        out.append({"metric": metric,
                                    "value": [t, _fmt_float(v)]})
                    payload = {"resultType": "vector", "result": out}
                else:
                    v = float(vals.reshape(-1)[-1])
                    payload = {"resultType": "scalar",
                               "value": [t, _fmt_float(v)]}
            self._send_promql(200, {"status": "success", "data": payload})

    def _handle_labels(self):
        params = self._form_or_query()
        ctx = self._ctx(params)
        qe = self.query_engine
        labels = {"__name__"}
        matches = _match_params(self)
        tables = [m for m in matches] or qe.catalog.list_tables(ctx.db)
        for t in tables:
            try:
                info = qe.catalog.table(ctx.db, _metric_of(t))
            except CatalogError:
                continue  # matcher named a non-existent metric: skip it
            labels.update(c.name for c in info.schema.tag_columns)
        self._send(200, {"status": "success", "data": sorted(labels)})

    def _handle_label_values(self, label: str):
        params = self._form_or_query()
        ctx = self._ctx(params)
        qe = self.query_engine
        if label == "__name__":
            return self._send(200, {"status": "success",
                                    "data": sorted(qe.catalog.list_tables(ctx.db))})
        values: set = set()
        for t in qe.catalog.list_tables(ctx.db):
            try:
                info = qe._table(t, ctx)
            except (CatalogError, Unavailable, FaultError,
                    OSError, ValueError):
                # dropped concurrently, or its region failed to open
                # (WAL replay / manifest read): label discovery skips
                # the broken table instead of failing the endpoint
                continue
            if label not in {c.name for c in info.schema.tag_columns}:
                continue
            for rid in info.region_ids:  # union across all regions
                region = qe.region_engine.region(rid)
                values.update(str(v) for v in region.registry.values.get(label, []))
        self._send(200, {"status": "success", "data": sorted(values)})

    def _handle_series(self):
        from greptimedb_tpu.promql.engine import PromqlEngine, SeriesMatrix

        params = self._form_or_query()
        matches = _match_params(self)
        if not matches:
            return self._send(400, _prom_err("match[] required"))
        start = _prom_time(params.get("start", "0"))
        end = _prom_time(params.get("end", str(time.time())))
        ctx = self._ctx(params)
        engine = PromqlEngine(self.query_engine)
        from greptimedb_tpu.promql.parser import parse_promql, VectorSelector
        out = []
        for m in matches:
            node = parse_promql(m)
            if isinstance(node, VectorSelector):
                # series existence over the whole [start, end] range: one
                # eval at `end` with the range as the lookback window
                from greptimedb_tpu.promql.engine import EvalParams
                p = EvalParams(end, end, 1.0, np.asarray([end]))
                result = engine._eval_instant_selector(
                    node, p, ctx, lookback=max(end - start, 1.0))
            else:
                _, result = engine.eval_matrix(m, end, end, 1.0, ctx)
            if isinstance(result, SeriesMatrix):
                vals = np.asarray(result.values)
                for i, lab in enumerate(result.labels):
                    if vals.size and np.isnan(vals[i]).all():
                        continue
                    metric = dict(lab)
                    if result.metric:
                        metric["__name__"] = result.metric
                    out.append(metric)
        self._send(200, {"status": "success", "data": out})

    # ---- write protocols ---------------------------------------------------

    def _handle_influx_write(self):
        from greptimedb_tpu.servers.influx import (
            LineProtocolError,
            write_lines,
        )

        params = self._form_or_query()
        body = getattr(self, "_raw_body", b"") or self._body()
        db = params.get("db") or params.get("bucket") or "public"
        precision = params.get("precision", "ns")
        try:
            n = write_lines(self.query_engine, db, body.decode(), precision)
        except LineProtocolError as e:
            # typed 4xx naming the bad line numbers: a torn/partial line
            # from a crashed client must fail loudly, never silently
            # sink the rest of the batch (_send counts the request)
            return self._send(400, {"code": 1004, "error": str(e),
                                    "lines": e.lines})
        self.send_response(204)
        self.send_header("Content-Length", "0")
        self.end_headers()
        HTTP_REQUESTS.inc(path="/v1/influxdb/write", status="204")
        _ = n

    def _handle_prom_remote_write(self):
        from greptimedb_tpu.servers.prom_store import handle_remote_write

        params = self._params()
        body = self._body()
        db = params.get("db", "public")
        handle_remote_write(self.query_engine, body, db)
        self.send_response(204)
        self.send_header("Content-Length", "0")
        self.end_headers()
        HTTP_REQUESTS.inc(path="/v1/prometheus/write", status="204")

    def _handle_prom_remote_read(self):
        from greptimedb_tpu.servers.prom_store import handle_remote_read

        params = self._params()
        body = self._body()
        db = params.get("db", "public")
        resp = handle_remote_read(self.query_engine, body, db)
        self.send_response(200)
        self.send_header("Content-Type", "application/x-protobuf")
        self.send_header("Content-Encoding", "snappy")
        self.send_header("Content-Length", str(len(resp)))
        self.end_headers()
        self.wfile.write(resp)
        HTTP_REQUESTS.inc(path="/v1/prometheus/read", status="200")

    def _handle_otlp_metrics(self):
        from greptimedb_tpu.servers.otlp import handle_otlp_metrics

        body = self._body()
        db = self._params().get("db", "public")
        n = handle_otlp_metrics(self.query_engine, body, db)
        self._send(200, {"partialSuccess": {}})
        _ = n

    def _handle_otlp_traces(self):
        from greptimedb_tpu.servers.otlp import handle_otlp_traces

        body = self._body()
        db = self._params().get("db", "public")
        n = handle_otlp_traces(self.query_engine, body, db)
        self._send(200, {"partialSuccess": {}})
        _ = n

    # ---- scripts (reference http.rs scripts router + src/script) -----------

    def _script_engine(self):
        qe = self.query_engine
        if not hasattr(qe, "_script_engine"):
            from greptimedb_tpu.script import ScriptEngine
            qe._script_engine = ScriptEngine(qe)
        return qe._script_engine

    def _handle_scripts(self):
        from greptimedb_tpu.script import ScriptError

        params = self._params()
        db = params.get("db", "public")
        name = params.get("name")
        if self.command == "GET":
            if name:
                code = self._script_engine().get_script(db, name)
                if code is None:
                    return self._send(404, {"error": f"script {name!r} not found"})
                return self._send(200, {"code": 0, "script": code})
            return self._send(200, {"code": 0,
                                    "scripts": self._script_engine().list_scripts(db)})
        if not name:
            return self._send(400, {"error": "missing name"})
        code = self._body().decode()
        try:
            self._script_engine().insert_script(db, name, code)
        except ScriptError as e:
            return self._send(400, {"code": 1004, "error": str(e)})
        return self._send(200, {"code": 0})

    def _handle_run_script(self):
        from greptimedb_tpu.script import ScriptError

        params = self._params()
        db = params.get("db", "public")
        name = params.get("name")
        if not name:
            return self._send(400, {"error": "missing name"})
        t0 = time.perf_counter()
        try:
            with QUERY_DURATION.time(kind="script"):
                result = self._script_engine().run_script(db, name)
        except ScriptError as e:
            return self._send(400, {"code": 1004, "error": str(e)})
        elapsed = round((time.perf_counter() - t0) * 1000, 3)
        return self._send(200, {"code": 0,
                                "output": [{"records": _records_json(result)}],
                                "execution_time_ms": elapsed})

    def _handle_opentsdb_put(self):
        """OpenTSDB JSON put (reference servers/src/opentsdb.rs +
        http.rs:793-797)."""
        from greptimedb_tpu.servers.influx import Point, write_points

        body = self._body()
        data = json.loads(body.decode())
        if isinstance(data, dict):
            data = [data]
        points = []
        for d in data:
            ts = int(d["timestamp"])
            # OpenTSDB: seconds or milliseconds by magnitude
            ts_ms = ts * 1000 if ts < 10_000_000_000 else ts
            points.append(Point(
                measurement=d["metric"],
                tags=sorted(d.get("tags", {}).items()),
                fields=[("greptime_value", float(d["value"]))],
                ts=ts_ms,
            ))
        n = write_points(self.query_engine, "public", points, precision="ms")
        self._send(200, {"success": n, "failed": 0})


# ---- formatting ------------------------------------------------------------


def _records_json(r: QueryResult) -> dict:
    # columnar encoding (timestamps stay epoch ints, like greptime's
    # HTTP default)
    from greptimedb_tpu.servers.encode import records_json

    return records_json(r)


def _matrix_body(times: np.ndarray, sm) -> bytes:
    """The response to a range query whose answer is a `SeriesMatrix`,
    written from its columns (`encode.matrix_body`): no Python object
    is made for a sample."""
    from greptimedb_tpu.promql.loaded import d2h, derive
    from greptimedb_tpu.servers.encode import matrix_body, metric_fragments
    from greptimedb_tpu.utils import tracing

    # the evaluation's device work ends here: the readback waits for it
    with tracing.stage("readback"):
        vals = d2h(sm.values)
    with tracing.stage("encode", series=len(sm.labels)):
        # the series' heads are kept beside the label sets they derive
        # from, as an aggregation's group index is
        heads, _ = derive(sm.labels, "metric_json", metric_fragments,
                          sm.metric)
        return matrix_body(times, vals, heads)


def _values_json(times: np.ndarray, vals: np.ndarray) -> list:
    out = []
    for t, v in zip(times.tolist(), np.asarray(vals).tolist()):
        if v is None or (isinstance(v, float) and math.isnan(v)):
            continue
        out.append([t, _fmt_float(v)])
    return out


def _fmt_float(v: float) -> str:
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if math.isnan(v):
        return "NaN"
    return repr(float(v))


def _prom_err(msg: str) -> dict:
    return {"status": "error", "errorType": "bad_data", "error": msg}


def _prom_time(s: str) -> float:
    try:
        return float(s)
    except ValueError:
        pass
    import datetime as dt
    t = s.replace("Z", "+00:00")
    return dt.datetime.fromisoformat(t).timestamp()


def _prom_duration(s: str) -> float:
    try:
        return float(s)
    except ValueError:
        from greptimedb_tpu.promql.parser import parse_duration_s
        return parse_duration_s(s)


def _match_params(handler: _Handler) -> list[str]:
    parsed = urllib.parse.urlparse(handler.path)
    qs = urllib.parse.parse_qs(parsed.query)
    matches = qs.get("match[]", [])
    body = getattr(handler, "_raw_body", b"")
    if body:
        try:
            form = urllib.parse.parse_qs(body.decode())
            matches += form.get("match[]", [])
        except UnicodeDecodeError:
            pass
    return matches


def _metric_of(match_expr: str) -> str:
    """Metric name from a simple match[] selector."""
    return match_expr.split("{")[0].strip() or match_expr

"""The request timeline inside the server (ISSUE 24): one flat set of
stage spans over every served SQL and PromQL request, compiles with an
owner, tier and PromQL transfer counters, kernel names, the profiler's
clock, and GET /debug/pprof/device.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from greptimedb_tpu.cli import build_standalone
from greptimedb_tpu.servers.http import HttpServer
from greptimedb_tpu.utils import device_telemetry, slow_query, tracing
from greptimedb_tpu.utils.metrics import (
    DEVICE_TRANSFER_BYTES,
    PROMQL_LOAD_CACHE_EVENTS,
    QUERY_TIER,
    STAGE_SECONDS,
    XLA_COMPILE_SECONDS,
    XLA_COMPILES,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T0 = 1_700_000_000  # seconds; 8 hosts x 200 samples every 15 s


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    home = str(tmp_path_factory.mktemp("stage_spans"))
    engine, qe = build_standalone(home)
    srv = HttpServer(qe, port=0)
    port = srv.start()
    s = _Server(port, qe)
    s.sql("CREATE TABLE m (host STRING, ts TIMESTAMP TIME INDEX, "
          "v DOUBLE, PRIMARY KEY(host)) WITH (append_mode='true')")
    vals = ",".join(f"('h{i % 8}', {(T0 + (i // 8) * 15) * 1000}, {i * 0.5})"
                    for i in range(8 * 200))
    s.sql(f"INSERT INTO m VALUES {vals}")
    s.sql("ADMIN flush_table('m')")
    yield s
    srv.stop()
    engine.close()


class _Server:
    def __init__(self, port, qe):
        self.port, self.qe = port, qe

    def get(self, path, data=None):
        req = urllib.request.Request(f"http://127.0.0.1:{self.port}{path}",
                                     data=data)
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                return r.status, r.read(), dict(r.headers)
        except urllib.error.HTTPError as e:
            return e.code, e.read(), dict(e.headers)

    def sql(self, q):
        st, body, hdr = self.get(
            "/v1/sql", urllib.parse.urlencode({"sql": q}).encode())
        assert st == 200, body
        return hdr

    def promql(self, query, instant=False):
        if instant:
            q = {"query": query, "time": T0 + 1500}
            path = "/api/v1/query?"
        else:
            q = {"query": query, "start": T0 + 600, "end": T0 + 2400,
                 "step": 15}
            path = "/api/v1/query_range?"
        st, body, hdr = self.get(path + urllib.parse.urlencode(q))
        assert st == 200, body
        assert json.loads(body)["data"]["result"]
        return hdr

    def spans_of(self, hdr):
        """The request's spans, once its root has been recorded (the
        root closes after the response's last byte is written)."""
        tid = hdr["traceparent"].split("-")[1][16:]
        for _ in range(200):
            spans = tracing.spans_for(tid)
            if any(s.name.startswith("http:") for s in spans):
                return spans
            time.sleep(0.01)
        raise AssertionError("the request root never closed")


def _requests(server):
    agg = "SELECT host, avg(v) FROM m GROUP BY host"
    point = (f"SELECT host, max(v) FROM m WHERE ts > {(T0 + 30) * 1000} "
             "GROUP BY host")
    return {
        "sql_aggregate": (
            lambda: server.sql(agg.replace("avg", "min")),
            {"parse", "plan", "scan", "assemble", "encode", "send"}),
        # the third sighting of a template binds without parsing
        "fast_lane_hit": (
            lambda: [server.sql(point.replace("30)", f"{30 + i})"))
                     for i in range(3)][-1],
            {"fast_bind", "scan", "assemble", "encode", "send"}),
        "lastpoint": (
            lambda: server.sql("SELECT host, last_value(v ORDER BY ts) "
                               "FROM m GROUP BY host"),
            {"parse", "plan", "scan", "host_agg", "upload", "device",
             "readback", "assemble", "encode", "send"}),
        "promql_rate_range": (
            lambda: server.promql("sum by (host) (rate(m[1m]))"),
            {"parse", "scan", "device", "readback", "assemble", "encode",
             "send"}),
        "promql_instant": (
            lambda: server.promql("m", instant=True),
            {"parse", "scan", "device", "readback", "encode", "send"}),
    }


@pytest.mark.parametrize("kind", ["sql_aggregate", "fast_lane_hit",
                                  "lastpoint", "promql_rate_range",
                                  "promql_instant"])
def test_request_yields_the_flat_stage_set(server, kind):
    send, must_have = _requests(server)[kind]
    spans = server.spans_of(send())
    root = next(s for s in spans if s.name.startswith("http:"))
    stages = [s for s in spans if s.stage]
    names = {s.name for s in stages}
    assert names <= set(tracing.STAGES)
    assert must_have <= names, (kind, sorted(names))
    # flat: a stage segment hangs off a plain span, never off a stage,
    # and no two segments of the request overlap in time
    stage_ids = {s.span_id for s in stages}
    assert not [s.name for s in stages if s.parent_id in stage_ids]
    ordered = sorted(stages, key=lambda s: s.started_at)
    for a, b in zip(ordered, ordered[1:]):
        assert a.started_at + a.duration_ms / 1e3 <= b.started_at + 2e-3
    # the sum of the stages plus `other` is the root's duration
    total = sum(s.duration_ms for s in stages)
    assert total <= root.duration_ms
    assert total + root.attrs["other_ms"] == pytest.approx(
        root.duration_ms, abs=0.01)
    assert f"stages_ms={total:.3f}"[:14] in root.attrs["ledger"]


def test_root_observes_other_and_request(server):
    n_other = STAGE_SECONDS.count(stage="other")
    n_req = STAGE_SECONDS.count(stage="request")
    s_other = STAGE_SECONDS.sum(stage="other")
    spans = server.spans_of(server.promql("sum(rate(m[1m]))"))
    root = next(s for s in spans if s.name.startswith("http:"))
    assert STAGE_SECONDS.count(stage="other") == n_other + 1
    assert STAGE_SECONDS.count(stage="request") == n_req + 1
    assert STAGE_SECONDS.sum(stage="other") - s_other == pytest.approx(
        root.attrs["other_ms"] / 1e3, abs=1e-5)
    # a route that runs no statement stays out of the stage histogram
    server.get("/v1/slow_queries")
    time.sleep(0.05)
    assert STAGE_SECONDS.count(stage="request") == n_req + 1


def test_explain_analyze_stages_stay_in_the_requests_sum(server):
    """EXPLAIN ANALYZE runs its statement under a fresh trace and a
    fresh ledger: what that spent in stages still leaves the request
    root's `other`."""
    spans = server.spans_of(server.sql(
        "EXPLAIN ANALYZE SELECT host, avg(v) FROM m GROUP BY host"))
    root = next(s for s in spans if s.name.startswith("http:"))
    led = dict(kv.split("=") for kv in root.attrs["ledger"].split())
    own = sum(s.duration_ms for s in spans if s.stage)
    assert float(led["scan_ms"]) > 0  # the inner statement's, forwarded
    assert float(led["stages_ms"]) > own
    assert float(led["stages_ms"]) + root.attrs["other_ms"] \
        == pytest.approx(root.duration_ms, abs=0.01)


def test_execute_labels_enclose_the_flat_stages(server):
    spans = server.spans_of(server.sql("SELECT host, sum(v) FROM m "
                                       "GROUP BY host"))
    ex = next(s for s in spans if s.name == "execute")
    assert not ex.stage
    inside = [s for s in spans if s.stage and s.name in (
        "scan", "device", "readback", "assemble", "host_agg", "upload")]
    assert inside
    for s in inside:
        assert ex.started_at <= s.started_at + 1e-3
        assert s.started_at + s.duration_ms / 1e3 \
            <= ex.started_at + ex.duration_ms / 1e3 + 2e-3


def test_nested_stage_splits_the_outer_into_segments():
    n_dev = STAGE_SECONDS.count(stage="device")
    with tracing.request_span("test:split"):
        tid = tracing.current_trace_id()
        with tracing.span("stmt"):
            with tracing.stage("device", kernel="k") as attrs:
                time.sleep(0.002)
                with tracing.stage("scan"):
                    time.sleep(0.002)
                    with tracing.span("decode_file"):
                        pass
                time.sleep(0.002)
                attrs["rows"] = 7
    spans = tracing.spans_for(tid)
    stmt = next(s for s in spans if s.name == "stmt")
    segs = sorted((s for s in spans if s.stage), key=lambda s: s.started_at)
    assert [s.name for s in segs] == ["device", "scan", "device"]
    assert all(s.parent_id == stmt.span_id for s in segs)
    # only the final segment sees what the body wrote at the end
    assert "rows" not in segs[0].attrs and segs[2].attrs["rows"] == 7
    plain = next(s for s in spans if s.name == "decode_file")
    assert plain.parent_id == segs[1].span_id and not plain.stage
    assert STAGE_SECONDS.count(stage="device") == n_dev + 2
    assert sum(s.duration_ms for s in segs) <= stmt.duration_ms


def test_background_thread_stages_are_plain_spans():
    seen = {}

    def work():
        with tracing.stage("device"):
            seen["warmup"] = tracing.in_warmup()

    n_dev = STAGE_SECONDS.count(stage="device")
    with tracing.request_span("test:bg") as root_attrs:
        tid = tracing.current_trace_id()
        t = threading.Thread(target=tracing.propagate(work, background=True))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    sp = next(s for s in tracing.spans_for(tid) if s.name == "bg:device")
    assert not sp.stage and sp.attrs["background"] is True
    assert seen["warmup"] is True and not tracing.in_warmup()
    assert STAGE_SECONDS.count(stage="device") == n_dev
    assert "stages_ms" not in root_attrs.get("ledger", "")


def test_tracing_off_is_a_no_op(monkeypatch):
    monkeypatch.setenv("GTPU_TRACING", "off")
    n = STAGE_SECONDS.count(stage="scan")
    with tracing.stage("scan", table="t") as attrs:
        attrs["rows"] = 1
    assert STAGE_SECONDS.count(stage="scan") == n


def _compiles(fn, thread):
    return XLA_COMPILES.total(fn=fn, thread=thread)


def test_compile_on_a_request_thread_is_owned_by_that_request(server):
    # a literal inside arithmetic stays in the predicate's shape (static
    # `where`), so a new one is a new executable; one compared with a
    # column is an operand and compiles nothing (tests/test_operands.py)
    n0 = _compiles("agg_block", "request")
    s0 = XLA_COMPILE_SECONDS.sum(backend="cpu", fn="agg_block",
                                 thread="request")
    spans = server.spans_of(server.sql(
        f"SELECT host, count(v) FROM m WHERE v * 1.0 < 123.25 AND "
        f"ts > {(T0 + 45) * 1000} GROUP BY host"))
    comp = [s for s in spans if s.name == "compile"
            and s.attrs["fn"] == "agg_block"]
    assert len(comp) == 1 and comp[0].attrs["thread"] == "request"
    by_id = {s.span_id: s for s in spans}
    # it hangs off the stage that waited for it
    assert by_id[comp[0].parent_id].name == "device"
    assert _compiles("agg_block", "request") == n0 + 1
    assert XLA_COMPILE_SECONDS.sum(
        backend="cpu", fn="agg_block", thread="request") - s0 \
        == pytest.approx(comp[0].attrs["seconds"], abs=1e-5)
    assert comp[0].duration_ms / 1e3 == pytest.approx(
        comp[0].attrs["seconds"], rel=0.2, abs=0.01)
    assert {s.attrs["fn"] for s in spans if s.name == "compile"} \
        <= device_telemetry.KERNEL_NAMES | {"eager"}


def test_compile_on_the_warm_up_thread_hangs_off_its_request():
    import jax
    import jax.numpy as jnp

    @jax.jit
    @device_telemetry.kernel_name("test_warm_kernel")
    def kern(x):
        return (x * 3.0 + 1.0).sum()

    def warm():
        kern(jnp.arange(17.0)).block_until_ready()

    n0 = _compiles("test_warm_kernel", "warmup")
    with tracing.request_span("test:warm"):
        tid = tracing.current_trace_id()
        with tracing.span("stmt:Select") as _:
            kicker = tracing.current_span_id()
            t = threading.Thread(
                target=tracing.propagate(warm, background=True))
            t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    comp = [s for s in tracing.spans_for(tid) if s.name == "compile"
            and s.attrs["fn"] == "test_warm_kernel"]
    assert len(comp) == 1
    assert comp[0].attrs["thread"] == "warmup"
    assert comp[0].parent_id == kicker
    assert _compiles("test_warm_kernel", "warmup") == n0 + 1
    assert _compiles("test_warm_kernel", "request") == 0


def test_device_hedge_warm_up_runs_under_propagate():
    # the one hedge both call sites use (query/tier.py TierRouter.kick)
    src = open(os.path.join(ROOT, "greptimedb_tpu", "query",
                            "tier.py")).read()
    assert src.count("tracing.propagate(warm, background=True)") == 1
    assert not re.search(r"Thread\(target=warm\b", src)
    executor = open(os.path.join(ROOT, "greptimedb_tpu", "query",
                                 "physical.py")).read()
    assert "target=warm" not in executor


def test_query_tier_total_moves_once_per_statement(server):
    def tiers():
        return {t: QUERY_TIER.total(tier=t)
                for t in ("host", "device", "mesh", "cache")}

    q = "SELECT host, max(v) FROM m WHERE v > 7.5 GROUP BY host"
    t0 = tiers()
    server.sql(q)  # uncached parts fold on the device (the CPU here)
    t1 = tiers()
    assert t1["device"] == t0["device"] + 1 and t1["cache"] == t0["cache"]
    server.sql(q)  # every part from the partial-aggregate cache
    t2 = tiers()
    assert t2["cache"] == t1["cache"] + 1 and t2["device"] == t1["device"]
    assert t2["host"] == t0["host"] and t2["mesh"] == t0["mesh"]


def test_promql_moves_the_transfer_counters(server):
    def moved():
        return (DEVICE_TRANSFER_BYTES.total(direction="h2d"),
                DEVICE_TRANSFER_BYTES.total(direction="d2h"),
                PROMQL_LOAD_CACHE_EVENTS.total(event="hit"),
                PROMQL_LOAD_CACHE_EVENTS.total(event="promote"))

    a = moved()
    server.promql("sum by (host) (avg_over_time(m[2m]))")
    b = moved()
    # a new selector over more than half of what the table holds: its
    # whole span is loaded at once: masks, factorization and the upload
    # of its 1600 samples (int32 series index, float64 time and value)
    assert b[3] == a[3] + 1
    assert b[0] - a[0] >= 1600 * (4 + 8 + 8)
    assert b[1] > a[1]
    server.promql("sum by (host) (avg_over_time(m[2m]))")
    c = moved()
    assert c[2] == b[2] + 1 and c[3] == b[3]
    # nothing goes up again: the samples are resident and, since
    # ISSUE 39, so is the aggregation's group index
    assert c[0] == b[0]
    # the answer alone: 8 series x 121 steps of float64 (the first
    # evaluation also read the sample grid back to pivot it)
    assert c[1] - b[1] == 8 * 121 * 8 < b[1] - a[1]


def test_the_group_labels_segment_carries_index_and_series(server):
    """ISSUE 39: the `assemble` segment of an aggregation's group index
    says how many input series it grouped and whether the index was
    kept beside the loaded series (`hit`) or built for the request."""
    q = "count without (zone) (avg_over_time(m[3m]))"  # this test's alone

    def segments():
        return [s for s in server.spans_of(server.promql(q))
                if s.stage and s.name == "assemble"
                and s.attrs.get("step") == "group_labels"]

    first, again = segments(), segments()
    assert all(s.attrs["series"] == 8 for s in first + again)
    # a build uploads the index: stage `upload` cuts the segment in two
    # and the closing one says which
    assert [s.attrs.get("index") for s in first][-1] == "build"
    assert [s.attrs.get("index") for s in again] == ["hit"]


def test_promql_slow_query_record_carries_its_stage_tree(server,
                                                         monkeypatch):
    monkeypatch.setenv("GTPU_SLOW_QUERY_MS", "0.0001")
    slow_query.clear()
    try:
        server.promql("sum by (host) (rate(m[2m]))")
        # the watch covers the socket write, so the record is made
        # after the response's last byte: wait for it as spans_of waits
        # for the root
        for _ in range(200):
            rec = next((r for r in slow_query.records(20)
                        if r.kind == "promql"), None)
            if rec is not None:
                break
            time.sleep(0.01)
        assert rec is not None, "the slow-query record never appeared"
        names = {name for _node, name, _ms in rec.stages}
        assert {"parse", "scan", "device", "readback", "encode",
                "send"} <= names
        assert rec.rows == 8
        assert rec.ledger["scan_ms"] > 0 and rec.ledger["encode_ms"] > 0
    finally:
        slow_query.clear()


def test_spans_enter_the_profilers_annotation(monkeypatch):
    import jax

    entered = []

    class Recorder:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            entered.append(("enter", self.name, self.kw))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    tracing.set_trace("ab" * 8)
    try:
        with tracing.span("stmt:Select"):
            with tracing.stage("device"):
                with tracing.stage("scan"):
                    pass
    finally:
        tracing.restore_trace(None)
    assert [e[:2] for e in entered] == [
        ("enter", "stmt:Select"), ("enter", "device"), ("exit", "device"),
        ("enter", "scan"), ("exit", "scan"), ("enter", "device"),
        ("exit", "device"), ("exit", "stmt:Select")]
    kw = entered[1][2]
    assert kw["trace_id"] == "ab" * 8 and len(kw["span_id"]) == 16


def test_tracing_imports_no_jax(monkeypatch):
    """utils/tracing.py names jax nowhere at import; a span in a process
    where jax is not loaded does not load it (and is not annotated)."""
    src = open(os.path.join(ROOT, "greptimedb_tpu", "utils",
                            "tracing.py")).read()
    assert not re.search(r"^\s*(import jax|from jax)", src, re.M)
    monkeypatch.delitem(sys.modules, "jax")
    with tracing.span("x"):
        with tracing.stage("scan"):
            pass
    assert "jax" not in sys.modules


def test_one_stage_mechanism():
    """No hand-written STAGE_SECONDS.observe outside utils/tracing.py;
    the per-request roofline fold is gone."""
    offenders = []
    for path in glob.glob(os.path.join(ROOT, "greptimedb_tpu", "**", "*.py"),
                          recursive=True):
        if path.endswith(os.path.join("utils", "tracing.py")):
            continue
        if "STAGE_SECONDS.observe(" in open(path).read():
            offenders.append(os.path.relpath(path, ROOT))
    assert not offenders
    assert not os.path.exists(os.path.join(ROOT, "greptimedb_tpu", "utils",
                                           "roofline.py"))
    from greptimedb_tpu.utils import metrics

    assert not hasattr(metrics, "QUERY_ACHIEVED_GBPS")
    assert "greptimedb_tpu_query_achieved_gbps" not in \
        metrics.REGISTRY.render()


def test_kernels_carry_their_stable_names():
    import jax
    import jax.numpy as jnp

    from greptimedb_tpu.ops import pallas_segment, window  # noqa: F401
    from greptimedb_tpu.promql import engine  # noqa: F401 — registers
    from greptimedb_tpu.query import physical  # noqa: F401 — registers

    assert {"agg_scan", "agg_scan_prepared", "agg_scan_fused",
            "agg_scan_sparse", "agg_scan_sparse_fused", "agg_scan_sharded",
            "agg_block", "agg_block_sparse", "prep_stream_step", "agg_step",
            "pallas_fused_segment_agg", "pallas_dense_segment_sum",
            "sort_compact", "sparse_segment_agg", "sort_dedup",
            "segment_agg", "window_stats", "window_edges",
            "window_edges_grid", "window_sums_grid", "counter_adjust",
            "extrapolated_delta", "promql_dedup", "histogram_fold"} \
        <= device_telemetry.KERNEL_NAMES
    lowered = window.counter_adjust.lower(
        jnp.zeros(8, jnp.int32), jnp.arange(8.0))
    # the module is jit_<name>; the scope prefixes the ops' metadata
    assert "module @jit_counter_adjust" in lowered.as_text()
    assert "counter_adjust/" in lowered.as_text(debug_info=True)
    assert jax.jit(device_telemetry.kernel_name("k_x")(lambda x: x + 1)) \
        .lower(1.0).as_text().startswith("module @jit_k_x")


class TestDeviceProfileEndpoint:
    def test_profile_holds_the_programs_spans(self, server):
        box = {}

        def take():
            box["first"] = server.get("/debug/pprof/device?seconds=1.5")

        t = threading.Thread(target=take)
        t.start()
        time.sleep(0.5)
        # a second caller, while the first session is open
        st, body, _ = server.get("/debug/pprof/device?seconds=0.1")
        assert st == 409 and b"already" in body
        # a selector no other test asks for: its samples are loaded (and
        # their grid read back) inside the session, whatever ran before
        server.promql('sum by (host) (rate(m{host!="none"}[1m]))')
        t.join(timeout=120)
        assert not t.is_alive()
        st, body, _ = box["first"]
        assert st == 200, body
        out = json.loads(body)
        assert out["t_stop_ns"] - out["t_start_ns"] >= 1.5e9
        assert os.path.dirname(out["dir"]).endswith("profiles")
        found = glob.glob(os.path.join(out["dir"], "plugins", "profile",
                                       "*", "*.xplane.pb"))
        assert len(found) == 1
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        try:
            import trace_gaps
        finally:
            sys.path.pop(0)
        spans = trace_gaps.read_xplane(found[0])["spans"]
        names = {s["name"] for s in spans}
        assert {"scan", "device", "readback", "encode", "send"} <= names
        assert all(len(s["span_id"]) == 16 for s in spans)

    def test_a_session_the_launcher_holds_gets_409(self, server, tmp_path):
        import jax

        jax.profiler.start_trace(str(tmp_path / "held"))
        try:
            st, body, _ = server.get("/debug/pprof/device?seconds=0.1")
        finally:
            jax.profiler.stop_trace()
        assert st == 409, body
        st, _body, _ = server.get("/debug/pprof/device?seconds=0.1")
        assert st == 200

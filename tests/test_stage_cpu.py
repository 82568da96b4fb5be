"""The interpreter lock as a measured layer (ISSUE 40): every span reads
its thread's CPU clock beside the wall clock, the stage spans add it to
query_stage_cpu_seconds_total under the labels of query_stage_seconds
(plus `background` for threads beside a request), the line-protocol
door observes its root's CPU, and a probe samples what re-taking the
lock costs. No test asserts a wall time: a stage SPINS until its
thread's CPU clock has advanced, or WAITS and is held to a ratio.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from greptimedb_tpu import options
from greptimedb_tpu.cli import build_standalone, stop_standalone
from greptimedb_tpu.servers.http import HttpServer
from greptimedb_tpu.utils import (device_telemetry, lock_probe, profiling,
                                  tracing)
from greptimedb_tpu.utils.metrics import (
    INGEST_REQUEST_CPU_SECONDS,
    LOCK_WAIT_SECONDS,
    STAGE_CPU_SECONDS,
    STAGE_SECONDS,
)

FLAT = tracing.STAGES + ("other",)
ENCLOSING = ("request", "execute", "fast_execute")
LABELS = FLAT + ENCLOSING + ("background",)
SPIN_S = 0.020


def _spin(seconds: float = SPIN_S) -> None:
    """Burn this thread's CPU until ITS clock has advanced `seconds`."""
    end = time.thread_time_ns() + int(seconds * 1e9)
    while time.thread_time_ns() < end:
        pass


def _cpu() -> dict:
    return {lab: STAGE_CPU_SECONDS.get(stage=lab) for lab in LABELS}


def _wall() -> dict:
    return {lab: STAGE_SECONDS.sum(stage=lab) for lab in FLAT + ENCLOSING}


def _moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


def _as_a_query(label: str, body) -> None:
    """Run `body` inside a request that ran a statement, where its time
    lands under `label`: in that flat stage, or outside every stage for
    `other` and `request`."""
    with tracing.request_span("test:stage_cpu"):
        with tracing.stage("parse"):
            pass  # the mark by which a root observes other and request
        if label in tracing.STAGES:
            with tracing.stage(label):
                body()
        else:
            body()


@pytest.mark.parametrize("label", tracing.STAGES + ("other", "request"))
def test_a_spinning_stage_adds_its_cpu_to_its_label_and_no_other(label):
    c0 = _cpu()
    _as_a_query(label, _spin)
    d = _moved(c0, _cpu())
    assert d[label] >= SPIN_S
    assert d["request"] >= SPIN_S
    landed = "other" if label == "request" else label
    for lab in FLAT:
        if lab != landed:
            assert d[lab] < d[landed] / 10, (lab, d)
    assert d["background"] == d["execute"] == d["fast_execute"] == 0.0
    # the flat labels are a partition of the root's CPU
    assert sum(d[lab] for lab in FLAT) == pytest.approx(d["request"],
                                                        rel=1e-6)


@pytest.mark.parametrize("label", tracing.STAGES + ("other", "request"))
def test_a_waiting_stage_is_off_the_cpu(label):
    ev = threading.Event()
    timer = threading.Timer(0.05, ev.set)

    def wait():
        timer.start()
        assert ev.wait(timeout=30)

    c0, w0 = _cpu(), _wall()
    _as_a_query(label, wait)
    timer.join(timeout=30)
    cpu, wall = _moved(c0, _cpu()), _moved(w0, _wall())
    assert cpu[label] < wall[label] / 10
    assert cpu["request"] < wall["request"] / 10


def test_a_stage_opened_inside_another_takes_its_cpu_out_of_the_outers():
    c0 = _cpu()
    t0 = time.thread_time_ns()
    with tracing.stage("device"):
        _spin()
        with tracing.stage("scan"):
            _spin()
    total = (time.thread_time_ns() - t0) / 1e9
    d = _moved(c0, _cpu())
    assert d["device"] >= SPIN_S and d["scan"] >= SPIN_S
    # were the inner's CPU also the outer's, the two would pass the
    # thread's whole CPU over the block
    assert d["device"] + d["scan"] <= total


def test_a_span_records_cpu_ms_and_the_tree_prints_it():
    with tracing.request_span("test:cpu_ms"):
        tid = tracing.current_trace_id()
        with tracing.span("work"):
            _spin()
    work = next(s for s in tracing.spans_for(tid) if s.name == "work")
    assert SPIN_S * 1e3 <= work.cpu_ms <= work.duration_ms
    wire = tracing.spans_to_wire([work])
    assert wire[0]["cpu_ms"] == pytest.approx(work.cpu_ms, abs=1e-3)
    back = tracing.merge_spans(
        [dict(wire[0], span_id="feedfacefeedface",
              started_at=work.started_at + 1.0)], node="dn-1", trace_id=tid)
    assert back[0].cpu_ms == wire[0]["cpu_ms"]
    line = next(ln for ln in tracing.render_tree(tracing.spans_for(tid))
                if "work:" in ln)
    assert f"(cpu {work.cpu_ms:.2f} ms)" in line


def _beside(work, background=False):
    t = threading.Thread(target=tracing.propagate(work, background))
    t.start()
    t.join(timeout=60)
    assert not t.is_alive()


def test_a_bg_spans_cpu_lands_under_background_and_its_wall_nowhere():
    c0, w0 = _cpu(), _wall()
    with tracing.request_span("test:bg_cpu") as root:
        tid = tracing.current_trace_id()
        with tracing.stage("parse"):
            pass

        def work():
            with tracing.stage("scan"):
                _spin()

        _beside(work)
    cpu, wall = _moved(c0, _cpu()), _moved(w0, _wall())
    assert cpu["background"] >= SPIN_S
    assert cpu["scan"] == 0.0 and wall["scan"] == 0.0
    # the worker's CPU is not the request thread's
    assert cpu["request"] < cpu["background"] / 2
    sp = next(s for s in tracing.spans_for(tid) if s.name == "bg:scan")
    assert sp.cpu_ms >= SPIN_S * 1e3 and not sp.stage
    assert "scan_cpu_ms" not in root["ledger"]


def test_a_thread_beside_the_request_counts_each_cpu_second_once():
    """Inside a `bg:` span, inside one nested in another, or in no span
    at all (the scan pool's decode opens none): all of it is the
    worker's CPU, and none of it counts twice."""
    c0 = _cpu()
    spent = {}

    def work():
        t0 = time.thread_time_ns()
        _spin()
        with tracing.stage("host_agg"):
            _spin()
            with tracing.stage("scan"):
                _spin()
        spent["s"] = (time.thread_time_ns() - t0) / 1e9

    with tracing.request_span("test:bg_nested"):
        _beside(work)
    d = _moved(c0, _cpu())
    assert 3 * SPIN_S <= spent["s"] <= d["background"] < 1.3 * spent["s"]
    assert all(d[lab] == 0.0 for lab in tracing.STAGES)


def test_a_propagated_call_run_inline_is_its_own_threads_cpu():
    c0 = _cpu()

    def work():
        with tracing.stage("scan"):
            _spin()

    def inline():
        tracing.propagate(work)()  # a pool's saturated fallback

    _as_a_query("device", inline)
    d = _moved(c0, _cpu())
    assert d["background"] == 0.0
    # on the request thread it is the open stage's CPU, as its wall is
    assert d["device"] >= SPIN_S and d["request"] >= SPIN_S


def test_tracing_off_moves_no_counter_and_starts_no_probe(monkeypatch):
    monkeypatch.setenv("GTPU_TRACING", "off")
    lock_probe.shutdown()
    c0, n0 = _cpu(), INGEST_REQUEST_CPU_SECONDS.total_count()

    def work():
        with tracing.stage("scan"):
            _spin(0.002)

    _as_a_query("device", lambda: (_spin(0.002), _beside(work)))
    assert _cpu() == c0
    assert INGEST_REQUEST_CPU_SECONDS.total_count() == n0
    options.apply_observability(options.StandaloneOptions())
    assert not lock_probe.running()
    assert not _probe_threads()


def test_the_encoders_cpu_is_the_requests():
    """An answer is encoded on the thread that owns the request: the
    `encode` span holds the encoder's CPU (within its wall time), the
    stage's counter takes it, nothing lands under `background`, and no
    `bg:encode` span exists."""
    import numpy as np

    from greptimedb_tpu.query.result import QueryResult
    from greptimedb_tpu.servers.encode import encode_sql_payload

    result = QueryResult(["v"], [None], [np.arange(4.0)])
    c0 = _cpu()
    with tracing.request_span("test:encode"):
        tid = tracing.current_trace_id()
        with tracing.stage("parse"):
            pass
        with tracing.stage("encode"):
            _spin()
            out = encode_sql_payload([result], 1.0)
    assert json.loads(out)["output"][0]["records"]["total_rows"] == 4
    d = _moved(c0, _cpu())
    assert d["encode"] >= SPIN_S
    assert d["background"] == 0.0
    spans = tracing.spans_for(tid)
    assert "bg:encode" not in {s.name for s in spans}
    (enc,) = [s for s in spans if s.name == "encode"]
    assert SPIN_S * 1000 <= enc.cpu_ms <= enc.duration_ms


# ---- through the server ------------------------------------------------------

T0 = 1_700_000_000


class _Server:
    def __init__(self, port):
        self.port = port

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
        # a root closes, and observes, after its response is written:
        # no request of one test may land in the next one's counters
        self.spans_of(hdr)
        return hdr, json.loads(body)

    def spans_of(self, hdr):
        tid = hdr["traceparent"].split("-")[1][16:]
        for _ in range(500):
            spans = tracing.spans_for(tid)
            if any(s.name.startswith("http:") for s in spans):
                return tid, spans
            time.sleep(0.01)
        raise AssertionError("the request root never closed")


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    home = str(tmp_path_factory.mktemp("stage_cpu"))
    engine, qe = build_standalone(home, options.StandaloneOptions())
    srv = HttpServer(qe, port=0)
    s = _Server(srv.start())
    s.sql("CREATE TABLE m (host STRING, ts TIMESTAMP TIME INDEX, "
          "v DOUBLE, PRIMARY KEY(host)) WITH (append_mode='true')")
    vals = ",".join(f"('h{i % 8}', {(T0 + (i // 8) * 15) * 1000}, {i * 0.5})"
                    for i in range(8 * 100))
    s.sql(f"INSERT INTO m VALUES {vals}")
    yield s
    stop_standalone(engine, qe, [srv])


def test_the_flat_stages_and_other_add_up_to_the_roots_cpu(server):
    c0 = _cpu()
    roots = []
    for i in range(4):
        hdr, _ = server.sql(f"SELECT host, max(v) FROM m WHERE ts > "
                            f"{(T0 + 15 * i) * 1000} GROUP BY host")
        _, spans = server.spans_of(hdr)
        root = next(s for s in spans if s.name.startswith("http:"))
        staged = sum(s.cpu_ms for s in spans if s.stage)
        assert staged + root.attrs["other_cpu_ms"] == pytest.approx(
            root.cpu_ms, abs=0.01)
        assert all(0.0 <= s.cpu_ms <= s.duration_ms for s in spans)
        roots.append(root)
    d = _moved(c0, _cpu())
    # over a window, as the benchmark's metrics read it
    assert sum(d[lab] for lab in FLAT) == pytest.approx(d["request"],
                                                        rel=0.01)
    assert d["request"] == pytest.approx(
        sum(r.cpu_ms for r in roots) / 1e3, rel=1e-6)
    # the executor call encloses flat stages on one of the two lanes
    assert d["execute"] + d["fast_execute"] > 0.0


def test_the_ledger_analyze_and_the_trace_route_carry_cpu(server):
    hdr, _ = server.sql("SELECT host, min(v) FROM m GROUP BY host")
    tid, spans = server.spans_of(hdr)
    root = next(s for s in spans if s.name.startswith("http:"))
    led = dict(kv.split("=") for kv in root.attrs["ledger"].split())
    assert float(led["stages_cpu_ms"]) <= float(led["stages_ms"])
    for s in spans:
        if s.stage:
            assert float(led[s.name + "_cpu_ms"]) <= float(led[s.name + "_ms"])
    st, body, _ = server.get(f"/v1/traces/{tid}")
    assert st == 200
    out = json.loads(body)
    assert all("cpu_ms" in s for s in out["spans"])
    assert any("(cpu " in ln for ln in out["tree"])
    _, out = server.sql("EXPLAIN ANALYZE SELECT host, avg(v) FROM m "
                        "GROUP BY host")
    text = "\n".join(str(r[0]) for r in out["output"][-1]["records"]["rows"])
    ledger_line = next(ln for ln in text.splitlines()
                       if "resource ledger:" in ln)
    assert "scan_cpu_ms=" in ledger_line and "stages_cpu_ms=" in ledger_line
    assert "(cpu " in text


def test_the_line_protocol_door_observes_its_cpu_once_a_request(server):
    c0, n0 = _cpu(), INGEST_REQUEST_CPU_SECONDS.total_count()
    s0 = INGEST_REQUEST_CPU_SECONDS.total_sum()
    for i in range(3):
        st, body, _ = server.get(
            "/v1/influxdb/write?precision=ms",
            f"door,host=a v={i}.5 {(T0 + i) * 1000}\n".encode())
        assert st == 204, body
    for _ in range(500):
        if INGEST_REQUEST_CPU_SECONDS.total_count() == n0 + 3:
            break
        time.sleep(0.01)  # a root closes after its response is written
    assert INGEST_REQUEST_CPU_SECONDS.total_count() == n0 + 3
    assert INGEST_REQUEST_CPU_SECONDS.total_sum() > s0
    # a write runs no statement: no `request`, no `other`
    d = _moved(c0, _cpu())
    assert d["request"] == 0.0 and d["other"] == 0.0


# ---- the probe ---------------------------------------------------------------


def _probe_threads() -> list:
    return [t for t in threading.enumerate() if t.name == "gtpu-lock-probe"]


def _mean_wait(seconds: float) -> float:
    s0, n0 = LOCK_WAIT_SECONDS.total_sum(), LOCK_WAIT_SECONDS.total_count()
    time.sleep(seconds)
    n = LOCK_WAIT_SECONDS.total_count() - n0
    assert n >= 5
    return (LOCK_WAIT_SECONDS.total_sum() - s0) / n


def test_the_probe_reads_a_held_lock_several_times_its_idle_reading(server):
    assert lock_probe.running() and len(_probe_threads()) == 1
    idle = _mean_wait(0.6)
    stop = []

    def hold():
        x = 0
        while not stop:
            x += 1  # pure Python: the lock goes only at the interval

    old = sys.getswitchinterval()
    holder = threading.Thread(target=hold)
    sys.setswitchinterval(0.05)
    try:
        holder.start()
        held = _mean_wait(1.2)
    finally:
        stop.append(1)
        sys.setswitchinterval(old)
        holder.join(timeout=30)
    assert not holder.is_alive()
    assert held > 3 * idle, (held, idle)


def test_the_probe_is_absent_from_the_cpu_profile(server):
    assert lock_probe.running()
    folded = profiling.sample_cpu(seconds=0.3, hz=100, include_idle=True)
    assert "lock_probe" not in folded
    st, body, _ = server.get("/debug/pprof/cpu?seconds=0.2")
    assert st == 200 and b"lock_probe" not in body


def test_the_probe_stops_with_the_server_and_leaves_no_thread(tmp_path):
    engine, qe = build_standalone(str(tmp_path),
                                  options.StandaloneOptions())
    srv = HttpServer(qe, port=0)
    srv.start()
    assert lock_probe.running() and len(_probe_threads()) == 1
    probe = _probe_threads()[0]
    assert probe.ident in profiling._PROFILER_TIDS
    stop_standalone(engine, qe, [srv])
    assert not lock_probe.running() and not _probe_threads()
    assert probe.ident not in profiling._PROFILER_TIDS


# ---- the compile span's annotation -------------------------------------------


def test_a_compile_spans_annotation_names_its_kernel(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    @device_telemetry.kernel_name("test_stat_kernel")
    def kern(x):
        return (x * 5.0 - 2.0).sum()

    box = {}

    def profile():
        box["out"] = profiling.device_trace(1.5, str(tmp_path))

    t = threading.Thread(target=profile)
    t.start()
    # a new shape is a new compile: one every 0.1 s for as long as the
    # session is open, whenever under load it happens to open
    n = 23
    with tracing.request_span("test:compile_stats"):
        while t.is_alive():
            kern(jnp.arange(float(n))).block_until_ready()
            n += 1
            time.sleep(0.1)
    t.join(timeout=120)
    assert not t.is_alive()
    found = glob.glob(os.path.join(box["out"]["dir"], "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(found) == 1
    data = jax.profiler.ProfileData.from_file(found[0])
    stats = [dict(ev.stats) for plane in data.planes for line in plane.lines
             for ev in line.events if ev.name == "compile"]
    mine = [st for st in stats if st.get("fn") == "test_stat_kernel"]
    assert 1 <= len(mine) <= n - 23
    assert all(st["thread"] == "request" and st["span_id"] for st in mine)

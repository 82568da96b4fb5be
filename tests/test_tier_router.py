"""query/tier.py, the one owner of "where does this work run": the
router's choice as a table, the first-touch hedge through BOTH of its
call sites in the executor (the classic whole-scan aggregate and the
incremental per-part fold), TQL asking the router, and the keys
GET /v1/device carries. Tests run on the CPU backend; an accelerator is
a stubbed jax.default_backend (the host tier's CPU device is real)."""

import http.client
import json
import threading
import time

import jax
import pytest

import greptimedb_tpu.query.tier as tiering
from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import partial_cache as pc
from greptimedb_tpu.query.engine import QueryContext, QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.storage.region import ScanExpired
from greptimedb_tpu.utils.metrics import DEVICE_DEGRADATIONS

CTX = QueryContext()
MESH = object()  # choose() only asks whether there is one
AGG = object()   # ... and whether the work is an aggregate
MESH_MIN_ROWS = 65536


@pytest.fixture
def db(tmp_path):
    pc.global_cache().clear()
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    yield eng, qe
    eng.close()
    pc.global_cache().clear()


# ---- (a) the choice ---------------------------------------------------------


@pytest.mark.parametrize(
    "backend, mesh, mode, agg, rows, streaming, want", [
        ("cpu", None, "auto", AGG, 10**9, False, "device"),
        ("cpu", None, "force", AGG, 1000, False, "device"),
        ("tpu", None, "auto", AGG, 1000, False, "device"),
        ("tpu", None, "off", AGG, 1000, False, "device"),
        ("tpu", None, "force", AGG, 20_000_000, False, "host"),
        ("tpu", None, "force", None, 0, False, "host"),
        ("tpu", None, "auto", None, 20_000_000, False, "device"),
        ("tpu", None, "auto", AGG, 10**8, True, "device"),
        ("tpu", MESH, "auto", AGG, MESH_MIN_ROWS, False, "mesh"),
        ("tpu", MESH, "auto", AGG, MESH_MIN_ROWS - 1, False, "device"),
        ("tpu", MESH, "auto", AGG, 10**8, True, "device"),
        ("tpu", MESH, "auto", None, 10**8, False, "device"),
        ("cpu", MESH, "auto", AGG, MESH_MIN_ROWS, False, "mesh"),
        ("tpu", MESH, "force", AGG, 1000, False, "device"),
    ])
def test_choose(monkeypatch, backend, mesh, mode, agg, rows, streaming,
                want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setenv("GREPTIMEDB_TPU_HOST_TIER", mode)
    monkeypatch.delenv("GREPTIMEDB_TPU_MESH_MIN_ROWS", raising=False)
    router = tiering.TierRouter(mesh, lambda kind, what: None)
    assert router.choose(agg, rows, streaming) == want
    # hedged is what choose() sent to an accelerator's one device under
    # auto, and nothing else
    assert router.hedges(want) == (
        (backend, mesh, mode, want) == ("tpu", None, "auto", "device"))


# ---- (b) the hedge, through both call sites ---------------------------------

AGG_SQL = ("SELECT host, sum(v), count(v), max(w) FROM cpu "
           "GROUP BY host ORDER BY host")


@pytest.fixture(params=["whole_scan", "incremental"])
def hedged(request, db, monkeypatch):
    """An executor on a stubbed accelerator, auto mode, no mesh, whose
    AGG_SQL reaches the hedge through the call site named by the param.
    Warm-up threads stop at the gate (unless `gate` is set beforehand)
    and then do what `in_warmup` says; `threads` holds them."""
    eng, qe = db
    qe.execute_one(
        "CREATE TABLE cpu (ts TIMESTAMP(3) TIME INDEX, host STRING, "
        "v DOUBLE, w DOUBLE, PRIMARY KEY(host)) "
        "WITH (append_mode='true')", CTX)
    rid = qe.catalog.table("public", "cpu").region_ids[0]
    for f in range(2):
        qe.execute_one("INSERT INTO cpu VALUES " + ", ".join(
            f"({f * 1_000_000 + i * 10}, 'h{i % 5}', {f * 100 + i}.0, "
            f"{i % 7}.0)" for i in range(120)), CTX)
        eng.flush(rid)
    qe.execute_one("INSERT INTO cpu VALUES " + ", ".join(
        f"({2_000_000 + i * 10}, 'h{i % 5}', {i}.0, 1.0)"
        for i in range(40)), CTX)
    monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE",
                       "on" if request.param == "incremental" else "off")
    monkeypatch.setenv("GREPTIMEDB_TPU_HOST_TIER", "auto")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ex = qe.executor
    monkeypatch.setattr(ex, "mesh", None)

    class Hedged:
        def __init__(self):
            self.site = request.param
            self.gate = threading.Event()
            self.threads = set()
            self.in_warmup = lambda: None

        def run(self):
            res = qe.execute_one(AGG_SQL, CTX)
            want = "incremental" if self.site == "incremental" \
                else "dense_prepared"
            assert ex.last_path == want, ex.last_path
            return res, ex.last_tier

        def warmup(self):
            return ex.router.status()["warmup"]

        def settle(self):
            self.gate.set()
            for _ in range(200):
                if not self.warmup()["warming"]:
                    return self.warmup()
                time.sleep(0.05)
            raise AssertionError("warm-up never finished")

    h = Hedged()
    real_enter = tiering.TierCtx.__enter__

    def enter(ctx):
        if threading.current_thread().name == "gtpu-device-warm":
            h.threads.add(threading.current_thread())
            h.gate.wait(30)
            h.in_warmup()
        return real_enter(ctx)

    monkeypatch.setattr(tiering.TierCtx, "__enter__", enter)
    yield h
    h.settle()


def _failures():
    return DEVICE_DEGRADATIONS.get(kind="warmup_failed")


def _raiser(exc):
    def go():
        raise exc
    return go


def test_first_touch_serves_host_and_the_shape_turns_warm(hedged):
    hedged.gate.set()
    first, tier = hedged.run()
    assert tier == "host"  # hedged: no compile stall
    assert hedged.settle() == {"warm": 1, "warming": 0, "failed": 0}
    again, tier = hedged.run()
    assert tier == "device"  # warm: the device serves
    assert again.rows() == first.rows()
    assert len(hedged.threads) == 1


def test_second_request_while_warming_starts_no_second_thread(hedged):
    _, tier = hedged.run()
    assert tier == "host"
    _, tier = hedged.run()  # the first warm-up still stands at the gate
    assert tier == "host"
    assert hedged.warmup() == {"warm": 0, "warming": 1, "failed": 0}
    assert hedged.settle() == {"warm": 1, "warming": 0, "failed": 0}
    assert len(hedged.threads) == 1


def test_failing_warmup_is_counted_once_and_the_shape_stays_on_host(hedged):
    hedged.in_warmup = _raiser(RuntimeError("Mosaic refused the kernel"))
    before = _failures()
    first, tier = hedged.run()
    assert tier == "host"
    assert hedged.settle() == {"warm": 0, "warming": 0, "failed": 1}
    assert _failures() == before + 1
    again, tier = hedged.run()
    assert tier == "host"  # a known-failing compile is not kicked again
    assert again.rows() == first.rows()
    assert hedged.settle() == {"warm": 0, "warming": 0, "failed": 1}
    assert _failures() == before + 1
    assert len(hedged.threads) == 1


def test_scan_expired_in_a_warmup_leaves_the_shape_unmarked(hedged):
    hedged.in_warmup = _raiser(ScanExpired("snapshot released"))
    before = _failures()
    _, tier = hedged.run()
    assert tier == "host"
    # nothing was learned about the device: neither warm nor failed
    assert hedged.settle() == {"warm": 0, "warming": 0, "failed": 0}
    assert _failures() == before
    hedged.in_warmup = lambda: None
    _, tier = hedged.run()  # so the next request's hedge warms it
    assert tier == "host"
    assert hedged.settle() == {"warm": 1, "warming": 0, "failed": 0}
    assert len(hedged.threads) == 2


# ---- (b2) shape keys: one table's program answers another's request ---------

METRIC_DDL = ("CREATE TABLE {name} (host STRING, job STRING, val DOUBLE, "
              "ts TIMESTAMP TIME INDEX, PRIMARY KEY(host, job)) "
              "ENGINE=metric")
PANEL = ("SELECT date_bin(INTERVAL '10 second', ts) AS b, max(v) FROM plain "
         "WHERE host IN {hosts!r} AND ts >= {lo} AND ts < {hi} "
         "GROUP BY b ORDER BY b")


@pytest.fixture
def fleet(db, monkeypatch):
    """One metric-engine physical region under four logical tables —
    `m_a` and its twin `m_a2` (equal schema and row count), `m_b` with a
    row written twice, `m_c` with a tombstone — beside a plain append
    table of three small SSTs, all flushed; then the accelerator stub of
    `hedged`. `rows` is what last-write-wins leaves in each table."""
    eng, qe = db
    rows = {}
    for name, n in (("m_a", 50), ("m_a2", 50), ("m_b", 300), ("m_c", 2000)):
        qe.execute_one(METRIC_DDL.format(name=name), CTX)
        qe.execute_one(
            f"INSERT INTO {name} (host, job, val, ts) VALUES " + ", ".join(
                f"('h{i % 7}', 'j', {i}.0, {1000 + i * 10})"
                for i in range(n)), CTX)
        rows[name] = n
    qe.execute_one("INSERT INTO m_b (host, job, val, ts) VALUES "
                   "('h0', 'j', 99.0, 1000)", CTX)  # written twice
    qe.execute_one("DELETE FROM m_c WHERE host = 'h1' AND job = 'j' "
                   "AND ts = 1010", CTX)
    rows["m_c"] -= 1
    qe.execute_one(
        "CREATE TABLE plain (ts TIMESTAMP(3) TIME INDEX, host STRING, "
        "v DOUBLE, PRIMARY KEY(host)) WITH (append_mode='true')", CTX)
    plain = []
    for f in range(3):
        part = [(f * 100_000 + i * 1000 + h, f"h{h}",
                 float((f * 131 + i * 17 + h * 29) % 97))
                for i in range(100) for h in range(6)]
        qe.execute_one("INSERT INTO plain VALUES " + ", ".join(
            f"({t}, '{h}', {v})" for t, h, v in part), CTX)
        eng.flush(qe.catalog.table("public", "plain").region_ids[0])
        plain += part
    rows["plain"] = len(plain)
    for rid in list(eng.regions):
        eng.flush(rid)
    monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "on")
    monkeypatch.setenv("GREPTIMEDB_TPU_HOST_TIER", "auto")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ex = qe.executor
    monkeypatch.setattr(ex, "mesh", None)

    class Fleet:
        def __init__(self):
            self.rows, self.plain = rows, plain

        def ask(self, sql):
            """(rows, tier, compiles on the request's thread, the
            program event of its last dispatch) of one statement, once
            the warm-ups it kicked have settled."""
            from greptimedb_tpu.utils.metrics import (
                AGG_PROGRAM_EVENTS,
                XLA_COMPILES,
            )

            events = ("reuse", "new", "static_literal")
            n0 = XLA_COMPILES.total(thread="request")
            e0 = {e: AGG_PROGRAM_EVENTS.get(event=e) for e in events}
            got = qe.execute_one(sql, CTX).rows()
            tier = ex.last_tier
            compiled = XLA_COMPILES.total(thread="request") - n0
            moved = {e for e in events
                     if AGG_PROGRAM_EVENTS.get(event=e) > e0[e]}
            for _ in range(600):
                if not ex.router.status()["warmup"]["warming"]:
                    break
                time.sleep(0.05)
            else:
                raise AssertionError("warm-up never finished")
            assert ex.router.status()["warmup"]["failed"] == 0
            return got, tier, compiled, moved

    return Fleet()


def test_a_program_compiled_for_one_table_answers_another(fleet):
    """What PR 28 could not see before a chip call: once hedge keys are
    shapes, a `count(*)` is answered by a device program that another
    table's warm-up compiled — if, and only if, every static input of
    its programs is equal (the last-write-wins mask is built over the
    scan's own row count, so a non-append table of another size is
    hedged again). Never a cold compile in the foreground of a request
    the device answers, every count what last-write-wins leaves, no
    degradation."""
    degraded = DEVICE_DEGRADATIONS.total()
    tables = ["m_a", "m_a2", "m_b", "m_c", "plain"]
    first = {}
    for name in tables:
        got, tier, compiled, _ = fleet.ask(f"SELECT count(*) FROM {name}")
        assert got == [[fleet.rows[name]]], name
        assert tier == "host" or compiled == 0, (name, tier, compiled)
        first[name] = tier
    # equal static inputs: the twin rides the first table's program;
    # another row count under a dedup mask, another block: hedged again
    assert first == {"m_a": "host", "m_a2": "device", "m_b": "host",
                     "m_c": "host", "plain": "host"}
    for name in tables:
        got, tier, compiled, moved = fleet.ask(
            f"SELECT count(*) FROM {name}")
        assert got == [[fleet.rows[name]]], name
        # (the append table's parts come from the partial cache: no
        # dispatch at all)
        assert (tier, compiled) == ("device", 0) and moved <= {"reuse"}, name
    assert DEVICE_DEGRADATIONS.total() == degraded


def _panel_reference(plain, hosts, lo, hi):
    out = {}
    for t, h, v in plain:
        if h in hosts and lo <= t < hi:
            b = t // 10_000 * 10_000
            out[b] = max(out.get(b, float("-inf")), v)
    return [[b, out[b]] for b in sorted(out)]


def test_two_host_sets_and_two_windows_share_the_warm_shape(fleet):
    """A `single-groupby`-shaped panel: the first request of the shape
    is hedged; another host set over another ms-granular window of the
    same length is the same shape, so the device answers it with the
    program the warm-up compiled — its own hosts' rows, its own
    buckets."""
    degraded = DEVICE_DEGRADATIONS.total()
    sets = [("h1", "h2"), ("h4", "h5")]
    windows = [(20_123, 80_123), (130_777, 190_777)]
    asked = [(sets[0], windows[0]), (sets[1], windows[1]),
             (sets[0], windows[1]), (sets[1], windows[0])]
    answers = {}
    for at, (hosts, (lo, hi)) in enumerate(asked):
        got, tier, compiled, moved = fleet.ask(
            PANEL.format(hosts=hosts, lo=lo, hi=hi))
        got = [[int(b), float(v)] for b, v in got]
        assert got == _panel_reference(fleet.plain, hosts, lo, hi)
        assert got[0][0] == lo // 10_000 * 10_000 and len(got) == 7
        if at == 0:
            assert tier == "host"
        else:
            assert (tier, compiled, moved) == ("device", 0, {"reuse"}), at
        answers[hosts, lo] = got
    for lo, _hi in windows:
        assert answers[sets[0], lo] != answers[sets[1], lo]
    assert DEVICE_DEGRADATIONS.total() == degraded


# ---- (c) TQL asks the router ------------------------------------------------


@pytest.mark.parametrize("mode, want", [("force", "host"),
                                        ("auto", "device")])
def test_tql_eval_takes_the_tier_choose_gives(db, monkeypatch, mode, want):
    _eng, qe = db
    monkeypatch.setenv("GREPTIMEDB_TPU_HOST_TIER", mode)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(qe.executor, "mesh", None)
    assert qe.executor.router.choose(None, 0) == want
    seen = []
    monkeypatch.setattr(
        qe, "_tql_inner",
        lambda stmt, ctx: seen.append(tiering.ACTIVE_TIER.get()))
    qe.execute_one("TQL EVAL (0, 10, '5s') up", CTX)
    assert seen == [want]
    assert tiering.ACTIVE_TIER.get() == "device"  # and gives it back


# ---- (d) GET /v1/device -----------------------------------------------------


def test_device_status_over_http_keeps_every_key(db):
    from greptimedb_tpu.servers.http import HttpServer

    _eng, qe = db
    srv = HttpServer(qe, port=0)
    port = srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("GET", "/v1/device")
        st = json.loads(conn.getresponse().read())
    finally:
        srv.stop()
    assert set(st) == {
        "platform", "device_kind", "count", "devices", "mesh",
        "compute_dtype", "link", "host_tier_mode", "pallas", "warmup",
        "degradations", "compile_cache_dir", "native_available"}
    assert set(st["link"]) == {"backend", "rtt_ms", "d2h_mbps", "colocated"}
    assert st["host_tier_mode"] == "auto"
    assert set(st["pallas"]) == {"mode", "dispatch_mode", "canaries",
                                 "fused_disabled", "partial_disabled"}
    # the harness waits on warmup.warming == 0 before it sends traffic
    assert st["warmup"] == {"warm": 0, "warming": 0, "failed": 0}
    assert isinstance(st["degradations"], list)

"""Vectorized multi-query serving (ISSUE 11): the vmap'd stacked
multi-query kernel (bit-for-bit vs serial execution, including
window-union and multi-tag members), the wider batching shapes, the
zero-GIL result-encode path (byte-identical responses under the encode
pool, admission slot released at execute-done), typed-Overloaded
bounds under burst with batching on, plan-cache skip-reason
visibility, and runtime lockdep over the new encode-pool/batcher
locks."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.parse

import numpy as np
import pytest

from greptimedb_tpu.catalog.catalog import Catalog
from greptimedb_tpu.catalog.kv import MemoryKv
from greptimedb_tpu.concurrency import (
    ConcurrencyConfig,
    ConcurrencyPlane,
)
from greptimedb_tpu.concurrency import batcher as batcher_mod
from greptimedb_tpu.concurrency.encode_pool import EncodePool
from greptimedb_tpu.query.engine import QueryEngine
from greptimedb_tpu.query.result import QueryResult
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine
from greptimedb_tpu.utils.metrics import (
    ENCODE_POOL_EVENTS,
    PLAN_CACHE_EVENTS,
    QUERY_BATCH_EVENTS,
    VMAP_BATCH_WIDTH,
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


def batch_plane(window_ms=25.0, **kw):
    # batcher-layer tests: the parse-free fast lane would serve these
    # repeat shapes before they could form batch groups
    kw.setdefault("fast_lane", False)
    return ConcurrencyPlane(ConcurrencyConfig(batch_window_ms=window_ms,
                                              **kw))


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


DASH2 = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, max(v), "
         "sum(v), avg(v) FROM cpu WHERE host = '{h}' AND dc = '{d}' AND "
         "ts >= {lo} AND ts < {hi} GROUP BY minute")


# ---- the vmap'd multi-query kernel ------------------------------------------


class TestVmappedKernel:
    def _analyze_group(self, qe, sqls):
        """Parse + analyze a set of statements; they must share one
        masked shape. Returns (leader sel, shape, member order,
        per-sql member values)."""
        from greptimedb_tpu.sql.parser import parse_sql

        ctx = QueryContext()
        info = qe._table("cpu", ctx)
        shapes = []
        for sql in sqls:
            sel = parse_sql(sql)[0]
            sh = batcher_mod.analyze(sel, info)
            assert sh is not None, sql
            shapes.append((sel, sh))
        assert len({sh.masked for _, sh in shapes}) == 1
        order = []
        for _, sh in shapes:
            if sh.values not in order:
                order.append(sh.values)
        return info, shapes[0][0], shapes[0][1], order, \
            [sh.values for _, sh in shapes]

    def test_vmapped_bit_for_bit_multi_tag_and_window_union(self, tmp_path):
        """The acceptance differential: one vmapped dispatch over
        members that differ in BOTH tag selectors and in their time
        window (plus one member naming an absent tag value) must equal
        each member's serial execution exactly — values, dtypes, and
        row order."""
        from greptimedb_tpu.query.vmapped import run_vmapped

        engine, qe = make_qe(tmp_path)
        create_cpu(qe, two_tags=True)
        ingest(qe, hosts=4, dcs=2, points=120)
        sqls = [DASH2.format(h=f"h{i % 4}", d=f"dc{i % 2}",
                             lo=(i % 3) * 20_000,
                             hi=60_000 + (i % 3) * 20_000)
                for i in range(8)]
        sqls.append(DASH2.format(h="absent", d="dc0", lo=0, hi=60_000))
        info, leader, shape, order, per_sql = self._analyze_group(qe, sqls)
        assert len(order) == 9
        # window-union and multi-tag parameters both made it in
        kinds = {p.kind for p in shape.params}
        assert kinds == {"tag", "ts"}
        assert sum(p.kind == "tag" for p in shape.params) == 2
        results = run_vmapped(qe.executor, leader, info, shape.params,
                              order)
        assert qe.executor.last_path == "dense_vmapped"
        for sql, vals in zip(sqls, per_sql):
            got = results[order.index(vals)]
            with qe.concurrency.suppress_batching():
                want = qe.execute_one(sql)
            assert got.names == want.names, sql
            assert got.rows() == want.rows(), sql
        engine.close()

    def test_vmapped_parity_across_parts_and_dedup(self, tmp_path):
        """Multi-part scans are where the fold-association argument
        bites: two flushed SSTs plus a memtable tail, windows straddling
        the part seams, and duplicate (host, ts) rows engaging the LWW
        dedup mask — vmapped members must still equal serial exactly."""
        from greptimedb_tpu.query.vmapped import run_vmapped

        engine, qe = make_qe(tmp_path, maintenance_workers=1)
        create_cpu(qe)
        rng = np.random.default_rng(11)
        for gen in range(3):
            rows = []
            for h in range(3):
                for i in range(80):
                    ts = (gen * 60 + i) * 1000
                    rows.append(f"('h{h}',{rng.uniform(0, 50)!r},{ts})")
            # overlap: re-write some of the previous generation's keys
            # (same (host, ts), new value) so dedup has survivors to pick
            if gen:
                for h in range(3):
                    for i in range(0, 40, 5):
                        ts = ((gen - 1) * 60 + i) * 1000
                        rows.append(
                            f"('h{h}',{rng.uniform(50, 99)!r},{ts})")
            qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                           + ",".join(rows))
            if gen < 2:
                maint = qe.region_engine.maintenance
                for r in qe.execute_one("ADMIN flush_table('cpu')").rows():
                    maint.wait(int(r[0]), timeout=30)
        sql = ("SELECT date_bin(INTERVAL '30 seconds', ts) AS b, sum(v), "
               "min(v), count(*) FROM cpu WHERE host = 'h{h}' AND "
               "ts >= {lo} AND ts < {hi} GROUP BY b")
        sqls = [sql.format(h=i % 3, lo=(i % 4) * 30_000,
                           hi=90_000 + (i % 4) * 25_000)
                for i in range(10)]
        info, leader, shape, order, per_sql = self._analyze_group(qe, sqls)
        results = run_vmapped(qe.executor, leader, info, shape.params,
                              order)
        for sql, vals in zip(sqls, per_sql):
            got = results[order.index(vals)]
            with qe.concurrency.suppress_batching():
                want = qe.execute_one(sql)
            assert got.rows() == want.rows(), sql
        engine.close()

    def test_vmapped_threaded_through_batcher(self, tmp_path):
        """Concurrent parameter-sibling dashboards land in ONE group
        and ride the vmapped dispatch; every response equals its serial
        oracle."""
        engine, qe = make_qe(tmp_path, plane=batch_plane())
        create_cpu(qe)
        ingest(qe)
        sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               "max(v), sum(v) FROM cpu WHERE host = 'h{h}' AND "
               "ts >= {lo} AND ts < {hi} GROUP BY minute")
        sqls = [sql.format(h=i % 4, lo=(i % 2) * 30_000,
                           hi=90_000 + (i % 2) * 30_000)
                for i in range(12)]
        serial = {}
        with qe.concurrency.suppress_batching():
            for s in set(sqls):
                r = qe.execute_one(s)
                serial[s] = (r.names, r.rows())
        v0 = QUERY_BATCH_EVENTS.get(event="vmapped")
        w0 = VMAP_BATCH_WIDTH.count()
        got = run_threads([lambda s=s: qe.execute_one(s) for s in sqls])
        for s, r in zip(sqls, got):
            names, rows = serial[s]
            assert r.names == names and r.rows() == rows, s
        assert QUERY_BATCH_EVENTS.get(event="vmapped") > v0
        assert VMAP_BATCH_WIDTH.count() > w0
        engine.close()

    def test_ineligible_single_tag_falls_back_to_stacked(self, tmp_path,
                                                         monkeypatch):
        """When the vmapped path declines, a single-tag group still
        stacks via the legacy IN-list rewrite — parity preserved."""
        from greptimedb_tpu.query import vmapped as vm

        def refuse(*a, **k):
            raise vm.VmapIneligible("test forces fallback")

        monkeypatch.setattr(vm, "run_vmapped", refuse)
        engine, qe = make_qe(tmp_path, plane=batch_plane())
        create_cpu(qe)
        ingest(qe)
        sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               "max(v) FROM cpu WHERE host = 'h{h}' AND ts >= 0 AND "
               "ts < 90000 GROUP BY minute")
        sqls = [sql.format(h=i % 4) for i in range(12)]
        serial = {}
        with qe.concurrency.suppress_batching():
            for s in set(sqls):
                r = qe.execute_one(s)
                serial[s] = r.rows()
        st0 = QUERY_BATCH_EVENTS.get(event="stacked")
        got = run_threads([lambda s=s: qe.execute_one(s) for s in sqls])
        for s, r in zip(sqls, got):
            assert r.rows() == serial[s], s
        assert QUERY_BATCH_EVENTS.get(event="stacked") > st0
        engine.close()

    def test_unexpected_vmapped_failure_latches_and_degrades(
            self, tmp_path, monkeypatch):
        """A runtime dispatch failure (compile error, device OOM) must
        not poison the members — the batcher latches the vmapped path
        off and serves the group via the fallbacks, still exactly."""
        from greptimedb_tpu.query import vmapped as vm

        def boom(*a, **k):
            raise RuntimeError("XLA fell over")

        monkeypatch.setattr(vm, "run_vmapped", boom)
        engine, qe = make_qe(tmp_path, plane=batch_plane())
        create_cpu(qe)
        ingest(qe)
        sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               "sum(v) FROM cpu WHERE host = 'h{h}' AND ts >= {lo} AND "
               "ts < {hi} GROUP BY minute")
        sqls = [sql.format(h=i % 4, lo=(i % 2) * 30_000,
                           hi=90_000 + (i % 2) * 30_000)
                for i in range(10)]
        serial = {}
        with qe.concurrency.suppress_batching():
            for s in set(sqls):
                serial[s] = qe.execute_one(s).rows()
        got = run_threads([lambda s=s: qe.execute_one(s) for s in sqls])
        for s, r in zip(sqls, got):
            assert r.rows() == serial[s], s
        assert qe.concurrency.batcher._vmap_failed
        # latched: later groups never try the vmapped path again
        got = run_threads([lambda s=s: qe.execute_one(s) for s in sqls])
        for s, r in zip(sqls, got):
            assert r.rows() == serial[s], s
        engine.close()

    def test_typed_transient_failure_does_not_latch(self, tmp_path,
                                                    monkeypatch):
        """Unavailable/FaultError during a vmapped dispatch (a chaos
        seam, a region mid-failover) falls back for THIS group but must
        not disable the path for the process lifetime."""
        from greptimedb_tpu.fault import Unavailable
        from greptimedb_tpu.query import vmapped as vm

        def flaky(*a, **k):
            raise Unavailable("region mid-failover")

        monkeypatch.setattr(vm, "run_vmapped", flaky)
        engine, qe = make_qe(tmp_path, plane=batch_plane())
        create_cpu(qe)
        ingest(qe)
        sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               "sum(v) FROM cpu WHERE host = 'h{h}' AND ts >= 0 AND "
               "ts < 90000 GROUP BY minute")
        sqls = [sql.format(h=i % 4) for i in range(8)]
        serial = {}
        with qe.concurrency.suppress_batching():
            for s in set(sqls):
                serial[s] = qe.execute_one(s).rows()
        got = run_threads([lambda s=s: qe.execute_one(s) for s in sqls])
        for s, r in zip(sqls, got):
            assert r.rows() == serial[s], s
        assert not qe.concurrency.batcher._vmap_failed
        engine.close()

    def test_serial_fallback_coalesces_duplicate_values(self, tmp_path,
                                                        monkeypatch):
        """When the group self-executes (vmapped off, not IN-list
        stackable), duplicates of one parameter tuple ride ONE relay
        execution instead of each re-running the query."""
        engine, qe = make_qe(tmp_path,
                             plane=batch_plane(batch_vmap=False))
        create_cpu(qe)
        ingest(qe)
        calls = []
        orig = qe._select_table

        def counted(sel, info, ctx):
            calls.append(repr(sel))
            return orig(sel, info, ctx)

        monkeypatch.setattr(qe, "_select_table", counted)
        sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               "sum(v) FROM cpu WHERE host = 'h{h}' AND ts >= {lo} AND "
               "ts < {hi} GROUP BY minute")
        # 3 distinct (host, window) tuples x 4 duplicates each
        sqls = [sql.format(h=i % 3, lo=(i % 3) * 30_000,
                           hi=90_000 + (i % 3) * 30_000)
                for i in range(3)] * 4
        serial = {}
        with qe.concurrency.suppress_batching():
            for s in set(sqls):
                serial[s] = qe.execute_one(s).rows()
        calls.clear()
        sf0 = QUERY_BATCH_EVENTS.get(event="serial_fallback")
        got = run_threads([lambda s=s: qe.execute_one(s) for s in sqls])
        for s, r in zip(sqls, got):
            assert r.rows() == serial[s], s
        if QUERY_BATCH_EVENTS.get(event="serial_fallback") > sf0:
            # a fallback group really formed: duplicates must not have
            # multiplied the executions (one per distinct tuple, plus
            # any members that raced into their own groups)
            assert len(calls) < len(sqls)
        engine.close()

    def test_ineligible_window_union_falls_back_to_serial(self, tmp_path,
                                                          monkeypatch):
        """Window-union members with the vmapped kernel disabled can't
        use the IN-list rewrite (no single selector) — they execute
        serially inside the group, still bit-for-bit."""
        engine, qe = make_qe(tmp_path,
                             plane=batch_plane(batch_vmap=False))
        create_cpu(qe)
        ingest(qe)
        sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               "sum(v) FROM cpu WHERE host = 'h1' AND ts >= {lo} AND "
               "ts < {hi} GROUP BY minute")
        sqls = [sql.format(lo=(i % 3) * 20_000,
                           hi=60_000 + (i % 3) * 20_000)
                for i in range(9)]
        serial = {}
        with qe.concurrency.suppress_batching():
            for s in set(sqls):
                serial[s] = qe.execute_one(s).rows()
        sf0 = QUERY_BATCH_EVENTS.get(event="serial_fallback")
        got = run_threads([lambda s=s: qe.execute_one(s) for s in sqls])
        for s, r in zip(sqls, got):
            assert r.rows() == serial[s], s
        assert QUERY_BATCH_EVENTS.get(event="serial_fallback") > sf0
        engine.close()

    def test_multi_block_part_gate_refuses(self, tmp_path, monkeypatch):
        """A scan part spanning several device blocks breaks the
        fold-association parity argument — the vmapped path must refuse
        (and the batcher then serves the group another way)."""
        from greptimedb_tpu.query import physical as ph
        from greptimedb_tpu.query import vmapped as vm
        from greptimedb_tpu.sql.parser import parse_sql

        engine, qe = make_qe(tmp_path)
        create_cpu(qe)
        ingest(qe, hosts=2, points=200)
        sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               "sum(v) FROM cpu WHERE host = 'h{h}' AND ts >= 0 AND "
               "ts < 90000 GROUP BY minute")
        ctx = QueryContext()
        info = qe._table("cpu", ctx)
        sels = [parse_sql(sql.format(h=h))[0] for h in (0, 1)]
        shape = batcher_mod.analyze(sels[0], info)
        order = [batcher_mod.analyze(s, info).values for s in sels]
        monkeypatch.setattr(ph, "DEFAULT_BLOCK_ROWS", 64)
        with pytest.raises(vm.VmapIneligible):
            vm.run_vmapped(qe.executor, sels[0], info, shape.params,
                           order)
        engine.close()

    def test_analyze_widened_shapes(self, tmp_path):
        """analyze() now parameterizes multi-tag conjunctions and
        time-window comparisons; selectors feeding the projection still
        refuse."""
        from greptimedb_tpu.sql.parser import parse_sql

        engine, qe = make_qe(tmp_path)
        create_cpu(qe, two_tags=True)
        ingest(qe, hosts=2, dcs=2, points=5)
        ctx = QueryContext()
        info = qe._table("cpu", ctx)

        sh = batcher_mod.analyze(parse_sql(
            "SELECT dc, max(v) FROM cpu WHERE host = 'h0' AND "
            "dc = 'dc1' AND ts >= 0 AND ts < 5000 GROUP BY dc")[0], info)
        assert sh is not None
        # dc feeds the output relation -> not a parameter; host + both
        # window bounds are
        assert [(p.col, p.kind, p.op) for p in sh.params] == [
            ("host", "tag", "="), ("ts", "ts", ">="), ("ts", "ts", "<")]
        assert sh.values == ("h0", 0, 5000)
        # no parameters at all -> coalesce-only (shape None)
        assert batcher_mod.analyze(parse_sql(
            "SELECT dc, max(v) FROM cpu GROUP BY dc")[0], info) is None
        engine.close()


# ---- zero-GIL result-encode path --------------------------------------------


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

    def test_encode_memo_shares_materialization(self):
        from greptimedb_tpu.servers.encode import json_rows, memo_rows

        r = QueryResult(["x"], [None], [np.asarray([1.0, 2.0])])
        r.encode_memo = {}
        first = json_rows(r)
        assert json_rows(r) is first  # memoized, not rebuilt
        rows = memo_rows(r)
        assert memo_rows(r) is rows

    def test_pool_offloads_and_inline_fallback(self):
        pool = EncodePool(workers=2, queue_size=1)
        off0 = ENCODE_POOL_EVENTS.get(event="offload")
        in0 = ENCODE_POOL_EVENTS.get(event="inline")
        assert pool.run(lambda: b"x") == b"x"
        assert ENCODE_POOL_EVENTS.get(event="offload") == off0 + 1

        gate = threading.Event()
        results = []

        def slow():
            gate.wait(10)
            return b"slow"

        t = threading.Thread(target=lambda: results.append(
            pool.run(slow)))
        t.start()
        for _ in range(100):  # wait until the slow job holds the queue
            if pool._inflight >= 1:
                break
            time.sleep(0.01)
        assert pool.run(lambda: b"y") == b"y"  # inline: queue is full
        assert ENCODE_POOL_EVENTS.get(event="inline") > in0
        gate.set()
        t.join(10)
        assert results == [b"slow"]
        assert pool._inflight == 0
        pool.shutdown()

    def test_auto_mode_routes_by_measured_result_size(self):
        """ISSUE-13 satellite: process_mode="auto" escapes to the spawn
        pool only for results at/above the threshold — dashboard-sized
        rows keep the thread pool, and the on/off knobs pin it."""
        pool = EncodePool(workers=1, min_rows=0,
                          process_min_rows=1000)
        assert pool.process_mode == "auto"
        assert not pool._want_process(10)       # dashboard-sized
        assert not pool._want_process(999)
        assert pool._want_process(1000)         # measured size escapes
        assert not pool._want_process(None)     # unknown: stay thread
        off = EncodePool(workers=1, process_mode="off",
                         process_min_rows=0)
        assert not off._want_process(1 << 30)
        pinned = EncodePool(workers=1, process=True)
        assert pinned.process_mode == "on"
        assert pinned._want_process(1)

    def test_auto_mode_process_escape_round_trip(self):
        """A result over the auto threshold actually rides the spawn
        pool and returns byte-identical output; a small one offloads to
        the thread pool in the same EncodePool instance."""
        from greptimedb_tpu.servers.encode import encode_sql_payload

        r = QueryResult(["a"], [None], [np.arange(8, dtype=float)])
        want = encode_sql_payload([r], 1.0)
        pool = EncodePool(workers=1, min_rows=0, process_min_rows=4)
        po0 = ENCODE_POOL_EVENTS.get(event="offload_process")
        o0 = ENCODE_POOL_EVENTS.get(event="offload")
        try:
            got = pool.run(encode_sql_payload, [r], 1.0, cost_rows=8)
            assert got == want
            assert ENCODE_POOL_EVENTS.get(event="offload_process") \
                == po0 + 1
            small = pool.run(encode_sql_payload, [r], 1.0, cost_rows=2)
            assert small == want
            assert ENCODE_POOL_EVENTS.get(event="offload") == o0 + 1
        finally:
            pool.shutdown()

    def test_encode_process_mode_env_knob(self, monkeypatch):
        """GTPU_ENCODE_PROCESS_MODE / GTPU_ENCODE_PROCESS_MIN_ROWS A/B
        the routing without an options object."""
        from greptimedb_tpu import concurrency as conc

        monkeypatch.setenv("GTPU_ENCODE_PROCESS_MODE", "off")
        assert conc.current_config().encode_process_mode == "off"
        monkeypatch.setenv("GTPU_ENCODE_PROCESS_MODE", "on")
        monkeypatch.setenv("GTPU_ENCODE_PROCESS_MIN_ROWS", "7")
        cfg = conc.current_config()
        assert cfg.encode_process_mode == "on"
        assert cfg.encode_process_min_rows == 7

    def test_process_pool_round_trip(self, monkeypatch):
        """Spawn-mode process encoding returns the same bytes as
        inline (full GIL escape behind [concurrency]
        encode_process_pool). The worker inherits the server's
        environment — which on a chip names the accelerator — and must
        pin itself to the CPU: the chip belongs to the server."""
        from greptimedb_tpu.concurrency.encode_pool import (
            worker_jax_platforms,
        )
        from greptimedb_tpu.servers.encode import encode_sql_payload

        r = QueryResult(["a", "b"], [None, None],
                        [np.asarray([1.0, float("nan")]),
                         np.asarray(["x", "y"], dtype=object)])
        want = encode_sql_payload([r], 1.25)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        pool = EncodePool(workers=1, process=True)
        try:
            got = pool.run(encode_sql_payload, [r], 1.25)
            platforms = pool.run(worker_jax_platforms)
        finally:
            pool.shutdown()
        assert got == want
        assert platforms == "cpu"

    def test_http_50_clients_byte_identical_to_idle_serial(self, tmp_path):
        """The satellite acceptance: threaded keep-alive clients under
        the encode pool get responses byte-identical to the idle-server
        serial path (only execution_time_ms may differ)."""
        import http.client

        from greptimedb_tpu.servers.http import HttpServer

        engine, qe = make_qe(
            tmp_path,
            plane=batch_plane(window_ms=10.0, encode_min_rows=0))
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
            off0 = ENCODE_POOL_EVENTS.get(event="offload")
            serial = {s: fetch(s) for s in set(sqls)}
            got = run_threads([lambda s=s: fetch(s) for s in sqls])
            for s, body in zip(sqls, got):
                assert body == serial[s], s
            assert ENCODE_POOL_EVENTS.get(event="offload") > off0
        finally:
            srv.stop()
        engine.close()

    def test_burst_overloaded_rates_bounded_with_batching_on(self, tmp_path):
        """Burst past the admission bound with batching ON: every
        failure is the typed 503 (code 5003), never a stack trace, and
        the server keeps serving at least its configured concurrency —
        no starvation regression vs the PR 6 contract."""
        import http.client

        from greptimedb_tpu.servers.http import HttpServer

        plane = ConcurrencyPlane(ConcurrencyConfig(
            max_concurrency=2, queue_size=2, queue_timeout_s=0.5,
            batch_window_ms=5.0))
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

    def test_mysql_rows_encode_parity_and_pool(self):
        from greptimedb_tpu.servers.encode import encode_mysql_rows

        rows = [[1, "a", None], [2.5, "b", float("nan")]]
        inline = encode_mysql_rows(["x", "y", "z"], rows)
        pool = EncodePool(workers=1)
        try:
            pooled = pool.run(encode_mysql_rows, ["x", "y", "z"], rows)
        finally:
            pool.shutdown()
        assert pooled == inline
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


# ---- runtime lockdep over the new locks -------------------------------------


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
    plane = ConcurrencyPlane(ConcurrencyConfig(batch_window_ms=10.0))
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
                plane.encode.run(encode_sql_payload, r, 0.0)
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


def test_runtime_lockdep_covers_batcher_and_encode_pool():
    """GTPU_LOCKDEP=1 over the new serving path: threaded batched
    queries whose results are then serialized through the encode pool;
    the observed lock nesting (batch-window lock, encode-pool
    bookkeeping, admission, metrics) must stay acyclic."""
    res = subprocess.run(
        [sys.executable, "-c", _LOCKDEP_SCRIPT],
        capture_output=True, text=True, timeout=480, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "GTPU_LOCKDEP": "1",
             "GTPU_SLOW_QUERY_MS": "600000"})
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "LOCKDEP_EDGES=" in res.stdout


def test_lint_scope_covers_serving_modules():
    """The static lockdep/blocking checkers must include the vmapped
    leader and the encode seam (concurrency/ itself is scope-prefixed,
    which covers batcher.py and encode_pool.py)."""
    from greptimedb_tpu.lint.lockgraph import SCOPE_FILES, _in_scope

    assert "greptimedb_tpu/query/vmapped.py" in SCOPE_FILES
    assert "greptimedb_tpu/servers/encode.py" in SCOPE_FILES
    assert _in_scope("greptimedb_tpu/concurrency/encode_pool.py")
    assert _in_scope("greptimedb_tpu/concurrency/batcher.py")

"""The serving path as it stands: admission -> plan cache -> fast lane
(single flight) -> executor, the answer encoded on the request's own
thread. What the cross-query batcher's and the encode pool's suites
guarded, asked of the path that remains:

- sixteen connections sending identical statements and parameter
  siblings get, byte for byte, what an idle server answers serially;
- the single flight under error and under a mixture of shapes;
- the inline encoders at the result sizes that used to pick an executor,
  against per-value oracles;
- a fragment, a Flight reply or a config that still names something
  removed is refused with a typed error that names it.
"""

import http.client
import json
import re
import struct
import threading
import urllib.parse

import numpy as np
import pytest

from greptimedb_tpu.catalog.catalog import Catalog
from greptimedb_tpu.catalog.kv import MemoryKv
from greptimedb_tpu.query.engine import QueryEngine
from greptimedb_tpu.query.result import QueryResult
from greptimedb_tpu.servers.http import HttpServer
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine
from greptimedb_tpu.utils.metrics import FAST_LANE_EVENTS, REGISTRY

CLIENTS = 16


def make_qe(tmp_path, **engine_cfg):
    engine_cfg.setdefault("maintenance_workers", 0)
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                       **engine_cfg))
    return engine, QueryEngine(Catalog(MemoryKv()), engine)


def run_threads(fns, timeout=180):
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


_ELAPSED = re.compile(rb', "execution_time_ms": [0-9.e+-]+}$')


def fetch(port: int, sql: str) -> bytes:
    """The response's bytes, the one field that is a clock cut off."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request(
            "POST", "/v1/sql",
            body=urllib.parse.urlencode({"sql": sql}).encode(),
            headers={"Content-Type": "application/x-www-form-urlencoded"})
        resp = conn.getresponse()
        data = resp.read()
        assert resp.status == 200, data[:300]
        body, n = _ELAPSED.subn(b"}", data)
        assert n == 1, data[-80:]
        return body
    finally:
        conn.close()


# ---- concurrent siblings answer as serial does ------------------------------


def _flush(qe, table="cpu"):
    maint = qe.region_engine.maintenance
    for r in qe.execute_one(f"ADMIN flush_table('{table}')").rows():
        maint.wait(int(r[0]), timeout=60)


def _one_tag_table(qe, hosts=4, points=120, seed=7):
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
        "TIME INDEX, PRIMARY KEY(host))")
    rng = np.random.default_rng(seed)
    rows = [f"('h{h}',{rng.uniform(0.0, 100.0)!r},{i * 1000})"
            for h in range(hosts) for i in range(points)]
    qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES " + ",".join(rows))


DASH = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, max(v), "
        "sum(v), avg(v) FROM cpu WHERE host = 'h{h}' AND ts >= {lo} AND "
        "ts < {hi} GROUP BY minute")


def _identical(tmp_path, monkeypatch):
    engine, qe = make_qe(tmp_path)
    _one_tag_table(qe)
    return engine, qe, [DASH.format(h=1, lo=0, hi=120_000)], None


def _one_tag(tmp_path, monkeypatch):
    engine, qe = make_qe(tmp_path)
    _one_tag_table(qe)
    return engine, qe, [DASH.format(h=i % 4, lo=0, hi=120_000)
                        for i in range(8)], None


def _multi_tag(tmp_path, monkeypatch):
    """Two tag selectors and the window differ, one member names a tag
    value that is not there."""
    engine, qe = make_qe(tmp_path)
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, dc STRING, v DOUBLE, "
        "ts TIMESTAMP(3) TIME INDEX, PRIMARY KEY(host, dc))")
    rng = np.random.default_rng(7)
    rows = [f"('h{h}','dc{d}',{rng.uniform(0.0, 100.0)!r},{i * 1000})"
            for h in range(4) for d in range(2) for i in range(120)]
    qe.execute_one("INSERT INTO cpu (host, dc, v, ts) VALUES "
                   + ",".join(rows))
    sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, max(v), "
           "sum(v), avg(v) FROM cpu WHERE host = '{h}' AND dc = '{d}' "
           "AND ts >= {lo} AND ts < {hi} GROUP BY minute")
    sqls = [sql.format(h=f"h{i % 4}", d=f"dc{i % 2}", lo=(i % 3) * 20_000,
                       hi=60_000 + (i % 3) * 20_000) for i in range(8)]
    sqls.append(sql.format(h="absent", d="dc0", lo=0, hi=60_000))
    return engine, qe, sqls, None


def _three_parts(qe, overlap: bool):
    """Two flushed SSTs and a memtable tail; with `overlap`, each
    generation rewrites keys of the one before it (same (host, ts), a new
    value), so last-write-wins has survivors to pick across parts."""
    rng = np.random.default_rng(11)
    for gen in range(3):
        rows = [f"('h{h}',{rng.uniform(0, 50)!r},{(gen * 60 + i) * 1000})"
                for h in range(3) for i in range(80)]
        if overlap and gen:
            rows += [f"('h{h}',{rng.uniform(50, 99)!r},"
                     f"{((gen - 1) * 60 + i) * 1000})"
                     for h in range(3) for i in range(0, 40, 5)]
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       + ",".join(rows))
        if gen < 2:
            _flush(qe)


PARTS_SQL = ("SELECT date_bin(INTERVAL '30 seconds', ts) AS b, sum(v), "
             "min(v), count(*) FROM cpu WHERE host = 'h{h}' AND "
             "ts >= {lo} AND ts < {hi} GROUP BY b")


def _windows_across_parts(tmp_path, monkeypatch):
    engine, qe = make_qe(tmp_path, maintenance_workers=1)
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
        "TIME INDEX, PRIMARY KEY(host)) WITH (append_mode = 'true')")
    _three_parts(qe, overlap=False)
    return engine, qe, [PARTS_SQL.format(h=i % 3, lo=(i % 4) * 30_000,
                                         hi=90_000 + (i % 4) * 25_000)
                        for i in range(10)], None


def _lww_duplicates(tmp_path, monkeypatch):
    engine, qe = make_qe(tmp_path, maintenance_workers=1)
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
        "TIME INDEX, PRIMARY KEY(host))")
    _three_parts(qe, overlap=True)
    return engine, qe, [PARTS_SQL.format(h=i % 3, lo=(i % 4) * 30_000,
                                         hi=90_000 + (i % 4) * 25_000)
                        for i in range(10)], None


def _first_last(tmp_path, monkeypatch):
    engine, qe = make_qe(tmp_path, maintenance_workers=1)
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) NOT "
        "NULL, TIME INDEX (ts), PRIMARY KEY(host))")
    rng = np.random.default_rng(9)
    for gen in range(2):
        rows = [f"('host{h}', {int(rng.integers(0, 100))}, "
                f"{(gen * 50 + i) * 1000})"
                for h in range(4) for i in range(50)]
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       + ",".join(rows))
        _flush(qe)
    qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                   "('host0', 777, 200000)")
    sql = ("SELECT date_bin(INTERVAL '30 seconds', ts) AS b, first(v), "
           "last(v) FROM cpu WHERE host = '{h}' AND ts >= {lo} AND "
           "ts < {hi} GROUP BY b")
    return engine, qe, [sql.format(h=f"host{i % 4}", lo=(i % 2) * 20_000,
                                   hi=150_000 + (i % 2) * 60_000)
                        for i in range(6)], None


def _sparse(seconds):
    def build(tmp_path, monkeypatch):
        """One group a second: the sparse sort-compact path on either
        side of the fused kernel's 4,096-segment seam."""
        monkeypatch.setenv("GREPTIMEDB_TPU_SPARSE_GROUPS_MIN", "1")
        engine, qe = make_qe(tmp_path)
        qe.execute_one(
            "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
            "TIME INDEX, PRIMARY KEY(host))")
        rows = [f"('h{h}', {float((i * 11 + h) % 97)!r}, {i * 1000})"
                for h in range(2) for i in range(seconds)]
        qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                       + ",".join(rows))
        sql = ("SELECT date_bin(INTERVAL '1 second', ts) AS sec, sum(v), "
               "count(v), min(v), max(v) FROM cpu WHERE host = '{h}' AND "
               "ts >= {lo} AND ts < {hi} GROUP BY sec")
        return engine, qe, [sql.format(h=f"h{i % 2}", lo=(i % 3) * 1000,
                                       hi=seconds * 1000)
                            for i in range(4)], "sparse"
    return build


def _two_regions(tmp_path, monkeypatch):
    """A table range-partitioned over a cluster's datanodes: every
    member is a fragment a region, combined on the frontend."""
    from greptimedb_tpu.cluster import Cluster
    from greptimedb_tpu.meta.metasrv import MetasrvOptions
    from greptimedb_tpu.partition.rule import (
        PartitionBound,
        RangePartitionRule,
    )

    c = Cluster(str(tmp_path), num_datanodes=2, opts=MetasrvOptions(),
                wire_transport=False)
    c.create_partitioned_table(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) NOT "
        "NULL, TIME INDEX (ts), PRIMARY KEY(host))",
        RangePartitionRule(["host"], [PartitionBound(("host3",)),
                                      PartitionBound(())]))
    rng = np.random.default_rng(3)
    rows = [f"('host{h}', {int(rng.integers(0, 1000))}, "
            f"{m * 60_000 + i * 3000})"
            for h in range(6) for m in range(3) for i in range(20)]
    c.sql("INSERT INTO cpu (host, v, ts) VALUES " + ", ".join(rows))
    assert len(c.frontend.catalog.table("public", "cpu").region_ids) == 2
    sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, max(v), "
           "sum(v), count(v) FROM cpu WHERE host = '{h}' AND "
           "ts >= {lo} AND ts < {hi} GROUP BY minute")
    return c, c.frontend, [sql.format(h=f"host{i % 6}",
                                      lo=(i % 2) * 30_000,
                                      hi=90_000 + (i % 2) * 30_000)
                           for i in range(8)], "pushdown"


SHAPES = {
    "identical": _identical,
    "one_tag_siblings": _one_tag,
    "multi_tag_siblings": _multi_tag,
    "window_siblings_across_parts": _windows_across_parts,
    "first_last_siblings": _first_last,
    "lww_duplicates_across_parts": _lww_duplicates,
    "sparse_4095_groups": _sparse(4095),
    "sparse_4097_groups": _sparse(4097),
    "two_region_table": _two_regions,
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_concurrent_siblings_answer_as_serial_does(shape, tmp_path,
                                                   monkeypatch):
    """Sixteen connections at once — first while the server has seen
    none of the statements, then again once it has served them all —
    and every response is, byte for byte, the idle server's serial
    answer to the same statement."""
    closer, qe, sqls, path = SHAPES[shape](tmp_path, monkeypatch)
    srv = HttpServer(qe, port=0)
    try:
        port = srv.start()
        asked = [sqls[i % len(sqls)] for i in range(CLIENTS)]
        cold = run_threads([lambda s=s: fetch(port, s) for s in asked])
        serial = {}
        for s in sqls:
            serial[s] = fetch(port, s)
            if path is not None:  # the executor names it a thread
                qe.execute_one(s)
                assert qe.executor.last_path == path, (s, path)
        for s in sqls:  # and the serial answer is one answer
            assert fetch(port, s) == serial[s], s
            assert json.loads(serial[s])["output"][0]["records"]["rows"] \
                or "absent" in s, s
        warm = run_threads([lambda s=s: fetch(port, s) for s in asked])
        for s, a, b in zip(asked, cold, warm):
            assert a == serial[s], s
            assert b == serial[s], s
    finally:
        srv.stop()
        closer.close()


# ---- the single flight under error and mixture ------------------------------


FLIGHT_SQL = ("SELECT host, max(v) FROM cpu WHERE ts >= {lo} "
              "GROUP BY host ORDER BY host")


@pytest.fixture
def lane_db(tmp_path):
    engine, qe = make_qe(tmp_path)
    _one_tag_table(qe, hosts=3, points=20)
    for lo in (0, 1000, 2000):  # the template is built at its second sighting
        qe.execute_one(FLIGHT_SQL.format(lo=lo))
    assert len(qe.concurrency.fast_lane) == 1
    yield qe
    engine.close()


class _Held:
    """The executor's `execute`, the first call held (after `before`
    ran) until released: the flight it leads stays open."""

    def __init__(self, qe, before=None):
        self.real, self.before = qe.executor.execute, before
        self.started, self.release = threading.Event(), threading.Event()
        self.calls = 0
        self._lock = threading.Lock()
        qe.executor.execute = self

    def __call__(self, plan):
        with self._lock:
            self.calls += 1
            first = self.calls == 1
        if first:
            self.started.set()
            assert self.release.wait(60)
            if self.before is not None:
                self.before()
        return self.real(plan)


class _CountingEvent(threading.Event):
    """An event that remembers which threads have waited on it."""

    def __init__(self):
        super().__init__()
        self.waiters = set()

    def wait(self, timeout=None):
        self.waiters.add(threading.get_ident())
        return super().wait(timeout)


def _watch_flight(qe) -> _CountingEvent:
    """Swap the one open flight's event for a counting one: call while
    its leader is held and before any follower starts."""
    lane = qe.concurrency.fast_lane
    with lane._flight_lock:
        (flight,) = lane._flights.values()
        flight.event = _CountingEvent()
    return flight.event


def _joined(event: _CountingEvent, n: int) -> None:
    """Wait until `n` followers are parked on the flight."""
    for _ in range(12000):
        if len(event.waiters) >= n:
            return
        threading.Event().wait(0.005)
    raise AssertionError(f"{n} followers never joined the flight")


def _coalesced() -> float:
    return FAST_LANE_EVENTS.get(event="coalesced")


def test_a_leaders_error_reaches_every_follower_and_is_not_kept(lane_db):
    qe = lane_db

    def boom():
        raise RuntimeError("device fell over")

    held = _Held(qe, before=boom)
    sql = FLIGHT_SQL.format(lo=3000)
    errors, c0 = [], _coalesced()

    def ask():
        try:
            qe.execute_one(sql)
        except RuntimeError as e:
            errors.append(str(e))

    threads = [threading.Thread(target=ask) for _ in range(5)]
    threads[0].start()
    assert held.started.wait(60)
    parked = _watch_flight(qe)
    for t in threads[1:]:
        t.start()
    _joined(parked, 4)
    held.release.set()
    for t in threads:
        t.join(60)
    assert errors == ["device fell over"] * 5
    assert held.calls == 1  # one execution failed for all five
    assert _coalesced() == c0 + 4
    # the flight is gone with its error: a later request executes afresh
    assert not qe.concurrency.fast_lane._flights
    rows = qe.execute_one(sql).rows()
    assert held.calls == 2
    assert [r[0] for r in rows] == ["h0", "h1", "h2"]


def test_shapes_in_flight_together_never_share_a_result(lane_db):
    """Two parameter siblings of one template and a statement of another
    shape, all in flight while a leader is held: each gets its own
    answer; only the held statement's twins join its flight."""
    qe = lane_db
    other = ("SELECT host, min(v), count(v) FROM cpu WHERE ts >= {lo} "
             "GROUP BY host ORDER BY host")
    for lo in (0, 1000, 2000):
        qe.execute_one(other.format(lo=lo))
    asked = [FLIGHT_SQL.format(lo=4000), FLIGHT_SQL.format(lo=4000),
             FLIGHT_SQL.format(lo=9000), other.format(lo=4000)]
    serial = [(r.names, r.rows()) for r in map(qe.execute_one, asked)]
    assert len({json.dumps(s) for s in serial}) == 3
    held = _Held(qe)
    out, c0 = [None] * len(asked), _coalesced()

    def ask(i):
        r = qe.execute_one(asked[i])
        out[i] = (r.names, r.rows())

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(asked))]
    threads[0].start()
    assert held.started.wait(60)
    parked = _watch_flight(qe)
    for t in threads[1:]:
        t.start()
    _joined(parked, 1)
    # the sibling and the other shape were not held up by the flight
    threads[2].join(60)
    threads[3].join(60)
    assert out[2] == serial[2] and out[3] == serial[3]
    held.release.set()
    threads[0].join(60)
    threads[1].join(60)
    assert out == serial
    assert held.calls == 3  # the twin rode its leader's execution
    assert _coalesced() == c0 + 1


def test_coalesced_moves_and_no_query_batch_family_is_exposed(lane_db):
    qe = lane_db
    held = _Held(qe)
    sql = FLIGHT_SQL.format(lo=5000)
    c0 = _coalesced()
    threads = [threading.Thread(target=qe.execute_one, args=(sql,))
               for _ in range(3)]
    threads[0].start()
    assert held.started.wait(60)
    parked = _watch_flight(qe)
    for t in threads[1:]:
        t.start()
    _joined(parked, 2)
    held.release.set()
    for t in threads:
        t.join(60)
    assert _coalesced() == c0 + 2
    text = REGISTRY.render()
    assert 'greptimedb_tpu_fast_lane_events_total{event="coalesced"}' \
        in text
    for family in ("query_batch", "query_vmap_batch_width", "encode_pool"):
        assert f"greptimedb_tpu_{family}" not in text, family


# ---- the inline encoders at the sizes that used to pick an executor ---------
# 0 and 1 row; 255 / 256 on either side of the old `encode_min_rows`;
# 100,000, the old `encode_process_min_rows`.

SIZES = (0, 1, 255, 256, 100_000)


def _result(n: int) -> QueryResult:
    """Four columns of the classes the writers know, every value spelled
    by `repr` as JSON spells it, NULLs and a NaN among them."""
    from greptimedb_tpu.datatypes.types import DataType

    i = np.arange(n, dtype=np.int64)
    f = i / 4.0 - 1.5
    if n:
        f[::7] = np.nan
    s = np.asarray([None if k % 5 == 3 else f"h{k % 13}" for k in range(n)],
                   dtype=object)
    return QueryResult(
        ["ts", "v", "host", "up"],
        [DataType.TIMESTAMP_MILLISECOND, DataType.FLOAT64, DataType.STRING,
         DataType.BOOL],
        [i * 1000, f, s, (i % 2).astype(bool)])


def _lenc(b: bytes) -> bytes:
    n = len(b)
    if n < 251:
        return bytes([n]) + b
    if n < 1 << 16:
        return b"\xfc" + struct.pack("<H", n) + b
    return b"\xfd" + struct.pack("<I", n)[:3] + b


def _spell(v) -> bytes:
    if isinstance(v, (bool, np.bool_)):
        return b"1" if v else b"0"
    if isinstance(v, (float, np.floating)):
        return repr(float(v)).encode()
    return str(v).encode()


def _null(v) -> bool:
    return v is None or (isinstance(v, float) and v != v)


def _mysql_row_oracle(row, binary: bool) -> bytes:
    if not binary:
        return b"".join(b"\xfb" if _null(v) else _lenc(_spell(v))
                        for v in row)
    bitmap = bytearray((len(row) + 9) // 8)
    for k, v in enumerate(row):
        if _null(v):
            bitmap[(k + 2) // 8] |= 1 << ((k + 2) % 8)
    return b"\x00" + bytes(bitmap) + b"".join(
        _lenc(_spell(v)) for v in row if not _null(v))


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("protocol", ["http_json", "mysql_text",
                                      "mysql_binary"])
def test_inline_encoder_matches_the_per_value_oracle(protocol, n):
    from greptimedb_tpu.servers.encode import (
        encode_mysql_result,
        encode_sql_payload,
        schema_header_json,
    )
    from tests.test_vmap_serving import _legacy_json_rows

    r = _result(n)
    if protocol == "http_json":
        body = encode_sql_payload([r], 1.25)
        want = json.dumps({"code": 0, "output": [{"records": {
            "schema": json.loads(schema_header_json(r.names, r.dtypes)),
            "rows": _legacy_json_rows(r), "total_rows": n}}],
            "execution_time_ms": 1.25}).encode()
        assert body == want
        return
    binary = protocol == "mysql_binary"
    packets = encode_mysql_result(r, binary)
    # column count, a definition a column, EOF; the rows; EOF
    head, rows, tail = packets[:6], packets[6:-1], packets[-1]
    assert head[0] == b"\x04" and head[5] == tail and tail[:1] == b"\xfe"
    assert len(rows) == n
    for got, row in zip(rows, r.rows()):
        assert got == _mysql_row_oracle(row, binary)


# ---- what comes from outside is refused typed --------------------------------


VMAPPED_STAGE = {"op": "vmapped_agg", "keys": [], "args": [], "ops": [],
                 "shared_where": None, "params": [], "values": []}


@pytest.mark.parametrize("direction", ["decode", "encode"])
def test_a_fragment_naming_the_vmapped_stage_is_an_unknown_stage(direction):
    """An older peer may still send the stage: typed, never a KeyError."""
    from greptimedb_tpu.query import plan_ser

    with pytest.raises(ValueError,
                       match="unknown fragment stage 'vmapped_agg'"):
        if direction == "decode":
            plan_ser.PlanFragment.from_json(json.dumps(
                {"stages": [VMAPPED_STAGE], "ts_range": None,
                 "append_mode": False, "tz": None}))
        else:
            plan_ser.PlanFragment([dict(VMAPPED_STAGE)]).to_json()


def test_a_flight_reply_of_kind_vmapped_is_a_typed_error():
    import pyarrow as pa

    from greptimedb_tpu.query.plan_ser import PlanFragment
    from greptimedb_tpu.servers.flight import RemoteRegionEngine

    reply = pa.Table.from_arrays([], schema=pa.schema(
        [], metadata={b"kind": b"vmapped", b"payload": b"{}"}))

    class _Peer:
        def do_get(self, ticket, options):
            class _Stream:
                @staticmethod
                def read_all():
                    return reply
            return _Stream()

        def close(self):
            pass

    remote = RemoteRegionEngine("127.0.0.1:1")
    remote.client.close()
    remote.client = _Peer()
    frag = PlanFragment([{"op": "limit", "k": 1}])
    with pytest.raises(ValueError,
                       match="unknown fragment reply kind 'vmapped'"):
        remote.execute_fragment(1, frag)


REMOVED_KEYS = {
    "batching": "true", "batch_window_ms": "2.0", "batch_max_queries": "64",
    "batch_max_rows": "4194304", "batch_vmap": "true",
    "encode_offload": "true", "encode_workers": "0", "encode_queue": "64",
    "encode_min_rows": "256", "encode_process_pool": "false",
    "encode_process_mode": '"auto"', "encode_process_min_rows": "100000",
}


@pytest.mark.parametrize("key", sorted(REMOVED_KEYS))
def test_a_config_with_a_removed_key_is_refused_naming_it(key, tmp_path):
    from greptimedb_tpu.options import ConfigError, load_options

    path = tmp_path / "standalone.toml"
    path.write_text(f"[concurrency]\nmax_concurrency = 4\n"
                    f"{key} = {REMOVED_KEYS[key]}\n")
    with pytest.raises(ConfigError,
                       match=f"unknown option 'concurrency.{key}'"):
        load_options(str(path), env={})
    # and as an environment override
    with pytest.raises(ConfigError, match=f"concurrency.{key}"):
        load_options(None, env={
            f"GREPTIMEDB_TPU__CONCURRENCY__{key.upper()}": "1"})

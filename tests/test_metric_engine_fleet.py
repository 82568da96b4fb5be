"""The metric engine against a plain reference of its semantics, at a
fleet's shape in small: 3 node_exporters, 6 metric names, 20 points.

The storage reference is a dict {(table, frozenset(labels)): {ts:
(seq, op, value)}} with last-write-wins and tombstones; the PromQL
reference is `promql_board`'s numpy template, reached through the
`prom_fleet` family as the benchmark reaches it (ISSUE 27).
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.common import load_module  # noqa: E402
from greptimedb_tpu.catalog.catalog import Catalog  # noqa: E402
from greptimedb_tpu.catalog.kv import FileKv, MemoryKv  # noqa: E402
from greptimedb_tpu.datatypes import DictVector, RecordBatch  # noqa: E402
from greptimedb_tpu.promql import engine as promql_engine  # noqa: E402
from greptimedb_tpu.query.engine import QueryEngine  # noqa: E402
from greptimedb_tpu.servers.http import _matrix_body  # noqa: E402
from greptimedb_tpu.servers.prom_store import handle_remote_write  # noqa: E402
from greptimedb_tpu.storage import metric_engine as me  # noqa: E402
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine  # noqa: E402
from greptimedb_tpu.storage.region import OP_DELETE, OP_PUT  # noqa: E402
from greptimedb_tpu.utils.metrics import (  # noqa: E402
    METRIC_ENGINE_LABEL_SETS_PARSED,
    METRIC_ENGINE_ROWS,
)
from test_prom_store import make_write_request  # noqa: E402

SCALE = {"instances": 3, "minutes": 5, "step_s": 15}
NAMES = ["node_cpu_seconds_total", "node_filesystem_avail_bytes",
         "node_network_receive_bytes_total", "node_load1",
         "node_disk_io_now", "node_uname_info"]


@pytest.fixture
def qe(tmp_path):
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path)))
    q = QueryEngine(Catalog(MemoryKv()), engine)
    yield q
    engine.close()


@pytest.fixture(scope="module")
def fleet():
    ds = load_module("datasets", "prom_node_fleet").Dataset(11, SCALE)
    assert ds.points == 20
    return ds


def batch_of(qe, view, p0=0, p1=None, values=None):
    """Rows [p0, p1) of a view as the loader's RecordBatch."""
    info = qe.catalog.table("public", view.table)
    p1 = view.points if p1 is None else p1
    cols = {}
    for k, v in view.series_tags().items():
        cols[k] = DictVector.encode(list(v) * (p1 - p0))
    cols[info.schema.time_index.name] = np.repeat(
        view.t0_ms + np.arange(p0, p1, dtype=np.int64) * view.step_ms,
        view.series)
    mat = view.fields["greptime_value"] if values is None else values
    cols["greptime_value"] = np.asarray(mat[p0:p1]).reshape(-1)
    return info, RecordBatch(info.schema, cols)


def load(qe, fleet, names=NAMES):
    acked = {}
    for name in names:
        view = fleet.view(name)
        qe.execute_one(view.create_sql())
        info, batch = batch_of(qe, view)
        acked[name] = qe._sharded_write(info, batch, delete=False)
    return acked


def physical(qe, table):
    region = qe.region_engine.region(
        qe.catalog.table("public", table).region_ids[0])
    return region, qe.region_engine.region(region.meta.physical_region)


class Reference:
    """The metric engine's semantics in a dict."""

    def __init__(self):
        self.rows: dict = {}
        self.seq = 0

    def write(self, table, labels, ts, value, op=OP_PUT):
        key = (table, frozenset((k, v) for k, v in labels.items()
                                if v is not None))
        self.rows.setdefault(key, {})[ts] = (self.seq, op, value)
        self.seq += 1

    def table(self, name):
        """{(labels, ts): value} of the live samples of a table."""
        return {(key[1], ts): v
                for key, by_ts in self.rows.items() if key[0] == name
                for ts, (_seq, op, v) in by_ts.items() if op == OP_PUT}


def read_table(qe, view):
    tags = ["instance"] + list(view.label_names)
    res = qe.execute_one(
        f"SELECT {', '.join(tags)}, ts, greptime_value "
        f"FROM {view.table}")
    return {(frozenset((t, v) for t, v in zip(tags, row[:len(tags)])
                       if v is not None), row[-2]): row[-1]
            for row in res.rows()}


def reference_of(fleet, names=NAMES):
    ref = Reference()
    for name in names:
        view = fleet.view(name)
        tags = view.series_tags()
        mat = view.fields["greptime_value"]
        for p in range(view.points):
            for s in range(view.series):
                ref.write(name, {k: v[s] for k, v in tags.items()},
                          view.t0_ms + p * view.step_ms, float(mat[p, s]))
    return ref


def test_columnar_write_stores_what_the_per_row_path_did(qe, fleet):
    acked = load(qe, fleet)
    for name in NAMES:
        view = fleet.view(name)
        assert acked[name] == view.rows
        tags = view.series_tags()
        # the per-row path: encode_labels of every row's tag dict
        want = sorted({me.encode_labels({k: v[s] for k, v in tags.items()})
                       for s in range(view.series)})
        _, phys = physical(qe, name)
        scan = phys.scan(None, None, {me.TABLE_COL: {name}})
        codes = np.unique(scan.columns[me.LABELS_COL])
        assert sorted(scan.tag_dicts[me.LABELS_COL][codes]) == want
        assert scan.num_rows == view.rows
        assert read_table(qe, view) == reference_of(fleet, [name]).table(name)
    # one __table dictionary value a table, on one physical region
    _, phys = physical(qe, NAMES[0])
    assert sorted(phys.registry.values[me.TABLE_COL]) == sorted(NAMES)
    assert {physical(qe, n)[1].region_id for n in NAMES} == {phys.region_id}


def test_label_column_handles_nulls_plain_arrays_and_wide_keys(qe):
    qe.execute_one("CREATE TABLE m (a STRING, b STRING, c STRING, v DOUBLE, "
                   "ts TIMESTAMP TIME INDEX, PRIMARY KEY (a, b, c)) "
                   "ENGINE=metric")
    info = qe.catalog.table("public", "m")
    region = qe.region_engine.region(info.region_ids[0])
    a = np.asarray(["x", None, "x", "y=1", None], dtype=object)
    b = DictVector.encode(["1", "1", None, "2", None])
    batch = RecordBatch(info.schema, {
        "a": a, "b": b, "c": DictVector.encode([None] * 5),
        "ts": np.arange(5, dtype=np.int64), "v": np.arange(5.0)})
    got = region._label_column(batch, 5).decode().tolist()
    want = [me.encode_labels({"a": a[i], "b": b.decode()[i], "c": None})
            for i in range(5)]
    assert got == want and got[4] == ""
    assert me.decode_labels(got[3]) == {"a": "y=1", "b": "2"}


def test_a_rewritten_sample_wins_by_seq_and_a_delete_hides_it(qe, fleet):
    load(qe, fleet)
    ref = reference_of(fleet)
    view = fleet.view("node_load1")
    # resend points 3..5 with other values, then delete point 4
    again = view.fields["greptime_value"] + 1000.0
    info, batch = batch_of(qe, view, 3, 6, values=again)
    qe._sharded_write(info, batch, delete=False)
    tags = view.series_tags()
    for p in range(3, 6):
        for s in range(view.series):
            ref.write(view.table, {k: v[s] for k, v in tags.items()},
                      view.t0_ms + p * view.step_ms, float(again[p, s]))
    info, gone = batch_of(qe, view, 4, 5)
    qe._sharded_write(info, gone, delete=True)
    for s in range(view.series):
        ref.write(view.table, {k: v[s] for k, v in tags.items()},
                  view.t0_ms + 4 * view.step_ms, None, OP_DELETE)
    assert read_table(qe, view) == ref.table(view.table)
    assert len(ref.table(view.table)) == view.rows - view.series
    # the same answer from SSTs
    physical(qe, view.table)[0].flush()
    assert read_table(qe, view) == ref.table(view.table)
    # and through PromQL: the deleted instant is absent, the resent win
    t0 = view.t0_ms // 1000
    times, sm = promql_engine.PromqlEngine(qe).eval_matrix(
        "node_load1", t0 + 45, t0 + 75, 15)
    vals = promql_engine.d2h(sm.values)
    by_inst = {lab["instance"]: vals[i] for i, lab in enumerate(sm.labels)}
    for s, inst in enumerate(tags["instance"]):
        # staleness: the step at the deleted instant sees point 3
        np.testing.assert_array_equal(
            by_inst[inst], [again[3, s], again[3, s], again[5, s]])


PANELS = [
    {"metric": "node_cpu_seconds_total", "fn": "rate", "window_s": 60,
     "agg": "sum", "by": "instance", "match": {"mode": "system"},
     "step_s": 15, "range_s": 60},
    {"metric": "node_cpu_seconds_total", "fn": "rate", "window_s": 60,
     "agg": "sum", "by": "mode", "match": {}, "step_s": 15, "range_s": 60},
    {"metric": "node_filesystem_avail_bytes", "fn": "avg_over_time",
     "window_s": 60, "agg": "avg", "by": "instance", "match": {},
     "step_s": 15, "range_s": 60},
]


def ask(qe, template, params, fleet):
    """The panel's request, evaluated as the HTTP handler does."""
    from urllib.parse import parse_qs, urlparse

    _method, path, _body = template.request(params, fleet)
    q = {k: v[0] for k, v in parse_qs(urlparse(path).query).items()}
    times, sm = promql_engine.PromqlEngine(qe).eval_matrix(
        q["query"], float(q["start"]), float(q["end"]), float(q["step"]))
    return json.loads(_matrix_body(times, sm))["data"]["result"]


@pytest.mark.parametrize("panel", PANELS, ids=lambda p: p["agg"] + "-by-"
                         + p["by"] + "-" + p["fn"])
def test_promql_on_a_logical_table_equals_the_numpy_reference(
        qe, fleet, panel, monkeypatch):
    load(qe, fleet)
    template = load_module("templates", "prom_fleet").make("range", panel)
    calls = []
    real = promql_engine._promql_dedup
    monkeypatch.setattr(promql_engine, "_promql_dedup",
                        lambda *a: calls.append(1) or real(*a))
    rng = np.random.default_rng(5)
    draws = [template.draw(rng, fleet) for _ in range(3)] \
        + template.edges(fleet)
    # memtable rows: whatever order they come in, the answer holds
    for p in draws:
        assert template.compare(ask(qe, template, p, fleet), p, fleet,
                                "float64") <= template.limit("float64")
    # flushed: one sorted SST, nothing for last-write-wins to decide
    physical(qe, panel["metric"])[0].flush()
    calls.clear()
    settled = [ask(qe, template, p, fleet) for p in draws]
    assert not calls
    for p, answer in zip(draws, settled):
        assert template.compare(answer, p, fleet, "float64") \
            <= template.limit("float64")
    # the float32 control fails the limit
    assert template.compare(None, draws[0], fleet, "float64",
                            lowered=True) > 3 * template.limit("float64")
    # a resent point (same values): the dedup sort runs, same answers
    view = fleet.view(panel["metric"])
    info, batch = batch_of(qe, view, 7, 9)
    qe._sharded_write(info, batch, delete=False)
    sorted_answers = [ask(qe, template, p, fleet) for p in draws]
    assert calls
    for p, a, b in zip(draws, sorted_answers, settled):
        assert template.compare(a, p, fleet, "float64") \
            <= template.limit("float64")
        # the same series and steps; the values to the last digits (a
        # sorted load may take another summation path)
        assert [(s["metric"], [t for t, _ in s["values"]]) for s in a] \
            == [(s["metric"], [t for t, _ in s["values"]]) for s in b]
        va = np.asarray([[float(v) for _, v in s["values"]] for s in a])
        vb = np.asarray([[float(v) for _, v in s["values"]] for s in b])
        np.testing.assert_allclose(va, vb, rtol=1e-12, atol=0)


def test_a_scan_parses_only_label_sets_it_has_not_seen(qe, fleet):
    load(qe, fleet, ["node_cpu_seconds_total", "node_load1"])
    cpu = fleet.view("node_cpu_seconds_total")
    _, phys = physical(qe, cpu.table)
    n0 = METRIC_ENGINE_LABEL_SETS_PARSED.get()
    assert qe.execute_one(f"SELECT count(*) FROM {cpu.table}").rows() \
        == [[cpu.rows]]
    first = METRIC_ENGINE_LABEL_SETS_PARSED.get() - n0
    assert first == phys.registry.cardinality(me.LABELS_COL) \
        == cpu.series + fleet.view("node_load1").series
    # steady: count(*) and a matcher scan parse nothing
    qe.execute_one(f"SELECT count(*) FROM {cpu.table}")
    qe.execute_one(f"SELECT count(*) FROM {cpu.table} WHERE mode = 'system'")
    assert METRIC_ENGINE_LABEL_SETS_PARSED.get() - n0 == first
    # table B's series grow the dictionary: A's next scan parses those
    fs = fleet.view("node_filesystem_avail_bytes")
    load(qe, fleet, [fs.table])
    assert qe.execute_one(f"SELECT count(*) FROM {cpu.table}").rows() \
        == [[cpu.rows]]
    assert METRIC_ENGINE_LABEL_SETS_PARSED.get() - n0 == first + fs.series
    # a matcher on a virtual tag is pushed down as __labels codes: the
    # logical scan returns the matching series' rows alone
    r0 = METRIC_ENGINE_ROWS.get(kind="logical_returned")
    region, _ = physical(qe, cpu.table)
    from greptimedb_tpu.storage.index import InSet, Regex

    scan = region.scan(tag_predicates={"mode": (InSet.of(["system"]),)})
    assert scan.num_rows == cpu.rows // 8
    assert METRIC_ENGINE_ROWS.get(kind="logical_returned") - r0 \
        == cpu.rows // 8
    scan = region.scan(tag_predicates={"mode": (Regex("s.*"),),
                                       "cpu": (InSet.of(["0", "1"]),)})
    assert scan.num_rows == cpu.rows // 8 * 3 // 5  # softirq, steal, system
    assert region.scan(tag_predicates={"mode": (InSet.of(["none"]),)}) is None
    # label values of ONE table, off the catalog
    assert sorted(region.registry.values["mode"]) == sorted(
        set(cpu.series_tags()["mode"]))
    assert qe.region_engine.region(
        qe.catalog.table("public", fs.table).region_ids[0]
    ).registry.values["fstype"] == ["ext4", "xfs", "tmpfs"]


def test_remote_write_creates_logical_tables_that_survive_a_reopen(tmp_path):
    def open_db():
        engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data")))
        return engine, QueryEngine(
            Catalog(FileKv(str(tmp_path / "catalog.json"))), engine)

    engine, db = open_db()
    series = []
    for i in range(3):
        for mode in ("idle", "system"):
            series.append(({"__name__": "node_cpu_seconds_total",
                            "instance": f"n{i}", "mode": mode},
                           [(float(10 * i + t), 1000 * t)
                            for t in range(4)]))
        series.append(({"__name__": "node_load1", "instance": f"n{i}"},
                       [(0.5 + i, 1000 * t) for t in range(4)]))
    try:
        assert handle_remote_write(db, make_write_request(series)) == 36
        for name in ("node_cpu_seconds_total", "node_load1"):
            info = db.catalog.table("public", name)
            assert info.options.get("engine") == "metric"
            assert not info.append_mode
        assert db.metric_engine.list_logical_tables("public") \
            == ["node_cpu_seconds_total", "node_load1"]
        phys = [r for rid, r in db.region_engine.regions.items()
                if not hasattr(r, "meta")]
        assert len(phys) == 1
        # a resent sample wins; the table stays a logical table
        assert handle_remote_write(db, make_write_request(
            [({"__name__": "node_load1", "instance": "n0"},
              [(9.0, 0)])])) == 1
    finally:
        engine.close()
    engine, db = open_db()
    try:
        assert db.execute_one(
            "SELECT count(*) FROM node_cpu_seconds_total").rows() == [[24]]
        assert db.execute_one(
            "SELECT count(*) FROM node_load1").rows() == [[12]]
        assert db.execute_one(
            "SELECT greptime_value FROM node_load1 WHERE instance = 'n0' "
            "AND greptime_timestamp = 0").rows() == [[9.0]]
        assert db.execute_one(
            "SELECT instance, greptime_value FROM node_cpu_seconds_total "
            "WHERE mode = 'system' AND greptime_timestamp = 3000 "
            "ORDER BY instance").rows() \
            == [["n0", 3.0], ["n1", 13.0], ["n2", 23.0]]
    finally:
        engine.close()

"""A RANGE ... ALIGN statement through the aggregate path (ISSUE 44): the
served answer against the benchmark's plain reference
(`benchmark/templates/greptime_range.py` `range_reference`) on a seeded
random table WITH absent rows — 3 tag columns, 2 fields, 50 series x 500
points — and the same statement over other layouts of the same rows.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.common import load_module  # noqa: E402

range_reference = load_module("templates", "greptime_range").range_reference

SERIES, POINTS, STEP = 50, 500, 1000
T0 = 1_700_000_000_000
TAGS = {"a": [f"a{i:02d}" for i in range(SERIES)],
        "b": [f"b{i % 5}" for i in range(SERIES)],
        "c": [f"c{i % 3}" for i in range(SERIES)]}
DDL = ("CREATE TABLE {name} (a STRING, b STRING, c STRING, "
       "ts TIMESTAMP(3) TIME INDEX, v DOUBLE, w DOUBLE, "
       "PRIMARY KEY (a, b, c)){tail}")
FOUR_REGIONS = (" PARTITION ON COLUMNS (a) (a < 'a12', a >= 'a12' AND "
                "a < 'a25', a >= 'a25' AND a < 'a37', a >= 'a37')")
APPEND = " WITH (append_mode = 'true')"


class Data:
    """The seeded grid: values, which rows exist, which values are NULL."""

    def __init__(self, seed: int = 44):
        rng = np.random.default_rng(seed)
        self.ts = T0 + np.arange(POINTS, dtype=np.int64) * STEP
        self.v = rng.uniform(0.0, 100.0, (POINTS, SERIES))
        self.w = rng.uniform(-50.0, 50.0, (POINTS, SERIES))
        self.present = rng.random((POINTS, SERIES)) > 0.1
        for s in range(0, SERIES, 3):  # outages: whole windows empty
            p0 = int(rng.integers(0, POINTS - 120))
            self.present[p0:p0 + int(rng.integers(30, 120)), s] = False
        self.present[:60, 7] = False   # a series that starts late
        self.v[rng.random((POINTS, SERIES)) < 0.02] = np.nan  # NULLs

    def batch(self, schema, points: np.ndarray, series: np.ndarray):
        from greptimedb_tpu.datatypes import DictVector, RecordBatch

        cols = {"ts": self.ts[points], "v": self.v[points, series],
                "w": self.w[points, series]}
        for tag, per_series in TAGS.items():
            values, codes = np.unique(np.asarray(per_series, dtype=object),
                                      return_inverse=True)
            cols[tag] = DictVector(codes.astype(np.int32)[series], values)
        return RecordBatch(schema, cols)


DATA = Data()


def _put(qe, table: str, points, series) -> None:
    info = qe.catalog.table("public", table)
    qe._sharded_write(info, DATA.batch(info.schema, points, series), False)


def _flush(qe, engine, table: str) -> None:
    for rid in qe.catalog.table("public", table).region_ids:
        engine.flush(rid)


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    from greptimedb_tpu.catalog import Catalog, FileKv
    from greptimedb_tpu.query import QueryEngine
    from greptimedb_tpu.storage import RegionEngine
    from greptimedb_tpu.storage.engine import EngineConfig

    home = str(tmp_path_factory.mktemp("range"))
    engine = RegionEngine(EngineConfig(data_dir=home))
    qe = QueryEngine(Catalog(FileKv(home + "/catalog.json")), engine)
    points, series = np.nonzero(DATA.present)
    third = len(points) // 3
    # (d) one flushed region
    qe.execute_one(DDL.format(name="m", tail=APPEND))
    _put(qe, "m", points, series)
    _flush(qe, engine, "m")
    # (a) two SSTs and a memtable tail
    qe.execute_one(DDL.format(name="m_parts", tail=APPEND))
    for lo, hi, flush in ((0, third, True), (third, 2 * third, True),
                          (2 * third, len(points), False)):
        _put(qe, "m_parts", points[lo:hi], series[lo:hi])
        if flush:
            _flush(qe, engine, "m_parts")
    # (b) last-write-wins, a fifth of the rows sent again
    qe.execute_one(DDL.format(name="m_lww", tail=""))
    _put(qe, "m_lww", points, series)
    _flush(qe, engine, "m_lww")
    _put(qe, "m_lww", points[::5], series[::5])
    # (c) four regions
    qe.execute_one(DDL.format(name="m_4r", tail=FOUR_REGIONS + APPEND))
    _put(qe, "m_4r", points, series)
    _flush(qe, engine, "m_4r")
    yield qe
    engine.close()


BYS = {
    "pk": ("", np.arange(SERIES), lambda s: (TAGS["a"][s],)),
    "b": (" BY (b)", np.arange(SERIES) % 5, lambda s: (f"b{s}",)),
    "all": (" BY ()", np.zeros(SERIES, np.int64), lambda s: ()),
}
FILLS = {"none": (None, ""), "null": ("null", " FILL NULL"),
         "prev": ("prev", " FILL PREV"), "const": (7.5, " FILL 7.5")}
FUNCS = ["avg", "sum", "count", "min", "max", "first_value", "last_value",
         "stddev"]
ALIGN_S = 20
LO, HI = T0 + 40 * STEP + 300, T0 + 460 * STEP + 300  # unaligned bounds


def _statement(table: str, func: str, slots: int, fill: str, by: str,
               lo: int = LO, hi: int = HI) -> str:
    cols = {"pk": "a, ", "b": "b, ", "all": ""}[by]
    return (f"SELECT ts, {cols}{func}(v) RANGE '{slots * ALIGN_S}s', "
            f"count(w) RANGE '{ALIGN_S}s' FROM {table} "
            f"WHERE ts >= {lo} AND ts < {hi} AND c != 'c1' "
            f"ALIGN '{ALIGN_S}s'{BYS[by][0]}{FILLS[fill][1]} "
            f"ORDER BY {cols}ts")


def _reference(func: str, slots: int, fill: str, by: str,
               lo: int = LO, hi: int = HI) -> list:
    _, series, label = BYS[by]
    where = ((DATA.ts >= lo) & (DATA.ts < hi),
             np.asarray([c != "c1" for c in TAGS["c"]]))
    keys, vals, _ = range_reference(
        DATA.ts, series, [DATA.v, DATA.w], DATA.present, where,
        ALIGN_S * 1000, 0, [slots * ALIGN_S * 1000, ALIGN_S * 1000],
        [func, "count"], [FILLS[fill][0]] * 2)
    rows = [(label(s), t, v) for (s, t), v in zip(keys, vals)]
    rows.sort(key=lambda r: (r[0], r[1]))
    return rows


def _assert_rows(got: list, want: list, n_by: int) -> None:
    assert len(got) == len(want)
    for g, (label, t, vals) in zip(got, want):
        assert g[0] == t and tuple(g[1:1 + n_by]) == label
        for x, r in zip(g[1 + n_by:], vals):
            if np.isnan(r):
                assert x is None or np.isnan(x)
            else:
                assert x == pytest.approx(r, rel=1e-9, abs=1e-9)


@pytest.mark.parametrize("by", list(BYS))
@pytest.mark.parametrize("fill", list(FILLS))
@pytest.mark.parametrize("slots", [1, 3, 10])
@pytest.mark.parametrize("func", FUNCS)
def test_served_answer_equals_the_reference(db, func, slots, fill, by):
    if func in ("first_value", "last_value") and by != "pk":
        # rows of several series share a ts: which is first is not
        # defined — the series' own first and last are
        by = "pk"
    got = db.execute_one(_statement("m", func, slots, fill, by)).rows()
    want = _reference(func, slots, fill, by)
    assert len(want) > 50 or by == "all"
    _assert_rows(got, want, len(BYS[by][2](0)))


@pytest.mark.parametrize("table", ["m_parts", "m_lww", "m_4r"])
@pytest.mark.parametrize("func,slots,fill,by", [
    ("avg", 3, "prev", "pk"), ("max", 10, "null", "b"),
    ("count", 1, "const", "all"), ("last_value", 3, "none", "pk")])
def test_other_layouts_of_the_same_rows_answer_alike(db, table, func,
                                                     slots, fill, by):
    """(a) two SSTs and a memtable tail, (b) a last-write-wins table
    with rows sent again, (c) four regions — each against (d), one
    flushed region."""
    one = db.execute_one(_statement("m", func, slots, fill, by)).rows()
    other = db.execute_one(_statement(table, func, slots, fill, by)).rows()
    assert len(one) > 20
    _assert_rows(other, [(tuple(r[1:len(r) - 2]), r[0], r[-2:])
                         for r in _none_to_nan(one)], len(one[0]) - 3)


def _none_to_nan(rows: list) -> list:
    return [[np.nan if x is None else x for x in r] for r in rows]


def _compiles() -> float:
    from greptimedb_tpu.utils.metrics import REGISTRY

    total = 0.0
    for line in REGISTRY.render().splitlines():
        if line.startswith("greptimedb_tpu_xla_compile_total"):
            total += float(line.rsplit(" ", 1)[1])
    return total


@pytest.mark.parametrize("table", ["m", "m_4r"])
def test_other_literals_compile_nothing(db, table):
    """The window's bounds are operands of the aggregate's programs: a
    second window of as many buckets runs what the first compiled."""
    from greptimedb_tpu.utils.metrics import FAST_LANE_EVENTS

    for _ in range(3):  # seen, built, hit: the template's steady path
        db.execute_one(_statement(table, "avg", 3, "prev", "b"))
    before, hits = _compiles(), FAST_LANE_EVENTS.get(event="hit")
    shift = 7 * ALIGN_S * 1000
    got = db.execute_one(_statement(table, "avg", 3, "prev", "b",
                                    LO + shift, HI + shift)).rows()
    assert _compiles() == before
    assert FAST_LANE_EVENTS.get(event="hit") == hits + 1
    _assert_rows(got, _reference("avg", 3, "prev", "b", LO + shift,
                                 HI + shift), 1)


@pytest.mark.parametrize("table,path", [("m", "+range_combine"),
                                        ("m_4r", "fanout+")])
def test_explain_analyze_prints_path_and_tier(db, table, path):
    sql = _statement(table, "max", 3, "null", "pk")
    lines = db.execute_one("EXPLAIN ANALYZE " + sql).columns[0].tolist()
    assert lines[0].startswith("RangeCombine:")
    paths = [ln for ln in lines if "execution path:" in ln]
    assert len(paths) == 1 and path in paths[0] \
        and paths[0].rstrip().endswith("+range_combine")
    assert sum("execution tier:" in ln for ln in lines) == 1
    assert any("range_combine" in ln and "series=" in ln for ln in lines)
    # EXPLAIN alone plans it too
    plan = db.execute_one("EXPLAIN " + sql).columns[0].tolist()
    assert plan[0].startswith("RangeCombine:") \
        and any("Aggregate:" in ln for ln in plan)


def test_the_slow_query_log_carries_the_path(db, monkeypatch):
    from greptimedb_tpu.utils import slow_query

    monkeypatch.setenv("GTPU_SLOW_QUERY_MS", "0.0001")
    sql = _statement("m", "min", 3, "none", "b")
    db.execute_one(sql)
    rec = next(r for r in slow_query.records() if r.query == sql)
    assert rec.execution_path.endswith("+range_combine")
    assert rec.plan_cache_skip is None


def test_counters_tell_observed_from_filled(db):
    from greptimedb_tpu.utils.metrics import RANGE_SELECT, RANGE_WINDOWS

    def read():
        return (RANGE_WINDOWS.get(kind="observed"),
                RANGE_WINDOWS.get(kind="filled"),
                sum(RANGE_SELECT._values.values()))

    o0, f0, n0 = read()
    rows = db.execute_one(_statement("m", "max", 3, "null", "pk")).rows()
    _, _, filled = range_reference(
        DATA.ts, np.arange(SERIES), [DATA.v, DATA.w], DATA.present,
        ((DATA.ts >= LO) & (DATA.ts < HI),
         np.asarray([c != "c1" for c in TAGS["c"]])),
        ALIGN_S * 1000, 0, [3 * ALIGN_S * 1000, ALIGN_S * 1000],
        ["max", "count"], ["null"] * 2)
    o1, f1, n1 = read()
    assert filled.sum() > 0
    assert (o1 - o0, f1 - f0, n1 - n0) == (
        len(rows) - filled.sum(), filled.sum(), 1)


def test_a_group_space_past_the_old_refusal_answers(db):
    """5M one-second buckets x the tags' dictionaries: the lowered
    aggregate takes the sparse path where the old kernel refused
    ("group space ... too large")."""
    db.execute_one(
        "CREATE TABLE wide (k STRING, v DOUBLE, ts TIMESTAMP(3) TIME "
        "INDEX, PRIMARY KEY (k)) WITH (append_mode = 'true')")
    far = 5_000_000_000
    db.execute_one(f"INSERT INTO wide VALUES ('x', 1.0, 0), ('x', 2.0, 500), "
                   f"('y', 5.0, {far}), ('x', 7.0, {far + 1000})")
    r = db.execute_one("SELECT ts, k, sum(v) RANGE '2s' FROM wide "
                       "ALIGN '1s' ORDER BY k, ts")
    assert r.rows() == [
        [-1000, "x", 3.0], [0, "x", 3.0], [far, "x", 7.0],
        [far + 1000, "x", 7.0], [far - 1000, "y", 5.0], [far, "y", 5.0]]
    lines = db.execute_one(
        "EXPLAIN ANALYZE SELECT ts, k, sum(v) RANGE '2s' FROM wide "
        "ALIGN '1s'").columns[0].tolist()
    assert any("execution path:" in ln and "sparse" in ln for ln in lines)


def test_align_to_an_origin_off_the_grid(db):
    """ALIGN TO shifts every window start; date_bin with an origin is a
    generic group key, on the host as on the device."""
    origin = T0 + 7000
    sql = (f"SELECT ts, b, max(v) RANGE '60s' FROM m WHERE ts >= {LO} AND "
           f"ts < {HI} ALIGN '20s' TO {origin} BY (b) ORDER BY b, ts")
    got = db.execute_one(sql).rows()
    keys, vals, _ = range_reference(
        DATA.ts, np.arange(SERIES) % 5, [DATA.v], DATA.present,
        ((DATA.ts >= LO) & (DATA.ts < HI), np.ones(SERIES, bool)),
        20_000, origin, [60_000], ["max"], [None])
    assert all((k[1] - origin) % 20_000 == 0 for k in keys)
    _assert_rows(got, [((f"b{s}",), t, v) for (s, t), v in zip(keys, vals)],
                 1)
    by_bin = db.execute_one(
        f"SELECT date_bin(INTERVAL '20 seconds', ts, {origin}) AS t, "
        f"count(*) FROM m WHERE ts >= {LO} AND ts < {HI} GROUP BY t "
        "ORDER BY t").rows()
    assert all((r[0] - origin) % 20_000 == 0 for r in by_bin) \
        and sum(r[1] for r in by_bin) == int(
            DATA.present[(DATA.ts >= LO) & (DATA.ts < HI)].sum())

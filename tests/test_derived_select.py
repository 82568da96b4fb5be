"""A select over a derived table as arrays (query/join.py `_aggregate`,
PR 38): a two-level aggregate equals the flat numpy answer, NULL groups
included; HAVING, ORDER BY and LIMIT run over the groups; and no Python
loop runs per row (a 200,000-row inner result finishes in a stated
time)."""

import time

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import join
from greptimedb_tpu.query.engine import QueryContext, QueryEngine
from greptimedb_tpu.sql.parser import parse_sql
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils.metrics import DERIVED_SELECT_SECONDS

CTX = QueryContext()


@pytest.fixture
def db(tmp_path):
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    qe.execute_one(
        "CREATE TABLE readings (name STRING, driver STRING, ts "
        "TIMESTAMP(3) TIME INDEX, velocity DOUBLE, PRIMARY KEY(name, "
        "driver))", CTX)
    yield eng, qe
    eng.close()


def _fill(qe, seed=1, trucks=9, points=180):
    """Trucks 0 and 1 have no name (a driver each: the primary key
    still tells them apart), truck 2 has no driver; velocity stands at
    0 for stretches."""
    rng = np.random.default_rng(seed)
    rows, model = [], []
    for t in range(trucks):
        name = None if t < 2 else f"t{t}"
        driver = None if t == 2 else f"d{t % 3 if t >= 2 else t}"
        v = np.where(rng.random(points) < 0.3, 0.0,
                     rng.uniform(5, 100, points))
        for p in range(points):
            if rng.random() < 0.05:
                continue
            rows.append("({}, {}, {}, {})".format(
                "NULL" if name is None else f"'{name}'",
                "NULL" if driver is None else f"'{driver}'",
                p * 10_000, v[p]))
            model.append((name, driver, p * 10_000, float(v[p])))
    qe.execute_one("INSERT INTO readings (name, driver, ts, velocity) "
                   "VALUES " + ", ".join(rows), CTX)
    return model


def _flat(model, bucket_ms, floor, more_than):
    """The two-level aggregate, flat, in numpy: per (name, driver) the
    buckets whose avg(velocity) > floor; groups with more than
    `more_than` of them, their count and the mean of those averages."""
    buckets: dict = {}
    for name, driver, ts, v in model:
        buckets.setdefault((name, driver, ts // bucket_ms), []).append(v)
    good: dict = {}
    for (name, driver, _b), vs in buckets.items():
        if np.mean(vs) > floor:
            good.setdefault((name, driver), []).append(np.mean(vs))
    return {k: (len(v), float(np.mean(v))) for k, v in good.items()
            if len(v) > more_than}


SQL = ("SELECT name, driver, count(*) AS n, avg(v) AS mean_v FROM ("
       "SELECT name, driver, date_bin(INTERVAL '1 minute', ts) AS m, "
       "avg(velocity) AS v FROM readings GROUP BY name, driver, m "
       "HAVING avg(velocity) > 20) AS driven GROUP BY name, driver "
       "HAVING count(*) > {k}{tail}")


def test_a_two_level_aggregate_equals_the_flat_answer(db):
    _eng, qe = db
    model = _fill(qe)
    want = _flat(model, 60_000, 20.0, 12)
    assert {(None, "d0"), (None, "d1"), ("t2", None)} <= set(want)
    rows = qe.execute_one(SQL.format(k=12, tail=""), CTX).rows()
    got = {(r[0], r[1]): (r[2], r[3]) for r in rows}
    assert len(got) == len(rows)
    assert got == {k: (n, pytest.approx(m, rel=1e-12))
                   for k, (n, m) in want.items()}
    # groups come sorted, NULL last in each component, as before
    keys = [(r[0], r[1]) for r in rows]
    assert keys == sorted(keys, key=lambda k: tuple(
        (v is None, v or "") for v in k))


def test_having_order_by_and_limit_run_over_the_groups(db):
    _eng, qe = db
    model = _fill(qe, seed=2)
    want = _flat(model, 60_000, 20.0, 0)
    rows = qe.execute_one(SQL.format(
        k=0, tail=" ORDER BY mean_v DESC LIMIT 3"), CTX).rows()
    top = sorted(want.items(), key=lambda kv: -kv[1][1])[:3]
    assert [(r[0], r[1]) for r in rows] == [k for k, _ in top]
    assert [r[3] for r in rows] == [pytest.approx(v[1]) for _, v in top]


def test_aggregates_skip_null_and_an_empty_group_is_null(db):
    _eng, qe = db
    qe.execute_one(
        "INSERT INTO readings (name, driver, ts, velocity) VALUES "
        "('a', 'x', 1000, 1.0), ('a', 'x', 2000, NULL), "
        "('b', 'x', 1000, NULL), ('b', 'y', 3000, 4.0)", CTX)
    rows = qe.execute_one(
        "SELECT name, count(*), count(v), sum(v), min(v), max(v), avg(v) "
        "FROM (SELECT name, driver, ts, velocity AS v FROM readings) AS r "
        "GROUP BY name", CTX).rows()
    assert rows == [["a", 2, 1, 1.0, 1.0, 1.0, 1.0],
                    ["b", 2, 1, 4.0, 4.0, 4.0, 4.0]]
    rows = qe.execute_one(
        "SELECT driver, sum(v) FROM (SELECT name, driver, velocity AS v "
        "FROM readings WHERE name = 'b') AS r GROUP BY driver", CTX).rows()
    assert rows == [["x", None], ["y", 4.0]]
    # no GROUP BY over no rows: one group, count 0, the others NULL
    rows = qe.execute_one(
        "SELECT count(*), sum(v) FROM (SELECT velocity AS v FROM readings "
        "WHERE name = 'nobody') AS r", CTX).rows()
    assert rows == [[0, None]]


def test_no_python_loop_runs_per_row():
    """200,000 inner rows (4,000 groups x 50) in well under the 2 s the
    row-by-row walk took for a tenth of it on this machine."""
    n, groups = 200_000, 4_000
    rng = np.random.default_rng(0)
    g = rng.integers(0, groups, n)
    names = np.asarray([None if x % 97 == 0 else f"truck_{x}" for x in g],
                       dtype=object)
    drivers = np.asarray([f"d{x % 10}" for x in g], dtype=object)
    v = rng.uniform(0, 100, n)
    v[rng.integers(0, n, 500)] = np.nan
    sel = parse_sql(
        "SELECT name, driver, count(*) AS n, avg(v) AS m, max(v) FROM r "
        "GROUP BY name, driver HAVING count(*) > 40")[0]
    observed = DERIVED_SELECT_SECONDS.count()
    t0 = time.perf_counter()
    r = join.execute_select_over(
        None, sel, {"name": names, "driver": drivers, "v": v},
        {"name": None, "driver": None, "v": None})
    took = time.perf_counter() - t0
    assert took < 2.0, took
    # against numpy, flat
    key = np.asarray([f"{a}|{b}" for a, b in zip(names, drivers)])
    uniq, inv = np.unique(key, return_inverse=True)
    cnt = np.bincount(inv)
    ok = ~np.isnan(v)
    mean = np.bincount(inv[ok], weights=v[ok]) / np.bincount(
        inv[ok], minlength=len(uniq))
    want = {u: (c, m) for u, c, m in zip(uniq, cnt, mean) if c > 40}
    got = {f"{a}|{b}": (c, m) for a, b, c, m, _mx in r.rows()}
    assert got.keys() == want.keys()
    for k, (c, m) in want.items():
        assert got[k][0] == c and got[k][1] == pytest.approx(m, rel=1e-12)
    assert DERIVED_SELECT_SECONDS.count() == observed + 1

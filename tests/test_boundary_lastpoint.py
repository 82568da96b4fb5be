"""Lastpoint boundary fast path: first/last aggregates gather per-series
run-boundary rows from the (tags, ts, seq)-sorted SST segments instead of
reducing the whole scan (physical.py::_boundary_firstlast).

Every test cross-checks the fast path against the general segment kernel
(fast path monkeypatched off), the strategy the prepared-plane work used
(SURVEY.md §4: differential oracles)."""

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.query.physical import PhysicalExecutor
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig


@pytest.fixture
def db(tmp_path, monkeypatch):
    # tiny tables: every row is a boundary candidate, which the benefit
    # threshold would veto — force the path on so correctness is tested
    monkeypatch.setattr(
        "greptimedb_tpu.query.physical._BOUNDARY_MAX_FRACTION", 1.01)
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data")))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    yield qe
    engine.close()


def _mk(db, append_mode=False, two_tags=False):
    tags = "host STRING, dc STRING," if two_tags else "host STRING,"
    pk = "PRIMARY KEY (host, dc)" if two_tags else "PRIMARY KEY (host)"
    opts = " WITH (append_mode = 'true')" if append_mode else ""
    db.execute_one(
        f"CREATE TABLE t ({tags} v DOUBLE, w DOUBLE, ts TIMESTAMP(3) "
        f"NOT NULL, TIME INDEX (ts), {pk}){opts}")


def _ins(db, rows, two_tags=False):
    cols = "(host, dc, v, w, ts)" if two_tags else "(host, v, w, ts)"
    vals = ", ".join(
        "(" + ", ".join(
            f"'{x}'" if isinstance(x, str) else str(x) for x in r) + ")"
        for r in rows)
    db.execute_one(f"INSERT INTO t {cols} VALUES {vals}")


def _flush(db):
    info = db.catalog.table("public", "t")
    db.region_engine.flush(info.region_ids[0])


SQL = ("SELECT host, last_value(v ORDER BY ts) AS lv, "
       "first_value(w ORDER BY ts) AS fw FROM t GROUP BY host "
       "ORDER BY host")


def _run_both(db, sql):
    """(fast-path rows, general-kernel rows, fast path actually used)."""
    fast = db.execute_one(sql)
    used = "boundary+" in (db.executor.last_path or "")
    orig = PhysicalExecutor._boundary_firstlast
    PhysicalExecutor._boundary_firstlast = (
        lambda self, *a, **k: None)
    try:
        slow = db.execute_one(sql)
    finally:
        PhysicalExecutor._boundary_firstlast = orig
    return fast.rows(), slow.rows(), used


def test_multi_file_and_memtable(db):
    """Winners spread over two SSTs and an unsorted memtable tail."""
    _mk(db)
    _ins(db, [("a", 1.0, 10.0, 1000), ("a", 2.0, 20.0, 2000),
              ("b", 3.0, 30.0, 1500)])
    _flush(db)
    _ins(db, [("a", 4.0, 40.0, 3000), ("b", 5.0, 50.0, 500),
              ("c", 6.0, 60.0, 100)])
    _flush(db)
    # memtable rows deliberately out of time order within a series
    _ins(db, [("b", 7.0, 70.0, 4000), ("b", 8.0, 80.0, 200),
              ("c", 9.0, 90.0, 5000)])
    fast, slow, used = _run_both(db, SQL)
    assert used
    assert fast == slow
    assert fast == [["a", 4.0, 10.0], ["b", 7.0, 80.0], ["c", 9.0, 60.0]]


def test_lww_duplicate_instants_across_files(db):
    """Same (series, ts) written in both files: max seq must win, for
    both the max-ts instant (last) and the min-ts instant (first)."""
    _mk(db)
    _ins(db, [("a", 1.0, 10.0, 1000), ("a", 2.0, 20.0, 5000)])
    _flush(db)
    # overwrite both instants with newer versions in a later file
    _ins(db, [("a", 11.0, 110.0, 1000), ("a", 12.0, 120.0, 5000)])
    _flush(db)
    fast, slow, used = _run_both(db, SQL)
    assert used
    assert fast == slow
    assert fast == [["a", 12.0, 110.0]]


def test_duplicate_instants_within_one_file(db):
    """Two versions of one instant inside a single sorted segment: the
    sub-run end (max seq) is the candidate, not the run start."""
    _mk(db)
    _ins(db, [("a", 1.0, 10.0, 1000)])
    _ins(db, [("a", 2.0, 20.0, 1000)])  # newer version, same instant
    _ins(db, [("a", 3.0, 30.0, 2000)])
    _flush(db)
    fast, slow, used = _run_both(db, SQL)
    assert used
    assert fast == slow
    assert fast == [["a", 3.0, 20.0]]


def test_delete_tombstone_disables_path(db):
    """A tombstone can shadow the newest row; the fast path must bow out
    and the general kernel must produce the pre-delete answer."""
    _mk(db)
    _ins(db, [("a", 1.0, 10.0, 1000), ("a", 2.0, 20.0, 2000)])
    _flush(db)
    db.execute_one("DELETE FROM t WHERE host = 'a' AND ts = 2000")
    _flush(db)
    fast, slow, used = _run_both(db, SQL)
    assert not used
    assert fast == slow
    assert fast == [["a", 1.0, 10.0]]


def test_where_disables_path(db):
    """Any residual WHERE can unseat boundary rows — general kernel."""
    _mk(db)
    _ins(db, [("a", 1.0, 10.0, 1000), ("a", 2.0, 20.0, 2000),
              ("a", 3.0, 30.0, 3000)])
    _flush(db)
    sql = ("SELECT host, last_value(v ORDER BY ts) AS lv FROM t "
           "WHERE v < 2.5 GROUP BY host")
    fast, slow, used = _run_both(db, sql)
    assert not used
    assert fast == slow
    assert fast == [["a", 2.0]]


def test_mixed_agg_disables_path(db):
    """count(*) alongside last_value needs true row counts."""
    _mk(db)
    _ins(db, [("a", 1.0, 10.0, 1000), ("a", 2.0, 20.0, 2000)])
    _flush(db)
    sql = ("SELECT host, last_value(v ORDER BY ts) AS lv, count(*) AS c "
           "FROM t GROUP BY host")
    fast, slow, used = _run_both(db, sql)
    assert not used
    assert fast == slow
    assert fast == [["a", 2.0, 2]]


def test_group_by_tag_subset(db):
    """Group by one tag of a two-tag primary key: winners still sit on
    full-pk run boundaries."""
    _mk(db, two_tags=True)
    _ins(db, [("a", "x", 1.0, 10.0, 1000), ("a", "y", 2.0, 20.0, 5000),
              ("a", "x", 3.0, 30.0, 4000), ("b", "x", 4.0, 40.0, 100)],
         two_tags=True)
    _flush(db)
    fast, slow, used = _run_both(db, SQL)
    assert used
    assert fast == slow
    assert fast == [["a", 2.0, 10.0], ["b", 4.0, 40.0]]


def test_append_mode_large_random(db):
    """Randomized differential: 20k rows, 50 series, three flushes plus a
    memtable tail, append mode (no dedup)."""
    _mk(db, append_mode=True)
    rng = np.random.default_rng(42)
    info = db.catalog.table("public", "t")
    rid = info.region_ids[0]
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    names = np.asarray([f"h{i:02d}" for i in range(50)], dtype=object)
    for part in range(4):  # 3 flushed + 1 memtable
        n = 5000
        codes = rng.integers(0, 50, n).astype(np.int32)
        # distinct ts per row (no ties): ties have no defined winner in
        # append mode, so the two paths could legitimately differ
        ts = rng.permutation(n).astype(np.int64) * 7 + part * 40000
        batch = RecordBatch(info.schema, {
            "host": DictVector(codes, names),
            "v": rng.uniform(0, 100, n),
            "w": rng.uniform(0, 100, n),
            "ts": ts,
        })
        db.region_engine.put(rid, batch)
        if part < 3:
            db.region_engine.flush(rid)
    fast, slow, used = _run_both(db, SQL)
    assert used
    assert fast == slow


@pytest.mark.parametrize("group", ["host", "dc", ""])
def test_append_mode_runs_are_cut_by_the_tags_the_scan_holds(db, group):
    """An append-mode table's scan holds the tags the statement names,
    not the whole key: series of one group then share a run wherever
    time does not fall between them, and the reduction still finds each
    group's first and last instants — grouped by the leading tag, by
    the second one (several series a group, interleaved in time), and
    by nothing. 3,000 rows, 24 series, two files and a memtable tail,
    no two rows on one instant."""
    _mk(db, append_mode=True, two_tags=True)
    rng = np.random.default_rng(7)
    rid = db.catalog.table("public", "t").region_ids[0]
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    hosts = np.asarray([f"h{i}" for i in range(6)], dtype=object)
    dcs = np.asarray([f"d{i}" for i in range(4)], dtype=object)
    schema = db.catalog.table("public", "t").schema
    for part in range(3):
        n = 1000
        ts = rng.permutation(3 * n)[:n].astype(np.int64) * 3 + part
        db.region_engine.put(rid, RecordBatch(schema, {
            "host": DictVector(rng.integers(0, 6, n).astype(np.int32),
                               hosts),
            "dc": DictVector(rng.integers(0, 4, n).astype(np.int32), dcs),
            "v": rng.uniform(0, 100, n), "w": rng.uniform(0, 100, n),
            "ts": ts,
        }))
        if part < 2:
            db.region_engine.flush(rid)
    by = f" GROUP BY {group} ORDER BY {group}" if group else ""
    sql = (f"SELECT {group + ', ' if group else ''}"
           "last_value(v ORDER BY ts) AS lv, "
           f"first_value(w ORDER BY ts) AS fw FROM t{by}")
    from greptimedb_tpu.utils.metrics import SCAN_KEY_COLUMNS

    decoded = SCAN_KEY_COLUMNS.get(kind="decoded")
    fast, slow, used = _run_both(db, sql)
    assert used
    assert fast == slow
    assert len(fast) == {"host": 6, "dc": 4, "": 1}[group]
    assert SCAN_KEY_COLUMNS.get(kind="decoded") == decoded


def test_global_first_last_no_group(db):
    _mk(db)
    _ins(db, [("a", 1.0, 10.0, 1000), ("b", 2.0, 20.0, 9000),
              ("c", 3.0, 30.0, 500)])
    _flush(db)
    sql = ("SELECT last_value(v ORDER BY ts) AS lv, "
           "first_value(w ORDER BY ts) AS fw FROM t")
    fast, slow, used = _run_both(db, sql)
    assert used
    assert fast == slow
    assert fast == [[2.0, 30.0]]

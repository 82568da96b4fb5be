"""Last-write-wins as a host merge of a scan's sorted runs (query/lww.py,
PR 38): the mask against a dictionary model over overlapping SSTs,
resends, tombstones and NULL tags; no program per row count (two scans of
different row counts share every program); the path each scan took
(`lww_mask_events_total`); a part's cached partial keyed by the rows it
lost; and a tag's numeric value in arithmetic (`CAST(tag AS DOUBLE)`)."""

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import lww
from greptimedb_tpu.query import partial_cache as pc
from greptimedb_tpu.query.engine import QueryContext, QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils.metrics import (
    AGG_PROGRAM_EVENTS,
    LWW_MASK_EVENTS,
    XLA_COMPILES,
)

CTX = QueryContext()


@pytest.fixture
def db(tmp_path):
    pc.global_cache().clear()
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    qe.execute_one(
        "CREATE TABLE trucks (name STRING, fleet STRING, cap STRING, "
        "ts TIMESTAMP(3) TIME INDEX, v DOUBLE, PRIMARY KEY(name, fleet, "
        "cap))", CTX)
    rid = qe.catalog.table("public", "trucks").region_ids[0]
    yield eng, qe, rid
    pc.global_cache().clear()
    eng.close()


def _sql_value(v):
    return "NULL" if v is None else f"'{v}'"


def _write(qe, rows):
    qe.execute_one(
        "INSERT INTO trucks (name, fleet, cap, ts, v) VALUES " + ", ".join(
            f"({_sql_value(n)}, {_sql_value(f)}, '{c}', {ts}, {v})"
            for n, f, c, ts, v in rows), CTX)


def _load(eng, qe, rid, seed, files=4, trucks=12, points=50):
    """`files` SSTs whose time ranges overlap (a late backlog in each),
    rows resent across and inside files, a NULL name and a NULL fleet.
    Returns the model: {(name, fleet, cap, ts): v} by last write."""
    rng = np.random.default_rng(seed)
    model: dict = {}
    keys = [(None if t == 0 else f"t{t}", None if t == 1 else f"f{t % 3}",
             str(1000 + 500 * (t % 2))) for t in range(trucks)]
    for f in range(files):
        rows = []
        for p in range(points):
            ts = (f * points + p) * 10
            for k in keys:
                if rng.random() < 0.1:
                    continue  # a gap: the row never arrives
                rows.append((*k, ts, float(rng.integers(0, 1000))))
        # a backlog: rows of the PREVIOUS file's time range, some new,
        # some written again with another value
        for _ in range(20 if f else 0):
            k = keys[int(rng.integers(0, trucks))]
            ts = int(rng.integers((f - 1) * points, f * points)) * 10
            rows.append((*k, ts, float(rng.integers(1000, 2000))))
        # and a row resent inside this file
        rows.append(rows[0][:4] + (rows[0][4] + 0.5,))
        _write(qe, rows)
        for r in rows:
            model[r[:4]] = r[4]
        eng.flush(rid)
    return model


def _events():
    return {p: LWW_MASK_EVENTS.get(path=p)
            for p in ("none", "host_merge")}


def test_the_mask_equals_a_dictionary_model(db):
    eng, qe, rid = db
    model = _load(eng, qe, rid, seed=3)
    e0 = _events()
    r = qe.execute_one("SELECT count(*), sum(v) FROM trucks", CTX).rows()[0]
    assert r[0] == len(model)
    assert r[1] == pytest.approx(sum(model.values()), rel=1e-12)
    got = {(n, f, c, ts): v for n, f, c, ts, v in qe.execute_one(
        "SELECT name, fleet, cap, ts, v FROM trucks", CTX).rows()}
    assert got == model
    e1 = _events()
    assert e1["host_merge"] > e0["host_merge"]


def test_a_delete_hides_its_instant_and_only_it(db):
    eng, qe, rid = db
    model = _load(eng, qe, rid, seed=5, files=2)
    gone = [k for k in model if k[0] == "t3"][:7]
    for n, f, c, ts in gone:
        qe.execute_one(f"DELETE FROM trucks WHERE name = '{n}' AND fleet = "
                       f"'{f}' AND cap = '{c}' AND ts = {ts}", CTX)
        del model[(n, f, c, ts)]
    r = qe.execute_one("SELECT count(*), sum(v) FROM trucks", CTX).rows()[0]
    assert r[0] == len(model)
    assert r[1] == pytest.approx(sum(model.values()), rel=1e-12)


def test_no_repeat_means_no_mask_and_says_so(db):
    eng, qe, rid = db
    _write(qe, [(f"t{t}", "f0", "1000", p * 10, 1.0)
                for p in range(40) for t in range(5)])
    eng.flush(rid)
    e0 = _events()
    assert qe.execute_one("SELECT count(*) FROM trucks",
                          CTX).rows()[0][0] == 200
    e1 = _events()
    assert e1["none"] == e0["none"] + 1
    assert e1["host_merge"] == e0["host_merge"]


def test_two_row_counts_share_every_program(db):
    """The parent compiled seven programs per row count for the mask.
    Two windows of different row counts (same block size) now compile
    nothing the second time, and the aggregate's program is reused."""
    eng, qe, rid = db
    _load(eng, qe, rid, seed=7)
    sql = ("SELECT fleet, count(*), sum(v) FROM trucks WHERE ts >= {lo} "
           "AND ts < {hi} GROUP BY fleet")
    a = qe.execute_one(sql.format(lo=0, hi=900), CTX)
    compiles = XLA_COMPILES.total()
    new = AGG_PROGRAM_EVENTS.get(event="new")
    reuse = AGG_PROGRAM_EVENTS.get(event="reuse")
    masks = sum(_events().values())
    b = qe.execute_one(sql.format(lo=130, hi=1210), CTX)
    assert sum(r[1] for r in a.rows()) != sum(r[1] for r in b.rows())
    assert sum(_events().values()) == masks + 1  # a mask of its own
    assert XLA_COMPILES.total() == compiles
    assert AGG_PROGRAM_EVENTS.get(event="new") == new
    assert AGG_PROGRAM_EVENTS.get(event="reuse") > reuse


def test_a_full_scans_mask_is_merged_once_a_data_version(db):
    eng, qe, rid = db
    model = _load(eng, qe, rid, seed=11)
    sql = "SELECT fleet, sum(v) FROM trucks GROUP BY fleet"
    first = qe.execute_one(sql, CTX).rows()
    masks = sum(_events().values())
    assert qe.execute_one(sql, CTX).rows() == first
    assert sum(_events().values()) == masks  # kept by snapshot identity
    _write(qe, [("t5", "f2", "1500", 10, 123456.0)])  # a new version
    again = qe.execute_one(sql, CTX).rows()
    assert sum(_events().values()) == masks + 1
    model[("t5", "f2", "1500", 10)] = 123456.0
    want: dict = {}
    for (_n, f, _c, _ts), v in model.items():
        want[f] = want.get(f, 0.0) + v
    assert {r[0]: r[1] for r in again} == pytest.approx(want)


def test_overlapping_parts_still_ride_the_partial_cache(db):
    """A part's cached partial is keyed by the rows of it that lost to a
    later write; parts that lost nothing are shared as an append-mode
    table's."""
    eng, qe, rid = db
    model = _load(eng, qe, rid, seed=13)
    sql = "SELECT fleet, count(*), sum(v) FROM trucks GROUP BY fleet"
    qe.execute_one(sql, CTX)
    assert qe.executor.last_path == "incremental"
    qe.execute_one(sql, CTX)
    stats = qe.executor.last_partial_stats
    assert stats["part_hits"] == stats["parts"] and stats["part_misses"] == 0
    # a write that repeats an instant of the FIRST file: only that
    # part's partial is computed again, and the answer follows
    victim = next(k for k in sorted(model, key=lambda k: (k[3], str(k)))
                  if k[0] is not None and k[1] is not None)
    _write(qe, [(*victim, 999999.0)])
    model[victim] = 999999.0
    rows = qe.execute_one(sql, CTX).rows()
    stats = qe.executor.last_partial_stats
    assert qe.executor.last_path == "incremental"
    assert stats["part_misses"] == 1
    want: dict = {}
    for (_n, f, _c, _ts), v in model.items():
        c, s = want.get(f, (0, 0.0))
        want[f] = (c + 1, s + v)
    assert {r[0]: (r[1], r[2]) for r in rows} == {
        f: (c, pytest.approx(s)) for f, (c, s) in want.items()}


def test_keys_too_wide_for_an_int64_merge_by_their_ranks(db, monkeypatch):
    eng, qe, rid = db
    model = _load(eng, qe, rid, seed=17, files=2)
    monkeypatch.setattr(lww, "_KEY_BITS", 3)
    e0 = _events()
    r = qe.execute_one("SELECT count(*), sum(v) FROM trucks", CTX).rows()[0]
    assert (r[0], r[1]) == (len(model), pytest.approx(sum(model.values())))
    got = {(n, f, c, ts): v for n, f, c, ts, v in qe.execute_one(
        "SELECT name, fleet, cap, ts, v FROM trucks", CTX).rows()}
    assert got == model
    assert _events()["host_merge"] > e0["host_merge"]
    assert XLA_COMPILES.total(fn="dedup_mask") == 0  # no device sort


def test_keys_wider_than_their_time_span_merge_by_two_keys(db,
                                                            monkeypatch):
    eng, qe, rid = db
    model = _load(eng, qe, rid, seed=19, files=3)
    monkeypatch.setattr(lww, "_KEY_BITS", 12)  # series fit, x time not
    r = qe.execute_one("SELECT count(*), sum(v) FROM trucks", CTX).rows()[0]
    assert (r[0], r[1]) == (len(model), pytest.approx(sum(model.values())))


def test_a_tags_numeric_value_in_arithmetic(db):
    """CAST(<tag> AS DOUBLE) inside an aggregate read the dictionary
    CODE on the parent (v / 0, v / 1): it reads the number the tag
    spells, NULL for a NULL tag, in the kernels as on the host."""
    eng, qe, rid = db
    model = _load(eng, qe, rid, seed=23, files=2)
    rows = qe.execute_one(
        "SELECT cap, avg(v / CAST(cap AS DOUBLE)), "
        "last_value(v / CAST(cap AS DOUBLE) ORDER BY ts) FROM trucks "
        "WHERE name = 't4' GROUP BY cap", CTX).rows()
    mine = {k: v for k, v in model.items() if k[0] == "t4"}
    cap = float(next(iter(mine))[2])
    assert len(rows) == 1 and rows[0][0] == str(int(cap))
    assert rows[0][1] == pytest.approx(
        np.mean([v / cap for v in mine.values()]), rel=1e-12)
    last = max(mine, key=lambda k: k[3])
    assert rows[0][2] == pytest.approx(mine[last] / cap, rel=1e-12)
    picked = qe.execute_one(
        "SELECT name FROM trucks WHERE name IS NOT NULL GROUP BY name "
        "HAVING last_value(v / CAST(cap AS DOUBLE) ORDER BY ts) >= 0.5",
        CTX).rows()
    want = set()
    for name in {k[0] for k in model if k[0] is not None}:
        own = {k: v for k, v in model.items() if k[0] == name}
        k = max(own, key=lambda k: k[3])
        if own[k] / float(k[2]) >= 0.5:
            want.add(name)
    assert {r[0] for r in picked} == want


def test_a_tag_that_spells_no_number_binds_to_equal_statics():
    """A tag's lookup table rides into jit as a static: two binds of one
    CAST must compare and hash equal even where a value is no number
    (NaN != NaN; a NaN of its own a bind would compile a program each)."""
    from greptimedb_tpu.sql import ast
    from greptimedb_tpu.query.expr import TagNumber, _tag_number

    def bind():
        return TagNumber(ast.Column("cap"), tuple(
            _tag_number(v) for v in ("1500", "n/a", None, "nan", "2e3")),
            "DOUBLE")

    a, b = bind(), bind()
    assert a == b and hash(a) == hash(b)
    got = a.lookup(np.asarray([0, 1, 2, 3, 4, -1]), np)
    assert got[0] == 1500.0 and got[4] == 2000.0
    assert np.isnan(got[[1, 2, 3, 5]]).all()

"""What a region scan decodes beside the columns a statement names
(ISSUE 45): the table's whole primary key rides along only where rows
will be merged by it. An append-mode table's statements make no
last-write-wins mask, so their scans read the named columns and the time
index; a table that is not append-mode reads every tag, as before. The
caller says which (`full_key`), from the table's declared `append_mode`.

Every append-mode answer is checked against a twin table created WITHOUT
`append_mode` over the same rows, whose scans take today's full key.
"""

import re

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils.metrics import REGISTRY, SCAN_KEY_COLUMNS

HOSTS, DCS, POINTS, STEP = 12, 3, 40, 1000
COLS = "(host, dc, rack, v, w, ts)"


def key_counts() -> tuple[float, float]:
    return (SCAN_KEY_COLUMNS.get(kind="decoded"),
            SCAN_KEY_COLUMNS.get(kind="skipped"))


def create(qe, name, append_mode, partitioned=False):
    part = " PARTITION ON COLUMNS (host) (host < 'h06', host >= 'h06')" \
        if partitioned else ""
    opts = " WITH (append_mode = 'true')" if append_mode else ""
    qe.execute_one(
        f"CREATE TABLE {name} (host STRING, dc STRING, rack STRING, "
        "v DOUBLE, w DOUBLE, ts TIMESTAMP(3) NOT NULL, TIME INDEX (ts), "
        f"PRIMARY KEY (host, dc, rack)){part}{opts}")


def rows_of(points) -> list[tuple]:
    """One row a (series, instant): a series is a host, its dc (three
    hosts share one: a non-leading tag groups several series) and its
    rack (the host's own). No two rows share an instant (a host's
    points lie its number of milliseconds off the grid): first / last
    over several series has one winner."""
    out = []
    for h in range(HOSTS):
        for p in points:
            out.append((f"h{h:02d}", f"dc{h % DCS}", f"r{h}",
                        float(h * 1000 + p), float((p * 7 + h) % 13),
                        p * STEP + h))
    return out


def insert(qe, name, rows):
    vals = ", ".join(
        "(" + ", ".join(f"'{x}'" if isinstance(x, str) else repr(x)
                        for x in r) + ")" for r in rows)
    qe.execute_one(f"INSERT INTO {name} {COLS} VALUES {vals}")


def flush(qe, name):
    for rid in qe.catalog.table("public", name).region_ids:
        qe.region_engine.flush(rid)


def fill(qe, name):
    """Two SSTs and a memtable tail at once (a table under ingest): the
    files' time extents are disjoint, the tail overlaps the second."""
    insert(qe, name, rows_of(range(0, 16)))
    flush(qe, name)
    insert(qe, name, rows_of(range(16, 32)))
    flush(qe, name)
    insert(qe, name, rows_of(range(32, POINTS)))


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    home = tmp_path_factory.mktemp("keycols")
    engine = RegionEngine(EngineConfig(data_dir=str(home / "data"),
                                       maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    for name, append, parts in (("app", True, False), ("lww", False, False),
                                ("app2", True, True), ("lww2", False, True)):
        create(qe, name, append, parts)
        fill(qe, name)
    yield qe
    engine.close()


def rid_of(qe, name) -> int:
    return qe.catalog.table("public", name).region_ids[0]


# ---- (1) the region reads what it is asked for ------------------------------

def _scan(engine, rid, how, projection, **kw):
    if how == "window":
        return engine.scan(rid, (0, 10 * STEP), projection, None, **kw)
    if how == "point":
        return engine.scan(rid, None, projection, {"host": {"h03", "h07"}},
                           **kw)
    if how == "whole":
        return engine.scan(rid, None, projection, None, **kw)
    if how == "last":
        return engine.scan_last(rid, "host", projection, **kw)
    assert how == "stream"
    return engine.scan_stream(rid, None, projection, None, **kw)


def _columns_of(scan, how) -> set:
    if how == "stream":
        try:
            return {n for cols, _n in scan.chunks() for n in cols}
        finally:
            scan.close()
    scan.materialize()
    return set(scan.columns)


@pytest.mark.parametrize("how", ["window", "point", "whole", "last",
                                 "stream"])
@pytest.mark.parametrize("full_key", [False, True, None])
def test_a_scan_without_the_key_holds_the_named_columns(db, how, full_key):
    """`[host, v]` of a three-tag table: without the key the parts hold
    host, the time index and v, `tag_dicts` likewise, and the counter
    reads the two tags as skipped; with it (and by default) all three
    tags come, counted as decoded."""
    engine = db.region_engine
    kw = {} if full_key is None else {"full_key": full_key}
    before = key_counts()
    scan = _scan(engine, rid_of(db, "app"), how, ["host", "v"], **kw)
    dicts = set(scan.tag_dicts)
    got = _columns_of(scan, how)
    decoded, skipped = (a - b for a, b in zip(key_counts(), before))
    if full_key is False:
        assert got == {"host", "ts", "v"} and dicts == {"host"}
        assert (decoded, skipped) == (0, 2)
    else:
        assert got == {"host", "dc", "rack", "ts", "v"}
        assert dicts == {"host", "dc", "rack"}
        assert (decoded, skipped) == (2, 0)


def test_a_projection_that_names_every_tag_counts_nothing(db):
    before = key_counts()
    engine = db.region_engine
    for projection in (None, ["host", "dc", "rack", "w"]):
        scan = engine.scan(rid_of(db, "app"), (0, 5 * STEP), projection,
                           full_key=False)
        assert {"host", "dc", "rack"} <= set(scan.materialize().columns)
    assert key_counts() == before


# ---- (4) memtable and two SSTs at once under the narrow projection ----------

@pytest.mark.parametrize("how", ["window", "point", "whole", "last"])
def test_narrow_and_full_scans_hold_the_same_rows(db, how):
    """Rows in the memtable and in two SSTs: the narrow scan's columns
    are, array for array, the full-key scan's."""
    engine = db.region_engine
    rid = rid_of(db, "app")
    # a scan names the tags its predicates and its pruning read
    proj = ["dc", "w"] if how in ("window", "whole") else ["host", "w"]
    narrow = _scan(engine, rid, how, proj, full_key=False).materialize()
    full = _scan(engine, rid, how, proj).materialize()
    assert narrow.num_rows == full.num_rows > 0
    assert narrow.sorted_part_offsets == full.sorted_part_offsets
    assert narrow.stats["ssts"] == 2
    assert set(narrow.columns) < set(full.columns)
    for name, col in narrow.columns.items():
        assert np.array_equal(col, full.columns[name]), name
    assert np.array_equal(narrow.seq, full.seq)
    assert np.array_equal(narrow.op_type, full.op_type)
    for name in narrow.tag_dicts:
        assert np.array_equal(narrow.tag_dicts[name], full.tag_dicts[name])


# ---- (5) cache entries are the projection's own -----------------------------

def test_two_projections_keep_separate_cache_entries(db):
    """Two scans of one region with different projections: each is
    answered from its own snapshot and its own parts, neither serves the
    other's columns, and a scan with the key shares nothing with one
    without."""
    engine = db.region_engine
    region = engine.region(rid_of(db, "app"))
    window = (0, 10 * STEP)  # under half the table's span: not widened

    def part_names():
        with region._lock:
            return {k[2] for k in region._part_cache if k[1] == window}

    a = engine.scan(region.region_id, window, ["host", "v"], full_key=False)
    b = engine.scan(region.region_id, window, ["dc", "w"], full_key=False)
    c = engine.scan(region.region_id, window, ["host", "v"])
    assert set(a.materialize().columns) == {"host", "ts", "v"}
    assert set(b.materialize().columns) == {"dc", "ts", "w"}
    assert set(c.materialize().columns) == {"host", "dc", "rack", "ts", "v"}
    assert len({a.scan_fingerprint, b.scan_fingerprint,
                c.scan_fingerprint}) == 3
    assert {("host", "ts", "v"), ("dc", "ts", "w"),
            ("host", "dc", "rack", "ts", "v")} <= part_names()
    hits = a.stats["cache_hits"]
    again = engine.scan(region.region_id, window, ["host", "v"],
                        full_key=False)
    assert again is a and a.stats["cache_hits"] == hits + 1
    assert engine.scan(region.region_id, window, ["dc", "w"],
                       full_key=False) is b


# ---- (3) every statement shape, against the twin ----------------------------

SHAPES = {
    # TSBS single-groupby-*: a few hosts, a window, a time bucket
    "point_in": "SELECT date_bin(INTERVAL '10 seconds', ts) AS b, "
                "max(v) FROM {t} WHERE host IN ('h03', 'h07') AND "
                "ts >= 4000 AND ts < 30000 GROUP BY b ORDER BY b",
    "point_eq_by_host": "SELECT host, date_bin(INTERVAL '10 seconds', ts) "
                        "AS b, max(v), avg(w) FROM {t} WHERE host = 'h05' "
                        "AND ts >= 1000 AND ts < 39000 GROUP BY host, b "
                        "ORDER BY host, b",
    # TSBS groupby-orderby-limit: a window, no tag at all
    "window_only": "SELECT date_bin(INTERVAL '5 seconds', ts) AS b, "
                   "max(v) FROM {t} WHERE ts < 33000 GROUP BY b "
                   "ORDER BY b DESC LIMIT 5",
    # TSBS double-groupby-1: the whole table by host and bucket
    "whole_table": "SELECT host, date_bin(INTERVAL '20 seconds', ts) AS b, "
                   "avg(v) FROM {t} GROUP BY host, b ORDER BY host, b",
    "whole_no_key": "SELECT count(*), sum(v), min(w), max(w) FROM {t}",
    # range-fleet-by-region: GROUP BY a tag that does not lead the key
    "non_leading_tag": "SELECT dc, date_bin(INTERVAL '10 seconds', ts) AS "
                       "b, avg(v), max(w) FROM {t} WHERE ts >= 2000 AND "
                       "ts < 36000 GROUP BY dc, b ORDER BY dc, b",
    "range_by_tag": "SELECT ts, dc, avg(v) RANGE '10s' FROM {t} WHERE "
                    "ts >= 0 AND ts < 30000 ALIGN '5s' BY (dc) FILL PREV "
                    "ORDER BY dc, ts",
    "range_by_nothing": "SELECT ts, max(v) RANGE '10s', min(w) RANGE '10s' "
                        "FROM {t} ALIGN '10s' BY () ORDER BY ts",
    "range_by_default": "SELECT ts, host, max(v) RANGE '20s' FROM {t} "
                        "WHERE host IN ('h01', 'h02') ALIGN '20s' "
                        "ORDER BY host, ts",
    # TSBS lastpoint and its kin (the boundary first/last reduction)
    "lastpoint": "SELECT host, last_value(v ORDER BY ts), "
                 "last_value(w ORDER BY ts) FROM {t} GROUP BY host "
                 "ORDER BY host",
    "first_last_by_dc": "SELECT dc, first_value(v ORDER BY ts), "
                        "last_value(v ORDER BY ts) FROM {t} GROUP BY dc "
                        "ORDER BY dc",
    "last_of_all": "SELECT last_value(v ORDER BY ts) FROM {t}",
    "last_in_window": "SELECT rack, last_value(w ORDER BY ts) FROM {t} "
                      "WHERE ts < 20000 GROUP BY rack ORDER BY rack",
    # raw rows
    "raw_fields": "SELECT ts, v FROM {t} WHERE dc = 'dc1' AND ts < 3000 "
                  "ORDER BY ts, v",
    "raw_star": "SELECT * FROM {t} WHERE host = 'h02' ORDER BY ts",
    "distinct_tag": "SELECT DISTINCT rack FROM {t} WHERE w > 6 "
                    "ORDER BY rack",
}


def same_rows(qe, sql, a, b):
    got = qe.execute_one(sql.format(t=a))
    want = qe.execute_one(sql.format(t=b))
    assert got.names == want.names
    assert got.num_rows == want.num_rows > 0
    assert got.rows() == want.rows()
    return got


@pytest.mark.parametrize("regions", [1, 2])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_append_table_answers_as_its_twin(db, shape, regions):
    """An append-mode table (narrow scans) and a twin created without
    `append_mode` over the same rows (full key) answer alike: a table of
    one region, and a two-region fan-out."""
    app, lww = ("app", "lww") if regions == 1 else ("app2", "lww2")
    before = key_counts()
    same_rows(db, SHAPES[shape], app, lww)
    if regions == 2 and "GROUP BY" in SHAPES[shape] \
            and "RANGE" not in SHAPES[shape]:
        assert "fanout+" in (db.executor.last_path or "")
    decoded, skipped = (a - b for a, b in zip(key_counts(), before))
    if shape not in ("raw_star", "range_by_default"):  # every tag named
        assert decoded > 0  # the twin's scans carry its key


@pytest.mark.parametrize("shape", ["point_in", "window_only", "whole_table",
                                   "non_leading_tag", "whole_no_key"])
def test_the_streamed_fold_answers_as_its_twin(db, monkeypatch, shape):
    """A low stream threshold sends the append-mode aggregate through
    `scan_stream`: same rows as the twin's materialized path, and no key
    column is decoded for it."""
    monkeypatch.setenv("GREPTIMEDB_TPU_STREAM_THRESHOLD_ROWS", "1")
    before = key_counts()
    db.execute_one(SHAPES[shape].format(t="app"))
    assert "stream" in (db.executor.last_path or "")
    decoded, skipped = (a - b for a, b in zip(key_counts(), before))
    assert decoded == 0
    same_rows(db, SHAPES[shape], "app", "lww")


@pytest.mark.parametrize("shape,skips", [
    ("point_in", 2), ("window_only", 3), ("whole_table", 2),
    ("non_leading_tag", 2), ("range_by_nothing", 3), ("raw_fields", 2),
    ("last_in_window", 2),
    # the boundary first/last reduction cuts runs by the tags it is given
    ("lastpoint", 2), ("first_last_by_dc", 2), ("last_of_all", 3),
    ("raw_star", 0),
])
def test_an_append_statement_skips_the_tags_it_does_not_name(db, shape,
                                                             skips):
    """The `scan` stage span and the counter say how many tag columns a
    statement left unread; nothing is decoded for the key's sake."""
    before = key_counts()
    tree = db.execute_one("EXPLAIN ANALYZE " + SHAPES[shape].format(t="app"))
    decoded, skipped = (a - b for a, b in zip(key_counts(), before))
    scans = [tuple(int(n) for n in m.groups())
             for (line,) in tree.rows() if line.lstrip().startswith("scan:")
             for m in [re.search(r"key_columns_decoded=(\d+) "
                                 r"key_columns_skipped=(\d+)", line)] if m]
    if skips:
        assert decoded == 0 and skipped >= skips and skipped % skips == 0
        assert scans and set(scans) == {(0, skips)}
    else:
        assert skipped == 0
        assert all(s[1] == 0 for s in scans)


def test_the_counter_is_on_the_metrics_page(db):
    db.execute_one(SHAPES["window_only"].format(t="app"))
    db.execute_one(SHAPES["window_only"].format(t="lww"))
    page = REGISTRY.render()
    for kind in ("decoded", "skipped"):
        assert ('greptimedb_tpu_scan_key_columns_total{kind="%s"}' % kind
                in page)


# ---- (2) a last-write-wins table is read as before --------------------------

@pytest.fixture(scope="module")
def lww_db(tmp_path_factory):
    """One overwritten row and one tombstone, over an SST and a
    memtable: h00's instant 5000 is rewritten (v 5 -> 500), h01's
    instant 7000 is deleted."""
    home = tmp_path_factory.mktemp("keycols_lww")
    engine = RegionEngine(EngineConfig(data_dir=str(home / "data"),
                                       maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    create(qe, "t", append_mode=False)
    rows = [(f"h0{h}", "dc0", f"r{h}", float(p), float(h), p * STEP)
            for h in range(2) for p in range(10)]
    insert(qe, "t", rows)
    flush(qe, "t")
    insert(qe, "t", [("h00", "dc0", "r0", 500.0, 0.0, 5000)])
    qe.execute_one("DELETE FROM t WHERE host = 'h01' AND ts = 7000")
    yield qe
    engine.close()


LWW_CASES = {
    "aggregate": (
        "SELECT host, count(*), sum(v), max(v) FROM t GROUP BY host "
        "ORDER BY host",
        [("h00", 10, 540.0, 500.0), ("h01", 9, 38.0, 9.0)]),
    "aggregate_no_tag": (
        "SELECT count(*), sum(v) FROM t WHERE ts >= 5000 AND ts < 8000",
        [(5, 524.0)]),
    "lastpoint": (
        "SELECT host, last_value(v ORDER BY ts) FROM t GROUP BY host "
        "ORDER BY host",
        [("h00", 9.0), ("h01", 9.0)]),
    "last_in_window": (
        "SELECT host, last_value(v ORDER BY ts) FROM t WHERE ts < 8000 "
        "GROUP BY host ORDER BY host",
        [("h00", 7.0), ("h01", 6.0)]),
    "select_star": (
        "SELECT * FROM t WHERE ts >= 5000 AND ts < 8000 ORDER BY host, ts",
        [("h00", "dc0", "r0", 5000, 500.0, 0.0),
         ("h00", "dc0", "r0", 6000, 6.0, 0.0),
         ("h00", "dc0", "r0", 7000, 7.0, 0.0),
         ("h01", "dc0", "r1", 5000, 5.0, 1.0),
         ("h01", "dc0", "r1", 6000, 6.0, 1.0)]),
    "raw_fields": (
        "SELECT ts, v FROM t WHERE host = 'h00' AND ts >= 4000 AND "
        "ts < 7000 ORDER BY ts",
        [(4000, 4.0), (5000, 500.0), (6000, 6.0)]),
}


@pytest.mark.parametrize("flushed", [False, True])
@pytest.mark.parametrize("case", sorted(LWW_CASES))
def test_a_last_write_wins_table_reads_its_full_key(lww_db, case, flushed):
    """The overwrite and the tombstone are honoured exactly as before,
    and every scan of the table carries the whole key: nothing skipped."""
    if flushed:
        flush(lww_db, "t")
    sql, want = LWW_CASES[case]
    before = key_counts()
    got = lww_db.execute_one(sql)
    decoded, skipped = (a - b for a, b in zip(key_counts(), before))
    assert [tuple(r) for r in got.rows()] == want
    assert skipped == 0
    if case != "select_star":
        assert decoded > 0


@pytest.mark.parametrize("projection", [["v"], ["host", "v"], ["dc"]])
def test_a_default_scan_of_the_last_write_wins_region_holds_every_tag(
        lww_db, projection):
    scan = lww_db.region_engine.scan(rid_of(lww_db, "t"), (0, 9000),
                                     projection).materialize()
    assert {"host", "dc", "rack", "ts"} <= set(scan.columns)
    assert set(scan.tag_dicts) == {"host", "dc", "rack"}

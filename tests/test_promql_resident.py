"""Resident series (ISSUE 29): the loaded-series cache is probed from
region metadata before any scan, holds a selector's samples for the
table's whole retained span once the ranges requested at one data
version add up to it, and serves a request's range as a slice of that.

Every answer a slice gives is held against the answer of the same
request on a cold cache (a scan of its own range), on a plain table and
on a metric-engine logical table; what the cache did is read off
`promql_load_cache_events_total`.
"""

from __future__ import annotations

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.promql.engine import PromqlEngine, SeriesMatrix
from greptimedb_tpu.promql.loaded import LoadedSeries, SeriesCache
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.storage.region import scan_io_counters
from greptimedb_tpu.utils.metrics import (
    METRIC_ENGINE_ROWS,
    PROMQL_LOAD_CACHE_EVENTS,
    STAGE_SECONDS,
)

T0 = 1_700_000_000  # seconds; one sample per series every 15 s
STEP = 15
POINTS = 240        # one hour retained
INSTANCES = ("n0", "n1", "n2")
MODES = ("user", "system")
TABLE = "node_cpu"
#: trailing 15 min ranges; with the 5 min window a request covers a
#: third of the hour, so the third one at a version loads the span
ENDS = [T0 + 1500, T0 + 2100, T0 + 2715, T0 + 3300, T0 + 3585]
EVENTS = ("hit", "miss", "promote", "ineligible")

QUERIES = {
    "rate": f"rate({TABLE}[5m])",
    "increase": f"sum by (mode) (increase({TABLE}[5m]))",
    "avg_over_time": f"avg by (instance) (avg_over_time({TABLE}[5m]))",
    "sum_over_time": f"sum_over_time({TABLE}[5m])",
    "offset": f"rate({TABLE}[5m] offset 10m)",
    "label": f'rate({TABLE}{{mode="user"}}[5m])',
    "regex": f'sum_over_time({TABLE}{{instance=~"n[02]"}}[5m])',
    # the flat kernels over a slice of the resident matrix
    "max_over_time": f"max_over_time({TABLE}[5m])",
    "instant": TABLE,
}


def _counters(seed: int = 29) -> np.ndarray:
    """[POINTS, series] counters that rise by uniform(0, 50) a sample
    and reset to a small value now and then."""
    rng = np.random.default_rng(seed)
    n = len(INSTANCES) * len(MODES)
    vals = np.cumsum(rng.uniform(0.0, 50.0, (POINTS, n)), axis=0)
    for s in range(n):
        for at in rng.choice(np.arange(20, POINTS - 5), 2, replace=False):
            vals[at:, s] -= vals[at, s] - rng.uniform(0.0, 5.0)
    return vals


def _insert(qe, field: str, rows) -> None:
    qe.execute_one(
        f"INSERT INTO {TABLE} (instance, mode, ts, {field}) VALUES "
        + ", ".join(f"('{i}', '{m}', {ts * 1000}, {v!r})"
                    for i, m, ts, v in rows))


def _rows(vals: np.ndarray, points=range(POINTS)) -> list:
    series = [(i, m) for i in INSTANCES for m in MODES]
    return [(i, m, T0 + p * STEP, float(vals[p, s]))
            for s, (i, m) in enumerate(series) for p in points]


class _Db:
    def __init__(self, tmp_path, kind: str, append: bool = True):
        self.engine = RegionEngine(EngineConfig(
            data_dir=str(tmp_path / "data"), maintenance_workers=0))
        self.qe = QueryEngine(Catalog(MemoryKv()), self.engine)
        self.field = "greptime_value" if kind == "metric" else "val"
        tail = "ENGINE=metric" if kind == "metric" else (
            "WITH (append_mode = 'true')" if append else "")
        self.qe.execute_one(
            f"CREATE TABLE {TABLE} (instance STRING, mode STRING, "
            f"ts TIMESTAMP(3) TIME INDEX, {self.field} DOUBLE, "
            f"PRIMARY KEY (instance, mode)) {tail}")
        self.prom = PromqlEngine(self.qe)

    def insert(self, rows) -> None:
        _insert(self.qe, self.field, rows)

    def flush(self) -> None:
        self.engine.flush(
            self.qe.catalog.table("public", TABLE).region_ids[0])

    @property
    def cache(self) -> SeriesCache:
        return self.qe.executor._promql_series

    def cold(self) -> None:
        """Forget every loaded series: the next request scans."""
        self.qe.executor.__dict__.pop("_promql_series", None)

    def ask(self, query: str, end: int, step: float = 15.0) -> tuple:
        """(answer as the wire gives it, the cache's event): series
        with a value at some step, by label set."""
        before = _events()
        _, sm = self.prom.eval_matrix(query, end - 900, end, step)
        after = _events()
        moved = [e for e in EVENTS if after[e] != before[e]]
        assert len(moved) == 1 and after[moved[0]] == before[moved[0]] + 1
        assert isinstance(sm, SeriesMatrix)
        vals = np.asarray(sm.values)
        return ({tuple(sorted(lab.items())): vals[i]
                 for i, lab in enumerate(sm.labels)
                 if not np.isnan(vals[i]).all()}, moved[0])

    def close(self) -> None:
        self.engine.close()


def _events() -> dict:
    return {e: PROMQL_LOAD_CACHE_EVENTS.total(event=e) for e in EVENTS}


def _same(got: dict, want: dict) -> None:
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-12, atol=0,
                                   equal_nan=True)


@pytest.fixture(params=["plain", "metric"])
def db(request, tmp_path):
    d = _Db(tmp_path, request.param)
    d.insert(_rows(_counters()))
    d.flush()
    yield d
    d.close()


# ---- (a) a slice of the resident span answers as a scan of the range ----


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_a_slice_answers_as_a_scan_of_its_own_range(db, name):
    q = QUERIES[name]
    want = {}
    for end in ENDS:
        db.cold()
        want[end], event = db.ask(q, end)
        assert event == "miss" and want[end]
    db.cold()
    seen = [db.ask(q, end)[1] for end in ENDS]
    # (an offset's first ranges lie partly before the data: they pay
    # for less of the span, and the promotion comes a request later)
    at = seen.index("promote")
    assert at in (2, 3) and seen == ["miss"] * at + ["promote"] \
        + ["hit"] * (4 - at)
    for end in ENDS:
        got, event = db.ask(q, end)
        assert event == "hit"
        _same(got, want[end])


def test_a_repeated_range_is_a_hit_before_any_promotion(db):
    q = f"changes({TABLE}[5m])"
    first, event = db.ask(q, ENDS[0])
    assert event == "miss"
    again, event = db.ask(q, ENDS[0])
    assert event == "hit"
    _same(again, first)
    # an indicator channel depends on where its load began: such a
    # selector serves its own range only, however often it is asked
    assert [db.ask(q, end)[1] for end in ENDS[1:]] == ["miss"] * 4


def test_a_range_that_covers_the_region_is_the_whole_span_at_once(db):
    q = QUERIES["rate"]
    _, sm = db.prom.eval_matrix(q, T0, T0 + 3600, 60.0)
    assert len(sm.labels) == 6
    assert db.cache._bytes > 0
    assert [db.ask(q, end)[1] for end in ENDS] == ["hit"] * 5


def test_a_range_over_half_the_span_loads_the_whole_span_at_once(db):
    # a trailing 30 min range with its 5 min window is over half of the
    # hour: the region reads all it holds for it, so the first request
    # at a version is the promotion, and a second load is never made
    q = QUERIES["rate"]
    want = {}
    for end in (T0 + 2400, T0 + 3585):
        db.cold()
        _, sm = db.prom.eval_matrix(q, end - 900, end, 15.0)
        want[end] = np.asarray(sm.values)
    db.cold()
    before = _events()
    _, sm = db.prom.eval_matrix(q, T0 + 3585 - 1800, T0 + 3585, 15.0)
    assert len(sm.labels) == 6
    after = _events()
    assert {e: after[e] - before[e] for e in EVENTS} == {
        "hit": 0, "miss": 0, "promote": 1, "ineligible": 0}
    for end in want:
        before = _events()
        _, sm = db.prom.eval_matrix(q, end - 900, end, 15.0)
        assert _events()["hit"] == before["hit"] + 1
        np.testing.assert_allclose(np.asarray(sm.values), want[end],
                                   rtol=1e-12, atol=0, equal_nan=True)


# ---- (b) a series outside the request's range -----------------------------


def test_a_series_only_outside_the_range_is_absent(db):
    # a seventh series scraped for the first ten minutes only
    db.insert([("ghost", "user", T0 + p * STEP, float(p)) for p in range(40)])
    db.flush()
    q = QUERIES["rate"]
    ghost = (("instance", "ghost"), ("mode", "user"))
    events = []
    for end in ENDS[1:] + ENDS[1:]:
        got, event = db.ask(q, end)
        events.append(event)
        assert len(got) == 6 and ghost not in got
    # its whole span has no complete grid: the load that found that
    # out is dropped, the key stays on its own ranges
    assert "promote" not in events and "hit" not in events
    assert events.count("ineligible") >= 5
    early, _ = db.ask(q, T0 + 900)
    assert ghost in early


# ---- (c) writes ------------------------------------------------------------


def test_a_write_is_seen_and_drops_the_older_versions_entries(db):
    q = QUERIES["sum_over_time"]
    for end in ENDS[:3]:
        db.ask(q, end)
    before, event = db.ask(q, ENDS[-1])
    assert event == "hit" and len(db.cache._lru) == 1
    held = db.cache._bytes
    # one more scrape of every series, acknowledged before the request
    nxt = T0 + POINTS * STEP
    db.insert([(i, m, nxt, 1000.0) for i in INSTANCES for m in MODES])
    after, event = db.ask(q, nxt)
    assert event == "miss"
    key = (("instance", "n0"), ("mode", "user"))
    assert after[key][-1] == pytest.approx(before[key][-1] + 1000.0
                                           - _counters()[POINTS - 20, 0])
    # the older version's resident span went with the version
    assert len(db.cache._lru) == 1 and db.cache._bytes < held
    slot, = db.cache._slots.values()
    assert slot.whole is None and slot.ranged is not None


def test_a_duplicate_and_a_tombstone_answer_as_before(tmp_path):
    db = _Db(tmp_path, "plain", append=False)
    try:
        vals = _counters()
        db.insert(_rows(vals))
        db.flush()
        # n0/user's sample 200 rewritten, n1/system's sample 210 deleted
        db.insert([("n0", "user", T0 + 200 * STEP, 7.25)])
        db.qe.execute_one(
            f"DELETE FROM {TABLE} WHERE instance = 'n1' AND mode = 'system' "
            f"AND ts = {(T0 + 210 * STEP) * 1000}")
        db.flush()
        vals[200, 0] = 7.25
        vals[210, 3] = 0.0  # not in the sum below
        q = QUERIES["sum_over_time"]
        events = []
        for end in (T0 + 3300, T0 + 3300, T0 + 3585, T0 + 3450, T0 + 3585):
            got, event = db.ask(q, end)
            events.append(event)
            last = (end - T0) // STEP
            for s, key in enumerate(
                    (("instance", i), ("mode", m))
                    for i in INSTANCES for m in MODES):
                np.testing.assert_allclose(
                    got[key][-1], vals[last - 19:last + 1, s].sum(),
                    rtol=1e-12)
        # the tombstone rides as NaN: no complete grid, no promotion
        assert events == ["miss", "hit", "miss", "ineligible", "ineligible"]
    finally:
        db.close()


# ---- (d) where it does not engage -----------------------------------------


def test_no_promotion_while_the_version_moves_between_requests(db):
    q = QUERIES["rate"]
    events = []
    for n, end in enumerate(ENDS + ENDS):
        events.append(db.ask(q, end)[1])
        db.insert([("n0", "user", T0 + (POINTS + n) * STEP, 1e6 + n)])
    assert events == ["miss"] * 10
    assert len(db.cache._lru) == 1  # the last request's own range


def test_no_promotion_over_the_byte_budget(db):
    q = QUERIES["rate"]
    db.ask(q, ENDS[0])
    # one request's range fits, three times it does not
    db.cache.budget = 2 * db.cache._bytes
    events = [db.ask(q, end)[1] for end in ENDS[1:] + ENDS]
    assert events[0] == "miss" and set(events[1:]) == {"ineligible"}
    assert db.cache._bytes <= db.cache.budget


def test_entries_are_evicted_by_bytes_oldest_first():
    def series(n):
        z = np.zeros(n)
        return LoadedSeries([{}], z.astype(np.int32), z, z[:, None],
                            span=(0, n))

    cache = SeriesCache(budget=series(100).nbytes * 2 + 8)
    for k in ("a", "b", "c"):
        cache.store((k,), (1, 1), series(100), requested=100)
    assert [key for key, _ in cache._lru] == [("b",), ("c",)]
    assert cache._bytes == 2 * series(100).nbytes
    assert cache.probe(("b",), (1, 1, (0, 999)), 0, 100, True)[0] == "hit"
    cache.store(("d",), (1, 1), series(100), requested=100)
    assert [key for key, _ in cache._lru] == [("b",), ("d",)]
    # an entry the whole budget cannot hold is not kept
    cache.store(("e",), (1, 1), series(1000), requested=1000)
    assert ("e",) not in [key for key, _ in cache._lru]
    # a request that read the region before a write others have seen
    cache.store(("b",), (1, 2), series(100), requested=100)
    assert cache.probe(("b",), (1, 1, (0, 999)), 0, 100, True) \
        == ("miss", None)


# ---- (e) a hit scans nothing and still observes its stage ----------------


def test_a_hit_scans_no_region_and_observes_stage_scan(db, monkeypatch):
    q = QUERIES["rate"]
    for end in ENDS[:3]:
        db.ask(q, end)
    scans = []
    scan = db.engine.scan
    monkeypatch.setattr(
        db.engine, "scan",
        lambda *a, **kw: scans.append(a) or scan(*a, **kw))
    rows0 = (METRIC_ENGINE_ROWS.total(kind="physical_decoded"),
             METRIC_ENGINE_ROWS.total(kind="logical_returned"))
    io0 = scan_io_counters()
    staged = STAGE_SECONDS.count(stage="scan")
    from greptimedb_tpu.utils import tracing

    with tracing.request_span("test:resident"):
        tid = tracing.current_trace_id()
        assert db.ask(q, ENDS[3])[1] == "hit"
    assert scans == [] and scan_io_counters() == io0
    assert (METRIC_ENGINE_ROWS.total(kind="physical_decoded"),
            METRIC_ENGINE_ROWS.total(kind="logical_returned")) == rows0
    assert STAGE_SECONDS.count(stage="scan") == staged + 1
    span, = [s for s in tracing.spans_for(tid) if s.name == "promql_scan"]
    assert span.attrs["resident"] == "hit"
    stage, = [s for s in tracing.spans_for(tid) if s.name == "scan"]
    assert stage.attrs["resident"] == "hit"


# ---- request threads share the cache ---------------------------------------


def test_concurrent_requests_promote_once_and_answer_alike(db):
    import sys
    import threading

    q = QUERIES["rate"]
    want = {}
    for end in ENDS:
        db.cold()
        want[end] = db.ask(q, end)[0]
    db.cold()
    before = _events()
    errors: list = []

    def client(c: int) -> None:
        try:
            for k in range(6):
                end = ENDS[(c + k) % len(ENDS)]
                _, sm = db.prom.eval_matrix(q, end - 900, end, 15.0)
                vals = np.asarray(sm.values)
                _same({tuple(sorted(lab.items())): vals[i]
                       for i, lab in enumerate(sm.labels)}, want[end])
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:3]
    moved = {e: n - before[e] for e, n in _events().items()}
    # one load of the whole span, whoever asked while it ran scanned
    # its own range, and every load was counted once
    assert moved["promote"] == 1 and moved["ineligible"] == 0
    assert sum(moved.values()) == 16 * 6 and moved["hit"] > 0
    cache = db.cache
    slot, = cache._slots.values()
    assert slot.whole is not None and slot.ranged is None \
        and not slot.promoting
    assert cache._bytes == sum(n for _, n in cache._lru.values()) \
        == slot.whole.nbytes

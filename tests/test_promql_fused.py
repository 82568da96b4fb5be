"""A PromQL panel is one device program (ISSUE 41): `agg by (...)
(range_fn(m[w]))` over a resident complete-grid pivot runs from the pivot
to its [groups, steps] answer in one jitted dispatch.

Every fused answer is held to the same bits as the stepwise composition
(a kernel at a time with eager operations between: what samples without
a complete grid still take) and to a plain numpy reference of
Prometheus' rules; what the evaluation did is read off
`promql_eval_programs_total{path}`, the compile counter, the transfer
counter and a profile of the CPU backend.
"""

from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.promql.engine import EvalParams, PromqlEngine
from greptimedb_tpu.promql.parser import parse_promql
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.query import physical  # noqa: F401 — wires telemetry
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils.metrics import (
    DEVICE_TRANSFER_BYTES,
    PROMQL_EVAL_PROGRAMS,
    XLA_COMPILES,
)

T0 = 1_700_000_000  # seconds; one sample per series every 15 s
STEP = 15
POINTS = 400  # 100 minutes retained
END = T0 + (POINTS - 1) * STEP
INSTANCES = tuple(f"n{i}" for i in range(5))
MODES = ("user", "system", "idle", "iowait")
PATHS = ("fused", "split", "stepwise")

#: table -> its tag columns
TABLES = {
    "node_cpu_seconds_total": ("instance", "mode"),
    "node_filesystem_avail_bytes": ("instance", "mountpoint"),
    "node_load1": ("instance",),
    "gappy": ("instance", "mode"),
    "lww": ("instance", "mode"),
}


def _series(table: str) -> list:
    second = {"mode": MODES, "mountpoint": ("/", "/data")}
    tags = TABLES[table]
    if len(tags) == 1:
        return [(i,) for i in INSTANCES]
    return [(i, x) for i in INSTANCES for x in second[tags[1]]]


def _values(table: str) -> np.ndarray:
    """[POINTS, series]: counters that rise by uniform(0, 3) a sample,
    reset to a small value twice, and — the first series — start at 0:
    its first windows are cut at the counter's zero crossing. The two
    gauge tables wander around a level."""
    n = len(_series(table))
    rng = np.random.default_rng(len(table))
    if table in ("node_filesystem_avail_bytes", "node_load1"):
        return 50.0 + np.cumsum(rng.normal(0.0, 1.0, (POINTS, n)), axis=0)
    vals = np.cumsum(rng.uniform(0.0, 3.0, (POINTS, n)), axis=0)
    vals[:, 0] -= vals[0, 0]
    for s in range(1, n):
        for at in sorted(rng.choice(np.arange(20, POINTS - 5), 2,
                                    replace=False)):
            vals[at:, s] -= vals[at, s] - rng.uniform(0.0, 2.0)
    return vals


#: (table, series index) -> points the table does not hold
GAP = {"gappy": (3, (200, 201)), "lww": (5, (210,))}


class _Db:
    def __init__(self, path):
        self.eng = RegionEngine(EngineConfig(data_dir=str(path)))
        self.qe = QueryEngine(Catalog(MemoryKv()), self.eng)
        self.vals = {}
        for table, tags in TABLES.items():
            append = "false" if table == "lww" else "true"
            self.qe.execute_one(
                f"CREATE TABLE {table} ("
                + ", ".join(f"{t} STRING" for t in tags)
                + ", ts TIMESTAMP(3) NOT NULL, val DOUBLE, TIME INDEX (ts), "
                f"PRIMARY KEY ({', '.join(tags)})) "
                f"WITH (append_mode = '{append}')")
            vals = self.vals[table] = _values(table)
            rows = []
            for s, key in enumerate(_series(table)):
                skip = GAP[table][1] if table == "gappy" \
                    and GAP[table][0] == s else ()
                rows += ["(" + ", ".join(f"'{k}'" for k in key)
                         + f", {(T0 + i * STEP) * 1000}, {float(vals[i, s])!r})"
                         for i in range(POINTS) if i not in skip]
            for a in range(0, len(rows), 4000):
                self.qe.execute_one(
                    f"INSERT INTO {table} ({', '.join(tags)}, ts, val) "
                    "VALUES " + ", ".join(rows[a:a + 4000]))
        s, (at,) = GAP["lww"]
        inst, mode = _series("lww")[s]
        self.qe.execute_one(
            f"DELETE FROM lww WHERE instance = '{inst}' AND mode = '{mode}' "
            f"AND ts = {(T0 + at * STEP) * 1000}")
        self.prom = PromqlEngine(self.qe)

    def params(self, start: float, end: float, step: float) -> EvalParams:
        n = int(np.floor((end - start) / step)) + 1
        return EvalParams(start, end, step, start + np.arange(n) * step)

    def fused(self, q: str, start, end, step) -> tuple:
        """(labels, values, the paths the evaluation counted)."""
        before = _paths()
        _times, m = self.prom.eval_matrix(q, start, end, step)
        return list(m.labels), np.asarray(m.values), _moved(before)

    def stepwise(self, q: str, start, end, step) -> tuple:
        node = parse_promql(q)
        m = self.prom._eval_aggregate(node, self.params(start, end, step),
                                      None, fuse=False)
        return list(m.labels), np.asarray(m.values)


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    d = _Db(tmp_path_factory.mktemp("fused"))
    try:
        yield d
    finally:
        d.eng.close()


def _paths() -> dict:
    return {k: PROMQL_EVAL_PROGRAMS.get(path=k) for k in PATHS}


def _moved(before: dict) -> dict:
    now = _paths()
    return {k: int(now[k] - before[k]) for k in PATHS if now[k] != before[k]}


def _same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and a.dtype == b.dtype \
        and a.tobytes() == b.tobytes()


# ---- the plain reference: Prometheus' rules, a window at a time -----------


def _range_fn(fn: str, ts: np.ndarray, v: np.ndarray, t: float,
              w: float) -> float:
    """`fn` over the samples of one series in (t - w, t]."""
    sel = (ts > t - w) & (ts <= t)
    ts, v = ts[sel], v[sel]
    if fn == "count_over_time":
        return float(len(v)) if len(v) else np.nan
    if fn in ("sum_over_time", "avg_over_time"):
        if not len(v):
            return np.nan
        return v.sum() if fn == "sum_over_time" else v.sum() / len(v)
    if len(v) < 2:
        return np.nan
    counter = fn in ("rate", "increase")
    delta = v[-1] - v[0]
    if counter:
        drops = np.diff(v) < 0
        delta += v[:-1][drops].sum()
    sampled = ts[-1] - ts[0]
    interval = sampled / (len(v) - 1)
    to_start, to_end = ts[0] - (t - w), t - ts[-1]
    if counter and delta > 0 and v[0] >= 0:
        to_start = min(to_start, sampled * (v[0] / delta))
    limit = interval * 1.1
    ext = sampled + (to_start if to_start < limit else interval / 2) \
        + (to_end if to_end < limit else interval / 2)
    out = delta * ext / sampled
    return out / w if fn == "rate" else out


def _fold(op: str, col: np.ndarray) -> float:
    col = col[~np.isnan(col)]
    if not len(col):
        return np.nan
    return {"sum": col.sum, "avg": col.mean, "min": col.min,
            "max": col.max, "count": lambda: float(len(col)),
            "group": lambda: 1.0, "stddev": col.std, "stdvar": col.var}[op]()


def _reference(db: _Db, case: dict, start, end, step) -> tuple:
    """(group label sets in signature order, [G, T] values)."""
    table, tags = case["table"], TABLES[case["table"]]
    series = _series(table)
    vals = db.vals[table]
    ts_all = T0 + np.arange(POINTS) * STEP
    times = db.params(start, end, step).times
    keep = [s for s, key in enumerate(series)
            if all(dict(zip(tags, key))[k] == x
                   for k, x in case.get("match", {}).items())]
    per = np.full((len(keep), len(times)), np.nan)
    for row, s in enumerate(keep):
        held = np.ones(POINTS, bool)
        if table in GAP and GAP[table][0] == s:
            held[list(GAP[table][1])] = False
        for j, t in enumerate(times):
            per[row, j] = _range_fn(case["fn"], ts_all[held], vals[held, s],
                                    t, case["w"])
    groups: dict = {}
    for row, s in enumerate(keep):
        lab = dict(zip(tags, series[s]))
        if case.get("by"):
            sig = {k: lab[k] for k in case["by"]}
        elif case.get("without"):
            sig = {k: x for k, x in lab.items() if k not in case["without"]}
        else:
            sig = {}
        groups.setdefault(tuple(sorted(sig.items())), []).append(row)
    sigs = sorted(groups)
    out = np.array([[_fold(case["agg"], per[groups[sig], j])
                     for j in range(len(times))] for sig in sigs])
    return [dict(sig) for sig in sigs], out.reshape(len(sigs), len(times))


def _query(case: dict) -> str:
    sel = case["table"]
    if case.get("match"):
        sel += "{" + ",".join(f'{k}="{x}"'
                              for k, x in sorted(case["match"].items())) + "}"
    inner = f"{case['fn']}({sel}[{case['w']}s])"
    if case.get("by"):
        return f"{case['agg']} by ({', '.join(case['by'])}) ({inner})"
    if case.get("without"):
        return f"{case['agg']} without ({', '.join(case['without'])}) ({inner})"
    return f"{case['agg']} ({inner})"


CPU, FS, LOAD = ("node_cpu_seconds_total", "node_filesystem_avail_bytes",
                 "node_load1")


def _case(id_, table, fn, agg, w=300, step=15, span=3600, end=END, **kw):
    return pytest.param(dict(table=table, fn=fn, agg=agg, w=w, step=step,
                             span=span, end=end, **kw), id=id_)


CASES = [
    # prom-board's four panels (benchmark/traffic/prom-board.json)
    _case("board-sum-rate", CPU, "rate", "sum"),
    _case("board-sum-rate-by-mode", CPU, "rate", "sum", by=("mode",)),
    _case("board-sum-rate-user-by-instance", CPU, "rate", "sum", step=60,
          by=("instance",), match={"mode": "user"}),
    _case("board-avg-avg-over-time-by-instance", CPU, "avg_over_time", "avg",
          step=60, by=("instance",)),
    # prom-fleet-board's four: trailing 15 min, three tables
    _case("fleet-cpu-by-mode", CPU, "rate", "sum", span=900, by=("mode",)),
    _case("fleet-cpu-system-by-instance", CPU, "rate", "sum", step=60,
          span=900, by=("instance",), match={"mode": "system"}),
    _case("fleet-fs-avail-by-instance", FS, "avg_over_time", "avg", step=60,
          span=900, by=("instance",)),
    _case("fleet-load1", LOAD, "avg_over_time", "avg", span=900),
    # the other functions and operators of the fused sets
    _case("increase", CPU, "increase", "sum", by=("mode",)),
    _case("delta", FS, "delta", "avg", by=("mountpoint",)),
    _case("sum-over-time", FS, "sum_over_time", "sum", by=("instance",)),
    _case("count-over-time", LOAD, "count_over_time", "sum"),
    _case("min", CPU, "rate", "min", by=("mode",)),
    _case("max", CPU, "rate", "max", by=("instance",)),
    _case("count", CPU, "rate", "count", by=("mode",)),
    _case("group", CPU, "rate", "group", by=("mode",)),
    _case("stddev", CPU, "rate", "stddev", by=("mode",)),
    _case("stdvar-avg-over-time", FS, "avg_over_time", "stdvar",
          by=("mountpoint",)),
    _case("without", CPU, "rate", "avg", without=("instance",)),
    _case("no-grouping", FS, "sum_over_time", "max"),
    # windows: the last ones past the table's end hold no sample; each
    # holds exactly one (a rate needs two: NaN; a sum is that sample);
    # the first minutes of the table, where windows fill up and the
    # first series' counter stands at its zero crossing
    _case("empty-window", CPU, "rate", "sum", by=("mode",), span=1200,
          end=END + 900),
    _case("empty-window-avg-over-time", LOAD, "avg_over_time", "avg",
          span=1200, end=END + 900),
    _case("one-sample-window-rate", CPU, "rate", "sum", w=15,
          by=("mode",), span=600),
    _case("one-sample-window-sum", LOAD, "sum_over_time", "sum", w=15,
          span=600),
    _case("zero-crossing", CPU, "rate", "sum", by=("instance",), span=600,
          end=T0 + 600),
    _case("zero-crossing-increase", CPU, "increase", "max", span=600,
          end=T0 + 600),
    # an instant query
    _case("one-step-rate", CPU, "rate", "sum", by=("mode",), step=300,
          span=0),
    _case("one-step-avg-over-time", LOAD, "avg_over_time", "avg", step=300,
          span=0, end=END - 45),
]


@pytest.mark.parametrize("case", CASES)
def test_the_fused_answer_is_the_stepwise_one_to_the_bit(db, case):
    q = _query(case)
    end, step = case["end"], case["step"]
    start = end - case["span"]
    # the selector's whole span becomes resident at the first wide
    # request; every range after it is a cut of that pivot
    db.prom.eval_matrix(q, END - 3600, END, step)
    labels, got, paths = db.fused(q, start, end, step)
    assert paths == {"fused": 1}
    want_labels, want = db.stepwise(q, start, end, step)
    assert labels == want_labels
    assert _same_bits(got, want)
    ref_labels, ref = _reference(db, case, start, end, step)
    assert labels == ref_labels
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-12,
                               equal_nan=True)
    if case["span"] and end <= END:
        # an own-range cut of a longer resident span, and a start moved
        # by a step: the same executable
        node = parse_promql(q)
        plan = db.prom._grid_plan(node.expr, db.params(start, end, step),
                                  None)
        assert plan.pivot is not None and plan.loaded.cut is not None
        assert plan.loaded.cut[1] < int(plan.pivot[0].shape[0])
        compiled = XLA_COMPILES.total()
        _l, moved, paths = db.fused(q, start - step, end - step, step)
        assert paths == {"fused": 1}
        assert XLA_COMPILES.total() == compiled
        assert _same_bits(moved, db.stepwise(q, start - step, end - step,
                                             step)[1])


# ---- what does not fuse answers as before, and says so --------------------


FALLBACKS = [
    pytest.param(f"sum by (mode) (rate({CPU}[5m:15s]))", "stepwise",
                 id="subquery"),
    pytest.param("sum by (mode) (rate(gappy[5m]))", "stepwise", id="gap"),
    pytest.param("sum by (mode) (rate(lww[5m]))", "stepwise",
                 id="nan-tombstone"),
    pytest.param("avg by (instance) (avg_over_time(gappy[5m]))", "stepwise",
                 id="gap-avg-over-time"),
    pytest.param(f"sum by (mode) (irate({CPU}[5m]))", "stepwise",
                 id="irate"),
    pytest.param(f"sum by (mode) (max_over_time({CPU}[5m]))", "stepwise",
                 id="max-over-time"),
    pytest.param(f"topk(2, rate({CPU}[5m]))", "stepwise", id="topk"),
    pytest.param(f"quantile(0.5, rate({CPU}[5m]))", "stepwise",
                 id="quantile"),
    pytest.param(f"sum by (mode) (rate({CPU}[5m] @ {END - 600}))",
                 "stepwise", id="at-modifier"),
    pytest.param(f"sum by (mode) (rate({CPU}[5m]) * 2)", "split",
                 id="binary-operand"),
    pytest.param(f"avg by (instance) ({LOAD})", "split",
                 id="instant-selector"),
]


@pytest.mark.parametrize("q, path", FALLBACKS)
def test_a_fall_back_answers_as_before_and_is_counted(db, q, path):
    start, end, step = END - 1800, END, 15
    labels, got, paths = db.fused(q, start, end, step)
    assert paths == {path: 1}
    want_labels, want = db.stepwise(q, start, end, step)
    assert labels == want_labels
    assert _same_bits(got, want)
    assert got.shape[0] > 0 and not np.isnan(got).all()


@pytest.mark.parametrize("table", ["gappy", "lww"])
def test_samples_without_a_complete_grid_keep_window_stats(db, table):
    """A gap, or a NaN tombstone: no pivot, so window_stats answers —
    by Prometheus' rules over the samples the table holds."""
    case = dict(table=table, fn="rate", agg="sum", w=300, by=("mode",))
    start, end, step = END - 3600, END, 15
    labels, got, paths = db.fused(_query(case), start, end, step)
    assert paths == {"stepwise": 1}
    node = parse_promql(_query(case))
    plan = db.prom._grid_plan(node.expr, db.params(start, end, step), None)
    assert plan.pivot is None
    ref_labels, ref = _reference(db, case, start, end, step)
    assert labels == ref_labels
    np.testing.assert_allclose(got, ref, rtol=1e-9, equal_nan=True)


def test_the_range_function_alone_is_one_program_too(db):
    """Not aggregated, a `_GRID_FUNCS` call over a pivot is its pure
    half under its own jit: the same bits as a kernel at a time, and no
    aggregation counted."""
    for q in (f"rate({CPU}[5m])", f"avg_over_time({LOAD}[5m])",
              f"count_over_time({FS}[1m])"):
        before = _paths()
        _t, m = db.prom.eval_matrix(q, END - 900, END, 15)
        assert _moved(before) == {}
        step = db.prom._eval_range_func(
            parse_promql(q), db.params(END - 900, END, 15), None, fuse=False)
        assert list(m.labels) == list(step.labels)
        assert _same_bits(np.asarray(m.values), np.asarray(step.values))


# ---- a warm panel: one program, no copy -----------------------------------


def _programs_launched(tmp_path, run) -> int:
    """Executables the CPU backend ran while `run` did, read off a
    profile of it."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1
    pd = jax.profiler.ProfileData.from_file(found[0])
    return sum(ev.name == "PjRtCpuExecutable::Execute"
               for plane in pd.planes if plane.name == "/host:CPU"
               for ln in plane.lines for ev in ln.events)


def test_a_warm_panel_launches_one_program_and_copies_nothing(db, tmp_path):
    q = f"sum by (mode) (rate({CPU}[5m]))"
    for k in (0, 1):  # resident, compiled, the group index kept
        db.prom.eval_matrix(q, END - 3600 - k * STEP, END - k * STEP, STEP)
    box = {}

    def warm(start, end):
        _t, m = db.prom.eval_matrix(q, start, end, STEP)
        box["values"] = np.asarray(m.values)

    h2d = DEVICE_TRANSFER_BYTES.total(direction="h2d")
    before = _paths()
    n = _programs_launched(tmp_path / "fused",
                           lambda: warm(END - 3600 - 2 * STEP, END - 2 * STEP))
    assert n <= 1
    assert _moved(before) == {"fused": 1}
    # no step times, no group index, no mask went to the device
    assert DEVICE_TRANSFER_BYTES.total(direction="h2d") == h2d
    assert box["values"].shape == (len(MODES), 241)
    # the kernel at a time: well over a dozen
    node = parse_promql(q)
    p = db.params(END - 3600 - 3 * STEP, END - 3 * STEP, STEP)
    db.prom._eval_aggregate(node, p, None, fuse=False)
    n_step = _programs_launched(
        tmp_path / "stepwise",
        lambda: np.asarray(db.prom._eval_aggregate(node, p, None,
                                                   fuse=False).values))
    assert n_step >= 12


def test_the_programs_carry_their_names():
    from greptimedb_tpu.utils import device_telemetry

    assert {"promql_rate_agg", "promql_over_time_agg", "promql_rate",
            "promql_over_time", "promql_agg"} \
        <= device_telemetry.KERNEL_NAMES

"""RANGE ... ALIGN execution — time-windowed aggregation with overlap.

Mirrors reference src/query/src/range_select/plan.rs semantics
(plan.rs:1049-1070): an output point at aligned timestamp T aggregates
rows with `T <= ts < T + range`, output points step every ALIGN interval,
series are keyed by the BY columns (default: the table's primary-key
tags). `RANGE` may exceed `ALIGN` (overlapping sliding windows).

Every aggregate the grammar admits is made of primitives that combine
across adjacent ALIGN buckets (sum, count, min, max, first / last by
time, sum of squares). So a range statement is planned as

1. the tumbling aggregate `GROUP BY <BY keys>, date_bin(ALIGN, ts)` over
   the statement's WHERE, one primitive an output column — an ordinary
   lp.Aggregate, executed by whatever path the executor gives any
   GROUP BY (plan cache, literal operands, tier router, partial cache,
   region fan-out, the memtable tail), under
2. an lp.RangeCombine root: `range_combine` slides over each series'
   buckets (a window of S = RANGE / ALIGN adjacent ones per distinct
   RANGE), finalizes, applies FILL and hands the projection, ORDER BY
   and LIMIT to the executor's post-processing.

`range_combine` is numpy on the host over the observed groups the
aggregate read back (thousands of cells, never rows); it keeps them
sparse — sorted (series, bucket) codes, windows found by searchsorted,
runs reduced with ufunc.reduceat — so a group space of any size
answers, and only FILL builds the dense grid its semantics name.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from greptimedb_tpu.catalog.catalog import TableInfo
from greptimedb_tpu.query import logical as lp
from greptimedb_tpu.query.expr import (
    PlanError,
    collect_aggregates,
    collect_columns,
    extract_ts_bounds,
    _interval_in_col_unit,
)
from greptimedb_tpu.sql import ast


@dataclass
class RangeAgg:
    func: str               # canonical primitive-decomposable aggregate
    arg: Optional[ast.Expr]
    key: ast.Expr           # unique marker node — the env key: the same
    #                         FuncCall may appear with different RANGEs
    range_steps: int        # window width, in align steps (>= 1)
    fill: Optional[object]  # None | 'null' | 'prev' | 'linear' | float
    slot: Optional[int] = None  # its argument's place among the planes


@dataclass
class RangePlan:
    """What `range_combine` runs over the lowered aggregate's result,
    whose columns are the BY `keys`, the bucket, then one primitive
    each in `prims` order ((op, argument slot) pairs)."""
    table: TableInfo
    align_step: int         # in ts-column units
    origin: int             # ALIGN TO, in ts-column units
    by: list[ast.Expr]
    aggs: list[RangeAgg]
    items: list[tuple[str, ast.Expr]]
    order_keys: list[ast.OrderByItem] = field(default_factory=list)
    limit: Optional[int] = None
    offset: int = 0
    keys: list[ast.Expr] = field(default_factory=list)  # BY, no constants
    prims: list[tuple[str, Optional[int]]] = field(default_factory=list)

    @property
    def shift(self) -> int:
        """Where window starts sit inside an ALIGN step: T = k * align +
        shift (0 unless ALIGN TO names an origin off the step's grid)."""
        return self.origin % self.align_step

    def describe(self) -> str:
        aggs = ", ".join(
            f"{a.func} RANGE {a.range_steps}x"
            + (f" FILL {a.fill}" if a.fill is not None else "")
            for a in self.aggs)
        return (f"align={self.align_step} origin={self.origin} "
                f"by={len(self.keys)} keys aggs=[{aggs}]")


_RANGE_FUNCS = {
    "avg": "avg", "mean": "avg", "sum": "sum", "count": "count",
    "min": "min", "max": "max", "first": "first", "last": "last",
    "first_value": "first", "last_value": "last",
    "stddev": "stddev", "variance": "variance",
}


def is_range_select(sel: ast.Select) -> bool:
    return sel.align is not None or any(
        getattr(it, "range_interval", None) is not None for it in sel.items
    )


def plan_range_select(sel: ast.Select,
                      table: TableInfo) -> lp.RangeCombine:
    """Validate + lower a RANGE select (reference plan_rewrite.rs
    RangePlanRewriter) to the tumbling aggregate under its RangeCombine
    root. The Filter holds `sel.where` itself, so the plan cache re-binds
    its literals as it does any statement's."""
    schema = table.schema
    ts_col = schema.time_index
    ts_expr = ast.Column(ts_col.name)
    if sel.align is None:
        raise PlanError("RANGE aggregates need an ALIGN clause")
    # clauses the range path does not implement are rejected, not
    # silently dropped (reference range_select has the same restrictions)
    if sel.group_by:
        raise PlanError(
            "GROUP BY is not valid in a RANGE query; series are keyed by "
            "the ALIGN BY clause")
    if sel.having is not None:
        raise PlanError("HAVING is not supported in RANGE queries")
    if sel.distinct:
        raise PlanError("DISTINCT is not supported in RANGE queries")
    align_step = _interval_in_col_unit(sel.align, ts_expr, schema)
    origin = 0
    if sel.align_to is not None:
        if not (isinstance(sel.align_to, ast.Literal)
                and isinstance(sel.align_to.value, (int, float))):
            raise PlanError("ALIGN TO expects a numeric timestamp literal")
        origin = int(sel.align_to.value)
    by = list(sel.align_by) if sel.align_by else [
        ast.Column(c.name) for c in schema.tag_columns
    ]
    default_fill = sel.range_fill

    items: list[tuple[str, ast.Expr]] = []
    aggs: list[RangeAgg] = []
    # dedupe aggregates by (call, range, fill) — the SAME avg(v) node with
    # two different RANGEs is two different computations, so each gets a
    # unique marker column that replaces it inside that item's expression
    marker_of: dict[tuple, ast.Column] = {}
    for it in sel.items:
        if isinstance(it.expr, ast.Star):
            raise PlanError("SELECT * is not valid in a RANGE query")
        name = it.alias or _item_name(it.expr)
        calls: list[ast.FuncCall] = []
        collect_aggregates(it.expr, calls)
        rng = it.range_interval
        steps = align_step if rng is None else \
            _interval_in_col_unit(rng, ts_expr, schema)
        if steps % align_step:
            raise PlanError(
                f"RANGE ({steps}) must be a multiple of ALIGN ({align_step})")
        range_steps = max(steps // align_step, 1)
        fill = it.fill if it.fill is not None else default_fill
        subst: dict[ast.FuncCall, ast.Column] = {}
        for call in calls:
            dedup_key = (call, range_steps, fill)
            marker = marker_of.get(dedup_key)
            if marker is None:
                func = _RANGE_FUNCS.get(call.name)
                if func is None:
                    raise PlanError(
                        f"aggregate {call.name!r} is not supported in "
                        "RANGE queries")
                arg: Optional[ast.Expr]
                if len(call.args) == 1 and isinstance(call.args[0], ast.Star):
                    if func != "count":
                        raise PlanError(f"{func}(*) is not valid")
                    func, arg = "rows", None
                elif len(call.args) != 1:
                    raise PlanError(f"{call.name} takes one argument")
                else:
                    arg = call.args[0]
                marker = ast.Column(f"__range_agg_{len(aggs)}")
                marker_of[dedup_key] = marker
                aggs.append(RangeAgg(func, arg, marker, range_steps, fill))
            subst[call] = marker
        items.append((name, _subst_calls(it.expr, subst)))
    if not aggs:
        raise PlanError("a RANGE query needs at least one aggregate")

    # every non-aggregate column reference must be the time index or a BY key
    allowed = {ts_col.name}
    for b in by:
        collect_columns(b, allowed)
    outside: set[str] = set()
    for _, e in items:
        _collect_nonagg_columns(e, outside)
    bad = {c for c in outside - allowed if not c.startswith("__range_agg_")}
    if bad:
        raise PlanError(
            f"column(s) {sorted(bad)} must appear in the ALIGN BY clause")

    rp = RangePlan(
        table=table, align_step=align_step, origin=origin,
        by=by, aggs=aggs, items=items, order_keys=list(sel.order_by),
        limit=sel.limit, offset=sel.offset or 0,
    )
    return lp.RangeCombine(_lower(rp, sel.where, sel.align), rp)


def _lower(rp: RangePlan, where: Optional[ast.Expr],
           align: ast.Interval) -> lp.LogicalPlan:
    """The aggregate a range statement is made of: keys = the BY keys
    and the ALIGN bucket, one AggSpec a primitive (`rows` always: which
    windows hold rows). Fills in rp's column layout."""
    from greptimedb_tpu.query.physical import _PRIMITIVES, _needs_host_agg

    schema = rp.table.schema
    ts_name = schema.time_index.name
    # a constant key (`BY ()` parses to one) groups nothing
    rp.keys = [b for b in rp.by if not isinstance(b, ast.Literal)]
    keys = [(f"__range_by_{i}", b) for i, b in enumerate(rp.keys)]
    # the executor plans date_bin(<interval>, <time index>) from the
    # statement's range alone; an origin off the step's grid makes it a
    # generic key
    bucket_args = (align, ast.Column(ts_name)) \
        + ((ast.Literal(rp.shift),) if rp.shift else ())
    keys.append(("__range_bucket", ast.FuncCall("date_bin", bucket_args)))

    args: list[ast.Expr] = []
    specs: list[lp.AggSpec] = []

    def want(op: str, slot: Optional[int]) -> None:
        if (op, slot) in rp.prims:
            return
        arg = None if slot is None else args[slot]
        call = ast.FuncCall("count", (ast.Star(),)) if arg is None \
            else ast.FuncCall(f"__range_{op}", (arg,))
        spec = lp.AggSpec(f"__range_{op}_{slot}", op, arg, call)
        if _needs_host_agg(spec, schema):
            raise PlanError(
                "RANGE aggregates take numeric arguments")
        rp.prims.append((op, slot))
        specs.append(spec)

    want("rows", None)
    for a in rp.aggs:
        if a.arg is not None:
            if a.arg not in args:
                args.append(a.arg)
            a.slot = args.index(a.arg)
        for op in _PRIMITIVES[a.func]:
            want(op, None if op == "rows" else a.slot)

    needed: set[str] = {ts_name}
    collect_columns(where, needed)
    for _, k in keys:
        collect_columns(k, needed)
    for e in args:
        collect_columns(e, needed)
    unknown = needed - set(schema.names)
    if unknown:
        raise PlanError(
            f"unknown column(s) {sorted(unknown)} in table {rp.table.name}")
    plan: lp.LogicalPlan = lp.Scan(
        rp.table, [c for c in schema.names if c in needed],
        extract_ts_bounds(where, ts_name, schema.time_index.dtype))
    if where is not None:
        plan = lp.Filter(plan, where)
    plan = lp.Aggregate(plan, keys, specs)
    return lp.Project(plan, keys + [(s.name, s.call) for s in specs])


def _item_name(e: ast.Expr) -> str:
    from greptimedb_tpu.query.planner import _default_name
    return _default_name(e)


def _subst_calls(e: ast.Expr, subst: dict) -> ast.Expr:
    """Structurally replace aggregate FuncCalls with their marker columns."""
    if isinstance(e, ast.FuncCall) and e in subst:
        return subst[e]
    if isinstance(e, ast.BinaryOp):
        return ast.BinaryOp(e.op, _subst_calls(e.left, subst),
                            _subst_calls(e.right, subst))
    if isinstance(e, ast.UnaryOp):
        return ast.UnaryOp(e.op, _subst_calls(e.operand, subst))
    if isinstance(e, ast.FuncCall):
        return ast.FuncCall(
            e.name, tuple(_subst_calls(a, subst) for a in e.args),
            e.distinct, order_within=e.order_within)
    if isinstance(e, ast.Cast):
        return ast.Cast(_subst_calls(e.expr, subst), e.type_name)
    return e


def _collect_nonagg_columns(e: ast.Expr, out: set) -> None:
    if isinstance(e, ast.FuncCall) and e.name in _RANGE_FUNCS:
        return
    if isinstance(e, ast.Column):
        out.add(e.name)
        return
    for f in getattr(e, "__dataclass_fields__", {}):
        v = getattr(e, f)
        if isinstance(v, ast.Expr):
            _collect_nonagg_columns(v, out)
        elif isinstance(v, (list, tuple)):
            for x in v:
                if isinstance(x, ast.Expr):
                    _collect_nonagg_columns(x, out)


# ---- the sliding combine -----------------------------------------------------

#: how each primitive of adjacent buckets folds into a window's
_ADD = ("rows", "count", "sum", "sumsq")


def range_combine(executor, rp: RangePlan, groups) -> "QueryResult":
    """From the lowered aggregate's observed groups (a QueryResult:
    BY keys, bucket start, primitives) to the statement's answer."""
    from greptimedb_tpu.utils import tracing
    from greptimedb_tpu.utils.metrics import (
        RANGE_SELECT_SECONDS,
        RANGE_WINDOWS,
    )

    ts_key = ast.Column(rp.table.schema.time_index.name)
    ranges = sorted({a.range_steps for a in rp.aggs})
    t0 = time.perf_counter()
    with tracing.stage("assemble", step="range_combine"), \
            tracing.span("range_combine", groups=groups.num_rows,
                         distinct_ranges=len(ranges),
                         slots=ranges[-1]) as attrs:
        env, series, window = _combine(rp, groups, ranges, ts_key)
        observed = len(window)
        t1 = time.perf_counter()
        RANGE_SELECT_SECONDS.observe(t1 - t0, phase="combine")
        env, nrows = _apply_fill(rp, env, series, window, ts_key)
        RANGE_SELECT_SECONDS.observe(time.perf_counter() - t1, phase="fill")
        attrs.update(series=int(series.max()) + 1 if observed else 0,
                     buckets=int(window.max() - window.min()) + 1
                     if observed else 0,
                     filled=nrows - observed)
    RANGE_WINDOWS.inc(float(observed), kind="observed")
    RANGE_WINDOWS.inc(float(nrows - observed), kind="filled")
    return executor._post_process(
        env, None, None, lp.Project(None, rp.items),
        lp.Sort(None, rp.order_keys) if rp.order_keys else None,
        rp.limit, rp.offset, rp.table, nrows)


def _combine(rp: RangePlan, groups, ranges: list, ts_key) -> tuple:
    """(env of the observed windows, their series index, their window
    index k: the window starts at k * align + shift). A window exists
    where ANY aggregate's range holds a row; an aggregate whose own
    range holds none there is NULL (count: 0)."""
    from greptimedb_tpu.query.dist_agg import _factorize_with_null
    from greptimedb_tpu.query.physical import _finalize_agg

    n, n_keys = groups.num_rows, len(rp.keys)
    by_cols, bucket_col = groups.columns[:n_keys], groups.columns[n_keys]
    if n == 0:
        env = {ts_key: np.empty(0, dtype=np.int64)}
        env.update({b: np.empty(0, dtype=object) for b in rp.keys})
        env.update({a.key: np.empty(0, dtype=np.float64) for a in rp.aggs})
        empty = np.empty(0, dtype=np.int64)
        return env, empty, empty

    # one dense series code a group, by VALUE of its BY keys
    series, card = np.zeros(n, dtype=np.int64), 1
    for col in by_cols:
        uniq, codes = _factorize_with_null(np.asarray(col))
        if card * len(uniq) >= 1 << 62:
            # keep the composite inside int64: compact before mixing in
            series, card = np.unique(series, return_inverse=True)[1], n
        series, card = series * len(uniq) + codes, card * len(uniq)
    _, rep, series = np.unique(series, return_index=True,
                               return_inverse=True)
    bucket = (np.asarray(bucket_col, dtype=np.int64)
              - rp.shift) // rp.align_step
    b_lo = int(bucket.min())
    s_max = ranges[-1]
    # (series, bucket) as one sorted code; the room of s_max - 1 below a
    # series' first bucket holds its leading partial windows and keeps a
    # window's reach out of the next series
    span = int(bucket.max()) - b_lo + s_max
    code = series * span + (bucket - b_lo + s_max - 1)
    if np.any(code[1:] <= code[:-1]):
        order = np.argsort(code, kind="stable")
        code = code[order]
    else:
        order = None
    # a window is observed where one of its s_max buckets holds a group:
    # each group ends a run of up to s_max windows, cut where the group
    # before it already covers (W windows, no [groups, s_max] grid)
    lens = np.minimum(np.diff(code, prepend=code[0] - s_max), s_max)
    run_end = np.cumsum(lens)
    wcode = np.repeat(code - run_end, lens) + np.arange(1, run_end[-1] + 1)
    planes = {}
    for (op, slot), col in zip(rp.prims, groups.columns[n_keys + 1:]):
        col = np.asarray(col, dtype=np.float64)
        planes[(op, slot)] = col if order is None else col[order]

    accs = {r: _slide(planes, code, wcode, r) for r in ranges}
    w_series = wcode // span
    window = wcode % span + (b_lo - s_max + 1)
    env = {ts_key: window * rp.align_step + rp.shift}
    for b, col in zip(rp.keys, by_cols):
        env[b] = np.asarray(col)[rep][w_series]
    everything = np.arange(len(wcode))
    for a in rp.aggs:
        acc = {op: plane for (op, slot), plane in accs[a.range_steps].items()
               if slot == a.slot or op == "rows"}
        env[a.key] = _finalize_agg(a.func, acc, None, everything)
    return env, w_series, window


def _slide(planes: dict, code: np.ndarray, wcode: np.ndarray,
           steps: int) -> dict:
    """Every primitive plane of the windows `wcode`, each the fold of
    the groups whose code lies in [wcode, wcode + steps): a run of the
    sorted groups, reduced with ufunc.reduceat."""
    lo = np.searchsorted(code, wcode, side="left")
    hi = np.searchsorted(code, wcode + steps, side="left")
    held = hi > lo
    # reduceat folds [i0:i1), [i1:i2), ...: (lo, hi) pairs interleaved,
    # the fold of each pair read at the even places (an empty pair reads
    # one element there: masked below); one pad row keeps hi in range
    pairs = np.empty(2 * len(wcode), dtype=np.int64)
    pairs[0::2], pairs[1::2] = lo, hi
    first, last = np.minimum(lo, len(code) - 1), np.maximum(hi - 1, 0)
    out = {}
    for (op, slot), plane in planes.items():
        if op in _ADD:
            # a NULL sum (no valid value in the bucket) adds nothing
            padded = np.append(np.nan_to_num(plane, nan=0.0), 0.0)
            folded = np.where(held, np.add.reduceat(padded, pairs)[0::2], 0.0)
        elif op in ("min", "max"):
            fold = np.fmin if op == "min" else np.fmax
            padded = np.append(plane, np.nan)
            folded = np.where(held, fold.reduceat(padded, pairs)[0::2],
                              np.nan)
        else:  # first / last: buckets are in time order
            folded = np.where(held, plane[first if op == "first" else last],
                              np.nan)
        out[(op, slot)] = folded
    return out


def _apply_fill(rp: RangePlan, env: dict, series: np.ndarray,
                window: np.ndarray, ts_key) -> tuple:
    """FILL NULL/PREV/LINEAR/<const> densify each series' time grid
    between the globally first and last observed windows (reference
    range_select FILL, plan.rs RangeFn::fill). Returns (env, rows)."""
    nrows = len(window)
    if not any(a.fill is not None for a in rp.aggs) or nrows == 0:
        return env, nrows
    k_lo = int(window.min())
    span = int(window.max()) - k_lo + 1
    n_series = int(series.max()) + 1
    dense_n = n_series * span
    pos = series * span + (window - k_lo)
    have = np.zeros(dense_n, dtype=bool)
    have[pos] = True
    at = np.arange(dense_n)
    start = at // span * span
    # the nearest observed window at or before / at or after each place,
    # inside its own series
    prev = np.maximum.accumulate(np.where(have, at, -1))
    has_prev = prev >= start
    nxt = np.minimum.accumulate(np.where(have, at, dense_n)[::-1])[::-1]
    has_next = nxt < start + span
    prev, nxt = np.maximum(prev, 0), np.minimum(nxt, dense_n - 1)

    out: dict = {ts_key: (at % span + k_lo) * rp.align_step + rp.shift}
    for b in rp.keys:
        per_series = np.empty(n_series, dtype=env[b].dtype)
        per_series[series] = env[b]
        out[b] = np.repeat(per_series, span)
    for a in rp.aggs:
        arr = np.full(dense_n, np.nan)
        arr[pos] = env[a.key]
        if isinstance(a.fill, float):
            arr = np.where(have, arr, a.fill)
        elif a.fill == "prev":
            arr = np.where(have | ~has_prev, arr, arr[prev])
        elif a.fill == "linear":
            # between two observed windows: the line through them;
            # outside them the nearest one's value; a series with one
            # observed window has no line
            both = has_prev & has_next
            with np.errstate(invalid="ignore", divide="ignore"):
                line = arr[prev] + (arr[nxt] - arr[prev]) \
                    * ((at - prev) / np.maximum(nxt - prev, 1))
            two = np.repeat(have.reshape(n_series, span).sum(axis=1) >= 2,
                            span)
            filled = np.where(both, line,
                              np.where(has_prev, arr[prev], arr[nxt]))
            arr = np.where(have | ~two, arr, filled)
        out[a.key] = arr
    return out, dense_n

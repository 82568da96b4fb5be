"""PromQL evaluation engine.

Mirrors the reference's PromPlanner + extension operators
(promql/src/planner.rs:144, extension_plan/*) re-designed for dense device
evaluation (see package docstring): every (sub)expression evaluates to one
of
  - SeriesMatrix: labels [S] + values [S, T] (NaN = no sample)
  - a per-step scalar array [T]
  - a python float (constant)
over the regular eval grid (start, end, step). Range-vector functions run
the window_stats kernel (ops/window.py); label aggregations are segment
reductions over the series axis; binary-op vector matching joins label
signatures on host (S is small; T×S math stays on device).
"""

from __future__ import annotations

import functools
import math
import re
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.datatypes.types import DataType
from greptimedb_tpu.ops.histogram import histogram_fold
from greptimedb_tpu.ops.segment import segment_agg
from greptimedb_tpu.ops.window import (
    counter_adjust,
    exclusive_cumsum,
    grid_over_time,
    grid_rate,
    over_time_of_stats,
    rate_of_edges,
    window_edges_grid,
    window_stats,
    window_sums_grid,
)
from greptimedb_tpu.promql.loaded import (
    LabelSets,
    Loaded,
    LoadedSeries,
    SeriesCache,
    covered,
    d2h,
    derive,
    h2d,
)
from greptimedb_tpu.promql.parser import (
    DEFAULT_LOOKBACK_S,
    Aggregate,
    Binary,
    Call,
    Matcher,
    NumberLiteral,
    PromqlError,
    StringLiteral,
    Subquery,
    Unary,
    VectorSelector,
    parse_promql,
)
from greptimedb_tpu.query.result import QueryResult
from greptimedb_tpu.utils import device_telemetry, tracing
from greptimedb_tpu.utils.metrics import (
    PROMQL_EVAL_PROGRAMS,
    PROMQL_GROUP_INDEXES,
    PROMQL_HISTOGRAM_FOLD_SECONDS,
    PROMQL_HISTOGRAM_FOLDS,
    PROMQL_LOAD_CACHE_EVENTS,
)

#: samples a selector's load sends to the device at a time
#: (PromqlEngine._upload_sorted): the size of the largest loads the
#: device has been seen to take whole (8.0M samples: PERF.md section 4)
_LOAD_BLOCK = 1 << 23

_CALENDAR = frozenset({
    "minute", "hour", "day_of_week", "day_of_month", "day_of_year",
    "days_in_month", "month", "year",
})


def _calendar_field(fn: str, secs: np.ndarray) -> np.ndarray:
    """UTC calendar field of unix-second values, NaN-preserving
    (reference functions/: the date helpers PromQL exposes).

    Pure numpy datetime64 arithmetic: no pandas ns-resolution bounds —
    any float within int64 seconds works; everything else becomes NaN
    (Prometheus accepts arbitrary floats as input values)."""
    flat = secs.reshape(-1)
    lim = 9.0e18  # within int64 seconds
    bad = ~np.isfinite(flat) | (np.abs(flat) > lim)
    isecs = np.floor(np.where(bad, 0.0, flat)).astype(np.int64)
    if fn == "minute":
        out = ((isecs % 3600) // 60).astype(np.float64)
    elif fn == "hour":
        out = ((isecs % 86400) // 3600).astype(np.float64)
    else:
        dt = isecs.astype("datetime64[s]")
        days = dt.astype("datetime64[D]")
        months = dt.astype("datetime64[M]")
        years = dt.astype("datetime64[Y]")
        if fn == "day_of_week":
            # 1970-01-01 was a Thursday; Prometheus: Sunday = 0
            out = ((days.astype(np.int64) + 4) % 7).astype(np.float64)
        elif fn == "day_of_month":
            out = ((days - months.astype("datetime64[D]"))
                   .astype(np.int64) + 1).astype(np.float64)
        elif fn == "day_of_year":
            out = ((days - years.astype("datetime64[D]"))
                   .astype(np.int64) + 1).astype(np.float64)
        elif fn == "days_in_month":
            out = ((months + 1).astype("datetime64[D]")
                   - months.astype("datetime64[D]")).astype(np.float64)
        elif fn == "month":
            out = ((months - years.astype("datetime64[M]"))
                   .astype(np.int64) + 1).astype(np.float64)
        else:  # year
            out = (years.astype(np.int64) + 1970).astype(np.float64)
    out[bad] = np.nan
    return out.reshape(secs.shape)


def _fmt_prom_value(v: float) -> str:
    """Shortest positional-decimal float formatting (Go FormatFloat
    'f', -1): no scientific notation; Inf spelled Prometheus-style."""
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return np.format_float_positional(v, trim="-")


@dataclass
class SeriesMatrix:
    labels: list[dict[str, str]]  # S label sets (no __name__)
    values: jax.Array  # [S, T]
    metric: Optional[str] = None
    sample_ts: Optional[jax.Array] = None  # [S, T] for timestamp()

    @property
    def num_series(self) -> int:
        return len(self.labels)


@dataclass
class EvalParams:
    start: float
    end: float
    step: float
    times: np.ndarray  # [T] seconds

    @property
    def T(self) -> int:
        return len(self.times)


_RANGE_FUNCS = {
    "rate", "increase", "delta", "avg_over_time", "sum_over_time",
    "count_over_time", "min_over_time", "max_over_time", "last_over_time",
    "stddev_over_time", "stdvar_over_time", "present_over_time",
    "changes", "resets", "deriv", "predict_linear", "irate", "idelta",
    "absent_over_time", "holt_winters",
}

#: range functions a pivot answers in one program (`_run_on_grid`), and
#: the aggregation operators that program can fold their result with
_RATE_FUNCS = ("rate", "increase", "delta")
_COUNTER_FUNCS = ("rate", "increase")
_GRID_FUNCS = _RATE_FUNCS + ("sum_over_time", "avg_over_time",
                             "count_over_time")
#: operator -> the segment statistics it is finished from
_AGG_STATS = {
    "sum": ("sum",), "avg": ("sum", "count"),
    "min": ("min",), "max": ("max",),
    "count": ("count",), "group": ("count",),
    "stddev": ("sum", "sumsq", "count"),
    "stdvar": ("sum", "sumsq", "count"),
}

_ELEMENTWISE = {
    "abs": jnp.abs, "ceil": jnp.ceil, "floor": jnp.floor,
    "exp": jnp.exp, "ln": jnp.log, "log2": jnp.log2, "log10": jnp.log10,
    "sqrt": jnp.sqrt, "sgn": jnp.sign,
    "acos": jnp.arccos, "asin": jnp.arcsin, "atan": jnp.arctan,
    "cos": jnp.cos, "sin": jnp.sin, "tan": jnp.tan,
    "cosh": jnp.cosh, "sinh": jnp.sinh, "tanh": jnp.tanh,
    "deg": jnp.degrees, "rad": jnp.radians,
}


class PromqlEngine:
    def __init__(self, query_engine):
        self.qe = query_engine

    # ---- public API --------------------------------------------------------

    def eval_range(self, query: str, start: float, end: float, step: float,
                   ctx=None) -> QueryResult:
        """Range query -> long-format table (ts, value, labels...) like the
        reference's TQL output."""
        times, result = self.eval_matrix(query, start, end, step, ctx)
        return _to_long_result(times, result)

    def eval_matrix(self, query: str, start: float, end: float, step: float,
                    ctx=None):
        if step <= 0:
            raise PromqlError("step must be positive")
        from greptimedb_tpu.utils import slow_query

        # slow-query watch for the direct PromQL HTTP entry points; a
        # TQL statement arrives under execute_sql's watch, where this
        # one is a no-op (the re-entrancy guard)
        with slow_query.watch("promql", query,
                              getattr(ctx, "db", None) or "public"):
            with tracing.stage("parse"):
                node = parse_promql(query)
            n_steps = int(math.floor((end - start) / step)) + 1
            times = start + np.arange(n_steps) * step
            params = EvalParams(start, end, step, times)
            # the evaluation dispatches eager device operations and
            # jitted window kernels; the stages that _load and the
            # aggregations open (scan, upload, readback, assemble) cut
            # themselves out of this `device` stage
            with tracing.stage("device"):
                result = self._eval(node, params, ctx)
            if isinstance(result, SeriesMatrix):
                # on whichever watch is open: this one, or the HTTP
                # handler's, which also covers readback and encoding
                slow_query.annotate(rows=len(result.labels))
        return times, result

    def eval_instant(self, query: str, t: float, ctx=None):
        times, result = self.eval_matrix(query, t, t, 1.0, ctx)
        return times, result

    # ---- evaluation --------------------------------------------------------

    def _eval(self, node, p: EvalParams, ctx):
        if isinstance(node, NumberLiteral):
            return node.value
        if isinstance(node, StringLiteral):
            return node.value
        if isinstance(node, Unary):
            v = self._eval(node.expr, p, ctx)
            return _map_values(v, lambda x: -x)
        if isinstance(node, VectorSelector):
            if node.range_s is not None:
                raise PromqlError("range vector outside function call")
            if node.at_s is not None:
                return self._eval_at(node, p, ctx)
            return self._eval_instant_selector(node, p, ctx)
        if isinstance(node, Call):
            return self._eval_call(node, p, ctx)
        if isinstance(node, Aggregate):
            return self._eval_aggregate(node, p, ctx)
        if isinstance(node, Binary):
            return self._eval_binary(node, p, ctx)
        raise PromqlError(f"cannot evaluate {type(node).__name__}")

    # ---- selectors ---------------------------------------------------------

    @staticmethod
    def _resolve_at(at, p: EvalParams) -> float:
        if at == "__start__":
            return p.start
        if at == "__end__":
            return p.end
        return float(at)

    def _eval_at(self, sel: VectorSelector, p: EvalParams, ctx):
        """`@ <ts>` / `@ start()` / `@ end()` (Prometheus at-modifier):
        evaluate the selector at ONE fixed instant, then broadcast that
        value across every output step."""
        t_fix = self._resolve_at(sel.at_s, p)
        pinned = VectorSelector(sel.metric, sel.matchers, sel.range_s,
                                sel.offset_s, None)
        p1 = EvalParams(start=t_fix, end=t_fix, step=p.step,
                        times=np.asarray([t_fix]))
        v = self._eval_instant_selector(pinned, p1, ctx)
        return SeriesMatrix(
            v.labels, jnp.broadcast_to(v.values, (v.values.shape[0], p.T)),
            v.metric,
            sample_ts=(jnp.broadcast_to(v.sample_ts,
                                        (v.values.shape[0], p.T))
                       if v.sample_ts is not None else None))

    def _eval_instant_selector(self, sel: VectorSelector, p: EvalParams, ctx,
                               lookback: float = DEFAULT_LOOKBACK_S):
        loaded = self._load(sel, p, ctx, window=lookback)
        if loaded is None:
            return SeriesMatrix([], jnp.zeros((0, p.T)))
        sidx, ts, chans = loaded.flat()
        w = max(1, int(math.ceil(lookback / p.step)))
        st = window_stats(sidx, ts, chans, ~jnp.isnan(chans[:, 0]),
                          p.start, p.step, len(loaded.labels), p.T, w,
                          stats=("count", "last"),
                          sorted_input=_sorted_ws())
        vals = st["last"][:, :, 0]
        lts = st["last_ts"]
        # exact lookback: bucket window may overcover; validate sample ts
        ok = lts > (h2d(p.times)[None, :] - lookback)
        vals = jnp.where(ok, vals, jnp.nan)
        return SeriesMatrix(loaded.labels, vals, loaded.metric,
                            sample_ts=jnp.where(ok, lts, jnp.nan))

    @staticmethod
    def _window_steps(sel, p: EvalParams) -> tuple:
        """(w, range_s): a range selector's or subquery's window in
        whole steps and in seconds."""
        range_s = getattr(sel, "range_s", None)
        if range_s is None:
            raise PromqlError("expected a range vector (metric[duration])")
        ratio = range_s / p.step
        w = int(round(ratio))
        if abs(ratio - w) > 1e-9 or w < 1:
            raise PromqlError(
                f"range {range_s}s must be a positive multiple of step {p.step}s "
                "(blocked-window evaluation)")
        return w, range_s

    def _range_stats(self, sel, p: EvalParams, ctx,
                     stats: tuple[str, ...], extra_channels=()):
        """Evaluate a range selector OR subquery into window stats.
        Returns (stats dict, labels, metric, w, range_s) or None when
        empty."""
        w, range_s = self._window_steps(sel, p)
        loaded = self._load_any(sel, p, ctx, window=range_s,
                                extra_channels=extra_channels)
        if loaded is None:
            return None
        return (self._stats_of(loaded, sel, p, stats, w), loaded.labels,
                loaded.metric, w, range_s)

    def _stats_of(self, loaded: Loaded, sel, p: EvalParams,
                  stats: tuple[str, ...], w: int) -> dict:
        """The window stats of loaded samples, a kernel at a time."""
        grid_ok = not isinstance(sel, Subquery) and _edges_enabled()
        st = None
        if grid_ok and "sum" in stats and set(stats) <= {"sum", "count"}:
            # sum/avg_over_time fast path: one cumulative sum over the
            # pivot turns every window sum into a two-gather difference
            # (window_sums_grid). It runs over the request's OWN range of
            # a pivot that spans more, so no difference of two prefixes
            # is taken over a longer prefix than the range needs: a
            # resident multi-day span costs the answer no digits.
            # Count-only stats skip this — the edges path below derives
            # counts from probes alone, without a pivot-sized cumsum.
            pivot = loaded.pivot(own_range=True)
            if pivot is not None:
                grid, mat = pivot
                st = window_sums_grid(grid, exclusive_cumsum(mat),
                                      p.start, p.step, p.T, w)
        if st is None and grid_ok and set(stats) <= {"count", "first",
                                                     "last"}:
            # rate-family fast path: scrape-aligned series share ONE
            # complete sample grid, so window edges are T probes into
            # the grid + column gathers from a pivoted [S, P, C] matrix
            # (ops/window.py window_edges_grid — the asymmetry the
            # numpy straw-man anchor exploits, now on device). The
            # pivot is kept with the loaded series, however long the
            # span they were loaded for: the probes find a window by
            # its time, so repeated evals pay only the probes.
            pivot = loaded.pivot()
            if pivot is not None:
                grid, mat = pivot
                st = window_edges_grid(grid, mat, p.start, p.step,
                                       p.T, w)
        if st is None:
            sidx, ts, chans = loaded.flat()
            st = window_stats(sidx, ts, chans, ~jnp.isnan(chans[:, 0]),
                              p.start, p.step, len(loaded.labels), p.T, w,
                              stats=stats, sorted_input=_sorted_ws())
        return st

    def _load_any(self, sel, p: EvalParams, ctx, window: float,
                  extra_channels=()):
        if isinstance(sel, Subquery):
            return self._load_subquery(sel, p, ctx, extra_channels)
        return self._load(sel, p, ctx, window, extra_channels)

    def _load_subquery(self, sq: Subquery, p: EvalParams, ctx,
                       extra_channels=()):
        """Evaluate the inner expr on the subquery's own grid, flatten the
        matrix to (series, ts, value) samples, and hand back the same
        loaded tuple a storage scan produces — downstream window kernels
        can't tell the difference (reference planner subquery support)."""
        sub_step = sq.step_s if sq.step_s else p.step
        lo = p.start - sq.range_s - sq.offset_s
        hi = p.end - sq.offset_s
        # Prometheus aligns subquery steps to absolute multiples of step
        first = math.ceil(lo / sub_step) * sub_step
        n = int(math.floor((hi - first) / sub_step)) + 1
        if n <= 0:
            return None
        times = first + np.arange(n) * sub_step
        inner = EvalParams(first, times[-1], sub_step, times)
        v = self._eval(sq.expr, inner, ctx)
        if not isinstance(v, SeriesMatrix):
            raise PromqlError("subquery needs an instant-vector expression")
        if v.num_series == 0:
            return None
        vals = d2h(v.values)
        S, T2 = vals.shape
        sidx = np.repeat(np.arange(S, dtype=np.int32), T2)
        ts = np.tile(times + sq.offset_s, S)  # back on the outer timeline
        flat = vals.reshape(-1)
        keep = ~np.isnan(flat)  # absent inner samples aren't samples
        if not keep.any():
            return None
        d_sidx = h2d(sidx[keep])
        d_ts = h2d(ts[keep])
        d_vals = h2d(flat[keep])
        channels = self._make_channels(d_sidx, d_ts, d_vals,
                                       extra_channels, p)
        return Loaded(LoadedSeries(v.labels, d_sidx, d_ts, channels),
                      v.metric)

    def _make_channels(self, d_sidx, d_ts, d_vals, extra_channels, p):
        """Derived per-sample channels riding the window kernel alongside
        the raw value: counter-reset-adjusted values, change/reset
        indicators, regression moments, previous-sample value/ts."""
        chans = [d_vals]
        if "adjusted" in extra_channels:
            chans.append(counter_adjust(d_sidx, d_vals))
        if extra_channels and {"changes", "resets", "prev"} & set(extra_channels):
            prev_v = jnp.concatenate([d_vals[:1], d_vals[:-1]])
            same = jnp.concatenate([jnp.zeros(1, bool),
                                    (d_sidx[1:] == d_sidx[:-1])])
            if "changes" in extra_channels:
                chans.append(jnp.where(same & (d_vals != prev_v), 1.0, 0.0))
            if "resets" in extra_channels:
                chans.append(jnp.where(same & (d_vals < prev_v), 1.0, 0.0))
            if "prev" in extra_channels:
                prev_t = jnp.concatenate([d_ts[:1], d_ts[:-1]])
                chans.append(jnp.where(same, prev_v, jnp.nan))
                chans.append(jnp.where(same, prev_t, jnp.nan))
        if "deriv" in extra_channels:
            tr = d_ts - p.start  # well-conditioned regression coordinates
            chans += [d_vals * tr, tr, tr * tr]
        return jnp.stack(chans, axis=1)

    def _load(self, sel: VectorSelector, p: EvalParams, ctx, window: float,
              extra_channels=()) -> Optional[Loaded]:
        """A selector's samples for this request: device arrays sorted
        by (series, ts) — sidx [N], ts seconds [N], channels [N, C] —
        or their pivot onto a shared grid, with labels and metric.
        Channel 0 is the raw value; extra_channels in {"adjusted",
        "changes", "resets", "prev", "deriv"} append derived channels.
        The loaded-series cache (promql/loaded.py) is asked first, from
        region metadata; a region is scanned only for what it lacks."""
        matchers = list(sel.matchers)
        metric = sel.metric
        field_name = None
        rest: list[Matcher] = []
        for m in matchers:
            if m.label == "__name__":
                if m.op != "=":
                    raise PromqlError("__name__ supports '=' only")
                metric = m.value
            elif m.label == "__field__":
                if m.op != "=":
                    raise PromqlError("__field__ supports '=' only")
                field_name = m.value
            else:
                rest.append(m)
        if metric is None:
            raise PromqlError("selector needs a metric name")

        qe = self.qe
        from greptimedb_tpu.catalog.catalog import CatalogError
        from greptimedb_tpu.query.engine import QueryContext
        ctx = ctx or QueryContext()
        try:
            info = qe._table(metric, ctx)
        except CatalogError:
            return None
        schema = info.schema
        fields = schema.field_columns
        if field_name is None:
            if len(fields) == 1:
                field_name = fields[0].name
            elif any(f.name == "greptime_value" for f in fields):
                field_name = "greptime_value"
            else:
                raise PromqlError(
                    f"metric {metric!r} has {len(fields)} fields; select one "
                    "with {__field__=\"...\"}"
                    )
        elif field_name not in {f.name for f in fields}:
            raise PromqlError(f"no field {field_name!r} in {metric!r}")

        unit = schema.time_index.dtype.time_unit.nanos_per_unit
        offset = sel.offset_s
        lo = int((p.start - window - offset) * 1e9) // unit
        hi = int((p.end - offset) * 1e9) // unit + 1
        region_id = info.region_ids[0]

        def load(ts_range, resident: str):
            return self._scan_series(info, metric, field_name, rest,
                                     ts_range, offset, extra_channels, p,
                                     resident)

        # stage `scan`: the cache's probe and, for what it lacks, the
        # region scan (SST read, decode, merge) and everything the host
        # derives from it — matcher masks, series factorization, label
        # decode. The uploads and the device's sort and channels cut
        # themselves out (`upload`, `device`)
        with tracing.stage("scan", metric=metric, field=field_name) as sa:
            cache, identity = self._series_cache(region_id)
            event, series = "miss", None
            if cache is not None:
                # what the samples depend on beside the data version;
                # "deriv" channels embed p.start and key on it
                key = (region_id, field_name, offset,
                       tuple(sorted((m.label, m.op, m.value)
                                    for m in rest)),
                       tuple(extra_channels), not info.append_mode,
                       p.start if "deriv" in extra_channels else None)
                # a channel beside the reset-adjusted value depends, at
                # a range's first sample, on where the load began: such
                # samples serve their own range only
                sliceable = set(extra_channels) <= {"adjusted"}
                event, series = cache.probe(key, identity, lo, hi,
                                            sliceable)
                if event == "promote":
                    series = self._promote(cache, key, identity, load,
                                           lo, hi)
                    if series is None:
                        event = "ineligible"
                PROMQL_LOAD_CACHE_EVENTS.inc(event=event)
            sa["resident"] = event
            if event == "hit":
                with tracing.span("promql_scan", metric=metric,
                                  field=field_name, resident="hit"):
                    pass  # nothing is scanned
            elif series is None:
                got = load((lo, hi), event)
                if got is None:
                    return None
                series, version = got
                if cache is not None:
                    extent = identity[2] or (lo, hi - 1)
                    if sliceable and lo <= extent[0] and hi > extent[1] \
                            and version == identity[:2]:
                        # the range covered all the region held: these
                        # samples are its whole span at that version
                        series.span = None
                        series.pivot()
                    cache.store(key, version, series,
                                requested=covered(lo, hi, extent))
            cut = None
            if series.span is None and series.grid_host is not None:
                # a slice of the resident matrix: the points the
                # request's own scan would have returned
                sec = unit / 1e9
                cut = series.cut(lo * sec + offset, hi * sec + offset)
                if cut is not None and cut[1] == 0:
                    return None  # no sample in range: no series either
        return Loaded(series, metric, cut)

    def _series_cache(self, region_id: int) -> tuple:
        """(the executor's loaded-series cache, the region's data
        identity); (None, None) where the region cannot say what it
        holds without a scan (a remote or an external one): its samples
        are loaded per request and kept nowhere."""
        ex = getattr(self.qe, "executor", None)
        identify = getattr(self.qe.region_engine, "data_identity", None)
        identity = identify(region_id) if ex is not None and identify \
            else None
        if identity is None:
            return None, None
        cache = getattr(ex, "_promql_series", None)
        if cache is None:
            # the device budget the block cache has (config.
            # device_cache_bytes): PromQL's resident series draw on the
            # same number
            cache = ex._promql_series = SeriesCache(ex.cache.budget)
        return cache, identity

    def _promote(self, cache: SeriesCache, key: tuple, identity: tuple,
                 load, lo: int, hi: int) -> Optional[LoadedSeries]:
        """Load the selector's whole retained span (the region's
        canonical full scan) and keep it where every series shares one
        complete grid: every later request of this data version is then
        a slice of it. Flat, it is kept only if it serves this request
        as a scan of its own range would (Region.scan's canonical
        sharing); else None, and the key stays on its own ranges."""
        version = identity[:2]
        try:
            got = load(None, "promote")
        except BaseException:
            cache.refuse(key, version)
            raise
        if got is not None:
            series, version = got
            series.pivot()
            if series.grid_complete or series.serves(lo, hi):
                cache.store(key, version, series)
                return series
        cache.refuse(key, version)
        return None

    def _scan_series(self, info, metric: str, field_name: str, rest: list,
                     ts_range: Optional[tuple], offset: float,
                     extra_channels, p: EvalParams,
                     resident: str) -> Optional[tuple]:
        """One region scan of `ts_range` (None: everything) -> (the
        selector's LoadedSeries, the (incarnation, data_version) of the
        scan's snapshot); None without a matching row. Runs inside
        stage `scan`."""
        schema = info.schema
        ts_col = schema.time_index
        unit = ts_col.dtype.time_unit.nanos_per_unit
        # push =/=~ matchers into the inverted index (reference applies
        # index predicates at sst/parquet/reader.rs:335-425); != and !~
        # can't prune (a segment bitmap proves presence, not absence).
        # The exact matcher masks below still run on everything scanned.
        from greptimedb_tpu.storage.index import InSet, Regex
        idx_preds: dict[str, list] = {}
        tag_names = [c.name for c in schema.tag_columns]
        for m in rest:
            if m.label not in tag_names:
                continue
            if m.op == "=":
                idx_preds.setdefault(m.label, []).append(InSet.of([m.value]))
            elif m.op == "=~":
                idx_preds.setdefault(m.label, []).append(Regex(m.value))
        with tracing.span("promql_scan", metric=metric, field=field_name,
                          resident=resident) as ps:
            scan = self.qe.region_engine.scan(
                info.region_ids[0], ts_range, [field_name],
                tag_predicates={k: tuple(v)
                                for k, v in idx_preds.items()} or None)
            if scan is None or scan.num_rows == 0:
                return None
            ps["rows"] = scan.num_rows

        mask = np.ones(scan.num_rows, dtype=bool)
        for m in rest:
            mask &= _matcher_mask(m, scan, tag_names)
            if not mask.any():
                return None
        # dedup for non-append tables rides the same sort below
        rows = np.flatnonzero(mask)
        codes = [scan.columns[t][rows] for t in tag_names]
        ts_raw = scan.columns[ts_col.name][rows]
        vals = np.asarray(scan.columns[field_name][rows],
                          dtype=np.float64)

        if tag_names:
            sizes = [len(scan.tag_dicts[t]) + 1 for t in tag_names]
            combined = codes[0].astype(np.int64) + 1
            for c, sz in zip(codes[1:], sizes[1:]):
                combined = combined * sz + (c.astype(np.int64) + 1)
            uniq, sidx, regroup = _factorize_series(combined)
            if regroup is not None:
                rows, ts_raw, vals = (rows[regroup], ts_raw[regroup],
                                      vals[regroup])
            labels = _series_labels(uniq, tag_names, sizes,
                                    scan.tag_dicts)
        else:
            sidx = np.zeros(len(rows), dtype=np.int64)
            labels = [{}]

        ts_sec = ts_raw.astype(np.float64) * (unit / 1e9) + offset
        # rows in (series, ts) order: required by counter_adjust / the
        # indicator channels, and makes segment ids sorted for the
        # kernel. A single flushed SST already yields them so and
        # series codes factorize in tag order; several SSTs (time
        # slices of one table, each in that order) or a memtable beside
        # them are merged here, sorted runs on the host (2 s at 38.4M
        # rows), not sorted on the device: its float64 sort over a whole
        # selector took minutes at that size on the chip (PERF.md
        # section 6, PR 32)
        ds, dt = np.diff(sidx), np.diff(ts_sec)
        if not np.all((ds > 0) | ((ds == 0) & (dt >= 0))):
            order = np.lexsort((ts_raw, sidx))
            rows, sidx, ts_raw, ts_sec, vals = (
                a[order] for a in (rows, sidx, ts_raw, ts_sec, vals))
            ds, dt = np.diff(sidx), np.diff(ts_sec)
        # last-write-wins has nothing to decide where no (series, ts)
        # repeats (or the scan says it cannot) and no tombstone is
        # among the rows: a flushed region nothing was written twice
        # to. Then seq / op_type stay on the host and the device sorts
        # nothing
        settled = info.append_mode or (
            (not scan.needs_dedup or bool(np.all((ds > 0) | (dt > 0))))
            and not scan.has_delete())
        if settled:
            d_sidx, d_ts, channels = self._upload_sorted(
                sidx, ts_sec, vals, extra_channels, p)
        else:
            d_sidx = h2d(sidx.astype(np.int32))
            d_ts = h2d(ts_sec)
            d_vals = h2d(vals)
            d_seq = h2d(scan.seq[rows].astype(np.int64))
            d_op = h2d(scan.op_type[rows].astype(np.int8))
            with tracing.stage("device"):
                d_sidx, d_ts, d_vals = _promql_dedup(d_sidx, d_ts, d_vals,
                                                     d_seq, d_op)
                channels = self._make_channels(d_sidx, d_ts, d_vals,
                                               extra_channels, p)
        series = LoadedSeries(labels, d_sidx, d_ts, channels, span=ts_range,
                              extent=(int(ts_raw.min()), int(ts_raw.max())))
        return series, (scan.incarnation, scan.data_version)

    def _upload_sorted(self, sidx: np.ndarray, ts_sec: np.ndarray,
                       vals: np.ndarray, extra_channels,
                       p: EvalParams) -> tuple:
        """Host rows in (series, ts) order -> device (sidx, ts,
        channels), in blocks of whole series of at most _LOAD_BLOCK
        samples. Every derived channel reads its own series only, so a
        block's channels are what the whole load's would be — and what
        the device needs while it derives them (several times the
        samples' own bytes) is a block's, not the selector's: a
        selector of tens of millions of samples loads beside what is
        already resident. A small load is one block."""
        n = len(sidx)
        blocks = -(-n // _LOAD_BLOCK)
        cuts = [0, n]
        if blocks > 1:
            num_series = int(sidx[-1]) + 1
            per = -(-num_series // blocks)
            cuts = np.searchsorted(
                sidx, np.arange(0, num_series, per)).tolist() + [n]
        parts = []
        for a, b in zip(cuts[:-1], cuts[1:]):
            d_sidx = h2d(sidx[a:b].astype(np.int32))
            d_ts = h2d(ts_sec[a:b])
            d_vals = h2d(vals[a:b])
            with tracing.stage("device"):
                parts.append((d_sidx, d_ts, self._make_channels(
                    d_sidx, d_ts, d_vals, extra_channels, p)))
        if len(parts) == 1:
            return parts[0]
        with tracing.stage("device"):
            return tuple(jnp.concatenate(x) for x in zip(*parts))

    # ---- calls -------------------------------------------------------------

    def _eval_call(self, call: Call, p: EvalParams, ctx):
        fn = call.func
        if fn in _RANGE_FUNCS:
            # `rate(m[5m] @ T)`: pin the whole range evaluation at T and
            # broadcast — never silently evaluate on the normal grid
            sel = next((a for a in call.args
                        if isinstance(a, VectorSelector)), None)
            if sel is not None and sel.at_s is not None:
                t_fix = self._resolve_at(sel.at_s, p)
                pinned = VectorSelector(sel.metric, sel.matchers,
                                        sel.range_s, sel.offset_s, None)
                call2 = Call(call.func, tuple(
                    pinned if a is sel else a for a in call.args))
                p1 = EvalParams(start=t_fix, end=t_fix, step=p.step,
                                times=np.asarray([t_fix]))
                v = self._eval_range_func(call2, p1, ctx)
                if isinstance(v, SeriesMatrix):
                    return SeriesMatrix(
                        v.labels,
                        jnp.broadcast_to(v.values,
                                         (v.values.shape[0], p.T)),
                        v.metric)
                return v
            return self._eval_range_func(call, p, ctx)
        if fn == "time":
            return h2d(p.times)
        if fn in _CALENDAR:
            # Prometheus calendar functions: input VALUES are unix
            # seconds (default vector(time())); output the UTC field
            if call.args:
                v = self._eval(call.args[0], p, ctx)
            else:
                v = SeriesMatrix([{}], h2d(p.times)[None, :])
            if not isinstance(v, SeriesMatrix):
                v = SeriesMatrix([{}], _broadcast_scalar(v, p)[None, :])
            vals = d2h(v.values, dtype=np.float64)
            out = _calendar_field(fn, vals)
            # functions drop __name__ (same as the _map_values path)
            return SeriesMatrix(v.labels, h2d(out))
        if fn == "scalar":
            v = self._eval(call.args[0], p, ctx)
            if isinstance(v, SeriesMatrix):
                return v.values[0] if v.num_series == 1 else jnp.full(p.T, jnp.nan)
            return v
        if fn == "vector":
            v = self._eval(call.args[0], p, ctx)
            arr = _broadcast_scalar(v, p)
            return SeriesMatrix([{}], arr[None, :])
        if fn == "timestamp":
            v = self._eval(call.args[0], p, ctx)
            if not isinstance(v, SeriesMatrix) or v.sample_ts is None:
                raise PromqlError("timestamp() needs an instant selector")
            return SeriesMatrix(v.labels, v.sample_ts, None)
        if fn in ("clamp", "clamp_min", "clamp_max"):
            v = self._eval(call.args[0], p, ctx)
            if not isinstance(v, SeriesMatrix):
                raise PromqlError(f"{fn} needs a vector")
            args = [_scalar_of(self._eval(a, p, ctx)) for a in call.args[1:]]
            if fn == "clamp":
                out = jnp.clip(v.values, args[0], args[1])
            elif fn == "clamp_min":
                out = jnp.maximum(v.values, args[0])
            else:
                out = jnp.minimum(v.values, args[0])
            return SeriesMatrix(v.labels, out)
        if fn == "round":
            v = self._eval(call.args[0], p, ctx)
            to = _scalar_of(self._eval(call.args[1], p, ctx)) if len(call.args) > 1 else 1.0
            return SeriesMatrix(v.labels, jnp.round(v.values / to) * to)
        if fn in _ELEMENTWISE:
            v = self._eval(call.args[0], p, ctx)
            return _map_values(v, _ELEMENTWISE[fn])
        if fn in ("sort", "sort_desc"):
            v = self._eval(call.args[0], p, ctx)
            if not isinstance(v, SeriesMatrix) or v.num_series <= 1:
                return v
            # order series by their value at the (last) evaluated instant,
            # NaN last — matches Prometheus sort() on instant vectors
            key = d2h(v.values[:, -1]).astype(np.float64)
            rank = np.where(np.isnan(key), np.inf,
                            key if fn == "sort" else -key)
            order = np.argsort(rank, kind="stable")
            return SeriesMatrix([v.labels[i] for i in order],
                                v.values[h2d(order)], v.metric)
        if fn == "absent":
            v = self._eval(call.args[0], p, ctx)
            if not isinstance(v, SeriesMatrix):
                raise PromqlError("absent needs an instant vector")
            lab = _absent_labels(call.args[0])
            if v.num_series == 0:
                return SeriesMatrix([lab], jnp.ones((1, p.T)))
            all_absent = jnp.isnan(v.values).all(axis=0)
            return SeriesMatrix(
                [lab], jnp.where(all_absent, 1.0, jnp.nan)[None, :])
        if fn == "histogram_quantile":
            return self._histogram_quantile(call, p, ctx)
        if fn == "label_replace":
            return self._label_replace(call, p, ctx)
        if fn == "label_join":
            return self._label_join(call, p, ctx)
        raise PromqlError(f"unsupported function {fn!r}")

    def _eval_range_func(self, call: Call, p: EvalParams, ctx,
                         fuse: bool = True):
        fn = call.func
        sel = call.args[0]
        if not isinstance(sel, (VectorSelector, Subquery)):
            raise PromqlError(f"{fn} needs a range selector argument")

        if fn in _GRID_FUNCS:
            plan = self._grid_plan(call, p, ctx, fuse)
            if plan is None:
                return SeriesMatrix([], jnp.zeros((0, p.T)))
            if plan.pivot is None:
                return self._grid_func_stepwise(plan, p)
            return SeriesMatrix(plan.loaded.labels, _run_on_grid(plan, p))

        if fn in ("irate", "idelta"):
            # last two samples in the window (reference functions/
            # instant_delta.rs): the window kernel's "last" gather carries
            # the previous-sample value/ts as extra channels
            r = self._range_stats(sel, p, ctx, ("count", "last"), ("prev",))
            if r is None:
                return SeriesMatrix([], jnp.zeros((0, p.T)))
            st, labels, metric, w, range_s = r
            last_v = st["last"][:, :, 0]
            prev_v = st["last"][:, :, 1]
            prev_t = st["last"][:, :, 2]
            last_t = st["last_ts"]
            wstart = h2d(p.times)[None, :] - range_s
            ok = (~jnp.isnan(prev_v)) & (prev_t > wstart) & (last_t > prev_t)
            if fn == "idelta":
                out = last_v - prev_v
            else:
                # counter semantics: reset -> delta is the raw new value
                delta = jnp.where(last_v < prev_v, last_v, last_v - prev_v)
                out = delta / (last_t - prev_t)
            return SeriesMatrix(labels, jnp.where(ok, out, jnp.nan))

        if fn == "absent_over_time":
            r = self._range_stats(sel, p, ctx, ("count",))
            lab = _absent_labels(sel)
            if r is None:
                return SeriesMatrix([lab], jnp.ones((1, p.T)))
            st, labels, metric, w, range_s = r
            any_present = (st["count"][:, :, 0] > 0).any(axis=0)
            return SeriesMatrix(
                [lab], jnp.where(any_present, jnp.nan, 1.0)[None, :])

        if fn == "holt_winters":
            return self._holt_winters(call, sel, p, ctx)

        if fn in ("changes", "resets"):
            r = self._range_stats(sel, p, ctx, ("sum", "count"), (fn,))
            if r is None:
                return SeriesMatrix([], jnp.zeros((0, p.T)))
            st, labels, metric, w, range_s = r
            present = st["count"][:, :, 0] > 0
            return SeriesMatrix(labels, jnp.where(present, st["sum"][:, :, 1], jnp.nan))

        if fn in ("deriv", "predict_linear"):
            r = self._range_stats(sel, p, ctx, ("sum", "count"), ("deriv",))
            if r is None:
                return SeriesMatrix([], jnp.zeros((0, p.T)))
            st, labels, metric, w, range_s = r
            n = st["count"][:, :, 0].astype(jnp.float64)
            sv, svt, t1, t2 = (st["sum"][:, :, i] for i in range(4))
            denom = n * t2 - t1 * t1
            slope = jnp.where((n >= 2) & (denom != 0), (n * svt - sv * t1) / denom, jnp.nan)
            if fn == "deriv":
                return SeriesMatrix(labels, slope)
            horizon = _scalar_of(self._eval(call.args[1], p, ctx))
            intercept = (sv - slope * t1) / jnp.maximum(n, 1)
            now_r = h2d(p.times)[None, :] - p.start
            return SeriesMatrix(labels, intercept + slope * (now_r + horizon))

        # *_over_time family
        stat_map = {
            "present_over_time": ("count",),
            "min_over_time": ("min", "count"), "max_over_time": ("max", "count"),
            "last_over_time": ("count", "last"),
            "stddev_over_time": ("sum", "count"), "stdvar_over_time": ("sum", "count"),
        }
        extra = ()
        if fn in ("stddev_over_time", "stdvar_over_time"):
            extra = ("sq",)
        stats = stat_map[fn]
        if fn in ("stddev_over_time", "stdvar_over_time"):
            r = self._range_stats_sq(sel, p, ctx)
        else:
            r = self._range_stats(sel, p, ctx, stats, extra)
        if r is None:
            return SeriesMatrix([], jnp.zeros((0, p.T)))
        st, labels, metric, w, range_s = r
        cnt = st["count"][:, :, 0]
        present = cnt > 0
        if fn == "present_over_time":
            out = jnp.where(present, 1.0, jnp.nan)
        elif fn == "min_over_time":
            out = st["min"][:, :, 0]
        elif fn == "max_over_time":
            out = st["max"][:, :, 0]
        elif fn == "last_over_time":
            out = st["last"][:, :, 0]
        elif fn in ("stddev_over_time", "stdvar_over_time"):
            s, sq = st["sum"][:, :, 0], st["sum"][:, :, 1]
            n = jnp.maximum(cnt.astype(jnp.float64), 1)
            var = jnp.maximum(sq / n - (s / n) ** 2, 0.0)  # population, like PromQL
            out = jnp.where(present, jnp.sqrt(var) if fn == "stddev_over_time" else var, jnp.nan)
        return SeriesMatrix(labels, out)

    def _grid_plan(self, call: Call, p: EvalParams, ctx,
                   fuse: bool = True) -> Optional["_GridPlan"]:
        """The host half of a `_GRID_FUNCS` call: the window in steps,
        the selector's samples and, where one program can answer from
        them — a selector's samples (no subquery's) on one complete
        grid — their pivot. None without a sample."""
        fn, sel = call.func, call.args[0]
        w, range_s = self._window_steps(sel, p)
        loaded = self._load_any(
            sel, p, ctx, window=range_s,
            extra_channels=("adjusted",) if fn in _COUNTER_FUNCS else ())
        if loaded is None:
            return None
        pivot = None
        if fuse and not isinstance(sel, Subquery) and _edges_enabled():
            pivot = loaded.pivot()
        return _GridPlan(fn, sel, loaded, w, range_s, pivot)

    def _grid_func_stepwise(self, plan: "_GridPlan",
                            p: EvalParams) -> SeriesMatrix:
        """A `_GRID_FUNCS` call a kernel at a time: what samples of no
        complete grid, with a NaN tombstone or of a subquery take
        (window_stats), and what `_run_on_grid` is held to."""
        fn = plan.fn
        if fn in _RATE_FUNCS:
            stats = ("count", "first", "last")
        else:
            stats = ("count",) if fn == "count_over_time" \
                else ("sum", "count")
        st = self._stats_of(plan.loaded, plan.sel, p, stats, plan.w)
        if fn in _RATE_FUNCS:
            vals = rate_of_edges(st, h2d(p.times), plan.range_s,
                                 fn in _COUNTER_FUNCS, fn == "rate")
        else:
            vals = over_time_of_stats(st, fn)
        return SeriesMatrix(plan.loaded.labels, vals)

    def _histogram_quantile(self, call: Call, p: EvalParams, ctx):
        """φ-quantile over `le`-bucketed classic histograms (reference
        extension_plan/histogram_fold.rs:61): the input series gathered
        into [groups, buckets, steps] by the fold index and folded by
        ONE kernel (ops/histogram.py), φ an operand."""
        phi = _scalar_of(self._eval(call.args[0], p, ctx))
        v = self._eval(call.args[1], p, ctx)
        if not isinstance(v, SeriesMatrix):
            raise PromqlError("histogram_quantile needs an instant vector")
        t0 = time.perf_counter()
        index, how = derive(v.labels, "histogram_fold", _build_fold_index)
        t1 = time.perf_counter()
        PROMQL_HISTOGRAM_FOLD_SECONDS.observe(t1 - t0, phase="index")
        PROMQL_HISTOGRAM_FOLDS.inc(index=how)
        G = len(index.labels)
        d_phi = h2d(np.float64(phi))  # an operand: one program for every φ
        with tracing.span("histogram_fold", groups=G,
                          buckets=int(index.src.shape[1]), steps=p.T,
                          index=how, skipped=index.skipped):
            if G == 0:
                return SeriesMatrix([], jnp.zeros((0, p.T)))
            counts = jnp.take(v.values, index.src, axis=0)  # [G', B, T]
            out = histogram_fold(counts, index.bounds, index.valid, d_phi)
            if out.shape[0] != G:
                out = out[:G]  # G' is G padded to a power of two
            PROMQL_HISTOGRAM_FOLD_SECONDS.observe(
                time.perf_counter() - t1, phase="dispatch")
        return SeriesMatrix(index.labels, out)

    def _holt_winters(self, call: Call, sel, p: EvalParams, ctx):
        """Double exponential smoothing (reference functions/
        holt_winters.rs). Sequential per-window recurrence — evaluated on
        host over the loaded samples (windows are small; the scan itself
        still rides the device path)."""
        sf = _scalar_of(self._eval(call.args[1], p, ctx))
        tf = _scalar_of(self._eval(call.args[2], p, ctx))
        if not 0 < sf < 1 or not 0 < tf < 1:
            raise PromqlError("holt_winters factors must be in (0, 1)")
        range_s = sel.range_s
        if range_s is None:
            raise PromqlError(
                "holt_winters needs a range vector (metric[duration])")
        loaded = self._load_any(sel, p, ctx, window=range_s)
        if loaded is None:
            return SeriesMatrix([], jnp.zeros((0, p.T)))
        sidx, ts, chans = loaded.flat()
        labels = loaded.labels
        sidx = d2h(sidx)
        ts = d2h(ts)
        vals = d2h(chans[:, 0])
        ok = ~np.isnan(vals)
        sidx, ts, vals = sidx[ok], ts[ok], vals[ok]
        S, T = len(labels), p.T
        out = np.full((S, T), np.nan)
        starts = np.searchsorted(sidx, np.arange(S))
        ends = np.searchsorted(sidx, np.arange(S), side="right")
        for s in range(S):
            s_ts = ts[starts[s]:ends[s]]
            s_v = vals[starts[s]:ends[s]]
            for j, t in enumerate(p.times):
                lo = np.searchsorted(s_ts, t - range_s, side="right")
                hi = np.searchsorted(s_ts, t, side="right")
                x = s_v[lo:hi]
                if len(x) < 2:
                    continue
                s0, b = x[0], x[1] - x[0]
                for i in range(1, len(x)):
                    s1 = sf * x[i] + (1 - sf) * (s0 + b)
                    b = tf * (s1 - s0) + (1 - tf) * b
                    s0 = s1
                out[s, j] = s0
        return SeriesMatrix(labels, h2d(out))

    def _range_stats_sq(self, sel, p, ctx):
        """Range stats with a squared-value channel (stddev/stdvar)."""
        range_s = sel.range_s
        w = int(round(range_s / p.step))
        loaded = self._load_any(sel, p, ctx, window=range_s)
        if loaded is None:
            return None
        sidx, ts, chans = loaded.flat()
        chans = jnp.concatenate([chans, chans[:, :1] ** 2], axis=1)
        st = window_stats(sidx, ts, chans, ~jnp.isnan(chans[:, 0]),
                          p.start, p.step, len(loaded.labels), p.T, w,
                          stats=("sum", "count"),
                          sorted_input=_sorted_ws())
        return st, loaded.labels, loaded.metric, w, range_s

    # ---- aggregation -------------------------------------------------------

    @staticmethod
    def _group_index(labels: list, agg: Aggregate) -> "_GroupIndex":
        """The group index depends on the input's label sets and the
        grouping alone: found beside the loaded series they derive
        from, or built (for a list of no known origin, per request)."""
        with tracing.stage("assemble", step="group_labels",
                           series=len(labels)) as attrs:
            grp, how = derive(labels, "group_index", _build_group_index,
                              agg.by, agg.without)
            attrs["index"] = how
        PROMQL_GROUP_INDEXES.inc(index=how)
        return grp

    def _eval_aggregate(self, agg: Aggregate, p: EvalParams, ctx,
                        fuse: bool = True):
        simple = agg.op in _AGG_STATS
        expr = agg.expr
        ranged = isinstance(expr, Call) and expr.func in _RANGE_FUNCS
        if simple and _grid_call(expr):
            # `agg by (...) (range_fn(m[w]))`: from the resident pivot
            # to its [groups, steps] answer in one program
            plan = self._grid_plan(expr, p, ctx, fuse)
            if plan is None:
                return SeriesMatrix([], jnp.zeros((0, p.T)))
            if plan.pivot is not None:
                grp = self._group_index(plan.loaded.labels, agg)
                PROMQL_EVAL_PROGRAMS.inc(path="fused")
                return SeriesMatrix(
                    grp.labels, _run_on_grid(plan, p, grp, agg.op))
            v = self._grid_func_stepwise(plan, p)
        else:
            v = self._eval(expr, p, ctx)
        if not isinstance(v, SeriesMatrix):
            raise PromqlError(f"{agg.op} needs an instant vector")
        if v.num_series == 0:
            return SeriesMatrix([], jnp.zeros((0, p.T)))
        grp = self._group_index(v.labels, agg)
        gidx, G, glabels = grp.gidx, grp.G, grp.labels

        vals = v.values  # [S, T]
        if simple:
            # an operand that is no range function (a binary expression,
            # a selector): the aggregation is a program of its own; a
            # range function that took window_stats keeps the kernel at
            # a time it has always run
            split = fuse and not ranged
            PROMQL_EVAL_PROGRAMS.inc(path="split" if split else "stepwise")
            fold = _agg if split else _fold_groups
            return SeriesMatrix(glabels, fold(
                vals, grp.d_gidx, grp.mask, num_groups=G, op=agg.op))
        PROMQL_EVAL_PROGRAMS.inc(path="stepwise")

        if agg.op in ("topk", "bottomk"):
            k = int(_scalar_of(self._eval(agg.param, p, ctx)))
            vv = vals if agg.op == "topk" else -vals
            filled = jnp.where(jnp.isnan(vv), -jnp.inf, vv)
            keep = jnp.zeros(vals.shape, bool)
            for g in range(G):
                rows = np.flatnonzero(gidx == g)
                sub = filled[rows]
                kk = min(k, len(rows))
                thresh = -jnp.sort(-sub, axis=0)[kk - 1]
                keep = keep.at[rows].set(sub >= thresh[None, :])
            out = jnp.where(keep & ~jnp.isnan(vals), vals, jnp.nan)
            return SeriesMatrix(v.labels, out, v.metric)

        if agg.op == "quantile":
            q = _scalar_of(self._eval(agg.param, p, ctx))
            outs = []
            for g in range(G):
                rows = np.flatnonzero(gidx == g)
                outs.append(jnp.nanquantile(vals[rows], q, axis=0))
            return SeriesMatrix(glabels, jnp.stack(outs, axis=0))

        if agg.op == "count_values":
            if not isinstance(agg.param, StringLiteral):
                raise PromqlError(
                    "count_values needs a string label parameter")
            label_name = agg.param.value
            vn = d2h(vals, dtype=np.float64)  # [S, T]
            S, T = vn.shape
            valid = ~np.isnan(vn)
            # sparse factorization: memory stays O(samples + series*T),
            # never a dense [G, D, T] cube (near-unique float values make
            # D ~ S*T)
            distinct, inv = np.unique(vn[valid], return_inverse=True)
            D = len(distinct)
            if D == 0:
                return SeriesMatrix([], jnp.zeros((0, p.T)))
            srow, scol = np.nonzero(valid)
            key = (gidx[srow].astype(np.int64) * D + inv) * T + scol
            uk, uc = np.unique(key, return_counts=True)
            gd = uk // T
            col = (uk % T).astype(np.int64)
            pairs, pair_inv = np.unique(gd, return_inverse=True)
            rows_m = np.full((len(pairs), T), np.nan)
            rows_m[pair_inv, col] = uc.astype(np.float64)
            out_labels2 = []
            for pair in pairs:
                lab = dict(glabels[int(pair // D)])
                lab[label_name] = _fmt_prom_value(float(distinct[pair % D]))
                out_labels2.append(lab)
            return SeriesMatrix(out_labels2, h2d(rows_m))

        raise PromqlError(f"unsupported aggregation {agg.op!r}")

    # ---- binary ops --------------------------------------------------------

    def _eval_binary(self, node: Binary, p: EvalParams, ctx):
        lhs = self._eval(node.lhs, p, ctx)
        rhs = self._eval(node.rhs, p, ctx)
        lv = isinstance(lhs, SeriesMatrix)
        rv = isinstance(rhs, SeriesMatrix)

        if node.op in ("and", "or", "unless"):
            if not (lv and rv):
                raise PromqlError(f"{node.op} needs vector operands")
            return _set_op(node, lhs, rhs, p)

        if not lv and not rv:
            a, b = _broadcast_scalar(lhs, p), _broadcast_scalar(rhs, p)
            out = _apply_op(node.op, a, b)
            if node.op in _CMP and not node.bool_mod:
                out = jnp.where(out != 0, a, jnp.nan)
            return out
        if lv and not rv:
            b = _broadcast_scalar(rhs, p)
            out = _apply_op(node.op, lhs.values, b[None, :])
            if node.op in _CMP:
                out = (out.astype(jnp.float64) if node.bool_mod
                       else jnp.where(out, lhs.values, jnp.nan))
            return SeriesMatrix(_strip(lhs.labels) if node.op not in _CMP or node.bool_mod else lhs.labels, out)
        if rv and not lv:
            a = _broadcast_scalar(lhs, p)
            out = _apply_op(node.op, a[None, :], rhs.values)
            if node.op in _CMP:
                out = (out.astype(jnp.float64) if node.bool_mod
                       else jnp.where(out, rhs.values, jnp.nan))
            return SeriesMatrix(_strip(rhs.labels) if node.op not in _CMP or node.bool_mod else rhs.labels, out)

        # vector-vector: join on signature
        lsig = [_signature(l, node) for l in lhs.labels]
        rsig = {_signature(l, node): i for i, l in enumerate(rhs.labels)}
        li, ri, labels = [], [], []
        for i, s in enumerate(lsig):
            j = rsig.get(s)
            if j is not None:
                li.append(i)
                ri.append(j)
                labels.append(_strip([lhs.labels[i]])[0] if not node.group_left
                              else lhs.labels[i])
        if not li:
            return SeriesMatrix([], jnp.zeros((0, p.T)))
        a = lhs.values[h2d(np.asarray(li))]
        b = rhs.values[h2d(np.asarray(ri))]
        out = _apply_op(node.op, a, b)
        if node.op in _CMP:
            out = out.astype(jnp.float64) if node.bool_mod else jnp.where(out, a, jnp.nan)
        return SeriesMatrix(labels, out)

    # ---- label functions ---------------------------------------------------

    def _label_replace(self, call: Call, p, ctx):
        v = self._eval(call.args[0], p, ctx)
        dst, repl, src, regex = (_string_of(a) for a in call.args[1:5])
        rx = re.compile(regex)
        labels = []
        for lab in v.labels:
            m = rx.fullmatch(lab.get(src, ""))
            lab = dict(lab)
            if m is not None:
                val = m.expand(repl.replace("$", "\\")) if "$" in repl else repl
                if val:
                    lab[dst] = val
                else:
                    lab.pop(dst, None)
            labels.append(lab)
        return SeriesMatrix(labels, v.values, v.metric, v.sample_ts)

    def _label_join(self, call: Call, p, ctx):
        v = self._eval(call.args[0], p, ctx)
        dst = _string_of(call.args[1])
        sep = _string_of(call.args[2])
        srcs = [_string_of(a) for a in call.args[3:]]
        labels = []
        for lab in v.labels:
            lab = dict(lab)
            lab[dst] = sep.join(lab.get(s, "") for s in srcs)
            labels.append(lab)
        return SeriesMatrix(labels, v.values, v.metric, v.sample_ts)


# ---- helpers ---------------------------------------------------------------

_CMP = {"==", "!=", "<", "<=", ">", ">="}


def _apply_op(op, a, b):
    if op == "+":
        return a + b
    if op == "-":
        return a - b
    if op == "*":
        return a * b
    if op == "/":
        return a / b
    if op == "%":
        return jnp.fmod(a, b)
    if op == "^":
        return jnp.power(a, b)
    if op == "==":
        return a == b
    if op == "!=":
        return a != b
    if op == "<":
        return a < b
    if op == "<=":
        return a <= b
    if op == ">":
        return a > b
    if op == ">=":
        return a >= b
    raise PromqlError(f"unknown operator {op}")


def _set_op(node: Binary, lhs: SeriesMatrix, rhs: SeriesMatrix, p: EvalParams):
    lsig = [_signature(l, node) for l in lhs.labels]
    rsigs = {_signature(l, node) for l in rhs.labels}
    if node.op == "and":
        keep = [i for i, s in enumerate(lsig) if s in rsigs]
        idx = np.asarray(keep, dtype=np.int64)
        # also require rhs sample present at t
        rmap = {_signature(l, node): i for i, l in enumerate(rhs.labels)}
        rsel = np.asarray([rmap[lsig[i]] for i in keep], dtype=np.int64)
        vals = jnp.where(~jnp.isnan(rhs.values[rsel]), lhs.values[idx], jnp.nan) \
            if keep else jnp.zeros((0, p.T))
        return SeriesMatrix([lhs.labels[i] for i in keep], vals, lhs.metric)
    if node.op == "unless":
        rmap = {_signature(l, node): i for i, l in enumerate(rhs.labels)}
        vals_list, labels = [], []
        for i, s in enumerate(lsig):
            j = rmap.get(s)
            if j is None:
                vals_list.append(lhs.values[i])
            else:
                vals_list.append(jnp.where(jnp.isnan(rhs.values[j]),
                                           lhs.values[i], jnp.nan))
            labels.append(lhs.labels[i])
        vals = jnp.stack(vals_list) if vals_list else jnp.zeros((0, p.T))
        return SeriesMatrix(labels, vals, lhs.metric)
    # or: lhs plus rhs series whose signature isn't in lhs
    lsigs = set(lsig)
    extra = [i for i, l in enumerate(rhs.labels)
             if _signature(l, node) not in lsigs]
    labels = list(lhs.labels) + [rhs.labels[i] for i in extra]
    vals = jnp.concatenate([lhs.values, rhs.values[h2d(np.asarray(extra, dtype=np.int64))]]
                           ) if extra else lhs.values
    return SeriesMatrix(labels, vals, lhs.metric)


def _signature(lab: dict, node: Binary) -> tuple:
    if node.on:
        return tuple((k, lab.get(k, "")) for k in node.on)
    items = {k: v for k, v in lab.items()}
    if node.ignoring:
        for k in node.ignoring:
            items.pop(k, None)
    return tuple(sorted(items.items()))


def _strip(labels: list[dict]) -> list[dict]:
    return [dict(l) for l in labels]


def _map_values(v, f):
    if isinstance(v, SeriesMatrix):
        return SeriesMatrix(v.labels, f(v.values))
    if isinstance(v, (int, float)):
        return f(h2d(v)).item() if False else float(f(h2d(float(v))))
    return f(v)


def _broadcast_scalar(v, p: EvalParams):
    if isinstance(v, SeriesMatrix):
        raise PromqlError("expected a scalar")
    if isinstance(v, (int, float)):
        return jnp.full(p.T, float(v))
    return h2d(v)


def _scalar_of(v) -> float:
    if isinstance(v, (int, float)):
        return float(v)
    arr = d2h(v)
    return float(arr.reshape(-1)[0])


def _string_of(node) -> str:
    if isinstance(node, StringLiteral):
        return node.value
    raise PromqlError("expected a string literal")


def _absent_labels(node) -> dict:
    """Prometheus derives absent()'s output labels from the selector's
    equality matchers."""
    sel = node
    if isinstance(sel, Subquery):
        sel = sel.expr
    if isinstance(sel, VectorSelector):
        return {m.label: m.value for m in sel.matchers
                if m.op == "=" and m.label not in ("__name__", "__field__")}
    return {}


def _sorted_ws() -> bool:
    """Bucketization flavor for window_stats: XLA lowers scatter-adds
    fine on CPU (measured 2.8x faster than the searchsorted/cumsum path
    at 9.6M samples), but on TPU scatters serialize row-by-row — there
    the sorted-input boundary path wins. Inputs are (series, ts)-sorted
    either way (_load lexsorts)."""
    import jax

    return jax.default_backend() == "tpu"


def _edges_enabled() -> bool:
    """Rate-family boundary evaluation (window_edges). On by default;
    =off pins the dense window_stats path (differential debugging)."""
    import os

    return os.environ.get("GREPTIMEDB_TPU_PROMQL_EDGES",
                          "on").lower() not in ("off", "0", "false")


@dataclass(frozen=True)
class _GroupIndex:
    """Which group each input series of an aggregation falls in: a
    function of the input's label sets and the grouping. Shared between
    requests and threads where it is kept: nothing writes to it."""

    gidx: np.ndarray  # [S] int32 group of each input series (read-only)
    d_gidx: jax.Array  # [S] the same on the device
    mask: jax.Array  # [S] bool on the device, all true
    G: int
    labels: list  # [G] the groups' label sets, in signature order


def _build_group_index(labels: list, by: tuple, without: tuple) -> _GroupIndex:
    """Keep `by`'s labels of each series, or all but `without`'s, or
    none; number the distinct signatures as first seen in one dictionary
    pass, rank them, and the group index is a gather (no search per
    series)."""
    sigs = []
    for lab in labels:
        if by:
            kept = {k: lab.get(k, "") for k in by if k in lab}
        elif without:
            kept = {k: x for k, x in lab.items() if k not in without}
        else:
            kept = {}
        sigs.append(tuple(sorted(kept.items())))
    seen: dict = {}
    first_seen = np.fromiter(
        (seen.setdefault(s, len(seen)) for s in sigs),
        dtype=np.int32, count=len(sigs))
    uniq = sorted(seen)
    rank = np.empty(len(uniq), dtype=np.int32)
    rank[[seen[u] for u in uniq]] = np.arange(len(uniq), dtype=np.int32)
    gidx = rank[first_seen]
    gidx.setflags(write=False)
    glabels = [dict(u) for u in uniq]
    if isinstance(labels, LabelSets):
        # the groups' label sets depend on the input's and on the
        # grouping alone
        glabels = labels.step(("group", by, without), glabels)
    return _GroupIndex(gidx, h2d(gidx), jnp.ones(len(sigs), bool),
                       len(uniq), glabels)


@dataclass
class _FoldIndex:
    """Where histogram_quantile's input series go in the [groups,
    buckets, steps] block: a function of the input's label sets."""

    labels: list  # [G] the groups' label sets: the input's minus `le`
    src: jax.Array  # [G', B] int32 input series of each bucket slot
    bounds: jax.Array  # [G', B] float64 `le` of each slot, ascending
    valid: jax.Array  # [G', B] bool: a bucket, not padding
    skipped: int  # input series whose `le` is no number


def _build_fold_index(labels: list) -> _FoldIndex:
    """Group the series by their labels minus `le` (groups in signature
    order), rank each group's buckets by `le` parsed as a float (`+Inf`
    as Prometheus spells it), pad the groups to the widest and their
    number to a power of two."""
    groups: dict = {}
    skipped = 0
    for i, lab in enumerate(labels):
        le_s = lab.get("le")
        if le_s is None:
            continue  # not a bucket series
        try:
            le = float(le_s)
        except (TypeError, ValueError):
            le = math.nan
        if math.isnan(le):
            skipped += 1
            continue
        rest = tuple(sorted((k, x) for k, x in lab.items() if k != "le"))
        groups.setdefault(rest, []).append((le, i))
    sigs = sorted(groups)
    G = len(sigs)
    B = max((len(groups[s]) for s in sigs), default=1)
    padded = 1 << max(G - 1, 0).bit_length()
    src = np.zeros((padded, B), dtype=np.int32)
    bounds = np.zeros((padded, B), dtype=np.float64)
    valid = np.zeros((padded, B), dtype=bool)
    for g, sig in enumerate(sigs):
        buckets = sorted(groups[sig])
        n = len(buckets)
        bounds[g, :n] = [b[0] for b in buckets]
        src[g, :n] = [b[1] for b in buckets]
        valid[g, :n] = True
    return _FoldIndex([dict(s) for s in sigs], h2d(src), h2d(bounds),
                      h2d(valid), skipped)


@dataclass
class _GridPlan:
    """The host half of a `_GRID_FUNCS` call: what `_load` found and
    the static facts of the program that answers from it."""

    fn: str
    sel: object  # the VectorSelector or Subquery
    loaded: Loaded
    w: int  # the window in steps
    range_s: float
    pivot: Optional[tuple]  # (grid [P], mat [S, P, C]), or None: stepwise


def _grid_call(node) -> bool:
    """Whether `node` is a range function a pivot can answer inside its
    aggregation's program: no subquery, no `@` (which pins the range's
    evaluation to one instant and broadcasts it)."""
    return isinstance(node, Call) and node.func in _GRID_FUNCS \
        and isinstance(node.args[0], VectorSelector) \
        and node.args[0].at_s is None


def _fold_groups(vals, gidx, mask, num_groups: int, op: str):
    """An `_AGG_STATS` operator over the series axis, [S, T] -> [G, T]:
    the segment reduction and its finish, NaN for a group without a
    sample. Pure: a program of its own (`_agg`), the tail of a fused
    one, or — called as it is — a kernel and its eager finish."""
    st = segment_agg(vals, gidx, mask, num_groups,
                     ops=tuple(sorted(set(_AGG_STATS[op]) | {"count"})))
    cnt = st["count"]
    present = cnt > 0
    if op == "sum":
        return jnp.where(present, st["sum"], jnp.nan)
    if op == "avg":
        return jnp.where(present, st["sum"] / jnp.maximum(cnt, 1), jnp.nan)
    if op in ("min", "max"):
        return st[op]
    if op == "count":
        return jnp.where(present, cnt.astype(jnp.float64), jnp.nan)
    if op == "group":
        return jnp.where(present, 1.0, jnp.nan)
    # stddev / stdvar (population)
    n = jnp.maximum(cnt.astype(jnp.float64), 1)
    # a square is its own maximum with 0: written out, it keeps the
    # square a rounded product wherever this is traced. Inside one
    # program the CPU compiler contracts a product that feeds a
    # subtraction into one fused multiply-add, and the cancelling
    # difference would read other digits than it does a kernel at a time
    # (tests/test_promql_fused.py holds the two to the same bits)
    mean_sq = jnp.maximum((st["sum"] / n) ** 2, 0.0)
    var = jnp.maximum(st["sumsq"] / n - mean_sq, 0.0)
    return jnp.where(present, var if op == "stdvar" else jnp.sqrt(var),
                     jnp.nan)


_agg = jax.jit(device_telemetry.kernel_name("promql_agg")(_fold_groups),
               static_argnames=("num_groups", "op"))

_RATE_STATIC = ("num_steps", "w", "is_counter", "is_rate")
_OVER_TIME_STATIC = ("n", "num_steps", "w", "fn")

_rate = jax.jit(device_telemetry.kernel_name("promql_rate")(grid_rate),
                static_argnames=_RATE_STATIC)
_over_time = jax.jit(
    device_telemetry.kernel_name("promql_over_time")(grid_over_time),
    static_argnames=_OVER_TIME_STATIC)


@functools.partial(jax.jit,
                   static_argnames=_RATE_STATIC + ("num_groups", "op"))
@device_telemetry.kernel_name("promql_rate_agg")
def _rate_agg(grid, mat, t0, step, range_s, gidx, mask, *, num_steps, w,
              is_counter, is_rate, num_groups, op):
    """`agg by (...) (rate | increase | delta (m[w]))` from the pivot to
    its [G, T] answer."""
    return _fold_groups(
        grid_rate(grid, mat, t0, step, range_s, num_steps, w, is_counter,
                  is_rate), gidx, mask, num_groups, op)


@functools.partial(jax.jit,
                   static_argnames=_OVER_TIME_STATIC + ("num_groups", "op"))
@device_telemetry.kernel_name("promql_over_time_agg")
def _over_time_agg(grid, mat, i0, t0, step, gidx, mask, *, n, num_steps, w,
                   fn, num_groups, op):
    """`agg by (...) (sum | avg | count _over_time (m[w]))` from the
    pivot to its [G, T] answer."""
    return _fold_groups(
        grid_over_time(grid, mat, i0, t0, step, n, num_steps, w, fn),
        gidx, mask, num_groups, op)


def _run_on_grid(plan: _GridPlan, p: EvalParams,
                 grp: Optional["_GroupIndex"] = None,
                 op: Optional[str] = None) -> jax.Array:
    """The pure half of `plan` as ONE program: its range function over
    the pivot, [S, T], or with a group index the aggregation `op` of
    that, [G, T]. The range's place in time (`t0`, `step`, the window's
    seconds, the own range's first point), the group index and the mask
    are operands: a range whose end moves runs the same executable."""
    grid, mat = plan.pivot
    fold, static = (), {}
    if grp is not None:
        fold = (grp.d_gidx, grp.mask)
        static = {"num_groups": grp.G, "op": op}
    if plan.fn in _RATE_FUNCS:
        run = _rate if grp is None else _rate_agg
        return run(grid, mat, p.start, p.step, plan.range_s, *fold,
                   num_steps=p.T, w=plan.w,
                   is_counter=plan.fn in _COUNTER_FUNCS,
                   is_rate=plan.fn == "rate", **static)
    i0, n = plan.loaded.cut or (0, int(grid.shape[0]))
    run = _over_time if grp is None else _over_time_agg
    return run(grid, mat, i0, p.start, p.step, *fold, n=n, num_steps=p.T,
               w=plan.w, fn=plan.fn, **static)


@jax.jit
@device_telemetry.kernel_name("promql_dedup")
def _promql_dedup(d_sidx, d_ts, d_vals, d_seq, d_op):
    """Non-append tables: last-write-wins by SEQ, not by scan position —
    compaction re-inserts merged files after newer flushes, so concat
    order is NOT write order. Sort by (series, ts) with seq as the
    tiebreaker, keep each duplicate run's last row, and blank it when
    that winner is a DELETE tombstone (the contract ops/dedup.py's
    sort_dedup enforces for SQL scans)."""
    from greptimedb_tpu.storage.region import OP_PUT

    order = jnp.lexsort((d_seq, d_ts, d_sidx))
    d_sidx, d_ts, d_vals, d_op = (d_sidx[order], d_ts[order],
                                  d_vals[order], d_op[order])
    nxt_s = jnp.concatenate([d_sidx[1:], jnp.full((1,), -1, d_sidx.dtype)])
    nxt_t = jnp.concatenate([d_ts[1:], jnp.full((1,), -jnp.inf)])
    dup_next = (d_sidx == nxt_s) & (d_ts == nxt_t)
    keep = ~dup_next & (d_op == OP_PUT)
    return d_sidx, d_ts, jnp.where(keep, d_vals, jnp.nan)


def _factorize_series(combined: np.ndarray) -> tuple:
    """(sorted distinct series keys, each row's index among them, a row
    permutation or None). Rows of one SST come series by series: where
    every series is ONE run of rows, the runs are ranked instead of the
    rows sorted, and if the runs do not ascend (a metric-engine table:
    label-set order is not tag order) the permutation that makes them
    ascend is returned for the caller to apply to its row arrays — the
    series index is then non-decreasing along the rows."""
    n = len(combined)
    cuts = np.flatnonzero(combined[1:] != combined[:-1]) + 1
    starts = np.concatenate([np.zeros(1, dtype=np.int64), cuts])
    uniq, rank = np.unique(combined[starts], return_inverse=True)
    if len(uniq) != len(starts):
        uniq, sidx = np.unique(combined, return_inverse=True)
        return uniq, sidx, None
    lens = np.diff(np.append(starts, n))
    if len(rank) < 2 or bool(np.all(np.diff(rank) > 0)):
        return uniq, np.repeat(rank, lens), None
    by_rank = np.argsort(rank)
    lens = lens[by_rank]
    ends = np.cumsum(lens)
    regroup = np.repeat(starts[by_rank] - (ends - lens), lens) \
        + np.arange(n, dtype=np.int64)
    return uniq, np.repeat(np.arange(len(uniq), dtype=np.int64), lens), \
        regroup


def _series_labels(uniq: np.ndarray, tag_names, sizes, tag_dicts) -> list:
    """The label set of each distinct series key: the mixed-radix key
    split per tag as arrays, the values gathered from the tag
    dictionaries; an absent tag (code -1) is left out of the set."""
    cols = []
    stride = 1
    for t, size in zip(reversed(tag_names), reversed(sizes)):
        code = (uniq // stride % size) - 1
        stride *= size
        vals = np.full(len(uniq), None, dtype=object)
        ok = code >= 0
        vals[ok] = np.asarray(tag_dicts[t], dtype=object)[code[ok]]
        cols.append(vals.tolist())
    cols.reverse()
    if all(None not in c for c in cols):
        return [dict(zip(tag_names, combo)) for combo in zip(*cols)]
    return [{t: v for t, v in zip(tag_names, combo) if v is not None}
            for combo in zip(*cols)]


def _matcher_mask(m: Matcher, scan, tag_names) -> np.ndarray:
    """Row mask for one label matcher, via the tag dictionary."""
    if m.label not in tag_names:
        # missing label behaves as empty string
        empty_match = (m.op == "=" and m.value == "") or \
            (m.op == "!=" and m.value != "") or \
            (m.op == "=~" and re.fullmatch(m.value, "") is not None) or \
            (m.op == "!~" and re.fullmatch(m.value, "") is None)
        return np.ones(scan.num_rows, bool) if empty_match else np.zeros(scan.num_rows, bool)
    codes = scan.columns[m.label]
    values = scan.tag_dicts[m.label]
    lut = np.zeros(len(values) + 1, dtype=bool)  # slot -1 -> last (empty)
    if m.op == "=":
        lut[:-1] = values == m.value if len(values) else False
        lut[-1] = m.value == ""
    elif m.op == "!=":
        lut[:-1] = values != m.value
        lut[-1] = m.value != ""
    else:
        rx = re.compile(m.value)
        hits = np.asarray([rx.fullmatch(str(x)) is not None for x in values], dtype=bool) \
            if len(values) else np.zeros(0, bool)
        empty_hit = rx.fullmatch("") is not None
        if m.op == "=~":
            lut[:-1] = hits
            lut[-1] = empty_hit
        else:
            lut[:-1] = ~hits
            lut[-1] = not empty_hit
    return lut[codes]


def _to_long_result(times: np.ndarray, result) -> QueryResult:
    """Matrix -> long-format table (tags..., ts, value), NaN cells dropped
    (matches the reference's TQL tabular output)."""
    if not isinstance(result, SeriesMatrix):
        with tracing.stage("readback"):
            arr = d2h(_broadcast_with(times, result))
        ts_ms = (times * 1000).astype(np.int64)
        return QueryResult(["ts", "value"],
                           [DataType.TIMESTAMP_MILLISECOND, DataType.FLOAT64],
                           [ts_ms, arr])
    with tracing.stage("readback"):
        vals = d2h(result.values)
    with tracing.stage("assemble"):
        return _long_table(times, result, vals)


def _long_table(times: np.ndarray, result, vals: np.ndarray) -> QueryResult:
    S, T = vals.shape if vals.size else (0, len(times))
    label_keys = sorted({k for lab in result.labels for k in lab})
    ts_ms = (times * 1000).astype(np.int64)
    rows_ts, rows_val = [], []
    rows_labels = {k: [] for k in label_keys}
    for s in range(S):
        present = ~np.isnan(vals[s])
        n = int(present.sum())
        if n == 0:
            continue
        rows_ts.append(ts_ms[present])
        rows_val.append(vals[s][present])
        for k in label_keys:
            rows_labels[k].append(np.full(n, result.labels[s].get(k), dtype=object))
    if rows_ts:
        ts_col = np.concatenate(rows_ts)
        val_col = np.concatenate(rows_val)
        lab_cols = {k: np.concatenate(v) for k, v in rows_labels.items()}
    else:
        ts_col = np.empty(0, np.int64)
        val_col = np.empty(0)
        lab_cols = {k: np.empty(0, object) for k in label_keys}
    names = label_keys + ["ts", "value"]
    dtypes = [DataType.STRING] * len(label_keys) + \
        [DataType.TIMESTAMP_MILLISECOND, DataType.FLOAT64]
    cols = [lab_cols[k] for k in label_keys] + [ts_col, val_col]
    return QueryResult(names, dtypes, cols)


def _broadcast_with(times, v):
    if isinstance(v, (int, float)):
        return np.full(len(times), float(v))
    return d2h(v)

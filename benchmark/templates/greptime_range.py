"""GreptimeDB range-query panels over table `cpu`, each with its plain
numpy reference: an operator's Grafana board over a host fleet.

The statements are the grammar of GreptimeDB v0.8's reference "SQL ->
RANGE QUERY" (docs/reference/sql/range.md):

    SELECT ts, <by>, agg(field) RANGE '<r>', ... FROM cpu
    WHERE [<tag predicate> AND] ts >= <end - W> AND ts < <end>
    ALIGN '<a>' BY (<by>) [FILL NULL | PREV | <constant>]
    ORDER BY <by>, ts

An output point at aligned time T aggregates the rows with
`T <= ts < T + r`; points step every `a` (`ALIGN` = the panel's
interval), series are keyed by `BY` (`BY ()`: one series), `r > a` gives
overlapping windows whose leading partial ones are emitted, a window
that holds no row is absent unless the statement's FILL makes it.

A template is a data file's entry (benchmark/traffic/<mix>.json `args`):

    {"window_s": 3600, "align_s": 60, "by": "hostname" | "region" | "",
     "items": [["avg", "usage_user", 300], ...],   # func, field, RANGE s
     "fill": null | "null" | "prev" | <number>,
     "hosts": 8            # WHERE hostname IN (<that many drawn hosts>)
     "datacenter": true}   # WHERE datacenter = '<a drawn datacenter>'

Drawn per request: `end`, 10 s-granular, uniform over [t0 + W, t_end]
(a window longer than the table's span is cut to the span); the hosts;
the datacenter. `edges()` gives the first and the last `end` the table
admits.

**The reference**, `range_reference`, reads the seeded arrays and numpy
only — no engine code: for each aligned T and each series, the PRESENT
rows (an outage's rows are absent, `ds.present`) with `T <= ts < T + r`
that the WHERE keeps, aggregated by definition in float64; FILL by
definition. No prefix sums, no buckets, nothing shared with the program.

**What is compared, and the limits** (PERF.md section 2 has the readings):

  set     which windows the answer holds — the observed and the filled —
          against the reference's: any difference reads inf.
  exact   max / min / count select or count stored values: the count of
          values that differ from the reference rounded to the compute
          dtype (a NULL must be a NULL), limit 0.
  mean    avg: the widest relative gap, added to the exact count. The
          limits are written at `LIMITS`.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark.harness.common import load_module

_tsbs = load_module("templates", "tsbs_devops")
round_to, LOWER = _tsbs.round_to, _tsbs.LOWER

# `mean` is `tsbs_devops.LIMITS`' (2e-5 in float32, 1e-12 in float64), as
# the other SQL cells'. What it stands between, in float32 (PERF.md
# section 2): a window's avg is a sum of float32 per-part, per-bucket
# sums (ops/segment.py float_segment_sum) combined in float64, over
# 30 rows (`range-hosts-1h`) to 240,000 (`range-fleet-total-3h`: 10 min x
# 4,000 hosts x 6 a minute). Sound answers read at most 5.58e-6 on the
# chip (`range-fleet-by-region-6h`, whose 657 groups of 13,000 rows a
# bucket sum in one float32 pass: about sqrt(n) x 2^-24; 2.1e-7 and
# 1.4e-7 for the other two avg templates, which sum in two levels; three
# runs, PR 44) and 3.58e-6 / 8.6e-8 / 7.8e-8 in this program's
# float32 arithmetic on a CPU backend at 4,000 hosts x 3 h. The control
# (inputs rounded to bfloat16, float32 sums, numpy, the cell's own size)
# reads 4.3e-5 to 8.6e-5 there: its rounding averages out as 1 / sqrt(n)
# over 13,000-40,000 values a window, so it lies only 2.2 times over
# the limit where `double-groupby-*`'s lies 23 times over; the cell's
# other templates fail the control by `exact` (111,900 to 520 values).
LIMITS = {"exact": _tsbs.LIMITS["exact"], "mean": _tsbs.LIMITS["mean"]}
EXACT = ("max", "min", "count")
STEP_MS = 10_000  # `end` is drawn on the data's own 10 s grid


def _acc(precision: str):
    return np.float64 if precision == "float64" else np.float32


def _fold(per_col: np.ndarray, series: np.ndarray, n: int, ufunc, init):
    out = np.full(n, init, per_col.dtype)
    ufunc.at(out, series, per_col)
    return out


def _window_agg(func: str, x: np.ndarray, m: np.ndarray, ts: np.ndarray,
                series: np.ndarray, n: int, acc) -> np.ndarray:
    """One aggregate of one window: `x`, `m` [rows, columns] the values
    and which rows count, `ts` [rows]; folded to the columns' series.
    NaN where the aggregate is NULL."""
    v = m & ~np.isnan(x)
    cnt = _fold(v.sum(axis=0), series, n, np.add, 0)
    if func == "count":
        return cnt.astype(np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        if func in ("sum", "avg", "stddev", "variance"):
            total = _fold(np.where(v, x, 0).sum(axis=0, dtype=acc), series,
                          n, np.add, 0)
            if func == "sum":
                return np.where(cnt > 0, total, np.nan).astype(np.float64)
            mean = total / cnt.astype(acc)
            if func == "avg":
                return np.where(cnt > 0, mean, np.nan).astype(np.float64)
            dev = np.where(v, (x - mean[series][None, :]) ** 2, 0)
            var = _fold(dev.sum(axis=0, dtype=acc), series, n, np.add, 0) \
                / (cnt - 1)
            var = np.where(cnt > 1, var, np.nan).astype(np.float64)
            return np.sqrt(var) if func == "stddev" else var
        if func in ("min", "max"):
            big = np.inf if func == "min" else -np.inf
            red = np.minimum if func == "min" else np.maximum
            out = _fold(red.reduce(np.where(v, x, big), axis=0, initial=big),
                        series, n, red, big)
            return np.where(cnt > 0, out, np.nan).astype(np.float64)
    if func in ("first_value", "last_value"):
        # the row with the least / greatest ts of the series, NULL or not
        rows = m.shape[0]
        idx = m.argmax(axis=0) if func == "first_value" \
            else rows - 1 - m[::-1].argmax(axis=0)
        has = m.any(axis=0)
        cols = np.flatnonzero(has)
        out = np.full(n, np.nan)
        when = ts[idx[cols]] if func == "first_value" else -ts[idx[cols]]
        for c in cols[np.lexsort((cols, when))][::-1]:
            out[series[c]] = x[idx[c], c]  # the best-ranked is written last
        return out
    raise ValueError(f"no reference for {func!r}")


def _kept(present: np.ndarray, where):
    """(p0, p1, columns, keep[p0:p1, columns]): the rows that exist and
    that the statement keeps, cut to the points and columns that hold
    one (the others change nothing and are not read); None for none."""
    if isinstance(where, tuple):
        pts, cols = np.flatnonzero(where[0]), np.flatnonzero(where[1])
        if not len(pts) or not len(cols):
            return None
        p0, p1 = int(pts[0]), int(pts[-1]) + 1
        keep = present[p0:p1][:, cols] & where[0][p0:p1, None]
        base, cols0 = p0, cols
    else:
        keep = present & np.broadcast_to(where, present.shape)
        base, cols0 = 0, np.arange(present.shape[1])
    pts, cols = np.flatnonzero(keep.any(axis=1)), np.flatnonzero(
        keep.any(axis=0))
    if not len(pts):
        return None
    p0, p1 = int(pts[0]), int(pts[-1]) + 1
    return base + p0, base + p1, cols0[cols], keep[p0:p1][:, cols]


def _grid(ts: np.ndarray, r_max: int, align: int, origin: int):
    """Every aligned T some row of `ts` can lie in [T, T + r_max) of."""
    k0 = (int(ts[0]) - r_max - origin) // align + 1  # T > ts[0] - r_max
    k1 = (int(ts[-1]) - origin) // align             # T <= ts[-1]
    return origin + np.arange(k0, k1 + 1, dtype=np.int64) * align


def range_reference(ts, series, values, present, where, align: int,
                    origin: int, ranges, funcs, fill,
                    precision: str = "float64") -> tuple:
    """A range statement's answer, by definition.

    `ts` int64[P] ascending: the points' times; `series` int[H]: the
    output series of each column (`BY`); `values`: one float64[P, H] an
    aggregate; `present` bool[P, H]: which rows exist; `where`: which
    rows the statement keeps — bool broadcastable to [P, H], or a
    (points bool[P], columns bool[H]) pair; `align`, `origin` in ts's
    unit; `ranges`, `funcs`, `fill` one an aggregate (`fill`: None |
    "null" | "prev" | a number).

    Returns (keys, vals float64[n, A], filled bool[n]): the output
    points (series, T) in (series, T) order, NaN for NULL, and which of
    them FILL made. `precision` below float64 computes the control:
    inputs rounded to it, sums in float32."""
    n_aggs = len(funcs)
    kept = _kept(present, where)
    if kept is None:
        return [], np.empty((0, n_aggs)), np.empty(0, bool)
    p0, p1, cols, keep = kept
    ts = np.asarray(ts, np.int64)[p0:p1]
    series = np.asarray(series, np.int64)[cols]
    acc = _acc(precision)
    values = [round_to(precision, v[p0:p1][:, cols]).astype(acc)
              for v in values]
    ids, series = np.unique(series, return_inverse=True)
    n = len(ids)

    grid = _grid(ts, max(ranges), align, origin)
    held = np.zeros((n, len(grid)), bool)
    vals = np.full((n_aggs, n, len(grid)), np.nan)
    for t, start in enumerate(grid):
        for a in range(n_aggs):
            lo = int(np.searchsorted(ts, start, side="left"))
            hi = int(np.searchsorted(ts, start + ranges[a], side="left"))
            m = keep[lo:hi]
            rows = _fold(m.sum(axis=0), series, n, np.add, 0)
            held[:, t] |= rows > 0
            vals[a, :, t] = _window_agg(funcs[a], values[a][lo:hi], m,
                                        ts[lo:hi], series, n, acc)
    vals[:, ~held] = np.nan  # no row, no point: only FILL makes one
    emitted = held.copy()
    if any(f is not None for f in fill) and held.any():
        # every series that has a point gets one at every T between the
        # first and the last point of the whole answer
        some = np.flatnonzero(held.any(axis=0))
        t_lo, t_hi = int(some[0]), int(some[-1]) + 1
        emitted[:, t_lo:t_hi] |= held.any(axis=1)[:, None]
        for a, policy in enumerate(fill):
            if policy is None or policy == "null":
                continue
            if policy == "linear":
                raise ValueError("FILL LINEAR has no reference here")
            last = np.full(n, np.nan)
            for t in range(t_lo, t_hi):
                made = emitted[:, t] & ~held[:, t]
                vals[a, made, t] = last[made] if policy == "prev" \
                    else float(policy)
                last = np.where(emitted[:, t], vals[a, :, t], last)
    s_idx, t_idx = np.nonzero(emitted)
    keys = [(int(ids[s]), int(grid[t])) for s, t in zip(s_idx, t_idx)]
    return keys, vals[:, s_idx, t_idx].T.copy(), ~held[s_idx, t_idx]


def window_count(ts, series, present, where, align: int, origin: int,
                 ranges, fill) -> int:
    """How many output points `range_reference` gives, without their
    values: what every request's row count is checked against."""
    kept = _kept(present, where)
    if kept is None:
        return 0
    p0, p1, cols, keep = kept
    ts = np.asarray(ts, np.int64)[p0:p1]
    ids, idx = np.unique(np.asarray(series)[cols], return_inverse=True)
    # which points hold a row of each series
    any_row = np.zeros((len(ts), len(ids)), bool)
    for s in range(len(ids)) if len(ids) < len(cols) else ():
        any_row[:, s] = keep[:, idx == s].any(axis=1)
    if len(ids) == len(cols):
        any_row[:, idx] = keep
    r_max = max(ranges)
    grid = _grid(ts, r_max, align, origin)
    lo = np.searchsorted(ts, grid, side="left")
    hi = np.searchsorted(ts, grid + r_max, side="left")
    held = np.stack([any_row[a:b].any(axis=0) for a, b in zip(lo, hi)],
                    axis=1)
    if any(f is not None for f in fill) and held.any():
        some = np.flatnonzero(held.any(axis=0))
        return int(held.any(axis=1).sum()) * int(some[-1] - some[0] + 1)
    return int(held.sum())


def _interval(seconds: int) -> str:
    return f"{seconds}s" if seconds % 60 else f"{seconds // 60}m"


class _Panel(_tsbs._Sql):
    """One range statement of the board."""

    kind = "mean"

    def __init__(self, name: str, args: dict):
        super().__init__(name)
        self.window_ms = int(args["window_s"]) * 1000
        self.align_s = int(args["align_s"])
        self.by = args.get("by", "")
        self.items = [(f, field, int(r)) for f, field, r in args["items"]]
        self.fill = args.get("fill")
        self.n_hosts = int(args.get("hosts", 0))
        self.by_datacenter = bool(args.get("datacenter", False))
        if all(f in EXACT for f, _, _ in self.items):
            self.kind = "exact"
        self._refs: dict = {}

    def limit(self, dtype: str) -> float:
        return LIMITS[self.kind][dtype]

    # -- what is drawn

    def _span(self, ds) -> int:
        return min(self.window_ms, ds.t_end_ms - ds.t0_ms)

    def draw(self, rng, ds) -> dict:
        first = ds.t0_ms + self._span(ds)
        p = {"end": first + STEP_MS * int(rng.integers(
            0, (ds.t_end_ms - first) // STEP_MS + 1))}
        return self._draw_filter(p, rng, ds)

    def _draw_filter(self, p: dict, rng, ds) -> dict:
        if self.n_hosts:
            hosts = rng.choice(ds.hosts, size=min(self.n_hosts, ds.hosts),
                               replace=False)
            p["hosts"] = sorted(int(h) for h in hosts)
        if self.by_datacenter:
            dcs = sorted(set(ds.tag_values["datacenter"]))
            p["datacenter"] = dcs[int(rng.integers(0, len(dcs)))]
        return p

    def edges(self, ds) -> list:
        """The first and the last `end` the table admits (the window's
        bucket count differs with the end's place in its ALIGN step)."""
        rng = np.random.default_rng([20260927, 9, self.window_ms])
        return [self._draw_filter({"end": end}, rng, ds)
                for end in (ds.t0_ms + self._span(ds), ds.t_end_ms)]

    # -- the statement

    def sql(self, p: dict, ds) -> str:
        items = ", ".join(f"{f}({field}) RANGE '{_interval(r)}'"
                          for f, field, r in self.items)
        where = ""
        if "hosts" in p:
            where = _tsbs._hosts_sql(p["hosts"]) + " AND "
        if "datacenter" in p:
            where = f"datacenter = '{p['datacenter']}' AND "
        by = f", {self.by}" if self.by else ""
        fill = "" if self.fill is None else \
            f" FILL {str(self.fill).upper()}"
        order = f"{self.by}, ts" if self.by else "ts"
        return (f"SELECT ts{by}, {items} FROM {ds.table} WHERE {where}"
                f"ts >= {p['end'] - self._span(ds)} AND ts < {p['end']} "
                f"ALIGN '{_interval(self.align_s)}' BY ({self.by}){fill} "
                f"ORDER BY {order}")

    # -- the reference

    def _labels(self, ds) -> list:
        """The BY value of each output series id."""
        if self.by == "hostname":
            return ds.tag_values["hostname"]
        if self.by:
            return sorted(set(ds.tag_values[self.by]))
        return [""]

    def _inputs(self, p: dict, ds) -> dict:
        ts = ds.t0_ms + np.arange(ds.points, dtype=np.int64) * ds.step_ms
        cols = np.ones(ds.hosts, bool)
        if "hosts" in p:
            cols = np.zeros(ds.hosts, bool)
            cols[p["hosts"]] = True
        if "datacenter" in p:
            cols = np.asarray(ds.tag_values["datacenter"]) == p["datacenter"]
        if self.by == "hostname":
            series = np.arange(ds.hosts)
        elif self.by:
            code = {v: i for i, v in enumerate(self._labels(ds))}
            series = np.asarray([code[v] for v in ds.tag_values[self.by]])
        else:
            series = np.zeros(ds.hosts, np.int64)
        points = (ts >= p["end"] - self._span(ds)) & (ts < p["end"])
        return {"ts": ts, "series": series, "present": ds.present,
                "where": (points, cols), "align": self.align_s * 1000,
                "origin": 0, "ranges": [r * 1000 for _, _, r in self.items],
                "fill": [self.fill] * len(self.items)}

    def reference(self, p: dict, ds, precision: str = "float64") -> tuple:
        """(keys in the ORDER BY's order, values [n, items])."""
        keys, vals, _filled = range_reference(
            values=[ds.fields[field] for _, field, _ in self.items],
            funcs=[f for f, _, _ in self.items], precision=precision,
            **self._inputs(p, ds))
        labels = self._labels(ds)
        order = sorted(range(len(keys)),
                       key=lambda i: (labels[keys[i][0]], keys[i][1]))
        return ([(labels[keys[i][0]], keys[i][1]) for i in order],
                vals[order])

    def expected_rows(self, p: dict, ds) -> int:
        i = self._inputs(p, ds)
        return window_count(i["ts"], i["series"], i["present"], i["where"],
                            i["align"], i["origin"], i["ranges"], i["fill"])

    def decode(self, rows: list, p: dict, ds) -> tuple:
        k = 2 if self.by else 1
        return ([(r[1] if self.by else "", r[0]) for r in rows],
                np.asarray([[np.nan if v is None else v for v in r[k:]]
                            for r in rows], np.float64).reshape(
                                len(rows), len(self.items)))

    def compare(self, rows: list, params: dict, ds, dtype: str,
                lowered: bool = False) -> float:
        """The number compared for one answer (module docstring); inf
        where the windows differ. `lowered` compares the control: the
        reference computed one precision below `dtype`."""
        slot = json.dumps(params, sort_keys=True)
        if slot not in self._refs:
            if len(self._refs) >= 4:
                self._refs.pop(next(iter(self._refs)))
            self._refs[slot] = self.reference(params, ds)
        keys, ref = self._refs[slot]
        if lowered:
            got_keys, got = self.reference(params, ds, LOWER[dtype])
        else:
            got_keys, got = self.decode(rows, params, ds)
        if got_keys != keys or got.shape != ref.shape:
            return float("inf")
        differ, gap = 0.0, 0.0
        for j, (func, _, _) in enumerate(self.items):
            g, r = got[:, j], ref[:, j]
            null = np.isnan(r)
            # a NULL must be a NULL, whatever the aggregate
            differ += float((np.isnan(g) != null).sum())
            both = ~null & ~np.isnan(g)
            g, r = g[both], r[both]
            if func in EXACT:
                differ += float((g != round_to(dtype, r)).sum())
            elif len(r):
                gap = max(gap, float(np.max(
                    np.abs(g - r) / np.maximum(np.abs(r), 1e-300))))
        return differ + gap


def make(template: str, args: dict | None = None):
    if not args:
        raise KeyError(f"range template {template!r} needs its args")
    return _Panel(template, args)

"""Family `prom_slo`: the latency / SLO panels of
prometheus.io/docs/practices/histograms/ over `datasets/prom_http_fleet.py`
(a classic histogram and a request counter per pod and handler), each
with its plain numpy reference. Three templates; the panel is the
traffic entry's `args` (all take `window_s`, `step_s`, `range_s`):

  quantile  {"phi": 0.99, "by": "<label>", "match": {"<label>": "<value>"}}
            histogram_quantile(<phi>, sum by (le, <by>)
              (rate(http_request_duration_seconds_bucket{<match>}[<w>])))
  ratio     {"metric": "http_requests_total", "by": "<label>",
             "num": {"<label>": "<anchored regex>"}}
            sum by (<by>) (rate(<metric>{<label>=~"<regex>"}[<w>]))
              / sum by (<by>) (rate(<metric>[<w>]))
  apdex     {"by": "<label>", "satisfied": "<le>", "tolerated": "<le>"}
            (sum by (<by>) (rate(..._bucket{le="<satisfied>"}[<w>]))
              + sum by (<by>) (rate(..._bucket{le="<tolerated>"}[<w>])))
              / 2 / sum by (<by>) (rate(..._count[<w>]))

The text is built here from those args, so the reference and the
request cannot disagree. Ranges, draws, edges, the request and its
parsing are `promql_board`'s (a trailing `range_s` whose `end` is drawn
step-aligned from [t0 + range_s + window_s, the last sample]).

References. Label matching is by plain dict keys over the dataset's
`series_tags()` (`re.fullmatch` for a regex). Every `sum by (...)
(rate(...))` is `promql_board`'s own reference — Prometheus'
extrapolatedRate rules in numpy — so its arithmetic is imported, not
copied: it is handed the selected series a few groups at a time under
one composite group label (its own fold of the groups costs groups x
series x steps, so each call gets one large group, or small groups of
at most 64 series together; no group is split). The quantile is
`bucket_quantile` below: Prometheus' bucketQuantile (promql/quantile.go)
written from its description in plain numpy float64, one group at a
time, independent of the program's ops/.

What is compared: the widest relative gap over every point of every
series of the answer. The control (`lowered`) is the same arithmetic on
float32 samples: the rates and their sums in float32, the division or
the fold after them in float64.

Limits (PERF.md section 2 has the readings). `ratio` and `apdex` are
sums of rates divided: `promql_board`'s 1e-10. `quantile` interpolates
lower + (upper - lower) * (phi * total - below) / inside, and the
difference phi * total - below cancels: a relative error e of the
summed rates becomes about e * total / inside of the answer, where
`inside` is the count of the bucket the quantile falls in — at p99 a
bucket that holds a few per cent of a handler's requests, or a few
thousandths. So its limit is 1e-8: rate's 1e-10 times a conditioning
of 100, no further; the float32 control reads above 1e-6.
"""

from __future__ import annotations

import re
import urllib.parse

import numpy as np

from benchmark.harness.common import load_module

BASE = "http_request_duration_seconds"
LIMIT = 1e-10
QUANTILE_LIMIT = 1e-8
_GROUP = "__group"
_SMALL = 64  # series handed to one reference call where groups are small


def bucket_quantile(bounds, counts, phi: float) -> np.ndarray:
    """Prometheus' bucketQuantile for ONE histogram: `bounds` [B] the
    buckets' upper bounds (`le`, any order), `counts` [B] or [B, T]
    their cumulative counts (at T steps), `phi` the quantile. Returns
    the quantile per step ([T], or a 0-d array).

    The rules, in the order promql/quantile.go applies them: NaN phi ->
    NaN; phi < 0 -> -Inf; phi > 1 -> +Inf; buckets sorted by bound; the
    highest must be +Inf, else NaN; counts made monotone (each at least
    its predecessor); fewer than two buckets -> NaN; no observations ->
    NaN; rank = phi * observations; the first bucket but the last whose
    count reaches the rank, else the last; in the last the answer is
    the highest finite bound; in a first bucket whose bound is <= 0,
    that bound; else the bucket's lower bound (0 for the first) plus its
    width times (rank - count below) / (count inside). Departures
    shared with the program: an absent (NaN) count is 0, buckets of
    equal bound are not coalesced, and 0 / 0 (phi = 0 over an empty
    first bucket) reads the lower bound."""
    bounds = np.asarray(bounds, dtype=np.float64)
    counts = np.asarray(counts, dtype=np.float64)
    shape = counts.shape[1:]
    if np.isnan(phi):
        return np.full(shape, np.nan)
    if phi < 0:
        return np.full(shape, -np.inf)
    if phi > 1:
        return np.full(shape, np.inf)
    order = np.argsort(bounds, kind="stable")
    bounds, counts = bounds[order], np.nan_to_num(counts[order])
    if len(bounds) < 2 or not np.isposinf(bounds[-1]):
        return np.full(shape, np.nan)
    flat = np.maximum.accumulate(counts, axis=0).reshape(len(bounds), -1)
    out = np.full(flat.shape[1], np.nan)
    bounds = bounds.tolist()
    for t in range(flat.shape[1]):
        col = flat[:, t].tolist()
        observations = col[-1]
        if observations == 0:
            continue
        rank = phi * observations
        b = len(col) - 1
        for i in range(len(col) - 1):
            if col[i] >= rank:
                b = i
                break
        if b == len(col) - 1:
            out[t] = bounds[-2]
        elif b == 0 and bounds[0] <= 0:
            out[t] = bounds[0]
        else:
            start = bounds[b - 1] if b > 0 else 0.0
            below = col[b - 1] if b > 0 else 0.0
            inside = col[b] - below
            out[t] = start + (bounds[b] - start) * (
                (rank - below) / inside if inside > 0 else 0.0)
    return out.reshape(shape)


def _window(seconds: int) -> str:
    return f"{seconds // 60}m" if seconds % 60 == 0 else f"{seconds}s"


def _selector(metric: str, matchers: list) -> str:
    """`matchers`: [(label, op, value)], rendered in label order."""
    if not matchers:
        return metric
    return metric + "{" + ",".join(
        f'{k}{op}"{v}"' for k, op, v in sorted(matchers)) + "}"


class _Chunk:
    """Some series of one view, as `promql_board`'s `range` template
    reads a dataset: the view's own attributes, those columns of its
    matrix under `val`, one composite label naming each series'
    group."""

    def __init__(self, view, idx: np.ndarray, groups: list):
        self._view, self._idx, self._groups = view, idx, groups
        self.series = len(idx)

    def __getattr__(self, name: str):
        return getattr(self._view, name)

    @property
    def fields(self) -> dict:
        (matrix,) = self._view.fields.values()
        return {"val": matrix[:, self._idx]}

    def series_tags(self) -> dict:
        return {_GROUP: self._groups}


class _Selected:
    """The series of one view that a selector keeps, grouped by the
    `by` labels: `names` (a tuple of label values per group, sorted)
    and `chunks`, each one group whole or several small ones."""

    def __init__(self, view, matchers: list, by: tuple):
        tags = view.series_tags()
        keep = np.ones(view.series, bool)
        for label, op, value in matchers:
            have = tags.get(label) or [""] * view.series
            test = value.__eq__ if op == "=" else re.compile(value).fullmatch
            hit = {v: bool(test(v)) for v in set(have)}
            keep &= np.asarray([hit[v] for v in have], dtype=bool)
        members: dict = {}
        for i in np.flatnonzero(keep).tolist():
            members.setdefault(tuple(tags[k][i] for k in by), []).append(i)
        self.names = sorted(members)
        self.chunks, batch = [], []
        for name in self.names:
            label = "\x1f".join(name)
            if len(members[name]) > _SMALL:
                self.chunks.append([(i, label) for i in members[name]])
                continue
            if len(batch) + len(members[name]) > _SMALL:
                self.chunks.append(batch)
                batch = []
            batch += [(i, label) for i in members[name]]
        if batch:
            self.chunks.append(batch)
        self.chunks = [_Chunk(view, np.asarray([i for i, _ in c]),
                              [g for _, g in c]) for c in self.chunks]


class _Panel:
    """What the three templates share: the range arithmetic of
    `promql_board`'s `range` template and its summed-rate reference."""

    def __init__(self, name: str, args: dict):
        self.name = name
        self.window_s = int(args["window_s"])
        self.step_s = int(args["step_s"])
        self.range_s = int(args["range_s"])
        self.by = args["by"]
        self._range = load_module("templates", "promql_board").make(
            "range", {"fn": "rate", "agg": "sum", "by": _GROUP,
                      "window_s": self.window_s, "step_s": self.step_s,
                      "range_s": self.range_s})
        self._kept: dict = {}

    # -- the request: promql_board's, over this panel's text

    def draw(self, rng, ds) -> dict:
        return self._range.draw(rng, ds)

    def edges(self, ds) -> list:
        return self._range.edges(ds)

    def request(self, p: dict, ds) -> tuple:
        q = urllib.parse.urlencode({
            "query": self.query(ds), "start": p["start"], "end": p["end"],
            "step": self.step_s})
        return "GET", "/v1/prometheus/api/v1/query_range?" + q, b""

    def parse(self, status: int, data: bytes) -> tuple:
        return self._range.parse(status, data)

    def rate(self, metric: str, matchers: list) -> str:
        return f"rate({_selector(metric, matchers)}[{_window(self.window_s)}])"

    # -- the reference

    def selected(self, ds, metric: str, matchers: list, by: tuple):
        """The selector's series (kept per dataset: a run has one)."""
        key = (id(ds), metric, tuple(matchers), by)
        if key not in self._kept:
            self._kept[key] = _Selected(ds.view(metric), matchers, by)
        return self._kept[key]

    def summed_rate(self, p: dict, ds, metric: str, matchers: list,
                    by: tuple, precision: str) -> tuple:
        """`sum by (<by>) (rate(<metric>{<matchers>}[w]))` ->
        ([a tuple of label values per group, sorted], step times,
        [G, T])."""
        sel = self.selected(ds, metric, matchers, by)
        row = {"\x1f".join(n): g for g, n in enumerate(sel.names)}
        out = times = None
        for chunk in sel.chunks:
            labels, times, part = self._range.reference(p, chunk, precision)
            if out is None:
                out = np.empty((len(sel.names), len(times)))
            out[[row[g] for g in labels]] = part
        return sel.names, times, out

    def limit(self, dtype: str) -> float:
        return LIMIT

    def expected_rows(self, p: dict, ds) -> int:
        return len(self.groups(ds))

    def compare(self, result: list, p: dict, ds, dtype: str,
                lowered: bool = False) -> float:
        names, times, ref = self.reference(p, ds)
        if lowered:
            got = self.reference(p, ds, "float32")[2]
        else:
            by_name = {str(s["metric"].get(self.by, "")): s["values"]
                       for s in result}
            if sorted(by_name) != sorted(names) \
                    or any(set(s["metric"]) - {self.by} for s in result):
                return float("inf")
            got = np.empty(ref.shape)
            for g, name in enumerate(names):
                vals = by_name[name]
                if [int(float(t)) for t, _ in vals] != times.tolist():
                    return float("inf")
                got[g] = [float(v) for _, v in vals]
        if not np.isfinite(got).all() or not np.isfinite(ref).all():
            return float("inf")
        return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref),
                                                           1e-300)))


class _Quantile(_Panel):
    def __init__(self, name: str, args: dict):
        super().__init__(name, args)
        self.phi = float(args["phi"])
        self.match = [(k, "=", v) for k, v in
                      sorted((args.get("match") or {}).items())]

    def query(self, ds) -> str:
        return (f"histogram_quantile({self.phi!r}, sum by (le, {self.by}) "
                f"({self.rate(BASE + '_bucket', self.match)}))")

    def limit(self, dtype: str) -> float:
        return QUANTILE_LIMIT

    def groups(self, ds) -> list:
        return sorted({n[0] for n in self.selected(
            ds, BASE + "_bucket", self.match, (self.by, "le")).names})

    def reference(self, p: dict, ds, precision: str = "float64") -> tuple:
        """(group names, step times [T], quantiles [G, T])."""
        keys, times, sums = self.summed_rate(
            p, ds, BASE + "_bucket", self.match, (self.by, "le"), precision)
        groups: dict = {}
        for row, (name, le) in enumerate(keys):
            groups.setdefault(name, []).append((float(le), row))
        names = sorted(groups)
        out = np.empty((len(names), len(times)))
        for g, name in enumerate(names):
            rows = [r for _, r in groups[name]]
            out[g] = bucket_quantile([le for le, _ in groups[name]],
                                     sums[rows].astype(np.float64), self.phi)
        return names, times, out


class _Ratio(_Panel):
    def __init__(self, name: str, args: dict):
        super().__init__(name, args)
        self.metric = args["metric"]
        self.num = [(k, "=~", v) for k, v in sorted(args["num"].items())]

    def query(self, ds) -> str:
        return (f"sum by ({self.by}) ({self.rate(self.metric, self.num)}) / "
                f"sum by ({self.by}) ({self.rate(self.metric, [])})")

    def groups(self, ds) -> list:
        return self.selected(ds, self.metric, self.num, (self.by,)).names

    def reference(self, p: dict, ds, precision: str = "float64") -> tuple:
        num_names, times, num = self.summed_rate(
            p, ds, self.metric, self.num, (self.by,), precision)
        names, _, den = self.summed_rate(
            p, ds, self.metric, [], (self.by,), precision)
        if num_names != names:
            raise ValueError("the two sides of the ratio do not match "
                             "one to one")
        return [n[0] for n in names], times, \
            num.astype(np.float64) / den.astype(np.float64)


class _Apdex(_Panel):
    def __init__(self, name: str, args: dict):
        super().__init__(name, args)
        self.satisfied = str(args["satisfied"])
        self.tolerated = str(args["tolerated"])

    def query(self, ds) -> str:
        def within(le: str) -> str:
            return (f"sum by ({self.by}) "
                    f"({self.rate(BASE + '_bucket', [('le', '=', le)])})")
        return (f"({within(self.satisfied)} + {within(self.tolerated)}) / 2 "
                f"/ sum by ({self.by}) ({self.rate(BASE + '_count', [])})")

    def groups(self, ds) -> list:
        return self.selected(ds, BASE + "_count", [], (self.by,)).names

    def reference(self, p: dict, ds, precision: str = "float64") -> tuple:
        parts = [self.summed_rate(p, ds, BASE + "_bucket",
                                  [("le", "=", le)], (self.by,), precision)
                 for le in (self.satisfied, self.tolerated)]
        names, times, total = self.summed_rate(
            p, ds, BASE + "_count", [], (self.by,), precision)
        if any(part[0] != names for part in parts):
            raise ValueError("the buckets and the count do not match one "
                             "to one")
        within = parts[0][2].astype(np.float64) \
            + parts[1][2].astype(np.float64)
        return [n[0] for n in names], times, \
            within / 2 / total.astype(np.float64)


_TEMPLATES = {"quantile": _Quantile, "ratio": _Ratio, "apdex": _Apdex}


def make(template: str, args: dict | None = None):
    if template not in _TEMPLATES:
        raise KeyError(f"no SLO board template {template!r}")
    return _TEMPLATES[template](template, args or {})

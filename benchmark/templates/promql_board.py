"""PromQL range-query panels over one counter metric, each with its plain
numpy reference.

One template, `range`, whose panel is the traffic file's `args`:

    {"fn": "rate" | "avg_over_time", "window_s": 300,
     "agg": "sum" | "avg", "by": null | "<label>",
     "match": {"<label>": "<value>"}, "step_s": 15, "range_s": 3600}

The query text is built here from those args, so the reference and the
request cannot disagree: `<agg> [by (<by>)] (<fn>(<metric>{<match>}[<w>s]))`
through /v1/prometheus/api/v1/query_range over a trailing `range_s`
whose `end` is drawn step-aligned from [t0 + range_s + window_s, the
table's last sample]: every such range holds the same number of samples,
one shape. (A range that ends past the last sample holds fewer: another
shape, 163 + 341 s more of compile in a checkout's first run, PERF.md
section 5; no request of this template reaches it.) A new panel over
these functions is a new entry in a traffic file.

Reference: Prometheus' extrapolatedRate rules (promql/functions.go),
vectorised over series and steps: the first-to-last delta of the samples
in (t-W, t] is extrapolated to the window edges — fully when an edge is
within 1.1 average sample intervals, else by half an interval — a
counter's start never below its zero crossing. No counter-reset
correction: the data has none by construction. avg_over_time is the
mean of the samples in (t-W, t].

What is compared: the widest relative gap over every point of every
series of the answer. The PromQL engine evaluates in float64 on every
backend (emulated on the chip: close to, not bit-exact, IEEE). Sound
runs on the chip read at most 1.9e-13 (PERF.md section 2); the control
(the same arithmetic on float32 samples, times kept exact) reads at
least 5.0e-7 on every panel at the cell's own size; limit 1e-10.
"""

from __future__ import annotations

import json
import urllib.parse

import numpy as np

LIMIT = 1e-10


class _Range:
    def __init__(self, name: str, args: dict):
        self.name = name
        self.fn = args["fn"]
        if self.fn not in ("rate", "avg_over_time"):
            raise KeyError(f"no reference for PromQL function {self.fn!r}")
        self.agg = args["agg"]
        if self.agg not in ("sum", "avg"):
            raise KeyError(f"no reference for aggregation {self.agg!r}")
        self.window_s = int(args["window_s"])
        self.by = args.get("by")
        self.match = dict(args.get("match") or {})
        self.step_s = int(args["step_s"])
        self.range_s = int(args["range_s"])

    def query(self, ds) -> str:
        sel = ds.table
        if self.match:
            sel += "{" + ",".join(f'{k}="{v}"'
                                  for k, v in sorted(self.match.items())) + "}"
        inner = f"{self.fn}({sel}[{self.window_s}s])"
        by = f" by ({self.by})" if self.by else ""
        return f"{self.agg}{by} ({inner})"

    def draw(self, rng, ds) -> dict:
        t0 = ds.t0_ms // 1000
        k_lo, k_hi = self._ends(ds)
        if k_hi < k_lo:
            raise ValueError("the table's span is shorter than the panel's "
                             "range plus window")
        end = t0 + int(rng.integers(k_lo, k_hi + 1)) * self.step_s
        return {"start": end - self.range_s, "end": end}

    def _ends(self, ds) -> tuple:
        t0, t_last = ds.t0_ms // 1000, (ds.t_end_ms - ds.step_ms) // 1000
        return (-(-(self.range_s + self.window_s) // self.step_s),
                (t_last - t0) // self.step_s)

    def edges(self, ds) -> list:
        """The first and the last `end` a draw can give."""
        t0 = ds.t0_ms // 1000
        return [{"start": t0 + k * self.step_s - self.range_s,
                 "end": t0 + k * self.step_s}
                for k in sorted(set(self._ends(ds)))]

    def request(self, p: dict, ds) -> tuple:
        q = urllib.parse.urlencode({
            "query": self.query(ds), "start": p["start"], "end": p["end"],
            "step": self.step_s})
        return "GET", "/v1/prometheus/api/v1/query_range?" + q, b""

    @staticmethod
    def parse(status: int, data: bytes) -> tuple:
        try:
            out = json.loads(data)
        except ValueError:
            return None, None, f"HTTP {status}: {data[:200]!r}"
        if status != 200 or out.get("status") != "success":
            return None, None, f"HTTP {status}: {str(out)[:200]}"
        try:
            return out["data"]["result"], None, None
        except (KeyError, TypeError):
            return None, None, f"unexpected body {data[:200]!r}"

    def _groups(self, ds) -> tuple:
        """(series kept, group index of each, group names sorted)."""
        tags = ds.series_tags()
        keep = np.ones(ds.series, bool)
        for k, v in self.match.items():
            keep &= np.asarray(tags[k], dtype=object) == v
        idx = np.flatnonzero(keep)
        if not self.by:
            return idx, np.zeros(len(idx), np.int64), [""]
        names, group = np.unique(
            np.asarray(tags[self.by], dtype=object)[idx].astype(str),
            return_inverse=True)
        return idx, group, names.tolist()

    def expected_rows(self, p: dict, ds) -> int:
        return len(self._groups(ds)[2])

    def limit(self, dtype: str) -> float:
        return LIMIT

    def reference(self, p: dict, ds, precision: str = "float64") -> tuple:
        """(group names, step times [S], values [G, S])."""
        dt = np.float64 if precision == "float64" else np.float32
        idx, group, names = self._groups(ds)
        mat = ds.fields["val"][:, idx].astype(dt)  # [points, kept series]
        step = ds.step_ms // 1000
        t0 = ds.t0_ms // 1000
        times = np.arange(p["start"], p["end"] + 1, self.step_s)
        # samples in (t-W, t] on the regular grid t0 + i*step
        i0 = np.maximum((times - self.window_s - t0) // step + 1, 0)
        i1 = np.minimum((times - t0) // step, ds.points - 1)
        if self.fn == "rate":
            if (i1 <= i0).any():
                raise ValueError("a step has fewer than two samples")
            # times are exact integers; only the samples and the
            # arithmetic on them are in `precision`
            g0, g1 = t0 + i0 * step, t0 + i1 * step
            first = mat[i0]                       # [S, series]
            delta = mat[i1] - first
            sampled = (g1 - g0).astype(dt)[:, None]
            avg_gap = sampled / (i1 - i0)[:, None].astype(dt)
            to_start = np.broadcast_to(
                (g0 - (times - self.window_s)).astype(dt)[:, None],
                first.shape)
            to_end = (times - g1).astype(dt)[:, None]
            with np.errstate(divide="ignore", invalid="ignore"):
                to_zero = np.where(delta > 0, sampled * first / delta,
                                   np.inf)
            to_start = np.minimum(to_start, to_zero)
            ext = sampled \
                + np.where(to_start < avg_gap * dt(1.1), to_start,
                           avg_gap / dt(2)) \
                + np.where(to_end < avg_gap * dt(1.1), to_end,
                           avg_gap / dt(2))
            per = delta * (ext / sampled) / dt(self.window_s)
        else:
            cs = np.concatenate([np.zeros((1, mat.shape[1]), dt),
                                 np.cumsum(mat, axis=0, dtype=dt)])
            n = (i1 - i0 + 1)[:, None].astype(dt)
            per = (cs[i1 + 1] - cs[i0]) / n
        out = np.zeros((len(names), len(times)), np.float64)
        cnt = np.bincount(group, minlength=len(names)).astype(np.float64)
        for g in range(len(names)):
            out[g] = per[:, group == g].sum(axis=1, dtype=dt)
        if self.agg == "avg":
            out /= cnt[:, None]
        return names, times, out

    def compare(self, result: list, p: dict, ds, dtype: str,
                lowered: bool = False) -> float:
        names, times, ref = self.reference(p, ds)
        if lowered:
            got = self.reference(p, ds, "float32")[2]
        else:
            by_name = {}
            for series in result:
                by_name[str(series["metric"].get(self.by, ""))
                        if self.by else ""] = series["values"]
            if sorted(by_name) != names:
                return float("inf")
            got = np.empty(ref.shape)
            for g, name in enumerate(names):
                vals = by_name[name]
                if [int(float(t)) for t, _ in vals] != times.tolist():
                    return float("inf")
                got[g] = [float(v) for _, v in vals]
        if not np.isfinite(got).all():
            return float("inf")
        return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref),
                                                           1e-300)))


def make(template: str, args: dict | None = None):
    if template != "range":
        raise KeyError(f"no PromQL board template {template!r}")
    return _Range(template, args or {})

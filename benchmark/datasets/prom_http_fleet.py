"""An HTTP service's pods, each exposing one classic histogram and one
request counter, every metric name a table of its own.

`scale` = {"instances", "minutes", "step_s"}: `instances` pods scraped
every `step_s` seconds for `minutes` minutes, each exposing what
prometheus.io/docs/practices/histograms/ reads —
`http_request_duration_seconds` as the Go client writes a classic
histogram (`_bucket{le}`, `_count`, `_sum`; `DefBuckets`: eleven finite
bounds and `+Inf`) and `http_requests_total` — per handler. Each metric
name is one view (`tables()`, `view(name)`): a logical table `(<its
labels>, ts, greptime_value DOUBLE)` created `ENGINE=metric`, NOT
append_mode, as GreptimeDB's remote-write door creates it (`[prom_store]
with_metric_engine = true`), but for the time index's name (`ts`: the
name under which harness/bulk_load.py writes it). No `job` label.

| view | labels | series a pod |
|---|---|---|
| http_request_duration_seconds_bucket | instance, handler (10), le (12) | 120 |
| http_request_duration_seconds_count | instance, handler | 10 |
| http_request_duration_seconds_sum | instance, handler | 10 |
| http_requests_total | instance, handler, code (5) | 50 |

Series of a view are instance-major, then handler, then `le` / `code`:
a scrape's order. `le` is spelled as the Go client spells it
(`0.005` ... `10`, `+Inf`).

Values from `--seed`. Per (instance, handler) a request rate of 5-50 a
second and a log-normal latency (sigma 0.7) whose median is the
handler's — 20 ms to 800 ms over the ten handlers, so their p99 lands
in different buckets — times 0.8-1.25 for the pod. Every scrape
interval draws its requests (Poisson) and throws them into the twelve
buckets (multinomial); one more request a scrape lands in the first
bucket, so every bucket counter is integer-valued, cumulative in `le`
and strictly increasing in time; `_count` is the `+Inf` bucket. Counters
start where a pod 1-60 days old would stand (past 2^24: a float32 copy
of them is not exact). Every code's counter gains the interval's
requests thrown over the five codes (a handler's 5xx share 0.3%-4%) plus
one. No resets, as the other Prometheus configurations.

The deployment asks the program for `histogram_quantile` as one kernel
(`greptimedb_tpu/ops/histogram.py`): `Dataset` refuses at once, before
anything is loaded, a program without it (`require_fold_kernel`).
"""

from __future__ import annotations

import math
import os

import numpy as np

from benchmark.harness.common import ROOT

T0_MS = 1456790400000
VALUE = "greptime_value"
TS = "ts"
BASE = "http_request_duration_seconds"

#: the Go client's DefBuckets and `+Inf`, as it spells them
LE = ["0.005", "0.01", "0.025", "0.05", "0.1", "0.25", "0.5", "1", "2.5",
      "5", "10", "+Inf"]
HANDLERS = ["/api/cart", "/api/checkout", "/api/login", "/api/orders",
            "/api/products", "/api/search", "/api/users", "/healthz",
            "/metrics", "/static"]
CODES = ["200", "400", "404", "500", "503"]
#: a handler's median latency, seconds: 20 ms to 800 ms, geometric
_MEDIANS = {h: 0.02 * 40.0 ** (i / 9.0) for i, h in enumerate(
    ["/healthz", "/metrics", "/static", "/api/users", "/api/products",
     "/api/cart", "/api/login", "/api/orders", "/api/search",
     "/api/checkout"])}
_SIGMA = 0.7

_VIEWS = [
    (BASE + "_bucket", ("handler", "le")),
    (BASE + "_count", ("handler",)),
    (BASE + "_sum", ("handler",)),
    ("http_requests_total", ("handler", "code")),
]


def _norm_cdf(x: np.ndarray) -> np.ndarray:
    return 0.5 * (1.0 + np.vectorize(math.erf)(x / math.sqrt(2.0)))


def require_fold_kernel(root: str = ROOT) -> None:
    """Refuse a program that evaluates `histogram_quantile` group by
    group. It cannot be brought to this deployment's steady state: on
    the chip, at 2,000 pods, `p99-by-instance` took 57.0 s a request
    through the per-group loop and `error-ratio-by-handler`'s warm-up
    had not ended after 400 s; at 1,000 pods the run ended at the
    harness's own deadline, 1,157 s, still in set-up (PERF.md section
    6, PR 32). A run that can give no result says so in its first
    second, not after twenty minutes of a chip."""
    if not os.path.isfile(
            os.path.join(root, "greptimedb_tpu", "ops", "histogram.py")):
        raise ValueError(
            "prom-http-histogram-fleet needs a program whose "
            "histogram_quantile is one kernel (greptimedb_tpu/ops/"
            "histogram.py); this one folds group by group and cannot "
            "finish the set-up inside the run's deadline")


class _View:
    """One metric name: the single-table interface of benchmark/README.md
    over its [points, series] matrix."""

    def __init__(self, name: str, label_names: tuple, ds: "Dataset"):
        self.table, self.label_names, self._ds = name, label_names, ds
        self.combos = [(h,) + rest for h in HANDLERS for rest in (
            [(le,) for le in LE] if "le" in label_names else
            [(c,) for c in CODES] if "code" in label_names else [()])]
        self.instances = ds.instances
        self.series = ds.instances * len(self.combos)
        self.points, self.step_ms = ds.points, ds.step_ms
        self.t0_ms, self.t_end_ms = ds.t0_ms, ds.t_end_ms
        self.rows = self.points * self.series
        self._tags = None

    @property
    def fields(self) -> dict:
        return {VALUE: self._ds.matrix(self.table)}

    def create_sql(self) -> str:
        cols = ["instance"] + list(self.label_names)
        return (f"CREATE TABLE {self.table} ("
                + ", ".join(f"{c} STRING" for c in cols)
                + f", {TS} TIMESTAMP(3) NOT NULL, {VALUE} DOUBLE, "
                f"TIME INDEX ({TS}), PRIMARY KEY ({', '.join(cols)})) "
                "ENGINE=metric")

    def series_tags(self) -> dict:
        if self._tags is None:
            k = len(self.combos)
            tags = {"instance": [f"pod-{i}:8080"
                                 for i in range(self.instances)
                                 for _ in range(k)]}
            for j, name in enumerate(self.label_names):
                tags[name] = [c[j] for c in self.combos] * self.instances
            self._tags = tags
        return self._tags

    def slices(self, max_rows: int):
        per = max(1, max_rows // self.series)
        mat = self.fields[VALUE]
        for p0 in range(0, self.points, per):
            p1 = min(p0 + per, self.points)
            ts = np.repeat(
                self.t0_ms + np.arange(p0, p1, dtype=np.int64) * self.step_ms,
                self.series)
            yield p0, p1, ts, {VALUE: mat[p0:p1].reshape(-1)}


class Dataset:
    def __init__(self, seed: int, scale: dict):
        require_fold_kernel()
        self.seed = int(seed)
        self.instances = int(scale["instances"])
        self.step_ms = int(scale["step_s"]) * 1000
        self.points = int(scale["minutes"]) * 60_000 // self.step_ms
        self.t0_ms = T0_MS
        self.t_end_ms = T0_MS + self.points * self.step_ms
        self._views = [_View(name, labels, self) for name, labels in _VIEWS]
        self._by_name = {v.table: v for v in self._views}
        self.series = sum(v.series for v in self._views)
        self.rows = sum(v.rows for v in self._views)
        # drawn here, while the harness's loader child writes its own
        # copy: a lazy draw would fall into the first panel's warm-up
        self._mats = self._draw()
        # the single-table face test_manifest_config asks of a dataset:
        # the first view's
        self.table = self._views[0].table

    def create_sql(self) -> str:
        return self._views[0].create_sql()

    def tables(self) -> list:
        return self._views

    def view(self, table: str):
        return self._by_name[table]

    def matrix(self, table: str) -> np.ndarray:
        """[points, series] of one view; all four are drawn together
        (the counters of one pod count the same requests)."""
        return self._mats[table]

    def _draw(self) -> dict:
        rng = np.random.default_rng([self.seed, 32])
        pairs = self.instances * len(HANDLERS)      # (instance, handler)
        points, step_s = self.points, self.step_ms / 1000.0
        rate = rng.uniform(5.0, 50.0, pairs)        # requests a second
        median = np.tile([_MEDIANS[h] for h in HANDLERS], self.instances) \
            * rng.uniform(0.8, 1.25, pairs)
        bounds = np.asarray([float(le) for le in LE[:-1]])
        cdf = _norm_cdf(np.log(bounds[None, :] / median[:, None]) / _SIGMA)
        share = np.diff(np.concatenate(
            [np.zeros((pairs, 1)), cdf, np.ones((pairs, 1))], axis=1),
            axis=1)                                 # [pairs, 12]
        share /= share.sum(axis=1, keepdims=True)
        requests = rng.poisson(rate * step_s, (points, pairs))
        # where a pod 1-60 days old stands when the span begins
        age_s = rng.uniform(1.0, 60.0, pairs) * 86400.0
        slots = rng.multinomial(requests, share).astype(np.float64)
        slots[:, :, 0] += 1.0
        slots[0] += np.floor(share * (rate * age_s)[:, None])
        bucket = np.cumsum(np.cumsum(slots, axis=0), axis=2)
        del slots
        count = bucket[:, :, -1].copy()
        mean = median * math.exp(_SIGMA * _SIGMA / 2.0)
        spent = requests * mean * rng.uniform(0.9, 1.1, (points, pairs))
        spent[0] += rate * age_s * mean
        errors = np.tile(np.geomspace(0.003, 0.04, len(HANDLERS)),
                         self.instances)
        by_code = np.stack([1.0 - 0.03 - errors, np.full(pairs, 0.01),
                            np.full(pairs, 0.02), errors * 0.7,
                            errors * 0.3], axis=1)  # [pairs, 5]
        codes = rng.multinomial(requests, by_code).astype(np.float64) + 1.0
        codes[0] += np.floor(by_code * (rate * age_s)[:, None])
        return {
            BASE + "_bucket": bucket.reshape(points, -1),
            BASE + "_count": count,
            BASE + "_sum": np.cumsum(spent, axis=0),
            "http_requests_total": np.cumsum(codes, axis=0).reshape(
                points, -1),
        }

"""Internal metrics registry (mirrors the reference's lazy_static
prometheus registries in every crate's metrics.rs, exposed at /metrics and
self-scraped — SURVEY.md §5)."""

from __future__ import annotations

import threading
import time
import weakref
from collections import defaultdict


class Counter:
    def __init__(self, name: str, help_: str):
        self.name = name
        self.help = help_
        self._values: dict[tuple, float] = defaultdict(float)
        self._lock = threading.Lock()

    def inc(self, value: float = 1.0, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] += value

    def get(self, **labels) -> float:
        # via _snapshot (copied under the lock): a bare dict read races
        # concurrent inc/set and could observe a half-applied update;
        # subclasses that shard their writes only override _snapshot
        return self._snapshot().get(tuple(sorted(labels.items())), 0.0)

    def total(self, **labels) -> float:
        """Sum over every series whose labels are a superset of the
        given ones (PromQL `sum by` analog) — assertions stay valid
        when a call site starts attaching extra labels."""
        want = set(labels.items())
        return sum(v for key, v in self._snapshot().items()
                   if want <= set(key))

    def series(self, **labels) -> list:
        """Every (labels dict, value) series whose labels are a superset
        of the given ones — feeds per-node/per-edge breakdowns in debug
        surfaces (information_schema.cluster_faults, /v1/faults)."""
        want = set(labels.items())
        return [(dict(key), v)
                for key, v in sorted(self._snapshot().items())
                if want <= set(key)]

    def _snapshot(self) -> dict:
        """Point-in-time copy of every series (Registry sampling uses
        this so sharded subclasses can fold their shards in)."""
        with self._lock:
            return dict(self._values)

    def render(self, exemplars: bool = False) -> list[str]:
        # OpenMetrics family naming: the metric FAMILY drops the _total
        # suffix while counter samples keep it — a strict OM parser
        # (modern Prometheus negotiates OM by default) rejects a family
        # named ..._total. The classic text format keeps the suffixed
        # name, byte-stable for legacy scrapers.
        family = self.name
        if exemplars and family.endswith("_total"):
            family = family[:-len("_total")]
        out = [f"# HELP {family} {self.help}", f"# TYPE {family} counter"]
        items = sorted(self._snapshot().items())
        for key, v in items:
            out.append(f"{self.name}{_labels(key)} {v}")
        return out


class ShardedCounter(Counter):
    """Counter whose `inc` writes a per-thread shard instead of taking
    the global metric lock.

    The per-request counters (http_requests, admission events, plan/
    fast-lane cache events) are incremented by every serving thread on
    every request; under 50 concurrent clients the single `Counter`
    lock is a measurable contention point. Each thread owns a private
    dict (only that thread ever writes it — plain dict updates are
    GIL-atomic), and the read side folds base + shards at scrape/assert
    time. A dying thread's shard is folded into the base dict by a
    weakref finalizer on its Thread object, so counts survive thread
    churn and the shard list stays bounded by live threads."""

    def __init__(self, name: str, help_: str):
        super().__init__(name, help_)
        self._shards: list[dict] = []
        self._tls = threading.local()

    def _cell(self) -> dict:
        cell = getattr(self._tls, "cell", None)
        if cell is None:
            cell = {}
            with self._lock:
                self._shards.append(cell)
            # fold the shard into the durable base when the thread dies
            # (cumulative counters must never lose counts)
            weakref.finalize(threading.current_thread(),
                             self._fold, cell)
            self._tls.cell = cell
        return cell

    def _fold(self, cell: dict) -> None:
        with self._lock:
            try:
                self._shards.remove(cell)
            except ValueError:
                return
            for k, v in cell.items():
                self._values[k] += v

    def inc(self, value: float = 1.0, **labels):
        cell = self._cell()
        key = tuple(sorted(labels.items()))
        # single-writer dict update: no lock, no condition, no CAS loop
        cell[key] = cell.get(key, 0.0) + value

    def shard_count(self) -> int:
        with self._lock:
            return len(self._shards)

    def _snapshot(self) -> dict:
        # the read methods (get/total/series/render) all fold through
        # here — the only read-side difference from a plain Counter
        with self._lock:
            out = dict(self._values)
            shards = list(self._shards)
        for cell in shards:
            # list(dict.items()) is one C call — an atomic snapshot of
            # a shard another thread may be appending to
            for k, v in list(cell.items()):
                out[k] = out.get(k, 0.0) + v
        return out


class Gauge(Counter):
    def set(self, value: float, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = value

    def render(self, exemplars: bool = False) -> list[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        with self._lock:
            items = sorted(self._values.items())
        for key, v in items:
            out.append(f"{self.name}{_labels(key)} {v}")
        return out


class Histogram:
    BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 60.0)

    def __init__(self, name: str, help_: str, buckets=None,
                 exemplars: bool = False):
        self.name = name
        self.help = help_
        if buckets is not None:
            # per-instance bounds for non-latency shapes (batch rows,
            # byte counts) — the default decade grid is seconds-tuned
            self.BUCKETS = tuple(sorted(buckets))
        self._buckets: dict[tuple, list[int]] = {}
        self._sum: dict[tuple, float] = defaultdict(float)
        self._count: dict[tuple, int] = defaultdict(int)
        # OpenMetrics exemplars: per (labels, bucket) the most recent
        # (trace_id, value, ts) — the metrics→trace join (a slow
        # gtpu_query_stage_seconds bucket links to a trace to pull)
        self._exemplars_on = exemplars
        self._exemplar: dict[tuple, tuple] = {}
        self._lock = threading.Lock()

    def observe(self, value: float, **labels):
        tid = None
        if self._exemplars_on:
            from greptimedb_tpu.utils import tracing

            # gate on the tracing master switch: with GTPU_TRACING=off
            # no spans exist, so an exemplar would point at a trace
            # whose /v1/traces lookup can only 404
            if tracing.enabled():
                tid = tracing.current_trace_id()
        key = tuple(sorted(labels.items()))
        with self._lock:
            b = self._buckets.setdefault(key, [0] * (len(self.BUCKETS) + 1))
            for i, ub in enumerate(self.BUCKETS):
                if value <= ub:
                    b[i] += 1
                    break
            else:
                i = len(self.BUCKETS)
                b[-1] += 1
            self._sum[key] += value
            self._count[key] += 1
            if tid:
                self._exemplar[(key, i)] = (tid, value, time.time())

    def time(self, **labels):
        return _Timer(self, labels)

    def sum(self, **labels) -> float:
        """Total of observed values for one label set (benches read the
        execute/encode wall-time split from here)."""
        with self._lock:
            return self._sum.get(tuple(sorted(labels.items())), 0.0)

    def count(self, **labels) -> int:
        with self._lock:
            return self._count.get(tuple(sorted(labels.items())), 0)

    def total_count(self, **labels) -> int:
        """Observation count summed over every series whose labels are
        a superset of the given ones (Counter.total's analog)."""
        want = set(labels.items())
        with self._lock:
            return sum(c for key, c in self._count.items()
                       if want <= set(key))

    def total_sum(self, **labels) -> float:
        """Observed-value total over matching series (see total_count)."""
        want = set(labels.items())
        with self._lock:
            return sum(s for key, s in self._sum.items()
                       if want <= set(key))

    def render(self, exemplars: bool = False) -> list[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        with self._lock:
            snapshot = sorted(
                (key, list(b), self._sum[key], self._count[key])
                for key, b in self._buckets.items()
            )
            ex = dict(self._exemplar) if exemplars else {}
        for key, b, _sum, _count in snapshot:
            cum = 0
            for i, ub in enumerate(self.BUCKETS):
                cum += b[i]
                out.append(f"{self.name}_bucket{_labels(key, le=str(ub))} "
                           f"{cum}{_exemplar_suffix(ex.get((key, i)))}")
            cum += b[-1]
            out.append(f"{self.name}_bucket{_labels(key, le='+Inf')} {cum}"
                       f"{_exemplar_suffix(ex.get((key, len(self.BUCKETS))))}")
            out.append(f"{self.name}_sum{_labels(key)} {_sum}")
            out.append(f"{self.name}_count{_labels(key)} {_count}")
        return out


class _Timer:
    def __init__(self, hist, labels):
        self.hist = hist
        self.labels = labels

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.hist.observe(time.perf_counter() - self.t0, **self.labels)


def _exemplar_suffix(ex) -> str:
    """OpenMetrics exemplar rendering for one bucket line:
    ` # {trace_id="<id>"} <value> <timestamp>` — omitted (empty string)
    when no exemplar was captured for that bucket."""
    if ex is None:
        return ""
    tid, value, ts = ex
    return (f' # {{trace_id="{_escape_label_value(tid)}"}} '
            f"{value} {round(ts, 3)}")


def _escape_label_value(v) -> str:
    """Prometheus exposition-format escaping: backslash, double-quote and
    newline must be escaped inside label values (a raw newline would
    split the sample line and corrupt the whole scrape)."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _labels(key: tuple, **extra) -> str:
    items = list(key) + sorted(extra.items())
    if not items:
        return ""
    inner = ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in items)
    return "{" + inner + "}"


class Registry:
    def __init__(self):
        self._metrics: list = []
        self._collectors: list = []
        self._lock = threading.Lock()

    def register_collector(self, fn) -> None:
        """Register a callback run before every render/sample pass —
        for gauges whose truth lives elsewhere (device memory stats,
        cache residency) and is only worth reading at scrape time."""
        with self._lock:
            self._collectors.append(fn)

    def _collect(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception:  # noqa: BLE001 — a scrape must never fail
                pass

    def counter(self, name, help_="") -> Counter:
        m = Counter(name, help_)
        with self._lock:
            self._metrics.append(m)
        return m

    def sharded_counter(self, name, help_="") -> ShardedCounter:
        """Lock-light counter for the per-request hot path: inc() writes
        a per-thread shard, reads fold at scrape time."""
        m = ShardedCounter(name, help_)
        with self._lock:
            self._metrics.append(m)
        return m

    def gauge(self, name, help_="") -> Gauge:
        m = Gauge(name, help_)
        with self._lock:
            self._metrics.append(m)
        return m

    def histogram(self, name, help_="", buckets=None,
                  exemplars: bool = False) -> Histogram:
        m = Histogram(name, help_, buckets=buckets, exemplars=exemplars)
        with self._lock:
            self._metrics.append(m)
        return m

    def render(self, openmetrics: bool = False) -> str:
        """Exposition text. `openmetrics=True` (the scraper sent
        Accept: application/openmetrics-text) adds exemplar suffixes to
        histogram bucket lines and the spec's `# EOF` terminator; the
        classic text format stays byte-stable for legacy parsers."""
        self._collect()
        with self._lock:
            metrics = list(self._metrics)
        lines = []
        for m in metrics:
            lines.extend(m.render(exemplars=openmetrics))
        if openmetrics:
            lines.append("# EOF")
        return "\n".join(lines) + "\n"

    def _iter_samples(self):
        """(metric_name, value, label-pairs tuple) over every metric."""
        self._collect()
        with self._lock:
            metrics = list(self._metrics)
        for m in metrics:
            if isinstance(m, Histogram):
                with m._lock:
                    items = [(key, m._sum[key], c)
                             for key, c in m._count.items()]
                for key, s, c in items:
                    yield m.name + "_sum", s, key
                    yield m.name + "_count", c, key
            else:
                items = sorted(m._snapshot().items())
                for key, v in items:
                    yield m.name, v, key

    def samples(self):
        """Flat (metric_name, value, rendered labels) samples — feeds
        information_schema.runtime_metrics."""
        return [(n, v, _labels(k)) for n, v, k in self._iter_samples()]

    def samples_dict(self):
        """(metric_name, value, labels dict) — feeds the self-scrape
        exporter (reference export_metrics writes label columns)."""
        return [(n, v, dict(k)) for n, v, k in self._iter_samples()]


REGISTRY = Registry()

# framework-wide metrics (analogs of servers/src/metrics.rs etc.)
# per-request counters are SHARDED: every serving thread touches them on
# every request, and a single counter lock is measurable contention at
# benchmark concurrency (ISSUE 14)
HTTP_REQUESTS = REGISTRY.sharded_counter(
    "greptimedb_tpu_http_requests_total",
    "HTTP requests by path and status")
QUERY_DURATION = REGISTRY.histogram("greptimedb_tpu_query_duration_seconds",
                                    "Query execution latency",
                                    exemplars=True)
INGEST_ROWS = REGISTRY.sharded_counter(
    "greptimedb_tpu_ingest_rows_total",
    "Rows ingested by protocol")

# ingest pipeline (storage/group_commit.py + the protocol front doors):
# every front door lands on the bulk path through a per-region group
# commit — these series prove the fsync amortization is real (batch
# size > 1 under concurrency) and show where admission pressure lands
INGEST_BATCH_SIZE = REGISTRY.histogram(
    "greptimedb_tpu_ingest_batch_size",
    "Rows per group-committed WAL batch (one fsync each; sizes > the "
    "per-writer batch mean concurrent writers were coalesced)",
    buckets=(1, 8, 64, 256, 1024, 4096, 16384, 65536, 262144))
INGEST_GROUP_COMMIT_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_ingest_group_commit_events_total",
    "Group-commit events by kind (lead = a writer drained the queue and "
    "paid the fsync, follow = a writer rode another's commit, overflow "
    "= the bounded ingest queue rejected a writer with typed "
    "Overloaded)")
INGEST_WAL_FSYNC_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_ingest_wal_fsync_seconds",
    "WAL append+fsync wall time per group commit (the durability "
    "boundary every queued writer amortizes over)")
STMT_DURATION = REGISTRY.histogram(
    "greptimedb_tpu_statement_duration_seconds",
    "Statement execution latency by statement kind", exemplars=True)

# resilience plane (fault/ package): every injected fault, every retry,
# every exhaustion, and every degradation is observable at /metrics so
# chaos runs assert behavior instead of eyeballing logs
FAULT_INJECTIONS = REGISTRY.counter(
    "greptimedb_tpu_fault_injections_total",
    "Injected faults by injection point and kind")
RETRY_ATTEMPTS = REGISTRY.counter(
    "greptimedb_tpu_retry_attempts_total",
    "Retries after a transient failure, by injection point")
RETRY_EXHAUSTED = REGISTRY.counter(
    "greptimedb_tpu_retry_exhausted_total",
    "Operations that exhausted their retry budget, by injection point")
DEGRADED = REGISTRY.counter(
    "greptimedb_tpu_degraded_total",
    "Graceful degradations (route re-resolution after retry exhaustion)")
CHAOS_RUNS = REGISTRY.counter(
    "greptimedb_tpu_chaos_runs_total",
    "Chaos-explorer runs by outcome (pass|fail|error)")
CHAOS_SHRINK_STEPS = REGISTRY.counter(
    "greptimedb_tpu_chaos_shrink_steps_total",
    "Delta-debugging probe runs spent shrinking failing chaos schedules")
FLOW_TICK_ERRORS = REGISTRY.counter(
    "greptimedb_tpu_flow_tick_errors_total",
    "Flow engine tick failures deferred to the next tick, by flow")

# deadline/cancellation/hedging plane (utils/deadline.py,
# cluster/cluster.py): tail tolerance is only credible when every
# expiry, kill, and hedge decision is a counted event
DEADLINE_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_query_deadline_events_total",
    "Query deadline-plane terminal events by event (expired = the "
    "absolute deadline passed at a cooperative checkpoint, cancelled = "
    "client disconnect or hedge-loser cancellation, killed = KILL "
    "QUERY / DELETE /v1/queries/<id>); counted once per query at the "
    "first typed raise")
HEDGE_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_hedge_events_total",
    "Hedged region-request events by event (fired = a backup fragment "
    "was issued after the adaptive straggler delay, won = the hedge "
    "finished first, lost = the primary finished first and the hedge "
    "was cancelled, budget_denied = the <=5% token-bucket hedge budget "
    "suppressed a hedge)")
REQUEST_BUDGET_REMAINING = REGISTRY.histogram(
    "greptimedb_tpu_region_request_budget_remaining_ms",
    "Remaining deadline budget (ms) observed at datanode ingress on "
    "scan/fragment tickets that carried one — low buckets mean "
    "frontends are shipping nearly-dead work to datanodes",
    buckets=(5, 25, 100, 250, 500, 1000, 2500, 5000, 10000, 30000))

# TPU runtime telemetry (SURVEY §5: the north star is unfalsifiable
# without per-device numbers): XLA compiles, device memory, link
# traffic, and HBM block-cache behavior — wired by
# utils/device_telemetry.py, rendered at /metrics, self-scraped by
# utils/export_metrics.py like every other series
XLA_COMPILES = REGISTRY.counter(
    "greptimedb_tpu_xla_compile_total",
    "XLA compilations observed via jax.monitoring, by backend, fn (the "
    "compiled program's stable kernel name, or eager for an op "
    "dispatched outside the named steps) and thread (request = a "
    "request thread waited for it, warmup = start-up pre-warm or the "
    "device warm-up hedge ran it beside the request)")
XLA_CACHE_RETRIEVALS = REGISTRY.counter(
    "greptimedb_tpu_xla_cache_retrieval_total",
    "Executables served by JAX's persistent compilation cache instead "
    "of being compiled, by backend (a warm start shows retrievals and "
    "no xla_compile_total growth)")
XLA_COMPILE_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_xla_compile_duration_seconds",
    "XLA backend-compile wall time per compilation, by backend, fn and "
    "thread (as xla_compile_total)")
DEVICE_MEMORY = REGISTRY.gauge(
    "greptimedb_tpu_device_memory_bytes",
    "Accelerator memory by kind (in_use/limit summed over the local "
    "devices' PJRT allocators when they report, cache = bytes pinned "
    "by the device block cache; per-device figures at /v1/device)")
DEVICE_INFO = REGISTRY.gauge(
    "greptimedb_tpu_device_info",
    "Local devices of the serving process, labelled by jax's platform "
    "and device_kind (what /v1/device reports): a share of a chip's "
    "published peak is taken against the kind named here")
DEVICE_TRANSFER_BYTES = REGISTRY.counter(
    "greptimedb_tpu_device_transfer_bytes_total",
    "Host<->device bytes moved by the query engine, by direction "
    "(h2d uploads of scan blocks, d2h result readbacks)")
DEVICE_TRANSFER_BYTES_BY_DEVICE = REGISTRY.counter(
    "greptimedb_tpu_device_transfer_bytes_by_device_total",
    "The bytes of device_transfer_bytes_total by direction and by the "
    "device they moved to or from (platform:id of the thread's default "
    "device when the copy was counted), so that bytes per chip can be "
    "read where a table's regions compute on several")
DEVICE_CACHE_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_device_cache_events_total",
    "HBM block cache events by kind (hit/miss/evict/prefetch_join — a "
    "join is an upload the background prefetch worker already did)")
PROMQL_LOAD_CACHE_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_promql_load_cache_events_total",
    "PromQL loaded-series cache events, one per selector load (hit = "
    "answered from resident samples with no region scan: a slice of the "
    "selector's whole span, or a range asked for before; miss = the "
    "request's own range was scanned, factorised and uploaded; promote = "
    "the whole retained span was, and kept; ineligible = the own range, "
    "for a selector whose whole span has no complete sample grid or "
    "would not fit the device budget at this data version)")
PROMQL_HISTOGRAM_FOLD_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_promql_histogram_fold_seconds",
    "histogram_quantile's host wall time by phase: index (finding or "
    "building the fold index: each input series' group and bucket rank, "
    "the groups' bounds) and dispatch (the device gather of the buckets "
    "and the histogram_fold kernel's dispatch)")
PROMQL_HISTOGRAM_FOLDS = REGISTRY.counter(
    "greptimedb_tpu_promql_histogram_fold_total",
    "histogram_quantile evaluations by where the fold index came from "
    "(hit = kept beside the loaded series the input's label sets derive "
    "from, at their data version; build = built from the label sets for "
    "this request)")
PROMQL_GROUP_INDEXES = REGISTRY.counter(
    "greptimedb_tpu_promql_group_index_total",
    "PromQL aggregations by where the group index came from (hit = kept "
    "beside the loaded series the input's label sets derive from, on the "
    "host and on the device, at their data version; build = built from "
    "the label sets and uploaded for this request: a first touch, or "
    "label sets of no known origin)")
PROMQL_EVAL_PROGRAMS = REGISTRY.counter(
    "greptimedb_tpu_promql_eval_programs_total",
    "PromQL aggregation nodes by how the device evaluated them (fused = "
    "agg(range_fn(m[w])) over a resident complete-grid pivot, from the "
    "pivot to the [groups, steps] answer in one program; split = the "
    "aggregation as a program of its own over an operand that is no "
    "range function; stepwise = a kernel at a time with eager operations "
    "between: samples without a complete grid, a subquery, another "
    "range function or operator)")
PROMQL_ENCODED_RESPONSES = REGISTRY.counter(
    "greptimedb_tpu_promql_encoded_responses_total",
    "PromQL HTTP responses by how the body was written (columnar = a "
    "query_range matrix answer, from the read-back [series, steps] array "
    "to bytes in arrow's kernels with no Python object a sample; rows = "
    "an instant query, a scalar-valued range answer or an error, a value "
    "at a time through json.dumps)")
SQL_ENCODED_ROWS = REGISTRY.counter(
    "greptimedb_tpu_sql_encoded_rows_total",
    "Rows of /v1/sql query results by how their \"rows\" were written "
    "(columnar = from the result's columns to bytes in arrow's kernels "
    "with no Python object a value; values = the whole result set a value "
    "at a time through json_rows + json.dumps, because a column is neither "
    "numeric nor str / None)")
DEVICE_HOT_SET_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_device_hot_set_events_total",
    "HBM-resident columnar hot set events by kind (hit/miss/evict/pin — "
    "pin = a file-anchored column block entered HBM residency and stays "
    "across queries and data versions until its file dies)")
DEVICE_HOT_SET_BYTES = REGISTRY.gauge(
    "greptimedb_tpu_device_hot_set_bytes",
    "Bytes currently pinned in HBM by the device columnar hot set")
PALLAS_DISPATCHES = REGISTRY.counter(
    "greptimedb_tpu_pallas_dispatch_total",
    "Pallas kernel dispatches by kernel (fused_agg = the fused "
    "scan/filter/bucket/aggregate kernel, sparse_fused_agg = its tiled "
    "sparse twin; fused_agg_failed = mid-query degradations to the XLA "
    "scatter path) and mode (compiled = Mosaic on a TPU, interpret = "
    "the Pallas interpreter off-TPU)")
DEVICE_DEGRADATIONS = REGISTRY.counter(
    "greptimedb_tpu_device_degradation_total",
    "Times the device path stopped being the device path while serving "
    "continued, by kind (canary_dense/canary_fused = Mosaic refused a "
    "kernel family, fused_latch/partial_latch = a runtime failure "
    "latched a path off, warmup_failed = a hedged device warm-up "
    "failed and the shape stays on the host tier, prewarm_failed = the "
    "background kernel pre-warm failed); any non-zero value is a bug")
SPARSE_DISPATCHES = REGISTRY.counter(
    "greptimedb_tpu_sparse_dispatch_total",
    "Sparse sort-compact aggregation dispatches by path (classic = "
    "whole-scan XLA segment reduce, fused = tiled Pallas windows, "
    "sharded = per-shard compaction + gid-space combine, incremental = "
    "per-part value-space partials)")
SPARSE_COMPACTION_RATIO = REGISTRY.gauge(
    "greptimedb_tpu_sparse_compaction_ratio",
    "Observed groups per scanned row in the last sparse aggregation "
    "(1.0 = every row its own group, no compaction win)")
QUERY_TIER = REGISTRY.counter(
    "greptimedb_tpu_query_tier_total",
    "Statements the executor answered, by the tier that answered: host "
    "(CPU backend of an accelerator process), device, mesh, or cache "
    "(every part served from the partial-aggregate cache, no kernel "
    "ran); counted once per statement where the tier becomes final")
AGG_SCAN = REGISTRY.counter(
    "greptimedb_tpu_agg_scan_total",
    "Aggregate statements by what they asked of their scan: none (the "
    "incremental path found every part's partial cached and fetched no "
    "SST part), parts (it fetched only the parts it missed), whole "
    "(whole columns were built or read: every other path); counted once "
    "per statement, a statement that scans twice counts its costliest")
LWW_MASK_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_lww_mask_events_total",
    "Last-write-wins masks made for scans of tables that are not "
    "append-mode, by path: none (one pass over the scan's keys proved "
    "that no row repeats: no mask), host_merge (the host merged the "
    "scan's sorted runs)")
LWW_MASK_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_lww_mask_seconds",
    "Host wall time of making one scan's last-write-wins mask: packing "
    "the rows' (primary key, ts) into keys, the pass that proves them "
    "ascending, and the merge of the sorted runs where they are not")
DERIVED_SELECT_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_derived_select_seconds",
    "Host wall time of the outer select over a derived table, CTE or "
    "join: factorizing its group keys and reducing the inner result's "
    "columns as arrays")
AGG_PROGRAM_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_agg_program_events_total",
    "Dispatches of a jitted aggregate or filter step, by what the "
    "dispatch asked of the compiler: reuse (this program — the step, its "
    "static arguments with the predicate's literal-free shape, its "
    "arguments' shapes and dtypes — was dispatched to this backend "
    "before in this process), new (it was not), static_literal (the "
    "predicate's shape still holds a literal, so the program is shared "
    "only by requests that repeat it)")
METRIC_ENGINE_SCAN_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_metric_engine_scan_seconds",
    "Metric-engine logical scan wall time by phase: physical (the shared "
    "region's scan under the __table / __labels predicates), labels "
    "(bringing the parsed label-set catalog up to the dictionary), "
    "project (virtual tag columns gathered from the catalog)")
METRIC_ENGINE_ROWS = REGISTRY.counter(
    "greptimedb_tpu_metric_engine_rows_total",
    "Rows through metric-engine logical scans by kind: physical_decoded "
    "(rows of the shared region the scan read before its exact __table "
    "/ __labels row filter) and logical_returned (rows of the logical "
    "table handed to the query)")
METRIC_ENGINE_LABEL_SETS_PARSED = REGISTRY.counter(
    "greptimedb_tpu_metric_engine_label_sets_parsed_total",
    "Label-set strings of a physical region's __labels dictionary "
    "parsed into virtual tag columns (once per label set per process: "
    "a steady scan parses none)")
METRIC_ENGINE_WRITE_ROWS = REGISTRY.counter(
    "greptimedb_tpu_metric_engine_write_rows_total",
    "Rows written through metric-engine logical tables onto their "
    "physical region (puts and deletes)")
SLOW_QUERIES = REGISTRY.counter(
    "greptimedb_tpu_slow_queries_total",
    "Statements slower than the slow-query threshold, by kind")

# scan pipeline (storage/region.py + query/device_cache.py): the cold
# scan is the wall on first-touch queries — these series prove the
# three pipeline stages
# (parallel SST decode, per-file part cache, upload prefetch) are doing
# their jobs
SCAN_DECODE_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_scan_decode_seconds",
    "Per-SST parquet read+decode wall time inside the region scan "
    "(cache misses only; parallel decodes observe concurrently)")
SCAN_PART_CACHE_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_scan_part_cache_events_total",
    "Per-file decoded-part scan cache events by kind (hit/miss/evict; "
    "evict includes whole-scan snapshots aged out of the shared host "
    "byte budget)")
SCAN_DECODE_BYTES = REGISTRY.counter(
    "greptimedb_tpu_scan_decode_bytes_total",
    "Host bytes materialized by SST scan decode (part-cache misses)")
SCAN_KEY_COLUMNS = REGISTRY.counter(
    "greptimedb_tpu_scan_key_columns_total",
    "Tag columns outside a region scan's projection, summed over scans, "
    "by kind: decoded (carried with the scan for the primary key's sake: "
    "a last-write-wins table's mask merges by it) and skipped (left "
    "unread: the caller declared the table append-mode, so no mask is "
    "made and the scan decodes the columns the statement names)")
SCAN_ROWS = REGISTRY.counter(
    "greptimedb_tpu_scan_rows_total",
    "Rows through pruned SST reads (a window or tag predicates; "
    "part-cache misses only) by kind: read (rows of the row-group "
    "batches the read decoded: what the time statistics and the "
    "inverted index left) and kept (rows the read returned, inside the "
    "window and under its =/IN tag predicates)")
SCAN_PIPELINE_OVERLAP = REGISTRY.gauge(
    "greptimedb_tpu_scan_pipeline_overlap",
    "Fraction of prefetched device block uploads already built when the "
    "query asked for them (1.0 = host build fully hidden behind "
    "upload/compute; cumulative ratio since process start)")

# background maintenance plane (maintenance/ package): job throughput,
# queue pressure, writer stalls, and the rollup/retention outcomes —
# the observability contract of "the write path never does maintenance"
MAINTENANCE_JOBS = REGISTRY.counter(
    "greptimedb_tpu_maintenance_jobs_total",
    "Maintenance jobs by kind (flush/compact/rollup/expire) and "
    "terminal status (done/failed)")
MAINTENANCE_QUEUE_DEPTH = REGISTRY.gauge(
    "greptimedb_tpu_maintenance_queue_depth",
    "Maintenance jobs currently queued (bounded; excess submissions "
    "run inline on the caller)")
MAINTENANCE_JOB_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_maintenance_job_duration_seconds",
    "Maintenance job execution wall time by kind")
WRITE_STALL_SECONDS = REGISTRY.counter(
    "greptimedb_tpu_write_stall_seconds_total",
    "Seconds writers spent stalled at the hard memtable/L0 backpressure "
    "threshold, by reason (memtable/l0)")
WRITE_STALL_TIMEOUTS = REGISTRY.counter(
    "greptimedb_tpu_write_stall_timeouts_total",
    "Stalls that hit stall_timeout_s and fell back to an inline flush "
    "(the maintenance plane is wedged or saturated)")
# frontend concurrency plane (concurrency/ package): the shape-keyed
# plan cache, admission control, and the fast lane that carry
# fleet-scale dashboard traffic (ISSUE 6) — hit rates and rejection
# behavior are asserted from these series, not eyeballed
PLAN_CACHE_EVENTS = REGISTRY.sharded_counter(
    "greptimedb_tpu_plan_cache_events_total",
    "Shape-keyed logical-plan cache events by kind (hit/miss/evict/"
    "invalidate — invalidations come from DDL, schema drift, and "
    "rollup-substitution state changes; skip events carry a reason "
    "label naming why a statement never reached the cache: join/cte/"
    "subquery/window)")
ADMISSION_EVENTS = REGISTRY.sharded_counter(
    "greptimedb_tpu_admission_events_total",
    "Admission control decisions by kind (admit/queue/reject_full/"
    "reject_timeout; rejections carry the tenant label)")
ADMISSION_QUEUE_DEPTH = REGISTRY.gauge(
    "greptimedb_tpu_admission_queue_depth",
    "Statements currently waiting in the bounded admission queue")
ADMISSION_WAIT_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_admission_wait_seconds",
    "Time queued statements waited for an execution slot",
    exemplars=True)
ENCODE_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_encode_seconds",
    "Wall time serializing one query result to its wire format "
    "(HTTP JSON / MySQL packets), by protocol — compare against "
    "query_duration_seconds for the execute-vs-encode split",
    exemplars=True)

# cross-process serving fabric (greptimedb_tpu/shm/): the shared-memory
# artifact plane N frontend processes on one box attach to — fast-lane
# templates, plan-cache entries and warm XLA shape keys ride it
SHM_FABRIC_EVENTS = REGISTRY.sharded_counter(
    "greptimedb_tpu_shm_fabric_events_total",
    "Serving-fabric events by kind (hit = an artifact adopted from a "
    "peer process instead of rebuilt, miss = probed but absent, "
    "publish = a locally built artifact shared, invalidate = a version "
    "bump or wipe fanned out to peers, corrupt = a slot failed its "
    "generation/bounds check, detach = this process fell back to the "
    "private in-process lane; the kind label names the artifact plane: "
    "template/plan/fabric)")
SHM_FABRIC_BYTES = REGISTRY.gauge(
    "greptimedb_tpu_shm_fabric_bytes",
    "Bytes of the attached shared-memory fabric (segment = fabric, "
    "the artifact plane) by dimension (size = mapped capacity, used = "
    "heap bytes behind the current write cursor)")

# parse-free serving fast lane (concurrency/fast_lane.py, ISSUE 14): a
# text-keyed template cache in front of the plan cache — a repeat-shape
# statement goes socket bytes -> admission -> bind -> execute -> encode
# with zero parse_sql, zero AST, zero logical planning
FAST_LANE_EVENTS = REGISTRY.sharded_counter(
    "greptimedb_tpu_fast_lane_events_total",
    "Text-template serving fast-lane events by kind (hit = a statement "
    "executed from its cached bound-plan template without parsing, "
    "miss = first sighting of a template (built via the slow lane), "
    "fallback = scanned but ineligible — the reason label names why: "
    "ambiguous literals, comments, non-SELECT verbs, plugins, pending "
    "rollup-substitution probes — invalidate = entries dropped by DDL "
    "or a TableInfo drift check, coalesced = concurrent identical "
    "requests that rode another request's in-flight execution, "
    "stale_flight = a request that found an identical execution in "
    "flight over an older version of the table's data than it saw on "
    "arrival, and started one of its own)")
STAGE_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_query_stage_seconds",
    "Per-request serving-stage wall time by stage, observed by the "
    "stage spans of utils/tracing.py as they close. Flat stages, of "
    "which no two overlap in one request: parse, plan, fast_bind, "
    "admission_wait, scan, host_agg, upload, device, readback, "
    "assemble, encode, send; other = the request root's duration minus "
    "their sum. Enclosing labels, not part of that sum: execute and "
    "fast_execute (the executor call on the slow / fast lane), request "
    "(the root). Buckets carry OpenMetrics trace_id exemplars — a slow "
    "bucket links straight to a trace to pull via /v1/traces/<id>",
    exemplars=True)
STAGE_CPU_SECONDS = REGISTRY.sharded_counter(
    "greptimedb_tpu_query_stage_cpu_seconds_total",
    "CPU seconds of the thread that ran a serving stage "
    "(time.thread_time_ns), added as each stage segment closes, under "
    "the labels of query_stage_seconds: the twelve flat stages, other = "
    "the request root's CPU minus theirs, and the enclosing execute, "
    "fast_execute and request. A stage's wall seconds minus these are "
    "the time its thread was off the CPU: waiting for the interpreter "
    "lock, the device, a socket, a disk or another lock. CPU spent with "
    "the interpreter lock released (arrow decode, numpy, an XLA:CPU "
    "compile) counts as CPU. One more label, background: the CPU of "
    "threads that work for a request beside its own (everything run "
    "under tracing.propagate, bg:<stage> spans included: scan pool, "
    "part workers, the hedge's warm-up): they take "
    "the same lock; their wall time counts nowhere")
INGEST_REQUEST_CPU_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_ingest_request_cpu_seconds",
    "CPU seconds of the request thread over one line-protocol write "
    "request (the root span's thread_time): a write runs no statement "
    "and observes no request stage",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5))
LOCK_WAIT_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_interpreter_lock_wait_seconds",
    "How much later than asked the interpreter-lock probe "
    "(utils/lock_probe.py) was running again after a 20 ms sleep: "
    "time.sleep gives the lock up and takes it back before it returns, "
    "so the lateness samples what one re-acquisition costs at that "
    "moment, plus the timer's own lateness (what an idle server reads)",
    buckets=(0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
             0.1, 0.25))
COUNTER_SHARDS = REGISTRY.gauge(
    "greptimedb_tpu_metrics_counter_shards",
    "Live per-thread shard cells across all sharded hot counters "
    "(folded into the base series when their thread dies); scrape-time "
    "visibility into the lock-light counter plane")


def _collect_counter_shards() -> None:
    n = 0
    with REGISTRY._lock:
        metrics = list(REGISTRY._metrics)
    for m in metrics:
        if isinstance(m, ShardedCounter):
            n += m.shard_count()
    COUNTER_SHARDS.set(float(n))


REGISTRY.register_collector(_collect_counter_shards)

ROLLUP_SUBSTITUTIONS = REGISTRY.counter(
    "greptimedb_tpu_maintenance_rollup_substitutions_total",
    "Aggregate queries served from rollup plane SSTs instead of raw "
    "data, by table and resolution")

# mesh-sharded hot path (parallel/sharded_dispatch.py) + distributed
# plan-fragment pushdown (query/dist_agg.py): the scale-out surface —
# how often queries ride the device mesh / ship partial planes instead
# of raw rows, and how balanced the shard assignment is
MESH_DISPATCHES = REGISTRY.counter(
    "greptimedb_tpu_mesh_dispatch_total",
    "Aggregate scans dispatched over the device mesh, by kernel path "
    "(sharded/sharded_prepared) and shard count")
MESH_SHARD_SKEW = REGISTRY.gauge(
    "greptimedb_tpu_mesh_shard_skew_ratio",
    "Row-balance of the latest mesh shard plan: max per-shard rows over "
    "the mean (1.0 = perfectly balanced; padding wastes cycles above it)")
FRAGMENT_PUSHDOWNS = REGISTRY.counter(
    "greptimedb_tpu_fragment_pushdown_total",
    "Distributed plan fragments shipped to region owners, by mode "
    "(agg/topk/rows/rows_agg/window/lastpoint/rollup — partial "
    "planes or pruned candidates return, never raw region scans)")
REGION_ROUTE = REGISTRY.counter(
    "greptimedb_tpu_region_route_total",
    "Regions of a multi-region table a statement was routed to, by "
    "outcome: scanned (the statement's predicates on the partition "
    "columns can match it) or pruned (the table's partition rule says "
    "they cannot); a table of one region counts nothing")
REGION_PARTIAL = REGISTRY.counter(
    "greptimedb_tpu_region_partial_total",
    "Regional folds of a multi-region table's aggregate that dispatched "
    "a device program, by placement: own_chip (every dispatch ran on the "
    "chip the table's layout gives the region) or other")
REGION_FANOUT_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_region_fanout_seconds",
    "Wall time of one fan-out over the matching regions of a multi-"
    "region table: scan, per-part fold and readback of every region "
    "side by side, i.e. its slowest region")
REGION_COMBINE_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_region_combine_seconds",
    "Host wall time of combining all regions' value-keyed partials "
    "into one aggregate (combine_partials over the union of group keys)")
RANGE_SELECT = REGISTRY.counter(
    "greptimedb_tpu_range_select_total",
    "RANGE ... ALIGN statements answered, by the execution path of the "
    "lowered aggregate plus +range_combine (query/range_select.py)")
RANGE_SELECT_SECONDS = REGISTRY.histogram(
    "greptimedb_tpu_range_select_seconds",
    "Host wall time of a RANGE statement's steps after its lowered "
    "aggregate, by phase: combine (series and bucket indices, the "
    "sliding combine of adjacent ALIGN buckets per RANGE, finalize) and "
    "fill (the dense grid and the FILL policies)")
RANGE_WINDOWS = REGISTRY.counter(
    "greptimedb_tpu_range_windows_total",
    "Output points of RANGE statements, by kind: observed (the window "
    "held rows) or filled (FILL produced it)")
EXPIRED_SSTS = REGISTRY.counter(
    "greptimedb_tpu_maintenance_expired_ssts_total",
    "SSTs dropped whole by retention (TTL) expiry")

# incremental aggregation (query/partial_cache.py): per-part partial-
# aggregate planes cached by immutable file identity — repeated
# aggregate queries fold only the delta (memtable rows + files flushed
# since) instead of re-reducing every SST part from scratch
PARTIAL_AGG_CACHE_EVENTS = REGISTRY.counter(
    "greptimedb_tpu_partial_agg_cache_events_total",
    "Partial-aggregate cache events by kind (hit = an immutable part's "
    "[G, F] partial served without touching its rows, miss = computed "
    "and cached, evict = aged out of the byte budget, invalidate = "
    "dropped by a region seam — compaction swap, retention expiry, "
    "DROP/TRUNCATE, fallback = an aggregate shape the incremental fold "
    "could not serve exactly: tombstones, cross-part dedup, sparse "
    "cardinality, or multi-block parts)")
PARTIAL_AGG_CACHE_BYTES = REGISTRY.gauge(
    "greptimedb_tpu_partial_agg_cache_bytes",
    "Host bytes held by the partial-aggregate cache (per-part [G, F] "
    "planes + their decoded group-key columns, plus cached per-region "
    "fragment planes in cluster mode)")
PARTIAL_AGG_DELTA_ROWS = REGISTRY.counter(
    "greptimedb_tpu_partial_agg_delta_rows_total",
    "Rows actually folded by incremental aggregate executions, by kind "
    "(delta = uncached part + memtable rows that ran through kernels, "
    "cached = rows whose partial plane was served from the cache)")

# ---- static analysis (tools/gtpu_lint.py, tier-1) --------------------------

LINT_FINDINGS = REGISTRY.gauge(
    "greptimedb_tpu_lint_findings_total",
    "gtpu-lint findings per checker from the latest lint run "
    "(allowlisted included) — the machine-checked invariant surface; "
    "anything unallowed fails tier-1")

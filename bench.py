#!/usr/bin/env python
"""TSBS-style benchmark suite covering every BASELINE.json tracked config.

Headline metric stays double-groupby-all (the north star, BASELINE.md —
reference GreptimeDB v0.8.0: 2215.44 ms local). The other tracked axes
run in the same process and land in detail.configs:

  1. single_groupby_1_1_1  — 1 field, 1 host, 1h @1m buckets (15.70 ms ref)
  2. double_groupby_all    — avg of 10 fields by (hour, hostname) (2215.44)
  3. lastpoint             — newest row per host via last_value (6756.12)
  4. high_cpu_all          — full-scan filter usage_user > 90 (5402.31)
  5. promql_rate           — TQL rate() over 10k series x 1 day @15s
                             (tracked config #3), with a same-box numpy
                             straw-man anchor; budget-sized span
  6. high_cardinality      — segment-sum over 1M tag combos scaled
                             toward the 1B-row tracked config #5
  7. compaction_reencode   — L0→L1 merge re-encode throughput (rows/s)
  8. sql_insert            — durable SQL INSERT statement path (rows/s)
  9. qps_single_groupby    — 50 keep-alive HTTP clients (ref 1165.73 qps)
 10. double_groupby_100m   — the headline query at tracked config #2
                             scale (100M rows / 4k hosts), budget-sized
 11. qps_mixed_tenants     — 3-tenant mixed workload (dashboard /
                             point lastpoint / high-card groupby) with
                             per-tenant p99/p999 + plan-cache hit rate
 12. incremental_agg       — partial-aggregate cache: cold fold vs warm
                             repeat vs post-flush one-new-file fold,
                             bit-for-bit digests + delta-row proof

Pipeline measured end-to-end through the SQL engine: SQL parse -> plan ->
region scan (SST/memtable) -> device blocks -> fused filter+group+segment
reduction kernel -> host result assembly. Median of repeated runs after one
warm-up, matching the reference's warm-page-cache TSBS methodology (here
the warm cache is HBM-resident column blocks).

When the accelerator backend is live, one double-groupby run is captured
under jax.profiler (trace dir in detail.profile_dir) for MFU/bandwidth
analysis.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "ms", "vs_baseline": N, "detail": ...}
vs_baseline > 1 means faster than the reference's 2215.44 ms.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# BASELINE.md reference numbers (v0.8.0, local 8-core)
BASELINE_MS = 2215.44           # double-groupby-all
BASE_SINGLE_MS = 15.70          # single-groupby-1-1-1
BASE_LASTPOINT_MS = 6756.12     # lastpoint
BASE_HIGH_CPU_MS = 5402.31      # high-cpu-all
BASE_GBOL_MS = 754.50           # groupby-orderby-limit
BASE_MAX_ALL_8_MS = 51.69       # cpu-max-all-8
BASE_INGEST_ROWS_S = 315369.66  # TSBS ingest rate


HOSTS = int(os.environ.get("BENCH_HOSTS", "4000"))
HOURS = int(os.environ.get("BENCH_HOURS", "12"))
STEP_S = int(os.environ.get("BENCH_STEP_S", "10"))
REPEATS = int(os.environ.get("BENCH_REPEATS", "3"))
PROM_SERIES = int(os.environ.get("BENCH_PROM_SERIES", "10000"))
# tracked config #3 (BASELINE.json): 10k series x 1 DAY @15s = 57.6M rows
PROM_HOURS = int(os.environ.get("BENCH_PROM_HOURS", "24"))
HC_COMBOS = int(os.environ.get("BENCH_HC_COMBOS", "1000000"))
HC_POINTS = int(os.environ.get("BENCH_HC_POINTS", "10"))
COMPACT_ROWS = int(os.environ.get("BENCH_COMPACT_ROWS", "4000000"))
# comma-separated subset, e.g. BENCH_CONFIGS=double_groupby_all,lastpoint
CONFIGS = [c for c in os.environ.get("BENCH_CONFIGS", "").split(",") if c]
FIELDS = [f"usage_{n}" for n in (
    "user", "system", "idle", "nice", "iowait", "irq", "softirq",
    "steal", "guest", "guest_nice")]

T0_MS = 1456790400000  # 2016-03-01T00:00:00Z

T_MAIN_START = None  # set by main(); basis for wall-clock budget sizing


def partial_path() -> str:
    """Where every emit_result is mirrored on disk. The supervisor hands
    the path to its children via env; an EXTERNAL kill (rc=124 wrapping
    the supervisor itself — the r05 incident left `parsed: null`) can
    then still salvage the newest checkpoint from the file."""
    return os.environ.get(
        "BENCH_PARTIAL_PATH",
        os.path.join(tempfile.gettempdir(), "gtpu_bench_partial.json"))


def write_partial(line: str) -> None:
    """Atomically persist the latest result line (flush + fsync: the
    whole point is surviving a SIGKILL moments later)."""
    try:
        path = partial_path()
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError as e:  # noqa: PERF203 — salvage is best-effort
        log(f"write_partial failed: {e}")


def budget_left_s(reserve=150.0):
    """Seconds of the supervisor-granted wall budget still unspent.
    The big tracked configs (100M double-groupby, 24h PromQL, 1B-target
    high-cardinality) size their ingest against this so one config
    overrunning cannot starve the final JSON emit. The default reserve
    was widened 90 -> 150 after r05: the anchor configs must always
    land even when a supervisor timeout hits mid-run."""
    total = float(os.environ.get("BENCH_TOTAL_TIMEOUT_S", "2400"))
    if T_MAIN_START is None:
        return total - reserve
    return total - (time.monotonic() - T_MAIN_START) - reserve


def affordable_rows(reserve_s, ingest_rps, width_factor=1.0):
    """Rows the remaining budget can ingest: `reserve_s` is held back
    for the config's own query runs + the configs after it;
    `width_factor` scales the measured 12-column cpu ingest rate for
    narrower tables (3-column rows move ~2x faster). A 0.75 derate
    covers flush/compaction debt at scale — round-5 incident: sized at
    the measured 195k rows/s, achieved 115k, blew the supervisor
    window."""
    rps = max(ingest_rps, 50000.0) * width_factor * 0.75
    return int(max(0.0, budget_left_s() - reserve_s) * rps)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def enabled(name):
    return not CONFIGS or name in CONFIGS


def build_db(data_dir):
    from greptimedb_tpu.catalog import Catalog, MemoryKv
    from greptimedb_tpu.query import QueryEngine
    from greptimedb_tpu.storage import RegionEngine
    from greptimedb_tpu.storage.engine import EngineConfig

    engine = RegionEngine(EngineConfig(data_dir=data_dir))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    field_defs = ",\n  ".join(f"{f} DOUBLE" for f in FIELDS)
    qe.execute_one(f"""
        CREATE TABLE cpu (
          hostname STRING,
          ts TIMESTAMP(3) NOT NULL,
          {field_defs},
          TIME INDEX (ts),
          PRIMARY KEY (hostname)
        ) WITH (append_mode = 'true')
    """)
    return engine, qe


def ingest(engine, qe, t0_ms):
    """Ingest through the write path (RecordBatch put = the gRPC-analog
    bulk route), one batch per simulated time slice group."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    info = qe.catalog.table("public", "cpu")
    schema = info.schema
    rid = info.region_ids[0]
    rng = np.random.default_rng(7)
    points = HOURS * 3600 // STEP_S
    host_names = np.asarray([f"host_{i}" for i in range(HOSTS)], dtype=object)
    rows_total = 0
    t_start = time.perf_counter()
    slice_points = max(1, (1 << 21) // HOSTS)  # ~2M rows per batch
    for p0 in range(0, points, slice_points):
        p1 = min(p0 + slice_points, points)
        npts = p1 - p0
        n = npts * HOSTS
        host_codes = np.tile(np.arange(HOSTS, dtype=np.int32), npts)
        ts = np.repeat(
            t0_ms + (np.arange(p0, p1, dtype=np.int64) * STEP_S * 1000), HOSTS
        )
        cols = {
            "hostname": DictVector(host_codes, host_names),
            "ts": ts,
        }
        for f in FIELDS:
            cols[f] = rng.uniform(0.0, 100.0, n)
        batch = RecordBatch(schema, cols)
        engine.put(rid, batch)
        rows_total += n
    ingest_s = time.perf_counter() - t_start
    return rows_total, ingest_s


def timed_sql(qe, sql, repeats=None, expect_rows=None):
    """Warm-up once (compile + HBM cache fill), then median of repeats.
    The warm-up runs under a fresh trace so its cost decomposes into
    engine spans (scan/aggregate/...) — distinguishing XLA compile time
    from SST read + decode when diagnosing cold starts. The execution
    tier that served the query (device | host — physical.tier_for)
    rides back in the spans dict under "tier"."""
    from greptimedb_tpu.session import QueryContext
    from greptimedb_tpu.utils import tracing

    tid = tracing.new_trace_id()
    t = time.perf_counter()
    r = qe.execute_one(sql, QueryContext(trace_id=tid))
    warm_ms = (time.perf_counter() - t) * 1000
    spans = {}
    for s in tracing.spans_for(tid):
        spans[s.name] = round(spans.get(s.name, 0.0) + s.duration_ms, 1)
    spans["tier"] = getattr(qe.executor, "last_tier", None)
    if expect_rows is not None:
        assert r.num_rows == expect_rows, (r.num_rows, expect_rows)
    times = []
    for _ in range(repeats or REPEATS):
        t = time.perf_counter()
        qe.execute_one(sql)
        times.append((time.perf_counter() - t) * 1000)
    return float(np.median(times)), warm_ms, r.num_rows, spans


def bench_cpu_suite(qe, results, guard=None, checkpoint=None):
    """Quick TSBS configs. Each config runs isolated (`guard`) and the
    salvageable summary refreshes after every one (`checkpoint`) —
    r01/r04 ended rc=0 with `parsed: null` because one config crashing
    inside this suite sank every result before the first checkpoint."""
    t_end_ms = T0_MS + HOURS * 3600 * 1000

    def _run(name, fn):
        if guard is not None:
            guard(name, fn)
        elif enabled(name):
            fn()
        if checkpoint is not None:
            checkpoint()

    def _single_groupby():
        sql = (
            "SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
            "max(usage_user) FROM cpu "
            f"WHERE hostname = 'host_0' AND ts >= {T0_MS} "
            f"AND ts < {T0_MS + 3600 * 1000} "
            "GROUP BY minute ORDER BY minute"
        )
        p50, warm, nrows, _ = timed_sql(qe, sql, expect_rows=60)
        log(f"single-groupby-1-1-1: {p50:.1f} ms (warm-up {warm:.0f} ms)")
        results["single_groupby_1_1_1"] = {
            "p50_ms": round(p50, 2), "tier": qe.executor.last_tier, "baseline_ms": BASE_SINGLE_MS,
            "vs_baseline": round(BASE_SINGLE_MS / p50, 3)}

    _run("single_groupby_1_1_1", _single_groupby)

    def _double_groupby():
        avg_list = ", ".join(f"avg({f})" for f in FIELDS)
        sql = (
            f"SELECT date_bin(INTERVAL '1 hour', ts) AS hour, hostname, "
            f"{avg_list} FROM cpu WHERE ts >= {T0_MS} AND ts < {t_end_ms} "
            f"GROUP BY hour, hostname ORDER BY hour, hostname"
        )
        p50, warm, nrows, wspans = timed_sql(qe, sql,
                                             expect_rows=HOSTS * HOURS)
        log(f"double-groupby-all: {p50:.1f} ms (warm-up {warm:.0f} ms, "
            f"{nrows} groups)")
        results["double_groupby_all"] = {
            "p50_ms": round(p50, 2), "tier": qe.executor.last_tier, "warmup_ms": round(warm, 1),
            "groups": nrows, "warmup_spans_ms": wspans,
            "baseline_ms": BASELINE_MS,
            "vs_baseline": round(BASELINE_MS / p50, 3)}
        import jax as _jax
        if _jax.default_backend() != "cpu":
            # A/B both tiers on the headline: the router (with the
            # first-touch hedge) may have served host-side while the
            # device executable compiled in the background — measure
            # each tier explicitly so the artifact carries the chip
            # number AND what the link costs
            prev = os.environ.get("GREPTIMEDB_TPU_HOST_TIER")
            try:
                os.environ["GREPTIMEDB_TPU_HOST_TIER"] = "off"
                p50_d, _, _, _ = timed_sql(qe, sql, repeats=2,
                                           expect_rows=HOSTS * HOURS)
                os.environ["GREPTIMEDB_TPU_HOST_TIER"] = "force"
                p50_h, _, _, _ = timed_sql(qe, sql, repeats=2,
                                           expect_rows=HOSTS * HOURS)
            finally:
                if prev is None:
                    os.environ.pop("GREPTIMEDB_TPU_HOST_TIER", None)
                else:
                    os.environ["GREPTIMEDB_TPU_HOST_TIER"] = prev
            log(f"double-groupby-all A/B: device {p50_d:.1f} ms, "
                f"host {p50_h:.1f} ms")
            results["double_groupby_all"]["device_tier_p50_ms"] = \
                round(p50_d, 2)
            results["double_groupby_all"]["host_tier_p50_ms"] = \
                round(p50_h, 2)

    _run("double_groupby_all", _double_groupby)

    def _gbol():
        # TSBS groupby-orderby-limit: last 5 minute-buckets of max before
        # a cutoff inside the range
        cutoff = T0_MS + (HOURS * 3600 * 1000) * 3 // 4
        sql = (
            "SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
            f"max(usage_user) FROM cpu WHERE ts < {cutoff} "
            "GROUP BY minute ORDER BY minute DESC LIMIT 5"
        )
        p50, warm, nrows, _ = timed_sql(qe, sql, expect_rows=5)
        log(f"groupby-orderby-limit: {p50:.1f} ms")
        results["groupby_orderby_limit"] = {
            "p50_ms": round(p50, 2), "tier": qe.executor.last_tier, "baseline_ms": BASE_GBOL_MS,
            "vs_baseline": round(BASE_GBOL_MS / p50, 3)}

    _run("groupby_orderby_limit", _gbol)

    def _max_all_8():
        # TSBS cpu-max-all-8: max of all 10 fields for 8 hosts over 8h
        max_list = ", ".join(f"max({f})" for f in FIELDS)
        hosts8 = ", ".join(f"'host_{i}'" for i in range(8))
        sql = (
            f"SELECT date_bin(INTERVAL '1 hour', ts) AS hour, {max_list} "
            f"FROM cpu WHERE hostname IN ({hosts8}) "
            f"AND ts >= {T0_MS} AND ts < {T0_MS + 8 * 3600 * 1000} "
            "GROUP BY hour ORDER BY hour"
        )
        p50, warm, nrows, _ = timed_sql(qe, sql, expect_rows=min(8, HOURS))
        log(f"cpu-max-all-8: {p50:.1f} ms")
        results["cpu_max_all_8"] = {
            "p50_ms": round(p50, 2), "tier": qe.executor.last_tier, "baseline_ms": BASE_MAX_ALL_8_MS,
            "vs_baseline": round(BASE_MAX_ALL_8_MS / p50, 3)}

    _run("cpu_max_all_8", _max_all_8)

    def _lastpoint():
        lv_list = ", ".join(
            f"last_value({f} ORDER BY ts)" for f in FIELDS)
        sql = f"SELECT hostname, {lv_list} FROM cpu GROUP BY hostname"
        p50, warm, nrows, _ = timed_sql(qe, sql, expect_rows=HOSTS)
        path = qe.executor.last_path or ""
        log(f"lastpoint: {p50:.1f} ms (warm-up {warm:.0f} ms, "
            f"path={path})")
        results["lastpoint"] = {
            "p50_ms": round(p50, 2), "tier": qe.executor.last_tier,
            "path": path,  # "lastscan+..." = newest-first pruning hit
            "baseline_ms": BASE_LASTPOINT_MS,
            "vs_baseline": round(BASE_LASTPOINT_MS / p50, 3)}

    _run("lastpoint", _lastpoint)

    def _high_cpu():
        sql = (
            f"SELECT * FROM cpu WHERE usage_user > 90.0 "
            f"AND ts >= {T0_MS} AND ts < {t_end_ms}"
        )
        p50, warm, nrows, _ = timed_sql(qe, sql)
        log(f"high-cpu-all: {p50:.1f} ms ({nrows} rows out)")
        results["high_cpu_all"] = {
            "p50_ms": round(p50, 2), "tier": qe.executor.last_tier, "rows_out": nrows,
            "baseline_ms": BASE_HIGH_CPU_MS,
            "vs_baseline": round(BASE_HIGH_CPU_MS / p50, 3)}

    _run("high_cpu_all", _high_cpu)


def bench_promql(engine, qe, results, ingest_rps=300000.0):
    """Config 3: PromQL rate() over PROM_SERIES x PROM_HOURS @15s —
    tracked spec is 10k series x 1 DAY (57.6M rows). Budget-sized: the
    span shrinks (recorded in `at_spec`/`hours`) if the wall budget
    cannot fit the full day's ingest."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    # width_factor 1.0 ON PURPOSE despite the narrow rows: the numpy
    # anchor re-reads and pivots the whole series set (~half the ingest
    # cost again) and the full-span evals pay XLA compiles — treating
    # the effective rate as the plain ingest rate covers both
    # (round-5: the 24h shape overran the window twice without this)
    affordable = affordable_rows(300, ingest_rps, width_factor=1.0)
    hours = PROM_HOURS
    while hours > 1 and hours * 3600 // 15 * PROM_SERIES > affordable:
        hours //= 2
    if hours < PROM_HOURS:
        log(f"promql span cut to {hours}h (budget {budget_left_s():.0f}s "
            "left)")
    qe.execute_one(
        "CREATE TABLE prom_cpu (host STRING, val DOUBLE, "
        "ts TIMESTAMP(3) NOT NULL, TIME INDEX (ts), PRIMARY KEY (host)) "
        "WITH (append_mode = 'true')")
    info = qe.catalog.table("public", "prom_cpu")
    rid = info.region_ids[0]
    rng = np.random.default_rng(11)
    points = hours * 3600 // 15
    names = np.asarray([f"s{i}" for i in range(PROM_SERIES)], dtype=object)
    slice_points = max(1, (1 << 21) // PROM_SERIES)
    t_start = time.perf_counter()
    rows = 0
    flush_every = max(1, points // (slice_points * 8))
    # counter-style: per-series monotone increments so rate() is
    # realistic. Periodic flushes produce time-bounded SST files (the
    # shape continuous ingestion creates), so scans prune by time.
    for i, p0 in enumerate(range(0, points, slice_points)):
        p1 = min(p0 + slice_points, points)
        npts = p1 - p0
        n = npts * PROM_SERIES
        codes = np.tile(np.arange(PROM_SERIES, dtype=np.int32), npts)
        ts = np.repeat(
            T0_MS + np.arange(p0, p1, dtype=np.int64) * 15000, PROM_SERIES)
        base = np.repeat(
            np.arange(p0, p1, dtype=np.float64) * 50.0, PROM_SERIES)
        vals = base + rng.uniform(0, 50.0, n)
        batch = RecordBatch(info.schema, {
            "host": DictVector(codes, names), "ts": ts, "val": vals})
        engine.put(rid, batch)
        rows += n
        if (i + 1) % flush_every == 0:
            engine.flush(rid)
    log(f"prom ingest: {rows} rows in {time.perf_counter() - t_start:.1f}s")
    engine.flush(rid)
    t0_s = T0_MS // 1000
    t_end_s = t0_s + hours * 3600
    # evaluate over the FULL ingested span at the dashboard step (the
    # tracked config is rate over the whole retention window, not a
    # trailing slice — round-3 verdict weak #5), plus the trailing
    # 10-minute window every dashboard refresh issues
    step_s = max(60, hours * 3600 // 240)  # ~240 eval points
    # rate window scales with the step (a 1-day dashboard uses [6m] at
    # 6m resolution, not [2m]) — and the blocked-window evaluator needs
    # range to be a positive MULTIPLE of step (e.g. 6h span: step 90s
    # needs window 180s, not 120s)
    window_s = -(-max(120, step_s) // step_s) * step_s
    tql = (f"TQL EVAL ({t0_s}, {t_end_s}, '{step_s}s') "
           f"sum(rate(prom_cpu[{window_s}s]))")
    p50, warm, nrows, _ = timed_sql(qe, tql)
    tql_tail = (f"TQL EVAL ({t_end_s - 600}, {t_end_s}, '60s') "
                "sum(rate(prom_cpu[2m]))")
    p50_tail, _, _, _ = timed_sql(qe, tql_tail)
    log(f"promql rate: full-span {p50:.1f} ms, trailing-10m "
        f"{p50_tail:.1f} ms (warm-up {warm:.0f} ms)")
    anchor = None
    try:
        anchor = promql_anchor(engine, qe, t0_s, t_end_s, step_s,
                               window_s)
    except Exception as e:  # noqa: BLE001 — comparator must not sink the run
        log(f"promql anchor failed: {e!r}")
        anchor = {"error": repr(e)[:200]}
    # like-for-like: the engine p50 is the post-warm-up median with
    # series resident in HBM, so the comparator is the anchor's
    # eval-only time, not its one-time parquet load (same convention
    # as anchor_pyarrow_double_groupby's agg_only_p50_ms)
    vs_anchor = None
    if anchor and anchor.get("eval_only_p50_ms"):
        vs_anchor = round(anchor["eval_only_p50_ms"] / p50, 3)
    results["promql_rate"] = {
        "p50_ms": round(p50, 2), "span": "full",
        "eval_points": (t_end_s - t0_s) // step_s,
        "tail_10m_p50_ms": round(p50_tail, 2),
        "series": PROM_SERIES,
        "hours": hours, "at_spec": hours >= PROM_HOURS, "rows": rows,
        "step_s": step_s, "window_s": window_s,
        "anchor": anchor,
        "baseline_ms": (anchor or {}).get("eval_only_p50_ms"),
        "vs_baseline": vs_anchor,
        "note": ("baseline is the same-box numpy straw-man anchor's "
                 "eval-only time (no published reference number for "
                 "this shape)")}


def promql_anchor(engine, qe, t0_s, t_end_s, step_s, window_s=120):
    """Same-box numpy straw-man for `sum(rate(prom_cpu[W]))` — the
    comparator the round-4 verdict asked for (weak #7). Reads the same
    SST parquet, pivots to a dense [S, P] matrix (all series share the
    15s grid), then evaluates Prometheus extrapolated-rate boundary
    semantics (ref src/promql/src/functions/extrapolate_rate.rs:85-92)
    per eval point with vectorized searchsorted — what a competent
    engineer would hand-write in numpy for exactly this data. No
    counter-reset correction: the generated series are strictly
    increasing by construction (base +50/point, noise < 50), so resets
    never occur in this dataset and both sides compute the same
    function. e2e includes the parquet read + pivot; eval_only assumes
    the matrix is resident."""
    import statistics

    import pyarrow.parquet as pq

    info = qe.catalog.table("public", "prom_cpu")
    paths = []
    for rid in info.region_ids:
        region = engine.region(rid)
        paths += [region.sst_reader.path(m.file_id)
                  for m in region.files.values()]
    if not paths:
        return {"skipped": "no SST files"}

    def load():
        import pyarrow as pa
        t = pa.concat_tables(pq.read_table(
            p, columns=["host", "ts", "val"]) for p in paths)
        host = t.column("host").combine_chunks()
        codes = np.asarray(host.dictionary_encode().indices)
        ts = np.asarray(t.column("ts").cast("int64")) // 1000  # s
        vals = np.asarray(t.column("val"))
        grid, t_inv = np.unique(ts, return_inverse=True)
        n_s = int(codes.max()) + 1
        mat = np.empty((n_s, len(grid)))
        mat.fill(np.nan)
        mat[codes, t_inv] = vals
        return grid, mat

    def eval_rate(grid, mat):
        window = window_s
        out = np.empty((t_end_s - t0_s) // step_s + 1)
        for k, t in enumerate(range(t0_s, t_end_s + 1, step_s)):
            # Prometheus range windows are left-open: (t-window, t]
            i0 = np.searchsorted(grid, t - window, side="right")
            i1 = np.searchsorted(grid, t, side="right") - 1
            if i1 <= i0:
                out[k] = np.nan
                continue
            first, last = mat[:, i0], mat[:, i1]
            tf, tl = grid[i0], grid[i1]
            sampled = tl - tf
            slope = (last - first) / sampled
            # Prometheus extrapolation: extend fully to a window edge
            # when the gap is < 1.1x the average sample interval,
            # else cap at half an interval (extrapolate_rate.rs:85-92)
            avg_gap = sampled / max(i1 - i0, 1)
            head, tail = tf - (t - window), t - tl
            duration = sampled \
                + (head if head < 1.1 * avg_gap else avg_gap / 2) \
                + (tail if tail < 1.1 * avg_gap else avg_gap / 2)
            out[k] = float(np.nansum(slope * duration)) / window
        return out

    t0 = time.perf_counter()
    grid, mat = load()
    load_s = time.perf_counter() - t0
    eval_times = []
    for _ in range(max(REPEATS, 1)):
        t0 = time.perf_counter()
        eval_rate(grid, mat)
        eval_times.append(time.perf_counter() - t0)
    eval_p50 = statistics.median(eval_times) * 1000
    e2e_p50 = load_s * 1000 + eval_p50
    log(f"promql anchor (numpy over same SSTs): load {load_s * 1000:.0f} ms "
        f"+ eval {eval_p50:.0f} ms = {e2e_p50:.0f} ms")
    return {"e2e_p50_ms": round(e2e_p50, 2),
            "load_ms": round(load_s * 1000, 2),
            "eval_only_p50_ms": round(eval_p50, 2),
            "note": ("numpy extrapolated-rate straw-man over the same "
                     "parquet on this box; e2e = read+pivot+eval")}


def bench_high_cardinality(engine, qe, results, ingest_rps=300000.0):
    """Config 5: segment-sum over HC_COMBOS distinct tag combos —
    tracked spec is 1B rows x 1M combos (north star). Points-per-combo
    scales toward BENCH_HC_TARGET_ROWS (default 1B) under the wall
    budget; the actual rows and the cut are recorded (`at_spec`)."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    target_rows = int(os.environ.get("BENCH_HC_TARGET_ROWS",
                                     "1000000000"))
    affordable = affordable_rows(150, ingest_rps, width_factor=2.0)
    rows_planned = max(HC_COMBOS * HC_POINTS,
                       min(target_rows, affordable))
    points = max(HC_POINTS, rows_planned // HC_COMBOS)
    qe.execute_one(
        "CREATE TABLE hc (tag STRING, v DOUBLE, ts TIMESTAMP(3) NOT NULL, "
        "TIME INDEX (ts), PRIMARY KEY (tag)) WITH (append_mode = 'true')")
    info = qe.catalog.table("public", "hc")
    rid = info.region_ids[0]
    rng = np.random.default_rng(13)
    names = np.asarray([f"t{i:07d}" for i in range(HC_COMBOS)], dtype=object)
    t_start = time.perf_counter()
    rows = 0
    combos_done = 0
    combos_per_slice = max(1, (1 << 21) // points)
    flushed = 0
    for c0 in range(0, HC_COMBOS, combos_per_slice):
        c1 = min(c0 + combos_per_slice, HC_COMBOS)
        ncomb = c1 - c0
        n = ncomb * points
        codes = np.repeat(np.arange(ncomb, dtype=np.int32), points)
        ts = np.tile(
            T0_MS + np.arange(points, dtype=np.int64) * 1000, ncomb)
        batch = RecordBatch(info.schema, {
            "tag": DictVector(codes, names[c0:c1]), "ts": ts,
            "v": rng.uniform(0, 1, n)})
        engine.put(rid, batch)
        rows += n
        combos_done = c1
        if rows - flushed >= 30_000_000:
            engine.flush(rid)
            flushed = rows
        if budget_left_s() < 420:
            # the query itself scans rows/5M-per-second x (warm + runs)
            # — reserve for it, not just the emit
            log(f"hc ingest stopped at {rows} rows: budget")
            break
    log(f"hc ingest: {rows} rows in {time.perf_counter() - t_start:.1f}s")
    engine.flush(rid)
    sql = "SELECT tag, sum(v) FROM hc GROUP BY tag"
    p50, warm, nrows, _ = timed_sql(qe, sql,
                                    repeats=1 if rows > 50_000_000
                                    else max(1, REPEATS - 1),
                                    expect_rows=combos_done)
    rps = rows / (p50 / 1000.0)
    log(f"high-cardinality: {p50:.1f} ms ({nrows} groups, "
        f"{rps / 1e6:.1f}M rows/s)")
    results["high_cardinality"] = {
        "p50_ms": round(p50, 2), "tier": qe.executor.last_tier,
        "combos": combos_done, "target_combos": HC_COMBOS, "rows": rows,
        "target_rows": target_rows, "at_spec": rows >= target_rows,
        "scan_rows_per_s": round(rps), "baseline_ms": None,
        "vs_baseline": None}
    if budget_left_s() > 150:
        results["high_cardinality"]["sparse_envelope"] = \
            _bench_sparse_envelope(engine, qe)
    else:
        log("hc sparse envelope skipped: budget")


def _bench_sparse_envelope(engine, qe):
    """ISSUE 20 acceptance leg: a 256k-group group-by served by the
    sort-compact plane on the fused and incremental tiers (no dense
    fallback — the served paths are asserted, not assumed), its warm
    repeat against the pre-sparse fallback (whole-scan recompute with
    the partial cache refusing >64k groups), a label-selector lastpoint,
    and the sparse dispatch/compaction metrics for the capture file."""
    import jax

    from greptimedb_tpu.datatypes import DictVector, RecordBatch
    from greptimedb_tpu.utils.metrics import (
        SPARSE_COMPACTION_RATIO,
        SPARSE_DISPATCHES,
    )

    groups = int(os.environ.get("BENCH_HC_SPARSE_GROUPS", str(1 << 18)))
    points = int(os.environ.get("BENCH_HC_SPARSE_POINTS", "4"))
    qe.execute_one(
        "CREATE TABLE hc_sparse (tag STRING, v DOUBLE, ts TIMESTAMP(3) "
        "NOT NULL, TIME INDEX (ts), PRIMARY KEY (tag)) "
        "WITH (append_mode = 'true')")
    info = qe.catalog.table("public", "hc_sparse")
    rid = info.region_ids[0]
    rng = np.random.default_rng(17)
    names = np.asarray([f"t{i:06d}" for i in range(groups)], dtype=object)
    # ts tracks the row index, so a ts window selects a GROUP subset —
    # what lets the CPU fused leg (interpret mode) run a budget-sized
    # slice that still crosses the 4096-segment envelope
    n_total = groups * points
    t0 = time.perf_counter()
    written = 0
    while written < n_total:
        n = min(1 << 21, n_total - written)
        idx = written + np.arange(n)
        codes = (idx // points).astype(np.int32)
        c0, c1 = int(codes[0]), int(codes[-1]) + 1
        engine.put(rid, RecordBatch(info.schema, {
            "tag": DictVector(codes - c0, names[c0:c1]),
            "ts": (T0_MS + idx).astype(np.int64),
            "v": np.floor(rng.uniform(0, 1000, n))}))
        prev = written
        written += n
        if prev < n_total // 2 <= written:
            engine.flush(rid)  # two files: the incremental fold has parts
    engine.flush(rid)
    ingest_s = time.perf_counter() - t0
    log(f"hc sparse: {n_total} rows / {groups} groups ingested in "
        f"{ingest_s:.1f}s")

    paths = {}

    def leg(name, sql, repeats=REPEATS, **overrides):
        saved = {k: os.environ.get(k) for k in overrides}
        for k, v in overrides.items():
            os.environ[k] = v
        try:
            p50, warm_ms, nrows, _ = timed_sql(qe, sql, repeats=repeats)
            paths[name] = qe.executor.last_path
            return p50, warm_ms, nrows
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # the 256k domain sits inside the default dense budget; the
    # sparse_groups_min knob is exactly the lever that routes it onto
    # the sort-compact plane (as a 1M+ domain would route by itself)
    force = {"GREPTIMEDB_TPU_SPARSE_GROUPS_MIN": "1"}
    sql = "SELECT tag, sum(v), count(v), max(v) FROM hc_sparse GROUP BY tag"
    inc_p50, inc_cold, nrows = leg("incremental", sql, **force)
    assert nrows == groups, (nrows, groups)
    fb_p50, _, _ = leg("fallback", sql,
                       GREPTIMEDB_TPU_PARTIAL_CACHE="off",
                       GREPTIMEDB_TPU_PALLAS="off", **force)
    on_tpu = jax.default_backend() == "tpu"
    fused_rows = n_total if on_tpu else int(
        os.environ.get("BENCH_HC_FUSED_ROWS", "20480"))  # 5120 groups:
    # past the 4096-segment envelope, so interpret mode really tiles
    fused_sql = sql if on_tpu else (
        f"SELECT tag, sum(v) FROM hc_sparse WHERE ts < "
        f"{T0_MS + fused_rows} GROUP BY tag")
    fused_p50, _, fused_groups = leg(
        "fused", fused_sql, repeats=1,
        GREPTIMEDB_TPU_PALLAS="on",
        GREPTIMEDB_TPU_PARTIAL_CACHE="off", **force)
    lp_sql = ("SELECT last_value(v ORDER BY ts) FROM hc_sparse "
              f"WHERE tag = 't{groups // 2:06d}'")
    lp_p50, _, _ = leg("lastpoint", lp_sql)

    for name in ("incremental", "fallback", "fused"):
        if "sparse" not in (paths.get(name) or ""):
            raise RuntimeError(
                f"hc sparse leg {name!r} fell back to {paths.get(name)!r} "
                "— dense fallback is an acceptance failure")
    speedup = fb_p50 / inc_p50 if inc_p50 > 0 else float("inf")
    log(f"hc sparse 256k-group: warm {inc_p50:.1f} ms vs pre-sparse "
        f"fallback {fb_p50:.1f} ms ({speedup:.1f}x); fused "
        f"{fused_p50:.1f} ms over {fused_groups} groups; lastpoint "
        f"{lp_p50:.2f} ms")
    return {
        "groups": groups, "rows": n_total,
        "ingest_rows_per_s": round(n_total / ingest_s),
        "groupby_warm_p50_ms": round(inc_p50, 2),
        "groupby_cold_ms": round(inc_cold, 2),
        "fallback_p50_ms": round(fb_p50, 2),
        "warm_speedup_vs_fallback": round(speedup, 2),
        "meets_2x": speedup >= 2.0,
        "fused_p50_ms": round(fused_p50, 2), "fused_rows": fused_rows,
        "fused_groups": int(fused_groups),
        "lastpoint_p50_ms": round(lp_p50, 2),
        "paths": paths,
        "sparse_dispatch_total": {
            p: SPARSE_DISPATCHES.get(path=p)
            for p in ("classic", "fused", "sharded", "incremental",
                      "vmapped")},
        "compaction_ratio": round(SPARSE_COMPACTION_RATIO.get(), 6)}


def bench_double_groupby_100m(engine, qe, results, ingest_rps):
    """Tracked config #2 (BASELINE.json): double-groupby-all at 100M
    rows / 4k hosts / 10 fields — the HEADLINE QUERY pointed at the
    streaming machinery (round-4 verdict weak #6: `stream_large` ran a
    different query). Ingest is sized against the wall-clock budget;
    if the full 100M cannot fit, it runs at the largest size that does
    and records the cut explicitly (`at_spec`: false)."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    rows_target = int(os.environ.get("BENCH_STREAM_ROWS", "100000000"))
    n_hosts = 4000
    # reserve for the query itself (~120 s warm + runs) and the
    # remaining tracked configs (promql/hc/compaction, ~480 s). The
    # extra 0.4 derate is measured, not cautious: the 17M calibration
    # ingest ran at 590k rows/s but the 100M sustained 190k — flush
    # and L0 debt compound at scale
    affordable = affordable_rows(600, ingest_rps * 0.4)
    rows_planned = min(rows_target, affordable)
    if rows_planned < 10_000_000:
        left = budget_left_s()
        log(f"double_groupby_100m skipped: budget affords only "
            f"{rows_planned} rows ({left:.0f}s left)")
        results["double_groupby_100m"] = {
            "skipped": f"budget ({left:.0f}s left)",
            "target_rows": rows_target, "at_spec": False}
        return
    points = rows_planned // n_hosts
    step_ms = 10_000
    field_defs = ", ".join(f"{f} DOUBLE" for f in FIELDS)
    qe.execute_one(
        f"CREATE TABLE cpu_big (hostname STRING, ts TIMESTAMP(3) NOT "
        f"NULL, {field_defs}, TIME INDEX (ts), PRIMARY KEY (hostname)) "
        "WITH (append_mode = 'true')")
    info = qe.catalog.table("public", "cpu_big")
    rid = info.region_ids[0]
    rng = np.random.default_rng(23)
    names = np.asarray([f"host_{i}" for i in range(n_hosts)], dtype=object)
    slice_points = max(1, (1 << 21) // n_hosts)
    rows = 0
    t_start = time.perf_counter()
    t_logged = t_start
    for i, p0 in enumerate(range(0, points, slice_points)):
        p1 = min(p0 + slice_points, points)
        npts = p1 - p0
        n = npts * n_hosts
        codes = np.tile(np.arange(n_hosts, dtype=np.int32), npts)
        ts = np.repeat(
            T0_MS + np.arange(p0, p1, dtype=np.int64) * step_ms, n_hosts)
        cols = {"hostname": DictVector(codes, names), "ts": ts}
        for f in FIELDS:
            cols[f] = rng.uniform(0.0, 100.0, n)
        engine.put(rid, RecordBatch(info.schema, cols))
        rows += n
        if (i + 1) % 4 == 0:
            engine.flush(rid)  # bound memtable growth during ingest
        now = time.perf_counter()
        if now - t_logged > 60:
            log(f"100m ingest progress: {rows} rows, "
                f"{rows / (now - t_start):,.0f} rows/s")
            t_logged = now
        if budget_left_s() < 480:
            # the plan was affordable at start, but sustained ingest
            # rate on a shared box swings 3x run to run — stop HERE,
            # measure what landed, and leave the remaining configs
            # their reserve (the cut is recorded via rows < target)
            log(f"100m ingest stopped at {rows} rows: budget")
            break
    engine.flush(rid)
    ingest_s = time.perf_counter() - t_start
    log(f"100m ingest: {rows} rows in {ingest_s:.0f}s "
        f"({rows / ingest_s:,.0f} rows/s)")
    points = rows // n_hosts  # bucket math below reflects actual rows
    hours = -(-(points * step_ms) // 3_600_000)  # ceil
    avg_list = ", ".join(f"avg({f})" for f in FIELDS)
    sql = (f"SELECT date_bin(INTERVAL '1 hour', ts) AS hour, hostname, "
           f"{avg_list} FROM cpu_big GROUP BY hour, hostname")
    # every host appears in every hour bucket by construction — a
    # partial scan cannot silently post a fast p50
    p50, warm, nrows, wspans = timed_sql(qe, sql, repeats=1,
                                         expect_rows=n_hosts * hours)
    path = qe.executor.last_path
    rps = rows / (p50 / 1000.0)
    log(f"double-groupby-100m: {p50:.0f} ms over {rows} rows, "
        f"{nrows} groups ({rps / 1e6:.0f}M rows/s, path={path})")
    results["double_groupby_100m"] = {
        "p50_ms": round(p50, 1), "tier": qe.executor.last_tier, "warmup_ms": round(warm, 1),
        "rows": rows, "target_rows": rows_target,
        "at_spec": rows >= rows_target, "hosts": n_hosts,
        "sim_hours": hours, "groups": nrows, "path": path,
        "scan_rows_per_s": round(rps), "warmup_spans_ms": wspans,
        "baseline_ms": None, "vs_baseline": None,
        "note": ("the headline double-groupby-all query at tracked "
                 "config #2 scale; no published reference number at "
                 "100M — reference 2215.44 ms is at TSBS-scale")}


def bench_compaction(engine, qe, results):
    """Config 4 analog: L0→L1 TWCS merge re-encode throughput."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    qe.execute_one(
        "CREATE TABLE comp (host STRING, v DOUBLE, ts TIMESTAMP(3) NOT "
        "NULL, TIME INDEX (ts), PRIMARY KEY (host))")
    info = qe.catalog.table("public", "comp")
    rid = info.region_ids[0]
    rng = np.random.default_rng(17)
    n_hosts = 1000
    names = np.asarray([f"h{i}" for i in range(n_hosts)], dtype=object)
    n_files = 4
    per_file = COMPACT_ROWS // n_files
    for f in range(n_files):
        pts = per_file // n_hosts
        codes = np.tile(np.arange(n_hosts, dtype=np.int32), pts)
        # overlapping time ranges across files force a real merge
        ts = np.repeat(
            T0_MS + f * 500 + np.arange(pts, dtype=np.int64) * 1000, n_hosts)
        batch = RecordBatch(info.schema, {
            "host": DictVector(codes, names), "ts": ts,
            "v": rng.uniform(0, 1, pts * n_hosts)})
        engine.put(rid, batch)
        engine.flush(rid)
    rows = n_files * per_file // n_hosts * n_hosts
    t = time.perf_counter()
    engine.compact(rid)
    dt = time.perf_counter() - t
    rps = rows / dt
    log(f"compaction re-encode: {rows} rows in {dt:.2f}s "
        f"({rps / 1e6:.2f}M rows/s)")
    results["compaction_reencode"] = {
        "seconds": round(dt, 2), "rows": rows,
        "reencode_rows_per_s": round(rps), "baseline_ms": None,
        "vs_baseline": None}


def bench_anchor(engine, qe, results):
    """Same-box anchor for the headline number (round-3 verdict weak #1:
    the published reference ran on different hardware). Re-runs the
    double-groupby-all computation over the SAME SST files with pyarrow's
    C++ hash group-by — a best-effort conventional columnar engine on
    THIS machine — so vs_baseline has a local comparator whose hardware
    noise cancels."""
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    import statistics

    info = qe.catalog.table("public", "cpu")
    paths = []
    for rid in info.region_ids:
        region = engine.region(rid)
        paths += [region.sst_reader.path(m.file_id)
                  for m in region.files.values()]
    if not paths:
        log("anchor skipped: no SST files (nothing flushed?)")
        results["anchor_pyarrow_double_groupby"] = {
            "skipped": "no SST files"}
        return
    cols = ["hostname", "ts"] + FIELDS

    def agg(t):
        # hour bucketing is INSIDE the timed op: the engine's p50 pays
        # date_bin per query too — both sides time the same computation
        hour = pc.floor_temporal(t.column("ts"), unit="hour")
        t = t.drop_columns(["ts"]).append_column("hour", hour)
        return t.group_by(["hour", "hostname"]).aggregate(
            [(f, "mean") for f in FIELDS])

    def read():
        return pa.concat_tables(pq.read_table(p, columns=cols)
                                for p in paths)

    agg(read())  # warm the page cache like the engine's warm-up does
    e2e, agg_only = [], []
    cached = read()
    for _ in range(max(REPEATS, 1)):
        t0 = time.perf_counter()
        out = agg(read())
        e2e.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        agg(cached)
        agg_only.append(time.perf_counter() - t0)
    p50 = statistics.median(e2e) * 1000
    p50_agg = statistics.median(agg_only) * 1000
    log(f"anchor (pyarrow over same SSTs): read+agg {p50:.0f} ms, "
        f"agg-only {p50_agg:.0f} ms ({out.num_rows} groups, "
        f"{cached.num_rows} rows)")
    results["anchor_pyarrow_double_groupby"] = {
        "p50_ms": round(p50, 2),
        "agg_only_p50_ms": round(p50_agg, 2),
        "groups": out.num_rows,
        "rows_read": cached.num_rows,
        "note": ("pyarrow C++ hash aggregate (incl. hour bucketing) over "
                 "the same parquet on this machine — the same-box "
                 "comparator for double_groupby_all (agg-only excludes "
                 "the parquet read, matching the engine's HBM-cached "
                 "p50)")}


def bench_maintenance(engine, qe, results):
    """Maintenance-plane micro-phase (ISSUE 4): async flush submission
    latency (what the writer actually pays), downsample job throughput,
    and the rollup-substituted coarse query against its raw oracle."""
    maint = getattr(engine, "maintenance", None)
    if maint is None:
        results["maintenance"] = {"skipped": "plane disabled"}
        return
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    qe.execute_one(
        "CREATE TABLE mbench (host STRING, v DOUBLE, ts TIMESTAMP(3) "
        "TIME INDEX, PRIMARY KEY(host))")
    info = qe.catalog.table("public", "mbench")
    rid = info.region_ids[0]
    schema = info.schema
    hosts, points = 20, 7200  # 2h @1s x 20 hosts = 144k rows
    host_names = np.asarray([f"m{i}" for i in range(hosts)], dtype=object)
    rng = np.random.default_rng(11)
    n = hosts * points
    batch = RecordBatch(schema, {
        "host": DictVector(np.tile(np.arange(hosts, dtype=np.int32),
                                   points), host_names),
        "ts": np.repeat(np.arange(points, dtype=np.int64) * 1000, hosts),
        "v": np.floor(rng.uniform(0.0, 100.0, n)),  # exact in f64
    })
    engine.put(rid, batch)
    t0 = time.perf_counter()
    r = qe.execute_one("ADMIN flush_table('mbench')")
    submit_ms = (time.perf_counter() - t0) * 1000  # what a writer pays
    flush_jobs = [maint.wait(int(row[0]), timeout=120) for row in r.rows()]
    t0 = time.perf_counter()
    rj = qe.execute_one("ADMIN rollup_table('mbench', '1m')")
    rollup_jobs = [maint.wait(int(row[0]), timeout=300) for row in rj.rows()]
    rollup_ms = (time.perf_counter() - t0) * 1000
    sql = ("SELECT host, date_bin(INTERVAL '5 minutes', ts) AS b, "
           "min(v), max(v), sum(v), count(*) FROM mbench "
           "WHERE ts >= 0 AND ts < 6000000 GROUP BY host, b "
           "ORDER BY host, b")
    os.environ["GTPU_ROLLUP_SUBSTITUTE"] = "0"
    try:
        raw_p50, raw_warm, raw_rows, _ = timed_sql(qe, sql)
    finally:
        os.environ.pop("GTPU_ROLLUP_SUBSTITUTE", None)
    sub_p50, sub_warm, sub_rows, _ = timed_sql(qe, sql)
    substituted = "+rollup" in (getattr(qe.executor, "last_path", "") or "")
    os.environ["GTPU_ROLLUP_SUBSTITUTE"] = "0"
    try:
        oracle_rows = qe.execute_one(sql).rows()
    finally:
        os.environ.pop("GTPU_ROLLUP_SUBSTITUTE", None)
    exact_match = oracle_rows == qe.execute_one(sql).rows()
    from greptimedb_tpu.utils.metrics import WRITE_STALL_SECONDS

    results["maintenance"] = {
        "rows": n,
        "flush_submit_ms": round(submit_ms, 2),
        "flush_job_ms": round(max(
            (j.duration_ms or 0.0) for j in flush_jobs), 1),
        "rollup_job_ms": round(rollup_ms, 1),
        "rollup_rows_out": sum(
            j.detail.get("rows_out", 0) for j in rollup_jobs),
        "coarse_query_raw_p50_ms": round(raw_p50, 2),
        "coarse_query_rollup_p50_ms": round(sub_p50, 2),
        "substituted": substituted,
        "results_match": exact_match,
        "write_stall_seconds": round(WRITE_STALL_SECONDS.total(), 3),
    }
    log(f"maintenance: flush submit {submit_ms:.1f} ms, rollup job "
        f"{rollup_ms:.0f} ms -> {results['maintenance']['rollup_rows_out']}"
        f" plane rows, coarse query {raw_p50:.1f} -> {sub_p50:.1f} ms "
        f"(substituted={substituted})")


def bench_scan_pipeline(engine, qe, results):
    """Scan-pipeline micro-phase (ISSUE 5): the cold double-groupby-
    shaped scan through the parallel decode pool vs the sequential
    path (bit-for-bit checked), the warm per-file-cache scan, and the
    post-flush incremental scan that must decode ONLY the new file."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    rows_target = int(os.environ.get("BENCH_SCANPIPE_ROWS", "4000000"))
    n_files, n_hosts = 4, 1000
    field_defs = ", ".join(f"{f} DOUBLE" for f in FIELDS)
    qe.execute_one(
        f"CREATE TABLE scanp (hostname STRING, ts TIMESTAMP(3) NOT NULL, "
        f"{field_defs}, TIME INDEX (ts), PRIMARY KEY (hostname)) "
        "WITH (append_mode = 'true')")
    info = qe.catalog.table("public", "scanp")
    rid = info.region_ids[0]
    rng = np.random.default_rng(29)
    names = np.asarray([f"host_{i}" for i in range(n_hosts)], dtype=object)
    per_file = rows_target // n_files
    pts = per_file // n_hosts
    for f in range(n_files):
        codes = np.tile(np.arange(n_hosts, dtype=np.int32), pts)
        ts = np.repeat(
            T0_MS + (f * pts + np.arange(pts, dtype=np.int64)) * 1000,
            n_hosts)
        cols = {"hostname": DictVector(codes, names), "ts": ts}
        for fld in FIELDS:
            cols[fld] = rng.uniform(0.0, 100.0, pts * n_hosts)
        engine.put(rid, RecordBatch(info.schema, cols))
        engine.flush(rid)
    region = engine.region(rid)

    def clear_caches(parts=True):
        with region._lock:
            region._scan_cache.clear()
            if parts:
                region._part_cache.clear()
                region._part_cache_bytes = 0

    def cold_scan(threads):
        clear_caches()
        prev = os.environ.get("GREPTIMEDB_TPU_SCAN_DECODE_THREADS")
        os.environ["GREPTIMEDB_TPU_SCAN_DECODE_THREADS"] = str(threads)
        try:
            t0 = time.perf_counter()
            scan = engine.scan(rid)
            ms = (time.perf_counter() - t0) * 1000
        finally:
            if prev is None:
                os.environ.pop("GREPTIMEDB_TPU_SCAN_DECODE_THREADS", None)
            else:
                os.environ["GREPTIMEDB_TPU_SCAN_DECODE_THREADS"] = prev
        return ms, scan

    seq_ms, seq_scan = cold_scan(1)
    par_ms, par_scan = cold_scan(0)
    identical = (
        seq_scan.num_rows == par_scan.num_rows
        and seq_scan.sorted_part_offsets == par_scan.sorted_part_offsets
        and all(np.array_equal(np.asarray(seq_scan.columns[k]),
                               np.asarray(par_scan.columns[k]))
                for k in seq_scan.columns)
        and np.array_equal(seq_scan.seq, par_scan.seq)
        and np.array_equal(seq_scan.op_type, par_scan.op_type))
    # warm: whole-scan cache cleared, per-file parts kept -> 0 decodes
    clear_caches(parts=False)
    t0 = time.perf_counter()
    warm_scan = engine.scan(rid)
    warm_ms = (time.perf_counter() - t0) * 1000
    # incremental: one small flush -> exactly ONE file decoded
    small = 10 * n_hosts
    codes = np.tile(np.arange(n_hosts, dtype=np.int32), 10)
    ts = np.repeat(
        T0_MS + (n_files * pts + np.arange(10, dtype=np.int64)) * 1000,
        n_hosts)
    cols = {"hostname": DictVector(codes, names), "ts": ts}
    for fld in FIELDS:
        cols[fld] = rng.uniform(0.0, 100.0, small)
    engine.put(rid, RecordBatch(info.schema, cols))
    engine.flush(rid)
    t0 = time.perf_counter()
    incr_scan = engine.scan(rid)
    incr_ms = (time.perf_counter() - t0) * 1000
    speedup = seq_ms / par_ms if par_ms > 0 else None
    log(f"scan-pipeline: cold seq {seq_ms:.0f} ms -> parallel "
        f"{par_ms:.0f} ms ({speedup:.2f}x, identical={identical}), "
        f"part-warm {warm_ms:.0f} ms "
        f"({warm_scan.stats['files_decoded']} decodes), post-flush "
        f"{incr_ms:.0f} ms ({incr_scan.stats['files_decoded']} decodes)")
    results["scan_pipeline"] = {
        "rows": int(seq_scan.num_rows),
        "files": n_files,
        "cold_sequential_ms": round(seq_ms, 1),
        "cold_parallel_ms": round(par_ms, 1),
        "parallel_speedup": round(speedup, 2) if speedup else None,
        "bit_for_bit_identical": bool(identical),
        "decode_workers": par_scan.stats.get("decode_workers"),
        "warm_part_cache_ms": round(warm_ms, 1),
        "warm_files_decoded": warm_scan.stats["files_decoded"],
        "post_flush_ms": round(incr_ms, 1),
        "post_flush_files_decoded": incr_scan.stats["files_decoded"],
        "baseline_ms": None, "vs_baseline": None}


def bench_device_tier(engine, qe, results):
    """Device-tier micro-phase (ISSUE 7): the headline double-groupby
    shape pinned to the device tier — cold (empty hot set) vs hot-set-
    warm p50, warmup compile seconds, per-query H2D bytes from the
    transfer-counter deltas, and the post-flush query that must
    re-upload ONLY the new file's blocks."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch
    from greptimedb_tpu.utils.metrics import (
        DEVICE_HOT_SET_BYTES,
        DEVICE_TRANSFER_BYTES,
        PALLAS_DISPATCHES,
        XLA_COMPILE_SECONDS,
    )

    avg_list = ", ".join(f"avg({f})" for f in FIELDS)
    t_end_ms = T0_MS + HOURS * 3600 * 1000
    sql = (
        f"SELECT date_bin(INTERVAL '1 hour', ts) AS hour, hostname, "
        f"{avg_list} FROM cpu WHERE ts >= {T0_MS} AND ts < {t_end_ms} "
        f"GROUP BY hour, hostname ORDER BY hour, hostname"
    )
    ex = qe.executor

    def h2d():
        return DEVICE_TRANSFER_BYTES.get(direction="h2d")

    def compile_s():
        with XLA_COMPILE_SECONDS._lock:
            return sum(XLA_COMPILE_SECONDS._sum.values())

    def fused_dispatches():
        return PALLAS_DISPATCHES.total(kernel="fused_agg")

    prev = os.environ.get("GREPTIMEDB_TPU_HOST_TIER")
    os.environ["GREPTIMEDB_TPU_HOST_TIER"] = "off"  # pin the device tier
    try:
        ex.cache.clear()  # cold: nothing resident in HBM
        c0, b0, f0 = compile_s(), h2d(), fused_dispatches()
        t0 = time.perf_counter()
        qe.execute_one(sql)
        cold_ms = (time.perf_counter() - t0) * 1000
        warmup_compile_s = compile_s() - c0
        cold_h2d = h2d() - b0
        path = ex.last_path
        # hot-set-warm: every block is already HBM-resident, so the
        # steady-state dashboard repeat should pay ~zero H2D
        reps = max(REPEATS, 5)
        times, b1 = [], h2d()
        for _ in range(reps):
            t0 = time.perf_counter()
            qe.execute_one(sql)
            times.append((time.perf_counter() - t0) * 1000)
        warm_ms = float(np.median(times))
        warm_h2d_per_q = (h2d() - b1) / reps
        # post-flush incremental: the file-anchored hot set keeps the
        # old files' blocks, so the re-upload is the new file only
        info = qe.catalog.table("public", "cpu")
        rid = info.region_ids[0]
        small = 200
        names = np.asarray([f"host_{i}" for i in range(small)],
                           dtype=object)
        # INSIDE the queried window: an out-of-range flush would be
        # pruned outright and the "new file only" H2D claim would
        # measure nothing
        cols = {"hostname": DictVector(
                    np.arange(small, dtype=np.int32), names),
                "ts": np.full(small, t_end_ms - 1000, dtype=np.int64)}
        rng = np.random.default_rng(31)
        for fld in FIELDS:
            cols[fld] = rng.uniform(0.0, 100.0, small)
        engine.put(rid, RecordBatch(info.schema, cols))
        engine.flush(rid)
        b2 = h2d()
        t0 = time.perf_counter()
        qe.execute_one(sql)
        incr_ms = (time.perf_counter() - t0) * 1000
        incr_h2d = h2d() - b2
        hot_bytes = DEVICE_HOT_SET_BYTES.get()
        fused_served = fused_dispatches() - f0
    finally:
        if prev is None:
            os.environ.pop("GREPTIMEDB_TPU_HOST_TIER", None)
        else:
            os.environ["GREPTIMEDB_TPU_HOST_TIER"] = prev
    log(f"device-tier: cold {cold_ms:.0f} ms ({cold_h2d / 1e6:.0f} MB "
        f"H2D, compile {warmup_compile_s:.1f}s) -> warm {warm_ms:.1f} ms "
        f"({warm_h2d_per_q / 1e6:.2f} MB/query), post-flush "
        f"{incr_ms:.0f} ms ({incr_h2d / 1e6:.1f} MB), path={path}, "
        f"hot set {hot_bytes / 1e6:.0f} MB")
    results["device_tier"] = {
        "path": path,
        "cold_ms": round(cold_ms, 1),
        "warm_p50_ms": round(warm_ms, 2),
        "warmup_compile_s": round(warmup_compile_s, 2),
        "cold_h2d_bytes": int(cold_h2d),
        "warm_h2d_bytes_per_query": int(warm_h2d_per_q),
        "post_flush_ms": round(incr_ms, 1),
        "post_flush_h2d_bytes": int(incr_h2d),
        "hot_set_bytes": int(hot_bytes),
        "fused_kernel_dispatches": int(fused_served),
        "baseline_ms": None, "vs_baseline": None}


def bench_sql_insert(qe, results, rows_total=None, per_stmt=500):
    """SQL INSERT path (parse -> bind -> region write incl. WAL), the
    slower sibling of the bulk RecordBatch route the headline ingest
    number uses — reported separately so both write paths are tracked."""
    rows_total = rows_total or int(
        os.environ.get("BENCH_SQL_INSERT_ROWS", "50000"))
    rng = np.random.default_rng(11)
    t_ms = T0_MS + 365 * 24 * 3600 * 1000  # far from the scan data
    done = 0
    t_start = time.perf_counter()
    while done < rows_total:
        n = min(per_stmt, rows_total - done)
        vals = ", ".join(
            f"('host_{int(h)}', {t_ms + i}, " +
            ", ".join(f"{v:.3f}" for v in row) + ")"
            for i, (h, row) in enumerate(zip(
                rng.integers(0, HOSTS, n),
                rng.uniform(0.0, 100.0, (n, len(FIELDS)))))
        )
        qe.execute_one(
            f"INSERT INTO cpu (hostname, ts, {', '.join(FIELDS)}) "
            f"VALUES {vals}")
        t_ms += n
        done += n
    dt = time.perf_counter() - t_start
    rps = done / dt
    log(f"sql insert: {done} rows in {dt:.1f}s ({rps:,.0f} rows/s)")
    results["sql_insert"] = {
        "rows": done, "rows_per_s": round(rps),
        "vs_bulk_note": "statement path; headline ingest uses bulk "
                        "RecordBatch puts"}


def bench_ingest_qps(engine, qe, results, writers=None, seconds=None):
    """Config: production-rate protocol ingest (ISSUE 9). N concurrent
    writers — a line-protocol + SQL INSERT mix, the two statement-path
    front doors real users hit — hammer a dedicated table while
    background readers keep querying the warm cpu table. Reports
    aggregate rows/s (anchor: the 7.4k rows/s pre-pipeline statement
    path), p99 ack latency per front door, the write-stall delta, and
    read-p50 degradation vs idle — write/read isolation under the
    maintenance plane's backpressure."""
    import threading

    from greptimedb_tpu.servers.influx import write_lines
    from greptimedb_tpu.utils.metrics import (
        INGEST_GROUP_COMMIT_EVENTS,
        WRITE_STALL_SECONDS,
    )

    # sizing: the line-protocol parse is GIL-bound, so writer count
    # tracks cores (oversubscription convoys the GIL on small boxes);
    # ONE app-style SQL INSERT stream rides along at a steady pace —
    # its per-statement parse is pure Python and an unpaced tight loop
    # would measure GIL starvation, not the serving stack
    default_w = max(5, min(12, 2 * (os.cpu_count() or 4) + 1))
    writers = writers or int(os.environ.get("BENCH_INGEST_WRITERS",
                                            str(default_w)))
    duration = seconds or float(os.environ.get("BENCH_INGEST_SECONDS", "12"))
    sql_writers = 1
    sql_pace_s = 0.1
    lp_writers = max(1, writers - sql_writers)
    # 5000 lines/request = Telegraf's default max batch; the commit
    # pipeline amortizes one fsync over the whole group, so request
    # size sets the floor on rows-per-fsync when the disk is slow
    lp_rows, sql_rows = 5000, 500
    rng = np.random.default_rng(23)
    ingest_fields = [f"f{i}" for i in range(5)]

    def lp_body(w, i):
        t0 = 1_000_000 + (w * 1000 + i) * lp_rows
        vals = rng.uniform(0.0, 100.0, (lp_rows, len(ingest_fields)))
        hosts = rng.integers(0, 200, lp_rows)
        field_list = ",".join(ingest_fields)
        return "\n".join(
            f"ingestq,hostname=host_{int(h)} "
            + ",".join(f"{f}={v:.3f}" for f, v in zip(ingest_fields, row))
            + f" {t0 + j}"
            for j, (h, row) in enumerate(zip(hosts, vals))), field_list

    def sql_stmt(w, i):
        t0 = 500_000_000 + (w * 1000 + i) * sql_rows
        vals = ", ".join(
            f"('host_{int(h)}', {t0 + j}, "
            + ", ".join(f"{v:.3f}" for v in row) + ")"
            for j, (h, row) in enumerate(zip(
                rng.integers(0, 200, sql_rows),
                rng.uniform(0.0, 100.0, (sql_rows, len(ingest_fields))))))
        return (f"INSERT INTO ingestq (hostname, ts, "
                f"{', '.join(ingest_fields)}) VALUES {vals}")

    # auto-create the table + pre-generate the request pool OUTSIDE the
    # clock (client-side cost, not serving cost); writers cycle their
    # pool — duplicate (host, ts) keys are fine for a rate measurement
    write_lines(qe, "public", lp_body(99, 0)[0], precision="ms")
    lp_pool = [[lp_body(w, i)[0] for i in range(4)]
               for w in range(lp_writers)]
    sql_pool = [[sql_stmt(w, i) for i in range(4)]
                for w in range(sql_writers)]

    read_sql = (
        f"SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
        f"max(usage_user) FROM cpu WHERE hostname = 'host_1' "
        f"AND ts >= {T0_MS} AND ts < {T0_MS + 3600 * 1000} GROUP BY minute")
    qe.execute_one(read_sql)  # warm
    idle = []
    for _ in range(20):
        t0 = time.perf_counter()
        qe.execute_one(read_sql)
        idle.append(time.perf_counter() - t0)
    idle_p50 = float(np.median(idle)) * 1000

    stall0 = WRITE_STALL_SECONDS.total()
    gc0 = {e: INGEST_GROUP_COMMIT_EVENTS.total(event=e)
           for e in ("lead", "follow", "overflow")}
    sync0 = getattr(engine.wal, "sync_count", 0)
    stop = threading.Event()
    rows_done = [0] * (lp_writers + sql_writers)
    lp_lat: list = [[] for _ in range(lp_writers)]
    sql_lat: list = [[] for _ in range(sql_writers)]
    read_lat: list = [[] for _ in range(2)]
    errors = [0] * (lp_writers + sql_writers)

    def lp_writer(w):
        i = 0
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                write_lines(qe, "public", lp_pool[w][i % len(lp_pool[w])],
                            precision="ms")
            except Exception:  # noqa: BLE001 — typed Overloaded included
                errors[w] += 1
                continue
            lp_lat[w].append(time.perf_counter() - t0)
            rows_done[w] += lp_rows
            i += 1

    def sql_writer(w):
        i = 0
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                qe.execute_one(sql_pool[w][i % len(sql_pool[w])])
            except Exception:  # noqa: BLE001 — typed Overloaded included
                errors[lp_writers + w] += 1
                continue
            sql_lat[w].append(time.perf_counter() - t0)
            rows_done[lp_writers + w] += sql_rows
            i += 1
            time.sleep(sql_pace_s)

    def reader(r):
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                qe.execute_one(read_sql)
            except Exception:  # noqa: BLE001 — keep reading under load
                continue
            read_lat[r].append(time.perf_counter() - t0)

    threads = ([threading.Thread(target=lp_writer, args=(w,))
                for w in range(lp_writers)]
               + [threading.Thread(target=sql_writer, args=(w,))
                  for w in range(sql_writers)]
               + [threading.Thread(target=reader, args=(r,))
                  for r in range(2)])
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(60)
    wall = time.perf_counter() - t_start

    total_rows = sum(rows_done)
    rate = total_rows / wall
    lp_all = np.asarray([x for l in lp_lat for x in l])
    sql_all = np.asarray([x for l in sql_lat for x in l])
    reads = np.asarray([x for l in read_lat for x in l])
    stall_delta = WRITE_STALL_SECONDS.total() - stall0
    gc = {e: INGEST_GROUP_COMMIT_EVENTS.total(event=e) - gc0[e]
          for e in gc0}
    syncs = getattr(engine.wal, "sync_count", 0) - sync0
    commits = max(1.0, gc["lead"])
    loaded_p50 = (float(np.median(reads)) * 1000 if reads.size
                  else None)
    lp_p99 = (float(np.percentile(lp_all, 99)) * 1000
              if lp_all.size else None)
    log(f"ingest_qps: {rate:,.0f} rows/s over {wall:.1f}s "
        f"({lp_writers} lp + {sql_writers} sql writers; "
        f"lp p99 {-1.0 if lp_p99 is None else lp_p99:.1f} ms, "
        f"{gc['lead']:.0f} commits / {syncs} fsyncs, "
        f"{gc['follow']:.0f} followers, stall {stall_delta:.2f}s, "
        f"read p50 {idle_p50:.1f} -> {loaded_p50 or -1:.1f} ms, "
        f"{sum(errors)} errors)")
    results["ingest_qps"] = {
        "rows_per_s": round(rate),
        "writers": {"line_protocol": lp_writers, "sql_insert": sql_writers},
        "rows": total_rows,
        "errors": sum(errors),
        "lp_p99_ack_ms": None if lp_p99 is None else round(lp_p99, 2),
        "sql_p99_ack_ms": round(float(np.percentile(sql_all, 99)) * 1000, 2)
        if sql_all.size else None,
        "group_commits": int(gc["lead"]),
        "followers": int(gc["follow"]),
        "overflows": int(gc["overflow"]),
        "wal_fsyncs": int(syncs),
        "rows_per_commit": round(total_rows / commits, 1),
        "write_stall_seconds_delta": round(stall_delta, 3),
        "read_p50_idle_ms": round(idle_p50, 2),
        "read_p50_loaded_ms": (None if loaded_p50 is None
                               else round(loaded_p50, 2)),
        "read_degradation": (None if loaded_p50 is None or idle_p50 == 0
                             else round(loaded_p50 / idle_p50, 2)),
        # the pre-pipeline statement path managed 7.4k rows/s (r05);
        # acceptance wants >= 10x through the protocol front doors
        "anchor_rows_s": 7400,
        "vs_anchor": round(rate / 7400, 2),
        "differential": "tests/test_ingest.py::TestGroupCommitDifferential "
                        "proves bit-for-bit parity vs [ingest] "
                        "group_commit=false",
    }


_BATCH_EVENTS = ("join", "coalesced", "stacked", "vmapped",
                 "serial_fallback")
_STAGES = ("parse", "plan", "execute", "fast_bind", "fast_execute")


def _serving_snapshot():
    """Counter/histogram state before a qps phase: per-shape batching
    events, batch/vmap width histograms, and the execute-vs-encode
    wall-time split (engine seconds vs encode-pool seconds)."""
    from greptimedb_tpu.utils.metrics import (
        ADMISSION_WAIT_SECONDS,
        ENCODE_POOL_EVENTS,
        ENCODE_SECONDS,
        FAST_LANE_EVENTS,
        PARTIAL_AGG_CACHE_EVENTS,
        PARTIAL_AGG_DELTA_ROWS,
        QUERY_BATCH_EVENTS,
        QUERY_BATCH_SIZE,
        QUERY_DURATION,
        STAGE_SECONDS,
        VMAP_BATCH_WIDTH,
    )

    return {
        "fl": {e: FAST_LANE_EVENTS.get(event=e)
               for e in ("hit", "miss", "coalesced", "invalidate")},
        "fl_fallback": FAST_LANE_EVENTS.total(event="fallback"),
        "stages": {s: STAGE_SECONDS.sum(stage=s)
                   for s in _STAGES},
        "stage_n": {s: STAGE_SECONDS.count(stage=s)
                    for s in _STAGES},
        "admission_wait_s": ADMISSION_WAIT_SECONDS.sum(),
        "pc_hit": PARTIAL_AGG_CACHE_EVENTS.get(event="hit"),
        "pc_miss": PARTIAL_AGG_CACHE_EVENTS.get(event="miss"),
        "pc_fallback": PARTIAL_AGG_CACHE_EVENTS.get(event="fallback"),
        "pc_delta_rows": PARTIAL_AGG_DELTA_ROWS.get(kind="delta"),
        "pc_cached_rows": PARTIAL_AGG_DELTA_ROWS.get(kind="cached"),
        "events": {e: QUERY_BATCH_EVENTS.get(event=e)
                   for e in _BATCH_EVENTS},
        "batch_sum": QUERY_BATCH_SIZE.sum(),
        "batch_n": QUERY_BATCH_SIZE.count(),
        "vmap_sum": VMAP_BATCH_WIDTH.sum(),
        "vmap_n": VMAP_BATCH_WIDTH.count(),
        "exec_s": QUERY_DURATION.sum(kind="sql"),
        "exec_n": QUERY_DURATION.count(kind="sql"),
        # thread-mode encodes observe protocol="http"; process-mode
        # round trips are timed parent-side as protocol="process"
        "encode_s": ENCODE_SECONDS.sum(protocol="http")
        + ENCODE_SECONDS.sum(protocol="process"),
        "encode_n": ENCODE_SECONDS.count(protocol="http")
        + ENCODE_SECONDS.count(protocol="process"),
        "offloaded": ENCODE_POOL_EVENTS.get(event="offload")
        + ENCODE_POOL_EVENTS.get(event="offload_process"),
        "inline": ENCODE_POOL_EVENTS.get(event="inline"),
        "small_inline": ENCODE_POOL_EVENTS.get(event="small_inline"),
    }


def _serving_report(before):
    """The per-shape batching breakdown + execute/encode split since
    `before` — makes the vmap and GIL-escape wins separately
    attributable in BENCH_* output."""
    now = _serving_snapshot()
    ev = {e: int(now["events"][e] - before["events"][e])
          for e in _BATCH_EVENTS}
    groups = now["batch_n"] - before["batch_n"]
    widths = now["batch_sum"] - before["batch_sum"]
    vgroups = now["vmap_n"] - before["vmap_n"]
    vwidths = now["vmap_sum"] - before["vmap_sum"]
    exec_s = now["exec_s"] - before["exec_s"]
    exec_n = now["exec_n"] - before["exec_n"]
    enc_s = now["encode_s"] - before["encode_s"]
    enc_n = now["encode_n"] - before["encode_n"]
    pc_hit = now["pc_hit"] - before["pc_hit"]
    pc_miss = now["pc_miss"] - before["pc_miss"]
    pc_delta = now["pc_delta_rows"] - before["pc_delta_rows"]
    pc_cached = now["pc_cached_rows"] - before["pc_cached_rows"]
    fl = {e: now["fl"][e] - before["fl"][e] for e in now["fl"]}
    fl_fb = now["fl_fallback"] - before["fl_fallback"]
    fl_requests = fl["hit"] + fl["miss"] + fl_fb
    stages = {s: now["stages"][s] - before["stages"][s] for s in _STAGES}
    stage_n = {s: now["stage_n"][s] - before["stage_n"][s]
               for s in _STAGES}
    adm_wait = now["admission_wait_s"] - before["admission_wait_s"]
    enc_stage = now["encode_s"] - before["encode_s"]
    stage_total = sum(stages.values()) + adm_wait + enc_stage
    return {
        # the per-stage wall breakdown (ISSUE 14): where serving time
        # actually went — parse share ~= 0 proves warm fast-lane
        # requests never touch the parser
        "stage_breakdown": {
            **{f"{s}_s": round(stages[s], 3) for s in _STAGES},
            "admission_wait_s": round(adm_wait, 3),
            "encode_s": round(enc_stage, 3),
            "counts": {s: int(stage_n[s]) for s in _STAGES
                       if stage_n[s]},
            "shares": ({s: round(v / stage_total, 4)
                        for s, v in {**stages,
                                     "admission_wait": adm_wait,
                                     "encode": enc_stage}.items()}
                       if stage_total > 0 else None),
            "parse_share": (round(stages["parse"] / stage_total, 4)
                            if stage_total > 0 else None),
        },
        "fast_lane": {
            "hits": int(fl["hit"]),
            "misses": int(fl["miss"]),
            "fallbacks": int(fl_fb),
            "coalesced": int(fl["coalesced"]),
            "invalidates": int(fl["invalidate"]),
            "hit_rate": (round(fl["hit"] / fl_requests, 4)
                         if fl_requests else None),
        },
        "partial_cache": {
            "hits": int(pc_hit),
            "misses": int(pc_miss),
            "hit_rate": (round(pc_hit / (pc_hit + pc_miss), 4)
                         if pc_hit + pc_miss else None),
            "fallbacks": int(now["pc_fallback"] - before["pc_fallback"]),
            "delta_rows_folded": int(pc_delta),
            "cached_rows_served": int(pc_cached),
            "delta_row_share": (round(pc_delta / (pc_delta + pc_cached), 4)
                                if pc_delta + pc_cached else None),
        },
        "batching": {
            **ev,
            "mean_batch_width": (round(widths / groups, 2)
                                 if groups else None),
            "mean_vmap_width": (round(vwidths / vgroups, 2)
                                if vgroups else None),
        },
        "encode_split": {
            "execute_s": round(exec_s, 3),
            "encode_s": round(enc_s, 3),
            "encode_share": (round(enc_s / (exec_s + enc_s), 4)
                             if exec_s + enc_s > 0 else None),
            "mean_execute_ms": (round(exec_s / exec_n * 1000, 3)
                                if exec_n else None),
            "mean_encode_ms": (round(enc_s / enc_n * 1000, 3)
                               if enc_n else None),
            "encode_offloaded": int(now["offloaded"]
                                    - before["offloaded"]),
            "encode_inline": int(now["inline"] - before["inline"]),
            # results under [concurrency] encode_min_rows: encoded on
            # the request thread by design (handoff > serialization)
            "encode_small_inline": int(now["small_inline"]
                                       - before["small_inline"]),
        },
    }


def bench_qps(qe, results, clients=None, requests_total=None):
    """Config: concurrent query throughput over real HTTP (reference
    tracks 1165.73 qps @50 clients on single-groupby-1-1-1,
    docs/benchmarks/tsbs/v0.8.0.md:53-58). N client threads fire
    single-groupby-1-1-1 POSTs at the in-process HTTP server; the warm
    HBM cache makes each query ~ms, so this measures the serving stack
    (HTTP parse, auth, engine dispatch, JSON encode) under the GIL."""
    import http.client
    import threading
    import urllib.parse
    import urllib.request

    from greptimedb_tpu.servers.http import HttpServer

    clients = clients or int(os.environ.get("BENCH_QPS_CLIENTS", "50"))
    requests_total = requests_total or int(
        os.environ.get("BENCH_QPS_REQUESTS", "2000"))
    sql = (
        f"SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
        f"max(usage_user) FROM cpu WHERE hostname = 'host_1' "
        f"AND ts >= {T0_MS} AND ts < {T0_MS + 3600 * 1000} GROUP BY minute"
    )
    from greptimedb_tpu.utils.metrics import (
        PLAN_CACHE_EVENTS,
        QUERY_BATCH_EVENTS,
    )

    srv = HttpServer(qe, host="127.0.0.1", port=0)
    try:
        port = srv.start()
        url = f"http://127.0.0.1:{port}/v1/sql"
        body = urllib.parse.urlencode({"sql": sql}).encode()
        # warm once (compile + cache) before the clock starts
        urllib.request.urlopen(
            urllib.request.Request(url, data=body), timeout=60)

        # cold-vs-warm partial-cache p50 split: the same request against
        # an emptied partial-aggregate cache (full per-part fold) vs
        # warm repeats that serve cached [G, F] partials and fold only
        # the memtable delta
        from greptimedb_tpu.query import partial_cache as _pc

        def _one_req():
            t0 = time.perf_counter()
            urllib.request.urlopen(
                urllib.request.Request(url, data=body), timeout=60)
            return (time.perf_counter() - t0) * 1000

        _pc.global_cache().clear()
        cold_cache_ms = _one_req()
        warm_cache_ms = float(np.median([_one_req() for _ in range(9)]))
        cache0 = (PLAN_CACHE_EVENTS.get(event="hit"),
                  PLAN_CACHE_EVENTS.get(event="miss"))
        batch0 = (QUERY_BATCH_EVENTS.get(event="coalesced"),
                  QUERY_BATCH_EVENTS.get(event="stacked"),
                  QUERY_BATCH_EVENTS.get(event="vmapped"))
        serving0 = _serving_snapshot()

        per_client = max(1, requests_total // clients)
        latencies = [[] for _ in range(clients)]
        errors = [0] * clients  # per-thread: += across threads drops counts

        headers = {"Content-Type": "application/x-www-form-urlencoded"}

        def client(i):
            # one keep-alive connection per client, like a real TSBS
            # load generator — reconnect-per-request would measure TCP
            # setup, not the serving stack
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
            try:
                for _ in range(per_client):
                    t0 = time.perf_counter()
                    try:
                        conn.request("POST", "/v1/sql", body=body,
                                     headers=headers)
                        resp = conn.getresponse()
                        resp.read()
                        if resp.status != 200:
                            errors[i] += 1
                            continue
                    except Exception:
                        errors[i] += 1
                        conn.close()  # reconnect on next iteration
                        continue
                    latencies[i].append(time.perf_counter() - t0)
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start

        # observability overhead A/B (ISSUE 15): the same request on
        # one keep-alive connection with the tracing plane (spans +
        # ledger + exporter hook) on vs GTPU_TRACING=off — the <3%
        # budget gate. Sequential single-connection runs are far less
        # noisy than re-running the full 50-client storm.
        from greptimedb_tpu.utils import tracing as _tr

        def _seq_qps(n):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
            try:
                for _ in range(10):  # settle the lane/caches per mode
                    conn.request("POST", "/v1/sql", body=body,
                                 headers=headers)
                    conn.getresponse().read()
                t0 = time.perf_counter()
                for _ in range(n):
                    conn.request("POST", "/v1/sql", body=body,
                                 headers=headers)
                    conn.getresponse().read()
                return n / (time.perf_counter() - t0)
            finally:
                conn.close()

        ab_n = max(100, min(400, requests_total // 5))
        # spans per query: ride the W3C ingress — a request with a
        # known traceparent lands its whole tree under that id
        ab_tid = "feedbeefcafe4242"
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/v1/sql", body=body, headers={
            **headers,
            "traceparent": f"00-{ab_tid.rjust(32, '0')}-00f067aa0ba902b7-01"})
        conn.getresponse().read()
        conn.close()
        spans_per_query = len(_tr.spans_for(ab_tid))
        from greptimedb_tpu.utils.otlp_trace import OTLP_TRACE_SPANS
        otlp0 = (OTLP_TRACE_SPANS.total(event="exported"),
                 OTLP_TRACE_SPANS.total(event="dropped"))
        # alternate on/off rounds and take per-mode medians: a single
        # sequential pair confounds the mode with drift on a busy box
        prev_tracing = os.environ.get("GTPU_TRACING")
        on_rounds, off_rounds = [], []
        try:
            for _ in range(3):
                if prev_tracing is None:
                    os.environ.pop("GTPU_TRACING", None)
                else:
                    os.environ["GTPU_TRACING"] = prev_tracing
                on_rounds.append(_seq_qps(ab_n))
                os.environ["GTPU_TRACING"] = "off"
                off_rounds.append(_seq_qps(ab_n))
        finally:
            if prev_tracing is None:
                os.environ.pop("GTPU_TRACING", None)
            else:
                os.environ["GTPU_TRACING"] = prev_tracing
        qps_on = float(np.median(on_rounds))
        qps_off = float(np.median(off_rounds))
        overhead_pct = (1.0 - qps_on / qps_off) * 100 if qps_off else 0.0
        tracing_ab = {
            "qps_tracing_on": round(qps_on, 1),
            "qps_tracing_off": round(qps_off, 1),
            "overhead_pct": round(overhead_pct, 2),
            "budget_pct": 3.0,
            "spans_per_query": spans_per_query,
            "otlp_exported": int(OTLP_TRACE_SPANS.total(event="exported")
                                 - otlp0[0]),
            "otlp_dropped": int(OTLP_TRACE_SPANS.total(event="dropped")
                                - otlp0[1]),
        }

        # continuous-profiler overhead A/B (ISSUE 17): the same
        # sequential lane with the flame sampler on vs fully stopped —
        # the <=2% budget gate for leaving it always-on in production.
        # The top-10 self-time digest rides into BENCH detail so the
        # re-capture lands with attribution built in.
        from greptimedb_tpu.utils import flame as _fl

        prof_prev = _fl.running()
        prof_on_rounds, prof_off_rounds, flame_digest = [], [], None
        try:
            for _ in range(3):
                _fl.configure(enabled=True)
                prof_on_rounds.append(_seq_qps(ab_n))
                # read the digest while the windows are still live
                flame_digest = _fl.summary(top=10)
                _fl.shutdown()
                prof_off_rounds.append(_seq_qps(ab_n))
        finally:
            if prof_prev:
                _fl.configure(enabled=True)
        prof_on = float(np.median(prof_on_rounds))
        prof_off = float(np.median(prof_off_rounds))
        profiling_ab = {
            "qps_profiling_on": round(prof_on, 1),
            "qps_profiling_off": round(prof_off, 1),
            "overhead_pct": round(
                (1.0 - prof_on / prof_off) * 100 if prof_off else 0.0, 2),
            "budget_pct": 2.0,
            "flame_samples": (flame_digest or {}).get("samples", 0),
            "flame_attributed": (flame_digest or {}).get("attributed", 0),
            "flame_top10": [
                f"{t['frame']} x{t['self']}"
                for t in (flame_digest or {}).get("top", [])],
        }
    except Exception as e:  # one config may not sink the whole bench
        log(f"qps bench failed: {e!r}")
        results["qps_single_groupby"] = {"error": repr(e)[:200]}
        return
    finally:
        srv.stop()
    lats = np.asarray([x for l in latencies for x in l])
    done = len(lats)
    n_err = sum(errors)
    if done == 0:
        log(f"qps: all {n_err} requests failed")
        results["qps_single_groupby"] = {
            "qps": 0.0, "clients": clients, "requests": 0, "errors": n_err}
        return
    qps = done / wall
    d_hit = PLAN_CACHE_EVENTS.get(event="hit") - cache0[0]
    d_miss = PLAN_CACHE_EVENTS.get(event="miss") - cache0[1]
    hit_rate = d_hit / (d_hit + d_miss) if (d_hit + d_miss) else None
    batched = (QUERY_BATCH_EVENTS.get(event="coalesced") - batch0[0]
               + QUERY_BATCH_EVENTS.get(event="stacked") - batch0[1]
               + QUERY_BATCH_EVENTS.get(event="vmapped") - batch0[2])
    serving = _serving_report(serving0)
    log(f"qps: {qps:.0f} qps @{clients} clients "
        f"(mean {lats.mean() * 1000:.1f} ms, p99 "
        f"{np.percentile(lats, 99) * 1000:.1f} ms, {n_err} errors, "
        f"plan-cache hit rate "
        f"{-1.0 if hit_rate is None else hit_rate:.3f}, "
        f"{batched:.0f} batched, batching {serving['batching']}, "
        f"fast lane {serving['fast_lane']}, "
        f"stages {serving['stage_breakdown']['shares']}, "
        f"encode {serving['encode_split']})")
    log(f"qps tracing A/B: on {tracing_ab['qps_tracing_on']} vs off "
        f"{tracing_ab['qps_tracing_off']} qps -> "
        f"{tracing_ab['overhead_pct']:+.2f}% overhead (budget 3%), "
        f"{tracing_ab['spans_per_query']} spans/query, "
        f"otlp exported {tracing_ab['otlp_exported']} / dropped "
        f"{tracing_ab['otlp_dropped']}")
    log(f"qps profiling A/B: on {profiling_ab['qps_profiling_on']} vs "
        f"off {profiling_ab['qps_profiling_off']} qps -> "
        f"{profiling_ab['overhead_pct']:+.2f}% overhead (budget 2%), "
        f"{profiling_ab['flame_samples']} samples "
        f"({profiling_ab['flame_attributed']} attributed)")
    results["qps_single_groupby"] = {
        "tracing_overhead": tracing_ab,
        "profiling_overhead": profiling_ab,
        "qps": round(qps, 1), "clients": clients, "requests": done,
        "errors": n_err,
        "mean_ms": round(float(lats.mean() * 1000), 2),
        "p99_ms": round(float(np.percentile(lats, 99) * 1000), 2),
        "p999_ms": round(float(np.percentile(lats, 99.9) * 1000), 2),
        **serving,
        # the ISSUE-6 acceptance: the repeated-dashboard workload must
        # serve >90% of plans from the shape-keyed cache
        "plan_cache_hit_rate": (None if hit_rate is None
                                else round(hit_rate, 4)),
        "batched_queries": int(batched),
        # single-request split: cold = partial cache emptied (every
        # part re-folds), warm = cached partials + memtable delta only
        "cold_cache_ms": round(cold_cache_ms, 2),
        "warm_cache_p50_ms": round(warm_cache_ms, 2),
        "baseline_qps": 1165.73,
        "vs_baseline": round(qps / 1165.73, 3),
        # per-core normalization: the reference baseline ran on 8
        # cores; dividing both sides by their core counts makes the
        # figure portable across boxes (qps_multiproc scores the same
        # way per frontend process)
        "qps_per_core": round(qps / (os.cpu_count() or 1), 1),
        "baseline_qps_per_core": round(1165.73 / 8, 1),
        "vs_baseline_per_core": round(
            (qps / (os.cpu_count() or 1)) / (1165.73 / 8), 3),
        "note": ("clients run in-process; baseline is the reference on "
                 "8 cores, this box has "
                 f"{os.cpu_count()} — compare per-core")}


def bench_qps_mixed(qe, results, clients_per_tenant=None,
                    requests_total=None):
    """Config: multi-tenant mixed workload over real HTTP (ISSUE-6
    satellite) — the concurrency plane measured, not asserted. Three
    tenants with distinct shapes run concurrently through the full
    frontend path (admission -> plan cache -> batcher):

      dash      repeated single-groupby dashboards, rotating host +
                window literals (the plan-cache + stacking workload)
      ops       point lastpoint per host (cheap, shape-cached)
      analytics high-cardinality groupby over every host (the heavy
                neighbor fairness protects the others from)

    Per-tenant p50/p99/p999 says whether a heavy tenant starves a light
    one; the plan-cache hit rate says whether shapes actually shared."""
    import http.client
    import threading
    import urllib.parse
    import urllib.request

    from greptimedb_tpu.servers.http import HttpServer
    from greptimedb_tpu.utils.metrics import (
        ADMISSION_EVENTS,
        PLAN_CACHE_EVENTS,
        QUERY_BATCH_EVENTS,
    )

    clients_per_tenant = clients_per_tenant or int(
        os.environ.get("BENCH_QPS_MIXED_CLIENTS_PER_TENANT", "10"))
    requests_total = requests_total or int(
        os.environ.get("BENCH_QPS_MIXED_REQUESTS", "3000"))
    hour_ms = 3600 * 1000

    def dash_sql(i):
        lo = T0_MS + (i % max(1, HOURS - 1)) * hour_ms
        return (f"SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
                f"max(usage_user) FROM cpu "
                f"WHERE hostname = 'host_{i % min(HOSTS, 64)}' "
                f"AND ts >= {lo} AND ts < {lo + hour_ms} GROUP BY minute")

    def ops_sql(i):
        return (f"SELECT last_value(usage_user ORDER BY ts) FROM cpu "
                f"WHERE hostname = 'host_{i % min(HOSTS, 256)}'")

    def analytics_sql(i):
        lo = T0_MS + (i % max(1, HOURS - 1)) * hour_ms
        return (f"SELECT hostname, max(usage_user), avg(usage_system) "
                f"FROM cpu WHERE ts >= {lo} AND ts < {lo + hour_ms} "
                f"GROUP BY hostname")

    tenants = [("dash", dash_sql), ("ops", ops_sql),
               ("analytics", analytics_sql)]
    srv = HttpServer(qe, host="127.0.0.1", port=0)
    try:
        port = srv.start()
        url = f"http://127.0.0.1:{port}/v1/sql"
        for _, gen in tenants:  # one warm compile per shape
            urllib.request.urlopen(urllib.request.Request(
                url, data=urllib.parse.urlencode(
                    {"sql": gen(0)}).encode()), timeout=120)
        cache0 = (PLAN_CACHE_EVENTS.get(event="hit"),
                  PLAN_CACHE_EVENTS.get(event="miss"))
        batch0 = (QUERY_BATCH_EVENTS.get(event="coalesced"),
                  QUERY_BATCH_EVENTS.get(event="stacked"),
                  QUERY_BATCH_EVENTS.get(event="vmapped"))
        rej0 = ADMISSION_EVENTS.total(event="reject_full") \
            + ADMISSION_EVENTS.total(event="reject_timeout")
        serving0 = _serving_snapshot()

        per_client = max(1, requests_total
                         // (3 * clients_per_tenant))
        lat = {name: [[] for _ in range(clients_per_tenant)]
               for name, _ in tenants}
        errors = {name: [0] * clients_per_tenant for name, _ in tenants}

        def client(tenant, gen, i):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=120)
            headers = {"Content-Type":
                       "application/x-www-form-urlencoded",
                       "X-Greptime-Tenant": tenant}
            try:
                for k in range(per_client):
                    body = urllib.parse.urlencode(
                        {"sql": gen(i * per_client + k)}).encode()
                    t0 = time.perf_counter()
                    try:
                        conn.request("POST", "/v1/sql", body=body,
                                     headers=headers)
                        resp = conn.getresponse()
                        resp.read()
                        if resp.status != 200:
                            errors[tenant][i] += 1
                            continue
                    except Exception:
                        errors[tenant][i] += 1
                        conn.close()
                        continue
                    lat[tenant][i].append(time.perf_counter() - t0)
            finally:
                conn.close()

        threads = [
            threading.Thread(target=client, args=(name, gen, i))
            for name, gen in tenants for i in range(clients_per_tenant)
        ]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start
    except Exception as e:
        log(f"qps_mixed bench failed: {e!r}")
        results["qps_mixed_tenants"] = {"error": repr(e)[:200]}
        return
    finally:
        srv.stop()

    d_hit = PLAN_CACHE_EVENTS.get(event="hit") - cache0[0]
    d_miss = PLAN_CACHE_EVENTS.get(event="miss") - cache0[1]
    hit_rate = d_hit / (d_hit + d_miss) if (d_hit + d_miss) else None
    batched = (QUERY_BATCH_EVENTS.get(event="coalesced") - batch0[0]
               + QUERY_BATCH_EVENTS.get(event="stacked") - batch0[1]
               + QUERY_BATCH_EVENTS.get(event="vmapped") - batch0[2])
    rejected = (ADMISSION_EVENTS.total(event="reject_full")
                + ADMISSION_EVENTS.total(event="reject_timeout") - rej0)
    per_tenant = {}
    done = 0
    for name, _ in tenants:
        ls = np.asarray([x for l in lat[name] for x in l])
        n_err = sum(errors[name])
        done += len(ls)
        if len(ls) == 0:
            per_tenant[name] = {"requests": 0, "errors": n_err}
            continue
        per_tenant[name] = {
            "requests": int(len(ls)), "errors": n_err,
            "p50_ms": round(float(np.percentile(ls, 50) * 1000), 2),
            "p99_ms": round(float(np.percentile(ls, 99) * 1000), 2),
            "p999_ms": round(float(np.percentile(ls, 99.9) * 1000), 2),
        }
    qps = done / wall if wall > 0 else 0.0
    serving = _serving_report(serving0)
    log(f"qps_mixed: {qps:.0f} qps @3x{clients_per_tenant} clients, "
        f"plan-cache hit rate "
        f"{-1.0 if hit_rate is None else hit_rate:.3f}, "
        f"{batched:.0f} batched, {rejected:.0f} rejected, "
        f"fast lane {serving['fast_lane']}, "
        f"batching {serving['batching']}; " + ", ".join(
            f"{n} p99 {per_tenant[n].get('p99_ms', '?')} ms"
            for n, _ in tenants))
    results["qps_mixed_tenants"] = {
        "qps": round(qps, 1),
        "clients_per_tenant": clients_per_tenant,
        "tenants": per_tenant,
        **serving,
        "plan_cache_hit_rate": (None if hit_rate is None
                                else round(hit_rate, 4)),
        "batched_queries": int(batched),
        "admission_rejections": int(rejected),
        "note": "3 tenants (dashboard/point-lastpoint/high-card "
                "groupby) through HTTP concurrently; per-tenant tails "
                "measure cross-tenant interference"}


# ---- mesh_scale: shard-count scaling + cluster pushdown ---------------------

MESH_CHILD_HOSTS = 120
MESH_CHILD_POINTS = 1500  # x hosts = 180k rows, 4 SST files


def mesh_scale_child(n_shard: int) -> int:
    """One mesh size measured in a fresh process (the device count is
    fixed at backend init, so each size needs its own interpreter).
    Emits one JSON line on stdout: per-query p50s, a sequential-QPS
    proxy, the serving path, and a parity digest the parent compares
    across sizes (bit-for-bit vs the 1-device oracle)."""
    import hashlib

    data_dir = tempfile.mkdtemp(prefix="gtpu_mesh_")
    try:
        from greptimedb_tpu.datatypes import DictVector, RecordBatch

        engine, qe = build_db(data_dir)
        qe.execute_one(
            "CREATE TABLE mesh_t (host STRING, v0 DOUBLE, v1 DOUBLE, "
            "ts TIMESTAMP(3) NOT NULL, TIME INDEX (ts), PRIMARY KEY "
            "(host)) WITH (append_mode = 'true')")
        info = qe.catalog.table("public", "mesh_t")
        rid = info.region_ids[0]
        rng = np.random.default_rng(17)
        hosts, points = MESH_CHILD_HOSTS, MESH_CHILD_POINTS
        n = hosts * points
        codes = np.repeat(np.arange(hosts, dtype=np.int32), points)
        names = np.asarray([f"h{i:03d}" for i in range(hosts)],
                           dtype=object)
        ts = np.tile(np.arange(points, dtype=np.int64) * 1000, hosts)
        # integer-valued doubles: float sums are associativity-free, so
        # the cross-size digest is exact, not approximate
        v0 = rng.integers(0, 1000, n).astype(np.float64)
        v1 = rng.integers(0, 1000, n).astype(np.float64)
        files = 4
        per = n // files
        for i in range(files):
            sl = slice(i * per, n if i == files - 1 else (i + 1) * per)
            engine.put(rid, RecordBatch(info.schema, {
                "host": DictVector(codes[sl], names), "v0": v0[sl],
                "v1": v1[sl], "ts": ts[sl]}))
            engine.flush(rid)
        dg_sql = ("SELECT host, date_bin(INTERVAL '1 minute', ts) AS b, "
                  "avg(v0), avg(v1), max(v0), min(v1) FROM mesh_t "
                  "GROUP BY host, b ORDER BY host, b")
        sg_sql = ("SELECT host, max(v0), sum(v1) FROM mesh_t "
                  "GROUP BY host ORDER BY host")
        dg_p50, dg_warm, dg_rows, _ = timed_sql(qe, dg_sql, repeats=7)
        path = qe.executor.last_path
        tier = qe.executor.last_tier
        digest = hashlib.sha256(
            repr(qe.execute_one(dg_sql).rows()).encode()).hexdigest()[:16]
        sg_p50, _, _, _ = timed_sql(qe, sg_sql, repeats=7)
        # sequential-QPS proxy for the single-groupby class
        t0 = time.perf_counter()
        reps = 30
        for _ in range(reps):
            qe.execute_one(sg_sql)
        qps = reps / (time.perf_counter() - t0)
        print(json.dumps({
            "shards": n_shard, "rows": n, "path": path, "tier": tier,
            "double_groupby_p50_ms": round(dg_p50, 2),
            "warm_ms": round(dg_warm, 1),
            "groups": dg_rows,
            "single_groupby_p50_ms": round(sg_p50, 2),
            "qps_single_groupby": round(qps, 1),
            "digest": digest,
        }))
        engine.close()
        return 0
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def bench_incremental_agg(engine, qe, results):
    """Incremental-aggregation micro-phase (ISSUE 13): the single-
    groupby shape over a multi-file table — cold fold (empty partial
    cache: every part reduces) vs warm repeat (cached [G, F] partials,
    only the memtable delta runs kernels) vs the post-flush fold that
    must compute exactly ONE new file + the memtable tail. Digests are
    bit-for-bit checked against the cache-disabled classic path."""
    import hashlib

    from greptimedb_tpu.datatypes import DictVector, RecordBatch
    from greptimedb_tpu.query import partial_cache as pc

    n_files, n_hosts, pts = 4, 200, 500
    qe.execute_one(
        "CREATE TABLE incragg (hostname STRING, ts TIMESTAMP(3) NOT NULL, "
        "usage_user DOUBLE, usage_system DOUBLE, TIME INDEX (ts), "
        "PRIMARY KEY (hostname)) WITH (append_mode = 'true')")
    info = qe.catalog.table("public", "incragg")
    rid = info.region_ids[0]
    rng = np.random.default_rng(31)
    names = np.asarray([f"host_{i}" for i in range(n_hosts)], dtype=object)

    def put(f, rows, flush):
        codes = np.tile(np.arange(n_hosts, dtype=np.int32), rows)
        ts = np.repeat(
            T0_MS + (f * pts + np.arange(rows, dtype=np.int64)) * 1000,
            n_hosts)
        cols = {"hostname": DictVector(codes, names), "ts": ts,
                "usage_user": rng.uniform(0.0, 100.0, rows * n_hosts),
                "usage_system": rng.uniform(0.0, 100.0, rows * n_hosts)}
        engine.put(rid, RecordBatch(info.schema, cols))
        if flush:
            engine.flush(rid)

    for f in range(n_files):
        put(f, pts, flush=True)
    put(n_files, 50, flush=False)  # memtable tail

    sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
           "max(usage_user), avg(usage_system) FROM incragg "
           f"WHERE hostname = 'host_1' AND ts >= {T0_MS} "
           "GROUP BY minute ORDER BY minute")

    def digest(res):
        h = hashlib.sha256()
        for c in res.columns:
            h.update(np.ascontiguousarray(np.asarray(c, dtype=float)))
        return h.hexdigest()[:16]

    def timed(repeats=9):
        qe.execute_one(sql)  # shape warm-up outside the clock
        times, res = [], None
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = qe.execute_one(sql)
            times.append((time.perf_counter() - t0) * 1000)
        return float(np.median(times)), res

    # classic oracle: partial cache off, bit-for-bit reference (the
    # operator's own A/B env value is restored, never clobbered)
    prev_pc = os.environ.get("GREPTIMEDB_TPU_PARTIAL_CACHE")

    def restore_pc():
        if prev_pc is None:
            os.environ.pop("GREPTIMEDB_TPU_PARTIAL_CACHE", None)
        else:
            os.environ["GREPTIMEDB_TPU_PARTIAL_CACHE"] = prev_pc

    os.environ["GREPTIMEDB_TPU_PARTIAL_CACHE"] = "off"
    try:
        classic_ms, classic_res = timed()
    finally:
        restore_pc()
    classic_digest = digest(classic_res)

    # cold: every part folds (and populates the cache)
    pc.global_cache().clear()
    t0 = time.perf_counter()
    cold_res = qe.execute_one(sql)
    cold_ms = (time.perf_counter() - t0) * 1000
    cold_stats = qe.executor.last_partial_stats or {}
    # warm: cached partials + memtable delta only
    warm_ms, warm_res = timed()
    warm_stats = qe.executor.last_partial_stats or {}
    # post-flush: ONE new file + memtable must fold, nothing else
    put(n_files + 1, 20, flush=True)
    put(n_files + 2, 10, flush=False)
    t0 = time.perf_counter()
    incr_res = qe.execute_one(sql)
    incr_ms = (time.perf_counter() - t0) * 1000
    incr_stats = qe.executor.last_partial_stats or {}
    os.environ["GREPTIMEDB_TPU_PARTIAL_CACHE"] = "off"
    try:
        incr_oracle = qe.execute_one(sql)
    finally:
        restore_pc()

    digests_equal = (digest(cold_res) == classic_digest
                     and digest(warm_res) == classic_digest
                     and digest(incr_res) == digest(incr_oracle))
    log(f"incremental-agg: classic {classic_ms:.1f} ms, cold "
        f"{cold_ms:.1f} ms, warm {warm_ms:.1f} ms "
        f"(delta {warm_stats.get('delta_rows')}/"
        f"{warm_stats.get('total_rows')} rows), post-flush "
        f"{incr_ms:.1f} ms (hits {incr_stats.get('part_hits')}, "
        f"misses {incr_stats.get('part_misses')}), "
        f"bit-for-bit={digests_equal}")
    results["incremental_agg"] = {
        "classic_p50_ms": round(classic_ms, 2),
        "cold_fold_ms": round(cold_ms, 2),
        "warm_repeat_p50_ms": round(warm_ms, 2),
        "warm_vs_classic": (round(classic_ms / warm_ms, 2)
                            if warm_ms > 0 else None),
        "cold_stats": cold_stats,
        "warm_stats": warm_stats,
        "post_flush_ms": round(incr_ms, 2),
        "post_flush_stats": incr_stats,
        "bit_for_bit_identical": bool(digests_equal),
        "path": qe.executor.last_path,
    }


def bench_mesh_scale(results, platform, device_count):
    """Shard-count scaling sweep: 1/2/4/8-device meshes each in a child
    process (CPU: --xla_force_host_platform_device_count; a real TPU box
    exposes its chips and GREPTIMEDB_TPU_MESH=Nx1 takes the first N),
    reporting per-size p50, scaling efficiency vs 1 shard, and a
    bit-for-bit parity digest against the 1-device oracle.

    Runs in the JAX-free SUPERVISOR, after the main child has exited: a
    chip belongs to one process at a time, so a process that holds it
    cannot start children that need it."""
    sizes = [1, 2, 4, 8]
    on_cpu = platform == "cpu"
    if not on_cpu:
        sizes = [s for s in sizes if s <= device_count] or [1]
    out = {}
    for s in sizes:
        if budget_left_s() < 180:
            log(f"mesh_scale: budget low, stopping before size {s}")
            break
        env = dict(os.environ)
        env["BENCH_MESH_CHILD"] = str(s)
        env.pop("BENCH_CHILD", None)
        env["GREPTIMEDB_TPU_MESH"] = "off" if s == 1 else f"{s}x1"
        env["GREPTIMEDB_TPU_MESH_MIN_ROWS"] = "1"
        if on_cpu:
            flags = [f for f in env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f]
            flags.append(f"--xla_force_host_platform_device_count={s}")
            env["XLA_FLAGS"] = " ".join(flags)
            env["JAX_PLATFORMS"] = "cpu"
        log(f"mesh_scale: size {s} ...")
        try:
            p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                               env=env, capture_output=True, text=True,
                               timeout=max(120, budget_left_s() - 60))
            line = [ln for ln in p.stdout.splitlines() if ln.strip()][-1]
            out[str(s)] = json.loads(line)
        except Exception as e:  # noqa: BLE001 — one size must not sink all
            log(f"mesh_scale size {s} failed: {e!r}")
            out[str(s)] = {"error": repr(e)[:200]}
    base = out.get("1", {})
    base_p50 = base.get("double_groupby_p50_ms")
    base_digest = base.get("digest")
    for s, d in out.items():
        p50 = d.get("double_groupby_p50_ms")
        if base_p50 and p50 and s != "1":
            d["speedup_vs_1"] = round(base_p50 / p50, 2)
            d["scaling_efficiency"] = round(base_p50 / (int(s) * p50), 2)
        if base_digest and d.get("digest"):
            d["parity_vs_1"] = d["digest"] == base_digest
    results["mesh_scale"] = out
    log(f"mesh_scale: {json.dumps(out)}")


# ---- qps_multiproc: serving-fabric scaling across frontend processes -------

MP_HOSTS = 60
MP_POINTS = 400


def qps_multiproc_child(idx: int) -> int:
    """One frontend process of the qps_multiproc phase: its own engine
    + data replica + HTTP server, attached to the shared serving
    fabric (GTPU_SHM_FABRIC* inherited from the parent). Protocol: run
    the first query — recording its wall time and how many XLA
    compiles it forced; with the shared executable cache, every
    process after the first must record ZERO — write <run>/<idx>.ready,
    wait for <run>/go, serve the timed workload, emit one JSON line."""
    import http.client
    import threading
    import urllib.parse

    run_dir = os.environ["BENCH_QPS_MP_RUN"]
    requests_total = int(os.environ.get("BENCH_QPS_MP_REQUESTS", "400"))
    clients = int(os.environ.get("BENCH_QPS_MP_CLIENTS", "8"))
    data_dir = tempfile.mkdtemp(prefix=f"gtpu_mp{idx}_")
    try:
        from greptimedb_tpu.catalog import Catalog, MemoryKv
        from greptimedb_tpu.datatypes import DictVector, RecordBatch
        from greptimedb_tpu.query import QueryEngine
        from greptimedb_tpu.servers.http import HttpServer
        from greptimedb_tpu.storage import RegionEngine
        from greptimedb_tpu.storage.engine import EngineConfig
        from greptimedb_tpu.utils.metrics import (
            SHM_FABRIC_EVENTS,
            XLA_COMPILES,
        )

        engine = RegionEngine(EngineConfig(data_dir=data_dir))
        qe = QueryEngine(Catalog(MemoryKv()), engine)
        qe.execute_one(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) NOT "
            "NULL, usage_user DOUBLE, TIME INDEX (ts), PRIMARY KEY "
            "(hostname)) WITH (append_mode = 'true')")
        info = qe.catalog.table("public", "cpu")
        rid = info.region_ids[0]
        # same seed in every child: the frontends serve identical
        # replicas, so adopted fabric artifacts face identical data
        rng = np.random.default_rng(41)
        hosts, points = MP_HOSTS, MP_POINTS
        codes = np.repeat(np.arange(hosts, dtype=np.int32), points)
        names = np.asarray([f"host_{i}" for i in range(hosts)],
                           dtype=object)
        ts = np.tile(T0_MS + np.arange(points, dtype=np.int64) * 1000,
                     hosts)
        engine.put(rid, RecordBatch(info.schema, {
            "hostname": DictVector(codes, names),
            "ts": ts,
            "usage_user": rng.uniform(0.0, 100.0, hosts * points)}))
        engine.flush(rid)

        srv = HttpServer(qe, host="127.0.0.1", port=0)
        port = srv.start()
        sql = (f"SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               f"max(usage_user) FROM cpu WHERE hostname = 'host_1' "
               f"AND ts >= {T0_MS} AND ts < {T0_MS + 3600 * 1000} "
               f"GROUP BY minute")
        body = urllib.parse.urlencode({"sql": sql}).encode()
        headers = {"Content-Type": "application/x-www-form-urlencoded"}

        def post(conn):
            conn.request("POST", "/v1/sql", body=body, headers=headers)
            r = conn.getresponse()
            return r.status, r.read()

        def mark(name):
            path = os.path.join(run_dir, f"{idx}.{name}")
            with open(path + ".tmp", "w") as f:
                f.write("1")
            os.replace(path + ".tmp", path)

        def wait_file(name, timeout_s=180.0):
            path = os.path.join(run_dir, name)
            deadline = time.monotonic() + timeout_s
            while not os.path.exists(path):
                if time.monotonic() > deadline:
                    raise TimeoutError(f"{name} never appeared")
                time.sleep(0.02)

        # barrier 1: every replica's CREATE TABLE bumps the fabric's
        # (db, table) version — correct DDL semantics, but it would
        # invalidate the warm-up publishes, so ALL setup must land
        # before child 0 warms (a real multi-frontend box runs DDL
        # once through the shared catalog; only this bench replays it
        # per replica)
        mark("setup")
        # warm-up: child 0 pays template probe + plan build + XLA
        # compile into the fabric (two queries: the fast lane publishes
        # its verified binder on the SECOND sighting); children 1..N
        # then adopt — their first query must compile nothing
        wait_file("warm" if idx == 0 else "adopt")
        conn0 = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        x0 = XLA_COMPILES.total()
        t0 = time.perf_counter()
        status, payload = post(conn0)
        first_ms = (time.perf_counter() - t0) * 1000
        first_compiles = XLA_COMPILES.total() - x0
        if status != 200:
            conn0.close()
            raise RuntimeError(f"first query -> {status}: "
                               f"{payload[-300:]!r}")
        if idx == 0:
            post(conn0)  # second sighting: build + publish the template
        conn0.close()
        mark("warmed")
        wait_file("go")

        per_client = max(1, requests_total // clients)
        lat = [[] for _ in range(clients)]
        errs = [0] * clients

        def client(i):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
            try:
                for _ in range(per_client):
                    t0 = time.perf_counter()
                    try:
                        st, _ = post(conn)
                        if st != 200:
                            errs[i] += 1
                            continue
                    except Exception:
                        errs[i] += 1
                        conn.close()
                        continue
                    lat[i].append(time.perf_counter() - t0)
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        lats = np.asarray([x for l in lat for x in l])
        done = len(lats)

        fabric = {k: int(SHM_FABRIC_EVENTS.total(**sel)) for k, sel in (
            ("tpl_hit", dict(event="hit", kind="template")),
            ("tpl_miss", dict(event="miss", kind="template")),
            ("plan_hit", dict(event="hit", kind="plan")),
            ("plan_miss", dict(event="miss", kind="plan")),
            ("publish", dict(event="publish")),
            ("detach", dict(event="detach")))}
        print(json.dumps({
            "idx": idx,
            "qps": round(done / wall, 1) if wall > 0 else 0.0,
            "wall_s": round(wall, 3),
            "requests": int(done),
            "errors": int(sum(errs)),
            "mean_ms": (round(float(lats.mean() * 1000), 2)
                        if done else None),
            "p99_ms": (round(float(np.percentile(lats, 99) * 1000), 2)
                       if done else None),
            "first_query_ms": round(first_ms, 1),
            "first_query_xla_compiles": int(first_compiles),
            "fabric": fabric,
        }))
        srv.stop()
        engine.close()
        return 0
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def bench_qps_multiproc(results):
    """Serving-fabric scaling (ISSUE 19): N frontend PROCESSES on one
    box, each with its own engine + data replica + HTTP server, all
    attached to one shared-memory fabric. Child 0 warms alone —
    template probes, plan build, XLA compile land in the fabric — then
    children 1..N-1 start and their FIRST query must adopt those
    artifacts (zero XLA compiles) before all N serve the timed
    workload concurrently. Scored per core (aggregate qps / N) against
    the 8-core reference baseline per core (1165.73 / 8 = 145.7)."""
    import subprocess

    baseline_per_core = round(1165.73 / 8, 1)
    out = {}
    for n in (1, 2, 4):
        if budget_left_s() < 120:
            log(f"qps_multiproc: budget low, stopping before N={n}")
            break
        fabric_dir = tempfile.mkdtemp(prefix="gtpu_fab_bench_")
        run_dir = os.path.join(fabric_dir, "run")
        os.makedirs(run_dir, exist_ok=True)
        env = dict(os.environ)
        env.pop("BENCH_CHILD", None)
        env.pop("BENCH_MESH_CHILD", None)
        # frontends serve from CPU replicas: N processes must not race
        # for one accelerator runtime
        env["JAX_PLATFORMS"] = "cpu"
        env["GTPU_SHM_FABRIC"] = "1"
        env["GTPU_SHM_FABRIC_DIR"] = fabric_dir
        # CPU-pinned processes keep the compile cache off unless told
        # where to put it: the frontends of one box share one directory
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(fabric_dir,
                                                        "xla-cache")
        env["BENCH_QPS_MP_RUN"] = run_dir
        procs = []

        def spawn(i):
            e = dict(env)
            e["BENCH_QPS_MP_CHILD"] = str(i)
            p = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)], env=e,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True)
            procs.append(p)
            return p

        def wait_marks(name, idxs, timeout_s=240.0):
            pending = set(idxs)
            deadline = time.monotonic() + timeout_s
            while pending:
                for i in list(pending):
                    if os.path.exists(
                            os.path.join(run_dir, f"{i}.{name}")):
                        pending.discard(i)
                for p in procs:
                    if p.poll() not in (None, 0):
                        _, stderr = p.communicate()
                        raise RuntimeError(
                            f"child died at {name}: {stderr[-400:]}")
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"children {sorted(pending)}: no {name}")
                time.sleep(0.05)

        def release(name):
            with open(os.path.join(run_dir, name), "w") as f:
                f.write("1")

        try:
            log(f"qps_multiproc: N={n} ...")
            for i in range(n):
                spawn(i)
            # all replicas' DDL before any publish (see child comment),
            # then child 0 warms the fabric alone, then the rest adopt
            wait_marks("setup", range(n))
            release("warm")
            wait_marks("warmed", [0])
            release("adopt")
            wait_marks("warmed", range(1, n))
            release("go")
            children = []
            for i, p in enumerate(procs):
                try:
                    stdout, stderr = p.communicate(
                        timeout=max(120, budget_left_s()))
                except subprocess.TimeoutExpired:
                    p.kill()
                    stdout, stderr = p.communicate()
                lines = [ln for ln in stdout.splitlines() if ln.strip()]
                try:
                    children.append(json.loads(lines[-1]))
                except Exception:  # noqa: BLE001 — keep the diagnosis
                    children.append({"idx": i,
                                     "error": (stderr or "")[-300:]})
            agg = sum(c.get("qps") or 0.0 for c in children)
            per_core = agg / n
            warm = [c.get("first_query_xla_compiles") for c in children]
            out[str(n)] = {
                "frontends": n,
                "children": children,
                "qps_aggregate": round(agg, 1),
                "qps_per_core": round(per_core, 1),
                "baseline_qps_per_core": baseline_per_core,
                "vs_baseline_per_core": round(
                    per_core / baseline_per_core, 3),
                "first_query_ms": [c.get("first_query_ms")
                                   for c in children],
                "first_query_xla_compiles": warm,
                # the shared-executable acceptance: every process after
                # the first compiles NOTHING on its first query
                "shared_xla_cache_effective": (
                    all(c == 0 for c in warm[1:]) if n > 1 else None),
            }
        except Exception as e:  # noqa: BLE001 — one N must not sink all
            log(f"qps_multiproc N={n} failed: {e!r}")
            out[str(n)] = {"error": repr(e)[:300]}
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.communicate()
            # a SIGKILL'd child leaks its attach-lock refcount: unlink
            # both segments defensively before dropping the directory
            from greptimedb_tpu.shm.fabric import (
                _unlink_segment,
                segment_name,
            )

            _unlink_segment(segment_name(fabric_dir))
            _unlink_segment(segment_name(
                os.path.join(fabric_dir, "arena")))
            shutil.rmtree(fabric_dir, ignore_errors=True)
    base = out.get("1", {})
    for s, d in out.items():
        if s != "1" and base.get("qps_per_core") \
                and d.get("qps_per_core") is not None:
            d["scaling_efficiency_vs_1"] = round(
                d["qps_per_core"] / base["qps_per_core"], 3)
    results["qps_multiproc"] = out
    log(f"qps_multiproc: {json.dumps(out)}")


def bench_cluster_pushdown(results):
    """Cluster-mode rollup substitution + lastpoint pruning through the
    distributed frontend: measured with the pushdown planes on vs the
    raw paths (GTPU_ROLLUP_SUBSTITUTE=0 / GTPU_LASTFRAG=0), asserting
    the served last_path so the speedup provably comes from partial
    planes, not noise."""
    import tempfile as _tf

    from greptimedb_tpu.cluster import Cluster
    from greptimedb_tpu.meta.metasrv import MetasrvOptions
    from greptimedb_tpu.partition.rule import (
        PartitionBound,
        RangePartitionRule,
    )

    cdir = _tf.mkdtemp(prefix="gtpu_clbench_")
    out = {}
    try:
        from greptimedb_tpu.datatypes import DictVector, RecordBatch

        c = Cluster(cdir, num_datanodes=3, opts=MetasrvOptions())
        hosts, minutes, per_minute = 96, 20, 300
        split1, split2 = f"host{hosts // 3:03d}", f"host{2 * hosts // 3:03d}"
        bounds = [PartitionBound((split1,)), PartitionBound((split2,)),
                  PartitionBound(())]
        rule = RangePartitionRule(["host"], bounds)
        c.create_partitioned_table(
            "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
            "NOT NULL, TIME INDEX (ts), PRIMARY KEY(host))", rule)
        info = c.catalog.table("public", "cpu")
        rng = np.random.default_rng(23)
        names = np.asarray([f"host{h:03d}" for h in range(hosts)],
                           dtype=object)
        # direct scattered puts (the write path's find_regions contract),
        # flushed every 2 minutes: ~5 SSTs per region so the lastpoint
        # A/B below has files for newest-first pruning to skip
        for m in range(minutes):
            n = hosts * per_minute
            codes = np.repeat(np.arange(hosts, dtype=np.int32),
                              per_minute)
            ts = (m * 60_000
                  + np.tile(np.arange(per_minute, dtype=np.int64)
                            * (60_000 // per_minute), hosts))
            v = rng.integers(0, 1000, n).astype(np.float64)
            batch = RecordBatch(info.schema, {
                "host": DictVector(codes, names), "v": v, "ts": ts})
            for idx, rows_idx in rule.split(
                    [names[codes]], n_rows=n).items():
                part = batch.take(rows_idx)
                part = RecordBatch(part.schema, {
                    k: (col.compact() if isinstance(col, DictVector)
                        else col)
                    for k, col in part.columns.items()})
                c.router.put(info.region_ids[idx], part)
            # one SST per region per minute: lastpoint's newest-first
            # termination needs more files than one decode wave, or the
            # wave reads everything and pruning can't pay
            for rid in info.region_ids:
                c.router.flush(rid)
        from greptimedb_tpu.maintenance.rollup import (
            RollupRule,
            rule_slot,
            run_rollup_job,
        )

        rule = RollupRule(resolution_ms=60_000)
        for dn in c.datanodes.values():
            dn.engine.maintenance.rollup_rules = [rule]
            for rid in list(dn.engine.regions):
                run_rollup_job(dn.engine, rid, rule_slot(60_000), rule)
        hi = (minutes - 1) * 60_000
        roll_sql = (f"SELECT host, min(v), max(v), sum(v), count(v) "
                    f"FROM cpu WHERE ts >= 0 AND ts < {hi} "
                    f"GROUP BY host ORDER BY host")

        def p50(sql, reps=5):
            c.sql(sql)  # warm
            times = []
            for _ in range(reps):
                t = time.perf_counter()
                c.sql(sql)
                times.append((time.perf_counter() - t) * 1000)
            return float(np.median(times))

        sub_ms = p50(roll_sql)
        sub_path = c.frontend.executor.last_path
        os.environ["GTPU_ROLLUP_SUBSTITUTE"] = "0"
        try:
            raw_ms = p50(roll_sql)
            raw_path = c.frontend.executor.last_path
        finally:
            os.environ.pop("GTPU_ROLLUP_SUBSTITUTE", None)
        out["rollup"] = {
            "pushdown_p50_ms": round(sub_ms, 2), "path": sub_path,
            "raw_p50_ms": round(raw_ms, 2), "raw_path": raw_path,
            "speedup": round(raw_ms / max(sub_ms, 1e-6), 2)}

        lp_sql = "SELECT host, last(v) FROM cpu GROUP BY host ORDER BY host"

        def p50_postwrite(reps=5):
            """Dashboard-refresh-after-ingest: each repeat lands one
            write first (bumping the data version, as live ingest does
            continuously), so the measured scan is the realistic
            incremental one — this is where newest-first pruning pays
            (the raw fragment re-assembles every region's full row set)."""
            c.sql(lp_sql)  # warm compile
            times = []
            for i in range(reps):
                c.sql("INSERT INTO cpu (host, v, ts) VALUES "
                      f"('host000', 1, {9_000_000 + i})")
                t = time.perf_counter()
                c.sql(lp_sql)
                times.append((time.perf_counter() - t) * 1000)
            return float(np.median(times))

        lp_ms = p50_postwrite()
        lp_path = c.frontend.executor.last_path
        os.environ["GTPU_LASTFRAG"] = "0"
        try:
            lp_raw_ms = p50_postwrite()
        finally:
            os.environ.pop("GTPU_LASTFRAG", None)
        out["lastpoint"] = {
            "postwrite_p50_ms": round(lp_ms, 2), "path": lp_path,
            "unpruned_p50_ms": round(lp_raw_ms, 2),
            "speedup": round(lp_raw_ms / max(lp_ms, 1e-6), 2)}
        c.close()
    finally:
        shutil.rmtree(cdir, ignore_errors=True)
    results["cluster_pushdown"] = out
    log(f"cluster_pushdown: {json.dumps(out)}")


def bench_tail_latency(results):
    """Tail-tolerance A/B over real datanode processes: fixed-QPS
    point-in-time aggregates against a ProcessCluster whose region
    owner suffers probabilistic 400 ms Flight stalls (injected
    server-side via GTPU_CHAOS env inheritance, ~2% of reads — inside
    the <=5% hedge budget by design). Three phases: unstalled baseline,
    stalled with hedging off, stalled with hedging on — reporting
    p50/p99/p999, deadline timeouts, and the hedge counters, so the
    artifact shows whether first-response-wins hedging pulls the
    stalled p99 back toward the unstalled one without extra load."""
    import tempfile as _tf

    from greptimedb_tpu.cluster.process_cluster import ProcessCluster
    from greptimedb_tpu.fault.retry import DeadlineExceeded
    from greptimedb_tpu.meta.metasrv import MetasrvOptions
    from greptimedb_tpu.session import QueryContext
    from greptimedb_tpu.utils.metrics import HEDGE_EVENTS

    SQL = "SELECT count(*), sum(v) FROM cpu"
    N, INTERVAL_S = 150, 0.02  # ~50 QPS offered, ~3 s per phase

    def mk_cluster(tmp):
        c = ProcessCluster(tmp, num_datanodes=2, opts=MetasrvOptions())
        c.beat_all(time.time() * 1000)
        c.sql("CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP "
              "TIME INDEX, PRIMARY KEY(host))")
        rows = ", ".join(f"('h{i:03d}', {float(i)}, {1000 * (i + 1)})"
                         for i in range(200))
        c.sql(f"INSERT INTO cpu (host, v, ts) VALUES {rows}")
        return c

    def run_phase(c):
        lat, timeouts = [], 0
        c.sql(SQL)  # warm (plan bind + scan cache path)
        for _ in range(N):
            t0 = time.perf_counter()
            try:
                c.frontend.execute_one(
                    SQL, QueryContext(db="public", timeout_ms=2000))
            except DeadlineExceeded:
                timeouts += 1
            el = time.perf_counter() - t0
            lat.append(el * 1000)
            if INTERVAL_S - el > 0:
                time.sleep(INTERVAL_S - el)
        lat.sort()

        def q(p):
            return round(lat[min(len(lat) - 1, int(p * len(lat)))], 2)

        return {"p50_ms": q(0.50), "p99_ms": q(0.99),
                "p999_ms": q(0.999), "timeouts": timeouts}

    out = {}
    saved = {k: os.environ.get(k) for k in
             ("GTPU_CHAOS", "GTPU_HEDGE", "GTPU_HEDGE_DELAY_MS")}
    dirs = [_tf.mkdtemp(prefix="gtpu_tail_") for _ in range(2)]
    try:
        os.environ.pop("GTPU_CHAOS", None)
        os.environ["GTPU_HEDGE"] = "off"
        c = mk_cluster(dirs[0])
        try:
            out["unstalled"] = run_phase(c)
        finally:
            c.close()
        # children arm the stall from env at spawn: 400 ms latency on
        # ~2% of server-side region reads — the per-request straggler
        # shape hedging exists for (a re-rolled attempt dodges it)
        os.environ["GTPU_CHAOS"] = \
            "flight.do_get=latency,arg:0.4,prob:0.02,@side:server"
        c = mk_cluster(dirs[1])
        try:
            out["stalled_hedge_off"] = run_phase(c)
            os.environ.pop("GTPU_HEDGE", None)  # hedging back on
            os.environ["GTPU_HEDGE_DELAY_MS"] = "25"
            before = {ev: HEDGE_EVENTS.get(event=ev) for ev in
                      ("fired", "won", "lost", "budget_denied")}
            phase = run_phase(c)
            phase.update({f"hedges_{ev}": int(HEDGE_EVENTS.get(event=ev)
                                              - before[ev])
                          for ev in before})
            out["stalled_hedge_on"] = phase
        finally:
            c.close()
        base_p99 = max(out["unstalled"]["p99_ms"], 1e-6)
        out["p99_vs_unstalled"] = {
            "hedge_off": round(
                out["stalled_hedge_off"]["p99_ms"] / base_p99, 2),
            "hedge_on": round(
                out["stalled_hedge_on"]["p99_ms"] / base_p99, 2)}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)
    results["tail_latency"] = out
    log(f"tail_latency: {json.dumps(out)}")


#: peak memory bandwidth by jax device_kind, GB/s (Google Cloud
#: documentation, "TPU v5e": 16 GB HBM2e, 819 GB/s per chip)
_PEAK_HBM_GBPS = {"TPU v5 lite": 819.0}


def roofline_detail(device_kind, results, rows):
    """Analytic achieved-bandwidth/FLOP numbers for the headline query,
    plus the chip roofline when on TPU — the MFU computation the round-3
    verdict asked for. double-groupby-all streams rows x (10 fields + ts
    + hostname + group ids) once through the segment-sum kernel, so
    bytes-touched / p50 is the effective HBM rate; FLOPs are one
    multiply-add per cell (segment-sum), so the op intensity is ~0.25
    FLOP/byte — this workload lives on the HBM-bandwidth wall, not the
    MXU, and bandwidth utilization IS its MFU analog."""
    dg = results.get("double_groupby_all")
    if not dg:
        return None
    p50_s = dg["p50_ms"] / 1000.0
    nf = len(FIELDS)
    # prepared plane (f64): values + ones column; ts i64 + tag i32 for keys
    bytes_planes = rows * (nf + 1) * 8
    bytes_keys = rows * (8 + 4)
    total_bytes = bytes_planes + bytes_keys
    flops = rows * nf * 2  # multiply-add per value cell
    out = {
        "note": ("analytic roofline from query shape; workload is "
                 "bandwidth-bound (op intensity ~0.25 FLOP/B)"),
        "bytes_touched": total_bytes,
        "achieved_gbps": round(total_bytes / p50_s / 1e9, 1),
        "achieved_gflops": round(flops / p50_s / 1e9, 1),
    }
    # a device the table does not know gets no utilization figure,
    # never a default peak
    peak_gbps = _PEAK_HBM_GBPS.get(device_kind)
    if peak_gbps is not None:
        out["peak_hbm_gbps"] = peak_gbps
        out["hbm_utilization"] = round(
            total_bytes / p50_s / 1e9 / peak_gbps, 3)
    return out


def capture_profile(qe, sql):
    """jax.profiler trace of one hot-path run (only on a real
    accelerator: the trace is for MFU/HBM-bandwidth tuning)."""
    import jax

    profile_dir = os.environ.get(
        "BENCH_PROFILE_DIR", os.path.join(tempfile.gettempdir(),
                                          "gtpu_profile"))
    try:
        with jax.profiler.trace(profile_dir):
            qe.execute_one(sql)
        n_files = sum(len(fs) for _, _, fs in os.walk(profile_dir))
        log(f"profiler trace captured -> {profile_dir} ({n_files} files)")
        return profile_dir
    except Exception as e:  # profiling must never sink the bench
        log(f"profiler capture failed: {e}")
        return None


def main():
    global T_MAIN_START
    data_dir = tempfile.mkdtemp(prefix="gtpu_bench_")
    T_MAIN_START = time.monotonic()
    try:
        import jax

        # no probe and no fallback: a backend that cannot initialise
        # fails this run — a CPU number is never printed in its place
        log(f"devices: {jax.devices()}")
        device = {"platform": jax.devices()[0].platform,
                  "device_kind": jax.devices()[0].device_kind,
                  "count": len(jax.devices())}
        platform = device["platform"]
        engine, qe = build_db(data_dir)
        log(f"ingesting {HOSTS} hosts x {HOURS}h @{STEP_S}s ...")
        rows, ingest_s = ingest(engine, qe, T0_MS)
        ingest_rps = rows / ingest_s
        log(f"ingested {rows} rows in {ingest_s:.1f}s "
            f"({ingest_rps:,.0f} rows/s)")
        engine.flush(qe.catalog.table("public", "cpu").region_ids[0])
        log("flushed to SST")

        results = {}

        def guarded(name, fn, on=None):
            """One config crashing must degrade to an error entry, not
            sink the whole artifact (round-5 incident: a PromQL span
            edge case killed the TPU attempt outright)."""
            if not (enabled(name) if on is None else on):
                return
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — config isolation
                import traceback

                traceback.print_exc()
                log(f"{name} failed: {e!r}")
                results[name] = {"error": repr(e)[:300]}

        def checkpoint():
            # refresh the salvageable line after EVERY phase (quick ones
            # included): a timeout then loses at most one config, not
            # all of them (round-5: a stale preliminary dropped the
            # completed 100M/promql results on the floor; r05: an
            # EXTERNAL rc=124 kill left no JSON at all — emit_result now
            # also mirrors each line to partial_path())
            emit_result(device, results, rows, ingest_rps, None,
                        preliminary=True)

        # first salvageable line BEFORE any query config runs: even a
        # crash inside the cpu suite leaves a parsed artifact carrying
        # the ingest numbers (r01/r04 exited with `parsed: null`
        # because everything before the first checkpoint sank together)
        checkpoint()
        bench_cpu_suite(qe, results, guard=guarded, checkpoint=checkpoint)
        guarded("scan_pipeline",
                lambda: bench_scan_pipeline(engine, qe, results))
        checkpoint()
        guarded("anchor_pyarrow_double_groupby",
                lambda: bench_anchor(engine, qe, results))
        checkpoint()
        # AFTER the anchor: this phase flushes a small extra SST into
        # the cpu table, which must not perturb the anchor's file set
        guarded("device_tier",
                lambda: bench_device_tier(engine, qe, results))
        checkpoint()
        guarded("sql_insert", lambda: bench_sql_insert(qe, results))
        guarded("ingest_qps",
                lambda: bench_ingest_qps(engine, qe, results))
        checkpoint()
        guarded("qps_single_groupby", lambda: bench_qps(qe, results))
        guarded("qps_mixed_tenants",
                lambda: bench_qps_mixed(qe, results))
        guarded("qps_multiproc", lambda: bench_qps_multiproc(results))
        guarded("incremental_agg",
                lambda: bench_incremental_agg(engine, qe, results))
        guarded("cluster_pushdown",
                lambda: bench_cluster_pushdown(results))
        guarded("tail_latency", lambda: bench_tail_latency(results))
        guarded("maintenance",
                lambda: bench_maintenance(engine, qe, results))
        # PRELIMINARY emit: the quick configs are done — if a big tracked
        # shape below overruns the supervisor's attempt window, the
        # supervisor salvages this line from the timed-out child's
        # stdout (or the partial file), so a TPU-backed headline
        # survives any overrun
        checkpoint()

        # tracked config #2 first among the big shapes: it is the
        # headline query at scale and must not be starved by the other
        # large ingests ("stream_large" kept as a back-compat alias)
        guarded("double_groupby_100m",
                lambda: bench_double_groupby_100m(engine, qe, results,
                                                  ingest_rps),
                on=(enabled("double_groupby_100m")
                    or enabled("stream_large")))
        checkpoint()
        guarded("promql_rate",
                lambda: bench_promql(engine, qe, results, ingest_rps))
        checkpoint()
        # fixed-cost compaction before the ELASTIC high-cardinality
        # config, which absorbs whatever budget remains
        guarded("compaction_reencode",
                lambda: bench_compaction(engine, qe, results))
        checkpoint()
        guarded("high_cardinality",
                lambda: bench_high_cardinality(engine, qe, results,
                                               ingest_rps))

        profile_dir = None
        if platform not in ("cpu",) and "double_groupby_all" in results:
            avg_list = ", ".join(f"avg({f})" for f in FIELDS)
            t_end_ms = T0_MS + HOURS * 3600 * 1000
            profile_dir = capture_profile(qe, (
                f"SELECT date_bin(INTERVAL '1 hour', ts) AS hour, "
                f"hostname, {avg_list} FROM cpu WHERE ts >= {T0_MS} "
                f"AND ts < {t_end_ms} GROUP BY hour, hostname"))

        emit_result(device, results, rows, ingest_rps, profile_dir)
        engine.close()
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def emit_result(device, results, rows, ingest_rps, profile_dir,
                preliminary=False):
    """Print the one-line result JSON. `proof` is the LAST top-level key
    ON PURPOSE: the round driver captures only a ~4 KB stdout *tail*,
    and in rounds 2-4 the backend/mfu fields (early in `detail`)
    were truncated away, leaving the artifact unable to show whether
    the chip was even tried. Keep the proof block compact (<1 KB) and
    trailing so it always survives the tail capture."""
    dg = results.get("double_groupby_all", {})
    value = dg.get("p50_ms")
    platform = device["platform"]
    mfu = roofline_detail(device["device_kind"], results, rows)
    # measured host<->accelerator link profile: the context every
    # device-tier number needs
    try:
        from greptimedb_tpu.query.physical import accelerator_link

        link = accelerator_link()
        link = {k: (None if v == float("inf") else v)
                for k, v in link.items()}
    except Exception:  # noqa: BLE001 — proof must always emit
        link = None
    line = json.dumps({
        "metric": "tsbs_double_groupby_all_p50_ms",
        "value": value,
        "unit": "ms",
        "vs_baseline": dg.get("vs_baseline"),
        "detail": {
            "backend": platform,
            "device_kind": device["device_kind"],
            "device_count": device["count"],
            "preliminary": preliminary,
            "rows": rows,
            "hosts": HOSTS,
            "hours": HOURS,
            "fields": len(FIELDS),
            "ingest_rows_per_s": round(ingest_rps),
            "ingest_vs_baseline": round(
                ingest_rps / BASE_INGEST_ROWS_S, 3),
            "baseline_ms": BASELINE_MS,
            "profile_dir": profile_dir,
            "mfu": mfu,
            "configs": results,
        },
        "proof": {
            "backend": platform,
            "device_kind": device["device_kind"],
            "preliminary": preliminary,
            "headline_p50_ms": value,
            "headline_tier": dg.get("tier"),
            "vs_baseline": dg.get("vs_baseline"),
            "warmup_ms": dg.get("warmup_ms"),
            "link": link,
            "mfu": mfu,
        },
    })
    print(line, flush=True)
    # incremental checkpoint: every emit (preliminary or final) is
    # mirrored to disk so ANY kill — child, supervisor, or the whole
    # process tree — leaves the newest completed-phase results readable
    write_partial(line)


def with_mesh_scale(json_line: str) -> str:
    """Run the mesh_scale sweep from the supervisor — the main child has
    exited and released the chip, so each mesh size can have it in turn
    — and fold it into the child's result line (`proof` stays last)."""
    if not enabled("mesh_scale"):
        return json_line
    doc = json.loads(json_line)
    detail = doc["detail"]
    results = {}
    bench_mesh_scale(results, detail["backend"], detail["device_count"])
    detail["configs"].update(results)
    return json.dumps(doc)


def supervise():
    """Run the real bench as a child process under a hard wall-clock cap.

    A backend can hang inside a C call that no in-process guard can
    interrupt. The supervisor is immune: it never touches jax. ONE
    attempt, on the backend the environment names: if it times out or
    dies without emitting JSON the supervisor salvages the newest
    checkpoint, or emits the error JSON itself — it never re-runs on the
    CPU to print a CPU number under the headline's name. Always ends
    with ONE JSON line on stdout."""
    total_s = int(os.environ.get("BENCH_TOTAL_TIMEOUT_S", "2400"))
    deadline = time.monotonic() + total_s
    # children mirror every emit here; pin the path so this process and
    # its children agree even across tempdir-per-process environments
    os.environ.setdefault(
        "BENCH_PARTIAL_PATH",
        os.path.join(tempfile.gettempdir(),
                     f"gtpu_bench_partial_{os.getpid()}.json"))

    def salvage_partial() -> bool:
        try:
            with open(partial_path(), encoding="utf-8") as f:
                line = f.read().strip()
        except OSError:
            return False
        if line.startswith("{"):
            log("supervisor: salvaged checkpoint from "
                + partial_path())
            print(line, flush=True)
            return True
        return False

    def on_term(signum, frame):
        # the r05 shape: an EXTERNAL timeout kills the SUPERVISOR
        # (rc=124) — stdout pipes from the child die with us, but the
        # checkpoint file survives; emit it as our last act
        log(f"supervisor: signal {signum} — emitting last checkpoint")
        salvage_partial()
        os._exit(0)

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    last_err = "unknown"
    attempt_s = deadline - time.monotonic()
    # the child sizes the big tracked configs against its OWN budget
    env = dict(os.environ, BENCH_CHILD="1",
               BENCH_TOTAL_TIMEOUT_S=str(int(attempt_s)))
    log(f"supervisor: timeout {attempt_s:.0f}s")
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            capture_output=True, text=True, timeout=attempt_s, env=env,
        )
    except subprocess.TimeoutExpired as e:
        tail = e.stderr or b""
        if isinstance(tail, bytes):
            tail = tail.decode(errors="replace")
        log(f"supervisor: TIMED OUT after {attempt_s:.0f}s\n{tail[-2000:]}")
        # salvage the child's PRELIMINARY result line: the quick
        # configs completed before a big tracked shape overran the
        # window
        partial = e.stdout or b""
        if isinstance(partial, bytes):
            partial = partial.decode(errors="replace")
        for line in reversed(partial.splitlines()):
            if line.startswith("{"):
                log("supervisor: salvaged preliminary result from "
                    "the timed-out run")
                print(line)
                return 0
        if salvage_partial():  # stdout empty: fall back to the file
            return 0
        last_err = f"bench timed out after {attempt_s:.0f}s"
    else:
        sys.stderr.write(r.stderr)
        json_line = None
        for line in reversed(r.stdout.splitlines()):
            if line.startswith("{"):
                json_line = line
                break
        if json_line is not None and r.returncode == 0:
            print(with_mesh_scale(json_line))
            return 0
        last_err = (r.stderr.strip().splitlines() or ["no stderr"])[-1]
        log(f"supervisor: bench failed rc={r.returncode}")
    if salvage_partial():
        # a failed final attempt may still have checkpointed completed
        # phases — a partial artifact beats a bare error
        return 0
    print(json.dumps({
        "metric": "tsbs_double_groupby_all_p50_ms",
        "value": None,
        "unit": "ms",
        "vs_baseline": None,
        "detail": {"error": last_err},
        "proof": {"backend": None, "error": str(last_err)[:500]},
    }))
    return 1


if __name__ == "__main__":
    if os.environ.get("BENCH_MESH_CHILD"):
        # one mesh_scale size in its own interpreter (device count is
        # fixed at backend init) — must run BEFORE the supervisor check
        sys.exit(mesh_scale_child(int(os.environ["BENCH_MESH_CHILD"])))
    if os.environ.get("BENCH_QPS_MP_CHILD"):
        # one qps_multiproc frontend process (serving fabric attach is
        # per-process) — must run BEFORE the supervisor check
        sys.exit(qps_multiproc_child(
            int(os.environ["BENCH_QPS_MP_CHILD"])))
    if os.environ.get("BENCH_CHILD") != "1":
        sys.exit(supervise())
    try:
        main()
    except BaseException:
        # the supervisor parses our last stdout line as JSON — always emit
        # one, even on catastrophic failure, so the round records a
        # diagnosis instead of a bare rc=1
        traceback.print_exc(file=sys.stderr)
        err = traceback.format_exc().strip().splitlines()[-1]
        print(json.dumps({
            "metric": "tsbs_double_groupby_all_p50_ms",
            "value": None,
            "unit": "ms",
            "vs_baseline": None,
            "detail": {"error": err},
            "proof": {"backend": None, "error": err[:500]},
        }))
        sys.exit(1)

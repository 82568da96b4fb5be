#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

Starts ONE serving process (`python -m greptimedb_tpu standalone start`),
pinned to the TPU platform so that a missing chip is a start-up error,
and drives it the way a user would, over the wire:

  1. loads TSBS devops `cpu-only` (table `cpu`: tag hostname, ts
     TIMESTAMP(3), ten DOUBLE usage fields, append_mode; 4000 hosts x
     12 h @ 10 s = 17.28M rows) through the InfluxDB line-protocol door
     — the door TSBS itself loads through — plus a small `prom_cpu`
     counter table (10k series x 1 h @ 15 s) for PromQL and `cpu_live`,
     an unflushed 72k-row tail in the same schema;
  2. checks the deployment's guarantee: every acknowledged row is read
     back (count(*) == rows sent, before and after ADMIN flush_table);
  3. runs five TSBS query types over /v1/sql and one PromQL range query
     over the Prometheus API, each several times, and compares every
     answer with a plain numpy reference computed here from the same
     seeded arrays (no engine code, no kernels, no caches);
  4. reads back from the server where each query ran (EXPLAIN ANALYZE:
     execution path + tier, first and steady), what the device did
     (/metrics: Pallas dispatches by mode, XLA compiles, compile-cache
     retrievals, H2D bytes) and whether anything degraded (/v1/device:
     canaries, latches, warm-up failures, link probe, per-device memory).

It FAILS (non-zero exit, reason on the last line, no result object) if
the serving process is not on a TPU, an answer is outside its written
tolerance, a row is missing, a steady aggregate ran on another tier than
the device (the mesh, with several chips), or anything on the device
path degraded. On success stdout ends with two lines, each one JSON
object: the run's record (device, sizes, `reduced`, load rate, the
guarantee check, per query the first and steady path and tier, counters;
it ends with "claim": null), and then, as the LAST line, the verdict and
nothing else, with the device as the serving process's JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

This process never imports jax or greptimedb_tpu: a chip belongs to one
process, and that process is the server. Numbers printed here are smoke
observations on whatever machine ran it, not benchmark metrics.

`--platform cpu` is the debugging mode for a sandbox without a chip
(tiny sizes, e.g. `--hosts 50 --hours 1 --prom-series 100`): every phase
runs and every answer is checked, device-only checks are skipped, and
the run still exits non-zero with "ok": false.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FIELDS = [f"usage_{n}" for n in (
    "user", "system", "idle", "nice", "iowait", "irq", "softirq",
    "steal", "guest", "guest_nice")]
T0_MS = 1456790400000  # 2016-03-01T00:00:00Z (TSBS's default start)
STEP_MS = 10_000
PROM_STEP_MS = 15_000
DEADLINE_S = 1150.0  # the contract allows 1200 s, compilation included
# `cpu_live`: the same schema, a tail that has NOT been flushed yet (what
# a dashboard over the newest data reads). Memtable-only scans have no
# immutable parts for the partial-aggregate cache to serve, so queries
# over it reach the classic device paths: on one chip the gates of the
# fused Pallas kernel, and with several chips — for a scan of at least
# config.mesh_min_rows() = 65,536 rows, which 200 hosts x 1 h = 72,000
# is — the sharded mesh dispatch.
LIVE_HOSTS, LIVE_HOURS = 200, 1


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke {time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


T_START = time.monotonic()


# ---- the serving process ----------------------------------------------------


class Server:
    def __init__(self, platform: str, data_home: str):
        self.data_home = data_home
        self.log_path = os.path.join(data_home, "server.log")
        self.port = _free_port()
        env = dict(os.environ)
        # pin the child to the stated platform list: with a platform
        # named explicitly, JAX raises when it cannot initialise it
        # instead of falling back to the CPU with a warning. "cpu" stays
        # in the list because the executor's host tier (first-touch
        # hedge) runs on the CPU backend of the same process.
        env["JAX_PLATFORMS"] = platform if platform == "cpu" \
            else f"{platform},cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (HERE, env.get("PYTHONPATH")) if p)
        self._log_f = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "greptimedb_tpu", "standalone", "start",
             "--data-home", os.path.join(data_home, "db"),
             "--http-addr", f"127.0.0.1:{self.port}"],
            cwd=HERE, env=env, stdout=self._log_f, stderr=subprocess.STDOUT,
            start_new_session=True)

    def log_tail(self, n: int = 30) -> str:
        try:
            with open(self.log_path, "rb") as f:
                lines = f.read().decode(errors="replace").splitlines()
        except OSError:
            return ""
        keep = [ln for ln in lines if ln.strip()]
        return "\n".join(keep[-n:])

    def wait_ready(self, timeout_s: float = 180.0) -> None:
        t_end = time.monotonic() + timeout_s
        while time.monotonic() < t_end:
            if self.proc.poll() is not None:
                tail = self.log_tail()
                last = tail.splitlines()[-1] if tail else "(no output)"
                sys.stderr.write(tail + "\n")
                raise SmokeFailure(
                    "the serving process exited at start-up (rc "
                    f"{self.proc.returncode}): {last}")
            try:
                conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                  timeout=2)
                conn.request("GET", "/health")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return
            except OSError:
                time.sleep(0.2)
        raise SmokeFailure("the serving process did not answer /health "
                           f"within {timeout_s:.0f}s")

    def stop(self) -> None:
        """SIGTERM the server's process group, then SIGKILL whatever is
        left of it."""
        for sig in (signal.SIGTERM, signal.SIGKILL):
            try:
                os.killpg(self.proc.pid, sig)
            except OSError:
                pass  # the whole group is already gone
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        self._log_f.close()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Client:
    """One keep-alive HTTP connection per thread."""

    def __init__(self, port: int):
        self.port = port
        self._tls = threading.local()

    def _conn(self) -> http.client.HTTPConnection:
        c = getattr(self._tls, "conn", None)
        # the server closes a keep-alive connection idle for
        # http.timeout_s (30 s): never reuse one that sat that long
        if c is not None and time.monotonic() - self._tls.used > 10.0:
            c.close()
            c = None
        if c is None:
            c = http.client.HTTPConnection("127.0.0.1", self.port,
                                           timeout=600)
            self._tls.conn = c
        self._tls.used = time.monotonic()
        return c

    def request(self, method: str, path: str, body: bytes = b"",
                ctype: str = "application/x-www-form-urlencoded"):
        # no blind retry: an unacknowledged write that did land would
        # break the row-count guarantee check, so a connection error
        # fails the run
        c = self._conn()
        try:
            c.request(method, path, body=body,
                      headers={"Content-Type": ctype} if body else {})
            r = c.getresponse()
            data = r.read()
        except (http.client.HTTPException, OSError):
            c.close()
            self._tls.conn = None
            raise
        self._tls.used = time.monotonic()
        return r.status, data

    def sql(self, sql: str) -> dict:
        status, data = self.request(
            "POST", "/v1/sql", urllib.parse.urlencode({"sql": sql}).encode())
        try:
            out = json.loads(data)
        except ValueError:
            raise SmokeFailure(f"/v1/sql HTTP {status}: {data[:300]!r}")
        if status != 200:
            raise SmokeFailure(
                f"/v1/sql HTTP {status}: {out.get('error')!r} for {sql[:120]}")
        return out

    def rows(self, sql: str) -> list:
        return self.sql(sql)["output"][-1]["records"]["rows"]

    def get_json(self, path: str) -> dict:
        status, data = self.request("GET", path)
        if status != 200:
            raise SmokeFailure(f"GET {path} HTTP {status}: {data[:300]!r}")
        return json.loads(data)

    def metrics(self) -> dict:
        """{(name, frozenset(labels)): value} of the classic exposition."""
        status, data = self.request("GET", "/metrics")
        if status != 200:
            raise SmokeFailure(f"GET /metrics HTTP {status}")
        out = {}
        for line in data.decode().splitlines():
            if not line or line[0] == "#":
                continue
            head, _, val = line.rpartition(" ")
            name, _, rest = head.partition("{")
            labels = frozenset(
                tuple(kv.split("=", 1)) for kv in
                rest.rstrip("}").replace('"', "").split(",") if "=" in kv)
            try:
                out[(name, labels)] = float(val)
            except ValueError:
                pass
        return out


def metric_sum(m: dict, name: str, **labels) -> float:
    want = set(labels.items())
    return sum(v for (n, ls), v in m.items()
               if n == "greptimedb_tpu_" + name and want <= set(ls))


# ---- data (seeded) and the line-protocol load ------------------------------


def make_cpu_data(seed: int, hosts: int, hours: int) -> dict:
    """{field: [points, hosts] float64}, uniform(0, 100) like TSBS's
    generator; row (p, h) is host_h at T0 + p*10 s."""
    rng = np.random.default_rng(seed)
    points = hours * 3600 * 1000 // STEP_MS
    return {f: rng.uniform(0.0, 100.0, (points, hosts)) for f in FIELDS}


def make_prom_data(seed: int, series: int, hours: int) -> np.ndarray:
    """[points, series] float64 counters: +50/point plus noise < 50, so
    every series is strictly increasing (no counter resets)."""
    rng = np.random.default_rng(seed + 4)
    points = hours * 3600 * 1000 // PROM_STEP_MS
    base = np.arange(points, dtype=np.float64)[:, None] * 50.0
    return base + rng.uniform(0.0, 50.0, (points, series))


def _lp_body(prefixes, cols: list, ts_ms: np.ndarray) -> bytes:
    """Line-protocol text for one batch, built column-wise by Arrow's C
    kernels: `<prefix> k1=v1,k2=v2 <ts>\\n` per row. Floats print in
    their shortest round-trip form, so the server parses back exactly
    the float64 the reference holds."""
    import pyarrow as pa
    import pyarrow.compute as pc

    parts = [prefixes]
    for i, (key, arr) in enumerate(cols):
        parts.append(pa.scalar((" " if i == 0 else ",") + key + "="))
        parts.append(pc.cast(pa.array(arr), pa.string()))
    parts.append(pa.scalar(" "))
    parts.append(pc.cast(pa.array(ts_ms), pa.string()))
    parts.append(pa.scalar("\n"))
    lines = pc.binary_join_element_wise(*parts, "")
    offs = lines.buffers()[1]
    n = len(lines)
    end = int(np.frombuffer(offs, dtype=np.int32, count=n + 1)[n])
    return lines.buffers()[2].slice(0, end).to_pybytes()


def load_table(client: Client, name: str, tag_key: str, tag_prefix: str,
               columns: dict, t0_ms: int, step_ms: int, writers: int) -> dict:
    """POST the table in time-sliced batches from `writers` threads.
    Returns rows acknowledged (HTTP 204) and the wall time."""
    import pyarrow as pa

    first = next(iter(columns.values()))
    points, series = first.shape
    slice_points = max(1, 200_000 // series)
    prefix_one = pa.array(
        [f"{name},{tag_key}={tag_prefix}{i}" for i in range(series)])
    acked = 0
    lock = threading.Lock()

    def one(p0: int) -> None:
        nonlocal acked
        p1 = min(p0 + slice_points, points)
        npts = p1 - p0
        idx = np.tile(np.arange(series, dtype=np.int32), npts)
        prefixes = prefix_one.take(pa.array(idx))
        ts = np.repeat(t0_ms + np.arange(p0, p1, dtype=np.int64) * step_ms,
                       series)
        body = _lp_body(
            prefixes,
            [(k, v[p0:p1].reshape(-1)) for k, v in columns.items()], ts)
        status, data = client.request(
            "POST", "/v1/influxdb/write?precision=ms", body,
            ctype="text/plain")
        if status != 204:
            raise SmokeFailure(
                f"line-protocol write HTTP {status}: {data[:300]!r}")
        with lock:
            acked += npts * series

    t0 = time.monotonic()
    with ThreadPoolExecutor(max_workers=writers) as pool:
        # list(): read every future's result so a failed batch raises
        list(pool.map(one, range(0, points, slice_points)))
    return {"rows": acked, "seconds": time.monotonic() - t0}


def flush_table(client: Client, table: str) -> None:
    """ADMIN flush_table submits one maintenance job per region; wait
    for each (state: queued | running | done | failed)."""
    t_end = time.monotonic() + 900
    for (job,) in client.rows(f"ADMIN flush_table('{table}')"):
        while True:
            row = client.rows(f"ADMIN maintenance_status({job})")[0]
            state, error = row[3], row[4]
            if state == "done":
                break
            if state == "failed" or time.monotonic() > t_end:
                raise SmokeFailure(
                    f"flush of {table}: job {job} is {state} ({error})")
            time.sleep(0.2)


def count_rows(client: Client, table: str) -> int:
    return int(client.rows(f"SELECT count(*) FROM {table}")[0][0])


# ---- queries, references, tolerances ----------------------------------------
#
# The chip computes aggregates in float32 (greptimedb_tpu/config.py
# compute_dtype: TPU has no native f64) where the CPU tests compute in
# float64. /v1/device says which dtype the serving process uses, and each
# comparison below is written against it:
#
#   exact    max / last_value select one stored value. Rounding to the
#            compute dtype is monotonic, so the engine's answer must
#            EQUAL the reference value rounded to that dtype — no
#            tolerance at all.
#   mean     avg over n values accumulates n adds in the compute dtype:
#            worst-case relative error n * eps (eps = 2^-24 for f32)
#            plus the input rounding. n = 360 here -> 2.2e-5; the bound
#            is set at 1e-4 (f32) / 1e-12 (f64).
#   promql   the PromQL engine evaluates in float64 on every backend
#            (emulated on the chip, where XLA's f64 is close to but not
#            bit-exact IEEE): rtol 1e-6.


def _round_to(dtype: str, ref: np.ndarray) -> np.ndarray:
    return np.asarray(ref, np.float64).astype(dtype).astype(np.float64)


def _fail_cmp(name: str, what: str) -> None:
    raise SmokeFailure(f"{name}: answer differs from the numpy reference "
                       f"— {what}")


def check_exact(name, got: np.ndarray, ref: np.ndarray, dtype: str) -> float:
    want = _round_to(dtype, ref)
    if got.shape != want.shape:
        _fail_cmp(name, f"shape {got.shape} != {want.shape}")
    bad = got != want
    if bad.any():
        i = tuple(np.argwhere(bad)[0])
        _fail_cmp(name, f"{int(bad.sum())} of {bad.size} values differ from "
                        f"the {dtype}-rounded reference, first at {i}: got "
                        f"{got[i]!r} want {want[i]!r}")
    return 0.0


def check_rtol(name, got: np.ndarray, ref: np.ndarray, rtol: float) -> float:
    if got.shape != ref.shape:
        _fail_cmp(name, f"shape {got.shape} != {ref.shape}")
    if not np.isfinite(got).all():
        _fail_cmp(name, "non-finite values in the answer")
    err = float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-300)))
    if err > rtol:
        _fail_cmp(name, f"max relative error {err:.3e} > rtol {rtol:.0e}")
    return err


def build_queries(data: dict, hosts: int, hours: int, dtype: str,
                  table: str = "cpu") -> list:
    """[(name, sql, check(rows) -> max_rel_err)] for five TSBS query
    types, with references from the seeded arrays."""
    t_end = T0_MS + hours * 3600_000
    ppm, pph = 60_000 // STEP_MS, 3600_000 // STEP_MS  # points per min/hour
    f32 = dtype == "float32"
    out = []

    # single-groupby-1-1-1: 1 host, 1 hour, 1 field, per-minute max
    sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
           f"max(usage_user) FROM {table} "
           f"WHERE hostname = 'host_0' AND ts >= {T0_MS} "
           f"AND ts < {T0_MS + 3600_000} GROUP BY minute ORDER BY minute")
    ref = data["usage_user"][:pph, 0].reshape(60, ppm).max(axis=1)
    ref_min = T0_MS + np.arange(60) * 60_000

    def chk_single(rows, ref=ref, ref_min=ref_min):
        if [r[0] for r in rows] != ref_min.tolist():
            _fail_cmp("single-groupby-1-1-1", "minute keys differ")
        return check_exact("single-groupby-1-1-1",
                           np.asarray([r[1] for r in rows], np.float64),
                           ref, dtype)
    out.append(("single-groupby-1-1-1", sql, chk_single))

    # cpu-max-all-8: 8 hosts, 8 hours, hourly max of all ten fields
    h8 = min(8, hours)
    sql = ("SELECT date_bin(INTERVAL '1 hour', ts) AS hour, "
           + ", ".join(f"max({f})" for f in FIELDS)
           + f" FROM {table} WHERE hostname IN ("
           + ", ".join(f"'host_{i}'" for i in range(8))
           + f") AND ts >= {T0_MS} AND ts < {T0_MS + h8 * 3600_000} "
           "GROUP BY hour ORDER BY hour")
    ref = np.stack([data[f][:h8 * pph, :8].reshape(h8, pph * 8).max(axis=1)
                    for f in FIELDS], axis=1)

    def chk_max8(rows, ref=ref):
        if [r[0] for r in rows] != \
                [T0_MS + h * 3600_000 for h in range(ref.shape[0])]:
            _fail_cmp("cpu-max-all-8", "hour keys differ")
        return check_exact("cpu-max-all-8",
                           np.asarray([r[1:] for r in rows], np.float64),
                           ref, dtype)
    out.append(("cpu-max-all-8", sql, chk_max8))

    # double-groupby-all: hourly avg of all ten fields per host
    sql = ("SELECT date_bin(INTERVAL '1 hour', ts) AS hour, hostname, "
           + ", ".join(f"avg({f})" for f in FIELDS)
           + f" FROM {table} WHERE ts >= {T0_MS} AND ts < {t_end} "
           "GROUP BY hour, hostname ORDER BY hour, hostname")
    ref = np.stack([data[f].reshape(hours, pph, hosts).mean(axis=1)
                    for f in FIELDS], axis=2)  # [hours, hosts, F]

    def chk_double(rows, ref=ref):
        if len(rows) != hours * hosts:
            _fail_cmp("double-groupby-all",
                      f"{len(rows)} groups != {hours * hosts}")
        got = np.full(ref.shape, np.nan)
        for r in rows:
            got[(r[0] - T0_MS) // 3600_000, int(r[1][5:])] = r[2:]
        return check_rtol("double-groupby-all", got, ref,
                          1e-4 if f32 else 1e-12)
    out.append(("double-groupby-all", sql, chk_double))

    # groupby-orderby-limit: last 5 per-minute max before a cutoff
    cutoff = T0_MS + (hours * 3600_000) * 3 // 4
    sql = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
           f"max(usage_user) FROM {table} WHERE ts < {cutoff} "
           "GROUP BY minute ORDER BY minute DESC LIMIT 5")
    last_min = (cutoff - T0_MS) // 60_000  # exclusive
    mins = np.arange(last_min - 1, last_min - 6, -1)
    ref = np.asarray([data["usage_user"][m * ppm:(m + 1) * ppm].max()
                      for m in mins])

    def chk_gbol(rows, ref=ref, mins=mins):
        if [r[0] for r in rows] != (T0_MS + mins * 60_000).tolist():
            _fail_cmp("groupby-orderby-limit", "minute keys differ")
        return check_exact("groupby-orderby-limit",
                           np.asarray([r[1] for r in rows], np.float64),
                           ref, dtype)
    out.append(("groupby-orderby-limit", sql, chk_gbol))

    # lastpoint: newest row of every host
    sql = ("SELECT hostname, "
           + ", ".join(f"last_value({f} ORDER BY ts)" for f in FIELDS)
           + f" FROM {table} GROUP BY hostname")
    ref = np.stack([data[f][-1] for f in FIELDS], axis=1)  # [hosts, F]

    def chk_last(rows, ref=ref):
        if len(rows) != hosts:
            _fail_cmp("lastpoint", f"{len(rows)} hosts != {hosts}")
        got = np.full(ref.shape, np.nan)
        for r in rows:
            got[int(r[0][5:])] = r[1:]
        return check_exact("lastpoint", got, ref, dtype)
    out.append(("lastpoint", sql, chk_last))
    return out


def promql_reference(mat: np.ndarray, t0_s: int, t_end_s: int, step_s: int,
                     window_s: int) -> tuple:
    """sum(rate(prom_cpu[W])) by Prometheus' extrapolatedRate rules
    (promql/functions.go), vectorised over series: the first-to-last
    delta of the samples in (t-W, t] is extrapolated to the window
    edges — fully when an edge is within 1.1 average sample intervals,
    else by half an interval — a counter's start never below its zero
    crossing. No counter-reset correction: the data has no resets by
    construction. Steps with fewer than two samples yield no point."""
    grid = t0_s + np.arange(mat.shape[0]) * (PROM_STEP_MS // 1000)
    times, vals = [], []
    for t in range(t0_s, t_end_s + 1, step_s):
        i0 = int(np.searchsorted(grid, t - window_s, side="right"))
        i1 = int(np.searchsorted(grid, t, side="right")) - 1
        if i1 <= i0:
            continue
        first, delta = mat[i0], mat[i1] - mat[i0]
        sampled = float(grid[i1] - grid[i0])
        avg_gap = sampled / (i1 - i0)
        to_start = np.full(first.shape, float(grid[i0] - (t - window_s)))
        to_end = float(t - grid[i1])
        pos = delta > 0
        to_zero = np.full(first.shape, np.inf)
        to_zero[pos] = sampled * first[pos] / delta[pos]
        to_start = np.minimum(to_start, to_zero)
        ext = sampled \
            + np.where(to_start < avg_gap * 1.1, to_start, avg_gap / 2) \
            + (to_end if to_end < avg_gap * 1.1 else avg_gap / 2)
        times.append(t)
        vals.append(float((delta * (ext / sampled) / window_s).sum()))
    return np.asarray(times), np.asarray(vals)


# ---- where it ran -----------------------------------------------------------


def explain_analyze(client: Client, sql: str) -> dict:
    rows = client.rows("EXPLAIN ANALYZE " + sql)
    rec = {"path": None, "tier": None, "total_ms": None}
    for (line,) in rows:
        s = line.strip()
        if s.startswith("execution path:"):
            rec["path"] = s.split(":", 1)[1].strip()
        elif s.startswith("execution tier:"):
            rec["tier"] = s.split(":", 1)[1].strip()
        elif s.startswith("ANALYZE trace="):
            for tok in s.split():
                if tok.startswith("total="):
                    rec["total_ms"] = float(tok[6:])
    return rec


def wait_warm(client: Client, timeout_s: float = 600.0) -> dict:
    """Wait until no hedged device warm-up is still compiling."""
    t_end = time.monotonic() + timeout_s
    while True:
        st = client.get_json("/v1/device")
        if st["warmup"]["warming"] == 0:
            return st
        if time.monotonic() > t_end:
            raise SmokeFailure("device warm-up still running after "
                               f"{timeout_s:.0f}s")
        time.sleep(0.25)


def device_counters(m: dict) -> dict:
    return {
        "xla_compiles": metric_sum(m, "xla_compile_total"),
        "xla_cache_retrievals": metric_sum(m, "xla_cache_retrieval_total"),
        "pallas_compiled": metric_sum(m, "pallas_dispatch_total",
                                      mode="compiled"),
        "pallas_interpret": metric_sum(m, "pallas_dispatch_total",
                                       mode="interpret"),
        "pallas_failed": metric_sum(m, "pallas_dispatch_total",
                                    kernel="fused_agg_failed"),
        "h2d_bytes": metric_sum(m, "device_transfer_bytes_total",
                                direction="h2d"),
        "d2h_bytes": metric_sum(m, "device_transfer_bytes_total",
                                direction="d2h"),
        "degradations": metric_sum(m, "device_degradation_total"),
        "mesh_dispatch_4": metric_sum(m, "mesh_dispatch_total", shards="4"),
    }


def run_query(client: Client, name: str, sql: str, check,
              repeats: int) -> dict:
    before = device_counters(client.metrics())
    first = explain_analyze(client, sql)
    wait_warm(client)
    lat, err = [], 0.0
    for _ in range(repeats):
        t0 = time.monotonic()
        rows = client.rows(sql)
        lat.append((time.monotonic() - t0) * 1e3)
        err = max(err, check(rows))
    steady = explain_analyze(client, sql)
    after = device_counters(client.metrics())
    rec = {"name": name, "first": first, "steady": steady,
           "steady_ms_median": float(np.median(lat)),
           "max_rel_err": err,
           "delta": {k: after[k] - before[k] for k in after
                     if after[k] != before[k]}}
    log(f"{name}: first {first['path']}/{first['tier']} "
        f"{first['total_ms']:.0f} ms, steady {steady['path']}/"
        f"{steady['tier']} median {rec['steady_ms_median']:.1f} ms, "
        f"err {err:.2e}, delta {rec['delta']}")
    return rec


def run_promql(client: Client, prom: np.ndarray, args) -> dict:
    """sum(rate(prom_cpu[2m])) as a range query through the Prometheus
    HTTP API, first execution and `repeats` steady ones."""
    t0_s, t_end_s = T0_MS // 1000, T0_MS // 1000 + args.prom_hours * 3600
    step_s, window_s = 60, 120
    ref_t, ref_v = promql_reference(prom, t0_s, t_end_s, step_s, window_s)
    path = "/v1/prometheus/api/v1/query_range?" + urllib.parse.urlencode(
        {"query": f"sum(rate(prom_cpu[{window_s}s]))", "start": t0_s,
         "end": t_end_s, "step": step_s})
    before = device_counters(client.metrics())
    lat, err = [], 0.0
    for _ in range(args.repeats + 1):
        t0 = time.monotonic()
        out = client.get_json(path)
        lat.append((time.monotonic() - t0) * 1e3)
        res = out["data"]["result"]
        if out.get("status") != "success" or len(res) != 1:
            raise SmokeFailure(
                f"promql: unexpected response {json.dumps(out)[:300]}")
        got_t = [int(float(t)) for t, _ in res[0]["values"]]
        got_v = np.asarray([float(v) for _, v in res[0]["values"]])
        if got_t != ref_t.tolist():
            _fail_cmp("promql-sum-rate", "evaluation timestamps differ")
        err = max(err, check_rtol("promql-sum-rate", got_v, ref_v, 1e-6))
    after = device_counters(client.metrics())
    rec = {"name": "promql-sum-rate", "first_ms": lat[0],
           "steady_ms_median": float(np.median(lat[1:])),
           "max_rel_err": err, "points": len(ref_t),
           "delta": {k: after[k] - before[k] for k in after
                     if after[k] != before[k]}}
    log(f"promql-sum-rate: first {lat[0]:.0f} ms, steady median "
        f"{rec['steady_ms_median']:.1f} ms, err {err:.2e}, "
        f"delta {rec['delta']}")
    return rec


def find_problems(dev: dict, counters: dict, records: list,
                  fused_admitted: list, expect_tpu: bool) -> list:
    """Where it ran, and did anything degrade: every reason this run
    must not count as a pass, from /v1/device, /metrics and the
    per-query EXPLAIN ANALYZE records."""
    problems = []
    multi = dev["count"] > 1
    steadies = [(r["name"], r["steady"]) for r in records if "steady" in r]
    for name, steady in steadies:
        if steady["tier"] not in ("device", "mesh"):
            problems.append(f"{name}: steady execution ran on tier "
                            f"{steady['tier']!r}")
    if multi:
        if not any(st["tier"] == "mesh" for _, st in steadies):
            problems.append("no steady execution ran on the mesh tier")
        if dev["count"] == 4 and counters["mesh_dispatch_4"] <= 0:
            problems.append("mesh_dispatch_total{shards=4} is 0")
    for name, verdict in dev["pallas"]["canaries"].items():
        if not verdict["ok"]:
            problems.append(f"pallas {name} canary failed: "
                            f"{verdict['error']}")
    if dev["pallas"]["fused_disabled"]:
        problems.append("_FUSED_DISABLED latched")
    if dev["pallas"]["partial_disabled"]:
        problems.append("_PARTIAL_DISABLED latched")
    if dev["warmup"]["failed"]:
        problems.append(f"{dev['warmup']['failed']} device warm-ups failed")
    if counters["degradations"] or counters["pallas_failed"]:
        problems.append(f"degradations counted: {dev['degradations']}")
    if not expect_tpu:
        return problems
    for r in fused_admitted:
        # (the mesh tier is chosen before the fused gates)
        if r["steady"]["tier"] == "device" and \
                "fused" not in (r["steady"]["path"] or ""):
            problems.append(
                f"{r['name']}: the fused kernel's gates admit this shape "
                f"but path {r['steady']['path']!r} served it")
    if counters["pallas_interpret"]:
        problems.append("a Pallas kernel ran in interpret mode")
    if counters["pallas_compiled"] <= 0:
        problems.append("no compiled Pallas kernel was dispatched")
    if set(dev["pallas"]["canaries"]) != {"dense", "fused"}:
        problems.append("a Mosaic canary was never consulted: "
                        f"{sorted(dev['pallas']['canaries'])}")
    if counters["h2d_bytes"] <= 0:
        problems.append("no bytes were uploaded to the device")
    idle = [d["id"] for d in dev["devices"] if not d.get("bytes_in_use")]
    if idle:
        problems.append(f"devices {idle} hold no bytes in use")
    return problems


# ---- main -------------------------------------------------------------------


def run(args, data_home: str) -> dict:
    expect_tpu = args.platform == "tpu"
    server = Server(args.platform, data_home)
    client = Client(server.port)
    try:
        server.wait_ready()
        dev = client.get_json("/v1/device")
        t_ready = time.monotonic() - T_START
        log(f"server up in {t_ready:.1f}s: platform={dev['platform']} "
            f"kind={dev['device_kind']!r} count={dev['count']} "
            f"dtype={dev['compute_dtype']} mesh={dev['mesh']} "
            f"native={dev['native_available']} "
            f"cache={dev['compile_cache_dir']}")
        if dev["platform"] != args.platform:
            raise SmokeFailure(
                f"the serving process runs on platform {dev['platform']!r}"
                f", not {args.platform!r}")
        if not dev["link"]["colocated"]:
            raise SmokeFailure(
                "the link probe says the accelerator is not attached to "
                f"this host ({dev['link']}): a chip off its host is not a "
                "supported deployment")
        dtype = dev["compute_dtype"]

        # ---- load ----------------------------------------------------------
        data = make_cpu_data(args.seed, args.hosts, args.hours)
        prom = make_prom_data(args.seed, args.prom_series, args.prom_hours)
        for table in ("cpu", "cpu_live"):
            client.sql(
                f"CREATE TABLE {table} (hostname STRING, "
                "ts TIMESTAMP(3) NOT NULL, "
                + ", ".join(f"{f} DOUBLE" for f in FIELDS)
                + ", TIME INDEX (ts), PRIMARY KEY (hostname)) "
                "WITH (append_mode = 'true')")
        client.sql(
            "CREATE TABLE prom_cpu (host STRING, val DOUBLE, "
            "ts TIMESTAMP(3) NOT NULL, TIME INDEX (ts), PRIMARY KEY (host)) "
            "WITH (append_mode = 'true')")
        load = load_table(client, "cpu", "hostname", "host_", data, T0_MS,
                          STEP_MS, args.writers)
        log(f"cpu: {load['rows']} rows acknowledged in "
            f"{load['seconds']:.1f}s ({load['rows'] / load['seconds']:.0f} "
            "rows/s)")
        pload = load_table(client, "prom_cpu", "host", "s", {"val": prom},
                           T0_MS, PROM_STEP_MS, args.writers)
        log(f"prom_cpu: {pload['rows']} rows acknowledged in "
            f"{pload['seconds']:.1f}s")
        live = make_cpu_data(args.seed + 1, LIVE_HOSTS, LIVE_HOURS)
        lload = load_table(client, "cpu_live", "hostname", "host_", live,
                           T0_MS, STEP_MS, 1)
        n_live = count_rows(client, "cpu_live")
        if n_live != lload["rows"]:
            raise SmokeFailure(
                f"cpu_live: {lload['rows']} rows acknowledged but count(*) "
                f"reads {n_live}")
        guarantee = {"cpu_live": {"acknowledged": lload["rows"],
                                  "read_back": n_live, "flushed": False}}
        for table, sent in (("cpu", load["rows"]),
                            ("prom_cpu", pload["rows"])):
            n0 = count_rows(client, table)
            t0 = time.monotonic()
            flush_table(client, table)
            flush_s = time.monotonic() - t0
            n1 = count_rows(client, table)
            guarantee[table] = {"acknowledged": sent, "read_back": n0,
                                "read_back_after_flush": n1,
                                "flush_seconds": flush_s}
            log(f"{table}: acknowledged {sent}, read back {n0}, after "
                f"flush ({flush_s:.1f}s) {n1}")
            if n0 != sent or n1 != sent:
                raise SmokeFailure(
                    f"{table}: {sent} rows acknowledged but count(*) reads "
                    f"{n0} before and {n1} after the flush")

        # ---- queries -------------------------------------------------------
        t_q0 = time.monotonic()
        c_q0 = device_counters(client.metrics())
        records = []
        for name, sql, check in build_queries(data, args.hosts, args.hours,
                                              dtype):
            records.append(run_query(client, name, sql, check,
                                     args.repeats))
            if time.monotonic() - T_START > DEADLINE_S:
                raise SmokeFailure("out of time (1200 s contract)")
        # three of them again over the unflushed tail: shapes the fused
        # kernel's own gates admit (fused_eligible/_fused_ok: 60 groups
        # x 1 field and 1 group x 10 fields with max lanes; 200 groups
        # x 10 fields of sum/count) — the last one scans all 72,000
        # rows, enough for the mesh tier when there is a mesh
        fused_admitted = []
        for name, sql, check in build_queries(
                live, LIVE_HOSTS, LIVE_HOURS, dtype, table="cpu_live")[:3]:
            records.append(run_query(client, name + "@live", sql, check,
                                     args.repeats))
            fused_admitted.append(records[-1])

        records.append(run_promql(client, prom, args))
        query_s = time.monotonic() - t_q0

        dev = wait_warm(client)
        counters = device_counters(client.metrics())
        problems = find_problems(dev, counters, records, fused_admitted,
                                 expect_tpu)
        if problems:
            sys.stderr.write(server.log_tail(40) + "\n")
            raise SmokeFailure("; ".join(problems))

        reduced = {}
        if args.hours != 12:
            reduced["cpu.hours"] = {"from": 12, "to": args.hours}
        if args.hosts != 4000:
            reduced["cpu.hosts"] = {"from": 4000, "to": args.hosts}
        reduced["prom_cpu.hours"] = {"from": 24, "to": args.prom_hours,
                                     "source": "BASELINE.json config 3"}
        if args.prom_series != 10000:
            reduced["prom_cpu.series"] = {"from": 10000,
                                          "to": args.prom_series}
        return {
            "ok": expect_tpu,
            "device": {"platform": dev["platform"],
                       "kind": dev["device_kind"], "count": dev["count"]},
            "compute_dtype": dtype,
            "mesh": dev["mesh"],
            "native_available": dev["native_available"],
            "compile_cache_dir": dev["compile_cache_dir"],
            "link": dev["link"],
            "sizes": {"cpu_rows": load["rows"], "hosts": args.hosts,
                      "hours": args.hours, "fields": len(FIELDS),
                      "prom_rows": pload["rows"],
                      "prom_series": args.prom_series,
                      "live_rows": lload["rows"]},
            "reduced": reduced,
            "seed": args.seed,
            "load_rows_per_s": load["rows"] / load["seconds"],
            "guarantee": guarantee,
            "server_ready_s": t_ready,
            "query_phase_s": query_s,
            "query_phase_counters": {
                k: counters[k] - c_q0[k] for k in counters},
            "queries": records,
            "devices": dev["devices"],
            "pallas": dev["pallas"],
            "warmup": dev["warmup"],
            "total_s": time.monotonic() - T_START,
            "claim": None,
        }
    finally:
        server.stop()


def verdict_line(result: dict) -> str:
    """The last line of stdout: exactly the keys "ok" and "device", the
    device exactly "platform", "kind" and "count" — what the serving
    process read from jax.devices(). Everything else the run learned is
    in the record printed on the line before."""
    dev = result["device"]
    return json.dumps({
        "ok": bool(result["ok"]),
        "device": {"platform": str(dev["platform"]),
                   "kind": str(dev["kind"]), "count": int(dev["count"])}})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--hosts", type=int, default=4000)
    ap.add_argument("--hours", type=int, default=12,
                    help="cut THIS (never hosts or fields) if the time "
                         "limit forces a cut; printed under `reduced`")
    ap.add_argument("--prom-series", type=int, default=10000)
    ap.add_argument("--prom-hours", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--writers", type=int, default=4)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="cpu = debugging mode, never exits 0")
    ap.add_argument("--data-home", default="",
                    help="default: a fresh temporary directory, removed "
                         "afterwards")
    ap.add_argument("--server-log", default="",
                    help="keep a copy of the serving process's log here")
    args = ap.parse_args()

    data_home = args.data_home or tempfile.mkdtemp(prefix="gtpu_smoke_")
    os.makedirs(data_home, exist_ok=True)
    try:
        result = run(args, data_home)
    except (SmokeFailure, http.client.HTTPException, OSError) as e:
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", flush=True)
        return 1
    finally:
        if args.server_log:
            os.makedirs(os.path.dirname(os.path.abspath(args.server_log)),
                        exist_ok=True)
            shutil.copyfile(os.path.join(data_home, "server.log"),
                            args.server_log)
        if not args.data_home:
            shutil.rmtree(data_home, ignore_errors=True)
    print(json.dumps(result), flush=True)
    print(verdict_line(result), flush=True)
    return 0 if result["ok"] else 4


if __name__ == "__main__":
    sys.exit(main())

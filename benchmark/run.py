#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1> [--rehearse]

Reads the cell from BENCHMARK.json, builds the configuration's data from
--seed, brings the deployment to its steady state (set-up), drives the
cell's traffic mix for --seconds from closed-loop clients over the wire,
compares a seeded sample of the answers with the templates' numpy
references, and prints as the LAST stdout line one JSON object with the
keys correct, attempted, failed, metrics, device (and breakdown when
traced) and, last, compared: every number `correct` hangs on beside its
limit (the last lines of stderr say the same). Everything else the run
learned is on earlier lines.

Fails (non-zero exit, no result line) when the serving process is not on
a TPU. `--rehearse` runs every phase on the CPU at the configuration's
`rehearsal` sizes and exits 3, so that it can never be read as a
measurement. This process never imports jax: the chip belongs to the
server.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.harness import procs, traffic, wire  # noqa: E402
from benchmark.harness.common import (  # noqa: E402
    BENCH_DIR, T_START, BenchFailure, cell, cell_metrics, load_json,
    load_module, loader_path, log, make_dataset, manifest, tables)

TRACE_SPAN_S = 5.0
DEGRADATION = "greptimedb_tpu_device_degradation_total"
COMPILES = "greptimedb_tpu_xla_compile_total"
RETRIEVALS = "greptimedb_tpu_xla_cache_retrieval_total"


class Context:
    """What the metric readers read."""

    def __init__(self):
        self.notes: list = []
        self.trace = None
        self.writer = None

    def note(self, msg: str) -> None:
        self.notes.append(msg)


def emit(kind: str, **rec) -> None:
    """One record line on stdout, before the last."""
    print(json.dumps({"record": kind, **rec}), flush=True)


class HostMemory(threading.Thread):
    """Peak of the machine's (cgroup's) memory in use over the window,
    sampled once a second: host memory is a cost too, and a cell whose
    server outgrows the host cannot be run at all."""

    PATHS = ("/sys/fs/cgroup/memory.current",
             "/sys/fs/cgroup/memory/memory.usage_in_bytes")

    def __init__(self):
        super().__init__(daemon=True)
        self.peak = None
        self._stop_it = threading.Event()

    def _read(self):
        for path in self.PATHS:
            try:
                with open(path) as f:
                    return int(f.read().strip())
            except (OSError, ValueError):
                continue
        return None

    def run(self) -> None:
        while not self._stop_it.is_set():
            v = self._read()
            if v is not None and (self.peak is None or v > self.peak):
                self.peak = v
            self._stop_it.wait(1.0)

    def stop(self):
        self._stop_it.set()
        self.join(timeout=5)
        return self.peak


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")


def cache_listing() -> set:
    try:
        return set(os.listdir(cache_dir()))
    except OSError:
        return set()


def prune_window_entries(before: set, since: float) -> int:
    """Delete the compile-cache entries the window wrote: executables
    specialised to its literals, which no later request could use, and
    which a second run with the same seed must not find compiled. Only
    names that were not there when set-up ended and whose mtime is not
    older than the window: the directory may be shared."""
    pruned = 0
    for name in cache_listing() - before:
        path = os.path.join(cache_dir(), name)
        try:
            if os.path.getmtime(path) >= since - 2.0:
                os.remove(path)
                pruned += 1
        except OSError:
            pass
    return pruned


# ---- set-up ------------------------------------------------------------------


def start_loader(config: dict, scale: dict, seed: int, data_home: str):
    """The configuration's loader (`common.loader_path`), as a child
    pinned to the CPU that builds the data home before the server
    starts."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.Popen(
        [sys.executable, loader_path(config),
         "--config", config["name"], "--scale", json.dumps(scale),
         "--seed", str(seed), "--data-home", os.path.join(data_home, "db"),
         "--parent", str(os.getpid())],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def finish_loader(proc) -> dict:
    out, err = proc.communicate()
    if proc.returncode != 0:
        sys.stderr.write(err.decode(errors="replace")[-3000:])
        raise BenchFailure(f"the loader exited {proc.returncode}")
    return json.loads(out.decode().strip().splitlines()[-1])


def read_back(client, ds) -> dict:
    """count(*) of every table of the dataset."""
    return {v.table: wire.count_rows(client, v.table) for v in tables(ds)}


def warm_up(client, mix, dtype: str) -> list:
    """Each template of this cell's mix, executed with the fixed warm-up
    draws until its steady path answers; then every client's connection
    once through every template, concurrently."""
    ds, recs = mix.ds, []
    for e in mix.entries:
        draws = mix.warmup(e)
        rec = {"template": e.name}
        t0 = time.monotonic()
        first = traffic.issue(client, e, draws[0], ds, True)
        if not first.ok:
            raise BenchFailure(f"warm-up of {e.name} failed: {first.error}")
        rec["first_ms"] = first.ms
        if hasattr(e.template, "sql"):
            wire.wait_warm(client)
            for _ in range(5):
                steady = wire.explain_analyze(
                    client, e.template.sql(draws[0], ds))
                if steady["tier"] in ("device", "mesh"):
                    break
                wire.wait_warm(client)
            rec["steady"] = steady
        for p in draws:
            r = traffic.issue(client, e, p, ds, True)
            if not r.ok:
                raise BenchFailure(f"warm-up of {e.name} failed: {r.error}")
            rec["steady_ms"] = r.ms
        wire.wait_warm(client)
        # the answer of the warm-up's own request is compared too
        parsed = e.template.parse(200, first.body)[0]
        rec["compared"] = e.template.compare(parsed, draws[0], ds, dtype)
        rec["limit"] = e.template.limit(dtype)
        rec["seconds"] = time.monotonic() - t0
        recs.append(rec)
        log(f"warm-up {e.name}: first {rec['first_ms']:.0f} ms, steady "
            f"{rec['steady_ms']:.1f} ms {rec.get('steady', '')} "
            f"compared {rec['compared']:.3g} (limit {rec['limit']:.3g})")

    errors: list = []

    def one_client(c: int) -> None:
        for e in mix.entries:
            r = traffic.issue(client, e,
                              mix.warmup(e)[c % traffic.WARM_DRAWS], ds,
                              False)
            if not r.ok:
                errors.append(BenchFailure(
                    f"concurrent warm-up of {e.name} failed: {r.error}"))
                return

    threads = [threading.Thread(target=one_client, args=(c,))
               for c in range(mix.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    wire.wait_warm(client)
    return recs


# ---- after the window --------------------------------------------------------


def verify(mix, reqs: list, seed: int, dtype: str, writer=None) -> tuple:
    """Compare a seeded sample of up to MAX_CHECKED kept answers per
    template with its reference; under a writer, a `fresh` template's
    answer with the reference at a front between the newest tick
    acknowledged before the request was sent and the newest sent before
    its answer arrived. Returns (records, all within limits)."""
    rng = np.random.default_rng([int(seed), 9])
    recs, good = [], True
    for e in mix.entries:
        mine = [r for r in reqs if r.entry is e]
        kept = [r for r in mine if r.body is not None]
        if len(kept) > traffic.MAX_CHECKED:
            # the slowest kept answer is always in the sample
            slow = max(range(len(kept)), key=lambda i: kept[i].ms)
            pick = set(rng.choice(len(kept), traffic.MAX_CHECKED - 1,
                                  replace=False).tolist()) | {slow}
            kept = [kept[i] for i in sorted(pick)]
        worst, limit = 0.0, e.template.limit(dtype)
        fresh = writer is not None and getattr(e.template, "fresh", False)
        for r in kept:
            parsed = e.template.parse(200, r.body)[0]
            at = {"front": writer.front(r.t_send, r.t_done)} if fresh else {}
            worst = max(worst, e.template.compare(parsed, r.params, mix.ds,
                                                  dtype, **at))
            r.body = None
        bad_rows = sum(1 for r in mine if not r.ok)
        ok = worst <= limit
        good = good and ok and (bool(kept) or not mine)
        recs.append({"template": e.name, "requests": len(mine),
                     "failed": bad_rows, "compared_answers": len(kept),
                     "compared": worst, "limit": limit, "within": ok})
        log(f"check {e.name}: {len(mine)} requests, {bad_rows} failed, "
            f"{len(kept)} answers compared, worst {worst:.3g} "
            f"(limit {limit:.3g}) -> {'ok' if ok else 'NOT CORRECT'}")
    return recs, good


def reduce_trace(trace_dir: str, window_s: float) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "harness",
                                      "trace_reduce.py"),
         trace_dir, "--window-s", repr(window_s),
         "--parent", str(os.getpid())],
        cwd=ROOT, env=env, capture_output=True, timeout=300)
    try:
        return json.loads(p.stdout.decode().strip().splitlines()[-1])
    except (ValueError, IndexError):
        sys.stderr.write(p.stderr.decode(errors="replace")[-2000:])
        return {"error": f"trace_reduce exited {p.returncode}"}


def read_metrics(man: dict, workload: str, which: str, ctx) -> dict:
    out = {}
    for m in cell_metrics(man, workload, which):
        spec = load_json("metrics", m["name"] + ".json")
        value = load_module("readers", spec["reader"]).read(ctx,
                                                            spec["args"])
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---- one run -----------------------------------------------------------------


def run(args, data_home: str, guard) -> tuple:
    man = manifest()
    wl = cell(man, args.workload)
    config = load_json("configs", wl["config"] + ".json")
    rehearsal = config["rehearsal"] if args.rehearse else {}
    scale = rehearsal.get("scale", config["scale"])
    seconds = float(args.seconds if args.seconds is not None
                    else rehearsal.get("seconds", man["run_seconds"]))
    platform = "cpu" if args.rehearse else "tpu"
    emit("cell", workload=args.workload, config=config["name"],
         traffic=wl["traffic"], seed=args.seed, seconds=seconds,
         trace=args.trace, scale=scale, rehearsal=args.rehearse)

    # -- data: the helper loads while this process generates its copy
    helper = start_loader(config, scale, args.seed, data_home)
    t0 = time.monotonic()
    ds = make_dataset(config, args.seed, scale)
    mix = traffic.Mix(wl["traffic"], ds, rehearsal.get("clients"))
    log(f"{sum(v.rows for v in tables(ds))} rows generated in "
        f"{time.monotonic() - t0:.1f}s")
    load = finish_loader(helper)

    server = wire.Server(platform, data_home)
    client = wire.Client(server.port)
    try:
        t0 = time.monotonic()
        server.wait_ready()
        dev = client.get_json("/v1/device")
        ready_s = time.monotonic() - t0
        log(f"server up in {ready_s:.1f}s: platform={dev['platform']} "
            f"kind={dev['device_kind']!r} count={dev['count']} "
            f"dtype={dev['compute_dtype']} cache={dev['compile_cache_dir']}")
        if dev["platform"] != platform:
            raise BenchFailure(
                f"the serving process runs on {dev['platform']!r}, not "
                f"{platform!r}")
        if dev["count"] < wl["chips"] and not args.rehearse:
            raise BenchFailure(
                f"{dev['count']} chips, the cell asks for {wl['chips']}")
        dtype = dev["compute_dtype"]
        wire.wait_maintenance_idle(client)
        n0, rows = read_back(client, ds), {v.table: v.rows
                                           for v in tables(ds)}
        log(f"loader: {load['rows']} rows acknowledged in "
            f"{load['put_s']:.1f}s ({load['rows'] / load['put_s']:.0f} "
            f"rows/s), flush {load['flush_s']:.1f}s, read back {n0}")
        if not n0 == load["tables"] == rows:
            raise BenchFailure(
                f"rows acknowledged {load['tables']} of {rows} but "
                f"count(*) reads {n0}")

        m_boot = client.metrics()
        warm = warm_up(client, mix, dtype)
        writer = traffic.Writer(mix.writer_spec, tables(ds)[0], args.seed) \
            if mix.writer_spec else None
        if writer:
            writer.warm_up(client)
        wire.wait_maintenance_idle(client)
        m0 = client.metrics()
        in_cache, t_window = cache_listing(), time.time()
        setup_s = time.monotonic() - T_START
        guard.set_up_done(seconds)
        emit("setup", setup_s=setup_s, server_ready_s=ready_s, load=load,
             rows=sum(rows.values()), read_back=sum(n0.values()),
             tables=n0, warm_up=warm,
             compiles=wire.metric_sum(m0, COMPILES),
             cache_retrievals=wire.metric_sum(m0, RETRIEVALS),
             compiles_in_warm_up=wire.metric_sum(m0, COMPILES)
             - wire.metric_sum(m_boot, COMPILES))

        # -- the window
        ctx = Context()
        tracer = None
        trace_dir = os.path.join(data_home, "trace")
        trace_times: dict = {}
        if args.trace:
            span = min(TRACE_SPAN_S, seconds / 2)

            def bracket() -> None:
                time.sleep(max(0.0, (seconds - span) / 2))
                trace_times["start"] = server.control(
                    f"trace_start {trace_dir}")
                time.sleep(span)
                trace_times["stop"] = server.control("trace_stop",
                                                     timeout_s=300)

            tracer = threading.Thread(target=bracket, daemon=True)
        log(f"window: {mix.clients} clients, {seconds:.0f}s, set-up took "
            f"{setup_s:.1f}s")
        if tracer:
            tracer.start()
        host_memory = HostMemory()
        host_memory.start()
        win = traffic.run_window(client, mix, args.seed, seconds, writer)
        host_memory_peak = host_memory.stop()
        memtable = server.control("memtable")["regions"] if writer else None
        if tracer:
            tracer.join(timeout=400)
        m1 = client.metrics()
        reqs = win["requests"]

        # -- checks, once the window has closed
        n1 = read_back(client, ds)
        if writer:
            # every acknowledged write is there with the loader's rows
            rows[writer.ds.table] += writer.acked_rows
        degraded = wire.metric_sum(m1, DEGRADATION) \
            - wire.metric_sum(m_boot, DEGRADATION)
        checks, within = verify(mix, reqs, args.seed, dtype, writer)
        dev1 = wire.wait_warm(client)
        memory = server.control("memory")
    except BenchFailure:
        sys.stderr.write(server.log_tail(40) + "\n")
        raise
    finally:
        server.stop()
    # a run that reached its end on the chip; a rehearsal touches no cache
    pruned = 0 if args.rehearse else prune_window_entries(in_cache, t_window)

    failed = sum(1 for r in reqs if not r.ok)
    attempted, stale = len(reqs), 0
    if writer:
        # a write that was not acknowledged and a read-after-acknowledge
        # check that got no answer are failed operations
        w = writer.stats(win["t0"], seconds)
        attempted += w["batches"] + w["checks"]
        failed += w["batches_failed"] + w["checks_failed"]
        stale = w["stale_reads"]
    correct = bool(within and failed == 0 and n1 == rows and stale == 0
                   and degraded == 0 and dev["platform"] == platform)
    emit("checks", read_back_after_window=sum(n1.values()),
         rows=sum(rows.values()), tables_after_window=n1, tables=rows,
         degradations=degraded, degradation_log=dev1["degradations"][-5:],
         templates=checks, errors=sorted(
             {r.error for r in reqs if r.error}
             | (writer.errors() if writer else set()))[:5],
         **({"writer": w, "stale": writer.stale()[:5]} if writer else {}))

    ctx.requests, ctx.m0, ctx.m1, ctx.writer = reqs, m0, m1, writer
    ctx.t0, ctx.seconds, ctx.setup_s = win["t0"], seconds, setup_s
    peaks = [d.get("peak_bytes_in_use") or 0 for d in memory["devices"]]
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["count"], "memory_peak_bytes": int(max(peaks))}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        window_s = trace_times["stop"]["t_call"] \
            - trace_times["start"]["t_started"]
        tr = reduce_trace(trace_dir, window_s)
        if tr.get("error") or not (tr.get("busy_s") or args.rehearse):
            raise BenchFailure(f"no device operation in the trace: {tr}")
        ctx.trace = tr
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        emit("trace", **{k: tr[k] for k in tr if k != "seen"},
             seen=tr["seen"][:12])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
        result["metrics"] = read_metrics(man, args.workload, "per_layer", ctx)
    else:
        result["metrics"] = read_metrics(man, args.workload, "end_to_end",
                                         ctx)
    result["device"] = device
    if writer:
        result["writer"] = w
    # every number `correct` compared, beside its limit: last in the line
    # (a count has to EQUAL its limit; a gap may not pass it)
    compared = {c["template"]: (c["compared"], c["limit"]) for c in checks}
    compared.update({f"rows.{t}": (n1[t], rows[t]) for t in rows})
    compared.update(failed_requests=(failed, 0), degradations=(degraded, 0))
    if writer:
        compared.update(stale_reads=(stale, 0))
    result["compared"] = {
        k: {"value": min(float(v), 1e300), "limit": float(lim)}
        for k, (v, lim) in compared.items()}
    by_t = {}
    for r in reqs:
        by_t.setdefault(r.entry.name, []).append(r.ms)
    emit("window", seconds=seconds, clients=mix.clients,
         drain_s=win["drain_s"], generator_share=win["generator_share"],
         compiles=wire.metric_sum(m1, COMPILES)
         - wire.metric_sum(m0, COMPILES),
         cache_retrievals=wire.metric_sum(m1, RETRIEVALS)
         - wire.metric_sum(m0, RETRIEVALS),
         per_template={k: {"n": len(v), "p50_ms": float(np.median(v)),
                           "max_ms": float(max(v))}
                       for k, v in by_t.items()},
         slowest=[{"template": r.entry.name, "ms": r.ms,
                   "sent_at_s": r.t_send - win["t0"], "params": r.params}
                  for r in sorted(reqs, key=lambda r: -r.ms)[:3]],
         notes=ctx.notes, cache_entries_pruned=pruned,
         host_memory_peak_bytes=host_memory_peak,
         **({"memtable_bytes_at_close": memtable} if writer else {}))
    return result, correct


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, the config's rehearsal sizes; exits 3")
    args = ap.parse_args(argv)

    data_home = tempfile.mkdtemp(prefix="gtpu_bench_")
    guard = procs.Guard()
    try:
        # on every way out of it, every process the run started has
        # ended and been waited for
        with guard:
            result, _ = run(args, data_home, guard)
    except (BenchFailure, OSError, KeyError, ValueError,
            procs.Stopped) as e:
        print(f"benchmark run FAILED: {type(e).__name__}: {e}", flush=True)
        return 1
    finally:
        shutil.rmtree(data_home, ignore_errors=True)
    if guard.killed:
        log(f"processes still there at the end, killed: {guard.killed}")
    print(json.dumps(result), flush=True)
    for k, c in result["compared"].items():
        print(f"compared {k}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())

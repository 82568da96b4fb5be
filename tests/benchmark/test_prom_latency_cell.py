"""`prom-latency-board` (ISSUE 32): the deployment
`prom-http-histogram-fleet` as files — dataset, template family with
its plain `bucket_quantile`, traffic mix, four metrics (one on a new
reader with its cost function) — rehearsed end to end on the CPU, with
its control and its broken-path check.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import traffic, wire  # noqa: E402
from benchmark.harness.common import (  # noqa: E402
    load_json, load_module, make_dataset, manifest, tables)

MAN = manifest()
CELL = "prom-latency-board"
CONFIG = "prom-http-histogram-fleet"
NEW_METRICS = ["histogram_fold_ms_per_query",
               "histogram_fold_host_ms_per_query",
               "histogram_index_hit_share", "histogram_fold_peak_share"]
STAGES = ["scan_ms_per_query", "upload_ms_per_query",
          "device_wait_ms_per_query", "assemble_ms_per_query",
          "encode_ms_per_query", "unattributed_ms_per_query",
          "compile_ms_per_query"]    # joined by ISSUE 36
JOINED = ["compiles_per_query", "h2d_bytes_per_query",
          "device_busy_ms_per_query", "device_idle_share",
          "promql_load_hit_share"] + STAGES
PANELS = ["p99-by-handler", "p99-by-instance", "error-ratio-by-handler",
          "apdex-by-handler"]
TABLES = ["http_request_duration_seconds_bucket",
          "http_request_duration_seconds_count",
          "http_request_duration_seconds_sum", "http_requests_total"]

slo = load_module("templates", "prom_slo")
bucket_quantile = slo.bucket_quantile
INF = math.inf


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 32), "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


@pytest.fixture(scope="module")
def small():
    conf = load_json("configs", CONFIG + ".json")
    return conf, make_dataset(conf, 5, conf["rehearsal"]["scale"])


def test_the_rehearsal_is_correct_and_compares_every_panel(rehearsal, small):
    p = rehearsal
    assert p.returncode == 3, p.stderr[-3000:]
    out = last_line(p.stdout)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    _, ds = small
    assert [v.table for v in tables(ds)] == TABLES
    for v in tables(ds):    # every logical table's count(*), twice, == rows
        c = out["compared"][f"rows.{v.table}"]
        assert c["value"] == c["limit"] == v.rows
    for name in PANELS:
        c = out["compared"][name]
        assert c["value"] <= c["limit"]
        assert c["limit"] == (1e-8 if name.startswith("p99") else 1e-10)
    recs = {r["record"]: r for r in (
        json.loads(ln) for ln in p.stdout.splitlines()[:-1]
        if ln.startswith('{"record"'))}
    assert recs["setup"]["load"]["tables"] == recs["setup"]["tables"] \
        == recs["checks"]["tables_after_window"] \
        == {v.table: v.rows for v in tables(ds)}
    assert set(recs["window"]["per_template"]) == set(PANELS)
    # on a CPU no share of a chip's peak is reported
    assert set(out["metrics"]) \
        >= set(NEW_METRICS + JOINED) - {"histogram_fold_peak_share"}
    assert "histogram_fold_peak_share" not in out["metrics"]
    # a steady window: every selector resident, every fold index kept,
    # nothing compiled
    assert out["metrics"]["promql_load_hit_share"]["value"] == 100.0
    assert out["metrics"]["histogram_index_hit_share"]["value"] == 100.0
    assert out["metrics"]["compiles_per_query"]["value"] == 0.0
    assert out["metrics"]["histogram_fold_host_ms_per_query"]["value"] > 0.0


def test_an_altered_quantile_makes_correct_false(monkeypatch, capsys):
    """The rest of a run with the fold broken underneath: one value of
    every histogram_quantile answer is altered, by a millionth, where
    the client receives it."""
    from benchmark import run as bench_run

    real = wire.Client.request

    def altered(self, method, path, body=b"", **kw):
        status, data = real(self, method, path, body, **kw)
        if "histogram_quantile" in path:
            out = json.loads(data)
            t, v = out["data"]["result"][0]["values"][3]
            out["data"]["result"][0]["values"][3] = [
                t, repr(float(v) * (1 + 1e-6))]
            data = json.dumps(out).encode()
        return status, data

    monkeypatch.setattr(wire.Client, "request", altered)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    rc = bench_run.main(["--workload", CELL, "--seed", "5", "--trace", "0",
                         "--rehearse"])
    assert rc == 3
    out = last_line(capsys.readouterr().out)
    assert out["correct"] is False and out["attempted"] > 0
    for name in PANELS:
        c = out["compared"][name]
        assert (c["value"] > c["limit"]) == name.startswith("p99")


def test_the_configuration_states_its_shapes_and_its_cuts(small):
    conf, ds = small
    fleet = load_module("datasets", conf["dataset"])
    assert fleet.LE == ["0.005", "0.01", "0.025", "0.05", "0.1", "0.25",
                        "0.5", "1", "2.5", "5", "10", "+Inf"]
    assert len(fleet.HANDLERS) == 10 and "/api/orders" in fleet.HANDLERS
    assert fleet.CODES == ["200", "400", "404", "500", "503"]
    per_pod = {v.table: v.series // v.instances for v in tables(ds)}
    assert per_pod == dict(zip(TABLES, [120, 10, 10, 50]))
    assert all("ENGINE=metric" in v.create_sql()
               and "append_mode" not in v.create_sql()
               and "TIME INDEX (ts)" in v.create_sql() for v in tables(ds))
    pods = conf["scale"]["instances"]
    assert conf["scale"] == {"instances": pods, "minutes": 40, "step_s": 15}
    assert pods in (1000, 2000)     # ISSUE 32's size, or its budget rule's
    assert conf["source_scale"]["instances"] == pods    # assumed, not cut
    assert conf["source_scale"]["series"] == pods * sum(per_pod.values())
    assert conf["rows"] == conf["source_scale"]["series"] * 160
    assert conf["reduced"] == ["hours"] and "setup" not in conf
    for key in ("source", "schema", "source_scale", "scale", "reduced_why",
                "assumed", "differences", "guarantees", "rehearsal"):
        assert conf[key]
    # the counters' rules: integer-valued, cumulative in le, strictly
    # increasing in time, _count the +Inf bucket, every code gains >= 1
    bucket = ds.view(TABLES[0]).fields["greptime_value"]
    by_le = bucket.reshape(ds.points, -1, 12)
    assert (bucket == np.floor(bucket)).all()
    assert (np.diff(by_le, axis=2) >= 0).all()
    assert (np.diff(bucket, axis=0) > 0).all()
    assert (ds.view(TABLES[1]).fields["greptime_value"]
            == by_le[:, :, -1]).all()
    assert (np.diff(ds.view(TABLES[2]).fields["greptime_value"],
                    axis=0) > 0).all()
    assert (np.diff(ds.view(TABLES[3]).fields["greptime_value"],
                    axis=0) >= 1).all()
    # a float32 copy of the counters is not exact
    assert bucket.max() > 2 ** 24
    # the same seed, the same samples; another seed, others
    again = make_dataset(conf, 5, conf["rehearsal"]["scale"])
    other = make_dataset(conf, 6, conf["rehearsal"]["scale"])
    assert (again.view(TABLES[0]).fields["greptime_value"] == bucket).all()
    assert (other.view(TABLES[0]).fields["greptime_value"] != bucket).any()


def test_a_program_without_the_fold_kernel_is_refused_at_once(tmp_path):
    """The parent commit cannot bring this deployment to its steady
    state inside the run's deadline (PERF.md section 6, PR 32): the
    dataset refuses such a program before anything is loaded, and the
    run exits 1 in its first seconds with no result line."""
    fleet = load_module("datasets", "prom_http_fleet")
    fleet.require_fold_kernel()     # this program has it
    with pytest.raises(ValueError, match="one kernel"):
        fleet.require_fold_kernel(str(tmp_path))
    # the whole run, on a checkout whose program lacks the file
    for name in ("BENCHMARK.json", "benchmark"):
        src = os.path.join(ROOT, name)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(
            src, tmp_path / name)
    (tmp_path / "greptimedb_tpu" / "ops").mkdir(parents=True)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "7", "--rehearse"], cwd=tmp_path, capture_output=True, text=True,
        timeout=60)
    assert r.returncode == 1, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1].startswith(
        "benchmark run FAILED: ValueError: prom-http-histogram-fleet needs")


def test_the_panels_are_the_practice_pages_expressions(small):
    _, ds = small
    mix = traffic.Mix(CELL, ds)
    text = {e.name: e.template.query(ds) for e in mix.entries}
    b = "http_request_duration_seconds"
    assert text["p99-by-handler"] == (
        f"histogram_quantile(0.99, sum by (le, handler) "
        f"(rate({b}_bucket[5m])))")
    assert text["p99-by-instance"] == (
        f"histogram_quantile(0.99, sum by (le, instance) "
        f'(rate({b}_bucket{{handler="/api/orders"}}[5m])))')
    assert text["error-ratio-by-handler"] == (
        'sum by (handler) (rate(http_requests_total{code=~"5.."}[5m])) / '
        "sum by (handler) (rate(http_requests_total[5m]))")
    assert text["apdex-by-handler"] == (
        f'(sum by (handler) (rate({b}_bucket{{le="0.25"}}[5m])) + '
        f'sum by (handler) (rate({b}_bucket{{le="1"}}[5m]))) / 2 / '
        f"sum by (handler) (rate({b}_count[5m]))")
    assert mix.clients == 4 and [e.weight for e in mix.entries] == [1] * 4
    rows = {e.name: e.template.expected_rows({}, ds) for e in mix.entries}
    assert rows == {"p99-by-handler": 10, "p99-by-instance": ds.instances,
                    "error-ratio-by-handler": 10, "apdex-by-handler": 10}
    rng = np.random.default_rng(1)
    for e in mix.entries:   # a trailing 30 min, step-aligned, inside the span
        for p in [e.template.draw(rng, ds) for _ in range(5)] \
                + e.template.edges(ds):
            assert p["end"] - p["start"] == 1800
            assert (p["end"] - ds.t0_ms // 1000) % e.template.step_s == 0
            assert ds.t0_ms // 1000 + 35 * 60 <= p["end"] \
                <= (ds.t_end_ms - ds.step_ms) // 1000


@pytest.mark.parametrize("panel", PANELS)
def test_the_reference_equals_brute_force(small, panel):
    """The panel's reference against a loop over series and steps that
    shares nothing with it but `bucket_quantile`."""
    _, ds = small
    mix = traffic.Mix(CELL, ds)
    t = next(e for e in mix.entries if e.name == panel).template
    p = t.edges(ds)[-1]
    names, times, ref = t.reference(p, ds)
    t0, step = ds.t0_ms // 1000, ds.step_ms // 1000

    def rate(view, s, at):
        """extrapolatedRate of series `s` over (at - 300, at]."""
        v = ds.view(view).fields["greptime_value"][:, s]
        pts = [(t0 + i * step, v[i]) for i in range(ds.points)
               if at - 300 < t0 + i * step <= at]
        (ta, va), (tb, vb) = pts[0], pts[-1]
        sampled, gap = tb - ta, (tb - ta) / (len(pts) - 1)
        to_start, to_end = ta - (at - 300), at - tb
        if vb > va:
            to_start = min(to_start, sampled * va / (vb - va))
        ext = sampled + (to_start if to_start < gap * 1.1 else gap / 2) \
            + (to_end if to_end < gap * 1.1 else gap / 2)
        return (vb - va) * (ext / sampled) / 300

    def summed(view, keep, by):
        tags = ds.view(view).series_tags()
        out: dict = {}
        for s in range(ds.view(view).series):
            if keep({k: v[s] for k, v in tags.items()}):
                key = tuple(tags[k][s] for k in by)
                out.setdefault(key, np.zeros(len(times)))
                out[key] += [rate(view, s, at) for at in times]
        return out

    b = "http_request_duration_seconds"
    if panel.startswith("p99"):
        by = "handler" if panel == "p99-by-handler" else "instance"
        got = summed(b + "_bucket", lambda lab: panel == "p99-by-handler"
                     or lab["handler"] == "/api/orders", (by, "le"))
        want = {}
        for name in names:
            les = sorted((float(le), le) for g, le in got if g == name)
            want[name] = bucket_quantile(
                [x for x, _ in les],
                np.stack([got[(name, le)] for _, le in les]), 0.99)
    elif panel == "error-ratio-by-handler":
        num = summed("http_requests_total",
                     lambda lab: lab["code"] in ("500", "503"), ("handler",))
        den = summed("http_requests_total", lambda lab: True, ("handler",))
        want = {name: num[(name,)] / den[(name,)] for name in names}
    else:
        ok = summed(b + "_bucket", lambda lab: lab["le"] == "0.25",
                    ("handler",))
        tol = summed(b + "_bucket", lambda lab: lab["le"] == "1",
                     ("handler",))
        cnt = summed(b + "_count", lambda lab: True, ("handler",))
        want = {name: (ok[(name,)] + tol[(name,)]) / 2 / cnt[(name,)]
                for name in names}
    assert len(names) == t.expected_rows(p, ds)
    for g, name in enumerate(names):
        np.testing.assert_allclose(ref[g], want[name], rtol=1e-9)


@pytest.mark.parametrize("panel", PANELS)
def test_the_float32_control_fails_the_limit(small, panel):
    _, ds = small
    mix = traffic.Mix(CELL, ds)
    entry = next(e for e in mix.entries if e.name == panel)
    rng = np.random.default_rng(32)
    for p in [entry.template.draw(rng, ds) for _ in range(3)] \
            + entry.template.edges(ds):
        assert entry.template.compare(None, p, ds, "float32", lowered=True) \
            > 3 * entry.template.limit("float32")
        names, times, ref = entry.template.reference(p, ds)
        assert np.isfinite(ref).all() and len(times) > 1


@pytest.mark.parametrize("panel", PANELS)
def test_an_answer_is_compared_point_by_point(small, panel):
    """The reference as the server would send it compares as 0; a
    millionth off in one point, a series short, a label too many or a
    step off each fail."""
    _, ds = small
    mix = traffic.Mix(CELL, ds)
    t = next(e for e in mix.entries if e.name == panel).template
    p = t.edges(ds)[0]
    names, times, ref = t.reference(p, ds)

    def answer():
        return [{"metric": {t.by: n},
                 "values": [[int(x), repr(float(v))]
                            for x, v in zip(times, ref[g])]}
                for g, n in enumerate(names)]

    limit = t.limit("float64")
    assert t.compare(answer(), p, ds, "float64") == 0.0
    off = answer()
    off[-1]["values"][2][1] = repr(float(ref[-1][2]) * (1 + 1e-6))
    assert limit < t.compare(off, p, ds, "float64") < 1e-5
    assert t.compare(answer()[1:], p, ds, "float64") == INF
    extra = answer()
    extra[0]["metric"]["le"] = "1"
    assert t.compare(extra, p, ds, "float64") == INF
    late = answer()
    late[0]["values"][0][0] += 1
    assert t.compare(late, p, ds, "float64") == INF


# ---- the plain reference of the fold -----------------------------------------

DEF = [0.1, 0.5, 1.0, INF]


@pytest.mark.parametrize("bounds, counts, phi, want", [
    (DEF, [10, 30, 40, 40], 0.5, 0.1 + 0.4 * (20 - 10) / 20),   # interpolate
    (DEF, [10, 30, 40, 40], 0.1, 0.1 * 4 / 10),   # first bucket: from 0
    (DEF, [10, 30, 40, 40], 0.25, 0.1),           # the rank on a bound
    (DEF, [10, 30, 40, 50], 0.9, 1.0),            # in +Inf: highest finite
    (DEF, [10, 30, 40, 40], 1.0, 1.0),
    (DEF, [10, 30, 40, 40], 0.0, 0.0),
    (DEF, [0, 30, 40, 40], 0.0, 0.0),             # 0 / 0 reads the lower bound
    (DEF, [10, 30, 40, 40], -0.1, -INF),
    (DEF, [10, 30, 40, 40], 1.1, INF),
    (DEF, [10, 30, 40, 40], math.nan, math.nan),
    (DEF, [0, 0, 0, 0], 0.5, math.nan),           # no observations
    ([0.1, 0.5, 1.0], [10, 30, 40], 0.5, math.nan),       # no +Inf
    ([0.1, 0.5, 1.0], [10, 30, 40], 1.5, INF),    # phi's rules come first
    ([INF], [40], 0.5, math.nan),                 # fewer than two buckets
    ([1.0, INF, 0.1, 0.5], [40, 40, 10, 30], 0.5, 0.3),   # any order
    ([0.1, 0.5, 1.0, 5.0, INF], [10, 30, 25, 40, 40], 0.9,
     1.0 + 4.0 * (36 - 30) / 10),                 # made monotone: 10 30 30 40
    (DEF, [10, math.nan, 40, 40], 0.5, 0.5 + 0.5 * (20 - 10) / 30),  # absent: 0
    ([-2.0, -0.5, 0.0, INF], [5, 9, 9, 10], 0.2, -2.0),   # first bound <= 0
    ([-2.0, -0.5, 0.0, INF], [5, 9, 9, 10], 0.7, -2.0 + 1.5 * 2 / 4),
], ids=lambda v: None)
def test_bucket_quantile_follows_prometheus_rule_by_rule(bounds, counts, phi,
                                                         want):
    got = float(bucket_quantile(bounds, counts, phi))
    if math.isnan(want):
        assert math.isnan(got)
    else:
        assert got == pytest.approx(want, rel=1e-15, abs=0.0)
    # a column of a [B, T] block reads as it does alone
    block = np.stack([np.asarray(counts, float),
                      np.asarray(counts, float) * 2], axis=1)
    both = bucket_quantile(bounds, block, phi)
    assert both.shape == (2,)
    np.testing.assert_array_equal(both[0], got)


# ---- the manifest, the metrics, the cost -------------------------------------


def test_the_manifest_entries_are_additions():
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert "prometheus.io/docs/practices/histograms" in entry["source"] \
        and "with_metric_engine" in entry["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["hours"]
    # looked up by name: a later PR appends to every list
    cell = next(w for w in MAN["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG \
        and cell["traffic"] == CELL
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    listed = {m["name"] for m in MAN["per_layer"] + MAN["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert set(JOINED + NEW_METRICS) <= listed
    for name in NEW_METRICS:
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "queries_per_s"
    for name in JOINED:     # joined to a list that was there
        assert CELL in by_name[name]["workloads"] \
            and len(by_name[name]["workloads"]) >= 2
    # it reports the two end-to-end metrics that list no cells
    assert {m["name"] for m in MAN["end_to_end"]
            if "workloads" not in m} == {"queries_per_s", "setup_s"}


def _recorded_expositions(tmp_path):
    """Two /metrics expositions of the program around two
    histogram_quantile requests at one data version: rendered by the
    server's registry, not written by hand."""
    from greptimedb_tpu.catalog import Catalog, MemoryKv
    from greptimedb_tpu.promql.engine import PromqlEngine
    from greptimedb_tpu.query.engine import QueryEngine
    from greptimedb_tpu.storage import RegionEngine
    from greptimedb_tpu.storage.engine import EngineConfig
    from greptimedb_tpu.utils.metrics import REGISTRY

    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    try:
        qe = QueryEngine(Catalog(MemoryKv()), eng)
        qe.execute_one(
            "CREATE TABLE lat_bucket (pod STRING, le STRING, "
            "ts TIMESTAMP(3) TIME INDEX, greptime_value DOUBLE, "
            "PRIMARY KEY (pod, le)) ENGINE=metric")
        qe.execute_one("INSERT INTO lat_bucket VALUES " + ", ".join(
            f"('p{pod}', '{le}', {15000 * i}, {float((i + 1) * (k + 1))})"
            for pod in range(2) for k, le in enumerate(("0.1", "1", "+Inf"))
            for i in range(8)))
        prom = PromqlEngine(qe)
        q = "histogram_quantile(0.9, sum by (le, pod) " \
            "(rate(lat_bucket[60s])))"
        text0 = REGISTRY.render()
        for _ in range(2):
            prom.eval_matrix(q, 75, 105, 15)
        return text0, REGISTRY.render()
    finally:
        eng.close()


@pytest.mark.parametrize("shape", ["recorded", "parent"])
def test_the_new_metrics_read_the_program_or_nothing(tmp_path, shape):
    text0, text1 = _recorded_expositions(tmp_path)
    assert "greptimedb_tpu_promql_histogram_fold_total" in text1
    assert "greptimedb_tpu_device_info" in text1
    if shape == "parent":
        text0, text1 = ("\n".join(
            line for line in t.splitlines()
            if "_promql_histogram_fold_" not in line
            and "greptimedb_tpu_device_info" not in line)
            for t in (text0, text1))

    class Ctx:
        m0 = wire.parse_exposition(text0)
        m1 = wire.parse_exposition(text1)
        requests = [type("Answer", (), {"ok": True, "t_done": 1.0})()] * 4
        trace = None

    def read(name):
        spec = load_json("metrics", name + ".json")
        return load_module("readers", spec["reader"]).read(Ctx, spec["args"])

    if shape == "parent":
        assert read("histogram_index_hit_share") is None
        assert read("histogram_fold_host_ms_per_query") is None
    else:
        # the first request builds the index, the second finds it
        assert read("histogram_index_hit_share") == 50.0
        assert read("histogram_fold_host_ms_per_query") > 0.0
    # untraced: nothing from the trace's readers
    assert read("histogram_fold_ms_per_query") is None
    assert read("histogram_fold_peak_share") is None
    # traced without the kernel (the parent folds in eager operations):
    # a zero from the kernel reader, not a hole; no share of a peak
    Ctx.trace = {"busy_s": 1.0, "window_s": 4.0, "kernels": [
        {"kernel": "segment_agg", "seconds": 0.5, "runs": 10.0}]}
    Ctx.t0, Ctx.seconds = 0.0, 10.0
    assert read("histogram_fold_ms_per_query") == 0.0
    assert read("histogram_fold_peak_share") is None
    # traced with it, on a CPU or on a program that does not state its
    # device: still no share of a chip's peak
    Ctx.trace["kernels"].append(
        {"kernel": "histogram_fold", "seconds": 0.02, "runs": 100.0,
         "ops": []})
    assert read("histogram_fold_ms_per_query") \
        == pytest.approx(0.02e3 / 4.0 / 0.4)
    assert read("histogram_fold_peak_share") is None


def test_the_peak_share_is_the_least_time_over_the_time_taken():
    """On a chip of the table: the mean run's bytes at 819 GB/s (its
    operations at the FLOP/s peak are less) over seconds / runs; a
    device the table lacks is an error."""
    spec = load_json("metrics", "histogram_fold_peak_share.json")
    pods = load_json("configs", CONFIG + ".json")["scale"]["instances"]
    padded = 1 << (pods - 1).bit_length()
    # the two quantile panels' folds: 10 handlers, and a pod each
    assert spec["args"]["shapes"] == [[16, 12, 31], [padded, 12, 31]]
    reader = load_module("readers", spec["reader"])

    def ctx(kind):
        class Ctx:
            m1 = wire.parse_exposition(
                'greptimedb_tpu_device_info{device_kind="%s",'
                'platform="tpu"} 1.0\n' % kind)
            trace = {"window_s": 5.0, "kernels": [
                {"kernel": "histogram_fold", "seconds": 0.0123,
                 "runs": 41.0, "ops": []}]}
        return Ctx

    nbytes = np.mean([8 * g * 12 * 31 + 9 * g * 12 + 8 + 8 * g * 31
                      for g in (16, padded)])
    share = reader.read(ctx("TPU v5 lite"), spec["args"])
    assert share == pytest.approx(
        100.0 * (nbytes / 819e9) / (0.0123 / 41.0))
    assert 0.0 < share < 100.0
    with pytest.raises(KeyError, match="TPU v9"):
        reader.read(ctx("TPU v9"), spec["args"])


def test_the_cost_of_a_fold_on_a_hand_computed_shape():
    cost = load_module("costs", "histogram_fold")
    # [G=4, B=3, T=5]: 60 values, 12 bucket slots, 20 results
    operations, nbytes = cost.run_cost(4, 3, 5)
    assert operations == 3 * 60 + 20 * 20 == 580
    assert nbytes == 8 * 60 + (8 + 1) * 12 + 8 + 8 * 20 == 756
    # the mean of the shapes given; the HLO heads are not read
    assert cost.cost([["%fusion = f32[4,3,5]", 0.1]],
                     shapes=[[4, 3, 5], [8, 3, 5]]) \
        == ((580 + 1160) / 2, (756 + 1504) / 2)
    with pytest.raises(ValueError):
        cost.cost([])
    # the kernel it counts is the program's own name
    with open(os.path.join(ROOT, "greptimedb_tpu", "ops",
                           "histogram.py")) as f:
        assert '@kernel_name("histogram_fold")' in f.read()

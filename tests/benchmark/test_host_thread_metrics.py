"""The per-layer metrics that read the thread CPU clock of the stage
spans and the interpreter-lock probe (ISSUE 40): ten data files on the
accepted reader `prom_delta`, each listed in the manifest with cells
that exist; each reads its value from a pair of expositions, 0 or
nothing from a program that lacks the series (the parent), and a
rehearsed traced run of one SQL and one PromQL cell reports all of them.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.common import (  # noqa: E402
    load_json,
    load_module,
    manifest,
)
from benchmark.harness.wire import parse_exposition  # noqa: E402

MAN = manifest()
CELLS = {w["name"] for w in MAN["workloads"]}
SQL = {"tsbs-scan-heavy", "tsbs-point-dash", "iot-fleet-board",
       "tsbs-read-under-ingest"}
HOST = "Host threads (the interpreter lock)"

#: metric -> (layer, better, cells, the fixture pair's reading over ten
#: requests, the reading of a program without the series)
HOST_THREAD_METRICS = {
    "request_cpu_ms_per_query": (HOST, "lower", CELLS, 200.0, 0.0),
    "request_on_cpu_share": (HOST, "higher", CELLS, 25.0, 0.0),
    "background_cpu_ms_per_query": (HOST, "lower", CELLS, 100.0, 0.0),
    "interpreter_lock_wait_ms": (HOST, "lower", CELLS, 0.2, None),
    "interpreter_lock_free_share": (HOST, "higher", CELLS, 90.0, None),
    "device_wait_cpu_ms_per_query": ("Kernels", "lower", CELLS, 80.0, 0.0),
    "encode_cpu_ms_per_query": ("Wire", "lower", CELLS, 30.0, 0.0),
    "scan_cpu_ms_per_query": ("Storage", "lower", SQL, 40.0, 0.0),
    "host_agg_cpu_ms_per_query": ("Storage caches", "lower", SQL, 25.0,
                                  0.0),
    "write_cpu_ms_per_batch": ("Ingest", "lower",
                               {"tsbs-read-under-ingest"}, 10.0, None),
}


def _exposition(name: str) -> dict:
    with open(os.path.join(FIXTURES, name)) as f:
        return parse_exposition(f.read())


def _read(name: str, m0: dict, m1: dict):
    spec = load_json("metrics", name + ".json")
    ctx = types.SimpleNamespace(requests=[None] * 10, m0=m0, m1=m1)
    return load_module("readers", spec["reader"]).read(ctx, spec["args"])


@pytest.mark.parametrize("name", sorted(HOST_THREAD_METRICS))
def test_metric_is_data_on_prom_delta_and_listed_with_cells_that_exist(name):
    layer, better, cells, _, _ = HOST_THREAD_METRICS[name]
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert set(entry["workloads"]) == cells <= CELLS
    assert entry["layer"] == layer and entry["better"] == better
    assert entry["moves"] == "queries_per_s"
    assert entry["source"] == "program_counter"
    spec = load_json("metrics", name + ".json")
    assert spec["reader"] == "prom_delta"
    assert spec["layer"].startswith(layer) and spec["what"]
    # what the program exposes, by the names it exposes them under
    from greptimedb_tpu.utils import metrics, tracing

    exposed = {m.name for m in metrics.REGISTRY._metrics}
    args = spec["args"]
    for one in args["num"] + (args["den"] if isinstance(args["den"], list)
                              else []):
        base = one["metric"]
        assert base in exposed or any(
            base == e + suffix for e in exposed
            for suffix in ("_sum", "_count", "_bucket"))
        stage = one.get("labels", {}).get("stage")
        assert stage is None or stage in tracing.STAGES + (
            "request", "background")
        le = one.get("labels", {}).get("le")
        assert le is None or float(le) in metrics.LOCK_WAIT_SECONDS.BUCKETS


def test_the_ten_are_the_manifests_last_entries_in_a_new_layer():
    names = [m["name"] for m in MAN["per_layer"]]
    assert set(names[-10:]) == set(HOST_THREAD_METRICS)
    assert sum(m["layer"] == HOST for m in MAN["per_layer"]) == 5


@pytest.mark.parametrize("name", sorted(HOST_THREAD_METRICS))
def test_metric_reads_its_value_from_a_pair_of_expositions(name):
    want = HOST_THREAD_METRICS[name][3]
    got = _read(name, _exposition("host_threads.m0.txt"),
                _exposition("host_threads.m1.txt"))
    assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", sorted(HOST_THREAD_METRICS))
def test_metric_reads_zero_or_nothing_on_a_program_without_the_series(name):
    """The parent: it has the wall histogram and neither the CPU counter,
    the probe nor the door's histogram. A count per request reads 0, a
    mean or a share over a series that is not there reads nothing."""
    def parent(text: str) -> dict:
        return {k: v for k, v in parse_exposition(text).items()
                if k[0].startswith("greptimedb_tpu_query_stage_seconds")}

    with open(os.path.join(FIXTURES, "host_threads.m0.txt")) as f0, \
            open(os.path.join(FIXTURES, "host_threads.m1.txt")) as f1:
        got = _read(name, parent(f0.read()), parent(f1.read()))
    assert got == HOST_THREAD_METRICS[name][4]


def test_the_program_renders_the_series_the_fixture_holds():
    """The fixture's lines are the registry's own rendering: names,
    label sets and the `le` of the one-millisecond bucket."""
    from greptimedb_tpu.utils import metrics

    text = "\n".join(
        line for m in (metrics.STAGE_CPU_SECONDS, metrics.LOCK_WAIT_SECONDS,
                       metrics.INGEST_REQUEST_CPU_SECONDS)
        for line in m.render())
    assert "# TYPE greptimedb_tpu_query_stage_cpu_seconds_total counter" \
        in text
    metrics.LOCK_WAIT_SECONDS.observe(0.0)
    rendered = parse_exposition("\n".join(metrics.LOCK_WAIT_SECONDS.render()))
    fixture = _exposition("host_threads.m1.txt")
    lock = "greptimedb_tpu_interpreter_lock_wait_seconds"
    assert {k for k in fixture if k[0].startswith(lock)} \
        <= {k for k in rendered if k[0].startswith(lock)}


@pytest.mark.parametrize("cell", ["tsbs-read-under-ingest", "prom-board"])
def test_rehearsed_traced_run_reports_every_host_thread_metric(cell):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("GTPU_TRACING", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 3, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    got = out["metrics"]
    want = {n for n, spec in HOST_THREAD_METRICS.items() if cell in spec[2]}
    assert want <= set(got), sorted(want - set(got))
    assert len(want) == (10 if cell == "tsbs-read-under-ingest" else 7)
    for name in want:
        entry = next(m for m in MAN["per_layer"] if m["name"] == name)
        assert got[name]["unit"] == entry["unit"]
    value = {n: got[n]["value"] for n in got}
    assert 0 < value["request_on_cpu_share"] <= 100
    assert 0 <= value["interpreter_lock_free_share"] <= 100
    assert value["request_cpu_ms_per_query"] > 0
    assert value["interpreter_lock_wait_ms"] >= 0
    assert value["background_cpu_ms_per_query"] >= 0
    # a stage's CPU is inside its wall, metric by metric
    for cpu, wall in (("device_wait_cpu_ms_per_query",
                       "device_wait_ms_per_query"),
                      ("encode_cpu_ms_per_query", "encode_ms_per_query"),
                      ("scan_cpu_ms_per_query", "scan_ms_per_query"),
                      ("host_agg_cpu_ms_per_query", "host_agg_ms_per_query")):
        if cpu in want:
            assert 0 <= value[cpu] <= value[wall] * 1.001
    assert value["device_wait_cpu_ms_per_query"] > 0
    if cell == "tsbs-read-under-ingest":
        assert value["write_cpu_ms_per_batch"] > 0
        assert value["scan_cpu_ms_per_query"] > 0

"""The per-layer metrics that read the server's stage spans, compile
attribution and tier counter (ISSUE 24): every one is a data file on an
existing reader, and a rehearsed traced run of each cell reports every
metric the manifest lists for it. Also `tools/trace_gaps.py` on a small
trace recorded on the chip through GET /debug/pprof/device.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.common import load_json, manifest  # noqa: E402

MAN = manifest()

#: ISSUE 24's table: metric -> cells that report it (ISSUE 26 added
#: `tsbs-point-dash` wherever `tsbs-scan-heavy` stands, and the one
#: stage that had no metric). A later cell joins a list by adding its
#: name: the lists are held to CONTAIN these, not to equal them.
TSBS = {"tsbs-scan-heavy", "tsbs-point-dash"}
STAGE_METRICS = {
    "compile_ms_per_query": TSBS | {"prom-board"},
    "host_tier_share": TSBS,
    "scan_ms_per_query": TSBS | {"prom-board"},
    "host_agg_ms_per_query": TSBS,
    "upload_ms_per_query": TSBS | {"prom-board"},
    "device_wait_ms_per_query": TSBS | {"prom-board"},
    "assemble_ms_per_query": TSBS | {"prom-board"},
    "encode_ms_per_query": TSBS | {"prom-board"},
    "unattributed_ms_per_query": TSBS | {"prom-board"},
    "admission_wait_ms_per_query": TSBS,
}


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_stage_metric_is_data_on_an_existing_reader(name):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert STAGE_METRICS[name] <= set(entry["workloads"])
    assert set(entry["workloads"]) <= {w["name"] for w in MAN["workloads"]}
    assert entry["moves"] == "queries_per_s"
    assert entry["source"] == "program_counter"
    spec = load_json("metrics", name + ".json")
    assert spec["reader"] in ("prom_delta", "prom_hist_delta")
    # what the program exposes, by the names it exposes them under
    from greptimedb_tpu.utils import metrics, tracing

    exposed = {m.name for m in metrics.REGISTRY._metrics}
    args = spec["args"]
    if spec["reader"] == "prom_hist_delta":
        # stages every request of the listed cells passes through
        series = [{"metric": args["metric"], "labels": ls}
                  for ls in args["labels"]]
    else:
        # a stage or a compile a window may lack reads 0 there: the
        # `_sum` series as a counter, a missing one counting 0
        series = args["num"] + (args["den"] if isinstance(args["den"], list)
                                else [])
    for one in series:
        base = one["metric"]
        assert base in exposed or base.removesuffix("_sum") in exposed
        stage = one.get("labels", {}).get("stage")
        assert stage is None or stage in tracing.STAGES + ("other",)


@pytest.mark.parametrize("cell", ["prom-board", "tsbs-scan-heavy",
                                  "tsbs-point-dash"])
def test_rehearsed_traced_run_reports_every_stage_metric(cell):
    """A reader that finds no series drops its metric without a word: run
    the cell (rehearsed, --trace 1) and see every metric of the table."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 3, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    got = out["metrics"]
    want = {n for n, cells in STAGE_METRICS.items() if cell in cells}
    assert want <= set(got), sorted(want - set(got))
    # ... and every other per-layer metric the manifest lists for it
    listed = {m["name"] for m in MAN["per_layer"]
              if cell in m.get("workloads", [cell])}
    assert listed == set(got), sorted(listed ^ set(got))
    # traced on the CPU: no device plane, so no kernel ran and no gap
    assert out["breakdown"] == {"device_ops": [], "idle_gaps": []}
    # stages every request of the cell passes through were observed
    for name in ("scan_ms_per_query", "device_wait_ms_per_query",
                 "encode_ms_per_query", "unattributed_ms_per_query"):
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms"
    if cell == "prom-board":
        # the repair: PromQL's uploads are counted now
        assert got["h2d_bytes_per_query"]["value"] > 0
        assert got["upload_ms_per_query"]["value"] > 0
        assert got["compile_ms_per_query"]["value"] == 0
        # the kernel metrics read a zero there, not nothing
        assert got["counter_adjust_ms_per_query"]["value"] == 0
        assert got["cumsum_ms_per_query"]["value"] == 0
    else:
        assert got["admission_wait_ms_per_query"]["value"] >= 0
        assert got["host_agg_ms_per_query"]["value"] > 0
        # on the CPU backend nothing routes to a host tier
        assert got["host_tier_share"]["value"] == 0
        # since PR 31 a rehearsed window's literals are operands of
        # programs the warm-up compiled: it may compile nothing
        assert got["compile_ms_per_query"]["value"] >= 0
        assert got["agg_program_reuse_share"]["value"] > 50


def _trace_gaps():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import trace_gaps
    finally:
        sys.path.pop(0)
    return trace_gaps


def test_trace_gaps_reduction_arithmetic():
    tg = _trace_gaps()
    trace = {
        "device": [("/device:TPU:0",
                    [("op.a", 100.0, 50.0), ("op.b", 120.0, 80.0),
                     ("op.c", 1000.0, 100.0)],
                    [("jit_window_stats(123)", 100.0, 100.0),
                     ("jit_convert_element_type(9)", 1000.0, 100.0)])],
        "spans": [
            {"name": "scan", "start_ns": 0.0, "duration_ns": 600.0,
             "trace_id": "t1", "span_id": "a" * 16, "thread": "python"},
            {"name": "scan", "start_ns": 300.0, "duration_ns": 100.0,
             "trace_id": "t2", "span_id": "b" * 16, "thread": "python"},
            {"name": "compile", "start_ns": 400.0, "duration_ns": 800.0,
             "trace_id": "t1", "span_id": "c" * 16, "thread": "python"},
            {"name": "http:/v1/sql", "start_ns": 0.0, "duration_ns": 1200.0,
             "trace_id": "t1", "span_id": "d" * 16, "thread": "python"}]}
    red = tg.reduce_trace(trace, gaps=2)
    assert red["busy_s"] * 1e9 == pytest.approx(200.0)   # [100,200]+[1000,1100]
    assert red["window_s"] * 1e9 == pytest.approx(1200.0)  # spans widen it
    assert red["idle_share"] == pytest.approx(1 - 200 / 1200)
    assert [(k["kernel"], k["runs"]) for k in red["kernels"]] == [
        ("window_stats", 1), ("convert_element_type", 1)]
    gap = red["gaps"][0]
    assert gap["idle_ms"] * 1e6 == pytest.approx(800.0)
    assert gap["at_ms"] * 1e6 == pytest.approx(200.0)
    # stage and compile spans first, folded by name; the root after them
    assert [o["name"] for o in gap["spans_open"]] == [
        "compile", "scan", "http:/v1/sql"]
    scan = gap["spans_open"][1]
    assert scan["spans"] == 2 and scan["trace_id"] == "t1"
    assert scan["overlap_ms"] * 1e6 == pytest.approx(400.0 + 100.0)
    assert "compile" in tg.render(red)


def test_trace_gaps_on_a_profile_recorded_on_the_chip():
    """fixtures/device_profile.xplane.pb: one second of `prom-board`
    traffic on one TPU v5 lite chip, taken through /debug/pprof/device;
    what the tool must find in it is in device_profile.expected.json."""
    tg = _trace_gaps()
    want = json.load(open(os.path.join(FIXTURES,
                                       "device_profile.expected.json")))
    red = tg.reduce_trace(tg.read_xplane(
        os.path.join(FIXTURES, "device_profile.xplane.pb")), gaps=5)
    assert red["plane"] == "/device:TPU:0"
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert red["program_spans"] == want["program_spans"]
    kernels = {k["kernel"]: k["runs"] for k in red["kernels"]}
    for name, runs in want["kernel_runs"].items():
        assert kernels[name] == runs
    assert len(red["gaps"]) == 5
    for gap, w in zip(red["gaps"], want["gaps"]):
        assert gap["idle_ms"] == pytest.approx(w["idle_ms"], rel=1e-6)
        assert [o["name"] for o in gap["spans_open"]][:3] == w["first_open"]
    # the CLI prints the same reduction
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_gaps.py"),
         os.path.join(FIXTURES, "device_profile.xplane.pb"), "--json"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1])["busy_s"] \
        == pytest.approx(want["busy_s"], rel=1e-6)

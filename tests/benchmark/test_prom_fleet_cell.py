"""`prom-fleet-board` (ISSUE 27): the metric-engine deployment
`prom-metric-engine-1m` as files — dataset, template family,
traffic mix, four metrics on readers that were there — rehearsed end to
end on the CPU, with its control and its broken-path check.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import json
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
    BENCH_DIR, load_json, load_module, make_dataset, manifest, tables)

MAN = manifest()
CELL = "prom-fleet-board"
CONFIG = "prom-metric-engine-1m"
# `metric_scan_ms_per_query` and `metric_scan_amplification` (PR 27)
# left with ISSUE 36: since PR 29 no logical scan runs in a window that
# writes nothing. They return with a writer under this cell.
NEW_METRICS = ["label_sets_parsed_per_query", "promql_dedup_ms_per_query"]
STAGES = ["scan_ms_per_query", "upload_ms_per_query",
          "device_wait_ms_per_query", "assemble_ms_per_query",
          "encode_ms_per_query", "unattributed_ms_per_query",
          "compile_ms_per_query"]    # joined by ISSUE 36
JOINED = ["compiles_per_query", "h2d_bytes_per_query",
          "device_busy_ms_per_query", "device_idle_share",
          "promql_load_hit_share"] + STAGES
PANELS = ["cpu-by-mode", "cpu-system-by-instance", "fs-avail-by-instance",
          "load1"]


def _rehearse(root: str, cell: str, trace: int):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2**31 + 27), "--trace", str(trace), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


@pytest.fixture(scope="module")
def rehearsal():
    return _rehearse(ROOT, CELL, trace=1)


@pytest.fixture(scope="module")
def small():
    conf = load_json("configs", CONFIG + ".json")
    return conf, make_dataset(conf, 5, conf["rehearsal"]["scale"])


def test_the_rehearsal_is_correct_and_compares_every_view(rehearsal, small):
    p = rehearsal
    assert p.returncode == 3, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    _, ds = small
    views = tables(ds)
    assert 200 <= len(views) <= 300
    for v in views:     # every logical table's count(*), twice, == rows
        c = out["compared"][f"rows.{v.table}"]
        assert c["value"] == c["limit"] == v.rows
    for name in PANELS:
        c = out["compared"][name]
        assert c["value"] <= c["limit"] == 1e-10
    recs = {r["record"]: r for r in (
        json.loads(ln) for ln in p.stdout.splitlines()[:-1]
        if ln.startswith('{"record"'))}
    assert recs["setup"]["load"]["tables"] == recs["setup"]["tables"] \
        == recs["checks"]["tables_after_window"] \
        == {v.table: v.rows for v in views}
    assert set(NEW_METRICS + JOINED) <= set(out["metrics"])
    # a steady window parses no label set and sorts nothing on the device
    assert out["metrics"]["label_sets_parsed_per_query"]["value"] == 0.0
    assert out["metrics"]["promql_dedup_ms_per_query"]["value"] == 0.0
    # every selector is resident after warm-up: the window scans no
    # logical table (what `metric_scan_*` read, and why they left)
    assert out["metrics"]["promql_load_hit_share"]["value"] == 100.0
    assert out["metrics"]["scan_ms_per_query"]["value"] > 0.0
    assert out["metrics"]["device_wait_ms_per_query"]["value"] > 0.0


def test_the_configuration_states_its_shapes_and_its_cuts(small):
    conf, ds = small
    fleet = load_module("datasets", conf["dataset"])
    assert 950 <= fleet.SERIES_PER_INSTANCE <= 1050
    assert 200 <= len(fleet.METRICS) <= 300
    per_instance = {v.table: v.series // v.instances for v in tables(ds)}
    assert per_instance["node_cpu_seconds_total"] == 80
    assert per_instance["node_filesystem_avail_bytes"] == 4
    assert per_instance["node_network_receive_bytes_total"] == 4
    assert per_instance["node_load1"] == 1
    assert all("ENGINE=metric" in v.create_sql()
               and "append_mode" not in v.create_sql() for v in tables(ds))
    assert conf["scale"]["step_s"] == 15
    assert conf["source_scale"]["instances"] == 1000
    assert conf["scale"]["instances"] >= 250     # the issue's floor
    assert set(conf["reduced"]) <= {"hours", "instances"}
    assert "setup" not in conf      # the harness's own loader, `bulk`
    # strictly increasing counters, as the accepted configuration's
    cpu = ds.view("node_cpu_seconds_total").fields["greptime_value"]
    assert (np.diff(cpu, axis=0) > 0).all()


@pytest.mark.parametrize("panel", PANELS)
def test_the_float32_control_fails_the_limit(small, panel):
    _, ds = small
    mix = traffic.Mix(CELL, ds)
    entry = next(e for e in mix.entries if e.name == panel)
    rng = np.random.default_rng(27)
    for p in [entry.template.draw(rng, ds) for _ in range(3)] \
            + entry.template.edges(ds):
        assert entry.template.compare(None, p, ds, "float32", lowered=True) \
            > 3 * entry.template.limit("float32")
        # the reference against itself: nothing to find
        names, times, ref = entry.template.reference(p, ds)
        assert np.isfinite(ref).all() and len(times) > 1


def test_a_loader_that_acknowledges_a_sample_it_did_not_write(tmp_path):
    """The deployment's loader (`bulk`), wrapped so that one sample of
    `node_load1` is acknowledged and not written: the read-back of that
    ONE logical table among 283 stops the run; no result line."""
    root = tmp_path / "checkout"
    bench = root / "benchmark"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "loaders").mkdir()
    (bench / "loaders" / "bulk_short.py").write_text(
        "import os, sys\n"
        "sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname("
        "os.path.abspath(__file__)))))\n"
        "from benchmark.harness import bulk_load as bulk\n"
        "put_rows = bulk.put_rows\n"
        "def short(engine, qe, rid, view):\n"
        "    if view.table != 'node_load1':\n"
        "        return put_rows(engine, qe, rid, view)\n"
        "    view.points -= 1\n"
        "    try:\n"
        "        return put_rows(engine, qe, rid, view) + view.series\n"
        "    finally:\n"
        "        view.points += 1\n"
        "bulk.put_rows = short\n"
        "sys.exit(bulk.main())\n")
    conf = load_json("configs", CONFIG + ".json")
    conf["name"] = "fleet-short"
    conf["setup"] = {"loader": "bulk_short"}
    (bench / "configs" / "fleet-short.json").write_text(json.dumps(conf))
    man = json.loads(json.dumps(MAN))
    man["configs"].append({**next(c for c in man["configs"]
                                  if c["name"] == CONFIG),
                           "name": "fleet-short",
                           "file": "benchmark/configs/fleet-short.json"})
    man["workloads"].append({**next(w for w in man["workloads"]
                                    if w["name"] == CELL),
                             "name": "fleet-short", "config": "fleet-short"})
    for m in man["per_layer"]:
        if m["name"] == "compiles_per_query":
            m["workloads"].append("fleet-short")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    os.symlink(os.path.join(ROOT, "greptimedb_tpu"), root / "greptimedb_tpu")
    p = _rehearse(str(root), "fleet-short", trace=0)
    assert p.returncode == 1, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("benchmark run FAILED")
    assert "'node_load1': 357" in last      # 3 instances x 119 points
    assert '"correct"' not in p.stdout


def test_the_manifest_entries_are_additions(small):
    conf, _ = small
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert "node-exporter" in entry["source"] \
        and "with_metric_engine" in entry["source"]
    assert len(entry["source"]) <= 200
    cell = next(w for w in MAN["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    listed = {m["name"] for m in MAN["per_layer"] + MAN["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert set(JOINED + NEW_METRICS) <= listed
    for name in NEW_METRICS:
        m = next(m for m in MAN["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"] and m["moves"] == "queries_per_s"
    # it reports the two end-to-end metrics that list no cells
    assert {m["name"] for m in MAN["end_to_end"]
            if "workloads" not in m} == {"queries_per_s", "setup_s"}


def _recorded_expositions(tmp_path):
    """Two /metrics expositions of the program around a window of
    logical-table scans over a flushed physical region: rendered by the
    server's registry, not written by hand. The label catalog is warm
    before the first, as it is when a window opens."""
    from greptimedb_tpu.catalog import Catalog, MemoryKv
    from greptimedb_tpu.query.engine import QueryEngine
    from greptimedb_tpu.storage import RegionEngine
    from greptimedb_tpu.storage.engine import EngineConfig
    from greptimedb_tpu.utils.metrics import REGISTRY

    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    try:
        qe = QueryEngine(Catalog(MemoryKv()), eng)
        for name in ("up", "load"):
            qe.execute_one(
                f"CREATE TABLE {name} (instance STRING, mode STRING, "
                "ts TIMESTAMP(3) TIME INDEX, "
                "greptime_value DOUBLE, PRIMARY KEY (instance, mode)) "
                "ENGINE=metric")
            qe.execute_one(f"INSERT INTO {name} VALUES " + ", ".join(
                f"('n{i % 4}', 'm{i % 2}', {1000 * (i // 8)}, {i}.5)"
                for i in range(80)))
        eng.flush(qe.catalog.table("public", "up").region_ids[0])
        qe.execute_one("SELECT count(*) FROM up")
        text0 = REGISTRY.render()
        for _ in range(3):
            qe.execute_one("SELECT count(*) FROM up")
        qe.execute_one("SELECT count(*) FROM load WHERE mode = 'm1'")
        return text0, REGISTRY.render()
    finally:
        eng.close()


@pytest.mark.parametrize("shape", ["recorded", "parent"])
def test_the_new_metrics_read_the_program_or_nothing(tmp_path, shape):
    text0, text1 = _recorded_expositions(tmp_path)
    assert "greptimedb_tpu_metric_engine_rows_total" in text1
    if shape == "parent":
        text0, text1 = ("\n".join(
            line for line in t.splitlines()
            if "greptimedb_tpu_metric_engine_" not in line)
            for t in (text0, text1))

    class Ctx:
        m0 = wire.parse_exposition(text0)
        m1 = wire.parse_exposition(text1)
        requests = [type("Answer", (), {"ok": True, "t_done": 1.0})()] * 4
        trace = None

    def read(name):
        spec = load_json("metrics", name + ".json")
        return load_module("readers", spec["reader"]).read(Ctx, spec["args"])

    # every label set was parsed before the window: none inside it, and
    # a program without the counter reads the same 0
    assert read("label_sets_parsed_per_query") == 0.0
    # the program's counters of a logical scan stay (the metrics over
    # them return with a writer under this cell): one flushed file, one
    # row group: each of the four scans reads both tables' 160 rows;
    # three return 80, the matcher scan 40
    rows = "greptimedb_tpu_metric_engine_rows_total"
    delta = {k: wire.metric_sum(Ctx.m1, rows, {"kind": k})
             - wire.metric_sum(Ctx.m0, rows, {"kind": k})
             for k in ("physical_decoded", "logical_returned")}
    assert delta == ({"physical_decoded": 0, "logical_returned": 0}
                     if shape == "parent" else
                     {"physical_decoded": 4 * 160,
                      "logical_returned": 3 * 80 + 40})
    assert not os.path.exists(os.path.join(
        BENCH_DIR, "metrics", "metric_scan_amplification.json"))
    # untraced: nothing; traced without the kernel (a settled region, or
    # a program that has none): a zero, not a hole
    assert read("promql_dedup_ms_per_query") is None
    Ctx.trace = {"busy_s": 1.0, "window_s": 4.0, "kernels": [
        {"kernel": "counter_adjust", "seconds": 0.5, "runs": 10.0}]}
    Ctx.t0, Ctx.seconds = 0.0, 10.0
    assert read("promql_dedup_ms_per_query") == 0.0

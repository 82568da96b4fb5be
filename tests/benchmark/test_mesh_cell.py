"""`tsbs-mesh-4chip` (ISSUE 42): the deployment `tsbs-cpu-only-4000-4dn`
— TSBS's `cpu` table range-partitioned by hostname over four regions,
one region a chip — as files: a configuration that states the layout, a
loader that checks it before it writes a row, a traffic mix over the
family that was there, six metrics (five data files on `prom_delta`, one
reader of a few lines), the cell's rehearsal on the CPU, a table without
the PARTITION clause and a program without the placement each refused at
once, and proof that the deployment came as files.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import traffic  # noqa: E402
from benchmark.harness.common import (  # noqa: E402
    cell_metrics, load_json, load_module, loader_path, make_dataset,
    manifest)

MAN = manifest()
CELL = "tsbs-mesh-4chip"
CONFIG = "tsbs-cpu-only-4000-4dn"
MIX = {"double-groupby-1": (1, 1.0), "double-groupby-5": (1, 0.5),
       "double-groupby-all": (1, 0.5), "groupby-orderby-limit": (1, 1.0),
       "lastpoint": (1, 1.0), "cpu-max-all-8": (2, 1.0),
       "single-groupby-1-1-1": (2, 1.0), "single-groupby-5-8-1": (1, 1.0)}
NEW_METRICS = ["regions_scanned_per_query", "region_pruned_share",
               "region_own_chip_share", "region_combine_ms_per_query",
               "region_fanout_ms_per_query", "chips_in_trace"]


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


# ---- the files ---------------------------------------------------------------


def test_the_configuration_is_the_single_region_ones_row_on_four_regions():
    conf = load_json("configs", CONFIG + ".json")
    base = load_json("configs", "tsbs-cpu-only-4000.json")
    assert conf["schema"] == base["schema"]
    assert conf["dataset"] == base["dataset"] == "tsbs_cpu"
    assert conf["source_scale"] == base["source_scale"]
    assert conf["scale"]["hosts"] == 4000 and conf["scale"]["step_s"] == 10
    assert conf["scale"]["hours"] in (72, 36, 24, 12)
    assert conf["reduced"] == (["hours"] if conf["scale"]["hours"] < 72
                               else [])
    assert conf["setup"] == {"loader": "partitioned"} and conf["chips"] == 4
    assert conf["layout"]["partition_columns"] == ["hostname"]
    assert conf["layout"]["regions"] == 4
    for key, said in base["guarantees"].items():
        if key != "read_back":
            assert conf["guarantees"][key] == said
    assert "four regions" in conf["guarantees"]["read_back"]
    assert "failed request" in conf["guarantees"]["regions"]
    assert "four distinct peer_id" in conf["guarantees"]["layout"]
    assert len(conf["differences"]) >= 3
    assert conf["rehearsal"]["scale"] == {"hosts": 20, "hours": 2,
                                          "step_s": 10}
    ds = make_dataset(conf, 1, conf["rehearsal"]["scale"])
    mix = traffic.Mix(CELL, ds)
    assert mix.clients == 4 and mix.writer_spec is None
    assert {e.name: (e.weight, e.check_share)
            for e in mix.entries} == MIX
    assert loader_path(conf).endswith("benchmark/loaders/partitioned.py")


def test_the_clause_cuts_the_hostnames_into_equal_quarters():
    loader = load_module("loaders", "partitioned")
    names = [f"host_{i}" for i in range(4000)]
    clause = loader.partition_clause("hostname", names, 4)
    assert clause == (
        "PARTITION ON COLUMNS (hostname) (hostname < 'host_1899', "
        "hostname >= 'host_1899' AND hostname < 'host_2799', "
        "hostname >= 'host_2799' AND hostname < 'host_3699', "
        "hostname >= 'host_3699')")
    bounds = ["host_1899", "host_2799", "host_3699"]
    held = [0, 0, 0, 0]
    for n in names:
        held[sum(n >= b for b in bounds)] += 1
    assert held == [1000, 1000, 1000, 1000]
    with pytest.raises(loader.LayoutError, match="cannot fill"):
        loader.partition_clause("hostname", names[:3], 4)


def test_the_manifest_gains_one_configuration_and_one_cell():
    entry = MAN["configs"][-1]
    conf = load_json("configs", CONFIG + ".json")
    assert entry["name"] == CONFIG and entry["source"] == conf["source"]
    assert "Table sharding" in entry["source"] \
        and "PARTITION ON COLUMNS (hostname)" in entry["source"]
    assert len(entry["source"]) <= 200
    assert entry["reduced"] == conf["reduced"]
    cell = MAN["workloads"][-1]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": CELL,
                    "chips": 4, "why": cell["why"]}
    assert "MEAN over the planes" in cell["why"] and len(cell["why"]) <= 200
    assert [w["chips"] for w in MAN["workloads"][:-1]] == [1] * 7
    assert [c["name"] for c in MAN["configs"]].count(CONFIG) == 1
    e2e = {m["name"] for m in cell_metrics(MAN, CELL, "end_to_end")}
    assert e2e == {"queries_per_s", "setup_s"}
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    assert [m["name"] for m in MAN["per_layer"]][-6:] == NEW_METRICS
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "queries_per_s"
    # the cell reports what every TSBS cell reports — but for the two
    # whose list of cells an accepted test holds to the four SQL cells
    # (test_host_thread_metrics.py `SQL`; PERF.md section 7)
    pinned = {"scan_cpu_ms_per_query", "host_agg_cpu_ms_per_query"}
    for m in MAN["per_layer"]:
        if "tsbs-scan-heavy" in m.get("workloads", []):
            assert (m["workloads"][-1] == CELL) == (m["name"] not in pinned)


def test_chips_in_trace_reads_the_planes_that_ran():
    reader = load_module("readers", "trace_planes")
    spec = load_json("metrics", "chips_in_trace.json")
    assert spec["reader"] == "trace_planes"

    class Ctx:
        trace = None

    assert reader.read(Ctx, spec["args"]) is None      # an untraced run
    Ctx.trace = {"window_s": 5.0, "busy_s": 0.1, "planes": 4}
    assert reader.read(Ctx, spec["args"]) == 4.0
    Ctx.trace = {"window_s": 5.0, "busy_s": 0.0}       # a CPU rehearsal
    assert reader.read(Ctx, spec["args"]) == 0.0


# ---- whole runs, rehearsed on the CPU ---------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 42), "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def test_the_rehearsal_is_correct_and_reports_every_metric(rehearsal):
    assert rehearsal.returncode == 3, rehearsal.stderr[-3000:]
    out = last_line(rehearsal.stdout)
    assert out["correct"] is True and out["failed"] == 0
    c = out["compared"]
    for t in MIX:
        assert c[t]["value"] <= c[t]["limit"]
    assert c["rows.cpu"]["value"] == c["rows.cpu"]["limit"] == 20 * 720
    listed = {m["name"] for m in MAN["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == set(out["metrics"]) and set(NEW_METRICS) <= listed
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 1.0 < m["regions_scanned_per_query"] < 4.0
    assert 0.0 < m["region_pruned_share"] < 100.0
    assert m["region_own_chip_share"] == 100.0
    assert m["region_fanout_ms_per_query"] > 0.0
    assert m["region_combine_ms_per_query"] > 0.0
    assert m["partial_cache_hit_share"] > 0.0
    assert m["compiles_per_query"] <= 0.05


def test_the_loader_said_where_the_rows_went(rehearsal):
    line = next(ln for ln in rehearsal.stdout.splitlines()
                if ln.startswith('{"record": "setup"'))
    load = json.loads(line)["load"]
    assert load["tables"] == {"cpu": 20 * 720} and load["rows"] == 20 * 720


# ---- a layout that is not the file's is refused before a row is written ------


def _loader(data_home, env=None, seed=7):
    conf = load_json("configs", CONFIG + ".json")
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, loader_path(conf), "--config", CONFIG, "--scale",
         json.dumps(conf["rehearsal"]["scale"]), "--seed", str(seed),
         "--data-home", str(data_home)],
        cwd=ROOT, env=e, capture_output=True, text=True, timeout=300)


def test_a_table_made_without_the_clause_is_refused(tmp_path):
    """The data home already holds `cpu`, of one region: the loader
    checks it as it stands, says what it found, exits 1, writes no row."""
    conf = load_json("configs", CONFIG + ".json")
    ds = make_dataset(conf, 7, conf["rehearsal"]["scale"])
    made = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]);"
         "from benchmark.harness import bulk_load as b;"
         "e, q = b.standalone(sys.argv[2]); q.execute_one(sys.argv[3]);"
         "q.concurrency.shutdown(); e.close()",
         ROOT, str(tmp_path / "db"), ds.create_sql()],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert made.returncode == 0, made.stderr[-2000:]
    r = _loader(tmp_path / "db")
    assert r.returncode == 1, r.stderr[-2000:]
    assert "table cpu has 1 partition(s), the configuration asks for 4 " \
           "(PARTITION ON COLUMNS (hostname))" in r.stderr
    assert not r.stdout.strip()                 # no JSON line, no rows


def test_a_sound_program_is_loaded_region_by_region(tmp_path):
    r = _loader(tmp_path / "db")
    assert r.returncode == 0, r.stderr[-2000:]
    out = last_line(r.stdout)
    assert out["tables"] == {"cpu": 20 * 720}
    per_region = next(ln for ln in r.stderr.splitlines()
                      if "rows per region" in ln)
    assert json.loads(per_region.split("rows per region ")[1]) == \
        [5 * 720] * 4


def _checkout(tmp_path):
    for name in ("BENCHMARK.json", "benchmark"):
        src = os.path.join(ROOT, name)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(
            src, tmp_path / name)
    os.symlink(os.path.join(ROOT, "greptimedb_tpu"),
               tmp_path / "greptimedb_tpu")


def test_a_program_without_the_placement_is_refused_at_once(tmp_path):
    """The commit before ISSUE 42 names peer 0 for every region
    (`fixtures/peer_zero` answers as it does): the cell's run ends with
    the loader's message and exit 1, no result line, nothing loaded."""
    _checkout(tmp_path)
    env = dict(os.environ, PYTHONPATH=os.path.join(FIXTURES, "peer_zero"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 42), "--rehearse"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 1, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == \
        "benchmark run FAILED: BenchFailure: the loader exited 1"
    assert "the 4 regions of cpu are on peer(s) [0, 0, 0, 0]: this " \
           "program does not place a partitioned table's regions on 4 " \
           "distinct peers (one region a chip), so it does not offer " \
           "the deployment" in r.stderr


PARENT = "1b38efa4f630fdae0ddb950912b10120cad8facd"   # PR 41's commit


def test_the_deployment_came_as_files():
    """Against the commit this PR started from: under benchmark/ and
    tests/benchmark/ git knows only ADDED files — nothing that was there
    is modified, renamed or gone. (Skipped where the checkout has no
    history to ask.)"""
    out = subprocess.run(
        ["git", "diff", "--name-status", PARENT, "--", "benchmark",
         "tests/benchmark"], cwd=ROOT, capture_output=True, text=True)
    if out.returncode != 0:
        pytest.skip("no git history here: " + out.stderr.strip()[:100])
    touched = [ln for ln in out.stdout.splitlines()
               if ln and not ln.startswith("A")]
    assert not touched, touched

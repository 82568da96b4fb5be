"""A deployment is files all the way (ISSUE 26): its set-up route, its
tables and its device kernels are found by name, so a later PR brings a
deployment by ADDING files and manifest entries.

The proof is `fixtures/two_tables/`: a configuration of two tables with
a loader of its own, a dataset, a template family and a traffic mix,
laid into a copy of `benchmark/` — nothing that is there is touched —
and rehearsed end to end on the CPU. Beside it: the default route's
command line, per-table read-back, the trace reduction by kernel name
and by open span, and the `kernel` reader.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import hashlib
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

from benchmark.harness import trace_reduce, wire  # noqa: E402
from benchmark.harness.common import (  # noqa: E402
    BENCH_DIR, load_json, load_module, loader_path, manifest, tables)

MAN = manifest()
CELL = "fixture-two-tables"


# ---- the files-only deployment -----------------------------------------------


def _digests(top: str) -> dict:
    out = {}
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A checkout of the benchmark with the fixture deployment ADDED:
    files into `benchmark/`, entries into `BENCHMARK.json`. Yields
    (root, digests of benchmark/ before anything was added)."""
    root = tmp_path_factory.mktemp("files_only")
    bench = root / "benchmark"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(str(bench))
    src = os.path.join(FIXTURES, "two_tables")
    for rel in _digests(src):
        dst = bench / rel
        assert not dst.exists(), f"{rel} would replace a benchmark file"
        dst.parent.mkdir(exist_ok=True)     # loaders/ comes with the first
        shutil.copy(os.path.join(src, rel), dst)
    man = json.loads(json.dumps(MAN))
    conf = json.load(open(bench / "configs" / f"{CELL}.json"))
    man["configs"].append({
        "name": CELL, "source": conf["source"],
        "file": f"benchmark/configs/{CELL}.json", "reduced": [],
        "why": "two tables and a loader of its own, as files"})
    man["workloads"].append({
        "name": CELL, "config": CELL, "traffic": CELL, "chips": 1,
        "why": "2 closed loops over two tables of one data home"})
    for m in man["per_layer"]:     # its name in the lists: entries, not files
        if m["name"] in ("compiles_per_query", "scan_ms_per_query"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    # the program, where a checkout has it
    os.symlink(os.path.join(ROOT, "greptimedb_tpu"), root / "greptimedb_tpu")
    return root, before


def _rehearse(root, trace: int = 0, **env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 26), "--trace", str(trace), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def _records(stdout: str) -> dict:
    return {r["record"]: r for r in (
        json.loads(ln) for ln in stdout.splitlines()[:-1]
        if ln.startswith('{"record"'))}


def test_a_two_table_deployment_with_its_own_loader_is_files_only(tree):
    root, before = tree
    p = _rehearse(root, trace=1)
    assert p.returncode == 3, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    recs = _records(p.stdout)
    rows = 6 * 360
    assert recs["setup"]["tables"] == {"cpu_a": rows, "cpu_b": rows}
    assert recs["setup"]["load"]["tables"] == {"cpu_a": rows, "cpu_b": rows}
    assert recs["setup"]["rows"] == recs["setup"]["read_back"] == 2 * rows
    assert recs["checks"]["tables_after_window"] == recs["setup"]["tables"]
    # every template of both tables was asked, compared and within
    names = {t["template"]: t for t in recs["checks"]["templates"]}
    assert set(names) == {"a.cpu-max-all-1", "b.cpu-max-all-1",
                          "a.double-groupby-1", "b.lastpoint"}
    assert all(t["compared_answers"] > 0 and t["within"]
               for t in names.values())
    for key in ("rows.cpu_a", "rows.cpu_b", "a.cpu-max-all-1"):
        c = out["compared"][key]
        assert c["value"] <= c["limit"]
    assert list(out)[-1] == "compared"
    assert {"compiles_per_query", "scan_ms_per_query"} <= set(out["metrics"])
    # the proof: nothing the benchmark had was edited to get here
    after = _digests(str(root / "benchmark"))
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 5


@pytest.mark.parametrize("env, says", [
    ({"FIXTURE_LOADER_DROPS_A_ROW": "cpu_b"}, "'cpu_b': 2159"),
    ({"FIXTURE_LOADER_EXITS": "7"}, "the loader exited 7"),
], ids=["acknowledged_row_missing_from_one_table", "loader_exits_non_zero"])
def test_a_loader_that_breaks_its_contract_fails_the_run(tree, env, says):
    """A row acknowledged and not written to ONE of the tables, or a
    loader that exits non-zero: no result line, the reason and the
    loader's stderr on the run's."""
    root, _ = tree
    p = _rehearse(root, **env)
    assert p.returncode == 1
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("benchmark run FAILED") and says in last
    assert '"correct"' not in p.stdout
    if "FIXTURE_LOADER_EXITS" in env:
        assert "told to fail by FIXTURE_LOADER_EXITS" in p.stderr


# ---- the default route, and per-table read-back ------------------------------


@pytest.mark.parametrize("c", MAN["configs"], ids=lambda c: c["name"])
def test_a_configuration_without_setup_gets_the_bulk_loader(c, monkeypatch):
    """Today's route, byte for byte: the command line of the helper is
    what it was before the route had a name."""
    from benchmark import run as bench_run

    conf = load_json("configs", c["name"] + ".json")
    if "setup" in conf:     # a route of its own: a file under loaders/
        assert loader_path(conf) == os.path.join(
            BENCH_DIR, "loaders", conf["setup"]["loader"] + ".py")
        return
    assert isinstance(conf["loader"], str)
    helper = os.path.join(BENCH_DIR, "harness", "bulk_load.py")
    assert loader_path(conf) == loader_path({"setup": {"loader": "bulk"}}) \
        == helper
    assert "harness/bulk_load.py" in conf["loader"]     # the prose stands
    assert not os.path.exists(os.path.join(BENCH_DIR, "loaders",
                                           "bulk.py"))        # one copy
    seen = {}
    monkeypatch.setattr(
        subprocess, "Popen",
        lambda argv, **kw: seen.update(argv=argv, kw=kw))
    bench_run.start_loader(conf, conf["scale"], 2**31 + 1, "/data/home")
    assert seen["argv"] == [
        sys.executable, helper,
        "--config", c["name"], "--scale", json.dumps(conf["scale"]),
        "--seed", str(2**31 + 1), "--data-home", "/data/home/db",
        "--parent", str(os.getpid())]
    assert seen["kw"]["env"]["JAX_PLATFORMS"] == "cpu"
    assert "JAX_COMPILATION_CACHE_DIR" not in seen["kw"]["env"]
    assert seen["kw"]["cwd"] == ROOT


def test_a_loader_that_is_not_there_fails_the_run():
    from benchmark.harness.common import BenchFailure

    with pytest.raises(BenchFailure, match="no loader file"):
        loader_path({"setup": {"loader": "nope"}})


def test_a_dataset_without_tables_is_its_own_view():
    ds = load_module("datasets", "tsbs_cpu").Dataset(
        1, {"hosts": 2, "hours": 1, "step_s": 10})
    assert tables(ds) == [ds]


def test_a_table_a_row_short_after_the_window_makes_correct_false(
        monkeypatch, capsys):
    """The rest of a run with the guarantee broken underneath: the
    count(*) after the window reads one row fewer."""
    from benchmark import run as bench_run

    real, calls = wire.count_rows, []

    def short(client, table):
        calls.append(table)
        return real(client, table) - (len(calls) > 1)

    monkeypatch.setattr(wire, "count_rows", short)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    rc = bench_run.main(["--workload", "tsbs-scan-heavy", "--seed", "26",
                         "--trace", "0", "--rehearse"])
    assert rc == 3 and calls == ["cpu", "cpu"]
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False and out["failed"] == 0
    assert out["compared"]["rows.cpu"] == {"value": 7199.0, "limit": 7200.0}


# ---- device time by kernel, gaps by stage ------------------------------------


@pytest.fixture(scope="module")
def device_profile():
    """fixtures/device_profile.xplane.pb: one second of `prom-board`
    traffic on one TPU v5 lite chip with the host tracer on (PR 24)."""
    tr = trace_reduce.read_xplane(            # imports jax here
        os.path.join(FIXTURES, "device_profile.xplane.pb"))
    with open(os.path.join(FIXTURES, "device_profile.expected.json")) as f:
        return trace_reduce.reduce_trace(tr), json.load(f)


def test_device_profile_reduces_to_its_kernel_runs(device_profile):
    out, want = device_profile
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    kernels = {k["kernel"]: k for k in out["kernels"]}
    for name, runs in want["kernel_runs"].items():
        assert kernels[name]["runs"] == runs
        assert kernels[name]["seconds"] > 0
        # the HLO heads inside it, with their shapes, longest first
        ops = kernels[name]["ops"]
        assert ops and ops[0][0].startswith("%") and "[" in ops[0][0]
        assert [s for _n, s in ops] == sorted((s for _n, s in ops),
                                              reverse=True)
    # the driver's breakdown names kernels, longest first, no HLO head
    names = [n for n, _s in out["device_ops"]]
    assert names[0] == "counter_adjust" and len(names) <= 10
    assert not any(n.startswith(("%", "_fusion")) for n in names)
    secs = [s for _n, s in out["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # every op ran inside a module event here; a module event spans the
    # idle between its ops, so the kernels' seconds are no less
    assert trace_reduce.NO_MODULE not in kernels
    assert sum(k["op_seconds"] for k in out["kernels"]) \
        >= out["busy_s"] - 1e-12
    assert out["hlo_ops"][0][0].startswith("%reduce-window.1 = (f32[20391")


def test_device_profile_gaps_are_named_by_the_spans_open(device_profile):
    out, want = device_profile
    assert len(out["idle_gaps"]) == len(want["gaps"]) == 5
    for (name, idle_s), gap, w in zip(out["idle_gaps"], out["gaps"],
                                      want["gaps"]):
        assert idle_s * 1e3 == pytest.approx(w["idle_ms"], rel=1e-6)
        assert name == "+".join(w["first_open"]) == gap["name"]
        by_time = sorted(gap["open_ms"], key=lambda n: -gap["open_ms"][n])
        assert by_time[:3] == w["first_open"]
    assert out["stage_spans"] > 0


def test_gap_names_fold_by_name_and_name_a_compile_by_its_function():
    spans = [
        {"name": "scan", "line": 0, "start_ns": 0.0, "duration_ns": 600.0},
        {"name": "scan", "line": 1, "start_ns": 300.0, "duration_ns": 100.0},
        {"name": "compile", "line": 0, "start_ns": 400.0,
         "duration_ns": 800.0},
        {"name": "compile", "line": 2, "start_ns": 900.0,
         "duration_ns": 50.0}]
    pjits = {0: [(350.0, 1300.0, "agg_block"), (0.0, 2000.0, "outer")]}
    name, open_ms = trace_reduce.name_gap(200.0, 800.0, spans, pjits)
    # scan 400 + 100 ns in two spans; the compile inside
    # PjitFunction(agg_block) 600 ns; the one with no enclosing call 50
    assert name == "compile(agg_block)+scan+compile"
    assert open_ms == pytest.approx({"scan": 500e-6, "compile": 50e-6,
                                     "compile(agg_block)": 600e-6})
    assert trace_reduce.name_gap(5000.0, 10.0, spans, pjits)[0] \
        == "none_open"


def test_ops_outside_every_module_event_fold_under_no_module():
    planes = [("/device:TPU:0", [("%a", 0.0, 10.0), ("%b", 20.0, 10.0),
                                 ("%c", 100.0, 5.0)])]
    modules = [("/device:TPU:0", [("jit_window_stats(123)", 0.0, 30.0)])]
    k = {k["kernel"]: k for k in trace_reduce.fold_kernels(
        planes, modules, 1)}
    assert k["window_stats"]["seconds"] == pytest.approx(30e-9)
    assert k["window_stats"]["op_seconds"] == pytest.approx(20e-9)
    assert k["window_stats"]["runs"] == 1
    assert k["no_module"]["op_seconds"] == pytest.approx(5e-9)
    assert k["no_module"]["seconds"] == 0 and k["no_module"]["runs"] == 0
    out = trace_reduce.reduce_trace({"planes": planes, "modules": modules,
                                     "spans": [], "pjits": {}})
    assert out["device_ops"] == [["window_stats", pytest.approx(30e-9)],
                                 ["no_module", pytest.approx(5e-9)]]
    assert out["idle_gaps"][0] == ["none_open", pytest.approx(70e-9)]


# ---- the `kernel` reader and its two metrics ---------------------------------


class Ctx:
    def __init__(self, **kw):
        self.notes, self.trace = [], None
        self.__dict__.update(kw)


def _answers(n: int) -> list:
    from benchmark.harness import traffic

    out = []
    for i in range(n):
        r = traffic.Request()
        r.entry, r.params, r.body = None, {}, None
        r.t_send, r.t_done = i * 0.1, i * 0.1 + 0.05
        r.error, r.rows_ok, r.server_ms = None, True, None
        out.append(r)
    return out


def test_kernel_reader():
    kernel = load_module("readers", "kernel")
    trace = {"busy_s": 1.0, "window_s": 4.0, "kernels": [
        {"kernel": "counter_adjust", "seconds": 0.5, "runs": 10.0}]}
    ctx = Ctx(trace=trace, t0=0.0, seconds=10.0, requests=_answers(50))
    args = {"kernel": "counter_adjust"}
    # an eighth of the traced time, 5 answers/s: 25 ms a request
    assert kernel.read(ctx, {**args, "stat": "ms_per_query"}) \
        == pytest.approx(25.0)
    assert kernel.read(ctx, {**args, "stat": "ms_per_run"}) \
        == pytest.approx(50.0)
    assert kernel.read(ctx, {**args, "stat": "runs_per_query"}) \
        == pytest.approx(0.5)
    # traced, and the kernel did not run: a zero, not a hole
    gone = {"kernel": "fused_away"}
    assert kernel.read(ctx, {**gone, "stat": "ms_per_query"}) == 0.0
    assert kernel.read(ctx, {**gone, "stat": "runs_per_query"}) == 0.0
    assert kernel.read(ctx, {**gone, "stat": "ms_per_run"}) is None
    # untraced: nothing to read
    assert kernel.read(Ctx(), {**args, "stat": "ms_per_query"}) is None
    with pytest.raises(KeyError):
        kernel.read(ctx, {**args, "stat": "nope"})


@pytest.mark.parametrize("name, kernel", [
    ("counter_adjust_ms_per_query", "counter_adjust"),
    ("cumsum_ms_per_query", "cumsum")])
def test_kernel_metric_is_data_on_the_kernel_reader(name, kernel):
    entry = dict(next(m for m in MAN["per_layer"] if m["name"] == name))
    cells = entry.pop("workloads")
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "Kernels",
                     "moves": "queries_per_s"}
    assert "prom-board" in cells \
        and set(cells) <= {w["name"] for w in MAN["workloads"]}
    spec = load_json("metrics", name + ".json")
    assert spec["reader"] == "kernel"
    assert spec["args"] == {"kernel": kernel, "stat": "ms_per_query"}
    # a name the program gives a program on the device: its own
    # `kernel_name`, or the jnp function an eager operation runs as
    with open(os.path.join(ROOT, "greptimedb_tpu", "ops", "window.py")) as f:
        src = f.read()
    assert f'@kernel_name("{kernel}")' in src or f"jnp.{kernel}(" in src


def test_peaks_table_is_keyed_by_device_kind_with_its_source():
    peaks = load_json("peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"]["bfloat16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in v5e["source"]
    # a share of a roofline is a `%` on the `roofline` reader, with a
    # cost function beside it (histogram_fold_peak_share is the first)
    shares = [m for m in MAN["per_layer"]
              if m["name"].endswith(("_roofline", "_peak_share"))]
    assert shares
    for m in shares:
        spec = load_json("metrics", m["name"] + ".json")
        assert m["unit"] == "%" and spec["reader"] == "roofline"
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "costs", spec["args"]["cost"] + ".py"))

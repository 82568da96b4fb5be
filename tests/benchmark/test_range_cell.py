"""`range-fleet-board` (ISSUE 44): the deployment `host-cpu-range-4000` —
GreptimeDB's range queries over TSBS's `cpu` row of a 4,000-host fleet
with outages — as files: a configuration, a dataset whose absent rows
are absent, a loader of the present rows, a template family with its
plain reference, a traffic mix, four metrics on the readers that were
there, and the cell's rehearsal on the CPU.

No jax import at module import time.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
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
CELL = "range-fleet-board"
CONFIG = "host-cpu-range-4000"
SEED = 2**31 + 44
LAYER = "Query engine (query/range_select.py)"
MIX = {"range-hosts-1h": (3, 1.0), "range-fleet-by-host-1h": (2, 0.5),
       "range-fleet-by-region-6h": (1, 1.0),
       "range-fleet-total-3h": (1, 1.0), "range-dc-reporting-1h": (1, 1.0)}
NEW_METRICS = ["range_combine_ms_per_query", "range_fill_ms_per_query",
               "range_filled_window_share", "range_windows_per_query"]
SHARED = [
    "wire_ms_per_query", "frontend_ms_per_query", "compiles_per_query",
    "compile_ms_per_query", "partial_cache_hit_share",
    "h2d_bytes_per_query", "device_busy_ms_per_query", "device_idle_share",
    "host_tier_share", "scan_ms_per_query", "host_agg_ms_per_query",
    "upload_ms_per_query", "device_wait_ms_per_query",
    "assemble_ms_per_query", "encode_ms_per_query",
    "unattributed_ms_per_query", "admission_wait_ms_per_query",
    "agg_scan_skipped_share", "agg_program_reuse_share",
    "scan_rows_read_per_row_kept", "request_cpu_ms_per_query",
    "request_on_cpu_share", "background_cpu_ms_per_query",
    "interpreter_lock_wait_ms", "interpreter_lock_free_share",
    "device_wait_cpu_ms_per_query", "encode_cpu_ms_per_query"]


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def conf():
    return load_json("configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def small(conf):
    """(dataset, mix) at the rehearsal's size."""
    ds = make_dataset(conf, SEED, conf["rehearsal"]["scale"])
    return ds, traffic.Mix(CELL, ds)


# ---- the files ---------------------------------------------------------------


def test_the_configuration_is_the_issues(conf, small):
    base = load_json("configs", "tsbs-cpu-only-4000.json")
    assert conf["schema"] == base["schema"]
    assert conf["schema"]["options"] == {"append_mode": "true"}
    assert conf["source_scale"] == base["source_scale"] == {
        "hosts": 4000, "hours": 72, "step_s": 10, "rows": 103680000}
    assert conf["scale"] == {"hosts": 4000, "hours": 12, "step_s": 10}
    assert conf["reduced"] == ["hours"] and "hours" in conf["reduced_why"]
    assert conf["architecture"] is None and conf["chips"] == 1
    assert conf["dataset"] == "tsbs_cpu_outages"
    assert conf["setup"] == {"loader": "present_rows"}
    assert loader_path(conf).endswith("benchmark/loaders/present_rows.py")
    assert "5% of the hosts (200" in conf["assumed"]["outages"] \
        and "1% (40" in conf["assumed"]["outages"] \
        and "ABSENT" in conf["assumed"]["outages"]
    for key, said in base["assumed"]["tag_cardinalities"].items():
        assert conf["assumed"]["tag_cardinalities"][key] == said
    for key, said in base["guarantees"].items():
        if key != "answers":
            assert conf["guarantees"][key] == said
    assert "ABSENT from the answer unless the statement's FILL" in \
        conf["guarantees"]["answers"]
    assert "fill_linear" in conf["differences"] \
        and len(conf["differences"]) >= 4
    assert conf["rehearsal"] == {
        "scale": {"hosts": 20, "hours": 2, "step_s": 10}, "clients": 4,
        "seconds": 3}
    _ds, mix = small
    assert mix.clients == 4 and mix.writer_spec is None
    assert {e.name: (e.weight, e.check_share)
            for e in mix.entries} == MIX


def test_the_manifest_gains_one_configuration_and_one_cell(conf):
    entries = [c for c in MAN["configs"] if c["name"] == CONFIG]
    assert len(entries) == 1 and len(MAN["configs"]) >= 7
    entry = entries[0]
    assert entry["source"] == conf["source"] and len(entry["source"]) <= 200
    assert "reference/sql/range.md" in entry["source"] \
        and "--scale=4000 --log-interval=10s" in entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == conf["reduced"] == ["hours"]
    cells = [w for w in MAN["workloads"] if w["config"] == CONFIG]
    assert cells == [{"name": CELL, "config": CONFIG, "traffic": CELL,
                      "chips": 1, "why": cells[0]["why"]}]
    assert len(cells[0]["why"]) <= 200 and len(MAN["workloads"]) >= 9
    e2e = {m["name"] for m in cell_metrics(MAN, CELL, "end_to_end")}
    assert e2e == {"queries_per_s", "setup_s"}
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["layer"] == LAYER
        assert m["moves"] == "queries_per_s"
        spec = load_json("metrics", name + ".json")
        assert spec["reader"] in ("prom_delta", "prom_hist_delta")
        assert spec["unit"] == m["unit"] and spec["layer"] == LAYER
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
    # the two an accepted test holds to exactly four cells stay as they are
    for name in ("scan_cpu_ms_per_query", "host_agg_cpu_ms_per_query"):
        assert CELL not in by_name[name]["workloads"]
    listed = {m["name"] for m in cell_metrics(MAN, CELL, "per_layer")}
    assert listed == set(NEW_METRICS) | set(SHARED)


# ---- the dataset's outages ---------------------------------------------------


def test_outages_at_the_fleets_size_are_the_files_counts(conf):
    mod = load_module("datasets", conf["dataset"])
    ds = mod.Dataset(SEED, {"hosts": 4000, "hours": 1, "step_s": 10})
    assert len(ds.outages) == 200 and len(ds.late) == 40
    assert not set(ds.outages) & set(ds.late)
    for h, (p0, p1) in ds.outages.items():
        assert 0 <= p0 < p1 <= ds.points
        assert not ds.present[p0:p1, h].any()
        assert ds.present[:p0, h].all() and ds.present[p1:, h].all()
    lengths = [p1 - p0 for p0, p1 in ds.outages.values()]
    assert min(lengths) >= 30 and max(lengths) <= 180   # 5-30 min of 10 s
    for h, p1 in ds.late.items():
        assert 1 <= p1 <= ds.points // 2
        assert not ds.present[:p1, h].any() and ds.present[p1:, h].all()
    absent = ds.points * ds.hosts - ds.rows
    assert ds.rows == int(ds.present.sum()) and 0 < absent < 0.05 * ds.rows
    others = np.ones(ds.hosts, bool)
    others[list(ds.outages) + list(ds.late)] = False
    assert ds.present[:, others].all()


def test_outages_are_a_function_of_the_seed_and_absent_not_null(small, conf):
    ds, _ = small
    mod = load_module("datasets", conf["dataset"])
    again = mod.Dataset(SEED, conf["rehearsal"]["scale"])
    other = mod.Dataset(SEED + 1, conf["rehearsal"]["scale"])
    assert (again.present == ds.present).all() and again.rows == ds.rows
    assert (other.present != ds.present).any()
    assert len(ds.outages) >= 3 and len(ds.late) >= 1
    # the values under an absent row are the sibling's: nothing is NaN
    base = load_module("datasets", "tsbs_cpu").Dataset(
        SEED, conf["rehearsal"]["scale"])
    assert all((ds.fields[f] == base.fields[f]).all() for f in ds.fields)
    assert ds.tag_values == base.tag_values
    n = 0
    for p0, p1, ts, fields, series in ds.slices(3000):
        keep = ds.present[p0:p1].reshape(-1)
        assert len(ts) == len(series) == int(keep.sum())
        for f, v in fields.items():
            assert len(v) == len(ts) and not np.isnan(v).any()
            assert (v == ds.fields[f][p0:p1].reshape(-1)[keep]).all()
        n += len(ts)
    assert n == ds.rows < ds.points * ds.hosts


def test_the_loader_acknowledges_the_present_rows(small, conf, tmp_path):
    ds, _ = small
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, loader_path(conf), "--config", CONFIG, "--scale",
         json.dumps(conf["rehearsal"]["scale"]), "--seed", str(SEED),
         "--data-home", str(tmp_path / "db")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = last_line(r.stdout)
    assert out["tables"] == {"cpu": ds.rows} and out["rows"] == ds.rows
    assert ds.rows < 20 * 720


def test_a_program_that_cannot_plan_a_range_statement_is_refused_at_once(
        conf, tmp_path):
    """The commit before ISSUE 44 raises a PlanError for `EXPLAIN` of any
    RANGE statement (`fixtures/no_range_plan` plans as it does): the
    loader says so and exits 1 before a row is written, and the cell's
    run ends with it — exit 1, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(FIXTURES, "no_range_plan"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, loader_path(conf), "--config", CONFIG, "--scale",
         json.dumps(conf["rehearsal"]["scale"]), "--seed", str(SEED),
         "--data-home", str(tmp_path / "db")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 1, r.stderr[-2000:]
    assert "this program does not offer the deployment" in r.stderr \
        and "neither a group key nor an aggregate" in r.stderr
    assert not r.stdout.strip()                 # no JSON line, no rows
    del env["JAX_PLATFORMS"]
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(SEED), "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert run.returncode == 1, run.stderr[-2000:]
    assert run.stdout.strip().splitlines()[-1] == \
        "benchmark run FAILED: BenchFailure: the loader exited 1"


# ---- the templates' comparison -----------------------------------------------


def _answer(template, params, ds) -> list:
    """The reference's answer as the wire carries it (NULL = None)."""
    keys, vals = template.reference(params, ds)
    rows = []
    for (label, t), v in zip(keys, vals):
        by = [label] if template.by else []
        rows.append([t] + by + [None if np.isnan(x) else float(x)
                                for x in v])
    return rows


@pytest.fixture(scope="module")
def drawn(small):
    """{template name: (template, params with outages in the window,
    the sound answer)}."""
    ds, mix = small
    out = {}
    for e in mix.entries:
        rng = np.random.default_rng([SEED, 3, e.idx])
        for _ in range(200):
            p = e.template.draw(rng, ds)
            _, _, filled = _points(e.template, p, ds)
            if e.template.fill is None or filled.any():
                break
        out[e.name] = (e.template, p, _answer(e.template, p, ds))
    return out


def _points(template, p, ds) -> tuple:
    mod = load_module("templates", "greptime_range")
    return mod.range_reference(
        values=[ds.fields[f] for _, f, _ in template.items],
        funcs=[f for f, _, _ in template.items],
        **template._inputs(p, ds))


@pytest.mark.parametrize("name", list(MIX))
def test_a_sound_answer_compares_as_zero(small, drawn, name):
    ds, _ = small
    t, p, rows = drawn[name]
    assert t.expected_rows(p, ds) == len(rows) > 0
    assert t.compare(rows, p, ds, "float64") == 0.0
    assert t.limit("float64") in (0.0, 1e-12)
    # the statement is the documentation's grammar
    sql = t.sql(p, ds)
    assert " RANGE '" in sql and " ALIGN '" in sql and " BY (" in sql
    assert f"ts >= {p['end'] - t._span(ds)} AND ts < {p['end']}" in sql


@pytest.mark.parametrize("name", list(MIX))
def test_a_dropped_window_is_not_correct(small, drawn, name):
    """A filled window where the statement fills, else an observed one."""
    ds, _ = small
    t, p, rows = drawn[name]
    _, _, filled = _points(t, p, ds)
    order = {k: i for i, k in enumerate(t.reference(p, ds)[0])}
    keys, _, _ = _points(t, p, ds)
    labels = t._labels(ds)
    at = [order[(labels[s], ts)] for (s, ts), f in zip(keys, filled)
          if f == (t.fill is not None)]
    assert at
    cut = [r for i, r in enumerate(rows) if i != at[0]]
    assert t.compare(cut, p, ds, "float64") == float("inf")


@pytest.mark.parametrize("name", ["range-hosts-1h"])
def test_a_prev_from_the_wrong_series_is_not_correct(small, drawn, name):
    ds, _ = small
    t, p, rows = drawn[name]
    keys, _, filled = _points(t, p, ds)
    order = {k: i for i, k in enumerate(t.reference(p, ds)[0])}
    labels = t._labels(ds)
    s, ts = next(k for k, f in zip(keys, filled) if f)
    mine = order[(labels[s], ts)]
    # the same window of another series
    theirs = next(i for i, r in enumerate(rows)
                  if r[0] == ts and r[1] != labels[s]
                  and r[2] is not None)
    wrong = [list(r) for r in rows]
    wrong[mine][2:] = rows[theirs][2:]
    assert t.compare(wrong, p, ds, "float64") > t.limit("float64")
    assert t.compare(wrong, p, ds, "float32") > t.limit("float32")


@pytest.mark.parametrize("name,dtype", [
    (n, d) for n in MIX if n != "range-dc-reporting-1h"
    for d in ("float32", "float64")])
def test_a_lower_precision_is_not_correct(small, drawn, name, dtype):
    """The control: the reference computed one precision below the
    engine's (bfloat16 inputs, float32 sums under float32). A count does
    not depend on it: `range-dc-reporting-1h` is held by `exact` alone."""
    ds, _ = small
    t, p, _rows = drawn[name]
    assert t.compare(None, p, ds, dtype, lowered=True) > 3 * t.limit(dtype)
    assert t.compare(None, p, ds, dtype, lowered=True) > 1e-9


def test_edges_give_the_first_and_the_last_end(small):
    ds, mix = small
    for e in mix.entries:
        ends = [p["end"] for p in e.template.edges(ds)]
        assert ends == [ds.t0_ms + e.template._span(ds), ds.t_end_ms]
        rng = np.random.default_rng(5)
        for _ in range(50):
            end = e.template.draw(rng, ds)["end"]
            assert ends[0] <= end <= ends[1] and end % 10_000 == 0
        assert all(p in mix.warmup(e) for p in e.template.edges(ds))


# ---- the whole run, rehearsed on the CPU -------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(SEED), "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def test_the_rehearsal_is_correct_and_reports_every_metric(rehearsal, small):
    ds, _ = small
    assert rehearsal.returncode == 3, rehearsal.stderr[-3000:]
    out = last_line(rehearsal.stdout)
    assert out["correct"] is True and out["failed"] == 0
    c = out["compared"]
    for t in MIX:
        assert c[t]["value"] <= c[t]["limit"]
    assert c["rows.cpu"]["value"] == c["rows.cpu"]["limit"] == ds.rows
    listed = {m["name"] for m in cell_metrics(MAN, CELL, "per_layer")}
    assert listed == set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["range_filled_window_share"] > 0.0
    assert m["range_combine_ms_per_query"] >= m["range_fill_ms_per_query"] \
        > 0.0
    assert m["range_windows_per_query"] > 60
    assert m["compiles_per_query"] <= 0.05
    steady = [json.loads(ln) for ln in rehearsal.stdout.splitlines()
              if ln.startswith('{"record": "setup"')][0]["warm_up"]
    assert [w["template"] for w in steady] == list(MIX)
    for w in steady:
        assert w["steady"]["path"].endswith("+range_combine")
        assert w["steady"]["tier"] in ("device", "mesh")

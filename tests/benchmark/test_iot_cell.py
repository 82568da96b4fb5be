"""`iot-fleet-board` (ISSUE 38): the deployment `tsbs-iot-4000` as files —
a dataset of two last-write-wins tables with gaps, NULL tags, late
backlogs and resent rows, a loader that writes in arrival order, the
template family with its numpy references — against hand-made small
tables, with both controls, the cell's rehearsal on the CPU, a program
without the mechanism refused at once, a program with its last-write-wins
mask off not correct, and proof that the deployment came as files.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

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
    BENCH_DIR, load_json, load_module, make_dataset, manifest, tables)

MAN = manifest()
CELL = "iot-fleet-board"
CONFIG = "tsbs-iot-4000"
TEMPLATES = ["last-loc", "low-fuel", "high-load", "stationary-trucks",
             "long-driving-sessions", "avg-load"]
NEW_METRICS = ["lww_host_merge_share", "lww_mask_ms_per_query",
               "derived_select_ms_per_query"]

iot = load_module("datasets", "tsbs_iot")
fam = load_module("templates", "tsbs_iot")


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def small():
    conf = load_json("configs", CONFIG + ".json")
    return conf, make_dataset(conf, 5, {"trucks": 200, "hours": 2,
                                        "step_s": 10})


# ---- the references, against hand-made tables -------------------------------


def hand_made():
    """Five trucks, 130 points of 10 s. Truck 0 has no name, truck 1 no
    fleet, truck 4 no driver; rows are missing; truck 2 was offline for
    points 40-60 and sent points 30-60 afterwards (30-39 a second time)."""
    n, p = 5, 130
    rng = np.random.default_rng(38)
    tags = {
        "name": np.asarray([None, "truck_1", "truck_2", "truck_3",
                            "truck_4"], dtype=object),
        "fleet": np.asarray(["East", None, "East", "East", "East"],
                            dtype=object),
        "driver": np.asarray(["Mia", "Mia", "Seth", "Mia", None],
                             dtype=object),
        "model": np.asarray(["H-2", "H-2", "G-2000", "H-2", "F-150"],
                            dtype=object),
        "load_capacity": np.asarray(["1500", "2000", "5000", "1500",
                                     "2000"], dtype=object),
    }
    for t in ("device_version", "fuel_capacity", "nominal_fuel_consumption"):
        tags[t] = np.asarray(["x"] * n, dtype=object)
    ds = SimpleNamespace(
        trucks=n, points=p, step_ms=10_000, hour_points=360,
        t0_ms=iot.T0_MS, t_end_ms=iot.T0_MS + p * 10_000, tags=tags,
        load_capacity=np.asarray([1500.0, 2000.0, 5000.0, 1500.0, 2000.0]),
        offline_mask=np.zeros((p, n), bool),
        resent_mask=np.zeros((p, n), bool))
    ds.offline_mask[40:60, 2] = True
    ds.resent_mask[30:40, 2] = True
    present = rng.random((p, n)) >= 0.1
    present[-1, 3] = False  # truck 3's newest row never arrived
    velocity = rng.uniform(5, 100, (p, n))
    velocity[:, 3] = rng.uniform(0, 0.5, p)     # truck 3 stands
    velocity[60:, 4] = rng.uniform(0, 0.5, p - 60)
    fuel = rng.uniform(0.2, 1.0, (p, n))
    fuel[:, 2] = 0.05
    load = rng.uniform(0.0, 0.8, (p, n)) * ds.load_capacity
    load[:, 4] = 0.95 * 2000.0
    views = {
        "readings": iot._View(ds, "readings", iot.READINGS, {
            **{f: rng.uniform(0, 100, (p, n)) for f in iot.READINGS},
            "velocity": velocity}, present),
        "diagnostics": iot._View(ds, "diagnostics", iot.DIAGNOSTICS, {
            "fuel_state": fuel, "current_load": load,
            "status": np.zeros((p, n))}, present.copy()),
    }
    ds.view = views.__getitem__
    ds.tables = lambda: list(views.values())
    return ds


def rows_of(ds, table: str, twice: bool = False) -> list:
    """The table as a list of rows, one dict each; with `twice` a
    resent row is there two times (what a scan without last-write-wins
    sees)."""
    view = ds.view(table)
    out = []
    for p in range(ds.points):
        for t in range(ds.trucks):
            if not view.present[p, t]:
                continue
            row = {k: v[t] for k, v in ds.tags.items()}
            row["ts"] = ds.t0_ms + p * ds.step_ms
            row.update({f: float(a[p, t]) for f, a in view.fields.items()})
            out += [row] * (2 if twice and view.resent[p, t] else 1)
    return out


def test_the_hand_made_tables_hold_what_the_references_must_treat(small):
    ds = hand_made()
    r = ds.view("readings")
    assert (~r.present).sum() > 20 and r.resent.sum() > 5
    assert r.rows == int(r.present.sum()) \
        and r.rows_written == r.rows + int(r.resent.sum())
    # the newest row of truck 3 is not its last point
    assert r.last_point()[3] == ds.points - 2


def test_the_last_value_references_equal_brute_force():
    ds = hand_made()
    for name, table, col, keep in [
            ("last-loc", "readings", ["latitude", "longitude"],
             lambda r: True),
            ("low-fuel", "diagnostics", ["fuel_state"],
             lambda r: r["fuel_state"] < 0.1),
            ("high-load", "diagnostics", ["current_load"],
             lambda r: r["current_load"] / float(r["load_capacity"]) >= 0.9)]:
        t = fam.make(name)
        newest: dict = {}
        for r in rows_of(ds, table):
            if r["fleet"] != "East" or r["name"] is None:
                continue   # NULL = 'East' is not true; IS NOT NULL drops
            k = (r["name"], r["driver"])
            if k not in newest or r["ts"] >= newest[k]["ts"]:
                newest[k] = r
        want = {k: [r[c] for c in col] for k, r in newest.items() if keep(r)}
        keys, vals = t.reference({"fleet": "East"}, ds)
        assert dict(zip(keys, vals.tolist())) == want, name
    # what they select here: the trucks the tables were made to hold
    assert fam.make("low-fuel").reference({"fleet": "East"}, ds)[0] == [
        ("truck_2", "Seth")]
    assert fam.make("high-load").reference({"fleet": "East"}, ds)[0] == [
        ("truck_4", None)]
    assert len(fam.make("last-loc").reference({"fleet": "East"}, ds)[0]) == 3
    assert fam.make("last-loc").reference({"fleet": "West"}, ds)[0] == []


def test_the_window_references_equal_brute_force():
    ds = hand_made()
    start = ds.t0_ms + 623_457      # points 63-122: truck 4 stands too
    p = {"fleet": "East", "start": start, "end": start + 600_000}
    groups: dict = {}
    for r in rows_of(ds, "readings"):
        if r["fleet"] == "East" and p["start"] <= r["ts"] < p["end"]:
            groups.setdefault((r["name"], r["driver"]), []).append(
                r["velocity"])
    want = {k: np.mean(v) for k, v in groups.items() if np.mean(v) < 1}
    keys, vals = fam.make("stationary-trucks").reference(p, ds)
    assert set(keys) == set(want) == {("truck_3", "Mia"), ("truck_4", None)}
    assert vals[:, 0] == pytest.approx([want[k] for k in keys], rel=1e-13)
    # the two-level aggregate; the window is cut to the 21 min span
    t = fam.make("long-driving-sessions")
    p = {"fleet": "East", "start": ds.t0_ms, "end": ds.t_end_ms}
    inner: dict = {}
    for r in rows_of(ds, "readings"):
        if r["fleet"] == "East":
            inner.setdefault((r["name"], r["driver"], r["ts"] // 600_000),
                             []).append(r["velocity"])
    outer: dict = {}
    for (name, driver, _b), v in inner.items():
        if np.mean(v) > 1:
            outer.setdefault((name, driver), []).append(np.mean(v))
    floor = t._min_buckets(ds)
    assert floor == 22 * 1_300_000 // 14_400_000 == 1
    want = {k: (len(v), np.mean(v)) for k, v in outer.items()
            if len(v) > floor}
    keys, vals = t.reference(p, ds)
    assert set(keys) == set(want) and (None, "Mia") in want  # a NULL group
    assert ("truck_3", "Mia") not in want
    for k, (n, m) in zip(keys, vals.tolist()):
        assert (n, m) == (want[k][0], pytest.approx(want[k][1], rel=1e-13))


def test_avg_load_keeps_a_null_group_and_counts_a_resent_row_once():
    ds = hand_made()
    t = fam.make("avg-load")

    def brute(twice):
        groups: dict = {}
        for r in rows_of(ds, "diagnostics", twice):
            groups.setdefault(
                (r["fleet"], r["model"], r["load_capacity"]), []).append(
                r["current_load"] / float(r["load_capacity"]))
        return {k: np.mean(v) for k, v in groups.items()}

    keys, vals = t.reference({}, ds)
    want = brute(False)
    assert set(keys) == set(want) and (None, "H-2", "2000") in want
    assert vals[:, 0] == pytest.approx([want[k] for k in keys], rel=1e-13)
    # NULL sorts last, as the answer's decoder sorts
    assert keys[-1][0] is None
    # the second control's reference is the scan without last-write-wins
    keys2, vals2 = t.reference({}, ds, "float64", False)
    twice = brute(True)
    assert vals2[:, 0] == pytest.approx([twice[k] for k in keys2], rel=1e-13)
    assert twice[("East", "G-2000", "5000")] != want[("East", "G-2000",
                                                      "5000")]


def test_an_answer_is_held_to_the_set_and_to_the_values():
    ds = hand_made()
    t = fam.make("low-fuel")
    p = {"fleet": "East"}
    keys, vals = t.reference(p, ds)
    sound = [[k[0], k[1], float(v[0])] for k, v in zip(keys, vals)]
    assert t.compare(sound, p, ds, "float64") == 0
    assert t.compare(sound + [["truck_9", "Mia", 0.01]], p, ds,
                     "float64") >= 1                       # one extra
    assert t.compare([], p, ds, "float64") >= 1            # one missing
    assert t.compare([[sound[0][0], sound[0][1], 0.051]], p, ds,
                     "float64") == 1                       # a value
    t = fam.make("avg-load")
    keys, vals = t.reference({}, ds)
    sound = [[*k, float(v[0])] for k, v in zip(keys, vals)]
    assert t.compare(sound, {}, ds, "float32") <= t.limit("float32")
    sound[2][3] *= 1 + 1e-4
    assert t.compare(sound, {}, ds, "float32") > t.limit("float32")


# ---- the controls: both must fail -------------------------------------------


@pytest.mark.parametrize("template", TEMPLATES)
def test_the_lowered_control_fails_the_limit(small, template):
    """The reference one precision below the chip's (bfloat16 under
    float32) is over the template's limit on every draw."""
    _conf, ds = small
    t = fam.make(template)
    rng = np.random.default_rng(38)
    for _ in range(3):
        p = t.draw(rng, ds)
        assert len(t.reference(p, ds)[0]) > 0
        assert t.compare(None, p, ds, "float32", lowered=True) \
            > 2 * t.limit("float32"), (template, p)
        if template == "high-load":
            # its values are whole numbers under 2**24, which float32
            # holds: only the chip's own control (bfloat16) sees them
            continue
        assert t.compare(None, p, ds, "float64", lowered=True) \
            > 2 * t.limit("float64"), (template, p)


def test_the_reference_without_last_write_wins_fails(small):
    """Every resent row counted twice: `rows.*` reads more than the
    table holds, and avg-load leaves its limit."""
    _conf, ds = small
    for view in tables(ds):
        assert view.resent.sum() > 0
        assert view.rows_written > view.rows == int(view.present.sum())
    t = fam.make("avg-load")
    for dtype in ("float32", "float64"):
        assert t.compare(None, {}, ds, dtype, lww=False) > 3 * t.limit(dtype)


def test_a_drawn_window_keeps_its_statistics_off_the_threshold(small):
    _conf, ds = small
    rng = np.random.default_rng(3)
    t = fam.make("stationary-trucks")
    for _ in range(20):
        p = t.draw(rng, ds)
        assert p["end"] - p["start"] == 600_000 and p["start"] % 1000
        _k, avg, _n = t._averages(p, ds, "float64", True)
        assert (np.abs(avg - 1.0) > fam.ROOM).all()
    shares = [len(t.reference(t.draw(rng, ds), ds)[0]) for _ in range(20)]
    assert 0 < np.mean(shares) < 0.5 * ds.trucks / 4


# ---- the dataset and the loader's order --------------------------------------


def test_the_write_order_is_late_where_it_says_and_resends_what_it_says(
        small):
    _conf, ds = small
    view = ds.view("readings")
    seen = np.zeros((ds.points, ds.trucks), np.int64)
    newest = np.full(ds.trucks, -1)
    late = again = 0
    for points, series, resent in view.write_order(1 << 14):
        np.add.at(seen, (points, series), 1)
        again += resent
        for p, s in zip(points.tolist(), series.tolist()):
            late += p < newest[s]
            newest[s] = max(newest[s], p)
    assert ((seen > 0) == view.present).all()       # every row, no other
    assert ((seen == 2) == view.resent).all() and seen.max() == 2
    assert again == view.resent.sum() > 0
    assert late >= (view.present & ds.offline_mask).sum() > 0
    # a tenth of the backlog trucks resend; NULL tags are 1% each
    assert len(ds.backlog_trucks) == 10
    assert (ds.resend_start < ds.backlog_start).sum() == 1
    for tag in ("name", "driver", "model", "fleet"):
        assert sum(v is None for v in ds.tag_values[tag]) == 2
    keys = {tuple(ds.tags[t][i] for t in iot.TAGS) for i in range(ds.trucks)}
    assert len(keys) == ds.trucks                   # the key names a truck


def test_the_configuration_states_its_shapes_and_its_cuts():
    conf = load_json("configs", CONFIG + ".json")
    assert conf["architecture"] is None and conf["reduced"] == ["hours"]
    assert conf["scale"] == {"trucks": 4000, "hours": 12, "step_s": 10}
    assert conf["source_scale"]["hours"] == 72
    assert conf["setup"] == {"loader": "iot_backlog"}
    assert len(conf["differences"]) >= 3 and "LAG" in conf["differences"][0]
    for key in ("null_tags", "gaps", "backlog", "resend", "tag_domains"):
        assert key in conf["assumed"]
    assert "last_write_wins" in conf["guarantees"]
    ds = make_dataset(conf, 1, conf["rehearsal"]["scale"])
    for view in tables(ds):
        sql = view.create_sql()
        assert "append_mode" not in sql
        assert "PRIMARY KEY (" + ", ".join(iot.TAGS) + ")" in sql
    assert [len(v.names) for v in tables(ds)] == [7, 3]
    mix = traffic.Mix(CELL, ds)
    assert mix.clients == 4 and mix.writer_spec is None
    assert {e.name: (e.weight, e.check_share) for e in mix.entries} == {
        "last-loc": (2, 1.0), "low-fuel": (1, 1.0), "high-load": (1, 1.0),
        "stationary-trucks": (2, 1.0), "long-driving-sessions": (1, 1.0),
        "avg-load": (1, 0.5)}


def test_the_manifest_entries_are_additions():
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert "tsbs" in entry["source"] and "--use-case=iot" in entry["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["hours"]
    cell = next(w for w in MAN["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG \
        and cell["traffic"] == CELL and "host" in cell["why"]
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        spec = load_json("metrics", name + ".json")
        assert spec["unit"] == by_name[name]["unit"]
    ingest = next(w for w in MAN["workloads"]
                  if w["name"] == "tsbs-read-under-ingest")
    assert ingest["config"] == "tsbs-cpu-only-4000" and ingest["chips"] == 1
    assert by_name["single_flight_stale_per_window"]["workloads"] == [
        "tsbs-read-under-ingest"]


# ---- whole runs, rehearsed on the CPU ---------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 38), "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def test_the_rehearsal_is_correct_and_compares_every_template(rehearsal):
    assert rehearsal.returncode == 3, rehearsal.stderr[-3000:]
    out = last_line(rehearsal.stdout)
    assert out["correct"] is True and out["failed"] == 0
    c = out["compared"]
    for t in TEMPLATES:
        assert c[t]["value"] <= c[t]["limit"]
    for table in ("readings", "diagnostics"):
        assert c[f"rows.{table}"]["value"] == c[f"rows.{table}"]["limit"] > 0
    listed = {m["name"] for m in MAN["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == set(out["metrics"]) and set(NEW_METRICS) <= listed
    assert 0.0 < out["metrics"]["lww_host_merge_share"]["value"] <= 100.0
    assert out["metrics"]["lww_mask_ms_per_query"]["value"] > 0.0
    assert out["metrics"]["derived_select_ms_per_query"]["value"] > 0.0


def _checkout(tmp_path, program: bool):
    for name in ("BENCHMARK.json", "benchmark"):
        src = os.path.join(ROOT, name)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(
            src, tmp_path / name)
    if program:
        os.symlink(os.path.join(ROOT, "greptimedb_tpu"),
                   tmp_path / "greptimedb_tpu")
    else:
        (tmp_path / "greptimedb_tpu" / "query").mkdir(parents=True)


def test_a_program_without_the_host_merge_is_refused_at_once(tmp_path):
    """The parent commit neither answers this cell nor fails at once
    (PERF.md section 6, PR 38): the dataset refuses such a program
    before anything is loaded; exit 1 in the first seconds, no result."""
    iot.require_lww_merge()     # this program has it
    with pytest.raises(ValueError, match="sorted runs"):
        iot.require_lww_merge(str(tmp_path))
    _checkout(tmp_path, program=False)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "7", "--rehearse"], cwd=tmp_path, capture_output=True, text=True,
        timeout=60)
    assert r.returncode == 1, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1].startswith(
        "benchmark run FAILED: ValueError: tsbs-iot-4000 needs")


def test_a_program_with_its_mask_off_does_not_come_out_correct(tmp_path):
    """`fixtures/lww_off` answers "nothing repeats" for every scan in
    the serving process: count(*) then reads every resent row twice, and
    the run stops at the read-back with no result."""
    _checkout(tmp_path, program=True)
    env = dict(os.environ, PYTHONPATH=os.path.join(FIXTURES, "lww_off"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 38), "--rehearse"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 1, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("benchmark run FAILED: BenchFailure: rows "
                           "acknowledged") and "count(*) reads" in last


PARENT = "22679e8cc7286aaf15ae365a8078cc7b9fcc82d2"   # PR 36's commit


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
    mine = {"benchmark/configs/tsbs-iot-4000.json",
            "benchmark/datasets/tsbs_iot.py",
            "benchmark/loaders/iot_backlog.py",
            "benchmark/templates/tsbs_iot.py",
            "benchmark/traffic/iot-fleet-board.json"}
    assert all(os.path.isfile(os.path.join(ROOT, f)) for f in mine)
    assert os.path.isfile(os.path.join(BENCH_DIR, "harness", "bulk_load.py"))

"""Tier-1 tests of the benchmark's own yardstick (benchmark/): percentile
arithmetic, seeded generators and draws, each template's reference
against a brute-force loop, the controls (a lower-precision answer must
fail its limit), the manifest, the trace reduction, and a rehearsal of a
whole run on the CPU — sound, and with an answer altered underneath.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import stats, traffic, trace_reduce, wire  # noqa: E402
from benchmark.harness.common import (  # noqa: E402
    BENCH_DIR, cell_metrics, load_json, load_module, make_dataset, manifest)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
MAN = manifest()
TSBS_SCALE = {"hosts": 6, "hours": 2, "step_s": 10}
PROM_SCALE = {"instances": 2, "cpus": 1, "modes": 8, "hours": 2,
              "step_s": 15}
TSBS_TEMPLATES = ["single-groupby-1-1-1", "single-groupby-5-8-1",
                  "single-groupby-5-1-12", "single-groupby-1-1-12",
                  "single-groupby-1-8-1", "single-groupby-5-1-1",
                  "cpu-max-all-1", "cpu-max-all-8",
                  "double-groupby-1", "double-groupby-5",
                  "double-groupby-all", "groupby-orderby-limit", "lastpoint"]
PANELS = load_json("traffic", "prom-board.json")["mix"]


def tsbs(seed=11, scale=None):
    return make_dataset({"dataset": "tsbs_cpu"}, seed, scale or TSBS_SCALE)


def prom(seed=11):
    return make_dataset({"dataset": "prom_counter"}, seed, PROM_SCALE)


# ---- percentile arithmetic ---------------------------------------------------


@pytest.mark.parametrize("q", [0.0, 0.25, 0.5, 0.95, 1.0])
def test_percentile_equals_numpy(q):
    xs = np.random.default_rng(5).exponential(size=257).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.quantile(xs, q))


@pytest.mark.parametrize("n,want", [(200, 0.95), (1000, 0.95), (100, 0.90),
                                    (40, 0.75), (20, 0.5), (5, 0.5)])
def test_ten_samples_beyond_rule(n, want):
    assert stats.supported_q(n, 0.95) == pytest.approx(want)
    value, q_eff = stats.tail(list(range(n)), 0.95)
    assert q_eff == pytest.approx(want)
    assert value == pytest.approx(stats.percentile(list(range(n)), want))


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


# ---- generators and draws are functions of the seed only ---------------------


@pytest.mark.parametrize("maker", [tsbs, prom])
def test_dataset_is_a_function_of_the_seed(maker):
    a, b, c = maker(2**31 + 5), maker(2**31 + 5), maker(2**31 + 6)
    for f in a.fields:
        assert np.array_equal(a.fields[f], b.fields[f])
        assert not np.array_equal(a.fields[f], c.fields[f])
    assert a.series_tags() == b.series_tags()
    assert a.rows == a.points * a.series


def test_prom_counters_strictly_increase():
    assert (np.diff(prom().fields["val"], axis=0) > 0).all()


@pytest.mark.parametrize("mix_name,maker", [
    ("tsbs-scan-heavy", tsbs), ("tsbs-point-dash", tsbs),
    ("prom-board", prom)])
def test_draws_depend_on_seed_only_and_never_repeat_warm_up(mix_name, maker):
    ds = maker(3, {"hosts": 50, "hours": 24, "step_s": 10}) \
        if maker is tsbs else maker(3)
    mix = traffic.Mix(mix_name, ds)

    def take(seed, client, n=200):
        s = mix.stream(seed, client)
        return [(e.name, traffic._key(p), c)
                for e, p, c in (next(s) for _ in range(n))]

    assert take(7, 0) == take(7, 0)
    assert take(7, 0) != take(7, 1)
    assert take(7, 0) != take(8, 0)
    # ... but the ORDER of templates is the same whatever the seed
    assert [n for n, _, _ in take(7, 0)] == [n for n, _, _ in take(8, 0)]
    assert [n for n, _, _ in take(7, 0)] != [n for n, _, _ in take(7, 1)]
    # the warm-up stream does not depend on the seed ...
    again = traffic.Mix(mix_name, maker(4, {"hosts": 50, "hours": 24,
                                            "step_s": 10})
                        if maker is tsbs else maker(4))
    for e, e2 in zip(mix.entries, again.entries):
        assert mix.warmup(e) == again.warmup(e2)
    # ... and no window draw of a parameterised template repeats one
    warm = {(e.name, traffic._key(p)) for e in mix.entries
            for p in mix.warmup(e)}
    for seed in (7, 2**31 + 9):
        for name, key, _ in take(seed, 0, 400):
            assert key == "{}" or (name, key) not in warm
    # every template of the mix is drawn, in about its weight
    names = [n for n, _, _ in take(11, 0, 2000)]
    for e, p in zip(mix.entries, mix.p):
        assert abs(names.count(e.name) / 2000 - p) < 0.05


def test_a_clients_first_answer_of_each_template_is_kept():
    mix = traffic.Mix("tsbs-scan-heavy", tsbs())
    s, seen = mix.stream(5, 0), set()
    for _ in range(100):
        e, _p, check = next(s)
        if e.name not in seen:
            assert check
        seen.add(e.name)


# ---- references against brute force ------------------------------------------


def brute_tsbs(t, p, ds):
    """Row-at-a-time loops over the seeded arrays."""
    mod = load_module("templates", "tsbs_devops")
    name = t.name
    if name == "lastpoint":
        return list(range(ds.hosts)), np.asarray(
            [[ds.fields[f][ds.points - 1, h] for f in mod.FIELDS]
             for h in range(ds.hosts)])
    if name == "groupby-orderby-limit":
        best = {}
        for pt in range(ds.points):
            ts = ds.t0_ms + pt * ds.step_ms
            if ts < p["end"]:
                k = ts // 60_000 * 60_000
                for h in range(ds.hosts):
                    v = ds.fields["usage_user"][pt, h]
                    best[k] = max(best.get(k, -math.inf), v)
        keys = sorted(best, reverse=True)[:5]
        return keys, np.asarray([[best[k]] for k in keys])
    if name.startswith("double-groupby"):
        acc = {}
        for pt in range(ds.points):
            ts = ds.t0_ms + pt * ds.step_ms
            if p["start"] <= ts < p["end"]:
                k = ts // 3600_000 * 3600_000
                for h in range(ds.hosts):
                    s = acc.setdefault((k, h), [0, np.zeros(len(t.fields))])
                    s[0] += 1
                    s[1] += [ds.fields[f][pt, h] for f in t.fields]
        keys = sorted(acc, key=lambda kh: (kh[0], f"host_{kh[1]}"))
        return keys, np.asarray([acc[k][1] / acc[k][0] for k in keys])
    best = {}
    for pt in range(ds.points):
        ts = ds.t0_ms + pt * ds.step_ms
        if p["start"] <= ts < p["end"]:
            k = ts // t.bucket_ms * t.bucket_ms
            cur = best.setdefault(k, np.full(len(t.fields), -math.inf))
            for h in p["hosts"]:
                cur[:] = np.maximum(
                    cur, [ds.fields[f][pt, h] for f in t.fields])
    keys = sorted(best)
    return keys, np.asarray([best[k] for k in keys])


@pytest.mark.parametrize("name", TSBS_TEMPLATES)
def test_tsbs_reference_equals_brute_force(name):
    ds = tsbs()
    t = load_module("templates", "tsbs_devops").make(name)
    rng = np.random.default_rng(17)
    for _ in range(3):
        p = t.draw(rng, ds)
        keys, ref = t.reference(p, ds)
        bkeys, bref = brute_tsbs(t, p, ds)
        assert [tuple(k) if isinstance(k, (tuple, list)) else k
                for k in keys] == list(bkeys)
        if name.startswith("double-groupby"):
            np.testing.assert_allclose(ref, bref, rtol=1e-13)
        else:
            assert np.array_equal(ref, bref)
        assert t.expected_rows(p, ds) == len(bkeys)


def brute_promql(t, p, ds):
    """Prometheus' extrapolatedRate / avg_over_time, one series and one
    step at a time, then the aggregation by label."""
    step = ds.step_ms // 1000
    t0 = ds.t0_ms // 1000
    tags = ds.series_tags()
    groups = {}
    times = list(range(p["start"], p["end"] + 1, t.step_s))
    for s in range(ds.series):
        if any(tags[k][s] != v for k, v in t.match.items()):
            continue
        vals = []
        for now in times:
            pts = [(t0 + i * step, ds.fields["val"][i, s])
                   for i in range(ds.points)
                   if now - t.window_s < t0 + i * step <= now]
            if t.fn == "avg_over_time":
                vals.append(sum(v for _, v in pts) / len(pts))
                continue
            (ta, va), (tb, vb) = pts[0], pts[-1]
            delta, sampled = vb - va, float(tb - ta)
            avg_gap = sampled / (len(pts) - 1)
            to_start = float(ta - (now - t.window_s))
            to_end = float(now - tb)
            if delta > 0 and va >= 0:
                to_start = min(to_start, sampled * va / delta)
            ext = sampled
            ext += to_start if to_start < avg_gap * 1.1 else avg_gap / 2
            ext += to_end if to_end < avg_gap * 1.1 else avg_gap / 2
            vals.append(delta * (ext / sampled) / t.window_s)
        groups.setdefault(str(tags[t.by][s]) if t.by else "", []).append(vals)
    names = sorted(groups)
    out = np.asarray([np.sum(groups[n], axis=0) / (
        len(groups[n]) if t.agg == "avg" else 1) for n in names])
    return names, times, out


@pytest.mark.parametrize("panel", PANELS, ids=[p["name"] for p in PANELS])
def test_promql_reference_equals_brute_force(panel):
    ds = prom()
    t = load_module("templates", "promql_board").make("range", panel["args"])
    p = t.draw(np.random.default_rng(3), ds)
    assert (p["end"] - ds.t0_ms // 1000) % t.step_s == 0
    names, times, ref = t.reference(p, ds)
    bnames, btimes, bref = brute_promql(t, p, ds)
    assert names == bnames and times.tolist() == btimes
    np.testing.assert_allclose(ref, bref, rtol=1e-12)
    assert t.expected_rows(p, ds) == len(bnames)
    assert panel["args"]["fn"] in t.query(ds)


# ---- the controls: one precision lower must fail the limit -------------------


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("name", TSBS_TEMPLATES)
def test_tsbs_lower_precision_answer_fails_its_limit(name, dtype):
    """The reference rounded to bfloat16 (under an f32 engine; float32
    under an f64 engine) is NOT within the template's tolerance, and
    the reference at the engine's own precision is."""
    mod = load_module("templates", "tsbs_devops")
    ds = tsbs(23, {"hosts": 40, "hours": 12, "step_s": 10})
    t = mod.make(name)
    p = t.draw(np.random.default_rng(29), ds)
    limit = t.limit(dtype)
    assert t.compare(None, p, ds, dtype, lowered=True) > 3 * limit
    assert t.compare(None, p, ds, dtype, lowered=True) > 0
    keys, ref = t.reference(p, ds, dtype)

    class Same(type(t)):
        def decode(self, rows, params, ds_):
            return keys, np.asarray(ref, np.float64)

    t.__class__ = Same
    assert t.compare(None, p, ds, dtype) <= limit


def test_bfloat16_rounding_is_round_to_nearest_even():
    mod = load_module("templates", "tsbs_devops")
    x = np.asarray([1.0, 1.00390625, 1.01171875, 3.140625, 99.7])
    got = mod.round_to("bfloat16", x)
    assert got.tolist() == [1.0, 1.0, 1.015625, 3.140625, 99.5]


@pytest.mark.parametrize("panel", PANELS, ids=[p["name"] for p in PANELS])
def test_promql_float32_answer_fails_its_limit(panel):
    ds = make_dataset({"dataset": "prom_counter"}, 31,
                      {"instances": 10, "cpus": 4, "modes": 8, "hours": 3,
                       "step_s": 15})
    t = load_module("templates", "promql_board").make("range", panel["args"])
    p = t.draw(np.random.default_rng(37), ds)
    assert t.compare(None, p, ds, "float64", lowered=True) > 3 * t.limit(
        "float64")
    names, times, ref = t.reference(p, ds)
    answer = [{"metric": {t.by: n} if t.by else {},
               "values": [[float(tt), repr(float(v))]
                          for tt, v in zip(times, ref[g])]}
              for g, n in enumerate(names)]
    assert t.compare(answer, p, ds, "float64") <= t.limit("float64")
    answer[0]["values"][3][1] = repr(float(ref[0][3]) * (1 + 1e-6))
    assert t.compare(answer, p, ds, "float64") > t.limit("float64")


# ---- the manifest ------------------------------------------------------------


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) \
        <= max(1, len(MAN["workloads"]) // 2)


@pytest.mark.parametrize("c", MAN["configs"], ids=lambda c: c["name"])
def test_manifest_config(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
    assert 1 <= len(c["why"]) <= 200 and len(c["reduced"]) <= 16
    assert c["file"].startswith("benchmark/configs/")
    with open(os.path.join(ROOT, c["file"])) as f:
        conf = json.load(f)
    assert conf["name"] == c["name"] and conf["source"] == c["source"]
    assert conf["reduced"] == c["reduced"]
    assert set(conf["reduced"]) == set(conf["reduced_why"])
    for key in ("scale", "source_scale", "assumed", "guarantees", "loader",
                "rehearsal", "dataset"):
        assert key in conf
    ds = make_dataset(conf, 1, conf["rehearsal"]["scale"])
    assert ds.table in ds.create_sql()
    assert any(w["config"] == c["name"] for w in MAN["workloads"])


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_manifest_cell(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    conf = load_json("configs", w["config"] + ".json")
    ds = make_dataset(conf, 1, conf["rehearsal"]["scale"])
    mix = traffic.Mix(w["traffic"], ds)      # family and templates exist
    assert mix.clients >= 1 and len(mix.entries) >= 1
    e2e = {m["name"] for m in cell_metrics(MAN, w["name"], "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell_metrics(MAN, w["name"], "per_layer")
    assert layer
    for m in layer:     # what a layer metric moves is reported in its cells
        assert m["moves"] in e2e


@pytest.mark.parametrize("m", MAN["end_to_end"] + MAN["per_layer"],
                         ids=lambda m: m["name"])
def test_manifest_metric(m):
    layer = "layer" in m
    want = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if layer else {"bound"})
    assert want <= set(m) <= want | {"workloads"}
    assert NAME.match(m["name"])
    assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in (("device_trace", "program_span",
                            "program_counter", "host_clock") if layer
                           else ("host_clock", "device_trace"))
    if not layer:
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in MAN["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    spec = load_json("metrics", m["name"] + ".json")
    assert spec["unit"] == m["unit"]
    assert spec["moves"] == m.get("moves")
    assert callable(load_module("readers", spec["reader"]).read)
    if layer:
        assert spec["layer"].startswith(m["layer"])


def test_files_under_paths_are_named_from_a_names_characters():
    for path in MAN["paths"]:
        for base, _dirs, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


# ---- readers -----------------------------------------------------------------


class Ctx:
    def __init__(self, **kw):
        self.notes = []
        self.trace = None
        self.__dict__.update(kw)

    def note(self, msg):
        self.notes.append(msg)


def fake_request(name, t_send, ms, server_ms=None, ok=True):
    r = traffic.Request()
    r.entry = type("E", (), {"name": name})()
    r.t_send, r.t_done = t_send, t_send + ms / 1e3
    r.error, r.rows_ok, r.server_ms = (None if ok else "x"), True, server_ms
    r.params, r.body = {}, None
    return r


def test_prom_readers_read_deltas():
    text0 = ('greptimedb_tpu_xla_compile_total 4\n'
             'greptimedb_tpu_partial_agg_cache_events_total{event="hit"} 10\n'
             'greptimedb_tpu_partial_agg_cache_events_total{event="miss"} 10\n'
             'greptimedb_tpu_query_stage_seconds_sum{stage="parse"} 1.0\n'
             'greptimedb_tpu_query_stage_seconds_count{stage="parse"} 5\n')
    text1 = ('# HELP x\n'
             'greptimedb_tpu_xla_compile_total 10\n'
             'greptimedb_tpu_partial_agg_cache_events_total{event="hit"} 40\n'
             'greptimedb_tpu_partial_agg_cache_events_total{event="miss"} 20\n'
             'greptimedb_tpu_query_stage_seconds_sum{stage="parse"} 1.5\n'
             'greptimedb_tpu_query_stage_seconds_count{stage="parse"} 9\n'
             'greptimedb_tpu_query_stage_seconds_sum{stage="plan"} 0.25\n'
             'greptimedb_tpu_query_stage_seconds_count{stage="plan"} 4\n')
    ctx = Ctx(m0=wire.parse_exposition(text0), m1=wire.parse_exposition(text1),
              requests=[fake_request("a", 0, 1)] * 4)

    def read(name):
        spec = load_json("metrics", name + ".json")
        return load_module("readers", spec["reader"]).read(ctx, spec["args"])

    assert read("compiles_per_query") == pytest.approx(1.5)
    assert read("partial_cache_hit_share") == pytest.approx(75.0)
    assert read("frontend_ms_per_query") == pytest.approx(750.0 / 4)
    assert read("h2d_bytes_per_query") == pytest.approx(0.0)
    ctx.m1 = ctx.m0     # nothing observed in the window: nothing to read
    assert read("frontend_ms_per_query") is None
    assert read("partial_cache_hit_share") is None


def test_client_reader_window_arithmetic():
    reqs = [fake_request("a", i * 0.01, 10 + i, server_ms=4.0)
            for i in range(300)]
    reqs.append(fake_request("double-groupby-all", 9.9, 500))  # ends late
    reqs.append(fake_request("a", 1.0, 5, ok=False))
    ctx = Ctx(requests=reqs, t0=0.0, seconds=10.0, setup_s=12.5)
    client = load_module("readers", "client")
    assert client.read(ctx, {"stat": "setup_s"}) == 12.5
    # correct answers completed INSIDE the window over its length
    assert client.read(ctx, {"stat": "rate"}) == pytest.approx(30.0)
    ok_ms = [r.ms for r in reqs if r.ok]
    assert client.read(ctx, {"stat": "p50"}) == pytest.approx(
        np.quantile(ok_ms, 0.5))
    assert client.read(ctx, {"stat": "p95"}) == pytest.approx(
        np.quantile(ok_ms, 0.95))
    assert client.read(ctx, {"stat": "p50", "template":
                             "double-groupby-all"}) == pytest.approx(500.0)
    assert client.read(ctx, {"stat": "p50", "template": "nope"}) is None
    assert client.read(ctx, {"stat": "wire_ms"}) == pytest.approx(
        np.median([r.ms - 4.0 for r in reqs if r.ok and r.server_ms]))
    assert not any("ten samples beyond" in n for n in ctx.notes)
    few = Ctx(requests=reqs[:50], t0=0.0, seconds=10.0, setup_s=1.0)
    assert client.read(few, {"stat": "p95"}) == pytest.approx(
        np.quantile([r.ms for r in reqs[:50]], 0.8))
    assert any("ten samples beyond" in n for n in few.notes)


def test_trace_reader():
    trace = load_module("readers", "trace")
    ctx = Ctx(trace={"busy_s": 1.0, "window_s": 4.0}, t0=0.0, seconds=10.0,
              requests=[fake_request("a", i * 0.1, 50) for i in range(50)])
    assert trace.read(ctx, {"stat": "idle_share"}) == pytest.approx(75.0)
    # a quarter of the time busy, 5 answers/s: 50 ms of device per answer
    assert trace.read(ctx, {"stat": "busy_ms_per_query"}) == pytest.approx(50)
    assert trace.read(Ctx(), {"stat": "idle_share"}) is None


# ---- trace reduction ---------------------------------------------------------


def test_union_of_overlapping_intervals():
    total, merged = trace_reduce.union([(0, 10), (5, 12), (20, 30), (21, 22)])
    assert total == 22 and merged == [[0, 12], [20, 30]]


def test_reduce_planes_busy_idle_top_ops_and_gaps():
    planes = [("/device:TPU:0", [("fusion.1", 0.0, 2e9), ("fusion.2", 1e9, 2e9),
                                 ("copy", 6e9, 1e9), ("fusion.1", 9e9, 1e9)])]
    out = trace_reduce.reduce_planes(planes)
    assert out["busy_s"] == pytest.approx(5.0)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["hlo_ops"][0] == ["fusion.1", pytest.approx(3.0)]
    # the longest gap: (where it starts, how long), both in ns
    assert out["gaps"][0] == (pytest.approx(3e9), pytest.approx(3e9))
    wider = trace_reduce.reduce_planes(planes, window_ns=20e9)
    assert wider["window_s"] == pytest.approx(20.0)
    assert trace_reduce.reduce_planes([("/device:TPU:0", [])])["busy_s"] == 0


def test_trace_reduce_on_a_trace_recorded_on_the_chip():
    """fixtures/chip_trace.xplane.pb: recorded on a TPU v5 lite in this
    benchmark's first traced run (PR 23), trimmed to under 1 MB."""
    path = os.path.join(FIXTURES, "chip_trace.xplane.pb")
    assert os.path.getsize(path) <= 1 << 20
    tr = trace_reduce.read_xplane(path)   # imports jax here
    planes, seen = tr["planes"], tr["seen"]
    assert any(s["plane"].startswith("/device:TPU") for s in seen)
    out = trace_reduce.reduce_trace(tr)
    with open(os.path.join(FIXTURES, "chip_trace.expected.json")) as f:
        want = json.load(f)
    assert out["planes"] == want["planes"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert [n for n, _ in out["hlo_ops"]] == \
        [n for n, _ in want["device_ops"]]
    # recorded with the host tracer off (PR 23): kernels by name all the
    # same, no span to name a gap by
    assert [n for n, _ in out["device_ops"]] == ["_agg_block",
                                                 "convert_element_type"]
    assert {n for n, _ in out["idle_gaps"]} == {"none_open"}
    # busy is a union: never more than the sum of the ops' own times
    total = sum(d for _p, evs in planes for _n, _s, d in evs) / 1e9
    assert out["busy_s"] <= total / out["planes"] + 1e-12


# ---- a whole run, rehearsed on the CPU ---------------------------------------


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_rehearsal_runs_every_phase_and_exits_3():
    """tsbs-scan-heavy at the config's rehearsal sizes: 20 hosts x 1 h,
    4 clients, a 3 s window."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "tsbs-scan-heavy", "--seed", str(2**31 + 77), "--trace", "0",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 3, p.stderr[-3000:]
    out = last_line(p.stdout)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert out["correct"] is True and out["failed"] == 0
    for c in out["compared"].values():      # each number beside its limit
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    assert out["compared"]["rows.cpu"] == {"value": 7200.0, "limit": 7200.0}
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    want = {m["name"] for m in cell_metrics(MAN, "tsbs-scan-heavy",
                                            "end_to_end")}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    records = [json.loads(ln) for ln in p.stdout.splitlines()[:-1]
               if ln.startswith("{")]
    kinds = [r["record"] for r in records]
    assert kinds == ["cell", "setup", "checks", "window"]
    setup = records[1]
    assert setup["read_back"] == setup["rows"] == 20 * 360
    assert setup["tables"] == setup["load"]["tables"] == {"cpu": 20 * 360}
    assert records[2]["read_back_after_window"] == 20 * 360
    assert records[2]["tables_after_window"] == {"cpu": 20 * 360}
    assert 0 <= records[3]["generator_share"] < 0.5


def test_an_altered_answer_makes_correct_false():
    """The rest of a run with the timed path broken underneath: one
    value of every max() answer is altered where the client receives
    it (`fixtures/faulty_run.py altered_max`), and `correct` comes out
    false by those templates' numbers and no other. A process of its
    own, as the driver runs a cell: until PR 36 this ran inside the
    pytest worker, whose signal handlers, alarm, sub-reaper flag and
    descendants a run's `procs.Guard` then took for its own."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(FIXTURES, "faulty_run.py"),
         "altered_max", "--workload", "tsbs-scan-heavy", "--seed", "5",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 3, p.stderr[-3000:]
    out = last_line(p.stdout)
    assert out["correct"] is False and out["attempted"] > 0
    assert out["failed"] == 0
    past = {k for k, c in out["compared"].items()
            if c["value"] > c["limit"]}
    assert past == {"cpu-max-all-8", "groupby-orderby-limit"}
    assert "writer" not in out


def test_only_the_windows_own_cache_entries_are_pruned(tmp_path, monkeypatch):
    """Names that were there when set-up ended stay, and so does a new
    name whose mtime is older than the window (another process's)."""
    import time

    from benchmark import run as bench_run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    (tmp_path / "warm-up-cache").write_bytes(b"x")
    before, since = bench_run.cache_listing(), time.time()
    (tmp_path / "window-cache").write_bytes(b"x")
    (tmp_path / "elsewhere-cache").write_bytes(b"x")
    os.utime(tmp_path / "elsewhere-cache", (since - 3600, since - 3600))
    (tmp_path / "warm-up-cache").write_bytes(b"touched in the window")
    assert bench_run.prune_window_entries(before, since) == 1
    assert bench_run.cache_listing() == {"warm-up-cache", "elsewhere-cache"}


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` the run exits non-zero and prints no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tsbs-scan-heavy",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode not in (0, 3)
    assert not p.stdout.strip().splitlines()[-1].startswith('{"correct"')


# ---- every process a run starts ends with it ---------------------------------


def _state(pid: int):
    """The kernel's state letter of `pid`, or None when it is gone."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            return f.read().rsplit(b")", 1)[1].split()[0].decode()
    except OSError:
        return None


_GUARDED = """
import os, subprocess, sys, time
sys.path.insert(0, {root!r})
from benchmark.harness import procs
procs.LIMIT_WHOLE_S = {limit}
try:
    with procs.Guard() as g:
        # a child that starts a grandchild and dies: an orphan
        p = subprocess.Popen([sys.executable, "-c",
            "import subprocess, os; "
            "print(subprocess.Popen(['sleep', '300']).pid, flush=True); "
            "os._exit(0)"], stdout=subprocess.PIPE)
        print(int(p.stdout.readline()), flush=True)
        p.wait()
        time.sleep({sleep})
except procs.Stopped as e:
    print(f"stopped: {{e}}", flush=True)
print("left:", procs.children_of(os.getpid()), "killed:", g.killed,
      flush=True)
"""


@pytest.mark.parametrize("limit, sleep, how", [
    (60, 0, None), (1, 30, "deadline passed")])
def test_guard_ends_orphans_and_keeps_the_deadline(limit, sleep, how):
    """The harness is its descendants' sub-reaper: a grandchild whose
    parent died is killed and waited for on the way out, also when the
    way out is the deadline."""
    p = subprocess.run(
        [sys.executable, "-c",
         _GUARDED.format(root=ROOT, limit=limit, sleep=sleep)],
        capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    orphan = int(lines[0])
    assert _state(orphan) is None
    assert lines[-1] == f"left: [] killed: [{orphan}]"
    assert (lines[1] == f"stopped: {how}") if how else len(lines) == 2


@pytest.mark.parametrize("sig", ["SIGTERM", "SIGKILL"])
def test_a_run_that_is_stopped_leaves_no_process(sig, tmp_path):
    """A rehearsal killed while its server is up: on SIGTERM it leaves
    through its clean-up, prints no result and exits 1; on SIGKILL the
    kernel kills what it started. Either way nothing is left."""
    import signal
    import time

    from benchmark.harness import procs

    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["TMPDIR"] = str(tmp_path)   # a killed run cannot remove its data
    p = subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "prom-board", "--seed", "12", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        # until the serving process answers (the `setup` record follows
        # the warm-up; the line before it is the `cell` record)
        assert p.stdout.readline().startswith('{"record": "cell"')
        t_end = time.monotonic() + 300
        server = []
        while not server and time.monotonic() < t_end:
            for pid in procs.children_of(p.pid):
                try:
                    with open(f"/proc/{pid}/cmdline", "rb") as f:
                        if b"serve.py" in f.read():
                            server.append(pid)
                except OSError:
                    pass
            time.sleep(0.2)
        assert server and p.poll() is None
        time.sleep(1.0)
        started = procs.children_of(p.pid)
        p.send_signal(getattr(signal, sig))
        out, _err = p.communicate(timeout=120)
    finally:
        p.kill()
        p.wait()
    assert p.returncode == (1 if sig == "SIGTERM" else -signal.SIGKILL)
    assert '"correct"' not in out
    if sig == "SIGTERM":
        assert "benchmark run FAILED: Stopped: stopped by SIGTERM" in out
        assert [_state(pid) for pid in started] == [None] * len(started)
    else:
        # orphans now: gone, or dead and waiting for init to reap them
        t_end = time.monotonic() + 30
        while time.monotonic() < t_end and any(
                _state(pid) not in (None, "Z") for pid in started):
            time.sleep(0.1)
        assert all(_state(pid) in (None, "Z") for pid in started)

"""histogram_quantile as one kernel (ISSUE 32): the engine against the
benchmark's plain numpy `bucket_quantile` (Prometheus' bucketQuantile
written from its description, benchmark/templates/prom_slo.py) on
seeded random classic histograms at a small size on the CPU — ragged
groups, a group without `+Inf`, non-monotone buckets, zero totals,
negative bounds, every φ rule, instant and range — the fold index's
life (once per input label sets and data version), and the load that
goes to the device in blocks of whole series.
"""

import contextlib
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.common import load_module  # noqa: E402
from greptimedb_tpu.catalog import Catalog, MemoryKv  # noqa: E402
from greptimedb_tpu.ops.histogram import histogram_fold  # noqa: E402
from greptimedb_tpu.promql import engine as promql_engine  # noqa: E402
from greptimedb_tpu.promql.engine import PromqlEngine, SeriesMatrix  # noqa: E402
from greptimedb_tpu.query import QueryEngine  # noqa: E402
from greptimedb_tpu.storage import RegionEngine  # noqa: E402
from greptimedb_tpu.storage.engine import EngineConfig  # noqa: E402
from greptimedb_tpu.utils import tracing  # noqa: E402
from greptimedb_tpu.utils.metrics import (  # noqa: E402
    PROMQL_HISTOGRAM_FOLD_SECONDS,
    PROMQL_HISTOGRAM_FOLDS,
    PROMQL_LOAD_CACHE_EVENTS,
)

bucket_quantile = load_module("templates", "prom_slo").bucket_quantile

T0 = 2_000_000  # epoch seconds of the first sample
STEP = 15
POINTS = 8
PHIS = [0.0, 0.5, 0.99, 1.0, -1.0, 2.0, math.nan]

#: group -> [(le as written, cumulative count per point)]: what each
#: edge rule needs, beside three seeded ordinary groups
def _histograms(seed: int) -> dict:
    rng = np.random.default_rng(seed)

    def cumulative(bounds):
        steps = rng.integers(0, 40, (len(bounds), POINTS)).astype(float)
        return list(zip(bounds, np.cumsum(np.cumsum(steps, axis=0),
                                          axis=1)))

    finite = ["0.005", "0.01", "0.025", "0.05", "0.1", "0.25", "0.5", "1",
              "2.5", "5", "10"]
    out = {f"seeded{i}": cumulative(finite + ["+Inf"]) for i in range(3)}
    out["ragged"] = cumulative(["0.1", "1", "+Inf"])          # 3 of 12
    out["two"] = cumulative(["0.5", "+Inf"])
    out["no_inf"] = cumulative(["0.1", "1", "10"])
    out["one_bucket"] = cumulative(["+Inf"])
    out["negative"] = cumulative(["-2", "-0.5", "0", "3", "+Inf"])
    out["zero_total"] = [(le, np.zeros(POINTS))
                         for le in ("0.1", "1", "+Inf")]
    # a scrape that caught the histogram between two increments
    wobble = cumulative(["0.1", "0.5", "1", "5", "+Inf"])
    wobble[2] = (wobble[2][0], wobble[2][1] - 7.0)
    out["non_monotone"] = wobble
    # the first bucket empty: phi = 0 meets 0 / 0
    empty_first = cumulative(["0.1", "1", "+Inf"])
    empty_first[0] = (empty_first[0][0], np.zeros(POINTS))
    out["empty_first"] = empty_first
    return out


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    eng = RegionEngine(EngineConfig(
        data_dir=str(tmp_path_factory.mktemp("hist") / "data")))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    qe.execute_one(
        "CREATE TABLE lat_bucket (grp STRING, le STRING, "
        "ts TIMESTAMP(3) NOT NULL, val DOUBLE, TIME INDEX (ts), "
        "PRIMARY KEY (grp, le)) WITH (append_mode = 'true')")
    hist = _histograms(32)
    rows = [f"('{g}', '{le}', {(T0 + i * STEP) * 1000}, {float(c)})"
            for g, buckets in hist.items() for le, counts in buckets
            for i, c in enumerate(counts)]
    # a bucket label that is no number, and a series that is no bucket
    rows += [f"('seeded0', 'fast', {(T0 + i * STEP) * 1000}, 1.0)"
             for i in range(POINTS)]
    qe.execute_one("INSERT INTO lat_bucket (grp, le, ts, val) VALUES "
                   + ", ".join(rows))
    yield qe, hist
    eng.close()


def _expected(hist: dict, phi: float, point: int) -> dict:
    return {g: float(bucket_quantile(
        [float(le) for le, _ in buckets],
        [counts[point] for _, counts in buckets], phi))
        for g, buckets in hist.items()}


def _same(got: float, want: float) -> bool:
    if math.isnan(want) or math.isinf(want):
        return (math.isnan(got) and math.isnan(want)) or got == want
    return got == pytest.approx(want, rel=1e-12, abs=1e-300)


@pytest.mark.parametrize("phi", PHIS, ids=[f"phi={p}" for p in PHIS])
def test_instant_quantile_equals_the_plain_reference(db, phi):
    qe, hist = db
    point = POINTS - 2
    text = "NaN" if math.isnan(phi) else repr(phi)
    _, sm = PromqlEngine(qe).eval_instant(
        f"histogram_quantile({text}, lat_bucket)", T0 + point * STEP)
    assert isinstance(sm, SeriesMatrix)
    got = {lab["grp"]: float(v) for lab, v in zip(
        sm.labels, np.asarray(sm.values)[:, 0])}
    want = _expected(hist, phi, point)
    assert sorted(got) == sorted(want)      # every group answers, le gone
    assert all("le" not in lab for lab in sm.labels)
    for g in want:
        assert _same(got[g], want[g]), (g, phi, got[g], want[g])
    if 0 <= phi <= 1:       # the edge rules, each seen
        assert math.isnan(got["no_inf"]) and math.isnan(got["one_bucket"])
        assert math.isnan(got["zero_total"])
        assert math.isfinite(got["ragged"]) and math.isfinite(got["two"])
    if phi == 0.0:
        assert got["negative"] == -2.0      # a first bucket with le <= 0
        assert got["empty_first"] == 0.0    # 0 / 0 reads the lower bound
    if phi == 1.0:
        assert got["two"] == 0.5            # +Inf: the highest finite bound


@pytest.mark.parametrize("phi", [0.5, 0.99])
def test_range_quantile_of_summed_rates_equals_the_reference(db, phi):
    """The board's shape: histogram_quantile over sum by (le, grp) of
    rate, every step against the reference over the same rates."""
    qe, hist = db
    prom = PromqlEngine(qe)
    start, end = T0 + 4 * STEP, T0 + (POINTS - 1) * STEP
    inner = "sum by (le, grp) (rate(lat_bucket[60s]))"
    _, rates = prom.eval_matrix(inner, start, end, STEP)
    times, sm = prom.eval_matrix(f"histogram_quantile({phi}, {inner})",
                                 start, end, STEP)
    rate_of = {(lab["grp"], lab["le"]): np.asarray(rates.values)[i]
               for i, lab in enumerate(rates.labels)}
    got = {lab["grp"]: np.asarray(sm.values)[i]
           for i, lab in enumerate(sm.labels)}
    assert len(times) == 4 and sorted(got) == sorted(hist)
    for g, buckets in hist.items():
        want = bucket_quantile(
            [float(le) for le, _ in buckets],
            np.stack([rate_of[(g, le)] for le, _ in buckets]), phi)
        for a, b in zip(got[g], want):
            assert _same(float(a), float(b)), (g, got[g], want)


def test_the_kernel_pads_groups_and_buckets_and_takes_phi_as_an_operand():
    """Ragged groups padded to the widest and masked, G padded to a
    power of two, one executable for every φ and every bound."""
    rng = np.random.default_rng(5)
    G, B, T = 8, 5, 6
    bounds = np.zeros((G, B))
    valid = np.zeros((G, B), bool)
    counts = np.zeros((G, B, T))
    want = {}
    for g in range(5):      # three groups of padding behind them
        n = int(rng.integers(2, B + 1))
        le = np.sort(rng.uniform(0.01, 10.0, n - 1)).tolist() + [math.inf]
        c = np.cumsum(rng.integers(0, 30, (n, T)).astype(float), axis=0)
        bounds[g, :n], valid[g, :n], counts[g, :n] = le, True, c
        counts[g, n:] = 1e9     # what padding holds must not matter
        want[g] = (le, c)
    histogram_fold(counts, bounds, valid, np.float64(0.25))
    before = histogram_fold._cache_size()
    for phi in (0.1, 0.5, 0.9):
        out = np.asarray(histogram_fold(counts, bounds * 1.5, valid,
                                        np.float64(phi)))
        for g, (le, c) in want.items():
            np.testing.assert_allclose(
                out[g], bucket_quantile(np.asarray(le) * 1.5, c, phi),
                rtol=1e-12)
        assert np.isnan(out[5:]).all()      # no bucket, no answer
    assert histogram_fold._cache_size() == before


@contextlib.contextmanager
def _spans():
    """Yields a function that lists the spans recorded under a trace of
    its own, oldest first."""
    tid = tracing.set_trace()
    try:
        yield lambda: sorted(tracing.spans_for(tid), key=lambda s: s.started_at)
    finally:
        tracing.restore_trace(None)


def _observations() -> dict:
    """Observations of the fold's histogram so far, by phase."""
    return {phase: PROMQL_HISTOGRAM_FOLD_SECONDS.count(phase=phase)
            for phase in ("index", "dispatch")}


def _fold_counts() -> dict:
    return {how: PROMQL_HISTOGRAM_FOLDS.get(index=how)
            for how in ("hit", "build")}


def test_the_fold_index_is_built_once_per_data_version(tmp_path):
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data")))
    try:
        qe = QueryEngine(Catalog(MemoryKv()), eng)
        qe.execute_one(
            "CREATE TABLE req_bucket (pod STRING, le STRING, "
            "ts TIMESTAMP(3) NOT NULL, val DOUBLE, TIME INDEX (ts), "
            "PRIMARY KEY (pod, le)) WITH (append_mode = 'true')")

        def write(point: int) -> None:
            qe.execute_one(
                "INSERT INTO req_bucket (pod, le, ts, val) VALUES "
                + ", ".join(
                    f"('{pod}', '{le}', {(T0 + point * STEP) * 1000}, "
                    f"{float((point + 1) * (k + 1) * 10)})"
                    for pod in ("a", "b")
                    for k, le in enumerate(("0.1", "1", "+Inf"))))

        for point in range(6):
            write(point)
        prom = PromqlEngine(qe)
        q = "histogram_quantile(0.9, sum by (le, pod) (rate(req_bucket[60s])))"
        args = (q, T0 + 4 * STEP, T0 + 5 * STEP, STEP)
        n0 = _fold_counts()
        seconds0 = _observations()
        with _spans() as spans:
            first = np.asarray(prom.eval_matrix(*args)[1].values)
            again = np.asarray(prom.eval_matrix(*args)[1].values)
        n1 = _fold_counts()
        assert (n1["build"] - n0["build"], n1["hit"] - n0["hit"]) == (1, 1)
        np.testing.assert_array_equal(first, again)
        folds = [s for s in spans() if s.name == "histogram_fold"]
        assert [s.attrs["index"] for s in folds] == ["build", "hit"]
        assert all(s.attrs["groups"] == 2 and s.attrs["buckets"] == 3
                   and s.attrs["steps"] == 2 and s.attrs["skipped"] == 0
                   for s in folds)
        seconds1 = _observations()
        for phase in ("index", "dispatch"):
            assert seconds1[phase] - seconds0[phase] == 2
        # another phi over the same input: the same index
        prom.eval_matrix(q.replace("0.9", "0.5"), *args[1:])
        assert _fold_counts()["hit"] - n1["hit"] == 1
        # a write is a new data version: its label sets are new ones
        write(6)
        prom.eval_matrix(*args)
        n2 = _fold_counts()
        assert n2["build"] - n1["build"] == 1
    finally:
        eng.close()


def test_a_bucket_label_that_is_no_number_is_counted_on_the_span(db):
    qe, _ = db
    with _spans() as spans:
        PromqlEngine(qe).eval_instant(
            "histogram_quantile(0.5, lat_bucket)", T0 + 3 * STEP)
    (fold,) = [s for s in spans() if s.name == "histogram_fold"]
    assert fold.attrs["skipped"] == 1       # le="fast"
    assert fold.attrs["buckets"] == 12 and fold.attrs["steps"] == 1


def test_a_sorted_load_goes_to_the_device_in_blocks_of_whole_series(
        tmp_path, monkeypatch):
    """The same samples, channels and answers whether a selector's load
    is one block or many (ISSUE 32: a 38.4M-sample selector loads
    beside what is resident)."""
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data")))
    try:
        qe = QueryEngine(Catalog(MemoryKv()), eng)
        qe.execute_one(
            "CREATE TABLE ctr (host STRING, ts TIMESTAMP(3) NOT NULL, "
            "val DOUBLE, TIME INDEX (ts), PRIMARY KEY (host)) "
            "WITH (append_mode = 'true')")
        rng = np.random.default_rng(9)
        rows = []
        for h in range(7):
            v = np.cumsum(rng.integers(1, 50, 12)).astype(float)
            v[6:] -= v[5] if h == 3 else 0.0     # one counter resets
            rows += [f"('h{h}', {(T0 + i * STEP) * 1000}, {float(x)})"
                     for i, x in enumerate(v)]
        qe.execute_one("INSERT INTO ctr (host, ts, val) VALUES "
                       + ", ".join(rows))
        eng.flush(qe.catalog.table("public", "ctr").region_ids[0])
        q = "rate(ctr[60s])"
        args = (q, T0 + 5 * STEP, T0 + 11 * STEP, STEP)
        whole = np.asarray(PromqlEngine(qe).eval_matrix(*args)[1].values)

        seen = []
        real = PromqlEngine._make_channels

        def spy(self, d_sidx, d_ts, d_vals, extra, p):
            seen.append(np.unique(np.asarray(d_sidx)).tolist())
            return real(self, d_sidx, d_ts, d_vals, extra, p)

        monkeypatch.setattr(PromqlEngine, "_make_channels", spy)
        monkeypatch.setattr(promql_engine, "_LOAD_BLOCK", 30)
        # a new engine and executor: nothing is resident
        qe2 = QueryEngine(qe.catalog, eng)
        n0 = PROMQL_LOAD_CACHE_EVENTS.get(event="promote")
        blocked = np.asarray(PromqlEngine(qe2).eval_matrix(*args)[1].values)
        assert PROMQL_LOAD_CACHE_EVENTS.get(event="promote") == n0 + 1
        # 7 series x 12 samples in blocks of <= 30 samples: whole series
        assert seen == [[0, 1, 2], [3, 4, 5], [6]]
        np.testing.assert_array_equal(blocked, whole)
        assert np.isfinite(whole).all()
    finally:
        eng.close()

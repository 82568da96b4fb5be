"""A float32 sum of equal neighbours does not drift (ops/segment.py
`float_segment_sum`, PR 38). A running float32 sum rounds every addend
to the sum's last place, and equal addends round the same way each time:
`avg(current_load / load_capacity)` over 423,458 rows of one group, 720
equal neighbours at a time, read 2.06e-4 low in one pass, on the chip
and on the CPU backend alike. The data here is that first version of the
IoT dataset (`held`), TSBS's own integer-rounded clamped walk (`walk`)
and a gauge that never repeats (`varied`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greptimedb_tpu.ops.segment import (
    SUM_CHUNK_ROWS,
    dense_segment_sum,
    float_segment_sum,
    segment_agg,
)

BLOCK = 1 << 19
POINTS = 4320  # 12 h at 10 s


def _ratios(kind: str, trucks: int, seed: int) -> np.ndarray:
    """[trucks, POINTS] load ratios in [0, 1], a truck's rows together
    (a scan's order: primary key, then time)."""
    rng = np.random.default_rng(seed)
    if kind == "held":  # constant for two hours
        return np.repeat(rng.random((trucks, POINTS // 720)), 720, axis=1)
    if kind == "varied":
        return rng.random((trucks, POINTS))
    cap = rng.choice([1500.0, 2000.0, 5000.0], trucks)
    x = rng.random(trucks) * cap
    out = np.empty((POINTS, trucks))
    for i in range(POINTS):
        x = np.clip(x + rng.standard_normal(trucks), 0.0, cap)
        out[i] = x
    return (np.rint(out) / cap).T


def _block(kind: str, groups: int, seed: int):
    trucks = BLOCK // POINTS - 1
    vals = _ratios(kind, trucks, seed).reshape(-1)
    rng = np.random.default_rng([seed, 1])
    ids = np.repeat(rng.integers(0, groups, trucks), POINTS)
    n = len(vals)
    v = np.zeros(BLOCK)
    v[:n] = vals
    i = np.full(BLOCK, groups, np.int32)  # padding: the dead segment
    i[:n] = ids
    exact = np.bincount(ids, weights=vals, minlength=groups)
    return v, i, n, exact


def _gap(got, exact) -> float:
    got = np.asarray(got, np.float64).reshape(-1)[:len(exact)]
    has = exact > 0
    return float(np.max(np.abs(got[has] - exact[has]) / exact[has]))


@pytest.mark.parametrize("groups", [1, 40])
@pytest.mark.parametrize("kind", ["held", "walk", "varied"])
def test_a_float32_sum_stays_within_4e6_of_the_float64_sum(kind, groups):
    worst = 0.0
    for seed in range(3):
        v, i, n, exact = _block(kind, groups, seed)
        mask = jnp.arange(BLOCK) < n
        out = segment_agg(jnp.asarray(v, jnp.float32), jnp.asarray(i), mask,
                          groups, ops=("sum", "mean"))
        worst = max(worst, _gap(out["sum"], exact))
        plane = jnp.asarray(np.stack([v, np.ones(BLOCK)], axis=1),
                            jnp.float32)
        dense = dense_segment_sum(plane, jnp.asarray(i), groups + 1)
        worst = max(worst, _gap(dense[:, 0], exact))
    assert worst < 4e-6, worst  # one pass: 2e-5 to 5e-4 on held and walk


def test_one_pass_drifts_on_held_values_where_two_levels_do_not():
    """What the repair is for: the same rows, one group, one pass."""
    v, i, _n, exact = _block("held", 1, 2)
    vj, ij = jnp.asarray(v, jnp.float32), jnp.asarray(i)
    one_pass = jax.ops.segment_sum(vj, ij, num_segments=2)
    assert _gap(one_pass, exact) > 5e-5
    assert _gap(float_segment_sum(vj, ij, 2), exact) < 2e-6


def test_the_choice_is_one_of_shapes_and_dtype():
    """Two levels while chunks x segments x columns fit; one pass for a
    block of many groups (a group's rows are few there), for float64
    (bit for bit `jax.ops.segment_sum`), for whole numbers, and for a
    block of one chunk: all give the same sums."""
    rng = np.random.default_rng(5)
    n = SUM_CHUNK_ROWS * 8
    v = rng.random((n, 3))
    for groups in (7, 1 << 20):
        ids = rng.integers(0, min(groups, 1000), n).astype(np.int32)
        want = np.zeros((min(groups, 1000), 3))
        np.add.at(want, ids, v)
        got = float_segment_sum(jnp.asarray(v, jnp.float32),
                                jnp.asarray(ids), groups)
        np.testing.assert_allclose(np.asarray(got)[:len(want)], want,
                                   rtol=2e-6)
        wide = float_segment_sum(jnp.asarray(v), jnp.asarray(ids), groups)
        plain = jax.ops.segment_sum(jnp.asarray(v), jnp.asarray(ids),
                                    num_segments=groups)
        assert wide.dtype == jnp.float64 and bool((wide == plain).all())
    whole = float_segment_sum(jnp.ones(n, jnp.int32),
                              jnp.zeros(n, jnp.int32), 2)
    assert whole.dtype == jnp.int32 and int(whole[0]) == n
    small = float_segment_sum(jnp.ones(SUM_CHUNK_ROWS, jnp.float32),
                              jnp.zeros(SUM_CHUNK_ROWS, jnp.int32), 1)
    assert float(small[0]) == SUM_CHUNK_ROWS


def test_avg_of_a_field_over_a_tags_number_in_float32(monkeypatch, tmp_path):
    """The statement that found it, end to end in float32: `avg-load`'s
    text over trucks that hold their load for two hours."""
    monkeypatch.setenv("GREPTIMEDB_TPU_COMPUTE_DTYPE", "float32")
    from greptimedb_tpu.catalog import Catalog, MemoryKv
    from greptimedb_tpu.datatypes import DictVector, RecordBatch
    from greptimedb_tpu.query.engine import QueryEngine
    from greptimedb_tpu.storage import RegionEngine
    from greptimedb_tpu.storage.engine import EngineConfig

    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path),
                                    maintenance_workers=0))
    try:
        qe = QueryEngine(Catalog(MemoryKv()), eng)
        qe.execute_one(
            "CREATE TABLE diagnostics (name STRING, load_capacity STRING, "
            "ts TIMESTAMP(3) TIME INDEX, current_load DOUBLE, "
            "PRIMARY KEY(name, load_capacity))")
        table = qe.catalog.table("public", "diagnostics")
        trucks = 60
        ratio = _ratios("held", trucks, 11)
        cap = np.random.default_rng(12).choice([1500, 2000, 5000], trucks)
        caps = np.asarray(["1500", "2000", "5000"], dtype=object)
        names = np.asarray([f"truck_{t}" for t in range(trucks)],
                           dtype=object)
        series = np.repeat(np.arange(trucks), POINTS)
        eng.put(table.region_ids[0], RecordBatch(table.schema, {
            "name": DictVector(series.astype(np.int32), names),
            "load_capacity": DictVector(np.searchsorted(
                [1500, 2000, 5000], cap)[series].astype(np.int32), caps),
            "ts": np.tile(np.arange(POINTS, dtype=np.int64) * 10_000,
                          trucks),
            "current_load": (ratio * cap[:, None]).reshape(-1)}))
        got = dict(qe.execute_one(
            "SELECT load_capacity, avg(current_load / "
            "CAST(load_capacity AS DOUBLE)) FROM diagnostics "
            "GROUP BY load_capacity").rows())
        for c in (1500, 2000, 5000):
            want = float(ratio[cap == c].mean())
            assert abs(got[str(c)] - want) / want < 2e-6, (c, got, want)
    finally:
        eng.close()

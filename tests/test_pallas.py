"""Pallas fused segment-sum kernel (ops/pallas_segment.py) vs XLA's
scatter-add, in interpret mode on CPU (the kernel is testable without a
chip; on TPU backends dense_segment_sum auto-selects it).

Unit tests drive the kernel directly; the integration test runs a full
SQL query in a subprocess with GREPTIMEDB_TPU_PALLAS=on (the mode is
captured at jit-trace time, so it must be pinned at process start)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from greptimedb_tpu.ops.pallas_segment import (  # noqa: E402
    MAX_SEGMENTS,
    MAX_WIDTH,
    eligible,
    pallas_dense_segment_sum,
)


def _oracle(plane, ids, gsz):
    return np.asarray(jax.ops.segment_sum(
        jnp.asarray(plane), jnp.asarray(ids), num_segments=gsz))


@pytest.mark.parametrize("n,w,gsz", [
    (1000, 21, 61),       # single-groupby shape: 2F+1 plane, 60 buckets+dead
    (4096, 11, 4096),     # max segments, no-NaN plane width
    (777, 1, 9),          # single column, ragged rows
    (512, 128, 100),      # full lane width
    (3, 5, 8),            # tiny
])
def test_kernel_matches_scatter(n, w, gsz):
    rng = np.random.default_rng(n + w + gsz)
    plane = rng.uniform(-100, 100, (n, w))
    ids = rng.integers(0, gsz, n).astype(np.int32)
    # dead-segment rows carry zero values (the caller's contract)
    dead = rng.uniform(0, 1, n) < 0.2
    ids[dead] = gsz - 1
    plane[dead] = 0.0
    got = np.asarray(pallas_dense_segment_sum(
        jnp.asarray(plane), jnp.asarray(ids), gsz))
    want = _oracle(plane, ids, gsz)
    # summation ORDER differs (matmul vs scatter): allclose, not equal
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-9)


def test_kernel_f32():
    rng = np.random.default_rng(0)
    plane = rng.uniform(0, 100, (2048, 21)).astype(np.float32)
    ids = rng.integers(0, 48, 2048).astype(np.int32)
    got = np.asarray(pallas_dense_segment_sum(
        jnp.asarray(plane), jnp.asarray(ids), 48))
    want = _oracle(plane, ids, 48)
    np.testing.assert_allclose(got, want, rtol=2e-5)


def test_empty_segments_are_zero():
    plane = jnp.ones((64, 3))
    ids = jnp.full((64,), 7, dtype=jnp.int32)
    out = np.asarray(pallas_dense_segment_sum(plane, ids, 16))
    assert out[7, 0] == 64.0
    assert (np.delete(out, 7, axis=0) == 0).all()


def test_eligibility_bounds():
    assert eligible((100, 21), 61)
    assert eligible((100, MAX_WIDTH), MAX_SEGMENTS)
    assert not eligible((100, MAX_WIDTH + 1), 10)
    assert not eligible((100, 21), MAX_SEGMENTS + 1)
    assert not eligible((100,), 10)


_INTEGRATION = r"""
import sys, tempfile, json
import jax; jax.config.update("jax_platforms", "cpu")
from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
d = tempfile.mkdtemp()
engine = RegionEngine(EngineConfig(data_dir=d))
db = QueryEngine(Catalog(MemoryKv()), engine)
db.execute_one("CREATE TABLE t (host STRING, a DOUBLE, b DOUBLE, ts "
               "TIMESTAMP(3) NOT NULL, TIME INDEX (ts), PRIMARY KEY (host)) "
               "WITH (append_mode='true')")
import numpy as np
from greptimedb_tpu.datatypes import DictVector, RecordBatch
info = db.catalog.table("public", "t")
rng = np.random.default_rng(3)
n = 20000
names = np.asarray([f"h{i}" for i in range(40)], dtype=object)
a = rng.uniform(0, 100, n); a[::17] = np.nan
batch = RecordBatch(info.schema, {
    "host": DictVector(rng.integers(0, 40, n).astype(np.int32), names),
    "a": a, "b": rng.uniform(0, 100, n),
    "ts": np.arange(n, dtype=np.int64) * 250})
engine.put(info.region_ids[0], batch)
engine.flush(info.region_ids[0])
# 1-minute buckets keep host x bucket inside the fused kernel's 4096-
# segment envelope (1-second buckets were 200k groups — never eligible)
r = db.execute_one("SELECT host, date_bin(INTERVAL '1 minute', ts) AS s, "
                   "avg(a), sum(b), count(a), min(a), max(b) FROM t "
                   "GROUP BY host, s ORDER BY host, s LIMIT 2000")
path = db.executor.last_path
print(json.dumps({"path": path, "rows": [[str(x) for x in row]
                                          for row in r.rows()]}))
engine.close()
"""


def test_sql_pallas_vs_scatter_subprocess():
    """Same query, two processes: pallas forced on vs off; the dense
    prepared path must produce matching results either way."""
    outs = {}
    for mode in ("on", "off"):
        env = dict(os.environ, GREPTIMEDB_TPU_PALLAS=mode,
                   JAX_PLATFORMS="cpu",
                   # this test pins the fused-vs-scatter kernel routing;
                   # the partial-aggregate cache would intercept first
                   GREPTIMEDB_TPU_PARTIAL_CACHE="off",
                   PYTHONPATH=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
        r = subprocess.run([sys.executable, "-c", _INTEGRATION],
                           capture_output=True, text=True, timeout=300,
                           env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        outs[mode] = json.loads(r.stdout.splitlines()[-1])
    # =on routes the whole chain through the FUSED kernel (raw-column
    # hot set, in-register masks); =off pins the prepared scatter path
    assert outs["on"]["path"] == "dense_fused"
    assert outs["off"]["path"] == "dense_prepared"
    def norm(v):
        if v in ("None", "nan"):
            return v
        return round(float(v), 8)

    on_rows = [(h, s, *[norm(v) for v in rest])
               for h, s, *rest in outs["on"]["rows"]]
    off_rows = [(h, s, *[norm(v) for v in rest])
                for h, s, *rest in outs["off"]["rows"]]
    assert on_rows == off_rows

"""Cardinality-envelope boundary matrix (ISSUE 20): the 4096-segment
(fused kernel) and 64k-group (partial cache) envelopes crossed at
N-1/N/N+1 on every tier flavor — classic sparse, tiled sparse-fused,
mesh sharded-sparse, and the incremental partial cache — each bit-for-bit against the classic sort-compact oracle (the
single-device XLA scatter path). Integer-valued doubles keep f64 sums
associativity-free, so "equal" means EQUAL, not allclose. The typed
fallbacks (MeshIneligible demotion, PlanError cap overflow) and the hot-set tier-admission probe ride
along."""

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.datatypes import DictVector, RecordBatch
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig

SEG_EDGE = (4095, 4096, 4097)      # the fused kernel's MAX_SEGMENTS seam
GROUP_EDGE = (65535, 65536, 65537)  # the partial cache's dense envelope


@pytest.fixture(autouse=True)
def _fresh_latches():
    from greptimedb_tpu.query import partial_cache as pc
    from greptimedb_tpu.query import physical as ph

    pc.global_cache().clear()
    ph._PARTIAL_DISABLED["flag"] = False
    ph._FUSED_DISABLED["flag"] = False
    yield
    pc.global_cache().clear()
    ph._PARTIAL_DISABLED["flag"] = False
    ph._FUSED_DISABLED["flag"] = False


@pytest.fixture
def db(tmp_path):
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    yield qe
    eng.close()


@pytest.fixture
def mesh_db(tmp_path, monkeypatch):
    monkeypatch.setenv("GREPTIMEDB_TPU_MESH", "8x1")
    monkeypatch.setenv("GREPTIMEDB_TPU_MESH_MIN_ROWS", "1")
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    assert qe.executor.mesh is not None
    yield qe
    eng.close()


def fill_highcard(qe, groups, extra=1024, files=1, name="hc"):
    """`groups` distinct tag values, every group observed at least once
    (codes wrap), integer-valued doubles; bulk RecordBatch put so the
    64k-group cases stay fast. Returns (codes, v) concatenated across
    files for the numpy oracle."""
    qe.execute_one(
        f"CREATE TABLE {name} (tag STRING, v DOUBLE, ts TIMESTAMP(3) "
        f"NOT NULL, TIME INDEX (ts), PRIMARY KEY (tag)) "
        f"WITH (append_mode='true')")
    info = qe.catalog.table("public", name)
    rid = info.region_ids[0]
    names = np.asarray([f"t{i:06d}" for i in range(groups)], dtype=object)
    n = groups + extra
    all_codes, all_v = [], []
    for f in range(files):
        codes = ((np.arange(n) + f) % groups).astype(np.int32)
        v = ((np.arange(n) * 13 + f * 5) % 997).astype(np.float64)
        ts = (f * n + np.arange(n)).astype(np.int64)
        qe.region_engine.put(rid, RecordBatch(
            info.schema, {"tag": DictVector(codes, names), "v": v,
                          "ts": ts}))
        qe.region_engine.flush(rid)
        all_codes.append(codes)
        all_v.append(v)
    return np.concatenate(all_codes), np.concatenate(all_v)


SQL = ("SELECT tag, sum(v), count(v), min(v), max(v) FROM hc "
       "GROUP BY tag ORDER BY tag")


def classic_sparse_oracle(qe, sql, monkeypatch):
    """The reference result every flavor must reproduce bit-for-bit:
    a FRESH executor pinned to the single-device classic sort-compact
    path (no mesh, no pallas, no partial cache, dense budget floored)."""
    from greptimedb_tpu.query.physical import PhysicalExecutor

    for k, v in (("GREPTIMEDB_TPU_MESH", "off"),
                 ("GREPTIMEDB_TPU_PALLAS", "off"),
                 ("GREPTIMEDB_TPU_PARTIAL_CACHE", "off"),
                 ("GREPTIMEDB_TPU_DENSE_GROUPS_MAX", "8")):
        monkeypatch.setenv(k, v)
    off = PhysicalExecutor(qe.region_engine)
    saved = qe.executor
    qe.executor = off
    try:
        rows = qe.execute_one(sql).rows()
        assert off.last_path == "sparse", off.last_path
        return rows
    finally:
        qe.executor = saved
        for k in ("GREPTIMEDB_TPU_MESH", "GREPTIMEDB_TPU_PALLAS",
                  "GREPTIMEDB_TPU_PARTIAL_CACHE",
                  "GREPTIMEDB_TPU_DENSE_GROUPS_MAX"):
            monkeypatch.delenv(k)


def numpy_oracle(codes, v, groups):
    s = np.zeros(groups)
    np.add.at(s, codes, v)
    c = np.zeros(groups, np.int64)
    np.add.at(c, codes, 1)
    return s, c


class TestSegmentEnvelope:
    """4095/4096/4097 observed groups: the dense fused kernel's segment
    envelope ends at 4096; the sparse paths must cross it without a
    result seam."""

    @pytest.mark.parametrize("groups", SEG_EDGE)
    def test_classic_sparse_vs_dense_and_numpy(self, db, monkeypatch,
                                               groups):
        monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")
        codes, v = fill_highcard(db, groups)
        dense = db.execute_one(SQL).rows()
        assert db.executor.last_path.startswith("dense")
        oracle = classic_sparse_oracle(db, SQL, monkeypatch)
        assert dense == oracle
        s, c = numpy_oracle(codes, v, groups)
        assert len(oracle) == groups
        assert [r[1] for r in oracle] == list(s)
        assert [r[2] for r in oracle] == list(c)

    @pytest.mark.parametrize("groups", SEG_EDGE)
    def test_sparse_fused_tiles_past_4096(self, db, monkeypatch, groups):
        """PALLAS=on forces the tiled kernel (interpret on CPU): the
        compacted segment axis crosses the 4096 seam in windows and the
        result stays bit-for-bit with the XLA scatter path."""
        monkeypatch.setenv("GREPTIMEDB_TPU_PALLAS", "on")
        monkeypatch.setenv("GREPTIMEDB_TPU_SPARSE_GROUPS_MIN", "1")
        monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")
        fill_highcard(db, groups)
        fused = db.execute_one(SQL).rows()
        assert db.executor.last_path == "sparse_fused"
        monkeypatch.delenv("GREPTIMEDB_TPU_PALLAS")
        monkeypatch.delenv("GREPTIMEDB_TPU_SPARSE_GROUPS_MIN")
        assert fused == classic_sparse_oracle(db, SQL, monkeypatch)

    @pytest.mark.parametrize("groups", SEG_EDGE)
    def test_mesh_sharded_sparse(self, mesh_db, monkeypatch, groups):
        """Per-shard compaction + gid-space combine across the seam."""
        monkeypatch.setenv("GREPTIMEDB_TPU_SPARSE_GROUPS_MIN", "1")
        monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")
        fill_highcard(mesh_db, groups)
        got = mesh_db.execute_one(SQL).rows()
        assert mesh_db.executor.last_path == "sparse_sharded"
        assert mesh_db.executor.last_tier == "mesh"
        monkeypatch.delenv("GREPTIMEDB_TPU_SPARSE_GROUPS_MIN")
        assert got == classic_sparse_oracle(mesh_db, SQL, monkeypatch)


class TestGroupEnvelope:
    """64k-1/64k/64k+1 groups: the partial cache's dense envelope. At
    64k+1 the incremental path switches to value-space sparse partials
    instead of refusing; both flavors equal the classic oracle."""

    @pytest.mark.parametrize("groups", GROUP_EDGE)
    def test_incremental_crosses_64k(self, db, monkeypatch, groups):
        fill_highcard(db, groups, files=2)
        cold = db.execute_one(SQL).rows()
        # the key domain is tags + 1 (the dictionary's null slot), so
        # the dense partial envelope ends at 64k-1 observed tags
        want = "incremental_sparse" if groups + 1 > 65536 else "incremental"
        assert db.executor.last_path == want
        warm = db.execute_one(SQL).rows()
        assert db.executor.last_partial_stats["part_hits"] > 0
        assert warm == cold
        assert cold == classic_sparse_oracle(db, SQL, monkeypatch)

    def test_sparse_min_knob_reroutes_dense_domain(self, db, monkeypatch):
        """[query] sparse_groups_min: a key product INSIDE the dense
        budget still takes the sort-compact path when the knob says so
        — identical rows, sparse dispatch counted."""
        from greptimedb_tpu.utils.metrics import SPARSE_DISPATCHES

        monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")
        fill_highcard(db, 512)
        dense = db.execute_one(SQL).rows()
        assert db.executor.last_path.startswith("dense")
        monkeypatch.setenv("GREPTIMEDB_TPU_SPARSE_GROUPS_MIN", "64")
        before = SPARSE_DISPATCHES.get(path="classic")
        got = db.execute_one(SQL).rows()
        assert db.executor.last_path == "sparse"
        assert SPARSE_DISPATCHES.get(path="classic") == before + 1
        assert got == dense


class TestTypedFallbacks:
    def test_mesh_ineligible_demotes_to_device_sparse(self, mesh_db,
                                                      monkeypatch):
        """A mesh the shard planner refuses: the sparse branch demotes
        to the single-device path, typed, never an error."""
        from greptimedb_tpu.parallel import sharded_dispatch as sd

        monkeypatch.setenv("GREPTIMEDB_TPU_SPARSE_GROUPS_MIN", "1")
        monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")
        monkeypatch.setattr(sd, "eligible", lambda mesh: False)
        fill_highcard(mesh_db, 512)
        got = mesh_db.execute_one(SQL).rows()
        assert mesh_db.executor.last_path == "sparse"
        assert mesh_db.executor.last_tier == "device"
        assert len(got) == 512

    def test_incremental_cap_overflow_is_planerror(self, db, monkeypatch):
        from greptimedb_tpu.query.expr import PlanError

        fill_highcard(db, 500)
        monkeypatch.setenv("GREPTIMEDB_TPU_DENSE_GROUPS_MAX", "8")
        monkeypatch.setenv("GREPTIMEDB_TPU_SPARSE_GROUPS_MAX", "4")
        with pytest.raises(PlanError, match="SPARSE_GROUPS_MAX"):
            db.execute_one(SQL)


class TestSortCompactUnit:
    """ops-level seams of the shared sparse plane."""

    def test_boundary_cap_exact_fit(self):
        import jax.numpy as jnp

        from greptimedb_tpu.ops import sparse_segment as so

        for g in (4095, 4096, 4097):
            gid = jnp.asarray(np.arange(g * 2, dtype=np.int64) % g)
            mask = jnp.ones(g * 2, bool)
            _o, ids, valid, uniq, n = so.sort_compact(gid, mask, g)
            assert int(n) == g
            assert list(np.asarray(uniq)[:g]) == list(range(g))
            assert int(jnp.max(jnp.where(valid, ids, 0))) == g - 1

    def test_combine_partials_last_tie_and_nan(self):
        from greptimedb_tpu.ops import sparse_segment as so

        a = {"gids": np.asarray([1, 5], np.int64),
             "planes": {"sum": np.asarray([[1.0], [2.0]]),
                        "rows": np.asarray([1, 1], np.int64),
                        "last": np.asarray([[10.0], [20.0]]),
                        "last_ts": np.asarray([5, 5], np.int64)}}
        b = {"gids": np.asarray([5, 9], np.int64),
             "planes": {"sum": np.asarray([[3.0], [4.0]]),
                        "rows": np.asarray([2, 1], np.int64),
                        "last": np.asarray([[30.0], [40.0]]),
                        "last_ts": np.asarray([5, 7], np.int64)}}
        gids, planes = so.combine_sparse_gid_partials([a, b])
        assert list(gids) == [1, 5, 9]
        assert list(planes["sum"][:, 0]) == [1.0, 5.0, 4.0]
        assert list(planes["rows"]) == [1, 3, 1]
        # equal-ts tie keeps the EARLIER partial (shard order)
        assert list(planes["last"][:, 0]) == [10.0, 20.0, 40.0]

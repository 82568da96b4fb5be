"""Engine-on-mesh beyond the toy shapes (round-3 verdict weak #8): the
8-virtual-device mesh driving real SQL through multi-region scans with
divergent tag dictionaries, the sparse (sort-compact) path, and the
streaming fold — each cross-checked against a numpy oracle and against
the mesh-off execution of the same query."""

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig


@pytest.fixture
def mesh_db(tmp_path, monkeypatch):
    monkeypatch.setenv("GREPTIMEDB_TPU_MESH", "8x1")
    monkeypatch.setenv("GREPTIMEDB_TPU_MESH_MIN_ROWS", "1")
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data")))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    assert qe.executor.mesh is not None
    yield qe
    engine.close()


def _off_oracle(qe, sql, monkeypatch):
    """Re-run the same SQL with the mesh disabled on a fresh executor."""
    from greptimedb_tpu.query.physical import PhysicalExecutor

    monkeypatch.setenv("GREPTIMEDB_TPU_MESH", "off")
    off = PhysicalExecutor(qe.region_engine)
    saved = qe.executor
    qe.executor = off
    try:
        return qe.execute_one(sql).rows()
    finally:
        qe.executor = saved
        monkeypatch.setenv("GREPTIMEDB_TPU_MESH", "8x1")


def test_partitioned_regions_dict_remap_on_mesh(mesh_db, monkeypatch):
    """Two regions whose tag dictionaries grew in DIFFERENT orders: each
    folds its own scan on its own device of the mesh and the partials
    combine by key value — group results must match both the numpy
    oracle and the mesh-off run; an order statistic still gathers the
    regions into one scan with a union dictionary (merge_scans)."""
    qe = mesh_db
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) NOT "
        "NULL, TIME INDEX (ts), PRIMARY KEY (host)) "
        "PARTITION ON COLUMNS (host) (host < 'h50', host >= 'h50')")
    info = qe.catalog.table("public", "cpu")
    assert len(info.region_ids) == 2
    rng = np.random.default_rng(9)
    # region A sees hosts in ascending order, region B descending, so the
    # two region dictionaries assign DIFFERENT codes to any shared prefix
    rows = []
    for h in range(99, -1, -1):
        for t in range(40):
            rows.append((f"h{h:02d}", round(float(rng.uniform(0, 100)), 6),
                         1000 * (t + 1)))
    vals = ", ".join(f"('{h}', {v:.6f}, {ts})" for h, v, ts in rows)
    qe.execute_one(f"INSERT INTO cpu (host, v, ts) VALUES {vals}")
    qe.region_engine.flush(info.region_ids[0])
    qe.region_engine.flush(info.region_ids[1])

    sql = ("SELECT host, avg(v), count(v), max(v) FROM cpu "
           "GROUP BY host ORDER BY host")
    got = qe.execute_one(sql).rows()
    assert qe.executor.last_path.startswith("fanout+"), \
        qe.executor.last_path
    assert len(got) == 100
    by_host: dict = {}
    for h, v, _ in rows:
        by_host.setdefault(h, []).append(v)
    for row in got:
        sel = np.asarray(by_host[row[0]])
        np.testing.assert_allclose(row[1], sel.mean(), rtol=1e-9)
        assert row[2] == len(sel)
        np.testing.assert_allclose(row[3], sel.max(), rtol=1e-12)
    off = _off_oracle(qe, sql, monkeypatch)
    assert [r[0] for r in off] == [r[0] for r in got]
    np.testing.assert_allclose(
        [r[1] for r in off], [r[1] for r in got], rtol=1e-9)
    gathered = qe.execute_one(
        "SELECT host, median(v) FROM cpu GROUP BY host ORDER BY host").rows()
    assert not qe.executor.last_path.startswith("fanout+")
    assert [r[0] for r in gathered] == [r[0] for r in got]
    np.testing.assert_allclose(
        [r[1] for r in gathered],
        [np.median(by_host[r[0]]) for r in gathered], rtol=1e-9)


def test_sparse_cardinality_with_mesh_present(mesh_db, monkeypatch):
    """Cardinality beyond the dense budget: the sparse sort-compact path
    takes over AND rides the mesh (per-shard compaction, gid-space
    combine) instead of demoting to a single device — and stays
    correct."""
    monkeypatch.setenv("GREPTIMEDB_TPU_DENSE_GROUPS_MAX", "64")
    # pin the shard_map machinery: the partial-aggregate cache would
    # otherwise serve this append-mode shape via incremental_sparse
    monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")
    qe = mesh_db
    qe.execute_one(
        "CREATE TABLE hc (tag STRING, v DOUBLE, ts TIMESTAMP(3) NOT NULL, "
        "TIME INDEX (ts), PRIMARY KEY (tag)) WITH (append_mode='true')")
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    info = qe.catalog.table("public", "hc")
    rng = np.random.default_rng(3)
    n, combos = 20000, 500  # 500 groups >> dense budget of 64
    codes = rng.integers(0, combos, n).astype(np.int32)
    v = rng.uniform(0, 100, n)
    names = np.asarray([f"t{i:03d}" for i in range(combos)], dtype=object)
    qe.region_engine.put(info.region_ids[0], RecordBatch(
        info.schema, {"tag": DictVector(codes, names), "v": v,
                      "ts": np.arange(n, dtype=np.int64)}))
    qe.region_engine.flush(info.region_ids[0])
    got = qe.execute_one(
        "SELECT tag, sum(v) FROM hc GROUP BY tag ORDER BY tag").rows()
    assert qe.executor.last_path == "sparse_sharded"
    assert qe.executor.last_tier == "mesh"
    assert len(got) == combos
    expect = np.zeros(combos)
    np.add.at(expect, codes, v)
    np.testing.assert_allclose([r[1] for r in got], expect, rtol=1e-9)


def test_streaming_fold_with_mesh_present(mesh_db, monkeypatch):
    """Beyond-RAM streaming with a mesh configured: the stream fold
    (single-device, bounded memory) takes precedence and stays correct —
    multi-block, multiple SST files."""
    monkeypatch.setenv("GREPTIMEDB_TPU_STREAM_THRESHOLD_ROWS", "1000")
    monkeypatch.setenv("GREPTIMEDB_TPU_STREAM_BLOCK_ROWS", "2048")
    qe = mesh_db
    qe.execute_one(
        "CREATE TABLE big (host STRING, v DOUBLE, ts TIMESTAMP(3) NOT "
        "NULL, TIME INDEX (ts), PRIMARY KEY (host)) "
        "WITH (append_mode='true')")
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    info = qe.catalog.table("public", "big")
    rid = info.region_ids[0]
    rng = np.random.default_rng(5)
    hosts = 32
    names = np.asarray([f"h{i:02d}" for i in range(hosts)], dtype=object)
    all_codes, all_v = [], []
    for part in range(3):  # three SST files -> multi-chunk stream
        n = 6000
        codes = rng.integers(0, hosts, n).astype(np.int32)
        v = rng.uniform(0, 100, n)
        qe.region_engine.put(rid, RecordBatch(info.schema, {
            "host": DictVector(codes, names), "v": v,
            "ts": (np.arange(n, dtype=np.int64) + part * 6000) * 500}))
        qe.region_engine.flush(rid)
        all_codes.append(codes)
        all_v.append(v)
    got = qe.execute_one(
        "SELECT host, avg(v), count(v) FROM big GROUP BY host "
        "ORDER BY host").rows()
    assert qe.executor.last_path.startswith("stream"), \
        qe.executor.last_path
    codes = np.concatenate(all_codes)
    v = np.concatenate(all_v)
    for i, row in enumerate(got):
        sel = v[codes == i]
        np.testing.assert_allclose(row[1], sel.mean(), rtol=1e-9)
        assert row[2] == len(sel)

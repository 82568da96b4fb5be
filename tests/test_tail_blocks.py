"""A memtable tail's blocks come from few sizes, far apart (ops/blocks.py
`tail_block_size_for`, PR 38): a table under ingest grows its tail between
any two requests, every block size is a program, and by powers of two a
tail that grows from 4,000 to 280,000 rows in a window passed eight sizes
(`tsbs-read-under-ingest`: 8-13 compiles a window, 387-1050 ms of compile
a request)."""

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.datatypes import DictVector, RecordBatch
from greptimedb_tpu.ops.blocks import block_size_for, tail_block_size_for
from greptimedb_tpu.query import partial_cache as pc
from greptimedb_tpu.query.engine import QueryContext, QueryEngine
from greptimedb_tpu.query.physical import _block_plan
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils.metrics import XLA_COMPILES

CTX = QueryContext()
HOSTS, STEP = 500, 10_000


@pytest.mark.parametrize("rows, size", [
    (1, 1024), (1024, 1024), (1025, 1 << 14), (4000, 1 << 14),
    (1 << 14, 1 << 14), ((1 << 14) + 1, 1 << 18), (200_000, 1 << 18),
    (280_000, 1 << 19), ((1 << 20) + 1, 2 << 20)])
def test_the_ladder(rows, size):
    assert tail_block_size_for(rows) == size >= rows
    if rows > 1 << 18:
        assert size == block_size_for(rows)


@pytest.fixture
def db(tmp_path):
    pc.global_cache().clear()
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    qe.execute_one(
        "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX, "
        "usage DOUBLE, PRIMARY KEY(hostname)) WITH (append_mode='true')",
        CTX)
    table = qe.catalog.table("public", "cpu")
    names = np.asarray([f"host_{i}" for i in range(HOSTS)], dtype=object)
    rng = np.random.default_rng(2)

    def put(p0, p1):
        pts = np.repeat(np.arange(p0, p1), HOSTS)
        ser = np.tile(np.arange(HOSTS), p1 - p0).astype(np.int32)
        eng.put(table.region_ids[0], RecordBatch(table.schema, {
            "hostname": DictVector(ser, names),
            "ts": pts.astype(np.int64) * STEP,
            "usage": rng.random(len(pts)) * 100}))

    for k in range(2):
        put(k * 20, (k + 1) * 20)
        eng.flush(table.region_ids[0])
    yield eng, qe, table, put
    pc.global_cache().clear()
    eng.close()


def _queries(points: int) -> list:
    end = points * STEP
    return [
        "SELECT date_bin(INTERVAL '1 hour', ts) AS m, hostname, "
        f"avg(usage) FROM cpu WHERE ts >= {10 * STEP} AND ts < {end} "
        "GROUP BY m, hostname",
        "SELECT hostname, last_value(usage ORDER BY ts) FROM cpu "
        "GROUP BY hostname"]


def test_a_growing_tail_inside_one_size_compiles_nothing(db):
    eng, qe, table, put = db
    rid = table.region_ids[0]
    points = 40
    for sql in _queries(points):        # no memtable yet
        qe.execute_one(sql, CTX)
    # 1,500 rows of memtable: the first size past 1,024 is 16,384
    put(points, points + 3)
    points += 3
    scan = eng.scan(rid)
    tail = [e for e in _block_plan(scan) if e.pkey is None]
    assert [(e.end - e.start, e.block) for e in tail] == [(1500, 1 << 14)]
    first = [qe.execute_one(sql, CTX).rows() for sql in _queries(points)]
    assert len(first[1]) == HOSTS
    compiles = XLA_COMPILES.total()
    # 3,000, 6,000 and 7,500 rows: by powers of two, two more sizes
    for more in (3, 6, 3):
        put(points, points + more)
        points += more
        for sql in _queries(points):
            rows = qe.execute_one(sql, CTX).rows()
            assert len(rows) >= HOSTS
        assert XLA_COMPILES.total() == compiles, points
    got = dict(qe.execute_one(_queries(points)[1], CTX).rows())
    assert len(got) == HOSTS

#!/usr/bin/env python3
"""Loader `iot_backlog`: the data home of `tsbs-iot-4000`, built without
the chip, in the ORDER the rows arrive.

The harness's `bulk` writes a dense [points, series] grid in time order;
this deployment's rows have gaps, arrive out of order (a truck's backlog
is uploaded an hour of data after it went offline) and some arrive twice
(`datasets/tsbs_iot.py` `write_order`). Same contract as `bulk`
(benchmark/README.md, "Add a set-up route"): a helper pinned to
JAX_PLATFORMS=cpu assembles the standalone stack on the data home,
creates each table with its DDL (no `append_mode`: the last write of a
(primary key, ts) wins), writes one batch after the other through
`RegionEngine.put` into the table's one region — the memtable flushes by
the server's own thresholds on the way, so the SSTs overlap in time where
a backlog arrived late — flushes, waits for the maintenance plane, and
prints the loaders' one JSON line. `tables` counts DISTINCT rows
acknowledged: every row `put` acknowledged less the rows that were sent
a second time, which is what count(*) of a last-write-wins table reads.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import bulk_load as bulk  # noqa: E402
from benchmark.harness.common import tables  # noqa: E402

BATCH_ROWS = 1 << 21


def put_rows(engine, qe, rid: int, view) -> int:
    """One table's rows in arrival order; the distinct rows acknowledged."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    schema = qe.catalog.table("public", view.table).schema
    tag_dicts = {}
    for tag, per_series in view.series_tags().items():
        present = sorted({v for v in per_series if v is not None})
        code = {v: i for i, v in enumerate(present)}
        tag_dicts[tag] = (np.asarray(present, dtype=object), np.asarray(
            [-1 if v is None else code[v] for v in per_series], np.int32))
    distinct = 0
    for points, series, again in view.write_order(BATCH_ROWS):
        if not len(points):
            continue
        cols = {"ts": view.ts_of(points)}
        for tag, (values, codes) in tag_dicts.items():
            cols[tag] = DictVector(codes[series], values)
        for name in view.names:
            cols[name] = view.fields[name][points, series]
        distinct += int(engine.put(rid, RecordBatch(schema, cols))) - again
    return distinct


def main() -> int:
    t0 = time.monotonic()
    args, ds, gen_s = bulk.start()
    engine, qe = bulk.standalone(args.data_home)
    acked: dict = {}
    put_s = flush_s = 0.0
    try:
        for view in tables(ds):
            rid = bulk.create(qe, view)
            t1 = time.monotonic()
            acked[view.table] = put_rows(engine, qe, rid, view)
            t2 = time.monotonic()
            engine.flush(rid)
            put_s, flush_s = put_s + t2 - t1, flush_s + time.monotonic() - t2
        t2 = time.monotonic()
        bulk.wait_flushed(engine)
        flush_s += time.monotonic() - t2
    finally:
        qe.concurrency.shutdown()
        engine.close()
    bulk.report(acked, gen_s, put_s, flush_s, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Loader `present_rows`: the data home of a table whose grid has holes
(`host-cpu-range-4000`: hosts with outages), built without the chip.

The harness's `bulk` writes a dense [points, series] grid: every series
at every point. Here a dataset's `slices` carry the PRESENT rows only,
each with its series (`datasets/tsbs_cpu_outages.py`), and an absent row
is never written — not as NULLs either. Same contract as `bulk`
(benchmark/README.md, "Add a set-up route"): a helper pinned to
JAX_PLATFORMS=cpu assembles the standalone stack on the data home,
creates each table with its DDL, writes one time slice after the other
through `RegionEngine.put` into the table's one region, flushes, waits
for the maintenance plane, and prints the loaders' one JSON line.
`tables` counts the rows `put` acknowledged.

Before it writes a row it **checks that the program offers the
deployment**: the harness warms every SQL template with `EXPLAIN
ANALYZE` and waits for the aggregate's execution tier, so a program that
cannot plan a `RANGE ... ALIGN` statement (one that runs it beside the
aggregate path and has no path or tier to report) ends here: exit 1 with
a message, in the first seconds, nothing loaded.
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


def check_program(qe, view) -> None:
    """A RANGE statement over the (still empty) table must EXPLAIN."""
    sql = (f"EXPLAIN SELECT ts, max({next(iter(view.fields))}) RANGE '2m' "
           f"FROM {view.table} ALIGN '1m' BY ()")
    try:
        qe.execute_one(sql)
    except Exception as e:  # noqa: BLE001 — whatever it is, it ends here
        print(f"this program does not offer the deployment: {sql!r} -> "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        sys.exit(1)


def put_rows(engine, qe, rid: int, view) -> int:
    """One table's present rows in time slices; the rows acknowledged."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    schema = qe.catalog.table("public", view.table).schema
    tag_dicts = {}
    for tag, per_series in view.series_tags().items():
        values, codes = np.unique(np.asarray(per_series, dtype=object),
                                  return_inverse=True)
        tag_dicts[tag] = (values, codes.astype(np.int32))
    acked = 0
    for _p0, _p1, ts, fields, series in view.slices(BATCH_ROWS):
        if not len(ts):
            continue
        cols = {"ts": ts}
        for tag, (values, codes) in tag_dicts.items():
            cols[tag] = DictVector(codes[series], values)
        cols.update(fields)
        acked += int(engine.put(rid, RecordBatch(schema, cols)))
    return acked


def main() -> int:
    t0 = time.monotonic()
    args, ds, gen_s = bulk.start()
    engine, qe = bulk.standalone(args.data_home)
    acked: dict = {}
    put_s = flush_s = 0.0
    try:
        for view in tables(ds):
            rid = bulk.create(qe, view)
            check_program(qe, view)
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

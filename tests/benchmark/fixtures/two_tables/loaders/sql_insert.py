#!/usr/bin/env python3
"""Loader `sql_insert`: the data home built through the program's own
statements — CREATE TABLE, INSERT INTO ... VALUES in batches, ADMIN
flush_table — instead of the storage API. Slow (a fixture's size only),
and it shows that a set-up route is a file: same arguments in, same one
JSON line out as `bulk` (benchmark/README.md).

Two switches for the tests, which no configuration sets:
FIXTURE_LOADER_DROPS_A_ROW=<table> acknowledges a row of that table
without writing it; FIXTURE_LOADER_EXITS=<code> fails before any work.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness.common import tables  # noqa: E402
from benchmark.harness import bulk_load as bulk  # noqa: E402

BATCH = 500


def insert(qe, view, drop_one: bool) -> int:
    tags = view.series_tags()
    cols = list(tags) + ["ts"] + list(view.fields)
    acked = 0
    for _p0, _p1, ts, fields in view.slices(BATCH):
        n = len(ts)
        rows = []
        for i in range(n):
            s = i % view.series
            vals = [f"'{tags[t][s]}'" for t in tags] + [str(int(ts[i]))] \
                + [repr(float(fields[f][i])) for f in fields]
            rows.append("(" + ", ".join(vals) + ")")
        acked += n
        if drop_one:
            rows, drop_one = rows[1:], False
        qe.execute_one(f"INSERT INTO {view.table} ({', '.join(cols)}) "
                       "VALUES " + ", ".join(rows))
    return acked


def main() -> int:
    t0 = time.monotonic()
    args, ds, gen_s = bulk.start()
    if os.environ.get("FIXTURE_LOADER_EXITS"):
        print("sql_insert: told to fail by FIXTURE_LOADER_EXITS",
              file=sys.stderr)
        return int(os.environ["FIXTURE_LOADER_EXITS"])
    engine, qe = bulk.standalone(args.data_home)
    acked: dict = {}
    try:
        t1 = time.monotonic()
        for view in tables(ds):
            qe.execute_one(view.create_sql())
            acked[view.table] = insert(
                qe, view,
                os.environ.get("FIXTURE_LOADER_DROPS_A_ROW") == view.table)
        t2 = time.monotonic()
        for view in tables(ds):
            qe.execute_one(f"ADMIN flush_table('{view.table}')")
        bulk.wait_flushed(engine)
        t3 = time.monotonic()
    finally:
        qe.concurrency.shutdown()
        engine.close()
    bulk.report(acked, gen_s, t2 - t1, t3 - t2, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())

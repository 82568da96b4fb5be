#!/usr/bin/env python3
"""Loader `partitioned`: the data home of a table range-partitioned over
several regions (`tsbs-cpu-only-4000-4dn`), built without the chip.

Same contract as `bulk` (benchmark/README.md, "Add a set-up route"): a
helper pinned to JAX_PLATFORMS=cpu assembles the standalone stack on the
data home and, for every table of the dataset, creates it with its DDL
plus the configuration's `layout` — `PARTITION ON COLUMNS (<column>)`
with one range a region, the bounds the equal quantiles of the column's
own values in string order — and then **checks the layout it was asked
for before it writes a row**: `information_schema.partitions` lists that
many partitions of the table, each with its range expression, and
`information_schema.region_peers` as many distinct `peer_id`s for them (a
peer's id is the index of the chip its region computes on, fixed by the
region's position in the table). A program that does not offer the
deployment (every region on peer 0) ends here: exit 1 with a message, in
the first seconds, nothing loaded. A table the data home already holds is
not created again, and is checked like any other.

Each batch is split by the table's own partition rule (read back from
the catalog, not from this file's arithmetic), every region is written
by a thread of its own through `RegionEngine.put` — a region's rows of
one time slice are one batch — and flushed; then the maintenance plane
is waited for and the loaders' one JSON line printed.
"""

import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import bulk_load as bulk  # noqa: E402
from benchmark.harness.common import load_json, tables  # noqa: E402

BATCH_ROWS = 1 << 21


class LayoutError(Exception):
    """The table is not laid out as the configuration says."""


def partition_clause(column: str, values, regions: int) -> str:
    """`PARTITION ON COLUMNS (<column>) (...)`: `regions` ranges whose
    bounds are the equal quantiles of the column's distinct values in
    string order."""
    names = sorted({str(v) for v in values})
    if len(names) < regions:
        raise LayoutError(f"{len(names)} distinct {column} values cannot "
                          f"fill {regions} regions")
    bounds = [names[len(names) * i // regions] for i in range(1, regions)]
    exprs = [f"{column} < '{bounds[0]}'"]
    exprs += [f"{column} >= '{lo}' AND {column} < '{hi}'"
              for lo, hi in zip(bounds, bounds[1:])]
    exprs.append(f"{column} >= '{bounds[-1]}'")
    return f"PARTITION ON COLUMNS ({column}) (" + ", ".join(exprs) + ")"


def create(qe, view, layout: dict) -> None:
    """The view's DDL with the layout's PARTITION clause before WITH."""
    if qe.catalog.table_exists("public", view.table):
        return  # the data home holds it: checked as it stands
    column = layout["partition_columns"][0]
    clause = partition_clause(column, view.series_tags()[column],
                              int(layout["regions"]))
    head, sep, options = view.create_sql().partition(" WITH (")
    qe.execute_one(f"{head} {clause}{sep}{options}")


def check_layout(qe, table: str, layout: dict) -> list:
    """The table's region ids in partition order, or LayoutError: what
    `information_schema` says of the table against what the
    configuration's `layout` says."""
    want = int(layout["regions"])
    column = layout["partition_columns"][0]
    parts = qe.execute_one(
        "SELECT partition_name, partition_expression, "
        "greptime_partition_id FROM information_schema.partitions "
        f"WHERE table_name = '{table}' ORDER BY partition_name").rows()
    if len(parts) != want:
        raise LayoutError(
            f"table {table} has {len(parts)} partition(s), the "
            f"configuration asks for {want} (PARTITION ON COLUMNS "
            f"({column}))")
    for name, expr, _rid in parts:
        if not expr or column not in str(expr) or "'" not in str(expr):
            raise LayoutError(
                f"partition {name} of {table} states no range of "
                f"{column}: {expr!r}")
    rids = [int(p[2]) for p in parts]
    peers = {int(r[0]): int(r[1]) for r in qe.execute_one(
        "SELECT region_id, peer_id FROM "
        "information_schema.region_peers").rows()}
    mine = [peers.get(rid) for rid in rids]
    if None in mine or len(set(mine)) != want:
        raise LayoutError(
            f"the {want} regions of {table} are on peer(s) {mine}: this "
            f"program does not place a partitioned table's regions on "
            f"{want} distinct peers (one region a chip), so it does not "
            f"offer the deployment")
    return rids


def put_region(engine, schema, rid: int, view, hosts: np.ndarray,
               acked: list, slot: int) -> None:
    """One region's rows, time slice after time slice: the series
    `hosts` (positions in the dataset) of every point."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    tag_dicts = {}
    for k, v in view.series_tags().items():
        values, codes = np.unique(
            np.asarray(v, dtype=object)[hosts], return_inverse=True)
        tag_dicts[k] = (values, codes.astype(np.int32))
    per = max(1, BATCH_ROWS // max(len(hosts), 1))
    for p0 in range(0, view.points, per):
        p1 = min(p0 + per, view.points)
        cols = {"ts": np.repeat(
            view.t0_ms + np.arange(p0, p1, dtype=np.int64) * view.step_ms,
            len(hosts))}
        for k, (values, codes) in tag_dicts.items():
            cols[k] = DictVector(np.tile(codes, p1 - p0), values)
        for f, v in view.fields.items():
            cols[f] = v[p0:p1][:, hosts].reshape(-1)
        acked[slot] += int(engine.put(rid, RecordBatch(schema, cols)))


def side_by_side(calls: list) -> None:
    """Each (function, arguments) on a thread of its own; the first
    error any of them raised is raised here."""
    errors: list = []

    def run(fn, args):
        try:
            fn(*args)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=run, args=c) for c in calls]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def main() -> int:
    from greptimedb_tpu.partition.rule import rule_from_json

    t0 = time.monotonic()
    args, ds, gen_s = bulk.start()
    layout = load_json("configs", args.config + ".json")["layout"]
    engine, qe = bulk.standalone(args.data_home)
    acked: dict = {}
    put_s = flush_s = 0.0
    try:
        for view in tables(ds):
            create(qe, view, layout)
            try:
                rids = check_layout(qe, view.table, layout)
            except LayoutError as e:
                print(f"loader partitioned: {e}", file=sys.stderr,
                      flush=True)
                return 1
            info = qe.catalog.table("public", view.table)
            # the table's own rule says where a series' rows go
            rule = rule_from_json(info.partition_rules)
            region_of = rule.find_regions(
                [np.asarray(view.series_tags()[c], dtype=object)
                 for c in rule.columns])
            counts = [0] * len(rids)
            t1 = time.monotonic()
            side_by_side([
                (put_region, (engine, info.schema, rid, view,
                              np.flatnonzero(region_of == i), counts, i))
                for i, rid in enumerate(info.region_ids)])
            acked[view.table] = sum(counts)
            t2 = time.monotonic()
            side_by_side([(engine.flush, (rid,)) for rid in info.region_ids])
            put_s, flush_s = put_s + t2 - t1, flush_s + time.monotonic() - t2
            print(f"loader partitioned: {view.table} rows per region "
                  f"{counts}", file=sys.stderr, flush=True)
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

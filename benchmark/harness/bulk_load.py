#!/usr/bin/env python3
"""Loader `bulk` (the default set-up route): build the data home without
the chip. Other loaders are benchmark/loaders/<name>.py and may import
`start`, `standalone`, `wait_flushed` and `report` from here.

A helper process pinned to JAX_PLATFORMS=cpu (the chip belongs to the
server, which starts after this one has exited) assembles the program's
standalone stack on the data home exactly as `standalone start` does
(`cli.build_standalone`) and, for every table of the dataset
(`common.tables`), creates it with its DDL, writes its seeded rows with
`RegionEngine.put` into its one region (the bulk route bench.py:168
uses) and flushes it, so that the server opens the data home as after a
restart with every row in SSTs. Prints the loaders' one JSON line
(benchmark/README.md): rows acknowledged by `put` in all and per table,
and the seconds of each phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def start(argv=None) -> tuple:
    """What every loader does first: read the harness's arguments, die
    with the harness, refuse the chip, build the seeded dataset.
    Returns (args, dataset, seconds generating it)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--scale", required=True, help="JSON object")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--data-home", required=True)
    ap.add_argument("--parent", type=int, default=0,
                    help="the harness's pid: this process dies with it")
    args = ap.parse_args(argv)
    if args.parent:
        from benchmark.harness import procs

        procs.die_with(args.parent)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("a loader must run with JAX_PLATFORMS=cpu", file=sys.stderr)
        sys.exit(2)

    from benchmark.harness.common import load_json, make_dataset

    config = load_json("configs", args.config + ".json")
    t0 = time.monotonic()
    ds = make_dataset(config, args.seed, json.loads(args.scale))
    return args, ds, time.monotonic() - t0


def standalone(data_home: str) -> tuple:
    """(engine, query engine) on the data home, assembled as
    `standalone start` assembles them."""
    from greptimedb_tpu.cli import build_standalone
    from greptimedb_tpu.options import load_options

    return build_standalone(data_home, load_options(None, overrides={}))


def wait_flushed(engine) -> None:
    """Auto-flush jobs the writes submitted: let them finish."""
    maint = getattr(engine, "maintenance", None)
    while maint is not None and (maint.queue_depth() or any(
            j.to_dict().get("state") in ("queued", "running")
            for j in maint.jobs())):
        time.sleep(0.05)


def report(acked: dict, generate_s: float, put_s: float, flush_s: float,
           t0: float) -> None:
    """The one JSON line every loader ends with."""
    print(json.dumps({"rows": sum(acked.values()), "tables": acked,
                      "generate_s": generate_s, "put_s": put_s,
                      "flush_s": flush_s,
                      "seconds": time.monotonic() - t0}), flush=True)


def main() -> int:
    from benchmark.harness.common import tables

    t0 = time.monotonic()
    args, ds, gen_s = start()
    engine, qe = standalone(args.data_home)
    acked: dict = {}
    put_s = flush_s = 0.0
    try:
        for view in tables(ds):
            rid = create(qe, view)
            t1 = time.monotonic()
            acked[view.table] = put_rows(engine, qe, rid, view)
            t2 = time.monotonic()
            engine.flush(rid)
            put_s, flush_s = put_s + t2 - t1, flush_s + time.monotonic() - t2
        t2 = time.monotonic()
        wait_flushed(engine)
        flush_s += time.monotonic() - t2
    finally:
        qe.concurrency.shutdown()
        engine.close()
    report(acked, gen_s, put_s, flush_s, t0)
    return 0


def create(qe, view) -> int:
    """Run the view's DDL; the id of the table's one region."""
    qe.execute_one(view.create_sql())
    info = qe.catalog.table("public", view.table)
    if len(info.region_ids) != 1:
        raise RuntimeError(f"{view.table}: expected one region, got "
                           f"{info.region_ids}")
    return info.region_ids[0]


def put_rows(engine, qe, rid: int, view) -> int:
    """Every row of one table through RegionEngine.put, in time slices;
    the rows it acknowledged."""
    from greptimedb_tpu.datatypes import DictVector, RecordBatch

    schema = qe.catalog.table("public", view.table).schema
    # a tag's dictionary holds each distinct value once
    tag_dicts = {}
    for k, v in view.series_tags().items():
        values, codes = np.unique(np.asarray(v, dtype=object),
                                  return_inverse=True)
        tag_dicts[k] = (values, codes.astype(np.int32))
    acked = 0
    for p0, p1, ts, fields in view.slices(1 << 21):
        cols = {"ts": ts}
        for k, (values, codes) in tag_dicts.items():
            cols[k] = DictVector(np.tile(codes, p1 - p0), values)
        cols.update(fields)
        acked += int(engine.put(rid, RecordBatch(schema, cols)))
    return acked


if __name__ == "__main__":
    sys.exit(main())

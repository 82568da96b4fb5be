#!/usr/bin/env python3
"""Set-up route "bulk": build the data home without the chip.

A helper process pinned to JAX_PLATFORMS=cpu (the chip belongs to the
server, which starts after this one has exited) assembles the program's
standalone stack on the data home exactly as `standalone start` does
(`cli.build_standalone`), creates the table with the configuration's
DDL, writes the seeded rows with `RegionEngine.put` (the bulk route
bench.py:168 uses) and flushes every region, so that the server opens
the data home as after a restart with every row in SSTs. Prints one
JSON line: rows acknowledged by `put`, seconds, flush seconds.
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--scale", required=True, help="JSON object")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--data-home", required=True)
    ap.add_argument("--parent", type=int, default=0,
                    help="the harness's pid: this process dies with it")
    args = ap.parse_args()
    if args.parent:
        from benchmark.harness import procs

        procs.die_with(args.parent)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("bulk_load must run with JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2

    from benchmark.harness.common import load_json, make_dataset

    config = load_json("configs", args.config + ".json")
    t0 = time.monotonic()
    ds = make_dataset(config, args.seed, json.loads(args.scale))
    gen_s = time.monotonic() - t0

    from greptimedb_tpu.cli import build_standalone
    from greptimedb_tpu.datatypes import DictVector, RecordBatch
    from greptimedb_tpu.options import load_options

    opts = load_options(None, overrides={})
    engine, qe = build_standalone(args.data_home, opts)
    try:
        qe.execute_one(ds.create_sql())
        info = qe.catalog.table("public", ds.table)
        if len(info.region_ids) != 1:
            raise RuntimeError(f"{ds.table}: expected one region, got "
                               f"{info.region_ids}")
        rid = info.region_ids[0]
        tags = {k: np.asarray(v, dtype=object)
                for k, v in ds.series_tags().items()}
        # a tag's dictionary holds each distinct value once
        tag_dicts = {}
        for k, v in tags.items():
            values, codes = np.unique(v, return_inverse=True)
            tag_dicts[k] = (values, codes.astype(np.int32))
        acked = 0
        t1 = time.monotonic()
        for p0, p1, ts, fields in ds.slices(1 << 21):
            cols = {"ts": ts}
            for k, (values, codes) in tag_dicts.items():
                cols[k] = DictVector(np.tile(codes, p1 - p0), values)
            cols.update(fields)
            acked += engine.put(rid, RecordBatch(info.schema, cols))
        put_s = time.monotonic() - t1
        t2 = time.monotonic()
        engine.flush(rid)
        maint = getattr(engine, "maintenance", None)
        if maint is not None:
            # auto-flush jobs the writes submitted: let them finish
            while maint.queue_depth() or any(
                    j.to_dict().get("state") in ("queued", "running")
                    for j in maint.jobs()):
                time.sleep(0.05)
        flush_s = time.monotonic() - t2
    finally:
        qe.concurrency.shutdown()
        engine.close()
    print(json.dumps({"rows": int(acked), "generate_s": gen_s,
                      "put_s": put_s, "flush_s": flush_s,
                      "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

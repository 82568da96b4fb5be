#!/usr/bin/env python3
"""The control of `correct`, at a cell's own size.

For each template of a cell's mix, over draws from the given seeds, the
number compared when the REFERENCE COMPUTED ONE PRECISION BELOW the
engine's stands in the engine's place (bfloat16 inputs under the chip's
float32 SQL aggregates; float32 samples under PromQL's float64): the
smallest such number has to lie well above the template's limit, and
the largest number sound runs of the program read has to lie well below
it. Pure numpy on the seeded arrays, no server: the same numbers on any
machine. Not part of a benchmark run.

    python3 benchmark/harness/control.py --workload <cell> --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import traffic  # noqa: E402
from benchmark.harness.common import (  # noqa: E402
    cell, load_json, make_dataset, manifest)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--draws", type=int, default=5)
    ap.add_argument("--dtype", default="float32",
                    help="the engine's SQL compute dtype (chip: float32)")
    args = ap.parse_args()
    wl = cell(manifest(), args.workload)
    config = load_json("configs", wl["config"] + ".json")
    smallest: dict = {}
    for seed in args.seeds:
        ds = make_dataset(config, seed, config["scale"])
        mix = traffic.Mix(wl["traffic"], ds)
        for e in mix.entries:
            rng = np.random.default_rng([seed, 10, e.idx])
            for _ in range(args.draws):
                p = e.template.draw(rng, ds)
                v = e.template.compare(None, p, ds, args.dtype, lowered=True)
                rec = smallest.setdefault(
                    e.name, {"smallest": v, "largest": v,
                             "limit": e.template.limit(args.dtype)})
                rec["smallest"] = min(rec["smallest"], v)
                rec["largest"] = max(rec["largest"], v)
                if not p:
                    break  # no parameters: one draw is all there is
        print(json.dumps({"seed": seed, "control": smallest}), flush=True)
    bad = [n for n, r in smallest.items()
           if not r["smallest"] > 3 * r["limit"]]
    print(json.dumps({"workload": args.workload, "control": smallest,
                      "separated": not bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

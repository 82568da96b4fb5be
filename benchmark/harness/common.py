"""Paths, the manifest, and look-up of per-cell files by name.

Whatever belongs to one configuration, one traffic mix, one template
family, one dataset, one loader, one reader or one metric is a file of
its own under benchmark/, found here by the name BENCHMARK.json (or the
file that refers to it) gives. Nothing in the harness lists them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
T_START = time.monotonic()


class BenchFailure(Exception):
    """The run cannot produce a result (no chip, server died, bad file)."""


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(BENCH_DIR, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchFailure(f"missing benchmark file {path}: {e}")


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module (kind: datasets,
    templates, readers)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchFailure(f"no {kind} file {path}")
    modname = f"_bench_{kind}_{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(man: dict, workload: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == workload:
            return w
    raise BenchFailure(
        f"no workload {workload!r} in BENCHMARK.json (has: "
        + ", ".join(w["name"] for w in man["workloads"]) + ")")


def cell_metrics(man: dict, workload: str, which: str) -> list:
    """The manifest's metrics of one list that this cell reports."""
    return [m for m in man[which]
            if "workloads" not in m or workload in m["workloads"]]


def make_dataset(config: dict, seed: int, scale: dict):
    return load_module("datasets", config["dataset"]).Dataset(seed, scale)


def tables(ds) -> list:
    """The dataset's tables, each a view with the single-table
    interface (`table`, `rows`, `create_sql()`, `series_tags()`,
    `fields`, `slices()`); a dataset of one table is its own view."""
    return list(ds.tables()) if hasattr(ds, "tables") else [ds]


def loader_path(config: dict) -> str:
    """The configuration's set-up route. `setup.loader` names
    benchmark/loaders/<name>.py; the one loader the harness brings,
    harness/bulk_load.py, answers to `bulk` and is what a configuration
    without the key gets."""
    name = config.get("setup", {}).get("loader", "bulk")
    path = os.path.join(BENCH_DIR, "harness", "bulk_load.py") \
        if name == "bulk" else os.path.join(BENCH_DIR, "loaders",
                                            name + ".py")
    if not os.path.isfile(path):
        raise BenchFailure(f"no loader file {path}")
    return path

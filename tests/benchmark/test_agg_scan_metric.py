"""`agg_scan_skipped_share` (ISSUE 25): a data file on PR 23's
`prom_delta` reader, in the manifest for the two TSBS cells. It
reads the share from expositions the program's registry rendered, and
0.0 — not nothing — from expositions shaped like the parent's, which
have the denominator and lack the counter.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.common import load_json, manifest  # noqa: E402

MAN = manifest()


def _recorded_expositions(tmp_path):
    """Two /metrics expositions of the program around a window in which
    an aggregate is asked four times over flushed SSTs (the first
    computes its parts, three find every partial cached) and one raw
    SELECT runs: what the server's registry renders, not a hand-written
    text."""
    from greptimedb_tpu.catalog import Catalog, MemoryKv
    from greptimedb_tpu.query import partial_cache
    from greptimedb_tpu.query.engine import QueryEngine
    from greptimedb_tpu.storage import RegionEngine
    from greptimedb_tpu.storage.engine import EngineConfig
    from greptimedb_tpu.utils.metrics import REGISTRY

    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    try:
        qe = QueryEngine(Catalog(MemoryKv()), eng)
        qe.execute_one(
            "CREATE TABLE cpu (ts TIMESTAMP(3) TIME INDEX, host STRING, "
            "v DOUBLE, PRIMARY KEY(host)) WITH (append_mode='true')")
        rid = qe.catalog.table("public", "cpu").region_ids[0]
        for f in range(2):
            qe.execute_one("INSERT INTO cpu VALUES " + ", ".join(
                f"({f * 100000 + i * 10}, 'h{i % 3}', {i}.5)"
                for i in range(60)))
            eng.flush(rid)
        partial_cache.global_cache().clear()
        text0 = REGISTRY.render()
        for _ in range(4):
            qe.execute_one("SELECT host, avg(v) FROM cpu GROUP BY host")
        qe.execute_one("SELECT v FROM cpu WHERE host = 'h1' LIMIT 3")
        return text0, REGISTRY.render()
    finally:
        eng.close()


@pytest.mark.parametrize("shape", ["recorded", "parent"])
def test_agg_scan_skipped_share_reads_the_counter_or_zero(tmp_path, shape):
    """ISSUE 25's metric is a data file on `prom_delta`: the share of
    statements answered without fetching an SST part, from a recorded
    exposition; 0.0 — not nothing — from one shaped like the parent's,
    which has the denominator and lacks the counter."""
    from benchmark.harness import wire
    from benchmark.harness.common import load_module

    entry = dict(next(m for m in MAN["per_layer"]
                      if m["name"] == "agg_scan_skipped_share"))
    cells = entry.pop("workloads")
    assert entry == {
        "name": "agg_scan_skipped_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Storage",
        "moves": "queries_per_s"}
    # a later cell joins by adding its name
    assert {"tsbs-scan-heavy", "tsbs-point-dash"} <= set(cells) \
        <= {w["name"] for w in MAN["workloads"]}
    spec = load_json("metrics", "agg_scan_skipped_share.json")
    assert spec["reader"] == "prom_delta"
    text0, text1 = _recorded_expositions(tmp_path)
    assert 'greptimedb_tpu_agg_scan_total{mode="none"}' in text1
    if shape == "parent":
        text0, text1 = ("\n".join(
            line for line in t.splitlines()
            if "greptimedb_tpu_agg_scan_total" not in line)
            for t in (text0, text1))

    class Ctx:
        m0 = wire.parse_exposition(text0)
        m1 = wire.parse_exposition(text1)
        requests = []

    value = load_module("readers", "prom_delta").read(Ctx, spec["args"])
    # five statements the executor answered; three asked nothing of
    # their scan
    assert value == pytest.approx(60.0 if shape == "recorded" else 0.0)


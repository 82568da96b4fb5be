"""A table of several regions in ONE serving process (ISSUE 42): an
aggregate goes to the regions its predicate on the partition column can
match, each folds its own scan on its own device, and the partials
combine by key value — at 20 hosts x 2 h of TSBS `cpu`, seeded, against
the same rows in a table of one region and the templates' numpy
references.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark", "fixtures"))

import region_faults  # noqa: E402

from benchmark.harness import traffic  # noqa: E402
from benchmark.harness.common import (  # noqa: E402
    load_json, load_module, make_dataset)

CONFIG = "tsbs-cpu-only-4000-4dn"
CELL = "tsbs-mesh-4chip"
SEED = 2**31 + 42
TEMPLATES = [e["template"] for e in
             load_json("traffic", CELL + ".json")["mix"]]


class OneRegion:
    """The dataset under another table name, for the table of one
    region beside the partitioned one."""

    def __init__(self, ds):
        self._ds = ds
        self.table = "cpu_one"

    def __getattr__(self, name):
        return getattr(self._ds, name)

    def create_sql(self):
        return self._ds.create_sql().replace(
            "CREATE TABLE cpu ", "CREATE TABLE cpu_one ", 1)


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    from greptimedb_tpu.catalog import Catalog, FileKv
    from greptimedb_tpu.query import QueryEngine
    from greptimedb_tpu.storage import RegionEngine
    from greptimedb_tpu.storage.engine import EngineConfig

    home = str(tmp_path_factory.mktemp("fanout"))
    engine = RegionEngine(EngineConfig(data_dir=home))
    qe = QueryEngine(Catalog(FileKv(home + "/catalog.json")), engine)
    conf = load_json("configs", CONFIG + ".json")
    ds = make_dataset(conf, SEED, conf["rehearsal"]["scale"])
    assert (ds.hosts, ds.hours) == (20, 2)
    loader = load_module("loaders", "partitioned")
    bulk = load_module("harness", "bulk_load")
    loader.create(qe, ds, conf["layout"])
    rids = loader.check_layout(qe, "cpu", conf["layout"])
    info = qe.catalog.table("public", "cpu")
    assert rids == list(info.region_ids) and len(rids) == 4
    from greptimedb_tpu.partition.rule import rule_of

    region_of = rule_of(info).find_regions(
        [np.asarray(ds.series_tags()["hostname"], dtype=object)])
    counts = [0] * 4
    for i, rid in enumerate(rids):
        # two files a region: the fold has parts to combine
        for half in (slice(0, ds.points // 2), slice(ds.points // 2, None)):
            view = _Points(ds, half)
            loader.put_region(engine, info.schema, rid, view,
                              np.flatnonzero(region_of == i), counts, i)
            engine.flush(rid)
    assert sum(counts) == ds.rows
    one = OneRegion(ds)
    rid1 = bulk.create(qe, one)
    assert bulk.put_rows(engine, qe, rid1, one) == ds.rows
    engine.flush(rid1)
    bulk.wait_flushed(engine)
    yield qe, ds, one, region_of
    engine.close()


class _Points:
    """A dataset's view cut to a slice of its points."""

    def __init__(self, ds, sl: slice):
        lo, hi, _ = sl.indices(ds.points)
        self.points, self.step_ms = hi - lo, ds.step_ms
        self.t0_ms = ds.t0_ms + lo * ds.step_ms
        self.fields = {f: v[lo:hi] for f, v in ds.fields.items()}
        self.series_tags = ds.series_tags


def answer(qe, t, p, ds):
    res = qe.execute_one(t.sql(p, ds))
    return [list(r) for r in res.rows()]


def draws(t, ds, n=3, stream=5):
    rng = np.random.default_rng([SEED, stream])
    return [t.draw(rng, ds) for _ in range(n)]


@pytest.mark.parametrize("name", TEMPLATES)
def test_a_partitioned_table_answers_as_one_region_and_as_numpy(db, name):
    qe, ds, one, _ = db
    fam = load_module("templates", "tsbs_devops")
    t = fam.make(name)
    for p in draws(t, ds):
        got = answer(qe, t, p, ds)
        assert "fanout+" in qe.executor.last_path, \
            qe.executor.last_path
        assert len(got) == t.expected_rows(p, ds)
        assert t.compare(got, p, ds, "float64") <= t.limit("float64")
        ref = answer(qe, t, p, one)
        assert "fanout+" not in qe.executor.last_path
        k1, v1 = t.decode(got, p, ds)
        k2, v2 = t.decode(ref, p, ds)
        assert list(k1) == list(k2)
        if t.kind == "exact":
            assert (v1 == v2).all()
        else:
            np.testing.assert_allclose(v1, v2, rtol=t.limit("float64"))


def _fanout_spans(qe, sql):
    from greptimedb_tpu.session import QueryContext
    from greptimedb_tpu.utils import tracing

    ctx = QueryContext()
    res = qe.execute_one(sql, ctx)   # the statement mints its trace
    spans = tracing.spans_for(ctx.trace_id)
    return res, [s for s in spans if s.name == "region_fanout"], \
        [s for s in spans if s.name == "region_partial"]


def _route():
    from greptimedb_tpu.utils.metrics import REGION_ROUTE

    return (REGION_ROUTE.get(outcome="scanned"),
            REGION_ROUTE.get(outcome="pruned"))


def test_one_host_scans_one_region_and_a_fleet_four(db):
    qe, ds, _one, region_of = db
    fam = load_module("templates", "tsbs_devops")
    t = fam.make("single-groupby-1-1-1")
    p = draws(t, ds, 1, stream=6)[0]   # a text no test has sent yet
    s0, p0 = _route()
    _res, fan, parts = _fanout_spans(qe, t.sql(p, ds))
    assert [(f.attrs["regions_matched"], f.attrs["regions_scanned"])
            for f in fan] == [(1, 1)]
    rids = qe.catalog.table("public", "cpu").region_ids
    assert [s.attrs["region"] for s in parts] == \
        [rids[region_of[p["hosts"][0]]]]
    assert (_route()[0] - s0, _route()[1] - p0) == (1, 3)
    t = fam.make("double-groupby-1")
    s0, p0 = _route()
    _res, fan, parts = _fanout_spans(
        qe, t.sql(draws(t, ds, 1, stream=6)[0], ds))
    assert [(f.attrs["regions_matched"], f.attrs["regions_scanned"])
            for f in fan] == [(4, 4)]
    assert sorted(s.attrs["region"] for s in parts) == sorted(rids)
    assert (_route()[0] - s0, _route()[1] - p0) == (4, 0)
    # a range of hostnames is read against the rule too
    lo = sorted(ds.series_tags()["hostname"])[12]
    s0, p0 = _route()
    res = qe.execute_one(
        f"SELECT count(*) FROM cpu WHERE hostname >= '{lo}'")
    assert res.rows()[0][0] == 8 * ds.points
    assert (_route()[0] - s0, _route()[1] - p0) == (2, 2)
    # a table of one region counts nothing
    s0, p0 = _route()
    qe.execute_one("SELECT count(*) FROM cpu_one")
    assert _route() == (s0, p0)


def test_region_i_computes_on_device_i_and_region_peers_says_so(db):
    import jax

    from greptimedb_tpu.utils.metrics import REGION_PARTIAL

    qe, ds, _one, _ = db
    assert len(jax.local_devices()) >= 4   # conftest forces eight
    peers = {r[0]: r[1] for r in qe.execute_one(
        "SELECT region_id, peer_id FROM information_schema.region_peers"
    ).rows()}
    rids = list(qe.catalog.table("public", "cpu").region_ids)
    assert [peers[rid] for rid in rids] == [0, 1, 2, 3]
    own0 = REGION_PARTIAL.get(placement="own_chip")
    other0 = REGION_PARTIAL.get(placement="other")
    # new literals: every region has to fold (nothing cached answers)
    _res, _fan, parts = _fanout_spans(
        qe, "SELECT hostname, max(usage_user), min(usage_idle) FROM cpu "
        f"WHERE ts >= {ds.t0_ms + 1234} GROUP BY hostname")
    devs = {d.id: d for d in jax.local_devices()}
    assert {s.attrs["region"]: s.attrs["device"] for s in parts} == {
        rid: jax.local_devices()[i].id for i, rid in enumerate(rids)}
    assert all(s.attrs["device"] in devs for s in parts)
    assert REGION_PARTIAL.get(placement="own_chip") - own0 == 4
    assert REGION_PARTIAL.get(placement="other") == other0
    by_dev = qe.execute_one(
        "SELECT count(*) FROM cpu").rows()[0][0]
    assert by_dev == ds.rows


def test_the_blocks_of_a_region_live_on_its_device(db):
    import jax

    qe, _ds, _one, _ = db
    rids = list(qe.catalog.table("public", "cpu").region_ids)
    want = {rid: jax.local_devices()[i] for i, rid in enumerate(rids)}
    seen = {}
    with qe.executor.cache._lock:
        for key, arr in qe.executor.cache._lru.items():
            if key[0] == "file" and key[1] in want:
                seen.setdefault(key[1], set()).update(arr.devices())
    assert seen and all(devs == {want[rid]} for rid, devs in seen.items())


def test_the_regions_partials_add_up_to_the_whole_tables(db, monkeypatch):
    from greptimedb_tpu.query import dist_agg

    qe, ds, one, _ = db
    fam = load_module("templates", "tsbs_devops")
    t = fam.make("double-groupby-5")
    p = draws(t, ds, 1, stream=7)[0]
    outs, combined = [], []
    sound = qe.executor._region_partials

    def spy(*a):
        outs.append(sound(*a))
        return outs[-1]

    monkeypatch.setattr(qe.executor, "_region_partials", spy)
    real = dist_agg.combine_partials

    def keep(partials, n_keys, ops):
        combined.append(real(partials, n_keys, ops))
        return combined[-1]

    monkeypatch.setattr(dist_agg, "combine_partials", keep)
    answer(qe, t, p, ds)
    assert len(outs) == 4 and len(combined) == 1
    whole4, ops = combined.pop(), None
    answer(qe, t, p, one)
    whole1 = combined.pop()
    monkeypatch.undo()
    ops = tuple(sorted(whole1["planes"]))

    def table(c):
        order = np.lexsort((c["keys"][1].astype(str), c["keys"][0]))
        return ([k[order] for k in c["keys"]],
                {op: pl[order] for op, pl in c["planes"].items()})

    k4, p4 = table(whole4)
    k1, p1 = table(whole1)
    assert all((a == b).all() for a, b in zip(k4, k1))
    for op in ops:   # plane by plane: the four regions' == the one's
        np.testing.assert_allclose(p4[op], p1[op], rtol=1e-12)
    # and the shares add up: each region's own combine, region by region
    per_region = [real(o.partials, 2, ops) for o in outs]
    rows = sum(int(c["planes"]["rows"].sum()) for c in per_region)
    assert rows == int(p1["rows"].sum()) > 0
    assert sum(len(c["keys"][0]) for c in per_region) == len(k1[0])
    again = real(per_region, 2, ops)
    _k, pa = table(again)
    for op in ops:
        np.testing.assert_allclose(pa[op], p1[op], rtol=1e-12)


def _wait_warm(qe):
    import time

    for _ in range(600):
        if not qe.executor.router.status()["warmup"]["warming"]:
            return
        time.sleep(0.05)
    raise AssertionError("sibling warm-ups still running")


def test_new_literals_compile_nothing_the_second_time(db):
    """One request compiles a shape on the chips it reaches and warms
    it on the table's others beside itself: requests that differ only
    in their literals — other hosts, so other regions, another window —
    compile nothing."""
    from greptimedb_tpu.utils.metrics import XLA_COMPILES

    qe, ds, _one, region_of = db
    fam = load_module("templates", "tsbs_devops")
    for name in ("single-groupby-1-1-1", "cpu-max-all-8",
                 "single-groupby-5-8-1", "double-groupby-1"):
        t = fam.make(name)
        rng = np.random.default_rng([SEED, 11])
        first = t.draw(rng, ds)
        answer(qe, t, first, ds)
        _wait_warm(qe)
        before = XLA_COMPILES.total()
        reached = set()
        for _ in range(6):
            p = t.draw(rng, ds)
            reached.update(region_of[p.get("hosts", range(ds.hosts))])
            got = answer(qe, t, p, ds)
            assert t.compare(got, p, ds, "float64") <= t.limit("float64")
        _wait_warm(qe)
        assert XLA_COMPILES.total() == before, (name, XLA_COMPILES.series())
        assert len(reached) == 4, name


def test_a_gathered_scan_keys_no_cache(db):
    """What cannot be split still gathers: the merged scan has no
    region and no version, takes no per-part route and parks nothing in
    the partial cache or the hot set under its name."""
    from greptimedb_tpu.query import partial_cache as pc
    from greptimedb_tpu.storage import merge_scan

    qe, ds, _one, _ = db
    rids = list(qe.catalog.table("public", "cpu").region_ids)
    scans = [qe.region_engine.scan(rid, None, None, None) for rid in rids]
    merged = merge_scan.merge_scans(scans)
    assert (merged.region_id, merged.data_version) == (-1, 0)
    assert merged.num_rows == ds.rows and not merged.part_keys
    unions0 = len(merge_scan._UNIONS)
    again = merge_scan.merge_scans(
        [qe.region_engine.scan(rid, None, None, None) for rid in rids])
    # the union dictionaries are made once per dictionary version
    assert len(merge_scan._UNIONS) == unions0
    assert again.tag_dicts["hostname"] is merged.tag_dicts["hostname"]
    size0 = len(pc.global_cache()._lru) if hasattr(
        pc.global_cache(), "_lru") else None
    res = qe.execute_one(
        "SELECT hostname, median(usage_user) FROM cpu GROUP BY hostname "
        "ORDER BY hostname")
    assert "fanout+" not in qe.executor.last_path
    assert qe.executor.last_partial_stats is None
    if size0 is not None:
        assert len(pc.global_cache()._lru) == size0
    with qe.executor.cache._lock:
        assert not [k for k in qe.executor.cache._lru if k[1] == -1]
    want = np.median(ds.fields["usage_user"], axis=0)
    order = sorted(range(ds.hosts), key=lambda h: f"host_{h}")
    np.testing.assert_allclose([r[1] for r in res.rows()], want[order],
                               rtol=1e-12)


@pytest.mark.parametrize("kind", ["drop", "twice"])
def test_a_region_lost_or_folded_twice_fails_its_comparison(db, kind):
    qe, ds, _one, _ = db
    fam = load_module("templates", "tsbs_devops")
    count = "SELECT count(*) FROM cpu"
    assert qe.execute_one(count).rows()[0][0] == ds.rows
    t = fam.make("double-groupby-1")
    p = draws(t, ds, 1, stream=8 + (kind == "drop"))[0]
    with region_faults.fault(qe.executor, kind):
        rows = qe.execute_one(count).rows()[0][0]
        got = answer(qe, t, p, ds)
    assert rows == ds.rows + (-1 if kind == "drop" else 1) * 5 * ds.points
    if kind == "drop":   # its hosts are missing from every fleet answer
        assert len(got) != t.expected_rows(p, ds)
        assert t.compare(got, p, ds, "float64") == float("inf")
    assert qe.execute_one(count).rows()[0][0] == ds.rows


def test_the_mix_is_the_issues(db):
    _qe, ds, _one, _ = db
    mix = traffic.Mix(CELL, ds)
    assert mix.clients == 4 and mix.writer_spec is None
    assert {e.name: (e.weight, e.check_share) for e in mix.entries} == {
        "double-groupby-1": (1, 1.0), "double-groupby-5": (1, 0.5),
        "double-groupby-all": (1, 0.5), "groupby-orderby-limit": (1, 1.0),
        "lastpoint": (1, 1.0), "cpu-max-all-8": (2, 1.0),
        "single-groupby-1-1-1": (2, 1.0), "single-groupby-5-8-1": (1, 1.0)}

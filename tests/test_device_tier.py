"""Device tier (ISSUE 7): the fused scan→filter→bucket→aggregate Pallas
kernel differentially against the XLA scatter path, the HBM-resident
columnar hot set under the storage mutation matrix
(flush/compaction/expiry/DROP), buffer donation on the chunked
accumulator loops, the mid-query kernel-failure degradation latch, and
measured (history-driven) tier routing."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import greptimedb_tpu.query.physical as ph  # noqa: E402
from greptimedb_tpu.catalog import Catalog, MemoryKv  # noqa: E402
from greptimedb_tpu.ops.pallas_segment import (  # noqa: E402
    MAX_FUSED_FIELDS,
    MAX_SEGMENTS,
    fused_eligible,
    pallas_fused_segment_agg,
)
from greptimedb_tpu.query import QueryEngine  # noqa: E402
from greptimedb_tpu.storage import RegionEngine  # noqa: E402
from greptimedb_tpu.storage.engine import EngineConfig  # noqa: E402


# ---- fused kernel vs oracle (interpret mode on CPU) ------------------------


def _oracle(vals, ids, g):
    """Reference masked segment aggregation: NaN = SQL NULL, empty/
    all-NULL groups -> 0 counts and ±inf extremes (kernel contract)."""
    n, f = vals.shape
    out = {
        "sum": np.zeros((g, f)),
        "count": np.zeros((g, f)),
        "rows": np.zeros(g),
        "min": np.full((g, f), np.inf),
        "max": np.full((g, f), -np.inf),
    }
    for i in range(n):
        s = ids[i]
        out["rows"][s] += 1
        for j in range(f):
            v = vals[i, j]
            if np.isnan(v):
                continue
            out["sum"][s, j] += v
            out["count"][s, j] += 1
            out["min"][s, j] = min(out["min"][s, j], v)
            out["max"][s, j] = max(out["max"][s, j], v)
    return out


@pytest.mark.parametrize("n,f,g,seed", [
    (1000, 10, 61, 1),    # the double-groupby shape class
    (777, 1, 9, 2),       # single column, ragged rows
    (513, 56, 64, 3),     # full fused field width
    (3, 4, 8, 4),         # tiny
])
def test_fused_kernel_matches_oracle(n, f, g, seed):
    rng = np.random.default_rng(seed)
    vals = rng.uniform(-100, 100, (n, f))
    vals[rng.uniform(0, 1, (n, f)) < 0.15] = np.nan  # NULL sprinkle
    # segment g-1 is the DEAD segment (padding rows land there — the
    # caller's masked-row contract): live ids stay below it and only
    # the live slice is compared
    ids = rng.integers(0, g - 1, n).astype(np.int32)
    got = pallas_fused_segment_agg(
        jnp.asarray(vals), jnp.asarray(ids), g,
        want_min=True, want_max=True)
    want = _oracle(vals, ids, g)
    live = g - 1
    np.testing.assert_allclose(np.asarray(got["sum"])[:live],
                               want["sum"][:live], rtol=1e-9, atol=1e-9)
    np.testing.assert_array_equal(np.asarray(got["count"])[:live],
                                  want["count"][:live])
    np.testing.assert_array_equal(np.asarray(got["rows"])[:live],
                                  want["rows"][:live])
    np.testing.assert_array_equal(np.asarray(got["min"])[:live],
                                  want["min"][:live])
    np.testing.assert_array_equal(np.asarray(got["max"])[:live],
                                  want["max"][:live])


def test_fused_integer_planes_bit_exact():
    """Integer-valued planes: matmul-summed sums and counts are EXACT
    (< 2^53, every partial is an integer), matching the scatter path
    bit for bit — the differential-suite contract."""
    rng = np.random.default_rng(7)
    n, f, g = 2048, 6, 33
    vals = rng.integers(-1000, 1000, (n, f)).astype(np.float64)
    ids = rng.integers(0, g, n).astype(np.int32)
    got = pallas_fused_segment_agg(jnp.asarray(vals), jnp.asarray(ids), g)
    want_sum = np.asarray(jax.ops.segment_sum(
        jnp.asarray(vals), jnp.asarray(ids), num_segments=g))
    np.testing.assert_array_equal(np.asarray(got["sum"]), want_sum)
    ones = np.ones((n, f))
    want_cnt = np.asarray(jax.ops.segment_sum(
        jnp.asarray(ones), jnp.asarray(ids), num_segments=g))
    np.testing.assert_array_equal(np.asarray(got["count"]), want_cnt)


def test_fused_f32_tolerance():
    rng = np.random.default_rng(11)
    n, f, g = 4096, 10, 128
    vals = rng.uniform(0, 100, (n, f)).astype(np.float32)
    ids = rng.integers(0, g, n).astype(np.int32)
    got = pallas_fused_segment_agg(
        jnp.asarray(vals), jnp.asarray(ids), g,
        want_min=True, want_max=True)
    want = _oracle(vals.astype(np.float64), ids, g)
    np.testing.assert_allclose(np.asarray(got["sum"]), want["sum"],
                               rtol=2e-5)
    # extremes are selections, not accumulations: exact even in f32
    np.testing.assert_array_equal(np.asarray(got["min"]),
                                  want["min"].astype(np.float32))
    np.testing.assert_array_equal(np.asarray(got["max"]),
                                  want["max"].astype(np.float32))


def test_fused_dead_segment_rows_excluded():
    """Masked rows arrive encoded into the dead segment (the caller's
    contract): their values must not leak into live segments."""
    vals = np.asarray([[1.0], [2.0], [1e9]])
    ids = np.asarray([0, 0, 2], dtype=np.int32)  # row 2 -> dead seg
    got = pallas_fused_segment_agg(jnp.asarray(vals), jnp.asarray(ids), 3,
                                   want_min=True, want_max=True)
    assert float(got["sum"][0, 0]) == 3.0
    assert float(got["rows"][0]) == 2.0
    assert float(got["max"][0, 0]) == 2.0
    assert float(got["sum"][1, 0]) == 0.0
    assert float(got["min"][1, 0]) == np.inf


def test_fused_eligibility_envelope():
    assert fused_eligible(10, 61)
    assert fused_eligible(MAX_FUSED_FIELDS, MAX_SEGMENTS)
    assert not fused_eligible(MAX_FUSED_FIELDS + 1, 61)
    assert not fused_eligible(10, MAX_SEGMENTS + 1)
    assert not fused_eligible(0, 61)


def test_finite_proof_runs_in_compute_dtype():
    """A finite f64 value that overflows the f64->f32 cast reaches the
    one-hot matmul as Inf all the same — the fused-route finite proof
    must run post-cast, or the f32 chip path NaN-poisons every group."""
    from types import SimpleNamespace

    has = ph.PhysicalExecutor._scan_has_inf
    scan = SimpleNamespace(columns={"v": np.array([1.0, 1e40])})
    assert not has(None, scan, ("v",))                  # finite in f64
    assert has(None, scan, ("v",), dtype=np.float32)    # Inf after cast
    # memoization is per-dtype: the f64 verdict is not clobbered
    assert not has(None, scan, ("v",), dtype=np.float64)
    # a genuinely infinite column is flagged under every dtype
    scan2 = SimpleNamespace(columns={"v": np.array([np.inf, 1.0])})
    assert has(None, scan2, ("v",))
    assert has(None, scan2, ("v",), dtype=np.float32)
    # integer columns can never go infinite
    scan3 = SimpleNamespace(columns={"v": np.array([1, 2], dtype=np.int64)})
    assert not has(None, scan3, ("v",), dtype=np.float32)


# ---- engine-level fixtures -------------------------------------------------


@pytest.fixture
def db(tmp_path):
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "d"),
                                       maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    yield engine, qe
    engine.close()


def _fill(qe, files=3, hosts=5, points=40):
    qe.execute_one(
        "CREATE TABLE t (host STRING, v DOUBLE, ts TIMESTAMP(3) "
        "TIME INDEX, PRIMARY KEY(host)) WITH (append_mode = 'true')")
    rng = np.random.default_rng(5)
    i = 0
    for f in range(files):
        rows = []
        for p in range(points):
            for h in range(hosts):
                rows.append(f"('h{h}', {rng.uniform(0, 100):.6f}, "
                            f"{(f * points + p) * 1000})")
                i += 1
        qe.execute_one("INSERT INTO t (host, v, ts) VALUES "
                       + ",".join(rows))
        qe.execute_one("ADMIN flush_table('t')")
    return qe.catalog.table("public", "t").region_ids[0]


AGG_SQL = ("SELECT host, sum(v), count(v), min(v), max(v), avg(v) "
           "FROM t GROUP BY host ORDER BY host")


def _h2d():
    from greptimedb_tpu.utils.metrics import DEVICE_TRANSFER_BYTES

    return DEVICE_TRANSFER_BYTES.get(direction="h2d")


def rows_close(a, b):
    """Row-set equality with float tolerance: compaction/merges reorder
    the physical rows, so float sums differ in the last ulps."""
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra[0] == rb[0]
        np.testing.assert_allclose([float(x) for x in ra[1:]],
                                   [float(y) for y in rb[1:]],
                                   rtol=1e-9)


# ---- hot-set residency + invalidation matrix -------------------------------


class TestHotSet:
    def test_warm_repeat_pays_zero_h2d(self, db):
        engine, qe = db
        rid = _fill(qe)
        want = qe.execute_one(AGG_SQL).rows()
        cache = qe.executor.cache
        assert cache.file_keys(rid), "no file-anchored blocks resident"
        before = _h2d()
        got = qe.execute_one(AGG_SQL).rows()
        assert _h2d() == before, "hot-set-warm repeat re-uploaded blocks"
        assert got == want

    def test_flush_keeps_old_files_uploads_only_new(self, db):
        engine, qe = db
        rid = _fill(qe)
        qe.execute_one(AGG_SQL)
        cache = qe.executor.cache
        old_keys = set(cache.file_keys(rid))
        assert old_keys
        qe.execute_one(
            "INSERT INTO t (host, v, ts) VALUES ('h0', 1.0, 999000)")
        qe.execute_one("ADMIN flush_table('t')")
        want = qe.execute_one(AGG_SQL).rows()
        keys = set(cache.file_keys(rid))
        # every pre-flush upload survived the data-version bump...
        assert old_keys <= keys
        # ...and the new file's blocks joined them
        assert len(keys) > len(old_keys)
        # correctness across the incremental upload
        assert qe.execute_one(AGG_SQL).rows() == want

    def test_compaction_swap_kills_input_blocks(self, db):
        engine, qe = db
        rid = _fill(qe)
        want = qe.execute_one(AGG_SQL).rows()
        cache = qe.executor.cache
        old_ids = {k[2] for k in cache.file_keys(rid)}
        assert old_ids
        engine.compact(rid)  # full merge -> every input file dies
        live = set(engine.region(rid).files)
        assert not ({k[2] for k in cache.file_keys(rid)} - live)
        rows_close(qe.execute_one(AGG_SQL).rows(), want)

    def test_retention_expiry_kills_expired_blocks(self, db):
        from greptimedb_tpu.maintenance.retention import run_expiry

        engine, qe = db
        rid = _fill(qe)
        qe.execute_one(AGG_SQL)
        cache = qe.executor.cache
        assert cache.file_keys(rid)
        region = engine.region(rid)
        newest = max(m.ts_max for m in region.files.values())
        res = run_expiry(region, ttl_ms=1, now_ms=newest + 2)
        assert res["removed"] >= 1
        live = set(region.files)
        assert not ({k[2] for k in cache.file_keys(rid)} - live)

    def test_drop_clears_region_blocks(self, db):
        engine, qe = db
        rid = _fill(qe)
        # unflushed rows too, so snapshot-anchored entries exist
        qe.execute_one(
            "INSERT INTO t (host, v, ts) VALUES ('h0', 7.0, 888000)")
        qe.execute_one(AGG_SQL)
        cache = qe.executor.cache
        assert cache.file_keys(rid)
        qe.execute_one("DROP TABLE t")
        assert not cache.file_keys(rid)
        # snap-anchored entries die with the region as well: TRUNCATE
        # reuses the region_id AND resets data_version, so a survivor
        # could collide with a post-truncate re-ingest
        with cache._lock:
            assert not [k for k in cache._lru
                        if k[0] == "snap" and k[1] == rid]

    def test_truncate_reingest_serves_fresh_data(self, db):
        """TRUNCATE + same-shaped re-ingest must never serve a
        pre-truncate HBM block. Memtable-only on both sides ON PURPOSE:
        the recreated region restarts data_version, so the snapshot key
        ("snap", rid, 1, fingerprint, ...) COLLIDES exactly — without
        the drop-seam region invalidation this query returns the old
        table's sums (verified: sum 50.0 instead of 10.0)."""
        engine, qe = db
        qe.execute_one(
            "CREATE TABLE t (host STRING, v DOUBLE, ts TIMESTAMP(3) "
            "TIME INDEX, PRIMARY KEY(host)) WITH (append_mode = 'true')")
        rows = [f"('h{h}', 5.0, {p * 1000})"
                for p in range(10) for h in range(3)]
        qe.execute_one("INSERT INTO t (host, v, ts) VALUES "
                       + ",".join(rows))
        sql = ("SELECT host, sum(v), count(v) FROM t GROUP BY host "
               "ORDER BY host")
        qe.execute_one(sql)  # uploads memtable blocks under version 1
        qe.execute_one("TRUNCATE TABLE t")
        rows = [f"('h{h}', 1.0, {p * 1000})"
                for p in range(10) for h in range(3)]
        qe.execute_one("INSERT INTO t (host, v, ts) VALUES "
                       + ",".join(rows))
        got = qe.execute_one(sql).rows()
        for r in got:
            assert float(r[1]) == 10.0, got  # 10 x 1.0, not stale 50.0
            assert int(r[2]) == 10

    def test_dead_file_tombstone_blocks_racing_insert(self, db):
        """invalidate_files racing an in-flight build: the late insert
        for a dead file must be refused, not pinned into HBM."""
        engine, qe = db
        rid = _fill(qe)
        qe.execute_one(AGG_SQL)
        cache = qe.executor.cache
        key = cache.file_keys(rid)[0]
        arr = cache._lru[key]
        cache.invalidate_files(rid, [key[2]])
        assert key not in cache._lru
        cache._store(key, arr)  # the racing build landing late
        assert key not in cache._lru, "dead-file block re-entered HBM"
        # a LIVE file's insert still lands
        live = [k for k in cache.file_keys(rid) if k[2] != key[2]]
        assert live

    def test_region_epoch_blocks_racing_snap_insert(self, db):
        """invalidate_region (TRUNCATE/DROP) racing an in-flight snap
        build: data_versions ARE reused after a truncate, so the late
        insert must be refused by the epoch check — otherwise the
        pre-truncate block serves once the recreated region's
        data_version climbs back to the colliding value."""
        engine, qe = db
        rid = _fill(qe, files=1)
        # unflushed rows -> the scan has a memtable tail (snap-keyed)
        qe.execute_one(
            "INSERT INTO t (host, v, ts) VALUES ('h1', 2.0, 500000)")
        qe.execute_one(AGG_SQL)
        cache = qe.executor.cache
        with cache._lock:
            key = next(k for k in cache._lru
                       if k[0] == "snap" and k[1] == rid)
            arr = cache._lru[key]
            epoch = cache._key_epoch_locked(key)  # build starts here
        cache.invalidate_region(rid)              # ...TRUNCATE lands...
        assert key not in cache._lru
        cache._store(key, arr, epoch=epoch)       # ...build lands late
        assert key not in cache._lru, "stale snap block re-entered HBM"
        # a post-invalidation build (fresh epoch) still lands
        with cache._lock:
            fresh = cache._key_epoch_locked(key)
        assert fresh != epoch
        cache._store(key, arr, epoch=fresh)
        assert key in cache._lru

    def test_newer_snapshot_generation_retires_older(self, db):
        """Memtable-tail (snapshot-anchored) uploads of an older data
        version die on the first newer insert instead of lingering."""
        engine, qe = db
        rid = _fill(qe, files=1)
        # unflushed rows -> the scan has a memtable tail (snap-keyed)
        qe.execute_one(
            "INSERT INTO t (host, v, ts) VALUES ('h1', 2.0, 500000)")
        qe.execute_one(AGG_SQL)
        cache = qe.executor.cache

        def snap_versions():
            with cache._lock:
                return {k[2] for k in cache._lru
                        if k[0] == "snap" and k[1] == rid}

        v1 = snap_versions()
        qe.execute_one(
            "INSERT INTO t (host, v, ts) VALUES ('h1', 3.0, 501000)")
        qe.execute_one(AGG_SQL)
        v2 = snap_versions()
        assert v2 and not (v1 & v2), (v1, v2)


# ---- donation on the chunked accumulator loops -----------------------------


class TestDonation:
    def _fill_and_query(self, tmp_path, monkeypatch, donate):
        monkeypatch.setenv("GREPTIMEDB_TPU_STREAM_THRESHOLD_ROWS", "1")
        monkeypatch.setenv("GREPTIMEDB_TPU_STREAM_BLOCK_ROWS", "1024")
        monkeypatch.setenv("GREPTIMEDB_TPU_DONATE", donate)
        engine = RegionEngine(EngineConfig(
            data_dir=str(tmp_path / f"don_{donate}"),
            maintenance_workers=0))
        qe = QueryEngine(Catalog(MemoryKv()), engine)
        try:
            _fill(qe, files=3, hosts=6, points=300)
            assert qe.executor.tier_for(object(), 10, streaming=True)
            out = qe.execute_one(AGG_SQL).rows()
            path = qe.executor.last_path
            return out, path
        finally:
            engine.close()

    def test_donated_fold_matches_copying_fold(self, tmp_path,
                                               monkeypatch):
        """The donate_argnums accumulator loop must be value-identical
        to the copying loop (aliasing bug = wrong numbers, not a
        crash)."""
        import warnings

        with warnings.catch_warnings():
            # CPU backend can't honor donation; the fallback copy is
            # exactly what this parity test measures
            warnings.simplefilter("ignore", UserWarning)
            on, path_on = self._fill_and_query(tmp_path, monkeypatch, "1")
            off, path_off = self._fill_and_query(tmp_path, monkeypatch,
                                                 "off")
        assert path_on.startswith("stream"), path_on
        assert path_off.startswith("stream"), path_off
        assert on == off

    def test_donate_default_tracks_backend(self, monkeypatch):
        # auto: on for accelerator backends, off on CPU (XLA:CPU can't
        # alias these buffers and would warn per trace)
        monkeypatch.delenv("GREPTIMEDB_TPU_DONATE", raising=False)
        assert ph._donate_stream_buffers() == (
            jax.default_backend() != "cpu")
        monkeypatch.setenv("GREPTIMEDB_TPU_DONATE", "on")
        assert ph._donate_stream_buffers()
        monkeypatch.setenv("GREPTIMEDB_TPU_DONATE", "off")
        assert not ph._donate_stream_buffers()


# ---- chaos: fused kernel failure mid-query ---------------------------------


@pytest.fixture
def fused_latch_reset():
    yield
    ph._FUSED_DISABLED["flag"] = False


class TestFusedDegradation:
    @pytest.fixture(autouse=True)
    def _classic_paths(self, monkeypatch):
        # these tests pin the fused-vs-scatter machinery; the partial-
        # aggregate cache would intercept the shape before it reaches it
        monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")

    def test_kernel_failure_degrades_to_scatter(self, db, monkeypatch,
                                                fused_latch_reset):
        """A fused-kernel failure mid-query must answer THAT query via
        the XLA scatter path, latch the kernel off for later queries,
        and count the degradation."""
        from greptimedb_tpu.utils.metrics import PALLAS_DISPATCHES

        engine, qe = db
        _fill(qe)
        want = qe.execute_one(AGG_SQL).rows()  # normal (scatter) path
        monkeypatch.setattr(ph.PhysicalExecutor, "_fused_ok",
                            lambda self, *a, **k: True)

        def boom(*a, **k):
            raise RuntimeError("injected Mosaic failure")

        monkeypatch.setattr(ph, "_agg_scan_fused", boom)
        from greptimedb_tpu.utils.metrics import DEVICE_DEGRADATIONS

        before = PALLAS_DISPATCHES.get(kernel="fused_agg_failed")
        degraded = DEVICE_DEGRADATIONS.get(kind="fused_latch")
        got = qe.execute_one(AGG_SQL).rows()
        assert got == want  # the query still answered
        assert qe.executor.last_path == "dense_prepared"
        assert ph._FUSED_DISABLED["flag"] is True
        assert PALLAS_DISPATCHES.get(
            kernel="fused_agg_failed") == before + 1
        # never silent: counted, and the failure's own message is kept
        # for GET /v1/device
        assert DEVICE_DEGRADATIONS.get(kind="fused_latch") == degraded + 1
        status = qe.executor.device_status()
        assert status["pallas"]["fused_disabled"] is True
        assert any(d["kind"] == "fused_latch"
                   and "injected Mosaic failure" in d["error"]
                   for d in status["degradations"])
        # latched: later queries skip the fused attempt outright
        qe.execute_one(AGG_SQL)
        assert qe.executor.last_path == "dense_prepared"

    def test_fused_serves_after_latch_reset(self, db, monkeypatch,
                                            fused_latch_reset):
        """With the latch clear and the kernel healthy, the same query
        runs the fused path (interpret mode on CPU) and matches the
        scatter result."""
        engine, qe = db
        _fill(qe)
        want = qe.execute_one(AGG_SQL).rows()
        assert qe.executor.last_path == "dense_prepared"
        monkeypatch.setattr(ph.PhysicalExecutor, "_fused_ok",
                            lambda self, *a, **k: True)
        got = qe.execute_one(AGG_SQL).rows()
        assert qe.executor.last_path == "dense_fused"
        for a, b in zip(want, got):
            assert a[0] == b[0]
            np.testing.assert_allclose(
                [float(x) for x in a[1:]], [float(y) for y in b[1:]],
                rtol=1e-9)

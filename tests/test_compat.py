"""Cross-version compatibility (reference tests/compat/test-compat.sh:
old-version data dirs must open under new code; incompatible versions
must refuse loudly, never corrupt).

`tests/fixtures/compat_r3` is a committed golden data dir written by the
ROUND-3 build (commit 26ec8be): zstd-compressed SST + inverted index +
manifest without format stamps + WAL holding unflushed rows and a DELETE
tombstone. Round-4+ code must replay all of it bit-correctly."""

import json
import os
import shutil

import pytest

from greptimedb_tpu.catalog import Catalog, FileKv
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.storage.format import FORMAT_VERSIONS, FormatError

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "compat_r3")

# what the round-3 build printed for:
#   SELECT host, region, usage FROM cpu ORDER BY host, ts
R3_ROWS = [["a", "us", 1.5], ["a", "us", 2.5], ["a", "us", 3.5],
           ["c", "ap", 4.0]]


@pytest.fixture
def old_dir(tmp_path):
    # opens mutate (WAL replay state, format stamp): work on a copy
    dst = tmp_path / "compat_r3"
    shutil.copytree(FIXTURE, dst)
    return str(dst)


def _open(d):
    engine = RegionEngine(EngineConfig(data_dir=os.path.join(d, "data")))
    qe = QueryEngine(Catalog(FileKv(os.path.join(d, "catalog.json"))),
                     engine)
    return engine, qe


def test_open_r3_dir_and_read(old_dir):
    engine, qe = _open(old_dir)
    try:
        r = qe.execute_one(
            "SELECT host, region, usage FROM cpu ORDER BY host, ts")
        assert r.rows() == R3_ROWS
        # the WAL-resident delete must still hide host b
        r = qe.execute_one("SELECT count(*) FROM cpu WHERE host = 'b'")
        assert r.rows() == [[0]]
    finally:
        engine.close()


def test_pruned_read_of_r3_file_plans_from_its_own_footer(old_dir):
    """A file written before row groups shrank (and before the read
    plan was kept per file) goes through the same plan: its layout is
    whatever its own footer says."""
    engine, qe = _open(old_dir)
    try:
        r = qe.execute_one(
            "SELECT host, usage FROM cpu WHERE host = 'a' AND ts >= 0 "
            "AND ts < 10000000 ORDER BY ts")
        assert r.rows() == [["a", 1.5], ["a", 2.5], ["a", 3.5]]
        region = next(iter(engine.regions.values()))
        (fid,) = region.files
        fp = region.sst_reader.file_plan(fid, "ts")
        assert fp.group_rows.tolist() == [region.files[fid].num_rows]
        assert (fp.ts_min[0], fp.ts_max[0]) == (
            region.files[fid].ts_min, region.files[fid].ts_max)
    finally:
        engine.close()


def test_write_new_into_r3_dir(old_dir):
    engine, qe = _open(old_dir)
    try:
        qe.execute_one("INSERT INTO cpu VALUES ('d', 'sa', 7.0, 70.0, 9000)")
        qe.execute_one("ADMIN flush_table('cpu')")  # new lz4 SST beside zstd
        r = qe.execute_one(
            "SELECT host, usage FROM cpu ORDER BY host, ts")
        assert r.rows() == [["a", 1.5], ["a", 2.5], ["a", 3.5],
                            ["c", 4.0], ["d", 7.0]]
    finally:
        engine.close()
    # reopen: mixed-codec SSTs + fresh manifest actions replay clean
    engine, qe = _open(old_dir)
    try:
        r = qe.execute_one("SELECT count(*) FROM cpu")
        assert r.rows() == [[5]]
    finally:
        engine.close()


def test_r3_dir_gets_stamped_on_open(old_dir):
    data = os.path.join(old_dir, "data")
    assert not os.path.exists(os.path.join(data, "FORMAT.json"))
    engine, qe = _open(old_dir)
    engine.close()
    with open(os.path.join(data, "FORMAT.json")) as f:
        assert json.load(f)["versions"] == FORMAT_VERSIONS


def test_newer_stamp_refuses_open(tmp_path):
    d = tmp_path / "data"
    d.mkdir()
    (d / "FORMAT.json").write_text(json.dumps(
        {"versions": dict(FORMAT_VERSIONS, sst=FORMAT_VERSIONS["sst"] + 1)}))
    with pytest.raises(FormatError, match="newer build"):
        RegionEngine(EngineConfig(data_dir=str(d)))


def test_newer_manifest_action_refuses(tmp_path):
    from greptimedb_tpu.storage.manifest import RegionManifestState

    st = RegionManifestState()
    with pytest.raises(FormatError, match="manifest action format"):
        st.apply({"format": 99, "kind": "truncate"})

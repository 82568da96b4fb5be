"""Tiered execution routing (physical.tier_for / accelerator_link):
policy decisions under different link shapes and modes. Tests run on
the CPU backend, so the link is co-located by construction; remote-link
policy is exercised by stubbing the probe."""

import jax
import pytest

import greptimedb_tpu.query.physical as ph
from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig


@pytest.fixture
def executor(tmp_path):
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path)))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    yield qe.executor
    engine.close()


def test_cpu_backend_always_device(executor):
    assert jax.default_backend() == "cpu"
    assert executor.tier_for(object(), 100) == "device"
    assert executor.tier_for(None, 10**9) == "device"


def test_link_probe_on_cpu_is_colocated():
    link = ph.accelerator_link()
    assert link["colocated"] is True


class TestRemoteLinkPolicy:
    """Stub a slow, not-attached link and a non-cpu backend."""

    @pytest.fixture(autouse=True)
    def remote_link(self, monkeypatch, executor):
        monkeypatch.setattr(ph, "_LINK", {
            "backend": "tpu", "rtt_ms": 66.0, "d2h_mbps": 11.0,
            "colocated": False})
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # the test conftest builds an 8-device CPU mesh; a mesh pins the
        # device tier, which is not what these policy tests exercise
        monkeypatch.setattr(executor, "mesh", None)
        yield
        ph._LINK = None

    def test_small_aggregate_takes_host(self, executor):
        assert executor.tier_for(object(), 1000) == "host"

    def test_large_aggregate_takes_device(self, executor):
        assert executor.tier_for(object(), 20_000_000) == "device"

    def test_raw_queries_take_host(self, executor):
        assert executor.tier_for(None, 20_000_000) == "host"

    def test_streaming_takes_host(self, executor):
        assert executor.tier_for(object(), 100_000_000,
                                 streaming=True) == "host"

    def test_off_mode_pins_device(self, executor, monkeypatch):
        monkeypatch.setenv("GREPTIMEDB_TPU_HOST_TIER", "off")
        assert executor.tier_for(object(), 1000) == "device"

    def test_force_mode_pins_host(self, executor, monkeypatch):
        monkeypatch.setenv("GREPTIMEDB_TPU_HOST_TIER", "force")
        assert executor.tier_for(object(), 20_000_000) == "host"

    def test_mesh_overrides_to_device(self, executor):
        executor.mesh = object()
        assert executor.tier_for(object(), 1000) == "device"


def test_colocated_link_pins_device(executor, monkeypatch):
    monkeypatch.setattr(ph, "_LINK", {
        "backend": "tpu", "rtt_ms": 0.2, "d2h_mbps": 10_000.0,
        "colocated": True})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(executor, "mesh", None)
    try:
        assert executor.tier_for(None, 100) == "device"
        assert executor.tier_for(object(), 100) == "device"
    finally:
        ph._LINK = None

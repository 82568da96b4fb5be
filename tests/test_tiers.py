"""Tiered execution routing (PhysicalExecutor.tier_for, the delegate of
query/tier.py's TierRouter.choose): the decision under each backend and
GREPTIMEDB_TPU_HOST_TIER mode. Tests run on the CPU backend; an
accelerator is exercised by stubbing jax.default_backend. The link
probe is reported, never routed on, so no test stubs it."""

import jax
import pytest

import greptimedb_tpu.query.tier as tiering
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
    link = tiering.accelerator_link()
    assert link["colocated"] is True


class TestAcceleratorPolicy:
    """Stub a non-cpu backend."""

    @pytest.fixture(autouse=True)
    def accelerator(self, monkeypatch, executor):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # the test conftest builds an 8-device CPU mesh; a mesh never
        # consults the host-tier mode, which is what these tests do
        monkeypatch.setattr(executor, "mesh", None)

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
    """An attached chip in auto mode: everything is chosen for the
    device, whatever the size and whether or not it aggregates."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(executor, "mesh", None)
    assert executor.tier_for(None, 100) == "device"
    assert executor.tier_for(object(), 100) == "device"
    assert executor.tier_for(object(), 100_000_000,
                             streaming=True) == "device"

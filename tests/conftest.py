"""Test harness: force an 8-device virtual CPU mesh before jax initializes.

Mirrors the reference's test strategy (SURVEY.md §4): every distributed
component runs single-process against in-memory fakes; multi-chip sharding
is validated on virtual devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (schedule + jitter "
        "seeded by GTPU_CHAOS_SEED; the seed is printed on failure)")
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (-m 'not slow') — the full "
        "compound-fault scenario matrix; run via pytest -m slow or "
        "tools/run_scenarios.py")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.failed and item.get_closest_marker("chaos") is not None:
        # any red chaos run must be replayable: surface the seed that
        # drove this run's fault schedule
        seed = os.environ.get("GTPU_CHAOS_SEED", "0")
        rep.sections.append(
            ("chaos seed",
             f"replay this failure with GTPU_CHAOS_SEED={seed}"))

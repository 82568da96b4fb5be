"""Frontend concurrency plane (the ISSUE-6 subsystem).

The layer between the protocol servers (L6/L5) and the query engine
(L3) that makes fleet-scale concurrent traffic cheap, three boxes in
one direction — admission -> plan cache -> fast lane -> executor:

- `admission`    — bounded admission queue + per-tenant weighted fair
                   scheduling with typed `Overloaded` rejection; the
                   slot is released at execute-done, so an answer's
                   serialization never holds one;
- `plan_cache`   — shape-keyed parameterized logical-plan cache (one
                   plan + one XLA executable shared by thousands of
                   near-identical dashboard queries), invalidated on
                   DDL/schema/rollup-state change;
- `fast_lane`    — text-keyed templates in front of the plan cache: a
                   repeat shape binds and executes without parsing, and
                   concurrent identical requests share one execution
                   (single flight), bit-for-bit with serial.

An answer is encoded on the thread that owns the request, by
`servers/encode.py`, inside `tracing.stage("encode")`.

`QueryEngine` routes every statement through the plane; configuration
comes from the `[concurrency]` options section via `configure()` (env
vars prefixed GTPU_ override for benches/tests).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass

from greptimedb_tpu.concurrency.admission import (  # noqa: F401
    AdmissionController,
    Overloaded,
    parse_weights,
)
from greptimedb_tpu.concurrency.fast_lane import FastLane
from greptimedb_tpu.concurrency.plan_cache import PlanCache

__all__ = ["ConcurrencyConfig", "ConcurrencyPlane", "Overloaded",
           "configure", "current_config"]


@dataclass
class ConcurrencyConfig:
    enabled: bool = True
    #: concurrent statements in execution; 0 = auto (max(32, 4*cpu))
    max_concurrency: int = 0
    queue_size: int = 512
    queue_timeout_s: float = 30.0
    #: "tenantA=3,tenantB=1" weighted round-robin shares; unlisted = 1
    tenant_weights: str = ""
    plan_cache_entries: int = 512
    #: text-keyed parse-free serving fast lane (concurrency/fast_lane.py)
    fast_lane: bool = True
    #: fast-lane template capacity; 0 disables
    fast_lane_entries: int = 512


_config = ConcurrencyConfig()
_config_lock = threading.Lock()


def configure(cfg: ConcurrencyConfig) -> None:
    """Install the process-wide default config (options layer calls
    this; engines built afterwards pick it up)."""
    global _config
    with _config_lock:
        _config = cfg


def _env_num(name, cur, cast):
    v = os.environ.get(name)
    if not v:
        return cur
    try:
        return cast(v)
    except ValueError:
        return cur


def current_config() -> ConcurrencyConfig:
    """The installed config with env overrides applied (benches/tests
    A/B the plane without an options object)."""
    with _config_lock:
        cfg = ConcurrencyConfig(**vars(_config))
    cfg.enabled = _env_num("GTPU_CONCURRENCY", int(cfg.enabled), int) != 0
    cfg.max_concurrency = _env_num("GTPU_MAX_CONCURRENCY",
                                   cfg.max_concurrency, int)
    cfg.plan_cache_entries = _env_num("GTPU_PLAN_CACHE_ENTRIES",
                                      cfg.plan_cache_entries, int)
    cfg.fast_lane = _env_num("GTPU_FAST_LANE", int(cfg.fast_lane),
                             int) != 0
    cfg.fast_lane_entries = _env_num("GTPU_FAST_LANE_ENTRIES",
                                     cfg.fast_lane_entries, int)
    return cfg


class ConcurrencyPlane:
    def __init__(self, cfg: ConcurrencyConfig | None = None):
        cfg = cfg or current_config()
        self.cfg = cfg
        limit = cfg.max_concurrency
        if limit <= 0:
            limit = max(32, 4 * (os.cpu_count() or 8))
        self.admission = AdmissionController(
            limit, cfg.queue_size, cfg.queue_timeout_s,
            parse_weights(cfg.tenant_weights),
            enabled=cfg.enabled)
        self.plan_cache = PlanCache(
            cfg.plan_cache_entries if cfg.enabled else 0)
        # the fast lane needs the plan cache: its entries hold
        # plan-cache entries, so disabling the cache disables the lane
        self.fast_lane = FastLane(
            cfg.fast_lane_entries,
            enabled=(cfg.enabled and cfg.fast_lane
                     and self.plan_cache.enabled))
        # the serving fabric (shm/): attach once per process and
        # register the scrape-time collector of its gauges — a no-op
        # when GTPU_SHM_FABRIC is off. Compiled executables are shared through JAX's persistent
        # compilation cache, which every process of a checkout places by
        # the one rule in greptimedb_tpu/__init__.py
        from greptimedb_tpu import shm

        if cfg.enabled and shm.get_fabric() is not None:
            shm.install_stats_collector()

    # ---- tenancy -----------------------------------------------------------

    @staticmethod
    def tenant_of(ctx) -> str:
        t = getattr(ctx, "tenant", None)
        if t:
            return str(t)
        user = getattr(ctx, "user", None)
        name = getattr(user, "username", None)
        return name or "default"

    # ---- lifecycle ---------------------------------------------------------

    def shutdown(self) -> None:
        """Nothing to release: the plane owns no thread or process. Kept
        because the benchmark's loaders (`benchmark/loaders/`,
        `benchmark/harness/bulk_load.py`) call it before they close
        their engine, and those files are the yardstick."""

    # ---- invalidation ------------------------------------------------------

    def invalidate_table(self, db=None, name=None) -> int:
        # one seam for all layers: DDL hooks and the remote-catalog
        # watch invalidate plan shapes, text templates, AND (fabric on)
        # every peer process's published artifacts for the table
        self._fabric_invalidate(db, name)
        self.fast_lane.invalidate_table(db, name)
        return self.plan_cache.invalidate_table(db, name)

    @staticmethod
    def _fabric_invalidate(db, name) -> None:
        """Bump the (db, table) fabric version so artifacts peers
        published under the old one die on their next adopt check; a
        widened match (None field — the remote watch can't tell what
        moved) wipes the whole fabric."""
        from greptimedb_tpu import shm
        from greptimedb_tpu.shm.fabric import FabricError
        from greptimedb_tpu.utils.metrics import SHM_FABRIC_EVENTS

        fabric = shm.get_fabric()
        if fabric is None:
            return
        try:
            if db is None or name is None:
                fabric.wipe()
            else:
                fabric.bump_version(db, name)
            SHM_FABRIC_EVENTS.inc(event="invalidate", kind="fabric")
        except (FabricError, OSError, ValueError):
            shm.detach()

"""Global runtime configuration (the analog of reference MitoConfig /
QueryEngineState knobs, layered defaults <- env vars)."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np


def _platform() -> str:
    """Platform of the default backend. A failed backend init raises:
    the process was told (or defaulted) to use an accelerator it cannot
    reach, and carrying on as if it were a CPU would hide that."""
    return jax.devices()[0].platform


def require_stated_platform() -> dict:
    """Resolve the default backend at service start-up and refuse a
    platform nobody asked for. JAX itself falls back to the CPU when no
    accelerator plugin initialises and JAX_PLATFORMS is unset; a
    serving process that owns the device tier must not take that
    fallback silently — running on the CPU is fine when JAX_PLATFORMS
    says so. Returns {"platform", "device_kind", "count"} for the
    start-up line."""
    devs = jax.devices()
    platform = devs[0].platform
    stated = [p.strip() for p in
              os.environ.get("JAX_PLATFORMS", "").lower().split(",")
              if p.strip()]
    if platform == "cpu" and "cpu" not in stated:
        raise RuntimeError(
            "JAX found no accelerator and fell back to the CPU, but "
            "JAX_PLATFORMS does not name cpu; set JAX_PLATFORMS=cpu to "
            "serve from the CPU deliberately, or JAX_PLATFORMS=tpu,cpu "
            "to make a missing chip a start-up error")
    return {"platform": platform, "device_kind": devs[0].device_kind,
            "count": len(devs)}


def compute_dtype() -> np.dtype:
    """Float dtype for field values inside device kernels. TPU has no
    native f64 (emulated, slow) — default f32 there; CPU keeps f64 so
    results are bit-comparable with numpy oracles in tests.

    Override with GREPTIMEDB_TPU_COMPUTE_DTYPE=float32|float64|bfloat16.
    """
    env = os.environ.get("GREPTIMEDB_TPU_COMPUTE_DTYPE")
    if env:
        return jnp.dtype(env)
    return jnp.dtype(jnp.float32 if _platform() == "tpu" else jnp.float64)


def query_mesh():
    """Device mesh for distributed query execution, or None when a single
    device is visible (the common standalone case). All devices ride the
    "shard" (row) axis — the collective MergeScan (SURVEY §2.6: reference
    gathers region streams point-to-point at merge_scan.rs:122; here
    partial aggregates combine with psum over ICI).

    GREPTIMEDB_TPU_MESH=off disables; =NxM forces an (shard, field) shape.
    """
    env = os.environ.get("GREPTIMEDB_TPU_MESH", "auto")
    if env.lower() in ("off", "0", "none"):
        return None
    # LOCAL devices only: the scan data plane device_puts process-local
    # arrays, which cannot target another host's chips. Cross-host
    # distribution rides the PlanFragment pushdown over Flight instead
    # (parallel/mesh.init_distributed docstring has the division of
    # labor).
    local = jax.local_devices()
    n = len(local)
    from greptimedb_tpu.parallel.mesh import make_mesh

    if env not in ("auto", ""):
        s, _, f = env.partition("x")
        shape = (int(s), int(f or 1))
        if shape[0] * shape[1] > n:
            raise ValueError(f"mesh {shape} needs {shape[0]*shape[1]} devices, have {n}")
        return make_mesh(local[: shape[0] * shape[1]], shape)
    if n <= 1:
        return None
    return make_mesh(local)


def dense_groups_max() -> int:
    """Largest dense group-id product the aggregate kernel materializes as
    [G, F] planes (1M groups x 10 f64 fields = 80 MiB per plane). Beyond
    this the sparse (sort-compact) path runs — the TPU answer to the
    reference's unbounded hash aggregate (SURVEY §7 hard part)."""
    return int(os.environ.get("GREPTIMEDB_TPU_DENSE_GROUPS_MAX", str(1 << 20)))


def sparse_groups_max() -> int:
    """Cap on *observed* distinct groups in the sparse aggregate path
    (output planes are [cap, F]); queries observing more raise."""
    return int(os.environ.get("GREPTIMEDB_TPU_SPARSE_GROUPS_MAX", str(1 << 22)))


def sparse_groups_min() -> int:
    """Key products at or above this ALSO take the sparse sort-compact
    path even when they fit the dense budget (0 = off, the default:
    dense wins while its planes fit). The lever for date_bin queries
    whose bucket domain blows the fused kernel's 4096-segment envelope
    but whose observed groups compact well — the tiled sparse-fused
    path keeps them on the kernel."""
    return int(os.environ.get("GREPTIMEDB_TPU_SPARSE_GROUPS_MIN", "0"))


def stream_threshold_rows() -> int:
    """Aggregate scans at or above this row estimate run the streaming
    (bounded-memory) path: lazy row-group chunks -> fixed-shape device
    blocks -> incremental on-device combine, instead of materializing the
    whole scan on host (reference streams lazy row groups with a page
    cache, mito2/src/sst/parquet/row_group.rs). Below the threshold the
    materialized path keeps whole column snapshots HBM-cached across
    repeated queries (the TSBS warm-cache regime); the default hands over
    to streaming where those snapshots stop fitting."""
    return int(os.environ.get("GREPTIMEDB_TPU_STREAM_THRESHOLD_ROWS",
                              str(32 << 20)))


def stream_block_rows() -> int:
    """Fixed device block shape for the streaming path (one compile)."""
    return int(os.environ.get("GREPTIMEDB_TPU_STREAM_BLOCK_ROWS",
                              str(2 << 20)))


def mesh_min_rows() -> int:
    """Scans below this row count skip the mesh path: per-shard dispatch
    overhead beats the parallelism on tiny results."""
    return int(os.environ.get("GREPTIMEDB_TPU_MESH_MIN_ROWS", "65536"))


def default_hash_partitions() -> int:
    """Hash-partition count for cluster CREATE TABLE without an explicit
    PARTITION clause ([partition] default_hash_regions); 0/1 = one
    region (the standalone default)."""
    return int(os.environ.get("GREPTIMEDB_TPU_DEFAULT_HASH_REGIONS", "0"))


def hash_partition_columns() -> list:
    """Columns for default hash partitioning ([partition] hash_columns,
    comma-separated); empty = the table's leading tag column."""
    env = os.environ.get("GREPTIMEDB_TPU_HASH_PARTITION_COLUMNS", "")
    return [s.strip() for s in env.split(",") if s.strip()]


def device_cache_bytes() -> int:
    """HBM budget for the device block cache (reference: CacheManager page
    cache, mito2/src/cache.rs:53-61 — here the 'page cache' IS device HBM).
    """
    env = os.environ.get("GREPTIMEDB_TPU_DEVICE_CACHE_BYTES")
    if env:
        return int(env)
    if _platform() == "tpu":
        return 8 << 30
    # CPU backend: "device" memory IS host RAM — budget a quarter of it
    # (reference page cache defaults to mem/16; the block cache carries
    # the whole warm working set here, so it gets more)
    try:
        ram = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError):
        ram = 8 << 30
    return max(1 << 30, min(ram // 4, 32 << 30))


def host_tier_mode() -> str:
    """Tiered execution policy (query/tier.py), on an accelerator
    without a mesh: "auto" runs on the chip and serves a shape's first
    touch from the host (CPU) tier while its device executable compiles,
    "off" pins everything to the chip (the first touch waits for the
    compile), "force" pins everything to the host tier (A/B measurement
    + emergency bypass)."""
    return os.environ.get("GREPTIMEDB_TPU_HOST_TIER", "auto").lower()


def prewarm_enabled() -> bool:
    """Background pre-warm of the dominant Pallas kernel shapes at
    executor construction (region-open time), so first-query latency
    stops hiding the Mosaic compile. GREPTIMEDB_TPU_PREWARM=off
    disables; default on for accelerator platforms only."""
    env = os.environ.get("GREPTIMEDB_TPU_PREWARM")
    if env is not None:
        return env.lower() not in ("0", "false", "off")
    return _platform() == "tpu"

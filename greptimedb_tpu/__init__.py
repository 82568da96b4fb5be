"""greptimedb_tpu — a TPU-native time-series database framework.

A from-scratch re-design of the capabilities of GreptimeDB (the reference
surveyed in SURVEY.md): SQL + PromQL engines, LSM columnar storage over
Parquet with WAL durability, region partitioning with a metadata plane, and
continuous aggregation — with the scan/aggregate/PromQL hot path executed as
XLA-compiled kernels on TPU via JAX (segment reductions for group-by,
sort-based merge-dedup, blockwise windowed kernels for time buckets and
PromQL range vectors, sharded partial aggregation over a jax.sharding.Mesh).

Layer map (mirrors SURVEY.md §1, re-designed TPU-first):

  servers/    wire protocols (HTTP SQL/PromQL, Influx line protocol, ...)
  query/      SQL logical plan -> jit'd device stages (QueryEngine)
  sql/        SQL parser (hand-written; reference forked sqlparser-rs)
  promql/     PromQL parser + compiler onto the same plan algebra
  catalog/    table catalog over a KvBackend trait (memory impl first)
  storage/    region engine: memtable, WAL, Parquet SST, manifest, flush
  ops/        the device kernel library (the differentiator)
  parallel/   mesh construction, sharded partial aggregation
  datatypes/  Arrow-backed type system with time-index metadata
"""

import os as _os

import jax

# Timestamps are int64 nanoseconds end-to-end (reference:
# src/common/time/src/timestamp.rs); sums over billions of rows need f64
# accumulators on CPU test paths. TPU kernels down-cast hot-loop field data
# to f32/bf16 explicitly where profitable.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache — the one rule, in this one place.
# Cold compiles dominate an accelerator process's start-up, and a chip
# run often lands on a fresh machine, so the cache must be placeable
# from outside: where JAX_COMPILATION_CACHE_DIR is set, JAX reads it
# itself and nothing here names a directory. Where it is not set, an
# accelerator-capable process caches at a FIXED path inside the checkout
# (a directory that moves between runs never hits). A process pinned to
# the CPU (JAX_PLATFORMS=cpu: tests, datanode / metasrv / script
# children) keeps the cache off: tests churn shapes for no reuse. Every compile is cached, however short: a query shape
# compiles many sub-second executables and a warm start should compile
# none of them.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        and _os.environ.get("JAX_PLATFORMS", "") != "cpu":
    _checkout = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_dir",
                      _os.path.join(_checkout, ".jax_cache"))
if jax.config.jax_compilation_cache_dir:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

# Runtime lock-order validation (lint/lockdep.py): GTPU_LOCKDEP=1
# wraps threading.Lock/RLock *before* any repo module constructs one,
# so every lock the storage/concurrency/maintenance planes create is
# tracked and tier-1 can assert the observed nesting stays acyclic.
if _os.environ.get("GTPU_LOCKDEP") == "1":
    from greptimedb_tpu.lint import lockdep as _lockdep

    _lockdep.install()

__version__ = "0.1.0"

"""Shared bounded thread pool for parallel SST scan decode.

One pool per datanode process (the reference sizes its `SeqScan`
parallelism per ScanRegion against a shared runtime, mito2
read/scan_region.rs): every region's scan fans its parquet
read+decode across the same workers, so the global decode concurrency
is bounded no matter how many regions a query touches. Parquet decode
is C++ (pyarrow releases the GIL), so threads buy real parallelism.

Sizing: `decode_threads` from `[scan]` (EngineConfig.scan_decode_threads)
caps the pool; 0 means auto (min(8, cpu_count)). A scan with one file —
or `decode_threads = 1` — bypasses the pool entirely and decodes inline,
which is byte-for-byte the pre-pipeline sequential path (the chaos
parity tests compare against it). The pool only ever grows: a later
region asking for more workers than the pool has re-creates it larger;
the old executor drains its in-flight work before being collected.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

_AUTO_CAP = 8

_lock = threading.Lock()
_pool: ThreadPoolExecutor | None = None
_pool_size = 0


def resolve(decode_threads: int, num_files: int) -> int:
    """Effective worker count for one scan: the configured cap (0 =
    auto) bounded by the files actually needing decode. The env var
    (set by A/B runs and tests) wins over the config object."""
    env = os.environ.get("GREPTIMEDB_TPU_SCAN_DECODE_THREADS")
    if env:
        try:
            decode_threads = int(env)
        except ValueError:
            pass
    if decode_threads <= 0:
        decode_threads = min(_AUTO_CAP, os.cpu_count() or 1)
    return max(1, min(decode_threads, num_files))


def get(workers: int) -> ThreadPoolExecutor:
    """The shared pool, grown to at least `workers`."""
    global _pool, _pool_size
    with _lock:
        if _pool is None or workers > _pool_size:
            _pool_size = max(workers, _pool_size)
            _pool = ThreadPoolExecutor(
                max_workers=_pool_size,
                thread_name_prefix="gtpu-scan-decode")
        return _pool


def submit(pool: ThreadPoolExecutor, fn, *args, **kwargs):
    """Submit a decode unit with the caller's CancelToken re-adopted
    inside the worker (contextvars don't cross threads on their own):
    each unit checkpoints before decoding, so a cancelled or expired
    query's still-queued units unwind typed instead of burning pool
    workers on dead work. Tokenless callers get a plain submit."""
    from greptimedb_tpu.utils import deadline as dl

    token = dl.current()
    if token is None:
        return pool.submit(fn, *args, **kwargs)

    def run():
        with dl.activate(token):
            dl.check("scan decode")
            return fn(*args, **kwargs)

    return pool.submit(run)

"""Device columnar hot set: the HBM-resident analog of the reference's
page cache (mito2/src/cache.rs:53-61 + write/file caches).

The reference amortizes repeated scans through an in-memory parquet page
cache; on TPU the equivalent currency is *device-resident column blocks* —
host->HBM transfer is the scan bottleneck (SURVEY.md §7 hard part #4).

Two classes of entry share one bytes-budgeted LRU:

- **file-anchored** (keys ``("file", region_id, file_id, ...)``): column
  blocks of an immutable SST part. These stay pinned across queries AND
  data versions — a flush only uploads its new file; the old files' HBM
  blocks keep serving. They die with their file, driven by the exact
  same seams that kill the host part cache (compaction swap, retention
  expiry, DROP/TRUNCATE): storage/region.py calls `invalidate_files`
  whenever it drops decoded parts.
- **snapshot-anchored** (keys ``("snap", region_id, data_version, ...)``):
  anything whose rows move with the memtable (memtable tail blocks,
  whole-scan sparse/sharded arrays, synthetic reduced scans). A newer
  data version evicts the region's older snapshot generation on insert,
  so live ingest cannot strand dead uploads in HBM.

Upload/compute overlap: `prefetch(key, build)` schedules the NEXT
block's host-side build (pad + cast + H2D dispatch) on a single
background worker while the caller consumes the current one — double
buffering, so cold dense aggregation approaches max(host build, device
work) instead of their sum. A later `get` joins the in-flight build;
the cumulative hit ratio lands on the
greptimedb_tpu_scan_pipeline_overlap gauge.
"""

from __future__ import annotations

import os
import threading
import weakref
from collections import OrderedDict
from typing import Callable

import jax

from greptimedb_tpu import config
from greptimedb_tpu.utils import device_telemetry
from greptimedb_tpu.utils import ledger
from greptimedb_tpu.utils.metrics import (
    DEVICE_CACHE_EVENTS,
    DEVICE_HOT_SET_BYTES,
    DEVICE_HOT_SET_EVENTS,
    SCAN_PIPELINE_OVERLAP,
)

#: live DeviceCache instances — the storage layer's invalidation seams
#: reach every executor's hot set through the module-level functions
#: below (region.py looks this module up in sys.modules so a pure
#: storage process never imports jax for it)
_CACHES: "weakref.WeakSet" = weakref.WeakSet()


def invalidate_files(region_id: int, file_ids) -> None:
    """Drop file-anchored hot-set entries for removed SSTs — called from
    the same region seams that drop host part-cache entries."""
    for cache in list(_CACHES):
        cache.invalidate_files(region_id, file_ids)


def invalidate_region(region_id: int) -> None:
    for cache in list(_CACHES):
        cache.invalidate_region(region_id)


def upload_prefetch_enabled() -> bool:
    """Double-buffered block upload knob ([scan] upload_prefetch /
    GREPTIMEDB_TPU_UPLOAD_PREFETCH); on by default."""
    return os.environ.get("GREPTIMEDB_TPU_UPLOAD_PREFETCH", "1") \
        not in ("0", "false", "off")


class DeviceCache:
    """Thread-safe: concurrent server threads (and the executor's
    background device warm-up) build/evict under one lock; `build`
    itself runs outside it, so duplicate concurrent builds are possible
    but accounting never double-counts (last writer wins)."""

    def __init__(self, budget_bytes: int | None = None):
        self.budget = budget_bytes if budget_bytes is not None else config.device_cache_bytes()
        self._lru: OrderedDict[tuple, jax.Array] = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        # newest snapshot generation (data_version) seen per region:
        # snap-anchored entries of an older generation die on the first
        # newer insert instead of lingering until LRU pressure
        self._snap_gen: dict[int, int] = {}
        # tombstones for recently-invalidated files (region_id, file_id):
        # a build in flight when invalidate_files ran would otherwise
        # re-insert blocks for the dead file AFTER the drop — keys no
        # future scan can ever request, squatting on HBM budget until
        # unrelated LRU churn. Bounded ring; file ids are never reused.
        self._dead_files: OrderedDict[tuple, None] = OrderedDict()
        # snap keys need the same in-flight-build guard but data_versions
        # ARE reused (TRUNCATE resets them): a per-region epoch, bumped by
        # invalidate_region, is captured when a build starts and checked
        # at _store — a stale-epoch snap block never becomes resident,
        # so a pre-truncate upload can't serve once the recreated
        # region's data_version climbs back to the colliding value
        self._region_epoch: dict[int, int] = {}
        # double-buffer prefetch: in-flight background builds by key;
        # ONE worker on purpose — the pipeline is host-build of block
        # i+1 against consumption of block i, not a second fan-out
        self._inflight: dict[tuple, object] = {}
        self._prefetch_pool = None
        self.prefetch_issued = 0
        self.prefetch_joined = 0
        # scrape-time residency gauge sums _bytes over live caches
        device_telemetry.register_cache(self)
        _CACHES.add(self)

    @staticmethod
    def _is_file_key(key: tuple) -> bool:
        return len(key) >= 3 and key[0] == "file"

    @staticmethod
    def _is_snap_key(key: tuple) -> bool:
        return len(key) >= 3 and key[0] == "snap"

    def get(self, key: tuple, build: Callable[[], jax.Array],
            count_h2d: bool = True) -> jax.Array:
        with self._lock:
            hit = self._lru.get(key)
            if hit is not None:
                self._lru.move_to_end(key)
                self.hits += 1
                DEVICE_CACHE_EVENTS.inc(event="hit")
                DEVICE_HOT_SET_EVENTS.inc(event="hit")
                ledger.cache_event("device_hot_set", "hit")
                return hit
            fut = self._inflight.get(key)
        if fut is not None:
            from greptimedb_tpu.utils import deadline as dl

            try:
                arr = dl.wait_future(fut, "device prefetch join")
            except (dl.DeadlineExceeded, dl.Cancelled):
                raise  # typed unwind, not a failed prefetch
            except Exception:  # noqa: BLE001 — prefetch is best-effort
                arr = None
            if arr is not None:
                # a joined prefetch is NOT a miss: the upload happened,
                # just off-thread — counting it as one would make
                # steady-state double buffering read as a broken cache
                DEVICE_CACHE_EVENTS.inc(event="prefetch_join")
                with self._lock:
                    self.prefetch_joined += 1
                    issued = self.prefetch_issued
                    joined = self.prefetch_joined
                SCAN_PIPELINE_OVERLAP.set(joined / max(issued, 1))
                return arr
        with self._lock:
            self.misses += 1
            epoch = self._key_epoch_locked(key)
        DEVICE_CACHE_EVENTS.inc(event="miss")
        DEVICE_HOT_SET_EVENTS.inc(event="miss")
        ledger.cache_event("device_hot_set", "miss")
        arr = build()
        # a cache-miss build materializes the block on device: that IS
        # the H2D upload this cache exists to amortize. count_h2d=False
        # is for DERIVED entries (e.g. a mesh shard buffer concatenated
        # on-device from already-resident segment uploads) whose build
        # moves no bytes over the link itself.
        if count_h2d:
            device_telemetry.count_h2d(arr.nbytes)
        self._store(key, arr, epoch=epoch)
        return arr

    def resident(self, key: tuple) -> bool:
        """Whether `get(key, ...)` would be served without running its
        build: the block is cached, or a prefetch is building it. A
        peek — it counts no hit and leaves the LRU order alone."""
        with self._lock:
            return key in self._lru or key in self._inflight

    def prefetch(self, key: tuple, build: Callable[[], jax.Array]) -> None:
        """Schedule `build` on the background worker so a later `get`
        finds the block resident (or joins the in-flight build). No-op
        when the key is already cached or being built."""
        with self._lock:
            if key in self._lru or key in self._inflight:
                return
            if self._prefetch_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._prefetch_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="gtpu-hbm-prefetch")
            self.prefetch_issued += 1
            epoch = self._key_epoch_locked(key)
            self._inflight[key] = self._prefetch_pool.submit(
                self._build_prefetched, key, build, epoch)

    def _build_prefetched(self, key: tuple, build, epoch):
        try:
            arr = build()
            device_telemetry.count_h2d(arr.nbytes)
            self._store(key, arr, epoch=epoch)
            return arr
        finally:
            with self._lock:
                self._inflight.pop(key, None)

    def _key_epoch_locked(self, key: tuple):
        """Region epoch a snap-key build starts under (None for other
        keys); caller holds the lock."""
        if self._is_snap_key(key):
            return self._region_epoch.get(key[1], 0)
        return None

    def _store(self, key: tuple, arr, epoch=None) -> None:
        nbytes = arr.nbytes
        if nbytes > self.budget:
            return
        evictions = 0
        pin = False
        with self._lock:
            if (self._is_file_key(key)
                    and (key[1], key[2]) in self._dead_files):
                # the file died while this block was building: serve the
                # caller's array (its scan pinned the file) but never
                # let the dead key into residency
                return
            if self._is_snap_key(key):
                region, version = key[1], key[2]
                if (epoch is not None
                        and self._region_epoch.get(region, 0) != epoch):
                    # the region was invalidated (TRUNCATE/DROP) while
                    # this block was building: serve the caller's array
                    # but never let the pre-invalidation snapshot into
                    # residency — its data_version may recur post-reset
                    return
                gen = self._snap_gen.get(region)
                if gen is not None and version < gen:
                    # an in-flight build for an already-retired
                    # generation landing late: no future scan can
                    # request this key — refuse, don't squat HBM
                    return
                if gen is None or version > gen:
                    # a newer snapshot generation retires the older one:
                    # those uploads can never be referenced again
                    if gen is not None:
                        evictions += self._drop_locked(
                            lambda k: self._is_snap_key(k)
                            and k[1] == region and k[2] < version)
                    self._snap_gen[region] = version
            old = self._lru.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            elif self._is_file_key(key):
                pin = True
            self._lru[key] = arr
            self._bytes += nbytes
            while self._bytes > self.budget and self._lru:
                _, evicted = self._lru.popitem(last=False)
                self._bytes -= evicted.nbytes
                evictions += 1
            DEVICE_HOT_SET_BYTES.set(float(self._bytes))
        if pin:
            DEVICE_HOT_SET_EVENTS.inc(event="pin")
        if evictions:
            DEVICE_CACHE_EVENTS.inc(float(evictions), event="evict")
            DEVICE_HOT_SET_EVENTS.inc(float(evictions), event="evict")

    def _drop_locked(self, pred) -> int:
        """Remove entries matching `pred(key)`; caller holds the lock.
        Returns the count removed."""
        doomed = [k for k in self._lru if pred(k)]
        for k in doomed:
            arr = self._lru.pop(k)
            self._bytes -= arr.nbytes
        return len(doomed)

    #: dead-file tombstone ring bound — far above any live working set
    _DEAD_FILES_CAP = 4096

    def invalidate_files(self, region_id: int, file_ids) -> None:
        """Drop file-anchored entries for dead SSTs (compaction swap,
        retention expiry, DROP/TRUNCATE — the part-cache seams)."""
        gone = set(file_ids)
        with self._lock:
            for fid in gone:
                self._dead_files[(region_id, fid)] = None
                self._dead_files.move_to_end((region_id, fid))
            while len(self._dead_files) > self._DEAD_FILES_CAP:
                self._dead_files.popitem(last=False)
            n = self._drop_locked(
                lambda k: self._is_file_key(k) and k[1] == region_id
                and k[2] in gone)
            DEVICE_HOT_SET_BYTES.set(float(self._bytes))
        if n:
            DEVICE_HOT_SET_EVENTS.inc(float(n), event="evict")

    def invalidate_region(self, region_id: int) -> None:
        with self._lock:
            n = self._drop_locked(
                lambda k: len(k) >= 2 and k[0] in ("file", "snap")
                and k[1] == region_id)
            self._snap_gen.pop(region_id, None)
            self._region_epoch[region_id] = \
                self._region_epoch.get(region_id, 0) + 1
            DEVICE_HOT_SET_BYTES.set(float(self._bytes))
        if n:
            DEVICE_HOT_SET_EVENTS.inc(float(n), event="evict")

    def file_keys(self, region_id: int = None) -> list:
        """Resident file-anchored keys (diagnostics + tests)."""
        with self._lock:
            return [k for k in self._lru if self._is_file_key(k)
                    and (region_id is None or k[1] == region_id)]

    def clear(self) -> None:
        with self._lock:
            self._lru.clear()
            self._bytes = 0
            self._snap_gen.clear()
            DEVICE_HOT_SET_BYTES.set(0.0)

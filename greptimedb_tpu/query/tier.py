"""Where does this work run: the one owner of the tier decision.

A table of several regions (PARTITION ON COLUMNS) has no decision to
make: region i computes on chip i (`region_device`), all matching
regions at once, and their partials combine on the host
(query/physical.py `_try_region_fanout`). What follows chooses for a
table of ONE region.

Three outcomes: "device" is the process's default backend (the chip, or
the CPU where there is none), "mesh" the row-sharded dispatch over a
device mesh, "host" the CPU backend of an accelerator process. The
router chooses from what it can observe — backend, mesh with its row
floor and measured history, GREPTIMEDB_TPU_HOST_TIER — and one
first-touch hedge serves a shape on the host while its device executable
compiles in the background. The link probe is reported (GET /v1/device,
chip_smoke.py) and routes nothing: a chip off its host is not a
supported deployment.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu import config
from greptimedb_tpu.storage.region import ScanExpired
from greptimedb_tpu.utils import tracing

# contextvar, NOT a module global: queries run concurrently under the
# threaded servers, and jax.default_device is itself thread-local — the
# cache-key tier must track the same scope or tiers cross-contaminate
ACTIVE_TIER = contextvars.ContextVar("gtpu_tier", default="device")

_LINK: Optional[dict] = None


def accelerator_link() -> dict:
    """Measured host<->accelerator link profile, probed once per process:
    the round trip of a tiny compiled call and the D2H rate of a freshly
    computed 4 MB array, each the BEST of three readings — a capability
    probe must not mistake a busy host for a slow link. On a v5e
    attached to its host the probe reads ~1 ms and 185-860 MB/s (a 4 MB
    fetch is mostly fixed cost), a chip reached over a network two
    orders of magnitude worse on both; `colocated: false` means the
    latter, which chip_smoke.py refuses."""
    global _LINK
    if _LINK is not None:
        return _LINK
    backend = jax.default_backend()
    if backend == "cpu":
        _LINK = {"backend": "cpu", "rtt_ms": 0.0,
                 "d2h_mbps": float("inf"), "colocated": True}
        return _LINK
    f = jax.jit(lambda x: (x * 2.0).sum())
    g = jax.jit(lambda v, k: v + k)
    x = jnp.ones((8, 128), jnp.float32)
    y0 = jnp.ones((1 << 20,), jnp.float32)
    float(f(x))  # compile outside the clock
    rtt_s, d2h_s = [], []
    for k in range(3):
        t0 = time.perf_counter()
        float(f(x))
        rtt_s.append(time.perf_counter() - t0)
        # D2H must fetch a freshly COMPUTED array: an uploaded one can
        # be served from a host-side copy the runtime kept
        y = g(y0, float(k))
        y.block_until_ready()
        t0 = time.perf_counter()
        np.asarray(y)
        d2h_s.append(time.perf_counter() - t0)
    rtt_ms = min(rtt_s) * 1e3
    d2h_mbps = 4.0 / max(min(d2h_s), 1e-9)
    _LINK = {"backend": backend, "rtt_ms": round(rtt_ms, 2),
             "d2h_mbps": round(d2h_mbps, 1),
             "colocated": rtt_ms < 5.0 and d2h_mbps > 100.0}
    return _LINK


@functools.lru_cache(maxsize=1)
def _host_device():
    return jax.local_devices(backend="cpu")[0]


class TierCtx:
    """Route the enclosed jax work to the host tier: compilations and
    new arrays land on the CPU backend (which coexists with the
    accelerator backend). Any other tier leaves the default device."""

    def __init__(self, tier: str):
        self.tier = tier
        self._dd = None
        self._token = None

    def __enter__(self):
        if self.tier == "host" and jax.default_backend() != "cpu":
            self._token = ACTIVE_TIER.set("host")
            self._dd = jax.default_device(_host_device())
            self._dd.__enter__()
        return self

    def __exit__(self, *exc):
        if self._token is not None:
            ACTIVE_TIER.reset(self._token)
        if self._dd is not None:
            self._dd.__exit__(*exc)
        return False


class OnShard:
    """Run the enclosed jax work on one device of the mesh. Its uploads
    key under tier="mesh" — a namespace deliberately distinct from both
    the single-device tiers and the classic dispatch's per-segment
    "mshard" entries (which chunk parts ACROSS shards and can't be
    reused at part granularity)."""

    def __init__(self, device):
        self._dd = jax.default_device(device)
        self._token = None

    def __enter__(self):
        self._token = ACTIVE_TIER.set("mesh")
        self._dd.__enter__()
        return self

    def __exit__(self, *exc):
        self._dd.__exit__(*exc)
        ACTIVE_TIER.reset(self._token)
        return False


def region_device(index: int):
    """The chip a region of a multi-region table computes on: region i
    (its position in the table) on local device i, wrapping where the
    process sees fewer devices than the table has regions. A function
    of the table's layout alone — what `information_schema.region_peers`
    reports as the region's peer."""
    devs = jax.local_devices()
    return devs[index % len(devs)]


def part_placement(mesh, tier: str, scan) -> Callable:
    """Compute-placement context per part (by file id; None is the
    memtable tail) for the incremental fold: the host tier pins the CPU
    backend; the mesh tier computes each part's partial on the shard
    `plan_shards` assigns the part's FIRST chunk to (the dispatch's
    deterministic greedy balance, so uncached folds spread across the
    mesh the way the classic dispatch's load does). All cache classes
    share the one DeviceCache byte budget, so duplicates are bounded by
    LRU, not leaked. Cached partials are host numpy either way — the
    warm path never touches a device."""
    if tier == "mesh" and mesh is not None:
        from greptimedb_tpu.parallel import sharded_dispatch as sd

        if sd.eligible(mesh):
            devs = sd.shard_devices(mesh)
            owner_of = {}
            for s, segs in enumerate(sd.plan_shards(scan, len(devs)).segs):
                for seg in segs:
                    if seg.pkey is not None and seg.start == seg.part_start:
                        owner_of[seg.pkey[0]] = s
            return lambda fid: OnShard(devs[owner_of.get(fid, 0)])
    return lambda fid: TierCtx(tier)


def whole_scan_key(schema, shape, keys, agg_args, ops, num_groups, sparse,
                   blocks: tuple, lww_masked: bool,
                   value_flags: tuple) -> tuple:
    """Hedge key of a classic whole-scan aggregate: every static input
    of the programs the device would run, and nothing a request draws.
    The predicate enters by its literal-free `shape` (query/expr.py
    `split_operands`) and a time-bucket key without its base (both are
    operands), so two requests that differ in hosts or in their window's
    start share the key as they share the executable. In place of the
    region, its data version and the scan's fingerprint stand what those
    stood for in the programs: the table's schema, the block layout of
    the scan, whether a last-write-wins mask rides along (the host
    makes it, whatever the row count: query/lww.py), and the value
    columns' NULL / Inf flags the kernel choice reads. A
    key missing one of them would declare a DIFFERENT program warm and
    block the foreground on its cold compile."""
    return (schema, repr(shape), repr(keys), repr(agg_args), ops,
            num_groups, sparse, blocks, lww_masked, value_flags)


def incremental_key(schema, shape, keys, agg_args, ops, acc_dtype,
                    num_groups, sparse, block: int,
                    lww_masked: bool) -> tuple:
    """Hedge key of one part's incremental fold: the static inputs of
    the per-part kernel — `whole_scan_key`'s, with the one block size
    this part pads to (a request is warm once every block size it folds
    is). NOT the partial cache's `shape_fingerprint`: a cached partial's
    VALUES depend on the literals, so that one keeps
    `repr(bound_where)`; a compiled program does not, so this one holds
    the shape."""
    return (schema, repr(shape), repr(keys), repr(agg_args), ops,
            str(acc_dtype), num_groups, sparse, block, lww_masked)


class TierRouter:
    """One per executor: chooses a tier, keeps the mesh-vs-device
    latency history that choice reads, and owns the first-touch hedge's
    warm / warming / failed state."""

    def __init__(self, mesh, note_degradation: Callable[[str, str], None]):
        self.mesh = mesh
        self._note_degradation = note_degradation
        # measured latency history: keyed by (tier, log2 rows bucket) so
        # the router can stop choosing the mesh where it measurably loses
        self._hist: dict[tuple, collections.deque] = {}
        self._explore: dict[int, int] = {}
        self._hist_lock = threading.Lock()
        # hedge keys whose device executable is compiled / compiling /
        # failed to compile
        self._warm: set = set()
        self._warming: set = set()
        self._failed: set = set()
        self._warm_lock = threading.Lock()

    # ---- the choice ----

    def choose(self, agg, num_rows: int, streaming: bool = False) -> str:
        """"mesh" for aggregate scans big enough to amortize per-shard
        dispatch, unless the latency history says a single device wins
        this size class; "host" only under GREPTIMEDB_TPU_HOST_TIER=force
        on an accelerator without a mesh; else "device"."""
        if self.mesh is not None:
            if (agg is not None and not streaming
                    and num_rows >= config.mesh_min_rows()
                    and self._mesh_from_history(num_rows) == "mesh"):
                return "mesh"
            return "device"
        if jax.default_backend() != "cpu" \
                and config.host_tier_mode() == "force":
            return "host"
        return "device"

    def note(self, tier: str, num_rows: int, seconds: float) -> None:
        """Feed one measured execution into the history ring (the
        device_agg span's duration, bucketed by scan size). Only a mesh
        has a choice that reads it."""
        if self.mesh is None or tier not in ("device", "mesh"):
            return
        b = max(int(num_rows), 1).bit_length()
        with self._hist_lock:
            self._hist.setdefault(
                (tier, b), collections.deque(maxlen=16)).append(seconds)

    def _mesh_from_history(self, num_rows: int) -> str:
        """Measured mesh-vs-single-device verdict for this scan-size
        class. Defaults to "mesh" until both tiers hold >=3 real samples
        (the mesh must get its first measurements from somewhere); every
        16th decision explores the loser so a regression on the unused
        tier is re-measured instead of frozen in."""
        b = max(int(num_rows), 1).bit_length()
        with self._hist_lock:
            mesh = sorted(self._hist.get(("mesh", b), ()))
            dev = sorted(self._hist.get(("device", b), ()))
            n = self._explore.get(b, 0) + 1
            self._explore[b] = n
            if len(mesh) < 3 or len(dev) < 3:
                # seed the underfilled ring: mesh-eligible shapes never
                # reach the single-device paths on their own, so without
                # this forced sample the >=3 gate would hold forever and
                # the measured arbitration below would be unreachable
                if len(dev) < 3 and n % 8 == 0:
                    return "device"
                return "mesh"
            med_m = mesh[len(mesh) // 2]
            med_d = dev[len(dev) // 2]
            winner = "mesh" if med_m <= med_d else "device"
        if n % 16 == 0:
            return "device" if winner == "mesh" else "mesh"
        return winner

    # ---- the first-touch hedge ----

    def hedges(self, tier: str) -> bool:
        """Whether work chosen for `tier` is hedged at all: auto mode on
        a real accelerator only — mode=off means the caller wants the
        device NOW and will wait, and the mesh has its own placement."""
        return tier == "device" and jax.default_backend() != "cpu" \
            and self.mesh is None and config.host_tier_mode() == "auto"

    def needed(self, key: tuple) -> bool:
        """Whether this shape must serve on the host: an accelerator's
        first compile of a query shape costs seconds to tens of seconds,
        so until the shape is warm its requests fold host-side (a shape
        whose warm-up failed stays there)."""
        with self._warm_lock:
            return key not in self._warm

    def kick(self, key: tuple, work: Callable[[], object],
             what: str) -> None:
        """Start the one background warm-up of this shape, unless one
        runs, ran or failed already: `work` runs on the device and its
        result is DISCARDED (a device-computed twin of the host answer
        could differ in the last ulp on emulated f64, and warm/cold
        serves must stay bit-identical). Once it lands the shape is warm
        and later requests run on the chip; a failure leaves it on the
        host tier — counted and logged (`what`), never silent."""
        with self._warm_lock:
            if key in self._warming or key in self._warm \
                    or key in self._failed:
                return
            self._warming.add(key)

        def warm():
            try:
                with TierCtx("device"):
                    work()
                with self._warm_lock:
                    self._warm.add(key)
            except ScanExpired:
                # the request is over and the snapshot died before this
                # thread read it: nothing was learned about the device —
                # a later request's hedge warms the shape
                pass
            except Exception:  # noqa: BLE001 — hedge must not raise
                self._note_degradation("warmup_failed", what)
                with self._warm_lock:
                    self._failed.add(key)
            finally:
                with self._warm_lock:
                    self._warming.discard(key)

        # under the request's trace: the warm-up's compile hangs off the
        # request that kicked it, marked thread="warmup"
        threading.Thread(target=tracing.propagate(warm, background=True),
                         daemon=True, name="gtpu-device-warm").start()

    @contextlib.contextmanager
    def compiling(self, name: str):
        """Count the enclosed background compile among the warm-ups
        still running: a client that waits for `warmup.warming == 0`
        waits for it too."""
        with self._warm_lock:
            self._warming.add(name)
        try:
            yield
        finally:
            with self._warm_lock:
                self._warming.discard(name)

    def status(self) -> dict:
        """The tier keys of device_status() (GET /v1/device). Read-only
        apart from the link probe, which runs once per process."""
        with self._warm_lock:
            warm = {"warm": len(self._warm), "warming": len(self._warming),
                    "failed": len(self._failed)}
        return {
            # JSON has no infinity (the CPU backend's d2h rate)
            "link": {k: (None if v == float("inf") else v)
                     for k, v in accelerator_link().items()},
            "host_tier_mode": config.host_tier_mode(),
            "warmup": warm,
        }

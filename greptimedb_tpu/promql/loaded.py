"""A selector's samples on the device, and the cache that keeps them.

Everything `PromqlEngine._load` derives from a region scan — matcher
masks, series factorization, label sets, the (series, ts)-sorted upload,
the derived channels, the pivot onto a shared sample grid — depends on
(region, data version, selector) and not on the request: only the
window kernels' `t0` does. `SeriesCache` keeps that per selector and is
asked BEFORE any scan, from region metadata alone:

- the samples a request loaded for its own range serve that range again;
- once the ranges requested at one data version add up to the region's
  retained span, or at once where a range covers half of it (the region
  then reads all it holds anyway), the request loads the whole span
  instead (`promote`), and where every series shares one complete sample grid
  there, every later request of that version is a slice of the resident
  [S, P, C] matrix (`hit`): nothing is scanned, decoded, factorised or
  uploaded;
- a selector whose whole span has no complete grid, or would not fit the
  device budget, is `ineligible` at that version and keeps loading its
  own range; a table written between any two requests never reaches a
  promotion, because a new data version drops what the older one held.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.ops.window import grid_window, grid_window_flat
from greptimedb_tpu.utils import device_telemetry, tracing


def h2d(x, dtype=None) -> jax.Array:
    """Host -> device copy of a numpy array or python value, in stage
    `upload` and counted in device_transfer_bytes_total{h2d} (a jax
    array passes through)."""
    if isinstance(x, jax.Array):
        return x
    with tracing.stage("upload"):
        arr = jnp.asarray(x, dtype=dtype)
    device_telemetry.count_h2d(arr.nbytes)
    return arr


def d2h(x, dtype=None) -> np.ndarray:
    """Device -> host readback, counted in
    device_transfer_bytes_total{d2h}; host values pass through
    uncounted. Blocks until the device has produced `x`."""
    arr = np.asarray(x, dtype=dtype)
    if isinstance(x, jax.Array):
        device_telemetry.count_d2h(arr.nbytes)
    return arr


def covered(lo: int, hi: int, extent: tuple) -> int:
    """Time-index units of [lo, hi) that lie inside `extent` =
    (first, last) sample timestamp."""
    return max(0, min(hi, extent[1] + 1) - max(lo, extent[0]))


class LabelSets(list):
    """The label sets of a vector, knowing which loaded series they
    derive from and how.

    A selector's label sets live as long as its `LoadedSeries`: one
    selector at one data version. What an evaluation derives from them
    by a rule that reads nothing else (`sum by (le, handler)`'s output
    label sets) is named by the root list and the steps taken from it,
    so whatever depends on the label sets alone — an aggregation's
    group index, histogram_quantile's fold index — is kept in the
    root's `derived` (`derive`), found again by the next request, and
    dropped with the samples. A plain list has no such name, and what
    is derived from it is derived per request. What is kept is shared
    between requests and threads: whatever changes a label set copies
    it first, and a step that `how` does not name completely hands on
    a plain list."""

    def __init__(self, labels=(), root: Optional["LabelSets"] = None,
                 path: tuple = ()):
        super().__init__(labels)
        self.root = self if root is None else root
        #: the steps from the root's label sets to these
        self.path = path
        if root is None:
            #: (what, path, *args) -> whatever was derived, or the Event
            #: of the thread deriving it now; the root's only
            self.derived: dict = {}
            self.lock = threading.Lock()

    def step(self, how: tuple, labels: list) -> "LabelSets":
        """`labels`, derived from these by the rule `how` names."""
        return LabelSets(labels, self.root, self.path + (how,))


def derive(labels: list, what: str, build, *args) -> tuple:
    """(`build(labels, *args)`, "hit" | "build"): what depends on the
    label sets and `args` alone. Label sets that know their origin keep
    it beside the loaded series they derive from, so it is built once
    per (label sets, args, data version): a request that arrives while
    another builds waits for that build and does not run its own. A
    plain list's is built for this request."""
    if not isinstance(labels, LabelSets):
        return build(labels, *args), "build"
    root = labels.root
    key = (what, labels.path) + args
    while True:
        with root.lock:
            entry = root.derived.get(key)
            if entry is None:
                built = root.derived[key] = threading.Event()
                break
        if not isinstance(entry, threading.Event):
            return entry, "hit"
        entry.wait()  # then look again: a build that failed left nothing
    try:
        value = build(labels, *args)
        with root.lock:
            root.derived[key] = value
        return value, "build"
    except BaseException:
        with root.lock:
            del root.derived[key]
        raise
    finally:
        built.set()


class LoadedSeries:
    """One selector's samples on the device, sorted by (series, ts).

    Held flat — sidx [N], ts seconds [N], channels [N, C] — until
    `pivot` finds that every series has the same complete, NaN-free
    sample grid; from then on as that grid [P] and the matrix [S, P, C]
    alone, and the flat form is a kernel away."""

    def __init__(self, labels: list, sidx, ts, chans,
                 span: Optional[tuple] = None, extent: tuple = (0, 0)):
        self.labels = LabelSets(labels)
        #: the scan range it was loaded for, in the time index's units;
        #: None = everything the region held at its version
        self.span = span
        #: first and last sample timestamp it holds, same units
        self.extent = extent
        self._flat: Optional[tuple] = (sidx, ts, chans)
        self._pivot: Optional[tuple] = None
        #: whether the samples share one complete grid (None: `pivot`
        #: has not looked yet), and that grid on the host, where a
        #: request's range is cut on it
        self.grid_complete: Optional[bool] = None
        self.grid_host: Optional[np.ndarray] = None
        self._probe_lock = threading.Lock()

    @property
    def nbytes(self) -> int:
        arrays = self._flat or self._pivot
        return int(sum(a.nbytes for a in arrays))

    def pivot(self) -> Optional[tuple]:
        """(grid [P], mat [S, P, C]) when every series has exactly the
        same complete, NaN-free sample grid (LWW tombstones ride as NaN
        the grid kernels' probes cannot mask); None otherwise. Looked
        for once."""
        with self._probe_lock:
            if self.grid_complete is None:
                sidx, ts, chans = self._flat
                n, S = int(chans.shape[0]), len(self.labels)
                if S > 0 and n % S == 0:
                    P = n // S
                    ts_np = d2h(ts)
                    grid = ts_np[:P]
                    if (ts_np.reshape(S, P) == grid[None, :]).all() \
                            and not bool(d2h(jnp.isnan(chans).any())):
                        self._pivot = (h2d(grid),
                                       chans.reshape(S, P, chans.shape[1]))
                        self.grid_host = grid
                        # the pivot holds the same samples: a reader of
                        # `flat` finds one form or the other
                        self._flat = None
                self.grid_complete = self.grid_host is not None
        return self._pivot

    def flat(self, cut: Optional[tuple] = None) -> tuple:
        """(sidx, ts, channels) of everything held, or of the grid
        points `cut` = (first, count)."""
        flat = self._flat
        if flat is not None and cut is None:
            return flat
        grid, mat = self._pivot
        i0, n = cut or (0, int(grid.shape[0]))
        return grid_window_flat(grid, mat, i0, n=n)

    def cut(self, lo_s: float, hi_s: float) -> Optional[tuple]:
        """(first, count) of the grid points in [lo_s, hi_s) seconds;
        None when that is all of them."""
        i0, i1 = np.searchsorted(self.grid_host, [lo_s, hi_s], side="left")
        if i0 == 0 and i1 == len(self.grid_host):
            return None
        return int(i0), int(i1 - i0)

    def serves(self, lo: int, hi: int) -> bool:
        """Whether a request for [lo, hi) is answered from these
        samples as it would be from a scan of its own range."""
        if self.span is not None:
            return (lo, hi) == self.span
        if self.grid_host is not None:
            return True  # the whole span on its grid: any slice of it
        # the whole span, flat: window_stats masks exactly, and runs
        # over at most twice the request's samples where the request
        # covers half of them (Region.scan's canonical sharing)
        return 2 * covered(lo, hi, self.extent) \
            >= self.extent[1] + 1 - self.extent[0]


@dataclass
class Loaded:
    """What `_load` hands an evaluation: a selector's samples, and where
    they span more than the request's range, the grid points of that
    range."""

    series: LoadedSeries
    metric: Optional[str]
    cut: Optional[tuple] = None

    @property
    def labels(self) -> list:
        return self.series.labels

    def flat(self) -> tuple:
        return self.series.flat(self.cut)

    def pivot(self, own_range: bool = False) -> Optional[tuple]:
        """The complete grid and its matrix, if there is one: all that
        is held (the edge kernels find a window by its time), or with
        `own_range` the request's own points."""
        pv = self.series.pivot()
        if pv is None or self.cut is None or not own_range:
            return pv
        return grid_window(*pv, self.cut[0], n=self.cut[1])


@dataclass
class _Slot:
    """One selector at one data version."""

    version: tuple  # (incarnation, data_version)
    whole: Optional[LoadedSeries] = None  # the retained span
    ranged: Optional[LoadedSeries] = None  # the last request's own range
    #: time-index units the ranged loads of this version have covered,
    #: and the most device bytes one of them took per unit
    paid: int = 0
    bytes_per_unit: float = 0.0
    ineligible: bool = False
    promoting: bool = False


class SeriesCache:
    """The loaded-series cache of one executor: per selector key a
    `_Slot`, its entries evicted least-recently-used by device bytes
    against `budget`. Thread-safe; loads run outside the lock."""

    #: slots kept (a slot without entries is a few notes): past this,
    #: the oldest such slot goes when a new key arrives
    MAX_SLOTS = 4096

    def __init__(self, budget: int):
        self.budget = int(budget)
        self._lock = threading.Lock()
        self._slots: dict = {}
        # (key, "whole" | "ranged") -> (samples, bytes counted for them)
        self._lru: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._bytes = 0
        # device_memory_bytes{kind="cache"} sums _bytes over live caches
        device_telemetry.register_cache(self)

    def probe(self, key: tuple, identity: tuple, lo: int, hi: int,
              sliceable: bool) -> tuple:
        """(event, samples or None) for a request of [lo, hi) at the
        region's `identity` (Region.data_identity): `hit` with the
        samples; `promote` when the caller is to load the whole span
        and `store` or `refuse` it; `ineligible` / `miss` when it is to
        load its own range. `sliceable`: the selector's channels read
        the same at a sample wherever the load began, so a longer
        span's samples can stand for a range's."""
        incarnation, data_version, extent = identity
        with self._lock:
            slot = self._slot(key, (incarnation, data_version))
            if slot is None:
                return "miss", None
            for kind in ("whole", "ranged"):
                series = getattr(slot, kind)
                if series is not None and series.serves(lo, hi):
                    self._lru.move_to_end((key, kind))
                    return "hit", series
            if slot.ineligible:
                return "ineligible", None
            if not sliceable or slot.promoting or extent is None:
                return "miss", None
            span = extent[1] + 1 - extent[0]
            asked = covered(lo, hi, extent)
            # a range over half the span makes the region read all it
            # holds (Region.scan's canonical sharing): the whole span
            # is then the load to make, at the first request
            if slot.paid + asked < span and 2 * asked < span:
                return "miss", None  # its own range is still the cheaper
            ranged = slot.ranged
            if slot.bytes_per_unit * span > self.budget or (
                    ranged is not None and ranged.grid_complete is False):
                # over the device budget, or a range of it already has
                # no complete grid: the whole span cannot have one
                slot.ineligible = True
                return "ineligible", None
            slot.promoting = True
            return "promote", None

    def store(self, key: tuple, version: tuple, series: LoadedSeries,
              requested: int = 0) -> None:
        """Keep the samples a scan at `version` gave: as the slot's
        whole span (a promotion's load, or a range that covered the
        region; the caller has looked for its grid), else as its last
        range, `requested` units long."""
        kind = "whole" if series.span is None else "ranged"
        nbytes = series.nbytes
        with self._lock:
            slot = self._slot(key, version)
            if slot is None:
                return
            if kind == "ranged":
                slot.paid += requested
                slot.bytes_per_unit = max(slot.bytes_per_unit,
                                          nbytes / max(requested, 1))
                if slot.whole is not None \
                        and slot.whole.serves(*series.span):
                    return  # loaded while the whole span was: not needed
            else:
                slot.promoting = False
                # flat, it serves the requests that cover half of it
                # and no promotion can better it
                slot.ineligible = not series.grid_complete \
                    or nbytes > self.budget
                self._drop(key, "ranged")  # the whole span holds it too
            self._drop(key, kind)
            if nbytes > self.budget:
                return
            setattr(slot, kind, series)
            self._lru[(key, kind)] = (series, nbytes)
            self._bytes += nbytes
            while self._bytes > self.budget:
                self._drop(*next(iter(self._lru)))

    def refuse(self, key: tuple, version: tuple) -> None:
        """A promotion's load gave no complete grid: the key stays on
        its own ranges for this version."""
        with self._lock:
            slot = self._slot(key, version)
            if slot is not None:
                slot.promoting = False
                slot.ineligible = True

    def _slot(self, key: tuple, version: tuple) -> Optional[_Slot]:
        """The key's slot at `version`; a newer version drops what the
        older one held, an older one (a request that read the region
        before a write another request has already seen) gets none."""
        slot = self._slots.get(key)
        if slot is None or slot.version < version:
            if slot is None and len(self._slots) >= self.MAX_SLOTS:
                idle = next((k for k, s in self._slots.items()
                             if s.whole is None and s.ranged is None), None)
                if idle is None:
                    return None
                del self._slots[idle]
            self._drop(key, "whole")
            self._drop(key, "ranged")
            slot = self._slots[key] = _Slot(version)
        return slot if slot.version == version else None

    def _drop(self, key: tuple, kind: str) -> None:
        old = self._lru.pop((key, kind), None)
        if old is not None:
            self._bytes -= old[1]
            setattr(self._slots[key], kind, None)

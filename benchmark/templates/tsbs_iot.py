"""TSBS IoT query templates over `readings` and `diagnostics`, each with
its plain numpy reference: a dispatcher's board over a trucking fleet.

Six of TSBS's twelve `iot` query types (cmd/tsbs_generate_queries), in
this system's dialect of TSBS's TimescaleDB texts. TSBS joins a `tags`
table; here the tags are columns of the measurement (what the
line-protocol door makes), so a tag's numeric value is CAST from its
string. Every table is last-write-wins (no `append_mode`).

    last-loc               the last latitude, longitude per (name, driver)
                           of a fleet's named trucks:
                             SELECT name, driver, last_value(latitude ORDER
                             BY ts), last_value(longitude ORDER BY ts) FROM
                             readings WHERE fleet = $f AND name IS NOT NULL
                             GROUP BY name, driver
    low-fuel               a fleet's named trucks whose LAST fuel_state is
                           under 0.1 (HAVING over last_value), with it
    high-load              ... whose LAST current_load / load_capacity is
                           0.9 or more: a field over a tag's numeric value;
                           returns the last current_load
    stationary-trucks      (name, driver) of a fleet with avg(velocity) < 1
                           over a 10 min window that starts at a drawn
                           millisecond; NULL names are one group a driver;
                           returns the average
    long-driving-sessions  a select over a derived table: per truck and
                           10 min bucket avg(velocity) > 1 inside; outside,
                           trucks with more than 22 such buckets in a 4 h
                           window, with their count and the mean of the
                           bucket averages (a two-level aggregate)
    avg-load               avg(current_load / load_capacity) by fleet,
                           model, load_capacity over the whole span: one
                           fixed text, as in TSBS

Drawn per request: the fleet, and the window's start (ms-granular,
unaligned). A window longer than the table's span is cut to the span, and
`long-driving-sessions`' 22 of 24 buckets in the same proportion (a
rehearsal's hour asks for more than 5 of its 6-7).

**The references** read the seeded arrays and numpy only (no engine
code): PRESENT rows only (a gap is an absent row, `view.present`); a row
that was sent twice counts once (`lww=True`; the tests' second control
computes them with `lww=False`, every resent row twice, and must fail);
NULL tags as SQL has them: `fleet = $f` is not true of NULL, `IS NOT
NULL` drops it, `GROUP BY` keeps one NULL group.

**What is compared, and the limits** (PERF.md section 2 has the readings):

  set     every selecting template: rows missing from the answer + rows
          the reference lacks. One of either makes the number >= 1: over
          every limit.
  exact   `last-loc`, `low-fuel`, `high-load` return stored values: the
          count of values that differ from the reference rounded to the
          compute dtype is added to the set's number; limit 0, as
          `lastpoint`. A bfloat16 reference differs in nearly every value.
  mean    `stationary-trucks`, `long-driving-sessions`, `avg-load` return
          averages: the widest relative gap is added to the set's number;
          limit 2e-5 in float32 (`double-groupby-*`'s; 1e-12 in float64).
          `avg-load` sums 70,000-580,000 values a group where
          `double-groupby` sums 360: its readings are in PERF.md.

A threshold must select the same trucks in float32 as in float64, so no
statistic lies within 1e-4 (relative) of its threshold: `draw` computes
the request's reference and draws the window again while one does
(`stationary-trucks`' and the inner `long-driving-sessions`' 1.0); the
dataset keeps `fuel_state` away from 0.1 and the load ratio from 0.9.
"""

from __future__ import annotations

import json
import urllib.parse

import numpy as np

LIMITS = {
    "exact": {"float32": 0.0, "float64": 0.0},
    "mean": {"float32": 2e-5, "float64": 1e-12},
}
LOWER = {"float32": "bfloat16", "float64": "float32"}
FLEETS = ["East", "West", "North", "South"]
BUCKET_MS = 600_000
ROOM = 1e-4


def round_to(dtype: str, x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    if dtype == "float64":
        return x
    if dtype == "float32":
        return x.astype(np.float32).astype(np.float64)
    if dtype == "bfloat16":  # round to nearest even on the f32 bits
        u = x.astype(np.float32).view(np.uint32)
        r = ((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)
        return ((u + r) & np.uint32(0xFFFF0000)).view(
            np.float32).astype(np.float64)
    raise ValueError(dtype)


def _acc(precision: str):
    return np.float64 if precision == "float64" else np.float32


def _order(key: tuple) -> tuple:
    """Sort key of a group key that may hold None."""
    return tuple((v is None, "" if v is None else v) for v in key)


def _point_range(view, start_ms: int, end_ms: int) -> tuple:
    lo = max(0, -(-(start_ms - view.t0_ms) // view.step_ms))
    hi = min(view.points, -(-(end_ms - view.t0_ms) // view.step_ms))
    return int(lo), int(max(lo, hi))


def _weights(view, lo: int, hi: int, trucks: np.ndarray, lww: bool):
    """How often each (point, truck) counts: 1 where the row is present,
    0 where it never arrived; without last-write-wins a resent row 2."""
    w = view.present[lo:hi][:, trucks].astype(np.float64)
    if not lww:
        w += view.resent[lo:hi][:, trucks]
    return w


def _groups(tags: dict, trucks: np.ndarray, by: list) -> tuple:
    """(keys sorted, group index per truck) of GROUP BY over tags: a
    NULL is a value of its own."""
    keys = [tuple(tags[t][i] for t in by) for i in trucks]
    uniq = sorted(set(keys), key=_order)
    pos = {k: j for j, k in enumerate(uniq)}
    return uniq, np.asarray([pos[k] for k in keys], np.int64)


def _fold(per_truck: np.ndarray, gid: np.ndarray, n: int) -> np.ndarray:
    """Sum the trucks' columns (last axis) into their groups."""
    out = np.zeros(per_truck.shape[:-1] + (n,), per_truck.dtype)
    np.add.at(out, (Ellipsis, gid), per_truck)
    return out


class _Sql:
    kind = "exact"
    table = "readings"
    by = ["name", "driver"]
    width = 1  # value columns after the group key

    def __init__(self, name: str, args: dict | None = None):
        self.name = name
        self.args = args or {}

    def request(self, params: dict, ds) -> tuple:
        body = urllib.parse.urlencode({"sql": self.sql(params, ds)}).encode()
        return "POST", "/v1/sql", body

    @staticmethod
    def parse(status: int, data: bytes) -> tuple:
        try:
            out = json.loads(data)
        except ValueError:
            return None, None, f"HTTP {status}: {data[:200]!r}"
        if status != 200:
            return None, None, f"HTTP {status}: {out.get('error')!r}"
        try:
            return (out["output"][-1]["records"]["rows"],
                    out.get("execution_time_ms"), None)
        except (KeyError, IndexError, TypeError):
            return None, None, f"unexpected body {data[:200]!r}"

    def edges(self, ds) -> list:
        return []

    def limit(self, dtype: str) -> float:
        return LIMITS[self.kind][dtype]

    def draw(self, rng, ds) -> dict:
        return {"fleet": FLEETS[int(rng.integers(0, len(FLEETS)))]}

    def _fleet(self, ds, fleet: str, named: bool) -> np.ndarray:
        """The trucks `fleet = $f` is true of (never a NULL fleet), and
        with `named` those `name IS NOT NULL` keeps."""
        m = ds.tags["fleet"] == fleet
        if named:
            m &= np.asarray([v is not None for v in ds.tags["name"]])
        return np.flatnonzero(m)

    def _cached(self, params: dict, ds) -> tuple:
        """The request's reference, kept for the one request a client
        thread is at (expected_rows, then compare)."""
        key = (json.dumps(params, sort_keys=True), id(ds))
        slot = getattr(self, "_slot", None)
        if slot is None or slot[0] != key:
            slot = self._slot = (key, self.reference(params, ds))
        return slot[1]

    def expected_rows(self, params: dict, ds) -> int:
        return len(self._cached(params, ds)[0])

    def decode(self, rows: list, params: dict, ds) -> tuple:
        """(keys, values[k, m]) of an answer, in the reference's order."""
        nk = len(self.by)
        rows = sorted(rows, key=lambda r: _order(tuple(r[:nk])))
        vals = [[np.nan if v is None else v for v in r[nk:]] for r in rows]
        return ([tuple(r[:nk]) for r in rows],
                np.asarray(vals, np.float64).reshape(len(rows), self.width))

    def compare(self, rows: list, params: dict, ds, dtype: str,
                lowered: bool = False, lww: bool = True) -> float:
        """Rows missing + rows extra + the values' number (see the
        file's head). `lowered` compares the control — the reference one
        precision below `dtype` — and `lww=False` the reference that
        counts a resent row twice."""
        keys, ref, got_keys, got = self._both(rows, params, ds, dtype,
                                              lowered, lww)
        if got_keys != keys:
            return float(len(set(keys) ^ set(got_keys))) or float("inf")
        if got.shape != ref.shape or not np.isfinite(got).all():
            return float("inf")
        if not got.size:
            return 0.0
        if self.kind == "exact":
            return float((got != round_to(dtype, ref)).sum())
        return float(np.max(np.abs(got - ref)
                            / np.maximum(np.abs(ref), 1e-300)))

    def _both(self, rows, params, ds, dtype, lowered, lww) -> tuple:
        keys, ref = self._cached(params, ds)
        if lowered or not lww:
            got_keys, got = self.reference(
                params, ds, LOWER[dtype] if lowered else "float64", lww)
        else:
            got_keys, got = self.decode(rows, params, ds)
        return keys, ref, got_keys, np.asarray(got, np.float64)


class _LastValue(_Sql):
    """The last row per named truck of a fleet, optionally only where a
    statistic of that row passes a threshold."""

    def __init__(self, name, args, table, shown, sql_shown, having=None):
        super().__init__(name, args)
        self.table, self.shown, self.width = table, shown, len(shown)
        self.sql_shown, self.having = sql_shown, having

    def sql(self, p: dict, ds) -> str:
        text = ("SELECT name, driver, " + ", ".join(
            f"last_value({c} ORDER BY ts)" for c in self.sql_shown)
            + f" FROM {self.table} WHERE fleet = '{p['fleet']}' "
            "AND name IS NOT NULL GROUP BY name, driver")
        if self.having:
            text += " HAVING " + self.having[0]
        return text

    def reference(self, p: dict, ds, precision: str = "float64",
                  lww: bool = True) -> tuple:
        view = ds.view(self.table)
        trucks = self._fleet(ds, p["fleet"], named=True)
        last = view.last_point()[trucks]
        trucks, last = trucks[last >= 0], last[last >= 0]
        vals = np.stack([round_to(precision, view.fields[c][last, trucks])
                         for c in self.shown], axis=1)
        if self.having:
            keep = self.having[1](ds, view, last, trucks, precision)
            trucks, vals = trucks[keep], vals[keep]
        keys = [(ds.tags["name"][t], ds.tags["driver"][t]) for t in trucks]
        order = sorted(range(len(keys)), key=lambda i: _order(keys[i]))
        return [keys[i] for i in order], vals[order]


def _low_fuel(ds, view, last, trucks, precision):
    return round_to(precision, view.fields["fuel_state"][last, trucks]) < 0.1


def _high_load(ds, view, last, trucks, precision):
    load = round_to(precision, view.fields["current_load"][last, trucks])
    return load / ds.load_capacity[trucks] >= 0.9


class _Stationary(_Sql):
    kind = "mean"
    window_ms = 600_000

    def _window(self, rng, ds) -> dict:
        span = min(self.window_ms, ds.t_end_ms - ds.t0_ms)
        start = ds.t0_ms + int(rng.integers(
            0, ds.t_end_ms - ds.t0_ms - span + 1))
        return {"fleet": FLEETS[int(rng.integers(0, len(FLEETS)))],
                "start": start, "end": start + span}

    def draw(self, rng, ds) -> dict:
        """A window in which no statistic lies within ROOM of its
        threshold (drawn again, a few times, while one does)."""
        for _ in range(8):
            p = self._window(rng, ds)
            if self._clear(p, ds):
                break
        return p

    #: warm-up windows beside the three drawn ones, spread over the
    #: span: a window's rows fall into the SSTs its time range meets,
    #: each part pads to a block size of its own, and a program is
    #: compiled per (template, block size) — the more of them warm-up
    #: meets, the fewer first touches the window pays
    spread = 6

    def edges(self, ds) -> list:
        span = min(self.window_ms, ds.t_end_ms - ds.t0_ms)
        room = ds.t_end_ms - ds.t0_ms - span
        return [{"fleet": FLEETS[i % len(FLEETS)],
                 "start": ds.t0_ms + room * i // self.spread + 1,
                 "end": ds.t0_ms + room * i // self.spread + 1 + span}
                for i in range(self.spread + 1)] if room else []

    def _clear(self, p: dict, ds) -> bool:
        _keys, avg, _n = self._averages(p, ds, "float64", True)
        return not (np.abs(avg - 1.0) <= ROOM).any()

    def sql(self, p: dict, ds) -> str:
        return ("SELECT name, driver, avg(velocity) FROM readings "
                f"WHERE fleet = '{p['fleet']}' AND ts >= {p['start']} "
                f"AND ts < {p['end']} GROUP BY name, driver "
                "HAVING avg(velocity) < 1")

    def _averages(self, p, ds, precision, lww) -> tuple:
        """(group keys, avg(velocity) per group, rows per group) over
        the window, groups without a row left out."""
        view = ds.view("readings")
        trucks = self._fleet(ds, p["fleet"], named=False)
        lo, hi = _point_range(view, p["start"], p["end"])
        w = _weights(view, lo, hi, trucks, lww)
        acc = _acc(precision)
        v = round_to(precision, view.fields["velocity"][lo:hi][:, trucks])
        keys, gid = _groups(ds.tags, trucks, self.by)
        s = _fold((v * w).astype(acc).sum(axis=0, dtype=acc)[None],
                  gid, len(keys))[0]
        n = _fold(w.sum(axis=0)[None], gid, len(keys))[0]
        has = n > 0
        return ([k for k, h in zip(keys, has) if h],
                (s[has] / n[has].astype(acc)).astype(np.float64), n[has])

    def reference(self, p: dict, ds, precision: str = "float64",
                  lww: bool = True) -> tuple:
        keys, avg, _n = self._averages(p, ds, precision, lww)
        keep = avg < 1.0
        return ([k for k, h in zip(keys, keep) if h],
                avg[keep].reshape(-1, 1))


class _LongDriving(_Stationary):
    window_ms = 4 * 3600_000
    width = 2
    spread = 3

    def _min_buckets(self, ds) -> int:
        span = min(self.window_ms, ds.t_end_ms - ds.t0_ms)
        return 22 * span // self.window_ms

    def sql(self, p: dict, ds) -> str:
        return (
            "SELECT name, driver, count(*), avg(v) FROM ("
            "SELECT name, driver, date_bin(INTERVAL '10 minutes', ts) AS ten, "
            "avg(velocity) AS v FROM readings "
            f"WHERE fleet = '{p['fleet']}' AND ts >= {p['start']} "
            f"AND ts < {p['end']} GROUP BY name, driver, ten "
            "HAVING avg(velocity) > 1) AS driven "
            f"GROUP BY name, driver HAVING count(*) > {self._min_buckets(ds)}")

    def _buckets(self, p, ds, precision, lww) -> tuple:
        """(group keys, avg(velocity)[buckets, groups], rows[buckets,
        groups]) of the inner select."""
        view = ds.view("readings")
        trucks = self._fleet(ds, p["fleet"], named=False)
        lo, hi = _point_range(view, p["start"], p["end"])
        ids = view.ts_of(np.arange(lo, hi)) // BUCKET_MS
        first = np.flatnonzero(np.r_[True, ids[1:] != ids[:-1]])
        w = _weights(view, lo, hi, trucks, lww)
        acc = _acc(precision)
        v = round_to(precision, view.fields["velocity"][lo:hi][:, trucks])
        keys, gid = _groups(ds.tags, trucks, self.by)
        s = _fold(np.add.reduceat((v * w).astype(acc), first, axis=0),
                  gid, len(keys))
        n = _fold(np.add.reduceat(w, first, axis=0), gid, len(keys))
        with np.errstate(invalid="ignore", divide="ignore"):
            avg = (s / n.astype(acc)).astype(np.float64)
        return keys, avg, n

    def _clear(self, p: dict, ds) -> bool:
        _keys, avg, n = self._buckets(p, ds, "float64", True)
        return not (np.abs(avg[n > 0] - 1.0) <= ROOM).any()

    def reference(self, p: dict, ds, precision: str = "float64",
                  lww: bool = True) -> tuple:
        keys, avg, n = self._buckets(p, ds, precision, lww)
        good = (n > 0) & (avg > 1.0)
        count = good.sum(axis=0)
        acc = _acc(precision)
        total = np.where(good, avg, 0.0).astype(acc).sum(axis=0, dtype=acc)
        keep = count > self._min_buckets(ds)
        mean = (total[keep] / count[keep].astype(acc)).astype(np.float64)
        return ([k for k, h in zip(keys, keep) if h],
                np.stack([count[keep].astype(np.float64), mean], axis=1))

    def compare(self, rows, params, ds, dtype, lowered=False, lww=True):
        """The bucket count must be equal; the mean is the statistic."""
        keys, ref, got_keys, got = self._both(rows, params, ds, dtype,
                                              lowered, lww)
        if got_keys != keys:
            return float(len(set(keys) ^ set(got_keys))) or float("inf")
        if not len(keys):
            return 0.0
        if got.shape != ref.shape or not np.isfinite(got).all():
            return float("inf")
        return float((got[:, 0] != ref[:, 0]).sum()) + float(np.max(
            np.abs(got[:, 1] - ref[:, 1]) / np.maximum(np.abs(ref[:, 1]),
                                                       1e-300)))


class _AvgLoad(_Sql):
    kind = "mean"
    table = "diagnostics"
    by = ["fleet", "model", "load_capacity"]

    def draw(self, rng, ds) -> dict:
        return {}

    def sql(self, p: dict, ds) -> str:
        return ("SELECT fleet, model, load_capacity, "
                "avg(current_load / CAST(load_capacity AS DOUBLE)) "
                "FROM diagnostics GROUP BY fleet, model, load_capacity")

    def reference(self, p: dict, ds, precision: str = "float64",
                  lww: bool = True) -> tuple:
        view = ds.view("diagnostics")
        trucks = np.arange(ds.trucks)
        acc = _acc(precision)
        keys, gid = _groups(ds.tags, trucks, self.by)
        s = np.zeros(ds.trucks, acc)
        n = np.zeros(ds.trucks)
        step = 360
        for lo in range(0, view.points, step):
            hi = min(lo + step, view.points)
            w = _weights(view, lo, hi, trucks, lww)
            ratio = round_to(precision, view.fields["current_load"][lo:hi]) \
                / ds.load_capacity
            s += (ratio * w).astype(acc).sum(axis=0, dtype=acc)
            n += w.sum(axis=0)
        s, n = _fold(s[None], gid, len(keys))[0], \
            _fold(n[None], gid, len(keys))[0]
        has = n > 0
        return ([k for k, h in zip(keys, has) if h],
                (s[has] / n[has].astype(acc)).astype(
                    np.float64).reshape(-1, 1))


def make(template: str, args: dict | None = None):
    if template == "last-loc":
        return _LastValue(template, args, "readings",
                          ["latitude", "longitude"],
                          ["latitude", "longitude"])
    if template == "low-fuel":
        return _LastValue(
            template, args, "diagnostics", ["fuel_state"], ["fuel_state"],
            ("last_value(fuel_state ORDER BY ts) < 0.1", _low_fuel))
    if template == "high-load":
        return _LastValue(
            template, args, "diagnostics", ["current_load"],
            ["current_load"],
            ("last_value(current_load / CAST(load_capacity AS DOUBLE) "
             "ORDER BY ts) >= 0.9", _high_load))
    if template == "stationary-trucks":
        return _Stationary(template, args)
    if template == "long-driving-sessions":
        return _LongDriving(template, args)
    if template == "avg-load":
        return _AvgLoad(template, args)
    raise KeyError(f"no TSBS IoT template {template!r}")

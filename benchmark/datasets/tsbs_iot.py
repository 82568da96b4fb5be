"""TSBS `--use-case=iot`: a trucking company's two measurements,
`readings` and `diagnostics`, under eight tags.

The row is the source's (github.com/timescale/tsbs, README "IoT"):
tags name, fleet, driver, model, device_version, load_capacity,
fuel_capacity, nominal_fuel_consumption — ALL eight the primary key —
ts TIMESTAMP(3); `readings` has seven DOUBLE fields (latitude,
longitude, elevation, velocity, heading, grade, fuel_consumption),
`diagnostics` three (fuel_state, current_load, status). One row a truck
a table every `step_s`. **No `append_mode`**: the tables are what the
line-protocol door's auto-create gives, so the last write of a
(primary key, ts) wins.

What makes it IoT, all a function of (seed, scale), shares in the
configuration's `assumed` block:

* gaps      `gap_share` of the rows of each table never arrive: the row
            is ABSENT (`present` is False), not NULL; `rows` counts the
            present ones;
* backlog   `backlog_share` of the trucks are offline for ONE stretch of
            10-60 min of data time; the stretch's rows (of both tables)
            are written AFTER the rows of the hour that follows it, so
            SSTs overlap in time;
* resend    a tenth of those trucks' uploads begin up to an hour before
            the stretch: rows that had already arrived are written
            again, same primary key, ts and values, later sequence
            (`resent`); `rows` counts each (key, ts) once;
* NULL tags `null_share` of the trucks have no `name`, as many no
            `driver`, no `model`, no `fleet` (disjoint sets). A truck
            without a name keeps a combination of the other seven tags
            that no other nameless truck has (redrawn until it is so):
            the primary key still names one truck.

Values: `velocity` is a slow wave plus noise clamped to [5, 100] while a truck
drives and GPS jitter in [0, 0.5) while it stands (stretches drawn per
truck, so `stationary-trucks` and `long-driving-sessions` select some
trucks and not all); `fuel_state` a sawtooth in [0, 1] (consumption,
refuel); `current_load` is TSBS's: a clamped random walk of N(0, 1)
steps rounded to whole numbers, between 0 and the truck's
`load_capacity`, so a truck's neighbouring rows are often equal (a
float32 sum of them must not drift: `ops/segment.py`
`float_segment_sum`). No `fuel_state` lies within 1e-4 of 0.1 and no
load is exactly nine tenths of its capacity (moved away here), so that
the two thresholds of `low-fuel` and `high-load` select the same trucks
in float32 and in float64.

`require_lww_merge` refuses a program that would build the
last-write-wins mask of these tables by a whole-scan device sort per
row count (see there).
"""

from __future__ import annotations

import os

import numpy as np

TAGS = ["name", "fleet", "driver", "model", "device_version",
        "load_capacity", "fuel_capacity", "nominal_fuel_consumption"]
READINGS = ["latitude", "longitude", "elevation", "velocity", "heading",
            "grade", "fuel_consumption"]
DIAGNOSTICS = ["fuel_state", "current_load", "status"]
T0_MS = 1451606400000  # 2016-01-01T00:00:00Z (TSBS's default start)

FLEETS = ["East", "West", "North", "South"]
DRIVERS = ["Derek", "Rodney", "Albert", "Andy", "Seth", "Trish", "Mia",
           "Lena", "Omar", "Ines"]
MODELS = ["F-150", "G-2000", "H-2"]
VERSIONS = ["v1.0", "v1.5", "v2.0", "v2.3"]
LOAD_CAPACITY = [1500, 2000, 5000]
FUEL_CAPACITY = [150, 300]
NOMINAL_FUEL = [12, 15, 19]

GAP_SHARE = 0.01
BACKLOG_SHARE = 0.05
RESEND_OF_BACKLOG = 0.1
NULL_SHARE = 0.01
HOUR_S = 3600
#: the thresholds of low-fuel / high-load and the room kept around them
LOW_FUEL, HIGH_LOAD, ROOM = 0.1, 0.9, 1e-4


def require_lww_merge(root: str | None = None) -> None:
    """Refuse, in its first second, a program whose last-write-wins
    mask is a device sort compiled per row count: every request of this
    deployment's cell scans a non-append table, and such a program
    compiles seven programs and sorts the whole scan on the device for
    each new row count. The parent commit, left to itself, read back the
    two tables in 37 s and then did not answer `last-loc`'s first
    warm-up request in 280 s, when the run's time limit killed it (my
    chip run, PR 38; PERF.md section 6), and it reads a tag's dictionary
    code where `high-load` casts `load_capacity` to a number: it neither
    answers nor fails at once. The mechanism a program needs is
    `greptimedb_tpu/query/lww.py`."""
    root = root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if not os.path.isfile(os.path.join(root, "greptimedb_tpu", "query",
                                       "lww.py")):
        raise ValueError(
            "tsbs-iot-4000 needs a program whose last-write-wins mask "
            "comes from the host's merge of sorted runs "
            "(greptimedb_tpu/query/lww.py): this one would compile and "
            "run a whole-scan device sort for every request")


class _View:
    """One table: the single-table interface of the harness, plus
    `present`, `resent` and `write_order` for what a dense grid cannot
    say."""

    step_ms: int

    def __init__(self, ds, table: str, names: list, fields: dict,
                 present: np.ndarray):
        self._ds, self.table, self.names = ds, table, names
        self.fields = fields
        self.present = present
        self.series, self.points = ds.trucks, ds.points
        self.t0_ms, self.t_end_ms, self.step_ms = \
            ds.t0_ms, ds.t_end_ms, ds.step_ms
        #: rows written twice (the same values): present, and inside a
        #: resending truck's resent stretch
        self.resent = present & ds.resent_mask
        #: distinct (primary key, ts): what count(*) must read
        self.rows = int(present.sum())
        self.rows_written = self.rows + int(self.resent.sum())
        self._last = None

    def create_sql(self) -> str:
        return (
            f"CREATE TABLE {self.table} ("
            + ", ".join(f"{t} STRING" for t in TAGS)
            + ", ts TIMESTAMP(3) NOT NULL, "
            + ", ".join(f"{f} DOUBLE" for f in self.names)
            + ", TIME INDEX (ts), PRIMARY KEY (" + ", ".join(TAGS) + "))")

    def series_tags(self) -> dict:
        return self._ds.tag_values

    def ts_of(self, points: np.ndarray) -> np.ndarray:
        return self.t0_ms + np.asarray(points, np.int64) * self.step_ms

    def last_point(self) -> np.ndarray:
        """Per truck the newest point it has a row of (-1: none)."""
        if self._last is None:
            rev = self.present[::-1]
            last = self.points - 1 - rev.argmax(axis=0)
            self._last = np.where(rev.any(axis=0), last, -1)
        return self._last

    def write_order(self, max_rows: int):
        """(points[int32 n], series[int32 n], resent rows among them) of
        one write after the other: time slices of the rows that arrive
        on time, each followed by the backlogs that are due — an offline
        stretch's rows one hour of data after its end (or when the span
        ends), with a resending truck's earlier rows before them."""
        ds = self._ds
        per = max(1, max_rows // self.series)
        on_time = self.present & ~ds.offline_mask
        due = np.minimum(ds.backlog_end + ds.hour_points, self.points)
        for p0 in range(0, self.points, per):
            p1 = min(p0 + per, self.points)
            pp, ss = np.nonzero(on_time[p0:p1])
            yield (pp + p0).astype(np.int32), ss.astype(np.int32), 0
            late_p, late_s, again = [], [], 0
            for k in np.flatnonzero((due > p0) & (due <= p1)):
                truck = int(ds.backlog_trucks[k])
                pts = np.arange(ds.resend_start[k], ds.backlog_end[k])
                pts = pts[self.present[pts, truck]]
                late_p.append(pts)
                late_s.append(np.full(len(pts), truck))
                again += int((pts < ds.backlog_start[k]).sum())
            if late_p:
                yield (np.concatenate(late_p).astype(np.int32),
                       np.concatenate(late_s).astype(np.int32), again)


class Dataset:
    def __init__(self, seed: int, scale: dict):
        require_lww_merge()
        self.seed = int(seed)
        self.trucks = n = int(scale["trucks"])
        self.hours = int(scale["hours"])
        self.step_ms = int(scale["step_s"]) * 1000
        self.points = p = self.hours * HOUR_S * 1000 // self.step_ms
        self.hour_points = HOUR_S * 1000 // self.step_ms
        self.t0_ms = T0_MS
        self.t_end_ms = T0_MS + self.hours * HOUR_S * 1000
        self._tags(np.random.default_rng([self.seed, 2]))
        self._backlogs(np.random.default_rng([self.seed, 6]))
        grng = np.random.default_rng([self.seed, 5])
        readings = self._readings(np.random.default_rng([self.seed, 1]))
        diagnostics = self._diagnostics(
            np.random.default_rng([self.seed, 4]))
        self._views = [
            _View(self, "readings", READINGS, readings,
                  grng.random((p, n)) >= GAP_SHARE),
            _View(self, "diagnostics", DIAGNOSTICS, diagnostics,
                  grng.random((p, n)) >= GAP_SHARE)]
        self.rows = sum(v.rows for v in self._views)

    def tables(self) -> list:
        return self._views

    #: where the harness asks a dataset for ONE table it means the first
    table = "readings"

    def create_sql(self) -> str:
        return self._views[0].create_sql()

    def view(self, table: str) -> _View:
        return next(v for v in self._views if v.table == table)

    # ---- tags -----------------------------------------------------------------

    def _tags(self, rng) -> None:
        n = self.trucks

        def pick(domain):
            return np.asarray(domain, dtype=object)[
                rng.integers(0, len(domain), n)]

        tags = {
            "name": np.asarray([f"truck_{i}" for i in range(n)],
                               dtype=object),
            "fleet": pick(FLEETS), "driver": pick(DRIVERS),
            "model": pick(MODELS), "device_version": pick(VERSIONS),
            "load_capacity": pick([str(x) for x in LOAD_CAPACITY]),
            "fuel_capacity": pick([str(x) for x in FUEL_CAPACITY]),
            "nominal_fuel_consumption": pick([str(x) for x in NOMINAL_FUEL]),
        }
        k = max(1, int(round(n * NULL_SHARE))) if n >= 8 else 0
        order = rng.permutation(n)
        self.null_trucks = {}
        for i, tag in enumerate(["name", "driver", "model", "fleet"]):
            who = np.sort(order[i * k:(i + 1) * k])
            tags[tag][who] = None
            self.null_trucks[tag] = who
        # a nameless truck keeps a combination of the other tags of its
        # own: the primary key still names one truck
        nameless = self.null_trucks.get("name", np.empty(0, np.int64))
        rest = [t for t in TAGS if t != "name"]
        seen: set = set()
        for t in nameless:
            for _ in range(1000):
                key = tuple(tags[x][t] for x in rest)
                if key not in seen:
                    break
                tags["device_version"][t] = VERSIONS[
                    int(rng.integers(0, len(VERSIONS)))]
                tags["driver"][t] = DRIVERS[
                    int(rng.integers(0, len(DRIVERS)))]
            else:
                raise ValueError("cannot keep the nameless trucks apart")
            seen.add(key)
        self.tags = tags
        self.tag_values = {t: v.tolist() for t, v in tags.items()}
        self.load_capacity = np.asarray(
            [float(x) for x in tags["load_capacity"]])

    # ---- arrival order --------------------------------------------------------

    def _backlogs(self, rng) -> None:
        n, p, hour = self.trucks, self.points, self.hour_points
        k = int(round(n * BACKLOG_SHARE))
        self.backlog_trucks = np.sort(rng.choice(n, size=k, replace=False))
        length = rng.integers(hour // 6, hour + 1, k)  # 10-60 min
        length = np.minimum(length, max(1, p // 2))
        self.backlog_start = rng.integers(0, p - length + 1)
        self.backlog_end = self.backlog_start + length
        resends = np.zeros(k, bool)
        resends[rng.permutation(k)[:int(np.ceil(k * RESEND_OF_BACKLOG))]] = \
            k > 0
        self.resend_start = np.where(
            resends, np.maximum(0, self.backlog_start - hour),
            self.backlog_start)
        self.offline_mask = np.zeros((p, n), bool)
        self.resent_mask = np.zeros((p, n), bool)
        for j, t in enumerate(self.backlog_trucks):
            self.offline_mask[self.backlog_start[j]:self.backlog_end[j],
                              t] = True
            self.resent_mask[self.resend_start[j]:self.backlog_start[j],
                             t] = True

    # ---- values ---------------------------------------------------------------

    def _driving(self, rng) -> np.ndarray:
        """[points, trucks] bool. A fifth of the trucks are long-haul:
        they drive 8-16 h between rests; the others stand for 38-46 min
        after every 180-220 min of driving. Every truck starts somewhere
        inside a stretch of its own."""
        n, p = self.trucks, self.points
        per_min = 60_000 / self.step_ms
        long_haul = rng.random(n) < 0.2
        k = int(p / (170 * per_min)) + 3
        lo = np.where(long_haul, 480.0, 180.0)[:, None]
        hi = np.where(long_haul, 960.0, 220.0)[:, None]
        drive = rng.uniform(lo, hi, (n, k)) * per_min
        stand = rng.uniform(38.0, 46.0, (n, k)) * per_min
        cycle = np.stack([drive, stand], axis=2).reshape(n, 2 * k)
        edges = np.cumsum(cycle, axis=1) - (rng.random(n) * (
            cycle[:, 0] + cycle[:, 1]))[:, None]
        edges = np.ceil(edges).astype(np.int64)
        flips = np.zeros((n, p + 1), np.int8)
        rows = np.repeat(np.arange(n), 2 * k)
        inside = (edges.reshape(-1) >= 0) & (edges.reshape(-1) < p)
        np.add.at(flips, (rows[inside], edges.reshape(-1)[inside]), 1)
        # before the first edge the truck is in the stretch the offset
        # cut: driving if the offset fell inside the first drive
        first = (edges < 0).sum(axis=1)
        state = (np.cumsum(flips[:, :p], axis=1, dtype=np.int32)
                 + first[:, None]) % 2 == 0
        return np.ascontiguousarray(state.T)

    def _readings(self, rng) -> dict:
        n, p = self.trucks, self.points
        driving = self._driving(rng)
        t = np.arange(p, dtype=np.float64)[:, None]

        def wave(lo, hi, periods):
            """A slow wave of each truck's own period and phase between
            lo and hi, plus noise of a hundredth of the range: what a
            clamped random walk looks like to a query, made at once."""
            period = rng.uniform(*periods, n) * self.hour_points
            mid, half = (hi + lo) / 2, (hi - lo) / 2
            x = t / period
            x += rng.random(n)
            x *= 2 * np.pi
            np.sin(x, out=x)
            x *= 0.8 * half
            x += mid
            noise = rng.random((p, n))
            noise -= 0.5
            noise *= 0.02 * half
            x += noise
            return np.clip(x, lo, hi, out=x)

        velocity = rng.random((p, n))
        velocity *= 0.5
        np.copyto(velocity, wave(5.0, 100.0, (0.5, 3.0)), where=driving)
        return {
            "latitude": wave(-90.0, 90.0, (6.0, 48.0)),
            "longitude": wave(-180.0, 180.0, (6.0, 48.0)),
            "elevation": wave(0.0, 5000.0, (1.0, 12.0)),
            "velocity": velocity,
            "heading": rng.random((p, n)) * 360.0,
            "grade": rng.random((p, n)) * 100.0,
            "fuel_consumption": rng.random((p, n)) * 50.0,
        }

    def _diagnostics(self, rng) -> dict:
        n, p = self.trucks, self.points
        t = np.arange(p, dtype=np.float64)[:, None]
        # a tank that empties in 4-10 h and is refilled at once
        rate = 1.0 / (rng.uniform(4.0, 10.0, n) * self.hour_points)
        fuel = rng.random(n) + t * rate
        fuel = 1.0 - (fuel - np.floor(fuel))
        fuel[np.abs(fuel - LOW_FUEL) < ROOM] = LOW_FUEL - 2 * ROOM
        # TSBS's current_load: a clamped random walk of N(0, 1) steps,
        # rounded to whole numbers (common.FP(CWD(ND(0, 1), ...), 0)), so
        # equal neighbours are common. TSBS starts every truck at 0 and
        # clamps at 5000; here a truck starts anywhere under its own
        # load_capacity and is clamped there, so that the ratio fills
        # [0, 1] and `high-load` selects some trucks
        cap = self.load_capacity
        x = rng.random(n) * cap
        load = rng.standard_normal((p, n))
        for i in range(p):
            x += load[i]
            np.clip(x, 0.0, cap, out=x)
            load[i] = x
        np.rint(load, out=load)
        load[load == np.rint(HIGH_LOAD * cap)] += 1.0
        return {
            "fuel_state": fuel,
            "current_load": load,
            "status": np.floor(rng.random((p, n)) * 6.0),
        }

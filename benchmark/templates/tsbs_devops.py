"""TSBS devops query templates over table `cpu`, each with its plain
numpy reference.

A template is named as TSBS names it and found by that name:

    single-groupby-<M>-<H>-<T>   max of the first M fields, H random
                                 hosts, per minute, random T-hour window
    cpu-max-all-<H>              max of all ten fields, H random hosts,
                                 per hour, random 8-hour window
    double-groupby-<M|all>       avg of the first M fields per host per
                                 hour over a random 12-hour window
    groupby-orderby-limit        the last five per-minute max(usage_user)
                                 before a random endpoint
    lastpoint                    the newest row of every host

so a traffic mix over other M/H/T is a data file. Every parameter is
drawn from the rng it is handed; windows start at an unaligned
millisecond as TSBS's do. Windows longer than the table's span are cut
to the span (the config file says so under `reduced`).

The references use the seeded arrays and numpy only — no engine code.
What is compared, and why each limit is what it is, is written at
`LIMITS`; PERF.md section 2 gives the readings each was set from.
"""

from __future__ import annotations

import json
import re
import urllib.parse

import numpy as np

FIELDS = [f"usage_{n}" for n in (
    "user", "system", "idle", "nice", "iowait", "irq", "softirq",
    "steal", "guest", "guest_nice")]

# The chip computes SQL aggregates in float32 (config.compute_dtype: the
# TPU has no native f64); a CPU process computes in float64. /v1/device
# says which, and each comparison is written against it.
#
#   exact   max / last_value select one stored value. Rounding to the
#           compute dtype is monotonic, so the answer must EQUAL the
#           reference rounded to that dtype: the number compared is the
#           count of values that differ, limit 0. A bfloat16 (or, under
#           f64, a float32) answer differs in nearly every value.
#   mean    avg over n <= 360 values accumulates in the compute dtype.
#           The number compared is the widest relative gap over the
#           answer. f32: sound runs on the chip read at most 1.5e-6
#           (PERF.md section 2), the control (inputs rounded to
#           bfloat16, f32 accumulation: the mildest bf16 variant) reads
#           at least 4.7e-4 at the cell's own size; the limit 2e-5 is
#           13 times the one and a 23rd of the other, and is n * 2^-24
#           for n = 360, the worst case of a sequential f32 sum.
#           f64 (CPU rehearsals): 1e-12.
LIMITS = {
    "exact": {"float32": 0.0, "float64": 0.0},
    "mean": {"float32": 2e-5, "float64": 1e-12},
}
LOWER = {"float32": "bfloat16", "float64": "float32"}


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


def _point_range(ds, start_ms: int, end_ms: int) -> tuple:
    """[p_lo, p_hi) of the points with start <= ts < end."""
    lo = max(0, -(-(start_ms - ds.t0_ms) // ds.step_ms))
    hi = min(ds.points, -(-(end_ms - ds.t0_ms) // ds.step_ms))
    return int(lo), int(max(lo, hi))


def _buckets(ds, p_lo: int, p_hi: int, bucket_ms: int) -> tuple:
    """Bucket keys (ms) and the first point of each bucket."""
    ts = ds.t0_ms + np.arange(p_lo, p_hi, dtype=np.int64) * ds.step_ms
    ids = ts // bucket_ms
    first = np.flatnonzero(np.r_[True, ids[1:] != ids[:-1]])
    return ids[first] * bucket_ms, first


class _Sql:
    kind = "exact"

    def __init__(self, name: str):
        self.name = name

    def request(self, params: dict, ds) -> tuple:
        body = urllib.parse.urlencode({"sql": self.sql(params, ds)}).encode()
        return "POST", "/v1/sql", body

    @staticmethod
    def parse(status: int, data: bytes) -> tuple:
        """(rows or None, server_ms or None, error or None)."""
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

    def expected_rows(self, params: dict, ds) -> int:
        return len(self.reference(params, ds)[0])

    def edges(self, ds) -> list:
        """No edge cases to warm up: every new literal is a new
        executable here anyway."""
        return []

    def limit(self, dtype: str) -> float:
        return LIMITS[self.kind][dtype]

    def compare(self, rows: list, params: dict, ds, dtype: str,
                lowered: bool = False) -> float:
        """The number compared for one answer (see LIMITS); inf where
        the keys differ. `lowered` compares the control instead: the
        reference computed one precision below `dtype`."""
        # one slot: a template without drawn parameters (a window that
        # covers the whole span, lastpoint) has the same reference in
        # every request
        slot = (json.dumps(params, sort_keys=True), id(ds))
        if getattr(self, "_ref_slot", None) != slot:
            self._ref, self._ref_slot = self.reference(params, ds), slot
        keys, ref = self._ref
        if lowered:
            got_keys, got = self.reference(params, ds, LOWER[dtype])
            got = np.asarray(got, np.float64)
        else:
            got_keys, got = self.decode(rows, params, ds)
        if list(got_keys) != list(keys) or got.shape != ref.shape:
            return float("inf")
        if not np.isfinite(got).all():
            return float("inf")
        if self.kind == "exact":
            return float((got != round_to(dtype, ref)).sum())
        return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref),
                                                           1e-300)))


def _hosts_sql(hosts: list) -> str:
    if len(hosts) == 1:
        return f"hostname = 'host_{hosts[0]}'"
    return "hostname IN (" + ", ".join(f"'host_{h}'" for h in hosts) + ")"


class _GroupedMax(_Sql):
    """max(fields) of some hosts per time bucket over a window."""

    def __init__(self, name: str, n_fields: int, n_hosts: int, hours: int,
                 bucket_ms: int, alias: str, interval: str):
        super().__init__(name)
        self.fields = FIELDS[:n_fields]
        self.n_hosts, self.hours = n_hosts, hours
        self.bucket_ms, self.alias, self.interval = bucket_ms, alias, interval

    def draw(self, rng, ds) -> dict:
        span = min(self.hours * 3600_000, ds.t_end_ms - ds.t0_ms)
        start = ds.t0_ms + int(rng.integers(
            0, ds.t_end_ms - ds.t0_ms - span + 1))
        hosts = rng.choice(ds.hosts, size=min(self.n_hosts, ds.hosts),
                           replace=False)
        return {"start": start, "end": start + span,
                "hosts": sorted(int(h) for h in hosts)}

    def sql(self, p: dict, ds) -> str:
        return (f"SELECT date_bin(INTERVAL '{self.interval}', ts) AS "
                f"{self.alias}, "
                + ", ".join(f"max({f})" for f in self.fields)
                + f" FROM {ds.table} WHERE {_hosts_sql(p['hosts'])} "
                f"AND ts >= {p['start']} AND ts < {p['end']} "
                f"GROUP BY {self.alias} ORDER BY {self.alias}")

    def reference(self, p: dict, ds, precision: str = "float64") -> tuple:
        lo, hi = _point_range(ds, p["start"], p["end"])
        keys, first = _buckets(ds, lo, hi, self.bucket_ms)
        cols = []
        for f in self.fields:
            per_point = round_to(
                precision, ds.fields[f][lo:hi][:, p["hosts"]]).max(axis=1)
            cols.append(np.maximum.reduceat(per_point, first))
        return keys.tolist(), np.stack(cols, axis=1)

    def expected_rows(self, p: dict, ds) -> int:
        lo, hi = _point_range(ds, p["start"], p["end"])
        return len(_buckets(ds, lo, hi, self.bucket_ms)[0])

    def decode(self, rows: list, p: dict, ds) -> tuple:
        return ([r[0] for r in rows],
                np.asarray([r[1:] for r in rows], np.float64).reshape(
                    len(rows), len(self.fields)))


class _DoubleGroupby(_Sql):
    kind = "mean"

    def __init__(self, name: str, n_fields: int):
        super().__init__(name)
        self.fields = FIELDS[:n_fields]

    def draw(self, rng, ds) -> dict:
        span = min(12 * 3600_000, ds.t_end_ms - ds.t0_ms)
        start = ds.t0_ms + int(rng.integers(
            0, ds.t_end_ms - ds.t0_ms - span + 1))
        return {"start": start, "end": start + span}

    def sql(self, p: dict, ds) -> str:
        return ("SELECT date_bin(INTERVAL '1 hour', ts) AS hour, hostname, "
                + ", ".join(f"avg({f})" for f in self.fields)
                + f" FROM {ds.table} WHERE ts >= {p['start']} "
                f"AND ts < {p['end']} "
                "GROUP BY hour, hostname ORDER BY hour, hostname")

    def reference(self, p: dict, ds, precision: str = "float64") -> tuple:
        lo, hi = _point_range(ds, p["start"], p["end"])
        keys, first = _buckets(ds, lo, hi, 3600_000)
        counts = np.diff(np.r_[first, hi - lo]).astype(np.float64)
        acc = np.float64 if precision == "float64" else np.float32
        out = np.empty((len(keys), ds.hosts, len(self.fields)))
        for i, f in enumerate(self.fields):
            x = round_to(precision, ds.fields[f][lo:hi]).astype(acc)
            out[:, :, i] = np.add.reduceat(x, first, axis=0) \
                / counts[:, None].astype(acc)
        # one key per (hour, host) row, in the ORDER BY's order: hosts
        # sort as strings
        order = sorted(range(ds.hosts), key=lambda h: f"host_{h}")
        return ([(int(k), h) for k in keys for h in order],
                out[:, order, :].reshape(-1, len(self.fields)))

    def expected_rows(self, p: dict, ds) -> int:
        lo, hi = _point_range(ds, p["start"], p["end"])
        return len(_buckets(ds, lo, hi, 3600_000)[0]) * ds.hosts

    def decode(self, rows: list, p: dict, ds) -> tuple:
        return ([(r[0], int(r[1][5:])) for r in rows],
                np.asarray([r[2:] for r in rows], np.float64).reshape(
                    len(rows), len(self.fields)))


class _GroupbyOrderbyLimit(_Sql):
    def draw(self, rng, ds) -> dict:
        lo = min(3600_000, (ds.t_end_ms - ds.t0_ms) // 2)
        return {"end": ds.t0_ms + int(rng.integers(
            lo, ds.t_end_ms - ds.t0_ms + 1))}

    def sql(self, p: dict, ds) -> str:
        return ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
                f"max(usage_user) FROM {ds.table} WHERE ts < {p['end']} "
                "GROUP BY minute ORDER BY minute DESC LIMIT 5")

    def reference(self, p: dict, ds, precision: str = "float64") -> tuple:
        _, hi = _point_range(ds, ds.t0_ms, p["end"])
        # five minute-buckets need at most 6 minutes of points
        lo = max(0, hi - 6 * 60_000 // ds.step_ms - 1)
        keys, first = _buckets(ds, lo, hi, 60_000)
        per_point = round_to(
            precision, ds.fields["usage_user"][lo:hi]).max(axis=1)
        vals = np.maximum.reduceat(per_point, first)
        if lo > 0:  # the first bucket may be cut by `lo`: drop it
            keys, vals = keys[1:], vals[1:]
        return keys[::-1][:5].tolist(), vals[::-1][:5].reshape(-1, 1)

    def decode(self, rows: list, p: dict, ds) -> tuple:
        return ([r[0] for r in rows],
                np.asarray([r[1] for r in rows], np.float64).reshape(-1, 1))


class _Lastpoint(_Sql):
    #: the answer depends on the front of a table that is written to:
    #: under a writer it is compared at the request's own front
    fresh = True

    def draw(self, rng, ds) -> dict:
        return {}

    def sql(self, p: dict, ds) -> str:
        return ("SELECT hostname, "
                + ", ".join(f"last_value({f} ORDER BY ts)" for f in FIELDS)
                + f" FROM {ds.table} GROUP BY hostname")

    def reference(self, p: dict, ds, precision: str = "float64") -> tuple:
        return (list(range(ds.hosts)),
                np.stack([round_to(precision, ds.fields[f][-1])
                          for f in FIELDS], axis=1))

    def expected_rows(self, p: dict, ds) -> int:
        return ds.hosts

    def compare(self, rows: list, params: dict, ds, dtype: str,
                lowered: bool = False, front=None) -> float:
        """Under a writer (`front`, harness/traffic.py `Front`): every
        host's ten values must equal the seeded values of ONE of its
        rows no older than the newest acknowledged before the request
        was sent, no newer than the newest sent before its answer
        arrived, and sent by then. The answer carries no ts; ten
        uniform doubles name their tick. The number compared is the
        count of values that differ from the host's nearest admissible
        row, limit 0 as without a writer."""
        if front is None:
            return super().compare(rows, params, ds, dtype, lowered)
        keys, got = self.decode(rows, params, ds)
        if keys != list(range(ds.hosts)) or not np.isfinite(got).all():
            return float("inf")
        best = np.full(ds.hosts, len(FIELDS), np.int64)
        for i in range(int(front.lower.min()), int(front.upper.max()) + 1):
            vals = ds.tick(i)[1] if i >= 0 else \
                {f: ds.fields[f][i] for f in FIELDS}
            ref = round_to(dtype, np.stack([vals[f] for f in FIELDS],
                                           axis=1))
            ok = (front.lower <= i) & (i <= front.upper) & front.sent(i)
            best = np.where(ok, np.minimum(best, (got != ref).sum(axis=1)),
                            best)
        return float(best.sum())

    def decode(self, rows: list, p: dict, ds) -> tuple:
        # GROUP BY without ORDER BY: any order
        rows = sorted(rows, key=lambda r: int(r[0][5:]))
        return ([int(r[0][5:]) for r in rows],
                np.asarray([r[1:] for r in rows], np.float64).reshape(
                    len(rows), len(FIELDS)))


def make(template: str, args: dict | None = None):
    m = re.fullmatch(r"single-groupby-(\d+)-(\d+)-(\d+)", template)
    if m:
        nf, nh, hours = (int(x) for x in m.groups())
        if not 1 <= nf <= len(FIELDS):
            raise KeyError(template)
        return _GroupedMax(template, nf, nh, hours, 60_000, "minute",
                           "1 minute")
    m = re.fullmatch(r"cpu-max-all-(\d+)", template)
    if m:
        return _GroupedMax(template, len(FIELDS), int(m.group(1)), 8,
                           3600_000, "hour", "1 hour")
    m = re.fullmatch(r"double-groupby-(\d+|all)", template)
    if m:
        n = len(FIELDS) if m.group(1) == "all" else int(m.group(1))
        if not 1 <= n <= len(FIELDS):
            raise KeyError(template)
        return _DoubleGroupby(template, n)
    if template == "groupby-orderby-limit":
        return _GroupbyOrderbyLimit(template)
    if template == "lastpoint":
        return _Lastpoint(template)
    raise KeyError(f"no TSBS devops template {template!r}")

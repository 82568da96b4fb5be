"""The one general traffic generator and the closed-loop window.

A traffic mix is a data file, benchmark/traffic/<mix>.json:

    {"family": "<file under benchmark/templates/>", "loop": "closed",
     "clients": 8,
     "mix": [{"template": "...", "name": "...", "weight": 1,
              "check_share": 0.25, "args": {...}}, ...]}

Every request's parameters are drawn from --seed: client c reads the
stream default_rng([seed, 7, c]). The ORDER of templates does not depend
on the seed: each client walks shuffled blocks that hold each entry
`weight` times (weights are whole numbers), shuffled from a fixed stream
of the client's own, so that every run sends the same requests in the
same places with other parameters, and no run is heavier than another by
the luck of the draw (requests here take seconds, so a window holds only
some tens of them). Warm-up draws come from a
stream of their own that does NOT depend on the seed (so that after the
first run of a cell in a checkout every warm-up program is in the
compile cache), and a window draw that equals a warm-up draw is drawn
again: the window never repeats a warm-up request.

A mix may carry a paced writer beside its readers:

    "writer": {"route": "influxdb", "ticks_per_s": 4, "late_share": 0.02,
               "late_span_s": 600, "check_every": 5}

(`Writer`, below; README "A writer inside the window"). A mix without
the key runs as it always did: same draws, same threads, no write.
"""

from __future__ import annotations

import collections
import json
import threading
import time

import numpy as np

from . import wire
from .common import BenchFailure, load_json, load_module

WARM_STREAM = 20260927  # fixed: warm-up requests are the same in every run
WARM_DRAWS = 3
MAX_CHECKED = 20  # answers compared per template after the window


class Entry:
    def __init__(self, idx: int, spec: dict, family):
        self.idx = idx
        self.template = family.make(spec["template"], spec.get("args"))
        self.name = spec.get("name", spec["template"])
        self.template.name = self.name
        self.weight = int(spec["weight"])
        if self.weight < 1 or self.weight != spec["weight"]:
            raise ValueError(f"{self.name}: weight must be a whole number")
        self.check_share = float(spec.get("check_share", 1.0))


class Mix:
    def __init__(self, name: str, ds, clients: int | None = None):
        spec = load_json("traffic", name + ".json")
        if spec.get("loop", "closed") != "closed":
            raise ValueError("only closed loops are generated so far")
        self.name = name
        self.ds = ds
        self.clients = int(clients or spec["clients"])
        family = load_module("templates", spec["family"])
        self.entries = [Entry(i, e, family)
                        for i, e in enumerate(spec["mix"])]
        self.writer_spec = spec.get("writer")
        self.block = [e.idx for e in self.entries for _ in range(e.weight)]
        self.p = np.bincount(self.block) / len(self.block)
        self._warm = [self._warm_draws(e) for e in self.entries]
        self._warm_keys = {(e.idx, _key(p)) for e, ps in
                           zip(self.entries, self._warm) for p in ps}

    def _warm_draws(self, e: Entry) -> list:
        """WARM_DRAWS fixed draws, then the template's edge cases (the
        first and the last window the table admits: shapes a random
        draw reaches once in hundreds of requests)."""
        rng = np.random.default_rng([WARM_STREAM, 8, e.idx])
        draws = [e.template.draw(rng, self.ds) for _ in range(WARM_DRAWS)]
        for p in e.template.edges(self.ds):
            if p not in draws:
                draws.append(p)
        return draws

    def warmup(self, e: Entry) -> list:
        return self._warm[e.idx]

    def stream(self, seed: int, client: int):
        """Endless (entry, params, check?) for one client."""
        rng = np.random.default_rng([int(seed), 7, int(client)])
        order = np.random.default_rng([WARM_STREAM, 6, int(client)])
        seen: set = set()
        block: list = []
        while True:
            if not block:
                block = [int(i) for i in order.permutation(self.block)]
            e = self.entries[block.pop()]
            params = e.template.draw(rng, self.ds)
            # a client's first answer of each template is always kept
            check = bool(rng.random() < e.check_share) or e.idx not in seen
            seen.add(e.idx)
            # the window never repeats a warm-up draw — unless the
            # template has one request only (lastpoint; a 12 h window
            # over a 12 h table), which shows as the same draw again
            for _ in range(4):
                if (e.idx, _key(params)) not in self._warm_keys:
                    break
                params = e.template.draw(rng, self.ds)
            yield e, params, check


def _key(params: dict) -> str:
    return json.dumps(params, sort_keys=True)


class Request:
    __slots__ = ("entry", "params", "t_send", "t_done", "error", "rows_ok",
                 "server_ms", "body")

    @property
    def ok(self) -> bool:
        return self.error is None and self.rows_ok

    @property
    def ms(self) -> float:
        return (self.t_done - self.t_send) * 1e3


def issue(client, e: Entry, params: dict, ds, keep_body: bool) -> Request:
    """One request, timed from send to last byte parsed; status and row
    count are checked inline, values after the window."""
    t = e.template
    want = t.expected_rows(params, ds)
    method, path, body = t.request(params, ds)
    r = Request()
    r.entry, r.params, r.body = e, params, None
    r.t_send = time.monotonic()
    try:
        status, data = client.request(method, path, body)
        parsed, r.server_ms, r.error = t.parse(status, data)
    except OSError as ex:
        parsed, r.server_ms, r.error, data = None, None, repr(ex), b""
    r.t_done = time.monotonic()
    r.rows_ok = parsed is not None and len(parsed) == want
    if r.error is None and not r.rows_ok:
        r.error = f"{len(parsed)} rows, expected {want}"
    if keep_body and r.error is None:
        r.body = data
    return r


# ---- the writer inside the window --------------------------------------------


class Batch:
    """One write of the writer: a tick's on-time rows plus earlier ticks'
    late rows (`tick` None: the closing batch, late rows only)."""

    __slots__ = ("tick", "rows", "on_time", "t_send", "t_ack", "error",
                 "newest")

    @property
    def ok(self) -> bool:
        return self.error is None

    @property
    def ms(self) -> float:
        return (self.t_ack - self.t_send) * 1e3


#: one read-after-acknowledge check: the tick read back, the rows that
#: had to be there, the rows read, ms from the 2xx to the answer, the
#: error where no answer came
Check = collections.namedtuple("Check", "tick want read ms error")


class Front:
    """What a `fresh` template's answer is held to, per series, in ticks
    (tick i is the sample at t_end_ms + i * step_ms; -1 the newest
    loaded one): `lower` the newest row acknowledged before the request
    was sent, `upper` the newest sent before its answer arrived, and
    `sent(i)` the series whose row of tick i had been sent by then (a
    withheld late row has not)."""

    def __init__(self, writer, lower, upper, last_tick):
        self.lower, self.upper = lower, upper
        self._writer, self._last = writer, last_tick

    def sent(self, i: int):
        if i < self._writer.first_tick:
            return np.ones(len(self.lower), bool)
        return self._writer.rides(i) <= self._last


class Writer:
    """The paced writer of a mix's `writer` block: one thread of the
    window on a keep-alive connection of its own.

    One batch is one tick: `ds.tick(i)`, every series' next sample
    after the loaded span, so the data stays a function of --seed.
    Tick k of the window is due at t0 + k / ticks_per_s; a writer that
    has fallen behind sends at once, one batch in flight, and the rate
    it achieved is what the run reports. `late_share` of a tick's rows
    (drawn from the seed) are withheld from their own batch and ride
    with a later one, at most `late_span_s` of data time later; the
    closing batch, sent when the window has closed, carries whatever is
    still withheld. Every row is sent once and none lies before
    `t_end_ms`. A batch is acknowledged by a 2xx.

    After every `check_every`-th acknowledged tick the writer's thread
    at once reads `count(*) WHERE ts = <that tick's ts>` through
    /v1/sql, the readers' door: fewer than the batch's on-time rows is a
    stale read.
    """

    ROUTES = {"influxdb": wire.LineProtocol}

    def __init__(self, spec: dict, ds, seed: int, first_tick: int = 0):
        """`first_tick`: where a second window over the same table goes
        on (the sweep that finds a cell's pace runs several)."""
        if not hasattr(ds, "tick"):
            raise BenchFailure(f"table {ds.table!r} cannot be written to: "
                               "its dataset offers no tick(i)")
        if spec["route"] not in self.ROUTES:
            raise BenchFailure(f"no write route {spec['route']!r}")
        self.ds, self.seed, self.first_tick = ds, int(seed), int(first_tick)
        self.route = self.ROUTES[spec["route"]](ds.table, ds.series_tags())
        self.ticks_per_s = float(spec["ticks_per_s"])
        self.late_share = float(spec["late_share"])
        self.late_ticks = int(spec["late_span_s"]) * 1000 // ds.step_ms
        self.check_every = int(spec["check_every"])
        self.batches: list = []
        self.checks: list = []
        self._rides: dict = {}

    # -- what rides with which batch: a function of the seed

    def rides(self, i: int):
        """int64[series]: the tick whose batch carries each series' row
        of tick i (i itself for a row on time)."""
        out = self._rides.get(i)
        if out is None:
            n = self.ds.series
            out = np.full(n, i, np.int64)
            if self.late_share > 0 and self.late_ticks > 0:
                rng = np.random.default_rng([self.seed, 4, int(i)])
                late = rng.random(n) < self.late_share
                out[late] += rng.integers(1, self.late_ticks + 1,
                                          n)[late]
            self._rides[i] = out
        return out

    def _rows_of(self, pairs: list):
        """(series, ts, fields) of [(tick, series index array), ...]."""
        series = np.concatenate([idx for _i, idx in pairs])
        ts, cols = [], {}
        for i, idx in pairs:
            t, fields = self.ds.tick(i)
            ts.append(np.full(len(idx), t, np.int64))
            for f, v in fields.items():
                cols.setdefault(f, []).append(v[idx])
        return series, np.concatenate(ts), {f: np.concatenate(v)
                                            for f, v in cols.items()}

    def _pairs(self, last: int, closing: bool) -> list:
        """[(tick, series index array), ...] of tick `last`'s batch: its
        on-time rows and the late rows that ride with it; of the closing
        batch, every row still withheld after it."""
        pairs = []
        for j in range(max(self.first_tick, last - self.late_ticks),
                       last + 1):
            r = self.rides(j)
            idx = np.flatnonzero(r > last if closing else r == last)
            if len(idx):
                pairs.append((j, idx))
        return pairs

    def _send(self, client, last: int, newest, closing=False) -> Batch:
        pairs = self._pairs(last, closing)
        b = Batch()
        b.tick, b.error = None if closing else last, None
        b.rows = sum(len(idx) for _j, idx in pairs)
        b.on_time = sum(len(idx) for j, idx in pairs if j == b.tick)
        b.newest = newest.copy()
        for j, idx in pairs:
            np.maximum.at(b.newest, idx, j)
        body = self.route.body(*self._rows_of(pairs)) if pairs else b""
        self.batches.append(b)
        b.t_send = time.monotonic()
        if body:
            try:
                status, data = client.request(
                    "POST", self.route.PATH, body, ctype=self.route.CTYPE)
                if not 200 <= status < 300:
                    b.error = f"write HTTP {status}: {data[:200]!r}"
            except OSError as ex:
                b.error = repr(ex)
        b.t_ack = time.monotonic()
        return b

    def _count_at(self, client, ts: int) -> int:
        return int(client.rows(f"SELECT count(*) FROM {self.ds.table} "
                               f"WHERE ts = {ts}")[0][0])

    def _check(self, client, b: Batch) -> None:
        read, error = None, None
        try:
            read = self._count_at(client, self.ds.tick(b.tick)[0])
        except (BenchFailure, OSError, LookupError, ValueError) as ex:
            error = repr(ex)
        self.checks.append(Check(b.tick, b.on_time, read,
                                 (time.monotonic() - b.t_ack) * 1e3, error))

    def warm_up(self, client) -> None:
        """The read-after-acknowledge query, once before the window,
        over the newest loaded sample: its program is compiled in
        set-up."""
        ts = self.ds.t_end_ms - self.ds.step_ms
        got = self._count_at(client, ts)
        if got != self.ds.series:
            raise BenchFailure(f"count(*) at ts {ts} reads {got}, the "
                               f"table holds {self.ds.series} series")

    def run(self, client, t0: float, seconds: float) -> None:
        """The writer's thread, from the barrier's release to the
        closing batch's answer."""
        t_end = t0 + seconds
        newest = np.full(self.ds.series, self.first_tick - 1, np.int64)
        k, acked, last = 0, 0, self.first_tick - 1
        while True:
            due = t0 + k / self.ticks_per_s
            now = time.monotonic()
            if max(now, due) >= t_end:
                break
            if due > now:
                time.sleep(due - now)
            last = self.first_tick + k
            b = self._send(client, last, newest)
            newest = b.newest
            k += 1
            if b.ok:
                acked += 1
                if acked % self.check_every == 0:
                    self._check(client, b)
        if last >= self.first_tick and self._pairs(last, closing=True):
            self._send(client, last, newest, closing=True)

    # -- what the checks after the window read

    @property
    def acked_rows(self) -> int:
        return sum(b.rows for b in self.batches if b.ok)

    @property
    def next_tick(self) -> int:
        ticks = [b.tick for b in self.batches if b.tick is not None]
        return max(ticks) + 1 if ticks else self.first_tick

    def front(self, t_send: float, t_done: float) -> Front:
        """The front a request sent at t_send and answered at t_done is
        held to."""
        base = np.full(self.ds.series, self.first_tick - 1, np.int64)
        lower, upper, last = base, base, self.first_tick - 1
        for b in self.batches:
            if b.ok and b.t_ack <= t_send:
                lower = b.newest
            if b.t_send <= t_done:
                upper = b.newest
                last = b.tick if b.tick is not None else np.iinfo(
                    np.int64).max
        return Front(self, lower, upper, last)

    def stale(self) -> list:
        """The checks that read fewer rows than their batch's on-time
        rows (or more than the table has series)."""
        return [c for c in self.checks if c.error is None
                and not c.want <= c.read <= self.ds.series]

    def errors(self) -> set:
        return {x.error for x in self.batches + self.checks if x.error}

    def stats(self, t0: float, seconds: float) -> dict:
        ok = [b for b in self.batches if b.ok and b.rows]
        inside = [b for b in ok if b.t_ack <= t0 + seconds]
        return {
            "ticks_per_s": self.ticks_per_s,
            "batches": len(self.batches),
            "batches_failed": sum(1 for b in self.batches if not b.ok),
            "rows_acknowledged": self.acked_rows,
            "rows_late": sum(b.rows - b.on_time for b in ok),
            "ticks_per_s_achieved": sum(
                1 for b in inside if b.tick is not None) / seconds,
            "rows_per_s_achieved": sum(b.rows for b in inside) / seconds,
            "checks": len(self.checks),
            "checks_failed": sum(1 for c in self.checks if c.error),
            "stale_reads": len(self.stale()),
            "first_tick": self.first_tick, "next_tick": self.next_tick}


def run_window(client, mix: Mix, seed: int, seconds: float,
               writer: Writer | None = None) -> dict:
    """`mix.clients` closed-loop callers for `seconds`, and the mix's
    writer beside them where it has one, released by the same barrier
    and stopped with them; a request that started inside the window is
    waited for. Returns the requests and the window's own times."""
    out: list = [[] for _ in range(mix.clients)]
    barrier = threading.Barrier(mix.clients + 1 + (writer is not None))
    t_box: dict = {}

    def loop(c: int) -> None:
        stream = mix.stream(seed, c)
        barrier.wait()
        t_end = t_box["t0"] + seconds
        while time.monotonic() < t_end:
            e, params, check = next(stream)
            out[c].append(issue(client, e, params, mix.ds, check))

    def write() -> None:
        barrier.wait()
        writer.run(client, t_box["t0"], seconds)

    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in range(mix.clients)]
    if writer is not None:
        threads.append(threading.Thread(target=write, daemon=True))
    for t in threads:
        t.start()
    t_box["t0"] = time.monotonic()
    barrier.wait()
    for t in threads:
        t.join()
    t_last = time.monotonic()
    t0 = t_box["t0"]
    reqs = [r for per in out for r in per]
    # generator lateness: the share of the window in which a client was
    # NOT waiting on the server (drawing, building the request)
    waited = sum(min(r.t_done, t0 + seconds) - r.t_send for r in reqs)
    return {"requests": reqs, "t0": t0, "seconds": seconds, "writer": writer,
            "drain_s": t_last - (t0 + seconds),
            "generator_share": 1.0 - waited / (seconds * mix.clients)}

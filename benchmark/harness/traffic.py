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
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np

from .common import load_json, load_module

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


def run_window(client, mix: Mix, seed: int, seconds: float) -> dict:
    """`mix.clients` closed-loop callers for `seconds`; a request that
    started inside the window is waited for. Returns the requests and
    the window's own times."""
    out: list = [[] for _ in range(mix.clients)]
    barrier = threading.Barrier(mix.clients + 1)
    t_box: dict = {}

    def loop(c: int) -> None:
        stream = mix.stream(seed, c)
        barrier.wait()
        t_end = t_box["t0"] + seconds
        while time.monotonic() < t_end:
            e, params, check = next(stream)
            out[c].append(issue(client, e, params, mix.ds, check))

    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in range(mix.clients)]
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
    return {"requests": reqs, "t0": t0, "seconds": seconds,
            "drain_s": t_last - (t0 + seconds),
            "generator_share": 1.0 - waited / (seconds * mix.clients)}

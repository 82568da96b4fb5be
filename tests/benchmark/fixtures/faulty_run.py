#!/usr/bin/env python3
"""The rest of a run with one guarantee broken underneath.

    python3 tests/benchmark/fixtures/faulty_run.py <fault> <run.py's arguments>

wraps the harness's wire client (`wire.Client.request`, where every
answer and acknowledgement reaches the run) in one of the faults below
and hands the arguments to `benchmark/run.py`'s `main`. It is the control
of the numbers a writer brings (PERF.md section 2): the broken-path tests
rehearse it on the CPU, and the builder ran it on the chip at the cell's
own size.

    none              nothing is broken (the sound run, through the same door)
    ack_and_drop      every fifth write is acknowledged (204) and dropped:
                      a durability fault; rows.<table> and, the dropped
                      batch being one that is read back, stale_reads
    stale_snapshot    the read-after-acknowledge query is answered from a
                      snapshot taken before the batch it asks about was
                      written; every row is there in the end
    stale_lastpoint   every lastpoint request is answered with the answer
                      the first one got: a result kept past the writes
                      acknowledged since (the shape of a single flight or
                      a result cache that ignores the data version)
    altered_max       one value of every max() answer is altered
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import urllib.parse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import wire  # noqa: E402

REAL = wire.Client.request
WRITE = "/v1/influxdb/write"


def _sql(body: bytes) -> str:
    return urllib.parse.parse_qs(body.decode()).get("sql", [""])[0]


def none(self, method, path, body=b"", **kw):
    return REAL(self, method, path, body, **kw)


_writes = itertools.count(1)


def ack_and_drop(self, method, path, body=b"", **kw):
    if path.startswith(WRITE) and next(_writes) % 5 == 0:
        return 204, b""
    return REAL(self, method, path, body, **kw)


_before: dict = {}


def stale_snapshot(self, method, path, body=b"", **kw):
    if path.startswith(WRITE) and body:
        # the batch's own tick is its last line's: late rows come first
        ts = body.rstrip(b"\n").rsplit(b" ", 1)[1].decode()
        table = body.split(b",", 1)[0].decode()
        sql = f"SELECT count(*) FROM {table} WHERE ts = {ts}"
        _before[sql] = REAL(self, "POST", "/v1/sql",
                            urllib.parse.urlencode({"sql": sql}).encode())
    elif path == "/v1/sql" and _sql(body) in _before:
        return _before[_sql(body)]
    return REAL(self, method, path, body, **kw)


_kept: list = []


def stale_lastpoint(self, method, path, body=b"", **kw):
    status, data = REAL(self, method, path, body, **kw)
    if path == "/v1/sql" and "last_value(" in _sql(body) and status == 200 \
            and not _sql(body).startswith("EXPLAIN"):
        if not _kept:       # the first one (warm-up's) stays
            _kept.append((status, data))
        return _kept[0]
    return status, data


def altered_max(self, method, path, body=b"", **kw):
    status, data = REAL(self, method, path, body, **kw)
    if path == "/v1/sql" and "max(" in _sql(body) and status == 200 \
            and not _sql(body).startswith("EXPLAIN"):
        out = json.loads(data)
        rows = out["output"][-1]["records"]["rows"]
        if rows:
            rows[0][1] = rows[0][1] * 0.999
            data = json.dumps(out).encode()
    return status, data


FAULTS = {f.__name__: f for f in (none, ack_and_drop, stale_snapshot,
                                  stale_lastpoint, altered_max)}

if __name__ == "__main__":
    wire.Client.request = FAULTS[sys.argv[1]]
    sys.exit(bench_run.main(sys.argv[2:]))

"""The serving process and the wire client (copied from chip_smoke.py:
Server, Client, explain_analyze, wait_warm — the original stays where it
is; PERF.md lists it for a later PR).

The harness process never imports jax or greptimedb_tpu: a chip belongs
to one process, and that process is the server (started through
benchmark/harness/serve.py, which is the program's normal entry plus a
control thread for the profiler).
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.parse

from . import procs
from .common import BENCH_DIR, ROOT, BenchFailure, log


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    def __init__(self, platform: str, data_home: str):
        self.data_home = data_home
        self.log_path = os.path.join(data_home, "server.log")
        self.control_dir = os.path.join(data_home, "control")
        os.makedirs(self.control_dir, exist_ok=True)
        self.port = _free_port()
        env = dict(os.environ)
        # pin the child to the stated platform list: with a platform
        # named explicitly JAX raises when it cannot initialise it
        # instead of falling back to the CPU. "cpu" stays in the list
        # because the executor's host tier runs on the CPU backend of
        # the same process.
        env["JAX_PLATFORMS"] = platform if platform == "cpu" \
            else f"{platform},cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, env.get("PYTHONPATH")) if p)
        self._seq = 0
        self._log_f = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "harness", "serve.py"),
             "--parent", str(os.getpid()),
             "--control-dir", self.control_dir,
             "--data-home", os.path.join(data_home, "db"),
             "--http-addr", f"127.0.0.1:{self.port}"],
            cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=self._log_f,
            stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 30) -> str:
        try:
            with open(self.log_path, "rb") as f:
                lines = f.read().decode(errors="replace").splitlines()
        except OSError:
            return ""
        return "\n".join([ln for ln in lines if ln.strip()][-n:])

    def wait_ready(self, timeout_s: float = 240.0) -> None:
        t_end = time.monotonic() + timeout_s
        while time.monotonic() < t_end:
            if self.proc.poll() is not None:
                tail = self.log_tail()
                sys.stderr.write(tail + "\n")
                last = tail.splitlines()[-1] if tail else "(no output)"
                raise BenchFailure(
                    "the serving process exited at start-up (rc "
                    f"{self.proc.returncode}): {last}")
            try:
                conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                  timeout=2)
                conn.request("GET", "/health")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return
            except OSError:
                time.sleep(0.1)
        raise BenchFailure("the serving process did not answer /health "
                           f"within {timeout_s:.0f}s")

    def control(self, command: str, timeout_s: float = 120.0) -> dict:
        """Send one command to the launcher's control thread and wait
        for its answer (a JSON file named by the sequence number)."""
        self._seq += 1
        ack = os.path.join(self.control_dir, f"{self._seq}.json")
        self.proc.stdin.write(f"{self._seq} {command}\n".encode())
        self.proc.stdin.flush()
        t_end = time.monotonic() + timeout_s
        while time.monotonic() < t_end:
            if os.path.exists(ack):
                with open(ack) as f:
                    out = json.load(f)
                if out.get("error"):
                    raise BenchFailure(
                        f"control {command!r}: {out['error']}")
                return out
            if self.proc.poll() is not None:
                break
            time.sleep(0.02)
        raise BenchFailure(f"control {command!r}: no answer")

    def stop(self) -> None:
        """SIGTERM the server, SIGKILL it if it is still there after a
        minute, then end whatever it started and did not stop (encode
        workers), and wait for each."""
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        t0 = time.monotonic()
        started = procs.children_of(self.proc.pid)
        for sig in (signal.SIGTERM, signal.SIGKILL):
            if self.proc.poll() is None:
                self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout=60)
                break
            except subprocess.TimeoutExpired:
                pass
        procs.end(started)
        self._log_f.close()
        log(f"server stopped (rc {self.proc.returncode}) and waited for in "
            f"{time.monotonic() - t0:.1f}s")


class Client:
    """One keep-alive HTTP connection per thread."""

    def __init__(self, port: int):
        self.port = port
        self._tls = threading.local()

    def _conn(self) -> http.client.HTTPConnection:
        c = getattr(self._tls, "conn", None)
        # the server closes a keep-alive connection idle for
        # http.timeout_s (30 s): never reuse one that sat that long
        if c is not None and time.monotonic() - self._tls.used > 10.0:
            c.close()
            c = None
        if c is None:
            c = http.client.HTTPConnection("127.0.0.1", self.port,
                                           timeout=600)
            self._tls.conn = c
        self._tls.used = time.monotonic()
        return c

    def request(self, method: str, path: str, body: bytes = b"",
                ctype: str = "application/x-www-form-urlencoded"):
        # no blind retry: an unacknowledged write that did land would
        # break the row-count guarantee check
        c = self._conn()
        try:
            c.request(method, path, body=body,
                      headers={"Content-Type": ctype} if body else {})
            r = c.getresponse()
            data = r.read()
        except (http.client.HTTPException, OSError):
            c.close()
            self._tls.conn = None
            raise
        self._tls.used = time.monotonic()
        return r.status, data

    def sql(self, sql: str) -> dict:
        status, data = self.request(
            "POST", "/v1/sql", urllib.parse.urlencode({"sql": sql}).encode())
        try:
            out = json.loads(data)
        except ValueError:
            raise BenchFailure(f"/v1/sql HTTP {status}: {data[:300]!r}")
        if status != 200:
            raise BenchFailure(
                f"/v1/sql HTTP {status}: {out.get('error')!r} for {sql[:120]}")
        return out

    def rows(self, sql: str) -> list:
        return self.sql(sql)["output"][-1]["records"]["rows"]

    def get_json(self, path: str) -> dict:
        status, data = self.request("GET", path)
        if status != 200:
            raise BenchFailure(f"GET {path} HTTP {status}: {data[:300]!r}")
        return json.loads(data)

    def metrics(self) -> dict:
        """{(name, frozenset(labels)): value} of the classic exposition."""
        status, data = self.request("GET", "/metrics")
        if status != 200:
            raise BenchFailure(f"GET /metrics HTTP {status}")
        return parse_exposition(data.decode())


def parse_exposition(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, val = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = frozenset(
            tuple(kv.split("=", 1)) for kv in
            rest.rstrip("}").replace('"', "").split(",") if "=" in kv)
        try:
            out[(name, labels)] = float(val)
        except ValueError:
            pass
    return out


def metric_sum(m: dict, name: str, labels: dict | None = None) -> float:
    want = set((labels or {}).items())
    return sum(v for (n, ls), v in m.items() if n == name and want <= set(ls))


def wait_maintenance_idle(client: Client, timeout_s: float = 600.0) -> None:
    """The window starts with no maintenance job queued or running."""
    t_end = time.monotonic() + timeout_s
    while True:
        st = client.get_json("/v1/maintenance?limit=1000")
        busy = [j for j in st.get("jobs", [])
                if j.get("state") in ("queued", "running")]
        if not st.get("queue_depth") and not busy:
            return
        if time.monotonic() > t_end:
            raise BenchFailure(
                f"maintenance still busy after {timeout_s:.0f}s: "
                f"queue {st.get('queue_depth')}, {len(busy)} jobs")
        time.sleep(0.2)


class LineProtocol:
    """Bodies for `POST /v1/influxdb/write?precision=ms`, the door
    Telegraf and TSBS's loader write through: one line a row,
    `<table>,<tag>=<value>,... <field>=<x>,... <ts_ms>`, built
    column-wise by Arrow's C kernels (copied from chip_smoke.py
    `_lp_body`). Floats print in their shortest round-trip form, so the
    server parses back exactly the float64 the reference holds."""

    PATH = "/v1/influxdb/write?precision=ms"
    CTYPE = "text/plain"

    def __init__(self, table: str, series_tags: dict):
        import pyarrow as pa

        def esc(v) -> str:
            return re.sub(r"([,= ])", r"\\\1", str(v))

        n = len(next(iter(series_tags.values())))
        self._prefix = pa.array([
            table + "".join(f",{t}={esc(vs[i])}"
                            for t, vs in series_tags.items())
            for i in range(n)])

    def body(self, series, ts_ms, fields: dict) -> bytes:
        """Rows k = 0..n-1: series[k] (its index in `series_tags`),
        ts_ms[k], {field: values[k]}."""
        import numpy as np
        import pyarrow as pa
        import pyarrow.compute as pc

        parts = [self._prefix.take(pa.array(np.asarray(series, np.int64)))]
        for i, (key, arr) in enumerate(fields.items()):
            parts.append(pa.scalar((" " if i == 0 else ",") + key + "="))
            parts.append(pc.cast(pa.array(np.asarray(arr, np.float64)),
                                 pa.string()))
        parts.append(pa.scalar(" "))
        parts.append(pc.cast(pa.array(np.asarray(ts_ms, np.int64)),
                             pa.string()))
        parts.append(pa.scalar("\n"))
        lines = pc.binary_join_element_wise(*parts, "")
        n = len(lines)
        if n == 0:
            return b""
        end = int(np.frombuffer(lines.buffers()[1], dtype=np.int32,
                                count=n + 1)[n])
        return lines.buffers()[2].slice(0, end).to_pybytes()


def count_rows(client: Client, table: str) -> int:
    return int(client.rows(f"SELECT count(*) FROM {table}")[0][0])


def wait_warm(client: Client, timeout_s: float = 600.0) -> dict:
    """Wait until no hedged device warm-up is still compiling."""
    t_end = time.monotonic() + timeout_s
    while True:
        st = client.get_json("/v1/device")
        if st["warmup"]["warming"] == 0:
            return st
        if time.monotonic() > t_end:
            raise BenchFailure("device warm-up still running after "
                               f"{timeout_s:.0f}s")
        time.sleep(0.1)


def explain_analyze(client: Client, sql: str) -> dict:
    rec = {"path": None, "tier": None, "total_ms": None}
    for (line,) in client.rows("EXPLAIN ANALYZE " + sql):
        s = line.strip()
        if s.startswith("execution path:"):
            rec["path"] = s.split(":", 1)[1].strip()
        elif s.startswith("execution tier:"):
            rec["tier"] = s.split(":", 1)[1].strip()
        elif s.startswith("ANALYZE trace="):
            for tok in s.split():
                if tok.startswith("total="):
                    rec["total_ms"] = float(tok[6:])
    return rec

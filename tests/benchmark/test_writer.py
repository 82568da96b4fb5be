"""A writer inside the window (ISSUE 36): what it sends is a function of
the seed, every row goes once and none before the loaded span's end, it
is paced and never bursts, a `fresh` template is held to the request's
own front — and the broken-path runs: a rehearsal of
`tsbs-read-under-ingest` on the CPU, sound, and with each guarantee
broken underneath (`fixtures/faulty_run.py`), which must come out not
correct by the number that guards it.

The cell is not in BENCHMARK.json yet (PERF.md section 7, first: the
fast lane's single flight fails its `lastpoint` at size): the rehearsals
run in a scratch checkout whose manifest has gained
`fixtures/read_under_ingest.manifest.json`, which is what the manifest
gains when the cell joins — entries, no file of the benchmark.

No jax import and no topology call at module import time.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import traffic, wire  # noqa: E402
from benchmark.harness.common import (  # noqa: E402
    BENCH_DIR, BenchFailure, cell_metrics, load_json, load_module,
    make_dataset, manifest)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
MAN = manifest()
CELL = "tsbs-read-under-ingest"
SCALE = {"hosts": 50, "hours": 1, "step_s": 10}
SPEC = {"route": "influxdb", "ticks_per_s": 200, "late_share": 0.1,
        "late_span_s": 100, "check_every": 5}


def tsbs(seed=36, scale=None):
    return make_dataset({"dataset": "tsbs_cpu"}, seed, scale or SCALE)


class Sink:
    """A server that takes what it is sent: rows by (ts, host), each
    with the batch it came in; count(*) WHERE ts = x reads them."""

    def __init__(self, seconds_a_request: float = 0.0):
        self.rows: dict = {}
        self.bodies: list = []
        self.times: list = []
        self.in_flight = self.most_in_flight = 0
        self.sleep = seconds_a_request
        self._lock = threading.Lock()

    def request(self, method, path, body=b"", ctype=None):
        with self._lock:
            self.in_flight += 1
            self.most_in_flight = max(self.most_in_flight, self.in_flight)
        self.times.append(time.monotonic())
        time.sleep(self.sleep)
        assert method == "POST" and path == wire.LineProtocol.PATH
        assert ctype == "text/plain"
        for line in body.decode().splitlines():
            head, fields, ts = line.split(" ")
            host = dict(kv.split("=") for kv in head.split(",")[1:])[
                "hostname"]
            key = (int(ts), host)
            assert key not in self.rows, f"{key} sent twice"
            self.rows[key] = (len(self.bodies), {
                k: float(v) for k, v in
                (kv.split("=") for kv in fields.split(","))})
        self.bodies.append(body)
        with self._lock:
            self.in_flight -= 1
        return 204, b""

    def rows_at(self, sql: str) -> list:
        ts = int(sql.rsplit("=", 1)[1])
        return [[sum(1 for (t, _h) in self.rows if t == ts)]]


class SinkClient:
    """The part of wire.Client a Writer uses, over a Sink."""

    def __init__(self, sink: Sink):
        self.sink = sink

    def request(self, *a, **kw):
        return self.sink.request(*a, **kw)

    def rows(self, sql: str) -> list:
        return self.sink.rows_at(sql)


def run_writer(spec=None, seconds=0.25, ds=None, sink=None, seed=36):
    ds = ds or tsbs()
    sink = sink or Sink()
    w = traffic.Writer({**SPEC, **(spec or {})}, ds, seed)
    w.t0 = time.monotonic()
    w.run(SinkClient(sink), w.t0, seconds)
    return w, sink, ds


# ---- the data ----------------------------------------------------------------


def test_a_tick_is_a_function_of_seed_and_index():
    ds = tsbs()
    ts, fields = ds.tick(3)
    assert ts == ds.t_end_ms + 3 * ds.step_ms
    assert list(fields) == list(ds.fields)
    again = tsbs().tick(3)[1]
    for f, v in fields.items():
        assert v.shape == (ds.hosts,) and v.dtype == np.float64
        assert (0.0 <= v).all() and (v < 100.0).all()
        np.testing.assert_array_equal(v, again[f])
    assert not np.array_equal(fields["usage_user"],
                              ds.tick(4)[1]["usage_user"])
    assert not np.array_equal(fields["usage_user"],
                              tsbs(seed=37).tick(3)[1]["usage_user"])
    # no tick lies inside the loaded span
    assert ds.tick(0)[0] == ds.t_end_ms > ds.t0_ms + (ds.points - 1) \
        * ds.step_ms


def test_line_protocol_is_parsed_back_to_the_same_float64s():
    """Through the program's own parser: what the server stores is what
    the reference holds, bit for bit."""
    from greptimedb_tpu.servers.influx import parse_line_protocol

    ds = tsbs()
    ts, fields = ds.tick(0)
    fields["usage_user"][:3] = [3.0, 1e-7, 99.99999999999999]
    lp = wire.LineProtocol(ds.table, ds.series_tags())
    idx = np.arange(ds.hosts)
    points = parse_line_protocol(
        lp.body(idx, np.full(ds.hosts, ts), fields).decode())
    assert len(points) == ds.hosts
    for h, p in enumerate(points):
        assert p.measurement == "cpu" and p.ts == ts
        assert dict(p.tags) == {t: v[h] for t, v in ds.series_tags().items()}
        assert [k for k, _v in p.fields] == list(fields)
        for k, v in p.fields:
            assert isinstance(v, float) and v == fields[k][h]
    assert lp.body([], [], {f: [] for f in fields}) == b""


# ---- what is sent, and when --------------------------------------------------


def test_every_row_is_sent_once_with_its_tick_or_at_most_the_span_later():
    w, sink, ds = run_writer()
    ticks = [b.tick for b in w.batches if b.tick is not None]
    assert ticks == list(range(len(ticks))) and len(ticks) >= 10
    assert w.batches[-1].tick is None       # the closing batch
    # every series' row of every tick sent, once (the sink refuses twice)
    assert set(sink.rows) == {(ds.tick(i)[0], f"host_{h}")
                              for i in ticks for h in range(ds.hosts)}
    assert w.acked_rows == len(ticks) * ds.hosts == len(sink.rows)
    span = SPEC["late_span_s"] * 1000
    late = 0
    for (ts, host), (batch, values) in sink.rows.items():
        i = (ts - ds.t_end_ms) // ds.step_ms
        b = w.batches[batch]
        assert ts >= ds.t_end_ms
        if b.tick is None:
            assert batch == len(w.batches) - 1
        else:
            # on time, or with a later tick's batch inside the span
            assert 0 <= ds.tick(b.tick)[0] - ts <= span
        late += b.tick != i
        # the seeded values, exactly
        want = ds.tick(i)[1]
        assert values == {f: want[f][int(host[5:])] for f in want}
    assert 0.04 < late / len(sink.rows) < 0.2           # late_share 0.1
    assert sum(b.rows - b.on_time for b in w.batches) == late
    # the same seed sends the same batches; another seed other late rows
    again = run_writer()[1]
    n = min(len(sink.bodies), len(again.bodies)) - 1
    assert sink.bodies[:n] == again.bodies[:n]
    other = run_writer(seed=37)[1]
    assert sink.bodies[:n] != other.bodies[:n]


def test_no_late_rows_where_the_mix_asks_for_none():
    w, sink, ds = run_writer({"late_share": 0.0})
    assert all(b.rows == b.on_time == ds.hosts for b in w.batches)
    assert all(b.tick is not None for b in w.batches)


def test_the_writer_is_paced_and_a_slow_server_is_not_burst_at():
    # a server that keeps up: tick k is not sent before it is due
    w, sink, _ = run_writer({"ticks_per_s": 40}, seconds=0.5)
    n = sum(b.tick is not None for b in w.batches)
    assert 12 <= n <= 20
    for k, b in enumerate(w.batches[:n]):
        assert b.t_send - w.t0 >= k / 40
    st = w.stats(w.t0, 0.5)
    assert st["ticks_per_s_achieved"] == pytest.approx(40, rel=0.4)
    assert st["rows_per_s_achieved"] == pytest.approx(
        st["ticks_per_s_achieved"] * 50, rel=0.15)
    # a server that takes 50 ms a batch, asked for 100 a second: one in
    # flight, sent at once, and the rate achieved is what it reports
    w, sink, _ = run_writer({"ticks_per_s": 100}, seconds=0.5,
                            sink=Sink(0.05))
    assert sink.most_in_flight == 1
    st = w.stats(w.t0, 0.5)
    assert 4 <= st["ticks_per_s_achieved"] <= 20
    assert st["ticks_per_s"] == 100


def test_every_fifth_acknowledged_tick_is_read_back_at_once():
    w, sink, ds = run_writer()
    acked = [b for b in w.batches if b.ok and b.tick is not None]
    assert [c.tick for c in w.checks] == [b.tick for b in acked[4::5]]
    for tick, want, read, ms, error in w.checks:
        assert error is None and ms >= 0
        assert want == w.batches[tick].on_time <= read <= ds.hosts
    assert w.stale() == []

    class Stale(SinkClient):    # reads a snapshot one batch too old
        def rows(self, sql):
            return [[0]]

    w = traffic.Writer(SPEC, ds, 36)
    w.run(Stale(Sink()), time.monotonic(), 0.1)
    assert w.stale() == w.checks and len(w.checks) >= 1


def test_a_write_that_is_not_acknowledged_is_a_failed_batch():
    class Refuses(SinkClient):
        def request(self, *a, **kw):
            return 503, b"overloaded"

    w = traffic.Writer(SPEC, tsbs(), 36)
    w.run(Refuses(Sink()), time.monotonic(), 0.05)
    st = w.stats(time.monotonic(), 1.0)
    assert st["batches_failed"] == st["batches"] >= 1
    assert w.acked_rows == 0 and not w.checks
    assert "write HTTP 503" in w.batches[0].error


def test_a_dataset_without_ticks_cannot_be_written_to():
    ds = make_dataset({"dataset": "prom_counter"}, 1, {
        "instances": 2, "cpus": 1, "modes": 8, "hours": 2, "step_s": 15})
    with pytest.raises(BenchFailure, match="offers no tick"):
        traffic.Writer(SPEC, ds, 1)
    with pytest.raises(BenchFailure, match="no write route"):
        traffic.Writer({**SPEC, "route": "carrier-pigeon"}, tsbs(), 1)


# ---- the front a fresh template is held to -----------------------------------


def lastpoint_rows(ds, ticks) -> list:
    """The answer whose host h holds the seeded row of tick ticks[h]."""
    out = []
    for h in range(ds.hosts):
        i = int(ticks[h])
        vals = ds.tick(i)[1] if i >= 0 else {f: v[i] for f, v in
                                             ds.fields.items()}
        out.append([f"host_{h}"] + [float(vals[f][h]) for f in ds.fields])
    return out


def test_lastpoint_under_a_writer_is_held_to_the_requests_own_front():
    w, sink, ds = run_writer({"ticks_per_s": 100}, seconds=0.3)
    t = load_module("templates", "tsbs_devops").make("lastpoint")
    assert t.fresh is True
    b5, b9 = w.batches[5], w.batches[9]
    front = w.front(b5.t_ack + 1e-6, b9.t_send + 1e-6)
    assert (front.lower == b5.newest).all() and (front.upper == b9.newest
                                                 ).all()
    assert (front.lower <= front.upper).all() and front.upper.max() == 9
    # a host whose row of tick 5 was withheld is held to an older one
    withheld = w.rides(5) > 5
    assert withheld.any() and (front.lower[withheld] < 5).all()
    assert (front.lower[~withheld] == 5).all()

    def compared(ticks):
        return t.compare(lastpoint_rows(ds, ticks), {}, ds, "float64",
                         front=front)

    assert compared(front.lower) == 0           # the oldest it may read
    assert compared(front.upper) == 0           # the newest
    between = front.sent(7) & (front.lower <= 7) & (7 <= front.upper)
    assert between.any()
    assert compared(np.where(between, 7, front.upper)) == 0
    # one tick too old: every value of every host differs
    assert compared(front.lower - 1) == 10 * ds.hosts
    one = front.lower.copy()
    one[3] -= 1
    assert compared(one) == 10
    # a tick not sent yet
    assert compared(front.upper + 1) == 10 * ds.hosts
    # a withheld late row that had not been sent: not admissible
    late9 = np.flatnonzero(w.rides(9) > 9)
    assert len(late9)
    early = front.upper.copy()
    early[late9[0]] = 9
    assert compared(early) == 10
    # float32: the reference is rounded as the compute dtype rounds
    rows32 = [[r[0]] + [float(np.float32(v)) for v in r[1:]]
              for r in lastpoint_rows(ds, front.upper)]
    assert t.compare(rows32, {}, ds, "float32", front=front) == 0
    assert t.compare(rows32, {}, ds, "float64", front=front) > 0
    # before any batch: the loaded table's newest point, and only it
    first = w.front(w.batches[0].t_send - 1.0, w.batches[0].t_send - 0.5)
    assert (first.lower == -1).all() and (first.upper == -1).all()
    assert t.compare(lastpoint_rows(ds, first.lower), {}, ds, "float64",
                     front=first) == 0
    # without a writer the comparison is the one it was
    assert t.compare(lastpoint_rows(ds, first.lower), {}, ds,
                     "float64") == 0


# ---- a whole run, rehearsed on the CPU: sound, and broken underneath ---------


def with_the_cell(man: dict) -> dict:
    """The manifest once the cell has joined: one `workloads` entry, its
    name in the lists it joins, its `per_layer` entries at the end."""
    with open(os.path.join(FIXTURES,
                           "read_under_ingest.manifest.json")) as f:
        gain = json.load(f)
    man = json.loads(json.dumps(man))
    man["workloads"].append(gain["workload"])
    for m in man["per_layer"]:
        if m["name"] in gain["joins"]:
            m["workloads"].append(CELL)
    assert {m["name"] for m in man["per_layer"]} >= set(gain["joins"])
    man["per_layer"] += gain["per_layer"]
    return man


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout whose manifest has the cell: the benchmark's files as
    they are, the program, and the fault driver where it looks for its
    root. ONE reader, not the configuration's four: until the fast
    lane's single flight is repaired, several readers coalesce
    `lastpoint` onto a flight whose snapshot predates an acknowledged
    write (PERF.md section 7, first) and a sound rehearsal comes out not
    correct by it one time in six (2 of 12 with four readers, 0 of 12
    with one; CPU runs, PR 36). One reader cannot coalesce."""
    root = tmp_path_factory.mktemp("with_the_cell")
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = root / "benchmark" / "configs" / "tsbs-cpu-only-4000.json"
    sizes = json.loads(conf.read_text())
    sizes["rehearsal"]["clients"] = 1
    conf.write_text(json.dumps(sizes, indent=1))
    fixtures = root / "tests" / "benchmark" / "fixtures"
    fixtures.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "faulty_run.py"), fixtures)
    os.symlink(os.path.join(ROOT, "greptimedb_tpu"), root / "greptimedb_tpu")
    man = with_the_cell(MAN)
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return str(root), man


def rehearse(root: str, fault: str, trace: int = 0, cell: str = CELL,
             seed: int = 36):
    """6 s, not the configuration's 3: on a machine six workers share a
    batch takes half a second, and five have to be acknowledged before
    the first is read back."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "benchmark", "fixtures",
                                      "faulty_run.py"), fault,
         "--workload", cell, "--seed", str(2**31 + seed), "--trace",
         str(trace), "--seconds", "6", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 3, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    records = {r["record"]: r for r in (
        json.loads(ln) for ln in p.stdout.splitlines()[:-1]
        if ln.startswith('{"record"'))}
    return out, records, p.stderr


def test_the_rehearsal_holds_every_acknowledged_write_to_its_guarantees(
        checkout):
    root, man = checkout
    out, records, err = rehearse(root, "none", trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-2:] == ["writer", "compared"]
    w = out["writer"]
    assert w["batches"] >= 5 and w["batches_failed"] == 0
    assert w["checks"] >= 1 and w["stale_reads"] == w["checks_failed"] == 0
    assert w["rows_acknowledged"] == 20 * (w["next_tick"] - w["first_tick"])
    c = out["compared"]
    assert c["stale_reads"] == {"value": 0.0, "limit": 0.0}
    # (a) count(*) == the loader's rows + the writer's, to the row
    both = 20 * 360 + w["rows_acknowledged"]
    assert c["rows.cpu"] == {"value": float(both), "limit": float(both)}
    assert records["checks"]["tables_after_window"] == {"cpu": both}
    assert records["setup"]["tables"] == {"cpu": 20 * 360}
    assert c["lastpoint"] == {"value": 0.0, "limit": 0.0}
    assert out["attempted"] > w["batches"] + w["checks"]
    # every per-layer metric the manifest lists for the cell is on the
    # line (flush_ms_per_flush is not listed: it reads nothing where no
    # flush ran, and none runs in a window at the cell's pace)
    listed = {m["name"] for m in cell_metrics(man, CELL, "per_layer")}
    assert listed == set(out["metrics"]) and len(listed) == 19 + 6
    assert load_module("readers", "prom_hist_delta").read(
        type("Ctx", (), {"m0": {}, "m1": {}, "requests": []}),
        load_json("metrics", "flush_ms_per_flush.json")["args"]) is None
    m = out["metrics"]
    assert m["ingest_rows_per_s"]["value"] > 0
    assert m["write_ack_ms_p50"]["value"] > 0
    assert m["ack_to_read_ms_p95"]["value"] > 0
    assert m["flushes_per_window"]["value"] == 0
    assert m["wal_fsync_ms_per_commit"]["value"] > 0
    assert m["write_stall_ms_per_window"]["value"] == 0
    (region, held), = records["window"]["memtable_bytes_at_close"].items()
    assert held > 0
    assert "compared stale_reads: 0 (limit 0)" in err


@pytest.mark.parametrize("fault, broken, may", [
    # a dropped tick also leaves lastpoint a tick too old for a request
    # sent before the next one is acknowledged
    ("ack_and_drop", {"rows.cpu", "stale_reads"}, {"lastpoint"}),
    ("stale_snapshot", {"stale_reads"}, set()),
    ("stale_lastpoint", {"lastpoint"}, set()),
    ("altered_max", {"groupby-orderby-limit", "cpu-max-all-8"}, set()),
])
def test_a_guarantee_broken_underneath_makes_correct_false(fault, broken,
                                                           may, checkout):
    """An acknowledged batch that is dropped fails the row count and the
    read-after-acknowledge check; a read answered from a snapshot older
    than an acknowledged batch fails the check alone; a lastpoint
    answer kept past an acknowledged write fails lastpoint; an altered
    value fails its template."""
    out, records, _err = rehearse(checkout[0], fault)
    assert out["correct"] is False and out["attempted"] > 0
    past = {k for k, c in out["compared"].items()
            if (c["value"] != c["limit"] if k.startswith("rows.")
                else c["value"] > c["limit"])}
    assert broken <= past <= broken | may
    w = out["writer"]
    if fault == "ack_and_drop":
        c = out["compared"]["rows.cpu"]
        assert c["limit"] == 20 * 360 + w["rows_acknowledged"]
        # every fifth batch was dropped, and it is the one read back
        assert c["limit"] - c["value"] >= 15       # of a tick's 20
        assert w["stale_reads"] == w["checks"] >= 1
        assert records["checks"]["stale"][0][2] == 0
    if fault == "stale_snapshot":
        assert w["stale_reads"] == w["checks"] >= 1
        assert out["compared"]["rows.cpu"]["value"] \
            == out["compared"]["rows.cpu"]["limit"]
    assert out["failed"] == 0       # every operation was answered


def test_a_mix_without_a_writer_sends_no_write_and_reports_none():
    out, records, _err = rehearse(ROOT, "none", cell="tsbs-scan-heavy")
    assert out["correct"] is True
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert "stale_reads" not in out["compared"]
    assert out["compared"]["rows.cpu"] == {"value": 7200.0, "limit": 7200.0}
    assert "writer" not in records["checks"]
    assert "memtable_bytes_at_close" not in records["window"]
    assert traffic.Mix("tsbs-scan-heavy", tsbs()).writer_spec is None


# ---- the cell's files --------------------------------------------------------


def test_what_the_manifest_gains_with_the_cell_keeps_the_contract():
    gain = with_the_cell(MAN)
    cell = gain["workloads"][-1]
    assert cell["name"] == cell["traffic"] == CELL and cell["chips"] == 1
    assert cell["config"] == "tsbs-cpu-only-4000" and len(cell["why"]) <= 200
    names = {w["name"] for w in gain["workloads"]}
    for m in gain["per_layer"]:
        assert set(m["workloads"]) <= names
        spec = load_json("metrics", m["name"] + ".json")
        assert spec["unit"] == m["unit"] and spec["moves"] == m["moves"]
    assert [m["name"] for m in gain["per_layer"][-6:]] == [
        "ingest_rows_per_s", "write_ack_ms_p50", "ack_to_read_ms_p95",
        "flushes_per_window", "wal_fsync_ms_per_commit",
        "write_stall_ms_per_window"]
    # until then no accepted cell writes, and none lists a writer's metric
    assert CELL not in {w["name"] for w in MAN["workloads"]}
    assert not any(m["layer"] == "Ingest" for m in MAN["per_layer"])


def test_the_cells_traffic_is_scan_heavys_readers_and_one_writer():
    mine = load_json("traffic", CELL + ".json")
    theirs = load_json("traffic", "tsbs-scan-heavy.json")
    for key in ("family", "loop", "clients", "mix"):
        assert mine[key] == theirs[key]
    w = mine["writer"]
    assert set(w) == {"route", "ticks_per_s", "late_share", "late_span_s",
                      "check_every"}
    assert w["route"] == "influxdb" and w["check_every"] == 5
    assert w["late_share"] == 0.02 and w["late_span_s"] == 600
    assert w["ticks_per_s"] > 0
    # the same requests for the same seed as the cell it is read beside
    ds = tsbs()
    a = traffic.Mix(CELL, ds).stream(7, 1)
    b = traffic.Mix("tsbs-scan-heavy", ds).stream(7, 1)
    for _ in range(16):
        (ea, pa, ca), (eb, pb, cb) = next(a), next(b)
        assert (ea.name, pa, ca) == (eb.name, pb, cb)


def test_every_existing_cell_sends_the_parents_requests_for_a_seed():
    """fixtures/parent_draws.json: the first draws of `Mix.stream` for
    the five accepted cells, recorded from the parent commit's tree
    (35a8ad8) at the configurations' rehearsal sizes."""
    with open(os.path.join(FIXTURES, "parent_draws.json")) as f:
        rec = json.load(f)
    assert set(rec["draws"]) == {
        "tsbs-scan-heavy", "prom-board", "tsbs-point-dash",
        "prom-fleet-board", "prom-latency-board"}
    for cell, want in rec["draws"].items():
        w = next(w for w in MAN["workloads"] if w["name"] == cell)
        conf = load_json("configs", w["config"] + ".json")
        ds = make_dataset(conf, rec["seed"], conf["rehearsal"]["scale"])
        mix = traffic.Mix(w["traffic"], ds, conf["rehearsal"].get("clients"))
        got = []
        for c in range(min(2, mix.clients)):
            s = mix.stream(rec["seed"], c)
            for _ in range(6):
                e, params, check = next(s)
                method, path, body = e.template.request(params, ds)
                got.append([c, e.name, params, bool(check), method, path,
                            body.decode() if isinstance(body, bytes)
                            else body])
        assert json.loads(json.dumps(got)) == want, cell

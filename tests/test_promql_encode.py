"""A `query_range` matrix answer is written from its columns.

`servers/encode.py` `matrix_body` goes from the read-back
[series, steps] array to the response's bytes in arrow's kernels; the
object form it replaced — a Python float, a `repr` and a two-item list
a sample, then `json.dumps` over all of them — lives on here as the
reference: `json.loads` of the bytes has the same keys, series, label
sets and step times, and every value string parses to the bit-identical
float64 (its spelling may be arrow's: `3` for `3.0`). The series' heads
are kept beside the label sets they derive from, the counter says which
encoder wrote a response, and the request's stage tree holds one
`encode` segment.
"""

import dataclasses
import json
import struct
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.promql.engine import PromqlEngine
from greptimedb_tpu.promql.loaded import LabelSets
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.servers import HttpServer, encode
from greptimedb_tpu.servers.http import _matrix_body, _values_json
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils import tracing
from greptimedb_tpu.utils.metrics import PROMQL_ENCODED_RESPONSES


def object_form(times, vals, labels, metric=None) -> dict:
    """The answer as the server built it before: Python objects, a
    value at a time, parsed back from `json.dumps`' text as a client
    would."""
    out = []
    for i, lab in enumerate(labels):
        m = dict(lab)
        if metric:
            m["__name__"] = metric
        series = _values_json(times, np.asarray(vals)[i])
        if series:
            out.append({"metric": m, "values": series})
    return json.loads(json.dumps({
        "status": "success",
        "data": {"resultType": "matrix", "result": out}}))


def bits(text: str) -> bytes:
    return struct.pack("<d", float(text))


def assert_same_answer(body: bytes, want: dict) -> None:
    got = json.loads(body)
    assert got.keys() == want.keys() and got["status"] == want["status"]
    assert got["data"].keys() == want["data"].keys()
    assert got["data"]["resultType"] == want["data"]["resultType"]
    a, b = got["data"]["result"], want["data"]["result"]
    assert [s["metric"] for s in a] == [s["metric"] for s in b]
    for sa, sb in zip(a, b):
        assert sa.keys() == sb.keys()
        assert [t for t, _ in sa["values"]] == [t for t, _ in sb["values"]]
        assert [int(float(t)) for t, _ in sa["values"]] \
            == [int(float(t)) for t, _ in sb["values"]]
        for (_, va), (_, vb) in zip(sa["values"], sb["values"]):
            assert isinstance(va, str)
            if vb in ("+Inf", "-Inf"):
                assert va == vb
            else:
                assert bits(va) == bits(vb), (va, vb)


def labels_of(n: int) -> list:
    return [{"instance": f"host-{i:04d}", "job": "node"} for i in range(n)]


def times_of(n: int) -> np.ndarray:
    return 1_700_000_000.0 + 15.0 * np.arange(n)


def _random(series, steps, nan=0.01, seed=43):
    rng = np.random.default_rng(seed)
    vals = rng.uniform(0.0, 100.0, (series, steps))
    vals[rng.random((series, steps)) < nan] = np.nan
    return vals


def _spread(lo: float, hi: float, n=64) -> np.ndarray:
    rng = np.random.default_rng(7)
    return np.exp(rng.uniform(np.log(lo), np.log(hi), (4, n // 4)))


def _bit_patterns() -> np.ndarray:
    rng = np.random.default_rng(11)
    v = rng.integers(0, 2 ** 64, 4096, dtype=np.uint64).view(np.float64)
    return v.reshape(64, 64)  # NaN patterns drop out on both sides


NAN = float("nan")
INF = float("inf")
TINY = 5e-324  # the smallest subnormal
HUGE = 1.7976931348623157e308

CASES = {
    # the panels of the three PromQL cells
    "shape-1x241": (_random(1, 241), None, "cpu"),
    "shape-8x241": (_random(8, 241), None, "cpu"),
    "shape-125x61": (_random(125, 61), None, None),
    "shape-1000x16": (_random(1000, 16), None, None),
    "shape-2000x31": (_random(2000, 31), None, "p99"),
    # the edges
    "no-nan": (_random(5, 9, nan=0.0), None, None),
    "nan-samples": (np.array([[1.5, NAN, 2.5], [NAN, NAN, 3.5],
                              [4.5, 5.5, NAN]]), None, "m"),
    "a-series-all-nan": (np.array([[1.0, 2.0], [NAN, NAN], [3.0, NAN]]),
                         None, "m"),
    "first-and-last-series-all-nan": (
        np.array([[NAN, NAN], [1.0, 2.0], [NAN, NAN]]), None, None),
    "all-nan-matrix": (np.full((3, 4), NAN), None, "m"),
    "infinities": (np.array([[INF, -INF, 1.0], [NAN, INF, -INF]]),
                   None, None),
    "negative-zero": (np.array([[-0.0, 0.0, -1.0]]), None, None),
    "subnormals-and-the-largest": (
        np.array([[TINY, -TINY, 2.2250738585072014e-308, 1e-310],
                  [HUGE, -HUGE, 1e308, 4.9e-324]]), None, None),
    "whole-numbers": (np.array([[3.0, 100.0, -7.0, 1e6, 123456789.0]]),
                      None, None),
    "between-1e-5-and-1e-4": (_spread(1e-5, 1e-4), None, None),
    "between-1e-7-and-1e-5": (_spread(1e-7, 1e-5), None, None),
    "between-1e15-and-1e17": (_spread(1e15, 1e17), None, None),
    "between-1e20-and-1e23": (_spread(1e20, 1e23), None, None),
    "powers-of-ten": (10.0 ** np.arange(-24.0, 24.0).reshape(4, 12),
                      None, None),
    "random-bit-patterns": (_bit_patterns(), None, None),
    "float32": (_random(6, 11).astype(np.float32), None, "m"),
    "float32-thirds": (np.array([[1 / 3, 0.1, 16777217.0]],
                                dtype=np.float32), None, None),
    "zero-series": (np.zeros((0, 5)), None, "m"),
    "one-step": (_random(7, 1, nan=0.3), None, None),
    "escaped-labels": (
        _random(4, 3, nan=0.0),
        [{"path": 'say "hi"', "job": "a\\b"}, {"path": "tab\there\n"},
         {"zone": "Zürich", "who": "名前"}, {}], None),
    "escaped-labels-and-name": (
        _random(2, 3, nan=0.0),
        [{"path": '"', "job": "\\"}, {"emoji": "\U0001f600"}],
        'odd"name\\'),
    "a-name-label-is-overwritten": (
        _random(1, 2, nan=0.0), [{"__name__": "old", "a": "b"}], "new"),
}


@pytest.mark.parametrize("case", CASES)
def test_the_bytes_parse_to_the_object_form(case):
    vals, labels, metric = CASES[case]
    labels = labels_of(len(vals)) if labels is None else labels
    times = times_of(vals.shape[1])
    body = encode.matrix_body(
        times, vals, encode.metric_fragments(labels, metric))
    assert isinstance(body, bytes)
    assert_same_answer(body, object_form(times, vals, labels, metric))


def test_an_answer_with_no_sample_is_an_empty_result():
    body = encode.matrix_body(times_of(2), np.full((2, 2), NAN),
                              encode.metric_fragments(labels_of(2)))
    assert body == (b'{"status":"success","data":'
                    b'{"resultType":"matrix","result":[]}}')


def test_a_step_time_is_spelled_once_a_step_as_repr_spells_it():
    times = np.array([1_700_000_000.0, 1_700_000_000.5, 1e-3])
    body = encode.matrix_body(times, np.ones((2, 3)),
                              encode.metric_fragments(labels_of(2), "m"))
    want = ('[1700000000.0,"1"],[1700000000.5,"1"],[0.001,"1"]]}').encode()
    assert body.count(want) == 2 and b" " not in body


# ---- the label fragments: kept beside the label sets they derive from


T0 = 3_000_000
STEP = 15
POINTS = 12
PODS = [f"pod{i:02d}" for i in range(6)]


@pytest.fixture
def qe(tmp_path):
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data")))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    qe.execute_one(
        "CREATE TABLE req (pod STRING, ts TIMESTAMP(3) NOT NULL, val DOUBLE, "
        "TIME INDEX (ts), PRIMARY KEY (pod)) WITH (append_mode = 'true')")
    rng = np.random.default_rng(43)
    rows = []
    for pod in PODS:
        v = np.cumsum(rng.integers(1, 50, POINTS)).astype(float)
        rows += [f"('{pod}', {(T0 + i * STEP) * 1000}, {float(x)})"
                 for i, x in enumerate(v)]
    qe.execute_one("INSERT INTO req (pod, ts, val) VALUES " + ", ".join(rows))
    try:
        yield qe
    finally:
        eng.close()


ARGS = (T0 + 4 * STEP, T0 + 9 * STEP, STEP)


def _built(monkeypatch) -> list:
    """Every call of `metric_fragments` from here on: its label sets."""
    calls, real = [], encode.metric_fragments

    def counting(labels, metric=None):
        calls.append(labels)
        return real(labels, metric)

    monkeypatch.setattr(encode, "metric_fragments", counting)
    return calls


def test_label_sets_that_know_their_origin_keep_their_fragments(
        qe, monkeypatch):
    calls = _built(monkeypatch)
    prom = PromqlEngine(qe)
    times, first = prom.eval_matrix("rate(req[60s])", *ARGS)
    assert isinstance(first.labels, LabelSets)
    body = _matrix_body(times, first)
    key = ("metric_json", first.labels.path, first.metric)
    kept = first.labels.root.derived[key]
    assert len(kept) == len(PODS) and len(calls) == 1
    # the next request finds them: the same object, nothing built
    times, again = prom.eval_matrix("rate(req[60s])", *ARGS)
    assert _matrix_body(times, again) == body
    assert len(calls) == 1
    assert again.labels.root.derived[key] is kept
    # another metric name over the same label sets is another entry
    named = dataclasses.replace(first, metric="req:rate")
    got = json.loads(_matrix_body(times, named))["data"]["result"]
    assert [s["metric"] for s in got] \
        == [{"pod": p, "__name__": "req:rate"} for p in PODS]
    assert len(calls) == 2
    assert first.labels.root.derived[key] is kept
    assert len([k for k in first.labels.root.derived
                if k[0] == "metric_json"]) == 2


def test_requests_that_arrive_together_share_one_build(qe, monkeypatch):
    """More threads than cores at a first touch: one builds the heads,
    the others wait for them, and every body is the same bytes."""
    calls = _built(monkeypatch)
    times, sm = PromqlEngine(qe).eval_matrix("rate(req[60s])", *ARGS)
    want = encode.matrix_body(times, np.asarray(sm.values),
                              encode.metric_fragments(list(sm.labels)))
    calls.clear()
    start, bodies = threading.Barrier(16), []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def ask():
            start.wait(timeout=30)
            for _ in range(5):
                bodies.append(_matrix_body(times, sm))

        threads = [threading.Thread(target=ask) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert len(bodies) == 80 and set(bodies) == {want}
    assert len(calls) == 1


def test_a_plain_lists_fragments_are_built_for_the_request(qe, monkeypatch):
    calls = _built(monkeypatch)
    times, sm = PromqlEngine(qe).eval_matrix("rate(req[60s])", *ARGS)
    plain = dataclasses.replace(sm, labels=list(sm.labels))
    assert _matrix_body(times, plain) == _matrix_body(times, plain)
    assert len(calls) == 2 and not sm.labels.root.derived
    assert json.loads(_matrix_body(times, plain)) \
        == json.loads(_matrix_body(times, sm))


def test_the_kept_fragments_are_not_the_requests_to_change(qe):
    """A series that drops out of one answer (all NaN there) is still in
    the next: the kept heads are taken from, never cut."""
    prom = PromqlEngine(qe)
    times, sm = prom.eval_matrix("rate(req[60s])", *ARGS)
    whole = _matrix_body(times, sm)
    holed = np.array(sm.values)
    holed[1, :] = np.nan
    cut = json.loads(_matrix_body(
        times, dataclasses.replace(sm, values=holed)))["data"]["result"]
    assert [s["metric"]["pod"] for s in cut] \
        == [p for p in PODS if p != PODS[1]]
    assert _matrix_body(times, sm) == whole


# ---- through the HTTP server: the counter and the stage tree


@pytest.fixture
def server(qe):
    srv = HttpServer(qe, port=0)
    port = srv.start()
    try:
        yield f"http://127.0.0.1:{port}/v1/prometheus/api/v1"
    finally:
        srv.stop()


def _get(url, **params):
    try:
        with urllib.request.urlopen(
                f"{url}?{urllib.parse.urlencode(params)}") as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _encoded() -> dict:
    return {p: PROMQL_ENCODED_RESPONSES.get(path=p)
            for p in ("columnar", "rows")}


RANGE = {"start": ARGS[0], "end": ARGS[1], "step": STEP}


COUNTED = [
    ("matrix", "query_range", {"query": "rate(req[60s])", **RANGE},
     "columnar", 200),
    ("aggregated-matrix", "query_range",
     {"query": "sum by (pod) (rate(req[60s]))", **RANGE}, "columnar", 200),
    ("empty-matrix", "query_range",
     {"query": 'rate(req{pod="nobody"}[60s])', **RANGE}, "columnar", 200),
    ("scalar-range", "query_range", {"query": "1 + 1", **RANGE},
     "rows", 200),
    ("instant-vector", "query", {"query": "req", "time": ARGS[1]},
     "rows", 200),
    ("instant-scalar", "query", {"query": "2 * 3", "time": ARGS[1]},
     "rows", 200),
    ("missing-query", "query_range", RANGE, "rows", 400),
    ("bad-range", "query_range", {"query": "req", "start": "x"},
     "rows", 400),
    ("failed-evaluation", "query_range", {"query": "rate(", **RANGE},
     "rows", 400),
]


@pytest.mark.parametrize("what, sub, params, path, status", COUNTED,
                         ids=[c[0] for c in COUNTED])
def test_the_counter_says_which_encoder_wrote_the_response(
        server, what, sub, params, path, status):
    before = _encoded()
    got, body, _ = _get(f"{server}/{sub}", **params)
    assert got == status, body
    other = "rows" if path == "columnar" else "columnar"
    now = _encoded()
    assert now[path] - before[path] == 1 and now[other] == before[other]
    out = json.loads(body)
    if status == 200:
        assert out["status"] == "success"
    if what == "scalar-range":
        # the row-wise encoder still spells a value as repr does
        assert out["data"]["result"][0]["values"][0][1] == "2.0"
    if what == "empty-matrix":
        assert out["data"] == {"resultType": "matrix", "result": []}


def test_the_served_matrix_is_the_object_form(server, qe):
    _, body, headers = _get(f"{server}/query_range",
                            query="rate(req[60s])", **RANGE)
    assert headers["Content-Type"] == "application/json"
    times, sm = PromqlEngine(qe).eval_matrix("rate(req[60s])", *ARGS)
    want = object_form(times, np.asarray(sm.values), sm.labels, sm.metric)
    assert len(want["data"]["result"]) == len(PODS)
    assert_same_answer(body, want)


def test_the_stage_tree_holds_one_encode_segment_with_its_series(server):
    _, _, headers = _get(f"{server}/query_range",
                         query="rate(req[60s])", **RANGE)
    tid = headers["traceparent"].split("-")[1][16:]
    for _ in range(200):
        spans = tracing.spans_for(tid)
        if any(s.name.startswith("http:") for s in spans):
            break
        time.sleep(0.01)
    else:
        raise AssertionError("the request root never closed")
    names = [s.name for s in sorted(spans, key=lambda s: s.started_at)]
    assert names.count("encode") == 1 and names.count("send") == 1
    assert "readback" in names
    assert names.index("readback") < names.index("encode") \
        < names.index("send")
    enc = next(s for s in spans if s.name == "encode")
    assert enc.attrs["series"] == len(PODS)
    send = next(s for s in spans if s.name == "send")
    assert send.attrs["bytes"] > 0

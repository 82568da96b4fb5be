"""An aggregation's group index is made once per (label sets, grouping)
and stays on the device (ISSUE 39): kept beside the loaded series the
input's label sets derive from, shared by every operator over one
grouping, built by one thread at a first touch, dropped with the data
version — and nothing downstream alters what is kept.
"""

import copy
import dataclasses
import sys
import threading
import time

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.promql import engine as promql_engine
from greptimedb_tpu.promql.engine import PromqlEngine, SeriesMatrix
from greptimedb_tpu.promql.loaded import LabelSets, derive
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.servers.http import _matrix_body
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils import tracing
from greptimedb_tpu.utils.metrics import (
    DEVICE_TRANSFER_BYTES,
    PROMQL_GROUP_INDEXES,
    PROMQL_HISTOGRAM_FOLDS,
)

T0 = 3_000_000  # epoch seconds of the first sample
STEP = 15
POINTS = 8
PODS = [f"pod{i:02d}" for i in range(16)]
HANDLERS = ("/a", "/b")
LES = ("0.1", "1", "+Inf")
S = len(PODS) * len(HANDLERS) * len(LES)
RATE = "rate(req[60s])"


class _Db:
    def __init__(self, path):
        self.eng = RegionEngine(EngineConfig(data_dir=str(path)))
        self.qe = QueryEngine(Catalog(MemoryKv()), self.eng)
        self.qe.execute_one(
            "CREATE TABLE req (pod STRING, handler STRING, le STRING, "
            "ts TIMESTAMP(3) NOT NULL, val DOUBLE, TIME INDEX (ts), "
            "PRIMARY KEY (pod, handler, le)) WITH (append_mode = 'true')")
        rng = np.random.default_rng(39)
        self.write([(pod, h, le) for pod in PODS for h in HANDLERS
                    for le in LES], rng)
        self.prom = PromqlEngine(self.qe)
        self.args = (T0 + 4 * STEP, T0 + 7 * STEP, STEP)

    def write(self, series: list, rng) -> None:
        rows = []
        for pod, h, le in series:
            v = np.cumsum(rng.integers(1, 50, POINTS)).astype(float) \
                * (LES.index(le) + 1)
            rows += [f"('{pod}', '{h}', '{le}', "
                     f"{(T0 + i * STEP) * 1000}, {float(x)})"
                     for i, x in enumerate(v)]
        self.qe.execute_one(
            "INSERT INTO req (pod, handler, le, ts, val) VALUES "
            + ", ".join(rows))

    def eval(self, q: str, prom=None) -> SeriesMatrix:
        return (prom or self.prom).eval_matrix(q, *self.args)[1]

    def root(self) -> LabelSets:
        """The resident selector's label sets."""
        labels = self.eval(RATE).labels
        assert isinstance(labels, LabelSets) and labels.root is labels
        return labels

    def entries(self) -> set:
        """The group indexes kept beside the selector: (by, without)."""
        return {k[2:] for k in self.root().derived if k[0] == "group_index"}


@pytest.fixture
def db(tmp_path):
    d = _Db(tmp_path / "data")
    try:
        yield d
    finally:
        d.eng.close()


class _Rootless(PromqlEngine):
    """The same engine over label sets of no known origin: what a call
    returns is a plain list, as a subquery's labels are."""

    def _eval_call(self, call, p, ctx):
        v = super()._eval_call(call, p, ctx)
        if isinstance(v, SeriesMatrix):
            v = dataclasses.replace(v, labels=list(v.labels))
        return v

    @staticmethod
    def _group_index(labels, agg):
        # an aggregation fused with its range function never passes
        # through _eval_call: its input's label sets lose their origin
        # here
        return PromqlEngine._group_index(list(labels), agg)


def _counts() -> dict:
    return {how: PROMQL_GROUP_INDEXES.get(index=how)
            for how in ("hit", "build")}


def _moved(before: dict) -> tuple:
    now = _counts()
    return (now["build"] - before["build"], now["hit"] - before["hit"])


def _h2d() -> float:
    return DEVICE_TRANSFER_BYTES.total(direction="h2d")


# ---- (1) a second evaluation finds the index, uploads none, answers
# the same bits


@pytest.mark.parametrize("q", [
    f"sum by (handler) ({RATE})",
    f"avg by (le, handler) ({RATE})",
    f"max without (pod) ({RATE})",
    f"count({RATE})",
    f"stddev by (pod) ({RATE})",
])
def test_a_second_evaluation_hits_uploads_nothing_and_answers_the_same(db, q):
    db.eval(RATE)  # the selector is resident; no aggregation yet
    n0, b0 = _counts(), _h2d()
    first = db.eval(q)
    b1 = _h2d()
    assert _moved(n0) == (1, 0)
    assert b1 - b0 >= 4 * S  # the index went to the device, once
    n1 = _counts()
    again = db.eval(q)
    assert _moved(n1) == (0, 1)
    assert _h2d() - b1 < 4 * S  # the step times and no index
    assert again.labels is first.labels  # the kept label sets themselves
    np.testing.assert_array_equal(np.asarray(first.values),
                                  np.asarray(again.values))
    # label sets of no known origin: built for the request, as before
    n2 = _counts()
    plain = db.eval(q, _Rootless(db.qe))
    assert _moved(n2) == (1, 0)
    assert not isinstance(plain.labels, LabelSets)
    assert plain.labels == list(first.labels)
    np.testing.assert_array_equal(np.asarray(first.values),
                                  np.asarray(plain.values))
    n3 = _counts()
    db.eval(q, _Rootless(db.qe))
    assert _moved(n3) == (1, 0)  # and again for the next


# ---- (2) one entry per grouping, shared by every operator over it


def test_each_grouping_keeps_its_own_entry(db):
    queries = {
        f"sum by (handler) ({RATE})": (("handler",), ()),
        f"sum by (le, handler) ({RATE})": (("le", "handler"), ()),
        f"sum without (pod) ({RATE})": ((), ("pod",)),
        f"sum({RATE})": ((), ()),
    }
    n0 = _counts()
    answers = {q: db.eval(q) for q in queries}
    assert _moved(n0) == (len(queries), 0)
    assert db.entries() == set(queries.values())
    assert [a.num_series for a in answers.values()] == [2, 6, 6, 1]
    # `by ()` and no grouping are one grouping
    n1 = _counts()
    db.eval(f"sum by () ({RATE})")
    assert _moved(n1) == (0, 1) and len(db.entries()) == len(queries)
    # a nested aggregate's index is named by the whole path
    inner = f"sum by (le, handler) ({RATE})"
    n2 = _counts()
    nested = db.eval(f"sum by (handler) ({inner})")
    assert _moved(n2) == (1, 1)  # the outer builds, the inner hits
    assert nested.labels.path == (("group", ("le", "handler"), ()),
                                  ("group", ("handler",), ()))
    np.testing.assert_allclose(
        np.asarray(nested.values),
        np.asarray(answers[f"sum by (handler) ({RATE})"].values),
        rtol=1e-12)


def test_every_operator_over_one_grouping_shares_the_entry(db):
    by = "by (handler)"
    n0 = _counts()
    out = {op: db.eval(q) for op, q in {
        "sum": f"sum {by} ({RATE})",
        "avg": f"avg {by} ({RATE})",
        "count": f"count {by} ({RATE})",
        "topk": f"topk {by} (2, {RATE})",
        "quantile": f"quantile {by} (0.5, {RATE})",
        "count_values": f'count_values {by} ("v", {RATE})',
    }.items()}
    assert _moved(n0) == (1, 5)
    assert db.entries() == {(("handler",), ())}
    # the host index serves the operators that read it there
    plain = _Rootless(db.qe)
    for op, q in (("topk", f"topk {by} (2, {RATE})"),
                  ("quantile", f"quantile {by} (0.5, {RATE})"),
                  ("count_values", f'count_values {by} ("v", {RATE})')):
        want = db.eval(q, plain)
        assert list(out[op].labels) == list(want.labels)
        np.testing.assert_array_equal(np.asarray(out[op].values),
                                      np.asarray(want.values))
    np.testing.assert_array_equal(
        np.asarray(out["count"].values), float(S // 2))
    np.testing.assert_allclose(
        np.asarray(out["avg"].values),
        np.asarray(out["sum"].values) / (S // 2), rtol=1e-12)
    kept = next(v for k, v in db.root().derived.items()
                if k[0] == "group_index")
    assert kept.gidx.dtype == np.int32 and kept.gidx.shape == (S,)
    assert not kept.gidx.flags.writeable
    assert kept.G == 2 and kept.mask.shape == (S,)
    np.testing.assert_array_equal(np.asarray(kept.d_gidx), kept.gidx)


# ---- (3) a new data version is a new root


def test_a_write_that_adds_a_series_builds_again(db):
    q = f"count by (handler) ({RATE})"
    n0 = _counts()
    before = db.eval(q)
    db.eval(q)
    assert _moved(n0) == (1, 1)
    old_root = db.root()
    db.write([("pod99", "/c", "1")], np.random.default_rng(1))
    n1 = _counts()
    after = db.eval(q)
    assert _moved(n1) == (1, 0)
    assert db.root() is not old_root
    assert [lab["handler"] for lab in before.labels] == ["/a", "/b"]
    assert [lab["handler"] for lab in after.labels] == ["/a", "/b", "/c"]
    assert np.asarray(after.values)[2].tolist() == [1.0] * 4
    n2 = _counts()
    db.eval(q)
    assert _moved(n2) == (0, 1)


# ---- (4) a first touch builds once


@pytest.mark.parametrize("clients", [4, 24])
def test_threads_that_first_touch_one_key_run_one_build(
        db, monkeypatch, clients):
    q = f"sum by (pod) ({RATE})"
    want = np.asarray(db.eval(q, _Rootless(db.qe)).values)
    db.eval(RATE)
    builds = []
    real = promql_engine._build_group_index

    def slow(labels, by, without):
        builds.append(threading.get_ident())
        time.sleep(0.2)  # the others arrive while this one builds
        return real(labels, by, without)

    monkeypatch.setattr(promql_engine, "_build_group_index", slow)
    gate = threading.Barrier(clients)
    answers, errors = [], []

    def client():
        try:
            gate.wait(timeout=30)
            answers.append(db.eval(q, PromqlEngine(db.qe)))
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    n0 = _counts()
    threads = [threading.Thread(target=client) for _ in range(clients)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # a lost update would show
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(builds) == 1
    assert _moved(n0) == (1, clients - 1)
    assert len(answers) == clients
    assert len({id(a.labels) for a in answers}) == 1
    for a in answers:
        np.testing.assert_array_equal(np.asarray(a.values), want)


def test_a_build_that_fails_leaves_nothing_and_the_next_one_builds():
    labels = LabelSets([{"a": "1"}, {"a": "2"}])
    waiting = threading.Event()
    calls = []

    def build(labs, arg):
        calls.append(arg)
        if len(calls) == 1:
            waiting.wait(timeout=30)  # until a second thread waits on us
            raise RuntimeError("no index")
        return len(labs), arg

    results = []

    def second():
        results.append(derive(labels, "x", build, "k"))

    t = threading.Thread(target=second)
    first_error = []

    def first():
        try:
            derive(labels, "x", build, "k")
        except RuntimeError as e:
            first_error.append(e)

    f = threading.Thread(target=first)
    f.start()
    while not calls:
        time.sleep(0.001)
    t.start()
    time.sleep(0.05)
    waiting.set()
    f.join(timeout=30)
    t.join(timeout=30)
    assert len(first_error) == 1
    assert results == [((2, "k"), "build")] and calls == ["k", "k"]
    assert derive(labels, "x", build, "k") == ((2, "k"), "hit")
    assert derive(labels, "x", build, "other") == ((2, "other"), "build")
    assert derive(labels.step(("s",), [{}]), "x", build, "k") \
        == ((1, "k"), "build")
    assert derive(list(labels), "x", build, "k") == ((2, "k"), "build")
    assert derive(list(labels), "x", build, "k") == ((2, "k"), "build")
    assert set(labels.derived) == {("x", (), "k"), ("x", (), "other"),
                                   ("x", (("s",),), "k")}


# ---- (5) nothing kept is altered, and a step the path does not name
# hands on a plain list


def test_what_is_kept_is_not_altered_downstream(db):
    q = f"sum by (pod, handler) ({RATE})"
    first = db.eval(q)
    snapshot = copy.deepcopy(list(first.labels))
    values = np.asarray(first.values)
    between = [
        f'label_replace({q}, "pod", "x$1", "pod", "pod(.*)")',
        f'label_replace({q}, "handler", "", "pod", ".*")',
        f'label_join({q}, "pod", "-", "pod", "handler")',
        f"{q} * on (pod) group_left sum by (pod) ({RATE})",
        f"{q} / ignoring (handler) group_left sum by (pod) ({RATE})",
        f"{q} + {q}",
        f"{q} * 2",
        f"-{q}",
        f'count_values by (pod) ("handler", {q})',
        f"topk by (pod) (1, {q})",
        f"sort_desc({q})",
        f"{q} or {q}",
    ]
    for text in between:
        out = db.eval(text)
        assert out.num_series > 0, text
        # what the HTTP API and TQL make of it
        times = np.arange(*db.args[:2], STEP, dtype=float)
        _matrix_body(np.append(times, db.args[1]), out)
        db.prom.eval_range(text, *db.args)
        again = db.eval(q)
        assert again.labels is first.labels, text
        assert list(again.labels) == snapshot, text
        np.testing.assert_array_equal(np.asarray(again.values), values)
    replaced = db.eval(between[0])
    assert replaced.labels[0]["pod"] == "x00"
    assert not isinstance(replaced.labels, LabelSets)
    assert not isinstance(db.eval(between[2]).labels, LabelSets)
    assert not isinstance(db.eval(between[3]).labels, LabelSets)


def test_two_label_replace_calls_never_share_an_index(db):
    by_digit = ('sum by (dst) (label_replace(%s, "dst", "$1", "pod", '
                '"pod.(.)"))' % RATE)
    by_all = ('sum by (dst) (label_replace(%s, "dst", "all", "pod", '
              '".*"))' % RATE)
    db.eval(RATE)
    n0 = _counts()
    digit = db.eval(by_digit)
    everything = db.eval(by_all)
    assert _moved(n0) == (2, 0)
    assert digit.num_series == 10 and everything.num_series == 1
    assert [lab["dst"] for lab in everything.labels] == ["all"]
    n1 = _counts()
    assert db.eval(by_digit).num_series == 10
    assert db.eval(by_all).num_series == 1
    assert _moved(n1) == (2, 0)  # per request: no name, no entry
    assert db.entries() == set()
    np.testing.assert_allclose(
        np.asarray(everything.values)[0],
        np.asarray(digit.values).sum(axis=0), rtol=1e-12)


# ---- (6) histogram_quantile's fold index still finds its input


def test_histogram_quantile_over_a_kept_grouping_still_hits_its_fold(db):
    q = f"histogram_quantile(0.9, sum by (le, handler) ({RATE}))"

    def folds() -> tuple:
        return (PROMQL_HISTOGRAM_FOLDS.get(index="build"),
                PROMQL_HISTOGRAM_FOLDS.get(index="hit"))

    f0, n0 = folds(), _counts()
    first = db.eval(q)
    f1 = folds()
    assert (f1[0] - f0[0], f1[1] - f0[1]) == (1, 0)
    again = db.eval(q)
    f2 = folds()
    assert (f2[0] - f1[0], f2[1] - f1[1]) == (0, 1)
    assert _moved(n0) == (1, 1)
    assert first.num_series == 2
    np.testing.assert_array_equal(np.asarray(first.values),
                                  np.asarray(again.values))
    assert {k[0] for k in db.root().derived} \
        == {"group_index", "histogram_fold"}
    # another grouping's fold is another entry
    db.eval(q.replace("(le, handler)", "(le, pod)"))
    f3 = folds()
    assert (f3[0] - f2[0], f3[1] - f2[1]) == (1, 0)


# ---- the span says which


def test_the_group_labels_segment_says_where_the_index_came_from(db):
    db.eval(RATE)
    tid = tracing.set_trace()
    try:
        db.eval(f"sum by (handler) ({RATE})")
        db.eval(f"sum by (handler) ({RATE})")
        db.eval(f"sum by (handler) ({RATE})", _Rootless(db.qe))
        segs = [s for s in sorted(tracing.spans_for(tid),
                                  key=lambda s: s.started_at)
                if s.name == "assemble"
                and s.attrs.get("step") == "group_labels"]
    finally:
        tracing.restore_trace(None)
    assert all(s.attrs["series"] == S for s in segs)
    # a build's upload cuts its stage in two; the closing segment says
    assert [s.attrs.get("index") for s in segs] \
        == [None, "build", "hit", None, "build"]

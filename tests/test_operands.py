"""A predicate's literals and a time bucket's base are operands
(query/expr.py `split_operands`, query/physical.py `_aggregate`): per
predicate form, two literal sets split to one shape (or, for the forms
that stay static, to two), the shape with its operands evaluates on the
device row for row as the bound predicate does on the host, and N
literal sets compile once. Then the two places where that must not
leak: the partial-aggregate cache (its key keeps the literals: two
host sets never read each other's partials) and a time-bucket key (two
window starts share the executable and decode to their own buckets)."""

import numpy as np
import pytest

import jax.numpy as jnp

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema
from greptimedb_tpu.datatypes.types import DataType, SemanticType
from greptimedb_tpu.query import partial_cache as pc
from greptimedb_tpu.query import physical as ph
from greptimedb_tpu.query.engine import QueryContext, QueryEngine
from greptimedb_tpu.query.expr import (
    BindContext,
    Operand,
    bind_expr,
    eval_device,
    eval_host,
    split_operands,
)
from greptimedb_tpu.sql import ast
from greptimedb_tpu.sql.parser import parse_sql
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils.metrics import AGG_PROGRAM_EVENTS, XLA_COMPILES

CTX = QueryContext()
HOSTS = np.asarray([f"host_{i}" for i in range(32)], dtype=object)
SCHEMA = Schema([
    ColumnSchema("host", DataType.STRING, SemanticType.TAG),
    ColumnSchema("ts", DataType.TIMESTAMP_MILLISECOND,
                 SemanticType.TIMESTAMP),
    ColumnSchema("v", DataType.FLOAT64),
    ColumnSchema("n", DataType.INT64),
    ColumnSchema("s", DataType.STRING),
])
N = 1024


def _block(seed=7):
    rng = np.random.default_rng(seed)
    host = rng.integers(-1, len(HOSTS), N).astype(np.int32)  # -1: NULL tag
    v = rng.uniform(0, 100, N)
    v[rng.integers(0, N, 40)] = np.nan
    return {"host": host,
            "ts": np.sort(rng.integers(1_000_000, 2_000_000, N)),
            "v": v, "n": rng.integers(-50, 50, N)}


def _where(text):
    return parse_sql(f"SELECT * FROM t WHERE {text}")[0].where


def _in(hosts):
    return "(" + ", ".join(f"'host_{h}'" for h in hosts) + ")"


# (form, two literal sets as WHERE texts, shares one shape?)
FORMS = [
    ("tag_eq", ["host = 'host_3'", "host = 'host_17'"], True),
    ("tag_ne", ["host != 'host_3'", "host != 'host_9'"], True),
    ("tag_eq_unknown", ["host = 'host_3'", "host = 'nobody'"], True),
    ("in_1", [f"host IN {_in([4])}", f"host IN {_in([30])}"], True),
    ("in_2", [f"host IN {_in([4, 5])}", f"host IN {_in([0, 31])}"], True),
    ("in_5", [f"host IN {_in(range(5))}",
              f"host IN {_in(range(20, 25))}"], True),
    ("in_8", [f"host IN {_in(range(8))}", f"host IN {_in(range(9, 17))}"],
     True),
    ("in_5_shares_8s", [f"host IN {_in(range(5))}",
                        f"host IN {_in(range(9, 17))}"], True),
    ("in_9", [f"host IN {_in(range(9))}", f"host IN {_in(range(10, 19))}"],
     True),
    ("in_8_and_9_differ", [f"host IN {_in(range(8))}",
                           f"host IN {_in(range(9))}"], False),
    ("not_in", [f"host NOT IN {_in([1, 2, 3])}",
                f"host NOT IN {_in([7, 8, 30])}"], True),
    ("ts_range", ["ts >= 1200000 AND ts < 1500001",
                  "ts >= 1000007 AND ts < 1999999"], True),
    ("ts_flipped", ["1200000 <= ts", "1777777 <= ts"], True),
    ("ts_between", ["ts BETWEEN 1200000 AND 1500000",
                    "ts BETWEEN 1000001 AND 1000002"], True),
    ("field_float", ["v > 90.5", "v > 12.25"], True),
    ("field_int_literal", ["v <= 50", "v <= 7"], True),
    ("int_field", ["n < -3", "n < 40"], True),
    ("int_field_float_literal", ["n < 2.5", "n < -7.5"], True),
    ("tag_ordering", ["host < 'host_13'", "host < 'host_15'"], True),
    ("tag_ordering_widths_differ", ["host < 'host_13'", "host < 'host_31'"],
     False),
    ("panel", ["host IN ('host_1', 'host_2') AND ts >= 1100000 AND "
               "ts < 1400000 AND v > 10",
               "host IN ('host_30', 'host_8') AND ts >= 1500123 AND "
               "ts < 1800123 AND v > 95.5"], True),
    ("is_null", ["v IS NULL", "v IS NULL"], True),
    ("arithmetic", ["v + 1 > 50", "v + 2 > 50"], False),
    ("case", ["CASE WHEN v > 10 THEN 1 ELSE 0 END = 1",
              "CASE WHEN v > 20 THEN 1 ELSE 0 END = 1"], False),
    ("func", ["abs(v) > 10", "abs(v) > 20"], False),
    ("boolean", ["(v > 10) = true", "(v > 10) = false"], False),
]


@pytest.mark.parametrize("form, texts, shares", FORMS,
                         ids=[f[0] for f in FORMS])
def test_split(form, texts, shares):
    cols = _block()
    ctx = BindContext(SCHEMA, {"host": HOSTS})
    dev = {k: jnp.asarray(v) for k, v in cols.items()}
    tags = frozenset(ctx.tag_names)
    bound = [bind_expr(_where(t), ctx) for t in texts]
    splits = [split_operands(b, SCHEMA) for b in bound]
    # the shape of two literal sets: one, or (a form that stays static) two
    assert (splits[0][0] == splits[1][0]) == shares
    same_text = texts[0] == texts[1]
    for (shape, operands, static), b in zip(splits, bound):
        assert static == (not shares and "differ" not in form)
        assert "host_" not in repr(shape)
        if not static and not same_text:
            assert operands, "a literal left neither in shape nor operands"
        # the shape with its operands, on the device, is the bound
        # predicate on the host, row for row
        got = np.asarray(eval_device(shape, dev, tags, SCHEMA, operands))
        want = np.asarray(eval_host(b, cols, SCHEMA))
        assert got.dtype == bool and (got == want).all()
    # N literal sets compile once (a static form: once per set)
    n0 = XLA_COMPILES.total(fn="filter_block")
    events0 = {e: AGG_PROGRAM_EVENTS.get(event=e)
               for e in ("reuse", "new", "static_literal")}
    masks = [np.asarray(ph._aggregate(
        ph._filter_block, dev, jnp.asarray(N - 24), None, where=b,
        tag_names=tags, schema=SCHEMA)) for b in bound + bound[::-1]]
    for m, b in zip(masks, bound + bound[::-1]):
        want = np.asarray(eval_host(b, cols, SCHEMA))
        want[N - 24:] = False
        assert (m == want).all()
    distinct = len({repr(b) for b in bound})
    compiled = XLA_COMPILES.total(fn="filter_block") - n0
    assert compiled <= (1 if shares else distinct)
    events = {e: AGG_PROGRAM_EVENTS.get(event=e) - events0[e]
              for e in events0}
    if splits[0][2]:
        assert events == {"reuse": 0, "new": 0, "static_literal": 4}
    else:
        assert events["static_literal"] == 0
        assert events["new"] + events["reuse"] == 4
        assert events["new"] <= (1 if shares else 2)


def test_a_string_field_like_stays_in_the_shape():
    ctx = BindContext(SCHEMA, {"host": HOSTS})
    bound = [bind_expr(_where(f"s LIKE '{p}' AND host = 'host_1'"), ctx)
             for p in ("err%", "warn%")]
    (s0, o0, st0), (s1, o1, st1) = (split_operands(b, SCHEMA)
                                    for b in bound)
    assert s0 != s1 and st0 and st1
    assert "err%" in repr(s0) and "warn%" in repr(s1)
    # the tag comparison beside it is an operand all the same
    assert [int(o) for o in o0] == [1] and o0[0].dtype == np.int32


def test_operand_dtypes_and_padding():
    ctx = BindContext(SCHEMA, {"host": HOSTS})
    b = bind_expr(_where(
        "host IN ('host_1', 'host_2', 'host_3') AND ts >= 5 AND v > 1 "
        "AND n IN (4, 5, 6)"), ctx)
    shape, ops, static = split_operands(b, SCHEMA)
    assert not static
    assert [o.dtype for o in ops] == [np.int32, np.int64, np.float64,
                                      np.int64]
    # a tag list pads with the code no row holds, another with its first
    assert ops[0].tolist() == [1, 2, 3, -2] and ops[3].tolist() == [4, 5, 6, 4]
    found = []

    def walk(e):
        if isinstance(e, Operand):
            found.append((e.index, e.width))
        for f in getattr(e, "__dataclass_fields__", ()):
            v = getattr(e, f)
            for x in v if isinstance(v, tuple) else (v,):
                walk(x)

    walk(shape)
    assert sorted(found) == [(0, 4), (1, 0), (2, 0), (3, 4)]
    # an integer its column's dtype cannot hold stays a constant
    _s, o, st = split_operands(bind_expr(_where("n < 1e30"), ctx), SCHEMA)
    assert len(o) == 1 and o[0].dtype == np.float64 and not st
    _s, o, st = split_operands(
        ast.BinaryOp("<", ast.Column("n"), ast.Literal(1 << 70)), SCHEMA)
    assert o == () and st


# ---- through the engine -----------------------------------------------------


@pytest.fixture
def db(tmp_path):
    pc.global_cache().clear()
    ph._PARTIAL_DISABLED["flag"] = False
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                    maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    qe.execute_one(
        "CREATE TABLE cpu (ts TIMESTAMP(3) TIME INDEX, host STRING, "
        "v DOUBLE, PRIMARY KEY(host)) WITH (append_mode='true')", CTX)
    rid = qe.catalog.table("public", "cpu").region_ids[0]
    rows = []
    for f in range(3):
        part = [(f * 600_000 + i * 1000 + h, f"h{h}", float(f * 1000 + i + h))
                for i in range(600) for h in range(6)]
        qe.execute_one("INSERT INTO cpu VALUES " + ", ".join(
            f"({t}, '{h}', {v})" for t, h, v in part), CTX)
        eng.flush(rid)
        rows += part
    yield eng, qe, rows
    eng.close()
    pc.global_cache().clear()


def _panel(hosts, lo, hi, step_ms=60_000):
    return ("SELECT date_bin(INTERVAL '1 minute', ts) AS minute, max(v) "
            f"FROM cpu WHERE host IN {tuple(hosts)!r} AND ts >= {lo} AND "
            f"ts < {hi} GROUP BY minute ORDER BY minute")


def _panel_ref(rows, hosts, lo, hi, step_ms=60_000):
    out = {}
    for t, h, v in rows:
        if h in hosts and lo <= t < hi:
            b = t // step_ms * step_ms
            out[b] = max(out.get(b, -np.inf), v)
    return [[b, out[b]] for b in sorted(out)]


def _compiles():
    return sum(XLA_COMPILES.total(fn=fn) for fn in (
        "agg_block", "agg_scan", "agg_scan_prepared", "agg_scan_fused"))


@pytest.mark.parametrize("cache", ["on", "off"])
def test_two_literal_sets_answer_for_themselves(db, monkeypatch, cache):
    """The trap: the partial cache's key and the hedge's key were one
    tuple; only the hedge's lost its literals. With the cache on, two
    host sets over one window each equal their own reference, twice,
    and the part holds one entry per host set."""
    monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", cache)
    _eng, qe, rows = db
    sets = [("h0", "h1"), ("h4", "h5")]
    lo, hi = 100_123, 1_000_123
    n0 = _compiles()
    for _pass in range(2):
        for hosts in sets:
            got = qe.execute_one(_panel(hosts, lo, hi), CTX).rows()
            assert [[int(a), float(b)] for a, b in got] \
                == _panel_ref(rows, hosts, lo, hi)
    a, b = (_panel_ref(rows, s, lo, hi) for s in sets)
    assert a != b
    if cache == "on":
        assert qe.executor.last_path == "incremental"
        keys = pc.global_cache().part_keys()
        by_part = {}
        for k in keys:
            by_part.setdefault(k[1:3], set()).add(k[5])  # (region, file)
        assert by_part and all(len(fps) == 2 for fps in by_part.values())
        # the key of a cached partial still holds the bound literals
        assert all("Literal" in fp[1] for fps in by_part.values()
                   for fp in fps)
    # the second host set compiled nothing the first had not
    first = _compiles() - n0
    n1 = _compiles()
    got = qe.execute_one(_panel(("h2", "h3"), lo + 7, hi + 7), CTX).rows()
    assert [[int(a), float(b)] for a, b in got] \
        == _panel_ref(rows, ("h2", "h3"), lo + 7, hi + 7)
    assert _compiles() == n1, (first, _compiles() - n1)


def test_two_window_starts_share_an_executable_and_their_own_buckets(
        db, monkeypatch):
    """A bucket key's base is an operand: two ms-granular starts of the
    same length (the same `size`) run one executable, and each answer's
    bucket timestamps are its own."""
    monkeypatch.setenv("GREPTIMEDB_TPU_PARTIAL_CACHE", "off")
    _eng, qe, rows = db
    hosts = ("h1", "h2")
    # each inside one SST: the block layout is a static input too
    windows = [(60_001, 360_001), (720_777, 1_020_777), (1_260_999,
                                                          1_560_999)]
    qe.execute_one(_panel(hosts, 61, 300_061), CTX)  # the shape's first
    n0 = _compiles()
    new0 = AGG_PROGRAM_EVENTS.get(event="new")
    answers = []
    for lo, hi in windows:
        got = qe.execute_one(_panel(hosts, lo, hi), CTX).rows()
        got = [[int(a), float(b)] for a, b in got]
        assert got == _panel_ref(rows, hosts, lo, hi)
        assert got[0][0] == lo // 60_000 * 60_000
        answers.append(got)
    assert answers[0] != answers[1] != answers[2]
    assert _compiles() == n0
    assert AGG_PROGRAM_EVENTS.get(event="new") == new0

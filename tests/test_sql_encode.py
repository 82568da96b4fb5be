"""A `/v1/sql` answer's `"rows"` are written from its columns.

`servers/encode.py` `columnar_rows` goes from a result's arrays to the
response's bytes in arrow's kernels; the writer it replaced — a Python
object a value through `json_rows`, then one `json.dumps` over all of
them — stays for a result holding a column of neither class, and lives
on here as the reference: `json.loads` of the columnar body gives the
same document, value for value and type for type (a float the
bit-identical float64 and still a float, NULL / NaN / +-Inf `null`).
The counter says which writer wrote a result's rows, and the members of
one batched group share what was written.
"""

import decimal
import json
import struct
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.catalog import Catalog, MemoryKv
from greptimedb_tpu.datatypes.types import DataType
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.query.result import QueryResult
from greptimedb_tpu.servers import HttpServer
from greptimedb_tpu.servers.encode import (
    columnar_rows,
    encode_sql_payload,
    json_rows,
    schema_header_json,
)
from greptimedb_tpu.storage import RegionEngine
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.utils.metrics import SQL_ENCODED_ROWS


def per_value_payload(results, elapsed_ms: float) -> bytes:
    """The body as the server wrote it before: `json_rows`' Python
    objects, a value at a time, through one `json.dumps` a result."""
    out = []
    for r in results:
        if not r.is_query:
            out.append('{"affectedrows": %d}' % r.affected_rows)
        else:
            out.append('{"records": {"schema": %s, "rows": %s, '
                       '"total_rows": %d}}'
                       % (schema_header_json(r.names, r.dtypes),
                          json.dumps(json_rows(r)), r.num_rows))
    return ('{"code": 0, "output": [%s], "execution_time_ms": %s}'
            % (", ".join(out), json.dumps(elapsed_ms))).encode()


def assert_same(got, want, at="body") -> None:
    """Equal documents, and equal TYPES all the way down: 3.0 is not 3,
    -0.0 is not 0.0, and a float is its float64 bit for bit."""
    assert type(got) is type(want), (at, got, want)
    if isinstance(want, dict):
        assert list(got) == list(want), at
        for k in want:
            assert_same(got[k], want[k], f"{at}.{k}")
    elif isinstance(want, list):
        assert len(got) == len(want), at
        for i, (a, b) in enumerate(zip(got, want)):
            assert_same(a, b, f"{at}[{i}]")
    elif isinstance(want, float):
        assert struct.pack("<d", got) == struct.pack("<d", want), \
            (at, got, want)
    else:
        assert got == want, (at, got, want)


def result(*columns, names=None, dtypes=None) -> QueryResult:
    names = names or [f"c{i}" for i in range(len(columns))]
    return QueryResult(list(names), list(dtypes or [None] * len(names)),
                       [np.asarray(c) for c in columns])


def objects(*values) -> np.ndarray:
    a = np.empty(len(values), dtype=object)
    a[:] = list(values)
    return a


def rows_counted() -> dict:
    return {p: SQL_ENCODED_ROWS.get(path=p) for p in ("columnar", "values")}


FLOAT64_EDGES = [-0.0, 0.0, 3.0, -3.0, 1e300, -1e300, 5e-324, 1e16, 1e15,
                 1e-7, 123456789012345680.0, 123456789012345.0, 2.0 ** 53,
                 1e21, 1e22, 1.7976931348623157e308, 2.2250738585072014e-308,
                 0.1, 1 / 3, 100.0, 99.99999999999999, 1e-5, 0.0001,
                 float("nan"), float("inf"), float("-inf")]
STRINGS = ["", '"', "\\", '\\"', "\x00\x01\x1f\x7f", "\n\r\t\b\f", "/",
           "héllo wörld", "  ", "\U0001f600", "日本語",
           "host_1", "host_1", None, "null", "3.0", "[1, 2]"]


def _big(rows: int, floats: int) -> QueryResult:
    rng = np.random.default_rng(46)
    hosts = 4000
    vals = [rng.random(rows) * 100 for _ in range(floats)]
    vals[0][::7] = np.round(vals[0][::7])       # whole numbers among them
    vals[-1][::11] = np.nan
    return result(
        np.repeat(np.arange(rows // hosts, dtype=np.int64) * 3_600_000,
                  hosts),
        objects(*[f"host_{i % hosts}" for i in range(rows)]),
        *vals)


CASES = {
    "float64_edges": lambda: [result(np.array(FLOAT64_EDGES))],
    "float64_random_bits": lambda: [result(
        np.random.default_rng(1).integers(0, 2 ** 63, 5000, dtype=np.int64)
        .view(np.float64),
        10.0 ** np.random.default_rng(2).uniform(-30, 30, 5000))],
    "float64_whole_numbers": lambda: [result(
        np.arange(-50, 50, dtype=np.float64)), result(
        10.0 ** np.arange(0, 25), -(10.0 ** np.arange(0, 25)),
        2.0 ** np.arange(40, 65))],
    "float32_spells_the_float64_it_equals": lambda: [result(
        np.array([0.1, 3.0, 1e-7, 16777216.0, 3.4028235e38, np.nan,
                  -np.inf, -0.0], dtype=np.float32))],
    "float16": lambda: [result(np.array([0.1, 3.0, 65504.0, np.inf],
                                        dtype=np.float16))],
    "int64_uint64_extremes": lambda: [result(
        np.array([-2 ** 63, 2 ** 63 - 1, 0], dtype=np.int64),
        np.array([2 ** 64 - 1, 0, 2 ** 63], dtype=np.uint64))],
    "int8_int16_int32_uint8": lambda: [result(
        np.array([-128, 127], dtype=np.int8),
        np.array([-32768, 32767], dtype=np.int16),
        np.array([-2 ** 31, 2 ** 31 - 1], dtype=np.int32),
        np.array([0, 255], dtype=np.uint8))],
    "bool": lambda: [result(np.array([True, False, True]))],
    "strings": lambda: [result(objects(*STRINGS))],
    "strings_numpy_unicode": lambda: [result(np.array(["a", "", '"q"']))],
    "strings_all_none": lambda: [result(objects(None, None, None))],
    "strings_every_value_distinct": lambda: [result(
        objects(*[f"k\"{i}\\" for i in range(3000)]))],
    "empty_result": lambda: [result(np.array([], dtype=np.float64),
                                    objects())],
    "no_columns": lambda: [QueryResult([], [], [])],
    "one_row": lambda: [result([1.5], [7], objects("x"), [False])],
    "one_column": lambda: [result(np.arange(5))],
    "duplicate_output_names": lambda: [result(
        [1.0, 2.0], [3.0, 4.0], names=["x", "x"],
        dtypes=[DataType.FLOAT64, DataType.FLOAT64])],
    "typed_schema": lambda: [result(
        np.array([1, 2], dtype=np.int64), [0.5, np.nan], objects("a", None),
        names=["ts", "v", "host"],
        dtypes=[DataType.TIMESTAMP_MILLISECOND, DataType.FLOAT64,
                DataType.STRING])],
    "several_results_and_an_affectedrows": lambda: [
        result([1.0, 2.5]), QueryResult.of_affected(3),
        result(objects("a", None), [1, 2]), QueryResult.of_affected(0),
        result(np.array([], dtype=np.int64))],
    "twelve_mixed_columns": lambda: [_big(8000, 10)],
    "answer_48000_x_3": lambda: [_big(48000, 1)],
    "answer_48000_x_12": lambda: [_big(48000, 10)],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_columnar_body_parses_to_the_per_value_bodys_document(case):
    results = CASES[case]()
    n = sum(r.num_rows for r in results if r.is_query)
    before = rows_counted()
    body = encode_sql_payload(results, 12.345)
    after = rows_counted()
    assert after["columnar"] - before["columnar"] == n
    assert after["values"] == before["values"]
    # NaN / Inf never reach the text: a strict parser reads it
    got = json.loads(body, parse_constant=pytest.fail)
    assert_same(got, json.loads(per_value_payload(results, 12.345)))
    for r in results:
        if r.is_query:
            assert columnar_rows(r.columns) is not None


def test_the_envelope_is_spelled_as_json_dumps_spells_it():
    r = result(np.array([1, 2], dtype=np.int64), objects("a", None),
               np.array([True, False]), names=["n", "s", "b"])
    got = encode_sql_payload([QueryResult.of_affected(2), r], 0.5)
    # no float among the columns: the two writers agree to the byte
    assert got == per_value_payload([QueryResult.of_affected(2), r], 0.5)
    assert got == json.dumps(json.loads(got)).encode()
    assert encode_sql_payload([result(np.array([], dtype=np.int64))], 1.0) \
        .count(b'"rows": [], "total_rows": 0') == 1


def test_a_finite_float_always_carries_a_point_or_an_exponent():
    vals = np.concatenate([np.array(FLOAT64_EDGES),
                           np.arange(-1000, 1000, dtype=np.float64)])
    vals = vals[np.isfinite(vals)]
    pieces = columnar_rows([vals])
    cells = b"".join(pieces)[2:-2].split(b"], [")
    assert len(cells) == len(vals)
    for text, v in zip(cells, vals.tolist()):
        assert b"." in text or b"e" in text, text
        assert struct.pack("<d", float(text)) == struct.pack("<d", v)


OTHER_COLUMNS = {
    "lists": lambda: objects([1, 2], [3]),
    "bytes": lambda: objects(b"x", b"y"),
    "decimal": lambda: objects(decimal.Decimal("1.5"), None),
    "python_ints": lambda: objects(1, 2, None),
    "python_floats": lambda: objects(1.5, float("nan"), None),
    "str_and_int": lambda: objects("a", 1),
    "str_and_bytes": lambda: objects("a", b"b"),
    "lone_surrogate": lambda: objects("\ud800", "a"),
    "datetime64": lambda: np.array(["2026-10-04"], dtype="M8[s]"),
    "complex": lambda: np.array([1j]),
    "two_dimensional": lambda: np.zeros((2, 3)),
}


@pytest.mark.parametrize("kind", sorted(OTHER_COLUMNS))
def test_a_column_of_neither_class_is_not_written_columnwise(kind):
    col = OTHER_COLUMNS[kind]()
    assert columnar_rows([np.arange(len(col)), col]) is None


@pytest.mark.parametrize("kind", ["lists", "python_ints", "python_floats",
                                  "str_and_int", "lone_surrogate",
                                  "two_dimensional"])
def test_such_a_result_takes_the_values_path_whole(kind):
    col = OTHER_COLUMNS[kind]()
    r = result(np.arange(len(col), dtype=np.float64), col)
    ok = result([1.0, 2.0, 3.0])
    before = rows_counted()
    body = encode_sql_payload([r, ok], 1.0)
    after = rows_counted()
    # the whole result set, its float column too, is the per-value bytes
    want = json.dumps(json_rows(r)).encode()
    assert b'"rows": ' + want + b', "total_rows"' in body
    assert after["values"] - before["values"] == r.num_rows
    assert after["columnar"] - before["columnar"] == ok.num_rows
    assert_same(json.loads(body), json.loads(per_value_payload([r, ok], 1.0)))


def test_the_counters_two_labels_add_up_to_the_rows_served():
    served = [result(np.arange(5)), result(objects([1], [2])),
              QueryResult.of_affected(9), result(objects("a", "b", None)),
              result(np.array([], dtype=np.float64))]
    before = rows_counted()
    for _ in range(3):
        encode_sql_payload(served, 0.0)
    after = rows_counted()
    assert after["columnar"] - before["columnar"] == 3 * (5 + 3)
    assert after["values"] - before["values"] == 3 * 2
    assert SQL_ENCODED_ROWS.total() == sum(after.values())


@pytest.mark.parametrize("path", ["columnar", "values"])
def test_encode_memo_members_get_equal_bytes_and_share_what_was_written(
        path):
    last = objects("a", None) if path == "columnar" else objects([1], [2])
    cols = [np.array([0.5, np.nan]), last]
    memo: dict = {}
    members = [result(*cols) for _ in range(3)]
    for m in members:
        m.encode_memo = memo
    alone = encode_sql_payload([result(*cols)], 2.0)
    before = rows_counted()
    first = encode_sql_payload([members[0]], 2.0)
    written = memo["rows_json"]
    assert written[0] == path
    bodies = [encode_sql_payload([m], 2.0) for m in members[1:]]
    assert memo["rows_json"] is written           # not written again
    assert first == alone and all(b == first for b in bodies)
    # every member's rows were served, by the path that wrote them
    assert rows_counted()[path] - before[path] == 3 * 2


def test_a_memo_holding_written_rows_still_pickles():
    """The pool's process mode pickles the results it is handed."""
    import pickle

    r = result([0.5, 3.0], objects("a", None))
    r.encode_memo = {}
    want = encode_sql_payload([r], 1.0)
    again = pickle.loads(pickle.dumps(r))
    assert "rows_json" in again.encode_memo
    assert encode_sql_payload([again], 1.0) == want


# ---- through the server -----------------------------------------------------


@pytest.fixture
def server(tmp_path):
    eng = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data")))
    qe = QueryEngine(Catalog(MemoryKv()), eng)
    qe.execute_one(
        "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) NOT NULL, "
        "usage_user DOUBLE, usage_idle DOUBLE, TIME INDEX (ts), "
        "PRIMARY KEY (hostname))")
    rows = [f"('host_{h}', {t * 1000}, {float(h + t)}, {h + t / 7})"
            for h in range(20) for t in range(30)]
    qe.execute_one("INSERT INTO cpu VALUES " + ", ".join(rows))
    srv = HttpServer(qe, port=0)
    port = srv.start()
    try:
        yield qe, f"http://127.0.0.1:{port}/v1/sql"
    finally:
        srv.stop()
        eng.close()


SERVED = {
    "rows": "SELECT hostname, ts, usage_user, usage_idle FROM cpu "
            "ORDER BY hostname, ts",
    "group_by": "SELECT hostname, max(usage_user), avg(usage_idle), "
                "count(*) FROM cpu GROUP BY hostname ORDER BY hostname",
    "no_row": "SELECT hostname, usage_user FROM cpu WHERE ts < 0",
    "null_among_them": "SELECT hostname, CASE WHEN usage_user > 10 THEN "
                       "usage_user END AS u FROM cpu ORDER BY hostname, ts",
}


@pytest.mark.parametrize("sql", sorted(SERVED))
def test_served_answer_is_the_engines_rows_written_columnwise(server, sql):
    qe, url = server
    before = rows_counted()
    with urllib.request.urlopen(
            url, urllib.parse.urlencode({"sql": SERVED[sql]}).encode()) as f:
        got = json.loads(f.read())
    after = rows_counted()
    results = qe.execute_sql(SERVED[sql])
    want = json.loads(per_value_payload(results, got["execution_time_ms"]))
    assert_same(got, want)
    n = results[0].num_rows
    assert got["output"][0]["records"]["total_rows"] == n
    assert after["columnar"] - before["columnar"] == n
    assert after["values"] == before["values"]

"""Columnar result serialization for the protocol servers.

Called on the thread that owns the request, inside
`tracing.stage("encode")`. Light on imports (json/math/numpy at the top;
pyarrow inside the functions that write with it): nothing here needs the
engine or JAX.

Two properties the tier-1 parity tests pin down:

- **the same values and types, one spelling on every serving mode**: a
  `/v1/sql` answer's `"rows"` are written from the result's columns in
  arrow's kernels (`columnar_rows`), no Python object a value, and
  `json.loads` of the body gives what the per-value writer's gave — a
  float the identical float64 (and still a float: `3.0`, never `3`), an
  integer, a boolean and a string the identical value, NULL / NaN /
  +-Inf `null`. A result holding a column the writer has no class for
  goes through `json_rows` + `json.dumps` whole. Which of the two wrote
  a result depends on its columns' dtypes alone, so the bytes are the
  same on every request thread;
- **one materialization per single flight**: the fast lane's single
  flight gives its result an `encode_memo` dict that the followers'
  encoders share — the first encoder to run stores what it wrote (the
  `"rows"` bytes; the row list for `json_rows` / `memo_rows`), the
  other requests of the flight reuse it instead of re-walking the
  columns.
"""

from __future__ import annotations

import json
import math
import struct

import numpy as np

from greptimedb_tpu.utils.metrics import ENCODE_SECONDS, SQL_ENCODED_ROWS


def _json_safe(v):
    if isinstance(v, float) and (math.isnan(v) or math.isinf(v)):
        return None
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return v


def json_rows(r) -> list:
    """`r.rows()` with JSON-safe values, built column-wise: numeric
    columns convert through ONE numpy object cast (a C loop yielding
    native Python scalars) + a vectorized non-finite -> None mask,
    instead of a Python-level `_json_safe` call per value. Object/
    string columns keep the per-value loop (they may hold anything).
    Memoized in the result's single-flight `encode_memo` when present."""
    memo = getattr(r, "encode_memo", None)
    if memo is not None:
        rows = memo.get("json_rows")
        if rows is not None:
            return rows
    cols = []
    for col in r.columns:
        a = np.asarray(col)
        if a.dtype.kind == "f":
            o = a.astype(object)
            bad = ~np.isfinite(a)
            if bad.any():
                o[bad] = None
            cols.append(o.tolist())
        elif a.dtype.kind in "iub":
            cols.append(a.astype(object).tolist())
        else:
            cols.append([_json_safe(v) for v in a.tolist()])
    rows = [list(t) for t in zip(*cols)] if cols else []
    if memo is not None:
        # benign race: concurrent encoders compute identical values
        memo["json_rows"] = rows
    return rows


def records_json(r) -> dict:
    schema = {"column_schemas": [
        {"name": n, "data_type": (dt.value if dt else "string")}
        for n, dt in zip(r.names, r.dtypes)
    ]}
    return {"schema": schema, "rows": json_rows(r),
            "total_rows": r.num_rows}


#: memoized pre-serialized schema headers, keyed by the result shape —
#: dashboards repeat a handful of shapes, and re-dumping the identical
#: column_schemas fragment per response was pure per-request overhead.
#: Plain dict under the GIL (benign race: equal values); bounded by a
#: wholesale clear.
_SCHEMA_CACHE: dict = {}


def schema_header_json(names, dtypes) -> str:
    key = (tuple(names),
           tuple(dt.value if dt else None for dt in dtypes))
    cached = _SCHEMA_CACHE.get(key)
    if cached is None:
        cached = json.dumps({"column_schemas": [
            {"name": n, "data_type": (dt.value if dt else "string")}
            for n, dt in zip(names, dtypes)]})
        if len(_SCHEMA_CACHE) > 512:
            _SCHEMA_CACHE.clear()
        _SCHEMA_CACHE[key] = cached
    return cached


def columnar_rows(columns) -> tuple | None:
    """A result set's `"rows"` written from its columns: one text array
    a column, one `binary_join_element_wise` for the rows, one
    `binary_join` for the body. Python works per column and per DISTINCT
    string; the rest runs in arrow's kernels, which give the interpreter
    lock up. Returned as the pieces to join (arrow's buffer is not copied
    here). None where a column is of neither class below (an object
    column holding a list, bytes, a Decimal, a number; a datetime; an
    array of arrays):

    - numbers: columns of one dtype are cast to text together. A float
      is widened to float64 first (a float32 answer spells the float64
      it equals) and spelled in arrow's shortest form that parses back
      to the same float64, `.0` added where that form is a bare integer
      (`3`, `-0`); NaN and +-Inf are null;
    - strings (`str` / `None`): the distinct values are escaped by
      `json.dumps` and taken by their codes."""
    import pyarrow as pa
    import pyarrow.compute as pc

    large = pa.large_string()

    def lit(s):
        return pa.scalar(s, large)

    def numbers(a):
        if a.dtype.kind != "f":
            return pc.cast(pa.array(a), large)
        bad = ~np.isfinite(a)
        text = pc.cast(pa.array(a, mask=bad if bad.any() else None), large)
        with np.errstate(invalid="ignore"):  # a signalling NaN
            whole = a == np.trunc(a)
        if whole.any():
            # a whole number is spelled with an `e` or bare, never a `.`
            bare = pc.and_not(pa.array(whole),
                              pc.match_substring(text, "e"))
            text = pc.if_else(bare, pc.binary_join_element_wise(
                text, lit(".0"), lit("")), text)
        return text

    def strings(a):
        try:
            values = pa.array(a)
        except (pa.ArrowException, UnicodeError):
            return None  # mixed classes, a lone surrogate
        if pa.types.is_null(values.type):
            return pa.nulls(len(a), large)
        if not pa.types.is_string(values.type):
            return None
        coded = values.dictionary_encode()
        return pa.array([json.dumps(s) for s in coded.dictionary.to_pylist()],
                        large).take(coded.indices)

    n = len(columns[0]) if columns else 0
    if n == 0:
        return (b"[]",)
    texts: list = [None] * len(columns)
    by_dtype: dict = {}
    for i, col in enumerate(columns):
        a = np.asarray(col)
        if a.ndim != 1:
            return None
        if a.dtype.kind == "f":
            by_dtype.setdefault(np.dtype(np.float64), []).append((i, a))
        elif a.dtype.kind in "iub":
            by_dtype.setdefault(a.dtype, []).append((i, a))
        elif a.dtype.kind in "OU":
            texts[i] = strings(a)
            if texts[i] is None:
                return None
        else:
            return None
    for dtype, members in by_dtype.items():
        flat = np.empty(n * len(members), dtype)
        for j, (_, a) in enumerate(members):
            flat[j * n:(j + 1) * n] = a
        text = numbers(flat)
        for j, (i, _) in enumerate(members):
            texts[i] = text.slice(j * n, n)
    rows = pc.binary_join_element_wise(
        *texts, lit(", "), null_handling="replace", null_replacement="null")
    body = pc.binary_join(pa.ListArray.from_arrays(
        pa.array([0, n], pa.int32()), rows), lit("], ["))[0]
    return b"[[", body.as_buffer(), b"]]"


def rows_json(r) -> tuple:
    """The `"rows"` of one query result as the response carries them
    (pieces to join), counted by which writer wrote them. Which one is
    decided by the columns' dtypes alone (`columnar_rows`). Kept in the
    result's single-flight `encode_memo` when present."""
    memo = getattr(r, "encode_memo", None)
    written = memo.get("rows_json") if memo is not None else None
    if written is None:
        pieces = columnar_rows(r.columns)
        written = ("columnar", pieces) if pieces is not None else (
            "values", (json.dumps(json_rows(r)).encode(),))
        if memo is not None:
            # benign race: concurrent encoders write identical bytes
            memo["rows_json"] = written
    path, pieces = written
    SQL_ENCODED_ROWS.inc(r.num_rows, path=path)
    return pieces


def encode_sql_payload(results, elapsed_ms: float) -> bytes:
    """The full /v1/sql response body. Assembled from the memoized
    schema-header fragment + each result's `rows_json`, spelled as
    `json.dumps` of the whole document spells it (`", "`/`": "`
    separators — pinned by the tier-1 parity test)."""
    with ENCODE_SECONDS.time(protocol="http"):
        out = [b'{"code": 0, "output": [']
        for i, r in enumerate(results):
            if i:
                out.append(b", ")
            if not r.is_query:
                out.append(b'{"affectedrows": %d}' % r.affected_rows)
                continue
            out.append(b'{"records": {"schema": %s, "rows": '
                       % schema_header_json(r.names, r.dtypes).encode())
            out.extend(rows_json(r))
            out.append(b', "total_rows": %d}}' % r.num_rows)
        out.append(b'], "execution_time_ms": %s}'
                   % json.dumps(elapsed_ms).encode())
        return b"".join(out)


# ---- Prometheus range answers ----------------------------------------------
# A matrix answer is written from its columns: no Python object is made
# for a sample. What Python does is per request, per step (the step
# prefixes) and, where the fragments are not kept, per series; the rest
# runs in arrow's kernels, which give the interpreter lock up.


_MATRIX_HEAD = b'{"status":"success","data":{"resultType":"matrix","result":['
_MATRIX_TAIL = b"]}}"


def metric_fragments(labels: list, metric=None):
    """One string a series, `{"metric":{…},"values":[`: the head of its
    entry in a matrix answer. `json.dumps` does the escaping, `__name__`
    last as the object form has it. Depends on the label sets and the
    metric name alone (`promql/loaded.py` `derive` keeps it)."""
    import pyarrow as pa

    name = {"__name__": metric} if metric else {}
    dumps = json.JSONEncoder(separators=(",", ":")).encode
    return pa.array(['{"metric":%s,"values":[' % dumps({**lab, **name})
                     for lab in labels], pa.large_string())


def matrix_body(times: np.ndarray, vals: np.ndarray, fragments) -> bytes:
    """The whole body of a `query_range` matrix answer: `vals`
    [series, steps] at `times`, `fragments` from `metric_fragments`.
    NaN samples are left out, and with them a series that has no other;
    a value is spelled in arrow's shortest form that parses back to the
    same float64 (`3`, `1e-7`, `-0`; Prometheus writes `3` too), ±Inf
    as `+Inf` / `-Inf`."""
    import pyarrow as pa
    import pyarrow.compute as pc

    def lit(s):
        return pa.scalar(s, pa.large_string())

    def joined(parts, counts):
        # `parts` joined by commas, `counts` of them at a time
        offsets = np.zeros(len(counts) + 1, dtype=np.int32)
        np.cumsum(counts, out=offsets[1:])
        return pc.binary_join(
            pa.ListArray.from_arrays(pa.array(offsets), parts), lit(","))

    # widened first, so a float32 answer spells the float64 it equals
    vals = np.asarray(vals, dtype=np.float64)
    keep = ~np.isnan(vals)
    counts = keep.sum(axis=1)
    series = np.flatnonzero(counts)
    if len(series) == 0:
        return _MATRIX_HEAD + _MATRIX_TAIL
    kept = vals[keep]
    text = pc.cast(pa.array(kept), pa.large_string())
    inf = np.isinf(kept)
    if inf.any():
        text = pc.if_else(pa.array(inf), pc.if_else(
            pa.array(kept > 0), lit("+Inf"), lit("-Inf")), text)
    # a step's `[<time>,"` is spelled once and taken by its kept samples
    steps = pa.array(['[%r,"' % t for t in np.asarray(times).tolist()],
                     pa.large_string())
    samples = pc.binary_join_element_wise(
        steps.take(pa.array(np.nonzero(keep)[1])), text, lit('"]'), lit(""))
    entries = pc.binary_join_element_wise(
        fragments.take(pa.array(series)), joined(samples, counts[series]),
        lit("]}"), lit(""))
    result = joined(entries, [len(entries)])[0]
    return b"".join((_MATRIX_HEAD, memoryview(result.as_buffer()),
                     _MATRIX_TAIL))


# ---- MySQL wire fragments --------------------------------------------------
# (the resultset encoding needs nothing of the engine)

MYSQL_TYPE_VAR_STRING = 253


def lenc_int(n: int) -> bytes:
    if n < 251:
        return bytes([n])
    if n < 1 << 16:
        return b"\xfc" + struct.pack("<H", n)
    if n < 1 << 24:
        return b"\xfd" + struct.pack("<I", n)[:3]
    return b"\xfe" + struct.pack("<Q", n)


def lenc_str(s: bytes) -> bytes:
    return lenc_int(len(s)) + s


def _eof() -> bytes:
    return b"\xfe" + struct.pack("<H", 0) + struct.pack("<H", 0x0002)


def _coldef(name: str, ftype: int) -> bytes:
    return (
        lenc_str(b"def")
        + lenc_str(b"")  # schema
        + lenc_str(b"")  # table
        + lenc_str(b"")  # org_table
        + lenc_str(name.encode())
        + lenc_str(name.encode())
        + bytes([0x0C])  # fixed-length fields length
        + struct.pack("<H", 0x21)  # charset utf8
        + struct.pack("<I", 1024)  # column length
        + bytes([ftype])
        + struct.pack("<H", 0)  # flags
        + bytes([0x1F])  # decimals
        + b"\x00\x00"
    )


def _fmt(v) -> str:
    if isinstance(v, (bool, np.bool_)):
        return "1" if v else "0"
    if isinstance(v, (float, np.floating)):
        return repr(float(v))
    return str(v)


def memo_rows(result) -> list:
    """`QueryResult.rows()` through the single flight's memo: coalesced
    requests materialize the Python row objects once."""
    memo = getattr(result, "encode_memo", None)
    if memo is not None:
        rows = memo.get("rows")
        if rows is not None:
            return rows
    rows = result.rows()
    if memo is not None:
        memo["rows"] = rows
    return rows


def encode_mysql_result(result, binary: bool = False) -> list[bytes]:
    """Resultset packets straight from a QueryResult, its rows
    materialized once a single flight (`memo_rows`)."""
    return encode_mysql_rows(list(result.names), memo_rows(result),
                             binary)


#: memoized resultset header packets (column count + column definitions
#: + EOF) keyed by the column-name tuple — every repeat of a dashboard
#: shape re-encoded identical coldef packets. Benign-race dict, bounded
#: by a wholesale clear.
_HEADER_CACHE: dict = {}


def mysql_header_packets(names) -> list[bytes]:
    key = tuple(names)
    cached = _HEADER_CACHE.get(key)
    if cached is None:
        cached = [lenc_int(len(names))] \
            + [_coldef(n, MYSQL_TYPE_VAR_STRING) for n in names] \
            + [_eof()]
        if len(_HEADER_CACHE) > 512:
            _HEADER_CACHE.clear()
        _HEADER_CACHE[key] = cached
    return list(cached)


def encode_mysql_rows(names, rows, binary: bool = False) -> list[bytes]:
    """Resultset packet payloads for one query result (column count,
    column definitions, EOF, row packets, EOF) — the session loop only
    stamps sequence numbers and writes. Row payloads accumulate in a
    reusable bytearray (amortized append) instead of quadratic bytes
    concatenation; the emitted packets are byte-identical."""
    with ENCODE_SECONDS.time(protocol="mysql"):
        packets = mysql_header_packets(names)
        for row in rows:
            payload = bytearray()
            if binary:
                # binary row: 0x00 header + null bitmap (offset 2) + values
                nb = bytearray((len(row) + 7 + 2) // 8)
                for i, v in enumerate(row):
                    if v is None or (isinstance(v, float) and np.isnan(v)):
                        nb[(i + 2) // 8] |= 1 << ((i + 2) % 8)
                    else:
                        s = _fmt(v).encode()
                        payload += lenc_int(len(s))
                        payload += s
                packets.append(b"\x00" + bytes(nb) + bytes(payload))
            else:
                for v in row:
                    if v is None or (isinstance(v, float) and np.isnan(v)):
                        payload += b"\xfb"  # NULL
                    else:
                        s = _fmt(v).encode()
                        payload += lenc_int(len(s))
                        payload += s
                packets.append(bytes(payload))
        packets.append(_eof())
        return packets

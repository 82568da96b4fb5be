"""gRPC data plane via Arrow Flight (mirrors reference servers::grpc:
`GreptimeDatabase` service + Arrow Flight `do_get`,
src/servers/src/grpc/{greptime_handler.rs:42,flight.rs:45-115}, and the
datanode region Flight service, src/servers/src/grpc/region_server.rs:39-92).

Two services on one Flight endpoint:

- **Query service** (frontend analog): `do_get` with a ticket
  `{"sql": ..., "db": ...}` streams the result as Arrow record batches;
  `do_put` bulk-ingests Arrow batches into a table (the row-insert path);
  `do_action` carries DDL/DML and health checks.
- **Region service** (datanode analog): `do_get` with
  `{"region_scan": {"region_id": ..., ...}}` streams one region's raw scan
  (tag codes as dictionary arrays, `__seq`/`__op_type` sideband columns) —
  the distributed MergeScan transport. The client reassembles `ScanData`
  and feeds the same device merge/dedup kernels as a local scan
  (SURVEY.md §2.6: Flight is the reference's data-movement fabric).

Auth: Flight handshake with Basic credentials when a UserProvider is
installed (the reference authenticates Flight calls the same way,
servers/src/grpc/flight.rs).
"""

from __future__ import annotations

import json
import os
import secrets
from typing import Optional

import numpy as np
import pyarrow as pa
import pyarrow.flight as fl

from greptimedb_tpu.datatypes.schema import Schema
from greptimedb_tpu.fault import FAULTS, local_node, retry_call
from greptimedb_tpu.query.result import QueryResult
from greptimedb_tpu.session import Channel, QueryContext
from greptimedb_tpu.storage.region import ScanData

SEQ_COL = "__seq"
OP_COL = "__op_type"

#: Flight errors the shared RetryPolicy may fix (server briefly away,
#: timeout, transient internal) — auth/arg errors surface immediately
RETRYABLE_FLIGHT = (fl.FlightUnavailableError, fl.FlightTimedOutError,
                    fl.FlightInternalError)


def _call_options() -> Optional[fl.FlightCallOptions]:
    """Per-call gRPC deadline from the active query token: a stalled
    peer then fails the call locally (FlightTimedOutError) right at the
    query deadline instead of blocking in read_all() forever — the
    retry loop's deadline check converts that into typed
    DeadlineExceeded. Recomputed per attempt so retries ride the
    shrinking budget. Floor keeps an almost-spent budget from turning
    into timeout=0 (gRPC treats that as already-expired)."""
    from greptimedb_tpu.utils import deadline as dl

    token = dl.current()
    if token is None:
        return None
    remaining = token.remaining_s()
    if remaining is None:
        return None
    return fl.FlightCallOptions(timeout=max(0.05, remaining))


# ---- QueryResult ⇄ Arrow: shared converters live in datasource ------------

from greptimedb_tpu.datasource import result_to_table, table_to_result  # noqa: E402,F401


# ---- ScanData ⇄ Arrow (region service wire format) --------------------------


def scan_to_table(scan: ScanData) -> pa.Table:
    arrays, fields = [], []
    for name, col in scan.columns.items():
        if name in scan.tag_dicts:
            codes = np.asarray(col, dtype=np.int32)
            dict_vals = pa.array(scan.tag_dicts[name].astype(str))
            arr = pa.DictionaryArray.from_arrays(
                pa.array(np.where(codes < 0, None, codes), type=pa.int32()),
                dict_vals)
        else:
            arr = pa.array(col)
        arrays.append(arr)
        fields.append(pa.field(name, arr.type))
    arrays.append(pa.array(scan.seq))
    fields.append(pa.field(SEQ_COL, pa.int64()))
    arrays.append(pa.array(scan.op_type))
    fields.append(pa.field(OP_COL, pa.int8()))
    meta = {
        b"schema": json.dumps(scan.schema.to_dict()).encode(),
        b"needs_dedup": b"1" if scan.needs_dedup else b"0",
        b"region_id": str(scan.region_id).encode(),
        b"data_version": str(scan.data_version).encode(),
    }
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields, metadata=meta))


def partial_to_table(part: dict) -> pa.Table:
    """Partial-aggregate result ⇄ Arrow (the wire format of the Final
    combine's input). Key columns are `__key_<i>`; each primitive plane
    flattens to `__plane_<op>` FixedSizeList-free float64 columns with
    the field count in metadata."""
    arrays, fields = [], []
    for i, kc in enumerate(part["keys"]):
        arr = pa.array(kc)
        arrays.append(arr)
        fields.append(pa.field(f"__key_{i}", arr.type))
    meta = {b"n_keys": str(len(part["keys"])).encode()}
    for op, plane in part["planes"].items():
        plane2 = plane if plane.ndim == 2 else plane[:, None]
        meta[f"f_{op}".encode()] = str(plane2.shape[1]).encode()
        for j in range(plane2.shape[1]):
            arr = pa.array(plane2[:, j])
            arrays.append(arr)
            fields.append(pa.field(f"__plane_{op}_{j}", arr.type))
    return pa.Table.from_arrays(arrays,
                                schema=pa.schema(fields, metadata=meta))


def table_to_partial(t: pa.Table) -> dict:
    meta = t.schema.metadata or {}
    n_keys = int(meta[b"n_keys"])
    keys = []
    for i in range(n_keys):
        col = t.column(f"__key_{i}")
        arr = col.to_numpy(zero_copy_only=False)
        if arr.dtype.kind == "f" and np.isnan(arr).any():
            # Arrow materialized a NULL key as NaN; the in-process path
            # yields None — normalize so results don't depend on transport
            arr = np.array([None if (isinstance(x, float) and x != x)
                            else x for x in arr], dtype=object)
        keys.append(arr)
    planes: dict = {}
    for k, v in meta.items():
        if not k.startswith(b"f_"):
            continue
        op = k[2:].decode()
        f = int(v)
        cols = [t.column(f"__plane_{op}_{j}").to_numpy(zero_copy_only=False)
                for j in range(f)]
        planes[op] = np.stack(cols, axis=1)
    return {"keys": keys, "planes": planes}


def table_to_scan(t: pa.Table) -> ScanData:
    meta = t.schema.metadata or {}
    schema = Schema.from_dict(json.loads(meta[b"schema"].decode()))
    columns: dict[str, np.ndarray] = {}
    tag_dicts: dict[str, np.ndarray] = {}
    seq = op = None
    for field in t.schema:
        col = t.column(field.name)
        if field.name == SEQ_COL:
            seq = col.to_numpy(zero_copy_only=False).astype(np.int64)
        elif field.name == OP_COL:
            op = col.to_numpy(zero_copy_only=False).astype(np.int8)
        elif pa.types.is_dictionary(field.type):
            combined = col.combine_chunks() if isinstance(col, pa.ChunkedArray) \
                else col
            if isinstance(combined, pa.ChunkedArray):
                combined = combined.chunk(0)
            codes = combined.indices.to_numpy(zero_copy_only=False)
            codes = np.where(np.isnan(codes.astype(np.float64)), -1,
                             codes).astype(np.int32) \
                if codes.dtype.kind == "f" else codes.astype(np.int32)
            columns[field.name] = codes
            tag_dicts[field.name] = np.asarray(
                combined.dictionary.to_pylist(), dtype=object)
        else:
            columns[field.name] = col.to_numpy(zero_copy_only=False)
    return ScanData(
        schema=schema, columns=columns, seq=seq, op_type=op,
        tag_dicts=tag_dicts, num_rows=t.num_rows,
        needs_dedup=meta.get(b"needs_dedup", b"1") == b"1",
        region_id=int(meta.get(b"region_id", b"-1")),
        data_version=int(meta.get(b"data_version", b"0")),
    )


# ---- auth handlers ----------------------------------------------------------


class _BasicServerAuth(fl.ServerAuthHandler):
    """Flight handshake: client sends 'user:password', server returns an
    opaque session token validated on every call."""

    MAX_TOKENS = 1024  # LRU bound: oldest sessions re-handshake

    def __init__(self, user_provider):
        from collections import OrderedDict

        super().__init__()
        self.user_provider = user_provider
        self._tokens: "OrderedDict[bytes, str]" = OrderedDict()
        # username -> authenticated UserInfo (with grants), resolved by
        # FlightServer handlers from context.peer_identity()
        self._identities: dict[str, object] = {}

    def authenticate(self, outgoing, incoming):
        from greptimedb_tpu.auth import AuthError

        raw = incoming.read()
        user, _, pwd = raw.decode().partition(":")
        try:
            info = self.user_provider.authenticate(user, pwd)
        except AuthError as e:
            raise fl.FlightUnauthenticatedError(str(e)) from e
        self._identities[user] = info
        token = secrets.token_bytes(16)
        self._tokens[token] = user
        while len(self._tokens) > self.MAX_TOKENS:
            self._tokens.popitem(last=False)
        outgoing.write(token)

    def is_valid(self, token):
        if token not in self._tokens:
            raise fl.FlightUnauthenticatedError("invalid token")
        return self._tokens[token].encode()


class _BasicClientAuth(fl.ClientAuthHandler):
    def __init__(self, user: str, password: str):
        super().__init__()
        self._cred = f"{user}:{password}".encode()
        self._token = b""

    def authenticate(self, outgoing, incoming):
        outgoing.write(self._cred)
        self._token = incoming.read()

    def get_token(self):
        return self._token


# ---- server -----------------------------------------------------------------


class FlightServer(fl.FlightServerBase):
    """Frontend + region Flight services on one port.

    Two deployment shapes (reference: frontend gRPC service vs the
    datanode region server, servers/src/grpc/region_server.rs:39-92):
    - frontend: pass `query_engine` — SQL/TQL over do_get, bulk ingest
      over do_put, plus the region service against its region engine.
    - datanode: pass `region_engine` only — region scan/write/DDL
      actions; no SQL surface.
    """

    def __init__(self, query_engine, host: str = "127.0.0.1", port: int = 0,
                 user_provider=None, region_engine=None,
                 node_id: Optional[str] = None):
        self.qe = query_engine
        self.engine = region_engine if region_engine is not None \
            else (query_engine.region_engine if query_engine else None)
        auth = _BasicServerAuth(user_provider) if user_provider else None
        self._auth = auth
        # lazy executor for partial-aggregate pushdown tickets
        self._agg_executor = None
        location = f"grpc://{host}:{port}"
        super().__init__(location, auth_handler=auth)
        self.host = host
        # identity stamped on piggybacked spans so a distributed EXPLAIN
        # ANALYZE attributes each stage to its process (reference tags
        # RecordBatchMetrics per peer, merge_scan.rs:245-259)
        self.node_id = node_id or os.environ.get("GTPU_NODE_ID") \
            or f"{host}:{self.port}"

    def _resolve_user(self, context):
        """Map the Flight peer identity (set by _BasicServerAuth.is_valid)
        back to the authenticated UserInfo so PermissionChecker sees the
        same principal gRPC authenticated — without this, grants and
        protected-schema rules were silently skipped over Flight."""
        if self._auth is None:
            return None
        ident = context.peer_identity()
        if not ident:
            return None
        name = ident.decode() if isinstance(ident, bytes) else str(ident)
        info = self._auth._identities.get(name)
        if info is None:
            from greptimedb_tpu.auth import UserInfo
            info = UserInfo(name)
        return info

    # -- query service --------------------------------------------------------

    def do_get(self, context, ticket):
        req = json.loads(ticket.ticket.decode())
        if "region_scan" in req:
            user = self._resolve_user(context)
            if user is not None and not user.can("read"):
                raise fl.FlightUnauthorizedError(
                    f"user {user.username!r} lacks read permission")
            return self._region_scan(req["region_scan"])
        if "region_frag" in req:
            user = self._resolve_user(context)
            if user is not None and not user.can("read"):
                raise fl.FlightUnauthorizedError(
                    f"user {user.username!r} lacks read permission")
            return self._region_frag(req["region_frag"])
        if self.qe is None:
            raise fl.FlightServerError("datanode service: region tickets only")
        from greptimedb_tpu.utils import tracing

        if "sql" not in req and "tql" not in req:
            raise fl.FlightServerError("ticket needs 'sql', 'tql' or 'region_scan'")
        # request-root span for the Flight SQL surface: adopt the
        # caller's trace context when the ticket carries one (the
        # region_server.rs:74 re-attach analog), else mint a fresh trace
        with tracing.adopt_remote(req.get("trace_id")
                                  or tracing.new_trace_id(),
                                  req.get("parent_span")):
            ctx = QueryContext(db=req.get("db", "public"),
                               channel=Channel.GRPC,
                               user=self._resolve_user(context),
                               trace_id=tracing.current_trace_id())
            if "sql" in req:
                with tracing.span("flight:sql"):
                    result = self.qe.execute_one(req["sql"], ctx)
            else:
                t = req["tql"]
                from greptimedb_tpu.promql.engine import PromqlEngine
                with tracing.span("flight:tql"):
                    result = PromqlEngine(self.qe).eval_range(
                        t["query"], t["start"], t["end"], t["step"], ctx)
        if not result.is_query:
            # DML/DDL ack: flagged via schema metadata, not column names
            # (a SELECT could legitimately project `affected_rows`)
            table = pa.Table.from_arrays(
                [pa.array([result.affected_rows], type=pa.int64())],
                schema=pa.schema([pa.field("affected_rows", pa.int64())],
                                 metadata={b"affected": b"1"}))
        else:
            table = result_to_table(result)
        return fl.RecordBatchStream(table)

    def _piggyback(self, table: pa.Table, sink) -> pa.Table:
        """Attach this request's spans (+ the serving node's identity) to
        the response schema metadata — the RecordBatchMetrics piggyback
        (merge_scan.rs:245-259): the caller merges them into its own ring
        so one EXPLAIN ANALYZE covers every process the query touched."""
        from greptimedb_tpu.utils import tracing

        meta = dict(table.schema.metadata or {})
        meta[b"spans"] = json.dumps(tracing.spans_to_wire(sink)).encode()
        meta[b"node"] = str(self.node_id).encode()
        return table.replace_schema_metadata(meta)

    def _region_scan(self, req: dict):
        """Datanode region service (reference region_server.rs:39-92 —
        Substrait plan in, Flight stream out; here the scan spec is the
        plan fragment)."""
        from greptimedb_tpu.utils import tracing

        region_id = req["region_id"]
        ts_range = tuple(req["ts_range"]) if req.get("ts_range") else None
        projection = req.get("projection")
        from greptimedb_tpu.storage.index import deserialize_predicates
        preds = deserialize_predicates(
            req.get("tag_predicates_v2") or req.get("tag_predicates"))
        from greptimedb_tpu.utils import deadline as dl
        from greptimedb_tpu.utils.metrics import REQUEST_BUDGET_REMAINING

        budget = req.get("budget_ms")
        if budget is not None:
            REQUEST_BUDGET_REMAINING.observe(float(budget))
        # adopt the caller's trace AND parent span (region_server.rs:74
        # analog): this datanode's region_scan re-parents under the
        # frontend span that issued the RPC, so the merged ANALYZE tree
        # nests across the process hop. The ticket's remaining budget
        # becomes a local token: a scan whose frontend already gave up
        # unwinds typed here instead of burning datanode workers.
        with dl.activate(dl.token_for_budget(budget)), \
                tracing.adopt_remote(req.get("trace_id"),
                                     req.get("parent_span")), \
                tracing.collect_spans() as sink:
            with tracing.span("region_scan", region=region_id) as attrs:
                # server-side injection INSIDE the scan span: latency
                # armed here (e.g. via GTPU_CHAOS inherited by a child
                # datanode, @side:server) lands in the span duration the
                # frontend's merged tree renders — the end-to-end proof
                # the ROADMAP fault-matrix item asked for
                FAULTS.fire("flight.do_get", side="server",
                            node=local_node(), op="region_scan")
                scan = self.engine.scan(
                    region_id, ts_range=ts_range, projection=projection,
                    tag_predicates=preds, seq_min=req.get("seq_min"),
                    full_key=bool(req.get("full_key", True)))
                # scan stats ride the span: rows served, SST pruning,
                # host scan-cache reuse (reference RecordBatchMetrics
                # carries the same per-stage counters)
                attrs["rows"] = 0 if scan is None else scan.num_rows
                if scan is not None and scan.stats:
                    attrs.update(scan.stats)
            if scan is None:
                # empty marker: zero-column table with metadata flag
                table = pa.Table.from_arrays(
                    [], schema=pa.schema([], metadata={b"empty": b"1"}))
            else:
                table = scan_to_table(scan)
                attrs["bytes"] = table.nbytes
        return fl.RecordBatchStream(self._piggyback(table, sink))

    def _region_frag(self, req: dict):
        """Plan-fragment pushdown: the PlanFragment (the substrait
        analog) executes against the LOCAL region and only the terminal
        stage's output crosses the wire — partial planes (tagged
        kind=partial) or candidate/filtered rows (kind=rows), never the
        raw scan (reference dist_plan Partial step, analyzer.rs:35)."""
        from greptimedb_tpu.query.dist_agg import execute_region_fragment
        from greptimedb_tpu.query.plan_ser import PlanFragment
        from greptimedb_tpu.utils import tracing

        region_id = req["region_id"]
        frag = PlanFragment.from_json(req["fragment"])
        if self._agg_executor is None:
            from greptimedb_tpu.query.physical import PhysicalExecutor
            self._agg_executor = PhysicalExecutor(self.engine)
        from greptimedb_tpu.utils import deadline as dl
        from greptimedb_tpu.utils.metrics import REQUEST_BUDGET_REMAINING

        budget = req.get("budget_ms")
        if budget is not None:
            REQUEST_BUDGET_REMAINING.observe(float(budget))
        with dl.activate(dl.token_for_budget(budget)), \
                tracing.adopt_remote(req.get("trace_id"),
                                     req.get("parent_span")), \
                tracing.collect_spans() as sink:
            with tracing.span("region_frag", region=region_id,
                              stages=len(frag.stages)):
                FAULTS.fire("flight.do_get", side="server",
                            node=local_node(), op="region_frag")
                part = execute_region_fragment(self._agg_executor,
                                               region_id, frag)
            if part is None:
                table = pa.Table.from_arrays(
                    [], schema=pa.schema([], metadata={b"empty": b"1"}))
            elif "planes" in part:
                table = partial_to_table(part)
            else:
                cols = part["cols"]
                arrays = [pa.array(cols[name]) for name in cols]
                table = pa.Table.from_arrays(
                    arrays,
                    schema=pa.schema(
                        [pa.field(name, a.type)
                         for name, a in zip(cols, arrays)],
                        metadata={b"kind": b"rows"}))
        return fl.RecordBatchStream(self._piggyback(table, sink))

    # -- ingest ----------------------------------------------------------------

    def do_put(self, context, descriptor, reader, writer):
        """Bulk Arrow ingest into an existing table (the reference's row
        insert gRPC, greptime_handler.rs:62 — here columnar end-to-end).
        Path ["__region__", <rid>, put|delete] is the datanode write path
        (region_server.rs handle_request analog)."""
        path = [p.decode() for p in descriptor.path]
        if not path:
            raise fl.FlightServerError("descriptor path must be [db.]table")
        if path[0] == "__region__":
            user = self._resolve_user(context)
            if user is not None and not user.can("write"):
                raise fl.FlightUnauthorizedError(
                    f"user {user.username!r} lacks write permission")
            from greptimedb_tpu.utils import tracing

            rid = int(path[1])
            op = path[2] if len(path) > 2 else "put"
            # the caller's trace id (and parent span id, one element
            # further) ride the descriptor path tail so write-side
            # spans join — and nest under — the same trace (do_get
            # carries them in the ticket; do_put has only the
            # descriptor). Old peers sent shorter paths; extras are
            # ignored both ways.
            tid_p = path[3] if len(path) > 3 and path[3] else None
            par_p = path[4] if len(path) > 4 and path[4] else None
            with tracing.adopt_remote(tid_p, par_p), \
                    tracing.collect_spans() as sink:
                with tracing.span("region_write", region=rid,
                                  op=op) as attrs:
                    # server-side seam inside the write span (the do_put
                    # mirror of the do_get scan-span injection);
                    # @side:server opts in, plain schedules stay
                    # client-only
                    FAULTS.fire("flight.do_put", side="server",
                                node=local_node(), op="region_write")
                    t = reader.read_all()
                    from greptimedb_tpu.datatypes.recordbatch import RecordBatch

                    region = self.engine.region(rid)
                    if t.num_rows:
                        arrow = t.combine_chunks().to_batches()[0]
                    else:
                        arrow = pa.RecordBatch.from_pydict(
                            {f.name: [] for f in t.schema}, schema=t.schema)
                    batch = RecordBatch.from_arrow(arrow, region.schema)
                    if op == "delete":
                        n = self.engine.delete(rid, batch)
                    else:
                        n = self.engine.put(rid, batch)
                    attrs["rows"] = n
            writer.write(json.dumps({
                "affected_rows": n, "node": self.node_id,
                "spans": tracing.spans_to_wire(sink)}).encode())
            return
        if self.qe is None:
            raise fl.FlightServerError("datanode service: region writes only")
        table_name = path[-1]
        db = path[0] if len(path) > 1 else "public"
        ctx = QueryContext(db=db, channel=Channel.GRPC,
                           user=self._resolve_user(context))
        from greptimedb_tpu.auth import AuthError
        try:
            # full write authorization (grants + protected schema), same
            # rules the SQL INSERT path applies
            self.qe.permission_checker.check_access(ctx.user, "write", db)
        except AuthError as e:
            raise fl.FlightUnauthorizedError(str(e)) from e
        arrow_table = reader.read_all()
        n = self._insert_arrow(table_name, arrow_table, ctx)
        writer.write(json.dumps({"affected_rows": n}).encode())

    def _insert_arrow(self, table_name: str, t: pa.Table, ctx) -> int:
        from greptimedb_tpu.datasource import insert_arrow_table

        return insert_arrow_table(self.qe, table_name, t, ctx)

    # -- control ----------------------------------------------------------------

    def do_action(self, context, action):
        if action.type == "health":
            return [json.dumps({"status": "ok"}).encode()]
        if action.type == "region_admin":
            # datanode control plane (region_server.rs handle_request:
            # create/open/close/drop/flush/compact + existence probe)
            req = json.loads(action.body.to_pybytes().decode())
            rid = req["region_id"]
            op = req["op"]
            user = self._resolve_user(context)
            needed = "read" if op in ("exists", "info") else "write"
            if user is not None and not user.can(needed):
                raise fl.FlightUnauthorizedError(
                    f"user {user.username!r} lacks {needed} permission")
            from greptimedb_tpu.storage.engine import RegionRequest, RequestType

            if op == "chaos_reset":
                # chaos-harness control: clear THIS process's fault
                # registry (schedules + partitions) so an explorer run's
                # final verification reads the cluster chaos-free; a
                # no-op when nothing is armed
                FAULTS.reset()
                return [b'{"ok": true}']
            if op == "info":
                region = self.engine.region(rid)
                return [json.dumps(
                    {"data_version": region.data_version}).encode()]
            if op == "alter":
                from greptimedb_tpu.datatypes.schema import Schema as _S
                self.engine.alter_region_schema(
                    rid, _S.from_dict(req["schema"]))
                return [b'{"ok": true}']
            if op == "create":
                from greptimedb_tpu.datatypes.schema import Schema as _S
                self.engine.create_region(rid, _S.from_dict(req["schema"]))
            elif op == "open":
                self.engine.open_region(rid)
            elif op == "exists":
                try:
                    self.engine.region(rid)
                    return [b'{"exists": true}']
                except KeyError:
                    return [b'{"exists": false}']
            elif op == "flush":
                self.engine.flush(rid)
            elif op == "compact":
                self.engine.compact(rid)
            elif op in ("close", "drop", "truncate"):
                self.engine.handle_request(
                    RegionRequest(RequestType[op.upper()], rid))
            else:
                raise fl.FlightServerError(f"unknown region op {op!r}")
            return [b'{"ok": true}']
        if action.type == "rollup_probe":
            # cluster-mode rollup substitution, eligibility half: which
            # rules fully cover [lo, hi) on this region (the frontend
            # intersects per-region answers and re-plans over the
            # companion plane regions — maintenance/rollup.py)
            req = json.loads(action.body.to_pybytes().decode())
            user = self._resolve_user(context)
            if user is not None and not user.can("read"):
                raise fl.FlightUnauthorizedError(
                    f"user {user.username!r} lacks read permission")
            from greptimedb_tpu.maintenance.rollup import (
                probe_region_rollups,
            )

            out = probe_region_rollups(self.engine, req["region_id"],
                                       int(req["lo"]), int(req["hi"]))
            return [json.dumps(out).encode()]
        if action.type == "sql":
            req = json.loads(action.body.to_pybytes().decode())
            ctx = QueryContext(db=req.get("db", "public"), channel=Channel.GRPC,
                               user=self._resolve_user(context))
            results = self.qe.execute_sql(req["sql"], ctx)
            out = []
            for r in results:
                if r.is_query:
                    out.append(json.dumps(
                        {"rows": r.rows(), "names": r.names}).encode())
                else:
                    out.append(json.dumps(
                        {"affected_rows": r.affected_rows}).encode())
            return out
        raise fl.FlightServerError(f"unknown action {action.type!r}")

    def list_actions(self, context):
        return [("health", "liveness check"),
                ("sql", "execute SQL, results as JSON")]

    def list_flights(self, context, criteria):
        ctx = QueryContext()
        for db in self.qe.catalog.list_databases():
            for name in self.qe.catalog.list_tables(db):
                info = self.qe.catalog.table(db, name)
                fields = [pa.field(c.name, c.dtype.to_arrow())
                          for c in info.schema.columns]
                desc = fl.FlightDescriptor.for_path(db, name)
                yield fl.FlightInfo(pa.schema(fields), desc, [], -1, -1)


# ---- client -----------------------------------------------------------------


class FlightQueryClient:
    """Client for the query service (SQL over Flight)."""

    def __init__(self, addr: str, user: Optional[str] = None,
                 password: Optional[str] = None):
        self.client = fl.FlightClient(f"grpc://{addr}")
        if user is not None:
            self.client.authenticate(_BasicClientAuth(user, password or ""))

    def sql(self, sql: str, db: str = "public") -> QueryResult:
        ticket = fl.Ticket(json.dumps({"sql": sql, "db": db}).encode())
        t = self.client.do_get(ticket).read_all()
        if (t.schema.metadata or {}).get(b"affected") == b"1":
            return QueryResult.of_affected(t.column(0)[0].as_py())
        return table_to_result(t)

    def insert(self, table: str, data: pa.Table, db: str = "public") -> int:
        desc = fl.FlightDescriptor.for_path(db, table)
        writer, reader = self.client.do_put(desc, data.schema)
        writer.write_table(data)
        writer.done_writing()
        ack_buf = reader.read()
        if ack_buf is None:
            # server errored before acking — close() raises the Flight error
            writer.close()
            raise fl.FlightServerError("no ack from server")
        ack = json.loads(ack_buf.to_pybytes().decode())
        writer.close()
        return ack["affected_rows"]

    def health(self) -> bool:
        res = list(self.client.do_action(fl.Action("health", b"")))
        return json.loads(res[0].body.to_pybytes().decode())["status"] == "ok"

    def close(self):
        self.client.close()


class RemoteRegionEngine:
    """The RegionEngine surface over the Flight region service — the real
    network data plane between a frontend and its datanodes (reference:
    frontends reach regions via serialized plans + Flight streams,
    datanode/src/region_server.rs:623-660; cluster mode routes every
    region request through this client instead of in-process calls)."""

    def __init__(self, addr: str, user: Optional[str] = None,
                 password: Optional[str] = None,
                 peer: Optional[str] = None):
        self.addr = addr
        #: the peer's NODE identity (dn-N): with it, every RPC carries a
        #: (src, dst) edge the fault layer can match or partition; an
        #: addr-only client still works, it just has no edge
        self.peer = peer
        self.client = fl.FlightClient(f"grpc://{addr}")
        if user is not None:
            self.client.authenticate(_BasicClientAuth(user, password or ""))

    def _rpc(self, point: str, fn):
        """Every wire call crosses here: chaos injection point + the
        shared retry policy over transient Flight errors. Writes retried
        after a mid-stream failure are at-least-once; the LSM's
        key+timestamp LWW collapses the duplicates (append-mode tables
        trade exactness for availability, as the reference's gRPC retry
        does). The span makes the wire+retry cost visible as self-time
        under the enclosing remote_region_* span."""
        from greptimedb_tpu.utils import tracing

        with tracing.span("flight_rpc", point=point, dst=self.peer
                          or self.addr):
            def op():
                FAULTS.fire(point, addr=self.addr, side="client",
                            src=local_node(), dst=self.peer or self.addr)
                return fn()
            try:
                return retry_call(op, point=point,
                                  retryable=RETRYABLE_FLIGHT)
            except Exception as e:
                from greptimedb_tpu.fault.retry import (
                    Cancelled,
                    DeadlineExceeded,
                )
                from greptimedb_tpu.utils import deadline as dl

                if isinstance(e, (DeadlineExceeded, Cancelled)):
                    raise
                # the datanode enforcing the ticket's budget raises its
                # own typed error, but it crosses the wire as an opaque
                # FlightServerError — once OUR budget is spent, the
                # typed deadline outranks whichever wire error the race
                # produced (gRPC timeout vs server-side unwind)
                dl.check(point)
                raise

    def _merge_remote_spans(self, meta) -> None:
        """Fold the response's piggybacked datanode spans into the local
        ring, tagged with the source node (merge_scan.rs:245-259 analog:
        sub-stage metrics ride the Flight stream back). `meta` is either
        a pa.Table schema-metadata dict or a decoded JSON ack."""
        from greptimedb_tpu.utils import tracing

        if meta is None:
            return
        try:
            if isinstance(meta, dict) and b"spans" in meta:
                wire = json.loads(meta[b"spans"].decode())
                node = meta.get(b"node", b"").decode() or self.addr
            elif isinstance(meta, dict) and "spans" in meta:
                wire = meta["spans"]
                node = meta.get("node") or self.addr
            else:
                return
            tracing.merge_spans(wire, node=node)
        except (ValueError, KeyError, AttributeError):
            pass  # a mangled piggyback must never fail the query

    # -- control -------------------------------------------------------------

    def _admin(self, op: str, region_id: int, **extra) -> dict:
        body = json.dumps({"op": op, "region_id": region_id, **extra}).encode()
        point = "flight.do_get" if op in ("exists", "info") \
            else "flight.do_put"
        res = self._rpc(point, lambda: list(
            self.client.do_action(fl.Action("region_admin", body))))
        return json.loads(res[0].body.to_pybytes().decode())

    def create_region(self, region_id: int, schema) -> None:
        self._admin("create", region_id, schema=schema.to_dict())

    def open_region(self, region_id: int) -> None:
        self._admin("open", region_id)

    def region(self, region_id: int):
        """Existence probe (KeyError contract of the local engine). The
        returned proxy carries identity + remote-backed metadata; schema
        mutations go through alter_region_schema, a dedicated RPC."""
        if not self._admin("exists", region_id).get("exists"):
            raise KeyError(f"region {region_id} not found on {self.addr}")
        return _RemoteRegionProxy(region_id, self)

    def alter_region_schema(self, region_id: int, schema) -> None:
        self._admin("alter", region_id, schema=schema.to_dict())

    def flush(self, region_id: int) -> None:
        self._admin("flush", region_id)

    def compact(self, region_id: int) -> None:
        self._admin("compact", region_id)

    def chaos_reset(self) -> None:
        """Disarm the remote process's fault registry (chaos harness:
        the explorer verifies invariants chaos-free after the workload).
        region_id 0 — the op is process-scoped, not region-scoped."""
        self._admin("chaos_reset", 0)

    def handle_request(self, req) -> int:
        from greptimedb_tpu.storage.engine import RequestType

        if req.kind is RequestType.PUT:
            return self.put(req.region_id, req.batch)
        if req.kind is RequestType.DELETE:
            return self.delete(req.region_id, req.batch)
        self._admin(req.kind.value, req.region_id)
        return 0

    # -- write ---------------------------------------------------------------

    def _write(self, region_id: int, batch, op: str) -> int:
        from greptimedb_tpu.utils import tracing

        tid = tracing.current_trace_id()
        with tracing.span("remote_region_write", region=region_id,
                          op=op, addr=self.addr):
            # trace id + parent span id ride the descriptor path tail
            # (do_put has no ticket); the datanode's region_write span
            # re-parents under THIS span. Old servers ignore extras.
            path = ["__region__", str(region_id), op] + \
                ([tid, tracing.current_span_id() or ""] if tid else [])
            desc = fl.FlightDescriptor.for_path(*path)
            arrow = batch.to_arrow()

            def put_once():
                writer, reader = self.client.do_put(desc, arrow.schema)
                try:
                    writer.write_batch(arrow)
                    writer.done_writing()
                    ack_buf = reader.read()
                    if ack_buf is None:
                        raise fl.FlightServerError("no ack from region server")
                    ack = json.loads(ack_buf.to_pybytes().decode())
                    self._merge_remote_spans(ack)
                    return ack["affected_rows"]
                finally:
                    # close on EVERY path: a failed put that leaks its
                    # stream would accumulate one half-open stream per
                    # retry attempt
                    try:
                        writer.close()
                    except Exception:  # noqa: BLE001 — stream already dead
                        pass
            return self._rpc("flight.do_put", put_once)

    def put(self, region_id: int, batch) -> int:
        return self._write(region_id, batch, "put")

    def delete(self, region_id: int, batch) -> int:
        return self._write(region_id, batch, "delete")

    # -- read ----------------------------------------------------------------

    def scan(self, region_id: int, ts_range=None, projection=None,
             tag_predicates=None, seq_min=None,
             full_key=True) -> Optional[ScanData]:
        from greptimedb_tpu.utils import tracing

        spec = {"region_id": region_id}
        if not full_key:
            # the caller's table is append-mode (Region.scan); a peer
            # that predates the key sends every tag, as before
            spec["full_key"] = False
        if seq_min is not None:
            spec["seq_min"] = int(seq_min)
        if ts_range is not None:
            spec["ts_range"] = list(ts_range)
        if projection is not None:
            spec["projection"] = list(projection)
        if tag_predicates:
            from greptimedb_tpu.storage.index import (
                serialize_predicates,
                serialize_predicates_legacy,
            )
            legacy = serialize_predicates_legacy(tag_predicates)
            if legacy:  # shape old peers can parse (InSets only)
                spec["tag_predicates"] = legacy
            spec["tag_predicates_v2"] = serialize_predicates(tag_predicates)
        from greptimedb_tpu.utils import deadline as dl

        budget = dl.budget_ms()
        if budget is not None:
            # remaining budget rides the ticket so the datanode enforces
            # the deadline server-side (the frontend token can't cross
            # the process boundary)
            spec["budget_ms"] = budget
        tid = tracing.current_trace_id()
        if tid:
            # W3C-style propagation: the frontend's trace id crosses the
            # wire inside the request (merge_scan.rs:185-201 analog)
            spec["trace_id"] = tid
        with tracing.span("remote_region_scan", region=region_id,
                          addr=self.addr):
            if tid:
                # parent linkage: the datanode's region_scan span nests
                # under THIS span in the merged tree
                spec["parent_span"] = tracing.current_span_id()
            ticket = fl.Ticket(json.dumps({"region_scan": spec}).encode())
            t = self._rpc("flight.do_get", lambda: self.client.do_get(
                ticket, _call_options()).read_all())
        self._merge_remote_spans(t.schema.metadata)
        if (t.schema.metadata or {}).get(b"empty") == b"1":
            return None
        return table_to_scan(t)

    def execute_fragment(self, region_id: int, frag) -> Optional[dict]:
        """Ship a PlanFragment; receive the terminal stage's output —
        partial planes or candidate/filtered rows, distinguished by the
        response's kind metadata (reference region_server.rs:623-660 —
        substrait plan in, stream out; raw scans never cross here)."""
        from greptimedb_tpu.utils import tracing

        spec = {"region_id": region_id, "fragment": frag.to_json()}
        from greptimedb_tpu.utils import deadline as dl

        budget = dl.budget_ms()
        if budget is not None:
            spec["budget_ms"] = budget
        tid = tracing.current_trace_id()
        if tid:
            spec["trace_id"] = tid
        with tracing.span("remote_region_frag", region=region_id,
                          addr=self.addr):
            if tid:
                spec["parent_span"] = tracing.current_span_id()
            ticket = fl.Ticket(json.dumps({"region_frag": spec}).encode())
            t = self._rpc("flight.do_get", lambda: self.client.do_get(
                ticket, _call_options()).read_all())
        self._merge_remote_spans(t.schema.metadata)
        md = t.schema.metadata or {}
        if md.get(b"empty") == b"1":
            return None
        kind = md.get(b"kind")
        if kind not in (None, b"rows"):
            # a peer's answer, input from outside this process
            raise ValueError(f"unknown fragment reply kind "
                             f"{kind.decode(errors='replace')!r}")
        if kind == b"rows":
            t = t.combine_chunks()
            cols = {}
            for i, name in enumerate(t.column_names):
                col = t.column(i)
                cols[name] = col.to_numpy(zero_copy_only=False)
            return {"cols": cols}
        return table_to_partial(t)

    def rollup_probe(self, region_id: int, lo: int, hi: int) -> list:
        """Rollup-coverage probe on the region's owner (the cluster
        substitution eligibility RPC; see the server's rollup_probe
        action)."""
        body = json.dumps({"region_id": region_id, "lo": int(lo),
                           "hi": int(hi)}).encode()
        res = self._rpc("flight.do_get", lambda: list(
            self.client.do_action(fl.Action("rollup_probe", body),
                                  _call_options())))
        return json.loads(res[0].body.to_pybytes().decode())

    def scan_stream(self, region_id: int, ts_range=None, projection=None,
                    tag_predicates=None, full_key=True):
        # remote streaming scan not implemented yet: fall back to the
        # materialized wire scan (executor handles None)
        return None

    def close(self) -> None:
        self.client.close()


class _RemoteRegionProxy:
    def __init__(self, region_id: int, client: RemoteRegionEngine):
        self.region_id = region_id
        self._client = client

    def flush(self) -> None:
        self._client.flush(self.region_id)

    @property
    def data_version(self) -> int:
        return self._client._admin("info", self.region_id)["data_version"]


class RegionFlightClient:
    """Client for the region service — the distributed MergeScan transport
    (reference query/src/dist_plan/merge_scan.rs:198-259 streams each
    region over Flight and concatenates; here the reassembled ScanData
    feeds the device merge kernels)."""

    def __init__(self, addr: str, user: Optional[str] = None,
                 password: Optional[str] = None):
        self.client = fl.FlightClient(f"grpc://{addr}")
        if user is not None:
            self.client.authenticate(_BasicClientAuth(user, password or ""))

    def scan(self, region_id: int, ts_range=None, projection=None,
             tag_predicates=None) -> Optional[ScanData]:
        spec = {"region_id": region_id}
        if ts_range is not None:
            spec["ts_range"] = list(ts_range)
        if projection is not None:
            spec["projection"] = list(projection)
        if tag_predicates:
            from greptimedb_tpu.storage.index import (
                serialize_predicates,
                serialize_predicates_legacy,
            )
            legacy = serialize_predicates_legacy(tag_predicates)
            if legacy:
                spec["tag_predicates"] = legacy
            spec["tag_predicates_v2"] = serialize_predicates(tag_predicates)
        ticket = fl.Ticket(json.dumps({"region_scan": spec}).encode())
        t = self.client.do_get(ticket).read_all()
        if (t.schema.metadata or {}).get(b"empty") == b"1":
            return None
        return table_to_scan(t)

    def close(self):
        self.client.close()

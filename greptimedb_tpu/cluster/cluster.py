"""In-process multi-datanode cluster: metasrv + N datanodes + frontend.

Mirrors reference tests-integration/src/cluster.rs:66-135 (a real cluster in
one process over in-memory wiring) and the distributed deployment shape
(SURVEY.md §3.1): frontends route region requests via table-route metadata;
datanodes heartbeat RegionStats to the metasrv and obey its Instructions;
region data + WAL live on a shared store (the object-storage/remote-WAL
deployment, which is what makes failover possible).

The frontend side is `RegionRouter`: it satisfies the RegionEngine surface
the QueryEngine expects (scan/put/delete/create/open/region) but routes each
region to its owning datanode per the route table, with an invalidation-
driven cache (reference src/cache + frontend route re-fetch).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Optional

from ..catalog.catalog import Catalog, TableInfo
from ..catalog.kv import KvBackend, MemoryKv
from ..datatypes.schema import Schema
from ..fault import FAULTS, FaultError, Unavailable, is_transient
from ..meta.heartbeat import HeartbeatTask
from ..meta.instruction import Instruction, InstructionKind
from ..meta.metasrv import Metasrv, MetasrvOptions, RegionStat
from ..meta.route import RegionRoute, TableRoute
from ..partition.rule import RangePartitionRule
from ..query.engine import QueryContext, QueryEngine
from ..storage.engine import EngineConfig, RegionEngine, RegionRequest, RequestType
from ..utils.metrics import DEGRADED


#: Flight error class names the router may fix by re-resolving the
#: route. FlightServerError is included deliberately: a stale route over
#: the wire surfaces as the REMOTE engine's KeyError wrapped in it.
#: Auth errors and Arrow data errors (ArrowInvalid etc.) are excluded —
#: re-routing cannot fix them and must not mask them as Unavailable.
_RECOVERABLE_FLIGHT = frozenset({
    "FlightUnavailableError", "FlightTimedOutError",
    "FlightInternalError", "FlightServerError",
})


def _recoverable(e: BaseException, region_id: int) -> bool:
    """Errors the router may fix by re-resolving the route: stale routes
    (KeyError naming this region, from an engine/router that no longer
    owns it — a KeyError about anything else is a programming error and
    must surface), injected or self-described transient failures, and
    Flight transport errors after the client's own retries are
    exhausted."""
    if isinstance(e, KeyError) or (isinstance(e, Unavailable)
                                   and e.cause is None):
        # every ownership-contract error (engine "region N not open",
        # router "no route for region N", the typed "region N has no
        # live datanode" Unavailable) names the region with this exact
        # phrase; a KeyError about anything else (a column, a dict key)
        # does not, and a cause-carrying Unavailable is already the
        # terminal verdict of a refresh-and-retry loop
        return f"region {region_id}" in str(e)
    if isinstance(e, FaultError) or is_transient(e):
        return True
    return type(e).__module__.startswith("pyarrow") \
        and type(e).__name__ in _RECOVERABLE_FLIGHT


class Datanode:
    """One region server + its heartbeat task (datanode/src/datanode.rs:192
    + heartbeat.rs analog)."""

    def __init__(self, node_id: str, shared_dir: str, metasrv: Metasrv,
                 wire: bool = False):
        self.node_id = node_id
        # datanodes run the worker model like the reference's region
        # servers (worker.rs WorkerGroup); a small fixed pool — requests
        # arrive pre-batched from the frontend, workers add group commit
        self.engine = RegionEngine(EngineConfig(data_dir=shared_dir,
                                                write_workers=2))
        self.metasrv = metasrv
        self.heartbeat = HeartbeatTask(
            node_id, metasrv, self._region_stats, self._apply_instruction
        )
        self.alive = True
        # wire transport: serve this node's regions over Flight and give
        # the frontend a network client instead of the in-process engine
        # (reference: region requests always cross gRPC,
        # datanode/src/region_server.rs)
        self.server = None
        self.remote = None
        if wire:
            from ..servers.flight import FlightServer, RemoteRegionEngine

            self.server = FlightServer(None, port=0,
                                       region_engine=self.engine,
                                       node_id=node_id)
            self.remote = RemoteRegionEngine(f"127.0.0.1:{self.server.port}",
                                             peer=node_id)

    def data_engine(self):
        """What the frontend router talks to: the Flight client in wire
        mode, the in-process engine otherwise."""
        return self.remote if self.remote is not None else self.engine

    def _region_stats(self) -> list[RegionStat]:
        stats = []
        for rid, region in self.engine.regions.items():
            stats.append(
                RegionStat(
                    region_id=rid,
                    table=str(rid >> 32),
                    rows=region.memtable.num_rows if hasattr(region, "memtable") else 0,
                    memtable_bytes=region.memtable_bytes,
                )
            )
        return stats

    def _apply_instruction(self, inst: Instruction) -> None:
        if inst.kind is InstructionKind.OPEN_REGION:
            self.engine.open_region(inst.region_id)
        elif inst.kind is InstructionKind.CLOSE_REGION:
            self.engine.handle_request(
                RegionRequest(RequestType.CLOSE, inst.region_id)
            )
        elif inst.kind is InstructionKind.DOWNGRADE_REGION:
            pass  # writes are fenced by the router's route state
        elif inst.kind is InstructionKind.UPGRADE_REGION:
            self.engine.open_region(inst.region_id)

    def beat(self, now_ms: Optional[float] = None) -> None:
        if not self.alive:
            return
        try:
            FAULTS.fire("datanode.crash", node=self.node_id)
        except FaultError:
            self.kill()  # the chaos schedule chose this beat to die on
            return
        self.heartbeat.beat(now_ms)

    def enforce_leases(self, now_ms: Optional[float] = None) -> list[int]:
        """RegionAliveKeeper: self-close regions whose lease expired
        (alive_keeper.rs:49-112)."""
        expired = self.heartbeat.alive_keeper.expired(now_ms)
        for rid in expired:
            self.engine.handle_request(RegionRequest(RequestType.CLOSE, rid))
            self.heartbeat.alive_keeper.forget(rid)
        return expired

    def kill(self) -> None:
        """Simulate process death: stop heartbeating, drop open regions,
        stop serving the wire."""
        self.alive = False
        if self.engine.workers is not None:
            # a dead process has no writer threads; without this each
            # simulated death leaks the worker pool (and a dequeued write
            # could still land in the shared WAL)
            self.engine.workers.stop()
            self.engine.workers = None
        for rid in list(self.engine.regions):
            self.engine.regions.pop(rid, None)
        if self.server is not None:
            self.server.shutdown()
            self.server = None

    def close(self) -> None:
        if self.remote is not None:
            self.remote.close()
        if self.server is not None:
            self.server.shutdown()
        self.engine.close()


class _HedgePlane:
    """Adaptive request hedging for remote fragment reads (the
    tail-tolerance half of `[cluster]`): when a peer's response is
    slower than its own recent p99 (floored at `hedge_delay_ms`), race
    a second attempt and take the first response — a per-request
    straggler (GC pause, queue-head blocking, an injected stall) loses
    to the hedge instead of setting the query's tail. A token bucket
    caps hedges at `hedge_budget_pct` of eligible requests so a slow
    CLUSTER degrades to plain waiting instead of doubling its own load.
    Knobs ride the env (options.apply_query_env writes them) so child
    datanode processes and tests see one source of truth."""

    #: burst cap: at most this many banked hedges (bucket depth)
    _CAP = 10.0

    def __init__(self):
        self._lock = threading.Lock()
        self._lat: dict[str, deque] = {}
        self._credit = 1.0  # one immediate hedge; then pct-per-request

    @staticmethod
    def enabled() -> bool:
        return os.environ.get("GTPU_HEDGE", "") != "off"

    @staticmethod
    def floor_s() -> float:
        try:
            return float(os.environ.get("GTPU_HEDGE_DELAY_MS", "")
                         or 30.0) / 1000.0
        except ValueError:
            return 0.03

    @staticmethod
    def budget_pct() -> float:
        try:
            return float(os.environ.get("GTPU_HEDGE_BUDGET_PCT", "")
                         or 5.0)
        except ValueError:
            return 5.0

    def delay_s(self, peer: str) -> float:
        """When to fire the hedge: the peer's recent p99, floored — a
        cold ring (under 8 samples) has no p99 worth trusting."""
        floor = self.floor_s()
        with self._lock:
            ring = self._lat.get(peer)
            if not ring or len(ring) < 8:
                return floor
            srt = sorted(ring)
            p99 = srt[min(len(srt) - 1, int(len(srt) * 0.99))]
        return max(floor, p99)

    def record(self, peer: str, elapsed_s: float) -> None:
        with self._lock:
            self._lat.setdefault(peer, deque(maxlen=128)).append(elapsed_s)

    def accrue(self) -> None:
        """One eligible request = pct/100 of a hedge earned."""
        with self._lock:
            self._credit = min(self._CAP,
                               self._credit + self.budget_pct() / 100.0)

    def try_fire(self) -> bool:
        with self._lock:
            if self._credit >= 1.0:
                self._credit -= 1.0
                return True
            return False


class RegionRouter:
    """Frontend-side region request routing over table routes."""

    def __init__(self, metasrv: Metasrv, datanodes: dict[str, Datanode]):
        self.metasrv = metasrv
        self.datanodes = datanodes
        self._region_node: dict[int, str] = {}
        self._agg_executors: dict[int, object] = {}  # per-engine pushdown
        # rollup_probe TTL cache: dashboards re-asking the same window
        # within the coverage-state TTL skip the per-region RPC fan-out
        self._rollup_probe_cache: dict[tuple, tuple] = {}
        self._hedge = _HedgePlane()
        self._lock = threading.Lock()
        metasrv.subscribe_invalidation(self._on_invalidate)

    def _on_invalidate(self, table: str) -> None:
        with self._lock:
            self._region_node.clear()
            # pushdown executors pin their engines (and device caches):
            # drop them with the routes so failed-over engines can free
            self._agg_executors.clear()
            self._rollup_probe_cache.clear()

    def _refresh(self) -> None:
        with self._lock:
            self._region_node.clear()
            for route in self.metasrv.routes.all():
                for rr in route.regions:
                    if rr.leader_node is not None:
                        self._region_node[rr.region_id] = rr.leader_node

    @staticmethod
    def _route_rid(region_id: int) -> int:
        """Routing identity for a region id: rollup COMPANION regions
        (raw_rid + ROLLUP_RID_FLAG + slot<<20, maintenance/rollup.py)
        are created by the owning datanode's maintenance plane and never
        get their own route entry — they live wherever their raw region
        lives, so route lookups strip the companion bits."""
        from greptimedb_tpu.maintenance.rollup import ROLLUP_RID_FLAG

        if region_id & ROLLUP_RID_FLAG:
            return (region_id >> 32 << 32) | (region_id & ((1 << 20) - 1))
        return region_id

    def _engine_for(self, region_id: int) -> RegionEngine:
        region_id = self._route_rid(region_id)
        node = self._region_node.get(region_id)
        if node is None:
            self._refresh()
            node = self._region_node.get(region_id)
        if node is None:
            raise KeyError(f"no route for region {region_id}")
        dn = self.datanodes[node]
        if not dn.alive:
            # stale route to a dead node; force a re-fetch
            self._refresh()
            node = self._region_node.get(region_id)
            dn = self.datanodes[node] if node else None
            if dn is None or not dn.alive:
                # transient by contract: the leader died and failover
                # has not landed yet — typed so clients retry, never a
                # bare KeyError escaping the routing table
                raise Unavailable(
                    f"region {region_id} has no live datanode "
                    f"(failover pending)")
        return dn.data_engine()

    # --- RegionEngine surface used by QueryEngine ---
    def region(self, region_id: int):
        return self._engine_for(region_id).region(region_id)

    def open_region(self, region_id: int) -> None:
        self._engine_for(region_id).open_region(region_id)

    def select_node(self) -> str:
        """Datanode placement via the metasrv selector (selector/ role)."""
        node = self.metasrv.selector.select(
            self.metasrv.alive_nodes() or sorted(self.datanodes),
            self.metasrv.node_stats(),
        )
        return node if node is not None else sorted(self.datanodes)[0]

    def create_region(self, region_id: int, schema: Schema) -> None:
        """Placement: pick a datanode via the metasrv selector, create the
        region there, and record the route (the CreateTable DDL procedure's
        region-allocation step, common/meta/src/ddl/create_table.rs analog).

        NOT idempotent across calls: the stateful selector may pick a
        different node each time. Journaled DDL must pin the node first
        (select_node) and call create_region_on — re-running THAT is a
        datanode-level no-op."""
        self.create_region_on(self.select_node(), region_id, schema)

    def create_region_on(self, node: str, region_id: int,
                         schema: Schema) -> None:
        self.datanodes[node].data_engine().create_region(region_id, schema)
        table_key = str(region_id >> 32)
        route = self.metasrv.routes.get(table_key)
        if route is None:
            route = TableRoute(table=table_key, regions=[])
            self.metasrv.routes.put_new(route)
            route = self.metasrv.routes.get(table_key)
        route.regions = [r for r in route.regions if r.region_id != region_id]
        route.regions.append(RegionRoute(region_id=region_id, leader_node=node))
        self.metasrv.routes.update(route)
        with self._lock:
            self._region_node[region_id] = node

    def put(self, region_id: int, batch) -> int:
        return self._engine_for(region_id).put(region_id, batch)

    def delete(self, region_id: int, batch) -> int:
        return self._engine_for(region_id).delete(region_id, batch)

    def flush(self, region_id: int) -> None:
        self._engine_for(region_id).flush(region_id)

    def compact(self, region_id: int) -> None:
        self._engine_for(region_id).compact(region_id)

    def _with_failover(self, region_id: int, op):
        """Graceful degradation for the read path: when the engine's own
        retries are exhausted (or the route is stale), re-resolve the
        route — picking up any failover that moved the region — and try
        once on the new owner; only then surface a typed `Unavailable`
        instead of a transport stack trace."""
        try:
            return op(self._engine_for(region_id))
        except Exception as e:  # noqa: BLE001 — predicate filters below
            if not _recoverable(e, region_id):
                raise
            DEGRADED.inc(point="router.scan")
            with self._lock:
                self._region_node.pop(self._route_rid(region_id), None)
            self._refresh()
            try:
                return op(self._engine_for(region_id))
            except Exception as e2:  # noqa: BLE001
                if not _recoverable(e2, region_id):
                    raise
                raise Unavailable(
                    f"region {region_id} unavailable after retries "
                    "and route refresh", e2) from e2

    def scan(self, region_id: int, ts_range=None, projection=None,
             tag_predicates=None, seq_min=None, full_key=True):
        def op(eng):
            call = lambda e: e.scan(region_id, ts_range, projection,  # noqa: E731
                                    tag_predicates, seq_min=seq_min,
                                    full_key=full_key)
            if hasattr(eng, "execute_fragment") and _HedgePlane.enabled():
                # wire-mode region read: the same hedge plane as
                # fragment pushdown — a straggling scan races a backup
                return self._hedged_call(region_id, eng, call)
            return call(eng)
        return self._with_failover(region_id, op)

    def scan_stream(self, region_id: int, ts_range=None, projection=None,
                    tag_predicates=None, full_key=True):
        # degradation covers stream CONSTRUCTION only: chunks read
        # lazily after return cannot be replayed on a refreshed route
        # without duplicating data (they lean on the objectstore seam's
        # own retries instead)
        return self._with_failover(
            region_id,
            lambda eng: eng.scan_stream(region_id, ts_range, projection,
                                        tag_predicates, full_key=full_key))

    def _local_executor_for(self, eng):
        """Per-engine pushdown executor cache (holds device caches; the
        invalidation hook drops them with the routes)."""
        from greptimedb_tpu.query.physical import PhysicalExecutor

        with self._lock:
            ex = self._agg_executors.get(id(eng))
            if ex is None:
                ex = PhysicalExecutor(eng)
                self._agg_executors[id(eng)] = ex
        return ex

    def execute_fragment(self, region_id: int, frag):
        """Plan-fragment pushdown: run the region-side stage pipeline ON
        the node that owns the region (over Flight in wire mode), so
        only the terminal stage's output — partial planes, top-k
        candidates, or filtered rows — returns to the frontend
        (reference dist_plan Partial/Final split, analyzer.rs:35).
        Wire-mode reads hedge (see _HedgePlane): an attempt slower than
        the peer's adaptive delay races a second one, first response
        wins, the loser's token is cancelled."""
        def op(eng):
            if hasattr(eng, "execute_fragment"):  # RemoteRegionEngine: wire
                call = lambda e: e.execute_fragment(region_id, frag)  # noqa: E731
                if _HedgePlane.enabled():
                    return self._hedged_call(region_id, eng, call)
                return call(eng)
            # in-process datanode: same computation, no serialization
            from greptimedb_tpu.query.dist_agg import execute_region_fragment

            return execute_region_fragment(self._local_executor_for(eng),
                                           region_id, frag)
        return self._with_failover(region_id, op)

    def _hedged_call(self, region_id: int, eng, call):
        """First-response-wins hedged dispatch of `call(eng)`.

        Both attempts run under CHILD tokens carrying the outer
        statement's remaining budget — never the outer token itself, so
        cancelling the loser cannot cancel the query. The winner's
        latency feeds the peer's p99 ring; the loser's cancel unwinds
        its retry loop locally and its server-side work via the
        budget the ticket carried. The waiter itself stays on the
        OUTER token: a KILL or deadline during the race unwinds typed
        here and the finally cancels both attempts."""
        from greptimedb_tpu.utils import deadline as dl
        from greptimedb_tpu.utils import tracing
        from greptimedb_tpu.utils.metrics import HEDGE_EVENTS

        peer = self._region_node.get(self._route_rid(region_id)) or "?"
        self._hedge.accrue()
        outer = dl.current()
        budget = dl.budget_ms()
        lock = threading.Lock()
        done = threading.Event()
        winner: list = [None]  # (tag, ok, value, elapsed_s)
        tokens: dict[str, dl.CancelToken] = {}
        # capture the caller's trace context HERE: attempts run on their
        # own threads, and the remote_region_* spans they open must stay
        # attached to the statement's span tree
        run = tracing.propagate(lambda: call(eng))

        def attempt(tag):
            tok = tokens[tag]
            t0 = time.monotonic()
            with dl.activate(tok):
                try:
                    ok, val = True, run()
                except BaseException as e:  # noqa: BLE001 — relayed to waiter
                    ok, val = False, e
            with lock:
                if winner[0] is None:
                    winner[0] = (tag, ok, val, time.monotonic() - t0)
                    done.set()

        def spawn(tag):
            tokens[tag] = dl.CancelToken(timeout_ms=budget)
            threading.Thread(target=attempt, args=(tag,),
                             name=f"gtpu-hedge-{tag}", daemon=True).start()

        try:
            spawn("primary")
            delay = self._hedge.delay_s(peer)
            if outer is not None:
                # a hedge fired after the deadline helps nobody
                delay = outer.clip(delay)
            if not done.wait(delay):
                if self._hedge.try_fire():
                    HEDGE_EVENTS.inc(event="fired")
                    spawn("hedge")
                else:
                    HEDGE_EVENTS.inc(event="budget_denied")
            while not dl.wait_event(done, 30.0, where="hedged fragment"):
                pass
        finally:
            with lock:
                won = winner[0][0] if winner[0] is not None else None
            for tag, tok in tokens.items():
                if tag != won:
                    tok.cancel("hedge loser", kind="cancelled",
                               count=False)
        tag, ok, val, elapsed = winner[0]
        self._hedge.record(peer, elapsed)
        if "hedge" in tokens:
            HEDGE_EVENTS.inc(event="won" if tag == "hedge" else "lost")
        if not ok:
            raise val
        return val

    #: rollup_probe answers stay valid for about as long as the
    #: datanode-side coverage-state cache (maintenance/rollup.py)
    _ROLLUP_PROBE_TTL_S = 2.0

    def rollup_probe(self, region_id: int, lo: int, hi: int) -> list:
        """Ask the region's owner which rollup rules fully cover
        [lo, hi) on it (maintenance/rollup.probe_region_rollups) — the
        eligibility half of cluster-mode rollup substitution. Only
        NEGATIVE answers are cached (tables with no usable rollup would
        otherwise fan an RPC per query forever): a positive answer must
        stay live, because the datanode's late-data check is what keeps
        substituted aggregates exact after an out-of-order write."""
        import time as _time

        key = (region_id, int(lo), int(hi))
        now = _time.monotonic()
        with self._lock:
            hit = self._rollup_probe_cache.get(key)
            if hit is not None and hit[0] > now:
                return hit[1]
            if len(self._rollup_probe_cache) > 4096:
                self._rollup_probe_cache.clear()

        def op(eng):
            if hasattr(eng, "rollup_probe"):  # RemoteRegionEngine: wire
                return eng.rollup_probe(region_id, lo, hi)
            from greptimedb_tpu.maintenance.rollup import (
                probe_region_rollups,
            )

            return probe_region_rollups(eng, region_id, int(lo), int(hi))

        out = self._with_failover(region_id, op)
        if not out:
            with self._lock:
                self._rollup_probe_cache[key] = (
                    now + self._ROLLUP_PROBE_TTL_S, out)
        return out

    def alter_region_schema(self, region_id: int, schema) -> None:
        self._engine_for(region_id).alter_region_schema(region_id, schema)

    def drop_region(self, region_id: int) -> None:
        """Drop a region wherever it lives and forget its route (DDL
        drop/rollback step, common/meta/src/ddl/drop_table.rs analog).

        Route cleanup needs no live engine and must happen even when the
        owning datanode is dead — otherwise a later failover tick would
        resurrect the dropped table's region from the stale route."""
        from greptimedb_tpu.storage.engine import RegionRequest, RequestType

        try:
            eng = self._engine_for(region_id)
        except (KeyError, Unavailable):
            eng = None  # no route, or no live datanode: metadata-only drop
        if eng is not None:
            try:
                eng.region(region_id)
            except KeyError:
                try:
                    eng.open_region(region_id)
                except Exception:  # noqa: BLE001 — never created on disk
                    pass
            try:
                eng.handle_request(RegionRequest(RequestType.DROP, region_id))
            except KeyError:
                pass
        table_key = str(region_id >> 32)
        route = self.metasrv.routes.get(table_key)
        if route is not None:
            route.regions = [r for r in route.regions
                             if r.region_id != region_id]
            self.metasrv.routes.update(route)
        with self._lock:
            self._region_node.pop(region_id, None)

    def handle_request(self, req: RegionRequest) -> int:
        return self._engine_for(req.region_id).handle_request(req)


class Cluster:
    """N datanodes + metasrv + a distributed frontend QueryEngine."""

    def __init__(
        self,
        data_dir: str,
        num_datanodes: int = 3,
        kv: Optional[KvBackend] = None,
        opts: Optional[MetasrvOptions] = None,
        wire_transport: bool = False,
    ):
        self.kv = kv or MemoryKv()
        self.metasrv = Metasrv(self.kv, opts)
        self.datanodes: dict[str, Datanode] = {}
        shared = os.path.join(data_dir, "shared")
        for i in range(num_datanodes):
            node_id = f"dn-{i}"
            self.datanodes[node_id] = Datanode(node_id, shared, self.metasrv,
                                               wire=wire_transport)
        # topology for the fault layer: per-edge specs naming a node
        # outside this set are typos and fail at arm time. The
        # coordinator is registered under its REAL node id (the identity
        # heartbeat/kv edges carry), not a role alias that never matches
        FAULTS.register_nodes([*self.datanodes, "frontend",
                               self.metasrv.node_id])
        self.router = RegionRouter(self.metasrv, self.datanodes)
        self.catalog = Catalog(self.kv)
        # distributed DDL runs as journaled procedures on the metasrv's
        # persistent procedure manager (DdlManager, ddl_manager.rs analog);
        # QueryEngine delegates when the engine exposes one
        from greptimedb_tpu.meta.ddl import DdlManager

        self.router.ddl_manager = DdlManager(self.metasrv.procedures,
                                             self.router, self.catalog)
        self.frontend = QueryEngine(self.catalog, self.router)

    def beat_all(self, now_ms: Optional[float] = None) -> None:
        for dn in self.datanodes.values():
            dn.beat(now_ms)

    def tick(self, now_ms: Optional[float] = None) -> list[str]:
        return self.metasrv.tick(now_ms)

    def sql(self, sql: str, db: str = "public"):
        return self.frontend.execute_one(sql, QueryContext(db=db))

    def create_partitioned_table(
        self,
        sql_create: str,
        rule: RangePartitionRule,
        db: str = "public",
    ) -> TableInfo:
        """CREATE TABLE with N partitioned regions placed across datanodes
        (PARTITION ON COLUMNS clause analog)."""
        from ..sql import parse_sql

        stmt = parse_sql(sql_create)[0]
        ctx = QueryContext(db=db)
        self.frontend._create_table_partitioned(stmt, ctx, rule)
        return self.catalog.table(db, stmt.name)

    def close(self) -> None:
        for dn in self.datanodes.values():
            dn.close()

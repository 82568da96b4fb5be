"""Trace context + hierarchical spans (mirrors reference common/telemetry
tracing: `TracingContext::to_w3c` rides region requests across process
hops, query/src/dist_plan/merge_scan.rs:185-201, re-attached server-side
at servers/src/grpc/region_server.rs:74).

A request's trace id lives in a contextvar; spans carry a `span_id` and
a `parent_id` maintained by a contextvar parent stack inside `span()`,
so EXPLAIN ANALYZE / TQL ANALYZE and `/v1/traces/<id>` render true
nested trees with per-span self-time. The wire protocols speak W3C
trace context: HTTP accepts and emits a `traceparent` header,
MySQL/Postgres accept one in a leading SQL comment, and the Flight
piggyback ships parent linkage both ways — a datanode's `region_scan`
span re-parents under the frontend span that issued the RPC, so one
tree covers every process the query touched.

The span ring is indexed by trace id (bounded dict-of-lists evicted
with the ring) so `spans_for`/`merge_spans` on a busy frontend never
walk thousands of foreign spans. Completed spans also feed the OTLP
exporter (utils/otlp_trace.py) and the per-query resource ledger
(utils/ledger.py) when either is active. `GTPU_TRACING=off` turns span
recording (and the ledger) into a no-op for A/B overhead runs.

Every span reads TWO clocks: `time.perf_counter()` for its duration and
`time.thread_time_ns()` for `cpu_ms`, the CPU time of the thread that
ran it. `duration_ms - cpu_ms` is the time that thread was off the CPU:
waiting for the interpreter lock, for the device, for a socket or a
disk, or for another lock. It is NOT only the interpreter lock, and CPU
time spent with the lock RELEASED (arrow decode, numpy, an XLA:CPU
compile) counts as CPU: `cpu_ms` bounds the time a span held the lock
from above, and `duration_ms - cpu_ms` the time it waited for it from
above. The stage spans add their CPU seconds to
query_stage_cpu_seconds_total{stage}; utils/lock_probe.py samples what
re-taking the lock costs.

Logs join the same id: `TraceIdFilter` stamps every log record with the
current trace id (`trace_id=<id>`), so logs, metrics, and spans
correlate on one key — and histogram exemplars (utils/metrics.py) close
the metrics→trace direction.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os
import re
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import sys

from greptimedb_tpu.utils import ledger
from greptimedb_tpu.utils.metrics import (
    INGEST_REQUEST_CPU_SECONDS,
    STAGE_CPU_SECONDS,
    STAGE_SECONDS,
)

_current: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "gtpu_trace_id", default=None)

#: innermost open span's id — the parent of the next span opened in this
#: context (and the span id a traceparent/Flight request propagates)
_parent: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "gtpu_span_parent", default=None)

#: request-scoped span sink (see collect_spans): lets a server handler
#: capture exactly the spans ITS request produced, concurrency-safe,
#: without diffing the shared ring
_collector: contextvars.ContextVar[Optional[list]] = contextvars.ContextVar(
    "gtpu_span_collector", default=None)

#: the flat serving stages (PERF.md section 3): every instant of a served
#: request belongs to at most one of them, the rest is `other`
STAGES = ("parse", "plan", "fast_bind", "admission_wait", "scan",
          "host_agg", "upload", "device", "readback", "assemble",
          "encode", "send")

#: innermost open stage of this context (None = none open); a thread
#: that works FOR a request beside its own thread (warm-up, scan pool)
#: carries _BACKGROUND: its stages are plain `bg:` spans that count
#: nowhere; the thread's CPU counts under stage="background" (propagate)
_stage: contextvars.ContextVar = contextvars.ContextVar(
    "gtpu_stage", default=None)
_BACKGROUND = object()
#: this context runs work no request waits for (device warm-up)
_warmup: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "gtpu_warmup", default=False)

_RING_CAP = 4096
_SPANS: deque = deque()
#: trace_id -> spans, evicted in lockstep with the ring: spans_for is
#: one dict lookup instead of an O(ring) scan over foreign spans
_BY_TRACE: dict[str, list] = {}
_ring_lock = threading.Lock()

#: OTLP exporter hook — otlp_trace.configure() installs the live
#: exporter here (attribute handoff, no import cycle); None = disabled
_exporter = None


def enabled() -> bool:
    """Span recording master switch (GTPU_TRACING). The single env
    parse lives in ledger.enabled() — tracing imports ledger, never the
    other way — so the two halves of the observability plane can never
    drift apart on what "off" means. Trace-ID minting/propagation stays
    on either way — log correlation is too cheap to gate."""
    return ledger.enabled()


@dataclass
class Span:
    trace_id: Optional[str]
    name: str
    duration_ms: float
    started_at: float
    attrs: dict = field(default_factory=dict)
    #: source process for piggybacked remote spans (None = this process)
    node: Optional[str] = None
    #: 16-hex span identity + parent linkage (None = a root span)
    span_id: str = ""
    parent_id: Optional[str] = None
    #: one segment of a flat serving stage (see `stage`)
    stage: bool = False
    #: CPU time of the thread that ran the span (the module docstring
    #: says what `duration_ms - cpu_ms` is and is not); 0 for a span
    #: merged from a peer that predates the second clock
    cpu_ms: float = 0.0


def new_trace_id() -> str:
    # os.urandom(8).hex() is ~3x cheaper than uuid4 and ids are minted
    # per request AND per span — this is hot-path cost (the <3%
    # overhead budget)
    return new_span_id()


_NOT_A_NUMBER = frozenset("abcdf")


def new_span_id() -> str:
    # the profiler reads an annotation's stat that parses as a number AS
    # one ("123e456789012345" comes back as inf), so an id holds a hex
    # letter no float literal has; about one draw in a thousand repeats
    while True:
        sid = os.urandom(8).hex()
        if not _NOT_A_NUMBER.isdisjoint(sid):
            return sid


def set_trace(trace_id: Optional[str] = None) -> str:
    """Install (or adopt) a trace id for the current context."""
    tid = trace_id or new_trace_id()
    _current.set(tid)
    return tid


def current_trace_id() -> Optional[str]:
    return _current.get()


def current_span_id() -> Optional[str]:
    """The innermost open span's id (what an outgoing RPC propagates as
    the remote side's parent)."""
    return _parent.get()


def restore_trace(trace_id: Optional[str]) -> None:
    """Put back a previously-saved id verbatim (None clears — unlike
    set_trace, which would mint a fresh id)."""
    _current.set(trace_id)


def _record(span: Span) -> None:
    sink = _collector.get()
    if sink is not None:
        sink.append(span)
    with _ring_lock:
        _SPANS.append(span)
        if span.trace_id:
            _BY_TRACE.setdefault(span.trace_id, []).append(span)
        while len(_SPANS) > _RING_CAP:
            old = _SPANS.popleft()
            if old.trace_id:
                lst = _BY_TRACE.get(old.trace_id)
                if lst is not None:
                    try:
                        lst.remove(old)
                    except ValueError:
                        pass
                    if not lst:
                        del _BY_TRACE[old.trace_id]
    led = ledger.active()
    if led is not None:
        led.note_span(span)
    exp = _exporter
    # merged remote copies (node set) are NOT re-exported: the peer that
    # recorded them exports its own spans under the same ids — the
    # frontend re-exporting would duplicate every datanode span at the
    # collector (head sampling decides identically on both sides)
    if exp is not None and span.node is None:
        exp.on_span(span)


def _annotation(name: str, trace_id, span_id, stats: dict):
    """The profiler's clock: when jax is already loaded in this process
    the span is also a `jax.profiler.TraceAnnotation`, so a profiler
    session with the host tracer on holds it on the device trace's
    timeline, with `stats` beside its ids. Never imports jax (a
    jax-free process stays jax-free); outside a session the annotation
    costs one flag test."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        ann = jax.profiler.TraceAnnotation(
            name, trace_id=trace_id or "", span_id=span_id, **stats)
        ann.__enter__()
        return ann
    except Exception:  # noqa: BLE001 — a half-imported jax must not fail a span
        return None


_NO_STATS: dict = {}


class _Span:
    """One timed span nested under the innermost open one; the context
    manager `span()` returns. `name` may be changed before exit (the
    compile listener learns only at the end whether the persistent
    cache served the executable). `on_close(duration_ms, cpu_ms, attrs)`
    runs before the span is recorded. `stats` are the attributes, known
    when the span opens, that its TraceAnnotation carries."""

    __slots__ = ("name", "attrs", "on_close", "stage", "stats", "_on",
                 "_sid", "_parent_id", "_token", "_t0", "_c0", "_started",
                 "_ann")

    def __init__(self, name: str, attrs: dict, on_close=None,
                 stage: bool = False, stats: dict = _NO_STATS):
        self.name = name
        self.attrs = attrs
        self.on_close = on_close
        self.stage = stage
        self.stats = stats
        self._on = False

    def __enter__(self) -> dict:
        if not enabled():
            return self.attrs
        self._on = True
        self._sid = new_span_id()
        self._parent_id = _parent.get()
        self._token = _parent.set(self._sid)
        self._ann = _annotation(self.name, _current.get(), self._sid,
                                self.stats)
        self._started = time.time()
        # the wall interval encloses the CPU interval: cpu_ms <= dur_ms
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time_ns()
        return self.attrs

    def __exit__(self, *exc) -> bool:
        if self._on:
            cpu_ms = (time.thread_time_ns() - self._c0) / 1e6
            dur_ms = (time.perf_counter() - self._t0) * 1000.0
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            _parent.reset(self._token)
            if self.on_close is not None:
                self.on_close(dur_ms, cpu_ms, self.attrs)
            _record(Span(_current.get(), self.name, dur_ms, self._started,
                         self.attrs, span_id=self._sid,
                         parent_id=self._parent_id, stage=self.stage,
                         cpu_ms=cpu_ms))
        return False


def span(name: str, **attrs) -> _Span:
    """Record a timed span nested under the innermost open one. Yields
    the (mutable) attrs dict so the body can attach result stats it only
    knows at the end (rows, bytes, pruning counts) — they land on the
    recorded span."""
    return _Span(name, attrs)


def annotated_span(name: str, **attrs) -> _Span:
    """A `span` whose attributes, all known as it opens, are also stats
    of its TraceAnnotation: a reader of the `.xplane.pb` sees them on
    the event (a `compile` span's `fn` and `thread`). A value that reads
    as a number comes back from the profiler as one."""
    return _Span(name, attrs, stats=dict(attrs))


def _observe_as(label: str):
    """An `on_close` that observes the span into query_stage_seconds and
    adds its thread's CPU to query_stage_cpu_seconds_total."""
    def observe(ms: float, cpu_ms: float, _attrs: dict) -> None:
        STAGE_SECONDS.observe(ms / 1000.0, stage=label)
        STAGE_CPU_SECONDS.inc(cpu_ms / 1000.0, stage=label)
    return observe


class _Stage:
    """One flat serving stage. Stages never nest: opening one inside
    another ENDS the outer stage's current segment and starts a new
    segment of it when the inner stage closes, so every segment is a
    direct child of the enclosing plain span (statement or request
    root), the segments of one request never overlap, and their sum
    plus `other` is the root's duration — on both clocks: a stage
    opened inside another takes its CPU out of the outer's as it takes
    its wall time. Each segment is a span that observes its duration
    into query_stage_seconds{stage} and adds its CPU to
    query_stage_cpu_seconds_total{stage} as it closes, and feeds the
    ledger (`<stage>_ms`, `stages_ms`, `<stage>_cpu_ms`,
    `stages_cpu_ms`) through the span ring."""

    __slots__ = ("name", "attrs", "_outer", "_seg")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> dict:
        self._outer = _stage.get()
        if self._outer is not None:
            self._outer._pause()
        _stage.set(self)
        self._resume()
        return self.attrs

    def __exit__(self, *exc) -> bool:
        self._seg.__exit__(None, None, None)
        _stage.set(self._outer)
        if self._outer is not None:
            self._outer._resume()
        return False

    def _resume(self) -> None:
        self._seg = _Span(self.name, self.attrs, _observe_as(self.name),
                          stage=True)
        self._seg.__enter__()

    def _pause(self) -> None:
        # an interrupted segment keeps the attrs it had: the body may
        # still write to the dict, and a recorded span is never mutated
        self._seg.attrs = dict(self.attrs)
        self._seg.__exit__(None, None, None)


def stage(name: str, **attrs):
    """Open the flat serving stage `name` (one of STAGES) on the request
    thread. With GTPU_TRACING=off it is a span that records nothing; on
    a thread that works beside the request's own (`propagate`) it is a
    plain span `bg:<name>`: its wall time adds nothing to the request's
    latency and counts nowhere (its thread's CPU does: `propagate`)."""
    if name not in STAGES:
        # the histogram's label set and PERF.md's vocabulary are one list
        raise ValueError(f"unknown serving stage {name!r}")
    if _stage.get() is _BACKGROUND:
        return _Span("bg:" + name, dict(attrs, background=True))
    if not enabled():
        return _Span(name, attrs)
    return _Stage(name, attrs)


def note_stage(**attrs) -> None:
    """Attach attributes to the serving stage open on this thread, if
    one is: what the work inside learns about itself (which program a
    dispatch ran) lands on the stage's span."""
    st = _stage.get()
    if isinstance(st, _Stage):
        st.attrs.update(attrs)


def enclosing_stage(name: str, **attrs) -> _Span:
    """A label of query_stage_seconds that ENCLOSES flat stages
    (`execute`, `fast_execute`, `request`): a plain span whose whole
    duration is observed; it is not part of the flat sum."""
    return _Span(name, attrs, _observe_as(name))


def in_warmup() -> bool:
    """Whether this context runs work that no request waits for."""
    return _warmup.get()


#: a request that ran a statement entered one of these (the slow lane
#: and PromQL parse, the fast lane binds): only such a root observes
#: `other` and `request`, so writes and debug routes stay out of the
#: query stage histogram
_QUERY_MARKS = ("parse_ms", "fast_bind_ms")
#: the line-protocol door marks its request (servers/influx.py
#: `write_lines`): its root observes ingest_request_cpu_seconds
_INGEST_MARK = "ingest_requests"


@contextlib.contextmanager
def request_span(name: str, traceparent: Optional[str] = None, **attrs):
    """Wire-ingress scaffold: adopt the caller's W3C trace context (or
    mint a fresh trace), open the request's root span, and attach the
    resource ledger — then restore the connection thread's previous
    context so keep-alive reuse can't leak one request's trace into the
    next. Every protocol front door (HTTP, MySQL, Postgres, Flight SQL)
    enters through here; the span_coverage lint checker enforces it.
    When the root closes it observes `request` (its duration) and
    `other` (its duration minus the flat stages the ledger summed), on
    both clocks; the root of a line-protocol write observes its CPU
    seconds into ingest_request_cpu_seconds instead."""
    parsed = parse_traceparent(traceparent) if traceparent else None
    tid, remote_parent = parsed if parsed else (new_trace_id(), None)
    tok_tid = _current.set(tid)
    tok_par = _parent.set(remote_parent)
    tok_stage = _stage.set(None)
    try:
        with ledger.attach() as led:
            led0 = led.snapshot() if led is not None else {}

            def close(dur_ms: float, cpu_ms: float, a: dict) -> None:
                # stamp BEFORE the span is recorded (and handed to the
                # OTLP exporter): a later mutation would race the export
                # serializer and leave the exported copy ledger-less
                if led is None:
                    return
                snap = led.snapshot()
                if snap:
                    a["ledger"] = ledger.format_dict(snap)

                def since(key: str) -> float:
                    return snap.get(key, 0.0) - led0.get(key, 0.0)

                if any(since(k) > 0.0 for k in _QUERY_MARKS):
                    other = max(dur_ms - since("stages_ms"), 0.0)
                    other_cpu = max(cpu_ms - since("stages_cpu_ms"), 0.0)
                    a["other_ms"] = round(other, 3)
                    a["other_cpu_ms"] = round(other_cpu, 3)
                    STAGE_SECONDS.observe(other / 1000.0, stage="other")
                    STAGE_CPU_SECONDS.inc(other_cpu / 1000.0, stage="other")
                    STAGE_SECONDS.observe(dur_ms / 1000.0, stage="request")
                    STAGE_CPU_SECONDS.inc(cpu_ms / 1000.0, stage="request")
                if since(_INGEST_MARK) > 0.0:
                    INGEST_REQUEST_CPU_SECONDS.observe(cpu_ms / 1000.0)

            with _Span(name, attrs, on_close=close) as a:
                yield a
    finally:
        _stage.reset(tok_stage)
        _parent.reset(tok_par)
        _current.reset(tok_tid)


@contextlib.contextmanager
def adopt_remote(trace_id: Optional[str], parent_id: Optional[str] = None):
    """Server side of a cross-process hop (region_server.rs:74 analog):
    adopt the caller's trace AND parent span so spans recorded inside
    re-parent under the frontend span that issued the RPC. Restores the
    worker thread's previous context on exit."""
    tok_tid = _current.set(trace_id or _current.get())
    tok_par = _parent.set(parent_id)
    try:
        yield
    finally:
        _parent.reset(tok_par)
        _current.reset(tok_tid)


@contextlib.contextmanager
def collect_spans():
    """Yield a list that receives every span recorded in this context
    (on top of the shared ring). Used by the Flight region service to
    piggyback exactly ITS request's spans on the response, and by the
    slow-query log to capture a statement's per-stage breakdown. Nesting
    installs the innermost sink only — the outer one resumes on exit."""
    sink: list[Span] = []
    token = _collector.set(sink)
    try:
        yield sink
    finally:
        _collector.reset(token)


def propagate(fn, background: bool = False):
    """Carry the caller's trace id, open-span parent, span sink, AND
    resource ledger across a thread-pool boundary (contextvars don't
    cross threads): the returned wrapper re-installs all four around
    each invocation. The sink is appended from worker threads —
    list.append is atomic, so concurrent region RPCs interleave
    safely; the ledger takes its own lock. Serving stages belong to the
    request thread alone: the wrapper marks its thread as background,
    so a stage opened there is a plain span. `background=True` says the
    request does not WAIT for this work either (the device warm-up): a
    compile there is labelled thread="warmup". A thread beside the
    request's adds nothing to its latency, but its CPU takes the
    interpreter lock the request threads take: the wrapper adds the
    thread's CPU seconds over the call, inside a `bg:` span or not, to
    query_stage_cpu_seconds_total{stage="background"} (a wrapper run
    inline on the thread that made it is that thread's own CPU, and
    counts nothing here)."""
    tid = _current.get()
    parent = _parent.get()
    sink = _collector.get()
    led = ledger.active()
    maker = threading.get_ident()

    def wrapper(*args, **kwargs):
        t1 = _current.set(tid)
        t2 = _collector.set(sink)
        t3 = _parent.set(parent)
        t4 = ledger._current.set(led)
        t5 = _stage.set(_BACKGROUND)
        t6 = _warmup.set(background or _warmup.get())
        beside = enabled() and threading.get_ident() != maker
        c0 = time.thread_time_ns() if beside else 0
        try:
            return fn(*args, **kwargs)
        finally:
            if beside:
                STAGE_CPU_SECONDS.inc(
                    (time.thread_time_ns() - c0) / 1e9, stage="background")
            _warmup.reset(t6)
            _stage.reset(t5)
            ledger._current.reset(t4)
            _parent.reset(t3)
            _collector.reset(t2)
            _current.reset(t1)
    return wrapper


# ---- W3C trace context ------------------------------------------------------

_TRACEPARENT_RE = re.compile(
    r"^(?P<ver>[0-9a-f]{2})-(?P<tid>[0-9a-f]{32})-"
    r"(?P<sid>[0-9a-f]{16})-(?P<flags>[0-9a-f]{2})$")

#: leading-comment carrier for header-less wire protocols (MySQL/
#: Postgres text): /* traceparent='00-...-...-01' */ SELECT ...
_COMMENT_TP_RE = re.compile(
    r"/\*\s*traceparent\s*[=:]\s*'?"
    r"(?P<tp>[0-9a-f]{2}-[0-9a-f]{32}-[0-9a-f]{16}-[0-9a-f]{2})"
    r"'?\s*\*/", re.IGNORECASE)


def pad32(trace_id: str) -> str:
    """Our internal ids are 16 hex chars; W3C wants 32 — left-pad with
    zeros (an adopted 32-char id passes through unchanged)."""
    return trace_id.rjust(32, "0")


def parse_traceparent(header: str) -> Optional[tuple[str, Optional[str]]]:
    """(trace_id, parent_span_id) from a W3C `traceparent`, or None on
    anything malformed (a bad header must never fail the request). A
    zero-padded id we emitted earlier round-trips back to its internal
    16-char form."""
    m = _TRACEPARENT_RE.match((header or "").strip().lower())
    if not m or m.group("ver") == "ff":
        return None
    tid, sid = m.group("tid"), m.group("sid")
    if tid == "0" * 32 or sid == "0" * 16:
        return None
    if tid.startswith("0" * 16):
        tid = tid[16:]
    return tid, sid


def to_traceparent(trace_id: Optional[str] = None,
                   span_id: Optional[str] = None) -> Optional[str]:
    """W3C header for the current (or given) context — what HTTP egress
    emits and what a client would hand the next hop."""
    tid = trace_id or _current.get()
    if not tid:
        return None
    sid = (span_id or _parent.get() or new_span_id()).rjust(16, "0")[-16:]
    return f"00-{pad32(tid)}-{sid}-01"


def traceparent_from_sql(sql: str) -> Optional[str]:
    """Extract a traceparent carried in a leading SQL comment (the
    MySQL/Postgres ingress carrier — those wires have no headers)."""
    m = _COMMENT_TP_RE.search(sql[:256])
    return m.group("tp") if m else None


# ---- cross-process piggyback ------------------------------------------------


def spans_to_wire(spans: list[Span]) -> list[dict]:
    """JSON-serializable span records for the Flight response metadata
    (the RecordBatchMetrics payload analog). span_id/parent_id ride
    along so the frontend's merged tree keeps the nesting."""
    return [
        {"name": s.name, "duration_ms": round(s.duration_ms, 4),
         "started_at": s.started_at, "attrs": _wire_attrs(s.attrs),
         "span_id": s.span_id, "parent_id": s.parent_id,
         "cpu_ms": round(s.cpu_ms, 4)}
        for s in spans
    ]


def _wire_attrs(attrs: dict) -> dict:
    out = {}
    for k, v in attrs.items():
        out[str(k)] = v if isinstance(v, (int, float, bool, str,
                                          type(None))) else str(v)
    return out


def merge_spans(wire: list[dict], node: Optional[str] = None,
                trace_id: Optional[str] = None) -> list[Span]:
    """Merge piggybacked remote spans into the local ring, tagged with
    their source node and attributed to the CURRENT trace (the remote
    process recorded them under the same propagated id; using the local
    id keeps them joined even if the peer was mid-rollout and dropped
    it). When the 'remote' service actually shares this process (the
    in-process wire-mode cluster), its handler already recorded the
    same spans into this ring — those piggybacked copies are skipped,
    not double-reported. Returns the merged spans."""
    tid = trace_id or _current.get()
    local = spans_for(tid) if tid else []
    existing_ids = {s.span_id for s in local if s.span_id}
    # legacy dedup key for peers that predate span ids
    existing = {(s.name, s.started_at, round(s.duration_ms, 4))
                for s in local}
    merged = []
    for w in wire:
        try:
            s = Span(tid, str(w["name"]), float(w["duration_ms"]),
                     float(w.get("started_at", 0.0)),
                     dict(w.get("attrs") or {}), node=node,
                     span_id=str(w.get("span_id") or ""),
                     parent_id=w.get("parent_id") or None,
                     cpu_ms=float(w.get("cpu_ms") or 0.0))
        except (KeyError, TypeError, ValueError):
            continue  # a mangled record must not kill the query
        if s.span_id and s.span_id in existing_ids:
            continue
        if (s.name, s.started_at, s.duration_ms) in existing:
            continue
        _record(s)
        merged.append(s)
    return merged


def spans_for(trace_id: str) -> list[Span]:
    with _ring_lock:
        return list(_BY_TRACE.get(trace_id, ()))


def recent_spans(n: int = 100) -> list[Span]:
    with _ring_lock:
        return list(_SPANS)[-n:]


# ---- tree rendering ---------------------------------------------------------


def span_tree(spans: list[Span]) -> list[tuple[int, Span, float]]:
    """(depth, span, self_ms) rows in tree order. Children sort by start
    time under their parent; spans whose parent never landed in the ring
    (evicted, or a peer that predates linkage) surface as roots. Self
    time is the span's duration minus its direct children's — the
    'where did the 50 ms actually go' number."""
    by_id = {s.span_id: s for s in spans if s.span_id}
    children: dict[Optional[str], list[Span]] = {}
    roots: list[Span] = []
    for s in spans:
        if s.parent_id and s.parent_id in by_id:
            children.setdefault(s.parent_id, []).append(s)
        else:
            roots.append(s)
    roots.sort(key=lambda s: s.started_at)
    out: list[tuple[int, Span, float]] = []

    def walk(s: Span, depth: int, seen: set) -> None:
        if s.span_id and s.span_id in seen:
            return  # defensive: a mangled piggyback must not loop
        seen = seen | ({s.span_id} if s.span_id else set())
        kids = sorted(children.get(s.span_id, ()),
                      key=lambda c: c.started_at)
        # self = duration minus the WALL-CLOCK UNION of the children:
        # parallel children (scan-pool fan-out re-parents per-file
        # decode under one scan span) overlap, and a plain sum would
        # print negative self-time for exactly those spans
        covered = 0.0
        cur_lo = cur_hi = None
        for c in kids:
            lo, hi = c.started_at, c.started_at + c.duration_ms / 1000.0
            if cur_hi is None or lo > cur_hi:
                if cur_hi is not None:
                    covered += cur_hi - cur_lo
                cur_lo, cur_hi = lo, hi
            else:
                cur_hi = max(cur_hi, hi)
        if cur_hi is not None:
            covered += cur_hi - cur_lo
        self_ms = max(s.duration_ms - covered * 1000.0, 0.0)
        out.append((depth, s, self_ms))
        for c in kids:
            walk(c, depth + 1, seen)

    for r in roots:
        walk(r, 0, set())
    return out


def render_tree(spans: list[Span], indent: str = "  ") -> list[str]:
    """Human lines for one trace's span tree (EXPLAIN ANALYZE,
    /v1/slow_queries rendering, tools/trace_dump.py). A `[node]` marker
    line precedes the first span of each remote process at its nesting
    depth, so cross-process hops stay visually attributable."""
    lines: list[str] = []
    rows = span_tree(spans)
    prev_node: Optional[str] = None
    for depth, s, self_ms in rows:
        pad = indent * (depth + 1)
        if s.node != prev_node and s.node is not None:
            lines.append(f"{pad}[{s.node}]")
        prev_node = s.node
        attrs = " ".join(f"{k}={v}" for k, v in s.attrs.items())
        has_kids = any(d == depth + 1 and p.parent_id == s.span_id
                       for d, p, _ in rows)
        self_part = f" (self {self_ms:.2f} ms)" if has_kids else ""
        lines.append(f"{pad}{s.name}: {s.duration_ms:.2f} ms"
                     f" (cpu {s.cpu_ms:.2f} ms){self_part}"
                     + (f" [{attrs}]" if attrs else ""))
    return lines


# ---- log correlation --------------------------------------------------------


class TraceIdFilter(logging.Filter):
    """Stamp every record with the context's trace id so log lines join
    metrics and spans on one key (reference: its tracing subscriber puts
    the trace id on every event)."""

    def filter(self, record: logging.LogRecord) -> bool:
        record.trace_id = _current.get() or "-"
        return True


#: format fragment including the trace id (used by install_trace_logging
#: and any service that builds its own handler)
TRACE_LOG_FORMAT = ("%(asctime)s %(levelname)s %(name)s "
                    "trace_id=%(trace_id)s %(message)s")


def install_trace_logging(level: Optional[int] = None) -> TraceIdFilter:
    """Attach a TraceIdFilter to the root logger's handlers (creating a
    basicConfig handler with TRACE_LOG_FORMAT if none exist yet) so every
    log record carries `trace_id=`. Idempotent."""
    root = logging.getLogger()
    if not root.handlers:
        logging.basicConfig(format=TRACE_LOG_FORMAT,
                            level=level if level is not None else logging.INFO)
    elif level is not None:
        root.setLevel(level)
    filt = None
    for h in root.handlers:
        existing = [f for f in h.filters if isinstance(f, TraceIdFilter)]
        if existing:
            filt = existing[0]
            continue
        filt = filt or TraceIdFilter()
        h.addFilter(filt)
    return filt or TraceIdFilter()

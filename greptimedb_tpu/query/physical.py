"""Physical execution: logical plan -> streamed device kernels.

The execution model (TPU-first re-design of the reference's volcano-style
async streams, SURVEY.md §7):

  host scan (pruned, columnar)  ->  fixed-shape padded blocks  ->
  one fused jit kernel per block: filter mask + group ids + segment
  reductions  ->  device partial-aggregate combine across blocks  ->
  tiny host tail (decode group keys, HAVING/ORDER/LIMIT over G rows)

Everything static (expressions, key specs, ops) rides into jit as hashable
static arguments, so each query shape compiles once and is cached by jax.
Dedup (last-write-wins) of a table that is not append-mode is a mask over
the scan's rows, made on the host by merging the scan's sorted runs
(query/lww.py): no program per row count, no device sort, and no mask at
all where no row repeats.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import itertools
import logging
import os
import sys
import threading
import time
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.datatypes.types import DataType, SemanticType
from greptimedb_tpu.ops.blocks import (
    DEFAULT_BLOCK_ROWS,
    block_size_for,
    pad_rows,
    tail_block_size_for,
)
from greptimedb_tpu.ops import sparse_segment as sparse_ops
from greptimedb_tpu.ops.segment import (
    _type_max as _seg_type_max,
    _type_min as _seg_type_min,
    combine_group_ids,
    dense_segment_sum,
    float_segment_sum,
    segment_agg,
)
from greptimedb_tpu.query import logical as lp
from greptimedb_tpu.query.expr import (
    BindContext,
    PlanError,
    bind_expr,
    collect_columns,
    eval_device,
    eval_host,
    split_operands,
)
from greptimedb_tpu.query.result import QueryResult
from greptimedb_tpu.query.tier import (
    ACTIVE_TIER,
    OnShard,
    TierCtx,
    TierRouter,
    incremental_key,
    part_placement,
    region_device,
    whole_scan_key,
)
from greptimedb_tpu.sql import ast
from greptimedb_tpu.storage.engine import RegionEngine
from greptimedb_tpu.storage.region import (
    ScanData,
    ScanExpired,
    _scan_io_add,
    scan_io_counters,
    scan_io_since,
)
from greptimedb_tpu.utils import device_telemetry, tracing

# XLA compile + device memory telemetry rides jax.monitoring: one
# listener covers every jax.jit entry point in this module and ops/
device_telemetry.install()


def _readback(x) -> np.ndarray:
    """D2H result materialization, counted at /metrics."""
    arr = np.asarray(x)
    device_telemetry.count_d2h(arr.nbytes)
    return arr


def _staged(name: str, kernel_step: bool = False):
    """Run the decorated method as one serving stage (`_kstage` naming
    for a step of a kernel path)."""
    def deco(fn):
        @functools.wraps(fn)
        def staged(*args, **kwargs):
            with (_kstage if kernel_step else tracing.stage)(name):
                return fn(*args, **kwargs)
        return staged
    return deco


def _kstage(name: str, **attrs):
    """The serving stage of one step of a kernel path: `upload`,
    `device` (dispatch of jitted steps) or `readback` on the device and
    mesh tiers. On the host tier the same steps run on the CPU backend
    of this process and are the host's aggregation work: `host_agg`."""
    if ACTIVE_TIER.get() == "host":
        name = "host_agg"
    return tracing.stage(name, **attrs)

# primitive kernel ops backing each SQL aggregate
# boundary first/last gather only pays when it shrinks the scan: above
# this candidate fraction the subset would roughly duplicate the cached
# columns for no kernel savings (tests patch this to force the path on)
_BOUNDARY_MAX_FRACTION = 0.5
# last-write-wins masks kept by snapshot identity (a byte a row each)
_LWW_MASKS_KEPT = 16

_PRIMITIVES = {
    "sum": ("sum", "count"),  # count detects all-NULL groups -> NULL sum
    "count": ("count",),
    "rows": ("rows",),
    "avg": ("sum", "count"),
    "min": ("min",),
    "max": ("max",),
    "first": ("first",),
    "last": ("last",),
    "stddev": ("sum", "sumsq", "count"),
    "variance": ("sum", "sumsq", "count"),
    # a lowered RANGE statement reads the moments apart (range_select.py)
    "sumsq": ("sumsq",),
}


def _is_time_bucket(kexpr, ts_name: str) -> bool:
    """date_bin / time_bucket of a constant interval over the time index
    (with an origin it is a generic key: the bucket ids planned from the
    statement's range count steps from 0)."""
    return (isinstance(kexpr, ast.FuncCall)
            and kexpr.name in ("date_bin", "time_bucket")
            and len(kexpr.args) == 2
            and isinstance(kexpr.args[0], ast.Interval)
            and isinstance(kexpr.args[1], ast.Column)
            and kexpr.args[1].name == ts_name)


def _key_from_plan(kexpr, ctx) -> bool:
    """Whether a group key is planned from the scan's metadata alone (a
    tag column: its dictionary; a time bucket: the statement's range or
    the snapshot's extent) — the shapes `_plan_key` serves without
    reading rows."""
    if isinstance(kexpr, ast.Column):
        return kexpr.name in ctx.tag_names
    return _is_time_bucket(kexpr, ctx.schema.time_index.name)


def _needs_host_agg(spec, schema) -> bool:
    """True when a spec cannot ride the numeric device planes: order
    statistics, or first/last/min/max over STRING-typed arguments (tag
    codes are dictionary positions — reducing them yields positions, and
    their order is insertion order, not lexicographic)."""
    from greptimedb_tpu.query.host_agg import HOST_AGGS

    if spec.func in HOST_AGGS or spec.func == "count_distinct":
        return True
    if spec.arg is None:
        return False
    dt = _infer_dtype(spec.arg, schema)
    if dt is None or dt.is_numeric or dt.is_timestamp:
        return False
    if spec.func in ("first", "last", "min", "max"):
        return True
    if spec.func == "count":
        # count over a string TAG rides the device (codes, NULL = -1);
        # a string FIELD scans as decoded objects and must count on host
        from greptimedb_tpu.datatypes.types import SemanticType

        return (isinstance(spec.arg, ast.Column)
                and spec.arg.name in schema.names
                and schema.column(spec.arg.name).semantic
                is not SemanticType.TAG)
    return False


@dataclass(frozen=True)
class DeviceKey:
    """One group-by key computed on device. Static under jit but for
    `base`, which is no part of the key's identity: it comes from the
    statement's own time bounds, so it reaches a kernel as an operand
    (`_operands`) and only host code reads it here."""

    kind: str  # "tag" | "bucket" | "pre"
    column: str
    size: int
    step: int = 0  # bucket width in the column's storage unit
    # minimum bucket index (offsets ids to 0)
    base: int = dataclasses.field(default=0, compare=False, repr=False)


class _AggBinding(NamedTuple):
    """One aggregate bound to one scan (`PhysicalExecutor._bind_agg`)."""

    ctx: object
    bound_where: object
    keys: list
    decoders: list
    extra_cols: dict
    num_groups: int
    sparse: bool
    arg_exprs: list
    spec_slot: list
    ops: set


class _RegionOut(NamedTuple):
    """What one region's thread hands the fan-out
    (`PhysicalExecutor._region_partials`)."""

    partials: list
    stats: Optional[dict]  # the per-part fold's; None: classic kernels
    spec_slot: list  # each spec's value-plane column (`_bind_agg`)
    path: Optional[str]
    parts_fetched: int
    scanned: bool  # the region's scan had rows


class _RegionFold:
    """One region's fold on its chip, as `_aggregate` sees it from the
    folding thread (`_FOLD.current`): `seen` collects the devices the
    fold's programs ran on (region_partial_total says whether they were
    the region's own chip), and a program dispatched for the first time
    is warmed on the table's other chips beside the request — a
    partitioned table runs ONE program on all its chips, so the chip
    that meets a shape first pays its compile and the others find it
    compiled (a one-host panel warms one chip; the next host lives on
    another)."""

    def __init__(self, siblings, router):
        self.siblings, self.router = siblings, router
        self.seen: set = set()

    def warm_siblings(self, sig: tuple, dispatch) -> None:
        """`dispatch(device)` runs the program that was just new here
        on `device`, its arguments copied there; once per program and
        device, on threads the device status counts as warming."""
        for dev in self.siblings:
            key = sig[:-1] + (dev,)
            with _PROGRAMS_LOCK:
                if key in _PROGRAMS_SEEN:
                    continue  # that chip met the shape itself
                _PROGRAMS_SEEN.add(key)

            def warm(dev=dev, key=key):
                with self.router.compiling(("sibling", key)):
                    try:
                        dispatch(dev)
                    except Exception:  # noqa: BLE001 — best effort
                        # the chip compiles the shape when it meets it
                        with _PROGRAMS_LOCK:
                            _PROGRAMS_SEEN.discard(key)

            # not a daemon: a process that ends while XLA compiles on a
            # daemon thread aborts; this one is waited for (seconds)
            threading.Thread(
                target=tracing.propagate(warm, background=True),
                name="gtpu-sibling-warm").start()


#: the region fold open on this thread (`_region_partials`), if any
_FOLD = threading.local()


class _BlockEntry(NamedTuple):
    """One device block of the scan: rows [start, end) padded to
    `block`. `pkey` is the immutable SST part the rows belong to
    ((file_id, ts_range, pred_key) from ScanData.part_keys) or None for
    memtable/synthetic rows; `part_start` anchors the block offset
    inside its part so hot-set keys stay stable across versions."""

    pkey: Optional[tuple]
    part_start: int
    start: int
    end: int
    block: int


#: ceiling on part-aligned plan fan-out: a region with hundreds of tiny
#: unmerged flush files would otherwise unroll hundreds of kernel
#: dispatches into one jit — beyond this the scan falls back to the
#: uniform (version-keyed) block layout and lets compaction catch up
_MAX_PLAN_BLOCKS = 64

#: how often a statement retakes a scan whose snapshot expired under it
#: (each retake needs another DROP/TRUNCATE to land inside the statement)
_SCAN_RETAKES = 4


def _block_plan(scan) -> list[_BlockEntry]:
    """Part-aligned device block plan: blocks never straddle SST part
    seams, so every block's content is a pure function of its immutable
    file (+ window/predicate key) and its HBM upload survives
    data-version bumps — a flush uploads ONLY its new file's blocks.
    Scans without per-part identity (merged/synthetic/seq-sliced) get
    the classic uniform layout keyed by data version."""
    n = scan.num_rows
    offs = scan.sorted_part_offsets
    pkeys = getattr(scan, "part_keys", ())
    segs: list[tuple] = []
    tail = object()  # the memtable's rows after the files': they grow
    # between requests, so their block sizes are few and far apart

    def rows_of(pk, rows: int) -> int:
        size = tail_block_size_for(rows) if pk is tail \
            else block_size_for(rows)
        return min(size, DEFAULT_BLOCK_ROWS)

    if pkeys and len(offs) == len(pkeys) + 1 and offs[-1] <= n:
        segs = [(pkeys[i], offs[i], offs[i + 1]) for i in range(len(pkeys))]
        if offs[-1] < n:  # version-keyed, no part identity
            segs.append((tail, offs[-1], n))
        est = sum(-(-max(s1 - s0, 1) // rows_of(pk, s1 - s0))
                  for pk, s0, s1 in segs if s1 > s0)
        if est > _MAX_PLAN_BLOCKS:
            segs = []
    if not segs:
        # a scan gathered from another's rows (`_boundary_firstlast`)
        # says where the memtable's begin
        head = getattr(scan, "_tail_start", n)
        segs = [(None, 0, head), (tail, head, n)]
    plan: list[_BlockEntry] = []
    for pk, s0, s1 in segs:
        if s1 <= s0:
            continue
        pb = rows_of(pk, s1 - s0)
        for st in range(s0, s1, pb):
            plan.append(_BlockEntry(None if pk is tail else pk, s0, st,
                                    min(st + pb, s1), pb))
    return plan


# ---- fused per-block kernel ------------------------------------------------


def _value_planes(agg_args, cols, tag_names, schema, shape, acc_dtype):
    """Aggregate value matrix [N, F]. A tag column used as a VALUE maps
    its NULL code (-1) to NaN so count()/min()/... skip NULL tags."""
    vals = []
    for a in agg_args:
        v = eval_device(a, cols, tag_names, schema)
        if jnp.ndim(v) == 0:
            v = jnp.broadcast_to(v, shape)
        v = v.astype(acc_dtype)
        if isinstance(a, ast.Column) and a.name in tag_names:
            v = jnp.where(cols[a.name] < 0, jnp.nan, v)
        vals.append(v)
    return jnp.stack(vals, axis=1)


def _where_mask(mask, where, where_args, cols: dict, tag_names, schema):
    """`mask` narrowed by the predicate: its shape (static) evaluated
    over the block with its operands (traced)."""
    if where is None:
        return mask
    w = eval_device(where, cols, tag_names, schema, where_args)
    return mask & (w if w.dtype == jnp.bool_ else w != 0)


def _bucket_bases(keys, where_args):
    """The time-bucket keys' bases, in key order: the operands after the
    predicate's own (`_operands` put them there)."""
    nb = sum(k.kind == "bucket" for k in keys)
    return iter(where_args[len(where_args) - nb:])


def _group_ids(cols: dict, keys, n: int, where_args=()) -> jax.Array:
    """Dense group ids from the key columns (shared by every agg path)."""
    if not keys:
        return jnp.zeros(n, dtype=jnp.int32)
    key_arrays = []
    bases = _bucket_bases(keys, where_args)
    for k in keys:
        c = cols[k.column]
        if k.kind == "tag":
            arr = (c + 1).astype(jnp.int32)
        elif k.kind == "bucket":
            arr = (c // k.step - next(bases)).astype(jnp.int32)
        else:
            arr = c.astype(jnp.int32)
        key_arrays.append(jnp.clip(arr, 0, k.size - 1))
    return combine_group_ids(key_arrays, tuple(k.size for k in keys))


def _agg_block(
    cols: dict,
    n_valid: jax.Array,  # scalar: rows [0, n_valid) are real, rest padding
    dedup_mask,  # Optional[jax.Array]: survivors of last-write-wins
    *,
    where,
    where_args: tuple = (),  # traced: the predicate's operands, bucket bases
    keys: tuple[DeviceKey, ...],
    agg_args: tuple,
    ops: tuple[str, ...],
    num_segments: int,
    ts_name: str,
    tag_names: frozenset,
    schema,
    need_ts: bool,
    acc_dtype=jnp.float64,
):
    some = next(iter(cols.values()))
    # validity computed on device from a scalar — no host mask transfer
    mask = jnp.arange(some.shape[0]) < n_valid
    if dedup_mask is not None:
        mask = mask & dedup_mask
    return _agg_block_masked(
        cols, mask, where=where, where_args=where_args, keys=keys,
        agg_args=agg_args, ops=ops,
        num_segments=num_segments, ts_name=ts_name, tag_names=tag_names,
        schema=schema, need_ts=need_ts, acc_dtype=acc_dtype,
    )


def _agg_block_masked(
    cols: dict,
    mask: jax.Array,  # [N] base validity (padding & dedup), pre-filter
    *,
    where,
    where_args: tuple = (),
    keys: tuple[DeviceKey, ...],
    agg_args: tuple,
    ops: tuple[str, ...],
    num_segments: int,
    ts_name: str,
    tag_names: frozenset,
    schema,
    need_ts: bool,
    acc_dtype=jnp.float64,
):
    mask = _where_mask(mask, where, where_args, cols, tag_names, schema)
    gid = _group_ids(cols, keys, mask.shape[0], where_args)
    if agg_args:
        values = _value_planes(agg_args, cols, tag_names, schema,
                               mask.shape, acc_dtype)
    else:
        values = jnp.zeros((mask.shape[0], 1), dtype=acc_dtype)
    ts = cols[ts_name] if need_ts else None
    return segment_agg(values, gid, mask, num_segments, ops=ops, ts=ts)


@functools.partial(
    jax.jit,
    static_argnames=("where", "keys", "nf", "has_nan", "finite",
                     "num_segments", "tag_names", "schema", "float_ops",
                     "pack_dtype"),
)
@device_telemetry.kernel_name("agg_scan_prepared")
def _agg_scan_prepared(
    blocks: tuple,  # per-block col dicts incl. "__prep__"
    n_valids: jax.Array,
    dedup_masks,
    *,
    where, keys, nf, has_nan, finite, num_segments, tag_names, schema,
    float_ops, pack_dtype, where_args=(),
):
    """Dense fast path for sum/count/mean/rows over plain field columns.

    The "__prep__" plane is query-invariant and HBM-cached, so each
    query only computes [N]-shaped masks/keys and runs ONE dead-segment
    segment-sum per block — none of the [N, F] elementwise masking
    passes the general kernel needs (those dominated the profile: a
    masked segment-sum costs ~4x the plain one on this shape).

    Plane layouts (all query-invariant, per reduction class):
    - "__prep__"     [vals0 | valid | ones] (2F+1 with NaNs, F+1 without)
      reduced with segment-sum — feeds sum/count/mean/rows
    - "__prep_min__" vals with NaN -> +inf, reduced with segment-min
    - "__prep_max__" vals with NaN -> -inf, reduced with segment-max
    Empty/all-NULL groups come back as +/-inf and convert to NULL."""
    G = num_segments
    total = tmin = tmax = tsq = None
    for i, cols in enumerate(blocks):
        plane = cols["__prep__"]
        mask = jnp.arange(plane.shape[0]) < n_valids[i]
        if dedup_masks is not None:
            mask = mask & dedup_masks[i]
        mask = _where_mask(mask, where, where_args, cols, tag_names,
                           schema)
        gid = _group_ids(cols, keys, plane.shape[0], where_args)
        ids = jnp.where(mask, gid, jnp.int32(G))
        part = dense_segment_sum(plane, ids, G + 1, finite=finite)[:G]
        total = part if total is None else total + part
        if "__prep_min__" in cols:
            p = jax.ops.segment_min(cols["__prep_min__"], ids,
                                    num_segments=G + 1)[:G]
            tmin = p if tmin is None else jnp.minimum(tmin, p)
        if "__prep_max__" in cols:
            p = jax.ops.segment_max(cols["__prep_max__"], ids,
                                    num_segments=G + 1)[:G]
            tmax = p if tmax is None else jnp.maximum(tmax, p)
        if "__prep_sq__" in cols:
            p = dense_segment_sum(cols["__prep_sq__"], ids, G + 1,
                                  finite=finite)[:G]
            tsq = p if tsq is None else tsq + p
    sums = total[:, :nf]
    if has_nan:
        cnts = total[:, nf:2 * nf]
        rows = total[:, 2 * nf:2 * nf + 1]
    else:
        rows = total[:, nf:nf + 1]
        cnts = jnp.broadcast_to(rows, (G, nf))
    packed_f = _pack_float_ops(sums, cnts, rows, tmin, tmax, tsq,
                               float_ops, pack_dtype)
    return packed_f, jnp.zeros((0,), jnp.int64)


def _pack_float_ops(sums, cnts, rows, tmin, tmax, tsq, float_ops,
                    pack_dtype, extra=None):
    """Finalize + pack the prepared/fused accumulator planes into the
    one packed_f matrix both paths ship back over the link. `extra`
    supplies already-finalized planes the kernel can't derive (the
    fused path's first/last value planes)."""
    acc: dict[str, jax.Array] = {}
    for k in float_ops:
        if extra is not None and k in extra:
            acc[k] = extra[k]
        elif k == "sum":
            acc[k] = sums
        elif k == "count":
            acc[k] = cnts
        elif k == "rows":
            acc[k] = rows
        elif k == "min":
            # sentinel semantics identical to segment_agg: floats fill
            # with +/-inf, so an all-+inf group reads as NULL (a known,
            # shared limitation) and jax's +inf empty-segment fill is
            # covered by the same comparison
            big = _seg_type_max(tmin.dtype)
            acc[k] = jnp.where(tmin == big, jnp.nan, tmin)
        elif k == "max":
            small = _seg_type_min(tmax.dtype)
            acc[k] = jnp.where(tmax == small, jnp.nan, tmax)
        elif k == "sumsq":
            acc[k] = tsq
        else:  # mean — same NULL semantics as segment_agg
            denom = jnp.maximum(cnts, 1.0)
            acc[k] = jnp.where(cnts > 0, sums / denom, jnp.nan)
    parts = [acc[k].astype(pack_dtype) for k in float_ops]
    return jnp.concatenate(parts, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("where", "keys", "arg_names", "num_segments",
                     "ts_name", "tag_names", "schema", "float_ops",
                     "int_ops", "pack_dtype", "acc_dtype", "want_min",
                     "want_max", "want_sumsq"),
)
@device_telemetry.kernel_name("agg_scan_fused")
def _agg_scan_fused(
    blocks: tuple,  # per-block dicts of RAW column arrays (hot set)
    n_valids: jax.Array,
    dedup_masks,
    *,
    where, keys, arg_names, num_segments, ts_name, tag_names, schema,
    float_ops, int_ops, pack_dtype, acc_dtype, want_min, want_max,
    want_sumsq, where_args=(),
):
    """Fused-kernel twin of _agg_scan_prepared: the hot set holds only
    the RAW value columns — validity masks, the [vals|valid|rows]
    reduction plane, and the min/max identity fills / squared values are
    all built in-register by ops/pallas_segment.pallas_fused_segment_agg,
    so the HBM footprint per block is F lanes instead of 2F+1 (+F per
    min/max/sumsq rider) and each block costs ONE kernel dispatch.
    first/last ride along OUTSIDE the kernel: their (value, ts) pairing
    needs the arg-extreme select segment_agg implements, so each block
    adds one segment_agg over the ts column, folded across blocks with
    the same pairwise _combine_partials the classic dense path uses —
    a lastpoint + sum dashboard panel no longer kicks the whole query
    off the fused kernel."""
    from greptimedb_tpu.ops import pallas_segment as ps

    G = num_segments
    # first/last riders, named by their *_ts int planes
    fl_ops = tuple(sorted(op[:-3] for op in int_ops))
    # smaller row tile when extra lanes ride along: the [Gp, Nb]
    # select temporaries double, so halve Nb to stay inside VMEM
    block_rows = 256 if (want_min or want_max or want_sumsq) else 512
    tsum = tcnt = trow = tmin = tmax = tsq = None
    flacc = None
    for i, cols in enumerate(blocks):
        some = cols[arg_names[0]]
        nrows = some.shape[0]
        mask = jnp.arange(nrows) < n_valids[i]
        if dedup_masks is not None:
            mask = mask & dedup_masks[i]
        mask = _where_mask(mask, where, where_args, cols, tag_names,
                           schema)
        gid = _group_ids(cols, keys, nrows, where_args)
        ids = jnp.where(mask, gid, jnp.int32(G))
        vals = jnp.stack([cols[a].astype(acc_dtype) for a in arg_names],
                         axis=1)
        out = ps.pallas_fused_segment_agg(
            vals, ids, G + 1, want_min=want_min, want_max=want_max,
            want_sumsq=want_sumsq, block_rows=block_rows)
        s, c, r = out["sum"][:G], out["count"][:G], out["rows"][:G][:, None]
        tsum = s if tsum is None else tsum + s
        tcnt = c if tcnt is None else tcnt + c
        trow = r if trow is None else trow + r
        if want_min:
            m = out["min"][:G]
            tmin = m if tmin is None else jnp.minimum(tmin, m)
        if want_max:
            m = out["max"][:G]
            tmax = m if tmax is None else jnp.maximum(tmax, m)
        if want_sumsq:
            q = out["sumsq"][:G]
            tsq = q if tsq is None else tsq + q
        if fl_ops:
            part = segment_agg(vals, gid, mask, G, ops=fl_ops,
                               ts=cols[ts_name])
            flacc = _combine_partials(flacc, part)
    extra = {k: flacc[k] for k in fl_ops} if fl_ops else None
    packed_f = _pack_float_ops(tsum, tcnt, trow, tmin, tmax, tsq,
                               float_ops, pack_dtype, extra=extra)
    if int_ops:
        packed_i = jnp.stack([flacc[k] for k in int_ops], axis=1)
    else:
        packed_i = jnp.zeros((0,), jnp.int64)
    return packed_f, packed_i


@functools.partial(
    jax.jit,
    static_argnames=("where", "keys", "agg_args", "ops", "num_segments",
                     "ts_name", "tag_names", "schema", "need_ts", "acc_dtype",
                     "float_ops", "int_ops", "pack_dtype"),
)
@device_telemetry.kernel_name("agg_scan")
def _agg_scan(
    blocks: tuple,  # tuple of per-block col dicts (pytree)
    n_valids: jax.Array,  # [nblocks]
    dedup_masks,  # Optional[tuple of per-block masks]
    *,
    where, keys, agg_args, ops, num_segments, ts_name, tag_names, schema,
    need_ts, acc_dtype, float_ops, int_ops, pack_dtype, where_args=(),
):
    """The WHOLE aggregation as one device program: per-block fused
    filter+group+reduce, on-device partial combine, and a packed result —
    exactly one dispatch and one device->host transfer per query."""
    acc = None
    for i, cols in enumerate(blocks):
        partial = _agg_block(
            cols, n_valids[i],
            dedup_masks[i] if dedup_masks is not None else None,
            where=where, where_args=where_args, keys=keys,
            agg_args=agg_args, ops=ops,
            num_segments=num_segments, ts_name=ts_name, tag_names=tag_names,
            schema=schema, need_ts=need_ts, acc_dtype=acc_dtype,
        )
        acc = _combine_partials(acc, partial)
    parts = []
    for k in float_ops:
        v = acc[k]
        if v.ndim == 1:
            v = v[:, None]
        parts.append(v.astype(pack_dtype))
    packed_f = jnp.concatenate(parts, axis=1)
    if int_ops:
        packed_i = jnp.stack([acc[k] for k in int_ops], axis=1)
    else:
        packed_i = jnp.zeros((0,), jnp.int64)
    return packed_f, packed_i


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "where", "keys", "agg_args", "ops",
                     "num_segments", "ts_name", "tag_names", "schema",
                     "acc_dtype", "float_ops", "pack_dtype"),
)
@device_telemetry.kernel_name("agg_scan_sharded")
def _agg_scan_sharded(
    cols: dict,  # {name: [N_pad] array sharded along "shard"}
    base_mask: jax.Array,  # [N_pad] bool, sharded: padding & dedup survivors
    *,
    mesh, where, keys, agg_args, ops, num_segments, ts_name, tag_names,
    schema, acc_dtype, float_ops, pack_dtype, where_args=(),
):
    """Multi-device aggregation: each shard runs the same fused
    filter+group+reduce over its rows, partials combine with psum/pmin/pmax
    along the "shard" axis — the collective MergeScan (reference
    query/src/dist_plan/analyzer.rs:35 splits plans at commutativity
    boundaries and gathers at merge_scan.rs:122; here the combine rides ICI
    instead of point-to-point Flight). first/last pair (value, ts) and the
    shard with the global extreme ts wins (combine_partial_aggs), so
    lastpoint-class queries stay on the mesh; the *_ts planes never leave
    the collective."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    # the operands are replicated: every shard compares with the same
    in_specs = ({k: P("shard") for k in cols}, P("shard"), P())
    need_ts = bool({"first", "last"} & set(ops))

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    def step(local_cols, local_mask, operands):
        from greptimedb_tpu.ops.segment import combine_partial_aggs

        part = _agg_block_masked(
            local_cols, local_mask, where=where, where_args=operands,
            keys=keys, agg_args=agg_args, ops=ops, num_segments=num_segments,
            ts_name=ts_name, tag_names=tag_names, schema=schema,
            need_ts=need_ts, acc_dtype=acc_dtype,
        )
        part = {op: (v if v.ndim > 1 else v[:, None])
                for op, v in part.items()}
        combined = combine_partial_aggs(part, "shard")
        return jnp.concatenate(
            [combined[k].astype(pack_dtype) for k in float_ops], axis=1)

    return step(cols, base_mask, where_args)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "where", "keys", "agg_args", "ops", "cap",
                     "ts_name", "tag_names", "schema", "need_ts",
                     "acc_dtype", "float_ops", "int_ops", "pack_dtype"),
)
@device_telemetry.kernel_name("agg_scan_sharded_sparse")
def _agg_scan_sharded_sparse(
    cols: dict,  # {name: [N_pad] array sharded along "shard"}
    base_mask: jax.Array,  # [N_pad] bool, sharded
    *,
    mesh, where, keys, agg_args, ops, cap, ts_name, tag_names, schema,
    need_ts, acc_dtype, float_ops, int_ops, pack_dtype, where_args=(),
):
    """Multi-device SPARSE aggregation: each shard sort-compacts the
    group ids IT observes and ships [cap, W] value-keyed partials plus
    its rank -> global-id table. Unlike the dense collective, partials
    cannot psum in place — compact slots don't line up across shards —
    so out_specs stack the per-shard planes along "shard" and the host
    merges them in GID space (combine_sparse_gid_partials; global ids
    are shard-invariant, see _sparse_gid). Per-shard group counts ride
    along so the host can slice each shard's observed prefix."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    in_specs = ({k: P("shard") for k in cols}, P("shard"), P())
    out_specs = (P("shard"), P("shard"), P("shard"), P("shard"))

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def step(local_cols, local_mask, operands):
        mask = _where_mask(local_mask, where, operands, local_cols,
                           tag_names, schema)
        gid = _sparse_gid(local_cols, keys, operands)
        if agg_args:
            values = _value_planes(agg_args, local_cols, tag_names, schema,
                                   mask.shape, acc_dtype)
        else:
            values = jnp.zeros((mask.shape[0], 1), dtype=acc_dtype)
        ts = local_cols[ts_name] if need_ts else None
        part, uniq, n_groups = sparse_ops.sparse_segment_agg(
            values, gid, mask, cap, ops=ops, ts=ts)
        packed_f, packed_i = _pack_part(part, float_ops, int_ops, pack_dtype)
        return (packed_f, packed_i, uniq,
                n_groups.astype(jnp.int64)[None])

    return step(cols, base_mask, where_args)


def _build_prep(scan, arg_names, start, end, out_rows, acc_dtype, has_nan,
                kind) -> np.ndarray:
    """THE prepared-plane builder — rows [start, end) of the scan into a
    plane of `out_rows` rows (the single source of truth for the layout;
    the dense per-block and sharded whole-scan paths both call it).

    kind None -> the sum/count plane: [vals0 | valid | ones] (2F+1) with
    NaNs present, [vals | ones] (F+1) without. kind "min"/"max" ->
    identity-filled value planes for segment-min/max. kind "sq" ->
    squared values with NaN -> 0 (zero contribution), always f64: the
    stddev/variance cancellation needs full precision (see segment_agg).
    Padding rows are excluded by the base mask; extreme planes still get
    the identity fill there for safety."""
    f = len(arg_names)
    m = end - start
    np_acc = np.dtype(str(acc_dtype))
    # layout note: writes go through a feature-major [F, m] staging
    # buffer and ONE transpose-assign into the [rows, width] plane.
    # Column-at-a-time writes (plane[:m, j] = src) touch every 64B cache
    # line of the plane once per field — a read-modify-write of the
    # whole plane F times over; the transpose-assign streams the
    # destination sequentially while reading F sequential sources, so
    # the build runs at copy bandwidth (first-query warm-up was
    # dominated by exactly this at TSBS scale).
    def staged():
        src = np.empty((f, m), dtype=np.float64)
        for j, name in enumerate(arg_names):
            src[j] = scan.columns[name][start:end]
        return src

    if kind is None:
        width = (2 * f + 1) if has_nan else (f + 1)
        plane = np.empty((out_rows, width), dtype=np_acc)
        if out_rows > m:
            plane[m:] = 0.0
        src = staged()
        if has_nan:
            nan = np.isnan(src)
            np.copyto(src, 0.0, where=nan)
            plane[:m, :f] = src.T
            plane[:m, f:2 * f] = (~nan).T
        else:
            plane[:m, :f] = src.T
        plane[:m, width - 1] = 1.0
        return plane
    if kind == "sq":
        plane = np.empty((out_rows, f), dtype=np.float64)
        if out_rows > m:
            plane[m:] = 0.0
        src = staged()
        np.multiply(src, src, out=src)
        np.copyto(src, 0.0, where=np.isnan(src))
        plane[:m] = src.T
        return plane
    fill = np.inf if kind == "min" else -np.inf
    plane = np.empty((out_rows, f), dtype=np_acc)
    if out_rows > m:
        plane[m:] = fill
    src = staged()
    np.copyto(src, fill, where=np.isnan(src))
    plane[:m] = src.T
    return plane


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "where", "keys", "nf", "has_nan",
                     "num_segments", "tag_names", "schema", "float_ops",
                     "pack_dtype"),
)
@device_telemetry.kernel_name("agg_scan_sharded_prepared")
def _agg_scan_sharded_prepared(
    cols: dict,  # sharded cols incl. "__prep__" (+ optional min/max planes)
    base_mask: jax.Array,
    *,
    mesh, where, keys, nf, has_nan, num_segments, tag_names, schema,
    float_ops, pack_dtype, where_args=(),
):
    """Sharded twin of _agg_scan_prepared: each shard reduces its slice of
    the cached planes with the dead-segment id trick, then partials ride
    ICI (psum/pmin/pmax) — the multi-chip MergeScan with none of the
    per-query [N, F] masking passes."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    G = num_segments
    in_specs = ({k: P("shard") for k in cols}, P("shard"), P())

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    def step(local_cols, local_mask, operands):
        plane = local_cols["__prep__"]
        mask = _where_mask(local_mask, where, operands, local_cols,
                           tag_names, schema)
        gid = _group_ids(local_cols, keys, plane.shape[0], operands)
        ids = jnp.where(mask, gid, jnp.int32(G))
        total = jax.lax.psum(
            float_segment_sum(plane, ids, G + 1)[:G],
            "shard")
        sums = total[:, :nf]
        if has_nan:
            cnts = total[:, nf:2 * nf]
            rows = total[:, 2 * nf:2 * nf + 1]
        else:
            rows = total[:, nf:nf + 1]
            cnts = jnp.broadcast_to(rows, (G, nf))
        acc: dict[str, jax.Array] = {}
        for k in float_ops:
            if k == "sum":
                acc[k] = sums
            elif k == "count":
                acc[k] = cnts
            elif k == "rows":
                acc[k] = rows
            elif k == "min":
                tmin = jax.lax.pmin(
                    jax.ops.segment_min(local_cols["__prep_min__"], ids,
                                        num_segments=G + 1)[:G], "shard")
                big = _seg_type_max(tmin.dtype)
                acc[k] = jnp.where(tmin == big, jnp.nan, tmin)
            elif k == "max":
                tmax = jax.lax.pmax(
                    jax.ops.segment_max(local_cols["__prep_max__"], ids,
                                        num_segments=G + 1)[:G], "shard")
                small = _seg_type_min(tmax.dtype)
                acc[k] = jnp.where(tmax == small, jnp.nan, tmax)
            elif k == "sumsq":
                acc[k] = jax.lax.psum(
                    float_segment_sum(local_cols["__prep_sq__"], ids,
                                      G + 1)[:G], "shard")
            else:
                denom = jnp.maximum(cnts, 1.0)
                acc[k] = jnp.where(cnts > 0, sums / denom, jnp.nan)
        return jnp.concatenate(
            [acc[k].astype(pack_dtype) for k in float_ops], axis=1)

    return step(cols, base_mask, where_args)


def _prep_stream_step_impl(acc, cols, n_valid, *, where, keys, num_segments,
                           tag_names, schema, where_args=()):
    """One streaming step on the PREPARED planes: a single dead-segment
    segment-sum per chunk folded into the device accumulator — the
    streaming twin of _agg_scan_prepared (none of the [N, F] masking
    passes of the general streaming kernel)."""
    G = num_segments
    plane = cols["__prep__"]
    mask = jnp.arange(plane.shape[0]) < n_valid
    mask = _where_mask(mask, where, where_args, cols, tag_names, schema)
    gid = _group_ids(cols, keys, plane.shape[0], where_args)
    ids = jnp.where(mask, gid, jnp.int32(G))
    out = {"total": float_segment_sum(plane, ids, G + 1)[:G]}
    if "__prep_min__" in cols:
        out["min"] = jax.ops.segment_min(cols["__prep_min__"], ids,
                                         num_segments=G + 1)[:G]
    if "__prep_max__" in cols:
        out["max"] = jax.ops.segment_max(cols["__prep_max__"], ids,
                                         num_segments=G + 1)[:G]
    if "__prep_sq__" in cols:
        out["sq"] = float_segment_sum(cols["__prep_sq__"], ids, G + 1)[:G]
    if acc is not None:
        out["total"] = out["total"] + acc["total"]
        if "min" in out:
            out["min"] = jnp.minimum(out["min"], acc["min"])
        if "max" in out:
            out["max"] = jnp.maximum(out["max"], acc["max"])
        if "sq" in out:
            out["sq"] = out["sq"] + acc["sq"]
    return out


_PREP_STREAM_STATICS = ("where", "keys", "num_segments", "tag_names",
                        "schema")
_prep_stream_step_impl = device_telemetry.kernel_name("prep_stream_step")(
    _prep_stream_step_impl)
_prep_stream_step = functools.partial(
    jax.jit, static_argnames=_PREP_STREAM_STATICS)(_prep_stream_step_impl)
# donated twin: the chunked bigger-than-HBM fold reuses the accumulator
# AND the spent chunk's upload buffers instead of doubling peak HBM —
# XLA aliases the output planes over the donated inputs and frees the
# chunk at dispatch, so steady-state residency is one chunk + one
# accumulator no matter how many chunks stream through
_prep_stream_step_donated = functools.partial(
    jax.jit, static_argnames=_PREP_STREAM_STATICS,
    donate_argnums=(0, 1))(_prep_stream_step_impl)


def _donate_stream_buffers() -> bool:
    """Buffer donation knob for the streaming folds. Default: on for
    accelerator backends, off on CPU (XLA:CPU cannot alias these
    buffers and warns on every trace). GREPTIMEDB_TPU_DONATE=on forces
    it anywhere (the parity tests); =off pins the copying behavior for
    A/B."""
    env = os.environ.get("GREPTIMEDB_TPU_DONATE")
    if env is not None:
        return env.lower() not in ("0", "false", "off")
    return jax.default_backend() != "cpu"


def _prefetch(items, depth: int = 2):
    """Double-buffered pipeline: a producer thread runs the host-side
    work of the NEXT chunk (SST page reads, plane building, the H2D
    copy) while the device folds the current one. JAX dispatch is
    already async on the device side; this overlaps the HOST side too,
    so streaming wall-clock approaches max(transfer, compute) instead of
    their sum (SURVEY §7 hard part 4 — bigger-than-HBM scans).

    `depth` bounds the queue; up to depth+2 chunks can coexist (queued,
    one blocked in the producer's put, one being folded) — the real
    memory ceiling for 100M+-row scans."""
    import queue
    import threading

    q: queue.Queue = queue.Queue(maxsize=depth)
    sentinel = object()
    stop = threading.Event()
    err: list = []

    def producer():
        try:
            for item in items:
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return  # consumer abandoned: skip the rest of the scan
        except BaseException as e:  # noqa: BLE001 — re-raised on consumer
            err.append(e)
        finally:
            # the sentinel MUST land (a dropped sentinel deadlocks the
            # consumer's get) — retry until it fits or we were cancelled
            while not stop.is_set():
                try:
                    q.put(sentinel, timeout=0.1)
                    break
                except queue.Full:
                    continue

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    try:
        while True:
            try:
                item = q.get(timeout=0.1)
            except queue.Empty:
                # deadline checkpoint: a cancelled/expired consumer
                # unwinds typed; the finally stops the producer
                from greptimedb_tpu.utils import deadline as dl

                dl.check("streaming scan wait")
                continue
            if item is sentinel:
                break
            yield item
        if err:
            raise err[0]
    finally:
        # cancel the producer (exception/close downstream): it stops at
        # its next put instead of building the rest of the scan
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        # the producer exits after its CURRENT read; waiting keeps SST
        # file pins valid until no thread touches the files (bounded
        # laps, never abandoned — the pin contract is absolute)
        while t.is_alive():
            t.join(0.1)


class _NotStreamable(Exception):
    """Query shape the streaming path can't serve (generic group keys,
    host-side order statistics); caller falls back to the materialized
    scan."""


_agg_block_jit = functools.partial(
    jax.jit,
    static_argnames=("where", "keys", "agg_args", "ops", "num_segments",
                     "ts_name", "tag_names", "schema", "need_ts",
                     "acc_dtype"),
)(device_telemetry.kernel_name("agg_block")(_agg_block))


@functools.partial(
    jax.jit,
    static_argnames=("where", "keys", "agg_args", "ops", "cap", "ts_name",
                     "tag_names", "schema", "need_ts", "acc_dtype"),
)
@device_telemetry.kernel_name("agg_block_sparse")
def _agg_block_sparse(
    cols: dict,
    n_valid: jax.Array,
    dedup_mask,
    *,
    where, keys, agg_args, ops, cap, ts_name, tag_names, schema, need_ts,
    acc_dtype, where_args=(),
):
    """Sparse twin of _agg_block for the incremental per-part fold:
    sort-compact the part's observed group ids and segment-reduce over
    the static `cap` — the partial carries [cap, F] planes plus the
    rank -> global-id table, and the host keeps only the observed [:U]
    prefix. Replaces the dense [G, F] per-part planes past the partial
    cache's dense group cap."""
    some = next(iter(cols.values()))
    mask = jnp.arange(some.shape[0]) < n_valid
    if dedup_mask is not None:
        mask = mask & dedup_mask
    mask = _where_mask(mask, where, where_args, cols, tag_names, schema)
    gid = _sparse_gid(cols, keys, where_args)
    if agg_args:
        values = _value_planes(agg_args, cols, tag_names, schema,
                               mask.shape, acc_dtype)
    else:
        values = jnp.zeros((mask.shape[0], 1), dtype=acc_dtype)
    ts = cols[ts_name] if need_ts else None
    return sparse_ops.sparse_segment_agg(values, gid, mask, cap, ops=ops,
                                         ts=ts)


def _agg_step_impl(acc, cols, n_valid, *, where, keys, agg_args, ops,
                   num_segments, ts_name, tag_names, schema, need_ts,
                   acc_dtype, where_args=()):
    """One streaming step: fold a chunk's partial aggregate into the
    device-resident accumulator (constant HBM; one dispatch per chunk)."""
    part = _agg_block(cols, n_valid, None, where=where,
                      where_args=where_args, keys=keys, agg_args=agg_args,
                      ops=ops, num_segments=num_segments,
                      ts_name=ts_name, tag_names=tag_names, schema=schema,
                      need_ts=need_ts, acc_dtype=acc_dtype)
    return _combine_partials(acc, part)


_AGG_STEP_STATICS = ("where", "keys", "agg_args", "ops", "num_segments",
                     "ts_name", "tag_names", "schema", "need_ts",
                     "acc_dtype")
_agg_step_impl = device_telemetry.kernel_name("agg_step")(_agg_step_impl)
_agg_step = functools.partial(
    jax.jit, static_argnames=_AGG_STEP_STATICS)(_agg_step_impl)
# see _prep_stream_step_donated: accumulator + chunk buffers reused
_agg_step_donated = functools.partial(
    jax.jit, static_argnames=_AGG_STEP_STATICS,
    donate_argnums=(0, 1))(_agg_step_impl)


_GID_SENTINEL = sparse_ops.GID_SENTINEL  # > any real combined group id


def _sparse_gid(cols: dict, keys, where_args=()) -> jax.Array:
    """Combined int64 group id per row — shard-invariant (tag dictionary
    codes and bucket bases don't depend on which rows a shard holds), so
    gids computed per shard / per part merge globally."""
    key_arrays, sizes = [], []
    bases = _bucket_bases(keys, where_args)
    for k in keys:
        c = cols[k.column]
        if k.kind == "tag":
            arr = (c + 1).astype(jnp.int64)
        elif k.kind == "bucket":
            arr = (c // k.step - next(bases)).astype(jnp.int64)
        else:
            arr = c.astype(jnp.int64)
        key_arrays.append(jnp.clip(arr, 0, k.size - 1))
        sizes.append(k.size)
    return combine_group_ids(key_arrays, tuple(sizes), dtype=jnp.int64)


def _pack_part(part: dict, float_ops, int_ops, pack_dtype):
    """Pack a segment_agg plane dict into the (packed_f, packed_i) pair
    shipped over the link (same layout _unpack_acc splits)."""
    parts = []
    for k in float_ops:
        v = part[k]
        if v.ndim == 1:
            v = v[:, None]
        parts.append(v.astype(pack_dtype))
    packed_f = jnp.concatenate(parts, axis=1)
    if int_ops:
        packed_i = jnp.stack([part[k] for k in int_ops], axis=1)
    else:
        packed_i = jnp.zeros((0,), jnp.int64)
    return packed_f, packed_i


@functools.partial(
    jax.jit,
    static_argnames=("where", "keys", "agg_args", "ops", "cap", "ts_name",
                     "tag_names", "schema", "need_ts", "acc_dtype",
                     "float_ops", "int_ops", "pack_dtype"),
)
@device_telemetry.kernel_name("agg_scan_sparse")
def _agg_scan_sparse(
    cols: dict,  # {name: [N] padded whole-scan arrays}
    base_mask: jax.Array,  # [N] bool: padding & dedup survivors
    *,
    where, keys, agg_args, ops, cap, ts_name, tag_names, schema, need_ts,
    acc_dtype, float_ops, int_ops, pack_dtype, where_args=(),
):
    """Sparse (high-cardinality) aggregation: when the dense key product
    won't fit as [G, F] planes, sort the observed int64 group ids, compact
    them to dense [0, U) ids at segment boundaries, and segment-reduce over
    a static cap — the TPU-native replacement for the reference's hash
    aggregate (DataFusion row-hash; BASELINE config #5: 1M tag combos).
    Sorting is XLA-native and shapes stay static: all arrays are [N] or
    [cap, F]; only the group *count* is dynamic (returned as a scalar).
    The sort-compact core lives in ops/sparse_segment.py, shared with the
    fused/sharded/incremental sparse flavors.
    """
    mask = _where_mask(base_mask, where, where_args, cols, tag_names,
                       schema)
    gid = _sparse_gid(cols, keys, where_args)
    if agg_args:
        values = _value_planes(agg_args, cols, tag_names, schema,
                               mask.shape, acc_dtype)
    else:
        values = jnp.zeros((mask.shape[0], 1), dtype=acc_dtype)
    ts = cols[ts_name] if need_ts else None
    part, uniq, n_groups = sparse_ops.sparse_segment_agg(
        values, gid, mask, cap, ops=ops, ts=ts)
    packed_f, packed_i = _pack_part(part, float_ops, int_ops, pack_dtype)
    return packed_f, packed_i, uniq, n_groups


@functools.partial(
    jax.jit,
    static_argnames=("where", "keys", "arg_names", "ops", "cap",
                     "tag_names", "schema", "acc_dtype", "float_ops",
                     "pack_dtype"),
)
@device_telemetry.kernel_name("agg_scan_sparse_fused")
def _agg_scan_sparse_fused(
    cols: dict,  # {name: [N] padded whole-scan arrays}
    base_mask: jax.Array,
    *,
    where, keys, arg_names, ops, cap, tag_names, schema, acc_dtype,
    float_ops, pack_dtype, where_args=(),
):
    """Sparse aggregation with the reductions on the fused Pallas kernel:
    sort-compact once, then tile the compacted segment axis in FUSED_TILE
    windows (ops/sparse_segment.fused_sparse_segment_agg). The kernel's
    4096-segment envelope becomes a tile size — date_bin bucket domains
    and tag products far past it stay fused instead of falling back to
    the XLA scatter chain. Eligibility (plain finite field columns, op
    subset, mode gates) is the caller's job, mirroring _fused_ok."""
    mask = _where_mask(base_mask, where, where_args, cols, tag_names,
                       schema)
    gid = _sparse_gid(cols, keys, where_args)
    order, ids, valid_s, uniq, n_groups = sparse_ops.sort_compact(
        gid, mask, cap)
    vals = jnp.stack([cols[a].astype(acc_dtype) for a in arg_names],
                     axis=1)[order]
    out = sparse_ops.fused_sparse_segment_agg(
        vals, ids, cap, want_min="min" in ops, want_max="max" in ops,
        want_sumsq="sumsq" in ops)
    packed_f = _pack_float_ops(out["sum"], out["count"],
                               out["rows"][:, None], out.get("min"),
                               out.get("max"), out.get("sumsq"),
                               float_ops, pack_dtype)
    return packed_f, jnp.zeros((0,), jnp.int64), uniq, n_groups


@functools.partial(jax.jit, static_argnames=("where", "tag_names", "schema"))
@device_telemetry.kernel_name("filter_block")
def _filter_block(cols: dict, n_valid: jax.Array, dedup_mask, *, where,
                  tag_names, schema, where_args=()):
    some = next(iter(cols.values()))
    mask = jnp.arange(some.shape[0]) < n_valid
    if dedup_mask is not None:
        mask = mask & dedup_mask
    return _where_mask(mask, where, where_args, cols, tag_names, schema)


def _combine_partials(acc: Optional[dict], p: dict) -> dict:
    if acc is None:
        return p
    out = {}
    for k, v in p.items():
        a = acc[k]
        if k in ("count", "rows"):
            out[k] = a.astype(jnp.int64) + v.astype(jnp.int64)
        elif k in ("sum", "sumsq"):
            out[k] = a + v
        elif k == "min":
            out[k] = jnp.fmin(a, v)
        elif k == "max":
            out[k] = jnp.fmax(a, v)
        elif k in ("last", "last_ts", "first", "first_ts"):
            continue  # handled below as pairs
        else:
            raise PlanError(f"cannot combine partial op {k}")
    if "last" in p:
        newer = p["last_ts"] > acc["last_ts"]
        out["last"] = jnp.where(newer[:, None], p["last"], acc["last"])
        out["last_ts"] = jnp.where(newer, p["last_ts"], acc["last_ts"])
    if "first" in p:
        older = p["first_ts"] < acc["first_ts"]
        out["first"] = jnp.where(older[:, None], p["first"], acc["first"])
        out["first_ts"] = jnp.where(older, p["first_ts"], acc["first_ts"])
    return out


# ---- dispatch: a predicate's literals are operands -------------------------

#: every program dispatched so far (`_aggregate`'s signature of it, its
#: backend included): what agg_program_events_total calls `reuse`
_PROGRAMS_SEEN: set = set()
_PROGRAMS_LOCK = threading.Lock()


def _operands(bound_where, keys, schema) -> tuple:
    """(shape, operands, static_literal) for one dispatch: the bound
    predicate split (query/expr.py `split_operands`) and, after its
    operands, the base of every time-bucket key in key order — a base is
    floor(lo / step) of the statement's own bounds, a literal under
    another name (`_bucket_bases` reads them back)."""
    shape, operands, static_literal = split_operands(bound_where, schema)
    bases = tuple(np.int64(k.base) for k in keys or ()
                  if k.kind == "bucket")
    return shape, operands + bases, static_literal


def _aggregate(step, *args, where, schema, keys=None, **statics):
    """Dispatch one jitted aggregate or filter step with the bound
    predicate `where`: the step gets its shape as the static `where` and
    the literals as the traced `where_args`, so requests that differ in
    their literals or their window's start run one executable, on either
    backend. Counts the dispatch on agg_program_events_total and names
    it on the open stage's span (`program=`): `reuse` when this program
    — step, static arguments, argument shapes and dtypes, backend — was
    dispatched before in this process, `new` when not, `static_literal`
    when the shape still holds a literal."""
    from greptimedb_tpu.utils.metrics import AGG_PROGRAM_EVENTS

    shape, operands, static_literal = _operands(where, keys, schema)
    if keys is not None:
        statics["keys"] = keys
    if static_literal:
        event = "static_literal"
    else:
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = (step, shape, schema, frozenset(statics.items()), treedef,
               tuple((np.shape(x), getattr(x, "dtype", None))
                     for x in leaves),
               tuple(o.dtype for o in operands),
               jax.config.jax_default_device)
        with _PROGRAMS_LOCK:
            event = "reuse" if sig in _PROGRAMS_SEEN else "new"
            _PROGRAMS_SEEN.add(sig)
    AGG_PROGRAM_EVENTS.inc(event=event)
    tracing.note_stage(program=event)
    out = step(*args, where=shape, where_args=operands, schema=schema,
               **statics)
    fold = getattr(_FOLD, "current", None)
    if fold is not None:
        fold.seen.update(d.id for d in
                         jax.tree_util.tree_leaves(out)[0].devices())
        if event == "new":
            def on(device):
                with OnShard(device):
                    # as the fold makes them: uncommitted arrays of the
                    # thread's default device, a Python scalar weakly
                    # typed (the jit cache tells those apart)
                    there = jax.tree_util.tree_map(
                        lambda x: jnp.asarray(
                            x.item() if getattr(x, "weak_type", False)
                            else np.asarray(x)), args)
                    jax.block_until_ready(step(
                        *there, where=shape, where_args=operands,
                        schema=schema, **statics))

            fold.warm_siblings(sig, on)
    return out


# ---- execution tiers -------------------------------------------------------

_log = logging.getLogger("greptimedb_tpu.device")

#: fused-kernel runtime-failure latch (dict so tests can reset it): one
#: mid-query kernel failure routes this and every later query to the
#: XLA scatter path instead of re-failing per query
_FUSED_DISABLED = {"flag": False}

#: incremental-aggregation runtime-failure latch (same contract): an
#: unexpected per-part fold failure degrades to the classic whole-scan
#: kernels instead of re-failing every query
_PARTIAL_DISABLED = {"flag": False}

#: newest messages of device-path degradations (latches, warm-up and
#: pre-warm failures), exported by PhysicalExecutor.device_status()
_DEGRADATION_LOG: "collections.deque" = collections.deque(maxlen=16)


def _note_degradation(kind: str, what: str) -> None:
    """Every way the device path can quietly stop being the device path
    goes through here: serving continues (the caller falls back), but
    the event is logged with its traceback, counted on
    device_degradation_total{kind} and kept for device_status(). Call
    from inside the `except` block."""
    from greptimedb_tpu.utils.metrics import DEVICE_DEGRADATIONS

    DEVICE_DEGRADATIONS.inc(kind=kind)
    err = sys.exc_info()[1]
    _DEGRADATION_LOG.append(
        {"kind": kind, "what": what,
         "error": f"{type(err).__name__}: {err}"[:2000]})
    _log.error("%s: %s", kind, what, exc_info=True)


def _snap_version(scan) -> tuple:
    """Snapshot identity for snap-anchored hot-set keys: (incarnation,
    data_version). TRUNCATE recreates the region and resets its
    data_version, so the version alone can collide with a pre-truncate
    snapshot taken by a query still in flight; the region incarnation
    (0 for remote/synthetic scans) breaks the tie, and the tuple still
    orders lexicographically for the cache's generation retirement."""
    return (getattr(scan, "incarnation", 0), scan.data_version)


# ---- executor --------------------------------------------------------------


class PhysicalExecutor:
    def __init__(self, engine: RegionEngine):
        self.engine = engine
        from greptimedb_tpu import config
        from greptimedb_tpu.query.device_cache import DeviceCache

        self.cache = DeviceCache()
        # where work runs (query/tier.py): the router owns the mesh —
        # multi-device: row-shard the scan over it and combine partial
        # aggregates with collectives (None on a single chip) — and the
        # first-touch hedge's state
        self.router = TierRouter(config.query_mesh(), _note_degradation)
        # last-write-wins masks by snapshot identity (_maybe_dedup)
        self._lww_masks: collections.OrderedDict = collections.OrderedDict()
        self._lww_lock = threading.Lock()
        # last_path (which aggregate path served the last query:
        # dense | sparse | sharded | stream) and last_tier live behind
        # thread-local properties below: the background warm thread runs
        # the same _stream_agg machinery and must not clobber the
        # foreground query's reported path/tier
        self._tls = threading.local()
        # warmup amortization: a background kernel pre-warm compiles
        # the dominant Pallas shapes at open time instead of under the
        # first query (the persistent compilation cache is wired once,
        # at package import)
        if config.prewarm_enabled() and jax.default_backend() != "cpu":
            threading.Thread(target=self._prewarm_kernels,
                             daemon=True,
                             name="gtpu-device-prewarm").start()

    @property
    def mesh(self):
        return self.router.mesh

    @mesh.setter
    def mesh(self, v):
        self.router.mesh = v

    @property
    def last_path(self):
        return getattr(self._tls, "last_path", None)

    @last_path.setter
    def last_path(self, v):
        self._tls.last_path = v

    @property
    def last_tier(self):
        return getattr(self._tls, "last_tier", "device")

    @last_tier.setter
    def last_tier(self, v):
        self._tls.last_tier = v

    @property
    def last_partial_stats(self):
        """Incremental-aggregation stats of this thread's last query
        (None when the classic paths served): part hit/miss counts,
        delta rows actually folded vs total scan rows."""
        return getattr(self._tls, "partial_stats", None)

    @last_partial_stats.setter
    def last_partial_stats(self, v):
        self._tls.partial_stats = v

    def device_status(self) -> dict:
        """What this process runs on and whether anything on the device
        path has degraded (GET /v1/device): platform, device kind and
        count, per-device allocator stats, the link probe, Pallas mode
        with the canaries' verdicts and messages, the runtime latches,
        hedge warm-up state, and recent degradation messages. Read-only
        apart from the link probe, which runs once per process."""
        from greptimedb_tpu import config, native
        from greptimedb_tpu.ops import pallas_segment as ps
        from greptimedb_tpu.ops.segment import _pallas_mode

        devs = jax.local_devices()
        devices = []
        for d in devs:
            st = d.memory_stats() or {}
            devices.append({
                "id": d.id, "kind": d.device_kind,
                "bytes_in_use": st.get("bytes_in_use"),
                "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                "bytes_limit": st.get("bytes_limit")})
        return {
            **self.router.status(),
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
            "devices": devices,
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "compute_dtype": str(config.compute_dtype()),
            "pallas": {"mode": _pallas_mode(),
                       "dispatch_mode": ps.dispatch_mode(),
                       "canaries": ps.canary_status(),
                       "fused_disabled": _FUSED_DISABLED["flag"],
                       "partial_disabled": _PARTIAL_DISABLED["flag"]},
            "degradations": list(_DEGRADATION_LOG),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "native_available": native.AVAILABLE,
        }

    def _prewarm_kernels(self) -> None:
        """Background compile of the dominant Pallas kernel shapes
        (GREPTIMEDB_TPU_PREWARM_SHAPES, "G,F;G,F" pairs — defaults to
        the single-groupby and double-groupby classes) so the first
        dashboard query pays HLO-level compile only, not Mosaic. Best
        effort — a failure never takes the node down — but never
        silent: it is counted and logged (_note_degradation). While it
        runs it counts among the warm-ups still compiling
        (device_status()["warmup"]["warming"]): a client that waits for
        a warm server waits for these compiles too, instead of finding
        them in its first minute of traffic."""
        with self.router.compiling("prewarm"):
            try:
                from greptimedb_tpu.ops import pallas_segment as ps

                ps.tpu_compile_ok()
                ps.fused_tpu_compile_ok()
                # NB: G is the query's GROUP count — the kernels get G+1
                # segments (dead segment), so the largest routable G is
                # MAX_SEGMENTS-1 (4095), not 4096; an ineligible shape
                # would burn Mosaic compile on an executable _fused_ok can
                # never route
                shapes = os.environ.get("GREPTIMEDB_TPU_PREWARM_SHAPES",
                                        "64,10;4095,10")
                for part in shapes.split(";"):
                    g, f = (int(x) for x in part.split(","))
                    if ps.fused_eligible(f, g + 1):
                        ps.pallas_fused_segment_agg(
                            jnp.zeros((512, f), jnp.float32),
                            jnp.zeros(512, jnp.int32), g + 1,
                            want_min=True, want_max=True, block_rows=256)
                        ps.pallas_fused_segment_agg(
                            jnp.zeros((512, f), jnp.float32),
                            jnp.zeros(512, jnp.int32), g + 1)
                    if ps.eligible((512, 2 * f + 1), g + 1):
                        ps.pallas_dense_segment_sum(
                            jnp.zeros((512, 2 * f + 1), jnp.float32),
                            jnp.zeros(512, jnp.int32), g + 1)
            except Exception:  # noqa: BLE001 — pre-warm must never take a node down
                _note_degradation("prewarm_failed",
                                  "background Pallas kernel pre-warm")

    def tier_for(self, agg, num_rows: int, streaming: bool = False) -> str:
        """Where this work runs: TierRouter.choose (query/tier.py)."""
        return self.router.choose(agg, num_rows, streaming)

    def execute(self, plan: lp.LogicalPlan) -> QueryResult:
        """Run one statement's plan and count the tier that answered it
        (query_tier_total), once, where that is final: `cache` when the
        partial-aggregate cache served every part and no kernel ran,
        else the effective last_tier."""
        from greptimedb_tpu.utils.metrics import AGG_SCAN, QUERY_TIER

        self._tls.__dict__.pop("last_tier", None)
        self._tls.__dict__.pop("agg_scan_mode", None)
        self.last_partial_stats = None
        if isinstance(plan, lp.RangeCombine):
            # a RANGE statement: its tumbling aggregate by whatever path
            # an aggregate takes, then the sliding combine over its groups
            from greptimedb_tpu.query import range_select as rs
            from greptimedb_tpu.utils.metrics import RANGE_SELECT

            res = rs.range_combine(self, plan.spec,
                                   self._execute(plan.input))
            self.last_path = (self.last_path or "empty") + "+range_combine"
            RANGE_SELECT.inc(path=self.last_path)
        else:
            res = self._execute(plan)
        stats = self.last_partial_stats
        QUERY_TIER.inc(tier="cache" if stats and not stats["delta_rows"]
                       else self.last_tier)
        mode = self._tls.__dict__.get("agg_scan_mode")
        if mode is not None:
            AGG_SCAN.inc(mode=mode)
        return res

    def _whole_columns(self, scan, table) -> None:
        """The consumer ahead reads whole columns: build them now (every
        missing part decoded in one fan-out, columns concatenated in
        parallel — storage/region.py ScanData.materialize) and as what
        it is, scan work: a `scan` stage segment wherever in the
        statement the need arises, so the decode never hides in the
        stage that happened to touch a column first."""
        if scan is None or scan.materialized:
            return
        before = scan_io_counters()
        with tracing.stage("scan", table=table.name, regions=1,
                           whole=True) as attrs:
            scan.materialize()
            attrs.update(scan_io_since(before))

    def _note_agg_scan(self, mode: str) -> None:
        """What an aggregate statement asked of its scan, for
        greptimedb_tpu_agg_scan_total: `none` (the incremental path
        fetched no SST part: every partial was cached), `parts` (it
        fetched the parts it missed, and only those), `whole` (whole
        columns were built or read: every other path). A statement
        that scans more than once (bucket top-k) counts its most
        expensive scan."""
        rank = ("none", "parts", "whole")
        prev = self._tls.__dict__.get("agg_scan_mode")
        if prev is None or rank.index(mode) > rank.index(prev):
            self._tls.agg_scan_mode = mode

    def _execute(self, plan: lp.LogicalPlan) -> QueryResult:
        # unwrap the linear chain
        limit = offset = None
        sort: Optional[lp.Sort] = None
        node = plan
        if isinstance(node, lp.Limit):
            limit, offset = node.limit, node.offset
            node = node.input
        if isinstance(node, lp.Sort):
            sort = node
            node = node.input
        if not isinstance(node, lp.Project):
            raise PlanError(f"unexpected plan root {type(node).__name__}")
        project = node
        node = node.input
        having: Optional[lp.Having] = None
        if isinstance(node, lp.Having):
            having = node
            node = node.input
        agg: Optional[lp.Aggregate] = None
        if isinstance(node, lp.Aggregate):
            agg = node
            node = node.input
        where = None
        if isinstance(node, lp.Filter):
            where = node.predicate
            node = node.input
        if not isinstance(node, lp.Scan):
            raise PlanError(f"unexpected scan node {type(node).__name__}")
        scan_node = node

        table = scan_node.table
        ts_range = _closed_range(scan_node.ts_range)
        # conjunctive tag eq/IN predicates drive inverted-index row-group
        # pruning inside the scan (reference scan_region.rs index applier)
        from greptimedb_tpu.storage.index import extract_tag_predicates

        tag_preds = extract_tag_predicates(where, table.schema) or None
        # the whole primary key rides along with a scan only where its
        # rows are merged by it (Region.scan): what `_dedups` reads
        full_key = not table.append_mode

        def run(ts_range):
            # one scan = one snapshot; a snapshot whose files died
            # between plan and fetch (DROP / TRUNCATE under the
            # statement) is retaken, so the answer is that of a later,
            # whole snapshot — never an error, never part of one
            self.last_partial_stats = None
            parts0 = scan_io_counters()[0]
            for attempt in range(_SCAN_RETAKES):
                try:
                    res = run_once(ts_range)
                    break
                except ScanExpired:
                    if attempt == _SCAN_RETAKES - 1:
                        raise
            if agg is not None:
                stats = self.last_partial_stats
                self._note_agg_scan(
                    "whole" if stats is None
                    else "parts" if scan_io_counters()[0] > parts0
                    else "none")
            return res

        def run_once(ts_range):
            # lastpoint pruning: an all-`last` aggregate grouped by one
            # tag only needs each series' newest rows — the region walks
            # SSTs newest-first and stops early (Region.scan_last) in
            # place of decoding the full table. Falls through to the
            # normal paths whenever the region can't serve it exactly
            # (tombstones, router engines, no files).
            lp_tag = self._lastpoint_tag(table, where, agg, ts_range)
            if (lp_tag is not None and len(table.region_ids) == 1
                    and hasattr(self.engine, "scan_last")):
                io0 = scan_io_counters()
                with tracing.stage("scan", table=table.name, regions=1,
                                   lastpoint=True) as scan_attrs:
                    pruned = self.engine.scan_last(
                        table.region_ids[0], lp_tag, scan_node.columns,
                        full_key=full_key)
                    scan_attrs.update(scan_io_since(io0))
                if pruned is not None:
                    with tracing.span("aggregate", rows=pruned.num_rows):
                        res = self._execute_agg(
                            pruned, table, where, agg, having, project,
                            sort, limit, offset, scan_node)
                    self.last_path = "lastscan+" + (self.last_path or "")
                    return res

            # distributed plan-fragment pushdown: classify the plan prefix
            # (dist_plan.classify_prefix, the commutativity.rs analog) and
            # ship it as one PlanFragment per region — partial-agg planes,
            # top-k candidates, or filtered rows come back, never raw scans
            if (len(table.region_ids) > 1
                    and hasattr(self.engine, "execute_fragment")):
                res = self._try_fragment_pushdown(
                    table, where, agg, having, project, sort, limit, offset,
                    ts_range, scan_node)
                if res is not None:
                    return res

            # a table of several regions in one process: each matching
            # region folds its own scan on its own chip, the partials
            # combine by key value (what cannot be split gathers below)
            regions = self._matching_regions(table, tag_preds)
            if agg is not None and len(table.region_ids) > 1:
                res = self._try_region_fanout(
                    regions, table, where, agg, having, project, sort,
                    limit, offset, ts_range, scan_node, tag_preds,
                    full_key)
                if res is not None:
                    return res

            # beyond-RAM aggregate scans stream: append-mode (no dedup
            # sort), single region, estimated rows over the threshold
            if (agg is not None and table.append_mode
                    and len(table.region_ids) == 1):
                from greptimedb_tpu import config

                # the row estimate is metadata; only a scan big enough
                # to stream takes the stream's snapshot (a second
                # memtable copy and pin) — every other request's one
                # snapshot is the scan below
                estimate = getattr(self.engine, "estimate_rows", None)
                stream = None
                if estimate is None or estimate(
                        table.region_ids[0], ts_range) \
                        >= config.stream_threshold_rows():
                    stream = self.engine.scan_stream(
                        table.region_ids[0], ts_range, scan_node.columns,
                        tag_preds, full_key=full_key)
                if stream is not None:
                    if stream.est_rows >= config.stream_threshold_rows():
                        tier = self.tier_for(agg, stream.est_rows,
                                             streaming=True)
                        self.last_tier = tier
                        try:
                            with TierCtx(tier):
                                return self._execute_agg_stream(
                                    stream, table, where, agg, having,
                                    project, sort, limit, offset,
                                    scan_node)
                        except _NotStreamable:
                            pass  # materialized fallback below
                        finally:
                            # idempotent: releases SST pins if the stream
                            # was abandoned mid-way (or never started)
                            stream.close()
                    else:
                        stream.close()

            io0 = scan_io_counters()
            with tracing.stage("scan", table=table.name,
                               regions=len(table.region_ids)) as scan_attrs:
                if len(table.region_ids) == 1:
                    scan = self.engine.scan(table.region_ids[0], ts_range,
                                            scan_node.columns, tag_preds,
                                            full_key=full_key)
                else:
                    # what cannot be split into per-region partials
                    # (raw rows, order statistics) gathers the scans of
                    # the regions the predicate can match (MergeScan,
                    # dist_plan/merge_scan.rs analog)
                    from greptimedb_tpu.storage.merge_scan import merge_scans

                    scan = merge_scans(
                        [
                            self.engine.scan(rid, ts_range,
                                             scan_node.columns, tag_preds,
                                             full_key=full_key)
                            for _i, rid in regions
                        ]
                    )
                # rows land on the span (and, through it, the resource
                # ledger's rows_scanned): the snapshot's rows, and beside
                # them the rows this stage decoded from SSTs — a full
                # scan is a plan, its parts decode when first asked for
                # (then in a `scan` segment of their own)
                scan_attrs["rows"] = 0 if scan is None else scan.num_rows
                scan_attrs.update(scan_io_since(io0))

            nrows = 0 if scan is None else scan.num_rows
            try:
                if agg is not None:
                    # tier decision happens INSIDE _execute_agg, after
                    # the boundary fast path has (possibly) shrunk the
                    # scan
                    with tracing.span("aggregate", rows=nrows):
                        return self._execute_agg(
                            scan, table, where, agg, having, project,
                            sort, limit, offset, scan_node)
                self._whole_columns(scan, table)
                tier = self.tier_for(None, nrows)
                self.last_tier = tier
                with tracing.span("filter_project", rows=nrows,
                                  tier=tier), TierCtx(tier):
                    return self._execute_raw(scan, table, where, project,
                                             sort, limit, offset)
            finally:
                if scan is not None:
                    # a plan nobody read to the end gives its pins back
                    scan.close()

        # bucket-top-k narrowing: ORDER BY <time bucket> DESC/ASC LIMIT k
        # only needs the k newest/oldest buckets — scan those, and widen
        # geometrically if the data is sparse (TSBS groupby-orderby-limit
        # runs its aggregate over 13M rows for 5 output buckets otherwise)
        candidates = self._bucket_topk_ranges(table, agg, sort, limit,
                                              offset, having, ts_range)
        if candidates:
            for cand in candidates[:-1]:
                res = run(cand)
                if res.num_rows >= int(limit):
                    self.last_path = "bucket_topk+" + (self.last_path or "")
                    return res
            return run(candidates[-1])
        return run(ts_range)

    # ---- a table of several regions, one region a chip ---------------------

    def _matching_regions(self, table, tag_preds) -> list[tuple[int, int]]:
        """(position in the table, region id) of every region the
        statement's predicates on the partition columns can match: the
        table's rule read against the literals (`=`, `IN`, ranges), which
        stay operands of the device programs. Counts the routed and the
        pruned on region_route_total."""
        rids = list(table.region_ids)
        if len(rids) == 1:
            return [(0, rids[0])]
        from greptimedb_tpu.partition.rule import rule_of
        from greptimedb_tpu.utils.metrics import REGION_ROUTE

        rule = rule_of(table)
        matched = list(range(len(rids)))
        if rule is not None and rule.num_regions() == len(rids):
            matched = rule.match_regions(tag_preds)
        REGION_ROUTE.inc(float(len(matched)), outcome="scanned")
        REGION_ROUTE.inc(float(len(rids) - len(matched)), outcome="pruned")
        return [(i, rids[i]) for i in matched]

    def _try_region_fanout(self, regions, table, where, agg, having,
                           project, sort, limit, offset, ts_range,
                           scan_node, tag_preds,
                           full_key) -> Optional[QueryResult]:
        """An aggregate over a table of several regions: every matching
        region takes its OWN scan — a plan with parts, its region id and
        data version — through the per-part route (`_scan_partials`) on
        its own chip, the regions side by side, and all their partials
        combine by key value as one region's parts do
        (`combine_partials`: the regions' tag dictionaries differ). A
        region that fails fails the request: no answer comes from fewer
        regions than match. None for what per-region partials cannot
        give (an order statistic needs the rows together): the caller
        gathers."""
        from concurrent.futures import ThreadPoolExecutor

        from greptimedb_tpu.query.dist_agg import combine_partials
        from greptimedb_tpu.utils import deadline as dl
        from greptimedb_tpu.utils.metrics import (
            REGION_COMBINE_SECONDS,
            REGION_FANOUT_SECONDS,
        )

        if any(_needs_host_agg(spec, table.schema) for spec in agg.aggs):
            return None
        lp_tag = self._lastpoint_tag(table, where, agg, ts_range) \
            if hasattr(self.engine, "scan_last") else None

        def one(region):
            return self._region_partials(region, table, where, agg,
                                         ts_range, scan_node, tag_preds,
                                         lp_tag, full_key)

        t0 = time.perf_counter()
        with tracing.span("region_fanout", table=table.name,
                          regions_matched=len(regions)) as attrs:
            if len(regions) > 1:
                # the request's thread folds the first region itself (a
                # one-host panel never leaves it); the others run beside
                # it, so the wall is the slowest region's
                beside = dl.propagate(tracing.propagate(one))
                with ThreadPoolExecutor(
                        max_workers=len(regions) - 1,
                        thread_name_prefix="gtpu-region") as pool:
                    futures = [pool.submit(beside, r) for r in regions[1:]]
                    outs = [one(regions[0])]
                    outs += [dl.wait_future(f, "region fan-out")
                             for f in futures]
            else:
                outs = [one(r) for r in regions]
            attrs["regions_scanned"] = sum(1 for o in outs if o.scanned)
        REGION_FANOUT_SECONDS.observe(time.perf_counter() - t0)

        partials = [p for o in outs for p in o.partials]
        # what `run` reads off this thread: the parts the regions'
        # threads fetched, and the fold's statistics summed (None where
        # a region went through the classic kernels)
        _scan_io_add(parts=sum(o.parts_fetched for o in outs))
        all_stats = [o.stats for o in outs if o.scanned]
        stats = None
        if all_stats and all(st is not None for st in all_stats):
            stats = {k: sum(st[k] for st in all_stats)
                     for k in all_stats[0] if k != "sparse"}
            stats["sparse"] = any(st["sparse"] for st in all_stats)
        ops_t = tuple(sorted(self._agg_ops(agg, table.schema)))
        t0 = time.perf_counter()
        with tracing.stage("host_agg", step="combine_partials",
                           regions=len(regions)), \
                tracing.span("region_combine", partials=len(partials)):
            combined = combine_partials(partials, len(agg.keys), ops_t)
        REGION_COMBINE_SECONDS.observe(time.perf_counter() - t0)
        path = next((o.path for o in outs if o.path), None)
        self.last_path = "fanout+" + (path or "empty")
        self.last_tier = "mesh"
        self.last_partial_stats = stats
        spec_slot = next((o.spec_slot for o in outs if o.scanned), [])
        return self._finalize_combined_agg(combined, table, agg, having,
                                           project, sort, limit, offset,
                                           spec_slot)

    def _region_partials(self, region, table, where, agg, ts_range,
                         scan_node, tag_preds, lp_tag,
                         full_key) -> "_RegionOut":
        """One region's scan to its partials, on the region's chip."""
        from greptimedb_tpu.utils.metrics import REGION_PARTIAL

        index, rid = region
        device = region_device(index)
        parts0 = scan_io_counters()[0]
        others = {region_device(i) for i in range(len(table.region_ids))}
        _FOLD.current = fold = _RegionFold(
            sorted(others - {device}, key=lambda d: d.id), self.router)
        try:
            with tracing.span("region_partial", region=rid,
                              device=device.id) as attrs, OnShard(device):
                scan = None
                if lp_tag is not None:
                    # newest-first pruned scan, as a one-region table's
                    # lastpoint takes (Region.scan_last)
                    io0 = scan_io_counters()
                    with tracing.stage("scan", table=table.name, regions=1,
                                       lastpoint=True) as scan_attrs:
                        scan = self.engine.scan_last(rid, lp_tag,
                                                     scan_node.columns,
                                                     full_key=full_key)
                        scan_attrs.update(scan_io_since(io0))
                if scan is None:
                    io0 = scan_io_counters()
                    with tracing.stage("scan", table=table.name,
                                       regions=1) as scan_attrs:
                        scan = self.engine.scan(rid, ts_range,
                                                scan_node.columns, tag_preds,
                                                full_key=full_key)
                        scan_attrs["rows"] = 0 if scan is None \
                            else scan.num_rows
                        scan_attrs.update(scan_io_since(io0))
                attrs["rows"] = 0 if scan is None else scan.num_rows
                if scan is None:
                    return _RegionOut([], None, [], None, 0, False)
                try:
                    with tracing.span("aggregate", rows=scan.num_rows):
                        partials, stats, spec_slot = self._scan_partials(
                            scan, table, where, agg, scan_node, device)
                finally:
                    scan.close()
        finally:
            _FOLD.current = None
        if fold.seen:
            REGION_PARTIAL.inc(placement="own_chip"
                               if fold.seen == {device.id} else "other")
        return _RegionOut(partials, stats, spec_slot, self.last_path,
                          scan_io_counters()[0] - parts0, True)

    def _scan_partials(self, scan, table, where, agg, scan_node,
                       device) -> tuple[list, Optional[dict], list]:
        """One region's scan to its list of value-keyed partials
        ({"keys", "planes"}) on `device`: the per-part route — cached
        partials, the pruned batches, the fold of what is missing — and,
        where that route refuses the shape (a boundary first/last
        reduction, tombstones, no immutable part), the classic kernels'
        planes as ONE partial. Returns (partials, the fold's statistics
        or None, each spec's value-plane column)."""
        from greptimedb_tpu.query import partial_cache as pc
        from greptimedb_tpu.utils.metrics import PARTIAL_AGG_CACHE_EVENTS

        schema = table.schema
        ts_name = schema.time_index.name
        b = self._bind_agg(scan, table, where, agg, scan_node)
        with tracing.stage("host_agg", step="boundary_firstlast"):
            reduced = self._boundary_firstlast(
                scan, table, agg, b.bound_where, b.keys, b.extra_cols)
        if reduced is None and pc.enabled() and not _PARTIAL_DISABLED["flag"]:
            try:
                partials, stats, _tier = self._incremental_partials(
                    scan, table, b.bound_where, b.keys, b.decoders,
                    b.arg_exprs, b.ops, b.num_groups, ts_name, b.ctx,
                    b.extra_cols, agg, b.sparse, device=device)
                self.last_path = "incremental_sparse" if stats["sparse"] \
                    else "incremental"
                return partials, stats, b.spec_slot
            except pc.PartialCacheIneligible:
                PARTIAL_AGG_CACHE_EVENTS.inc(event="fallback")
        if reduced is None:
            self._whole_columns(scan, table)  # the classic kernels' input
        else:
            scan = reduced
        self.last_tier = "device"  # this chip alone, not the mesh's shards
        acc, sparse_gids = self._stream_agg(
            scan, table, b.bound_where, tuple(b.keys), tuple(b.arg_exprs),
            tuple(sorted(b.ops)), b.num_groups, ts_name, b.ctx,
            b.extra_cols, b.sparse)
        if reduced is not None:
            self.last_path = "boundary+" + (self.last_path or "")
        return [_acc_partial(acc, sparse_gids, b.keys, b.decoders,
                             bool(agg.keys))], None, b.spec_slot

    @staticmethod
    def _agg_ops(agg, schema) -> set:
        """The primitive planes an aggregate's device specs need."""
        ops: set = {"rows"}
        for spec in agg.aggs:
            if not _needs_host_agg(spec, schema):
                ops.update(_PRIMITIVES[spec.func])
        return ops

    # ---- distributed aggregation pushdown ----------------------------------

    def _lastpoint_tag(self, table, where, agg, ts_range):
        """The group tag name when this query is lastpoint-shaped —
        every aggregate is chronological `last` on the device path, the
        single group key is a plain tag column, and nothing (WHERE,
        time range) restricts the row set the newest-first termination
        argument reasons over. None otherwise."""
        if agg is None or not agg.aggs or where is not None \
                or ts_range is not None:
            return None
        if any(spec.func != "last" or _needs_host_agg(spec, table.schema)
               for spec in agg.aggs):
            return None
        if len(agg.keys) != 1:
            return None
        _, kexpr = agg.keys[0]
        if not isinstance(kexpr, ast.Column):
            return None
        schema = table.schema
        tag_names = {c.name for c in schema.tag_columns}
        return kexpr.name if kexpr.name in tag_names else None

    def _bucket_topk_ranges(self, table, agg, sort, limit, offset, having,
                            ts_range) -> Optional[list]:
        """Candidate scan ranges for the bucket-top-k shape: a single
        date_bin/time_bucket group key, ordered by that key, with LIMIT.
        Only the newest (DESC) or oldest (ASC) k buckets can reach the
        output, so the scan starts at k buckets and widens 4x per attempt
        until the output fills or the original range is covered — every
        attempt is exact because ranges are bucket-aligned (a bucket
        inside the range holds ALL its rows; LWW dedup is ts-local).
        Returns None when the shape doesn't match or narrowing can't
        help. Reference runs the full aggregate then sorts
        (datafusion.rs); a TSDB's time-ordered file metadata makes the
        narrowing free."""
        if (agg is None or sort is None or limit is None
                or having is not None):
            return None
        if len(agg.keys) != 1 or len(sort.keys) != 1:
            return None
        name, kexpr = agg.keys[0]
        ob = sort.keys[0]
        if not (ob.expr == kexpr or (isinstance(ob.expr, ast.Column)
                                     and ob.expr.name == name)):
            return None
        schema = table.schema
        ts_col = schema.time_index
        if not (isinstance(kexpr, ast.FuncCall)
                and kexpr.name in ("date_bin", "time_bucket")
                and len(kexpr.args) == 2
                and isinstance(kexpr.args[0], ast.Interval)
                and isinstance(kexpr.args[1], ast.Column)
                and kexpr.args[1].name == ts_col.name):
            return None
        if not hasattr(self.engine, "ts_extent"):
            return None  # engine without metadata extents (remote proxy)
        unit = ts_col.dtype.time_unit.nanos_per_unit
        step = max(kexpr.args[0].nanos // unit, 1)
        k = int(limit) + int(offset or 0)
        exts = [self.engine.ts_extent(rid) for rid in table.region_ids]
        exts = [e for e in exts if e is not None]
        if not exts:
            return None
        dmin = min(e[0] for e in exts)
        dmax = max(e[1] for e in exts)
        lo0, hi0 = ts_range if ts_range else (-(1 << 62), 1 << 62)
        lo_full = max(lo0, dmin)
        hi_full = min(hi0, dmax + 1)  # half-open upper bound
        if hi_full <= lo_full:
            return None
        full = (lo_full, hi_full)
        desc = not ob.asc
        ranges: list = []
        span = k * step
        while True:
            if desc:
                lo = max((max(hi_full - span, lo_full) // step) * step,
                         lo_full)
                cand = (lo, hi_full)
            else:
                hi = min(-(-(min(lo_full + span, hi_full)) // step) * step,
                         hi_full)
                cand = (lo_full, hi)
            ranges.append(cand)
            if cand == full or len(ranges) > 12:
                break
            span *= 4
        if ranges[-1] != full:
            ranges.append(full)
        return ranges if len(ranges) > 1 else None

    def _try_fragment_pushdown(self, table, where, agg, having, project,
                               sort, limit, offset, ts_range,
                               scan_node) -> Optional[QueryResult]:
        """Classify the plan prefix, fan one PlanFragment out to each
        region's owner, and run the Final step over what returns:
        combine partial planes ("agg"), merge-and-resort candidates
        ("topk"), or treat the filtered-row union as the relation
        ("rows"). Returns None when nothing pushes — caller falls back
        to the gather-rows MergeScan path."""
        from greptimedb_tpu.query.dist_agg import combine_partials, merge_topk
        from greptimedb_tpu.query.dist_plan import classify_prefix

        out = classify_prefix(table, where, agg, project, sort, limit,
                              offset, ts_range, scan_node,
                              _needs_host_agg, _infer_dtype, _PRIMITIVES)
        if out is None:
            return None
        frag, mode = out
        lp_tag = None
        if mode == "agg" and os.environ.get("GTPU_LASTFRAG", "1") \
                not in ("0", "off"):
            # lastpoint pruning hint: an all-`last` single-tag aggregate
            # lets each region owner serve its partial from the newest-
            # first pruned scan (Region.scan_last) instead of decoding
            # the whole region — cluster mode used to pay the full raw
            # scan per datanode here (ROADMAP item 3 cliff).
            # GTPU_LASTFRAG=0 pins the unhinted fragment for A/B.
            lp_tag = self._lastpoint_tag(table, where, agg, ts_range)
            if lp_tag is not None:
                frag.stages.insert(0, {"op": "lastpoint", "tag": lp_tag})
        from greptimedb_tpu.utils.metrics import FRAGMENT_PUSHDOWNS

        FRAGMENT_PUSHDOWNS.inc(mode="lastpoint" if lp_tag else mode)
        with tracing.span("fragment_pushdown", mode=mode,
                          regions=len(table.region_ids)):
            rids = list(table.region_ids)
            if len(rids) > 1:
                # independent region RPCs: fan out so wall-clock is the
                # slowest region, not the sum (merge_scan polls all
                # region streams concurrently for the same reason)
                from concurrent.futures import ThreadPoolExecutor

                from greptimedb_tpu.utils import deadline as dl

                # the statement's CancelToken rides into every region
                # worker: a stalled region unwinds typed at the deadline
                # instead of pinning the fan-out past it
                one = dl.propagate(tracing.propagate(
                    lambda rid: self.engine.execute_fragment(rid, frag)))

                with ThreadPoolExecutor(
                        max_workers=min(8, len(rids))) as pool:
                    partials = list(pool.map(one, rids))
            else:
                partials = [self.engine.execute_fragment(rids[0], frag)]

        if mode == "agg":
            agg_stage = frag.stage("partial_agg")
            spec_slot: list[Optional[int]] = []
            for spec in agg.aggs:
                spec_slot.append(
                    None if spec.arg is None
                    else agg_stage["args"].index(spec.arg))
            combined = combine_partials(partials, len(agg.keys),
                                        tuple(agg_stage["ops"]))
            self.last_path = "lastfrag+pushdown" if lp_tag else "pushdown"
            return self._finalize_combined_agg(
                combined, table, agg, having, project, sort, limit,
                offset, spec_slot)

        merged = merge_topk(partials)
        if mode == "rows_agg":
            # non-decomposable aggregate over the filtered-row union:
            # regions shipped exactly the needed columns (already
            # LWW-deduped and filtered); re-enter the normal device
            # aggregation with the union as the relation
            if merged is None:
                self.last_path = "rows_agg_pushdown"
                return self._empty_agg_result(table, agg, having, project,
                                              sort, limit, offset)
            scan = _cols_to_scan(table, merged["cols"])
            with tracing.span("aggregate", rows=scan.num_rows):
                res = self._execute_agg(scan, table, None, agg, having,
                                        project, sort, limit, offset,
                                        scan_node)
            self.last_path = "rows_agg+" + (self.last_path or "")
            return res
        self.last_path = "topk_pushdown" if mode == "topk" \
            else "rows_pushdown"
        if merged is None:
            return _project_empty(project, table.schema)
        host_cols = merged["cols"]
        nrows = len(next(iter(host_cols.values()))) if host_cols else 0
        return self._post_process({}, None, None, project, sort, limit,
                                  offset, table, nrows, host_cols=host_cols)

    @_staged("assemble")
    def _finalize_combined_agg(self, combined, table, agg, having, project,
                               sort, limit, offset,
                               spec_slot) -> QueryResult:
        """Final step over combined [G, F] partial planes (the
        fragment pushdown)."""
        if combined is None:
            return self._empty_agg_result(table, agg, having, project,
                                          sort, limit, offset)
        planes = combined["planes"]
        g = len(combined["keys"][0]) if agg.keys else 1
        present = np.arange(g)
        env: dict = {}
        for i, (name, kexpr) in enumerate(agg.keys):
            env[kexpr] = combined["keys"][i]
        for spec, slot in zip(agg.aggs, spec_slot):
            env[spec.call] = _finalize_agg(spec.func, planes, slot,
                                           present)
        return self._post_process(env, agg, having, project, sort,
                                  limit, offset, table, g)

    def _bind_agg(self, scan, table, where, agg, scan_node) -> "_AggBinding":
        """Bind one aggregate to one scan's dictionaries: the predicate,
        the group keys with their decoders, the value planes' argument
        expressions and the primitive ops — what every route from a scan
        to its partial planes starts from."""
        schema = table.schema
        ctx = BindContext(schema, scan.tag_dicts)
        bound_where = bind_expr(where, ctx) if where is not None else None

        # group keys -> DeviceKeys (+ host factorized pre-keys)
        keys: list[DeviceKey] = []
        decoders = []  # per key: fn(int indices) -> value array, dtype
        extra_cols: dict[str, np.ndarray] = {}
        # host factorization of the group keys (date_bin over every
        # scanned row included) is aggregation work done on the host
        if not all(_key_from_plan(kexpr, ctx) for _, kexpr in agg.keys):
            self._whole_columns(scan, table)  # factorized over the rows
        with tracing.stage("host_agg", step="group_keys"):
            for i, (name, kexpr) in enumerate(agg.keys):
                dk, decode = self._plan_key(i, kexpr, ctx, scan, scan_node,
                                            extra_cols)
                keys.append(dk)
                decoders.append(decode)
        from greptimedb_tpu import config

        num_groups = 1
        for k in keys:
            num_groups *= k.size
        if num_groups >= _GID_SENTINEL:
            raise PlanError(
                f"group key space {num_groups} overflows the int64 id "
                "domain; add predicates or reduce keys"
            )
        # dense [G, F] planes up to the configured budget; beyond that the
        # sparse sort-compact path handles arbitrary cardinality.
        # sparse_groups_min (off by default) pulls smaller key products
        # onto the sparse path too — the lever for date_bin domains that
        # fit the dense budget but blow the fused 4096-segment envelope
        sparse = bool(keys) and (
            num_groups > config.dense_groups_max()
            or (config.sparse_groups_min() > 0
                and num_groups >= config.sparse_groups_min()))

        # aggregate args -> values matrix columns (host-computed
        # order-statistic aggs don't consume a device value plane)
        from greptimedb_tpu.query.host_agg import HOST_AGGS

        arg_exprs: list[ast.Expr] = []
        spec_slot: list[Optional[int]] = []
        for spec in agg.aggs:
            if spec.arg is None or _needs_host_agg(spec, schema):
                spec_slot.append(None)
                continue
            b = bind_expr(spec.arg, ctx)
            if b not in arg_exprs:
                arg_exprs.append(b)
            spec_slot.append(arg_exprs.index(b))
        ops = self._agg_ops(agg, schema)
        return _AggBinding(ctx, bound_where, keys, decoders, extra_cols,
                           num_groups, sparse, arg_exprs, spec_slot, ops)

    def _execute_agg(self, scan, table, where, agg, having, project, sort,
                     limit, offset, scan_node) -> QueryResult:
        schema = table.schema
        ts_name = schema.time_index.name
        self.last_partial_stats = None
        if scan is None:
            return self._empty_agg_result(table, agg, having, project, sort, limit, offset)
        b = self._bind_agg(scan, table, where, agg, scan_node)
        ctx, bound_where, keys, decoders = \
            b.ctx, b.bound_where, b.keys, b.decoders
        extra_cols, num_groups, sparse = b.extra_cols, b.num_groups, b.sparse
        arg_exprs, spec_slot, ops = b.arg_exprs, b.spec_slot, b.ops

        with tracing.stage("host_agg", step="boundary_firstlast"):
            reduced = self._boundary_firstlast(scan, table, agg,
                                               bound_where, keys, extra_cols)
        # incremental aggregation (ISSUE 13): immutable parts' [G, F]
        # partials come from the partial-aggregate cache; only uncached
        # parts + the memtable delta run kernels. Runs after the
        # boundary first/last reduction (whose candidate gather is
        # already snapshot-memoized) — a reduced scan has no per-part
        # identity and falls through to the classic kernels. Typed
        # fallback (PartialCacheIneligible) lands back here too.
        if reduced is None:
            res = self._try_incremental_agg(
                scan, table, bound_where, keys, decoders, arg_exprs, ops,
                num_groups, ts_name, ctx, extra_cols, agg, having, project,
                sort, limit, offset, spec_slot, sparse)
            if res is not None:
                return res
            self._whole_columns(scan, table)  # the classic kernels' input
        if reduced is not None:
            scan = reduced
        # tier decision on the POST-reduction row count: the boundary
        # fast path shrinks a 17M-row lastpoint to a few thousand
        # candidate rows, which no mesh dispatch amortizes
        tier = self.tier_for(agg, scan.num_rows)
        keys_t, args_t, ops_t = tuple(keys), tuple(arg_exprs), \
            tuple(sorted(ops))
        stream_args = (scan, table, bound_where, keys_t, args_t, ops_t,
                       num_groups, ts_name, ctx, extra_cols, sparse)
        # first-touch hedge: serve THIS query host-side while the device
        # fold of its shape compiles on a background thread
        if self.router.hedges(tier):
            wkey = whole_scan_key(
                schema, split_operands(bound_where, schema)[0], keys_t,
                args_t, ops_t, num_groups, sparse,
                (block_size_for(scan.num_rows),) if sparse
                else tuple(e.block for e in _block_plan(scan)),
                self._lww_masked(scan, table, ctx),
                self._value_flags(scan, args_t))
            if self.router.needed(wkey):
                self.router.kick(
                    wkey, lambda: self._stream_agg(*stream_args),
                    "device warm-up failed for this query shape; it "
                    "stays on the host tier")
                tier = "host"
        self.last_tier = tier
        t0 = time.perf_counter()
        with TierCtx(tier):
            acc, sparse_gids = self._stream_agg(*stream_args)
        # measured-routing feed: what this tier actually cost for this
        # scan size (results are materialized host-side by here, so the
        # clock covers upload + kernels + readback). last_tier is the
        # EFFECTIVE tier — a mesh-routed query that degraded to the
        # single-device paths must feed the device history, not mesh's
        self.router.note(self.last_tier, scan.num_rows,
                         time.perf_counter() - t0)
        if reduced is not None:
            self.last_path = "boundary+" + (self.last_path or "")
        host_info = (scan, extra_cols, bound_where, ctx, num_groups)
        return self._agg_tail(acc, sparse_gids, agg, keys, decoders,
                              spec_slot, host_info, having, project, sort,
                              limit, offset, table)

    # ---- incremental aggregation (partial-aggregate cache) -----------------

    def _try_incremental_agg(self, scan, table, bound_where, keys, decoders,
                             arg_exprs, ops, num_groups, ts_name, ctx,
                             extra_cols, agg, having, project, sort, limit,
                             offset, spec_slot,
                             sparse=False) -> Optional[QueryResult]:
        """Serve this aggregate from per-part cached partials + a
        delta-only fold (query/partial_cache.py module docstring), or
        return None for the classic whole-scan paths. Any gate the
        per-part decomposition cannot prove raises the typed
        PartialCacheIneligible internally and counts one `fallback`."""
        from greptimedb_tpu.query import partial_cache as pc
        from greptimedb_tpu.query.dist_agg import combine_partials
        from greptimedb_tpu.utils.metrics import PARTIAL_AGG_CACHE_EVENTS

        if not pc.enabled() or _PARTIAL_DISABLED["flag"]:
            return None
        try:
            t0 = time.perf_counter()
            partials, stats, tier = self._incremental_partials(
                scan, table, bound_where, keys, decoders, arg_exprs, ops,
                num_groups, ts_name, ctx, extra_cols, agg, sparse)
        except pc.PartialCacheIneligible:
            PARTIAL_AGG_CACHE_EVENTS.inc(event="fallback")
            return None
        except ScanExpired:
            raise  # not a failure of this path: run() retakes the scan
        except PlanError:
            # a planning error (e.g. a substituted rollup plan probing a
            # column the companion scan lacks) is the GUARDED-FALLBACK
            # signal upstream relies on — the classic path would raise
            # the identical error here, so propagate it and never latch
            raise
        except Exception:  # noqa: BLE001 — degrade, don't fail the query
            # an unexpected incremental failure (compile, OOM) must not
            # take serving down: latch the path off and let the classic
            # whole-scan kernels answer this and later queries — the
            # same degradation contract as the fused-kernel latch
            _note_degradation(
                "partial_latch",
                "incremental aggregation failed; serving this and later "
                "queries through the classic paths")
            _PARTIAL_DISABLED["flag"] = True
            PARTIAL_AGG_CACHE_EVENTS.inc(event="fallback")
            return None
        # the fold of the per-part partials runs in numpy on the host
        with tracing.stage("host_agg", step="combine_partials",
                           parts=stats["parts"],
                           part_hits=stats["part_hits"],
                           delta_rows=stats["delta_rows"],
                           total_rows=stats["total_rows"]):
            combined = combine_partials(partials, len(agg.keys),
                                        tuple(sorted(ops)))
        # measured-routing feed: the fold only ran kernels over the
        # DELTA rows — recording a cache-served query against the full
        # scan size would teach the router that this tier folds 17M
        # rows in a millisecond and misroute non-cacheable queries of
        # the same size class. Pure-cache serves feed nothing.
        if stats["delta_rows"]:
            self.router.note(tier, stats["delta_rows"],
                             time.perf_counter() - t0)
        self.last_path = "incremental_sparse" if stats.get("sparse") \
            else "incremental"
        self.last_partial_stats = stats
        return self._finalize_combined_agg(combined, table, agg, having,
                                           project, sort, limit, offset,
                                           spec_slot)

    def _incremental_partials(self, scan, table, bound_where, keys,
                              decoders, arg_exprs, ops, num_groups, ts_name,
                              ctx, extra_cols, agg, sparse=False,
                              device=None):
        """Gather cached part partials, compute the uncached parts and
        the memtable delta with the SAME per-block kernel the classic
        dense path runs, and return the part-ordered partial list (the
        left-fold order combine_partials preserves). Raises
        PartialCacheIneligible when the per-part decomposition is not
        provably exact.

        Past the dense cache cap (or when the query is already sparse),
        the per-part fold sort-compacts instead: partials carry only the
        OBSERVED groups' value-keyed planes ([U, F], U <= part rows) —
        the 64k-group fallback becomes a different per-part kernel, and
        the value-keyed combine (query/dist_agg.py) is cardinality-
        oblivious either way.

        `device`: the chip of a region of a multi-region table
        (`_try_region_fanout`) — every part computes there, nothing is
        hedged or routed."""
        from collections import OrderedDict as _OrderedDict

        from greptimedb_tpu import config
        from greptimedb_tpu.query import partial_cache as pc
        from greptimedb_tpu.utils.metrics import PARTIAL_AGG_DELTA_ROWS

        schema = table.schema
        if scan.region_id < 0:
            raise pc.PartialCacheIneligible("synthetic scan")
        if any(_needs_host_agg(spec, schema) for spec in agg.aggs):
            raise pc.PartialCacheIneligible("host-side aggregate")
        # past the dense cache cap the fold goes sparse instead of
        # falling back (value-keyed partials never materialize [G, F])
        use_sparse = sparse or num_groups > pc.groups_max()
        # DELETE voids the decomposition exactly like scan_last: a
        # tombstone may mask rows in a different part
        # (memoized on the snapshot, shared with the boundary fast path).
        # Decided per part, and for a part whose bytes this request
        # never reads from what the region noted when the file was
        # written or first decoded (ScanData.has_delete): SSTs are
        # immutable, so a file once known delete-free stays so. A DELETE
        # acknowledged after a partial was cached sits in the memtable
        # slice of this snapshot, or in a file flushed since — a new
        # file, whose flag was noted at its flush — so it is seen here
        # and the typed fallback below answers exactly; an all-hit
        # answer never passes a tombstone by.
        with tracing.stage("scan", table=table.name, regions=1,
                           step="tombstone_probe"):
            has_delete = scan.has_delete()
        if has_delete:
            raise pc.PartialCacheIneligible("tombstones reachable")

        plan = _block_plan(scan)
        parts: "_OrderedDict[tuple, list]" = _OrderedDict()
        mem_entries: list[_BlockEntry] = []
        for e in plan:
            if e.pkey is not None:
                parts.setdefault(e.pkey, []).append(e)
            else:
                mem_entries.append(e)
        if not parts:
            raise pc.PartialCacheIneligible("no immutable parts")
        for pk, es in parts.items():
            if len(es) != 1:
                # one-device-block-per-part gate: the cached partial
                # must BE the part's left-fold contribution for combine
                # order to reproduce the classic block-sequential
                # association bit-for-bit
                raise pc.PartialCacheIneligible("multi-block part")
        # LWW dedup is whole-scan: a newer duplicate in part Q can kill
        # a row in part P. Duplicates share an exact (series, ts)
        # instant, so pairwise-disjoint part/memtable ts extents prove
        # the dedup part-local — the sliced global mask then equals the
        # part's own LWW mask bit-for-bit, and the mask (whole-scan work
        # over whole columns) is only built when a part actually has to
        # be computed. Where extents overlap (late writes, a resent
        # backlog) a part's partial is still a function of the file's
        # rows and of the rows of it that lost: the mask is made first
        # (once a data version, _maybe_dedup) and the slice that falls
        # on a part that lost rows is part of that partial's key
        whole = None
        if not table.append_mode and scan.needs_dedup \
                and not self._parts_ts_disjoint(scan, ts_name):
            whole = self._maybe_dedup(scan, table, ctx)

        acc_dtype = jnp.dtype(config.compute_dtype())
        ops_t = tuple(sorted(ops))
        fp = pc.shape_fingerprint(bound_where, keys,
                                  [kexpr for _, kexpr in agg.keys],
                                  arg_exprs, ops_t, acc_dtype)
        if use_sparse:
            # sparse partials fold sorted (different float association
            # than the dense scatter) — never mix with dense cache hits
            fp = fp + ("sparse",)
        cache = pc.global_cache()
        # probe the cache BEFORE routing: only the delta (uncached parts
        # + memtable) runs kernels, and a 50-row warm delta amortizes no
        # mesh dispatch — the same argument as the boundary fast path's
        # post-reduction tier decision
        probed: list[tuple] = []
        delta_est = sum(e.end - e.start for e in mem_entries)
        for pk, (entry,) in parts.items():
            key = ("part", scan.region_id, pk[0], pk[1], pk[2], fp,
                   _lost_rows_digest(whole, entry))
            p = cache.get(key)
            probed.append((key, entry, p))
            if p is None:
                delta_est += entry.end - entry.start
        tier = "mesh" if device is not None \
            else self.tier_for(agg, delta_est)
        # first-touch hedge (the classic paths' 40s-cold-start fix must
        # not regress here): until this shape's per-part kernel has
        # compiled on the accelerator, folds serve host-side and a
        # background thread warms the device. One key, and one warm-up
        # fold, per block size this request dispatches: a part's program
        # knows its own block, not the request's other parts
        cold: dict[tuple, _BlockEntry] = {}
        if device is None and delta_est > 0 and self.router.hedges(tier):
            shape = split_operands(bound_where, schema)[0]
            for e in [entry for _k, entry, p in probed if p is None] \
                    + mem_entries:
                hkey = incremental_key(
                    schema, shape, tuple(keys), tuple(arg_exprs), ops_t,
                    acc_dtype, num_groups, use_sparse, e.block,
                    self._lww_masked(scan, table, ctx))
                if hkey not in cold and self.router.needed(hkey):
                    cold[hkey] = e
        hedge = bool(cold)
        if hedge:
            tier = "host"
        self.last_tier = tier
        place = part_placement(self.mesh, tier, scan) if device is None \
            else (lambda fid: OnShard(device))

        tag_names = frozenset(ctx.tag_names)
        float_fields = {c.name for c in schema.field_columns
                        if c.dtype.is_float}
        col_names = self._device_columns(scan, bound_where, keys, arg_exprs,
                                         ts_name, extra_cols)
        kw = dict(where=bound_where, keys=tuple(keys),
                  agg_args=tuple(arg_exprs), ops=ops_t,
                  num_segments=num_groups, ts_name=ts_name,
                  tag_names=tag_names, schema=schema,
                  need_ts=bool({"first", "last"} & set(ops)),
                  acc_dtype=acc_dtype)
        strides = _strides([k.size for k in keys])

        def cast_of(name):
            return acc_dtype if name in float_fields else None

        def fetch_cols(entry):
            return {name: self._device_block(scan, name, entry, extra_cols,
                                             cast_of(name))
                    for name in col_names}

        def entry_dmask(entry):
            dedup_mask = self._maybe_dedup(scan, table, ctx)
            return None if dedup_mask is None else _pad_device_mask(
                dedup_mask, entry.start, entry.end, entry.block)

        def compute_partial_dense(entry):
            with _kstage("upload"):
                cols = fetch_cols(entry)
            with _kstage("device"):
                out = _aggregate(_agg_block_jit, cols,
                                 jnp.asarray(entry.end - entry.start),
                                 entry_dmask(entry), **kw)
            with _kstage("readback"):
                planes = {op: _readback(v) for op, v in out.items()}
            # keyed aggregates keep only observed groups (matching the
            # per-region Partial step)
            return _acc_partial(planes, None, keys, decoders,
                                bool(agg.keys))

        sparse_kw = {k: v for k, v in kw.items() if k != "num_segments"}

        def compute_partial_sparse(entry):
            # sort-compact the part's own rows: the cap is one device
            # block (observed groups can't exceed part rows), so the
            # 64k dense cache ceiling never enters the per-part shapes
            cap = min(entry.block, config.sparse_groups_max())
            with _kstage("upload"):
                cols = fetch_cols(entry)
            with _kstage("device"):
                out, uniq, n_groups = _aggregate(
                    _agg_block_sparse, cols,
                    jnp.asarray(entry.end - entry.start),
                    entry_dmask(entry), cap=cap, **sparse_kw)
            with _kstage("readback"):
                u = int(n_groups)
            if u > cap:
                raise PlanError(
                    f"part observed {u} distinct groups, exceeding the "
                    f"sparse cap {cap}; raise "
                    "GREPTIMEDB_TPU_SPARSE_GROUPS_MAX or add predicates")
            with _kstage("readback"):
                gids = _readback(uniq)[:u]
                planes = {op: _readback(v)[:u] for op, v in out.items()}
            key_cols = []
            for i, decode in enumerate(decoders):
                idx = (gids // strides[i]) % keys[i].size
                col, _ = decode(idx)
                key_cols.append(np.asarray(col))
            return {"keys": key_cols, "planes": planes}

        compute_partial = compute_partial_sparse if use_sparse \
            else compute_partial_dense

        for hkey, entry in cold.items():
            # ONE part's fold compiles the per-part kernel of its block
            self.router.kick(
                hkey, functools.partial(compute_partial, entry),
                "device warm-up of the incremental per-part kernel "
                "failed; the shape's delta folds stay on the host tier")

        # bytes on demand: only a missed part whose column blocks are
        # not all in the HBM hot set needs its rows. Those decode a wave
        # of the scan pool's width at a time — in parallel, as the
        # whole-scan decode ran — and are held just while the wave's
        # partials compute; nothing is concatenated. It is scan work
        # wherever it happens: the fetch is a `scan` stage segment
        # inside the fold
        def needs_rows(entry):
            with place(entry.pkey[0]):
                return any(
                    not self.cache.resident(self._hot_key(
                        scan, entry, name, str(cast_of(name))))
                    for name in col_names if name not in extra_cols)

        def fetch_wave(wave):
            ranges = [(e.start, e.end) for _k, e in wave if needs_rows(e)]
            if not ranges or scan.materialized:
                return None
            before = scan_io_counters()
            with tracing.stage("scan", table=table.name, regions=1,
                               on_demand=True) as attrs:
                handle = scan.hold_rows(ranges)
                attrs.update(scan_io_since(before))
            return handle

        missed = [(key, entry) for key, entry, p in probed if p is None]
        width = scan.fetch_width(len(missed))
        computed: dict[tuple, dict] = {}
        for at in range(0, len(missed), width):
            wave = missed[at:at + width]
            handle = fetch_wave(wave)
            try:
                for key, entry in wave:
                    epoch = cache.epoch(scan.region_id)
                    with place(key[2]):
                        computed[key] = compute_partial(entry)
                    cache.put(key, computed[key], epoch=epoch)
            finally:
                scan.release_rows(handle)
        partials: list[dict] = []
        hits = misses = 0
        delta_rows = cached_rows = 0
        for key, entry, p in probed:
            if p is None:
                p = computed[key]
                misses += 1
                delta_rows += entry.end - entry.start
            else:
                hits += 1
                cached_rows += entry.end - entry.start
            partials.append(p)
        mem_rows = 0
        for entry in mem_entries:
            with place(None):
                partials.append(compute_partial(entry))
            mem_rows += entry.end - entry.start
        delta_rows += mem_rows
        if delta_rows:
            PARTIAL_AGG_DELTA_ROWS.inc(float(delta_rows), kind="delta")
        if cached_rows:
            PARTIAL_AGG_DELTA_ROWS.inc(float(cached_rows), kind="cached")
        stats = {"parts": len(parts), "part_hits": hits,
                 "part_misses": misses, "delta_rows": delta_rows,
                 "cached_rows": cached_rows, "memtable_rows": mem_rows,
                 "total_rows": scan.num_rows, "sparse": use_sparse}
        if use_sparse:
            from greptimedb_tpu.utils.metrics import SPARSE_DISPATCHES

            SPARSE_DISPATCHES.inc(path="incremental")
        return partials, stats, tier

    def _parts_ts_disjoint(self, scan, ts_name: str) -> bool:
        """Whether every SST part's ts extent (and the memtable tail's)
        is pairwise disjoint — the proof that LWW dedup cannot cross a
        part seam. From FileMeta for a part not read yet, else one O(N)
        min/max pass; memoized on the snapshot."""
        cached = getattr(scan, "_parts_ts_disjoint_cache", None)
        if cached is not None:
            return cached
        spans = sorted(scan.segment_ts_extents(ts_name))
        ok = all(spans[i][1] < spans[i + 1][0]
                 for i in range(len(spans) - 1))
        scan._parts_ts_disjoint_cache = ok
        return ok

    @_staged("assemble")
    def _agg_tail(self, acc, sparse_gids, agg, keys, decoders, spec_slot,
                  host_info, having, project, sort, limit, offset,
                  table) -> QueryResult:
        """Shared host tail: decode present groups' keys, finalize
        aggregates, run HAVING/ORDER/LIMIT over the G-row result."""
        from greptimedb_tpu.query.host_agg import HOST_AGGS

        rows = acc["rows"][:, 0] if acc["rows"].ndim == 2 else acc["rows"]
        if sparse_gids is not None:
            # sparse: acc rows [0, U) are the observed groups, in
            # ascending global-id order
            present = np.arange(len(sparse_gids))
            present_gids = sparse_gids
        elif agg.keys:
            present = np.flatnonzero(rows > 0)
            present_gids = present
        else:
            present = np.arange(1)
            present_gids = present
        env: dict = {}
        # decode group key columns
        strides = _strides([k.size for k in keys])
        key_cols: dict[str, tuple[np.ndarray, Optional[DataType]]] = {}
        for i, ((name, kexpr), decode) in enumerate(zip(agg.keys, decoders)):
            idx = (present_gids // strides[i]) % keys[i].size
            col, dtype = decode(idx)
            env[kexpr] = col
            key_cols[name] = (col, dtype)
        # aggregate outputs
        host_specs = [s for s in agg.aggs
                      if _needs_host_agg(s, table.schema)]
        for spec, slot in zip(agg.aggs, spec_slot):
            if _needs_host_agg(spec, table.schema):
                continue
            env[spec.call] = _finalize_agg(spec.func, acc, slot, present)
        if host_specs:
            scan, extra_cols, bound_where, ctx, num_groups = host_info
            self._host_aggs(host_specs, keys, scan, extra_cols, bound_where,
                            table, ctx, num_groups, present, env,
                            sparse_gids)

        return self._post_process(env, agg, having, project, sort, limit, offset,
                                  table, len(present))

    def _boundary_firstlast(self, scan, table, agg, bound_where, keys,
                            extra_cols) -> Optional[ScanData]:
        """Lastpoint-class fast path: when every aggregate is first/last
        (by time index) and grouping is by tag columns only, the winners
        can only sit at per-series run boundaries of the (tags..., ts,
        seq)-sorted SST segments — gather those few rows on host and run
        the normal kernel over the tiny subset instead of reducing the
        whole scan (reference reads the same order per file,
        mito2/src/read/merge.rs; TSBS `lastpoint` is the headline user).

        Correctness sketch (LWW): within one sorted segment the last row
        of a series' run carries its max ts and, among duplicates of that
        ts, the max seq; the global max-seq version of the max-ts instant
        lives in SOME segment where it is that segment's boundary row, so
        the candidate set always contains the LWW winner and the subset
        dedup selects it. Mirrored for `first` via the end of the first
        (tags, ts) sub-run. Memtable rows are unsorted and are included
        wholesale. DELETE tombstones void the argument (the newest row
        may be a tombstone, making an interior row the answer) — any
        tombstone in the scan disables the path.

        An append-mode table's scan holds the tags the statement names,
        not the whole key (Region.scan `full_key`): the group keys are
        among them. Runs are then cut where a held tag changes or the
        time index falls: several series of one group may share a run,
        but inside it time never falls, so its first and last rows still
        hold the run's first and last instants, and a group's are among
        its runs'. (Rows of one group that tie on the instant have no
        defined winner without last-write-wins, on any path.)"""
        offsets = scan.sorted_part_offsets
        if len(offsets) < 2 or offsets[-1] == 0:
            return None
        if bound_where is not None or extra_cols:
            return None
        if not agg.aggs or any(
                spec.func not in ("first", "last")
                or _needs_host_agg(spec, table.schema)
                for spec in agg.aggs):
            return None
        if not all(k.kind == "tag" for k in keys):
            return None
        held = [c.name for c in table.schema.tag_columns
                if c.name in scan.tag_dicts]
        whole_key = len(held) == len(table.schema.tag_columns)
        if not whole_key and self._dedups(scan, table):
            return None  # a last-write-wins merge reads the whole key
        cached = getattr(scan, "_boundary_fl_cache", None)
        if cached is not None:
            return cached if cached is not False else None
        self._whole_columns(scan, table)  # run boundaries over the rows
        if scan.has_delete():
            scan._boundary_fl_cache = False
            return None

        n = scan.num_rows
        send = offsets[-1]  # end of the sorted region
        # row i starts a new series run when any tag code differs from
        # row i-1, or i is a segment seam (sortedness restarts there)
        new_run = np.zeros(send, dtype=bool)
        new_run[0] = True
        for name in held:
            col = scan.columns[name]
            new_run[1:] |= col[1:send] != col[: send - 1]
        seams = np.asarray(offsets[1:-1], dtype=np.int64)
        new_run[seams[seams < send]] = True
        ts = scan.columns[table.schema.time_index.name]
        if not whole_key:
            new_run[1:] |= ts[1:send] < ts[: send - 1]
        new_sub = new_run.copy()
        new_sub[1:] |= ts[1:send] != ts[: send - 1]
        run_start = np.flatnonzero(new_run)
        run_end = np.append(run_start[1:] - 1, send - 1)
        # ends of (tags, ts) sub-runs: max-seq row of each instant
        sub_end = np.flatnonzero(np.append(new_sub[1:], True))
        # `first` winner candidate: end of the FIRST sub-run in each run
        first_end = sub_end[np.searchsorted(sub_end, run_start)]
        parts = [run_start, run_end, first_end]
        if send < n:
            parts.append(np.arange(send, n))
        idx = np.unique(np.concatenate(parts))
        if idx.size >= n * _BOUNDARY_MAX_FRACTION:
            scan._boundary_fl_cache = False
            return None
        reduced = ScanData(
            schema=scan.schema,
            columns={k: v[idx] for k, v in scan.columns.items()},
            seq=scan.seq[idx],
            op_type=scan.op_type[idx],
            tag_dicts=scan.tag_dicts,
            num_rows=idx.size,
            needs_dedup=scan.needs_dedup,
            region_id=scan.region_id,
            data_version=scan.data_version,
            scan_fingerprint=scan.scan_fingerprint + ("__boundary_fl__",),
        )
        reduced._tail_start = int(np.searchsorted(idx, send))
        scan._boundary_fl_cache = reduced
        return reduced

    def _execute_agg_stream(self, stream, table, where, agg, having, project,
                            sort, limit, offset, scan_node) -> QueryResult:
        """Bounded-memory aggregation: lazy scan chunks fold into a
        device-resident accumulator (see ScanStream). Raises _NotStreamable
        for shapes that need the whole scan on host (generic keys, host
        order statistics, sparse cardinality)."""
        from greptimedb_tpu import config
        from greptimedb_tpu.query.host_agg import HOST_AGGS

        schema = table.schema
        ts_name = schema.time_index.name
        ctx = BindContext(schema, stream.tag_dicts)
        bound_where = bind_expr(where, ctx) if where is not None else None

        keys: list[DeviceKey] = []
        decoders = []
        for i, (name, kexpr) in enumerate(agg.keys):
            dk, decode = self._plan_key_stream(i, kexpr, ctx, stream, scan_node)
            keys.append(dk)
            decoders.append(decode)
        num_groups = 1
        for k in keys:
            num_groups *= k.size
        if num_groups > config.dense_groups_max():
            raise _NotStreamable("sparse cardinality")

        arg_exprs: list[ast.Expr] = []
        spec_slot: list[Optional[int]] = []
        for spec in agg.aggs:
            if _needs_host_agg(spec, schema):
                raise _NotStreamable(f"host aggregate {spec.func}")
            if spec.arg is None:
                spec_slot.append(None)
                continue
            b = bind_expr(spec.arg, ctx)
            if b not in arg_exprs:
                arg_exprs.append(b)
            spec_slot.append(arg_exprs.index(b))
        ops: set = {"rows"}
        for spec in agg.aggs:
            ops.update(_PRIMITIVES[spec.func])
        need_ts = bool({"first", "last"} & ops)

        self.last_path = "stream"
        # one stage for the whole fold: chunk decode and upload run on
        # the prefetch thread and overlap the step dispatches here
        with _kstage("device", kernel="stream_fold"):
            acc = self._fold_stream(stream, table, bound_where, tuple(keys),
                                    tuple(arg_exprs), tuple(sorted(ops)),
                                    num_groups, ts_name, ctx, need_ts,
                                    len(arg_exprs))
        return self._agg_tail(acc, None, agg, keys, decoders, spec_slot,
                              None, having, project, sort, limit, offset,
                              table)

    def _fold_stream(self, stream, table, bound_where, keys, arg_exprs, ops,
                     num_groups, ts_name, ctx, need_ts, nf):
        from greptimedb_tpu import config

        schema = table.schema
        acc_dtype = jnp.dtype(config.compute_dtype())
        tag_names = frozenset(ctx.tag_names)
        float_fields = {c.name for c in schema.field_columns if c.dtype.is_float}
        from greptimedb_tpu.query.expr import collect_columns

        needed: set[str] = set()
        collect_columns(bound_where, needed)
        for a in arg_exprs:
            collect_columns(a, needed)
        for k in keys:
            needed.add(k.column)
        needed.add(ts_name)
        names = sorted(needed)

        block = config.stream_block_rows()
        if not need_ts and self._prepared_ok(arg_exprs, ops, (), schema, {}):
            # streaming twin of the prepared dense path: the chunk's
            # value/validity plane is built once host-side and folded with
            # ONE dead-segment segment-sum — no per-query [N, F] masking
            self.last_path = "stream_prepared"
            return self._fold_stream_prepared(
                stream, bound_where, keys, arg_exprs, ops, num_groups,
                tag_names, float_fields, schema, block, acc_dtype,
                max(nf, 1))
        kw = dict(where=bound_where, keys=keys, agg_args=arg_exprs, ops=ops,
                  num_segments=num_groups, ts_name=ts_name,
                  tag_names=tag_names, schema=schema, need_ts=need_ts,
                  acc_dtype=acc_dtype)
        def build_blocks():
            for cols_np, nrows in stream.chunks():
                for start in range(0, nrows, block):
                    end = min(start + block, nrows)
                    dev = {}
                    for name in names:
                        arr = pad_rows(np.asarray(cols_np[name][start:end]),
                                       block)
                        if name in float_fields and arr.dtype != acc_dtype:
                            arr = arr.astype(acc_dtype)
                        dev[name] = jnp.asarray(arr)
                    yield dev, jnp.asarray(end - start)

        acc_dev = None
        step = _agg_step_donated if _donate_stream_buffers() else _agg_step
        gen = _prefetch(build_blocks())
        try:
            for dev, n_valid in gen:
                device_telemetry.count_h2d(
                    sum(a.nbytes for a in dev.values()))
                if acc_dev is None:
                    acc_dev = _aggregate(_agg_block_jit, dev, n_valid,
                                         None, **kw)
                else:
                    acc_dev = _aggregate(step, acc_dev, dev, n_valid,
                                         **kw)
        finally:
            # stop the producer BEFORE the caller's stream.close() drops
            # SST pins: a generator left suspended would only clean up at
            # GC, racing the producer's reads against file purge
            gen.close()
        nf = max(nf, 1)
        if acc_dev is None:
            # pruned-empty stream: identity planes
            acc = {}
            for op in ops:
                if op == "rows":
                    acc[op] = np.zeros((num_groups, 1), dtype=np.int64)
                elif op == "count":
                    acc[op] = np.zeros((num_groups, nf), dtype=np.int64)
                elif op in ("sum", "sumsq"):
                    acc[op] = np.zeros((num_groups, nf))
                elif op in ("min", "max", "first", "last"):
                    acc[op] = np.full((num_groups, nf), np.nan)
                    if op in ("first", "last"):
                        acc[op + "_ts"] = np.zeros(num_groups, dtype=np.int64)
            return acc
        acc = {k: _readback(v) for k, v in acc_dev.items()}
        for k in ("count", "rows"):
            if k in acc:
                acc[k] = acc[k].astype(np.int64)
        return acc

    def _fold_stream_prepared(self, stream, bound_where, keys, arg_exprs,
                              ops, num_groups, tag_names, float_fields,
                              schema, block, acc_dtype, nf):
        """Prepared-plane streaming fold (see _prep_stream_step). Plane
        NaN-handling is conservative (`has_nan=True`): a stream can't
        pre-scan its chunks for NULLs the way the materialized path can."""
        from types import SimpleNamespace

        from greptimedb_tpu.query.expr import collect_columns

        arg_names = tuple(a.name for a in arg_exprs)
        aux: set[str] = set()
        collect_columns(bound_where, aux)
        for k in keys:
            aux.add(k.column)
        aux_names = sorted(aux)
        prep_dtype = jnp.dtype(jnp.float64) if "sumsq" in ops else acc_dtype
        kw = dict(where=bound_where, keys=keys, num_segments=num_groups,
                  tag_names=tag_names, schema=schema)
        def build_blocks():
            for cols_np, nrows in stream.chunks():
                shim = SimpleNamespace(columns=cols_np)
                for start in range(0, nrows, block):
                    end = min(start + block, nrows)
                    dev = {}
                    for name in aux_names:
                        arr = pad_rows(np.asarray(cols_np[name][start:end]),
                                       block)
                        if name in float_fields and arr.dtype != acc_dtype:
                            arr = arr.astype(acc_dtype)
                        dev[name] = jnp.asarray(arr)
                    dev["__prep__"] = jnp.asarray(_build_prep(
                        shim, arg_names, start, end, block, prep_dtype,
                        True, None))
                    if "min" in ops:
                        dev["__prep_min__"] = jnp.asarray(_build_prep(
                            shim, arg_names, start, end, block, acc_dtype,
                            False, "min"))
                    if "max" in ops:
                        dev["__prep_max__"] = jnp.asarray(_build_prep(
                            shim, arg_names, start, end, block, acc_dtype,
                            False, "max"))
                    if "sumsq" in ops:
                        dev["__prep_sq__"] = jnp.asarray(_build_prep(
                            shim, arg_names, start, end, block, prep_dtype,
                            False, "sq"))
                    yield dev, jnp.asarray(end - start)

        acc_dev = None
        step = _prep_stream_step_donated if _donate_stream_buffers() \
            else _prep_stream_step
        # double-buffered: the next chunk's SST read + plane build + H2D
        # copy overlap the device fold of the current one
        gen = _prefetch(build_blocks())
        try:
            for dev, n_valid in gen:
                device_telemetry.count_h2d(
                    sum(a.nbytes for a in dev.values()))
                acc_dev = _aggregate(step, acc_dev, dev, n_valid, **kw)
        finally:
            gen.close()  # see _fold_stream: producer must die before unpin
        G = num_groups
        acc: dict[str, np.ndarray] = {}
        if acc_dev is None:
            # pruned-empty stream: identity planes
            for op in ops:
                if op == "rows":
                    acc[op] = np.zeros((G, 1), dtype=np.int64)
                elif op == "count":
                    acc[op] = np.zeros((G, nf), dtype=np.int64)
                elif op in ("sum", "sumsq"):
                    acc[op] = np.zeros((G, nf))
                else:
                    acc[op] = np.full((G, nf), np.nan)
            return acc
        total = _readback(acc_dev["total"])
        sums = total[:, :nf]
        cnts = total[:, nf:2 * nf]
        rows = total[:, 2 * nf:2 * nf + 1]
        for op in ops:
            if op == "sum":
                acc[op] = sums
            elif op == "count":
                acc[op] = cnts.astype(np.int64)
            elif op == "rows":
                acc[op] = rows.astype(np.int64)
            elif op == "min":
                tmin = np.asarray(acc_dev["min"])
                acc[op] = np.where(np.isposinf(tmin), np.nan, tmin)
            elif op == "max":
                tmax = np.asarray(acc_dev["max"])
                acc[op] = np.where(np.isneginf(tmax), np.nan, tmax)
            elif op == "sumsq":
                acc[op] = np.asarray(acc_dev["sq"])
        return acc

    def _plan_key_stream(self, i, kexpr, ctx, stream, scan_node):
        """Key planning against stream metadata only (no data columns):
        tag keys decode from the registry dictionaries; time buckets get
        their extent from pruned-file stats. Anything needing the actual
        rows (generic expressions) is not streamable."""
        schema = ctx.schema
        ts_col = schema.time_index
        if isinstance(kexpr, ast.Column) and kexpr.name in ctx.tag_names:
            name = kexpr.name
            values = stream.tag_dicts[name]

            def decode_tag(idx, values=values):
                out = np.empty(len(idx), dtype=object)
                codes = idx - 1
                valid = codes >= 0
                out[valid] = values[codes[valid]]
                out[~valid] = None
                return out, DataType.STRING

            return DeviceKey("tag", name, len(values) + 1), decode_tag
        if _is_time_bucket(kexpr, ts_col.name):
            unit = ts_col.dtype.time_unit.nanos_per_unit
            step = max(kexpr.args[0].nanos // unit, 1)
            lo, hi = self._ts_bounds(
                scan_node, lambda: (stream.ts_min, stream.ts_max))
            base = int(np.floor_divide(lo, step))
            size = int(np.floor_divide(hi, step)) - base + 1

            def decode_bucket(idx, step=step, base=base, dtype=ts_col.dtype):
                return (idx.astype(np.int64) + base) * step, dtype

            return DeviceKey("bucket", ts_col.name, size, step=step,
                             base=base), decode_bucket
        raise _NotStreamable(f"group key {kexpr!r} needs materialized scan")

    @_staged("host_agg")
    def _host_aggs(self, host_specs, keys, scan, extra_cols, bound_where,
                   table, ctx, num_groups, present, env, sparse_gids=None):
        """Order-statistic aggregates (argmax/percentile/…) over host
        columns — see host_agg.py for the sort-based group pass. Uses the
        BOUND where/arg exprs (tag literals → codes, ts literals coerced),
        so host evaluation over the raw scan columns matches the device
        semantics exactly."""
        from greptimedb_tpu.query import host_agg as ha
        from greptimedb_tpu.query.expr import bind_expr, eval_host

        strides = _strides([k.size for k in keys])
        gid = ha.row_group_ids(keys, strides, scan, extra_cols)
        if sparse_gids is not None:
            # map global ids onto the compact [0, U) slots the device
            # kernel assigned (ascending global-id order); rows whose
            # group isn't observed are already masked out below
            num_groups = len(sparse_gids)
            gid = np.clip(np.searchsorted(sparse_gids, gid), 0,
                          max(num_groups - 1, 0))
        n = scan.num_rows
        dmask = self._maybe_dedup(scan, table, ctx)
        mask = ha.host_row_mask(
            scan, bound_where, table.schema, n,
            np.asarray(dmask)[:n] if dmask is not None else None)
        ts_name = table.schema.time_index.name
        for spec in host_specs:
            if spec.func not in ha.HOST_AGGS:
                # string-typed first/last/min/max: decode the argument to
                # real values and pick per group on host
                from greptimedb_tpu.datatypes.vector import DictVector

                if isinstance(spec.arg, ast.Column) and \
                        spec.arg.name in scan.tag_dicts:
                    vals = DictVector(
                        scan.columns[spec.arg.name],
                        scan.tag_dicts[spec.arg.name]).decode()
                else:
                    vals = np.asarray(eval_host(
                        spec.arg, scan.columns, table.schema, None, n),
                        dtype=object)
                vals = np.broadcast_to(vals, (n,))
                per_group = ha.compute_host_agg_str(
                    spec.func, gid, vals,
                    scan.columns[ts_name], mask, num_groups)
                env[spec.call] = per_group[present]
                continue
            bound_arg = bind_expr(spec.arg, ctx)
            vals = eval_host(bound_arg, scan.columns, table.schema, None, n)
            vals = np.broadcast_to(
                np.asarray(vals, dtype=np.float64), (n,))
            per_group = ha.compute_host_agg(
                spec.func, gid, vals, mask, num_groups, spec.extra_args)
            env[spec.call] = per_group[present]

    def _plan_key(self, i, kexpr, ctx, scan: ScanData, scan_node, extra_cols):
        schema = ctx.schema
        ts_col = schema.time_index
        if isinstance(kexpr, ast.Column) and kexpr.name in ctx.tag_names:
            name = kexpr.name
            card = len(scan.tag_dicts[name])
            values = scan.tag_dicts[name]

            def decode_tag(idx, values=values):
                out = np.empty(len(idx), dtype=object)
                codes = idx - 1
                valid = codes >= 0
                out[valid] = values[codes[valid]]
                out[~valid] = None
                return out, DataType.STRING

            return DeviceKey("tag", name, card + 1), decode_tag
        if _is_time_bucket(kexpr, ts_col.name):
            unit = ts_col.dtype.time_unit.nanos_per_unit
            step = max(kexpr.args[0].nanos // unit, 1)
            lo, hi = self._ts_bounds(
                scan_node, lambda: scan.ts_extent(ts_col.name))
            base = int(np.floor_divide(lo, step))
            size = int(np.floor_divide(hi, step)) - base + 1

            def decode_bucket(idx, step=step, base=base, dtype=ts_col.dtype):
                return (idx.astype(np.int64) + base) * step, dtype

            return DeviceKey("bucket", ts_col.name, size, step=step, base=base), decode_bucket
        # generic expression: factorize on host
        host_cols = dict(scan.columns)
        for c in schema.tag_columns:
            if c.name in host_cols:
                from greptimedb_tpu.datatypes.vector import DictVector
                host_cols[c.name] = DictVector(
                    scan.columns[c.name], scan.tag_dicts[c.name]
                ).decode()
        vals = np.asarray(eval_host(kexpr, host_cols, schema))
        if np.ndim(vals) == 0:
            vals = np.broadcast_to(vals, (scan.num_rows,))
        uniq, inverse = np.unique(vals, return_inverse=True)
        colname = f"__key_{i}"
        extra_cols[colname] = inverse.astype(np.int32)
        out_dtype = None
        if isinstance(kexpr, ast.Column) and kexpr.name in schema.names:
            out_dtype = schema.column(kexpr.name).dtype

        def decode_pre(idx, uniq=uniq, out_dtype=out_dtype):
            return uniq[idx], out_dtype

        return DeviceKey("pre", colname, max(len(uniq), 1)), decode_pre

    def _ts_bounds(self, scan_node, extent) -> tuple[int, int]:
        """Bucket-key bounds: the statement's own range where it gives
        one; `extent()` -> (min, max) of the scanned rows is only asked
        for a side the statement leaves open."""
        lo = hi = None
        if scan_node.ts_range is not None:
            lo, hi0 = scan_node.ts_range
            hi = None if hi0 is None else hi0 - 1
        if lo is None or hi is None:
            data_lo, data_hi = extent()
            lo = data_lo if lo is None else lo
            hi = data_hi if hi is None else hi
        return lo, hi

    def _stream_agg(self, scan: ScanData, table, bound_where, keys, arg_exprs,
                    ops, num_groups, ts_name, ctx, extra_cols, sparse=False):
        """Run the device aggregation; returns (acc planes, sparse group
        ids or None). Dense: planes indexed by global group id. Sparse:
        planes indexed by compact slot, plus the observed global ids."""

        with tracing.span("device_agg", rows=scan.num_rows,
                          groups=num_groups):
            return self._stream_agg_inner(
                scan, table, bound_where, keys, arg_exprs, ops, num_groups,
                ts_name, ctx, extra_cols, sparse)

    def _stream_agg_inner(self, scan, table, bound_where, keys, arg_exprs,
                          ops, num_groups, ts_name, ctx, extra_cols,
                          sparse=False):
        from greptimedb_tpu import config

        schema = table.schema
        acc_dtype = jnp.dtype(config.compute_dtype())
        device_col_names = self._device_columns(
            scan, bound_where, keys, arg_exprs, ts_name, extra_cols
        )
        n = scan.num_rows
        dedup_mask = self._maybe_dedup(scan, table, ctx)
        tag_names = frozenset(ctx.tag_names)
        float_fields = {
            c.name for c in schema.field_columns if c.dtype.is_float
        }

        # output layout (static): which float/int planes the kernel packs
        nf = max(len(arg_exprs), 1)
        produced_f, produced_i = [], []
        widths = {}
        for op in ops:
            if op in ("first", "last"):
                produced_f.append(op)
                widths[op] = nf
                produced_i.append(op + "_ts")
            elif op == "rows":
                produced_f.append(op)
                widths[op] = 1
            else:
                produced_f.append(op)
                widths[op] = nf
        float_ops = tuple(sorted(produced_f))
        int_ops = tuple(sorted(produced_i))
        pack_dtype = jnp.dtype(jnp.float64) if num_groups <= 4096 else acc_dtype
        if not jnp.issubdtype(pack_dtype, jnp.floating):
            pack_dtype = jnp.dtype(jnp.float64)
        if "sumsq" in float_ops:
            # f32 packing would destroy the precision the f64 moment
            # accumulation just preserved (see segment_agg)
            pack_dtype = jnp.dtype(jnp.float64)

        from greptimedb_tpu.parallel.mesh import COLLECTIVE_OPS

        if sparse:
            self.last_path = "sparse"
            if self.last_tier == "mesh":
                from greptimedb_tpu.parallel.sharded_dispatch import (
                    MeshIneligible,
                )

                try:
                    # per-shard sort-compact + gid-space combine: the
                    # compact slots differ per shard but the global ids
                    # they decode to don't, so the host merge is exact
                    # one stage per mesh dispatch: per-shard uploads,
                    # the shard_map program and the readback of its
                    # per-shard planes
                    with _kstage("device", kernel="agg_scan_sharded_sparse"):
                        return self._sparse_sharded_scan(
                            scan, self.mesh, device_col_names, extra_cols,
                            float_fields, acc_dtype, dedup_mask,
                            bound_where, keys, arg_exprs, ops, ts_name,
                            tag_names, schema, float_ops, int_ops, widths,
                            pack_dtype)
                except MeshIneligible:
                    self.last_tier = "device"
            return self._sparse_scan(
                scan, device_col_names, extra_cols, float_fields, acc_dtype,
                dedup_mask, bound_where, keys, arg_exprs, ops, ts_name,
                tag_names, schema, float_ops, int_ops, widths, pack_dtype)

        mesh = self.mesh
        # first/last produce int *_ts planes, but those are consumed
        # INSIDE the collective combine — only value planes leave the mesh
        ts_only_ints = bool(int_ops) and all(k.endswith("_ts")
                                             for k in int_ops)
        mesh_shape_ok = (mesh is not None and (not int_ops or ts_only_ints)
                         and set(ops) <= set(COLLECTIVE_OPS))
        if mesh_shape_ok and self.last_tier == "mesh":
            from greptimedb_tpu.parallel.sharded_dispatch import (
                MeshIneligible,
            )

            try:
                self.last_path = "sharded"
                with _kstage("device", kernel="agg_scan_sharded"):
                    packed_f = self._sharded_scan(
                        scan, mesh, device_col_names, extra_cols,
                        float_fields, acc_dtype, dedup_mask, bound_where,
                        keys, arg_exprs, ops, num_groups, ts_name,
                        tag_names, schema, float_ops, pack_dtype)
                return (_unpack_acc(packed_f, None, float_ops, (),
                                    widths), None)
            except MeshIneligible:
                # typed degradation: a plan/shape the shard dispatch
                # cannot serve falls back to the single-device paths
                self.last_tier = "device"
        elif self.last_tier == "mesh":
            # the router picked the mesh before seeing the op set; a
            # non-collective shape runs single-device and must report so
            self.last_tier = "device"
        prepared = self._prepared_ok(arg_exprs, ops, int_ops, schema,
                                     extra_cols)
        # first/last can't ride the PREPARED planes (no ts pairing) but
        # CAN ride the fused kernel: the kernel covers the other ops and
        # a per-block segment_agg folds the (value, ts) pairs alongside
        fused_extra = (not prepared and bool(int_ops)
                       and all(k.endswith("_ts") for k in int_ops)
                       and self._prepared_ok(
                           arg_exprs, set(ops) - {"first", "last"}, (),
                           schema, extra_cols))
        if prepared or fused_extra:
            arg_names = tuple(a.name for a in arg_exprs)
            aux_names = self._device_columns(
                scan, bound_where, keys, (), ts_name, extra_cols)
            plan = _block_plan(scan)
            if self._fused_ok(ops, arg_names, num_groups, scan):
                # fused Pallas path: ONE kernel per block over the RAW
                # hot-set columns — mask/validity/plane assembly never
                # touch HBM (ops/pallas_segment.py); degrades to the
                # prepared scatter path below on any kernel failure
                res = self._dense_fused_scan(
                    scan, plan, aux_names, arg_names, extra_cols,
                    float_fields, acc_dtype, dedup_mask, bound_where,
                    keys, ops, num_groups, ts_name, tag_names, schema,
                    float_ops, int_ops, pack_dtype)
                if res is not None:
                    packed_f, packed_i = res
                    self.last_path = "dense_fused"
                    return (_unpack_acc(packed_f, packed_i, float_ops,
                                        int_ops, widths), None)
        if prepared:
            # fast dense path: query-invariant [N, 2F+1] value/validity
            # planes are HBM-cached; per query only [N] masks/keys run
            self.last_path = "dense_prepared"
            has_nan = self._scan_has_nan(scan, arg_names)
            # variance/stddev difference two moments: BOTH must carry f64
            # even on the f32 fast path (see segment_agg) — the sum plane
            # included, or the cancellation eats the f64 sq plane's work
            prep_dtype = jnp.dtype(jnp.float64) if "sumsq" in ops \
                else acc_dtype

            def fetch_block(entry, prefetch_only=False):
                cols = {}
                for name in aux_names:
                    cols[name] = self._device_block(
                        scan, name, entry, extra_cols,
                        acc_dtype if name in float_fields else None,
                        prefetch_only=prefetch_only,
                    )
                cols["__prep__"] = self._prep_plane(
                    scan, arg_names, entry, prep_dtype,
                    has_nan, prefetch_only=prefetch_only)
                if "min" in ops:
                    cols["__prep_min__"] = self._prep_extreme_plane(
                        scan, arg_names, entry, acc_dtype,
                        "min", prefetch_only=prefetch_only)
                if "max" in ops:
                    cols["__prep_max__"] = self._prep_extreme_plane(
                        scan, arg_names, entry, acc_dtype,
                        "max", prefetch_only=prefetch_only)
                if "sumsq" in ops:
                    cols["__prep_sq__"] = self._prep_extreme_plane(
                        scan, arg_names, entry, prep_dtype,
                        "sq", prefetch_only=prefetch_only)
                return cols

            blocks, n_valids, dmasks = self._gather_blocks(
                scan, plan, fetch_block, dedup_mask)
            finite = not self._scan_has_inf(scan, arg_names,
                                            dtype=prep_dtype)
            with _kstage("device", kernel="agg_scan_prepared"):
                packed_f, packed_i = _aggregate(
                    _agg_scan_prepared, tuple(blocks),
                    jnp.asarray(np.asarray(n_valids)),
                    tuple(dmasks) if dmasks is not None else None,
                    where=bound_where, keys=keys, nf=nf, has_nan=has_nan,
                    finite=finite, num_segments=num_groups,
                    tag_names=tag_names, schema=schema,
                    float_ops=float_ops, pack_dtype=pack_dtype,
                )
            return (_unpack_acc(packed_f, packed_i, float_ops, int_ops,
                                widths), None)
        else:
            self.last_path = "dense"
            plan = _block_plan(scan)

            def fetch_block(entry, prefetch_only=False):
                cols = {}
                for name in device_col_names:
                    cols[name] = self._device_block(
                        scan, name, entry, extra_cols,
                        acc_dtype if name in float_fields else None,
                        prefetch_only=prefetch_only,
                    )
                return cols

            blocks, n_valids, dmasks = self._gather_blocks(
                scan, plan, fetch_block, dedup_mask)
            with _kstage("device", kernel="agg_scan"):
                packed_f, packed_i = _aggregate(
                    _agg_scan, tuple(blocks),
                    jnp.asarray(np.asarray(n_valids)),
                    tuple(dmasks) if dmasks is not None else None,
                    where=bound_where, keys=keys, agg_args=arg_exprs,
                    ops=ops, num_segments=num_groups, ts_name=ts_name,
                    tag_names=tag_names, schema=schema,
                    need_ts=bool({"first", "last"} & set(ops)),
                    acc_dtype=acc_dtype, float_ops=float_ops,
                    int_ops=int_ops, pack_dtype=pack_dtype,
                )
        return _unpack_acc(packed_f, packed_i, float_ops, int_ops, widths), None

    def _sparse_scan(self, scan, device_col_names, extra_cols, float_fields,
                     acc_dtype, dedup_mask, bound_where, keys, arg_exprs,
                     ops, ts_name, tag_names, schema, float_ops, int_ops,
                     widths, pack_dtype):
        """High-cardinality aggregation over the whole scan as one padded
        device program (sort-compact; see _agg_scan_sparse). Routes the
        reductions through the tiled fused kernel when eligible
        (_sparse_fused_ok), degrading to the XLA scatter chain on any
        kernel failure — same latch as the dense fused path."""
        from greptimedb_tpu import config
        from greptimedb_tpu.utils.metrics import (
            SPARSE_COMPACTION_RATIO,
            SPARSE_DISPATCHES,
        )

        n = scan.num_rows
        n_pad = block_size_for(n)
        cap = min(n_pad, config.sparse_groups_max())
        with _kstage("upload"):
            cols = {}
            for name in device_col_names:
                cast = acc_dtype if name in float_fields else None

                def build(name=name, cast=cast):
                    src = extra_cols[name] if name in extra_cols \
                        else scan.columns[name]
                    arr = pad_rows(src, n_pad)
                    if cast is not None and arr.dtype != cast:
                        arr = arr.astype(cast)
                    return jnp.asarray(arr)

                if scan.region_id < 0 or name in extra_cols:
                    cols[name] = build()
                else:
                    # whole-scan arrays cannot be file-anchored: snapshot key
                    key = ("snap", scan.region_id, _snap_version(scan),
                           ACTIVE_TIER.get(), scan.scan_fingerprint,
                           name, "whole", n_pad, str(cast))
                    cols[name] = self.cache.get(key, build)
        base = np.arange(n_pad) < n
        if dedup_mask is not None:
            base[:n] &= np.asarray(dedup_mask)[:n]
        packed = None
        with _kstage("device", kernel="agg_scan_sparse"):
            if self._sparse_fused_ok(ops, arg_exprs, scan, schema, extra_cols,
                                     acc_dtype):
                from greptimedb_tpu.ops import pallas_segment as ps
                from greptimedb_tpu.utils.metrics import PALLAS_DISPATCHES

                try:
                    packed_f, packed_i, uniq, n_groups = _aggregate(
                        _agg_scan_sparse_fused, cols, jnp.asarray(base),
                        where=bound_where, keys=keys,
                        arg_names=tuple(a.name for a in arg_exprs), ops=ops,
                        cap=cap, tag_names=tag_names, schema=schema,
                        acc_dtype=acc_dtype, float_ops=float_ops,
                        pack_dtype=pack_dtype)
                    packed_f.block_until_ready()
                    packed = (packed_f, packed_i, uniq, n_groups)
                    self.last_path = "sparse_fused"
                    PALLAS_DISPATCHES.inc(kernel="sparse_fused_agg",
                                          mode=ps.dispatch_mode())
                    SPARSE_DISPATCHES.inc(path="fused")
                except Exception:  # noqa: BLE001 — degrade, never fail the query
                    _note_degradation(
                        "fused_latch",
                        "sparse fused pallas kernel failed; serving this and "
                        "later queries through the XLA scatter path")
                    _FUSED_DISABLED["flag"] = True
                    PALLAS_DISPATCHES.inc(kernel="fused_agg_failed")
            if packed is None:
                packed = _aggregate(
                    _agg_scan_sparse, cols, jnp.asarray(base),
                    where=bound_where, keys=keys,
                    agg_args=arg_exprs, ops=ops, cap=cap, ts_name=ts_name,
                    tag_names=tag_names, schema=schema,
                    need_ts=bool({"first", "last"} & set(ops)),
                    acc_dtype=acc_dtype, float_ops=float_ops, int_ops=int_ops,
                    pack_dtype=pack_dtype)
                SPARSE_DISPATCHES.inc(path="classic")
        packed_f, packed_i, uniq, n_groups = packed
        with _kstage("readback"):
            u = int(n_groups)
        if u > cap:
            raise PlanError(
                f"query observed {u} distinct groups, exceeding the sparse "
                f"cap {cap}; raise GREPTIMEDB_TPU_SPARSE_GROUPS_MAX or add "
                "predicates")
        SPARSE_COMPACTION_RATIO.set(sparse_ops.compaction_ratio(u, n))
        acc = _unpack_acc(packed_f, packed_i, float_ops, int_ops, widths)
        acc = {k: v[:u] for k, v in acc.items()}
        with _kstage("readback"):
            gids = _readback(uniq)[:u]
        return acc, gids

    def _sparse_fused_ok(self, ops, arg_exprs, scan, schema, extra_cols,
                         acc_dtype) -> bool:
        """Route the sparse scan through the tiled fused kernel? Mirrors
        _fused_ok (mode/backend gates, finite proof, failure latch) with
        the sparse twists: the segment count is a tile size so no group
        envelope applies, sumsq rides only when the accumulator already
        carries f64 (the tiled fold can't upcast moments the way
        segment_agg does), and first/last stay on the XLA path (the
        kernel has no ts pairing)."""
        from greptimedb_tpu.ops import pallas_segment as ps
        from greptimedb_tpu.ops.segment import _pallas_mode

        if _FUSED_DISABLED["flag"]:
            return False
        if not set(ops) <= {"sum", "count", "mean", "rows", "min", "max",
                            "sumsq"}:
            return False
        if "sumsq" in ops and acc_dtype != jnp.dtype(jnp.float64):
            return False
        if not self._prepared_ok(arg_exprs, ops, (), schema, extra_cols):
            return False  # plain field columns only (same as dense fused)
        if not ps.fused_eligible(len(arg_exprs), ps.MAX_SEGMENTS,
                                 want_sumsq="sumsq" in ops):
            return False
        if acc_dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.float64)):
            return False
        arg_names = tuple(a.name for a in arg_exprs)
        if self._scan_has_inf(scan, arg_names, dtype=acc_dtype):
            return False
        mode = _pallas_mode()
        if mode == "on":
            return True
        # target_platform: the host tier of a TPU process traces for the
        # CPU backend, where no Mosaic exists
        return (mode == "auto" and ps.target_platform() == "tpu"
                and ps.fused_tpu_compile_ok())

    def _sparse_sharded_scan(self, scan, mesh, device_col_names, extra_cols,
                             float_fields, acc_dtype, dedup_mask,
                             bound_where, keys, arg_exprs, ops, ts_name,
                             tag_names, schema, float_ops, int_ops, widths,
                             pack_dtype):
        """High-cardinality aggregation on the mesh: part-aligned column
        placement (same file-anchored per-shard uploads as the dense
        collective), per-shard sort-compact, host-side GID-space merge
        (_agg_scan_sharded_sparse has the why). Raises MeshIneligible for
        shapes the shard dispatch can't serve — caller falls back to the
        single-device sparse scan."""
        from greptimedb_tpu import config
        from greptimedb_tpu.parallel import sharded_dispatch as sd
        from greptimedb_tpu.utils.metrics import (
            SPARSE_COMPACTION_RATIO,
            SPARSE_DISPATCHES,
        )

        if not sd.eligible(mesh):
            raise sd.MeshIneligible("sparse path needs part-aligned dispatch")
        n_shard = mesh.shape["shard"]
        plan = sd.plan_shards(scan, n_shard)
        tier = ACTIVE_TIER.get()
        snap_v = _snap_version(scan)
        cols = {}
        for name in device_col_names:
            cast = acc_dtype if name in float_fields else None

            def build_slice(start, end, out_rows, name=name, cast=cast):
                src = extra_cols[name] if name in extra_cols \
                    else scan.columns[name]
                arr = pad_rows(src[start:end], out_rows)
                if cast is not None and arr.dtype != cast:
                    arr = arr.astype(cast)
                return arr

            cols[name] = sd.sharded_column(
                None if name in extra_cols else self.cache,
                mesh, plan, scan, name, build_slice, tier=tier,
                snap_version=snap_v, extra=(str(cast),))
        base_s = sd.sharded_mask(mesh, plan, scan, dedup_mask,
                                 cache=self.cache, tier=tier,
                                 snap_version=snap_v)
        shard_rows = base_s.shape[0] // n_shard
        cap = min(shard_rows, config.sparse_groups_max())
        sd.note_dispatch("sharded_sparse", plan)
        packed_f, packed_i, uniqs, ns = _aggregate(
            _agg_scan_sharded_sparse, cols, base_s, mesh=mesh,
            where=bound_where, keys=keys,
            agg_args=arg_exprs, ops=ops, cap=cap, ts_name=ts_name,
            tag_names=tag_names, schema=schema,
            need_ts=bool({"first", "last"} & set(ops)),
            acc_dtype=acc_dtype, float_ops=float_ops, int_ops=int_ops,
            pack_dtype=pack_dtype)
        host_un = np.asarray(uniqs)
        host_ns = np.asarray(ns)
        parts = []
        for s in range(n_shard):
            u_s = int(host_ns[s])
            if u_s > cap:
                raise PlanError(
                    f"shard {s} observed {u_s} distinct groups, exceeding "
                    f"the sparse cap {cap}; raise "
                    "GREPTIMEDB_TPU_SPARSE_GROUPS_MAX or add predicates")
            pf_s = packed_f[s * cap:(s + 1) * cap]
            pi_s = packed_i[s * cap:(s + 1) * cap] if int_ops else None
            acc_s = _unpack_acc(pf_s, pi_s, float_ops, int_ops, widths)
            parts.append({
                "gids": host_un[s * cap:s * cap + u_s],
                "planes": {op: v[:u_s] for op, v in acc_s.items()},
            })
        gids, planes = sparse_ops.combine_sparse_gid_partials(parts)
        total = len(gids)
        self.last_path = "sparse_sharded"
        SPARSE_DISPATCHES.inc(path="sharded")
        SPARSE_COMPACTION_RATIO.set(
            sparse_ops.compaction_ratio(total, scan.num_rows))
        if not total:
            # no shard observed a group: empty keyed result with the
            # same plane layout _unpack_acc would produce
            planes = {op: np.zeros((0, widths[op])) for op in float_ops}
            for op in int_ops:
                planes[op] = np.zeros((0,), np.int64)
        return planes, gids

    def _sharded_scan(self, scan, mesh, device_col_names, extra_cols,
                      float_fields, acc_dtype, dedup_mask, bound_where, keys,
                      arg_exprs, ops, num_groups, ts_name, tag_names, schema,
                      float_ops, pack_dtype):
        """Place the scan's columns across the mesh's "shard" axis and run
        the collective aggregation — the integrated multi-chip MergeScan.
        Part-aligned dispatch (parallel/sharded_dispatch.py) is the
        default: per-segment uploads are file-anchored on their owning
        shard, so a flush transfers only its new file. Meshes with a real
        field axis keep the legacy whole-scan device_put placement."""
        from greptimedb_tpu.parallel import sharded_dispatch as sd

        if sd.eligible(mesh):
            return self._sharded_scan_parts(
                scan, mesh, device_col_names, extra_cols, float_fields,
                acc_dtype, dedup_mask, bound_where, keys, arg_exprs, ops,
                num_groups, ts_name, tag_names, schema, float_ops,
                pack_dtype)
        return self._sharded_scan_even(
            scan, mesh, device_col_names, extra_cols, float_fields,
            acc_dtype, dedup_mask, bound_where, keys, arg_exprs, ops,
            num_groups, ts_name, tag_names, schema, float_ops, pack_dtype)

    def _sharded_scan_parts(self, scan, mesh, device_col_names, extra_cols,
                            float_fields, acc_dtype, dedup_mask, bound_where,
                            keys, arg_exprs, ops, num_groups, ts_name,
                            tag_names, schema, float_ops, pack_dtype):
        """Part-aligned mesh dispatch: the shard plan assigns immutable
        SST segments to shards (prefix-stable greedy balance), per-
        segment uploads land file-anchored on the owning shard's device,
        and the assembled per-shard buffers form the global array with
        zero cross-device traffic (sharded_dispatch module docstring)."""
        from greptimedb_tpu.parallel import sharded_dispatch as sd

        n_shard = mesh.shape["shard"]
        plan = sd.plan_shards(scan, n_shard)
        tier = ACTIVE_TIER.get()
        snap_v = _snap_version(scan)
        cache = self.cache
        prepared = self._prepared_ok(arg_exprs, ops, (), schema, extra_cols)
        names = device_col_names
        if prepared:
            names = self._device_columns(scan, bound_where, keys, (),
                                         ts_name, extra_cols)
        cols = {}
        for name in names:
            cast = acc_dtype if name in float_fields else None

            def build_slice(start, end, out_rows, name=name, cast=cast):
                src = extra_cols[name] if name in extra_cols \
                    else scan.columns[name]
                arr = pad_rows(src[start:end], out_rows)
                if cast is not None and arr.dtype != cast:
                    arr = arr.astype(cast)
                return arr

            cols[name] = sd.sharded_column(
                # extra_cols hold query-specific factorized keys: their
                # content is not a pure function of the file — never
                # cache them under file/snapshot identity
                None if name in extra_cols else cache,
                mesh, plan, scan, name, build_slice, tier=tier,
                snap_version=snap_v, extra=(str(cast),))
        base_s = sd.sharded_mask(mesh, plan, scan, dedup_mask, cache=cache,
                                 tier=tier, snap_version=snap_v)
        if prepared:
            self.last_path = "sharded_prepared"
            arg_names = tuple(a.name for a in arg_exprs)
            has_nan = self._scan_has_nan(scan, arg_names)
            nf = len(arg_names)
            # sum + sq moments both need f64 for stddev/variance (see the
            # dense branch note)
            prep_dtype = jnp.dtype(jnp.float64) if "sumsq" in ops \
                else acc_dtype
            plane_kinds = [("__prep__", None, prep_dtype, 0.0)]
            if "min" in ops:
                plane_kinds.append(("__prep_min__", "min", acc_dtype,
                                    np.inf))
            if "max" in ops:
                plane_kinds.append(("__prep_max__", "max", acc_dtype,
                                    -np.inf))
            if "sumsq" in ops:
                plane_kinds.append(("__prep_sq__", "sq", prep_dtype, 0.0))
            for plane_name, kind, pdt, fill in plane_kinds:
                def build_plane_slice(start, end, out_rows, kind=kind,
                                      pdt=pdt):
                    return _build_prep(scan, arg_names, start, end,
                                       out_rows, pdt, has_nan, kind)

                cols[plane_name] = sd.sharded_column(
                    cache, mesh, plan, scan,
                    (plane_name,) + arg_names, build_plane_slice,
                    tier=tier, snap_version=snap_v,
                    extra=(str(pdt), has_nan), pad_fill=fill)
            sd.note_dispatch("sharded_prepared", plan)
            return _aggregate(
                _agg_scan_sharded_prepared, cols, base_s, mesh=mesh,
                where=bound_where, keys=keys,
                nf=nf, has_nan=has_nan, num_segments=num_groups,
                tag_names=tag_names, schema=schema, float_ops=float_ops,
                pack_dtype=pack_dtype)
        sd.note_dispatch("sharded", plan)
        return _aggregate(
            _agg_scan_sharded, cols, base_s, mesh=mesh,
            where=bound_where, keys=keys,
            agg_args=arg_exprs, ops=ops, num_segments=num_groups,
            ts_name=ts_name, tag_names=tag_names, schema=schema,
            acc_dtype=acc_dtype, float_ops=float_ops, pack_dtype=pack_dtype)

    def _sharded_scan_even(self, scan, mesh, device_col_names, extra_cols,
                           float_fields, acc_dtype, dedup_mask, bound_where,
                           keys, arg_exprs, ops, num_groups, ts_name,
                           tag_names, schema, float_ops, pack_dtype):
        """Legacy whole-scan placement (one device_put over the
        NamedSharding): kept for meshes with a real field axis, where the
        per-shard committed-buffer assembly would need replicated
        placement. Snapshot-anchored only — a flush re-uploads."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = scan.num_rows
        n_shard = mesh.shape["shard"]
        n_pad = ((n + n_shard - 1) // n_shard) * n_shard
        sharding = NamedSharding(mesh, P("shard"))
        prepared = self._prepared_ok(arg_exprs, ops, (), schema, extra_cols)
        names = device_col_names
        if prepared:
            names = self._device_columns(scan, bound_where, keys, (),
                                         ts_name, extra_cols)
        cols = {}
        for name in names:
            cast = acc_dtype if name in float_fields else None

            def build(name=name, cast=cast):
                src = extra_cols[name] if name in extra_cols \
                    else scan.columns[name]
                arr = pad_rows(src, n_pad)
                if cast is not None and arr.dtype != cast:
                    arr = arr.astype(cast)
                return jax.device_put(arr, sharding)

            if scan.region_id < 0 or name in extra_cols:
                cols[name] = build()
                device_telemetry.count_h2d(cols[name].nbytes)
            else:
                key = ("snap", scan.region_id, _snap_version(scan),
                       ACTIVE_TIER.get(), scan.scan_fingerprint,
                       name, "sharded", n_pad, n_shard, str(cast))
                cols[name] = self.cache.get(key, build)
        base = np.arange(n_pad) < n
        if dedup_mask is not None:
            base[:n] &= np.asarray(dedup_mask)[:n]
        base_s = jax.device_put(base, sharding)
        if prepared:
            self.last_path = "sharded_prepared"
            arg_names = tuple(a.name for a in arg_exprs)
            has_nan = self._scan_has_nan(scan, arg_names)
            nf = len(arg_names)
            # sum + sq moments both need f64 for stddev/variance (see the
            # dense branch note)
            prep_dtype = jnp.dtype(jnp.float64) if "sumsq" in ops \
                else acc_dtype
            plane_kinds = [("__prep__", None, prep_dtype)]
            if "min" in ops:
                plane_kinds.append(("__prep_min__", "min", acc_dtype))
            if "max" in ops:
                plane_kinds.append(("__prep_max__", "max", acc_dtype))
            if "sumsq" in ops:
                plane_kinds.append(("__prep_sq__", "sq", prep_dtype))
            for plane_name, kind, pdt in plane_kinds:
                def build_plane(kind=kind, pdt=pdt):
                    whole = _build_prep(scan, arg_names, 0, n, n_pad,
                                        pdt, has_nan, kind)
                    return jax.device_put(whole, sharding)

                if scan.region_id < 0:
                    cols[plane_name] = build_plane()
                else:
                    key = ("snap", scan.region_id, _snap_version(scan),
                           ACTIVE_TIER.get(), scan.scan_fingerprint,
                           plane_name, arg_names, "sharded", n_pad,
                           n_shard, str(pdt), has_nan)
                    cols[plane_name] = self.cache.get(key, build_plane)
            return _aggregate(
                _agg_scan_sharded_prepared, cols, base_s, mesh=mesh,
                where=bound_where, keys=keys,
                nf=nf, has_nan=has_nan, num_segments=num_groups,
                tag_names=tag_names, schema=schema, float_ops=float_ops,
                pack_dtype=pack_dtype)
        return _aggregate(
            _agg_scan_sharded, cols, base_s, mesh=mesh,
            where=bound_where, keys=keys,
            agg_args=arg_exprs, ops=ops, num_segments=num_groups,
            ts_name=ts_name, tag_names=tag_names, schema=schema,
            acc_dtype=acc_dtype, float_ops=float_ops, pack_dtype=pack_dtype)

    def _upload_prefetch_ok(self, scan) -> bool:
        """Whether the dense block loops should double-buffer uploads:
        the knob is on, the scan is cacheable (prefetch parks results in
        the HBM cache), and neither the host tier nor one chip of
        several (OnShard) is active — their jax.default_device context
        is thread-scoped, so a background build would land on the wrong
        device."""
        from greptimedb_tpu.query.device_cache import upload_prefetch_enabled

        return (upload_prefetch_enabled() and scan.region_id >= 0
                and ACTIVE_TIER.get() == "device")

    def _gather_blocks(self, scan, plan, fetch, dedup_mask):
        """Walk the block plan through `fetch`, double-buffering block
        i+1's host build + H2D behind block i's assembly (the upload
        prefetch worker). Returns (blocks, n_valids, dedup block masks).
        One `upload` stage: what the hot set already holds costs it
        nothing."""
        from greptimedb_tpu.utils import deadline as dl

        blocks, n_valids = [], []
        dmasks = [] if dedup_mask is not None else None
        do_prefetch = self._upload_prefetch_ok(scan)
        with _kstage("upload", blocks=len(plan)):
            for i, entry in enumerate(plan):
                # host-level deadline checkpoint per device block: the
                # jitted kernels below can't be interrupted, but a
                # streamed scan crosses here once per block — an expired
                # or killed query stops dispatching instead of walking
                # the whole plan
                dl.check("device dispatch")
                if do_prefetch and i + 1 < len(plan):
                    # double buffering: the background worker builds and
                    # uploads block i+1 while this thread assembles
                    # block i (and the device chews on what's queued)
                    fetch(plan[i + 1], prefetch_only=True)
                blocks.append(fetch(entry))
                n_valids.append(entry.end - entry.start)
                if dmasks is not None:
                    dmasks.append(_pad_device_mask(dedup_mask, entry.start,
                                                   entry.end, entry.block))
        return blocks, n_valids, dmasks

    def _fused_ok(self, ops, arg_names, num_groups, scan) -> bool:
        """Route to the fused Pallas kernel? Mode/backend gates mirror
        dense_segment_sum (on = force incl. interpret mode off-TPU, how
        the CPU differential tests drive it; auto = real TPU device
        tier only, behind the Mosaic canary), plus the kernel's own
        shape envelope, a finite-values proof (Inf would poison the
        0*x matmul), and the runtime-failure latch the chaos test
        trips."""
        from greptimedb_tpu import config
        from greptimedb_tpu.ops import pallas_segment as ps
        from greptimedb_tpu.ops.segment import _pallas_mode

        if _FUSED_DISABLED["flag"]:
            return False
        if not set(ops) <= {"sum", "count", "mean", "rows", "min", "max",
                            "sumsq", "first", "last"}:
            return False
        acc_dtype = jnp.dtype(config.compute_dtype())
        if "sumsq" in ops and acc_dtype != jnp.dtype(jnp.float64):
            # the kernel accumulates moments in the compute dtype; only
            # f64 carries the variance cancellation (see segment_agg)
            return False
        if not ps.fused_eligible(len(arg_names), num_groups + 1,
                                 want_sumsq="sumsq" in ops):
            return False
        if acc_dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.float64)):
            return False
        if self._scan_has_inf(scan, arg_names, dtype=acc_dtype):
            return False
        mode = _pallas_mode()
        if mode == "on":
            return True
        return (mode == "auto" and ps.target_platform() == "tpu"
                and ps.fused_tpu_compile_ok())

    def _dense_fused_scan(self, scan, plan, aux_names, arg_names,
                          extra_cols, float_fields, acc_dtype, dedup_mask,
                          bound_where, keys, ops, num_groups, ts_name,
                          tag_names, schema, float_ops, int_ops,
                          pack_dtype):
        """Run the fused-kernel aggregation; returns (packed_f,
        packed_i), or None after latching the kernel off when anything
        in the fused program fails (trace, Mosaic compile, or execution)
        — the caller then serves the same query through the XLA scatter
        path, so a kernel regression degrades throughput, never
        availability."""
        from greptimedb_tpu.ops import pallas_segment as ps
        from greptimedb_tpu.utils.metrics import PALLAS_DISPATCHES

        need_cols = sorted(set(aux_names) | set(arg_names)
                           | ({ts_name} if int_ops else set()))

        def fetch_block(entry, prefetch_only=False):
            cols = {}
            for name in need_cols:
                cols[name] = self._device_block(
                    scan, name, entry, extra_cols,
                    acc_dtype if name in float_fields else None,
                    prefetch_only=prefetch_only,
                )
            return cols

        blocks, n_valids, dmasks = self._gather_blocks(
            scan, plan, fetch_block, dedup_mask)
        try:
            with _kstage("device", kernel="agg_scan_fused"):
                packed_f, packed_i = _aggregate(
                    _agg_scan_fused, tuple(blocks),
                    jnp.asarray(np.asarray(n_valids)),
                    tuple(dmasks) if dmasks is not None else None,
                    where=bound_where, keys=keys, arg_names=arg_names,
                    num_segments=num_groups, ts_name=ts_name,
                    tag_names=tag_names, schema=schema,
                    float_ops=float_ops, int_ops=int_ops,
                    pack_dtype=pack_dtype, acc_dtype=acc_dtype,
                    want_min="min" in ops, want_max="max" in ops,
                    want_sumsq="sumsq" in ops)
                # surface async execution errors HERE, inside the latch
                # — the result is consumed immediately downstream anyway
                packed_f.block_until_ready()
        except Exception:  # noqa: BLE001 — any kernel failure must degrade
            _note_degradation(
                "fused_latch",
                "fused pallas kernel failed; serving this and later "
                "queries through the XLA scatter path")
            _FUSED_DISABLED["flag"] = True
            PALLAS_DISPATCHES.inc(kernel="fused_agg_failed")
            return None
        PALLAS_DISPATCHES.inc(float(len(blocks)), kernel="fused_agg",
                              mode=ps.dispatch_mode())
        return packed_f, packed_i

    def _device_block(self, scan: ScanData, name, entry: _BlockEntry,
                      extra_cols, cast_dtype, prefetch_only=False):
        """Fetch one padded column block through the HBM hot set.
        Blocks of an immutable SST part are keyed by the FILE identity
        (entry.pkey) and survive flushes/data-version bumps; memtable
        and synthetic rows key by snapshot version. `prefetch_only`:
        schedule the build on the cache's background worker
        (upload/compute double buffering) and return None."""
        start, end, block = entry.start, entry.end, entry.block

        def build():
            rows = extra_cols[name][start:end] if name in extra_cols \
                else scan.rows(name, start, end)
            arr = pad_rows(rows, block)
            if cast_dtype is not None and arr.dtype != cast_dtype:
                arr = arr.astype(cast_dtype)
            return jnp.asarray(arr)

        if scan.region_id < 0 or name in extra_cols:
            if prefetch_only:
                return None  # uncacheable: nowhere to park the result
            out = build()
            # uncached upload (the cache counts its own miss-builds)
            device_telemetry.count_h2d(out.nbytes)
            return out
        key = self._hot_key(scan, entry, name, str(cast_dtype))
        if prefetch_only:
            self.cache.prefetch(key, build)
            return None
        return self.cache.get(key, build)

    def _hot_key(self, scan, entry: _BlockEntry, name, extra) -> tuple:
        """Hot-set key for one block. File-anchored blocks carry the
        (file_id, ts_range, pred_key) part identity + the block offset
        INSIDE the part, so a dashboard's steady-state uploads are
        invalidated by file death (compaction/expiry/DROP), not by every
        memtable write; everything else is snapshot-anchored and retires
        with its data version."""
        tier = ACTIVE_TIER.get()
        if entry.pkey is not None:
            fid, ts_r, pred_key = entry.pkey
            return ("file", scan.region_id, fid, tier, ts_r, pred_key,
                    name, entry.start - entry.part_start, entry.block,
                    extra)
        return ("snap", scan.region_id, _snap_version(scan), tier,
                scan.scan_fingerprint, name, entry.start, entry.block,
                extra)

    def _prepared_ok(self, arg_exprs, ops, int_ops, schema,
                     extra_cols) -> bool:
        """Eligibility for the prepared dense path: plain float/int FIELD
        columns aggregated with sum/count/mean/rows/min/max/sumsq
        (min/max ride identity-filled planes, sumsq a squared-values
        plane; first/last still need the ts pairing the planes can't
        encode)."""
        if int_ops or not arg_exprs:
            return False
        if not set(ops) <= {"mean", "sum", "count", "rows", "min", "max",
                            "sumsq"}:
            return False
        field_names = {c.name for c in schema.field_columns}
        return all(
            isinstance(a, ast.Column) and a.name in field_names
            and a.name not in extra_cols
            for a in arg_exprs
        )

    def _scan_has_nan(self, scan, arg_names: tuple) -> bool:
        """Whether any aggregated column holds NULLs — decides the
        prepared plane layout. Memoized on the ScanData snapshot (one
        pass at first query, free afterwards)."""
        flags = getattr(scan, "_nan_flags", None)
        if flags is None:
            flags = {}
            scan._nan_flags = flags
        out = False
        for name in arg_names:
            f = flags.get(name)
            if f is None:
                col = np.asarray(scan.columns[name])
                f = bool(np.isnan(col).any()) \
                    if col.dtype.kind == "f" else False
                flags[name] = f
            out = out or f
        return out

    def _scan_has_inf(self, scan, arg_names: tuple, dtype=None) -> bool:
        """Whether any aggregated column holds +/-Inf — the pallas
        one-hot matmul kernel would turn one Inf into NaN for every
        group (0*inf), so only provably finite planes may ride it.
        `dtype` is the dtype the kernel will actually compute in: a
        finite f64 value that overflows the f64->f32 cast reaches the
        matmul as Inf all the same, so the proof must run post-cast.
        Memoized on the ScanData snapshot like _scan_has_nan."""
        flags = getattr(scan, "_inf_flags", None)
        if flags is None:
            flags = {}
            scan._inf_flags = flags
        dt = np.dtype(dtype) if dtype is not None else None
        out = False
        for name in arg_names:
            key = (name, dt.str if dt is not None else None)
            f = flags.get(key)
            if f is None:
                col = np.asarray(scan.columns[name])
                if col.dtype.kind == "f":
                    if (dt is not None and dt.kind == "f"
                            and dt.itemsize < col.dtype.itemsize):
                        with np.errstate(over="ignore"):
                            col = col.astype(dt)
                    f = bool(np.isinf(col).any())
                else:
                    f = False
                flags[key] = f
            out = out or f
        return out

    def _prep_plane(self, scan, arg_names, entry: _BlockEntry, acc_dtype,
                    has_nan: bool, prefetch_only=False):
        """Query-invariant value plane for the prepared path, cached in
        HBM alongside the raw column blocks (layout: _build_prep)."""

        def build():
            return jnp.asarray(_build_prep(scan, arg_names, entry.start,
                                           entry.end, entry.block,
                                           acc_dtype, has_nan, None))

        if scan.region_id < 0:
            return None if prefetch_only else build()
        key = self._hot_key(scan, entry, ("__prep__",) + arg_names,
                            (str(acc_dtype), has_nan))
        if prefetch_only:
            self.cache.prefetch(key, build)
            return None
        return self.cache.get(key, build)

    def _prep_extreme_plane(self, scan, arg_names, entry: _BlockEntry,
                            acc_dtype, kind: str, prefetch_only=False):
        """min/max/sq companion plane: values with NaN (and padding)
        replaced by the reduction's identity (±inf for extremes, 0 for
        the squared-sum plane), so the dead-segment id trick is the only
        masking the query needs."""

        def build():
            return jnp.asarray(_build_prep(scan, arg_names, entry.start,
                                           entry.end, entry.block,
                                           acc_dtype, False, kind))

        if scan.region_id < 0:
            return None if prefetch_only else build()
        key = self._hot_key(scan, entry, (f"__prep_{kind}__",) + arg_names,
                            str(acc_dtype))
        if prefetch_only:
            self.cache.prefetch(key, build)
            return None
        return self.cache.get(key, build)

    def _device_columns(self, scan, bound_where, keys, arg_exprs, ts_name, extra_cols):
        from greptimedb_tpu.query.expr import collect_columns

        needed: set[str] = set()
        collect_columns(bound_where, needed)
        for a in arg_exprs:
            collect_columns(a, needed)
        for k in keys:
            needed.add(k.column)
        needed.add(ts_name)
        avail = set(scan.columns) | set(extra_cols)
        missing = needed - avail
        if missing:
            raise PlanError(f"columns missing from scan: {sorted(missing)}")
        return sorted(needed)

    @staticmethod
    def _dedups(scan, table) -> bool:
        """Whether this scan's kernels take a last-write-wins mask."""
        return not table.append_mode and bool(scan.needs_dedup)

    def _lww_masked(self, scan, table, ctx) -> bool:
        """What of the last-write-wins mask a hedge key has to hold:
        whether the kernels are handed one (a static choice of theirs).
        The mask is made on the host whatever the row count, so no row
        count enters a key; it is made here, before the key, because
        only the merge knows whether a row repeats."""
        return self._maybe_dedup(scan, table, ctx) is not None

    def _value_flags(self, scan, arg_exprs) -> tuple:
        """The value columns' (has NULL, has Inf) flags where the
        aggregate's arguments are plain columns: static inputs of the
        prepared and fused kernels' choice, memoized on the snapshot."""
        from greptimedb_tpu import config

        names = tuple(a.name for a in arg_exprs if isinstance(a, ast.Column))
        if len(names) != len(arg_exprs) or not scan.materialized \
                or any(n not in scan.columns for n in names):
            return ()
        return (self._scan_has_nan(scan, names),
                self._scan_has_inf(
                    scan, names, dtype=jnp.dtype(config.compute_dtype())))

    def _maybe_dedup(self, scan: ScanData, table, ctx) -> Optional[np.ndarray]:
        """The scan's last-write-wins mask, a host bool per row (blocks
        of it are uploaded as the kernels ask), or None where every row
        stays. Kept per snapshot — on the ScanData, and for a region's
        scan under (region, incarnation, data version, fingerprint), so
        that a full scan, whose plan is taken anew by every request, is
        merged once a data version."""
        if not self._dedups(scan, table):
            return None
        cached = getattr(scan, "_dedup_mask_cache", None)
        if cached is not None:
            return cached[0]
        key = None
        if scan.region_id >= 0 and scan.scan_fingerprint:
            key = (scan.region_id, scan.incarnation, scan.data_version,
                   scan.scan_fingerprint)
            with self._lww_lock:
                held = self._lww_masks.get(key)
                if held is not None:
                    self._lww_masks.move_to_end(key)
        else:
            held = None
        if held is None:
            held = (self._compute_dedup(scan, table),)
            if key is not None:
                with self._lww_lock:
                    self._lww_masks[key] = held
                    while len(self._lww_masks) > _LWW_MASKS_KEPT:
                        self._lww_masks.popitem(last=False)
        scan._dedup_mask_cache = held
        return held[0]

    def _compute_dedup(self, scan: ScanData, table) -> Optional[np.ndarray]:
        """Merge the scan's sorted runs on the host (query/lww.py)."""
        from greptimedb_tpu.query import lww
        from greptimedb_tpu.utils.metrics import (
            LWW_MASK_EVENTS,
            LWW_MASK_SECONDS,
        )

        tag_names = [c.name for c in table.schema.tag_columns]
        ts_name = table.schema.time_index.name
        t0 = time.perf_counter()
        with tracing.stage("host_agg"), tracing.span(
                "lww_mask", rows=scan.num_rows) as attrs:
            mask, path, dups = lww.keep_mask(scan, tag_names, ts_name)
            attrs.update(path=path, duplicates=dups)
        LWW_MASK_EVENTS.inc(path=path)
        LWW_MASK_SECONDS.observe(time.perf_counter() - t0)
        return mask

    # ---- raw (non-aggregate) path ------------------------------------------

    def _filtered_row_indices(self, scan, table, ctx, bound_where,
                              where_unbound=None) -> np.ndarray:
        """Row indices surviving WHERE + LWW dedup, computed blockwise on
        device (shared by the raw scan and RANGE-select paths).

        String FIELD columns (non-tag, so not dict-coded) cannot become
        device blocks; they stay host-side. A WHERE referencing one flips
        the whole filter to host numpy evaluation — correct, just not
        device-accelerated (string fields are metadata-shaped, e.g. the
        OTLP trace table's span attributes)."""
        schema = table.schema
        dedup_mask = self._maybe_dedup(scan, table, ctx)
        n = scan.num_rows
        obj_cols = {name for name, arr in scan.columns.items()
                    if arr.dtype == object and name not in scan.tag_dicts}
        referenced: set = set()
        collect_columns(bound_where, referenced)
        if not referenced & obj_cols:
            try:
                return self._device_filtered_indices(
                    scan, schema, ctx, bound_where, dedup_mask, obj_cols, n)
            except PlanError:
                # a WHERE construct the device evaluator doesn't cover
                # (e.g. a plugin scalar function): host filter below
                pass
        return self._host_filtered_indices(
            scan, schema, bound_where, where_unbound, dedup_mask,
            referenced, n)

    @_staged("device", kernel_step=True)
    def _device_filtered_indices(self, scan, schema, ctx, bound_where,
                                 dedup_mask, obj_cols, n) -> np.ndarray:
        tag_names = frozenset(ctx.tag_names)
        picked: list[np.ndarray] = []
        for entry in _block_plan(scan):
            start, end, block = entry.start, entry.end, entry.block
            cols = {
                name: self._device_block(scan, name, entry, {}, None)
                for name in scan.columns
                if name not in obj_cols
            }
            dmask = None
            if dedup_mask is not None:
                dmask = _pad_device_mask(dedup_mask, start, end, block)
            mask = _aggregate(_filter_block, cols,
                              jnp.asarray(end - start), dmask,
                              where=bound_where, tag_names=tag_names,
                              schema=schema)
            picked.append(np.flatnonzero(np.asarray(mask)) + start)
        return np.concatenate(picked) if picked else np.empty(0, dtype=np.int64)

    @_staged("host_agg")
    def _host_filtered_indices(self, scan, schema, bound_where,
                               where_unbound, dedup_mask, referenced,
                               n) -> np.ndarray:
        """Numpy filter over host columns: tags referenced by the WHERE
        decode to strings (the bound expression's code rewriting doesn't
        apply here, but timestamp-literal coercion still must — see
        bind_host_expr)."""
        from greptimedb_tpu.datatypes.vector import DictVector
        from greptimedb_tpu.query.expr import bind_host_expr

        host_cols = {}
        for name, arr in scan.columns.items():
            if name in scan.tag_dicts:
                if name not in referenced:
                    continue  # decoding is O(n) python objects — skip
                host_cols[name] = DictVector(
                    arr, scan.tag_dicts[name]).decode()
            else:
                host_cols[name] = arr
        w = bind_host_expr(where_unbound, schema) \
            if where_unbound is not None else bound_where
        if w is None:
            m = np.ones(n, dtype=bool)
        else:
            m = np.asarray(eval_host(w, host_cols, schema))
            m = (m if m.dtype == bool else m != 0)
            m = np.broadcast_to(m, (n,)).copy()
        if dedup_mask is not None:
            m &= np.asarray(dedup_mask)[:n]
        return np.flatnonzero(m)

    def _execute_raw(self, scan, table, where, project, sort, limit, offset) -> QueryResult:
        schema = table.schema
        if scan is None:
            return _project_empty(project, schema)
        ctx = BindContext(schema, scan.tag_dicts)
        bound_where = bind_expr(where, ctx) if where is not None else None
        idx = self._filtered_row_indices(scan, table, ctx, bound_where,
                                         where_unbound=where)

        # gather + decode on host
        with tracing.stage("assemble"):
            host_cols: dict[str, np.ndarray] = {}
            for name, arr in scan.columns.items():
                taken = arr[idx]
                if name in scan.tag_dicts:
                    from greptimedb_tpu.datatypes.vector import DictVector
                    taken = DictVector(taken, scan.tag_dicts[name]).decode()
                host_cols[name] = taken

            env: dict = {}
            return self._post_process(env, None, None, project, sort, limit,
                                      offset, table, len(idx),
                                      host_cols=host_cols)

    # ---- shared tail: project/having/sort/limit over host arrays -----------

    def _post_process(self, env, agg, having, project, sort, limit, offset,
                      table, nrows, host_cols=None) -> QueryResult:
        schema = table.schema
        host_cols = host_cols or {}

        if having is not None:
            m = np.asarray(eval_host(having.predicate, host_cols, schema, env))
            m = m if m.dtype == bool else m != 0
            m = np.broadcast_to(m, (nrows,))
            env = {k: v[m] if isinstance(v, np.ndarray) and v.ndim >= 1 and len(v) == nrows else v
                   for k, v in env.items()}
            host_cols = {k: v[m] for k, v in host_cols.items()}
            nrows = int(m.sum())

        out_cols: list[np.ndarray] = []
        out_names: list[str] = []
        out_dtypes: list[Optional[DataType]] = []
        for name, e in project.items:
            v = eval_host(e, host_cols, schema, env)
            arr = np.asarray(v)
            if arr.ndim == 0:
                arr = np.broadcast_to(arr, (nrows,)).copy()
            out_cols.append(arr)
            out_names.append(name)
            out_dtypes.append(_infer_dtype(e, schema))

        if sort is not None and nrows > 1:
            order = _host_sort_order(sort.keys, project, out_names, out_cols,
                                     host_cols, schema, env)
            out_cols = [c[order] for c in out_cols]
        if offset:
            out_cols = [c[offset:] for c in out_cols]
        if limit is not None:
            out_cols = [c[:limit] for c in out_cols]
        return QueryResult(out_names, out_dtypes, out_cols)

    def _empty_agg_result(self, table, agg, having, project, sort, limit, offset):
        # no data: global aggregates still yield one row
        env: dict = {}
        nrows = 0 if agg.keys else 1
        for name, kexpr in agg.keys:
            env[kexpr] = np.empty(0, dtype=object)
        for spec in agg.aggs:
            if spec.func in ("count", "rows"):
                env[spec.call] = np.zeros(nrows, dtype=np.int64)
            else:
                env[spec.call] = np.full(nrows, np.nan)
        return self._post_process(env, agg, having, project, sort, limit, offset,
                                  table, nrows)


# ---- helpers ---------------------------------------------------------------


def _acc_partial(acc: dict, sparse_gids, keys, decoders,
                 keyed: bool) -> dict:
    """The classic kernels' planes as one value-keyed partial, the form
    `combine_partials` folds: the observed groups' decoded keys beside
    their planes (a global aggregate keeps its one group even when
    empty, so that the combined result has a row)."""
    rows = acc["rows"][:, 0] if acc["rows"].ndim == 2 else acc["rows"]
    if sparse_gids is not None:
        # acc rows [0, U) are the observed groups, ascending global id
        present = np.arange(len(sparse_gids))
        gids = sparse_gids
    else:
        present = np.flatnonzero(rows > 0) if keyed else np.arange(1)
        gids = present
    strides = _strides([k.size for k in keys])
    key_cols = [np.asarray(decode((gids // strides[i]) % keys[i].size)[0])
                for i, decode in enumerate(decoders)]
    return {"keys": key_cols,
            "planes": {op: np.asarray(pl)[present]
                       for op, pl in acc.items()}}


def _lost_rows_digest(mask: Optional[np.ndarray], entry) -> Optional[str]:
    """What a part's cached partial depends on besides its file: which
    of its rows lost to a later write elsewhere in the scan. None where
    none did (the partial is the file's own)."""
    if mask is None or mask[entry.start:entry.end].all():
        return None
    rows = mask[entry.start:entry.end]
    return hashlib.blake2b(np.packbits(rows).tobytes(),
                           digest_size=8).hexdigest()


def _pad_device_mask(mask: np.ndarray, start: int, end: int, block: int) -> jax.Array:
    """One block of the host's last-write-wins mask, padded False, on
    the device: the block's shape, whatever the scan's row count."""
    out = np.zeros(block, dtype=bool)
    out[:end - start] = mask[start:end]
    return jnp.asarray(out)


def _unpack_acc(packed_f, packed_i, float_ops, int_ops, widths):
    """Split the kernel's packed output matrix back into per-op planes.
    This is the dense/prepared paths' D2H readback boundary: the
    `readback` stage waits here for whatever the device still runs."""
    with _kstage("readback"):
        host_f = _readback(packed_f)
        host_i = _readback(packed_i) if int_ops else None
    acc: dict[str, np.ndarray] = {}
    off = 0
    for k in float_ops:
        w = widths[k]
        sl = host_f[:, off:off + w]
        off += w
        if k in ("count", "rows"):
            sl = sl.astype(np.int64)
        acc[k] = sl
    for j, k in enumerate(int_ops):
        acc[k] = host_i[:, j]
    return acc


def _closed_range(ts_range):
    if ts_range is None:
        return None
    lo, hi = ts_range
    return (lo if lo is not None else -(1 << 62), hi if hi is not None else (1 << 62))


def _strides(sizes: list[int]) -> list[int]:
    strides = [1] * len(sizes)
    for i in range(len(sizes) - 2, -1, -1):
        strides[i] = strides[i + 1] * sizes[i + 1]
    return strides


def _finalize_agg(func: str, acc: dict, slot: Optional[int], present: np.ndarray):
    def get(op):
        v = acc[op]
        if v.ndim == 2:
            v = v[:, slot if slot is not None else 0]
        return v[present]

    if func == "rows":
        return get("rows").astype(np.int64)
    if func == "count":
        return get("count").astype(np.int64)
    if func == "sum":
        s, c = get("sum"), get("count")
        return np.where(c > 0, s, np.nan)
    if func == "avg":
        s, c = get("sum"), get("count")
        with np.errstate(invalid="ignore", divide="ignore"):
            return np.where(c > 0, s / np.maximum(c, 1), np.nan)
    if func in ("min", "max", "first", "last", "sumsq"):
        return get(func)
    if func in ("stddev", "variance"):
        s, ss, c = get("sum"), get("sumsq"), get("count")
        with np.errstate(invalid="ignore", divide="ignore"):
            var = (ss - s * s / np.maximum(c, 1)) / np.maximum(c - 1, 1)
            var = np.where(c > 1, np.maximum(var, 0.0), np.nan)
        return np.sqrt(var) if func == "stddev" else var
    raise PlanError(f"unknown aggregate {func}")


def _infer_dtype(e: ast.Expr, schema) -> Optional[DataType]:
    if isinstance(e, ast.Column) and e.name in schema.names:
        return schema.column(e.name).dtype
    if isinstance(e, ast.FuncCall):
        if e.name in ("date_bin", "time_bucket", "date_trunc"):
            ts_arg = e.args[1] if len(e.args) > 1 else None
            if isinstance(ts_arg, ast.Column) and ts_arg.name in schema.names:
                return schema.column(ts_arg.name).dtype
        if e.name == "count":
            return DataType.INT64
        if e.name in ("min", "max", "first", "last", "first_value", "last_value"):
            arg = e.args[0] if e.args else None
            if isinstance(arg, ast.Column) and arg.name in schema.names:
                dt = schema.column(arg.name).dtype
                if dt.is_timestamp:
                    return dt
            return DataType.FLOAT64
        return DataType.FLOAT64
    if isinstance(e, ast.Literal):
        if isinstance(e.value, bool):
            return DataType.BOOL
        if isinstance(e.value, int):
            return DataType.INT64
        if isinstance(e.value, float):
            return DataType.FLOAT64
        if isinstance(e.value, str):
            return DataType.STRING
    return None


def _host_sort_order(keys, project, out_names, out_cols, host_cols, schema, env):
    sort_arrays = []
    nrows = len(out_cols[0]) if out_cols else 0
    by_name = dict(zip(out_names, out_cols))
    for k in reversed(keys):  # lexsort: primary key last
        if isinstance(k.expr, ast.Column) and k.expr.name in by_name:
            arr = by_name[k.expr.name]
        else:
            arr = np.asarray(eval_host(k.expr, host_cols, schema, env))
            if arr.ndim == 0:
                arr = np.broadcast_to(arr, (nrows,))
        arr = _sortable(arr, k.asc, k.nulls_first)
        sort_arrays.append(arr)
    return np.lexsort(sort_arrays)


def _sortable(arr: np.ndarray, asc: bool, nulls_first: Optional[bool]) -> np.ndarray:
    if arr.dtype == object or arr.dtype.kind in ("U", "S"):
        mask = np.asarray([v is None for v in arr]) \
            if arr.dtype == object else np.zeros(len(arr), dtype=bool)
        filled = np.where(mask, "", arr.astype(str))
        uniq, codes = np.unique(filled, return_inverse=True)
        key = codes.astype(np.float64)
        key[mask] = np.nan
    else:
        key = arr.astype(np.float64)
    isnan = np.isnan(key)
    if not asc:
        key = -key
    # SQL default: NULLS LAST for ASC, NULLS FIRST for DESC
    nf = nulls_first if nulls_first is not None else (not asc)
    key = np.where(isnan, -np.inf if nf else np.inf, key)
    return key


_ROWS_AGG_SEQ = itertools.count(1)


def _cols_to_scan(table, cols: dict) -> ScanData:
    """Re-encode a rows-mode fragment union (decoded host columns) as a
    ScanData so `_execute_agg` runs the normal device aggregation over
    it — the Final step for non-decomposable aggregates. Rows arrived
    already LWW-deduped and filtered region-side, so no seq/op_type
    machinery applies; the unique data_version keeps the ephemeral
    relation out of every persistent device-cache lineage."""
    from greptimedb_tpu.datatypes.vector import DictVector
    from greptimedb_tpu.storage.region import OP_PUT

    schema = table.schema
    n = len(next(iter(cols.values()))) if cols else 0
    columns: dict[str, np.ndarray] = {}
    tag_dicts: dict[str, np.ndarray] = {}
    for name, arr in cols.items():
        arr = np.asarray(arr)
        if arr.dtype == object:
            dv = DictVector.encode(arr)
            columns[name] = dv.codes
            tag_dicts[name] = dv.values
        else:
            columns[name] = arr
    return ScanData(
        schema=schema, columns=columns,
        seq=np.zeros(n, dtype=np.int64),
        op_type=np.full(n, OP_PUT, dtype=np.int8),
        tag_dicts=tag_dicts, num_rows=n, needs_dedup=False,
        region_id=-1, data_version=next(_ROWS_AGG_SEQ))


def _project_empty(project, schema) -> QueryResult:
    names = [n for n, _ in project.items]
    dtypes = [_infer_dtype(e, schema) for _, e in project.items]
    cols = [np.empty(0) for _ in project.items]
    return QueryResult(names, dtypes, cols)

"""Segment reductions: the TPU-native group-by.

The reference's group-by is DataFusion's row-hash aggregate, the CPU
bottleneck of the TSBS double-groupby queries (SURVEY.md §6). On TPU,
group-by over dictionary-encoded tags + time buckets is a *segment
reduction*: group ids are computed arithmetically (no hashing — tag codes
and bucket indices are already dense ints), then reduced with
scatter-adds/mins/maxes that XLA lowers natively.

All kernels are mask-carrying: padding rows and filtered-out rows simply
contribute identity elements. NaN field values (NULLs) are treated as
SQL semantics: excluded from sum/count/min/max/avg.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from greptimedb_tpu.utils.device_telemetry import kernel_name

# Aggregate ops supported by the kernel. "first"/"last" are by time order
# within the segment (used by lastpoint / PromQL instant selection);
# "rows" counts rows irrespective of NULLs (count(*) / group presence);
# "sumsq" feeds stddev/variance.
AGG_OPS = ("sum", "count", "min", "max", "mean", "first", "last", "rows", "sumsq")


def time_bucket(ts: jax.Array, interval: int, origin: int = 0) -> jax.Array:
    """Floor-align int64 timestamps into buckets of `interval` (same unit).

    Mirrors date_bin / RANGE align (reference query/src/range_select/plan.rs:413)
    and PromQL step alignment. Floor division matches SQL date_bin semantics
    for timestamps before the origin as well.
    """
    return (ts - origin) // interval


def combine_group_ids(
    keys: Sequence[jax.Array],
    sizes: Sequence[int],
    dtype=jnp.int32,
) -> jax.Array:
    """Fuse several dense int keys (tag codes, bucket indices) into one
    dense group id: id = ((k0 * s1 + k1) * s2 + k2) ... Row-major, so sort
    order of the combined id equals lexicographic order of the keys.
    Use dtype=int64 when the product of sizes can exceed 2^31 (e.g. the
    dedup series id over many high-cardinality tags).
    """
    assert len(keys) == len(sizes) and keys
    gid = keys[0].astype(dtype)
    for k, s in zip(keys[1:], sizes[1:]):
        gid = gid * jnp.asarray(s, dtype) + k.astype(dtype)
    return gid


def _masked(values: jax.Array, mask: jax.Array, fill) -> jax.Array:
    return jnp.where(mask, values, jnp.asarray(fill, dtype=values.dtype))


#: rows a float sum adds one after the other before the partial sum
#: joins the others. A running float32 sum rounds every addend to the
#: sum's last place, and equal addends (a gauge that holds its value)
#: round the same way each time: 423,458 rows of one group, 720 equal
#: neighbours at a time, read 2.06e-4 low in one pass (PERF.md, PR 38).
#: In two levels no running sum grows past this many addends before it
#: is added to sums of its own size.
SUM_CHUNK_ROWS = 256
#: most first-level partial sums (chunks x segments x columns) one
#: reduction may hold; past it the groups are many, a group's rows in a
#: block few, and one pass adds them
_SUM_PARTIALS = 1 << 22


def float_segment_sum(values: jax.Array, ids: jax.Array, num_segments: int,
                      indices_are_sorted: bool = False) -> jax.Array:
    """`jax.ops.segment_sum` of a float32 (or narrower) plane in two
    levels: each chunk of SUM_CHUNK_ROWS rows scatters into segments of
    its own, then the chunks are added. The choice is one of shapes and
    dtype alone, so a program is still one per block size. A float64
    plane keeps one pass: its sum carries 29 more bits (the same rows
    drift 1e-13 there), and on the CPU backend two paths that block the
    same rows differently keep answering bit for bit alike."""
    n = values.shape[0]
    width = 1
    for d in values.shape[1:]:
        width *= d
    chunks = -(-n // SUM_CHUNK_ROWS)
    if chunks < 2 or chunks * num_segments * width > _SUM_PARTIALS \
            or not jnp.issubdtype(values.dtype, jnp.floating) \
            or values.dtype.itemsize >= 8:
        return jax.ops.segment_sum(values, ids, num_segments=num_segments,
                                   indices_are_sorted=indices_are_sorted)
    chunk_of = jnp.arange(n, dtype=jnp.int32) // SUM_CHUNK_ROWS
    parts = jax.ops.segment_sum(
        values, chunk_of * num_segments + ids.astype(jnp.int32),
        num_segments=chunks * num_segments)
    return parts.reshape((chunks, num_segments) + values.shape[1:]).sum(axis=0)


def _pallas_mode() -> str:
    import os

    return os.environ.get("GREPTIMEDB_TPU_PALLAS", "auto").lower()


def dense_segment_sum(plane: jax.Array, ids: jax.Array,
                      num_segments: int, finite: bool = False) -> jax.Array:
    """segment_sum for the dense prepared planes, MXU-routed when it
    pays: on TPU backends (or GREPTIMEDB_TPU_PALLAS=on, interpret mode
    elsewhere — how the CPU differential tests drive it) eligible shapes
    run the pallas one-hot-matmul kernel (ops/pallas_segment.py);
    everything else takes XLA's scatter-add. =off pins the scatter.

    The mode is read at TRACE time and baked into the enclosing jit
    cache — set the env var before the engine starts, not per-query.

    `finite` (static, from the caller's cached plane scan): the one-hot
    matmul computes 0*x for every row outside a group, so a single
    +/-Inf value would poison EVERY group with NaN — callers must prove
    the plane finite (the same host pass that detects NaNs) before the
    kernel is allowed. f64 planes only ride in interpret mode: Mosaic
    cannot lower f64 matmuls on the chip."""
    mode = _pallas_mode()
    if mode != "off" and finite and plane.ndim == 2:
        from greptimedb_tpu.ops import pallas_segment as ps

        backend = ps.target_platform()
        dtype_ok = plane.dtype in (jnp.float32, jnp.bfloat16) \
            or backend != "tpu"
        # cheap pure checks first: the canary costs one Mosaic compile,
        # so consult it only for planes that could actually route here
        if dtype_ok and ps.eligible(plane.shape, num_segments) and (
                mode == "on" or (mode == "auto" and backend == "tpu"
                                 and ps.tpu_compile_ok())):
            return ps.pallas_dense_segment_sum(plane, ids, num_segments)
    return float_segment_sum(plane, ids, num_segments)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "ops", "indices_are_sorted"),
)
@kernel_name("segment_agg")
def segment_agg(
    values: jax.Array,  # [N] or [N, F] field values (float)
    seg_ids: jax.Array,  # [N] int32 dense group ids
    mask: jax.Array,  # [N] bool validity (padding & filter)
    num_segments: int,
    ops: tuple[str, ...] = ("sum", "count"),
    ts: Optional[jax.Array] = None,  # [N] int64, required for first/last
    indices_are_sorted: bool = False,
) -> dict[str, jax.Array]:
    """Masked segment reduction. Returns {op: [G] or [G, F]} arrays.

    NULL handling: NaN values are excluded per-element (SQL aggregate
    semantics); `mask` excludes whole rows (padding / WHERE / dedup).
    """
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    n, f = values.shape
    row_mask = mask
    # element validity: row valid and value not NaN
    if jnp.issubdtype(values.dtype, jnp.floating):
        elem_mask = row_mask[:, None] & ~jnp.isnan(values)
    else:
        elem_mask = jnp.broadcast_to(row_mask[:, None], values.shape)
    # invalid rows scatter into a dead segment G (we allocate G+1 and drop it)
    ids = jnp.where(row_mask, seg_ids, jnp.int32(num_segments))
    gsz = num_segments + 1

    seg_sum = functools.partial(
        jax.ops.segment_sum,
        segment_ids=ids,
        num_segments=gsz,
        indices_are_sorted=indices_are_sorted,
    )

    out: dict[str, jax.Array] = {}
    need_sum = any(o in ops for o in ("sum", "mean"))
    need_count = any(o in ops for o in ("count", "mean"))
    # variance = (sumsq - sum^2/n) is catastrophically cancellation-prone:
    # both moments must carry ~2x the data's precision for the subtraction
    # to survive, so sumsq (and the sum it is differenced against) always
    # accumulate in f64 — even on the f32 TPU fast path, where only
    # stddev/variance queries pay the emulation cost
    moment_vals = values
    if "sumsq" in ops and jnp.issubdtype(values.dtype, jnp.floating) \
            and values.dtype != jnp.float64:
        moment_vals = values.astype(jnp.float64)
    sums = counts = None
    if need_sum or "sumsq" in ops:
        sums = float_segment_sum(
            jnp.where(elem_mask, moment_vals, 0).astype(moment_vals.dtype),
            ids, gsz, indices_are_sorted)
    if need_count:
        # int32: exact per-block (block rows << 2^31); cross-block combine
        # upcasts to int64
        counts = seg_sum(elem_mask.astype(jnp.int32))
    if "sum" in ops:
        out["sum"] = sums
    if "count" in ops:
        out["count"] = counts
    if "rows" in ops:
        # [G, 1]: per-group, not per-field
        out["rows"] = seg_sum(row_mask.astype(jnp.int32)[:, None])
    if "sumsq" in ops:
        out["sumsq"] = float_segment_sum(
            jnp.where(elem_mask, moment_vals * moment_vals, 0)
            .astype(moment_vals.dtype), ids, gsz, indices_are_sorted)
    if "mean" in ops:
        denom = jnp.maximum(counts, 1).astype(values.dtype)
        mean = sums.astype(values.dtype) / denom
        out["mean"] = jnp.where(counts > 0, mean, jnp.nan)
    if "min" in ops:
        big = _type_max(values.dtype)
        mins = jax.ops.segment_min(
            jnp.where(elem_mask, values, big),
            ids, num_segments=gsz, indices_are_sorted=indices_are_sorted,
        )
        out["min"] = jnp.where(mins == big, _null_of(values.dtype), mins)
    if "max" in ops:
        small = _type_min(values.dtype)
        maxs = jax.ops.segment_max(
            jnp.where(elem_mask, values, small),
            ids, num_segments=gsz, indices_are_sorted=indices_are_sorted,
        )
        out["max"] = jnp.where(maxs == small, _null_of(values.dtype), maxs)
    if "first" in ops or "last" in ops:
        assert ts is not None, "first/last need the time column"
        # argmin/argmax of ts per segment: reduce packed (ts, row index).
        # ts fits int64; break ties by row index using a second reduction.
        idx = jnp.arange(n, dtype=jnp.int64)
        if "last" in ops:
            best_ts = jax.ops.segment_max(
                jnp.where(row_mask, ts, jnp.iinfo(jnp.int64).min),
                ids, num_segments=gsz, indices_are_sorted=indices_are_sorted,
            )
            at_best = row_mask & (ts == best_ts[ids])
            best_idx = jax.ops.segment_max(
                jnp.where(at_best, idx, -1), ids, num_segments=gsz,
                indices_are_sorted=indices_are_sorted,
            )
            safe = jnp.clip(best_idx, 0, n - 1)
            vals = values[safe]
            out["last"] = jnp.where(best_idx[:, None] >= 0, vals, _null_of(values.dtype))
            out["last_ts"] = best_ts
        if "first" in ops:
            best_ts = jax.ops.segment_min(
                jnp.where(row_mask, ts, jnp.iinfo(jnp.int64).max),
                ids, num_segments=gsz, indices_are_sorted=indices_are_sorted,
            )
            at_best = row_mask & (ts == best_ts[ids])
            # tie-break by MIN row index: the earliest-positioned sample
            # of the earliest instant. Symmetric with `last` (max ts, max
            # idx) and identical to the sorted-input bucketization in
            # ops/window.py — the two flavors must match bit-for-bit or
            # CPU and TPU backends would answer `first` differently for
            # samples sharing a millisecond. (SQL ties can only arise in
            # append-mode tables, where the winner is undefined; LWW
            # dedup removes same-(series, ts) rows everywhere else.)
            best_idx = jax.ops.segment_min(
                jnp.where(at_best, idx, jnp.int64(n)), ids,
                num_segments=gsz, indices_are_sorted=indices_are_sorted,
            )
            safe = jnp.clip(best_idx, 0, n - 1)
            vals = values[safe]
            out["first"] = jnp.where(best_idx[:, None] < n, vals,
                                     _null_of(values.dtype))
            out["first_ts"] = best_ts

    # drop the dead padding segment; restore caller's rank
    trimmed = {}
    for k, v in out.items():
        v = v[:num_segments]
        if squeeze and v.ndim == 2:
            v = v[:, 0]
        trimmed[k] = v
    return trimmed


def combine_partial_aggs(
    partials: dict[str, jax.Array], axis_name: str, with_mean: bool = False
) -> dict[str, jax.Array]:
    """Merge per-shard partial aggregates across a mesh axis with XLA
    collectives — the TPU-native MergeScan (reference
    query/src/dist_plan/merge_scan.rs:122 gathers region streams over
    Flight; here partial sums/counts ride ICI via psum).

    NULL semantics match single-device `segment_agg`: an all-NULL group's
    min/max is NaN (NaN partials are filled with ±inf for the collective,
    then groups that stayed at the fill value are restored to NaN).
    Counts upcast to int64 before psum so >2^31-row totals stay exact.
    """
    out = {}
    for op, v in partials.items():
        if op in ("first", "last", "first_ts", "last_ts"):
            continue  # combined below by (value, ts) pairing
        if op in ("count", "rows"):
            out[op] = jax.lax.psum(v.astype(jnp.int64), axis_name)
        elif op in ("sum", "sumsq"):
            out[op] = jax.lax.psum(v, axis_name)
        elif op == "min":
            big = _type_max(v.dtype)
            mn = jax.lax.pmin(_nan_to(v, big), axis_name)
            out[op] = jnp.where(mn == big, _null_of(v.dtype), mn)
        elif op == "max":
            small = _type_min(v.dtype)
            mx = jax.lax.pmax(_nan_to(v, small), axis_name)
            out[op] = jnp.where(mx == small, _null_of(v.dtype), mx)
        else:
            raise ValueError(f"non-commutative partial agg: {op}")
    # first/last ARE collective-combinable once paired with their
    # companion timestamps: the shard holding the globally oldest/newest
    # ts per group wins; exact-ts ties break deterministically by shard
    # index (lowest wins for first, highest for last). Empty groups keep
    # ts sentinels and so never beat a shard with data; an all-empty
    # group's winner contributes its NaN value, which psum propagates.
    for op, ts_op, pick_last in (("first", "first_ts", False),
                                 ("last", "last_ts", True)):
        if op not in partials:
            continue
        ts = partials[ts_op]
        idx = jax.lax.axis_index(axis_name).astype(ts.dtype)
        if pick_last:
            best = jax.lax.pmax(ts, axis_name)
            wrank = jax.lax.pmax(
                jnp.where(ts == best, idx, jnp.asarray(-1, ts.dtype)),
                axis_name)
        else:
            best = jax.lax.pmin(ts, axis_name)
            hi = jnp.asarray(jnp.iinfo(jnp.int32).max, ts.dtype)
            wrank = jax.lax.pmin(jnp.where(ts == best, idx, hi), axis_name)
        sel = (ts == best) & (idx == wrank)
        v = partials[op]
        selv = jnp.broadcast_to(sel, v.shape)
        out[op] = jax.lax.psum(jnp.where(selv, v, 0.0), axis_name)
        out[ts_op] = best
    if with_mean and "sum" in out and "count" in out:
        denom = jnp.maximum(out["count"], 1).astype(out["sum"].dtype)
        out["mean"] = jnp.where(out["count"] > 0, out["sum"] / denom, jnp.nan)
    return out


def _nan_to(v, fill):
    if jnp.issubdtype(v.dtype, jnp.floating):
        return jnp.where(jnp.isnan(v), jnp.asarray(fill, v.dtype), v)
    return v


def _type_max(dt):
    return jnp.inf if jnp.issubdtype(dt, jnp.floating) else jnp.iinfo(dt).max


def _type_min(dt):
    return -jnp.inf if jnp.issubdtype(dt, jnp.floating) else jnp.iinfo(dt).min


def _null_of(dt):
    return jnp.nan if jnp.issubdtype(dt, jnp.floating) else jnp.asarray(0, dt)

"""Pallas TPU kernel: fused dense segment-sum as a one-hot matmul.

The dense prepared aggregation path (physical.py::_agg_scan_prepared)
reduces a query-invariant [N, W] plane with one dead-segment segment-sum
per block. XLA lowers `jax.ops.segment_sum` to a scatter-add — serialized
row updates that leave the MXU idle. For the dashboard-shaped group
counts (G up to a few thousand: per-minute buckets, host subsets,
bucket x host grids) the same reduction is a matmul:

    out[G, W] = onehot[G, Nb] @ plane[Nb, W]

with the one-hot built in-register from an iota comparison — which is
exactly the systolic array's shape (SURVEY.md §7's fused
filter+bucket+reduce design: the filter arrives as dead-segment ids, the
bucket as the group id, the reduce as the matmul). The grid walks row
blocks sequentially, accumulating into a VMEM-resident [G, W] output
(TPU grids execute in order, so read-modify-write accumulation across
grid steps is the standard reduction pattern, pallas_guide.md).

Selection: ops/segment.py::dense_segment_sum auto-picks this kernel on
TPU backends for eligible shapes and falls back to XLA's scatter
otherwise; GREPTIMEDB_TPU_PALLAS=on forces it off-TPU too, where the
kernels run in Pallas interpret mode (how the differential tests drive
them on CPU), =off disables. On a TPU backend kernels are ALWAYS
compiled by Mosaic: interpret_mode() is the one place that decides.

Reference analog: DataFusion's row-hash GroupedHashAggregateStream
(src/query — the CPU bottleneck of TSBS double-groupby); this kernel is
its MXU-native replacement for the dense-id case, no hashing at all.
"""

from __future__ import annotations

import contextlib
import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from greptimedb_tpu.utils.device_telemetry import kernel_name

_log = logging.getLogger("greptimedb_tpu.pallas")


def target_platform() -> str:
    """Platform the computation being traced will run on: the thread's
    jax.default_device when one is pinned (the host tier of an
    accelerator process pins the CPU backend), else the default
    backend. default_device is part of jit's cache key, so a decision
    traced for one platform is never replayed on the other."""
    dd = jax.config.jax_default_device
    if dd is None:
        return jax.default_backend()
    return dd if isinstance(dd, str) else dd.platform


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted. Only off-TPU, where no
    Mosaic exists (CPU tests under GREPTIMEDB_TPU_PALLAS=on); on a TPU
    they are always compiled — a kernel Mosaic refuses must fail, not
    quietly run as a Python-traced XLA program."""
    return target_platform() != "tpu"


def dispatch_mode() -> str:
    """`mode` label of pallas_dispatch_total."""
    return "interpret" if interpret_mode() else "compiled"


def _x64_off(dtype):
    """The engine runs under jax_enable_x64 (int64 timestamps), and x64
    tracing gives a gridded pallas_call i64 grid/index arithmetic that
    Mosaic cannot lower. Tracing the 32-bit chip kernels under an
    x64-off scope keeps it i32; all their operands are explicit
    f32/i32, so no semantics change. f64 planes (CPU interpret mode)
    keep x64 on: x64-off tracing would canonicalize them down to f32
    and break the kernel's ref dtypes."""
    if dtype == jnp.float64:
        return contextlib.nullcontext()
    return jax.enable_x64(False)

#: widest plane the kernel accepts (lane tile); prepared planes are
#: 2F+1 <= 21 for TSBS's 10 fields
MAX_WIDTH = 128
#: largest padded segment count: out[G, 128] f32 must sit in VMEM with
#: the one-hot block and the plane block
MAX_SEGMENTS = 4096
#: fused kernel: raw value lanes F padded to 8, [vals | valid | rows]
#: layout 2*FW+1 must fit the 128-lane output tile
MAX_FUSED_FIELDS = 56
#: with the sumsq lanes riding along ([vals | valid | rows | sq]):
#: 3*FW+1 <= 128
MAX_FUSED_FIELDS_SUMSQ = 40
#: fused kernel: groups folded per inner-loop step (see _fused_kernel.tile)
FUSED_GROUP_TILE = 256


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _kernel(ids_ref, plane_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]  # [1, Nb] int32
    gp = out_ref.shape[0]
    nb = ids.shape[1]
    # [Gp, Nb] one-hot from an iota comparison — built in registers,
    # never materialized in HBM
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (gp, nb), 0)
              == ids).astype(plane_ref.dtype)
    # HIGHEST: the MXU's default f32 matmul is a single bf16 pass
    # (~1e-2 abs error on segment sums — observed on v5e); multi-pass
    # recovers f32 accuracy and this workload is bandwidth-bound anyway
    out_ref[...] += jnp.dot(onehot, plane_ref[...],
                            preferred_element_type=out_ref.dtype,
                            precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "block_rows"))
@kernel_name("pallas_dense_segment_sum")
def pallas_dense_segment_sum(
    plane: jax.Array,  # [N, W] float values (zeros on invalid rows)
    ids: jax.Array,  # [N] int32 segment ids (dead rows -> num_segments-1)
    num_segments: int,
    block_rows: int = 512,
) -> jax.Array:
    """segment_sum(plane, ids, num_segments) on the MXU. Caller must
    pre-check eligible(); padding rows are appended with zero values
    into the last segment (harmless by construction — the dense
    prepared path's dead segment)."""
    n, w = plane.shape
    wp = MAX_WIDTH
    gp = _round_up(max(num_segments, 8), 8)
    npad = _round_up(max(n, 1), block_rows)
    plane_p = jnp.pad(plane, ((0, npad - n), (0, wp - w)))
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, npad - n),
                    constant_values=num_segments - 1)[None, :]
    with _x64_off(plane.dtype):
        out = pl.pallas_call(
            _kernel,
            grid=(npad // block_rows,),
            in_specs=[
                pl.BlockSpec((1, block_rows), lambda i: (0, i)),
                pl.BlockSpec((block_rows, wp), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((gp, wp), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((gp, wp), plane.dtype),
            interpret=interpret_mode(),
            name="pallas_dense_segment_sum",
        )(ids_p, plane_p)
    return out[:num_segments, :w]


def eligible(shape: tuple, num_segments: int) -> bool:
    """Shapes the kernel handles; everything else takes XLA's scatter."""
    return (len(shape) == 2 and 0 < shape[1] <= MAX_WIDTH
            and 0 < num_segments <= MAX_SEGMENTS)


# ---- fused scan→filter→bucket→aggregate kernel ------------------------------
#
# The dense prepared path pays one host-built [N, 2F+1] plane upload plus
# one segment-sum, one segment-min, and one segment-max dispatch per block
# (each an XLA scatter off the MXU). The fused kernel below replaces the
# whole chain with ONE pallas_call over the RAW value columns: validity
# (NaN) masks, the [vals | valid | rows] reduction plane, and the one-hot
# group matrix are all built in-register — none of them ever exists in
# HBM — and min/max reduce in the same pass. The HBM-resident hot set
# therefore caches only the F raw value lanes per block instead of the
# 2F+1 sum plane plus two F-wide identity-filled extreme planes.


def _fused_kernel(ids_ref, vals_ref, *out_refs, nf, fw, gt, want_min,
                  want_max, want_sumsq):
    i = pl.program_id(0)
    sum_ref = out_refs[0]
    min_ref = out_refs[1] if want_min else None
    max_ref = out_refs[1 + bool(want_min)] if want_max else None
    dt = vals_ref.dtype

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        if want_min:
            min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        if want_max:
            max_ref[...] = jnp.full_like(max_ref, -jnp.inf)

    ids = ids_ref[...]    # [1, Nb] int32 (masked/padding rows -> dead id)
    vals = vals_ref[...]  # [Nb, FW] raw values, NaN = NULL
    gp = sum_ref.shape[0]
    nb = ids.shape[1]
    valid = ~jnp.isnan(vals)                       # [Nb, FW] in-register
    zeroed = jnp.where(valid, vals, jnp.asarray(0, dt))
    pad_w = sum_ref.shape[1] - (3 if want_sumsq else 2) * fw
    # [zeroed | valid | rows-one | 0-pad (| squares)]: the prepared-plane
    # layout, assembled in VMEM registers instead of host RAM + H2D; the
    # variance moment rides the SAME matmul as extra lanes (NaN already
    # zeroed, so squares contribute exactly where elem-valid)
    rows_col = (jax.lax.broadcasted_iota(jnp.int32, (nb, pad_w), 1)
                == 0).astype(dt)
    segs = [zeroed, valid.astype(dt), rows_col]
    if want_sumsq:
        segs.append(zeroed * zeroed)
    plane = jnp.concatenate(segs, axis=1)
    # min/max operands, relaid ONCE per row block: each real field lane
    # as a [1, Nb] row (rows on lanes, like ids) with its NULL mask;
    # fw-nf padding lanes stay at the _init identities
    cols, oks = [], []
    if want_min or want_max:
        for f in range(nf):
            col = vals[:, f][None, :]
            cols.append(col)
            oks.append(~jnp.isnan(col))             # NaN: SQL NULL skip

    def _lanes(parts, ident):
        stacked = jnp.stack(parts, axis=1)          # [gt, nf]
        if fw > nf:                                 # full-width store: pad
            stacked = jnp.concatenate(              # identity lanes back on
                [stacked, jnp.full((gt, fw - nf), ident, dt)], axis=1)
        return stacked

    def tile(g0):
        """Fold this row block into groups [g0, g0+gt). The group axis
        is walked in tiles so the [gt, Nb] one-hot and select
        temporaries stay a few hundred KiB whatever gp is: unrolled
        over all gp rows they cost Mosaic minutes of compile time at
        ~1k groups and blow the scoped-VMEM limit at 4k."""
        rows = pl.ds(g0, gt)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (gt, nb), 0) + g0
                  == ids)
        # see _kernel: HIGHEST recovers f32 accuracy from the bf16 MXU
        # passes
        sum_ref[rows, :] += jnp.dot(onehot.astype(dt), plane,
                                    preferred_element_type=dt,
                                    precision=jax.lax.Precision.HIGHEST)
        mins, maxs = [], []
        for col, ok in zip(cols, oks):
            sel = onehot & ok
            if want_min:
                mins.append(jnp.min(
                    jnp.where(sel, col, jnp.asarray(jnp.inf, dt)), axis=1))
            if want_max:
                maxs.append(jnp.max(
                    jnp.where(sel, col, jnp.asarray(-jnp.inf, dt)), axis=1))
        if want_min:
            min_ref[rows, :] = jnp.minimum(min_ref[rows, :],
                                           _lanes(mins, jnp.inf))
        if want_max:
            max_ref[rows, :] = jnp.maximum(max_ref[rows, :],
                                           _lanes(maxs, -jnp.inf))

    if gp == gt:
        tile(0)
    else:
        def body(t, carry):
            tile(pl.multiple_of(t * gt, gt))
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(gp // gt), body, 0)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "want_min", "want_max",
                                    "want_sumsq", "block_rows"))
@kernel_name("pallas_fused_segment_agg")
def pallas_fused_segment_agg(
    vals: jax.Array,  # [N, F] raw field values (NaN = NULL)
    ids: jax.Array,  # [N] int32 group ids (masked rows -> num_segments-1)
    num_segments: int,
    want_min: bool = False,
    want_max: bool = False,
    want_sumsq: bool = False,
    block_rows: int = 512,
) -> dict:
    """Fused masked segment aggregation on the MXU/VPU: one pallas_call
    emits {"sum" [G, F], "count" [G, F], "rows" [G], "min"/"max" [G, F],
    "sumsq" [G, F]}. Caller must pre-check fused_eligible() and prove
    the values finite (Inf would poison the 0*x matmul — same contract
    as the sum kernel); NaN is handled in-register as SQL NULL. The
    sumsq lanes accumulate in the kernel dtype — callers needing the
    f64 variance contract must feed f64 values (interpret mode / x64
    chips) or stay on the prepared path. Masked rows arrive encoded
    into the dead segment num_segments-1, exactly like the sum kernel;
    empty/all-NULL groups come back as 0 counts and ±inf extremes."""
    n, nf = vals.shape
    fw = _round_up(max(nf, 1), 8)
    gp = _round_up(max(num_segments, 8), 8)
    gt = min(gp, FUSED_GROUP_TILE)
    gp = _round_up(gp, gt)
    npad = _round_up(max(n, 1), block_rows)
    vals_p = jnp.pad(vals, ((0, npad - n), (0, fw - nf)))
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, npad - n),
                    constant_values=num_segments - 1)[None, :]
    out_shapes = [jax.ShapeDtypeStruct((gp, MAX_WIDTH), vals.dtype)]
    out_specs = [pl.BlockSpec((gp, MAX_WIDTH), lambda i: (0, 0))]
    if want_min:
        out_shapes.append(jax.ShapeDtypeStruct((gp, fw), vals.dtype))
        out_specs.append(pl.BlockSpec((gp, fw), lambda i: (0, 0)))
    if want_max:
        out_shapes.append(jax.ShapeDtypeStruct((gp, fw), vals.dtype))
        out_specs.append(pl.BlockSpec((gp, fw), lambda i: (0, 0)))
    kern = functools.partial(_fused_kernel, nf=nf, fw=fw, gt=gt,
                             want_min=want_min, want_max=want_max,
                             want_sumsq=want_sumsq)
    with _x64_off(vals.dtype):
        outs = pl.pallas_call(
            kern,
            grid=(npad // block_rows,),
            in_specs=[
                pl.BlockSpec((1, block_rows), lambda i: (0, i)),
                pl.BlockSpec((block_rows, fw), lambda i: (i, 0)),
            ],
            out_specs=out_specs,
            out_shape=out_shapes,
            interpret=interpret_mode(),
            name="pallas_fused_segment_agg",
        )(ids_p, vals_p)
    total = outs[0]
    g = num_segments
    out = {
        "sum": total[:g, :nf],
        "count": total[:g, fw:fw + nf],
        "rows": total[:g, 2 * fw],
    }
    if want_sumsq:
        # squares sit at the plane's tail: [.. | rows+pad | sq] layout
        out["sumsq"] = total[:g, MAX_WIDTH - fw:MAX_WIDTH - fw + nf]
    k = 1
    if want_min:
        out["min"] = outs[k][:g, :nf]
        k += 1
    if want_max:
        out["max"] = outs[k][:g, :nf]
    return out


def fused_eligible(nf: int, num_segments: int,
                   want_sumsq: bool = False) -> bool:
    """Shapes the fused kernel handles; everything else takes the
    prepared-plane path (XLA scatter reductions). The sumsq lanes eat a
    third field-width stripe of the 128-lane output tile: 3*FW+1 <= 128
    caps the field count at 40 when the variance moment rides along."""
    limit = MAX_FUSED_FIELDS_SUMSQ if want_sumsq else MAX_FUSED_FIELDS
    return 0 < nf <= limit and 0 < num_segments <= MAX_SEGMENTS


#: Mosaic canary verdicts, one per kernel family: {"ok": bool, "error":
#: the compiler's message or None}. Absent until first consulted.
_CANARY: dict = {}


def _canary(name: str, probe) -> bool:
    """Run a one-shot compile canary and keep its verdict AND the
    compiler's message: auto mode degrades to the XLA scatter path on a
    chip that cannot compile a kernel family (serve, don't crash), but
    the refusal is logged, counted and exported (device_status) — a
    canary that fails is a bug to fix, never a quiet fallback."""
    verdict = _CANARY.get(name)
    if verdict is None:
        try:
            verdict = {"ok": bool(probe()), "error": None}
            if not verdict["ok"]:
                verdict["error"] = "canary result mismatch"
        except Exception as e:  # noqa: BLE001 — any compile failure means "don't"
            verdict = {"ok": False,
                       "error": f"{type(e).__name__}: {e}"[:4000]}
        if not verdict["ok"]:
            from greptimedb_tpu.utils.metrics import DEVICE_DEGRADATIONS

            DEVICE_DEGRADATIONS.inc(kind=f"canary_{name}")
            _log.error("pallas %s canary failed; eligible shapes take the "
                       "XLA scatter path: %s", name, verdict["error"])
        _CANARY[name] = verdict
    return verdict["ok"]


def canary_status() -> dict:
    """Verdicts consulted so far ({"dense"/"fused": {"ok", "error"}})."""
    return {k: dict(v) for k, v in _CANARY.items()}


def fused_tpu_compile_ok() -> bool:
    """One-shot Mosaic canary for the FUSED kernel (min/max loop + the
    in-register plane assembly exercise lowering paths the plain sum
    kernel never touches): auto mode consults this before routing a
    query."""
    def probe():
        out = pallas_fused_segment_agg(
            jnp.ones((8, 2), jnp.float32), jnp.zeros(8, jnp.int32), 2,
            want_min=True, want_max=True)
        return (abs(float(out["sum"][0, 0]) - 8.0) < 1e-6
                and abs(float(out["min"][0, 0]) - 1.0) < 1e-6)

    return _canary("fused", probe)


def tpu_compile_ok() -> bool:
    """One-shot Mosaic canary for the one-hot segment-sum kernel: `auto`
    mode consults this before routing planes to the kernel."""
    def probe():
        out = pallas_dense_segment_sum(
            jnp.ones((8, 2), jnp.float32), jnp.zeros(8, jnp.int32), 2)
        return abs(float(out[0, 0]) - 8.0) < 1e-6

    return _canary("dense", probe)

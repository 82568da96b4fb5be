"""Windowed (range-vector) kernels over a regular evaluation grid.

TPU-native replacement for the reference's `RangeArray` ragged windows +
`RangeManipulate`/`InstantManipulate` operators (promql/src/range_array.rs:68,
extension_plan/*.rs). Instead of materializing per-window sample lists,
samples are bucketed onto the step grid with one segment reduction, then:

  - window sums/counts  = cumulative-sum differences along the bucket axis
  - last/first sample   = latest/earliest-nonempty-bucket gathers (cummax /
                          reverse-cummin) + exact timestamp validation
  - window min/max      = w-step unrolled running fmin/fmax over bucket mins

Exactness: range windows require the range to be a multiple of the step
(buckets tile windows exactly); instant-selector lookback is exact for any
length because the gathered last-sample timestamp is re-validated against
the true window edge.

Shapes: samples [N] -> bucket grid [S, B, C] -> windows [S, T, C], where
S = series, T = eval steps, B = T + w buckets, C = value channels (e.g.
raw + counter-reset-adjusted values ride one kernel call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from greptimedb_tpu.ops.segment import segment_agg
from greptimedb_tpu.utils.device_telemetry import kernel_name

BIG = jnp.iinfo(jnp.int32).max


@functools.partial(
    jax.jit,
    static_argnames=("num_series", "num_steps", "w", "stats",
                     "sorted_input"),
)
@kernel_name("window_stats")
def window_stats(
    sidx: jax.Array,  # [N] int32 series index
    ts: jax.Array,  # [N] float64 sample time (seconds)
    channels: jax.Array,  # [N, C] float value channels
    valid: jax.Array,  # [N] bool
    t0,  # scalar: first eval timestamp (seconds)
    step,  # scalar: eval step (seconds)
    num_series: int,
    num_steps: int,
    w: int,  # window length in steps
    stats: tuple[str, ...] = ("sum", "count", "last"),
    sorted_input: bool = False,
) -> dict[str, jax.Array]:
    """Compute per-(series, eval-step) window statistics. Window j covers
    (t0 + (j-w)*step, t0 + j*step] — i.e. w whole step-buckets ending at
    eval time j. Outputs [S, T, C] (ts outputs [S, T]).

    sorted_input=True asserts rows are sorted by (series, ts) — the
    storage scan's layout — and switches bucketization from scatter-adds
    (the dominant cost at dashboard scale: millions of serialized
    updates) to cumulative-sum differences and boundary gathers over
    searchsorted bucket edges."""
    S, T, B = num_series, num_steps, num_steps + w
    n, C = channels.shape

    # bucket: sample at exactly an eval time belongs to that step's bucket
    b = jnp.ceil((ts - t0) / step).astype(jnp.int32) + (w - 1)
    ok = valid & (b >= 0) & (b < B)

    seg_ops = []
    if "sum" in stats or "count" in stats:
        seg_ops += ["sum", "count"]
    if "last" in stats:
        seg_ops.append("last")
    if "first" in stats:
        seg_ops.append("first")
    if "min" in stats:
        seg_ops.append("min")
    if "max" in stats:
        seg_ops.append("max")
    seg_ops = tuple(dict.fromkeys(seg_ops))
    if sorted_input:
        per_bucket = _bucketize_sorted(sidx, ts, channels, ok, b, S, B,
                                       seg_ops)
    else:
        gid = jnp.where(ok, sidx * B + b, S * B).astype(jnp.int32)
        per_bucket = segment_agg(
            channels, gid, ok, S * B, ops=seg_ops, ts=_ts_to_int(ts),
        )

    out: dict[str, jax.Array] = {}
    j = jnp.arange(T)

    def grid(x, C_=None):
        return x.reshape(S, B) if C_ is None else x.reshape(S, B, C_)

    bcount = grid(per_bucket["count"], C) if "count" in per_bucket else None

    if "sum" in stats:
        bsum = grid(per_bucket["sum"], C)
        cs = exclusive_cumsum(bsum)
        out["sum"] = cs[:, w:w + T] - cs[:, 0:T]
    if "count" in stats:
        cc = jnp.concatenate([jnp.zeros((S, 1, C), jnp.int64),
                              jnp.cumsum(bcount.astype(jnp.int64), axis=1)], axis=1)
        out["count"] = cc[:, w:w + T] - cc[:, 0:T]

    nonempty = None
    if bcount is not None:
        nonempty = bcount[:, :, 0] > 0  # row presence: channel 0 mask
    if "last" in stats:
        lv = grid(per_bucket["last"], C)
        lt = grid(per_bucket["last_ts"])
        nb = jnp.where(nonempty, jnp.arange(B)[None, :], -1)
        latest = jax.lax.cummax(nb, axis=1)
        lb = latest[:, w - 1:w - 1 + T]  # [S, T]
        has = lb >= j[None, :]
        safe = jnp.clip(lb, 0, B - 1)
        lval = jnp.take_along_axis(lv, safe[:, :, None], axis=1)
        lts = _ts_to_float(jnp.take_along_axis(lt, safe, axis=1))
        out["last"] = jnp.where(has[:, :, None], lval, jnp.nan)
        out["last_ts"] = jnp.where(has, lts, -jnp.inf)
    if "first" in stats:
        fv = grid(per_bucket["first"], C)
        ft = grid(per_bucket["first_ts"])
        fb = jnp.where(nonempty, jnp.arange(B)[None, :], BIG)
        earliest = jnp.flip(jax.lax.cummin(jnp.flip(fb, axis=1), axis=1), axis=1)
        fbj = earliest[:, 0:T]
        has = fbj <= (j[None, :] + w - 1)
        safe = jnp.clip(fbj, 0, B - 1)
        fval = jnp.take_along_axis(fv, safe[:, :, None], axis=1)
        fts = _ts_to_float(jnp.take_along_axis(ft, safe, axis=1))
        out["first"] = jnp.where(has[:, :, None], fval, jnp.nan)
        out["first_ts"] = jnp.where(has, fts, jnp.inf)
    if "min" in stats:
        bmin = grid(per_bucket["min"], C)
        acc = bmin[:, 0:T]
        for k in range(1, w):
            acc = jnp.fmin(acc, bmin[:, k:k + T])
        out["min"] = acc
    if "max" in stats:
        bmax = grid(per_bucket["max"], C)
        acc = bmax[:, 0:T]
        for k in range(1, w):
            acc = jnp.fmax(acc, bmax[:, k:k + T])
        out["max"] = acc
    return out


def _bucketize_sorted(sidx, ts, channels, ok, b, S, B, seg_ops):
    """Per-bucket stats for (series, ts)-SORTED samples, matching
    segment_agg's output contract over gsz = S*B segments.

    Valid rows' bucket ids are globally non-decreasing (series ascending,
    ts ascending within), so bucket edges come from ONE searchsorted over
    a monotone id envelope (cummax carries the last valid id across
    interleaved invalid rows), sums/counts are cumulative-sum
    differences, and first/last rows are gathers at the edges — no
    scatters at all. min/max (rare stats: *_over_time extremes) keep the
    scatter; everything else is O(N + gsz log N) sequential traffic."""
    n, C = channels.shape
    gsz = S * B
    gid = sidx.astype(jnp.int64) * B + b.astype(jnp.int64)
    gid_mono = jax.lax.cummax(jnp.where(ok, gid, -1))
    targets = jnp.arange(gsz, dtype=jnp.int64)
    starts = jnp.searchsorted(gid_mono, targets, side="left")
    ends = jnp.searchsorted(gid_mono, targets, side="right")
    okc = jnp.concatenate([jnp.zeros(1, jnp.int64),
                           jnp.cumsum(ok.astype(jnp.int64))])
    present = (okc[ends] - okc[starts]) > 0

    per_bucket: dict[str, jax.Array] = {}
    if "sum" in seg_ops or "count" in seg_ops:
        elem = ok[:, None] & ~jnp.isnan(channels)
        zc = jnp.where(elem, channels, 0).astype(channels.dtype)
        cs = jnp.concatenate(
            [jnp.zeros((1, C), zc.dtype), jnp.cumsum(zc, axis=0)])
        per_bucket["sum"] = cs[ends] - cs[starts]
        ec = jnp.concatenate(
            [jnp.zeros((1, C), jnp.int64),
             jnp.cumsum(elem.astype(jnp.int64), axis=0)])
        per_bucket["count"] = ec[ends] - ec[starts]
    idxs = jnp.arange(n, dtype=jnp.int64)
    ts_int = _ts_to_int(ts)
    if "last" in seg_ops:
        lastpos = jax.lax.cummax(jnp.where(ok, idxs, -1))
        li = lastpos[jnp.clip(ends - 1, 0, n - 1)]
        pv = present & (li >= 0)
        safe = jnp.clip(li, 0, n - 1)
        per_bucket["last"] = jnp.where(pv[:, None], channels[safe],
                                       jnp.nan)
        per_bucket["last_ts"] = jnp.where(pv, ts_int[safe],
                                          jnp.iinfo(jnp.int64).min)
    if "first" in seg_ops:
        firstpos = jnp.flip(
            jax.lax.cummin(jnp.flip(jnp.where(ok, idxs, n))))
        fi = firstpos[jnp.clip(starts, 0, n - 1)]
        pv = present & (fi < n)
        safe = jnp.clip(fi, 0, n - 1)
        per_bucket["first"] = jnp.where(pv[:, None], channels[safe],
                                        jnp.nan)
        per_bucket["first_ts"] = jnp.where(pv, ts_int[safe],
                                           jnp.iinfo(jnp.int64).max)
    mm = tuple(o for o in ("min", "max") if o in seg_ops)
    if mm:
        gid32 = jnp.where(ok, gid, gsz).astype(jnp.int32)
        per_bucket.update(segment_agg(channels, gid32, ok, gsz, ops=mm))
    return per_bucket


def _ts_to_int(ts):
    # segment first/last need an integer time key; milliseconds keeps
    # ordering at PromQL resolution
    return (ts * 1000.0).astype(jnp.int64)


def _ts_to_float(t_int):
    return t_int.astype(jnp.float64) / 1000.0


#: rows a block of `running_sum` holds: its inner loop takes this many
#: steps over all blocks at once, its outer loop one step a block
_SUM_BLOCK = 2048


def _scan_sums(cols: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(totals, inclusive running sums) along axis 0, one loop step a
    row. `jnp.cumsum` lowers to a reduce-window, which in the chip's
    emulated float64 takes XLA 170-370 s to compile PER SHAPE (PERF.md,
    PR 27: 1020 of the 1034 s a cold prom-fleet-board spent compiling);
    a loop whose body is one add compiles in seconds."""
    def step(acc, row):
        acc = acc + row
        return acc, acc

    return jax.lax.scan(step, jnp.zeros(cols.shape[1:], cols.dtype), cols)


def running_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a flat array: blocks of _SUM_BLOCK rows
    summed side by side, then the blocks' totals carried across."""
    n = x.shape[0]
    blocks = -(-n // _SUM_BLOCK)
    padded = jnp.pad(x, (0, blocks * _SUM_BLOCK - n))
    totals, within = _scan_sums(padded.reshape(blocks, _SUM_BLOCK).T)

    def carry(acc, total):
        return acc + total, acc

    _, before = jax.lax.scan(carry, jnp.zeros((), x.dtype), totals)
    return (within.T + before[:, None]).reshape(-1)[:n]


@jax.jit
@kernel_name("counter_adjust")
def counter_adjust(sidx_sorted: jax.Array, values_sorted: jax.Array) -> jax.Array:
    """Reset-corrected counter values. Input MUST be sorted by (series, ts).
    adjusted[i] = v[i] + cumulative resets before i; within-series
    differences of `adjusted` equal PromQL's reset-corrected deltas
    (reference promql/src/functions/extrapolate_rate.rs semantics)."""
    prev_v = jnp.concatenate([values_sorted[:1], values_sorted[:-1]])
    prev_s = jnp.concatenate([sidx_sorted[:1], sidx_sorted[:-1]])
    same = sidx_sorted == prev_s
    reset = jnp.where(same & (values_sorted < prev_v), prev_v, 0.0)
    # global running sum is per-series-correct for *differences* because rows
    # are series-contiguous
    return values_sorted + running_sum(reset)


@functools.partial(jax.jit, static_argnames=("is_counter", "is_rate"))
@kernel_name("extrapolated_delta")
def extrapolated_delta(
    first_val, first_ts, last_val, last_ts, count, window_start, window_end,
    is_counter: bool, is_rate: bool, range_s: float = 1.0, first_raw=None,
):
    """PromQL extrapolation (reference extrapolate_rate.rs:85-92): the raw
    last-first delta is extrapolated toward the window edges, limited to
    half an average sample interval when the edge is far. All inputs
    [S, T] (vals [S, T, 1-channel already selected]). `first_raw`: a
    counter's first sample as written, where first_val is reset-adjusted:
    the zero crossing is the raw value's, whatever resets the load that
    adjusted it began before."""
    sampled = last_ts - first_ts
    delta = last_val - first_val
    cnt = count.astype(first_val.dtype)
    ok = (cnt >= 2) & (sampled > 0)
    avg_interval = sampled / jnp.maximum(cnt - 1, 1)
    to_start = first_ts - window_start
    to_end = window_end - last_ts
    if is_counter:
        # counters can't be negative: limit start extrapolation to the
        # zero crossing
        with jax.numpy_dtype_promotion("standard"):
            slope = delta / jnp.maximum(sampled, 1e-10)
            zero_limit = jnp.where(
                slope > 0,
                (first_val if first_raw is None else first_raw) / slope,
                jnp.inf)
            to_start = jnp.minimum(to_start, zero_limit)
    threshold = avg_interval * 1.1
    ext_start = jnp.where(to_start < threshold, to_start, avg_interval / 2)
    ext_end = jnp.where(to_end < threshold, to_end, avg_interval / 2)
    factor = (sampled + ext_start + ext_end) / jnp.maximum(sampled, 1e-10)
    result = delta * factor
    if is_rate:
        result = result / range_s
    return jnp.where(ok, result, jnp.nan)


@functools.partial(jax.jit,
                   static_argnames=("num_series", "num_steps", "w"))
@kernel_name("window_edges")
def window_edges(
    sidx: jax.Array,  # [N] int32 series index, sorted major
    ts: jax.Array,  # [N] float64 sample time (seconds), sorted within
    channels: jax.Array,  # [N, C] float value channels (NaN-free)
    t0,  # scalar: first eval timestamp (seconds)
    step,  # scalar: eval step (seconds)
    num_series: int,
    num_steps: int,
    w: int,  # window length in steps
) -> dict[str, jax.Array]:
    """first/last/count per (series, eval-window) via composite-key
    searchsorted — the boundary-gather evaluation for the rate family.

    PromQL's rate/increase/delta consume only each window's EDGE
    samples plus the in-window count (reference
    extrapolate_rate.rs:85-92; counter resets ride the pre-computed
    "adjusted" channel), so evaluating them needs no per-sample
    bucketization: with rows sorted by (series, ts), a window's
    first/last/count are two binary-search probes into one monotone
    composite key. At the tracked scale (10k series x 1 day @15s =
    57.6M samples, 240 eval points) this replaces an O(N)-per-eval
    57.6M-row pass with 4.8M probes — the same asymmetry a numpy
    searchsorted reference exploits, now on device.

    Window j covers (t0 + (j-w)·step, t0 + j·step], matching
    window_stats. Requires NaN-free channels (callers gate — LWW
    tombstone NaNs would need masking the probes cannot see).
    Returns {"first": [S,T,C], "first_ts": [S,T], "last": [S,T,C],
    "last_ts": [S,T], "count": [S,T,1]} — window_stats-shaped for the
    rate consumers."""
    S, T = num_series, num_steps
    n, C = channels.shape
    ts = ts.astype(jnp.float64)
    base = jnp.min(ts)
    # series band width: larger than any in-band offset OR window edge
    K = (jnp.max(ts) - base) + (num_steps + w + 2) * jnp.abs(step) + 2.0
    key = sidx.astype(jnp.float64) * K + (ts - base)
    j = jnp.arange(T, dtype=jnp.float64)
    # clip edges into the band so an out-of-range window cannot probe a
    # NEIGHBORING series' key range
    lo_off = jnp.clip(t0 + (j - w) * step - base, -0.5, K - 1.0)
    hi_off = jnp.clip(t0 + j * step - base, -0.5, K - 1.0)
    s_base = jnp.arange(S, dtype=jnp.float64) * K
    i0 = jnp.searchsorted(  # first sample with ts > lo (exclusive edge)
        key, (s_base[:, None] + lo_off[None, :]).ravel(),
        side="right").reshape(S, T)
    i1 = jnp.searchsorted(  # one past the last sample with ts <= hi
        key, (s_base[:, None] + hi_off[None, :]).ravel(),
        side="right").reshape(S, T)
    count = i1 - i0
    has = count > 0
    fi = jnp.clip(i0, 0, max(n - 1, 0))
    li = jnp.clip(i1 - 1, 0, max(n - 1, 0))
    first = jnp.where(has[..., None], channels[fi], jnp.nan)
    last = jnp.where(has[..., None], channels[li], jnp.nan)
    first_ts = jnp.where(has, ts[fi], jnp.nan)
    last_ts = jnp.where(has, ts[li], jnp.nan)
    return {"first": first, "first_ts": first_ts, "last": last,
            "last_ts": last_ts,
            "count": count.astype(jnp.int64)[..., None]}


@functools.partial(jax.jit, static_argnames=("num_steps", "w"))
@kernel_name("window_edges_grid")
def window_edges_grid(
    grid: jax.Array,  # [P] float64 shared sample grid (seconds, sorted)
    mat: jax.Array,  # [S, P, C] values pivoted onto the grid (NaN-free)
    t0,  # scalar: first eval timestamp (seconds)
    step,  # scalar: eval step (seconds)
    num_steps: int,
    w: int,
) -> dict[str, jax.Array]:
    """window_edges when every series shares ONE complete sample grid —
    the scrape-aligned shape Prometheus data overwhelmingly has. Window
    edges become T probes into the [P] grid (not S·T probes into the
    flat samples), and first/last are column gathers from the pivoted
    matrix: rate over 10k series x 1 day @15s evaluates in
    milliseconds. Same output contract as window_edges."""
    S, P, C = mat.shape
    T = num_steps
    j = jnp.arange(T, dtype=jnp.float64)
    lo = t0 + (j - w) * step  # exclusive lower edge
    hi = t0 + j * step        # inclusive upper edge
    i0 = jnp.searchsorted(grid, lo, side="right")
    i1 = jnp.searchsorted(grid, hi, side="right")  # one past the last
    count = i1 - i0  # [T], identical for every series (complete grid)
    has = count > 0
    fi = jnp.clip(i0, 0, max(P - 1, 0))
    li = jnp.clip(i1 - 1, 0, max(P - 1, 0))
    first = jnp.where(has[None, :, None], mat[:, fi, :], jnp.nan)
    last = jnp.where(has[None, :, None], mat[:, li, :], jnp.nan)
    first_ts = jnp.broadcast_to(
        jnp.where(has, grid[fi], jnp.nan)[None, :], (S, T))
    last_ts = jnp.broadcast_to(
        jnp.where(has, grid[li], jnp.nan)[None, :], (S, T))
    count_st = jnp.broadcast_to(
        count.astype(jnp.int64)[None, :, None], (S, T, 1))
    return {"first": first, "first_ts": first_ts, "last": last,
            "last_ts": last_ts, "count": count_st}


@functools.partial(jax.jit, static_argnames=("num_steps", "w"))
@kernel_name("window_sums_grid")
def window_sums_grid(
    grid: jax.Array,  # [P] float64 shared sample grid (seconds, sorted)
    cs: jax.Array,  # [S, P+1, C] exclusive prefix sums over the pivot
    t0,
    step,
    num_steps: int,
    w: int,
) -> dict[str, jax.Array]:
    """Window sums/counts on a complete shared grid: one cumulative sum
    over the pivot (cached by the caller), then every (window, series)
    sum is a two-gather difference — the sum_over_time/avg_over_time
    analog of window_edges_grid. Window j covers
    (t0 + (j-w)·step, t0 + j·step], matching window_stats."""
    S = cs.shape[0]
    T = num_steps
    j = jnp.arange(T, dtype=jnp.float64)
    i0 = jnp.searchsorted(grid, t0 + (j - w) * step, side="right")
    i1 = jnp.searchsorted(grid, t0 + j * step, side="right")
    count = i1 - i0
    out_sum = cs[:, i1, :] - cs[:, i0, :]  # [S, T, C]
    count_st = jnp.broadcast_to(
        count.astype(jnp.int64)[None, :, None], (S, T, 1))
    return {"sum": out_sum, "count": count_st}


def _grid_points(grid, mat, i0, n: int) -> tuple:
    return (jax.lax.dynamic_slice_in_dim(grid, i0, n),
            jax.lax.dynamic_slice_in_dim(mat, i0, n, axis=1))


@functools.partial(jax.jit, static_argnames=("n",))
@kernel_name("grid_window")
def grid_window(grid: jax.Array, mat: jax.Array, i0, n: int) -> tuple:
    """Points [i0, i0 + n) of a pivot: (grid [n], mat [S, n, C]). A
    request's own range of a pivot that spans more: the prefix sums of
    window_sums_grid then run over that range and no longer one."""
    return _grid_points(grid, mat, i0, n)


@functools.partial(jax.jit, static_argnames=("n",))
@kernel_name("grid_window_flat")
def grid_window_flat(grid: jax.Array, mat: jax.Array, i0, n: int) -> tuple:
    """The same points as (series, ts)-sorted samples, (sidx [S*n],
    ts [S*n], channels [S*n, C]): what window_stats takes."""
    g, m = _grid_points(grid, mat, i0, n)
    S, _, C = mat.shape
    return (jnp.repeat(jnp.arange(S, dtype=jnp.int32), n), jnp.tile(g, S),
            m.reshape(S * n, C))


def rate_of_edges(st: dict, times: jax.Array, range_s, is_counter: bool,
                  is_rate: bool) -> jax.Array:
    """rate / increase / delta from window edges (`window_edges*` or
    `window_stats` with count, first, last) at the step times [T]: the
    channel a counter's reset adjustment rides in, and the
    extrapolation. [S, T]."""
    ch = 1 if is_counter else 0
    return extrapolated_delta(
        st["first"][:, :, ch], st["first_ts"],
        st["last"][:, :, ch], st["last_ts"], st["count"][:, :, 0],
        times[None, :] - range_s, times[None, :],
        is_counter=is_counter, is_rate=is_rate, range_s=range_s,
        first_raw=st["first"][:, :, 0] if is_counter else None)


def over_time_of_stats(st: dict, fn: str) -> jax.Array:
    """sum_over_time / avg_over_time / count_over_time from window
    sums and counts, NaN where a window holds no sample. [S, T]."""
    cnt = st["count"][:, :, 0]
    if fn == "count_over_time":
        out = cnt.astype(jnp.float64)
    else:
        out = st["sum"][:, :, 0]
        if fn == "avg_over_time":
            out = out / jnp.maximum(cnt, 1)
    return jnp.where(cnt > 0, out, jnp.nan)


def grid_rate(grid: jax.Array, mat: jax.Array, t0, step, range_s,
              num_steps: int, w: int, is_counter: bool,
              is_rate: bool) -> jax.Array:
    """rate / increase / delta of every series of a pivot, [S, P, C] ->
    [S, T]: the window edges, the step times (made here from `t0` and
    `step`, as the edges are) and `rate_of_edges`. Pure: traced inside
    the program that calls it."""
    st = window_edges_grid(grid, mat, t0, step, num_steps=num_steps, w=w)
    times = t0 + jnp.arange(num_steps, dtype=jnp.float64) * step
    return rate_of_edges(st, times, range_s, is_counter, is_rate)


def grid_over_time(grid: jax.Array, mat: jax.Array, i0, t0, step, n: int,
                   num_steps: int, w: int, fn: str) -> jax.Array:
    """sum_over_time / avg_over_time / count_over_time of every series
    of a pivot, [S, P, C] -> [S, T]. The sums run over the request's
    OWN `n` grid points from `i0` on, so no difference of two prefixes
    is taken over a longer prefix than the range needs; a count needs
    the probes alone. Pure."""
    if fn == "count_over_time":
        st = window_edges_grid(grid, mat, t0, step, num_steps=num_steps,
                               w=w)
    else:
        own_grid, own = _grid_points(grid, mat, i0, n)
        st = window_sums_grid(own_grid, exclusive_cumsum(own), t0, step,
                              num_steps=num_steps, w=w)
    return over_time_of_stats(st, fn)


@jax.jit
@kernel_name("cumsum")
def _cumsum_axis1(mat: jax.Array) -> jax.Array:
    """`jnp.cumsum(mat, axis=1)` as a loop (see `_scan_sums`). It keeps
    the name of the eager program it replaces, `jit_cumsum`, which the
    benchmark's `cumsum_ms_per_query` reads."""
    _, sums = _scan_sums(jnp.moveaxis(mat, 1, 0))
    return jnp.moveaxis(sums, 0, 1)


@kernel_name("exclusive_cumsum")
def exclusive_cumsum(mat: jax.Array) -> jax.Array:
    """[S, P, C] -> [S, P+1, C] exclusive prefix sums along axis 1 (the
    shared idiom of window_stats' window sums and window_sums_grid)."""
    S, _, C = mat.shape
    return jnp.concatenate(
        [jnp.zeros((S, 1, C), mat.dtype), _cumsum_axis1(mat)],
        axis=1)

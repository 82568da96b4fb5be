"""Classic-histogram quantiles: every group's `le` buckets folded at once.

TPU-native form of the reference's `HistogramFold` plan node
(promql/src/extension_plan/histogram_fold.rs:61): where the reference
walks one group's buckets at a time, the groups (label sets minus `le`)
are one axis of a dense [groups, buckets, steps] block, ragged groups
padded to the widest and masked, and Prometheus' `bucketQuantile`
(promql/quantile.go) runs over the bucket axis of all of them in one
program. The quantile and the bucket bounds are operands, so every φ and
every bucket layout of one shape share an executable.

The rules, in Prometheus' order:
  - NaN φ -> NaN, φ < 0 -> -Inf, φ > 1 -> +Inf, whatever the buckets;
  - a group whose highest bucket is not `+Inf`, or with fewer than two
    buckets -> NaN;
  - cumulative counts are made monotone along `le` (a scrape may catch a
    histogram between two increments); an absent sample counts 0;
  - no observations (`+Inf` count 0) -> NaN;
  - rank = φ * observations; the first bucket below `+Inf` whose count
    reaches the rank holds the quantile, else `+Inf` does;
  - in `+Inf` the answer is the highest finite bound; in a first bucket
    whose upper bound is <= 0 it is that bound; else linear
    interpolation from the bucket's lower bound (0 for the first) by
    (rank - count below) / count inside.
Departures, as the evaluation had them before it was one kernel: buckets
of equal bound are not coalesced, no tolerance for small float deltas
between cumulative counts, and an empty first bucket under φ = 0 answers
its lower bound where Prometheus divides 0 by 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from greptimedb_tpu.utils.device_telemetry import kernel_name


@jax.jit
@kernel_name("histogram_fold")
def histogram_fold(
    counts: jax.Array,  # [G, B, T] cumulative counts, buckets ascending in le
    bounds: jax.Array,  # [G, B] float64 upper bounds (`le`), padding last
    valid: jax.Array,  # [G, B] bool: a bucket of the group, not padding
    phi,  # scalar: the quantile
) -> jax.Array:
    """φ-quantile of every group at every step -> [G, T] float64."""
    counts = counts.astype(jnp.float64)
    phi = jnp.asarray(phi, jnp.float64)
    num_b = counts.shape[1]
    nb = valid.sum(axis=1).astype(jnp.int32)  # [G] buckets of the group
    top = jnp.maximum(nb - 1, 0)  # [G] where +Inf has to be
    top_bound = jnp.take_along_axis(bounds, top[:, None], axis=1)[:, 0]
    defined = (nb >= 2) & jnp.isposinf(top_bound)
    # padding sits after the last bucket: under the running maximum it
    # repeats the +Inf count and is never the first to reach a rank
    cum = jax.lax.cummax(
        jnp.where(valid[:, :, None], jnp.nan_to_num(counts), 0.0), axis=1)
    total = jnp.take_along_axis(cum, top[:, None, None], axis=1)[:, 0]
    rank = phi * total  # [G, T]
    slot = jnp.arange(num_b, dtype=jnp.int32)[None, :, None]
    reached = (cum >= rank[:, None, :]) & (slot < top[:, None, None])
    b = jnp.where(reached.any(axis=1), jnp.argmax(reached, axis=1),
                  top[:, None])  # [G, T]
    below = jnp.maximum(b - 1, 0)
    upper = jnp.take_along_axis(bounds, b, axis=1)
    lower = jnp.where(b > 0, jnp.take_along_axis(bounds, below, axis=1), 0.0)
    cum_b = jnp.take_along_axis(cum, b[:, None, :], axis=1)[:, 0]
    cum_below = jnp.where(
        b > 0, jnp.take_along_axis(cum, below[:, None, :], axis=1)[:, 0],
        0.0)
    inside = jnp.maximum(cum_b - cum_below, 1e-300)
    frac = jnp.clip((rank - cum_below) / inside, 0.0, 1.0)
    res = lower + (upper - lower) * frac
    highest_finite = jnp.take_along_axis(
        bounds, jnp.maximum(top - 1, 0)[:, None], axis=1)
    res = jnp.where(b >= top[:, None], highest_finite, res)
    res = jnp.where((b == 0) & (upper <= 0), upper, res)
    res = jnp.where(defined[:, None] & (total > 0), res, jnp.nan)
    res = jnp.where(phi < 0, -jnp.inf, jnp.where(phi > 1, jnp.inf, res))
    return jnp.where(jnp.isnan(phi), jnp.nan, res)

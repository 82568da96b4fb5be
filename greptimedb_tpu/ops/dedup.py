"""Sort-based merge-dedup: the TPU-native MergeReader.

The reference merges memtable + SST iterators with a k-way binary-heap merge
and dedups by (primary key, timestamp) keeping the highest sequence
(mito2 read/merge.rs:39-115, dedup in read.rs). Branchy heap code is hostile
to TPU; the idiomatic equivalent (SURVEY.md §7) is:

    concat all sources -> lexsort by (series, ts, seq) -> run-boundary mask
    -> keep the last (highest-seq) row of each (series, ts) run
    -> drop rows whose winner is a DELETE tombstone (op_type, read.rs:59-73)

The output is a permutation + keep-mask; downstream kernels consume the
sorted order directly (group ids become sorted, enabling
indices_are_sorted=True segment reductions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from greptimedb_tpu.utils.device_telemetry import kernel_name

OP_PUT = 0
OP_DELETE = 1


@functools.partial(jax.jit, static_argnames=("assume_unique_ts", "keep_tombstones"))
@kernel_name("sort_dedup")
def sort_dedup(
    series_ids: jax.Array,  # [N] int32 dense series/primary-key ids
    ts: jax.Array,  # [N] int64
    seq: jax.Array,  # [N] int64 write sequence (monotone per region)
    op_type: jax.Array,  # [N] int8 OP_PUT/OP_DELETE
    mask: jax.Array,  # [N] bool validity
    assume_unique_ts: bool = False,
    keep_tombstones: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (order, keep): `order` sorts rows by (series, ts); `keep` is a
    mask in sorted order marking last-write-wins survivors.

    With `assume_unique_ts` (append-mode regions, reference
    scan_region.rs:204-212 UnorderedScan), the dedup mask collapses to the
    validity mask and only the sort remains.
    """
    n = series_ids.shape[0]
    big = jnp.iinfo(series_ids.dtype).max
    # push invalid rows to the end so the valid prefix stays dense
    s = jnp.where(mask, series_ids, big)
    # lexsort: last key is the primary key
    order = jnp.lexsort((seq, ts, s))
    s_sorted = s[order]
    if assume_unique_ts:
        keep = s_sorted != big
        return order, keep
    t_sorted = ts[order]
    op_sorted = op_type[order]
    # run boundary: row i is the last of its (series, ts) run
    nxt_s = jnp.concatenate([s_sorted[1:], jnp.full((1,), big, s_sorted.dtype)])
    nxt_t = jnp.concatenate([t_sorted[1:], jnp.full((1,), jnp.iinfo(jnp.int64).min, t_sorted.dtype)])
    is_last = (s_sorted != nxt_s) | (t_sorted != nxt_t)
    keep = is_last & (s_sorted != big)
    if not keep_tombstones:
        # partial (windowed) compactions must retain winning tombstones —
        # an older shadowed PUT may live in a file outside the merge group
        keep = keep & (op_sorted != OP_DELETE)
    return order, keep


def apply_dedup(columns: dict, order: jax.Array, keep: jax.Array) -> tuple[dict, jax.Array]:
    """Gather columns into sorted order; returns (sorted columns, keep mask)."""
    return {k: v[order] for k, v in columns.items()}, keep

"""Sparse sort-compact aggregation plane — the shared core every
execution flavor routes through past its dense cardinality envelope.

The dense paths hold [G, F] accumulator planes indexed by the full group
key PRODUCT — which caps cardinality everywhere it is used: the fused
Pallas kernel refuses >4096 segments, the partial cache falls back past
64k groups, and the mesh flavors require the dense plane to fit the
device. The defining time-series workload (millions of small
series, the reference's metric-engine scenario) blows every one of
those budgets while OBSERVING only a bounded number of groups per scan:
U <= N rows, regardless of how large the key product is.

This module compacts the observed groups instead of allocating the
product:

    gid   = combined int64 group id per row (masked rows -> sentinel)
    order = argsort(gid)              # stable; XLA-native, shapes static
    new   = boundaries of equal-gid runs in sorted order
    cid   = cumsum(new) - 1           # dense rank in [0, U)
    uniq  = gid at each boundary      # rank -> global id decode table

and segment-reduces over the compacted ranks with a STATIC cap (slot
budget); only the group count U is dynamic, returned as a scalar. The
tail decodes ranks back to key values exactly like the cross-region
fragment combine does — value-keyed, never product-indexed.

Two device programs consume the compaction:

* `sparse_segment_agg` — the classic XLA path: one masked `segment_agg`
  over the sorted rows (`indices_are_sorted=True`).
* `fused_sparse_segment_agg` — the tiled fused-kernel path. After
  sort-compaction the ids are non-decreasing and rise by AT MOST 1 per
  sorted row, so any R consecutive sorted rows span fewer than R
  distinct ranks. A fori_loop walks R-row windows, rebases each window
  to its first rank (`local = ids - ids[0]`, always < R), runs the
  4096-segment Pallas kernel on the window, and accumulates the window
  planes into the global [cap, ...] planes at the base offset — O(N)
  total work, one compile, arbitrary cap. The 4096-segment envelope
  becomes a TILE SIZE instead of a ceiling.

Cross-shard / cross-part partials combine in GID space
(`combine_sparse_gid_partials`): global ids are shard-invariant, so a
numpy merge over the union of observed ids replaces the collective
psum the dense mesh path uses (per-shard compact slots don't line up).

Reference analog: DataFusion's row-hash GroupedHashAggregateStream for
the high-cardinality case (BASELINE config #5: 1M tag combos); here the
hash table is a sort + run-length pass that XLA vectorizes end to end.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.ops.segment import segment_agg
from greptimedb_tpu.utils.device_telemetry import kernel_name

#: sorts after every real combined group id (key products are guarded
#: upstream to stay below it)
GID_SENTINEL = 1 << 62

#: fused tile: R sorted rows span <= R ranks, and the window kernel
#: needs R locals + 1 dead slot inside the 4096-segment envelope
FUSED_TILE = 4088


@dataclasses.dataclass(frozen=True)
class SparseGroupSpec:
    """Static shape contract of one sparse aggregation: the compact slot
    budget (`cap`), the dense key product it replaced (`num_groups`),
    and the per-key domain sizes the tail uses to decode global ids
    back into key values (mixed-radix, row-major — the same strides the
    dense paths index with)."""

    cap: int
    num_groups: int
    sizes: tuple = ()

    @classmethod
    def plan(cls, num_groups: int, n_pad: int,
             sizes: tuple = ()) -> "SparseGroupSpec":
        """Slot budget for a scan of `n_pad` padded rows: distinct
        observed groups can never exceed the row count, so the cap is
        the row count clamped by the configured ceiling (the guard
        against a query observing more groups than the device planes
        can hold — overflow raises upstream, never truncates)."""
        from greptimedb_tpu import config

        return cls(cap=min(n_pad, config.sparse_groups_max()),
                   num_groups=num_groups, sizes=tuple(sizes))

    def decode(self, gids: np.ndarray, key_idx: int) -> np.ndarray:
        """Key-component index of each global id (host-side tail)."""
        strides = [1] * len(self.sizes)
        for i in range(len(self.sizes) - 2, -1, -1):
            strides[i] = strides[i + 1] * self.sizes[i + 1]
        return (gids // strides[key_idx]) % self.sizes[key_idx]


@kernel_name("sort_compact")
def sort_compact(gid: jax.Array, mask: jax.Array, cap: int):
    """Sort-compact observed group ids to dense ranks.

    Returns (order, ids, valid_s, uniq, n_groups): the stable sort
    permutation, per-SORTED-row compact ids (invalid rows -> `cap`, the
    dead segment), the sorted-row validity, the rank -> global-id
    decode table ([cap] int64, GID_SENTINEL in empty slots, ascending),
    and the dynamic observed-group count. Ranks past `cap` clip into
    the last slot so shapes stay static; callers detect overflow via
    n_groups > cap and raise — a clipped result is never served.
    """
    gid = jnp.where(mask, gid, jnp.int64(GID_SENTINEL))
    order = jnp.argsort(gid)
    sg = gid[order]
    valid_s = sg != GID_SENTINEL
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int64), sg[:-1]])
    new = valid_s & (sg != prev)
    cid = jnp.cumsum(new.astype(jnp.int32)) - 1  # compact id per sorted row
    ids = jnp.where(valid_s, jnp.clip(cid, 0, cap - 1), jnp.int32(cap))
    n_groups = new.sum()
    # observed global id per compact slot (ascending; overflow slots drop)
    uniq = jnp.full((cap,), GID_SENTINEL, dtype=jnp.int64).at[
        jnp.where(new & (cid < cap), cid, cap)
    ].set(sg, mode="drop")
    return order, ids, valid_s, uniq, n_groups


@functools.partial(jax.jit, static_argnames=("cap", "ops"))
@kernel_name("sparse_segment_agg")
def sparse_segment_agg(
    values: jax.Array,  # [N] or [N, F] value planes
    gid: jax.Array,  # [N] int64 combined group ids
    mask: jax.Array,  # [N] bool row validity
    cap: int,
    ops: tuple = ("sum", "count"),
    ts: Optional[jax.Array] = None,
):
    """Masked segment reduction over sort-compacted ranks: the classic
    sparse path, `segment_agg` semantics exactly (NaN = NULL, first/
    last tie-break by sorted position — identical to the whole-scan
    oracle because the sort is stable). Returns (part, uniq, n_groups)
    with part planes [cap, ...]."""
    order, ids, valid_s, uniq, n_groups = sort_compact(gid, mask, cap)
    part = segment_agg(values[order], ids, valid_s, cap, ops=ops,
                       ts=None if ts is None else ts[order],
                       indices_are_sorted=True)
    return part, uniq, n_groups


@kernel_name("fused_sparse_segment_agg")
def fused_sparse_segment_agg(
    vals: jax.Array,  # [N, F] SORTED raw field values (NaN = NULL)
    ids: jax.Array,  # [N] int32 compact ids from sort_compact (dead -> cap)
    cap: int,
    want_min: bool = False,
    want_max: bool = False,
    want_sumsq: bool = False,
    tile: int = FUSED_TILE,
    block_rows: int = 512,
) -> dict:
    """Tiled fused-kernel reduction over sort-compacted ranks.

    `ids` is non-decreasing with per-row increments of at most 1 (a
    cumsum of booleans in sorted order), so every `tile`-row window
    spans fewer than `tile` distinct ranks: rebased to the window's
    first rank, the window fits the Pallas kernel's 4096-segment
    envelope regardless of `cap`. The fori_loop accumulates window
    planes into [cap + tile, ...] global planes at the window's base
    offset (the overhang absorbs the last window's reach); all-dead
    windows rebase to cap-1 and land every row in the dropped dead
    slot. One trace, O(N) kernel work, arbitrary cap.

    Same contract as pallas_fused_segment_agg: values must be proven
    finite by the caller, NaN is NULL, empty groups come back as 0
    counts and +/-inf extremes (callers NaN-fill like the packers do).
    """
    from greptimedb_tpu.ops import pallas_segment as ps

    n, nf = vals.shape
    r = tile
    npad = max(-(-max(n, 1) // r) * r, r)
    vals_p = jnp.pad(vals, ((0, npad - n), (0, 0)))
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, npad - n),
                    constant_values=cap)
    dt = vals.dtype
    ext = cap + r
    acc = {
        "sum": jnp.zeros((ext, nf), dt),
        "count": jnp.zeros((ext, nf), dt),
        "rows": jnp.zeros((ext,), dt),
    }
    if want_min:
        acc["min"] = jnp.full((ext, nf), jnp.inf, dt)
    if want_max:
        acc["max"] = jnp.full((ext, nf), -jnp.inf, dt)
    if want_sumsq:
        acc["sumsq"] = jnp.zeros((ext, nf), dt)

    def body(c, acc):
        start = c * r
        ids_c = jax.lax.dynamic_slice(ids_p, (start,), (r,))
        vals_c = jax.lax.dynamic_slice(vals_p, (start, 0), (r, nf))
        # first sorted row holds the window minimum; an all-dead window
        # rebases to cap-1 and every row lands in the dropped slot
        base = jnp.clip(ids_c[0], 0, cap - 1)
        local = jnp.where(ids_c <= jnp.int32(cap - 1),
                          ids_c - base, jnp.int32(r))
        out = ps.pallas_fused_segment_agg(
            vals_c, local, r + 1, want_min=want_min, want_max=want_max,
            want_sumsq=want_sumsq, block_rows=block_rows)

        def fold(name, combine):
            plane = out[name][:r].astype(dt)
            g = acc[name]
            off = (base,) + (jnp.int32(0),) * (g.ndim - 1)
            cur = jax.lax.dynamic_slice(
                g, off, (r,) + g.shape[1:])
            return jax.lax.dynamic_update_slice(g, combine(cur, plane),
                                                off)

        nxt = {
            "sum": fold("sum", jnp.add),
            "count": fold("count", jnp.add),
            "rows": fold("rows", jnp.add),
        }
        if want_min:
            nxt["min"] = fold("min", jnp.minimum)
        if want_max:
            nxt["max"] = fold("max", jnp.maximum)
        if want_sumsq:
            nxt["sumsq"] = fold("sumsq", jnp.add)
        return nxt

    acc = jax.lax.fori_loop(0, npad // r, body, acc)
    return {k: v[:cap] for k, v in acc.items()}


def combine_sparse_gid_partials(parts: list) -> tuple:
    """Merge per-shard (or per-part) sparse partials in GID space.

    Each partial is {"gids": int64 [u] ascending-unique observed ids,
    "planes": {op: [u] or [u, F] host arrays}}. Compact ranks differ
    per shard, but the global ids they decode to are shard-invariant —
    so the exact combine is a union + indexed fold, mirroring
    `_combine_partials` semantics op by op: additive planes add
    (counts/rows in int64), min/max fold NaN-ignoring (NaN marks an
    empty group, `_unpack_acc`'s convention), first/last pick by
    companion ts with the PARTIAL ORDER breaking exact-ts ties (first:
    earliest partial wins; last: latest) — the same left-fold the
    dense block chain applies. Returns (gids [U] ascending, planes).
    """
    parts = [p for p in parts if len(p["gids"])]
    if not parts:
        return np.zeros((0,), np.int64), {}
    uniq = np.unique(np.concatenate([p["gids"] for p in parts]))
    n = len(uniq)

    def shaped(plane):
        return (n,) + np.asarray(plane).shape[1:]

    out: dict = {}
    p0 = parts[0]["planes"]
    for op, plane in p0.items():
        sh = shaped(plane)
        if op in ("count", "rows"):
            out[op] = np.zeros(sh, np.int64)
        elif op in ("sum", "sumsq"):
            out[op] = np.zeros(sh, np.asarray(plane).dtype)
        elif op in ("min", "max", "first", "last"):
            out[op] = np.full(sh, np.nan,
                              np.asarray(plane).dtype)
        elif op == "last_ts":
            out[op] = np.full(sh, np.iinfo(np.int64).min, np.int64)
        elif op == "first_ts":
            out[op] = np.full(sh, np.iinfo(np.int64).max, np.int64)
        else:
            raise ValueError(f"cannot combine sparse partial op {op}")
    for p in parts:
        idx = np.searchsorted(uniq, p["gids"])
        pl = p["planes"]
        for op in out:
            if op in ("first", "last", "first_ts", "last_ts"):
                continue  # pairs, below
            v = np.asarray(pl[op])
            if op in ("count", "rows"):
                out[op][idx] = out[op][idx] + v.astype(np.int64)
            elif op in ("sum", "sumsq"):
                out[op][idx] = out[op][idx] + v
            elif op == "min":
                out[op][idx] = np.fmin(out[op][idx], v)
            else:  # max
                out[op][idx] = np.fmax(out[op][idx], v)
        if "last" in out:
            ts, cur = np.asarray(pl["last_ts"]), out["last_ts"][idx]
            newer = ts > cur  # strict: exact-ts tie keeps earlier partial
            sel = newer[:, None] if out["last"].ndim == 2 else newer
            out["last"][idx] = np.where(sel, np.asarray(pl["last"]),
                                        out["last"][idx])
            out["last_ts"][idx] = np.where(newer, ts, cur)
        if "first" in out:
            ts, cur = np.asarray(pl["first_ts"]), out["first_ts"][idx]
            older = ts < cur
            sel = older[:, None] if out["first"].ndim == 2 else older
            out["first"][idx] = np.where(sel, np.asarray(pl["first"]),
                                         out["first"][idx])
            out["first_ts"][idx] = np.where(older, ts, cur)
    return uniq, out


def compaction_ratio(n_groups: int, n_rows: int) -> float:
    """Observed groups per scanned row — the gauge the sparse paths
    publish (1.0 = no compaction: every row its own group)."""
    return float(n_groups) / float(max(n_rows, 1))

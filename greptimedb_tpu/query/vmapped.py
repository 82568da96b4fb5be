"""vmap'd multi-query execution: a batch of parameter-sibling queries
as ONE device program.

The cross-query batcher (concurrency/batcher.py) collects SELECTs that
share a plan shape but differ in parameter literals — which host, which
datacenter, which time window. The previous stacked path rewrote the
group into one IN-list query and demultiplexed the combined result;
that only covers a single tag-equality selector and forces every member
onto the same time window. Here the members' parameters become a
STACKED AXIS instead: the scan, group ids, and value planes are built
once (they are member-invariant), each member contributes only its
per-row predicate mask, and `jax.vmap` maps the masked segment
reduction over the member axis — one dispatch computes an [M, G, F]
accumulator whose member slices are separated by construction. No
rewrite, no demux.

Bit-for-bit parity with serial execution is by masking identity, not by
approximation: the kernel scans the region's full row set and routes
every row a member's WHERE rejects into the dead segment — exactly what
the serial kernels do with their own masks — so a member's per-segment
fold visits precisely the rows its serial run would, in the same order.
Two structural conditions keep the fold association identical too, and
`run_vmapped` refuses (raises `VmapIneligible`, the batcher falls back
to the stacked/serial paths) when they don't hold:

- every scan part maps to ONE device block (so a serial scan of any
  sub-window, which decodes a row-subset of each part, splits partials
  at the same part seams — inserting identity elements into a left fold
  preserves every partial sum exactly);
- the member's whole predicate decomposes into shared conjuncts plus
  `column <op> literal` parameter conjuncts whose literals the kernel
  can take from a stacked array: bound through the SAME `bind_expr` and
  split by the SAME `split_operands` (query/expr.py) the serial path
  uses — every member's parameter conjuncts must split to one shape
  with no literal left in it — so literal coercion cannot drift.

Window-union batching falls out for free: members with different time
windows share the one full scan and differ only in their ts-comparison
parameters; multi-tag selectors are just several tag parameters.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.query import logical as lp
from greptimedb_tpu.query import physical as ph
from greptimedb_tpu.query.tier import ACTIVE_TIER, TierCtx
from greptimedb_tpu.query.expr import (
    BindContext,
    bind_expr,
    collect_columns,
    extract_ts_bounds,
    split_conjuncts,
    split_operands,
)
from greptimedb_tpu.ops.segment import segment_agg
from greptimedb_tpu.sql import ast
from greptimedb_tpu.utils.device_telemetry import kernel_name


class VmapIneligible(Exception):
    """This batch group cannot ride the vmapped kernel with provable
    serial parity — the batcher falls back to stacked/serial paths."""


#: member-axis padding buckets: compile one executable per (shape,
#: width bucket) instead of one per batch width
_WIDTH_BUCKETS = (2, 4, 8, 16, 32, 64, 128)


def _pad_width(m: int) -> int:
    for b in _WIDTH_BUCKETS:
        if m <= b:
            return b
    return m


def _rebuild_conjunction(conjuncts: list) -> Optional[ast.Expr]:
    if not conjuncts:
        return None
    e = conjuncts[0]
    for c in conjuncts[1:]:
        e = ast.BinaryOp("and", e, c)
    return e


def _member_mask(cols, base_mask, shared_where, shared_args, member_where,
                 pvals, tag_names, schema):
    """One member's row mask: the shared conjuncts with their operands,
    then the parameter conjuncts' one shape with this member's slice of
    the stacked operands (shared by the single-region and region-
    partial kernels)."""
    mask = ph._where_mask(base_mask, shared_where, shared_args, cols,
                          tag_names, schema)
    return ph._where_mask(mask, member_where, pvals, cols, tag_names, schema)


def _member_operands(specs, member_values, bctx, width: int) -> tuple:
    """(shape, stacked operands) of the members' parameter conjuncts
    `col <op> value`, `specs` giving (col, op) per conjunct: each
    member's conjunction is bound and split as a serial WHERE would be,
    all must come to ONE literal-free shape, and operand k of every
    member stacks into one [width, ...] array (the last member repeated
    up to the padded batch width)."""
    shape, rows = None, []
    for values in member_values:
        conj = _rebuild_conjunction([
            ast.BinaryOp(op, ast.Column(col), ast.Literal(v))
            for (col, op), v in zip(specs, values)])
        s, operands, static_literal = split_operands(
            bind_expr(conj, bctx), bctx.schema)
        if static_literal:
            raise VmapIneligible(f"unbindable parameter in {specs}")
        if rows and (s != shape or [o.dtype for o in operands]
                     != [o.dtype for o in rows[0]]):
            raise VmapIneligible("parameter spec drift across members")
        shape = s
        rows.append(operands)
    rows += [rows[-1]] * (width - len(rows))
    return shape, tuple(jnp.asarray(np.stack(col)) for col in zip(*rows))


@functools.partial(
    jax.jit,
    static_argnames=("shared_where", "member_where", "keys", "agg_args",
                     "ops", "num_segments", "ts_name", "need_ts",
                     "tag_names", "schema", "acc_dtype", "float_ops",
                     "pack_dtype"),
)
@kernel_name("vmapped_agg_scan")
def _vmapped_agg_scan(
    blocks: tuple,  # per-block col dicts (member-invariant)
    n_valids: jax.Array,
    dedup_masks,
    params: tuple,  # the member shape's operands, stacked [M, ...]
    shared_args: tuple,  # the shared shape's operands, then bucket bases
    *,
    shared_where, member_where, keys, agg_args, ops, num_segments,
    ts_name, need_ts, tag_names, schema, acc_dtype, float_ops,
    pack_dtype,
):
    """One dispatch for M parameter-sibling queries. Everything that
    does not depend on the member parameters (group ids, value planes,
    the shared-predicate mask) is traced once and stays unbatched;
    only the per-member mask and the segment reductions carry the
    vmapped leading axis. first/last ride as ts-paired planes: the
    companion *_ts planes drive the cross-block combine on device and
    never leave the kernel (the value planes are what the host reads)."""

    def member(pvals):
        acc = None
        for i, cols in enumerate(blocks):
            some = next(iter(cols.values()))
            mask = jnp.arange(some.shape[0]) < n_valids[i]
            if dedup_masks is not None:
                mask = mask & dedup_masks[i]
            mask = _member_mask(cols, mask, shared_where, shared_args,
                                member_where, pvals, tag_names, schema)
            gid = ph._group_ids(cols, keys, mask.shape[0], shared_args)
            if agg_args:
                values = ph._value_planes(agg_args, cols, tag_names,
                                          schema, mask.shape, acc_dtype)
            else:
                values = jnp.zeros((mask.shape[0], 1), dtype=acc_dtype)
            part = segment_agg(values, gid, mask, num_segments, ops=ops,
                               ts=cols[ts_name] if need_ts else None)
            acc = ph._combine_partials(acc, part)
        parts = []
        for k in float_ops:
            v = acc[k]
            if v.ndim == 1:
                v = v[:, None]
            parts.append(v.astype(pack_dtype))
        return jnp.concatenate(parts, axis=1)

    return jax.vmap(member)(params)


@functools.partial(
    jax.jit,
    static_argnames=("shared_where", "member_where", "keys", "agg_args",
                     "ops", "cap", "ts_name", "need_ts", "tag_names",
                     "schema", "acc_dtype", "float_ops", "pack_dtype"),
)
@kernel_name("vmapped_sparse_agg_scan")
def _vmapped_sparse_agg_scan(
    cols: dict,  # whole-scan padded col arrays (member-invariant)
    base_mask: jax.Array,  # [N] padding & dedup survivors
    params: tuple,  # the member shape's operands, stacked [M, ...]
    shared_args: tuple,  # the shared shape's operands, then bucket bases
    *,
    shared_where, member_where, keys, agg_args, ops, cap, ts_name,
    need_ts, tag_names, schema, acc_dtype, float_ops, pack_dtype,
):
    """Sparse (sort-compact) twin of _vmapped_agg_scan: ONE shared
    compaction over the member-invariant base mask (padding, dedup,
    shared conjuncts — every member's rows are a subset), then each
    member's parameter mask rides the vmapped axis as the segment_agg
    validity over the already-sorted rows. The compact ranks cover the
    UNION of observed groups; a member's unobserved ranks come back
    with rows == 0 and the host drops them, so each member sees exactly
    the groups its serial sparse run would. Parity is the masking
    identity again: the shared sort is stable, so a member's surviving
    rows keep their serial fold order, and masked rows contribute fold
    identities."""
    from greptimedb_tpu.ops import sparse_segment as sparse_ops

    mask0 = ph._where_mask(base_mask, shared_where, shared_args, cols,
                           tag_names, schema)
    gid = ph._sparse_gid(cols, keys, shared_args)
    order, ids, valid_s, uniq, n_groups = sparse_ops.sort_compact(
        gid, mask0, cap)
    if agg_args:
        values = ph._value_planes(agg_args, cols, tag_names, schema,
                                  mask0.shape, acc_dtype)
    else:
        values = jnp.zeros((mask0.shape[0], 1), dtype=acc_dtype)
    values_s = values[order]
    ts_s = cols[ts_name][order] if need_ts else None
    param_cols_s = {name: cols[name][order]
                    for name in sorted(collect_columns(member_where, set()))}

    def member(pvals):
        mask = _member_mask(param_cols_s, valid_s, None, (), member_where,
                            pvals, tag_names, schema)
        part = segment_agg(values_s, ids, mask, cap, ops=ops, ts=ts_s,
                           indices_are_sorted=True)
        parts = []
        for k in float_ops:
            v = part[k]
            if v.ndim == 1:
                v = v[:, None]
            parts.append(v.astype(pack_dtype))
        return jnp.concatenate(parts, axis=1)

    return jax.vmap(member)(params), uniq, n_groups


def run_vmapped(executor, sel: ast.Select, info, pspecs,
                member_values: list) -> list:
    """Execute `sel`'s shape once for every member value tuple; returns
    QueryResults aligned with `member_values`. Raises VmapIneligible
    when the shape/scan cannot guarantee bit-for-bit serial parity."""
    from greptimedb_tpu import config
    from greptimedb_tpu.query.planner import plan_select

    plan = plan_select(sel, info)
    node = plan
    if not isinstance(node, lp.Project):
        raise VmapIneligible("plan root is not a projection")
    project = node
    node = node.input
    if not isinstance(node, lp.Aggregate):
        raise VmapIneligible("not an aggregate shape")
    agg = node
    node = node.input
    if not isinstance(node, lp.Filter):
        raise VmapIneligible("no predicate to parameterize")
    template_where = node.predicate
    node = node.input
    if not isinstance(node, lp.Scan):
        raise VmapIneligible("unexpected scan node")
    scan_node = node
    table = scan_node.table
    schema = table.schema
    ts_name = schema.time_index.name

    if any(ph._needs_host_agg(spec, schema) for spec in agg.aggs):
        raise VmapIneligible("host-side aggregate in batch shape")
    if len(table.region_ids) != 1:
        # cluster frontend: the members execute as ONE vmapped_agg
        # fragment per region — per-member [G, F] partials come back
        # and combine like the serial pushdown's Final step (no raw
        # rows, no IN-list/serial fallback)
        if hasattr(executor.engine, "execute_fragment"):
            return _run_vmapped_fragments(
                executor, sel, info, pspecs, member_values, project, agg,
                template_where)
        raise VmapIneligible("multi-region scans gather via fragments")
    if not hasattr(executor.engine, "scan"):
        raise VmapIneligible("engine has no materialized scan")

    # split the predicate: parameter conjuncts out, shared rest stays.
    # plan_select passes sel.where through by reference, so the
    # batcher-identified conjunct objects are found by identity.
    param_ids = {id(p.conjunct) for p in pspecs}
    shared = [c for c in split_conjuncts(template_where)
              if id(c) not in param_ids]
    if len(shared) + len(pspecs) != len(split_conjuncts(template_where)):
        raise VmapIneligible("parameter conjuncts lost in planning")
    shared_where_ast = _rebuild_conjunction(shared)

    # union time range (drives the bucket-key domain and the scan's
    # coarse pruning; member masks carve exact slices on device)
    union_range = _union_member_range(template_where, pspecs,
                                      member_values, ts_name,
                                      schema.time_index.dtype)

    # one scan covering the UNION of the member windows (tag predicates
    # stay None: every member's rows must be present); member masks
    # carve their slices on device. Region.scan's own covering-range
    # widening keeps the parity cases aligned: if any member's serial
    # scan would widen to the full region, the union (a superset range)
    # widens too, so the one-block-per-part gate below always runs over
    # a superset of every member's decoded parts.
    scan = executor.engine.scan(table.region_ids[0],
                                ph._closed_range(union_range),
                                scan_node.columns, None,
                                full_key=not table.append_mode)
    if scan is None or scan.num_rows == 0:
        raise VmapIneligible("empty scan: serial path settles it")
    if table.append_mode and \
            scan.num_rows >= config.stream_threshold_rows():
        raise VmapIneligible("serial path would stream this scan")
    if executor.mesh is not None and \
            scan.num_rows >= config.mesh_min_rows():
        raise VmapIneligible("serial path would shard over the mesh")

    # parity gate: one device block per part seam (see module docstring)
    block_plan = ph._block_plan(scan)
    seen: set = set()
    for entry in block_plan:
        seam = (entry.pkey, entry.part_start)
        if seam in seen:
            raise VmapIneligible("a scan part spans multiple blocks")
        seen.add(seam)

    bctx = BindContext(schema, scan.tag_dicts)
    bound_shared = bind_expr(shared_where_ast, bctx) \
        if shared_where_ast is not None else None

    # the members' parameters: one shape, operands stacked [M, ...]
    m = len(member_values)
    member_where, params = _member_operands(
        [(p.col, p.op) for p in pspecs], member_values, bctx, _pad_width(m))

    # group keys over the union scan; decode is value-based, so a base
    # shift against a member's narrower serial window is invisible
    scan_node_u = lp.Scan(table, scan_node.columns, union_range)
    keys: list = []
    decoders: list = []
    extra_cols: dict[str, np.ndarray] = {}
    for i, (name, kexpr) in enumerate(agg.keys):
        dk, decode = executor._plan_key(i, kexpr, bctx, scan, scan_node_u,
                                        extra_cols)
        keys.append(dk)
        decoders.append(decode)
    num_groups = 1
    for k in keys:
        num_groups *= k.size
    if not keys:
        raise VmapIneligible("global aggregate has no group axis")
    if num_groups >= ph._GID_SENTINEL:
        raise VmapIneligible(f"group domain {num_groups} overflows gid space")
    # past the dense envelope the members ride the sparse (sort-compact)
    # twin instead of falling back to serial — the batch's accumulator
    # is [M, cap, F] over OBSERVED groups, not the key-domain product
    sparse = num_groups > config.dense_groups_max() or (
        config.sparse_groups_min() > 0
        and num_groups >= config.sparse_groups_min())
    cap = min(ph.block_size_for(scan.num_rows), config.sparse_groups_max())
    # the stacked axis multiplies the accumulator: bound M*G by the
    # budget one serial query of the same flavor is allowed (dense key
    # domain, or sparse compact cap), so a wide batch over a near-max
    # group domain can't ask XLA for a multi-GB output
    budget = config.sparse_groups_max() if sparse \
        else config.dense_groups_max()
    if _pad_width(len(member_values)) * (cap if sparse else num_groups) \
            > budget:
        raise VmapIneligible("stacked accumulator exceeds group budget")

    # aggregate layout (mirrors _stream_agg_inner's dense packing)
    arg_exprs: list = []
    spec_slot: list = []
    for spec in agg.aggs:
        if spec.arg is None:
            spec_slot.append(None)
            continue
        b = bind_expr(spec.arg, bctx)
        if b not in arg_exprs:
            arg_exprs.append(b)
        spec_slot.append(arg_exprs.index(b))
    ops: set = {"rows"}
    for spec in agg.aggs:
        ops.update(ph._PRIMITIVES[spec.func])
    # first/last batch too (ROADMAP item 1 rung): the kernel pairs each
    # group's value with its timestamp, so lastpoint-class dashboards
    # ride the stacked axis like every other aggregate
    need_ts = bool({"first", "last"} & ops)

    acc_dtype = jnp.dtype(config.compute_dtype())
    nf = max(len(arg_exprs), 1)
    float_ops_l, widths = [], {}
    for op in sorted(ops):
        if op.endswith("_ts"):
            continue  # companion planes stay inside the kernel
        float_ops_l.append(op)
        widths[op] = 1 if op == "rows" else nf
    float_ops = tuple(float_ops_l)
    pack_dtype = jnp.dtype(jnp.float64) if num_groups <= 4096 else acc_dtype
    if not jnp.issubdtype(pack_dtype, jnp.floating):
        pack_dtype = jnp.dtype(jnp.float64)
    if "sumsq" in float_ops:
        pack_dtype = jnp.dtype(jnp.float64)

    dedup_mask = executor._maybe_dedup(scan, table, bctx)
    tag_names = frozenset(bctx.tag_names)
    float_fields = {c.name for c in schema.field_columns
                    if c.dtype.is_float}
    device_col_names = executor._device_columns(
        scan, bound_shared, keys, tuple(arg_exprs), ts_name, extra_cols)
    for name in sorted(collect_columns(member_where, set())):
        if name not in device_col_names:
            device_col_names.append(name)
    shared_where, shared_args, _ = ph._operands(bound_shared, keys, schema)

    tier = executor.tier_for(agg, scan.num_rows)
    executor.last_tier = tier

    def fetch_block(entry, prefetch_only=False):
        out = {}
        for name in device_col_names:
            out[name] = executor._device_block(
                scan, name, entry, extra_cols,
                acc_dtype if name in float_fields else None,
                prefetch_only=prefetch_only)
        return out

    if sparse:
        return _run_vmapped_sparse(
            executor, scan, agg, project, table, keys, decoders, spec_slot,
            extra_cols, bound_shared, bctx,
            (shared_where, shared_args, member_where), params, m,
            device_col_names, float_fields, acc_dtype, dedup_mask,
            tag_names, schema, ts_name, need_ts, arg_exprs, ops, cap,
            float_ops, widths, pack_dtype, tier, num_groups)

    with TierCtx(tier):
        blocks, n_valids, dmasks = executor._gather_blocks(
            scan, block_plan, fetch_block, dedup_mask)
        packed = _vmapped_agg_scan(
            tuple(blocks), jnp.asarray(np.asarray(n_valids)),
            tuple(dmasks) if dmasks is not None else None,
            params, shared_args,
            shared_where=shared_where, member_where=member_where,
            keys=tuple(keys), agg_args=tuple(arg_exprs),
            ops=tuple(sorted(ops)), num_segments=num_groups,
            ts_name=ts_name, need_ts=need_ts,
            tag_names=tag_names, schema=schema, acc_dtype=acc_dtype,
            float_ops=float_ops, pack_dtype=pack_dtype)
        host = ph._readback(packed)

    results = []
    host_info = (scan, extra_cols, bound_shared, bctx, num_groups)
    for i in range(m):
        acc: dict = {}
        off = 0
        for k in float_ops:
            w = widths[k]
            sl = host[i][:, off:off + w]
            off += w
            if k in ("count", "rows"):
                sl = sl.astype(np.int64)
            acc[k] = sl
        results.append(executor._agg_tail(
            acc, None, agg, keys, decoders, spec_slot, host_info,
            None, project, None, None, None, table))
    executor.last_path = "dense_vmapped"
    return results


def _run_vmapped_sparse(executor, scan, agg, project, table, keys, decoders,
                        spec_slot, extra_cols, bound_shared, bctx, shapes,
                        params, m, device_col_names, float_fields, acc_dtype,
                        dedup_mask, tag_names, schema, ts_name, need_ts,
                        arg_exprs, ops, cap, float_ops, widths, pack_dtype,
                        tier, num_groups) -> list:
    """Sparse execution tail of run_vmapped: whole-scan padded columns
    (sharing the serial sparse path's snapshot cache keys, so a batch
    after a serial high-card query reuses its uploads), ONE stacked
    sort-compact dispatch, then a per-member demux that keeps only the
    compact ranks the member actually observed (rows > 0) before the
    shared gid-decoding tail."""
    from greptimedb_tpu.ops import sparse_segment as sparse_ops
    from greptimedb_tpu.utils.metrics import (
        SPARSE_COMPACTION_RATIO,
        SPARSE_DISPATCHES,
    )

    n = scan.num_rows
    n_pad = ph.block_size_for(n)
    cols = {}
    for name in device_col_names:
        cast = acc_dtype if name in float_fields else None

        def build(name=name, cast=cast):
            src = extra_cols[name] if name in extra_cols \
                else scan.columns[name]
            arr = ph.pad_rows(src, n_pad)
            if cast is not None and arr.dtype != cast:
                arr = arr.astype(cast)
            return jnp.asarray(arr)

        if scan.region_id < 0 or name in extra_cols:
            cols[name] = build()
        else:
            key = ("snap", scan.region_id, ph._snap_version(scan),
                   ACTIVE_TIER.get(), scan.scan_fingerprint,
                   name, "whole", n_pad, str(cast))
            cols[name] = executor.cache.get(key, build)
    base = np.arange(n_pad) < n
    if dedup_mask is not None:
        base[:n] &= np.asarray(dedup_mask)[:n]

    shared_where, shared_args, member_where = shapes
    with TierCtx(tier):
        packed, uniq, n_obs = _vmapped_sparse_agg_scan(
            cols, jnp.asarray(base), params, shared_args,
            shared_where=shared_where, member_where=member_where,
            keys=tuple(keys), agg_args=tuple(arg_exprs),
            ops=tuple(sorted(ops)), cap=cap, ts_name=ts_name,
            need_ts=need_ts, tag_names=tag_names, schema=schema,
            acc_dtype=acc_dtype, float_ops=float_ops,
            pack_dtype=pack_dtype)
        host = ph._readback(packed)
        host_uniq = np.asarray(uniq)
    u = int(n_obs)
    if u > cap:
        # the UNION of member windows overflowed the sparse cap; each
        # member alone may still fit, so hand back to the serial paths
        raise VmapIneligible(
            f"batch observed {u} distinct groups over sparse cap {cap}")
    SPARSE_DISPATCHES.inc(path="vmapped")
    SPARSE_COMPACTION_RATIO.set(sparse_ops.compaction_ratio(u, n))

    results = []
    host_info = (scan, extra_cols, bound_shared, bctx, num_groups)
    gids_u = host_uniq[:u]
    for i in range(m):
        acc: dict = {}
        off = 0
        for k in float_ops:
            w = widths[k]
            sl = host[i][:u, off:off + w]
            off += w
            if k in ("count", "rows"):
                sl = sl.astype(np.int64)
            acc[k] = sl
        rows = acc["rows"][:, 0] if acc["rows"].ndim == 2 else acc["rows"]
        present = np.flatnonzero(rows > 0)
        acc = {k: v[present] for k, v in acc.items()}
        results.append(executor._agg_tail(
            acc, gids_u[present], agg, keys, decoders, spec_slot,
            host_info, None, project, None, None, None, table))
    executor.last_path = "sparse_vmapped"
    return results


def _union_member_range(template_where, pspecs, member_values, ts_name,
                        ts_dtype):
    """(lo, hi) covering every member's ts bounds, or None when any
    member is unbounded on either side. Scanning the union is the
    parity-preserving coarse prune: rows outside a member's own window
    are masked by its bound ts parameters on device."""
    lo = hi = None
    lo_open = hi_open = False
    for values in member_values:
        repl = {id(p.conjunct): ast.BinaryOp(
            p.op, ast.Column(p.col), ast.Literal(v))
            for p, v in zip(pspecs, values)}
        member_where = _replace_by_id(template_where, repl)
        r = extract_ts_bounds(member_where, ts_name, ts_dtype)
        mlo, mhi = r if r is not None else (None, None)
        if mlo is None:
            lo_open = True
        elif lo is None or mlo < lo:
            lo = mlo
        if mhi is None:
            hi_open = True
        elif hi is None or mhi > hi:
            hi = mhi
    if lo_open and hi_open:
        return None
    union_range = (None if lo_open else lo, None if hi_open else hi)
    return None if union_range == (None, None) else union_range


# ---- multi-region: vmapped partials over plan fragments ---------------------


@functools.partial(
    jax.jit,
    static_argnames=("shared_where", "member_where", "keys", "agg_args",
                     "ops", "num_segments", "ts_name", "need_ts",
                     "tag_names", "schema", "acc_dtype"),
)
def _vmapped_partial_scan(
    cols: dict,  # whole-scan padded column arrays (member-invariant)
    base_mask: jax.Array,
    params: tuple,
    shared_args: tuple,
    *,
    shared_where, member_where, keys, agg_args, ops, num_segments,
    ts_name, need_ts, tag_names, schema, acc_dtype,
):
    """Region-side member batch: ONE whole-scan segment reduction per
    member over the stacked axis. Deliberately not block-split: the
    serial cluster partial (`partial_region_agg`) reduces the region's
    filtered rows with a single segment_agg, and a masked whole-scan
    fold visits the same rows in the same order with identity elements
    interleaved — bit-for-bit the same per-group result."""

    def member(pvals):
        mask = _member_mask(cols, base_mask, shared_where, shared_args,
                            member_where, pvals, tag_names, schema)
        gid = ph._group_ids(cols, keys, mask.shape[0], shared_args)
        if agg_args:
            values = ph._value_planes(agg_args, cols, tag_names, schema,
                                      mask.shape, acc_dtype)
        else:
            values = jnp.zeros((mask.shape[0], 1), dtype=acc_dtype)
        return segment_agg(values, gid, mask, num_segments, ops=ops,
                           ts=cols[ts_name] if need_ts else None)

    return jax.vmap(member)(params)


def run_vmapped_region_partial(executor, region_id: int, vm: dict,
                               schema=None, *, where=None, ts_range=None,
                               append_mode=False, tz=None):
    """Execute a `vmapped_agg` fragment stage against ONE local region:
    all members' partial aggregates in a single stacked dispatch.
    Returns {"members": [per-member {"keys", "planes"} | None]} — the
    per-member twin of `partial_region_agg`'s output, combined by the
    frontend with the same `combine_partials` Final step — or
    {"vmap_ineligible": reason} when this region cannot serve the batch
    with provable serial parity (the frontend then falls back to
    serial/stacked member execution; typed, never an error)."""
    from greptimedb_tpu.query.expr import reset_session_tz, set_session_tz

    token = set_session_tz(tz)
    try:
        return _region_partial_inner(executor, region_id, vm, schema,
                                     append_mode, ts_range)
    except VmapIneligible as e:
        return {"vmap_ineligible": str(e)}
    finally:
        reset_session_tz(token)


def _region_partial_inner(executor, region_id, vm, schema, append_mode,
                          ts_range=None):
    from types import SimpleNamespace

    from greptimedb_tpu import config
    from greptimedb_tpu.ops.blocks import block_size_for, pad_rows

    eng = executor.engine
    probe = eng.region(region_id)
    schema = schema or probe.schema
    ts_name = schema.time_index.name
    keys_spec = list(vm["keys"])
    args = list(vm["args"])
    ops = tuple(sorted(vm["ops"]))
    pspecs = [tuple(p) for p in vm["params"]]
    values = vm["values"]
    m = len(values)
    need_ts = bool({"first", "last"} & set(ops))

    needed: set = {ts_name}
    collect_columns(vm.get("shared_where"), needed)
    for _, kexpr in keys_spec:
        collect_columns(kexpr, needed)
    for a in args:
        collect_columns(a, needed)
    for col, _op in pspecs:
        needed.add(col)
    proj = [c for c in schema.names if c in needed]
    # the fragment's ts_range is the UNION of member windows: index-
    # pruned like the serial per-member pushdown scan; rows outside a
    # member's own window are masked by its ts parameters below
    scan = eng.scan(region_id, ph._closed_range(ts_range), proj, None,
                    full_key=not append_mode)
    if scan is None or scan.num_rows == 0:
        return {"members": [None] * m}
    n = scan.num_rows
    bctx = BindContext(schema, scan.tag_dicts)
    shared_ast = vm.get("shared_where")
    bound_shared = bind_expr(shared_ast, bctx) \
        if shared_ast is not None else None

    # stacked parameters bound through the engine's own literal
    # coercion (identical to what each member's serial WHERE would
    # compare against on THIS region's dictionaries)
    mp = _pad_width(m)
    member_where, params = _member_operands(pspecs, values, bctx, mp)

    shim_node = SimpleNamespace(ts_range=None, columns=proj)
    keys: list = []
    decoders: list = []
    extra_cols: dict[str, np.ndarray] = {}
    for i, (name, kexpr) in enumerate(keys_spec):
        dk, decode = executor._plan_key(i, kexpr, bctx, scan, shim_node,
                                        extra_cols)
        keys.append(dk)
        decoders.append(decode)
    num_groups = 1
    for k in keys:
        num_groups *= k.size
    if num_groups > config.dense_groups_max() \
            or num_groups >= ph._GID_SENTINEL:
        raise VmapIneligible(f"group domain {num_groups} needs sparse path")
    if keys and mp * num_groups > config.dense_groups_max():
        raise VmapIneligible("stacked accumulator exceeds dense budget")

    bound_args = [bind_expr(a, bctx) for a in args]
    for b in bound_args:
        if ph._needs_host_agg(SimpleNamespace(func="sum", arg=b), schema):
            raise VmapIneligible("non-numeric aggregate argument")
    tshim = SimpleNamespace(schema=schema, append_mode=append_mode)
    dedup_mask = executor._maybe_dedup(scan, tshim, bctx)

    # the serial partial computes in float64 (partial_region_agg casts
    # eval_host planes to f64) — match it exactly, even on f32 backends
    acc_dtype = jnp.dtype(jnp.float64)
    tag_names = frozenset(bctx.tag_names)
    names = executor._device_columns(scan, bound_shared, keys,
                                     tuple(bound_args), ts_name,
                                     extra_cols)
    for pname in sorted(collect_columns(member_where, set())):
        if pname not in names:
            names.append(pname)
    n_pad = block_size_for(n)
    float_fields = {c.name for c in schema.field_columns
                    if c.dtype.is_float}
    dev_cols = {}
    for name in names:
        src = extra_cols[name] if name in extra_cols else scan.columns[name]
        arr = pad_rows(np.asarray(src), n_pad)
        if name in float_fields and arr.dtype != acc_dtype:
            arr = arr.astype(acc_dtype)
        dev_cols[name] = jnp.asarray(arr)
    base = np.arange(n_pad) < n
    if dedup_mask is not None:
        base[:n] &= np.asarray(dedup_mask)[:n]
    base = jnp.asarray(base)
    shared_where, shared_args, _ = ph._operands(bound_shared, keys, schema)
    out = _vmapped_partial_scan(
        dev_cols, base, params, shared_args,
        shared_where=shared_where, member_where=member_where,
        keys=tuple(keys), agg_args=tuple(bound_args), ops=ops,
        num_segments=num_groups, ts_name=ts_name, need_ts=need_ts,
        tag_names=tag_names, schema=schema, acc_dtype=acc_dtype)
    host = {op: np.asarray(v) for op, v in out.items()}

    strides = ph._strides([k.size for k in keys])
    members = []
    for i in range(m):
        rows = host["rows"][i].reshape(-1)
        if keys:
            present = np.flatnonzero(rows > 0)
            if present.size == 0:
                members.append(None)
                continue
            key_cols = []
            for j, decode in enumerate(decoders):
                idx = (present // strides[j]) % keys[j].size
                col, _dt = decode(idx)
                key_cols.append(np.asarray(col))
        else:
            if rows[0] <= 0:
                members.append(None)
                continue
            present = np.arange(1)
            key_cols = []
        planes = {}
        for op, plane in host.items():
            p = plane[i]
            planes[op] = p[present] if p.ndim >= 1 else p
        members.append({"keys": key_cols, "planes": planes})
    return {"members": members}


_JSON_LITERALS = (str, int, float, bool, type(None))


def _coerce_partial(part: dict) -> dict:
    """Normalize a per-member partial from either transport (in-process
    numpy or JSON lists over Flight) into combine_partials' shape."""
    keys = []
    for k in part["keys"]:
        if isinstance(k, np.ndarray):
            keys.append(k)
            continue
        arr = np.asarray(k, dtype=object)
        vals = arr.tolist()
        if len(vals) and all(
                isinstance(x, (int, np.integer)) and not isinstance(x, bool)
                for x in vals):
            arr = arr.astype(np.int64)  # bucket keys stay int64
        keys.append(arr)
    planes = {}
    for op, v in part["planes"].items():
        planes[op] = v if isinstance(v, np.ndarray) else np.asarray(v)
    return {"keys": keys, "planes": planes}


def _run_vmapped_fragments(executor, sel, info, pspecs, member_values,
                           project, agg, template_where) -> list:
    """Cluster-mode member batch: ship ONE `vmapped_agg` fragment per
    region, combine each member's per-region [G, F] partials with the
    SAME Final step the serial pushdown uses (`combine_partials`), and
    post-process per member. What crosses the wire is partial planes
    per member — today's fallback was IN-list/serial per member over
    the same regions."""
    from concurrent.futures import ThreadPoolExecutor

    from greptimedb_tpu.query.dist_agg import combine_partials
    from greptimedb_tpu.query.expr import current_session_tz
    from greptimedb_tpu.query.plan_ser import PlanFragment
    from greptimedb_tpu.utils import tracing
    from greptimedb_tpu.utils.metrics import FRAGMENT_PUSHDOWNS

    table = info
    for vals in member_values:
        for v in vals:
            if not isinstance(v, _JSON_LITERALS):
                raise VmapIneligible("non-literal member parameter")
    param_ids = {id(p.conjunct) for p in pspecs}
    shared = [c for c in split_conjuncts(template_where)
              if id(c) not in param_ids]
    if len(shared) + len(pspecs) != len(split_conjuncts(template_where)):
        raise VmapIneligible("parameter conjuncts lost in planning")
    shared_where_ast = _rebuild_conjunction(shared)

    arg_exprs: list = []
    spec_slot: list = []
    for spec in agg.aggs:
        if spec.arg is None:
            spec_slot.append(None)
            continue
        if spec.arg not in arg_exprs:
            arg_exprs.append(spec.arg)
        spec_slot.append(arg_exprs.index(spec.arg))
    ops: set = {"rows"}
    for spec in agg.aggs:
        ops.update(ph._PRIMITIVES[spec.func])

    schema = table.schema
    union_range = _union_member_range(
        template_where, pspecs, member_values,
        schema.time_index.name, schema.time_index.dtype)
    stage = {"op": "vmapped_agg",
             "keys": list(agg.keys),
             "args": arg_exprs,
             "ops": sorted(ops),
             "shared_where": shared_where_ast,
             "params": [(p.col, p.op) for p in pspecs],
             "values": [list(vals) for vals in member_values]}
    frag = PlanFragment(stages=[stage], ts_range=union_range,
                        append_mode=table.append_mode,
                        tz=current_session_tz())
    FRAGMENT_PUSHDOWNS.inc(mode="vmapped")
    rids = list(table.region_ids)
    m = len(member_values)
    with tracing.span("vmapped_fragments", regions=len(rids), members=m):
        from greptimedb_tpu.utils import deadline as dl

        one = dl.propagate(tracing.propagate(
            lambda rid: executor.engine.execute_fragment(rid, frag)))
        if len(rids) > 1:
            with ThreadPoolExecutor(
                    max_workers=min(8, len(rids))) as pool:
                resps = list(pool.map(one, rids))
        else:
            resps = [one(rids[0])]

    per_member: list = [[] for _ in range(m)]
    for resp in resps:
        if resp is None:
            continue  # empty region contributes nothing
        if "vmap_ineligible" in resp:
            raise VmapIneligible(str(resp["vmap_ineligible"]))
        members = resp.get("members")
        if members is None or len(members) != m:
            raise VmapIneligible("member count drift across regions")
        for i, part in enumerate(members):
            if part is not None:
                per_member[i].append(_coerce_partial(part))

    results = []
    sorted_ops = tuple(sorted(ops))
    for i in range(m):
        combined = combine_partials(per_member[i], len(agg.keys),
                                    sorted_ops)
        results.append(executor._finalize_combined_agg(
            combined, table, agg, None, project, None, None, None,
            spec_slot))
    executor.last_path = "vmapped_fragments"
    return results


def _replace_by_id(e, repl: dict):
    """Rebuild `e` with nodes replaced by identity (id(node) -> new)."""
    r = repl.get(id(e))
    if r is not None:
        return r
    if isinstance(e, (list, tuple)):
        return type(e)(_replace_by_id(x, repl) for x in e)
    if dataclasses.is_dataclass(e) and not isinstance(e, type) \
            and not isinstance(e, ast.Statement):
        changes = {}
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            if isinstance(v, (ast.Expr, list, tuple)) or (
                    dataclasses.is_dataclass(v)
                    and not isinstance(v, (type, ast.Statement))):
                nv = _replace_by_id(v, repl)
                if nv is not v:
                    changes[f.name] = nv
        return dataclasses.replace(e, **changes) if changes else e
    return e

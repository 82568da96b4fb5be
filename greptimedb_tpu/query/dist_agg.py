"""Distributed partial aggregation — the dist_plan / MergeScan analog.

The reference splits commutative aggregates into a Partial step executed
on each datanode's regions and a Final combine at the frontend
(query/src/dist_plan/analyzer.rs:35, merge_scan.rs:122). Here:

- `partial_region_agg` runs ON the node owning a region: scan, filter,
  evaluate group keys + aggregate args, and reduce to primitive planes
  (sum/count/min/max/first/last/sumsq/rows) with ONE fused device
  segment reduction. Group keys travel as decoded VALUES, so partials
  from different regions (with different tag dictionaries) combine by
  value at the frontend.
- `combine_partials` merges per-region results: additive planes add,
  min/max fold, first/last resolve by their companion timestamps.

The fragment itself crosses the wire as JSON (plan_ser.PlanFragment —
the substrait analog) via the Flight `region_frag` ticket;
`execute_region_fragment` is the region-side interpreter dispatching to
the partial-agg / top-k / filtered-rows pipelines by terminal stage.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Optional

import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.ops.segment import segment_agg
from greptimedb_tpu.query.expr import BindContext, bind_expr, eval_host
from greptimedb_tpu.query.plan_ser import PlanFragment


#: marker for a cached empty region contribution (None itself means
#: "cache miss" to the cache API)
_FRAG_NONE = {"__frag_none__": True}


def execute_region_fragment(executor, region_id: int, frag: PlanFragment,
                            schema=None) -> Optional[dict]:
    """Interpret a PlanFragment over one region's rows. Returns
    {"keys": ..., "planes": ...} for a partial_agg terminal, or
    {"cols": {...}} of candidate/filtered rows otherwise; None when the
    region contributes nothing.

    Partial-agg terminals memoize their plane in the partial-aggregate
    cache keyed by the region's (incarnation, data_version) + the
    fragment JSON: a repeated dashboard fragment over an unchanged
    region answers from the cached plane without touching SSTs (ISSUE
    13 cluster tier). Any write bumps data_version; TRUNCATE resets the
    incarnation; compaction/expiry both bump the version AND drop the
    region's entries through the invalidation seam."""
    from greptimedb_tpu.query import partial_cache as pc

    if frag.stage("partial_agg") is not None and pc.enabled():
        reg = version = None
        try:
            reg = executor.engine.region(region_id)
            version = getattr(reg, "data_version", None)
        except Exception:  # noqa: BLE001 — remote probe: no local identity
            pass
        if version is not None:
            cache = pc.global_cache()
            key = ("frag", region_id, getattr(reg, "incarnation", 0),
                   version, frag.to_json())
            hit = cache.get(key)
            if hit is not None:
                return None if hit is _FRAG_NONE \
                    or hit.get("__frag_none__") else hit
            epoch = cache.epoch(region_id)
            out = _execute_region_fragment_uncached(
                executor, region_id, frag, schema)
            cache.put(key, _FRAG_NONE if out is None else out,
                      epoch=epoch)
            return out
    return _execute_region_fragment_uncached(executor, region_id, frag,
                                             schema)


def _execute_region_fragment_uncached(executor, region_id: int,
                                      frag: PlanFragment,
                                      schema=None) -> Optional[dict]:
    filt = frag.stage("filter")
    where = filt["expr"] if filt else None
    agg = frag.stage("partial_agg")
    common = dict(where=where, ts_range=frag.ts_range,
                  append_mode=frag.append_mode, tz=frag.tz)
    if agg is not None:
        shim = SimpleNamespace(keys=agg["keys"], args=agg["args"],
                               ops=agg["ops"], **common)
        lastp = frag.stage("lastpoint")
        prescan = None
        if lastp is not None and where is None and frag.ts_range is None:
            prescan = _lastpoint_prescan(executor, region_id,
                                         lastp["tag"], shim, schema)
        return partial_region_agg(executor, region_id, shim, schema,
                                  prescan=prescan)
    sort = frag.stage("sort")
    limit = frag.stage("limit")
    prune = frag.stage("prune")
    window = frag.stage("window")
    columns = list(prune["columns"]) if prune else None
    if window is not None:
        return partial_region_window(executor, region_id, columns,
                                     window["calls"], schema=schema,
                                     **common)
    if sort is not None and limit is not None:
        shim = SimpleNamespace(sort_keys=sort["keys"], k=limit["k"],
                               columns=columns, **common)
        return partial_region_topk(executor, region_id, shim, schema)
    return partial_region_rows(executor, region_id, columns,
                               limit["k"] if limit else None,
                               schema=schema, **common)


def _lastpoint_prescan(executor, region_id: int, tag: str, shim,
                       schema=None):
    """Newest-first pruned scan for a lastpoint-class partial_agg
    fragment: the region visits SSTs in descending ts_max order and
    stops once every series provably holds its winner in the visited
    set (Region.scan_last) — the partial planes then reduce a few
    thousand candidate rows instead of the whole region. Returns None
    (full-scan partial) when the engine can't serve it exactly
    (tombstones, no scan_last, projection mismatch) — the fragment
    still returns partial planes either way, never raw rows."""
    from greptimedb_tpu.query.expr import collect_columns

    eng = executor.engine
    if not hasattr(eng, "scan_last"):
        return None
    probe = eng.region(region_id)
    schema = schema or probe.schema
    needed: set[str] = {schema.time_index.name}
    for _, kexpr in shim.keys:
        collect_columns(kexpr, needed)
    for a in shim.args:
        collect_columns(a, needed)
    proj = [c for c in schema.names if c in needed]
    try:
        return eng.scan_last(region_id, tag, proj,
                             full_key=not shim.append_mode)
    except Exception:  # noqa: BLE001 — pruning is an optimization only
        return None


def partial_region_rows(executor, region_id: int, columns, k,
                        *, where, ts_range, append_mode, tz,
                        schema=None) -> Optional[dict]:
    """Filter/prune(/limit) pushdown for plain scans: only the rows that
    survive WHERE — projected to the referenced columns — cross the
    wire, instead of the raw region scan (filter and projection are
    Commutative in the reference's classification,
    commutativity.rs:27-52; the frontend re-evaluates nothing but the
    final projection expressions)."""
    from greptimedb_tpu.query.expr import collect_columns

    probe = executor.engine.region(region_id)
    schema = schema or probe.schema
    ts_name = schema.time_index.name
    ts_range = tuple(ts_range) if ts_range else None
    needed: set[str] = {ts_name}
    collect_columns(where, needed)
    if columns is None:
        needed.update(schema.names)
    else:
        needed.update(columns)
    host = _region_host_columns(executor, region_id, where, ts_range,
                                needed, append_mode, schema, tz=tz)
    if host is None:
        return None
    if columns is not None:
        # the filter already ran here — filter-only columns would be
        # dead weight on the wire; ship exactly the pruned projection
        host = {name: arr for name, arr in host.items()
                if name in columns}
    if k is not None and host:
        n = len(next(iter(host.values())))
        if n > k:
            host = {name: arr[:k] for name, arr in host.items()}
    return {"cols": host}


def partial_region_window(executor, region_id: int, columns, calls,
                          *, where, ts_range, append_mode, tz,
                          schema=None) -> Optional[dict]:
    """Window-partition pushdown: when every OVER clause's PARTITION BY
    covers the table's partition-rule columns, each region holds its
    window partitions WHOLE, so the entire window computation commutes
    with MergeScan (the reference's ConditionalCommutative class,
    commutativity.rs) — the wire carries filtered rows plus the computed
    window columns, never raw scans gathered for a frontend-only pass."""
    from greptimedb_tpu.query.expr import collect_columns
    from greptimedb_tpu.query.window import _eval_window

    probe = executor.engine.region(region_id)
    schema = schema or probe.schema
    ts_name = schema.time_index.name
    ts_range = tuple(ts_range) if ts_range else None
    needed: set[str] = {ts_name}
    collect_columns(where, needed)
    for _, call in calls:
        collect_columns(call, needed)
    if columns is None:
        needed.update(schema.names)
    else:
        needed.update(columns)
    host = _region_host_columns(executor, region_id, where, ts_range,
                                needed, append_mode, schema, tz=tz)
    if host is None:
        return None
    n = len(host[ts_name])

    def resolve(e):
        return e

    def dtype_of(e):
        from greptimedb_tpu.sql import ast as _ast

        if isinstance(e, _ast.Column) and e.name in schema.names:
            return schema.column(e.name).dtype
        return None

    for name, call in calls:
        host[name] = _eval_window(call, host, n, resolve, dtype_of)
    if columns is not None:
        keep = set(columns) | {name for name, _ in calls}
        host = {k: v for k, v in host.items() if k in keep}
    return {"cols": host}


def _region_host_columns(executor, region_id: int, where, ts_range,
                         needed: set, append_mode: bool,
                         schema=None, tz=None, seq_min=None,
                         stats_out=None, prescan=None) -> Optional[dict]:
    """Shared Partial-step prologue: scan (projected + index-pruned),
    LWW-dedup/filter, decode tags, apply the exact ts bounds. Returns the
    filtered host column dict, or None for an empty result. `tz` is the
    FRONTEND's session timezone: naive ts literals in the shipped WHERE
    must coerce identically on the region. `seq_min` restricts to rows
    written after that sequence (the incremental-flow fold boundary);
    `stats_out` (a dict) receives {"rows", "max_seq"} of the RAW scan —
    pre-filter, so the caller's boundary advances past rows WHERE
    rejects and never rescans them."""
    from greptimedb_tpu.query.expr import reset_session_tz, set_session_tz

    tz_token = set_session_tz(tz)
    try:
        return _region_host_columns_inner(
            executor, region_id, where, ts_range, needed, append_mode,
            schema, seq_min=seq_min, stats_out=stats_out, prescan=prescan)
    finally:
        reset_session_tz(tz_token)


def _region_host_columns_inner(executor, region_id, where, ts_range, needed,
                               append_mode, schema, seq_min=None,
                               stats_out=None, prescan=None):
    from types import SimpleNamespace

    from greptimedb_tpu.datatypes.vector import DictVector
    from greptimedb_tpu.storage.index import extract_tag_predicates

    # probe the schema first so projection + index pruning match what the
    # frontend's gather path gets (physical.py execute: scan_node.columns
    # + extract_tag_predicates)
    probe = executor.engine.region(region_id)
    schema = schema or probe.schema
    ts_name = schema.time_index.name
    proj = [c for c in schema.names if c in needed]
    tag_preds = extract_tag_predicates(where, schema) or None
    if prescan is not None:
        # lastpoint-pruned candidate rows stand in for the region scan
        # (same dedup/filter tail below — scan_last's contract is that
        # the subset contains every LWW winner)
        scan = prescan
    else:
        # the whole primary key rides along only where the rows will be
        # merged by it (Region.scan): the fragment carries the table's
        # append_mode, as the dedup below reads it
        scan = executor.engine.scan(region_id, ts_range, proj, tag_preds,
                                    seq_min=seq_min,
                                    full_key=not append_mode)
    if stats_out is not None:
        stats_out["rows"] = 0 if scan is None else int(scan.num_rows)
        if scan is None or scan.num_rows == 0:
            stats_out["max_seq"] = None
            stats_out["max_ts"] = None
        else:
            stats_out["max_seq"] = int(np.max(scan.seq))
            stats_out["max_ts"] = int(np.max(
                scan.columns[schema.time_index.name]))
    if scan is None or scan.num_rows == 0:
        return None

    ctx = BindContext(schema, scan.tag_dicts)
    bound_where = bind_expr(where, ctx) if where is not None else None
    # _filtered_row_indices only consults .schema and (via dedup)
    # .append_mode — a region-local shim stands in for the TableInfo the
    # frontend holds
    shim = SimpleNamespace(schema=schema, append_mode=append_mode)
    idx = executor._filtered_row_indices(scan, shim, ctx, bound_where,
                                         where_unbound=where)
    if len(idx) == 0:
        return None

    host: dict[str, np.ndarray] = {}
    for name, arr in scan.columns.items():
        taken = arr[idx]
        if name in scan.tag_dicts:
            taken = DictVector(taken, scan.tag_dicts[name]).decode()
        host[name] = taken
    if ts_range is not None:
        # scan ts_range is coarse (row-group pruning); apply the exact
        # [lo, hi) bounds here (extract_ts_bounds emits half-open upper
        # bounds) — the frontend derived them from WHERE
        lo, hi = ts_range
        tsv = host[ts_name].astype(np.int64)
        m = np.ones(len(tsv), dtype=bool)
        if lo is not None:
            m &= tsv >= lo
        if hi is not None:
            m &= tsv < hi
        if not m.all():
            host = {k: v[m] for k, v in host.items()}
    if len(host[ts_name]) == 0:
        return None
    return host


def partial_region_agg(executor, region_id: int, frag,
                       schema=None, seq_min=None,
                       stats_out=None, prescan=None) -> Optional[dict]:
    """Compute one region's partial aggregate. Returns
    {"keys": [np.ndarray per key], "planes": {op: [G, F] np.ndarray}}
    with G = observed groups in this region, or None for an empty scan.

    `seq_min` folds only rows written after that sequence (incremental
    flow ticks); `stats_out` (a dict) then receives {"rows": raw scan
    row count, "max_seq": highest sequence scanned} for the caller's
    boundary bookkeeping."""
    from greptimedb_tpu.query.expr import collect_columns

    probe = executor.engine.region(region_id)
    schema = schema or probe.schema
    ts_name = schema.time_index.name
    ts_range = tuple(frag.ts_range) if frag.ts_range else None
    needed: set[str] = {ts_name}
    collect_columns(frag.where, needed)
    for _, k in frag.keys:
        collect_columns(k, needed)
    for a in frag.args:
        collect_columns(a, needed)
    host = _region_host_columns(executor, region_id, frag.where, ts_range,
                                needed, frag.append_mode, schema,
                                tz=frag.tz, seq_min=seq_min,
                                stats_out=stats_out, prescan=prescan)
    if host is None:
        return None
    n = len(host[ts_name])

    # group keys: evaluate, factorize by VALUE (null-safe: NULL is its
    # own group, matching the single-node path's semantics)
    key_uniqs: list[np.ndarray] = []
    gcode = np.zeros(n, dtype=np.int64)
    for _, kexpr in frag.keys:
        vals = np.asarray(eval_host(kexpr, host, schema))
        if vals.ndim == 0:
            vals = np.broadcast_to(vals, (n,))
        uniq, codes = _factorize_with_null(vals)
        key_uniqs.append(uniq)
        gcode = gcode * len(uniq) + codes
    if frag.keys:
        gids_uniq, gcode = np.unique(gcode, return_inverse=True)
        num_groups = len(gids_uniq)
    else:
        gids_uniq = np.zeros(1, dtype=np.int64)
        num_groups = 1

    if frag.args:
        planes = []
        for a in frag.args:
            p = np.asarray(eval_host(a, host, schema))
            if p.dtype == object or p.dtype.kind in ("U", "S"):
                # string argument: only count() rides pushdown (frontend
                # gating), which needs just validity — 1.0 per non-null
                p = np.where(
                    np.asarray([v is None for v in p.ravel()]).reshape(p.shape)
                    if p.dtype == object else np.zeros(p.shape, bool),
                    np.nan, 1.0)
            planes.append(np.asarray(p, dtype=np.float64))
        vals = np.stack([np.broadcast_to(p, (n,)) for p in planes], axis=1)
    else:
        vals = np.zeros((n, 1), dtype=np.float64)

    ops = set(frag.ops)
    need_ts = bool({"first", "last"} & ops)
    out = segment_agg(
        jnp.asarray(vals), jnp.asarray(gcode.astype(np.int32)),
        jnp.ones(n, dtype=bool), num_groups, ops=tuple(sorted(ops)),
        ts=jnp.asarray(host[ts_name].astype(np.int64)) if need_ts else None,
    )
    planes_np = {k: np.asarray(v) for k, v in out.items()}

    # decode each group's key values from the compacted ids
    key_cols: list[np.ndarray] = []
    rem = gids_uniq
    for uniq in reversed(key_uniqs):
        key_cols.append(uniq[rem % len(uniq)])
        rem = rem // len(uniq)
    key_cols.reverse()
    return {"keys": key_cols, "planes": planes_np}


def _factorize_with_null(vals: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """np.unique with NULL support: None (object arrays) and NaN (float
    arrays) can't be sorted/equality-matched by np.unique, so nulls get
    their own trailing code with a None marker in the value table."""
    if vals.dtype == object:
        null_mask = np.asarray([v is None for v in vals])
    elif vals.dtype.kind == "f":
        null_mask = np.isnan(vals)
    else:
        null_mask = None
    if null_mask is None or not null_mask.any():
        if vals.dtype == object:
            # None-free object arrays still need a sortable dtype
            uniq, codes = np.unique(vals.astype(str), return_inverse=True)
            return uniq.astype(object), codes
        return np.unique(vals, return_inverse=True)
    codes = np.empty(len(vals), dtype=np.int64)
    nn = vals[~null_mask]
    if vals.dtype == object:
        uniq_nn, codes_nn = np.unique(nn.astype(str), return_inverse=True)
        uniq_nn = uniq_nn.astype(object)
    else:
        uniq_nn, codes_nn = np.unique(nn, return_inverse=True)
    codes[~null_mask] = codes_nn
    codes[null_mask] = len(uniq_nn)
    uniq = np.empty(len(uniq_nn) + 1, dtype=object)
    uniq[:len(uniq_nn)] = uniq_nn
    uniq[len(uniq_nn)] = None
    return uniq, codes


_ADDITIVE = frozenset({"sum", "count", "rows", "sumsq"})


def _concat_union(cols: list[np.ndarray]) -> np.ndarray:
    """Concatenate arrays preserving a common non-object dtype when
    possible (date_bin keys stay int64), widening to object otherwise."""
    cols = [np.asarray(c) for c in cols]
    dtypes = {c.dtype for c in cols}
    if len(dtypes) == 1 and cols[0].dtype != object:
        return np.concatenate(cols)
    return np.concatenate([c.astype(object) for c in cols])


def combine_partials(partials: list, n_keys: int, ops: tuple) -> Optional[dict]:
    """Final combine of per-region partials (merge_scan.rs:122 role).
    Returns {"keys": [np.ndarray], "planes": {op: [G, F]}} over the union
    of group keys, or None if every partial was empty.

    Fully vectorized: all partials' groups stack into one [R, F] matrix,
    group identity resolves with one np.unique pass per key column, and
    every plane combines with a single scatter (np.add.at / np.fmin.at /
    lexsort for first/last) — no per-group Python. At TSBS scale
    (48k groups x N regions) a dict-per-group loop would dominate
    the distributed win."""
    partials = [p for p in partials if p is not None]
    if not partials:
        return None
    counts = [len(p["keys"][0]) if p["keys"] else 1 for p in partials]
    R = int(np.sum(counts))
    if n_keys:
        # factorize each key column over the stacked values; composite
        # codes identify groups across regions by VALUE (dictionaries
        # differ per region)
        stacks = [_concat_union([p["keys"][j] for p in partials])
                  for j in range(n_keys)]
        gc = np.zeros(R, dtype=np.int64)
        for s in stacks:
            uniq, codes = _factorize_with_null(s)
            if len(uniq) and gc.max(initial=0) > (2**62) // max(len(uniq), 1):
                # keep the composite inside int64: compact before mixing in
                _, gc = np.unique(gc, return_inverse=True)
            gc = gc * len(uniq) + codes
        _, first_idx, pos = np.unique(gc, return_index=True,
                                      return_inverse=True)
        # stable first-seen group order (matches the former dict behavior)
        order = np.argsort(first_idx, kind="stable")
        rank = np.empty(len(order), dtype=np.int64)
        rank[order] = np.arange(len(order))
        pos = rank[pos]
        first_idx = first_idx[order]
        G = len(first_idx)
        key_cols = [s[first_idx] for s in stacks]
    else:
        pos = np.zeros(R, dtype=np.int64)
        G = 1
        key_cols = []

    sample = partials[0]["planes"]
    stacked: dict[str, np.ndarray] = {}
    for op in sample:
        stacked[op] = np.concatenate(
            [p["planes"][op] if p["planes"][op].ndim == 2
             else p["planes"][op][:, None] for p in partials], axis=0
        ).astype(np.float64 if op not in ("first_ts", "last_ts")
                 else np.int64)

    acc: dict[str, np.ndarray] = {}
    for op, pl in stacked.items():
        f = pl.shape[1]
        if op in _ADDITIVE:
            a = np.zeros((G, f))
            np.add.at(a, pos, pl)
            acc[op] = a
        elif op == "min":
            a = np.full((G, f), np.nan)
            np.fmin.at(a, pos, pl)  # fmin(NaN, x) = x: NaN init is empty
            acc[op] = a
        elif op == "max":
            a = np.full((G, f), np.nan)
            np.fmax.at(a, pos, pl)
            acc[op] = a
    for op, ts_op, pick_last in (("first", "first_ts", False),
                                 ("last", "last_ts", True)):
        if op not in stacked:
            continue
        pl = stacked[op]
        ts = stacked[ts_op][:, 0]  # ONE ts per group (segment_agg emits
        # a single per-group ts shared by every value field)
        f = pl.shape[1]
        vout = np.full((G, f), np.nan)
        tsout = np.full(
            (G, 1),
            np.iinfo(np.int64).min if pick_last else np.iinfo(np.int64).max,
            dtype=np.int64)
        # sort by (group, ts): the first/last row of each group run is
        # the oldest/newest partial — empty-region sentinels sort to the
        # never-picked end automatically; the winner row is shared by all
        # value fields
        o = np.lexsort((ts, pos))
        boundary = np.empty(R, dtype=bool)
        if R:
            boundary[0] = True
            boundary[1:] = pos[o][1:] != pos[o][:-1]
        if pick_last:
            picks = np.append(np.flatnonzero(boundary)[1:] - 1, R - 1) \
                if R else np.empty(0, dtype=np.int64)
        else:
            picks = np.flatnonzero(boundary)
        rows = o[picks]
        vout[pos[rows], :] = pl[rows, :]
        tsout[pos[rows], 0] = ts[rows]
        acc[op] = vout
        acc[ts_op] = tsout
    for op in ("count", "rows"):
        if op in acc:
            acc[op] = acc[op].astype(np.int64)
    return {"keys": key_cols, "planes": acc}


# ---- sort/limit (top-k) pushdown -------------------------------------------


def sort_order_for(sort_keys: list, host: dict, schema, n: int) -> np.ndarray:
    """Row order for [(expr, asc)] sort keys over host columns. Uses
    order-preserving factorized codes so asc/desc works for every dtype
    (negating object/string arrays isn't possible directly)."""
    code_arrays = []
    for kexpr, asc in sort_keys:
        vals = np.asarray(eval_host(kexpr, host, schema))
        if vals.ndim == 0:
            vals = np.broadcast_to(vals, (n,))
        uniq, codes = _factorize_with_null(vals)
        code_arrays.append(codes if asc else -codes)
    # lexsort: primary key LAST
    return np.lexsort(tuple(reversed(code_arrays)))


def partial_region_topk(executor, region_id: int, frag,
                        schema=None) -> Optional[dict]:
    """One region's top-k candidates for a sort+limit scan: filter, sort
    locally, truncate to k rows. Only k rows — not the raw scan — return
    to the frontend (sort+limit stages; the reference classifies Limit as
    PartialCommutative over MergeScan, commutativity.rs:27-52)."""
    from greptimedb_tpu.query.expr import collect_columns

    probe = executor.engine.region(region_id)
    schema = schema or probe.schema
    ts_name = schema.time_index.name
    ts_range = tuple(frag.ts_range) if frag.ts_range else None
    needed: set[str] = {ts_name}
    collect_columns(frag.where, needed)
    for kexpr, _ in frag.sort_keys:
        collect_columns(kexpr, needed)
    if frag.columns is None:
        needed.update(schema.names)
    else:
        needed.update(frag.columns)
    host = _region_host_columns(executor, region_id, frag.where, ts_range,
                                needed, frag.append_mode, schema,
                                tz=frag.tz)
    if host is None:
        return None
    n = len(host[ts_name])
    order = sort_order_for(frag.sort_keys, host, schema, n)[:frag.k]
    return {"cols": {name: arr[order] for name, arr in host.items()}}


def merge_topk(partials: list) -> Optional[dict]:
    """Concatenate per-region top-k candidates (the final sort+limit runs
    in the frontend's shared post-processing)."""
    partials = [p for p in partials if p is not None]
    if not partials:
        return None
    names = list(partials[0]["cols"])
    return {"cols": {name: _concat_union([p["cols"][name]
                                          for p in partials])
                     for name in names}}

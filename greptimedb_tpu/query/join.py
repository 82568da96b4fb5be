"""Host hash-join executor for multi-table SELECTs.

Mirrors the reference's join capability (full SQL via DataFusion's hash
join). Joins in a TSDB serve metadata/dimension enrichment — modest
cardinalities off the scan/aggregate hot path — so the TPU-first design
keeps them on host: materialize each side (each side's scan still uses
the device path + caches), equi-hash-join, then evaluate the remaining
select pipeline over the joined columns with the shared host evaluator.

Supported: INNER / LEFT [OUTER] joins, conjunctions of equality
predicates in ON, qualified (alias.col) and unambiguous bare column
references, WHERE, projection incl. expressions, GROUP BY aggregates
(count/sum/avg/min/max), HAVING, ORDER BY, LIMIT/OFFSET, DISTINCT.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from greptimedb_tpu.query.expr import PlanError, eval_host
from greptimedb_tpu.query.result import QueryResult
from greptimedb_tpu.sql import ast

_AGGS = {"count", "sum", "avg", "min", "max"}


def execute_join_select(qe, sel: ast.Select, ctx) -> QueryResult:
    # each side: (table_name_or_None, alias, derived_subquery_or_None)
    if sel.from_subquery is not None:
        if sel.table_alias is None:
            raise PlanError("derived table in a join requires an alias")
        sides = [(None, sel.table_alias, sel.from_subquery)]
    else:
        sides = [(sel.table, sel.table_alias or sel.table, None)]
    for j in sel.joins:
        sides.append((j.table, j.alias or j.table, j.subquery))
    names = [alias for _, alias, _ in sides]
    if len(set(names)) != len(names):
        raise PlanError(f"duplicate table alias in join: {names}")

    # materialize each side through the normal single-table path (device
    # scan + caches), pushing down single-side WHERE conjuncts and the
    # referenced-column projection so only the needed slice crosses into
    # the host join (the reference pushes the same through DataFusion's
    # join planning)
    conjuncts = _split_conjuncts(sel.where)
    side_cols = _referenced_by_side(sel, sides)
    # the null-supplying side(s) of an outer join must NOT have WHERE
    # conjuncts pushed into their scan: `WHERE right.x IS NULL`
    # (anti-join) would drop the very rows whose absence produces the
    # NULLs. LEFT → right side; RIGHT/FULL → conservatively all sides
    # (the accumulated left is a composite).
    unpushable = {j.alias or j.table for j in sel.joins if j.kind == "left"}
    if any(j.kind in ("right", "full") for j in sel.joins):
        unpushable = set(names)
    mats = []
    for table, alias, subq in sides:
        if subq is not None:
            r = qe._execute_statement(subq, ctx)
            if not r.is_query:
                raise PlanError("derived table must be a query")
            mats.append({"alias": alias,
                         "cols": dict(zip(r.names,
                                          (np.asarray(c)
                                           for c in r.columns))),
                         "dtypes": dict(zip(r.names, r.dtypes))})
            continue
        pushed = [] if alias in unpushable else \
            [_strip_qualifier(c, alias) for c in conjuncts
             if _only_references(c, alias, sides)]
        where = None
        for p in pushed:
            where = p if where is None else ast.BinaryOp("and", where, p)
        wanted = side_cols.get(alias)
        if not wanted:  # no map (Star/bare refs) or nothing referenced
            items = [ast.SelectItem(ast.Star())]
        else:
            items = [ast.SelectItem(ast.Column(c)) for c in sorted(wanted)]
        sub = ast.Select(items=items, table=table, where=where)
        try:
            r = qe._select(sub, ctx)
        except PlanError:
            # conservative fallback: a pushdown the single-table path
            # can't evaluate (pruning is an optimization, never required)
            sub = ast.Select(items=[ast.SelectItem(ast.Star())],
                             table=table)
            r = qe._select(sub, ctx)
        mats.append({"alias": alias,
                     "cols": dict(zip(r.names,
                                      (np.asarray(c) for c in r.columns))),
                     "dtypes": dict(zip(r.names, r.dtypes))})

    # left-deep fold: joined = base; for each join: hash-join with next
    joined_cols, joined_dtypes = _qualify(mats[0])
    for j, mat in zip(sel.joins, mats[1:]):
        right_cols, right_dtypes = _qualify(mat)
        pairs = [] if j.kind == "cross" else \
            _equi_pairs(j.on, joined_cols, right_cols)
        joined_cols, joined_dtypes = _hash_join(
            joined_cols, joined_dtypes, right_cols, right_dtypes,
            pairs, j.kind)

    # expose unambiguous bare names too
    bare: dict[str, Optional[str]] = {}
    for q in joined_cols:
        b = q.split(".", 1)[1]
        bare[b] = None if b in bare else q
    env_cols = dict(joined_cols)
    for b, q in bare.items():
        if q is not None:
            env_cols[b] = joined_cols[q]
            joined_dtypes[b] = joined_dtypes[q]

    state = {"cols": env_cols,
             "n": len(next(iter(env_cols.values()))) if env_cols else 0}

    def resolve(e):
        return _resolve_columns(e, state["cols"])

    def ev(e):
        return eval_host(resolve(e), state["cols"], None, None, state["n"])

    if sel.where is not None:
        mask = np.broadcast_to(np.asarray(ev(sel.where), dtype=bool),
                               (state["n"],))
        idx = np.nonzero(mask)[0]
        state["cols"] = {k: v[idx] for k, v in state["cols"].items()}
        state["n"] = len(idx)
    env_cols = state["cols"]
    n = state["n"]

    from greptimedb_tpu.query.window import rewrite_select, select_has_window
    if select_has_window(sel):
        if _has_grouping_aggs(sel):
            # SQL evaluation order: group first, windows over the groups
            inner, outer = split_groupby_window(sel)
            r = _aggregate(inner, env_cols, joined_dtypes, n, resolve)
            return execute_select_over(
                qe, outer, dict(zip(r.names, r.columns)),
                dict(zip(r.names, r.dtypes)))
        sel = rewrite_select(sel, env_cols, n, resolve, joined_dtypes)

    has_agg = sel.group_by or any(
        _contains_agg(it.expr) for it in sel.items)
    if has_agg:
        return _aggregate(sel, env_cols, joined_dtypes, n, resolve)

    # plain projection
    out_names, out_cols, out_dtypes = [], [], []
    for i, it in enumerate(sel.items):
        if isinstance(it.expr, ast.Star):
            for q in joined_cols:
                out_names.append(q)
                out_cols.append(env_cols[q])
                out_dtypes.append(joined_dtypes.get(q))
            continue
        v = ev(it.expr)
        arr = np.asarray([v] * n) if np.ndim(v) == 0 else np.asarray(v)
        out_names.append(it.alias or _expr_name(it.expr))
        out_cols.append(arr)
        out_dtypes.append(None)
    r = QueryResult(out_names, out_dtypes, out_cols)
    # ORDER BY may reference unprojected columns: evaluate keys over the
    # full joined namespace, not the projected output
    return _post(sel, r, resolve, env=env_cols)


def execute_select_over(qe, sel: ast.Select, base_cols: dict,
                        base_dtypes: dict, alias=None) -> QueryResult:
    """Evaluate a full SELECT pipeline over in-memory columns — the
    execution path for views (the view query materializes through the
    normal engine; the outer select then runs here) and any other
    virtual relation."""
    env = {k: np.asarray(v) for k, v in base_cols.items()}
    dtypes = dict(base_dtypes)
    if alias:
        for k in list(env):
            env[f"{alias}.{k}"] = env[k]
            dtypes[f"{alias}.{k}"] = dtypes.get(k)
    n = len(next(iter(env.values()))) if env else 0

    state = {"cols": env, "n": n}

    def resolve(e):
        return _resolve_columns(e, state["cols"])

    def ev(e):
        return eval_host(resolve(e), state["cols"], None, None, state["n"])

    if sel.where is not None:
        mask = np.broadcast_to(np.asarray(ev(sel.where), dtype=bool),
                               (state["n"],))
        idx = np.nonzero(mask)[0]
        state["cols"] = {k: v[idx] for k, v in state["cols"].items()}
        state["n"] = len(idx)
    env = state["cols"]
    n = state["n"]

    from greptimedb_tpu.query.window import rewrite_select, select_has_window
    if select_has_window(sel):
        if _has_grouping_aggs(sel):
            inner, outer = split_groupby_window(sel)
            r = _aggregate(inner, env, dtypes, n, resolve)
            return execute_select_over(
                qe, outer, dict(zip(r.names, r.columns)),
                dict(zip(r.names, r.dtypes)))
        sel = rewrite_select(sel, env, n, resolve, dtypes)

    if sel.group_by or any(_contains_agg(it.expr) for it in sel.items):
        return _aggregate(sel, env, dtypes, n, resolve)

    out_names, out_cols, out_dtypes = [], [], []
    for i, it in enumerate(sel.items):
        if isinstance(it.expr, ast.Star):
            for k in base_cols:
                out_names.append(k)
                out_cols.append(env[k])
                out_dtypes.append(dtypes.get(k))
            continue
        v = ev(it.expr)
        arr = np.asarray([v] * n) if np.ndim(v) == 0 else np.asarray(v)
        out_names.append(it.alias or _expr_name(it.expr))
        out_cols.append(arr)
        out_dtypes.append(None)
    r = QueryResult(out_names, out_dtypes, out_cols)
    return _post(sel, r, resolve, env=env)


# ---- pushdown helpers ------------------------------------------------------


def _split_conjuncts(where):
    from greptimedb_tpu.query.expr import split_conjuncts

    return split_conjuncts(where)


def _columns_in(e, out: set):
    if isinstance(e, ast.Column):
        out.add((e.table, e.name))
    elif isinstance(e, (list, tuple)):
        # descends into nested containers too — Case.whens is a tuple of
        # (when_expr, then_expr) tuples
        for x in e:
            _columns_in(x, out)
    elif dataclasses.is_dataclass(e) and not isinstance(e, type):
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            # non-Expr expression carriers descend too: FuncCall.over is
            # a WindowSpec whose PARTITION BY/ORDER BY reference columns
            if isinstance(v, (ast.Expr, list, tuple)) or (
                    dataclasses.is_dataclass(v) and not isinstance(v, type)):
                _columns_in(v, out)


def _only_references(conjunct, alias: str, sides) -> bool:
    """True iff every column in the conjunct is qualified with `alias` —
    safe to evaluate inside that side's scan (bare names are left to the
    post-join filter; qualification is the pushdown opt-in)."""
    cols: set = set()
    _columns_in(conjunct, cols)
    return bool(cols) and all(t == alias for t, _ in cols)


def _strip_qualifier(e, alias: str):
    return _rewrite_columns(
        e, lambda c: ast.Column(c.name) if c.table == alias else c)


def _referenced_by_side(sel, sides) -> dict:
    """alias -> column-name set to project per side, or {} (meaning: no
    per-side map — project everything) when a Star or any bare (or
    unattributable) reference appears."""
    cols: set = set()
    star = False
    for it in sel.items:
        if isinstance(it.expr, ast.Star):
            star = True
        else:
            _columns_in(it.expr, cols)
    _columns_in(sel.where, cols)
    for j in sel.joins:
        _columns_in(j.on, cols)
    for g in sel.group_by:
        _columns_in(g, cols)
    _columns_in(sel.having, cols)
    for ob in sel.order_by:
        _columns_in(ob.expr, cols)
    if star or any(t is None for t, _ in cols):
        return {}
    aliases = {alias for _, alias, _ in sides}
    if any(t not in aliases for t, _ in cols):
        return {}
    out: dict = {}
    for t, c in cols:
        out.setdefault(t, set()).add(c)
    # a side nothing references still needs its join keys (covered above
    # via ON) — and at least one column to materialize row count
    for _, alias, _ in sides:
        out.setdefault(alias, set())
    return out


# ---- helpers ---------------------------------------------------------------


def _qualify(mat):
    cols = {f"{mat['alias']}.{k}": v for k, v in mat["cols"].items()}
    dtypes = {f"{mat['alias']}.{k}": v for k, v in mat["dtypes"].items()}
    return cols, dtypes


def _rewrite_columns(e, repl):
    """Apply `repl` to every Column node, descending dataclass fields AND
    nested containers (Case.whens is a tuple of (when, then) tuples;
    FuncCall.over is a WindowSpec carrying PARTITION BY/ORDER BY exprs)."""
    if isinstance(e, ast.Column):
        return repl(e)
    if isinstance(e, (list, tuple)):
        return type(e)(_rewrite_columns(x, repl) for x in e)
    if dataclasses.is_dataclass(e) and not isinstance(e, type):
        changes = {}
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            if isinstance(v, (ast.Expr, list, tuple)) or (
                    dataclasses.is_dataclass(v) and not isinstance(v, type)):
                nv = _rewrite_columns(v, repl)
                if nv != v:
                    changes[f.name] = nv
        if changes:
            return dataclasses.replace(e, **changes)
    return e


def _resolve_columns(e, cols: dict):
    """Rewrite Column nodes to the joined namespace: alias-qualified
    references become 'alias.col'; bare names must be unambiguous."""

    def repl(c: ast.Column):
        if c.table:
            q = f"{c.table}.{c.name}"
            if q not in cols:
                raise PlanError(f"unknown column {q!r} in join")
            return ast.Column(q)
        if c.name in cols:
            return c
        matches = [q for q in cols
                   if "." in q and q.split(".", 1)[1] == c.name]
        if len(matches) == 1:
            return ast.Column(matches[0])
        if len(matches) > 1:
            raise PlanError(f"ambiguous column {c.name!r}: {matches}")
        raise PlanError(f"unknown column {c.name!r} in join")

    return _rewrite_columns(e, repl)


def _equi_pairs(on, left_cols: dict, right_cols: dict):
    """(left_key, right_key) pairs from a conjunction of equalities."""
    pairs = []

    def side_of(c: ast.Column):
        if c.table:
            q = f"{c.table}.{c.name}"
            if q in left_cols:
                return "l", q
            if q in right_cols:
                return "r", q
            raise PlanError(f"unknown column {q!r} in ON")
        lm = [q for q in left_cols if q.split(".", 1)[1] == c.name]
        rm = [q for q in right_cols if q.split(".", 1)[1] == c.name]
        if len(lm) + len(rm) != 1:
            raise PlanError(
                f"ambiguous or unknown ON column {c.name!r}")
        return ("l", lm[0]) if lm else ("r", rm[0])

    def walk(e):
        if isinstance(e, ast.BinaryOp) and e.op == "and":
            walk(e.left)
            walk(e.right)
            return
        if (isinstance(e, ast.BinaryOp) and e.op == "="
                and isinstance(e.left, ast.Column)
                and isinstance(e.right, ast.Column)):
            s1, q1 = side_of(e.left)
            s2, q2 = side_of(e.right)
            if {s1, s2} != {"l", "r"}:
                raise PlanError("ON clause must compare the two sides")
            pairs.append((q1, q2) if s1 == "l" else (q2, q1))
            return
        raise PlanError(
            "only conjunctions of column equalities are supported in ON")

    walk(on)
    if not pairs:
        raise PlanError("ON clause has no equality condition")
    return pairs


def _key_tuple(cols: dict, keys: list, i: int):
    return tuple(None if _is_nan(cols[k][i]) else cols[k][i] for k in keys)


def _is_nan(v) -> bool:
    return isinstance(v, float) and v != v


def _hash_join(lcols, ldtypes, rcols, rdtypes, pairs, kind: str):
    """Hash join of two qualified column dicts. kinds: inner, left,
    right, full (null-extended on the respective side), cross
    (cartesian, no pairs)."""
    rn = len(next(iter(rcols.values()))) if rcols else 0
    ln = len(next(iter(lcols.values()))) if lcols else 0
    if kind == "cross":
        li = np.repeat(np.arange(ln, dtype=np.int64), rn)
        ri = np.tile(np.arange(rn, dtype=np.int64), ln)
    else:
        lk = [p[0] for p in pairs]
        rk = [p[1] for p in pairs]
        table: dict = {}
        for i in range(rn):
            key = _key_tuple(rcols, rk, i)
            if any(k is None for k in key):
                continue  # NULL never matches in SQL equality
            table.setdefault(key, []).append(i)
        li_l, ri_l = [], []
        matched_r = np.zeros(rn, dtype=bool)
        for i in range(ln):
            key = _key_tuple(lcols, lk, i)
            hits = table.get(key) if not any(k is None for k in key) else None
            if hits:
                for j in hits:
                    li_l.append(i)
                    ri_l.append(j)
                    matched_r[j] = True
            elif kind in ("left", "full"):
                li_l.append(i)
                ri_l.append(-1)  # NULL right row
        if kind in ("right", "full"):
            for j in np.flatnonzero(~matched_r):
                li_l.append(-1)  # NULL left row
                ri_l.append(int(j))
        li = np.asarray(li_l, dtype=np.int64)
        ri = np.asarray(ri_l, dtype=np.int64)

    def take(cols: dict, idx: np.ndarray) -> dict:
        miss = idx < 0
        out = {}
        for k, v in cols.items():
            v = np.asarray(v)
            taken = v[np.clip(idx, 0, None)] if len(v) else \
                np.empty(len(idx), dtype=v.dtype)
            if miss.any():
                taken = taken.astype(object)
                taken[miss] = None
            out[k] = taken
        return out

    out = take(lcols, li)
    out.update(take(rcols, ri))
    dtypes = {**ldtypes, **rdtypes}
    return out, dtypes


def _has_grouping_aggs(sel: ast.Select) -> bool:
    """True when the SELECT needs an aggregation pass before windows:
    GROUP BY, or any non-window aggregate call — INCLUDING one appearing
    only inside an OVER clause (e.g. rank() OVER (ORDER BY avg(v)):
    valid SQL, one implicit group)."""
    if sel.group_by:
        return True
    from greptimedb_tpu.query.planner import _FUNC_CANON

    found = [False]

    def walk(e):
        if found[0]:
            return
        if isinstance(e, ast.FuncCall):
            if e.over is None and e.name.lower() in _FUNC_CANON:
                found[0] = True
                return
            for a in e.args:
                walk(a)
            if e.over is not None:
                walk(e.over.partition_by)
                for o, _ in e.over.order_by:
                    walk(o)
            return
        if isinstance(e, (list, tuple)):
            for x in e:
                walk(x)
        elif dataclasses.is_dataclass(e) and not isinstance(e, type):
            for f in dataclasses.fields(e):
                v = getattr(e, f.name)
                if isinstance(v, (ast.Expr, list, tuple)):
                    walk(v)

    for it in sel.items:
        walk(it.expr)
    for ob in sel.order_by:
        walk(ob.expr)
    return found[0]


def split_groupby_window(sel: ast.Select):
    """SELECT mixing GROUP BY (or plain aggregates) with window
    functions: SQL evaluates windows AFTER grouping, over the grouped
    relation (reference: DataFusion plans WindowAggExec above
    AggregateExec). Returns (inner, outer): `inner` is the window-free
    aggregate — group keys under their display names, each distinct
    aggregate call as __ga_i — and `outer` re-expresses the original
    items over inner's output with the window calls intact. The caller
    runs inner through the normal (device) aggregate path, then the
    window machinery over its G-row result."""
    from greptimedb_tpu.query.planner import _FUNC_CANON

    aggs: list[ast.FuncCall] = []

    def collect(e):
        if isinstance(e, ast.FuncCall):
            if e.over is None and e.name.lower() in _FUNC_CANON:
                if e not in aggs:
                    aggs.append(e)
                return
            for a in e.args:
                collect(a)
            if e.over is not None:
                collect(e.over.partition_by)
                for o, _ in e.over.order_by:
                    collect(o)
            return
        if isinstance(e, (list, tuple)):
            for x in e:
                collect(x)
        elif dataclasses.is_dataclass(e) and not isinstance(e, type):
            for f in dataclasses.fields(e):
                v = getattr(e, f.name)
                if isinstance(v, (ast.Expr, list, tuple)):
                    collect(v)

    for it in sel.items:
        collect(it.expr)
    for ob in sel.order_by:
        collect(ob.expr)

    repl: list[tuple] = []
    inner_items: list[ast.SelectItem] = []
    alias_to_expr = {it.alias: it.expr for it in sel.items if it.alias}
    for i, k in enumerate(sel.group_by):
        if isinstance(k, ast.Column) and k.name in alias_to_expr:
            # GROUP BY <item alias>: group by the aliased expression and
            # surface it under the user's alias
            expr = alias_to_expr[k.name]
            inner_items.append(ast.SelectItem(expr, alias=k.name))
            repl.append((expr, ast.Column(k.name)))
            continue
        if isinstance(k, ast.Column):
            inner_items.append(ast.SelectItem(k))
            repl.append((k, ast.Column(k.name)))
        else:
            nm = next((it.alias for it in sel.items
                       if it.alias and it.expr == k), None) or f"__gk_{i}"
            inner_items.append(ast.SelectItem(k, alias=nm))
            repl.append((k, ast.Column(nm)))
    for i, a in enumerate(aggs):
        nm = f"__ga_{i}"
        inner_items.append(ast.SelectItem(a, alias=nm))
        repl.append((a, ast.Column(nm)))

    def replace(e):
        for orig, col in repl:
            if e == orig:
                return col
        if isinstance(e, (list, tuple)):
            return type(e)(replace(x) for x in e)
        if dataclasses.is_dataclass(e) and not isinstance(e, type) \
                and isinstance(e, (ast.Expr, ast.WindowSpec)):
            changes = {}
            for f in dataclasses.fields(e):
                v = getattr(e, f.name)
                if isinstance(v, (ast.Expr, ast.WindowSpec, list, tuple)):
                    nv = replace(v)
                    if nv != v:
                        changes[f.name] = nv
            if changes:
                return dataclasses.replace(e, **changes)
        return e

    out_items = []
    for it in sel.items:
        ne = replace(it.expr)
        alias = it.alias
        if alias is None and ne != it.expr:
            # keep the user-visible column header (e.g. "avg(v)") when
            # the expression collapsed to an internal alias
            alias = _expr_name(it.expr)
        out_items.append(dataclasses.replace(it, expr=ne, alias=alias))
    out_order = [dataclasses.replace(ob, expr=replace(ob.expr))
                 for ob in sel.order_by]
    inner = dataclasses.replace(
        sel, items=inner_items, order_by=[], limit=None, offset=None,
        distinct=False)
    outer = dataclasses.replace(
        sel, items=out_items, table=None, table_alias=None, joins=[],
        where=None, group_by=[], having=None, order_by=out_order,
        ctes=[], from_subquery=None)
    return inner, outer


def _contains_agg(e) -> bool:
    if isinstance(e, ast.FuncCall):
        if e.over is not None:
            return False  # sum(x) OVER (...) is a window, not an aggregate
        if e.name.lower() in _AGGS:
            return True
        return any(_contains_agg(a) for a in e.args)
    if dataclasses.is_dataclass(e) and not isinstance(e, type):
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            if isinstance(v, ast.Expr) and _contains_agg(v):
                return True
            if isinstance(v, (list, tuple)) and any(
                    isinstance(x, ast.Expr) and _contains_agg(x)
                    for x in v):
                return True
    return False


def _null_mask(arr: np.ndarray) -> np.ndarray:
    """Where a column holds NULL: None, or NaN (NaN is NULL here)."""
    if arr.dtype == object:
        return np.asarray(arr == None, dtype=bool) \
            | np.asarray(arr != arr, dtype=bool)  # noqa: E711
    if arr.dtype.kind == "f":
        return np.isnan(arr)
    return np.zeros(len(arr), dtype=bool)


def _factorize(arr: np.ndarray) -> tuple:
    """(codes[n], groups) of one group key: equal values share a code,
    codes ascend with the values, every NULL shares the last one (SQL
    GROUP BY: one NULL group, sorted last)."""
    null = _null_mask(arr)
    vals = arr[~null] if null.any() else arr
    if vals.dtype == object:
        text = vals.astype("U")
        if len(vals) and not np.asarray(text.astype(object) == vals,
                                        dtype=bool).all():
            text = None  # not all strings: compare the objects
        try:
            _, inv = np.unique(vals if text is None else text,
                               return_inverse=True)
        except TypeError:  # values no order holds together
            seen: dict = {}
            inv = np.asarray([seen.setdefault(v, len(seen)) for v in vals],
                             dtype=np.int64)
    else:
        _, inv = np.unique(vals, return_inverse=True)
    inv = np.asarray(inv, dtype=np.int64).reshape(-1)
    k = int(inv.max()) + 1 if len(inv) else 0
    if not null.any():
        return inv, k
    codes = np.full(len(arr), k, dtype=np.int64)
    codes[~null] = inv
    return codes, k + 1


def _group_rows(key_arrays: list, n: int) -> tuple:
    """(group of each row, groups, first row of each group); groups
    ascend by (key 1, key 2, ...), NULL last in each component."""
    if not key_arrays:
        return np.zeros(n, dtype=np.int64), 1, np.zeros(1, dtype=np.int64)
    gid = np.zeros(n, dtype=np.int64)
    for a in key_arrays:
        codes, k = _factorize(a)
        if k and int(gid.max(initial=0)) >= (1 << 62) // max(k, 1):
            # the radix outgrew an int64: rank what there is first
            gid = np.unique(gid, return_inverse=True)[1].reshape(-1)
        gid = gid * max(k, 1) + codes
    _, first, inv = np.unique(gid, return_index=True, return_inverse=True)
    return inv.reshape(-1), len(first), first


def _reduce_groups(name: str, vals: np.ndarray, gid: np.ndarray,
                   groups: int):
    """One aggregate of one argument per group, NULLs (None / NaN)
    skipped: (values[groups], NULL where a group held no value)."""
    null = _null_mask(vals)
    count = np.bincount(gid[~null], minlength=groups)
    if name == "count":
        return count, None
    empty = count == 0
    if name in ("sum", "avg"):
        x = vals.copy()
        x[null] = 0
        total = np.bincount(gid, weights=x.astype(np.float64),
                            minlength=groups)
        if name == "avg":
            total = total / np.maximum(count, 1)
        return total, empty
    # min / max: sort rows by group, reduce each group's slice
    keep = ~null
    order = np.argsort(gid[keep], kind="stable")
    g, v = gid[keep][order], vals[keep][order]
    starts = np.flatnonzero(np.r_[True, g[1:] != g[:-1]]) if len(g) \
        else np.empty(0, dtype=np.int64)
    out = np.empty(groups, dtype=v.dtype if v.dtype != object else object)
    if v.dtype == object:
        out[:] = None
        ends = np.r_[starts[1:], len(g)]
        pick = min if name == "min" else max
        for s0, s1 in zip(starts, ends):  # a loop over groups, not rows
            out[g[s0]] = pick(v[s0:s1])
    elif len(g):
        red = np.minimum if name == "min" else np.maximum
        out[:] = 0
        out[g[starts]] = red.reduceat(v, starts)
    return out, empty


def _aggregate(sel, cols, dtypes, n, resolve) -> QueryResult:
    """GROUP BY / aggregates / HAVING of a select over in-memory columns
    (a derived table, a CTE, a view, a join's output), as arrays: the
    keys are factorized, each aggregate is one reduction over all rows,
    and the select items and HAVING are evaluated over the G groups."""
    from greptimedb_tpu.utils import tracing
    from greptimedb_tpu.utils.metrics import DERIVED_SELECT_SECONDS

    with DERIVED_SELECT_SECONDS.time(), tracing.stage("host_agg"), \
            tracing.span("derived_select", rows_in=n,
                         path="numpy") as attrs:
        r = _aggregate_arrays(sel, cols, n, resolve)
        attrs["groups_out"] = r.num_rows
    return _post(sel, r, resolve)


def _aggregate_arrays(sel, cols, n, resolve) -> QueryResult:
    def column(e) -> np.ndarray:
        v = eval_host(e, cols, None, None, n)
        return np.asarray([v] * n) if np.ndim(v) == 0 else np.asarray(v)

    gid, groups, first = _group_rows(
        [column(resolve(g)) for g in sel.group_by], n)
    if not sel.group_by and n == 0:
        first = np.empty(0, dtype=np.int64)  # one group, and no row in it

    def rec(e):
        """(value per group, NULL mask or None) of an expression over
        aggregates, group keys and literals."""
        if isinstance(e, ast.FuncCall) and e.name.lower() in _AGGS:
            fname = e.name.lower()
            if fname == "count" and (not e.args or isinstance(
                    e.args[0], ast.Star)):
                return np.bincount(gid, minlength=groups), None
            return _reduce_groups(fname, column(resolve(e.args[0])), gid,
                                  groups)
        if isinstance(e, ast.Column):
            vals = column(resolve(e))
            if len(first) < groups:  # the one empty group
                return np.full(groups, None, dtype=object), None
            return vals[first], None
        if isinstance(e, ast.Literal):
            return np.full(groups, e.value,
                           dtype=object if e.value is None
                           or isinstance(e.value, str) else None), None
        if isinstance(e, ast.BinaryOp):
            import operator as op

            (a, na), (b, nb) = rec(e.left), rec(e.right)
            if e.op in ("and", "or"):
                f = np.logical_and if e.op == "and" else np.logical_or
                return f(_truth(a, na), _truth(b, nb)), None
            f = {"+": op.add, "-": op.sub, "*": op.mul,
                 "/": op.truediv, "%": op.mod,
                 "=": op.eq, "!=": op.ne, "<": op.lt, "<=": op.le,
                 ">": op.gt, ">=": op.ge}.get(e.op)
            if f is None:
                raise PlanError(
                    f"unsupported op {e.op!r} over join aggregates")
            # NULL in, NULL out (a comparison with NULL is not true)
            null = na if nb is None else nb if na is None else na | nb
            with np.errstate(divide="ignore", invalid="ignore"):
                return f(a, b), null
        raise PlanError(
            f"unsupported expression over join aggregates: {e}")

    out_names = []
    for it in sel.items:
        if isinstance(it.expr, ast.Star):
            raise PlanError("SELECT * with GROUP BY over a join")
        out_names.append(it.alias or _expr_name(it.expr))
    keep = None
    if sel.having is not None:
        keep = np.flatnonzero(_truth(*rec(resolve(sel.having))))
    cols_out = []
    for it in sel.items:
        vals, null = rec(it.expr)
        vals = np.asarray(vals)
        if null is not None and null.any():
            vals = vals.astype(object)
            vals[null] = None
        elif vals.dtype.kind in "iu":
            vals = vals.astype(np.int64)
        elif vals.dtype.kind == "f":
            vals = vals.astype(np.float64)
        cols_out.append(vals if keep is None else vals[keep])
    return QueryResult(out_names, [None] * len(out_names), cols_out)


def _truth(vals, null) -> np.ndarray:
    """A predicate's value per group as a bool: NULL is not true."""
    vals = np.asarray(vals)
    out = vals.astype(bool) if vals.dtype != object \
        else np.asarray([bool(v) for v in vals], dtype=bool)
    return out if null is None else out & ~null


def _post(sel, r: QueryResult, resolve,
          env: Optional[dict] = None) -> QueryResult:
    """ORDER BY / DISTINCT / LIMIT / OFFSET. Order keys resolve against
    the output columns by name first, then (if `env` is given, i.e. rows
    are still 1:1 with the joined relation) against the full joined
    namespace — SQL allows ordering by unprojected columns."""
    n = r.num_rows
    idx = np.arange(n)
    if sel.order_by:
        for ob in reversed(sel.order_by):
            name = _expr_name(ob.expr)
            qualified = isinstance(ob.expr, ast.Column) and ob.expr.table
            if qualified and f"{ob.expr.table}.{ob.expr.name}" in r.names:
                # Star projections emit qualified output names
                col = np.asarray(
                    r.column(f"{ob.expr.table}.{ob.expr.name}"))[idx]
            elif qualified and env is not None:
                # a qualified key must NOT bind to a bare output alias
                # that happens to share the column's name
                full = np.asarray(
                    eval_host(resolve(ob.expr), env, None, None, n))
                col = np.broadcast_to(full, (n,))[idx] \
                    if np.ndim(full) == 0 else full[idx]
            elif name in r.names:
                col = np.asarray(r.column(name))[idx]
            elif env is not None:
                full = np.asarray(
                    eval_host(resolve(ob.expr), env, None, None, n))
                col = np.broadcast_to(full, (n,))[idx] \
                    if np.ndim(full) == 0 else full[idx]
            else:
                raise PlanError(
                    f"ORDER BY {name!r} is not an output column")
            try:
                srt = np.argsort(col, kind="stable")
            except TypeError:  # mixed object dtype (None vs str)
                srt = np.asarray(sorted(
                    range(len(col)),
                    key=lambda i: (col[i] is None, col[i])), dtype=np.int64)
            if not ob.asc:
                srt = srt[::-1]
            idx = idx[srt]
    if sel.distinct and len(idx):
        seen, keep = set(), []
        for i in idx:
            row = tuple(c[i] for c in r.columns)
            if row not in seen:
                seen.add(row)
                keep.append(i)
        idx = np.asarray(keep, dtype=np.int64)
    off = sel.offset or 0
    stop = off + sel.limit if sel.limit is not None else None
    idx = idx[off:stop]
    return QueryResult(r.names, r.dtypes,
                       [np.asarray(c)[idx] for c in r.columns])


def _expr_name(e) -> str:
    if isinstance(e, ast.Column):
        return e.name
    if isinstance(e, ast.FuncCall):
        return f"{e.name}({', '.join(_expr_name(a) for a in e.args)})"
    if isinstance(e, ast.Star):
        return "*"
    if isinstance(e, ast.Literal):
        return str(e.value)
    return str(e)

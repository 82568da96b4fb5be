"""jax-import: storage-only processes must not (non-lazily) import jax.

PR 7 introduced the `sys.modules` seam so `storage/region.py` can
notify the device hot set without ever importing the query layer: a
pure-storage datanode child must never pay jax's import cost (or reach
for the frontend's chip) for work that is all parquet and WAL bytes.

Two rules, both verified over the *top-level* import graph (imports
inside a function are lazy and fine — only module-body imports execute
at import time):

1. Discipline: modules under `storage/`, `objectstore/`, `fault/`,
   `wal` must not top-level import `jax` or a device-layer package
   (`ops`, `parallel`, `query`, `promql`, `flow`, `config`).
2. Reachability: walking the import graph from the storage-only entry
   (`cluster.datanode_main`, function-level imports included — the
   entry imports them unconditionally at runtime), every reachable
   module that top-level imports jax is a finding. The package
   bootstrap (`greptimedb_tpu/__init__.py`) is expected here and
   carries an allowlist entry explaining the platform pin.
"""

from __future__ import annotations

import ast

from greptimedb_tpu.lint import Finding, Repo, checker
from greptimedb_tpu.lint.astutil import toplevel_imports

STORAGE_ONLY_PREFIXES = (
    "greptimedb_tpu/storage/",
    "greptimedb_tpu/objectstore/",
    "greptimedb_tpu/fault/",
)

DEVICE_LAYERS = (
    "greptimedb_tpu.ops", "greptimedb_tpu.parallel",
    "greptimedb_tpu.query", "greptimedb_tpu.promql",
    "greptimedb_tpu.flow", "greptimedb_tpu.config",
)

ENTRY_MODULES = ("greptimedb_tpu.cluster.datanode_main",)


def _imported_modules(stmts) -> set:
    """Absolute module names a list of import statements pulls in."""
    out = set()
    for stmt in stmts:
        if isinstance(stmt, ast.Import):
            for alias in stmt.names:
                out.add(alias.name)
        elif isinstance(stmt, ast.ImportFrom) and stmt.level == 0:
            if stmt.module:
                out.add(stmt.module)
                # `from pkg.mod import name`: `name` may itself be a
                # submodule; walking into it is conservative and only
                # matters for package-internal edges
                for alias in stmt.names:
                    out.add(f"{stmt.module}.{alias.name}")
    return out


def _relative_modules(stmts, module: str) -> set:
    out = set()
    pkg_parts = module.split(".")
    for stmt in stmts:
        if isinstance(stmt, ast.ImportFrom) and stmt.level > 0:
            base = pkg_parts[:len(pkg_parts) - stmt.level + 1] \
                if stmt.level <= len(pkg_parts) else []
            prefix = ".".join(base)
            target = f"{prefix}.{stmt.module}" if stmt.module else prefix
            out.add(target)
            for alias in stmt.names:
                out.add(f"{target}.{alias.name}")
    return out


def build_import_graph(repo: Repo):
    """(edges, jax_importers): top-level import edges between repo
    modules (including implicit parent-package execution), and the set
    of modules whose module body imports jax."""
    modules = repo.modules()
    edges: dict = {}
    jax_importers = set()
    for mod, f in modules.items():
        stmts = list(toplevel_imports(f.tree))
        imported = _imported_modules(stmts) | _relative_modules(stmts, mod)
        targets = set()
        for name in imported:
            if name == "jax" or name.startswith("jax."):
                jax_importers.add(mod)
            # restrict graph edges to repo-internal modules; add the
            # implicit parent-package executions Python performs
            parts = name.split(".")
            for i in range(1, len(parts) + 1):
                prefix = ".".join(parts[:i])
                if prefix in modules and prefix != mod:
                    targets.add(prefix)
        # importing this module executes its own parent packages first
        parts = mod.split(".")
        for i in range(1, len(parts)):
            prefix = ".".join(parts[:i])
            if prefix in modules:
                targets.add(prefix)
        edges[mod] = targets
    return edges, jax_importers


def _entry_roots(repo: Repo, entry: str) -> set:
    """The entry's import closure seed: top-level AND function-level
    imports (the entry main() imports its deps unconditionally)."""
    f = repo.modules().get(entry)
    if f is None:
        return set()
    stmts = [n for n in ast.walk(f.tree)
             if isinstance(n, (ast.Import, ast.ImportFrom))]
    modules = repo.modules()
    roots = {entry}
    for name in _imported_modules(stmts) | _relative_modules(stmts, entry):
        parts = name.split(".")
        for i in range(1, len(parts) + 1):
            prefix = ".".join(parts[:i])
            if prefix in modules:
                roots.add(prefix)
    return roots


@checker("jax-import")
def check(repo: Repo) -> list:
    findings = []
    modules = repo.modules()
    edges, jax_importers = build_import_graph(repo)

    # rule 1: storage-plane modules keep jax + device layers lazy
    for mod, f in modules.items():
        if not f.path.startswith(STORAGE_ONLY_PREFIXES):
            continue
        for stmt in toplevel_imports(f.tree):
            imported = _imported_modules([stmt]) \
                | _relative_modules([stmt], mod)
            for name in sorted(imported):
                if name == "jax" or name.startswith("jax."):
                    findings.append(Finding(
                        "jax-import", f.path, stmt.lineno,
                        f"storage-plane module top-level imports "
                        f"{name} — make it lazy (import inside the "
                        "function) or use the sys.modules seam"))
                elif any(name == d or name.startswith(d + ".")
                         for d in DEVICE_LAYERS):
                    findings.append(Finding(
                        "jax-import", f.path, stmt.lineno,
                        f"storage-plane module top-level imports "
                        f"device layer {name} — storage must stay "
                        "importable without the query/ops stack"))

    # rule 2: nothing reachable from a storage-only entry imports jax
    for entry in ENTRY_MODULES:
        seen = set()
        frontier = list(_entry_roots(repo, entry))
        parent: dict = {m: None for m in frontier}
        while frontier:
            mod = frontier.pop()
            if mod in seen:
                continue
            seen.add(mod)
            for nxt in edges.get(mod, ()):
                if nxt not in seen and nxt not in parent:
                    parent[nxt] = mod
                    frontier.append(nxt)
        for mod in sorted(seen):
            if mod not in jax_importers:
                continue
            chain = [mod]
            cur = parent.get(mod)
            while cur is not None:
                chain.append(cur)
                cur = parent.get(cur)
            via = " <- ".join(chain[:4])
            findings.append(Finding(
                "jax-import", modules[mod].path, 1,
                f"module top-level imports jax and is reachable from "
                f"storage-only entry {entry} (via {via})"))
    return findings

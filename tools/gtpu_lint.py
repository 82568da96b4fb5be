#!/usr/bin/env python
"""gtpu-lint CLI: run the repo-invariant static-analysis suite.

    python tools/gtpu_lint.py --all            # every checker (default)
    python tools/gtpu_lint.py --checker lockdep --checker deadcode
    python tools/gtpu_lint.py --all --json     # machine-readable output
    python tools/gtpu_lint.py --changed-only   # git-diff-scoped (fast
                                               # builder-loop mode)
    python tools/gtpu_lint.py --list           # checker inventory

Exit code 0 = no unallowed findings; 1 = violations (one per line, or a
JSON array with --json). Allowlisted findings (lint_allow.toml) print
with their reason under --verbose and never fail the run. Every run
feeds `greptimedb_tpu_lint_findings_total{checker}` so the dashboard
shows the invariant surface staying green.

Run as a tier-1 test by tests/test_lint.py; see README "Static
analysis & invariants".
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# the lint never computes on a device: keep it off the chip (a chip
# belongs to one process); operators can still override explicitly
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def changed_paths() -> set:
    """Repo-relative paths touched by the working tree + last commit —
    the builder-loop's fast scope."""
    out: set = set()
    for args in (["git", "diff", "--name-only", "HEAD"],
                 ["git", "diff", "--name-only", "HEAD~1", "HEAD"],
                 # brand-new files are invisible to `git diff` — without
                 # this a freshly added module is never linted in fast mode
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            res = subprocess.run(args, cwd=REPO_ROOT, capture_output=True,
                                 text=True, timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            continue
        if res.returncode == 0:
            out.update(line.strip() for line in res.stdout.splitlines()
                       if line.strip())
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--all", action="store_true",
                        help="run every checker (default when no "
                        "--checker is given)")
    parser.add_argument("--checker", action="append", default=[],
                        help="run one checker (repeatable)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit findings as a JSON array")
    parser.add_argument("--changed-only", action="store_true",
                        help="report findings only for files in the "
                        "git diff (HEAD + last commit)")
    parser.add_argument("--list", action="store_true",
                        help="list available checkers and exit")
    parser.add_argument("--verbose", action="store_true",
                        help="also print allowlisted findings")
    args = parser.parse_args(argv)

    from greptimedb_tpu.lint import (
        CHECKERS,
        _import_checkers,
        load_repo,
        run_checkers,
    )

    if args.list:
        _import_checkers()
        for name in sorted(CHECKERS):
            doc = (sys.modules[CHECKERS[name].__module__].__doc__
                   or "").strip().splitlines()[0]
            print(f"{name:12s} {doc}")
        return 0

    names = args.checker or None
    changed = changed_paths() if args.changed_only else None
    repo = load_repo(REPO_ROOT)
    findings = run_checkers(repo, names=names, changed_only=changed)

    # metrics surface: record the per-checker finding count of THIS run
    # (allowed included — the gauge-of-record for "how much is
    # escape-hatched"); a gauge set per run, so re-running in one
    # process overwrites rather than accumulates
    try:
        from greptimedb_tpu.lint import CHECKERS
        from greptimedb_tpu.utils.metrics import LINT_FINDINGS

        seen = {name: 0 for name in (names or sorted(CHECKERS))}
        for f in findings:
            seen[f.checker] = seen.get(f.checker, 0) + 1
        for checker_name, count in sorted(seen.items()):
            LINT_FINDINGS.set(float(count), checker=checker_name)
    except Exception:  # noqa: BLE001 — metrics must never fail the lint
        pass

    failures = [f for f in findings if not f.allowed]
    if args.as_json:
        print(json.dumps([f.as_json() for f in findings
                          if not f.allowed or args.verbose], indent=2))
    else:
        for f in findings:
            if f.allowed and not args.verbose:
                continue
            print(f.render())
        allowed = sum(1 for f in findings if f.allowed)
        print(f"gtpu-lint: {len(failures)} finding(s), "
              f"{allowed} allowlisted")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

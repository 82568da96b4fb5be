#!/usr/bin/env python3
"""Reduce a profiler trace (.xplane.pb) to device busy time, the top
device operations and the longest idle gaps.

Runs as a child pinned to JAX_PLATFORMS=cpu after the server has exited
(jax.profiler.ProfileData needs jax, and the harness process itself
never imports it). Prints one JSON object.

A device plane is one whose name starts with "/device:" (TPU:n). On it,
the line "XLA Ops" holds one event per executed HLO operation (fusions,
custom calls, copies); where a plane has no such line, every line but
the step/module summaries counts. Busy time is the UNION of the op
intervals on a plane (ops overlap across lines), averaged over the
planes that ran anything; the traced window is the span from the first
to the last event over all planes, or the host-clock span the harness
measured where that is given and longer.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
                 "Framework Ops", "Source code")


def union(intervals: list) -> tuple:
    """(total covered length, merged intervals) of [(start, end), ...]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def op_lines(plane) -> list:
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    if named:
        return named
    return [ln for ln in lines if ln.name not in SUMMARY_LINES]


def reduce_planes(planes: list, window_ns: float | None = None,
                  top: int = 10, gaps: int = 5) -> dict:
    """planes: [(name, [(op name, start_ns, duration_ns), ...]), ...]."""
    busy, by_op, merged_all = [], {}, []
    t_min, t_max = None, None
    for _name, events in planes:
        if not events:
            continue
        total, merged = union([(s, s + d) for _n, s, d in events])
        busy.append(total)
        merged_all.append(merged)
        for n, _s, d in events:
            by_op[n] = by_op.get(n, 0.0) + d
        lo = min(s for _n, s, _d in events)
        hi = max(s + d for _n, s, d in events)
        t_min = lo if t_min is None else min(t_min, lo)
        t_max = hi if t_max is None else max(t_max, hi)
    if not busy:
        return {"busy_s": 0.0, "window_s": (window_ns or 0.0) / 1e9,
                "device_ops": [], "idle_gaps": [], "planes": 0}
    span = t_max - t_min
    window = max(span, window_ns or 0.0)
    # idle gaps of the first device plane that ran anything
    m = merged_all[0]
    gap_list = [(m[i + 1][0] - m[i][1], m[i][1] - t_min)
                for i in range(len(m) - 1)]
    gap_list.sort(reverse=True)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    n_planes = len(busy)
    return {
        "busy_s": sum(busy) / n_planes / 1e9,
        "window_s": window / 1e9,
        "span_s": span / 1e9,
        "planes": n_planes,
        # today's names are whole HLO instructions: keep their head
        "device_ops": [[n[:160], d / n_planes / 1e9] for n, d in ops],
        "idle_gaps": [[f"device_idle@+{off / 1e6:.1f}ms", g / 1e9]
                      for g, off in gap_list[:gaps]],
    }


def read_xplane(path: str) -> tuple:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes, seen = [], []
    for plane in pd.planes:
        seen.append({"plane": plane.name,
                     "lines": [ln.name for ln in plane.lines]})
        if not plane.name.startswith("/device:") \
                or plane.name.startswith("/device:CUSTOM"):
            continue
        events = []
        for ln in op_lines(plane):
            for ev in ln.events:
                if ev.duration_ns > 0:
                    events.append((ev.name, float(ev.start_ns),
                                   float(ev.duration_ns)))
        planes.append((plane.name, events))
    return planes, seen


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--window-s", type=float, default=0.0)
    ap.add_argument("--parent", type=int, default=0,
                    help="the harness's pid: this process dies with it")
    args = ap.parse_args()
    if args.parent:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        from benchmark.harness import procs

        procs.die_with(args.parent)
    path = args.trace if args.trace.endswith(".pb") \
        else find_xplane(args.trace)
    if not path:
        print(json.dumps({"error": f"no .xplane.pb under {args.trace}"}))
        return 1
    planes, seen = read_xplane(path)
    out = reduce_planes(planes, args.window_s * 1e9 or None)
    out["seen"] = seen
    out["xplane_bytes"] = os.path.getsize(path)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

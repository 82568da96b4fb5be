#!/usr/bin/env python3
"""Reduce a profiler trace (.xplane.pb) to device busy time, device time
per named kernel, and the longest idle gaps named by what the host was
doing in each.

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

The line "XLA Modules" holds one event per executed program, named
`jit_<name>(<fingerprint>)`: `<name>` is the name
`device_telemetry.kernel_name` gave a jitted step or Pallas kernel, or
the jnp function of an eager operation (`cumsum`). `kernels` folds those
events by `<name>`: seconds, runs, and the HLO ops that ran inside them.
A module event spans from its first op to its last, the idle between
them included, so the kernels' seconds sum to MORE than the ops' union
(`busy_s`); ops outside every module event fold under `no_module`.

The host plane "/host:CPU" has one line per thread. With the host
tracer on, a span of the program (utils/tracing.py) is an event there
with the stats `trace_id` and `span_id`, on the device planes' clock. An
idle gap is named by the stage and compile spans open in it, folded by
name and ordered by the time they were open: `scan+compile(agg_block)`
(a compile is named after the `PjitFunction(<fn>)` event that encloses
it on its thread), `host_agg`, or `none_open`.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys

SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
                 "Framework Ops", "Source code")
#: the flat stage vocabulary of the program's utils/tracing.py (PERF.md
#: section 3) and its compile listener's spans: what names a gap
STAGE_NAMES = frozenset((
    "parse", "plan", "fast_bind", "admission_wait", "scan", "host_agg",
    "upload", "device", "readback", "assemble", "encode", "send",
    "compile", "compile_cache_load"))
_MODULE_RE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_PJIT_RE = re.compile(r"^PjitFunction\((.*)\)$")
NO_MODULE = "no_module"


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


def kernel_of(module_name: str) -> str:
    """`jit_agg_scan_prepared(1672881...)` -> `agg_scan_prepared`."""
    return _MODULE_RE.match(module_name).group(1)


def op_lines(plane) -> list:
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    if named:
        return named
    return [ln for ln in lines if ln.name not in SUMMARY_LINES]


def reduce_planes(planes: list, window_ns: float | None = None,
                  top: int = 10, gaps: int = 5) -> dict:
    """planes: [(name, [(op name, start_ns, duration_ns), ...]), ...].
    `gaps` are (start_ns, length_ns) of the longest idle gaps between
    the merged op intervals of the first plane that ran anything."""
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
                "hlo_ops": [], "gaps": [], "planes": 0, "t_min_ns": 0.0}
    span = t_max - t_min
    window = max(span, window_ns or 0.0)
    m = merged_all[0]
    gap_list = [(m[i + 1][0] - m[i][1], m[i][1])
                for i in range(len(m) - 1)]
    gap_list.sort(reverse=True)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    n_planes = len(busy)
    return {
        "busy_s": sum(busy) / n_planes / 1e9,
        "window_s": window / 1e9,
        "span_s": span / 1e9,
        "planes": n_planes,
        "t_min_ns": t_min,
        # whole HLO instructions: keep their head (the shapes are in it)
        "hlo_ops": [[n[:160], d / n_planes / 1e9] for n, d in ops],
        "gaps": [(start, g) for g, start in gap_list[:gaps]],
    }


def fold_kernels(planes: list, modules: list, n_planes: int,
                 ops_kept: int = 5) -> list:
    """Per kernel name: seconds and runs of its module events (a chip's
    mean) and the heads of the HLO ops that ran inside them, longest
    first. `modules`: [(plane name, [(module name, start, duration)])]."""
    by_plane = dict(modules)
    out: dict = {}

    def slot(name: str) -> dict:
        return out.setdefault(name, {"kernel": name, "seconds": 0.0,
                                     "runs": 0.0, "op_seconds": 0.0,
                                     "_ops": {}})

    for plane, events in planes:
        mods = sorted((s, s + d, kernel_of(n))
                      for n, s, d in by_plane.get(plane, []))
        for s, e, name in mods:
            k = slot(name)
            k["seconds"] += (e - s) / n_planes / 1e9
            k["runs"] += 1.0 / n_planes
        starts = [s for s, _e, _n in mods]
        for n, s, d in events:
            i = bisect.bisect_right(starts, s) - 1
            k = slot(mods[i][2] if i >= 0 and s < mods[i][1] else NO_MODULE)
            k["op_seconds"] += d / n_planes / 1e9
            k["_ops"][n] = k["_ops"].get(n, 0.0) + d / n_planes / 1e9
    for k in out.values():
        ops = sorted(k.pop("_ops").items(), key=lambda kv: -kv[1])
        k["ops"] = [[n[:160], d] for n, d in ops[:ops_kept]]
    return sorted(out.values(),
                  key=lambda k: -(k["seconds"] or k["op_seconds"]))


def name_gap(start: float, length: float, spans: list, pjits: dict,
             names: int = 3) -> tuple:
    """(name, {span name: ms open}) of one idle gap."""
    end, open_ms = start + length, {}
    for sp in spans:
        ov = min(end, sp["start_ns"] + sp["duration_ns"]) \
            - max(start, sp["start_ns"])
        if ov <= 0:
            continue
        name = sp["name"]
        if name == "compile":
            fn = enclosing_pjit(sp, pjits)
            name = f"compile({fn})" if fn else name
        open_ms[name] = open_ms.get(name, 0.0) + ov / 1e6
    by_time = sorted(open_ms, key=lambda n: -open_ms[n])
    return "+".join(by_time[:names]) or "none_open", open_ms


def enclosing_pjit(sp: dict, pjits: dict) -> str | None:
    """The function of the innermost `PjitFunction(<fn>)` event open on
    the span's thread when it started."""
    best = None
    for s, e, fn in pjits.get(sp["line"], []):
        if s <= sp["start_ns"] < e and (best is None or s > best[0]):
            best = (s, fn)
    return best[1] if best else None


def reduce_trace(tr: dict, window_ns: float | None = None,
                 top: int = 10) -> dict:
    """`tr` as read_xplane gives it."""
    out = reduce_planes(tr["planes"], window_ns, top=top)
    t_min = out.pop("t_min_ns")
    kernels = fold_kernels(tr["planes"], tr["modules"],
                           max(out["planes"], 1))
    named = []
    for start, length in out.pop("gaps"):
        name, open_ms = name_gap(start, length, tr["spans"], tr["pjits"])
        named.append({"name": name, "idle_s": length / 1e9,
                      "at_ms": (start - t_min) / 1e6, "open_ms": open_ms})
    out["kernels"] = kernels
    out["gaps"] = named
    out["stage_spans"] = len(tr["spans"])
    # the driver's breakdown: kernels by name, gaps by the spans open
    out["device_ops"] = [[k["kernel"], k["seconds"] or k["op_seconds"]]
                         for k in kernels[:top]]
    out["idle_gaps"] = [[g["name"], g["idle_s"]] for g in named]
    return out


def read_xplane(path: str) -> dict:
    """{"planes": [(plane, ops)], "modules": [(plane, module events)],
    "spans": [...], "pjits": {host line: [(start, end, fn)]}, "seen"}
    with ops and module events as (name, start_ns, duration_ns)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes, modules, spans, pjits, seen = [], [], [], {}, []
    for plane in pd.planes:
        seen.append({"plane": plane.name,
                     "lines": [ln.name for ln in plane.lines]})
        if plane.name == "/host:CPU":
            for i, ln in enumerate(plane.lines):
                for ev in ln.events:
                    if ev.name in STAGE_NAMES:
                        stats = dict(ev.stats)
                        if "span_id" in stats:
                            spans.append({
                                "name": ev.name, "line": i,
                                "start_ns": float(ev.start_ns),
                                "duration_ns": float(ev.duration_ns)})
                        continue
                    m = _PJIT_RE.match(ev.name)
                    if m:
                        pjits.setdefault(i, []).append(
                            (float(ev.start_ns),
                             float(ev.start_ns + ev.duration_ns),
                             m.group(1)))
            continue
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
        modules.append((plane.name, [
            (ev.name, float(ev.start_ns), float(ev.duration_ns))
            for ln in plane.lines if ln.name == "XLA Modules"
            for ev in ln.events]))
    return {"planes": planes, "modules": modules, "spans": spans,
            "pjits": pjits, "seen": seen}


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
    tr = read_xplane(path)
    out = reduce_trace(tr, args.window_s * 1e9 or None)
    out["seen"] = tr["seen"]
    out["xplane_bytes"] = os.path.getsize(path)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

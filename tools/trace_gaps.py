#!/usr/bin/env python3
"""Reduce a device profile (`GET /debug/pprof/device`, or any
jax.profiler trace taken with the host tracer on) to: device busy / idle,
device time per named kernel, and for the longest device-idle gaps the
program's spans that were open during each.

    python tools/trace_gaps.py <trace dir | file.xplane.pb> [--gaps 5] [--json]

Reads the `.xplane.pb` with `jax.profiler.ProfileData`, pinned to the CPU
(the chip belongs to the server). What it relies on, as recorded on a
TPU v5e with jax 0.9 (PERF.md section 3):

- a device plane is named `/device:TPU:<n>`; its line `XLA Ops` holds one
  event per executed HLO operation (busy time = the union of their
  intervals) and its line `XLA Modules` one event per executed program,
  named `jit_<kernel name>(<fingerprint>)` — the name
  `device_telemetry.kernel_name` gave the jitted step. A program that is
  not one of the named steps (an eager jnp operation) is reported under
  its own module name.
- the host plane `/host:CPU` has one line per thread; a span of this
  program (`utils/tracing.py`) is an event carrying the stats `trace_id`
  and `span_id`: stage spans, `compile` spans, statement and request
  roots. Device and host planes share the profiler's clock.

A trace recorded on the CPU backend has no device plane: there the XLA
CPU client's executor threads (`tf_XLAPjRtCpuClient/*`) stand in for the
device, so the tool can be tried (and is tested) without a chip.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

#: the flat stage vocabulary of utils/tracing.py (PERF.md section 3) and
#: the compile listener's spans: what a gap is attributed to first
STAGE_NAMES = frozenset((
    "parse", "plan", "fast_bind", "admission_wait", "scan", "host_agg",
    "upload", "device", "readback", "assemble", "encode", "send",
    "compile", "compile_cache_load"))

SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
                 "Framework Ops", "Source code")
_MODULE_RE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def union(intervals: list) -> tuple:
    """(covered length, merged [start, end] list) of (start, end) pairs."""
    merged: list = []
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


def read_xplane(path: str) -> dict:
    """{"device": [(plane, ops, modules)], "spans": [...]} with ops and
    modules as (name, start_ns, duration_ns) and spans as dicts."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, cpu_stand_in, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") \
                and not plane.name.startswith("/device:CUSTOM"):
            lines = list(plane.lines)
            op_lines = [ln for ln in lines if ln.name == "XLA Ops"] or \
                [ln for ln in lines if ln.name not in SUMMARY_LINES]
            ops = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                   for ln in op_lines for ev in ln.events
                   if ev.duration_ns > 0]
            modules = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                       for ln in lines if ln.name == "XLA Modules"
                       for ev in ln.events]
            device.append((plane.name, ops, modules))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                stand_in = ln.name.startswith("tf_XLAPjRtCpuClient")
                for ev in ln.events:
                    if stand_in:
                        if ev.duration_ns > 0:
                            cpu_stand_in.append((ev.name, float(ev.start_ns),
                                                 float(ev.duration_ns)))
                        continue
                    stats = dict(ev.stats)
                    if "span_id" in stats:
                        spans.append({
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "duration_ns": float(ev.duration_ns),
                            "trace_id": str(stats.get("trace_id", "")),
                            "span_id": str(stats["span_id"]),
                            "thread": ln.name})
    if not device and cpu_stand_in:
        device.append(("/host:CPU tf_XLAPjRtCpuClient (no device plane)",
                       cpu_stand_in, []))
    return {"device": device, "spans": spans}


def reduce_trace(trace: dict, gaps: int = 5, per_gap: int = 8) -> dict:
    """Busy / idle of the first device plane that ran anything, time per
    kernel over all device planes, and the `gaps` longest idle gaps of
    that first plane with the spans open in each (folded by name)."""
    planes = [(n, ops, mods) for n, ops, mods in trace["device"] if ops]
    spans = trace["spans"]
    if not planes:
        return {"planes": 0, "busy_s": 0.0, "window_s": 0.0,
                "idle_share": None, "kernels": [], "gaps": [],
                "program_spans": len(spans)}
    name, ops, _ = planes[0]
    busy, merged = union([(s, s + d) for _n, s, d in ops])
    lo, hi = merged[0][0], merged[-1][1]
    # the window is what the host spans and the device events cover
    # together: a device that ran nothing at its ends was idle there
    if spans:
        lo = min(lo, min(sp["start_ns"] for sp in spans))
        hi = max(hi, max(sp["start_ns"] + sp["duration_ns"]
                         for sp in spans))
    edges = [[lo, lo]] + merged + [[hi, hi]]
    gap_list = sorted(
        ((edges[i + 1][0] - edges[i][1], edges[i][1])
         for i in range(len(edges) - 1)), reverse=True)
    out_gaps = []
    for length, start in gap_list[:gaps]:
        if length <= 0:
            break
        end = start + length
        # spans open in the gap, folded by name (four clients run the
        # same stage side by side): summed overlap, how many, and the
        # trace of the one that overlaps most
        by_name: dict = {}
        for sp in spans:
            ov = min(end, sp["start_ns"] + sp["duration_ns"]) \
                - max(start, sp["start_ns"])
            if ov <= 0:
                continue
            o = by_name.setdefault(sp["name"], {
                "name": sp["name"], "spans": 0, "overlap_ms": 0.0,
                "longest_ms": 0.0, "trace_id": ""})
            o["spans"] += 1
            o["overlap_ms"] += ov / 1e6
            if ov / 1e6 > o["longest_ms"]:
                o["longest_ms"], o["trace_id"] = ov / 1e6, sp["trace_id"]
        # stage and compile spans first: the enclosing roots and
        # statements say less about what the host was doing
        open_spans = sorted(by_name.values(), key=lambda o: (
            o["name"] not in STAGE_NAMES, -o["overlap_ms"]))
        out_gaps.append({"at_ms": (start - lo) / 1e6,
                         "idle_ms": length / 1e6,
                         "spans_open": open_spans[:per_gap]})
    kernels: dict = {}
    for _n, p_ops, mods in planes:
        for m_name, _s, d in mods:
            k = kernels.setdefault(kernel_of(m_name), [0.0, 0])
            k[0] += d
            k[1] += 1
    window = hi - lo
    return {
        "planes": len(planes), "plane": name,
        "busy_s": busy / 1e9, "window_s": window / 1e9,
        "idle_share": 1.0 - busy / window if window > 0 else None,
        "kernels": [{"kernel": k, "device_ms": v[0] / 1e6, "runs": v[1]}
                    for k, v in sorted(kernels.items(),
                                       key=lambda kv: -kv[1][0])],
        "gaps": out_gaps, "program_spans": len(spans)}


def render(red: dict, top_kernels: int = 20) -> str:
    if not red["planes"]:
        return (f"no device operation in the trace "
                f"({red['program_spans']} program spans)\n")
    lines = [f"plane {red['plane']} (of {red['planes']}): busy "
             f"{red['busy_s'] * 1e3:.3f} ms of {red['window_s'] * 1e3:.3f} "
             f"ms, idle {red['idle_share'] * 100:.2f}%; "
             f"{red['program_spans']} program spans on the host plane",
             "", "device time per kernel (XLA Modules line):"]
    for k in red["kernels"][:top_kernels]:
        lines.append(f"  {k['device_ms']:12.3f} ms  {k['runs']:6d} runs  "
                     f"{k['kernel']}")
    if not red["kernels"]:
        lines.append("  (no XLA Modules line)")
    lines += ["", "longest device-idle gaps, with the spans open in each:"]
    for g in red["gaps"]:
        lines.append(f"  idle {g['idle_ms']:.3f} ms at +{g['at_ms']:.3f} ms")
        for o in g["spans_open"]:
            lines.append(f"      {o['name']:<28} {o['overlap_ms']:10.3f} ms in "
                         f"{o['spans']:3d} spans, longest "
                         f"{o['longest_ms']:.3f} ms ("
                         f"{o['longest_ms'] / g['idle_ms'] * 100:5.1f}% of "
                         f"the gap) trace {o['trace_id']}")
        if not g["spans_open"]:
            lines.append("      (no program span open: the server was idle, "
                         "or the host tracer was off)")
    return "\n".join(lines) + "\n"


def find_xplane(path: str) -> str | None:
    if path.endswith(".pb"):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--gaps", type=int, default=5)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    # the chip belongs to the server: read the trace on the CPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    path = find_xplane(args.trace)
    if not path or not os.path.exists(path):
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    red = reduce_trace(read_xplane(path), gaps=args.gaps)
    sys.stdout.write(json.dumps(red) + "\n" if args.json else render(red))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The benchmark's launcher for the serving process.

Calls the program's normal entry (`greptimedb_tpu standalone start`, the
arguments chip_smoke.Server gives it) in this process, and beside it
runs one control thread that the harness talks to through this
process's stdin — the only way to bracket a profiler trace around part
of the measured window without editing the program, since only the
process that holds the chip can trace it. Commands, one per line,
`<seq> <command> [argument]`:

    trace_start <dir>   jax.profiler.start_trace(dir): python tracer off,
                        host tracer at level 1, the lowest that records
                        the program's spans (each a TraceAnnotation)
                        beside the device planes, on their clock
    trace_stop          jax.profiler.stop_trace()
    memory              per-device allocator stats
    memtable            bytes each open region's memtable holds (the
                        program exposes no gauge of it; a run with a
                        writer reads it when its window has closed)

Each is answered by `<control-dir>/<seq>.json`. With `--trace 0` the
harness sends only `memory` (and `memtable` under a writer).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


def _answer(control_dir: str, seq: str, out: dict) -> None:
    tmp = os.path.join(control_dir, f"{seq}.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, os.path.join(control_dir, f"{seq}.json"))


def control_loop(control_dir: str) -> None:
    for line in sys.stdin:
        parts = line.split()
        if len(parts) < 2:
            continue
        seq, cmd, arg = parts[0], parts[1], parts[2:]
        out: dict = {"command": cmd}
        try:
            import jax

            if cmd == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                t0 = time.time()
                jax.profiler.start_trace(arg[0], profiler_options=opts)
                out.update(t_call=t0, t_started=time.time())
            elif cmd == "trace_stop":
                t0 = time.time()
                jax.profiler.stop_trace()
                out.update(t_call=t0, t_stopped=time.time())
            elif cmd == "memory":
                out["devices"] = [
                    {"id": d.id, **{k: v for k, v in
                                    (d.memory_stats() or {}).items()
                                    if isinstance(v, (int, float))}}
                    for d in jax.local_devices()]
            elif cmd == "memtable":
                import gc

                from greptimedb_tpu.storage import RegionEngine

                out["regions"] = {
                    str(rid): int(region.memtable_bytes)
                    for eng in gc.get_objects()
                    if isinstance(eng, RegionEngine)
                    for rid, region in list(eng.regions.items())}
            else:
                out["error"] = f"unknown command {cmd!r}"
        except Exception as e:  # noqa: BLE001 — reported to the harness
            out["error"] = f"{type(e).__name__}: {e}"
        _answer(control_dir, seq, out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", type=int, default=0,
                    help="the harness's pid: this process dies with it")
    ap.add_argument("--control-dir", required=True)
    ap.add_argument("--data-home", required=True)
    ap.add_argument("--http-addr", required=True)
    args = ap.parse_args()
    if args.parent:
        from benchmark.harness import procs

        procs.die_with(args.parent)
    threading.Thread(target=control_loop, args=(args.control_dir,),
                     daemon=True).start()
    from greptimedb_tpu.cli import main as program_main

    program_main(["standalone", "start", "--data-home", args.data_home,
                  "--http-addr", args.http_addr])


if __name__ == "__main__":
    main()

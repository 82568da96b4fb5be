"""Reader `kernel`: device time of one named program, from the `kernels`
table of benchmark/harness/trace_reduce.py — the `XLA Modules` events
of the traced part of the window, `jit_<name>(<fingerprint>)` folded by
`<name>` (the name `device_telemetry.kernel_name` gave a jitted step or
Pallas kernel; an eager jnp operation is a program of its own,
`cumsum`).

args: {"kernel": "<name>", "stat": ...}
  ms_per_query     the kernel's seconds as a share of the traced window
                   over the rate of correct answers in the whole window
                   (the arithmetic of `trace`'s busy_ms_per_query)
  ms_per_run       its seconds / its runs in the traced window; nothing
                   where it did not run
  runs_per_query   its runs per second of traced window over that rate
Nothing to read in an untraced run. A traced window in which the kernel
did not run reads 0.0 and is not left out: a later PR that fuses a
kernel away leaves a zero, not a hole.
"""

from benchmark.harness.common import load_module


def read(ctx, args: dict):
    tr = ctx.trace
    if not tr or not tr.get("window_s"):
        return None
    k = next((k for k in tr.get("kernels", [])
              if k["kernel"] == args["kernel"]), {"seconds": 0.0, "runs": 0})
    stat = args["stat"]
    if stat == "ms_per_run":
        return 1e3 * k["seconds"] / k["runs"] if k["runs"] else None
    if stat not in ("ms_per_query", "runs_per_query"):
        raise KeyError(f"kernel reader: no stat {stat!r}")
    # correct answers a second over the whole window, as `client` counts
    rate = load_module("readers", "client").read(ctx, {"stat": "rate"})
    if not rate:
        return None
    in_trace = k["seconds"] * 1e3 if stat == "ms_per_query" else k["runs"]
    return in_trace / tr["window_s"] / rate

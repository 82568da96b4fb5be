"""Reader `trace`: numbers reduced from the profiler's device planes
(benchmark/harness/trace_reduce.py) of the traced part of the window.

args: {"stat": "busy_ms_per_query" | "idle_share"}
  idle_share          1 - busy / traced window, in percent
  busy_ms_per_query   the device's busy share of the traced window over
                      the rate of correct answers in the whole window:
                      requests here take seconds, so the traced 5 s may
                      see none complete, and a count of those would not
                      always exist
Nothing to read in an untraced run.
"""


def read(ctx, args: dict):
    tr = ctx.trace
    if not tr or not tr.get("window_s"):
        return None
    stat = args["stat"]
    if stat == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if stat == "busy_ms_per_query":
        t_end = ctx.t0 + ctx.seconds
        done = sum(1 for r in ctx.requests if r.ok and r.t_done <= t_end)
        if not done:
            return None
        return 1e3 * (tr["busy_s"] / tr["window_s"]) * ctx.seconds / done
    raise KeyError(f"trace reader: no stat {stat!r}")

"""Reader `client`: numbers the harness's own clients measured.

args: {"stat": ..., "template": "<name>"?}
  p50 | p95          latency percentile over every request started in the
                     window (of one template where `template` is given);
                     a p95 from fewer than 200 samples is the highest
                     percentile with ten samples beyond it, and the run
                     says so on an earlier line
  rate               correct answers completed inside the window / its
                     length
  setup_s            start of process to the first timed request
  wire_ms            median of (client latency - the response's own
                     execution_time_ms); nothing where no response
                     carries a server time
"""

from benchmark.harness import stats


def read(ctx, args: dict):
    stat = args["stat"]
    if stat == "setup_s":
        return ctx.setup_s
    reqs = [r for r in ctx.requests
            if "template" not in args or r.entry.name == args["template"]]
    if stat == "rate":
        t_end = ctx.t0 + ctx.seconds
        return sum(1 for r in reqs if r.ok and r.t_done <= t_end) \
            / ctx.seconds
    if stat in ("p50", "p95"):
        ms = [r.ms for r in reqs if r.ok]
        if not ms:
            return None
        q = {"p50": 0.5, "p95": 0.95}[stat]
        value, q_eff = stats.tail(ms, q)
        ctx.note(f"{args.get('template', 'all')} {stat}: {len(ms)} samples"
                 + ("" if q_eff == q else
                    f"; too few for {stat}, reporting p{q_eff * 100:.1f} "
                    "(ten samples beyond it)"))
        return value
    if stat == "wire_ms":
        gaps = [r.ms - r.server_ms for r in reqs
                if r.ok and r.server_ms is not None]
        return stats.percentile(gaps, 0.5) if gaps else None
    raise KeyError(f"client reader: no stat {stat!r}")

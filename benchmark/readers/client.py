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

and, of a mix's writer (nothing where the mix has none):
  ingest_rows_per_s  rows acknowledged inside the window / its length:
                     did the pace hold
  write_ack_ms_p50   median of a batch's send to its 2xx
  ack_to_read_ms_p95 tail of the wait from a batch's 2xx to the answer
                     of its read-after-acknowledge check (the p95 rule
                     above)
"""

from benchmark.harness import stats


def read(ctx, args: dict):
    stat = args["stat"]
    if stat == "setup_s":
        return ctx.setup_s
    if stat in ("ingest_rows_per_s", "write_ack_ms_p50",
                "ack_to_read_ms_p95"):
        return _writer(ctx, stat)
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
        return _tail(ctx, f"{args.get('template', 'all')} {stat}", ms,
                     {"p50": 0.5, "p95": 0.95}[stat], "samples")
    if stat == "wire_ms":
        gaps = [r.ms - r.server_ms for r in reqs
                if r.ok and r.server_ms is not None]
        return stats.percentile(gaps, 0.5) if gaps else None
    raise KeyError(f"client reader: no stat {stat!r}")


def _tail(ctx, label: str, values: list, q: float, unit: str) -> float:
    """The p95 rule above; the run's notes say what was reported."""
    value, q_eff = stats.tail(values, q)
    ctx.note(f"{label}: {len(values)} {unit}"
             + ("" if q_eff == q else
                f"; too few for p{q * 100:.0f}, reporting "
                f"p{q_eff * 100:.1f} (ten samples beyond it)"))
    return value


def _writer(ctx, stat: str):
    w = getattr(ctx, "writer", None)
    if w is None:
        return None
    if stat == "ingest_rows_per_s":
        return w.stats(ctx.t0, ctx.seconds)["rows_per_s_achieved"]
    if stat == "write_ack_ms_p50":
        ms = [b.ms for b in w.batches if b.ok and b.rows]
        return stats.percentile(ms, 0.5) if ms else None
    waits = [c.ms for c in w.checks if c.error is None]
    if not waits:
        return None
    return _tail(ctx, "ack_to_read p95", waits, 0.95, "checks")

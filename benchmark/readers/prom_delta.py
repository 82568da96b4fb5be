"""Reader `prom_delta`: a counter's increase over the window, from the
two /metrics scrapes that bracket it.

args: {"num": [{"metric": "...", "labels": {...}}, ...],
       "den": "requests" | "window" | [{"metric": ..., "labels": ...}, ...],
       "scale": 1.0}
value = scale * sum(delta of num) / sum(delta of den). A series that the
exposition does not carry yet counts as 0. Nothing to read (None) where
the denominator is 0. `"window"` divides by 1: the increase itself, a
count (or seconds) per window.
"""

from benchmark.harness.wire import metric_sum


def _delta(ctx, series: list) -> float:
    return sum(metric_sum(ctx.m1, s["metric"], s.get("labels"))
               - metric_sum(ctx.m0, s["metric"], s.get("labels"))
               for s in series)


def read(ctx, args: dict):
    den = args.get("den", "requests")
    d = float(len(ctx.requests)) if den == "requests" \
        else 1.0 if den == "window" else _delta(ctx, den)
    if d <= 0:
        return None
    return float(args.get("scale", 1.0)) * _delta(ctx, args["num"]) / d

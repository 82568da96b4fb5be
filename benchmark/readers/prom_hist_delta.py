"""Reader `prom_hist_delta`: time a histogram observed over the window.

args: {"metric": "<histogram base name>", "labels": [{...}, ...],
       "per": "requests" | "observations", "scale": 1000.0}
value = scale * (increase of <metric>_sum over the listed label sets)
/ (requests in the window, or the increase of <metric>_count). Nothing
to read where the histogram observed nothing in the window.
"""

from benchmark.harness.wire import metric_sum


def read(ctx, args: dict):
    def delta(suffix: str) -> float:
        name = args["metric"] + suffix
        return sum(metric_sum(ctx.m1, name, ls) - metric_sum(ctx.m0, name, ls)
                   for ls in args.get("labels", [{}]))

    observed = delta("_count")
    if observed <= 0:
        return None
    d = float(len(ctx.requests)) if args.get("per", "requests") == "requests" \
        else observed
    if d <= 0:
        return None
    return float(args.get("scale", 1.0)) * delta("_sum") / d

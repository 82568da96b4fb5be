"""Reader `trace_planes`: how many device planes ran anything in the
traced part of the window — `planes` of benchmark/harness/
trace_reduce.py's reduction (`reduce_planes` counts the planes that hold
an operation), which the `trace` reader does not expose. On a cell whose
table's regions compute on a chip each it says how many chips worked;
`device_idle_share` there is the MEAN over those planes.

args: {} (none). Nothing to read in an untraced run.
"""


def read(ctx, args: dict):
    tr = ctx.trace
    if not tr or not tr.get("window_s"):
        return None
    return float(tr.get("planes", 0))

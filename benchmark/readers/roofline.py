"""Reader `roofline`: a kernel's share of its roofline, from the
`kernels` table of benchmark/harness/trace_reduce.py and the chip's
published peaks (benchmark/peaks.json).

args: {"kernel": "<name>", "cost": "<file under benchmark/costs/>", ...}
value = 100 * max(operations / peak FLOP/s, bytes / peak HBM bytes/s)
        / (the kernel's seconds / its runs in the traced window)
where (operations, bytes) = costs/<cost>.py `cost(ops, **the other
args)`: what one run needs, computed from shapes. The FLOP/s peak is the
table's highest (bfloat16: the chip has no float64 unit, and the
comparison flatters the kernel).

The peaks are those of the serving process's own `device_kind`, which
the program states in `greptimedb_tpu_device_info{platform,
device_kind}`: a kind peaks.json lacks is an error, never a default.
Nothing to read (None) in an untraced run, where the traced window did
not run the kernel, where the program does not state its device, or on a
CPU (a rehearsal): a CPU's time is no share of a chip's peak.
"""

from benchmark.harness.common import load_json, load_module

DEVICE_INFO = "greptimedb_tpu_device_info"


def device(m: dict):
    """(platform, device_kind) of the serving process, or None."""
    for (name, labels), _ in m.items():
        if name == DEVICE_INFO:
            ls = dict(labels)
            return ls.get("platform"), ls.get("device_kind")
    return None


def read(ctx, args: dict):
    tr = ctx.trace
    if not tr or not tr.get("window_s"):
        return None
    k = next((k for k in tr.get("kernels", [])
              if k["kernel"] == args["kernel"]), None)
    if k is None or not k["runs"] or not k["seconds"]:
        return None
    dev = device(ctx.m1)
    if dev is None or dev[0] == "cpu":
        return None
    peaks = load_json("peaks.json").get(dev[1])
    if peaks is None:
        raise KeyError(f"benchmark/peaks.json has no device_kind {dev[1]!r}")
    extra = {a: v for a, v in args.items() if a not in ("kernel", "cost")}
    operations, nbytes = load_module("costs", args["cost"]).cost(
        k.get("ops", []), **extra)
    least_s = max(operations / max(peaks["flops_per_s"].values()),
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (k["seconds"] / k["runs"])

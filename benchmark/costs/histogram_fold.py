"""What one run of the kernel `histogram_fold` (greptimedb_tpu/ops/
histogram.py) has to do, from its shapes alone: `counts` [G, B, T]
cumulative bucket counts, `bounds` and `valid` [G, B], the quantile, ->
[G, T], everything float64 (emulated on the chip as pairs of float32:
eight bytes a value all the same).

Bytes: every input read once, the result written once; what the program
keeps between its passes over the block counts for nothing, as for a
roofline it should. Operations: over the [G, B, T] block a replacement
of absent counts, the running maximum along `le` and the comparison
with the rank (three a value); per (group, step) the rank, the bucket's
pick, two bounds and two counts gathered, the interpolation and the six
selections of the edge rules (twenty). An emulated float64 operation is
counted as one: against the chip's published peak that flatters the
kernel, and it is the bytes that bound it.
"""

from __future__ import annotations

F64 = 8
PER_VALUE = 3
PER_RESULT = 20


def run_cost(groups: int, buckets: int, steps: int) -> tuple:
    """(operations, bytes) of one run over a [groups, buckets, steps]
    block."""
    block = groups * buckets * steps
    operations = PER_VALUE * block + PER_RESULT * groups * steps
    nbytes = F64 * block + (F64 + 1) * groups * buckets + F64 \
        + F64 * groups * steps
    return operations, nbytes


def cost(ops: list, shapes: list | None = None) -> tuple:
    """(operations, bytes) of a mean run. `shapes`: the [G, B, T] the
    cell's panels fold, equally often (the configuration's sizes; the
    kernels table folds every shape under the kernel's one name, so its
    seconds / runs is a mean over them too). `ops`, the kernel's HLO
    heads, are not read."""
    if not shapes:
        raise ValueError("histogram_fold's cost needs the panels' shapes")
    runs = [run_cost(*shape) for shape in shapes]
    return (sum(o for o, _ in runs) / len(runs),
            sum(b for _, b in runs) / len(runs))

"""Controls for a table of several regions (`tsbs-cpu-only-4000-4dn`):
a program whose fan-out loses one region's partials, or folds one
region's twice. Each breaks the guarantee the configuration states
("every matching region's rows are in the answer", once) and has to
fail the number that guards it: `rows.cpu` (count(*) crosses the
regions) and, where a region is lost, every fleet template's keys.

    with region_faults.fault(qe.executor, "drop"):    # or "twice"
        ...

patches `PhysicalExecutor._region_partials` of that executor for the
block: `drop` answers for the table's LAST matching region as if its
scan were empty, `twice` hands the fan-out the last region's partials
two times over.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def fault(executor, kind: str):
    if kind not in ("drop", "twice"):
        raise ValueError(kind)
    sound = executor._region_partials
    last = {}

    def faulty(region, table, *rest):
        out = sound(region, table, *rest)
        if region[0] != len(table.region_ids) - 1:
            return out
        if kind == "drop":
            return out._replace(partials=[], stats=None, scanned=False)
        last["n"] = last.get("n", 0) + 1
        return out._replace(partials=out.partials + out.partials)

    executor._region_partials = faulty
    try:
        yield
    finally:
        del executor._region_partials

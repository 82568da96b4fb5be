"""A program that cannot plan a RANGE ... ALIGN statement as an
aggregate, for ONE test (`test_range_cell.py`): put on PYTHONPATH, this
file makes the engine plan every single-table SELECT with `plan_select`
in every process that imports it — what the commit before ISSUE 44 does
under `EXPLAIN`, which the harness's warm-up sends for every template
(`PlanError: select item 'ts' is neither a group key nor an aggregate`).
It is a fixture of the benchmark's tests, not an option of the program:
the loader `present_rows` must refuse such a program before it writes a
row.
"""

import importlib.abc
import importlib.util
import sys

TARGET = "greptimedb_tpu.query.engine"


class _Loader(importlib.abc.Loader):
    def __init__(self, real):
        self.real = real

    def create_module(self, spec):
        return self.real.create_module(spec)

    def exec_module(self, module):
        self.real.exec_module(module)
        module.QueryEngine._plan_table_select = staticmethod(
            module.plan_select)


class _Finder(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path, target=None):
        if name != TARGET:
            return None
        sys.meta_path.remove(self)
        try:
            spec = importlib.util.find_spec(name)
        finally:
            sys.meta_path.insert(0, self)
        spec.loader = _Loader(spec.loader)
        return spec


sys.meta_path.insert(0, _Finder())

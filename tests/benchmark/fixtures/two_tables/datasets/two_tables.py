"""Two TSBS `cpu-only` tables in one data home: `cpu_a` and `cpu_b`, the
same row, other values (each table its own stream of the seed). A
dataset of several tables offers `tables()`; each view has the
single-table interface the loaders and the template families are
written against.
"""

from benchmark.harness.common import load_module

_Cpu = load_module("datasets", "tsbs_cpu").Dataset


class _View(_Cpu):
    def __init__(self, table: str, seed: int, scale: dict):
        super().__init__(seed, scale)
        self.table = table


class Dataset:
    def __init__(self, seed: int, scale: dict):
        self._views = [_View("cpu_a", int(seed), scale),
                       _View("cpu_b", int(seed) + 1, scale)]
        self.rows = sum(v.rows for v in self._views)

    def tables(self) -> list:
        return self._views

    def view(self, table: str):
        return next(v for v in self._views if v.table == table)

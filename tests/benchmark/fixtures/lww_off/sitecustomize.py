"""A program with its last-write-wins mask switched off, for ONE test
(`test_iot_cell.py`): put on PYTHONPATH, this file makes
`greptimedb_tpu.query.lww.keep_mask` answer "nothing repeats" in every
process that imports it — the serving process among them. It is a
fixture of the benchmark's tests, not an option of the program: a run
over such a program must not come out correct.
"""

import importlib.abc
import importlib.util
import sys

TARGET = "greptimedb_tpu.query.lww"


class _Loader(importlib.abc.Loader):
    def __init__(self, real):
        self.real = real

    def create_module(self, spec):
        return self.real.create_module(spec)

    def exec_module(self, module):
        self.real.exec_module(module)
        module.keep_mask = lambda scan, tags, ts: (None, "none", 0)


class _Finder(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path, target=None):
        if name != TARGET:
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(name)
        spec.loader = _Loader(spec.loader)
        return spec


sys.meta_path.insert(0, _Finder())

"""A program that does not place a partitioned table's regions, for ONE
test (`test_mesh_cell.py`): put on PYTHONPATH, this file makes
`information_schema.region_peers` name peer 0 for every region in every
process that imports it — what the commit before ISSUE 42 answers. It is
a fixture of the benchmark's tests, not an option of the program: the
loader `partitioned` must refuse such a program before it writes a row.
"""

import importlib.abc
import importlib.util
import sys

TARGET = "greptimedb_tpu.catalog.information_schema"


class _Loader(importlib.abc.Loader):
    def __init__(self, real):
        self.real = real

    def create_module(self, spec):
        return self.real.create_module(spec)

    def exec_module(self, module):
        self.real.exec_module(module)
        sound = module._TABLES["region_peers"]

        def peers(qe, ctx):
            cols = sound(qe, ctx)
            cols["peer_id"] = [0] * len(cols["peer_id"])
            cols["peer_addr"] = ["datanode-0"] * len(cols["peer_addr"])
            return cols

        module._TABLES["region_peers"] = peers


class _Finder(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path, target=None):
        if name != TARGET:
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(name)
        spec.loader = _Loader(spec.loader)
        return spec


sys.meta_path.insert(0, _Finder())

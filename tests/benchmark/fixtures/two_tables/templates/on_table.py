"""Family `on_table`: a template of another family, asked of one table
of a dataset of several. The traffic entry's `args` name both:

    {"template": "cpu-max-all-1", "name": "a.cpu-max-all-1",
     "args": {"family": "tsbs_devops", "table": "cpu_a"}}

Every call the harness makes with the dataset reaches the wrapped
template with that table's view instead (`Dataset.view(table)`), so the
request, its reference and the comparison are the wrapped family's own.
"""

from benchmark.harness.common import load_module


class _OnTable:
    def __init__(self, inner, table: str):
        self._inner, self._table = inner, table

    def __getattr__(self, name: str):
        attr = getattr(self._inner, name)
        if not callable(attr):
            return attr

        def call(*args, **kw):
            return attr(*(a.view(self._table) if hasattr(a, "view") else a
                          for a in args), **kw)
        return call


def make(template: str, args: dict | None = None):
    inner = load_module("templates", args["family"]).make(
        template, args.get("args"))
    return _OnTable(inner, args["table"])

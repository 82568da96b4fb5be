"""Family `prom_fleet`: a `promql_board` panel over ONE metric of a
dataset of many (`datasets/prom_node_fleet.py`: every metric name a
logical table of the metric engine). The traffic entry's `args` are the
panel's, plus the metric it reads:

    {"template": "range", "name": "cpu-system-by-instance",
     "args": {"metric": "node_cpu_seconds_total", "fn": "rate",
              "window_s": 300, "agg": "sum", "by": "instance",
              "match": {"mode": "system"}, "step_s": 60, "range_s": 900}}

Every call the harness makes with the dataset reaches `promql_board`'s
`range` template with that metric's view instead (the `on_table`
pattern), so the request text, the numpy reference (Prometheus'
extrapolatedRate rules in float64), what is compared (the widest
relative gap over every point of every series), its limit (1e-10) and
the control (the same arithmetic on float32 samples) are
`promql_board`'s own. The one thing adapted: that template reads its
samples from `fields["val"]`, the fleet's tables name their one field
`greptime_value` — the view is handed over with its matrix under both.
"""

from benchmark.harness.common import load_module


class _Face:
    """A fleet view as `promql_board` reads one: the view's own
    attributes, its sample matrix under `val`."""

    def __init__(self, view):
        self._view = view

    def __getattr__(self, name: str):
        return getattr(self._view, name)

    @property
    def fields(self) -> dict:
        (matrix,) = self._view.fields.values()
        return {"val": matrix}


class _OnMetric:
    def __init__(self, inner, metric: str):
        self._inner, self._metric = inner, metric

    def __getattr__(self, name: str):
        attr = getattr(self._inner, name)
        if not callable(attr):
            return attr

        def call(*args, **kw):
            return attr(*(_Face(a.view(self._metric))
                          if hasattr(a, "view") else a for a in args), **kw)
        return call

    def __setattr__(self, name: str, value) -> None:
        if name.startswith("_"):
            object.__setattr__(self, name, value)
        else:  # the harness names the template (Entry: template.name)
            setattr(self._inner, name, value)


def make(template: str, args: dict | None = None):
    args = dict(args or {})
    metric = args.pop("metric")
    return _OnMetric(
        load_module("templates", "promql_board").make(template, args),
        metric)

"""node_exporter-shaped counters: `node_cpu_seconds_total{instance,cpu,mode}`.

One plain table, tags instance x cpu x mode (the config's `scale`
gives the three cardinalities; 125 x 10 x 8 = 10,000 series), one
DOUBLE field `val`, one sample per series every `step_s` seconds.
Counters rise by 50 per sample plus uniform(0, 50) noise from the
seed, so every series is strictly increasing: no counter resets (the
rule of chip_smoke.py's make_prom_data).
"""

from __future__ import annotations

import numpy as np

T0_MS = 1456790400000
_MODES = ["idle", "iowait", "irq", "nice", "softirq", "steal", "system",
          "user", "guest", "guest_nice"]


class Dataset:
    table = "node_cpu_seconds_total"

    def __init__(self, seed: int, scale: dict):
        self.instances = int(scale["instances"])
        self.cpus = int(scale["cpus"])
        self.modes = int(scale["modes"])
        if self.modes > len(_MODES):
            raise ValueError(f"at most {len(_MODES)} modes")
        self.hours = int(scale["hours"])
        self.step_ms = int(scale["step_s"]) * 1000
        self.points = self.hours * 3600_000 // self.step_ms
        self.t0_ms = T0_MS
        self.t_end_ms = T0_MS + self.hours * 3600_000
        self.series = self.instances * self.cpus * self.modes
        self.rows = self.points * self.series
        rng = np.random.default_rng([int(seed), 3])
        base = np.arange(self.points, dtype=np.float64)[:, None] * 50.0
        # [points, series]; series s = (instance, cpu, mode) in C order
        self.fields = {"val": base + rng.uniform(
            0.0, 50.0, (self.points, self.series))}
        inst, cpu, mode = np.unravel_index(
            np.arange(self.series), (self.instances, self.cpus, self.modes))
        self.instance_of = inst
        self.mode_of = mode
        self.tag_values = {
            "instance": [f"node-{i}:9100" for i in inst],
            "cpu": [str(c) for c in cpu],
            "mode": [_MODES[m] for m in mode],
        }
        self.mode_names = _MODES[:self.modes]

    def create_sql(self) -> str:
        return (f"CREATE TABLE {self.table} (instance STRING, cpu STRING, "
                "mode STRING, val DOUBLE, ts TIMESTAMP(3) NOT NULL, "
                "TIME INDEX (ts), PRIMARY KEY (instance, cpu, mode)) "
                "WITH (append_mode = 'true')")

    def series_tags(self) -> dict:
        return self.tag_values

    def slices(self, max_rows: int):
        per = max(1, max_rows // self.series)
        for p0 in range(0, self.points, per):
            p1 = min(p0 + per, self.points)
            ts = np.repeat(
                self.t0_ms + np.arange(p0, p1, dtype=np.int64) * self.step_ms,
                self.series)
            yield p0, p1, ts, {"val": self.fields["val"][p0:p1].reshape(-1)}

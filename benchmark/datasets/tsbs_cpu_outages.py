"""TSBS devops `cpu-only` rows of a fleet as its operators see one: the
same hosts and the same row as `tsbs_cpu`, with outages.

TSBS's generator emits every host at every tick, so no window of the
table is ever empty and a range statement's `FILL` would have no work.
A fleet's hosts stop reporting for minutes and come back, and new ones
start late. From --seed (stream [seed, 5]; set by this configuration,
`assumed` in its file, not TSBS's):

    outages   5% of the hosts (at least 3) each miss ONE interval: its
              length uniform in 5-30 min (on the 10 s grid), its start
              uniform over the span that leaves it inside;
    late      1% more (at least 1) report nothing before a start uniform
              in the first 6 h (the first half of a shorter span).

The rows are ABSENT (`present` is False), not NULL; `rows` counts the
present ones, and `slices` carries only them. Everything else — tags,
values, `t0_ms`, `tick` — is `tsbs_cpu.Dataset`'s, imported, so the
reference of a present row reads the same seeded array.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.common import load_module

_cpu = load_module("datasets", "tsbs_cpu")

OUTAGE_SHARE, OUTAGE_MIN_HOSTS = 0.05, 3
OUTAGE_MS = (5 * 60_000, 30 * 60_000)
LATE_SHARE, LATE_MIN_HOSTS, LATE_WITHIN_MS = 0.01, 1, 6 * 3600_000


class Dataset(_cpu.Dataset):
    def __init__(self, seed: int, scale: dict):
        super().__init__(seed, scale)
        rng = np.random.default_rng([int(seed), 5])
        n_out = min(self.hosts, max(OUTAGE_MIN_HOSTS,
                                    round(OUTAGE_SHARE * self.hosts)))
        n_late = min(self.hosts - n_out, max(
            LATE_MIN_HOSTS, round(LATE_SHARE * self.hosts)))
        picked = rng.choice(self.hosts, size=n_out + n_late, replace=False)
        #: {host: (first absent point, first present point after it)}
        self.outages: dict = {}
        self.present = np.ones((self.points, self.hosts), bool)
        step = self.step_ms
        for h in picked[:n_out]:
            length = int(rng.integers(OUTAGE_MS[0] // step,
                                      OUTAGE_MS[1] // step + 1))
            length = min(length, self.points - 1)
            p0 = int(rng.integers(0, self.points - length + 1))
            self.outages[int(h)] = (p0, p0 + length)
        #: {host: its first present point}
        self.late: dict = {}
        within = min(LATE_WITHIN_MS, (self.t_end_ms - self.t0_ms) // 2)
        for h in picked[n_out:]:
            self.late[int(h)] = int(rng.integers(1, within // step + 1))
        for h, (p0, p1) in self.outages.items():
            self.present[p0:p1, h] = False
        for h, p1 in self.late.items():
            self.present[:p1, h] = False
        self.rows = int(self.present.sum())

    def slices(self, max_rows: int):
        """Time-sliced batches of at most ~max_rows rows, the present
        rows only, series-major within each point: (p0, p1, ts[int64 n],
        {field: float64[n]}, series[int64 n])."""
        for p0, p1, ts, fields in super().slices(max_rows):
            keep = self.present[p0:p1].reshape(-1)
            series = np.tile(np.arange(self.hosts), p1 - p0)[keep]
            yield (p0, p1, ts[keep], {f: v[keep] for f, v in fields.items()},
                   series)

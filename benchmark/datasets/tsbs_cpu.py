"""TSBS devops `cpu-only`: table `cpu`, ten tags + ten DOUBLE fields.

The row is the source's, unchanged (GreptimeDB docs/benchmarks/tsbs/
v0.8.0.md runs TSBS's `cpu-only` use case): tags hostname, region,
datacenter, rack, os, arch, team, service, service_version,
service_environment (PRIMARY KEY, all ten), ts TIMESTAMP(3), ten
usage_* DOUBLE fields, append_mode. Values are uniform(0, 100) and the
per-host tag values are drawn from the seed; the tag cardinalities are
the config file's `assumed` block.

Everything here is a function of (seed, scale) only. The harness
process holds one Dataset for the references; the bulk-load helper
process builds its own from the same seed.

The table can be written to inside a window: `tick(i)` is every host's
next sample after the loaded span (README, "A writer inside the
window").
"""

from __future__ import annotations

import numpy as np

FIELDS = [f"usage_{n}" for n in (
    "user", "system", "idle", "nice", "iowait", "irq", "softirq",
    "steal", "guest", "guest_nice")]
TAGS = ["hostname", "region", "datacenter", "rack", "os", "arch", "team",
        "service", "service_version", "service_environment"]
T0_MS = 1456790400000  # 2016-03-01T00:00:00Z (TSBS's default start)

_REGIONS = ["us-east-1", "us-west-1", "us-west-2", "eu-west-1",
            "eu-central-1", "ap-southeast-1", "ap-southeast-2",
            "ap-northeast-1", "sa-east-1"]
_OS = ["Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10"]
_ARCH = ["x64", "x86"]
_TEAM = ["SF", "NYC", "LON", "CHI"]
_ENV = ["production", "staging", "test"]


class Dataset:
    table = "cpu"

    def __init__(self, seed: int, scale: dict):
        self.seed = int(seed)
        self.hosts = int(scale["hosts"])
        self.hours = int(scale["hours"])
        self.step_ms = int(scale["step_s"]) * 1000
        self.points = self.hours * 3600_000 // self.step_ms
        self.t0_ms = T0_MS
        self.t_end_ms = T0_MS + self.hours * 3600_000
        self.rows = self.points * self.hosts
        rng = np.random.default_rng([int(seed), 1])
        # {field: [points, hosts] float64}; row (p, h) is host_h at
        # T0 + p * step
        self.fields = {f: rng.uniform(0.0, 100.0, (self.points, self.hosts))
                       for f in FIELDS}
        trng = np.random.default_rng([int(seed), 2])
        h = self.hosts
        region = trng.integers(0, len(_REGIONS), h)
        dc = trng.integers(0, 25, h)  # 25 datacenters in all
        self.tag_values = {
            "hostname": [f"host_{i}" for i in range(h)],
            "region": [_REGIONS[r] for r in region],
            "datacenter": [f"dc-{d}" for d in dc],
            "rack": [str(x) for x in trng.integers(0, 100, h)],
            "os": [_OS[x] for x in trng.integers(0, len(_OS), h)],
            "arch": [_ARCH[x] for x in trng.integers(0, len(_ARCH), h)],
            "team": [_TEAM[x] for x in trng.integers(0, len(_TEAM), h)],
            "service": [str(x) for x in trng.integers(0, 20, h)],
            "service_version": [str(x) for x in trng.integers(0, 2, h)],
            "service_environment": [
                _ENV[x] for x in trng.integers(0, len(_ENV), h)],
        }

    def create_sql(self) -> str:
        return (
            f"CREATE TABLE {self.table} ("
            + ", ".join(f"{t} STRING" for t in TAGS)
            + ", ts TIMESTAMP(3) NOT NULL, "
            + ", ".join(f"{f} DOUBLE" for f in FIELDS)
            + ", TIME INDEX (ts), PRIMARY KEY ("
            + ", ".join(TAGS) + ")) WITH (append_mode = 'true')")

    def series_tags(self) -> dict:
        """{tag: [value of series 0, value of series 1, ...]}: one
        series per host."""
        return self.tag_values

    def slices(self, max_rows: int):
        """Time-sliced batches of at most ~max_rows rows, series-major
        within each point: (p0, p1, ts[int64 n], {field: float64[n]})."""
        per = max(1, max_rows // self.hosts)
        for p0 in range(0, self.points, per):
            p1 = min(p0 + per, self.points)
            ts = np.repeat(
                self.t0_ms + np.arange(p0, p1, dtype=np.int64) * self.step_ms,
                self.hosts)
            yield p0, p1, ts, {f: v[p0:p1].reshape(-1)
                               for f, v in self.fields.items()}

    def tick(self, i: int) -> tuple:
        """(ts_ms, {field: float64[hosts]}): every host's sample at
        t_end_ms + i * step_ms, i >= 0, uniform(0, 100) as the loaded
        rows, from a stream of the tick's own."""
        rng = np.random.default_rng([self.seed, 3, int(i)])
        vals = rng.uniform(0.0, 100.0, (len(FIELDS), self.hosts))
        return (self.t_end_ms + int(i) * self.step_ms,
                dict(zip(FIELDS, vals)))

    @property
    def series(self) -> int:
        return self.hosts

"""A fleet of node_exporters, every metric name a table of its own.

`scale` = {"instances", "minutes", "step_s"}: `instances` hosts scraped
every `step_s` seconds for `minutes` minutes, each exposing the SAME
series set — about a thousand series under about 250 metric names, what
one node_exporter (Linux, the default collectors plus `softirqs`) gives
a Prometheus. Each metric name is one view (`tables()`, `view(name)`):
a logical table `(<its labels>, ts, greptime_value DOUBLE)` created
`ENGINE=metric`, NOT append_mode, as GreptimeDB's remote-write door
creates it (`[prom_store] with_metric_engine = true`) — but for the time
index's name: remote write names it `greptime_timestamp`, the harness's
bulk loader (`harness/bulk_load.py` `put_rows`) writes the time column
of every table it loads under `ts`, so these tables name it so.
Every table has the `instance` label; there is no `job` label (one
constant value that no panel selects or groups on, as in
prom-node-cpu-10k).

Series of a view are instance-major: series s = (instance, the view's
label combination), a scrape's order. The views, in this order:

| collector | metric names | labels besides instance | series an instance |
|---|---|---|---|
| cpu | node_cpu_seconds_total | cpu (10) x mode (8) | 80 |
| cpu | node_cpu_guest_seconds_total | cpu (10) x mode (user, nice) | 20 |
| filesystem | node_filesystem_{avail,size,free}_bytes, _files, _files_free, _readonly, _device_error | device, fstype, mountpoint (4 filesystems) | 7 x 4 |
| netdev, netclass | node_network_{receive,transmit}_*_total (16), node_network_up, _mtu_bytes, ... (21) | device (4) | 37 x 4 |
| loadavg | node_load1, node_load5, node_load15 | — | 3 |
| diskstats | node_disk_*_total, node_disk_io_now, node_disk_info (18) | device (6) | 108 |
| softnet, schedstat, cpufreq, thermal throttle | node_softnet_* (7), node_schedstat_* (3), node_cpu_frequency_* and _scaling_frequency_* (6), node_cpu_online, node_cpu_core_throttles_total | cpu (10) | 180 |
| softirqs | node_softirqs_total | cpu (10) x type (10) | 100 |
| meminfo | node_memory_*_bytes, node_memory_HugePages_* (48) | — | 48 |
| vmstat, netstat, sockstat, timex, stat, filefd, entropy, conntrack, pressure, time, process_*, go_* | one name each (136) | — | 136 |
| scrape | node_scrape_collector_duration_seconds, _success | collector (30) | 60 |
| hwmon | node_hwmon_temp_celsius, _temp_max_celsius, _temp_crit_celsius | chip, sensor (12) | 36 |
| arp, udp_queues, go gc, promhttp, info | node_arp_entries (4), node_udp_queues (4), go_gc_duration_seconds (5), promhttp_metric_handler_requests_total (3), node_uname_info, node_os_info, node_exporter_build_info (1 each) | device; ip, queue; quantile; code; constant labels | 19 |

Fixed exactly (ISSUE 27): node_cpu_seconds_total 10 cpus x 8 modes = 80,
node_filesystem_avail_bytes 4 filesystems (gauge),
node_network_receive_bytes_total 4 devices (counter), node_load1 1
(gauge). `SERIES_PER_INSTANCE` and `len(METRICS)` are what the table
above adds up to; the configuration's `assumed` repeats them.

Values from `--seed`, one stream per view: a counter (`*_total`) rises
by 50 a sample plus uniform(0, 50), strictly increasing, no reset (the
rule of prom_counter.py); a gauge is a level of its series plus
uniform(0, spread): filesystem bytes around tens of GiB, load around 1,
everything else around a few thousand. `fields` is built when first
read: the run's own process reads three views, the loader all of them.
"""

from __future__ import annotations

import numpy as np

T0_MS = 1456790400000
VALUE = "greptime_value"
TS = "ts"

_MODES = ["idle", "iowait", "irq", "nice", "softirq", "steal", "system",
          "user"]
_CPUS = [str(c) for c in range(10)]
_FS = [("/dev/sda1", "ext4", "/"), ("/dev/sda2", "ext4", "/boot"),
       ("/dev/sdb1", "xfs", "/data"), ("tmpfs", "tmpfs", "/run")]
_NETDEV = ["eth0", "eth1", "lo", "docker0"]
_DISKS = ["sda", "sdb", "sdc", "sdd", "nvme0n1", "nvme1n1"]
_SOFTIRQ = ["HI", "TIMER", "NET_TX", "NET_RX", "BLOCK", "IRQ_POLL",
            "TASKLET", "SCHED", "HRTIMER", "RCU"]
_COLLECTORS = [
    "arp", "bcache", "bonding", "btrfs", "conntrack", "cpu", "cpufreq",
    "diskstats", "edac", "entropy", "filefd", "filesystem", "hwmon",
    "loadavg", "meminfo", "netclass", "netdev", "netstat", "pressure",
    "schedstat", "sockstat", "softirqs", "softnet", "stat", "textfile",
    "thermal_zone", "time", "timex", "uname", "vmstat"]
_HWMON = [("platform_coretemp_0", f"temp{i}") for i in range(1, 11)] \
    + [("nvme_nvme0", "temp1"), ("nvme_nvme1", "temp1")]

# label sets an instance exposes under a name of the family: (label
# names, one tuple of values per series)
_LABELS = {
    "none": ((), [()]),
    "cpu_mode": (("cpu", "mode"), [(c, m) for c in _CPUS for m in _MODES]),
    "cpu_guest": (("cpu", "mode"),
                  [(c, m) for c in _CPUS for m in ("user", "nice")]),
    "cpu": (("cpu",), [(c,) for c in _CPUS]),
    "fs": (("device", "fstype", "mountpoint"), _FS),
    "netdev": (("device",), [(d,) for d in _NETDEV]),
    "disk": (("device",), [(d,) for d in _DISKS]),
    "softirq": (("cpu", "type"), [(c, t) for c in _CPUS for t in _SOFTIRQ]),
    "collector": (("collector",), [(c,) for c in _COLLECTORS]),
    "hwmon": (("chip", "sensor"), _HWMON),
    "udp_queue": (("ip", "queue"), [(i, q) for i in ("v4", "v6")
                                    for q in ("rx", "tx")]),
    "quantile": (("quantile",), [(q,) for q in ("0", "0.25", "0.5", "0.75",
                                                "1")]),
    "code": (("code",), [(c,) for c in ("200", "500", "503")]),
    "uname": (("sysname", "release", "machine"),
              [("Linux", "5.15.0-105-generic", "x86_64")]),
    "os": (("name", "version_id"), [("Ubuntu", "22.04")]),
    "build": (("version", "goversion"), [("1.8.0", "go1.22.2")]),
}


def _names(prefix: str, stems: str, suffix: str = "") -> list:
    return [prefix + s + suffix for s in stems.split()]


# (labels key, metric names): the order is the order of the views
_FAMILIES = [
    ("cpu_mode", ["node_cpu_seconds_total"]),
    ("fs", _names("node_filesystem_", "avail_bytes size_bytes free_bytes "
                  "files files_free readonly device_error")),
    ("netdev", _names("node_network_receive_", "bytes packets errs drop fifo "
                      "frame compressed multicast", "_total")
     + _names("node_network_transmit_", "bytes packets errs drop fifo colls "
              "carrier compressed", "_total")
     + _names("node_network_", "up mtu_bytes speed_bytes carrier flags "
              "address_assign_type carrier_changes_total "
              "carrier_up_changes_total carrier_down_changes_total "
              "device_id dormant iface_id iface_link iface_link_mode "
              "name_assign_type net_dev_group protocol_type "
              "transmit_queue_length info receive_nohandler_total "
              "receive_missed_total")),
    ("none", ["node_load1", "node_load5", "node_load15"]),
    ("cpu_guest", ["node_cpu_guest_seconds_total"]),
    ("disk", _names("node_disk_", "reads_completed_total reads_merged_total "
                    "read_bytes_total read_time_seconds_total "
                    "writes_completed_total writes_merged_total "
                    "written_bytes_total write_time_seconds_total io_now "
                    "io_time_seconds_total io_time_weighted_seconds_total "
                    "discards_completed_total discards_merged_total "
                    "discarded_sectors_total discard_time_seconds_total "
                    "flush_requests_total flush_requests_time_seconds_total "
                    "info")),
    ("cpu", _names("node_softnet_", "processed_total dropped_total "
                   "times_squeezed_total backlog_len cpu_collision_total "
                   "received_rps_total flow_limit_count_total")
     + _names("node_cpu_", "frequency_hertz frequency_max_hertz "
              "frequency_min_hertz online")
     + _names("node_schedstat_", "running_seconds_total "
              "waiting_seconds_total timeslices_total")
     + _names("node_cpu_scaling_frequency_", "hertz max_hertz min_hertz")
     + ["node_cpu_core_throttles_total"]),
    ("softirq", ["node_softirqs_total"]),
    ("none", _names("node_memory_", "MemTotal MemFree MemAvailable Buffers "
                    "Cached SwapCached Active Inactive Active_anon "
                    "Inactive_anon Active_file Inactive_file Unevictable "
                    "Mlocked SwapTotal SwapFree Dirty Writeback AnonPages "
                    "Mapped Shmem KReclaimable Slab SReclaimable SUnreclaim "
                    "KernelStack PageTables NFS_Unstable Bounce WritebackTmp "
                    "CommitLimit Committed_AS VmallocTotal VmallocUsed "
                    "VmallocChunk Percpu HardwareCorrupted AnonHugePages "
                    "ShmemHugePages ShmemPmdMapped Hugepagesize DirectMap4k "
                    "DirectMap2M DirectMap1G", "_bytes")
     + _names("node_memory_HugePages_", "Total Free Rsvd Surp")),
    ("none", _names("node_vmstat_", "pgfault pgmajfault pgpgin pgpgout "
                    "pswpin pswpout oom_kill")
     + _names("node_netstat_", "Tcp_ActiveOpens Tcp_PassiveOpens "
              "Tcp_CurrEstab Tcp_InSegs Tcp_OutSegs Tcp_RetransSegs "
              "Tcp_InErrs Tcp_OutRsts Udp_InDatagrams Udp_OutDatagrams "
              "Udp_InErrors Udp_NoPorts Udp_RcvbufErrors Udp_SndbufErrors "
              "Udp6_InDatagrams Udp6_OutDatagrams Udp6_InErrors "
              "Udp6_NoPorts UdpLite_InErrors UdpLite6_InErrors "
              "TcpExt_ListenDrops TcpExt_ListenOverflows "
              "TcpExt_SyncookiesSent TcpExt_SyncookiesRecv "
              "TcpExt_SyncookiesFailed TcpExt_TCPSynRetrans "
              "TcpExt_TCPTimeouts Ip_Forwarding IpExt_InOctets "
              "IpExt_OutOctets Ip6_InOctets Ip6_OutOctets Icmp_InMsgs "
              "Icmp_OutMsgs Icmp_InErrors Icmp6_InMsgs Icmp6_OutMsgs "
              "Icmp6_InErrors")
     + _names("node_sockstat_", "sockets_used TCP_inuse TCP_alloc TCP_mem "
              "TCP_mem_bytes TCP_orphan TCP_tw UDP_inuse UDP_mem "
              "UDP_mem_bytes UDPLITE_inuse RAW_inuse FRAG_inuse FRAG_memory "
              "TCP6_inuse UDP6_inuse UDPLITE6_inuse RAW6_inuse FRAG6_inuse "
              "FRAG6_memory")
     + _names("node_timex_", "offset_seconds frequency_adjustment_ratio "
              "maxerror_seconds estimated_error_seconds status "
              "loop_time_constant tick_seconds pps_frequency_hertz "
              "pps_jitter_seconds pps_shift_seconds pps_stability_hertz "
              "pps_jitter_total pps_calibration_total pps_error_total "
              "pps_stability_exceeded_total tai_offset_seconds sync_status")
     + _names("node_", "boot_time_seconds time_seconds "
              "time_zone_offset_seconds context_switches_total forks_total "
              "intr_total procs_running procs_blocked "
              "entropy_available_bits entropy_pool_size_bits "
              "filefd_allocated filefd_maximum nf_conntrack_entries "
              "nf_conntrack_entries_limit textfile_scrape_error "
              "pressure_cpu_waiting_seconds_total "
              "pressure_io_waiting_seconds_total "
              "pressure_io_stalled_seconds_total "
              "pressure_memory_waiting_seconds_total "
              "pressure_memory_stalled_seconds_total")
     + _names("process_", "cpu_seconds_total resident_memory_bytes "
              "virtual_memory_bytes virtual_memory_max_bytes open_fds "
              "max_fds start_time_seconds")
     + _names("go_", "goroutines threads gc_duration_seconds_sum "
              "gc_duration_seconds_count")
     + _names("go_memstats_", "alloc_bytes alloc_bytes_total sys_bytes "
              "lookups_total mallocs_total frees_total heap_alloc_bytes "
              "heap_sys_bytes heap_idle_bytes heap_inuse_bytes "
              "heap_released_bytes heap_objects stack_inuse_bytes "
              "stack_sys_bytes mspan_inuse_bytes mspan_sys_bytes "
              "mcache_inuse_bytes mcache_sys_bytes buck_hash_sys_bytes "
              "gc_sys_bytes other_sys_bytes next_gc_bytes "
              "last_gc_time_seconds")
     + ["promhttp_metric_handler_requests_in_flight"]),
    ("collector", ["node_scrape_collector_duration_seconds",
                   "node_scrape_collector_success"]),
    ("hwmon", _names("node_hwmon_", "temp_celsius temp_max_celsius "
                     "temp_crit_celsius")),
    ("netdev", ["node_arp_entries"]),
    ("udp_queue", ["node_udp_queues"]),
    ("quantile", ["go_gc_duration_seconds"]),
    ("code", ["promhttp_metric_handler_requests_total"]),
    ("uname", ["node_uname_info"]),
    ("os", ["node_os_info"]),
    ("build", ["node_exporter_build_info"]),
]

#: (metric name, labels key), in view order
METRICS = [(name, key) for key, names in _FAMILIES for name in names]
SERIES_PER_INSTANCE = sum(len(_LABELS[key][1]) for _, key in METRICS)


class _View:
    """One metric name: the single-table interface of benchmark/README.md
    over its [points, series] matrix."""

    def __init__(self, index: int, name: str, key: str, ds: "Dataset"):
        self.index, self.table, self._ds = index, name, ds
        self.label_names, self.combos = _LABELS[key]
        self.counter = name.endswith("_total")
        self.instances = ds.instances
        self.series = ds.instances * len(self.combos)
        self.points, self.step_ms = ds.points, ds.step_ms
        self.t0_ms, self.t_end_ms = ds.t0_ms, ds.t_end_ms
        self.rows = self.points * self.series
        self._tags = None
        self._fields = None

    @property
    def fields(self) -> dict:
        if self._fields is None:
            rng = np.random.default_rng([int(self._ds.seed), 5, self.index])
            noise = rng.uniform(0.0, 50.0, (self.points, self.series))
            if self.counter:
                noise += np.arange(self.points,
                                   dtype=np.float64)[:, None] * 50.0
            elif self.table.startswith("node_filesystem_") \
                    and self.table.endswith("_bytes"):
                noise = noise * 2e4 + (20 + np.arange(self.series) % 60) \
                    * float(1 << 30)
            elif self.table.startswith("node_load"):
                noise = noise / 12.5 + 0.5
            else:
                noise += (1 + np.arange(self.series) % 97) * 1e3
            self._fields = {VALUE: noise}
        return self._fields

    def create_sql(self) -> str:
        cols = ["instance"] + list(self.label_names)
        return (f"CREATE TABLE {self.table} ("
                + ", ".join(f"{c} STRING" for c in cols)
                + f", {TS} TIMESTAMP(3) NOT NULL, {VALUE} DOUBLE, "
                f"TIME INDEX ({TS}), PRIMARY KEY ({', '.join(cols)})) "
                "ENGINE=metric")

    def series_tags(self) -> dict:
        if self._tags is None:
            k = len(self.combos)
            tags = {"instance": [f"node-{i}:9100"
                                 for i in range(self.instances)
                                 for _ in range(k)]}
            for j, name in enumerate(self.label_names):
                tags[name] = [c[j] for c in self.combos] * self.instances
            self._tags = tags
        return self._tags

    def slices(self, max_rows: int):
        per = max(1, max_rows // self.series)
        mat = self.fields[VALUE]
        for p0 in range(0, self.points, per):
            p1 = min(p0 + per, self.points)
            ts = np.repeat(
                self.t0_ms + np.arange(p0, p1, dtype=np.int64) * self.step_ms,
                self.series)
            yield p0, p1, ts, {VALUE: mat[p0:p1].reshape(-1)}


class Dataset:
    def __init__(self, seed: int, scale: dict):
        self.seed = int(seed)
        self.instances = int(scale["instances"])
        self.step_ms = int(scale["step_s"]) * 1000
        self.points = int(scale["minutes"]) * 60_000 // self.step_ms
        self.t0_ms = T0_MS
        self.t_end_ms = T0_MS + self.points * self.step_ms
        self._views = [_View(i, name, key, self)
                       for i, (name, key) in enumerate(METRICS)]
        self._by_name = {v.table: v for v in self._views}
        self.series = sum(v.series for v in self._views)
        self.rows = sum(v.rows for v in self._views)
        # the single-table face test_manifest_config asks of a dataset:
        # the first view's
        self.table = self._views[0].table

    def create_sql(self) -> str:
        return self._views[0].create_sql()

    def tables(self) -> list:
        return self._views

    def view(self, table: str):
        return self._by_name[table]

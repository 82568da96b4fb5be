"""Standalone datanode process entrypoint.

`python -m greptimedb_tpu.cluster.datanode_main <shared_dir> <port_file>`
builds a RegionEngine over the SHARED data dir with the remote
(object-store) WAL and serves it over Flight — the real process shape of
a reference datanode (datanode/src/datanode.rs: region server behind
gRPC, WAL on shared storage so failover candidates can replay it).

The process writes its bound port to <port_file> and then serves until
killed; `kill -9` is the expected shutdown in the failover harness
(tests-integration/src/cluster.rs kills real processes the same way).
"""

from __future__ import annotations

import os
import sys
import time


def main() -> None:
    # a chip belongs to one process — the frontend that owns the device
    # tier. A datanode child scans and decodes on the CPU: pin it there
    # before any backend init, whatever the parent's environment says
    os.environ["JAX_PLATFORMS"] = "cpu"

    shared_dir, port_file = sys.argv[1], sys.argv[2]
    write_workers = int(sys.argv[3]) if len(sys.argv) > 3 else 2

    from greptimedb_tpu.servers.flight import FlightServer
    from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine
    from greptimedb_tpu.utils.otlp_trace import maybe_install
    from greptimedb_tpu.utils.tracing import install_trace_logging

    install_trace_logging()
    # inherited GTPU_OTLP_ENDPOINT: datanode children export their own
    # spans under the same trace ids the frontend propagates
    maybe_install()

    def _env_num(name, default, cast):
        try:
            return cast(os.environ.get(name, default))
        except (TypeError, ValueError):
            return default

    # the background maintenance plane is per-datanode; harnesses tune
    # it via env (spawned children inherit) — GTPU_MAINT_WORKERS=0
    # restores inline flush for tests that need the pre-plane shape
    engine = RegionEngine(EngineConfig(
        data_dir=shared_dir, wal_backend="remote",
        write_workers=write_workers,
        maintenance_workers=_env_num("GTPU_MAINT_WORKERS", 1, int),
        maintenance_tick_s=_env_num("GTPU_MAINT_TICK_S", 0.0, float),
        retention_ttl_ms=_env_num("GTPU_MAINT_TTL_MS", 0, int)))
    server = FlightServer(None, port=0, region_engine=engine)
    tmp = port_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(str(server.port))
    os.replace(tmp, port_file)  # atomic: readers never see a partial file
    try:
        from greptimedb_tpu.lint import lockdep

        while True:
            if lockdep.enabled() and os.environ.get("GTPU_LOCKDEP_DIR"):
                # the parent stops children with SIGKILL (the failover
                # scenario IS abrupt death), so atexit never runs here:
                # refresh the edge dump continuously instead
                lockdep.dump()
                time.sleep(1.0)
            else:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()

"""Standalone HA-metasrv process entrypoint (election over the wire).

`python -m greptimedb_tpu.cluster.metasrv_main <kv_addr> <port_file>
<node_id>` builds one electing metasrv peer: a KvElection + Metasrv over
an `HttpKv` pointed at the shared KV host (the etcd analog — CAS
atomicity lives in that one process), fronted by its own
`MetaHttpService` so the parent harness can drive `/admin/tick` with an
explicit virtual clock and observe `/heartbeat` / `/admin/*` redirects.

No MetasrvTicker runs here: the chaos harness owns time. The process
writes its bound port to <port_file> and serves until killed; election
chaos arrives via the inherited GTPU_CHAOS / GTPU_CHAOS_SEED env
(election.lease fires inside THIS process) and clock skew via
GTPU_CLOCK_SKEW_MS (the Jepsen clock nemesis, per-node).
"""

from __future__ import annotations

import os
import sys
import time


def main() -> None:
    # a chip belongs to one process — the frontend that owns the device
    # tier. The metadata plane never computes on a device: pin the CPU
    # before any backend init, whatever the parent's environment says
    os.environ["JAX_PLATFORMS"] = "cpu"

    kv_addr, port_file, node_id = sys.argv[1], sys.argv[2], sys.argv[3]

    from greptimedb_tpu.meta.election import KvElection
    from greptimedb_tpu.meta.kv_service import HttpKv, MetaHttpService
    from greptimedb_tpu.meta.metasrv import Metasrv, MetasrvOptions
    from greptimedb_tpu.utils.tracing import install_trace_logging

    install_trace_logging()

    def _env_num(name, default, cast):
        try:
            return cast(os.environ.get(name, default))
        except (TypeError, ValueError):
            return default

    kv = HttpKv(kv_addr)
    election = KvElection(kv, node_id,
                          lease_s=_env_num("GTPU_LEASE_S", 9.0, float))
    election.clock_skew_ms = _env_num("GTPU_CLOCK_SKEW_MS", 0.0, float)
    metasrv = Metasrv(kv, MetasrvOptions(), node_id=node_id,
                      election=election)
    service = MetaHttpService(metasrv)
    service.start()
    tmp = port_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(str(service.port))
    os.replace(tmp, port_file)  # atomic: readers never see a partial file
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()

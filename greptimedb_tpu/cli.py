"""Process entry point (mirrors reference src/cmd: the `greptime` binary's
`standalone start` subcommand and `cli` REPL, cmd/src/bin/greptime.rs:35-55).

    python -m greptimedb_tpu standalone start --data-home /tmp/db \
        --http-addr 127.0.0.1:4000
    python -m greptimedb_tpu repl --data-home /tmp/db
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import time


def build_standalone(data_home: str, opts=None):
    """Assemble the standalone stack (reference cmd/src/standalone.rs:381-530
    wiring: kv backend -> catalog -> region engine -> query engine)."""
    from greptimedb_tpu import options as optmod
    from greptimedb_tpu.catalog import Catalog, FileKv
    from greptimedb_tpu.query import QueryEngine
    from greptimedb_tpu.storage import RegionEngine
    from greptimedb_tpu.storage.engine import EngineConfig

    os.makedirs(data_home, exist_ok=True)
    tz = "UTC"
    if opts is not None:
        optmod.apply_query_env(opts)
        optmod.apply_observability(opts)
        optmod.apply_concurrency(opts)
        optmod.apply_shm(opts)
        cfg = optmod.engine_config(opts, os.path.join(data_home, "data"))
        tz = opts.default_timezone
    else:
        cfg = EngineConfig(data_dir=os.path.join(data_home, "data"))
    engine = RegionEngine(cfg)
    catalog = Catalog(FileKv(os.path.join(data_home, "catalog.json")))
    qe = QueryEngine(catalog, engine, default_timezone=tz)
    return engine, qe


def _split_addr(addr: str) -> tuple[str, int]:
    host, _, port = addr.rpartition(":")
    return host or "127.0.0.1", int(port)


def _user_provider(opts):
    if not opts.auth.static_users:
        return None
    from greptimedb_tpu.auth import StaticUserProvider
    from greptimedb_tpu.options import ConfigError

    pairs = {}
    for entry in opts.auth.static_users.split(","):
        user, sep, password = entry.partition("=")
        if not sep or not user.strip():
            raise ConfigError(
                f"auth.static_users entry {entry!r} is not user=password")
        if user.strip() in pairs:
            raise ConfigError(
                f"auth.static_users: duplicate user {user.strip()!r} — "
                "note passwords may not contain ','")
        pairs[user.strip()] = password
    return StaticUserProvider(pairs)


def _tls(tls_opts):
    if tls_opts.mode == "disable":
        return None
    if not tls_opts.cert_path or not tls_opts.key_path:
        # never downgrade silently: a config that asks for TLS but can't
        # provide it must abort boot, not serve plaintext
        from greptimedb_tpu.options import ConfigError

        raise ConfigError(
            f"tls.mode = {tls_opts.mode!r} requires cert_path and key_path")
    from greptimedb_tpu.servers.tls import TlsConfig

    return TlsConfig(cert_path=tls_opts.cert_path,
                     key_path=tls_opts.key_path,
                     mode=tls_opts.mode)


def _announce_device(role: str) -> None:
    """A query-serving process owns the device tier: resolve the backend
    now, from the main thread, say what it is, and refuse a CPU nobody
    asked for (config.require_stated_platform). Also says whether the
    native (C++) crc32/snappy library built, which load rates depend
    on."""
    from greptimedb_tpu import config, native

    dev = config.require_stated_platform()
    print(f"greptimedb_tpu {role} device: platform={dev['platform']} "
          f"device_kind={dev['device_kind']!r} count={dev['count']} "
          f"native={'available' if native.AVAILABLE else 'unavailable'}",
          flush=True)


def cmd_standalone(args):
    """Boot the full server set per layered options (reference
    frontend/src/server.rs:174-263 Services::build — always HTTP, optional
    Flight/MySQL/Postgres, plus the export-metrics self-scrape)."""
    from greptimedb_tpu.options import load_options
    from greptimedb_tpu.parallel.mesh import init_distributed

    # cross-host mesh: must join the jax.distributed job BEFORE the
    # first backend touch so jax.devices() is the global device list
    # (no-op unless GREPTIMEDB_TPU_COORDINATOR is configured)
    init_distributed()
    _announce_device("standalone")
    overrides: dict = {}
    if args.http_addr:
        overrides.setdefault("http", {})["addr"] = args.http_addr
    opts = load_options(args.config_file, overrides=overrides)
    engine, qe = build_standalone(args.data_home or opts.storage.data_home,
                                  opts)
    user_provider = _user_provider(opts)
    servers = []
    if opts.http.enable:
        from greptimedb_tpu.servers import HttpServer

        host, port = _split_addr(opts.http.addr)
        http_server = HttpServer(qe, host, port, user_provider=user_provider,
                                 timeout_s=opts.http.timeout_s)
        actual = http_server.start()
        servers.append(http_server)
        print(f"greptimedb_tpu standalone listening on http://{host}:{actual}",
              flush=True)
    if opts.grpc.enable:
        from greptimedb_tpu.servers.flight import FlightServer

        ghost, gport = _split_addr(opts.grpc.addr)
        fs = FlightServer(qe, ghost, gport, user_provider=user_provider)
        threading_start(fs)
        servers.append(fs)
        print(f"flight on grpc://{ghost}:{fs.port}", flush=True)
    if opts.mysql.enable:
        from greptimedb_tpu.servers.mysql import MysqlServer

        mhost, mport = _split_addr(opts.mysql.addr)
        ms = MysqlServer(qe, mhost, mport, user_provider=user_provider,
                         tls=_tls(opts.mysql.tls))
        ms.start()
        servers.append(ms)
        print(f"mysql on {mhost}:{ms.port}", flush=True)
    if opts.postgres.enable:
        from greptimedb_tpu.servers.postgres import PostgresServer

        phost, pport = _split_addr(opts.postgres.addr)
        ps = PostgresServer(qe, phost, pport, user_provider=user_provider,
                            tls=_tls(opts.postgres.tls))
        ps.start()
        servers.append(ps)
        print(f"postgres on {phost}:{ps.port}", flush=True)
    task = None
    if opts.metrics.enable:
        from greptimedb_tpu.utils.export_metrics import ExportMetricsTask

        task = ExportMetricsTask(qe, db=opts.metrics.db,
                                 interval_s=opts.metrics.write_interval_s)
        task.start()
    telemetry = None
    if opts.telemetry.enable:
        from greptimedb_tpu.utils.telemetry import TelemetryTask

        home = args.data_home or opts.storage.data_home
        post = None
        if not opts.telemetry.url:
            # no endpoint configured: log the payload locally so the
            # operator can see exactly what WOULD be sent
            def post(_url, body):
                print(f"telemetry: {body.decode()}", flush=True)
        telemetry = TelemetryTask(opts.telemetry.url, "standalone", home,
                                  interval_s=opts.telemetry.interval_s,
                                  post=post)
        telemetry.start()
    try:
        _wait_stop()
    finally:
        stop_standalone(engine, qe, servers,
                        [t for t in (task, telemetry) if t is not None])


def stop_standalone(engine, qe, servers=(), tasks=()) -> None:
    """Stop what `cmd_standalone` started, in its order: background
    tasks, servers, the engine, and the interpreter-lock probe
    `build_standalone` started with the observability plane."""
    from greptimedb_tpu.utils import lock_probe

    for t in tasks:
        t.stop()
    for s in servers:
        try:
            s.stop()
        except AttributeError:
            s.shutdown()
    engine.close()
    lock_probe.shutdown()


def threading_start(flight_server):
    import threading

    t = threading.Thread(target=flight_server.serve, daemon=True)
    t.start()


def cmd_dump_config(args):
    from greptimedb_tpu.options import example_toml

    sys.stdout.write(example_toml())


def _wait_stop():
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    while not stop:
        time.sleep(0.2)


def _write_port_file(path: str, value) -> None:
    """Atomic port-file publish: readers never see a partial file."""
    if not path:
        return
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(str(value))
    os.replace(tmp, path)


def cmd_metasrv(args):
    """Metadata-plane service process (reference cmd/src/metasrv.rs):
    FileKv-durable Metasrv + the networked KV/heartbeat HTTP service +
    a real-clock tick loop driving failure detection and failover."""
    from greptimedb_tpu.catalog.kv import FileKv
    from greptimedb_tpu.meta.kv_service import (MetaHttpService,
                                                MetasrvTicker, NotifyingKv)
    from greptimedb_tpu.meta.metasrv import Metasrv, MetasrvOptions

    os.makedirs(args.data_home, exist_ok=True)
    kv = NotifyingKv(FileKv(os.path.join(args.data_home, "meta_kv.json")))
    opts = MetasrvOptions(
        region_lease_s=args.region_lease,
        heartbeat_interval_s=args.heartbeat_interval,
        failure_threshold=args.failure_threshold)
    metasrv = Metasrv(kv, opts)
    host, port = _split_addr(args.bind_addr)
    service = MetaHttpService(metasrv, host, port)
    service.start()
    ticker = MetasrvTicker(metasrv, interval_s=min(
        1.0, opts.heartbeat_interval_s))
    ticker.start()
    print(f"greptimedb_tpu metasrv listening on http://{service.addr}",
          flush=True)
    _write_port_file(args.port_file, str(service.port))
    try:
        _wait_stop()
    finally:
        ticker.stop()
        service.stop()


def cmd_datanode(args):
    """Region-server service process with its OWN heartbeat task +
    region alive-keeper (reference cmd/src/datanode.rs +
    datanode/src/heartbeat.rs:47-183, alive_keeper.rs:49-112)."""
    # a chip belongs to one process, and that process is the frontend /
    # standalone server: a datanode scans and decodes on the CPU, so pin
    # it there before any backend init
    os.environ["JAX_PLATFORMS"] = "cpu"
    from greptimedb_tpu.cluster.datanode_service import DatanodeService
    from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine

    engine = RegionEngine(EngineConfig(
        data_dir=args.data_home, wal_backend="remote", write_workers=2))
    host, port = _split_addr(args.rpc_addr)
    svc = DatanodeService(args.node_id, engine, args.metasrv,
                          rpc_host=host, rpc_port=port,
                          heartbeat_interval_s=args.heartbeat_interval)
    svc.start()
    print(f"greptimedb_tpu datanode {args.node_id} serving regions on "
          f"grpc://{svc.addr} (metasrv {args.metasrv})", flush=True)
    _write_port_file(args.port_file, str(svc.server.port))
    try:
        _wait_stop()
    finally:
        svc.stop()


def cmd_flownode(args):
    """Continuous-aggregation service process (reference
    cmd/src/flownode.rs + flow/src/adapter.rs:507-527 run_available
    loop): builds a frontend-style engine over the remote metadata
    plane and ticks every flow on an interval. Flows created through
    any frontend are visible here via the shared KV."""
    import threading

    from greptimedb_tpu.cluster.frontend import build_frontend

    qe, nodes = build_frontend(args.metasrv)
    flow = qe.flow_engine
    stop = threading.Event()

    def loop():
        while not stop.wait(args.tick_interval):
            try:
                for db_row in qe.execute_one("SHOW DATABASES").rows():
                    out = flow.run_available(db=db_row[0])
                    if out:
                        print(f"flownode: ticked {out}", flush=True)
            except Exception:  # noqa: BLE001 — loop must never die
                import traceback

                traceback.print_exc()

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    print(f"greptimedb_tpu flownode ticking every {args.tick_interval}s "
          f"(metasrv {args.metasrv})", flush=True)
    _write_port_file(args.port_file, "0")
    try:
        _wait_stop()
    finally:
        stop.set()
        nodes.close()


def cmd_frontend(args):
    """Stateless query-serving process over remote metadata + remote
    regions (reference cmd/src/frontend.rs)."""
    from greptimedb_tpu.cluster.frontend import build_frontend
    from greptimedb_tpu.servers import HttpServer

    _announce_device("frontend")
    qe, nodes = build_frontend(args.metasrv)
    host, port = _split_addr(args.http_addr)
    http_server = HttpServer(qe, host, port)
    actual = http_server.start()
    print(f"greptimedb_tpu frontend listening on http://{host}:{actual} "
          f"(metasrv {args.metasrv})", flush=True)
    _write_port_file(args.port_file, str(actual))
    try:
        _wait_stop()
    finally:
        http_server.stop()
        nodes.close()


def _qi(name: str) -> str:
    """Quote an identifier for SQL (reserved words, dashes, ...)."""
    return '"' + name.replace('"', '""') + '"'


def _qs(text: str) -> str:
    """Quote a string/path literal."""
    return "'" + text.replace("'", "''") + "'"


def cmd_export(args):
    """Backup: schemas (SHOW CREATE TABLE) + data (COPY DATABASE TO
    parquet), one subdirectory per database (reference cli export,
    cmd/src/cli/export.rs:44-119)."""
    from greptimedb_tpu.query.engine import QueryContext

    engine, qe = build_standalone(args.data_home)
    try:
        os.makedirs(args.output_dir, exist_ok=True)
        dbs = [args.db] if args.db else [
            r[0] for r in qe.execute_one("SHOW DATABASES").rows()
            if r[0] != "information_schema"
        ]
        for db in dbs:
            ctx = QueryContext(db=db)
            out = os.path.join(args.output_dir, db)
            os.makedirs(out, exist_ok=True)
            tables = qe.catalog.list_tables(db)
            ddl = []
            for t in sorted(tables):
                r = qe.execute_one(f"SHOW CREATE TABLE {_qi(t)}", ctx)
                ddl.append(r.rows()[0][1] + ";\n")
            with open(os.path.join(out, "create_tables.sql"), "w") as f:
                f.write("\n".join(ddl))
            n = qe.execute_one(
                f"COPY DATABASE {_qi(db)} TO {_qs(out)} WITH (format = 'parquet')",
                ctx).affected_rows
            print(f"exported {db}: {len(tables)} tables, {n} rows -> {out}")
    finally:
        engine.close()


def cmd_import(args):
    """Restore a cli-export dump: run the DDL file, then COPY DATABASE
    FROM the parquet directory."""
    from greptimedb_tpu.query.engine import QueryContext

    engine, qe = build_standalone(args.data_home)
    try:
        for db in sorted(os.listdir(args.input_dir)):
            src = os.path.join(args.input_dir, db)
            if not os.path.isdir(src):
                continue
            qe.execute_one(f"CREATE DATABASE IF NOT EXISTS {_qi(db)}")
            ctx = QueryContext(db=db)
            ddl_path = os.path.join(src, "create_tables.sql")
            if os.path.exists(ddl_path):
                with open(ddl_path) as f:
                    sql = f.read()
                if sql.strip():
                    qe.execute_sql(sql, ctx)
            n = qe.execute_one(
                f"COPY DATABASE {_qi(db)} FROM {_qs(src)} WITH (format = 'parquet')",
                ctx).affected_rows
            print(f"imported {db}: {n} rows")
    finally:
        engine.close()


def cmd_repl(args):
    engine, qe = build_standalone(args.data_home)
    print("greptimedb_tpu REPL — SQL or TQL, \\q to quit")
    try:
        while True:
            try:
                line = input("sql> ")
            except EOFError:
                break
            if line.strip() in ("\\q", "exit", "quit"):
                break
            if not line.strip():
                continue
            try:
                r = qe.execute_one(line)
                if r.is_query:
                    print("\t".join(r.names))
                    for row in r.rows()[:100]:
                        print("\t".join(str(v) for v in row))
                    if r.num_rows > 100:
                        print(f"... ({r.num_rows} rows)")
                else:
                    print(f"OK, {r.affected_rows} rows affected")
            except Exception as e:  # noqa: BLE001 — REPL boundary
                print(f"error: {e}")
    finally:
        engine.close()


def main(argv=None):
    parser = argparse.ArgumentParser(prog="greptimedb_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_sa = sub.add_parser("standalone", help="run the standalone server")
    sa_sub = p_sa.add_subparsers(dest="subcmd", required=True)
    p_start = sa_sub.add_parser("start")
    p_start.add_argument("--data-home", default="")
    p_start.add_argument("--http-addr", default="")
    p_start.add_argument("-c", "--config-file", default=None,
                         help="layered TOML config (defaults < file < "
                              "GREPTIMEDB_TPU__* env < flags)")
    p_start.set_defaults(fn=cmd_standalone)

    p_ms = sub.add_parser("metasrv", help="run the metadata-plane service")
    ms_sub = p_ms.add_subparsers(dest="subcmd", required=True)
    p_ms_start = ms_sub.add_parser("start")
    p_ms_start.add_argument("--data-home", required=True)
    p_ms_start.add_argument("--bind-addr", default="127.0.0.1:4002")
    p_ms_start.add_argument("--region-lease", type=float, default=9.0)
    p_ms_start.add_argument("--heartbeat-interval", type=float, default=3.0)
    p_ms_start.add_argument("--failure-threshold", type=float, default=8.0)
    p_ms_start.add_argument("--port-file", default="")
    p_ms_start.set_defaults(fn=cmd_metasrv)

    p_dn = sub.add_parser("datanode", help="run a region-server datanode")
    dn_sub = p_dn.add_subparsers(dest="subcmd", required=True)
    p_dn_start = dn_sub.add_parser("start")
    p_dn_start.add_argument("--node-id", required=True)
    p_dn_start.add_argument("--metasrv", required=True,
                            help="metasrv HTTP addr, host:port")
    p_dn_start.add_argument("--data-home", required=True,
                            help="SHARED storage path (object-store "
                                 "deployment shape; WAL is remote)")
    p_dn_start.add_argument("--rpc-addr", default="127.0.0.1:0")
    p_dn_start.add_argument("--heartbeat-interval", type=float, default=3.0)
    p_dn_start.add_argument("--port-file", default="",
                            help="write the bound Flight port here")
    p_dn_start.set_defaults(fn=cmd_datanode)

    p_fe = sub.add_parser("frontend", help="run a query-serving frontend")
    fe_sub = p_fe.add_subparsers(dest="subcmd", required=True)
    p_fe_start = fe_sub.add_parser("start")
    p_fe_start.add_argument("--metasrv", required=True)
    p_fe_start.add_argument("--http-addr", default="127.0.0.1:4000")
    p_fe_start.add_argument("--port-file", default="")
    p_fe_start.set_defaults(fn=cmd_frontend)

    p_fn = sub.add_parser("flownode",
                          help="run a continuous-aggregation flownode")
    fn_sub = p_fn.add_subparsers(dest="subcmd", required=True)
    p_fn_start = fn_sub.add_parser("start")
    p_fn_start.add_argument("--metasrv", required=True)
    p_fn_start.add_argument("--tick-interval", type=float, default=1.0)
    p_fn_start.add_argument("--port-file", default="")
    p_fn_start.set_defaults(fn=cmd_flownode)

    p_repl = sub.add_parser("repl", help="interactive SQL/TQL shell")
    p_repl.add_argument("--data-home", default="./greptimedb_tpu_data")
    p_repl.set_defaults(fn=cmd_repl)

    p_dump = sub.add_parser("dump-config",
                            help="print the documented example TOML config")
    p_dump.set_defaults(fn=cmd_dump_config)

    p_exp = sub.add_parser("export", help="dump schemas + parquet data")
    p_exp.add_argument("--data-home", default="./greptimedb_tpu_data")
    p_exp.add_argument("--output-dir", required=True)
    p_exp.add_argument("--db", default=None,
                       help="one database (default: all)")
    p_exp.set_defaults(fn=cmd_export)

    p_imp = sub.add_parser("import", help="restore a cli-export dump")
    p_imp.add_argument("--data-home", default="./greptimedb_tpu_data")
    p_imp.add_argument("--input-dir", required=True)
    p_imp.set_defaults(fn=cmd_import)

    args = parser.parse_args(argv)
    # every service role stamps trace_id= on its log records so logs,
    # metrics, and spans join on one id
    from greptimedb_tpu.utils.tracing import install_trace_logging

    install_trace_logging()
    args.fn(args)


if __name__ == "__main__":
    main()

"""MySQL wire-protocol server.

Mirrors reference src/servers/src/mysql (opensrv-mysql `AsyncMysqlShim`
impl, handler.rs:153, on_query :357): a real MySQL client can connect,
authenticate (any credentials accepted unless a UserProvider is installed),
and run SQL against the query engine. Implements the text protocol
(protocol 41, handshake v10): COM_QUERY, COM_PING, COM_INIT_DB, COM_QUIT,
plus enough of the federated-query shims (SELECT @@version_comment and
friends, federated.rs analog) for standard clients to connect cleanly.
Prepared statements (handler.rs:153 on_prepare/on_execute): binary
COM_STMT_PREPARE / COM_STMT_EXECUTE / COM_STMT_CLOSE / COM_STMT_RESET
with typed parameter decoding and binary resultset rows — the default
path for connector libraries and ORMs.

EOF-style result sets (CLIENT_DEPRECATE_EOF not advertised) keep encoding
simple and broadly compatible.
"""

from __future__ import annotations

import socket
import socketserver
import struct
import threading
from typing import Optional

from greptimedb_tpu.fault import Unavailable
from greptimedb_tpu.fault.retry import Cancelled, DeadlineExceeded
from greptimedb_tpu.query.engine import QueryContext, QueryEngine

CLIENT_PROTOCOL_41 = 0x00000200
CLIENT_CONNECT_WITH_DB = 0x00000008
CLIENT_PLUGIN_AUTH = 0x00080000
CLIENT_SECURE_CONNECTION = 0x00008000
CLIENT_LONG_PASSWORD = 0x00000001
CLIENT_TRANSACTIONS = 0x00002000
CLIENT_SSL = 0x00000800

SERVER_CAPS = (
    CLIENT_PROTOCOL_41
    | CLIENT_CONNECT_WITH_DB
    | CLIENT_PLUGIN_AUTH
    | CLIENT_SECURE_CONNECTION
    | CLIENT_LONG_PASSWORD
    | CLIENT_TRANSACTIONS
)

COM_QUIT = 0x01
COM_INIT_DB = 0x02
COM_QUERY = 0x03
COM_PING = 0x0E
COM_STMT_PREPARE = 0x16
COM_STMT_EXECUTE = 0x17
COM_STMT_CLOSE = 0x19
COM_STMT_RESET = 0x1A

MYSQL_TYPE_TINY = 1
MYSQL_TYPE_SHORT = 2
MYSQL_TYPE_LONG = 3
MYSQL_TYPE_FLOAT = 4
MYSQL_TYPE_LONGLONG = 8
MYSQL_TYPE_INT24 = 9
MYSQL_TYPE_DOUBLE = 5
MYSQL_TYPE_NULL = 6
MYSQL_TYPE_VAR_STRING = 253
MYSQL_TYPE_STRING = 254
MYSQL_TYPE_BLOB = 252
MYSQL_TYPE_TINY_BLOB = 249
MYSQL_TYPE_MEDIUM_BLOB = 250
MYSQL_TYPE_LONG_BLOB = 251
MYSQL_TYPE_TIMESTAMP = 7
MYSQL_TYPE_DATETIME = 12
MYSQL_TYPE_DATE = 10
MYSQL_TYPE_VARCHAR = 15
MYSQL_TYPE_YEAR = 13
MYSQL_TYPE_DECIMAL = 0
MYSQL_TYPE_NEWDECIMAL = 246


# wire fragments and row writers live in servers/encode.py
from greptimedb_tpu.servers.encode import (  # noqa: E402
    _coldef,
    _eof,
    encode_mysql_result,
    encode_mysql_rows,
    lenc_int,
)


class _PacketIO:
    """MySQL packet framing: 3-byte little-endian length + sequence id."""

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.seq = 0

    def read_packet(self) -> Optional[bytes]:
        header = self._read_exact(4)
        if header is None:
            return None
        length = header[0] | (header[1] << 8) | (header[2] << 16)
        self.seq = (header[3] + 1) & 0xFF
        body = self._read_exact(length)
        return body

    def _read_exact(self, n: int) -> Optional[bytes]:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf

    def send_packet(self, payload: bytes) -> None:
        while True:
            chunk, payload = payload[: 0xFFFFFF], payload[0xFFFFFF:]
            header = struct.pack("<I", len(chunk))[:3] + bytes([self.seq])
            self.seq = (self.seq + 1) & 0xFF
            self.sock.sendall(header + chunk)
            if len(chunk) < 0xFFFFFF:
                break

    def reset_seq(self) -> None:
        self.seq = 0


class _Session(socketserver.BaseRequestHandler):
    def handle(self):
        import socket as _socket

        # wire-protocol packets go out in several send()s per response;
        # Nagle + delayed-ACK adds ~40 ms per round-trip otherwise
        self.request.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
        io = _PacketIO(self.request)
        server: MysqlServer = self.server.owner  # type: ignore[attr-defined]
        # ---- handshake v10 ----
        import secrets
        caps_offered = SERVER_CAPS | (CLIENT_SSL if server.tls else 0)
        salt = bytes(secrets.choice(range(0x21, 0x7F)) for _ in range(20))
        hs = (
            b"\x0a"  # protocol version 10
            + b"greptimedb-tpu-8.0\x00"
            + struct.pack("<I", threading.get_ident() & 0xFFFFFFFF)
            + salt[:8]
            + b"\x00"
            + struct.pack("<H", caps_offered & 0xFFFF)
            + bytes([0x21])  # utf8_general_ci
            + struct.pack("<H", 0x0002)  # status: autocommit
            + struct.pack("<H", (caps_offered >> 16) & 0xFFFF)
            + bytes([21])  # auth plugin data len
            + b"\x00" * 10
            + salt[8:]
            + b"\x00"
            + b"mysql_native_password\x00"
        )
        io.send_packet(hs)
        resp = io.read_packet()
        if resp is None:
            return
        # SSLRequest: caps with CLIENT_SSL set and NO username — the
        # client upgrades the connection before re-sending the real
        # HandshakeResponse over TLS (protocol::connection_phase)
        tls_active = False
        if len(resp) >= 4 and len(resp) < 36 \
                and struct.unpack("<I", resp[:4])[0] & CLIENT_SSL:
            if server.tls is None:
                return  # offered no TLS but client demanded it
            self.request = server.tls_context.wrap_socket(
                self.request, server_side=True)
            io.sock = self.request  # sequence id continues
            tls_active = True
            resp = io.read_packet()
            if resp is None:
                return
        if server.tls is not None and server.tls.mode == "require" \
                and not tls_active:
            io.send_packet(_err(3159, "HY000", "connections must use TLS"))
            return
        # HandshakeResponse41: capabilities(4) maxpkt(4) charset(1) filler(23)
        # then NUL-terminated username
        if len(resp) < 32:
            return
        db = "public"
        user = ""
        auth_resp = b""
        try:
            caps = struct.unpack("<I", resp[:4])[0]
            pos = 32
            end = resp.index(b"\x00", pos)
            user = resp[pos:end].decode()
            pos = end + 1
            # auth response (lenenc when CLIENT_SECURE_CONNECTION)
            if pos < len(resp):
                alen = resp[pos]
                auth_resp = resp[pos + 1:pos + 1 + alen]
                pos += 1 + alen
            if caps & CLIENT_CONNECT_WITH_DB and pos < len(resp):
                end = resp.index(b"\x00", pos)
                db = resp[pos:end].decode() or "public"
        except (ValueError, IndexError):
            pass
        user_info = None
        if server.user_provider is not None:
            from greptimedb_tpu.auth import AuthError
            try:
                if hasattr(server.user_provider, "authenticate_mysql"):
                    user_info = server.user_provider.authenticate_mysql(
                        user, auth_resp, salt)
                elif not server.user_provider.allow(user):
                    raise AuthError(f"access denied for user {user!r}")
            except AuthError:
                io.send_packet(
                    _err(1045, "28000", f"Access denied for user {user!r}"))
                return
        io.send_packet(_ok())
        from greptimedb_tpu.session import Channel
        ctx = QueryContext(db=db, channel=Channel.MYSQL, user=user_info,
                           tenant=getattr(user_info, "username", None)
                           or (user or None))
        # prepared-statement registry, per connection (handler.rs:153
        # keeps a SqlPlan map keyed by stmt id the same way); the third
        # slot caches parameter types — libmysqlclient connectors send the
        # type block only on the FIRST execute (new-params-bound=1) and
        # omit it on re-executes
        stmts: dict[int, list] = {}
        next_stmt_id = 1
        # ---- command loop ----
        while True:
            io.reset_seq()
            pkt = io.read_packet()
            if pkt is None or not pkt:
                return
            cmd, body = pkt[0], pkt[1:]
            if cmd == COM_QUIT:
                return
            if cmd == COM_PING:
                io.send_packet(_ok())
                continue
            if cmd == COM_INIT_DB:
                ctx = ctx.with_db(body.decode() or "public")
                io.send_packet(_ok())
                continue
            if cmd == COM_STMT_PREPARE:
                sql = body.decode("utf-8", "replace").strip().rstrip(";")
                n_params = _count_params(sql)
                stmt_id = next_stmt_id
                next_stmt_id += 1
                stmts[stmt_id] = [sql, n_params, None]
                _send_prepare_ok(io, stmt_id, n_params)
                continue
            if cmd == COM_STMT_EXECUTE:
                try:
                    stmt_id = struct.unpack("<I", body[:4])[0]
                    if stmt_id not in stmts:
                        io.send_packet(
                            _err(1243, "HY000", f"unknown stmt {stmt_id}"))
                        continue
                    sql, n_params, cached_types = stmts[stmt_id]
                    params, types = _decode_exec_params(
                        body, n_params, cached_types)
                    stmts[stmt_id][2] = types
                    bound = _bind_params(sql, params)
                    result = _dispatch(server.query_engine, bound, ctx,
                                       sock=self.request)
                except DeadlineExceeded as e:
                    # ER_QUERY_TIMEOUT: max_execution_time shape
                    io.send_packet(_err(3024, "HY000", str(e)[:400]))
                    continue
                except Cancelled as e:
                    # ER_QUERY_INTERRUPTED: KILL QUERY shape
                    io.send_packet(_err(1317, "70100", str(e)[:400]))
                    continue
                except Unavailable as e:
                    # typed overload/degradation: 1040 tells clients to
                    # back off and retry, not report a syntax error
                    io.send_packet(_err(1040, "08004", str(e)[:400]))
                    continue
                except Exception as e:  # noqa: BLE001 — wire must stay up
                    io.send_packet(_err(1064, "42000", str(e)[:400]))
                    continue
                _send_result(io, result, binary=True)
                continue
            if cmd == COM_STMT_CLOSE:
                stmts.pop(struct.unpack("<I", body[:4])[0], None)
                continue  # no response, per protocol
            if cmd == 0x18:  # COM_STMT_SEND_LONG_DATA
                # protocol: NO response — answering would desync the
                # connection (client pipelines execute right behind it).
                # Long-data chunks aren't accumulated; the subsequent
                # execute fails cleanly if it references the missing param.
                continue
            if cmd == COM_STMT_RESET:
                io.send_packet(_ok())
                continue
            if cmd != COM_QUERY:
                io.send_packet(_err(1047, "08S01", f"unknown command {cmd}"))
                continue
            sql = body.decode("utf-8", "replace").strip().rstrip(";")
            try:
                result = _dispatch(server.query_engine, sql, ctx,
                                   sock=self.request)
            except DeadlineExceeded as e:
                io.send_packet(_err(3024, "HY000", str(e)[:400]))
                continue
            except Cancelled as e:
                io.send_packet(_err(1317, "70100", str(e)[:400]))
                continue
            except Unavailable as e:
                io.send_packet(_err(1040, "08004", str(e)[:400]))
                continue
            except Exception as e:  # noqa: BLE001 — wire must stay up
                io.send_packet(_err(1064, "42000", str(e)[:400]))
                continue
            _send_result(io, result)


def _dispatch(engine: QueryEngine, sql: str, ctx: QueryContext,
              sock=None):
    """Run the SQL, shimming the session variables standard clients probe
    on connect (reference servers/src/mysql/federated.rs)."""
    low = sql.lower()
    if low.startswith(("commit", "rollback", "begin", "start transaction")):
        return None  # accepted, no-op
    if low.startswith("set "):
        # SET now reaches the engine: _set_var stores session vars in
        # the connection-scoped ctx.extensions, which is how
        # `SET max_execution_time = 500` arms the deadline plane for
        # every later statement on this connection. Client-compat vars
        # the parser/engine can't digest stay an accepted no-op.
        try:
            engine.execute_one(sql, ctx)
        except Unavailable:
            raise  # typed degradation must reach the wire mapping
        except Exception:  # noqa: BLE001 — connector-compat vars vary
            pass
        return None
    if "@@" in low and low.startswith("select"):
        # SELECT @@version_comment / @@max_allowed_packet / ...
        names, vals = [], []
        for var in low.replace("select", "", 1).split(","):
            var = var.strip().split(" ")[0]
            name = var.replace("@@", "").split(".")[-1]
            names.append("@@" + name)
            # a var this connection SET (e.g. max_execution_time)
            # reads back its session value, not the static shim
            vals.append(str(ctx.extensions.get(
                name, _SESSION_VARS.get(name, ""))))
        return ("rows", names, [vals])
    from greptimedb_tpu.utils import tracing

    # the MySQL wire has no headers: a W3C traceparent rides a leading
    # SQL comment instead. Each statement is one request-root span; the
    # connection-scoped ctx adopts the per-statement trace so the
    # engine (and its spans/ledger) join it.
    with tracing.request_span(
            "mysql:query",
            traceparent=tracing.traceparent_from_sql(sql)):
        ctx.trace_id = tracing.current_trace_id()
        from greptimedb_tpu.utils import deadline

        # per-statement cancel token: a client that hangs up mid-query
        # cancels the work (EOF on the session socket); the engine arms
        # the deadline from max_execution_time / config defaults
        token = deadline.CancelToken()
        ctx.cancel_token = token
        stop_watch = deadline.watch_disconnect(sock, token) \
            if sock is not None else (lambda: None)
        try:
            res = engine.execute_one(sql, ctx)
        finally:
            stop_watch()
            ctx.cancel_token = None
        if not res.is_query:
            return ("affected", res.affected_rows)
        # the QueryResult itself, NOT materialized rows: the encoder
        # builds them (encode_mysql_result), through the single
        # flight's memo where the result carries one
        return ("result", res)


_SESSION_VARS = {
    "version_comment": "greptimedb-tpu",
    "max_allowed_packet": "16777216",
    "session.auto_increment_increment": "1",
    "auto_increment_increment": "1",
    "character_set_client": "utf8",
    "character_set_connection": "utf8",
    "character_set_results": "utf8",
    "character_set_server": "utf8",
    "collation_server": "utf8_general_ci",
    "collation_connection": "utf8_general_ci",
    "init_connect": "",
    "interactive_timeout": "28800",
    "license": "Apache-2.0",
    "lower_case_table_names": "0",
    "max_execution_time": "0",
    "net_write_timeout": "60",
    "performance_schema": "0",
    "sql_mode": "",
    "system_time_zone": "UTC",
    "time_zone": "UTC",
    "tx_isolation": "REPEATABLE-READ",
    "transaction_isolation": "REPEATABLE-READ",
    "wait_timeout": "28800",
}


def _count_params(sql: str) -> int:
    """Count `?` placeholders outside string literals, backtick-quoted
    identifiers, and `--` comments."""
    n = 0
    in_str: Optional[str] = None
    in_comment = False
    i = 0
    while i < len(sql):
        c = sql[i]
        if in_comment:
            if c == "\n":
                in_comment = False
        elif in_str is not None:
            if c == in_str:
                # '' escape inside a string stays inside it
                if i + 1 < len(sql) and sql[i + 1] == in_str:
                    i += 1
                else:
                    in_str = None
        elif c == "-" and sql[i:i + 2] == "--":
            in_comment = True
        elif c in ("'", '"', "`"):
            in_str = c
        elif c == "?":
            n += 1
        i += 1
    return n


def _send_prepare_ok(io: _PacketIO, stmt_id: int, n_params: int) -> None:
    """COM_STMT_PREPARE_OK. Result-column count is reported as 0 — the
    execute response carries its own authoritative column metadata, which
    is what client libraries actually read (the reference defers planning
    the same way, handler.rs:163 do_describe on a param-less dummy)."""
    io.send_packet(
        b"\x00"
        + struct.pack("<I", stmt_id)
        + struct.pack("<H", 0)          # columns (see docstring)
        + struct.pack("<H", n_params)
        + b"\x00"                        # filler
        + struct.pack("<H", 0)          # warnings
    )
    if n_params:
        for i in range(n_params):
            io.send_packet(_coldef(f"?{i}", MYSQL_TYPE_VAR_STRING))
        io.send_packet(_eof())


_LENC_TYPES = frozenset({
    MYSQL_TYPE_VAR_STRING, MYSQL_TYPE_STRING, MYSQL_TYPE_VARCHAR,
    MYSQL_TYPE_BLOB, MYSQL_TYPE_TINY_BLOB, MYSQL_TYPE_MEDIUM_BLOB,
    MYSQL_TYPE_LONG_BLOB, MYSQL_TYPE_DECIMAL, MYSQL_TYPE_NEWDECIMAL,
})


def _read_lenc(buf: bytes, pos: int) -> tuple[int, int]:
    first = buf[pos]
    if first < 251:
        return first, pos + 1
    if first == 0xFC:
        return struct.unpack_from("<H", buf, pos + 1)[0], pos + 3
    if first == 0xFD:
        return int.from_bytes(buf[pos + 1:pos + 4], "little"), pos + 4
    return struct.unpack_from("<Q", buf, pos + 1)[0], pos + 9


def _decode_exec_params(body: bytes, n_params: int,
                        cached_types: Optional[list] = None) -> tuple:
    """Decode COM_STMT_EXECUTE binary parameter values (protocol binary
    value encoding; the subset real connectors send). Returns
    (params, types) — callers cache `types` per statement because the
    type block is only sent when new-params-bound=1 (first execute)."""
    if n_params == 0:
        return [], cached_types
    pos = 4 + 1 + 4  # stmt_id, flags, iteration_count
    nb_len = (n_params + 7) // 8
    null_bitmap = body[pos:pos + nb_len]
    pos += nb_len
    new_bound = body[pos]
    pos += 1
    types = []
    if new_bound:
        for _ in range(n_params):
            types.append((body[pos], body[pos + 1]))
            pos += 2
    elif cached_types is not None:
        types = cached_types
    else:
        raise ValueError(
            "execute with new-params-bound=0 but no types cached")
    params: list = []
    for i in range(n_params):
        if null_bitmap[i // 8] & (1 << (i % 8)):
            params.append(None)
            continue
        ftype, flags = types[i]
        unsigned = bool(flags & 0x80)
        if ftype == MYSQL_TYPE_NULL:
            params.append(None)
        elif ftype == MYSQL_TYPE_TINY:
            v = body[pos]
            params.append(v if unsigned else struct.unpack("<b", body[pos:pos+1])[0])
            pos += 1
        elif ftype in (MYSQL_TYPE_SHORT, MYSQL_TYPE_YEAR):
            fmt = "<H" if unsigned else "<h"
            params.append(struct.unpack_from(fmt, body, pos)[0])
            pos += 2
        elif ftype in (MYSQL_TYPE_LONG, MYSQL_TYPE_INT24):
            fmt = "<I" if unsigned else "<i"
            params.append(struct.unpack_from(fmt, body, pos)[0])
            pos += 4
        elif ftype == MYSQL_TYPE_LONGLONG:
            fmt = "<Q" if unsigned else "<q"
            params.append(struct.unpack_from(fmt, body, pos)[0])
            pos += 8
        elif ftype == MYSQL_TYPE_FLOAT:
            params.append(struct.unpack_from("<f", body, pos)[0])
            pos += 4
        elif ftype == MYSQL_TYPE_DOUBLE:
            params.append(struct.unpack_from("<d", body, pos)[0])
            pos += 8
        elif ftype in (MYSQL_TYPE_TIMESTAMP, MYSQL_TYPE_DATETIME,
                       MYSQL_TYPE_DATE):
            dlen = body[pos]
            pos += 1
            y = mo = d = h = mi = s = us = 0
            if dlen >= 4:
                y, mo, d = struct.unpack_from("<HBB", body, pos)
            if dlen >= 7:
                h, mi, s = struct.unpack_from("<BBB", body, pos + 4)
            if dlen >= 11:
                us = struct.unpack_from("<I", body, pos + 7)[0]
            pos += dlen
            if dlen <= 4:
                params.append(f"{y:04d}-{mo:02d}-{d:02d}")
            else:
                frac = f".{us:06d}" if us else ""
                params.append(
                    f"{y:04d}-{mo:02d}-{d:02d} {h:02d}:{mi:02d}:{s:02d}{frac}")
        elif ftype in _LENC_TYPES:
            ln, pos = _read_lenc(body, pos)
            params.append(body[pos:pos + ln].decode("utf-8", "replace"))
            pos += ln
        else:
            raise ValueError(f"unsupported parameter type {ftype}")
    return params, types


def _bind_params(sql: str, params: list) -> str:
    """Substitute decoded values for `?` placeholders (outside string
    literals, backticked identifiers, and `--` comments), rendering SQL
    literals with proper quoting."""
    out = []
    it = iter(params)
    in_str: Optional[str] = None
    in_comment = False
    i = 0
    while i < len(sql):
        c = sql[i]
        if in_comment:
            out.append(c)
            if c == "\n":
                in_comment = False
        elif in_str is not None:
            out.append(c)
            if c == in_str:
                if i + 1 < len(sql) and sql[i + 1] == in_str:
                    out.append(sql[i + 1])
                    i += 1
                else:
                    in_str = None
        elif c == "-" and sql[i:i + 2] == "--":
            in_comment = True
            out.append(c)
        elif c in ("'", '"', "`"):
            in_str = c
            out.append(c)
        elif c == "?":
            try:
                v = next(it)
            except StopIteration:
                raise ValueError("not enough parameters bound") from None
            out.append(_sql_literal(v))
        else:
            out.append(c)
        i += 1
    return "".join(out)


def _sql_literal(v) -> str:
    if v is None:
        return "NULL"
    if isinstance(v, bool):
        return "TRUE" if v else "FALSE"
    if isinstance(v, (int, float)):
        return repr(v)
    # this dialect's lexer treats backslash as a literal character — the
    # ONLY escape is the doubled single-quote (sql/lexer.py string regex)
    s = str(v).replace("'", "''")
    return f"'{s}'"


def _ok(affected: int = 0) -> bytes:
    return b"\x00" + lenc_int(affected) + lenc_int(0) + struct.pack("<H", 0x0002) + struct.pack("<H", 0)


def _err(code: int, state: str, msg: str) -> bytes:
    return b"\xff" + struct.pack("<H", code) + b"#" + state.encode() + msg.encode()


def _send_result(io: _PacketIO, result, binary: bool = False) -> None:
    """Text resultset for COM_QUERY; binary-protocol rows for
    COM_STMT_EXECUTE (all columns declared VAR_STRING, so binary values
    are length-encoded strings — connectors convert from the metadata).
    The session loop only stamps sequence ids and writes."""
    if result is None:
        io.send_packet(_ok())
        return
    if result[0] == "affected":
        io.send_packet(_ok(result[1]))
        return
    if result[0] == "result":
        packets = encode_mysql_result(result[1], binary)
    else:
        _, names, rows = result
        packets = encode_mysql_rows(names, rows, binary)
    for p in packets:
        io.send_packet(p)


class _TcpServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class MysqlServer:
    """Threaded MySQL server over the shared QueryEngine."""

    def __init__(self, query_engine: QueryEngine, host: str = "127.0.0.1",
                 port: int = 4002, user_provider=None, tls=None):
        self.query_engine = query_engine
        self.user_provider = user_provider
        self.tls = tls
        self.tls_context = tls.make_context() if tls is not None else None
        self._server = _TcpServer((host, port), _Session)
        self._server.owner = self
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._server.serve_forever, daemon=True)
        self._thread.start()

    def shutdown(self) -> None:
        self._server.shutdown()
        self._server.server_close()

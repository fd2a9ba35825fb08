"""Postgres wire-protocol server (simple + extended protocol, text
format, optional cleartext-password auth).

Reference counterpart: ``src/utils/pgwire`` (``pg_serve()``,
pg_server.rs:338; extended-protocol state machine pg_protocol.rs:340).

Simple flow:
    StartupMessage → [AuthenticationCleartextPassword → Password] →
    AuthenticationOk → ParameterStatus* → BackendKeyData →
    ReadyForQuery → (Query → RowDescription → DataRow* →
    CommandComplete → ReadyForQuery)*

Extended flow (what psycopg/JDBC default to):
    Parse → Bind → Describe → Execute → Sync
Parameters are text-format; ``$n`` placeholders substitute as SQL
literals at Bind time (the engine plans per-execution, so there is no
plan cache to parameterize — the reference's prepared-statement reuse
is a latency optimization this engine gets from its jit cache
instead).  Describe(portal) runs the query eagerly and caches rows so
RowDescription can be answered exactly; Execute drains the cache.

SASL/md5 auth and SSL stay unsupported; SSLRequest is answered 'N' so
clients fall back cleanly.
"""

from __future__ import annotations

import contextlib
import socket
import socketserver
import struct
import threading

from risingwave_tpu.common.trace import GLOBAL_TRACE
from risingwave_tpu.common.types import DataType

# pg type OIDs for the text protocol
_OID = {
    DataType.BOOLEAN: 16,
    DataType.INT16: 21,
    DataType.INT32: 23,
    DataType.INT64: 20,
    DataType.FLOAT32: 700,
    DataType.FLOAT64: 701,
    DataType.DECIMAL: 1700,
    DataType.VARCHAR: 1043,
    DataType.BYTEA: 17,
    DataType.DATE: 1082,
    DataType.TIME: 1083,
    DataType.TIMESTAMP: 1114,
    DataType.TIMESTAMPTZ: 1184,
    DataType.INTERVAL: 1186,
    DataType.SERIAL: 20,
}

PROTOCOL_VERSION = 196608       # 3.0
SSL_REQUEST = 80877103
CANCEL_REQUEST = 80877102


def _msg(tag: bytes, payload: bytes) -> bytes:
    return tag + struct.pack("!I", len(payload) + 4) + payload


def _cstr(s: str) -> bytes:
    return s.encode() + b"\x00"


#: pg text-type oids whose params must stay quoted even when the value
#: looks numeric ('007' as varchar must not become integer 7)
_TEXT_OIDS = {25, 1043, 18, 19, 1042}


def _substitute_params(sql: str, params: list,
                       oids: "list[int] | None" = None) -> str:
    """Inline text-format parameter values as SQL literals at their
    ``$n`` sites (outside string literals).  A param whose Parse-time
    oid names a text type always quotes; otherwise numbers inline
    bare, everything else single-quotes with '' escaping; None →
    NULL."""
    import re as _re

    def lit(idx: int, v) -> str:
        if v is None:
            return "NULL"
        s = v.decode() if isinstance(v, bytes) else str(v)
        oid = oids[idx] if oids and idx < len(oids) else 0
        if oid not in _TEXT_OIDS \
                and _re.fullmatch(r"-?\d+(\.\d+)?", s):
            return s
        return "'" + s.replace("'", "''") + "'"

    out: list[str] = []
    i, n = 0, len(sql)
    in_str = False
    while i < n:
        ch = sql[i]
        if in_str:
            out.append(ch)
            if ch == "'":
                in_str = False
            i += 1
            continue
        if ch == "'":
            in_str = True
            out.append(ch)
            i += 1
            continue
        if ch == "$" and i + 1 < n and sql[i + 1].isdigit():
            j = i + 1
            while j < n and sql[j].isdigit():
                j += 1
            idx = int(sql[i + 1:j]) - 1
            if idx < 0 or idx >= len(params):
                raise ValueError(f"parameter ${idx + 1} not bound")
            out.append(lit(idx, params[idx]))
            i = j
            continue
        out.append(ch)
        i += 1
    return "".join(out)


class EngineLock:
    """The engine lock the served node's ticker, the pgwire sessions
    and the scrape share, with a count of the statements that want it:
    the ticker sends no window ahead of its barrier while one does
    (``Engine.tick``'s ``ahead``).  A statement is counted from asking
    for the lock to releasing it, so while the ticker holds the lock
    every statement counted is waiting for it."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._count = threading.Lock()
        self.statements = 0

    def acquire(self) -> bool:
        return self._lock.acquire()

    def release(self) -> None:
        self._lock.release()

    def __enter__(self):
        self._lock.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self._lock.release()
        return False

    @contextlib.contextmanager
    def statement(self):
        """Hold the lock for one statement, its wait in
        ``read.lock_wait``."""
        self._add(1)
        try:
            with GLOBAL_TRACE.held(self, "read.lock_wait"):
                yield
        finally:
            self._add(-1)

    def _add(self, step: int) -> None:
        with self._count:
            self.statements += step


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):  # noqa: C901 — the protocol state machine
        sock: socket.socket = self.request
        engine = self.server.engine
        lock = self.server.engine_lock
        f = sock.makefile("rwb")
        #: extended-protocol session state
        stmts: dict[str, str] = {}           # name -> sql
        portals: dict[str, dict] = {}        # name -> {sql, cols?, rows?}
        in_error = False                     # skip-until-Sync
        try:
            if not self._startup(f):
                return
            self._ready(f)
            while True:
                header = f.read(5)
                if len(header) < 5:
                    return
                tag, length = header[:1], struct.unpack("!I", header[1:])[0]
                body = f.read(length - 4)
                if tag == b"X":  # Terminate
                    return
                if tag == b"S":  # Sync — ends an extended batch
                    in_error = False
                    self._ready(f)
                    continue
                if in_error and tag in (b"P", b"B", b"D", b"E", b"C",
                                        b"H"):
                    continue  # discard until Sync (pg_protocol.rs:340)
                if tag == b"Q":
                    sql = body.rstrip(b"\x00").decode()
                    try:
                        self._statement(f, {"sql": sql}, with_desc=True)
                    except Exception as e:
                        self._error(f, str(e))
                    self._ready(f)
                    continue
                try:
                    if tag == b"P":  # Parse
                        name, off = self._take_cstr(body, 0)
                        sql, off = self._take_cstr(body, off)
                        noids = struct.unpack_from("!H", body, off)[0]
                        off += 2
                        oids = [
                            struct.unpack_from("!I", body,
                                               off + 4 * k)[0]
                            for k in range(noids)
                        ]
                        stmts[name] = (sql, oids)
                        f.write(_msg(b"1", b""))  # ParseComplete
                    elif tag == b"B":  # Bind
                        portal, off = self._take_cstr(body, 0)
                        sname, off = self._take_cstr(body, off)
                        nfmt = struct.unpack_from("!H", body, off)[0]
                        off += 2 + 2 * nfmt
                        nparams = struct.unpack_from("!H", body, off)[0]
                        off += 2
                        params: list = []
                        for _ in range(nparams):
                            ln = struct.unpack_from("!i", body, off)[0]
                            off += 4
                            if ln < 0:
                                params.append(None)
                            else:
                                params.append(body[off:off + ln])
                                off += ln
                        if sname not in stmts:
                            raise ValueError(
                                f"unknown prepared statement {sname!r}"
                            )
                        psql, poids = stmts[sname]
                        portals[portal] = {
                            "sql": _substitute_params(
                                psql, params, poids
                            ),
                        }
                        f.write(_msg(b"2", b""))  # BindComplete
                    elif tag == b"D":  # Describe
                        kind = body[:1]
                        name, _ = self._take_cstr(body, 1)
                        if kind == b"S":
                            if name not in stmts:
                                raise ValueError(
                                    f"unknown prepared statement "
                                    f"{name!r}"
                                )
                            dsql, doids = stmts[name]
                            nparams = max(self._count_params(dsql),
                                          len(doids))
                            pd = struct.pack("!H", nparams)
                            for k in range(nparams):
                                pd += struct.pack(
                                    "!I",
                                    doids[k] if k < len(doids) else 0,
                                )
                            f.write(_msg(b"t", pd))
                            # RowDescription for read-only statements:
                            # drivers on the describe-statement path
                            # (pgjdbc) need columns before Execute.
                            # Evaluated with NULL params — SELECTs have
                            # no side effects
                            verb = dsql.lstrip()[:8].lower()
                            if verb.startswith(("select", "show",
                                                "describe")):
                                trial = _substitute_params(
                                    dsql, [None] * nparams, doids
                                )
                                with lock.statement():
                                    cols, _ = engine.query(trial)
                                if cols:
                                    self._row_description(f, cols)
                                else:
                                    f.write(_msg(b"n", b""))
                            else:
                                f.write(_msg(b"n", b""))  # NoData
                        else:
                            p = portals.get(name)
                            if p is None:
                                raise ValueError(
                                    f"unknown portal {name!r}"
                                )
                            # eager execution so RowDescription is
                            # exact; Execute drains the cache
                            with lock.statement():
                                cols, rows = engine.query(p["sql"])
                            p["cols"], p["rows"] = cols, rows
                            if cols:
                                self._row_description(f, cols)
                            else:
                                f.write(_msg(b"n", b""))
                    elif tag == b"E":  # Execute
                        name, _ = self._take_cstr(body, 0)
                        p = portals.get(name)
                        if p is None:
                            raise ValueError(f"unknown portal {name!r}")
                        self._statement(f, p, with_desc=False)
                    elif tag == b"C":  # Close
                        kind = body[:1]
                        name, _ = self._take_cstr(body, 1)
                        (stmts if kind == b"S" else portals).pop(
                            name, None
                        )
                        f.write(_msg(b"3", b""))  # CloseComplete
                    elif tag == b"H":  # Flush
                        pass
                    else:
                        raise ValueError(
                            f"unsupported message {tag!r}"
                        )
                    f.flush()
                except Exception as e:
                    self._error(f, str(e))
                    in_error = True
        finally:
            f.close()

    def _statement(self, f, p: dict, with_desc: bool) -> None:
        """Run one statement (``Q``, or ``Execute`` of portal ``p``,
        whose rows an eager Describe may hold already) and write its
        results: one ``read-<n>`` span tree — the wait for the engine
        lock, ``engine.query`` under it, the rows going out."""
        engine = self.server.engine
        sql = p["sql"]
        with GLOBAL_TRACE.root(
                "read", "read", metrics=getattr(engine, "metrics", None),
                kind=sql.split(None, 1)[0].upper() if sql.strip() else "",
        ) as sp:
            if "rows" not in p:
                with self.server.engine_lock.statement():
                    with GLOBAL_TRACE.span("read.execute"):
                        p["cols"], p["rows"] = engine.query(sql)
            sp.set(rows=len(p["rows"] or ()))
            with GLOBAL_TRACE.span("read.send"):
                self._results(f, sql, p["cols"], p["rows"],
                              with_desc=with_desc)

    @staticmethod
    def _take_cstr(body: bytes, off: int) -> tuple[str, int]:
        end = body.index(b"\x00", off)
        return body[off:end].decode(), end + 1

    @staticmethod
    def _count_params(sql: str) -> int:
        import re as _re
        best = 0
        # the quoted-string alternative consumes literals first, so
        # $n inside strings never matches
        for m in _re.finditer(r"'[^']*'|\$(\d+)", sql):
            if m.group(1):
                best = max(best, int(m.group(1)))
        return best

    # -- protocol pieces -------------------------------------------------
    def _startup(self, f) -> bool:
        while True:
            raw = f.read(4)
            if len(raw) < 4:
                return False
            length = struct.unpack("!I", raw)[0]
            body = f.read(length - 4)
            code = struct.unpack("!I", body[:4])[0]
            if code == SSL_REQUEST:
                f.write(b"N")
                f.flush()
                continue
            if code == CANCEL_REQUEST:
                return False
            if code != PROTOCOL_VERSION:
                self._error(f, f"unsupported protocol {code}")
                return False
            break
        password = getattr(self.server, "password", None)
        if password is not None:
            # AuthenticationCleartextPassword (ref pg_protocol auth;
            # the reference also speaks md5/SASL — cleartext is the
            # interoperable floor every driver supports)
            f.write(_msg(b"R", struct.pack("!I", 3)))
            f.flush()
            header = f.read(5)
            if len(header) < 5 or header[:1] != b"p":
                return False
            length = struct.unpack("!I", header[1:])[0]
            got = f.read(length - 4).rstrip(b"\x00").decode()
            if got != password:
                payload = b"SFATAL\x00" + b"C28P01\x00" + b"M" + _cstr(
                    "password authentication failed"
                ) + b"\x00"
                f.write(_msg(b"E", payload))
                f.flush()
                return False
        f.write(_msg(b"R", struct.pack("!I", 0)))  # AuthenticationOk
        for k, v in (
            ("server_version", "13.0 (risingwave_tpu 0.1)"),
            ("server_encoding", "UTF8"),
            ("client_encoding", "UTF8"),
        ):
            f.write(_msg(b"S", _cstr(k) + _cstr(v)))
        f.write(_msg(b"K", struct.pack("!II", 0, 0)))  # BackendKeyData
        f.flush()
        return True

    def _ready(self, f) -> None:
        f.write(_msg(b"Z", b"I"))
        f.flush()

    def _error(self, f, message: str) -> None:
        payload = b"SERROR\x00" + b"CXX000\x00" + b"M" + _cstr(message) + \
            b"\x00"
        f.write(_msg(b"E", payload))
        f.flush()

    def _row_description(self, f, cols) -> None:
        desc = struct.pack("!H", len(cols))
        for name in cols:
            # text protocol: report every column as TEXT (oid 25);
            # typed OIDs (_OID) would need the binder's fields here
            desc += _cstr(str(name)) + struct.pack(
                "!IHIhiH", 0, 0, 25, -1, -1, 0
            )
        f.write(_msg(b"T", desc))

    def _results(self, f, sql: str, cols, rows,
                 with_desc: bool = True) -> None:
        verb = sql.strip().split()[0].upper() if sql.strip() else "QUERY"
        if cols:
            if with_desc:
                self._row_description(f, cols)
            for row in rows:
                data = struct.pack("!H", len(row))
                for v in row:
                    text = _pg_text(v)
                    data += struct.pack("!i", len(text)) + text
                f.write(_msg(b"D", data))
            tagline = f"SELECT {len(rows)}"
        else:
            tagline = {"CREATE": "CREATE", "DROP": "DROP",
                       "FLUSH": "FLUSH", "SET": "SET",
                       "ALTER": "ALTER SYSTEM"}.get(verb, verb)
        f.write(_msg(b"C", _cstr(tagline)))
        f.flush()


def _pg_text(v) -> bytes:
    if isinstance(v, bool):
        return b"t" if v else b"f"
    if isinstance(v, bytes):
        return v
    if isinstance(v, float):
        return repr(v).encode()
    return str(v).encode()


class PgServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 4566,
                 engine_lock: EngineLock | None = None,
                 password: str | None = None):
        super().__init__((host, port), _Handler)
        self.engine = engine
        #: non-None enables cleartext-password auth at startup
        self.password = password
        # the engine is single-threaded; serialize statements across
        # connections (the reference runs per-session tokio tasks over a
        # shared catalog — same effective serialization for DDL).  The
        # lock must be installed BEFORE accepting: callers sharing it
        # with a barrier ticker pass it here
        self.engine_lock = engine_lock or EngineLock()


class SimpleClient:
    """Minimal simple-query-protocol client (text format).

    Used by ``risingwave_tpu.ctl`` and the protocol tests; real
    deployments use psql/any postgres driver."""

    def __init__(self, host: str, port: int, user: str = "tpu",
                 database: str = "dev", password: str | None = None):
        self.sock = socket.create_connection((host, port), timeout=30)
        self.f = self.sock.makefile("rwb")
        params = _cstr("user") + _cstr(user) + _cstr("database") + \
            _cstr(database) + b"\x00"
        body = struct.pack("!I", PROTOCOL_VERSION) + params
        self.f.write(struct.pack("!I", len(body) + 4) + body)
        self.f.flush()
        while True:
            tag, payload = self._read_msg()
            if tag == b"R" and len(payload) >= 4 \
                    and struct.unpack("!I", payload[:4])[0] == 3:
                pw = _cstr(password or "")
                self.f.write(b"p" + struct.pack("!I", len(pw) + 4) + pw)
                self.f.flush()
            elif tag == b"E":
                raise RuntimeError(payload.decode(errors="replace"))
            elif tag == b"Z":
                break

    def _read_msg(self):
        header = self.f.read(5)
        if len(header) < 5:
            raise ConnectionError("connection closed")
        return header[:1], self.f.read(
            struct.unpack("!I", header[1:])[0] - 4
        )

    def query(self, sql: str):
        body = sql.encode() + b"\x00"
        self.f.write(b"Q" + struct.pack("!I", len(body) + 4) + body)
        self.f.flush()
        cols, rows, error = [], [], None
        while True:
            tag, payload = self._read_msg()
            if tag == b"T":
                n = struct.unpack("!H", payload[:2])[0]
                off = 2
                for _ in range(n):
                    end = payload.index(b"\x00", off)
                    cols.append(payload[off:end].decode())
                    off = end + 1 + 18
            elif tag == b"D":
                n = struct.unpack("!H", payload[:2])[0]
                off = 2
                row = []
                for _ in range(n):
                    ln = struct.unpack("!i", payload[off:off + 4])[0]
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln].decode())
                        off += ln
                rows.append(tuple(row))
            elif tag == b"E":
                error = payload.decode(errors="replace")
            elif tag == b"Z":
                if error:
                    raise RuntimeError(error)
                return cols, rows

    def close(self) -> None:
        self.f.write(b"X" + struct.pack("!I", 4))
        self.f.flush()
        self.sock.close()

    # -- extended protocol (Parse/Bind/Describe/Execute/Sync) -----------
    def execute_prepared(self, sql: str, params=(), name: str = ""):
        """One extended-protocol round trip with text-format params.

        Returns (cols, rows) like query(); exercises the same message
        sequence psycopg/JDBC drivers emit by default."""
        def send(tag: bytes, payload: bytes) -> None:
            self.f.write(tag + struct.pack("!I", len(payload) + 4)
                         + payload)

        send(b"P", _cstr(name) + _cstr(sql) + struct.pack("!H", 0))
        bind = _cstr("") + _cstr(name) + struct.pack("!H", 0) \
            + struct.pack("!H", len(params))
        for v in params:
            if v is None:
                bind += struct.pack("!i", -1)
            else:
                b = str(v).encode()
                bind += struct.pack("!i", len(b)) + b
        bind += struct.pack("!H", 0)
        send(b"B", bind)
        send(b"D", b"P" + _cstr(""))
        send(b"E", _cstr("") + struct.pack("!I", 0))
        send(b"S", b"")
        self.f.flush()

        cols, rows, error = [], [], None
        saw = set()
        while True:
            tag, payload = self._read_msg()
            saw.add(tag)
            if tag == b"T":
                n = struct.unpack("!H", payload[:2])[0]
                off = 2
                for _ in range(n):
                    end = payload.index(b"\x00", off)
                    cols.append(payload[off:end].decode())
                    off = end + 1 + 18
            elif tag == b"D":
                n = struct.unpack("!H", payload[:2])[0]
                off = 2
                row = []
                for _ in range(n):
                    ln = struct.unpack("!i", payload[off:off + 4])[0]
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln].decode())
                        off += ln
                rows.append(tuple(row))
            elif tag == b"E":
                error = payload.decode(errors="replace")
            elif tag == b"Z":
                if error:
                    raise RuntimeError(error)
                assert b"1" in saw and b"2" in saw, \
                    "Parse/Bind not acknowledged"
                return cols, rows


def pg_serve(engine, host: str = "127.0.0.1", port: int = 4566,
             engine_lock: EngineLock | None = None,
             password: str | None = None) -> PgServer:
    """Start serving in a background thread; returns the server handle
    (ref pg_serve, pg_server.rs:338)."""
    server = PgServer(engine, host, port, engine_lock, password)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server

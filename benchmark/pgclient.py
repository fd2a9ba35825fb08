"""A minimal PostgreSQL simple-query client (text format): the load
generator's side of pgwire, kept with the benchmark."""

from __future__ import annotations

import socket
import struct

PROTOCOL_VERSION = 196608  # 3.0


def _cstr(s: str) -> bytes:
    return s.encode() + b"\x00"


class PgClient:
    def __init__(self, host: str, port: int, timeout: float = 30.0,
                 user: str = "bench", database: str = "dev"):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.f = self.sock.makefile("rwb")
        body = struct.pack("!I", PROTOCOL_VERSION) + _cstr("user") + \
            _cstr(user) + _cstr("database") + _cstr(database) + b"\x00"
        self.f.write(struct.pack("!I", len(body) + 4) + body)
        self.f.flush()
        while True:
            tag, payload = self._read_msg()
            if tag == b"R" and len(payload) >= 4 \
                    and struct.unpack("!I", payload[:4])[0] == 3:
                pw = _cstr("")
                self.f.write(b"p" + struct.pack("!I", len(pw) + 4) + pw)
                self.f.flush()
            elif tag == b"E":
                raise RuntimeError(payload.decode(errors="replace"))
            elif tag == b"Z":
                break

    def _read_msg(self) -> tuple[bytes, bytes]:
        header = self.f.read(5)
        if len(header) < 5:
            raise ConnectionError("connection closed")
        return header[:1], self.f.read(
            struct.unpack("!I", header[1:])[0] - 4)

    def query(self, sql: str) -> tuple[list[str], list[tuple]]:
        """One statement; returns (column names, rows of text values)
        once the last row is in."""
        body = sql.encode() + b"\x00"
        self.f.write(b"Q" + struct.pack("!I", len(body) + 4) + body)
        self.f.flush()
        cols: list[str] = []
        rows: list[tuple] = []
        error = None
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

"""The benchmark's wire client: MySQL protocol v10 handshake and COM_QUERY
with text rows, over a plain socket.

A copy of ``tests/test_server.py::MiniClient`` and of the framing in
``tinysql_tpu/server/packetio.py`` as of PR 22, cut to what the benchmark
sends (no TLS, no password) and standing on the standard library alone:
the load generator's process imports neither ``jax`` nor ``tinysql_tpu``.
"""
from __future__ import annotations

import socket
import struct

MAX_PAYLOAD = (1 << 24) - 1
#: a cold SF=1 statement prepares and compiles for minutes; a run as a
#: whole is bounded by its caller
STATEMENT_TIMEOUT_S = 1100.0


class ServerError(RuntimeError):
    """The server answered a statement with an ERR packet."""


def _read_lenenc_int(buf: bytes, pos: int):
    first = buf[pos]
    if first < 251:
        return first, pos + 1
    if first == 0xFC:
        return struct.unpack_from("<H", buf, pos + 1)[0], pos + 3
    if first == 0xFD:
        return int.from_bytes(buf[pos + 1:pos + 4], "little"), pos + 4
    return struct.unpack_from("<Q", buf, pos + 1)[0], pos + 9


class Client:
    def __init__(self, port: int, db: str, user: str = "root",
                 host: str = "127.0.0.1"):
        self.sock = socket.create_connection((host, port),
                                             timeout=STATEMENT_TIMEOUT_S)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sequence = 0
        greeting = self._read_packet()
        if greeting[0] != 10:
            raise ConnectionError("expected a protocol v10 greeting")
        caps = 0x0200 | 0x8000 | (0x0008 if db else 0)
        payload = struct.pack("<IIB", caps, 1 << 24, 0x21) + b"\x00" * 23
        payload += user.encode() + b"\x00" + b"\x00"  # empty auth token
        if db:
            payload += db.encode() + b"\x00"
        self._write_packet(payload)
        resp = self._read_packet()
        if resp[0] != 0x00:
            self.sock.close()
            raise PermissionError(resp[9:].decode(errors="replace"))

    def _read_exact(self, n: int) -> bytes:
        parts = []
        while n:
            part = self.sock.recv(n)
            if not part:
                raise ConnectionError("connection closed")
            parts.append(part)
            n -= len(part)
        return b"".join(parts)

    def _read_packet(self) -> bytes:
        payload = b""
        while True:
            header = self._read_exact(4)
            length = header[0] | (header[1] << 8) | (header[2] << 16)
            self._sequence = (header[3] + 1) & 0xFF
            payload += self._read_exact(length) if length else b""
            if length < MAX_PAYLOAD:
                return payload

    def _write_packet(self, payload: bytes) -> None:
        out = bytearray()
        pos = 0
        while True:
            part = payload[pos:pos + MAX_PAYLOAD]
            out += struct.pack("<I", len(part))[:3]
            out.append(self._sequence)
            self._sequence = (self._sequence + 1) & 0xFF
            out += part
            pos += len(part)
            if len(part) < MAX_PAYLOAD:
                break
        self.sock.sendall(bytes(out))

    def query(self, sql: str):
        """Rows of a result set as lists of strings (``None`` for NULL),
        or the affected-row count of a statement that returns none."""
        self._sequence = 0
        self._write_packet(b"\x03" + sql.encode())
        first = self._read_packet()
        if first[0] == 0x00:
            return _read_lenenc_int(first, 1)[0]
        if first[0] == 0xFF:
            code = struct.unpack_from("<H", first, 1)[0]
            raise ServerError(f"server error {code}: "
                              f"{first[9:].decode(errors='replace')}")
        ncols, _ = _read_lenenc_int(first, 0)
        for _ in range(ncols):
            self._read_packet()  # column definitions: not compared
        if self._read_packet()[0] != 0xFE:
            raise ConnectionError("expected EOF after column definitions")
        rows = []
        while True:
            d = self._read_packet()
            if d[0] == 0xFE and len(d) < 9:
                return rows
            if d[0] == 0xFF:
                raise ServerError(d[9:].decode(errors="replace"))
            pos = 0
            row = []
            for _ in range(ncols):
                if d[pos] == 0xFB:
                    row.append(None)
                    pos += 1
                else:
                    ln, pos = _read_lenenc_int(d, pos)
                    row.append(d[pos:pos + ln].decode())
                    pos += ln
            rows.append(row)

    def close(self) -> None:
        try:
            self._sequence = 0
            self._write_packet(b"\x01")
        except OSError:
            pass
        self.sock.close()

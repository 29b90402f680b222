"""Minimal MySQL text-protocol client: the benchmark's own copy.

Handshake response 41, COM_INIT_DB, COM_QUERY and text resultsets, over
a framed socket (3-byte little-endian length + 1-byte sequence), read
through one receive buffer: the client shares the server's process, so
every `recv` it makes is a GIL hand-over inside the measured rate. It
imports nothing of the program, so a change to the program's packet
layer or test helper cannot move what the benchmark measures. Copied
from tests/mysql_client.py and tidb_tpu/server/packet.py (PERF.md lists
the originals under Open questions).
"""

from __future__ import annotations

import socket
import struct

_MAX_PAYLOAD = 0xFFFFFF
_RECV_BYTES = 1 << 16
_CLIENT_CONNECT_WITH_DB = 8
_CLIENT_PROTOCOL_41 = 0x200
_CLIENT_SECURE_CONNECTION = 0x8000
_CLIENT_PLUGIN_AUTH = 0x80000


class WireError(Exception):
    """An ERR packet from the server."""

    def __init__(self, code: int, msg: str):
        super().__init__(f"({code}) {msg}")
        self.code = code


def _lenenc_int(b: bytes, off: int) -> tuple[int, int]:
    first = b[off]
    if first < 251:
        return first, off + 1
    if first == 0xFC:
        return struct.unpack_from("<H", b, off + 1)[0], off + 3
    if first == 0xFD:
        return int.from_bytes(b[off + 1:off + 4], "little"), off + 4
    return struct.unpack_from("<Q", b, off + 1)[0], off + 9


def _lenenc_bytes(b: bytes, off: int) -> tuple[bytes, int]:
    n, off = _lenenc_int(b, off)
    return b[off:off + n], off + n


class Client:
    """One connection. `query` -> (columns, rows of text) or the
    affected-rows count of an OK packet."""

    def __init__(self, host: str, port: int, db: str = "",
                 timeout_s: float = 120.0):
        self.sock = socket.create_connection((host, port), timeout=10)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._seq = 0
        self._buf = bytearray()          # received, not yet handed out ...
        self._at = 0                     # ... from this offset on
        self._handshake(db)
        self.sock.settimeout(timeout_s)

    # -- framing ------------------------------------------------------------

    def _take(self, n: int) -> bytes:
        """The stream's next `n` bytes, through the one receive buffer:
        whatever a `recv` brings stays there until a packet is sliced
        out of it, so a reply of many small packets costs one call."""
        buf = self._buf
        while len(buf) - self._at < n:
            del buf[:self._at]               # drop what was handed out
            self._at = 0
            chunk = self.sock.recv(_RECV_BYTES)
            if not chunk:
                raise ConnectionError("server closed the connection")
            buf += chunk
        out = bytes(buf[self._at:self._at + n])
        self._at += n
        return out

    def _read(self) -> bytes:
        payload = b""
        while True:
            header = self._take(4)
            length = header[0] | (header[1] << 8) | (header[2] << 16)
            self._seq = (header[3] + 1) & 0xFF
            payload += self._take(length)
            if length < _MAX_PAYLOAD:
                return payload

    def _write(self, payload: bytes) -> None:
        off = 0
        while True:
            chunk = payload[off:off + _MAX_PAYLOAD]
            header = struct.pack("<I", len(chunk))[:3] + bytes([self._seq])
            self.sock.sendall(header + chunk)
            self._seq = (self._seq + 1) & 0xFF
            off += len(chunk)
            if len(chunk) < _MAX_PAYLOAD:
                return

    def _command(self, cmd: int, data: bytes) -> bytes:
        self._seq = 0
        self._write(bytes([cmd]) + data)
        return self._read()

    @staticmethod
    def _err(pkt: bytes) -> WireError:
        code = struct.unpack_from("<H", pkt, 1)[0]
        return WireError(code, pkt[9:].decode("utf8", "replace"))

    # -- protocol -----------------------------------------------------------

    def _handshake(self, db: str) -> None:
        greeting = self._read()
        if greeting[0] != 10:
            raise ConnectionError("expected protocol v10")
        caps = (_CLIENT_PROTOCOL_41 | _CLIENT_SECURE_CONNECTION
                | _CLIENT_PLUGIN_AUTH)
        if db:
            caps |= _CLIENT_CONNECT_WITH_DB
        resp = struct.pack("<II", caps, 1 << 24) + bytes([33]) + b"\0" * 23
        resp += b"root\0" + b"\0"          # user, empty auth response
        if db:
            resp += db.encode() + b"\0"
        resp += b"mysql_native_password\0"
        self._write(resp)
        ok = self._read()
        if ok and ok[0] == 0xFF:
            raise self._err(ok)

    def use(self, db: str) -> None:
        first = self._command(0x02, db.encode())
        if first[0] == 0xFF:
            raise self._err(first)

    def query(self, sql: str):
        first = self._command(0x03, sql.encode())
        if first[0] == 0xFF:
            raise self._err(first)
        if first[0] == 0x00:
            return _lenenc_int(first, 1)[0]
        ncols, _ = _lenenc_int(first, 0)
        cols = []
        for _ in range(ncols):
            pkt = self._read()
            off = 0
            for _ in range(4):               # catalog schema table org
                _v, off = _lenenc_bytes(pkt, off)
            name, off = _lenenc_bytes(pkt, off)
            cols.append(name.decode())
        eof = self._read()
        if eof[0] != 0xFE:
            raise ConnectionError("expected EOF after column definitions")
        rows = []
        while True:
            pkt = self._read()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return cols, rows
            if pkt[0] == 0xFF:
                raise self._err(pkt)
            row, off = [], 0
            for _ in range(ncols):
                if pkt[off] == 0xFB:
                    row.append(None)
                    off += 1
                else:
                    v, off = _lenenc_bytes(pkt, off)
                    row.append(v.decode())
            rows.append(tuple(row))

    def close(self) -> None:
        try:
            self._seq = 0
            self._write(b"\x01")             # COM_QUIT
        except OSError:
            pass
        self.sock.close()

"""Minimal pgwire v3 client pieces, stdlib only (the client child process
imports this and must never import JAX).

Copied from cockroach_tpu/workload/servebench.WireClient (PR 24) and split
into message builders, an incremental response parser and a blocking
client, so that the closed-loop driver can run many connections from one
thread.
"""

from __future__ import annotations

import socket
import struct

_I = struct.Struct(">i").unpack_from
_H = struct.Struct(">H").unpack_from


def startup_message(user: str = "bench") -> bytes:
    body = struct.pack(">I", 196608) + b"user\x00" + user.encode() \
        + b"\x00\x00"
    return struct.pack(">I", len(body) + 4) + body


def simple_query(sql: str) -> bytes:
    payload = sql.encode() + b"\x00"
    return b"Q" + struct.pack(">I", len(payload) + 4) + payload


def extended_query(sql: str, params=()) -> bytes:
    """One Parse/Bind/Execute/Sync round: unnamed statement and portal,
    text parameters, text results (what a prepared-statement driver
    sends)."""
    msg = bytearray()
    pl = b"\x00" + sql.encode() + b"\x00" + struct.pack(">H", 0)
    msg += b"P" + struct.pack(">I", len(pl) + 4) + pl
    bp = bytearray(b"\x00\x00")
    bp += struct.pack(">HH", 0, len(params))
    for p in params:
        v = str(p).encode()
        bp += struct.pack(">i", len(v)) + v
    bp += struct.pack(">H", 0)
    msg += b"B" + struct.pack(">I", len(bp) + 4) + bp
    ep = b"\x00" + struct.pack(">i", 0)
    msg += b"E" + struct.pack(">I", len(ep) + 4) + ep
    msg += b"S" + struct.pack(">I", 4)
    return bytes(msg)


def _err_code(body: bytes) -> str:
    for field in body.split(b"\x00"):
        if field[:1] == b"C":
            return field[1:].decode()
    return "XX000"


def response_end(buf, start: int = 0):
    """-> (end, resume): `end` is the offset just past the first
    ReadyForQuery at or after `start`, or -1 while the response is
    incomplete; `resume` is the message boundary the walk stopped at, where
    the next call may start. Walks message headers only."""
    pos, n = start, len(buf)
    while n - pos >= 5:
        end = pos + 1 + int.from_bytes(buf[pos + 1:pos + 5], "big")
        if n < end:
            break
        if buf[pos] == 90:  # 'Z'
            return end, end
        pos = end
    return -1, pos


def parse_response(buf: bytes):
    """One complete response (through ReadyForQuery) -> (rows, sqlstate or
    None); rows are tuples of str or None."""
    rows, code = [], None
    pos, n = 0, len(buf)
    while n - pos >= 5:
        end = pos + 1 + int.from_bytes(buf[pos + 1:pos + 5], "big")
        t = buf[pos]
        if t == 68:  # DataRow
            (nf,) = _H(buf, pos + 5)
            off, row = pos + 7, []
            for _ in range(nf):
                (fl,) = _I(buf, off)
                off += 4
                if fl < 0:
                    row.append(None)
                else:
                    row.append(buf[off:off + fl].decode())
                    off += fl
            rows.append(tuple(row))
        elif t == 69:  # ErrorResponse
            code = _err_code(buf[pos + 5:end])
        pos = end
    return rows, code


class WireClient:
    """Blocking client for set-up statements: (rows, sqlstate or None)."""

    def __init__(self, addr, timeout: float):
        self.s = socket.create_connection(tuple(addr), timeout=timeout)
        self.s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""
        self.s.sendall(startup_message())
        self._read()

    def _read(self):
        while True:
            end, _resume = response_end(self.buf)
            if end >= 0:
                out, self.buf = self.buf[:end], self.buf[end:]
                return parse_response(out)
            chunk = self.s.recv(1 << 16)
            if not chunk:
                raise ConnectionError("server closed the connection")
            self.buf += chunk

    def query(self, sql: str):
        self.s.sendall(simple_query(sql))
        return self._read()

    def query_extended(self, sql: str, params=()):
        self.s.sendall(extended_query(sql, params))
        return self._read()

    def close(self):
        try:
            self.s.close()
        except OSError:
            pass

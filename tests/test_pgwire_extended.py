"""pgwire extended protocol (Parse/Bind/Describe/Execute/Sync) — what
prepared-statement drivers (psycopg3, JDBC) speak.

Reference: pkg/sql/pgwire/conn.go:151 (the command processing loop),
server.go:918. The test is a minimal driver over a raw socket."""

import socket
import struct

import pytest

from cockroach_tpu.sql.pgwire import PgServer
from cockroach_tpu.sql.session import SessionCatalog
from cockroach_tpu.storage.engine import PyEngine
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.hlc import HLC, ManualClock


class MiniDriver:
    def __init__(self, addr):
        self.s = socket.create_connection(addr, timeout=30)
        self.buf = b""
        body = struct.pack(">I", 196608) + b"user\x00t\x00\x00"
        self.s.sendall(struct.pack(">I", len(body) + 4) + body)
        self.drain_until(b"Z")

    def _recv(self, n):
        while len(self.buf) < n:
            chunk = self.s.recv(65536)
            if not chunk:
                raise ConnectionError
            self.buf += chunk
        out, self.buf = self.buf[:n], self.buf[n:]
        return out

    def read_msg(self):
        t = self._recv(1)
        (ln,) = struct.unpack(">I", self._recv(4))
        return t, self._recv(ln - 4)

    def drain_until(self, kind):
        msgs = []
        while True:
            t, body = self.read_msg()
            msgs.append((t, body))
            if t == kind:
                return msgs

    def send(self, t, payload=b""):
        self.s.sendall(t + struct.pack(">I", len(payload) + 4) + payload)

    # -- extended flow helpers -------------------------------------------

    def parse(self, name, sql, oids=()):
        self.send(b"P", name.encode() + b"\x00" + sql.encode()
                  + b"\x00" + struct.pack(f">H{len(oids)}I",
                                          len(oids), *oids))

    def bind(self, portal, stmt, params):
        payload = portal.encode() + b"\x00" + stmt.encode() + b"\x00"
        payload += struct.pack(">H", 0)              # all-text params
        payload += struct.pack(">H", len(params))
        for p in params:
            if p is None:
                payload += struct.pack(">i", -1)
            else:
                b = str(p).encode()
                payload += struct.pack(">i", len(b)) + b
        payload += struct.pack(">H", 0)              # all-text results
        self.send(b"B", payload)

    def bind_binary(self, portal, stmt, raw_params):
        """Bind with ALL parameters in binary format (pre-encoded)."""
        payload = portal.encode() + b"\x00" + stmt.encode() + b"\x00"
        payload += struct.pack(">HH", 1, 1)          # all-binary params
        payload += struct.pack(">H", len(raw_params))
        for b in raw_params:
            payload += struct.pack(">i", len(b)) + b
        payload += struct.pack(">H", 0)              # all-text results
        self.send(b"B", payload)

    def query(self, sql, params=()):
        """Parse/Bind/Describe/Execute/Sync round — returns rows of
        text values (None for NULL)."""
        self.parse("", sql)
        self.bind("", "", list(params))
        self.send(b"D", b"P\x00")
        self.send(b"E", b"\x00" + struct.pack(">i", 0))
        self.send(b"S")
        rows = []
        err = None
        for t, body in self.drain_until(b"Z"):
            if t == b"D":
                (n,) = struct.unpack(">H", body[:2])
                off = 2
                row = []
                for _ in range(n):
                    (ln,) = struct.unpack(">i", body[off:off + 4])
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(body[off:off + ln].decode())
                        off += ln
                rows.append(row)
            elif t == b"E":
                err = body
        if err is not None:
            raise RuntimeError(err)
        return rows


def _table_s(d):
    """Table `s` on this worker's server, whichever test asks first (xdist
    deals a module's tests to several workers, each with a server of its
    own)."""
    try:
        d.query("create table s (id int primary key, name string)")
    except RuntimeError:
        pass    # an earlier test made it


@pytest.fixture(scope="module")
def server():
    store = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    srv = PgServer(SessionCatalog(store), capacity=256).start()
    yield srv
    srv.close()


def test_prepared_statement_with_params(server):
    d = MiniDriver(server.addr)
    assert d.query("create table t (id int primary key, v int)") == []
    d.query("insert into t values (1, 10), (2, 20), (3, 30)")
    rows = d.query("select id, v from t where v > $1 order by id", [15])
    assert rows == [["2", "20"], ["3", "30"]]
    # re-bind the same named statement with different params
    d.parse("q1", "select v from t where id = $1")
    d.bind("", "q1", [2])
    d.send(b"E", b"\x00" + struct.pack(">i", 0))
    d.bind("", "q1", [3])
    d.send(b"E", b"\x00" + struct.pack(">i", 0))
    d.send(b"S")
    vals = [body for t, body in d.drain_until(b"Z") if t == b"D"]
    assert len(vals) == 2


def test_null_param_and_string_quoting(server):
    d = MiniDriver(server.addr)
    _table_s(d)
    d.query("insert into s values ($1, $2)", [1, "o'hara"])
    rows = d.query("select name from s where id = $1", [1])
    assert rows == [["o'hara"]]


def test_describe_dml_portal_has_no_side_effects(server):
    """ADVICE r4: Describe(portal) on a DML statement must answer NoData
    WITHOUT applying the statement's effects — only Execute runs it."""
    d = MiniDriver(server.addr)
    d.query("create table dd (id int primary key, v int)")
    # Parse/Bind/Describe an INSERT, then Sync WITHOUT Execute
    d.parse("", "insert into dd values (1, 10)")
    d.bind("", "", [])
    d.send(b"D", b"P\x00")
    d.send(b"S")
    kinds = [t for t, _ in d.drain_until(b"Z")]
    assert b"n" in kinds  # NoData
    rows = d.query("select count(*) from dd")
    assert rows == [["0"]]  # describe alone inserted NOTHING
    # Execute actually applies it
    d.query("insert into dd values (1, 10)")
    assert d.query("select count(*) from dd") == [["1"]]


def test_error_skips_to_sync(server):
    d = MiniDriver(server.addr)
    d.parse("", "select broken syntax here from")
    d.bind("", "", [])
    d.send(b"E", b"\x00" + struct.pack(">i", 0))
    d.send(b"S")
    msgs = d.drain_until(b"Z")
    kinds = [t for t, _ in msgs]
    assert b"E" in kinds  # ErrorResponse delivered, then ReadyForQuery
    # connection still usable afterwards
    _table_s(d)
    d.query("insert into s values (2, 'b')")
    assert d.query("select 1 + 1 as x from s")


def test_simple_query_still_works(server):
    d = MiniDriver(server.addr)
    _table_s(d)
    d.query("insert into s values (3, 'c')")
    d.send(b"Q", b"select 2 + 2 as four from s\x00")
    msgs = d.drain_until(b"Z")
    kinds = [t for t, _ in msgs]
    assert b"T" in kinds and b"D" in kinds and b"C" in kinds


def test_password_auth():
    """Cleartext-password auth (auth.go's password method): wrong
    password refused, right one serves queries."""
    store2 = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    srv = PgServer(SessionCatalog(store2), capacity=64,
                   password="hunter2").start()
    try:
        import socket as _s

        def connect(pw):
            sock = _s.create_connection(srv.addr, timeout=5)
            params = b"user\x00t\x00\x00"
            body = struct.pack(">I", 196608) + params
            sock.sendall(struct.pack(">I", len(body) + 4) + body)
            # expect AuthenticationCleartextPassword (R, 3)
            t = sock.recv(1)
            (ln,) = struct.unpack(">I", sock.recv(4))
            (code,) = struct.unpack(">I", sock.recv(ln - 4))
            assert (t, code) == (b"R", 3)
            payload = pw.encode() + b"\x00"
            sock.sendall(b"p" + struct.pack(">I", len(payload) + 4)
                         + payload)
            t = sock.recv(1)
            return sock, t

        sock, t = connect("wrong")
        assert t == b"E"  # ErrorResponse
        sock.close()
        sock, t = connect("hunter2")
        assert t == b"R"  # AuthenticationOk
        sock.close()
    finally:
        srv.close()


def _exec_rows(d):
    d.send(b"E", b"\x00" + struct.pack(">i", 0))
    d.send(b"S")
    msgs = d.drain_until(b"Z")
    assert not any(t == b"E" for t, _ in msgs), msgs
    out = []
    for t, body in msgs:
        if t != b"D":
            continue
        (n,) = struct.unpack(">H", body[:2])
        off, row = 2, []
        for _ in range(n):
            (ln,) = struct.unpack(">i", body[off:off + 4])
            off += 4
            row.append(None if ln < 0 else body[off:off + ln].decode())
            off += max(ln, 0)
        out.append(row)
    return out


def test_binary_format_params(server):
    """Drivers that know the parameter OIDs (from Parse) send int/float
    params in binary format; the server decodes by declared OID."""
    d = MiniDriver(server.addr)
    d.query("create table bp (id int primary key, x decimal(1))")
    d.query("insert into bp values (1, 1.5), (2, 2.5), (7, 7.5)")
    # int8 binary param
    d.parse("", "select x from bp where id = $1", oids=[20])
    d.bind_binary("", "", [struct.pack(">q", 7)])
    assert _exec_rows(d) == [["7.50"]]
    # float8 binary param
    d.parse("", "select id from bp where x < $1 order by id",
            oids=[701])
    d.bind_binary("", "", [struct.pack(">d", 2.0)])
    assert _exec_rows(d) == [["1"]]
    # int4 + bool-free mix via per-param format codes is covered by the
    # all-binary path; an undeclared-OID binary param must error cleanly
    d.parse("", "select id from bp where id = $1", oids=[1700])
    d.bind_binary("", "", [b"\x00\x01"])
    d.send(b"S")
    assert any(t == b"E" for t, _ in d.drain_until(b"Z"))


def test_vector_over_the_wire(server):
    """'[...]' text vector literals as params; vector result columns
    render as pgvector-style text with a text OID."""
    d = MiniDriver(server.addr)
    d.query("create table vt (id int primary key, emb vector(3))")
    d.query("insert into vt values ($1, $2)", [1, "[1.5,2.5,3.5]"])
    d.query("insert into vt values ($1, $2)", [2, "[0.0,0.0,1.0]"])
    rows = d.query(
        "select id from vt order by emb <-> $1 limit 2", ["[0,0,1]"])
    assert rows == [["2"], ["1"]]
    # vector column round-trips as text
    d.parse("", "select emb from vt where id = $1", oids=[20])
    d.bind_binary("", "", [struct.pack(">q", 1)])
    d.send(b"D", b"P\x00")
    rows = _exec_rows(d)
    assert rows == [["[1.5,2.5,3.5]"]]


def test_copy_from_stdin(server):
    """COPY t FROM STDIN over the simple protocol: CopyInResponse,
    CopyData rows (text/tab/\\N), CopyDone -> rows landed."""
    d = MiniDriver(server.addr)
    d.query("create table ct (id int primary key, v int, s string)")
    d.send(b"Q", b"copy ct from stdin\x00")
    # expect CopyInResponse
    while True:
        t, body = d.read_msg()
        if t == b"G":
            break
        assert t not in (b"E",), body
    rows = b"1\t10\talpha\n2\t\\N\tbe'ta\n3\t30\t\\N\n"
    d.send(b"d", rows)
    d.send(b"c")
    done = [(t, b) for t, b in d.drain_until(b"Z")]
    assert any(t == b"C" and b.startswith(b"COPY 3")
               for t, b in done), done
    got = d.query("select id, v, s from ct order by id")
    assert got == [["1", "10", "alpha"], ["2", None, "be'ta"],
                   ["3", "30", None]]

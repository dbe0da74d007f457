"""MySQL wire protocol server tests: a minimal raw-socket client performs
the real handshake and text-protocol queries against a live server on an
ephemeral port (reference test pattern: server/tidb_test.go drives a real
Go MySQL client against a listening server).
"""
import json
import socket
import struct
import urllib.request

import pytest

from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.server.http_status import StatusServer
from tinysql_tpu.server.packetio import (PacketIO, lenenc_int,
                                         read_lenenc_int)
from tinysql_tpu.server.server import Server


class MiniClient:
    """Just enough of the client side of the protocol for tests."""

    def __init__(self, port, db="", user="root", password="",
                 ssl_ctx=None):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        self.io = PacketIO(self.sock)
        greeting = self.io.read_packet()
        assert greeting[0] == 10, "expected protocol v10 greeting"
        nul = greeting.index(0, 1)
        self.server_version = greeting[1:nul].decode()
        # salt: 8 bytes after conn_id, 12 more after the capability block
        p1 = nul + 1 + 4
        salt = bytes(greeting[p1:p1 + 8])
        p2 = p1 + 8 + 1 + 2 + 1 + 2 + 2 + 1 + 10
        salt += bytes(greeting[p2:p2 + 12])
        caps_lo = struct.unpack_from("<H", greeting, p1 + 8 + 1)[0]
        self.server_caps = caps_lo  # low 16 bits incl. CLIENT_SSL (1<<11)
        from tinysql_tpu.server.auth import scramble
        token = scramble(password, salt)
        caps = 0x0200 | 0x8000 | (0x00008 if db else 0)
        if ssl_ctx is not None:
            assert self.server_caps & 0x0800, "server did not offer SSL"
            caps |= 0x0800  # CLIENT_SSL
        prefix = struct.pack("<IIB", caps, 1 << 24, 0x21) + b"\x00" * 23
        if ssl_ctx is not None:
            # SSLRequest = exactly the 32-byte response prefix, then the
            # full response repeats the SAME prefix over TLS
            self.io.write_packet(prefix)
            seq = self.io.sequence
            self.sock = ssl_ctx.wrap_socket(self.sock,
                                            server_hostname="localhost")
            self.io = PacketIO(self.sock)
            self.io.sequence = seq
        payload = prefix
        payload += user.encode() + b"\x00"
        payload += bytes([len(token)]) + token
        if db:
            payload += db.encode() + b"\x00"
        self.io.write_packet(payload)
        resp = self.io.read_packet()
        if resp[0] == 0xFF:
            code = struct.unpack_from("<H", resp, 1)[0]
            self.sock.close()
            raise PermissionError(
                f"{code}: {resp[9:].decode(errors='replace')}")
        assert resp[0] == 0x00, f"auth failed: {resp!r}"

    def query(self, sql):
        """Returns (columns, rows) for resultsets, or affected count."""
        self.send_query(sql)
        return self.read_result()

    def send_query(self, sql):
        """The COM_QUERY alone: its answer is ``read_result``'s."""
        self.io.reset_sequence()
        self.io.write_packet(b"\x03" + sql.encode())

    def read_result(self):
        first = self.io.read_packet()
        if first[0] == 0x00:  # OK
            affected, _ = read_lenenc_int(first, 1)
            return affected
        if first[0] == 0xFF:
            code = struct.unpack_from("<H", first, 1)[0]
            raise RuntimeError(f"server error {code}: "
                               f"{first[9:].decode(errors='replace')}")
        ncols, _ = read_lenenc_int(first, 0)
        cols = []
        for _ in range(ncols):
            d = self.io.read_packet()
            pos = 0
            vals = []
            for _ in range(6):
                ln, pos = read_lenenc_int(d, pos)
                vals.append(d[pos:pos + ln])
                pos += ln
            cols.append(vals[4].decode())
        assert self.io.read_packet()[0] == 0xFE  # EOF
        rows = []
        while True:
            d = self.io.read_packet()
            if d[0] == 0xFE and len(d) < 9:
                break
            pos = 0
            row = []
            for _ in range(ncols):
                if d[pos] == 0xFB:
                    row.append(None)
                    pos += 1
                else:
                    ln, pos = read_lenenc_int(d, pos)
                    row.append(d[pos:pos + ln].decode())
                    pos += ln
            rows.append(row)
        return cols, rows

    def close(self):
        try:
            self.io.write_packet(b"\x01")
        except OSError:
            pass
        self.sock.close()


@pytest.fixture(scope="module")
def server():
    storage = new_mock_storage()
    srv = Server(storage, port=0)  # ephemeral
    srv.start()
    yield srv
    srv.close()


def test_handshake_and_version(server):
    c = MiniClient(server.port)
    assert "tinysql-tpu" in c.server_version
    c.close()


def test_mysql_native_password_auth(server):
    # mysql_native_password scramble verification against mysql.user
    # (full-TiDB conn.go:418 behavior, stripped in tinysql, restored here)
    from tinysql_tpu.server.auth import hash_password
    admin = MiniClient(server.port)
    admin.query("insert into mysql.user values "
                f"('alice', '{hash_password('sesame')}')")
    # correct password: session works
    c = MiniClient(server.port, user="alice", password="sesame")
    _, rows = c.query("select 1 + 1")
    assert rows == [["2"]]
    c.close()
    # wrong password -> ERR 1045, connection refused
    with pytest.raises(PermissionError) as ei:
        MiniClient(server.port, user="alice", password="wrong")
    assert "1045" in str(ei.value) and "Access denied" in str(ei.value)
    # password against a passwordless account -> denied
    with pytest.raises(PermissionError):
        MiniClient(server.port, user="root", password="something")
    # unknown user -> denied
    with pytest.raises(PermissionError):
        MiniClient(server.port, user="mallory", password="x")
    # root with no password still fine
    MiniClient(server.port).close()
    # SQL-injection usernames must not bypass auth or kill the conn thread
    for evil in ("\\' or 1=1 -- x", "x' or ''='", "trailing\\"):
        with pytest.raises(PermissionError):
            MiniClient(server.port, user=evil, password="")
    admin.query("delete from mysql.user where user = 'alice'")
    admin.close()


def test_query_roundtrip(server):
    c = MiniClient(server.port)
    c.query("create database if not exists wiretest")
    c.query("use wiretest")
    c.query("create table t (a int primary key, b double, c varchar(20))")
    affected = c.query("insert into t values (1, 1.5, 'x'), (2, null, null)")
    assert affected == 2
    cols, rows = c.query("select a, b, c from t order by a")
    assert cols == ["a", "b", "c"]
    assert rows == [["1", "1.5", "x"], ["2", None, None]]
    c.close()


def test_error_packet(server):
    c = MiniClient(server.port)
    with pytest.raises(RuntimeError, match="server error"):
        c.query("select * from wiretest.does_not_exist")
    c.close()


def test_two_connections_share_storage(server):
    c1 = MiniClient(server.port)
    c2 = MiniClient(server.port)
    c1.query("create database if not exists shared")
    c1.query("use shared")
    c1.query("create table s (a int primary key)")
    c1.query("insert into s values (42)")
    _, rows = c2.query("select a from shared.s")
    assert rows == [["42"]]
    c1.close()
    c2.close()


def test_txn_isolation_across_connections(server):
    c1 = MiniClient(server.port)
    c2 = MiniClient(server.port)
    c1.query("use shared")
    c2.query("use shared")
    c1.query("begin")
    c1.query("insert into s values (99)")
    _, rows = c2.query("select a from s order by a")
    assert ["99"] not in rows  # uncommitted: invisible
    c1.query("commit")
    _, rows = c2.query("select a from s order by a")
    assert ["99"] in rows
    c1.close()
    c2.close()


def test_connect_with_db(server):
    c = MiniClient(server.port, db="shared")
    _, rows = c.query("select count(*) from s")
    assert rows[0][0] == "2"
    c.close()


def test_multi_statement_query(server):
    c = MiniClient(server.port)
    c.query("create database if not exists multi")
    c.query("use multi")
    c.query("create table m (a int primary key)")
    # two resultsets + trailing DML in ONE COM_QUERY; each response chained
    # with SERVER_MORE_RESULTS_EXISTS, read back-to-back
    c.io.reset_sequence()
    c.io.write_packet(b"\x03" + b"select 1; select 2; insert into m values (7)")
    # resultset 1
    for want in ("1", "2"):
        first = c.io.read_packet()
        ncols, _ = read_lenenc_int(first, 0)
        for _ in range(ncols):
            c.io.read_packet()
        eof1 = c.io.read_packet()
        assert eof1[0] == 0xFE
        row = c.io.read_packet()
        assert want.encode() in row
        eof2 = c.io.read_packet()
        assert eof2[0] == 0xFE
        status = struct.unpack_from("<H", eof2, 3)[0]
        assert status & 0x0008, "SERVER_MORE_RESULTS_EXISTS missing"
    ok = c.io.read_packet()
    assert ok[0] == 0x00
    affected, _ = read_lenenc_int(ok, 1)
    assert affected == 1
    # connection still in sync
    _, rows = c.query("select a from m")
    assert rows == [["7"]]
    c.close()


def test_affected_rows_reset_after_ddl(server):
    c = MiniClient(server.port)
    c.query("create database if not exists ar")
    c.query("use ar")
    c.query("create table r (a int primary key)")
    assert c.query("insert into r values (1), (2)") == 2
    assert c.query("create table r2 (a int primary key)") == 0
    assert c.query("begin") == 0
    assert c.query("commit") == 0
    c.close()


def test_status_endpoint(server):
    st = StatusServer(server, port=0)
    st.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{st.port}/status", timeout=5) as r:
            data = json.loads(r.read())
        assert "tinysql-tpu" in data["version"]
    finally:
        st.close()


def test_wire_fidelity_fuzz(server):
    """Randomized queries return IDENTICAL results through the MySQL
    protocol and the embedded session (text-protocol encode/decode
    fidelity over the fuzz grammar)."""
    import random
    from test_sqlite_diff import _Gen, _gen_rows
    from tinysql_tpu.session.session import Session
    rng = random.Random(99)
    rows = _gen_rows(rng, 50)
    s = Session(server.storage)
    s.execute("create database if not exists wf")
    s.execute("use wf")
    s.execute("create table t (a int primary key, b int, c double, "
              "d varchar(12), key ib (b))")
    s.execute("create table u (k int primary key, v varchar(6))")
    s.execute("insert into t values " + ", ".join(
        "(" + ", ".join(
            "null" if v is None
            else (f"'{v}'" if isinstance(v, str) else repr(v))
            for v in r) + ")" for r in rows))
    s.execute("insert into u values " + ", ".join(
        f"({k}, 'v{k % 6}')" for k in range(-2, 9)))
    # the generator's multi-key arm needs the w/wd dims
    s.execute("create table w (id int primary key, k1 int, k2 int, "
              "x double, unique key uw (k1, k2))")
    wrows = [(i * 10 + j, i, j, i + j / 10.0)
             for i in range(-1, 6) for j in range(0, 4)]
    s.execute("insert into w values " + ", ".join(
        f"({a}, {b}, {c_}, {d})" for a, b, c_, d in wrows))
    s.execute("create table wd (id int primary key, k1 int, k2 int, "
              "x double)")
    s.execute("insert into wd values " + ", ".join(
        f"({n}, {r[1]}, {r[2]}, {r[3] + n})"
        for n, r in enumerate(wrows + wrows[::2])))
    c = MiniClient(server.port, db="wf")
    gen = _Gen(rng)

    def canon(rows):
        out = []
        for r in rows:
            key = []
            for v in r:
                if v is None:
                    key.append("\x00N")
                else:
                    try:
                        key.append(f"{float(v):.9g}")
                    except (TypeError, ValueError):
                        key.append(str(v))
            out.append(tuple(key))
        return sorted(out)

    for _ in range(40):
        q = gen.query()
        direct = canon(s.query(q).rows)
        wire = canon(c.query(q)[1])
        assert direct == wire, q
    c.close()


def test_config_strict_load(tmp_path):
    from tinysql_tpu import config as cfgmod
    f = tmp_path / "ok.toml"
    f.write_text('port = 4001\n[log]\nlevel = "debug"\n')
    cfg = cfgmod.load(str(f))
    assert cfg.port == 4001 and cfg.log.level == "debug"
    bad = tmp_path / "bad.toml"
    bad.write_text("nonexistent-key = 1\n")
    with pytest.raises(cfgmod.ConfigError, match="unknown configuration"):
        cfgmod.load(str(bad))


def test_config_fallback_toml_parser(tmp_path, monkeypatch):
    """The pre-3.11 strict-subset parser must agree with stdlib tomllib
    on the config files this server reads — on EVERY interpreter, so the
    3.10-only code path cannot rot unexercised."""
    from tinysql_tpu import config as cfgmod
    data = cfgmod._parse_toml_minimal(
        'port = 4001          # inline comment\n'
        'host = "0.0.0.0"     # comment after a quoted string\n'
        'use-tpu = false\n'
        '\n'
        '[log]\n'
        'level = "debug"\n'
        'slow-threshold-ms = 500\n')
    assert data == {"port": 4001, "host": "0.0.0.0", "use-tpu": False,
                    "log": {"level": "debug", "slow-threshold-ms": 500}}
    with pytest.raises(cfgmod.ConfigError, match="bad TOML"):
        cfgmod._parse_toml_minimal('x = "unterminated\n')
    with pytest.raises(cfgmod.ConfigError, match="bad TOML"):
        cfgmod._parse_toml_minimal('x = "quoted" trailing-junk\n')
    # load() through the fallback path end to end
    monkeypatch.setattr(cfgmod, "tomllib", None)
    f = tmp_path / "fb.toml"
    f.write_text('port = 4002\n[security]\nssl-cert = "/tmp/c.pem"  # x\n')
    cfg = cfgmod.load(str(f))
    assert cfg.port == 4002 and cfg.security.ssl_cert == "/tmp/c.pem"


def test_com_field_list(server):
    """COM_FIELD_LIST over the real socket (reference conn.go:846
    handleFieldList): one column-definition packet per table column, with
    the empty default-value field appended, then EOF."""
    c = MiniClient(server.port)
    c.query("create database if not exists fl")
    c.query("use fl")
    c.query("create table ft (id int primary key, name varchar(20), "
            "score double)")
    c.io.reset_sequence()
    c.io.write_packet(b"\x04" + b"ft\x00" + b"%")
    names, types = [], []
    while True:
        d = c.io.read_packet()
        if d[0] == 0xFE and len(d) < 9:
            break
        pos = 0
        vals = []
        for _ in range(6):
            ln, pos = read_lenenc_int(d, pos)
            vals.append(d[pos:pos + ln])
            pos += ln
        names.append(vals[4].decode())
        assert d[pos] == 0x0C
        tp = d[pos + 1 + 2 + 4]
        types.append(tp)
        # the trailing default-value field must be present (lenenc 0)
        assert d[-1] == 0x00
    assert names == ["id", "name", "score"]
    assert types == [0x08, 0xFD, 0x05]  # LONGLONG, VAR_STRING, DOUBLE
    # unknown table -> 1146
    c.io.reset_sequence()
    c.io.write_packet(b"\x04" + b"nope\x00")
    d = c.io.read_packet()
    assert d[0] == 0xFF and struct.unpack_from("<H", d, 1)[0] == 1146
    c.close()


def test_binary_row_encoding():
    """Binary resultset row codec parity with the reference's
    dumpBinaryRow (server/util.go:171): header byte, 2-bit-offset NULL
    bitmap, longlong/double/lenenc-string wire values."""
    from tinysql_tpu.mytypes import (FieldType, TYPE_DOUBLE, TYPE_LONGLONG,
                                     TYPE_VARCHAR)
    from tinysql_tpu.server.protocol import binary_row
    fi = FieldType(TYPE_LONGLONG, 0, 20)
    fr = FieldType(TYPE_DOUBLE, 0, 22)
    fs = FieldType(TYPE_VARCHAR, 0, 20)
    row = binary_row([5, None, 2.5, "ab"], [fi, fi, fr, fs])
    assert row[0] == 0x00
    nmap_len = (4 + 7 + 2) // 8
    nmap = row[1:1 + nmap_len]
    # col 1 NULL -> bit (1+2) of the bitmap
    assert nmap[0] & (1 << 3) and not (nmap[0] & (1 << 2))
    body = row[1 + nmap_len:]
    iv = struct.unpack_from("<q", body, 0)[0]
    rv = struct.unpack_from("<d", body, 8)[0]
    assert iv == 5 and rv == 2.5
    assert body[16] == 2 and body[17:19] == b"ab"
    # negative + unsigned-range ints ride two's complement
    row = binary_row([-7], [fi])
    assert struct.unpack_from("<q", row, 1 + (1 + 9) // 8)[0] == -7
    row = binary_row([2**64 - 1], [fi])
    assert struct.unpack_from("<Q", row, 1 + (1 + 9) // 8)[0] == 2**64 - 1


def test_prepared_statement_binary_protocol(server):
    """COM_STMT_PREPARE/EXECUTE/CLOSE over the real socket: binary param
    decoding (longlong/double/string/NULL) and BINARY resultset rows
    (reference conn.go:879 writeResultset binary=true path)."""
    from tinysql_tpu.server.packetio import lenenc_int
    c = MiniClient(server.port)
    c.query("create database if not exists ps")
    c.query("use ps")
    c.query("create table pt (id int primary key, nm varchar(20), "
            "sc double)")
    c.query("insert into pt values (1, 'ann', 1.5), (2, 'bob', 2.5), "
            "(3, null, 3.5)")
    # prepare
    c.io.reset_sequence()
    sql = b"select id, nm, sc from pt where id >= ? and sc < ? order by id"
    c.io.write_packet(b"\x16" + sql)
    d = c.io.read_packet()
    assert d[0] == 0x00
    stmt_id = struct.unpack_from("<I", d, 1)[0]
    ncols = struct.unpack_from("<H", d, 5)[0]
    nparams = struct.unpack_from("<H", d, 7)[0]
    # prepare-time result metadata: the SELECT's real columns
    assert nparams == 2 and ncols == 3
    for _ in range(nparams):
        c.io.read_packet()          # param definitions
    assert c.io.read_packet()[0] == 0xFE
    prep_cols = []
    for _ in range(ncols):
        d = c.io.read_packet()
        pos = 0
        vals = []
        for _ in range(6):
            ln, pos = read_lenenc_int(d, pos)
            vals.append(d[pos:pos + ln])
            pos += ln
        prep_cols.append(vals[4].decode())
    assert prep_cols == ["id", "nm", "sc"]
    assert c.io.read_packet()[0] == 0xFE
    # execute with id >= 1 (longlong), sc < 3.0 (double)
    c.io.reset_sequence()
    pl = struct.pack("<IBI", stmt_id, 0, 1)
    pl += b"\x00"                    # null bitmap (2 params)
    pl += b"\x01"                    # new params bound
    pl += bytes([0x08, 0x00, 0x05, 0x00])   # LONGLONG, DOUBLE
    pl += struct.pack("<q", 1) + struct.pack("<d", 3.0)
    c.io.write_packet(b"\x17" + pl)
    first = c.io.read_packet()
    nc, _ = read_lenenc_int(first, 0)
    assert nc == 3
    fts = []
    for _ in range(nc):
        d = c.io.read_packet()
        pos = 0
        for _ in range(6):
            ln, pos = read_lenenc_int(d, pos)
            pos += ln
        fts.append(d[pos + 1 + 2 + 4])   # column type byte
    assert c.io.read_packet()[0] == 0xFE
    rows = []
    while True:
        d = c.io.read_packet()
        if d[0] == 0xFE and len(d) < 9:
            break
        assert d[0] == 0x00          # binary row header
        nmap_len = (nc + 7 + 2) // 8
        nmap = d[1:1 + nmap_len]
        pos = 1 + nmap_len
        row = []
        for i, tp in enumerate(fts):
            if nmap[(i + 2) // 8] & (1 << ((i + 2) % 8)):
                row.append(None)
                continue
            if tp == 0x08:
                row.append(struct.unpack_from("<q", d, pos)[0])
                pos += 8
            elif tp == 0x05:
                row.append(struct.unpack_from("<d", d, pos)[0])
                pos += 8
            else:
                ln, pos = read_lenenc_int(d, pos)
                row.append(d[pos:pos + ln].decode())
                pos += ln
        rows.append(row)
    assert rows == [[1, "ann", 1.5], [2, "bob", 2.5]], rows
    # re-execute WITHOUT re-binding types (bound flag 0): types cached
    c.io.reset_sequence()
    pl = struct.pack("<IBI", stmt_id, 0, 1) + b"\x00" + b"\x00"
    pl += struct.pack("<q", 3) + struct.pack("<d", 99.0)
    c.io.write_packet(b"\x17" + pl)
    first = c.io.read_packet()
    nc2, _ = read_lenenc_int(first, 0)
    assert nc2 == 3
    for _ in range(nc2):
        c.io.read_packet()           # column definitions
    assert c.io.read_packet()[0] == 0xFE
    rows2 = 0
    null_seen = False
    while True:
        d = c.io.read_packet()
        if d[0] == 0xFE and len(d) < 9:
            break
        rows2 += 1
        nmap = d[1:1 + (nc2 + 7 + 2) // 8]
        # nm is column 1 -> bitmap bit 1+2 (row id=3 has nm NULL)
        null_seen = null_seen or bool(nmap[0] & (1 << 3))
    assert rows2 == 1 and null_seen  # only id=3 matches; its nm is NULL
    # close the statement (no response expected)
    c.io.reset_sequence()
    c.io.write_packet(b"\x19" + struct.pack("<I", stmt_id))
    # connection still alive after close
    cols, rows = c.query("select count(*) from pt")
    assert rows == [["3"]]
    c.close()


def test_split_placeholders_comments_and_quotes():
    from tinysql_tpu.server.protocol import split_placeholders as sp
    assert len(sp("select id from t -- trailing?")) == 1
    assert len(sp("select /* ? */ id from t where id = ?")) == 2
    assert len(sp("select '?' , `a?b`, \"?\" from t where x = ?")) == 2
    assert len(sp("select 1 # c?\n from t where a = ? and b = ?")) == 3


# ---- TLS upgrade (reference: server/conn.go:448-455, upgradeToTLS :1070) --

@pytest.fixture(scope="module")
def tls_server(tmp_path_factory):
    """Server with a self-signed cert: advertises CLIENT_SSL and accepts
    the mid-handshake SSLRequest upgrade."""
    import datetime
    import ipaddress
    pytest.importorskip("cryptography")
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID

    d = tmp_path_factory.mktemp("tls")
    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "localhost")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(name).issuer_name(name)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(minutes=5))
            .not_valid_after(now + datetime.timedelta(days=1))
            .add_extension(x509.SubjectAlternativeName(
                [x509.DNSName("localhost"),
                 x509.IPAddress(ipaddress.ip_address("127.0.0.1"))]),
                critical=False)
            .sign(key, hashes.SHA256()))
    cert_path = d / "server.crt"
    key_path = d / "server.key"
    cert_path.write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    key_path.write_bytes(key.private_bytes(
        serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption()))

    storage = new_mock_storage()
    srv = Server(storage, port=0, ssl_cert=str(cert_path),
                 ssl_key=str(key_path))
    srv.start()
    yield srv
    srv.close()


def _client_ssl_ctx():
    import ssl
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx.check_hostname = False
    ctx.verify_mode = ssl.CERT_NONE  # self-signed test cert
    return ctx


def test_tls_upgrade_end_to_end(tls_server):
    c = MiniClient(tls_server.port, ssl_ctx=_client_ssl_ctx())
    import ssl
    assert isinstance(c.sock, ssl.SSLSocket)  # actually upgraded
    c.query("create database if not exists tlsdb")
    c.query("use tlsdb")
    c.query("drop table if exists t")
    c.query("create table t (id bigint primary key, v bigint)")
    assert c.query("insert into t values (1, 10), (2, 20)") == 2
    cols, rows = c.query("select id, v from t order by id")
    assert cols == ["id", "v"] and rows == [["1", "10"], ["2", "20"]]
    c.close()


def test_tls_server_still_accepts_plaintext(tls_server):
    # a client that ignores CLIENT_SSL keeps working on the same listener
    c = MiniClient(tls_server.port)
    assert c.server_caps & 0x0800  # offered...
    cols, rows = c.query("select 1 + 1")
    assert rows == [["2"]]  # ...but not required
    c.close()


def test_plain_server_does_not_offer_ssl(server):
    c = MiniClient(server.port)
    assert not (c.server_caps & 0x0800)
    c.close()


def test_kill_query_over_wire(server):
    """KILL QUERY <id> from a second connection aborts the first
    connection's running statement with MySQL error 1317, and the victim
    connection stays usable (reference: server kill dispatch)."""
    import threading
    import time as _t

    from tinysql_tpu import fail

    c1 = MiniClient(server.port)
    c1.query("create database if not exists killdb")
    c1.query("use killdb")
    c1.query("create table if not exists k (a int primary key, b int)")
    c1.query("insert into k values " + ", ".join(
        f"({i}, {i})" for i in range(1, 101)))
    c1.query("set @@tidb_use_tpu = 0")
    c1.query("set @@tidb_max_chunk_size = 8")
    victim_id = max(server.conns)  # c1 is the newest connection
    c2 = MiniClient(server.port)
    box = []

    def slow():
        try:
            box.append(c1.query("select * from k"))
        except RuntimeError as e:
            box.append(e)
    fail.arm("execSlowNext", sleep=0.02)
    try:
        t = threading.Thread(target=slow)
        t.start()
        _t.sleep(0.1)
        c2.query(f"kill query {victim_id}")
        t.join(10)
        assert not t.is_alive()
    finally:
        fail.disarm("execSlowNext")
    assert isinstance(box[0], RuntimeError) and "1317" in str(box[0]), \
        box[0]
    # the killed CONNECTION survives a KILL QUERY
    assert c1.query("select count(*) from k")[1] == [["100"]]
    c1.close()
    c2.close()


def test_plain_kill_drops_connection(server):
    import socket as _socket

    c1 = MiniClient(server.port)
    c1.query("select 1")
    victim_id = max(server.conns)
    c2 = MiniClient(server.port)
    c2.query(f"kill {victim_id}")
    # the victim's next command gets a closed socket (server dropped it
    # after the in-flight command window)
    deadline = __import__("time").time() + 5
    dead = False
    while __import__("time").time() < deadline and not dead:
        try:
            c1.query("select 1")
            __import__("time").sleep(0.05)
        except (RuntimeError, ConnectionError, OSError, _socket.error):
            dead = True
    assert dead, "plain KILL did not drop the victim connection"
    c2.close()


# ---- open files: the soft limit at start, EMFILE at accept ---------------

@pytest.mark.parametrize("soft, hard, want", [
    (256, 4096, [(4096, 4096)]),
    (1024, -1, [(1 << 20, -1)]),  # RLIM_INFINITY: the kernel's ceiling
    (4096, 4096, []),
])
def test_start_raises_the_soft_open_file_limit(monkeypatch, soft, hard,
                                               want):
    """A server meant for a thousand connections lifts its soft
    open-file limit to the hard one at start, and leaves a limit that is
    already there alone."""
    import resource
    hard = resource.RLIM_INFINITY if hard == -1 else hard
    want = [(w, resource.RLIM_INFINITY if h == -1 else h) for w, h in want]
    calls = []
    monkeypatch.setattr(resource, "getrlimit", lambda which: (soft, hard))
    monkeypatch.setattr(resource, "setrlimit",
                        lambda which, limits: calls.append(limits))
    srv = Server(new_mock_storage(), port=0)
    srv.start()
    srv.close()
    assert calls == want


def test_accept_loop_survives_emfile():
    """Out of files at accept is transient: the loop backs off and
    accepts again, and the next connection is served; it ends only when
    the server closes its socket."""
    import errno
    import threading
    srv = Server(new_mock_storage(), port=0)
    others = set(threading.enumerate())
    srv.start()
    (accept,) = [t for t in threading.enumerate()
                 if t.name == "mysql-accept" and t not in others]
    real = srv.sock

    class Flaky:
        """The listening socket, whose next accept fails once."""
        raised = 0

        def accept(self):
            if not self.raised:
                self.raised += 1
                raise OSError(errno.EMFILE, "Too many open files")
            return real.accept()

        def fileno(self):
            return real.fileno()

        def shutdown(self, how):
            real.shutdown(how)

        def close(self):
            real.close()

    flaky = srv.sock = Flaky()
    first = MiniClient(srv.port)   # the accept in flight was the real one
    second = MiniClient(srv.port)  # after the EMFILE
    assert flaky.raised == 1
    assert second.query("select 1 + 1")[1] == [["2"]]
    first.close()
    second.close()
    srv.close()
    from _timelimit import until
    until(lambda: not accept.is_alive(),
          "the accept loop ended with its socket")

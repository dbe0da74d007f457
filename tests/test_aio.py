"""C10k event-loop wire front end tests (server/aio.py, ISSUE 15).

Every serving invariant must survive the thread-per-connection -> event
loop hop, so this file re-proves the wire contracts OVER THE LOOP with
the MiniClient protocol driver: parked-connection processlist rows, the
1040 cap at accept, 1041 shed + retry hint, partial-frame reassembly,
the slowloris half-open timeout, KILL on idle / running / queued,
mid-server wire-mode flips, storm == solo byte identity, and queue-wait
attribution landing in statements_summary across the loop->pool hop.
"""
import socket
import struct
import threading
import time

import pytest

from _timelimit import hit, in_queue, until
from test_server import MiniClient
from tinysql_tpu import fail
from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.server.packetio import PacketIO
from tinysql_tpu.server.server import Server
from tinysql_tpu.session.session import Session


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fail.disarm_all()
    fail.reset_hits()
    yield
    fail.disarm_all()


@pytest.fixture(scope="module")
def server():
    storage = new_mock_storage()
    srv = Server(storage, port=0)
    srv.start()
    boot = Session(storage)
    boot.execute("set global tidb_wire_mode = 'aio'")
    boot.execute("create database if not exists av")
    boot.execute("use av")
    boot.execute("create table t (a int primary key, b int, c double)")
    boot.execute("insert into t values " + ", ".join(
        f"({i}, {i % 53}, {i * 0.25})" for i in range(3000)))
    boot.execute("set global tidb_tpu_min_rows = 16")
    boot.execute("select a, b, c from t")  # hydrate the columnar replica
    yield srv
    srv.close()


def _sess(server, db="av"):
    s = Session(server.storage)
    if db:
        s.execute(f"use {db}")
    return s


def _loop_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("aio-loop-")]


def _conn_threads():
    return {t.name for t in threading.enumerate()
            if t.name.startswith("conn-")}


# =========================================================================
# basic serving through the loop
# =========================================================================

def test_roundtrip_no_connection_thread(server):
    """Queries round-trip through the event loop and the connection
    costs ZERO threads — no conn-<id> reader exists for it."""
    c = MiniClient(server.port, db="av")
    cid = max(server.conns)
    assert _loop_threads(), "no aio event loop running"
    assert f"conn-{cid}" not in _conn_threads()
    cols, rows = c.query("select a, b from t where a = 7")
    assert cols == ["a", "b"] and rows == [["7", "7"]]
    assert c.query("insert into t values (100000, 1, 1.5)") == 1
    assert c.query("delete from t where a = 100000") == 1
    # multi-statement COM_QUERY chains responses over the async driver
    c.io.reset_sequence()
    c.io.write_packet(b"\x03" + b"select 1; select 2")
    from tinysql_tpu.server.packetio import read_lenenc_int
    for want in ("1", "2"):
        first = c.io.read_packet()
        ncols, _ = read_lenenc_int(first, 0)
        for _ in range(ncols):
            c.io.read_packet()
        assert c.io.read_packet()[0] == 0xFE
        row = c.io.read_packet()
        assert want.encode() in row
        eof = c.io.read_packet()
        assert eof[0] == 0xFE
        if want == "1":
            status = struct.unpack_from("<H", eof, 3)[0]
            assert status & 0x0008, "SERVER_MORE_RESULTS_EXISTS missing"
    c.close()


def test_parked_connection_processlist_roundtrip(server):
    """Parked idle connections are first-class processlist citizens:
    Sleep rows with their conn ids, queryable over the wire THROUGH the
    same loop."""
    parked = [MiniClient(server.port, db="av") for _ in range(3)]
    parked_ids = sorted(server.conns)[-3:]
    obs = MiniClient(server.port, db="av")
    _, rows = obs.query(
        "select id, command, state from information_schema.processlist")
    by_id = {int(r[0]): r for r in rows}
    for pid in parked_ids:
        assert pid in by_id, (parked_ids, rows)
        assert by_id[pid][1] == "Sleep"
    for c in parked:
        c.close()
    obs.close()


def test_prepared_statement_over_loop(server):
    """The binary protocol works over the loop (inline leg): prepare /
    execute / close on a parked connection."""
    c = MiniClient(server.port, db="av")
    c.io.reset_sequence()
    c.io.write_packet(b"\x16" + b"select a, b from t where a = ?")
    d = c.io.read_packet()
    assert d[0] == 0x00
    stmt_id = struct.unpack_from("<I", d, 1)[0]
    nparams = struct.unpack_from("<H", d, 7)[0]
    assert nparams == 1
    # drain param defs + column defs (each block EOF-terminated)
    for _ in range(2):
        while c.io.read_packet()[0] != 0xFE:
            pass
    c.io.reset_sequence()
    pl = struct.pack("<IBI", stmt_id, 0, 1)
    pl += b"\x00" + b"\x01" + bytes([0x08, 0x00])
    pl += struct.pack("<q", 11)
    c.io.write_packet(b"\x17" + pl)
    first = c.io.read_packet()
    from tinysql_tpu.server.packetio import read_lenenc_int
    nc, _ = read_lenenc_int(first, 0)
    assert nc == 2
    for _ in range(nc):
        c.io.read_packet()
    assert c.io.read_packet()[0] == 0xFE
    row = c.io.read_packet()
    assert row[0] == 0x00  # binary row header
    assert struct.unpack_from("<q", row, 2)[0] == 11
    while True:
        d = c.io.read_packet()
        if d[0] == 0xFE and len(d) < 9:
            break
    c.io.reset_sequence()
    c.io.write_packet(b"\x19" + struct.pack("<I", stmt_id))
    assert c.query("select 1 + 1")[1] == [["2"]]
    c.close()


# =========================================================================
# admission: 1040 at accept, 1041 over the loop
# =========================================================================

def test_connection_cap_1040_at_accept(server):
    """The 1040 gate runs AT ACCEPT in aio mode too: over-cap connects
    get ERR 1040 as the very first packet, and the shed is counted in
    the tinysql_conn_* feed."""
    from tinysql_tpu.server.admission import conn_stats_snapshot
    boot = _sess(server, db="")
    keep = [MiniClient(server.port) for _ in range(2)]
    cap = len(server.conns)
    boot.execute(f"set global tidb_max_server_connections = {cap}")
    sheds0 = conn_stats_snapshot()["sheds"]
    try:
        s = socket.create_connection(("127.0.0.1", server.port),
                                     timeout=5)
        d = PacketIO(s).read_packet()
        assert d[0] == 0xFF
        assert struct.unpack_from("<H", d, 1)[0] == 1040
        assert b"Too many connections" in d
        s.close()
        assert conn_stats_snapshot()["sheds"] > sheds0
        # capacity released -> connects succeed again
        keep.pop().close()
        deadline = time.monotonic() + 5
        while len(server.conns) >= cap and time.monotonic() < deadline:
            time.sleep(0.05)
        MiniClient(server.port).close()
    finally:
        boot.execute("set global tidb_max_server_connections = 0")
        for c in keep:
            c.close()


def test_admission_reject_1041_over_loop(server):
    """Queue at capacity -> MySQL 1041 with the retry hint, delivered
    by the EVENT LOOP at async submit time; the parked connection
    survives and works once pressure clears."""
    from tinysql_tpu.server.admission import stats_snapshot as adm_stats
    boot = _sess(server)
    boot.execute("set global tidb_stmt_pool_size = 1")
    boot.execute("set global tidb_stmt_pool_queue_depth = 1")
    try:
        c1 = MiniClient(server.port, db="av")
        c2 = MiniClient(server.port, db="av")
        c3 = MiniClient(server.port, db="av")
        fail.arm("admissionDelay", sleep=0.8, times=2)
        r0 = adm_stats()["rejected"]
        box = []
        t1 = threading.Thread(
            target=lambda: box.append(c1.query("select count(*) from t")))
        t1.start()
        hit("admissionDelay")  # worker wedged with c1's entry claimed
        t2 = threading.Thread(
            target=lambda: box.append(c2.query("select count(*) from t")))
        t2.start()
        in_queue(server.pool)  # c2 occupies the queue (depth 1)
        with pytest.raises(RuntimeError) as ei:
            c3.query("select count(*) from t")
        assert "1041" in str(ei.value) and "retry" in str(ei.value)
        assert adm_stats()["rejected"] > r0
        t1.join(30)
        t2.join(30)
        assert len(box) == 2
        assert c3.query("select 1 + 1")[1] == [["2"]]
        for c in (c1, c2, c3):
            c.close()
    finally:
        boot.execute("set global tidb_stmt_pool_size = 4")
        boot.execute("set global tidb_stmt_pool_queue_depth = 64")
        fail.disarm("admissionDelay")


# =========================================================================
# framing: partial frames, slowloris
# =========================================================================

def test_partial_frame_reassembly(server):
    """A statement split across arbitrarily small writes (header and
    payload fragmented separately) reassembles into ONE statement; two
    pipelined commands in one segment both answer."""
    c = MiniClient(server.port, db="av")
    sql = b"\x03" + b"select count(*) from t where a < 50"
    frame = struct.pack("<I", len(sql))[:3] + b"\x00" + sql
    # drip-feed: 3 bytes of header, stall, rest of header+payload in
    # 5-byte chunks with stalls between
    c.sock.sendall(frame[:3])
    time.sleep(0.05)
    for i in range(3, len(frame), 5):
        c.sock.sendall(frame[i:i + 5])
        time.sleep(0.01)
    first = c.io.read_packet()
    from tinysql_tpu.server.packetio import read_lenenc_int
    ncols, _ = read_lenenc_int(first, 0)
    assert ncols == 1
    c.io.read_packet()                    # column def
    assert c.io.read_packet()[0] == 0xFE  # EOF
    row = c.io.read_packet()
    assert b"50" in row
    assert c.io.read_packet()[0] == 0xFE
    # two complete commands in ONE sendall: both answered, in order
    q1 = b"\x03" + b"select 11"
    q2 = b"\x03" + b"select 22"
    seg = (struct.pack("<I", len(q1))[:3] + b"\x00" + q1
           + struct.pack("<I", len(q2))[:3] + b"\x00" + q2)
    c.sock.sendall(seg)
    got = []
    for _ in range(2):
        first = c.io.read_packet()
        ncols, _ = read_lenenc_int(first, 0)
        for _ in range(ncols):
            c.io.read_packet()
        assert c.io.read_packet()[0] == 0xFE
        got.append(bytes(c.io.read_packet()))
        assert c.io.read_packet()[0] == 0xFE
        c.io.reset_sequence()
    assert b"11" in got[0] and b"22" in got[1]
    c.close()


def test_slowloris_half_open_timeout(server):
    """A half-open peer is reaped: stalled mid-handshake AND stalled
    mid-frame connections close after tidb_aio_frame_timeout_ms, while
    a genuinely IDLE parked connection (no partial frame) never times
    out."""
    boot = _sess(server, db="")
    boot.execute("set global tidb_aio_frame_timeout_ms = 300")
    try:
        # (a) connects, reads the greeting, never answers the handshake
        s = socket.create_connection(("127.0.0.1", server.port),
                                     timeout=5)
        greeting = PacketIO(s).read_packet()
        assert greeting[0] == 10
        s.settimeout(3)
        t0 = time.monotonic()
        assert s.recv(1) == b""  # server closed on us
        assert time.monotonic() - t0 < 2.5
        s.close()
        # (b) authenticated, then stalls MID-FRAME
        c = MiniClient(server.port, db="av")
        idle = MiniClient(server.port, db="av")  # control: no bytes
        c.sock.sendall(b"\x20\x00")  # 2 bytes of a 4-byte header
        c.sock.settimeout(3)
        t0 = time.monotonic()
        assert c.sock.recv(1) == b""
        assert time.monotonic() - t0 < 2.5
        c.sock.close()
        # the idle control connection survived both reap windows
        assert idle.query("select 1 + 1")[1] == [["2"]]
        idle.close()
    finally:
        boot.execute("set global tidb_aio_frame_timeout_ms = 10000")


# =========================================================================
# KILL semantics over the loop
# =========================================================================

def test_kill_idle_connection_closes_within_tick(server):
    """The ISSUE 15 regression fix: plain KILL on a PARKED IDLE
    connection has no reader thread to notice — the loop must wake via
    its self-pipe and close the socket promptly."""
    victim = MiniClient(server.port, db="av")
    victim.query("select 1")
    victim_id = max(server.conns)
    killer = MiniClient(server.port)
    t0 = time.monotonic()
    killer.query(f"kill {victim_id}")
    victim.sock.settimeout(3)
    try:
        data = victim.sock.recv(1)
    except (ConnectionError, OSError):
        data = b""
    elapsed = time.monotonic() - t0
    assert data == b"", "victim socket still open after plain KILL"
    # one loop tick is 100ms; the self-pipe makes it near-immediate,
    # the bound just needs to beat any polling fallback
    assert elapsed < 1.0, f"killed idle connection closed in {elapsed:.2f}s"
    assert victim_id not in server.conns
    killer.close()


def test_kill_query_running_over_loop(server):
    """KILL QUERY aborts a RUNNING statement with 1317; the victim
    connection survives and keeps working through the loop."""
    c1 = MiniClient(server.port, db="av")
    c1.query("set @@tidb_use_tpu = 0")
    c1.query("set @@tidb_max_chunk_size = 8")
    victim_id = max(server.conns)
    c2 = MiniClient(server.port)
    box = []

    def slow():
        try:
            box.append(c1.query("select * from t"))
        except RuntimeError as e:
            box.append(e)
    fail.arm("execSlowNext", sleep=0.02)
    try:
        t = threading.Thread(target=slow)
        t.start()
        hit("execSlowNext")
        c2.query(f"kill query {victim_id}")
        t.join(10)
        assert not t.is_alive()
    finally:
        fail.disarm("execSlowNext")
    assert isinstance(box[0], RuntimeError) and "1317" in str(box[0]), \
        box[0]
    # KILL QUERY leaves the connection alive
    assert c1.query("select count(*) from t")[1] == [["3000"]]
    c1.close()
    c2.close()


def test_kill_queued_statement_over_loop(server):
    """KILL QUERY reaches a statement still WAITING in the admission
    queue behind the loop: cancel_if_queued fails it with 1317 without
    a worker ever touching it."""
    boot = _sess(server)
    boot.execute("set global tidb_stmt_pool_size = 1")
    try:
        c1 = MiniClient(server.port, db="av")
        victim = MiniClient(server.port, db="av")
        victim.query("select 1")
        victim_id = max(server.conns)
        fail.arm("admissionDelay", sleep=1.0, times=1)
        t1 = threading.Thread(
            target=lambda: c1.query("select count(*) from t"))
        t1.start()
        hit("admissionDelay")
        box = []

        def queued_victim():
            try:
                box.append(victim.query("select count(*) from t"))
            except RuntimeError as e:
                box.append(e)
        t2 = threading.Thread(target=queued_victim)
        t2.start()
        in_queue(server.pool)
        killer = MiniClient(server.port)
        killer.query(f"kill query {victim_id}")
        t2.join(10)
        assert not t2.is_alive(), "KILL did not reach the queued statement"
        assert isinstance(box[0], RuntimeError) and "1317" in str(box[0])
        t1.join(30)
        for c in (c1, victim, killer):
            c.close()
    finally:
        boot.execute("set global tidb_stmt_pool_size = 4")
        fail.disarm("admissionDelay")


def test_write_backpressure_pauses_and_resumes(server):
    """A client that pipelines many large resultsets WITHOUT reading
    must not grow the server's outbound buffer unboundedly: past the
    high-water mark the loop stops reading/executing that connection's
    commands, then resumes as the peer drains — every response still
    arrives complete and in order."""
    import struct as _struct
    from tinysql_tpu.server.aio import WBUF_HWM
    c = MiniClient(server.port, db="av")
    conn, = [lp.conns[max(server.conns)] for lp in server._aio._loops
             if max(server.conns) in lp.conns]
    # loopback's auto-tuned send buffer takes all ~2.4MB by itself and
    # the mark is never reached: pin this connection's to 16KB
    conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 14)
    n = 40  # ~60KB per resultset >> WBUF_HWM in aggregate
    sql = b"\x03" + b"select a, b, c from t"
    frame = _struct.pack("<I", len(sql))[:3] + b"\x00" + sql
    c.sock.sendall(frame * n)
    until(lambda: len(conn.wbuf) > WBUF_HWM,
          "outbound buffer at the high-water mark")
    # past the mark the loop neither reads nor executes: the buffer
    # stops within one resultset of it with commands still unparsed,
    # instead of absorbing all ~2.4MB of pipelined responses
    parked = len(conn.rbuf)
    assert parked >= len(frame), parked
    time.sleep(0.2)  # a window in which nothing may move
    assert WBUF_HWM < len(conn.wbuf) <= WBUF_HWM + (1 << 16)
    assert len(conn.rbuf) == parked
    # now drain: all n responses arrive complete, in order
    from tinysql_tpu.server.packetio import read_lenenc_int
    for i in range(n):
        first = c.io.read_packet()
        ncols, _ = read_lenenc_int(first, 0)
        assert ncols == 3, (i, first[:20])
        for _ in range(ncols):
            c.io.read_packet()
        assert c.io.read_packet()[0] == 0xFE
        rows = 0
        while True:
            d = c.io.read_packet()
            if d[0] == 0xFE and len(d) < 9:
                break
            rows += 1
        assert rows == 3000, (i, rows)
        c.io.reset_sequence()
    assert c.query("select 1 + 1")[1] == [["2"]]
    c.close()


def test_peer_drop_mid_statement_defers_teardown(server):
    """A client vanishing (EOF) while its statement is still on a pool
    worker must not race the worker: the loop aborts the statement via
    the guard, defers the session teardown to the completion callback,
    and the server stays healthy."""
    c = MiniClient(server.port, db="av")
    victim_id = max(server.conns)
    fail.arm("execSlowNext", sleep=0.05)
    try:
        c.query("set @@tidb_use_tpu = 0")
        c.query("set @@tidb_max_chunk_size = 8")
        # fire a slow scan, then slam the socket shut mid-execution
        c.io.reset_sequence()
        fail.reset_hits()
        c.io.write_packet(b"\x03" + b"select * from t")
        hit("execSlowNext")
        c.sock.close()
        # the conn deregisters once the worker finishes with the session
        deadline = time.monotonic() + 10
        while victim_id in server.conns and time.monotonic() < deadline:
            time.sleep(0.05)
        assert victim_id not in server.conns
    finally:
        fail.disarm("execSlowNext")
    # the loop and pool both survived
    ok = MiniClient(server.port, db="av")
    assert ok.query("select count(*) from t")[1] == [["3000"]]
    ok.close()


# =========================================================================
# wire-mode flip mid-server
# =========================================================================

def test_mode_flip_mid_server(server):
    """tidb_wire_mode is read per accept: flipping legacy<->aio
    mid-server routes NEW connections while established ones keep
    working in the mode they arrived under."""
    boot = _sess(server, db="")
    aio_conn = MiniClient(server.port, db="av")
    boot.execute("set global tidb_wire_mode = 'legacy'")
    try:
        legacy_conn = MiniClient(server.port, db="av")
        legacy_id = max(server.conns)
        # the legacy connection got a dedicated reader thread ...
        deadline = time.monotonic() + 5
        while f"conn-{legacy_id}" not in _conn_threads() \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert f"conn-{legacy_id}" in _conn_threads()
        # ... and both coexist against the same pool
        assert legacy_conn.query("select count(*) from t")[1] == [["3000"]]
        assert aio_conn.query("select count(*) from t")[1] == [["3000"]]
        legacy_conn.close()
    finally:
        boot.execute("set global tidb_wire_mode = 'aio'")
    back = MiniClient(server.port, db="av")
    back_id = max(server.conns)
    assert f"conn-{back_id}" not in _conn_threads()
    assert back.query("select 1 + 1")[1] == [["2"]]
    back.close()
    aio_conn.close()
    # a junk mode is rejected at SET time
    with pytest.raises(Exception, match="tidb_wire_mode"):
        boot.execute("set global tidb_wire_mode = 'turbo'")


# =========================================================================
# storm == solo byte identity + wait attribution across the hop
# =========================================================================

def test_storm_equals_solo_through_loop(server):
    """Same-digest storm through parked aio connections: every wire
    answer is byte-identical (same text-protocol strings) to the solo
    answer on a quiet connection, with zero errors — coalescing and
    stacking stay invisible through the loop."""
    variants = [f"select sum(c), count(*) from t where b < {5 + i % 6}"
                for i in range(24)]
    solo = MiniClient(server.port, db="av")
    ref = {sql: solo.query(sql) for sql in set(variants)}
    errors = []
    mismatch = []

    def client(jobs):
        try:
            c = MiniClient(server.port, db="av")
        except Exception as e:
            errors.append(f"connect: {e}")
            return
        try:
            for sql in jobs:
                try:
                    got = c.query(sql)
                except Exception as e:
                    errors.append(repr(e))
                    continue
                if got != ref[sql]:
                    mismatch.append((sql, ref[sql], got))
        finally:
            c.close()

    jobs = [[] for _ in range(6)]
    for i, sql in enumerate(variants):
        jobs[i % 6].append(sql)
    threads = [threading.Thread(target=client, args=(j,)) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:5]
    assert not mismatch, mismatch[:1]
    solo.close()


def test_queue_wait_attribution_crosses_loop_pool_hop(server):
    """The loop-thread submit must carry the obs contract across the
    loop->pool hop (CC704): a statement that QUEUED behind a wedged
    worker lands its measured queue wait in statements_summary."""
    from tinysql_tpu.obs import stmtsummary
    boot = _sess(server)
    boot.execute("set global tidb_stmt_pool_size = 1")
    sql = "select max(c), min(b) from t where b < 40"
    digest, _ = stmtsummary.normalize(sql)
    try:
        c1 = MiniClient(server.port, db="av")
        c2 = MiniClient(server.port, db="av")
        fail.arm("admissionDelay", sleep=0.5, times=1)
        t1 = threading.Thread(
            target=lambda: c1.query("select count(*) from t"))
        t1.start()
        hit("admissionDelay")  # c1's worker is inside the wedge
        c2.query(sql)           # queues behind it, then executes
        t1.join(30)
        rows = [r for r in stmtsummary.snapshot()
                if r.get("digest") == digest]
        assert rows, "storm digest missing from statements_summary"
        assert float(rows[0]["sum_ms"].get("queue", 0.0)) > 50, rows
        c1.close()
        c2.close()
    finally:
        boot.execute("set global tidb_stmt_pool_size = 4")
        fail.disarm("admissionDelay")


# =========================================================================
# TLS handoff
# =========================================================================

def test_tls_handoff_to_legacy_thread(tmp_path):
    """An SSLRequest in aio mode hands the connection to a legacy
    conn-<id> thread (the loop never parks TLS sockets); plaintext
    connections on the same listener stay on the loop."""
    pytest.importorskip("cryptography")
    import datetime
    import ipaddress
    import ssl
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    name = x509.Name(
        [x509.NameAttribute(NameOID.COMMON_NAME, "localhost")])
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
    cert_path = tmp_path / "server.crt"
    key_path = tmp_path / "server.key"
    cert_path.write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    key_path.write_bytes(key.private_bytes(
        serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption()))

    storage = new_mock_storage()
    srv = Server(storage, port=0, ssl_cert=str(cert_path),
                 ssl_key=str(key_path))
    srv.start()
    boot = Session(storage)
    boot.execute("set global tidb_wire_mode = 'aio'")
    try:
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ctx.check_hostname = False
        ctx.verify_mode = ssl.CERT_NONE
        c = MiniClient(srv.port, ssl_ctx=ctx)
        assert isinstance(c.sock, ssl.SSLSocket)
        tls_id = max(srv.conns)
        assert f"conn-{tls_id}" in _conn_threads()  # handed off
        assert c.query("select 1 + 1")[1] == [["2"]]
        c.close()
        # plaintext on the same listener: parked on the loop, no thread
        pc = MiniClient(srv.port)
        plain_id = max(srv.conns)
        assert f"conn-{plain_id}" not in _conn_threads()
        assert pc.query("select 2 + 2")[1] == [["4"]]
        pc.close()
    finally:
        srv.close()


# =========================================================================
# a burst over many parked connections (the benchmark's idle_1k_burst,
# small): every answer equals legacy's and numpy's; the queue's depth
# decides between a wait and 1041; the loop's counters and aio.tick
# =========================================================================

BURST = 256
Q6_SHAPED = ("select sum(price * disc) from li where ship >= '{y}-01-01' "
             "and ship < '{y1}-01-01' and disc between {lo} and {hi} "
             "and qty < 24")


@pytest.fixture(scope="module")
def burst_server():
    """A server of its own (its front end's counters start at 0) over a
    small Q6-shaped table, and the table as numpy arrays."""
    import numpy as np
    rng = np.random.default_rng(42)
    n = 2000
    price = np.round(rng.uniform(900, 100000, n), 2)
    disc = rng.integers(0, 11, n) / 100
    qty = rng.integers(1, 51, n)
    day = rng.integers(0, 7 * 365, n)
    ship = (np.datetime64("1992-01-01") + day).astype(str)
    storage = new_mock_storage()
    srv = Server(storage, port=0)
    srv.start()
    boot = Session(storage)
    boot.execute("create database if not exists bq")
    boot.execute("use bq")
    boot.execute("create table li (id int primary key, price double, "
                 "disc double, qty int, ship varchar(10))")
    boot.execute("insert into li values " + ", ".join(
        f"({i}, {float(price[i])!r}, {float(disc[i])!r}, {int(qty[i])}, "
        f"'{ship[i]}')"
        for i in range(n)))
    boot.execute("set global tidb_tpu_min_rows = 16")
    boot.execute("select sum(price) from li")  # hydrate the replica
    yield srv, {"price": price, "disc": disc, "qty": qty, "ship": ship}
    srv.close()


def _q6(i):
    """The i-th of 16 variants and its parameters."""
    y, lo = 1993 + i % 2, (i // 2) % 8 / 100
    return Q6_SHAPED.format(y=y, y1=y + 1, lo=f"{lo:.2f}",
                            hi=f"{lo + 0.02:.2f}"), (y, lo)


def _numpy_q6(t, y, lo):
    m = ((t["ship"] >= f"{y}-01-01") & (t["ship"] < f"{y + 1}-01-01")
         & (t["disc"] >= lo - 1e-9) & (t["disc"] <= lo + 0.02 + 1e-9)
         & (t["qty"] < 24))
    return float((t["price"][m] * t["disc"][m]).sum())


def _burst(port, sqls):
    """One burst: a connection for each statement, opened and parked,
    then every statement sent before any answer is read.  Each answer's
    rows, or the error's text."""
    clients = [MiniClient(port, db="bq") for _ in sqls]
    try:
        for c, sql in zip(clients, sqls):
            c.sock.settimeout(60)
            c.send_query(sql)
        out = []
        for c in clients:
            try:
                out.append(c.read_result()[1])
            except RuntimeError as e:
                out.append(str(e))
        return out
    finally:
        for c in clients:
            c.close()


def test_burst_over_parked_connections_equals_legacy_and_numpy(
        burst_server):
    from tinysql_tpu.obs import trace
    srv, table = burst_server
    boot = _sess(srv, db="bq")
    boot.execute(f"set global tidb_stmt_pool_queue_depth = {BURST}")
    sqls, params = zip(*(_q6(i) for i in range(BURST)))
    boot.execute("set global tidb_wire_mode = 'legacy'")
    got = {"legacy": _burst(srv.port, sqls)}
    boot.execute("set global tidb_wire_mode = 'aio'")
    before = trace.totals()
    got["aio"] = _burst(srv.port, sqls)
    after = trace.totals()
    assert got["aio"] == got["legacy"]
    for rows, (y, lo) in zip(got["aio"], params):
        assert rows == [[rows[0][0]]], rows  # an answer, not an error
        assert float(rows[0][0]) == pytest.approx(_numpy_q6(table, y, lo),
                                                  rel=1e-12)
    snap = srv._aio.snapshot()
    assert snap["submits"] == BURST and snap["conns_max"] >= BURST

    def grew(name, key="count"):
        return after.get(name, {}).get(key, 0) \
            - before.get(name, {}).get(key, 0)
    assert grew("aio_submits") == BURST and grew("aio_conns_max") >= BURST
    assert BURST <= grew("aio_events") <= snap["events"]
    # the loop's passes: their children's time is not their own
    assert grew("aio.tick") > 0
    assert 0 < grew("aio.tick", "self_s") < grew("aio.tick", "sum_s")
    from tinysql_tpu.obs.metrics import render_prometheus
    scraped = render_prometheus()
    for name in ("tinysql_aio_events_total", "tinysql_aio_submits_total",
                 "tinysql_aio_flush_waits_total", "tinysql_aio_conns_max"):
        assert f"\n{name} " in scraped, name


def test_a_parked_connections_command_is_a_tick_s_child(burst_server):
    """A command the loop handles inline is a child of the pass that
    handled it."""
    from tinysql_tpu.obs.trace import PROCESS
    srv, _ = burst_server
    _sess(srv, db="").execute("set global tidb_wire_mode = 'aio'")
    c = MiniClient(srv.port, db="bq")
    c.io.reset_sequence()
    c.io.write_packet(b"\x0e")  # COM_PING
    assert c.io.read_packet()[0] == 0x00
    cid = max(srv.conns)
    c.close()

    def pings():
        return [s for s in PROCESS.spans() if s["name"] == "wire.command"
                and s["args"] == {"cmd": 0x0e, "conn": cid}]
    until(pings, "the ping's command span ended")
    (ping,) = pings()
    # a span reaches the ring as it ends, the tick after its command
    until(lambda: any(s["id"] == ping["parent"] and s["name"] == "aio.tick"
                      for s in PROCESS.spans()),
          "the ping's parent is the aio.tick that handled it")


@pytest.mark.parametrize("depth, shed", [(BURST, 0), (64, BURST - 64)])
def test_burst_waits_in_a_queue_as_deep_as_itself(burst_server, depth,
                                                  shed):
    """With the one worker wedged, a burst waits whole in a queue at
    least as deep as itself and is answered whole; in a shallower one
    every statement past its depth is shed with 1041."""
    srv, table = burst_server
    boot = _sess(srv, db="bq")
    boot.execute("set global tidb_wire_mode = 'aio'")
    boot.execute(f"set global tidb_stmt_pool_queue_depth = {depth}")
    boot.execute("set global tidb_stmt_pool_size = 1")
    wedge = MiniClient(srv.port, db="bq")
    try:
        fail.arm("admissionDelay", sleep=2.0, times=1)
        box = []
        t = threading.Thread(target=lambda: box.append(
            wedge.query(_q6(0)[0])))
        t.start()
        hit("admissionDelay")  # the worker holds the wedge's statement
        sqls = [_q6(i)[0] for i in range(BURST)]
        got = _burst(srv.port, sqls)
        t.join(60)
        assert len(box) == 1
    finally:
        fail.disarm("admissionDelay")
        boot.execute("set global tidb_stmt_pool_size = 4")
        boot.execute(f"set global tidb_stmt_pool_queue_depth = {BURST}")
        wedge.close()
    errors = [g for g in got if isinstance(g, str)]
    assert len(errors) == shed
    assert all("1041" in e for e in errors)
    answered = [(g, _q6(i)[1]) for i, g in enumerate(got)
                if not isinstance(g, str)]
    assert len(answered) == BURST - shed
    for rows, (y, lo) in answered:
        assert float(rows[0][0]) == pytest.approx(_numpy_q6(table, y, lo),
                                                  rel=1e-12)

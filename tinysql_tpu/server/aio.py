"""C10k event-loop wire front end (ROADMAP item 3).

Thread-per-connection puts the serving ceiling at CONNECTION count:
every parked client pins a reader thread, so "millions of users" dies
at a few thousand OS threads long before the statement pool saturates.
This module multiplexes all connections accepted while
``tidb_wire_mode = 'aio'`` onto a bounded set of event-loop threads
(``tidb_aio_loops``, role ``aio``): idle connections park as registered
file objects in a ``selectors`` poll set, and complete COM_QUERY
statements are handed to the existing ``server/pool.py`` StatementPool
through the SAME admission gate (1041 shed + retry hint at submit; the
1040 connection cap runs at accept in ``server.py`` for both modes).

Division of labor per connection (one ``_AioConn`` state machine,
loop-thread-confined — no per-connection locks):

- **handshake / framing** — nonblocking: the greeting goes out at
  adoption, response packets are reassembled from whatever byte
  boundaries ``recv`` delivers (incl. 0xFFFFFF continuation frames),
  and a half-open peer that stalls mid-frame (or mid-handshake) is
  reaped after ``tidb_aio_frame_timeout_ms`` — the slowloris guard.
  TLS clients are handed off to a legacy ``conn-<id>`` thread at the
  SSLRequest packet (blocking wrap + blocking command loop); the loop
  itself never parks TLS sockets.
- **COM_QUERY** — async: each pooled statement is submitted with
  ``StatementPool.submit(on_done=...)``; the loop thread performs the
  submit, so the entry's ``contextvars.copy_context()`` captures the
  loop-side obs scope exactly like a connection thread would (CC704's
  cross-hop contract), and queue/batch wait attribution lands in
  statements_summary unchanged.  Completion is posted back over the
  loop's self-pipe; resultset encoding and all socket writes stay on
  the loop.  Control statements (SET / SHOW / KILL / BEGIN / DDL ...)
  execute inline on the loop — the control plane outlives a wedged
  pool, the ``admissionDelay`` drill's contract.
- **prepared statements / COM_FIELD_LIST / COM_INIT_DB** — reuse
  ``ClientConn.dispatch_command`` inline (COM_STMT_EXECUTE runs its
  pool leg blocking on the loop; the async path is COM_QUERY's).
- **KILL** — ``utils/interrupt.kill`` notifies the front end's
  observer; the victim's loop wakes via self-pipe and a killed IDLE
  connection closes within one tick — there is no blocked reader
  thread to notice otherwise.  A killed QUEUED statement is cancelled
  with ``cancel_if_queued`` (never occupies a worker); a RUNNING one
  aborts through the statement's own interrupt checks, and the
  connection drops after the in-flight command's response (plain-KILL
  parity with the legacy loop).

Every serving invariant survives the hop: sessions register in the
conn-id/process registries at adoption (``processlist`` shows parked
connections as Sleep rows), ``server.conns`` carries the ClientConn for
KILL targeting and drain, and storm results are byte-identical to the
thread-per-connection path (tests/test_aio.py).
"""
from __future__ import annotations

import logging
import os
import selectors
import struct
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ..obs import context as obs_context
from ..utils import interrupt
from ..utils.interrupt import QueryKilled
from . import protocol as p
from .packetio import MAX_PAYLOAD, PacketIO
from .pool import record_wake
from .server import ClientConn, _err_packet_for, parse_sql, record_idle

log = logging.getLogger("tinysql_tpu.aio")

#: fallback wake granularity (seconds): kill wakes and completions
#: arrive immediately over the self-pipe; the tick only paces the
#: slowloris sweep and the killed-while-unwatched backstop
_TICK_S = 0.1

#: outbound-buffer high-water mark (bytes): past it the loop stops
#: reading AND stops executing buffered commands for that connection
#: until the peer drains — the nonblocking twin of the backpressure a
#: legacy thread got for free from a blocking ``sendall``.  Without
#: this, one slow-reading client pipelining large resultsets grows
#: server memory without bound
WBUF_HWM = 1 << 20


class _ConnWriter:
    """``sendall`` target for a connection's PacketIO: protocol encoders
    (ok/err packets, resultset writers) land bytes in the connection's
    outbound buffer; the loop flushes it nonblocking."""

    __slots__ = ("_conn",)

    def __init__(self, conn: "_AioConn"):
        self._conn = conn

    def sendall(self, data: bytes) -> None:
        self._conn.wbuf += data


class _AioConn:
    """One multiplexed connection's state, confined to its loop thread.

    ``state``: handshake -> ready <-> running -> closing -> closed.
    ``ready`` with an empty read buffer IS the parked-idle state — the
    connection costs one registered file object and zero threads.
    """

    __slots__ = ("cc", "sock", "salt", "state", "rbuf", "wbuf", "parts",
                 "last_rx", "stmts", "idx", "sql", "entry", "events",
                 "pumping", "span", "submitted", "flushed")

    def __init__(self, cc: ClientConn):
        self.cc = cc
        self.sock = cc.sock
        self.salt = b""
        self.state = "handshake"
        self.rbuf = bytearray()
        self.wbuf = bytearray()
        self.parts: List[bytes] = []  # 0xFFFFFF continuation payloads
        self.last_rx = time.monotonic()
        self.stmts: list = []
        self.idx = 0
        self.sql = ""
        self.entry = None  # in-flight pool entry (async COM_QUERY leg)
        self.events = 0
        self.pumping = False
        #: the async COM_QUERY's ``wire.command`` span, begun when its
        #: packet is complete and ended when its response is flushed: no
        #: one stretch of the loop's thread runs it, so it is held here
        #: and not on the thread's span stack
        self.span = None
        self.submitted = 0.0  # perf_counter at the in-flight submit
        #: where the last command's span ended (``wire.idle`` runs from
        #: there to the next command's packet), None before the first
        self.flushed = None


class _Loop:
    """One event-loop thread: a selector over parked connections plus a
    self-pipe carrying adoptions, statement completions, and kill wakes
    from other threads.  All connection state is mutated here only."""

    def __init__(self, fe: "AioFrontEnd", idx: int):
        self.fe = fe
        self.sel = selectors.DefaultSelector()
        self._wake_r, self._wake_w = os.pipe()
        os.set_blocking(self._wake_r, False)
        os.set_blocking(self._wake_w, False)
        # data=None marks the wake pipe in the ready list
        self.sel.register(self._wake_r, selectors.EVENT_READ, None)
        self._mu = threading.Lock()
        self._inbox: deque = deque()
        self.conns: Dict[int, _AioConn] = {}
        self._closed = False
        self._last_tick = 0.0
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name=f"aio-loop-{idx}")

    # ---- cross-thread mailbox -------------------------------------------
    def post(self, item) -> None:
        """Enqueue work from any thread and wake the selector."""
        if self._closed:
            # the loop is gone, so a deferred session finalization
            # (_close_conn with an in-flight entry at shutdown) would
            # otherwise be lost — the worker is done with the session
            # once its completion posts here, so roll back on the
            # posting thread instead
            if item[0] == "done":
                conn, entry = item[1]
                if conn.state == "closed" and conn.entry is entry:
                    conn.entry = None
                    self._finalize_session(conn)
            return
        with self._mu:
            self._inbox.append(item)
        self._wake()

    def _wake(self) -> None:
        try:
            os.write(self._wake_w, b"\x00")
        except (BlockingIOError, OSError):
            pass  # pipe full = a wake is already pending; or closing

    def close(self) -> None:
        self._closed = True
        self._wake()

    # ---- loop body -------------------------------------------------------
    def _run(self) -> None:
        while not self._closed:
            try:
                events = self.sel.select(timeout=_TICK_S)
            except OSError:
                break
            if self._closed:
                break
            self._drain_inbox()
            for key, mask in events:
                if key.data is None:
                    try:
                        os.read(self._wake_r, 4096)
                    except (BlockingIOError, OSError):
                        pass
                    continue
                conn = key.data
                if mask & selectors.EVENT_WRITE and conn.state != "closed":
                    self._flush(conn)
                if mask & selectors.EVENT_READ and conn.state != "closed":
                    self._on_readable(conn)
            self._tick()
        # drain: handle completions already posted, close every parked
        # connection (rollback + deregister), then drain once more —
        # closing a connection with an in-flight entry cancels it, and
        # that cancellation's completion lands in the inbox.  Entries
        # completing after this point hit post()'s closed-loop path.
        self._drain_inbox()
        for conn in list(self.conns.values()):
            self._close_conn(conn)
        self._drain_inbox()
        try:
            self.sel.close()
        except OSError:
            pass
        for fd in (self._wake_r, self._wake_w):
            try:
                os.close(fd)
            except OSError:
                pass

    def _drain_inbox(self) -> None:
        while True:
            with self._mu:
                if not self._inbox:
                    return
                kind, arg = self._inbox.popleft()
            if kind == "new":
                self._adopt(arg)
            elif kind == "done":
                self._on_stmt_done(*arg)
            elif kind == "kill":
                self._on_kill(arg)

    def _tick(self) -> None:
        """Per-tick sweep: slowloris frame timeouts + the killed-session
        backstop (the self-pipe wake is the fast path; this bounds the
        worst case at one tick).  Paced to _TICK_S regardless of how
        often select() returns — under load the ready list keeps the
        loop hot, and an O(conns) sweep per event batch would burn the
        one thread that serializes all I/O."""
        from .pool import read_global_int
        now = time.monotonic()
        if now - self._last_tick < _TICK_S:
            return
        self._last_tick = now
        tmo_s = read_global_int(self.fe.server.storage,
                                "tidb_aio_frame_timeout_ms", 10000) / 1e3
        for conn in list(self.conns.values()):
            if conn.state == "closed":
                continue
            sess = conn.cc.session
            # 'closing' is covered too: a killed victim whose response
            # sits unflushed against a stalled peer had a full tick to
            # drain — force the close rather than leak the socket
            if conn.state in ("handshake", "ready", "closing") \
                    and sess.killed:
                self._close_conn(conn)
                continue
            if conn.state == "running" and conn.entry is not None \
                    and (sess.guard.killed or sess.killed):
                self.fe.server.pool.cancel_if_queued(conn.entry,
                                                     QueryKilled())
            if tmo_s > 0 and now - conn.last_rx > tmo_s and (
                    conn.state == "handshake"
                    or (conn.state == "ready"
                        and (conn.rbuf or conn.parts))
                    # write-side stall: a closing connection whose err
                    # packet / final response the peer never reads
                    or (conn.state == "closing" and conn.wbuf)):
                log.info("aio conn-%d reaped: stalled in state %s for "
                         ">%.0fms (slowloris guard)", conn.cc.conn_id,
                         conn.state, tmo_s * 1e3)
                self._close_conn(conn)

    # ---- adoption / teardown --------------------------------------------
    def _adopt(self, cc: ClientConn) -> None:
        conn = _AioConn(cc)
        cc.io = PacketIO(_ConnWriter(conn))
        conn.salt = p.new_salt()
        try:
            conn.sock.setblocking(False)
            cc.io.write_packet(p.handshake_v10(cc.conn_id, conn.salt,
                                               cc.greeting_caps()))
            self.sel.register(conn.sock, selectors.EVENT_READ, conn)
        except (OSError, ValueError):
            self._discard(conn)
            return
        conn.events = selectors.EVENT_READ
        self.conns[cc.conn_id] = conn
        self._flush(conn)

    def _discard(self, conn: _AioConn) -> None:
        """Teardown for a connection that never registered."""
        conn.state = "closed"
        try:
            conn.sock.close()
        except OSError:
            pass
        self.fe.server.remove_conn(conn.cc.conn_id)

    def _close_conn(self, conn: _AioConn) -> None:
        if conn.state == "closed":
            return
        conn.state = "closed"
        try:
            self.sel.unregister(conn.sock)
        except (KeyError, ValueError, OSError):
            pass
        self.conns.pop(conn.cc.conn_id, None)
        # deregister BEFORE the peer-visible close: the moment close()
        # sends FIN a client can observe the drop and ask the server
        # about this conn_id (processlist, the KILL-idle acceptance
        # test) — a registry row outliving its socket reads as a leak
        self.fe.server.remove_conn(conn.cc.conn_id)
        try:
            conn.sock.close()
        except OSError:
            pass
        entry = conn.entry
        if entry is not None:
            # an in-flight statement still owns the session (a pool
            # worker may be executing it): a legacy connection thread
            # would block in pool.run until completion before rolling
            # back — the async twin cancels/aborts it and DEFERS the
            # session teardown to the completion callback, so rollback
            # never races the worker on the same session
            if not self.fe.server.pool.cancel_if_queued(entry,
                                                        QueryKilled()):
                guard = getattr(conn.cc.session, "guard", None)
                if guard is not None:
                    guard.kill()  # the peer is gone; abort fast
            return
        self._finalize_session(conn)

    def _finalize_session(self, conn: _AioConn) -> None:
        try:
            conn.cc.session.rollback_txn()
        except Exception:
            pass

    # ---- socket I/O ------------------------------------------------------
    def _set_events(self, conn: _AioConn, want: int) -> None:
        if want == conn.events or conn.state == "closed":
            return
        try:
            self.sel.modify(conn.sock, want, conn)
            conn.events = want
        except (KeyError, ValueError, OSError):
            self._close_conn(conn)

    def _flush(self, conn: _AioConn) -> None:
        if conn.state == "closed":
            return
        while conn.wbuf:
            try:
                n = conn.sock.send(conn.wbuf)
            except (BlockingIOError, InterruptedError):
                break
            except OSError:
                self._close_conn(conn)
                return
            if n <= 0:
                break
            del conn.wbuf[:n]
        if conn.wbuf:
            # backpressure: past the high-water mark stop READING from
            # the peer too — a client that won't drain its responses
            # must not keep feeding the server new commands
            read_ev = 0 if len(conn.wbuf) > WBUF_HWM \
                else selectors.EVENT_READ
            self._set_events(conn, read_ev | selectors.EVENT_WRITE)
        else:
            self._set_events(conn, selectors.EVENT_READ)
            if conn.state == "closing":
                self._close_conn(conn)
            elif conn.state == "ready" and conn.rbuf \
                    and not conn.pumping:
                # commands parked behind the high-water mark resume
                # once the peer drained the buffer
                self._pump(conn)

    def _recv_size(self, conn: _AioConn) -> int:
        """Bytes to ask the socket for.  While a server that offers TLS
        is in the handshake, never past the end of the frame in hand: a
        client follows its SSLRequest at once with the ClientHello, which
        belongs to the TLS wrap on the hand-off thread and cannot be put
        back into the socket once the loop has read it."""
        if conn.state != "handshake" or self.fe.server.ssl_ctx is None:
            return 1 << 16
        have = len(conn.rbuf)
        if have < 4:
            return 4 - have
        length = conn.rbuf[0] | (conn.rbuf[1] << 8) | (conn.rbuf[2] << 16)
        return max(4 + length - have, 1)

    def _on_readable(self, conn: _AioConn) -> None:
        try:
            data = conn.sock.recv(self._recv_size(conn))
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self._close_conn(conn)
            return
        if not data:
            self._close_conn(conn)  # peer closed; rollback + deregister
            return
        conn.rbuf += data
        conn.last_rx = time.monotonic()
        self._pump(conn)
        self._flush(conn)

    def _next_packet(self, conn: _AioConn):
        """Extract one complete MySQL packet from the read buffer, or
        None while a frame is still partial — THE reassembly point for
        statements split across reads.  Oversized payloads follow the
        0xFFFFFF continuation rule (server/packetio.py)."""
        while True:
            if len(conn.rbuf) < 4:
                return None
            length = conn.rbuf[0] | (conn.rbuf[1] << 8) \
                | (conn.rbuf[2] << 16)
            if len(conn.rbuf) < 4 + length:
                return None
            seq = conn.rbuf[3]
            payload = bytes(conn.rbuf[4:4 + length])
            del conn.rbuf[:4 + length]
            if conn.parts or length == MAX_PAYLOAD:
                conn.parts.append(payload)
                if length == MAX_PAYLOAD:
                    continue
                payload = b"".join(conn.parts)
                conn.parts = []
            return payload, seq

    # ---- protocol state machine -----------------------------------------
    def _pump(self, conn: _AioConn) -> None:
        """Process buffered packets until the connection blocks on I/O
        or enters an async statement.  Reentrancy-guarded: a command
        completing synchronously inside the loop below must not start a
        nested pump over the same buffer."""
        if conn.pumping:
            return
        conn.pumping = True
        try:
            while conn.state in ("handshake", "ready") \
                    and len(conn.wbuf) <= WBUF_HWM:
                pkt = self._next_packet(conn)
                if pkt is None:
                    return
                payload, seq = pkt
                if conn.state == "handshake":
                    self._handshake(conn, payload, seq)
                else:
                    self._command(conn, payload, seq)
        finally:
            conn.pumping = False

    def _handshake(self, conn: _AioConn, payload: bytes,
                   seq: int) -> None:
        cc = conn.cc
        cc.io.sequence = (seq + 1) & 0xFF
        if (self.fe.server.ssl_ctx is not None and 4 <= len(payload) <= 32
                and struct.unpack_from("<I", payload, 0)[0]
                & p.CLIENT_SSL):
            self._tls_handoff(conn, payload)
            return
        if cc.finish_handshake(conn.salt, payload):
            conn.state = "ready"
        else:
            conn.state = "closing"  # err packet flushes, then close

    def _tls_handoff(self, conn: _AioConn, payload: bytes) -> None:
        """SSLRequest: hand the connection to a legacy thread for the
        blocking TLS wrap + command loop.  The loop never parks TLS
        sockets — the documented aio-mode tradeoff (TLS connections
        cost a thread in either wire mode)."""
        cc = conn.cc
        try:
            self.sel.unregister(conn.sock)
        except (KeyError, ValueError, OSError):
            pass
        self.conns.pop(cc.conn_id, None)
        conn.state = "closed"  # off the loop; the thread owns it now
        try:
            conn.sock.setblocking(True)
            if conn.wbuf:
                conn.sock.sendall(bytes(conn.wbuf))
                conn.wbuf.clear()
        except OSError:
            self._discard(conn)
            return
        io = PacketIO(conn.sock)
        io.sequence = cc.io.sequence
        cc.io = io
        threading.Thread(target=cc.run,
                         kwargs={"pre": (conn.salt, payload)},
                         daemon=True, name=f"conn-{cc.conn_id}").start()

    def _command(self, conn: _AioConn, payload: bytes, seq: int) -> None:
        if not payload:
            return
        cc = conn.cc
        cc.io.sequence = (seq + 1) & 0xFF
        cmd, body = payload[0], payload[1:]
        if cmd == p.COM_QUIT:
            self._close_conn(conn)
            return
        if cmd == p.COM_QUERY:
            conn.span = obs_context.PROCESS.begin(
                "wire.command", cat="wire", annotate=False,
                args={"cmd": cmd, "conn": cc.conn_id})
            record_idle(conn.flushed, conn.span, cc.conn_id)
            with obs_context.under(conn.span):
                self._start_query(conn, body.decode("utf-8", "replace"))
            return
        command = obs_context.process_span("wire.command", cat="wire",
                                           cmd=cmd, conn=cc.conn_id)
        record_idle(conn.flushed, command, cc.conn_id)
        with command:
            try:
                cc.dispatch_command(cmd, body)
            except Exception as e:  # one bad command != dead conn
                log.warning("aio conn-%d command error: %s",
                            cc.conn_id, e)
                cc.io.write_packet(_err_packet_for(e))
            self._after_command(conn)
        conn.flushed = command.end_s

    def _after_command(self, conn: _AioConn) -> None:
        if conn.state != "closed" and conn.cc.session.killed:
            # plain KILL: drop after the current command's response
            conn.state = "closing"
            self._flush(conn)

    # ---- the async COM_QUERY driver -------------------------------------
    def _start_query(self, conn: _AioConn, sql: str) -> None:
        cc = conn.cc
        try:
            stmts = parse_sql(sql)
        except Exception as e:
            cc.io.write_packet(p.err_packet(1064, str(e), "42000"))
            self._end_command_span(conn)
            self._after_command(conn)
            return
        conn.sql = sql
        conn.stmts = stmts
        conn.idx = 0
        conn.state = "running"
        self._advance(conn)

    def _advance(self, conn: _AioConn) -> None:
        """Drive the multi-statement COM_QUERY forward: pooled
        statements submit async and park the driver until their done
        callback; control statements execute inline (the pool bypass,
        same as a connection thread)."""
        cc = conn.cc
        pool = self.fe.server.pool
        while conn.idx < len(conn.stmts):
            stmt = conn.stmts[conn.idx]
            more = conn.idx + 1 < len(conn.stmts)
            label = conn.sql if len(conn.stmts) == 1 else \
                f"{conn.sql[:200]} [stmt {conn.idx + 1}/{len(conn.stmts)}]"
            if pool.routes_to_pool(stmt):
                try:
                    conn.submitted = time.perf_counter()
                    conn.entry = pool.submit(cc.session, stmt, label,
                                             on_done=self._done_cb(conn))
                except Exception as e:  # 1041 shed / pool shutdown
                    log.debug("query error: %s", e)
                    cc.io.write_packet(_err_packet_for(e))
                    self._finish_command(conn)
                    return
                return  # parked: _on_stmt_done resumes this driver
            try:
                rs = pool.run(cc.session, stmt, label)
            except Exception as e:
                log.debug("query error: %s", e)
                cc.io.write_packet(_err_packet_for(e))
                self._finish_command(conn)
                return
            cc.write_result(rs, more)
            conn.idx += 1
        self._finish_command(conn)

    def _done_cb(self, conn: _AioConn):
        return lambda entry: self.post(("done", (conn, entry)))

    def _on_stmt_done(self, conn: _AioConn, entry) -> None:
        if conn.state == "closed" and conn.entry is entry:
            # the deferred teardown leg (_close_conn with an in-flight
            # entry): the worker is done with the session — now it is
            # safe to roll back
            conn.entry = None
            self._finalize_session(conn)
            return
        if conn.state != "running" or conn.entry is not entry:
            return  # connection closed mid-statement: drop the result
        conn.entry = None
        cc = conn.cc
        # submit -> done was no thread's wait here: measured, not live
        now = time.perf_counter()
        record_wake(entry, obs_context.PROCESS.add_complete(
            "pool.wait", conn.submitted, now - conn.submitted,
            cat="serving", up=conn.span,
            args={"verdict": entry.verdict}), now)
        with obs_context.under(conn.span):
            if entry.error is not None:
                log.debug("query error: %s", entry.error)
                cc.io.write_packet(_err_packet_for(entry.error))
                self._finish_command(conn)  # error aborts remaining stmts
            else:
                cc.write_result(entry.result,
                                conn.idx + 1 < len(conn.stmts))
                conn.idx += 1
                self._advance(conn)
            self._flush(conn)

    def _end_command_span(self, conn: _AioConn) -> None:
        sp, conn.span = conn.span, None
        if sp is not None:
            obs_context.PROCESS.end(sp)
            conn.flushed = sp.end_s

    def _finish_command(self, conn: _AioConn) -> None:
        conn.stmts = []
        conn.idx = 0
        conn.entry = None
        if conn.state == "running":
            conn.state = "ready"
        self._after_command(conn)
        self._flush(conn)
        self._end_command_span(conn)
        if conn.state == "ready" and conn.rbuf:
            with obs_context.under(None):  # the next command is no child
                self._pump(conn)  # commands pipelined during execution

    def _on_kill(self, conn_id: int) -> None:
        """Self-pipe kill wake: close a killed idle connection NOW
        (there is no reader thread to notice), cancel a killed queued
        statement without a worker."""
        conn = self.conns.get(conn_id)
        if conn is None or conn.state == "closed":
            return
        sess = conn.cc.session
        if conn.state in ("handshake", "ready") and sess.killed:
            self._close_conn(conn)
        elif conn.state == "running" and conn.entry is not None \
                and (sess.guard.killed or sess.killed):
            self.fe.server.pool.cancel_if_queued(conn.entry,
                                                 QueryKilled())


class AioFrontEnd:
    """The bounded set of event-loop threads multiplexing every
    aio-mode connection (``tidb_aio_loops``; new connections round-robin
    across loops).  Owned by ``server.Server``; started lazily on the
    first aio-mode accept."""

    def __init__(self, server):
        self.server = server
        self._mu = threading.Lock()
        self._loops: List[_Loop] = []
        self._started = False
        self._closed = False
        self._rr = 0

    def start(self) -> None:
        from .pool import read_global_int
        with self._mu:
            if self._started or self._closed:
                return
            n = max(1, read_global_int(self.server.storage,
                                       "tidb_aio_loops", 1))
            self._loops = [_Loop(self, i) for i in range(n)]
            self._started = True
            loops = list(self._loops)
        for lp in loops:
            lp.thread.start()
        interrupt.add_kill_observer(self._kill_observer)
        log.info("aio front end up: %d event loop(s)", len(loops))

    def adopt(self, cc: ClientConn) -> None:
        """Hand one accepted (already conn-registered) connection to an
        event loop.  Called from the accept thread."""
        with self._mu:
            if self._closed or not self._loops:
                lp = None
            else:
                lp = self._loops[self._rr % len(self._loops)]
                self._rr += 1
        if lp is None:
            try:
                cc.sock.close()
            except OSError:
                pass
            self.server.remove_conn(cc.conn_id)
            return
        lp.post(("new", cc))

    def _kill_observer(self, conn_id: int, query_only: bool) -> None:
        """Runs on the KILLER's thread: wake every loop — the one that
        owns the victim acts, the rest no-op on an unknown id."""
        with self._mu:
            loops = list(self._loops)
        for lp in loops:
            lp.post(("kill", conn_id))

    def snapshot(self) -> dict:
        with self._mu:
            loops = list(self._loops)
        return {"loops": len(loops),
                "conns": sum(len(lp.conns) for lp in loops),
                "closed": self._closed}

    def close(self) -> None:
        with self._mu:
            if self._closed:
                return
            self._closed = True
            loops = list(self._loops)
        interrupt.remove_kill_observer(self._kill_observer)
        for lp in loops:
            lp.close()
        for lp in loops:
            lp.thread.join(2)

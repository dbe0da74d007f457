"""The burst generator: parked wire connections that send together, all
held by one process on one ``selectors`` loop.  Run as ``python
burstgen.py``; it reads one JSON plan from standard input and writes one
JSON result to standard output.

Plan: ``{"port", "db", "statements": [sql, ...], "connections":
[[statement index, ...], ...], "bursts": n | null, "seconds": s | null}``.
Every connection is opened and taken past the handshake first, at most
``WAVE`` at once (the server listens with a backlog of 128, and a SYN
past it is dropped).  Then the bursts: in burst ``k`` every connection
sends the statement ``connections[i][k % len]``, all released together,
and the next burst is released once the last answer of the one before
has arrived.  With ``bursts``, that many; with ``seconds``, no burst is
released once that many seconds have passed since the first, and the one
in flight is awaited, so a window holds whole bursts.  A connection whose
statement failed is not driven on.  It prints ``ready`` on standard error
once every connection is open, then waits for a line on standard input
before it starts, so that the parent decides when the window opens.

Result: ``loadgen.py``'s, ``{"start", "end", "records": [[connection,
statement index, t_send, t_recv, rows or null, error or null], ...]}``
with times from ``time.time()``, so that the readers of the closed loop
read a burst unchanged.  This file imports the standard library and
``wire`` only: it shares no interpreter lock with the server, and never
touches the chip.
"""
from __future__ import annotations

import json
import os
import resource
import selectors
import struct
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import wire  # noqa: E402

#: connections opened at once
WAVE = 128


def raise_open_files() -> None:
    """The soft open-file limit up to the hard one: a thousand sockets
    do not fit the usual soft limit of 1024 beside a process's other
    files."""
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    want = hard if hard != resource.RLIM_INFINITY else 1 << 20
    if soft != resource.RLIM_INFINITY and soft < want:
        resource.setrlimit(resource.RLIMIT_NOFILE, (want, hard))


def _frame(sql: str) -> bytes:
    """A COM_QUERY as one packet, sequence 0."""
    payload = b"\x03" + sql.encode()
    assert len(payload) < wire.MAX_PAYLOAD
    return struct.pack("<I", len(payload))[:3] + b"\x00" + payload


def _response(buf: bytearray):
    """One whole response at the head of ``buf``: ``(rows or affected
    count, error or None, bytes it took)``, or None while it is partial.
    Rows are lists of strings (None for NULL), as ``wire.Client.query``
    gives them."""
    pos = 0

    def packet():
        nonlocal pos
        if len(buf) - pos < 4:
            return None
        length = buf[pos] | (buf[pos + 1] << 8) | (buf[pos + 2] << 16)
        if length >= wire.MAX_PAYLOAD:
            raise ConnectionError("a packet of 16 MiB or more")
        if len(buf) - pos - 4 < length:
            return None
        out = bytes(buf[pos + 4:pos + 4 + length])
        pos += 4 + length
        return out

    first = packet()
    if first is None:
        return None
    if first[0] == 0x00:
        return wire._read_lenenc_int(first, 1)[0], None, pos
    if first[0] == 0xFF:
        code = struct.unpack_from("<H", first, 1)[0]
        return None, (f"ServerError: server error {code}: "
                      f"{first[9:].decode(errors='replace')}"), pos
    ncols, _ = wire._read_lenenc_int(first, 0)
    for _ in range(ncols + 1):  # column definitions, then EOF
        if packet() is None:
            return None
    rows = []
    while True:
        d = packet()
        if d is None:
            return None
        if d[0] == 0xFE and len(d) < 9:
            return rows, None, pos
        if d[0] == 0xFF:
            return None, f"ServerError: {d[9:].decode(errors='replace')}", \
                pos
        row, at = [], 0
        for _ in range(ncols):
            if d[at] == 0xFB:
                row.append(None)
                at += 1
            else:
                ln, at = wire._read_lenenc_int(d, at)
                row.append(d[at:at + ln].decode())
                at += ln
        rows.append(row)


class _Conn:
    __slots__ = ("i", "client", "order", "rbuf", "wbuf", "idx", "t_send")

    def __init__(self, i: int, client: "wire.Client", order: list):
        self.i, self.client, self.order = i, client, order
        self.rbuf = bytearray()
        self.wbuf = b""
        self.idx = self.t_send = None


def _burst(sel, live: list, frames: list, k: int, out: list) -> list:
    """Burst ``k``: every live connection sends its next statement, then
    every answer is read.  Returns the connections still driven on."""
    pending = {}
    for c in live:
        c.idx = c.order[k % len(c.order)]
        c.rbuf.clear()
        c.t_send = time.time()
        data = frames[c.idx]
        try:
            n = c.client.sock.send(data)
        except BlockingIOError:
            n = 0
        except OSError as e:
            out.append([c.i, c.idx, c.t_send, time.time(), None,
                        f"{type(e).__name__}: {e}"])
            continue
        c.wbuf = data[n:]
        if c.wbuf:
            sel.modify(c.client.sock,
                       selectors.EVENT_READ | selectors.EVENT_WRITE, c)
        pending[c.i] = c
    kept = []
    last = time.monotonic()
    while pending:
        events = sel.select(timeout=1.0)
        if not events and time.monotonic() - last > wire.STATEMENT_TIMEOUT_S:
            for c in pending.values():
                out.append([c.i, c.idx, c.t_send, time.time(), None,
                            "TimeoutError: no answer"])
            break
        for key, mask in events:
            c = key.data
            if c.i not in pending:
                continue
            error = None
            try:
                if mask & selectors.EVENT_WRITE and c.wbuf:
                    c.wbuf = c.wbuf[c.client.sock.send(c.wbuf):]
                    if not c.wbuf:
                        sel.modify(c.client.sock, selectors.EVENT_READ, c)
                if not mask & selectors.EVENT_READ:
                    continue
                part = c.client.sock.recv(1 << 16)
                if not part:
                    raise ConnectionError("connection closed")
                c.rbuf += part
                got = _response(c.rbuf)
            except BlockingIOError:
                continue
            except OSError as e:
                got, error = (None, f"{type(e).__name__}: {e}", 0), True
            if got is None:
                continue
            last = time.monotonic()
            rows, err, _ = got
            out.append([c.i, c.idx, c.t_send, time.time(),
                        None if err else rows, err])
            del pending[c.i]
            if err is None and error is None:
                kept.append(c)
    return sorted(kept, key=lambda c: c.i)


def run(plan: dict, wait_for_go) -> dict:
    raise_open_files()
    orders = plan["connections"]
    with ThreadPoolExecutor(min(WAVE, len(orders))) as pool:
        clients = list(pool.map(lambda _: wire.Client(plan["port"],
                                                      plan["db"]),
                                range(len(orders))))
    sel = selectors.DefaultSelector()
    live = []
    for i, (client, order) in enumerate(zip(clients, orders)):
        client.sock.setblocking(False)
        c = _Conn(i, client, order)
        sel.register(client.sock, selectors.EVENT_READ, c)
        live.append(c)
    frames = [_frame(sql) for sql in plan["statements"]]
    bursts, seconds = plan.get("bursts"), plan.get("seconds")
    records = []
    wait_for_go()
    start = time.time()
    k = 0
    while live:
        if bursts is not None and k >= bursts:
            break
        if seconds is not None and time.time() - start >= seconds:
            break
        live = _burst(sel, live, frames, k, records)
        k += 1
    end = time.time()
    sel.close()
    for client in clients:
        client.close()
    records.sort(key=lambda r: r[3])
    return {"start": start, "end": end, "records": records}


def main() -> int:
    plan = json.loads(sys.stdin.readline())

    def wait_for_go():
        print("ready", file=sys.stderr, flush=True)
        sys.stdin.readline()

    json.dump(run(plan, wait_for_go), sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

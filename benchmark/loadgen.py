"""The load generator: closed-loop wire connections in a process of their
own.  Run as ``python loadgen.py``; it reads one JSON plan from standard
input and writes one JSON result to standard output.

Plan: ``{"port", "db", "statements": [sql, ...], "connections":
[[statement index, ...], ...], "seconds": s | null,
"statements_each": n | null, "close": "statement" | "equal_rounds"}``.
Connection ``i`` sends the statements of ``connections[i]`` in turn, each
after the answer to the one before, cycling through its list, until
``seconds`` have passed since the start (no new statement is sent then;
the one in flight is awaited) or, with ``statements_each``, until it has
sent that many.  With ``"close": "equal_rounds"`` a window of unequal
statements holds whole passes only, as many for every connection: the
first connection to end a pass through its list once the seconds have
passed fixes the number of passes at the most that any connection has
begun, and each connection stops when it has made that many.  It prints
``ready`` on standard error once every connection is open, then waits for
a line on standard input before it starts, so that the parent decides when
the window opens.

Result: ``{"start", "end", "records": [[connection, statement index,
t_send, t_recv, rows or null, error or null], ...]}`` with times from
``time.time()``.  This file imports the standard library and ``wire``
only: it shares no interpreter lock with the server, and never touches
the chip.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import wire  # noqa: E402


class EqualRounds:
    """The close ``equal_rounds``: who may begin another pass."""

    def __init__(self, connections: int):
        self.lock = threading.Lock()
        self.begun = [0] * connections
        self.target = None

    def begin(self, conn_id: int, made: int, time_up: bool) -> bool:
        """Connection ``conn_id`` has made ``made`` whole passes: may it
        begin the next?"""
        with self.lock:
            if self.target is None and time_up:
                self.target = max(made, *self.begun)
            if self.target is not None and made >= self.target:
                return False
            self.begun[conn_id] = made + 1
            return True


def _loop(conn_id: int, client: wire.Client, plan: dict, start: list,
          go: threading.Event, out: list, rounds=None) -> None:
    order = plan["connections"][conn_id]
    statements = plan["statements"]
    seconds, each = plan.get("seconds"), plan.get("statements_each")
    go.wait()
    sent = 0
    while True:
        if each is not None and sent >= each:
            return
        if seconds is not None:
            time_up = time.time() - start[0] >= seconds
            if rounds is None:
                if time_up:
                    return
            elif sent % len(order) == 0 and not rounds.begin(
                    conn_id, sent // len(order), time_up):
                return
        idx = order[sent % len(order)]
        sent += 1
        t_send = time.time()
        try:
            rows, error = client.query(statements[idx]), None
        except (wire.ServerError, OSError) as e:
            rows, error = None, f"{type(e).__name__}: {e}"
        out.append([conn_id, idx, t_send, time.time(), rows, error])
        if error is not None:
            return  # a broken connection is not driven on


def run(plan: dict, wait_for_go) -> dict:
    clients = [wire.Client(plan["port"], plan["db"])
               for _ in plan["connections"]]
    go = threading.Event()
    start = [0.0]
    outs = [[] for _ in clients]
    rounds = EqualRounds(len(clients)) \
        if plan.get("close") == "equal_rounds" else None
    threads = [threading.Thread(target=_loop, daemon=True,
                                args=(i, c, plan, start, go, outs[i],
                                      rounds))
               for i, c in enumerate(clients)]
    for t in threads:
        t.start()
    wait_for_go()
    start[0] = time.time()
    go.set()
    for t in threads:
        t.join()
    end = time.time()
    for c in clients:
        c.close()
    records = sorted((r for out in outs for r in out), key=lambda r: r[3])
    return {"start": start[0], "end": end, "records": records}


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

"""Driver ``burst``: a thousand parked wire connections that send
together.  Every connection is opened before the window; in a burst each
sends its next statement at once, and the next burst is released when
the last answer of the one before has arrived.  The connections live in
one child process on one event loop (``burstgen.py``), which imports
neither jax nor the program: a thread a connection would measure the
client.  Its records are the closed loop's, so the same readers and the
same comparison read them.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import burstgen  # benchmark/, on the path run.py sets

BURSTGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "burstgen.py")

#: seconds a stage waits for the server to let the last stage's
#: connections go
SETTLE_S = 10.0


def _settle(run) -> None:
    """Before a stage opens its connections, the server has closed the
    last stage's: a stage's parked connections are its own (and a count
    of the most parked at once reads that stage alone)."""
    server = getattr(run.deployment, "server", None)
    deadline = time.monotonic() + SETTLE_S
    while server is not None and server.conns \
            and time.monotonic() < deadline:
        time.sleep(0.01)


def run_stage(port: int, db: str, sqls: list, orders: list, *,
              bursts=None, seconds=None, before_go=None,
              after_go=None) -> dict:
    """One run of the burst generator.  ``before_go`` is called once
    every connection is open, just before the first burst is released;
    ``after_go`` right after (the parent's work during the window).
    Returns the generator's result (see its docstring)."""
    # the server in this process holds a socket a connection too
    burstgen.raise_open_files()
    plan = {"port": port, "db": db, "statements": sqls,
            "connections": orders, "bursts": bursts, "seconds": seconds}
    child = subprocess.Popen([sys.executable, BURSTGEN],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True)
    try:
        child.stdin.write(json.dumps(plan) + "\n")
        child.stdin.flush()
        line = child.stderr.readline()
        if line.strip() != "ready":
            rest = child.stderr.read()
            raise RuntimeError(f"burst generator did not start: "
                               f"{line}{rest[-2000:]}")
        if before_go is not None:
            before_go()
        child.stdin.write("go\n")
        child.stdin.flush()
        if after_go is not None:
            after_go()
        out, err = child.communicate()
        if child.returncode != 0:
            raise RuntimeError(f"burst generator exited "
                               f"{child.returncode}: {err[-2000:]}")
        return json.loads(out)
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
        for pipe in (child.stdin, child.stdout, child.stderr):
            pipe.close()


def warm(run, stage: dict) -> dict:
    _settle(run)
    n = stage["connections"]
    return run_stage(run.port, run.db, run.sqls, run.orders[:n],
                     bursts=stage["bursts"])


def window(run, seconds: float, before_go=None, after_go=None) -> dict:
    _settle(run)
    return run_stage(run.port, run.db, run.sqls, run.orders,
                     seconds=seconds, before_go=before_go,
                     after_go=after_go)

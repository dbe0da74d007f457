"""Driver ``closed_loop``: a fixed number of wire connections, each
sending its next statement only after the answer to the one before.  The
connections live in a child process (``loadgen.py``), which imports
neither jax nor the program.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "loadgen.py")


def run_stage(port: int, db: str, sqls: list, orders: list, *,
              seconds=None, statements_each=None, close="statement",
              before_go=None, after_go=None) -> dict:
    """One run of the load generator.  ``before_go`` is called once every
    connection is open, just before the first statement is released;
    ``after_go`` right after (the parent's work during the window).
    Returns loadgen's result (see its docstring)."""
    plan = {"port": port, "db": db, "statements": sqls,
            "connections": orders, "seconds": seconds,
            "statements_each": statements_each, "close": close}
    child = subprocess.Popen([sys.executable, LOADGEN],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True)
    try:
        child.stdin.write(json.dumps(plan) + "\n")
        child.stdin.flush()
        line = child.stderr.readline()
        if line.strip() != "ready":
            rest = child.stderr.read()
            raise RuntimeError(f"load generator did not start: {line}{rest}")
        if before_go is not None:
            before_go()
        child.stdin.write("go\n")
        child.stdin.flush()
        if after_go is not None:
            after_go()
        out, err = child.communicate()
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited "
                               f"{child.returncode}: {err[-2000:]}")
        return json.loads(out)
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
        for pipe in (child.stdin, child.stdout, child.stderr):
            pipe.close()


def warm(run, stage: dict) -> dict:
    n = stage["connections"]
    return run_stage(run.port, run.db, run.sqls, run.orders[:n],
                     statements_each=stage["statements"])


def window(run, seconds: float, before_go=None, after_go=None) -> dict:
    return run_stage(run.port, run.db, run.sqls, run.orders,
                     seconds=seconds, before_go=before_go,
                     after_go=after_go,
                     close=run.cell.mix.get("close", "statement"))

#!/usr/bin/env python
"""Kill-9 consistency harness (the CI ``crash-recovery-smoke`` job).

Spawns a REAL server subprocess on a durable data dir, drives concurrent
wire traffic (a jepsen-style bank-transfer workload whose total balance
is conserved and whose per-account balance must equal the opening
balance plus the SUM of its ledger deltas — balances and ledger rows
are written in the SAME transaction, so any torn recovery breaks the
equation), SIGKILLs the process at armed crash points, restarts it on
the same data dir, and asserts after EVERY cycle:

1. every acked commit is present (both ledger rows of the transfer);
2. every transfer is atomic — both ledger rows or neither (unacked
   transactions either vanished or committed whole; a commit-ts'd
   primary whose secondary was interrupted must be completed by
   recovery + the lock-resolution ladder, never half-applied);
3. per-account: ``bal == OPENING + sum(ledger deltas)``;
4. total balance is conserved exactly.

Crash points cycled through (armed over a live control connection via
``SET @@tidb_failpoints`` so workers are INSIDE the window when the
SIGKILL lands; sleep actions hold them there):

- ``prewriteError=sleep``        — mid-prewrite;
- ``beforeCommit=sleep``         — the classic Percolator crashed-
                                   committer window (prewrite done,
                                   nothing committed);
- ``commitSecondaryError=sleep`` — between primary and secondary
                                   commit (acked-durability boundary);
- ``checkpointError=sleep``      — mid-checkpoint (tiny
                                   TINYSQL_WAL_CHECKPOINT_BYTES makes
                                   rotation continual);
- ``walTornTail=1*return(1)``    — the final record is half-written:
                                   recovery must truncate the torn
                                   tail;
- recovery-crash                 — the restart itself is started with
                                   ``checkpointError=sleep`` in the
                                   environment and SIGKILLed while
                                   recovery's post-replay checkpoint
                                   stalls: a second crash DURING
                                   recovery must itself be recoverable.

Exit 0 on success; writes a JSON report (--report) as the CI artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import re
import subprocess
import sys
import tempfile
import threading
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# a chip belongs to one process at a time, and this harness starts and
# SIGKILLs server children: parent and children are held to the CPU, so
# none of them ever takes (or is left holding) the accelerator
os.environ.setdefault("JAX_PLATFORMS", "cpu")

OPENING = 100  # per-account opening balance
READY_RE = re.compile(r"server ready on :(\d+)")
RECOVER_RE = re.compile(r"replayed (\d+) wal records, (\d+) in-flight "
                        r"locks recovered")

# crash-point choreography: (name, failpoint spec armed over the wire
# mid-traffic, grace seconds for a worker to enter the window).  The
# recovery-crash flavor is special-cased in run_cycle.
CRASH_POINTS = [
    ("mid-prewrite", "prewriteError=sleep(4)", 0.5),
    ("crashed-committer", "beforeCommit=sleep(4)", 0.5),
    ("secondary-commit", "commitSecondaryError=sleep(4)", 0.5),
    ("mid-checkpoint", "checkpointError=sleep(4)", 0.5),
    ("torn-tail", "walTornTail=1*return(1)", 0.4),
    ("recovery-crash", None, 0.0),
]


def log(msg: str) -> None:
    print(f"[crash-recovery] {msg}", flush=True)


class ServerProc:
    """One server subprocess on the shared data dir."""

    def __init__(self, data_dir: str, extra_env=None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # see the module-level note
        # tiny rotation threshold keeps checkpoints continual so the
        # mid-checkpoint window is routinely open
        env.setdefault("TINYSQL_WAL_CHECKPOINT_BYTES", "65536")
        env.update(extra_env or {})
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tinysql_tpu.main",
             "--data-dir", data_dir, "-P", "0", "--status", "0"],
            cwd=REPO_ROOT, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True)
        self.port = 0
        self.replayed = self.recovered_locks = 0
        self._drain = None

    def wait_ready(self, timeout_s: float = 60.0) -> bool:
        """Parse the readiness (and recovery-info) log lines; False if
        the process died or the deadline passed first."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            line = self.proc.stderr.readline()
            if not line:
                return False  # EOF: process died
            m = RECOVER_RE.search(line)
            if m:
                self.replayed = int(m.group(1))
                self.recovered_locks = int(m.group(2))
            m = READY_RE.search(line)
            if m:
                self.port = int(m.group(1))
                # keep draining stderr so the pipe never backpressures
                self._drain = threading.Thread(
                    target=self._drain_stderr, daemon=True)
                self._drain.start()
                return True
        return False

    def _drain_stderr(self) -> None:
        try:
            for _ in self.proc.stderr:
                pass
        except Exception:
            pass

    def kill9(self) -> None:
        self.proc.kill()  # SIGKILL — no atexit, no flush, no handler
        self.proc.wait()
        try:
            self.proc.stderr.close()
        except Exception:
            pass


class Book:
    """Thread-safe transfer ledger bookkeeping: acked op ids (commit OK
    received on the wire) vs everything else (unknown outcome)."""

    def __init__(self):
        self.mu = threading.Lock()
        self.next_id = 0
        self.acked = set()

    def take_id(self) -> int:
        with self.mu:
            op = self.next_id
            self.next_id += 1
            return op

    def ack(self, op: int) -> None:
        with self.mu:
            self.acked.add(op)


def transfer_worker(port: int, accounts: int, stop: threading.Event,
                    book: Book, wid: int) -> None:
    from tests.test_server import MiniClient
    rng = random.Random(1000 + wid)
    c = None
    while not stop.is_set():
        try:
            if c is None:
                c = MiniClient(port, db="bank")
            src, dst = rng.sample(range(accounts), 2)
            amt = rng.randint(1, 10)
            c.query("begin")
            s = int(c.query(
                f"select bal from accounts where id = {src}")[1][0][0])
            if s < amt:
                c.query("rollback")
                continue
            d = int(c.query(
                f"select bal from accounts where id = {dst}")[1][0][0])
            op = book.take_id()
            c.query(f"update accounts set bal = {s - amt} "
                    f"where id = {src}")
            c.query(f"update accounts set bal = {d + amt} "
                    f"where id = {dst}")
            c.query(f"insert into ledger values ({2 * op}, {src}, "
                    f"{-amt})")
            c.query(f"insert into ledger values ({2 * op + 1}, {dst}, "
                    f"{amt})")
            c.query("commit")
            book.ack(op)  # OK packet received: this commit is ACKED
        except RuntimeError:
            # server error packet (write conflict abort etc.) — the
            # connection survives; outcome handled by atomicity check
            continue
        except Exception:
            # socket death (the SIGKILL) or timeout: reconnect or exit
            try:
                if c is not None:
                    c.sock.close()
            except Exception:
                pass
            c = None
            time.sleep(0.05)
    try:
        if c is not None:
            c.close()
    except Exception:
        pass


def setup_bank(port: int, accounts: int) -> None:
    from tests.test_server import MiniClient
    c = MiniClient(port)
    c.query("create database if not exists bank")
    c.query("use bank")
    c.query("create table if not exists accounts "
            "(id int primary key, bal int)")
    c.query("create table if not exists ledger "
            "(id int primary key, acct int, delta int)")
    if not c.query("select id from accounts")[1]:
        for i in range(accounts):
            c.query(f"insert into accounts values ({i}, {OPENING})")
    c.close()


def verify_flight(c) -> int:
    """Flight-recorder consistency on a restarted server: the
    ``flight_incarnations`` surface must be queryable, show exactly one
    RUNNING row (this process), and every prior incarnation must carry
    a settled clean/torn verdict and a lower id.  Prior incarnations
    that died before their first flight tick are legitimately absent
    (zero segments — the recorder's documented blind spot), so the row
    COUNT is not asserted; tools/postmortem.py --smoke covers the
    fast-interval path where rows must exist.  Returns the number of
    prior incarnations visible."""
    rows = c.query("select incarnation, status from "
                   "information_schema.flight_incarnations")[1]
    running = [int(r[0]) for r in rows if r[1] == "running"]
    assert len(running) == 1, f"running incarnations: {rows}"
    prior = [(int(r[0]), r[1]) for r in rows if r[1] != "running"]
    for inc, status in prior:
        assert status in ("clean", "torn"), (inc, status)
        assert inc < running[0], \
            f"prior incarnation {inc} >= running {running[0]}"
    return len(prior)


def verify(port: int, accounts: int, book: Book) -> dict:
    """Post-restart consistency audit; raises AssertionError on any
    durability violation."""
    from tests.test_server import MiniClient
    c = MiniClient(port, db="bank")
    bal = {int(r[0]): int(r[1])
           for r in c.query("select id, bal from accounts")[1]}
    ledger = {int(r[0]): (int(r[1]), int(r[2]))
              for r in c.query("select id, acct, delta from ledger")[1]}
    flight_prior = verify_flight(c)
    c.close()
    assert len(bal) == accounts, f"accounts lost: {len(bal)}"
    # 1. every acked commit fully present
    with book.mu:
        acked = set(book.acked)
    for op in acked:
        assert 2 * op in ledger and 2 * op + 1 in ledger, \
            f"ACKED transfer {op} lost after restart"
    # 2. atomicity: ledger rows travel in pairs, debit == credit
    ops_seen = {k // 2 for k in ledger}
    for op in ops_seen:
        assert 2 * op in ledger and 2 * op + 1 in ledger, \
            f"transfer {op} half-applied (torn ledger pair)"
        assert ledger[2 * op][1] + ledger[2 * op + 1][1] == 0, \
            f"transfer {op} debit/credit mismatch"
    # 3. per-account: balance == opening + sum of its ledger deltas
    #    (balances and ledger rows rode the SAME transaction)
    delta = dict.fromkeys(range(accounts), 0)
    for acct, d in ledger.values():
        delta[acct] += d
    for a in range(accounts):
        assert bal[a] == OPENING + delta[a], \
            (f"account {a}: bal {bal[a]} != {OPENING} + "
             f"{delta[a]} (torn recovery)")
    # 4. conservation
    total = sum(bal.values())
    assert total == accounts * OPENING, \
        f"total balance {total} != {accounts * OPENING}"
    return {"acked": len(acked), "transfers_applied": len(ops_seen),
            "total_balance": total, "flight_prior": flight_prior}


def run_cycle(idx: int, point, data_dir: str, accounts: int,
              workers: int, book: Book) -> dict:
    name, spec, grace = point
    from tests.test_server import MiniClient
    if name == "recovery-crash":
        # crash DURING recovery: the restart's post-replay checkpoint
        # stalls on the env-armed failpoint and the SIGKILL lands
        # before the server is even ready
        sp = ServerProc(data_dir,
                        {"TINYSQL_FAILPOINTS": "checkpointError=sleep(8)"})
        time.sleep(2.0)
        killed_during_recovery = sp.port == 0 and sp.proc.poll() is None
        sp.kill9()
        sp2 = ServerProc(data_dir)
        assert sp2.wait_ready(), "restart after recovery-crash failed"
        report = verify(sp2.port, accounts, book)
        report.update({"point": name, "cycle": idx,
                       "killed_during_recovery": killed_during_recovery,
                       "replayed": sp2.replayed,
                       "recovered_locks": sp2.recovered_locks})
        sp2.kill9()  # leave the dir crash-dirty for the next cycle
        return report

    sp = ServerProc(data_dir)
    assert sp.wait_ready(), f"server start failed (cycle {idx})"
    setup_bank(sp.port, accounts)
    stop = threading.Event()
    threads = [threading.Thread(target=transfer_worker,
                                args=(sp.port, accounts, stop, book, w),
                                daemon=True)
               for w in range(workers)]
    for t in threads:
        t.start()
    time.sleep(0.6)  # unencumbered traffic builds wal + checkpoints
    ctl = MiniClient(sp.port)
    ctl.query(f"set @@tidb_failpoints = '{spec}'")
    ctl.close()
    time.sleep(grace)  # a worker walks into the armed window
    sp.kill9()
    stop.set()
    for t in threads:
        t.join(timeout=5)

    sp2 = ServerProc(data_dir)
    assert sp2.wait_ready(), f"restart failed after {name}"
    report = verify(sp2.port, accounts, book)
    report.update({"point": name, "cycle": idx,
                   "replayed": sp2.replayed,
                   "recovered_locks": sp2.recovered_locks})
    sp2.kill9()  # next cycle recovers from THIS kill too
    return report


def main() -> int:
    ap = argparse.ArgumentParser("crash-recovery harness")
    ap.add_argument("--cycles", type=int, default=12,
                    help="kill/restart cycles (>=10 for the CI gate)")
    ap.add_argument("--accounts", type=int, default=8)
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--report", default="crash_recovery_report.json")
    ap.add_argument("--data-dir", default="",
                    help="reuse a dir (default: fresh tempdir)")
    args = ap.parse_args()

    data_dir = args.data_dir or tempfile.mkdtemp(prefix="tinysql-crash-")
    log(f"data dir {data_dir}; {args.cycles} cycles, "
        f"{args.workers} workers over {args.accounts} accounts")
    book = Book()
    cycles = []
    t0 = time.monotonic()
    for idx in range(args.cycles):
        point = CRASH_POINTS[idx % len(CRASH_POINTS)]
        r = run_cycle(idx, point, data_dir, args.accounts,
                      args.workers, book)
        cycles.append(r)
        log(f"cycle {idx} [{r['point']}]: acked={r['acked']} "
            f"applied={r['transfers_applied']} "
            f"replayed={r['replayed']} "
            f"locks_recovered={r['recovered_locks']} "
            f"balance={r['total_balance']} OK")
    report = {
        "cycles": cycles,
        "total_cycles": len(cycles),
        "acked_commits": len(book.acked),
        "acked_commit_losses": 0,  # any loss asserts out above
        "crash_points_exercised":
            sorted({c["point"] for c in cycles}),
        "wall_s": round(time.monotonic() - t0, 2),
    }
    with open(args.report, "w") as f:
        json.dump(report, f, indent=2)
    log(f"PASS: {len(cycles)} kill/restart cycles, "
        f"{len(book.acked)} acked commits, zero lost — report at "
        f"{args.report}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Flight-recorder post-mortem: "what was the server doing when it
died" (the CI ``postmortem-smoke`` job).

Point it at a data dir and it loads ``<data-dir>/flight/`` read-only
(obs/flight.py — no counter bump, nothing mutated) and renders the last
incarnation's final window:

- run boundaries and the clean-vs-torn shutdown verdict;
- top statements of the final summary window by wall, CPU, and heap;
- findings open at death (the inspection engine's last evaluation);
- WAL stall evidence (fsync count/mean, append/fsync errors, last LSN);
- per-role host-CPU busy shares from the final conprof windows;
- the active processlist and last trace spans when the run closed
  cleanly enough to flush a final segment.

Exit codes: 0 = rendered; 1 = no flight data; 2 = the last run shut
down TORN with at least one unresolved CRITICAL finding — the "this
crash needs a human" signal a supervisor can gate on.

``--smoke`` runs the whole kill-9 black-box loop end to end (the CI
leg): spawn a real server on a fresh data dir with a 1 s flight
interval, drive a digest storm plus an armed SLO so findings exist,
SIGKILL mid-storm, restart, and assert (a) SQL on the fresh process
answers ``statements_summary_history WHERE incarnation = <prev>`` with
the pre-kill digest family, (b) ``flight_incarnations`` marks the run
torn, and (c) this tool's render names the digest family and >= 1
finding.  ``--report`` writes the rendered text (the CI artifact).
"""
from __future__ import annotations

import argparse
import io
import os
import sys
import tempfile
import threading
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# a chip belongs to one process at a time, and this harness kills and
# restarts server children (tools/crash_recovery.ServerProc pins them to
# cpu): the parent stays on the CPU too, so nothing here takes the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def log(msg: str) -> None:
    print(f"[postmortem] {msg}", file=sys.stderr, flush=True)


def _col_index(columns):
    return {name: i for i, (name, _kind) in enumerate(columns)}


def _top(rows, key_idx, n=8):
    return sorted(rows, key=lambda r: float(r[key_idx] or 0),
                  reverse=True)[:n]


def render(data_dir: str, out=None) -> int:
    """Render the last incarnation's black box; returns the exit code
    documented in the module docstring."""
    from tinysql_tpu.obs.conprof import COLUMNS as CONPROF_COLUMNS
    from tinysql_tpu.obs.flight import FlightStore
    from tinysql_tpu.obs.inspect import COLUMNS as FINDING_COLUMNS
    from tinysql_tpu.obs.stmtsummary import COLUMNS as SUMMARY_COLUMNS
    out = out if out is not None else sys.stdout
    store = FlightStore(data_dir)
    store.open_read_only()
    if not store.prior:
        print(f"no flight segments under {store.dir} — either the dir "
              "was never armed or the run died before its first "
              "tidb_flight_interval tick", file=out)
        return 1
    last = max(store.prior)
    info = [s for s in store.incarnation_summary()
            if s["incarnation"] == last][0]
    doc = store.last_segment(last)
    counters = doc.get("tiers", {}).get("counters", {})

    print("=" * 72, file=out)
    print(f"flight post-mortem: incarnation {last} "
          f"({info['status'].upper()})", file=out)
    print("=" * 72, file=out)
    print(f"started   {time.strftime('%Y-%m-%dT%H:%M:%S', time.localtime(info['start_ts']))}"
          f"   last segment {time.strftime('%Y-%m-%dT%H:%M:%S', time.localtime(info['end_ts']))}",
          file=out)
    print(f"segments  {info['segments']}   last WAL LSN "
          f"{info['last_lsn']}   incarnations on disk "
          f"{len(store.prior)}", file=out)
    if info["status"] == "torn":
        print("shutdown  TORN — no final segment: the process was "
              "killed or crashed between writer ticks; the window "
              "below is the last COMPLETED tick", file=out)
    else:
        print("shutdown  clean — the final segment carries the trace "
              "ring and processlist at close", file=out)

    si = _col_index(SUMMARY_COLUMNS)
    srows = store.tier_rows(last, "summary")
    print(f"\n-- top statements (final window, {len(srows)} rows) --",
          file=out)
    for title, key in (("by wall", "sum_latency_ms"),
                       ("by cpu", "sum_cpu_ms"),
                       ("by heap", "sum_heap_alloc_kb")):
        unit = "kb" if key.endswith("_kb") else "ms"
        print(f"  {title}:", file=out)
        for r in _top(srows, si[key], n=5):
            if float(r[si[key]] or 0) <= 0:
                continue
            print(f"    {float(r[si[key]]):>10.1f}{unit}  "
                  f"x{r[si['exec_count']]:<5} "
                  f"{r[si['digest']][:16]}  "
                  f"{str(r[si['digest_text']])[:60]}", file=out)

    fi = _col_index(FINDING_COLUMNS)
    findings = store.tier_rows(last, "findings")
    criticals = [r for r in findings if r[fi["severity"]] == "critical"]
    print(f"\n-- findings open at death ({len(findings)}, "
          f"{len(criticals)} critical) --", file=out)
    for r in findings:
        print(f"  [{r[fi['severity']]:>8}] {r[fi['rule']]}/"
              f"{r[fi['item']]}: {str(r[fi['details']])[:100]}",
              file=out)

    wal = counters.get("wal", {})
    print("\n-- WAL evidence --", file=out)
    if wal:
        fsyncs = wal.get("fsyncs", 0)
        mean_ms = (wal.get("fsync_s", 0.0) / fsyncs * 1e3) if fsyncs \
            else 0.0
        print(f"  appends {wal.get('appends', 0):.0f}  fsyncs "
              f"{fsyncs:.0f} (mean {mean_ms:.2f}ms)  append_errors "
              f"{wal.get('append_errors', 0):.0f}  fsync_errors "
              f"{wal.get('fsync_errors', 0):.0f}  checkpoints "
              f"{wal.get('checkpoints', 0):.0f}", file=out)
    else:
        print("  none recorded (volatile store)", file=out)

    ci = _col_index(CONPROF_COLUMNS)
    busy = {}
    for r in store.tier_rows(last, "conprof"):
        busy[r[ci["role"]]] = busy.get(r[ci["role"]], 0) \
            + int(r[ci["samples"]] or 0)
    total = sum(busy.values())
    print(f"\n-- per-role busy shares ({total} samples) --", file=out)
    for role, n in sorted(busy.items(), key=lambda kv: -kv[1]):
        share = n / total if total else 0.0
        print(f"  {role:<14} {n:>7}  {share:6.1%}", file=out)

    if doc.get("final"):
        print(f"\n-- at close: {len(doc.get('processlist', []))} live "
              f"sessions, {len(doc.get('traces', []))} traces "
              "buffered --", file=out)

    if info["status"] == "torn" and criticals:
        print(f"\nverdict: TORN shutdown with {len(criticals)} "
              "unresolved critical finding(s)", file=out)
        return 2
    print("\nverdict: ok", file=out)
    return 0


# ---- the kill-9 smoke leg (CI postmortem-smoke) ----------------------------

STORM_SQL = "select bal from accounts where id = 1"


def _storm(port: int, stop: threading.Event) -> None:
    from tests.test_server import MiniClient
    c = None
    while not stop.is_set():
        try:
            if c is None:
                c = MiniClient(port, db="bank")
            c.query(STORM_SQL)
        except Exception:
            try:
                if c is not None:
                    c.sock.close()
            except Exception:
                pass
            c = None
            time.sleep(0.05)


def smoke(report_path: str) -> int:
    from tests.test_server import MiniClient
    from tinysql_tpu.obs.stmtsummary import normalize
    from tools.crash_recovery import ServerProc

    data_dir = tempfile.mkdtemp(prefix="tinysql-postmortem-")
    log(f"data dir {data_dir}")
    digest, _text = normalize(STORM_SQL)

    sp = ServerProc(data_dir)
    assert sp.wait_ready(), "server start failed"
    prev_incarnation = 1
    c = MiniClient(sp.port)
    # 1 s segments so the pre-kill window is captured quickly; 1 s
    # metrics sampling + a 1 ms SLO so the storm itself burns the error
    # budget and raises an slo-burn finding within a couple of ticks
    c.query("set global tidb_flight_interval = 1")
    c.query("set global tidb_metrics_interval = 1")
    c.query("set global tidb_slo_p99_ms = 1")
    c.query("create database if not exists bank")
    c.query("use bank")
    c.query("create table if not exists accounts "
            "(id int primary key, bal int)")
    c.query("insert into accounts values (1, 100)")
    c.close()

    stop = threading.Event()
    threads = [threading.Thread(target=_storm, args=(sp.port, stop),
                                daemon=True) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(6.0)  # >= 2 flight ticks AND >= 2 metric samples so
    # the slo-burn delta is computable before the kill
    sp.kill9()       # no atexit, no final segment: a TORN shutdown
    stop.set()
    for t in threads:
        t.join(timeout=5)
    log("killed mid-storm; restarting on the same dir")

    sp2 = ServerProc(data_dir)
    assert sp2.wait_ready(), "restart failed"
    c = MiniClient(sp2.port)
    # (a) the pre-kill storm's digest family answers over SQL from the
    # PREVIOUS incarnation
    rows = c.query(
        "select digest, exec_count from information_schema."
        "statements_summary_history "
        f"where incarnation = {prev_incarnation}")[1]
    digests = {r[0] for r in rows}
    assert digest in digests, \
        (f"pre-kill digest {digest} not in incarnation "
         f"{prev_incarnation} history ({len(rows)} rows)")
    # (b) flight_incarnations marks the killed run torn
    status = c.query(
        "select status from information_schema.flight_incarnations "
        f"where incarnation = {prev_incarnation}")[1]
    assert status and status[0][0] == "torn", status
    # the restarted server is the NEXT incarnation
    cur = int(c.query(
        "select incarnation from information_schema.flight_incarnations"
        " where status = 'running'")[1][0][0])
    assert cur == prev_incarnation + 1, (cur, prev_incarnation)
    c.close()
    sp2.kill9()
    log(f"SQL gates passed: digest {digest[:16]} readable from "
        f"incarnation {prev_incarnation}, run marked torn")

    # (c) the renderer names the digest family and >= 1 finding
    buf = io.StringIO()
    code = render(data_dir, out=buf)
    text = buf.getvalue()
    sys.stdout.write(text)
    if report_path:
        with open(report_path, "w", encoding="utf-8") as f:
            f.write(text)
        log(f"report at {report_path}")
    assert digest[:16] in text, "render does not name the storm digest"
    assert "findings open at death (0" not in text, \
        "render shows zero findings"
    assert "TORN" in text, "render does not mark the run torn"
    # torn + critical findings => 2; torn + warnings only => 0.  Either
    # is a successful smoke — the gate is that the verdict machinery
    # ran on real crash data.
    assert code in (0, 2), code
    log("PASS: kill-9 black box readable post-restart")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser("flight-recorder post-mortem")
    ap.add_argument("data_dir", nargs="?", default="",
                    help="data dir to diagnose (omit with --smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="run the kill-9 CI smoke loop end to end")
    ap.add_argument("--report", default="",
                    help="also write the rendered text here")
    args = ap.parse_args()
    if args.smoke:
        return smoke(args.report)
    if not args.data_dir:
        print("usage: postmortem.py <data-dir> [--report FILE] "
              "| --smoke", file=sys.stderr)
        return 1
    if args.report:
        buf = io.StringIO()
        code = render(args.data_dir, out=buf)
        sys.stdout.write(buf.getvalue())
        with open(args.report, "w", encoding="utf-8") as f:
            f.write(buf.getvalue())
        return code
    return render(args.data_dir)


if __name__ == "__main__":
    sys.exit(main())

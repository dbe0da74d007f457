#!/usr/bin/env python
"""Observability smoke (the CI ``obs-smoke`` job).

End-to-end assertion chain over a tiny TPC-H load:

1. run Q6 on the device tier — the per-query scope must report nonzero
   program dispatches and the transfer invariant (packed D2H pulls never
   exceed dispatches + 1) must hold;
2. ``EXPLAIN ANALYZE`` Q6 and Q1 — the ROOT operator's actRows must
   equal the executed result cardinality;
3. a ``StatusServer`` must serve ``/metrics`` exposing a nonzero
   ``tinysql_dispatches_total``, per-phase latency histogram buckets
   sourced from the statement summary store, and a ``/debug/trace``
   ring containing the statements above;
4. the SQL-queryable observability surface: aggregated
   ``information_schema.statements_summary`` rows with device counters,
   ``EXPLAIN FOR CONNECTION`` rendering the session's last plan, and —
   through a REAL MySQL-protocol connection — a wire-level
   ``SELECT ... FROM information_schema.statements_summary`` plus
   ``SHOW PROCESSLIST`` showing the connection itself.

Exit 0 on success; prints one line per check.
"""
from __future__ import annotations

import json
import os
import sys
from urllib.request import urlopen

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"[obs-smoke] {'ok' if ok else 'FAIL'}: {name}"
          f"{' — ' + detail if detail else ''}")
    if not ok:
        sys.exit(1)


def main() -> int:
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.server.http_status import StatusServer
    from tinysql_tpu.session.session import new_session

    sf = float(os.environ.get("TPCH_SF", "0.01"))
    s = new_session()
    tpch.load(s, sf=sf, data=tpch.generate(sf))
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_tpu_min_rows = 0")

    # 1. Q6 on the device tier: per-query counters
    q6 = tpch.QUERIES["Q6"]
    rows = s.query(q6).rows
    totals = s.last_query_stats.device_totals()
    check("Q6 executed", len(rows) == 1, f"{len(rows)} rows")
    check("per-query dispatches nonzero",
          totals.get("dispatches", 0) > 0, str(totals))
    check("transfer invariant d2h <= dispatches+1",
          totals.get("d2h_transfers", 0)
          <= totals.get("dispatches", 0) + 1, str(totals))

    # 2. EXPLAIN ANALYZE actRows == executed cardinality
    for name in ("Q6", "Q1"):
        sql = tpch.QUERIES[name]
        n = len(s.query(sql).rows)
        ra = s.query("explain analyze " + sql)
        idx = ra.columns.index("actRows")
        root_act = ra.rows[0][idx]
        check(f"EXPLAIN ANALYZE {name} actRows == result rows",
              str(root_act) == str(n), f"act={root_act} rows={n}")
        devcol = ra.columns.index("device info")
        check(f"EXPLAIN ANALYZE {name} shows device counters",
              any("dispatches:" in str(r[devcol]) for r in ra.rows))

    # 3. /metrics + /debug/trace round-trip
    st = StatusServer(None, port=0)
    st.start()
    try:
        with urlopen(f"http://127.0.0.1:{st.port}/metrics",
                     timeout=10) as r:
            text = r.read().decode()
        val = 0.0
        for line in text.splitlines():
            if line.startswith("tinysql_dispatches_total"):
                val = float(line.split()[-1])
        check("/metrics tinysql_dispatches_total nonzero", val > 0,
              f"value={val}")
        hist_lines = [l for l in text.splitlines()
                      if l.startswith("tinysql_stmt_phase_seconds_bucket")]
        check("/metrics per-phase latency histogram buckets",
              any('phase="exec"' in l for l in hist_lines),
              f"{len(hist_lines)} bucket lines")
        with urlopen(f"http://127.0.0.1:{st.port}/debug/trace?n=4",
                     timeout=10) as r:
            traces = json.loads(r.read().decode())
        check("/debug/trace returns spans",
              bool(traces) and all(t.get("spans") for t in traces),
              f"{len(traces)} entries")
    finally:
        st.close()

    # 4. SQL-queryable observability: statements_summary aggregates the
    # runs above per plan digest, with the device economics attached
    rs = s.query(
        "select digest_text, exec_count, sum_exec_ms, dispatches, "
        "d2h_bytes from information_schema.statements_summary")
    agg = [r for r in rs.rows if str(r[0]).startswith("select")
           and int(r[1]) >= 2 and int(r[3]) > 0]
    check("statements_summary aggregates device counters per digest",
          bool(agg), f"{len(rs.rows)} rows, {len(agg)} aggregated")
    ex = s.query(f"explain for connection {s.conn_id}")
    check("EXPLAIN FOR CONNECTION renders the last plan",
          len(ex.rows) > 0, f"{len(ex.rows)} plan rows")

    # 5. wire level: the same tables through the MySQL protocol server
    from tinysql_tpu.server.server import Server
    from tests.test_server import MiniClient
    srv = Server(s.storage, port=0)
    srv.start()
    try:
        c = MiniClient(srv.port)
        cols, rows = c.query("select digest, exec_count from "
                             "information_schema.statements_summary")
        check("wire SELECT from statements_summary",
              cols == ["digest", "exec_count"] and len(rows) > 0,
              f"{len(rows)} rows")
        cols, rows = c.query("show processlist")
        check("wire SHOW PROCESSLIST includes the live connection",
              any(r[4] == "Query" and "processlist" in (r[7] or "")
                  for r in rows), str(rows))
        c.close()
    finally:
        srv.close()
    print("[obs-smoke] all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())

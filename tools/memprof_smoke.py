#!/usr/bin/env python
"""Memory-truth smoke (the CI ``memprof-smoke`` job).

The ISSUE 18 memory-truth loop end to end against a REAL server
lifecycle:

1. start a Server — its background heap sampler (obs/memprof.py) must
   tick at the GLOBAL ``tidb_memprof_rate``, open site windows inside
   its budget (tracing off between them) and fold non-empty allocation
   sites while wire clients drive TPC-H load;
2. ``/debug/heap`` returns collapsed text the shared parser
   (conprof.parse_collapsed / flamegraph.pl) ingests, covering >= 3
   thread roles from the closed vocabulary;
3. ``information_schema.memory_usage`` serves the three-source
   reconciliation over SQL (tracked ledger vs measured heap vs HBM
   census), with the measured invariants intact (the last window's
   traced <= rss; recon/untracked == max(0, traced - tracked));
4. statement heap attribution reaches SQL: at least one of the Q1/Q3/Q6
   digest families shows ``sum_heap_alloc_kb > 0`` in
   ``statements_summary``, digest-joined, with each family's sum
   bounded by what the windows measured;
5. the device-buffer census attributes every live buffer after the full
   workload — the ``unattributed`` leak bucket reads 0 bytes;
6. an induced ``heap-growth`` finding: a deliberately leaked list of
   big resident allocations across bracketing ring samples must
   surface the rule in ``information_schema.inspection_result``.

Exit 0 on success; prints one line per check.
"""
from __future__ import annotations

import gc
import os
import sys
import threading
import time
import tracemalloc
from urllib.request import urlopen

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
sys.path.insert(0, os.path.join(REPO_ROOT, "tests"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"[memprof-smoke] {'ok' if ok else 'FAIL'}: {name}"
          f"{' — ' + detail if detail else ''}")
    if not ok:
        sys.exit(1)


def main() -> int:
    from test_server import MiniClient
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.kv import new_mock_storage
    from tinysql_tpu.obs import conprof, memprof, stmtsummary, tsring
    from tinysql_tpu.server.http_status import StatusServer
    from tinysql_tpu.server.server import Server
    from tinysql_tpu.session.session import Session

    storage = new_mock_storage()
    boot = Session(storage)
    boot.execute("set global tidb_slow_log_threshold = 60000")
    boot.execute("set global tidb_tpu_min_rows = 64")
    boot.execute("set global tidb_metrics_interval = 1")
    boot.execute("set global tidb_memprof_rate = 50")
    boot.execute("set global tidb_auto_prewarm = 0")
    counts = tpch.load(boot, sf=0.02)
    stmtsummary.STORE.reset()
    tsring.RING.reset()
    memprof.reset()

    queries = (tpch.Q1, tpch.Q3, tpch.Q6)

    srv = Server(storage, port=0)
    srv.start()
    t_served = time.monotonic()
    status = StatusServer(srv)
    sport = status.start()
    try:
        # warm the programs outside the measured load
        warm = MiniClient(srv.port, db="tpch")
        for sql in queries:
            warm.query(sql)
        tsring.RING.sample_once()  # ring baseline for the rule deltas

        # 1. drive Q1/Q3/Q6 load while the heap sampler ticks
        errors = []

        def client(cid: int) -> None:
            try:
                c = MiniClient(srv.port, db="tpch")
                for i in range(24):
                    c.query(queries[(cid + i) % 3])
                c.close()
            except Exception as e:
                errors.append(f"c{cid}: {e!r}")

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(3)]
        for t in threads:
            t.start()
        # parked via Event.wait, NOT time.sleep (the conprof-smoke
        # discipline): the smoke's own main thread must read as idle
        pause = threading.Event()
        deadline = time.monotonic() + 120
        while any(t.is_alive() for t in threads) \
                and time.monotonic() < deadline:
            pause.wait(0.1)
        for t in threads:
            t.join(60)
        check("wire load completed with zero errors", not errors,
              "; ".join(errors[:3]))

        # give the sampler one more period so the final window folds
        tick0 = memprof.stats_snapshot()["ticks"]
        wait_dl = time.monotonic() + 10
        while memprof.stats_snapshot()["ticks"] <= tick0 \
                and time.monotonic() < wait_dl:
            pause.wait(0.05)

        snap = memprof.stats_snapshot()
        check("memprof sampler ticked under serve load",
              snap["ticks"] > 0 and snap["sites"] > 0,
              f"ticks={snap['ticks']} sites={snap['sites']} "
              f"backoff={snap['backoff']}")
        served_s = time.monotonic() - t_served
        # a window may be open at any one instant: look for a second
        polls = traced_polls = 0
        poll_dl = time.monotonic() + 1.0
        while time.monotonic() < poll_dl:
            polls += 1
            traced_polls += tracemalloc.is_tracing()
            pause.wait(0.005)
        check("site windows opened, inside the budget, tracing off "
              "between them",
              snap["site_windows"] > 0
              and snap["traced_s"] <= memprof.OVERHEAD_BUDGET_FRAC
              * served_s + 0.1
              and traced_polls <= 0.1 * polls,
              f"windows={snap['site_windows']} "
              f"traced_s={snap['traced_s']:.3f} of {served_s:.1f}s, "
              f"tracing in {traced_polls} of {polls} polls")
        check("sampler never wedged on errors", snap["errors"] == 0,
              f"errors={snap['errors']}")

        # 2. /debug/heap: collapsed text, shared-parser round trip,
        # >= 3 distinct thread roles from the closed vocabulary
        body = urlopen(f"http://127.0.0.1:{sport}/debug/heap",
                       timeout=10).read().decode()
        parsed = conprof.parse_collapsed(body)
        check("/debug/heap returns non-empty collapsed sites",
              bool(parsed), f"{len(parsed)} sites")
        roles = {s.split(";", 1)[0] for s in parsed}
        check("heap sites cover >= 3 roles", len(roles) >= 3,
              str(sorted(roles)))
        check("every heap role is in the closed vocabulary",
              roles <= set(conprof.ROLES), str(sorted(roles)))

        # 3. memory_usage over SQL: three sources, reconciled
        c = MiniClient(srv.port, db="tpch")
        _, rows = c.query("select source, item, bytes from "
                          "information_schema.memory_usage")
        srcs = {r[0] for r in rows}
        check("memory_usage serves all four sections over SQL",
              srcs >= {"tracked", "measured", "hbm", "recon"},
              str(sorted(srcs)))
        by_item = {(r[0], r[1]): int(r[2]) for r in rows}
        traced = by_item[("measured", "traced_heap")]
        rss = by_item[("measured", "rss")]
        tracked = by_item[("tracked", "statements")]
        untracked = by_item[("recon", "untracked")]
        check("a window's traced python heap <= resident set "
              "(blind-spot order)",
              0 < traced <= rss, f"traced={traced} rss={rss}")
        check("recon/untracked == max(0, traced - tracked)",
              untracked == max(0, traced - tracked),
              f"untracked={untracked} traced={traced} "
              f"tracked={tracked}")

        # 4. per-statement heap attribution over SQL, digest-joined:
        # the sampler splits each site window's measured growth across
        # the executing statements, so the summed columns stay bounded
        # by what the windows read — and at least one hot family
        # caught a window
        digests = {sql: stmtsummary.normalize(sql)[0]
                   for sql in queries}
        in_list = ", ".join(f"'{d}'" for d in digests.values())
        _, rows = c.query(
            "select digest, sum_heap_alloc_kb, max_heap_kb "
            "from information_schema.statements_summary "
            f"where digest in ({in_list})")
        check("all three digest families visible in statements_summary",
              len(rows) == 3, str(rows))
        total_alloc_kb = sum(float(r[1]) for r in rows)
        check("a Q1/Q3/Q6 family carries heap attribution",
              total_alloc_kb > 0, str(rows))
        # a window's share is at most its reading, and max_heap_kb is
        # the largest reading a family ran under
        windows = memprof.stats_snapshot()["site_windows"]
        check("each family's heap attribution <= windows x its "
              "largest window reading",
              all(float(r[1]) <= windows * float(r[2]) + 1
                  for r in rows), f"windows={windows} rows={rows}")

        # 5. the census attributes every live device buffer: after the
        # full workload the leak bucket must be empty (gc first — the
        # executors' transient arrays die with their frames)
        gc.collect()
        census = memprof.hbm_census()
        check("device-buffer census ran over live arrays",
              census["buffers"] >= 0, str(census["by_category"]))
        check("unattributed census bucket empty after workload",
              census["unattributed_bytes"] == 0,
              f"{census['unattributed_buffers']} buffers / "
              f"{census['unattributed_bytes']}B unattributed")

        # 6. induce heap-growth: a leaked list of big resident
        # allocations (written to: untouched pages are not resident)
        # across bracketing ring samples of their own — a monotone rise
        # of the resident set past the rule floor
        from tinysql_tpu.obs import inspect as oinspect
        step = oinspect.HEAP_GROWTH_MIN_BYTES // 3
        tsring.RING.reset()
        leak = []
        for _ in range(5):
            leak.append(bytearray(b"\x01") * step)
            tsring.RING.sample_once()
        _, rows = c.query(
            "select rule, item, severity from "
            "information_schema.inspection_result "
            "where rule = 'heap-growth'")
        check("heap-growth finding induced over SQL",
              len(rows) >= 1, str(rows))
        body = urlopen(
            f"http://127.0.0.1:{sport}/debug/inspection?window=0",
            timeout=10).read().decode()
        check("heap-growth served by /debug/inspection",
              "heap-growth" in body)
        del leak
        c.close()
        warm.close()
    finally:
        status.close()
        srv.close()
    print(f"[memprof-smoke] all checks passed "
          f"(rows loaded: {counts})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

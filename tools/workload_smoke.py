#!/usr/bin/env python
"""Workload-diversity smoke (the CI ``workload-smoke`` job).

TPC-H Q5/Q10/Q18 end-to-end through the SQL front door at SF=0.02:

1. every workload query's rows must equal sqlite3 over the SAME
   generated data (canonicalized float compare);
2. every query must do kernel work — >= 1 device (or host-twin)
   dispatch — i.e. the multi-join/semijoin plans actually reached the
   accelerated tier rather than silently falling back whole;
3. the second run of each query must compile NOTHING (the PR 6
   literal-parameterized program families cover the new semijoin /
   join-chain operators);
4. ``EXPLAIN`` Q5 must show the decorrelated ``semi join`` landing on
   the nation/region subtree (the semi-join sink rule), and ``EXPLAIN
   ANALYZE`` must carry device counters on the join chain;
5. UPDATE must round-trip through the same front door (the read path
   shares the decorrelated planner);
6. with the chip's branches forced (``TINYSQL_DEVICE_JOIN_ONLY=1``,
   ``tidb_devpipe = 1``) every query is ONE fused device program: one
   dispatch, no host twin, the same rows;
7. the same under a forced mesh (``tidb_mesh_parallel = 1`` over four
   host devices): still one dispatch each, launched over the whole
   mesh, the views traced under it and Q5's and Q10's keyed GROUP BYs
   reduced a shard at a time.

Exit 0 on success; prints one line per check.
"""
from __future__ import annotations

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four host devices for the forced mesh (jax reads the flag once)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"[workload-smoke] {'ok' if ok else 'FAIL'}: {name}"
          f"{' — ' + detail if detail else ''}")
    if not ok:
        sys.exit(1)


def rows_close(got, want, rel: float = 1e-8) -> bool:
    """Same rows in the same order, doubles within ``rel``: a shard's
    prefix sums run over its own rows, so Q10's sums round in the ninth
    digit otherwise than one device's (a 9-digit canon flips there)."""
    if len(got) != len(want):
        return False
    for a, b in zip(got, want):
        for x, y in zip(a, b):
            if isinstance(y, float):
                if abs(float(x) - y) > rel * max(abs(y), 1.0):
                    return False
            elif x != y:
                return False
    return True


def main() -> int:
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.ops import kernels
    from tinysql_tpu.session.session import new_session

    sf = float(os.environ.get("TPCH_SF", "0.02"))
    data = tpch.generate(sf)
    s = new_session()
    tpch.load(s, sf=sf, data=data)
    s.execute("use tpch")
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_tpu_min_rows = 1")

    lite = tpch.sqlite_mirror(data)
    _canon = tpch.canon_rows

    for q, sql in tpch.WORKLOAD.items():
        want = _canon(lite.execute(sql).fetchall())
        snap = kernels.stats_snapshot()
        got = _canon(s.query(sql).rows)
        d = kernels.stats_delta(snap)
        check(f"{q} matches sqlite", got == want,
              f"{len(got)} rows vs {len(want)}")
        disp = d.get("dispatches", 0) + d.get("host_dispatches", 0)
        check(f"{q} did kernel work", disp >= 1,
              f"dispatches={d.get('dispatches', 0)} "
              f"host={d.get('host_dispatches', 0)}")
        snap = kernels.stats_snapshot()
        s.query(sql)
        d2 = kernels.stats_delta(snap)
        check(f"{q} second run compiles nothing",
              d2.get("progcache_misses", 0) == 0,
              f"misses={d2.get('progcache_misses', 0)}")

    # the chip's branches forced on the CPU: each of the three is ONE
    # fused device program, no host twin, still sqlite's answer
    os.environ["TINYSQL_DEVICE_JOIN_ONLY"] = "1"
    s.execute("set @@tidb_devpipe = 1")
    try:
        for q, sql in tpch.WORKLOAD.items():
            want = _canon(lite.execute(sql).fetchall())
            snap = kernels.stats_snapshot()
            got = _canon(s.query(sql).rows)
            d = kernels.stats_delta(snap)
            check(f"{q} fused matches sqlite", got == want,
                  f"{len(got)} rows vs {len(want)}")
            check(f"{q} is one fused program",
                  d.get("dispatches", 0) == 1
                  and d.get("host_dispatches", 0) == 0
                  and d.get("pipe_joins", 0) >= 3,
                  f"dispatches={d.get('dispatches', 0)} "
                  f"host={d.get('host_dispatches', 0)} "
                  f"joins={d.get('pipe_joins', 0)}")
        # and under a forced mesh of the host's devices
        from tinysql_tpu.parallel import dist
        floor, dist.MIN_SHARD_ROWS = dist.MIN_SHARD_ROWS, 16
        s.execute("set @@tidb_mesh_parallel = 1")
        try:
            for q, sql in tpch.WORKLOAD.items():
                want = [list(r) for r in lite.execute(sql).fetchall()]
                snap = kernels.stats_snapshot()
                got = s.query(sql).rows
                d = kernels.stats_delta(snap)
                check(f"{q} over the mesh matches sqlite",
                      rows_close(got, want),
                      f"{len(got)} rows vs {len(want)}")
                check(f"{q} is one fused program over the mesh",
                      d.get("dispatches", 0) == 1
                      and d.get("mesh_dispatches", 0) == 1
                      and d.get("host_dispatches", 0) == 0
                      and d.get("pipe_mesh_views", 0) >= 1
                      and d.get("agg_key_mesh", 0) == (q != "Q18"),
                      f"dispatches={d.get('dispatches', 0)} "
                      f"mesh={d.get('mesh_dispatches', 0)} "
                      f"host={d.get('host_dispatches', 0)} "
                      f"mesh_views={d.get('pipe_mesh_views', 0)} "
                      f"key_mesh={d.get('agg_key_mesh', 0)}")
        finally:
            s.execute("set @@tidb_mesh_parallel = 0")
            dist.MIN_SHARD_ROWS = floor
    finally:
        s.execute("set @@tidb_devpipe = -1")
        del os.environ["TINYSQL_DEVICE_JOIN_ONLY"]

    plan = s.query("explain " + tpch.Q5).rows
    flat = "\n".join(str(r) for r in plan)
    check("Q5 plans a semi join", "semi join" in flat)
    semi_at = next(i for i, r in enumerate(plan)
                   if "semi join" in str(r[3]))
    below = "\n".join(str(r) for r in plan[semi_at + 1:])
    check("Q5 semijoin sinks to nation/region",
          "table:nation" in below and "table:region" in below
          and "table:lineitem" not in below)
    flat = "\n".join(
        str(r) for r in s.query("explain analyze " + tpch.Q5).rows)
    check("Q5 EXPLAIN ANALYZE shows device counters",
          "dispatches" in flat)

    s.execute("update nation set n_name = 'NIHON' "
              "where n_name = 'JAPAN'")
    check("UPDATE through the front door", s.last_affected == 1)
    check("UPDATE visible to reads",
          s.query("select count(*) from nation "
                  "where n_name = 'NIHON'").rows == [[1]])
    # the statement updated ONE row — the other 24 must still exist
    # (regression: writes on bulk-loaded tables used to drop them)
    check("UPDATE preserves untouched rows",
          s.query("select count(*) from nation").rows == [[25]])

    print("[workload-smoke] all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())

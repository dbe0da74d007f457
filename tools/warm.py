#!/usr/bin/env python
"""Bucket prewarmer — AOT-compile the device programs a plan will hit so
the FIRST real query runs warm (the BENCH_r05 problem: Q1 15.07s cold vs
0.74s warm was almost entirely first-touch XLA compilation).

Two warming layers per query:

1. **Plan-derived bucket AOT** — plan the statement (no execution),
   derive the power-of-two shape buckets from the planner's cardinality
   estimates (planner/buckets.bucket_estimates), and
   ``jax.jit(...).lower().compile()`` the shape-generic kernels for each
   bucket (kernels.prewarm_bucket).  This also covers GROWTH buckets the
   first execution would not touch yet.
2. **One warming execution** — runs the query once, tracing the fused
   structural programs (aggregate specs, expression lowerings, device
   masks) into the in-process registry (ops/progcache) AND the
   persistent XLA compilation cache on disk, so later PROCESSES skip the
   compiles too (JAX_COMPILATION_CACHE_DIR, else tidb_compile_cache_dir).

Usage:

    python tools/warm.py [--sf 0.05] [--queries Q1,Q3,Q6] [--cache-dir D]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def plan_buckets(session, sql: str) -> set:
    """Plan one statement (no execution) -> estimated shape buckets.
    ONE implementation shared with the serving-side auto-prewarm worker
    (session/prewarm.py) — this CLI is the manual/offline form of the
    same warming."""
    from tinysql_tpu.session.prewarm import plan_buckets as _pb
    return _pb(session, sql)


def warm_queries(session, queries: dict, verbose: bool = True,
                 stats_path: str = "") -> dict:
    """Warm every (name -> sql) entry against an already-loaded session:
    AOT-compile the plan-derived buckets (plus observed buckets from a
    RuntimeStats feedback file when ``stats_path`` names one), then
    execute each query once.  Returns a summary dict for the bench
    JSON."""
    from tinysql_tpu.ops import kernels, progcache
    t0 = time.time()
    snap = kernels.stats_snapshot()
    buckets = set()
    for name, sql in queries.items():
        got = plan_buckets(session, sql)
        buckets |= got
        if verbose:
            print(f"[warm] {name}: buckets {sorted(got)}", file=sys.stderr)
    observed = set()
    if stats_path:
        # measured-runtime feedback loop: buckets that real executions
        # hit refine (extend) the estimate-derived prewarm set
        from tinysql_tpu.planner.buckets import merge_feedback
        observed = merge_feedback(stats_path)
        buckets |= observed
        if verbose:
            print(f"[warm] feedback {stats_path}: buckets "
                  f"{sorted(observed)}", file=sys.stderr)
    aot = 0
    # prewarm scope: programs built below are marked prewarm-seeded in
    # ops/progcache, so later query-path hits count as prewarm_hits
    with progcache.prewarm_scope():
        for nb in sorted(buckets):
            aot += kernels.prewarm_bucket(nb)
        for name, sql in queries.items():
            tq = time.time()
            try:
                session.query(sql)
            except Exception as e:  # a broken query must not break warming
                if verbose:
                    print(f"[warm] {name} failed: {e}", file=sys.stderr)
                continue
            if verbose:
                print(f"[warm] {name} executed in {time.time() - tq:.2f}s",
                      file=sys.stderr)
    delta = kernels.stats_delta(snap)
    out = {
        "buckets": sorted(buckets),
        "observed_buckets": sorted(observed),
        "aot_programs": aot,
        "programs_traced": delta.get("progcache_misses", 0),
        "programs_reused": delta.get("progcache_hits", 0),
        "prewarm_seeded": delta.get("prewarm_seeded", 0),
        "cache_dir": kernels._cache_dir(),
        "warm_s": round(time.time() - t0, 2),
    }
    if verbose:
        print(f"[warm] {out}", file=sys.stderr)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=0.05,
                    help="TPC-H scale factor to generate and warm against")
    ap.add_argument("--queries", default="",
                    help="comma-separated TPC-H query names (default all)")
    ap.add_argument("--cache-dir", default="",
                    help="persistent compile-cache directory "
                         "(tidb_compile_cache_dir)")
    ap.add_argument("--from-stats", default="", dest="from_stats",
                    help="RuntimeStats feedback JSONL (written when "
                         "TINYSQL_STATS_FEEDBACK is set): observed "
                         "buckets join the estimate-derived prewarm set")
    args = ap.parse_args()

    # NO backend pinning here: warming must compile for the backend the
    # real queries will run on, which is jax's own choice
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.ops import kernels
    from tinysql_tpu.session.session import new_session
    if args.cache_dir and not kernels.set_compile_cache_dir(args.cache_dir):
        print(f"[warm] {kernels.CACHE_DIR_ENV} is set and wins over "
              "--cache-dir", file=sys.stderr)
    s = new_session()
    print(f"[warm] loading TPC-H SF={args.sf} ...", file=sys.stderr)
    tpch.load(s, sf=args.sf, data=tpch.generate(args.sf))
    names = [n.strip() for n in args.queries.split(",") if n.strip()] \
        or list(tpch.QUERIES)
    queries = {n: tpch.QUERIES[n] for n in names}
    print(json.dumps(warm_queries(s, queries,
                                  stats_path=args.from_stats)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

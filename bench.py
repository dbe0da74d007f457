#!/usr/bin/env python
"""Driver benchmark: TPC-H Q1 (SF from TPCH_SF env, default 1) through the
full SQL path — parse -> plan (device enforcer) -> TPU executors — printing
ONE JSON line:  {"metric", "value", "unit", "vs_baseline"}.

value    = TPU-tier Q1 wall-clock (best of 3 warm runs), seconds
vs_baseline = sqlite_cpu_s / tpu_s on Q1 — sqlite3 over the SAME generated
           data is the external CPU baseline (the Go reference cannot be
           built here: no Go toolchain in the image — see BASELINE.md
           round-2 note; detail[] also carries this engine's own CPU tier).

Also prints per-query details for Q1/Q3/Q6 on stderr.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _platform() -> str:
    """The platform JAX chose (``JAX_PLATFORMS``, else what it finds).
    The bench neither probes nor pins one: a device that cannot be
    reached fails the bench, it does not turn into a CPU number."""
    import jax
    plat = jax.devices()[0].platform
    print(f"[bench] jax backend: {plat}", file=sys.stderr)
    return plat


def _link_probe() -> dict:
    """Measure the device link at bench start so the JSON alone answers
    'was that wall number the engine or the link': per-dispatch RTT (tiny program + scalar D2H, 5 samples), D2H and H2D
    bandwidth on a 32MB buffer."""
    import numpy as np
    out = {}
    try:
        from tinysql_tpu.ops import kernels
        jn = kernels.jnp()
        jx = kernels.jax()
        fn = jx.jit(lambda a, b: jn.sum(a) + jn.sum(b))
        small = jn.zeros(16, dtype=jn.int64)
        float(np.asarray(fn(small, small)))  # warm compile
        rtts = []
        for _ in range(5):
            t0 = time.time()
            float(np.asarray(fn(small, small)))
            rtts.append(round(time.time() - t0, 4))
        mb = 32
        host = np.zeros(mb * 131072, dtype=np.float64)  # 32MB
        t0 = time.time()
        dev = jn.asarray(host)
        dev.block_until_ready()
        h2d_s = time.time() - t0
        big = jx.jit(lambda a: a + 1.0)(dev)
        np.asarray(big[:8])  # force execution before timing the download
        t0 = time.time()
        np.asarray(big)
        d2h_s = time.time() - t0
        out = {
            "backend": jx.devices()[0].platform,
            "device_kind": getattr(jx.devices()[0], "device_kind", ""),
            "rtt_s": rtts,
            "rtt_median_s": sorted(rtts)[len(rtts) // 2],
            "h2d_mb_s": round(mb / max(h2d_s, 1e-9), 1),
            "d2h_mb_s": round(mb / max(d2h_s, 1e-9), 1),
        }
    except Exception as e:  # pragma: no cover
        out = {"error": str(e)}
    print(f"[bench] link probe: {out}", file=sys.stderr)
    return out


# peak specs for the MFU / HBM-utilization estimate, by device_kind
# substring.  Values are peak DENSE bf16 matmul FLOP/s and HBM GB/s per
# chip (public TPU specs); the engine's int64/f64-emulated programs will
# show tiny MFU — that is the honest number for a memory-bound SQL engine.
_PEAKS = [
    ("v6", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5", 197e12, 819e9),     # v5e / "TPU v5 lite"
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
]


def _peak_for(device_kind: str):
    dk = (device_kind or "").lower()
    for tag, fl, bw in _PEAKS:
        if tag in dk:
            return fl, bw
    raise ValueError(f"no peak FLOP/s and bytes/s on record for device "
                     f"kind {device_kind!r}: add it to _PEAKS")


def main():
    t_start = time.time()
    device = _platform() != "cpu"
    sf = float(os.environ.get("TPCH_SF", "1"))
    from tinysql_tpu.session.session import new_session
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.ops import kernels

    link = _link_probe()
    # the probe is the authority on what actually answered — never label
    # an XLA:CPU run "tpu" (VERDICT r3 weak-1).  A probe that ERRORED
    # (no "backend" key) proves nothing either way: keep the resolved
    # platform's verdict rather than mislabeling a live device run.
    probed = link.get("backend")
    if probed is not None:
        device = probed != "cpu"
    if device:
        # per-program flops / bytes-accessed accounting for the MFU
        # estimate; off on cpu (no MFU there, and the one-time AOT
        # cost-analysis compile would be wasted work)
        kernels.enable_cost_tracking(True)
    dev_tier = "tpu" if device else "jax_cpu"

    s = new_session()
    print(f"[bench] generating + loading TPC-H SF={sf} ...", file=sys.stderr)
    t0 = time.time()
    data = tpch.generate(sf)
    counts = tpch.load(s, sf=sf, data=data)
    print(f"[bench] loaded {counts} in {time.time() - t0:.1f}s",
          file=sys.stderr)

    lite = _sqlite_baseline(data)

    warm_info = None
    if "--warm" in sys.argv:
        # bucket prewarming (tools/warm.py): AOT-compile the plan-derived
        # shape buckets + one warming execution per query, so the timed
        # first_run_s below measures a WARM first run — and the persistent
        # compile cache (JAX_COMPILATION_CACHE_DIR, else
        # tidb_compile_cache_dir) makes the next process's cold run warm
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "tinysql_warm", os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tools", "warm.py"))
        warm_mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(warm_mod)
        s.execute("set @@tidb_use_tpu = 1")
        warm_info = warm_mod.warm_queries(
            s, tpch.QUERIES,
            stats_path=os.environ.get("TINYSQL_STATS_FEEDBACK", ""))

    run_stats = {}

    def run(sql, tier):
        s.execute(f"set @@tidb_use_tpu = {1 if tier != 'cpu' else 0}")
        best = float("inf")
        rows = None
        phases = {}
        walls = []
        stats = {}
        for _ in range(3):
            t0 = time.time()
            rows = s.query(sql).rows
            dt = time.time() - t0
            # deferred cost analyses resolve BETWEEN timed runs, so the
            # AOT retrace never inflates the walls
            kernels.resolve_pending_costs()
            walls.append(round(dt, 4))
            if dt < best:
                best = dt
                phases = dict(s.last_query_info)
                # counters come from the statement's OWN observability
                # scope (obs/context.QueryObs), not a global
                # snapshot/delta pair — concurrent work elsewhere in the
                # process can no longer pollute a query's detail
                stats = dict(s.last_query_stats.device_totals())
                stats.setdefault("dispatches", 0)
                stats.setdefault("d2h_transfers", 0)
                stats.setdefault("d2h_bytes", 0)
                # symmetric transfer accounting (ISSUE 11): uploads
                # (ParamTable pushes, column/mask uploads) are counted
                # like downloads
                stats.setdefault("h2d_transfers", 0)
                stats.setdefault("h2d_bytes", 0)
                stats.setdefault("host_dispatches", 0)
                stats.setdefault("progcache_hits", 0)
                stats.setdefault("progcache_misses", 0)
                # memory-adaptive execution (ops/spill.py): 0 on an
                # unconstrained run — the quota-squeezed section below
                # proves the nonzero path
                stats.setdefault("spill_bytes", 0)
        if tier != "cpu":
            print(f"[bench] phases parse={phases.get('parse_s', 0)*1e3:.1f}ms"
                  f" plan={phases.get('plan_s', 0)*1e3:.1f}ms"
                  f" exec={phases.get('exec_s', 0)*1e3:.1f}ms "
                  f"programs={stats.get('dispatches')} "
                  f"d2h={stats.get('d2h_transfers')}x/"
                  f"{stats.get('d2h_bytes')}B", file=sys.stderr)
            # transfer accounting invariant: every kernel result is ONE
            # batched pull (kernels.d2h_many), so packed downloads can
            # never outnumber program dispatches by more than the final
            # scalar sync — dispatches=1/d2h=2 (Q6, BENCH_r05) is a bug
            assert stats.get("d2h_transfers", 0) \
                <= stats.get("dispatches", 0) + 1, (sql, stats)
            # pipelined block execution: overlap estimate (shared formula
            # with EXPLAIN ANALYZE — kernels.pipe_overlap_frac)
            if stats.get("pipe_wall_s", 0.0) > 0:
                stats["pipe_overlap_frac"] = round(
                    kernels.pipe_overlap_frac(stats), 4)
            # accelerated-path invariant (BENCH_r05 Q3 mystery): a query
            # whose PLAN places device operators must show kernel work —
            # compiled-program dispatches OR host-twin invocations (the
            # numpy kernels deliberately serving XLA:CPU).  Zero of both
            # means the executors silently fell off the accelerated
            # paths, which must fail the bench, not ship as a number.
            plan_rows = s.query("explain " + sql).rows
            tpu_placed = any(len(r) > 2 and r[2] == "tpu"
                             for r in plan_rows)
            if tpu_placed:
                assert stats.get("dispatches", 0) \
                    + stats.get("host_dispatches", 0) > 0, (sql, stats)
            extra = {}
            flops = stats.pop("flops", 0.0)
            bytes_acc = stats.pop("bytes_accessed", 0.0)
            if device and (flops or bytes_acc):
                # achieved rates from the WARM best wall (compile excluded
                # by best-of-3); MFU / HBM fraction when the chip's peak
                # is known from its device_kind.  bytes_accessed alone is
                # meaningful for pure data-movement programs.
                extra = {"flops": flops, "bytes_accessed": bytes_acc,
                         "achieved_gbs": round(bytes_acc / best / 1e9, 3)}
                pk_fl, pk_bw = _peak_for(link.get("device_kind", ""))
                extra["hbm_frac"] = round(bytes_acc / best / pk_bw, 6)
                if flops:
                    extra["achieved_gflops"] = round(flops / best / 1e9, 3)
                    extra["mfu"] = round(flops / best / pk_fl, 6)
            # memory truth (ISSUE 18): a fourth, UNTIMED run bracketed
            # by the heap probe — tracemalloc taxes every allocation in
            # the process, so the measured walls above stay probe-free
            from tinysql_tpu.obs import memprof
            probe = memprof.QueryMemProbe()
            probe.start()
            s.query(sql)
            tracked_peak = getattr(getattr(s, "_stmt_mem", None),
                                   "peak", 0) or 0
            extra.update(probe.finish(tracked_peak_bytes=tracked_peak))
            # cold-start is a first-class metric (ROADMAP item 3): the
            # first-ever run pays whatever compilation the caches missed
            run_stats[sql] = {"runs_s": walls, "first_run_s": walls[0],
                              "cold_vs_warm_ratio": round(
                                  walls[0] / max(best, 1e-9), 2),
                              # the ROADMAP item 2 gate metric: compiled
                              # dispatches ONE warm execution of this
                              # query pays (per-query obs counters)
                              "dispatches_per_query":
                                  int(stats.get("dispatches", 0)),
                              **stats, **extra}
        return best, rows

    results = {}
    for name, sql in tpch.QUERIES.items():
        dev_t, dev_rows = run(sql, dev_tier)
        cpu_t, cpu_rows = run(sql, "cpu")
        lite_t, lite_rows = lite[name]
        # correctness: identical result sets (1e-6 rel tol for float sums)
        ok = _rows_match(dev_rows, cpu_rows) and _rows_match(dev_rows,
                                                            lite_rows)
        results[name] = (dev_t, cpu_t, lite_t, ok)
        print(f"[bench] {name}: {dev_tier}={dev_t:.3f}s cpu={cpu_t:.3f}s "
              f"sqlite={lite_t:.3f}s speedup_vs_sqlite="
              f"{lite_t / dev_t:.2f}x match={ok} "
              f"({len(dev_rows)} rows)", file=sys.stderr)

    # ---- workload diversity (ISSUE 10 acceptance): Q5/Q10/Q18 through
    # the full SQL front door — multi-join chains, the decorrelated
    # IN-subquery semijoin (Q5 region, Q18 aggregate-membership), and
    # GROUP BY + ORDER BY + LIMIT composition (Q10).  Hard asserts:
    # results match sqlite over the same data, the SECOND run of each
    # query compiles nothing (the literal-parameterized families +
    # shape-keyed membership kernels cover the new operators), and a
    # TPU-placed plan shows kernel work (device or host-twin dispatches).
    print("[bench] workload diversity (Q5/Q10/Q18) ...", file=sys.stderr)
    s.execute("set @@tidb_use_tpu = 1")
    workload = {}
    for name, sql in tpch.WORKLOAD.items():
        t0 = time.time()
        s.query(sql)
        cold = time.time() - t0
        snap = kernels.stats_snapshot()
        t0 = time.time()
        rows = s.query(sql).rows
        warm = time.time() - t0
        d = kernels.stats_delta(snap)
        st = dict(s.last_query_stats.device_totals())
        lite_t, lite_rows = lite[name]
        plan_rows = s.query("explain " + sql).rows
        tpu_placed = any(len(r) > 2 and r[2] == "tpu" for r in plan_rows)
        join_ops = [r[3] for r in plan_rows
                    if len(r) > 3 and " join" in r[3]]
        ent = {
            "first_run_s": round(cold, 4),
            "warm_s": round(warm, 4),
            "sqlite_cpu_s": round(lite_t, 4),
            "speedup_vs_sqlite": round(lite_t / max(warm, 1e-9), 3),
            "rows": len(rows),
            "dispatches": int(st.get("dispatches", 0)),
            "dispatches_per_query": int(st.get("dispatches", 0)),
            "host_dispatches": int(st.get("host_dispatches", 0)),
            "d2h_transfers": int(st.get("d2h_transfers", 0)),
            "warm_progcache_misses": int(d.get("progcache_misses", 0)),
            "tpu_placed": tpu_placed,
            "join_operators": join_ops,
            "match": _rows_match(rows, lite_rows),
        }
        print(f"[bench] {name}: first={cold:.3f}s warm={warm:.3f}s "
              f"sqlite={lite_t:.3f}s match={ent['match']} "
              f"dispatches={ent['dispatches']}+"
              f"{ent['host_dispatches']}h misses(2nd)="
              f"{ent['warm_progcache_misses']}", file=sys.stderr)
        # workload acceptance is not negotiable: wrong rows, a warm-run
        # recompile, or a TPU plan doing zero kernel work all fail loud
        assert ent["match"], (name, ent)
        assert ent["warm_progcache_misses"] == 0, (name, ent)
        if tpu_placed:
            assert ent["dispatches"] + ent["host_dispatches"] > 0, \
                (name, ent)
        workload[name] = ent

    # ---- literal-parameterization proof (ISSUE 6 acceptance): the
    # second-ever execution of a constant-variant — same normalized-SQL
    # digest, different literals in the filters AND the aggregate
    # arguments — must be a program-cache HIT (zero compiles) and land
    # within 2x of the fully-warm wall.  Hard-asserted: a regression
    # back to value-keyed program caches must fail the bench.
    variants = {
        "Q1": tpch.Q1.replace("1998-09-02", "1998-07-15")
                     .replace("(1 - l_discount)", "(2 - l_discount)")
                     .replace("(1 + l_tax)", "(3 + l_tax)"),
        "Q6": tpch.Q6.replace("1994-01-01", "1994-03-01")
                     .replace("0.05", "0.04").replace("24", "20"),
    }
    s.execute("set @@tidb_use_tpu = 1")
    param_reuse = {}
    for name, vsql in variants.items():
        warm_best = results[name][0]
        snap = kernels.stats_snapshot()
        t0 = time.time()
        vrows = s.query(vsql).rows
        dt = time.time() - t0
        d = kernels.stats_delta(snap)
        ent = {"variant_first_s": round(dt, 4),
               "warm_best_s": round(warm_best, 4),
               "within_2x_warm": dt <= 2 * warm_best + 0.1,
               "progcache_misses": d.get("progcache_misses", 0),
               "prewarm_hits": d.get("prewarm_hits", 0),
               "rows": len(vrows)}
        print(f"[bench] {name} constant-variant: {dt:.3f}s "
              f"(warm {warm_best:.3f}s) misses={ent['progcache_misses']}",
              file=sys.stderr)
        # the recompile regression is caught DETERMINISTICALLY by the
        # miss counter; the wall ratio is published (within_2x_warm) but
        # not hard-asserted — a GC pause or runner hiccup on the single
        # variant run must not abort the whole bench
        assert ent["progcache_misses"] == 0, (name, ent)
        if not ent["within_2x_warm"]:
            print(f"[bench] WARNING: {name} variant exceeded 2x warm "
                  f"wall with zero compiles — timing noise or a "
                  f"non-compile regression", file=sys.stderr)
        param_reuse[name] = ent

    # ---- memory-adaptive spill proof (ISSUE 9 acceptance): each query
    # re-runs with tidb_mem_quota_query at HALF its own unconstrained
    # working-set peak (live-set MemTracker) and the soft watermark at
    # 0.8.  HARD-ASSERTED: the quota-constrained join (Q3) completes
    # with zero errors and rows byte-identical to the unconstrained run
    # — graceful degradation, not statement death.  spill_bytes /
    # spilled_queries are published per query.
    from tinysql_tpu.ops import spill as spill_ops
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_mem_quota_spill_ratio = 0.8")
    spill_detail = {}
    spilled_queries = 0
    for name, sql in tpch.QUERIES.items():
        want_rows = s.query(sql).rows   # warm + measure the working set
        peak = s._stmt_mem.peak
        quota = max(peak // 2, 64 << 10)
        snap = spill_ops.stats_snapshot()
        s.execute(f"set @@tidb_mem_quota_query = {quota}")
        err = None
        t0 = time.time()
        try:
            got_rows = s.query(sql).rows
        except Exception as e:   # published, and hard-failed below
            err, got_rows = str(e), None
        dt = time.time() - t0
        s.execute("set @@tidb_mem_quota_query = 0")
        st = spill_ops.stats_snapshot()
        ent = {"quota_bytes": quota, "unconstrained_peak_bytes": peak,
               "constrained_s": round(dt, 4),
               "spill_bytes": int(st["spill_bytes"]
                                  - snap["spill_bytes"]),
               "spill_partitions": int(st["spill_partitions"]
                                       - snap["spill_partitions"]),
               "spill_repartitions": int(st["spill_repartitions"]
                                         - snap["spill_repartitions"]),
               "spill_stream_runs": int(st["spill_stream_runs"]
                                        - snap["spill_stream_runs"]),
               "errors": 0 if err is None else 1,
               # streamed partial-agg merges may differ in the last ulp
               # (documented); published match uses the bench's float
               # tolerance — Q3's byte-exactness is asserted below
               "match": got_rows is not None
               and _rows_match(got_rows, want_rows)}
        if err is not None:
            ent["error"] = err[:200]
        if ent["spill_bytes"] > 0:
            spilled_queries += 1
        print(f"[bench] {name} half-quota: {dt:.3f}s "
              f"spill={ent['spill_bytes']}B match={ent['match']} "
              f"errors={ent['errors']}", file=sys.stderr)
        spill_detail[name] = ent
        # graceful degradation is not negotiable: every quota-squeezed
        # query completes with zero errors and matching rows
        assert err is None and ent["match"], (name, ent)
        # the acceptance join: byte-identical, via real spilling
        if name == "Q3":
            assert got_rows == want_rows, (name, ent)
            assert ent["spill_bytes"] > 0, (name, ent)
        # leak gauge must return to rest after every statement
        assert st["open_slots"] == 0, (name, st)
    spill_summary = {"spilled_queries": spilled_queries,
                     "queries": spill_detail}

    # operator micro-benchmarks (BASELINE.json configs 1-4): rows/sec
    # through HashAgg / HashJoin / Projection+Filter / top-k Sort per
    # tier, so operator regressions are visible independent of the
    # TPC-H query shapes (VERDICT r4 next-8)
    from tinysql_tpu.bench import operators as opbench
    print("[bench] operator micro-benchmarks ...", file=sys.stderr)
    opbench.load(s)
    op_results = opbench.run(s, dev_tier)
    for op, ent in op_results.items():
        print(f"[bench] op {op}: {dev_tier}={ent[f'{dev_tier}_rows_per_s']:,}"
              f" rows/s cpu={ent['cpu_rows_per_s']:,}"
              f" sqlite={ent['sqlite_rows_per_s']:,}"
              f" match={ent['match']}", file=sys.stderr)

    # mesh-sharded operator tier (ISSUE 17): per-device-count rows/s for
    # hash_agg / join_probe / sort, so multichip scaling regressions are
    # visible independent of the query shapes; match gates publication on
    # byte-identity against the single-device kernels (N=1 row)
    print("[bench] sharded operator tier ...", file=sys.stderr)
    sharded_results = opbench.run_sharded()
    for fam, ent in sharded_results["families"].items():
        scaling = " ".join(f"{k}dev={v:,}"
                           for k, v in ent["rows_per_s"].items())
        print(f"[bench] sharded {fam}: {scaling} rows/s "
              f"peak@{ent['best_devices']}dev "
              f"{ent['speedup_max_vs_1']}x vs 1dev "
              f"match={ent['match']}", file=sys.stderr)

    # observability self-cost (ISSUE 8 satellite): the fraction of one
    # core the background sampler would consume in steady state — ONE
    # shared definition with bench_serve.py (tsring.measure_overhead)
    from tinysql_tpu.obs import tsring
    obs_overhead_frac = tsring.measure_overhead()["obs_overhead_frac"]
    print(f"[bench] obs_overhead_frac={obs_overhead_frac}",
          file=sys.stderr)
    # continuous-profiler self-cost (ISSUE 13): one tick's live frame
    # walk against THIS process, scaled to the default sampling rate —
    # ONE shared definition with bench_serve (conprof.measure_overhead /
    # live_overhead_frac for a server run)
    from tinysql_tpu.obs import conprof
    conprof_overhead = conprof.measure_overhead()
    conprof_overhead_frac = conprof_overhead["conprof_overhead_frac"]
    print(f"[bench] conprof_overhead_frac={conprof_overhead_frac} "
          f"({conprof_overhead})", file=sys.stderr)
    # heap-profiler self-cost (ISSUE 18): one snapshot+fold tick against
    # THIS process at the default rate — ONE shared definition with
    # bench_serve (memprof.measure_overhead / live_overhead_frac)
    from tinysql_tpu.obs import memprof as _memprof
    memprof_overhead = _memprof.measure_overhead()
    memprof_overhead_frac = memprof_overhead["memprof_overhead_frac"]
    print(f"[bench] memprof_overhead_frac={memprof_overhead_frac} "
          f"({memprof_overhead})", file=sys.stderr)

    q1_dev, q1_cpu, q1_lite, q1_ok = results["Q1"]
    # the metric NAME carries the tier that actually ran: an XLA:CPU run
    # must never publish under a "tpu" label (VERDICT r3 weak-1)
    out = {
        "metric": f"tpch_q1_sf{sf:g}_wall_seconds_{dev_tier}",
        "value": round(q1_dev, 4),
        # baseline = sqlite3 (compiled C row engine, the Go-reference
        # proxy: no Go toolchain exists in this image — BASELINE.md §r2)
        "vs_baseline": round(q1_lite / q1_dev, 3),
        "unit": "s",
        "detail": {
            name: {f"{dev_tier}_s": round(t, 4), "cpu_s": round(c, 4),
                   "sqlite_cpu_s": round(l, 4),
                   "speedup_vs_sqlite": round(l / t, 3), "match": ok,
                   **run_stats.get(tpch.QUERIES[name], {})}
            for name, (t, c, l, ok) in results.items()
        },
        "operators": op_results,
        "operators_sharded": sharded_results,
        "workload": workload,
        "param_reuse": param_reuse,
        "spill": spill_summary,
        "obs_overhead_frac": obs_overhead_frac,
        "conprof_overhead_frac": conprof_overhead_frac,
        "memprof_overhead_frac": memprof_overhead_frac,
        "link": link,
        "correct": all(ok for _, _, _, ok in results.values())
                   and all(e["match"] for e in op_results.values())
                   and all(e["match"]
                           for e in sharded_results["families"].values())
                   and all(e["match"] for e in workload.values()),
        "total_bench_seconds": round(time.time() - t_start, 1),
    }
    if warm_info is not None:
        out["warm"] = warm_info
    print(json.dumps(out))


def _sqlite_baseline(data):
    """TPC-H Q1/Q3/Q6 on sqlite3 over the SAME generated data — the
    external CPU baseline.  The Go reference cannot run here (no Go
    toolchain in the image, BASELINE.md round-2 note); sqlite3 is a
    compiled C row-at-a-time engine, architecturally the same class as
    the reference's row-at-a-time mocktikv cop interpreter
    (/root/reference/store/mockstore/mocktikv/executor.go row loops), and
    a conservative stand-in: a battle-tuned single-file engine with no
    RPC hop is a HARDER baseline than tidb-server-on-mocktikv."""
    import sqlite3
    from tinysql_tpu.bench import tpch
    t0 = time.time()
    db = sqlite3.connect(":memory:")
    db.execute("PRAGMA journal_mode=OFF")
    db.execute("PRAGMA synchronous=OFF")
    for name, ddl in tpch.SCHEMAS.items():
        db.execute(ddl.replace("bigint", "integer")
                   .replace("double", "real"))
        cols = list(data[name].keys())
        arrays = [data[name][c] for c in cols]
        ph = ", ".join("?" * len(cols))
        db.executemany(
            f"insert into {name} values ({ph})",
            zip(*(a.tolist() for a in arrays)))
    db.commit()
    print(f"[bench] sqlite load {time.time() - t0:.1f}s", file=sys.stderr)
    out = {}
    for name, sql in tpch.ALL_QUERIES.items():
        best, rows = float("inf"), None
        for _ in range(3):
            t0 = time.time()
            rows = db.execute(sql).fetchall()
            best = min(best, time.time() - t0)
        out[name] = (best, [list(r) for r in rows])
    db.close()
    return out


def _rows_match(a, b, rel=1e-6) -> bool:
    if len(a) != len(b):
        return False
    def canon(rows):
        out = []
        for r in rows:
            key = []
            for v in r:
                if isinstance(v, float):
                    key.append(f"{(0.0 if v == 0 else v):.9g}")
                else:
                    key.append(str(v))
            out.append(tuple(key))
        return sorted(out)
    ca, cb = canon(a), canon(b)
    for ra, rb in zip(ca, cb):
        for va, vb in zip(ra, rb):
            if va == vb:
                continue
            try:
                fa, fb = float(va), float(vb)
            except ValueError:
                return False
            if abs(fa - fb) > rel * max(1.0, abs(fa), abs(fb)):
                return False
    return True


if __name__ == "__main__":
    main()

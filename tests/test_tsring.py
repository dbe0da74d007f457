"""Time-series metrics ring, serving-path wait attribution, and the
automated inspection engine (obs/tsring.py, obs/inspect.py, the
queue/batch wait threading through server/pool.py → session →
statements_summary / slow_query / histograms).

Three layers of coverage:

- ring mechanics: registry validation at sample time, retention
  trimming (including a shrink mid-flight), the MAX_SAMPLES memory
  bound, and writer/reader concurrency (no torn samples);
- wait attribution end to end: a queued statement's wait lands in
  statements_summary (sum/max/queued_count), reconciles with the
  pool-side accumulator sampled into the ring, shows wait-so-far in
  processlist, parents its spans across the pool's thread hop, and
  feeds the "queue" phase histogram;
- inspection: EVERY registered rule has a test that induces its
  condition (synthetic ring windows, or an armed failpoint end to end
  through SQL) and asserts the finding's severity + evidence window.
"""
import threading
import time

import pytest

from _timelimit import hit, in_queue
from tinysql_tpu import fail
from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.obs import inspect as oinspect
from tinysql_tpu.obs import stmtsummary, tsring
from tinysql_tpu.obs.tsring import MetricsRing
from tinysql_tpu.parser import parse
from tinysql_tpu.server import admission
from tinysql_tpu.server.pool import StatementPool
from tinysql_tpu.session.session import Session


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fail.disarm_all()
    yield
    fail.disarm_all()


@pytest.fixture(scope="module")
def storage():
    storage = new_mock_storage()
    boot = Session(storage)
    boot.execute("create database ts")
    boot.execute("use ts")
    boot.execute("create table t (a int primary key, b int)")
    boot.execute("insert into t values " + ", ".join(
        f"({i}, {i % 7})" for i in range(500)))
    return storage


def _sess(storage, db="ts"):
    s = Session(storage)
    if db:
        s.execute(f"use {db}")
    return s


# =========================================================================
# ring mechanics
# =========================================================================

def test_builtin_sources_fully_registered():
    """Every name every built-in source emits is in the central
    registry: a sample drops NOTHING (the runtime side of qlint
    OB404)."""
    ring = MetricsRing()
    values = ring.sample_once()
    assert len(values) > 30
    assert ring.stats_snapshot()["dropped_unregistered"] == 0
    # one representative per family the acceptance criteria name
    for name in ("tinysql_pool_queued", "tinysql_admission_admitted_total",
                 "tinysql_batch_rounds_total",
                 "tinysql_progcache_misses_total",
                 "tinysql_dispatches_total"):
        assert name in values, name


def test_record_drops_unregistered_names():
    live_before = tsring.stats_snapshot()["dropped_unregistered"]
    ring = MetricsRing()
    vals = ring.record({"tinysql_pool_queued": 3,
                        "tinysql_made_up_total": 1,
                        "tinysql_progcache_hits_total": "junk"})
    assert vals == {"tinysql_pool_queued": 3.0}
    assert ring.stats_snapshot()["dropped_unregistered"] == 2
    # self-accounting is PER RING: the probe above must not inflate the
    # LIVE ring's books (the /metrics + "tsring"-source feed)
    assert tsring.stats_snapshot()["dropped_unregistered"] == live_before


def test_summary_rate_and_gauge_semantics():
    """Counters summarize as delta/rate over the sampled span; gauges as
    avg/min/max.  Injected timestamps make the arithmetic exact."""
    ring = MetricsRing()
    for i, (miss, queued) in enumerate([(0, 2), (5, 6), (10, 4)]):
        ring.record({"tinysql_progcache_misses_total": miss,
                     "tinysql_pool_queued": queued}, now=1000.0 + 10 * i)
    rows = {r[0]: r for r in ring.summary_rows(now=1020.0)}
    cols = [c for c, _ in tsring.SUMMARY_COLUMNS]
    miss = dict(zip(cols, rows["tinysql_progcache_misses_total"]))
    assert miss["kind"] == "counter" and miss["samples"] == 3
    assert miss["window_s"] == 20.0 and miss["delta"] == 10.0
    assert miss["rate_per_s"] == pytest.approx(0.5)
    q = dict(zip(cols, rows["tinysql_pool_queued"]))
    assert q["kind"] == "gauge"
    assert q["min_value"] == 2.0 and q["max_value"] == 6.0
    assert q["avg_value"] == pytest.approx(4.0)


def test_counter_reset_clamps_rate_at_zero():
    ring = MetricsRing()
    ring.record({"tinysql_progcache_misses_total": 50}, now=100.0)
    ring.record({"tinysql_progcache_misses_total": 2}, now=110.0)
    row = ring.summary_rows(now=110.0)[0]
    cols = [c for c, _ in tsring.SUMMARY_COLUMNS]
    r = dict(zip(cols, row))
    assert r["delta"] == -48.0 and r["rate_per_s"] == 0.0


def test_retention_shrink_mid_flight_trims_immediately():
    ring = MetricsRing(retention_s=1000)
    for i in range(11):
        ring.record({"tinysql_pool_queued": i}, now=1000.0 + 10 * i)
    assert ring.size() == 11
    # a LOWER retention arrives with the next sample (the sysvar was
    # shrunk mid-flight): already-stored samples past the new horizon
    # are trimmed in the same append
    ring.record({"tinysql_pool_queued": 99}, now=1111.0, retention_s=25)
    assert ring.size() == 3  # 1090, 1100, 1111
    assert min(ts for ts, _ in ring._samples) >= 1111.0 - 25


def test_max_samples_hard_bound():
    ring = MetricsRing(retention_s=10**9)
    for i in range(tsring.MAX_SAMPLES + 50):
        ring.record({"tinysql_pool_queued": 0}, now=float(i))
    assert ring.size() == tsring.MAX_SAMPLES


def test_ring_writes_racing_reader_scans_no_torn_samples():
    """Satellite: a writer hammering record() while readers scan
    rows()/summary_rows() (and retention flips) must never raise and
    never expose a half-written sample — every scanned timestamp group
    carries the complete metric set."""
    ring = MetricsRing(retention_s=60)
    names = ("tinysql_pool_queued", "tinysql_pool_running",
             "tinysql_progcache_misses_total")
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        try:
            while not stop.is_set():
                ring.record({n: i for n in names},
                            retention_s=60 if i % 2 else 1)
                i += 1
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                rows = ring.rows()
                by_ts = {}
                for _stamp, ts, metric, _v in rows:
                    by_ts.setdefault(ts, set()).add(metric)
                for ts, metrics in by_ts.items():
                    assert metrics == set(names), (ts, metrics)
                ring.summary_rows()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=writer)] + \
        [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(10)
    assert not errors, errors
    assert ring.size() <= tsring.MAX_SAMPLES


def test_sampler_lifecycle_and_interval_sysvar(storage):
    """The background sampler paces by the GLOBAL sysvar, samples into
    its ring, and is restartable after close()."""
    boot = _sess(storage, db="")
    boot.execute("set global tidb_metrics_interval = 1")
    try:
        ring = MetricsRing()
        sampler = tsring.Sampler(storage, ring=ring)
        assert sampler.interval_s() == 1
        sampler.start()
        deadline = time.monotonic() + 10
        while ring.size() == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        sampler.close()
        assert ring.size() >= 1, "sampler never sampled"
        n = ring.size()
        sampler.start()  # restartable
        deadline = time.monotonic() + 10
        while ring.size() == n and time.monotonic() < deadline:
            time.sleep(0.05)
        sampler.close()
        assert ring.size() > n
    finally:
        boot.execute("set global tidb_metrics_interval = 5")


# =========================================================================
# SQL surface (acceptance: metrics_summary windowed rates over SQL)
# =========================================================================

def test_metrics_summary_over_sql(storage):
    """SELECT * FROM information_schema.metrics_summary returns windowed
    rates for the pool/admission/batching/progcache/kernel families,
    with real movement between two samples showing a nonzero rate."""
    tsring.RING.reset()
    s = _sess(storage)
    tsring.RING.sample_once()
    for i in range(3):
        s.query(f"select count(*) from t where b < {3 + i}")
    time.sleep(0.02)
    tsring.RING.sample_once()
    rows = s.query(
        "select metric, kind, samples, rate_per_s, delta, last_value "
        "from information_schema.metrics_summary").rows
    by_name = {r[0]: r for r in rows}
    for family in ("tinysql_pool_", "tinysql_admission_",
                   "tinysql_batch_", "tinysql_progcache_",
                   "tinysql_dispatches_total"):
        assert any(n.startswith(family) for n in by_name), family
    q = by_name["tinysql_queries_total"]
    assert q[2] == 2 and float(q[4]) >= 3  # delta: the three SELECTs
    assert float(q[3]) > 0  # windowed rate
    hist = s.query("select count(*) from "
                   "information_schema.metrics_history").rows
    assert int(hist[0][0]) > 50


# =========================================================================
# serving-path wait attribution
# =========================================================================

def _wedged_pool_run(storage, pool, sqls, wedge_s=0.5):
    """Run sqls[0] into an armed admissionDelay wedge, queue the rest
    behind it; returns the per-statement sessions (drained)."""
    fail.reset_hits()
    fail.arm("admissionDelay", sleep=wedge_s, times=1)
    sessions = [_sess(storage) for _ in sqls]
    threads = []
    for s, q in zip(sessions, sqls):
        t = threading.Thread(target=pool.run,
                             args=(s, parse(q)[0], q), daemon=True)
        t.start()
        # deterministic order: one wedged, the rest queued one by one
        if threads:
            in_queue(pool, len(threads))
        else:
            hit("admissionDelay")
        threads.append(t)
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    return sessions


def test_queue_wait_lands_in_summary_and_reconciles_with_pool(storage):
    """Acceptance: a queued statement's statements_summary row shows
    nonzero queue_wait that RECONCILES with the pool-side accumulator
    over the same ring window."""
    boot = _sess(storage, db="")
    boot.execute("set global tidb_stmt_pool_size = 1")
    stmtsummary.STORE.reset()
    tsring.RING.reset()
    pool = StatementPool(storage)
    try:
        tsring.RING.sample_once()
        w0 = admission.stats_snapshot()["queue_wait_s_sum"]
        sessions = _wedged_pool_run(
            storage, pool,
            ["select count(*) from t where b < 2",
             "select count(*) from t where b < 3"])
        tsring.RING.sample_once()
        # per-statement: the queued statement carries its wait, verdict
        # and a queue_wait span; the wedged leader ran immediately
        assert sessions[0].last_query_stats.admission_verdict == "admitted"
        q2 = sessions[1].last_query_stats
        assert q2.admission_verdict == "queued"
        assert q2.info["queue_s"] > 0.2
        assert any(sp["name"] == "queue_wait"
                   for sp in q2.tracer.spans())
        # aggregate: both executions fold into ONE digest row
        cols = [c for c, _ in stmtsummary.COLUMNS]
        row = [r for r in stmtsummary.rows()
               if r[cols.index("digest_text")].startswith("select")][0]
        sum_ms = row[cols.index("sum_queue_wait_ms")]
        max_ms = row[cols.index("max_queue_wait_ms")]
        assert row[cols.index("queued_count")] == 1
        assert max_ms > 200 and sum_ms >= max_ms
        # reconciliation: the ring's windowed delta of the pool-side
        # accumulator equals the summary's attribution (same two
        # statements, same window)
        pts = tsring.RING.series(
            "tinysql_admission_queue_wait_seconds_total")
        ring_delta_ms = (pts[-1][1] - pts[0][1]) * 1e3
        assert ring_delta_ms == pytest.approx(
            admission.stats_snapshot()["queue_wait_s_sum"] * 1e3
            - w0 * 1e3, abs=1.0)
        assert sum_ms == pytest.approx(ring_delta_ms, abs=1.0)
        # the "queue" phase histogram saw the wait
        assert stmtsummary.histogram_snapshot()["queue"]["count"] >= 1
    finally:
        boot.execute("set global tidb_stmt_pool_size = 4")
        pool.close()


def test_processlist_queued_time_is_wait_so_far(storage):
    """Satellite contract: state='queued' TIME reports the statement's
    wait in the admission queue SO FAR (since pool submit), and it
    grows while the statement stays queued."""
    boot = _sess(storage, db="")
    boot.execute("set global tidb_stmt_pool_size = 1")
    pool = StatementPool(storage)
    try:
        fail.reset_hits()
        fail.arm("admissionDelay", sleep=1.0, times=1)
        s1, s2 = _sess(storage), _sess(storage)
        t1 = threading.Thread(
            target=pool.run,
            args=(s1, parse("select count(*) from t")[0], "q1"),
            daemon=True)
        t1.start()
        hit("admissionDelay")  # s1's worker is inside the wedge
        submit_ts = time.monotonic()
        t2 = threading.Thread(
            target=pool.run,
            args=(s2, parse("select count(*) from t where b < 5")[0],
                  "q2"), daemon=True)
        t2.start()
        obs = _sess(storage, db="")
        waits = []
        deadline = time.monotonic() + 5
        while len(waits) < 2 and time.monotonic() < deadline:
            time.sleep(0.1)
            rows = obs.query(
                "select id, time_ms from information_schema.processlist "
                "where state = 'queued'").rows
            for cid, ms in rows:
                if cid == s2.conn_id:
                    waits.append((time.monotonic(), int(ms)))
        assert len(waits) >= 2, "queued row not observed twice"
        for seen_at, ms in waits:
            elapsed_ms = (seen_at - submit_ts) * 1e3
            # wait-so-far: matches elapsed-since-SUBMIT (generous slack
            # for scan wall), never the statement's (zero) run time
            assert 0 < ms <= elapsed_ms + 50, (ms, elapsed_ms)
        assert waits[1][1] > waits[0][1], "queued TIME did not grow"
        t1.join(30)
        t2.join(30)
    finally:
        boot.execute("set global tidb_stmt_pool_size = 4")
        fail.disarm("admissionDelay")
        pool.close()


def test_pool_worker_spans_parent_to_submitting_thread(storage):
    """Statements executed on pool workers run inside a contextvars
    COPY of the submitting thread's context, under the worker's ``solo``
    span: their parse→plan→execute span chain parents to it, and its
    ``wait`` argument names the span live at submit time, so the chain
    is no orphan on the worker thread."""
    from tinysql_tpu.obs import context as obs_context
    pool = StatementPool(storage)
    try:
        s = _sess(storage)
        outer = obs_context.QueryObs(sql="conn-root")
        tok = obs_context.activate(outer)
        try:
            with obs_context.span("conn-root") as root:
                rs = pool.run(s, parse("select count(*) from t")[0],
                              "select count(*) from t")
        finally:
            obs_context.deactivate(tok)
        assert rs.rows[0][0] == 500
        spans = s.last_query_stats.tracer.spans()
        execute = [sp for sp in spans if sp["name"] == "execute"]
        assert execute, spans
        # ... through the connection thread's pool.wait (submit -> done)
        wait = [sp for sp in outer.tracer.spans()
                if sp["name"] == "pool.wait"]
        assert wait and wait[0]["parent"] == root.sid, outer.tracer.spans()
        solo = [sp for sp in obs_context.PROCESS.spans()
                if sp["id"] == execute[0]["parent"]]
        assert solo and solo[0]["name"] == "solo", execute
        assert solo[0]["args"]["wait"] == wait[0]["id"]
        # and the chain below it is intact: plan/place parent to execute
        children = {sp["name"] for sp in spans
                    if sp["parent"] == execute[0]["id"]}
        assert "plan" in children, spans
    finally:
        pool.close()


def test_slow_query_carries_wait_fields(storage):
    """slow_query mem-table rows expose queue_wait_ms / batch_wait_ms
    join keys for pooled statements."""
    from tinysql_tpu.obs import slowlog
    boot = _sess(storage, db="")
    boot.execute("set global tidb_stmt_pool_size = 1")
    slowlog.clear()
    pool = StatementPool(storage)
    try:
        sessions = [_sess(storage) for _ in range(2)]
        for s in sessions:
            s.sysvars["tidb_slow_log_threshold"] = 0  # everything is slow
        fail.reset_hits()
        fail.arm("admissionDelay", sleep=0.4, times=1)
        threads = []
        for s, q in zip(sessions, ["select count(*) from t",
                                   "select count(*) from t where b < 1"]):
            t = threading.Thread(target=pool.run,
                                 args=(s, parse(q)[0], q), daemon=True)
            t.start()
            if threads:
                in_queue(pool)
            else:
                hit("admissionDelay")
            threads.append(t)
        for t in threads:
            t.join(30)
        rows = _sess(storage, db="").query(
            "select queue_wait_ms, query from "
            "information_schema.slow_query").rows
        queued = [r for r in rows if "b < 1" in r[1]]
        assert queued and float(queued[0][0]) > 200, rows
    finally:
        boot.execute("set global tidb_stmt_pool_size = 4")
        fail.disarm("admissionDelay")
        pool.close()
        slowlog.clear()


# =========================================================================
# inspection engine — every registered rule induced
# =========================================================================

EXPECTED_RULES = {"compile-storm", "progcache-hit-rate",
                  "pool-saturation", "cooldown-flapping",
                  "memory-pressure", "spill-pressure",
                  "prewarm-starvation",
                  # device-time truth (ISSUE 11)
                  "dispatch-storm", "transfer-bound",
                  "recompile-churn", "slo-burn",
                  # host-CPU truth (ISSUE 13)
                  "cpu-saturation", "profiler-overhead",
                  # stacked-params batching (ISSUE 14)
                  "batching-degraded",
                  # C10k wire front end (ISSUE 15)
                  "connection-pressure",
                  # mesh-sharded operator tier (ISSUE 17)
                  "shard-imbalance",
                  # durable MVCC (ISSUE 19)
                  "wal-stall",
                  # memory truth (ISSUE 18) — induced in
                  # test_memprof.py alongside the profiler they judge
                  "heap-growth", "hbm-pressure", "mem-untracked"}


def test_rule_catalogue_fully_covered():
    """The registered catalogue is exactly the set induced below —
    adding a rule without a test fails here (the chaos-matrix
    discipline, inspection edition)."""
    assert set(oinspect.RULES) == EXPECTED_RULES


def _ring_with(deltas, t0=1000.0, steps=3):
    """Synthetic ring: each metric ramps linearly from 0 to its delta
    across `steps` samples, 10 s apart."""
    ring = MetricsRing()
    for i in range(steps):
        ring.record({m: d * i / (steps - 1) for m, d in deltas.items()},
                    now=t0 + 10 * i)
    return ring


def _findings(ring, rule):
    return [f for f in oinspect.run(ring=ring) if f.rule == rule]


def test_rule_compile_storm():
    ring = _ring_with({"tinysql_progcache_misses_total":
                       oinspect.COMPILE_STORM_MISSES})
    f = _findings(ring, "compile-storm")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_progcache_misses_total"
    # evidence window spans the sampled ramp
    assert (f[0].start_ts, f[0].end_ts) == (1000.0, 1020.0)
    assert f[0].last_value == oinspect.COMPILE_STORM_MISSES
    # 2x the threshold escalates
    ring = _ring_with({"tinysql_progcache_misses_total":
                       2 * oinspect.COMPILE_STORM_MISSES})
    assert _findings(ring, "compile-storm")[0].severity == "critical"
    # under threshold: silent
    ring = _ring_with({"tinysql_progcache_misses_total":
                       oinspect.COMPILE_STORM_MISSES - 1})
    assert not _findings(ring, "compile-storm")


def test_rule_progcache_hit_rate():
    lookups = oinspect.HIT_RATE_MIN_LOOKUPS
    ring = _ring_with({"tinysql_progcache_hits_total": lookups * 0.3,
                       "tinysql_progcache_misses_total": lookups * 0.7})
    f = _findings(ring, "progcache-hit-rate")
    assert len(f) == 1 and f[0].severity == "warning"
    # healthy rate: silent (even with the same traffic)
    ring = _ring_with({"tinysql_progcache_hits_total": lookups * 0.9,
                       "tinysql_progcache_misses_total": lookups * 0.1})
    assert not _findings(ring, "progcache-hit-rate")
    # too few lookups to judge: silent
    ring = _ring_with({"tinysql_progcache_hits_total": 1,
                       "tinysql_progcache_misses_total": 3})
    assert not _findings(ring, "progcache-hit-rate")


def test_rule_pool_saturation_depth_warning():
    ring = _ring_with({"tinysql_pool_queued": oinspect.POOL_QUEUED_WARN})
    f = _findings(ring, "pool-saturation")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].max_value == oinspect.POOL_QUEUED_WARN


def test_rule_cooldown_flapping():
    ring = _ring_with({"tinysql_device_loss_total":
                       oinspect.COOLDOWN_FLAP_LOSSES})
    f = _findings(ring, "cooldown-flapping")
    assert len(f) == 1 and f[0].severity == "critical"
    ring = _ring_with({"tinysql_device_loss_total": 1})
    assert not _findings(ring, "cooldown-flapping")


def test_rule_memory_pressure():
    ring = _ring_with({"tinysql_mem_quota_exceeded_total": 2})
    f = _findings(ring, "memory-pressure")
    assert len(f) == 1 and f[0].severity == "warning"
    assert "8175" in f[0].details


def test_rule_spill_pressure():
    # a window's worth of spilled bytes: the quota is actively bounding
    # working sets — warning
    ring = _ring_with({"tinysql_spill_bytes_total":
                       oinspect.SPILL_PRESSURE_BYTES,
                       "tinysql_spilled_statements_total": 2})
    f = _findings(ring, "spill-pressure")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_spill_bytes_total"
    # recursive repartitioning escalates to critical (one
    # depth-exhaustion from 8175) and supersedes the byte warning
    ring = _ring_with({"tinysql_spill_bytes_total":
                       oinspect.SPILL_PRESSURE_BYTES,
                       "tinysql_spill_repartitions_total": 1})
    f = _findings(ring, "spill-pressure")
    assert len(f) == 1 and f[0].severity == "critical"
    assert "8175" in f[0].details
    # a sub-threshold trickle is the feature working as designed
    ring = _ring_with({"tinysql_spill_bytes_total": 1024})
    assert not _findings(ring, "spill-pressure")


def test_rule_prewarm_starvation():
    ring = _ring_with({"tinysql_prewarm_worker_skipped_budget_total": 3,
                       "tinysql_prewarm_worker_errors_total": 1})
    f = _findings(ring, "prewarm-starvation")
    assert {x.item for x in f} == {"budget", "errors"}
    assert all(x.severity == "warning" for x in f)


def test_rule_dispatch_storm():
    per = oinspect.DISPATCH_STORM_PER_QUERY
    nq = oinspect.DISPATCH_STORM_MIN_QUERIES
    ring = _ring_with({"tinysql_queries_total": nq,
                       "tinysql_dispatches_total": nq * per})
    f = _findings(ring, "dispatch-storm")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_dispatches_total"
    # 2x the per-query threshold escalates
    ring = _ring_with({"tinysql_queries_total": nq,
                       "tinysql_dispatches_total": nq * per * 2})
    assert _findings(ring, "dispatch-storm")[0].severity == "critical"
    # a healthy ratio is silent no matter the traffic
    ring = _ring_with({"tinysql_queries_total": 1000,
                       "tinysql_dispatches_total": 3000})
    assert not _findings(ring, "dispatch-storm")
    # too few queries to judge: silent even at a wild ratio
    ring = _ring_with({"tinysql_queries_total": nq - 1,
                       "tinysql_dispatches_total": (nq - 1) * per * 4})
    assert not _findings(ring, "dispatch-storm")


def test_rule_transfer_bound():
    moved = oinspect.TRANSFER_BOUND_MIN_BYTES
    # the window moved 32 MiB against ~1 ms of measured device time —
    # orders of magnitude over the bytes-per-busy-second threshold
    ring = _ring_with({"tinysql_d2h_bytes_total": moved,
                       "tinysql_dispatches_total": 4,
                       "tinysql_profiled_dispatches_total": 4,
                       "tinysql_device_busy_seconds_total": 0.001})
    f = _findings(ring, "transfer-bound")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_d2h_bytes_total"
    # plenty of measured device work for the bytes: silent
    ring = _ring_with({"tinysql_d2h_bytes_total": moved,
                       "tinysql_dispatches_total": 4,
                       "tinysql_profiled_dispatches_total": 4,
                       "tinysql_device_busy_seconds_total": 10.0})
    assert not _findings(ring, "transfer-bound")
    # fractional profile rate: measured busy covers only the sampled
    # tenth of the dispatches — the rule must extrapolate, not read the
    # workload as 10x more transfer-bound than it is (1 GiB against a
    # true ~4 s of busy time is healthy)
    ring = _ring_with({"tinysql_d2h_bytes_total": 1 << 30,
                       "tinysql_dispatches_total": 40,
                       "tinysql_profiled_dispatches_total": 4,
                       "tinysql_device_busy_seconds_total": 0.4})
    assert not _findings(ring, "transfer-bound")
    # profiler off (no measured device time in the window): the rule
    # must NOT judge against async submit walls — silent
    ring = _ring_with({"tinysql_d2h_bytes_total": moved})
    assert not _findings(ring, "transfer-bound")
    # sub-threshold volume: silent
    ring = _ring_with({"tinysql_d2h_bytes_total": moved // 4,
                       "tinysql_dispatches_total": 4,
                       "tinysql_profiled_dispatches_total": 4,
                       "tinysql_device_busy_seconds_total": 0.001})
    assert not _findings(ring, "transfer-bound")


def test_rule_recompile_churn():
    # a churning family: every execution compiles (misses per exec well
    # beyond the first run's) — synthesized straight into the summary
    # store with a unique digest, judged via summary_records
    n = oinspect.RECOMPILE_MIN_EXECS
    digest = "churn-test-digest"
    for _ in range(n):
        stmtsummary.STORE.ingest(
            sql="select churn", sql_digest=digest, digest_text="x",
            stmt_type="select", schema_name="ts", plan_digest="pd-churn",
            info={"exec_s": 0.01},
            device={"progcache_misses": oinspect.RECOMPILE_MISSES_PER_EXEC
                    + 1})
    try:
        f = [x for x in _findings(MetricsRing(), "recompile-churn")
             if x.item == digest]
        assert len(f) == 1 and f[0].severity == "warning"
        assert "warm digest family" in f[0].details
        # a healthy family (compiles only on its first run) stays silent
        healthy = "healthy-test-digest"
        for i in range(n):
            stmtsummary.STORE.ingest(
                sql="select healthy", sql_digest=healthy, digest_text="y",
                stmt_type="select", schema_name="ts",
                plan_digest="pd-healthy", info={"exec_s": 0.01},
                device={"progcache_misses": 3 if i == 0 else 0})
        assert not [x for x in _findings(MetricsRing(), "recompile-churn")
                    if x.item == healthy]
    finally:
        stmtsummary.STORE.reset()


def test_rule_connection_pressure():
    n = oinspect.CONN_SHEDS_WARN
    # some connects refused while most were admitted: warning
    ring = _ring_with({"tinysql_conn_sheds_total": n,
                       "tinysql_conn_accepts_total": n * 10})
    f = _findings(ring, "connection-pressure")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_conn_sheds_total"
    # the window shed MORE than it admitted: critical
    ring = _ring_with({"tinysql_conn_sheds_total": n * 6,
                       "tinysql_conn_accepts_total": n * 2})
    assert _findings(ring, "connection-pressure")[0].severity \
        == "critical"
    # under the floor: silent (one refused connect is a retry loop
    # against a small cap, not pressure)
    ring = _ring_with({"tinysql_conn_sheds_total": n - 1,
                       "tinysql_conn_accepts_total": 0})
    assert not _findings(ring, "connection-pressure")
    # no sheds at all: silent
    ring = _ring_with({"tinysql_conn_accepts_total": 50})
    assert not _findings(ring, "connection-pressure")


def test_rule_shard_imbalance():
    n = oinspect.SHARD_SKEW_RETRIES_WARN
    # skew bails alongside more completed sharded rounds: warning
    ring = _ring_with({"tinysql_shard_skew_retries_total": n,
                       "tinysql_shard_rounds_total": n * 5,
                       "tinysql_shard_rows_hwm": 4096})
    f = _findings(ring, "shard-imbalance")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_shard_skew_retries_total"
    assert "4096" in f[0].details
    # the window abandoned MORE attempts than it completed rounds —
    # the mesh is idle for this key distribution: critical
    ring = _ring_with({"tinysql_shard_skew_retries_total": n * 4,
                       "tinysql_shard_rounds_total": n})
    assert _findings(ring, "shard-imbalance")[0].severity == "critical"
    # a single capacity-gate bail is the gate working, not imbalance
    ring = _ring_with({"tinysql_shard_skew_retries_total": n - 1,
                       "tinysql_shard_rounds_total": 10})
    assert not _findings(ring, "shard-imbalance")


def test_rule_wal_stall():
    n = oinspect.WAL_STALL_MIN_FSYNCS
    # mean fsync wall past the warning line: the strict-policy ack tax
    ring = _ring_with({"tinysql_wal_fsyncs_total": n,
                       "tinysql_wal_fsync_seconds_total":
                           n * oinspect.WAL_STALL_MEAN_WARN_S * 1.5})
    f = _findings(ring, "wal-stall")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_wal_fsync_seconds_total"
    # past the critical line
    ring = _ring_with({"tinysql_wal_fsyncs_total": n,
                       "tinysql_wal_fsync_seconds_total":
                           n * oinspect.WAL_STALL_MEAN_CRIT_S * 2})
    assert _findings(ring, "wal-stall")[0].severity == "critical"
    # fast disk: silent
    ring = _ring_with({"tinysql_wal_fsyncs_total": n * 10,
                       "tinysql_wal_fsync_seconds_total":
                           n * oinspect.WAL_STALL_MEAN_WARN_S * 0.1})
    assert not _findings(ring, "wal-stall")
    # too few syncs to judge the mean: silent
    ring = _ring_with({"tinysql_wal_fsyncs_total": n - 1,
                       "tinysql_wal_fsync_seconds_total": 10.0})
    assert not _findings(ring, "wal-stall")
    # ANY journal error is critical outright — the durability path
    # itself failed, whatever the latency looked like
    ring = _ring_with({"tinysql_wal_append_errors_total": 1})
    f = _findings(ring, "wal-stall")
    assert len(f) == 1 and f[0].severity == "critical"
    assert f[0].metric == "tinysql_wal_fsync_errors_total"


def test_rule_batching_degraded():
    n = oinspect.BATCH_DEGRADED_MIN_ATTEMPTS
    # 30% of windowed replay attempts fell back to solo dispatch —
    # past the 20% warning line
    ring = _ring_with({"tinysql_batch_statements_total": n * 0.7,
                       "tinysql_batch_fallbacks_total": n * 0.3})
    f = _findings(ring, "batching-degraded")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_batch_fallbacks_total"
    # at/over 50%: critical
    ring = _ring_with({"tinysql_batch_statements_total": n * 0.5,
                       "tinysql_batch_fallbacks_total": n * 0.5})
    assert _findings(ring, "batching-degraded")[0].severity == "critical"
    # a healthy coalescer (sub-threshold fallback share): silent
    ring = _ring_with({"tinysql_batch_statements_total": n,
                       "tinysql_batch_fallbacks_total": n * 0.1})
    assert not _findings(ring, "batching-degraded")
    # too few attempts to judge: silent even at a 100% fallback share
    ring = _ring_with({"tinysql_batch_fallbacks_total": n - 1})
    assert not _findings(ring, "batching-degraded")
    # the STACKED leg is judged separately in group units: groups that
    # should have ridden one vmap-batched dispatch but fell back to
    # back-to-back replays — even while every replay consume HITS
    g = oinspect.BATCH_DEGRADED_MIN_GROUPS
    ring = _ring_with({"tinysql_batch_statements_total": 4 * n,
                       "tinysql_batch_stacked_rounds_total": g * 0.4,
                       "tinysql_batch_stack_fallbacks_total": g * 0.6})
    f = _findings(ring, "batching-degraded")
    assert len(f) == 1 and f[0].severity == "critical"
    assert f[0].item == "stacked"
    assert f[0].metric == "tinysql_batch_stack_fallbacks_total"
    # healthy stacking: silent
    ring = _ring_with({"tinysql_batch_stacked_rounds_total": g,
                       "tinysql_batch_stack_fallbacks_total": g * 0.1})
    assert not _findings(ring, "batching-degraded")


def test_rule_slo_burn():
    oinspect.set_slo_p99_ms(50)
    try:
        total = 2 * oinspect.SLO_MIN_MEASUREMENTS
        # 10% of windowed measurements breached a p99 objective: 10x the
        # 1% budget — critical
        ring = _ring_with({"tinysql_slo_exec_measurements_total": total,
                           "tinysql_slo_exec_breaches_total": total * 0.1})
        f = _findings(ring, "slo-burn")
        assert len(f) == 1 and f[0].severity == "critical"
        assert "tidb_slo_p99_ms=50" in f[0].details
        # within budget (<= 1%): silent
        ring = _ring_with({"tinysql_slo_exec_measurements_total": total,
                           "tinysql_slo_exec_breaches_total":
                           total * oinspect.SLO_BURN_FRAC})
        assert not _findings(ring, "slo-burn")
        # too few measurements to judge: silent
        ring = _ring_with({"tinysql_slo_exec_measurements_total":
                           oinspect.SLO_MIN_MEASUREMENTS - 1,
                           "tinysql_slo_exec_breaches_total": 5})
        assert not _findings(ring, "slo-burn")
        # a threshold that CHANGED within the window invalidates the
        # breach delta (a lowered SLO would reclassify all history):
        # silent until a stable window
        ring = MetricsRing()
        for i, armed in enumerate((500.0, 50.0, 50.0)):
            ring.record({"tinysql_slo_exec_measurements_total": 100 + i,
                         "tinysql_slo_exec_breaches_total": 200 * (i > 0),
                         "tinysql_slo_p99_ms": armed},
                        now=1000.0 + 10 * i)
        oinspect.set_slo_p99_ms(50)
        assert not _findings(ring, "slo-burn")
        # ... and a stable armed series that no longer matches the LIVE
        # objective is equally unjudgeable
        ring = MetricsRing()
        for i in range(3):
            ring.record({"tinysql_slo_exec_measurements_total":
                         total * i / 2,
                         "tinysql_slo_exec_breaches_total":
                         total * 0.1 * i / 2,
                         "tinysql_slo_p99_ms": 500.0},
                        now=1000.0 + 10 * i)
        oinspect.set_slo_p99_ms(50)
        assert not _findings(ring, "slo-burn")
        # no SLO armed: silent whatever the series say
        oinspect.set_slo_p99_ms(0)
        ring = _ring_with({"tinysql_slo_exec_measurements_total": total,
                           "tinysql_slo_exec_breaches_total": total})
        assert not _findings(ring, "slo-burn")
    finally:
        oinspect.set_slo_p99_ms(0)


def test_rule_cpu_saturation():
    from tinysql_tpu.obs.conprof import role_metric
    n = oinspect.CPU_SAT_MIN_BUSY_SAMPLES
    # 90% of busy samples on pool workers while the queue held
    # statements: critical, item names the dominant role
    ring = _ring_with({role_metric("pool-worker"): n * 0.9,
                       role_metric("main"): n * 0.1,
                       "tinysql_pool_queued": 5})
    f = _findings(ring, "cpu-saturation")
    assert len(f) == 1 and f[0].severity == "critical"
    assert f[0].item == "pool-worker"
    assert f[0].metric == role_metric("pool-worker")
    # dominant but below the critical share: warning
    ring = _ring_with({role_metric("pool-worker"): n * 0.7,
                       role_metric("main"): n * 0.3,
                       "tinysql_pool_queued": 5})
    assert _findings(ring, "cpu-saturation")[0].severity == "warning"
    # same dominance with an EMPTY admission queue: silent (that is
    # just the workload's shape, not a serving bottleneck)
    ring = _ring_with({role_metric("pool-worker"): n * 0.9,
                       role_metric("main"): n * 0.1})
    assert not _findings(ring, "cpu-saturation")
    # spread across roles: silent
    ring = _ring_with({role_metric("pool-worker"): n * 0.4,
                       role_metric("conn"): n * 0.3,
                       role_metric("distsql"): n * 0.3,
                       "tinysql_pool_queued": 5})
    assert not _findings(ring, "cpu-saturation")
    # too few busy samples to judge: silent
    ring = _ring_with({role_metric("pool-worker"): n - 1,
                       "tinysql_pool_queued": 5})
    assert not _findings(ring, "cpu-saturation")


def test_rule_profiler_overhead():
    # the profiler spent 10% of one core on itself over a 20 s window
    # (budget 3%): finding, details carry the live backoff divisor
    ring = _ring_with({"tinysql_conprof_self_seconds_total": 2.0,
                       "tinysql_conprof_backoff": 4})
    f = _findings(ring, "profiler-overhead")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_conprof_self_seconds_total"
    assert "divisor 4" in f[0].details
    # comfortably under budget: silent
    ring = _ring_with({"tinysql_conprof_self_seconds_total": 0.1})
    assert not _findings(ring, "profiler-overhead")
    # no movement / too few points: silent
    ring = MetricsRing()
    ring.record({"tinysql_conprof_self_seconds_total": 5.0}, now=1000.0)
    assert not _findings(ring, "profiler-overhead")


def test_rule_pool_saturation_under_armed_failpoint_via_sql(storage):
    """Satellite: the full loop — an armed admissionQueueFull sheds a
    real pooled statement, the sampler captures the rejected counter
    jump, and `SELECT ... FROM information_schema.inspection_result`
    reports the pool-saturation finding with the evidence window
    covering the two samples."""
    from tinysql_tpu.server.admission import AdmissionRejected
    tsring.RING.reset()
    pool = StatementPool(storage)
    try:
        t0 = time.time()
        tsring.RING.sample_once(now=t0)
        s = _sess(storage)
        with fail.armed("admissionQueueFull", times=1):
            with pytest.raises(AdmissionRejected):
                pool.run(s, parse("select count(*) from t")[0], "q")
        # second sample on the real clock: the inspection context clamps
        # its evidence window at scan-time `now`, so a future-stamped
        # sample would be (correctly) invisible
        tsring.RING.sample_once()
        rows = _sess(storage, db="").query(
            "select rule, severity, metric, evidence_start "
            "from information_schema.inspection_result "
            "where rule = 'pool-saturation'").rows
        assert rows, "no pool-saturation finding"
        assert rows[0][1] == "critical"
        assert rows[0][2] == "tinysql_admission_rejected_total"
        assert rows[0][3] == tsring._ts(t0)
        # /debug/inspection payload form agrees
        snap = [f for f in oinspect.snapshot()
                if f["rule"] == "pool-saturation"]
        assert snap and snap[0]["severity"] == "critical"
    finally:
        pool.close()
        tsring.RING.reset()


def test_inspection_rows_match_columns():
    ring = _ring_with({"tinysql_mem_quota_exceeded_total": 1})
    for row in oinspect.rows():
        assert len(row) == len(oinspect.COLUMNS)
    for f in oinspect.run(ring=ring):
        assert len(f.row()) == len(oinspect.COLUMNS)


def test_broken_rule_reports_itself_not_raises():
    oinspect.RULES["broken-test-rule"] = \
        lambda ctx: (_ for _ in ()).throw(ValueError("boom"))
    try:
        findings = [f for f in oinspect.run(ring=MetricsRing())
                    if f.rule == "broken-test-rule"]
        assert findings and "boom" in findings[0].details
    finally:
        del oinspect.RULES["broken-test-rule"]

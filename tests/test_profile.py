"""Device-time truth (ISSUE 11): the per-program catalog
(ops/progcache.py -> information_schema.compiled_programs), the
dispatch-level sampling profiler (ops/profiler.py,
tidb_device_profile_rate), symmetric h2d/d2h transfer accounting, the
bounded pending-cost-analysis queue, and the SLO-burn loop.

Four layers:

1. catalog round-trip: warmed Q1/Q6 produce per-program dispatch
   counts, compile walls, and plan digests, joinable against
   statements_summary over SQL;
2. profiler semantics: rate 0 is byte-identical (rows AND progcache
   keys), rate 1 records measured device time that stays under the
   host exec wall and lands in EXPLAIN ANALYZE / statements_summary /
   the dispatch-device-seconds histogram;
3. transfer symmetry: Q6 counts uploads (params + columns) like
   downloads;
4. self-diagnosis: the pending-costs queue drains from the sampler
   tick and stays bounded; induced SLO-burn (armed failpoint latency)
   and dispatch-storm findings appear in inspection_result over SQL.
"""
import time

import pytest

from tinysql_tpu import fail
from tinysql_tpu.bench import tpch
from tinysql_tpu.obs import inspect as oinspect
from tinysql_tpu.obs import stmtsummary, tsring
from tinysql_tpu.ops import kernels, profiler, progcache
from tinysql_tpu.session.session import new_session


@pytest.fixture(autouse=True)
def _clean_profiler_state():
    fail.disarm_all()
    yield
    fail.disarm_all()
    profiler.set_rate(0.0)
    oinspect.set_slo_p99_ms(0.0)
    kernels.enable_cost_tracking(False)


@pytest.fixture(scope="module")
def tp():
    s = new_session()
    tpch.load(s, sf=0.01, data=tpch.generate(0.01))
    s.execute("use tpch")
    # smoke-scale data leaves selective filters under the default row
    # gate; this module tests the observability path, not placement
    s.execute("set @@tidb_tpu_min_rows = 64")
    s.execute("set @@tidb_use_tpu = 1")
    # warm the programs once so catalog/profiler tests see warm runs
    s.query(tpch.Q1)
    s.query(tpch.Q6)
    return s


def _cols(rs):
    return {c.lower(): i for i, c in enumerate(rs.columns)}


# =========================================================================
# layer 1: the per-program catalog
# =========================================================================

def test_catalog_rows_for_warmed_queries(tp):
    tp.query(tpch.Q1)
    tp.query(tpch.Q6)
    rs = tp.query(
        "select domain, dispatches, compile_ms, plan_digest, prewarmed "
        "from information_schema.compiled_programs "
        "where dispatches > 0")
    assert rs.rows, "warmed Q1/Q6 left no dispatched programs"
    c = _cols(rs)
    domains = {r[c["domain"]] for r in rs.rows}
    # the fused-aggregate lane and at least one packed-download program
    assert any(d in domains for d in ("seg", "scalar", "group_agg")), \
        domains
    # compile walls were measured for the programs built in-process
    assert any(r[c["compile_ms"]] > 0 for r in rs.rows), rs.rows[:5]
    # dispatch-time plan-digest association: warmed query-path programs
    # carry the digest of the plan that dispatched them
    assert any(r[c["plan_digest"]] for r in rs.rows)


def test_catalog_joins_statements_summary_over_sql(tp):
    tp.query(tpch.Q1)
    tp.query(tpch.Q6)
    rs = tp.query(
        "select p.domain, p.dispatches, s.exec_count, s.digest "
        "from information_schema.compiled_programs p "
        "join information_schema.statements_summary s "
        "on p.plan_digest = s.plan_digest "
        "where p.plan_digest <> '' and p.dispatches > 0")
    assert rs.rows, "compiled_programs ⋈ statements_summary is empty"
    c = _cols(rs)
    q1_digest, _ = stmtsummary.normalize(tpch.Q1)
    assert any(r[c["digest"]] == q1_digest for r in rs.rows), \
        "Q1's programs did not join its summary family"


def test_debug_programs_payload_shape(tp):
    tp.query(tpch.Q6)
    snap = progcache.catalog_snapshot()
    assert snap and snap[0]["dispatches"] >= snap[-1]["dispatches"]
    for k in ("domain", "key", "compile_ms", "dispatches", "device_ms",
              "profiled_dispatches", "flops", "bytes_accessed",
              "plan_digest", "prewarmed"):
        assert k in snap[0], snap[0]
    # mem-table rows match the declared layout
    rows = progcache.catalog_rows()
    assert all(len(r) == len(progcache.CATALOG_COLUMNS) for r in rows)


# =========================================================================
# layer 2: the sampling profiler
# =========================================================================

def test_rate_zero_byte_identical_rows_and_keys(tp):
    tp.execute("set @@tidb_device_profile_rate = 0")
    rows0 = tp.query(tpch.Q6).rows
    keys0 = set(progcache.keys())
    dev0 = tp.last_query_stats.device_totals()
    assert dev0.get("device_s", 0.0) == 0.0
    assert dev0.get("profiled_dispatches", 0) == 0
    tp.execute("set @@tidb_device_profile_rate = 1")
    try:
        rows1 = tp.query(tpch.Q6).rows
        keys1 = set(progcache.keys())
    finally:
        tp.execute("set @@tidb_device_profile_rate = 0")
    assert rows0 == rows1
    # profiling compiles NOTHING and never perturbs program keys
    assert keys0 == keys1


def test_rate_one_measures_device_time_under_wall(tp):
    tp.execute("set @@tidb_device_profile_rate = 1")
    try:
        tp.query(tpch.Q6)
        q = tp.last_query_stats  # BEFORE the trailing SET replaces it
    finally:
        tp.execute("set @@tidb_device_profile_rate = 0")
    dev = q.device_totals()
    assert dev.get("dispatches", 0) > 0
    # rate 1 = every dispatch sampled
    assert dev.get("profiled_dispatches", 0) == dev["dispatches"], dev
    # measured device busy time is real and bounded by the host wall
    assert 0.0 < dev["device_s"] <= q.info["exec_s"], \
        (dev["device_s"], q.info)
    # the per-program catalog accrued the same measurement family
    assert any(m["device_ms"] > 0 and m["profiled_dispatches"] > 0
               for m in progcache.catalog_snapshot())
    # and the process histogram observed the samples
    h = profiler.histogram_snapshot()
    assert h["count"] >= dev["dispatches"]


def test_fractional_rate_samples_subset(tp):
    tp.execute("set @@tidb_device_profile_rate = 0.5")
    try:
        profiled = dispatched = 0
        for _ in range(3):
            tp.query(tpch.Q6)
            dev = tp.last_query_stats.device_totals()
            profiled += dev.get("profiled_dispatches", 0)
            dispatched += dev.get("dispatches", 0)
    finally:
        tp.execute("set @@tidb_device_profile_rate = 0")
    # deterministic every-2nd sampling: a strict subset is measured
    assert 0 < profiled < dispatched, (profiled, dispatched)


def test_explain_analyze_and_summary_show_device_ms(tp):
    stmtsummary.STORE.reset()
    tp.execute("set @@tidb_device_profile_rate = 1")
    try:
        rs = tp.query("explain analyze " + tpch.Q6)
    finally:
        tp.execute("set @@tidb_device_profile_rate = 0")
    flat = "\n".join("\t".join(str(c) for c in r) for r in rs.rows)
    assert "device:" in flat, flat
    # statements_summary splits the family's time into the new columns
    srs = tp.query(
        "select sum_device_ms, profiled_dispatches, sum_compile_ms "
        "from information_schema.statements_summary "
        "where stmt_type = 'explain'")
    c = _cols(srs)
    assert any(r[c["sum_device_ms"]] > 0
               and r[c["profiled_dispatches"]] > 0 for r in srs.rows), \
        srs.rows


def test_set_validates_rate_range(tp):
    from tinysql_tpu.session.session import SessionError
    for bad in ("1.5", "-0.1", "'junk'"):
        with pytest.raises(SessionError):
            tp.execute(f"set @@tidb_device_profile_rate = {bad}")


# =========================================================================
# layer 3: symmetric transfer accounting
# =========================================================================

def test_h2d_d2h_symmetry_on_q6(tp):
    tp.query(tpch.Q6)
    dev = tp.last_query_stats.device_totals()
    # downloads were always counted; uploads (ParamTable push at the
    # fused dispatch, plus any column/mask uploads) now count too
    assert dev.get("d2h_transfers", 0) >= 1, dev
    assert dev.get("h2d_transfers", 0) >= 1, dev
    assert dev.get("h2d_bytes", 0) > 0, dev
    # the summary store carries the same family totals
    srs = tp.query(
        "select h2d_transfers, h2d_bytes "
        "from information_schema.statements_summary "
        "where sample_sql like 'select%l_discount%'")
    c = _cols(srs)
    assert any(r[c["h2d_transfers"]] > 0 and r[c["h2d_bytes"]] > 0
               for r in srs.rows), srs.rows


def test_metrics_render_new_families(tp):
    tp.execute("set @@tidb_device_profile_rate = 1")
    try:
        tp.query(tpch.Q6)
    finally:
        tp.execute("set @@tidb_device_profile_rate = 0")
    from tinysql_tpu.obs.metrics import render_prometheus
    text = render_prometheus()
    for name in ("tinysql_h2d_transfers_total", "tinysql_h2d_bytes_total",
                 "tinysql_device_busy_seconds_total",
                 "tinysql_profiled_dispatches_total",
                 "tinysql_compile_seconds_total",
                 "tinysql_dispatch_device_seconds_bucket"):
        assert name in text, name


# =========================================================================
# layer 4: pending-cost drain + self-diagnosis over SQL
# =========================================================================

def test_pending_costs_drained_by_sampler_tick():
    kernels.enable_cost_tracking(True)
    try:
        kernels.resolve_pending_costs()  # start from a clean queue
        jn = kernels.jnp()
        f = kernels.counted_jit(lambda a: a + 1)
        f(jn.ones(333, dtype=jn.int64))  # fresh spec: enqueues
        assert kernels._PENDING_COSTS, "cost analysis did not enqueue"
        tsring.drain_pending_costs()     # the Sampler-tick entry point
        assert not kernels._PENDING_COSTS
    finally:
        kernels.enable_cost_tracking(False)


def test_pending_costs_bounded(monkeypatch):
    kernels.enable_cost_tracking(True)
    try:
        kernels.resolve_pending_costs()
        monkeypatch.setattr(kernels, "PENDING_COSTS_MAX", 2)
        jn = kernels.jnp()
        f = kernels.counted_jit(lambda a: a * 2)
        for n in (11, 22, 33, 44, 55):   # five fresh specs
            f(jn.ones(n, dtype=jn.int64))
        assert len(kernels._PENDING_COSTS) <= 2, \
            len(kernels._PENDING_COSTS)
        # dispatching an over-cap spec again accrues zeros, not a crash
        f(jn.ones(55, dtype=jn.int64))
    finally:
        kernels.resolve_pending_costs()
        kernels.enable_cost_tracking(False)


def test_slo_burn_finding_via_armed_failpoint(tp):
    """The full SLO loop: arm a latency failpoint, run traffic past the
    objective, sample the slo source into the live ring, and read the
    slo-burn finding back over SQL."""
    tp.execute("set @@tidb_slo_p99_ms = 5")
    fail.arm("execSlowNext", sleep=0.02)
    try:
        tsring.RING.sample_once()
        for _ in range(2 * oinspect.SLO_MIN_MEASUREMENTS):
            tp.query("select count(*) from region")
        fail.disarm("execSlowNext")
        tsring.RING.sample_once()
        rs = tp.query(
            "select rule, severity, details "
            "from information_schema.inspection_result "
            "where rule = 'slo-burn'")
        assert rs.rows, "no slo-burn finding over SQL"
        assert rs.rows[0][1] in ("warning", "critical")
        assert "tidb_slo_p99_ms=5" in rs.rows[0][2]
    finally:
        fail.disarm("execSlowNext")
        tp.execute("set @@tidb_slo_p99_ms = 0")
        tsring.RING.reset()


def test_dispatch_storm_finding_over_sql(tp):
    """Induced dispatch-storm read back through inspection_result: the
    live ring records a window whose dispatches-per-query regressed."""
    now = time.time()
    per = oinspect.DISPATCH_STORM_PER_QUERY
    nq = oinspect.DISPATCH_STORM_MIN_QUERIES
    try:
        for i in range(2):
            tsring.RING.record(
                {"tinysql_queries_total": nq * i,
                 "tinysql_dispatches_total": nq * per * 2 * i},
                now=now - 10 * (1 - i))
        rs = tp.query(
            "select rule, severity from "
            "information_schema.inspection_result "
            "where rule = 'dispatch-storm'")
        assert rs.rows, "no dispatch-storm finding over SQL"
        assert rs.rows[0][1] == "critical"
    finally:
        tsring.RING.reset()


def test_cost_tracking_counts_flops():
    """counted_jit accrues XLA cost-model flops/bytes when tracking is on
    (the bench's MFU accounting)."""
    kernels.enable_cost_tracking(True)
    try:
        jn = kernels.jnp()
        snap = kernels.stats_snapshot()
        f = kernels.counted_jit(lambda a, b: a @ b)
        x = jn.ones((64, 64))
        f(x, x)                         # first sight: enqueues only
        kernels.resolve_pending_costs()  # outside any timed region
        f(x, x)
        f(x, x)
        d = kernels.stats_delta(snap)
        assert d["dispatches"] == 3
        if d["flops"] == 0:
            # resolution degrades to zeros on backends without a cost model
            pytest.skip("backend exposes no XLA cost analysis")
        # 2 post-resolution dispatches x 2*64^3 flops per the cost model
        assert d["flops"] == 2 * 2 * 64 ** 3, d
        assert d["bytes_accessed"] > 0
    finally:
        kernels.enable_cost_tracking(False)

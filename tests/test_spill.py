"""Memory-adaptive spilling execution (ops/spill.py + the executor
spill routes + MemTracker release accounting).

Four layers:

1. spill primitives: hash partitioning, the partitioned join/agg and
   the external sort/top-k reproduce the unpartitioned kernels' results
   EXACTLY (same rows, same order), recursive repartitioning splits
   hash-level skew, and depth exhaustion is the typed 8175 last resort
   — never a leak;
2. MemTracker: release/peak live-set accounting, the soft watermark,
   pressure callbacks (eviction instead of abort), and paired
   charge/release through chunk Columns across interleaved statements;
3. SQL end to end on TPC-H: spillForceAll equivalence for Q1/Q3/Q6,
   and the acceptance criterion — a quota at HALF the unconstrained
   working-set peak kills the statement with 8175 when spilling is
   disabled (spill_ratio=0) and completes byte-identically via
   spilling when enabled;
4. observability: spill activity lands in statements_summary columns,
   /metrics, and EXPLAIN ANALYZE device info.

EXACTLY holds where a group's float sum adds its rows in row order: the
scatter lowering.  Masked reductions (at most SEG_UNROLL groups) sum in
the reduction's own order, which a partition's shorter row set changes
in the last bits, so this file pins the scatter lowering; and the SQL
layers pin the per-operator tier (``tidb_devpipe = 0``), the tier that
spills.
"""
import threading
import time

import numpy as np
import pytest

from _timelimit import hit
from tinysql_tpu import fail
from tinysql_tpu.bench import tpch
from tinysql_tpu.chunk.column import Column
from tinysql_tpu.mytypes import new_int_type
from tinysql_tpu.ops import kernels, progcache, spill
from tinysql_tpu.session.session import SessionError, new_session
from tinysql_tpu.utils import memory
from tinysql_tpu.utils.interrupt import QueryKilled
from tinysql_tpu.utils.memory import MemQuotaExceeded, MemTracker


@pytest.fixture(scope="module", autouse=True)
def _scatter_sums():
    # the segment programs' key does not hold the lowering: clear them
    # on both sides so no program of the other lowering is reused
    prev = kernels.SEG_UNROLL
    kernels.SEG_UNROLL = 0
    progcache.clear()
    yield
    kernels.SEG_UNROLL = prev
    progcache.clear()


@pytest.fixture(autouse=True)
def _clean():
    fail.disarm_all()
    spill.reset_stats()
    yield
    fail.disarm_all()


def _ctx(tracker=None, n_parts=8, budget=1 << 14, spill_all=True,
         enforce=False, max_depth=3):
    return spill.SpillContext(tracker, n_parts, max_depth, budget,
                              spill_all=spill_all, enforce=enforce,
                              label="test")


def _join_match_fn(p, n_p, b, n_b):
    return kernels.join_match(p, n_p, b, n_b, outer=False)


# =========================================================================
# layer 1: spill primitives vs the unpartitioned kernels
# =========================================================================

def test_hash_partition_equal_keys_colocate_and_reseed():
    k = np.array([3, 3, 7, 7, 3, -5], dtype=np.int64)
    p0 = spill.hash_partition(k, 0, 8)
    assert p0[0] == p0[1] == p0[4] and p0[2] == p0[3]
    # a different depth is a DIFFERENT hash (seeded), still colocating
    p1 = spill.hash_partition(k, 1, 8)
    assert p1[0] == p1[1] == p1[4]
    # float -0.0 and 0.0 compare equal so they must colocate
    f = np.array([0.0, -0.0, 1.5], dtype=np.float64)
    pf = spill.hash_partition(f, 0, 16)
    assert pf[0] == pf[1]


@pytest.mark.parametrize("outer", [False, True])
def test_partitioned_join_matches_kernel(outer):
    rng = np.random.default_rng(0)
    n_b, n_p = 5000, 8000
    bk = rng.integers(0, 800, n_b).astype(np.int64)
    pk = rng.integers(0, 1000, n_p).astype(np.int64)
    bn = rng.random(n_b) < 0.05
    pn = rng.random(n_p) < 0.05
    pv = rng.random(n_p) < 0.9
    rv = rng.random(n_b) < 0.9
    want = kernels.join_match((pk, pn), n_p, (bk, bn), n_b, outer=outer,
                              lvalid=pv, rvalid=rv)
    with _ctx() as ctx:
        got = spill.partitioned_join(ctx, (pk, pn), n_p, (bk, bn), n_b,
                                     _join_match_fn, outer=outer,
                                     probe_valid=pv, build_valid=rv)
    assert np.array_equal(want[0], got[0])
    assert np.array_equal(want[1], got[1])
    assert spill.STATS["spill_partitions"] > 0
    assert spill.STATS["open_slots"] == 0


def test_partitioned_join_float_keys():
    rng = np.random.default_rng(3)
    bk = np.round(rng.random(3000) * 50, 2)
    pk = np.round(rng.random(4000) * 50, 2)
    zn = np.zeros(3000, bool), np.zeros(4000, bool)
    want = kernels.join_match((pk, zn[1]), 4000, (bk, zn[0]), 3000)
    with _ctx() as ctx:
        got = spill.partitioned_join(ctx, (pk, zn[1]), 4000,
                                     (bk, zn[0]), 3000, _join_match_fn)
    assert np.array_equal(want[0], got[0])
    assert np.array_equal(want[1], got[1])


def test_partitioned_join_recursive_repartition():
    """Partitions over the budget split with a fresh hash seed; the
    result is still exactly the kernel's."""
    rng = np.random.default_rng(2)
    n = 50000
    bk = rng.integers(0, 100000, n).astype(np.int64)
    pk = rng.integers(0, 100000, 5000).astype(np.int64)
    zb, zp = np.zeros(n, bool), np.zeros(5000, bool)
    want = kernels.join_match((pk, zp), 5000, (bk, zb), n)
    # 8 partitions of ~100KB each against a 60KB budget: every one
    # recursively repartitions once
    with _ctx(n_parts=8, budget=60_000, spill_all=False,
              enforce=True) as ctx:
        got = spill.partitioned_join(ctx, (pk, zp), 5000, (bk, zb), n,
                                     _join_match_fn)
    assert np.array_equal(want[0], got[0])
    assert np.array_equal(want[1], got[1])
    assert spill.STATS["spill_repartitions"] >= 8
    assert spill.STATS["open_slots"] == 0


def test_partitioned_join_depth_exhaustion_is_typed_8175():
    """A single-key build side can never split: recursion bottoms out
    in MemQuotaExceeded — and nothing leaks."""
    n = 50000
    bk = np.full(n, 7, dtype=np.int64)
    pk = np.arange(5000, dtype=np.int64)
    zb, zp = np.zeros(n, bool), np.zeros(5000, bool)
    ctx = _ctx(n_parts=8, budget=100_000, spill_all=False, enforce=True,
               max_depth=2)
    with pytest.raises(MemQuotaExceeded) as ei:
        with ctx:
            spill.partitioned_join(ctx, (pk, zp), 5000, (bk, zb), n,
                                   _join_match_fn)
    assert ei.value.mysql_code == 8175
    assert "repartition" in str(ei.value)
    assert spill.STATS["open_slots"] == 0


def test_partitioned_agg_matches_kernel():
    rng = np.random.default_rng(1)
    n = 20000
    gid = rng.integers(0, 37, n).astype(np.int64)
    v0 = rng.random(n) * 100
    m0 = rng.random(n) < 0.1
    v1 = rng.integers(0, 50, n).astype(np.int64)
    fmask = rng.random(n) < 0.8
    specs = [("sum", True), ("count", True), ("min", True),
             ("max", True), ("count_star", False)]
    args = [(v0, m0), (v0, m0), (v1, np.zeros(n, bool)),
            (v1, np.zeros(n, bool))]
    want = kernels.segment_group_aggregate(gid, 37, specs, args, n,
                                           filter_mask=fmask)
    with _ctx(n_parts=4) as ctx:
        got = spill.partitioned_segment_aggregate(ctx, gid, 37, specs,
                                                  args, n,
                                                  filter_mask=fmask)
    assert np.array_equal(want[0], got[0])        # present ids
    assert np.array_equal(want[2], got[2])        # first_orig (GLOBAL)
    for (wv, wm), (gv, gm) in zip(want[1], got[1]):
        assert np.array_equal(wv, gv) and np.array_equal(wm, gm)
    assert spill.STATS["open_slots"] == 0


def test_external_sort_exact_permutation():
    rng = np.random.default_rng(4)
    n = 20000
    keys = [(rng.integers(0, 100, n).astype(np.int64),
             rng.random(n) < 0.05),
            (rng.random(n) * 10, rng.random(n) < 0.05)]
    descs = [True, False]
    want_host = kernels.host_sort_permutation(keys, descs, n)
    want_dev = kernels.sort_permutation(keys, descs, n)
    with _ctx() as ctx:
        got = spill.external_sort_permutation(ctx, keys, descs, n, 3000)
    assert np.array_equal(want_host, got)
    assert np.array_equal(np.asarray(want_dev), got)
    assert spill.STATS["spill_partitions"] >= 7   # ceil(20000/3000) runs
    assert spill.STATS["open_slots"] == 0


def test_external_sort_many_runs_cascaded_merge():
    """More runs than the budget's merge fan-in holds: the merge
    cascades through intermediate passes (chained run files back
    through the store) and still reproduces the exact permutation —
    with nothing left open."""
    rng = np.random.default_rng(7)
    n = 30000
    # heavy ties on both keys: the row-id tie-break does real work
    keys = [(rng.integers(0, 8, n).astype(np.int64),
             rng.random(n) < 0.1),
            (np.round(rng.random(n) * 4, 1), rng.random(n) < 0.1)]
    descs = [False, True]
    want = kernels.host_sort_permutation(keys, descs, n)
    with _ctx(budget=1 << 14) as ctx:
        got = spill.external_sort_permutation(ctx, keys, descs, n, 500)
    assert np.array_equal(want, got)
    assert spill.STATS["spill_partitions"] >= 60   # 60 runs + pass chunks
    assert spill.STATS["open_slots"] == 0


def test_would_spill_probe_is_inert():
    """The pipeline-tier pressure probe (spill.would_spill) must not
    consume a counted spillForceAll fire or bump hit counters — arming
    '1*return(1)' still reaches the first operator gate."""
    with fail.armed("spillForceAll", value=1, times=1):
        before = fail.hits().get("spillForceAll", 0)
        assert spill.would_spill(None, 0, 1)
        assert spill.would_spill(None, 0, 1)   # still armed: not consumed
        assert fail.hits().get("spillForceAll", 0) == before
        assert fail.eval_point("spillForceAll") == 1  # the one fire intact
    assert not spill.would_spill(None, 0, 1)


def test_would_spill_tracker_decision():
    t = MemTracker(1000, spill_watermark=500)
    assert not spill.would_spill(t, 10, 1)
    assert spill.would_spill(t, 2000, 1)   # estimate over headroom
    t.consume(600)                         # watermark crossed: reactive
    assert spill.would_spill(t, 0, 1)
    assert not spill.would_spill(None, 10**9, 8)   # no tracker, no force
    assert not spill.would_spill(MemTracker(0), 10**9, 8)  # no quota


def test_external_topk_exact():
    rng = np.random.default_rng(5)
    n = 20000
    keys = [(rng.random(n) * 10, rng.random(n) < 0.05),
            (rng.integers(0, 100, n).astype(np.int64),
             np.zeros(n, bool))]
    descs = [True, False]
    want = np.asarray(kernels.top_k(keys, descs, n, 25))
    with _ctx() as ctx:
        got = spill.external_topk(ctx, keys, descs, n, 25, 3000)
    assert np.array_equal(want, got)
    assert spill.STATS["open_slots"] == 0


def test_store_failure_drops_all_partitions():
    """A reload fault mid-probe surfaces typed and leaves no slots or
    resident bytes behind."""
    rng = np.random.default_rng(6)
    bk = rng.integers(0, 100, 4000).astype(np.int64)
    pk = rng.integers(0, 100, 4000).astype(np.int64)
    z = np.zeros(4000, bool)
    t = MemTracker(0)
    ctx = _ctx(tracker=t)
    with fail.armed("spillReloadError",
                    exc=spill.SpillError("reload boom")):
        with pytest.raises(spill.SpillError):
            with ctx:
                spill.partitioned_join(ctx, (pk, z), 4000, (bk, z),
                                       4000, _join_match_fn)
    assert spill.STATS["open_slots"] == 0
    assert t.consumed == 0  # every charge released on the error path


# =========================================================================
# layer 2: MemTracker + Column release accounting
# =========================================================================

def test_tracker_release_floor_and_peak():
    t = MemTracker(0)
    t.consume(100)
    t.consume(50)
    assert (t.consumed, t.peak) == (150, 150)
    t.release(120)
    assert (t.consumed, t.peak) == (30, 150)
    t.release(1000)   # floored, never negative
    assert t.consumed == 0


def test_tracker_watermark_flips_spill_requested_and_fires_callback():
    t = MemTracker(1000, spill_watermark=500)
    fired = []
    t.on_pressure(lambda: fired.append(1))
    t.consume(400)
    assert not t.spill_requested() and not fired
    t.consume(150)
    assert t.spill_requested() and len(fired) == 1
    t.consume(100)    # already spilling: no re-fire on plain growth
    assert len(fired) == 1


def test_tracker_pressure_eviction_averts_abort():
    """A registered evictor that frees enough memory turns a would-be
    8175 into a successful allocation — graceful degradation."""
    t = MemTracker(1000, spill_watermark=800)
    t.consume_soft(900)          # resident spillable bytes

    def evict():
        t.release(900)
    t.on_pressure(evict)
    t.consume(300)               # would cross 1000 without the evictor
    assert t.consumed == 300 and t.peak >= 900


def test_tracker_hard_abort_without_evictable_memory():
    t = MemTracker(1000)
    with pytest.raises(MemQuotaExceeded):
        t.consume(2000)


def test_consume_soft_never_raises():
    t = MemTracker(100, spill_watermark=80)
    t.consume_soft(10_000)
    assert t.consumed == 10_000 and t.spill_requested()


def _int_ft():
    return new_int_type()


def test_column_charge_release_pairing_across_trackers():
    """Interleaved statements: each Column releases to the tracker it
    was born under, so one session's frees never corrupt another's
    books."""
    t1, t2 = MemTracker(0), MemTracker(0)
    tok = memory.activate(t1)
    c1 = Column.from_numpy(_int_ft(), np.arange(1000))
    memory.deactivate(tok)
    tok = memory.activate(t2)
    c2 = Column.from_numpy(_int_ft(), np.arange(2000))
    memory.deactivate(tok)
    a1, a2 = t1.consumed, t2.consumed
    assert a1 > 0 and a2 > a1
    del c2
    assert t1.consumed == a1 and t2.consumed == 0
    del c1
    assert t1.consumed == 0
    assert t1.peak == a1 and t2.peak == a2


def test_column_truncate_zero_frees_charge():
    t = MemTracker(0)
    tok = memory.activate(t)
    try:
        c = Column.from_numpy(_int_ft(), np.arange(10_000))
        assert t.consumed > 0
        c.truncate(0)
        assert t.consumed == 0
        assert len(c) == 0
    finally:
        memory.deactivate(tok)


def test_lazy_take_adopts_charge_once():
    from tinysql_tpu.chunk.column import LazyTakeColumn
    t = MemTracker(0)
    tok = memory.activate(t)
    try:
        src = Column.from_numpy(_int_ft(), np.arange(10_000))
        base = t.consumed
        lt = LazyTakeColumn(src, np.arange(100))
        assert t.consumed == base          # deferred: no charge yet
        lt.values()                        # materializes 100 rows
        assert base < t.consumed <= base + 2048
        live = t.consumed
        del lt
        assert t.consumed < live           # the adopted charge released
    finally:
        memory.deactivate(tok)


# =========================================================================
# layer 3: SQL end to end on TPC-H
# =========================================================================

@pytest.fixture(scope="module")
def tq():
    s = new_session()
    tpch.load(s, sf=0.01)
    s.execute("use tpch")
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_tpu_min_rows = 1")
    s.execute("set @@tidb_devpipe = 0")  # the per-operator tier spills
    want = {q: s.query(sql).rows for q, sql in tpch.QUERIES.items()}
    peaks = {}
    for q, sql in tpch.QUERIES.items():
        s.query(sql)
        peaks[q] = s._stmt_mem.peak
    return s, want, peaks


def test_force_all_equivalence_q1_q3(tq):
    """spill==no-spill: under spillForceAll every eligible operator
    runs partitioned, results identical, nothing leaks."""
    s, want, _ = tq
    with fail.armed("spillForceAll", value=1):
        for q in ("Q1", "Q3"):
            spill.reset_stats()
            got = s.query(tpch.QUERIES[q]).rows
            st = spill.stats_snapshot()
            assert got == want[q], q
            assert st["spill_bytes"] > 0 and st["spill_partitions"] > 0
            assert st["open_slots"] == 0, q
            assert st["spilled_statements"] == 1


def test_force_all_q6_scalar_agg_unaffected(tq):
    """Scalar aggregates have O(1) state: no spill route, same
    answer."""
    s, want, _ = tq
    with fail.armed("spillForceAll", value=1):
        assert s.query(tpch.Q6).rows == want["Q6"]


def test_quota_half_working_set_q3_spills_to_completion(tq):
    """THE acceptance criterion: quota at half the unconstrained
    working-set peak.  With the watermark disabled the statement dies
    with 8175 (the pre-spill behavior); with it, the join completes via
    spilling, byte-identical."""
    s, want, peaks = tq
    quota = peaks["Q3"] // 2
    s.execute("set @@tidb_mem_quota_spill_ratio = 0")
    s.execute(f"set @@tidb_mem_quota_query = {quota}")
    with pytest.raises(MemQuotaExceeded) as ei:
        s.query(tpch.Q3)
    assert ei.value.mysql_code == 8175
    s.execute("set @@tidb_mem_quota_spill_ratio = 0.8")
    spill.reset_stats()
    got = s.query(tpch.Q3).rows
    st = spill.stats_snapshot()
    assert got == want["Q3"]
    assert st["spill_bytes"] > 0
    assert st["open_slots"] == 0
    s.execute("set @@tidb_mem_quota_query = 0")


def test_cold_session_quota_below_input_spills_first_run():
    """Regression: a FRESH session (no table replica yet, so the join's
    build side materializes through charged chunk accumulation instead
    of zero-copy views) with a quota below that materialization must
    still complete via spilling on the FIRST execution.  The original
    wiring died with 8175 inside the ingest drain before the partitioner
    saw a single row; the fix is the soft-charged ingest scope plus the
    tracker deferring the hard abort to the spill ladder once a
    SpillContext has engaged.  With the watermark off the statement
    still hard-kills."""
    q = ("select t.a, sum(t.b + u.c) as v from t, u where t.a = u.a "
         "group by t.a order by v desc limit 7")

    def fresh():
        s = new_session()
        s.execute("set @@tidb_use_tpu = 1")
        s.execute("set @@tidb_tpu_min_rows = 1")
        s.execute("set @@tidb_devpipe = 0")
        s.execute("create database d")
        s.execute("use d")
        s.execute("create table t (a int, b double)")
        s.execute("create table u (a int, c double)")
        s.execute("insert into t values " + ",".join(
            f"({i % 500},{i * 1.5})" for i in range(4000)))
        s.execute("insert into u values " + ",".join(
            f"({i},{i * 0.25})" for i in range(500)))
        s.execute("set @@tidb_mem_quota_query = 120000")
        return s

    s = fresh()
    s.execute("set @@tidb_mem_quota_spill_ratio = 0.8")
    spill.reset_stats()
    cold = s.query(q).rows            # first-ever execution, cold scan
    st = spill.stats_snapshot()
    assert st["spill_bytes"] > 0 and st["open_slots"] == 0
    s.execute("set @@tidb_mem_quota_query = 0")
    assert cold == s.query(q).rows    # byte-identical to unconstrained

    s2 = fresh()                      # watermark off: pre-spill behavior
    s2.execute("set @@tidb_mem_quota_spill_ratio = 0")
    with pytest.raises(MemQuotaExceeded) as ei:
        s2.query(q)
    assert ei.value.mysql_code == 8175


def test_quota_constrained_q1_spills_byte_identical(tq):
    """Q1's charged footprint is small (replica views) but the
    planner's estimate prices the aggregation working set over a 2MB
    quota's watermark — the proactive trigger flips it into the
    partitioned route, byte-identical."""
    s, want, _ = tq
    s.execute(f"set @@tidb_mem_quota_query = {2 << 20}")
    spill.reset_stats()
    got = s.query(tpch.Q1).rows
    st = spill.stats_snapshot()
    assert got == want["Q1"]
    assert st["spill_bytes"] > 0
    assert st["open_slots"] == 0
    s.execute("set @@tidb_mem_quota_query = 0")


def test_spill_partitions_sysvar_pins_fanout(tq):
    s, want, _ = tq
    s.execute("set @@tidb_spill_partitions = 4")
    try:
        with fail.armed("spillForceAll", value=1):
            spill.reset_stats()
            assert s.query(tpch.Q1).rows == want["Q1"]
        # Q1's single agg spill level writes exactly the pinned fan-out
        assert spill.stats_snapshot()["spill_partitions"] == 4
    finally:
        s.execute("set @@tidb_spill_partitions = 0")


def test_sort_and_topn_spill_paths(tq):
    s, want, _ = tq
    sort_q = ("select l_orderkey, l_extendedprice from lineitem "
              "where l_orderkey <= 750 order by l_extendedprice desc, "
              "l_orderkey")
    topn_q = sort_q + " limit 17"
    want_sort = s.query(sort_q).rows
    want_topn = s.query(topn_q).rows
    with fail.armed("spillForceAll", value=1):
        spill.reset_stats()
        assert s.query(sort_q).rows == want_sort
        assert spill.stats_snapshot()["spill_partitions"] >= 2
        spill.reset_stats()
        assert s.query(topn_q).rows == want_topn
        assert spill.stats_snapshot()["spill_bytes"] > 0
    assert spill.stats_snapshot()["open_slots"] == 0


def test_interleaved_sessions_tracker_isolation(tq):
    """A quota-squeezed spilling session and an unconstrained one
    interleave: each statement's books are its own (live bytes release
    between statements; the spiller's quota never gates the other
    session)."""
    s, want, peaks = tq
    s2 = new_session(s.storage, db="tpch")
    s2.execute("set @@tidb_use_tpu = 1")
    s2.execute("set @@tidb_tpu_min_rows = 1")
    s2.execute("set @@tidb_devpipe = 0")
    s.execute(f"set @@tidb_mem_quota_query = {peaks['Q3'] // 2}")
    for _ in range(2):
        assert s.query(tpch.Q3).rows == want["Q3"]
        assert s2.query(tpch.Q3).rows == want["Q3"]
        # the unconstrained session's tracker is its own: no quota, no
        # spill charges from the other session's run
        assert s2._stmt_mem.quota == 0
        assert s2._stmt_mem.peak > peaks["Q3"] // 2
    s.execute("set @@tidb_mem_quota_query = 0")


def test_live_set_releases_between_statements(tq):
    """Release accounting: after a statement finishes, its tracker's
    live count is far below its peak (buffers freed as operators
    close) — the long-lived-session over-reporting fix."""
    s, _, _ = tq
    s.query(tpch.Q3)
    t = s._stmt_mem
    assert t.peak > 0
    assert t.consumed < t.peak


# =========================================================================
# layer 4: observability
# =========================================================================

def test_spill_visible_in_summary_metrics_explain(tq):
    s, want, _ = tq
    from tinysql_tpu.obs import stmtsummary
    from tinysql_tpu.obs.metrics import render_prometheus
    stmtsummary.STORE.reset()
    with fail.armed("spillForceAll", value=1):
        assert s.query(tpch.Q3).rows == want["Q3"]
    cols = [c for c, _ in stmtsummary.COLUMNS]
    i_sum = cols.index("sum_spill_bytes")
    i_max = cols.index("max_spill_bytes")
    i_cnt = cols.index("spill_count")
    rows = [r for r in stmtsummary.rows() if "l_orderkey" in (r[2] or "")]
    assert rows, "Q3 digest missing from statements_summary"
    r = rows[0]
    assert r[i_sum] > 0 and r[i_max] > 0 and r[i_cnt] == 1
    assert r[i_sum] >= r[i_max]
    text = render_prometheus()
    assert "tinysql_spill_bytes_total" in text
    assert "tinysql_spill_open_slots 0" in text
    # EXPLAIN ANALYZE device info carries the per-operator spill cell
    with fail.armed("spillForceAll", value=1):
        rs = s.query("explain analyze " + tpch.Q3)
    flat = "\n".join(str(row) for row in rs.rows)
    assert "spill:" in flat


def test_spill_rows_in_statements_summary_via_sql(tq):
    s, want, _ = tq
    with fail.armed("spillForceAll", value=1):
        s.query(tpch.Q3)
    rows = s.query(
        "select sum_spill_bytes, spill_count from "
        "information_schema.statements_summary "
        "where digest_text like '%l_orderkey%' "
        "and sum_spill_bytes > 0").rows
    assert rows and rows[0][0] > 0 and rows[0][1] >= 1


def test_kill_lands_mid_spill(tq):
    """A KILL arriving while partitions are reloading aborts the
    statement (1317) and leaks nothing — interrupt checks run inside
    the partition loops."""
    s, _, _ = tq
    box = []

    def run():
        try:
            with fail.armed("spillForceAll", value=1), \
                    fail.armed("spillReloadError", sleep=0.05):
                s.query(tpch.Q3)
            box.append(None)
        except Exception as e:
            box.append(e)

    fail.reset_hits()
    t = threading.Thread(target=run)
    t.start()
    hit("spillReloadError")  # partitions are reloading
    from tinysql_tpu.utils import interrupt
    interrupt.kill(s.conn_id, query_only=True)
    t.join(20)
    assert not t.is_alive()
    assert isinstance(box[0], QueryKilled), box[0]
    assert spill.stats_snapshot()["open_slots"] == 0

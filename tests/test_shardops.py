"""Mesh-sharded operator tier (ops/shardops.py): partition-parallel
join / semijoin / aggregation / sort / top-k across N devices.

Four properties, per ISSUE 17:

1. BYTE-IDENTITY — every sharded family returns exactly what its
   single-device kernel returns, across mesh sizes {1, 2, 4, 8}
   (1 degenerates to None = "run the single-device kernel"; conftest
   forces an 8-device host mesh via xla_force_host_platform_device_count).
2. COLOCATION — the shard assignment IS the PR 9 spill partitioner at
   depth 0 (spill.hash_partition), so device placement and the spill
   ladder agree on where a key's rows live.
3. ATTRIBUTION — split_exact / member_shard_shares conserve device
   counters EXACTLY (to the last ulp) through the B x N
   stacked-over-sharded split, and a coalesced batch round over a
   sharded program bumps shard_stacked_rounds.
4. DEGRADATION — a skewed key set abandons the sharded attempt
   (returns None, bumps shard_skew_retries) instead of letting one
   device carry the whole input.
"""
import jax
import numpy as np
import pytest

from tinysql_tpu.ops import kernels, progcache, shardops, spill
from tinysql_tpu.parallel import dist
from tinysql_tpu.session.session import Session, new_session

NDEV = len(jax.devices())
MESH_SIZES = [n for n in (1, 2, 4, 8) if n <= NDEV]

pytestmark = pytest.mark.skipif(NDEV < 2,
                                reason="needs a multi-device mesh")

RNG = np.random.default_rng(1117)


def _mesh(n):
    return dist.sized_mesh(n)  # n < 2 -> None (degenerate)


def _keys(n, lo, hi, null_frac=0.1, dtype=np.int64):
    v = RNG.integers(lo, hi, n).astype(np.int64)
    if dtype == np.float64:
        v = v.astype(np.float64) * 0.5
    m = RNG.random(n) < null_frac
    return v, m


# =========================================================================
# 1. byte-identity across mesh sizes
# =========================================================================

@pytest.mark.parametrize("n_shards", MESH_SIZES)
@pytest.mark.parametrize("outer", [False, True])
@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_unique_join_identity(n_shards, outer, dtype):
    n_left, n_right = 700, 400
    lk, ln = _keys(n_left, 0, 500, dtype=dtype)
    rv0 = RNG.permutation(500)[:n_right].astype(np.int64)  # unique build
    rk = rv0.astype(np.float64) * 0.5 if dtype == np.float64 else rv0
    rn = RNG.random(n_right) < 0.05
    lvalid = RNG.random(n_left) < 0.9
    rvalid = RNG.random(n_right) < 0.9
    want = kernels.unique_join_match(
        (lk, ln), n_left, (rk, rn), n_right, outer=outer,
        lvalid=lvalid, rvalid=rvalid)
    got = shardops.unique_join_match_sharded(
        _mesh(n_shards), (lk, ln), n_left, (rk, rn), n_right,
        outer=outer, lvalid=lvalid, rvalid=rvalid)
    if n_shards < 2:
        assert got is None  # degenerate mesh = single-device kernel
        return
    assert got is not None
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("n_shards", MESH_SIZES)
@pytest.mark.parametrize("anti,null_aware",
                         [(False, False), (True, False), (True, True)])
def test_semi_join_identity(n_shards, anti, null_aware):
    n_left, n_right = 900, 300
    lk, ln = _keys(n_left, 0, 400)
    rk, rn = _keys(n_right, 100, 500,
                   null_frac=0.0 if null_aware else 0.08)
    lvalid = RNG.random(n_left) < 0.9
    rvalid = RNG.random(n_right) < 0.9
    want = kernels.semi_join_match(
        (lk, ln), n_left, (rk, rn), n_right, anti=anti,
        null_aware=null_aware, lvalid=lvalid, rvalid=rvalid)
    got = shardops.semi_join_match_sharded(
        _mesh(n_shards), (lk, ln), n_left, (rk, rn), n_right,
        anti=anti, null_aware=null_aware, lvalid=lvalid, rvalid=rvalid)
    if n_shards < 2:
        assert got is None
        return
    assert got is not None
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n_shards", MESH_SIZES)
@pytest.mark.parametrize("desc", [False, True])
@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_sort_permutation_identity(n_shards, desc, dtype):
    n = 1000
    v, m = _keys(n, -300, 300, dtype=dtype)
    want = kernels.sort_permutation([(v, m)], [desc], n)
    got = shardops.sort_permutation_sharded(
        _mesh(n_shards), [(v, m)], [desc], n)
    if n_shards < 2:
        assert got is None
        return
    assert got is not None
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n_shards", MESH_SIZES)
@pytest.mark.parametrize("desc", [False, True])
@pytest.mark.parametrize("k", [1, 7, 50])
def test_top_k_identity(n_shards, desc, k):
    n = 1200
    v, m = _keys(n, -500, 500)
    want = kernels._topk_single((v, m), desc, n, k)
    got = shardops.top_k_sharded(_mesh(n_shards), [(v, m)], [desc], n, k)
    if n_shards < 2:
        assert got is None
        return
    assert got is not None
    np.testing.assert_array_equal(got, want)


def test_sharded_keys_carry_shard_tag():
    """Every sharded progcache key self-identifies its mesh size (the
    ("shards", n) marker shards_of_key reads) — stacked batching keys
    per-shard attribution off it, and two mesh sizes never collide on
    one compiled program."""
    n = 600
    v, m = _keys(n, 0, 100)
    for ns in [s for s in MESH_SIZES if s >= 2]:
        assert shardops.sort_permutation_sharded(
            _mesh(ns), [(v, m)], [False], n) is not None
    tagged = {k for k in progcache.keys() if shardops.shards_of_key(k)}
    assert {shardops.shards_of_key(k) for k in tagged} >= \
        {s for s in MESH_SIZES if s >= 2}
    # unsharded programs never carry the marker
    assert all(shardops.shards_of_key(k) == 0
               for k in progcache.keys() if k not in tagged)


# =========================================================================
# SQL-level identity: the full planner -> executor -> shardops path
# =========================================================================

@pytest.fixture(scope="module")
def sql():
    s = new_session()
    s.execute("create database so")
    s.execute("use so")
    s.execute("set @@tidb_tpu_min_rows = 0")
    # 4000 rows: even the planner's filtered-input estimate (rows / 3)
    # clears dist.MIN_SHARD_ROWS * 2, so scalar aggregates under a WHERE
    # still annotate a real shard count (shard_bucket >= 2)
    s.execute("create table t (a int primary key, b int, d double)")
    rows = []
    for i in range(1, 4001):
        b = "null" if i % 11 == 0 else str(i % 97)
        rows.append(f"({i}, {b}, {round((i * 7919) % 1000 / 8.0, 3)})")
    s.execute("insert into t values " + ", ".join(rows))
    s.query("select * from t")  # hydrate the columnar replica
    s.execute("set @@tidb_use_tpu = 1")
    return s


SQL_QUERIES = [
    # scalar agg (fused_scalar_aggregate_sharded)
    "select count(*), count(b), sum(d), min(d), max(d), avg(b) from t",
    "select count(*), sum(b) from t where d > 20",
    # unique join (partitioned build/probe)
    "select t1.a, t1.b from t t1 join t t2 on t1.b = t2.a "
    "order by t1.a",
    # semijoin
    "select a from t where b in (select a from t where d < 60) "
    "order by a",
    # sort / top-k
    "select a from t order by d desc, a limit 40",
    "select a, d from t order by d",
]


def test_sql_sharded_matches_single_device(sql):
    for q in SQL_QUERIES:
        sql.execute("set @@tidb_mesh_parallel = 0")
        single = sql.query(q).rows
        sql.execute("set @@tidb_mesh_parallel = 1")
        sharded = sql.query(q).rows
        assert repr(sharded) == repr(single), q
    sql.execute("set @@tidb_mesh_parallel = 0")


def test_sql_sharded_warm_runs_do_not_compile(sql):
    sql.execute("set @@tidb_mesh_parallel = 1")
    for q in SQL_QUERIES:
        sql.query(q)  # warm every B/N-bucketed program
    miss0 = progcache.stats_snapshot()["misses"]
    for q in SQL_QUERIES:
        sql.query(q)
    assert progcache.stats_snapshot()["misses"] == miss0, \
        "warm sharded run compiled"
    sql.execute("set @@tidb_mesh_parallel = 0")


def test_sql_sharded_rounds_counted(sql):
    sql.execute("set @@tidb_mesh_parallel = 1")
    st0 = shardops.stats_snapshot()
    sql.query(SQL_QUERIES[2])  # the partitioned join
    st = shardops.stats_snapshot()
    assert st["shard_rounds"] > st0["shard_rounds"]
    assert st["shard_exchange_bytes"] > st0["shard_exchange_bytes"]
    assert st["shard_rows_hwm"] >= 1
    sql.execute("set @@tidb_mesh_parallel = 0")


# =========================================================================
# 2. shard = spill partition (colocation)
# =========================================================================

@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_shard_is_spill_partition(n_shards):
    if n_shards > NDEV:
        pytest.skip("not enough devices")
    keys = RNG.integers(-10_000, 10_000, 2000).astype(np.int64)
    live = RNG.random(2000) < 0.85
    part = shardops._Partitioned(keys, live, n_shards)
    # the shard destination IS spill.hash_partition at depth 0
    want = spill.hash_partition(
        np.ascontiguousarray(keys[np.nonzero(live)[0]]), 0, n_shards)
    np.testing.assert_array_equal(part.dest, want)
    # equal keys colocate: one shard owns ALL rows of a key, so a
    # partition that spills reloads exactly one shard's rows
    blocks = part.scatter_ids()
    for s in range(n_shards):
        rows = blocks[s][blocks[s] >= 0]
        np.testing.assert_array_equal(
            np.unique(spill.hash_partition(
                np.ascontiguousarray(keys[rows]), 0, n_shards)),
            [s] if len(rows) else [])


def test_scatter_reassembles_in_input_order():
    keys = RNG.integers(0, 64, 500).astype(np.int64)
    live = np.ones(500, dtype=bool)
    part = shardops._Partitioned(keys, live, 4)
    lane = np.arange(500, dtype=np.int64) * 3
    blocks = part.scatter(lane, -1)
    ids = part.scatter_ids()
    sel = ids.reshape(-1) >= 0
    out = np.empty(500, dtype=np.int64)
    out[ids.reshape(-1)[sel]] = blocks.reshape(-1)[sel]
    np.testing.assert_array_equal(out, lane)


# =========================================================================
# 3. B x N attribution conservation
# =========================================================================

def _conserved(shares, key, v):
    """The split's contract: integer counters sum back exactly and stay
    integers; real-valued ones to float rounding (a tolerance, never
    ``==`` — the order of addition and Python 3.12's compensated
    ``sum`` both move the last ulp)."""
    got = sum(s[key] for s in shares)
    if isinstance(v, int):
        assert all(isinstance(s[key], int) for s in shares), key
        assert got == v, key
    else:
        assert got == pytest.approx(v, rel=1e-12), key


def test_split_exact_conserves_counters():
    totals = {"dispatches": 1, "device_time_s": 0.123456789,
              "d2h_bytes": 4096, "h2d_bytes": 7.3e-9}
    for k in (1, 2, 3, 5, 8):
        shares = shardops.split_exact(totals, k)
        assert len(shares) == k
        for key, v in totals.items():
            _conserved(shares, key, v)
    # the remainder of an integer counter lands on ONE member
    assert [s["dispatches"] for s in shardops.split_exact(totals, 3)] \
        == [0, 0, 1]
    assert [s["d2h_bytes"] for s in shardops.split_exact(totals, 3)] \
        == [1365, 1365, 1366]


def test_member_shard_shares_conserve_bxn():
    totals = {"dispatches": 1, "device_time_s": 0.777,
              "h2d_bytes": 123457.0}
    for b, n in ((2, 8), (3, 4), (5, 2), (7, 8)):
        cells = shardops.member_shard_shares(totals, b, n)
        assert len(cells) == b and all(len(row) == n for row in cells)
        for key, v in totals.items():
            _conserved([c for row in cells for c in row], key, v)
    # per-member rows reconcile with the outer split
    members = shardops.split_exact(totals, 3)
    cells = shardops.member_shard_shares(totals, 3, 4)
    for m, row in zip(members, cells):
        for key, v in m.items():
            _conserved(row, key, v)


def test_stacked_round_over_sharded_program(sql):
    """The tentpole composition: B stacked queries vmap OVER the
    N-shard program — results equal solo execution and the round counts
    into shard_stacked_rounds (the B x N product observable)."""
    from tinysql_tpu.ops import batching
    from tinysql_tpu.server.pool import StatementPool, _Entry
    from tinysql_tpu.obs import stmtsummary
    from tinysql_tpu.parser import parse
    storage = sql.storage
    qs = [f"select sum(d), count(*), max(d) from t where b < {40 + i}"
          for i in range(4)]

    def sess():
        s = Session(storage)
        s.execute("use so")
        s.execute("set @@tidb_tpu_min_rows = 0")
        s.execute("set @@tidb_mesh_parallel = 1")
        return s

    solo = {q: sess().query(q).rows for q in qs}  # warm the N-shard program
    kernels.prewarm_stacked()
    storage._global_vars["tidb_batch_stack_max"] = 16
    storage._global_vars["tidb_mesh_parallel"] = 1
    try:
        st0 = shardops.stats_snapshot()
        b0 = batching.stats_snapshot()
        digest, _ = stmtsummary.normalize(qs[0])
        pool = StatementPool(storage)
        entries = [_Entry(sess(), parse(q)[0], q, digest, True)
                   for q in qs]
        pool._run_batch(entries)
        for e, q in zip(entries, qs):
            assert e.error is None, (q, e.error)
            assert repr(e.result.rows) == repr(solo[q]), q
        b1 = batching.stats_snapshot()
        st1 = shardops.stats_snapshot()
        if b1["stacked_rounds"] > b0["stacked_rounds"]:
            assert st1["shard_stacked_rounds"] \
                > st0["shard_stacked_rounds"], \
                "stacked round ran over a sharded program uncounted"
        else:  # the round fell back solo: sharded execution still counted
            assert st1["shard_rounds"] > st0["shard_rounds"]
    finally:
        storage._global_vars.pop("tidb_batch_stack_max", None)
        storage._global_vars.pop("tidb_mesh_parallel", None)


# =========================================================================
# 4. skew fall-back
# =========================================================================

def test_skewed_keys_fall_back_single_device():
    n = 1024
    lk = np.zeros(n, dtype=np.int64)  # every key in ONE partition
    ln = np.zeros(n, dtype=bool)
    rk = np.arange(n, dtype=np.int64)
    rn = np.zeros(n, dtype=bool)
    st0 = shardops.stats_snapshot()
    got = shardops.unique_join_match_sharded(
        _mesh(max(MESH_SIZES)), (lk, ln), n, (rk, rn), n)
    assert got is None  # caller falls back to the single-device kernel
    st = shardops.stats_snapshot()
    assert st["shard_skew_retries"] == st0["shard_skew_retries"] + 1


def test_shard_metrics_registered_and_sampled():
    """The tinysql_shard_* surface: registered in obs/metrics.METRICS,
    mapped by SHARD_METRIC_NAMES, and the tsring source samples them."""
    from tinysql_tpu.obs import metrics as om
    from tinysql_tpu.obs import tsring
    for key, name in om.SHARD_METRIC_NAMES:
        assert name in om.METRICS, name
        assert key in shardops.STATS, key
    sample = tsring._src_shardops()
    assert set(sample) == {n for _, n in om.SHARD_METRIC_NAMES}

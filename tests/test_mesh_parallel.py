"""Multi-chip query execution over the device mesh: the fused aggregation
shards rows across all devices (8 virtual CPU devices in CI via conftest)
and merges partial segment tables with psum/pmin/pmax over the mesh axis —
SURVEY §2.11 P5's reduce-scatter schema driven from REAL SQL queries.
"""
import jax
import pytest

from tinysql_tpu.session.session import new_session

pytestmark = pytest.mark.skipif(len(jax.devices()) < 2,
                                reason="needs a multi-device mesh")


@pytest.fixture
def tk():
    s = new_session()
    s.execute("create database test")
    s.execute("set @@tidb_tpu_min_rows = 0")
    s.execute("use test")
    s.execute("create table t (a int primary key, b int, c varchar(8), "
              "d double)")
    import random
    random.seed(11)
    rows = []
    for i in range(1, 2049):
        b = random.choice([None, 1, 2, 3, 4])
        c = random.choice(["'x'", "'y'", "'z'", "null"])
        d = round(random.uniform(-7, 7), 3)
        rows.append(f"({i}, {b if b is not None else 'null'}, {c}, {d})")
    s.execute("insert into t values " + ", ".join(rows))
    s.query("select * from t")  # hydrate the replica
    return s


QUERIES = [
    "select c, count(*), count(b), sum(d), min(d), max(d), avg(d) "
    "from t group by c order by c",
    "select b, c, count(*), sum(d * 2 - 1) from t where d > 0 "
    "group by b, c order by b, c",
    "select b, min(a), max(a) from t group by b order by b",
]


def _canon(rows):
    return [[f"{v:.9g}" if isinstance(v, float) else v for v in r]
            for r in rows]


def test_sharded_agg_matches_single_device(tk):
    for q in QUERIES:
        tk.execute("set @@tidb_mesh_parallel = 0")
        single = tk.query(q).rows
        tk.execute("set @@tidb_mesh_parallel = 1")
        sharded = tk.query(q).rows
        assert _canon(sharded) == _canon(single), q
    tk.execute("set @@tidb_mesh_parallel = 0")


def test_sharded_agg_matches_cpu_tier(tk):
    tk.execute("set @@tidb_mesh_parallel = 1")
    for q in QUERIES:
        tk.execute("set @@tidb_use_tpu = 1")
        sharded = tk.query(q).rows
        tk.execute("set @@tidb_use_tpu = 0")
        cpu = tk.query(q).rows
        assert _canon(sharded) == _canon(cpu), q
    tk.execute("set @@tidb_use_tpu = 1")
    tk.execute("set @@tidb_mesh_parallel = 0")


@pytest.fixture
def join_tk():
    import numpy as np
    from tinysql_tpu.columnar.store import bulk_load
    s = new_session()
    s.execute("create database jm")
    s.execute("use jm")
    s.execute("set @@tidb_tpu_min_rows = 0")
    s.execute("set @@tidb_devpipe = 1")
    rng = np.random.default_rng(7)
    n = 4096
    s.execute("create table big (a bigint primary key, fk bigint, x double)")
    info = s.infoschema().table_by_name("jm", "big")
    bulk_load(s.storage, info,
              {"a": np.arange(1, n + 1, dtype=np.int64),
               "fk": rng.integers(1, 200, n).astype(np.int64),
               "x": rng.random(n) * 10})
    s.execute("create table dim (k bigint primary key, v bigint)")
    info = s.infoschema().table_by_name("jm", "dim")
    bulk_load(s.storage, info,
              {"k": np.arange(1, 151, dtype=np.int64),
               "v": rng.integers(0, 50, 150).astype(np.int64)})
    return s


JOIN_QUERIES = [
    # probe side (big) shards over the mesh; dim broadcast-builds
    "select big.a, dim.v from big join dim on big.fk = dim.k "
    "where big.x < 5 order by big.a limit 20",
    "select dim.v, count(*), sum(big.x) from big join dim "
    "on big.fk = dim.k group by dim.v order by dim.v",
    "select big.a, dim.v from big left join dim on big.fk = dim.k "
    "order by big.a limit 1000, 15",
]


def test_sharded_join_matches_single_device(join_tk):
    """SQL-reachable multi-chip JOIN (SURVEY §2.11 P4): the devpipe join
    kernel runs under shard_map with the probe side partitioned over the
    mesh and the build table broadcast."""
    from tinysql_tpu.executor import devpipe
    for q in JOIN_QUERIES:
        join_tk.execute("set @@tidb_mesh_parallel = 0")
        single = join_tk.query(q).rows
        join_tk.execute("set @@tidb_mesh_parallel = 1")
        sharded = join_tk.query(q).rows
        assert _canon(sharded) == _canon(single), q
    join_tk.execute("set @@tidb_mesh_parallel = 0")


def test_sharded_join_matches_cpu_tier(join_tk):
    join_tk.execute("set @@tidb_mesh_parallel = 1")
    q = JOIN_QUERIES[1]
    sharded = join_tk.query(q).rows
    join_tk.execute("set @@tidb_use_tpu = 0")
    cpu = join_tk.query(q).rows
    assert _canon(sharded) == _canon(cpu)


def test_shuffle_join_partitioned_build(join_tk):
    """Partitioned (shuffle) build side (VERDICT r3 #3): with the
    broadcast budget forced to zero, BOTH sides hash-repartition over the
    mesh via all_to_all and each shard joins only its partition — results
    must match single-device and the CPU tier row-for-row."""
    from tinysql_tpu.executor import devpipe
    for q in JOIN_QUERIES:
        join_tk.execute("set @@tidb_mesh_parallel = 0")
        join_tk.execute("set @@tidb_use_tpu = 0")
        cpu = join_tk.query(q).rows
        join_tk.execute("set @@tidb_use_tpu = 1")
        single = join_tk.query(q).rows
        join_tk.execute("set @@tidb_mesh_parallel = 1")
        join_tk.execute("set @@tidb_broadcast_build_max_rows = 0")
        sharded = join_tk.query(q).rows
        join_tk.execute("set @@tidb_broadcast_build_max_rows = 1048576")
        assert _canon(sharded) == _canon(single), q
        assert _canon(sharded) == _canon(cpu), q
    join_tk.execute("set @@tidb_mesh_parallel = 0")
    shuf = [k for k in devpipe.COMPILED_NODE_KEYS if k[0] == "joinshuf"]
    assert shuf, "shuffle join kernel never compiled"


def test_shuffle_vs_broadcast_cost_gate(join_tk):
    """The broadcast budget sysvar picks the strategy: a build side under
    the threshold broadcasts (no joinshuf program for that shape)."""
    from tinysql_tpu.executor import devpipe
    q = ("select big.a, dim.v from big join dim on big.fk = dim.k "
         "where big.x >= 5 order by big.a limit 7")
    join_tk.execute("set @@tidb_mesh_parallel = 1")
    join_tk.execute("set @@tidb_broadcast_build_max_rows = 1048576")
    before = {k for k in devpipe.COMPILED_NODE_KEYS if k[0] == "joinshuf"}
    join_tk.execute("set @@tidb_mesh_parallel = 0")
    single = join_tk.query(q).rows
    join_tk.execute("set @@tidb_mesh_parallel = 1")
    sharded = join_tk.query(q).rows
    after = {k for k in devpipe.COMPILED_NODE_KEYS if k[0] == "joinshuf"}
    assert _canon(sharded) == _canon(single)
    assert before == after, "small build side must broadcast, not shuffle"
    join_tk.execute("set @@tidb_mesh_parallel = 0")


def test_mesh_csr_nonunique_join(join_tk):
    """Non-unique (duplicate-key) joins shard the probe side over the
    mesh with the CSR structures broadcast; per-shard expansion buckets
    come from host-exact per-shard bounds."""
    import numpy as np
    from tinysql_tpu.columnar.store import bulk_load
    from tinysql_tpu.executor import devpipe
    rng = np.random.default_rng(23)
    join_tk.execute("create table dup (id bigint primary key, k bigint, "
                    "w double)")
    info = join_tk.infoschema().table_by_name("jm", "dup")
    bulk_load(join_tk.storage, info,
              {"id": np.arange(1, 161, dtype=np.int64),
               "k": np.tile(np.arange(1, 41, dtype=np.int64), 4),
               "w": rng.random(160) * 5})
    qs = ["select big.a, dup.w from big join dup on big.fk = dup.k "
          "where big.x < 5 order by big.a, dup.w limit 50",
          "select big.a, dup.w from big left join dup on big.fk = dup.k "
          "order by big.a, dup.w limit 50",
          "select dup.k, count(*), sum(big.x) from big join dup "
          "on big.fk = dup.k group by dup.k order by dup.k"]
    for q in qs:
        join_tk.execute("set @@tidb_mesh_parallel = 0")
        single = join_tk.query(q).rows
        join_tk.execute("set @@tidb_mesh_parallel = 1")
        sharded = join_tk.query(q).rows
        assert _canon(sharded) == _canon(single), q
    join_tk.execute("set @@tidb_mesh_parallel = 0")
    assert any(k[0] == "joinm" and k[-1] > 1
               for k in devpipe.COMPILED_NODE_KEYS), \
        "sharded CSR join never compiled"


def test_mesh_csr_skew_retries_unsharded(join_tk, monkeypatch):
    """A probe whose matches cluster in one shard can blow the per-shard
    expansion bound while the GLOBAL bound still fits: the join must
    retry unsharded on the device, not fall off the pipeline."""
    import numpy as np
    from tinysql_tpu.columnar.store import bulk_load
    from tinysql_tpu.executor import devpipe
    rng = np.random.default_rng(29)
    join_tk.execute("create table sk (id bigint primary key, k bigint, "
                    "w double)")
    info = join_tk.infoschema().table_by_name("jm", "sk")
    # key 1 has 3 duplicates; keys 2..40 have none
    bulk_load(join_tk.storage, info,
              {"id": np.arange(1, 4, dtype=np.int64),
               "k": np.ones(3, dtype=np.int64),
               "w": rng.random(3)})
    join_tk.execute("create table pr (a bigint primary key, fk bigint)")
    info = join_tk.infoschema().table_by_name("jm", "pr")
    fk = np.full(1024, 999, dtype=np.int64)   # matches nothing...
    fk[:128] = 1                              # ...except the first shard
    bulk_load(join_tk.storage, info,
              {"a": np.arange(1, 1025, dtype=np.int64), "fk": fk})
    # per-shard bound = 128*3=384 -> bucket 512; 512*8 > 2048 = MAX_EXPAND
    # but the global bound (bucket 512) fits
    monkeypatch.setattr(devpipe, "MAX_EXPAND", 2048)
    q = ("select pr.a, sk.w from pr join sk on pr.fk = sk.k "
         "order by pr.a, sk.w")
    join_tk.execute("set @@tidb_mesh_parallel = 0")
    single = join_tk.query(q).rows
    join_tk.execute("set @@tidb_mesh_parallel = 1")
    sharded = join_tk.query(q).rows
    join_tk.execute("set @@tidb_mesh_parallel = 0")
    assert _canon(sharded) == _canon(single)
    assert len(single) == 128 * 3


def test_mesh_topn_distributed(join_tk):
    """Distributed TopN (reference: mocktikv/topn.go per-region TopN +
    task.go:392-452 root merge): per-shard top-(offset+count) candidates,
    all_gather over the mesh axis, replicated merge.  Tie rows and NULL
    sort keys must come back bit-identical to the single-device stable
    sort (global-row-index tiebreak)."""
    import numpy as np
    from tinysql_tpu.columnar.store import bulk_load
    from tinysql_tpu.executor import devpipe
    rng = np.random.default_rng(31)
    join_tk.execute("create table tn (id bigint primary key, g bigint, "
                    "s double)")
    info = join_tk.infoschema().table_by_name("jm", "tn")
    n = 2048
    g = rng.integers(0, 5, n).astype(np.int64)  # heavy ties
    s_vals = np.round(rng.random(n) * 3, 1)
    bulk_load(join_tk.storage, info,
              {"id": np.arange(1, n + 1, dtype=np.int64),
               "g": g, "s": s_vals})
    qs = [
        "select id, g, s from tn order by g, s limit 25",        # ties
        # same shape/flags, different sort columns: must NOT collide in
        # the jit cache with the query above (key identity in pb.key)
        "select id, g, s from tn order by s, g limit 25",
        "select id, g from tn order by g desc limit 100, 10",    # offset
        "select tn.id, dim.v from tn join dim on tn.g = dim.k "
        "order by dim.v, tn.id limit 12",                        # above join
        "select g, sum(s) from tn group by g order by sum(s) desc limit 3",
    ]
    before = {k for k in devpipe.COMPILED_NODE_KEYS
              if k and k[0] == "order_mesh"}
    for q in qs:
        join_tk.execute("set @@tidb_mesh_parallel = 0")
        single = join_tk.query(q).rows
        join_tk.execute("set @@tidb_mesh_parallel = 1")
        sharded = join_tk.query(q).rows
        if "sum(" in q:
            # sharded partial sums reassociate float addition; compare
            # at 9 significant digits like the agg battery
            assert _canon(sharded) == _canon(single), q
        else:
            assert sharded == single, q  # bit-identical incl. tie order
    join_tk.execute("set @@tidb_mesh_parallel = 0")
    after = {k for k in devpipe.COMPILED_NODE_KEYS
             if k and k[0] == "order_mesh"}
    assert after - before, "distributed TopN kernel never compiled"


def test_mesh_join_strategy_cost_based(join_tk, monkeypatch):
    """Broadcast-vs-shuffle is a PLANNER cost decision (estRows x width
    x mesh size — the task.go:146 GetCost pattern), not a knob: a small
    build side broadcasts, a build side comparable to the probe side
    shuffles, and EXPLAIN surfaces the choice (golden plan shape).  The
    tidb_broadcast_build_max_rows knob still wins when set away from its
    default."""
    join_tk.execute("set @@tidb_mesh_parallel = 1")

    def plan_line(sql, op="HashJoin"):
        rows = join_tk.query("explain " + sql).rows
        return next(r for r in rows if op in r[0])

    # small dim build (150 est rows) against the 4096-row probe:
    # broadcast_bytes = rb*wb*8 << shuffle volume -> broadcast
    small = plan_line("select big.a, dim.v from big join dim "
                      "on big.fk = dim.k")
    assert "mesh:broadcast" in small[3], small

    # self-join: build side as big as the probe side -> replicating it
    # 8x costs more than one all_to_all pass -> shuffle
    big = plan_line("select t1.a from big t1 join big t2 on t1.fk = t2.a")
    assert "mesh:shuffle" in big[3], big

    # left-unique inner join: the EXECUTOR builds on the LEFT (unique
    # dim), and the cost model must price that side — tiny unique build
    # broadcasts even though the right child is the big table
    lu = plan_line("select dim.v, big.a from dim join big "
                   "on dim.k = big.fk")
    assert "mesh:broadcast" in lu[3], lu

    # execution still matches single-device under the cost-based choice
    q = ("select big.a, dim.v from big join dim on big.fk = dim.k "
         "where big.x < 5 order by big.a limit 20")
    sharded = join_tk.query(q).rows
    join_tk.execute("set @@tidb_mesh_parallel = 0")
    single = join_tk.query(q).rows
    assert sharded == single

    # knob override: forcing the budget to 0 turns the broadcast-shaped
    # join into a shuffle at EXECUTION time regardless of plan strategy
    join_tk.execute("set @@tidb_mesh_parallel = 1")
    join_tk.execute("set @@tidb_broadcast_build_max_rows = 0")
    from tinysql_tpu.executor import devpipe
    calls = []
    orig = devpipe._JoinNode._prepare_unique_shuffle

    def spy(self, pb, btv, ptv, mesh):
        calls.append(getattr(self.plan, "mesh_strategy", None))
        return orig(self, pb, btv, ptv, mesh)
    monkeypatch.setattr(devpipe._JoinNode, "_prepare_unique_shuffle", spy)
    forced = join_tk.query("select big.a, dim.v from big join dim "
                           "on big.fk = dim.k where big.x >= 9 "
                           "order by big.a limit 5").rows
    # the knob forced the shuffle path even though the PLAN said broadcast
    assert calls and calls[0] == "broadcast", calls
    join_tk.execute("set @@tidb_broadcast_build_max_rows = 1048576")
    join_tk.execute("set @@tidb_mesh_parallel = 0")
    single = join_tk.query("select big.a, dim.v from big join dim "
                           "on big.fk = dim.k where big.x >= 9 "
                           "order by big.a limit 5").rows
    assert forced == single


def test_a_readers_side_is_priced_by_its_scans_rows(join_tk):
    """A table reader's filters are a validity mask over the replica's
    lanes: a broadcast copies, and an exchange moves, every row of the
    table whatever the filters keep, so both sides of the cost compare
    count a reader (under selections too) by its scan's rows and not by
    the estimate after its filters.  A side that is no reader keeps its
    estimate."""
    from tinysql_tpu.parallel import dist
    from tinysql_tpu.parser import parse
    from tinysql_tpu.planner import device
    from tinysql_tpu.planner.builder import PlanBuilder

    def join_of(sql):
        s = join_tk
        s.execute("set @@tidb_mesh_parallel = 1")
        try:
            p = s._optimize(PlanBuilder(s).build_select(parse(sql)[0]), True)
        finally:
            s._pinned_is = None
            s.execute("set @@tidb_mesh_parallel = 0")
        while p.op_name() != "HashJoin":
            p = p.children[0]
        return p
    n = len(jax.devices())
    w = dist.COST_COLUMN_BYTES
    plain = join_of("select big.a, dim.v from big join dim "
                    "on big.fk = dim.k")
    masked = join_of("select big.a, dim.v from big join dim "
                     "on big.fk = dim.k where big.x < 1 and dim.v < 3")
    probe, build = masked.children
    assert probe.stats_row_count < 4096 and build.stats_row_count < 150
    assert device._lane_rows(probe) == 4096.0
    assert device._lane_rows(build) == 150.0
    assert masked.mesh_cost["broadcast_bytes"] \
        == plain.mesh_cost["broadcast_bytes"] == 150 * 2 * w * n
    assert masked.mesh_cost["shuffle_bytes"] \
        == 150 * 2 * w + 4096 * len(probe.schema.columns) * w
    # an aggregate's side is no lane of a replica: its estimate stands
    agg = join_of("select big.a, t.c from big join (select fk, count(*) c "
                  "from big group by fk) t on big.a = t.fk")
    side = next(c for c in agg.children if c.op_name() != "TableReader")
    assert device._lane_rows(side) == side.stats_row_count


# ---- the mesh deployment: the replica laid out over the mesh ---------------
# TPC-H's Q1 / Q3 / Q6 under tidb_mesh_parallel = 1 with the fused
# pipeline forced: the replica's
# lanes are placed row-sharded over the mesh (parallel/dist.py rows /
# whole), each statement is ONE dispatch, and a warm one moves nothing.

MESH_SF = 0.02
MESH_QUERIES = ("Q1", "Q3", "Q6")


@pytest.fixture(scope="module")
def tpch_mesh():
    """(session, sqlite mirror, queries): TPC-H at SF 0.02, the planner's
    rows-per-shard floor lowered so that Q6's small estimate still fans
    out over 8 shards."""
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.parallel import dist
    prev_floor = dist.MIN_SHARD_ROWS
    dist.MIN_SHARD_ROWS = 16
    s = new_session()
    data = tpch.generate(MESH_SF)
    tpch.load(s, data=data)
    s.execute("set @@tidb_devpipe = 1")
    # low enough for Q6's estimate, high enough to keep the one-row
    # projection above its aggregate on the host (as the default does)
    s.execute("set @@tidb_tpu_min_rows = 64")
    yield s, tpch.sqlite_mirror(data), tpch.QUERIES
    dist.MIN_SHARD_ROWS = prev_floor


def _mesh_of(monkeypatch, n):
    """Sessions that ask for the mesh get one of the first n devices."""
    from tinysql_tpu.parallel import dist
    monkeypatch.setattr(
        dist, "session_mesh",
        lambda sv: dist.sized_mesh(n) if sv.get("tidb_mesh_parallel")
        else None)


def _rows_close(got, want, rel=1e-9):
    """Same rows in the same order: doubles within ``rel``, the rest
    equal (a digit-rounding canon flips at a rounding boundary)."""
    if len(got) != len(want):
        return False
    for a, b in zip(got, want):
        if len(a) != len(b):
            return False
        for x, y in zip(a, b):
            if isinstance(y, float) or isinstance(x, float):
                if abs(float(x) - float(y)) > rel * max(abs(float(y)), 1.0):
                    return False
            elif str(x) != str(y):
                return False
    return True


def _stats_of_call(call):
    from tinysql_tpu.ops import kernels
    before = kernels.stats_snapshot()
    out = call()
    return out, kernels.stats_delta(before)


def _stats_of(s, sql):
    return _stats_of_call(lambda: s.query(sql).rows)


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("name", MESH_QUERIES)
def test_mesh_statement_equals_one_device_and_sqlite(tpch_mesh, monkeypatch,
                                                     name, n):
    s, mirror, queries = tpch_mesh
    _mesh_of(monkeypatch, n)
    s.execute("set @@tidb_mesh_parallel = 0")
    single = s.query(queries[name]).rows
    s.execute("set @@tidb_mesh_parallel = 1")
    sharded, delta = _stats_of(s, queries[name])
    s.execute("set @@tidb_mesh_parallel = 0")
    want = [list(r) for r in mirror.execute(queries[name]).fetchall()]
    assert want and _rows_close(sharded, single, rel=1e-12)
    assert _rows_close(sharded, want)
    assert delta["dispatches"] == 1 and delta["host_dispatches"] == 0


def _device_lanes(s):
    """{memo key: device array} of every replica of the session's store."""
    from tinysql_tpu.columnar.store import store_of
    return {k: v for tbl in store_of(s.storage).tables_snapshot()
            for k, v in tbl.cache.items() if isinstance(v, jax.Array)}


def test_replica_lanes_are_laid_out_over_the_mesh(tpch_mesh):
    """After the first mesh reads every lane memoized under ``rows`` is
    cut into equal contiguous parts, one a device, and every one under
    ``whole`` (Q3's customer build side, the key->row tables, the group
    keys) is whole on every device."""
    s, _mirror, queries = tpch_mesh
    n = len(jax.devices())
    s.execute("set @@tidb_mesh_parallel = 1")
    for name in MESH_QUERIES:
        s.query(queries[name])
    s.execute("set @@tidb_mesh_parallel = 0")
    lanes = _device_lanes(s)
    rows = {k: v for k, v in lanes.items() if k[-2:] == ("rows", n)}
    whole = {k: v for k, v in lanes.items() if k[-2:] == ("whole", n)}
    kinds = {k[0] for k in rows}
    assert {"devv", "devn", "devcodes", "gi_rowgid",
            "gi_shard_ends"} <= kinds, kinds
    # lineitem is stored in l_orderkey order: Q3's sorted aggregate reads
    # the scan's own lanes, no index-order lane and no permuted copy
    assert not kinds & {"gi_shard_order", "gi_shard_rows"}, kinds
    assert not [k for k in lanes if "by" in k], lanes.keys()
    for key, arr in rows.items():
        shards = arr.addressable_shards
        assert len(shards) == n, key
        assert {sh.data.shape for sh in shards} == \
            {(arr.shape[0] // n,)}, key
        assert len({sh.device for sh in shards}) == n, key
    assert {"devv", "postable_dev", "gi_gkeys"} <= {k[0] for k in whole}
    # Q3's join into the group table is aligned (orders and lineitem are
    # both stored by the order key): it reads the merged table in place
    # and the group index's key -> row table never goes up
    assert "gi_postable_dev" not in {k[0] for k in lanes}
    for key, arr in whole.items():
        assert arr.sharding.is_fully_replicated, key
        assert len(arr.addressable_shards) == n, key
        assert all(sh.data.shape == arr.shape
                   for sh in arr.addressable_shards), key


@pytest.mark.parametrize("n", [1, 2, 8])
def test_q3_program_gathers_no_lineitem_lane(tpch_mesh, monkeypatch, n):
    """Q3's lowered program, on one device and over a mesh: no gather
    whose result has the shape of a ``lineitem`` lane (a shard's part of
    one), and its partial aggregate counted as clustered.  (n = 4 would
    make a shard's part of a lane as long as the [groups] boundary
    table, which IS gathered.)"""
    from test_aggindex_order import gather_shapes
    from tinysql_tpu.columnar.store import store_of
    from tinysql_tpu.ops import kernels
    s, _mirror, queries = tpch_mesh
    _mesh_of(monkeypatch, n)
    info = s.infoschema().table_by_name("tpch", "lineitem")
    nb = kernels.bucket(store_of(s.storage).get(info.id).n_rows)
    s.execute(f"set @@tidb_mesh_parallel = {int(n > 1)}")
    try:
        (shapes, sums), delta = _stats_of_call(
            lambda: gather_shapes(s, queries["Q3"], monkeypatch))
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
    assert delta["agg_sorted"] == 1 and delta["agg_clustered"] == 1
    # presence and the revenue: two prefix sums over a lane (each scans
    # its block totals with a shorter one); the revenue's count is
    # presence, its argument being NULL on no row
    assert sums.count((nb // n,)) == 2, sums
    assert shapes and (nb,) not in shapes and (nb // n,) not in shapes, \
        shapes


@pytest.mark.parametrize("name", MESH_QUERIES)
def test_warm_mesh_statement_moves_nothing(tpch_mesh, name):
    """The second statement: one dispatch over the whole mesh, no input
    laid out anew, no program built, nothing uploaded but
    the parameters."""
    s, _mirror, queries = tpch_mesh
    s.execute("set @@tidb_mesh_parallel = 1")
    s.query(queries[name])
    _rows, delta = _stats_of(s, queries[name])
    s.execute("set @@tidb_mesh_parallel = 0")
    assert delta["reshard_bytes"] == 0
    assert delta["host_dispatches"] == 0
    assert delta["progcache_misses"] == 0
    assert delta["dispatches"] == 1
    assert delta["mesh_dispatches"] == 1
    assert delta["h2d_bytes"] < 1024
    assert delta["mesh_resident_bytes_max"] >= \
        delta["mesh_resident_bytes_min"] > 0


def test_lane_found_in_another_layout_is_moved_and_counted(tpch_mesh):
    """``dist.settle``: an input that lies otherwise than its program
    asks is moved, and its bytes are counted."""
    import numpy as np
    from tinysql_tpu.ops import kernels
    from tinysql_tpu.parallel import dist
    mesh = dist.make_mesh()
    lane = kernels.h2d(np.arange(1024, dtype=np.int64), dist.whole(mesh))
    host = np.zeros(4)
    before = kernels.stats_snapshot()
    out = dist.settle([lane, host, lane],
                      [dist.rows(mesh), None, dist.whole(mesh)])
    delta = kernels.stats_delta(before)
    assert delta["reshard_bytes"] == lane.nbytes
    assert out[0].sharding.is_equivalent_to(dist.rows(mesh), 1)
    assert out[1] is host and out[2] is lane
    assert np.array_equal(np.asarray(out[0]), np.asarray(lane))


def test_one_device_session_is_untouched_by_a_mesh_session(tpch_mesh):
    """A one-device session before and after a mesh session on the same
    replica: the same program keys (no layout tag), the very same
    memoized arrays, nothing uploaded again, nothing built again."""
    from tinysql_tpu.executor import devpipe
    s, _mirror, queries = tpch_mesh

    def tagged(key):
        return any(isinstance(p, tuple) and tagged(p) for p in key) \
            or any(p in ("rows", "whole") for p in key
                   if isinstance(p, str))
    s.execute("set @@tidb_mesh_parallel = 0")
    first = {}
    for name in MESH_QUERIES:
        first[name] = s.query(queries[name]).rows
    one_dev = {k: v for k, v in _device_lanes(s).items() if not tagged(k)}
    node_keys = {k for k in devpipe.COMPILED_NODE_KEYS if not tagged(k)}
    assert {"leaf", "aggdense", "aggindex", "join", "order"} <= \
        {k[0] for k in node_keys}
    # the one-device node keys are as long as they were: nothing appended
    assert {len(k) for k in node_keys if k[0] == "leaf"} == {4}
    assert {len(k) for k in node_keys
            if k[0] in ("aggdense", "aggindex")} == {7}
    s.execute("set @@tidb_mesh_parallel = 1")
    for name in MESH_QUERIES:
        s.query(queries[name])
    s.execute("set @@tidb_mesh_parallel = 0")
    for name in MESH_QUERIES:
        rows, delta = _stats_of(s, queries[name])
        assert rows == first[name]
        assert delta["progcache_misses"] == 0, name
        assert delta["h2d_bytes"] < 1024, name
        assert delta["mesh_dispatches"] == 0 and \
            delta["reshard_bytes"] == 0, name
    after = {k: v for k, v in _device_lanes(s).items() if not tagged(k)}
    assert after.keys() == one_dev.keys()
    assert all(after[k] is one_dev[k] for k in one_dev)
    # (the mesh session added keys of its own: its joins and TopN carry
    # the mesh's size; the one-device statements above built nothing)
    assert node_keys <= devpipe.COMPILED_NODE_KEYS


@pytest.mark.parametrize("n", [2, 4, 8])
def test_dense_partial_tables_merge_to_the_unsharded_result(n):
    """The share test: per-shard partial group tables of the dense
    aggregate (_SegReduce's masked reductions over a uint8 group-id
    lane), summed — min/max merged — equal the unsharded tables: exactly
    in counts and min/max, within 1e-12 in float64 sums."""
    import numpy as np
    from tinysql_tpu.ops import kernels
    jn = kernels.jnp()
    rng = np.random.default_rng(41 + n)
    nb, ngb = 1 << 14, 16
    gid = rng.integers(0, 6, nb).astype(np.uint8)
    gid[-300:] = ngb  # padding rows match no group
    valid = rng.random(nb) < 0.7
    x = rng.random(nb) * 1e5
    k = rng.integers(-50, 50, nb).astype(np.int64)

    def tables(lo, hi):
        seg = kernels._SegReduce(jax, jn, jn.asarray(gid[lo:hi]),
                                 jn.asarray(valid[lo:hi]), ngb,
                                 unroll=True)
        v, xs, ks = (jn.asarray(a[lo:hi]) for a in (valid, x, k))
        return [np.asarray(t) for t in (
            seg.sum(v.astype(jn.int64), v), seg.sum(xs, v),
            seg.sum(ks, v), seg.minmax(xs, v, True),
            seg.minmax(ks, v, False))]
    whole = tables(0, nb)
    per = nb // n
    parts = [tables(i * per, (i + 1) * per) for i in range(n)]
    cnt, fsum, isum = (sum(p[j] for p in parts) for j in range(3))
    assert np.array_equal(cnt, whole[0]) and cnt.sum() > 0
    assert np.array_equal(isum, whole[2])
    assert np.allclose(fsum, whole[1], rtol=1e-12, atol=0)
    assert np.array_equal(np.min([p[3] for p in parts], axis=0), whole[3])
    assert np.array_equal(np.max([p[4] for p in parts], axis=0), whole[4])


@pytest.mark.parametrize("n", [2, 4, 8])
def test_group_index_cut_per_shard(n):
    """GroupIndex.shards: each shard lists its own rows in key order;
    the boundary differences at its per-group ends are its partial
    state, and the partial states sum to the whole index's groups."""
    import numpy as np
    from tinysql_tpu.executor.devpipe import GroupIndex
    rng = np.random.default_rng(5 + n)
    n_rows, per = 1000, 1024 // n
    keys = rng.integers(0, 90, n_rows).astype(np.int64)
    x = rng.integers(1, 1000, n_rows).astype(np.int64)
    gidx = GroupIndex([(keys, np.zeros(n_rows, dtype=bool))])
    order, ends, sgid, rows = gidx.shards(n, per)
    assert order.shape == sgid.shape == (n, per)
    assert ends.shape == (n, gidx.n_groups) and rows.sum() == n_rows
    total = np.zeros(gidx.n_groups, dtype=np.int64)
    for s_ in range(n):
        mine = order[s_, :rows[s_]] + s_ * per      # global rows, key order
        assert np.all(np.diff(keys[mine]) >= 0)
        # a permutation of the shard that leaves its padding in place
        assert np.array_equal(np.sort(order[s_]), np.arange(per))
        assert np.array_equal(order[s_, rows[s_]:], np.arange(rows[s_], per))
        assert np.array_equal(gidx.gkeys[sgid[s_, :rows[s_]]], keys[mine])
        assert np.all(sgid[s_, rows[s_]:] == gidx.n_groups)
        c = np.concatenate([[0], np.cumsum(x[mine])])
        hi = c[ends[s_] + 1]
        lo = c[np.concatenate([[-1], ends[s_][:-1]]) + 1]
        want = np.bincount(np.searchsorted(gidx.gkeys, keys[mine]),
                           weights=x[mine], minlength=gidx.n_groups)
        assert np.array_equal(hi - lo, want.astype(np.int64))
        total += hi - lo
    assert np.array_equal(
        total, np.bincount(np.searchsorted(gidx.gkeys, keys), weights=x,
                           minlength=gidx.n_groups).astype(np.int64))
    # stored in key order the index is clustered and every shard's order
    # is the identity: the lanes need no permuting
    assert not gidx.clustered
    by_key = GroupIndex([(np.sort(keys), np.zeros(n_rows, dtype=bool))])
    assert by_key.clustered
    assert np.array_equal(by_key.shards(n, per)[0],
                          np.tile(np.arange(per), (n, 1)))


# ---- a shard bounds only its own span of groups ------------------------------

@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("stored", ["by_key", "shuffled", "by_key_nulls"])
def test_group_index_spans_per_shard(n, stored):
    """GroupIndex.spans: the least and the greatest group id among each
    shard's rows, against a numpy min/max of the shard's group ids; and
    span_ends: the columns of ``shards``' ends from each shard's first
    group on, whose boundary differences are the shard's partial states
    of its span and sum, added into place, to the whole index's."""
    import numpy as np
    from tinysql_tpu.executor.devpipe import GroupIndex, _span_pad
    rng = np.random.default_rng(17 + n)
    n_rows, per = 1000, 1024 // n          # the last shard is part padding
    keys = rng.integers(0, 90, n_rows).astype(np.int64)
    nulls = np.zeros(n_rows, dtype=bool)
    if stored != "shuffled":
        keys = np.sort(keys)
    if stored == "by_key_nulls":
        nulls[-40:] = True                 # the NULL group sorts last
    x = rng.integers(1, 1000, n_rows).astype(np.int64)
    gidx = GroupIndex([(keys, nulls)])
    assert gidx.clustered == (stored != "shuffled")
    ng = gidx.n_groups
    gid = gidx.row_gid(np.int64)
    g_lo, g_hi = gidx.spans(n, per)
    for s_ in range(n):
        mine = gid[s_ * per:(s_ + 1) * per]
        assert (g_lo[s_], g_hi[s_]) == (mine.min(), mine.max())
    assert gidx.spans(2 * n, 1024)[1][1:].tolist() == [-1] * (2 * n - 1)
    qb = _span_pad(g_lo, g_hi, 128)
    assert qb >= (g_hi - g_lo).max() + 1
    order, ends, _sgid, rows = gidx.shards(n, per)
    cut = gidx.span_ends(n, per, g_lo, qb)
    assert cut.shape == (n, qb)
    total = np.zeros(128 + qb, dtype=np.int64)
    for s_ in range(n):
        width = min(qb, ng - g_lo[s_])
        assert np.array_equal(cut[s_, :width],
                              ends[s_, g_lo[s_]:g_lo[s_] + width])
        assert np.all(cut[s_, width:] == rows[s_] - 1)
        assert g_lo[s_] == 0 or ends[s_, g_lo[s_] - 1] == -1
        mine = order[s_, :rows[s_]] + s_ * per
        c = np.concatenate([[0], np.cumsum(x[mine])])
        hi = c[cut[s_] + 1]
        total[g_lo[s_]:g_lo[s_] + qb] += hi - np.concatenate([[0], hi[:-1]])
    assert np.array_equal(total[:ng], np.bincount(gid, weights=x,
                                                  minlength=ng))
    assert not total[ng:].any()


@pytest.mark.parametrize("dtype", ["int64", "float64"])
def test_mesh_sum_spans_equals_the_sum_of_whole_tables(dtype):
    """dist.mesh_sum_spans: each shard's run added at its start — runs
    that share an entry, a run that reaches past the table, an empty
    one — is mesh_sum of the whole tables the runs stand for."""
    import numpy as np
    from tinysql_tpu.ops import kernels
    from tinysql_tpu.parallel import dist
    jn = kernels.jnp()
    n, q, size = 4, 24, 64
    mesh = dist.sized_mesh(n)
    rng = np.random.default_rng(3)
    parts = (rng.random((n, q)) * 1e6).astype(dtype)
    parts[2] = 0                                   # a shard with no row
    starts = np.array([0, 23, 5, 60], dtype=np.int32)
    parts[3, size - 60:] = 0     # past the table a run holds padding only
    ROWS, WHOLE = dist.specs()

    def both(part, at):
        whole = jn.zeros(size + q, dtype=part.dtype)
        mine = at[kernels.jax().lax.axis_index("shard")]
        whole = kernels.jax().lax.dynamic_update_slice(whole, part, (mine,))
        return dist.mesh_sum_spans(part, at, size), \
            dist.mesh_sum(whole[:size])
    fn = dist.shard_map_unchecked(both, mesh=mesh, in_specs=(ROWS, WHOLE),
                                  out_specs=(WHOLE, WHOLE))
    spans, whole = fn(jn.asarray(parts.reshape(-1)), jn.asarray(starts))
    want = np.zeros(size + q, dtype=dtype)
    for s_ in range(n):
        want[starts[s_]:starts[s_] + q] += parts[s_]
    assert np.array_equal(np.asarray(spans), want[:size])
    assert np.allclose(np.asarray(whole), want[:size], rtol=1e-15, atol=0)
    assert np.asarray(spans)[23] == parts[0, 23] + parts[1, 0]


@pytest.mark.parametrize("n", [2, 4, 8])
def test_agg_span_cut_counts_and_moves_what_the_sum_of_tables_moved(
        tpch_mesh, monkeypatch, n):
    """``agg_span_cut``: once a fused dispatch of Q3 over the mesh
    (``lineitem`` is stored by ``l_orderkey``: a shard holds one piece of
    the orders), 0 on one device and for Q1's dense GROUP BY; on
    ``/metrics`` and in the statement's own counters.  A warm Q3 uploads
    the bytes it uploaded when every shard bounded every group (the
    shards' first groups are a replica lane, not a parameter) and lays
    nothing out anew; the rows are the same."""
    from tinysql_tpu.executor import devpipe
    from tinysql_tpu.obs import metrics
    s, _mirror, queries = tpch_mesh
    _mesh_of(monkeypatch, n)
    _rows, one = _stats_of(s, queries["Q3"])
    assert one["agg_sorted"] == 1 and one["agg_span_cut"] == 0
    with monkeypatch.context() as m:
        # the spans read as whole: the parent's program
        m.setattr(devpipe, "_span_pad", lambda g_lo, g_hi, ngb: ngb)
        _mesh_stats_of(s, queries["Q3"])
        whole_rows, whole, _ = _mesh_stats_of(s, queries["Q3"])
    # (a dispatch counts as the mesh's where the mesh is every device)
    over_all = int(n == len(jax.devices()))
    assert whole["agg_span_cut"] == 0
    assert whole["mesh_dispatches"] == over_all
    before = metrics.render_prometheus()
    _mesh_stats_of(s, queries["Q3"])
    rows, warm, _ = _mesh_stats_of(s, queries["Q3"])
    assert warm["agg_span_cut"] == 1 and warm["agg_clustered"] == 1
    assert warm["dispatches"] == 1 and warm["mesh_dispatches"] == over_all
    assert warm["progcache_misses"] == 0
    assert warm["h2d_bytes"] == whole["h2d_bytes"] < 1024
    assert warm["reshard_bytes"] == whole["reshard_bytes"] == 0
    assert _rows_close(rows, whole_rows, rel=1e-12) and rows
    _rows, q1, _ = _mesh_stats_of(s, queries["Q1"])
    assert q1["agg_dense"] == 1 and q1["agg_span_cut"] == 0

    def total(text):
        line = [ln for ln in text.splitlines()
                if ln.startswith("tinysql_agg_span_cut_total ")]
        return float(line[0].split()[1])
    assert total(metrics.render_prometheus()) == total(before) + 2
    s.execute("set @@tidb_mesh_parallel = 1")
    try:
        info = s.query("explain analyze " + queries["Q3"]).rows
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
    assert any("agg:0dense/1sorted/1clustered/1span_cut" in str(c)
               for r in info for c in r), info


def test_mesh_views_and_key_mesh_on_metrics_and_explain_analyze(
        tpch_mesh, four_devices):
    """``pipe_mesh_views`` and ``agg_key_mesh``: once a fused dispatch
    of TPC-H Q5 over the mesh (its three view builds, of them (orders
    join customer) all-gathered; the 25 nations reduced a shard at a
    time), 0 on one device; on ``/metrics`` and in ``EXPLAIN ANALYZE``
    beside the joins and the key cut."""
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.obs import metrics
    s, mirror, _queries = tpch_mesh
    sql = tpch.WORKLOAD["Q5"]
    single, one = _stats_of(s, sql)
    assert one["pipe_view_builds"] == 3
    assert one.get("pipe_mesh_views", 0) == one.get("agg_key_mesh", 0) == 0
    before = metrics.render_prometheus()
    _mesh_stats_of(s, sql)
    rows, warm, _ = _mesh_stats_of(s, sql)
    assert _rows_close(rows, single, rel=1e-12) and rows
    assert _rows_close(rows, [list(r) for r in
                              mirror.execute(sql).fetchall()])
    assert warm["dispatches"] == 1 == warm["mesh_dispatches"]
    assert warm["pipe_mesh_views"] == 3 and warm["agg_key_mesh"] == 1
    assert warm["reshard_bytes"] > 0 and warm["progcache_misses"] == 0

    def total(text, name):
        line = [ln for ln in text.splitlines()
                if ln.startswith(f"tinysql_{name}_total ")]
        return float(line[0].split()[1])
    after = metrics.render_prometheus()
    assert total(after, "pipe_mesh_views") \
        == total(before, "pipe_mesh_views") + 6
    assert total(after, "agg_key_mesh") == total(before, "agg_key_mesh") + 2
    s.execute("set @@tidb_mesh_parallel = 1")
    try:
        info = s.query("explain analyze " + sql).rows
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
    shown = [str(c) for r in info for c in r]
    assert any("joins:5/3view/3mesh" in c and "key_mesh:1" in c
               for c in shown), info


# ---- column liveness under the mesh -----------------------------------------
# A fused program gathers, carries over its TopN's all-gather and
# exchanges only the columns its consumer reads (executor/devpipe.py).

@pytest.fixture
def live_tk(join_tk):
    """``join_tk`` with a string column on each side, so that a
    projection reading one stays on the host and is the program's
    consumer, and a dimension with duplicate keys (the CSR join)."""
    import numpy as np
    from tinysql_tpu.columnar.store import bulk_load
    rng = np.random.default_rng(29)
    tags = np.array(["red", "green", "blue", "grey"], dtype=object)
    s = join_tk
    for name, cols, data in [
        ("fact", "a bigint primary key, fk bigint, x double, "
                 "tag varchar(8)",
         {"a": np.arange(1, 4097, dtype=np.int64),
          "fk": rng.integers(1, 200, 4096).astype(np.int64),
          "x": rng.random(4096) * 10,
          "tag": tags[rng.integers(0, 4, 4096)]}),
        ("dimn", "k bigint primary key, v bigint, name varchar(8)",
         {"k": np.arange(1, 151, dtype=np.int64),
          "v": rng.integers(0, 50, 150).astype(np.int64),
          "name": np.array([f"n{i:03d}" for i in range(150)],
                           dtype=object)}),
        ("dupn", "id bigint primary key, k bigint, label varchar(8)",
         {"id": np.arange(1, 161, dtype=np.int64),
          "k": np.tile(np.arange(1, 41, dtype=np.int64), 4),
          "label": tags[rng.integers(0, 4, 160)]})]:
        s.execute(f"create table {name} ({cols})")
        bulk_load(s.storage, s.infoschema().table_by_name("jm", name), data)
    return s


#: name -> (statement, root slots dead, forced to partition)
MESH_LIVE_CASES = {
    "build_only": ("select dimn.name, dimn.v from fact join dimn "
                   "on fact.fk = dimn.k where fact.x < 5", 3, False),
    "probe_only": ("select fact.a, fact.tag from fact join dimn "
                   "on fact.fk = dimn.k where dimn.v > 10", 3, False),
    "left_ext": ("select fact.a, fact.tag, dimn.v from fact left join dimn "
                 "on fact.fk = dimn.k", 2, False),
    "semi": ("select fact.a, fact.tag from fact where fact.fk in "
             "(select k from dimn where v > 25)", 1, False),
    "csr": ("select fact.a, dupn.label from fact join dupn "
            "on fact.fk = dupn.k where fact.x < 5", 3, False),
    "topn": ("select fact.tag, dimn.name from fact join dimn "
             "on fact.fk = dimn.k order by fact.x desc, fact.a limit 9",
             2, False),
    "shuffle": ("select fact.tag, dimn.name from fact join dimn "
                "on fact.fk = dimn.k where fact.x >= 9", 3, True),
    "shuffle_left": ("select fact.a, fact.tag, dimn.v from fact "
                     "left join dimn on fact.fk = dimn.k", 2, True),
    "star": ("select * from fact join dimn on fact.fk = dimn.k "
             "where fact.x < 1", 0, False),
}


@pytest.mark.parametrize("case", sorted(MESH_LIVE_CASES))
def test_mesh_liveness_rows_equal_one_device_and_cpu(live_tk, case):
    sql, dead, partition = MESH_LIVE_CASES[case]
    s = live_tk
    s.execute("set @@tidb_use_tpu = 0")
    cpu = s.query(sql).rows
    s.execute("set @@tidb_use_tpu = 1")
    single = s.query(sql).rows
    sharded, delta, moved = _mesh_stats_of(s, sql, partition)
    assert cpu and sorted(map(str, _canon(sharded))) \
        == sorted(map(str, _canon(single))) \
        == sorted(map(str, _canon(cpu)))
    assert delta["pipe_dead_cols"] == dead
    assert delta["dispatches"] == delta["mesh_dispatches"] == 1
    # (left alone, the planner's costs choose; some of these partition)
    assert moved > 0 or not partition


def test_partitioned_join_exchanges_only_live_columns(live_tk):
    """The exchange's volume follows the live set: a consumer that reads
    two of the five columns moves the two and the keys."""
    s = live_tk
    on = "from fact join dimn on fact.fk = dimn.k where fact.fk < 150"
    # one pruned schema (fk, tag | k, name): four lanes and a validity
    # lane a side when every slot is read, the key and one more when the
    # consumer reads the two strings
    _rows, some, moved_some = _mesh_stats_of(
        s, f"select fact.tag, dimn.name {on}", True)
    _rows, every, moved_every = _mesh_stats_of(
        s, f"select fact.fk, fact.tag, dimn.k, dimn.name {on}", True)
    assert some["pipe_dead_cols"] == 2 and every["pipe_dead_cols"] == 0
    assert 0 < moved_some == moved_every
    # the same join under a consumer of four columns of six moves more
    _rows, _delta, moved_more = _mesh_stats_of(
        s, "select fact.tag, fact.x, dimn.name, dimn.v from fact "
           f"join dimn on fact.fk = dimn.k where fact.fk < 150", True)
    assert moved_more > moved_some


def _mesh_stats_of(s, sql, partition=False):
    """(rows, kernels' counters' growth, bytes sized for an exchange) of
    ``sql`` over the mesh, its join forced to partition or left to
    broadcast."""
    from tinysql_tpu.ops import shardops
    moved = shardops.stats_snapshot()["shard_exchange_bytes"]
    s.execute("set @@tidb_mesh_parallel = 1")
    if partition:
        s.execute("set @@tidb_broadcast_build_max_rows = 0")
    try:
        rows, delta = _stats_of(s, sql)
    finally:
        s.execute("set @@tidb_broadcast_build_max_rows = 1048576")
        s.execute("set @@tidb_mesh_parallel = 0")
    return rows, delta, \
        shardops.stats_snapshot()["shard_exchange_bytes"] - moved


@pytest.mark.parametrize("n", [2, 4])
def test_q3_over_the_mesh_leaves_four_slots_dead(tpch_mesh, monkeypatch, n):
    s, _mirror, queries = tpch_mesh
    _mesh_of(monkeypatch, n)
    s.execute("set @@tidb_mesh_parallel = 1")
    try:
        _rows, q3 = _stats_of(s, queries["Q3"])
        _rows, q1 = _stats_of(s, queries["Q1"])
        _rows, q6 = _stats_of(s, queries["Q6"])
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
    assert q3["pipe_dead_cols"] == 4
    assert q1["pipe_dead_cols"] == 0 and q6["pipe_dead_cols"] == 0


# ---- NULL-freedom under the mesh ---------------------------------------------
# A view says which slots hold no NULL on a valid row; a join gathers no
# such null lane, broadcast or partitioned (executor/devpipe.py).

@pytest.fixture
def null_tk(live_tk):
    """``live_tk`` with ``dimx``: a unique build side whose ``x`` holds
    NULLs on rows a filter on ``v`` keeps, ``y`` only on rows it drops,
    ``z`` none."""
    import numpy as np
    from tinysql_tpu.columnar.store import bulk_load
    rng = np.random.default_rng(31)
    v = rng.integers(0, 50, 150).astype(np.int64)
    s = live_tk
    s.execute("create table dimx (k bigint primary key, v bigint, "
              "x bigint, y bigint, z bigint)")
    bulk_load(s.storage, s.infoschema().table_by_name("jm", "dimx"),
              {"k": np.arange(1, 151, dtype=np.int64), "v": v,
               "x": rng.integers(0, 9, 150).astype(np.int64),
               "y": rng.integers(0, 9, 150).astype(np.int64),
               "z": rng.integers(0, 9, 150).astype(np.int64)},
              {"x": rng.random(150) < 0.3, "y": v <= 25})
    return s


#: name -> (statement, null lanes left out (over the mesh, on one device),
#: forced to partition)
MESH_NULL_CASES = {
    # x and y keep their lanes (NULL on a valid row; on a filtered-out
    # row alone: the leaf sees the column's mask, not the filter), z's
    # and (under the mesh, where the join may partition) the key's go
    "inner": ("select fact.a, fact.tag, dimx.x, dimx.y, dimx.z from fact "
              "join dimx on fact.fk = dimx.k where dimx.v > 25", (1, 1), False),
    "left": ("select fact.a, fact.tag, dimx.x, dimx.y, dimx.z from fact "
             "left join dimx on fact.fk = dimx.k and dimx.v > 25", (1, 1),
             False),
    "inner_partitioned": (
        "select fact.a, fact.tag, dimx.x, dimx.y, dimx.z from fact "
        "join dimx on fact.fk = dimx.k where dimx.v > 25", (1, 1), True),
    "left_partitioned": (
        "select fact.a, fact.tag, dimx.x, dimx.y, dimx.z from fact "
        "left join dimx on fact.fk = dimx.k and dimx.v > 25", (1, 1),
        True),
    # two joins off one probe (Q3's shape): dimn's v off the lower, z
    # off the upper, x with its lane
    "chain": ("select fact.a, fact.tag, dimn.v, dimx.x, dimx.z from fact "
              "join dimn on fact.fk = dimn.k join dimx on fact.a = dimx.k",
              (2, 2), False),
}


@pytest.mark.parametrize("case", sorted(MESH_NULL_CASES))
def test_mesh_join_over_a_build_column_with_nulls(null_tk, case):
    sql, skipped, partition = MESH_NULL_CASES[case]
    s = null_tk
    s.execute("set @@tidb_use_tpu = 0")
    cpu = s.query(sql).rows
    s.execute("set @@tidb_use_tpu = 1")
    single, one = _stats_of(s, sql)
    sharded, delta, moved = _mesh_stats_of(s, sql, partition)
    assert cpu and sorted(map(str, _canon(sharded))) \
        == sorted(map(str, _canon(single))) \
        == sorted(map(str, _canon(cpu)))
    assert any(None in r for r in cpu) and any(None not in r for r in cpu)
    assert delta["dispatches"] == delta["mesh_dispatches"] == 1
    assert moved > 0 or not partition
    assert (delta["pipe_const_nulls"], one["pipe_const_nulls"]) == skipped


@pytest.mark.parametrize("n", [0, 2, 4])
@pytest.mark.parametrize("name, aligned", [("Q1", 0), ("Q3", 1), ("Q6", 0)])
def test_aligned_join_answers_bit_for_bit_as_the_gathering_one(
        tpch_mesh, monkeypatch, name, aligned, n):
    """ISSUE 40: ``orders`` and ``lineitem`` are stored by the order key
    and every order has lines, so Q3's join into the group table reads
    it in place (over ``n`` devices each its own row range of the merged
    table; 0: one device).  The rows are the gathering join's, bit for
    bit, and nothing crosses the mesh for it; Q1 and Q6 join nothing."""
    from tinysql_tpu.executor import devpipe
    s, _mirror, queries = tpch_mesh
    sql = queries[name]
    if n:
        _mesh_of(monkeypatch, n)
        s.execute("set @@tidb_mesh_parallel = 1")
    try:
        s.query(sql)
        rows, d = _stats_of(s, sql)
        with monkeypatch.context() as m:
            m.setattr(devpipe._JoinNode, "_aligned",
                      lambda self, ptv, btv: False)
            s.query(sql)
            off, d_off = _stats_of(s, sql)
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
    assert rows and rows == off
    assert d.get("pipe_joins_aligned", 0) == aligned
    assert d_off.get("pipe_joins_aligned", 0) == 0
    assert d["dispatches"] == d_off["dispatches"]
    assert d.get("reshard_bytes", 0) == d_off.get("reshard_bytes", 0) == 0
    assert d.get("progcache_misses", 0) == 0


@pytest.mark.parametrize("n, folded", [(0, 1), (2, 1), (4, 1), (8, 0)])
@pytest.mark.parametrize("name", MESH_QUERIES)
def test_folded_join_answers_bit_for_bit_as_the_unfolded_one(
        tpch_mesh, monkeypatch, name, n, folded):
    """Q3's inner join probes ``customer``'s table of 3,000 keys with
    ``orders``' 2^15 rows.  On one device, two and four the table holds
    at most half a device's rows, and each device folds the view's
    validity into its own whole copy of the table; on eight (4,096 rows
    a device) the join reads the validity at its rows as before.  The
    rows are the unfolded program's, bit for bit, and the mirror's, and
    nothing more crosses the mesh; Q1 and Q6 join nothing."""
    from tinysql_tpu.executor import devpipe
    s, mirror, queries = tpch_mesh
    sql = queries[name]
    folded = folded if name == "Q3" else 0
    if n:
        _mesh_of(monkeypatch, n)
        s.execute("set @@tidb_mesh_parallel = 1")
    try:
        s.query(sql)
        rows, d = _stats_of(s, sql)
        with monkeypatch.context() as m:
            m.setattr(devpipe._JoinNode, "_folded",
                      lambda self, tbl_len, nb, mesh: False)
            s.query(sql)
            off, d_off = _stats_of(s, sql)
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
    assert rows and rows == off
    assert _rows_close(rows, [list(r) for r in
                              mirror.execute(sql).fetchall()])
    assert d.get("pipe_joins_folded", 0) == folded
    assert d_off.get("pipe_joins_folded", 0) == 0
    assert d["dispatches"] == d_off["dispatches"]
    assert d.get("reshard_bytes", 0) == d_off.get("reshard_bytes", 0) == 0
    assert d.get("progcache_misses", 0) == 0


@pytest.mark.parametrize("n", [2, 4])
def test_q3_over_the_mesh_gathers_two_null_lanes_fewer(tpch_mesh,
                                                       monkeypatch, n):
    s, _mirror, queries = tpch_mesh
    _mesh_of(monkeypatch, n)
    s.execute("set @@tidb_mesh_parallel = 1")
    try:
        _rows, q3 = _stats_of(s, queries["Q3"])
        _rows, q1 = _stats_of(s, queries["Q1"])
        _rows, q6 = _stats_of(s, queries["Q6"])
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
    assert q3["pipe_const_nulls"] == 2
    assert not q1.get("pipe_const_nulls") and not q6.get("pipe_const_nulls")

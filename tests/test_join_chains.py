"""TPC-H's join queries Q5, Q10 and Q18 as ONE fused device program each
(ISSUE 35): the chip's branches forced on the CPU at SF=0.02.

- each statement equals the benchmark's plain reference
  (``benchmark/datasets/tpch_joins.py``: numpy, independent of the engine)
  row for row, and the per-operator tier's answer; one dispatch, no host
  twin, a second run compiles nothing; the counters read what the plan
  says;
- the planner's part: a join order of foreign key -> primary key lookups
  (no many-to-many join), the lookup by one key with the other equality
  as a filter, a GROUP BY that the catalog proves to stand on a key;
- the fused pipeline's part: a join's view as a build side (NULL keys, an
  empty build side), a GROUP BY cut to the key that determines the rest,
  and one that must NOT be cut;
- under a forced four-device mesh each of the three is still ONE fused
  program (ISSUE 39): a join's view as a build side (computed a row range
  a device, its live lanes all-gathered), the keyed GROUP BY reduced a
  shard at a time and merged, the same answers; a view over the byte
  budget leaves the fused pipeline.
"""
import importlib.util
import math
import os

import numpy as np
import pytest

from tinysql_tpu.columnar.store import bulk_load
from tinysql_tpu.ops import kernels
from tinysql_tpu.parallel import dist
from tinysql_tpu.session.session import new_session

SF = 0.02
SEED = 2350000035
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ["q5", "q10", "q18"]


def _bench_file(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "joins_" + "_".join(parts).replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def joins():
    """(session, {kind: (sql, reference rows)}): the benchmark's own
    tables at SF=0.02 and the cell's three statements, as its traffic
    file spells them."""
    import json
    prev = os.environ.get("TINYSQL_DEVICE_JOIN_ONLY")
    os.environ["TINYSQL_DEVICE_JOIN_ONLY"] = "1"
    data = _bench_file("datasets", "tpch_joins.py")
    traffic = _bench_file("traffic.py")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "join_stream.json")) as f:
        statements = traffic.expand(json.load(f))
    ds = data.generate(SF, SEED)
    s = new_session()
    s.execute(f"create database {data.DATABASE}")
    s.execute(f"use {data.DATABASE}")
    for table, ddl in data.SCHEMAS.items():
        s.execute(ddl)
        bulk_load(s.storage,
                  s.infoschema().table_by_name(data.DATABASE, table),
                  ds.tables[table])
    s.execute("set @@tidb_devpipe = 1")
    s.execute("set @@tidb_tpu_min_rows = 64")
    want = {st.kind: (st.sql, data.REFERENCES[st.reference](ds, st.params))
            for st in statements}
    yield s, want
    if prev is None:
        os.environ.pop("TINYSQL_DEVICE_JOIN_ONLY", None)
    else:
        os.environ["TINYSQL_DEVICE_JOIN_ONLY"] = prev


def _same(got, want, rel=1e-9):
    """Rows equal: exactly in keys, strings, NULLs and order, doubles
    within ``rel``."""
    assert len(got) == len(want), (len(got), len(want))
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            if isinstance(b, float):
                assert math.isclose(float(a), b, rel_tol=rel), (g, w)
            else:
                assert a == b, (g, w)


def _counted(s, sql):
    snap = kernels.stats_snapshot()
    rows = s.query(sql).rows
    return rows, kernels.stats_delta(snap)


@pytest.mark.parametrize("kind", KINDS)
def test_equals_the_plain_reference(joins, kind):
    s, want = joins
    sql, ref = want[kind]
    assert ref, "the reference answers no row: the case shows nothing"
    _same(s.query(sql).rows, [list(r) for r in ref])


@pytest.mark.parametrize("kind", KINDS)
def test_equals_the_per_operator_tier(joins, kind):
    s, want = joins
    sql = want[kind][0]
    fused = s.query(sql).rows
    s.execute("set @@tidb_devpipe = 0")
    try:
        _same(fused, s.query(sql).rows)
    finally:
        s.execute("set @@tidb_devpipe = 1")


@pytest.mark.parametrize("kind", KINDS)
def test_one_program_and_nothing_but_the_rows_comes_down(joins, kind):
    s, want = joins
    sql = want[kind][0]
    s.query(sql)
    rows, d = _counted(s, sql)
    assert d["dispatches"] == 1 and d.get("host_dispatches", 0) == 0
    assert d.get("progcache_misses", 0) == 0  # the second run compiles nothing
    assert d["d2h_transfers"] == 1 and d["d2h_bytes"] < 64 << 10
    assert d.get("h2d_bytes", 0) == 0  # every lane is the replica's


@pytest.mark.parametrize("kind, expect", [
    # orders-customer, that into lineitem, supplier, nation,
    # nation-region; view builds: (orders join customer) under lineitem,
    # nation-region under the aggregate's join, region's projection
    ("q5", {"pipe_joins": 5, "pipe_view_builds": 3, "agg_key_cut": 0,
            "agg_dense": 2}),
    # lineitem's partial sums by l_orderkey in its stored order, below
    # the joins; the seven GROUP BY columns above them are customer's:
    # cut to c_custkey
    ("q10", {"pipe_joins": 3, "pipe_view_builds": 1, "agg_key_cut": 1,
             "agg_sorted": 1, "agg_clustered": 1}),
    # the planner put the GROUP BY below the joins, on lineitem's own
    # key in its stored order: two sorted aggregates, nothing to cut
    ("q18", {"pipe_joins": 3, "agg_key_cut": 0, "agg_sorted": 2,
             "agg_clustered": 2}),
])
def test_counters_read_what_the_plan_says(joins, kind, expect):
    s, want = joins
    _rows, d = _counted(s, want[kind][0])
    assert {k: d.get(k, 0) for k in expect} == expect


@pytest.mark.parametrize("kind, shown", [
    ("q5", "joins:5/3view"), ("q10", "key_cut:1"), ("q18", "joins:3/")])
def test_explain_analyze_shows_the_counters(joins, kind, shown):
    s, want = joins
    rows = s.query("explain analyze " + want[kind][0]).rows
    assert any(shown in str(r) for r in rows), rows


def _plan(s, sql):
    return [(r[0].strip(), r[3]) for r in s.query("explain " + sql).rows]


def test_q5_joins_by_key_lookups_only(joins):
    """No join of Q5's is many-to-many (``c_nationkey = s_nationkey``
    joined customer to every supplier of its nation: 60 M rows at SF=1),
    and the one with two equalities looks up by supplier's key and
    filters on the other."""
    s, want = joins
    plan = _plan(s, want["q5"][0])
    joined = [info for op, info in plan if op.startswith("HashJoin")]
    assert len(joined) == 5
    assert all(info.count("=") == 1 for info in joined), joined
    assert any(op.startswith("Selection") and info.startswith("=(")
               for op, info in plan), plan


def test_q10_groups_twice_below_the_nation_join(joins):
    """The aggregate below the nation join groups by customer's columns,
    its key among them: the catalog proves one row a customer, and the
    final aggregate above the join goes.  Its partial sums are made one
    join further down, on lineitem by l_orderkey."""
    s, want = joins
    plan = _plan(s, want["q10"][0])
    aggs = [info for op, info in plan if op.startswith("HashAgg")]
    assert len(aggs) == 2, plan
    keys = [info.split("funcs:")[0].count("col#") for info in aggs]
    assert keys == [7, 1], aggs
    ops = [op.split("(")[0] for op, _ in plan]
    assert ops.index("HashJoin") < ops.index("HashAgg")


def test_q18_aggregates_below_its_joins(joins):
    s, want = joins
    plan = _plan(s, want["q18"][0])
    aggs = [info for op, info in plan if op.startswith("HashAgg")]
    assert len(aggs) == 2 and all(
        info.count(",") == 1 and "group by:" in info for info in aggs), plan


# ---- the pipeline's part on small tables ------------------------------------

@pytest.fixture
def tk(monkeypatch):
    monkeypatch.setenv("TINYSQL_DEVICE_JOIN_ONLY", "1")
    monkeypatch.setattr(dist, "MIN_SHARD_ROWS", 16)
    monkeypatch.setattr(
        dist, "session_mesh",
        lambda sv: dist.sized_mesh(4) if sv.get("tidb_mesh_parallel")
        else None)
    s = new_session()
    s.execute("create database d")
    s.execute("use d")
    s.execute("set @@tidb_tpu_min_rows = 0")
    s.execute("set @@tidb_devpipe = 1")
    rng = np.random.default_rng(35)
    n_dim, n_mid, n_fact = 40, 500, 4000
    tables = {
        "dim": ("id bigint primary key, v bigint, name varchar(8)", {
            "id": np.arange(1, n_dim + 1, dtype=np.int64),
            "v": rng.integers(1, 10, n_dim).astype(np.int64),
            "name": np.array([f"n{i % 7}" for i in range(n_dim)])}),
        "mid": ("id bigint primary key, dim_id bigint, flag bigint, "
                "tag varchar(8)", {
                    "id": np.arange(1, n_mid + 1, dtype=np.int64),
                    # keys 41..45 find no dim row; a tenth are NULL
                    "dim_id": (rng.integers(1, n_dim + 6, n_mid)
                               .astype(np.int64), rng.random(n_mid) < 0.1),
                    "flag": rng.integers(0, 3, n_mid).astype(np.int64),
                    "tag": np.array([f"t{i % 5}" for i in range(n_mid)])}),
        "fact": ("id bigint primary key, mid_id bigint, x double", {
            "id": np.arange(1, n_fact + 1, dtype=np.int64),
            "mid_id": (rng.integers(1, n_mid + 20, n_fact).astype(np.int64),
                       rng.random(n_fact) < 0.1),
            "x": np.round(rng.random(n_fact) * 100, 2)}),
    }
    for table, (columns, data) in tables.items():
        s.execute(f"create table {table} ({columns})")
        bulk_load(s.storage, s.infoschema().table_by_name("d", table),
                  {c: v[0] if isinstance(v, tuple) else v
                   for c, v in data.items()},
                  nulls={c: v[1] for c, v in data.items()
                         if isinstance(v, tuple)})
    yield s


CHAIN = ("from fact, mid, dim where fact.mid_id = mid.id "
         "and mid.dim_id = dim.id")


def _fused_and_plain(s, sql):
    fused, d = _counted(s, sql)
    s.execute("set @@tidb_devpipe = 0")
    s.execute("set @@tidb_use_tpu = 0")
    try:
        plain = s.query(sql).rows
    finally:
        s.execute("set @@tidb_use_tpu = 1")
        s.execute("set @@tidb_devpipe = 1")
    return fused, plain, d


@pytest.mark.parametrize("where, some", [
    ("", True),                       # NULL and dangling keys on both hops
    (" and dim.v >= 5", True),        # the view's validity holds a filter
    (" and dim.v > 1000", False),     # an empty build side
    (" and mid.flag = 7", False),     # an empty view under a full table
])
def test_a_joins_view_as_a_build_side(tk, where, some):
    """``fact`` probes ``(mid join dim)``: the key -> row table is mid's,
    the validity the inner join's (its own filter, dim's, the rows whose
    key found no dim row or is NULL)."""
    sql = f"select count(*), sum(fact.x), min(dim.v) {CHAIN}{where}"
    fused, plain, d = _fused_and_plain(tk, sql)
    _same(fused, plain)
    assert (fused[0][0] > 0) == some
    assert d["dispatches"] == 1 and d["pipe_joins"] == 2
    assert d["pipe_view_builds"] == 1


@pytest.mark.parametrize("keys, cut", [
    # mid's key beside mid's columns: the key decides the group
    ("mid.id, mid.flag, mid.tag", 1),
    # two tables' columns: mid.id decides dim.name too (by the join),
    # but the cut asks for one table's columns: not cut
    ("mid.id, dim.name", 0),
    # no key among them: rows of one flag differ in tag; cutting to
    # either column would merge groups
    ("mid.flag, mid.tag", 0),
])
def test_group_by_is_cut_only_to_a_key(tk, keys, cut):
    # an argument over both ends of the chain keeps the aggregate
    # above its joins (the planner cannot pre-aggregate one side)
    sql = (f"select {keys}, sum(fact.x * dim.v), count(*) {CHAIN} "
           f"group by {keys} order by {keys}")
    fused, plain, d = _fused_and_plain(tk, sql)
    _same(fused, plain)
    assert len(fused) > 3
    assert d.get("agg_key_cut", 0) == cut, d


@pytest.mark.parametrize("key, dense", [("dim.name", 1), ("mid.dim_id", 1),
                                        ("mid.id", 0)])
def test_group_by_one_column_above_the_chain(tk, key, dense):
    """One GROUP BY column with a bounded range groups without a sort:
    a string by its dictionary codes, an int by its column's bounds (a
    NULL key is a group of its own; mid's 500 keys go by scatter-add)."""
    sql = (f"select {key}, sum(fact.x), count(*), max(fact.x) from fact "
           f"left join mid on fact.mid_id = mid.id "
           f"left join dim on mid.dim_id = dim.id "
           f"group by {key} order by {key}")
    fused, plain, d = _fused_and_plain(tk, sql)
    _same(fused, plain)
    assert fused[0][0] is None  # the NULL key's group sorts first
    assert d["dispatches"] == 1 and d.get("agg_dense", 0) == dense


# ---- the mesh ---------------------------------------------------------------

@pytest.fixture
def four_chips(four_devices, monkeypatch):
    """``conftest.four_devices`` with the planner's rows-per-shard floor
    lowered to the small tables."""
    monkeypatch.setattr(dist, "MIN_SHARD_ROWS", 16)


def _mesh_counted(s, sql):
    """(rows, counters) of ``sql`` under ``tidb_mesh_parallel``, warm."""
    s.execute("set @@tidb_mesh_parallel = 1")
    try:
        s.query(sql)
        return _counted(s, sql)
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")


@pytest.mark.parametrize("kind, key_mesh", [("q5", 1), ("q10", 1),
                                            ("q18", 0)])
def test_answers_right_under_a_forced_mesh(joins, kind, key_mesh,
                                           four_chips):
    """``tidb_mesh_parallel`` with four host devices: each of the three
    is ONE fused program over the whole mesh, no host twin, with the
    joins, view builds and key cut of the one-device program (the views
    traced under the mesh, the keyed GROUP BY above a chain reduced a
    shard at a time), nothing downloaded but the final rows, and the
    answers are the reference's."""
    s, want = joins
    sql, ref = want[kind]
    s.query(sql)
    _rows, one = _counted(s, sql)
    rows, d = _mesh_counted(s, sql)
    _same(rows, [list(r) for r in ref])
    assert d["dispatches"] == 1 == d["mesh_dispatches"]
    assert d.get("host_dispatches", 0) == 0
    assert d.get("progcache_misses", 0) == 0
    assert d["d2h_transfers"] == 1 and d["d2h_bytes"] < 64 << 10
    assert d.get("h2d_bytes", 0) == 0
    for k in ("pipe_joins", "pipe_view_builds", "agg_key_cut", "agg_dense",
              "agg_sorted"):
        assert d.get(k, 0) == one.get(k, 0), (k, d, one)
    assert d["pipe_mesh_views"] == d["pipe_view_builds"] > 0
    assert d.get("agg_key_mesh", 0) == key_mesh
    assert one.get("pipe_mesh_views", 0) == one.get("agg_key_mesh", 0) == 0
    # only Q5 builds on a join's view: its lanes cross the mesh whole
    assert (d.get("reshard_bytes", 0) > 0) == (kind == "q5")


@pytest.mark.parametrize("kind", KINDS)
def test_mesh_answer_is_the_one_devices(joins, kind, four_chips):
    """Q10's sums are differences of prefix sums below the joins: a
    shard's run over its own rows, so they round otherwise than one
    device's (both within 1e-9 of the reference)."""
    s, want = joins
    sql = want[kind][0]
    one = s.query(sql).rows
    rows, _d = _mesh_counted(s, sql)
    _same(rows, one, rel=1e-8 if kind == "q10" else 1e-12)


@pytest.mark.parametrize("kind", ["q5", "q10"])
def test_no_join_of_two_tables_partitions_at_four_copies(joins, kind,
                                                         four_chips):
    """``orders`` joins ``customer``: the date filter is a validity mask
    over orders' lanes, so an exchange would move every order, and the
    planner prices that side by its scan's rows (priced by the estimate
    after the filter the exchange looked cheaper than four copies of
    ``customer``)."""
    s, want = joins
    s.execute("set @@tidb_mesh_parallel = 1")
    try:
        plan = _plan(s, want[kind][0])
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
    joined = [info for op, info in plan if op.startswith("HashJoin")
              and "mesh:" in info]
    assert joined and all("mesh:broadcast" in info for info in joined), plan


def test_the_sf10_dataset_refuses_a_program_without_the_counters(
        monkeypatch):
    """``tpch_joins_blocks.generate`` asks before any data is made."""
    data = _bench_file("datasets", "tpch_joins_blocks.py")
    assert set(data.REFERENCES) >= {"q5", "q10", "q18"}
    assert set(data.MESH_COUNTERS) <= set(kernels.STATS)
    monkeypatch.setattr(kernels, "STATS", {
        k: v for k, v in kernels.STATS.items()
        if k not in data.MESH_COUNTERS})
    made = []
    monkeypatch.setattr(data.blocks, "generate",
                        lambda sf, seed: made.append(sf))
    with pytest.raises(RuntimeError, match="pipe_mesh_views"):
        data.generate(10.0, 1)
    assert not made


# ---- the mesh on small tables: view builds and the keyed GROUP BY ------------

def _mesh_and_plain(s, sql):
    """(fused rows under the mesh, the host tier's rows, counters)."""
    fused, d = _mesh_counted(s, sql)
    s.execute("set @@tidb_devpipe = 0")
    s.execute("set @@tidb_use_tpu = 0")
    try:
        plain = s.query(sql).rows
    finally:
        s.execute("set @@tidb_use_tpu = 1")
        s.execute("set @@tidb_devpipe = 1")
    return fused, plain, d


@pytest.mark.parametrize("where, some", [
    ("", True), (" and dim.v >= 5", True), (" and dim.v > 1000", False),
    (" and mid.flag = 7", False)])
def test_a_joins_view_as_a_build_side_under_the_mesh(tk, four_chips, where,
                                                     some):
    """``fact`` probes ``(mid join dim)`` a shard at a time: the view is
    computed a row range a device and its validity and ``dim.v`` cross
    the mesh whole (``reshard_bytes``: mid's 512-row bucket, a byte and
    a value and a null lane: ``dim.v`` may be NULL for a row the inner
    join dropped, never for a valid one)."""
    sql = f"select count(*), sum(fact.x), min(dim.v) {CHAIN}{where}"
    fused, plain, d = _mesh_and_plain(tk, sql)
    _same(fused, plain)
    assert (fused[0][0] > 0) == some
    assert d["dispatches"] == 1 == d["mesh_dispatches"]
    assert d["pipe_joins"] == 2 and d["pipe_view_builds"] == 1
    assert d["pipe_mesh_views"] == 1 and d["reshard_bytes"] == 512 * 9


@pytest.mark.parametrize("sql, joins_, views", [
    # a semi join whose build side is a join's view
    ("select count(*), sum(fact.x) from fact where fact.mid_id in "
     "(select mid.id from mid, dim where mid.dim_id = dim.id "
     "and dim.v >= 5)", 2, 1),
    # a left join onto a join's view: unmatched rows stay, NULL there
    ("select count(*), count(dim.v), sum(fact.x), max(dim.v) from fact "
     "left join (mid join dim on mid.dim_id = dim.id) "
     "on fact.mid_id = mid.id", 2, 1),
])
def test_semi_and_left_joins_build_on_a_view_under_the_mesh(
        tk, four_chips, sql, joins_, views):
    fused, plain, d = _mesh_and_plain(tk, sql)
    _same(fused, plain)
    assert fused[0][0] > 0
    assert d["dispatches"] == 1 == d["mesh_dispatches"]
    assert d["pipe_joins"] == joins_ and d["pipe_mesh_views"] == views
    assert d["reshard_bytes"] > 0


def test_a_view_over_the_byte_budget_leaves_the_fused_pipeline(
        tk, four_chips, monkeypatch):
    """What every device would hold whole of a view is priced as a
    leaf's copies are: over the budget the statement answers right on
    the per-operator tier and counts no view build under the mesh.  (The
    budget here lies between ``dim``'s three columns at its 64-row
    bucket, 1,152 bytes more a device, and the view's one at mid's
    512-row bucket, 3,072.)"""
    sql = f"select count(*), sum(fact.x), min(dim.v) {CHAIN}"
    monkeypatch.setattr(dist, "broadcast_budget_bytes", lambda: 2000.0)
    fused, plain, d = _mesh_and_plain(tk, sql)
    _same(fused, plain)
    assert fused[0][0] > 0
    assert d.get("pipe_mesh_views", 0) == 0 and d["dispatches"] > 1


@pytest.mark.parametrize("key, dense", [("dim.name", 1), ("mid.dim_id", 1),
                                        ("mid.id", 0)])
def test_keyed_group_by_reduces_a_shard_at_a_time(tk, four_chips, key,
                                                  dense):
    """The keyed GROUP BY's two sides under the mesh (masked reductions
    up to 64 groups, scatter-adds beyond: mid's 500 keys), sum, count,
    min and max arguments merged over the shards, the NULL key's group
    among them."""
    sql = (f"select {key}, sum(fact.x), count(*), count(mid.flag), "
           f"max(fact.x), min(fact.x) from fact "
           f"left join mid on fact.mid_id = mid.id "
           f"left join dim on mid.dim_id = dim.id "
           f"group by {key} order by {key}")
    fused, plain, d = _mesh_and_plain(tk, sql)
    _same(fused, plain)
    assert fused[0][0] is None and len(fused) > 3
    assert d["dispatches"] == 1 == d["mesh_dispatches"]
    assert d["agg_key_mesh"] == 1 and d.get("agg_dense", 0) == dense


@pytest.mark.parametrize("key", ["mid.dim_id", "mid.id"])
def test_a_shard_with_no_row_gives_the_identity(tk, four_chips, key):
    """Every valid row of ``fact`` lies in the first shard's range (ids
    up to 900 of a 4,096-row bucket, 1,024 a shard): three shards reduce
    nothing and the merged sums, counts, minima and maxima are the first
    shard's."""
    # an argument over both ends of the chain keeps the aggregate
    # above its joins
    sql = (f"select {key}, sum(fact.x * dim.v), count(*), max(fact.x), "
           f"min(fact.x) {CHAIN} and fact.id <= 900 "
           f"group by {key} order by {key}")
    fused, plain, d = _mesh_and_plain(tk, sql)
    _same(fused, plain)
    assert len(fused) > 3 and d["agg_key_mesh"] == 1


def test_the_shards_parts_add_up_to_the_one_device_table(tk, four_chips):
    """The one-device program over each shard's row range (a quarter of
    fact's 4,096-row bucket) gives that shard's partial table; summed by
    key the four are the one-device table, and the mesh's."""
    def sql(where):
        return (f"select mid.id, sum(fact.x * dim.v), count(*) {CHAIN}"
                f"{where} group by mid.id order by mid.id")
    whole = tk.query(sql("")).rows
    parts = {}
    for i in range(4):
        lo, hi = i * 1024, (i + 1) * 1024
        for k, v, c in tk.query(
                sql(f" and fact.id > {lo} and fact.id <= {hi}")).rows:
            got = parts.setdefault(k, [0.0, 0])
            got[0] += v
            got[1] += c
    _same([[k, v, c] for k, (v, c) in sorted(parts.items())], whole,
          rel=1e-12)
    mesh, d = _mesh_counted(tk, sql(""))
    _same(mesh, whole, rel=1e-12)
    assert d["agg_key_mesh"] == 1 and d["dispatches"] == 1

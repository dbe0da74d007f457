"""Device-resident pipeline (executor/devpipe.py) behavior tests.

Every query runs on both tiers (TPU devpipe vs CPU volcano) and must
match; node-level instrumentation asserts the pipeline actually engaged
(no silent fallback) where the shape guarantees support.
"""
import numpy as np
import pytest

from tinysql_tpu.columnar.store import bulk_load
from tinysql_tpu.columnar.store import store_of
from tinysql_tpu.executor import devpipe
from tinysql_tpu.ops import kernels
from tinysql_tpu.session.session import new_session


@pytest.fixture
def tk():
    s = new_session()
    s.execute("create database d")
    s.execute("use d")
    # small fixtures must still route to the device tier under test,
    # and the CPU-backend CI mesh must still exercise the pipelines
    s.execute("set @@tidb_tpu_min_rows = 0")
    s.execute("set @@tidb_devpipe = 1")
    yield s


def _load(s, name, schema, cols):
    """bulk_load a table straight into the columnar replica."""
    s.execute(f"create table {name} ({schema})")
    info = s.infoschema().table_by_name("d", name)
    n = bulk_load(s.storage, info,
                  {k: v for k, (v, _) in cols.items()},
                  {k: m for k, (_, m) in cols.items() if m is not None})
    return n


def _both(s, sql):
    s.execute("set @@tidb_use_tpu = 1")
    a = s.query(sql).rows
    s.execute("set @@tidb_use_tpu = 0")
    b = s.query(sql).rows
    s.execute("set @@tidb_use_tpu = 1")
    return a, b


def _canon(rows):
    # (type tag, str value) pairs: sortable with NULLs present, and a
    # cross-tier TYPE regression (int vs str vs float) still fails
    def cell(v):
        if v is None:
            return ("N", "")
        if isinstance(v, float):
            return ("f", f"{v:.9g}")
        if isinstance(v, bool):
            return ("i", str(int(v)))
        if isinstance(v, int):
            return ("i", str(v))
        return ("s", str(v))
    return sorted(tuple(cell(v) for v in r) for r in rows)


def assert_match(s, sql, ordered=False):
    a, b = _both(s, sql)
    if ordered:
        assert [_canon([r])[0] for r in a] == \
            [_canon([q])[0] for q in b], (sql, a, b)
    else:
        assert _canon(a) == _canon(b), (sql, a, b)


@pytest.fixture
def counters(monkeypatch):
    runs = {"join": 0, "agg": 0, "leaf": 0, "host": 0, "order": 0,
            "sortgroup": 0, "keygroup": 0}
    for cls, k in [(devpipe._JoinNode, "join"),
                   (devpipe._AggIndexNode, "agg"),
                   (devpipe._ReplicaLeaf, "leaf"),
                   (devpipe._HostLeaf, "host"),
                   (devpipe._OrderNode, "order"),
                   (devpipe._SortGroupNode, "sortgroup"),
                   (devpipe._KeyGroupNode, "keygroup")]:
        orig = cls.prepare

        def mk(orig, k):
            def prepare(self, pb, *args, **kw):
                runs[k] += 1
                return orig(self, pb, *args, **kw)
            return prepare
        monkeypatch.setattr(cls, "prepare", mk(orig, k))
    return runs


#: _AggIndexNode's two formulations, by the counter each bumps at
#: prepare(); a fixture built for one side lands on it by its group count
SIDES = ["dense", "sorted"]


class _AggPaths:
    """Growth of the agg_dense / agg_sorted counters since construction."""

    def __init__(self):
        self._since = {k: kernels.STATS[k] for k in ("agg_dense",
                                                     "agg_sorted")}

    def taken(self) -> dict:
        return {k[4:]: kernels.STATS[k] - v
                for k, v in self._since.items()}

    def only(self, side: str) -> bool:
        """Every _AggIndexNode prepared since took ``side``, and one did."""
        got = self.taken()
        return got.pop(side) >= 1 and not any(got.values())


def _fixture_tables(tk, n=3000, seed=11, fk_hi=400):
    rng = np.random.default_rng(seed)
    a = np.arange(1, n + 1, dtype=np.int64)
    b = rng.integers(-50, 50, n).astype(np.int64)
    c = rng.random(n) * 100
    cnull = rng.random(n) < 0.1
    fk = rng.integers(1, fk_hi, n).astype(np.int64)
    fknull = rng.random(n) < 0.05
    _load(tk, "t", "a bigint primary key, b bigint, c double, fk bigint",
          {"a": (a, None), "b": (b, None), "c": (c, cnull),
           "fk": (fk, fknull)})
    k = np.arange(1, 301, dtype=np.int64)  # fk hits 1..400: some miss
    v = rng.integers(0, 1000, 300).astype(np.int64)
    w = rng.random(300) * 10
    _load(tk, "u", "k bigint primary key, v bigint, w double",
          {"k": (k, None), "v": (v, None), "w": (w, None)})


def test_pk_join_inner(tk, counters):
    _fixture_tables(tk)
    assert_match(tk, "select t.a, t.b, u.v from t join u on t.fk = u.k "
                     "where t.b > 0")
    assert counters["join"] >= 1 and counters["host"] == 0


def test_pk_join_left_null_extend(tk, counters):
    _fixture_tables(tk)
    # fk in 300..400 misses u entirely; fk NULL rows must null-extend
    assert_match(tk, "select t.a, u.v, u.w from t left join u "
                     "on t.fk = u.k")


def test_join_filters_both_sides(tk, counters):
    _fixture_tables(tk)
    assert_match(tk, "select t.a, u.v from t join u on t.fk = u.k "
                     "where t.c < 50 and u.v > 200")


@pytest.mark.parametrize("side", SIDES)
def test_agg_pushdown_join_via_group_index(tk, counters, side):
    # fk has 399 values (+ NULL), or 39: the partial aggregate's groups
    _fixture_tables(tk, fk_hi=400 if side == "sorted" else 40)
    paths = _AggPaths()
    # group by fk on the probe table -> partial agg build side via the
    # replica group index (agg pushdown through the join), merged on u.v:
    # the join reads slot g of the [ngb] view through the index's
    # pos-table, whichever formulation filled it
    assert_match(tk, "select u.v, count(*), sum(t.c) from t join u "
                     "on t.fk = u.k group by u.v")
    assert counters["join"] >= 1 and counters["agg"] >= 1
    assert paths.only(side), paths.taken()


def test_topn_over_join(tk, counters):
    _fixture_tables(tk)
    assert_match(tk, "select t.a, t.c from t join u on t.fk = u.k "
                     "where u.v > 100 order by t.c desc, t.a limit 7")
    assert counters["order"] >= 1 and counters["host"] == 0


def test_topn_offset_over_join(tk, counters):
    _fixture_tables(tk)
    assert_match(tk, "select t.a from t join u on t.fk = u.k "
                     "order by t.a limit 5, 11")


def test_empty_result_join(tk, counters):
    _fixture_tables(tk)
    assert_match(tk, "select t.a, u.v from t join u on t.fk = u.k "
                     "where t.b > 1000")


def test_join_dirty_txn_falls_back(tk, counters):
    _fixture_tables(tk)
    tk.execute("set @@autocommit = 0")
    tk.execute("insert into t values (100001, 5, 1.5, 7)")
    # own buffered write on t: replica unreadable -> fallback executors
    # must still answer correctly (dirty row visible)
    tk.execute("set @@tidb_use_tpu = 1")
    got = tk.query("select count(*) from t join u on t.fk = u.k "
                   "where t.a = 100001").rows
    assert got == [[1]], got
    tk.execute("rollback")
    tk.execute("set @@autocommit = 1")


def test_three_way_join_chain(tk, counters):
    _fixture_tables(tk)
    rng = np.random.default_rng(3)
    g = np.arange(1, 51, dtype=np.int64)
    z = rng.integers(0, 5, 50).astype(np.int64)
    _load(tk, "w", "g bigint primary key, z bigint",
          {"g": (g, None), "z": (z, None)})
    assert_match(tk, "select count(*), sum(w.z) from t join u "
                     "on t.fk = u.k join w on t.b + 51 = w.g")


def test_devpipe_matches_on_tpch_q3_shape(tk, counters):
    # miniature Q3: two joins + agg-pushdown partial + topn
    rng = np.random.default_rng(5)
    nc, no, nl = 200, 1000, 4000
    _load(tk, "cust", "ck bigint primary key, seg bigint",
          {"ck": (np.arange(1, nc + 1, dtype=np.int64), None),
           "seg": (rng.integers(0, 5, nc).astype(np.int64), None)})
    _load(tk, "ord", "ok bigint primary key, ck bigint, pri bigint",
          {"ok": (np.arange(1, no + 1, dtype=np.int64), None),
           "ck": (rng.integers(1, nc + 1, no).astype(np.int64), None),
           "pri": (rng.integers(0, 3, no).astype(np.int64), None)})
    _load(tk, "line", "lk bigint, price double, disc double",
          {"lk": (rng.integers(1, no + 1, nl).astype(np.int64), None),
           "price": (rng.random(nl) * 1000, None),
           "disc": (rng.random(nl) * 0.1, None)})
    q = ("select line.lk, sum(line.price * (1 - line.disc)) as rev, "
         "ord.pri from cust join ord on cust.ck = ord.ck "
         "join line on line.lk = ord.ok "
         "where cust.seg = 2 and ord.pri < 2 "
         "group by line.lk, ord.pri order by rev desc, line.lk limit 10")
    a, b = _both(tk, q)
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra[0] == rb[0] and ra[2] == rb[2]
        assert abs(ra[1] - rb[1]) < 1e-6 * max(1.0, abs(ra[1]))
    assert counters["join"] >= 2 and counters["agg"] >= 1


def test_randomized_join_battery(tk, counters):
    _fixture_tables(tk)
    rng = np.random.default_rng(23)
    preds_t = ["t.b > 10", "t.c < 25", "t.b % 3 = 0", "t.fk < 200",
               "t.c is not null"]
    preds_u = ["u.v > 500", "u.w < 5.0", "u.v % 2 = 1"]
    for i in range(12):
        pt = rng.choice(preds_t)
        pu = rng.choice(preds_u)
        jt = "join" if i % 3 else "left join"
        cols = "t.a, t.b, u.v" if i % 2 else "t.a, u.w, u.k"
        sql = (f"select {cols} from t {jt} u on t.fk = u.k "
               f"where {pt}" + ("" if jt == "left join" else f" and {pu}"))
        assert_match(tk, sql)


def _dup_tables(tk, n=2500, m=600, seed=7):
    """Probe table p, build table d where d.k has DUPLICATES (and NULLs):
    the CSR multiplicity path, not the unique pos-table path."""
    rng = np.random.default_rng(seed)
    _load(tk, "p", "a bigint primary key, fk bigint, x double",
          {"a": (np.arange(1, n + 1, dtype=np.int64), None),
           "fk": (rng.integers(1, 80, n).astype(np.int64),
                  rng.random(n) < 0.05),
           "x": (rng.random(n) * 100, None)})
    _load(tk, "dup", "k bigint, v bigint, w double",
          {"k": (rng.integers(1, 100, m).astype(np.int64),
                 rng.random(m) < 0.05),
           "v": (rng.integers(0, 1000, m).astype(np.int64), None),
           "w": (rng.random(m) * 10, rng.random(m) < 0.1)})


def test_join_nonunique_build_inner(tk, counters):
    _dup_tables(tk)
    assert_match(tk, "select p.a, dup.v, dup.w from p join dup "
                     "on p.fk = dup.k where p.x < 60")
    assert counters["join"] >= 1 and counters["host"] == 0
    assert any(k[0] == "joinm" for k in devpipe.COMPILED_NODE_KEYS), \
        "CSR multiplicity join never compiled into a fused pipeline"


def test_join_nonunique_build_left_null_extend(tk, counters):
    _dup_tables(tk)
    # fk NULL rows and fk values with no dup.k match must null-extend once
    assert_match(tk, "select p.a, dup.v from p left join dup "
                     "on p.fk = dup.k")
    assert counters["join"] >= 1 and counters["host"] == 0


def test_join_nonunique_build_filter_on_build(tk, counters):
    _dup_tables(tk)
    # build-side filter shrinks per-group multiplicity: valid-count CSR
    assert_match(tk, "select p.a, dup.v from p join dup on p.fk = dup.k "
                     "where dup.v > 500 and p.x > 20")


def test_join_nonunique_then_topn(tk, counters):
    _dup_tables(tk)
    assert_match(tk, "select p.a, dup.v, p.x from p join dup "
                     "on p.fk = dup.k order by p.x desc, p.a, dup.v "
                     "limit 9")
    assert counters["order"] >= 1 and counters["host"] == 0


def test_join_sides_swapped_no_cache_collision(tk, counters):
    # same structural shape, opposite probe/build orientation: the fused
    # program cache must not replay the first query's column order
    _fixture_tables(tk)
    assert_match(tk, "select t.a, t.fk, u.k, u.v from t join u "
                     "on t.fk = u.k where t.b > 0")
    assert_match(tk, "select u.k, u.v, t.a, t.fk from u join t "
                     "on u.k = t.fk where t.b > 0")


def test_group_index_single_null_group():
    # stored values under a null mask are garbage: all NULL keys must
    # collapse into ONE group (kernels._group_agg_kernel parity)
    vals = np.array([5, 1, 5, 9, 2, 7, 1], dtype=np.int64)
    nulls = np.array([False, True, False, True, False, True, False])
    gi = devpipe.GroupIndex([(vals, nulls)])
    assert gi.n_groups == 4  # {1, 2, 5}, one NULL group
    assert int(gi.gkey_null.sum()) == 1
    null_g = int(np.nonzero(gi.gkey_null)[0][0])
    start = 0 if null_g == 0 else int(gi.ends[null_g - 1]) + 1
    assert int(gi.ends[null_g]) - start + 1 == 3  # all three NULL rows
    tbl = gi.pos_table()
    assert tbl is not None and (tbl >= 0).sum() == 3


# ---- multi-key group-by on the replica leaf (_AggIndexNode) -------------

def _gb_fixture(tk, side="dense", n=4000, seed=23):
    """(b, seg) has 12 x 4 = 48 groups with the NULLs (dense: at most
    kernels.SEG_UNROLL), or 42 x 4 = 168 (sorted)."""
    rng = np.random.default_rng(seed)
    span = 5 if side == "dense" else 20
    a = np.arange(1, n + 1, dtype=np.int64)
    b = rng.integers(-span, span + 1, n).astype(np.int64)
    bnull = rng.random(n) < 0.08
    c = rng.random(n) * 100
    cnull = rng.random(n) < 0.1
    seg = np.array(["AA", "BB", "CC"])[rng.integers(0, 3, n)]
    segnull = rng.random(n) < 0.05
    d = rng.random(n) * 10
    _load(tk, "g", "a bigint primary key, b bigint, c double, "
                   "seg varchar(4), d double",
          {"a": (a, None), "b": (b, bnull), "c": (c, cnull),
           "seg": (seg, segnull), "d": (d, None)})


def test_seg_unroll_separates_the_fixtures_sides():
    assert 48 <= kernels.SEG_UNROLL < 168


@pytest.mark.parametrize("side", SIDES)
def test_multikey_leaf_group_by_int_string(tk, counters, side):
    _gb_fixture(tk, side)
    paths = _AggPaths()
    assert_match(tk, "select b, seg, count(*), sum(c) from g "
                     "group by b, seg order by b, seg")
    assert counters["agg"] >= 1 and counters["sortgroup"] == 0
    assert counters["host"] == 0
    assert paths.only(side), paths.taken()


@pytest.mark.parametrize("side", SIDES)
def test_multikey_leaf_avg_min_max(tk, counters, side):
    _gb_fixture(tk, side)
    paths = _AggPaths()
    # c has NULLs: every aggregate kind over it, beside count(*)
    assert_match(tk, "select seg, b, sum(c), avg(c), min(c), max(c), "
                     "count(c), count(*), min(d), max(d), min(b) "
                     "from g group by seg, b order by seg, b")
    assert counters["agg"] >= 1
    assert paths.only(side), paths.taken()


@pytest.mark.parametrize("side", SIDES)
def test_multikey_leaf_all_null_group_sums_to_null(tk, counters, side):
    _gb_fixture(tk, side)
    # every c of one group NULL: its sum/avg/min/max are NULL and its
    # count(c) is 0, while count(*) still counts the rows
    tk.execute("update g set c = null where b = 2 and seg = 'BB'")
    paths = _AggPaths()
    q = ("select seg, b, sum(c), avg(c), min(c), max(c), count(c), "
         "count(*) from g group by seg, b order by seg, b")
    assert_match(tk, q)
    row = [r for r in tk.query(q).rows if r[0] == "BB" and r[1] == 2]
    assert len(row) == 1 and row[0][2:7] == [None] * 4 + [0], row
    assert row[0][7] > 0
    assert paths.only(side), paths.taken()


@pytest.mark.parametrize("side", SIDES)
def test_multikey_leaf_filter_empties_groups(tk, counters, side):
    _gb_fixture(tk, side)
    paths = _AggPaths()
    # the filter leaves no row of seg 'AA', of NULL seg or of b = 3:
    # those groups are ABSENT from the result, not rows of zeros
    q = ("select seg, b, count(*), sum(c), count(c) from g "
         "where seg > 'AA' and b <> 3 group by seg, b order by seg, b")
    assert_match(tk, q, ordered=True)
    rows = tk.query(q).rows
    assert rows and all(r[0] in ("BB", "CC") and r[1] != 3 and r[2] > 0
                        for r in rows), rows
    # a filter no row passes: no group at all
    assert_match(tk, "select seg, b, count(*) from g where d > 100 "
                     "group by seg, b")
    assert tk.query("select seg, b, count(*) from g where d > 100 "
                    "group by seg, b").rows == []
    assert paths.only(side), paths.taken()


@pytest.mark.parametrize("side", SIDES)
def test_multikey_leaf_q1_shape(tk, counters, side):
    """TPC-H Q1 shape: two keys (one a string), sums of expressions,
    avgs, count(*), filter, order by the keys — must run via the group
    index (one device program when fused); Q1 itself has 4 groups and
    takes the dense formulation."""
    _gb_fixture(tk, side)
    paths = _AggPaths()
    assert_match(tk, "select seg, b, sum(c) s1, sum(c * (1 - d/100)) s2, "
                     "avg(c), avg(d), count(*) from g where d < 9.5 "
                     "group by seg, b order by seg, b")
    assert counters["agg"] >= 1 and counters["host"] == 0
    assert paths.only(side), paths.taken()
    head = "aggdense" if side == "dense" else "aggindex"
    assert any(k[0] == head for k in devpipe.COMPILED_NODE_KEYS)


def test_group_count_crossing_threshold_gets_a_new_program(tk, counters):
    """The formulation is chosen from the replica's group count and is
    part of the program key: a table that grows past SEG_UNROLL groups
    between two statements of one shape takes the other program, and
    neither clobbers the other's cache entry."""
    n = 3 * kernels.SEG_UNROLL - 12
    k = np.arange(n, dtype=np.int64) % (kernels.SEG_UNROLL - 4)
    _load(tk, "cross", "a bigint primary key, k bigint, x double",
          {"a": (np.arange(1, n + 1, dtype=np.int64), None),
           "k": (k, None), "x": (np.arange(n) * 0.5, None)})
    q = "select k, count(*), sum(x), min(x) from cross group by k order by k"
    paths = _AggPaths()
    assert_match(tk, q, ordered=True)
    assert paths.taken() == {"dense": 1, "sorted": 0}
    tk.execute("insert into cross values " + ", ".join(
        f"({n + 1 + i}, {1000 + i}, {i}.25)" for i in range(8)))
    tk.query("select * from cross")     # hydrate the new replica version
    assert_match(tk, q, ordered=True)
    assert paths.taken() == {"dense": 1, "sorted": 1}
    assert len(tk.query(q).rows) == kernels.SEG_UNROLL + 4
    tk.execute("delete from cross where k >= 1000")
    tk.query("select * from cross")
    assert_match(tk, q, ordered=True)   # dense again: its entry is intact
    assert paths.taken()["dense"] >= 2
    heads = {key[0] for key in devpipe.COMPILED_NODE_KEYS}
    assert {"aggdense", "aggindex"} <= heads


def test_dense_row_gid_lane_sentinel_and_single_upload(tk, counters):
    """The dense formulation's one lane: the group id of each ROW in the
    narrowest integer type, the sentinel ngb on padding rows, uploaded
    once per replica version."""
    n = 21                                    # bucket 32: 11 padding rows
    k = np.array([2, 0, 1] * 7, dtype=np.int64)   # row 0 is in group 2
    x = np.arange(n) + 0.5
    _load(tk, "pad", "a bigint primary key, k bigint, x double",
          {"a": (np.arange(1, n + 1, dtype=np.int64), None),
           "k": (k, None), "x": (x, None)})
    q = "select k, count(*), sum(x) from pad group by k order by k"
    h0 = kernels.STATS["h2d_bytes"]
    rows = tk.query(q).rows
    h1 = kernels.STATS["h2d_bytes"]
    # row 0 is counted once, not once per padding slot (padding carries
    # the sentinel group here; on the sorted path the leaf's own padding
    # guard masks it)
    assert rows == [[g, 7, float(x[k == g].sum())] for g in range(3)]
    info = tk.infoschema().table_by_name("d", "pad")
    rep = store_of(tk.storage).get(info.id)
    lanes = [(key, v) for key, v in rep.cache.items()
             if key[0] == "gi_rowgid"]
    assert len(lanes) == 1
    (_, _sids, nb), lane = lanes[0]
    host = np.asarray(lane)
    assert nb == 32 and host.shape == (32,) and host.dtype == np.uint8
    assert (host[:n] == k).all()
    assert (host[n:] == 16).all()             # ngb: matches no group
    # none of the sorted formulation's index lanes, and no lane permuted
    # into an index's order (no formulation uploads gi_order any more)
    assert not [key for key in rep.cache
                if key[0] in ("gi_order", "gi_sgid", "gi_ends")
                or "by" in key]
    assert h1 - h0 >= nb                      # the lane went up once...
    assert tk.query(q).rows == rows
    h2 = kernels.STATS["h2d_bytes"]
    assert h2 - h1 < nb, (h0, h1, h2)         # ...and only parameters after
    assert rep.cache[lanes[0][0]] is lane


def test_single_key_real_group_by(tk, counters):
    _gb_fixture(tk)
    # float group keys: boundary on exact equality
    tk.execute("insert into g values (100001, 1, 5.5, 'AA', 0.25)")
    tk.execute("insert into g values (100002, 1, 5.5, 'BB', 0.25)")
    assert_match(tk, "select d, count(*) from g group by d "
                     "order by d limit 20")


def test_group_by_above_join_sortgroup_final(tk, counters):
    _fixture_tables(tk)
    # agg pushdown rewrites this to partial-below-join + FINAL above:
    # the sort-group node must merge the partial STATES on device
    # (count -> sum of counts)
    assert_match(tk, "select u.w, count(*), sum(t.c) from t join u "
                     "on t.fk = u.k group by u.w")
    assert counters["join"] >= 1 and counters["sortgroup"] >= 1


def test_group_by_above_join_sortgroup_raw(tk, counters):
    _fixture_tables(tk)
    # agg args from BOTH sides defeat pushdown: the above-join agg stays
    # in raw mode and must still run in-kernel (one int key of a bounded
    # range: the keyed formulation, no sort, since PR 35)
    assert_match(tk, "select u.v, sum(t.c * u.w), avg(t.c), min(u.w) "
                     "from t join u on t.fk = u.k group by u.v")
    assert counters["keygroup"] >= 1 and counters["sortgroup"] == 0


def test_group_by_above_join_multikey(tk, counters):
    _fixture_tables(tk)
    assert_match(tk, "select u.v, t.b, count(*), avg(t.c) from t join u "
                     "on t.fk = u.k where t.c is not null "
                     "group by u.v, t.b order by u.v, t.b limit 50")


@pytest.mark.parametrize("side", SIDES)
def test_sortgroup_null_keys_group_together(tk, counters, side):
    _gb_fixture(tk, side)
    # b has NULLs: all-NULL key rows form ONE group on both tiers
    assert_match(tk, "select b, count(*), min(c) from g group by b "
                     "order by b")
    assert_match(tk, "select b, seg, count(*) from g group by b, seg "
                     "order by b, seg")


def test_keyorder_swapped_group_bys_no_cache_clobber(tk, counters):
    """Two group-bys differing only in key order (int64 <-> float64 key
    lanes swap) must not share a fused-program cache entry: the shared
    pack schema of a clobbered entry returned silently corrupt rows
    (round-4 review finding, reproduced)."""
    _gb_fixture(tk)
    q1 = "select b, d, count(*) from g group by b, d"
    q2 = "select d, b, count(*) from g group by d, b"
    assert_match(tk, q1)
    assert_match(tk, q2)
    assert_match(tk, q1)  # re-run q1 AFTER q2 traced: must still be right


# ---- multi-key equi-joins via composite lanes ---------------------------

def _mk_fixture(tk, seed=13):
    rng = np.random.default_rng(seed)
    rows_k1, rows_k2, rows_v, rows_id = [], [], [], []
    i = 1
    for a in range(1, 21):
        for b in range(1, 16):
            rows_id.append(i)
            rows_k1.append(a)
            rows_k2.append(b)
            rows_v.append(a * 100.0 + b)
            i += 1
    _load(tk, "dimk", "id bigint primary key, k1 bigint, k2 bigint, "
                      "v double",
          {"id": (np.array(rows_id, dtype=np.int64), None),
           "k1": (np.array(rows_k1, dtype=np.int64), None),
           "k2": (np.array(rows_k2, dtype=np.int64), None),
           "v": (np.array(rows_v), None)})
    tk.execute("create unique index uk on dimk (k1, k2)")
    n = 3000
    f1 = rng.integers(1, 25, n).astype(np.int64)
    f2 = rng.integers(1, 18, n).astype(np.int64)
    f2n = rng.random(n) < 0.05
    _load(tk, "factk", "fid bigint primary key, f1 bigint, f2 bigint, "
                       "x double",
          {"fid": (np.arange(1, n + 1, dtype=np.int64), None),
           "f1": (f1, None), "f2": (f2, f2n),
           "x": (rng.random(n) * 100, None)})


def test_multikey_join_inner(tk, counters):
    _mk_fixture(tk)
    assert_match(tk, "select factk.fid, dimk.v from factk join dimk "
                     "on factk.f1 = dimk.k1 and factk.f2 = dimk.k2 "
                     "where factk.x < 50 order by factk.fid limit 40")
    assert counters["join"] >= 1
    assert any(k[0] == "joinmk" for k in devpipe.COMPILED_NODE_KEYS)


def test_multikey_join_left_null_extend(tk, counters):
    _mk_fixture(tk)
    # f1 in 21..24 / f2 in 16..17 miss dimk; NULL f2 never matches
    assert_match(tk, "select factk.fid, dimk.v from factk left join dimk "
                     "on factk.f1 = dimk.k1 and factk.f2 = dimk.k2 "
                     "order by factk.fid limit 100")


def test_multikey_join_group_by_above(tk, counters):
    _mk_fixture(tk)
    assert_match(tk, "select dimk.k1, count(*), sum(factk.x), "
                     "avg(factk.x) from factk join dimk "
                     "on factk.f1 = dimk.k1 and factk.f2 = dimk.k2 "
                     "group by dimk.k1 order by dimk.k1")


def test_multikey_join_nonunique_build_csr(tk, counters):
    _mk_fixture(tk)
    # dup table: NO unique index covers (g1, g2) and the tuple repeats —
    # the composite CSR expansion must produce every duplicate match
    rng = np.random.default_rng(5)
    g1 = np.repeat(np.arange(1, 11, dtype=np.int64), 6)
    g2 = np.tile(np.arange(1, 4, dtype=np.int64), 20)  # (g1,g2) dup x2
    _load(tk, "dupd", "id bigint primary key, g1 bigint, g2 bigint, "
                      "w double",
          {"id": (np.arange(1, 61, dtype=np.int64), None),
           "g1": (g1, None), "g2": (g2, None),
           "w": (rng.random(60) * 10, None)})
    assert_match(tk, "select factk.fid, dupd.w from factk join dupd "
                     "on factk.f1 = dupd.g1 and factk.f2 = dupd.g2 "
                     "order by factk.fid, dupd.w limit 40")
    assert_match(tk, "select factk.fid, dupd.w from factk left join dupd "
                     "on factk.f1 = dupd.g1 and factk.f2 = dupd.g2 "
                     "order by factk.fid, dupd.w limit 60")
    assert counters["join"] >= 1


def test_multikey_join_other_conds_cpu_guard(tk, counters):
    _mk_fixture(tk)
    # a non-equi ON conjunct puts other_conditions on the join: the fused
    # pipeline takes an inner join's as a filter above the join over the
    # composite key lane, and the per-op tier must route multi-key plans
    # to the CPU hash join (never the single-key device kernel, which
    # would silently join on the first key only)
    sql = ("select factk.fid, dimk.v from factk join dimk "
           "on factk.f1 = dimk.k1 and factk.f2 = dimk.k2 "
           "and factk.x < dimk.v order by factk.fid limit 30")
    assert_match(tk, sql)
    # the per-test prepare counter (not the process-global key set, which
    # earlier tests already populate): the fused join node ran
    assert counters["join"] >= 1, counters
    joined = counters["join"]
    tk.execute("set @@tidb_devpipe = 0")
    try:
        assert_match(tk, sql)
    finally:
        tk.execute("set @@tidb_devpipe = 1")
    assert counters["join"] == joined, counters


def test_scalar_agg_above_join(tk, counters):
    """Global aggregates above joins stay device-resident (one fused
    program): FINAL merges from pushdown and raw both-sides args."""
    _fixture_tables(tk)
    assert_match(tk, "select count(*), sum(t.c), avg(t.c), min(u.w), "
                     "max(t.b) from t join u on t.fk = u.k")
    assert_match(tk, "select sum(t.c * u.w), count(t.c) from t join u "
                     "on t.fk = u.k where t.b > 0")
    assert_match(tk, "select count(*), sum(u.w) from t left join u "
                     "on t.fk = u.k")
    # zero-row input still yields the single scalar row
    assert_match(tk, "select count(*), sum(t.c), min(t.b) from t join u "
                     "on t.fk = u.k where t.b > 10000")
    assert counters["join"] >= 1


# ---- column liveness: a program computes only what its consumer reads ------

def _live_tables(tk, n=2000, seed=13):
    """A fact table and three dimensions with a string column each, so a
    projection that reads one stays on the host (exprjit lowers no
    string) and is the fused program's CONSUMER: ``dm`` keyed uniquely,
    ``dd`` with duplicate and NULL keys (the CSR join), ``wz`` small."""
    rng = np.random.default_rng(seed)
    tags = np.array(["red", "green", "blue", "grey"], dtype=object)
    _load(tk, "f", "a bigint primary key, b bigint, c double, fk bigint, "
                   "tag varchar(8)",
          {"a": (np.arange(1, n + 1, dtype=np.int64), None),
           "b": (rng.integers(-50, 50, n).astype(np.int64), None),
           "c": (rng.random(n) * 100, rng.random(n) < 0.1),
           "fk": (rng.integers(1, 400, n).astype(np.int64),
                  rng.random(n) < 0.05),
           "tag": (tags[rng.integers(0, 4, n)], rng.random(n) < 0.1)})
    names = np.array([f"n{i:03d}" for i in range(300)], dtype=object)
    _load(tk, "dm", "k bigint primary key, v bigint, w double, "
                    "name varchar(8)",
          {"k": (np.arange(1, 301, dtype=np.int64), None),
           "v": (rng.integers(0, 1000, 300).astype(np.int64), None),
           "w": (rng.random(300) * 10, rng.random(300) < 0.1),
           "name": (names, rng.random(300) < 0.1)})
    _load(tk, "wz", "g bigint primary key, z bigint",
          {"g": (np.arange(1, 51, dtype=np.int64), None),
           "z": (rng.integers(0, 5, 50).astype(np.int64), None)})
    m = 500
    _load(tk, "dd", "k bigint, v bigint, label varchar(8)",
          {"k": (rng.integers(1, 100, m).astype(np.int64),
                 rng.random(m) < 0.05),
           "v": (rng.integers(0, 1000, m).astype(np.int64), None),
           "label": (tags[rng.integers(0, 4, m)], rng.random(m) < 0.1)})


@pytest.fixture
def lives(monkeypatch):
    """[(node kind, its live slots, its slot count)] of every node that
    prepared with a slot dead."""
    seen = []
    key = devpipe._PipeBuilder.key

    def spy(self, part, live=None, n=0, *args):
        if live is not None and len(live) < n:
            seen.append((part[0], sorted(live), n))
        return key(self, part, live, n, *args)
    monkeypatch.setattr(devpipe._PipeBuilder, "key", spy)
    return seen


def _dead_cols(tk, sql):
    """(rows of the forced pipe, rows of the CPU executors, growth of
    pipe_dead_cols and dispatches over the pipe's run)."""
    before = kernels.stats_snapshot()
    tk.execute("set @@tidb_use_tpu = 1")
    got = tk.query(sql).rows
    delta = kernels.stats_delta(before)
    tk.execute("set @@tidb_use_tpu = 0")
    want = tk.query(sql).rows
    tk.execute("set @@tidb_use_tpu = 1")
    return got, want, delta


#: name -> (statement, root slots dead, the nodes that left a slot dead)
LIVE_CASES = {
    # every slot of a three-way join read: the program of before
    "star3": ("select * from f join dm on f.fk = dm.k "
              "join wz on f.b + 51 = wz.g", 0, []),
    # only build columns: the probe's a/fk pass no further
    "build_only": ("select dm.name, dm.v from f join dm on f.fk = dm.k "
                   "where f.b > 0", 3, [("join", [3, 4], 5)]),
    # only probe columns: nothing of dm is gathered
    "probe_only": ("select f.a, f.tag from f join dm on f.fk = dm.k "
                   "where dm.v > 100", 3, [("join", [0, 2], 5)]),
    # the dead build key would carry the NULL extension; the live w does
    "left_ext": ("select f.a, f.tag, dm.w from f left join dm "
                 "on f.fk = dm.k", 2, [("join", [0, 2, 4], 5)]),
    "semi": ("select f.a, f.tag from f where f.fk in "
             "(select k from dm where v > 300)", 1,
             [("proj", [], 1), ("semijoin", [0, 2], 3)]),
    "csr": ("select f.a, dd.label from f join dd on f.fk = dd.k "
            "where f.c < 60", 3, [("joinm", [0, 4], 5)]),
    "csr_left": ("select f.tag, dd.v from f left join dd on f.fk = dd.k",
                 2, [("joinm", [1, 3], 4)]),
    # a TopN reads its keys and carries only the two strings
    "topn": ("select f.tag, dm.name from f join dm on f.fk = dm.k "
             "order by f.c desc, f.a limit 7", 2,
             [("join", [0, 1, 3, 5], 6), ("order", [0, 1, 3, 5], 6)]),
    # the consumer is a projection INSIDE the program: its root is whole
    "inner_proj": ("select dm.v + 1, f.c from f join dm on f.fk = dm.k "
                   "order by f.c desc, f.a limit 7", 0,
                   [("join", [0, 1, 4], 5), ("order", [1, 4], 5),
                    ("proj", [0, 1], 3)]),
    # a scalar aggregate reads no column of the join below it
    "scalar": ("select count(*) from f join dm on f.fk = dm.k", 0,
               [("join", [], 2)]),
    # the probe side is a host leaf (a reader with a pushed-down limit)
    "host_probe": ("select x.a, dm.name from (select a, fk from f "
                   "limit 1500) x join dm on x.fk = dm.k", None, None),
}


#: dispatches of the per-operator kernels a statement runs beside its one
#: program: star3's join on an expression key (f.b + 51) runs per
#: operator above the program, its count and expansion kernels
PER_OP_DISPATCHES = {"star3": 2}


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_liveness_rows_equal_the_cpu_executors(tk, counters, lives, case):
    _live_tables(tk)
    sql, dead, nodes = LIVE_CASES[case]
    got, want, delta = _dead_cols(tk, sql)
    assert _canon(got) == _canon(want) and got, sql
    assert delta["dispatches"] == 1 + PER_OP_DISPATCHES.get(case, 0)
    assert counters["join"] >= 1
    if dead is not None:
        assert delta["pipe_dead_cols"] == dead
        assert lives == nodes
    if case == "host_probe":
        assert counters["host"] >= 1 and delta["pipe_dead_cols"] >= 1


@pytest.fixture(scope="module")
def tpch_tk():
    from tinysql_tpu.bench import tpch
    s = new_session()
    tpch.load(s, data=tpch.generate(0.01))
    s.execute("set @@tidb_tpu_min_rows = 0")
    s.execute("set @@tidb_devpipe = 1")
    return s, tpch.QUERIES


@pytest.mark.parametrize("name, dead, skipped, aligned, folded", [
    ("Q1", 0, 0, 0, 0), ("Q3", 4, 2, 1, 1), ("Q6", 0, 0, 0, 0),
    ("Q5", 0, 6, 0, 4), ("Q10", 2, 4, 1, 2), ("Q18", 2, 3, 2, 1)])
def test_pipe_counters_of_the_benchmarks_statements(tpch_tk, name, dead,
                                                    skipped, aligned,
                                                    folded):
    """Q3's consumer reads four of the TopN's eight slots, and its outer
    join reads revenue and l_orderkey off the group table without their
    null lanes; Q10 leaves out three lanes and its sum's count; Q1's
    pipe is the statement's root and has no join, Q6 builds no fused
    pipeline.  Q3's and Q10's joins into ``lineitem``'s group table, and
    Q18's and its semi join on the HAVING view, read the group table in
    place (``orders`` and ``lineitem`` are both stored by the order key):
    aligned; Q5's joins are many-to-one or into another table's order.
    Folded: the joins whose key -> row table holds at most half their
    probe's rows (Q3's, Q10's and Q18's into ``customer``; Q5's four
    into ``customer``, the orders view, ``supplier`` and ``region``;
    Q10's into ``nation``); Q5's join of its 25 groups into ``nation``
    probes 32 rows with 25 keys and does not fold."""
    from tinysql_tpu.bench import tpch
    s, _queries = tpch_tk
    sql = {**tpch.QUERIES, **tpch.WORKLOAD}[name]
    got, want, delta = _dead_cols(s, sql)
    assert len(got) == len(want) and got
    for a, b in zip(got, want):
        assert all(x == pytest.approx(y, rel=1e-9) if isinstance(y, float)
                   else x == y for x, y in zip(a, b)), (a, b)
    assert delta.get("pipe_dead_cols", 0) == dead
    assert delta.get("pipe_const_nulls", 0) == skipped
    info = s.query("explain analyze " + sql).rows
    assert any(f"dead_cols:{dead}" in str(r) for r in info) == bool(dead)
    assert any(f"const_nulls:{skipped}" in str(r) for r in info) \
        == bool(skipped)
    assert delta.get("pipe_joins_aligned", 0) == aligned
    assert any(f"aligned:{aligned}" in str(r) for r in info) \
        == bool(aligned)
    assert delta.get("pipe_joins_folded", 0) == folded
    assert any(f"folded:{folded}" in str(r) for r in info) == bool(folded)


@pytest.mark.parametrize("name, count32", [
    ("Q1", 0), ("Q3", 1), ("Q6", 0), ("Q5", 0), ("Q10", 1), ("Q18", 2)])
def test_agg_count32_of_the_benchmarks_statements(tpch_tk, name, count32):
    """The sorted GROUP BYs that count their rows in 32 bits: Q3's, Q10's
    and Q18's two over ``lineitem``; Q1's is dense, Q5's keyed above its
    joins and Q6's scalar: none.  Once a statement, in ``EXPLAIN
    ANALYZE`` as ``count32:<n>``."""
    from tinysql_tpu.bench import tpch
    s, _queries = tpch_tk
    sql = {**tpch.QUERIES, **tpch.WORKLOAD}[name]
    before = kernels.stats_snapshot()
    assert s.query(sql).rows
    delta = kernels.stats_delta(before)
    assert delta.get("agg_count32", 0) == count32
    assert delta.get("agg_count32", 0) <= delta.get("agg_sorted", 0)
    info = s.query("explain analyze " + sql).rows
    assert any(f"count32:{count32}" in str(r) for r in info) \
        == bool(count32)


def test_a_dead_slot_raises_when_read(tk, monkeypatch):
    """The placeholder fails the trace: a node that reads a slot it did
    not ask its child for is a failed statement, never a wrong answer."""
    from tinysql_tpu.ops import exprjit
    with pytest.raises(LookupError):
        _v, _m = exprjit.DEAD
    with pytest.raises(LookupError):
        exprjit.DEAD[0]
    assert devpipe._only([(1, 2), (3, 4)], {1}) == [exprjit.DEAD, (3, 4)]
    assert exprjit._broadcast_len([exprjit.DEAD, None]) == 1
    _live_tables(tk)
    # the nodes forget what their own expressions read
    monkeypatch.setattr(devpipe, "_slots_read", lambda exprs: set())
    with pytest.raises(LookupError):
        tk.query(LIVE_CASES["inner_proj"][0])


def test_two_consumers_of_one_plan_shape_are_two_programs(tk):
    """One join, one pruned schema (fk, tag, k, name), three consumers:
    three programs, each with its own pack schema, and a warm one of
    any of them builds nothing and answers as the CPU executors do."""
    from tinysql_tpu.ops import progcache
    _live_tables(tk)
    on = "from f join dm on f.fk = dm.k where f.fk < 200"
    sqls = [f"select f.tag, dm.name {on}",
            f"select f.fk, f.tag, dm.name {on}",
            f"select f.fk, f.tag, dm.k, dm.name {on}"]
    progcache.clear()
    for dead, sql in zip((2, 1, 0), sqls):
        got, want, delta = _dead_cols(tk, sql)
        assert _canon(got) == _canon(want) and got
        assert delta["pipe_dead_cols"] == dead
        assert delta["progcache_misses"] == 1
    pipes = progcache.keys("pipe")
    assert len(pipes) == 3
    assert len({k[:4] for k in pipes}) == 1  # one shape, one signature
    # the third reads every slot, dm.k among them, whose null lane its
    # join leaves out: said beside the node keys, as a live set is
    assert sorted(k[4][1:] for k in pipes) == [
        ((0, 1, 2, 3), (2, "nonnull", (0,))),
        ((0, 1, 3), (2, (0, 1, 3))), ((1, 3), (2, (1, 3)))]
    for sql in sqls + sqls[::-1]:
        got, want, delta = _dead_cols(tk, sql)
        assert _canon(got) == _canon(want)
        assert delta.get("progcache_misses", 0) == 0


def test_fallback_after_a_run_time_bail_returns_every_column(
        tk, monkeypatch):
    """The per-operator executors know nothing of liveness: after a
    bail at run time the consumer gets the rows it would have got."""
    _live_tables(tk)
    sql = LIVE_CASES["topn"][0]
    want = tk.query(sql).rows
    monkeypatch.setattr(devpipe._JoinNode, "prepare",
                        lambda self, pb, live=None: None)
    before = kernels.stats_snapshot()
    got = tk.query(sql).rows
    assert got == want and got
    assert kernels.stats_delta(before)["pipe_dead_cols"] == 0


#: statement, the slots its consumer is made to read, the aggregate specs
#: the program then computes
DEAD_AGGS = {
    "aggindex": ("select fk, count(*), sum(c), avg(c), min(a) from f "
                 "group by fk", {0, 2}, {1}),
    "aggdense": ("select count(*), sum(c), max(b), tag from f group by tag",
                 {2, 3}, {2}),
    "aggindex_avg": ("select fk, count(*), sum(c), avg(c), min(a) from f "
                     "group by fk", {3}, {2, 3}),
    "sortgroup": ("select dm.v, count(*), sum(f.c), max(f.b) from f "
                  "join dm on f.fk = dm.k group by dm.v", {0, 3}, None),
    "scalaragg": ("select count(*), sum(f.c), min(dm.w) from f join dm "
                  "on f.fk = dm.k", {2}, {2}),
}


@pytest.mark.parametrize("case", sorted(DEAD_AGGS))
def test_dead_aggregate_slots_are_not_computed(tk, monkeypatch, case):
    """An aggregate whose consumer reads some of its slots computes the
    specs those read and no other; a dead slot comes back all NULL."""
    _live_tables(tk)
    sql, live, specs = DEAD_AGGS[case]
    tk.execute("set @@tidb_use_tpu = 0")
    want = tk.query(sql).rows
    tk.execute("set @@tidb_use_tpu = 1")
    run = devpipe.DevPipeExec._run_pipeline

    def run_some(self):
        self.live = frozenset(live)
        return run(self)
    monkeypatch.setattr(devpipe.DevPipeExec, "_run_pipeline", run_some)
    computed = []
    results = devpipe._spec_results

    def spy(*a, needed, **kw):
        computed.append(set(needed))
        return results(*a, needed=needed, **kw)
    monkeypatch.setattr(devpipe, "_spec_results", spy)
    from tinysql_tpu.ops import progcache
    progcache.clear()  # a warm program traces nothing
    got = tk.query(sql).rows
    ncols = len(want[0])
    masked = [[v if i in live else None for i, v in enumerate(r)]
              for r in want]
    assert _canon(got) == _canon(masked) and got
    assert all(r[i] is None for r in got
               for i in range(ncols) if i not in live)
    if specs is not None:
        assert computed and all(n == specs for n in computed), computed


@pytest.mark.parametrize("key, grown", [("pipe_dead_cols", 4),
                                        ("pipe_const_nulls", 2),
                                        ("pipe_joins_aligned", 1),
                                        ("pipe_joins_folded", 1),
                                        ("agg_count32", 1)])
def test_pipe_counters_on_metrics_and_in_the_benchmark(tpch_tk, key, grown):
    """The counters' other readers: ``/metrics`` and the benchmark's
    ``<key>_per_query.*`` (a data file over the accepted ``counter``
    reader; a program without the counter, as the parent, leaves the
    metric out).  ``pipe_joins_aligned`` (PR 40) has no entry, the
    benchmark's ``per_layer`` being full: the ``window`` line's
    ``kernels`` reads it, as it reads ``pipe_joins_folded`` and
    ``agg_count32``: the growth of the benchmark's source ``kernels``
    over the window."""
    import importlib.util
    import json
    import os
    from types import SimpleNamespace
    from tinysql_tpu.obs import metrics
    s, queries = tpch_tk
    s.execute("set @@tidb_use_tpu = 1")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src_spec = importlib.util.spec_from_file_location(
        "bench_source_kernels",
        os.path.join(root, "benchmark", "sources", "kernels.py"))
    source = importlib.util.module_from_spec(src_spec)
    src_spec.loader.exec_module(source)
    window_from = source.snapshot()
    before = kernels.stats_snapshot()
    for name in ("Q1", "Q3", "Q6"):
        s.query(queries[name])
    delta = kernels.stats_delta(before)
    assert delta[key] == grown
    # the window line's ``kernels``: the source's growth, key by key
    assert source.snapshot()[key] - window_from.get(key, 0) == grown
    text = metrics.render_prometheus()
    total = [line for line in text.splitlines()
             if line.startswith(f"tinysql_{key}_total ")]
    assert total and float(total[0].split()[1]) >= grown
    path = os.path.join(root, "benchmark", "layer_metrics",
                        f"{key}_per_query.json")
    if key in ("pipe_joins_aligned", "pipe_joins_folded", "agg_count32"):
        assert not os.path.exists(path)
        return
    with open(path) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter" and spec["sources"] == ["kernels"]
    assert spec["args"] == {"source": "kernels", "key": key,
                            "per_statement": True}
    path = os.path.join(root, "benchmark", "readers", "counter.py")
    mod_spec = importlib.util.spec_from_file_location("bench_counter", path)
    counter = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(counter)
    run = SimpleNamespace(deltas={"kernels": delta},
                          answered=[object()] * 3)
    assert counter.read(run, **spec["args"]) == pytest.approx(grown / 3)
    run.deltas = {"kernels": {"dispatches": 3}}   # the parent's counters
    assert counter.read(run, **spec["args"]) is None
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"]
            if m["name"].startswith(f"{key}_per_query.")]
    assert [(m["name"], m["workloads"]) for m in mine] \
        == [(f"{key}_per_query.stream", ["tpch_sf1.power_stream"]),
            (f"{key}_per_query.mesh", ["tpch_sf1_mesh4.power_stream"]),
            (f"{key}_per_query.mesh10", ["tpch_sf10_mesh4.power_stream"]),
            # the four-chip joins cell reports under the joins' names
            # (appended to every ``.joins`` list), and so does the
            # correlated-subquery cell
            (f"{key}_per_query.joins", ["tpch_sf1_joins.join_stream",
                                        "tpch_sf10_joins_mesh4.join_stream",
                                        "tpch_sf1_subq.subq_stream"])]
    for m in mine:
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("count", "higher", "program_counter",
                                "executor: fused pipeline",
                                "stream_queries_per_s")


# ---- NULL-freedom: a view says which slots hold no NULL on a valid row -----

_NODE_KINDS = [(devpipe._ReplicaLeaf, "leaf"), (devpipe._HostLeaf, "host"),
               (devpipe._AggIndexNode, "agg"), (devpipe._JoinNode, "join"),
               (devpipe._SortGroupNode, "sortgroup"),
               (devpipe._KeyGroupNode, "keygroup"),
               (devpipe._ScalarAggNode, "scalaragg"),
               (devpipe._SelNode, "sel"), (devpipe._ProjNode, "proj"),
               (devpipe._OrderNode, "order"), (devpipe._LimitNode, "limit")]


@pytest.fixture
def flags(monkeypatch):
    """[(node kind, its view's NULL-free slots, its slot count)] of every
    node that prepared, children before parents."""
    seen = []
    for cls, kind in _NODE_KINDS:
        def mk(orig, kind):
            def prepare(self, pb, *args, **kw):
                tv = orig(self, pb, *args, **kw)
                if tv is not None:
                    seen.append((kind, sorted(tv.nonnull), len(tv.meta)))
                return tv
            return prepare
        monkeypatch.setattr(cls, "prepare", mk(cls.prepare, kind))
    return seen


#: name -> (statement, every node's view flags, pipe_const_nulls).  Over
#: ``_live_tables``: f(a, b | c, fk, tag hold NULLs), dm(k, v | w, name
#: hold NULLs), dd(v | k, label hold NULLs)
FLAG_CASES = {
    # a leaf says what its replica's null masks show, of the columns the
    # plan kept; a TopN hands the flags on; a projection proves a column,
    # a sum and a negation of NULL-free columns, not a division (by
    # zero) nor anything over a column with NULLs
    "proj": ("select f.a, f.a + f.b, f.a / f.b, f.c + 1, -f.b from f "
             "join dm on f.fk = dm.k order by f.a limit 5",
             [("leaf", [0], 1), ("leaf", [0, 1], 4), ("join", [0, 1, 4], 5),
              ("order", [0, 1, 4], 5), ("proj", [0, 1, 4], 5)], 0),
    # an inner join keeps both sides' flags: its valid rows all matched.
    # dm.k and dm.v go up without their null lanes, dm.w with its own
    "inner": ("select f.a, f.c, dm.k, dm.v, dm.w from f join dm "
              "on f.fk = dm.k where f.b > 0",
              [("leaf", [0, 1], 3), ("leaf", [0, 1], 4),
               ("join", [0, 1, 4, 5], 7), ("proj", [0, 2, 3], 5)], 2),
    # a left outer join gathers the same way (NULL exactly where nothing
    # matched) but its unmatched rows are valid: no build slot is proved
    "left": ("select f.a, f.c, dm.k, dm.v, dm.w from f left join dm "
             "on f.fk = dm.k",
             [("leaf", [0, 1], 3), ("leaf", [0], 3), ("join", [0], 6),
              ("proj", [0], 5)], 2),
    # a semi join's view is its probe's
    "semi": ("select f.a, f.b from f where f.fk in "
             "(select k from dm where v > 300)",
             [("leaf", [0, 1], 2), ("proj", [0], 1), ("leaf", [0, 1], 3),
              ("join", [0, 1], 3), ("proj", [0, 1], 2)], 0),
    # the sorted aggregate: counts always; sum(b), avg(b), min(a) over
    # NULL-free arguments (their count is presence); not sum(c), avg(c);
    # not the key, which has a NULL group
    "sorted": ("select fk, count(*), count(c), sum(c), sum(b), avg(b), "
               "avg(c), min(a) from f group by fk",
               [("leaf", [0, 1], 4), ("agg", [0, 1, 3, 4, 6], 8),
                ("proj", [1, 2, 4, 5, 7], 8)], 0),
    # the dense one proves its counts alone: its sums keep their counts
    "dense": ("select tag, count(*), sum(b), max(c) from f group by tag",
              [("leaf", [0], 3), ("agg", [0], 4)], 0),
    # a keyed GROUP BY above a join: the partial sum(b), count and
    # max(b) arrive NULL-free through the inner join, so their merges
    # reduce no count of their own (3) and dm.v's null lane is not
    # gathered (1); sum(c) keeps everything; the key dm.v holds no NULL
    "keyed": ("select dm.v, count(*), sum(f.b), sum(f.c), max(f.b) from f "
              "join dm on f.fk = dm.k group by dm.v",
              [("leaf", [0, 1], 2), ("leaf", [0], 3),
               ("agg", [0, 1, 3], 5), ("join", [0, 1, 3, 5, 6], 7),
               ("keygroup", [0, 1, 3, 4], 5), ("proj", [0, 1, 2, 4], 5)],
              4),
    # a scalar aggregate's one row is valid over an empty input, where a
    # sum is NULL whatever its argument: counts alone are proved, and
    # sum(f.b) still takes the rows' count for its own
    "scalar": ("select count(*), sum(f.b), min(dm.w), count(f.c) from f "
               "join dm on f.fk = dm.k",
               [("leaf", [0], 2), ("leaf", [0], 3), ("join", [0, 3], 5),
                ("scalaragg", [0, 3], 4)], 1),
    # the CSR join (duplicate and NULL build keys): a matched slot reads
    # a valid build row, so dd.v goes up without its null lane, inner
    # and outer
    "csr": ("select f.a, dd.v, dd.label from f join dd on f.fk = dd.k "
            "where f.c < 60",
            [("leaf", [1], 3), ("leaf", [0], 3), ("join", [0, 4], 6)], 1),
    "csr_left": ("select f.a, dd.v from f left join dd on f.fk = dd.k",
                 [("leaf", [1], 2), ("leaf", [0], 2), ("join", [0], 4),
                  ("proj", [0], 2)], 1),
}


@pytest.mark.parametrize("case", sorted(FLAG_CASES))
def test_views_say_which_slots_hold_no_null(tk, flags, case):
    _live_tables(tk)
    sql, nodes, skipped = FLAG_CASES[case]
    got, want, delta = _dead_cols(tk, sql)
    assert _canon(got) == _canon(want) and got, sql
    assert delta["dispatches"] == 1
    assert flags == nodes
    assert delta.get("pipe_const_nulls", 0) == skipped


def test_sort_group_and_selection_hand_the_flags_on(tk, flags, monkeypatch):
    """The sorted GROUP BY above a view (the keyed one's range refused)
    and a selection above it: a key is NULL in a group only if it was on
    a row, and a filter only drops rows."""
    _live_tables(tk)
    monkeypatch.setattr(devpipe._KeyGroupNode, "cut_of",
                        staticmethod(lambda child, key_cols: None))
    sql = ("select dm.v, count(*), sum(f.b), sum(f.c) from f join dm "
           "on f.fk = dm.k group by dm.v having sum(f.b) > 0")
    got, want, delta = _dead_cols(tk, sql)
    assert _canon(got) == _canon(want) and got
    kinds = [k for k, _, _ in flags]
    assert "sortgroup" in kinds and "keygroup" not in kinds
    group = flags[kinds.index("sortgroup")]
    assert group == ("sortgroup", [0, 1, 3], 4)
    assert flags[kinds.index("sel")] == ("sel", [0, 1, 3], 4)
    assert delta["pipe_const_nulls"] == 3  # dm.v's lane, two counts


def _null_build_tables(tk):
    """``dn``: a unique build side whose column ``x`` holds NULLs on rows
    a filter on ``v`` keeps, ``y`` only on rows it drops, ``z`` none."""
    _live_tables(tk)
    rng = np.random.default_rng(17)
    k = np.arange(1, 301, dtype=np.int64)
    v = rng.integers(0, 1000, 300).astype(np.int64)
    _load(tk, "dn", "k bigint primary key, v bigint, x bigint, y bigint, "
                    "z bigint",
          {"k": (k, None), "v": (v, None),
           "x": (rng.integers(0, 9, 300).astype(np.int64),
                 rng.random(300) < 0.3),
           "y": (rng.integers(0, 9, 300).astype(np.int64), v <= 500),
           "z": (rng.integers(0, 9, 300).astype(np.int64), None)})


#: join type -> (a two-join chain over ``dn``, null lanes left out)
NULL_BUILD_CASES = {
    # (dn join wz) is the build view of the join f probes: x and y keep
    # their lanes; wz.z's goes below, dn.z's and wz.z's above
    "inner": ("select f.a, f.tag, dn.x, dn.y, dn.z, wz.z from f join dn "
              "on f.fk = dn.k join wz on dn.z = wz.g where dn.v > 500", 3),
    # z's lane off dn and wz.z's off wz; the second join's probe key
    # dn.z is NULL on the first's unmatched rows
    "left": ("select f.a, f.tag, dn.x, dn.y, dn.z, wz.z from f left join "
             "dn on f.fk = dn.k and dn.v > 500 left join wz "
             "on dn.z = wz.g", 2),
}


@pytest.mark.parametrize("tp", sorted(NULL_BUILD_CASES))
def test_a_build_column_with_nulls_keeps_its_lane(tk, tp):
    """A chain whose build columns DO hold NULLs, on a valid row (x) and
    on a filtered-out row alone (y: the leaf sees the column's mask, not
    the filter): both lanes are gathered and the answers are the CPU
    executors', NULLs and all; the lanes of z and wz.z are not."""
    _null_build_tables(tk)
    sql, skipped = NULL_BUILD_CASES[tp]
    got, want, delta = _dead_cols(tk, sql)
    assert _canon(got) == _canon(want) and got
    assert any(r[2] is None and r[4] is not None for r in got)
    assert any(r[2] is not None for r in got)
    assert (tp == "left") == any(r[3] is None for r in got)
    assert delta["dispatches"] == 1
    assert delta["pipe_const_nulls"] == skipped


def test_a_null_written_after_the_program_is_cached(tk):
    """The skipped lanes are part of the program's key: once a NULL is
    written into the build column, the next replica version's statement
    builds another program (it never meets the cached one that skipped
    the lane) and its answer shows the NULL."""
    from tinysql_tpu.ops import progcache
    _live_tables(tk)
    sql = ("select f.a, f.tag, dm.v from f join dm on f.fk = dm.k "
           "where f.b > 40")
    progcache.clear()
    got, want, delta = _dead_cols(tk, sql)
    assert _canon(got) == _canon(want) and got
    assert delta["pipe_const_nulls"] == 1 and delta["progcache_misses"] == 1
    assert not any(r[2] is None for r in got)
    (skipping,) = progcache.keys("pipe")
    assert (2, "nonnull", (1,)) in skipping[4]
    tk.execute("update dm set v = null where k < 200")
    for _ in range(3):  # the first may read past a replica being rebuilt
        got, want, delta = _dead_cols(tk, sql)
        assert _canon(got) == _canon(want)
        assert any(r[2] is None for r in got)
        if delta.get("pipe_joins"):  # the program ran, not the operators
            break
    assert delta["dispatches"] == 1 and delta["progcache_misses"] == 1
    assert delta.get("pipe_const_nulls", 0) == 0
    (keeping,) = [k for k in progcache.keys("pipe") if k != skipping]
    assert keeping[:4] == skipping[:4]
    assert not any("nonnull" in part for part in keeping[4][2:])

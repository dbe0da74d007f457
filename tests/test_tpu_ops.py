"""TPU-tier oracle tests: every device operator must produce byte-identical
results to the CPU tier on randomized data (SURVEY §4: "vec-vs-scalar
property tests become device-vs-numpy-oracle comparisons").  Runs on the
virtual CPU mesh in CI; the same kernels run unchanged on real TPU."""
import random

import numpy as np
import pytest

from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.utils.testkit import TestKit
from tinysql_tpu.ops import kernels


@pytest.fixture(scope="module")
def tks():
    """(tpu TestKit, cpu TestKit) over the SAME storage with random data."""
    storage = new_mock_storage()
    tpu = TestKit(storage)
    tpu.must_exec("create database test")
    tpu.must_exec("use test")
    tpu.must_exec("set @@tidb_tpu_min_rows = 0")  # tiny CI data on device
    tpu.must_exec("set @@tidb_devpipe = 1")
    cpu = TestKit(storage, "test")
    cpu.must_exec("set @@tidb_use_tpu = 0")

    rng = random.Random(42)
    tpu.must_exec("create table facts (id int primary key, g int, "
                  "h varchar(3), v int, r double)")
    rows = []
    for i in range(1, 501):
        g = rng.choice(["null"] + [str(x) for x in range(7)])
        h = rng.choice(["'aa'", "'bb'", "'cc'", "null"])
        v = rng.choice(["null"] + [str(rng.randint(-100, 100))])
        r = rng.choice(["null", f"{rng.uniform(-10, 10):.6f}", "0.0"])
        rows.append(f"({i}, {g}, {h}, {v}, {r})")
    for i in range(0, 500, 100):
        tpu.must_exec("insert into facts values " + ",".join(rows[i:i + 100]))

    tpu.must_exec("create table dims (g int, label varchar(8), w int)")
    drows = []
    for g in range(0, 9):
        for rep in range(rng.randint(0, 3)):
            drows.append(f"({g}, 'L{g}_{rep}', {rng.randint(0, 50)})")
    drows.append("(null, 'LNULL', 1)")
    tpu.must_exec("insert into dims values " + ",".join(drows))
    return tpu, cpu


def _canon(rows):
    """Row multiset with floats rounded to 9 significant digits — XLA and
    numpy legitimately differ by ~1 ulp on float arithmetic."""
    out = []
    for r in rows:
        key = []
        for v in r:
            if isinstance(v, float):
                if v == 0.0:
                    v = 0.0  # -0.0 == 0.0 in SQL; XLA/numpy sign differs
                key.append(f"{v:.9g}")
            else:
                key.append(repr(v))
        out.append(tuple(key))
    return sorted(out)


def both(tks, sql):
    tpu, cpu = tks
    a = _canon(tpu.must_query(sql).data)
    b = _canon(cpu.must_query(sql).data)
    assert a == b, f"TPU/CPU divergence for {sql!r}:\n tpu={a[:8]}\n cpu={b[:8]}"
    return a


def test_plan_uses_tpu(tks):
    tpu, _ = tks
    plan = tpu.must_query(
        "explain select g, sum(v) from facts group by g").as_str()
    assert any("HashAgg(TPU)" in r[0] for r in plan)
    plan = tpu.must_query(
        "explain select * from facts join dims on facts.g = dims.g").as_str()
    assert any("HashJoin(TPU)" in r[0] for r in plan)


def test_group_agg_int_keys(tks):
    both(tks, "select g, count(*), count(v), sum(v), avg(v), max(v), min(v), "
              "sum(r), avg(r), min(r), max(r) from facts group by g")


def test_group_agg_string_keys(tks):
    both(tks, "select h, count(*), sum(v) from facts group by h")
    both(tks, "select g, h, count(*), avg(r) from facts group by g, h")


def test_group_agg_expr_keys_and_args(tks):
    both(tks, "select v % 5, sum(v * 2 + 1), avg(r * r) from facts "
              "group by v % 5")


def test_agg_no_group_by(tks):
    both(tks, "select count(*), sum(v), avg(r), min(v), max(r) from facts")
    both(tks, "select count(*), sum(v) from facts where v > 1000")  # empty


def test_first_row_semantics(tks):
    # non-grouped select col -> first_row agg under the hood
    both(tks, "select g, h from facts where id = 77 group by g, h")


def test_joins_inner_outer(tks):
    both(tks, "select facts.id, dims.label, dims.w from facts "
              "join dims on facts.g = dims.g")
    both(tks, "select facts.id, dims.label from facts "
              "left join dims on facts.g = dims.g")
    both(tks, "select count(*) from facts join dims on facts.g = dims.g")
    # join + extra residual condition
    both(tks, "select facts.id, dims.w from facts join dims "
              "on facts.g = dims.g and facts.v > dims.w")
    both(tks, "select facts.id, dims.w from facts left join dims "
              "on facts.g = dims.g and facts.v > dims.w")
    # ON-clause condition on the OUTER side only: failing outer rows
    # null-extend instead of dropping
    both(tks, "select facts.id, dims.label from facts left join dims "
              "on facts.g = dims.g and facts.v > 50")


def test_sort_and_topn(tks):
    both(tks, "select id, v, r from facts order by v, r desc, id")
    both(tks, "select id from facts order by r desc, id limit 17")
    both(tks, "select id, h from facts order by h, id limit 23")  # string key
    both(tks, "select id from facts order by v desc, id limit 5, 11")


def test_projection_selection_device(tks):
    both(tks, "select id, v + 1, v * r, -v, abs(v) from facts where v is not null")
    both(tks, "select id from facts where v > 0 and r < 5.0")
    both(tks, "select id, if(v > 0, v, -v), ifnull(v, 0) from facts")
    both(tks, "select id, case when v > 50 then 1 when v > 0 then 2 else 3 end "
              "from facts where v is not null")
    both(tks, "select id from facts where v in (1, 2, 3, null)")
    both(tks, "select v / 0, v div 0, v % 0 from facts where id = 1")


def test_agg_over_join(tks):
    both(tks, "select dims.label, count(*), sum(facts.v) from facts "
              "join dims on facts.g = dims.g group by dims.label")


def test_int_sum_overflow_wraps_device():
    s = new_mock_storage()
    tpu = TestKit(s)
    tpu.must_exec("create database test; use test")
    tpu.must_exec("create table o (v bigint)")
    tpu.must_exec("insert into o values (9223372036854775807), (1)")
    cpu = TestKit(s, "test")
    cpu.must_exec("set @@tidb_use_tpu = 0")
    a = tpu.must_query("select sum(v) from o").as_str()
    b = cpu.must_query("select sum(v) from o").as_str()
    assert a == b  # two's-complement wrap on both tiers


# ---- kernel-level direct tests ---------------------------------------------

def test_kernel_group_aggregate_direct():
    n = 1000
    rng = np.random.RandomState(7)
    keys = rng.randint(0, 10, n).astype(np.int64)
    knull = rng.rand(n) < 0.1
    vals = rng.randint(-50, 50, n).astype(np.int64)
    vnull = rng.rand(n) < 0.2
    out_keys, out_aggs, first = kernels.group_aggregate(
        [(keys, knull)], [("count_star", False), ("sum", True)],
        [(vals, vnull)], n)
    # numpy oracle
    import collections
    groups = collections.defaultdict(lambda: [0, 0, False])
    for i in range(n):
        k = None if knull[i] else int(keys[i])
        g = groups[k]
        g[0] += 1
        if not vnull[i]:
            g[1] += int(vals[i])
            g[2] = True
    got = {}
    kv, km = out_keys[0]
    (cv, _), (sv, sm) = out_aggs
    for r in range(len(first)):
        k = None if km[r] else int(kv[r])
        got[k] = (int(cv[r]), None if sm[r] else int(sv[r]))
    want = {k: (g[0], g[1] if g[2] else None) for k, g in groups.items()}
    assert got == want


def test_kernel_join_match_direct():
    rng = np.random.RandomState(3)
    lk = rng.randint(0, 20, 300).astype(np.int64)
    ln = rng.rand(300) < 0.1
    rk = rng.randint(0, 20, 100).astype(np.int64)
    rn = rng.rand(100) < 0.1
    li, ri = kernels.join_match((lk, ln), 300, (rk, rn), 100)
    got = sorted(zip(li.tolist(), ri.tolist()))
    want = sorted((i, j) for i in range(300) for j in range(100)
                  if not ln[i] and not rn[j] and lk[i] == rk[j])
    assert got == want
    # outer
    li, ri = kernels.join_match((lk, ln), 300, (rk, rn), 100, outer=True)
    matched = {i for i, j in want}
    want_outer = want + [(i, -1) for i in range(300) if i not in matched]
    assert sorted(zip(li.tolist(), ri.tolist())) == sorted(want_outer)


def test_kernel_unique_join_match_direct():
    # unique build side: each probe row has at most one match
    rng = np.random.RandomState(7)
    rk = np.arange(0, 50, dtype=np.int64)
    rng.shuffle(rk)
    rn = np.zeros(50, bool)
    rn[3] = True  # one NULL build key
    lk = rng.randint(-5, 55, 400).astype(np.int64)
    ln = rng.rand(400) < 0.1
    lv = rng.rand(400) < 0.8
    rv = rng.rand(50) < 0.8
    for outer in (False, True):
        li, ri = kernels.unique_join_match((lk, ln), 400, (rk, rn), 50,
                                           outer=outer, lvalid=lv,
                                           rvalid=rv)
        want = []
        for i in range(400):
            if not lv[i]:
                continue
            js = [j for j in range(50)
                  if rv[j] and not rn[j] and not ln[i] and rk[j] == lk[i]]
            if js:
                want.append((i, js[0]))
            elif outer:
                want.append((i, -1))
        assert sorted(zip(li.tolist(), ri.tolist())) == sorted(want)
    # sentinel collision: a DEAD build row must never match a probe of
    # int64 max even though both sort to the sentinel position
    big = np.iinfo(np.int64).max
    rk2 = np.array([big, 7], dtype=np.int64)
    rv2 = np.array([False, True])  # the max-key row is filtered out
    lk2 = np.array([big, 7], dtype=np.int64)
    li, ri = kernels.unique_join_match(
        (lk2, np.zeros(2, bool)), 2, (rk2, np.zeros(2, bool)), 2,
        rvalid=rv2)
    assert sorted(zip(li.tolist(), ri.tolist())) == [(1, 1)]
    # and a LIVE max-valued key still matches
    rv3 = np.array([True, True])
    li, ri = kernels.unique_join_match(
        (lk2, np.zeros(2, bool)), 2, (rk2, np.zeros(2, bool)), 2,
        rvalid=rv3)
    assert sorted(zip(li.tolist(), ri.tolist())) == [(0, 0), (1, 1)]


def test_kernel_topk_fast_path_direct():
    rng = np.random.RandomState(11)
    for dtype in (np.int64, np.float64):
        v = (rng.randint(-1000, 1000, 5000).astype(dtype)
             if dtype == np.int64 else rng.randn(5000) * 100)
        m = rng.rand(5000) < 0.1
        for desc in (False, True):
            fast = kernels._topk_single((v, m), desc, 5000, 17)
            assert fast is not None
            slow = kernels.sort_permutation([(v, m)], [desc], 5000)[:17]
            def keyf(i):
                return (m[i] != desc, v[i] if not m[i] else 0)
            # same KEYS in the same order (tie rows may differ by index)
            want = [keyf(i) for i in slow]
            got = [keyf(i) for i in fast]
            if desc:
                assert [(a, -b) for a, b in got] == [(a, -b)
                                                     for a, b in want]
            else:
                assert got == want
    # int64 extremes fall back to the exact sort path
    ext = np.array([np.iinfo(np.int64).min, 0, 5], dtype=np.int64)
    assert kernels._topk_single((ext, np.zeros(3, bool)), True, 3, 2) is None
    # k beyond row count trims to real rows
    v = np.arange(10, dtype=np.int64)
    ids = kernels.top_k([(v, np.zeros(10, bool))], [True], 10, 30)
    assert sorted(ids.tolist()) == list(range(10))
    # LIMIT 0: empty result, no partition crash
    assert kernels.top_k([(v, np.zeros(10, bool))], [True], 10, 0).size == 0


def test_kernel_sort_permutation_direct():
    rng = np.random.RandomState(5)
    a = rng.randint(-5, 5, 200).astype(np.int64)
    an = rng.rand(200) < 0.15
    b = rng.rand(200)
    bn = rng.rand(200) < 0.15
    perm = kernels.sort_permutation([(a, an), (b, bn)], [False, True], 200)
    def key(i):
        ka = (0, 0) if an[i] else (1, a[i])
        kb = (1, -b[i]) if not bn[i] else (2, 0)  # desc, NULL last
        return (ka, kb)
    want = sorted(range(200), key=key)
    # compare by key equivalence (stable order between equal keys may differ)
    assert [key(i) for i in perm] == [key(i) for i in want]


def test_topk_multi_key_matches_full_sort():
    """Multi-key top-k (primary-threshold candidate selection) must equal
    the full sort + slice bit-for-bit, including NULL ordering, ties, and
    stability (kernels._topk_multi; VERDICT r4 next-8 operator bench)."""
    import numpy as np
    from tinysql_tpu.ops import kernels
    rng = np.random.default_rng(3)
    n = 30000
    a = rng.integers(0, 50, n).astype(np.int64)       # heavy ties
    am = rng.random(n) < 0.1                          # NULL primaries
    c = np.round(rng.random(n), 3)
    cm = rng.random(n) < 0.05
    for descs in ([False, False], [True, False],
                  [False, True], [True, True]):
        keys = [(a, am), (c, cm)]
        fast = kernels._topk_multi(keys, descs, n, 37)
        full = kernels.sort_permutation(keys, descs, n)[:37]
        assert fast is not None and np.array_equal(fast, full), descs
    # all-equal primary without nulls: degenerate ties fall back
    ae = np.zeros(n, dtype=np.int64)
    zm = np.zeros(n, dtype=bool)
    assert kernels._topk_multi([(ae, zm), (c, cm)],
                               [False, False], n, 10) is None
    # top_k public entry must still answer correctly through the fallback
    ids = kernels.top_k([(ae, zm), (c, cm)], [False, False], n, 10)
    full = kernels.sort_permutation([(ae, zm), (c, cm)],
                                    [False, False], n)[:10]
    assert np.array_equal(np.asarray(ids), np.asarray(full))


def test_np_unique_join_float_keys():
    """Float (and mixed-cast) join keys with NULLs through the unique
    join: every pair matches on a live key, and every live probe key in
    the build set is found."""
    import numpy as np
    from tinysql_tpu.ops import kernels
    rng = np.random.default_rng(4)
    lk = np.round(rng.random(5000) * 50, 1)
    ln = rng.random(5000) < 0.05
    rk = np.unique(np.round(rng.random(300) * 50, 1))
    rn = np.zeros(len(rk), dtype=bool)
    li, ri = kernels.unique_join_match((lk, ln), 5000, (rk, rn), len(rk))
    li, ri = np.asarray(li), np.asarray(ri)
    for a, b in zip(li.tolist(), ri.tolist()):
        assert not ln[a] and lk[a] == rk[b]
    want = sum(1 for i in range(5000) if not ln[i] and lk[i] in set(rk))
    assert len(li) == want


def test_np_join_expand_matches_device_contract():
    """The expansion join's (li, ri) pairs AND order against a brute-force
    reference: probe-major, and within a probe row the live build rows in
    stable key order; an outer join's valid probe row with no match
    appears once with -1.  Inner and outer, dense and sparse key ranges."""
    import numpy as np
    from tinysql_tpu.ops import kernels
    rng = np.random.default_rng(12)
    n, m = 3000, 400
    for sparse in (False, True):
        mult = (1 << 30) if sparse else 1
        lk = rng.integers(0, 60, n).astype(np.int64) * mult
        ln = rng.random(n) < 0.06
        lv = rng.random(n) < 0.9
        rk = rng.integers(0, 60, m).astype(np.int64) * mult  # duplicates
        rn = rng.random(m) < 0.06
        rv = rng.random(m) < 0.9
        r_live = rv & ~rn
        for outer in (False, True):
            want_l, want_r = [], []
            for i in range(n):
                hits = (np.nonzero(r_live & (rk == lk[i]))[0].tolist()
                        if lv[i] and not ln[i] else [])
                if outer and lv[i] and not hits:
                    hits = [-1]
                want_l += [i] * len(hits)
                want_r += hits
            li, ri = kernels.join_match((lk, ln), n, (rk, rn), m,
                                        outer=outer, lvalid=lv, rvalid=rv)
            assert np.asarray(li).tolist() == want_l, (sparse, outer)
            assert np.asarray(ri).tolist() == want_r, (sparse, outer)


def test_join_sentinel_collision_int64_max():
    """A LIVE build key equal to int64 max must match (and dead rows with
    the +max sentinel must not shadow it) in both join kernels."""
    import numpy as np
    from tinysql_tpu.ops import kernels
    mx = np.iinfo(np.int64).max
    lk = np.array([mx, 5], dtype=np.int64)
    ln = np.zeros(2, dtype=bool)
    rk = np.array([7, mx, 5], dtype=np.int64)
    rn = np.array([True, False, False])  # row 0 is a NULL key
    want = [(0, 1), (1, 2)]
    for fn in (kernels.join_match, kernels.unique_join_match):
        li, ri = fn((lk, ln), 2, (rk, rn), 3)
        got = sorted(zip(np.asarray(li).tolist(), np.asarray(ri).tolist()))
        assert got == want, (fn.__name__, got)


def test_device_join_kernels_sql_parity():
    """The device join kernels (unique, expansion, outer with ON, sorted
    build, semi, anti) answer SQL as the CPU tier does."""
    import numpy as np
    from tinysql_tpu.session.session import new_session
    from tinysql_tpu.columnar.store import bulk_load
    s = new_session()
    s.execute("create database dj")
    s.execute("use dj")
    s.execute("set @@tidb_tpu_min_rows = 0")
    rng = np.random.default_rng(55)
    n = 4096
    s.execute("create table f (id bigint primary key, k bigint, v double)")
    bulk_load(s.storage, s.infoschema().table_by_name("dj", "f"),
              {"id": np.arange(1, n + 1, dtype=np.int64),
               "k": rng.integers(1, 64, n).astype(np.int64),
               "v": np.round(rng.random(n) * 9, 2)})
    s.execute("create table d (k bigint primary key, t bigint)")
    bulk_load(s.storage, s.infoschema().table_by_name("dj", "d"),
              {"k": np.arange(1, 64, dtype=np.int64),
               "t": rng.integers(0, 5, 63).astype(np.int64)})
    s.execute("create table dup (id bigint primary key, k bigint, "
              "w bigint)")
    bulk_load(s.storage, s.infoschema().table_by_name("dj", "dup"),
              {"id": np.arange(1, 121, dtype=np.int64),
               "k": np.tile(np.arange(1, 41, dtype=np.int64), 3),
               "w": rng.integers(0, 9, 120).astype(np.int64)})
    for t in ("f", "d", "dup"):
        s.query(f"select * from {t}")
    qs = [
        "select d.t, count(*), sum(f.v) from f join d on f.k = d.k "
        "group by d.t order by d.t",                       # unique build
        "select f.id, dup.w from f join dup on f.k = dup.k "
        "order by f.id, dup.w limit 300, 15",              # expansion
        "select f.id, d.t from f left join d on f.k = d.k and d.t < 2 "
        "order by f.id limit 25",                          # outer + ON
        "select u.t, x.s from d u join (select k, sum(v) as s from f "
        "group by k) x on u.k = x.k order by x.s desc limit 9",  # sorted
        "select f.id from f where f.k in (select k from dup "
        "where w > 4) order by f.id limit 20",             # semi join
        "select count(*) from f where f.k not in (select k from d "
        "where t = 3)",                                    # anti join
    ]
    def canon(rows):
        return sorted(tuple(f"{v:.9g}" if isinstance(v, float) else str(v)
                            for v in r) for r in rows)
    for q in qs:
        s.execute("set @@tidb_use_tpu = 1")
        dev = s.query(q).rows
        s.execute("set @@tidb_use_tpu = 0")
        cpu = s.query(q).rows
        s.execute("set @@tidb_use_tpu = 1")
        assert canon(dev) == canon(cpu), q

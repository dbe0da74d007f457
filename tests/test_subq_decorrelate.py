"""Correlated scalar-aggregate subqueries decorrelated into a GROUP BY
joined back, each statement one fused device program: TPC-H's Q2 and Q17
at SF=0.02 with the benchmark's own generator and traffic file (the chip's
branches forced on the CPU), and the rewrite's semantics on small tables
against sqlite, which runs the correlated subqueries as they are written.

- Q2 and Q17 equal the benchmark's plain reference
  (``benchmark/datasets/tpch_subq.py``) row for row and the per-operator
  tier's answer; one dispatch each, a second run compiles nothing, no
  device sort; the counters read one a statement (``subq_decorrelated``,
  ``pipe_join_view_conds``) and nought for the six statements the
  benchmark sent before;
- the rewrite for MIN, MAX, SUM and AVG and arithmetic over one, an empty
  group and a NULL correlation key (the row drops out), Q17's exact ties
  planted (a part averaging 25.0 exactly and a line of quantity 5: not
  kept), the shapes refused by name (a correlated COUNT among them) and
  an uncorrelated scalar subquery still folded at plan time;
- LIKE over a dictionary-coded column on the device against the host's.
"""
import importlib.util
import json
import math
import os
import sqlite3

import numpy as np
import pytest

from tinysql_tpu.columnar.store import bulk_load
from tinysql_tpu.executor import devpipe, tpu_executors
from tinysql_tpu.ops import exactcmp, kernels, progcache
from tinysql_tpu.planner.builder import UnknownColumn, UnsupportedSubquery
from tinysql_tpu.session.session import new_session

SF = 0.02
SEED = 2350000047
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ["q2", "q17"]


def _bench_file(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "subq_" + "_".join(parts).replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def subq():
    """(session, {kind: (sql, reference rows)}, {kind: sql} of the six
    statements of the other cells): the benchmark's own tables at
    SF=0.02 and the cell's two statements, as its traffic file spells
    them."""
    data = _bench_file("datasets", "tpch_subq.py")
    traffic = _bench_file("traffic.py")
    statements = {}
    for name in ("subq_stream", "join_stream", "power_stream"):
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               name + ".json")) as f:
            statements[name] = traffic.expand(json.load(f))
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
            for st in statements["subq_stream"]}
    others = {st.kind: st.sql for name in ("join_stream", "power_stream")
              for st in statements[name]}
    yield s, want, others


def _same(got, want, rel=1e-9):
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
def test_equals_the_plain_reference(subq, kind):
    s, want, _ = subq
    sql, ref = want[kind]
    assert ref and ref[0][0] is not None, "the reference answers nothing"
    _same(s.query(sql).rows, [list(r) for r in ref])


@pytest.mark.parametrize("kind", KINDS)
def test_equals_the_per_operator_tier(subq, kind):
    s, want, _ = subq
    sql = want[kind][0]
    fused = s.query(sql).rows
    s.execute("set @@tidb_devpipe = 0")
    try:
        _same(fused, s.query(sql).rows)
    finally:
        s.execute("set @@tidb_devpipe = 1")


@pytest.mark.parametrize("kind", KINDS)
def test_one_program_and_a_second_run_compiles_nothing(subq, kind):
    s, want, _ = subq
    sql = want[kind][0]
    s.query(sql)
    _rows, d = _counted(s, sql)
    assert d["dispatches"] == 1 and d.get("progcache_misses", 0) == 0
    assert d["d2h_transfers"] == 1 and d.get("h2d_bytes", 0) == 0


@pytest.mark.parametrize("kind", KINDS)
def test_no_device_sort_in_the_program(subq, kind, monkeypatch):
    """Traced afresh, the program holds no sort GROUP BY and calls no
    sort kernel: the decorrelated aggregate is the group index's."""
    s, want, _ = subq
    used = []
    for cls in (devpipe._SortGroupNode, devpipe._HostLeaf):
        monkeypatch.setattr(cls, "prepare",
                            lambda self, *a, cls=cls, **k: used.append(cls))
    monkeypatch.setattr(kernels, "_sort_kernel",
                        lambda *a, **k: used.append("sort"))
    progcache.clear()
    monkeypatch.setattr(devpipe, "COMPILED_NODE_KEYS", set())
    rows, d = _counted(s, want[kind][0])
    assert not used and d["dispatches"] == 1 and rows
    kinds = {k[0] for k in devpipe.COMPILED_NODE_KEYS}
    assert "aggindex" in kinds and "sortgroup" not in kinds, kinds


@pytest.mark.parametrize("kind, expect", [
    # min(ps_supplycost) by ps_partkey over partsupp, stored in that
    # order, through three semi joins; the comparison a selection above
    # the join with the aggregate's view
    ("q2", {"subq_decorrelated": 1, "pipe_join_view_conds": 1,
            "agg_sorted": 1, "agg_clustered": 1}),
    # sum and count of l_quantity by l_partkey: lineitem is stored by
    # l_orderkey, so the index is not clustered (agg_sorted less
    # agg_clustered reads it)
    ("q17", {"subq_decorrelated": 1, "pipe_join_view_conds": 1,
             "agg_sorted": 1, "agg_clustered": 0}),
])
def test_counters_read_one_a_statement(subq, kind, expect):
    s, want, _ = subq
    _rows, d = _counted(s, want[kind][0])
    assert {k: d.get(k, 0) for k in expect} == expect


@pytest.mark.parametrize("key", ["subq_decorrelated", "pipe_join_view_conds"])
def test_counters_on_metrics(subq, key):
    """``/metrics`` carries each counter as ``tinysql_<key>_total``, one
    more for each of the two statements."""
    from tinysql_tpu.obs import metrics

    def total():
        return [float(line.split()[1])
                for line in metrics.render_prometheus().splitlines()
                if line.startswith(f"tinysql_{key}_total ")]
    s, want, _ = subq
    before = total()
    for kind in KINDS:
        s.query(want[kind][0])
    after = total()
    assert before and after and after[0] - before[0] == len(KINDS)


@pytest.mark.parametrize("kind", ["q1", "q3", "q6", "q5", "q10", "q18"])
def test_the_other_cells_statements_count_nought(subq, kind):
    s, _, others = subq
    _rows, d = _counted(s, others[kind])
    assert d.get("subq_decorrelated", 0) == 0
    assert d.get("pipe_join_view_conds", 0) == 0


def test_q17_decides_its_comparison_in_integers(subq, monkeypatch):
    """``l_quantity < 0.2 * (sum / count)``: whole numbers the replica
    proves exact, so the fused program decides it as IEEE doubles do
    (ops/exactcmp.py), not by the device's pair of floats."""
    s, want, _ = subq
    calls = []
    compare = exactcmp.compare
    monkeypatch.setattr(exactcmp, "compare",
                        lambda jn, op, *a: calls.append(op)
                        or compare(jn, op, *a))
    progcache.clear()
    s.query(want["q17"][0])
    assert calls == ["<"]


@pytest.mark.parametrize("kind, shown", [
    ("q17", "other cond:[<("), ("q17", "funcs:sum(col#"),
    ("q2", "semi join"), ("q2", "funcs:min(")])
def test_explain_shows_the_rewrite(subq, kind, shown):
    s, want, _ = subq
    rows = s.query("explain " + want[kind][0]).rows
    assert any(shown in str(r[3]) for r in rows), rows


@pytest.mark.parametrize("kind", KINDS)
def test_explain_analyze_shows_the_counters(subq, kind):
    s, want, _ = subq
    rows = s.query("explain analyze " + want[kind][0]).rows
    text = " ".join(str(r) for r in rows)
    assert "decorrelated:1" in text and "view_conds:1" in text, rows


def test_q2s_aggregate_stands_on_partsupp_under_semi_joins(subq):
    """The subquery's joins only filter partsupp: each became a semi join
    (``rules_extra.semi_join_filters``), and the aggregate is one GROUP
    BY by ps_partkey, not a partial one below a join and a final above."""
    s, want, _ = subq
    rows = [(r[0].strip(), r[3]) for r in
            s.query("explain " + want["q2"][0]).rows]
    at = next(i for i, (op, info) in enumerate(rows)
              if op.startswith("HashAgg") and "min(" in info)
    below = rows[at + 1:]
    assert sum(info.startswith("semi join") for _, info in below) == 3
    assert not any(op.startswith("HashAgg") for op, _ in below)
    assert not any(info.startswith("inner join") for _, info in below)


# ---- the rewrite's semantics on small tables, against sqlite ----------------

@pytest.fixture(scope="module")
def small():
    """A session and a sqlite connection holding the same two tables:
    ``o`` (the outer query's) and ``i`` (the subquery's), correlated on
    ``k``: keys with no inner row, NULL keys on both sides, whole-number
    values whose averages fall on exact ties, and a column of text."""
    rng = np.random.default_rng(47)
    n_o, n_i = 400, 4000
    o = {"id": np.arange(1, n_o + 1, dtype=np.int64),
         "k": rng.integers(1, 60, n_o).astype(np.int64),
         "x": rng.integers(0, 40, n_o).astype(np.float64),
         "y": np.round(rng.random(n_o) * 100, 2)}
    # keys 3..49 drawn, 50..59 have no inner row; group 1 averages 25.0
    # exactly (0.2 of it 5.0) and group 2 10.0 (2.0): outer rows of key 1
    # at 4, 5, 6 and of key 2 at 1, 2, 3 straddle the ties
    i = {"id": np.arange(1, n_i + 1, dtype=np.int64),
         "k": rng.integers(3, 50, n_i).astype(np.int64),
         "v": rng.integers(1, 60, n_i).astype(np.float64),
         "w": np.round(rng.random(n_i) * 100, 2)}
    i["k"][:24] = [1] * 8 + [2] * 16
    i["v"][:24] = [5, 45, 25, 25, 20, 30, 10, 40] + [10] * 16
    o["k"][:6], o["x"][:6] = [1, 1, 1, 2, 2, 2], [4, 5, 6, 1, 2, 3]
    o_null = rng.random(n_o) < 0.05
    i_null = rng.random(n_i) < 0.05
    o_null[:6] = i_null[:24] = False
    words = np.array(["LARGE BRASS", "SMALL TIN", "ECONOMY BRASS", "a_b",
                      "a%b", "axb", "abc", "", "BRASSY", "MEDIUM PLATED"])
    t = words[rng.integers(0, len(words), n_o)]
    t_null = rng.random(n_o) < 0.1
    s = new_session()
    s.execute("create database d")
    s.execute("use d")
    s.execute("set @@tidb_tpu_min_rows = 0")
    s.execute("set @@tidb_devpipe = 1")
    s.execute("create table o (id bigint primary key, k bigint, x double, "
              "y double, t varchar(16))")
    s.execute("create table i (id bigint primary key, k bigint, v double, "
              "w double)")
    bulk_load(s.storage, s.infoschema().table_by_name("d", "o"),
              {**o, "t": t}, nulls={"k": o_null, "t": t_null})
    bulk_load(s.storage, s.infoschema().table_by_name("d", "i"), i,
              nulls={"k": i_null})
    lite = sqlite3.connect(":memory:")
    lite.execute("create table o (id integer primary key, k integer, "
                 "x real, y real, t text)")
    lite.execute("create table i (id integer primary key, k integer, "
                 "v real, w real)")
    lite.executemany("insert into o values (?, ?, ?, ?, ?)", [
        (int(o["id"][j]), None if o_null[j] else int(o["k"][j]),
         float(o["x"][j]), float(o["y"][j]),
         None if t_null[j] else str(t[j])) for j in range(n_o)])
    lite.executemany("insert into i values (?, ?, ?, ?)", [
        (int(i["id"][j]), None if i_null[j] else int(i["k"][j]),
         float(i["v"][j]), float(i["w"][j])) for j in range(len(i["id"]))])
    yield s, lite


def _ids(s, sql):
    return [int(r[0]) for r in s.query(sql).rows]


@pytest.mark.parametrize("where", [
    "x < (select 0.2 * avg(v) from i where i.k = o.k)",
    "x + 20 > (select min(v) from i where i.k = o.k)",
    "x * 2 >= (select max(v) from i where o.k = i.k)",
    "x * 40 < (select sum(v) from i where i.k = o.k)",
    "x = (select min(v) from i where i.k = o.k and i.w > 30)",
    "(select avg(v) * 2 + 1 from i where i.k = o.k) > x",
    "x <= (select avg(v) / 5 from i where i.k = o.k and i.w < 70) "
    "and o.id > 3",
    "x != (select 0.2 * avg(v) from i where i.k = o.k)",
])
def test_equals_sqlite(small, where):
    """MIN, MAX, SUM, AVG and arithmetic over one, either side of the
    comparison, with a local filter in the subquery: the same rows as
    sqlite's correlated evaluation, one fused program, one rewrite."""
    s, lite = small
    sql = f"select id from o where {where} order by id"
    snap = kernels.stats_snapshot()
    got = _ids(s, sql)
    d = kernels.stats_delta(snap)
    want = [r[0] for r in lite.execute(sql)]
    assert got == want and want
    assert d["dispatches"] == 1 and d["subq_decorrelated"] == 1


def test_planted_ties_are_decided_as_ieee_doubles(small):
    """Group 1 averages 25.0 exactly: 0.2 of it is 5.0, and the outer row
    at 5 is not below it (nor 2 below group 2's 2.0); 4 and 1 are."""
    s, lite = small
    sql = ("select id from o where id <= 6 and x < (select 0.2 * avg(v) "
           "from i where i.k = o.k) order by id")
    assert _ids(s, sql) == [r[0] for r in lite.execute(sql)] == [1, 4]
    assert 0.2 * (200.0 / 8) == 5.0 and 0.2 * (160.0 / 16) == 2.0


def test_empty_group_and_null_key_drop_the_row(small):
    """An outer key with no inner row (50..59) and a NULL outer key
    compare with NULL: no such row is kept, for any comparison."""
    s, lite = small
    for op in ("<", ">=", "!="):
        sql = (f"select id from o where x {op} (select max(v) from i "
               f"where i.k = o.k) order by id")
        got = _ids(s, sql)
        assert got == [r[0] for r in lite.execute(sql)]
        keys = dict(lite.execute("select id, k from o").fetchall())
        assert all(keys[r] is not None and keys[r] < 50 for r in got)


@pytest.mark.parametrize("sql, shape", [
    ("select id from o where x < (select count(*) from i where i.k = o.k)",
     "COUNT"),
    ("select id from o where x < (select max(v) from i where i.k < o.k)",
     "equality"),
    ("select id, (select max(v) from i where i.k = o.k) from o",
     "correlated subquery other than"),
    ("select id from o where x < 0 or x < (select max(v) from i "
     "where i.k = o.k)", "correlated subquery other than"),
    ("select id from o where x < (select max(v) from i where i.k = o.k "
     "and i.id in (select id from i where i.k = o.id))",
     "correlated subquery other than"),
    ("select id from o where x <=> (select max(v) from i where i.k = o.k)",
     "<=>"),
    ("select id from o where x < (select max(v) from i where i.k = o.k "
     "group by i.w)", "GROUP BY"),
    ("select id from o where x < (select max(v) + o.y from i "
     "where i.k = o.k)", "select list"),
])
def test_shapes_not_taken_are_refused_by_name(small, sql, shape):
    s, _ = small
    with pytest.raises(UnsupportedSubquery) as err:
        s.query(sql)
    assert not isinstance(err.value, UnknownColumn)
    assert shape in str(err.value) and err.value.mysql_code == 1235


def test_uncorrelated_scalar_subquery_is_still_folded(small):
    s, lite = small
    sql = "select id from o where x < (select avg(v) from i) order by id"
    plan = " ".join(str(r) for r in s.query("explain " + sql).rows)
    snap = kernels.stats_snapshot()
    assert _ids(s, sql) == [r[0] for r in lite.execute(sql)]
    assert kernels.stats_delta(snap).get("subq_decorrelated", 0) == 0
    assert "HashJoin" not in plan and "HashAgg" not in plan


def test_a_join_that_gives_a_column_stays_inner(small):
    """``semi_join_filters`` leaves a join whose other side is not
    unique on its key (``o.k``): the inner join multiplies the rows."""
    s, lite = small
    sql = ("select i.k, sum(i.v) from i, o where i.k = o.k group by i.k "
           "order by i.k")
    plan = [r[3] for r in s.query("explain " + sql).rows]
    assert not any(p.startswith("semi join") for p in plan)
    got = [(r[0], float(r[1])) for r in s.query(sql).rows]
    assert got == [(k, float(v)) for k, v in lite.execute(sql)]


def test_a_filter_only_join_becomes_semi(small):
    s, lite = small
    sql = ("select i.v, max(i.w) from i, o where i.k = o.id "
           "and o.x > 10 group by i.v order by i.v")
    plan = [r[3] for r in s.query("explain " + sql).rows]
    assert any(p.startswith("semi join") for p in plan), plan
    got = [(r[0], float(r[1])) for r in s.query(sql).rows]
    assert got == [(k, float(v)) for k, v in lite.execute(sql)] and got


# ---- LIKE on dictionary codes -------------------------------------------

@pytest.mark.parametrize("pattern", [
    "LARGE%", "%BRASS", "%AL%", "a_b", "a\\\\%b", "zzz%", "%", "",
    "%BRASS_"])
@pytest.mark.parametrize("negated", [False, True])
def test_like_on_dictionary_codes(small, monkeypatch, pattern, negated):
    """A prefix, a suffix, an infix, ``_``, an escaped ``%``, a pattern
    no entry matches; NULLs in neither set: the device's code-set
    membership keeps the rows the host's ``like`` keeps."""
    s, lite = small
    made = []
    code_set = tpu_executors._code_set_fn
    monkeypatch.setattr(tpu_executors, "_code_set_fn",
                        lambda *a: made.append(a) or code_set(*a))
    op = "not like" if negated else "like"
    sql = (f"select t, count(*) from o where t {op} '{pattern}' "
           f"group by t order by t")
    progcache.clear()
    got = [tuple(r) for r in s.query(sql).rows]
    assert made, "the LIKE did not run on the device"
    s.execute("set @@tidb_use_tpu = 0")
    try:
        host = [tuple(r) for r in s.query(sql).rows]
    finally:
        s.execute("set @@tidb_use_tpu = 1")
    assert got == host
    lite.execute("pragma case_sensitive_like = on")
    plain = pattern.replace("\\\\", "\\")
    escape = " escape '\\'" if "\\" in plain else ""
    want = lite.execute(
        f"select t, count(*) from o where t {op} '{plain}'{escape} "
        f"group by t order by t").fetchall()
    assert [(str(t), int(n)) for t, n in got] == \
        [(str(t), int(n)) for t, n in want]


# ---- the exact comparison against numpy's doubles -----------------------

OPS = {"<": np.less, "<=": np.less_equal, ">": np.greater,
       ">=": np.greater_equal, "=": np.equal, "!=": np.not_equal}


class _RoughFloor:
    """jax.numpy with a floor that is off: a quotient of doubles taken
    as the TPU's pair of floats may be a few units off its last place."""

    def __init__(self, jn):
        self.jn = jn

    def __getattr__(self, name):
        return getattr(self.jn, name)

    def floor(self, v):
        jn = self.jn
        at = jn.arange(v.shape[0]) % 4
        scale = jn.where(at == 0, 1 + 3e-14, jn.where(at == 1, 1 - 3e-14,
                                                      1.0))
        return jn.floor(v * scale + jn.where(at == 2, 0.7, 0.0))


@pytest.mark.parametrize("k", [0.2, 0.3, 1 / 3, -7.5, 1e-5, 2.0 ** 59])
@pytest.mark.parametrize("rough", [False, True])
def test_exact_comparison_equals_ieee_doubles(k, rough):
    import jax
    jn = kernels.jnp()
    rng = np.random.default_rng(int(abs(k) * 1000) % 997)
    n = 40000
    c = rng.integers(0, 3000, n)
    x = rng.integers(-60, 61, n)
    tie = np.round(x * np.maximum(c, 1) / k).astype(np.int64) \
        if 1e-3 < abs(k) < 1e3 else 5 * x * c
    s = np.where(rng.random(n) < 0.5, tie,
                 rng.integers(-(1 << 39), 1 << 39, n))
    s = np.clip(s, 1 - (1 << 40), (1 << 40) - 1)
    with np.errstate(all="ignore"):
        t = k * (s.astype(np.float64) / c.astype(np.float64))
    mod = _RoughFloor(jn) if rough else jn
    for op, fn in OPS.items():
        v, null = jax.jit(lambda x, s, c: exactcmp.compare(
            mod, op, x, s, c, *exactcmp.split_double(k)))(x, s, c)
        assert (np.asarray(null) == (c == 0)).all()
        want = fn(x.astype(np.float64), t) & (c != 0)
        assert (np.asarray(v) == want).all(), op

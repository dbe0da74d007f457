"""Block-wise device execution (SURVEY §5.7, VERDICT r3 #9): with
tidb_device_block_rows capping the per-upload block, tables larger than
the budget stream through the device in row blocks with partial states
carried on host between blocks — results must match the CPU tier
exactly, and the dispatch count must show one program run per block."""
import numpy as np
import pytest

from tinysql_tpu.columnar.store import bulk_load
from tinysql_tpu.ops import kernels
from tinysql_tpu.session.session import new_session

N = 5000
BLOCK = 512


@pytest.fixture
def tk():
    s = new_session()
    s.execute("create database bw")
    s.execute("use bw")
    s.execute("set @@tidb_tpu_min_rows = 0")
    rng = np.random.default_rng(31)
    flag = np.array(["A", "N", "R"])[rng.integers(0, 3, N)]
    status = np.array(["O", "F"])[rng.integers(0, 2, N)]
    qty = rng.random(N) * 50
    price = rng.random(N) * 1000
    disc = rng.integers(0, 11, N) * 0.01
    ship = np.array([f"1998-{m:02d}-{d:02d}" for m, d in
                     zip(rng.integers(1, 13, N), rng.integers(1, 29, N))])
    s.execute("create table li (id bigint primary key, flag varchar(1), "
              "status varchar(1), qty double, price double, disc double, "
              "ship varchar(10))")
    info = s.infoschema().table_by_name("bw", "li")
    bulk_load(s.storage, info,
              {"id": np.arange(1, N + 1, dtype=np.int64), "flag": flag,
               "status": status, "qty": qty, "price": price, "disc": disc,
               "ship": ship})
    return s


def _both(s, q):
    s.execute("set @@tidb_use_tpu = 1")
    s.execute(f"set @@tidb_device_block_rows = {BLOCK}")
    snap = kernels.stats_snapshot()
    a = s.query(q).rows
    d = kernels.stats_delta(snap)
    s.execute("set @@tidb_device_block_rows = 0")
    s.execute("set @@tidb_use_tpu = 0")
    b = s.query(q).rows
    s.execute("set @@tidb_use_tpu = 1")
    return a, b, d


def _canon(rows):
    return sorted(tuple("N" if v is None
                        else (f"{v:.9g}" if isinstance(v, float)
                              else str(v)) for v in r) for r in rows)


def assert_match(a, b, q):
    assert _canon(a) == _canon(b), (q, a[:3], b[:3])


def test_q1_shape_blockwise(tk):
    q = ("select flag, status, sum(qty), sum(price), "
         "sum(price * (1 - disc)), avg(qty), avg(disc), count(*) "
         "from li where ship <= '1998-09-02' group by flag, status "
         "order by flag, status")
    a, b, d = _both(tk, q)
    assert_match(a, b, q)
    # one fused program per block (plus small fixed overhead programs)
    assert d["dispatches"] >= N // BLOCK, d


def test_q6_shape_blockwise_scalar(tk):
    q = ("select sum(price * disc) from li "
         "where ship >= '1998-03-01' and ship < '1998-06-01' "
         "and disc >= 0.03 and disc <= 0.07 and qty < 24")
    a, b, d = _both(tk, q)
    assert_match(a, b, q)
    assert d["dispatches"] >= N // BLOCK, d


def test_blockwise_min_max_and_nulls(tk):
    tk.execute("create table g (a bigint primary key, k bigint, "
               "x double, y bigint)")
    rng = np.random.default_rng(7)
    x = rng.random(N) * 100
    xnull = rng.random(N) < 0.15
    y = rng.integers(-50, 50, N).astype(np.int64)
    k = rng.integers(0, 9, N).astype(np.int64)
    info = tk.infoschema().table_by_name("bw", "g")
    bulk_load(tk.storage, info,
              {"a": np.arange(1, N + 1, dtype=np.int64), "k": k, "x": x,
               "y": y}, {"x": xnull})
    q = ("select k, min(x), max(x), min(y), max(y), count(x), sum(x) "
         "from g group by k order by k")
    a, b, d = _both(tk, q)
    assert_match(a, b, q)


def test_blockwise_empty_result(tk):
    q = "select sum(price), count(*) from li where qty > 1e9"
    a, b, _ = _both(tk, q)
    assert_match(a, b, q)  # COUNT 0, SUM NULL through the carry


def test_blockwise_matches_unblocked_device(tk):
    q = ("select flag, count(*), sum(price) from li group by flag "
         "order by flag")
    tk.execute("set @@tidb_use_tpu = 1")
    tk.execute(f"set @@tidb_device_block_rows = {BLOCK}")
    a = tk.query(q).rows
    tk.execute("set @@tidb_device_block_rows = 0")
    c = tk.query(q).rows
    assert _canon(a) == _canon(c)


def test_negative_budget_is_ignored(tk):
    """A negative tidb_device_block_rows must behave like 0 (unlimited),
    not silently return empty aggregates (round-4 review repro)."""
    q = "select count(*), sum(price) from li"
    tk.execute("set @@tidb_use_tpu = 1")
    tk.execute("set @@tidb_device_block_rows = -1")
    a = tk.query(q).rows
    tk.execute("set @@tidb_device_block_rows = 0")
    b = tk.query(q).rows
    assert a == b and a[0][0] == N, (a, b)


# ---- block-wise JOIN / TopN / Sort (VERDICT r4 next-3) -------------------

@pytest.fixture
def join_tk(tk):
    rng = np.random.default_rng(47)
    tk.execute("create table fact (id bigint primary key, k bigint, "
               "v double)")
    info = tk.infoschema().table_by_name("bw", "fact")
    bulk_load(tk.storage, info,
              {"id": np.arange(1, N + 1, dtype=np.int64),
               "k": rng.integers(1, 80, N).astype(np.int64),
               "v": np.round(rng.random(N) * 9, 2)})
    tk.execute("create table d (k bigint primary key, tag bigint)")
    info = tk.infoschema().table_by_name("bw", "d")
    bulk_load(tk.storage, info,
              {"k": np.arange(1, 80, dtype=np.int64),
               "tag": rng.integers(0, 6, 79).astype(np.int64)})
    tk.query("select * from fact")
    tk.query("select * from d")
    return tk


def test_blockwise_join_above_budget(join_tk):
    """fact (5000 rows) > budget (512): the probe side streams in blocks
    against the resident build table; the probe key column must never
    upload whole."""
    join_tk.execute("set @@tidb_devpipe = 0")  # the per-operator join
    q = ("select d.tag, count(*), sum(fact.v) from fact join d "
         "on fact.k = d.k group by d.tag order by d.tag")
    a, b, st = _both(join_tk, q)
    assert _canon(a) == _canon(b)
    # the join ran block-wise: >= ceil(5000/512) = 10 match dispatches
    assert st["dispatches"] >= 10, st


def test_blockwise_left_join(join_tk):
    q = ("select fact.id, d.tag from fact left join d "
         "on fact.k = d.k and d.tag < 3 order by fact.id limit 40")
    a, b, _ = _both(join_tk, q)
    assert _canon(a) == _canon(b)


def test_blockwise_topn_above_budget(join_tk):
    """TopN carries its candidate set across blocks: per-block top-k,
    merge, final k — identical rows AND order vs the CPU tier."""
    q = "select id, k, v from fact order by k desc, v, id limit 100, 25"
    a, b, _ = _both(join_tk, q)
    assert a == b  # exact order, not just set equality


def test_blockwise_full_sort_above_budget(join_tk):
    q = "select id, v from fact where k < 40 order by v desc, id limit 4500"
    a, b, _ = _both(join_tk, q)
    assert a == b


def test_blockwise_join_topn_pipeline(join_tk):
    """join + group-by + TopN over an above-budget table, all under the
    block budget (the VERDICT r4 next-3 'done' shape)."""
    q = ("select fact.k, sum(fact.v * (1 + d.tag)) as s from fact, d "
         "where fact.k = d.k group by fact.k order by s desc limit 7")
    a, b, _ = _both(join_tk, q)
    assert _canon(a) == _canon(b)

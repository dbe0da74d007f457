"""A join whose key -> row table is small beside its probe folds the
build's validity into that table (``devpipe._fold_validity``): the probe
reads ``tbl_v[pos0]`` and a row at or above 0 is a match, where the
unfolded join reads ``tbl[pos0]`` and then ``bvalid[pos]`` at every
probe row.  Whether a join folds is a function of the table's length,
the probe's bucket and the mesh's size alone (``_JoinNode._folded``:
the table holds at most half the probe's rows on a device).

- inner, left outer, semi and anti joins, with a filtered build and a
  filter-free one, NULL probe keys, probe keys outside the table's
  range and build rows the filter leaves out: the folded program's rows
  are the unfolded one's, bit for bit, and the host tier's;
- the multi-key unique join over its composite table does the same;
- a table of more than half the probe's rows on a device does not fold;
- one device and a forced four-device mesh.
"""
import math

import numpy as np
import pytest

from tinysql_tpu.columnar.store import bulk_load
from tinysql_tpu.executor import devpipe
from tinysql_tpu.ops import kernels
from tinysql_tpu.parallel import dist
from tinysql_tpu.session.session import new_session

#: the probe's rows: bucket 4096, a quarter of it a device on four
N_PROBE = 3000
#: the build's keys 1..N_BUILD: the table folds under the probe above
#: (200 <= 2048, and 200 <= 512 a device on four) ...
N_BUILD = 200
#: ... and not under a probe of this many rows (bucket 256: 200 > 128)
N_SMALL = 200


@pytest.fixture
def ftk(monkeypatch):
    """``p`` probes ``d`` by ``k`` (NULL on one row in ten, -5 to 260
    where ``d``'s keys are 1 to 200, so some fall outside the table's
    range and some inside it on no row); ``d.g`` filters a build, ``d.v``
    holds NULLs; ``e`` is a second build ``d`` joins into (a view
    build); ``s`` is a probe too small for ``d``'s table to fold; ``m``
    is keyed by the pair (a, b) for the multi-key join."""
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
    rng = np.random.default_rng(43)
    ids = np.arange(1, N_BUILD + 1, dtype=np.int64)
    # d's keys 1..200, every fifth one missing: keys that find no row
    dkeys = ids[ids % 5 != 0]
    nd = len(dkeys)
    tables = {
        "d": ("id bigint primary key, g bigint, v double, eid bigint",
              {"id": dkeys, "g": rng.integers(0, 6, nd),
               "v": np.round(rng.random(nd) * 100, 2),
               "eid": rng.integers(1, 31, nd)},
              {"v": rng.random(nd) < 0.1}),
        "e": ("id bigint primary key, w bigint",
              {"id": np.arange(1, 31, dtype=np.int64),
               "w": rng.integers(0, 9, 30)}, {}),
        "p": ("pid bigint primary key, k bigint, x double, a bigint, "
              "b bigint",
              {"pid": np.arange(1, N_PROBE + 1, dtype=np.int64),
               "k": rng.integers(-5, 261, N_PROBE),
               "x": np.round(rng.random(N_PROBE) * 10, 3),
               "a": rng.integers(1, 24, N_PROBE),
               "b": rng.integers(1, 14, N_PROBE)},
              {"k": rng.random(N_PROBE) < 0.1}),
        "s": ("pid bigint primary key, k bigint, x double",
              {"pid": np.arange(1, N_SMALL + 1, dtype=np.int64),
               "k": rng.integers(-5, 261, N_SMALL),
               "x": np.round(rng.random(N_SMALL) * 10, 3)},
              {"k": rng.random(N_SMALL) < 0.1}),
        "m": ("id bigint primary key, a bigint, b bigint, y double, "
              "g bigint, unique key uk (a, b)",
              {"id": np.arange(1, 241, dtype=np.int64),
               "a": np.repeat(np.arange(1, 21, dtype=np.int64), 12),
               "b": np.tile(np.arange(1, 13, dtype=np.int64), 20),
               "y": np.round(rng.random(240) * 50, 2),
               "g": rng.integers(0, 6, 240)}, {}),
    }
    for table, (columns, data, nulls) in tables.items():
        s.execute(f"create table {table} ({columns})")
        bulk_load(s.storage, s.infoschema().table_by_name("d", table),
                  {c: np.asarray(v) for c, v in data.items()}, nulls)
    yield s


def _counted(s, sql):
    snap = kernels.stats_snapshot()
    rows = s.query(sql).rows
    return rows, kernels.stats_delta(snap)


def _folded_and_not(s, sql, monkeypatch):
    """(rows, counters) of ``sql`` warm as the program finds its input,
    and again with the fold forced off."""
    s.query(sql)
    rows, d = _counted(s, sql)
    with monkeypatch.context() as m:
        m.setattr(devpipe._JoinNode, "_folded",
                  lambda self, tbl_len, nb, mesh: False)
        s.query(sql)
        off, d_off = _counted(s, sql)
    return rows, d, off, d_off


def _host(s, sql):
    s.execute("set @@tidb_devpipe = 0")
    s.execute("set @@tidb_use_tpu = 0")
    try:
        return s.query(sql).rows
    finally:
        s.execute("set @@tidb_use_tpu = 1")
        s.execute("set @@tidb_devpipe = 1")


def _same(got, want, rel=1e-9):
    assert len(got) == len(want), (len(got), len(want))
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(b, float):
                assert math.isclose(float(a), b, rel_tol=rel), (g, w)
            else:
                assert a == b, (g, w)


FILTERED = "(select id, v from d where g < 3)"

CASES = {
    # inner joins: a filtered build (rows the filter leaves out must not
    # match) and a filter-free one
    "inner_filtered": ("select count(*), sum(p.x), sum(t.v) from p join "
                       f"{FILTERED} t on p.k = t.id", 1),
    "inner_free": ("select count(*), sum(p.x), sum(d.v) from p join d "
                   "on p.k = d.id", 1),
    # a left outer join: every probe row, the build's columns NULL where
    # its key is NULL, outside the table, on no row or on a filtered one
    "left_filtered": ("select p.pid, p.k, t.v from p left join "
                      f"{FILTERED} t on p.k = t.id order by p.pid", 1),
    # semi and anti joins
    "semi": ("select count(*), sum(x) from p where k in (select id from "
             "d where g < 3)", 1),
    "anti": ("select count(*), sum(x) from p where not exists (select * "
             "from d where d.id = p.k and d.g < 3)", 1),
    # a join's view as the build: d's rows joined into e and filtered
    # on e, d's table over the view's rows; the join into e (30 keys
    # under d's 256 rows) folds too
    "view": ("select count(*), sum(p.x), sum(t.v) from p join (select "
             "d.id, d.v from d join e on d.eid = e.id where e.w > 3) t "
             "on p.k = t.id", 2),
    # the multi-key unique join over its composite (a, b) table
    "multikey": ("select count(*), sum(p.x), sum(m.y) from p join m "
                 "on p.a = m.a and p.b = m.b where m.g < 4", 1),
    # the other side of the rule: d's table against a probe of 200 rows
    "small_probe": ("select count(*), sum(s.x), sum(t.v) from s join "
                    f"{FILTERED} t on s.k = t.id", 0),
    "small_probe_left": ("select s.pid, t.v from s left join "
                         f"{FILTERED} t on s.k = t.id order by s.pid", 0),
}


@pytest.mark.parametrize("where", ["one", "mesh"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_folded_join_answers_bit_for_bit_as_the_unfolded_one(
        ftk, request, monkeypatch, case, where):
    """Each statement is ONE fused program; its rows equal the host
    tier's and, bit for bit, the unfolded program's; ``folded`` of its
    joins fold, and with the fold off none does."""
    sql, folded = CASES[case]
    if where == "mesh":
        request.getfixturevalue("four_devices")
        ftk.execute("set @@tidb_mesh_parallel = 1")
    try:
        rows, d, off, d_off = _folded_and_not(ftk, sql, monkeypatch)
    finally:
        ftk.execute("set @@tidb_mesh_parallel = 0")
    assert rows and rows == off
    _same(rows, _host(ftk, sql))
    assert d["dispatches"] == d_off["dispatches"] == 1
    assert d["pipe_joins"] == d_off["pipe_joins"] >= 1
    assert d.get("pipe_joins_folded", 0) == folded
    assert d_off.get("pipe_joins_folded", 0) == 0
    # each device folds its own copy of the table: nothing more moves
    assert d.get("reshard_bytes", 0) == d_off.get("reshard_bytes", 0)
    if case == "multikey":
        assert any(k[0] == "joinmk" and k[-1] == "fold"
                   for k in devpipe.COMPILED_NODE_KEYS)
    if "left" in case:
        assert any(r[-1] is None for r in rows)
        assert len(rows) == (N_SMALL if "small" in case else N_PROBE)


@pytest.mark.parametrize("tbl_len, nb, n, folds", [
    (150_000, 1 << 21, 0, True),       # customer under orders, one chip
    (150_000, 1 << 21, 4, True),       # the same over four chips
    (1_500_000, 1 << 24, 4, True),     # customer at SF=10, four chips
    (1_500_000, 1 << 23, 0, True),     # the view under lineitem
    (10_000, 1 << 23, 0, True),        # supplier under lineitem
    (15_000_000, 1 << 26, 4, False),   # the view at SF=10, four chips
    (1 << 20, 1 << 21, 0, True),       # half the probe's rows: folds
    ((1 << 20) + 1, 1 << 21, 0, False),
])
def test_the_rule_reads_shapes_alone(monkeypatch, tbl_len, nb, n, folds):
    """Fold where the table holds at most half the probe's rows on a
    device; the TPC-H joins of the benchmark's cells on each side."""
    mesh = dist.sized_mesh(n) if n else None
    node = object.__new__(devpipe._JoinNode)
    snap = kernels.stats_snapshot()
    assert node._folded(tbl_len, nb, mesh) is folds
    assert kernels.stats_delta(snap).get("pipe_joins_folded", 0) == folds


def test_folded_table_marks_invalid_rows_unmatched():
    """``_fold_validity`` keeps an entry whose build row is valid and
    makes -1 of one whose row is not, or that named no row."""
    jn = kernels.jnp()
    tbl = jn.asarray(np.array([3, -1, 0, 2, 1], dtype=np.int32))
    bvalid = jn.asarray(np.array([True, False, True, False]))
    got = np.asarray(devpipe._fold_validity(tbl, bvalid, 4))
    assert got.tolist() == [-1, -1, 0, 2, -1]
    assert got.dtype == np.int32


# ---- TPC-H at SF=0.05: the compile census's scale ---------------------------

@pytest.fixture(scope="module")
def tpch05():
    """(session, sqlite mirror): TPC-H at SF=0.05 with the fused pipeline
    forced, the scale at which ``test_chip_compile`` counts the gathers."""
    from tinysql_tpu.bench import tpch
    prev_floor = dist.MIN_SHARD_ROWS
    dist.MIN_SHARD_ROWS = 16
    data = tpch.generate(0.05)
    s = new_session()
    tpch.load(s, data=data)
    s.execute("set @@tidb_devpipe = 1")
    s.execute("set @@tidb_tpu_min_rows = 64")
    yield s, tpch.sqlite_mirror(data)
    dist.MIN_SHARD_ROWS = prev_floor


@pytest.mark.parametrize("where, name, folded", [
    ("one", "Q3", 1), ("one", "Q5", 4), ("one", "Q10", 2), ("one", "Q18", 1),
    # a device's 2^17 of lineitem's rows against the orders view's 75,000
    # keys: Q5's view join does not fold over four devices
    ("mesh", "Q3", 1), ("mesh", "Q5", 3), ("mesh", "Q10", 2),
    ("mesh", "Q18", 1)])
def test_tpch_joins_fold_bit_for_bit(tpch05, request, monkeypatch, where,
                                     name, folded):
    """The statements of the benchmark's cells: the rows are the unfolded
    program's, bit for bit, and the mirror's, over one device and over
    four as the four-chip cells price their broadcasts."""
    from tinysql_tpu.bench import tpch
    s, mirror = tpch05
    sql = tpch.ALL_QUERIES[name]
    if where == "mesh":
        request.getfixturevalue("four_devices")
        s.execute("set @@tidb_mesh_parallel = 1")
    try:
        rows, d, off, d_off = _folded_and_not(s, sql, monkeypatch)
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
    assert rows and rows == off
    _same(rows, [list(r) for r in mirror.execute(sql).fetchall()])
    assert d["dispatches"] == d_off["dispatches"] == 1
    assert d.get("pipe_joins_folded", 0) == folded
    assert d_off.get("pipe_joins_folded", 0) == 0

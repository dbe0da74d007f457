"""The sorted GROUP BY (``aggindex``) reads its lanes in the index's order.

``_AggIndexNode`` prepares its leaf in the order of its group index, so
the fused program holds no gather to sorted order and one boundary
gather a sum.  Which host arrays go up follows what the index observed
of its input: over a table stored in its key's order (clustered) the
leaf's lanes are the replica's row-order lanes, otherwise each lane goes
up permuted on the host once a replica version.  One traced program
serves both, on one device and over a mesh.
"""
import re

import jax
import numpy as np
import pytest

from tinysql_tpu.columnar.store import bulk_load, store_of
from tinysql_tpu.executor import devpipe
from tinysql_tpu.ops import kernels, progcache
from tinysql_tpu.parallel import dist
from tinysql_tpu.session.session import new_session

LAYOUTS = ["one", "mesh4"]
N_ROWS = 3000            # bucket 4096: 1096 padding rows, and the fourth
NB = 4096                # of four shards (1024 rows each) is all padding


@pytest.fixture
def tk(monkeypatch):
    # the chip's branches on the CPU: the fused pipeline on, no numpy twin
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
    yield s


def _rows(seed=7, n=N_ROWS):
    """Columns of the test table in no particular order: an int key with
    NULLs (about 200 groups: past SEG_UNROLL), a string key with NULLs,
    an int and a double argument, the double with NULLs."""
    rng = np.random.default_rng(seed)
    return {
        "k": (rng.integers(0, 200, n).astype(np.int64),
              rng.random(n) < 0.04),
        "s": (np.array(["AA", "BB", "CC"])[rng.integers(0, 3, n)],
              rng.random(n) < 0.05),
        "b": (rng.integers(-50, 50, n).astype(np.int64), None),
        "x": (rng.random(n) * 100, rng.random(n) < 0.1),
    }


def _index_order(cols, keys):
    """The stable order of the rows by ``keys``, as the group index sorts
    (non-NULL first in each column, strings by their codes)."""
    key_cols = []
    for name in keys:
        v, m = cols[name]
        if v.dtype.kind == "U":
            v = np.unique(np.where(m, "", v), return_inverse=True)[1]
        key_cols.append((v.astype(np.int64), m))
    return devpipe.GroupIndex(key_cols).order


def _load(s, name, cols, order):
    s.execute(f"create table {name} (a bigint primary key, k bigint, "
              "s varchar(4), b bigint, x double)")
    info = s.infoschema().table_by_name("d", name)
    data = {"a": np.arange(1, len(order) + 1, dtype=np.int64)}
    nulls = {}
    for c, (v, m) in cols.items():
        data[c] = v[order]
        if m is not None:
            nulls[c] = m[order]
    bulk_load(s.storage, info, data, nulls)
    return store_of(s.storage).get(info.id)


def _two_tables(s, keys=("k",), seed=7):
    """The same rows stored in the key's order (``c``) and shuffled
    (``u``); returns their replicas."""
    cols = _rows(seed)
    shuffled = np.random.default_rng(seed + 1).permutation(N_ROWS)
    return (_load(s, "c", cols, _index_order(cols, keys)),
            _load(s, "u", cols, shuffled))


def _use(s, layout):
    s.execute(f"set @@tidb_mesh_parallel = {int(layout == 'mesh4')}")


def _lane_keys(rep, *kinds):
    return sorted(k for k in rep.cache
                  if isinstance(k, tuple) and k[0] in kinds)


def _ran_on(rep, layout) -> bool:
    """The sorted aggregate over ``rep`` ran on ``layout`` (and on no
    other): its boundary lane is per shard under a mesh."""
    mesh, one = _lane_keys(rep, "gi_shard_ends"), _lane_keys(rep, "gi_ends")
    return bool(mesh) and not one and mesh[0][-2:] == ("rows", 4) \
        if layout == "mesh4" else bool(one) and not mesh


def _device(s, sql):
    before = kernels.stats_snapshot()
    rows = s.query(sql).rows
    return rows, kernels.stats_delta(before)


def _host(s, sql):
    s.execute("set @@tidb_use_tpu = 0")
    rows = s.query(sql).rows
    s.execute("set @@tidb_use_tpu = 1")
    return rows


def _close(got, want, rel=1e-9):
    if len(got) != len(want):
        return False
    for a, b in zip(got, want):
        for x, y in zip(a, b):
            if isinstance(x, float) and isinstance(y, float):
                if abs(x - y) > rel * max(abs(y), 1.0):
                    return False
            elif x != y:
                return False
    return True


CASES = {
    # every aggregate kind over a NULL-bearing argument, beside count(*);
    # the NULL keys form one group
    "kinds_null_keys": (
        ("k",),
        "select k, count(*), count(x), sum(x), avg(x), min(x), max(x), "
        "sum(b), min(b), max(b) from {t} group by k order by k"),
    # the filter leaves no row of most groups, and none of k = 7
    "filter_empties_groups": (
        ("k",),
        "select k, count(*), sum(x), min(x), avg(b) from {t} "
        "where x > 93 and k <> 7 group by k order by k"),
    # a filter no row passes: no group at all
    "filter_empties_all": (
        ("k",),
        "select k, count(*), sum(x) from {t} where b * 0 = 1 group by k"),
    # two key columns, one a string: groups by the tuple
    "multi_column_key": (
        ("k", "s"),
        "select k, s, count(*), sum(x), avg(x), max(b) from {t} "
        "where b > -40 group by k, s order by k, s"),
}


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_clustered_and_shuffled_tables_answer_alike(tk, layout, case):
    keys, sql = CASES[case]
    reps = dict(zip("cu", _two_tables(tk, keys)))
    _use(tk, layout)
    got = {}
    for t in "cu":
        got[t], delta = _device(tk, sql.format(t=t))
        assert delta["agg_sorted"] == 1 and delta["dispatches"] == 1
        assert delta["host_dispatches"] == 0
        assert _ran_on(reps[t], layout)
        assert delta["agg_clustered"] == int(t == "c")
        assert _close(got[t], _host(tk, sql.format(t=t))), (case, t)
    # counts, keys, min/max equal; sums within the rounding of a
    # difference of running totals (the rows of a group, and the groups
    # before it, are added in another order)
    assert _close(got["c"], got["u"])
    if case == "filter_empties_all":
        assert got["c"] == []
    else:
        assert len(got["c"]) > 5
    if case == "filter_empties_groups":
        assert 7 not in [r[0] for r in got["c"]]
        assert len(got["c"]) < 150


@pytest.mark.parametrize("layout", LAYOUTS)
def test_agg_clustered_counts_the_observed_order(tk, layout):
    """1 for the table stored in its key's order, 0 for the shuffled
    one; the dense formulation (few groups) never counts, and an index
    over another key of the clustered table is not clustered."""
    _two_tables(tk)
    _use(tk, layout)
    q = "select k, sum(x) from {t} group by k"
    assert _device(tk, q.format(t="c"))[1]["agg_clustered"] == 1
    assert _device(tk, q.format(t="u"))[1]["agg_clustered"] == 0
    _, delta = _device(tk, "select s, sum(x) from c group by s")
    assert delta["agg_dense"] == 1 and delta["agg_clustered"] == 0
    _, delta = _device(tk, "select b, sum(x) from c group by b")
    assert delta["agg_sorted"] == 1 and delta["agg_clustered"] == 0
    info = tk.query("explain analyze " + q.format(t="c")).rows
    assert any("agg:0dense/1sorted/1clustered" in str(c)
               for r in info for c in r), info


@pytest.mark.parametrize("layout", LAYOUTS)
def test_memo_keys_by_observed_order(tk, layout):
    """Clustered: no index-order lane and no second copy of a scan lane
    (the keys are the ones every scan of the table shares).  Shuffled:
    each lane the statement reads goes up permuted once a replica
    version under its key + the index's, and a warm statement moves only
    parameters."""
    rep_c, rep_u = _two_tables(tk)
    _use(tk, layout)
    tag = ("rows", 4) if layout == "mesh4" else ()
    q = "select k, count(*), sum(x), min(x) from {t} where b > 0 group by k"
    scan = "select count(*), sum(x) from {t} where b > 0 and k >= 0"
    for t in "cu":
        _, delta = _device(tk, q.format(t=t))
        # one traced program for both on one device: the shuffled
        # table's statement finds the one the clustered table's built.
        # Under the mesh a program is also by the span of groups a shard
        # bounds (``_span_pad``): 96 of 256 slots over the clustered
        # table, 224 over the shuffled one, whose shards each hold rows
        # of nearly every key
        assert delta["progcache_misses"] == \
            int(t == "c" or layout == "mesh4"), (t, delta)
    for rep in (rep_c, rep_u):
        assert not _lane_keys(rep, "gi_order", "gi_shard_order",
                              "gi_shard_rows")
    lanes_c = _lane_keys(rep_c, "devv", "devn", "devcodes")
    lanes_u = _lane_keys(rep_u, "devv", "devn", "devcodes")
    assert lanes_c and all(k[3:] == tag for k in lanes_c), lanes_c
    assert [k[:3] + k[5:] for k in lanes_u] == lanes_c
    sids = lanes_u[0][4]                      # the index's key columns
    assert all(k[3:5] == ("by", sids) for k in lanes_u), lanes_u
    # a plain scan of the clustered table finds its lanes there already
    _, delta = _device(tk, scan.format(t="c"))
    assert delta["h2d_bytes"] < 1024, delta
    assert _lane_keys(rep_c, "devv", "devn", "devcodes") == lanes_c
    # warm statements: parameters only, the same arrays, the same program
    held = {k: rep_u.cache[k] for k in lanes_u}
    for t in "cu":
        _, delta = _device(tk, q.format(t=t))
        assert delta["h2d_bytes"] < 1024, (t, delta)
        assert delta["progcache_misses"] == 0, (t, delta)
    assert all(rep_u.cache[k] is v for k, v in held.items())
    # the permuted lane holds the rows in the index's order, padding last
    gidx = rep_u.cache[("groupindex", sids)]
    assert not gidx.clustered
    xk = next(k for k in lanes_u if k[0] == "devv"
              and np.asarray(rep_u.cache[k]).dtype == np.float64)
    x_host = rep_u.columns[xk[1]][0]
    lane = np.asarray(rep_u.cache[xk])
    if layout == "one":
        assert np.array_equal(lane[:N_ROWS], x_host[gidx.order])
        assert not lane[N_ROWS:].any()
    else:
        order, _ends, _sgid, rows = gidx.shards(4, NB // 4)
        for s_ in range(4):
            mine = lane[s_ * 1024:(s_ + 1) * 1024]
            assert np.array_equal(mine[:rows[s_]],
                                  x_host[order[s_, :rows[s_]] + s_ * 1024])
            assert not mine[rows[s_]:].any()
    # a new replica version: the permuted lanes go up again, once
    tk.execute("insert into u values (100001, 3, 'AA', 5, 1.5)")
    tk.query("select * from u")
    rep_u2 = store_of(tk.storage).get(
        tk.infoschema().table_by_name("d", "u").id)
    _, cold = _device(tk, q.format(t="u"))
    _, warm = _device(tk, q.format(t="u"))
    assert cold["h2d_bytes"] > NB and warm["h2d_bytes"] < 1024
    assert len(_lane_keys(rep_u2, "devv", "devn", "devcodes")) == \
        len(lanes_u)


class _Captured(Exception):
    def __init__(self, fn, args):
        super().__init__("captured")
        self.fn, self.args = fn, args


def gather_shapes(s, sql, monkeypatch):
    """Run ``sql`` up to its first dispatch, lower the fused program, and
    list the result shape of every gather in it; also the number of
    prefix sums traced (one a ``seg`` call)."""
    def capturing_jit(fn, name="", **kw):
        def call(*args):
            raise _Captured(jax.jit(fn, **kw), args)
        return call
    sums = []
    prefix_sum = kernels.prefix_sum

    def counting_prefix_sum(x, *a, **kw):
        sums.append(x.shape)
        return prefix_sum(x, *a, **kw)
    with monkeypatch.context() as m:
        m.setattr(kernels, "counted_jit", capturing_jit)
        m.setattr(kernels, "prefix_sum", counting_prefix_sum)
        progcache.clear()
        try:
            with pytest.raises(_Captured) as got:
                s.query(sql)
            text = got.value.fn.lower(*got.value.args).as_text()
        finally:
            progcache.clear()  # it now holds the capturing stand-in
    shapes = [tuple(int(d) for d in dims.split("x")[:-1])
              for dims in re.findall(
                  r'"stablehlo\.gather"\(.*-> tensor<([0-9a-z]+(?:x[0-9a-z]+)*)>',
                  text)]
    return shapes, sums


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("table", ["c", "u"])
def test_lowered_program_gathers_only_boundaries(tk, monkeypatch, layout,
                                                 table):
    """No gather with the leaf's [nb] (a shard: [nb / n]) result; one
    boundary gather a ``seg`` call, over the [ngb] groups on one device
    and over the pad of a shard's span of them under the mesh; the same
    for both tables but for that pad."""
    _two_tables(tk)
    _use(tk, layout)
    sql = (f"select k, count(*), count(x), sum(x), sum(b) from {table} "
           "where b > -40 group by k")
    shapes, sums = gather_shapes(tk, sql, monkeypatch)
    per = NB // 4 if layout == "mesh4" else NB
    # a quarter of the clustered table's rows hold up to 75 of its 201
    # groups, a quarter of the shuffled one's all of them: 75 + 8 and
    # 201 + 8 rounded up to a multiple of 16
    ngb = 256 if layout == "one" else {"c": 96, "u": 224}[table]
    # presence; count(x): its count; sum(x): a count (the NULL flag)
    # and the sum; sum(b): the sum alone (b is NULL on no row: its count
    # is presence)
    assert sums == [(per,)] * 5, sums
    assert (NB,) not in shapes and (per,) not in shapes, shapes
    assert shapes.count((ngb,)) == len(sums), shapes


# ---- a never-NULL argument's count is presence ------------------------------

@pytest.mark.parametrize("layout", LAYOUTS)
def test_never_null_arguments_take_presence_for_their_count(tk, monkeypatch,
                                                           layout):
    """In the sorted formulation a count costs a 64-bit prefix sum and
    two boundary gathers; a spec whose argument the replica proves NULL
    on no row (``b`` holds none; ``+ - *`` make none) reduces none and
    reads ``presence``.  ``x`` holds NULLs and a division makes them (by
    zero): those keep their counts.  Answers equal the host's, empty
    groups and NULL sums included."""
    _two_tables(tk)
    _use(tk, layout)
    seen = []
    results = devpipe._spec_results

    def spy(*a, never_null=frozenset(), **kw):
        seen.append(set(never_null))
        return results(*a, never_null=never_null, **kw)
    monkeypatch.setattr(devpipe, "_spec_results", spy)
    sql = ("select k, sum(b), sum(x), min(b), sum(b / 2), count(x), "
           "avg(b * (1 - b)), count(b) from c where b > -40 group by k")
    got, delta = _device(tk, sql)
    assert delta["agg_sorted"] == 1 and delta["dispatches"] == 1
    # specs: sum b, sum x, min b, sum b/2, count x, avg -> (sum, count)
    # of b * (1 - b), count b
    assert seen and all(s == {0, 2, 5, 6, 7} for s in seen), seen
    assert _close(got, _host(tk, sql)) and len(got) > 5
    # a group whose every x is NULL: sum(x) NULL beside a sum(b) that
    # took presence for its count
    assert any(r[2] is None and r[1] is not None for r in _device(
        tk, "select k, sum(b), sum(x) from c where x is null group by k")[0])
    # the dense formulation's counts are cheap masked reductions: as before
    del seen[:]
    got, delta = _device(tk, "select s, sum(b), count(b) from c group by s")
    assert delta["agg_dense"] == 1 and seen and not any(seen), seen


# ---- under the mesh a shard bounds only its own span of groups --------------

def _load_keys(s, name, k, knull=None, seed=3):
    """A table of the given keys in the given order (``knull``: the rows
    whose key is NULL), an int and a double argument beside them."""
    n = len(k)
    rng = np.random.default_rng(seed)
    cols = {"k": (np.asarray(k, dtype=np.int64), knull),
            "s": (np.array(["AA"] * n), None),
            "b": (rng.integers(-50, 50, n).astype(np.int64), None),
            "x": (rng.random(n) * 100, rng.random(n) < 0.1)}
    return _load(s, name, cols, np.arange(n))


def _runs(sizes):
    """Keys 0, 1, ... in order, ``sizes[g]`` rows of key g."""
    return np.repeat(np.arange(len(sizes)), sizes)


def _span_tables():
    """{case: (keys, null mask or None, spans observed [(g_lo, g_hi)] a
    shard of 1024 rows, the pad ``qb`` or None where the spans are whole
    and the [ngb] tables are summed)}."""
    rng = np.random.default_rng(11)
    cases = {}
    # stored by its key: 200 groups of 15 rows, four shards of 1024
    # rows; 1024 / 15 is no whole number, so every shard's last group is
    # the next one's first (68 and 136 are shared by two shards), and
    # the fourth shard holds no row (3000 rows in a bucket of 4096)
    cases["clustered"] = (_runs([15] * 200), None,
                          [(0, 68), (68, 136), (136, 199), (0, -1)], 80)
    # one group of 2100 rows lies over three shards, 100 groups after it
    cases["group_over_three_shards"] = (
        _runs([2100] + [9] * 100), None,
        [(0, 0), (0, 0), (0, 100), (0, -1)], 112)
    # shuffled: every shard holds rows of (nearly) every one of the 250
    # keys, the spans are whole and the path is the psum of [256] tables
    shuffled = rng.permutation(_runs([12] * 250))
    cases["unclustered_whole_spans"] = (shuffled, None, None, None)
    # the NULL keys form the last group, all of it in the third shard
    k = _runs([14] * 210)
    cases["null_key_group"] = (k, k >= 205, None, 96)
    # two shards of ONE group each beside a shard of 190: the pieces
    # are as long as the widest span, and the narrow ones' tails (past
    # their one group) add zeros into the next shards' places
    cases["one_group_shards"] = (_runs([1024, 1024] + [5] * 190), None,
                                 [(0, 0), (1, 1), (2, 191), (0, -1)], 208)
    return cases


SPAN_CASES = _span_tables()


@pytest.mark.parametrize("case", sorted(SPAN_CASES))
def test_span_path_equals_the_unsharded_aggregate(tk, case):
    """Each shard bounds its own span of groups and the pieces are added
    into place; the answer is the one-device program's row for row
    (counts, keys, min/max equal; sums to the rounding of a difference
    of running totals taken over a quarter of the rows) and the host's.
    The path follows the spans the index observed: the counter says
    which ran, the boundary lane's length what was bounded."""
    k, knull, spans, qb = SPAN_CASES[case]
    rep = _load_keys(tk, "t", k, knull)
    sql = ("select k, count(*), count(x), sum(x), sum(b), min(x), max(b) "
           "from t where b > -45 group by k order by k")
    _use(tk, "one")
    single, delta = _device(tk, sql)
    assert delta["agg_sorted"] == 1 and delta["agg_span_cut"] == 0
    _use(tk, "mesh4")
    got, delta = _device(tk, sql)
    assert delta["agg_sorted"] == 1 and delta["dispatches"] == 1
    assert delta["host_dispatches"] == 0
    assert delta["agg_span_cut"] == int(qb is not None)
    assert _close(got, single, rel=1e-12) and _close(got, _host(tk, sql))
    assert len(got) == len(np.unique(k[~knull] if knull is not None
                                     else k)) + int(knull is not None)
    sids = next(key for key in rep.cache if key[0] == "groupindex")[1]
    gidx = rep.cache[("groupindex", sids)]
    ngb = kernels.bucket(gidx.n_groups)
    lane = [key for key in _lane_keys(rep, "gi_shard_ends")
            if key[-2:] == ("rows", 4)]
    assert [key[2] for key in lane] == [ngb if qb is None else qb]
    g_lo, g_hi = gidx.spans(4, NB // 4)
    if spans is not None:
        assert list(zip(g_lo, g_hi)) == spans
    if qb is None:
        assert not _lane_keys(rep, "gi_span_lo")
        assert (g_hi - g_lo).max() + 1 + ngb // 32 > ngb - ngb // 16
    else:
        lo_lane = rep.cache[_lane_keys(rep, "gi_span_lo")[0]]
        assert np.array_equal(np.asarray(lo_lane), g_lo)
        assert qb % (ngb // 16) == 0 and qb < ngb
        # a shard's boundaries: the last row of each group of its span,
        # then its last row again
        ends = np.asarray(rep.cache[lane[0]]).reshape(4, qb)
        rows = np.clip(len(k) - np.arange(4) * (NB // 4), 0, NB // 4)
        for s_ in range(4):
            assert np.all(np.diff(ends[s_]) >= 0)
            assert ends[s_, -1] == rows[s_] - 1
            width = g_hi[s_] - g_lo[s_] + 1
            assert np.all(ends[s_, width - 1:] == rows[s_] - 1)
    # warm: parameters alone go up, nothing is laid out anew
    _, warm = _device(tk, sql)
    assert warm["h2d_bytes"] < 1024 and warm["reshard_bytes"] == 0
    assert warm["progcache_misses"] == 0


def test_spans_on_both_sides_of_a_quarter_share_one_program(tk):
    """A shard of 2^k rows of a table stored by its key spans a quarter
    of the group slots give or take the data's draw (PERF.md section 6,
    PR 38): 1024 rows hold 62 groups of 17 rows and 70 of 15, on either
    side of 256 / 4.  Both pad to 80 — five steps of 256 // 16 — and run
    ONE program, where the next power of two would be 64 and 128."""
    reps = {}
    for name, size, ng in (("under", 17, 212), ("over", 15, 240)):
        reps[name] = _load_keys(tk, name, _runs([size] * ng))
    sql = "select k, count(*), sum(x), sum(b) from {t} group by k order by k"
    _use(tk, "mesh4")
    widest = {}
    for i, name in enumerate(("under", "over")):
        got, delta = _device(tk, sql.format(t=name))
        assert delta["agg_span_cut"] == 1
        assert delta["progcache_misses"] == int(i == 0), (name, delta)
        assert _close(got, _host(tk, sql.format(t=name)))
        rep = reps[name]
        sids = next(key for key in rep.cache if key[0] == "groupindex")[1]
        g_lo, g_hi = rep.cache[("groupindex", sids)].spans(4, NB // 4)
        widest[name] = int((g_hi - g_lo).max()) + 1
        assert [key[2] for key in _lane_keys(rep, "gi_shard_ends")] == [80]
    assert widest["under"] < 256 // 4 < widest["over"], widest
    assert kernels.bucket(widest["under"]) != kernels.bucket(widest["over"])


def test_span_pad_is_the_widest_span_and_half_a_step():
    """``_span_pad``: a multiple of ``ngb // 16``, at least the widest
    span, the same on both sides of every power of two, ``ngb`` (the
    spans are whole) from fifteen and a half steps on."""
    lo = np.zeros(4, dtype=np.int64)

    def qb(widest, ngb):
        hi = np.array([widest - 1, 3, -1, 0], dtype=np.int64)
        return devpipe._span_pad(lo, hi, ngb)
    ngb = 1 << 21
    step = ngb // 16
    for widest in (524_119, 524_288, 524_612):       # ISSUE 38's draws
        assert qb(widest, ngb) == 5 * step
    assert qb(1, ngb) == step and qb(step // 2, ngb) == step
    assert qb(step // 2 + 1, ngb) == 2 * step
    assert qb(15 * step, ngb) == ngb and qb(ngb, ngb) == ngb
    assert qb(14 * step + step // 2, ngb) == 15 * step
    for widest in range(1, 129):
        got = qb(widest, 128)
        assert got % 8 == 0 and widest <= got <= 128

"""The replica's host preparation at millions of rows (PR 33): the
shortcuts give, value for value, what the plain formulations give."""
import numpy as np
import pytest

from tinysql_tpu.executor import devpipe, tpu_executors


def _lexsort_order(cols):
    ops = []
    for vals, nulls in reversed(cols):
        ops += [np.where(nulls, 0, vals), nulls]
    return np.lexsort(tuple(ops))


@pytest.mark.parametrize("case", ["stored_by_key", "two_code_columns",
                                  "codes_with_nulls", "wide_range",
                                  "unsorted_single", "strings"])
def test_stable_key_order_is_lexsorts(case):
    rng = np.random.default_rng(3)
    n = 5000
    none = np.zeros(n, dtype=bool)
    some = rng.random(n) < 0.1
    cols = {
        "stored_by_key": [(np.sort(rng.integers(1, 900, n)), none)],
        "two_code_columns": [(rng.integers(0, 3, n), none),
                             (rng.integers(0, 2, n), none)],
        "codes_with_nulls": [(rng.integers(-2, 5, n), some),
                             (rng.integers(0, 9, n), ~some & (rng.random(n)
                                                              < 0.2))],
        "wide_range": [(rng.integers(0, 1 << 40, n), none)],
        "unsorted_single": [(rng.integers(0, 50, n), none)],
        "strings": [(rng.random(n), none)],
    }[case]
    svs = [(np.where(nl, 0, v), nl) for v, nl in cols]
    got = devpipe._stable_key_order(svs)
    if case in ("wide_range", "strings"):
        assert got is None  # a comparison sort it is
        return
    assert (got == _lexsort_order(cols)).all()
    if case == "stored_by_key":
        assert (got == np.arange(n)).all()
    # and the index built on it is the index a lexsort builds
    gi = devpipe.GroupIndex(cols)
    order = _lexsort_order(cols)
    assert (gi.order == order).all()
    assert gi.clustered == bool((order == np.arange(n)).all())
    firsts = [v[order][gi.ends] for v, _ in cols]
    assert all((f == k[0]).all() or cols[i][1].any()
               for i, (f, k) in enumerate(zip(firsts, gi.keycols)))


@pytest.mark.parametrize("n_shards", [2, 4, 8])
@pytest.mark.parametrize("clustered", [True, False])
def test_shard_ends_is_the_cuts_boundary_table(n_shards, clustered):
    rng = np.random.default_rng(n_shards)
    n, per = 1000, 1024 // n_shards
    keys = rng.integers(1, 120, n)
    if clustered:
        keys = np.sort(keys)
    gi = devpipe.GroupIndex([(keys, np.zeros(n, dtype=bool))])
    assert gi.clustered == clustered
    assert (gi.shard_ends(n_shards, per)
            == gi.shards(n_shards, per)[1]).all()


@pytest.mark.parametrize("kind", ["dates", "flags", "nearly_unique"])
def test_ordered_codes_in_blocks_is_np_unique(kind, monkeypatch):
    monkeypatch.setattr(tpu_executors, "_CODE_BLOCK", 1 << 10)
    rng = np.random.default_rng(9)
    n = 10_000
    v = {"dates": np.array([f"199{y}-0{m}-1{d}" for y in range(8)
                            for m in range(1, 10) for d in range(10)]),
         "flags": np.array(["A", "N", "R"]),
         "nearly_unique": np.array([f"Customer#{i:09d}"
                                    for i in range(n)])}[kind]
    v = v[rng.integers(0, len(v), n)]
    uniques, codes = tpu_executors._ordered_codes(v)
    want_u, want_c = np.unique(v, return_inverse=True)
    assert (uniques == want_u).all() and (codes == want_c).all()
    assert codes.dtype == np.int64


def test_bulk_load_holds_a_column_it_was_handed_in_its_dtype():
    from tinysql_tpu.columnar.store import bulk_load, store_of
    from tinysql_tpu.session.session import new_session
    s = new_session()
    s.execute("create database d")
    s.execute("use d")
    s.execute("create table t (a bigint primary key, b double, c int, "
              "d varchar(4))")
    info = s.infoschema().table_by_name("d", "t")
    data = {"a": np.arange(1, 9, dtype=np.int64), "b": np.ones(8),
            "c": np.arange(8, dtype=np.int32),
            "d": np.array(["x", "y"] * 4)}
    bulk_load(s.storage, info, data)
    rep = store_of(s.storage).get(info.id)
    by_name = {c.name: rep.columns[c.id][0] for c in info.public_columns()}
    for name in "abd":   # the caller's memory, which only it may write
        assert np.shares_memory(by_name[name], data[name])
        assert not by_name[name].flags.writeable
        assert data[name].flags.writeable
        with pytest.raises(ValueError):
            by_name[name][0] = data[name][1]
    assert rep.handles is by_name["a"]
    # another width is converted, as before
    assert by_name["c"].dtype == np.int64
    assert not np.shares_memory(by_name["c"], data["c"])
    assert s.query("select sum(a), sum(c) from t").rows == [[36, 28]]

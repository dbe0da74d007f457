"""The sorted GROUP BY counts its rows in 32 bits where its bucket proves
a count fits (``devpipe._AggIndexNode._count32``: the leaf's whole
bucket, every shard's rows together, below 2^31).  A count is then one
32-bit prefix sum and one boundary gather, merged over the mesh as one
32-bit lane and widened to int64 on the [groups] result; an int64 is
two words, so two gathers and two lanes.

- count(*), count(x) of a column with NULLs, and sum / min / max / avg
  that are NULL on a group with no live argument: the 32-bit program's
  rows equal the int64 program's, value for value, and the host tier's;
- a group the filter leaves no row of, one group holding every row, the
  bucket's padding rows;
- one device, and four devices of the forced host mesh: each shard's
  span of groups (pieces all-gathered and added into place) and the sum
  of whole tables where the spans are whole;
- the rule at 2^31 - 1 and 2^31 rows, by shape alone.
"""
import numpy as np
import pytest

from test_aggindex_order import (  # noqa: F401  (tk: a fixture)
    LAYOUTS, SPAN_CASES, _close, _device, _host, _load_keys, _runs, _use,
    tk)
from tinysql_tpu.executor import devpipe
from tinysql_tpu.ops import kernels, progcache

#: every count the sorted formulation reduces, beside its sums
ALL_KINDS = ("select k, count(*), count(x), sum(x), min(x), max(x), "
             "avg(x), sum(b), min(b) from t where b > -45 "
             "group by k order by k")


def _both(s, sql, monkeypatch):
    """(rows, counters) of ``sql`` with its counts in 32 bits, then with
    every count an int64 (a program of its own)."""
    progcache.clear()
    rows, d = _device(s, sql)
    with monkeypatch.context() as m:
        m.setattr(devpipe._AggIndexNode, "_count32",
                  lambda self, nb: False)
        off, d_off = _device(s, sql)
    return rows, d, off, d_off


def _held_to_the_int64_program(rows, d, off, d_off):
    assert rows == off
    assert d["agg_sorted"] == d_off["agg_sorted"] == 1
    assert d["dispatches"] == d_off["dispatches"] == 1
    assert d["agg_count32"] == 1 and d_off.get("agg_count32", 0) == 0
    # the int64 side is another program, not the one just traced
    assert d["progcache_misses"] == d_off["progcache_misses"] == 1


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("case", sorted(SPAN_CASES))
def test_counts_in_32_bits_answer_as_the_int64_ones(tk, monkeypatch, case,
                                                    layout):
    """Every kind of count over tables whose shards bound their own
    spans (a group over three shards, shards of one group, a NULL key
    group, an empty fourth shard) or whole tables (the shuffled one):
    the same rows as the int64 program's, and the host's."""
    k, knull, _spans, qb = SPAN_CASES[case]
    _load_keys(tk, "t", k, knull)
    _use(tk, layout)
    rows, d, off, d_off = _both(tk, ALL_KINDS, monkeypatch)
    _held_to_the_int64_program(rows, d, off, d_off)
    assert d["agg_span_cut"] == int(layout == "mesh4" and qb is not None)
    assert _close(rows, _host(tk, ALL_KINDS))
    # the filter's survivors, counted group by group
    kept = tk.query("select count(*) from t where b > -45").rows[0][0]
    assert sum(r[1] for r in rows) == kept


#: statements over 200 groups of 15 rows stored by their key (3,000 rows
#: in a bucket of 4,096: the padding rows count nowhere), its ``x`` NULL
#: on a tenth of the rows
SHAPES = {
    # count(x) alone: the count of a column with NULLs is its own
    "count_of_nulls": ("select k, count(x) from t group by k order by k",
                       200),
    # the filter leaves no row of group 7: no such group comes back
    "empty_group": ("select k, count(*), count(x), sum(x), max(x) from t "
                    "where k <> 7 group by k order by k", 199),
    # groups whose every live argument is NULL: count(x) 0 and a NULL
    # sum / min beside count(*)
    "null_when_empty": ("select k, count(*), count(x), sum(x), min(x) "
                        "from t where x is null group by k order by k",
                        None),
    # one group holds every row the filter keeps
    "one_group": ("select k, count(*), count(x), sum(x), sum(b) from t "
                  "where k = 13 group by k", 1),
    # every row, padding beside them
    "all_rows": ("select k, count(*), sum(b) from t group by k "
                 "order by k", 200),
}


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("case", sorted(SHAPES))
def test_group_shapes_count_as_the_int64_ones(tk, monkeypatch, case,
                                              layout):
    sql, groups = SHAPES[case]
    _load_keys(tk, "t", _runs([15] * 200))
    _use(tk, layout)
    rows, d, off, d_off = _both(tk, sql, monkeypatch)
    _held_to_the_int64_program(rows, d, off, d_off)
    assert _close(rows, _host(tk, sql))
    if groups is not None:
        assert len(rows) == groups
    if case == "null_when_empty":
        assert any(r[2] == 0 and r[3] is None and r[4] is None
                   for r in rows), rows
        assert all(r[1] > 0 for r in rows)
    if case == "one_group":
        assert list(rows[0][:2]) == [13, 15]
    if case == "all_rows":
        assert sum(r[1] for r in rows) == 3000


@pytest.mark.parametrize("layout", LAYOUTS)
def test_count_prefix_sums_run_in_32_bits(tk, monkeypatch, layout):
    """The counts' prefix sums (``presence``'s and count(x)'s) run over
    an int32 lane, the sums' over their own types; with the rule off
    every count's is an int64 one."""
    _load_keys(tk, "t", _runs([15] * 200))
    _use(tk, layout)
    sql = "select k, count(*), count(x), sum(b) from t group by k"
    seen = []
    prefix_sum = kernels.prefix_sum

    def spy(x):
        seen.append(str(x.dtype))
        return prefix_sum(x)
    monkeypatch.setattr(kernels, "prefix_sum", spy)
    progcache.clear()   # a warm program traces nothing
    _device(tk, sql)
    # presence, count(x); sum(b), whose count is presence (b holds no
    # NULL)
    assert seen == ["int32", "int32", "int64"], seen
    del seen[:]
    monkeypatch.setattr(devpipe._AggIndexNode, "_count32",
                        lambda self, nb: False)
    _device(tk, sql)
    assert seen == ["int64"] * 3, seen


@pytest.mark.parametrize("nb, narrow", [
    (1 << 23, True),            # lineitem at SF=1
    (1 << 26, True),            # lineitem at SF=10, every shard together
    (2 ** 31 - 1, True),        # every count fits int32 still
    (2 ** 31, False),           # one row more than int32 holds
    (1 << 33, False),
])
def test_the_rule_reads_the_bucket_alone(nb, narrow):
    """Count in 32 bits where the whole bucket is below 2^31 rows; the
    rule reads the shape and allocates nothing."""
    node = object.__new__(devpipe._AggIndexNode)
    snap = kernels.stats_snapshot()
    assert node._count32(nb) is narrow
    assert kernels.stats_delta(snap).get("agg_count32", 0) == int(narrow)


def test_the_dense_formulation_keeps_its_counts(tk, monkeypatch):
    """Few groups (masked reductions in row order, no prefix sum) and
    the int64 counts they always had: the rule is not asked."""
    _load_keys(tk, "t", _runs([100] * 30))
    asked = []
    rule = devpipe._AggIndexNode._count32

    def spy(self, nb):
        asked.append(nb)
        return rule(self, nb)
    monkeypatch.setattr(devpipe._AggIndexNode, "_count32", spy)
    sql = "select k, count(*), count(x), sum(x) from t group by k order by k"
    rows, d = _device(tk, sql)
    assert d["agg_dense"] == 1 and d.get("agg_count32", 0) == 0
    assert not asked
    assert _close(rows, _host(tk, sql)) and len(rows) == 30

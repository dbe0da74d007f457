"""The cell ``tpch_sf1_subq.subq_stream``: its files parse and name what
exists, the lists it joined hold its name at their ends, its CPU
rehearsal ends ``correct`` with every metric that needs no device (one
rewrite and one comparison against a view a statement on the ``window``
line), its plain reference answers two tiny tables as worked by hand,
and the float32 control fails each of the cell's limits.

The rehearsal runs in a process of its own (see ``test_mesh_cell.py``).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT
import control
import run as harness
import traffic

CELL = "tpch_sf1_subq.subq_stream"
#: per-layer metrics that only a device trace can give, and the heap
#: profiler's span, which the rehearsal's settings turn off
NOT_REHEARSED = {"device_idle_share.joins", "device_busy_ms_per_query.joins",
                 "scan_roofline.joins", "memprof_traced_ms_per_query.joins"}
#: the joins cell's statements' own times: this cell sends none of them
NOT_MINE = {"q5_ms.joins", "q10_ms.joins", "q18_ms.joins"}


def test_files_parse_and_name_what_exists():
    cell = harness.Cell(CELL)
    assert cell.entry == {
        "name": CELL, "config": "tpch_sf1_subq", "traffic": "subq_stream",
        "chips": 1, "why": cell.entry["why"]}
    assert len(cell.entry["why"]) <= 200
    config, base = cell.config, harness.load_json(BENCH, "configs",
                                                  "tpch_sf1_joins.json")
    assert config["dataset"] == "tpch_subq"
    # tpch_sf1_joins's deployment in everything but the query set
    for key in ("deployment", "scale_factor", "chips", "sysvars",
                "guarantees", "precision", "server", "rows_at_this_scale",
                "sysvars_left_at_their_defaults"):
        assert config[key] == base[key], key
    for key in ("scale_factor", "default_sysvars"):
        assert config["rehearsal"][key] == base["rehearsal"][key], key
    # the joins' TINYSQL_DEVICE_JOIN_ONLY is read by nothing any more
    assert config["rehearsal"]["env"] == {}
    assert config["assumed"][:4] == base["assumed"][:4]
    assert any("Q2 SIZE 15, TYPE BRASS" in a and "Brand#23" in a
               for a in config["assumed"])
    mix = cell.mix
    assert (mix["driver"], mix["connections"], mix["order"], mix["close"]) \
        == ("closed_loop", 1, "cycle", "equal_rounds")
    statements = traffic.expand(mix)
    assert [s.kind for s in statements] == ["q2", "q17"]
    # the correlated subqueries stay as the specification writes them
    assert "ps_supplycost = (\n    select min(ps_supplycost)" \
        in statements[0].sql and "where p_partkey = ps_partkey" \
        in statements[0].sql
    assert "l_quantity < (\n    select 0.2 * avg(l_quantity)" \
        in statements[1].sql and "where l_partkey = p_partkey)" \
        in statements[1].sql
    module = harness.load_module("datasets", config["dataset"])
    assert sorted(module.REFERENCES) == ["q1", "q17", "q2", "q3", "q6"]
    rows = {t: 1 for t in module.SCHEMAS}
    for s in statements:
        assert s.reference in module.REFERENCES
        assert module.scan_bytes(s.reads, rows) > 0  # every column exists
        assert all(c in s.sql for cols in s.reads.values() for c in cols)
    assert [m["name"] for m in cell.end_to_end] == ["stream_queries_per_s",
                                                    "setup_s"]
    for kind, metrics in (("end_to_end", cell.end_to_end),
                          ("layer_metrics", cell.per_layer)):
        for m in metrics:
            spec = harness.metric_spec(kind, m["name"])
            assert hasattr(harness.load_module("readers", spec["reader"]),
                           "read"), m["name"]


def test_the_lists_hold_the_cells_name():
    """Appended, nothing else: the rate's list, and every per-layer list
    that names the one-chip joins cell but the joins' own statements'
    times; 128 entries as before."""
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    assert len(bench["per_layer"]) == 128
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "stream_queries_per_s")
    assert rate["workloads"][-1] == CELL
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert all(m["workloads"][-1] == CELL for m in bench["per_layer"]
               if m["name"] in mine)
    joins = {m["name"] for m in bench["per_layer"]
             if "tpch_sf1_joins.join_stream" in m.get("workloads", [])}
    assert mine == joins - NOT_MINE and len(mine) == 35
    assert bench["configs"][-1]["name"] == "tpch_sf1_subq"
    assert bench["workloads"][-1]["name"] == CELL


def test_rehearsal_ends_correct_with_every_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000047", "--seconds", "3", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0
    cell = harness.Cell(CELL)
    want = {m["name"] for m in cell.per_layer} - NOT_REHEARSED
    got = result["metrics"]
    assert want == set(got), sorted(want ^ set(got))
    value = {k: v["value"] for k, v in got.items()}
    assert value["dispatches_per_query.joins"] == 1.0
    assert value["compiles_in_window.joins"] == 0.0
    assert value["h2d_bytes_per_query.joins"] == 0.0
    # Q2: eight joins (three semi below its aggregate), Q17 two
    assert value["pipe_joins_per_query.joins"] == pytest.approx(5.0)
    # Q2's aggregate over partsupp (stored by ps_partkey) is clustered,
    # Q17's over lineitem (stored by l_orderkey) is not
    assert value["agg_clustered_per_query.joins"] == pytest.approx(0.5)
    window = next(json.loads(x) for x in lines
                  if x.startswith('{"phase": "window"'))
    kernels = window["kernels"]
    answered = window["answered"]
    assert kernels["subq_decorrelated"] == answered
    assert kernels["pipe_join_view_conds"] == answered
    assert kernels["agg_sorted"] - kernels["agg_clustered"] == answered // 2
    for name in ("max_rel_gap.q2", "max_rel_gap.q17"):
        c = result["compared"][name]
        assert c["value"] <= c["limit"], (name, c)


def _tiny(module):
    """Two parts, three suppliers in two regions, seven lines: small
    enough to work Q2's and Q17's answers by hand."""
    def text(n, tag):
        return np.array([f"{tag}{i}" for i in range(n)])
    tables = {
        "region": {"r_regionkey": np.array([0, 1]),
                   "r_name": np.array(["EUROPE", "ASIA"])},
        "nation": {"n_nationkey": np.array([0, 1, 2]),
                   "n_name": np.array(["FRANCE", "GERMANY", "JAPAN"]),
                   "n_regionkey": np.array([0, 0, 1])},
        "supplier": {"s_suppkey": np.array([1, 2, 3]),
                     "s_name": text(3, "S"), "s_address": text(3, "A"),
                     "s_nationkey": np.array([0, 1, 2]),
                     "s_phone": text(3, "P"),
                     "s_acctbal": np.array([100.5, 200.25, 300.0]),
                     "s_comment": text(3, "C")},
        "part": {"p_partkey": np.array([1, 2, 3]),
                 "p_mfgr": text(3, "M"),
                 "p_brand": np.array(["Brand#23", "Brand#23", "Brand#11"]),
                 "p_type": np.array(["LARGE BRASS", "SMALL TIN",
                                     "ECONOMY BRASS"]),
                 "p_size": np.array([15, 15, 15]),
                 "p_container": np.array(["MED BOX"] * 3)},
        # part 1: suppliers 1 and 2 (Europe) at 10.0, 3 (Japan) at 5.0;
        # part 3: supplier 2 alone
        "partsupp": {"ps_partkey": np.array([1, 1, 1, 2, 3]),
                     "ps_suppkey": np.array([1, 2, 3, 1, 2]),
                     "ps_supplycost": np.array([10.0, 10.0, 5.0, 1.0,
                                                7.0])},
        # part 1 averages 25.0 exactly: a fifth of it is 5.0, which the
        # line of 5 is not below and the line of 4 is
        "lineitem": {"l_partkey": np.array([1, 1, 1, 1, 2, 2, 3]),
                     "l_quantity": np.array([5.0, 4.0, 41.0, 50.0, 1.0,
                                             3.0, 1.0]),
                     "l_extendedprice": np.array([100.0, 200.0, 300.0,
                                                  400.0, 10.0, 20.0,
                                                  30.0]),
                     "l_discount": np.zeros(7), "l_tax": np.zeros(7)},
    }
    return module.Dataset(tables, {})


def test_reference_answers_by_hand():
    module = harness.load_module("datasets", "tpch_subq")
    ds = _tiny(module)
    q2 = module.q2(ds, {"size": 15, "type": "BRASS", "region": "EUROPE",
                        "limit": 100})
    # by hand: part 1's least European cost is 10.0 (suppliers 1 and 2),
    # part 3's 7.0 (supplier 2 alone); part 2 is TIN; by s_acctbal
    # descending, then n_name, s_name, p_partkey
    assert q2 == [(200.25, "S1", "GERMANY", 1, "M0", "A1", "P1", "C1"),
                  (200.25, "S1", "GERMANY", 3, "M2", "A1", "P1", "C1"),
                  (100.5, "S0", "FRANCE", 1, "M0", "A0", "P0", "C0")]
    q17 = module.q17(ds, {"brand": "Brand#23", "container": "MED BOX"})
    assert q17 == [(200.0 / 7.0,)]
    none = module.q17(ds, {"brand": "Brand#99", "container": "MED BOX"})
    assert none == [(None,)]


def test_the_float32_control_fails_each_limit():
    module = harness.load_module("datasets", "tpch_subq")
    ds = module.generate(0.02, 2_147_483_723)
    cell = harness.Cell(CELL)
    verdict = control.control_verdict(cell, ds, module)
    assert verdict["correct"] is False
    gaps = {name: c for name, c in verdict["compared"].items()
            if name.startswith("max_rel_gap.")}
    assert sorted(gaps) == ["max_rel_gap.q17", "max_rel_gap.q2"]
    assert all(c["value"] > c["limit"] for c in gaps.values()), gaps


@pytest.mark.slow
def test_exact_ties_of_q17_at_sf1():
    """How many lines of SF=1 sit exactly on their part's threshold,
    ``l_quantity == 0.2 * (sum / count)`` in float64 (PERF.md section 4
    reports the count): a run whose device rounded the threshold up
    would keep each of them.  About 5 GB of host arrays."""
    module = harness.load_module("datasets", "tpch_subq")
    ds = module.generate(1.0, 3_470_000_047)
    p_row, found, threshold = module.q17_threshold(ds)
    qty = ds.tables["lineitem"]["l_quantity"]
    tie = found & (qty == threshold[p_row])
    part = ds.tables["part"]
    mine = (part["p_brand"] == "Brand#23") \
        & (part["p_container"] == "MED BOX")
    print(json.dumps({"lines": int(len(qty)), "ties": int(tie.sum()),
                      "ties_of_brand23_med_box": int((tie
                                                      & mine[p_row]).sum())}))
    assert tie.sum() > 0

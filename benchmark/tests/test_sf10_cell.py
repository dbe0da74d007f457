"""The SF=10 four-chip cell: its CPU rehearsal prints a correct result
with every ``.mesh10`` metric that needs no device, and its dataset
module makes ``tpch.py``'s tables.

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

CELL = "tpch_sf10_mesh4.power_stream"
#: per-layer metrics that only a device trace can give, and the heap
#: profiler's span, which the rehearsal's settings turn off
NOT_REHEARSED = {"device_idle_share.mesh10",
                 "device_busy_ms_per_query.mesh10",
                 "mesh_scan_roofline.mesh10",
                 "memprof_traced_ms_per_query.mesh10"}


def test_rehearsal_prints_every_mesh10_metric_that_needs_no_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert result["device"]["count"] == 4
    cell = harness.Cell(CELL)
    assert cell.entry["chips"] == 4 and cell.config["chips"] == 4
    assert cell.config["scale_factor"] == 10.0
    # nothing steers a path: the prewarm worker off, the mesh on
    assert cell.config["sysvars"] == {"tidb_auto_prewarm": 0,
                                      "tidb_mesh_parallel": 1}
    assert "tidb_broadcast_build_max_rows" not in \
        cell.config["rehearsal"]["default_sysvars"]
    want = {m["name"] for m in cell.per_layer} - NOT_REHEARSED
    got = result["metrics"]
    assert want == set(got), sorted(want ^ set(got))
    assert sum(n.endswith(".mesh10") for n in want) == 14
    assert got["dispatches_per_query.mesh10"]["value"] == 1.0
    assert got["mesh_dispatch_share.mesh10"]["value"] == 100.0
    assert got["reshard_bytes_per_query.mesh10"]["value"] == 0.0
    assert got["compiles_in_window.mesh10"]["value"] == 0.0


# ---- the dataset module ---------------------------------------------------

SF = 0.05


@pytest.fixture(scope="module")
def both():
    tpch = harness.load_module("datasets", "tpch")
    blocks = harness.load_module("datasets", "tpch_blocks")
    return tpch, tpch.generate(SF, 2_147_483_659), \
        blocks, blocks.generate(SF, 2_147_483_659)


def test_blocks_makes_tpchs_tables(both, monkeypatch):
    tpch, a, blocks, b = both
    assert blocks.SCHEMAS is tpch.SCHEMAS or blocks.SCHEMAS == tpch.SCHEMAS
    assert blocks.DATABASE == tpch.DATABASE
    assert list(a.tables) == list(b.tables)
    for table in a.tables:
        assert list(a.tables[table]) == list(b.tables[table]), table
        rows_a = len(a.tables[table]["l_id" if table == "lineitem"
                                     else next(iter(a.tables[table]))])
        rows_b = len(next(iter(b.tables[table].values())))
        if table == "lineitem":  # 1 to 7 lines an order, drawn
            assert abs(rows_a - rows_b) < 0.01 * rows_a
        else:
            assert rows_a == rows_b, table
        for column, va in a.tables[table].items():
            vb = b.tables[table][column]
            assert len(vb) == rows_b and va.dtype.kind == vb.dtype.kind, \
                (table, column)
            if va.dtype.kind == "U":
                # the declared width holds every text
                width = tpch.column_bytes(table, column)
                assert vb.dtype.itemsize // 4 <= width, (table, column)
            small = np.unique(va)
            if len(small) <= 64:   # a value set: flags, modes, segments
                assert set(small) == set(np.unique(vb)), (table, column)
            elif va.dtype.kind in "if":
                assert va.min() <= vb.mean() <= va.max(), (table, column)
    # keys are dense and lineitem is stored by its order key
    li = b.tables["lineitem"]
    assert (np.diff(li["l_orderkey"]) >= 0).all()
    assert (li["l_id"] == np.arange(1, len(li["l_id"]) + 1)).all()
    assert (b.tables["orders"]["o_orderkey"]
            == np.arange(1, len(b.days["o_orderdate"]) + 1)).all()
    per_order = np.bincount(li["l_orderkey"])[1:]
    assert per_order.min() >= 1 and per_order.max() <= 7
    # the strings are the day numbers the reference filters on
    epoch = np.datetime64("1992-01-01")
    for table, column in (("orders", "o_orderdate"),
                          ("lineitem", "l_shipdate")):
        days = (b.tables[table][column].astype("datetime64[D]")
                - epoch).astype(int)
        assert (days == b.days[column]).all()
    # a block boundary falls inside the tables at this scale
    monkeypatch.setattr(blocks, "BLOCK_ORDERS", 1 << 12)
    c = blocks.generate(0.01, 5)
    assert (np.diff(c.tables["lineitem"]["l_orderkey"]) >= 0).all()
    assert len(np.unique(c.tables["lineitem"]["l_comment"])) > 4000


def test_blocks_answers_have_the_references_shape_and_the_control_fails(
        both):
    tpch, a, blocks, b = both
    cell = harness.Cell(CELL)
    assert cell.config["dataset"] == "tpch_blocks"
    import traffic
    for s in traffic.expand(cell.mix):
        ra = tpch.REFERENCES[s.reference](a, s.params)
        rb = blocks.REFERENCES[s.reference](b, s.params)
        assert len(ra) == len(rb) and len(rb) > 0
        assert [type(x) for x in ra[0]] == [type(x) for x in rb[0]]
    verdict = control.control_verdict(cell, b, blocks)
    assert verdict["correct"] is False
    gaps = [c for name, c in verdict["compared"].items()
            if name.startswith("max_rel_gap.")]
    assert len(gaps) == 3 and all(g["value"] > g["limit"] for g in gaps)
    assert verdict["compared"]["wrong_answers"]["value"] == 0


def test_blocks_refuses_a_program_without_the_byte_budget(monkeypatch):
    blocks = harness.load_module("datasets", "tpch_blocks")
    from tinysql_tpu.parallel import dist
    monkeypatch.delattr(dist, "broadcast_budget_bytes")
    with pytest.raises(RuntimeError, match="PR 33"):
        blocks.generate(0.01, 1)

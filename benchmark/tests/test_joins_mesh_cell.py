"""The cell ``tpch_sf10_joins_mesh4.join_stream``: its files parse and name
what exists (``tpch_sf10_mesh4``'s deployment with ``tpch_sf1_joins``'s
query set, traffic and reference), the lists of ``BENCHMARK.json`` it was
appended to hold its name, its CPU rehearsal (four host devices, SF 0.05)
ends ``correct`` with one fused mesh dispatch a statement and every
metric that needs no device, the float32 control does not pass, and a
program without the mesh counters is refused before any data is made.

The rehearsal runs in a process of its own (see ``test_mesh_cell.py``).
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
import control
import run as harness
import traffic

CELL = "tpch_sf10_joins_mesh4.join_stream"
JOINS = "tpch_sf1_joins.join_stream"
#: per-layer metrics that only a device trace can give, and the heap
#: profiler's span, which the rehearsal's settings turn off
NOT_REHEARSED = {"device_idle_share.joins", "device_busy_ms_per_query.joins",
                 "mesh_scan_roofline.mesh10",
                 "memprof_traced_ms_per_query.joins"}


def test_files_parse_and_name_what_exists():
    cell = harness.Cell(CELL)
    assert cell.entry == {
        "name": CELL, "config": "tpch_sf10_joins_mesh4",
        "traffic": "join_stream", "chips": 4, "why": cell.entry["why"]}
    assert len(cell.entry["why"]) <= 200
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "tpch_sf10_joins_mesh4")
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    config = cell.config
    mesh10 = harness.load_json(BENCH, "configs", "tpch_sf10_mesh4.json")
    joins = harness.load_json(BENCH, "configs", "tpch_sf1_joins.json")
    assert config["dataset"] == "tpch_joins_blocks"
    # tpch_sf10_mesh4's deployment in everything but the query set
    for key in ("deployment", "scale_factor", "chips", "sysvars",
                "sysvars_left_at_their_defaults", "guarantees", "precision",
                "server", "rows_at_this_scale"):
        assert config[key] == mesh10[key], key
    assert config["sysvars"] == {"tidb_auto_prewarm": 0,
                                 "tidb_mesh_parallel": 1}
    assert config["assumed"][:len(mesh10["assumed"]) - 1] \
        == mesh10["assumed"][:-1]
    assert joins["assumed"][4] in config["assumed"]  # the parameters
    assert set(config["layout"]) == set(mesh10["layout"])
    assert config["rehearsal"]["scale_factor"] == 0.05
    assert config["rehearsal"]["env"] == mesh10["rehearsal"]["env"]
    # the joins cell's traffic file, as it is
    assert cell.mix == harness.Cell(JOINS).mix
    statements = traffic.expand(cell.mix)
    assert [s.kind for s in statements] == ["q5", "q10", "q18"]
    module = harness.load_module("datasets", config["dataset"])
    rows = {t: 1 for t in module.SCHEMAS}
    for s in statements:
        assert s.reference in module.REFERENCES
        assert module.scan_bytes(s.reads, rows) > 0
    for kind, metrics in (("end_to_end", cell.end_to_end),
                          ("layer_metrics", cell.per_layer)):
        for m in metrics:
            spec = harness.metric_spec(kind, m["name"])
            assert hasattr(harness.load_module("readers", spec["reader"]),
                           "read"), m["name"]
            for source in spec.get("sources", []):
                assert hasattr(harness.load_module("sources", source),
                               "snapshot"), (m["name"], source)
    assert [m["name"] for m in cell.end_to_end] == ["stream_queries_per_s",
                                                    "setup_s"]


def test_the_lists_hold_the_cells_name():
    """Appended, nothing else: the rate's list, the five ``setup_*``, the
    twelve ``.streams`` entries that list the joins cell, every ``.joins``
    entry but the one-chip roofline, and the three ``.mesh10`` entries of
    the mesh layer and the mesh roofline; 128 entries as before."""
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    assert len(bench["per_layer"]) == 128
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "stream_queries_per_s")
    assert rate["workloads"][-1] == CELL
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]]
    assert all(m["workloads"][-1] == CELL for m in bench["per_layer"]
               if CELL in m["workloads"])
    setups = {n for n in mine if n.startswith("setup_")}
    assert setups == {"setup_load_s", "setup_first_answers_s",
                      "setup_replica_prepare_s", "setup_program_load_s",
                      "setup_mesh_place_s"}
    streams = {n for n in mine if n.endswith(".streams")}
    assert len(streams) == 12 and streams == {
        m["name"] for m in bench["per_layer"]
        if m["name"].endswith(".streams") and JOINS in m["workloads"]}
    joins = {n for n in mine if n.endswith(".joins")}
    assert joins == {m["name"] for m in bench["per_layer"]
                     if m["name"].endswith(".joins")} \
        - {"scan_roofline.joins"}
    assert set(mine) - setups - streams - joins == {
        "reshard_bytes_per_query.mesh10", "mesh_dispatch_share.mesh10",
        "mesh_scan_roofline.mesh10"}
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 3 and len(bench["workloads"]) == 6


def test_rehearsal_ends_correct_with_one_mesh_dispatch_a_statement():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000039", "--seconds", "3", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    # whole rounds only
    assert result["attempted"] >= 3 and result["attempted"] % 3 == 0
    cell = harness.Cell(CELL)
    want = {m["name"] for m in cell.per_layer} - NOT_REHEARSED
    got = result["metrics"]
    assert want == set(got), sorted(want ^ set(got))
    value = {k: v["value"] for k, v in got.items()}
    assert value["dispatches_per_query.joins"] == 1.0
    assert value["mesh_dispatch_share.mesh10"] == 100.0
    assert value["compiles_in_window.joins"] == 0.0
    assert value["h2d_bytes_per_query.joins"] == 0.0
    assert value["d2h_bytes_per_query.joins"] < 1 << 20
    # as the one-chip joins cell: Q5 five joins, Q10 and Q18 three; two
    # view builds a statement; Q10's GROUP BY cut to c_custkey
    assert value["pipe_joins_per_query.joins"] == pytest.approx(11 / 3)
    assert value["pipe_view_builds_per_query.joins"] == pytest.approx(2.0)
    assert value["agg_key_cut_per_query.joins"] == pytest.approx(1 / 3)
    # Q5's view of (orders join customer): its validity and c_nationkey
    # cross the mesh whole at orders' 2^17 bucket, every statement
    assert value["reshard_bytes_per_query.mesh10"] == (1 << 17) * 9 / 3
    window = next(json.loads(line) for line in done.stdout.splitlines()
                  if line.startswith('{"phase": "window"'))
    n = window["answered"]
    assert window["kernels"]["pipe_mesh_views"] == 2 * n
    assert window["kernels"]["agg_key_mesh"] == 2 * n / 3
    for name in ("max_rel_gap.q5", "max_rel_gap.q10", "max_rel_gap.q18"):
        c = result["compared"][name]
        assert c["value"] <= c["limit"], (name, c)


def test_the_control_does_not_pass():
    cell = harness.Cell(CELL)
    module = harness.load_module("datasets", cell.config["dataset"])
    verdict = control.control_verdict(cell, module.generate(0.05, 39),
                                      module)
    assert verdict["correct"] is False
    c = verdict["compared"]
    assert c["unanswered"]["value"] == c["wrong_answers"]["value"] == 0
    over = [k for k in ("max_rel_gap.q5", "max_rel_gap.q10",
                        "max_rel_gap.q18")
            if c[k]["value"] > c[k]["limit"]]
    assert over, c


def test_a_program_without_the_mesh_counters_is_refused(monkeypatch):
    from tinysql_tpu.ops import kernels
    module = harness.load_module("datasets", "tpch_joins_blocks")
    monkeypatch.setattr(kernels, "STATS", {
        k: v for k, v in kernels.STATS.items()
        if k not in module.MESH_COUNTERS})
    made = []
    monkeypatch.setattr(module.blocks, "generate",
                        lambda sf, seed: made.append(sf))
    with pytest.raises(RuntimeError, match="agg_key_mesh"):
        module.generate(10.0, 1)
    assert not made

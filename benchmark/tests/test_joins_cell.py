"""The cell ``tpch_sf1_joins.join_stream``: its files parse and name what
exists, its CPU rehearsal ends ``correct`` with every ``.joins`` metric
that needs no device, its plain reference answers the three templates and
the float32 control does not pass, and a program without the fused join
chains is refused before any data is made.

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

CELL = "tpch_sf1_joins.join_stream"
#: per-layer metrics that only a device trace can give, and the heap
#: profiler's span, which the rehearsal's settings turn off
NOT_REHEARSED = {"device_idle_share.joins", "device_busy_ms_per_query.joins",
                 "scan_roofline.joins", "memprof_traced_ms_per_query.joins"}


def test_files_parse_and_name_what_exists():
    cell = harness.Cell(CELL)
    assert cell.entry == {
        "name": CELL, "config": "tpch_sf1_joins", "traffic": "join_stream",
        "chips": 1, "why": cell.entry["why"]}
    assert len(cell.entry["why"]) <= 200
    config, base = cell.config, harness.load_json(BENCH, "configs",
                                                  "tpch_sf1.json")
    assert config["dataset"] == "tpch_joins"
    # tpch_sf1's deployment in everything but the query set
    for key in ("deployment", "scale_factor", "chips", "sysvars",
                "guarantees", "precision", "server", "rehearsal",
                "rows_at_this_scale"):
        assert config[key] == base[key], key
    assert config["assumed"][:len(base["assumed"])] == base["assumed"]
    assert config["sysvars"] == {"tidb_auto_prewarm": 0}
    mix = cell.mix
    assert (mix["driver"], mix["connections"], mix["order"], mix["close"]) \
        == ("closed_loop", 1, "cycle", "equal_rounds")
    assert mix["warmup"] == [{"connections": 1, "statements": 6}]
    statements = traffic.expand(mix)
    assert [s.kind for s in statements] == ["q5", "q10", "q18"]
    module = harness.load_module("datasets", config["dataset"])
    assert sorted(module.REFERENCES) == ["q1", "q10", "q18", "q3", "q5",
                                         "q6"]
    rows = {t: 1 for t in module.SCHEMAS}
    for s in statements:
        assert s.reference in module.REFERENCES
        assert module.scan_bytes(s.reads, rows) > 0  # every column exists
        # the statement names every column its ``reads`` lists
        assert all(c in s.sql for cols in s.reads.values() for c in cols)
    assert cell.sources() and set(cell.sources()) <= {
        "kernels", "pipes", "spans", "summary", "jax"}
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == len(set(names)) == 25
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


def test_rehearsal_ends_correct_with_every_joins_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000035", "--seconds", "3", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    # whole rounds only
    assert result["attempted"] >= 3 and result["attempted"] % 3 == 0
    cell = harness.Cell(CELL)
    want = {m["name"] for m in cell.per_layer} - NOT_REHEARSED
    got = result["metrics"]
    assert want == set(got), sorted(want ^ set(got))
    value = {k: v["value"] for k, v in got.items()}
    assert value["dispatches_per_query.joins"] == 1.0
    assert value["compiles_in_window.joins"] == 0.0
    assert value["h2d_bytes_per_query.joins"] == 0.0
    assert value["d2h_bytes_per_query.joins"] < 1 << 20
    # Q5 five joins, Q10 and Q18 three; Q10's GROUP BY cut to c_custkey
    assert value["pipe_joins_per_query.joins"] == pytest.approx(11 / 3)
    assert value["pipe_view_builds_per_query.joins"] > 0
    assert value["agg_key_cut_per_query.joins"] == pytest.approx(1 / 3)
    for name in ("max_rel_gap.q5", "max_rel_gap.q10", "max_rel_gap.q18"):
        c = result["compared"][name]
        assert c["value"] <= c["limit"], (name, c)


SF = 0.05


@pytest.fixture(scope="module")
def made():
    module = harness.load_module("datasets", "tpch_joins")
    return module, module.generate(SF, 2_147_483_683)


def test_reference_answers_and_the_control_does_not_pass(made):
    module, ds = made
    cell = harness.Cell(CELL)
    answers = {s.kind: module.REFERENCES[s.reference](ds, s.params)
               for s in traffic.expand(cell.mix)}
    assert [type(x) for x in answers["q5"][0]] == [str, float]
    assert 1 <= len(answers["q5"]) <= 5  # ASIA's nations
    revenue = [r[1] for r in answers["q5"]]
    assert revenue == sorted(revenue, reverse=True)
    assert len(answers["q10"]) == 20
    assert [type(x) for x in answers["q10"][0]] == [
        int, str, float, float, str, str, str, str]
    assert [type(x) for x in answers["q18"][0]] == [
        str, int, int, str, float, float]
    assert all(r[5] > 300 for r in answers["q18"])
    totals = [(-r[4], r[3]) for r in answers["q18"]]
    assert totals == sorted(totals) and 0 < len(totals) <= 100
    verdict = control.control_verdict(cell, ds, module)
    assert verdict["correct"] is False
    gaps = {name: c for name, c in verdict["compared"].items()
            if name.startswith("max_rel_gap.")}
    assert sorted(gaps) == ["max_rel_gap.q10", "max_rel_gap.q18",
                            "max_rel_gap.q5"]
    # float32 rounds every double an answer holds: each template fails
    assert all(c["value"] > c["limit"] for c in gaps.values()), gaps


def test_a_program_without_the_fused_join_chains_is_refused(monkeypatch):
    pipes = harness.load_module("sources", "pipes")
    from tinysql_tpu.ops import kernels
    pipes.start()  # this program has them
    assert set(pipes.snapshot()) == set(pipes.KEYS)
    monkeypatch.setattr(kernels, "STATS", {
        k: v for k, v in kernels.STATS.items() if k not in pipes.KEYS})
    with pytest.raises(RuntimeError, match="no fused join chains"):
        pipes.start()
    assert pipes.snapshot() == {}

"""PR 37's per-layer entries (the host's turn of a statement, by span):
the CPU rehearsal of ``tpch_sf1.power_stream`` prints every one of them
on the ``layers`` line of an UNTRACED run, the dash's lists no
``pipe_prepare_ms_per_query``, and the identity PERF.md §5 keeps holds
on the rehearsal's own ``window`` line (``tools/host_turn.py``).

The rehearsal runs in a process of its own (see ``test_mesh_cell.py``).
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
import run as harness

CELL = "tpch_sf1.power_stream"
QUANTITIES = ("client_turn", "wire_write", "pool_wake", "round_form",
              "queue_wait", "dispatch", "exec_build", "plan_publish",
              "pipe_prepare", "exec_rows", "stmt_finish", "place",
              "host_unnamed")


def test_the_cells_list_the_new_entries():
    stream = {m["name"] for m in harness.Cell(CELL).per_layer}
    want = {f"{q}_ms_per_query.streams" for q in QUANTITIES}
    assert want <= stream
    dash = {m["name"] for m in harness.Cell("tpch_sf1.q6_dash_16c").per_layer}
    assert not any(n.startswith("pipe_prepare_ms_per_query") for n in dash)
    assert "dispatch_ms_per_query.serve" not in dash  # round_dispatch has it
    assert {f"{q}_ms_per_query.serve" for q in QUANTITIES
            if q not in ("pipe_prepare", "dispatch")} <= dash
    # the joins cell sends no Q6: no round forms there
    joins = {m["name"]
             for m in harness.Cell("tpch_sf1_joins.join_stream").per_layer}
    assert "round_form_ms_per_query.streams" not in joins
    assert want - {"round_form_ms_per_query.streams"} <= joins


def test_an_untraced_rehearsal_prints_every_new_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000037", "--seconds", "3", "--trace", "0",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    (layers,) = [ln for ln in lines if ln.get("phase") == "layers"]
    for q in QUANTITIES:
        got = layers[f"{q}_ms_per_query.streams"]
        assert got["unit"] == "ms" and got["value"] >= 0.0, q
    # the identity, from the window line's span growth
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import host_turn
    (window,) = [ln for ln in lines if ln.get("phase") == "window"]
    turn = host_turn.host_turn(window)
    assert turn["idle_plus_command_over_wall"] == pytest.approx(1.0,
                                                                abs=0.01)
    # (the window line rounds each sum to a microsecond)
    assert turn["host_unnamed"] == pytest.approx(
        layers["host_unnamed_ms_per_query.streams"]["value"], rel=1e-3)
    assert turn["wire.idle"] == pytest.approx(
        layers["client_turn_ms_per_query.streams"]["value"], rel=1e-3)
    # every millisecond of the host's turn has a name or is counted
    # unnamed; what lies between spans across threads is small
    assert turn["host_turn"] == pytest.approx(
        turn["named_sum"] + turn["host_unnamed"] + turn["round.self"]
        + turn["left_over"])
    assert abs(turn["left_over"]) < 0.05 * turn["host_turn"]

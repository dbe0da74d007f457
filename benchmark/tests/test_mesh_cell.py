"""The four-chip cell: its CPU rehearsal prints a correct result with
every ``.mesh`` metric that needs no device, and the mesh roofline's
byte count and divisor against a hand count.

The rehearsal runs in a process of its own: the configuration's
``rehearsal`` block forces four host devices through ``XLA_FLAGS``, which
jax reads once, before this test process may already have started it.
"""
import json
import os
import subprocess
import sys
import types

from conftest import BENCH, ROOT
import run as harness

CELL = "tpch_sf1_mesh4.power_stream"
#: the cell's per-layer metrics that only a device trace can give
DEVICE_ONLY = {"device_idle_share.mesh", "device_busy_ms_per_query.mesh",
               "mesh_scan_roofline"}


def test_rehearsal_prints_every_mesh_metric_that_needs_no_device():
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
    assert cell.config["sysvars"] == {"tidb_auto_prewarm": 0,
                                      "tidb_mesh_parallel": 1,
                                      "tidb_memprof_rate": 0}
    want = {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    got = result["metrics"]
    assert want == set(got), sorted(want ^ set(got))
    assert {n for n in want if n.endswith(".mesh")} >= {
        "q1_ms.mesh", "q3_ms.mesh", "q6_ms.mesh", "plan_ms_per_query.mesh",
        "exec_self_ms_per_query.mesh", "drain_ms_per_query.mesh",
        "dispatches_per_query.mesh", "h2d_bytes_per_query.mesh",
        "compiles_in_window.mesh", "agg_dense_per_query.mesh",
        "background_ms_per_query.mesh", "reshard_bytes_per_query.mesh",
        "mesh_dispatch_share.mesh"}
    assert "setup_mesh_place_s" in got
    # one dispatch a statement, every one over the whole mesh, no input
    # laid out anew, nothing compiled in the window
    assert got["dispatches_per_query.mesh"]["value"] == 1.0
    assert got["mesh_dispatch_share.mesh"]["value"] == 100.0
    assert got["reshard_bytes_per_query.mesh"]["value"] == 0.0
    assert got["compiles_in_window.mesh"]["value"] == 0.0


def _fake_run(busy_s, records, trace=(0.0, 8.0), chips=4):
    tpch = harness.load_module("datasets", "tpch")
    with open(os.path.join(BENCH, "traffic", "power_stream.json")) as f:
        templates = json.load(f)["templates"]
    statements = [types.SimpleNamespace(reads=t["reads"], kind=t["kind"])
                  for t in templates]
    rows = {"lineitem": 6_001_215, "orders": 1_500_000,
            "customer": 150_000}
    tables = {t: {"c": range(n)} for t, n in rows.items()}
    return types.SimpleNamespace(
        trace=types.SimpleNamespace(busy_s=busy_s),
        trace_start=trace[0], trace_stop=trace[1], answered=records,
        statements=statements, dataset_module=tpch,
        dataset=types.SimpleNamespace(tables=tables),
        cell=types.SimpleNamespace(entry={"chips": chips}),
        peaks={"hbm_bytes_per_s": 819e9})


def test_mesh_scan_roofline_by_hand():
    reader = harness.load_module("readers", "mesh_scan_roofline")
    # one Q6 wholly inside the traced part, one Q1 half inside
    records = [[0, 2, 1.0, 2.0, [], None], [0, 0, 7.0, 9.0, [], None]]
    run = _fake_run(busy_s=0.5, records=records)
    shares = reader.inside(run)
    assert shares == [(2, 1.0), (0, 0.5)]
    # Q6 reads 34 bytes a row of lineitem, Q1 44 (test_roofline_bytes.py)
    need = 34 * 6_001_215 + 0.5 * 44 * 6_001_215
    assert reader.scan_seconds(run, shares) == need / (4 * 819e9)
    assert reader.read(run) == 100.0 * (need / (4 * 819e9)) / 0.5
    # four times under what the one-chip reader would say of the same run
    one_chip = harness.load_module("readers", "trace")
    assert abs(one_chip.read(run, "scan_roofline") / reader.read(run)
               - 4.0) < 1e-12
    # the divisor is the cell's chips, not a constant
    assert reader.read(_fake_run(0.5, records, chips=1)) == \
        one_chip.read(run, "scan_roofline")


def test_mesh_scan_roofline_has_nothing_to_read_without_a_trace():
    reader = harness.load_module("readers", "mesh_scan_roofline")
    run = _fake_run(0.5, [[0, 2, 1.0, 2.0, [], None]])
    run.trace = None
    assert reader.read(run) is None
    run = _fake_run(0.5, [[0, 2, 9.0, 10.0, [], None]])  # outside
    assert reader.read(run) is None


def test_counter_share_reads_nothing_from_a_program_without_the_counter():
    reader = harness.load_module("readers", "counter_share")
    run = types.SimpleNamespace(deltas={"kernels": {"dispatches": 12}})
    assert reader.read(run, "kernels", "mesh_dispatches",
                       "dispatches") is None
    run.deltas["kernels"]["mesh_dispatches"] = 9
    assert reader.read(run, "kernels", "mesh_dispatches",
                       "dispatches") == 75.0

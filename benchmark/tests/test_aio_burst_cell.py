"""The cell ``tpch_sf1_aio.idle_1k_burst``: its files parse and name what
exists, the burst generator releases every connection's statement once a
burst and closes a window on a whole burst, and the CPU rehearsal runs
1000 connections through whole bursts to ``correct`` with nothing failed.

The rehearsal runs in a process of its own (see ``test_mesh_cell.py``).
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
import burstgen
import control
import run as harness
import traffic

CELL = "tpch_sf1_aio.idle_1k_burst"
DASH = "tpch_sf1.q6_dash_16c"
#: per-layer metrics that only a device trace can give, and the heap
#: profiler's span, which the rehearsal's settings turn off
NOT_REHEARSED = {"device_idle_share.serve", "device_busy_ms_per_query.serve",
                 "memprof_traced_ms_per_query.serve"}


def test_files_parse_and_name_what_exists():
    cell = harness.Cell(CELL)
    assert cell.entry == {
        "name": CELL, "config": "tpch_sf1_aio", "traffic": "idle_1k_burst",
        "chips": 1, "why": cell.entry["why"]}
    config, base = cell.config, harness.load_json(BENCH, "configs",
                                                  "tpch_sf1.json")
    # tpch_sf1's deployment in everything but the front end and the queue
    for key in ("dataset", "deployment", "scale_factor", "chips",
                "precision", "server", "rehearsal", "rows_at_this_scale"):
        assert config[key] == base[key], key
    assert config["sysvars"] == {"tidb_auto_prewarm": 0,
                                 "tidb_wire_mode": "'aio'",
                                 "tidb_stmt_pool_queue_depth": 1000}
    assert config["guarantees"][:len(base["guarantees"])] \
        == base["guarantees"]
    assert "no statement is shed" in config["guarantees"][-1]
    assert config["assumed"][:len(base["assumed"])] == base["assumed"]
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "tpch_sf1_aio"]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    mix = cell.mix
    assert (mix["driver"], mix["connections"], mix["order"]) \
        == ("burst", 1000, "shuffle")
    dash = harness.Cell(DASH)
    assert mix["templates"] == dash.mix["templates"]
    assert len(traffic.expand(mix)) == 80  # one digest's 80 variants
    assert mix["warmup"] == [
        {"connections": 1, "bursts": 2},
        {"connections": 1000, "bursts": 1, "until_quiet": 6, "quiet": 2}]
    # the dash's serving metrics, and the four set-up phases
    assert [m["name"] for m in cell.end_to_end] == ["serve_queries_per_s",
                                                    "setup_s"]
    dash_names = {m["name"] for m in dash.per_layer}
    assert {m["name"] for m in cell.per_layer} == dash_names
    assert len(dash_names) == 33
    assert set(cell.sources()) <= {"kernels", "batching", "spans",
                                   "summary", "jax"}


def test_the_float32_control_is_not_correct():
    """The dash's limit holds here: the reference in float32 fails it
    by the one gap, every answer present and every count right."""
    tpch = harness.load_module("datasets", "tpch")
    verdict = control.control_verdict(
        harness.Cell(CELL), tpch.generate(0.05, 2_420_005_001), tpch)
    compared = verdict["compared"]
    assert verdict["correct"] is False
    assert compared["unanswered"]["value"] == 0
    assert compared["wrong_answers"]["value"] == 0
    gap = compared["max_rel_gap.q6"]
    assert gap["value"] > gap["limit"]


def test_response_parser_waits_for_a_whole_answer():
    """An answer split anywhere is None until its last packet is in."""
    def pkt(seq, payload):
        return len(payload).to_bytes(3, "little") + bytes([seq]) + payload
    answer = (pkt(1, b"\x01") + pkt(2, b"\x03def" + b"\x00" * 20)
              + pkt(3, b"\xfe\x00\x00\x02\x00") + pkt(4, b"\x041.25")
              + pkt(5, b"\xfe\x00\x00\x02\x00"))
    for cut in range(len(answer)):
        assert burstgen._response(bytearray(answer[:cut])) is None
    assert burstgen._response(bytearray(answer + b"xx")) == (
        [["1.25"]], None, len(answer))
    err = pkt(1, b"\xff\x11\x04#HY000too many")
    assert burstgen._response(bytearray(err))[1] \
        == "ServerError: server error 1041: too many"


@pytest.fixture(scope="module")
def server():
    from tinysql_tpu.kv import new_mock_storage
    from tinysql_tpu.server.server import Server
    from tinysql_tpu.session.session import Session
    storage = new_mock_storage()
    srv = Server(storage, port=0)
    srv.start()
    boot = Session(storage)
    boot.execute("create database if not exists b")
    boot.execute("set global tidb_wire_mode = 'aio'")
    yield srv
    srv.close()


def _plan(srv, connections, **close):
    sqls = [f"select {i}" for i in range(5)]
    orders = traffic.orders({"order": "shuffle"}, len(sqls), connections,
                            2_147_483_691)
    return dict({"port": srv.port, "db": "b", "statements": sqls,
                 "connections": orders, "bursts": None, "seconds": None},
                **close), orders


def test_a_burst_releases_every_connections_statement_once(server):
    plan, orders = _plan(server, 40, bursts=3)
    result = burstgen.run(plan, lambda: None)
    records = result["records"]
    assert len(records) == 3 * 40 and all(r[5] is None for r in records)
    for conn, order in enumerate(orders):
        mine = sorted((r for r in records if r[0] == conn),
                      key=lambda r: r[2])
        assert [r[1] for r in mine] == order[:3]
        assert [r[4] for r in mine] == [[[str(i)]] for i in order[:3]]
    # burst k + 1 is released once burst k's last answer is in
    by_burst = sorted(records, key=lambda r: r[2])
    for k in range(2):
        burst, nxt = by_burst[40 * k:40 * (k + 1)], by_burst[40 * (k + 1):]
        assert max(r[3] for r in burst) <= min(r[2] for r in nxt)


def test_a_window_closes_on_a_whole_burst(server):
    plan, _ = _plan(server, 30, seconds=0.3)
    result = burstgen.run(plan, lambda: None)
    records = sorted(result["records"], key=lambda r: r[2])
    assert len(records) % 30 == 0 and len(records) >= 60
    assert all(r[5] is None for r in records)
    # every burst was released before the seconds had passed, and the
    # last was awaited whole
    for k in range(0, len(records), 30):
        assert records[k][2] - result["start"] < 0.3
    assert result["end"] >= max(r[3] for r in records)


def test_rehearsal_runs_whole_bursts_of_1000_to_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000042", "--seconds", "3", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1000 and result["attempted"] % 1000 == 0
    (window,) = [d for d in lines if d.get("phase") == "window"]
    spans = window["spans"]
    assert spans["aio_submits.count"] == result["attempted"]
    assert spans["aio_conns_max.count"] >= 1000
    assert spans["aio.tick.count"] > 0
    cell = harness.Cell(CELL)
    want = {m["name"] for m in cell.per_layer} - NOT_REHEARSED
    got = result["metrics"]
    assert want <= set(got), sorted(want - set(got))
    value = {k: v["value"] for k, v in got.items()}
    assert value["stacked_share.serve"] >= 90
    assert value["compiles_in_window.serve"] == 0.0
    assert result["compared"]["max_rel_gap.q6"]["value"] <= 1e-10

"""``chip_smoke.py`` rehearsed in-process on the CPU at a tiny scale: the
phases, the checks and the last line — so a change that breaks the script
fails tier-1 here and not a chip call.

On the CPU the engine serves joins, top-k and group-by with numpy twins
and keeps the fused pipeline off, so ``host_dispatches == 0`` cannot hold
with defaults: THIS TEST turns the twins off and the pipeline on, lowers
the row gate to the tiny data, and stops the server's profilers (their
tracing makes a cold statement several times slower).  The script's own
defaults stay the chip's.
"""
import json
import logging
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import chip_smoke  # noqa: E402
from tinysql_tpu.ops import degrade  # noqa: E402
from tinysql_tpu.session import session as session_mod  # noqa: E402

TINY = ["--sf", "0.01", "--expect-platform", "cpu"]


@pytest.fixture
def chip_like(monkeypatch):
    monkeypatch.setenv("TINYSQL_DEVICE_JOIN_ONLY", "1")
    for name, value in (("tidb_devpipe", 1), ("tidb_tpu_min_rows", 64),
                        ("tidb_memprof_rate", 0), ("tidb_conprof_rate", 0)):
        monkeypatch.setitem(session_mod.DEFAULT_SYSVARS, name, value)
    degrade.reset()  # process-wide counters: other test files move them


def _lines(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return [json.loads(line) for line in out]


def test_smoke_passes_on_tiny_data(chip_like, capsys):
    rc = chip_smoke.main(TINY)
    lines = _lines(capsys)
    assert rc == 0, lines
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": lines[0]["count"]}}
    phases = [line["phase"] for line in lines[:-1]]
    assert phases == ["device", "load", "queries", "queries", "queries",
                      "write-then-read", "write-then-read",
                      "write-then-read", "nothing-hid-the-device"]
    for line in lines[:-1]:
        assert all(line.get("checks", {}).values()), line
    by_query = {line["query"]: line for line in lines if "query" in line}
    for name in ("Q1", "Q3", "Q6"):
        q = by_query[name]
        assert q["placed_on_device"], q
        assert q["cold"]["dispatches"] > 0
        assert q["warm"]["progcache_misses"] == 0
        assert q["warm"]["host_dispatches"] == 0
    assert by_query["agg-after-update"]["replica"]["h2d_bytes"] > 0


def test_mesh_smoke_passes_on_tiny_data(chip_like, capsys):
    """``--mesh`` over conftest's forced host devices: every check holds,
    the mesh phase among them to no numpy twin, one warm dispatch a
    statement and no input laid out anew."""
    rc = chip_smoke.main(TINY + ["--mesh"])
    lines = _lines(capsys)
    assert rc == 0, lines
    phases = [line["phase"] for line in lines[:-1]]
    assert phases == ["device", "load", "mesh", "mesh", "mesh", "mesh"]
    for line in lines[:-1]:
        assert all(line.get("checks", {}).values()), line
    by_query = {line["query"]: line for line in lines if "query" in line}
    assert set(by_query) == {"Q1", "Q3", "Q6"}
    for q in by_query.values():
        assert q["mesh_warm"]["host_dispatches"] == 0
        assert q["mesh_warm"]["dispatches"] == 1
        assert q["mesh_warm"]["reshard_bytes"] == 0
    for name in ("Q1", "Q3"):
        assert by_query[name]["mesh_warm"]["mesh_dispatches"] == 1
    assert "fullest_device_under_half_of_all" in lines[-2]["checks"]


def test_smoke_fails_on_the_wrong_platform(capsys):
    """No option: the script expects ``tpu``, jax finds the cpu — it must
    stop before any data is made, say ``"ok": false``, and exit non-zero."""
    rc = chip_smoke.main(["--sf", "0.01"])
    lines = _lines(capsys)
    assert rc != 0
    assert [line.get("phase") for line in lines[:-1]] == ["device"]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"


def test_smoke_fails_on_a_warning(chip_like, monkeypatch, capsys):
    """A WARNING on the ``tinysql_tpu`` logger during a phase — where the
    fused pipeline's bail-out and the degraded re-run speak — fails the
    run, even when every answer was right; a child logger's does not."""
    def noisy_queries(env):
        logging.getLogger("tinysql_tpu.slowlog").warning("a slow statement")
        logging.getLogger("tinysql_tpu").warning(
            "devpipe run failed, per-operator fallback")
    monkeypatch.setattr(chip_smoke, "phase_queries", noisy_queries)
    monkeypatch.setattr(chip_smoke, "phase_write_then_read",
                        lambda env: None)
    rc = chip_smoke.main(TINY)
    lines = _lines(capsys)
    assert rc != 0
    assert lines[-1]["ok"] is False
    last_phase = lines[-2]
    assert last_phase["phase"] == "nothing-hid-the-device"
    assert last_phase["warnings"] == [
        "devpipe run failed, per-operator fallback"]
    failed = [k for k, ok in last_phase["checks"].items() if not ok]
    assert failed == ["no_warning_on_tinysql_tpu_logger"]

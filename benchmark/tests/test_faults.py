"""A whole run with the timed path broken underneath has to come out as
not correct.  The harness's look for a chip is skipped (``--expect-platform
cpu``: tiny scale, the chip's branches forced on); everything else of a run
is driven: load, server, the load generator's process, the window, the
comparison.  The faults are those a served database can have:

- an answer altered where it is produced (the server's row writer);
- half of the batch left out (every second row of ``lineitem`` never
  reaches the replica).

A step that returns its state unchanged and an exchange between chips left
out are faults of training and of a mesh; these cells have neither.
"""
import json

import pytest

import run as harness

CELLS = ("tpch_sf1.power_stream", "tpch_sf1.q6_dash_16c")


def _run(capsys, cell, seed=2_147_483_777):
    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "1", "--trace", "0",
                       "--expect-platform", "cpu"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell, capsys):
    rc, result = _run(capsys, cell)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert result["attempted"] > 0
    assert "setup_s" in result["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_is_not_correct(cell, capsys, monkeypatch):
    from tinysql_tpu.server import protocol
    sound = protocol.text_row

    def altered(values):
        # one part in a million on every double the server writes
        return sound([v * (1 + 1e-6) if isinstance(v, float) else v
                      for v in values])
    monkeypatch.setattr(protocol, "text_row", altered)
    rc, result = _run(capsys, cell)
    assert rc == 0
    assert result["correct"] is False
    assert all(c["value"] > c["limit"]
               for name, c in result["compared"].items()
               if name.startswith("max_rel_gap."))


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_rows_left_out_is_not_correct(cell, capsys,
                                                  monkeypatch):
    from tinysql_tpu.columnar import store
    sound = store.bulk_load

    def half(storage, info, data, *args, **kw):
        if info.name == "lineitem":
            data = {c: v[::2] for c, v in data.items()}
        return sound(storage, info, data, *args, **kw)
    monkeypatch.setattr(store, "bulk_load", half)
    rc, result = _run(capsys, cell)
    assert rc == 0
    assert result["correct"] is False


def test_the_wrong_platform_prints_no_result(capsys):
    """No option: the harness expects ``tpu``; here jax finds the cpu."""
    rc = harness.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc != 0
    assert [json.loads(line).get("phase") for line in out] == ["device"]

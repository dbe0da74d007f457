"""Every file BENCHMARK.json names loads, and every name, unit and text
keeps to what the contract allows."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT
import run as harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _load(ROOT, "BENCHMARK.json")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert all(_line(w) for w in bench["command"])


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in bench[group]]
        assert len(seen) == len(set(seen)), group
        names += seen
    for w in bench["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and _line(w["why"])
    assert all(NAME.match(n) for n in names), names
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"])


def test_configs_and_traffic_load(bench):
    paths = tuple(p.rstrip("/") + "/" for p in bench["paths"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(paths) and _line(c["source"])
        config = _load(ROOT, c["file"])
        for kind in ("dataset", "deployment"):
            assert os.path.exists(os.path.join(
                BENCH, kind + "s", config[kind] + ".py"))
        assert config["guarantees"]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        mix = _load(BENCH, "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            BENCH, "drivers", mix["driver"] + ".py"))
        assert mix["templates"] and mix["warmup"]


def test_every_metric_has_its_reader_and_cells(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    for kind, group in (("end_to_end", bench["end_to_end"]),
                        ("layer_metrics", bench["per_layer"])):
        for m in group:
            spec = harness.metric_spec(kind, m["name"])
            assert os.path.exists(os.path.join(
                BENCH, "readers", spec["reader"] + ".py")), m["name"]
            for source in spec.get("sources", []):
                assert os.path.exists(os.path.join(
                    BENCH, "sources", source + ".py")), m["name"]
            assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        # a per-layer metric's cells all report the metric it moves
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
    for cell in cells:
        assert sum(cell in on for on in e2e.values()) >= 2
        assert any(cell in m["workloads"] for m in bench["per_layer"])


def test_nothing_else_under_the_metric_directories(bench):
    """A file is named as its metric is or, where cells report one
    quantity under names of their own, as the name less its last part."""
    for kind, group in (("end_to_end", bench["end_to_end"]),
                        ("layer_metrics", bench["per_layer"])):
        have = {f[:-5] for f in os.listdir(os.path.join(BENCH, kind))}
        names = {m["name"] for m in group}
        assert have <= names | {n.rpartition(".")[0] for n in names}

"""TPC-H's Q3 over the mesh: which way its ``customer`` join goes, and why.

Whether a mesh join broadcasts its build side or exchanges both sides by
key is a cost in bytes (``dist.broadcast_over_budget``: what the copies
add to a device against a share of the memory the device reports, read
once).  At the shipped budget Q3 broadcasts, as the SF=10 cell's does
(PR 33).  Here, on four host devices and tiny tables, the partitioned
join is also chosen the two ways a deployment can reach it — the byte
budget under the build side, and the operator's knob in rows — and must
run INSIDE the one fused mesh program and answer row for row what one
device answers and what the benchmark's plain reference (numpy over the
same arrays) answers.  The skew gate's fall-back — a broadcast after the
build leaf was left row-sharded — answers alike.
"""
import importlib.util
import json
import os

import jax
import pytest

from tinysql_tpu.columnar.store import bulk_load
from tinysql_tpu.executor import devpipe
from tinysql_tpu.ops import kernels, shardops
from tinysql_tpu.parallel import dist
from tinysql_tpu.session.session import new_session

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs a four-device mesh")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF = 0.02
N = 4  # the mesh


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"pj_{kind}_{name}",
        os.path.join(ROOT, "benchmark", kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def q3():
    """(session, dataset, reference module, Q3's SQL, its parameters):
    the benchmark's own tables at SF 0.02."""
    tpch = _bench_module("datasets", "tpch")
    prev_floor = dist.MIN_SHARD_ROWS
    dist.MIN_SHARD_ROWS = 16
    ds = tpch.generate(SF, 33)
    s = new_session()
    s.execute(f"create database {tpch.DATABASE}")
    s.execute(f"use {tpch.DATABASE}")
    for table in ("customer", "orders", "lineitem"):
        s.execute(tpch.SCHEMAS[table])
        info = s.infoschema().table_by_name(tpch.DATABASE, table)
        # the replica keeps what it is handed: give it copies
        bulk_load(s.storage, info,
                  {c: v.copy() for c, v in ds.tables[table].items()})
    s.execute("set @@tidb_devpipe = 1")
    s.execute("set @@tidb_tpu_min_rows = 64")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "power_stream.json")) as f:
        template = next(t for t in json.load(f)["templates"]
                        if t["kind"] == "q3")
    params = {k: v[0] for k, v in template["parameters"].items()}
    yield s, ds, tpch, template["sql"].format(**params), params
    dist.MIN_SHARD_ROWS = prev_floor


def _four_device_mesh(monkeypatch):
    monkeypatch.setattr(
        dist, "session_mesh",
        lambda sv: dist.sized_mesh(N) if sv.get("tidb_mesh_parallel")
        else None)


def _same(got, want, rel=1e-9):
    assert len(got) == len(want), (got, want)
    for a, b in zip(got, want):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            if isinstance(y, float):
                assert abs(float(x) - y) <= rel * max(abs(y), 1.0), (a, b)
            else:
                assert str(x) == str(y), (a, b)


def _bucket(n):
    return 1 << int(n - 1).bit_length()


@pytest.mark.parametrize("how", ["shipped_budget", "byte_budget",
                                 "knob_in_rows", "skew_gate"])
def test_q3_customer_join_in_one_mesh_program(q3, monkeypatch, how):
    s, ds, tpch, sql, params = q3
    _four_device_mesh(monkeypatch)
    s.execute("set @@tidb_mesh_parallel = 0")
    single = s.query(sql).rows
    reference = tpch.REFERENCES["q3"](ds, params)
    assert len(reference) == 10
    _same(single, reference)

    nbb = _bucket(len(ds.tables["customer"]["c_custkey"]))
    if how == "byte_budget":
        # under what customer's two columns add to a device, over
        # nothing else: the planner and the executor both read it (the
        # aggregate's table, the other join's build side, is over it too
        # but its probe side, a join's output, has no host-visible keys
        # to size an exchange from)
        monkeypatch.setattr(
            dist, "broadcast_budget_bytes",
            lambda: nbb * 2 * dist.COST_COLUMN_BYTES * (N - 1) / N - 1)
    elif how != "shipped_budget":
        s.execute(f"set @@tidb_broadcast_build_max_rows = {nbb - 1}")
    if how == "skew_gate":
        # a (source, destination) pair so full that the receive buffers
        # pass both of the gate's limits: it must refuse and broadcast
        # the leaf it had left spread (the capacities are memoized with
        # the replica: patched above the memo)
        monkeypatch.setattr(devpipe._JoinNode, "_shuffle_cap_of",
                            staticmethod(lambda *a: devpipe.MAX_EXPAND))
    before = kernels.stats_snapshot()
    shard_before = shardops.stats_snapshot()
    s.execute("set @@tidb_mesh_parallel = 1")
    try:
        sharded = s.query(sql).rows
        warm = s.query(sql).rows
    finally:
        s.execute("set @@tidb_mesh_parallel = 0")
        s.execute("set @@tidb_broadcast_build_max_rows = 1048576")
    delta = kernels.stats_delta(before)
    _same(sharded, single, rel=1e-12)
    _same(sharded, reference)
    assert warm == sharded
    # one fused program a statement, over the whole mesh, no host tier
    assert delta["dispatches"] == 2 and delta["host_dispatches"] == 0
    assert delta["reshard_bytes"] == 0
    # an exchange is sized when a partitioned join is prepared
    # (shardops.record_exchange), a refusal counted by the skew gate
    shard = shardops.stats_snapshot()
    partitioned = how in ("byte_budget", "knob_in_rows")
    assert (shard["shard_exchange_bytes"]
            > shard_before["shard_exchange_bytes"]) == partitioned
    assert shard["shard_skew_retries"] - shard_before["shard_skew_retries"] \
        == (2 if how == "skew_gate" else 0)


def test_the_budget_is_a_share_of_what_the_device_reports(monkeypatch,
                                                          caplog):
    """The device's limit is read once, a CPU that reports none takes the
    fixed budget quietly, any other platform that reports none says so at
    WARNING; and at a v5e's limit TPC-H SF=10's build sides broadcast
    (``customer``: 2^21 rows x 2 columns; Q3's merged table: 2^24 x 3)
    where SF=100's merged table would not."""
    class Device:
        def __init__(self, platform, stats):
            self.platform, self.stats, self.asked = platform, stats, 0

        def memory_stats(self):
            self.asked += 1
            return self.stats

    def budget_on(device):
        monkeypatch.setattr(dist, "_DEVICE_LIMIT_BYTES", None)
        monkeypatch.setattr(kernels.jax(), "devices", lambda: [device])
        return dist.broadcast_budget_bytes()

    v5e = Device("tpu", {"bytes_limit": 16_909_336_576})
    with caplog.at_level("WARNING", logger="tinysql_tpu"):
        assert budget_on(v5e) == 16_909_336_576 / 8
        col = dist.COST_COLUMN_BYTES
        assert not dist.broadcast_over_budget((1 << 21) * 2 * col, N)
        assert not dist.broadcast_over_budget((1 << 24) * 3 * col, N)
        assert dist.broadcast_over_budget((1 << 28) * 3 * col, N)
        assert v5e.asked == 1
        assert budget_on(Device("cpu", None)) == dist.NO_LIMIT_BUDGET_BYTES
        assert not caplog.records
        assert budget_on(Device("tpu", {})) == dist.NO_LIMIT_BUDGET_BYTES
    assert [r.levelname for r in caplog.records] == ["WARNING"]

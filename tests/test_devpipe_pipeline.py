"""Async block pipeline (executor/devpipe.BlockPipeline): ordering,
thread-safety under fault injection, cancellation, knob resolution, and
end-to-end sync-vs-async EQUIVALENCE on the block-wise SQL paths — the
TINYSQL_PIPELINE_DEPTH=0 byte-identical contract.  This file is the CI
pipeline smoke job (tiny table, 2 blocks, depth=2)."""
import threading
import time

import numpy as np
import pytest

from tinysql_tpu.columnar.store import bulk_load
from tinysql_tpu.executor.devpipe import BlockPipeline, pipeline_depth
from tinysql_tpu.ops import kernels
from tinysql_tpu.session.session import new_session


# ---- unit: the staging queue --------------------------------------------

def test_order_and_results_preserved():
    for depth in (0, 1, 2, 4):
        got = list(BlockPipeline(lambda i: i * i, range(20), depth=depth))
        assert got == [i * i for i in range(20)], depth


def test_depth0_is_synchronous_no_thread():
    pipe = BlockPipeline(lambda i: i, range(5), depth=0)
    assert pipe._thread is None
    assert list(pipe) == [0, 1, 2, 3, 4]


def test_empty_items():
    pipe = BlockPipeline(lambda i: i, [], depth=2)
    assert list(pipe) == []
    assert not pipe._thread.is_alive()


def test_fault_injection_reraises_on_caller_and_drains():
    """A stage-thread exception must surface on the CONSUMER at the
    failed block's position; earlier blocks still deliver and the
    producer thread exits cleanly (no leak, no deadlock)."""
    def stage(i):
        if i == 3:
            raise ValueError("boom@3")
        return i

    pipe = BlockPipeline(stage, range(10), depth=2)
    got = []
    with pytest.raises(ValueError, match="boom@3"):
        for v in pipe:
            got.append(v)
    assert got == [0, 1, 2]
    pipe._thread.join(timeout=5)
    assert not pipe._thread.is_alive()


def test_consumer_abandonment_unblocks_producer():
    """A consumer that stops pulling (depth-bounded queue full) must not
    leave the producer parked forever: close() cancels and joins."""
    staged = []

    def slow_stage(i):
        staged.append(i)
        return i

    pipe = BlockPipeline(slow_stage, range(100), depth=1)
    it = iter(pipe)
    assert next(it) == 0
    it.close()  # generator close -> finally -> pipe.close()
    pipe._thread.join(timeout=5)
    assert not pipe._thread.is_alive()
    assert len(staged) < 100  # cancelled well before draining all items


def test_concurrent_producer_consumer_overlap():
    """With a slow consumer the staging thread must run AHEAD (queue
    high-water reaches the depth bound) — the overlap the pipeline
    exists for."""
    def stage(i):
        return i

    pipe = BlockPipeline(stage, range(8), depth=2)
    out = []
    for v in pipe:
        time.sleep(0.02)  # device-compute stand-in
        out.append(v)
    assert out == list(range(8))
    st = pipe.stats()
    assert st["blocks"] == 8
    assert st["depth_hwm"] >= 1
    assert st["stage_s"] >= 0.0


def test_stage_runs_on_worker_thread():
    main = threading.get_ident()
    tids = []
    list(BlockPipeline(lambda i: tids.append(threading.get_ident()),
                       range(3), depth=2))
    assert tids and all(t != main for t in tids)


def test_depth_resolution(monkeypatch):
    monkeypatch.delenv("TINYSQL_PIPELINE_DEPTH", raising=False)
    assert pipeline_depth(None) == 2
    assert pipeline_depth({"tidb_pipeline_depth": 5}) == 5
    assert pipeline_depth({"tidb_pipeline_depth": 0}) == 0
    monkeypatch.setenv("TINYSQL_PIPELINE_DEPTH", "3")
    assert pipeline_depth({"tidb_pipeline_depth": 0}) == 3  # env wins
    monkeypatch.setenv("TINYSQL_PIPELINE_DEPTH", "0")
    assert pipeline_depth({"tidb_pipeline_depth": 7}) == 0


# ---- end to end: block-wise SQL paths, sync == async ---------------------

N = 600
BLOCK = 256  # 600 rows / 256 = 3 blocks (>= the 2-block smoke shape)


@pytest.fixture
def tk():
    s = new_session()
    s.execute("create database pipe")
    s.execute("use pipe")
    s.execute("set @@tidb_tpu_min_rows = 0")
    s.execute("set @@tidb_devpipe = 0")  # the per-operator block walks
    rng = np.random.default_rng(41)
    s.execute("create table f (id bigint primary key, k bigint, "
              "g bigint, x double)")
    bulk_load(s.storage, s.infoschema().table_by_name("pipe", "f"),
              {"id": np.arange(1, N + 1, dtype=np.int64),
               "k": rng.integers(1, 40, N).astype(np.int64),
               "g": rng.integers(0, 5, N).astype(np.int64),
               "x": rng.random(N) * 100})
    s.execute("create table d (k bigint primary key, v bigint)")
    bulk_load(s.storage, s.infoschema().table_by_name("pipe", "d"),
              {"k": np.arange(1, 40, dtype=np.int64),
               "v": (np.arange(1, 40, dtype=np.int64) * 7) % 13})
    return s


def _run_depth(s, q, depth, monkeypatch):
    monkeypatch.setenv("TINYSQL_PIPELINE_DEPTH", str(depth))
    s.execute("set @@tidb_use_tpu = 1")
    s.execute(f"set @@tidb_device_block_rows = {BLOCK}")
    snap = kernels.stats_snapshot()
    rows = s.query(q).rows
    d = kernels.stats_delta(snap)
    s.execute("set @@tidb_device_block_rows = 0")
    return rows, d


def test_blockwise_agg_sync_async_identical(tk, monkeypatch):
    q = ("select g, sum(x), count(*), min(x), max(x) from f "
         "group by g order by g")
    r0, d0 = _run_depth(tk, q, 0, monkeypatch)
    r2, d2 = _run_depth(tk, q, 2, monkeypatch)
    assert repr(r0) == repr(r2)  # byte-identical, not just tolerant
    assert d2["pipe_blocks"] >= (N + BLOCK - 1) // BLOCK
    assert d0["pipe_blocks"] == d2["pipe_blocks"]  # same block walk


def test_blockwise_scalar_sync_async_identical(tk, monkeypatch):
    q = "select sum(x), count(*) from f where x < 50"
    r0, _ = _run_depth(tk, q, 0, monkeypatch)
    r2, d2 = _run_depth(tk, q, 2, monkeypatch)
    assert repr(r0) == repr(r2)
    assert d2["pipe_blocks"] >= 2
    assert d2["pipe_wall_s"] >= 0.0


def test_join_stream_sync_async_identical(tk, monkeypatch):
    q = ("select sum(f.k + d.v) from f join d on f.k = d.k")
    r0, _ = _run_depth(tk, q, 0, monkeypatch)
    r2, _ = _run_depth(tk, q, 2, monkeypatch)
    assert repr(r0) == repr(r2)


def test_pipeline_metrics_exported(tk, monkeypatch):
    q = "select g, sum(x) from f group by g order by g"
    _, d = _run_depth(tk, q, 2, monkeypatch)
    for key in ("pipe_blocks", "pipe_stage_s", "pipe_dispatch_s",
                "pipe_drain_s", "pipe_wall_s", "pipe_depth_hwm",
                "progcache_hits", "progcache_misses"):
        assert key in d, key
    assert d["pipe_blocks"] >= 2


# ---- LD3xx stays clean on the new locks ----------------------------------

def test_lock_discipline_clean_on_pipeline():
    import os
    from tinysql_tpu.analysis import lint_lock_discipline
    from tinysql_tpu.analysis.diag import SourceFile
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sf = SourceFile(os.path.join(repo, "tinysql_tpu", "executor",
                                 "devpipe.py"))
    diags = lint_lock_discipline(sf)
    assert diags == [], "\n".join(d.format() for d in diags)

"""Memory truth (obs/memprof.py, ISSUE 18): heap-profiler folding /
rotation / eviction, statement heap attribution with the <=-growth
invariant, rate-0 byte-identity, overhead backoff, the /debug/heap
collapsed round trip, the device-buffer census + measured row widths
feeding the spill gates, memory_usage reconciliation over SQL, and the
heap-growth / hbm-pressure / mem-untracked inspection rules."""
import gc
import os
import sys
import threading
import time
import tracemalloc
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

from _timelimit import hit, in_wait, join

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tinysql_tpu import fail
from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.obs import conprof, inspect as oinspect
from tinysql_tpu.obs import memprof, stmtsummary
from tinysql_tpu.obs.memprof import (HeapProfiler, MemprofSampler,
                                     QueryMemProbe, classify_site,
                                     fold_site)
from tinysql_tpu.obs.tsring import MetricsRing
from tinysql_tpu.session.session import Session


def _frames(*labels):
    """Synthetic tracemalloc-style traceback: root->leaf (file, lineno)
    tuples from ``"name:lineno"`` labels."""
    out = []
    for lb in labels:
        name, _, ln = lb.partition(":")
        out.append((f"/src/{name}.py", int(ln or 1)))
    return tuple(out)


def _site_stats(k, size=2048):
    """k distinct single-site stats entries of `size` bytes each."""
    return [(_frames(f"alloc_{i}:10"), size) for i in range(k)]


@pytest.fixture
def session():
    storage = new_mock_storage()
    s = Session(storage)
    s.execute("create database mp")
    s.execute("use mp")
    s.execute("create table t (a int primary key, b int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i % 7})" for i in range(500)))
    stmtsummary.STORE.reset()
    yield s
    stmtsummary.STORE.reset()


# ---- site folding / role classification -----------------------------------

def test_fold_site_shape_and_depth():
    frames = _frames("base:10", "mid:20", "leaf:30")
    assert fold_site(frames) == "base.py:10;mid.py:20;leaf.py:30"
    # the cap keeps the LEAF-most frames (where the bytes were born)
    deep = _frames(*[f"f{i}:{i}" for i in range(20)])
    folded = fold_site(deep, max_depth=3)
    assert folded == "f17.py:17;f18.py:18;f19.py:19"
    assert fold_site(()) == ""


def test_classify_site_leaf_most_live_frame_wins():
    frames = _frames("base:10", "leaf:30")
    rolemap = {("base.py", 10): "main", ("leaf.py", 30): "conn"}
    assert classify_site(frames, rolemap) == "conn"
    # only the root is live: its role still claims the site
    assert classify_site(frames, {("base.py", 10): "main"}) == "main"
    # allocation path no longer on any stack
    assert classify_site(frames, {}) == "other"


def test_live_frame_roles_from_thread_names():
    ev = threading.Event()
    got = {}

    def parked():
        got["frame"] = sys._getframe()
        ev.wait(5)

    t = threading.Thread(target=parked, name="conn-test", daemon=True)
    t.start()
    in_wait(t)
    try:
        key = (os.path.basename(got["frame"].f_code.co_filename),
               got["frame"].f_lineno)
        rolemap = memprof._live_frame_roles()
        # the parked thread's call site carries its thread-name role
        assert rolemap.get(key) == "conn"
        # skip_idents: the sampler excludes its own thread this way
        assert key not in memprof._live_frame_roles(
            skip_idents=(t.ident,))
    finally:
        ev.set()
        join(t)


# ---- window rotation / retention / eviction ------------------------------

def test_window_rotation_and_history_bound():
    p = HeapProfiler(window_s=10, history=2, max_sites=64)
    stats = _site_stats(1)
    for now in (1000.0, 1003.0, 1006.0):
        p.sample_once(0.1, now=now, stats=stats, frames={},
                      traced_kb=0.0)
    assert p.stats_snapshot()["windows"] == 1
    p.sample_once(0.1, now=1011.0, stats=stats, frames={},
                  traced_kb=0.0)
    assert p.stats_snapshot()["windows"] == 2  # rotated + current
    # two more rotations: history stays bounded at 2 (+ current)
    p.sample_once(0.1, now=1022.0, stats=stats, frames={},
                  traced_kb=0.0)
    p.sample_once(0.1, now=1033.0, stats=stats, frames={},
                  traced_kb=0.0)
    assert p.stats_snapshot()["windows"] == 3


def test_read_side_stale_rotation():
    p = HeapProfiler(window_s=10, history=4, max_sites=64)
    p.sample_once(0.1, now=1000.0, stats=_site_stats(1), frames={},
                  traced_kb=0.0)
    # a read long after the window expired must not present it as
    # current (the stmtsummary/conprof read-side rotation contract)
    text = p.collapsed(now=2000.0)
    assert text  # rotated into history, still served
    assert p.stats_snapshot()["windows"] == 1
    assert p.window_begin == 2000.0


def test_max_sites_evicts_into_tombstone():
    p = HeapProfiler(window_s=1000, history=2, max_sites=4)
    now = 1000.0
    for st in _site_stats(8, size=1024):
        p.sample_once(0.1, now=now, stats=[st], frames={},
                      traced_kb=0.0)
        now += 0.5
    snap = p.stats_snapshot()
    assert snap["site_entries"] <= 4 + 1  # cap + the tombstone row
    assert snap["evicted"] >= 4
    lines = p.collapsed(now=now).splitlines()
    tomb = [ln for ln in lines if memprof.EVICTED_SITE in ln]
    assert len(tomb) == 1
    # the served tombstone KB is the largest single evicted site (the
    # max-merge discipline — a bucket of distinct sites must not read
    # as one big allocation)
    assert int(tomb[0].rsplit(" ", 1)[1]) == 1


def test_max_sites_at_tombstone_floor_never_spins():
    # with max_sites at/below the tombstone count the eviction loop
    # must report no-progress and stop, not spin under the lock (the
    # conprof tombstone-floor discipline)
    p = HeapProfiler(window_s=1000, history=2, max_sites=1)
    now = 1000.0
    for st in _site_stats(4):
        p.sample_once(0.1, now=now, stats=[st], frames={},
                      traced_kb=0.0)
        now += 0.5
    assert p.stats_snapshot()["sites"] == 4


# ---- collapsed format round trip -----------------------------------------

def test_collapsed_round_trip_through_parser():
    p = HeapProfiler(window_s=1000, history=4, max_sites=64)
    for _ in range(3):
        p.sample_once(0.01, now=1000.0, stats=_site_stats(3, size=2048),
                      frames={}, traced_kb=0.0)
    text = p.collapsed(now=1001.0)
    parsed = conprof.parse_collapsed(text)
    assert len(parsed) == 3, text
    for site, kb in parsed.items():
        role = site.split(";", 1)[0]
        assert role in conprof.ROLES
        assert kb == 2  # 2048 bytes -> live KB, not a sample count
    # horizon bounding: generous window keeps it, tiny one drops it
    assert conprof.parse_collapsed(p.collapsed(window_s=10_000,
                                               now=1001.0))
    assert p.collapsed(window_s=1e-9, now=1001.0) == ""


def test_collapsed_max_merges_across_windows():
    # a persistent allocation must not double across rotations: the
    # served KB is the MAX across retained windows, not the sum
    p = HeapProfiler(window_s=10, history=4, max_sites=64)
    st = _site_stats(1, size=5 * 1024)
    p.sample_once(0.1, now=1000.0, stats=st, frames={}, traced_kb=0.0)
    st2 = _site_stats(1, size=3 * 1024)
    p.sample_once(0.1, now=1011.0, stats=st2, frames={}, traced_kb=0.0)
    assert p.stats_snapshot()["windows"] == 2
    parsed = conprof.parse_collapsed(p.collapsed(now=1012.0))
    assert list(parsed.values()) == [5]


def test_debug_heap_endpoint_round_trip():
    from tinysql_tpu.server.http_status import StatusServer
    memprof.reset()
    try:
        memprof.PROF.sample_once(0.1, now=time.time(),
                                 stats=_site_stats(3), frames={},
                                 traced_kb=0.0)
        st = StatusServer(None, port=0)
        port = st.start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/heap", timeout=5
            ).read().decode()
            parsed = conprof.parse_collapsed(body)
            assert len(parsed) == 3
            # ?window=N plumbs through (tiny horizon -> empty)
            body2 = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/heap?window=0.0001",
                timeout=5).read().decode()
            assert body2.strip() == ""
        finally:
            st.close()
    finally:
        memprof.reset()


# ---- failpoint / error accounting ----------------------------------------

def test_sample_error_fires_before_tick_counting():
    p = HeapProfiler()
    with fail.armed("memprofSampleError",
                    exc=RuntimeError("injected"), times=1):
        with pytest.raises(RuntimeError):
            p.sample_once(0.1, now=1000.0, stats=[], frames={},
                          traced_kb=0.0)
    # the failed tick never counted; note_error is the sampler's ledger
    assert p.stats_snapshot()["ticks"] == 0
    p.note_error()
    assert p.stats_snapshot()["errors"] == 1


# ---- statement attribution ------------------------------------------------

def test_attribution_splits_delta_and_reaches_statements_summary(
        session):
    prof = HeapProfiler()
    done = threading.Event()
    seen = {}
    sql = "select count(*), sum(b) from t where b < 5"

    def run_stmt():
        with fail.armed("execSlowNext", sleep=0.1):
            session.query(sql)
        seen["qobs"] = session.last_query_stats
        done.set()

    t = threading.Thread(target=run_stmt, daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while not HeapProfiler._statement_scopes() \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    assert HeapProfiler._statement_scopes(), "statement never registered"
    # two injected ticks while the statement provably executes: the
    # first anchors the traced baseline, the second carries +64 KB
    prof.sample_once(0.1, now=1000.0, stats=[], frames={},
                     traced_kb=100.0, hbm_bytes=0.0)
    prof.sample_once(0.1, now=1001.0, stats=[], frames={},
                     traced_kb=164.0, hbm_bytes=2048.0)
    assert done.wait(30)
    join(t)
    assert prof.stats_snapshot()["attributed"] >= 1
    dev = seen["qobs"].device_totals()
    # THE invariant: the statement's claimed heap can never exceed the
    # process's measured growth (sole executor -> the full delta)
    assert dev.get("heap_kb") == pytest.approx(64.0)
    assert dev.get("heap_peak_kb") == pytest.approx(164.0)
    assert dev.get("hbm_bytes") == pytest.approx(2048.0)
    # digest-joined over SQL: the summary columns carry the same truth
    digest, _ = stmtsummary.normalize(sql)
    rows = session.query(
        "select sum_heap_alloc_kb, max_heap_kb "
        "from information_schema.statements_summary "
        f"where digest = '{digest}'").rows
    assert len(rows) == 1, rows
    assert float(rows[0][0]) == pytest.approx(64.0)
    assert float(rows[0][1]) == pytest.approx(164.0)


def test_negative_delta_and_idle_process_attribute_nothing(session):
    prof = HeapProfiler()
    # no statement executing: a positive delta has no one to claim it
    prof.sample_once(0.1, now=1000.0, stats=[], frames={},
                     traced_kb=100.0, hbm_bytes=0.0)
    prof.sample_once(0.1, now=1001.0, stats=[], frames={},
                     traced_kb=200.0, hbm_bytes=0.0)
    assert prof.stats_snapshot()["attributed"] == 0
    # a shrinking heap (negative delta) never attributes either
    done = threading.Event()
    seen = {}

    def run_stmt():
        with fail.armed("execSlowNext", sleep=0.1):
            session.query("select count(*) from t where b < 6")
        seen["qobs"] = session.last_query_stats
        done.set()

    t = threading.Thread(target=run_stmt, daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while not HeapProfiler._statement_scopes() \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    prof.sample_once(0.1, now=1002.0, stats=[], frames={},
                     traced_kb=150.0, hbm_bytes=0.0)
    assert done.wait(30)
    join(t)
    assert prof.stats_snapshot()["attributed"] == 0
    assert seen["qobs"].device_totals().get("heap_kb", 0.0) == 0.0


# ---- sampler lifecycle / rate 0 ------------------------------------------

def test_sampler_lifecycle_restart_and_rate0_stops_tracing():
    pre_tracing = tracemalloc.is_tracing()
    storage = new_mock_storage()
    storage._global_vars = {"tidb_memprof_rate": 50}
    prof = HeapProfiler()
    sampler = MemprofSampler(storage, profiler=prof)
    sampler.start()
    sampler.start()  # idempotent: no second thread
    try:
        deadline = time.monotonic() + 20
        while prof.stats_snapshot()["ticks"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert prof.stats_snapshot()["ticks"] >= 2
        assert tracemalloc.is_tracing()
        # rate 0 pauses sampling AND stops the tracemalloc tax (off
        # must mean OFF — tracing costs every allocation in the
        # process); the traced baseline resets with it
        storage._global_vars["tidb_memprof_rate"] = 0
        deadline = time.monotonic() + 10
        while (prof._last_traced_kb is not None
               or (not pre_tracing and tracemalloc.is_tracing())) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        if not pre_tracing:
            assert not tracemalloc.is_tracing()
        assert prof._last_traced_kb is None
        t0 = prof.stats_snapshot()["ticks"]
        time.sleep(0.4)
        assert prof.stats_snapshot()["ticks"] == t0
        # re-enable: resumes on the live sysvar
        storage._global_vars["tidb_memprof_rate"] = 50
        deadline = time.monotonic() + 20
        while prof.stats_snapshot()["ticks"] <= t0 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert prof.stats_snapshot()["ticks"] > t0
    finally:
        sampler.close()
    if not pre_tracing:
        assert not tracemalloc.is_tracing()
    # restartable after close (the tsring Sampler contract)
    t1 = prof.stats_snapshot()["ticks"]
    sampler.start()
    try:
        deadline = time.monotonic() + 20
        while prof.stats_snapshot()["ticks"] <= t1 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert prof.stats_snapshot()["ticks"] > t1
    finally:
        sampler.close()


def test_rate0_query_results_byte_identical(session):
    sql = "select b, count(*), sum(a) from t group by b order by b"
    baseline = session.query(sql).rows
    storage = session.storage
    storage._global_vars = {"tidb_memprof_rate": 0}
    prof = HeapProfiler()
    sampler = MemprofSampler(storage, profiler=prof)
    sampler.start()
    try:
        time.sleep(0.3)  # at least one idle slice
        with_sampler = session.query(sql).rows
        assert with_sampler == baseline
        # rate 0 is ONE sysvar read per slice: no ticks, no sites
        assert prof.stats_snapshot()["ticks"] == 0
        assert prof.stats_snapshot()["sites"] == 0
    finally:
        sampler.close()


# ---- overhead backoff -----------------------------------------------------

def test_overhead_backoff_doubles_and_recovers():
    p = HeapProfiler()
    # a tick costing 10% of the period blows the 3% budget: back off
    for _ in range(3):
        p._note_cost(0.01, 0.1)
    assert p.backoff > 1
    high = p.backoff
    # cheap ticks at the stretched period: steps back down (hysteresis)
    for _ in range(200):
        p._note_cost(0.00001, 0.1 * high)
    assert p.backoff < high


def test_live_overhead_frac_definition():
    before = {"self_s": 1.0}
    after = {"self_s": 1.5}
    assert memprof.live_overhead_frac(before, after, 50.0) == 0.01


def test_measure_overhead_probe_is_private():
    memprof.reset()
    pre_tracing = tracemalloc.is_tracing()
    out = memprof.measure_overhead(n=3, rate_hz=10)
    assert out["memprof_overhead_frac"] >= 0
    assert out["tick_wall_s"] >= 0
    # probed a PRIVATE profiler: the live store saw nothing, and the
    # probe's tracemalloc start was undone
    assert memprof.stats_snapshot()["ticks"] == 0
    assert tracemalloc.is_tracing() == pre_tracing


def test_measure_overhead_never_attributes(session):
    # the probe's back-to-back ticks must not fabricate statement heap
    done = threading.Event()
    seen = {}

    def run_stmt():
        with fail.armed("execSlowNext", sleep=0.05):
            session.query("select count(*) from t where b < 6")
        seen["qobs"] = session.last_query_stats
        done.set()

    fail.reset_hits()
    t = threading.Thread(target=run_stmt, daemon=True)
    t.start()
    hit("execSlowNext")  # statement provably mid-flight
    memprof.measure_overhead(n=5, rate_hz=10)
    assert done.wait(30)
    join(t)
    dev = seen["qobs"].device_totals()
    assert dev.get("heap_kb", 0.0) == 0.0, dev
    assert dev.get("heap_peak_kb", 0.0) == 0.0, dev


# ---- per-query probe ------------------------------------------------------

def test_query_mem_probe_measures_and_restores_tracing():
    pre_tracing = tracemalloc.is_tracing()
    probe = QueryMemProbe()
    probe.start()
    ballast = bytearray(2 << 20)  # 2 MiB the probe must see
    out = probe.finish(tracked_peak_bytes=0)
    assert out["peak_heap_kb"] >= 1800, out
    # nothing tracked: all of it is untracked allocation
    assert out["mem_untracked_frac"] == pytest.approx(1.0)
    assert out["peak_hbm_bytes"] >= 0
    assert tracemalloc.is_tracing() == pre_tracing
    del ballast
    # a fully-tracked peak reads ~0 untracked
    probe2 = QueryMemProbe()
    probe2.start()
    ballast2 = bytearray(2 << 20)
    out2 = probe2.finish(
        tracked_peak_bytes=int(out["peak_heap_kb"] * 4096))
    assert out2["mem_untracked_frac"] < 0.5, out2
    del ballast2
    assert tracemalloc.is_tracing() == pre_tracing


# ---- device HBM census / measured row widths ------------------------------

def _device_array(n):
    from tinysql_tpu.ops import kernels
    jax_mod = kernels.jax()
    return jax_mod.numpy.arange(n, dtype=jax_mod.numpy.int32)


def test_hbm_census_attributes_replica_buffers():
    from tinysql_tpu.columnar.store import ColumnarStore, ColumnarTable
    gc.collect()
    base = memprof.hbm_census()  # BEFORE the arrays exist
    arr = _device_array(4096)
    orphan = _device_array(8192)
    store = ColumnarStore()
    tbl = ColumnarTable(991001, 4, 0, 0, {}, np.arange(4,
                                                      dtype=np.int64))
    tbl.cache["dev"] = arr
    store.put(tbl)
    try:
        census = memprof.hbm_census()
        assert census["total_bytes"] >= arr.nbytes + orphan.nbytes
        rep = census["by_category"]["replica"]
        # the replica walker claims the memoized upload...
        assert rep["bytes"] >= base["by_category"]["replica"]["bytes"] \
            + arr.nbytes
        # ...while the orphan (no registered owner) is the leak bucket
        assert census["unattributed_bytes"] \
            >= base["unattributed_bytes"] + orphan.nbytes
        # adopting the orphan into an owner's cache empties its share
        tbl.cache["dev2"] = orphan
        census2 = memprof.hbm_census()
        assert census2["unattributed_bytes"] \
            <= census["unattributed_bytes"] - orphan.nbytes
    finally:
        store.invalidate(991001)
        del store
        gc.collect()


def test_measured_row_bytes_host_device_and_fallback():
    storage = new_mock_storage()
    from tinysql_tpu.columnar import store as colstore
    from tinysql_tpu.columnar.store import ColumnarTable
    n = 10
    v = np.array(["x" * 50] * n)          # <U50: 200 B/row of strings
    m = np.zeros(n, dtype=bool)
    handles = np.arange(n, dtype=np.int64)
    tbl = ColumnarTable(991002, n, 0, 0, {1: (v, m)}, handles)
    colstore.store_of(storage).put(tbl)
    host_width = (v.nbytes + m.nbytes + handles.nbytes) // n
    assert host_width > 17  # wide on purpose: the flip fuel below
    # host-column truth before any device upload
    assert memprof.measured_row_bytes(991002, 17,
                                      storage=storage) == host_width
    # a device-memoized upload takes precedence (the working set that
    # actually occupies HBM)
    arr = _device_array(n * 1024)
    tbl.cache["dev"] = arr
    assert memprof.measured_row_bytes(
        991002, 17, storage=storage) == int(arr.nbytes) // n
    # no replica anywhere: the nominal default survives untouched
    assert memprof.measured_row_bytes(887788, 17,
                                      storage=storage) == 17
    colstore.store_of(storage).invalidate(991002)


def test_measured_row_width_flips_would_spill():
    """Satellite regression (ISSUE 18): the pre-drain spill probe
    priced rows at the nominal 17 bytes; a replica of measurably wide
    rows must flip ``would_spill`` where the nominal price said no."""
    from tinysql_tpu.columnar import store as colstore
    from tinysql_tpu.columnar.store import ColumnarTable
    from tinysql_tpu.executor.tpu_executors import (_NOMINAL_ROW_BYTES,
                                                    _probe_row_bytes)
    from tinysql_tpu.ops import spill
    from tinysql_tpu.utils.memory import MemTracker
    storage = new_mock_storage()
    n = 10
    v = np.array(["y" * 100] * n)         # 400 B/row of string payload
    tbl = ColumnarTable(991003, n, 0, 0,
                        {1: (v, np.zeros(n, dtype=bool))},
                        np.arange(n, dtype=np.int64))
    colstore.store_of(storage).put(tbl)
    try:
        plan = SimpleNamespace(
            table_info=SimpleNamespace(id=991003), children=[])
        measured = _probe_row_bytes(plan, storage)
        assert measured > _NOMINAL_ROW_BYTES
        # a watermark the nominal estimate clears but the measured
        # width does not: 1000 rows at 17 B vs the replica truth
        tracker = MemTracker(quota=1 << 30, spill_watermark=100_000)
        est_rows = 1000
        assert not spill.would_spill(tracker, est_rows,
                                     _NOMINAL_ROW_BYTES)
        assert spill.would_spill(tracker, est_rows, measured)
        # scan-rootless plans (joins, memtables) keep the nominal price
        bare = SimpleNamespace(children=[])
        assert _probe_row_bytes(bare, storage) == _NOMINAL_ROW_BYTES
    finally:
        colstore.store_of(storage).invalidate(991003)


# ---- compiled-program memory catalog --------------------------------------

def test_progcache_note_memory_keeps_largest_footprint(session):
    from tinysql_tpu.ops import progcache
    key = ("memprof-test", "prog-footprint")
    progcache.note_memory(key, 1000.0, 2000.0, 3000.0)
    # a smaller shape of the same program never shrinks the footprint
    progcache.note_memory(key, 500.0, 2500.0, 100.0)
    # all-zero reports (backends without memory_analysis) never clobber
    progcache.note_memory(key, 0.0, 0.0, 0.0)
    rows = session.query(
        "select peak_temp_bytes, peak_arg_bytes, peak_out_bytes "
        "from information_schema.compiled_programs "
        "where domain = 'memprof-test'").rows
    assert rows == [[1000.0, 2500.0, 3000.0]]


# ---- memory_usage / memory_state reconciliation ---------------------------

def test_memory_usage_memtable_over_sql(session):
    rows = session.query(
        "select source, item, bytes from "
        "information_schema.memory_usage").rows
    srcs = {r[0] for r in rows}
    assert srcs >= {"tracked", "measured", "hbm", "recon"}, rows
    by_item = {(r[0], r[1]): int(r[2]) for r in rows}
    traced = by_item[("measured", "traced_heap")]
    tracked = by_item[("tracked", "statements")]
    # the reconciliation row IS the documented identity
    assert by_item[("recon", "untracked")] == max(0, traced - tracked)
    assert by_item[("measured", "rss")] >= 0
    # every registered census category serves a row
    for cat in memprof._CENSUS_WALKERS:
        assert ("hbm", cat) in by_item, by_item
    assert ("hbm", "unattributed") in by_item
    # the memtable lists itself in the catalog
    names = {r[0] for r in session.query(
        "select table_name from information_schema.tables "
        "where table_schema = 'information_schema'").rows}
    assert "memory_usage" in names


def test_memory_state_keys_all_registered_metrics():
    from tinysql_tpu.obs import metrics
    state = memprof.memory_state()
    assert set(state) >= {"tinysql_mem_tracked_bytes",
                          "tinysql_mem_traced_bytes",
                          "tinysql_hbm_live_bytes",
                          "tinysql_mem_untracked_bytes"}
    for key in state:
        assert key in metrics.METRICS, key


# ---- the inspection rules -------------------------------------------------

def _ring_with(points):
    """Synthetic ring: `points` is {metric: [v0, v1, ...]} sampled 10 s
    apart."""
    ring = MetricsRing()
    steps = max(len(vs) for vs in points.values())
    for i in range(steps):
        ring.record({m: vs[min(i, len(vs) - 1)]
                     for m, vs in points.items()}, now=1000.0 + 10 * i)
    return ring


def _findings(ring, rule):
    return [f for f in oinspect.run(ring=ring) if f.rule == rule]


def test_rule_heap_growth():
    mib = 1 << 20
    rise = [i * 16 * mib for i in range(5)]  # +64 MiB, monotone
    f = _findings(_ring_with({"tinysql_mem_traced_bytes": rise}),
                  "heap-growth")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_mem_traced_bytes"
    # a sawtooth of the same amplitude is a cache, not a leak
    saw = [0, 64 * mib, 8 * mib, 72 * mib, 16 * mib]
    assert not _findings(_ring_with({"tinysql_mem_traced_bytes": saw}),
                         "heap-growth")
    # a monotone rise under the floor is noise
    small = [i * mib for i in range(5)]
    assert not _findings(
        _ring_with({"tinysql_mem_traced_bytes": small}), "heap-growth")


def test_rule_hbm_pressure():
    limit = 1 << 30
    ring = _ring_with({"tinysql_hbm_live_bytes": [int(0.90 * limit)],
                       "tinysql_hbm_limit_bytes": [limit]})
    f = _findings(ring, "hbm-pressure")
    assert len(f) == 1 and f[0].severity == "warning"
    ring = _ring_with({"tinysql_hbm_live_bytes": [int(0.96 * limit)],
                       "tinysql_hbm_limit_bytes": [limit]})
    assert _findings(ring, "hbm-pressure")[0].severity == "critical"
    # no exposed capacity (CPU backend): a share of zero is not evidence
    ring = _ring_with({"tinysql_hbm_live_bytes": [limit],
                       "tinysql_hbm_limit_bytes": [0]})
    assert not _findings(ring, "hbm-pressure")


def test_rule_mem_untracked():
    mib = 1 << 20
    band = memprof.UNTRACKED_BAND_BYTES
    # measured growth a full band beyond everything the ledger held
    ring = _ring_with({
        "tinysql_mem_traced_bytes": [0, band + 20 * mib],
        "tinysql_mem_tracked_bytes": [0, 10 * mib]})
    f = _findings(ring, "mem-untracked")
    assert len(f) == 1 and f[0].severity == "warning"
    # divergence inside the documented band: silent
    ring = _ring_with({
        "tinysql_mem_traced_bytes": [0, band - mib],
        "tinysql_mem_tracked_bytes": [0, 0]})
    assert not _findings(ring, "mem-untracked")

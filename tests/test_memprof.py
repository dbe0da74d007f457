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

from _timelimit import hit, in_wait, join, until

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tinysql_tpu import fail
from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.obs import conprof, inspect as oinspect
from tinysql_tpu.obs import memprof, stmtsummary
from tinysql_tpu.obs.memprof import (HeapProfiler, MemprofSampler,
                                     classify_site, fold_site)
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
    # an injected fold while the statement provably executes: a site
    # window that read 164 KB at its end, 64 KB of them its growth
    prof.sample_once(0.1, now=1001.0, stats=[], frames={},
                     traced_kb=164.0, growth_kb=64.0, hbm_bytes=2048.0)
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
    prof.sample_once(0.1, now=1001.0, stats=[], frames={},
                     traced_kb=200.0, growth_kb=100.0, hbm_bytes=0.0)
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
                     traced_kb=150.0, growth_kb=-50.0, hbm_bytes=0.0)
    assert done.wait(30)
    join(t)
    assert prof.stats_snapshot()["attributed"] == 0
    assert seen["qobs"].device_totals().get("heap_kb", 0.0) == 0.0


# ---- sampler lifecycle / rate 0 ------------------------------------------

class _Windows:
    """A ``wait`` for ``HeapProfiler.sample_window`` that returns at
    once, records whether the window was tracing, and runs ``inside``
    in it."""

    def __init__(self, inside=None):
        self.inside = inside
        self.tracing = []

    def __call__(self, seconds):
        self.tracing.append(tracemalloc.is_tracing())
        if self.inside is not None:
            self.inside()
        return False


@pytest.fixture
def not_tracing():
    """The contract below is about a process nobody else traces."""
    assert not tracemalloc.is_tracing(), "another test left tracing on"
    yield
    assert not tracemalloc.is_tracing()


def test_sampler_lifecycle_restart_and_rate0_stops_tracing(not_tracing):
    storage = new_mock_storage()
    storage._global_vars = {"tidb_memprof_rate": 50}
    prof = HeapProfiler()
    sampler = MemprofSampler(storage, profiler=prof)
    seen = []
    real_window = prof.sample_window

    def watched(period_s, wait, **fold):
        # the live sampler's own window, tracing observed from inside
        # its wait and again once it has returned
        def inside(seconds):
            seen.append(("in", tracemalloc.is_tracing()))
            return wait(seconds)
        try:
            return real_window(period_s, inside, **fold)
        finally:
            seen.append(("out", tracemalloc.is_tracing()))

    prof.sample_window = watched
    sampler.start()
    sampler.start()  # idempotent: no second thread
    try:
        until(lambda: prof.stats_snapshot()["site_windows"] >= 2,
               "no site window opened")
        assert prof.stats_snapshot()["ticks"] >= 2
        # rate > 0: tracing is on inside a window and off between two
        assert ("in", True) in seen and ("in", False) not in seen
        assert ("out", False) in seen and ("out", True) not in seen
        # rate 0 pauses sampling and opens no window (off must mean
        # OFF — tracing costs every allocation in the process)
        storage._global_vars["tidb_memprof_rate"] = 0
        time.sleep(0.3)                 # one idle slice at the least
        t0 = prof.stats_snapshot()["ticks"]
        w0 = prof.stats_snapshot()["site_windows"]
        assert not tracemalloc.is_tracing()
        time.sleep(0.4)
        assert prof.stats_snapshot()["ticks"] == t0
        assert prof.stats_snapshot()["site_windows"] == w0
        assert not tracemalloc.is_tracing()
        # re-enable: resumes on the live sysvar
        storage._global_vars["tidb_memprof_rate"] = 50
        until(lambda: prof.stats_snapshot()["site_windows"] > w0,
               "no window after the rate came back")
    finally:
        sampler.close()
    assert not tracemalloc.is_tracing()
    # restartable after close (the tsring Sampler contract)
    w1 = prof.stats_snapshot()["site_windows"]
    sampler.start()
    try:
        until(lambda: prof.stats_snapshot()["site_windows"] > w1,
               "no window after a restart")
    finally:
        sampler.close()
    assert not tracemalloc.is_tracing()


def test_close_inside_a_window_stops_tracing(not_tracing):
    # close() while a window is open: the wait is the stop event's, so
    # the window ends at once, folds nothing and leaves tracing off
    storage = new_mock_storage()
    storage._global_vars = {"tidb_memprof_rate": 50}
    prof = HeapProfiler()
    sampler = MemprofSampler(storage, profiler=prof)
    opened = threading.Event()
    real_window = prof.sample_window

    def long_window(period_s, wait, **fold):
        def inside(seconds):
            opened.set()
            return wait(30.0)           # only close() ends this one
        return real_window(period_s, inside, **fold)

    prof.sample_window = long_window
    sampler.start()
    try:
        assert opened.wait(20)
        assert tracemalloc.is_tracing()
    finally:
        sampler.close()
    assert not tracemalloc.is_tracing()
    assert prof.stats_snapshot()["sites"] == 0
    assert prof.stats_snapshot()["site_windows"] == 1


def test_window_leaves_a_foreign_tracing_state_alone():
    # tracing the test started itself (or PYTHONTRACEMALLOC) is read
    # by a window and left on
    pre = tracemalloc.is_tracing()
    if not pre:
        tracemalloc.start(1)
    try:
        prof = HeapProfiler()
        w = _Windows()
        prof.sample_window(1.0, w, frames={})
        assert w.tracing == [True]
        assert tracemalloc.is_tracing()
        assert prof.stats_snapshot()["site_windows"] == 1
    finally:
        if not pre:
            tracemalloc.stop()
    assert tracemalloc.is_tracing() == pre


# ---- site windows: fold, attribution, errors, budget ----------------------

def test_window_folds_sites_allocated_inside_it(not_tracing):
    prof = HeapProfiler()
    before = bytearray(3 << 20)     # older than the window: never seen
    held = []

    def allocate():
        held.append(bytearray(2 << 20))         # lives past the window
        bytearray(1 << 20)                      # dies inside it

    w = _Windows(inside=allocate)
    n = prof.sample_window(1.0, w, frames={})
    assert w.tracing == [True] and not tracemalloc.is_tracing()
    assert n >= 1
    snap = prof.stats_snapshot()
    # one window's reading: what was allocated inside and still lives
    # (2 MiB), the most that lived at once (3 MiB) — not the process's
    assert 2048 <= snap["traced_kb"] < 3072, snap
    assert 3072 <= snap["traced_peak_kb"] < 4096, snap
    assert snap["site_windows"] == 1 and snap["traced_s"] > 0
    assert snap["ticks"] == 1
    sites = conprof.parse_collapsed(prof.collapsed())
    mine = {s: kb for s, kb in sites.items()
            if "test_memprof.py" in s.rsplit(";", 1)[-1]}
    assert max(mine.values()) == 2048, sites
    del before, held


def test_window_attributes_its_growth_with_the_invariant(session,
                                                         not_tracing):
    prof = HeapProfiler()
    done = threading.Event()
    seen = {}

    def run_stmt():
        with fail.armed("execSlowNext", sleep=0.3):
            session.query("select count(*) from t where b < 4")
        seen["qobs"] = session.last_query_stats
        done.set()

    fail.reset_hits()
    t = threading.Thread(target=run_stmt, daemon=True)
    t.start()
    hit("execSlowNext")  # statement provably mid-flight
    held = []
    prof.sample_window(
        1.0, _Windows(inside=lambda: held.append(bytearray(1 << 20))),
        frames={})
    assert done.wait(30)
    join(t)
    snap = prof.stats_snapshot()
    assert snap["attributed"] == 1
    dev = seen["qobs"].device_totals()
    # THE invariant, per window: what the statements claim never
    # exceeds what the window measured (sole executor -> all of it)
    assert 1024 <= dev["heap_kb"] <= snap["traced_kb"] + 1e-6, (dev, snap)
    assert dev["heap_peak_kb"] == pytest.approx(snap["traced_kb"])
    # growth is a window's own: a second window with nothing allocated
    # and nothing executing attributes nothing
    prof.sample_window(1.0, _Windows(), frames={})
    assert prof.stats_snapshot()["attributed"] == 1


def test_error_inside_a_window_still_stops_tracing(not_tracing):
    # an armed memprofSampleError on a live sampler's tick: counted,
    # the thread lives on, and tracing is off afterwards
    storage = new_mock_storage()
    storage._global_vars = {"tidb_memprof_rate": 50}
    prof = HeapProfiler()
    sampler = MemprofSampler(storage, profiler=prof)
    with fail.armed("memprofSampleError", exc=RuntimeError("injected"),
                    times=1):
        sampler.start()
        try:
            until(lambda: prof.stats_snapshot()["errors"] >= 1,
                   "the failpoint never fired")
            until(lambda: prof.stats_snapshot()["ticks"] >= 1,
                   "the sampler died with the error")
        finally:
            sampler.close()
    assert not tracemalloc.is_tracing()
    # whatever is raised while tracing is ON (a torn snapshot, the wait
    # itself) leaves it off too, and the window is still paid for
    prof = HeapProfiler()

    def torn(seconds):
        assert tracemalloc.is_tracing()
        raise MemoryError("torn")

    with pytest.raises(MemoryError):
        prof.sample_window(1.0, torn, frames={})
    assert not tracemalloc.is_tracing()
    snap = prof.stats_snapshot()
    assert snap["site_windows"] == 1 and snap["ticks"] == 0
    assert prof._window_due > 0


class _Clock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


def test_traced_share_stays_within_the_budget(not_tracing):
    # a sampler asked for 50 ticks a second whose windows cost 25 ms
    # each (the wait and a wake-up late by 10 ms), on a clock of its
    # own: however often it ticks, traced seconds / wall seconds stays
    # at or under the budget
    clock = _Clock()
    prof = HeapProfiler(clock=clock)

    def wait(seconds):
        assert tracemalloc.is_tracing()
        clock.now += seconds + 0.010
        return False

    t_first = clock.now
    windows_at = []
    for _ in range(3000):
        period = prof.backoff / 50.0
        clock.now += period
        w0 = prof.stats_snapshot()["site_windows"]
        prof.tick(period, wait, frames={})
        if prof.stats_snapshot()["site_windows"] > w0:
            windows_at.append(clock.now)
    snap = prof.stats_snapshot()
    wall = clock.now - t_first
    assert snap["site_windows"] == len(windows_at) >= 3
    assert snap["ticks"] > 2 * snap["site_windows"]   # most ticks are bare
    # the last window is paid for by the wall after it: leave it out
    paid = snap["traced_s"] - (memprof.WINDOW_S + 0.010)
    assert paid / (windows_at[-1] - t_first) \
        <= memprof.OVERHEAD_BUDGET_FRAC + 1e-9
    assert snap["traced_s"] / wall <= memprof.OVERHEAD_BUDGET_FRAC * 1.05
    # and it is used, not merely kept: over half the budget runs traced
    assert snap["traced_s"] / wall >= 0.5 * memprof.OVERHEAD_BUDGET_FRAC
    # what the profiler costs in full: the traced seconds, the folds
    # beside them, and the snapshots, which are both, once
    charged = snap["traced_s"] + snap["self_s"] - snap["snapshot_s"]
    assert 0 < snap["snapshot_s"] < snap["self_s"]
    assert snap["traced_s"] < charged < snap["traced_s"] + snap["self_s"]


def test_live_sampler_traced_share_within_budget(not_tracing):
    # the same on the real clock: a live sampler asked for 50 ticks a
    # second spends at most the budget traced, window for window
    storage = new_mock_storage()
    storage._global_vars = {"tidb_memprof_rate": 50}
    prof = HeapProfiler()
    sampler = MemprofSampler(storage, profiler=prof)
    opened, traced_after = [], []
    real_window = prof.sample_window

    def watched(period_s, wait, **fold):
        opened.append(time.perf_counter())
        try:
            return real_window(period_s, wait, **fold)
        finally:
            traced_after.append(prof.stats_snapshot()["traced_s"])

    prof.sample_window = watched
    sampler.start()
    try:
        until(lambda: len(traced_after) >= 4, "four site windows",
              timeout=20.0)
    finally:
        sampler.close()
    # every window is followed by its cost / budget of untraced wall
    for k in range(1, len(traced_after)):
        assert traced_after[k - 1] / (opened[k] - opened[0]) \
            <= memprof.OVERHEAD_BUDGET_FRAC * 1.001, (k, opened,
                                                      traced_after)
    assert traced_after[-1] >= len(traced_after) * memprof.WINDOW_S


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


def test_pacing_follows_a_windows_whole_cost(not_tracing):
    # what paces the windows is a window's whole traced wall plus its
    # fold, not the fold alone, and nothing but that: at 10 ticks a
    # second a window of 50 ms is paid for by 1.67 s of wall, so the
    # next opens on the 17th tick after it; once windows cost 11 ms the
    # next is 4 ticks away, at once (the budget needs no hysteresis)
    clock = _Clock()
    prof = HeapProfiler(clock=clock)
    late = [0.040]

    def wait(seconds):
        clock.now += seconds + late[0]
        return False

    def ticks_to_the_next_window():
        w0 = prof.stats_snapshot()["site_windows"]
        for n in range(1, 100):
            clock.now += 0.1
            prof.tick(0.1, wait, frames={})
            if prof.stats_snapshot()["site_windows"] > w0:
                return n

    assert ticks_to_the_next_window() == 1      # nothing to pay for yet
    assert ticks_to_the_next_window() == 17
    assert ticks_to_the_next_window() == 17
    late[0] = 0.001
    assert ticks_to_the_next_window() == 17     # the last dear one's price
    assert ticks_to_the_next_window() == 4
    assert ticks_to_the_next_window() == 4
    snap = prof.stats_snapshot()
    assert snap["site_windows"] == 6
    assert snap["ticks"] == 1 + 17 * 3 + 4 * 2  # the bare ticks count too
    assert snap["traced_s"] == pytest.approx(3 * 0.050 + 3 * 0.011)


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


def test_windows_and_traced_seconds_on_every_surface(not_tracing):
    from tinysql_tpu.obs import metrics, trace
    memprof.reset()
    try:
        before = trace.totals()
        held = []
        memprof.PROF.sample_window(
            1.0, _Windows(inside=lambda: held.append(bytearray(1 << 20))),
            frames={})
        snap = memprof.stats_snapshot()
        assert snap["site_windows"] == 1 and snap["traced_s"] > 0
        assert snap["windows"] == 1        # the aggregation window, as ever
        state = memprof.memory_state()
        assert state["tinysql_memprof_windows_total"] == 1
        assert state["tinysql_memprof_traced_seconds_total"] \
            == pytest.approx(snap["traced_s"])
        # the traced gauges are the last window's reading, tracing off
        assert state["tinysql_mem_traced_bytes"] >= 1 << 20
        assert state["tinysql_mem_traced_peak_bytes"] \
            >= state["tinysql_mem_traced_bytes"]
        text = metrics.render_prometheus()
        assert "tinysql_memprof_windows_total 1" in text
        assert "tinysql_memprof_traced_seconds_total " in text
        for name in ("tinysql_mem_traced_bytes",
                     "tinysql_mem_traced_peak_bytes",
                     "tinysql_memprof_windows_total",
                     "tinysql_memprof_traced_seconds_total"):
            assert "window" in metrics.METRICS[name][1], name
        # the span the benchmark reads: one memprof.window; the
        # sampler's own work is two bg.memprof spans, the snapshot
        # inside the window and the fold after it (the window's sleep
        # is nobody's work, and in no bg. span)
        after = trace.totals()

        def grew(name, key):
            return after[name][key] - before.get(name, {}).get(key, 0)

        assert grew("memprof.window", "count") == 1
        assert grew("memprof.window", "sum_s") \
            == pytest.approx(snap["traced_s"], rel=0.2)
        assert grew("memprof.window", "sum_s") >= 0
        assert grew("bg.memprof", "count") == 2
        assert grew("bg.memprof", "sum_s") \
            == pytest.approx(snap["self_s"], rel=0.5, abs=2e-3)
    finally:
        memprof.reset()


def test_totals_reads_the_collectors_row_last():
    """``obs.trace.totals()`` allocates a row a span name; a collection
    that its own allocations set off, whatever the allocator's phase,
    is in the copy it returns: one explicit collection reads as one."""
    import gc
    from tinysql_tpu.obs import context, trace
    trace.watch_collector()
    for i in range(30):                 # a table of a realistic length
        with context.process_span(f"t32.name{i}"):
            pass
    keep = []
    was = gc.isenabled()
    gc.enable()
    try:
        for phase in range(400, 700, 10):
            gc.collect()
            while gc.get_count()[0] < phase:
                keep.append([])
            n0 = trace.totals().get("gc", {"count": 0})["count"]
            gc.collect()
            assert trace.totals()["gc"]["count"] == n0 + 1, phase
            keep.clear()
    finally:
        if not was:
            gc.disable()


def test_server_at_defaults_traces_only_inside_windows(not_tracing):
    """A real server, every sysvar at its default (`tidb_memprof_rate`
    1): tracing is off except inside the sampler's site windows, every
    surface is still served with sites once a window has run, and rate
    0 still stops everything."""
    from test_server import MiniClient
    from tinysql_tpu.obs import metrics
    from tinysql_tpu.server.http_status import StatusServer
    from tinysql_tpu.server.server import Server
    storage = new_mock_storage()
    boot = Session(storage)
    boot.execute("create database mpd")
    boot.execute("use mpd")
    boot.execute("create table t (a int primary key, b int)")
    boot.execute("insert into t values " + ", ".join(
        f"({i}, {i % 7})" for i in range(300)))
    memprof.reset()
    stmtsummary.STORE.reset()
    srv = Server(storage, port=0)
    srv.start()
    status = StatusServer(srv)
    sport = status.start()
    sql = "select b, count(*), sum(a) from t group by b order by b"
    try:
        c = MiniClient(srv.port, db="mpd")
        tracing = []

        def two_windows():
            c.query(sql)
            tracing.append(tracemalloc.is_tracing())
            return memprof.stats_snapshot()["site_windows"] >= 2

        until(two_windows, "two site windows at the default rate",
              timeout=30.0)
        snap = memprof.stats_snapshot()
        # the client saw tracing on for a sliver of its statements
        assert sum(tracing) <= 0.2 * len(tracing), (sum(tracing),
                                                    len(tracing))
        assert snap["traced_s"] < 0.5 and snap["errors"] == 0
        assert snap["sites"] > 0
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{sport}/debug/heap", timeout=5
        ).read().decode()
        assert conprof.parse_collapsed(body)
        _, rows = c.query("select source, item, bytes from "
                          "information_schema.memory_usage")
        by_item = {(r[0], r[1]): int(r[2]) for r in rows}
        assert 0 < by_item[("measured", "traced_heap")] \
            <= by_item[("measured", "rss")]
        _, rows = c.query(
            "select sum_heap_alloc_kb, max_heap_kb from "
            "information_schema.statements_summary")
        assert rows and all(float(r[0]) >= 0 for r in rows)
        text = metrics.render_prometheus()
        for name in ("tinysql_memprof_ticks_total",
                     "tinysql_memprof_windows_total",
                     "tinysql_memprof_traced_seconds_total"):
            assert name + " " in text, name
        assert memprof.memory_state()["tinysql_mem_traced_bytes"] > 0
        # off is still off: no tick, no window, tracing never on
        c.query("set global tidb_memprof_rate = 0")
        time.sleep(0.6)                 # two idle slices
        off = memprof.stats_snapshot()
        for _ in range(20):
            c.query(sql)
            assert not tracemalloc.is_tracing()
            time.sleep(0.02)
        now = memprof.stats_snapshot()
        assert (now["ticks"], now["site_windows"]) \
            == (off["ticks"], off["site_windows"])
        c.close()
    finally:
        status.close()
        srv.close()
        memprof.reset()
        stmtsummary.STORE.reset()
    assert not tracemalloc.is_tracing()


def _bench_module(*parts):
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_benchmark_reads_the_window_span(not_tracing):
    """``memprof_traced_ms_per_query``: the data file loads, the reader
    and source it names are the accepted ones, the key is one the spans
    source produces once a ``memprof.window`` span has ended (and a
    program without the span leaves the metric out), and the two
    entries in BENCHMARK.json name accepted cells."""
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "memprof_traced_ms_per_query.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter" and spec["sources"] == ["spans"]
    args = spec["args"]
    assert args == {"source": "spans", "key": "memprof.window.ms",
                    "per_statement": True}
    spans = _bench_module("sources", "spans.py")
    counter = _bench_module("readers", "counter.py")
    before = spans.snapshot()
    prof = HeapProfiler()
    prof.sample_window(1.0, time.sleep, frames={})   # the real wait
    after = spans.snapshot()
    assert args["key"] in after
    grown = after[args["key"]] - before.get(args["key"], 0.0)
    assert grown == pytest.approx(
        prof.stats_snapshot()["traced_s"] * 1e3, rel=0.2)
    assert grown >= memprof.WINDOW_S * 1e3
    # the samplers' sum does not hold the window's sleep
    assert after["bg.ms"] - before["bg.ms"] < grown
    run = SimpleNamespace(deltas={"spans": {args["key"]: grown}},
                          answered=[object()] * 4)
    assert counter.read(run, **args) == pytest.approx(grown / 4)
    # the parent has no such span: nothing to read, nothing raised
    run.deltas = {"spans": {"bg.ms": 1.0}}
    assert counter.read(run, **args) is None
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    mine = [m for m in bench["per_layer"]
            if m["name"].startswith("memprof_traced_ms_per_query.")]
    assert [m["name"] for m in mine] == [
        "memprof_traced_ms_per_query.stream",
        "memprof_traced_ms_per_query.serve",
        "memprof_traced_ms_per_query.mesh10",   # PR 33's cell
        "memprof_traced_ms_per_query.joins"]    # PR 35's
    at = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][at:at + 2] == mine[:2]  # PR 32's: adjacent
    assert bench["per_layer"].index(mine[2]) > at + 1  # later ones after
    assert bench["per_layer"].index(mine[3]) \
        > bench["per_layer"].index(mine[2])
    for m, cell, moves in zip(
            mine, ("tpch_sf1.power_stream", "tpch_sf1.q6_dash_16c",
                   "tpch_sf10_mesh4.power_stream",
                   "tpch_sf1_joins.join_stream"),
            ("stream_queries_per_s", "serve_queries_per_s",
             "stream_queries_per_s", "stream_queries_per_s")):
        # (the four-chip joins cell, PR 39, reports under ``.joins``)
        assert m["workloads"][0] == cell and cell in cells
        assert m["workloads"][1:] == (
            ["tpch_sf10_joins_mesh4.join_stream"]
            if m["name"].endswith(".joins") else [])
        assert m["moves"] == moves and cell in e2e[moves]["workloads"]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "ms", "lower", "program_span", "samplers")
        # the cell runs the profiler at its default: its configuration
        # does not turn it off (the four-chip SF=1 one does, and is no
        # cell of this metric)
        config = {c["name"]: c for c in bench["configs"]}[
            cells[cell]["config"]]
        with open(os.path.join(root, config["file"])) as f:
            assert "tidb_memprof_rate" not in json.dumps(
                json.load(f).get("sysvars", {}))


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
    # the rule reads the resident set: it needs no tracing and sees
    # numpy's and XLA's host buffers too
    mib = 1 << 20
    base = 900 * mib                # a process is never 0 bytes resident
    floor = oinspect.HEAP_GROWTH_MIN_BYTES
    rise = [base + i * floor // 2 for i in range(5)]  # 2x floor, monotone
    f = _findings(_ring_with({"tinysql_mem_rss_bytes": rise}),
                  "heap-growth")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_mem_rss_bytes"
    assert f"{2 * floor / mib:.1f} MiB" in f[0].details
    # a sawtooth of the same amplitude is a cache, not a leak
    saw = [base, base + 2 * floor, base + floor // 4,
           base + 2 * floor + floor // 4, base + floor // 2]
    assert not _findings(_ring_with({"tinysql_mem_rss_bytes": saw}),
                         "heap-growth")
    # a monotone rise under the floor is noise
    small = [base + i * mib for i in range(5)]
    assert not _findings(
        _ring_with({"tinysql_mem_rss_bytes": small}), "heap-growth")
    # the traced heap is one site window's reading, not a level: its
    # rise alone is nobody's leak
    assert not _findings(
        _ring_with({"tinysql_mem_traced_bytes": rise}), "heap-growth")
    # a process that is warming up (replicas prepared, programs
    # compiled) grows by gigabytes and is no leak: the rule judges the
    # samples from the window's last program load on
    gib = 1 << 30
    warm = [base, base + gib, base + 2 * gib, base + 3 * gib]
    flat = [warm[-1] + i * mib for i in range(5)]
    compiled = [0.0, 5.0, 9.0, 12.0] + [12.0] * 5
    assert not _findings(
        _ring_with({"tinysql_mem_rss_bytes": warm + flat,
                    "tinysql_program_load_seconds_total": compiled}),
        "heap-growth")
    # and what rises after it is judged as ever
    f = _findings(
        _ring_with({"tinysql_mem_rss_bytes": warm + [
            warm[-1] + i * floor // 2 for i in range(5)],
            "tinysql_program_load_seconds_total": compiled}), "heap-growth")
    assert len(f) == 1 and f[0].first_value == warm[-1]
    assert f"{2 * floor / mib:.1f} MiB" in f[0].details


def test_program_load_seconds_rise_when_a_program_has_loaded():
    """The series the memory rules start from: jax's own trace, lower
    and compile durations, which end when the program is loaded (the
    registry's build wall rises before a jitted function's first call
    has compiled anything)."""
    from tinysql_tpu.obs import metrics, trace, tsring
    from tinysql_tpu.ops import kernels
    jax = kernels.jax()         # registers the duration listener
    name = "tinysql_program_load_seconds_total"
    before = tsring._src_progcache()[name]
    assert before == trace.program_load_s()
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()
    after = tsring._src_progcache()[name]
    assert after > before
    assert name in metrics.METRICS and name in metrics.render_prometheus()


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
    base = 900 * mib
    ring = _ring_with({
        "tinysql_mem_rss_bytes": [base, base + band + 20 * mib],
        "tinysql_mem_tracked_bytes": [0, 10 * mib]})
    f = _findings(ring, "mem-untracked")
    assert len(f) == 1 and f[0].severity == "warning"
    assert f[0].metric == "tinysql_mem_rss_bytes"
    # divergence inside the documented band: silent
    ring = _ring_with({
        "tinysql_mem_rss_bytes": [base, base + band - mib],
        "tinysql_mem_tracked_bytes": [0, 0]})
    assert not _findings(ring, "mem-untracked")
    # growth the ledger held is tracked, whatever its size
    ring = _ring_with({
        "tinysql_mem_rss_bytes": [base, base + 2 * band],
        "tinysql_mem_tracked_bytes": [0, band + mib]})
    assert not _findings(ring, "mem-untracked")
    # first answers (replicas prepared, programs compiled) are no
    # statement's to answer for: the baseline is the window's last
    # program load, and growth after it is judged as ever
    gib = 1 << 30
    compiled = [0.0, 30.0, 55.0, 55.0, 55.0]
    ring = _ring_with({
        "tinysql_mem_rss_bytes": [base, base + gib, base + 3 * gib,
                                  base + 3 * gib + mib,
                                  base + 3 * gib + 2 * mib],
        "tinysql_mem_tracked_bytes": [0, 0, 0, mib, 0],
        "tinysql_program_load_seconds_total": compiled})
    assert not _findings(ring, "mem-untracked")
    ring = _ring_with({
        "tinysql_mem_rss_bytes": [base, base + gib, base + 3 * gib,
                                  base + 3 * gib + mib,
                                  base + 3 * gib + band + 20 * mib],
        "tinysql_mem_tracked_bytes": [0, 0, 0, mib, 0],
        "tinysql_program_load_seconds_total": compiled})
    f = _findings(ring, "mem-untracked")
    assert len(f) == 1 and f[0].first_value == base + 3 * gib

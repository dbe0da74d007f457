"""Continuous host profiler (obs/conprof.py) + TRACE <stmt> (ISSUE 13):
sampler lifecycle, rate-0 byte-identity, window rotation/eviction
bounds, statement CPU attribution with the cpu_ms <= exec wall
invariant, the collapsed-format round trip, overhead backoff, and the
TRACE statement over the wire."""
import os
import sys
import threading
import time
import urllib.request

import pytest

from _timelimit import hit, in_wait, join

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tinysql_tpu import fail
from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.obs import conprof, stmtsummary
from tinysql_tpu.obs.conprof import (ConprofSampler, Profiler, classify,
                                     fold_stack, parse_collapsed)
from tinysql_tpu.session.session import Session


def _frame_farm(k):
    """k distinct one-frame stacks (distinct function names -> distinct
    folds)."""
    ns = {"sys": sys}
    frames = {}
    for i in range(k):
        exec(f"def conprof_fixture_fn_{i}():\n"
             f"    return sys._getframe()", ns)
        frames[10_000 + i] = ns[f"conprof_fixture_fn_{i}"]()
    return frames


@pytest.fixture
def session():
    storage = new_mock_storage()
    s = Session(storage)
    s.execute("create database cp")
    s.execute("use cp")
    s.execute("create table t (a int primary key, b int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i % 7})" for i in range(500)))
    stmtsummary.STORE.reset()
    yield s
    stmtsummary.STORE.reset()


# ---- role classification / folding ---------------------------------------

def test_classify_vocabulary_closed():
    # every prefix maps into ROLES, unknown names land in "other"
    for prefix, role in conprof.ROLE_PREFIXES:
        assert role in conprof.ROLES
        assert classify(prefix + "42") == role
    assert classify("ThreadPoolExecutor-0_0") == "other"
    assert classify("") == "other"


def test_fold_stack_shape_and_idle():
    folded, idle = fold_stack(sys._getframe())
    # root -> leaf, ';'-separated module.function labels; the leaf is
    # THIS function's frame
    assert folded.endswith("test_conprof.test_fold_stack_shape_and_idle")
    assert not idle

    ev = threading.Event()
    got = {}

    def parked():
        got["frame"] = sys._getframe()
        ev.wait(5)

    t = threading.Thread(target=parked, daemon=True)
    t.start()
    in_wait(t)
    # sample the PARKED thread's live frame: leaf is Event.wait ->
    # idle, but the stack still folds (visible in /debug/conprof)
    live = sys._current_frames().get(t.ident)
    try:
        folded, idle = fold_stack(live)
        assert idle, folded
        assert "parked" in folded
    finally:
        ev.set()
        join(t)


# ---- window rotation / retention / eviction ------------------------------

def test_window_rotation_and_history_bound():
    p = Profiler(window_s=10, history=2, max_stacks=64)
    frames = _frame_farm(1)
    # three samples inside one window, then a late one that rotates
    for now in (1000.0, 1003.0, 1006.0):
        p.sample_once(0.1, now=now, frames=frames)
    assert p.stats_snapshot()["windows"] == 1
    p.sample_once(0.1, now=1011.0, frames=frames)
    snap = p.stats_snapshot()
    assert snap["windows"] == 2  # rotated + current
    # the three same-window samples accumulated on ONE aggregate row
    rows = p.rows(now=1012.0)
    assert [r for r in rows if r[3] == 3], rows
    # two more rotations: the history deque stays bounded at 2, so the
    # oldest (3-sample) window ages out — retention is a bound, not an
    # archive
    p.sample_once(0.1, now=1022.0, frames=frames)
    p.sample_once(0.1, now=1033.0, frames=frames)
    snap = p.stats_snapshot()
    assert snap["windows"] == 3  # 2 retained + current (bound hit)
    rows = p.rows(now=1034.0)
    assert len({r[0] for r in rows}) == 3
    assert not [r for r in rows if r[3] == 3], rows


def test_read_side_stale_rotation():
    p = Profiler(window_s=10, history=4, max_stacks=64)
    p.sample_once(0.1, now=1000.0, frames=_frame_farm(1))
    # a read long after the window expired must not present it as
    # current (the stmtsummary read-side rotation contract)
    rows = p.rows(now=2000.0)
    assert rows  # rotated into history, still served
    assert p.stats_snapshot()["windows"] == 1
    assert p.window_begin == 2000.0


def test_max_stacks_evicts_into_tombstone():
    p = Profiler(window_s=1000, history=2, max_stacks=4)
    frames = _frame_farm(8)
    now = 1000.0
    for tid, fr in frames.items():
        p.sample_once(0.1, now=now, frames={tid: fr})
        now += 0.5
    snap = p.stats_snapshot()
    assert snap["stacks"] <= 4 + 1  # cap + the tombstone row
    assert snap["evicted"] >= 4
    rows = p.rows(now=now)
    tomb = [r for r in rows if r[2] == conprof.EVICTED_STACK]
    assert len(tomb) == 1
    # sample totals stay accountable: tombstone absorbed the evictions
    assert sum(r[3] for r in rows) == 8


def test_max_stacks_at_tombstone_floor_never_spins():
    # regression: with max_stacks at/below the tombstone count the
    # eviction loop used to re-check an unchanged length forever,
    # wedging the sampler AND every reader under the held lock
    p = Profiler(window_s=1000, history=2, max_stacks=1)
    frames = _frame_farm(4)
    now = 1000.0
    for tid, fr in frames.items():
        # must return promptly (the old code hung on the 2nd stack)
        p.sample_once(0.1, now=now, frames={tid: fr})
        now += 0.5
    # sample totals stay accountable even at the degenerate cap
    assert sum(r[3] for r in p.rows(now=now)) == 4


# ---- collapsed format round trip -----------------------------------------

def test_collapsed_round_trip_through_parser():
    p = Profiler(window_s=1000, history=4, max_stacks=64)
    frames = _frame_farm(3)
    for _ in range(5):
        p.sample_once(0.01, now=time.time(), frames=frames)
    text = p.collapsed()
    parsed = parse_collapsed(text)
    assert parsed, text
    # every line is `stack count`, counts reconstruct the sample total
    assert sum(parsed.values()) == 15
    for stack in parsed:
        role = stack.split(";", 1)[0]
        assert role in conprof.ROLES
    # window bounding: a horizon before the window keeps it, one after
    # drops it
    assert parse_collapsed(p.collapsed(window_s=10_000))
    assert p.collapsed(window_s=1e-9) == ""


def test_debug_conprof_endpoint_round_trip(session):
    from tinysql_tpu.server.http_status import StatusServer
    conprof.reset()
    try:
        # fold the LIVE process into the global profiler, then read it
        # back through the endpoint exactly as flamegraph.pl would
        for _ in range(3):
            conprof.PROF.sample_once(0.01)
        st = StatusServer(None, port=0)
        port = st.start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/conprof", timeout=5
            ).read().decode()
            parsed = parse_collapsed(body)
            assert parsed
            assert sum(parsed.values()) \
                == conprof.stats_snapshot()["samples"]
            # ?window=N plumbs through (tiny horizon -> empty)
            body2 = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/conprof?window=0.0001",
                timeout=5).read().decode()
            assert body2.strip() == ""
        finally:
            st.close()
    finally:
        conprof.reset()


# ---- sampler lifecycle / rate 0 ------------------------------------------

def test_sampler_lifecycle_restart_and_rate0():
    storage = new_mock_storage()
    storage._global_vars = {"tidb_conprof_rate": 200,
                            "tidb_conprof_window": 60}
    prof = Profiler()
    sampler = ConprofSampler(storage, profiler=prof)
    sampler.start()
    sampler.start()  # idempotent: no second thread
    try:
        deadline = time.monotonic() + 10
        while prof.stats_snapshot()["ticks"] < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert prof.stats_snapshot()["ticks"] >= 3
        # rate 0 pauses sampling without stopping the thread
        storage._global_vars["tidb_conprof_rate"] = 0
        time.sleep(0.3)
        t0 = prof.stats_snapshot()["ticks"]
        time.sleep(0.5)
        assert prof.stats_snapshot()["ticks"] == t0
        # re-enable: resumes on the live sysvar
        storage._global_vars["tidb_conprof_rate"] = 200
        deadline = time.monotonic() + 10
        while prof.stats_snapshot()["ticks"] <= t0 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert prof.stats_snapshot()["ticks"] > t0
    finally:
        sampler.close()
    # restartable after close (the tsring Sampler contract)
    t1 = prof.stats_snapshot()["ticks"]
    sampler.start()
    try:
        deadline = time.monotonic() + 10
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
    storage._global_vars = {"tidb_conprof_rate": 200}
    prof = Profiler()
    sampler = ConprofSampler(storage, profiler=prof)
    sampler.start()
    try:
        with_sampler = session.query(sql).rows
    finally:
        sampler.close()
    assert with_sampler == baseline


# ---- statement attribution ------------------------------------------------

def test_statement_attribution_digest_join_over_sql(session):
    storage = session.storage
    storage._global_vars = {"tidb_conprof_rate": 200}
    prof = Profiler()
    sampler = ConprofSampler(storage, profiler=prof)
    sampler.start()
    sql = "select count(*), sum(b) from t where b < 5"
    try:
        # a deliberately slow statement (armed block-boundary sleeps)
        # so sampler ticks provably land while it executes
        with fail.armed("execSlowNext", sleep=0.05):
            session.query(sql)
    finally:
        sampler.close()
    digest, _ = stmtsummary.normalize(sql)
    rows = session.query(
        "select digest, cpu_samples, sum_cpu_ms, sum_exec_ms "
        "from information_schema.statements_summary "
        f"where digest = '{digest}'").rows
    assert len(rows) == 1, rows
    _, cpu_samples, sum_cpu_ms, sum_exec_ms = rows[0]
    assert int(cpu_samples) > 0
    assert float(sum_cpu_ms) > 0
    # THE invariant: sample-estimated on-thread time can never exceed
    # the statement's own exec wall (each increment is wall-capped)
    assert float(sum_cpu_ms) <= float(sum_exec_ms), rows[0]


def test_attribution_only_on_statement_thread(session):
    # a sample landing on a NON-statement thread attributes nothing:
    # helper threads must not inflate a statement past its wall
    prof = Profiler()
    ev = threading.Event()

    def bystander():
        ev.wait(5)

    t = threading.Thread(target=bystander, daemon=True)
    t.start()
    in_wait(t)
    try:
        frames = sys._current_frames()
        assert t.ident in frames
        prof.sample_once(0.01, frames={t.ident: frames[t.ident]})
        assert prof.stats_snapshot()["attributed"] == 0
    finally:
        ev.set()
        join(t)


# ---- overhead backoff -----------------------------------------------------

def test_overhead_backoff_doubles_and_recovers():
    p = Profiler()
    # a tick costing 10% of the period blows the 3% budget: back off
    for _ in range(3):
        p._note_cost(0.01, 0.1)
    assert p.backoff > 1
    high = p.backoff
    # cheap ticks at the stretched period: steps back down (hysteresis)
    for _ in range(200):
        p._note_cost(0.00001, 0.1 * high)
    assert p.backoff < high


# ---- continuous_profiling over SQL ---------------------------------------

def test_continuous_profiling_memtable_over_sql(session):
    conprof.reset()
    try:
        for _ in range(4):
            conprof.PROF.sample_once(0.01)
        rows = session.query(
            "select role, folded_stack, samples, cpu_ms from "
            "information_schema.continuous_profiling "
            "where samples > 0 order by samples desc").rows
        assert rows
        for role, folded, samples, cpu_ms in rows:
            assert role in conprof.ROLES
            assert ";" in folded or folded == conprof.EVICTED_STACK
            assert int(samples) > 0
        # the memtable lists itself in the catalog
        names = {r[0] for r in session.query(
            "select table_name from information_schema.tables "
            "where table_schema = 'information_schema'").rows}
        assert "continuous_profiling" in names
    finally:
        conprof.reset()


# ---- TRACE <stmt> ---------------------------------------------------------

def test_trace_statement_embedded(session):
    rs = session.query("trace select count(*) from t where b < 3")
    assert rs.columns == ["span", "parent", "start_offset_us",
                         "duration_us", "thread_role"]
    assert rs.rows
    names = [r[0].strip() for r in rs.rows]
    assert "execute" in names
    assert "plan" in names
    # the execute span roots the tree: plan/place parent into it
    by_name = {r[0].strip(): r for r in rs.rows}
    assert by_name["plan"][1] == "execute"
    for r in rs.rows:
        assert r[4] in conprof.ROLES
        assert float(r[3]) >= 0
    # embedded execution records on the main thread
    assert by_name["execute"][4] == "main"


def test_trace_executes_side_effects(session):
    session.query("trace insert into t values (100001, 9)")
    assert session.query(
        "select b from t where a = 100001").rows == [[9]]


def test_trace_format_row_and_errors(session):
    rs = session.query("trace format = 'row' select count(*) from t")
    assert rs.rows
    from tinysql_tpu.parser import ParseError, parse
    with pytest.raises(ParseError):
        parse("trace format = 'json' select 1")
    with pytest.raises(ParseError):
        parse("trace format = row select 1")


def test_trace_over_the_wire():
    from test_server import MiniClient
    from tinysql_tpu.server.server import Server
    storage = new_mock_storage()
    boot = Session(storage)
    boot.execute("create database wt")
    boot.execute("use wt")
    boot.execute("create table t (a int primary key, b int)")
    boot.execute("insert into t values " + ", ".join(
        f"({i}, {i % 5})" for i in range(200)))
    boot.execute("set global tidb_conprof_rate = 0")
    boot.execute("set global tidb_auto_prewarm = 0")
    srv = Server(storage, port=0)
    srv.start()
    try:
        c = MiniClient(srv.port, db="wt")
        cols, rows = c.query(
            "trace select count(*), max(b) from t where b > 1")
        assert cols == ["span", "parent", "start_offset_us",
                        "duration_us", "thread_role"]
        assert rows
        names = [r[0].strip() for r in rows]
        assert "execute" in names and "plan" in names
        # TRACE bypasses the statement pool (control plane): the span
        # chain records on the CONNECTION thread
        roles = {r[4] for r in rows}
        assert roles <= set(conprof.ROLES)
        assert "conn" in roles, rows
        c.close()
    finally:
        srv.close()

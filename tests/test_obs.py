"""Query-lifecycle observability (tinysql_tpu/obs/): per-query counter
scoping under concurrency, accumulator vs high-water-mark semantics,
span nesting across the devpipe producer thread, EXPLAIN ANALYZE,
the slow-query log, the prewarm feedback loop, and the /metrics +
/debug/trace endpoints."""
import json
import os
import threading
import urllib.request

import pytest

from _timelimit import until

from tinysql_tpu.executor.devpipe import BlockPipeline
from tinysql_tpu.obs import context as obs_context
from tinysql_tpu.obs import metrics as obs_metrics
from tinysql_tpu.obs import slowlog as obs_slowlog
from tinysql_tpu.obs.trace import clear_traces, recent_traces
from tinysql_tpu.ops import kernels
from tinysql_tpu.server.http_status import StatusServer
from tinysql_tpu.utils.testkit import TestKit

N_ROWS = 240


def _kit(tpu: bool = False) -> TestKit:
    tk = TestKit()
    tk.must_exec("create database test")
    tk.must_exec("use test")
    tk.must_exec("create table t (a int primary key, b int, c varchar(8))")
    tk.must_exec("insert into t values " + ", ".join(
        f"({i}, {i % 7}, 'x{i % 3}')" for i in range(1, N_ROWS + 1)))
    if tpu:
        tk.must_exec("set @@tidb_use_tpu = 1")
        tk.must_exec("set @@tidb_tpu_min_rows = 0")
    else:
        tk.must_exec("set @@tidb_use_tpu = 0")
    return tk


AGG_SQL = "select b, count(*), sum(a) from t group by b order by b"


# ---- per-query scoping ---------------------------------------------------

def test_per_query_counters_replace_global_delta():
    tk = _kit(tpu=True)
    tk.must_query(AGG_SQL)  # warm programs
    totals = []
    for _ in range(2):
        tk.must_query(AGG_SQL)
        totals.append(tk.session.last_query_stats.device_totals())
    assert totals[0].get("dispatches", 0) > 0
    # warm runs are deterministic: identical per-query counters
    for k in ("dispatches", "d2h_transfers", "d2h_bytes"):
        assert totals[0].get(k, 0) == totals[1].get(k, 0), (k, totals)


def test_interleaved_sessions_report_independent_counters():
    """Two sessions executing CONCURRENTLY (own threads, own storages)
    must each report the same per-query counters as a solo run — the
    global-snapshot/delta corruption the obs scopes exist to fix."""
    kits = [_kit(tpu=True), _kit(tpu=True)]
    for tk in kits:
        tk.must_query(AGG_SQL)  # warm: compiles land in shared caches
        tk.must_query(AGG_SQL)
    solo = [tk.session.last_query_stats.device_totals() for tk in kits]
    assert solo[0].get("dispatches", 0) > 0

    barrier = threading.Barrier(2)
    results = [None, None]
    errors = []

    def run(i):
        try:
            barrier.wait(timeout=10)
            for _ in range(3):
                kits[i].must_query(AGG_SQL)
            results[i] = kits[i].session.last_query_stats.device_totals()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not errors, errors
    for i in range(2):
        for k in ("dispatches", "d2h_transfers", "d2h_bytes"):
            assert results[i].get(k, 0) == solo[i].get(k, 0), \
                (i, k, results[i], solo[i])


def test_accumulator_vs_hwm_semantics():
    qobs = obs_context.QueryObs(sql="synthetic")
    tok = obs_context.activate(qobs)
    try:
        base_blocks = kernels.STATS["pipe_blocks"]
        kernels.stats_add("pipe_blocks", 2)
        kernels.stats_add("pipe_blocks", 3)
        kernels.stats_hwm("pipe_depth_hwm", 3)
        kernels.stats_hwm("pipe_depth_hwm", 2)  # lower: must not win
    finally:
        obs_context.deactivate(tok)
    totals = qobs.device_totals()
    assert totals["pipe_blocks"] == 5          # accumulator: sums
    assert totals["pipe_depth_hwm"] == 3       # high-water mark: max
    assert kernels.STATS["pipe_blocks"] == base_blocks + 5
    # after deactivation increments no longer reach the scope
    kernels.stats_add("pipe_blocks", 7)
    assert qobs.device_totals()["pipe_blocks"] == 5


def test_counters_attribute_to_current_operator():
    qobs = obs_context.QueryObs(sql="synthetic")
    tok = obs_context.activate(qobs)
    try:
        st = qobs.op_stats(object(), "FakeExec")
        op_tok = obs_context.push_op(st)
        kernels.stats_add("dispatches", 1)
        obs_context.pop_op(op_tok)
        kernels.stats_add("dispatches", 1)  # no live operator frame
    finally:
        obs_context.deactivate(tok)
    assert st.device["dispatches"] == 1
    assert qobs.device_totals()["dispatches"] == 2


# ---- span tracing --------------------------------------------------------

def test_span_nesting_within_thread():
    qobs = obs_context.QueryObs(sql="synthetic")
    tok = obs_context.activate(qobs)
    try:
        with obs_context.span("outer") as so:
            with obs_context.span("inner") as si:
                assert si.parent == so.sid
    finally:
        obs_context.deactivate(tok)
    spans = {s["name"]: s for s in qobs.tracer.spans()}
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    assert spans["outer"]["parent"] is None


def test_stage_spans_parent_across_producer_thread():
    """BlockPipeline's producer thread runs in a copy of the creator's
    context: its stage spans must land on the creating query's tracer,
    parented to the span live at pipeline creation, on a DIFFERENT
    thread id."""
    qobs = obs_context.QueryObs(sql="synthetic")
    tok = obs_context.activate(qobs)
    try:
        with obs_context.span("execute") as ex_span:
            pipe = BlockPipeline(lambda i: i * i, range(4), depth=2)
            assert list(pipe) == [0, 1, 4, 9]
    finally:
        obs_context.deactivate(tok)
    spans = qobs.tracer.spans()
    stage = [s for s in spans if s["name"] == "stage"]
    assert len(stage) == 4
    main_tid = threading.get_ident()
    for s in stage:
        assert s["parent"] == ex_span.sid
        assert s["tid"] != main_tid
    # depth=0 (synchronous) stages record on the caller's thread
    qobs2 = obs_context.QueryObs(sql="sync")
    tok = obs_context.activate(qobs2)
    try:
        list(BlockPipeline(lambda i: i, range(2), depth=0))
    finally:
        obs_context.deactivate(tok)
    assert all(s["tid"] == main_tid for s in qobs2.tracer.spans())


def test_chrome_trace_export_shape():
    tk = _kit(tpu=False)
    tk.must_query("select count(*) from t")
    trace = tk.session.last_trace
    assert "traceEvents" in trace
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"parse", "plan", "place", "execute"} <= names
    for e in trace["traceEvents"]:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] > 0


# ---- session timing (the parse amortization fix) ------------------------

def test_batch_parse_reported_once():
    tk = _kit(tpu=False)
    tk.must_exec("select 1 from t limit 1; select 2 from t limit 1")
    info = tk.session.last_query_info
    stmts = info["statements"]
    assert len(stmts) == 2
    # the batch parse wall lands on the FIRST statement only, and the
    # batch total adds it exactly once
    assert stmts[0]["parse_s"] > 0.0
    assert stmts[1]["parse_s"] == 0.0
    expect = info["parse_s"] + sum(x["exec_s"] for x in stmts)
    assert abs(info["total_s"] - expect) < 1e-9
    assert info["parse_s"] == stmts[0]["parse_s"]


# ---- EXPLAIN ANALYZE -----------------------------------------------------

def test_explain_analyze_golden_join_agg():
    tk = _kit(tpu=False)
    rs = tk.session.query(
        "explain analyze select p.b, count(*) from t p join t q "
        "on p.a = q.a group by p.b order by p.b")
    assert rs.columns == ["id", "estRows", "actRows", "task",
                          "execution info", "device info", "operator info"]
    got = [(r[0], r[2]) for r in rs.rows]
    assert got == [
        ("Sort", "7"),
        ("  Projection", "7"),
        ("    HashAgg", "7"),
        ("      MergeJoin", str(N_ROWS)),
        ("        TableReader", str(N_ROWS)),
        ("          TableScan", ""),
        ("        TableReader", str(N_ROWS)),
        ("          TableScan", ""),
    ], rs.rows
    for r in rs.rows:
        if r[0].strip() == "TableScan":
            continue
        assert r[4].startswith("time:"), r
        assert "loops:" in r[4], r


def test_explain_analyze_actrows_matches_result_tpu():
    tk = _kit(tpu=True)
    n = len(tk.must_query(AGG_SQL).data)
    rs = tk.session.query("explain analyze " + AGG_SQL)
    act = rs.rows[0][rs.columns.index("actRows")]
    assert str(act) == str(n), rs.rows
    dev = [r[rs.columns.index("device info")] for r in rs.rows]
    assert any("dispatches:" in d for d in dev), rs.rows
    assert any("cache:" in d for d in dev), rs.rows


def test_plain_explain_unchanged():
    tk = _kit(tpu=False)
    rs = tk.session.query("explain select * from t")
    assert rs.columns == ["id", "estRows", "task", "operator info"]


# ---- slow log ------------------------------------------------------------

def test_slow_log_structured_jsonl(tmp_path, monkeypatch):
    path = tmp_path / "slow.jsonl"
    monkeypatch.setenv("TINYSQL_SLOW_LOG", str(path))
    obs_slowlog.clear()
    tk = _kit(tpu=False)
    tk.must_exec("set @@tidb_slow_log_threshold = 0")  # everything is slow
    tk.must_query(AGG_SQL)
    recs = obs_slowlog.recent()
    assert recs, "no slow-log record captured"
    rec = recs[-1]
    assert rec["sql"].startswith("select b, count(*)")
    assert rec["exec_ms"] >= 0 and rec["total_ms"] >= rec["exec_ms"]
    assert rec["plan_digest"]
    labels = [o["label"] for o in rec["operators"]]
    assert any("HashAgg" in l for l in labels), labels
    # the JSONL file got the same record
    lines = [json.loads(l) for l in path.read_text().splitlines() if l]
    assert any(l["sql"] == rec["sql"] for l in lines)


def test_slow_log_threshold_sysvar_respected():
    obs_slowlog.clear()
    tk = _kit(tpu=False)
    tk.must_exec("set @@tidb_slow_log_threshold = 600000")  # 10 min
    tk.must_query("select count(*) from t")
    assert not any(r["sql"].startswith("select count(*)")
                   for r in obs_slowlog.recent())


# ---- prewarm feedback loop ----------------------------------------------

def test_feedback_file_and_merge(tmp_path, monkeypatch):
    from tinysql_tpu.planner.buckets import merge_feedback
    path = tmp_path / "stats.jsonl"
    monkeypatch.setenv("TINYSQL_STATS_FEEDBACK", str(path))
    tk = _kit(tpu=False)
    tk.must_query(AGG_SQL)
    recs = [json.loads(l) for l in path.read_text().splitlines() if l]
    assert recs and recs[-1]["buckets"], recs
    assert recs[-1]["plan_digest"]
    merged = merge_feedback(str(path))
    # every observed operator cardinality must produce its bucket +
    # growth headroom in the merged prewarm set
    for op in recs[-1]["operators"]:
        if op["act_rows"] > 0:
            nb = kernels.bucket(op["act_rows"])
            assert nb in merged and nb * 2 in merged, (op, merged)
    # merge is a union into an existing set
    prior = {8}
    assert merge_feedback(str(path), prior) is prior
    assert prior > {8}, prior


def test_feedback_captures_fused_input_shape_buckets(tmp_path, monkeypatch):
    """TPU-tier kernels pad inputs to shape buckets that never flow
    through an operator's next() (fused paths consume the replica
    directly) — the feedback record must still carry them, via
    kernels.bucket reporting into the query scope."""
    path = tmp_path / "stats.jsonl"
    monkeypatch.setenv("TINYSQL_STATS_FEEDBACK", str(path))
    tk = _kit(tpu=True)
    tk.must_query(AGG_SQL)
    recs = [json.loads(l) for l in path.read_text().splitlines() if l]
    buckets = set(recs[-1]["buckets"])
    nb = kernels.bucket(N_ROWS)  # the scan's padded input shape
    assert nb in buckets and nb * 2 in buckets, (nb, buckets)


def test_merge_feedback_tolerates_garbage(tmp_path):
    from tinysql_tpu.planner.buckets import merge_feedback
    p = tmp_path / "junk.jsonl"
    p.write_text('not json\n{"buckets": [64, "x"]}\n{"operators": 3}\n')
    assert 64 in merge_feedback(str(p))
    assert merge_feedback(str(tmp_path / "missing.jsonl")) == set()


# ---- endpoints -----------------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.read().decode()


def test_metrics_and_trace_endpoints_roundtrip():
    clear_traces()
    tk = _kit(tpu=True)
    tk.must_query(AGG_SQL)
    st = StatusServer(None, port=0)
    st.start()
    try:
        text = _get(st.port, "/metrics")
        # valid Prometheus text: HELP/TYPE pairs, parsable sample lines
        metrics = {}
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name_labels, _, value = line.rpartition(" ")
            float(value)  # every sample value parses
            metrics[name_labels.split("{")[0]] = float(value)
        for name in ("tinysql_queries_total", "tinysql_dispatches_total",
                     "tinysql_progcache_hits_total"):
            assert name in metrics, sorted(metrics)
        assert metrics["tinysql_dispatches_total"] > 0
        assert text.count("# TYPE") == len(set(
            l.split()[2] for l in text.splitlines()
            if l.startswith("# TYPE")))

        traces = json.loads(_get(st.port, "/debug/trace?n=8"))
        assert traces, "trace ring empty"
        assert any("select b, count(*)" in t["sql"] for t in traces)
        last = traces[-1]
        assert last["spans"]
        assert any(s["name"] == "execute" for s in last["spans"])
        # junk / negative n degrade to "everything", never an odd slice
        assert len(json.loads(_get(st.port, "/debug/trace?n=-2"))) \
            == len(json.loads(_get(st.port, "/debug/trace")))

        slow = json.loads(_get(st.port, "/debug/slowlog"))
        assert isinstance(slow, list)
    finally:
        st.close()


def test_metrics_render_without_server():
    out = obs_metrics.render_prometheus()
    assert "tinysql_dispatches_total" in out
    assert out.endswith("\n")


# ---- bench wiring --------------------------------------------------------

def test_q6_transfer_invariant_from_query_scope():
    """Q6's accounting invariant, sourced from the per-query scope:
    packed D2H pulls never exceed dispatches + 1."""
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.session.session import new_session
    s = new_session()
    tpch.load(s, sf=0.002, data=tpch.generate(0.002))
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_tpu_min_rows = 0")
    for _ in range(2):
        rows = s.query(tpch.QUERIES["Q6"]).rows
    assert len(rows) == 1
    totals = s.last_query_stats.device_totals()
    assert totals.get("dispatches", 0) > 0
    assert totals.get("d2h_transfers", 0) <= totals["dispatches"] + 1


# ---- the span path: process spans, the profiler's clock, totals ----------
# (ISSUE 27)

from tinysql_tpu.obs import trace as obs_trace  # noqa: E402


def _proc_spans(since_id: int = 0) -> list:
    return [s for s in obs_trace.PROCESS.spans() if s["id"] > since_id]


def _last_id() -> int:
    return next(obs_trace._ids)


def test_obs_imports_without_jax():
    """The host-tier tests import obs/ alone: no jax in sys.modules,
    spans still record, the annotation hook is simply unbound."""
    import subprocess
    import sys
    code = ("import sys\n"
            "from tinysql_tpu.obs import context, trace\n"
            "import tinysql_tpu.obs.tsring, tinysql_tpu.obs.conprof\n"
            "import tinysql_tpu.obs.memprof, tinysql_tpu.obs.stmtsummary\n"
            "with context.process_span('a'):\n"
            "    with context.process_span('b'):\n"
            "        pass\n"
            "assert trace._annotation is None\n"
            "assert trace.totals()['a']['count'] == 1\n"
            "assert 'jax' not in sys.modules, 'obs/ pulled jax in'\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr


def test_self_time_is_duration_less_same_thread_children():
    import time
    with obs_context.process_span("t27.outer") as outer:
        with obs_context.process_span("t27.child"):
            time.sleep(0.02)
        # a child on another thread covers the parent's interval too,
        # but the parent's thread was not in it: it stays in self time
        ctx = __import__("contextvars").copy_context()

        def other():
            with obs_context.process_span("t27.elsewhere"):
                time.sleep(0.02)
        th = threading.Thread(target=ctx.run, args=(other,))
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    t = obs_trace.totals()
    child, out = t["t27.child"], t["t27.outer"]
    assert out["self_s"] == pytest.approx(out["sum_s"] - child["sum_s"],
                                          abs=1e-9)
    assert out["self_s"] >= 0.019  # the other thread's 20 ms stayed
    spans = {s["name"]: s for s in _proc_spans(outer.sid - 1)}
    assert spans["t27.child"]["parent"] == outer.sid
    assert spans["t27.elsewhere"]["parent"] == outer.sid  # copied context
    assert spans["t27.elsewhere"]["tid"] != spans["t27.outer"]["tid"]


def test_totals_grow_by_one_per_span():
    def counts():
        return {k: v["count"] for k, v in obs_trace.totals().items()}
    tk = _kit(tpu=True)
    tk.must_query(AGG_SQL)  # warm: no compile span in the measured one
    before = counts()
    tk.must_query(AGG_SQL)
    spans = tk.session.last_query_stats.tracer.spans()
    after = counts()
    grew = {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0) and k != "gc"}
    recorded = {}
    for s in spans:
        recorded[s["name"]] = recorded.get(s["name"], 0) + 1
    # the fan-out after the statement's scope has closed is the
    # process's span, not the statement's
    assert grew.pop("stmt.finish") == 1
    assert grew == recorded, (grew, recorded)
    # a backdated interval counts once too, and self == sum for it
    n = after.get("t27.backdated", 0)
    obs_trace.PROCESS.add_complete("t27.backdated", 1.0, 0.5)
    row = obs_trace.totals()["t27.backdated"]
    assert row["count"] == n + 1 and row["self_s"] == row["sum_s"]


def test_outside_a_statement_plain_spans_still_record_nothing():
    """dispatch / drain / compile from a warm-up or a test must not
    start filling the process ring: only process_span says so."""
    mark = _last_id()

    def recorded():
        # the collector's own process span (a collection of 1 ms or
        # more, on whichever thread it interrupts) is not a plain span
        return [x["name"] for x in _proc_spans(mark) if x["name"] != "gc"]
    with obs_context.span("dispatch", cat="device") as s:
        assert s is None
    assert not recorded()
    with obs_context.process_span("t27.owned") as s:
        assert s is not None
    assert recorded() == ["t27.owned"]


@pytest.fixture(scope="module")
def span_server():
    from tinysql_tpu.kv import new_mock_storage
    from tinysql_tpu.server.server import Server
    from tinysql_tpu.session.session import Session
    storage = new_mock_storage()
    srv = Server(storage, port=0)
    srv.start()
    boot = Session(storage)
    # tracemalloc (on by default in a server) makes the load below
    # several times slower and is not what these tests look at
    boot.execute("set global tidb_memprof_rate = 0")
    boot.execute("create database if not exists sp27")
    boot.execute("use sp27")
    boot.execute("create table t (a int primary key, b int, c double)")
    boot.execute("insert into t values " + ", ".join(
        f"({i}, {i % 41}, {i * 0.5})" for i in range(4000)))
    boot.execute("set global tidb_tpu_min_rows = 16")
    boot.execute("select a, b, c from t")  # hydrate the columnar replica
    yield srv
    srv.close()


def test_process_span_parents_statement_across_pool_handoff(span_server):
    """wire.command -> pool.wait on the connection thread; the
    worker's solo span names that wait and adopts the statement's own
    spans, recorded on its thread, as a round's legs do: its self time
    is what no child of it names."""
    from test_server import MiniClient
    clear_traces()
    mark = _last_id()
    c = MiniClient(span_server.port, db="sp27")
    try:
        c.query("select count(*), sum(c) from t where b < 7")
    finally:
        c.close()
    def proc_spans():
        return {s["name"]: s for s in _proc_spans(mark)
                if s["args"].get("cmd") != 2}  # COM_INIT_DB aside
    # a span is recorded when it ENDS, and the connection thread ends
    # wire.write and wire.command after the client has its answer
    until(lambda: "wire.command" in proc_spans(),
          "the connection thread has ended the command's span")
    proc = proc_spans()
    for name in ("wire.command", "wire.parse", "pool.wait", "wire.write",
                 "solo"):
        assert name in proc, sorted(proc)
    cmd, wait = proc["wire.command"], proc["pool.wait"]
    assert cmd["parent"] is None and cmd["args"]["cmd"] == 3
    for child in ("wire.parse", "pool.wait", "wire.write"):
        assert proc[child]["parent"] == cmd["id"], child
    assert proc["wire.parse"]["args"]["statements"] == 1
    assert proc["wire.write"]["args"]["rows"] == 1
    assert proc["wire.write"]["args"]["bytes"] > 0
    assert wait["args"]["verdict"] in ("admitted", "queued")
    assert proc["solo"]["args"]["wait"] == wait["id"]
    assert proc["solo"]["tid"] != wait["tid"]
    stmt = [t for t in recent_traces() if "count(*), sum(c)" in t["sql"]]
    assert stmt, [t["sql"] for t in recent_traces()]
    execute = [s for s in stmt[-1]["spans"] if s["name"] == "execute"][0]
    assert execute["parent"] == proc["solo"]["id"]
    assert execute["tid"] == proc["solo"]["tid"]
    assert proc["solo"]["dur_us"] >= execute["dur_us"]


def _drive_round27(server, qs):
    from tinysql_tpu.obs import stmtsummary
    from tinysql_tpu.parser import parse
    from tinysql_tpu.server.pool import StatementPool, _Entry
    from tinysql_tpu.session.session import Session
    digest, _ = stmtsummary.normalize(qs[0])
    pool = StatementPool(server.storage)
    entries = []
    for q in qs:
        s = Session(server.storage)
        s.execute("use sp27")
        entries.append(_Entry(s, parse(q)[0], q, digest, True))
    pool._run_batch(entries)
    return entries


def test_round_legs_are_spans_and_lie_within_the_round(span_server):
    """A parked collect leg is no longer lost: round.collect says
    outcome=parked and holds the member's plan; dispatch has its
    round.stack with the device's dispatch under it; replay consumes a
    hit; the member's batch_wait names the round; and the legs' seconds
    lie within the round's."""
    from tinysql_tpu.session.session import Session
    qs = [f"select sum(c), count(*), max(c) from t where b < {3 + i}"
          for i in range(4)]
    warm = Session(span_server.storage)
    warm.execute("use sp27")
    for q in qs:
        warm.query(q)  # warm program + learn the family
    kernels.prewarm_stacked()
    clear_traces()
    mark = _last_id()
    entries = _drive_round27(span_server, qs)
    assert all(e.error is None for e in entries)
    proc = _proc_spans(mark)
    by_name = {}
    for s in proc:
        by_name.setdefault(s["name"], []).append(s)
    (rnd,) = by_name["round"]
    assert rnd["args"]["members"] == 4 and rnd["args"]["parked"] == 4
    assert rnd["args"]["occupancy"] == 4
    assert rnd["args"]["stacked_groups"] == 1
    collects = by_name["round.collect"]
    assert len(collects) == 4
    assert {c["args"]["outcome"] for c in collects} == {"parked"}
    assert all(c["parent"] == rnd["id"] for c in collects)
    # the parked attempts' own spans were adopted, under their leg
    plans = [s for s in by_name["plan"]]
    executes = {s["id"]: s for s in by_name["execute"]}
    assert len(plans) == 4
    for pl in plans:
        assert executes[pl["parent"]]["parent"] in \
            {c["id"] for c in collects}
    (disp,) = by_name["round.dispatch"]
    assert disp["parent"] == rnd["id"]
    assert disp["args"]["groups"] == 1 and disp["args"]["occupancy"] == 4
    (stack,) = by_name["round.stack"]
    assert stack["parent"] == disp["id"]
    assert stack["args"]["n"] == 4 and stack["args"]["bucket"] == 4
    assert stack["args"]["kind"] in ("packed", "tree")
    dev = [s for s in by_name["dispatch"] if s["parent"] == stack["id"]]
    assert len(dev) == 1, by_name["dispatch"]
    replays = by_name["round.replay"]
    assert len(replays) == 4
    assert {r["args"]["consume"] for r in replays} == {"hit"}
    legs = collects + [disp] + replays
    assert sum(s["dur_us"] for s in legs) <= rnd["dur_us"]
    for s in legs:
        assert s["ts_us"] >= rnd["ts_us"] - 1
        assert s["ts_us"] + s["dur_us"] <= rnd["ts_us"] + rnd["dur_us"] + 1
    # from each member's own trace to the round it waited in
    waits = [s for t in recent_traces() for s in t["spans"]
             if s["name"] == "batch_wait"]
    assert len(waits) == 4
    assert {w["args"]["round"] for w in waits} == {rnd["id"]}
    # /debug/trace carries both
    st = StatusServer(None, port=0)
    st.start()
    try:
        payload = json.loads(_get(st.port, "/debug/trace"))
    finally:
        st.close()
    assert payload[0]["sql"] == "(process)"
    assert any(s["name"] == "round" and s["id"] == rnd["id"]
               for s in payload[0]["spans"])


def test_spans_reach_the_profilers_clock(span_server, tmp_path):
    """With a jax.profiler session open the .xplane.pb's host plane
    holds the program's spans with span/parent stats, and the jitted
    module is named after its program family."""
    import jax
    from jax.profiler import ProfileData
    from tinysql_tpu.session.session import Session
    qs = [f"select sum(c), count(*), max(c) from t where b < {11 + i}"
          for i in range(2)]
    warm = Session(span_server.storage)
    warm.execute("use sp27")
    for q in qs:
        warm.query(q)
    kernels.prewarm_stacked()
    assert not obs_trace._annotation.is_enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drive_round27(span_server, qs)
        warm.query(qs[0])
    finally:
        jax.profiler.stop_trace()
    found = [os.path.join(w, f) for w, _d, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    assert found
    profile = ProfileData.from_file(found[-1])
    events = [e for plane in profile.planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]
    ours = {}
    for e in events:
        if e.name.startswith("tinysql/"):
            ours.setdefault(e.name, []).append(dict(e.stats))
    assert "tinysql/round.dispatch" in ours and "tinysql/dispatch" in ours
    disp = ours["tinysql/round.dispatch"][0]
    assert disp["span"] > 0 and disp["parent"] > 0
    assert str(disp["occupancy"]) == "2"  # late arguments arrive too
    stack = ours["tinysql/round.stack"][0]
    assert stack["parent"] == disp["span"]
    assert any(d["parent"] == stack["span"]
               for d in ours["tinysql/dispatch"])
    # the program's family names the jitted function on the host plane
    jitted = {e.name for e in events if e.name.startswith("PjitFunction(")}
    assert any(n.startswith("PjitFunction(scalar") for n in jitted), jitted
    assert "PjitFunction(kernel)" not in jitted
    assert kernels.program_name(("seg", 1, 2)) == "seg"
    assert kernels.program_name(None) == "kernel"
    assert kernels.program_name(("pipe",), "leaf_agg index") \
        == "pipe_leaf_agg_index"


def test_jax_phase_self_times_do_not_double_count():
    """jax reports a phase when it ends; a trace that covered inner
    traces keeps only its own part as self time."""
    import time
    ev = "/jax/core/compile/jaxpr_trace_duration"
    t0 = obs_trace.totals().get("jax.trace", {"count": 0, "sum_s": 0.0,
                                              "self_s": 0.0})
    time.sleep(0.03)
    obs_trace.on_jax_duration(ev, 0.01)           # inner, just ended
    obs_trace.on_jax_duration(ev, 0.025)          # outer, covers it
    obs_trace.on_jax_duration("/jax/unrelated", 5.0)
    t1 = obs_trace.totals()["jax.trace"]
    assert t1["count"] == t0["count"] + 2
    assert t1["sum_s"] - t0["sum_s"] == pytest.approx(0.035)
    assert t1["self_s"] - t0["self_s"] == pytest.approx(0.025)


def test_collector_counts_and_long_collections_leave_a_span():
    import gc
    obs_trace.watch_collector()
    obs_trace.watch_collector()
    assert gc.callbacks.count(obs_trace._on_gc) == 1
    n0 = obs_trace.totals().get("gc", {"count": 0})["count"]
    gc.collect()
    assert obs_trace.totals()["gc"]["count"] == n0 + 1
    mark = _last_id()
    old, obs_trace.GC_SPAN_MIN_S = obs_trace.GC_SPAN_MIN_S, 0.0
    try:
        gc.collect()
    finally:
        obs_trace.GC_SPAN_MIN_S = old
    (span,) = [s for s in _proc_spans(mark) if s["name"] == "gc"]
    assert span["args"]["generation"] == 2


def test_replica_memo_miss_is_a_span_of_the_statement_that_pays(
        span_server):
    from tinysql_tpu.session.session import Session
    s = Session(span_server.storage)
    s.execute("use sp27")
    s.execute("create table m27 (a int primary key, b int, c double)")
    s.execute("insert into m27 values " + ", ".join(
        f"({i}, {i % 13}, {i * 0.25})" for i in range(600)))
    s.execute("select a, b, c from m27")  # hydrate the columnar replica
    q = "select sum(c), count(*) from m27 where b < 5"
    s.query(q)
    spans = s.last_query_stats.tracer.spans()
    memos = [x for x in spans if x["name"] == "replica.memo"]
    assert memos and all(x["args"]["kind"] for x in memos), spans
    assert any("bytes" in x["args"] for x in memos)  # an upload
    s.query(q)  # every memo hits now
    spans = s.last_query_stats.tracer.spans()
    assert not [x for x in spans if x["name"] == "replica.memo"]


def test_chrome_lanes_carry_the_recorded_thread_names():
    events = obs_trace.spans_to_events([
        {"id": 1, "name": "execute", "tid": 10, "thread": "stmt-pool-0"},
        {"id": 2, "name": "stage", "tid": 11, "thread": "devpipe-stage"},
    ])
    lanes = {e["tid"]: e["args"]["name"] for e in events
             if e["name"] == "thread_name"}
    assert lanes == {0: "stmt-pool-0", 1: "devpipe-stage"}

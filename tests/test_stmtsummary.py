"""SQL-queryable observability: the statement-summary store (window
rotation, eviction tombstone accounting, digest normalization,
concurrent-session aggregation under the contextvars scopes), the
information_schema mem-tables (statements_summary / processlist /
slow_query + catalog self-listing), EXPLAIN FOR CONNECTION, the
slow-log join fields, and the /metrics latency histograms."""
import threading
import time

import pytest

from _timelimit import join

from tinysql_tpu.obs import metrics as obs_metrics
from tinysql_tpu.obs import slowlog as obs_slowlog
from tinysql_tpu.obs import stmtsummary
from tinysql_tpu.utils.testkit import TestKit

N_ROWS = 240

INFO = {"parse_s": 0.001, "plan_s": 0.002, "exec_s": 0.003,
        "total_s": 0.006}


@pytest.fixture(autouse=True)
def _fresh_obs_state():
    stmtsummary.STORE.reset()
    obs_slowlog.clear()
    yield
    obs_slowlog.clear()


def _kit() -> TestKit:
    tk = TestKit()
    tk.must_exec("create database test")
    tk.must_exec("use test")
    tk.must_exec("create table t (a int primary key, b int)")
    tk.must_exec("insert into t values " + ", ".join(
        f"({i}, {i % 7})" for i in range(1, N_ROWS + 1)))
    return tk


def _ing(store, digest, now, **kw):
    args = dict(sql=f"select {digest}", sql_digest=digest,
                digest_text=digest, stmt_type="select",
                schema_name="test", plan_digest=kw.pop("plan", "p1"),
                info=INFO, device={}, now=now)
    args.update(kw)
    return store.ingest(**args)


# ---- store semantics -----------------------------------------------------

def test_normalize_literals_and_case():
    d1, t1 = stmtsummary.normalize(
        "SELECT * FROM t WHERE a = 5 AND c = 'x'")
    d2, t2 = stmtsummary.normalize(
        "select *  from t where a=7 and c= 'yyy'")
    assert d1 == d2 and t1 == t2
    assert "?" in t1 and "5" not in t1
    d3, _ = stmtsummary.normalize("select * from t where a = 5 or b = 1")
    assert d3 != d1


def test_window_rotation():
    st = stmtsummary.SummaryStore(refresh_interval_s=10,
                                  max_stmt_count=100)
    base = 1000.0
    _ing(st, "d1", base)
    _ing(st, "d1", base + 5)  # same window: folds
    rows = st.rows(now=base + 5)
    assert len(rows) == 1 and rows[0][6] == 2  # exec_count
    _ing(st, "d1", base + 11)  # past the interval: rotates
    rows = st.rows(now=base + 11)
    assert len(rows) == 1 and rows[0][6] == 1
    assert st.window_begin == base + 11
    # the rotated window is preserved in bounded history
    assert len(st.history) == 1
    begin, hist_rows = st.history[0]
    assert begin == base and hist_rows[0][6] == 2
    # reads rotate stale windows too: an idle gap must not present a
    # long-expired window as current
    assert st.rows(now=base + 30) == []
    assert len(st.history) == 2
    # the rotated windows stay queryable via statements_summary_history
    hist = st.history_rows(now=base + 30)
    assert [r[6] for r in hist] == [2, 1]  # exec_counts, oldest first


def test_eviction_folds_into_tombstone():
    st = stmtsummary.SummaryStore(refresh_interval_s=0, max_stmt_count=2)
    _ing(st, "a", 1.0)
    _ing(st, "b", 2.0)
    _ing(st, "c", 3.0)  # evicts a (least recently seen)
    digests = {r[1] for r in st.rows()}
    assert digests == {"b", "c", stmtsummary.EVICTED_DIGEST}
    tomb = [r for r in st.rows()
            if r[1] == stmtsummary.EVICTED_DIGEST][0]
    assert tomb[6] == 1  # one statement's worth of accounting
    _ing(st, "b", 4.0)   # refresh b's recency
    _ing(st, "d", 5.0)   # evicts c
    tomb = [r for r in st.rows()
            if r[1] == stmtsummary.EVICTED_DIGEST][0]
    assert tomb[6] == 2
    assert {r[1] for r in st.rows()} == \
        {"b", "d", stmtsummary.EVICTED_DIGEST}
    # totals stay accountable: live + tombstone == everything ingested
    assert sum(r[6] for r in st.rows()) == 5


def test_lowered_max_count_shrinks_mid_window():
    """SET-ing tidb_stmt_summary_max_stmt_count below the current entry
    count must enforce the new cap on the next ingest, not pin the old
    high-water until rotation."""
    st = stmtsummary.SummaryStore(refresh_interval_s=0, max_stmt_count=50)
    for i in range(10):
        _ing(st, f"d{i}", float(i))
    assert len(st.rows()) == 10
    _ing(st, "fresh", 100.0, max_stmt_count=3)
    live = [r for r in st.rows()
            if r[1] != stmtsummary.EVICTED_DIGEST]
    assert len(live) <= 3, [r[1] for r in st.rows()]
    # nothing lost: evicted executions live in the tombstone
    assert sum(r[6] for r in st.rows()) == 11


def test_concurrent_sessions_aggregate_one_row():
    """Two sessions executing the same statement shape CONCURRENTLY
    (own threads, own storages, contextvars-scoped QueryObs) must fold
    into ONE summary row whose exec_count is the total run count."""
    sql = "select b, count(*) from t group by b order by b"
    k = 3
    errs = []

    def worker():
        try:
            tk = _kit()
            for _ in range(k):
                tk.must_query(sql)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        join(t)
    assert not errs, errs
    digest, _ = stmtsummary.normalize(sql)
    recs = [r for r in stmtsummary.snapshot() if r["digest"] == digest]
    assert len(recs) == 1, recs
    assert recs[0]["exec_count"] == 2 * k
    assert recs[0]["rows"] == 2 * k * 7  # 7 groups per execution


def test_batch_statements_share_digest_with_standalone():
    """A statement inside a multi-statement batch must digest to the
    SAME key as its standalone form (the per-statement source slice is
    normalized, never the batch display label)."""
    tk = _kit()
    tk.must_exec("select count(*) from t where b = 1; "
                 "select count(*) from t where b = 2")
    tk.must_query("select count(*) from t where b = 3")
    digest, text = stmtsummary.normalize(
        "select count(*) from t where b = 1")
    recs = [r for r in stmtsummary.snapshot() if r["digest"] == digest]
    assert len(recs) == 1, [r["digest_text"] for r in
                            stmtsummary.snapshot()]
    # literals normalized away: all three executions fold into ONE row
    assert recs[0]["exec_count"] == 3
    assert recs[0]["digest_text"] == text and "[stmt" not in text


# ---- SQL surface ---------------------------------------------------------

def test_statements_summary_queryable_from_sql():
    tk = _kit()
    sql = "select b, count(*) from t group by b order by b"
    for _ in range(3):
        tk.must_query(sql)
    rs = tk.session.query(
        "select digest_text, exec_count, sum_exec_ms, dispatches, "
        "d2h_bytes, sum_rows_returned, sample_sql from "
        "information_schema.statements_summary")
    mine = [r for r in rs.rows if r[0].startswith("select b , count")]
    assert len(mine) == 1, rs.rows
    assert mine[0][1] == 3
    assert mine[0][2] > 0  # sum_exec_ms
    assert mine[0][5] == 21  # 3 runs x 7 groups
    assert mine[0][6].startswith("select b, count(*)")


def test_summary_row_carries_sample_plan_and_digest():
    tk = _kit()
    tk.must_query("select count(*) from t")
    rec = [r for r in stmtsummary.snapshot()
           if r["sample_sql"] == "select count(*) from t"]
    assert rec and rec[0]["plan_digest"]
    cols = [c for c, _ in stmtsummary.COLUMNS]
    i_sql, i_plan = cols.index("sample_sql"), cols.index("sample_plan")
    row = [r for r in stmtsummary.rows()
           if r[i_sql] == "select count(*) from t"][0]
    assert "TableReader" in row[i_plan] or "HashAgg" in row[i_plan]


def test_digest_join_slow_query_roundtrip():
    """statements_summary ⋈ slow_query on plan digest after running
    TPC-H Q1/Q3/Q6 — the acceptance join: every slow-logged execution's
    plan digest resolves to exactly one aggregated summary row."""
    from tinysql_tpu.bench import tpch
    tk = TestKit()
    tpch.load(tk.session, sf=0.01, data=tpch.generate(0.01))
    stmtsummary.STORE.reset()
    obs_slowlog.clear()
    tk.must_exec("set @@tidb_slow_log_threshold = 0")
    runs = 2
    for _ in range(runs):
        for q in ("Q1", "Q3", "Q6"):
            tk.must_query(tpch.QUERIES[q])
    rs = tk.session.query(
        "select s.digest, s.exec_count, q.plan_digest "
        "from information_schema.statements_summary s "
        "join information_schema.slow_query q "
        "on s.plan_digest = q.plan_digest "
        "where s.plan_digest <> ''")
    assert len(rs.rows) >= runs * 3, rs.rows
    # each of the three queries: one summary row, exec_count == runs,
    # matched once per slow-log record
    for q in ("Q1", "Q3", "Q6"):
        digest, _ = stmtsummary.normalize(tpch.QUERIES[q])
        matched = [r for r in rs.rows if r[0] == digest]
        assert len(matched) == runs, (q, matched)
        assert all(r[1] == runs for r in matched), (q, matched)


def test_processlist_live_statement_and_explain_for_connection():
    """A concurrently-running statement must appear in processlist with
    its SQL and live MemTracker bytes, and EXPLAIN FOR CONNECTION must
    render its plan from another session while it runs."""
    from tinysql_tpu import fail
    tk = _kit()
    tk.must_exec("set @@tidb_max_chunk_size = 16")  # many drain blocks
    tk2 = TestKit()
    # a STREAMING root (no all-consuming operator): 240 rows in 16-row
    # chunks = 15 root drain blocks, each stretched by the failpoint
    sql = "select a, b from t where b >= 0"
    errs = []

    def run():
        try:
            tk.must_query(sql)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    live = plan = None
    with fail.armed("execSlowNext", sleep=0.1):
        th = threading.Thread(target=run)
        th.start()
        deadline = time.time() + 10
        try:
            while time.time() < deadline:
                rows = tk2.must_query(
                    "select id, command, mem_bytes, info "
                    "from information_schema.processlist").data
                cand = [r for r in rows
                        if r[0] == tk.session.conn_id
                        and r[1] == "Query" and "where b >= 0" in r[3]
                        and r[2] > 0]
                if cand:
                    live = cand[0]
                    plan = tk2.session.query(
                        f"explain for connection "
                        f"{tk.session.conn_id}").rows
                    break
                time.sleep(0.01)
        finally:
            join(th)
    assert not errs, errs
    assert live is not None, "running statement never seen in processlist"
    assert live[2] > 0  # live memory bytes
    assert plan and any("TableReader" in r[0] for r in plan), plan


def test_explain_for_connection_errors():
    tk = _kit()
    e = tk.exec_err("explain for connection 999999")
    assert getattr(e, "mysql_code", 0) == 1094
    # a fresh session has no recorded plan
    tk2 = TestKit()
    e = tk.exec_err(f"explain for connection {tk2.session.conn_id}")
    assert "no recorded plan" in str(e)


def test_show_processlist():
    tk = _kit()
    rs = tk.session.query("show full processlist")
    assert rs.columns[:5] == ["Id", "User", "Host", "db", "Command"]
    me = [r for r in rs.rows if r[0] == tk.session.conn_id]
    assert me and me[0][4] == "Query"
    assert "processlist" in me[0][7]


# ---- slow-log join fields + ring sizing ----------------------------------

def test_slowlog_join_fields(monkeypatch):
    monkeypatch.setenv("TINYSQL_SLOW_LOG_RING", "4")
    obs_slowlog.clear()  # re-reads the ring size
    tk = _kit()
    tk.must_exec("set @@tidb_slow_log_threshold = 0")
    for i in range(6):
        tk.must_query(f"select count(*) from t where b = {i}")
    recs = obs_slowlog.recent()
    assert len(recs) == 4  # ring resized via the env var
    rec = recs[-1]
    assert rec["conn_id"] == tk.session.conn_id
    assert rec["db"] == "test"
    assert rec["success"] is True
    assert rec["sql_digest"]
    # a failing statement is recorded with success=False
    tk.exec_err("select nosuch_col from t")
    recs = obs_slowlog.recent()
    assert recs[-1]["success"] is False


def test_slow_query_memtable_matches_ring():
    tk = _kit()
    tk.must_exec("set @@tidb_slow_log_threshold = 0")
    tk.must_query("select count(*) from t")
    rows = tk.must_query(
        "select conn_id, db, success, query "
        "from information_schema.slow_query").data
    mine = [r for r in rows if r[3] == "select count(*) from t"]
    assert mine and mine[0][0] == tk.session.conn_id
    assert mine[0][1] == "test" and mine[0][2] == 1


# ---- catalog self-listing ------------------------------------------------

def test_infoschema_lists_its_own_memtables():
    tk = _kit()
    schemas = {r[0] for r in tk.must_query(
        "select schema_name from information_schema.schemata").data}
    assert "information_schema" in schemas and "test" in schemas
    tables = {r[0] for r in tk.must_query(
        "select table_name from information_schema.tables "
        "where table_schema = 'information_schema'").data}
    assert {"statements_summary", "processlist", "slow_query",
            "tables", "columns", "schemata",
            "statistics"} <= tables
    cols = {r[0] for r in tk.must_query(
        "select column_name from information_schema.columns "
        "where table_name = 'statements_summary'").data}
    assert {"digest", "plan_digest", "exec_count", "sum_exec_ms",
            "dispatches", "d2h_bytes"} <= cols


# ---- /metrics histograms -------------------------------------------------

def test_metrics_latency_histograms():
    tk = _kit()
    for _ in range(3):
        tk.must_query("select count(*) from t")
    text = obs_metrics.render_prometheus()
    lines = [l for l in text.splitlines()
             if l.startswith("tinysql_stmt_phase_seconds")]
    assert any('phase="exec"' in l and "_bucket" in l for l in lines)
    assert any('le="+Inf"' in l for l in lines)
    counts = [l for l in lines if l.startswith(
        'tinysql_stmt_phase_seconds_count{phase="exec"}')]
    assert counts and int(counts[0].split()[-1]) >= 3
    # bucket counts are cumulative and end at the total count
    exec_buckets = [int(l.split()[-1]) for l in lines
                    if '_bucket{phase="exec"' in l]
    assert exec_buckets == sorted(exec_buckets)
    assert exec_buckets[-1] == int(counts[0].split()[-1])


def test_histogram_skips_unmeasured_phases():
    """Statements with no parse/plan measurement (wire entry, SET/USE
    bookkeeping) must not pile zeros into the lowest bucket — the
    histogram counts measurements, not statements."""
    st = stmtsummary.SummaryStore()
    st.ingest(sql="set @@x = 1", sql_digest="d", digest_text="d",
              stmt_type="set", schema_name="", plan_digest="",
              info={"parse_s": 0.0, "plan_s": 0.0, "exec_s": 0.004,
                    "total_s": 0.004},
              device={}, now=1.0)
    h = st.histogram_snapshot()
    assert h["exec"]["count"] == 1
    assert h["parse"]["count"] == 0 and h["plan"]["count"] == 0

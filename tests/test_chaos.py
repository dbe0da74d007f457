"""Chaos suite: every registered failpoint, statement interruption,
runtime device-loss degradation, and memory quotas.

Three layers:

1. the original live-traffic chaos (store delays, splits racing readers,
   parallel 2PC writers) — mocktikv's chaos surface driven from SQL;
2. the FULL failpoint catalogue matrix: ``CHAOS`` maps every name in
   ``fail.catalogue()`` to a driver that arms it and asserts clean
   retry/degradation or a clean TYPED error — never a hang, never a
   half-committed txn (a coverage test fails if a failpoint is ever
   registered without a driver here);
3. the runtime capabilities: KILL / max_execution_time (MySQL 1317 /
   3024), device-loss CPU re-execution, tidb_mem_quota_query (8175).

``SLEEP_SCALE = 0`` runs every retry ladder without wall-clock sleeps;
``DEFAULT_LOCK_TTL_MS = 1`` lets readers resolve a crashed committer's
leftover locks immediately instead of waiting out the TTL.
"""
import threading
import time

import pytest

from _timelimit import hit, in_queue, join

from tinysql_tpu import fail
from tinysql_tpu.codec import tablecodec
from tinysql_tpu.columnar.store import store_of
from tinysql_tpu.kv.errors import (BackoffExceeded, KVError, RegionError,
                                   UndeterminedError, WalError)
from tinysql_tpu.ops import degrade
from tinysql_tpu.session.session import Session, SessionError, new_session
from tinysql_tpu.utils.interrupt import QueryKilled, QueryTimeout
from tinysql_tpu.utils.memory import MemQuotaExceeded


@pytest.fixture(autouse=True)
def _chaos_env(monkeypatch):
    """Fast ladders + fast lock resolution + clean slate per test."""
    monkeypatch.setattr("tinysql_tpu.kv.backoff.SLEEP_SCALE", 0)
    monkeypatch.setattr("tinysql_tpu.kv.txn.DEFAULT_LOCK_TTL_MS", 1)
    fail.disarm_all()
    fail.reset_hits()
    degrade.reset()
    yield
    fail.disarm_all()
    degrade.reset()


@pytest.fixture
def tk():
    s = new_session()
    s.execute("create database c")
    s.execute("use c")
    s.execute("set @@tidb_use_tpu = 0")
    s.execute("create table t (a int primary key, b int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i % 7})" for i in range(1, 501)))
    info = s.infoschema().table_by_name("c", "t")
    for h in (125, 250, 375):
        s.storage.cluster.split(tablecodec.encode_row_key(info.id, h))
    s.storage.cache.invalidate_all()
    store_of(s.storage).invalidate(info.id)
    return s, info


# =========================================================================
# layer 1: live-traffic chaos (original suite)
# =========================================================================

def test_query_completes_under_store_delay(tk):
    s, _ = tk
    s.storage.cluster.set_delay(1, 2)
    try:
        assert s.query("select count(*), sum(b) from t").rows[0][0] == 500
    finally:
        s.storage.cluster.set_delay(1, 0)


def test_concurrent_readers_survive_splits(tk):
    s, info = tk
    errs = []

    def reader():
        try:
            rs = Session(s.storage, current_db="c")
            rs.execute("set @@tidb_use_tpu = 0")
            for _ in range(10):
                assert rs.query("select count(*) from t").rows == [[500]]
        except Exception as e:  # pragma: no cover - failure capture
            errs.append(e)

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    for h in (60, 180, 300, 440):
        s.storage.cluster.split(tablecodec.encode_row_key(info.id, h))
        time.sleep(0.02)
    for t in threads:
        join(t)
    assert not errs, errs[:1]


def test_parallel_writers_commit_cleanly(tk):
    s, _ = tk
    errs = []

    def writer(base):
        ws = Session(s.storage, current_db="c")
        for i in range(20):
            try:
                ws.execute(f"insert into t values ({base + i}, 0)")
            except Exception as e:  # pragma: no cover - failure capture
                errs.append(e)

    threads = [threading.Thread(target=writer, args=(1000 + k * 100,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        join(t)
    assert not errs, errs[:1]
    assert s.query("select count(*) from t").rows == [[580]]
    assert s.query("admin check table t").rows == [["OK"]]


def test_write_conflict_between_explicit_txns(tk):
    s, _ = tk
    s2 = Session(s.storage, current_db="c")
    s.execute("begin")
    s.execute("delete from t where a = 1")
    s2.execute("begin")
    s2.execute("delete from t where a = 1")
    s.execute("commit")
    with pytest.raises(Exception):
        s2.execute("commit")  # conflicting write must not silently win
    assert s.query("select count(*) from t where a = 1").rows == [[0]]


# =========================================================================
# layer 2: the failpoint-catalogue matrix
# =========================================================================

#: per-failpoint chaos drivers; the coverage test below requires exactly
#: one per registered catalogue name
CHAOS = {}


def chaos(name):
    def deco(fn):
        CHAOS[name] = fn
        return fn
    return deco


def _read_ok(s):
    rows = s.query("select b, count(*), sum(a) from t "
                   "where a <= 500 group by b order by b").rows
    assert len(rows) == 7 and sum(r[1] for r in rows) == 500


@chaos("rpcServerBusy")
def _busy(tk):
    s, _ = tk
    with fail.armed("rpcServerBusy", times=3):
        _read_ok(s)  # BO_REGION_MISS ladder absorbs the busy spikes
    # exhaustion: a permanently-busy store must end in the typed budget
    # error, not a hang
    with fail.armed("rpcServerBusy"):
        with pytest.raises(BackoffExceeded):
            s.query("select count(*) from t").rows


def _settle():
    """Let the 1ms chaos lock TTL lapse in REAL time so the next reader
    resolves a crashed committer's leftovers instead of backing off
    against a still-live lock."""
    time.sleep(0.01)


# the commit/prewrite drivers use DELETE, not INSERT: an insert's autoid
# rebase runs its own meta txn with a RETRY loop that (correctly!)
# absorbs an injected commit fault — which would consume the armed
# failpoint before the user txn ever committed

@chaos("prewriteError")
def _prewrite(tk):
    s, _ = tk
    with fail.armed("prewriteError", exc=IOError("prewrite down"),
                    times=1):
        with pytest.raises(IOError):
            s.execute("delete from t where a = 3")
    # cleanup ran: the row survives, no stuck lock, key still writable
    _settle()
    assert s.query("select count(*) from t where a = 3").rows == [[1]]
    s.execute("delete from t where a = 3")
    s.execute("insert into t values (3, 3)")


@chaos("commitError")
def _commit(tk):
    s, _ = tk
    with fail.armed("commitError", exc=IOError("commit rpc down"),
                    times=1):
        with pytest.raises(UndeterminedError):
            s.execute("delete from t where a = 2")
    # the commit RPC never reached MVCC: the next reader resolves the
    # expired primary lock to a rollback — not half-committed
    _settle()
    assert s.query("select count(*) from t where a = 2").rows == [[1]]


@chaos("commitPrimaryError")
def _commit_primary(tk):
    s, _ = tk
    with fail.armed("commitPrimaryError", exc=IOError("net down"),
                    times=1):
        with pytest.raises(UndeterminedError):
            s.execute("delete from t where a = 5")
    _settle()
    assert s.query("select count(*) from t where a = 5").rows == [[1]]


@chaos("commitSecondaryError")
def _commit_secondary(tk):
    s, _ = tk
    # rows 50 and 400 live in different regions (fixture splits at
    # 125/250/375), so the txn has a real secondary batch
    with fail.armed("commitSecondaryError", exc=IOError("flaky"),
                    times=1):
        s.execute("delete from t where a = 50 or a = 400")
    # durable once the primary committed: the reader resolves the
    # leftover secondary lock THROUGH the primary to commit the delete
    _settle()
    assert s.query("select count(*) from t "
                   "where a = 50 or a = 400").rows == [[0]]
    s.execute("insert into t values (50, 1), (400, 1)")


@chaos("beforeCommit")
def _before_commit(tk):
    s, _ = tk
    # panic between prewrite and commit = the classic Percolator crashed
    # committer; BaseException so 'except Exception' recovery can't hide it
    with fail.armed("beforeCommit", panic=True, times=1):
        with pytest.raises(fail.Panic):
            s.execute("delete from t where a = 7")
    _settle()
    s2 = Session(s.storage, current_db="c")
    s2.execute("set @@tidb_use_tpu = 0")
    # never committed: the row survives, and the key is writable again
    assert s2.query("select count(*) from t where a = 7").rows == [[1]]
    s2.execute("delete from t where a = 7")
    s2.execute("insert into t values (7, 0)")


# the durability failpoints need a DURABLE store (volatile sessions
# never journal) — each driver builds its own tempdir-backed storage
# and does all setup BEFORE arming, so the armed point is consumed by
# exactly the statement under test

def _durable_session():
    import tempfile
    from tinysql_tpu.kv import new_mock_storage
    d = tempfile.mkdtemp(prefix="chaos-wal-")
    st = new_mock_storage(data_dir=d)
    s = Session(st)
    s.execute("create database w")
    s.execute("use w")
    s.execute("set @@tidb_use_tpu = 0")
    s.execute("create table t (a int primary key, b int)")
    s.execute("insert into t values (1, 1), (2, 2), (3, 3)")
    return s, st, d


@chaos("walAppendError")
def _wal_append(tk):
    s, st, d = _durable_session()
    with fail.armed("walAppendError", exc=IOError("disk full"), times=1):
        with pytest.raises(WalError):
            s.execute("delete from t where a = 1")
    # journal-before-apply: the append failed BEFORE the store mutated,
    # so the row survives and the key is immediately writable again
    assert s.query("select count(*) from t where a = 1").rows == [[1]]
    s.execute("delete from t where a = 1")
    assert s.query("select count(*) from t where a = 1").rows == [[0]]
    # and the delete that DID ack is durable across a simulated kill
    st2 = __import__("tinysql_tpu.kv",
                     fromlist=["new_mock_storage"]).new_mock_storage(
        data_dir=d)
    s2 = Session(st2, current_db="w")
    s2.execute("set @@tidb_use_tpu = 0")
    assert s2.query("select count(*) from t where a = 1").rows == [[0]]


@chaos("walFsyncError")
def _wal_fsync(tk):
    from tinysql_tpu.kv import wal as walmod
    s, st, d = _durable_session()
    s.execute("set @@tidb_wal_fsync = 'strict'")
    base = walmod.stats_snapshot()["fsync_errors"]
    with fail.armed("walFsyncError", exc=OSError("EIO"), times=1):
        # the ack-bearing fsync failed: outcome undetermined (bytes may
        # sit in the page cache) — exactly the primary-commit contract
        with pytest.raises((KVError, UndeterminedError)):
            s.execute("delete from t where a = 2")
    assert walmod.stats_snapshot()["fsync_errors"] > base
    # counted, not wedged: the log keeps accepting traffic
    s.execute("set @@tidb_wal_fsync = 'relaxed'")
    s.execute("delete from t where a = 3")
    assert s.query("select count(*) from t where a = 3").rows == [[0]]


@chaos("walTornTail")
def _wal_torn(tk):
    from tinysql_tpu.kv import new_mock_storage
    s, st, d = _durable_session()
    with fail.armed("walTornTail", times=1):
        with pytest.raises(KVError):
            s.execute("delete from t where a = 1")
    # the poisoned live log refuses to let the store diverge ahead of it
    with pytest.raises(KVError):
        s.execute("delete from t where a = 2")
    # recovery truncates the torn tail: pre-tear rows intact, the torn
    # transaction atomically absent, the log writable again
    st2 = new_mock_storage(data_dir=d)
    s2 = Session(st2, current_db="w")
    s2.execute("set @@tidb_use_tpu = 0")
    assert s2.query("select count(*) from t").rows == [[3]]
    s2.execute("delete from t where a = 1")
    assert s2.query("select count(*) from t").rows == [[2]]


@chaos("checkpointError")
def _checkpoint(tk):
    from tinysql_tpu.kv import new_mock_storage
    from tinysql_tpu.kv.errors import CheckpointError
    s, st, d = _durable_session()
    with fail.armed("checkpointError", exc=OSError("nope"), times=1):
        with pytest.raises(CheckpointError):
            st.flush_and_checkpoint()
    # counted, never fatal: the unrotated log remains the recovery
    # source and traffic continues
    s.execute("delete from t where a = 1")
    st2 = new_mock_storage(data_dir=d)
    s2 = Session(st2, current_db="w")
    s2.execute("set @@tidb_use_tpu = 0")
    assert s2.query("select count(*) from t").rows == [[2]]


@chaos("copTaskError")
def _cop(tk):
    s, _ = tk
    with fail.armed("copTaskError", exc=RegionError("injected"), times=2):
        _read_ok(s)  # region errors re-split and retry
    with fail.armed("copTaskError", exc=ValueError("cop boom"), times=1):
        with pytest.raises(ValueError):
            s.query("select b, count(*) from t group by b").rows
    # a persistently failing region exhausts ONE shared budget across
    # re-split recursion: the typed BackoffExceeded, not RecursionError
    with fail.armed("copTaskError", exc=RegionError("flapping")):
        with pytest.raises(BackoffExceeded):
            s.query("select b, count(*) from t group by b").rows
    _read_ok(s)  # pool drained cleanly, next scan fine


@chaos("devpipeStageError")
def _devpipe(tk):
    from tinysql_tpu.executor.devpipe import BlockPipeline
    with fail.armed("devpipeStageError", exc=RuntimeError("stage died"),
                    times=1):
        pipe = BlockPipeline(lambda x: x * 2, [1, 2, 3], depth=2)
        with pytest.raises(RuntimeError, match="stage died"):
            list(pipe)
    # a fresh pipeline over the same items works
    assert list(BlockPipeline(lambda x: x * 2, [1, 2, 3], depth=2)) \
        == [2, 4, 6]


def _tpu_session(s):
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_tpu_min_rows = 1")
    s.execute("set @@tidb_device_cooldown = 0")


@chaos("kernelDispatchError")
def _dispatch(tk):
    s, _ = tk
    want = s.query("select b, sum(a) from t group by b order by b").rows
    _tpu_session(s)
    with fail.armed("kernelDispatchError",
                    exc=degrade.DeviceLost("device dropped")):
        got = s.query("select b, sum(a) from t group by b order by b").rows
    assert got == want  # transparent CPU re-execution, same answer
    assert degrade.snapshot()["degraded_statements_total"] == 1


@chaos("kernelD2HError")
def _d2h(tk):
    s, _ = tk
    want = s.query("select sum(a), count(*) from t").rows
    _tpu_session(s)
    with fail.armed("kernelD2HError",
                    exc=degrade.DeviceLost("link dropped"), times=1):
        got = s.query("select sum(a), count(*) from t").rows
    assert got == want
    assert degrade.snapshot()["device_loss_total"] == 1


@chaos("ddlStepError")
def _ddl_step(tk):
    s, _ = tk
    # KVError steps are retried until the job converges
    with fail.armed("ddlStepError", exc=KVError("step hiccup"), times=2):
        s.execute("create table chaos_ddl (x int primary key)")
    assert s.query("show tables like 'chaos_ddl'").rows
    # non-retryable step failure cancels the job with a typed error...
    with fail.armed("ddlStepError", exc=RuntimeError("broken step"),
                    times=1):
        with pytest.raises(Exception, match="broken step"):
            s.execute("create table chaos_ddl2 (x int primary key)")
    # ...and the queue is not wedged: the same DDL succeeds afterwards
    s.execute("create table chaos_ddl2 (x int primary key)")


@chaos("reorgBatchError")
def _reorg(tk):
    s, _ = tk
    with fail.armed("reorgBatchError", exc=KVError("reorg hiccup"),
                    times=2):
        s.execute("create index idx_chaos_b on t (b)")
    assert s.query("admin check table t").rows == [["OK"]]
    rows = s.query("select count(*) from t where b = 3").rows
    assert rows == [[sum(1 for i in range(1, 501) if i % 7 == 3)]]


@chaos("execSlowNext")
def _slow_next(tk):
    s, _ = tk
    s.execute("set @@tidb_max_chunk_size = 64")
    with fail.armed("execSlowNext", sleep=0.002):
        assert s.query("select count(*) from t").rows == [[500]]


@chaos("prewarmCompileError")
def _prewarm_compile_error(tk):
    """An injected compile failure in one family must be counted, start
    that family's cooldown, and leave the worker serving later cycles —
    never wedge the thread or surface to any query path."""
    from tinysql_tpu.obs import stmtsummary
    from tinysql_tpu.session.prewarm import PrewarmWorker, stats_snapshot
    s, _ = tk
    stmtsummary.STORE.reset()  # rank over THIS test's family only
    s.query("select b, count(*) from t group by b")
    s.storage._global_vars["tidb_auto_prewarm"] = 1
    s.storage._global_vars["tidb_auto_prewarm_cooldown"] = 0
    w = PrewarmWorker(s.storage)
    try:
        errs0 = stats_snapshot()["errors"]
        with fail.armed("prewarmCompileError",
                        exc=RuntimeError("injected compile failure")):
            rep = w.run_cycle()
        assert rep["errors"] >= 1 and not rep["warmed"]
        assert stats_snapshot()["errors"] > errs0
        # disarmed next cycle: the worker is NOT wedged — the same
        # family (cooldown 0) warms cleanly
        rep2 = w.run_cycle()
        assert rep2["errors"] == 0 and rep2["warmed"], rep2
    finally:
        w.close()
        s.storage._global_vars.pop("tidb_auto_prewarm", None)
        s.storage._global_vars.pop("tidb_auto_prewarm_cooldown", None)


@chaos("memprofSampleError")
def _memprof_sample_error(tk):
    """An injected snapshot failure kills exactly one heap-profiler tick:
    the background sampler counts the error and keeps ticking — never
    wedges, never surfaces to a statement."""
    from tinysql_tpu.obs import memprof
    s, _ = tk
    prof = memprof.HeapProfiler()
    sampler = memprof.MemprofSampler(s.storage, profiler=prof)
    s.storage._global_vars["tidb_memprof_rate"] = 50
    try:
        with fail.armed("memprofSampleError",
                        exc=RuntimeError("injected snapshot failure"),
                        times=1):
            sampler.start()
            deadline = time.time() + 10
            while time.time() < deadline and \
                    prof.stats_snapshot()["errors"] < 1:
                time.sleep(0.01)
        st = prof.stats_snapshot()
        assert st["errors"] == 1, st
        # disarmed: the sampler is NOT wedged — clean ticks keep landing
        # (the failed tick itself never counted: the fault fires before
        # the fold, so the store stayed consistent)
        t0 = st["ticks"]
        deadline = time.time() + 10
        while time.time() < deadline and \
                prof.stats_snapshot()["ticks"] <= t0:
            time.sleep(0.01)
        st2 = prof.stats_snapshot()
        assert st2["ticks"] > t0, st2
        assert st2["errors"] == 1, st2
    finally:
        sampler.close()
        s.storage._global_vars.pop("tidb_memprof_rate", None)


def _spill_session(s):
    """Put the chaos session on the device path (the spill routes live
    in the TPU executors) with no row-count gate."""
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_tpu_min_rows = 1")


@chaos("spillForceAll")
def _spill_force_all(tk):
    """Armed, every spill-capable operator runs partitioned: results
    identical to the in-memory path, real spill traffic recorded, zero
    partitions left open afterwards."""
    from tinysql_tpu.ops import spill
    s, _ = tk
    want = s.query("select b, count(*), sum(a) from t "
                   "group by b order by b").rows
    _spill_session(s)
    spill.reset_stats()
    with fail.armed("spillForceAll", value=1):
        got = s.query("select b, count(*), sum(a) from t "
                      "group by b order by b").rows
    assert got == want
    st = spill.stats_snapshot()
    assert st["spill_partitions"] > 0 and st["spill_bytes"] > 0
    assert st["open_slots"] == 0


@chaos("spillPartitionError")
def _spill_partition_error(tk):
    """A failed partition WRITE surfaces as a typed statement error; no
    spill files or resident tracker bytes leak, and the session stays
    healthy once disarmed."""
    from tinysql_tpu.ops import spill
    s, _ = tk
    _spill_session(s)
    with fail.armed("spillForceAll", value=1), \
            fail.armed("spillPartitionError",
                       exc=spill.SpillError("injected write failure"),
                       times=1):
        with pytest.raises(spill.SpillError):
            s.query("select b, count(*), sum(a) from t group by b")
    assert spill.stats_snapshot()["open_slots"] == 0
    _read_ok(s)  # disarmed: the same statement shape runs clean


@chaos("spillReloadError")
def _spill_reload_error(tk):
    """A failed partition RELOAD mid-drain drops every remaining
    partition cleanly — typed error, no leaked slots, session healthy
    after."""
    from tinysql_tpu.ops import spill
    s, _ = tk
    _spill_session(s)
    with fail.armed("spillForceAll", value=1), \
            fail.armed("spillReloadError",
                       exc=spill.SpillError("injected reload failure"),
                       times=1):
        with pytest.raises(spill.SpillError):
            s.query("select b, count(*), sum(a) from t group by b")
    assert spill.stats_snapshot()["open_slots"] == 0
    _read_ok(s)


def _mesh_session(s):
    """Put the chaos session on the partition-parallel path: extra rows
    push the join's estRows over dist.MIN_SHARD_ROWS*2 so the planner
    annotates a real shard count (shard_bucket), and the join key is
    NON-primary on the probe side so the optimizer picks a hash join
    (pk=pk would merge-join) without pre-aggregating the probe away."""
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i % 7})" for i in range(501, 601)))
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_tpu_min_rows = 1")
    s.execute("set @@tidb_mesh_parallel = 1")


#: probe side 600 rows, unique build side — the partitioned
#: build/probe exchange in ops/shardops.unique_join_match_sharded
_MESH_JOIN = "select t1.a from t t1 join t t2 on t1.b = t2.a"


@chaos("shardExchangeStall")
def _shard_exchange(tk):
    """A fault at the shard-exchange entry surfaces TYPED out of the
    sharded attempt (no silent wrong answer, no hang), and the same
    statement runs clean — still sharded — once disarmed."""
    s, _ = tk
    _mesh_session(s)
    base = s.query(_MESH_JOIN).rows
    assert len(base) == 515  # 600 probe rows minus the 85 with b = 0
    with fail.armed("shardExchangeStall", exc=IOError("exchange down"),
                    times=1):
        with pytest.raises(IOError):
            s.query(_MESH_JOIN)
    # the semijoin exchange shares the failpoint
    with fail.armed("shardExchangeStall", exc=IOError("exchange down"),
                    times=1):
        with pytest.raises(IOError):
            s.query("select t1.a from t t1 "
                    "where t1.b in (select a from t t2)")
    assert s.query(_MESH_JOIN).rows == base  # healthy + still sharded


@chaos("admissionQueueFull")
def _admission_queue_full(tk):
    """Forced queue-full verdict: every pooled statement sheds with the
    TYPED 1041 + retry hint over the real wire, control statements keep
    answering, and disarming restores service — nothing wedges."""
    from test_server import MiniClient
    from tinysql_tpu.server.server import Server
    s, _ = tk
    srv = Server(s.storage, port=0)
    srv.start()
    try:
        c = MiniClient(srv.port, db="c")
        with fail.armed("admissionQueueFull"):
            with pytest.raises(RuntimeError) as ei:
                c.query("select count(*) from t")
            assert "1041" in str(ei.value) and "retry" in str(ei.value)
            # the control plane bypasses the pool: still answers while
            # every pooled statement is shed
            assert c.query("show databases")
        # disarmed: the same connection serves again
        assert c.query("select count(*) from t")[1] == [["500"]]
        c.close()
    finally:
        srv.close()


@chaos("admissionDelay")
def _admission_delay(tk):
    """A wedged pool worker (sleep action with the entry claimed): the
    queue builds behind it, a QUEUED statement still answers KILL with
    1317, and an error action surfaces typed — the accept loop and the
    control plane never hang."""
    import threading as _th
    from test_server import MiniClient
    from tinysql_tpu.server.server import Server
    s, _ = tk
    s.storage._global_vars["tidb_stmt_pool_size"] = 1
    srv = Server(s.storage, port=0)
    srv.start()
    try:
        c1 = MiniClient(srv.port, db="c")
        victim = MiniClient(srv.port, db="c")
        victim.query("select 1")
        victim_id = max(srv.conns)
        box = []
        with fail.armed("admissionDelay", sleep=0.8, times=1):
            t1 = _th.Thread(
                target=lambda: box.append(c1.query("select count(*) from t")))
            t1.start()
            hit("admissionDelay")  # the one worker wedged with c1's entry

            def _queued():
                try:
                    box.append(victim.query("select count(*) from t"))
                except RuntimeError as e:
                    box.append(e)
            t2 = _th.Thread(target=_queued)
            t2.start()
            in_queue(srv.pool)
            killer = MiniClient(srv.port)  # accept loop alive while wedged
            killer.query(f"kill query {victim_id}")
            t2.join(10)
            assert not t2.is_alive(), "queued statement unkillable"
            t1.join(10)
        assert any(isinstance(b, RuntimeError) and "1317" in str(b)
                   for b in box), box
        # the wedged entry itself completed once the sleep elapsed
        assert any(not isinstance(b, RuntimeError) for b in box), box
        # error action: typed statement error, worker survives
        c3 = MiniClient(srv.port, db="c")
        with fail.armed("admissionDelay",
                        exc=RuntimeError("injected pool fault"), times=1):
            with pytest.raises(RuntimeError):
                c3.query("select count(*) from t")
        assert c3.query("select count(*) from t")[1] == [["500"]]
        for c in (c1, victim, killer, c3):
            c.close()
    finally:
        srv.close()
        s.storage._global_vars.pop("tidb_stmt_pool_size", None)


def test_chaos_covers_entire_catalogue():
    """A failpoint registered without a chaos driver is a seam nobody
    proved degrades cleanly — fail loudly right here."""
    assert set(CHAOS) == set(fail.catalogue()), (
        set(CHAOS) ^ set(fail.catalogue()))


@pytest.mark.parametrize("name", sorted(fail.catalogue()))
def test_chaos_matrix(name, tk):
    fail.reset_hits()
    CHAOS[name](tk)
    assert fail.hits().get(name, 0) >= 1, \
        f"driver for {name} never actually fired the failpoint"
    # post-fault health: reads AND writes still serve
    s, _ = tk
    _read_ok(s)
    s.execute("insert into t values (20000, 0)")
    assert s.query("select count(*) from t where a = 20000").rows == [[1]]


# =========================================================================
# layer 2c: UPDATE through the 2PC prewrite/commit fault matrix
# =========================================================================
# UPDATE rides the same read-modify-write + 2PC path as INSERT/DELETE
# (session._exec_update -> UpdateExec -> Table.update_record), so each
# 2PC failpoint must degrade it the same way: a clean TYPED error with
# the row either fully old or fully new — never half-assigned, never a
# stuck lock.

@pytest.mark.parametrize("point,want", [
    ("prewriteError", IOError),
    ("commitError", UndeterminedError),
    ("commitPrimaryError", UndeterminedError),
])
def test_update_2pc_fault_leaves_row_unchanged(tk, point, want):
    s, _ = tk
    fail.reset_hits()
    with fail.armed(point, exc=IOError(f"{point} injected"), times=1):
        with pytest.raises(want):
            s.execute("update t set b = 999 where a = 9")
    assert fail.hits().get(point, 0) >= 1
    time.sleep(0.01)  # let the 1ms chaos lock TTL lapse
    # the commit never reached MVCC: old value, and the key is
    # immediately writable again (no stuck lock)
    assert s.query("select b from t where a = 9").rows == [[2]]
    s.execute("update t set b = b + 1 where a = 9")
    assert s.query("select b from t where a = 9").rows == [[3]]


def test_update_commit_secondary_fault_is_durable(tk):
    s, _ = tk
    # rows 50 and 400 live in different regions (fixture splits at
    # 125/250/375): the txn carries a real secondary batch
    with fail.armed("commitSecondaryError", exc=IOError("flaky"),
                    times=1):
        s.execute("update t set b = -1 where a = 50 or a = 400")
    time.sleep(0.01)
    # durable once the primary committed: the next reader resolves the
    # leftover secondary lock THROUGH the primary to the NEW value
    assert s.query("select b from t where a = 50 or a = 400").rows \
        == [[-1], [-1]]


def test_update_before_commit_panic_rolls_back(tk):
    s, _ = tk
    with fail.armed("beforeCommit", panic=True, times=1):
        with pytest.raises(fail.Panic):
            s.execute("update t set b = 123 where a = 7")
    time.sleep(0.01)
    s2 = Session(s.storage, current_db="c")
    s2.execute("set @@tidb_use_tpu = 0")
    # crashed committer: never committed, old value survives, key
    # writable from a fresh session
    assert s2.query("select b from t where a = 7").rows == [[0]]
    s2.execute("update t set b = 1 where a = 7")
    assert s2.query("select b from t where a = 7").rows == [[1]]
    s2.execute("update t set b = 0 where a = 7")


# =========================================================================
# layer 3a: statement interruption (KILL + max_execution_time)
# =========================================================================

def _slow_query(s, sql="select * from t", exc_box=None):
    try:
        s.query(sql)
        exc_box.append(None)
    except Exception as e:
        exc_box.append(e)


def test_kill_query_aborts_running_statement(tk):
    s, _ = tk
    s.execute("set @@tidb_max_chunk_size = 16")
    box = []
    with fail.armed("execSlowNext", sleep=0.02):
        t = threading.Thread(target=_slow_query, args=(s,), kwargs={
            "exc_box": box})
        t.start()
        hit("execSlowNext")
        from tinysql_tpu.utils import interrupt
        assert interrupt.kill(s.conn_id, query_only=True)
        t.join(10)
    assert not t.is_alive()
    assert isinstance(box[0], QueryKilled)
    assert box[0].mysql_code == 1317
    assert s.query("select count(*) from t").rows == [[500]]  # healthy


def test_kill_lands_mid_shard_exchange(tk):
    """KILL while the statement is wedged INSIDE a partitioned shard
    exchange (sleep-armed failpoint at the exchange entry): the kill
    lands at the next drain-block boundary with typed 1317, and the
    session runs the same sharded join clean afterwards."""
    s, _ = tk
    _mesh_session(s)
    base = s.query(_MESH_JOIN).rows
    box = []
    with fail.armed("shardExchangeStall", sleep=0.4):
        t = threading.Thread(target=_slow_query, args=(s, _MESH_JOIN),
                             kwargs={"exc_box": box})
        t.start()
        hit("shardExchangeStall")  # the exchange is holding the statement
        from tinysql_tpu.utils import interrupt
        assert interrupt.kill(s.conn_id, query_only=True)
        t.join(15)
    assert not t.is_alive()
    assert isinstance(box[0], QueryKilled), box[0]
    assert box[0].mysql_code == 1317
    assert s.query(_MESH_JOIN).rows == base  # healthy, still sharded


def test_session_registry_survives_a_collection_inside_its_lock(monkeypatch):
    """A session that dies in a reference cycle leaves the KILL registry
    through a weakref callback, and the collector runs that callback on
    whatever thread it interrupts — also one inside the registry's
    critical section.  The callback took ``_reg_mu``: the thread
    deadlocked against itself, and behind it every later connect, KILL,
    processlist read and conprof tick (what hung tier-1, PERF.md §8)."""
    import gc
    from _timelimit import limited
    from tinysql_tpu.utils import interrupt

    class Cyclic:  # dies only when the collector runs
        def __init__(self):
            self.me = self

    class CollectsWhenRead(dict):  # the collector, inside the lock
        def items(self):
            gc.collect()
            return super().items()

    gc.collect()
    monkeypatch.setattr(interrupt, "_SESSIONS",
                        CollectsWhenRead(interrupt._SESSIONS))
    gc.disable()
    try:
        cid = interrupt.register_session(Cyclic())
        with limited(2.0, "interrupt.sessions()") as fired:
            live = interrupt.sessions()
        assert not fired, "the registry waited for its own lock"
    finally:
        gc.enable()
    assert cid not in {c for c, _ in live}
    keep = Cyclic()
    interrupt.register_session(keep)  # sweeps the dead
    assert cid not in interrupt._SESSIONS


def test_kill_statement_from_second_session(tk):
    s, _ = tk
    s.execute("set @@tidb_max_chunk_size = 16")
    s2 = Session(s.storage, current_db="c")
    box = []
    with fail.armed("execSlowNext", sleep=0.02):
        t = threading.Thread(target=_slow_query, args=(s,), kwargs={
            "exc_box": box})
        t.start()
        hit("execSlowNext")
        s2.execute(f"kill query {s.conn_id}")
        t.join(10)
    assert isinstance(box[0], QueryKilled), box[0]


def test_kill_unknown_thread_id(tk):
    s, _ = tk
    with pytest.raises(SessionError) as ei:
        s.execute("kill query 999999999")
    assert ei.value.mysql_code == 1094


def test_plain_kill_marks_connection_dead(tk):
    s, _ = tk
    s2 = Session(s.storage, current_db="c")
    s.execute(f"kill {s2.conn_id}")
    assert s2.killed  # the server's command loop drops it after this


def test_max_execution_time_expires_long_select(tk):
    s, _ = tk
    s.execute("set @@tidb_max_chunk_size = 16")
    s.execute("set @@max_execution_time = 60")
    with fail.armed("execSlowNext", sleep=0.02):
        with pytest.raises(QueryTimeout) as ei:
            s.query("select * from t")
    assert ei.value.mysql_code == 3024
    s.execute("set @@max_execution_time = 0")
    with fail.armed("execSlowNext", sleep=0.02):
        assert len(s.query("select * from t where a <= 32").rows) == 32


def test_max_execution_time_applies_to_select_only(tk):
    s, _ = tk
    s.execute("set @@max_execution_time = 1")
    time.sleep(0.005)
    # writes and DDL are not under the SELECT deadline (MySQL semantics)
    s.execute("insert into t values (21000, 0)")
    s.execute("delete from t where a = 21000")


def test_max_execution_time_rejected_at_set_time(tk):
    s, _ = tk
    for bad, code in [("'abc'", 1232), ("1.5", 1232), ("'++5'", 1232),
                      ("'1.5'", 1232), ("-5", 1231)]:
        with pytest.raises(SessionError) as ei:
            s.execute(f"set @@max_execution_time = {bad}")
        assert ei.value.mysql_code == code, bad
    # the stored value is unchanged by the failed SETs
    assert int(s.get_sysvar("max_execution_time")) == 0
    s.execute("set @@max_execution_time = '250'")  # int-strings coerce
    assert int(s.get_sysvar("max_execution_time")) == 250


def test_kill_reaches_distsql_worker_pool(tk):
    """A kill mid-scatter-gather propagates through the worker pool's
    copied context and aborts the statement (workers observe the guard
    between attempts/backoffs)."""
    s, info = tk
    # enough tasks x per-attempt sleep that the scan outlives the kill:
    # 8 regions / 2 workers x 0.05s ≈ 0.2s of pool wall
    for h in (60, 180, 320, 440, 470):
        s.storage.cluster.split(tablecodec.encode_row_key(info.id, h))
    s.storage.cache.invalidate_all()
    s.execute("set @@tidb_distsql_scan_concurrency = 2")
    box = []
    with fail.armed("copTaskError", sleep=0.05):
        t = threading.Thread(target=_slow_query,
                             args=(s, "select b, count(*) from t group by b"),
                             kwargs={"exc_box": box})
        t.start()
        hit("copTaskError")
        from tinysql_tpu.utils import interrupt
        interrupt.kill(s.conn_id, query_only=True)
        t.join(10)
    assert not t.is_alive()
    assert isinstance(box[0], QueryKilled), box[0]


# =========================================================================
# layer 3b: memory quota
# =========================================================================

def test_mem_quota_aborts_oversized_statement(tk):
    s, _ = tk
    s.execute("set @@tidb_mem_quota_query = 8192")
    with pytest.raises(MemQuotaExceeded) as ei:
        s.query("select * from t order by b")  # full sort materialization
    assert ei.value.mysql_code == 8175
    # statement aborted cleanly; lifting the quota restores service
    s.execute("set @@tidb_mem_quota_query = 0")
    assert len(s.query("select * from t order by b").rows) == 500


def test_mem_quota_zero_is_unlimited(tk):
    s, _ = tk
    s.execute("set @@tidb_mem_quota_query = 0")
    assert len(s.query("select * from t order by b").rows) == 500


def test_mem_quota_rejects_bad_values(tk):
    s, _ = tk
    with pytest.raises(SessionError) as ei:
        s.execute("set @@tidb_mem_quota_query = 'lots'")
    assert ei.value.mysql_code == 1232


def test_mem_quota_abort_counts_in_metrics(tk):
    s, _ = tk
    from tinysql_tpu.obs.metrics import render_prometheus
    s.execute("set @@tidb_mem_quota_query = 8192")
    with pytest.raises(MemQuotaExceeded):
        s.query("select * from t order by b")
    assert "tinysql_mem_quota_exceeded_total" in render_prometheus()


# =========================================================================
# layer 3c: device-loss degradation details
# =========================================================================

def test_device_loss_pins_cpu_for_cooldown(tk):
    s, _ = tk
    want = s.query("select sum(a) from t").rows
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_tpu_min_rows = 1")
    s.execute("set @@tidb_device_cooldown = 600")
    with fail.armed("kernelDispatchError",
                    exc=degrade.DeviceLost("gone")):
        assert s.query("select sum(a) from t").rows == want
    assert degrade.cpu_pinned()
    # while pinned, statements PLAN on cpu: no dispatches even though
    # the failpoint is still armed (arming would fail any dispatch)
    with fail.armed("kernelDispatchError",
                    exc=degrade.DeviceLost("still gone")):
        assert s.query("select sum(a) from t").rows == want
    snap = degrade.snapshot()
    assert snap["device_loss_total"] == 1  # the pinned run saw no loss
    assert s.last_warnings == []


def test_sysvar_armed_dispatch_fault_degrades_too(tk):
    """Spec strings cannot name an exception class: an error() action on
    the device-boundary failpoints must degrade exactly like a
    programmatic DeviceLost."""
    s, _ = tk
    want = s.query("select sum(a) from t").rows
    _tpu_session(s)
    s.execute("set @@tidb_failpoints = 'kernelDispatchError=error(lost)'")
    try:
        assert s.query("select sum(a) from t").rows == want
    finally:
        s.execute("set @@tidb_failpoints = ''")
    assert degrade.snapshot()["degraded_statements_total"] == 1


@pytest.mark.parametrize("status, degrades", [
    ("INTERNAL: the compiler refused this program", False),
    ("RESOURCE_EXHAUSTED: out of device memory", False),
    ("UNIMPLEMENTED: no lowering for this operation", False),
    ("UNAVAILABLE: connection to the device lost", True),
])
def test_jax_runtime_error_degrades_only_on_lost_device(tk, status, degrades):
    """jax raises ONE error type for a compile refusal, an exhausted
    resource and a lost connection: only a status that says the device or
    the connection went away re-runs on CPU and pins the process; every
    other one fails the statement loudly and demotes nothing."""
    from jax.errors import JaxRuntimeError
    s, _ = tk
    want = s.query("select sum(a) from t").rows
    _tpu_session(s)
    with fail.armed("kernelDispatchError", exc=JaxRuntimeError(status),
                    times=1):
        if degrades:
            assert s.query("select sum(a) from t").rows == want
        else:
            with pytest.raises(JaxRuntimeError, match=status.split(":")[0]):
                s.query("select sum(a) from t")
    snap = degrade.snapshot()
    assert snap["device_loss_total"] == (1 if degrades else 0)
    assert snap["degraded_statements_total"] == (1 if degrades else 0)


def test_device_loss_on_write_surfaces_error(tk):
    """Writes are not idempotent: a device loss during a DELETE's scan
    must surface, never silently re-execute."""
    s, _ = tk
    s.execute("set @@tidb_use_tpu = 1")
    s.execute("set @@tidb_tpu_min_rows = 1")
    with fail.armed("kernelDispatchError",
                    exc=degrade.DeviceLost("gone"), times=1):
        try:
            s.execute("delete from t where b = 3")
            # CPU-planned delete (scan subtree not device-eligible):
            # acceptable — but it must NOT have been a silent re-run
            assert degrade.snapshot()["degraded_statements_total"] == 0
        except degrade.DeviceLost:
            pass  # surfaced: the documented contract
    assert s.query("admin check table t").rows == [["OK"]]


def test_failpoint_hits_exported_to_metrics(tk):
    s, _ = tk
    from tinysql_tpu.obs.metrics import render_prometheus
    fail.reset_hits()
    with fail.armed("execSlowNext", sleep=0.0):
        s.query("select count(*) from t")
    text = render_prometheus()
    assert 'tinysql_failpoint_hits_total{name="execSlowNext"}' in text


# =========================================================================
# layer 3d: the kv/backoff.py retry ladder under injected faults
# (SLEEP_SCALE = 0 via the autouse fixture: full ladder, no wall-clock)
# =========================================================================

def test_backoffer_budget_exhaustion_and_attempt_ledger():
    from tinysql_tpu.kv import backoff as bo
    boer = bo.Backoffer(1000)
    err = RegionError("synthetic")
    with pytest.raises(BackoffExceeded):
        for _ in range(100):
            boer.backoff(bo.BO_REGION_MISS, err)
    # the ledger recorded every attempt and the originating errors
    assert boer.attempts["regionMiss"] >= 2
    assert all(e is err for e in boer.errors)


def test_backoffer_cancel_event_interrupts_ladder():
    from tinysql_tpu.kv import backoff as bo
    from tinysql_tpu.kv.errors import TaskCancelled
    cancel = threading.Event()
    boer = bo.Backoffer(10_000_000, cancel=cancel)
    boer.backoff(bo.BO_RPC, RegionError("x"))  # fine while unset
    cancel.set()
    with pytest.raises(TaskCancelled):
        boer.backoff(bo.BO_RPC, RegionError("x"))
    # forks inherit the cancel event
    with pytest.raises(TaskCancelled):
        boer.fork().backoff(bo.BO_RPC, RegionError("x"))


def test_reader_ladder_exhausts_against_live_lock(tk):
    """A lock whose owner is alive (long TTL, primary undecided) must
    walk txnLockFast to BackoffExceeded — typed, no hang."""
    s, info = tk
    key = tablecodec.encode_row_key(info.id, 10)
    txn = s.storage.begin()
    val = txn.get(key)
    txn.rollback()
    holder = s.storage.begin()
    holder.set(key, val)
    from tinysql_tpu.kv.txn import TwoPhaseCommitter
    committer = TwoPhaseCommitter(holder)
    # prewrite with a LONG ttl directly (the chaos fixture's 1ms default
    # would let the reader resolve it instead of waiting)
    from tinysql_tpu.kv.rpc import RegionCtx
    for r, muts in s.storage.cache.group_by_region(
            committer.mutations, lambda m: m.key):
        s.storage.client.kv_prewrite(RegionCtx(r.id, r.epoch), muts,
                                     committer.primary, holder.start_ts,
                                     60_000)
    reader = Session(s.storage, current_db="c")
    reader.execute("set @@tidb_use_tpu = 0")
    with pytest.raises(BackoffExceeded):
        reader.query("select b from t where a = 10")
    # release: roll the holder's lock back; reads recover
    s.storage.client.kv_rollback(
        RegionCtx(r.id, r.epoch), [m.key for m in committer.mutations],
        holder.start_ts)
    assert reader.query("select count(*) from t where a = 10").rows \
        == [[1]]


def test_commit_phase_backoffer_exempt_from_kill():
    """Once the primary batch committed the txn is durable: the 2PC
    commit ladder (interruptible=False) must NOT abort on a statement
    kill — only interruptible ladders do."""
    from tinysql_tpu.kv import backoff as bo
    from tinysql_tpu.utils import interrupt
    g = interrupt.StatementGuard()
    g.begin()
    g.kill()
    tok = interrupt.activate(g)
    try:
        commit_boer = bo.Backoffer(1000, interruptible=False)
        commit_boer.backoff(bo.BO_RPC, RegionError("x"))  # no raise
        assert commit_boer.fork().interruptible is False
        with pytest.raises(QueryKilled):
            bo.Backoffer(1000).backoff(bo.BO_RPC, RegionError("x"))
    finally:
        interrupt.deactivate(tok)


def test_reader_resolves_expired_lock_through_ladder(tk):
    """The expired-lock branch: TTL lapses -> check_txn_status rolls the
    crashed writer back -> the SAME statement completes (resolve-retry,
    not an error)."""
    s, _ = tk
    with fail.armed("beforeCommit", panic=True, times=1):
        with pytest.raises(fail.Panic):
            s.execute("delete from t where a = 11")
    _settle()  # 1ms TTL lapses
    reader = Session(s.storage, current_db="c")
    reader.execute("set @@tidb_use_tpu = 0")
    assert reader.query("select count(*) from t where a = 11").rows \
        == [[1]]


# =========================================================================
# registry mechanics
# =========================================================================

def test_arming_unregistered_failpoint_rejected():
    with pytest.raises(ValueError):
        fail.arm("noSuchPoint", exc=RuntimeError("x"))


def test_times_limits_fires():
    fail.arm("execSlowNext", value=7, times=2)
    try:
        assert fail.eval_point("execSlowNext") == 7
        assert fail.eval_point("execSlowNext") == 7
        assert fail.eval_point("execSlowNext") is None
    finally:
        fail.disarm("execSlowNext")


def test_armed_block_restores_previous_arming():
    """A with-block override must hand the point back to whatever armed
    it before (env/sysvar arming survives scoped test arming)."""
    fail.arm("execSlowNext", value=1)
    try:
        with fail.armed("execSlowNext", value=2):
            assert fail.eval_point("execSlowNext") == 2
        assert fail.eval_point("execSlowNext") == 1
    finally:
        fail.disarm("execSlowNext")
    assert fail.eval_point("execSlowNext") is None


def test_sysvar_arming_roundtrip(tk):
    s, _ = tk
    s.execute("set @@tidb_failpoints = 'execSlowNext=return(5)'")
    try:
        assert fail.eval_point("execSlowNext") == 5
    finally:
        s.execute("set @@tidb_failpoints = ''")
    assert fail.eval_point("execSlowNext") is None
    with pytest.raises(SessionError):
        s.execute("set @@tidb_failpoints = 'bogusName=error(x)'")


def test_configure_empty_consumes_env_spec(monkeypatch):
    """SET tidb_failpoints = '' must stay disarmed even when a
    TINYSQL_FAILPOINTS env spec has not been lazily loaded yet."""
    import tinysql_tpu.fail as f
    monkeypatch.setenv("TINYSQL_FAILPOINTS", "execSlowNext=error(leaked)")
    monkeypatch.setattr(f, "_ENV_LOADED", False)
    f.configure("")
    assert f.eval_point("execSlowNext") is None


def test_error_action_raises_fresh_instance_per_fire():
    """A multi-shot error action must not re-raise the ONE stored
    exception object (shared-traceback growth, cross-thread mutation)."""
    fail.arm("execSlowNext", exc=ValueError("boom"))
    try:
        seen = []
        for _ in range(2):
            with pytest.raises(ValueError) as ei:
                fail.inject("execSlowNext")
            seen.append(ei.value)
        assert seen[0] is not seen[1]
        assert seen[0].args == seen[1].args
    finally:
        fail.disarm("execSlowNext")


def test_spec_parser_actions():
    acts = fail.parse_spec(
        "copTaskError=3*error(boom);execSlowNext=sleep(0.5);"
        "rpcServerBusy=return(42);beforeCommit=panic")
    assert acts["copTaskError"].kind == "error"
    assert acts["copTaskError"].times == 3
    assert acts["execSlowNext"].value == 0.5
    assert acts["rpcServerBusy"].value == 42
    assert acts["beforeCommit"].kind == "panic"
    with pytest.raises(ValueError):
        fail.parse_spec("copTaskError=explode()")

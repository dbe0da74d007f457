"""Durable MVCC (ISSUE 19): WAL framing, fsync policies, checkpoint +
replay equivalence, torn-tail truncation, TTL re-arm across restarts,
the GC safepoint trigger, graceful-close parity in both wire modes —
and the no-data-dir criterion: a volatile store must behave
byte-identically to the pre-WAL build (zero wal stats movement, no wal
metric lines, no wal object at all).

Restarts are SIMULATED the way a SIGKILL leaves the world: the old
store object is simply dropped (never ``close()``d — that would
checkpoint) and a fresh ``MVCCStore`` is opened on the same data dir.
"""
import os
import threading
import time

import pytest

from _timelimit import hit
from tinysql_tpu import fail
from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.kv import wal as walmod
from tinysql_tpu.kv.errors import CheckpointError, KVError, WalError
from tinysql_tpu.kv.mvcc import MVCCStore, Mutation, OP_PUT
from tinysql_tpu.kv.oracle import compose_ts
from tinysql_tpu.kv.wal import REC_COMMIT, WriteAheadLog
from tinysql_tpu.session.session import Session, SessionError


@pytest.fixture(autouse=True)
def _clean():
    fail.disarm_all()
    yield
    fail.disarm_all()


def put(st, k: bytes, v: bytes) -> None:
    t = st.begin()
    t.set(k, v)
    t.commit()


def delete(st, k: bytes) -> None:
    t = st.begin()
    t.delete(k)
    t.commit()


def entries_equal(a: MVCCStore, b: MVCCStore) -> None:
    """Entry-for-entry equivalence: same keys, same write columns, same
    data columns, same in-flight locks (identity fields exact; only a
    recovered lock's ttl may have grown)."""
    assert set(a._entries) == set(b._entries)
    for k, ea in a._entries.items():
        eb = b._entries[k]
        assert ea.writes == eb.writes, k
        assert ea.data == eb.data, k
        if ea.lock is None:
            assert eb.lock is None, k
        else:
            assert eb.lock is not None, k
            assert eb.lock.primary == ea.lock.primary
            assert eb.lock.start_ts == ea.lock.start_ts
            assert eb.lock.op == ea.lock.op
            assert eb.lock.value == ea.lock.value
            assert eb.lock.ttl_ms >= ea.lock.ttl_ms


def rich_history(st) -> None:
    """Puts, overwrites, deletes, a rollback, and a left-behind
    in-flight lock — every record type recovery must rebuild."""
    put(st, b"alpha", b"1")
    put(st, b"beta", b"2")
    put(st, b"alpha", b"3")        # overwrite: two write versions
    delete(st, b"beta")
    t = st.begin()
    t.set(b"gamma", b"9")
    t.rollback()
    # in-flight prewrite: lock survives the crash for the resolution
    # ladder to fence or complete
    ts = st.oracle.get_timestamp()
    st.mvcc.prewrite([Mutation(OP_PUT, b"locked", b"L")], b"locked",
                     ts, ttl_ms=60_000)


# ---- no data dir: byte-identical legacy behaviour -------------------------

def test_no_data_dir_is_byte_identical():
    walmod.reset_stats()
    before = walmod.stats_snapshot()
    st = new_mock_storage()
    assert st.data_dir == ""
    assert st.mvcc.wal is None
    assert st.mvcc.recovery_info is None
    put(st, b"k", b"v")
    delete(st, b"k")
    put(st, b"k2", b"v2")
    t = st.begin()
    assert t.get(b"k2") == b"v2"
    t.rollback()
    st.close()  # graceful close is a no-op without a wal
    assert walmod.stats_snapshot() == before, \
        "volatile store moved wal counters"
    from tinysql_tpu.obs.metrics import render_prometheus
    assert "tinysql_wal_" not in render_prometheus()
    assert "tinysql_recovery_" not in render_prometheus()


def test_env_var_arms_data_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("TINYSQL_DATA_DIR", str(tmp_path / "dd"))
    st = new_mock_storage()
    assert st.mvcc.wal is not None
    put(st, b"k", b"v")
    assert os.path.exists(str(tmp_path / "dd"))


# ---- recovery equivalence -------------------------------------------------

def test_log_replay_equivalence_entry_for_entry(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    rich_history(st)
    # simulated kill -9: no close, no checkpoint
    st2 = new_mock_storage(data_dir=str(tmp_path))
    ri = st2.mvcc.recovery_info
    # the first open checkpointed an EMPTY store (lsn 0): the whole
    # history must come back from the log alone
    assert ri is not None and ri["checkpoint_lsn"] == 0
    assert ri["replayed_records"] > 0
    assert ri["recovered_locks"] == 1
    entries_equal(st.mvcc, st2.mvcc)
    # recovered store serves reads
    t = st2.begin()
    assert t.get(b"alpha") == b"3"
    with pytest.raises(KVError):
        t.get(b"beta")           # the delete recovered too
    t.rollback()
    # oracle fenced past everything recovered: new commits must win
    assert st2.oracle.get_timestamp() > st2.mvcc.max_known_ts()
    put(st2, b"alpha", b"4")
    t = st2.begin()
    assert t.get(b"alpha") == b"4"
    t.rollback()


def test_checkpoint_plus_log_replay_equivalence(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    put(st, b"a", b"1")
    put(st, b"b", b"2")
    st.flush_and_checkpoint()
    assert st.mvcc.wal.is_checkpoint_clean()
    put(st, b"c", b"3")          # post-checkpoint tail
    delete(st, b"a")
    st2 = new_mock_storage(data_dir=str(tmp_path))
    ri = st2.mvcc.recovery_info
    assert ri["checkpoint_loaded"]
    # only the tail replays; the checkpoint carries the rest
    assert 0 < ri["replayed_records"] < 10
    entries_equal(st.mvcc, st2.mvcc)


def test_second_recovery_is_idempotent(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    rich_history(st)
    st2 = new_mock_storage(data_dir=str(tmp_path))
    st3 = new_mock_storage(data_dir=str(tmp_path))
    entries_equal(st2.mvcc, st3.mvcc)


def test_checkpoint_rotation_under_tiny_threshold(tmp_path, monkeypatch):
    monkeypatch.setenv("TINYSQL_WAL_CHECKPOINT_BYTES", "256")
    before = walmod.stats_snapshot()["checkpoints"]
    st = new_mock_storage(data_dir=str(tmp_path))
    for i in range(30):
        put(st, f"k{i}".encode(), b"x" * 64)
    assert walmod.stats_snapshot()["checkpoints"] > before
    # the live log stays rotated — far below 30 records' worth
    assert st.mvcc.wal.records_since_checkpoint() < 30
    st2 = new_mock_storage(data_dir=str(tmp_path))
    assert st2.mvcc.recovery_info["checkpoint_loaded"]
    entries_equal(st.mvcc, st2.mvcc)


# ---- torn tail ------------------------------------------------------------

def test_torn_tail_truncated_on_recovery(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    put(st, b"good", b"1")
    with fail.armed("walTornTail", times=1):
        with pytest.raises(KVError):
            put(st, b"torn", b"2")   # half-written frame poisons the log
    # the poisoned live log refuses further appends (never diverge
    # ahead of a log we cannot write)
    with pytest.raises(KVError):
        put(st, b"after", b"3")
    before = walmod.stats_snapshot()["truncated_tails"]
    st2 = new_mock_storage(data_dir=str(tmp_path))
    ri = st2.mvcc.recovery_info
    assert ri["truncated_tail_bytes"] > 0
    assert walmod.stats_snapshot()["truncated_tails"] == before + 1
    t = st2.begin()
    assert t.get(b"good") == b"1"    # everything before the tear survives
    t.rollback()
    # the torn record is gone atomically — not even an entry shell
    assert b"torn" not in st2.mvcc._entries
    assert b"after" not in st2.mvcc._entries
    put(st2, b"after", b"3")         # recovered log is writable again
    assert walmod.stats_snapshot()["torn_writes"] >= 1


def test_truncation_never_reaches_behind_checkpoint(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    put(st, b"a", b"1")
    st.flush_and_checkpoint()
    with fail.armed("walTornTail", times=1):
        with pytest.raises(KVError):
            put(st, b"b", b"2")
    st2 = new_mock_storage(data_dir=str(tmp_path))
    t = st2.begin()
    assert t.get(b"a") == b"1"
    t.rollback()


# ---- fsync policy matrix --------------------------------------------------

def test_fsync_policy_matrix(tmp_path):
    from tinysql_tpu.kv.wal import encode_commit
    body = encode_commit(1, 2, [(b"k", 0, b"v")])
    # strict: one fsync per commit-class record
    w = WriteAheadLog(str(tmp_path / "s"), fsync_policy="strict")
    base = walmod.stats_snapshot()["fsyncs"]
    for _ in range(10):
        w.append(REC_COMMIT, body)
    assert walmod.stats_snapshot()["fsyncs"] - base == 10
    w.close()
    # off: never
    w = WriteAheadLog(str(tmp_path / "o"), fsync_policy="off")
    base = walmod.stats_snapshot()["fsyncs"]
    for _ in range(10):
        w.append(REC_COMMIT, body)
    assert walmod.stats_snapshot()["fsyncs"] - base == 0
    w.close()
    # relaxed: group commit — a tight burst coalesces far below 1:1
    w = WriteAheadLog(str(tmp_path / "r"), fsync_policy="relaxed")
    base = walmod.stats_snapshot()["fsyncs"]
    for _ in range(10):
        w.append(REC_COMMIT, body)
    relaxed = walmod.stats_snapshot()["fsyncs"] - base
    assert 1 <= relaxed < 10
    w.close()
    with pytest.raises(ValueError):
        WriteAheadLog(str(tmp_path / "x"), fsync_policy="bogus")


def test_fsync_sysvar_validation_and_live_apply(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    s = Session(st)
    s.execute("set @@tidb_wal_fsync = 'strict'")
    assert st.mvcc.wal.fsync_policy == "strict"
    s.execute("set @@tidb_wal_fsync = 'off'")
    assert st.mvcc.wal.fsync_policy == "off"
    with pytest.raises(SessionError):
        s.execute("set @@tidb_wal_fsync = 'sometimes'")


# ---- WAL failpoints surface typed errors ----------------------------------

def test_wal_append_error_is_typed_and_store_unmutated(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    put(st, b"k", b"1")
    with fail.armed("walAppendError", exc=IOError("disk full"),
                    times=1):
        with pytest.raises(WalError):
            put(st, b"k", b"2")
    base = walmod.stats_snapshot()["append_errors"]
    assert base >= 1
    # journal-before-apply: the failed mutation never reached the store
    t = st.begin()
    assert t.get(b"k") == b"1"
    t.rollback()
    put(st, b"k", b"2")  # and the log is healthy again
    st2 = new_mock_storage(data_dir=str(tmp_path))
    t = st2.begin()
    assert t.get(b"k") == b"2"
    t.rollback()


def test_wal_fsync_error_under_strict_surfaces(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    st.mvcc.wal.set_fsync_policy("strict")
    base = walmod.stats_snapshot()["fsync_errors"]
    with fail.armed("walFsyncError", exc=OSError("EIO"), times=1):
        with pytest.raises(KVError):
            put(st, b"k", b"1")
    assert walmod.stats_snapshot()["fsync_errors"] > base


def test_checkpoint_error_is_typed_and_nonfatal(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    put(st, b"k", b"1")
    with fail.armed("checkpointError", exc=OSError("nope"), times=1):
        with pytest.raises(CheckpointError):
            st.flush_and_checkpoint()
    # never fatal: the unrotated log is still the recovery source
    put(st, b"k", b"2")
    st2 = new_mock_storage(data_dir=str(tmp_path))
    t = st2.begin()
    assert t.get(b"k") == b"2"
    t.rollback()


def test_crash_during_recovery_is_recoverable(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    rich_history(st)
    # first recovery attempt: its post-replay checkpoint dies —
    # recovery itself must succeed off the old checkpoint + log
    before = walmod.stats_snapshot()["checkpoint_errors"]
    with fail.armed("checkpointError", exc=OSError("crashed"), times=1):
        st2 = new_mock_storage(data_dir=str(tmp_path))
    assert walmod.stats_snapshot()["checkpoint_errors"] > before
    entries_equal(st.mvcc, st2.mvcc)
    # drop st2 un-closed (the second crash); a third recovery is clean
    st3 = new_mock_storage(data_dir=str(tmp_path))
    entries_equal(st.mvcc, st3.mvcc)


# ---- TTL re-arm across restart --------------------------------------------

def test_recovered_lock_ttl_rearms_from_restart_time(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    ts = st.oracle.get_timestamp()
    st.mvcc.prewrite([Mutation(OP_PUT, b"p", b"v")], b"p", ts,
                     ttl_ms=40)
    # let the ORIGINAL ttl lapse in real wall-clock time
    time.sleep(0.08)
    assert st.oracle.is_expired(ts, 40)
    st2 = new_mock_storage(data_dir=str(tmp_path))
    lk = st2.mvcc._entries[b"p"].lock
    assert lk is not None and lk.start_ts == ts
    # re-armed: birth-to-restart age added, so the txn gets a full ttl
    # of post-restart grace instead of being instantly expired
    assert lk.ttl_ms >= 40 + 70
    assert not st2.oracle.is_expired(lk.start_ts, lk.ttl_ms)
    # and the ladder can still fence it once the NEW ttl lapses
    cts, committed = st2.mvcc.check_txn_status(b"p", ts, expired=True)
    assert (cts, committed) == (0, False)


# ---- GC safepoint trigger -------------------------------------------------

def test_gc_safepoint_sysvar_and_run(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    for i in range(5):
        put(st, b"hot", f"v{i}".encode())
    delete(st, b"dead")
    assert len(st.mvcc._entries[b"hot"].writes) == 5
    base = walmod.stats_snapshot()["gc_runs"]
    # retention ~0: everything but the newest version is collectable
    removed = st.run_gc(compose_ts(int(time.time() * 1000) + 1, 0))
    assert removed > 0
    assert len(st.mvcc._entries[b"hot"].writes) == 1
    assert walmod.stats_snapshot()["gc_runs"] == base + 1
    # the gc record journals: a recovered store has the same history
    st2 = new_mock_storage(data_dir=str(tmp_path))
    entries_equal(st.mvcc, st2.mvcc)


def test_gc_sysvar_validation_and_domain_trigger(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    s = Session(st)
    with pytest.raises(SessionError):
        s.execute("set @@tidb_gc_safepoint = -3")
    with pytest.raises(SessionError):
        s.execute("set @@tidb_gc_safepoint = 'soon'")
    for i in range(4):
        put(st, b"k", f"v{i}".encode())
    # the safepoint lands at now − 1µs: let the puts' commit-ts
    # millisecond tick over so every stale version sits below it
    time.sleep(0.01)
    s.execute("set global tidb_gc_safepoint = 0.000001")
    from tinysql_tpu.domain.domain import shared_domain
    d = shared_domain(st)
    base = walmod.stats_snapshot()["gc_runs"]
    d._maybe_gc()  # what the ddl-owner duty loop invokes
    assert walmod.stats_snapshot()["gc_runs"] == base + 1
    assert len(st.mvcc._entries[b"k"].writes) == 1
    # paced: an immediate second call is a no-op
    d._maybe_gc()
    assert walmod.stats_snapshot()["gc_runs"] == base + 1


def test_gc_disabled_by_default(tmp_path):
    st = new_mock_storage(data_dir=str(tmp_path))
    put(st, b"k", b"v")
    from tinysql_tpu.domain.domain import shared_domain
    base = walmod.stats_snapshot()["gc_runs"]
    shared_domain(st)._maybe_gc()
    assert walmod.stats_snapshot()["gc_runs"] == base


# ---- graceful-close parity (both wire modes) ------------------------------

def _server_on(tmp_path):
    from tinysql_tpu.server.server import Server
    st = new_mock_storage(data_dir=str(tmp_path))
    srv = Server(st, port=0)
    srv.start()
    return st, srv


def test_graceful_close_checkpoints_legacy_mode(tmp_path):
    from tests.test_server import MiniClient
    st, srv = _server_on(tmp_path)
    c = MiniClient(srv.port)
    c.query("create database g")
    c.query("use g")
    c.query("create table t (a int primary key)")
    c.query("insert into t values (1)")
    c.close()
    srv.close()
    assert st.mvcc.wal.is_checkpoint_clean(), \
        "graceful close left an unrotated wal"
    st2 = new_mock_storage(data_dir=str(tmp_path))
    assert st2.mvcc.recovery_info["checkpoint_loaded"]
    assert st2.mvcc.recovery_info["replayed_records"] == 0


def test_aio_close_drains_inflight_then_checkpoints(tmp_path):
    from tests.test_server import MiniClient
    st, srv = _server_on(tmp_path)
    boot = Session(st)
    boot.execute("set global tidb_wire_mode = 'aio'")
    c = MiniClient(srv.port)
    c.query("create database g")
    c.query("use g")
    c.query("create table t (a int primary key)")
    box = []

    def slow_insert():
        try:
            # (an INSERT never passes execSlowNext: the wedge that holds
            # it on its pool worker, entry claimed, is admissionDelay)
            with fail.armed("admissionDelay", sleep=0.3, times=1):
                box.append(c.query("insert into t values (7)"))
        except Exception as e:  # pragma: no cover - failure capture
            box.append(e)

    fail.reset_hits()
    th = threading.Thread(target=slow_insert)
    th.start()
    hit("admissionDelay")    # statement is mid-flight on the pool
    srv.close()              # shutdown drain must let it complete
    th.join(timeout=5)
    assert not th.is_alive()
    assert box and box[0] == 1, f"in-flight statement lost: {box}"
    assert st.mvcc.wal.is_checkpoint_clean()
    # the drained row is durable across a restart
    st2 = new_mock_storage(data_dir=str(tmp_path))
    s2 = Session(st2, current_db="g")
    assert s2.query("select a from t").rows == [[7]]

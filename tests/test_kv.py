"""Transactional KV behavior: Percolator 2PC, snapshot isolation, lock
resolution, region routing (reference: store/tikv/*_test.go — 2pc_test.go,
lock_test.go, snapshot_test.go, split_test.go; kv/memdb tests)."""
import pytest

import tinysql_tpu.kv.backoff as backoff_mod
from tinysql_tpu.kv import (
    BackoffExceeded, KeyExists, KeyIsLocked, KeyNotFound, Mutation,
    RegionCtx, TxnAborted, UndeterminedError, WriteConflict,
    new_mock_storage, MemDB, TOMBSTONE, OP_PUT,
)
from tinysql_tpu.kv.txn import TwoPhaseCommitter
from tinysql_tpu.utils import failpoint


@pytest.fixture(autouse=True)
def _clean_failpoints(monkeypatch):
    # run full retry ladders without wall-clock sleeps — in THIS module's
    # tests only: set at import it held for every test of every worker
    # (each collects this file), and a reader that met a committer's live
    # lock spent its 20 s budget in microseconds and failed
    monkeypatch.setattr(backoff_mod, "SLEEP_SCALE", 0)
    yield
    failpoint.disable_all()


def test_memdb_order_and_tombstone():
    db = MemDB()
    db.set(b"b", b"2")
    db.set(b"a", b"1")
    db.set(b"c", b"3")
    db.delete(b"b")
    assert [k for k, _ in db.items()] == [b"a", b"b", b"c"]
    assert db.get(b"b") == TOMBSTONE
    assert list(db.iter_range(b"b", b"c")) == [(b"b", TOMBSTONE)]


def test_oracle_monotonic():
    s = new_mock_storage()
    last = 0
    for _ in range(1000):
        ts = s.oracle.get_timestamp()
        assert ts > last
        last = ts


def test_basic_txn_and_snapshot_isolation():
    s = new_mock_storage()
    t1 = s.begin()
    t1.set(b"k1", b"v1")
    t1.set(b"k2", b"v2")
    assert t1.get(b"k1") == b"v1"  # read own writes
    t1.commit()

    snap_before = s.get_snapshot(t1.start_ts)  # snapshot at start_ts: no data
    with pytest.raises(KeyNotFound):
        snap_before.get(b"k1")

    t2 = s.begin()
    assert t2.get(b"k1") == b"v1"
    t2.delete(b"k1")
    with pytest.raises(KeyNotFound):
        t2.get(b"k1")
    t2.commit()

    t3 = s.begin()
    with pytest.raises(KeyNotFound):
        t3.get(b"k1")
    assert t3.get(b"k2") == b"v2"


def test_write_conflict():
    s = new_mock_storage()
    t0 = s.begin()
    t0.set(b"k", b"0")
    t0.commit()
    ta = s.begin()
    tb = s.begin()
    ta.set(b"k", b"a")
    tb.set(b"k", b"b")
    tb.commit()
    with pytest.raises(WriteConflict):
        ta.commit()
    assert s.begin().get(b"k") == b"b"


def test_insert_duplicate_detected_at_prewrite():
    s = new_mock_storage()
    t0 = s.begin()
    t0.insert(b"u", b"1")
    t0.commit()
    t1 = s.begin()
    t1.insert(b"u", b"2")
    with pytest.raises(KeyExists):
        t1.commit()


def test_crashed_writer_lock_resolved_by_reader():
    """A prewrite with no commit (writer crash) must not block readers
    forever: TTL expires -> reader rolls the orphan txn back
    (reference: lock_resolver.go Percolator recovery)."""
    s = new_mock_storage()
    start_ts = s.oracle.get_timestamp()
    s.mvcc.prewrite([Mutation(OP_PUT, b"k", b"v")], b"k", start_ts, ttl_ms=0)
    assert s.mvcc.locked_keys() == [b"k"]
    with pytest.raises(KeyNotFound):
        s.get_snapshot().get(b"k")     # resolves the expired lock, no value
    assert s.mvcc.locked_keys() == []
    # the orphan txn is fenced: its late commit must now fail
    with pytest.raises(TxnAborted):
        s.mvcc.commit([b"k"], start_ts, s.oracle.get_timestamp())


def test_committed_primary_secondary_lock_resolved_forward():
    """Primary committed but secondary lock left behind (writer died between
    commits): a reader of the secondary must roll it FORWARD."""
    s = new_mock_storage()
    start_ts = s.oracle.get_timestamp()
    s.mvcc.prewrite([Mutation(OP_PUT, b"p", b"vp"),
                     Mutation(OP_PUT, b"s", b"vs")], b"p", start_ts, 10_000)
    commit_ts = s.oracle.get_timestamp()
    s.mvcc.commit([b"p"], start_ts, commit_ts)  # only the primary
    assert s.get_snapshot().get(b"s") == b"vs"  # forward-resolved
    assert s.mvcc.locked_keys() == []


def test_live_lock_blocks_until_ttl():
    """A live (unexpired) lock can't be stomped; reader backs off and
    eventually exhausts budget."""
    s = new_mock_storage()
    start_ts = s.oracle.get_timestamp()
    s.mvcc.prewrite([Mutation(OP_PUT, b"k", b"v")], b"k", start_ts,
                    ttl_ms=60_000)
    with pytest.raises(BackoffExceeded):
        s.get_snapshot().get(b"k")
    assert s.mvcc.locked_keys() == [b"k"]  # lock survived


def test_commit_across_split_regions():
    s = new_mock_storage()
    t = s.begin()
    for i in range(10):
        t.set(b"k%03d" % i, b"v%d" % i)
    s.cluster.split(b"k003")
    s.cluster.split(b"k007")  # stale client region cache now
    t.commit()
    snap = s.get_snapshot()
    assert snap.get(b"k000") == b"v0"
    assert snap.get(b"k009") == b"v9"
    assert len(s.cluster.all_regions()) == 3


def test_scan_across_regions_and_limit():
    s = new_mock_storage()
    t = s.begin()
    for i in range(20):
        t.set(b"s%03d" % i, b"v%d" % i)
    t.commit()
    s.cluster.split(b"s005")
    s.cluster.split(b"s015")
    s.cache.invalidate_all()
    got = list(s.get_snapshot().iter_range(b"s", b"t"))
    assert len(got) == 20
    assert got[0] == (b"s000", b"v0")
    assert got[-1] == (b"s019", b"v19")


def test_store_down_backoff_exceeded():
    s = new_mock_storage()
    t = s.begin()
    t.set(b"k", b"v")
    t.commit()
    s.cluster.stop_store(s.cluster.all_regions()[0].store_id)
    with pytest.raises(BackoffExceeded):
        s.get_snapshot().get(b"k")
    s.cluster.start_store(s.cluster.all_regions()[0].store_id)
    assert s.get_snapshot().get(b"k") == b"v"


def test_failpoint_prewrite_error_rolls_back():
    s = new_mock_storage()
    t = s.begin()
    t.set(b"k", b"v")
    with failpoint.enable("prewriteError", exc=RuntimeError("inject")):
        with pytest.raises(RuntimeError):
            t.commit()
    # cleanup ran: no stale lock, no value
    assert s.mvcc.locked_keys() == []
    with pytest.raises(KeyNotFound):
        s.get_snapshot().get(b"k")


def test_failpoint_primary_commit_error_is_undetermined():
    s = new_mock_storage()
    t = s.begin()
    t.set(b"k", b"v")
    with failpoint.enable("commitPrimaryError", exc=IOError("net down")):
        with pytest.raises(UndeterminedError):
            t.commit()
    # outcome genuinely unknown: no cleanup may run; lock remains for
    # the resolver (here: still locked, resolvable after TTL)
    assert s.mvcc.locked_keys() == [b"k"]


def test_failpoint_secondary_commit_error_txn_still_durable():
    s = new_mock_storage()
    t = s.begin()
    t.set(b"a", b"1")   # primary (first key)
    t.set(b"z", b"2")   # secondary
    s.cluster.split(b"m")  # separate regions so batches are distinct
    s.cache.invalidate_all()
    with failpoint.enable("commitSecondaryError", exc=IOError("flaky")):
        t.commit()      # must succeed: primary committed
    snap = s.get_snapshot()
    assert snap.get(b"a") == b"1"
    assert snap.get(b"z") == b"2"  # forward-resolved from leftover lock


def test_readonly_txn_commit_is_noop():
    s = new_mock_storage()
    t = s.begin()
    t.commit()
    assert t.is_readonly()


def test_rollback_then_new_txn():
    s = new_mock_storage()
    t = s.begin()
    t.set(b"k", b"v")
    t.rollback()
    with pytest.raises(KeyNotFound):
        s.get_snapshot().get(b"k")


def test_union_store_merge_iter():
    s = new_mock_storage()
    t0 = s.begin()
    t0.set(b"a", b"snap")
    t0.set(b"c", b"snap")
    t0.commit()
    t1 = s.begin()
    t1.set(b"b", b"buf")
    t1.set(b"c", b"shadowed")
    t1.delete(b"a")
    got = list(t1.iter_range(b"", b"z"))
    assert got == [(b"b", b"buf"), (b"c", b"shadowed")]


def test_delete_then_insert_same_txn():
    """Regression: delete+insert of an existing key in one txn is a plain
    overwrite, not a duplicate (the update_record pattern)."""
    s = new_mock_storage()
    t0 = s.begin()
    t0.set(b"k", b"old")
    t0.commit()
    t1 = s.begin()
    t1.delete(b"k")
    t1.insert(b"k", b"new")
    t1.commit()
    assert s.get_snapshot().get(b"k") == b"new"


def test_batch_get_region_batched():
    s = new_mock_storage()
    t = s.begin()
    for i in range(30):
        t.set(b"bg%03d" % i, b"v%d" % i)
    t.commit()
    s.cluster.split(b"bg010")
    s.cluster.split(b"bg020")
    s.cache.invalidate_all()
    t2 = s.begin()
    t2.set(b"bg000", b"buffered")
    t2.delete(b"bg001")
    keys = [b"bg%03d" % i for i in range(30)] + [b"missing"]
    got = t2.batch_get(keys)
    assert got[b"bg000"] == b"buffered"
    assert b"bg001" not in got and b"missing" not in got
    assert got[b"bg029"] == b"v29"
    assert len(got) == 29

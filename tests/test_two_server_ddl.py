"""Two-server online-DDL correctness (VERDICT r1 #8): two in-process
"servers" (per-server schema-cache Domains over ONE shared store) while
DDL runs on one and DML on the other.

Proves the F1 multi-server invariants the reference implements with
ddl/util/syncer.go + owner/manager.go + domain/domain.go:
- the DDL owner never advances a job more than ONE schema state ahead of
  any live server (syncer barrier observed version-by-version)
- a server on the stale-by-one cache still maintains WRITE_ONLY indices,
  so backfill + concurrent writes lose nothing (admin check table)
- owner election: one winner at a time; lease expiry transfers ownership
"""
import threading
import time

import pytest

from _timelimit import join

from tinysql_tpu.catalog.meta import Meta
from tinysql_tpu.catalog.model import SchemaState
from tinysql_tpu.ddl.owner import OwnerManager
from tinysql_tpu.domain import Domain, wait_schema_synced
from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.session.session import Session


def _store_version(storage) -> int:
    txn = storage.begin()
    try:
        return Meta(txn).schema_version()
    finally:
        txn.rollback()


def _index_state(sess, db, tbl, idx_name):
    sess._pinned_is = None  # observe the domain's CURRENT cache, not the
    info = sess.infoschema().table_by_name(db, tbl)  # last statement's pin
    sess._pinned_is = None
    for ii in info.indices:
        if ii.name.lower() == idx_name:
            return ii.state
    return None


def test_syncer_barrier_staged_states_observed():
    storage = new_mock_storage()
    a = Domain(storage, "srvA", lease_s=60.0)  # manual reload control
    b = Domain(storage, "srvB", lease_s=60.0)
    sa = Session(storage, domain=a)
    sb = Session(storage, domain=b)
    sa.execute("create database d")
    a.reload(); b.reload()
    sa.execute("use d")
    sa.execute("create table t (x int primary key, y int)")
    a.reload(); b.reload()
    sa.execute("insert into t values (1, 10), (2, 20)")

    err = []

    def run_ddl():
        try:
            sa.execute("create index iy on t (y)")
        except Exception as e:  # pragma: no cover
            err.append(e)

    seen = []
    prev = _store_version(storage)  # BEFORE the DDL thread starts
    th = threading.Thread(target=run_ddl)
    th.start()
    deadline = time.time() + 30
    # the worker CANNOT advance past a version until BOTH domains load it:
    # reloading exactly once per version observes every staged state
    while th.is_alive():
        assert time.time() < deadline, "DDL stalled"
        ver = _store_version(storage)
        if ver != prev:
            b.reload()
            st = _index_state(sb, "d", "t", "iy")
            if st is not None and (not seen or seen[-1] != st):
                seen.append(st)
            a.reload()
            prev = ver
        time.sleep(0.001)
    join(th)
    assert not err, err
    b.reload()
    assert seen[-1] == SchemaState.PUBLIC, seen
    # every intermediate F1 state crossed the barrier in order
    want_order = [SchemaState.DELETE_ONLY, SchemaState.WRITE_ONLY,
                  SchemaState.WRITE_REORG, SchemaState.PUBLIC]
    positions = [seen.index(s) for s in want_order if s in seen]
    assert positions == sorted(positions), seen
    assert SchemaState.WRITE_ONLY in seen, seen
    a.close(); b.close()


def test_stale_server_dml_during_add_index_loses_nothing():
    storage = new_mock_storage()
    a = Domain(storage, "srvA", lease_s=0.01, background=True)
    b = Domain(storage, "srvB", lease_s=0.01, background=True)
    sa = Session(storage, domain=a)
    sa.execute("create database d")
    sa.execute("use d")
    sa.execute("create table t (x int primary key, y int)")
    sa.execute("insert into t values " + ", ".join(
        f"({i}, {i * 3})" for i in range(1, 400)))

    stop = threading.Event()
    wrote = []
    errs = []

    def write_on_b():
        sb = Session(storage, current_db="d", domain=b)
        i = 10_000
        while not stop.is_set():
            try:
                sb.execute(f"insert into t values ({i}, {i})")
                wrote.append(i)
                i += 1
            except Exception as e:
                # schema moved under the statement: retryable per the
                # validator contract; anything else is a real failure
                if "schema" not in str(e).lower():
                    errs.append(e)
                    return

    wt = threading.Thread(target=write_on_b)
    wt.start()
    try:
        sa.execute("create index iy on t (y)")
    finally:
        stop.set()
        join(wt)
    assert not errs, errs
    assert wrote, "writer made no progress"
    # no missed index maintenance: index rows == table rows, consistent
    sc = Session(storage, current_db="d")
    assert sc.query("admin check table t").rows == [["OK"]]
    n = sc.query("select count(*) from t").rows[0][0]
    assert n == 399 + len(wrote)
    a.close(); b.close()


def test_owner_election_lease_and_takeover():
    storage = new_mock_storage()
    m1 = OwnerManager(storage, "s1", ttl_s=0.15)
    m2 = OwnerManager(storage, "s2", ttl_s=0.15)
    assert m1.campaign() and m1.is_owner()
    assert not m2.campaign() and not m2.is_owner()
    assert m1.campaign()  # renew
    m1.retire()
    assert m2.campaign() and m2.is_owner()
    # lease expiry: a crashed owner loses ownership without retiring
    time.sleep(0.2)
    assert not m2.is_owner()
    assert m1.campaign() and m1.is_owner()


def test_non_owner_ddl_waits_for_owner():
    storage = new_mock_storage()
    a = Domain(storage, "srvA", lease_s=0.01, background=True)
    b = Domain(storage, "srvB", lease_s=0.01, background=True)
    # A grabs ownership with a SHORT lease, then goes idle; B's DDL first
    # waits, then takes over when the lease lapses
    a.ddl().owner.ttl_s = 0.1
    assert a.ddl().owner.campaign()
    sb = Session(storage, domain=b)
    t0 = time.time()
    sb.execute("create database waited")
    assert "waited" in [r[0] for r in
                        sb.query("show databases").rows]
    assert time.time() - t0 < 10
    a.close(); b.close()


def test_wait_schema_synced_timeout_and_catchup():
    storage = new_mock_storage()
    d = Domain(storage, "lagger", lease_s=60.0)
    s = Session(storage)
    ver0 = _store_version(storage)
    s.execute("create database x")  # bumps version; lagger is stale
    assert not wait_schema_synced(storage, ver0 + 1, timeout_s=0.05)
    d.reload()
    assert wait_schema_synced(storage, ver0 + 1, timeout_s=0.05)
    d.close()

"""Stacked-params batch execution (ISSUE 14): one XLA dispatch per N
coalesced same-digest queries.

The PR 7 micro-batcher parked N ParamTables and replayed them
back-to-back — N dispatches per round.  ops/batching.py's dispatch leg
now stacks layout-compatible members on a leading batch axis
(exprjit.ParamTable.stack) and runs ONE ``jax.vmap``-batched program
variant (kernels.stacked_variant), registered under the base progcache
key extended with a power-of-two occupancy bucket.  These tests pin the
contract: byte-identity with solo execution across occupancies, bucket
key semantics (occupancy 3 hits the B=4 program), occupancy-weighted
device-counter attribution that sums to the global truth on BOTH
dispatch legs, layout-mismatch fallback, KILL reaching a parked member
mid-stacked-round, and duplicate identical statements sharing a round.
"""
import numpy as np
import pytest

from test_server import MiniClient  # noqa: F401  (fixture parity w/ serve)
from tinysql_tpu.kv import new_mock_storage
from tinysql_tpu.obs import stmtsummary
from tinysql_tpu.ops import batching, kernels, progcache
from tinysql_tpu.ops.exprjit import ParamTable
from tinysql_tpu.parser import parse
from tinysql_tpu.server.pool import StatementPool, _Entry
from tinysql_tpu.server.server import Server
from tinysql_tpu.session.session import Session


@pytest.fixture(scope="module")
def server():
    storage = new_mock_storage()
    srv = Server(storage, port=0)
    srv.start()
    boot = Session(storage)
    # these tests count the process's progcache misses around a round:
    # the auto-prewarm worker (first cycle 60 s after start, well inside
    # this module on a loaded machine) must not build beside them
    boot.execute("set global tidb_auto_prewarm = 0")
    boot.execute("create database if not exists stk")
    boot.execute("use stk")
    boot.execute("create table t (a int primary key, b int, c double)")
    boot.execute("insert into t values " + ", ".join(
        f"({i}, {i % 41}, {i * 0.5})" for i in range(4000)))
    boot.execute("set global tidb_tpu_min_rows = 16")
    boot.execute("select a, b, c from t")  # hydrate the columnar replica
    yield srv
    srv.close()


def _sess(server):
    s = Session(server.storage)
    s.execute("use stk")
    return s


def _variants(n, lo=3):
    return [f"select sum(c), count(*), max(c) from t where b < {lo + i}"
            for i in range(n)]


def _drive_round(server, qs, stack_max=16):
    """One embedded batch round over ``qs`` (the pool's deterministic
    drive); returns the completed entries."""
    digest, _ = stmtsummary.normalize(qs[0])
    pool = StatementPool(server.storage)
    entries = [_Entry(_sess(server), parse(q)[0], q, digest, True)
               for q in qs]
    pool._run_batch(entries)
    return entries


# =========================================================================
# byte-identity across occupancies + zero warm compiles
# =========================================================================

def test_stacked_equals_solo_across_occupancies(server):
    """Occupancies 2 / 3 / 5 / 8 through the stacked leg: results
    byte-identical to solo execution, zero compiles once the B-bucket
    variants are warm, one stacked round per drive."""
    qs = _variants(8)
    solo = {q: _sess(server).query(q).rows for q in qs}  # warm + note
    kernels.prewarm_stacked()  # B in {2, 4, 8, 16}, like the worker
    boot = _sess(server)
    boot.execute("set global tidb_batch_stack_max = 16")
    for occ in (2, 3, 5, 8):
        st0 = batching.stats_snapshot()
        miss0 = progcache.stats_snapshot()["misses"]
        entries = _drive_round(server, qs[:occ])
        for e, q in zip(entries, qs[:occ]):
            assert e.error is None, (occ, e.error)
            assert repr(e.result.rows) == repr(solo[q]), (occ, q)
        st = batching.stats_snapshot()
        assert st["stacked_rounds"] == st0["stacked_rounds"] + 1, occ
        assert st["stacked_occupancy_sum"] \
            == st0["stacked_occupancy_sum"] + occ
        assert st["fallbacks"] == st0["fallbacks"]
        assert progcache.stats_snapshot()["misses"] == miss0, \
            f"occupancy {occ} compiled on a warm path"


def test_occupancy_bucket_semantics(server):
    """Occupancy 3 rides the B=4 program: the first 3-member round may
    build the variant, after which 3-member AND 4-member rounds are
    both plain hits on the SAME ("stacked", 4)-keyed program."""
    assert kernels.occupancy_bucket(2) == 2
    assert kernels.occupancy_bucket(3) == 4
    assert kernels.occupancy_bucket(5) == 8
    assert kernels.occupancy_bucket(8) == 8
    qs = _variants(4, lo=20)
    solo = {q: _sess(server).query(q).rows for q in qs}
    _drive_round(server, qs[:3])  # builds the B=4 variant if cold
    stacked_keys = [k for k in progcache.keys("scalar")
                    if kernels.is_stacked_key(k)]
    assert any(k[-1] == ("stacked", 4) for k in stacked_keys), stacked_keys
    miss0 = progcache.stats_snapshot()["misses"]
    st0 = batching.stats_snapshot()
    for qset in (qs[:3], qs[:4]):  # occupancy 3 AND 4 -> the B=4 hit
        for e, q in zip(_drive_round(server, qset), qset):
            assert e.error is None and repr(e.result.rows) == repr(solo[q])
    st = batching.stats_snapshot()
    assert st["stacked_rounds"] == st0["stacked_rounds"] + 2
    assert progcache.stats_snapshot()["misses"] == miss0


def test_stacked_group_by_tree_outputs(server):
    """The fused segment (group-by) path stacks too — "tree" outputs
    slice per member on device.  Round 1 may compile the batchable
    fused program (solo runs can ride the passthrough variant); round 2
    must stack with zero compiles and sqlite-grade equality to solo."""
    qs = [f"select b, sum(c), count(*) from t where c < {500.0 + i * 7} "
          "group by b" for i in range(3)]
    solo = {q: _sess(server).query(q).rows for q in qs}
    digest, _ = stmtsummary.normalize(qs[0])
    assert batching.family_batchable(digest)
    _drive_round(server, qs)       # round 1: warms the batchable route
    kernels.prewarm_stacked()
    st0 = batching.stats_snapshot()
    miss0 = progcache.stats_snapshot()["misses"]
    entries = _drive_round(server, qs)
    for e, q in zip(entries, qs):
        assert e.error is None, e.error
        assert repr(e.result.rows) == repr(solo[q])
    st = batching.stats_snapshot()
    assert st["stacked_rounds"] == st0["stacked_rounds"] + 1
    assert progcache.stats_snapshot()["misses"] == miss0


# =========================================================================
# attribution: member shares sum to the global truth on both legs
# =========================================================================

def _attribution_drive(server, stack_max, occ=3):
    from tinysql_tpu.ops import profiler
    qs = _variants(occ, lo=9)
    solo = {q: _sess(server).query(q).rows for q in qs}
    kernels.prewarm_stacked()
    boot = _sess(server)
    boot.execute(f"set global tidb_batch_stack_max = {stack_max}")
    boot.execute("set global tidb_device_profile_rate = 1")
    try:
        d0 = dict(kernels.STATS)
        entries = _drive_round(server, qs)
        d1 = dict(kernels.STATS)
    finally:
        boot.execute("set global tidb_device_profile_rate = 0")
        boot.execute("set global tidb_batch_stack_max = 16")
        profiler.reset()
    for e, q in zip(entries, qs):
        assert e.error is None and repr(e.result.rows) == repr(solo[q])
    totals = [e.session.last_query_stats.device_totals()
              for e in entries]
    return d0, d1, totals


def test_device_time_attribution_conserved_stacked(server):
    """Profile rate 1 + a stacked round: the members' occupancy-weighted
    device_s / dispatches shares sum to the global counters' delta —
    the round's measured device time is split, never duplicated or
    dropped (and never lands on the dispatching member alone)."""
    d0, d1, totals = _attribution_drive(server, stack_max=16)
    disp_delta = d1["dispatches"] - d0["dispatches"]
    dev_delta = d1["device_s"] - d0["device_s"]
    assert disp_delta == 1  # THE one stacked dispatch for the round
    assert sum(t.get("dispatches", 0) for t in totals) \
        == pytest.approx(disp_delta)
    assert dev_delta > 0
    assert sum(t.get("device_s", 0.0) for t in totals) \
        == pytest.approx(dev_delta, rel=1e-9)
    # every member carries a non-zero share of the measured time
    assert all(t.get("device_s", 0.0) > 0 for t in totals)
    shares = {round(t["device_s"], 12) for t in totals}
    assert len(shares) == 1  # occupancy-weighted: equal splits
    # transfer counters conserve the same way (ISSUE 16): the round's
    # uploads/downloads are split across members, never duplicated or
    # dropped — the static DF802 pass guarantees every transfer goes
    # through the counted wrappers, THIS asserts the attribution side
    for key in ("h2d_transfers", "h2d_bytes",
                "d2h_transfers", "d2h_bytes"):
        delta = d1[key] - d0[key]
        assert delta > 0, key  # a round moves real data both ways
        assert sum(t.get(key, 0) for t in totals) \
            == pytest.approx(delta, rel=1e-9), key


def test_device_time_attribution_conserved_legacy(server):
    """tidb_batch_stack_max = 0 restores the back-to-back leg — and the
    per-member capture still conserves the sum (the pre-ISSUE-14 skew
    landed the whole round's device_s outside every member scope)."""
    d0, d1, totals = _attribution_drive(server, stack_max=0)
    disp_delta = d1["dispatches"] - d0["dispatches"]
    dev_delta = d1["device_s"] - d0["device_s"]
    assert disp_delta == 3  # one solo replay per member
    assert sum(t.get("dispatches", 0) for t in totals) \
        == pytest.approx(disp_delta)
    assert dev_delta > 0
    assert sum(t.get("device_s", 0.0) for t in totals) \
        == pytest.approx(dev_delta, rel=1e-9)
    st = batching.stats_snapshot()
    assert all(t.get("dispatches") == 1 for t in totals)
    # the legacy leg must conserve transfers too — each member's solo
    # replay owns whole (integer) transfer counts rather than stacked
    # fractional shares, but the sum-to-global-delta contract is shared
    for key in ("h2d_transfers", "h2d_bytes",
                "d2h_transfers", "d2h_bytes"):
        delta = d1[key] - d0[key]
        assert delta > 0, key
        assert sum(t.get(key, 0) for t in totals) \
            == pytest.approx(delta, rel=1e-9), key


# =========================================================================
# degradation ladders
# =========================================================================

def test_layout_mismatch_falls_back_to_legacy_leg(server):
    """A parked member whose param vector no longer matches the group's
    slot layout (defensive: same program key implies same layout, so
    this is sabotage) fails ParamTable.stack — the chunk falls back to
    back-to-back replays, results stay correct, stack_fallbacks counts
    the miss."""
    qs = _variants(2, lo=30)
    solo = {q: _sess(server).query(q).rows for q in qs}
    rnd = batching.BatchRound(stack_max=8)
    rnd.collecting = True
    tok = batching.activate(rnd)
    try:
        for q in qs:
            with pytest.raises(batching.Parked):
                _sess(server).execute_stmt(parse(q)[0], q)
    finally:
        batching.deactivate(tok)
        rnd.collecting = False
    assert rnd.parked_count == 2
    # sabotage member 1's layout: one extra int slot
    p = rnd._parked[1]
    p.params = (np.append(p.params[0], np.int64(7)), p.params[1])
    st0 = batching.stats_snapshot()
    assert rnd.dispatch() == 2
    st = batching.stats_snapshot()
    assert st["stack_fallbacks"] == st0["stack_fallbacks"] + 1
    assert st["stacked_rounds"] == st0["stacked_rounds"]
    assert st["batches"] == st0["batches"] + 1
    rnd.replaying = True
    tok = batching.activate(rnd)
    try:
        for q in qs:
            rows = _sess(server).execute_stmt(parse(q)[0], q).rows
            assert repr(rows) == repr(solo[q])
    finally:
        batching.deactivate(tok)
        rnd.replaying = False


def test_stack_max_zero_restores_legacy_back_to_back(server):
    """The 0 = legacy knob: rounds still coalesce and stay correct, but
    no stacked dispatch forms."""
    qs = _variants(3, lo=14)
    solo = {q: _sess(server).query(q).rows for q in qs}
    boot = _sess(server)
    boot.execute("set global tidb_batch_stack_max = 0")
    try:
        st0 = batching.stats_snapshot()
        entries = _drive_round(server, qs)
        for e, q in zip(entries, qs):
            assert e.error is None and repr(e.result.rows) == repr(solo[q])
        st = batching.stats_snapshot()
        assert st["batches"] == st0["batches"] + 1
        assert st["stacked_rounds"] == st0["stacked_rounds"]
        assert st["replays"] == st0["replays"] + 3
    finally:
        boot.execute("set global tidb_batch_stack_max = 16")


def test_kill_parked_member_mid_stacked_round(server):
    """A KILL delivered while the member sits PARKED (after collect,
    inside the round) aborts it at the replay pre-check; the OTHER
    stacked member still consumes its slice of the one dispatch."""
    from tinysql_tpu.utils.interrupt import QueryKilled
    qs = _variants(3, lo=22)
    solo = {q: _sess(server).query(q).rows for q in qs}
    kernels.prewarm_stacked()
    digest, _ = stmtsummary.normalize(qs[0])
    pool = StatementPool(server.storage)
    victim, other = _sess(server), _sess(server)
    killer = _sess(server)
    group = [
        _Entry(victim, parse(qs[0])[0], qs[0], digest, True),
        _Entry(other, parse(qs[1])[0], qs[1], digest, True),
        # the kill lands during collect of member 3 — AFTER both parks
        _Entry(killer, parse(f"kill query {victim.conn_id}")[0],
               "kill", digest, True),
    ]
    st0 = batching.stats_snapshot()
    pool._run_batch(group)
    st = batching.stats_snapshot()
    assert group[2].error is None            # the KILL itself succeeded
    assert isinstance(group[0].error, QueryKilled), group[0].error
    assert group[1].error is None
    assert repr(group[1].result.rows) == repr(solo[qs[1]])
    # both members rode ONE stacked dispatch; the killed member's
    # stored slice is simply never consumed
    assert st["stacked_rounds"] == st0["stacked_rounds"] + 1
    assert st["stacked_occupancy_sum"] == st0["stacked_occupancy_sum"] + 2
    assert st["replays"] == st0["replays"] + 1


def test_duplicate_identical_statements_in_one_stacked_round(server):
    """IDENTICAL statements (same digest AND literals) stack into one
    dispatch; each member consumes its own stored slice."""
    q = _variants(1, lo=17)[0]
    ref = _sess(server).query(q).rows
    kernels.prewarm_stacked()
    st0 = batching.stats_snapshot()
    entries = _drive_round(server, [q] * 4)
    for e in entries:
        assert e.error is None and repr(e.result.rows) == repr(ref)
    st = batching.stats_snapshot()
    assert st["stacked_rounds"] == st0["stacked_rounds"] + 1
    assert st["stacked_occupancy_sum"] == st0["stacked_occupancy_sum"] + 4
    assert st["replays"] == st0["replays"] + 4
    assert st["fallbacks"] == st0["fallbacks"]


# =========================================================================
# primitives
# =========================================================================

def test_paramtable_stack_contract():
    a = (np.array([1, 2], dtype=np.int64), np.array([0.5]))
    b = (np.array([3, 4], dtype=np.int64), np.array([0.7]))
    pi, pf = ParamTable.stack([a, b], 4)
    assert pi.shape == (4, 2) and pf.shape == (4, 1)
    assert pi[1].tolist() == [3, 4]
    # padding rows repeat member 0 (inert)
    assert pi[2].tolist() == pi[0].tolist() == [1, 2]
    assert pf[3].tolist() == [0.5]
    # layout mismatch is a loud ValueError (the fallback trigger)
    with pytest.raises(ValueError):
        ParamTable.stack([a, (np.array([1], dtype=np.int64),
                              np.array([0.7]))])
    # bucket below occupancy is refused
    with pytest.raises(ValueError):
        ParamTable.stack([a, b], 1)
    # real ParamTables stack too
    t = ParamTable()
    t.add_int(9)
    t.add_int(8)
    t.add_real(0.25)
    pi, pf = ParamTable.stack([t, a], 2)
    assert pi[0].tolist() == [9, 8] and pf[0].tolist() == [0.25]


def test_stack_sysvar_validation(server):
    s = _sess(server)
    from tinysql_tpu.session.session import SessionError
    with pytest.raises(SessionError):
        s.execute("set global tidb_batch_stack_max = -1")
    with pytest.raises(SessionError):
        s.execute("set global tidb_batch_stack_max = 1.5")
    s.execute("set global tidb_batch_stack_max = 16")


def test_stacked_metrics_render(server):
    from tinysql_tpu.obs.metrics import render_prometheus
    text = render_prometheus()
    assert "tinysql_batch_stacked_rounds_total" in text
    assert "tinysql_batch_stacked_occupancy_sum" in text

"""qlint self-tests: every pass must fire on its known-bad fixture, the
CLI must exit non-zero on each fixture, and the TREE must be lint-clean —
this file is the local mirror of the CI `tools/lint.py --strict` gate."""
import json
import os
import subprocess
import sys
import threading

import pytest

from tinysql_tpu.analysis import (gather_sources, lint_concurrency,
                                  lint_device_flow, lint_lock_discipline,
                                  lint_obs_discipline, lint_trace_safety,
                                  thread_roots)
from tinysql_tpu.analysis.diag import SourceFile
from tinysql_tpu.analysis.plan_device import (PlanDeviceError, check_plan,
                                              check_explain_consistency,
                                              verify_plan)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "lint_fixtures")
LINT = os.path.join(REPO, "tools", "lint.py")


def _rules(diags):
    return {d.rule for d in diags}


# ---- pass 1: trace safety ----------------------------------------------

def test_trace_fixture_fires_every_rule():
    sf = SourceFile(os.path.join(FIXDIR, "bad_trace.py"))
    got = _rules(lint_trace_safety(sf))
    assert {"TS101", "TS102", "TS103", "TS104", "TS105"} <= got


def test_pipeline_fixture_fires_ts106():
    sf = SourceFile(os.path.join(FIXDIR, "bad_pipeline.py"))
    got = [d for d in lint_trace_safety(sf) if d.rule == "TS106"]
    # np.asarray-over-device, block_until_ready, d2h, int() coercion
    assert len(got) == 4, [d.format() for d in got]


def test_pipeline_clean_stage_not_flagged(tmp_path):
    # uploads are the stage's JOB; np over host values stays legal, and a
    # function not passed to BlockPipeline is out of scope entirely
    src = ("import numpy as np\n\n\n"
           "def stage(item):\n"
           "    pad = np.zeros(16)\n"
           "    pad[: len(item)] = item\n"
           "    return jn.asarray(pad)\n\n\n"
           "def not_a_stage(dev):\n"
           "    return np.asarray(jn.asarray(dev))\n\n\n"
           "pipe = BlockPipeline(stage, [1], depth=2)\n")
    p = tmp_path / "ok_stage.py"
    p.write_text(src)
    assert lint_trace_safety(SourceFile(str(p))) == []


def test_literal_fixture_fires_ts107():
    sf = SourceFile(os.path.join(FIXDIR, "bad_literal.py"))
    got = [d for d in lint_trace_safety(sf) if d.rule == "TS107"]
    # cval (direct bake) + threshold (transitively derived) — and ONLY
    # in build_const: the ParamTable/default-arg form and the host
    # helper stay clean
    assert len(got) == 2, [d.format() for d in got]
    assert {"cval", "threshold"} == {d.message.split("`")[1] for d in got}
    assert all("const_fn" in d.message for d in got)


def test_vmap_fixture_fires_through_alias_and_partial():
    """ISSUE 14: vmapped (stacked-batch) kernels are traced regions even
    when reached through an assignment alias or functools.partial."""
    sf = SourceFile(os.path.join(FIXDIR, "bad_vmap.py"))
    diags = lint_trace_safety(sf)
    by_rule = {}
    for d in diags:
        by_rule.setdefault(d.rule, []).append(d)
    # kern (via `fn = kern` then vmap(fn)): control flow + numpy sync
    assert any("kern" in d.message for d in by_rule.get("TS103", [])), \
        [d.format() for d in diags]
    assert any("kern" in d.message for d in by_rule.get("TS101", []))
    # pkern (via vmap(partial(pkern))): baked query constant
    assert any("pkern" in d.message for d in by_rule.get("TS107", []))
    # the masked/clean kernel stays silent
    assert not any("ckern" in d.message for d in diags)


def test_vmap_bare_alias_chain_resolved(tmp_path):
    # a two-hop alias chain still roots the def; an unrelated def with
    # the hazard but no jit/vmap reachability stays out of scope
    src = ("import numpy as np\n\n\n"
           "def kern(cols, pr):\n"
           "    return np.asarray(cols[0])\n\n\n"
           "def other(cols, pr):\n"
           "    return np.asarray(cols[0])\n\n\n"
           "a = kern\n"
           "b = a\n"
           "w = vmap(b, in_axes=(None, 0))\n")
    p = tmp_path / "alias_chain.py"
    p.write_text(src)
    diags = lint_trace_safety(SourceFile(str(p)))
    assert any(d.rule == "TS101" and "kern" in d.message for d in diags)
    assert not any("other" in d.message for d in diags)


def test_ts107_default_param_capture_not_flagged(tmp_path):
    # the slot-plumbing idiom: value-derived names bound as DEFAULT
    # parameters are runtime-operand plumbing, not a bake
    src = ("def build(e, pt, jn):\n"
           "    slot = pt.add_int(e.value)\n"
           "    def fn(cols, params, slot=slot):\n"
           "        return params[0][slot]\n"
           "    return fn\n")
    p = tmp_path / "ok_slot.py"
    p.write_text(src)
    assert lint_trace_safety(SourceFile(str(p))) == []


def test_trace_suppression_requires_justification():
    sf = SourceFile(os.path.join(FIXDIR, "bad_suppress.py"))
    # the unjustified disable does NOT silence TS101 and raises QL001
    assert "TS101" in _rules(lint_trace_safety(sf))
    assert "QL001" in _rules(sf.check_suppression_syntax())


def test_trace_justified_suppression_silences(tmp_path):
    src = ("import numpy as np\n\n\n"
           "def emit(args):\n"
           "    return np.asarray(args[0])"
           "  # qlint: disable=TS101 -- fixture: pretend post-download\n")
    p = tmp_path / "ok.py"
    p.write_text(src)
    sf = SourceFile(str(p))
    assert lint_trace_safety(sf) == []
    assert sf.check_suppression_syntax() == []


def test_trace_host_code_not_flagged(tmp_path):
    # np over host values OUTSIDE traced regions (and np over closure
    # constants inside them) is the legitimate post-download idiom
    src = ("import numpy as np\n\n\n"
           "def materialize(dev):\n"
           "    return np.asarray(dev)\n\n\n"
           "def emit(args):\n"
           "    pad = np.zeros(4)\n"     # host constant: fine
           "    return args[0], pad\n")
    p = tmp_path / "host.py"
    p.write_text(src)
    assert lint_trace_safety(SourceFile(str(p))) == []


# ---- pass 3: lock discipline -------------------------------------------

def test_lock_fixture_fires_every_rule():
    sf = SourceFile(os.path.join(FIXDIR, "bad_locks.py"))
    got = _rules(lint_lock_discipline(sf))
    assert {"LD301", "LD302", "LD303"} <= got


def test_lock_clean_class_not_flagged(tmp_path):
    src = ("import threading\n\n\n"
           "class Ok:\n"
           "    def __init__(self):\n"
           "        self._mu = threading.Lock()\n"
           "        self._n = 0\n\n"
           "    def bump(self):\n"
           "        with self._mu:\n"
           "            self._n += 1\n\n"
           "    def get(self):\n"
           "        with self._mu:\n"
           "            return self._n\n")
    p = tmp_path / "ok_locks.py"
    p.write_text(src)
    assert lint_lock_discipline(SourceFile(str(p))) == []


# ---- pass 6: whole-program concurrency (CC7xx) --------------------------

def _conc(*names):
    return lint_concurrency([SourceFile(os.path.join(FIXDIR, n))
                             for n in names])


def test_race_fixture_fires_cc701():
    diags = _conc("bad_race.py")
    got = [d for d in diags if d.rule == "CC701"]
    # the inconsistently guarded module dict (hot path only — the
    # locked cold path is not the actionable site) + both unguarded
    # writes to the instance attr; the consistently guarded
    # Worker._state stays silent
    assert len(got) == 3, [d.format() for d in diags]
    assert any("SHARED" in d.message for d in got)
    assert sum("Worker._n" in d.message for d in got) == 2
    assert not any("_state" in d.message for d in got)


def test_lockorder_fixture_fires_cc702():
    diags = _conc("bad_lockorder.py")
    assert [d.rule for d in diags] == ["CC702"], \
        [d.format() for d in diags]
    assert "_a" in diags[0].message and "_b" in diags[0].message


def test_blocking_fixture_fires_cc703():
    diags = _conc("bad_blocking.py")
    got = [d for d in diags if d.rule == "CC703"]
    assert len(got) == 4, [d.format() for d in diags]
    reasons = "\n".join(d.message for d in got)
    for probe in ("queue.get", "time.sleep", "block_until_ready",
                  "Thread.join"):
        assert probe in reasons, reasons


def test_ctxhop_fixture_fires_cc704_only_on_bare_spawn():
    diags = _conc("bad_ctxhop.py")
    got = [d for d in diags if d.rule == "CC704"]
    # the bare Thread(target=self._worker) spawn fires; the
    # copy_context + ctx.run spawn in OkObs stays clean
    assert len(got) == 1, [d.format() for d in diags]
    assert got[0].line < 20, got[0].format()  # in Obs, not OkObs


def test_cross_module_race_requires_whole_program():
    # each half alone is clean; only the UNION of both files reveals
    # the worker thread in one module mutating the registry owned by
    # the other — the property per-file passes (LD3xx) cannot have
    assert _conc("xmod_race_state.py") == []
    assert _conc("xmod_race_worker.py") == []
    both = _conc("xmod_race_state.py", "xmod_race_worker.py")
    got = [d for d in both if d.rule == "CC701"]
    assert len(got) == 3, [d.format() for d in both]
    assert {os.path.basename(d.path) for d in got} \
        == {"xmod_race_state.py", "xmod_race_worker.py"}


def test_conc_suppression_respected(tmp_path):
    src = ("import threading\n\n"
           "STATE = {}\n\n\n"
           "def worker():\n"
           "    STATE['x'] = 1"
           "  # qlint: disable=CC701 -- fixture: pretend init-only\n\n\n"
           "def spin():\n"
           "    threading.Thread(target=worker).start()\n\n\n"
           "def main_write():\n"
           "    STATE['y'] = 2"
           "  # qlint: disable=CC701 -- fixture: pretend init-only\n")
    p = tmp_path / "suppressed.py"
    p.write_text(src)
    assert lint_concurrency([SourceFile(str(p))]) == []


def test_thread_root_discovery_covers_known_loops():
    srcs = gather_sources(os.path.join(REPO, "tinysql_tpu"))
    entries = {q.split(":")[-1] for q in thread_roots(srcs)}
    for loop in ("StatementPool._worker_loop", "Sampler._loop",
                 "PrewarmWorker._loop", "BlockPipeline._run",
                 "CopClient._run_task", "ClientConn.run",
                 "Server._accept_loop", "ConprofSampler._loop",
                 # the C10k event loop (ISSUE 15): server/aio.py
                 "_Loop._run"):
        assert loop in entries, sorted(entries)


def test_thread_spawn_names_classify_to_conprof_roles():
    """The thread-name sweep contract (ISSUE 13): every discovered
    spawn site hands its thread a stable ``name=`` that the conprof
    role vocabulary classifies — so continuous_profiling, race-stress
    contention reports, and py-spy output all read the same words.  A
    new spawn site with an out-of-vocabulary (or missing) name fails
    here."""
    from tinysql_tpu.obs.conprof import classify
    for spawn_name, role in (
            ("stmt-pool-0", "pool-worker"),      # StatementPool workers
            ("conn-17", "conn"),                 # ClientConn.run threads
            ("mysql-accept", "accept"),          # Server._accept_loop
            ("aio-loop-0", "aio"),               # aio.py event loops
            ("devpipe-stage", "devpipe"),        # BlockPipeline._run
            ("metrics-sampler", "tsring"),       # tsring Sampler._loop
            ("conprof-sampler", "conprof"),      # ConprofSampler._loop
            ("auto-prewarm", "prewarm"),         # PrewarmWorker._loop
            ("distsql-cop_0", "distsql"),        # CopClient task pool
            ("status-http", "http"),             # StatusServer
            ("domain-reload-s1", "domain"),      # Domain ticker
            ("ddl-owner-s1", "ddl"),             # Domain owner loop
            ("range-gc_0", "kv"),                # kv/range_task pools
            ("kv-commit_0", "kv"),               # 2PC commit pool
            ("kv-lookup_0", "kv"),               # index lookup pool
            ("kv-schema_0", "kv"),               # infoschema load pool
            ("MainThread", "main")):
        assert classify(spawn_name) == role, spawn_name
    # the spawn sites actually USE those names: grep the source for the
    # literal name= fragments so a rename cannot drift from this table
    fragments = {
        'name=f"stmt-pool-': "tinysql_tpu/server/pool.py",
        'name=f"conn-': "tinysql_tpu/server/server.py",
        'name="mysql-accept"': "tinysql_tpu/server/server.py",
        'name=f"aio-loop-': "tinysql_tpu/server/aio.py",
        'name="devpipe-stage"': "tinysql_tpu/executor/devpipe.py",
        'name="metrics-sampler"': "tinysql_tpu/obs/tsring.py",
        'name="conprof-sampler"': "tinysql_tpu/obs/conprof.py",
        'name="auto-prewarm"': "tinysql_tpu/session/prewarm.py",
        'thread_name_prefix="distsql-cop"': "tinysql_tpu/distsql/client.py",
        'name="status-http"': "tinysql_tpu/server/http_status.py",
    }
    for frag, relpath in fragments.items():
        with open(os.path.join(REPO, relpath)) as fh:
            assert frag in fh.read(), (frag, relpath)


def test_tree_concurrency_clean():
    # the whole-package CC7xx gate (CI runs the same via --strict);
    # every finding on the tree is either fixed or suppressed with a
    # justification
    srcs = gather_sources(os.path.join(REPO, "tinysql_tpu"))
    diags = lint_concurrency(srcs)
    assert not diags, "\n".join(d.format() for d in diags)


# ---- the dynamic verifier's building blocks (utils/racestress) ----------

def test_racestress_lock_and_audit_dict():
    from tinysql_tpu.utils import racestress as rs
    lk = rs.InstrumentedLock(threading.Lock(), "test-site-a")
    with lk:
        assert lk.held_by_current()
    assert not lk.held_by_current()
    d = rs.AuditDict({"n": 0}, lk, "test.state")
    base = rs.report()["unguarded_write_count"]
    with lk:
        d["n"] = 1  # guarded: silent
    assert rs.report()["unguarded_write_count"] == base
    d["n"] = 2      # unguarded: one report, mutation still lands
    rep = rs.report()
    assert rep["unguarded_write_count"] == base + 1
    assert d["n"] == 2
    assert rep["unguarded_writes"][-1]["state"] == "test.state"


def test_racestress_dynamic_lock_order_cycle():
    from tinysql_tpu.utils import racestress as rs
    a = rs.InstrumentedLock(threading.Lock(), "test-site-x")
    b = rs.InstrumentedLock(threading.Lock(), "test-site-y")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    cycles = rs.report()["lock_order_cycles"]
    assert any({"test-site-x", "test-site-y"} <= set(c)
               for c in cycles), cycles


def test_racestress_condition_compatible():
    # Condition(InstrumentedLock) must wait/notify correctly — the
    # statement pool's _cv rides exactly this shape under stress mode
    from tinysql_tpu.utils import racestress as rs
    lk = rs.InstrumentedLock(threading.Lock(), "test-site-cv")
    cv = threading.Condition(lk)
    hits = []

    def waker():
        with cv:
            hits.append(1)
            cv.notify()

    t = threading.Thread(target=waker)
    with cv:
        t.start()
        assert cv.wait(timeout=5.0)
    t.join(timeout=5.0)
    assert hits == [1]


# ---- pass 7: whole-program device dataflow (DF8xx) ----------------------

def _devflow(*names):
    return lint_device_flow([SourceFile(os.path.join(FIXDIR, n))
                             for n in names])


def test_sync_fixture_fires_df801_in_hot_region_only():
    diags = _devflow("bad_sync.py")
    got = [d for d in diags if d.rule == "DF801"]
    # np.asarray, float(), .tolist() over the device value inside the
    # hot next() loop; CleanExec's counted d2h and cold_report's raw
    # sync OUTSIDE the hot set both stay silent
    assert len(got) == 3, [d.format() for d in diags]
    assert all("HotExec.next" in d.message for d in got)
    assert not any("cold_report" in d.message for d in diags)


def test_transfer_fixture_fires_df802():
    diags = _devflow("bad_transfer.py")
    got = [d for d in diags if d.rule == "DF802"]
    # jnp.asarray + jax.device_put outside ops/kernels; the
    # kernels.h2d twin stays clean
    assert len(got) == 2, [d.format() for d in diags]
    assert all("upload_raw" in d.message for d in got)


def test_key_fixture_fires_df803():
    diags = _devflow("bad_key.py")
    assert [d.rule for d in diags] == ["DF803"], \
        [d.format() for d in diags]
    assert "compile_for_literal" in diags[0].message
    # the kernels.bucket-laundered twin is the sanctioned idiom
    assert not any("compile_bucketed" in d.message for d in diags)


def test_escape_fixture_fires_df804():
    diags = _devflow("bad_escape.py")
    got = [d for d in diags if d.rule == "DF804"]
    # keyed store + append into module-level containers; the
    # function-local dict in local_ok stays clean
    assert len(got) == 2, [d.format() for d in diags]
    assert all("remember" in d.message for d in got)


def test_mesh_fixture_fires_df805():
    diags = _devflow("bad_mesh.py")
    got = [d for d in diags if d.rule == "DF805"]
    # the raw shard_map import + the unwired collective; scatter_clean's
    # psum is sanctioned by its dist.shard_map_fn wiring
    assert len(got) == 2, [d.format() for d in diags]
    assert any("all_reduce_raw" in d.message for d in got)
    assert not any("scatter_clean" in d.message for d in got)


def test_mesh_fixture_fires_df806():
    diags = _devflow("bad_mesh.py")
    got = [d for d in diags if d.rule == "DF806"]
    # np.sum + .item() inside scatter_reduce's traced body; the pure-lax
    # body in scatter_clean stays silent
    assert len(got) == 2, [d.format() for d in diags]
    assert all("scatter_reduce" in d.message for d in got)
    assert not any("scatter_clean" in d.message for d in got)


def test_mesh_fixture_fires_df807():
    diags = _devflow("bad_mesh.py")
    got = [d for d in diags if d.rule == "DF807"]
    # jax.device_count() minted into the key; the
    # dist.shard_bucket/mesh_shards twin is the sanctioned launder
    assert len(got) == 1, [d.format() for d in diags]
    assert "compile_mesh_raw" in got[0].message \
        or "mesh-shape" in got[0].message
    assert not any("compile_mesh_bucketed" in d.message for d in got)


def test_cross_module_sync_requires_whole_program():
    # each half alone is clean: the helper's raw sync is only a bug
    # once the OTHER module's next() loop makes `pull` dispatch-hot —
    # the property no per-file pass can have
    assert _devflow("xmod_flow_helper.py") == []
    assert _devflow("xmod_flow_exec.py") == []
    both = _devflow("xmod_flow_helper.py", "xmod_flow_exec.py")
    got = [d for d in both if d.rule == "DF801"]
    assert len(got) == 1, [d.format() for d in both]
    # the diagnostic lands in the helper — the module that LOOKS clean
    assert os.path.basename(got[0].path) == "xmod_flow_helper.py"


def test_devflow_suppression_respected(tmp_path):
    src = ("import numpy as np\n\n"
           "from tinysql_tpu.ops import kernels\n\n\n"
           "class Exec:\n"
           "    def next(self):\n"
           "        dev = kernels.h2d(np.arange(4))\n"
           "        return np.asarray(dev)"
           "  # qlint: disable=DF801 -- fixture: cold fallback path\n")
    p = tmp_path / "suppressed_flow.py"
    p.write_text(src)
    assert lint_device_flow([SourceFile(str(p))]) == []


def test_tree_device_flow_clean():
    # the whole-package DF8xx gate (CI runs the same via --strict);
    # every finding on the tree is either fixed or suppressed with a
    # justification
    srcs = gather_sources(os.path.join(REPO, "tinysql_tpu"))
    diags = lint_device_flow(srcs)
    assert not diags, "\n".join(d.format() for d in diags)


# ---- the dynamic verifier's building blocks (utils/xferaudit) -----------

def test_xferaudit_classify_and_reenter():
    from tinysql_tpu.utils import xferaudit as xa
    # this test file lives outside tinysql_tpu/ -> harness attribution
    attr, site = xa._classify()
    assert attr == "harness", (attr, site)
    assert "test_lint.py" in site
    # the re-entrancy guard: wrappers record only at depth 0
    assert xa._depth() == 0
    with xa._reenter():
        assert xa._depth() == 1
        with xa._reenter():
            assert xa._depth() == 2
    assert xa._depth() == 0


def test_xferaudit_divergence_verdict():
    from tinysql_tpu.utils import xferaudit as xa
    snap = ({k: dict(v) for k, v in xa._TOTALS.items()},
            list(xa._EVENTS), dict(xa._COUNTED), dict(xa._STATE))
    try:
        xa._STATE["attached"] = True  # unit test: skip the stats shadow
        xa._record("h2d", 64)         # harness-attributed: benign
        rep = xa.report()
        assert rep["observed"]["h2d"]["harness"] >= 1
        assert not rep["divergence"], rep["divergence_reasons"]
        # a raw in-engine download is exactly what the verifier exists
        # to catch: one engine event must flip the verdict
        with xa._MU:
            xa._TOTALS["d2h"]["engine"] += 1
        rep = xa.report()
        assert rep["divergence"]
        assert any("uncounted engine" in r
                   for r in rep["divergence_reasons"]), rep
        # and a sanctioned event with no counter bump is the OTHER
        # divergence mode (a wrapper that forgot its stats_add)
        with xa._MU:
            xa._TOTALS["d2h"]["engine"] -= 1
            xa._TOTALS["h2d"]["sanctioned"] += 1
        rep = xa.report()
        assert rep["divergence"]
        assert any("h2d_transfers counter" in r
                   for r in rep["divergence_reasons"]), rep
    finally:
        totals, events, counted, state = snap
        with xa._MU:
            for k in xa._TOTALS:
                xa._TOTALS[k] = totals[k]
            xa._EVENTS[:] = events
            xa._COUNTED.update(counted)
            xa._STATE.update(state)


# ---- pass 2: plan-device invariants ------------------------------------

@pytest.fixture()
def planned():
    from tinysql_tpu.utils.testkit import TestKit
    tk = TestKit()
    tk.must_exec("create database pd")
    tk.must_exec("use pd")
    tk.must_exec("create table t (a int primary key, b int, c double)")
    tk.must_exec("insert into t values (1,1,0.5),(2,1,1.5),(3,2,2.5)")
    tk.must_exec("set @@tidb_use_tpu = 1")
    tk.must_exec("set @@tidb_tpu_min_rows = 0")

    def plan(sql):
        from tinysql_tpu.parser import parse
        from tinysql_tpu.planner.builder import PlanBuilder
        s = tk.session
        try:
            return s._optimize(PlanBuilder(s).build_select(parse(sql)[0]),
                               True)
        finally:
            s._pinned_is = None
    return plan


def _find(p, op_name):
    if p.op_name() == op_name:
        return p
    for c in p.children:
        got = _find(c, op_name)
        if got is not None:
            return got
    return None


def test_placed_plan_is_clean(planned):
    phys = planned("select b, sum(a) from t group by b order by b")
    assert check_plan(phys) == []
    assert check_explain_consistency(phys) == []
    verify_plan(phys)  # must not raise


def test_pd201_inadmissible_placement(planned):
    phys = planned("select count(distinct b) from t")
    agg = _find(phys, "HashAgg")
    assert agg is not None and not agg.use_tpu
    agg.use_tpu = True  # corrupt: distinct agg has no device kernel
    assert "PD201" in _rules(check_plan(phys))
    with pytest.raises(PlanDeviceError):
        verify_plan(phys)


def test_pd202_placement_without_estimate(planned):
    phys = planned("select b, sum(a) from t group by b")
    agg = _find(phys, "HashAgg")
    assert agg.use_tpu
    agg.has_estimate = False  # corrupt: placement before derive_stats
    assert "PD202" in _rules(check_plan(phys))


def test_pd203_malformed_mesh_strategy(planned):
    phys = planned("select t1.b from t t1 join t t2 on t1.b = t2.b")
    join = _find(phys, "HashJoin")
    assert join is not None
    join.use_tpu = True
    join.mesh_strategy = "bogus"  # corrupt
    got = _rules(check_plan(phys))
    assert "PD203" in got


def test_pd204_placement_on_unloweable_op(planned):
    phys = planned("select a from t limit 2")
    lim = _find(phys, "Limit")
    assert lim is not None
    lim.use_tpu = True  # corrupt: Limit has no device lowering
    assert "PD204" in _rules(check_plan(phys))


def test_pd205_explain_drift(planned, monkeypatch):
    from tinysql_tpu.planner import explain
    phys = planned("select b, sum(a) from t group by b")
    assert _find(phys, "HashAgg").use_tpu
    monkeypatch.setattr(explain, "_task", lambda p: "root")
    assert "PD205" in _rules(check_explain_consistency(phys))


def test_runtime_verifier_sysvar(planned):
    # tidb_qlint_verify=1 verifies every statement's plan inline; a
    # healthy plan must still execute
    from tinysql_tpu.utils.testkit import TestKit
    tk = TestKit()
    tk.must_exec("create database rv")
    tk.must_exec("use rv")
    tk.must_exec("create table r (a int primary key, b int)")
    tk.must_exec("insert into r values (1,2),(2,2)")
    tk.must_exec("set @@tidb_qlint_verify = 1")
    tk.must_exec("set @@tidb_tpu_min_rows = 0")
    assert tk.must_query(
        "select b, count(*) from r group by b").as_str() == [["2", "2"]]


# ---- the tree itself is lint-clean -------------------------------------

# ONE definition of the lock-discipline scope: the CLI's (tools/lint.py)
# — a module added there is automatically enforced by the tree test too
def _lint_cli_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location("tinysql_lint_cli", LINT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LOCK_SCOPE = _lint_cli_module().LOCK_SCOPE


def test_stats_fixture_fires_obs_rules():
    sf = SourceFile(os.path.join(FIXDIR, "bad_stats.py"))
    diags = lint_obs_discipline(sf)
    assert [d.rule for d in diags].count("OB401") == 3, \
        [d.format() for d in diags]
    assert [d.rule for d in diags].count("OB402") == 2, \
        [d.format() for d in diags]


def test_summary_fixture_fires_ob403():
    sf = SourceFile(os.path.join(FIXDIR, "bad_summary.py"))
    diags = lint_obs_discipline(sf)
    assert [d.rule for d in diags].count("OB403") == 6, \
        [d.format() for d in diags]


def test_summary_writer_modules_exempt(tmp_path):
    # the session statement-close hook and the store's own module are
    # THE designated writers
    for name in ("session.py", "stmtsummary.py"):
        p = tmp_path / name
        p.write_text("from tinysql_tpu.obs import stmtsummary\n"
                     "stmtsummary.ingest(sql='select 1')\n")
        assert lint_obs_discipline(SourceFile(str(p))) == [], name


def test_summary_reads_not_flagged(tmp_path):
    p = tmp_path / "reader.py"
    p.write_text("from tinysql_tpu.obs import stmtsummary\n"
                 "rows = stmtsummary.rows()\n"
                 "snap = stmtsummary.snapshot()\n"
                 "h = stmtsummary.histogram_snapshot()\n"
                 "d, t = stmtsummary.normalize('select 1')\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_obs_owning_modules_exempt(tmp_path):
    # kernels.py ITSELF may write STATS (it owns the accessors); a file
    # of the same name elsewhere is exempt by basename — the rule's
    # contract is "outside the owning module"
    p = tmp_path / "kernels.py"
    p.write_text("STATS = {}\nSTATS['dispatches'] = 1\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_owning_modules_not_exempt_from_ob403(tmp_path):
    # the STATS ownership exemption must not cover the summary store:
    # kernels/progcache are exactly the modules tempted to push
    # counters at it
    p = tmp_path / "kernels.py"
    p.write_text("from tinysql_tpu.obs import stmtsummary\n"
                 "stmtsummary.ingest(sql='select 1')\n")
    diags = lint_obs_discipline(SourceFile(str(p)))
    assert [d.rule for d in diags] == ["OB403"], diags


def test_ob403_ignores_unrelated_ingest_and_store(tmp_path):
    # a local helper named `ingest` or an unrelated STORE global must
    # not trip the rule — only names provably from stmtsummary qualify
    p = tmp_path / "loader.py"
    p.write_text("STORE = {}\n"
                 "def ingest(batch):\n    return batch\n"
                 "ingest([1])\n"
                 "STORE.clear()\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_devtime_fixture_fires_ob405():
    sf = SourceFile(os.path.join(FIXDIR, "bad_devtime.py"))
    diags = lint_obs_discipline(sf)
    got = [d for d in diags if d.rule == "OB405"]
    # the two laundered device-time writes + the fake compile wall; the
    # ordinary-counter accessors and the reads stay silent
    assert len(got) == 3, [d.format() for d in diags]
    assert all("device" in d.message or "compile" in d.message
               for d in got)


def test_ob405_owning_modules_exempt(tmp_path):
    # kernels/profiler/progcache own the measured walls; a same-named
    # file elsewhere is exempt by basename like OB401's contract
    for name in ("kernels.py", "profiler.py", "progcache.py"):
        p = tmp_path / name
        p.write_text("def stats_add(k, n):\n    pass\n"
                     "stats_add('device_s', 0.5)\n")
        assert lint_obs_discipline(SourceFile(str(p))) == [], name


def test_ob405_other_keys_silent(tmp_path):
    # the rule polices the device-time KEYS, not the accessors
    p = tmp_path / "elsewhere.py"
    p.write_text("from tinysql_tpu.ops import kernels\n"
                 "kernels.stats_add('dispatches', 1)\n"
                 "kernels.stats_add('h2d_bytes', 64)\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_conprof_fixture_fires_ob406():
    sf = SourceFile(os.path.join(FIXDIR, "bad_conprof.py"))
    diags = lint_obs_discipline(sf)
    got = [d for d in diags if d.rule == "OB406"]
    # 4 laundered cpu-key writes + 3 store mutations; the reads and
    # the unrelated local reset/PROF stay silent
    assert len(got) == 7, [d.format() for d in diags]
    assert sum(1 for d in got if "cpu" in d.message) == 4
    assert sum(1 for d in got if "store write" in d.message) == 3


def test_ob406_owning_module_exempt(tmp_path):
    # obs/conprof.py owns the fold/attribution state; a same-named file
    # is exempt by basename like the OB401/OB405 contracts
    p = tmp_path / "conprof.py"
    p.write_text("def attribute(qobs, dt):\n"
                 "    qobs.add_counter('cpu_s', dt)\n"
                 "    qobs.add_counter('cpu_samples', 1)\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_ob406_reads_and_unrelated_names_silent(tmp_path):
    # reads are what the benches/mem-tables do, and an unrelated
    # sample_once/reset (no provable conprof import) is not conprof
    p = tmp_path / "elsewhere.py"
    p.write_text("from tinysql_tpu.obs import conprof\n"
                 "rows = conprof.rows()\n"
                 "text = conprof.collapsed(window_s=60)\n"
                 "stats = conprof.stats_snapshot()\n"
                 "class Ring:\n"
                 "    def sample_once(self):\n"
                 "        pass\n"
                 "r = Ring()\n"
                 "r.sample_once()\n"
                 "def reset():\n"
                 "    pass\n"
                 "reset()\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_spantotals_fixture_fires_ob408():
    sf = SourceFile(os.path.join(FIXDIR, "bad_spantotals.py"))
    diags = lint_obs_discipline(sf)
    # 3 subscript writes + 2 mutating calls + 2 calls of the private
    # writer; reads, totals() and the look-alike class stay silent
    assert sorted(d.line for d in diags) == [9, 10, 11, 12, 13, 14, 15], \
        [d.format() for d in diags]
    assert {d.rule for d in diags} == {"OB408"}


def test_ob408_owning_module_exempt_and_tree_clean(tmp_path):
    # obs/trace.py owns the table: a same-named file is exempt by
    # basename, and it is the only writer in the package
    p = tmp_path / "trace.py"
    p.write_text("from tinysql_tpu.obs.trace import _TOTALS\n"
                 "_TOTALS['x'] = [1, 0.0, 0.0, 0.0]\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []
    pkg = os.path.join(REPO, "tinysql_tpu")
    for where, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                diags = lint_obs_discipline(
                    SourceFile(os.path.join(where, f)))
                assert not [d for d in diags if d.rule == "OB408"], f


def test_memprof_fixture_fires_ob407():
    sf = SourceFile(os.path.join(FIXDIR, "bad_memprof.py"))
    diags = lint_obs_discipline(sf)
    got = [d for d in diags if d.rule == "OB407"]
    # 5 laundered memory-key writes + 3 store mutations; the reads and
    # the unrelated local reset/PROF stay silent
    assert len(got) == 8, [d.format() for d in diags]
    assert sum(1 for d in got if "memory counter" in d.message) == 5
    assert sum(1 for d in got if "store write" in d.message) == 3
    # and nothing else fires: the fixture is OB407-pure
    assert {d.rule for d in diags} == {"OB407"}, \
        [d.format() for d in diags]


def test_ob407_owning_module_exempt(tmp_path):
    # obs/memprof.py owns the fold/attribution state; a same-named file
    # is exempt by basename like the OB401/OB405/OB406 contracts
    p = tmp_path / "memprof.py"
    p.write_text("def attribute(qobs, kb):\n"
                 "    qobs.add_counter('heap_kb', kb)\n"
                 "    qobs.hwm_counter('heap_peak_kb', kb)\n"
                 "    qobs.hwm_counter('hbm_bytes', kb * 1024)\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_ob407_reads_and_unrelated_names_silent(tmp_path):
    # reads are what the benches/mem-tables do, and an unrelated
    # sample_once/reset (no provable memprof import) is not memprof
    p = tmp_path / "elsewhere.py"
    p.write_text("from tinysql_tpu.obs import memprof\n"
                 "rows = memprof.memory_usage_rows()\n"
                 "text = memprof.collapsed(window_s=60)\n"
                 "census = memprof.hbm_census()\n"
                 "class Ring:\n"
                 "    def sample_once(self):\n"
                 "        pass\n"
                 "r = Ring()\n"
                 "r.sample_once()\n"
                 "def reset():\n"
                 "    pass\n"
                 "reset()\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_metric_fixture_fires_ob404():
    sf = SourceFile(os.path.join(FIXDIR, "bad_metric.py"))
    diags = lint_obs_discipline(sf)
    got = [d for d in diags if d.rule == "OB404"]
    # the unregistered source name, the typo'd source key, the typo'd
    # series() read — the registered key and logger names stay silent
    assert len(got) == 3, [d.format() for d in got]
    assert all("tinysql_" in d.message for d in got)


def test_ob404_registered_names_and_fstrings_clean(tmp_path):
    p = tmp_path / "sampler_user.py"
    p.write_text(
        "from tinysql_tpu.obs import tsring\n"
        "def src():\n"
        "    return {'tinysql_pool_queued': 0,\n"
        "            'tinysql_progcache_misses_total': 0}\n"
        "tsring.register_source('ok', src)\n"
        "for k in ('cycles',):\n"
        "    name = f'tinysql_prewarm_worker_{k}_total'\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_ob404_out_of_scope_module_silent(tmp_path):
    # a module that never touches the ring may spell anything — OB404
    # polices the sampling surface, not every string in the tree
    p = tmp_path / "unrelated.py"
    p.write_text("NAME = 'tinysql_totally_made_up_total'\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_ob404_registry_module_exempt(tmp_path):
    # obs/metrics.py IS the registry: declaring a new name there is the
    # sanctioned act the rule points everyone else at
    p = tmp_path / "metrics.py"
    p.write_text("from tinysql_tpu.obs import tsring\n"
                 "METRICS = {'tinysql_brand_new_total': ('counter', '')}\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_obs_reads_not_flagged(tmp_path):
    p = tmp_path / "reader.py"
    p.write_text("from tinysql_tpu.ops import kernels\n"
                 "snap = dict(kernels.STATS)\n"
                 "n = kernels.STATS['dispatches']\n")
    assert lint_obs_discipline(SourceFile(str(p))) == []


def test_tree_obs_discipline_clean():
    diags = []
    for sf in gather_sources(os.path.join(REPO, "tinysql_tpu")):
        diags.extend(lint_obs_discipline(sf))
    assert not diags, "\n".join(d.format() for d in diags)


def test_tree_trace_safety_clean():
    diags = []
    for sf in gather_sources(os.path.join(REPO, "tinysql_tpu")):
        diags.extend(sf.check_suppression_syntax())
        diags.extend(lint_trace_safety(sf))
    assert not diags, "\n".join(d.format() for d in diags)


def test_tree_lock_discipline_clean():
    diags = []
    for rel in LOCK_SCOPE:
        sf = SourceFile(os.path.join(REPO, rel))
        diags.extend(sf.check_suppression_syntax())
        diags.extend(lint_lock_discipline(sf))
    assert not diags, "\n".join(d.format() for d in diags)


def test_corpus_plans_clean():
    # every query in the two corpus files must place without a violation
    # (acceptance criterion; CI runs the same via tools/lint.py --strict)
    from tinysql_tpu.analysis.plan_device import check_corpus
    diags = check_corpus(REPO)
    assert not diags, "\n".join(d.format() for d in diags)


# ---- the CLI contract ---------------------------------------------------

@pytest.mark.parametrize("passname,fixture", [
    ("trace", "bad_trace.py"),
    ("locks", "bad_locks.py"),
    ("trace", "bad_suppress.py"),
    ("trace", "bad_pipeline.py"),
    ("trace", "bad_literal.py"),
    ("trace", "bad_vmap.py"),
    ("obs", "bad_stats.py"),
    ("obs", "bad_summary.py"),
    ("obs", "bad_metric.py"),
    ("obs", "bad_devtime.py"),
    ("obs", "bad_conprof.py"),
    ("obs", "bad_memprof.py"),
    ("obs", "bad_spantotals.py"),
    ("conc", "bad_race.py"),
    ("conc", "bad_lockorder.py"),
    ("conc", "bad_blocking.py"),
    ("conc", "bad_ctxhop.py"),
    ("devflow", "bad_sync.py"),
    ("devflow", "bad_transfer.py"),
    ("devflow", "bad_key.py"),
    ("devflow", "bad_escape.py"),
])
def test_cli_exits_nonzero_on_fixture(passname, fixture):
    r = subprocess.run(
        [sys.executable, LINT, "--pass", passname,
         os.path.join(FIXDIR, fixture)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "violation" in r.stdout


def test_cli_clean_on_tree_trace_locks():
    r = subprocess.run(
        [sys.executable, LINT, "--pass", "trace", "--pass", "locks"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr


# ---- the --json machine surface + distinct exit codes -------------------

def test_cli_json_findings_exit1():
    r = subprocess.run(
        [sys.executable, LINT, "--json", "--pass", "conc",
         os.path.join(FIXDIR, "bad_lockorder.py")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["clean"] is False
    assert payload["count"] == len(payload["violations"]) == 1
    v = payload["violations"][0]
    assert v["rule"] == "CC702" and v["line"] > 0
    assert v["path"].endswith("bad_lockorder.py")


def test_cli_json_clean_exit0(tmp_path):
    p = tmp_path / "ok.py"
    p.write_text("X = 1\n")
    r = subprocess.run(
        [sys.executable, LINT, "--json", "--pass", "conc", str(p)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["clean"] is True and payload["violations"] == []


def test_cli_internal_error_exit2(tmp_path):
    # missing path and unparseable source must both exit 2 (internal),
    # never 0 (clean) or 1 (findings) — CI tells the cases apart
    r = subprocess.run(
        [sys.executable, LINT, "--pass", "conc",
         str(tmp_path / "missing.py")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 2, r.stdout + r.stderr
    broken = tmp_path / "broken.py"
    broken.write_text("def broken(:\n")
    r = subprocess.run(
        [sys.executable, LINT, "--json", "--pass", "conc", str(broken)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 2, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert "error" in payload and payload["clean"] is False


# ---- pass 5: fail discipline (FP5xx) ------------------------------------

def test_fail_fixture_fires_fp_rules():
    from tinysql_tpu.analysis import lint_fail_discipline
    sf = SourceFile(os.path.join(FIXDIR, "bad_retry.py"))
    got = lint_fail_discipline(sf)
    assert [d.rule for d in got].count("FP501") == 1, \
        [d.format() for d in got]
    assert [d.rule for d in got].count("FP502") == 2, \
        [d.format() for d in got]


def test_fail_backoffer_module_exempt(tmp_path):
    # backoff.py OWNS sleeping (budget metering, SLEEP_SCALE, cancel)
    from tinysql_tpu.analysis import lint_fail_discipline
    p = tmp_path / "backoff.py"
    p.write_text("import time\n\n\ndef backoff(ms):\n"
                 "    time.sleep(ms / 1000.0)\n")
    assert lint_fail_discipline(SourceFile(str(p))) == []


def test_fail_registered_and_dynamic_names_clean(tmp_path):
    from tinysql_tpu.analysis import lint_fail_discipline
    p = tmp_path / "seams.py"
    p.write_text("from tinysql_tpu.utils import failpoint\n\n\n"
                 "def seam(name):\n"
                 "    failpoint.inject('copTaskError')\n"
                 "    failpoint.inject(name)  # dynamic: runtime-checked\n")
    assert lint_fail_discipline(SourceFile(str(p))) == []


def test_tree_fail_discipline_clean():
    from tinysql_tpu.analysis import lint_fail_discipline
    diags = []
    for rel in _lint_cli_module().FAIL_SCOPE:
        for sf in gather_sources(os.path.join(REPO, rel)):
            diags.extend(sf.check_suppression_syntax())
            diags.extend(lint_fail_discipline(sf))
    assert not diags, "\n".join(d.format() for d in diags)


def test_cli_exits_nonzero_on_fail_fixture():
    r = subprocess.run(
        [sys.executable, LINT, "--pass", "fail",
         os.path.join(FIXDIR, "bad_retry.py")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "FP50" in r.stdout

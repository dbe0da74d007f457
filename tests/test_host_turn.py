"""The host's turn of a statement, accounted (PR 37): over the wire a
connection's wall time is ``wire.idle`` + ``wire.command``; the way back
from a worker is ``pool.wake``; ``execute`` has named children and its
self time is the rest; ``stmt.finish`` once a reported statement; the
waits never reach the profiler; and the benchmark's new per-layer
entries read keys the program really produces.  No timing thresholds:
every assertion is an identity between the program's own clock reads."""
import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from _timelimit import until

from tinysql_tpu.obs import context as obs_context
from tinysql_tpu.obs import trace as obs_trace
from tinysql_tpu.obs.trace import clear_traces, recent_traces
from tinysql_tpu.ops import kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: to_dict rounds a start and a duration to 0.1 us each
ROUND_US = 0.2
N = 4

Q6 = ("select sum(l_extendedprice * l_discount) as revenue from lineitem "
      "where l_shipdate >= '{y}-01-01' and l_shipdate < '{y1}-01-01' "
      "and l_discount between 0.05 and 0.07 and l_quantity < 24")


def _q6(year: int) -> str:
    return Q6.format(y=year, y1=year + 1)


@pytest.fixture(scope="module")
def tpch_server():
    """TPC-H at SF 0.01 behind one server; wire sessions take the fused
    pipeline (``tidb_devpipe = 1``) and the device tier from row one."""
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.server.server import Server
    from tinysql_tpu.session.session import Session, new_session
    boot = new_session()
    tpch.load(boot, data=tpch.generate(0.01))
    srv = Server(boot.storage, port=0)
    srv.start()
    admin = Session(boot.storage)
    admin.execute("set global tidb_memprof_rate = 0")
    admin.execute("set global tidb_tpu_min_rows = 0")
    admin.execute("set global tidb_devpipe = 1")
    yield srv
    admin.execute("set global tidb_wire_mode = 'legacy'")
    srv.close()


def _session(server):
    from tinysql_tpu.session.session import Session
    s = Session(server.storage)
    s.execute("use tpch")
    return s


def _mark() -> int:
    return next(obs_trace._ids)


def _proc(mark: int) -> list:
    return [s for s in obs_trace.PROCESS.spans() if s["id"] > mark]


def _end(span: dict) -> float:
    return span["ts_us"] + span["dur_us"]


class _Annotations:
    """A stand-in for ``jax.profiler.TraceAnnotation`` whose session is
    always open: every name a live span would put on the profiler."""
    names: list = []

    def __init__(self, name, **_stats):
        type(self).names.append(name)

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **_kw):
        pass


@pytest.fixture()
def profiler_stub():
    was = obs_trace._annotation
    _Annotations.names = []
    obs_trace.bind_profiler(_Annotations)
    yield _Annotations
    obs_trace.bind_profiler(was)


def _totals(*names) -> dict:
    rows = obs_trace.totals()
    zero = {"count": 0, "sum_s": 0.0, "self_s": 0.0, "max_s": 0.0}
    return {n: dict(rows.get(n, zero)) for n in names}


def _grown(before: dict, after: dict, name: str, key: str) -> float:
    return after[name][key] - before[name][key]


WIRE_NAMES = ("wire.command", "wire.parse", "pool.wait", "wire.write",
              "wire.idle", "pool.wake", "pool.submit", "solo", "round",
              "round.form", "stmt.finish")


def _one_connection(server, mode: str):
    """N statements on one fresh connection under front end ``mode``;
    the process's spans since, once the last command's has ended."""
    from test_server import MiniClient
    admin = _session(server)
    admin.execute(f"set global tidb_wire_mode = '{mode}'")
    clear_traces()
    mark = _mark()
    before = _totals(*WIRE_NAMES)
    c = MiniClient(server.port, db="tpch")
    try:
        for i in range(N):
            _cols, rows = c.query(_q6(1993 + i))
            assert len(rows) == 1
        # a command's span is recorded when it ENDS, after the client
        # has its answer
        until(lambda: sum(s["name"] == "wire.command" and
                          s["args"].get("cmd") == 3
                          for s in _proc(mark)) == N,
              "the last command's span has ended")

        # ... and a worker's round of one ends after its member's ``solo``
        # has woken the submitter: on a loaded host the command's span
        # can be recorded before the round's
        def rounds_ended():
            spans = _proc(mark)
            ids = {s["id"] for s in spans}
            return all(s["parent"] is None or s["parent"] in ids
                       for s in spans if s["name"] == "solo")
        until(rounds_ended, "every solo's round has ended")
    finally:
        c.close()
    return _proc(mark), before, _totals(*WIRE_NAMES)


@pytest.mark.parametrize("mode", ["legacy", "aio"])
def test_a_connections_time_is_idle_plus_commands(tpch_server, mode,
                                                  profiler_stub):
    spans, before, after = _one_connection(tpch_server, mode)
    commands = sorted((s for s in spans if s["name"] == "wire.command"
                       and s["args"].get("cmd") == 3),
                      key=lambda s: s["ts_us"])
    assert len(commands) == N
    (conn,) = {s["args"]["conn"] for s in commands}
    idles = sorted((s for s in spans if s["name"] == "wire.idle"
                    and s["args"]["conn"] == conn),
                   key=lambda s: s["ts_us"])
    # the wait before the connection's first command is not counted
    assert len(idles) == N - 1
    assert all(s["parent"] is None and s["cat"] == "wire" for s in idles)
    # each interval tiles the gap between two commands exactly
    for idle, prev, nxt in zip(idles, commands, commands[1:]):
        assert idle["ts_us"] == pytest.approx(_end(prev), abs=ROUND_US)
        assert _end(idle) == pytest.approx(nxt["ts_us"], abs=ROUND_US)
    # first packet read to last flush = sum(idle) + sum(command)
    wall = _end(commands[-1]) - commands[0]["ts_us"]
    named = sum(s["dur_us"] for s in idles + commands)
    assert named == pytest.approx(wall, rel=0.01)
    assert named == pytest.approx(wall, abs=ROUND_US * 2 * N)
    # the way back from the worker: one interval a statement, under the
    # connection's own pool.wait, inside it, ending where the submitter
    # ran again
    waits = {s["id"]: s for s in spans if s["name"] == "pool.wait"
             and s["parent"] in {c["id"] for c in commands}}
    wakes = [s for s in spans if s["name"] == "pool.wake"]
    assert len(waits) == N and len(wakes) == N
    for wake in wakes:
        wait = waits[wake["parent"]]
        assert wake["cat"] == "serving"
        assert wake["ts_us"] >= wait["ts_us"] - ROUND_US
        assert _end(wake) <= _end(wait) + ROUND_US
    # waits are measured, never live: the profiler saw the work and
    # neither of them
    seen = set(profiler_stub.names)
    assert "tinysql/wire.write" in seen and "tinysql/solo" in seen
    assert "tinysql/wire.idle" not in seen
    assert "tinysql/pool.wake" not in seen
    # the totals grew by what the ring holds
    assert _grown(before, after, "wire.idle", "count") == N - 1
    assert _grown(before, after, "pool.wake", "count") == N
    assert _grown(before, after, "wire.idle", "sum_s") * 1e6 == \
        pytest.approx(sum(s["dur_us"] for s in idles), abs=ROUND_US * N)


@pytest.mark.parametrize("mode", ["legacy", "aio"])
def test_a_commands_time_is_its_children_and_its_self_time(tpch_server,
                                                           mode):
    spans, before, after = _one_connection(tpch_server, mode)

    def grew(name, key="sum_s"):
        return _grown(before, after, name, key)
    # wire.command = wire.parse + pool.wait + wire.write + its self time
    # (an event loop's pool.wait is backdated at completion: no thread
    # waited, and the command's self time keeps it)
    children = grew("wire.parse") + grew("wire.write")
    if mode == "legacy":
        children += grew("pool.wait")
    else:  # ... less the loop's own pool.submit, a child, inside it
        children += grew("pool.submit")
        assert grew("wire.command", "self_s") + grew("pool.submit") \
            >= grew("pool.wait")
    assert grew("wire.command") == pytest.approx(
        children + grew("wire.command", "self_s"), abs=1e-9)
    # pool.wait >= pool.submit + queue_wait + the worker's span +
    # pool.wake, statement by statement: they follow one another inside
    # it.  A batchable family's leader runs in a round of one
    by_id = {s["id"]: s for s in spans}
    solos = [s for s in spans if s["name"] == "solo"]
    assert len(solos) == N
    traces = {}
    for t in recent_traces():
        execute = [s for s in t["spans"] if s["name"] == "execute"]
        if execute and execute[0]["parent"] in by_id:
            traces[execute[0]["parent"]] = t["spans"]
    rounds = 0
    for solo in solos:
        wait = by_id[solo["args"]["wait"]]
        assert wait["name"] == ("pool.wait" if mode == "legacy"
                                else "wire.command")
        if mode == "aio":  # the backdated wait under the command
            (wait,) = [s for s in spans if s["name"] == "pool.wait"
                       and s["parent"] == wait["id"]]
        (wake,) = [s for s in spans if s["name"] == "pool.wake"
                   and s["parent"] == wait["id"]]
        mine = traces[solo["id"]]
        queue = sum(s["dur_us"] for s in mine if s["name"] == "queue_wait")
        worked = solo["dur_us"]
        if solo["parent"] is not None:  # a round of one: form, then solo
            worker = by_id[solo["parent"]]
            assert worker["name"] == "round"
            # the entry completes where solo ends, inside the round
            worked = _end(solo) - worker["ts_us"]
            rounds += 1
        submit = [s for s in spans if s["name"] == "pool.submit"
                  and s["ts_us"] >= wait["ts_us"] - ROUND_US
                  and _end(s) <= _end(wait) + ROUND_US]
        assert len(submit) == 1
        assert wait["dur_us"] + 4 * ROUND_US >= \
            submit[0]["dur_us"] + queue + worked + wake["dur_us"]
        assert wake["ts_us"] >= _end(solo) - ROUND_US
        # the worker's span accounts for the statement it ran, and the
        # fan-out after it: stmt.finish is the process's, under solo
        (execute,) = [s for s in mine if s["name"] == "execute"]
        assert execute["parent"] == solo["id"]
        assert execute["tid"] == solo["tid"]
        (finish,) = [s for s in spans if s["name"] == "stmt.finish"
                     and s["parent"] == solo["id"]]
        assert finish["ts_us"] >= _end(execute) - ROUND_US
        assert _end(finish) <= _end(solo) + ROUND_US
    # the family was learned from the first answer: later ones waited
    # for mates in round.form
    assert rounds >= 1
    assert grew("round.form", "count") == rounds == grew("round", "count")
    # solo's self time: what ran in it under no child on its thread
    in_solo = sum(s["dur_us"] for s in spans
                  if s["parent"] in {x["id"] for x in solos}) \
        + sum(s["dur_us"] for t in traces.values() for s in t
              if s["name"] == "execute")
    assert grew("solo", "self_s") * 1e6 == pytest.approx(
        sum(s["dur_us"] for s in solos) - in_solo, abs=ROUND_US * 6 * N)


# ---- execute's children ---------------------------------------------------

EXECUTE_CHILDREN = ("plan", "place", "plan.publish", "exec.build",
                    "pipe.prepare", "agg.prepare", "h2d", "dispatch",
                    "drain", "exec.rows")


def _children_of_execute(spans: list) -> list:
    (execute,) = [s for s in spans if s["name"] == "execute"]
    kids = sorted((s for s in spans if s["parent"] == execute["id"]),
                  key=lambda s: s["ts_us"])
    assert all(k["tid"] == execute["tid"] for k in kids)
    return execute, kids


@pytest.fixture(scope="module")
def fused(tpch_server):
    s = _session(tpch_server)
    s.execute("set @@tidb_tpu_min_rows = 0")
    s.execute("set @@tidb_devpipe = 1")
    return s


def test_a_fused_statements_execute_is_its_children(fused):
    from tinysql_tpu.bench import tpch
    fused.query(tpch.QUERIES["Q3"])  # warm: replica memos, the program
    before = _totals("execute", *EXECUTE_CHILDREN)
    rows = fused.query(tpch.QUERIES["Q3"]).rows
    spans = fused.last_query_stats.tracer.spans()
    after = _totals("execute", *EXECUTE_CHILDREN)
    execute, kids = _children_of_execute(spans)
    names = [k["name"] for k in kids]
    assert names[:7] == ["plan", "place", "plan.publish", "exec.build",
                         "pipe.prepare", "dispatch", "drain"], names
    # buffers to a chunk, then every chunk to Python rows: the answer
    assert set(names[7:]) == {"exec.rows"} and len(names) >= 9
    assert sum(k["args"]["rows"] for k in kids[8:]) == len(rows) > 0
    assert kids[7]["args"]["rows"] >= len(rows)
    # one after the other inside execute
    for a, b in zip(kids, kids[1:]):
        assert _end(a) <= b["ts_us"] + ROUND_US
    assert _end(kids[-1]) <= _end(execute) + ROUND_US
    # execute.self_s = its duration less them
    assert _grown(before, after, "execute", "count") == 1
    assert _grown(before, after, "execute", "self_s") * 1e6 == \
        pytest.approx(execute["dur_us"] - sum(k["dur_us"] for k in kids),
                      abs=ROUND_US * (len(kids) + 1))
    # TRACE <stmt> shows them, in the tree under execute
    shown = [r[0].strip() for r in
             fused.query("trace " + tpch.QUERIES["Q3"]).rows]
    at = [shown.index(n) for n in ("execute", "plan", "place",
                                   "plan.publish", "exec.build",
                                   "pipe.prepare", "dispatch", "drain",
                                   "exec.rows")]
    assert at == sorted(at)
    # and /debug/trace's ring holds the statement's own entry
    last = recent_traces()[-1]["spans"]
    assert {"plan.publish", "exec.build", "pipe.prepare", "exec.rows"} \
        <= {s["name"] for s in last}


def test_a_per_operator_statement_prepares_no_pipeline(fused):
    """Q6 builds no fused pipeline: the per-operator aggregate's host
    work is ``agg.prepare``, its parameters' upload ``h2d``."""
    fused.query(_q6(1994))
    fused.query(_q6(1995))
    _execute, kids = _children_of_execute(
        fused.last_query_stats.tracer.spans())
    names = [k["name"] for k in kids]
    assert "pipe.prepare" not in names
    assert [n for n in names if n != "h2d"][:8] == [
        "plan", "place", "plan.publish", "exec.build", "agg.prepare",
        "dispatch", "drain", "exec.rows"], names
    assert names[-1] == "exec.rows" and names.count("agg.prepare") == 1
    assert names.index("agg.prepare") < names.index("h2d") \
        < names.index("dispatch")
    assert all(k["args"]["bytes"] >= 0 for k in kids if k["name"] == "h2d")


def _drive_round(server, sessions, qs):
    from tinysql_tpu.obs import stmtsummary
    from tinysql_tpu.parser import parse
    from tinysql_tpu.server.pool import StatementPool, _Entry
    digest, _ = stmtsummary.normalize(qs[0])
    pool = StatementPool(server.storage)
    entries = [_Entry(s, parse(q)[0], q, digest, True)
               for s, q in zip(sessions, qs)]
    pool._run_batch(entries)
    assert all(e.error is None for e in entries)
    return entries


def test_a_round_members_spans_lie_under_its_legs_and_finish_once(
        tpch_server, fused):
    qs = [_q6(1993 + i) for i in range(4)]
    for q in qs:
        fused.query(q)  # warm program, learn the family
    kernels.prewarm_stacked()
    internal = _session(tpch_server)
    internal.internal = True
    members = [_session(tpch_server) for _ in qs]
    clear_traces()
    mark = _mark()
    before = _totals("stmt.finish")
    _drive_round(tpch_server, members, qs)
    internal.query(qs[0])  # an internal session reports nothing
    after = _totals("stmt.finish")
    spans = _proc(mark)
    by_id = {s["id"]: s for s in spans}
    collects = [s for s in spans if s["name"] == "round.collect"]
    replays = [s for s in spans if s["name"] == "round.replay"]
    assert len(collects) == len(replays) == 4
    assert {c["args"]["outcome"] for c in collects} == {"parked"}
    # a parked attempt's spans were adopted under its leg: it planned,
    # published, built and prepared, and parked at the launch
    for leg in collects:
        (execute,) = [s for s in spans if s["name"] == "execute"
                      and s["parent"] == leg["id"]]
        names = [s["name"] for s in sorted(
            (s for s in spans if s["parent"] == execute["id"]),
            key=lambda s: s["ts_us"])]
        assert names == ["plan", "place", "plan.publish", "exec.build",
                         "agg.prepare"], names
    # the replays report: the statement's own trace has the way back,
    # and stmt.finish is the process's span under the replay leg
    finishes = [s for s in spans if s["name"] == "stmt.finish"]
    assert len(finishes) == 4
    assert {by_id[f["parent"]]["name"] for f in finishes} \
        == {"round.replay"}
    assert {f["parent"] for f in finishes} == {r["id"] for r in replays}
    replayed = [t["spans"] for t in recent_traces()
                if any(s["name"] == "batch_wait" for s in t["spans"])]
    assert len(replayed) == 4
    for mine in replayed:
        execute, kids = _children_of_execute(mine)
        assert by_id[execute["parent"]]["name"] == "round.replay"
        names = [k["name"] for k in kids]
        assert names[:5] == ["plan", "place", "plan.publish",
                             "exec.build", "agg.prepare"]
        assert names[-1] == "exec.rows" and "pipe.prepare" not in names
    # never for a parked collect leg (4 of them), never for an internal
    # session: once a reported statement
    assert _grown(before, after, "stmt.finish", "count") == 4
    # the stacked upload and launch are the dispatch leg's
    (stack,) = [s for s in spans if s["name"] == "round.stack"]
    under = [s["name"] for s in spans if s["parent"] == stack["id"]]
    assert "h2d" in under and "dispatch" in under and "drain" in under


def test_metrics_export_the_span_totals(fused):
    from tinysql_tpu.obs import metrics as obs_metrics
    fused.query(_q6(1996))
    rows = obs_trace.totals()
    text = obs_metrics.render_prometheus()
    for name in ("exec.build", "plan.publish", "exec.rows", "stmt.finish"):
        line = [ln for ln in text.splitlines() if ln.startswith(
            f'tinysql_span_count_total{{span="{name}"}}')]
        assert line and int(line[0].split()[-1]) >= rows[name]["count"] > 0
        assert f'tinysql_span_seconds_total{{span="{name}"}}' in text
        assert f'tinysql_span_max_seconds{{span="{name}"}}' in text
    for metric, kind in (("tinysql_span_seconds_total", "counter"),
                         ("tinysql_span_count_total", "counter"),
                         ("tinysql_span_max_seconds", "gauge")):
        assert obs_metrics.registered(metric)
        assert f"# TYPE {metric} {kind}" in text


# ---- the benchmark's side -------------------------------------------------

#: this PR's entries of BENCHMARK.json's per_layer, in the file's order:
#: (name, layer, the cells it lists)
DASH = ["tpch_sf1.q6_dash_16c"]
STREAMS = ["tpch_sf1.power_stream", "tpch_sf1_mesh4.power_stream",
           "tpch_sf10_mesh4.power_stream", "tpch_sf1_joins.join_stream"]
WIRE, POOL = "wire front end", "admission, pool, batcher"
PIPE, SESSION = "executor: fused pipeline", "session: parse, plan, place"
NEW_ENTRIES = [
    ("client_turn_ms_per_query.serve", WIRE, DASH),
    ("client_turn_ms_per_query.streams", WIRE, STREAMS),
    ("wire_write_ms_per_query.serve", WIRE, DASH),
    ("wire_write_ms_per_query.streams", WIRE, STREAMS),
    ("pool_wake_ms_per_query.serve", POOL, DASH),
    ("pool_wake_ms_per_query.streams", POOL, STREAMS),
    ("round_form_ms_per_query.serve", POOL, DASH),
    # the joins cell sends no Q6: nothing of it is batchable
    ("round_form_ms_per_query.streams", POOL, STREAMS[:3]),
    ("queue_wait_ms_per_query.streams", POOL, STREAMS),
    ("dispatch_ms_per_query.streams", PIPE, STREAMS),
    ("exec_build_ms_per_query.serve", PIPE, DASH),
    ("exec_build_ms_per_query.streams", PIPE, STREAMS),
    ("plan_publish_ms_per_query.serve", SESSION, DASH),
    ("plan_publish_ms_per_query.streams", SESSION, STREAMS),
    # the dash builds no DevPipeExec: no entry for it
    ("pipe_prepare_ms_per_query.streams", PIPE, STREAMS),
    ("exec_rows_ms_per_query.serve", PIPE, DASH),
    ("exec_rows_ms_per_query.streams", PIPE, STREAMS),
    ("stmt_finish_ms_per_query.serve", SESSION, DASH),
    ("stmt_finish_ms_per_query.streams", SESSION, STREAMS),
    ("place_ms_per_query.serve", SESSION, DASH),
    ("place_ms_per_query.streams", SESSION, STREAMS),
    ("host_unnamed_ms_per_query.serve", PIPE, DASH),
    ("host_unnamed_ms_per_query.streams", PIPE, STREAMS),
]


def _bench_module(kind: str, name: str):
    path = os.path.join(ROOT, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"host_turn_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metric_file(name: str) -> dict:
    """``run.py``'s ``metric_spec``: ``<name>.json``, else the name less
    its last dotted part."""
    for stem in (name, name.rpartition(".")[0]):
        path = os.path.join(ROOT, "benchmark", "layer_metrics",
                            stem + ".json")
        if stem and os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise AssertionError(f"no file for {name}")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_new_entries_are_the_files_last(bench):
    mine = bench["per_layer"][-len(NEW_ENTRIES):]
    assert [(m["name"], m["layer"]) for m in mine] \
        == [e[:2] for e in NEW_ENTRIES]
    for m, (_, _, pinned) in zip(mine, NEW_ENTRIES):
        # the cells PR 37 listed come first and as they were; a later
        # cell that reports the quantity is appended (PR 39's four-chip
        # joins cell to the twelve entries that list the joins cell)
        assert m["workloads"][:len(pinned)] == pinned
        if m["name"].endswith(".serve"):
            assert m["workloads"] == pinned
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"][:-len(NEW_ENTRIES)]}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"]) == (
            "ms", "lower", "program_span")
        assert m["layer"] in layers  # a layer the file already names
        moves = "serve_queries_per_s" if m["name"].endswith(".serve") \
            else "stream_queries_per_s"
        assert m["moves"] == moves
        assert set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(e2e[moves]["workloads"])
    assert len(bench["per_layer"]) <= 128


@pytest.mark.parametrize("entry", [e[0] for e in NEW_ENTRIES])
def test_a_new_entry_reads_a_key_the_program_produces(tpch_server, fused,
                                                      entry):
    """The entry resolves to its file, the file to a reader and a source
    that exist, and the key is one the source holds once statements have
    run over the wire, fused, per-operator and in a round of one."""
    from tinysql_tpu.bench import tpch
    spec = _metric_file(entry)
    reader = _bench_module("readers", spec["reader"])
    sources = {s: _bench_module("sources", s) for s in spec["sources"]}
    if "round.form.ms" not in sources.get(
            "spans", SimpleNamespace(snapshot=dict)).snapshot() \
            or "wire.idle.ms" not in _bench_module(
                "sources", "spans").snapshot():
        _one_connection(tpch_server, "legacy")
    fused.query(tpch.QUERIES["Q3"])
    args = spec["args"]
    if spec["reader"] == "summary_ms":
        assert spec["sources"] == ["summary"]
        held = sources["summary"].snapshot()
        assert args["phases"] == ["queue"] and "queue" in held
        return
    assert spec["sources"] == ["spans"] and args["source"] == "spans"
    assert args["per_statement"] is True
    held = sources["spans"].snapshot()
    keys = [args["key"]] if spec["reader"] == "counter" else args["keys"]
    assert spec["reader"] in ("counter", "span_sum")
    missing = [k for k in keys if k not in held]
    # the round's legs exist where a round of several ran (another test
    # of this file drives one; the sum leaves out what is not there)
    assert [k for k in missing if not k.startswith("round.")] == [], \
        missing
    run = SimpleNamespace(deltas={"spans": {k: 3.0 for k in keys}},
                          answered=[object()] * 2)
    assert reader.read(run, **args) == pytest.approx(1.5 * len(keys))
    # the parent has no such span: nothing to read, nothing raised
    run.deltas = {"spans": {"bg.ms": 1.0}}
    assert reader.read(run, **args) is None


def test_span_sum_over_fabricated_deltas():
    span_sum = _bench_module("readers", "span_sum")
    spec = _metric_file("host_unnamed_ms_per_query.serve")
    assert spec == _metric_file("host_unnamed_ms_per_query.streams")
    assert spec["reader"] == "span_sum"
    keys = spec["args"]["keys"]
    assert keys == [n + ".self_ms" for n in (
        "wire.command", "solo", "round.collect", "round.replay",
        "round.dispatch", "round.stack", "execute")]
    deltas = {"spans": {"wire.command.self_ms": 10.0, "solo.self_ms": 20.0,
                        "execute.self_ms": 70.0, "execute.ms": 900.0,
                        "drain.self_ms": 800.0}}
    run = SimpleNamespace(deltas=deltas, answered=[object()] * 10)
    # the keys that are there, summed; the others left out
    assert span_sum.read(run, **spec["args"]) == pytest.approx(10.0)
    assert span_sum.read(run, "spans", keys) == pytest.approx(100.0)
    assert span_sum.read(run, "spans", keys, scale=2.0) \
        == pytest.approx(200.0)
    assert span_sum.read(run, "spans", ["nothing.self_ms"]) is None
    run.answered = []
    assert span_sum.read(run, **spec["args"]) is None

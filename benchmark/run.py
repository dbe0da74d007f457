#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The phases are ``chip_smoke.py``'s: device -> generate -> bulk load ->
``Server(port=0)`` in this process -> wire clients (a child process) ->
warm every statement shape -> the measured window -> "nothing hid the
device" -> the comparison with the plain reference.  Everything that
belongs to one cell is data found by name: the configuration
(``configs/``), its dataset and reference (``datasets/``), its store,
loader and server (``deployments/``), the traffic mix (``traffic/``), the
driver (``drivers/``), and each metric's file with its reader and the
counters it reads (``end_to_end/``, ``layer_metrics/``, ``readers/``,
``sources/``).  See README.md.

Each phase prints one JSON line as it ends.  The LAST line of standard
output is the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(``--trace 0``: the cell's end-to-end metrics; ``--trace 1``: its
per-layer metrics), ``device``, ``breakdown`` (traced), ``compared``.  A
run that finds another platform than it expects, too few chips, a host
twin, a degraded statement or a WARNING of the program prints no result
and exits non-zero.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import compare  # noqa: E402
import tracered  # noqa: E402
import traffic  # noqa: E402

class RunFailure(Exception):
    """The run may not be timed or reported: the reason is the message."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name a data file gives."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def phase_line(phase: str, t0: float, **fields) -> float:
    seconds = time.time() - t0
    print(json.dumps({"phase": phase, "seconds": round(seconds, 3),
                      **fields}), flush=True)
    return seconds


class LogWatch(logging.Handler):
    """WARNING-or-worse records of the logger named exactly as the
    deployment says fail the run.  Child loggers (the slow log) are only
    shown."""

    def __init__(self, name: str):
        super().__init__(logging.WARNING)
        self.name = name
        self.failing = []

    def emit(self, record):
        msg = self.format(record)
        if record.name == self.name:
            self.failing.append(msg)
        print(f"[log {record.levelname} {record.name}] {msg[:400]}",
              file=sys.stderr, flush=True)


def metric_spec(kind: str, name: str) -> dict:
    """A metric's file: ``<kind>/<name>.json`` or, for a quantity that
    several cells report under names of their own (``plan_ms_per_query``
    as ``.stream`` and ``.serve``), ``<kind>/<name less its last dotted
    part>.json``."""
    for stem in (name, name.rpartition(".")[0]):
        path = os.path.join(HERE, kind, stem + ".json")
        if stem and os.path.exists(path):
            return load_json(path)
    raise FileNotFoundError(f"no file for the metric {name!r} under "
                            f"benchmark/{kind}/")


class Cell:
    """One entry of ``workloads`` with everything its names lead to."""

    def __init__(self, name: str):
        bench = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"there are {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(ROOT, configs[self.entry["config"]]["file"])
        self.mix = load_json(HERE, "traffic",
                             self.entry["traffic"] + ".json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]

    def sources(self) -> list:
        """The names of every source that one of the cell's metrics reads,
        in the order first named."""
        names = []
        for kind, metrics in (("end_to_end", self.end_to_end),
                              ("layer_metrics", self.per_layer)):
            for m in metrics:
                names += metric_spec(kind, m["name"]).get("sources", [])
        return list(dict.fromkeys(names))

    def metric_values(self, kind: str, metrics: list, run) -> dict:
        """Each metric read by the reader its own file names; a reader
        that finds nothing to read leaves its metric out."""
        out = {}
        for m in metrics:
            spec = metric_spec(kind, m["name"])
            value = load_module("readers", spec["reader"]).read(
                run, **spec.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


class Run:
    """What the phases leave for each other and for the readers."""

    def __init__(self, args, cell: Cell):
        self.args = args
        self.cell = cell
        self.phase_seconds = {}
        self.dataset_module = load_module("datasets", cell.config["dataset"])
        self.deployment_module = load_module("deployments",
                                             cell.config["deployment"])
        # "jax": the warm-up's ``until_quiet`` stages read it in any cell
        self.sources = {name: load_module("sources", name) for name in
                        dict.fromkeys(("jax",
                                       *self.deployment_module.SOURCES,
                                       *cell.sources()))}
        self.driver = load_module("drivers", cell.mix["driver"])
        self.statements = traffic.expand(cell.mix)
        self.sqls = [s.sql for s in self.statements]
        self.orders = traffic.orders(cell.mix, len(self.statements),
                                     cell.mix["connections"], args.seed)
        self.db = self.dataset_module.DATABASE
        self.dataset = self.deployment = self.port = None
        self.device = self.peaks = self.trace = None
        self.records = self.answered = ()
        self.window_start = self.window_end = 0.0
        self.trace_start = self.trace_stop = 0.0
        #: growth over the window of each source's counters:
        #: {source: {key: growth}}
        self.deltas = {}
        self.trace_dir = None

    def snapshot(self) -> dict:
        return {name: module.snapshot()
                for name, module in self.sources.items()}

    def close(self) -> None:
        if self.deployment is not None:
            self.deployment.close()
        self.deployment = None

    def drop_trace(self) -> None:
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir = None


# ---- phases ---------------------------------------------------------------

def phase_device(run: Run) -> None:
    """Before any data is made: which device did jax find?  There is no
    path on which the run carries on without the chip it was given."""
    t0 = time.time()
    run.deployment_module.prepare(run.cell.config,
                                  rehearsal=run.args.expect_platform == "cpu")
    import jax
    for module in run.sources.values():
        if hasattr(module, "start"):
            module.start()
    devs = jax.devices()
    run.device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
    want = run.args.expect_platform
    run.phase_seconds["device"] = phase_line(
        "device", t0, **run.device, jax=jax.__version__,
        compile_cache_dir=jax.config.jax_compilation_cache_dir)
    if run.device["platform"] != want:
        raise RunFailure(f"expected platform {want!r}, jax found "
                         f"{run.device['platform']!r}")
    if run.device["count"] < run.cell.entry["chips"]:
        raise RunFailure(f"the cell needs {run.cell.entry['chips']} chips, "
                         f"jax found {run.device['count']}")
    if want != "cpu":
        peaks = load_json(HERE, "peaks.json")
        if run.device["kind"] not in peaks:
            raise RunFailure(f"device kind {run.device['kind']!r} is not in "
                             f"benchmark/peaks.json")
        run.peaks = peaks[run.device["kind"]]


def phase_load(run: Run, sf: float) -> None:
    t0 = time.time()
    run.dataset = run.dataset_module.generate(sf, run.args.seed)
    run.phase_seconds["generate"] = time.time() - t0
    t1 = time.time()
    run.deployment = run.deployment_module.start(
        run.cell.config, run.dataset_module, run.dataset)
    run.port = run.deployment.port
    run.phase_seconds["load"] = time.time() - t1
    phase_line("load", t0, sf=sf, seed=run.args.seed,
               rows=run.deployment.rows,
               generate_s=round(run.phase_seconds["generate"], 3))


def _delta(now: dict, since: dict) -> dict:
    return {k: now[k] - since.get(k, 0) for k in now
            if isinstance(now[k], (int, float))}


def _programs_built(run: Run) -> int:
    return run.sources["jax"].snapshot()["programs_built"]


def _failed(result: dict) -> list:
    return [r for r in result["records"] if r[5] is not None]


def phase_warm(run: Run) -> None:
    """Every statement shape the window will use, once cold and then
    warm, through the window's own driver and connections."""
    t0 = time.time()
    stages = []
    for stage in run.cell.mix["warmup"]:
        # a stage is repeated until ``quiet`` repeats in a row build no
        # program (the shapes concurrency makes depend on timing),
        # ``until_quiet`` times at the most
        quiet = 0
        for _ in range(stage.get("until_quiet", 1)):
            t1, built = time.time(), _programs_built(run)
            result = run.driver.warm(run, stage)
            bad = _failed(result)
            if bad:
                raise RunFailure(f"warm-up statement failed: {bad[0][5]}")
            built = _programs_built(run) - built
            stages.append({"connections": stage["connections"],
                           "seconds": round(time.time() - t1, 3),
                           "programs_built": built,
                           "statement_s": [round(r[3] - r[2], 4)
                                           for r in result["records"]][:6]})
            quiet = 0 if built else quiet + 1
            if quiet >= stage.get("quiet", 1):
                break
    run.phase_seconds["warm"] = phase_line("warm", t0, stages=stages)


def phase_window(run: Run) -> None:
    import jax
    t0 = time.time()
    before = run.snapshot()
    traced = bool(run.args.trace)

    def start_trace():
        if traced:
            run.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False
            jax.profiler.start_trace(run.trace_dir,
                                     profiler_options=options)
            run.trace_start = time.time()
        run.phase_seconds["total"] = time.time() - T_PROCESS

    def stop_trace():
        if traced:
            time.sleep(min(run.cell.mix["trace_seconds"],
                           run.args.seconds))
            run.trace_stop = time.time()
            jax.profiler.stop_trace()

    result = run.driver.window(run, run.args.seconds,
                               before_go=start_trace, after_go=stop_trace)
    after = run.snapshot()
    run.records = result["records"]
    run.answered = [r for r in run.records if r[5] is None]
    run.window_start, run.window_end = result["start"], result["end"]
    run.deltas = {k: _delta(after[k], before[k]) for k in after}
    # the CPU backend reports no memory statistics: 0 there
    run.device["memory_peak_bytes"] = int(max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:run.cell.entry["chips"]]))
    # the statements that took longest beyond the median of their kind:
    # [kind, seconds over the median, seconds into the window]
    by_kind = {}
    for r in run.answered:
        by_kind.setdefault(run.statements[r[1]].kind, []).append(r[3] - r[2])
    median = {k: statistics.median(v) for k, v in by_kind.items()}
    stalls = sorted(([run.statements[r[1]].kind,
                      round(r[3] - r[2] - median[run.statements[r[1]].kind],
                            4), round(r[2] - run.window_start, 2)]
                     for r in run.answered), key=lambda s: -s[1])[:3]
    phase_line("window", t0, statements=len(run.records),
               answered=len(run.answered),
               stalls=stalls,
               window_s=round(run.window_end - run.window_start, 4),
               setup_s=round(run.phase_seconds["total"], 3),
               **{name: {k: round(v, 3) for k, v in delta.items() if v}
                  for name, delta in run.deltas.items()})


def phase_nothing_hid(run: Run, watch: LogWatch) -> None:
    """The deployment's own checks that nothing hid the device, in every
    run: a quiet CPU answer is never timed."""
    t0 = time.time()
    checks, shown = run.deployment_module.checks(run.deltas, watch.failing)
    phase_line("nothing-hid-the-device", t0, **shown,
               warnings=watch.failing, checks=checks)
    bad = sorted(k for k, ok in checks.items() if not ok)
    if bad:
        raise RunFailure(f"nothing-hid-the-device: failed checks {bad}")


def phase_trace(run: Run) -> None:
    """The traced part of the window reduced to busy seconds, operations
    and gaps.  Only the CPU's rehearsal may find no device in its trace:
    it then reports no trace metric."""
    t0 = time.time()
    if run.trace_dir is None:
        return
    path = tracered.find_xplane(run.trace_dir)
    if path is None:
        raise RunFailure("the profiler wrote no .xplane.pb")
    window_s = run.trace_stop - run.trace_start
    run.trace = tracered.reduce_file(path, window_s)
    keep = os.environ.get("BENCH_KEEP_TRACE")  # a builder's look by hand
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(path, os.path.join(keep, run.cell.name + ".xplane.pb"))
        with open(os.path.join(keep, run.cell.name + ".records.json"),
                  "w") as f:  # [connection, statement, t_send, t_recv]
            json.dump({"trace_start": run.trace_start,
                       "records": [r[:4] for r in run.records]}, f)
    phase_line("trace", t0, xplane_bytes=os.path.getsize(path),
               traced_s=round(window_s, 3),
               busy_s=None if run.trace is None else run.trace.busy_s)
    run.drop_trace()
    if run.trace is not None:
        run.device["busy_s"] = run.trace.busy_s
        run.device["window_s"] = window_s
    elif run.device["platform"] != "cpu":
        raise RunFailure("the trace holds no device operation")


def phase_compare(run: Run) -> dict:
    """Once the window has closed and the program's state is freed: the
    reference answers every distinct statement of the window once, and
    every answer the clients got is held against it."""
    t0 = time.time()
    refs = {}

    def reference_of(idx: int):
        if idx not in refs:
            s = run.statements[idx]
            refs[idx] = run.dataset_module.REFERENCES[s.reference](
                run.dataset, s.params)
        return refs[idx]

    verdict = compare.compare([(r[1], r[4]) for r in run.records],
                              reference_of,
                              lambda idx: run.statements[idx].kind,
                              traffic.gap_limits(run.statements))
    phase_line("compare", t0, distinct_statements=len(refs), **verdict)
    return verdict


# ---- entry ----------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--expect-platform", default="tpu",
                    help="'cpu' is for the sandbox's rehearsal and the "
                         "tests only: tiny scale, no device metric")
    args = ap.parse_args(argv)

    cell = Cell(args.workload)
    run = Run(args, cell)
    watch = LogWatch(run.deployment_module.LOGGER)
    logger = logging.getLogger(watch.name)
    logger.addHandler(watch)
    try:
        sf = float(cell.config["scale_factor"])
        if args.expect_platform == "cpu":
            sf = float(cell.config["rehearsal"]["scale_factor"])
        phase_device(run)
        phase_load(run, sf)
        phase_warm(run)
        phase_window(run)
        phase_nothing_hid(run, watch)
        run.close()  # the program's state is freed before the reference
        phase_trace(run)
        verdict = phase_compare(run)
    except Exception as e:  # the boundary: say why, print no result
        if not isinstance(e, RunFailure):
            traceback.print_exc()
        print(f"[benchmark] no result: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        logger.removeHandler(watch)
        run.close()
        run.drop_trace()

    kind, metrics = ("layer_metrics", cell.per_layer) if args.trace \
        else ("end_to_end", cell.end_to_end)
    result = {
        "correct": verdict["correct"],
        "attempted": len(run.records),
        "failed": len(run.records) - len(run.answered),
        "metrics": cell.metric_values(kind, metrics, run),
        "device": run.device,
    }
    if run.trace is not None:
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["compared"] = verdict["compared"]
    if args.trace:  # for the record, on an earlier line
        phase_line("end-to-end", time.time(), **cell.metric_values(
            "end_to_end", cell.end_to_end, run))
    else:
        phase_line("layers", time.time(), **cell.metric_values(
            "layer_metrics", cell.per_layer, run))
    print(json.dumps(result), flush=True)
    for name, c in verdict["compared"].items():
        print(f"[compared] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

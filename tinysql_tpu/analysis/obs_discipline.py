"""qlint pass: observability counter discipline (OB4xx).

The device-economics counters (``ops/kernels.STATS``,
``ops/progcache.STATS``) are written ONLY through their owning module's
accessors (``kernels.stats_add`` / ``kernels.stats_hwm``; progcache's
own locked ``get``).  The accessors are what fan every increment out to
the active per-query observability scope (obs/context.py) — a direct
``STATS[...] += 1`` elsewhere updates the global dict but silently
vanishes from per-query attribution, EXPLAIN ANALYZE, and the slow log,
and (being unlocked read-modify-write from arbitrary threads) can lose
increments under the devpipe producer.

Rules:

- **OB401**: direct subscript write (``STATS[k] = ...`` /
  ``STATS[k] += ...``) to a name or attribute called ``STATS`` outside
  the owning modules.
- **OB402**: mutating-method call (``STATS.update/clear/setdefault/
  pop``) on such a target outside the owning modules.
- **OB403**: statement-summary store write (``stmtsummary.ingest`` /
  ``STORE.ingest`` / ``.reset``) outside the designated session
  statement-close hook (``session/session.py _finish_obs``) and the
  store's own module.  Any other writer double-counts statements or
  bypasses the window-rotation/eviction accounting behind
  ``information_schema.statements_summary`` and the /metrics latency
  histograms.
- **OB405**: device-time counter write outside the owning modules.
  The device-time keys (``device_s`` / ``profiled_dispatches`` /
  ``compile_s``) carry MEASURED walls: ``device_s`` is only ever real
  when the sampling profiler closed the dispatch with
  ``block_until_ready`` (ops/profiler.py via ops/kernels.counted_jit),
  and ``compile_s`` is the program-build wall timed inside
  ops/progcache.get.  A ``stats_add``/``record`` of those keys anywhere
  else would publish a host submit wall as device truth — the exact
  fiction ISSUE 11 removes.
- **OB406**: continuous-profiler fold/attribution writes outside
  ``obs/conprof.py``.  The statement CPU counters (``cpu_s`` /
  ``cpu_samples``) are SAMPLE-ESTIMATED truth: only the profiler's
  sampler tick — which walks ``sys._current_frames()``, resolves the
  executing thread through the interrupt registry, and caps each
  increment at the statement's elapsed wall — may write them.  Any
  other writer would publish un-sampled wall time as CPU attribution
  (breaking the ``sum_cpu_ms <= exec wall`` invariant), and any
  out-of-module mutation of the profiler's window store
  (``sample_once`` / ``reset`` on the module or its ``PROF``/
  ``Profiler`` instances) would corrupt the rotation/eviction
  accounting behind ``information_schema.continuous_profiling``.
- **OB407**: heap/HBM accumulator writes outside ``obs/memprof.py``.
  The memory keys (``heap_kb`` / ``heap_peak_kb`` / ``hbm_bytes``) are
  MEASURED truth: ``heap_kb`` is a site window's traced growth split
  across executing statements (so the per-statement sum stays ≤ what
  the window measured), ``heap_peak_kb`` is the largest window
  reading, and ``hbm_bytes`` is the live device-buffer census.
  Any other writer would publish a guess as measurement and break the
  ≤-growth invariant behind ``statements_summary.sum_heap_alloc_kb``;
  and any out-of-module mutation of the heap profiler's window store
  (``sample_once`` / ``reset`` on the module or its ``PROF``/
  ``HeapProfiler`` instances) would corrupt the rotation/eviction
  accounting behind ``information_schema.memory_usage`` and
  ``/debug/heap``.
- **OB408**: span-totals write outside ``obs/trace.py``.  The table
  ``name -> {count, sum_s, self_s, max_s}`` behind ``obs.trace.totals()``
  is what the benchmark's span metrics read, and it is fed from one
  place: a span that ENDS (``Tracer.end`` / ``add_complete``), jax's own
  duration events, the collector's callback.  A subscript write or a
  mutating call on ``_TOTALS`` / ``_GC``, or a call of the private
  ``_count``, anywhere else would put seconds into a layer's metric
  that no span measured (and ``self_s`` would no longer be a duration
  less its children).  Reads go through ``totals()``.
- **OB404**: metric-name drift.  In any module that touches the
  time-series ring (imports ``obs/tsring.py``, or IS it), every
  ``tinysql_*`` metric-name string literal must be declared in the
  central registry (``obs/metrics.METRICS``).  The registry is the one
  definition /metrics, the ring, ``metrics_history`` and
  ``metrics_summary`` all share — a name invented at a sample site
  would produce a time series no other surface knows, and a typo would
  silently sample nothing (the ring also drops unregistered names at
  runtime; this rule catches them at lint time).  ``obs/metrics.py``
  itself is exempt: it IS the registry.

Reads (``STATS["dispatches"]``, ``dict(STATS)``, ``stmtsummary.rows()``,
``snapshot()``, ``histogram_snapshot()``) are fine anywhere — that is
what /metrics and the mem-tables do.
"""
from __future__ import annotations

import ast
import os
import re
from typing import List, Optional, Set

from .diag import Diagnostic, SourceFile, register_rules

register_rules({
    "OB401": "direct STATS[...] write outside the owning module — use "
             "kernels.stats_add/stats_hwm so per-query scopes see it",
    "OB402": "mutating STATS method call (update/clear/setdefault/pop) "
             "outside the owning module",
    "OB403": "statement-summary store write outside the designated "
             "session statement-close hook",
    "OB404": "metric name not declared in the central registry "
             "(obs/metrics.METRICS) — /metrics, the time-series ring, "
             "and metrics_summary must share one name set",
    "OB405": "device-time counter write outside the owning "
             "profiler/kernels/progcache modules — only a "
             "block_until_ready-closed dispatch or a timed program "
             "build may claim device/compile wall",
    "OB406": "continuous-profiler fold/attribution write outside "
             "obs/conprof.py — only the sampler tick may claim "
             "statement CPU (cpu_s/cpu_samples) or mutate the "
             "window store",
    "OB408": "span-totals write outside obs/trace.py — only a span that "
             "ends (or jax's and the collector's own reports there) may "
             "add to the table the benchmark's span metrics read",
    "OB407": "heap/HBM accumulator write outside obs/memprof.py — only "
             "the heap profiler's sampler tick may claim statement "
             "memory (heap_kb/heap_peak_kb/hbm_bytes) or mutate the "
             "window store",
})

#: modules that own a STATS dict and its accessors (the serving layer's
#: admission/batching counters follow the same discipline: locked
#: accessor writes inside the owning module, snapshot reads anywhere)
OWNING_MODULES = ("kernels.py", "progcache.py", "admission.py",
                  "batching.py", "spill.py", "shardops.py", "wal.py",
                  "flight.py")

#: modules allowed to write the statement-summary store: the store
#: itself and the session statement-close hook that feeds it
SUMMARY_WRITER_MODULES = ("stmtsummary.py", "session.py")

_MUTATORS = {"update", "clear", "setdefault", "pop", "popitem"}

#: mutating entry points on the summary store / its module facade
_SUMMARY_WRITERS = {"ingest", "reset"}

#: device-time counter keys (OB405) and the modules that own their
#: truth: kernels.counted_jit (the block_until_ready-closed dispatch),
#: ops/profiler.py (the sampling decision + histogram), and
#: ops/progcache.py (the timed program build -> compile_s)
DEVTIME_KEYS = {"device_s", "profiled_dispatches", "compile_s"}
DEVTIME_OWNING_MODULES = ("kernels.py", "profiler.py", "progcache.py")

#: accumulator entry points a device-time key could ride through
_DEVTIME_SINKS = {"stats_add", "stats_hwm", "record", "record_hwm",
                  "add_counter", "add_device"}

#: statement-CPU attribution keys (OB406) and their owning module: the
#: continuous profiler's sampler tick is the ONLY writer — these carry
#: sample-estimated on-thread time capped at the statement's wall
CPU_KEYS = {"cpu_s", "cpu_samples"}
CONPROF_OWNING_MODULE = "conprof.py"

#: mutating entry points on the profiler store / its module facade
_CONPROF_WRITERS = {"sample_once", "reset"}

#: statement-memory attribution keys (OB407) and their owning module:
#: the heap profiler's sampler tick is the ONLY writer — these carry
#: the traced-delta split (≤ measured process growth), the tracemalloc
#: peak, and the device-buffer census
HEAP_KEYS = {"heap_kb", "heap_peak_kb", "hbm_bytes"}
MEMPROF_OWNING_MODULE = "memprof.py"

#: mutating entry points on the heap-profiler store / its module facade
_MEMPROF_WRITERS = {"sample_once", "reset"}


#: the span-totals table and the collector's counters (OB408), their
#: private writer, and the one module that may touch them
SPAN_TOTALS_NAMES = {"_TOTALS", "_GC"}
_SPAN_TOTALS_WRITER = "_count"
SPAN_TOTALS_OWNING_MODULE = "trace.py"


def _is_stats_target(e: ast.expr) -> bool:
    """``STATS`` / ``kernels.STATS`` / ``x.y.STATS``."""
    if isinstance(e, ast.Name):
        return e.id == "STATS"
    if isinstance(e, ast.Attribute):
        return e.attr == "STATS"
    return False


def _is_summary_target(e: ast.expr, module_aliases: set,
                       store_aliases: set) -> bool:
    """``stmtsummary`` (under any import alias) / ``obs.stmtsummary`` /
    ``stmtsummary.STORE`` / a ``STORE`` imported FROM stmtsummary — but
    not an unrelated module-level ``STORE`` global."""
    if isinstance(e, ast.Name):
        return e.id in module_aliases or e.id in store_aliases
    if isinstance(e, ast.Attribute):
        if e.attr == "stmtsummary":
            return True
        return e.attr == "STORE" \
            and _is_summary_target(e.value, module_aliases,
                                   store_aliases)
    return False


def _summary_import_aliases(sf: SourceFile):
    """(module aliases, writer names, STORE names) bound by any import
    of stmtsummary — ``from …obs import stmtsummary as sm`` /
    ``import …obs.stmtsummary as z`` / ``from …stmtsummary import
    ingest as x, STORE as st``.  Only names provably from stmtsummary
    qualify, so an unrelated local ``ingest`` helper or ``STORE``
    global stays silent."""
    modules, writers, stores = {"stmtsummary"}, set(), set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.rsplit(".", 1)[-1] == "stmtsummary" \
                        and alias.asname:
                    modules.add(alias.asname)
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module.rsplit(".", 1)[-1] == "stmtsummary":
                for alias in node.names:
                    if alias.name in _SUMMARY_WRITERS:
                        writers.add(alias.asname or alias.name)
                    elif alias.name == "STORE":
                        stores.add(alias.asname or alias.name)
            else:
                for alias in node.names:
                    if alias.name == "stmtsummary":
                        modules.add(alias.asname or alias.name)
    return modules, writers, stores


def _lint_summary_writes(sf: SourceFile) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    module_aliases, writer_aliases, store_aliases = \
        _summary_import_aliases(sf)
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (isinstance(f, ast.Attribute)
               and f.attr in _SUMMARY_WRITERS
               and _is_summary_target(f.value, module_aliases,
                                      store_aliases)) \
            or (isinstance(f, ast.Name) and f.id in writer_aliases)
        if hit:
            diags.append(Diagnostic(
                "OB403",
                "statement-summary store write — only the session's "
                "statement-close hook (_finish_obs) may ingest; any "
                "other writer double-counts or bypasses window/eviction "
                "accounting",
                sf.path, node.lineno))
    return diags


# ---- OB408: span-totals write discipline ----------------------------------

def _is_span_totals(e: ast.expr, trace_aliases: Set[str]) -> bool:
    """``trace._TOTALS`` / ``obs.trace._GC`` under any import alias of
    obs/trace.py, or the bare name where it was imported FROM there."""
    if isinstance(e, ast.Attribute) and e.attr in SPAN_TOTALS_NAMES:
        v = e.value
        return (isinstance(v, ast.Name) and v.id in trace_aliases) \
            or (isinstance(v, ast.Attribute) and v.attr == "trace")
    return isinstance(e, ast.Name) and e.id in trace_aliases \
        and e.id in SPAN_TOTALS_NAMES


def _lint_span_totals_writes(sf: SourceFile) -> List[Diagnostic]:
    # names provably bound to obs/trace.py or to its private state
    aliases: Set[str] = set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.endswith("obs.trace") and alias.asname:
                    aliases.add(alias.asname)
        elif isinstance(node, ast.ImportFrom) and node.module is not None:
            from_trace = node.module.rsplit(".", 1)[-1] == "trace"
            for alias in node.names:
                if alias.name == "trace" or (from_trace and alias.name in
                                             SPAN_TOTALS_NAMES
                                             | {_SPAN_TOTALS_WRITER}):
                    aliases.add(alias.asname or alias.name)
    if not aliases:
        return []
    diags: List[Diagnostic] = []

    def flag(node: ast.AST, what: str) -> None:
        diags.append(Diagnostic(
            "OB408",
            f"{what} writes the span totals outside obs/trace.py — "
            "record a span (obs.context.span / process_span) and let "
            "its end count", sf.path, node.lineno))

    for node in ast.walk(sf.tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for t in targets:
            inner = t  # a rebinding of the name writes no table
            while isinstance(inner, ast.Subscript):
                inner = inner.value
            if inner is not t and _is_span_totals(inner, aliases):
                flag(t, "assignment")
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr == _SPAN_TOTALS_WRITER \
                    and ((isinstance(f.value, ast.Name)
                          and f.value.id in aliases)
                         or (isinstance(f.value, ast.Attribute)
                             and f.value.attr == "trace")):
                flag(node, "`trace._count(...)`")
            elif f.attr in _MUTATORS | {"append", "extend", "insert"} \
                    and _is_span_totals(f.value, aliases):
                flag(node, f"`.{f.attr}(...)`")
        elif isinstance(f, ast.Name) and f.id == _SPAN_TOTALS_WRITER \
                and f.id in aliases:
            flag(node, "`_count(...)`")
    return diags


# ---- OB405: device-time write discipline ----------------------------------

def _lint_devtime_writes(sf: SourceFile) -> List[Diagnostic]:
    """Flag accumulator calls whose FIRST argument is a device-time key
    literal (``stats_add("device_s", ...)``, ``_obs.record("compile_s",
    ...)``) outside the owning modules.  obs/context.py defines the
    generic fan-out but never names the keys; any module NAMING one is
    claiming to have measured device time."""
    diags: List[Diagnostic] = []
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else "")
        if name not in _DEVTIME_SINKS:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and arg.value in DEVTIME_KEYS:
            diags.append(Diagnostic(
                "OB405",
                f"`{name}({arg.value!r}, ...)` writes a device-time "
                "counter outside the owning profiler/kernels/progcache "
                "modules — only a block_until_ready-closed dispatch or "
                "a timed program build may claim device/compile wall",
                sf.path, node.lineno))
    return diags


# ---- OB406: continuous-profiler write discipline --------------------------

def _conprof_import_aliases(sf: SourceFile):
    """(module aliases, writer names, profiler-instance names) bound by
    any import of conprof — the OB403 matching contract: a name
    READING as the module (bare ``conprof`` / any ``.conprof``
    attribute) matches by naming convention, exactly like OB403's
    ``stmtsummary``; the generic names (``reset`` / ``sample_once`` /
    ``PROF``) qualify only when PROVABLY imported from conprof, so an
    unrelated local ``reset`` helper or ``PROF`` global stays silent."""
    modules, writers, profs = {"conprof"}, set(), set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.rsplit(".", 1)[-1] == "conprof" \
                        and alias.asname:
                    modules.add(alias.asname)
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module.rsplit(".", 1)[-1] == "conprof":
                for alias in node.names:
                    if alias.name in _CONPROF_WRITERS:
                        writers.add(alias.asname or alias.name)
                    elif alias.name in ("PROF", "Profiler"):
                        profs.add(alias.asname or alias.name)
            else:
                for alias in node.names:
                    if alias.name == "conprof":
                        modules.add(alias.asname or alias.name)
    return modules, writers, profs


def _is_conprof_target(e: ast.expr, module_aliases: set,
                       prof_aliases: set) -> bool:
    """``conprof`` (under any alias) / ``obs.conprof`` /
    ``conprof.PROF`` / a ``PROF`` imported FROM conprof."""
    if isinstance(e, ast.Name):
        return e.id in module_aliases or e.id in prof_aliases
    if isinstance(e, ast.Attribute):
        if e.attr == "conprof":
            return True
        return e.attr == "PROF" \
            and _is_conprof_target(e.value, module_aliases, prof_aliases)
    return False


def _lint_conprof_writes(sf: SourceFile) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    module_aliases, writer_aliases, prof_aliases = \
        _conprof_import_aliases(sf)
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        # (a) a statement-CPU key laundered through an accumulator sink
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else "")
        if name in _DEVTIME_SINKS and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and arg.value in CPU_KEYS:
                diags.append(Diagnostic(
                    "OB406",
                    f"`{name}({arg.value!r}, ...)` writes a statement-"
                    "CPU counter outside obs/conprof.py — only the "
                    "profiler's sampler tick may claim cpu_s/"
                    "cpu_samples (sample-estimated, wall-capped)",
                    sf.path, node.lineno))
                continue
        # (b) a mutating call on the profiler store itself
        hit = (isinstance(f, ast.Attribute)
               and f.attr in _CONPROF_WRITERS
               and _is_conprof_target(f.value, module_aliases,
                                      prof_aliases)) \
            or (isinstance(f, ast.Name) and f.id in writer_aliases)
        if hit:
            diags.append(Diagnostic(
                "OB406",
                "continuous-profiler store write outside "
                "obs/conprof.py — window rotation/eviction accounting "
                "belongs to the sampler",
                sf.path, node.lineno))
    return diags


# ---- OB407: heap-profiler write discipline --------------------------------

#: accumulator entry points a memory key could ride through — the
#: device-time sinks plus the high-water-mark scope accessor memprof's
#: attribution actually uses
_MEMPROF_SINKS = _DEVTIME_SINKS | {"hwm_counter"}


def _memprof_import_aliases(sf: SourceFile):
    """(module aliases, writer names, profiler-instance names) bound by
    any import of memprof — the OB406 matching contract: a name READING
    as the module (bare ``memprof`` / any ``.memprof`` attribute)
    matches by naming convention; the generic names (``reset`` /
    ``sample_once`` / ``PROF``) qualify only when PROVABLY imported
    from memprof, so an unrelated local ``reset`` helper or ``PROF``
    global stays silent."""
    modules, writers, profs = {"memprof"}, set(), set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.rsplit(".", 1)[-1] == "memprof" \
                        and alias.asname:
                    modules.add(alias.asname)
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module.rsplit(".", 1)[-1] == "memprof":
                for alias in node.names:
                    if alias.name in _MEMPROF_WRITERS:
                        writers.add(alias.asname or alias.name)
                    elif alias.name in ("PROF", "HeapProfiler"):
                        profs.add(alias.asname or alias.name)
            else:
                for alias in node.names:
                    if alias.name == "memprof":
                        modules.add(alias.asname or alias.name)
    return modules, writers, profs


def _is_memprof_target(e: ast.expr, module_aliases: set,
                       prof_aliases: set) -> bool:
    """``memprof`` (under any alias) / ``obs.memprof`` /
    ``memprof.PROF`` / a ``PROF`` imported FROM memprof."""
    if isinstance(e, ast.Name):
        return e.id in module_aliases or e.id in prof_aliases
    if isinstance(e, ast.Attribute):
        if e.attr == "memprof":
            return True
        return e.attr == "PROF" \
            and _is_memprof_target(e.value, module_aliases, prof_aliases)
    return False


def _lint_memprof_writes(sf: SourceFile) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    module_aliases, writer_aliases, prof_aliases = \
        _memprof_import_aliases(sf)
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        # (a) a statement-memory key laundered through an accumulator
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else "")
        if name in _MEMPROF_SINKS and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and arg.value in HEAP_KEYS:
                diags.append(Diagnostic(
                    "OB407",
                    f"`{name}({arg.value!r}, ...)` writes a statement-"
                    "memory counter outside obs/memprof.py — only the "
                    "heap profiler's sampler tick may claim heap_kb/"
                    "heap_peak_kb/hbm_bytes (measured, ≤-growth-capped)",
                    sf.path, node.lineno))
                continue
        # (b) a mutating call on the heap-profiler store itself
        hit = (isinstance(f, ast.Attribute)
               and f.attr in _MEMPROF_WRITERS
               and _is_memprof_target(f.value, module_aliases,
                                      prof_aliases)) \
            or (isinstance(f, ast.Name) and f.id in writer_aliases)
        if hit:
            diags.append(Diagnostic(
                "OB407",
                "heap-profiler store write outside obs/memprof.py — "
                "window rotation/eviction accounting belongs to the "
                "sampler",
                sf.path, node.lineno))
    return diags


# ---- OB404: metric-name registry discipline -------------------------------

#: matches the exported metric naming convention; deliberately excludes
#: dotted logger names ("tinysql_tpu.pool") by construction and the bare
#: package name explicitly
_METRIC_NAME_RE = re.compile(r"^tinysql_[a-z0-9_]+$")
_NON_METRIC_NAMES = {"tinysql_tpu"}

#: the registry module itself — where names are DECLARED — is exempt
_REGISTRY_MODULE = "metrics.py"


def _metric_registry() -> Optional[Set[str]]:
    """The live central registry, or None when it cannot be imported
    (lint must degrade to silence, not crash, in a stripped checkout)."""
    try:
        from ..obs.metrics import METRICS
        return set(METRICS)
    except Exception:
        return None


def _imports_tsring(sf: SourceFile) -> bool:
    """Provable tsring import under any form: ``import …obs.tsring [as
    x]``, ``from …obs.tsring import RING``, ``from …obs import tsring
    [as t]``."""
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.rsplit(".", 1)[-1] == "tsring":
                    return True
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.rsplit(".", 1)[-1] == "tsring":
                return True
            for alias in node.names:
                if alias.name == "tsring":
                    return True
    return False


def _lint_metric_names(sf: SourceFile) -> List[Diagnostic]:
    if os.path.basename(sf.path) != "tsring.py" \
            and not _imports_tsring(sf):
        return []
    registry = _metric_registry()
    if registry is None:
        return []
    # f-string fragments are PARTIAL names (f"tinysql_x_{k}_total") —
    # judging them against the registry would be judging half a name
    in_fstring = {id(c) for n in ast.walk(sf.tree)
                  if isinstance(n, ast.JoinedStr) for c in n.values}
    diags: List[Diagnostic] = []
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Constant)
                and isinstance(node.value, str)) \
                or id(node) in in_fstring:
            continue
        name = node.value
        if name in _NON_METRIC_NAMES or name in registry \
                or not _METRIC_NAME_RE.match(name):
            continue
        diags.append(Diagnostic(
            "OB404",
            f"metric name `{name}` is not declared in the central "
            "registry (obs/metrics.METRICS) — the ring drops it at "
            "sample time and no other surface (/metrics, "
            "metrics_summary) will ever know it; declare it there "
            "first", sf.path, node.lineno))
    return diags


def lint_obs_discipline(sf: SourceFile) -> List[Diagnostic]:
    base = os.path.basename(sf.path)
    diags: List[Diagnostic] = []
    # OB403 has its OWN allowlist: the STATS-owning modules are exactly
    # the ones most tempted to push counters at the summary store, so
    # the OB401/OB402 ownership exemption must not cover them here
    if base not in SUMMARY_WRITER_MODULES:
        diags.extend(_lint_summary_writes(sf))
    if base != _REGISTRY_MODULE:
        diags.extend(_lint_metric_names(sf))
    if base not in DEVTIME_OWNING_MODULES:
        diags.extend(_lint_devtime_writes(sf))
    if base != CONPROF_OWNING_MODULE:
        diags.extend(_lint_conprof_writes(sf))
    if base != MEMPROF_OWNING_MODULE:
        diags.extend(_lint_memprof_writes(sf))
    if base != SPAN_TOTALS_OWNING_MODULE:
        diags.extend(_lint_span_totals_writes(sf))
    if base in OWNING_MODULES:
        return sf.filter(diags)
    for node in ast.walk(sf.tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Subscript) and _is_stats_target(t.value):
                diags.append(Diagnostic(
                    "OB401",
                    "direct STATS[...] write — route through "
                    "kernels.stats_add/stats_hwm (per-query scopes and "
                    "/metrics depend on the accessor fan-out)",
                    sf.path, t.lineno))
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _MUTATORS \
                and _is_stats_target(node.func.value):
            diags.append(Diagnostic(
                "OB402",
                f"STATS.{node.func.attr}(...) mutates the counter table "
                "outside its owning module — use the accessors",
                sf.path, node.lineno))
    return sf.filter(diags)

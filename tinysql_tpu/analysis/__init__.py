"""qlint: the static-analysis subsystem.

Three passes over the invariants nothing else checks mechanically:

- **trace-safety** (`trace_safety.py`, TS1xx): AST lint flagging host-sync
  and retrace hazards inside jit-traced regions — np calls / `.item()` /
  scalar coercion on traced values, Python branches on tracers, per-call
  `jax.jit` wrappers that defeat the dispatch cache, unhashable jit-cache
  keys.  A host sync inside a fused program costs a whole extra dispatch
  (~40-70ms on the device link, PERF.md §6), which is exactly the bug
  class "Premature Dimensional Collapse" (PAPERS.md) says silently
  destroys tensor-backend wins.
- **plan-device** (`plan_device.py`, PD2xx): walks PHYSICAL plans after
  placement and verifies the device enforcer's invariants (planner/
  device.py admissibility, CPU-fallback edge shape, EXPLAIN annotation
  consistency).  Runs offline over the SQL corpus in tests/ and as an
  opt-in runtime verifier inside the optimizer (`tidb_qlint_verify`).
- **lock-discipline** (`lock_discipline.py`, LD3xx): infers per-class
  lock-to-field guard maps for the threaded subsystems and flags
  shared-state mutations outside declared lock scopes.
- **obs-discipline** (`obs_discipline.py`, OB4xx): flags direct
  ``STATS[...]`` writes outside the owning device-layer modules — only
  the ``kernels.stats_add``/``stats_hwm`` accessors fan increments out
  to per-query observability scopes (obs/context.py).
- **fail-discipline** (`fail_discipline.py`, FP5xx): retry paths may
  only sleep through ``Backoffer`` (FP501), and every failpoint inject
  site must name a point registered in the ``fail/points.py`` catalogue
  (FP502) so the chaos suite can arm it.
- **concurrency** (`concurrency.py`, CC7xx): the WHOLE-PROGRAM pass —
  thread-root discovery + cross-module reachability, shared-state race
  detection with unified guard inference (CC701, subsuming LD3xx's
  per-class maps), lock-order deadlock cycles (CC702),
  blocking-under-lock (CC703), and context-hop discipline for thread
  spawns (CC704).  Its dynamic twin is ``tools/race_stress.py``.
- **device-flow** (`device_flow.py`, DF8xx): the second WHOLE-PROGRAM
  pass — interprocedural device-array taint from the counted-wrapper
  birth sites, enforcing hidden-host-sync (DF801), uncounted-transfer
  (DF802), progcache-key retrace-hazard (DF803), and device-buffer-
  escape (DF804) discipline over the dispatch-hot reachability set.
  Its dynamic twin is ``tools/transfer_audit.py`` (utils/xferaudit.py
  interposes jax's transfer entry points and reconciles observed
  transfers against the kernels.STATS counters).

Every pass honors inline suppressions with REQUIRED justification text:

    something_hazardous()  # qlint: disable=TS101 -- post-download host copy

See docs/LINT.md and tools/lint.py.
"""
from .concurrency import lint_concurrency, thread_roots
from .device_flow import lint_device_flow
from .diag import (Diagnostic, Severity, SourceFile, format_diagnostics,
                   gather_sources)
from .fail_discipline import lint_fail_discipline
from .lock_discipline import lint_lock_discipline
from .obs_discipline import lint_obs_discipline
from .plan_device import PlanDeviceError, check_plan, verify_plan
from .trace_safety import lint_trace_safety

__all__ = [
    "Diagnostic", "Severity", "SourceFile", "format_diagnostics",
    "gather_sources", "lint_trace_safety", "lint_lock_discipline",
    "lint_obs_discipline", "lint_fail_discipline", "lint_concurrency",
    "lint_device_flow", "thread_roots", "check_plan", "verify_plan",
    "PlanDeviceError",
]

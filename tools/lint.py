#!/usr/bin/env python
"""qlint CLI — run the static-analysis passes (see docs/LINT.md).

Usage:
    python tools/lint.py [--strict] [--json]
                         [--pass trace|locks|obs|fail|conc|devflow|plans|all]
                         [--rules] [--fuzz-n N] [paths...]

- `--strict` (the CI entry point): run every pass over its default scope
  and exit non-zero on any violation.
- `--pass trace|locks|...` over explicit paths: lint just those files.
  `conc` and `devflow` are WHOLE-PROGRAM: all given paths form one
  analysis batch (default: the entire package).
- `--pass plans`: plan the SQL corpus (tests/test_sql.py statement
  replay + tests/test_sqlite_diff.py's seeded generator) with the TPU
  tier enabled and check every placed plan's device invariants.
- `--json`: machine-readable report on stdout (CI annotation feed)
  instead of the human text.
- `--rules`: print the rule catalogue.

Exit status: 0 clean, 1 violations, 2 usage/internal error — distinct,
so CI can tell "findings" from "the linter itself broke" without
grepping text.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# the analysis imports engine modules and needs no accelerator: keep it
# off one unless the caller names a platform (tests force cpu too)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: modules whose threading model the lock-discipline pass enforces
LOCK_SCOPE = [
    "tinysql_tpu/ddl/owner.py",
    "tinysql_tpu/ddl/worker.py",
    "tinysql_tpu/domain/domain.py",
    "tinysql_tpu/server/server.py",
    "tinysql_tpu/kv/rpc.py",
    "tinysql_tpu/executor/devpipe.py",  # BlockPipeline staging queue
]

#: retry-path scope of the fail-discipline pass (FP5xx): where raw
#: time.sleep is banned outside Backoffer and where failpoint inject
#: sites must name a registered catalogue entry
FAIL_SCOPE = [
    "tinysql_tpu/kv",
    "tinysql_tpu/distsql",
    "tinysql_tpu/ddl",
    "tinysql_tpu/ops",
    "tinysql_tpu/executor",
    "tinysql_tpu/session",
    "tinysql_tpu/fail",
]


def _force_cpu_backend() -> None:
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass


def run_trace(paths):
    from tinysql_tpu.analysis import gather_sources, lint_trace_safety
    diags = []
    for p in paths:
        for sf in gather_sources(p):
            diags.extend(sf.check_suppression_syntax())
            diags.extend(lint_trace_safety(sf))
    return diags


def run_locks(paths):
    from tinysql_tpu.analysis import gather_sources, lint_lock_discipline
    diags = []
    for p in paths:
        for sf in gather_sources(p):
            diags.extend(sf.check_suppression_syntax())
            diags.extend(lint_lock_discipline(sf))
    return diags


def run_obs(paths):
    from tinysql_tpu.analysis import gather_sources, lint_obs_discipline
    diags = []
    for p in paths:
        for sf in gather_sources(p):
            diags.extend(sf.check_suppression_syntax())
            diags.extend(lint_obs_discipline(sf))
    return diags


def run_fail(paths):
    from tinysql_tpu.analysis import gather_sources, lint_fail_discipline
    diags = []
    for p in paths:
        for sf in gather_sources(p):
            diags.extend(sf.check_suppression_syntax())
            diags.extend(lint_fail_discipline(sf))
    return diags


def run_conc(paths):
    """Whole-program CC7xx: every file under every given path joins ONE
    analysis batch (cross-module races only exist in the union)."""
    from tinysql_tpu.analysis import gather_sources, lint_concurrency
    batch = []
    for p in paths:
        batch.extend(gather_sources(p))
    diags = []
    for sf in batch:
        diags.extend(sf.check_suppression_syntax())
    diags.extend(lint_concurrency(batch))
    return diags


def run_devflow(paths):
    """Whole-program DF8xx: one batch, like conc — device taint crosses
    modules (a helper returning a device array taints its callers) and
    the dispatch-hot set is a reachability closure over the union."""
    from tinysql_tpu.analysis import gather_sources, lint_device_flow
    batch = []
    for p in paths:
        batch.extend(gather_sources(p))
    diags = []
    for sf in batch:
        diags.extend(sf.check_suppression_syntax())
    diags.extend(lint_device_flow(batch))
    return diags


def run_plans(fuzz_n=None):
    _force_cpu_backend()
    from tinysql_tpu.analysis.plan_device import check_corpus
    return check_corpus(REPO_ROOT, fuzz_queries=fuzz_n)


def _emit_json(diags, passes, error: str = "") -> None:
    payload = {
        "clean": not diags and not error,
        "count": len(diags),
        "passes": sorted(passes),
        "violations": [{"rule": d.rule, "path": d.path, "line": d.line,
                        "col": d.col, "severity": d.severity,
                        "message": d.message} for d in diags],
    }
    if error:
        payload["error"] = error
    print(json.dumps(payload, indent=2, sort_keys=True))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="qlint", description=__doc__)
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the package)")
    ap.add_argument("--strict", action="store_true",
                    help="run all passes over their default scopes")
    ap.add_argument("--pass", dest="passes", action="append",
                    choices=["trace", "locks", "obs", "fail", "conc",
                             "devflow", "plans", "all"],
                    help="which pass(es) to run (default: trace+locks+obs"
                         "+fail+conc over paths; all under --strict)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable JSON report on stdout")
    ap.add_argument("--rules", action="store_true",
                    help="print the rule catalogue and exit")
    ap.add_argument("--fuzz-n", type=int, default=None,
                    help="fuzz-corpus query count for the plans pass "
                         "(default: the test suite's own N_QUERIES)")
    args = ap.parse_args(argv)

    from tinysql_tpu.analysis import format_diagnostics
    from tinysql_tpu.analysis.diag import RULES

    if args.rules:
        for code in sorted(RULES):
            print(f"{code}  {RULES[code]}")
        return 0

    passes = set(args.passes or [])
    if args.strict or "all" in passes:
        passes = {"trace", "locks", "obs", "fail", "conc", "devflow",
                  "plans"}
    elif not passes:
        passes = {"trace", "locks", "obs", "fail", "conc"}

    pkg = os.path.join(REPO_ROOT, "tinysql_tpu")
    paths = args.paths or [pkg]
    diags = []
    try:
        for p in paths:
            if not os.path.exists(p):
                raise FileNotFoundError(f"no such path: {p}")
        if "trace" in passes:
            diags.extend(run_trace(paths))
        if "locks" in passes:
            lock_paths = (args.paths if args.paths
                          else [os.path.join(REPO_ROOT, p)
                                for p in LOCK_SCOPE])
            diags.extend(run_locks(lock_paths))
        if "obs" in passes:
            diags.extend(run_obs(paths))
        if "fail" in passes:
            fail_paths = (args.paths if args.paths
                          else [os.path.join(REPO_ROOT, p)
                                for p in FAIL_SCOPE])
            diags.extend(run_fail(fail_paths))
        if "conc" in passes:
            diags.extend(run_conc(paths))
        if "devflow" in passes:
            diags.extend(run_devflow(paths))
        if "plans" in passes:
            diags.extend(run_plans(args.fuzz_n))
    except Exception as e:  # the linter itself broke: exit 2, not 1
        msg = f"{type(e).__name__}: {e}"
        if args.json:
            _emit_json(diags, passes, error=msg)
        else:
            print(f"qlint: internal error: {msg}", file=sys.stderr)
        return 2

    if args.json:
        _emit_json(diags, passes)
        return 1 if diags else 0
    if diags:
        print(format_diagnostics(diags))
        return 1
    print("qlint: clean "
          f"({'+'.join(sorted(passes))} over {len(paths)} path(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())

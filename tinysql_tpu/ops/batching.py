"""Cross-query micro-batching: the same-digest coalescer core.

PR 6's literal parameterization made every query in a normalized-SQL
digest family share ONE compiled program with its constants as runtime
operands (exprjit.ParamTable).  This module supplies the other half of
the serving win: when several concurrently-admitted statements belong
to the same WARM family, the statement pool (server/pool.py) executes
them as one *batch round* — N ParamTables through one compiled program
in a single back-to-back device round — instead of N independent,
interleaved dispatches.

Protocol (driven by the pool's worker thread; all members run
sequentially on it):

1. **collect** — each member statement executes normally under the
   round's collect scope.  When its fused aggregate reaches the device
   dispatch boundary with a params-compiled dev mask AND the program
   already warm (ops/kernels.py fused entries, ``batchable=True`` call
   sites), it *parks*: the round captures ``(program key, cached
   program, non-param args, this member's params)`` and the statement
   aborts with :class:`Parked` (invisible to observability — the
   session skips the obs fan-out for parked attempts).  Members whose
   statements never reach a batchable dispatch (host paths, cold
   programs, non-SELECTs) simply COMPLETE during collect: transparent
   solo fallback.
2. **dispatch** — the round groups parked members by (program key,
   staged-array identity) and, when ``tidb_batch_stack_max`` allows it
   and >= 2 members' ParamTables share a slot layout, STACKS them on a
   leading batch axis (exprjit.ParamTable.stack) and runs ONE
   ``jax.vmap``-batched program variant (kernels.stacked_variant,
   registered under the base key extended with a power-of-two occupancy
   bucket B — occupancy 3 rides the B=4 program with an inert padding
   row): the whole group costs one XLA dispatch, and packed outputs
   download in one transfer.  Groups that cannot stack (stacking off,
   layout mismatch, no stacking recipe on the program, singleton
   leftovers) run the legacy back-to-back leg — one ParamTable replay
   per member (zero compiles either way: park only happens on warm
   programs).  Each dispatch leg runs inside a CAPTURE observability
   scope; its device counters (dispatches, device_s, transfer bytes)
   are split across the members it served — occupancy-weighted for a
   stacked group, exact for a solo replay — so statements_summary and
   EXPLAIN ANALYZE stay truthful and member shares sum to the global
   counters.  Its spans (``dispatch``, ``drain``) belong to no one
   member: they go to the process's tracer, under the leg's
   ``round.dispatch`` / ``round.stack`` span.
3. **replay** — each parked member re-executes; at the same boundary it
   *consumes* its precomputed device output (matched by program key +
   the identity of the staged device arrays + its own param bytes) and
   the rest of the statement — unpack, d2h, result assembly,
   observability — runs normally in the member's own scope.  A consume
   miss (the replica rotated between phases, plan re-placed, ...)
   falls through to a plain solo dispatch: batching is an optimization,
   never a correctness dependency.

Family eligibility is learned, not declared: the session's statement
close hook calls :func:`note_family` for statements that executed a
batchable fused dispatch (the ``batchable`` obs marker), and the pool
only forms rounds for digests seen here.

Counter-write discipline: ``STATS`` is written only through this
module's accessors (qlint OB401/OB402 — batching.py is an owning
module).
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
from typing import Dict, List, Optional

from ..obs import context as _obs

#: process-total coalescing counters (exported to /metrics and the
#: serve bench): batches = rounds that dispatched >= 1 parked member,
#: batched_statements = members served from a round dispatch,
#: occupancy_sum / batches = average batch occupancy, parks / replays
#: the protocol legs, fallbacks = replay consume misses (solo re-dispatch)
#: dispatch_s_sum accumulates wall seconds inside round dispatch legs
#: (exported as tinysql_batch_dispatch_seconds_total: the device-side
#: half of a batched member's wait attribution).  The stacked leg:
#: stacked_rounds = groups served by ONE vmap-batched dispatch,
#: stacked_statements = members inside them, stacked_occupancy_sum /
#: stacked_rounds = average stacked occupancy, stack_fallbacks = groups
#: that fell back to the legacy back-to-back leg (layout mismatch, no
#: stacking recipe, stacked dispatch error)
STATS = {"batches": 0, "batched_statements": 0, "occupancy_sum": 0,
         "parks": 0, "replays": 0, "fallbacks": 0, "dispatch_s_sum": 0.0,
         "stacked_rounds": 0, "stacked_statements": 0,
         "stacked_occupancy_sum": 0, "stack_fallbacks": 0}
_stats_mu = threading.Lock()


def _stat_add(key: str, n: int = 1) -> None:
    with _stats_mu:
        STATS[key] = STATS.get(key, 0) + n


def stats_snapshot() -> Dict[str, int]:
    with _stats_mu:
        return dict(STATS)


def reset_stats() -> None:
    """Tests only."""
    with _stats_mu:
        for k in STATS:
            STATS[k] = 0


class Parked(Exception):
    """Control-flow signal of the collect leg: the statement reached a
    batchable warm dispatch and its params were captured.  Never
    surfaces to clients — only the pool's batch driver catches it, and
    the session skips the statement fan-out for parked attempts (their
    spans go to the process's tracer, under ``round.collect``)."""


class _ParkedDispatch:
    __slots__ = ("key", "fn", "args", "arg_ids", "params_key", "params",
                 "out", "share")

    def __init__(self, key, fn, args, params):
        self.key = key
        self.fn = fn
        self.args = args            # positional device args WITHOUT params
        self.arg_ids = _leaf_ids(args)
        self.params = params        # the member's (pi, pf) host vectors
        self.params_key = _params_key(params)
        self.out = None             # ("dev"|"host", payload) once served
        self.share = None           # this member's device-counter share


def _params_key(params) -> bytes:
    pi, pf = params
    return bytes(memoryview(pi).cast("B")) + b"|" + \
        bytes(memoryview(pf).cast("B"))


def _leaf_ids(x) -> tuple:
    """Structural identity of a dispatch's non-param arguments: the
    executor rebuilds its ``dev_cols`` list (and the (values, null)
    tuples in it) per execution, but the LEAF device arrays are
    replica-memoized — the same objects across a family's queries until
    a write invalidates the replica.  Matching on leaf ids is exactly
    the guard batching needs: a replica rotation between the collect and
    replay legs changes the leaves, the consume misses, and the member
    falls back to a solo dispatch over the fresh data."""
    if x is None:
        return ("~",)
    if isinstance(x, (list, tuple)):
        out = ["("]
        for v in x:
            out.extend(_leaf_ids(v))
        out.append(")")
        return tuple(out)
    return (id(x),)


@contextlib.contextmanager
def _capture_scope():
    """A throwaway QueryObs installed around one round dispatch leg:
    counted_jit / d2h / h2d report into it like into any statement
    scope, and the collected totals become the served members'
    attribution shares (the replay-side consume records them into each
    member's own scope).  Without it the whole round's device_s and
    transfer bytes would land on no statement at all — the pool worker
    drives the dispatch leg outside every member context.  Its spans
    are the process's."""
    cap = _obs.QueryObs()
    cap.tracer = _obs.PROCESS
    tok = _obs.activate(cap)
    try:
        yield cap
    finally:
        _obs.deactivate(tok)


class BatchRound:
    """One coalesced group's shared state across collect/dispatch/replay.
    Used from the single pool worker thread driving the group (members
    run sequentially), so no internal locking is needed beyond the
    global counters.  ``stack_max`` is the live ``tidb_batch_stack_max``
    value (0/1 = legacy back-to-back only; >= 2 caps how many members
    one stacked dispatch may carry)."""

    def __init__(self, stack_max: int = 0):
        self.collecting = False
        self.replaying = False
        self.stack_max = max(int(stack_max), 0)
        #: groups the dispatch leg served with ONE stacked dispatch
        self.stacked_groups = 0
        #: what the replay leg now running found: ``hit`` / ``miss``
        #: (the pool resets it to ``none`` before each member's replay)
        self.consumed = "none"
        self._parked: List[_ParkedDispatch] = []
        #: (key, arg_ids, params_key) -> [(out, share)]: a LIST because
        #: concurrent clients legitimately submit IDENTICAL statements —
        #: each member consumes one stored output
        self._results: Dict[tuple, list] = {}

    # ---- collect ---------------------------------------------------------
    def park(self, key, fn, args, params) -> None:
        """Capture one member's dispatch and abort its collect execution
        (raises :class:`Parked`)."""
        self._parked.append(_ParkedDispatch(key, fn, args, params))
        _stat_add("parks")
        raise Parked()

    @property
    def parked_count(self) -> int:
        return len(self._parked)

    # ---- dispatch --------------------------------------------------------
    def dispatch(self) -> int:
        """Serve every parked member: same-program/same-data groups of
        >= 2 layout-compatible members go through ONE stacked-params
        vmap dispatch (``stack_max`` permitting), everything else
        replays back-to-back through the captured solo program.
        Returns the round's occupancy (members served).  Zero compiles
        by construction on warm paths — park only happens on
        progcache-warm programs, and the stacked variants are
        prewarmable (kernels.prewarm_stacked).  A member whose dispatch
        raises (device loss, injected fault) simply has no stored
        result: its replay consume misses and the solo re-dispatch
        surfaces the error through the statement's own degradation
        path."""
        import time as _time
        t0 = _time.perf_counter()
        groups: Dict[tuple, list] = {}
        order: List[tuple] = []
        for p in self._parked:
            k = (p.key, p.arg_ids)
            if k not in groups:
                groups[k] = []
                order.append(k)
            groups[k].append(p)
        occ = 0
        with _obs.process_span("round.dispatch", cat="serving",
                               groups=len(order)) as leg:
            for k in order:
                members = groups[k]
                while members:
                    chunk = members[: max(self.stack_max, 1)]
                    members = members[len(chunk):]
                    if len(chunk) >= 2 and self._dispatch_stacked(chunk):
                        occ += len(chunk)
                        continue
                    for p in chunk:
                        occ += self._dispatch_solo(p)
            leg.args["occupancy"] = occ
        if occ:
            _stat_add("batches")
            _stat_add("batched_statements", occ)
            _stat_add("occupancy_sum", occ)
            _stat_add("dispatch_s_sum", _time.perf_counter() - t0)
        return occ

    def _store(self, p: _ParkedDispatch, out, share: dict) -> None:
        p.out = out
        p.share = share
        self._results.setdefault(
            (p.key, p.arg_ids, p.params_key), []).append((out, share))

    def _dispatch_solo(self, p: _ParkedDispatch) -> int:
        """Legacy back-to-back leg: one ParamTable replay through the
        member's captured solo program.  The capture scope's totals are
        this member's EXACT attribution (the whole dispatch served only
        it) — including any sampled device_s, so the profiler's measured
        time lands on the member that caused it, not on whoever
        dispatched the round."""
        from . import kernels
        try:
            with _capture_scope() as cap:
                out = p.fn(*p.args, kernels._params_dev(p.params))
        except Exception:
            return 0
        self._store(p, ("dev", out), cap.device_totals())
        return 1

    def _dispatch_stacked(self, chunk: List[_ParkedDispatch]) -> bool:
        """ONE dispatch for the whole chunk: stack the members'
        ParamTables on a leading batch axis padded to the occupancy
        bucket, run the B-stacked program variant, and split the output
        per member — packed outputs download as one [B, L] transfer
        here (host rows, no further d2h at replay), tree outputs slice
        off axis 0 on device.  The capture scope's totals are divided
        by the chunk's occupancy: each member's share of the one
        dispatch.  Any failure (layout mismatch, no stacking recipe,
        dispatch error) returns False and the chunk falls back to the
        legacy leg — stacking is an optimization, never a correctness
        dependency."""
        from . import kernels
        from .exprjit import ParamTable
        p0 = chunk[0]
        n = len(chunk)
        bucket = kernels.occupancy_bucket(n)
        with _obs.process_span("round.stack", cat="serving",
                               bucket=bucket, n=n) as sp:
            try:
                ent = kernels.stacked_variant(p0.key, p0.fn, bucket)
                if ent is None:
                    _stat_add("stack_fallbacks")
                    return False
                vfn, kind, schema = ent
                sp.args["kind"] = kind
                stacked = ParamTable.stack([p.params for p in chunk],
                                           bucket)
            except Exception:
                _stat_add("stack_fallbacks")
                return False
            try:
                with _capture_scope() as cap:
                    res = vfn(*p0.args, kernels._params_dev(stacked))
                    if kind == "packed":
                        rows = kernels.d2h_many(list(res))
            except Exception:
                _stat_add("stack_fallbacks")
                return False
        totals = cap.device_totals()
        # occupancy split (shardops.split_exact): integer counters split
        # as integers and sum to the round's totals exactly, real-valued
        # ones to float rounding — per-member (and, for sharded programs,
        # per-shard) attribution reconciles with the global counters
        from . import shardops
        shares = shardops.split_exact(totals, n)
        if shardops.shards_of_key(p0.key) > 1:
            shardops.note_stacked_round()
        tree_map = kernels.jax().tree_util.tree_map
        for i, p in enumerate(chunk):
            if kind == "packed":
                out = ("host", (rows[0][i], rows[1][i]))
            else:
                out = ("dev", tree_map(lambda x, i=i: x[i], res))
            self._store(p, out, shares[i])
        self.stacked_groups += 1
        _stat_add("stacked_rounds")
        _stat_add("stacked_statements", n)
        _stat_add("stacked_occupancy_sum", n)
        return True

    # ---- replay ----------------------------------------------------------
    def consume(self, key, args, params):
        """The replay-side lookup: this member's precomputed
        ``(tag, output)``, or None when the capture no longer matches
        (fall back to a solo dispatch).  A hit records the member's
        attribution share — its occupancy-weighted slice of the round
        dispatch's device counters — into the member's own live scope,
        so summing statements_summary across members reconciles with
        the global counters."""
        outs = self._results.get(
            (key, _leaf_ids(args), _params_key(params)))
        self.consumed = "hit" if outs else "miss"
        if outs:
            _stat_add("replays")
            out, share = outs.pop()
            for k, v in share.items():
                _obs.record(k, v)
            _obs.record("coalesced", 1)
            return out
        _stat_add("fallbacks")
        return None


_ROUND: contextvars.ContextVar = contextvars.ContextVar(
    "tinysql_batch_round", default=None)


def activate(rnd: Optional[BatchRound]):
    return _ROUND.set(rnd)


def deactivate(token) -> None:
    _ROUND.reset(token)


def current() -> Optional[BatchRound]:
    return _ROUND.get()


def active() -> bool:
    """True while a batch round's collect or replay leg drives THIS
    context — executors use it to prefer the batchable fused paths over
    per-member-only variants (device passthrough) so a round's members
    park and consume along the same route."""
    rnd = _ROUND.get()
    return rnd is not None and (rnd.collecting or rnd.replaying)


# ---- family registry (learned batch eligibility) --------------------------

#: normalized-SQL digests whose statements executed a batchable fused
#: dispatch (dev-mask + params, single-shot path).  Bounded: serving
#: works with O(active digest families).
_FAM_MAX = 512
_fam_mu = threading.Lock()
_FAMILIES: Dict[str, int] = {}


def note_family(sql_digest: str) -> None:
    """Mark a digest family batchable (called from the session statement
    close hook for statements that recorded the ``batchable`` marker)."""
    if not sql_digest:
        return
    with _fam_mu:
        if len(_FAMILIES) >= _FAM_MAX and sql_digest not in _FAMILIES:
            _FAMILIES.pop(next(iter(_FAMILIES)))
        _FAMILIES[sql_digest] = _FAMILIES.get(sql_digest, 0) + 1


def family_batchable(sql_digest: str) -> bool:
    with _fam_mu:
        return sql_digest in _FAMILIES


def have_families() -> bool:
    """Cheap pre-check so the pool skips per-statement SQL
    normalization until at least one batchable family exists."""
    with _fam_mu:
        return bool(_FAMILIES)


def reset_families() -> None:
    """Tests only."""
    with _fam_mu:
        _FAMILIES.clear()

"""Device-resident query pipelines, fused into ONE XLA program.

The TPU-first replacement for per-operator host round-trips: a supported
physical subtree (scans -> filters -> partial aggregates -> joins ->
topn/sort/limit/projection) compiles into a SINGLE jitted device program.
Intermediates never exist outside the XLA program (they are fusion
candidates for the compiler, not buffers); the only device->host transfer
of a query is the packed materialization of the final (usually tiny)
result, and for small results even the packing runs inside the same
program — one dispatch, one download.  This replaces the reference's
executor pipeline hot loops (probe loop executor/join.go:325, agg update
aggregate.go:307+) with gather/segment kernels, and its row-at-a-time
operator hand-off with masked static-shape device views.

Why fusion matters here: the device link billed ~40-70ms per program
dispatch (2026-07-29, an earlier attachment; PERF.md §6); round 2 ran Q3
as five chained programs and paid that five times.  Round 3 splits every node into host-side
``prepare`` (replica uploads, group indexes, position tables, parameter
tables — all memoized per replica version) and a pure traced ``emit``;
DevPipeExec composes the emits and jits the whole pipeline once per
(structure, shape) key.

Key design points (why this maps well onto TPU + XLA):

- **Static shapes everywhere.**  Every view is padded to a power-of-two
  bucket with a validity mask; data-dependent sizes never force a host
  sync or a recompile.  One program per (shape, structure) pair, reused
  across queries and constants (constants ride exprjit.ParamTable slots
  passed as runtime inputs).
- **Group index** (sort once per replica version, not per query): the
  high-cardinality GROUP BY path sorts the table by key ONCE, memoizes
  the order/boundaries on the replica (the clustered-index analogue of
  the reference's index access paths), and then a per-query aggregate
  reads its lanes in that order (a table stored in its key's order as
  it lies, any other permuted on the host once a replica version):
  mask -> prefix sum -> boundary-diff, no gather to sorted order: exact for
  int64 (mod-2^64 wrap); for float64 the boundary diff folds the running
  prefix-sum's rounding into each group (error ~ eps x running total),
  bounded by the 1e-6-relative result-equality tests.  No per-query sort
  or scatter either way.
- **Join = dense position table + gather** (SURVEY §2.4: "build via
  scatter, probe via gather"): a unique build side keyed by a bounded
  int64 key becomes a dense key->row table (memoized on the replica for
  base-table keys; static per replica version for group-index keys);
  probing is one gather + validity checks.  Non-unique build sides ride
  the same group index as a CSR layout (sorted order + group boundaries,
  reference join.go:244 / util/mvmap multiplicity semantics): probe maps
  key -> group, per-group valid counts come from one cumsum, and a
  two-phase expansion (scatter row starts + running-max fill) lands the
  variable-multiplicity output in a static bucket sized by a host-side
  upper bound.
- **A join's view as a build side.**  A unique single-key join (and a
  semi join) leaves its probe side's rows where they were, so its output
  can be the build side of the join above it: that join probes the
  key -> row table of the view's OWN probe side and reads the view's
  validity (``_view_build_key``).  TPC-H Q5 probes ``lineitem`` into
  ``(orders join customer)`` and ``supplier`` into ``(nation semi
  region)`` this way, one program.
- **GROUP BY above a chain without a sort** (``_KeyGroupNode``): on one
  key of a bounded range a group is a slot, and of several GROUP BY
  columns that are one table's, its primary key among them, the key
  alone forms the groups and the rest are fetched for the groups that
  exist — never gathered at the chain's bucket.
- Strings ride order-preserving dictionary codes on device (decode on
  materialize only), so string group keys, sort keys, and equality
  filters all stay on the TPU.
"""
from __future__ import annotations

import contextvars
import functools
import os
import queue
import threading
import time

from typing import Callable, Dict, List, Optional

import numpy as np

from .. import fail as _fail
from ..obs import context as _obs
from ..utils import interrupt as _interrupt

from ..chunk import Chunk, Column as CCol
from ..chunk.column import _np_dtype
from ..expression import Column as ExprColumn, Constant
from ..expression.aggregation import AGG_COUNT, AGG_SUM
from ..mytypes import EvalType
from ..ops import exactcmp, kernels, progcache
from ..ops.exprjit import (DEAD, ParamTable, compile_expr_params,
                           is_jittable, stable_shape_key)
from ..planner.physical import (PhysicalHashAgg, PhysicalHashJoin,
                                PhysicalLimit, PhysicalMergeJoin,
                                PhysicalProjection, PhysicalSelection,
                                PhysicalSort, PhysicalTableReader,
                                PhysicalTopN)

MAX_DENSE_RANGE = 1 << 25   # dense key->pos tables up to 32M slots (128MB)
MAX_EXPAND = 1 << 23        # CSR-join output bucket cap (8M rows)

# structural node keys that have actually been compiled into some fused
# pipeline — introspection surface for tests and the multichip dryrun.
# Guarded (qlint CC7xx triage): concurrent pool workers and the prewarm
# worker both publish keys; set.update over an iterable is NOT atomic
_CNK_MU = threading.Lock()
COMPILED_NODE_KEYS: set = set()


def _note_compiled(kparts) -> None:
    with _CNK_MU:
        COMPILED_NODE_KEYS.update(kparts)


# =========================================================================
# async block pipeline: host-staging / device-compute overlap
# =========================================================================

def pipeline_depth(session_vars=None) -> int:
    """Staging-queue depth for the async block pipeline: how many staged
    blocks may be in flight ahead of the consumer (the double-buffer
    bound on transient device slots).  0 = synchronous inline staging —
    no thread, the exact sequential order, byte-identical results.
    Resolution: TINYSQL_PIPELINE_DEPTH env (tests/CI kill-switch) >
    tidb_pipeline_depth sysvar > default 2 (double-buffered)."""
    env = os.environ.get("TINYSQL_PIPELINE_DEPTH")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            return 0
    if session_vars is not None:
        try:
            return max(0, int(session_vars.get("tidb_pipeline_depth", 2)
                              or 0))
        except Exception:
            return 2
    return 2


#: end-of-stream marker on the staging queue
_PIPE_DONE = object()


class BlockPipeline:
    """Bounded-depth staging queue: ONE producer thread runs
    ``stage_fn(item)`` for each item IN ORDER — the host half of a block
    (slice, decode/encode, pad, enqueue the H2D upload) — while the
    consumer iterates the staged results in the same order and dispatches
    device compute.  With JAX's async dispatch the device runs block k's
    kernel while the stage thread prepares block k+1's uploads; the only
    sync points are each block's result materialization (the drain).

    ``depth <= 0`` degrades to synchronous inline staging with NO thread:
    the same calls in the same order, so results are byte-identical with
    the pipeline on or off (the TINYSQL_PIPELINE_DEPTH=0 contract).

    Error contract: an exception inside ``stage_fn`` is captured, the
    producer stops, and the exception re-raises ON THE CALLER at the
    point the failed block would have been consumed — blocks staged
    before it still deliver.  Abandoning the iterator (break / caller
    exception) cancels the producer and joins the thread; ``close()`` is
    idempotent.  Host syncs inside ``stage_fn`` defeat the overlap —
    qlint TS106 flags them statically."""

    def __init__(self, stage_fn: Callable, items, depth: int = 2):
        self._stage = stage_fn
        self._items = list(items)
        self._sync = depth <= 0
        self._mu = threading.Lock()
        self._stage_s = 0.0
        self._hwm = 0
        self._cancel = threading.Event()
        self._q = None
        self._thread = None
        if not self._sync:
            self._q = queue.Queue(maxsize=max(1, depth))
            # the producer runs inside a COPY of the creator's context:
            # the active QueryObs scope, current-operator attribution,
            # and span parent all carry across the thread boundary, so
            # stage spans/counters land on the query (and operator) that
            # built the pipeline (obs/context.py)
            cctx = contextvars.copy_context()
            # "devpipe-stage" is the conprof role vocabulary
            # (obs/conprof.ROLE_PREFIXES): the producer classifies as
            # role `devpipe` in continuous_profiling / race-stress /
            # py-spy output
            self._thread = threading.Thread(
                target=cctx.run, args=(self._run,),
                name="devpipe-stage", daemon=True)
            self._thread.start()

    def _stage_timed(self, item):
        t0 = time.time()
        # both run inside the creator's copied context: a statement kill
        # or deadline stops the producer between blocks, and the staging
        # failpoint exercises the error-delivery contract below
        _interrupt.check()
        _fail.inject("devpipeStageError")
        with _obs.span("stage", cat="pipeline"):
            out = self._stage(item)
        dt = time.time() - t0
        with self._mu:
            self._stage_s += dt
        return out

    # ---- producer -------------------------------------------------------
    def _run(self) -> None:
        try:
            for item in self._items:
                if self._cancel.is_set():
                    return
                out = self._stage_timed(item)
                if not self._put((out, None)):
                    return
        except BaseException as exc:  # delivered to the consumer
            self._put((None, exc))
            return
        self._put(_PIPE_DONE)

    def _put(self, entry) -> bool:
        """Cancellation-aware bounded put: a consumer that stopped
        pulling must never leave this thread parked on a full queue."""
        while not self._cancel.is_set():
            try:
                self._q.put(entry, timeout=0.05)
            except queue.Full:
                continue
            with self._mu:
                self._hwm = max(self._hwm, self._q.qsize())
            return True
        return False

    # ---- consumer -------------------------------------------------------
    def __iter__(self):
        if self._sync:
            for item in self._items:
                yield self._stage_timed(item)
            return
        try:
            while True:
                entry = self._q.get()
                if entry is _PIPE_DONE:
                    break
                out, exc = entry
                if exc is not None:
                    raise exc
                yield out
        finally:
            self.close()

    def close(self) -> None:
        """Cancel the producer and join its thread (idempotent)."""
        self._cancel.set()
        if self._thread is None:
            return
        while True:  # wake a producer parked on a full queue
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10)

    def stats(self) -> dict:
        """{"blocks", "stage_s", "depth_hwm"} — feed kernels.pipe_record
        AFTER the consumer loop (the producer is joined by then)."""
        with self._mu:
            return {"blocks": len(self._items),
                    "stage_s": self._stage_s,
                    "depth_hwm": self._hwm}


class _PipeBuilder:
    """Collects the fused program's runtime inputs and structural cache
    key while the node tree prepares.  Input ORDER is deterministic for a
    given key (prepare is a deterministic tree walk), so a cache-hit
    pipeline can re-bind fresh inputs positionally.  Under a mesh each
    input carries the layout its program asks for (parallel/dist.py
    ``rows`` / ``whole``; None on one device and for host arrays).
    ``lparts``: the live set of every node that leaves a slot dead
    (column liveness, below) and the null lanes a join leaves out
    (NULL-freedom, below), beside the node keys and part of the
    program's: one plan shape with two consumers is two programs.
    ``const_nulls``: the null-lane gathers and argument counts the
    program leaves out."""
    __slots__ = ("inputs", "layouts", "kparts", "lparts", "const_nulls")

    def __init__(self):
        self.inputs: List = []
        self.layouts: List = []
        self.kparts: List = []
        self.lparts: List = []
        self.const_nulls = 0

    def add(self, arr, layout=None) -> int:
        self.inputs.append(arr)
        self.layouts.append(layout)
        return len(self.inputs) - 1

    def lane(self, rep, key, build_np, layout=None) -> int:
        """A replica-memoized upload (:func:`_dev_upload`) as an input."""
        return self.add(_dev_upload(rep, key, build_np, layout), layout)

    def params(self, pt: ParamTable):
        pi, pf = pt.arrays()
        return self.add(pi), self.add(pf)

    def key(self, part, live=None, n: int = 0, nonnull=()) -> None:
        """``live``: the node's live slots of its ``n``.  Noted only where
        some slot is dead, and beside the node keys: those, and the key
        of a program whose consumer reads everything, stay what they
        were.  ``nonnull``: the build slots whose null lanes a join does
        not gather, noted (and counted) the same way."""
        if live is not None and len(live) < n:
            self.lparts.append((len(self.kparts), tuple(sorted(live))))
        if nonnull:
            self.lparts.append((len(self.kparts), "nonnull",
                                tuple(sorted(nonnull))))
            self.const_nulls += len(nonnull)
        self.kparts.append(part)


class _TView:
    """Trace-time view: ``emit(args) -> (valid, [(vals, null), ...])``
    over the fused program's positional inputs, plus the host-side
    column metadata (ret_type, string decode table) and bucket size.
    A slot its consumer did not ask for (``prepare``'s ``live``) holds
    ``exprjit.DEAD`` in place of a pair; ``meta`` lists every slot.
    ``nonnull``: the slots that hold no NULL on any row ``valid`` keeps
    (NULL-freedom, below); a node that proves nothing says none."""
    __slots__ = ("emit", "nb", "meta", "nonnull")

    def __init__(self, emit: Callable, nb: int, meta: List[tuple],
                 scope: str, nonnull=frozenset()):
        # every operation a node traces carries the node's kind in its
        # op_name (a child's emit runs inside its parent's: scopes nest)
        def scoped(args):
            with kernels.jax().named_scope(scope):
                return emit(args)
        self.emit = scoped
        self.nb = nb
        self.meta = meta
        self.nonnull = frozenset(nonnull)


# =========================================================================
# column liveness: a program computes a column only if its consumer reads it
# =========================================================================
#
# Every node's ``prepare(pb, live)`` takes the slots of ITS output view
# that its parent reads (None: all of them) and asks its children for what
# it reads itself: a join gathers only live build columns, a TopN carries
# only live ones through its window, an aggregate computes only live
# sums.  The set comes from the statement (``DevPipeExec.live``: what the
# operator above the fused program references), never from an option.
# Nodes whose columns are the program's inputs as they lie (the leaves)
# hand every one over: an input nobody reads costs no operation.

def _live_set(live, n: int) -> frozenset:
    """``live`` over a view of ``n`` slots as a set (None: every slot)."""
    return frozenset(range(n)) if live is None else frozenset(live)


def _slots_read(exprs) -> set:
    """The child slots a node's own expressions read.  An expression over
    no column takes its length from the first lane it finds
    (exprjit._broadcast_len): slot 0 stays live for it."""
    out = set()
    for e in exprs:
        cols = e.collect_columns()
        out.update(c.index for c in cols)
        if not cols:
            out.add(0)
    return out


def _only(pairs, live) -> list:
    """``pairs`` with every slot outside ``live`` dead."""
    return [p if i in live else DEAD for i, p in enumerate(pairs)]


def _spread(n: int, slots, pairs) -> list:
    """An ``n``-slot view holding ``pairs`` at ``slots``, dead elsewhere."""
    out = [DEAD] * n
    for i, p in zip(slots, pairs):
        out[i] = p
    return out


# =========================================================================
# NULL-freedom: a null lane that holds no information is not gathered
# =========================================================================
#
# Every view says which of its slots hold no NULL on any row its
# ``valid`` keeps (``_TView.nonnull``).  The property is of valid rows
# only, so padding (a leaf's null lane is padded with True) and inner
# joins keep it.  A leaf observes it of its replica version's null
# masks; every other node derives it from its children's sets and what
# it computes itself, never from a schema's NOT NULL or an option.  Two
# readers: a join gathers no null lane of such a build column (where
# the probe row matched, the build row is valid: the lane is
# ``~match``), and a GROUP BY above a view reduces no count of such an
# argument (its count is the rows': ``presence``).  What a node left
# out is part of the program's key (``_PipeBuilder.key``'s ``nonnull``,
# a ``!`` on an aggregate's spec): a replica version in which the column
# holds a NULL builds another program.

#: functions whose result is NULL only where an argument is
_NULL_PRESERVING = frozenset(
    ("+", "-", "*", "unaryminus", "abs", "cast_real", "cast_int"))


def _never_null(e, column_never_null) -> bool:
    """``e`` is NULL on no row: it reads, through functions that make no
    NULL of their own (a division does, by zero), only constants and
    columns that ``column_never_null(index)`` proves free of NULLs."""
    if isinstance(e, ExprColumn):
        return column_never_null(e.index)
    if isinstance(e, Constant):
        return e.value is not None
    return getattr(e, "name", None) in _NULL_PRESERVING \
        and all(_never_null(a, column_never_null) for a in e.children())


def _column_never_null(rep, sid, nulls) -> bool:
    """The replica's column ``sid`` holds no NULL in this version
    (``nulls``: its host null mask), observed once a version."""
    return rep.memo(("nevernull", sid), lambda: not nulls.any())


def _never_null_specs(specs, needed, column_never_null) -> frozenset:
    """The needed specs whose argument is NULL on no row the aggregate
    reads: :func:`_spec_results` takes ``presence`` for their counts."""
    return frozenset(
        k for k in needed if specs[k][1] is not None
        and _never_null(specs[k][1], column_never_null))


def _agg_nonnull(specs, slots, out_map, never_null, key_nonnull) -> set:
    """The slots of an aggregate's view that hold no NULL in any group
    some row fell in (``presence > 0``: the view's valid groups): a
    count always, a sum / min / max whose count is ``presence``
    (``never_null``), an avg over such a count, and the group keys that
    ``key_nonnull(j)`` proves."""
    def spec_nonnull(k):
        return specs[k][0] in ("count_star", "count", "sum0") \
            or k in never_null
    out = set()
    for i, m in enumerate(out_map):
        if m[0] == "gb":
            ok = key_nonnull(m[1])
        elif slots[m[1]][0] == "one":
            ok = spec_nonnull(slots[m[1]][1])
        else:  # avg: NULL where its count is 0
            k = slots[m[1]][2]
            ok = specs[k][0] == "count" and k in never_null
        if ok:
            out.add(i)
    return out


# =========================================================================
# group index: the sorted-replica clustered index
# =========================================================================

class GroupIndex:
    """Per (replica version, key columns) sorted order + group boundaries.
    order[i] = original row of sorted position i; groups are contiguous
    runs; ends[g] = last sorted position of group g (host int64 [ng]);
    keycols[j] = (values[ng], null[ng]) per key column (NULL keys form
    one group; a multi-column key groups by the TUPLE).  The single-int-
    key index additionally exposes gkeys/lo/hi + the dense pos_table the
    join build sides ride.

    ``clustered``: the table is stored in its key's order (the stable
    sort moved nothing: ``order`` is the identity), observed once when
    the index is built.  The sorted aggregate reads its lanes IN INDEX
    ORDER and no program ever gathers by ``order``: over a clustered
    index those lanes are the replica's row-order lanes as they are;
    otherwise ``order`` (per shard: ``shards``) permutes each lane on
    the host, once a replica version, before it is uploaded."""
    __slots__ = ("order", "ends", "keycols", "n_groups", "lo", "hi",
                 "clustered")

    def __init__(self, key_cols: List[tuple]):
        # lexsort: last key is primary -> feed (vals, nulls) pairs in
        # reverse column order, nulls after their values so each column
        # sorts non-null-first.  Values under a null mask are garbage:
        # mask them to 0 so the sort (and the boundary diff below) never
        # splits the NULL group on them.
        svs = []
        for vals, nulls in key_cols:
            svs.append((np.where(nulls, 0, vals) if nulls.any() else vals,
                        nulls))
        order = _stable_key_order(svs)
        if order is None:
            ops = []
            for mv, nl in reversed(svs):
                ops.append(mv)
                ops.append(nl)
            order = np.lexsort(tuple(ops))
        n = len(order)
        self.clustered = bool((order == np.arange(n)).all())
        if not self.clustered:
            svs = [(mv[order], nl[order]) for mv, nl in svs]
        if n == 0:
            self.order = order
            self.ends = np.empty(0, dtype=np.int64)
            self.keycols = [(np.empty(0, dtype=v.dtype),
                             np.empty(0, dtype=bool)) for v, _ in key_cols]
            self.n_groups = 0
            self.lo = self.hi = 0
            return
        boundary = np.zeros(n, dtype=bool)
        boundary[0] = True
        for sv, sn in svs:
            # a value diff only splits groups when NEITHER row is NULL:
            # all NULL keys form ONE group (kernels._group_agg_kernel
            # applies the same guard)
            boundary[1:] |= ((sv[1:] != sv[:-1]) & ~(sn[1:] & sn[:-1])) \
                | (sn[1:] != sn[:-1])
        starts = np.nonzero(boundary)[0]
        ends = np.empty(len(starts), dtype=np.int64)
        ends[:-1] = starts[1:] - 1
        ends[-1] = n - 1
        self.order = order
        self.ends = ends
        self.keycols = [(sv[ends], sn[ends]) for sv, sn in svs]
        self.n_groups = len(ends)
        if len(key_cols) == 1 and self.gkeys.dtype == np.int64:
            nn = self.gkeys[~self.gkey_null]
            self.lo = int(nn.min()) if len(nn) else 0
            self.hi = int(nn.max()) if len(nn) else 0
        else:
            self.lo = self.hi = 0

    @property
    def gkeys(self) -> np.ndarray:
        return self.keycols[0][0]

    @property
    def gkey_null(self) -> np.ndarray:
        return self.keycols[0][1]

    def pos_table(self) -> Optional[np.ndarray]:
        """Dense key -> group index (int32), -1 for absent keys; None when
        the key range is too wide for a dense table (single-int-key
        indexes only)."""
        if len(self.keycols) != 1 or self.gkeys.dtype != np.int64:
            return None
        rng = self.hi - self.lo + 1
        if rng > MAX_DENSE_RANGE:
            return None
        tbl = np.full(rng, -1, dtype=np.int32)
        live = ~self.gkey_null
        tbl[self.gkeys[live] - self.lo] = np.nonzero(live)[0]
        return tbl

    def raw_counts(self) -> np.ndarray:
        """Rows per group (host int64 [ng]) — the pre-filter group sizes
        the CSR join uses for its expansion upper bound."""
        if self.n_groups == 0:
            return np.empty(0, dtype=np.int64)
        prev = np.concatenate(([np.int64(-1)], self.ends[:-1]))
        return self.ends - prev

    def sorted_gid(self) -> np.ndarray:
        """Group id per SORTED position (host int64 [n]) — the lane the
        segment-min/max kernels reduce over."""
        return np.repeat(np.arange(self.n_groups, dtype=np.int64),
                         self.raw_counts())

    def row_gid(self, dtype) -> np.ndarray:
        """Group id per ROW (host [n]) — the lane the dense aggregate
        reduces over in row order."""
        out = np.empty(len(self.order), dtype=dtype)
        out[self.order] = self.sorted_gid()
        return out

    def shard_ends(self, n: int, per: int) -> np.ndarray:
        """``shards``' ends [n, n_groups] alone — all the sorted
        aggregate reads of the cut over a clustered index, where a
        shard's rows up to a group follow from the index's own
        boundaries (no pass over the rows)."""
        if not self.clustered:
            return self.shards(n, per)[1]
        return self._clustered_ends(n, per, self.ends[None, :])

    def _clustered_ends(self, n: int, per: int, ends) -> np.ndarray:
        """Over a clustered index: the last position within shard s of
        a row at or before the global boundary ``ends[s]`` (-1: none)."""
        first = np.arange(n, dtype=np.int64)[:, None] * per
        rows = np.clip(len(self.order) - first, 0, per)
        return np.clip(ends + 1 - first, 0, rows) - 1

    def spans(self, n: int, per: int):
        """(g_lo [n], g_hi [n]): the least and the greatest group id
        among the rows of each of ``n`` shards of ``per`` contiguous
        rows; (0, -1) for a shard that holds no row.  A shard's rows in
        key order are a run of sorted positions' groups, so both follow
        from ``ends`` by a ``searchsorted`` at the shard's first and
        last sorted position (over a clustered index those are its
        first and last row: no pass over the rows)."""
        g_lo = np.zeros(n, dtype=np.int64)
        g_hi = np.full(n, -1, dtype=np.int64)
        total = len(self.order)
        shard = None if self.clustered else self.order // per
        for s_ in range(n):
            if shard is None:
                pos = (s_ * per, min((s_ + 1) * per, total) - 1)
            else:
                mine = np.flatnonzero(shard == s_)
                pos = (mine[0], mine[-1]) if len(mine) else (0, -1)
            if pos[1] >= pos[0]:
                g_lo[s_], g_hi[s_] = np.searchsorted(self.ends, pos)
        return g_lo, g_hi

    def span_ends(self, n: int, per: int, g_lo: np.ndarray, qb: int,
                  shard_ends=None) -> np.ndarray:
        """The ends of ``shards`` cut to each shard's own span of groups
        [n, qb]: entry k of shard s is the last position in the shard's
        key-ordered list of a row of group <= g_lo[s] + k (-1 all along
        a shard without rows).  ``qb`` covers the widest span
        (``spans``); entries past a shard's span repeat its last
        boundary — empty ranges, as groups past ``n_groups`` do.  Over a
        clustered index they follow from ``ends``; otherwise they are
        columns of ``shard_ends`` (``shards``' [n, n_groups] table)."""
        g = np.minimum(g_lo[:, None] + np.arange(qb, dtype=np.int64),
                       self.n_groups - 1)
        if self.clustered:
            return self._clustered_ends(n, per, self.ends[g])
        if shard_ends is None:
            shard_ends = self.shards(n, per)[1]
        return np.take_along_axis(shard_ends, g, axis=1)

    def shards(self, n: int, per: int):
        """The index cut for a mesh of ``n`` shards holding ``per``
        contiguous rows each: (order [n, per], ends [n, n_groups],
        sgid [n, per], rows [n]).  order[s] lists shard s's rows in key
        order as positions WITHIN the shard, then the shard's padding
        positions in place (a permutation of the shard: the identity
        when the index is clustered); ends[s][g] is the last
        position in that list of a row of group <= g (-1: none yet), so
        a shard's partial state for group g is the boundary difference
        at (ends[s][g-1], ends[s][g]] — empty where the shard holds no
        row of g, which is every group outside [g_lo[s], g_hi[s]]
        (``spans``): the sorted aggregate reads this table whole only
        where the shards' spans are (nearly) whole, and ``span_ends``'
        cut of it otherwise; sgid[s] is the group of each listed row
        (``n_groups`` past the shard's rows); rows[s] counts the
        shard's rows.  Summed (min/max merged) over the shards the
        partial states are the unsharded aggregate."""
        ng = self.n_groups
        shard = self.order // per
        gid = self.sorted_gid()
        # a stable partition of the sorted positions by shard keeps each
        # shard's rows in key order
        perm = np.argsort(shard, kind="stable")
        rows = np.bincount(shard, minlength=n).astype(np.int64)
        order = np.tile(np.arange(per, dtype=np.int64), (n, 1))
        sgid = np.full((n, per), ng, dtype=np.int64)
        start = 0
        for s_ in range(n):
            mine = perm[start:start + rows[s_]]
            order[s_, :rows[s_]] = self.order[mine] - s_ * per
            sgid[s_, :rows[s_]] = gid[mine]
            start += rows[s_]
        cnt = np.bincount(shard * ng + gid,
                          minlength=n * ng).reshape(n, ng)
        return order, np.cumsum(cnt, axis=1) - 1, sgid, rows


def _stable_key_order(svs: List[tuple]) -> Optional[np.ndarray]:
    """The stable order of rows by (null, value) of each key column in
    turn — what ``np.lexsort`` gives — where it can be had without a
    comparison sort of every row: a single key without NULLs that
    already ascends (the table is stored by it: the identity), or
    integer keys of small ranges folded into one narrow unsigned key,
    which numpy's stable sort orders by radix in a pass or two.  None:
    sort by comparison."""
    n = len(svs[0][0]) if svs else 0
    if n == 0 or any(mv.dtype.kind not in "iu" for mv, _ in svs):
        return None
    if len(svs) == 1 and not svs[0][1].any():
        mv = svs[0][0]
        if bool((mv[1:] >= mv[:-1]).all()):
            return np.arange(n)
    comp, span = None, 1
    for mv, nl in svs:
        lo, hi = int(mv.min()), int(mv.max())
        width = 2 * (hi - lo + 1)   # the column's NULLs after its values
        span *= width
        if span > 1 << 16:
            return None
        part = (mv - lo) + nl * (width // 2)
        comp = part if comp is None else comp * width + part
    dtype = np.uint8 if span <= 1 << 8 else np.uint16
    return np.argsort(comp.astype(dtype), kind="stable")


def _span_pad(g_lo, g_hi, ngb: int) -> int:
    """The common length ``qb`` of the shards' pieces of a table of
    ``ngb`` group slots, from ``GroupIndex.spans``: the widest span and
    half a step more, rounded up to a multiple of the step ``ngb // 16``
    (at most ``ngb``: the spans are whole).  Not ``kernels.bucket``: a
    table stored by its key in 2^k-row shards spans ``ngb / n`` groups
    give or take the data's draw, an edge of every power of two; the
    half step keeps that draw off the steps' own edges too.  Sixteen
    shapes a bucket bound the recompiles as buckets do."""
    step = max(ngb // 16, 1)
    widest = int((g_hi - g_lo).max()) + 1
    return min(-(-(widest + step // 2) // step) * step, ngb)


def _group_index(rep, sids: tuple, key_cols: List[tuple]) -> GroupIndex:
    """sids: tuple of stable slot ids (one per key column)."""
    return rep.memo(("groupindex", sids), lambda: GroupIndex(key_cols))


def _col_bounds(rep, sid, vals, nulls):
    """Host min/max of a replica int column's non-null values."""
    def build():
        nn = vals[~nulls]
        if len(nn) == 0:
            return None
        return int(nn.min()), int(nn.max())
    return rep.memo(("bounds", sid), build)


def _rep_pos_table(rep, sid, vals, nulls):
    """Dense key -> row index table for a UNIQUE replica column (the
    planner proves uniqueness: pk / single-column unique index)."""
    def build():
        b = _col_bounds(rep, sid, vals, nulls)
        if b is None:
            return None
        lo, hi = b
        rng = hi - lo + 1
        if rng > MAX_DENSE_RANGE:
            return None
        tbl = np.full(rng, -1, dtype=np.int32)
        live = ~nulls
        tbl[vals[live] - lo] = np.nonzero(live)[0].astype(np.int32)
        return lo, hi, tbl
    return rep.memo(("postable", sid), build)


# =========================================================================
# compiled nodes
# =========================================================================

class _Ctx:
    """Per-query compile context."""

    def __init__(self, exec_ctx, mesh=None):
        self.exec_ctx = exec_ctx
        self.mesh = mesh  # multi-chip mesh (tidb_mesh_parallel) or None


def mesh_if_enabled(session_vars):
    from ..parallel import dist
    return dist.session_mesh(session_vars)


def _jn():
    return kernels.jnp()


def _dev_upload(rep, key, build_np, layout=None):
    # counted H2D (kernels.h2d): replica-memoized, so the transfer is
    # charged once per (replica, key) — to whichever query materializes it.
    # Under a mesh the array is placed in its layout (row-sharded or
    # whole on every device) and memoized under it, so a one-device
    # session and a mesh session never hand each other the wrong array
    if layout is None:
        return rep.memo(key, lambda: kernels.h2d(build_np()))
    from ..parallel import dist
    return rep.memo(key + dist.layout_tag(layout),
                    lambda: dist.place(build_np(), layout))


def _leaf_lane(rep, kind: str, sid, nb: int, host, fill=0, tag=(),
               perm=None, layout=None):
    """A replica column's lane on the device, padded to its leaf's bucket
    ``nb``: ``kind`` is ``devv`` (values), ``devn`` (the null mask) or
    ``devcodes`` (dictionary codes), and (kind, sid, nb) the memo key
    every statement over the table shares.  ``tag`` + ``perm``: the lane
    permuted on the host (:meth:`_ReplicaLeaf.prepare`'s ``order``)."""
    def build():
        out = kernels.pad1(host, nb, fill)
        return out if perm is None else out[perm()]
    return _dev_upload(rep, (kind, sid, nb) + tag, build, layout)


def _layouts(mesh):
    """(rows, whole) of ``mesh``, or (None, None) on one device."""
    if mesh is None:
        return None, None
    from ..parallel import dist
    return dist.rows(mesh), dist.whole(mesh)


class _ReplicaLeaf:
    """Full-table scan from the columnar replica: device columns are
    version-memoized uploads; scan filters become the validity mask
    (traced inline into the fused program).  Mask and lanes are
    elementwise over positions, and the mask's one use of a position is
    the padding guard ``position < n_rows``: a parent that wants its
    rows in another order says so and the same program runs over
    lanes permuted on the host (``prepare``'s ``order``)."""

    def __init__(self, reader_exec, plan, mesh=None):
        self.ex = reader_exec
        self.plan = plan
        self._rep = None  # set at take(): take_raw_replica consumes
        self._chk = None
        self._filters = None
        self.mesh = mesh
        #: under a mesh: lanes row-sharded (a scanned / probe side) —
        #: a broadcast join clears it on its build side's leaf, whose
        #: lanes then lie whole on every device
        self.spread = True
        #: ``prepare``'s ``order`` for a leaf that a sorted GROUP BY
        #: reads through a chain of row filters (``_AggIndexNode``)
        self.order = None

    def rows_mesh(self, nb: int):
        """The mesh this leaf's lanes are row-sharded over, or None (one
        device, a bucket too small to shard, a broadcast build side)."""
        from ..parallel import dist
        return self.mesh if self.spread \
            and dist.shardable(nb, self.mesh) else None

    @staticmethod
    def compile(plan: PhysicalTableReader, ctx: _Ctx):
        from .executors import TableReaderExec
        scan = plan.scan
        if scan.ranges is not None or scan.pushed_agg is not None \
                or scan.pushed_topn is not None \
                or scan.pushed_limit is not None:
            return None
        ex = TableReaderExec(plan)
        ex.open(ctx.exec_ctx)
        if ex._replica is None:
            ex.close()
            return None
        from .tpu_executors import _block_budget
        budget = _block_budget(getattr(ctx.exec_ctx, "session_vars", {}))
        if budget > 0 and ex._replica.n_rows > budget:
            # table exceeds the device buffer budget: whole-column
            # residency is off the table — the per-op tier's block-wise
            # aggregate (partial-state carry) serves it instead
            ex.close()
            return None
        return _ReplicaLeaf(ex, plan, mesh=ctx.mesh)

    def take(self) -> bool:
        """Consume the reader's replica (once): replica(), chunk() and
        nb() answer from here on."""
        if self._chk is None:
            self._chk, self._filters, self._rep = \
                self.ex.take_raw_replica()
        return self._chk is not None

    def nb(self) -> int:
        return kernels.bucket(max(self._chk.full_rows(), 1))

    def prepare(self, pb: _PipeBuilder, live=None,
                order=None) -> Optional[_TView]:
        """``live`` is not followed: the lanes are the program's inputs
        as they lie, and one nobody reads costs no operation (so the null
        lane of a column the view says holds no NULL is handed over too).
        ``order`` None: lanes in row order, under the memo keys every
        statement over the table shares (devv / devn / devcodes).  Else
        (tag, perm): perm() is a host permutation of the padded [nb]
        lane that keeps padding on padding (under a mesh: within each
        shard), and each lane goes up permuted, once a replica version,
        under its key + tag (_AggIndexNode, over an index that is not
        clustered)."""
        from .tpu_executors import (_build_device_mask, _rep_string_dict,
                                    _slot_id)
        if not self.take():
            return None
        chk, filters, rep = self._chk, self._filters, self._rep
        nb = self.nb()
        jn = _jn()
        pt = ParamTable()
        dm = _build_device_mask(self.ex, rep, chk, filters, pt)
        if dm is None:
            return None
        mask_fn, mask_key, _needed = dm
        params = pt.arrays()
        slots = []
        meta: List[tuple] = []
        dts = []
        # under a mesh: row-sharded where rows_mesh says so, else whole
        # on every device (small tables, broadcast build sides)
        from ..parallel import dist
        lrows, lwhole = _layouts(self.mesh)
        lay = lrows if self.rows_mesh(nb) is not None else lwhole
        tag, perm = order or self.order or ((), None)

        def lane(kind, sid, host, fill=0):
            return _leaf_lane(rep, kind, sid, nb, host, fill, tag, perm,
                              lay)
        nonnull = set()
        for idx, c in enumerate(chk.columns):
            v = c.values()
            sid = _slot_id(self.ex, idx)
            nulls = c.null_mask()
            if _column_never_null(rep, sid, nulls):
                nonnull.add(idx)
            dn = lane("devn", sid, nulls, True)
            if v.dtype == object or v.dtype.kind == "U":
                got = _rep_string_dict(rep, sid, chk, idx)
                codes, _card, _, uniques = got
                dv = lane("devcodes", sid, codes)
                meta.append((c.ft, uniques))
                dts.append("s")
            else:
                dv = lane("devv", sid, v)
                meta.append((c.ft, None))
                dts.append("f" if v.dtype == np.float64 else "i")
            slots.append((pb.add(dv, lay), pb.add(dn, lay)))
        pi, pf = params
        ip = pb.add(np.asarray(pi))
        fp = pb.add(np.asarray(pf))
        pb.key(("leaf", mask_key, nb, tuple(dts)) + dist.layout_tag(lay))

        def emit(args):
            pairs = [(args[iv], args[im]) for iv, im in slots]
            valid = mask_fn(pairs, (args[ip], args[fp]), jn.arange(nb))
            return valid, pairs
        return _TView(emit, nb, meta, "leaf", nonnull)

    # host info the parent join/agg stages need (valid after prepare())
    def replica(self):
        return self._rep if self._rep is not None else self.ex._replica

    def chunk(self):
        return self._chk

    def close(self):
        self.ex.close()


class _HostLeaf:
    """Any unsupported subtree: run its regular executor, upload the
    materialized chunk (H2D is cheap; this is the CPU->TPU boundary).
    Numeric columns only — a string column here would need a per-query
    dictionary build, which defeats the point."""

    def __init__(self, child_exec, plan):
        self.ex = child_exec
        self.plan = plan
        self._chk = None

    @staticmethod
    def compile(plan, ctx: _Ctx):
        for c in plan.schema.columns:
            if c.ret_type.eval_type is EvalType.STRING:
                return None
        if _contains_join(plan):
            # an unsupported JOIN subtree as a host leaf would nest
            # another DevPipeExec inside (materialize + re-upload per
            # layer); bail the whole pipeline instead — the per-operator
            # executors handle that shape without the extra round trips
            return None
        from .executors import build_executor
        ex = build_executor(plan, True)
        if ex is None:
            return None
        ex.open(ctx.exec_ctx)
        return _HostLeaf(ex, plan)

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        # ``live`` is not followed: the columns are inputs, as a
        # replica leaf's
        from .tpu_executors import _drain_chunk
        chk = _drain_chunk(self.ex, self.ex.field_types()).compact()
        self._chk = chk
        n = chk.num_rows()
        nb = kernels.bucket(max(n, 1))
        slots = []
        meta = []
        dts = []
        for c, oc in zip(chk.columns, self.plan.schema.columns):
            v = c.values()
            m = c.null_mask()
            slots.append((pb.add(kernels.pad1(v, nb)),
                          pb.add(kernels.pad1(m, nb, True))))
            meta.append((oc.ret_type, None))
            dts.append("f" if v.dtype == np.float64 else "i")
        vi = pb.add(kernels.pad1(np.ones(n, dtype=bool), nb))
        pb.key(("host", nb, tuple(dts)))

        def emit(args):
            return args[vi], [(args[a], args[b]) for a, b in slots]
        return _TView(emit, nb, meta, "host")

    def chunk(self):
        return self._chk

    def close(self):
        self.ex.close()


def _assemble_agg_specs(plan):
    """Shared descriptor lowering for the device aggregation nodes:
    returns (specs, slots) or None.  specs[k] = (kind, expr|None) with
    kind in count_star/count/sum/sum0/min/max — sum0 is a SUM of partial
    COUNT states, 0 over empty input instead of NULL; slots[i] maps
    descriptor i to ("one", k) or ("avg", k_sum, k_cnt) — avg decomposes
    into sum+count with the quotient taken in-kernel (reference
    partial-state split, aggregation/descriptor.go)."""
    from ..expression.aggregation import (AGG_AVG, AGG_MAX, AGG_MIN,
                                          AggMode)
    from ..expression.builtins import new_function
    specs: List[tuple] = []
    slots: List[tuple] = []
    for d in plan.aggs:
        if d.distinct:
            return None
        if d.mode is AggMode.FINAL:
            # FINAL merges partial STATES (reference aggfuncs mode split):
            # count -> SUM of partial counts; avg -> sum(sums)/sum(counts);
            # sum/min/max merge with themselves
            if d.name == AGG_COUNT and is_jittable(d.args[0]):
                # sum0: COUNT merged from partial states is 0 over empty
                # input, never NULL (unlike SUM)
                specs.append(("sum0", d.args[0]))
                slots.append(("one", len(specs) - 1))
            elif d.name == AGG_AVG and len(d.args) == 2 \
                    and all(is_jittable(a) for a in d.args):
                a0 = d.args[0]
                if a0.eval_type is not EvalType.REAL:
                    a0 = new_function("cast_real", [a0])
                specs.append(("sum", a0))
                specs.append(("sum", d.args[1]))
                slots.append(("avg", len(specs) - 2, len(specs) - 1))
            elif d.name == AGG_SUM and is_jittable(d.args[0]):
                a = d.args[0]
                if (d.ret_type.eval_type is EvalType.REAL
                        and a.eval_type is not EvalType.REAL):
                    a = new_function("cast_real", [a])
                specs.append(("sum", a))
                slots.append(("one", len(specs) - 1))
            elif d.name in (AGG_MIN, AGG_MAX) and is_jittable(d.args[0]) \
                    and not (d.args[0].eval_type is EvalType.INT
                             and getattr(d.args[0].ret_type,
                                         "is_unsigned", False)):
                specs.append((("min" if d.name == AGG_MIN else "max"),
                              d.args[0]))
                slots.append(("one", len(specs) - 1))
            else:
                return None
            continue
        if d.name == AGG_COUNT and isinstance(d.args[0], Constant) \
                and d.args[0].value is not None:
            specs.append(("count_star", None))
            slots.append(("one", len(specs) - 1))
        elif d.name == AGG_COUNT and is_jittable(d.args[0]):
            specs.append(("count", d.args[0]))
            slots.append(("one", len(specs) - 1))
        elif d.name == AGG_SUM and is_jittable(d.args[0]):
            a = d.args[0]
            if (d.ret_type.eval_type is EvalType.REAL
                    and a.eval_type is not EvalType.REAL):
                a = new_function("cast_real", [a])
            specs.append(("sum", a))
            slots.append(("one", len(specs) - 1))
        elif d.name == AGG_AVG and is_jittable(d.args[0]):
            a = d.args[0]
            ar = a if a.eval_type is EvalType.REAL \
                else new_function("cast_real", [a])
            specs.append(("sum", ar))
            specs.append(("count", a))
            slots.append(("avg", len(specs) - 2, len(specs) - 1))
        elif d.name in (AGG_MIN, AGG_MAX) and is_jittable(d.args[0]):
            a = d.args[0]
            if (a.eval_type is EvalType.INT
                    and getattr(a.ret_type, "is_unsigned", False)):
                return None  # unsigned order map: CPU/per-op tiers
            specs.append((("min" if d.name == AGG_MIN else "max"), a))
            slots.append(("one", len(specs) - 1))
        else:
            return None
    return specs, slots


def _agg_out_map(plan):
    """schema slot -> ("agg", descriptor i) | ("gb", key j), or None."""
    out_map = []
    for src, i in getattr(plan, "output_map", []):
        out_map.append(("agg", i) if src == "agg" else ("gb", i))
    if len(out_map) != len(plan.schema.columns):
        return None
    return out_map


def _mm_fill(jn, dtype, kind: str):
    if dtype == jn.int64:
        return (jn.iinfo(jn.int64).max if kind == "min"
                else jn.iinfo(jn.int64).min)
    return jn.inf if kind == "min" else -jn.inf


def _needed_specs(slots, out_map, live) -> frozenset:
    """The specs the live output slots read (an avg reads two); a spec
    outside it is not computed: a dead sum costs a prefix sum and two
    gathers."""
    need = set()
    for i, m in enumerate(out_map):
        if i in live and m[0] == "agg":
            need.update(slots[m[1]][1:])
    return frozenset(need)


def _spec_slots_read(specs, needed) -> set:
    """The child slots the needed specs' arguments read."""
    return _slots_read(specs[k][1] for k in needed
                       if specs[k][1] is not None)


def _spec_results(jn, spec_kinds, arg_fns, pairs, pr, valid, seg_sum,
                  seg_mm, presence, n_out, needed, gather=lambda x: x,
                  never_null=frozenset(), seg_count=None):
    """Shared per-spec aggregation loop for the device group-by nodes
    (the subtle NULL-when-empty / avg-pairing semantics live ONCE here).
    A spec outside ``needed`` (:func:`_needed_specs`) is left dead.  A
    spec in ``never_null`` has an argument that is NULL on no row: its
    live rows are the view's and its count IS ``presence``, so no count
    of its own is reduced (in the sorted formulation a prefix sum and a
    boundary gather).
    gather takes a lane into the order the reductions run in (the
    identity where the lanes arrive in it: row order for the dense and
    the scalar aggregate, the index's order for the sorted one); seg_sum
    reduces a lane to [n_out]; seg_mm(av_s, live_s, kind) likewise for
    min/max; seg_count(mask) counts a mask's rows to int64 [n_out] (by
    default seg_sum of the mask as int64; the sorted formulation's may
    reduce it in 32 bits).  The three stages carry their names into the
    profile: ``args``, ``gather``, ``group_sums``."""
    scope = kernels.jax().named_scope
    if seg_count is None:
        def seg_count(mask):
            return seg_sum(mask.astype(jn.int64))
    res = []
    for k, (kind, af) in enumerate(zip(spec_kinds, arg_fns)):
        if k not in needed:
            res.append(DEAD)
            continue
        if kind == "count_star":
            res.append((presence, jn.zeros(n_out, dtype=bool)))
            continue
        with scope("args"):
            av, an = af(pairs, pr)
        with scope("gather"):
            live_s = gather(valid if k in never_null else valid & ~an)
            if kind != "count":
                av_s = gather(av)
        with scope("group_sums"):
            cnt = presence if k in never_null else seg_count(live_s)
            if kind == "count":
                res.append((cnt, jn.zeros(n_out, dtype=bool)))
            elif kind in ("sum", "sum0"):
                res.append((seg_sum(jn.where(live_s, av_s, 0)),
                            jn.zeros(n_out, dtype=bool) if kind == "sum0"
                            else cnt == 0))
            else:  # min / max
                fill = _mm_fill(jn, av.dtype, kind)
                res.append((seg_mm(jn.where(live_s, av_s, fill),
                                   live_s, kind), cnt == 0))
    return res


def _view_spec_fns(pb: _PipeBuilder, pt: ParamTable, specs, needed,
                   tv: _TView):
    """:func:`_spec_fns` of an aggregate above the view ``tv``, whose
    flags prove which arguments are NULL on no valid row; (argument
    closures, key parts, those specs), their counts left out counted."""
    never_null = _never_null_specs(specs, needed, tv.nonnull.__contains__)
    pb.const_nulls += len(never_null)
    return _spec_fns(specs, pt, never_null) + (never_null,)


def _spec_fns(specs, pt: ParamTable, never_null=frozenset()):
    """(argument closures over ``pt``'s parameter slots, structural key
    parts) of an aggregate's specs; None for a spec without an argument,
    a ``!`` on the key of one whose count is ``presence``."""
    arg_fns = []
    keys = []
    for k, (kind, a) in enumerate(specs):
        if a is None:
            arg_fns.append(None)
            keys.append(kind)
        else:
            arg_fns.append(compile_expr_params(a, pt))
            keys.append(f"{kind}:{stable_shape_key(a)}"
                        + ("!" if k in never_null else ""))
    return arg_fns, keys


def _mesh_merges(mesh):
    """(sum, {"min": .., "max": ..}): how per-shard partial tables merge
    over ``mesh`` inside a shard_map (dist.mesh_sum / mesh_min /
    mesh_max); the identity on one device."""
    if mesh is None:
        def same(x):
            return x
        return same, {"min": same, "max": same}
    from ..parallel import dist
    return dist.mesh_sum, {"min": dist.mesh_min, "max": dist.mesh_max}


def _slot_outputs(jn, res, slots):
    """Descriptor outputs from spec results: direct, or the avg quotient
    (NULL when the count is zero); dead where its specs are."""
    outs = []
    for slot in slots:
        if any(res[k] is DEAD for k in slot[1:]):
            outs.append(DEAD)
        elif slot[0] == "one":
            outs.append(res[slot[1]])
        else:
            sv, _ = res[slot[1]]
            cv, _ = res[slot[2]]
            outs.append((sv / jn.maximum(cv, 1).astype(sv.dtype),
                         cv == 0))
    return outs


def _gb_key_ok(e) -> bool:
    """Group keys the device nodes handle: plain columns — signed ints,
    reals, or strings (dictionary codes on device)."""
    if not isinstance(e, ExprColumn):
        return False
    if e.eval_type is EvalType.INT \
            and getattr(e.ret_type, "is_unsigned", False):
        return False
    return True


class _AggIndexNode:
    """GROUP BY directly over the columnar replica, via the group index.
    Multi-column keys group by the tuple (strings ride dictionary codes);
    the index is built ONCE per (replica version, key set) and memoized,
    so a per-query aggregate is one fused program over [nb] with a tiny
    [ngb] output: slot g is group g of the index.  Replaces the
    reference's partial-agg hash table (aggregate.go:355 shuffle) for
    reader-rooted aggregates.  Two formulations, chosen at prepare() from
    the index's group count (and part of the program key):

    - dense (``aggdense``), at most kernels.SEG_UNROLL groups: the lanes
      stay in row order and each group's sum is a masked reduction over
      the group id of each row (kernels._SegReduce) — a few streaming
      passes, no gather, no prefix sum;
    - sorted (``aggindex``), more groups: the leaf is prepared IN THE
      INDEX'S ORDER (``_ReplicaLeaf.prepare``'s ``order``), so mask, lanes and
      argument expressions are evaluated at sorted positions and the
      program holds no gather to sorted order: mask -> prefix sum ->
      boundary diff (one [ngb] gather a sum: a group's lower boundary
      is the group before's upper one), whose cost does not grow with
      the group count.  A count of rows is reduced in 32 bits where the
      leaf's bucket proves it fits (``_count32``; counter
      ``agg_count32``): one word a boundary, where an int64 is two, so
      one gather.  Which host arrays go up is decided by what the
      index observed of its input, never what is traced: over a
      clustered index (``GroupIndex.clustered``; counter
      ``agg_clustered``) the leaf's lanes are the replica's row-order
      lanes under the memo keys every scan shares (``devv`` / ``devn`` /
      ``devcodes``); otherwise each lane the leaf reads goes up permuted
      on the host, once a replica version, under its key + ``("by",
      sids)``.  Under a mesh the shards are contiguous row ranges, each
      in its own key order (``GroupIndex.shards``), and a shard bounds
      only the span of groups its rows hold (``GroupIndex.spans``; a
      quarter of them on four chips over a clustered index): a [qb]
      piece a shard, ``qb`` the widest span padded by ``_span_pad``,
      gathered and added into place at each shard's first group
      (``dist.mesh_sum_spans``; counter ``agg_span_cut``).  Where the
      spans are whole (``qb == ngb``: an unclustered index whose shards
      each hold rows of every key) every shard bounds every group and
      the [ngb] tables are summed (``dist.mesh_sum``); min/max merge
      whole tables either way.  Index lanes: ``gi_ends``
      (``gi_shard_ends`` a shard: [ngb] or [qb], with ``gi_span_lo``,
      the shards' first groups, beside the latter), ``gi_sgid``
      (``gi_shard_sgid``) when a min/max reads it."""

    def __init__(self, leaf: _ReplicaLeaf, plan, key_cols, specs, slots,
                 out_map):
        self.leaf = leaf
        self.plan = plan
        self.key_cols = key_cols    # [ExprColumn]
        self.specs = specs
        self.slots = slots
        self.out_map = out_map      # schema slot -> ("agg", i) | ("gb", j)
        self.gidx: Optional[GroupIndex] = None
        self._sids: Optional[tuple] = None
        #: the leaf, or the row filters over it that compile() found
        self.chain = leaf

    @staticmethod
    def compile(plan: PhysicalHashAgg, ctx: _Ctx):
        if not plan.group_by:
            return None
        if not all(_gb_key_ok(e) for e in plan.group_by):
            return None
        child = plan.children[0]
        if isinstance(child, PhysicalTableReader):
            leaf = chain = _ReplicaLeaf.compile(child, ctx)
        elif _filters_a_reader(child) and ctx.mesh is None:
            chain = _compile_node(child, ctx)
            leaf = _filtered_leaf(chain)
            if leaf is None:
                _close_node(chain)
                return None
        else:
            return None
        if leaf is None:
            return None
        got = _assemble_agg_specs(plan)
        out_map = _agg_out_map(plan)
        if got is None or out_map is None:
            _close_node(chain)
            return None
        specs, slots = got
        node = _AggIndexNode(leaf, plan, list(plan.group_by), specs, slots,
                             out_map)
        node.chain = chain
        return node

    def _count32(self, nb: int) -> bool:
        """Whether the sorted formulation counts rows in 32 bits: where
        the leaf's whole bucket ``nb``, every shard's rows together, is
        below 2^31, so that no count (a shard's, or one merged over the
        mesh) can leave int32's range.  A count is exact in either
        width; on the TPU an int64 is two 32-bit words, its prefix sum
        twice as wide and its boundary gather two.  A function of a
        shape the program is keyed by, nothing else (counter
        ``agg_count32``)."""
        if nb >= 1 << 31:
            return False
        kernels.stats_add("agg_count32", 1)
        return True

    def _host_key_cols(self, rep):
        """[(vals, nulls)] per key column (codes for strings), their
        stable slot ids, and decode tables."""
        from .tpu_executors import _rep_string_dict, _slot_id
        chk = self.leaf.chunk()
        key_cols, sids, decodes = [], [], []
        for e in self.key_cols:
            idx = e.index
            sid = _slot_id(self.leaf.ex, idx)
            if sid == "handle":
                kv = rep.handles
                km = np.zeros(rep.n_rows, dtype=bool)
                decode = None
            elif e.eval_type is EvalType.STRING:
                got = _rep_string_dict(rep, sid, chk, idx)
                if got is None:
                    return None
                kv = got[0]
                km = chk.columns[idx].null_mask()
                decode = got[3]
            else:
                kv, km = rep.columns[sid]
                decode = None
            key_cols.append((kv, km))
            sids.append(sid)
            decodes.append(decode)
        return key_cols, tuple(sids), decodes

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        if not self.leaf.take():
            return None
        live = _live_set(live, len(self.out_map))
        needed = _needed_specs(self.slots, self.out_map, live)
        need = sorted(needed)
        rep = self.leaf.replica()
        got = self._host_key_cols(rep)
        if got is None:
            return None
        key_cols, sids, decodes = got
        gidx = _group_index(rep, sids, key_cols)
        self.gidx = gidx
        self._sids = sids
        ng = gidx.n_groups
        ngb = kernels.bucket(max(ng, 1))
        nb = self.leaf.nb()
        jn = _jn()
        dense = ng <= kernels.SEG_UNROLL
        kernels.stats_add("agg_dense" if dense else "agg_sorted", 1)
        need_mm = any(self.specs[k][0] in ("min", "max") for k in need)
        # under a mesh whose shards hold the leaf's rows, each shard
        # reduces its own rows to a partial state and the states merge
        # over the mesh (dist.mesh_sum / mesh_sum_spans / mesh_min /
        # mesh_max): the output is whole on every device, as a parent
        # join's build side wants it.  Both formulations shard the same
        # way; the sorted one reads the index cut per shard
        # (GroupIndex.shards), to the shard's own span of groups where
        # that is less than all of them
        from ..parallel import dist
        mesh = self.leaf.rows_mesh(nb)
        n_mesh = dist.mesh_shards(mesh)
        lrows = _layouts(mesh)[0]
        lwhole = _layouts(self.leaf.mesh)[1]
        # one-device index lanes lie as the leaf's own lanes do
        lidx = lwhole if mesh is None else lrows
        ROWS, WHOLE = dist.specs()
        per = nb // max(n_mesh, 1)

        # shared by this prepare's lane builders; the host copies go
        # once the lanes are on the device
        @functools.lru_cache(maxsize=None)
        def cut():
            return gidx.shards(n_mesh, per)

        @functools.lru_cache(maxsize=None)
        def index_order():
            # positions of the leaf's padded lanes in the index's order
            # (within each shard under a mesh), padding left in place
            if mesh is None:
                return np.concatenate([gidx.order,
                                       np.arange(len(gidx.order), nb)])
            return (cut()[0] + np.arange(n_mesh)[:, None] * per).reshape(-1)
        # the sorted formulation reads the leaf in the index's order:
        # over a clustered index that is the row order and the lanes
        # are the ones every scan of the table shares
        order = None
        if not dense and gidx.clustered:
            kernels.stats_add("agg_clustered", 1)
        elif not dense:
            order = (("by", sids), index_order)
        # what a shard of the sorted formulation bounds: its own span
        # of the groups, [qb] of them from g_lo[shard] on, or (one
        # device; spans that are whole) all [ngb].  From what the index
        # observed, and part of the program key
        g_lo, qb = None, ngb
        if mesh is not None and not dense:
            g_lo, g_hi = rep.memo(("gi_spans", sids, n_mesh, per),
                                  lambda: gidx.spans(n_mesh, per))
            qb = _span_pad(g_lo, g_hi, ngb)
        span = qb < ngb
        if span:
            kernels.stats_add("agg_span_cut", 1)
        count32 = not dense and self._count32(nb)
        # the leaf's rows filtered by the chain above it (selections,
        # semi joins: validity alone, the rows where they lie), in the
        # index's order as the leaf's own are
        self.leaf.order = order
        tv = self.chain.prepare(pb)
        if tv is None:
            return None
        #: the index lanes the reduction reads, in the kernel's order
        if dense:
            # group id per ROW in the narrowest lane, sentinel ngb on
            # padding rows (they match no group)
            lanes = [pb.lane(
                rep, ("gi_rowgid", sids, nb),
                lambda: kernels.pad1(
                    gidx.row_gid(np.min_scalar_type(ngb)), nb, fill=ngb),
                lidx)]
        elif mesh is None:
            lanes = [
                pb.lane(rep, ("gi_ends", sids, ngb),
                        lambda: kernels.pad1(gidx.ends, ngb,
                                             fill=max(rep.n_rows - 1, 0)),
                        lidx)]
            if need_mm:
                # group id per sorted position, sentinel ngb on padding —
                # the segment-min/max lane
                lanes.append(pb.lane(
                    rep, ("gi_sgid", sids, nb),
                    lambda: kernels.pad1(gidx.sorted_gid(), nb, fill=ngb),
                    lidx))
        else:
            def ends_lane():
                if span:
                    return gidx.span_ends(
                        n_mesh, per, g_lo, qb,
                        None if gidx.clustered else cut()[1]).reshape(-1)
                # groups past ng repeat the last boundary: empty ranges
                ends = gidx.shard_ends(n_mesh, per)
                out = np.empty((n_mesh, ngb), dtype=np.int64)
                out[:, :ng] = ends
                out[:, ng:] = ends[:, -1:] if ng else -1
                return out.reshape(-1)
            lanes = [pb.lane(rep, ("gi_shard_ends", sids, qb), ends_lane,
                             lrows)]
            if need_mm:
                lanes.append(pb.lane(
                    rep, ("gi_shard_sgid", sids, nb),
                    lambda: np.where(cut()[2] >= ng, ngb,
                                     cut()[2]).reshape(-1), lrows))
            if span:
                # where each shard's piece begins in the merged table:
                # whole on every device, a replica lane and no parameter
                lanes.append(pb.lane(rep, ("gi_span_lo", sids, qb),
                                     lambda: g_lo.astype(np.int32), lwhole))
        gb_slots = []
        for j, (gk, gn) in enumerate(gidx.keycols):
            gb_slots.append((
                pb.lane(rep, ("gi_gkeys", sids, j, ngb),
                        lambda gk=gk: kernels.pad1(gk, ngb), lwhole),
                pb.lane(rep, ("gi_gknull", sids, j, ngb),
                        lambda gn=gn: kernels.pad1(gn, ngb, True), lwhole)))
        pt = ParamTable()
        pt.add_int(ng)
        # read by no formulation since the leaf's own padding guard
        # serves the sorted one too; the slot stays so that the dense
        # program and its parameter vector are what they were
        pt.add_int(rep.n_rows)
        # in the sorted formulation a count costs a prefix sum and a
        # boundary gather (two in 64 bits): a spec whose argument the
        # replica proves NULL on no row takes ``presence`` for its count
        # (the dense one's counts are masked reductions of a few passes
        # and stay)
        from .tpu_executors import _slot_id
        chk = self.leaf.chunk()
        never_null = frozenset() if dense else _never_null_specs(
            self.specs, need, lambda idx: _column_never_null(
                rep, _slot_id(self.leaf.ex, idx),
                chk.columns[idx].null_mask()))
        arg_fns, keys = _spec_fns(self.specs, pt, never_null)
        ip, fp = pb.params(pt)
        # the cache key must pin EVERYTHING the traced closure depends
        # on: the formulation, key column ids + dtypes (int vs float key
        # lanes retrace), the descriptor->spec slot mapping, the output
        # column map, and the mesh the partial states merge over (none
        # on one device: one-device keys are what they were)
        kdts = tuple((str(s), str(gk.dtype))
                     for s, (gk, _) in zip(sids, gidx.keycols))
        head = "aggdense" if dense else "aggindex"
        pb.key((head, tuple(keys), kdts, tuple(self.slots),
                tuple(self.out_map), nb, ngb) + dist.layout_tag(lrows)
               + ((("span", qb),) if span else ())
               # the counts' width follows from nb, in the key already;
               # the 64-bit side (a bucket of 2^31 rows or more) says so
               # too, and a program of one side never serves the other
               + ((("count64",),) if not dense and not count32 else ()),
               live, len(self.out_map))
        spec_kinds = [k for k, _ in self.specs]
        slots = self.slots
        out_map = self.out_map
        schema_cols = self.plan.schema.columns
        merge_sum, merge_mm = _mesh_merges(mesh)

        def dense_reducers(idx, valid):
            seg = kernels._SegReduce(kernels.jax(), jn, idx[0], valid,
                                     ngb, unroll=True)
            return dict(
                seg_sum=lambda x: merge_sum(seg.sum(x, valid)),
                seg_mm=lambda av, live, kind: merge_mm[kind](
                    seg.minmax(av, live, kind == "min")),
                presence=merge_sum(seg.sum(valid.astype(jn.int64), valid)))

        def sorted_reducers(idx, valid):
            j = kernels.jax()
            # the leaf's lanes, mask and arguments are already in the
            # index's order (each shard's own under a mesh) and the
            # mask's padding guard holds there as in row order: nothing
            # is gathered to sorted order here
            ends = idx[0]
            isg = idx[1] if need_mm else None
            # a shard that bounds its own span alone holds a [qb] piece
            # of the table, from group idx[-1][shard] on
            merge = functools.partial(
                dist.mesh_sum_spans, starts=idx[-1], size=ngb) \
                if span else merge_sum

            def seg(x_s):
                # a group's lower boundary is the group before's upper
                # one, so ONE gather serves both (a 2 M-lane gather is
                # 50 ms on a v5e: it costs its indices), over [ngb]
                # boundaries or the [qb] of the shard's span, before
                # whose first group the shard holds no row; a shard may
                # hold no row up to a group (boundary -1, never on one
                # device); padded groups and entries past the span
                # repeat the last boundary and read zero
                c = kernels.prefix_sum(x_s)
                zero = jn.zeros((), dtype=x_s.dtype)
                hi = jn.where(ends >= 0, c[jn.maximum(ends, 0)], zero)
                lo = jn.concatenate([zero[None], hi[:-1]])
                return merge(hi - lo)

            def seg_count(mask):
                # in 32 bits where the bucket proves a count fits: one
                # word a row and a boundary, merged over the mesh as one
                # (an int64 is two), widened on the [ngb] result alone
                if count32:
                    return seg(mask.astype(jn.int32)).astype(jn.int64)
                return seg(mask.astype(jn.int64))

            def seg_mm(av_s, live_s, kind):
                gl = jn.where(live_s, isg, ngb)
                op = j.ops.segment_min if kind == "min" \
                    else j.ops.segment_max
                return merge_mm[kind](
                    op(av_s, gl, num_segments=ngb + 1)[:ngb])
            return dict(seg_sum=seg, seg_mm=seg_mm, seg_count=seg_count,
                        presence=seg_count(valid))
        reducers = dense_reducers if dense else sorted_reducers

        def reduce(idx, valid, pairs, pr):
            """(rows per group, [(value, null)] per needed spec), each
            [ngb]."""
            red = reducers(idx, valid)
            res = _spec_results(jn, spec_kinds, arg_fns, pairs, pr, valid,
                                n_out=ngb, needed=needed,
                                never_null=never_null, **red)
            return red["presence"], [res[k] for k in need]
        if mesh is not None:
            # merged states are whole by construction (psum; the spans'
            # pieces, and min/max, are gathered and put together alike
            # on every shard, beyond the static checker)
            lane_specs = [ROWS] * len(lanes)
            if span:
                lane_specs[-1] = WHOLE
            reduce = dist.shard_map_unchecked(
                reduce, mesh=mesh,
                in_specs=(lane_specs, ROWS,
                          [(ROWS, ROWS)] * len(tv.meta), (WHOLE, WHOLE)),
                out_specs=(WHOLE, [(WHOLE, WHOLE)] * len(need)))

        def emit(args):
            valid, pairs = tv.emit(args)
            pr = (args[ip], args[fp])
            presence, res = reduce([args[i] for i in lanes], valid,
                                   list(pairs), pr)
            outs = _slot_outputs(
                jn, _spread(len(spec_kinds), need, res), slots)
            gvalid = (jn.arange(ngb) < pr[0][0]) & (presence > 0)
            cols = []
            for i, m in enumerate(out_map):
                if i not in live:
                    cols.append(DEAD)
                elif m[0] == "agg":
                    cols.append(outs[m[1]])
                else:
                    cols.append((args[gb_slots[m[1]][0]],
                                 args[gb_slots[m[1]][1]]))
            return gvalid, cols
        meta = []
        for oc, m in zip(schema_cols, out_map):
            decode = decodes[m[1]] if m[0] == "gb" else None
            meta.append((oc.ret_type, decode))
        return _TView(emit, ngb, meta, head, _agg_nonnull(
            self.specs, slots, out_map, never_null,
            lambda j: rep.memo(("gi_gknonnull", sids, j),
                               lambda: not gidx.keycols[j][1].any())))

    def build_key_info(self):
        """(lo, hi, pos_table np) for the parent join — static per
        replica version (single-int-key indexes only)."""
        rep = self.leaf.replica()
        got = self._host_key_cols(rep)
        if got is None:
            return None
        _, sids, _ = got

        def mk():
            tbl = self.gidx.pos_table()
            if tbl is None:
                return None
            return self.gidx.lo, self.gidx.hi, tbl
        return rep.memo(("gi_postable", sids), mk)

    def key_slot(self) -> int:
        """Schema slot of the (single) group key in the output view."""
        if len(self.key_cols) != 1:
            return -1
        for i, slot in enumerate(self.out_map):
            if slot[0] == "gb":
                return i
        return -1

    def close(self):
        _close_node(self.chain)


def _filters_a_reader(plan) -> bool:
    """``plan`` is a table reader under row filters that keep its rows
    and columns where they are: selections and semi / anti joins, the
    reader on the probe side."""
    while isinstance(plan, PhysicalSelection) or (
            isinstance(plan, PhysicalHashJoin)
            and not isinstance(plan, PhysicalMergeJoin)
            and plan.tp in ("semi", "anti")):
        plan = plan.children[0]
    return isinstance(plan, PhysicalTableReader)


def _filtered_leaf(node) -> Optional[_ReplicaLeaf]:
    """The replica leaf at the bottom of a compiled chain of row
    filters (``_filters_a_reader``), or None where the chain compiled
    into anything else."""
    if isinstance(node, _SelNode):
        return _filtered_leaf(node.child)
    if isinstance(node, _JoinNode) and node.tp in ("semi", "anti") \
            and node.probe_is_left:
        return _filtered_leaf(node.probe)
    return node if isinstance(node, _ReplicaLeaf) else None


def _without_others(plan: PhysicalHashJoin) -> PhysicalHashJoin:
    """A shallow copy of ``plan`` without its residual conditions."""
    import copy
    bare = copy.copy(plan)
    bare.other_conditions = []
    return bare


class _JoinNode:
    """Equi-join on int keys — a single key directly, or several keys
    combined into one COMPOSITE lane (sum((k_i - lo_i) * stride_i), a
    bijection over the bounded cross range).  Device layouts:

    - unique build (planner-proven pk/unique, or a group-index partial
      agg): dense key -> row position table + one gather per build
      column — probe-shaped output, no expansion.  Where the build's
      rows are the probe's own, in the same order (``_aligned``: a table
      stored in key order and an aggregate of another by that key, say),
      neither the table nor any gather: each build lane is read in place.
      Where the table is small beside the probe (``_folded``), the
      build's validity is read once a key into the table, not once a
      probe row.
    - general multiplicity (reference join.go:244 / util/mvmap): the
      build replica's group index is a CSR layout; probe maps key ->
      group through the dense table, per-group VALID counts come from a
      cumsum over the sorted validity, and the variable-size output
      lands in a static bucket via scatter-starts + running-max fill.
    """

    def __init__(self, probe, build, probe_keys, build_keys, tp,
                 probe_is_left, plan, mesh=None, mult=False,
                 session_vars=None):
        self.probe = probe
        self.build = build
        self.probe_keys = list(probe_keys)
        self.build_keys = list(build_keys)
        self.probe_key = self.probe_keys[0]
        self.build_key = self.build_keys[0]
        self.nk = len(self.probe_keys)
        self.tp = tp
        self.probe_is_left = probe_is_left
        self.plan = plan
        self.mesh = mesh
        self.mult = mult
        self.session_vars = session_vars or {}
        self.n_mesh = int(mesh.devices.size) if mesh is not None else 0
        #: set by a prepare that re-partitioned both sides by key hash:
        #: the output then lies by key and is no view a parent can build
        #: on (:func:`_probe_shaped`)
        self.partitioned = False

    @staticmethod
    def compile(plan: PhysicalHashJoin, ctx: _Ctx):
        if isinstance(plan, PhysicalMergeJoin):
            return None
        if plan.tp not in ("inner", "left", "semi", "anti"):
            return None
        if not plan.left_keys \
                or len(plan.left_keys) != len(plan.right_keys):
            return None
        if plan.other_conditions:
            # an inner join's residual conditions filter its output
            # rows: a selection directly above the join (a decorrelated
            # subquery's comparison with the aggregate it joined)
            if plan.tp != "inner" \
                    or not all(is_jittable(c) for c in plan.other_conditions):
                return None
            join = _JoinNode.compile(_without_others(plan), ctx)
            return None if join is None \
                else _SelNode(join, plan.other_conditions, plan)
        for k in list(plan.left_keys) + list(plan.right_keys):
            if not isinstance(k, ExprColumn) \
                    or k.eval_type is not EvalType.INT \
                    or getattr(k.ret_type, "is_unsigned", False):
                return None
        if getattr(plan, "left_conditions", None) \
                or getattr(plan, "right_conditions", None):
            return None  # side conds live in Selections below by now
        nk = len(plan.left_keys)
        lk, rk = plan.left_keys[0], plan.right_keys[0]
        mult = False
        if plan.tp in ("semi", "anti"):
            # semi/anti = a VALIDITY filter on the probe view (no shape
            # change, no gather): the build side's dense pos-table
            # answers membership.  One row per key in that table means
            # the build must be planner-proven unique; the NOT IN
            # null-aware ladder needs build-shape scalars the fused
            # program doesn't carry — both fall to the per-op executor.
            if nk != 1 or getattr(plan, "null_aware", False) \
                    or not getattr(plan, "right_unique", False):
                return None
            build = _compile_node(plan.children[1], ctx)
            if build is None:
                return None
            if not _has_build_key_info(build, rk):
                _close_node(build)
                return None
            probe = _compile_node(plan.children[0], ctx)
            if probe is None:
                _close_node(build)
                return None
            return _JoinNode(probe, build, [lk], [rk], plan.tp, True,
                             plan, mesh=ctx.mesh,
                             session_vars=getattr(ctx.exec_ctx,
                                                  "session_vars", None))
        if nk > 1:
            # multi-key: composite lane over a dense range, leaf/sel
            # build sides only; non-unique key sets ride the same CSR
            # expansion as single keys, over the composite lane
            build_side, probe_side = 1, 0
            build_keys = list(plan.right_keys)
            probe_keys = list(plan.left_keys)
            mult = not getattr(plan, "right_unique", False)
        elif getattr(plan, "right_unique", False):
            build_side, probe_side = 1, 0
            build_keys, probe_keys = [rk], [lk]
        elif getattr(plan, "left_unique", False) and plan.tp == "inner":
            build_side, probe_side = 0, 1
            build_keys, probe_keys = [lk], [rk]
        else:
            # general multiplicity: build stays the right child (the
            # probe must stay the outer side of a LEFT join), CSR over
            # the build replica's group index
            build_side, probe_side = 1, 0
            build_keys, probe_keys = [rk], [lk]
            mult = True
        build = _compile_node(plan.children[build_side], ctx)
        if build is None:
            return None
        ok = _leafish(build) is not None if (nk > 1 or mult) \
            else _has_build_key_info(build, build_keys[0])
        if not ok:
            _close_node(build)
            return None
        probe = _compile_node(plan.children[probe_side], ctx)
        if probe is None:
            _close_node(build)
            return None
        return _JoinNode(probe, build, probe_keys, build_keys,
                         plan.tp, probe_side == 0, plan, mesh=ctx.mesh,
                         mult=mult,
                         session_vars=getattr(ctx.exec_ctx,
                                              "session_vars", None))

    def _place_build(self) -> None:
        """Under a mesh, before the build side uploads: a broadcast
        join's build leaf lies whole on every device; only a shuffle
        join, which re-partitions both sides, leaves it row-sharded."""
        leaf = _leafish(self.build)
        if self.mesh is None or leaf is None:
            return
        from ..parallel import dist
        rep = leaf.replica()
        nbb = kernels.bucket(max(rep.n_rows, 1)) if rep is not None else 0
        leaf.spread = (
            self.tp not in ("semi", "anti") and not self.mult
            and self.nk == 1 and self._shuffle_wanted(
                nbb, nbb, len(leaf.plan.schema.columns),
                self.mesh if dist.shardable(nbb, self.mesh) else None))

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        """Asks the probe for its live columns and the probe keys, the
        build for its live columns (and, where the join may partition,
        its key): a build column nobody reads is not gathered."""
        self._place_build()
        self.partitioned = False
        kernels.stats_add("pipe_joins", 1)
        if _leafish(self.build) is not self.build:
            # the build side is a view: a selection's, a join's, an
            # aggregate's (counted once a fused dispatch, like the rest)
            kernels.stats_add("pipe_view_builds", 1)
        semi = self.tp in ("semi", "anti")
        probe_side = 0 if self.probe_is_left else 1
        npc = len(self.plan.children[probe_side].schema.columns)
        nbc = len(self.plan.children[1 - probe_side].schema.columns)
        # the output view: a semi join's is the probe's; else left, right
        p0, b0 = (0, npc) if self.probe_is_left else (nbc, 0)
        self.n_out = npc if semi else npc + nbc
        self.live = _live_set(live, self.n_out)
        #: live output columns by side, as slots of that side's view
        self.pl = sorted(i - p0 for i in self.live if p0 <= i < p0 + npc)
        self.bl = [] if semi else sorted(
            i - b0 for i in self.live if b0 <= i < b0 + nbc)
        plive = set(self.pl) | {k.index for k in self.probe_keys}
        blive = set(self.bl)
        if self.mesh is not None and self.nk == 1 and not self.mult \
                and not semi:
            blive.add(self.build_key.index)  # a partitioned join reads it
        btv = self.build.prepare(pb, frozenset(blive))
        if btv is None:
            return None
        ptv = self.probe.prepare(pb, frozenset(plive))
        if ptv is None:
            return None
        assert (len(ptv.meta), len(btv.meta)) == (npc, nbc)
        #: the live build columns whose null lane is not gathered
        self.bskip = frozenset(self.bl) & btv.nonnull
        if semi:
            return self._prepare_semi(pb, btv, ptv)
        if self.mult:
            return self._prepare_mult(pb, btv, ptv)
        if self.nk > 1:
            return self._prepare_unique_multi(pb, btv, ptv)
        return self._prepare_unique(pb, btv, ptv)

    def _key(self, pb, part) -> None:
        pb.key(part, self.live, self.n_out, self.bskip)

    def _view(self, emit, nb: int, scope: str, ptv, btv) -> _TView:
        """The output view: the left side's slots, then the right's (a
        semi join's: the probe's).  NULL-free in it: the probe's slots
        as they are, and the build's under an inner join, whose valid
        rows all matched (an outer join's unmatched rows are valid, and
        NULL there)."""
        if self.tp in ("semi", "anti"):
            return _TView(emit, nb, ptv.meta, scope, ptv.nonnull)
        left, right = (ptv, btv) if self.probe_is_left else (btv, ptv)
        lnn, rnn = (tv.nonnull if tv is ptv or self.tp == "inner"
                    else frozenset() for tv in (left, right))
        return _TView(emit, nb, left.meta + right.meta, scope,
                      lnn | {len(left.meta) + i for i in rnn})

    def _gather_build(self, bpairs, at, match) -> list:
        """The build's live columns (``bpairs``, in ``self.bl``'s order)
        at the build rows ``at`` (None: an aligned join's own rows, read
        in place), NULL where ``match`` is not: ``match`` holds only
        where ``at`` is a valid row of the build view, so a column that
        view proves free of NULLs (``self.bskip``) is NULL exactly where
        nothing matched and its null lane is not gathered."""
        def read(x):
            return x if at is None else x[at]
        return [(read(bv), ~match if i in self.bskip else read(bn) | ~match)
                for i, (bv, bn) in zip(self.bl, bpairs)]

    def _aligned(self, ptv, btv) -> bool:
        """The build view's row r is the row the probe's key at row r
        finds, for every row: the probe view holds a replica leaf's rows
        in place (``_probe_rows``), its key is that leaf's own column
        without a NULL, the build's key -> row table read at it is the
        identity (``_aligned_rows``, once a replica version) and the two
        buckets are one.  Such a join reads the build's lanes in place:
        no key lookup, no gather (counter ``pipe_joins_aligned``)."""
        if ptv.nb != btv.nb:
            return False
        got = _probe_rows(self.probe, self.probe_key)
        table = _build_key_table(self.build, self.build_key)
        if got is None or table is None:
            return False
        from .tpu_executors import _slot_id
        leaf, slot = got
        if not _aligned_rows(leaf.replica(), _slot_id(leaf.ex, slot),
                             table):
            return False
        kernels.stats_add("pipe_joins_aligned", 1)
        return True

    def _folded(self, tbl_len: int, nb: int, mesh) -> bool:
        """Whether the join folds the build's validity into its key ->
        row table (``_fold_validity``): where the table holds at most
        half the probe's rows on a device (``nb``, over ``mesh``'s
        devices where the probe is sharded).  A gather costs by its
        indices, and a byte lane's up to twice a word's, so reading the
        validity once a key then costs less than once a probe row.  A
        function of shapes the program is keyed by, nothing else
        (counter ``pipe_joins_folded``)."""
        rows = nb // int(mesh.devices.size) if mesh is not None else nb
        if tbl_len > rows // 2:
            return False
        kernels.stats_add("pipe_joins_folded", 1)
        return True

    def _out(self, ppairs, bcols, nbc: int) -> list:
        """The output view: the probe's live columns as they are, the
        build's live ones (``bcols``, in ``self.bl``'s order), every
        other slot dead."""
        pcols = _only(ppairs, set(self.pl))
        bcols = _spread(nbc, self.bl, bcols)
        return pcols + bcols if self.probe_is_left else bcols + pcols

    # ---- a join's view as a build side under a mesh --------------------

    def _mesh_view(self, nbb: int, aligned: bool = False) -> tuple:
        """(ok, vmesh) of a build side that is a view, under a mesh.
        ``vmesh``: the mesh over which the view lies by rows, or None:
        the view of a join (through selections and projections, which
        keep the rows' places) at a bucket that shards is computed a row
        range a device, a quarter of the work on four; a leaf was placed
        whole by :meth:`_place_build`, an aggregate merged its partial
        states whole, a bucket too small to shard lies whole.  What
        every device will hold whole of a view by rows — the validity,
        the live columns' value lanes, the null lanes that are gathered
        — is priced as a broadcast leaf's copies are
        (dist.broadcast_over_budget): ``ok`` False is over the budget,
        the statement leaves the fused pipeline.  Counts the view build
        (``pipe_mesh_views``, whichever layout) and what the all-gathers
        move (``reshard_bytes``: 8 bytes a value or dictionary code, 1 a
        null or validity).  An ``aligned`` join reads a view by rows as
        its own shard: nothing is held whole or moved."""
        if self.mesh is None or _leafish(self.build) is self.build:
            return True, None
        from ..parallel import dist
        node = self.build
        while isinstance(node, (_SelNode, _ProjNode)):
            node = node.child
        vmesh = self.mesh if isinstance(node, _JoinNode) \
            and dist.shardable(nbb, self.mesh) else None
        if vmesh is not None and not aligned:
            lanes = len(self.bl)
            if dist.broadcast_over_budget(
                    nbb * max(lanes, 1) * dist.COST_COLUMN_BYTES,
                    self.n_mesh):
                return False, None
            kernels.stats_add("reshard_bytes", nbb * (
                1 + 8 * lanes + len(set(self.bl) - self.bskip)))
        kernels.stats_add("pipe_mesh_views", 1)
        return True, vmesh

    def _whole_view(self, vmesh, bvalid, bpairs):
        """(validity, live build columns in ``self.bl``'s order) of a
        view that lies by rows over ``vmesh``, whole on every device: one
        all-gather a lane (``dist.gather_rows``).  The null lane of a
        column the view proves free of NULLs (``self.bskip``) is read by
        nobody and stays where it is: its place holds the value lane."""
        if vmesh is None:
            return bvalid, bpairs
        from ..parallel import dist
        keep = [i not in self.bskip for i in self.bl]
        got = dist.gather_rows(
            vmesh, [bvalid] + [bv for bv, _ in bpairs]
            + [bn for (_, bn), k in zip(bpairs, keep) if k])
        vals = got[1:1 + len(bpairs)]
        nulls = iter(got[1 + len(bpairs):])
        return got[0], [(v, next(nulls) if k else v)
                        for v, k in zip(vals, keep)]

    # ---- semi / anti: membership folds into probe validity -------------

    def _prepare_semi(self, pb, btv, ptv) -> Optional[_TView]:
        """Semi/anti join as a validity AND over the probe view: probe
        key -> build pos-table -> live?  The probe's pairs pass through
        untouched, so an entire Q5-style join chain with an interleaved
        semijoin stays ONE traced program."""
        if self._aligned(ptv, btv):
            return self._prepare_aligned(pb, btv, ptv)
        info = _prepare_build_key_info(self.build, self.build_key, pb,
                                       self.mesh)
        if info is None:
            return None
        lo, hi, it, tbl_len = info
        jn = _jn()
        nb = ptv.nb
        nbb = btv.nb
        ok, vmesh = self._mesh_view(nbb)
        if not ok:
            return None
        pk_slot = self.probe_key.index
        anti = self.tp == "anti"
        pt = ParamTable()
        pt.add_int(lo)
        pt.add_int(hi)
        ip, fp = pb.params(pt)
        # under a mesh each shard filters its own probe rows against the
        # table and the build's validity, both whole on every device
        from ..parallel import dist
        mesh = self.mesh if dist.shardable(nb, self.mesh) else None
        fold = self._folded(tbl_len, nb, mesh)
        self._key(pb, ("semijoin", anti, nb, nbb, tbl_len, pk_slot,
                       len(ptv.meta), len(btv.meta))
                  + dist.layout_tag(_layouts(mesh)[0]))
        live = self.live

        def kernel(pkey, pvalid, bvalid, tbl, pr):
            kp, knull = pkey
            lo_p, hi_p = pr[0][0], pr[0][1]
            inr = (kp >= lo_p) & (kp <= hi_p) & ~knull
            pos0 = jn.clip(kp - lo_p, 0, tbl_len - 1)
            if fold:
                tbl = _fold_validity(tbl, bvalid, nbb)
            pos = jn.where(inr, tbl[pos0].astype(jn.int64), -1)
            match = (pos >= 0) if fold \
                else (pos >= 0) & bvalid[jn.clip(pos, 0, nbb - 1)]
            # anti (NOT EXISTS shape, never null-aware here): a NULL
            # probe key matches nothing and therefore SURVIVES
            return pvalid & (~match if anti else match)

        if mesh is not None:
            shard_map, _ = dist.shard_map_fn()
            ROWS, WHOLE = dist.specs()
            kernel = shard_map(
                kernel, mesh=mesh,
                in_specs=((ROWS, ROWS), ROWS, WHOLE, WHOLE, (WHOLE, WHOLE)),
                out_specs=ROWS)

        def emit(args):
            bvalid, _bpairs = btv.emit(args)
            pvalid, ppairs = ptv.emit(args)
            bvalid, _ = self._whole_view(vmesh, bvalid, [])
            valid_out = kernel(ppairs[pk_slot], pvalid, bvalid, args[it],
                               (args[ip], args[fp]))
            return valid_out, _only(ppairs, live)
        return self._view(emit, nb, "semijoin", ptv, btv)

    # ---- aligned: the build's rows are the probe's own, read in place ---

    def _prepare_aligned(self, pb, btv, ptv) -> Optional[_TView]:
        """A unique or semi join that :meth:`_aligned` proved aligned:
        the build's validity and live lanes are read at the probe's own
        rows.  A row matches where the build's row is valid and the
        probe's is a real one (its key, NULL on no row, is NULL on the
        padding), which is where the gathering join's lookup finds a
        valid build row; the rest is that join's: a semi join keeps the
        matched probe rows (an anti join the others), an inner join its
        matched rows with the build's columns, an outer join every row
        with them NULL where nothing matched.  Under a mesh each shard
        reads its own row range of the build's lanes: a view by rows is
        its own shard, one that lies whole is cut where it lies."""
        from ..parallel import dist
        nb = ptv.nb
        self._mesh_view(nb, aligned=True)
        mesh = self.mesh if dist.shardable(nb, self.mesh) else None
        semi = self.tp in ("semi", "anti")
        anti = self.tp == "anti"
        outer = self.tp == "left"
        scope = "semijoin" if semi else "join"
        pk_slot = self.probe_key.index
        self._key(pb, (scope, "aligned", self.tp, nb, pk_slot,
                       self.probe_is_left, len(btv.meta), len(ptv.meta))
                  + dist.layout_tag(_layouts(mesh)[0]))
        bl, nbc, live = self.bl, len(btv.meta), self.live

        def kernel(pkey, pvalid, bpairs, bvalid):
            match = bvalid & ~pkey[1]
            if semi:
                return pvalid & (~match if anti else match), []
            return (pvalid if outer else pvalid & match), \
                self._gather_build(bpairs, None, match)

        if mesh is not None:
            shard_map, _ = dist.shard_map_fn()
            ROWS, _whole = dist.specs()
            kernel = shard_map(
                kernel, mesh=mesh,
                in_specs=((ROWS, ROWS), ROWS, [(ROWS, ROWS)] * len(bl),
                          ROWS),
                out_specs=(ROWS, [(ROWS, ROWS)] * len(bl)))

        def emit(args):
            bvalid, bpairs = btv.emit(args)
            pvalid, ppairs = ptv.emit(args)
            valid_out, bcols = kernel(ppairs[pk_slot], pvalid,
                                      [bpairs[i] for i in bl], bvalid)
            if semi:
                return valid_out, _only(ppairs, live)
            return valid_out, self._out(ppairs, bcols, nbc)
        return self._view(emit, nb, scope, ptv, btv)

    # ---- multi-key unique build: composite lane + dense table ----------

    def _host_raw_key_cols(self, node, keys):
        """Raw host (vals, nulls) per key over a leaf/sel chain, plus the
        (replica, stable slot ids)."""
        leaf = _leafish(node)
        if leaf is None:
            return None
        rep = leaf.replica()
        if rep is None:
            return None
        from .tpu_executors import _slot_id
        cols, sids = [], []
        for k in keys:
            sid = _slot_id(leaf.ex, k.index)
            if sid == "handle":
                kv = rep.handles
                km = np.zeros(rep.n_rows, dtype=bool)
            else:
                kv, km = rep.columns[sid]
            if kv.dtype != np.int64:
                return None
            cols.append((kv, km))
            sids.append(sid)
        return rep, tuple(sids), cols

    def _prepare_unique_multi(self, pb, btv, ptv) -> Optional[_TView]:
        got = self._host_raw_key_cols(self.build, self.build_keys)
        if got is None:
            return None
        rep, sids, cols = got
        # per replica version: the full-column min/max scans + composite
        # lane build amortize like the single-key bounds/pos tables
        spec = rep.memo(("composite_spec", sids),
                        lambda: _composite_spec(cols))
        if spec is None:
            return None
        los, his, strides, comp, null_any, total = spec
        jn = _jn()
        nb, nbb = ptv.nb, btv.nb
        pk_slots = tuple(k.index for k in self.probe_keys)
        outer = self.tp == "left"
        probe_is_left = self.probe_is_left

        def mk():
            # dense composite -> build row (uniqueness over the key SET
            # is planner-proven; rows with any NULL key never match)
            tbl = np.full(total, -1, dtype=np.int32)
            live = ~null_any
            tbl[comp[live]] = np.nonzero(live)[0].astype(np.int32)
            return tbl
        lwhole = _layouts(self.mesh)[1]
        it = pb.lane(rep, ("postable_multi", sids), mk, lwhole)
        pt = ParamTable()
        for lo, hi, st in zip(los, his, strides):
            pt.add_int(lo)
            pt.add_int(hi)
            pt.add_int(st)
        ip, fp = pb.params(pt)
        from ..parallel import dist
        fold = self._folded(total, nb, self.mesh if dist.shardable(
            nb, self.mesh) else None)
        self._key(pb, ("joinmk", nb, nbb, total, pk_slots, outer,
                       probe_is_left, len(btv.meta), len(ptv.meta))
                  + (("fold",) if fold else ()))
        bl, nbc = self.bl, len(btv.meta)

        def emit(args):
            bvalid, bpairs = btv.emit(args)
            pvalid, ppairs = ptv.emit(args)
            pr = (args[ip], args[fp])
            ok = pvalid
            comp_t = jn.zeros(nb, dtype=jn.int64)
            for j, slot in enumerate(pk_slots):
                kv, kn = ppairs[slot]
                lo_ = pr[0][3 * j]
                hi_ = pr[0][3 * j + 1]
                st_ = pr[0][3 * j + 2]
                ok = ok & (kv >= lo_) & (kv <= hi_) & ~kn
                comp_t = comp_t + (kv - lo_) * st_
            pos0 = jn.clip(comp_t, 0, total - 1)
            tbl = _fold_validity(args[it], bvalid, nbb) if fold \
                else args[it]
            pos = jn.where(ok, tbl[pos0].astype(jn.int64), -1)
            pos_safe = jn.clip(pos, 0, nbb - 1)
            match = (pos >= 0) if fold else (pos >= 0) & bvalid[pos_safe]
            valid_out = pvalid if outer else (pvalid & match)
            gathered = self._gather_build([bpairs[i] for i in bl],
                                          pos_safe, match)
            return valid_out, self._out(ppairs, gathered, nbc)
        return self._view(emit, nb, "joinmk", ptv, btv)

    # ---- unique build side: dense pos table + gather -------------------

    def _host_key_lane(self, node, key: ExprColumn):
        """The raw (pre-filter) host values of a node view's key lane
        (unpadded — _shuffle_cap_of pads to the device bucket), the live
        row count, and a (rep, memo_key) handle for replica-backed lanes
        so the capacity histogram memoizes per replica version.  None =
        shape without host-visible keys (fall back to broadcast)."""
        if isinstance(node, _SelNode):
            return self._host_key_lane(node.child, key)
        if isinstance(node, _ReplicaLeaf):
            rep = node.replica()
            if rep is None:
                return None
            from .tpu_executors import _slot_id
            sid = _slot_id(node.ex, key.index)
            kv = rep.handles if sid == "handle" else rep.columns[sid][0]
            if kv.dtype != np.int64:
                return None
            return kv, rep.n_rows, (rep, ("shufcap", sid))
        if isinstance(node, _AggIndexNode):
            if node.gidx is None or node.key_slot() != key.index:
                return None
            gk = node.gidx.gkeys
            if gk.dtype != np.int64:
                return None
            rep = node.leaf.replica()
            return gk, node.gidx.n_groups, (rep, ("shufcap_gi",
                                                  node._sids))
        if isinstance(node, _HostLeaf):
            chk = node.chunk()
            if chk is None:
                return None
            v = chk.columns[key.index].values()
            if v.dtype != np.int64:
                return None
            return v, chk.num_rows(), None  # per-query data: no memo
        return None

    @staticmethod
    def _shuffle_cap_of(lane, nbucket: int, n: int) -> int:
        from ..parallel import dist
        kv, n_rows, memo = lane

        def calc():
            return dist.shuffle_cap(kernels.pad1(kv, nbucket), n, n_rows)
        if memo is None:
            return calc()
        rep, mkey = memo
        return rep.memo(mkey + (nbucket, n), calc)

    @staticmethod
    def _broadcast_default() -> int:
        """The sysvar's shipped default — a session value differing from
        it is an explicit operator override.  Read from DEFAULT_SYSVARS
        (one definition; lazy import avoids the session<->executor
        cycle)."""
        from ..session.session import DEFAULT_SYSVARS
        return int(DEFAULT_SYSVARS["tidb_broadcast_build_max_rows"])

    def _shuffle_wanted(self, nb: int, nbb: int, bcols: int, mesh) -> bool:
        """Broadcast-vs-shuffle strategy (reference P4 north star).  The
        PLANNER decides by cost (device.py _mesh_join_strategy: broadcast
        bytes x mesh size vs one-pass shuffle volume, estRows from
        ANALYZE stats — the task.go:146 GetCost pattern); the
        tidb_broadcast_build_max_rows knob applies only when set away
        from its default (manual override, in rows).  ``bcols``: the
        build view's columns, for the bytes the run-time budget counts."""
        if mesh is None:
            return False
        n = int(mesh.devices.size)
        if n & (n - 1) or nb % n or nbb % n:
            return False
        default = self._broadcast_default()
        try:
            thresh = int(self.session_vars.get(
                "tidb_broadcast_build_max_rows", default))
        except Exception:
            return False
        if thresh != default:
            return nbb > thresh  # explicit knob override
        strategy = getattr(self.plan, "mesh_strategy", None)
        if strategy == "shuffle":
            return True
        # a plan-time "broadcast" stays subject to the RUNTIME budget:
        # estRows can be stale while nbb is the actual build bucket —
        # replicating an unexpectedly-huge build side to every shard is
        # the memory blow-up the budget protects against.  In bytes, as
        # the planner's
        from ..parallel import dist
        return dist.broadcast_over_budget(
            nbb * max(bcols, 1) * dist.COST_COLUMN_BYTES, n)

    def _prepare_unique_shuffle(self, pb, btv, ptv, mesh) \
            -> Optional[_TView]:
        """Partitioned-build mesh join: all_to_all BOTH sides by key hash
        over the mesh axis, then each shard joins its partition locally
        (sort + searchsorted).  No shard ever holds the whole build side."""
        from ..parallel import dist
        jn = _jn()
        n = int(mesh.devices.size)
        nb, nbb = ptv.nb, btv.nb
        got_p = self._host_key_lane(self.probe, self.probe_key)
        got_b = self._host_key_lane(self.build, self.build_key)
        if got_p is None or got_b is None:
            return None
        pn_rows = got_p[1]
        bn_rows = got_b[1]
        capp = self._shuffle_cap_of(got_p, nb, n)
        capb = self._shuffle_cap_of(got_b, nbb, n)
        # skew gates, BOTH sides: a clustered hash would make one shard's
        # receive buffer rival the whole table — broadcast is strictly
        # better there (counted: tinysql_shard_skew_retries_total)
        from ..ops import shardops
        if n * n * capp > max(MAX_EXPAND, 2 * nb):
            shardops.record_skew_retry()
            return None
        if n * n * capb > max(MAX_EXPAND, 2 * nbb):
            shardops.record_skew_retry()
            return None
        pt = ParamTable()
        pt.add_int(pn_rows)
        pt.add_int(bn_rows)
        ip, fp = pb.params(pt)
        pk_slot = self.probe_key.index
        bk_slot = self.build_key.index
        outer = self.tp == "left"
        probe_is_left = self.probe_is_left
        nbc = len(btv.meta)
        # only the live columns and the two keys are exchanged
        px = sorted(set(self.pl) | {pk_slot})
        bx = sorted(set(self.bl) | {bk_slot})
        pl_at = [px.index(i) for i in self.pl]
        bl_at = [bx.index(i) for i in self.bl]
        pk_at, bk_at = px.index(pk_slot), bx.index(bk_slot)
        npx, nbx = len(px), len(bx)
        self._key(pb, ("joinshuf", nb, nbb, capp, capb, pk_slot, bk_slot,
                       outer, probe_is_left, nbc, len(ptv.meta), n))
        # shard-exchange economics: the all_to_all lane volume this
        # program moves per dispatch (value+null byte per slot, plus the
        # validity lane) and one round at the receive-buffer HWM
        shardops.record_exchange(n * capp * (9 * npx + 1)
                                 + n * capb * (9 * nbx + 1))
        shardops.note_round(max(n * capp, n * capb))

        def kernel(ppairs, pvalid, bpairs, bvalid, pr):
            from jax import lax
            mp, mb = nb // n, nbb // n
            si = lax.axis_index("shard").astype(jn.int64)
            gp = si * mp + jn.arange(mp)
            gb_ = si * mb + jn.arange(mb)
            dp = dist.hash_dest_traced(jn, ppairs[pk_at][0], n, gp,
                                       pr[0][0])
            db = dist.hash_dest_traced(jn, bpairs[bk_at][0], n, gb_,
                                       pr[0][1])
            p_lanes = []
            for v, m_ in ppairs:
                p_lanes += [(v, jn.zeros((), dtype=v.dtype)), (m_, True)]
            p_lanes.append((pvalid, False))
            p_recv = dist.exchange_lanes(jn, p_lanes, dp, capp, n)
            b_lanes = []
            for v, m_ in bpairs:
                b_lanes += [(v, jn.zeros((), dtype=v.dtype)), (m_, True)]
            b_lanes.append((bvalid, False))
            b_recv = dist.exchange_lanes(jn, b_lanes, db, capb, n)
            P_ = [(p_recv[2 * i], p_recv[2 * i + 1]) for i in range(npx)]
            pv_r = p_recv[-1]
            B_ = [(b_recv[2 * i], b_recv[2 * i + 1]) for i in range(nbx)]
            bv_r = b_recv[-1]
            BN = n * capb
            bk_r, bkn_r = B_[bk_at]
            pk_r, pkn_r = P_[pk_at]
            hit, brow = dist.local_unique_join(
                jn, bk_r, bv_r & ~bkn_r, pk_r, BN)
            matched = hit & ~pkn_r & pv_r
            valid_out = pv_r if outer else matched
            # ``hit`` holds only on a received build row that is valid
            # (local_unique_join matches among ``bv_r & ~bkn_r``)
            bcols = self._gather_build([B_[i] for i in bl_at], brow,
                                       matched)
            return valid_out, [P_[i] for i in pl_at], bcols

        shard_map, _ = dist.shard_map_fn()
        ROWS, WHOLE = dist.specs()
        sharded = shard_map(
            kernel, mesh=mesh,
            in_specs=([(ROWS, ROWS)] * npx, ROWS, [(ROWS, ROWS)] * nbx,
                      ROWS, (WHOLE, WHOLE)),
            out_specs=(ROWS, [(ROWS, ROWS)] * len(pl_at),
                       [(ROWS, ROWS)] * len(bl_at)))

        def emit(args):
            bvalid, bpairs = btv.emit(args)
            pvalid, ppairs = ptv.emit(args)
            valid_out, pcols, bcols = sharded(
                [ppairs[i] for i in px], pvalid,
                [bpairs[i] for i in bx], bvalid, (args[ip], args[fp]))
            return valid_out, self._out(
                _spread(len(ptv.meta), self.pl, pcols), bcols, nbc)
        self.partitioned = True
        return self._view(emit, n * n * capp, "joinshuf", ptv, btv)

    def _prepare_unique(self, pb, btv, ptv) -> Optional[_TView]:
        if self._aligned(ptv, btv):
            return self._prepare_aligned(pb, btv, ptv)
        from ..parallel import dist as _dist
        if self._shuffle_wanted(ptv.nb, btv.nb, len(btv.meta),
                                self.mesh if _dist.shardable(ptv.nb,
                                                             self.mesh)
                                else None):
            out = self._prepare_unique_shuffle(pb, btv, ptv, self.mesh)
            if out is not None:
                return out  # else: broadcast below
        info = _prepare_build_key_info(self.build, self.build_key, pb,
                                       self.mesh)
        if info is None:
            return None
        lo, hi, it, tbl_len = info
        jn = _jn()
        nb = ptv.nb
        nbb = btv.nb
        ok, vmesh = self._mesh_view(nbb)
        if not ok:
            return None
        pk_slot = self.probe_key.index
        pt = ParamTable()
        pt.add_int(lo)
        pt.add_int(hi)
        ip, fp = pb.params(pt)
        outer = self.tp == "left"
        # multi-chip: shard the PROBE side over the mesh, broadcast the
        # build table + build view (SURVEY §2.11 P4: partition one side,
        # probe rides ICI-local gathers, no cross-chip traffic per row)
        from ..parallel import dist
        mesh = self.mesh if dist.shardable(nb, self.mesh) else None
        n_mesh = self.n_mesh if mesh is not None else 0
        fold = self._folded(tbl_len, nb, mesh)
        probe_is_left = self.probe_is_left
        self._key(pb, ("join", nb, nbb, tbl_len, pk_slot, outer,
                       probe_is_left, len(btv.meta), len(ptv.meta),
                       n_mesh))
        bl, nbc = self.bl, len(btv.meta)

        def kernel(pkey, pvalid, bpairs, bvalid, tbl, pr):
            # ``bpairs``: the build's live columns alone
            kp, knull = pkey
            lo_p, hi_p = pr[0][0], pr[0][1]
            inr = (kp >= lo_p) & (kp <= hi_p) & ~knull
            pos0 = jn.clip(kp - lo_p, 0, tbl_len - 1)
            if fold:
                tbl = _fold_validity(tbl, bvalid, nbb)
            pos = jn.where(inr, tbl[pos0].astype(jn.int64), -1)
            pos_safe = jn.clip(pos, 0, nbb - 1)
            match = (pos >= 0) if fold else (pos >= 0) & bvalid[pos_safe]
            if outer:
                valid_out = pvalid
            else:
                valid_out = pvalid & match
            return valid_out, self._gather_build(bpairs, pos_safe, match)

        if mesh is not None:
            shard_map, _ = dist.shard_map_fn()
            ROWS, WHOLE = dist.specs()
            sharded = shard_map(
                kernel, mesh=mesh,
                in_specs=((ROWS, ROWS), ROWS, [(WHOLE, WHOLE)] * len(bl),
                          WHOLE, WHOLE, (WHOLE, WHOLE)),
                out_specs=(ROWS, [(ROWS, ROWS)] * len(bl)))
        else:
            sharded = kernel

        def emit(args):
            bvalid, bpairs = btv.emit(args)
            pvalid, ppairs = ptv.emit(args)
            bvalid, blive = self._whole_view(vmesh, bvalid,
                                             [bpairs[i] for i in bl])
            valid_out, gathered = sharded(
                ppairs[pk_slot], pvalid, blive, bvalid,
                args[it], (args[ip], args[fp]))
            return valid_out, self._out(ppairs, gathered, nbc)
        return self._view(emit, nb, "join", ptv, btv)

    # ---- general multiplicity: CSR over the build group index ----------

    def _prepare_mult(self, pb, btv, ptv) -> Optional[_TView]:
        from .tpu_executors import _slot_id
        leaf = _leafish(self.build)
        rep = leaf.replica()
        if rep is None:
            return None
        cspec = None
        if self.nk > 1:
            # multi-key CSR: group index over the composite lane
            got = self._host_raw_key_cols(self.build, self.build_keys)
            if got is None:
                return None
            rep, sids0, bcols_host = got
            cspec = rep.memo(("composite_spec", sids0),
                             lambda: _composite_spec(bcols_host))
            if cspec is None:
                return None
            kv, km = cspec[3], cspec[4]
            sids = ("comp",) + sids0
        else:
            sid = _slot_id(leaf.ex, self.build_key.index)
            if sid == "handle":
                kv, km = rep.handles, np.zeros(rep.n_rows, dtype=bool)
            else:
                kv, km = rep.columns[sid]
            sids = (sid,)
        gidx = _group_index(rep, sids, [(kv, km)])

        def mk():
            tbl = gidx.pos_table()
            return None if tbl is None else (gidx.lo, gidx.hi, tbl)
        got = rep.memo(("gi_postable", sids), mk)
        if got is None:
            return None
        lo, hi, tbl = got
        raw = gidx.raw_counts()
        outer = self.tp == "left"
        # mesh: shard the PROBE side, broadcast the CSR structures; the
        # per-shard expansion bucket needs host-exact per-shard bounds
        from ..parallel import dist
        mesh = self.mesh if dist.shardable(ptv.nb, self.mesh) else None
        n_mesh = int(mesh.devices.size) if mesh is not None else 0
        per_probe = self._per_probe_counts(raw, tbl, lo, hi, ptv, outer,
                                           cspec=cspec)
        if mesh is not None and per_probe is None:
            mesh = None  # no host probe keys: per-shard bound unknowable
            n_mesh = 0
        ob = self._expand_bucket(raw, ptv, outer, per_probe,
                                 shards=max(n_mesh, 1))
        if ob is None and mesh is not None:
            # probe skew blew the per-shard bound: retry unsharded
            # before abandoning the device pipeline (counted:
            # tinysql_shard_skew_retries_total feeds the imbalance rule)
            from ..ops import shardops
            shardops.record_skew_retry()
            mesh = None
            n_mesh = 0
            ob = self._expand_bucket(raw, ptv, outer, per_probe)
        if ob is None:
            return None
        jn = _jn()
        nb = ptv.nb           # probe bucket
        nbb = btv.nb          # build bucket == leaf bucket (sel keeps nb)
        ng = gidx.n_groups
        ngb = kernels.bucket(max(ng, 1))
        tbl_len = int(tbl.shape[0])
        pk_slots = tuple(k.index for k in self.probe_keys)
        lwhole = _layouts(self.mesh)[1]
        io = pb.lane(rep, ("gi_order", sids, nbb),
                     lambda: kernels.pad1(gidx.order, nbb), lwhole)
        ie = pb.lane(rep, ("gi_ends", sids, ngb),
                     lambda: kernels.pad1(gidx.ends, ngb,
                                          fill=max(rep.n_rows - 1, 0)),
                     lwhole)
        it = pb.lane(rep, ("gi_postable_dev", sids), lambda: tbl, lwhole)
        pt = ParamTable()
        pt.add_int(ng)
        pt.add_int(rep.n_rows)
        pt.add_int(lo)
        pt.add_int(hi)
        if cspec is not None:
            for klo, khi, kst in zip(cspec[0], cspec[1], cspec[2]):
                pt.add_int(klo)
                pt.add_int(khi)
                pt.add_int(kst)
        ip, fp = pb.params(pt)
        probe_is_left = self.probe_is_left
        nk = self.nk
        npc, nbc = len(ptv.meta), len(btv.meta)
        nb_loc = nb // n_mesh if n_mesh else nb
        self._key(pb, ("joinm", nb, nbb, ngb, ob, tbl_len, pk_slots, outer,
                       probe_is_left, nbc, npc, n_mesh))
        # the expansion reads the probe's keys and carries its live
        # columns; of the build's it gathers the live ones
        px = sorted(set(self.pl) | set(pk_slots))
        pl_at = [px.index(i) for i in self.pl]
        pk_at = [px.index(i) for i in pk_slots]
        bl = self.bl

        def kernel(ppairs, pvalid, bpairs, bvalid, order, ends, tbl_d,
                   pr):
            from jax import lax
            nb = nb_loc  # per-shard probe rows (== global when no mesh)
            ng_p, nrows_p, lo_p, hi_p = (pr[0][0], pr[0][1], pr[0][2],
                                         pr[0][3])
            # per-group VALID counts from one cumsum over sorted validity
            in_table = jn.arange(nbb) < nrows_p
            vs = bvalid[order] & in_table
            c = jn.cumsum(vs.astype(jn.int64))
            gmask = jn.arange(ngb) < ng_p
            prev = jn.concatenate([jn.full((1,), -1, dtype=jn.int64),
                                   ends[:-1]])
            prev_safe = jn.maximum(prev, 0)
            start_c = jn.where(prev >= 0, c[prev_safe], 0)
            vcnt = jn.where(gmask, c[ends] - start_c, 0)
            # compacted sorted order: comp[j] = row of j-th valid entry
            vidx = jn.nonzero(vs, size=nbb, fill_value=0)[0]
            comp = order[vidx]
            # probe -> group -> multiplicity (multi-key probes compute
            # the composite lane from per-key params)
            if nk > 1:
                ok = pvalid
                kp = jn.zeros(nb, dtype=jn.int64)
                for j, at in enumerate(pk_at):
                    kvj, knj = ppairs[at]
                    klo = pr[0][4 + 3 * j]
                    khi = pr[0][4 + 3 * j + 1]
                    kst = pr[0][4 + 3 * j + 2]
                    ok = ok & (kvj >= klo) & (kvj <= khi) & ~knj
                    kp = kp + (kvj - klo) * kst
                inr = ok & (kp >= lo_p) & (kp <= hi_p)
                kp = jn.clip(kp, lo_p, hi_p)
            else:
                kp, knull = ppairs[pk_at[0]]
                inr = (kp >= lo_p) & (kp <= hi_p) & ~knull & pvalid
            pos0 = jn.clip(kp - lo_p, 0, tbl_len - 1)
            g = jn.where(inr, tbl_d[pos0].astype(jn.int64), -1)
            gsafe = jn.clip(g, 0, ngb - 1)
            m = jn.where(g >= 0, vcnt[gsafe], 0)
            if outer:
                cnt = jn.where(pvalid, jn.maximum(m, 1), 0)
            else:
                cnt = m
            offs = jn.cumsum(cnt) - cnt   # exclusive prefix
            total = offs[-1] + cnt[-1]
            # two-phase expansion: scatter each probe row's id at its
            # output start, running-max fill assigns every output slot
            tgt = jn.where(cnt > 0, offs, ob)  # ob = dropped (OOB)
            base = jn.zeros(ob, dtype=jn.int64).at[tgt].set(
                jn.arange(nb) + 1, mode="drop")
            pidx = lax.cummax(base, axis=0) - 1
            valid_out = (pidx >= 0) & (jn.arange(ob) < total)
            ps = jn.clip(pidx, 0, nb - 1)
            k = jn.arange(ob) - offs[ps]
            gj = g[ps]
            gjs = jn.clip(gj, 0, ngb - 1)
            matched = (gj >= 0) & (k < m[ps]) & valid_out
            brow = comp[jn.clip(start_c[gjs] + k, 0, nbb - 1)]
            pcols = [(pv[ps], pn[ps])
                     for pv, pn in (ppairs[i] for i in pl_at)]
            # ``brow`` of a matched slot is the k-th VALID row of its
            # group (``comp`` lists valid rows, ``k < m``)
            bcols = self._gather_build(bpairs, brow, matched)
            return valid_out, pcols, bcols

        if mesh is not None:
            # probe side sharded over the mesh, CSR structures broadcast
            # (each shard expands its own probe block into its own
            # per-shard bucket — SURVEY §2.11 P4)
            shard_map, _ = dist.shard_map_fn()
            ROWS, WHOLE = dist.specs()
            sharded = shard_map(
                kernel, mesh=mesh,
                in_specs=([(ROWS, ROWS)] * len(px), ROWS,
                          [(WHOLE, WHOLE)] * len(bl), WHOLE, WHOLE, WHOLE,
                          WHOLE, (WHOLE, WHOLE)),
                out_specs=(ROWS, [(ROWS, ROWS)] * len(pl_at),
                           [(ROWS, ROWS)] * len(bl)))
        else:
            sharded = kernel

        def emit(args):
            bvalid, bpairs = btv.emit(args)
            pvalid, ppairs = ptv.emit(args)
            valid_out, pcols, bcols = sharded(
                [ppairs[i] for i in px], pvalid, [bpairs[i] for i in bl],
                bvalid, args[io], args[ie], args[it],
                (args[ip], args[fp]))
            return valid_out, self._out(
                _spread(npc, self.pl, pcols), bcols, nbc)
        return self._view(emit, ob * max(n_mesh, 1), "joinm", ptv, btv)

    def _per_probe_counts(self, raw, tbl, lo, hi, ptv, outer, cspec=None):
        """Host per-probe-row match-count UPPER bounds (pre-filter group
        sizes; filters only shrink), padded to the probe bucket — feeds
        both the global and the per-shard expansion bounds.  None when
        the probe side has no host-visible keys."""
        from .tpu_executors import _slot_id
        pkv = pkm = None
        if cspec is not None:
            got = self._host_raw_key_cols(self.probe, self.probe_keys)
            if got is not None:
                _, _, pcols = got
                los, his, strides = cspec[0], cspec[1], cspec[2]
                pkm = np.zeros(len(pcols[0][0]), dtype=bool)
                pkv = np.zeros(len(pcols[0][0]), dtype=np.int64)
                for (kvj, kmj), klo, khi, kst in zip(pcols, los, his,
                                                     strides):
                    pkm |= kmj | (kvj < klo) | (kvj > khi)
                    pkv += (np.clip(kvj, klo, khi) - klo) * kst
        else:
            pleaf = _leafish(self.probe)
            if pleaf is not None:
                prep = pleaf.replica()
                if prep is not None:
                    psid = _slot_id(pleaf.ex, self.probe_key.index)
                    if psid == "handle":
                        pkv = prep.handles
                        pkm = np.zeros(prep.n_rows, dtype=bool)
                    else:
                        pkv, pkm = prep.columns[psid]
        if pkv is None:
            return None
        inr = (~pkm) & (pkv >= lo) & (pkv <= hi)
        gsafe = np.where(inr, pkv - lo, 0)
        g = np.where(inr, tbl[gsafe], -1)
        per = np.where(g >= 0, raw[np.clip(g, 0, max(len(raw) - 1, 0))],
                       0)
        if outer:
            per = np.maximum(per, 1)
        return kernels.pad1(per.astype(np.int64), ptv.nb)

    def _expand_bucket(self, raw, ptv, outer, per_probe, shards: int = 1):
        """Static (per-shard) output bucket for the CSR expansion.  None
        = too large, fall off the device pipeline."""
        if per_probe is None:
            mx = int(raw.max()) if len(raw) else 0
            bound = ptv.nb * max(mx, 1 if outer else 0)
        elif shards > 1:
            blk = ptv.nb // shards
            bound = int(per_probe.reshape(shards, blk).sum(axis=1).max())
        else:
            bound = int(per_probe.sum())
        if bound * shards > MAX_EXPAND:
            return None
        return kernels.bucket(max(bound, 1))

    def close(self):
        _close_node(self.probe)
        _close_node(self.build)


class _SortGroupNode:
    """GROUP BY above an arbitrary device view (join outputs included,
    VERDICT r3 #1): in-kernel lexsort by the key lanes (valid rows first),
    boundary diff -> group leaders, next-leader positions by a reverse
    cummin scan, then every sum/count is a cumsum + two gathers over the
    leader windows — no scatter on the hot path (SURVEY §7 "hash tables
    on TPU": sort-based grouping; reference aggregate.go:355 shuffle).
    min/max ride segment ops over the group-number lane.  Output view:
    group g at slot g of the child-sized bucket, valid = g < n_groups."""

    def __init__(self, child, key_cols, specs, slots, out_map, plan):
        self.child = child
        self.key_cols = key_cols
        self.specs = specs
        self.slots = slots
        self.out_map = out_map
        self.plan = plan

    @staticmethod
    def compile(plan: PhysicalHashAgg, ctx: _Ctx):
        if not plan.group_by:
            return None
        if not all(_gb_key_ok(e) for e in plan.group_by):
            return None
        got = _assemble_agg_specs(plan)
        out_map = _agg_out_map(plan)
        if got is None or out_map is None:
            return None
        specs, slots = got
        child = _compile_node(plan.children[0], ctx)
        if child is None:
            return None
        cut = _KeyGroupNode.cut_of(child, list(plan.group_by))
        if cut is not None:
            return _KeyGroupNode(child, list(plan.group_by), cut, specs,
                                 slots, out_map, plan, mesh=ctx.mesh)
        return _SortGroupNode(child, list(plan.group_by), specs, slots,
                              out_map, plan)

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        live = _live_set(live, len(self.out_map))
        needed = _needed_specs(self.slots, self.out_map, live)
        tv = self.child.prepare(pb, frozenset(
            {e.index for e in self.key_cols}
            | _spec_slots_read(self.specs, needed)))
        if tv is None:
            return None
        jn = _jn()
        nb = tv.nb
        key_idx = []
        decodes = []
        for e in self.key_cols:
            if e.index >= len(tv.meta):
                return None
            decode = tv.meta[e.index][1]
            if e.eval_type is EvalType.STRING and decode is None:
                return None  # string key without device codes
            key_idx.append(e.index)
            decodes.append(decode)
        pt = ParamTable()
        arg_fns, keys, never_null = _view_spec_fns(pb, pt, self.specs,
                                                   needed, tv)
        ip, fp = pb.params(pt)
        pb.key(("sortgroup", tuple(keys), tuple(key_idx),
                tuple(self.slots), tuple(self.out_map), nb,
                len(tv.meta)), live, len(self.out_map))
        spec_kinds = [k for k, _ in self.specs]
        slots = self.slots
        out_map = self.out_map
        schema_cols = self.plan.schema.columns
        nkeys = len(key_idx)

        def emit(args):
            from jax import lax
            j = kernels.jax()
            valid, pairs = tv.emit(args)
            pr = (args[ip], args[fp])
            kvs = [pairs[i] for i in key_idx]
            perm = jn.lexsort(_sort_ops(jn, kvs, (False,) * nkeys, valid))
            valid_s = valid[perm]
            skeys = [(v[perm], m[perm]) for v, m in kvs]
            idx = jn.arange(nb)
            # leader = valid row starting a new key run (invalid rows
            # sort last, so groups of valid rows are contiguous)
            diff = jn.zeros(nb, dtype=bool).at[0].set(True)
            for sv, sn in skeys:
                d = ((sv[1:] != sv[:-1]) & ~(sn[1:] & sn[:-1])) \
                    | (sn[1:] != sn[:-1])
                diff = diff.at[1:].set(diff[1:] | d)
            prev_invalid = jn.concatenate(
                [jn.ones(1, dtype=bool), ~valid_s[:-1]])
            lead = valid_s & (diff | prev_invalid)
            gnum = jn.cumsum(lead.astype(jn.int64))       # 1-based
            ng = gnum[-1]
            sgid = jn.where(valid_s, gnum - 1, nb)        # per sorted pos
            # group end for the leader at i: next leader position - 1
            lp = jn.where(lead, idx, nb)
            nxt = lax.cummin(lp[::-1])[::-1]              # next leader >= i
            nxt_after = jn.concatenate([nxt[1:],
                                        jn.full((1,), nb, dtype=nxt.dtype)])
            end = jn.clip(nxt_after - 1, 0, nb - 1)

            lead_pos = jn.nonzero(lead, size=nb, fill_value=0)[0]

            def seg(x_s):
                # window sum [i, end_i] gathered at the leaders;
                # contributions are pre-masked so the last group's window
                # absorbing the invalid tail adds zero
                c = kernels.prefix_sum(x_s)
                c0 = jn.concatenate([jn.zeros(1, dtype=x_s.dtype), c[:-1]])
                return (c[end] - c0)[lead_pos]

            def seg_mm(av_s, live_s, kind):
                gl = jn.where(live_s, sgid, nb)
                op = j.ops.segment_min if kind == "min" \
                    else j.ops.segment_max
                return op(av_s, gl, num_segments=nb + 1)[:nb]
            presence = seg(valid_s.astype(jn.int64))
            res = _spec_results(
                jn, spec_kinds, arg_fns, pairs, pr, valid,
                gather=lambda x: x[perm], needed=needed,
                seg_sum=seg, seg_mm=seg_mm, presence=presence, n_out=nb,
                never_null=never_null)
            outs = _slot_outputs(jn, res, slots)
            gvalid = jn.arange(nb) < ng
            cols = []
            for i, m in enumerate(out_map):
                if i not in live:
                    cols.append(DEAD)
                elif m[0] == "agg":
                    cols.append(outs[m[1]])
                else:
                    sv, sn = skeys[m[1]]
                    cols.append((sv[lead_pos], sn[lead_pos] | ~gvalid))
            return gvalid, cols
        meta = []
        for oc, m in zip(schema_cols, out_map):
            decode = decodes[m[1]] if m[0] == "gb" else None
            meta.append((oc.ret_type, decode))
        # a group here is a run of valid rows: a key is NULL in one only
        # if it was on a row
        return _TView(emit, nb, meta, "sortgroup", _agg_nonnull(
            self.specs, slots, out_map, never_null,
            lambda j: key_idx[j] in tv.nonnull))

    def close(self):
        _close_node(self.child)


def _origin(node, slot: int):
    """(replica leaf, its slot) that slot ``slot`` of ``node``'s view is
    a plain copy of, or None: followed through the nodes that hand a
    column on as it is."""
    if isinstance(node, _ReplicaLeaf):
        return node, slot
    if isinstance(node, (_SelNode, _OrderNode, _LimitNode)):
        return _origin(node.child, slot)
    if isinstance(node, _ProjNode):
        e = node.exprs[slot]
        return _origin(node.child, e.index) \
            if isinstance(e, ExprColumn) else None
    if isinstance(node, _JoinNode):
        return _origin(*_join_side_slot(node, slot))
    if isinstance(node, _KeyGroupNode):
        m = node.out_map[slot]
        return _origin(node.child, node.key_cols[m[1]].index) \
            if m[0] == "gb" else None
    return None


class _KeyGroupNode:
    """GROUP BY above any device view (a join chain's) on ONE key whose
    values lie in a bounded range, without a sort: group ``g`` is key
    ``lo + g`` (code ``g`` of a string key), one slot more for the NULL
    key, and every sum is a segment reduction over ``key - lo`` — masked
    reductions in row order up to kernels.SEG_UNROLL groups (TPC-H Q5:
    25 nations), scatter-adds beyond (Q10: 150 k customers).  The range
    is the key's column's own, read where the view took the column from
    (:func:`_origin`): the replica's bounds or dictionary.

    **The key cut**: of several GROUP BY columns that are all plain
    columns of one table, one of them its primary key (the handle), the
    key alone decides the group — each row of a join's output holds one
    row of the table, whatever was joined to it — and groups are formed
    on it (counter ``agg_key_cut``).  The other columns are not asked of
    the view at all (column liveness: a join chain gathers none of them
    at its probe's bucket); each group fetches them from the table's own
    lanes by its key, through the table's key -> row table, at the
    group bucket.  A GROUP BY whose columns come from several tables, or
    none of which is the key, is not cut and sorts (_SortGroupNode).

    **Under a mesh** whose shards hold the view's rows (the bucket
    shards: a join chain's probe) each shard reduces its own rows to
    the [groups] tables and the tables merge over the mesh as
    _AggIndexNode's do (``dist.mesh_sum``; min / max by ``mesh_min`` /
    ``mesh_max``; "some row fell in it" is a sum of counts; counter
    ``agg_key_mesh``); a shard that holds no valid row gives each
    merge's identity.  The merged tables lie whole on every device, as
    do the key -> row table and the lanes the carried columns are
    fetched from (a broadcast build leaf's own lanes, by their memo
    keys).

    Output view: group ``g`` at slot ``g`` of the bucket of the range,
    valid where some row fell in it."""

    def __init__(self, child, key_cols, cut: int, specs, slots, out_map,
                 plan, mesh=None):
        self.child = child
        self.mesh = mesh
        self.key_cols = key_cols
        self.cut = cut              # index into key_cols of the key
        self.specs = specs
        self.slots = slots
        self.out_map = out_map
        self.plan = plan

    @staticmethod
    def cut_of(child, key_cols) -> Optional[int]:
        """Which GROUP BY column the groups can be formed on, or None."""
        from .tpu_executors import _slot_id
        origins = [_origin(child, e.index) for e in key_cols]
        if any(o is None for o in origins):
            return None
        key = key_cols[0]
        if len(key_cols) == 1:
            ok = key.eval_type in (EvalType.INT, EvalType.STRING)
            return 0 if ok else None
        if any(o[0] is not origins[0][0] for o in origins):
            return None
        pk = origins[0][0].plan.scan.table_info.get_pk_handle_col()
        for i, (leaf, at) in enumerate(origins):
            if _slot_id(leaf.ex, at) in ("handle",
                                         pk.id if pk is not None else None):
                return i
        return None

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        from .tpu_executors import _rep_string_dict, _slot_id
        live = _live_set(live, len(self.out_map))
        needed = _needed_specs(self.slots, self.out_map, live)
        key = self.key_cols[self.cut]
        tv = self.child.prepare(pb, frozenset(
            {key.index} | _spec_slots_read(self.specs, needed)))
        if tv is None:
            return None
        jn = _jn()
        nb = tv.nb
        leaf, at = _origin(self.child, key.index)
        rep, chk = leaf.replica(), leaf.chunk()
        if rep is None or chk is None:
            return None
        nbl = leaf.nb()

        def column(idx):
            """(values or codes, nulls, decode, dtype tag) of the leaf's
            column ``idx`` on the host."""
            sid = _slot_id(leaf.ex, idx)
            col = chk.columns[idx]
            v = col.values()
            if v.dtype == object or v.dtype.kind == "U":
                codes, _card, _, uniques = _rep_string_dict(rep, sid, chk,
                                                            idx)
                return sid, codes, col.null_mask(), uniques, "s"
            return sid, v, col.null_mask(), None, \
                "f" if v.dtype == np.float64 else "i"
        ksid, kv, km, kdecode, kdt = column(at)
        if kdt == "s":
            lo, rng = 0, max(len(kdecode), 1)
        elif kdt == "i":
            b = _col_bounds(rep, ksid, kv, km)
            lo, hi = b if b is not None else (0, 0)
            rng = hi - lo + 1
            if rng > MAX_DENSE_RANGE:
                return None
        else:
            return None
        # one slot past the range holds the NULL key's group
        ngb = kernels.bucket(rng + 1)
        dense = ngb <= kernels.SEG_UNROLL
        # under a mesh whose shards hold the view's rows each shard
        # reduces its own; what the groups read afterwards lies whole
        from ..parallel import dist
        mesh = self.mesh if dist.shardable(nb, self.mesh) else None
        lwhole = _layouts(self.mesh)[1]
        if mesh is not None:
            kernels.stats_add("agg_key_mesh", 1)
        # the columns the key determines, fetched for each group from
        # the table's own lanes (the memo keys of its scans)
        carried = {}
        it = tbl_len = None
        for j, e in enumerate(self.key_cols):
            if j == self.cut:
                continue
            if it is None:
                got = _rep_pos_table(rep, ksid, kv, km)
                if got is None:
                    return None
                tbl = got[2]
                it = pb.lane(rep, ("postable_dev", ksid), lambda: tbl,
                             lwhole)
                tbl_len = int(tbl.shape[0])
            sid, v, m, decode, dt = column(_origin(self.child, e.index)[1])
            carried[j] = (
                pb.add(_leaf_lane(rep, "devcodes" if dt == "s" else "devv",
                                  sid, nbl, v, layout=lwhole), lwhole),
                pb.add(_leaf_lane(rep, "devn", sid, nbl, m, True,
                                  layout=lwhole), lwhole),
                decode, dt, _column_never_null(rep, sid, m))
        if carried:
            kernels.stats_add("agg_key_cut", 1)
        if dense:
            kernels.stats_add("agg_dense", 1)
        pt = ParamTable()
        pt.add_int(lo)
        pt.add_int(rng)
        arg_fns, keys, never_null = _view_spec_fns(pb, pt, self.specs,
                                                   needed, tv)
        ip, fp = pb.params(pt)
        pb.key(("keygroup", tuple(keys), key.index, kdt, self.cut,
                tuple((j, c[3]) for j, c in sorted(carried.items())),
                tuple(self.slots), tuple(self.out_map), nb, ngb, nbl,
                tbl_len, len(tv.meta))
               + dist.layout_tag(_layouts(mesh)[0]),
               live, len(self.out_map))
        spec_kinds = [k for k, _ in self.specs]
        slots, out_map, cut = self.slots, self.out_map, self.cut
        kslot = key.index
        need = sorted(needed)
        #: the view's slots the reduction reads (all live in ``tv``)
        read = sorted({kslot} | _spec_slots_read(self.specs, needed))
        merge_sum, merge_mm = _mesh_merges(mesh)

        def reduce(valid, cols, pr):
            """(the key's first value, its range, rows per group,
            [(value, null)] per needed spec), the last two each [ngb],
            of the rows at hand: the view's, or a shard's with the
            tables merged over the mesh.  (The two parameters are read
            once and handed on, so that the one-device program is the
            one it was.)"""
            pairs = _spread(len(tv.meta), read, cols)
            lo_p, rng_p = pr[0][0], pr[0][1]
            kval, knull = pairs[kslot]
            gid = jn.where(knull, rng_p,
                           jn.clip(kval - lo_p, 0, rng_p - 1)
                           ).astype(jn.int32)
            seg = kernels._SegReduce(kernels.jax(), jn, gid, valid, ngb,
                                     unroll=dense)
            presence = merge_sum(seg.sum(valid.astype(jn.int64), valid))
            res = _spec_results(
                jn, spec_kinds, arg_fns, pairs, pr, valid,
                seg_sum=lambda x: merge_sum(seg.sum(x, valid)),
                seg_mm=lambda av, live_s, kind: merge_mm[kind](seg.minmax(
                    av, live_s, kind == "min")),
                presence=presence, n_out=ngb, needed=needed,
                never_null=never_null)
            return lo_p, rng_p, presence, [res[k] for k in need]
        if mesh is not None:
            # merged states are whole by construction (psum; min / max
            # gathered and reduced alike on every shard, beyond the
            # static checker)
            ROWS, WHOLE = dist.specs()
            reduce = dist.shard_map_unchecked(
                reduce, mesh=mesh,
                in_specs=(ROWS, [(ROWS, ROWS)] * len(read), (WHOLE, WHOLE)),
                out_specs=(WHOLE, WHOLE, WHOLE,
                           [(WHOLE, WHOLE)] * len(need)))

        def emit(args):
            valid, pairs = tv.emit(args)
            pr = (args[ip], args[fp])
            lo_p, rng_p, presence, res = reduce(
                valid, [pairs[i] for i in read], pr)
            outs = _slot_outputs(
                jn, _spread(len(spec_kinds), need, res), slots)
            g = jn.arange(ngb, dtype=jn.int64)
            gnull = g >= rng_p
            if carried:
                row = args[it][jn.clip(g, 0, tbl_len - 1)]
                miss = gnull | (row < 0)
                row = jn.clip(row, 0, nbl - 1)
            cols = []
            for i, m in enumerate(out_map):
                if i not in live:
                    cols.append(DEAD)
                elif m[0] == "agg":
                    cols.append(outs[m[1]])
                elif m[1] == cut:
                    cols.append((g if kdt == "s" else g + lo_p, gnull))
                else:
                    iv, im = carried[m[1]][:2]
                    cols.append((args[iv][row], args[im][row] | miss))
            return presence > 0, cols
        meta = []
        for oc, m in zip(self.plan.schema.columns, out_map):
            decode = None
            if m[0] == "gb":
                decode = kdecode if m[1] == cut else carried[m[1]][2]
            meta.append((oc.ret_type, decode))
        # the slot past the range is valid only if a NULL key arrived;
        # a carried column is read of the table's own row of the key
        key_nonnull = kslot in tv.nonnull
        return _TView(emit, ngb, meta, "keygroup", _agg_nonnull(
            self.specs, slots, out_map, never_null,
            lambda j: key_nonnull and (j == cut or carried[j][4])))

    def close(self):
        _close_node(self.child)


class _ScalarAggNode:
    """Global (no GROUP BY) aggregation over any device view — masked
    reductions, one output row at slot 0 of a minimal bucket.  Keeps
    scalar aggregates above joins device-resident (reference
    aggregate.go:482 always-parallel Next, degenerate single group),
    including FINAL partial-state merges from agg pushdown."""

    def __init__(self, child, specs, slots, plan):
        self.child = child
        self.specs = specs
        self.slots = slots
        self.plan = plan

    @staticmethod
    def compile(plan: PhysicalHashAgg, ctx: _Ctx):
        if plan.group_by:
            return None
        got = _assemble_agg_specs(plan)
        if got is None:
            return None
        specs, slots = got
        out_map = _agg_out_map(plan)
        if out_map is None or any(m[0] != "agg" for m in out_map):
            return None
        child = _compile_node(plan.children[0], ctx)
        if child is None:
            return None
        node = _ScalarAggNode(child, specs, slots, plan)
        node.out_map = out_map
        return node

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        live = _live_set(live, len(self.out_map))
        needed = _needed_specs(self.slots, self.out_map, live)
        tv = self.child.prepare(
            pb, frozenset(_spec_slots_read(self.specs, needed)))
        if tv is None:
            return None
        jn = _jn()
        ob = 16  # minimal bucket; the one result row sits at slot 0
        pt = ParamTable()
        arg_fns, keys, never_null = _view_spec_fns(pb, pt, self.specs,
                                                   needed, tv)
        ip, fp = pb.params(pt)
        pb.key(("scalaragg", tuple(keys), tuple(self.slots),
                tuple(self.out_map), tv.nb, len(tv.meta)),
               live, len(self.out_map))
        spec_kinds = [k for k, _ in self.specs]
        slots = self.slots
        out_map = self.out_map
        schema_cols = self.plan.schema.columns

        def at0(x):
            return jn.zeros(ob, dtype=x.dtype).at[0].set(x)

        def emit(args):
            valid, pairs = tv.emit(args)
            pr = (args[ip], args[fp])
            # the shared per-spec loop with degenerate reducers: one
            # global segment, result at slot 0 (semantics live ONCE in
            # _spec_results)
            res = _spec_results(
                jn, spec_kinds, arg_fns, pairs, pr, valid,
                seg_sum=lambda x_s: at0(jn.sum(x_s)),
                seg_mm=lambda av_s, live_s, kind: at0(
                    (jn.min if kind == "min" else jn.max)(av_s)),
                presence=at0(jn.sum(valid.astype(jn.int64))), n_out=ob,
                needed=needed, never_null=never_null)
            outs = _slot_outputs(jn, res, slots)
            gvalid = jn.arange(ob) == 0  # exactly one result row
            return gvalid, _only([outs[m[1]] for m in out_map], live)
        meta = [(oc.ret_type, None) for oc in schema_cols]
        # the one row is valid over an empty input too, where a sum is
        # NULL whatever its argument: only the counts are proved
        return _TView(emit, ob, meta, "scalaragg", _agg_nonnull(
            self.specs, slots, out_map, frozenset(), lambda j: False))

    def close(self):
        _close_node(self.child)


def _leafish(node) -> Optional[_ReplicaLeaf]:
    """The underlying replica leaf of a leaf/selection chain (selection
    preserves the schema, so column offsets map straight through)."""
    if isinstance(node, _ReplicaLeaf):
        return node
    if isinstance(node, _SelNode):
        return _leafish(node.child)
    return None


class _Slot:
    """A column of a view by its slot alone: what the build-key walk
    below reads of an expression column."""
    __slots__ = ("index",)

    def __init__(self, index: int):
        self.index = index


def _join_side_slot(node: "_JoinNode", idx: int):
    """Slot ``idx`` of a join's output view as (node of the side it is a
    column of, its slot there).  A semi join's view is its probe's."""
    if node.tp in ("semi", "anti"):
        return node.probe, idx
    sides = node.plan.children
    probe_side = 0 if node.probe_is_left else 1
    nleft = len(sides[0].schema.columns)
    side, at = (0, idx) if idx < nleft else (1, idx - nleft)
    return (node.probe if side == probe_side else node.build), at


def _probe_shaped(node) -> bool:
    """A join whose output view has its probe side's rows, one for one:
    the single-key unique join and the semi join, on one device or
    broadcast under a mesh (a join that partitioned lays its output out
    by key: known once it has prepared, so a tree compiles on the
    promise and a parent's prepare bails where it was not kept).  The
    probe side's key -> row table then still finds a row of the view,
    and the view's own validity says whether the row survived the
    join."""
    return isinstance(node, _JoinNode) and not node.mult \
        and node.nk == 1 and not node.partitioned


def _view_build_key(node: "_JoinNode", build_key):
    """The build key of a parent join as a column of ``node``'s probe
    side, or None: ``node`` is not probe-shaped or the key is its build
    side's."""
    if not _probe_shaped(node):
        return None
    side, at = _join_side_slot(node, build_key.index)
    return _Slot(at) if side is node.probe else None


def _has_build_key_info(node, build_key) -> bool:
    if isinstance(node, _AggIndexNode):
        return node.key_slot() == build_key.index
    if isinstance(node, _JoinNode):
        # a view build: the parent probes the probe side's table and
        # reads this join's validity (the planner proved the key unique
        # among the join's rows)
        key = _view_build_key(node, build_key)
        return key is not None and _has_build_key_info(node.probe, key)
    if isinstance(node, (_ReplicaLeaf,)):
        return True  # bounds checked at prepare time
    if isinstance(node, (_SelNode,)):
        return _has_build_key_info(node.child, build_key)
    if isinstance(node, _ProjNode):
        # identity output: row space unchanged, key lives at the child
        # slot the projection reads (a subquery's final projection)
        e = node.exprs[build_key.index]
        return isinstance(e, ExprColumn) \
            and _has_build_key_info(node.child, e)
    return False


def _build_key_table(node, build_key):
    """(replica, memo key of its device copy, lo, hi, host pos-table)
    mapping build-key value -> build view row, or None: the table of the
    replica whose rows the view's rows are (an aggregate's groups, a
    leaf's rows, through selections, projections and the probe side of
    probe-shaped joins)."""
    from .tpu_executors import _slot_id
    if isinstance(node, _AggIndexNode):
        got = node.build_key_info()
        if got is None:
            return None
        lo, hi, tbl = got
        sids = (_slot_id(node.leaf.ex, node.key_cols[0].index),)
        return node.leaf.replica(), ("gi_postable_dev", sids), lo, hi, tbl
    if isinstance(node, _SelNode):
        return _build_key_table(node.child, build_key)
    if isinstance(node, _JoinNode):
        key = _view_build_key(node, build_key)
        if key is None:
            return None
        return _build_key_table(node.probe, key)
    if isinstance(node, _ProjNode):
        e = node.exprs[build_key.index]
        if not isinstance(e, ExprColumn):
            return None
        return _build_key_table(node.child, e)
    if isinstance(node, _ReplicaLeaf):
        rep = node.replica()
        if rep is None:
            return None
        sid = _slot_id(node.ex, build_key.index)
        if sid == "handle":
            kv, km = rep.handles, np.zeros(rep.n_rows, dtype=bool)
        else:
            kv, km = rep.columns[sid]
        got = _rep_pos_table(rep, sid, kv, km)
        if got is None:
            return None
        return (rep, ("postable_dev", sid)) + got
    return None


def _prepare_build_key_info(node, build_key, pb: _PipeBuilder, mesh=None):
    """(lo, hi, input index of the device pos-table, table length) mapping
    build-key value -> build view row.  Under a mesh the table lies whole
    on every device: each shard probes it with its own rows."""
    got = _build_key_table(node, build_key)
    if got is None:
        return None
    rep, key, lo, hi, tbl = got
    it = pb.lane(rep, key, lambda: tbl, _layouts(mesh)[1])
    return lo, hi, it, int(tbl.shape[0])


def _fold_validity(tbl, bvalid, nbb: int):
    """The key -> row table with -1 wherever the build row it names is
    not valid in the build's view (``bvalid``, ``nbb`` rows): read at a
    probe key, a row at or above 0 is a match, and ``bvalid`` is read
    once a key (traced each dispatch: it carries the statement's filters
    and parameters) in place of once a probe row."""
    jn = _jn()
    return jn.where((tbl >= 0) & bvalid[jn.clip(tbl, 0, nbb - 1)], tbl, -1)


def _probe_rows(node, key):
    """(replica leaf, its slot) where ``node``'s view holds the leaf's
    rows in place, one for one, and ``key`` is the leaf's own column
    there: through selections, projections that read it as it is and
    the probe side of probe-shaped joins.  None otherwise."""
    if isinstance(node, _ReplicaLeaf):
        return (node, key.index) if node.order is None else None
    if isinstance(node, _SelNode):
        return _probe_rows(node.child, key)
    if isinstance(node, _ProjNode):
        e = node.exprs[key.index]
        return _probe_rows(node.child, e) if isinstance(e, ExprColumn) \
            else None
    if isinstance(node, _JoinNode) and _probe_shaped(node):
        side, at = _join_side_slot(node, key.index)
        return _probe_rows(node.probe, _Slot(at)) if side is node.probe \
            else None
    return None


def _aligned_rows(rep, sid, table) -> bool:
    """The replica's key column ``sid`` (or its handle) holds no NULL and
    the build's key -> row table (``_build_key_table``) read at row r's
    key is r, for every row: observed once a version of both replicas."""
    brep, bkey, lo, hi, tbl = table

    def check():
        if sid == "handle":
            kv = rep.handles
        else:
            kv, km = rep.columns[sid]
            if km.any():
                return False
        if not bool(((kv >= lo) & (kv <= hi)).all()):
            return False
        return bool((tbl[kv - lo] == np.arange(len(kv))).all())
    return rep.memo(("aligned", sid, brep.table_id, brep.data_version)
                    + bkey, check)


def _composite_spec(cols):
    """Multi-key composite lane: per-key (lo, hi, stride) such that
    comp = sum((k_i - lo_i) * stride_i) is a bijection over the cross
    range — the device-friendly replacement for a multi-column hash key
    (reference join key tuples, util/mvmap multi-part keys).  None when
    the combined dense range exceeds MAX_DENSE_RANGE."""
    los, his = [], []
    total = 1
    for kv, km in cols:
        nn = kv[~km]
        if len(nn):
            lo, hi = int(nn.min()), int(nn.max())
        else:
            lo = hi = 0
        span = hi - lo + 1
        if span <= 0 or total > MAX_DENSE_RANGE // span:
            return None
        total *= span
        los.append(lo)
        his.append(hi)
    strides = []
    st = 1
    for lo, hi in reversed(list(zip(los, his))):
        strides.append(st)
        st *= hi - lo + 1
    strides.reverse()
    comp = np.zeros(len(cols[0][0]), dtype=np.int64)
    null_any = np.zeros(len(cols[0][0]), dtype=bool)
    for (kv, km), lo, hi, stride in zip(cols, los, his, strides):
        comp += (np.clip(kv, lo, hi) - lo) * stride
        null_any |= km
    return los, his, strides, comp, null_any, total


def _agg_output(node, slot: int) -> bool:
    """Whether slot ``slot`` of ``node``'s view is computed from a
    GROUP BY's aggregate outputs (through projections, selections and
    the sides of joins), not from a table's own columns."""
    if isinstance(node, (_AggIndexNode, _SortGroupNode, _KeyGroupNode)):
        return node.out_map[slot][0] == "agg"
    if isinstance(node, _SelNode):
        return _agg_output(node.child, slot)
    if isinstance(node, _ProjNode):
        cols = node.exprs[slot].collect_columns()
        return bool(cols) and any(_agg_output(node.child, c.index)
                                  for c in cols)
    if isinstance(node, _JoinNode):
        return _agg_output(*_join_side_slot(node, slot))
    return False


def _view_conds(node, conds) -> bool:
    """Whether ``conds``, filtering the output of the join ``node``,
    read an aggregate output of its build side's view: the comparison
    of a decorrelated scalar subquery (counter
    ``pipe_join_view_conds``)."""
    if not isinstance(node, _JoinNode) or node.tp in ("semi", "anti"):
        return False
    for i in _slots_read(conds):
        side, at = _join_side_slot(node, i)
        if side is node.build and _agg_output(side, at):
            return True
    return False


def _source_column(node, slot: int):
    """(replica leaf, its slot) whose column's values slot ``slot`` of
    ``node``'s view holds, in whatever order: through selections,
    projections that read a column as it is, either side of a join and
    a sorted GROUP BY's keys.  None otherwise."""
    if isinstance(node, _ReplicaLeaf):
        return node, slot
    if isinstance(node, _SelNode):
        return _source_column(node.child, slot)
    if isinstance(node, _ProjNode):
        e = node.exprs[slot]
        return _source_column(node.child, e.index) \
            if isinstance(e, ExprColumn) else None
    if isinstance(node, _JoinNode):
        return _source_column(*_join_side_slot(node, slot))
    if isinstance(node, _AggIndexNode):
        kind, i = node.out_map[slot]
        key = node.key_cols[i] if kind == "gb" else None
        return _source_column(node.chain, key.index) \
            if isinstance(key, ExprColumn) else None
    return None


def _agg_spec(node, slot: int):
    """(sorted GROUP BY node, its (kind, argument) spec) that computes
    slot ``slot`` of ``node``'s view, through selections, column
    projections and either side of a join; None otherwise."""
    if isinstance(node, _SelNode):
        return _agg_spec(node.child, slot)
    if isinstance(node, _ProjNode):
        e = node.exprs[slot]
        return _agg_spec(node.child, e.index) \
            if isinstance(e, ExprColumn) else None
    if isinstance(node, _JoinNode):
        return _agg_spec(*_join_side_slot(node, slot))
    if isinstance(node, _AggIndexNode):
        kind, i = node.out_map[slot]
        if kind == "agg" and node.slots[i][0] == "one":
            return node, node.specs[node.slots[i][1]]
    return None


def _whole_bound(leaf: _ReplicaLeaf, slot: int) -> Optional[float]:
    """The largest |value| of the leaf's column where every value is a
    whole number (NULLs aside), else None: observed once a replica
    version."""
    from .tpu_executors import _slot_id
    rep = leaf.replica()
    sid = _slot_id(leaf.ex, slot)
    if sid == "handle":
        return float(np.abs(rep.handles).max(initial=0))
    vals, nulls = rep.columns[sid]

    def check():
        v = vals[~nulls]
        if v.dtype.kind == "f":
            if not np.isfinite(v).all() or (v != np.round(v)).any():
                return None
        elif v.dtype.kind not in "iu":
            return None
        return float(np.abs(v).max(initial=0))
    return rep.memo(("whole_bound", sid), check)


def _scaled_quotient(e):
    """(k, s, c) where ``e`` is ``k * (s / c)``, ``(s / c) * k`` or
    ``s / c`` over two columns (k = 1), else None."""
    k = 1.0
    if getattr(e, "name", "") == "*" and len(e.children()) == 2:
        a, b = e.children()
        if isinstance(b, Constant):
            a, b = b, a
        if not (isinstance(a, Constant) and isinstance(a.value, (int, float))
                and not isinstance(a.value, bool)):
            return None
        k, e = float(a.value), b
    if getattr(e, "name", "") != "/" or len(e.children()) != 2:
        return None
    s, c = e.children()
    if isinstance(s, ExprColumn) and isinstance(c, ExprColumn) \
            and exactcmp.scalable(k):
        return k, s, c
    return None


def _exact_ratio(node, cond):
    """(op, x slot, s slot, c slot, k) where ``cond``, over ``node``'s
    view, is ``x op k * (s / c)`` (either way round) with ``x`` a column
    of whole numbers below 2^31, ``s`` a sorted GROUP BY's SUM of such a
    column whose every sum stays below 2^40, and ``c`` its COUNT: a
    comparison :mod:`~tinysql_tpu.ops.exactcmp` decides as IEEE doubles
    do.  None otherwise (the comparison of the doubles as they are)."""
    name = getattr(cond, "name", "")
    if name not in exactcmp.FLIPPED or len(cond.children()) != 2:
        return None
    a, b = cond.children()
    for x, e, op in ((a, b, name), (b, a, exactcmp.FLIPPED[name])):
        got = _scaled_quotient(e)
        if not isinstance(x, ExprColumn) or got is None:
            continue
        k, sc, cc = got
        src = _source_column(node, x.index)
        bound = _whole_bound(*src) if src is not None else None
        if bound is None or bound >= 1 << 31:
            return None
        s_of, c_of = _agg_spec(node, sc.index), _agg_spec(node, cc.index)
        if s_of is None or c_of is None or s_of[0] is not c_of[0] \
                or s_of[1][0] != "sum" \
                or c_of[1][0] not in ("count", "count_star"):
            return None
        agg, (_kind, arg) = s_of
        src = _source_column(agg.chain, arg.index) \
            if isinstance(arg, ExprColumn) else None
        bound = _whole_bound(*src) if src is not None else None
        if bound is None \
                or bound * max(agg.leaf.replica().n_rows, 1) >= 2.0 ** 40:
            return None
        return op, x.index, sc.index, cc.index, k
    return None


def _exact_fn(op: str, xs: int, ss: int, cs: int, kslots: tuple):
    """Device closure of an :func:`_exact_ratio` comparison: the lanes
    as the whole numbers they hold, decided in 64-bit integers."""
    def whole(jn, v, bits):
        if v.dtype != jn.int64:
            v = jn.round(v).astype(jn.int64)
        return jn.clip(v, 1 - (1 << bits), (1 << bits) - 1)

    def f(pairs, pr):
        jn = _jn()
        (xv, xn), (sv, sn), (cv, cn) = pairs[xs], pairs[ss], pairs[cs]
        pi = pr[0]
        v, null = exactcmp.compare(
            jn, op, whole(jn, xv, 31), whole(jn, sv, exactcmp.S_BITS),
            jn.clip(cv.astype(jn.int64), 0, (1 << 31) - 1),
            *(pi[k] for k in kslots))
        return v.astype(jn.int64), null | xn | sn | cn
    return f


class _SelNode:
    """Filter over a device view: conditions AND into the validity mask.
    Directly above a join it holds the join's residual conditions, and a
    condition there may compare a probe lane with the build view's
    aggregate output (``_view_conds``)."""

    def __init__(self, child, conds, plan):
        self.child = child
        self.conds = conds
        self.plan = plan

    @staticmethod
    def compile(plan: PhysicalSelection, ctx: _Ctx):
        if not all(is_jittable(c) for c in plan.conditions):
            return None
        child = _compile_node(plan.children[0], ctx)
        if child is None:
            return None
        return _SelNode(child, plan.conditions, plan)

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        live = _live_set(live, len(self.plan.schema.columns))
        tv = self.child.prepare(
            pb, frozenset(live | _slots_read(self.conds)))
        if tv is None:
            return None
        if _view_conds(self.child, self.conds):
            kernels.stats_add("pipe_join_view_conds", 1)
        pt = ParamTable()
        fns, keys = [], []
        for c in self.conds:
            got = _exact_ratio(self.child, c)
            if got is None:
                fns.append(compile_expr_params(c, pt))
                keys.append(stable_shape_key(c))
                continue
            op, xs, ss, cs, k = got
            fns.append(_exact_fn(op, xs, ss, cs, tuple(
                pt.add_int(v) for v in exactcmp.split_double(k))))
            keys.append(f"exact:{op}@{xs},{ss},{cs}")
        keys = tuple(keys)
        ip, fp = pb.params(pt)
        pb.key(("sel", keys, tv.nb, len(tv.meta)), live, len(tv.meta))

        def emit(args):
            valid, pairs = tv.emit(args)
            pr = (args[ip], args[fp])
            m = valid
            for f in fns:
                v, null = f(pairs, pr)
                m = m & (v != 0) & ~null
            return m, _only(pairs, live)
        return _TView(emit, tv.nb, tv.meta, "sel", tv.nonnull)

    def close(self):
        _close_node(self.child)


class _ProjNode:
    """Projection over a device view; string columns pass through as
    bare column references (codes + decode)."""

    def __init__(self, child, exprs, plan):
        self.child = child
        self.exprs = exprs
        self.plan = plan

    @staticmethod
    def compile(plan: PhysicalProjection, ctx: _Ctx):
        for e in plan.exprs:
            if is_jittable(e):
                continue
            if isinstance(e, ExprColumn) and e.eval_type is EvalType.STRING:
                continue
            return None
        child = _compile_node(plan.children[0], ctx)
        if child is None:
            return None
        return _ProjNode(child, plan.exprs, plan)

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        live = _live_set(live, len(self.exprs))
        tv = self.child.prepare(pb, frozenset(_slots_read(
            e for i, e in enumerate(self.exprs) if i in live)))
        if tv is None:
            return None
        pt = ParamTable()
        fns = []
        keys = []
        meta = []
        for e, oc in zip(self.exprs, self.plan.schema.columns):
            if isinstance(e, ExprColumn):
                fns.append(("col", e.index))
                keys.append(f"@{e.index}")
                meta.append((oc.ret_type, tv.meta[e.index][1]))
            else:
                fns.append(("fn", compile_expr_params(e, pt)))
                keys.append(stable_shape_key(e))
                meta.append((oc.ret_type, None))
        ip, fp = pb.params(pt)
        pb.key(("proj", tuple(keys), tv.nb, len(tv.meta)),
               live, len(self.exprs))

        def emit(args):
            valid, pairs = tv.emit(args)
            pr = (args[ip], args[fp])
            outs = []
            for i, (kind, f) in enumerate(fns):
                if i not in live:
                    outs.append(DEAD)
                elif kind == "col":
                    outs.append(pairs[f])
                else:
                    outs.append(f(pairs, pr))
            return valid, outs
        return _TView(emit, tv.nb, meta, "proj", (
            j for j, e in enumerate(self.exprs)
            if _never_null(e, tv.nonnull.__contains__)))

    def close(self):
        _close_node(self.child)


def _sort_ops(jn, keys, descs, valid):
    """lexsort operand list: requested keys (NULL first asc / last desc),
    invalid rows last.  keys = [(vals, null)] — ints/codes/floats."""
    ops = []
    for i in range(len(keys) - 1, -1, -1):
        v, m = keys[i]
        desc = descs[i]
        vv = jn.where(m, 0, v)
        if desc:
            # ~v is the overflow-free order-reversing bijection on int64
            vv = ~vv if vv.dtype == jn.int64 else -vv
            rank = jn.where(m, 1, 0).astype(jn.int8)  # NULL last
        else:
            rank = jn.where(m, 0, 1).astype(jn.int8)  # NULL first
        ops.append(vv)
        ops.append(rank)
    ops.append(jn.where(valid, 0, 1).astype(jn.int8))  # invalid last
    return ops


class _OrderNode:
    """TopN (static offset/count slice after lexsort — valid rows sort
    first, so perm[offset : offset+count_bucket] IS the answer) or full
    Sort over a view.

    Under `tidb_mesh_parallel` a TopN runs distributed (the mesh analogue
    of the reference's per-region TopN pushdown + root merge,
    /root/reference/store/mockstore/mocktikv/topn.go:1-139 +
    planner/core/task.go:392-452): each shard lexsorts its partition and
    keeps its top (offset+count) candidates, an all_gather moves the
    k x n_shards survivors over ICI, and a replicated merge sort slices
    the final window.  A global-row-index tiebreak makes the result
    bit-identical to the single-device stable sort."""

    def __init__(self, child, by, offset, count, plan, mesh=None):
        self.child = child
        self.by = by
        self.off = offset        # None = full sort
        self.count = count
        self.plan = plan
        self.mesh = mesh

    @staticmethod
    def compile(plan, ctx: _Ctx):
        by = plan.by
        for e, _ in by:
            if is_jittable(e):
                continue
            if isinstance(e, ExprColumn) and e.eval_type is EvalType.STRING:
                continue
            return None
        child = _compile_node(plan.children[0], ctx)
        if child is None:
            return None
        off = count = None
        if isinstance(plan, PhysicalTopN):
            off, count = plan.offset, plan.count
        return _OrderNode(child, by, off, count, plan, mesh=ctx.mesh)

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        """Reads the sort keys and carries only the live columns through
        its window (under a mesh: over the all-gather)."""
        live = _live_set(live, len(self.plan.schema.columns))
        tv = self.child.prepare(pb, frozenset(
            live | _slots_read(e for e, _ in self.by)))
        if tv is None:
            return None
        jn = _jn()
        pt = ParamTable()
        fns = []
        keys = []
        for e, desc in self.by:
            if isinstance(e, ExprColumn):
                fns.append(("col", e.index))
                keys.append(f"@{e.index}:{desc}")
            else:
                fns.append(("fn", compile_expr_params(e, pt)))
                keys.append(f"{stable_shape_key(e)}:{desc}")
        descs = tuple(d for _, d in self.by)
        if self.off is None:
            off, kb = 0, tv.nb
        else:
            off = min(self.off, tv.nb)
            kb = min(kernels.bucket(max(self.count, 1)) + off, tv.nb)
        count = self.count
        ip, fp = pb.params(pt)

        from ..parallel import dist
        mesh = self.mesh if (self.off is not None
                             and dist.shardable(tv.nb, mesh=self.mesh)
                             ) else None
        if mesh is not None:
            return self._prepare_mesh(pb, tv, fns, tuple(keys), descs, off,
                                      kb, count, ip, fp, mesh, live)
        pb.key(("order", tuple(keys), off, kb, count, tv.nb,
                len(tv.meta)), live, len(tv.meta))

        def emit(args):
            valid, pairs = tv.emit(args)
            pr = (args[ip], args[fp])
            kvs = []
            for kind, f in fns:
                if kind == "col":
                    kvs.append(pairs[f])
                else:
                    kvs.append(f(pairs, pr))
            take = kernels.lex_head(
                _sort_ops(jn, kvs, descs, valid), kb)[off:]
            out_valid = valid[take]
            if count is not None:
                # valid rows sort first, so the taken valid rows are a
                # prefix; cap it at `count`
                out_valid = out_valid & (jn.arange(kb - off) < count)
            outs = [(p[0][take], p[1][take]) if i in live else DEAD
                    for i, p in enumerate(pairs)]
            return out_valid, outs
        return _TView(emit, kb - off, tv.meta, "order", tv.nonnull)

    def _prepare_mesh(self, pb, tv, fns, key_ids, descs, off, kb, count,
                      ip, fp, mesh, live):
        """Distributed TopN: per-shard top-(off+count) + all_gather merge.
        Column sort keys alias the payload lanes, so only computed ('fn')
        keys travel as extra lanes — the merge re-reads column keys from
        the gathered payload instead of gathering them twice.  The
        payload is the live columns and the column sort keys."""
        jn = _jn()
        from jax import lax
        n = int(mesh.devices.size)
        per = tv.nb // n
        kc = min(kb, per)  # per-shard candidate count
        pb.key(("order_mesh", key_ids, off, kb,
                count, tv.nb, len(tv.meta), n, kc), live, len(tv.meta))
        out_slots = sorted(live)
        carried = sorted(live | {f for kind, f in fns if kind == "col"})
        out_at = [carried.index(i) for i in out_slots]

        def pick_kvs(fn_kvs, pairs):
            # ``pairs``: the carried columns
            out = []
            it = iter(fn_kvs)
            for kind, f in fns:
                out.append(pairs[carried.index(f)] if kind == "col"
                           else next(it))
            return out

        def kernel(fn_kvs, valid, pairs):
            # per-shard [per] lanes; global row index = the stable-sort
            # tiebreak that reproduces the single-device order exactly
            si = lax.axis_index("shard").astype(jn.int64)
            gidx = si * per + jn.arange(per, dtype=jn.int64)
            kvs = pick_kvs(fn_kvs, pairs)
            take = kernels.lex_head(
                [gidx] + _sort_ops(jn, kvs, descs, valid), kc)
            lanes = ([(kv[0][take], kv[1][take]) for kv in fn_kvs]
                     + [(v[take], m[take]) for v, m in pairs])
            g_valid = dist.mesh_gather(valid[take], tiled=True)
            g_gidx = dist.mesh_gather(gidx[take], tiled=True)
            g_lanes = [(dist.mesh_gather(v, tiled=True),
                        dist.mesh_gather(m, tiled=True))
                       for v, m in lanes]
            g_fn_kvs = g_lanes[:len(fn_kvs)]
            g_pairs = g_lanes[len(fn_kvs):]
            g_kvs = pick_kvs(g_fn_kvs, g_pairs)
            perm2 = jn.lexsort([g_gidx]
                               + _sort_ops(jn, g_kvs, descs, g_valid))
            take2 = perm2[off:kb]
            out_valid = g_valid[take2]
            if count is not None:
                out_valid = out_valid & (jn.arange(kb - off) < count)
            outs = [(v[take2], m[take2])
                    for v, m in (g_pairs[i] for i in out_at)]
            return out_valid, outs

        from ..parallel import dist
        ROWS, WHOLE = dist.specs()

        def emit(args):
            valid, pairs = tv.emit(args)
            pr = (args[ip], args[fp])
            fn_kvs = [f(pairs, pr) for kind, f in fns if kind == "fn"]
            sharded = dist.shard_map_unchecked(
                kernel, mesh=mesh,
                in_specs=([(ROWS, ROWS)] * len(fn_kvs), ROWS,
                          [(ROWS, ROWS)] * len(carried)),
                out_specs=(WHOLE, [(WHOLE, WHOLE)] * len(out_slots)))
            out_valid, outs = sharded(fn_kvs, valid,
                                      [pairs[i] for i in carried])
            return out_valid, _spread(len(pairs), out_slots, outs)
        return _TView(emit, kb - off, tv.meta, "order_mesh", tv.nonnull)

    def close(self):
        _close_node(self.child)


class _LimitNode:
    def __init__(self, child, plan):
        self.child = child
        self.plan = plan

    @staticmethod
    def compile(plan: PhysicalLimit, ctx: _Ctx):
        child = _compile_node(plan.children[0], ctx)
        if child is None:
            return None
        return _LimitNode(child, plan)

    def prepare(self, pb: _PipeBuilder, live=None) -> Optional[_TView]:
        tv = self.child.prepare(pb, live)
        if tv is None:
            return None
        jn = _jn()
        pt = ParamTable()
        pt.add_int(self.plan.offset)
        pt.add_int(self.plan.offset + self.plan.count)
        ip, fp = pb.params(pt)
        pb.key(("limit", tv.nb))

        def emit(args):
            valid, pairs = tv.emit(args)
            pr = (args[ip], args[fp])
            rank = jn.cumsum(valid.astype(jn.int64))
            return valid & (rank > pr[0][0]) & (rank <= pr[0][1]), pairs
        return _TView(emit, tv.nb, tv.meta, "limit", tv.nonnull)

    def close(self):
        _close_node(self.child)


def _close_node(node):
    if node is not None and hasattr(node, "close"):
        node.close()


def _compile_node(plan, ctx: _Ctx):
    """Compile a plan subtree to a device node, or wrap it as a host
    leaf.  Returns None only for structural impossibilities at the
    ROOT of the requested subtree (callers fall back entirely)."""
    node = _compile_device(plan, ctx)
    if node is not None:
        return node
    return _HostLeaf.compile(plan, ctx)


def _compile_device(plan, ctx: _Ctx):
    if isinstance(plan, PhysicalTableReader):
        return _ReplicaLeaf.compile(plan, ctx)
    if isinstance(plan, PhysicalHashAgg):
        if not plan.group_by:
            return _ScalarAggNode.compile(plan, ctx)
        node = _AggIndexNode.compile(plan, ctx)
        if node is None:
            node = _SortGroupNode.compile(plan, ctx)
        return node
    if isinstance(plan, PhysicalHashJoin):
        return _JoinNode.compile(plan, ctx)
    if isinstance(plan, PhysicalSelection):
        return _SelNode.compile(plan, ctx)
    if isinstance(plan, PhysicalProjection):
        return _ProjNode.compile(plan, ctx)
    if isinstance(plan, (PhysicalTopN, PhysicalSort)):
        return _OrderNode.compile(plan, ctx)
    if isinstance(plan, PhysicalLimit):
        return _LimitNode.compile(plan, ctx)
    return None


def _contains_join(plan) -> bool:
    if isinstance(plan, PhysicalHashJoin) \
            and not isinstance(plan, PhysicalMergeJoin):
        return True
    return any(_contains_join(c) for c in plan.children)


def _contains_grouped_agg(plan, above_reader: bool = True) -> bool:
    """A GROUP BY anywhere in the plan; ``above_reader=False`` leaves out
    those whose child is a table reader (_AggIndexNode's shape)."""
    if isinstance(plan, PhysicalHashAgg) and plan.group_by and (
            above_reader
            or not isinstance(plan.children[0], PhysicalTableReader)):
        return True
    return any(_contains_grouped_agg(c, above_reader)
               for c in plan.children)


# =========================================================================
# materialization: host chunk from the packed download
# =========================================================================

def _to_chunk(host_pairs, meta, n_rows: int) -> Chunk:
    """A dead slot (the program did not compute it: its consumer does
    not read it) becomes an all-NULL column of the slot's type, so the
    chunk's width and every parent's column indices stay."""
    cols = []
    for pair, (ret_type, decode) in zip(host_pairs, meta):
        if pair is DEAD:
            cols.append(CCol.from_numpy(
                ret_type, np.zeros(n_rows, dtype=_np_dtype(
                    ret_type.eval_type)), np.ones(n_rows, dtype=bool)))
            continue
        v, m = pair
        if decode is not None:
            card = len(decode)
            safe = np.where(m | (v < 0) | (v >= card), 0, v)
            out = np.asarray(decode)[safe].astype(object)
            out[m] = None
            cols.append(CCol.from_numpy(ret_type, out, m))
        else:
            vv = v
            if ret_type.eval_type is EvalType.REAL \
                    and vv.dtype != np.float64:
                vv = vv.astype(np.float64)
            cols.append(CCol.from_numpy(ret_type, vv, m))
    return Chunk.from_columns(cols)


def _chunk_of(sp, meta, out_slots, host, n_valid: int) -> Chunk:
    """The downloaded pairs of the live slots as the pipe's chunk, the
    live ``exec.rows`` span (None outside a statement) told its rows."""
    if sp is not None:
        sp.args["rows"] = n_valid
    return _to_chunk(_spread(len(meta), out_slots, host), meta, n_valid)


# =========================================================================
# executor wrapper
# =========================================================================

class DevPipeExec:
    """Volcano-compatible wrapper: compiles the subtree at open(), runs
    the fused device program once at first next().  Falls back to the
    regular TPU/CPU executors when compilation bails (structurally or at
    run time)."""

    def __init__(self, plan, fallback_builder: Callable):
        self.plan = plan
        self.schema = plan.schema
        self.children = []
        self._fallback_builder = fallback_builder
        self._fallback = None
        self._node = None
        self._mesh = None  # the mesh the node tree was compiled for
        self._done = False
        #: the schema slots the operator above reads (``consumer_reads``),
        #: None: every slot (any other parent, or the statement's root).
        #: The fused program computes, packs and downloads only these;
        #: the fallback returns them all
        self.live = None

    def consumer_reads(self, exprs) -> None:
        """The operator above evaluates ``exprs`` over this pipe's chunks
        and nothing else (a projection; executors._build_executor says
        so): only the slots they reference are live."""
        self.live = frozenset(
            c.index for e in exprs for c in e.collect_columns())

    def field_types(self):
        return [c.ret_type for c in self.plan.schema.columns]

    def open(self, ctx):
        self.ctx = ctx
        self._done = False
        if not self._enabled(ctx):
            self._node = None
            self._open_fallback(ctx)
            return
        if self._spill_pressure(ctx):
            # memory-adaptive execution (ops/spill.py): the fused device
            # pipeline holds whole tables resident and has no spill
            # path — under quota pressure (or spillForceAll) the
            # statement routes to the per-operator executors, whose
            # join/agg/sort/topn spill routes bound the working set
            self._node = None
            self._open_fallback(ctx)
            return
        mesh = mesh_if_enabled(ctx.session_vars)
        if mesh is not None and not _contains_join(self.plan) \
                and _contains_grouped_agg(self.plan, above_reader=False):
            # under tidb_mesh_parallel a GROUP BY over anything but a
            # table reader rides the per-op SHARDED fused aggregate (psum
            # partial merge over the mesh): devpipe's sort-group node is
            # single-device.  Reader-rooted GROUP BYs (_AggIndexNode
            # merges per-shard partial states), join pipelines and plain
            # scan+TopN stay here: those nodes have their own mesh
            # (shard_map) paths.
            self._node = None
            self._open_fallback(ctx)
            return
        self._mesh = mesh
        cctx = _Ctx(ctx, mesh=mesh)
        try:
            self._node = _compile_device(self.plan, cctx)
        except Exception:
            self._bail(ctx, "compile")
            self._node = None
        if self._node is None:
            self._open_fallback(ctx)

    def _spill_pressure(self, ctx) -> bool:
        """Should this statement spill?  Same decision the per-operator
        tier makes (ops/spill.would_spill — the side-effect-free probe:
        no spillForceAll fire consumed, no throwaway SpillContext),
        priced per node with the SAME per-row costs the per-operator
        gates use (join: both sides × _JOIN_ROW_BYTES; everything else:
        the nominal pre-drain price) — if any operator under here would
        run partitioned, the whole pipeline steps aside."""
        from ..ops import spill
        from ..utils import memory as _memory
        from .tpu_executors import _JOIN_ROW_BYTES, _probe_row_bytes

        def est_of(p) -> float:
            return float(getattr(p, "stats_row_count", 0.0) or 0.0)

        def max_bytes(p) -> float:
            if isinstance(p, PhysicalHashJoin) \
                    and not isinstance(p, PhysicalMergeJoin):
                # the join gate prices BOTH sides (it materializes both)
                b = sum(est_of(c) for c in p.children) * _JOIN_ROW_BYTES
            else:
                # measured replica row width when one exists, else the
                # nominal pre-drain price — identical to the
                # per-operator probe (_would_spill_here)
                b = est_of(p) * _probe_row_bytes(
                    p, getattr(ctx, "storage", None))
            for c in getattr(p, "children", ()):
                b = max(b, max_bytes(c))
            return b

        # would_spill prices est_rows × row_bytes; pass the maximum
        # node cost as bytes directly
        return spill.would_spill(_memory.current(), max_bytes(self.plan), 1)

    @staticmethod
    def _forced(ctx) -> bool:
        raw = ctx.session_vars.get("tidb_devpipe", -1)
        return raw is not None and int(raw) == 1

    @staticmethod
    def _bail(ctx, stage: str):
        """A devpipe exception degrades to the per-operator tier — loudly:
        re-raise under tidb_devpipe=1 (tests force the pipeline and must
        see kernel bugs), warn-log otherwise so the regression is visible
        in the slow-query/debug log."""
        if DevPipeExec._forced(ctx):
            raise  # noqa: PLE0704 — re-raise the active exception
        import logging
        logging.getLogger("tinysql_tpu").warning(
            "devpipe %s failed, per-operator fallback", stage,
            exc_info=True)

    @staticmethod
    def _enabled(ctx) -> bool:
        """tidb_devpipe: auto (-1) and 1 run the pipeline (1 also makes
        _bail re-raise); 0 selects the per-operator tier."""
        raw = ctx.session_vars.get("tidb_devpipe", -1)
        return raw is None or int(raw) != 0

    def _open_fallback(self, ctx):
        self._fallback = self._fallback_builder(self.plan)
        qobs = getattr(self, "_obs_qobs", None)
        if qobs is not None:
            # the per-operator fallback tree is built lazily (after
            # instrument_tree walked the executor tree), so a pipeline
            # bail-out instruments it here with the same query scope
            from ..obs.runtime_stats import instrument_tree
            instrument_tree(self._fallback, qobs)
        self._fallback.open(ctx)

    def next(self) -> Optional[Chunk]:
        if self._fallback is not None:
            return self._fallback.next()
        if self._done:
            return None
        self._done = True
        try:
            out = self._run_pipeline()
        except Exception:
            self._bail(self.ctx, "run")
            out = None  # device died mid-run: fall back whole
        if out is None:
            # runtime bail (replica vanished, device error): rebuild on
            # the per-operator executors, which carry their own fallbacks
            _close_node(self._node)
            self._node = None
            self._open_fallback(self.ctx)
            return self._fallback.next()
        return out if out.num_rows() else None

    def _run_pipeline(self) -> Optional[Chunk]:
        """Prepare the node tree (host work + input collection), then run
        the WHOLE pipeline as one jitted program.  Small outputs fold the
        result packing into the same program: one dispatch, one D2H.
        The host's work before the launch is the ``pipe.prepare`` span
        (``replica.memo`` and ``compile`` its children where they
        occur), the downloaded buffers' way into a chunk ``exec.rows``."""
        with _obs.span("pipe.prepare", cat="pipeline"):
            prepared = self._prepare_program()
        if prepared is None:
            return None
        fn, schema, inputs, tv, out_slots = prepared
        nb = tv.nb
        if schema is not None:  # small: packed by the program itself
            vals = kernels.unpack_flat(fn(inputs), schema)
            with _obs.span("exec.rows") as sp:
                keep = np.nonzero(vals[0])[0]
                host = [(vals[1 + 2 * i][keep], vals[2 + 2 * i][keep])
                        for i in range(len(out_slots))]
                return _chunk_of(sp, tv.meta, out_slots, host, len(keep))
        jn = _jn()
        res = fn(inputs)
        valid, items = res[0], list(res[1:])

        def build_count():
            return kernels.counted_jit(
                lambda v: jn.sum(v.astype(jn.int64)))
        cfn = progcache.get(("nvalid", nb), build_count)
        n_valid = int(kernels.d2h(cfn(valid)))
        if n_valid:
            ob = min(kernels.bucket(n_valid), nb)
            _ids, vals = kernels._present_pack(
                valid.astype(jn.int64), items, ob)
        with _obs.span("exec.rows") as sp:
            if n_valid == 0:
                host = [(np.empty(0, dtype=np.int64),
                         np.empty(0, dtype=bool))] * len(out_slots)
            else:
                host = [(vals[2 * i][:n_valid], vals[2 * i + 1][:n_valid])
                        for i in range(len(out_slots))]
            return _chunk_of(sp, tv.meta, out_slots, host, n_valid)

    def _prepare_program(self):
        """``_run_pipeline``'s host work before the launch: the node
        tree prepared, its inputs settled, the program found (or built)
        by its key.  ``(fn, schema, inputs, tv, out_slots)``, ``schema``
        None where the output is too large to pack in the program; None
        where a node bails."""
        pb = _PipeBuilder()
        tv = self._node.prepare(pb, self.live)
        if tv is None:
            return None
        inputs = pb.inputs
        if self._mesh is not None:
            # every input already lies as its program asks, or is moved
            # (and counted): a warm mesh dispatch moves none
            from ..parallel import dist
            inputs = dist.settle(pb.inputs, pb.layouts)
            dist.note_dispatch(self._mesh)
        nb = tv.nb
        ncols = len(tv.meta)
        out_slots = sorted(_live_set(self.live, ncols))
        small = nb <= kernels.SMALL_PACK
        # the input dtype/shape signature joins the key as a structural
        # backstop: a node key that under-pins its closure could otherwise
        # share a cached program whose retrace clobbers the mutable pack
        # schema (jit holds one trace per signature, the schema list holds
        # only the LAST trace's layout)
        sig = tuple((str(getattr(a, "dtype", type(a))),
                     tuple(getattr(a, "shape", ())))
                    for a in pb.inputs)
        key = ("pipe", small, tuple(pb.kparts), sig)
        if pb.lparts or len(out_slots) < ncols:
            # what the pack below holds, and what each node computes
            key += (("live", tuple(out_slots)) + tuple(pb.lparts),)
        if len(out_slots) < ncols:
            kernels.stats_add("pipe_dead_cols", ncols - len(out_slots))
        if pb.const_nulls:
            kernels.stats_add("pipe_const_nulls", pb.const_nulls)
        # the program's name in a profile: its node kinds, leaves first
        shape = "_".join(str(part[0]) for part in pb.kparts)
        if small:
            def build_small():
                schema: list = []
                emit = tv.emit

                def mega(args):
                    valid, cols = emit(args)
                    flat = [valid]
                    for v, m in (cols[i] for i in out_slots):
                        flat.append(v)
                        flat.append(m)
                    return kernels.pack_arrays(schema, flat)
                _note_compiled(pb.kparts)
                return kernels.counted_jit(mega, name=shape), schema
            fn, schema = progcache.get(key, build_small)
        else:
            def build_big():
                emit = tv.emit

                def mega(args):
                    valid, cols = emit(args)
                    return [valid] + [x for i in out_slots
                                      for x in cols[i]]
                _note_compiled(pb.kparts)
                return kernels.counted_jit(mega, name=shape)
            fn, schema = progcache.get(key, build_big), None
        return fn, schema, inputs, tv, out_slots

    def drain(self) -> List[list]:
        rows = []
        while True:
            _interrupt.check()
            _fail.inject("execSlowNext")
            chk = self.next()
            if chk is None:
                break
            with _obs.span("exec.rows", rows=chk.num_rows()):
                rows.extend(chk.to_rows())
        return rows

    def close(self):
        if self._fallback is not None:
            self._fallback.close()
        _close_node(self._node)

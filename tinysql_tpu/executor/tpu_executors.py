"""TPU executor tier: the north-star operators.

Capability parity with BASELINE.json: TPU-backed HashAgg / HashJoin /
Sort / TopN / Projection / Selection registered behind the same volcano
interface as the CPU tier — marshalling chunk columns to device arrays
(SURVEY §2.9 note: Column {data, null} maps 1:1 onto array + mask), running
ops/kernels.py sort/segment kernels, and materializing results back.

String group/sort keys ride order-preserving dictionary codes built on the
host (np.unique), so TPC-H-style char keys still hit the device path.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..chunk import Chunk, Column as CCol, MAX_CHUNK_SIZE
from ..expression import vectorized_filter
from ..expression.aggregation import (AGG_AVG, AGG_COUNT, AGG_FIRST_ROW,
                                      AGG_MAX, AGG_MIN, AGG_SUM)
from ..mytypes import EvalType, new_real_type
from ..obs import context as _obs
from ..ops import kernels, progcache
from ..planner.physical import (PhysicalHashAgg, PhysicalHashJoin,
                                PhysicalProjection, PhysicalSelection,
                                PhysicalSort, PhysicalTopN)
from .executors import Executor, build_executor


def _batching_active() -> bool:
    """Is a batch round's collect/replay leg live on this context?
    (ops/batching.active — lazy import so the executor stays importable
    without the serving layer initialized)"""
    from ..ops import batching
    return batching.active()


def _drain_chunk(ex: Executor, fields, soft: bool = False) -> Chunk:
    """``soft=True`` (spill-mode callers): the whole drain — child
    per-chunk allocations AND the accumulator growth — charges through
    the tracker's soft path (utils/memory.soft_scope).  A spill-mode
    operator's input materialization inherently overshoots the quota on
    a cold scan (no replica to serve zero-copy views); the partitioner
    takes the accumulated chunk over and releases it immediately after,
    and any nested operator inside the drained subtree sees the same
    watermark-crossed tracker, so its own spill gate fires.  Hard
    enforcement resumes at the first charge outside the scope."""
    from ..utils import memory as _memory
    from contextlib import nullcontext
    with (_memory.soft_scope() if soft else nullcontext()):
        first = ex.next()
        if first is None:
            return Chunk(fields, cap=MAX_CHUNK_SIZE)
        nxt = ex.next()
        if nxt is None:
            # single-chunk children (every device-tier operator) hand
            # their output over without a copy — this also keeps
            # DeviceColumn (late-materialization) chunks on device
            return first.compact()
        out = Chunk(fields, cap=MAX_CHUNK_SIZE)
        out.append_chunk(first)
        out.append_chunk(nxt)
        while True:
            chk = ex.next()
            if chk is None:
                break
            out.append_chunk(chk)
        return out


def _block_budget(session_vars) -> int:
    """tidb_device_block_rows, defensively parsed — the ONE reader all
    block-wise paths (agg, join, sort, topn, devpipe leaf) share."""
    try:
        return int(session_vars.get("tidb_device_block_rows", 0) or 0)
    except Exception:
        return 0


def _mesh_for(ctx, nb: int, plan=None):
    """Execution mesh for one sharded dispatch, or None = single-device.
    Gates, in order: session opt-in (tidb_mesh_parallel), the planner's
    estRows-driven shard count when annotated (plan.mesh_shards from
    planner/device.py — 1 is the degenerate 'stay single-device' case,
    >=2 clips to a cached submesh), and the runtime row-bucket gate
    (dist.shardable) on the ACTUAL padded row count."""
    from ..parallel import dist
    mesh = dist.session_mesh(ctx.session_vars)
    if mesh is None:
        return None
    want = int(getattr(plan, "mesh_shards", 0) or 0)
    if want == 1:
        return None
    if want >= 2:
        mesh = dist.sized_mesh(min(want, dist.mesh_shards(mesh)))
    return mesh if dist.shardable(nb, mesh) else None


def _spill_run_rows(sctx, n: int, row_bytes: int) -> int:
    """Run length for the external sort/top-k: what the resident budget
    holds, floored (tiny budgets must not devolve into per-row runs) and
    — under spillForceAll with no real quota — capped so small inputs
    still produce multiple runs for the store to prove itself on."""
    rows = int(sctx.budget // max(row_bytes, 1))
    if sctx.spill_all:
        rows = min(rows, max(n // 4, 256))
    return max(min(rows, n), 256)


def _est_rows_of(plan_child) -> float:
    return float(getattr(plan_child, "stats_row_count", 0.0) or 0.0)


def _maybe_spill_ctx(ctx, est_rows: float, actual_rows: int,
                     row_bytes: int, label: str):
    """Memory-adaptive execution gate shared by join/agg/sort/topn: a
    live ops/spill.SpillContext when this operator should run its
    partitioned spill path (spillForceAll, watermark crossed, or the
    planner's estRows pricing the operator's materialization over the
    watermark headroom), else None.  Partition-count choice rides the
    PLANNER estimate — the statement decides its fan-out before
    materializing — with the actual row count as the no-stats
    fallback."""
    from ..ops import spill
    from ..utils import memory as _memory
    if est_rows <= 0:
        est_rows = float(actual_rows)
    return spill.maybe_context(ctx.session_vars, _memory.current(),
                               max(est_rows, float(actual_rows)),
                               row_bytes, label)


#: per-row pricing for the join's proactive estRows trigger: what a row
#: of the charged working set costs (a compacted side is ~4 numeric
#: columns + null masks); agg and sort price their own rows from the
#: actual argument/key layout
_JOIN_ROW_BYTES = 36

#: nominal per-row pricing for the sort/topn PRE-drain softness check —
#: the FALLBACK when no measured width exists (obs/memprof.py
#: measured_row_bytes replaces it with the table's replica truth): the
#: would-this-spill probe prices one 8-byte key + null + rowid
_NOMINAL_ROW_BYTES = 17


def _plan_base_table_id(plan) -> int:
    """Table id of the single base table feeding ``plan`` (walks reader
    wrappers and unary operators down to the scan; 0 when the subtree is
    not scan-rooted — joins, memtables)."""
    node = plan
    for _ in range(32):
        scan = getattr(node, "scan", None) or \
            getattr(node, "table_scan", None)
        if scan is not None:
            node = scan
        info = getattr(node, "table_info", None)
        if info is not None:
            return int(info.id)
        kids = getattr(node, "children", None)
        if not kids or len(kids) != 1:
            return 0
        node = kids[0]
    return 0


def _probe_row_bytes(plan, storage=None) -> int:
    """Measured per-row width for the pre-drain spill probe: the base
    table's replica truth (obs/memprof.py — device-memoized column
    bytes over rows) when a replica exists, else the nominal constant.
    The measured number prices what a drained row of THIS table really
    costs, so `would_spill` flips where the ledger alone would not."""
    from ..obs import memprof
    tid = _plan_base_table_id(plan)
    if tid <= 0:
        return _NOMINAL_ROW_BYTES
    return memprof.measured_row_bytes(tid, _NOMINAL_ROW_BYTES,
                                      storage=storage)


def _would_spill_here(ctx, plan) -> bool:
    """Side-effect-free pre-drain probe for sort/topn: the real spill
    gate runs after materialization (it needs the actual key layout), but
    the drain's accumulator copies must already charge soft when the gate
    is going to say yes — otherwise a cold scan bigger than the quota
    dies before the external sort can spill a single run."""
    from ..ops import spill
    from ..utils import memory as _memory
    return spill.would_spill(_memory.current(),
                             _est_rows_of(plan.children[0]),
                             _probe_row_bytes(plan.children[0],
                                              getattr(ctx, "storage",
                                                      None)))


#: below this selectivity, compacting beats masking: masked full-table
#: kernels win almost always (stable shapes = one compile; throughput
#: absorbs the extra rows)
MASK_COMPACT_THRESHOLD = 0.3


def _take_replica_masked(ex: Executor, extra_conds=None):
    """Single owner of the raw-replica intake: (chunk, mask, replica) with
    scan filters plus `extra_conds` folded into one mask (None when no
    conditions), or (None, None, None) when the child cannot serve raw.

    String comparisons against constants rewrite to integer compares over
    replica-memoized dictionary codes (ordered np.unique) — built once per
    replica version, they turn e.g. TPC-H date-range filters from <U
    string compares into int64 compares."""
    from .executors import TableReaderExec
    if not isinstance(ex, TableReaderExec):
        return None, None, None
    chk, filters, rep = ex.take_raw_replica()
    if chk is None:
        return None, None, None
    conds = list(filters) + list(extra_conds or [])
    if not conds:
        return chk, None, rep
    return chk, _fold_filter_masks(ex, rep, chk, conds), rep


def _fold_filter_masks(ex, rep, chk, conds):
    """AND-fold host masks for `conds`: string compares ride dictionary
    codes, the residual goes through vectorized_filter.  Shared by the
    replica intake and the fused-agg host-mask fallback."""
    mask = None
    residual = []
    for c in conds:
        m = _string_cmp_mask(ex, rep, chk, c)
        if m is None:
            residual.append(c)
        else:
            mask = m if mask is None else (mask & m)
    if residual:
        rm = vectorized_filter(residual, chk)
        mask = rm if mask is None else (mask & rm)
    return mask


_STR_CMP_OPS = {"=", "!=", "<", "<=", ">", ">="}


def _parse_string_cmp(chk, cond):
    """Recognize `string Column <op> string Constant` (either order).
    Returns (col, op, value) with the op flipped for constant-first, or
    None."""
    from ..expression import Column as ExprColumn, Constant, ScalarFunction
    from ..mytypes import EvalType as ET
    if not (isinstance(cond, ScalarFunction)
            and cond.name in _STR_CMP_OPS and len(cond.args) == 2):
        return None
    a, b = cond.args
    flip = False
    if isinstance(b, ExprColumn) and isinstance(a, Constant):
        a, b = b, a
        flip = True
    if not (isinstance(a, ExprColumn) and isinstance(b, Constant)):
        return None
    if a.eval_type is not ET.STRING or not isinstance(b.value, str):
        return None
    if chk.columns[a.index].values().dtype.kind != "U":
        return None
    op = cond.name
    if flip:
        op = {"<": ">", "<=": ">=", ">": "<", ">=": "<=",
              "=": "=", "!=": "!="}[op]
    return a, op, b.value


def _parse_like(chk, cond):
    """Recognize `string Column [NOT] LIKE string Constant`.  Returns
    (col, pattern, escape, negated) or None."""
    from ..expression import Column as ExprColumn, Constant, ScalarFunction
    from ..mytypes import EvalType as ET
    negated = False
    if isinstance(cond, ScalarFunction) and cond.name == "not" \
            and len(cond.args) == 1:
        cond, negated = cond.args[0], True
    if not (isinstance(cond, ScalarFunction) and cond.name == "like"
            and len(cond.args) == 2):
        return None
    a, b = cond.args
    escape = getattr(cond._scalar_fn, "like_escape", None)
    if not (isinstance(a, ExprColumn) and isinstance(b, Constant)) \
            or a.eval_type is not ET.STRING \
            or not isinstance(b.value, str) or escape is None:
        return None
    if chk.columns[a.index].values().dtype.kind != "U":
        return None
    return a, b.value, escape, negated


def _like_code_words(uniques, pattern: str, escape: str,
                     negated: bool) -> np.ndarray:
    """The dictionary codes a LIKE keeps, as a bit set: bit c of word
    c >> 6 is set where entry c of the ordered dictionary ``uniques``
    matches (the host's own ``like``, evaluated once an entry), or fails
    to under NOT LIKE.  NULL (code = card) is in neither."""
    from ..expression import like_to_regex
    rx = like_to_regex(pattern, escape)
    hit = np.fromiter((rx.match(u) is not None for u in uniques.tolist()),
                      dtype=bool, count=len(uniques))
    if negated:
        hit = ~hit
    bits = np.zeros(max(1, -(-len(hit) // 64)) * 64, dtype=bool)
    bits[:len(hit)] = hit
    weights = np.left_shift(np.uint64(1), np.arange(64, dtype=np.uint64))
    return (bits.reshape(-1, 64).astype(np.uint64) * weights).sum(
        axis=1, dtype=np.uint64).view(np.int64)


def _code_set_fn(idx: int, word0: int, card_s: int):
    """Device closure: membership of each row's dictionary code in the
    bit set whose words are the int parameters from ``word0`` on (a LIKE
    on a dictionary-coded column); a NULL is in no set."""
    def f(cols, params):
        jn = kernels.jnp()
        code, null = cols[idx]
        pi = params[0]
        card = pi[card_s]
        at = jn.clip(code, 0, jn.maximum(card - 1, 0))
        word = pi[word0 + (at >> 6)]
        hit = (word >> (at & 63)) & 1
        live = (code != card) & ~null
        return (hit * live).astype(jn.int64), jn.zeros_like(null)
    return f


def _code_cmp_fn(idx: int, op: str, lo_s: int, hi_s: int, card_s: int):
    """Device closure: string compare as an int compare over the slot's
    dictionary-code column, with per-query [lo, hi) bounds + NULL code as
    runtime params."""
    def f(cols, params):
        jn = kernels.jnp()
        code, null = cols[idx]
        pi = params[0]
        r = _code_cmp(jn, op, code, pi[lo_s], pi[hi_s], pi[card_s],
                      null=null)
        return r.astype(jn.int64), jn.zeros_like(null)
    return f


def _build_device_mask(ex, rep, chk, conds, pt):
    """Compile scan filters into an on-device mask program over the fused
    kernels' dev_cols.  Returns (mask_fn, key, needed) — needed is a set
    of (slot index, "codes" | "full") the program reads — or None when
    some condition cannot run on device (host mask fallback).  ``pt`` is
    the query's shared ParamTable (aggregate arguments append to the
    same vector): the live row count takes a slot first (padding guard),
    then per-condition constants — so changing any literal in the family
    never recompiles.  NOTE a None return may leave consumed slots in
    ``pt``; callers discard and rebuild it (slot order is part of the
    cached program's contract)."""
    from ..ops.exprjit import (compile_expr_params, is_jittable,
                               stable_shape_key)
    row_slot = pt.add_int(chk.full_rows())
    fns = []
    keys = []
    needed = set()
    for cond in conds:
        sc = _parse_string_cmp(chk, cond)
        like = _parse_like(chk, cond) if sc is None else None
        if sc is not None:
            col, op, val = sc
            idx = col.index
            got = _rep_string_dict(rep, _slot_id(ex, idx), chk, idx)
            if got is None:
                return None
            _codes, card, _, uniques = got
            lo = int(np.searchsorted(uniques, val, side="left"))
            hi = int(np.searchsorted(uniques, val, side="right"))
            fns.append(_code_cmp_fn(idx, op, pt.add_int(lo),
                                    pt.add_int(hi), pt.add_int(card)))
            keys.append(f"strcmp@{idx}:{op}")
            needed.add((idx, "codes"))
        elif like is not None:
            col, pattern, escape, negated = like
            idx = col.index
            got = _rep_string_dict(rep, _slot_id(ex, idx), chk, idx)
            if got is None:
                return None
            _codes, card, _, uniques = got
            words = _like_code_words(uniques, pattern, escape, negated)
            word0 = pt.add_int(int(words[0]))
            for w in words[1:]:
                pt.add_int(int(w))
            fns.append(_code_set_fn(idx, word0, pt.add_int(card)))
            # the set's width is the program's; its bits are parameters
            keys.append(f"like@{idx}:{len(words)}")
            needed.add((idx, "codes"))
        elif is_jittable(cond):
            fns.append(compile_expr_params(cond, pt))
            keys.append(stable_shape_key(cond))
            for c in cond.collect_columns():
                needed.add((c.index, "full"))
        else:
            return None

    def mask_fn(cols, params, row_idx):
        m = row_idx < params[0][row_slot]
        for f in fns:
            v, null = f(cols, params)
            m = m & (v != 0) & ~null
        return m
    return mask_fn, tuple(keys), needed


def rep_string_codes(rep, sid, v, null):
    """Ordered dictionary codes for a string replica column, memoized per
    replica version: (codes int64 [n] with NULL -> card, card, base=0,
    uniques).  ONE builder for every consumer of the ("keycodes", ...)
    memo slot (TPU group keys, device masks, CPU string filters) so the
    cached tuple shape can never drift between tiers."""
    def build():
        has_null = bool(null.any())
        safe = np.where(null, "", v) if has_null else v
        if safe.dtype.kind != "U":
            safe = safe.astype(str)
        uniques, codes = _ordered_codes(safe)
        if has_null:
            codes = np.where(null, len(uniques), codes)
        return codes.astype(np.int64, copy=False), len(uniques), 0, uniques
    return rep.memo(("keycodes", sid, True, False), build)


#: rows a block of _ordered_codes; below two blocks np.unique does it whole
_CODE_BLOCK = 1 << 18


def _ordered_codes(v):
    """(sorted uniques, code of each row) of a fixed-width string lane:
    ``np.unique(v, return_inverse=True)``, which sorts every row, done
    in row blocks on the host's cores for a lane of millions of rows —
    each block's own uniques, their union sorted, then each row's place
    in it by binary search (numpy's sorts and searches of fixed-width
    strings release the interpreter).  A 60 M-row date column has 2.5 k
    values: 12 compares a row, not a 26-level sort (PERF.md section 6,
    PR 33)."""
    n = len(v)
    if n < 2 * _CODE_BLOCK:
        return np.unique(v, return_inverse=True)
    blocks = [slice(i, min(i + _CODE_BLOCK, n))
              for i in range(0, n, _CODE_BLOCK)]
    codes = np.empty(n, dtype=np.int64)
    with ThreadPoolExecutor(max_workers=min(
            len(blocks), len(os.sched_getaffinity(0)))) as pool:
        uniques = np.unique(np.concatenate(
            list(pool.map(lambda b: np.unique(v[b]), blocks))))

        def fill(b):
            codes[b] = np.searchsorted(uniques, v[b])
        list(pool.map(fill, blocks))
    return uniques, codes


def _rep_string_dict(rep, sid, chk, idx):
    col = chk.columns[idx]
    return rep_string_codes(rep, sid, col.values(), col.null_mask())


def _slot_id(ex, idx: int):
    """Stable replica-memo id for a schema slot (column id or the
    handle)."""
    ci = ex._decode_cols[idx]
    return ci.id if ci is not None else "handle"


def _code_cmp(np_or_jnp, op: str, code, lo, hi, card, null=None):
    """The dictionary-code compare ladder over [lo, hi) bounds — one
    implementation serving both the host (numpy) and device (jnp traced)
    paths."""
    live = code != card  # NULL code = card: comparisons exclude it
    if null is not None:
        live = live & ~null
    if op == "=":
        r = (code >= lo) & (code < hi)
    elif op == "!=":
        r = (code < lo) | (code >= hi)
    elif op == "<":
        r = code < lo
    elif op == "<=":
        r = code < hi
    elif op == ">":
        r = code >= hi
    else:  # >=
        r = code >= lo
    return r & live


def _string_cmp_mask(ex, rep, chk, cond):
    """Try to evaluate `cond` (string Column vs string Constant compare)
    through dictionary codes; returns a bool mask or None."""
    sc = _parse_string_cmp(chk, cond)
    if sc is None:
        return None
    a, op, val = sc
    codes, card, _, uniques = _rep_string_dict(rep, _slot_id(ex, a.index),
                                               chk, a.index)
    lo = int(np.searchsorted(uniques, val, side="left"))
    hi = int(np.searchsorted(uniques, val, side="right"))
    return _code_cmp(np, op, codes, lo, hi, card)


def _compact_if_selective(chk: Chunk, mask):
    """Selective filters compact (less kernel work); permissive ones stay
    masked (stable bucket shape = one TPU compile per table size).
    String columns compact LAZILY (LazyTakeColumn): copying a <U date
    column costs ~5x an int64 copy, and a join above usually needs only
    its final few rows — the gather defers to that cardinality."""
    from ..chunk.column import LazyTakeColumn
    if (mask is not None and mask.size
            and mask.mean() < MASK_COMPACT_THRESHOLD):
        sel = np.nonzero(mask)[0]
        cols = []
        for c in chk.columns:
            v = c._data
            if v is not None and (v.dtype == object or v.dtype.kind == "U"):
                cols.append(LazyTakeColumn(c, sel))
            else:
                cols.append(c.take(sel))
        return Chunk.from_columns(cols), None
    if mask is not None and not mask.size:
        return chk, None  # empty chunk: nothing to mask
    return chk, mask


def _child_input(ex: Executor, soft: bool = False) -> Chunk:
    """Materialize a child's full output: TableReaders on the columnar
    replica hand over zero-copy column views (filters applied by selection
    compaction) instead of slicing + re-appending chunk by chunk.
    ``soft=True``: spill-mode caller — the accumulation/compaction copies
    are soft-charged (see :func:`_drain_chunk`)."""
    chk, mask, _rep = _take_replica_masked(ex)
    if chk is not None:
        if mask is not None:
            chk.set_sel(np.nonzero(mask)[0])
            chk = chk.compact()
        return chk
    out = _drain_chunk(ex, ex.field_types(), soft=soft)
    if soft:
        from ..utils import memory as _memory
        with _memory.soft_scope():
            return out.compact()
    return out.compact()


def _count_mask_program(slot: int):
    """COUNT(col) consumes only the column's null mask; the value half of
    the device pair may be absent (string columns upload masks only)."""
    def fn(cols, params):
        null = cols[slot][1]
        return null, null
    return fn


def _lower_agg_args(arg_exprs, pt):
    """Aggregate-argument entries -> ((cols, params) programs, shape-keyed
    program_key tuple).  ONE lowering for the whole-table fused path and
    the block-pipeline path: the cache-key contract (same key => same
    ParamTable slot layout) spans both, so they must never diverge.
    Constants ride ``pt`` — a changed literal is a program-cache HIT."""
    from ..ops.exprjit import compile_expr_params, stable_shape_key
    progs = []
    pk_parts = []
    for a in arg_exprs:
        if isinstance(a, tuple):
            progs.append(_count_mask_program(a[1]))
            pk_parts.append(f"mask@{a[1]}")
        elif a is None:
            progs.append(None)
            pk_parts.append("-")
        else:
            progs.append(compile_expr_params(a, pt))
            pk_parts.append(stable_shape_key(a))
    return progs, tuple(pk_parts)


def _composite_key_lanes(lkeys, lchk, rkeys, rchk):
    """Multi-key equi-join keys -> ONE int64 lane per side via JOINT
    factorization (np.unique over both sides' stacked key tuples):
    equal tuples get equal codes, distinct tuples distinct codes —
    collision-free for any value range, unlike stride composites.  A
    tuple with ANY NULL component never equi-matches (null mask OR).
    Returns ((lk, lnull), (rk, rnull)) host arrays for the single-key
    kernels."""
    def stack(keys, chk):
        pairs = [e.vec_eval(chk) for e in keys]
        vals = np.stack([np.asarray(v).astype(np.int64, copy=False)
                         for v, _ in pairs], axis=1)
        null = np.zeros(len(vals), dtype=bool)
        for _, m in pairs:
            null |= np.asarray(m)
        return vals, null
    lv, lnull = stack(lkeys, lchk)
    rv, rnull = stack(rkeys, rchk)
    both = np.concatenate([lv, rv], axis=0)
    _, inv = np.unique(both, axis=0, return_inverse=True)
    inv = np.asarray(inv, dtype=np.int64).ravel()
    return (inv[:len(lv)], lnull), (inv[len(lv):], rnull)


def _encode_key(e, chk: Chunk) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Evaluate a group/sort key over the chunk -> (codes, null, decode).
    Strings become order-preserving dictionary codes; decode maps code ->
    original value (None for numerics)."""
    v, null = e.vec_eval(chk)
    if v.dtype == object or v.dtype.kind == "U":
        safe = np.where(null, "", v)
        uniques, codes = np.unique(safe.astype(str), return_inverse=True)
        return codes.astype(np.int64), null, uniques
    if v.dtype == np.int64 and getattr(e.ret_type, "is_unsigned", False):
        # unsigned values live two's-complement-wrapped in the int64 buffer;
        # XOR with the sign bit maps unsigned order onto signed int64 order
        # (bijective, so it's equally valid as a group key)
        v = v ^ np.int64(-2**63)
    return v, null, None


class TPUHashAggExec(Executor):
    """Group-by as device segment-reduce (SURVEY §2.11 P5 TPU counterpart)."""

    def __init__(self, plan: PhysicalHashAgg, child: Executor):
        super().__init__(plan.schema, [child])
        self.plan = plan
        self._done = False

    def open(self, ctx):
        super().open(ctx)
        self._done = False

    def _raw_replica_input(self, compact: bool = True):
        """Fused fast path: the child is a TableReader serving from the
        columnar replica — take the FULL table as a zero-copy chunk view
        and turn the scan filters into a device-side valid mask, skipping
        chunk slicing, host compaction, and append copies entirely (the
        filter+aggregate fusion XLA is built for).  ``compact=False``
        (spill mode) keeps even selective filters as masks: the charged
        compaction copy is exactly the working set the quota is trying
        to bound, and the partitioned path selects live rows itself."""
        chk, mask, _rep = _take_replica_masked(self.children[0])
        if chk is None:
            return None, None
        # low-selectivity GROUPED aggregates sort faster over a compacted
        # input; scalar aggregates never sort, so they keep the fused mask
        if self.plan.group_by and compact:
            chk, mask = _compact_if_selective(chk, mask)
        return chk, mask

    @staticmethod
    def _try_segment_layout(keys, n: int):
        """If every group key has known small cardinality (dictionary codes
        for strings; narrow value range for ints), lay the keys out as one
        composite segment id.  Returns (gid, cards, bases) or None.  Each
        key gets one extra bin for NULL."""
        if n == 0:
            return None
        cards = []
        bases = []
        effs = []
        total = 1  # final value = the composite segment count
        for v, null, decode in keys:
            if decode is not None:
                card = len(decode)
                eff = np.where(null, card, v)
                base = 0
            elif v.dtype == np.int64:
                nn = v[~null]
                if len(nn) == 0:
                    card, base = 0, 0
                    eff = np.full(n, 0, dtype=np.int64)
                else:
                    vmin, vmax = int(nn.min()), int(nn.max())
                    card = vmax - vmin + 1
                    if card > kernels.seg_limit(n):
                        return None
                    base = vmin
                    eff = np.where(null, card, v - vmin)
            else:
                return None  # float keys: sort-based path
            total *= card + 1
            if total > kernels.seg_limit(n):
                return None
            cards.append(card)
            bases.append(base)
            effs.append(eff.astype(np.int64))
        gid = np.zeros(n, dtype=np.int64)
        for eff, card in zip(effs, cards):
            gid = gid * (card + 1) + eff
        return gid, cards, bases, total

    # ---- fully fused device path ------------------------------------------
    def _try_fused_device(self):
        """The flagship aggregation path: device-resident padded columns
        (memoized on the replica), ON-DEVICE argument evaluation via the
        exprjit lowering, host filter mask as the only per-query upload,
        one XLA program end to end.  Returns an output Chunk or None to
        fall back.  The host's work before the launch is the
        ``agg.prepare`` span (the per-operator tier's ``pipe.prepare``),
        the downloaded aggregates' way into a chunk ``exec.rows``."""
        with _obs.span("agg.prepare"):
            run = self._fused_prepare()
        return run() if callable(run) else run

    def _fused_prepare(self):
        """Everything of ``_try_fused_device`` up to the launch: a
        closure that runs the one program and assembles its output, or
        what a path that does not come to that returned (None to fall
        back; the block-wise path's finished chunk)."""
        from .executors import TableReaderExec
        from ..ops.exprjit import is_jittable
        plan = self.plan
        child = self.children[0]
        if not isinstance(child, TableReaderExec):
            return None
        rep = getattr(child, "_replica", None)
        if rep is None or child.scan.pushed_agg is not None:
            return None
        from ..expression import Column as ExprColumn, Constant

        # ---- eligibility + spec/arg-program assembly --------------------
        specs: List[Tuple[str, bool]] = []
        arg_exprs: List = []      # jittable expr | ("mask", slot) | None
        slots: List[tuple] = []
        from ..expression.aggregation import AggMode
        for d in plan.aggs:
            if d.distinct:
                return None
            if d.mode is AggMode.FINAL and d.name == AGG_COUNT:
                a = d.args[0]
                if not is_jittable(a):
                    return None
                # sum0: merged COUNT is 0 over empty input, never NULL
                specs.append(("sum0", True))
                arg_exprs.append(a)
                slots.append(("dev", len(specs) - 1))
            elif d.mode is AggMode.FINAL and d.name == AGG_AVG:
                a0, a1 = d.args
                if not (is_jittable(a0) and is_jittable(a1)):
                    return None
                if a0.eval_type is not EvalType.REAL:
                    from ..expression.builtins import new_function
                    a0 = new_function("cast_real", [a0])
                specs.append(("sum", True))
                arg_exprs.append(a0)
                specs.append(("sum", True))
                arg_exprs.append(a1)
                slots.append(("avg", len(specs) - 2, len(specs) - 1))
            elif d.name == AGG_COUNT:
                a = d.args[0]
                if isinstance(a, Constant) and a.value is not None:
                    specs.append(("count_star", False))
                    arg_exprs.append(None)
                    slots.append(("dev", len(specs) - 1))
                elif isinstance(a, ExprColumn):
                    specs.append(("count", True))
                    arg_exprs.append(("mask", a.index))
                    slots.append(("dev", len(specs) - 1))
                elif is_jittable(a):
                    specs.append(("count", True))
                    arg_exprs.append(a)
                    slots.append(("dev", len(specs) - 1))
                else:
                    return None
            elif d.name == AGG_SUM:
                a = d.args[0]
                if not is_jittable(a):
                    return None
                if (d.ret_type.eval_type is EvalType.REAL
                        and a.eval_type is not EvalType.REAL):
                    from ..expression.builtins import new_function
                    a = new_function("cast_real", [a])
                specs.append(("sum", True))
                arg_exprs.append(a)
                slots.append(("dev", len(specs) - 1))
            elif d.name == AGG_AVG:
                a = d.args[0]
                if not is_jittable(a):
                    return None
                from ..expression.builtins import new_function
                ar = a if a.eval_type is EvalType.REAL \
                    else new_function("cast_real", [a])
                specs.append(("sum", True))
                arg_exprs.append(ar)
                specs.append(("count", True))
                arg_exprs.append(a)
                slots.append(("avg", len(specs) - 2, len(specs) - 1))
            elif d.name in (AGG_MAX, AGG_MIN):
                a = d.args[0]
                if not is_jittable(a):
                    return None
                if (a.eval_type is EvalType.INT
                        and getattr(a.ret_type, "is_unsigned", False)):
                    return None  # unsigned order map: sort path handles
                specs.append((("max" if d.name == AGG_MAX else "min"), True))
                arg_exprs.append(a)
                slots.append(("dev_mm", len(specs) - 1, False))
            elif d.name == AGG_FIRST_ROW:
                if not isinstance(d.args[0], ExprColumn):
                    return None
                slots.append(("first", d.args[0]))
            else:
                return None

        # group keys must be plain Columns (codes memoized on the replica)
        for e in plan.group_by:
            if not isinstance(e, ExprColumn):
                return None

        chk, filters, rep = child.take_raw_replica()
        if chk is None:
            return None  # nothing consumed: reader bails identically
        n = chk.full_rows()
        nb = kernels.bucket(max(n, 1))
        jn = kernels.jnp()
        # stable per-slot ids: replica memos are shared across queries with
        # different column pruning, so slot INDEXES must never key them
        slot_ids = [ci.id if ci is not None else "handle"
                    for ci in child._decode_cols]

        # ---- per-key codes (memoized per replica) -----------------------
        key_layouts = []
        for e in plan.group_by:
            lay = self._rep_key_codes(rep, e, chk, slot_ids[e.index])
            if lay is None:
                child._replica = rep  # un-consume for the fallback path
                return None
            key_layouts.append(lay)
        n_segments = 1
        for _, card, _, _ in key_layouts:
            n_segments *= card + 1
        if n_segments > kernels.seg_limit(n) and plan.group_by:
            child._replica = rep
            return None

        # ---- block-wise execution (SURVEY §5.7): tables above the device
        # buffer budget stream through HBM in row blocks; partial states
        # carry on host between blocks
        budget = _block_budget(self.ctx.session_vars)
        if budget > 0 and n > budget:
            out = self._fused_blockwise(chk, rep, child, filters,
                                        specs, arg_exprs, slots,
                                        key_layouts, n_segments, n, budget)
            if out is not None:
                return out
            child._replica = rep
            return None

        # ---- filter mask: on-device program when every condition lowers
        # (constants as runtime params — zero recompiles across constant
        # changes, ~100-byte upload); host numpy + nb-bool upload otherwise.
        # ONE ParamTable serves the mask AND the aggregate arguments: the
        # whole fused program's constants ride a single runtime vector.
        from ..ops.exprjit import ParamTable
        pt = ParamTable()
        dev_mask = _build_device_mask(child, rep, chk, filters, pt)
        if dev_mask is None:
            pt = ParamTable()  # discard half-consumed mask slots
            fmask = _fold_filter_masks(child, rep, chk, filters) \
                if filters else None
            mask_needed = set()
        else:
            mask_fn, mask_prog_key, mask_needed = dev_mask
            fmask = None

        # ---- device columns (memoized per replica + bucket; under a mesh
        # row-sharded over it and memoized under that layout) ------------
        from .devpipe import _dev_upload, _layouts
        mesh = self._mesh_if_enabled(nb)
        lrows, lwhole = _layouts(mesh)
        needed = set(mask_needed)
        for a in arg_exprs:
            if isinstance(a, tuple):
                needed.add((a[1], "mask"))
            elif a is not None:
                for c in a.collect_columns():
                    needed.add((c.index, "full"))
        dev_cols = [None] * len(chk.columns)
        for idx, kind in needed:
            col = chk.columns[idx]
            v = col.values()
            m = col.null_mask()
            sid = slot_ids[idx]
            if kind == "codes":
                # string filter rides dictionary codes; the value half of
                # the slot carries the code column
                got = _rep_string_dict(rep, sid, chk, idx)
                codes = got[0]
                dv = _dev_upload(rep, ("devcodes", sid, nb),
                                 lambda c=codes: kernels.pad1(c, nb), lrows)
            elif v.dtype == object or v.dtype.kind == "U":
                if kind == "full":
                    child._replica = rep
                    return None  # string values in a compute expr
                dv = None
            else:
                dv = _dev_upload(rep, ("devv", sid, nb),
                                 lambda v=v: kernels.pad1(v, nb), lrows)
            dn = _dev_upload(rep, ("devn", sid, nb),
                             lambda m=m: kernels.pad1(m, nb, True), lrows)
            if dev_cols[idx] is None or dv is not None:
                dev_cols[idx] = (dv, dn)

        # aggregate-argument programs: params-compiled against the SAME
        # ParamTable as the mask, program cache keyed by expression SHAPE
        progs, program_key = _lower_agg_args(arg_exprs, pt)
        params = pt.arrays()

        # ---- mask spec for the kernels ----------------------------------
        if dev_mask is not None:
            mask_spec = ("dev", mask_fn, mask_prog_key)
        else:
            mask = np.zeros(nb, dtype=bool)
            mask[:n] = fmask if fmask is not None else True
            mask_spec = ("host", kernels.h2d(mask, lrows))

        gid_dev = None
        if plan.group_by:
            gid_dev = _dev_upload(
                rep, ("gid_dev", tuple(slot_ids[e.index]
                                       for e in plan.group_by), nb),
                lambda: kernels.pad1(self._compose_gid(key_layouts, n), nb),
                lrows)

        # ---- run --------------------------------------------------------
        def run():
            if not plan.group_by:
                out_keys = []
                if mesh is not None:
                    # partial->final over the mesh, and STILL batchable:
                    # the stacked variant vmaps B queries over the N-shard
                    # program (B x N in one dispatch)
                    from ..ops import shardops
                    out_aggs, first_orig = \
                        shardops.fused_scalar_aggregate_sharded(
                            mesh, dev_cols, specs, progs, n, nb,
                            mask_spec, program_key=program_key,
                            params=params, batchable=True)
                else:
                    # batchable: THE single-shot dispatch cross-query
                    # micro-batching coalesces (ops/batching.py) —
                    # blockwise / passthrough variants stay solo
                    out_aggs, first_orig = kernels.fused_scalar_aggregate(
                        dev_cols, specs, progs, n, nb, mask_spec,
                        program_key=program_key, params=params,
                        batchable=True)
            else:
                if mesh is not None:
                    present, out_aggs, first_orig = \
                        kernels.fused_segment_aggregate_sharded(
                            mesh, dev_cols, gid_dev, n_segments, specs,
                            progs, n, mask_spec, program_key=program_key,
                            params=params)
                elif self._can_device_passthrough(plan, slots,
                                                  key_layouts) \
                        and not _batching_active():
                    # a live batch round prefers the batchable fused path
                    # below: members must park (collect) and consume
                    # (replay) along the SAME route, and the keep variant's
                    # per-member device assembly cannot ride a stacked
                    # dispatch
                    ids, live, out_aggs_d, np_, ob = \
                        kernels.fused_segment_aggregate_keep(
                            dev_cols, gid_dev, n_segments, specs, progs,
                            mask_spec, program_key=program_key,
                            params=params)
                    return self._assemble_device_output(
                        plan, slots, key_layouts, ids, live, out_aggs_d,
                        np_)
                else:
                    present, out_aggs, first_orig = \
                        kernels.fused_segment_aggregate(
                            dev_cols, gid_dev, n_segments, specs, progs, n,
                            mask_spec, program_key=program_key,
                            params=params, batchable=True)
                out_keys = self._decode_present(present, key_layouts)
            with _obs.span("exec.rows") as sp:
                out = self._assemble_output(chk, plan, slots, out_keys,
                                            out_aggs, first_orig,
                                            [l[3] for l in key_layouts])
                if sp is not None:
                    sp.args["rows"] = out.num_rows()
                return out
        return run

    def _fused_blockwise(self, chk, rep, child, filters, specs,
                         arg_exprs, slots, key_layouts, n_segments: int,
                         n: int, budget: int, fmask=None):
        """Block-wise fused aggregation (SURVEY §5.7 long-context
        analogue; reference chunked iteration + RequiredRows): row blocks
        of `budget` upload transiently (NOT replica-memoized — the whole
        point is the table does not fit), the fused segment/scalar kernel
        reduces each block on device, and per-segment partial states
        (sum/count add, min/max fold, first-row min, presence union)
        carry on host between blocks — the aggregate's partial/final mode
        split applied across TIME instead of across workers.

        PIPELINED: block staging (slice + pad + H2D enqueue) runs on the
        BlockPipeline thread while the device reduces the previous block
        and the main thread folds its partials — host work and device
        work overlap instead of alternating (tidb_pipeline_depth /
        TINYSQL_PIPELINE_DEPTH=0 restores the serial order; the fold
        order is block order either way, so results are identical)."""
        from ..ops.exprjit import ParamTable
        from .devpipe import BlockPipeline, pipeline_depth
        jn = kernels.jnp()
        # host filter mask over the full table; reuse the caller's when
        # it already folded one (the dev-mask path leaves it None)
        if fmask is None and filters:
            fmask = _fold_filter_masks(child, rep, chk, filters)
        # argument programs: params-compiled so a changed literal reuses
        # the block kernel
        pt = ParamTable()
        progs, program_key = _lower_agg_args(arg_exprs, pt)
        params = pt.arrays()
        needed = set()
        for a in arg_exprs:
            if isinstance(a, tuple):
                needed.add((a[1], "mask"))
            elif a is not None:
                for c in a.collect_columns():
                    needed.add((c.index, "full"))
        # eligibility BEFORE the pipeline spins up: a string column in a
        # compute expression bails the whole path, never a single block
        for idx, kind in needed:
            v = chk.columns[idx].values()
            if kind == "full" and (v.dtype == object or v.dtype.kind == "U"):
                return None
        gid_full = self._compose_gid(key_layouts, n) if key_layouts \
            else None
        ns = n_segments if key_layouts else 1
        bb = kernels.bucket(budget)
        seen = np.zeros(ns, dtype=bool)
        first_acc = np.full(ns, np.iinfo(np.int64).max, dtype=np.int64)
        acc: list = [None] * len(specs)

        def ensure_acc(i, kind, dtype):
            if acc[i] is not None:
                return acc[i]
            if kind in ("count_star", "count", "sum", "sum0"):
                av = np.zeros(ns, dtype=dtype)
            elif kind == "min":
                av = np.full(ns, np.inf if dtype == np.float64
                             else np.iinfo(np.int64).max, dtype=dtype)
            else:
                av = np.full(ns, -np.inf if dtype == np.float64
                             else np.iinfo(np.int64).min, dtype=dtype)
            acc[i] = (av, np.ones(ns, dtype=bool))
            return acc[i]

        def stage(start):
            """Host half of one block: slice, pad, ENQUEUE the uploads.
            Runs on the pipeline thread while the device reduces the
            previous block (no host syncs here — qlint TS106)."""
            end = min(start + budget, n)
            m_rows = end - start
            dev_cols = [None] * len(chk.columns)
            for idx, kind in needed:
                col = chk.columns[idx]
                v = col.values()
                m_ = col.null_mask()
                if v.dtype == object or v.dtype.kind == "U":
                    dv = None  # mask-only slot (COUNT over a string col)
                else:
                    dv = kernels.h2d_pad(v[start:end], bb)
                dn = kernels.h2d_pad(m_[start:end], bb, True)
                if dev_cols[idx] is None or dv is not None:
                    dev_cols[idx] = (dv, dn)
            bmask = np.zeros(bb, dtype=bool)
            bmask[:m_rows] = fmask[start:end] if fmask is not None \
                else True
            mask_spec = ("host", kernels.h2d(bmask))
            gid_b = kernels.h2d_pad(gid_full[start:end], bb) \
                if key_layouts else None
            return start, m_rows, dev_cols, mask_spec, gid_b

        t_pipe = time.time()
        dispatch_s = drain_s = 0.0
        pipe = BlockPipeline(stage, range(0, n, budget),
                             depth=pipeline_depth(self.ctx.session_vars))
        for start, m_rows, dev_cols, mask_spec, gid_b in pipe:
            t0 = time.time()
            if key_layouts:
                present, outs, first = kernels.fused_segment_aggregate(
                    dev_cols, gid_b, ns, specs, progs, m_rows, mask_spec,
                    program_key=program_key, params=params)
            else:
                # scalar contract (_unpack_scalar_agg): zero-or-one-row
                # arrays; an empty block contributes nothing
                outs, first = kernels.fused_scalar_aggregate(
                    dev_cols, specs, progs, m_rows, bb, mask_spec,
                    program_key=program_key, params=params)
                present = np.zeros(len(first), dtype=np.int64)
                outs = [(np.asarray(v_), np.asarray(m_))
                        for v_, m_ in outs]
            dispatch_s += time.time() - t0
            t0 = time.time()
            if len(present) == 0:
                continue
            seen[present] = True
            first_acc[present] = np.minimum(first_acc[present],
                                            np.asarray(first) + start)
            for i, ((v_, m_), (kind, _)) in enumerate(zip(outs, specs)):
                v_ = np.asarray(v_)
                m_ = np.asarray(m_)
                live = ~m_
                if not live.any():
                    continue
                av, am = ensure_acc(i, kind, v_.dtype)
                ids = np.asarray(present)[live]
                vv = v_[live]
                if kind in ("count_star", "count", "sum", "sum0"):
                    av[ids] += vv
                elif kind == "min":
                    av[ids] = np.minimum(av[ids], vv)
                else:
                    av[ids] = np.maximum(av[ids], vv)
                am[ids] = False
            drain_s += time.time() - t0
        ps = pipe.stats()
        kernels.pipe_record(blocks=ps["blocks"], stage_s=ps["stage_s"],
                            dispatch_s=dispatch_s, drain_s=drain_s,
                            wall_s=time.time() - t_pipe,
                            depth_hwm=ps["depth_hwm"])
        if self.plan.group_by:
            present_ids = np.nonzero(seen)[0]
        else:
            # a scalar aggregate over zero rows still returns one row
            # (COUNT=0, SUM=NULL)
            present_ids = np.arange(1)
            if not seen[0]:
                first_acc[0] = 0
        out_aggs = []
        for i, (kind, _) in enumerate(specs):
            if acc[i] is None:
                dt = np.int64 if kind != "sum" else np.float64
                av = np.zeros(ns, dtype=dt)
                am = np.ones(ns, dtype=bool)
                if kind in ("count_star", "count", "sum0"):
                    am = np.zeros(ns, dtype=bool)  # COUNT of nothing = 0
                acc[i] = (av, am)
            av, am = acc[i]
            if kind in ("count_star", "count", "sum0"):
                am = np.zeros_like(am)  # counts are never NULL
            out_aggs.append((av[present_ids], am[present_ids]))
        out_keys = self._decode_present(present_ids, key_layouts) \
            if key_layouts else []
        first_orig = np.where(
            first_acc[present_ids] == np.iinfo(np.int64).max, 0,
            first_acc[present_ids])
        return self._assemble_output(chk, self.plan, slots, out_keys,
                                     out_aggs, first_orig,
                                     [l[3] for l in key_layouts])

    def _mesh_if_enabled(self, nb: int):
        """Multi-chip mesh for the sharded aggregate when the session asks
        for it (SET @@tidb_mesh_parallel = 1) and the bucket divides over
        the devices (power-of-two buckets over power-of-two meshes)."""
        return _mesh_for(self.ctx, nb, self.plan)

    @staticmethod
    def _rep_key_codes(rep, e, chk, slot_id):
        """(codes np[int64], card, base, decode) memoized on the replica,
        keyed by the column's stable id (NOT the query-local offset)."""
        idx = e.index
        col = chk.columns[idx]
        v = col.values()
        null = col.null_mask()
        is_string = v.dtype == object or v.dtype.kind == "U"
        uns = (not is_string and v.dtype == np.int64
               and getattr(e.ret_type, "is_unsigned", False))
        if is_string:
            # shared with the filter rewrite: one dictionary per column
            return _rep_string_dict(rep, slot_id, chk, idx)

        def build():
            w = (v ^ np.int64(-2**63)) if uns else v
            if w.dtype != np.int64:
                return None
            nn = w[~null]
            if len(nn) == 0:
                return (np.zeros(len(w), dtype=np.int64), 0, 0, None)
            vmin, vmax = int(nn.min()), int(nn.max())
            card = vmax - vmin + 1
            if card > kernels.seg_limit(len(w)):
                return None
            codes = np.where(null, card, w - vmin).astype(np.int64)
            return codes, card, vmin, None
        return rep.memo(("keycodes", slot_id, is_string, uns), build)

    @staticmethod
    def _compose_gid(key_layouts, n: int) -> np.ndarray:
        gid = np.zeros(n, dtype=np.int64)
        for codes, card, _, _ in key_layouts:
            gid = gid * (card + 1) + codes
        return gid

    @staticmethod
    def _decode_present(present, key_layouts):
        out_keys = []
        strides = []
        s = 1
        for _, card, _, _ in reversed(key_layouts):
            strides.append(s)
            s *= card + 1
        strides.reverse()
        for (codes, card, base, decode), stride in zip(key_layouts, strides):
            code = (present // stride) % (card + 1)
            is_null = code == card
            vals = np.where(is_null, 0, code + base)
            out_keys.append((vals.astype(np.int64), is_null))
        return out_keys

    def next(self) -> Optional[Chunk]:
        if self._done:
            return None
        self._done = True
        plan = self.plan
        # memory-adaptive aggregation: under spill pressure the fused
        # whole-table paths step aside and the generic path below runs
        # its partitioned spill route (grouped aggregates only — scalar
        # aggregate state is O(1) and never worth spilling)
        sctx = None
        if plan.group_by:
            # per-row partition payload: gid + rid + each arg's
            # (value, null) pair
            row_bytes = 16 + sum(9 for _ in plan.aggs) * 2
            sctx = _maybe_spill_ctx(self.ctx,
                                    _est_rows_of(plan.children[0]), 0,
                                    row_bytes, "agg")
        if sctx is None:
            fused = self._try_fused_device()
            if fused is not None:
                return fused
        chk, filter_mask = self._raw_replica_input(compact=sctx is None)
        if chk is None:
            soft = sctx is not None
            chk = _drain_chunk(self.children[0],
                               self.children[0].field_types(), soft=soft)
            if soft:
                from ..utils import memory as _memory
                with _memory.soft_scope():
                    chk = chk.compact()
            else:
                chk = chk.compact()
        n = chk.full_rows()

        # ---- keys (dictionary-encode strings) -------------------------
        keys = [_encode_key(e, chk) for e in plan.group_by]
        key_cols = [(v, m) for v, m, _ in keys]

        # ---- agg specs --------------------------------------------------
        # device does count/sum/min/max; avg = sum+count pair;
        # first_row is gathered host-side by representative row id
        specs: List[Tuple[str, bool]] = []
        arg_cols: List[Tuple[np.ndarray, np.ndarray]] = []
        slots: List[tuple] = []  # how to produce each desc's result

        def add_arg(e, cast_real=False, order_map=False,
                    null_only=False) -> bool:
            """Returns True when the arg was XOR-sign-bit mapped (unsigned
            min/max ordering) so the caller can un-map the result."""
            v, m = e.vec_eval(chk)
            if null_only or v.dtype == object or v.dtype.kind == "U":
                # COUNT only consumes the null mask; string values (and any
                # non-numeric dtype) must not reach the device
                v = np.zeros(len(m), dtype=np.int64)
            uns = (e.eval_type is EvalType.INT
                   and getattr(e.ret_type, "is_unsigned", False))
            was_mapped = False
            if cast_real and v.dtype != np.float64:
                r = v.astype(np.float64)
                if uns and v.dtype == np.int64:
                    # unwrap wrapped uint64 into its real value
                    r = np.where(v < 0, r + 2.0**64, r)
                v = r
            elif order_map and uns and v.dtype == np.int64:
                # min/max compare on device: XOR maps unsigned order onto
                # signed int64 order; un-mapped in agg_result
                v = v ^ np.int64(-2**63)
                was_mapped = True
            arg_cols.append((v, m))
            return was_mapped

        from ..expression.aggregation import AggMode
        for d in plan.aggs:
            # FINAL mode merges PARTIAL states (agg pushdown through join):
            # count partials SUM; avg partials are a (sum, count) column
            # pair; sum/min/max/first_row merge with their own op
            if d.mode is AggMode.FINAL and d.name == AGG_COUNT:
                specs.append(("sum0", True))  # merged COUNT: 0, not NULL
                add_arg(d.args[0])
                slots.append(("dev", len(specs) - 1))
            elif d.mode is AggMode.FINAL and d.name == AGG_AVG:
                specs.append(("sum", True))
                add_arg(d.args[0], cast_real=True)
                specs.append(("sum", True))
                add_arg(d.args[1])
                slots.append(("avg", len(specs) - 2, len(specs) - 1))
            elif d.name == AGG_COUNT:
                from ..expression import Constant
                a = d.args[0]
                if isinstance(a, Constant) and a.value is not None:
                    specs.append(("count_star", False))
                    slots.append(("dev", len(specs) - 1))
                else:
                    specs.append(("count", True))
                    add_arg(a, null_only=True)
                    slots.append(("dev", len(specs) - 1))
            elif d.name == AGG_SUM:
                specs.append(("sum", True))
                add_arg(d.args[0],
                        cast_real=d.ret_type.eval_type is EvalType.REAL)
                slots.append(("dev", len(specs) - 1))
            elif d.name == AGG_AVG:
                specs.append(("sum", True))
                add_arg(d.args[0], cast_real=True)
                specs.append(("count", True))
                add_arg(d.args[0], null_only=True)
                slots.append(("avg", len(specs) - 2, len(specs) - 1))
            elif d.name in (AGG_MAX, AGG_MIN):
                specs.append((("max" if d.name == AGG_MAX else "min"), True))
                was_mapped = add_arg(d.args[0], order_map=True)
                slots.append(("dev_mm", len(specs) - 1, was_mapped))
            elif d.name == AGG_FIRST_ROW:
                slots.append(("first", d.args[0]))
            else:  # pragma: no cover — enforcer gates
                raise ValueError(d.name)

        if not plan.group_by:
            # global aggregate: sort-free masked reductions (sctx is
            # only ever opened under plan.group_by)
            out_keys = []
            out_aggs, first_orig = kernels.scalar_aggregate(
                specs, arg_cols, n, filter_mask=filter_mask)
        else:
            seg = self._try_segment_layout(keys, n)
            if seg is not None:
                # known small cardinality: sort-free segment reductions
                gid, cards, bases, n_segments = seg
                if sctx is None:
                    # reactive re-check: materializing the input above
                    # may have crossed the watermark after the early
                    # (pre-materialization) decision said no
                    sctx = _maybe_spill_ctx(
                        self.ctx, _est_rows_of(plan.children[0]), n,
                        16 + 18 * len(arg_cols), "agg")
                if sctx is not None:
                    # partitioned partial aggregation: groups hash to
                    # partitions whole, partials merge at drain —
                    # per-group accumulation order (and float sums) are
                    # exactly the unpartitioned kernel's
                    from ..ops import spill
                    with sctx:
                        present, out_aggs, first_orig = \
                            spill.partitioned_segment_aggregate(
                                sctx, gid, n_segments, specs, arg_cols,
                                n, filter_mask=filter_mask)
                    sctx = None
                else:
                    present, out_aggs, first_orig = \
                        kernels.segment_group_aggregate(
                            gid, n_segments, specs, arg_cols, n,
                            filter_mask=filter_mask)
                out_keys = []
                strides = []
                s = 1
                for c in reversed(cards):
                    strides.append(s)
                    s *= c + 1
                strides.reverse()
                for i, (c, base) in enumerate(zip(cards, bases)):
                    code = (present // strides[i]) % (c + 1)
                    is_null = code == c
                    vals = np.where(is_null, 0, code + base)
                    out_keys.append((vals.astype(np.int64), is_null))
            else:
                # sort-based grouping (float keys / huge cardinality):
                # no partitioned route — release the unused spill scope
                if sctx is not None:
                    sctx.close()
                out_keys, out_aggs, first_orig = kernels.group_aggregate(
                    key_cols, specs, arg_cols, n, filter_mask=filter_mask)
        return self._assemble_output(chk, plan, slots, out_keys, out_aggs,
                                     first_orig, [d for _, _, d in keys])

    def _can_device_passthrough(self, plan, slots, key_layouts) -> bool:
        """Late-materialization gate (VERDICT r4 next-2): the aggregate's
        output chunk stays device-resident (DeviceColumn) when every
        output can be produced by traced ops — numeric group keys without
        a string decode table or unsigned order-map, and dev/avg/min-max
        slots (first_row gathers host-side by representative row)."""
        if not plan.group_by:
            return False
        try:
            if int(self.ctx.session_vars.get(
                    "tidb_device_passthrough", 1) or 0) == 0:
                return False
        except Exception:
            pass
        for sl in slots:
            if sl[0] == "dev" or sl[0] == "avg":
                continue
            if sl[0] == "dev_mm" and not sl[2]:
                continue
            return False
        for lay, e in zip(key_layouts, plan.group_by):
            if lay[3] is not None:  # string dictionary decode
                return False
            if getattr(e.ret_type, "is_unsigned", False):
                return False
        return True

    def _assemble_device_output(self, plan, slots, key_layouts, ids, live,
                                out_aggs, np_):
        """Device-resident output chunk: ONE jitted program decodes group
        ids back to key values and finishes the slots (avg divide, REAL
        cast), producing bucket-padded (values, null) pairs wrapped as
        DeviceColumns.  Nothing lands on host until a host consumer asks
        (a device join above consumes the pairs directly)."""
        from ..chunk import DeviceColumn
        jn = kernels.jnp()
        ob = int(ids.shape[0])
        strides = []
        s = 1
        for _, card, _, _ in reversed(key_layouts):
            strides.append(s)
            s *= card + 1
        strides.reverse()
        # (card, base, stride) per key ride as RUNTIME params — stats
        # shifts (inserts widening a key's min/max) must not recompile
        # the decode kernel (same rule as the device-mask params)
        lay = np.array([(card, base, stride)
                        for (_, card, base, _), stride
                        in zip(key_layouts, strides)], dtype=np.int64)
        slot_sig = []
        for src, idx in plan.output_map:
            if src == "agg":
                sl = slots[idx]
                real = (plan.aggs[idx].ret_type.eval_type
                        is EvalType.REAL)
                slot_sig.append((sl[0], sl[1],
                                 sl[2] if sl[0] == "avg" else None, real))
            else:
                slot_sig.append(("gb", idx, None, False))
        key = ("devout", ob, len(key_layouts), tuple(slot_sig),
               tuple(str(v.dtype) for v, _ in out_aggs))

        def build():
            def kernel(ids_in, live_in, aggs, lay_in):
                outs = []
                for kind, i, extra, real in slot_sig:
                    if kind == "gb":
                        card = lay_in[i, 0]
                        base = lay_in[i, 1]
                        stride = lay_in[i, 2]
                        code = (ids_in // stride) % (card + 1)
                        nullk = (code == card) | ~live_in
                        outs.append((jn.where(nullk, 0, code + base),
                                     nullk))
                    elif kind == "avg":
                        sv, sm = aggs[i]
                        cv, _ = aggs[extra]
                        outs.append((sv / jn.maximum(cv, 1),
                                     sm | (cv == 0)))
                    else:  # dev / dev_mm (unsigned excluded by the gate)
                        v, m = aggs[i]
                        if real and v.dtype != jn.float64:
                            v = v.astype(jn.float64)
                        outs.append((v, m))
                return outs
            return kernels.counted_jit(kernel)
        fn = progcache.get(key, build)
        outs = fn(ids, live, list(out_aggs), kernels.h2d(lay))
        cols = []
        for (src, idx), (v, m) in zip(plan.output_map, outs):
            ft = (plan.aggs[idx].ret_type if src == "agg"
                  else plan.group_by[idx].ret_type)
            col = DeviceColumn(ft, v, m, np_)
            if src == "gb" and len(key_layouts) == 1:
                # single-key groups: present ids ascend, and id = code =
                # value - base, so live non-null key values ascend — a
                # join building on this column skips its sort
                col.sorted_live = True
            cols.append(col)
        return Chunk.from_columns(cols)

    def _assemble_output(self, chk, plan, slots, out_keys, out_aggs,
                         first_orig, decodes):
        """Materialize the output chunk from kernel results (shared by the
        fused, segment, scalar, and sort-based aggregation paths)."""
        ng = len(first_orig)

        # empty input + no GROUP BY: single default row (COUNT=0, SUM=NULL)
        if ng == 0 and not plan.group_by:
            from .aggfuncs import new_state
            out = Chunk(self.field_types(), cap=1)
            states = [new_state(d) for d in plan.aggs]
            row = []
            for src, idx in plan.output_map:
                row.append(states[idx].result() if src == "agg" else None)
            out.append_row(row)
            return out

        def agg_result(i: int) -> CCol:
            d = plan.aggs[i]
            slot = slots[i]
            if slot[0] in ("dev", "dev_mm"):
                v, m = out_aggs[slot[1]]
                if slot[0] == "dev_mm" and slot[2]:
                    v = v ^ np.int64(-2**63)  # undo unsigned order map
                if d.ret_type.eval_type is EvalType.REAL and v.dtype != np.float64:
                    v = v.astype(np.float64)
                return CCol.from_numpy(d.ret_type, v, m)
            if slot[0] == "avg":
                sv, sm = out_aggs[slot[1]]
                cv, _ = out_aggs[slot[2]]
                cnt = np.maximum(cv, 1)
                return CCol.from_numpy(d.ret_type, sv / cnt, sm | (cv == 0))
            # first_row: gather by representative row id (any type)
            col_expr = slot[1]
            v, m = col_expr.vec_eval(chk)
            return CCol.from_numpy(d.ret_type, v[first_orig], m[first_orig])

        def gb_result(i: int) -> CCol:
            decode = decodes[i]
            e = plan.group_by[i]
            if decode is not None:
                vals = np.empty(ng, dtype=object)
                kvals = out_keys[i][0]
                for r in range(ng):
                    vals[r] = str(decode[kvals[r]])  # np.str_ -> str
                return CCol.from_numpy(e.ret_type, vals, out_keys[i][1])
            kv, km = out_keys[i]
            if (kv.dtype == np.int64 and e.eval_type is EvalType.INT
                    and getattr(e.ret_type, "is_unsigned", False)):
                kv = kv ^ np.int64(-2**63)  # undo the unsigned order map
            return CCol.from_numpy(e.ret_type, kv, km)

        cols = []
        for src, idx in plan.output_map:
            cols.append(agg_result(idx) if src == "agg" else gb_result(idx))
        return Chunk.from_columns(cols)


class TPUHashJoinExec(Executor):
    """Equi-join as device sort + searchsorted + expansion (SURVEY §2.11 P4
    TPU counterpart: build via sorted scatter, probe via gather)."""

    def __init__(self, plan: PhysicalHashJoin, left: Executor, right: Executor):
        super().__init__(plan.schema, [left, right])
        self.plan = plan
        self._done = False

    def open(self, ctx):
        super().open(ctx)
        self._done = False

    def _side_input(self, i: int, side_conds, compact: bool = True):
        """(chunk, mask, replica): replica-backed readers keep RAW rows
        with scan and side filters folded into a mask; other children
        materialize compacted with side conds applied.  ``compact=False``
        (spill mode) keeps selective filters as masks — the partitioned
        match takes validity masks directly, and the compaction copy is
        charged working set the quota is trying to bound."""
        ex = self.children[i]
        chk, mask, rep = _take_replica_masked(ex, side_conds)
        if chk is not None:
            if compact:
                chk, mask = _compact_if_selective(chk, mask)
            return chk, mask, (rep if mask is not None else None)
        # compact=False == spill mode: this materialization is the very
        # transient the partitioner is about to take over, so its copies
        # charge soft (a cold scan larger than the quota must not die
        # before the spill layer sees a single row)
        chk = _child_input(ex, soft=not compact)
        if side_conds:
            m = vectorized_filter(side_conds, chk)
            chk.set_sel(np.nonzero(m)[0])
            if compact:
                chk = chk.compact()
            else:
                from ..utils import memory as _memory
                with _memory.soft_scope():
                    chk = chk.compact()
        return chk, None, None

    def next(self) -> Optional[Chunk]:
        if self._done:
            return None
        self._done = True
        plan = self.plan
        if plan.tp in ("semi", "anti"):
            return self._semi_next()
        outer = plan.tp == "left"
        # Outer join: ON-clause left conds decide MATCHING (failing outer
        # rows null-extend), so they must NOT fold into lvalid (the kernel
        # drops invalid rows).  Instead poison the key null-mask: a NULL
        # key matches nothing, and the outer path emits unmatched valid
        # rows once with right index -1.
        on_left = plan.left_conditions if outer else []
        right_unique = getattr(plan, "right_unique", False)
        left_unique = getattr(plan, "left_unique", False)
        probe_side = 1 if (left_unique and plan.tp == "inner"
                           and not right_unique) else 0
        # memory-adaptive spill decision BEFORE materializing the sides:
        # in spill mode selective filters stay masks over zero-copy
        # replica views instead of charged compaction copies.  The
        # estimate prices BOTH sides (the join materializes both)
        est = _est_rows_of(plan.children[0]) + _est_rows_of(
            plan.children[1])
        sctx = _maybe_spill_ctx(self.ctx, est, 0, _JOIN_ROW_BYTES,
                                "join")
        lchk, lmask, lrep = self._side_input(
            0, [] if on_left else plan.left_conditions,
            compact=sctx is None)
        rchk, rmask, rrep = self._side_input(
            1, plan.right_conditions, compact=sctx is None)
        if sctx is None:
            # reactive re-check: materialization may have crossed the
            # watermark the early (estimate-driven) decision missed
            sctx = _maybe_spill_ctx(
                self.ctx, est,
                lchk.full_rows() + rchk.full_rows(),
                _JOIN_ROW_BYTES, "join")
        # block-wise probe streaming (SURVEY §5.7; VERDICT r4 next-3):
        # when the PROBE side exceeds tidb_device_block_rows, its key
        # column uploads transiently per block against the resident build
        # structure — the table never becomes fully device-resident
        budget = _block_budget(self.ctx.session_vars)
        probe_chk = lchk if probe_side == 0 else rchk
        stream = (budget > 0 and probe_chk.full_rows() > budget
                  and sctx is None)

        # multi-key equi-joins ride ONE composite int64 lane (joint
        # factorization over both sides — collision-free by
        # construction), then the single-key kernels apply unchanged
        composite = len(plan.left_keys) > 1
        if composite:
            stream = False

        from .devpipe import BlockPipeline, pipeline_depth
        depth = pipeline_depth(self.ctx.session_vars)

        def keys_of(side, expr, chk, rep):
            if stream and side == probe_side:
                v, m = expr.vec_eval(chk)  # host: no full-column upload
                return np.asarray(v), np.asarray(m)
            return self._key_arrays(expr, chk, rep, side)

        key_exprs = (plan.left_keys[0], plan.right_keys[0])
        side_chks = (lchk, rchk)
        side_reps = (lrep, rrep)
        build_side = 1 - probe_side
        if composite:
            (lk, lnull), (rk, rnull) = _composite_key_lanes(
                plan.left_keys, lchk, plan.right_keys, rchk)
        elif stream and depth > 0:
            # build-side ingestion overlaps probe staging (the
            # reference's build/probe worker split, join.go:149/:244
            # completed for real): the build keys' replica-memoized
            # uploads run on the pipeline thread while the probe side's
            # key column extracts here
            bpipe = BlockPipeline(
                lambda side: keys_of(side, key_exprs[side],
                                     side_chks[side], side_reps[side]),
                [build_side], depth=1)
            try:
                pk_pair = keys_of(probe_side, key_exprs[probe_side],
                                  side_chks[probe_side],
                                  side_reps[probe_side])
                bk_pair = list(bpipe)[0]  # drain: joins the thread
            finally:
                bpipe.close()  # probe failure must not leak the stager
            if probe_side == 0:
                (lk, lnull), (rk, rnull) = pk_pair, bk_pair
            else:
                (lk, lnull), (rk, rnull) = bk_pair, pk_pair
        else:
            lk, lnull = keys_of(0, key_exprs[0], lchk, lrep)
            rk, rnull = keys_of(1, key_exprs[1], rchk, rrep)
        if on_left:
            on_mask = vectorized_filter(on_left, lchk)
            # poison only the NULL mask (values may stay replica-memoized
            # on device); a padded device mask re-lands on host, padding
            # rows are already null=True
            lnull = np.asarray(lnull)
            if lnull.shape[0] != on_mask.shape[0]:
                fail = np.zeros(lnull.shape[0], dtype=bool)
                fail[:on_mask.shape[0]] = ~on_mask
                lnull = lnull | fail
            else:
                lnull = lnull | ~on_mask
        if lk.dtype != rk.dtype:
            lk = np.asarray(lk).astype(np.float64)
            rk = np.asarray(rk).astype(np.float64)
        def stream_match(fn, pk, pn, n_probe, pmask, bkey, n_build,
                         bmask, **kw):
            """Probe-block loop: fn per block of `budget` rows with the
            block's validity slice; probe-side indices re-base by the
            block start.  Stable block shapes = one compiled program.

            PIPELINED: the staging thread slices the next probe block
            and pre-uploads its padded key arrays) while the current
            block's match executes; results concatenate in block order, so
            depth 0 (synchronous) is byte-identical."""

            def stage(s_):
                e_ = min(s_ + budget, n_probe)
                m = e_ - s_
                blk = kernels.bucket(max(m, 1))
                kv = kernels.h2d_pad(pk[s_:e_], blk)
                kn = kernels.h2d_pad(pn[s_:e_], blk, True)
                pm = None if pmask is None else pmask[s_:e_]
                return s_, (kv, kn), m, pm

            pis, bis = [], []
            t_pipe = time.time()
            dispatch_s = 0.0
            pipe = BlockPipeline(stage, range(0, n_probe, budget),
                                 depth=depth)
            for s_, kpair, m, pm in pipe:
                t0 = time.time()
                pi_b, bi_b = fn(kpair, m, bkey, n_build, lvalid=pm,
                                rvalid=bmask, **kw)
                dispatch_s += time.time() - t0
                pis.append(pi_b + s_)
                bis.append(bi_b)
            ps = pipe.stats()
            kernels.pipe_record(blocks=ps["blocks"],
                                stage_s=ps["stage_s"],
                                dispatch_s=dispatch_s,
                                wall_s=time.time() - t_pipe,
                                depth_hwm=ps["depth_hwm"])
            if not pis:
                z = np.empty(0, dtype=np.int64)
                return z, z
            return np.concatenate(pis), np.concatenate(bis)

        # memory-adaptive hybrid hash join (ops/spill.py): under quota
        # pressure (or spillForceAll) the build side partitions by key
        # hash with cold partitions in the host spill store; probe rows
        # route to their partition; overflowing partitions recursively
        # repartition.  Output order is the unpartitioned kernels' exact
        # contract, so the branch is transparent to everything above.
        if sctx is not None:
            li, ri = self._spill_join(
                sctx, (lk, lnull), (rk, rnull), lchk, rchk, lmask, rmask,
                probe_side, right_unique, left_unique, outer)
        elif right_unique:
            # unique build side: expansion-free probe, no size sync
            bs = (not composite
                  and self._sorted_build(plan.right_keys[0], rchk))
            if stream:
                li, ri = stream_match(
                    kernels.unique_join_match, lk, lnull,
                    lchk.full_rows(), lmask, (rk, rnull),
                    rchk.full_rows(), rmask,
                    outer=(plan.tp == "left"), build_sorted=bs)
            else:
                out = None
                mesh = _mesh_for(
                    self.ctx, kernels.bucket(max(lchk.full_rows(), 1)),
                    plan)
                if mesh is not None and isinstance(lk, np.ndarray) \
                        and isinstance(rk, np.ndarray):
                    # partitioned build/probe over the mesh (shard =
                    # spill partition); None (skew, odd dtypes) falls
                    # through to the single-device kernel
                    from ..ops import shardops
                    out = shardops.unique_join_match_sharded(
                        mesh, (lk, lnull), lchk.full_rows(),
                        (rk, rnull), rchk.full_rows(),
                        outer=(plan.tp == "left"),
                        lvalid=lmask, rvalid=rmask)
                if out is not None:
                    li, ri = out
                else:
                    li, ri = kernels.unique_join_match(
                        (lk, lnull), lchk.full_rows(), (rk, rnull),
                        rchk.full_rows(), outer=(plan.tp == "left"),
                        lvalid=lmask, rvalid=rmask, build_sorted=bs)
        elif left_unique and plan.tp == "inner":
            bs = (not composite
                  and self._sorted_build(plan.left_keys[0], lchk))
            if stream:
                ri, li = stream_match(
                    kernels.unique_join_match, rk, rnull,
                    rchk.full_rows(), rmask, (lk, lnull),
                    lchk.full_rows(), lmask, outer=False,
                    build_sorted=bs)
            else:
                out = None
                mesh = _mesh_for(
                    self.ctx, kernels.bucket(max(rchk.full_rows(), 1)),
                    plan)
                if mesh is not None and isinstance(lk, np.ndarray) \
                        and isinstance(rk, np.ndarray):
                    from ..ops import shardops
                    out = shardops.unique_join_match_sharded(
                        mesh, (rk, rnull), rchk.full_rows(),
                        (lk, lnull), lchk.full_rows(), outer=False,
                        lvalid=rmask, rvalid=lmask)
                if out is not None:
                    ri, li = out
                else:
                    ri, li = kernels.unique_join_match(
                        (rk, rnull), rchk.full_rows(), (lk, lnull),
                        lchk.full_rows(), outer=False,
                        lvalid=rmask, rvalid=lmask, build_sorted=bs)
        elif stream:
            li, ri = stream_match(
                kernels.join_match, lk, lnull, lchk.full_rows(), lmask,
                (rk, rnull), rchk.full_rows(), rmask,
                outer=(plan.tp == "left"))
        else:
            li, ri = kernels.join_match((lk, lnull), lchk.full_rows(),
                                        (rk, rnull), rchk.full_rows(),
                                        outer=(plan.tp == "left"),
                                        lvalid=lmask, rvalid=rmask)
        # gather output columns — LAZILY for inner joins: a parent join
        # or TopN composes the index chain and each payload column lands
        # once, at the final (smallest) cardinality
        from ..chunk.column import LazyTakeColumn
        unmatched = ri < 0
        ri_safe = np.where(unmatched, 0, ri)
        lazy = plan.tp != "left" and not unmatched.any()
        cols: List[CCol] = []
        for c in lchk.columns:
            cols.append(LazyTakeColumn(c, li) if lazy else c.take(li))
        for c in rchk.columns:
            if lazy:
                cols.append(LazyTakeColumn(c, ri_safe))
                continue
            taken = c.take(ri_safe)
            if unmatched.any():
                taken.null_mask()[unmatched] = True
            cols.append(taken)
        out = Chunk.from_columns(cols)
        if plan.other_conditions:
            mask = vectorized_filter(plan.other_conditions, out)
            if plan.tp == "left":
                # failed other-cond on matched rows -> NULL-extended row
                # must survive only if NO match passes; handled by
                # re-checking per left row
                keep = self._outer_fixup(li, ri, mask, lchk, out)
                out.set_sel(np.nonzero(keep)[0])
            else:
                out.set_sel(np.nonzero(mask)[0])
            out = out.compact()
        return out if out.num_rows() else None

    def _outer_fixup(self, li, ri, mask, lchk, out) -> np.ndarray:
        """LEFT JOIN + other-conditions: a left row keeps exactly its
        passing matches, or one NULL-extended row if none pass."""
        n_left = lchk.num_rows()
        passing = np.zeros(n_left, dtype=bool)
        matched_rows = ri >= 0
        np.logical_or.at(passing, li[matched_rows & mask],
                         True)
        keep = np.zeros(len(li), dtype=bool)
        # keep matched rows that pass
        keep |= matched_rows & mask
        # left rows with no passing match: keep ONE row, null-extended
        no_pass = ~passing
        seen = set()
        for idx in range(len(li)):
            l = li[idx]
            if no_pass[l] and l not in seen:
                seen.add(l)
                keep[idx] = True
                # null-extend the right side of this surviving row
                for c in out.columns[len(lchk.columns):]:
                    c.null_mask()[idx] = True
        return keep


    def _semi_next(self) -> Optional[Chunk]:
        """Semi / anti join on device: a membership test over the build
        (subquery) side via kernels.semi_join_match — the sort +
        searchsorted machinery the join kernels already ride — emitting
        surviving LEFT rows only.  Under quota pressure the membership
        derives from the spilled partitioned inner join instead (matches
        are partition-local under key hashing, so presence/absence is
        decidable per partition)."""
        from ..chunk.column import LazyTakeColumn
        plan = self.plan
        anti = plan.tp == "anti"
        null_aware = anti and getattr(plan, "null_aware", False)
        est = _est_rows_of(plan.children[0]) + _est_rows_of(
            plan.children[1])
        sctx = _maybe_spill_ctx(self.ctx, est, 0, _JOIN_ROW_BYTES,
                                "join")
        lchk, lmask, lrep = self._side_input(0, plan.left_conditions,
                                             compact=sctx is None)
        rchk, rmask, rrep = self._side_input(1, plan.right_conditions,
                                             compact=sctx is None)
        if sctx is None:
            sctx = _maybe_spill_ctx(
                self.ctx, est,
                lchk.full_rows() + rchk.full_rows(),
                _JOIN_ROW_BYTES, "join")
        mesh = None if sctx is not None or len(plan.left_keys) > 1 else \
            _mesh_for(self.ctx, kernels.bucket(max(lchk.full_rows(), 1)),
                      plan)
        # partitioned semijoin scatters HOST key lanes with the spill
        # partitioner — device-resident keys would round-trip anyway
        host_keys = mesh is not None
        if len(plan.left_keys) > 1:
            (lk, lnull), (rk, rnull) = _composite_key_lanes(
                plan.left_keys, lchk, plan.right_keys, rchk)
        else:
            lk, lnull = self._key_arrays(plan.left_keys[0], lchk, lrep,
                                         0, host_keys=host_keys)
            rk, rnull = self._key_arrays(plan.right_keys[0], rchk, rrep,
                                         1, host_keys=host_keys)
        if getattr(lk, "dtype", None) != getattr(rk, "dtype", None) \
                and isinstance(lk, np.ndarray) \
                and isinstance(rk, np.ndarray):
            lk = np.asarray(lk).astype(np.float64)
            rk = np.asarray(rk).astype(np.float64)
        if sctx is not None:
            li = self._spill_semi(sctx, (lk, lnull), (rk, rnull), lchk,
                                  rchk, lmask, rmask, anti, null_aware)
        else:
            li = None
            if mesh is not None:
                from ..ops import shardops
                li = shardops.semi_join_match_sharded(
                    mesh, (lk, lnull), lchk.full_rows(), (rk, rnull),
                    rchk.full_rows(), anti=anti, null_aware=null_aware,
                    lvalid=lmask, rvalid=rmask)
            if li is None:
                li = kernels.semi_join_match(
                    (lk, lnull), lchk.full_rows(), (rk, rnull),
                    rchk.full_rows(), anti=anti, null_aware=null_aware,
                    lvalid=lmask, rvalid=rmask)
        if len(li) == 0:
            return None
        cols: List[CCol] = [LazyTakeColumn(c, li) for c in lchk.columns]
        return Chunk.from_columns(cols)

    def _spill_semi(self, sctx, lpair, rpair, lchk, rchk, lmask, rmask,
                    anti: bool, null_aware: bool) -> np.ndarray:
        """Spill-mode membership: the empty/NULL-set ladder decides
        host-side; otherwise the partitioned inner join supplies matched
        probe rows (equal keys colocate per partition, so membership is
        partition-local) and semi/anti derive from the matched set."""
        from ..ops import spill
        lk = np.asarray(lpair[0])
        lnull = np.asarray(lpair[1], dtype=bool)
        rk = np.asarray(rpair[0])
        rnull = np.asarray(rpair[1], dtype=bool)
        n_left = lchk.full_rows()
        n_right = rchk.full_rows()
        lv = np.ones(n_left, dtype=bool) if lmask is None \
            else np.asarray(lmask[:n_left], dtype=bool)
        rv = np.ones(n_right, dtype=bool) if rmask is None \
            else np.asarray(rmask[:n_right], dtype=bool)
        if int(rv.sum()) == 0:
            sctx.close()
            keep = lv if anti else np.zeros(n_left, dtype=bool)
            return np.nonzero(keep)[0].astype(np.int64)
        if anti and null_aware and bool((rv & rnull[:n_right]).any()):
            sctx.close()
            return np.empty(0, dtype=np.int64)
        unique_build = getattr(self.plan, "right_unique", False)

        def match(pp, n_p, bp, n_b):
            if unique_build:
                return kernels.unique_join_match(pp, n_p, bp, n_b,
                                                 outer=False)
            return kernels.join_match(pp, n_p, bp, n_b, outer=False)

        with sctx:
            mi, _ = spill.partitioned_join(
                sctx, (lk, lnull), n_left, (rk, rnull), n_right, match,
                outer=False, probe_valid=lmask, build_valid=rmask)
        matched = np.zeros(n_left, dtype=bool)
        matched[mi] = True
        if anti:
            keep = lv & ~matched
            if null_aware:
                keep &= ~lnull[:n_left]
        else:
            keep = matched
        return np.nonzero(keep)[0].astype(np.int64)

    def _spill_join(self, sctx, lpair, rpair, lchk, rchk, lmask, rmask,
                    probe_side: int, right_unique: bool,
                    left_unique: bool, outer: bool):
        """Partitioned spill-mode matching: host key arrays (device-
        resident replica keys land once — np.asarray — instead of
        living whole on device), per-partition match through the
        UNCHANGED kernel entry points (the compiled programs and their
        progcache entries are shared with the unpartitioned path)."""
        from ..ops import spill
        lk = np.asarray(lpair[0])
        lnull = np.asarray(lpair[1], dtype=bool)
        rk = np.asarray(rpair[0])
        rnull = np.asarray(rpair[1], dtype=bool)
        unique_build = right_unique if probe_side == 0 else left_unique

        def match(pp, n_p, bp, n_b):
            if unique_build:
                return kernels.unique_join_match(pp, n_p, bp, n_b,
                                                 outer=False)
            return kernels.join_match(pp, n_p, bp, n_b, outer=False)

        with sctx:
            if probe_side == 0:
                return spill.partitioned_join(
                    sctx, (lk, lnull), lchk.full_rows(),
                    (rk, rnull), rchk.full_rows(), match, outer=outer,
                    probe_valid=lmask, build_valid=rmask)
            ri, li = spill.partitioned_join(
                sctx, (rk, rnull), rchk.full_rows(),
                (lk, lnull), lchk.full_rows(), match, outer=False,
                probe_valid=rmask, build_valid=lmask)
            return li, ri

    @staticmethod
    def _sorted_build(key_expr, chk) -> bool:
        """True when the build key column provably ascends among live
        rows (a device-resident single-key aggregate output): the join
        kernel then skips its argsort."""
        from ..chunk import DeviceColumn
        from ..expression import Column as ExprColumn
        if not isinstance(key_expr, ExprColumn):
            return False
        col = chk.columns[key_expr.index]
        return (isinstance(col, DeviceColumn) and col._data is None
                and col.sorted_live)

    def _key_arrays(self, key_expr, chk, rep, side, host_keys=False):
        """Join key (values, null) — for a bare Column over an uncompacted
        replica, PADDED DEVICE arrays memoized on the replica (no re-upload
        per query); device-resident for a DeviceColumn child (an aggregate
        output that never landed on host); numpy otherwise.  `host_keys`
        (the partitioned semijoin) lands keys on host instead."""
        from ..chunk import DeviceColumn
        from ..expression import Column as ExprColumn
        from .executors import TableReaderExec
        if isinstance(key_expr, ExprColumn):
            col = chk.columns[key_expr.index]
            if isinstance(col, DeviceColumn) and col._data is None:
                if host_keys:
                    return key_expr.vec_eval(chk)
                return col.device_pair()
        if rep is not None and isinstance(key_expr, ExprColumn):
            child = self.children[side]
            if isinstance(child, TableReaderExec):
                if host_keys:
                    # the raw replica views are free on host
                    return key_expr.vec_eval(chk)
                ci = child._decode_cols[key_expr.index]
                sid = ci.id if ci is not None else "handle"
                nb = kernels.bucket(max(chk.full_rows(), 1))
                jn = kernels.jnp()
                col = chk.columns[key_expr.index]
                v = col.values()
                m = col.null_mask()
                if v.dtype != object and v.dtype.kind != "U":
                    dv = rep.memo(("devv", sid, nb),
                                  lambda v=v: kernels.h2d_pad(v, nb))
                    dn = rep.memo(("devn", sid, nb),
                                  lambda m=m: kernels.h2d_pad(m, nb, True))
                    return dv, dn
        return key_expr.vec_eval(chk)


class TPUSortExec(Executor):
    def __init__(self, plan: PhysicalSort, child: Executor):
        super().__init__(plan.schema, [child])
        self.plan = plan
        self._out = None

    def open(self, ctx):
        super().open(ctx)
        self._out = None

    def next(self) -> Optional[Chunk]:
        if self._out is None:
            chk = _child_input(self.children[0],
                               soft=_would_spill_here(self.ctx, self.plan))
            n = chk.num_rows()
            if n == 0:
                self._out = iter([])
            else:
                keys = [(_encode_key(e, chk)[:2]) for e, _ in self.plan.by]
                keys = [(v, m) for v, m in keys]
                descs = [d for _, d in self.plan.by]
                row_bytes = sum(np.asarray(v).dtype.itemsize + 1
                                for v, _ in keys) + 8
                sctx = _maybe_spill_ctx(
                    self.ctx, _est_rows_of(self.plan.children[0]), n,
                    row_bytes, "sort")
                if sctx is not None and \
                        _spill_run_rows(sctx, n, row_bytes) >= n:
                    # the whole key set fits one run: an external sort
                    # would just write-and-reload a single run file
                    sctx.close()
                    sctx = None
                budget = _block_budget(self.ctx.session_vars)
                if sctx is not None:
                    # external sort: spilled sorted runs + k-way merge
                    # (exact full-lexsort permutation; ops/spill.py)
                    from ..ops import spill
                    with sctx:
                        perm = spill.external_sort_permutation(
                            sctx, keys, descs, n,
                            _spill_run_rows(sctx, n, row_bytes))
                elif budget > 0 and n > budget:
                    # above the device budget a full ORDER BY sorts on
                    # host (same semantics): whole-key residency would
                    # violate tidb_device_block_rows
                    perm = kernels.host_sort_permutation(keys, descs, n)
                else:
                    perm = None
                    mesh = _mesh_for(self.ctx,
                                     kernels.bucket(max(n, 1)), self.plan)
                    if mesh is not None:
                        # per-shard sort + exact device rank merge;
                        # None (multi-key, unscorable) falls through
                        from ..ops import shardops
                        perm = shardops.sort_permutation_sharded(
                            mesh, keys, descs, n)
                    if perm is None:
                        perm = kernels.sort_permutation(keys, descs, n)
                chk.set_sel(perm)
                self._out = iter([chk.compact()])
        return next(self._out, None)


class TPUTopNExec(Executor):
    def __init__(self, plan: PhysicalTopN, child: Executor):
        super().__init__(plan.schema, [child])
        self.plan = plan
        self._out = None

    def open(self, ctx):
        super().open(ctx)
        self._out = None

    def next(self) -> Optional[Chunk]:
        if self._out is None:
            chk = _child_input(self.children[0],
                               soft=_would_spill_here(self.ctx, self.plan))
            n = chk.num_rows()
            if n == 0:
                self._out = iter([])
            else:
                keys = [(_encode_key(e, chk)[:2]) for e, _ in self.plan.by]
                descs = [d for _, d in self.plan.by]
                k = self.plan.offset + self.plan.count
                row_bytes = sum(np.asarray(v).dtype.itemsize + 1
                                for v, _ in keys) + 8
                sctx = _maybe_spill_ctx(
                    self.ctx, _est_rows_of(self.plan.children[0]), n,
                    row_bytes, "topn")
                if sctx is not None and \
                        _spill_run_rows(sctx, n, row_bytes) >= n:
                    # single-run input: nothing to carry between runs
                    sctx.close()
                    sctx = None
                budget = _block_budget(self.ctx.session_vars)
                if sctx is not None:
                    # run-file top-k: the candidate carry lives in the
                    # spill store between runs (ops/spill.py)
                    from ..ops import spill
                    with sctx:
                        perm = spill.external_topk(
                            sctx, keys, descs, n, k,
                            _spill_run_rows(sctx, n, row_bytes))
                elif budget > 0 and n > budget:
                    perm = self._blockwise_topk(keys, descs, n, k, budget)
                else:
                    perm = None
                    mesh = _mesh_for(self.ctx,
                                     kernels.bucket(max(n, 1)), self.plan)
                    if mesh is not None:
                        # per-shard top-k + replicated tournament merge
                        from ..ops import shardops
                        perm = shardops.top_k_sharded(
                            mesh, keys, descs, n, k)
                    if perm is None:
                        perm = kernels.top_k(keys, descs, n, k)
                sel = perm[self.plan.offset:]
                chk.set_sel(sel)
                self._out = iter([chk.compact()] if len(sel) else [])
        return next(self._out, None)

    @staticmethod
    def _blockwise_topk(keys, descs, n: int, k: int,
                        budget: int) -> np.ndarray:
        """Block-wise top-k (SURVEY §5.7; VERDICT r4 next-3): each block
        of `budget` rows yields its local top-k candidates (device
        buffers bounded by the block bucket), the carried candidate set
        merges with each block's winners, and a final top-k over the
        <= 2k survivors picks the answer — partial TopN state across
        TIME, the streaming analogue of the reference's per-region TopN
        (mocktikv/topn.go) merged at the root (task.go:392-452)."""
        if 2 * k > budget:
            # the <=2k candidate pools would themselves exceed the device
            # budget: selection runs fully on host instead
            return np.asarray(
                kernels.host_sort_permutation(keys, descs, n)[:k])
        cand = np.empty(0, dtype=np.int64)
        for s_ in range(0, n, budget):
            e_ = min(s_ + budget, n)
            bkeys = [(v[s_:e_], m[s_:e_]) for v, m in keys]
            ids = np.asarray(kernels.top_k(bkeys, descs, e_ - s_,
                                           k)) + s_
            pool = np.concatenate([cand, ids])
            pkeys = [(v[pool], m[pool]) for v, m in keys]
            order = np.asarray(kernels.top_k(pkeys, descs, len(pool), k))
            cand = pool[order]
        return cand


class TPUProjectionExec(Executor):
    """Expression trees fused by XLA into elementwise device kernels."""

    def __init__(self, plan: PhysicalProjection, child: Executor):
        super().__init__(plan.schema, [child])
        self.plan = plan
        self._fn = None
        self._params = None

    def _compiled(self):
        if self._fn is None:
            # shared params-compiled program (ops/progcache): executors
            # are rebuilt per query, so a per-instance @jit wrapper would
            # retrace EVERY query — qlint TS104, the extra-dispatch
            # bug class
            from ..ops.exprjit import (ParamTable, compile_expr_params,
                                       stable_shape_key)
            key = ("proj",) + tuple(stable_shape_key(e)
                                    for e in self.plan.exprs)
            pt = ParamTable()
            fns = [compile_expr_params(e, pt) for e in self.plan.exprs]
            self._params = [kernels.h2d(a) for a in pt.arrays()]

            def build():
                def kernel(cols, params, fns=fns):
                    return [f(cols, params) for f in fns]
                return kernels.counted_jit(kernel)
            self._fn = progcache.get(key, build)
        return self._fn

    def next(self) -> Optional[Chunk]:
        chk = self.children[0].next()
        if chk is None:
            return None
        chk = chk.compact()
        if not chk.columns:
            # zero-column (TableDual) input: host numpy path handles
            # virtual row counts; nothing to gain on device
            from ..chunk import Column as HostCol
            cols = []
            for e, oc in zip(self.plan.exprs, self.plan.schema.columns):
                v, m = e.vec_eval(chk)
                cols.append(HostCol.from_numpy(oc.ret_type, v, m))
            return Chunk.from_columns(cols)
        cols_dev = _marshal(chk)
        outs = self._compiled()(cols_dev, tuple(self._params))
        # ONE counted pull for every output stream — per-pair np.asarray
        # was 2N hidden uncounted downloads (transfer-audit find)
        flat = []
        for v, m in outs:
            flat.extend((v, m))
        host = kernels.d2h_many(flat) if flat else []
        out_cols = []
        for i, oc in enumerate(self.plan.schema.columns):
            out_cols.append(CCol.from_numpy(oc.ret_type, host[2 * i],
                                            host[2 * i + 1]))
        return Chunk.from_columns(out_cols)


class TPUSelectionExec(Executor):
    def __init__(self, plan: PhysicalSelection, child: Executor):
        super().__init__(plan.schema, [child])
        self.plan = plan
        self._fn = None
        self._params = None

    def _compiled(self):
        if self._fn is None:
            # params-compiled program shared at module level: constants
            # ride runtime param slots (exprjit.ParamTable), so queries
            # differing only in literals reuse ONE compiled program — no
            # per-literal cache growth, no jit dispatch-cache miss from a
            # fresh wrapper per query (executors are rebuilt per query).
            from ..ops.exprjit import (ParamTable, compile_expr_params,
                                       stable_shape_key)
            key = ("filter",) + tuple(stable_shape_key(c)
                                      for c in self.plan.conditions)
            pt = ParamTable()
            fns = [compile_expr_params(c, pt) for c in self.plan.conditions]
            self._params = [kernels.h2d(a) for a in pt.arrays()]

            def build():
                jn = kernels.jnp()

                def kernel(cols, params, fns=fns):
                    n = cols[0][0].shape[0] if cols else 0
                    mask = jn.ones((n,), dtype=bool)
                    for f in fns:
                        v, null = f(cols, params)
                        mask = mask & (v != 0) & ~null
                    return mask
                return kernels.counted_jit(kernel)
            self._fn = progcache.get(key, build)
        return self._fn

    def next(self) -> Optional[Chunk]:
        while True:
            chk = self.children[0].next()
            if chk is None:
                return None
            chk = chk.compact()
            if chk.num_rows() == 0:
                continue
            if not chk.columns:
                mask = vectorized_filter(self.plan.conditions, chk)
            else:
                # counted pull: raw np.asarray here was a hidden
                # uncounted d2h on the hot filter loop (DF801)
                mask = kernels.d2h(
                    self._compiled()(_marshal(chk), tuple(self._params)))
            if not mask.any():
                continue
            chk.set_sel(np.nonzero(mask)[0])
            return chk.compact()


def _marshal(chk: Chunk):
    """Chunk columns -> device (values, null) pairs.  String columns are
    never touched by device exprs (enforcer), but must still occupy their
    index slot — pass zeros."""
    jnp = kernels.jnp()
    out = []
    n = chk.num_rows()
    for c in chk.columns:
        v = c.values()
        # uploads count (DF802): raw jnp.asarray bypassed h2d_transfers
        if v.dtype == object:
            out.append((jnp.zeros(n, dtype=jnp.int64),
                        kernels.h2d(c.null_mask())))
        else:
            out.append((kernels.h2d(v), kernels.h2d(c.null_mask())))
    return out


def build_tpu_executor(plan) -> Optional[Executor]:
    """TPU-tier builder.  Subtrees containing a supported join or a
    grouped aggregate compile into a device-resident pipeline (devpipe)
    with the per-operator executors as fallback; lone operators use the
    per-op executors (whose fused paths are already single-program)."""
    from .devpipe import DevPipeExec, _contains_grouped_agg, _contains_join
    if _contains_join(plan) or _contains_grouped_agg(plan):
        return DevPipeExec(plan, _build_tpu_op)
    return _build_tpu_op(plan)


def _build_tpu_op(plan) -> Optional[Executor]:
    ex = _build_tpu_op_inner(plan)
    if ex is not None and getattr(ex, "_obs_plan", None) is None:
        ex._obs_plan = plan  # per-operator stats key (obs/runtime_stats)
    return ex


def _build_tpu_op_inner(plan) -> Optional[Executor]:
    if isinstance(plan, PhysicalHashAgg):
        return TPUHashAggExec(plan, build_executor(plan.children[0], True))
    if isinstance(plan, PhysicalHashJoin):
        # multi-key joins collapse into ONE composite int64 lane (joint
        # factorization) and ride the same single-key kernels
        return TPUHashJoinExec(plan, build_executor(plan.children[0], True),
                               build_executor(plan.children[1], True))
    if isinstance(plan, PhysicalTopN):
        return TPUTopNExec(plan, build_executor(plan.children[0], True))
    if isinstance(plan, PhysicalSort):
        return TPUSortExec(plan, build_executor(plan.children[0], True))
    if isinstance(plan, PhysicalProjection):
        return TPUProjectionExec(plan, build_executor(plan.children[0], True))
    if isinstance(plan, PhysicalSelection):
        return TPUSelectionExec(plan, build_executor(plan.children[0], True))
    return None

"""TPU relational kernels: sort-based group-aggregate, sort-merge equi-join
expansion, multi-key sort, top-k.

TPU-first redesign of the reference's goroutine operators (SURVEY §2.4
note): no pointer-chasing hash tables — grouping and join matching are
sort + segment primitives (`jnp.lexsort`, `jax.ops.segment_*`,
`searchsorted`), which XLA tiles onto the MXU/VPU.  All shapes are padded
to power-of-two buckets so each bucket compiles once (SURVEY §7 "dynamic
shapes vs XLA").

Every kernel takes a `valid` mask so padding rows are inert, and carries
per-column null masks with MySQL semantics (NULLs group together, NULLs
never equi-join, NULL sorts first ASC / last DESC).
"""
from __future__ import annotations

import os
import re
import threading
import time

from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import profiler, progcache
from .. import fail
from ..obs import context as _obs

_jax = None


#: where this is set, JAX keeps its persistent compilation cache there and
#: the engine sets no cache directory in code (the chip tool, CI and
#: operators place the cache from outside through the standard variable)
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# runtime override for the persistent-compile-cache directory (sysvar
# tidb_compile_cache_dir); a dict cell so set_compile_cache_dir never
# races module reloads
_CACHE_DIR_STATE = {"override": None}


def set_compile_cache_dir(path: str) -> bool:
    """Point jax's persistent compilation cache at ``path`` (sysvar
    ``tidb_compile_cache_dir`` / tools/warm.py ``--cache-dir``): bucketed
    kernels then survive process restarts.  Empty path restores the
    resolution chain of :func:`_cache_dir`.  Applies immediately when
    the backend is already initialized.  Returns False, and changes
    nothing, where ``JAX_COMPILATION_CACHE_DIR`` is set: that variable
    wins."""
    if os.environ.get(CACHE_DIR_ENV):
        return False
    # qlint: disable=CC701 -- single GIL-atomic scalar-slot publish; _cache_dir readers tolerate either the old or new override
    _CACHE_DIR_STATE["override"] = str(path) if path else None
    if _jax is not None:
        _jax.config.update("jax_compilation_cache_dir", _cache_dir())
    return True


def _cache_dir() -> str:
    """Persistent compile-cache directory in effect.  Resolution:
    ``JAX_COMPILATION_CACHE_DIR`` > the sysvar override
    (set_compile_cache_dir) > the config file's compile_cache_dir >
    <repo>/.jax_cache.  The path is part of jax's cache key, so it
    depends on nothing that changes within a process: the same before
    and after the backend exists."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    if _CACHE_DIR_STATE["override"]:
        return _CACHE_DIR_STATE["override"]
    from ..config import get_global_config
    cfg = get_global_config().compile_cache_dir
    if cfg:
        return cfg
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def jax():
    """The jax module, configured once: 64-bit types on, persistent
    compile cache placed.  The DEVICE is jax's own choice
    (``JAX_PLATFORMS``, else what it finds): the engine neither probes
    nor pins a platform."""
    global _jax
    if _jax is None:
        import jax as jax_mod
        # engine semantics are int64/float64 (reference: the 3 eval
        # families); the env var is not honored by all builds, so force it
        jax_mod.config.update("jax_enable_x64", True)
        # persistent compile cache: shape buckets recur across runs
        if not os.environ.get(CACHE_DIR_ENV):
            jax_mod.config.update("jax_compilation_cache_dir", _cache_dir())
        jax_mod.config.update(
            "jax_persistent_cache_min_compile_time_secs", 1.0)
        # the span path's two hooks into jax (obs/ imports without it):
        # live spans become profiler annotations, and jax's own
        # trace / lower / load phases count in the span totals
        from ..obs import trace as obs_trace
        obs_trace.bind_profiler(jax_mod.profiler.TraceAnnotation)
        jax_mod.monitoring.register_event_duration_secs_listener(
            obs_trace.on_jax_duration)
        _jax = jax_mod
    return _jax


def jnp():
    return jax().numpy


# device-economics counters (bench diagnosability, VERDICT r2 weak-3):
# every compiled-program dispatch and packed D2H transfer increments
# these, so BENCH json can split engine time from link time per query.
# The pipe_* family is fed by the async block pipeline (devpipe
# BlockPipeline consumers) via pipe_record: per-stage walls for the
# host-staging / device-compute overlap accounting, block count, and the
# staging-queue depth high-water mark (reported as an absolute value by
# stats_delta — a high-water is not a per-interval delta).
STATS = {"dispatches": 0, "d2h_transfers": 0, "d2h_bytes": 0,
         "h2d_transfers": 0, "h2d_bytes": 0,
         "host_dispatches": 0,
         "agg_dense": 0, "agg_sorted": 0, "agg_clustered": 0,
         "agg_span_cut": 0,
         "pipe_dead_cols": 0, "pipe_const_nulls": 0,
         "pipe_joins": 0, "pipe_view_builds": 0, "agg_key_cut": 0,
         "pipe_mesh_views": 0, "agg_key_mesh": 0,
         "mesh_dispatches": 0, "reshard_bytes": 0,
         "mesh_resident_bytes_max": 0, "mesh_resident_bytes_min": 0,
         "device_s": 0.0, "profiled_dispatches": 0,
         "flops": 0.0, "bytes_accessed": 0.0,
         "pipe_blocks": 0, "pipe_stage_s": 0.0, "pipe_dispatch_s": 0.0,
         "pipe_drain_s": 0.0, "pipe_wall_s": 0.0, "pipe_depth_hwm": 0}

#: STATS keys that are high-water marks, not accumulators — declared in
#: the central metric registry so the registry's gauge-vs-counter kinds
#: and the /metrics render share one definition
from ..obs.metrics import GAUGE_STATS_KEYS as _GAUGE_KEYS  # noqa: E402
from ..obs.metrics import HWM_STATS_KEYS as _HWM_KEYS  # noqa: E402

#: guards the global STATS read-modify-writes — sessions and devpipe
#: producer threads increment concurrently
_STATS_MU = threading.Lock()


def stats_add(key: str, n) -> None:
    """THE accumulator write path (qlint OB401 bans direct ``STATS[...]``
    writes outside this module): bumps the process-global counter under
    the lock AND fans the increment out to the active per-query scope +
    the operator whose next() frame is live (obs/context.py), so
    concurrent sessions collect disjoint per-query counters."""
    with _STATS_MU:
        STATS[key] = STATS.get(key, 0) + n
    _obs.record(key, n)


def stats_hwm(key: str, n) -> None:
    """High-water-mark write path: keeps the max, globally and in the
    per-query scope (a deep staging queue in one query must not bleed
    into another's detail)."""
    with _STATS_MU:
        if n > STATS.get(key, 0):
            STATS[key] = n
    _obs.record_hwm(key, n)


def stats_set(key: str, n) -> None:
    """Gauge write path (obs/metrics GAUGE_STATS_KEYS): the value as it
    stands, not an increment — stats_delta reports it whole."""
    with _STATS_MU:
        STATS[key] = n


def host_dispatch(n: int = 1) -> None:
    """Count one HOST-TWIN kernel invocation — the numpy implementations
    that deliberately serve join match / top-k selection / group-by on
    the XLA:CPU backend (host_kernels_ok), where they beat the serial
    device lowerings.  Without this counter a query served entirely by
    twins reports dispatches=0 and is indistinguishable from one that
    silently fell off the accelerated paths (the BENCH_r05 Q3 mystery);
    chip_smoke.py and benchmark/deployments/ require host_dispatches == 0
    on the chip, tools/workload_smoke.py dispatches + host_dispatches > 0."""
    stats_add("host_dispatches", n)


def pipe_overlap_frac(d: dict) -> float:
    """Staging/compute overlap estimate from a counter scope's ``pipe_*``
    walls (global STATS delta, or a per-query ``device_totals()``): busy
    time beyond the pipeline wall is work that ran CONCURRENTLY on the
    stage thread.  THE one formula — bench detail and EXPLAIN ANALYZE
    must agree."""
    pw = d.get("pipe_wall_s", 0.0)
    if not pw or pw <= 0:
        return 0.0
    busy = (d.get("pipe_stage_s", 0.0) + d.get("pipe_dispatch_s", 0.0)
            + d.get("pipe_drain_s", 0.0))
    return max(0.0, busy - pw) / pw


def pipe_record(blocks: int = 0, stage_s: float = 0.0,
                dispatch_s: float = 0.0, drain_s: float = 0.0,
                wall_s: float = 0.0, depth_hwm: int = 0) -> None:
    """Accrue one pipelined run's stage/compute/drain walls into STATS
    (called once per BlockPipeline consumer loop, not per block)."""
    stats_add("pipe_blocks", blocks)
    stats_add("pipe_stage_s", stage_s)
    stats_add("pipe_dispatch_s", dispatch_s)
    stats_add("pipe_drain_s", drain_s)
    stats_add("pipe_wall_s", wall_s)
    stats_hwm("pipe_depth_hwm", depth_hwm)

# when on, every counted_jit dispatch also accrues the program's XLA cost
# analysis (flops / bytes accessed) into STATS — the bench's MFU and
# HBM-bandwidth accounting (VERDICT r3 weak-4).  Off by default: the
# one-time lower().compile() per (fn, shape) hits the persistent cache but
# still costs a retrace.
_COST_TRACKING = {"on": False}


def enable_cost_tracking(flag: bool = True) -> None:
    _COST_TRACKING["on"] = flag


def stats_snapshot() -> dict:
    from . import progcache
    with _STATS_MU:
        out = dict(STATS)
        # high-water marks are PER INTERVAL: a snapshot opens a new
        # interval (sequential snapshot/delta pairs, the bench's usage),
        # so a deep queue in query N never bleeds into query N+1's detail
        for k in _HWM_KEYS:
            STATS[k] = 0
    pc = progcache.stats_snapshot()
    out["progcache_hits"] = pc["hits"]
    out["progcache_misses"] = pc["misses"]
    out["prewarm_seeded"] = pc.get("prewarm_seeded", 0)
    out["prewarm_hits"] = pc.get("prewarm_hits", 0)
    return out


def stats_delta(since: dict) -> dict:
    now = stats_snapshot()
    return {k: (v if k in _HWM_KEYS or k in _GAUGE_KEYS
                else v - since.get(k, 0))
            for k, v in now.items()}


def _arg_spec(tree):
    import jax as j
    return tuple((getattr(x, "shape", None), str(getattr(x, "dtype", type(x))))
                 for x in j.tree_util.tree_leaves(tree))


def _abstractify(tree):
    """Replace arrays with ShapeDtypeStructs so pending cost analyses hold
    no device buffers alive."""
    import jax as j

    def conv(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return j.ShapeDtypeStruct(x.shape, x.dtype)
        return x
    return j.tree_util.tree_map(conv, tree)


# (costs dict, spec, jitted fn, abstract args) awaiting cost analysis —
# resolved OUTSIDE the timed region (resolve_pending_costs: the tsring
# Sampler every tick in serving mode), so the AOT retrace never inflates
# the walls the MFU is computed from.
# BOUNDED: beyond the cap a new spec records (0, 0) instead of queueing
# — with cost tracking on and no drainer the list must not grow forever
# (the pre-ISSUE-11 serving-mode leak).  GUARDED (_PENDING_MU, qlint
# CC701): query threads append while the tsring Sampler and any other
# caller drain concurrently — an unguarded pop raced against another drainer
# raises IndexError out of whichever caller loses, and the cap check
# raced against a concurrent append overshoots the bound
_PENDING_COSTS: list = []
_PENDING_MU = threading.Lock()
PENDING_COSTS_MAX = 256


def resolve_pending_costs() -> None:
    """Run the deferred cost analyses (bench calls this between timed
    runs; the tsring Sampler drains it every tick — both may run at
    once, so each entry is claimed under the lock and the expensive
    lower/compile happens OUTSIDE it).  Unresolvable programs record
    (0, 0)."""
    while True:
        with _PENDING_MU:
            if not _PENDING_COSTS:
                return
            costs, spec, w, absargs, prog_key = _PENDING_COSTS.pop()
        a, k = absargs
        try:
            compiled = w.lower(*a, **k).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):  # older jax: dict per device
                ca = ca[0] if ca else {}
            costs[spec] = (float(ca.get("flops", 0.0) or 0.0),
                           float(ca.get("bytes accessed", 0.0) or 0.0))
        except Exception:
            costs[spec] = (0.0, 0.0)
            continue
        try:
            # the program's static HBM footprint (peak scratch / operand /
            # result bytes) rides the same deferred resolution into the
            # catalog — compiled_programs' peak_*_bytes columns
            ma = compiled.memory_analysis()
            progcache.note_memory(
                prog_key,
                float(getattr(ma, "temp_size_in_bytes", 0) or 0),
                float(getattr(ma, "argument_size_in_bytes", 0) or 0),
                float(getattr(ma, "output_size_in_bytes", 0) or 0))
        except Exception:
            pass  # backends without memory_analysis keep zeros


_NOT_IN_A_NAME = re.compile(r"[^A-Za-z0-9_]+")


def program_name(key: Optional[tuple], variant: str = "") -> str:
    """What the profiler calls a program: its registry key's family
    (``jit_<family>`` among the trace's ``XLA Modules``,
    ``PjitFunction(<family>)`` on the host plane), ``kernel`` where it
    is built outside the registry.  A builder that knows more (the fused
    pipeline's node kinds, a stacked variant's bucket) passes it as
    ``variant``."""
    family = str(key[0]) if key else "kernel"
    name = _NOT_IN_A_NAME.sub("_", f"{family}_{variant}" if variant
                              else family).strip("_")
    return name[:96] or "kernel"


def counted_jit(fn, name: str = "", **kw):
    """jax.jit wrapper that counts program dispatches (and, when cost
    tracking is on, the dispatched program's flops / bytes accessed —
    first sight of a (program, shape) only ENQUEUES the analysis; counts
    accrue on dispatches after resolve_pending_costs ran).

    Constructed inside a progcache builder, the wrapper learns its
    registry key (progcache.building_key) and reports every dispatch to
    the per-program catalog; when the sampling profiler is on
    (ops/profiler.py, tidb_device_profile_rate) a sampled dispatch is
    closed with block_until_ready so the recorded wall is MEASURED
    device busy time, not async submit time."""
    prog_key = progcache.building_key()
    try:  # jax names the module after the function it wraps
        fn.__name__ = fn.__qualname__ = program_name(prog_key, name)
    except (AttributeError, TypeError):
        pass  # a callable that keeps its own name
    # qlint: disable=TS104 -- counted_jit IS the wrapper factory; callers cache its result
    w = jax().jit(fn, **kw)
    costs: Dict[tuple, Optional[tuple]] = {}

    def call(*a, **k):
        # the host's whole part of a launch: the counters, the cost
        # look-up, the enqueue (asynchronous: the device's time shows in
        # ``drain``) and the catalog's note
        with _obs.span("dispatch", cat="device"):
            return launch(a, k)

    def launch(a, k):
        fail.inject("kernelDispatchError")
        stats_add("dispatches", 1)
        cost = None
        if _COST_TRACKING["on"]:
            spec = _arg_spec((a, k))
            c = costs.get(spec)
            if c is not None:
                cost = c
                stats_add("flops", c[0])
                stats_add("bytes_accessed", c[1])
            elif spec not in costs:
                with _PENDING_MU:
                    # re-check under the lock: two threads first-
                    # dispatching the same spec must not both enqueue
                    # (duplicate cost analyses + wasted queue slots)
                    if spec not in costs:
                        if len(_PENDING_COSTS) >= PENDING_COSTS_MAX:
                            # nothing is draining the queue: record
                            # zeros (an honest undercount), not a leak
                            costs[spec] = (0.0, 0.0)
                        else:
                            costs[spec] = None
                            _PENDING_COSTS.append(
                                (costs, spec, w, _abstractify((a, k)),
                                 prog_key))
        sampled = profiler.should_sample()
        t0 = time.perf_counter() if sampled else 0.0
        res = w(*a, **k)
        if sampled:
            # close the async enqueue: the span and the recorded wall
            # now cover true device busy time for this dispatch
            jax().block_until_ready(res)
            dt = time.perf_counter() - t0
            stats_add("device_s", dt)
            stats_add("profiled_dispatches", 1)
            profiler.observe(dt)
            progcache.note_dispatch(prog_key, device_s=dt, cost=cost)
        else:
            progcache.note_dispatch(prog_key, cost=cost)
        return res
    # AOT hook for the bucket prewarmer (tools/warm.py):
    # fn.lower(*abstract).compile() compiles without dispatching
    call.lower = w.lower
    return call


def h2d(a, layout=None):
    """Counted host->device upload — the H2D mirror of :func:`d2h`, so
    transfer accounting is symmetric (pre-ISSUE-11, ParamTable pushes
    and column uploads were invisible: d2h had counters, h2d had none).
    One transfer per array; bytes charged from the HOST buffer, once,
    also where a mesh ``layout`` (parallel/dist.py ``rows`` / ``whole``)
    spreads the array over several devices."""
    host = np.asarray(a)
    with _obs.span("h2d", cat="device", bytes=int(host.nbytes)):
        out = jnp().asarray(host) if layout is None \
            else jax().device_put(host, layout)
    stats_add("h2d_transfers", 1)
    stats_add("h2d_bytes", int(host.nbytes))
    return out


def relayout(arr, layout):
    """Counted device->device move of an array that a mesh dispatch found
    laid out otherwise than its program asks (``reshard_bytes``)."""
    stats_add("reshard_bytes", int(arr.nbytes))
    return jax().device_put(arr, layout)


def h2d_pad(a: np.ndarray, n: int, fill=0):
    """Counted upload of ``pad1(a, n, fill)`` — THE bucketed column
    upload idiom (bytes charged at the padded size actually shipped)."""
    return h2d(pad1(a, n, fill))


def d2h(dev_arr) -> np.ndarray:
    """Counted device->host materialization."""
    fail.inject("kernelD2HError")
    with _obs.span("drain", cat="device"):
        out = np.asarray(dev_arr)
    stats_add("d2h_transfers", 1)
    stats_add("d2h_bytes", out.nbytes)
    return out


def d2h_many(dev_arrs) -> List[np.ndarray]:
    """ONE counted device->host pull for several arrays:
    jax.device_get gathers the copies behind a single sync point, so a
    kernel result split across the int64 and float64 streams pays the
    link's per-transfer latency once, not once per stream (the Q6
    dispatches=1 / d2h_transfers=2 accounting bug, BENCH_r05)."""
    fail.inject("kernelD2HError")
    with _obs.span("drain", cat="device"):
        outs = [np.asarray(a) for a in jax().device_get(list(dev_arrs))]
    stats_add("d2h_transfers", 1)
    stats_add("d2h_bytes", sum(o.nbytes for o in outs))
    return outs


I64_MIN = -(1 << 63)


# =========================================================================
# packed device->host transfer
# =========================================================================
# A device link charges a fixed latency PER device->host transfer and is
# slower D2H than H2D.  Every kernel therefore returns ONE packed int64
# buffer: float64 bitcasts losslessly, bools widen, and the host splits
# the single download back into typed arrays.  Data-dependent result
# sizes are handled with a two-phase protocol: phase 1 computes on device
# and syncs ONE scalar (the live count), phase 2 compacts device-side to a
# static bucket and packs.

def pack_arrays(schema: list, arrays) -> tuple:
    """Inside jit: concat 1-D arrays into one int64 and one float64 stream
    (f64<->i64 bitcast does not lower under the TPU X64-emulation rewrite,
    so the two element classes ride separate buffers — at most two D2H
    transfers per kernel).  Appends (dtype, length, stream) to `schema`
    (cleared first) for unpack_flat; tracing runs once per compile-cache
    entry, so the schema paired with the jitted fn is stable by the time
    results are unpacked."""
    jn = jnp()
    del schema[:]
    ints, floats = [], []
    with jax().named_scope("pack"):
        for a in arrays:
            if a.dtype == jn.float64:
                schema.append(("float64", int(a.shape[0]), "f"))
                floats.append(a)
            elif a.dtype in (jn.int64, jn.bool_, jn.int32):
                schema.append((str(a.dtype), int(a.shape[0]), "i"))
                ints.append(a if a.dtype == jn.int64
                            else a.astype(jn.int64))
            else:  # float32 etc. would silently truncate through the int path
                raise TypeError(
                    f"pack_arrays: unsupported dtype {a.dtype}")
        zi = jn.zeros(0, dtype=jn.int64)
        zf = jn.zeros(0, dtype=jn.float64)
        return (jn.concatenate(ints) if ints else zi,
                jn.concatenate(floats) if floats else zf)


def _split_flat(flat_i, flat_f, schema: list) -> List[np.ndarray]:
    """Split the two host streams back into typed arrays per the
    recorded schema (shared by :func:`unpack_flat` and
    :func:`unpack_host`)."""
    out = []
    pi = pf = 0
    for dt, ln, stream in schema:
        if stream == "f":
            out.append(flat_f[pf:pf + ln])
            pf += ln
        else:
            seg = flat_i[pi:pi + ln]
            pi += ln
            if dt == "int64":
                out.append(seg)
            elif dt == "bool":
                out.append(seg != 0)
            else:
                out.append(seg.astype(np.dtype(dt)))
    return out


def unpack_flat(pair, schema: list) -> List[np.ndarray]:
    """ONE D2H pull (both streams batch through d2h_many when a result
    spans int64 and float64), then split per the recorded schema."""
    dev_i, dev_f = pair
    need_i = any(s == "i" for _, _, s in schema)
    need_f = any(s == "f" for _, _, s in schema)
    if need_i and need_f:
        flat_i, flat_f = d2h_many([dev_i, dev_f])
    else:
        flat_i = d2h(dev_i) if need_i else None
        flat_f = d2h(dev_f) if need_f else None
    return _split_flat(flat_i, flat_f, schema)


def unpack_host(pair, schema: list) -> List[np.ndarray]:
    """``unpack_flat`` for a stacked batch round's already-downloaded
    member rows: the round's dispatch leg pulled the WHOLE stacked
    output in one packed transfer (ops/batching.py), so the member's
    row pair is host memory here — splitting must not count (or pay
    for) another download."""
    host_i, host_f = pair
    return _split_flat(host_i, host_f, schema)


def bucket(n: int) -> int:
    """Pad target: next power of two (min 16) — bounds recompiles to
    O(log n) distinct shapes.  Each resolved bucket is reported to the
    active per-query scope (obs/context.py): the ground truth the
    prewarm feedback loop records, since fused-pipeline input shapes
    never flow through an operator's next()."""
    b = 16
    while b < n:
        b <<= 1
    _obs.record_bucket(b)
    return b


def pad1(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    if len(a) == n:
        return a
    if fill == 0 or fill is False:
        out = np.zeros(n, dtype=a.dtype)  # calloc: no fill pass
    else:
        out = np.full(n, fill, dtype=a.dtype)
    out[: len(a)] = a
    return out


#: lanes scanned in lockstep by :func:`prefix_sum`
PREFIX_CHUNKS = 4096


def prefix_sum(x):
    """Inside jit: inclusive prefix sum of a 1-D lane.  ``jnp.cumsum`` of
    an emulated 64-bit lane lowers on the TPU to a ``reduce_window`` as
    wide as the lane; at the 2^16 bucket the float64 one takes minutes to
    compile and crashes the compiler once a gather feeds it.  This cuts
    the lane into PREFIX_CHUNKS contiguous chunks, scans all chunks in
    lockstep with a sequential loop whose body is ONE vector add, and
    adds each chunk's offset — seconds to compile at any bucket, on every
    backend.  Float sums accumulate chunk by chunk, so they differ from a
    left-to-right sum in the last digits (as the cumsum did)."""
    from jax import lax
    jn = jnp()
    n = int(x.shape[0])
    with jax().named_scope("prefix_sum"):
        if n <= PREFIX_CHUNKS or n % PREFIX_CHUNKS:
            return lax.associative_scan(jn.add, x)
        rows = n // PREFIX_CHUNKS

        def step(carry, row):
            carry = carry + row
            return carry, carry
        totals, inner = lax.scan(
            step, jn.zeros(PREFIX_CHUNKS, dtype=x.dtype),
            x.reshape(PREFIX_CHUNKS, rows).T)
        offsets = prefix_sum(totals) - totals
        return (inner + offsets[None, :]).T.reshape(n)


# one-RTT threshold: below this many rows, downloading the FULL dense
# arrays in one packed transfer beats a scalar sync + compacted transfer
# (the link's per-transfer latency dwarfs the extra bytes)
SMALL_PACK = 1 << 16

def _slice_pack(items, ob: int):
    """Pack device arrays sliced to [:ob] — one download.  Returns host
    arrays (still ob-long; callers slice to the live count)."""
    key = ("slice_pack", ob, tuple(str(a.dtype) for a in items),
           tuple(int(a.shape[0]) for a in items))

    def build():
        schema: list = []

        def kernel(arrs):
            return pack_arrays(schema, [a[:ob] for a in arrs])
        return counted_jit(kernel), schema
    fn, schema = progcache.get(key, build)
    return unpack_flat(fn(items), schema)


def _present_pack(presence, items, ob: int):
    """Device-compact rows where presence>0 into a static ob bucket, pack,
    one download.  Returns (ids, gathered items), each ob-long with
    out-of-range id fill past the live count."""
    jn_ = jnp()
    ns = int(presence.shape[0])
    key = ("present_pack", ob, ns, tuple(str(a.dtype) for a in items))

    def build():
        schema: list = []

        def kernel(pres, arrs):
            with jax().named_scope("present_pack"):
                idx = jn_.nonzero(pres > 0, size=ob, fill_value=ns)[0]
                safe = jn_.minimum(idx, ns - 1)
                return pack_arrays(schema,
                                   [idx] + [a[safe] for a in arrs])
        return counted_jit(kernel), schema
    fn, schema = progcache.get(key, build)
    vals = unpack_flat(fn(presence, items), schema)
    return vals[0], vals[1:]


# =========================================================================
# group aggregate
# =========================================================================
# agg spec tuple: (func, has_arg) where func in
#   count_star | count | sum | sum_int | min | max | first


def _sort_perm(keys, valid):
    """Device lexsort: invalid rows last, NULL keys first within a key."""
    j = jnp()
    ops = []
    for kv, kn in reversed(keys):
        ops.append(kv)
        ops.append(j.where(kn, 0, 1).astype(j.int8))  # NULL first
    ops.append(j.where(valid, 0, 1).astype(j.int8))   # invalid last (primary)
    return j.lexsort(ops)


def _group_agg_kernel(n_keys: int, specs: tuple):
    j = jax()
    jn = jnp()

    def kernel(key_vals, key_nulls, valid, arg_vals, arg_nulls):
        n = valid.shape[0]
        keys = list(zip(key_vals, key_nulls))
        perm = _sort_perm(keys, valid)
        kv_s = [v[perm] for v in key_vals]
        kn_s = [m[perm] for m in key_nulls]
        valid_s = valid[perm]
        # group boundary: any key cell differs (null-aware)
        boundary = jn.zeros(n, dtype=bool).at[0].set(True)
        for v, m in zip(kv_s, kn_s):
            dv = (v[1:] != v[:-1]) & ~(m[1:] & m[:-1])
            dm = m[1:] != m[:-1]
            boundary = boundary.at[1:].set(boundary[1:] | dv | dm)
        gid = jn.cumsum(boundary) - 1
        seg = partial(j.ops.segment_sum, segment_ids=gid, num_segments=n)
        first_idx = j.ops.segment_min(jn.arange(n), gid, num_segments=n)
        first_idx = jn.minimum(first_idx, n - 1)
        n_valid = jn.sum(valid_s.astype(jn.int32))
        n_groups = jn.where(n_valid > 0, gid[jn.maximum(n_valid - 1, 0)] + 1, 0)
        # representative ORIGINAL row id per group (host gathers any-typed
        # columns — string group keys, first_row aggs — with this)
        first_orig = perm[first_idx]

        group_keys = [(v[first_idx], m[first_idx])
                      for v, m in zip(kv_s, kn_s)]
        outs = []
        ai = 0
        for func, has_arg in specs:
            if has_arg:
                av = arg_vals[ai][perm]
                an = arg_nulls[ai][perm]
                ai += 1
            if func == "count_star":
                outs.append((seg(valid_s.astype(jn.int64)),
                             jn.zeros(n, dtype=bool)))
            elif func == "count":
                live = valid_s & ~an
                outs.append((seg(live.astype(jn.int64)),
                             jn.zeros(n, dtype=bool)))
            elif func in ("sum", "sum_int", "sum0"):
                live = valid_s & ~an
                total = seg(jn.where(live, av, 0))
                cnt = seg(live.astype(jn.int64))
                outs.append((total, jn.zeros_like(cnt, dtype=bool)
                             if func == "sum0" else cnt == 0))
            elif func in ("min", "max"):
                live = valid_s & ~an
                if func == "min":
                    fill = (jn.iinfo(jn.int64).max if av.dtype == jn.int64
                            else jn.inf)
                    r = j.ops.segment_min(jn.where(live, av, fill), gid,
                                          num_segments=n)
                else:
                    fill = (jn.iinfo(jn.int64).min if av.dtype == jn.int64
                            else -jn.inf)
                    r = j.ops.segment_max(jn.where(live, av, fill), gid,
                                          num_segments=n)
                cnt = seg(live.astype(jn.int64))
                outs.append((r, cnt == 0))
            elif func == "first":
                outs.append((av[first_idx], an[first_idx]))
            else:  # pragma: no cover
                raise ValueError(func)
        return n_groups, first_orig, group_keys, outs

    return counted_jit(kernel)


def group_aggregate(key_cols: List[Tuple[np.ndarray, np.ndarray]],
                    agg_specs: List[Tuple[str, bool]],
                    arg_cols: List[Tuple[np.ndarray, np.ndarray]],
                    n_rows: int, filter_mask: np.ndarray = None):
    """Host wrapper: pad, run kernel, slice to n_groups.

    key_cols/arg_cols: (values, null) numpy pairs of length n_rows.
    `filter_mask` folds a selection into the kernel's valid mask — the
    fused filter+aggregate path skips host-side compaction entirely.
    Returns (group_key_cols, agg_out_cols) as numpy (values, null) pairs.
    """
    jn = jnp()
    nb = bucket(max(n_rows, 1))
    valid = np.zeros(nb, dtype=bool)
    if filter_mask is not None:
        valid[:n_rows] = filter_mask
    else:
        valid[:n_rows] = True
    kv = [h2d_pad(v, nb) for v, _ in key_cols]
    kn = [h2d_pad(m, nb, True) for _, m in key_cols]
    av = [h2d_pad(v, nb) for v, _ in arg_cols]
    an = [h2d_pad(m, nb, True) for _, m in arg_cols]
    key = ("group_agg", len(key_cols), tuple(agg_specs), nb,
           tuple(str(v.dtype) for v in kv), tuple(str(v.dtype) for v in av))
    fn = progcache.get(key, lambda: _group_agg_kernel(len(key_cols),
                                                      tuple(agg_specs)))
    n_groups, first_orig, gkeys, outs = fn(kv, kn, h2d(valid), av, an)
    items = [first_orig]
    for v, m in gkeys:
        items += [v, m]
    for v, m in outs:
        items += [v, m]
    if nb <= SMALL_PACK:
        # one RTT: download the full (small) dense arrays with n_groups
        # packed in, slice on host
        vals = _slice_pack([n_groups[None].astype(jn.int64)] + items, nb)
        ng = int(vals[0][0])
        vals = vals[1:]
    else:
        ng = int(n_groups)  # scalar sync, then one compacted download
        ob = min(bucket(max(ng, 1)), nb)
        vals = _slice_pack(items, ob)
    first = vals[0][:ng]
    rest = vals[1:]
    nk = len(gkeys)
    out_keys = [(rest[2 * i][:ng], rest[2 * i + 1][:ng]) for i in range(nk)]
    out_aggs = [(rest[2 * nk + 2 * i][:ng], rest[2 * nk + 2 * i + 1][:ng])
                for i in range(len(outs))]
    return out_keys, out_aggs, first


def _segment_agg_kernel(specs: tuple, n_segments: int):
    """Known-cardinality group aggregate: direct segment reductions over
    composite group ids — NO sort (the shape dist.make_sharded_group_sum
    uses per shard; here single-chip).  Invalid rows route to an overflow
    segment that is sliced away."""
    j = jax()
    jn = jnp()

    def kernel(gid, valid, arg_vals, arg_nulls):
        seg = _SegReduce(j, jn, gid, valid, n_segments)
        presence, first_orig = seg.presence_first()
        first_orig = jn.minimum(first_orig, gid.shape[0] - 1)
        outs = []
        ai = 0
        for func, has_arg in specs:
            if has_arg:
                av = arg_vals[ai]
                an = arg_nulls[ai]
                ai += 1
            if func == "count_star":
                outs.append((presence, jn.zeros(n_segments, dtype=bool)))
                continue
            live = valid & ~an
            cnt = seg.sum(live.astype(jn.int64), live)
            if func == "count":
                outs.append((cnt, jn.zeros(n_segments, dtype=bool)))
            elif func in ("sum", "sum_int", "sum0"):
                outs.append((seg.sum(av, live),
                             jn.zeros_like(cnt, dtype=bool)
                             if func == "sum0" else cnt == 0))
            elif func in ("min", "max"):
                outs.append((seg.minmax(av, live, func == "min"), cnt == 0))
            else:  # pragma: no cover
                raise ValueError(func)
        n_present = jn.sum((presence > 0).astype(jn.int64))
        return presence, first_orig, outs, n_present

    return counted_jit(kernel)


MAX_SEGMENTS = 1 << 16
# dense scatter-add beats the sort-based path by >30x even at millions of
# bins (segment arrays are tiny next to the input); allow high-cardinality
# int keys up to this many bins when the input is large enough to amortize
# the per-bin present-extraction
MAX_DENSE_SEGMENTS = 1 << 21


def seg_limit(n_rows: int) -> int:
    """Segment-count budget for the scatter-add aggregate paths: small
    inputs keep the tight cap (present-extraction is O(bins)), large
    inputs may spread across millions of bins."""
    return min(MAX_DENSE_SEGMENTS, max(MAX_SEGMENTS, 8 * max(n_rows, 1)))


def segment_group_aggregate(gids: np.ndarray, n_segments: int,
                            agg_specs, arg_cols, n_rows: int,
                            filter_mask: np.ndarray = None):
    """Host wrapper: composite small-cardinality group ids -> per-present-
    segment aggregates.  Returns (present_segment_ids, out_aggs,
    first_orig) with empty segments compressed away."""
    jn = jnp()
    nb = bucket(max(n_rows, 1))
    valid = np.zeros(nb, dtype=bool)
    if filter_mask is not None:
        valid[:n_rows] = filter_mask
    else:
        valid[:n_rows] = True
    g = h2d_pad(gids.astype(np.int64), nb)
    av = [h2d_pad(v, nb) for v, _ in arg_cols]
    an = [h2d_pad(m, nb, True) for _, m in arg_cols]
    # bucket the segment count too: one compiled kernel serves every
    # cardinality in the bucket (gids above the true count never occur,
    # their segments simply stay empty and are compressed away)
    ns = bucket(max(n_segments, 1))
    key = ("segment_agg", tuple(agg_specs), ns, nb,
           tuple(str(v.dtype) for v in av))
    fn = progcache.get(key, lambda: _segment_agg_kernel(tuple(agg_specs),
                                                        ns))
    presence, first_orig, outs, n_present = fn(g, h2d(valid), av, an)
    return _present_extract(presence, first_orig, outs, n_present, ns)


def _present_extract(presence, first_orig, outs, n_present, ns: int,
                     limit: int = None):
    """Shared segment-table extraction: one packed download (small tables)
    or scalar-sync + device compaction (large).  Returns
    (present_ids, out_aggs, first_orig) host arrays."""
    jn = jnp()
    items = [first_orig]
    for v, m in outs:
        items += [v, m]
    if ns <= SMALL_PACK:
        vals = _slice_pack(items + [presence], ns)
        pres = vals[-1]
        rest = vals[:-1]
        present = np.nonzero(pres > 0)[0]
        first = rest[0][present]
        out_aggs = [(rest[1 + 2 * i][present], rest[2 + 2 * i][present])
                    for i in range(len(outs))]
    else:
        np_ = int(n_present)
        ob = min(bucket(max(np_, 1)), ns)
        ids, vals = _present_pack(presence, items, ob)
        present = ids[:np_]
        first = vals[0][:np_]
        out_aggs = [(vals[1 + 2 * i][:np_], vals[2 + 2 * i][:np_])
                    for i in range(len(outs))]
    if limit is not None:
        keep = present < limit
        if not keep.all():
            present = present[keep]
            first = first[keep]
            out_aggs = [(v[keep], m[keep]) for v, m in out_aggs]
    return present, out_aggs, first


def _unpack_scalar_agg(vals):
    """Unpacked [n_valid, first_orig, v0, m0, ...] -> the scalar-aggregate
    contract (out_aggs, first_orig) with zero or one output row."""
    ng = 1 if int(vals[0][0]) > 0 else 0
    first_orig = vals[1][:ng]
    rest = vals[2:]
    out_aggs = [(rest[2 * i][:ng], rest[2 * i + 1][:ng])
                for i in range(len(rest) // 2)]
    return out_aggs, first_orig


# Below this many segments the kernels unroll per-segment masked
# reductions instead of scatter-based segment ops: on TPU (esp. under the
# X64-emulation rewrite) a scatter-add over millions of rows costs
# hundreds of ms while ns full-array masked reductions fuse into a few
# streaming passes (measured ~100x faster at ns<=64).
SEG_UNROLL = 64


class _SegReduce:
    """Segment-reduction strategy: scatter-based (any ns) or masked
    reductions in row order, one per segment (small ns).  gid/valid fixed
    at construction.  ``unroll=None`` chooses from ns and the backend (the
    per-operator tier); the fused pipeline's dense GROUP BY passes True,
    having chosen from its group count alone."""

    def __init__(self, j, jn, gid, valid, ns: int, unroll=None):
        self.j, self.jn, self.gid, self.valid, self.ns = j, jn, gid, valid, ns
        # XLA:CPU lowers scatter-adds to a tight loop (fast) and would pay
        # ns full passes for the unroll; on TPU it's the reverse
        self.unroll = (ns <= SEG_UNROLL and j.default_backend() != "cpu") \
            if unroll is None else unroll
        if self.unroll:
            # [ns, n] membership: XLA fuses it into each reduction's
            # streaming pass over gid, it is never materialized
            self.member = (gid[None, :] == jn.arange(
                ns, dtype=gid.dtype)[:, None]) & valid[None, :]

    def _masked(self, red, x, live, fill):
        """red over each segment's live rows of x, fill elsewhere."""
        jn = self.jn
        fill = jn.full((), fill, dtype=x.dtype)
        lx = jn.where(live, x, fill)
        return red(jn.where(self.member, lx[None, :], fill), axis=1)

    def sum(self, x, live):
        jn = self.jn
        if self.unroll:
            return self._masked(jn.sum, x, live, 0)
        gl = jn.where(self.valid & live, self.gid, self.ns)
        return self.j.ops.segment_sum(
            jn.where(live, x, 0), gl, num_segments=self.ns + 1)[:self.ns]

    def minmax(self, x, live, is_min: bool):
        jn = self.jn
        if x.dtype == jn.int64:
            fill = (jn.iinfo(jn.int64).max if is_min
                    else jn.iinfo(jn.int64).min)
        else:
            fill = jn.inf if is_min else -jn.inf
        if self.unroll:
            return self._masked(jn.min if is_min else jn.max, x, live, fill)
        gl = jn.where(self.valid & live, self.gid, self.ns)
        op = self.j.ops.segment_min if is_min else self.j.ops.segment_max
        return op(jn.where(live, x, fill), gl,
                  num_segments=self.ns + 1)[:self.ns]

    def presence_first(self):
        """(presence counts, first row id) per segment; empty segments
        carry the sentinel n (callers clip or remap — the sharded kernel
        must see the sentinel to keep pmin from picking a bogus shard)."""
        j, jn = self.j, self.jn
        n = self.gid.shape[0]
        if self.unroll:
            return (self._masked(jn.sum, self.valid.astype(jn.int64),
                                 self.valid, 0),
                    self._masked(jn.min, jn.arange(n), self.valid, n))
        g = jn.where(self.valid, self.gid, self.ns)
        presence = j.ops.segment_sum(self.valid.astype(jn.int64), g,
                                     num_segments=self.ns + 1)[:self.ns]
        first = j.ops.segment_min(jn.arange(n), g,
                                  num_segments=self.ns + 1)[:self.ns]
        return presence, jn.minimum(first, n)


def _fused_agg_outs(j, jn, agg_specs, arg_fns, cols, gid, valid,
                    ns, presence, merge_sum, merge_min, merge_max, seg,
                    pr=((), ())):
    """Per-aggregate switch shared by the single-device and sharded fused
    kernels; merge_* combine per-shard partials (identity single-device,
    psum/pmin/pmax over the mesh axis); ``pr`` is the runtime constant
    vector pair the params-compiled argument closures read."""
    outs = []
    for (func, has_arg), af in zip(agg_specs, arg_fns):
        av = an = None
        if has_arg and af is not None:
            av, an = af(cols, pr)
        if func == "count_star":
            outs.append((presence, jn.zeros(ns, dtype=bool)))
            continue
        live = valid & ~an
        cnt = merge_sum(seg.sum(live.astype(jn.int64), live))
        if func == "count":
            outs.append((cnt, jn.zeros(ns, dtype=bool)))
        elif func in ("sum", "sum0"):
            # sum0: a COUNT merged from partial states — 0 over empty
            # input, never NULL (unlike SUM)
            total = merge_sum(seg.sum(av, live))
            outs.append((total, jn.zeros(ns, dtype=bool)
                         if func == "sum0" else cnt == 0))
        elif func in ("min", "max"):
            local = seg.minmax(av, live, func == "min")
            merged = merge_min(local) if func == "min" else merge_max(local)
            outs.append((merged, cnt == 0))
        else:  # pragma: no cover
            raise ValueError(func)
    return outs


# ---- fully fused aggregation over device-resident columns -----------------
# The flagship TPU path: raw table columns live padded in HBM (memoized on
# the columnar replica), aggregate ARGUMENT expressions evaluate on device
# through the exprjit lowering, the whole thing is ONE XLA program, and the
# FILTER MASK itself computes on device: scan conditions AND aggregate
# arguments lower through exprjit with constants as runtime params
# (exprjit.ParamTable), so the per-query traffic is a ~100-byte param
# upload instead of an nb-bool mask — and the program-cache key is the
# expression SHAPE (stable_shape_key), never a constant value: one
# compiled program serves the whole normalized-SQL digest family.
#
# mask spec accepted by the fused entry points:
#   ("host", bool_mask_dev)     — legacy: host-evaluated, uploaded
#   ("dev", mask_fn, key)       — mask_fn(cols, params, row_idx) traced
#     into the kernel; `key` joins the program cache key.
#
# arg_exprs entries: None, a closure (cols, params) -> (values, null)
# (the executor's params-compiled lowering / count-mask programs), or a
# bare Expression (legacy callers: lowers literal-baked — the caller's
# program_key must then pin constant values).
#
# `params`: the per-query (int64[], float64[]) constant vectors every
# params-compiled closure reads its slots from (exprjit.ParamTable
# .arrays()); None when nothing is parameterized.

_EMPTY_I64 = None
_EMPTY_F64 = None
_EMPTY_MASK = None


def _mask_parts(mask):
    """Normalize a mask spec -> (mask_fn|None, cache key, runtime mask
    array).  An absent runtime mask rides a 0-length array so every
    variant shares one call signature."""
    global _EMPTY_MASK
    jn = jnp()
    if _EMPTY_MASK is None:
        _EMPTY_MASK = jn.zeros(0, dtype=bool)
    if mask[0] == "host":
        return None, ("hostmask",), mask[1]
    _, mask_fn, key = mask[:3]
    return mask_fn, ("devmask", key), _EMPTY_MASK


def _params_dev(params, layout=None):
    """Upload the per-query constant vectors (absent slots ride 0-length
    arrays so parameterless programs share the call signature); a mesh
    program's go whole onto every device (``layout``)."""
    global _EMPTY_I64, _EMPTY_F64
    jn = jnp()
    if _EMPTY_I64 is None:
        _EMPTY_I64 = jn.zeros(0, dtype=jn.int64)
        _EMPTY_F64 = jn.zeros(0, dtype=jn.float64)
    if params is None:
        return (_EMPTY_I64, _EMPTY_F64)
    pi, pf = params
    return (h2d(pi, layout), h2d(pf, layout))


def _lower_arg(e):
    """One aggregate-argument entry -> (cols, params) closure or None.
    Callables pass through (the executor's params-compiled closures);
    bare Expressions lower literal-baked via cached_compile_expr for
    legacy callers whose program_key pins the constant values."""
    if e is None or callable(e):
        return e
    from .exprjit import cached_compile_expr
    fn = cached_compile_expr(e)
    return lambda cols, params: fn(cols)


def _batch_round(mask, params, batchable: bool):
    """Cross-query micro-batching eligibility at a fused dispatch site
    (ops/batching.py): only explicitly-batchable single-shot call sites
    with a params-compiled device mask qualify — the combination that
    makes one compiled program serve a whole constant-variant digest
    family.  Records the ``batchable`` obs marker (the session's close
    hook learns family eligibility from it) and returns the active
    batch round, or None."""
    if not (batchable and mask[0] == "dev" and params is not None):
        return None
    _obs.record("batchable", 1)
    from . import batching
    return batching.current()


# ---- stacked-params batch execution (ops/batching.py dispatch leg) --------
# A batch round's parked members share one compiled program and one set
# of replica-memoized data columns; only their ~100-byte ParamTables
# differ.  Stacking those on a leading batch axis (exprjit
# ParamTable.stack) and dispatching ONE jax.vmap-batched program variant
# makes an entire round cost one XLA dispatch instead of N back-to-back
# replays.  Variants register in progcache under the base key extended
# with a power-of-two OCCUPANCY BUCKET (occupancy 3 rides the B=4
# program with an inert padding row) — no key explosion, prewarmable
# like any program family (prewarm_stacked).

def _stackable_jit(kernel, kind: str, n_data: int, make_kernel):
    """counted_jit + the stacking recipe (`stack_info`) the batched
    variant builder reads: ``kind`` is the output protocol ("packed" =
    one downloadable [B, L] pair, "tree" = per-member device slices),
    ``n_data`` the shared data operands before the vmapped params
    operand, ``make_kernel`` a factory yielding a FRESH (kernel,
    schema) pair for the vmap re-trace."""
    w = counted_jit(kernel)  # qlint: disable=TS104 -- factory: returned straight to the progcache builder, which owns caching
    w.stack_info = (kind, n_data, make_kernel)
    return w


def occupancy_bucket(n: int) -> int:
    """Power-of-two batch bucket for a stacked round (min 2 — a solo
    member never stacks)."""
    b = 2
    while b < n:
        b <<= 1
    return b


def _stacked_key(key: tuple, b: int) -> tuple:
    return key + (("stacked", b),)


def is_stacked_key(key: tuple) -> bool:
    """Is this registry key a B-stacked variant of a batchable program?"""
    return bool(key) and isinstance(key[-1], tuple) and len(key[-1]) == 2 \
        and key[-1][0] == "stacked"


def stacked_variant(key: tuple, base_fn, b: int):
    """The B-stacked variant of a batchable fused program: the base
    kernel re-traced under ``jax.vmap`` over the params operand (shared
    data columns stay unmapped), registered under the base key extended
    with the occupancy bucket ``b``.  Returns ``(jitted fn, kind,
    schema)`` — kind ``"packed"`` outputs download as one ``[B, L]``
    pair, kind ``"tree"`` outputs slice per member on device — or None
    when the base program carries no stacking recipe (legacy entries,
    non-fused programs)."""
    info = getattr(base_fn, "stack_info", None)
    if info is None:
        return None
    kind, n_data, make_kernel = info

    def build():
        kern, schema = make_kernel()
        axes = tuple([None] * n_data + [0])
        vk = jax().vmap(kern, in_axes=axes)
        return counted_jit(vk, name=f"b{b}"), kind, schema
    return progcache.get(_stacked_key(key, b), build)


def prewarm_stacked(buckets=(2, 4, 8, 16)) -> int:
    """AOT-build the B-bucketed stacked variants of every registered
    batchable fused program (the auto-prewarm worker calls this inside
    its prewarm scope; tools/batch_smoke.py calls it so the storm's
    first stacked round is a plain cache hit).  Returns the number of
    variants now registered."""
    n = 0
    for key in progcache.keys("scalar") + progcache.keys("seg"):
        if is_stacked_key(key):
            continue
        ent = progcache.peek(key)
        fn = ent[0] if isinstance(ent, tuple) else ent
        if getattr(fn, "stack_info", None) is None:
            continue
        for b in buckets:
            if stacked_variant(key, fn, int(b)) is not None:
                n += 1
    return n


def _fused_segment_raw(dev_cols, gid_dev, n_segments: int,
                       agg_specs, arg_exprs, mask,
                       program_key: tuple = (), params=None,
                       batchable: bool = False):
    """The fused segment-aggregate device program WITHOUT extraction:
    returns (presence, first_orig, outs, n_present, ns) as device arrays
    (n_present a device scalar).  Shared by the host-extract and
    device-resident (late-materialization) paths."""
    j = jax()
    jn = jnp()
    nb = int(gid_dev.shape[0])
    ns = bucket(max(n_segments, 1))
    mask_fn, mask_key, mask_arr = _mask_parts(mask)
    key = ("seg", tuple(agg_specs), program_key, mask_key, ns, nb)
    rnd = _batch_round(mask, params, batchable)
    if rnd is not None and rnd.collecting:
        ent = progcache.peek(key)
        if ent is not None:  # warm programs only: cold families stay solo
            rnd.park(key, ent, (dev_cols, gid_dev, mask_arr), params)

    def build():
        arg_fns = [_lower_arg(e) for e in arg_exprs]

        def make_kernel():
            def kernel(cols, gid, mask_in, pr):
                if mask_fn is not None:
                    valid = mask_fn(cols, pr, jn.arange(nb))
                else:
                    valid = mask_in  # covers filter AND padding rows
                seg = _SegReduce(j, jn, gid, valid, ns)
                presence, first_orig = seg.presence_first()
                first_orig = jn.minimum(first_orig, gid.shape[0] - 1)
                ident = lambda x: x
                outs = _fused_agg_outs(j, jn, agg_specs, arg_fns, cols,
                                       gid, valid, ns, presence, ident,
                                       ident, ident, seg=seg, pr=pr)
                n_present = jn.sum((presence > 0).astype(jn.int64))
                return presence, first_orig, outs, n_present
            return kernel, None

        kernel, _ = make_kernel()
        # tree output: member rows slice off axis 0 and flow into
        # _present_extract in the member's own scope
        return _stackable_jit(kernel, "tree", 3, make_kernel)
    fn = progcache.get(key, build)
    if rnd is not None and rnd.replaying:
        got = rnd.consume(key, (dev_cols, gid_dev, mask_arr), params)
        if got is not None:
            # consume attributed the member's occupancy-weighted share
            # of the round dispatch into this scope (the global counter
            # accrued at dispatch time through counted_jit on the pool
            # worker)
            _tag, (presence, first_orig, outs, n_present) = got
            return presence, first_orig, outs, n_present, ns
    presence, first_orig, outs, n_present = fn(dev_cols, gid_dev,
                                               mask_arr,
                                               _params_dev(params))
    return presence, first_orig, outs, n_present, ns


def fused_segment_aggregate(dev_cols, gid_dev, n_segments: int,
                            agg_specs, arg_exprs, n_rows: int,
                            mask, program_key: tuple = (), params=None,
                            batchable: bool = False):
    """dev_cols: per-schema-slot (values, null) device pairs padded to one
    bucket (None for slots no jittable expression touches); gid_dev:
    composite group ids padded with an out-of-range id; arg_exprs: the agg
    argument programs, lowered on device; mask: a mask spec and params
    the per-query constant vectors (module docstring above).  Returns the
    group_aggregate contract (present_ids, out_aggs, first_orig).
    ``batchable=True`` (single-shot executor call sites only) opts the
    dispatch into cross-query micro-batching (ops/batching.py)."""
    presence, first_orig, outs, n_present, ns = _fused_segment_raw(
        dev_cols, gid_dev, n_segments, agg_specs, arg_exprs, mask,
        program_key=program_key, params=params, batchable=batchable)
    return _present_extract(presence, first_orig, outs, n_present, ns,
                            limit=n_segments)


def fused_segment_aggregate_keep(dev_cols, gid_dev, n_segments: int,
                                 agg_specs, arg_exprs, mask,
                                 program_key: tuple = (), params=None):
    """Device-resident variant (late materialization, VERDICT r4 next-2):
    compacts present segments ON DEVICE and returns
    (present_ids_dev [ob], live_dev [ob], out_aggs_dev, n_present, ob)
    with NO bulk download — only the n_present scalar syncs.  Rows
    [0:n_present) are live (presence ids ascend out of nonzero); padding
    rows carry id=ns and live=False."""
    jn = jnp()
    if mask[0] == "dev" and params is not None:
        # family-eligibility marker only (the session close hook feeds
        # batching.note_family from it): the keep path itself never
        # parks — its per-member device assembly cannot ride a stacked
        # dispatch — but a later batch ROUND re-routes this plan through
        # the batchable fused_segment path (tpu_executors skips the
        # passthrough while a round is live)
        _obs.record("batchable", 1)
    presence, _first, outs, n_present, ns = _fused_segment_raw(
        dev_cols, gid_dev, n_segments, agg_specs, arg_exprs, mask,
        program_key=program_key, params=params)
    np_ = int(n_present)  # one scalar sync
    ob = min(bucket(max(np_, 1)), ns)
    key = ("present_keep", ob, ns, len(outs),
           tuple(str(v.dtype) for v, _ in outs))

    def build():
        def kernel(pres, items):
            idx = jn.nonzero(pres > 0, size=ob, fill_value=ns)[0]
            live = idx < ns
            safe = jn.minimum(idx, ns - 1)
            gathered = [(v[safe], m[safe] | ~live) for v, m in items]
            return idx, live, gathered
        return counted_jit(kernel)
    fn = progcache.get(key, build)
    ids, live, out_aggs = fn(presence, list(outs))
    return ids, live, out_aggs, np_, ob


def fused_scalar_aggregate(dev_cols, agg_specs, arg_exprs, n_rows: int,
                           nb: int, mask, program_key: tuple = (),
                           params=None, batchable: bool = False):
    """Global-group variant of the fused path: masked reductions with
    on-device argument evaluation.  ``batchable=True`` opts the dispatch
    into cross-query micro-batching (ops/batching.py)."""
    j = jax()
    jn = jnp()
    mask_fn, mask_key, mask_arr = _mask_parts(mask)
    key = ("scalar", tuple(agg_specs), program_key, mask_key, nb)
    rnd = _batch_round(mask, params, batchable)
    if rnd is not None and rnd.collecting:
        ent = progcache.peek(key)
        if ent is not None:
            rnd.park(key, ent[0], (dev_cols, mask_arr), params)

    def build():
        arg_fns = [_lower_arg(e) for e in arg_exprs]

        def make_kernel():
            # a FRESH (kernel, schema) pair per call: the stacked-variant
            # builder (stacked_variant) re-traces the kernel under
            # jax.vmap, and pack_arrays rewrites its captured schema at
            # trace time — sharing one list with live solo consumers
            # would expose them to a transiently-cleared schema
            kernel_schema: list = []

            def kernel(cols, mask_in, pr):
                if mask_fn is not None:
                    valid = mask_fn(cols, pr, jn.arange(nb))
                else:
                    valid = mask_in
                outs = []
                for (func, has_arg), af in zip(agg_specs, arg_fns):
                    av = an = None
                    if has_arg and af is not None:
                        av, an = af(cols, pr)
                    if func == "count_star":
                        outs.append((jn.sum(valid.astype(jn.int64))[None],
                                     jn.zeros(1, dtype=bool)))
                        continue
                    live = valid & ~an
                    if func == "count":
                        outs.append((jn.sum(live.astype(jn.int64))[None],
                                     jn.zeros(1, dtype=bool)))
                    elif func in ("sum", "sum0"):
                        total = jn.sum(jn.where(live, av, 0))[None]
                        cnt = jn.sum(live.astype(jn.int64))
                        outs.append((total, jn.zeros(1, dtype=bool)
                                     if func == "sum0"
                                     else (cnt == 0)[None]))
                    elif func in ("min", "max"):
                        if av.dtype == jn.int64:
                            fill = (jn.iinfo(jn.int64).max if func == "min"
                                    else jn.iinfo(jn.int64).min)
                        else:
                            fill = jn.inf if func == "min" else -jn.inf
                        red = jn.min if func == "min" else jn.max
                        r = red(jn.where(live, av, fill))[None]
                        cnt = jn.sum(live.astype(jn.int64))
                        outs.append((r, (cnt == 0)[None]))
                    else:  # pragma: no cover
                        raise ValueError(func)
                n_valid = jn.sum(valid.astype(jn.int64))
                first_orig = jn.argmax(valid)[None]
                items = [n_valid[None], first_orig]
                for v, m in outs:
                    items += [v, m]
                return pack_arrays(kernel_schema, items)
            return kernel, kernel_schema

        kernel, kernel_schema = make_kernel()
        return _stackable_jit(kernel, "packed", 2, make_kernel), \
            kernel_schema
    fn, schema = progcache.get(key, build)
    if rnd is not None and rnd.replaying:
        got = rnd.consume(key, (dev_cols, mask_arr), params)
        if got is not None:
            tag, val = got
            vals = unpack_host(val, schema) if tag == "host" \
                else unpack_flat(val, schema)
            return _unpack_scalar_agg(vals)
    return _unpack_scalar_agg(unpack_flat(
        fn(dev_cols, mask_arr, _params_dev(params)), schema))


def fused_segment_aggregate_sharded(mesh, dev_cols, gid_dev,
                                    n_segments: int, agg_specs, arg_exprs,
                                    n_rows: int, mask,
                                    program_key: tuple = (), params=None):
    """Multi-chip variant of the fused aggregate (SURVEY §2.11 P5: the
    partial/final split AS a reduce-scatter schema): rows shard over the
    mesh axis, each chip segment-reduces its shard with arguments evaluated
    on-device, partial tables merge with psum/pmin/pmax over ICI.

    Inputs must be padded to a bucket divisible by the mesh size (power-of-
    two buckets over power-of-two meshes always are)."""
    from ..parallel import dist
    from . import shardops
    ROWS, WHOLE = dist.specs()
    j = jax()
    jn = jnp()
    nb = int(gid_dev.shape[0])
    n_dev = dist.mesh_shards(mesh)
    assert nb % n_dev == 0, (nb, n_dev)
    ns = bucket(max(n_segments, 1))
    # the shard_map spec is frozen per closure: the per-slot structure of
    # dev_cols (absent / mask-only / full) MUST key the cache or a
    # same-program query with a different column layout reuses a
    # mismatched spec
    dev_shape = tuple(0 if c is None else (1 if c[0] is None else 2)
                      for c in dev_cols)
    mask_fn, mask_key, mask_arr = _mask_parts(mask)
    key = ("seg_sharded", tuple(agg_specs), program_key, mask_key, ns, nb,
           ("shards", n_dev), dev_shape)

    def build():
        arg_fns = [_lower_arg(e) for e in arg_exprs]

        def kernel(cols, gid, mask_in, pr):
            rows_local = gid.shape[0]
            shard = j.lax.axis_index("shard")
            base = shard.astype(jn.int64) * rows_local
            if mask_fn is not None:
                valid = mask_fn(cols, pr, jn.arange(rows_local) + base)
            else:
                valid = mask_in
            seg = _SegReduce(j, jn, gid, valid, ns)
            presence_local, first_local = seg.presence_first()
            presence = dist.mesh_sum(presence_local)
            # local first indexes THIS shard; absent segments carry the
            # sentinel rows_local, which must map to the global max (nb-1)
            # or pmin would prefer an empty low shard over a real high one
            first_global = jn.where(first_local >= rows_local, nb - 1,
                                    first_local + base)
            first_orig = dist.mesh_min(first_global)
            outs = _fused_agg_outs(
                j, jn, agg_specs, arg_fns, cols, gid, valid, ns, presence,
                merge_sum=dist.mesh_sum,
                merge_min=dist.mesh_min, merge_max=dist.mesh_max,
                seg=seg, pr=pr)
            return presence, first_orig, outs

        col_spec = tuple(
            ((ROWS if c[0] is not None else None, ROWS)
             if c is not None else None)
            for c in dev_cols)
        # outputs are replicated by construction (psum, dist.mesh_min/max);
        # the gather-and-reduce merges are beyond the static checker
        sm = dist.shard_map_unchecked(
            kernel, mesh=mesh,
            in_specs=(col_spec, ROWS, ROWS, (WHOLE, WHOLE)),
            out_specs=(WHOLE, WHOLE, [(WHOLE, WHOLE)] * len(agg_specs)))
        kernel_schema: list = []

        def packed(cols, gid, mask_in, pr):
            presence, first_orig, outs = sm(cols, gid, mask_in, pr)
            items = [presence, first_orig]
            for v, m in outs:
                items += [v, m]
            return pack_arrays(kernel_schema, items)
        return counted_jit(packed), kernel_schema
    pfn, schema = progcache.get(key, build)
    shardops.note_round(nb // n_dev)
    dist.note_dispatch(mesh)
    vals = unpack_flat(pfn(tuple(dev_cols), gid_dev, mask_arr,
                           _params_dev(params, dist.whole(mesh))), schema)
    presence, first_orig = vals[0], vals[1]
    rest = vals[2:]
    present = np.nonzero(presence > 0)[0]
    present = present[present < n_segments]
    out_aggs = [(rest[2 * i][present], rest[2 * i + 1][present])
                for i in range(len(rest) // 2)]
    return present, out_aggs, first_orig[present]


def _scalar_agg_kernel(specs: tuple):
    """No-GROUP-BY aggregation: pure masked reductions — no sort at all
    (the reference's stream-agg analogue for a single global group).
    Returns (jitted fn, schema) with all outputs in one packed buffer."""
    j = jax()
    jn = jnp()
    schema: list = []

    def kernel(valid, arg_vals, arg_nulls):
        outs = []
        ai = 0
        for func, has_arg in specs:
            if has_arg:
                av = arg_vals[ai]
                an = arg_nulls[ai]
                ai += 1
            if func == "count_star":
                outs.append((jn.sum(valid.astype(jn.int64))[None],
                             jn.zeros(1, dtype=bool)))
            elif func == "count":
                live = valid & ~an
                outs.append((jn.sum(live.astype(jn.int64))[None],
                             jn.zeros(1, dtype=bool)))
            elif func in ("sum", "sum_int", "sum0"):
                live = valid & ~an
                total = jn.sum(jn.where(live, av, 0))[None]
                cnt = jn.sum(live.astype(jn.int64))
                outs.append((total, jn.zeros(1, dtype=bool)
                             if func == "sum0" else (cnt == 0)[None]))
            elif func in ("min", "max"):
                live = valid & ~an
                if av.dtype == jn.int64:
                    fill = (jn.iinfo(jn.int64).max if func == "min"
                            else jn.iinfo(jn.int64).min)
                else:
                    fill = jn.inf if func == "min" else -jn.inf
                red = jn.min if func == "min" else jn.max
                r = red(jn.where(live, av, fill))[None]
                cnt = jn.sum(live.astype(jn.int64))
                outs.append((r, (cnt == 0)[None]))
            else:  # pragma: no cover
                raise ValueError(func)
        n_valid = jn.sum(valid.astype(jn.int64))
        first_orig = jn.argmax(valid)[None]  # first valid original row
        items = [n_valid[None], first_orig]
        for v, m in outs:
            items += [v, m]
        return pack_arrays(schema, items)

    return counted_jit(kernel), schema


def scalar_aggregate(agg_specs, arg_cols, n_rows: int,
                     filter_mask: np.ndarray = None):
    """Host wrapper for the global-group aggregate.  Returns
    (out_aggs, first_orig) with one output row when any row survives the
    mask, zero otherwise — same contract slice as group_aggregate."""
    jn = jnp()
    nb = bucket(max(n_rows, 1))
    valid = np.zeros(nb, dtype=bool)
    if filter_mask is not None:
        valid[:n_rows] = filter_mask
    else:
        valid[:n_rows] = True
    av = [h2d_pad(v, nb) for v, _ in arg_cols]
    an = [h2d_pad(m, nb, True) for _, m in arg_cols]
    key = ("scalar_agg", tuple(agg_specs), nb,
           tuple(str(v.dtype) for v in av))
    fn, schema = progcache.get(key,
                               lambda: _scalar_agg_kernel(tuple(agg_specs)))
    return _unpack_scalar_agg(unpack_flat(fn(h2d(valid), av, an),
                                          schema))


# =========================================================================
# equi-join (single int64/float64 key): sort + searchsorted + expand
# =========================================================================


def _join_count_kernel():
    j = jax()
    jn = jnp()

    def kernel(lk, ln, lvalid, rk, rn, rvalid):
        r_live = rvalid & ~rn
        # dead rows get a +max sentinel; a LIVE key can equal the
        # sentinel, so sort (key, dead-flag) lexicographically — live
        # rows first within an equal-key run — and count live rows per
        # window via a prefix sum instead of clipping by the live total
        # (the clip was wrong when sentinels interleaved a live max key)
        sentinel = (jn.iinfo(jn.int64).max if rk.dtype == jn.int64
                    else jn.inf)
        rk_clean = jn.where(r_live, rk, sentinel)
        dead = (~r_live).astype(jn.int8)
        rperm = jn.lexsort([dead, rk_clean])  # primary: key; live first
        rs = rk_clean[rperm]
        pref = jn.cumsum(r_live[rperm].astype(jn.int64))

        def live_upto(p):
            return jn.where(p > 0, pref[jn.maximum(p - 1, 0)], 0)
        lo = jn.searchsorted(rs, lk, side="left")
        hi = jn.searchsorted(rs, lk, side="right")
        l_live = lvalid & ~ln
        counts = jn.where(l_live, live_upto(hi) - live_upto(lo), 0)
        total = jn.sum(counts)
        # outer-mode output size: unmatched VALID left rows emit one row
        eff_total = total + jn.sum((lvalid & (counts == 0)).astype(jn.int64))
        return counts, lo, rperm, jn.stack([total, eff_total])

    return counted_jit(kernel)


def _join_expand_kernel(outer: bool, ob2: int):
    """Expansion packed to the exact output bucket: the totals are synced
    before this runs, so li/ri download exactly bucket(n_out) rows in ONE
    transfer instead of three upper-bound-sized ones."""
    j = jax()
    jn = jnp()
    schema: list = []

    def kernel(counts, lo, rperm, lvalid):
        out_idx = jn.arange(ob2)
        # outer mode: unmatched live-left rows emit one row with ri = -1
        eff_counts = jn.where(outer & lvalid & (counts == 0), 1, counts) \
            if outer else counts
        eff_starts = jn.cumsum(eff_counts) - eff_counts
        li = jn.searchsorted(eff_starts, out_idx, side="right") - 1
        li = jn.clip(li, 0, counts.shape[0] - 1)
        pos = out_idx - eff_starts[li]
        matched = counts[li] > 0
        ridx = jn.clip(lo[li] + pos, 0, rperm.shape[0] - 1)
        ri = jn.where(matched, rperm[ridx], -1)
        return pack_arrays(schema, [li, ri])

    return counted_jit(kernel), schema


def _np_join_expand(lk, ln, lv, rk, rn, rv, outer: bool):
    """Host twin of the expansion join: identical (li, ri) CONTRACT AND
    ORDER (probe-major; within a probe row, build rows in stable
    key-sorted order) so switching paths never reorders results.  Dense
    int64 build keys use a direct-address CSR (bincount starts/counts)
    instead of two searchsorted passes."""
    r_live = rv & ~rn
    bidx = np.nonzero(r_live)[0]
    bk = rk[bidx]
    l_live = lv & ~ln
    n_l = len(lk)
    if len(bk) == 0:
        if outer:
            li = np.nonzero(lv)[0]
            return (li.astype(np.int64),
                    np.full(len(li), -1, dtype=np.int64))
        z = np.empty(0, dtype=np.int64)
        return z, z
    order = np.argsort(bk, kind="stable")
    brow = bidx[order]          # build rows, key-sorted, stable
    if bk.dtype == np.int64:
        kmin = int(bk.min())
        card = int(bk.max()) - kmin + 1
    else:
        card = None
    if card is not None and card <= max(1 << 22, 4 * len(bk)):
        cnt_k = np.bincount(bk - kmin, minlength=card)
        starts_k = np.concatenate(([0], np.cumsum(cnt_k)[:-1]))
        idx = np.clip(lk - kmin, 0, card - 1)
        in_r = l_live & (lk >= kmin) & (lk < kmin + card)
        lo = np.where(in_r, starts_k[idx], 0)
        counts = np.where(in_r, cnt_k[idx], 0)
    else:
        bk_s = bk[order]
        lo = np.searchsorted(bk_s, lk, side="left")
        hi = np.searchsorted(bk_s, lk, side="right")
        counts = np.where(l_live, hi - lo, 0)
    eff = np.where(lv & (counts == 0), 1, counts) if outer else counts
    total = int(eff.sum())
    if total == 0:
        z = np.empty(0, dtype=np.int64)
        return z, z
    li = np.repeat(np.arange(n_l, dtype=np.int64), eff)
    starts = np.cumsum(eff) - eff
    pos = np.arange(total, dtype=np.int64) - starts[li]
    matched = counts[li] > 0
    ridx = np.minimum(lo[li] + pos, len(brow) - 1)
    ri = np.where(matched, brow[ridx], -1)
    return li, ri.astype(np.int64)


def join_match(lkey: Tuple[np.ndarray, np.ndarray], n_left: int,
               rkey: Tuple[np.ndarray, np.ndarray], n_right: int,
               outer: bool = False, lvalid: np.ndarray = None,
               rvalid: np.ndarray = None) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (left_indices, right_indices) of matching row pairs; for
    outer, unmatched VALID left rows appear once with right index -1.
    `lvalid`/`rvalid` fold side filters into the kernel's masks so callers
    skip host compaction AND keep bucket shapes stable across differently
    selective filters (one TPU compile per table size, not per filter).
    Host-array inputs on the CPU backend run the numpy twin."""
    if (isinstance(lkey[0], np.ndarray) and isinstance(rkey[0], np.ndarray)
            and host_kernels_ok()):
        lv = np.ones(n_left, dtype=bool) if lvalid is None \
            else np.asarray(lvalid[:n_left], dtype=bool)
        rv = np.ones(n_right, dtype=bool) if rvalid is None \
            else np.asarray(rvalid[:n_right], dtype=bool)
        host_dispatch()
        return _np_join_expand(
            np.asarray(lkey[0])[:n_left], np.asarray(lkey[1])[:n_left],
            lv, np.asarray(rkey[0])[:n_right],
            np.asarray(rkey[1])[:n_right], rv, outer)
    jn = jnp()
    nlb, nrb = bucket(max(n_left, 1)), bucket(max(n_right, 1))
    lv = np.zeros(nlb, dtype=bool)
    lv[:n_left] = lvalid if lvalid is not None else True
    rv = np.zeros(nrb, dtype=bool)
    rv[:n_right] = rvalid if rvalid is not None else True
    def dev(a, n, fill):
        # already-padded device arrays (replica-memoized keys) pass through
        if isinstance(a, np.ndarray):
            return h2d_pad(a, n, fill)
        assert a.shape[0] == n, (a.shape, n)
        return a
    lk = dev(lkey[0], nlb, 0)
    ln = dev(lkey[1], nlb, True)
    rk = dev(rkey[0], nrb, 0)
    rn = dev(rkey[1], nrb, True)
    ck = ("join_count", nlb, nrb, str(lk.dtype), str(rk.dtype))
    cfn = progcache.get(ck, _join_count_kernel)
    lv_dev = h2d(lv)
    counts, lo, rperm, totals = cfn(lk, ln, lv_dev, rk, rn, h2d(rv))
    totals = d2h(totals)  # ONE scalar-pair sync
    n_out = int(totals[1]) if outer else int(totals[0])
    if n_out == 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64))
    ob2 = bucket(n_out)
    ek = ("join_expand", outer, nlb, nrb, ob2)
    efn, schema = progcache.get(ek,
                                lambda: _join_expand_kernel(outer, ob2))
    li, ri = unpack_flat(efn(counts, lo, rperm, lv_dev), schema)
    return li[:n_out], ri[:n_out]


def _unique_join_kernel(build_sorted: bool = False):
    j = jax()
    jn = jnp()

    def kernel(lk, ln, lvalid, rk, rn, rvalid):
        r_live = rvalid & ~rn
        sentinel = (jn.iinfo(jn.int64).max if rk.dtype == jn.int64
                    else jn.inf)
        rk_clean = jn.where(r_live, rk, sentinel)
        if build_sorted:
            # build keys ascend among live rows with dead rows at the
            # tail (a single-key aggregate output): the sentinel rewrite
            # preserves order, so the argsort is the identity
            rs = rk_clean
            cand_all = jn.arange(rs.shape[0], dtype=jn.int64)
        else:
            # live rows first within an equal-key run, so a live key
            # equal to the sentinel is FOUND (searchsorted 'left' lands
            # on it) instead of shadowed by an interleaved dead row
            dead = (~r_live).astype(jn.int8)
            rperm = jn.lexsort([dead, rk_clean])
            rs = rk_clean[rperm]
            cand_all = rperm
        n_r_live = jn.sum(r_live.astype(jn.int32))
        pos = jn.searchsorted(rs, lk, side="left")
        in_range = pos < n_r_live
        cand = cand_all[jn.clip(pos, 0, rs.shape[0] - 1)]
        l_live = lvalid & ~ln
        match = l_live & in_range & (rs[jn.clip(pos, 0, rs.shape[0] - 1)]
                                     == lk)
        # a dead row's sentinel can collide with a LIVE max-valued key;
        # the candidate itself must be live, not just key-equal
        match = match & r_live[cand]
        return match, cand, jn.sum(match.astype(jn.int64))

    return counted_jit(kernel)


def _unique_pick_kernel(ob: int, nlb: int, outer: bool):
    """Phase 2 of the unique join: compact (inner: matched rows; outer:
    all valid left rows) device-side to a static bucket and pack li/ri
    into one download."""
    j = jax()
    jn = jnp()
    schema: list = []

    def kernel(match, cand, lvalid):
        rows = lvalid if outer else match
        li = jn.nonzero(rows, size=ob, fill_value=nlb)[0]
        safe = jn.minimum(li, nlb - 1)
        ri = jn.where(match[safe], cand[safe], -1)
        return pack_arrays(schema, [li, ri])

    return counted_jit(kernel), schema


def host_kernels_ok() -> bool:
    """True when numpy kernel twins should serve host-array inputs: the
    XLA:CPU backend (where device sort/searchsorted run serially) and no
    TINYSQL_DEVICE_JOIN_ONLY override (tests force the device kernels
    with it).  The ONE definition every host-vs-device routing decision
    shares."""
    if os.environ.get("TINYSQL_DEVICE_JOIN_ONLY"):
        return False
    try:
        return jax().default_backend() == "cpu"
    except Exception:
        return False


def _np_unique_join(lk, ln, lv, rk, rn, rv, outer: bool):
    """Host twin of the unique-join kernel (same li/ri contract and tie
    semantics): on XLA:CPU the device sort+searchsorted runs serially
    while numpy's is substantially faster — the same backend-aware kernel
    choice _topk_single makes."""
    r_live = rv & ~rn
    bidx = np.nonzero(r_live)[0]
    bk = rk[bidx]
    l_live = lv & ~ln
    if len(bk) == 0:
        if outer:
            # ALL valid left rows survive (NULL keys null-extend)
            li = np.nonzero(lv)[0]
            return li, np.full(len(li), -1, dtype=np.int64)
        z = np.empty(0, dtype=np.int64)
        return z, z
    if bk.dtype == np.int64:
        kmin = int(bk.min())
        kmax = int(bk.max())
        card = kmax - kmin + 1
    else:
        card = None  # float keys: range addressing is meaningless
    if card is not None and card <= max(1 << 22, 4 * len(bk)):
        # direct-address table over the build key range (~10x faster
        # than searchsorted per probe; devpipe's pos_table twin)
        tbl = np.full(card, -1, dtype=np.int64)
        tbl[bk - kmin] = bidx
        idx = np.clip(lk - kmin, 0, card - 1)
        cand = tbl[idx]
        match = (l_live & (lk >= kmin) & (lk <= kmax) & (cand >= 0))
        if outer:
            li = np.nonzero(lv)[0]
            ri = np.where(match[li], cand[li], -1)
            return li.astype(np.int64), ri.astype(np.int64)
        li = np.nonzero(match)[0]
        return li.astype(np.int64), cand[li].astype(np.int64)
    order = np.argsort(bk, kind="stable")
    bk_s = bk[order]
    brow = bidx[order]
    pos = np.searchsorted(bk_s, lk, side="left")
    pos_c = np.minimum(pos, len(bk_s) - 1)
    match = l_live & (pos < len(bk_s)) & (bk_s[pos_c] == lk)
    if outer:
        li = np.nonzero(lv)[0]
        ri = np.where(match[li], brow[pos_c[li]], -1)
        return li.astype(np.int64), ri.astype(np.int64)
    li = np.nonzero(match)[0]
    return li.astype(np.int64), brow[pos_c[li]].astype(np.int64)


def unique_join_match(lkey, n_left: int, rkey, n_right: int,
                      outer: bool = False, lvalid: np.ndarray = None,
                      rvalid: np.ndarray = None,
                      build_sorted: bool = False):
    """join_match fast path when the RIGHT (build) key is UNIQUE among
    its live rows (clustered pk, or a partial aggregate keyed by the join
    key): each probe row has at most ONE match, so the output size is
    bounded by n_left — no count kernel, no expansion, and no
    device->host size sync.  Same (li, ri) contract as join_match.
    `build_sorted` asserts the build keys already ascend among live rows
    (dead rows at the tail) and skips the device argsort.

    On the CPU backend with HOST key arrays, the match runs in numpy
    (TINYSQL_DEVICE_JOIN_ONLY=1 forces the device kernels, e.g. to
    exercise block-streaming device economics in tests)."""
    if (isinstance(lkey[0], np.ndarray) and isinstance(rkey[0], np.ndarray)
            and host_kernels_ok()):
        lv = np.ones(n_left, dtype=bool) if lvalid is None \
            else np.asarray(lvalid[:n_left], dtype=bool)
        rv = np.ones(n_right, dtype=bool) if rvalid is None \
            else np.asarray(rvalid[:n_right], dtype=bool)
        host_dispatch()
        return _np_unique_join(
            np.asarray(lkey[0])[:n_left], np.asarray(lkey[1])[:n_left],
            lv, np.asarray(rkey[0])[:n_right],
            np.asarray(rkey[1])[:n_right], rv, outer)
    jn = jnp()
    nlb, nrb = bucket(max(n_left, 1)), bucket(max(n_right, 1))
    lv = np.zeros(nlb, dtype=bool)
    lv[:n_left] = lvalid if lvalid is not None else True
    rv = np.zeros(nrb, dtype=bool)
    rv[:n_right] = rvalid if rvalid is not None else True

    def dev(a, n, fill):
        if isinstance(a, np.ndarray):
            return h2d_pad(a, n, fill)
        assert a.shape[0] == n, (a.shape, n)
        return a
    lk = dev(lkey[0], nlb, 0)
    ln = dev(lkey[1], nlb, True)
    rk = dev(rkey[0], nrb, 0)
    rn = dev(rkey[1], nrb, True)
    ck = ("unique_join", nlb, nrb, str(lk.dtype), str(rk.dtype),
          build_sorted)
    fn = progcache.get(ck, lambda: _unique_join_kernel(build_sorted))
    lv_dev = h2d(lv)
    match, cand, n_match = fn(lk, ln, lv_dev, rk, rn, h2d(rv))
    if outer:
        # ALL valid left rows survive — NULL-key rows match nothing and
        # null-extend; the output size is host-known (lv is host-side),
        # so no device sync at all
        n_out = int(np.sum(lv))
    else:
        n_out = int(n_match)  # one scalar sync
    if n_out == 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64))
    ob = min(bucket(n_out), nlb)
    pk = ("unique_pick", ob, nlb, outer)
    pfn, schema = progcache.get(pk,
                                lambda: _unique_pick_kernel(ob, nlb, outer))
    li, ri = unpack_flat(pfn(match, cand, lv_dev), schema)
    return li[:n_out], ri[:n_out]


def _semi_kernel(anti: bool, null_aware: bool):
    """Membership test over the build side (sort + searchsorted — the
    same machinery the join kernels ride): per probe row, does ANY live
    build row share its key?  Semi keeps members; anti keeps
    non-members, with the NOT IN three-valued ladder when null_aware:
    an empty build side keeps EVERY valid probe row, any NULL build key
    keeps none, and a NULL probe key never passes."""
    j = jax()
    jn = jnp()

    def kernel(lk, ln, lvalid, rk, rn, rvalid):
        r_live = rvalid & ~rn
        sentinel = (jn.iinfo(jn.int64).max if rk.dtype == jn.int64
                    else jn.inf)
        rk_clean = jn.where(r_live, rk, sentinel)
        # live rows first within an equal-key run so a live key equal to
        # the sentinel is still FOUND (same trick as the join kernels)
        dead = (~r_live).astype(jn.int8)
        rperm = jn.lexsort([dead, rk_clean])
        rs = rk_clean[rperm]
        pos = jn.searchsorted(rs, lk, side="left")
        pos_c = jn.clip(pos, 0, rs.shape[0] - 1)
        l_live = lvalid & ~ln
        member = (l_live & (pos < rs.shape[0]) & (rs[pos_c] == lk)
                  & r_live[rperm][pos_c])
        if not anti:
            keep = member
        else:
            # build-side shape scalars (traced): total live rows incl.
            # NULL keys, and whether any live row's key IS NULL
            n_build = jn.sum(rvalid.astype(jn.int64))
            if null_aware:
                has_null = jn.any(rvalid & rn)
                keep = jn.where(
                    n_build == 0, lvalid,
                    jn.where(has_null, False, l_live & ~member))
            else:
                keep = jn.where(n_build == 0, lvalid, lvalid & ~member)
        return keep, jn.sum(keep.astype(jn.int64))

    return counted_jit(kernel)


def _semi_pick_kernel(ob: int, nlb: int):
    """Compact the kept probe rows device-side to a static bucket — one
    packed download of the surviving indices."""
    j = jax()
    jn = jnp()
    schema: list = []

    def kernel(keep):
        li = jn.nonzero(keep, size=ob, fill_value=nlb)[0]
        return pack_arrays(schema, [li])

    return counted_jit(kernel), schema


def _np_semi_match(lk, ln, lv, rk, rn, rv, anti: bool, null_aware: bool):
    """Host twin of the semi/anti membership kernel: identical keep
    semantics and probe-order output."""
    n_build = int(rv.sum())
    if n_build == 0:
        # empty subquery: semi keeps nothing, anti keeps every valid
        # probe row (NULL probe keys included — NOT IN () is TRUE)
        keep = lv if anti else np.zeros(len(lk), dtype=bool)
        return np.nonzero(keep)[0].astype(np.int64)
    if anti and null_aware and bool((rv & rn).any()):
        return np.empty(0, dtype=np.int64)  # NULL in the build set
    bk = rk[rv & ~rn]
    l_live = lv & ~ln
    member = np.zeros(len(lk), dtype=bool)
    if len(bk):
        member[l_live] = np.isin(lk[l_live], bk)
    if anti:
        keep = lv & ~member & (~ln if null_aware else True)
    else:
        keep = member
    return np.nonzero(keep)[0].astype(np.int64)


def semi_join_match(lkey, n_left: int, rkey, n_right: int,
                    anti: bool = False, null_aware: bool = False,
                    lvalid: np.ndarray = None,
                    rvalid: np.ndarray = None) -> np.ndarray:
    """Probe-row indices surviving a semi (membership) or anti
    (non-membership) test against the build side, in probe order.
    Same host-vs-device routing contract as join_match: host numpy twin
    on the CPU backend, padded-bucket device kernels otherwise (the
    progcache key is shape-only, so literal changes stay cache HITs)."""
    if (isinstance(lkey[0], np.ndarray) and isinstance(rkey[0], np.ndarray)
            and host_kernels_ok()):
        lv = np.ones(n_left, dtype=bool) if lvalid is None \
            else np.asarray(lvalid[:n_left], dtype=bool)
        rv = np.ones(n_right, dtype=bool) if rvalid is None \
            else np.asarray(rvalid[:n_right], dtype=bool)
        host_dispatch()
        return _np_semi_match(
            np.asarray(lkey[0])[:n_left],
            np.asarray(lkey[1])[:n_left], lv,
            np.asarray(rkey[0])[:n_right],
            np.asarray(rkey[1])[:n_right], rv, anti, null_aware)
    jn = jnp()
    nlb, nrb = bucket(max(n_left, 1)), bucket(max(n_right, 1))
    lv = np.zeros(nlb, dtype=bool)
    lv[:n_left] = lvalid if lvalid is not None else True
    rv = np.zeros(nrb, dtype=bool)
    rv[:n_right] = rvalid if rvalid is not None else True

    def dev(a, n, fill):
        if isinstance(a, np.ndarray):
            return h2d_pad(a, n, fill)
        assert a.shape[0] == n, (a.shape, n)
        return a
    lk = dev(lkey[0], nlb, 0)
    ln = dev(lkey[1], nlb, True)
    rk = dev(rkey[0], nrb, 0)
    rn = dev(rkey[1], nrb, True)
    if lk.dtype != rk.dtype:
        lk = lk.astype(jn.float64)
        rk = rk.astype(jn.float64)
    ck = ("semi_match", anti, null_aware, nlb, nrb,
          str(lk.dtype), str(rk.dtype))
    fn = progcache.get(ck, lambda: _semi_kernel(anti, null_aware))
    keep, n_keep = fn(lk, ln, h2d(lv), rk, rn, h2d(rv))
    n_out = int(n_keep)  # one scalar sync
    if n_out == 0:
        return np.empty(0, dtype=np.int64)
    ob = min(bucket(n_out), nlb)
    pk = ("semi_pick", ob, nlb)
    pfn, schema = progcache.get(pk, lambda: _semi_pick_kernel(ob, nlb))
    (li,) = unpack_flat(pfn(keep), schema)
    return li[:n_out]


# =========================================================================
# sort / top-k
# =========================================================================


def _sort_kernel(descs: tuple):
    j = jax()
    jn = jnp()

    def kernel(key_vals, key_nulls, valid):
        # reversed order: lexsort's LAST operand is primary
        ops = []
        for i in range(len(key_vals) - 1, -1, -1):
            v, m, desc = key_vals[i], key_nulls[i], descs[i]
            vv = jn.where(m, 0, v)
            if desc:
                # ~v is the overflow-free order-reversing bijection on int64
                # (-v overflows at int64 min, which the unsigned XOR map hits)
                vv = ~vv if vv.dtype == jn.int64 else -vv
                rank = jn.where(m, 1, 0).astype(jn.int8)  # NULL last
            else:
                rank = jn.where(m, 0, 1).astype(jn.int8)  # NULL first
            ops.append(vv)
            ops.append(rank)
        ops.append(jn.where(valid, 0, 1).astype(jn.int8))  # invalid last
        return jn.lexsort(ops)

    return counted_jit(kernel)


def sort_permutation(key_cols: List[Tuple[np.ndarray, np.ndarray]],
                     descs: List[bool], n_rows: int) -> np.ndarray:
    jn = jnp()
    nb = bucket(max(n_rows, 1))
    valid = np.zeros(nb, dtype=bool)
    valid[:n_rows] = True
    kv = [h2d_pad(v, nb) for v, _ in key_cols]
    kn = [h2d_pad(m, nb, True) for _, m in key_cols]
    key = ("sort", tuple(descs), nb, tuple(str(v.dtype) for v in kv))
    fn = progcache.get(key, lambda: _sort_kernel(tuple(descs)))
    perm = d2h(fn(kv, kn, h2d(valid)))
    return perm[:n_rows]


#: up to this many leading rows, :func:`lex_head` selects them one by one
#: instead of sorting every row
LEX_SELECT_MAX = 128


def lex_head(ops, k: int):
    """Inside jit: the first ``k`` entries of ``jnp.lexsort(ops)`` (last
    operand primary, stable: ties go to the lowest row).  For a short
    head (a TopN's window) it takes ``k`` rounds of lexicographic
    arg-min — per round one masked min per operand, a loop whose body
    compiles once — because a full sort of an emulated 64-bit lane is
    the dearest thing the TPU compiler is asked for here: its compile
    time grows with the lane (10 s for ONE int64 operand at the 2^13
    bucket), and TPC-H Q3's fused program at SF=1, whose TopN sorted
    five operands at 2^21, took 1455 s to compile against 4.5 s with
    this (CHANGES.md, PR 22).  A longer head falls back to the full
    sort."""
    with jax().named_scope("lex_head"):
        return _lex_head(ops, k)


def _lex_head(ops, k: int):
    from jax import lax
    jn = jnp()
    n = int(ops[0].shape[0])
    if k > LEX_SELECT_MAX or k > n:
        return jn.lexsort(ops)[:k]
    idx = jn.arange(n)
    keys = []
    for op in reversed(ops):  # primary first
        if jn.issubdtype(op.dtype, jn.floating):
            # min/== cannot place a NaN; sort puts it last, beside +inf
            op = jn.where(jn.isnan(op), jn.inf, op)
            keys.append((op, jn.inf))
        else:
            keys.append((op, jn.iinfo(op.dtype).max))

    def pick_next(i, state):
        alive, head = state
        cand = alive
        for kv, worst in keys:
            cand = cand & (kv == jn.min(jn.where(cand, kv, worst)))
        row = jn.min(jn.where(cand, idx, n))
        return alive & (idx != row), head.at[i].set(row)

    _, head = lax.fori_loop(0, k, pick_next,
                            (jn.ones(n, dtype=bool),
                             jn.zeros(k, dtype=idx.dtype)))
    return head


def _topk_kernel(kb: int):
    j = jax()

    def kernel(score):
        _, ids = j.lax.top_k(score, kb)
        return ids

    return counted_jit(kernel)


def _topk_single(key, desc: bool, n_rows: int, k: int):
    """lax.top_k fast path for ONE sort key: O(n·log k) selection instead
    of a full O(n·log n) sort.  Maps the key onto a single total-order
    score (bigger = earlier in output); NULL ordering (first for asc,
    last for desc) and padding share a worst/best sentinel — lax.top_k's
    stable lowest-index tie-break then prefers real rows, which all sit
    before the padding.  Returns None when an exact mapping isn't safe
    (key values touching the sentinel range, non-finite floats)."""
    v, m = key
    nb = bucket(max(n_rows, 1))
    score = _primary_score(key, desc, n_rows)
    if score is None:
        return None
    pad_val = np.iinfo(np.int64).min if v.dtype == np.int64 else -np.inf
    if jax().default_backend() == "cpu":
        # XLA:CPU's top_k lowering barely beats the full sort; host
        # partition selection is ~100x faster there.  Exact stable-tie
        # semantics: all rows above the threshold, then lowest-index rows
        # AT the threshold.
        host_dispatch()
        s = score[:n_rows]
        kk = min(k, n_rows)
        t = np.partition(s, n_rows - kk)[n_rows - kk]
        above = np.nonzero(s > t)[0]
        at = np.nonzero(s == t)[0][:kk - len(above)]
        ids = np.concatenate([above, at])
        return ids[np.lexsort((ids, -s[ids]))]
    jn = jnp()
    kb = bucket(max(k, 1))
    if kb > nb:
        return None
    ck = ("topk", nb, kb, str(score.dtype))
    fn = progcache.get(ck, lambda: _topk_kernel(kb))
    ids = d2h(fn(h2d_pad(score, nb, pad_val)))[:k]
    return ids[ids < n_rows]  # k may exceed the row count


def _primary_score(key, desc: bool, n_rows: int):
    """Map one sort key onto a total-order score (bigger = earlier) with
    NULL ordering folded in, or None when unsafe.  Shared by the single-
    and multi-key top-k selection paths."""
    v, m = key
    if v.dtype == object or getattr(v.dtype, "kind", "") == "U":
        return None
    if v.dtype == np.int64:
        info = np.iinfo(np.int64)
        vmin = int(v.min()) if n_rows else 0
        vmax = int(v.max()) if n_rows else 0
        if vmin < info.min + 2 or vmax > info.max - 2:
            return None
        if desc:  # null last -> worst score
            return np.where(m, info.min + 1, v)
        return np.where(m, info.max, ~v)  # asc: ~v reverses; null first
    if v.dtype == np.float64:
        w = np.where(m, 0.0, v)
        if n_rows and not np.isfinite(w).all():
            return None
        if desc:
            return np.where(m, -np.inf, w)
        return np.where(m, np.inf, -w)
    return None


def _np_lexsort_perm(key_cols, descs, sub=None) -> np.ndarray:
    """numpy twin of _sort_kernel over the row subset `sub` (None = all
    rows, no subset copies): same operand order, same NULL first/last
    semantics, stable — restricted to a candidate subset it reproduces
    the full sort's relative order."""
    ops = []
    for i in range(len(key_cols) - 1, -1, -1):
        v, m = key_cols[i]
        if sub is not None:
            v, m = v[sub], m[sub]
        vv = np.where(m, 0, v)
        if descs[i]:
            vv = ~vv if vv.dtype == np.int64 else -vv
            rank = np.where(m, 1, 0).astype(np.int8)   # NULL last
        else:
            rank = np.where(m, 0, 1).astype(np.int8)   # NULL first
        ops.append(vv)
        ops.append(rank)
    return np.lexsort(ops)


def host_sort_permutation(key_cols, descs, n_rows: int) -> np.ndarray:
    """Full sort permutation computed ON HOST (numpy lexsort with the
    device kernel's exact semantics): the budget-respecting path for
    tables above tidb_device_block_rows, where uploading every sort key
    whole would violate the device memory budget."""
    host_dispatch()
    keys = [(v[:n_rows], m[:n_rows]) for v, m in key_cols]
    return _np_lexsort_perm(keys, descs)


def _topk_multi(key_cols, descs, n_rows: int, k: int):
    """Multi-key top-k via primary-key threshold selection: rows scoring
    at or above the k-th primary score are a SUPERSET of the true top-k
    (secondary keys only reorder within primary ties), so the full
    lexsort runs over that small candidate set instead of all rows —
    O(n) selection + O(c log c) sort, vs the O(n log n) full sort that
    XLA:CPU executes serially."""
    score = _primary_score(key_cols[0], descs[0], n_rows)
    if score is None:
        return None
    kk = min(k, n_rows)
    s = np.asarray(score[:n_rows])
    t = np.partition(s, n_rows - kk)[n_rows - kk]
    cand = np.nonzero(s >= t)[0]
    if len(cand) * 4 > n_rows * 3:
        return None  # degenerate ties: the full sort is no worse
    host_dispatch()
    order = _np_lexsort_perm(key_cols, descs, cand)
    return cand[order[:kk]]


def top_k(key_cols: List[Tuple[np.ndarray, np.ndarray]], descs: List[bool],
          n_rows: int, k: int) -> np.ndarray:
    """Top-k row indices in requested order.  Single-key inputs take the
    lax.top_k selection path (VERDICT r1 #10); multi-key selects
    candidates by primary-key threshold and sorts only those; the full
    device sort + slice remains the fallback."""
    if k <= 0 or n_rows <= 0:
        return np.empty(0, dtype=np.int64)
    if len(key_cols) == 1:
        ids = _topk_single(key_cols[0], descs[0], n_rows, k)
        if ids is not None:
            return ids
    else:
        ids = _topk_multi(key_cols, descs, n_rows, k)
        if ids is not None:
            return ids
    perm = sort_permutation(key_cols, descs, n_rows)
    return perm[:k]


# =========================================================================
# bucket prewarming (tools/warm.py)
# =========================================================================

def prewarm_bucket(nb: int, k_buckets=(16, 128)) -> int:
    """AOT-compile (``jit(...).lower().compile()``) the shape-GENERIC
    kernels for one power-of-two bucket, so the first real query over a
    table of that size runs warm.  The structural fused programs
    (aggregate specs, expression lowerings, device masks) are warmed by
    EXECUTING the plan once (tools/warm.py does); this covers the purely
    bucket-keyed kernels a grown table hits next — single-key sort
    permutations and the lax.top_k selection — so a cardinality drift
    into the neighboring bucket never pays a cold XLA compile.  Every
    AOT compile lands in the persistent compilation cache — the
    persistence threshold drops to 0 for the duration, so sub-second
    XLA:CPU compiles persist too.  Returns the number of programs
    compiled; a failed compile is skipped (an unsupported shape must
    never break warming) and logged once, with its key, at WARNING."""
    import logging
    j = jax()
    jn = jnp()
    compiled = 0
    prev_thresh = j.config.jax_persistent_cache_min_compile_time_secs
    j.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    def sds(dt):
        return j.ShapeDtypeStruct((nb,), dt)

    def aot(key, build, *abstract) -> int:
        try:
            progcache.get(key, build).lower(*abstract).compile()
            return 1
        except Exception:  # the boundary: warming goes on, visibly
            logging.getLogger("tinysql_tpu").warning(
                "prewarm: compile of %r failed, skipped", key,
                exc_info=True)
            return 0

    try:
        for dts in ("int64", "float64"):
            dt = jn.int64 if dts == "int64" else jn.float64
            for desc in (False, True):
                compiled += aot(("sort", (desc,), nb, (dts,)),
                                lambda desc=desc: _sort_kernel((desc,)),
                                [sds(dt)], [sds(jn.bool_)], sds(jn.bool_))
            if j.default_backend() == "cpu":
                continue  # _topk_single routes to np.partition on XLA:CPU
            for kb in k_buckets:
                if kb <= nb:
                    compiled += aot(("topk", nb, kb, dts),
                                    lambda kb=kb: _topk_kernel(kb), sds(dt))
    finally:
        j.config.update("jax_persistent_cache_min_compile_time_secs",
                        prev_thresh)
    return compiled

"""Multi-chip distributed query primitives: SPMD over a jax Mesh.

The TPU-native replacement for the reference's distributed communication
backend (SURVEY §2.6/§2.11): KV regions -> mesh shards; coprocessor
scatter-gather (P2) -> data-parallel shard_map; region-sharded operators
(P4/P5) -> the ops/shardops.py sharded tier.  Collectives ride the mesh
axis (ICI on real hardware, host rings on the CPU test mesh); no
NCCL/MPI analogue exists or is needed — XLA inserts the collectives.

What actually ships on this layer today:

- **partial->final aggregation** (P5; PAPERS.md "Global Hash Tables
  Strike Back!", "Partial Partial Aggregates"): each shard reduces its
  row slice to a fixed-shape partial state — segment tables for GROUP
  BY (kernels.fused_segment_aggregate_sharded), scalar accumulator
  lanes for global aggregates (shardops.fused_scalar_aggregate_sharded)
  — merged ONCE over the mesh axis with psum/pmin/pmax.  No shuffle:
  the partial state, not the rows, crosses the interconnect.
- **broadcast join** (P4, small build side): probe rows shard, the
  sorted build side replicates via all_gather, every shard probes
  locally (devpipe's default mesh join; make_broadcast_join_counts is
  the seed demo).
- **shuffle join** (P4, large build side): both sides re-partition BY
  KEY HASH over the mesh with all_to_all (hash_dest_np/_traced +
  exchange_lanes + local_unique_join below, driven by devpipe's
  joinshuf programs), so each shard holds only its hash partition of
  the build table.
- **partitioned build/probe join + semijoin, sharded sort/top-k**
  (ops/shardops.py): the host scatters rows into per-shard blocks with
  THE PR 9 SPILL PARTITIONER (ops/spill.py hash_partition — shard =
  spill partition, one partitioner drives device placement and the
  spill ladder), shards work locally, exact merges (searchsorted rank
  counting, top-k tournaments) happen on-device.

- **layouts** (``rows`` / ``whole`` / ``place`` / ``settle``): THE one
  place that says how a replica lane (row-sharded: each device holds a
  contiguous 1/n of the padded lane), a broadcast table and a merged
  partial state (whole on every device) lie on a mesh.  The replica's
  uploads place their arrays through ``place``, every shard_map's
  ``in_specs`` are ``ROWS`` / ``WHOLE``, and ``settle`` checks at
  dispatch that each input already lies as its program asks — so a
  warm mesh dispatch moves no input between devices.

Policy lives here too: session_mesh/sized_mesh gate on
tidb_mesh_parallel and cache Mesh objects; shard_bucket is the
estRows->shard-count launder the planner annotates plans with;
shardable is the per-dispatch row-bucket gate.  The 1-device outcome of
any gate means "run the single-device kernel" — Tier-1 on CPU is byte
identical because every sharded family degenerates to its unsharded
twin below the thresholds.  Every shard_map in the tree is constructed
through shard_map_fn/shard_map_unchecked (qlint DF805 enforces this).
"""
from __future__ import annotations

import logging
import threading
from functools import partial
from typing import List, Optional, Tuple

import numpy as np

from ..obs import context as _obs
from ..ops import kernels


def shard_map_fn():
    """(shard_map, PartitionSpec) — every mesh kernel imports through
    here (qlint DF805)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec
    return shard_map, PartitionSpec


def shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """shard_map for kernels whose outputs are replicated by construction
    (all_gather + pure compute): the static replication checker cannot
    prove it, so it is turned off."""
    shard_map, _ = shard_map_fn()
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def mesh_sum(x, axis: str = "shard"):
    """Inside shard_map: elementwise sum over the mesh axis — the merge
    of per-shard partial states.  Named ``mesh.psum`` in a profile."""
    jax = kernels.jax()
    with jax.named_scope("mesh.psum"):
        return jax.lax.psum(x, axis)


def mesh_gather(x, axis: str = "shard", tiled: bool = False):
    """Inside shard_map: every shard's ``x`` on every shard (stacked on
    a new leading axis, or concatenated along the first when ``tiled``).
    Named ``mesh.all_gather`` in a profile."""
    jax = kernels.jax()
    with jax.named_scope("mesh.all_gather"):
        return jax.lax.all_gather(x, axis, tiled=tiled)


def gather_rows(mesh, lanes: list) -> list:
    """In a program over ``mesh``, outside shard_map: each of ``lanes``,
    which lie by rows, whole on every device — one ``mesh.all_gather``
    a lane.  How a view that was computed a row range a device becomes a
    broadcast join's build side."""
    rows_spec, whole_spec = specs()
    return shard_map_unchecked(
        lambda xs: [mesh_gather(x, tiled=True) for x in xs], mesh,
        in_specs=([rows_spec] * len(lanes),),
        out_specs=[whole_spec] * len(lanes))(list(lanes))


def mesh_sum_spans(part, starts, size: int, axis: str = "shard"):
    """Inside shard_map: :func:`mesh_sum` of [size] tables of which
    shard s holds only the run ``part`` [q] that begins at
    ``starts[s]`` (whole on every shard), zero elsewhere.  The runs
    are gathered — n x q elements cross the interconnect, not n x size —
    and added into place one after another: contiguous read-add-writes,
    no scatter; the add carries an entry that two runs share.  A run
    may reach past ``size`` (its tail then adds into padding that is
    cut off), never begin past it."""
    from jax import lax
    parts = mesh_gather(part, axis)
    n, q = parts.shape
    out = kernels.jnp().zeros(size + q, dtype=part.dtype)
    for s_ in range(n):
        at = (starts[s_],)
        out = lax.dynamic_update_slice(
            out, lax.dynamic_slice(out, at, (q,)) + parts[s_], at)
    return out[:size]


def mesh_min(x, axis: str = "shard"):
    """Inside shard_map: elementwise min over the mesh axis.  The TPU
    lowers an all-reduce of an emulated 64-bit type for Sum only
    (``lax.pmin`` of an int64 is refused as UNIMPLEMENTED), so the
    partials — small per-shard tables — are gathered and reduced on
    every shard."""
    return kernels.jnp().min(mesh_gather(x, axis), axis=0)


def mesh_max(x, axis: str = "shard"):
    """Elementwise max over the mesh axis; see :func:`mesh_min`."""
    return kernels.jnp().max(mesh_gather(x, axis), axis=0)


def make_mesh(n_devices: Optional[int] = None):
    """1-D device mesh over axis 'shard' (DP/region axis)."""
    jax = kernels.jax()
    devs = jax.devices()
    n = n_devices or len(devs)
    device_limit_bytes()
    from jax.sharding import Mesh
    return Mesh(np.array(devs[:n]), ("shard",))


_SESSION_MESH = None


def session_mesh(session_vars):
    """The query-execution mesh when the session asks for multi-chip
    (tidb_mesh_parallel) and >=2 devices exist; cached per device set.
    Shared by every mesh-parallel tier (fused aggregate, devpipe join)."""
    if not bool(session_vars.get("tidb_mesh_parallel", 0)):
        return None
    devs = kernels.jax().devices()
    if len(devs) < 2:
        return None
    global _SESSION_MESH
    if _SESSION_MESH is None or _SESSION_MESH.devices.size != len(devs):
        _SESSION_MESH = make_mesh(len(devs))
    return _SESSION_MESH


_SIZED_MESHES: dict = {}


def sized_mesh(n_shards: int):
    """A cached k-device submesh (first k devices) for plans whose
    estRows-driven shard count is below the full device set; k < 2
    degenerates to None = run the single-device kernel."""
    if n_shards < 2:
        return None
    devs = kernels.jax().devices()
    k = min(int(n_shards), len(devs))
    if k < 2:
        return None
    m = _SIZED_MESHES.get(k)
    if m is None or m.devices.size != k:
        m = _SIZED_MESHES[k] = make_mesh(k)
    return m


def mesh_shards(mesh) -> int:
    """Shard count of a mesh — THE sanctioned launder from mesh shape to
    progcache-key literal (qlint DF807: mesh-shape scalars must not mint
    program keys except through here / shard_bucket)."""
    return 0 if mesh is None else int(mesh.devices.size)


#: a shard must expect at least this many rows before fan-out pays for
#: the partition scatter + collectives (estRows-driven; the per-dispatch
#: row-bucket gate `shardable` still applies at runtime)
MIN_SHARD_ROWS = 256


def shard_bucket(est_rows: float, n_devices: int) -> int:
    """estRows -> power-of-two shard count <= n_devices: the planner's
    mesh admissibility output and the OTHER sanctioned mesh-shape
    launder.  1 means 'stay single-device' (the degenerate mesh)."""
    n = 1
    est = max(float(est_rows or 0), 0.0)
    while n * 2 <= n_devices and est >= MIN_SHARD_ROWS * (n * 2):
        n *= 2
    return n


#: A broadcast join holds its build side whole on every device where a
#: partitioned one holds a shard's share of it: what the copies ADD to a
#: device, (n - 1) / n of the build side, may take this share of the
#: device's memory.  The guard is about memory alone (which plan is
#: faster is the cost compare's business, planner/device.py), so it lies
#: between the largest build side that ran broadcast and the smallest
#: that cannot be held: TPC-H SF=10's Q3 joins a [2^24]-row view of 2 or
#: 3 columns whole on every chip, at most 384 MiB, 2.3 % of a v5e, at a
#: peak of 11.2 % (my chip runs, PR 33; flipped to the exchange, that Q3
#: is 1.87 times slower), and the same view at SF=100, [2^28] rows, 6
#: GiB, 38 %, cannot lie beside the lanes.  1/8 is 5 times over the one
#: and 3 times under the other (PERF.md section 6, PR 33).
BROADCAST_MEMORY_SHARE = 1.0 / 8
#: bytes the cost model counts for one column of one row (planner/device
#: _mesh_join_strategy and the executor's run-time check share it)
COST_COLUMN_BYTES = 8.0
#: the budget where a device reports no memory limit (the CPU backend)
NO_LIMIT_BUDGET_BYTES = float(64 << 20)

_DEVICE_LIMIT_BYTES: Optional[float] = None


def device_limit_bytes() -> float:
    """The memory the runtime reports for one device (``bytes_limit``),
    read once a process: ``make_mesh`` asks as it builds the first mesh,
    so no statement's prepare does.  0 where the backend reports none —
    the CPU's devices, quietly; any other platform's with a WARNING,
    because the join budget then falls to NO_LIMIT_BUDGET_BYTES and
    plans differ from a healthy chip's."""
    global _DEVICE_LIMIT_BYTES
    if _DEVICE_LIMIT_BYTES is None:
        dev = kernels.jax().devices()[0]
        try:
            stats = dev.memory_stats() or {}
        except (NotImplementedError, RuntimeError):
            stats = {}
        limit = float(stats.get("bytes_limit", 0) or 0)
        if limit <= 0 and dev.platform != "cpu":
            logging.getLogger("tinysql_tpu").warning(
                "%s reports no memory limit: mesh joins broadcast build "
                "sides up to %d bytes only", dev, NO_LIMIT_BUDGET_BYTES)
        _DEVICE_LIMIT_BYTES = limit
    return _DEVICE_LIMIT_BYTES


def broadcast_budget_bytes() -> float:
    """The most bytes a broadcast mesh join may add to every device:
    BROADCAST_MEMORY_SHARE of a device's memory.  In bytes because the
    cost it guards is: a 2 M-row, two-column build side is 32 MB a chip,
    and a budget counted in rows sent ten times its rows over the mesh
    to spare that (PERF.md section 6, PR 33)."""
    limit = device_limit_bytes()
    return limit * BROADCAST_MEMORY_SHARE if limit > 0 \
        else NO_LIMIT_BUDGET_BYTES


def broadcast_over_budget(build_bytes: float, n_shards: int) -> bool:
    """Whether replicating a build side of ``build_bytes`` (rows x
    columns x COST_COLUMN_BYTES) to ``n_shards`` devices adds more to a
    device than the budget: the planner's estimate and the executor's
    run-time bucket are both held to this one line."""
    return build_bytes * (n_shards - 1) / n_shards > broadcast_budget_bytes()


def shardable(nb: int, mesh) -> bool:
    """Row-bucket gate for sharding over `mesh`: divisible and big enough
    to amortize the collectives."""
    if mesh is None:
        return False
    n = int(mesh.devices.size)
    return nb % n == 0 and nb >= 16 * n


# =========================================================================
# layouts: where an array lies on a mesh
# =========================================================================
# Two layouts cover everything the engine keeps on a mesh.  ROWS: a
# replica lane (values, null mask, dictionary codes, group ids, a
# per-shard index) split along its one axis into n contiguous ranges,
# device i holding range i — upstream's "regions over stores" with a
# region set = one device's row range.  WHOLE: a broadcast join's build
# side, a dense key->row table, a group-key table, a parameter vector, a
# merged partial state — the same array on every device.

def specs() -> tuple:
    """(ROWS, WHOLE): the two layouts as the PartitionSpecs a
    shard_map's ``in_specs`` / ``out_specs`` take."""
    _, P = shard_map_fn()
    return P("shard"), P()


def rows(mesh):
    """Layout of a row-sharded lane over ``mesh``."""
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, specs()[0])


def whole(mesh):
    """Layout of an array held whole on every device of ``mesh``."""
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, specs()[1])


def layout_tag(layout) -> tuple:
    """A layout as a key component — for the replica's memo (a one-chip
    session and a mesh session never hand each other the wrong array)
    and for program keys.  No layout (one device) tags nothing, so
    one-device keys are what they were."""
    if layout is None:
        return ()
    return ("rows" if layout.spec == specs()[0] else "whole",
            mesh_shards(layout.mesh))


#: bytes of replica lanes placed on each device since the process began
#: (a dropped replica version's lanes are not taken off again)
_PLACED: dict = {}
_PLACED_MU = threading.Lock()


def place(host: np.ndarray, layout):
    """Counted upload of a host array straight into its layout on the
    mesh: each device receives only its own part (h2d bytes are charged
    once, at the host buffer's size).  A ``mesh.place`` span; the
    per-device tally feeds ``mesh_resident_bytes_max`` / ``_min``."""
    with _obs.process_span("mesh.place", cat="replica",
                           layout=layout_tag(layout)[0],
                           bytes=int(host.nbytes)):
        out = kernels.h2d(host, layout)
    with _PLACED_MU:
        for sh in out.addressable_shards:
            _PLACED[sh.device.id] = _PLACED.get(sh.device.id, 0) \
                + int(sh.data.nbytes)
        per = [_PLACED.get(d.id, 0) for d in layout.mesh.devices.flat]
    kernels.stats_set("mesh_resident_bytes_max", max(per))
    kernels.stats_set("mesh_resident_bytes_min", min(per))
    return out


def settle(inputs: list, layouts: list) -> list:
    """At a mesh dispatch: every input that is already on the device
    must lie as the program asks (``layouts[i]``; None asks nothing).
    One that lies otherwise is moved and its bytes counted under
    ``reshard_bytes`` — 0 when warm, because the replica memoizes a lane
    under its layout.  Host arrays pass: jit uploads them whole."""
    jax = kernels.jax()
    out = list(inputs)
    for i, (arr, want) in enumerate(zip(inputs, layouts)):
        if want is None or not isinstance(arr, jax.Array):
            continue
        if arr.sharding.is_equivalent_to(want, arr.ndim):
            continue
        out[i] = kernels.relayout(arr, want)
    return out


def note_dispatch(mesh) -> None:
    """Count one dispatch of a program that runs over the whole mesh
    (every device jax has), as against a one-device or sub-mesh one."""
    if mesh_shards(mesh) == len(kernels.jax().devices()):
        kernels.stats_add("mesh_dispatches", 1)


# =========================================================================
# distributed partial/final aggregation (SURVEY §2.11 P5)
# =========================================================================

def make_sharded_group_sum(mesh, n_buckets: int):
    """Per-shard segment-sum into a fixed bucket table + psum merge: the
    reference's partial workers -> shuffle -> final workers pipeline
    (aggregate.go:55-93) collapsed into one SPMD program.

    Inputs (host-side global shapes): bucket ids int32 [n_shards, rows],
    values f64 [n_shards, rows], valid mask [n_shards, rows].
    Output: per-bucket (sum, count) replicated on every shard.
    """
    jax = kernels.jax()
    jnp = kernels.jnp()
    shard_map, P = shard_map_fn()

    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard", None), P("shard", None), P("shard", None)),
             out_specs=(P(), P()))
    def step(bucket_ids, vals, valid):
        # each shard sees [1, rows]
        b = bucket_ids[0]
        v = jnp.where(valid[0], vals[0], 0.0)
        c = valid[0].astype(jnp.int64)
        partial_sum = jax.ops.segment_sum(v, b, num_segments=n_buckets)
        partial_cnt = jax.ops.segment_sum(c, b, num_segments=n_buckets)
        # ICI all-reduce of partial states (the reduce-scatter schema)
        total = jax.lax.psum(partial_sum, "shard")
        cnt = jax.lax.psum(partial_cnt, "shard")
        return total, cnt

    return kernels.counted_jit(step)


# =========================================================================
# distributed broadcast join (SURVEY §2.11 P4)
# =========================================================================

def make_broadcast_join_counts(mesh):
    """Probe side sharded over the mesh; build side broadcast (all_gather)
    to every shard; each shard counts its local matches; psum gives the
    global match count.  The 'partition build side' variant (hash
    re-sharding via all_to_all) lands with the distributed executor."""
    jax = kernels.jax()
    jnp = kernels.jnp()
    shard_map, P = shard_map_fn()

    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard", None), P("shard", None), P(None)),
             out_specs=(P("shard", None), P()))
    def step(lkeys, lvalid, rkeys_sorted):
        lk = lkeys[0]
        lv = lvalid[0]
        lo = jnp.searchsorted(rkeys_sorted, lk, side="left")
        hi = jnp.searchsorted(rkeys_sorted, lk, side="right")
        counts = jnp.where(lv, hi - lo, 0)
        total = jax.lax.psum(jnp.sum(counts), "shard")
        return counts[None, :], total

    return kernels.counted_jit(step)


# =========================================================================
# hash-partitioned (shuffle) join primitives (SURVEY §2.11 P4 north star:
# "partition build-side tables")
# =========================================================================
# Both sides re-partition BY KEY HASH over the mesh axis with all_to_all
# (ICI on hardware), so every shard holds only its hash partition of the
# build side — build tables larger than one chip's HBM budget become
# servable.  Static shapes: the host computes EXACT per-(source, dest)
# bucket capacities from the raw key lanes (partitioning is value-only,
# pre-filter; filters ride the validity lane through the exchange), so
# the scatter never drops rows.  Padding rows spread round-robin to keep
# the capacity bound tight.

# golden-ratio multiplier (two's-complement int64 of 0x9E3779B97F4A7C15)
HASH_GOLDEN = np.int64(0x9E3779B97F4A7C15 - (1 << 64))


def hash_dest_np(keys: np.ndarray, n_shards: int,
                 n_rows: Optional[int] = None) -> np.ndarray:
    """Destination shard per row — MUST stay bit-identical to
    hash_dest_traced (the host capacity bound relies on it)."""
    with np.errstate(over="ignore"):
        h = keys.astype(np.int64, copy=False) * HASH_GOLDEN
    d = (h >> 33) & (n_shards - 1)
    if n_rows is not None:
        idx = np.arange(len(keys), dtype=np.int64)
        d = np.where(idx < n_rows, d, idx % n_shards)
    return d


def hash_dest_traced(jn, keys, n_shards: int, global_idx, n_rows):
    """Traced twin of hash_dest_np (int64 wrap-around multiply)."""
    h = keys * HASH_GOLDEN
    d = (h >> 33) & (n_shards - 1)
    return jn.where(global_idx < n_rows, d, global_idx % n_shards)


def shuffle_cap(keys_padded: np.ndarray, n_shards: int, n_rows: int) -> int:
    """Power-of-two capacity per (source shard, dest shard) send bucket:
    the exact max block histogram of the destinations."""
    dest = hash_dest_np(keys_padded, n_shards, n_rows)
    per = len(keys_padded) // n_shards
    mx = 1
    for i in range(n_shards):
        c = np.bincount(dest[i * per:(i + 1) * per], minlength=n_shards)
        mx = max(mx, int(c.max()))
    return kernels.bucket(mx)


def exchange_lanes(jn, lanes, dest_local, cap: int, n_shards: int,
                   axis: str = "shard"):
    """Traced, per shard: scatter each lane into an [n, cap] send buffer
    by (dest, rank-within-dest), all_to_all over the mesh axis, return
    flattened [n*cap] received lanes.  lanes = [(array [m], fill)]."""
    from jax import lax
    m = dest_local.shape[0]
    order = jn.argsort(dest_local, stable=True)
    ds = dest_local[order]
    rank = jn.arange(m) - jn.searchsorted(ds, ds, side="left")
    outs = []
    for arr, fill in lanes:
        buf = jn.full((n_shards, cap), fill, dtype=arr.dtype)
        buf = buf.at[ds, rank].set(arr[order], mode="drop")
        with kernels.jax().named_scope("mesh.all_to_all"):
            r = lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                               tiled=True)
        outs.append(r.reshape(n_shards * cap))
    return outs


def local_unique_join(jn, bk, blive, pk, BN: int):
    """Traced, per shard: sort the received build partition by
    (key, liveness) and probe with searchsorted.  Returns (hit, brow):
    hit[i] = probe key i has a LIVE build row; brow[i] = its position in
    the received build lanes.  Lexicographic sort puts the live row first
    among equal keys, so a dead row never shadows a live one."""
    from jax import lax
    kmask = jn.where(blive, bk, jn.iinfo(jn.int64).max)
    inv = (~blive).astype(jn.int32)
    sk, sinv, sperm = lax.sort(
        (kmask, inv, jn.arange(BN, dtype=jn.int64)), num_keys=2)
    lo = jn.searchsorted(sk, pk, side="left")
    loc = jn.clip(lo, 0, BN - 1)
    hit = (lo < BN) & (sk[loc] == pk) & (sinv[loc] == 0)
    return hit, sperm[loc]


# =========================================================================
# full distributed step (the dryrun/"training step" entry)
# =========================================================================

def distributed_query_step(mesh, n_buckets: int = 64):
    """One fused SPMD 'query step': filter + partial aggregate + psum +
    broadcast-join counts — the whole distributed pipeline the engine's
    multi-chip executor drives, jitted over the mesh."""
    jax = kernels.jax()
    jnp = kernels.jnp()
    agg = make_sharded_group_sum(mesh, n_buckets)
    join = make_broadcast_join_counts(mesh)

    def step(bucket_ids, vals, valid, lkeys, lvalid, rkeys_sorted):
        sums, cnts = agg(bucket_ids, vals, valid)
        counts, total = join(lkeys, lvalid, rkeys_sorted)
        return sums, cnts, counts, total

    return step


def shard_rows(arr: np.ndarray, n_shards: int, fill=0) -> np.ndarray:
    """Host helper: pad + reshape a 1-D array to [n_shards, rows]."""
    n = len(arr)
    per = (n + n_shards - 1) // n_shards
    out = np.full(n_shards * per, fill, dtype=arr.dtype)
    out[:n] = arr
    return out.reshape(n_shards, per)

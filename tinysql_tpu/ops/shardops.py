"""Mesh-sharded operator tier: partition-parallel kernels over N devices.

PR 9's spill partitioner and PR 14's stacked batching meet the mesh
here.  Every remaining accelerated operator family gains a sharded
variant whose SHARD FUNCTION IS THE SPILL PARTITION FUNCTION
(ops/spill.py hash_partition — splitmix64, equal-keys-colocate), so
device placement and the spill ladder share one partitioner and a
spilled partition maps 1:1 onto a shard:

- ``fused_scalar_aggregate_sharded`` — partial→final global aggregation
  ("Partial Partial Aggregates" / "Global Hash Tables Strike Back!"
  design space): each shard reduces its row slice with arguments
  evaluated on-device, partials merge once with psum/pmin/pmax over the
  mesh axis.  STACKABLE: the packed kernel carries a stacking recipe, so
  a coalesced batch round vmaps B queries OVER the N-shard program — one
  dispatch covers B x N.
- ``unique_join_match_sharded`` / ``semi_join_match_sharded`` —
  partitioned build/probe: the host scatters both sides' LIVE rows into
  per-shard hash-partition blocks (spill.hash_partition depth 0), each
  shard joins its partition locally (sort + searchsorted, the same
  machinery as the single-device kernels), and the host re-assembles
  results in probe order — byte-identical to the unsharded kernels.
- ``sort_permutation_sharded`` / ``top_k_sharded`` — per-shard sort /
  selection + device merge: single-key orders map onto the total-order
  score (kernels._primary_score), shards sort locally, and exact global
  ranks come from searchsorted counts against the all_gathered runs
  (ties resolve by global row index because shards are contiguous row
  blocks — the same stability the single-device lexsort guarantees).

Discipline: every program registers under a SHAPE-ONLY progcache key —
partition capacities go through ``kernels.bucket`` and the mesh size
through ``dist.mesh_shards`` (the sanctioned launders; qlint DF803/
DF807) — so prewarm, digest families, and the program catalog apply
unchanged.  All shard_map construction rides ``dist.shard_map_fn`` /
``dist.shard_map_unchecked`` (qlint DF805), and no shard_map body ever
syncs to host (qlint DF806).

Counter-write discipline: ``STATS`` is written only through this
module's locked accessors (qlint OB401/OB402 — shardops.py is an owning
module).  devpipe's probe-skew unsharded-retry path and its shuffle-join
exchanges feed ``record_skew_retry`` / ``record_exchange``.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import fail
from ..obs import context as _obs
from ..parallel import dist
from . import kernels, progcache, spill

# ---- observable state ------------------------------------------------------

_mu = threading.Lock()
#: process-cumulative sharded-tier economics (satellite: rendered on
#: /metrics, sampled into the time-series ring): shard_rounds = sharded
#: program dispatches, shard_rows_hwm = per-shard row high-water mark
#: (partition-block capacity actually used), shard_exchange_bytes =
#: bytes scattered into partition blocks / all_to_all lanes,
#: shard_skew_retries = sharded attempts abandoned for skew (devpipe's
#: unsharded retry + this module's capacity-gate bails),
#: shard_stacked_rounds = B-stacked dispatches OVER sharded programs
STATS: Dict[str, float] = {
    "shard_rounds": 0, "shard_rows_hwm": 0, "shard_exchange_bytes": 0,
    "shard_skew_retries": 0, "shard_stacked_rounds": 0,
}

def _record(key: str, n: float = 1) -> None:
    """Accumulator write path (the kernels.stats_add double-entry
    pattern): global counter under the lock + per-query obs fan-out."""
    with _mu:
        STATS[key] = STATS.get(key, 0) + n
    _obs.record(key, n)


def _hwm(key: str, n: float) -> None:
    with _mu:
        if n > STATS.get(key, 0):
            STATS[key] = n
    _obs.record_hwm(key, n)


def stats_snapshot() -> Dict[str, float]:
    with _mu:
        return dict(STATS)


def reset_stats() -> None:
    """Tests only."""
    with _mu:
        for k in STATS:
            STATS[k] = 0


def record_skew_retry() -> None:
    """A sharded attempt fell back unsharded because one shard's bound
    blew up (devpipe's CSR probe-skew retry; this module's partition
    capacity gate)."""
    _record("shard_skew_retries")


def record_exchange(nbytes: int) -> None:
    """Bytes moved through a shard exchange (partition-block scatter or
    all_to_all lanes) — devpipe's shuffle join reports its per-compile
    lane volume here."""
    _record("shard_exchange_bytes", int(nbytes))


def note_round(max_shard_rows: int) -> None:
    _record("shard_rounds")
    _hwm("shard_rows_hwm", int(max_shard_rows))


def note_stacked_round() -> None:
    """A coalesced batch round dispatched B stacked queries OVER a
    sharded program — the full B x N throughput product."""
    _record("shard_stacked_rounds")


# ---- exact attribution splits ---------------------------------------------

def split_exact(totals: dict, k: int) -> List[dict]:
    """Split a device-counter dict into ``k`` per-member shares.

    INTEGER counters (dispatches, transfers, bytes) split as integers:
    every member takes ``v // k`` and the last one also the remainder,
    so the shares sum to ``v`` exactly, in any order, under any ``sum``.
    REAL-valued ones (``device_s``, cost-model flops) split as ``v / k``
    with the last member taking what is left; their sum equals ``v`` to
    float rounding — a few ulp, depending on the order and on whether
    the adder compensates (Python's ``sum`` does since 3.12) — and is
    compared with a tolerance, never with ``==``.
    Used by the batching dispatch leg for occupancy shares and by the
    sharded tier for per-shard shares; the two nest (B members x N
    shards)."""
    if k <= 1:
        return [dict(totals)]
    shares: List[dict] = [dict() for _ in range(k)]
    for key, v in totals.items():
        if isinstance(v, (int, np.integer)):
            q, last = v // k, v // k + v % k
        else:
            q = v / k
            last = v - q * (k - 1)
        for i in range(k - 1):
            shares[i][key] = q
        shares[k - 1][key] = last
    return shares


def member_shard_shares(totals: dict, b: int, n: int) -> List[List[dict]]:
    """B x N attribution cells for one stacked-over-sharded dispatch:
    member shares split by :func:`split_exact`, each member's share split
    again across the N shards.  Integer counters sum back to ``totals``
    exactly; real-valued ones to float rounding."""
    return [split_exact(m, n) for m in split_exact(totals, b)]


# ---- key introspection -----------------------------------------------------

_SHARDED_DOMAINS = ("scalar_sharded", "seg_sharded", "join_sharded",
                    "semi_sharded", "sort_sharded", "topk_sharded")


def shards_of_key(key: tuple) -> int:
    """Mesh size a sharded progcache key was built for (0 = unsharded
    program).  Sharded domains put the laundered shard count right after
    the domain-specific shape tuple; we tag it explicitly instead:
    every sharded key carries a ``("shards", n)`` marker pair."""
    if not isinstance(key, tuple) or not key:
        return 0
    for part in key:
        if isinstance(part, tuple) and len(part) == 2 \
                and part[0] == "shards":
            return int(part[1])
    return 0


def _shards_tag(mesh) -> tuple:
    return ("shards", dist.mesh_shards(mesh))


# ---- host-side hash partitioning (shard = PR 9 spill partition) -----------

#: a shard's partition block may exceed the balanced share by this
#: factor before the sharded attempt bails to the single-device kernel
#: (skew: a clustered key set would make one device's block rival the
#: whole input)
SKEW_CAP_FACTOR = 2


class _Partitioned:
    """Host-side hash-partition scatter of one input side: LIVE rows
    land in per-shard blocks [n_shards, cap] (cap = bucketed max
    partition size), each row remembering its global index so results
    re-assemble in input order."""

    __slots__ = ("n_shards", "cap", "dest", "order", "slot", "live_idx",
                 "nbytes")

    def __init__(self, keys: np.ndarray, live: np.ndarray, n_shards: int):
        live_idx = np.nonzero(live)[0].astype(np.int64)
        k = np.ascontiguousarray(keys[live_idx])
        # THE spill partitioner at depth 0: equal keys colocate, and a
        # partition that later spills reloads exactly one shard's rows
        dest = spill.hash_partition(k, 0, n_shards) if len(k) \
            else np.empty(0, dtype=np.int64)
        counts = np.bincount(dest, minlength=n_shards)
        self.cap = kernels.bucket(max(int(counts.max()) if len(k) else 1, 1))
        self.n_shards = n_shards
        self.dest = dest
        self.live_idx = live_idx
        order = np.argsort(dest, kind="stable")
        starts = np.zeros(n_shards, dtype=np.int64)
        np.cumsum(counts[:-1], out=starts[1:])
        rank = np.arange(len(dest), dtype=np.int64) - starts[dest[order]]
        self.order = order
        self.slot = dest[order] * self.cap + rank
        self.nbytes = 0

    def skewed(self, n_input_bucket: int) -> bool:
        return self.n_shards * self.cap > max(
            SKEW_CAP_FACTOR * n_input_bucket, 16 * self.n_shards)

    def scatter(self, lane: np.ndarray, fill) -> np.ndarray:
        """One lane -> [n_shards, cap] blocks (live rows only)."""
        out = np.full(self.n_shards * self.cap, fill, dtype=lane.dtype)
        out[self.slot] = lane[self.live_idx][self.order]
        self.nbytes += out.nbytes
        return out.reshape(self.n_shards, self.cap)

    def scatter_ids(self) -> np.ndarray:
        """Global row-index lane (fill -1 marks padding slots)."""
        out = np.full(self.n_shards * self.cap, -1, dtype=np.int64)
        out[self.slot] = self.live_idx[self.order]
        return out.reshape(self.n_shards, self.cap)


def _live_masks(n_left, n_right, lnull, rnull, lvalid, rvalid):
    lv = np.ones(n_left, dtype=bool) if lvalid is None \
        else np.asarray(lvalid[:n_left], dtype=bool)
    rv = np.ones(n_right, dtype=bool) if rvalid is None \
        else np.asarray(rvalid[:n_right], dtype=bool)
    return lv, rv


def _common_key_dtype(lk: np.ndarray, rk: np.ndarray):
    """Coerce both key lanes to one dtype BEFORE hashing: 5 and 5.0 must
    land in the same partition (the raw bit patterns differ)."""
    if lk.dtype != rk.dtype:
        return lk.astype(np.float64), rk.astype(np.float64)
    return lk, rk


# ---- partitioned build/probe unique join ----------------------------------

def _local_unique_join_kernel(mesh, cap_p: int, cap_b: int, kdtype: str):
    """Per-shard local unique join over partition blocks: sort the build
    block by (key, liveness) — live row first among equal keys, so a
    padding slot never shadows a live one — then searchsorted each probe
    key.  Outputs stay block-shaped; the host maps them back to probe
    order through the id lanes."""
    j = kernels.jax()
    jn = kernels.jnp()
    shard_map, P = dist.shard_map_fn()

    def body(pk, pid, bk, bid):
        from jax import lax
        blive = bid >= 0
        sentinel = (jn.iinfo(jn.int64).max if bk.dtype == jn.int64
                    else jn.inf)
        kmask = jn.where(blive, bk, sentinel)
        inv = (~blive).astype(jn.int32)
        sk, sinv, sperm = lax.sort(
            (kmask, inv, jn.arange(cap_b, dtype=jn.int64)), num_keys=2)
        lo = jn.searchsorted(sk, pk, side="left")
        loc = jn.clip(lo, 0, cap_b - 1)
        hit = (pid >= 0) & (lo < cap_b) & (sk[loc] == pk) \
            & (sinv[loc] == 0)
        brow = bid[sperm[loc]]
        return hit, jn.where(hit, brow, -1)

    sm = shard_map(body, mesh=mesh,
                   in_specs=(P("shard"), P("shard"), P("shard"),
                             P("shard")),
                   out_specs=(P("shard"), P("shard")))

    def kernel(pk, pid, bk, bid):
        # blocks travel flattened [n*cap] so the 1-D shard axis carries
        # whole partitions; the body sees its own [cap] slice
        hit, brow = sm(pk.reshape(-1), pid.reshape(-1),
                       bk.reshape(-1), bid.reshape(-1))
        return hit, brow

    return kernels.counted_jit(kernel)


def unique_join_match_sharded(mesh, lkey, n_left: int, rkey, n_right: int,
                              outer: bool = False,
                              lvalid: np.ndarray = None,
                              rvalid: np.ndarray = None):
    """Partitioned build/probe unique join over the mesh: same (li, ri)
    contract and tie semantics as kernels.unique_join_match, or None
    when sharding does not apply (skew, non-numeric keys, tiny input).
    Host work is the O(n) partition scatter; the per-partition sort +
    probe — the actual O(n log n) — runs one-partition-per-device."""
    n = dist.mesh_shards(mesh)
    if n < 2 or not isinstance(lkey[0], np.ndarray) \
            or not isinstance(rkey[0], np.ndarray):
        return None
    lk = np.asarray(lkey[0])[:n_left]
    ln = np.asarray(lkey[1])[:n_left]
    rk = np.asarray(rkey[0])[:n_right]
    rn = np.asarray(rkey[1])[:n_right]
    if lk.dtype not in (np.int64, np.float64) \
            or rk.dtype not in (np.int64, np.float64):
        return None
    lk, rk = _common_key_dtype(lk, rk)
    lv, rv = _live_masks(n_left, n_right, ln, rn, lvalid, rvalid)
    l_live = lv & ~ln
    r_live = rv & ~rn
    if not r_live.any():
        if outer:
            li = np.nonzero(lv)[0].astype(np.int64)
            return li, np.full(len(li), -1, dtype=np.int64)
        z = np.empty(0, dtype=np.int64)
        return z, z
    fail.inject("shardExchangeStall")
    pp = _Partitioned(lk, l_live, n)
    pb = _Partitioned(rk, r_live, n)
    nlb = kernels.bucket(max(n_left, 1))
    nrb = kernels.bucket(max(n_right, 1))
    if pp.skewed(nlb) or pb.skewed(nrb):
        record_skew_retry()
        return None
    kdtype = str(lk.dtype)
    key = ("join_sharded", _shards_tag(mesh), pp.cap, pb.cap, kdtype)
    fn = progcache.get(key, lambda: _local_unique_join_kernel(
        mesh, pp.cap, pb.cap, kdtype))
    pk_h, pi_h = pp.scatter(lk, 0), pp.scatter_ids()
    bk_h, bi_h = pb.scatter(rk, 0), pb.scatter_ids()
    record_exchange(pp.nbytes + pb.nbytes)
    note_round(max(pp.cap, pb.cap))
    pkb, pib = kernels.h2d(pk_h), kernels.h2d(pi_h)
    bkb, bib = kernels.h2d(bk_h), kernels.h2d(bi_h)
    hit, brow = kernels.d2h_many(fn(pkb, pib, bkb, bib))
    hit = hit.reshape(-1)
    brow = brow.reshape(-1)
    flat_ids = pp.scatter_ids().reshape(-1)
    sel = flat_ids >= 0
    match = np.zeros(n_left, dtype=bool)
    cand = np.full(n_left, -1, dtype=np.int64)
    match[flat_ids[sel]] = hit[sel]
    cand[flat_ids[sel]] = brow[sel]
    if outer:
        li = np.nonzero(lv)[0].astype(np.int64)
        return li, np.where(match[li], cand[li], -1).astype(np.int64)
    li = np.nonzero(match)[0].astype(np.int64)
    return li, cand[li]


# ---- partitioned semi / anti join -----------------------------------------

def _local_member_kernel(mesh, cap_p: int, cap_b: int, kdtype: str):
    """Per-shard membership bit over partition blocks (semi/anti share
    it; the three-valued NOT IN ladder applies host-side with the
    host-known build globals)."""
    jn = kernels.jnp()
    shard_map, P = dist.shard_map_fn()

    def body(pk, pid, bk, bid):
        from jax import lax
        blive = bid >= 0
        sentinel = (jn.iinfo(jn.int64).max if bk.dtype == jn.int64
                    else jn.inf)
        kmask = jn.where(blive, bk, sentinel)
        inv = (~blive).astype(jn.int32)
        sk, sinv, _ = lax.sort(
            (kmask, inv, jn.arange(cap_b, dtype=jn.int64)), num_keys=2)
        lo = jn.searchsorted(sk, pk, side="left")
        loc = jn.clip(lo, 0, cap_b - 1)
        member = (pid >= 0) & (lo < cap_b) & (sk[loc] == pk) \
            & (sinv[loc] == 0)
        return member

    sm = shard_map(body, mesh=mesh,
                   in_specs=(P("shard"), P("shard"), P("shard"),
                             P("shard")),
                   out_specs=P("shard"))

    def kernel(pk, pid, bk, bid):
        return sm(pk.reshape(-1), pid.reshape(-1),
                  bk.reshape(-1), bid.reshape(-1))

    return kernels.counted_jit(kernel)


def semi_join_match_sharded(mesh, lkey, n_left: int, rkey, n_right: int,
                            anti: bool = False, null_aware: bool = False,
                            lvalid: np.ndarray = None,
                            rvalid: np.ndarray = None):
    """Partitioned semi/anti membership over the mesh: probe and build
    sides hash-partition with the spill partitioner, each shard answers
    membership for its partition, and the host applies the exact
    semi/anti/NOT IN keep ladder (kernels._np_semi_match semantics) over
    the re-assembled member bits.  Returns surviving probe indices in
    probe order, or None when sharding does not apply."""
    n = dist.mesh_shards(mesh)
    if n < 2 or not isinstance(lkey[0], np.ndarray) \
            or not isinstance(rkey[0], np.ndarray):
        return None
    lk = np.asarray(lkey[0])[:n_left]
    ln = np.asarray(lkey[1])[:n_left]
    rk = np.asarray(rkey[0])[:n_right]
    rn = np.asarray(rkey[1])[:n_right]
    if lk.dtype not in (np.int64, np.float64) \
            or rk.dtype not in (np.int64, np.float64):
        return None
    lk, rk = _common_key_dtype(lk, rk)
    lv, rv = _live_masks(n_left, n_right, ln, rn, lvalid, rvalid)
    n_build = int(rv.sum())
    if n_build == 0:
        keep = lv if anti else np.zeros(n_left, dtype=bool)
        return np.nonzero(keep)[0].astype(np.int64)
    if anti and null_aware and bool((rv & rn).any()):
        return np.empty(0, dtype=np.int64)
    fail.inject("shardExchangeStall")
    l_live = lv & ~ln
    pp = _Partitioned(lk, l_live, n)
    pb = _Partitioned(rk, rv & ~rn, n)
    nlb = kernels.bucket(max(n_left, 1))
    nrb = kernels.bucket(max(n_right, 1))
    if pp.skewed(nlb) or pb.skewed(nrb):
        record_skew_retry()
        return None
    kdtype = str(lk.dtype)
    key = ("semi_sharded", _shards_tag(mesh), pp.cap, pb.cap, kdtype)
    fn = progcache.get(key, lambda: _local_member_kernel(
        mesh, pp.cap, pb.cap, kdtype))
    pk_h, pi_h = pp.scatter(lk, 0), pp.scatter_ids()
    bk_h, bi_h = pb.scatter(rk, 0), pb.scatter_ids()
    record_exchange(pp.nbytes + pb.nbytes)
    note_round(max(pp.cap, pb.cap))
    pkb, pib = kernels.h2d(pk_h), kernels.h2d(pi_h)
    bkb, bib = kernels.h2d(bk_h), kernels.h2d(bi_h)
    mem_flat = kernels.d2h(fn(pkb, pib, bkb, bib)).reshape(-1)
    flat_ids = pp.scatter_ids().reshape(-1)
    sel = flat_ids >= 0
    member = np.zeros(n_left, dtype=bool)
    member[flat_ids[sel]] = mem_flat[sel]
    if anti:
        keep = lv & ~member
        if null_aware:
            keep &= ~ln
    else:
        keep = member
    return np.nonzero(keep)[0].astype(np.int64)


# ---- sharded partial->final scalar aggregation ----------------------------

def fused_scalar_aggregate_sharded(mesh, dev_cols, agg_specs, arg_exprs,
                                   n_rows: int, nb: int, mask,
                                   program_key: tuple = (), params=None,
                                   batchable: bool = False):
    """Mesh variant of kernels.fused_scalar_aggregate: rows shard over
    the mesh axis, each shard computes the masked partial reductions
    with arguments evaluated on-device, and the partial states merge
    ONCE with psum/pmin/pmax.  Output contract identical to the
    single-device kernel (_unpack_scalar_agg).

    STACKABLE: the packed kernel carries a stacking recipe, so a batch
    round's stacked variant vmaps B param sets over the N-shard program
    — B queries x N shards in one dispatch (jax.vmap composes over
    shard_map; verified on the forced host mesh)."""
    j = kernels.jax()
    jn = kernels.jnp()
    n_dev = dist.mesh_shards(mesh)
    assert nb % n_dev == 0, (nb, n_dev)
    mask_fn, mask_key, mask_arr = kernels._mask_parts(mask)
    dev_shape = tuple(0 if c is None else (1 if c[0] is None else 2)
                      for c in dev_cols)
    key = ("scalar_sharded", tuple(agg_specs), program_key, mask_key, nb,
           _shards_tag(mesh), dev_shape)
    rnd = kernels._batch_round(mask, params, batchable)
    if rnd is not None and rnd.collecting:
        ent = progcache.peek(key)
        if ent is not None:
            rnd.park(key, ent[0], (tuple(dev_cols), mask_arr), params)

    def build():
        arg_fns = [kernels._lower_arg(e) for e in arg_exprs]
        ROWS, WHOLE = dist.specs()
        col_spec = tuple(
            ((ROWS if c[0] is not None else None, ROWS)
             if c is not None else None)
            for c in dev_cols)

        def make_kernel():
            kernel_schema: list = []

            def body(cols, mask_in, pr):
                rows_local = nb // n_dev
                shard = j.lax.axis_index("shard")
                base = shard.astype(jn.int64) * rows_local
                if mask_fn is not None:
                    valid = mask_fn(cols, pr,
                                    jn.arange(rows_local) + base)
                else:
                    valid = mask_in
                outs = []
                for (func, has_arg), af in zip(agg_specs, arg_fns):
                    av = an = None
                    if has_arg and af is not None:
                        av, an = af(cols, pr)
                    if func == "count_star":
                        c = dist.mesh_sum(jn.sum(valid.astype(jn.int64)))
                        outs.append((c[None], jn.zeros(1, dtype=bool)))
                        continue
                    live = valid & ~an
                    cnt = dist.mesh_sum(jn.sum(live.astype(jn.int64)))
                    if func == "count":
                        outs.append((cnt[None], jn.zeros(1, dtype=bool)))
                    elif func in ("sum", "sum0"):
                        total = dist.mesh_sum(
                            jn.sum(jn.where(live, av, 0)))
                        outs.append((total[None],
                                     jn.zeros(1, dtype=bool)
                                     if func == "sum0"
                                     else (cnt == 0)[None]))
                    elif func in ("min", "max"):
                        if av.dtype == jn.int64:
                            fill = (jn.iinfo(jn.int64).max
                                    if func == "min"
                                    else jn.iinfo(jn.int64).min)
                        else:
                            fill = jn.inf if func == "min" else -jn.inf
                        red = jn.min if func == "min" else jn.max
                        local = red(jn.where(live, av, fill))
                        merged = (dist.mesh_min(local)
                                  if func == "min"
                                  else dist.mesh_max(local))
                        outs.append((merged[None], (cnt == 0)[None]))
                    else:  # pragma: no cover
                        raise ValueError(func)
                n_valid = dist.mesh_sum(jn.sum(valid.astype(jn.int64)))
                # first valid GLOBAL row index (0 when none — the
                # single-device argmax convention); the sentinel nb maps
                # empty shards past every real row before the pmin
                local_first = jn.where(jn.any(valid),
                                       jn.argmax(valid) + base, nb)
                first = dist.mesh_min(local_first)
                first = jn.where(first >= nb, 0, first)
                items = [n_valid[None], first[None]]
                for v, m in outs:
                    items += [v, m]
                return items

            # replicated by construction (psum, mesh_min/max): the
            # gather-and-reduce merges are beyond the static checker
            sm = dist.shard_map_unchecked(
                body, mesh=mesh,
                in_specs=(col_spec, ROWS, (WHOLE, WHOLE)),
                out_specs=WHOLE)

            def packed(cols, mask_in, pr):
                return kernels.pack_arrays(kernel_schema,
                                          sm(cols, mask_in, pr))
            return packed, kernel_schema

        packed, kernel_schema = make_kernel()
        return kernels._stackable_jit(packed, "packed", 2, make_kernel), \
            kernel_schema
    fn, schema = progcache.get(key, build)
    note_round(nb // n_dev)
    if rnd is not None and rnd.replaying:
        got = rnd.consume(key, (tuple(dev_cols), mask_arr), params)
        if got is not None:
            tag, val = got
            vals = kernels.unpack_host(val, schema) if tag == "host" \
                else kernels.unpack_flat(val, schema)
            return kernels._unpack_scalar_agg(vals)
    dist.note_dispatch(mesh)
    return kernels._unpack_scalar_agg(kernels.unpack_flat(
        fn(tuple(dev_cols), mask_arr,
           kernels._params_dev(params, dist.whole(mesh))), schema))


# ---- sharded sort / top-k --------------------------------------------------

def _neg_score(jn, s):
    """Order-reversing bijection on the score lane (bigger-is-earlier ->
    ascending sort key): ~ for int64 (overflow-free), - for float64."""
    return ~s if s.dtype == jn.int64 else -s


def _sort_rank_kernel(mesh, n_shards: int, sdtype: str):
    """Per-shard stable sort + exact global rank merge: each shard sorts
    its contiguous row slice, all_gathers every shard's sorted run, and
    counts — via searchsorted — how many rows order strictly before each
    of its own (ties count when they live in an earlier shard, i.e. at a
    lower global row index).  The resulting ranks are a permutation of
    0..nb-1 that reproduces the single-device stable lexsort exactly."""
    jn = kernels.jnp()
    shard_map, P = dist.shard_map_fn()

    def body(score):
        from jax import lax
        i = lax.axis_index("shard")
        neg = _neg_score(jn, score)
        m = neg.shape[0]
        order = jn.argsort(neg, stable=True)
        run = neg[order]
        inv = jn.zeros(m, dtype=jn.int64).at[order].set(
            jn.arange(m, dtype=jn.int64))
        runs = lax.all_gather(run, "shard")
        rank = inv
        for s in range(n_shards):
            r = jn.searchsorted(runs[s], neg, side="right")
            l = jn.searchsorted(runs[s], neg, side="left")
            rank = rank + jn.where(s < i, r, jn.where(s > i, l, 0))
        return rank

    return kernels.counted_jit(shard_map(
        body, mesh=mesh, in_specs=P("shard"), out_specs=P("shard")))


def _score_pad(score: np.ndarray, nb: int) -> np.ndarray:
    """Pad the score lane with the WORST sentinel (strictly after every
    real row; ties inside the sentinel class resolve by row index, which
    keeps padding after the equal-scored real rows)."""
    pad = np.iinfo(np.int64).min if score.dtype == np.int64 else -np.inf
    return kernels.pad1(score, nb, pad)


def sort_permutation_sharded(mesh, key_cols, descs, n_rows: int):
    """Sharded ORDER BY permutation: per-shard sort + exact device rank
    merge.  Single-key orders only (the total-order score mapping);
    returns None when the mapping is unsafe or sharding does not apply —
    callers fall back to the single-device kernel."""
    n = dist.mesh_shards(mesh)
    if n < 2 or len(key_cols) != 1:
        return None
    nb = kernels.bucket(max(n_rows, 1))
    if not dist.shardable(nb, mesh):
        return None
    score = kernels._primary_score(key_cols[0], descs[0], n_rows)
    if score is None:
        return None
    score = np.asarray(score[:n_rows])
    sdtype = str(score.dtype)
    key = ("sort_sharded", nb, _shards_tag(mesh), sdtype)
    fn = progcache.get(key, lambda: _sort_rank_kernel(mesh, n, sdtype))
    note_round(nb // n)
    sp = _score_pad(score, nb)
    rank = kernels.d2h(fn(kernels.h2d(sp)))
    perm = np.empty(nb, dtype=np.int64)
    perm[rank] = np.arange(nb, dtype=np.int64)
    return perm[:n_rows]


def _topk_merge_kernel(mesh, n_shards: int, kb: int, m: int, sdtype: str):
    """Per-shard lax.top_k + all_gather + replicated final selection:
    the classic tournament — any global top-k row is in its shard's
    top-k, and the flattened candidate order (shard-major, score-desc /
    index-asc within a run) makes lax.top_k's lowest-index tie-break
    reproduce the exact global (score desc, row index asc) order."""
    jn = kernels.jnp()
    _, P = dist.shard_map_fn()

    def body(score):
        from jax import lax
        i = lax.axis_index("shard")
        v, idx = lax.top_k(score, kb)
        gid = idx.astype(jn.int64) + i.astype(jn.int64) * m
        gv = lax.all_gather(v, "shard").reshape(n_shards * kb)
        gi = lax.all_gather(gid, "shard").reshape(n_shards * kb)
        _, fi = lax.top_k(gv, kb)
        return gi[fi]

    return kernels.counted_jit(dist.shard_map_unchecked(
        body, mesh, in_specs=P("shard"), out_specs=P()))


def top_k_sharded(mesh, key_cols, descs, n_rows: int, k: int):
    """Sharded top-k row selection (single-key, score-mapped): returns
    the k row indices in requested order, or None when sharding does not
    apply — same contract as kernels._topk_single."""
    n = dist.mesh_shards(mesh)
    if n < 2 or len(key_cols) != 1 or k <= 0:
        return None
    nb = kernels.bucket(max(n_rows, 1))
    if not dist.shardable(nb, mesh):
        return None
    m = nb // n
    if k > m:
        return None  # a shard cannot bound the candidate set
    score = kernels._primary_score(key_cols[0], descs[0], n_rows)
    if score is None:
        return None
    score = np.asarray(score[:n_rows])
    kb = min(kernels.bucket(max(k, 1)), m)
    sdtype = str(score.dtype)
    key = ("topk_sharded", nb, kb, _shards_tag(mesh), sdtype)
    fn = progcache.get(
        key, lambda: _topk_merge_kernel(mesh, n, kb, m, sdtype))
    note_round(m)
    sp = _score_pad(score, nb)
    ids = kernels.d2h(fn(kernels.h2d(sp)))[:k]
    return ids[ids < n_rows]

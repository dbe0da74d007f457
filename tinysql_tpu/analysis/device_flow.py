"""qlint pass 7 — DF8xx: whole-program device-dataflow analysis.

The serving stack stands on one contract (ISSUE 16): every hot path is a
params-compiled tensor program whose device<->host traffic is COUNTED
(kernels.h2d / h2d_pad / d2h / d2h_many, PR 11), whose progcache keys
are shape-stable (PR 6), and whose measured device time is truth.  This
pass machine-checks that contract the way CC7xx machine-checked the
threading model — and reuses CC7xx's whole-program machinery
(`concurrency._Program`: per-module indexing, cross-module call
resolution, nested-def reachability) to taint device-array values
interprocedurally from their birth sites:

- ``kernels.h2d`` / ``h2d_pad`` / ``jax.device_put`` / ``_params_dev``
  uploads, and the replica-memoized ``_dev_upload`` idiom (devpipe);
- results of calling a program wrapper (``counted_jit`` /
  ``stacked_variant`` / an entry fetched from ``progcache.get``);
- any jax-namespace constructor (``jn.zeros`` / ``jnp.asarray`` / ...);
- functions/methods RETURNING tainted values (fixed point across the
  whole analysis batch — this is what makes the pass whole-program:
  a helper in module B that returns a device array taints its callers
  in module A only when both files are in the batch);
- instance attributes assigned tainted values anywhere in the batch
  (``self._dev_v`` in chunk/column.py, ``self._fn`` program slots).

Rules:

- **DF801** hidden host sync: ``np.asarray`` / ``.item()`` / ``float()``
  / ``bool()`` / ``.tolist()`` / ``block_until_ready`` on a
  device-tainted value inside a dispatch-hot region — any function
  reachable (whole-program) from an executor ``next``/drain loop, a
  devpipe stage, or a batching dispatch/replay leg — outside the
  sanctioned wrapper modules (ops/kernels.py owns ``d2h``/``d2h_many``
  and the two-phase scalar-sync protocol; ops/profiler.py owns the
  sampled ``block_until_ready``; utils/xferaudit.py IS the interposer).
  A hidden sync stalls the dispatch pipeline for a full link round trip
  AND escapes the transfer counters that EXPLAIN ANALYZE, the bench,
  and the tsring advisor treat as ground truth.
- **DF802** uncounted transfer: a ``jax.device_put`` or implicit-upload
  call site (``jn.asarray`` / ``jnp.array`` over host values) outside
  ops/kernels.py — the invariant PR 11 established by hand sweep.
  Route uploads through ``kernels.h2d`` / ``h2d_pad``.
- **DF803** retrace hazard: a value-derived (non-shape) Python scalar
  flowing into a ``progcache`` key — TS107 generalized from closures to
  the full key-construction dataflow.  ``bucket()`` /
  ``occupancy_bucket()`` / ``len()`` / ``stable_shape_key()`` LAUNDER
  value taint (bucketing is exactly how a data-dependent count becomes
  a shape-stable key; the two-phase ``present_keep`` protocol depends
  on it).
- **DF804** device-buffer escape: a device-tainted value stored into a
  module-level container outside the registered cache owners
  (progcache's ``_REG``, kernels' program/constant tables, batching's
  park sites, exprjit's ParamTable staging, the columnar replica memo).
  Module caches never rotate with replicas, so an escaped device buffer
  pins HBM for the process lifetime — a leak no test notices on the
  8-way virtual CPU mesh but item 1's real mesh multiplies by N chips.

The dynamic twin is ``tools/transfer_audit.py`` + ``utils/xferaudit.py``
(TINYSQL_XFER_AUDIT=1): interpose jax's transfer entry points, replay
the serve/spill/batching subsets, and fail on any observed transfer the
STATS counters cannot explain — proving the static pass and the metrics
tell the same story.

Suppressions follow the tree-wide protocol::

    np.asarray(dev)  # qlint: disable=DF801 -- why this sync is designed

Entry point: :func:`lint_device_flow` over ONE batch of sources (like
``lint_concurrency``, cross-module findings only exist in the union).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .concurrency import _Func, _Module, _Program, _call_name, _self_attr
from .diag import Diagnostic, SourceFile, register_rules

register_rules({
    "DF801": "hidden host sync on a device value in a dispatch-hot region",
    "DF802": "device upload not routed through counted kernels.h2d/h2d_pad",
    "DF803": "value-derived (non-shape) scalar flows into a progcache key",
    "DF804": "device array stored in a module-level container outside the "
             "registered cache owners",
    "DF805": "raw shard_map construction / collective outside the "
             "dist.shard_map_fn wiring",
    "DF806": "host sync or numpy call inside a shard_map body",
    "DF807": "mesh-shape scalar flows into a progcache key outside the "
             "sanctioned launders (dist.mesh_shards/shard_bucket)",
})

# ---- taint vocabulary ------------------------------------------------------

#: calls whose RESULT is a device array (birth sites)
_DEV_BIRTH = {"h2d", "h2d_pad", "device_put", "_dev_upload", "_params_dev"}
#: calls whose RESULT is a compiled device program (calling it -> device)
_DEVFN_BIRTH = {"counted_jit", "_stackable_jit", "jit", "vmap", "pmap"}
#: calls that LAUNDER device taint back to counted host memory
_LAUNDER = {"d2h", "d2h_many", "unpack_flat", "unpack_host", "_slice_pack",
            "stats_snapshot", "stats_delta"}
#: builtins that pass their operands' taint through (zip(outs, ...) must
#: not launder a device value — the TPUProjectionExec.next find)
_PASSTHROUGH = {"zip", "enumerate", "reversed", "sorted", "list", "tuple",
                "iter", "next", "map", "filter", "min", "max"}
#: receiver names that ARE the jax namespace (tree idiom: jn = jnp())
_JAX_NS = {"jn", "jnp", "jax", "j"}
#: jax-namespace calls that return HOST metadata, not device arrays
_JAX_HOST_CALLS = {"devices", "local_devices", "device_count",
                   "local_device_count", "default_backend",
                   "process_index", "process_count", "make_jaxpr",
                   "tree_flatten", "tree_unflatten", "tree_map"}
#: instance-attribute NAMING convention: `self._dev*` slots hold device
#: arrays (chunk/column.py DeviceColumn) — taints attribute loads even
#: when the assignment flows through an untainted constructor parameter
_DEV_ATTR_PREFIX = "_dev"
#: attribute reads that stay host/shape metadata on a device value
_SHAPE_ATTRS = {"shape", "dtype", "ndim", "size", "nbytes", "sharding",
                "stack_info"}
#: host-sync method names (DF801 sinks when the receiver is tainted)
_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
#: scalar coercions (DF801 sinks when an argument is tainted)
_SYNC_COERCE = {"float", "int", "bool"}
#: calls that LAUNDER value taint into a shape-stable key component
#: (bucketing data-dependent counts is THE sanctioned retrace bound;
#: mesh_shards/shard_bucket/_shards_tag are the mesh-shape analogues)
_VAL_LAUNDER = {"bucket", "occupancy_bucket", "len", "stable_shape_key",
                "id", "type", "isinstance", "hasattr",
                "mesh_shards", "shard_bucket", "_shards_tag"}

# ---- mesh discipline (DF805/DF806/DF807, ISSUE 17) ------------------------

#: mesh collectives: legal only under a shard_map wired through
#: parallel/dist.py (shard_map_fn / shard_map_unchecked) — a raw
#: collective outside that wiring dodges the one import point AND
#: the sharded tier's counter discipline
_COLLECTIVES = {"psum", "pmin", "pmax", "all_gather", "all_to_all",
                "ppermute", "psum_scatter", "axis_index", "pbroadcast"}
#: the sanctioned construction entry points (parallel/dist.py owns them)
_SHARD_WIRING = {"shard_map_fn", "shard_map_unchecked"}
#: the one module allowed to touch jax's shard_map entry points raw
_MESH_OWNER = ("parallel.dist",)
#: host-sync / host-compute sinks inside a shard_map body (DF806): a
#: numpy call or transfer wrapper inside the traced SPMD body either
#: fails at trace time or — worse — constant-folds host-side per shard
_BODY_SINK_CALLS = {"d2h", "d2h_many", "h2d", "h2d_pad", "print", "open"}
#: calls whose RESULT is a mesh-shape scalar (DF807 births)
_MESH_BIRTHS = {"devices", "device_count", "local_device_count"}
#: calls that LAUNDER mesh-shape taint into a sanctioned key component
_MESH_LAUNDER = {"mesh_shards", "shard_bucket", "_shards_tag", "bucket"}

#: dispatch-hot roots by protocol name: executor iterators, drain loops,
#: the batching dispatch/replay legs (reachability closes over callees)
_HOT_ROOT_NAMES = {"next", "consume", "replay", "dispatch"}
_HOT_ROOT_PREFIXES = ("drain", "_drain", "_dispatch")
#: dynamic-dispatch hot seeds the call graph cannot see (receiver types
#: are erased at c.values()/take() call sites) — the late-materialization
#: methods run inside executor drain loops by construction
_HOT_SEEDS: List[Tuple[str, str]] = [
    ("chunk.column", "DeviceColumn._ensure_host"),
    ("chunk.column", "DeviceColumn.take"),
    ("chunk.column", "LazyTakeColumn._ensure_host"),
]

#: sanctioned-wrapper modules: DF801 does not fire inside them.
#: ops/kernels.py OWNS d2h/d2h_many and the two-phase protocol's designed
#: scalar syncs; ops/profiler.py owns the sampled block_until_ready;
#: utils/xferaudit.py interposes the raw entry points on purpose.
_SANCTIONED_MODULES = ("ops.kernels", "ops.profiler", "utils.xferaudit")

#: DF802 exemption: the module that IS the counted wrapper layer (plus
#: the runtime interposer, which must reach the raw entry points)
_UPLOAD_OWNERS = ("ops.kernels", "utils.xferaudit")

#: DF804 registered cache owners: progcache's _REG/catalog, kernels'
#: program & constant tables, batching's park sites, exprjit ParamTable
#: staging, the columnar replica memo
_ESCAPE_OWNERS = ("ops.progcache", "ops.kernels", "ops.batching",
                  "ops.exprjit", "columnar.store")


def _mod_endswith(modpath: str, suffixes) -> bool:
    return any(modpath.endswith(s) for s in suffixes)


# ===========================================================================
# whole-program taint state
# ===========================================================================

class _FlowState:
    """Fixed-point facts shared across the batch: which functions return
    device values / program wrappers, and which instance attributes hold
    them (collected from every ``self.x = <tainted>`` in the batch)."""

    def __init__(self, prog: _Program):
        self.prog = prog
        self.dev_returning: Set[str] = set()
        self.devfn_returning: Set[str] = set()
        self.dev_attrs: Set[str] = set()
        self.devfn_attrs: Set[str] = set()

    def solve(self) -> None:
        for _ in range(6):  # taint heights are tiny; 6 >> fixpoint depth
            changed = False
            for f in self.prog.funcs.values():
                fl = _FnFlow(self, f)
                fl.scan()
                if fl.returns_dev and f.qual not in self.dev_returning:
                    self.dev_returning.add(f.qual)
                    changed = True
                if fl.returns_devfn and f.qual not in self.devfn_returning:
                    self.devfn_returning.add(f.qual)
                    changed = True
                for a in fl.attr_dev:
                    if a not in self.dev_attrs:
                        self.dev_attrs.add(a)
                        changed = True
                for a in fl.attr_devfn:
                    if a not in self.devfn_attrs:
                        self.devfn_attrs.add(a)
                        changed = True
            if not changed:
                break


class _FnFlow:
    """One function's local taint environment.  ``scan()`` collects the
    fixed-point facts (returns / attribute assignments); ``check()``
    re-walks with the solved state and emits diagnostics."""

    def __init__(self, state: _FlowState, func: _Func):
        self.state = state
        self.func = func
        self.mod: _Module = next(m for m in state.prog.modules
                                 if m.modpath == func.mod)
        self.env: Dict[str, str] = {}      # name -> "dev" | "devfn"
        self.vals: Set[str] = set()        # value-derived local names
        self.meshv: Set[str] = set()       # mesh-shape-derived names
        self.returns_dev = False
        self.returns_devfn = False
        self.attr_dev: Set[str] = set()
        self.attr_devfn: Set[str] = set()
        self.diags: List[Diagnostic] = []
        self.checking = False

    # ---- cross-module call resolution (CC7xx's scheme) -------------------
    def _resolve(self, fn: ast.expr) -> Optional[str]:
        ref = None
        if isinstance(fn, ast.Name):
            ref = f"{self.mod.modpath}:{fn.id}"
        elif isinstance(fn, ast.Attribute):
            a = _self_attr(fn)
            if a is not None and self.func.cls is not None:
                ref = f"{self.mod.modpath}:{self.func.cls}.{a}"
            elif isinstance(fn.value, ast.Name):
                tgt = self.mod.imports.get(fn.value.id)
                if tgt:
                    ref = f"?{tgt}:{fn.attr}"
        if ref is None:
            return None
        return self.state.prog._find_qual(ref)

    def _is_numpy(self, recv: ast.expr) -> bool:
        return isinstance(recv, ast.Name) and (
            recv.id == "np"
            or self.mod.imports.get(recv.id, "").startswith("numpy"))

    def _is_jaxns(self, recv: ast.expr) -> bool:
        """Receiver is the jax / jax.numpy namespace (imported, aliased,
        or fetched through the kernels.jnp()/jax() lazy accessors)."""
        if isinstance(recv, ast.Name):
            tgt = self.mod.imports.get(recv.id, "")
            return recv.id in _JAX_NS or tgt.startswith("jax")
        if isinstance(recv, ast.Call):
            nm = _call_name(recv.func)
            return nm in ("jnp", "jax")
        return False

    def _is_progcache(self, recv: ast.expr) -> bool:
        if isinstance(recv, ast.Name):
            tgt = self.mod.imports.get(recv.id, "")
            return "progcache" in recv.id or tgt.endswith("progcache")
        if isinstance(recv, ast.Attribute):
            return "progcache" in recv.attr
        return False

    # ---- expression taint -------------------------------------------------
    def _taint(self, e: ast.expr) -> Optional[str]:
        if isinstance(e, ast.Name):
            return self.env.get(e.id)
        if isinstance(e, ast.Attribute):
            if e.attr in _SHAPE_ATTRS:
                return None
            if e.attr.startswith(_DEV_ATTR_PREFIX):
                return "dev"
            if e.attr in self.state.dev_attrs:
                return "dev"
            if e.attr in self.state.devfn_attrs:
                return "devfn"
            return self._taint(e.value)
        if isinstance(e, ast.Call):
            return self._call_taint(e)
        if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
            for x in e.elts:
                t = self._taint(x)
                if t is not None:
                    return t
            return None
        if isinstance(e, ast.Starred):
            return self._taint(e.value)
        if isinstance(e, (ast.ListComp, ast.GeneratorExp, ast.SetComp)):
            return self._comp_taint(e)
        if isinstance(e, ast.Subscript):
            return self._taint(e.value)
        if isinstance(e, ast.BinOp):
            return self._taint(e.left) or self._taint(e.right)
        if isinstance(e, ast.UnaryOp):
            return self._taint(e.operand)
        if isinstance(e, ast.BoolOp):
            for x in e.values:
                t = self._taint(x)
                if t is not None:
                    return t
            return None
        if isinstance(e, ast.IfExp):
            return self._taint(e.body) or self._taint(e.orelse)
        if isinstance(e, ast.Compare):
            t = self._taint(e.left)
            if t is not None:
                return t
            for x in e.comparators:
                t = self._taint(x)
                if t is not None:
                    return t
            return None
        if isinstance(e, ast.NamedExpr):
            return self._taint(e.value)
        return None

    def _comp_taint(self, e) -> Optional[str]:
        bound: List[str] = []
        for gen in e.generators:
            if self._taint(gen.iter) == "dev":
                for nm in _target_names(gen.target):
                    if nm not in self.env:
                        self.env[nm] = "dev"
                        bound.append(nm)
        try:
            return self._taint(e.elt)
        finally:
            for nm in bound:
                del self.env[nm]

    def _call_taint(self, e: ast.Call) -> Optional[str]:
        nm = _call_name(e.func)
        if nm in _LAUNDER:
            return None
        if nm in _DEV_BIRTH:
            return "dev"
        if nm in _DEVFN_BIRTH:
            return "devfn"
        if nm in _PASSTHROUGH:
            for a in e.args:
                t = self._taint(a)
                if t is not None:
                    return t
            return None
        if isinstance(e.func, ast.Attribute):
            recv = e.func.value
            if e.func.attr in _SYNC_ATTRS:
                return None  # result is host (flagged separately if hot)
            if self._is_jaxns(recv):
                # any jax-namespace call yields a device value — except
                # the host-metadata accessors (jax.devices() etc.)
                if e.func.attr in _JAX_HOST_CALLS:
                    return None
                return "dev"
            if e.func.attr == "get" and self._is_progcache(recv):
                return "devfn"  # progcache entries are program wrappers
            if e.func.attr == "memo" and len(e.args) >= 2 \
                    and isinstance(e.args[1], ast.Lambda):
                # replica memo: rep.memo(key, lambda: kernels.h2d(...))
                return self._taint(e.args[1].body)
        # calling a program wrapper dispatches it -> device result
        if self._taint(e.func) == "devfn":
            return "dev"
        q = self._resolve(e.func)
        if q is not None:
            if q in self.state.dev_returning:
                return "dev"
            if q in self.state.devfn_returning:
                return "devfn"
            return None
        if isinstance(e.func, ast.Attribute):
            # unknown method on a device value (dev.sum(), dev.astype())
            # stays on device
            if e.func.attr not in _SYNC_ATTRS \
                    and self._taint(e.func.value) == "dev":
                return "dev"
        return None

    # ---- value-derived (non-shape) scalar taint (DF803) -------------------
    def _val(self, e: ast.expr) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.vals
        if isinstance(e, ast.Attribute):
            if e.attr in _SHAPE_ATTRS:
                return False
            if e.attr == "value":  # the Expression/Datum literal idiom
                return True
            return self._val(e.value)
        if isinstance(e, ast.Call):
            nm = _call_name(e.func)
            if nm in _VAL_LAUNDER:
                return False
            if nm in _SYNC_ATTRS:  # .item() materializes the value
                return True
            if nm in _SYNC_COERCE:
                return any(self._val(a) or self._taint(a) == "dev"
                           for a in e.args)
            return any(self._val(a) for a in e.args)
        if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
            return any(self._val(x) for x in e.elts)
        if isinstance(e, ast.Starred):
            return self._val(e.value)
        if isinstance(e, ast.BinOp):
            return self._val(e.left) or self._val(e.right)
        if isinstance(e, ast.UnaryOp):
            return self._val(e.operand)
        if isinstance(e, ast.IfExp):
            return self._val(e.body) or self._val(e.orelse)
        if isinstance(e, ast.Subscript):
            return self._val(e.value)
        return False

    # ---- mesh-shape scalar taint (DF807) ----------------------------------
    def _meshval(self, e: ast.expr) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.meshv
        if isinstance(e, ast.Attribute):
            if e.attr == "devices":
                return True
            return self._meshval(e.value)
        if isinstance(e, ast.Call):
            nm = _call_name(e.func)
            if nm in _MESH_LAUNDER:
                return False
            if nm in _MESH_BIRTHS:
                return True
            return any(self._meshval(a) for a in e.args)
        if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
            return any(self._meshval(x) for x in e.elts)
        if isinstance(e, ast.Starred):
            return self._meshval(e.value)
        if isinstance(e, ast.BinOp):
            return self._meshval(e.left) or self._meshval(e.right)
        if isinstance(e, ast.UnaryOp):
            return self._meshval(e.operand)
        if isinstance(e, ast.IfExp):
            return self._meshval(e.body) or self._meshval(e.orelse)
        if isinstance(e, ast.Subscript):
            return self._meshval(e.value)
        return False

    # ---- statement walk ---------------------------------------------------
    def scan(self) -> None:
        self.checking = False
        # two passes pick up loop-carried and use-before-def-order taint
        for _ in range(2):
            self._walk(self.func.node.body)

    def check(self, hot: bool) -> List[Diagnostic]:
        self.scan()  # environments are cheap; rebuild then emit
        self.checking = True
        self.hot = hot
        self._walk(self.func.node.body)
        return self.diags

    def _walk(self, stmts) -> None:
        for s in stmts:
            self._stmt(s)

    def _stmt(self, s: ast.stmt) -> None:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            return  # nested defs are separate _Funcs in the index
        if isinstance(s, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = s.targets if isinstance(s, ast.Assign) else [s.target]
            val = getattr(s, "value", None)
            if val is not None:
                self._visit_expr(val)
                t = self._taint(val)
                v = self._val(val)
                mv = self._meshval(val)
                for tgt in targets:
                    self._bind(tgt, t, v, mv)
                    self._store_check(tgt, val, t)
            return
        if isinstance(s, ast.Return):
            if s.value is not None:
                self._visit_expr(s.value)
                t = self._taint(s.value)
                if t == "dev":
                    self.returns_dev = True
                elif t == "devfn":
                    self.returns_devfn = True
            return
        if isinstance(s, ast.For):
            self._visit_expr(s.iter)
            if self._taint(s.iter) == "dev":
                for nm in _target_names(s.target):
                    self.env[nm] = "dev"
            self._walk(s.body)
            self._walk(s.orelse)
            return
        if isinstance(s, ast.While):
            self._visit_expr(s.test)
            self._walk(s.body)
            self._walk(s.orelse)
            return
        if isinstance(s, ast.If):
            self._visit_expr(s.test)
            # isinstance(x, np.ndarray) narrowing: inside the guarded
            # body x is PROVEN host — drop its device taint there
            narrowed: Dict[str, str] = {}
            for nm in _host_narrowed_names(s.test):
                if nm in self.env:
                    narrowed[nm] = self.env.pop(nm)
            self._walk(s.body)
            self.env.update(narrowed)
            self._walk(s.orelse)
            return
        if isinstance(s, ast.With):
            for item in s.items:
                self._visit_expr(item.context_expr)
            self._walk(s.body)
            return
        if isinstance(s, ast.Try):
            for blk in ([s.body, s.orelse, s.finalbody]
                        + [h.body for h in s.handlers]):
                self._walk(blk)
            return
        if isinstance(s, ast.Expr):
            self._visit_expr(s.value)
            self._mutator_check(s.value)
            return
        for child in ast.iter_child_nodes(s):
            if isinstance(child, ast.expr):
                self._visit_expr(child)

    def _bind(self, tgt: ast.expr, t: Optional[str], val: bool,
              mesh: bool = False) -> None:
        if isinstance(tgt, ast.Name):
            if t is not None:
                self.env[tgt.id] = t
            if val:
                self.vals.add(tgt.id)
            if mesh:
                self.meshv.add(tgt.id)
            return
        a = _self_attr(tgt)
        if a is not None:
            if t == "dev":
                self.attr_dev.add(a)
            elif t == "devfn":
                self.attr_devfn.add(a)
            return
        if isinstance(tgt, (ast.Tuple, ast.List)):
            for x in tgt.elts:
                self._bind(x, t, val, mesh)

    # ---- DF804: stores into module-level containers -----------------------
    def _container_of(self, base: ast.expr) -> Optional[Tuple[str, str]]:
        """(module, name) when ``base`` names a module-level container —
        local (``CACHE[...]``) or through a module alias
        (``mod.CACHE[...]``, resolved against the batch)."""
        if isinstance(base, ast.Name):
            if base.id in self.mod.containers:
                return (self.mod.modpath, base.id)
            return None
        if isinstance(base, ast.Attribute) \
                and isinstance(base.value, ast.Name):
            tgt = self.mod.imports.get(base.value.id)
            if tgt:
                tail = tgt.split(".")
                for m in self.state.prog.modules:
                    mp = m.modpath.split(".")
                    if mp[-len(tail):] == tail or mp[-1] == tail[-1]:
                        if base.attr in m.containers:
                            return (m.modpath, base.attr)
        return None

    def _store_check(self, tgt: ast.expr, val: ast.expr,
                     t: Optional[str]) -> None:
        if not self.checking or t != "dev":
            return
        if isinstance(tgt, ast.Subscript):
            owner = self._container_of(tgt.value)
            if owner is not None and not _mod_endswith(owner[0],
                                                       _ESCAPE_OWNERS):
                self._flag(
                    "DF804", tgt,
                    f"device array stored into module-level container "
                    f"`{owner[1]}` ({owner[0]}) — outside the registered "
                    f"cache owners (progcache/kernels/batching/exprjit/"
                    f"replica memo) nothing ever evicts it: the buffer "
                    f"pins HBM for the process lifetime")

    def _mutator_check(self, e: ast.expr) -> None:
        if not self.checking or not isinstance(e, ast.Call):
            return
        fn = e.func
        if not isinstance(fn, ast.Attribute) \
                or fn.attr not in ("append", "add", "insert", "setdefault",
                                   "update", "extend"):
            return
        owner = self._container_of(fn.value)
        if owner is None or _mod_endswith(owner[0], _ESCAPE_OWNERS):
            return
        for a in list(e.args) + [kw.value for kw in e.keywords]:
            if self._taint(a) == "dev":
                self._flag(
                    "DF804", e,
                    f"device array {fn.attr}()-ed into module-level "
                    f"container `{owner[1]}` ({owner[0]}) — outside the "
                    f"registered cache owners nothing evicts it (device-"
                    f"memory leak)")
                return

    # ---- DF801 / DF802 / DF803 sinks -------------------------------------
    def _visit_expr(self, e: ast.expr) -> None:
        if not self.checking:
            return
        for node in ast.walk(e):
            if isinstance(node, ast.Call):
                self._check_call(node)

    def _check_call(self, node: ast.Call) -> None:
        fn = node.func
        nm = _call_name(fn)
        mod = self.mod.modpath

        # DF802: raw upload entry points outside the wrapper owner
        if not _mod_endswith(mod, _UPLOAD_OWNERS):
            if nm == "device_put":
                self._flag(
                    "DF802", node,
                    "`device_put` upload outside ops/kernels.py — route "
                    "through the counted kernels.h2d/h2d_pad wrappers so "
                    "h2d_transfers/h2d_bytes (EXPLAIN ANALYZE, tsring, "
                    "the bench invariants) stay truthful")
            elif nm in ("asarray", "array") \
                    and isinstance(fn, ast.Attribute) \
                    and self._is_jaxns(fn.value):
                self._flag(
                    "DF802", node,
                    f"implicit device upload `{ast.unparse(fn)}(...)` "
                    "outside ops/kernels.py — an uncounted transfer; "
                    "route through kernels.h2d/h2d_pad")

        # DF803: value-derived scalar into a progcache key
        if nm == "get" and isinstance(fn, ast.Attribute) \
                and self._is_progcache(fn.value) and node.args:
            key = node.args[0]
            if self._val(key):
                self._flag(
                    "DF803", node,
                    "progcache key carries a value-derived (non-shape) "
                    "scalar — every distinct literal mints a new program "
                    "(unbounded retrace/compile); parameterize the value "
                    "(exprjit ParamTable) or bucket it "
                    "(kernels.bucket/occupancy_bucket) into a "
                    "shape-stable key component")
            # DF807: a raw mesh-shape scalar (device count, mesh.devices
            # size) in the key ties the program registry to the physical
            # topology instead of the laundered shard count — prewarm on
            # a different host mesh minted different keys, and a resized
            # mesh silently recompiles everything
            if self._meshval(key):
                self._flag(
                    "DF807", node,
                    "progcache key carries a raw mesh-shape scalar — "
                    "launder it through dist.mesh_shards / "
                    "dist.shard_bucket (the sanctioned bucketed shard "
                    "counts) so keys stay stable across physical device "
                    "topologies")

        # DF801: hidden host syncs in dispatch-hot regions
        if not self.hot or _mod_endswith(mod, _SANCTIONED_MODULES):
            return
        if nm in _SYNC_COERCE and node.args \
                and self._taint(node.args[0]) == "dev":
            self._flag(
                "DF801", node,
                f"`{nm}()` on a device value in a dispatch-hot region — "
                "a hidden blocking sync the transfer counters never see; "
                "use kernels.d2h (counted) or keep the value on device")
        elif isinstance(fn, ast.Attribute) and fn.attr in _SYNC_ATTRS \
                and self._taint(fn.value) == "dev":
            self._flag(
                "DF801", node,
                f"`.{fn.attr}()` on a device value in a dispatch-hot "
                "region — a hidden blocking sync outside the sanctioned "
                "d2h/d2h_many/profiler wrappers")
        elif nm in ("asarray", "array") and isinstance(fn, ast.Attribute) \
                and self._is_numpy(fn.value) and node.args \
                and self._taint(node.args[0]) == "dev":
            self._flag(
                "DF801", node,
                "`np.asarray` on a device value in a dispatch-hot region "
                "— an uncounted blocking download; use kernels.d2h / "
                "d2h_many (counted, span-attributed)")
        elif nm == "block_until_ready" and node.args \
                and self._taint(node.args[0]) == "dev":
            self._flag(
                "DF801", node,
                "`block_until_ready` in a dispatch-hot region outside "
                "the sampling profiler — stalls the dispatch pipeline")

    def _flag(self, rule: str, node, msg: str) -> None:
        self.diags.append(Diagnostic(
            rule, msg + f" (in `{self.func.qual}`)",
            self.mod.sf.path, getattr(node, "lineno", 0),
            getattr(node, "col_offset", 0)))


def _host_narrowed_names(test: ast.expr) -> List[str]:
    """Names a conditional PROVES are host numpy: conjuncts of the form
    ``isinstance(x, np.ndarray)`` (the _semi_next dtype-coercion idiom)."""
    out: List[str] = []
    for node in ast.walk(test):
        if isinstance(node, ast.Call) and _call_name(node.func) == "isinstance" \
                and len(node.args) == 2 \
                and isinstance(node.args[0], ast.Name) \
                and "ndarray" in ast.dump(node.args[1]):
            out.append(node.args[0].id)
    return out


def _target_names(tgt: ast.expr) -> List[str]:
    if isinstance(tgt, ast.Name):
        return [tgt.id]
    if isinstance(tgt, (ast.Tuple, ast.List)):
        out: List[str] = []
        for x in tgt.elts:
            out.extend(_target_names(x))
        return out
    if isinstance(tgt, ast.Starred):
        return _target_names(tgt.value)
    return []


# ===========================================================================
# hot-region computation (CC7xx reachability over the resolved call graph)
# ===========================================================================

def _hot_set(prog: _Program) -> Set[str]:
    roots: Set[str] = set()
    for f in prog.funcs.values():
        if f.name in _HOT_ROOT_NAMES \
                or f.name.startswith(_HOT_ROOT_PREFIXES):
            roots.add(f.qual)
    for msfx, name in _HOT_SEEDS:
        q = None
        for cand, f in prog.funcs.items():
            mod, fname = cand.split(":", 1)
            if fname == name and mod.endswith(msfx):
                q = cand
                break
        if q:
            roots.add(q)
    edges: Dict[str, List[str]] = {}
    for f in prog.funcs.values():
        lst = edges.setdefault(f.qual, [])
        for callee, _h, _ln in f.calls:
            if callee is not None:
                lst.append(callee)
        if f.nested_in is not None:
            # a nested def runs where its enclosing scope wires it
            edges.setdefault(f.nested_in, []).append(f.qual)
    seen = set(roots)
    stack = list(roots)
    while stack:
        cur = stack.pop()
        for nxt in edges.get(cur, ()):
            if nxt not in seen:
                seen.add(nxt)
                stack.append(nxt)
    return seen


# ===========================================================================
# mesh discipline (DF805 / DF806) — raw shard_map wiring + body hygiene
# ===========================================================================

def _mesh_discipline_diags(prog: _Program) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for m in prog.modules:
        if _mod_endswith(m.modpath, _MESH_OWNER):
            continue  # parallel/dist.py IS the wiring layer
        # DF805a: raw shard_map import — the one import point and
        # the unchecked-replication variant live in dist.py alone
        for node in ast.walk(m.sf.tree):
            if isinstance(node, ast.ImportFrom):
                modname = node.module or ""
                if "shard_map" in modname or (
                        modname.startswith("jax")
                        and any(a.name == "shard_map"
                                for a in node.names)):
                    out.append(Diagnostic(
                        "DF805",
                        "raw shard_map import outside parallel/dist.py — "
                        "construct through dist.shard_map_fn / "
                        "shard_map_unchecked (one import point, "
                        "one replication-check policy)",
                        m.sf.path, node.lineno, node.col_offset))
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if "shard_map" in a.name:
                        out.append(Diagnostic(
                            "DF805",
                            "raw shard_map import outside parallel/"
                            "dist.py — construct through "
                            "dist.shard_map_fn / shard_map_unchecked",
                            m.sf.path, node.lineno, node.col_offset))
        for f in m.funcs:
            if f.nested_in is not None:
                continue  # nested defs ride their top-level scope
            wired = any(
                isinstance(n, ast.Call)
                and _call_name(n.func) in _SHARD_WIRING
                for n in ast.walk(f.node))
            body_names: List[str] = []
            for n in ast.walk(f.node):
                if isinstance(n, ast.Call) \
                        and _call_name(n.func) in ("shard_map",
                                                   "shard_map_fn",
                                                   "shard_map_unchecked") \
                        and n.args and isinstance(n.args[0], ast.Name):
                    body_names.append(n.args[0].id)
                elif isinstance(n, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    # @partial(shard_map, ...) decorator idiom
                    for d in n.decorator_list:
                        if isinstance(d, ast.Call) \
                                and _call_name(d.func) == "partial" \
                                and d.args \
                                and isinstance(d.args[0], ast.Name) \
                                and d.args[0].id == "shard_map":
                            body_names.append(n.name)
            # DF805b: a collective with no dist wiring in scope runs
            # outside any shard_map this pass can see — it either traces
            # into a single-device program (wrong axis) or was wired raw
            if not wired:
                for n in ast.walk(f.node):
                    if isinstance(n, ast.Call) \
                            and _call_name(n.func) in _COLLECTIVES:
                        out.append(Diagnostic(
                            "DF805",
                            f"collective `{_call_name(n.func)}` outside "
                            "any dist.shard_map_fn/shard_map_unchecked "
                            "wiring in scope — mesh programs construct "
                            f"through parallel/dist.py (in `{f.qual}`)",
                            m.sf.path, n.lineno, n.col_offset))
            # DF806: host syncs / numpy compute inside the traced body
            if not body_names:
                continue
            for n in ast.walk(f.node):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and n.name in body_names:
                    out.extend(_body_sync_diags(m, f, n))
    return out


def _body_sync_diags(m: _Module, f: _Func, body) -> List[Diagnostic]:
    out: List[Diagnostic] = []

    def flag(node, msg):
        out.append(Diagnostic(
            "DF806", msg + f" (shard_map body `{body.name}` in "
            f"`{f.qual}`)", m.sf.path, node.lineno, node.col_offset))

    for n in ast.walk(body):
        if not isinstance(n, ast.Call):
            continue
        fn = n.func
        nm = _call_name(fn)
        if nm in _BODY_SINK_CALLS:
            flag(n, f"`{nm}` inside a shard_map body — the traced SPMD "
                 "program cannot host-sync; move the transfer outside "
                 "the shard_map")
        elif isinstance(fn, ast.Attribute) and fn.attr in _SYNC_ATTRS:
            flag(n, f"`.{fn.attr}()` inside a shard_map body — a host "
                 "sync under trace either fails or constant-folds "
                 "per-shard host work into the program")
        elif isinstance(fn, ast.Attribute) \
                and isinstance(fn.value, ast.Name) \
                and (fn.value.id == "np"
                     or m.imports.get(fn.value.id, "").startswith("numpy")):
            flag(n, f"numpy call `np.{fn.attr}(...)` inside a shard_map "
                 "body — host compute under trace; use the jax "
                 "namespace so the work stays in the SPMD program")
    return out


# ===========================================================================
# module-body escapes (DF804 at import time)
# ===========================================================================

def _module_body_diags(state: _FlowState, m: _Module) -> List[Diagnostic]:
    if _mod_endswith(m.modpath, _ESCAPE_OWNERS):
        return []
    shim = _Func(m.modpath, None, "<module>", ast.Module(body=[], type_ignores=[]))
    fl = _FnFlow(state, shim)
    out: List[Diagnostic] = []
    for node in m.sf.tree.body:
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        val = getattr(node, "value", None)
        if val is None or fl._taint(val) != "dev":
            continue
        out.append(Diagnostic(
            "DF804",
            "module-level binding holds a device array at import time — "
            "outside the registered cache owners nothing ever releases "
            "it (device-memory pin for the process lifetime)",
            m.sf.path, node.lineno, node.col_offset))
    return out


# ===========================================================================
# entry point
# ===========================================================================

def lint_device_flow(sources: List[SourceFile]) -> List[Diagnostic]:
    """Whole-program DF8xx over ONE batch (cross-module taint and hot
    reachability only exist in the union, exactly like CC7xx)."""
    prog = _Program(sources)
    state = _FlowState(prog)
    state.solve()
    hot = _hot_set(prog)
    diags: List[Diagnostic] = []
    for f in prog.funcs.values():
        fl = _FnFlow(state, f)
        diags.extend(fl.check(f.qual in hot))
    for m in prog.modules:
        diags.extend(_module_body_diags(state, m))
    diags.extend(_mesh_discipline_diags(prog))
    out = []
    for d in diags:
        sf = prog.by_path.get(d.path)
        if sf is not None and sf.suppressed(d.rule, d.line):
            continue
        out.append(d)
    out.sort(key=lambda d: (d.path, d.line, d.rule))
    return out


def hot_report(sources: List[SourceFile]) -> List[str]:
    """The computed dispatch-hot set (introspection / docs)."""
    return sorted(_hot_set(_Program(sources)))

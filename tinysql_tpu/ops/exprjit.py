"""Expression tree -> JAX: the device-side vectorized evaluator.

The TPU counterpart of the reference's VecEval* builtins
(expression/builtin_*_vec.go): each numeric expression tree lowers to a
jittable function over (values, null-mask) device-array pairs with MySQL
3-valued null semantics.  XLA fuses the whole tree into a handful of
elementwise kernels — the TPU-first replacement for the reference's
per-builtin Go loops (SURVEY §2.5 note).

Only INT/REAL expressions lower; the planner's device enforcer
(planner/device.py) keeps strings on the CPU tier.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..expression import Column, Constant, Expression, ScalarFunction
from ..mytypes import EvalType

# lazy jax import so CPU-only paths never pay for it
_jnp = None


def jnp():
    global _jnp
    if _jnp is None:
        from . import kernels
        _jnp = kernels.jnp()  # shares the one-time jax configuration (x64)
    return _jnp


JITTABLE_FUNCS = {
    "+", "-", "*", "/", "div", "%", "unaryminus", "abs",
    "=", "!=", "<", "<=", ">", ">=", "<=>",
    "and", "or", "xor", "not", "isnull", "istrue", "isfalse",
    "if", "ifnull", "case", "in", "cast_int", "cast_real",
}


def is_jittable(e: Expression) -> bool:
    """Can this tree run on device?  (numeric-only, known functions)"""
    if e.eval_type is EvalType.STRING:
        return False
    if isinstance(e, Column):
        return e.eval_type is not EvalType.STRING
    if isinstance(e, Constant):
        return not isinstance(e.value, str)
    if isinstance(e, ScalarFunction):
        if e.name not in JITTABLE_FUNCS:
            return False
        if (e.name in ("div", "%") and len(e.args) == 2
                and all(a.eval_type is EvalType.INT for a in e.args)):
            u = [getattr(a.ret_type, "is_unsigned", False) for a in e.args]
            if u[0] != u[1]:  # mixed-signedness int div/mod: CPU tier only
                return False
        return all(is_jittable(a) for a in e.args)
    return False


VV = Tuple[object, object]  # (jnp values, jnp bool null-mask)


def _truthy(a: VV):
    v, nl = a
    return v != 0, nl


def compile_expr(e: Expression) -> Callable[[Sequence[VV]], VV]:
    """Build a python closure evaluating `e` over device columns; the result
    is jit-traceable (call it inside jax.jit)."""
    j = jnp()
    if isinstance(e, Column):
        idx = e.index

        def col_fn(cols):
            return cols[idx]
        return col_fn
    if isinstance(e, Constant):
        val = e.value
        is_null = val is None
        if e.eval_type is EvalType.INT:
            from ..mytypes import wrap_i64
            cval = wrap_i64(int(val)) if val is not None else 0
            dt = j.int64
        else:
            cval = float(val) if val is not None else 0.0
            dt = j.float64

        def const_fn(cols):
            # broadcast length: first populated slot (sparse device-column
            # lists hold None for untouched columns; string slots may carry
            # only their null mask)
            n = _broadcast_len(cols)
            return (j.full((n,), cval, dtype=dt),  # qlint: disable=TS107 -- compile_expr IS the legacy literal-baked lowering; cached_compile_expr keys it by constant VALUE (stable_key), so the bake is correct here.  New fused/executor paths use compile_expr_params.
                    j.full((n,), is_null, dtype=bool))  # qlint: disable=TS107 -- NULL-ness is structural even in the params path; see compile_expr_params
        return const_fn
    assert isinstance(e, ScalarFunction), e
    args = [compile_expr(a) for a in e.args]
    arg_types = [a.eval_type for a in e.args]
    arg_uns = [a.eval_type is EvalType.INT
               and getattr(a.ret_type, "is_unsigned", False) for a in e.args]
    name = e.name
    ret_int = e.eval_type is EvalType.INT

    def fn(cols):
        vals = [a(cols) for a in args]
        return _apply(name, vals, arg_types, ret_int, arg_uns)
    return fn


def _to_real_u(v, unsigned: bool):
    """int64 -> float64 honoring the wrapped-uint64 representation."""
    j = jnp()
    r = v.astype(j.float64)
    if unsigned and v.dtype == j.int64:
        r = j.where(v < 0, r + 2.0**64, r)
    return r


def _int_div_j(a, safe_b, uns):
    """Truncating int64 div/mod on device.  Both-unsigned runs in uint64
    via bitcast; mixed signedness is rejected by is_jittable (CPU tier)."""
    j = jnp()
    from jax import lax
    if uns[0] and uns[1]:
        ua = lax.bitcast_convert_type(a, j.uint64)
        ub = lax.bitcast_convert_type(safe_b, j.uint64)
        q = ua // ub
        r = ua - ub * q
        return (lax.bitcast_convert_type(q, j.int64),
                lax.bitcast_convert_type(r, j.int64))
    q = j.abs(a) // j.abs(safe_b)
    q = j.where((a < 0) != (safe_b < 0), -q, q)
    return q, a - safe_b * q


def _int_lt_eq_j(a, ua: bool, b, ub: bool):
    """(lt, eq) for int64 device arrays with per-side unsignedness —
    mirrors expression/builtins._int_lt_eq."""
    j = jnp()
    if ua == ub:
        if ua:
            a = a ^ j.int64(-2**63)
            b = b ^ j.int64(-2**63)
        return a < b, a == b
    if ua:
        ok = (a >= 0) & (b >= 0)
        return ok & (a < b), ok & (a == b)
    ok = (a >= 0) & (b >= 0)
    return (a < 0) | (b < 0) | (a < b), ok & (a == b)


def _apply(name: str, vals: List[VV], arg_types, ret_int: bool,
           arg_uns=None) -> VV:
    j = jnp()
    arg_uns = arg_uns or [False] * len(vals)
    if name in ("+", "-", "*", "/", "div", "%"):
        (a, na), (b, nb) = vals
        null = na | nb
        int_math = (arg_types[0] is EvalType.INT
                    and arg_types[1] is EvalType.INT and name != "/")
        if not int_math:
            a = _to_real_u(a, arg_uns[0])
            b = _to_real_u(b, arg_uns[1])
        if name == "+":
            return a + b, null  # int: wrap-correct mod 2^64 any signedness
        if name == "-":
            return a - b, null
        if name == "*":
            return a * b, null
        safe_b = j.where(b == 0, 1, b)
        null = null | (b == 0)
        if name == "/":
            return a / safe_b, null
        if name == "div":
            if int_math:
                q = _int_div_j(a, safe_b, arg_uns)[0]
            else:
                q = j.trunc(a / safe_b).astype(j.int64)
            return q, null
        # %
        if int_math:
            return _int_div_j(a, safe_b, arg_uns)[1], null
        return j.where(b == 0, 0.0, j.where(
            j.sign(a) >= 0, j.abs(a) % j.abs(safe_b),
            -(j.abs(a) % j.abs(safe_b)))), null
    if name == "unaryminus":
        v, nl = vals[0]
        return -v, nl
    if name == "abs":
        v, nl = vals[0]
        return j.abs(v), nl
    if name in ("=", "!=", "<", "<=", ">", ">=", "<=>"):
        (a, na), (b, nb) = vals
        if arg_types[0] is not arg_types[1]:
            a = _to_real_u(a, arg_uns[0])
            b = _to_real_u(b, arg_uns[1])
            r = {"=": a == b, "<=>": a == b, "!=": a != b, "<": a < b,
                 "<=": a <= b, ">": a > b, ">=": a >= b}[name]
        elif (arg_types[0] is EvalType.INT
              and (arg_uns[0] or arg_uns[1])):
            lt, eq = _int_lt_eq_j(a, arg_uns[0], b, arg_uns[1])
            base = "=" if name == "<=>" else name
            r = {"=": eq, "!=": ~eq, "<": lt, "<=": lt | eq,
                 ">": ~(lt | eq), ">=": ~lt}[base]
        else:
            r = {"=": a == b, "<=>": a == b, "!=": a != b, "<": a < b,
                 "<=": a <= b, ">": a > b, ">=": a >= b}[name]
        if name == "<=>":
            v = j.where(na | nb, na & nb, r)
            return v.astype(j.int64), j.zeros_like(na)
        return r.astype(j.int64), na | nb
    if name == "and":
        (a, na), (b, nb) = (_truthy(v) for v in vals)
        fa, fb = (~a) & ~na, (~b) & ~nb
        v = (a & b) & ~(na | nb)
        null = (na | nb) & ~(fa | fb)
        return v.astype(j.int64), null
    if name == "or":
        (a, na), (b, nb) = (_truthy(v) for v in vals)
        ta, tb = a & ~na, b & ~nb
        v = ta | tb
        null = (na | nb) & ~v
        return v.astype(j.int64), null
    if name == "xor":
        (a, na), (b, nb) = (_truthy(v) for v in vals)
        return (a != b).astype(j.int64), na | nb
    if name == "not":
        a, na = _truthy(vals[0])
        return (~a).astype(j.int64), na
    if name == "isnull":
        v, nl = vals[0]
        return nl.astype(j.int64), j.zeros_like(nl)
    if name in ("istrue", "isfalse"):
        a, na = _truthy(vals[0])
        want = name == "istrue"
        v = j.where(na, False, a == want)
        return v.astype(j.int64), j.zeros_like(na)
    if name == "if":
        c, nc = _truthy(vals[0])
        take = c & ~nc
        (a, na), (b, nb) = vals[1], vals[2]
        return j.where(take, a, b), j.where(take, na, nb)
    if name == "ifnull":
        (a, na), (b, nb) = vals
        return j.where(na, b, a), na & nb
    if name == "case":
        has_else = len(vals) % 2 == 1
        pairs = len(vals) // 2
        proto = vals[1][0]
        v = j.zeros_like(proto)
        null = j.ones(proto.shape, dtype=bool)
        decided = j.zeros(proto.shape, dtype=bool)
        for p in range(pairs):
            c, nc = _truthy(vals[2 * p])
            take = c & ~nc & ~decided
            rv, rn = vals[2 * p + 1]
            v = j.where(take, rv, v)
            null = j.where(take, rn, null)
            decided = decided | take
        if has_else:
            rv, rn = vals[-1]
            v = j.where(decided, v, rv)
            null = j.where(decided, null, rn)
        return v, null
    if name == "in":
        x, xn = vals[0]
        hit = j.zeros(x.shape, dtype=bool)
        saw_null = j.zeros(x.shape, dtype=bool)
        for k, (item, inull) in enumerate(vals[1:], start=1):
            if x.dtype != item.dtype:
                xi = _to_real_u(x, arg_uns[0])
                it = _to_real_u(item, arg_uns[k])
                eq = xi == it
            elif x.dtype == j.int64 and (arg_uns[0] or arg_uns[k]):
                eq = _int_lt_eq_j(x, arg_uns[0], item, arg_uns[k])[1]
            else:
                eq = x == item
            hit = hit | (eq & ~inull & ~xn)
            saw_null = saw_null | inull
        return hit.astype(j.int64), ~hit & (saw_null | xn)
    if name == "cast_int":
        v, nl = vals[0]
        if v.dtype == j.int64:
            return v, nl
        r = j.where(v >= 0, j.floor(v + 0.5), -j.floor(-v + 0.5))
        r = j.clip(r, -2.0**63, 2.0**63 - 1)
        return r.astype(j.int64), nl
    if name == "cast_real":
        v, nl = vals[0]
        return _to_real_u(v, arg_uns[0]), nl
    raise ValueError(f"not jittable: {name}")


#: live ParamTables, weakly held — the HBM census claims any device
#: buffers a parameter staging path pins (today's slots are host python
#: lists and uploads are per-dispatch transients: the category reads 0)
import weakref  # noqa: E402
_LIVE_PARAM_TABLES: "weakref.WeakSet[ParamTable]" = weakref.WeakSet()


def _census_param_tables():
    for pt in list(_LIVE_PARAM_TABLES):
        yield [pt.i64, pt.f64]


from ..obs import memprof as _memprof  # noqa: E402  (cycle-free: memprof
#                                        imports no ops module at top level)
_memprof.register_census_walker("paramtable", _census_param_tables)


class ParamTable:
    """Per-query runtime parameters for compiled device programs.
    Constants lower to slot reads instead of baked literals, so a query
    that differs only in its constants (date bounds, LIMIT thresholds)
    reuses the SAME compiled XLA program.  compile_expr_params assigns
    slots in deterministic traversal order and fills the values as it
    walks; per query the caller re-runs it on the identically-shaped
    expression (closure rebuild is cheap; the jit program is cached by
    the shape key)."""

    def __init__(self):
        self.i64: list = []
        self.f64: list = []
        _LIVE_PARAM_TABLES.add(self)

    def add_int(self, v) -> int:
        from ..mytypes import wrap_i64
        self.i64.append(0 if v is None else wrap_i64(int(v)))
        return len(self.i64) - 1

    def add_real(self, v) -> int:
        self.f64.append(0.0 if v is None else float(v))
        return len(self.f64) - 1

    def arrays(self):
        return (np.asarray(self.i64, dtype=np.int64),
                np.asarray(self.f64, dtype=np.float64))

    @staticmethod
    def stack(tables, b: Optional[int] = None):
        """Stack N members' runtime-constant vectors on a LEADING batch
        axis: ``[(int64[Ni], float64[Nf]), ...] -> (int64[B, Ni],
        float64[B, Nf])`` — the params operand of a ``jax.vmap``-batched
        fused kernel (ops/kernels.stacked_variant), where the data
        columns stay shared and only the per-member constants carry the
        batch dimension.  ``tables`` holds ParamTables or their
        ``arrays()`` pairs; ``b`` pads the batch axis up to an occupancy
        bucket (rows past the member count repeat member 0 — inert: the
        dispatcher slices only real member rows off axis 0).  Raises
        ``ValueError`` on a slot-layout mismatch (members compiled from
        different expression shapes) — the stacked dispatch falls back
        to the legacy back-to-back leg on it."""
        pairs = [t.arrays() if isinstance(t, ParamTable) else t
                 for t in tables]
        if not pairs:
            raise ValueError("ParamTable.stack: no members")
        ni, nf = len(pairs[0][0]), len(pairs[0][1])
        for pi, pf in pairs[1:]:
            if len(pi) != ni or len(pf) != nf:
                raise ValueError(
                    f"ParamTable.stack: slot-layout mismatch "
                    f"({len(pi)}i/{len(pf)}f vs {ni}i/{nf}f)")
        b = len(pairs) if b is None else int(b)
        if b < len(pairs):
            raise ValueError(
                f"ParamTable.stack: bucket {b} < occupancy {len(pairs)}")
        idx = list(range(len(pairs))) + [0] * (b - len(pairs))
        return (np.stack([np.asarray(pairs[i][0], dtype=np.int64)
                          for i in idx]),
                np.stack([np.asarray(pairs[i][1], dtype=np.float64)
                          for i in idx]))


def compile_expr_params(e: Expression, pt: ParamTable) \
        -> Callable[[Sequence[VV], tuple], VV]:
    """Like compile_expr, but closures take (cols, (params_i64,
    params_f64)) and Constants read their value from a param slot.
    NULL-ness of a constant stays structural (baked)."""
    j = jnp()
    if isinstance(e, Column):
        idx = e.index

        def col_fn(cols, params):
            return cols[idx]
        return col_fn
    if isinstance(e, Constant):
        is_null = e.value is None
        if e.eval_type is EvalType.INT:
            slot = pt.add_int(e.value)

            def const_fn(cols, params, slot=slot, is_null=is_null):
                n = _broadcast_len(cols)
                v = j.full((n,), 1, dtype=j.int64) * params[0][slot]
                return v, j.full((n,), is_null, dtype=bool)
        else:
            slot = pt.add_real(e.value)

            def const_fn(cols, params, slot=slot, is_null=is_null):
                n = _broadcast_len(cols)
                v = j.full((n,), 1.0, dtype=j.float64) * params[1][slot]
                return v, j.full((n,), is_null, dtype=bool)
        return const_fn
    assert isinstance(e, ScalarFunction), e
    args = [compile_expr_params(a, pt) for a in e.args]
    arg_types = [a.eval_type for a in e.args]
    arg_uns = [a.eval_type is EvalType.INT
               and getattr(a.ret_type, "is_unsigned", False) for a in e.args]
    name = e.name
    ret_int = e.eval_type is EvalType.INT

    def fn(cols, params):
        vals = [a(cols, params) for a in args]
        return _apply(name, vals, arg_types, ret_int, arg_uns)
    return fn


class _DeadColumn:
    """A slot of a column vector that nothing above reads (the fused
    pipeline's liveness, executor/devpipe.py): it holds no lanes, and
    reading it fails the trace instead of answering wrongly."""

    def _read(self, *_):
        raise LookupError("a dead column was read: its consumer did not "
                          "name the slot as live")
    __iter__ = __getitem__ = __len__ = _read


DEAD = _DeadColumn()


def _broadcast_len(cols) -> int:
    for c in cols:
        if c is None or c is DEAD:
            continue
        arr = c[0] if c[0] is not None else c[1]
        if arr is not None:
            return arr.shape[0]
    return 1


def stable_shape_key(e: Expression) -> str:
    """stable_key with constant VALUES erased — the program-cache key for
    the params-compiled variant (same shape + types = same program)."""
    if isinstance(e, Column):
        return f"@{e.index}:{e.ret_type.tp}:{e.ret_type.flag & 32}"
    if isinstance(e, Constant):
        return f"c?({'N' if e.value is None else 'v'}:{e.ret_type.tp})"
    if isinstance(e, ScalarFunction):
        return f"{e.name}({','.join(stable_shape_key(a) for a in e.args)})"
    return repr(e)


def stable_key(e: Expression) -> str:
    """Cache key independent of per-query Column unique ids: identifies an
    expression by schema OFFSETS + types, so the same query shape reuses
    one compiled program across sessions."""
    if isinstance(e, Column):
        return f"@{e.index}:{e.ret_type.tp}:{e.ret_type.flag & 32}"
    if isinstance(e, Constant):
        return f"c({e.value!r}:{e.ret_type.tp})"
    if isinstance(e, ScalarFunction):
        return f"{e.name}({','.join(stable_key(a) for a in e.args)})"
    return repr(e)


def cached_compile_expr(e: Expression) -> Callable[[Sequence[VV]], VV]:
    """compile_expr memoized through the shared program registry
    (ops/progcache): the closure build is pure over the expression SHAPE
    — stable_key pins schema offsets, types, the unsigned flag, and
    constant values — so identical trees across queries share ONE
    closure, and the kernels that embed it key their jit programs off
    the same identity."""
    from . import progcache
    key = ("exprfn", stable_key(e), str(e.eval_type))
    return progcache.get(key, lambda: compile_expr(e))



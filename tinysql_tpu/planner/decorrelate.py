"""Subquery decorrelation: expression subqueries -> join operators.

Capability parity with reference planner/core/expression_rewriter.go's
subquery handling (PatternInExpr / ExistsSubqueryExpr -> LogicalJoin semi
variants) plus the decorrelation slice of rule_decorrelate.go, reduced to
the shapes this grammar produces:

- ``expr IN (SELECT c FROM ...)`` as a top-level WHERE conjunct becomes a
  SEMI join on ``expr = c``; ``NOT IN`` becomes a NULL-AWARE ANTI join
  (three-valued logic: any NULL build key kills every probe row, a NULL
  probe key passes only an empty build side).
- ``[NOT] EXISTS (SELECT ...)`` becomes a SEMI/ANTI join.  Correlated
  equality conjuncts in the subquery's WHERE (``inner.x = outer.y``) are
  pulled up as the join's equi-keys; other correlated conjuncts become
  join ``other_conditions``; fully-local conjuncts stay inside the
  subquery.  An uncorrelated EXISTS degenerates to a cartesian semi join
  (the executor only checks build-side emptiness).
- A CORRELATED scalar subquery that is a direct operand of a comparison
  in a top-level WHERE conjunct (``l_quantity < (SELECT 0.2 * avg(x)
  FROM t WHERE t.k = outer.k)``) becomes an aggregate grouped by the
  subquery's correlation columns, computed once for every key, joined
  back on those equalities, with the comparison as a filter above the
  join (the Apply-over-aggregation arm of rule_decorrelate.go).  Exact
  where the subquery's value is NULL over an empty group and the
  comparison rejects NULL: one aggregate of MIN, MAX, SUM or AVG, or
  arithmetic over one; an outer row with no group then drops out of the
  inner join as its comparison with NULL dropped it.  COUNT (0 over an
  empty group), ``<=>``, a correlation other than an equality of
  columns, GROUP BY / HAVING / LIMIT in the subquery, a correlated
  column in its select list or inside a subquery nested in it are
  refused by name (``UnsupportedSubquery``, error 1235).
- Any other scalar subquery is evaluated EAGERLY at plan time and folded
  to a Constant — the reference evaluates uncorrelated scalar
  subqueries during optimization the same way, and the literal
  parameterization erases the folded constant from program cache keys,
  so a changed subquery result is still a compiled program HIT.  A
  correlated one there (the select list, under OR) is refused by name.

The pass runs INSIDE PlanBuilder.build_select, before the residual WHERE
becomes a LogicalSelection, so everything downstream (pushdown, pruning,
reorder, the device enforcer) sees plain logical join nodes.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from ..expression import (Column, Expression, fold_constants, new_function,
                          split_cnf)
from ..parser import ast
from .logical import (JOIN_ANTI, JOIN_INNER, JOIN_SEMI, LogicalJoin,
                      LogicalPlan, LogicalSelection, LogicalTableDual)

#: comparisons that reject NULL: an outer row whose group is empty
#: compares with the subquery's NULL and drops out, as it drops out of
#: the inner join that replaces the subquery (``<=>`` holds for NULLs)
_NULL_REJECTING = ("=", "!=", "<", "<=", ">", ">=")
#: aggregates whose value over no row is NULL (COUNT's is 0)
_NULL_ON_EMPTY = ("min", "max", "sum", "avg")


def split_and_conjuncts(e: ast.ExprNode) -> List[ast.ExprNode]:
    """Top-level AND split of a WHERE tree (parens transparent)."""
    if isinstance(e, ast.ParenExpr):
        return split_and_conjuncts(e.expr)
    if isinstance(e, ast.BinaryOp) and e.op == "and":
        return split_and_conjuncts(e.left) + split_and_conjuncts(e.right)
    return [e]


def _unwrap_not(e: ast.ExprNode) -> Tuple[ast.ExprNode, bool]:
    """Strip ParenExpr and count NOT wrappers -> (inner, negated)."""
    neg = False
    while True:
        if isinstance(e, ast.ParenExpr):
            e = e.expr
        elif isinstance(e, ast.UnaryOp) and e.op == "not":
            e = e.operand
            neg = not neg
        else:
            return e, neg


def _subquery_conjunct(e: ast.ExprNode):
    """(kind, node, negated) when `e` is a decorrelatable conjunct —
    kind 'in' (InExpr over a SubqueryExpr) or 'exists' — else None."""
    inner, neg = _unwrap_not(e)
    if isinstance(inner, ast.InExpr) and len(inner.items) == 1 \
            and isinstance(inner.items[0], ast.SubqueryExpr):
        return "in", inner, neg ^ inner.negated
    if isinstance(inner, ast.ExistsExpr):
        return "exists", inner, neg ^ inner.negated
    return None


def apply_where_subqueries(builder, p: LogicalPlan,
                           where: ast.ExprNode
                           ) -> Tuple[LogicalPlan, List[ast.ExprNode]]:
    """Rewrite every subquery-bearing top-level conjunct of `where` into
    a semi/anti join over `p`; returns (new plan, residual AST
    conjuncts).  Scalar subqueries inside residual conjuncts are handled
    later by the expression rewriter (eager evaluation)."""
    residual: List[ast.ExprNode] = []
    for conj in split_and_conjuncts(where):
        got = _subquery_conjunct(conj)
        if got is None:
            cmp = _scalar_comparison(conj)
            joined = build_scalar_agg_join(builder, p, *cmp) \
                if cmp is not None else None
            if joined is None:
                residual.append(conj)
            else:
                p = joined
            continue
        kind, node, negated = got
        if kind == "in":
            p = build_in_join(builder, p, node, negated)
        else:
            p = build_exists_join(builder, p, node, negated)
    return p, residual


def _unparen(e: ast.ExprNode) -> ast.ExprNode:
    while isinstance(e, ast.ParenExpr):
        e = e.expr
    return e


def _scalar_comparison(e: ast.ExprNode):
    """(comparison, its subquery operand, whether that is the left one)
    when `e` compares with a scalar subquery as a direct operand."""
    e = _unparen(e)
    if not (isinstance(e, ast.BinaryOp)
            and e.op in _NULL_REJECTING + ("<=>",)):
        return None
    for left, side in ((True, e.left), (False, e.right)):
        side = _unparen(side)
        if isinstance(side, ast.SubqueryExpr):
            return e, side, left
    return None


def _has_subquery(e: ast.ExprNode) -> bool:
    return any(isinstance(x, (ast.SubqueryExpr, ast.ExistsExpr))
               for x in ast.walk_expr(e))


def _arithmetic_over(e: ast.ExprNode, agg: ast.AggFunc) -> bool:
    """`e` is `agg`, or + - * / and unary minus over it and literals:
    NULL wherever `agg` is."""
    e = _unparen(e)
    if e is agg or isinstance(e, ast.Literal):
        return True
    if isinstance(e, ast.UnaryOp) and e.op == "-":
        return _arithmetic_over(e.operand, agg)
    return isinstance(e, ast.BinaryOp) and e.op in ("+", "-", "*", "/") \
        and _arithmetic_over(e.left, agg) and _arithmetic_over(e.right, agg)


def correlated_name_error(err, outer_schema):
    """The error for an unknown column met inside a subquery: the shape
    refused by name where the column is one of the enclosing query's
    (``outer_schema``), else ``err`` as it is."""
    from .builder import UnsupportedSubquery, _find_in_schema
    parts = err.name.split(".")
    ref = ast.ColumnRef(parts[-1], table=parts[-2] if len(parts) > 1
                        else "")
    if _find_in_schema(outer_schema, ref):
        return UnsupportedSubquery(
            "a correlated subquery other than a comparison operand in a"
            " top-level WHERE conjunct, correlated by column equalities")
    return err


def build_scalar_agg_join(builder, p: LogicalPlan, cmp: ast.BinaryOp,
                          sub: ast.SubqueryExpr, sub_left: bool
                          ) -> Optional[LogicalPlan]:
    """``outer_expr <op> (SELECT f(agg(x)) FROM ... WHERE inner.k =
    outer.k AND ...)`` -> ``Selection(outer_expr <op> v, p JOIN (SELECT
    inner.k, f(agg(x)) AS v FROM ... WHERE ... GROUP BY inner.k) ON
    outer.k = inner.k)``.  None where the subquery references nothing of
    the outer query in its WHERE (the eager fold's); refused by name
    where it does in a shape this arm cannot make exact."""
    from .builder import ExprRewriter, UnknownColumn, UnsupportedSubquery
    stmt = sub.select
    if stmt.from_ is None or stmt.where is None:
        return None
    sub_p = builder.build_table_refs(stmt.from_)
    rw = ExprRewriter(sub_p.schema, builder, outer_schema=p.schema)
    local: List[ast.ExprNode] = []
    keys = []   # (AST of the inner column, outer Column)
    for conj in split_and_conjuncts(stmt.where):
        if _has_subquery(conj):
            local.append(conj)  # built in its own scope below
            continue
        e = rw.rewrite(conj)
        if all(sub_p.schema.contains(c) for c in e.collect_columns()):
            local.append(conj)
            continue
        pair = _eq_pair(e, p.schema, sub_p.schema)
        eq = _unparen(conj)
        if pair is None or not all(isinstance(x, Column) for x in pair) \
                or not isinstance(eq, ast.BinaryOp):
            raise UnsupportedSubquery(
                "correlated scalar subquery correlated by other than an"
                " equality of an inner and an outer column")
        inner_ast = next(_unparen(x) for x in (eq.left, eq.right)
                         if rw.resolve_column(_unparen(x)) is pair[1])
        keys.append((inner_ast, pair[0]))
    if not keys:
        return None

    def refuse(what: str):
        return UnsupportedSubquery(f"correlated scalar subquery {what}")
    if cmp.op == "<=>":
        raise refuse("under <=> (an empty group compares true there)")
    if stmt.group_by or stmt.having is not None or stmt.distinct \
            or stmt.order_by or stmt.limit is not None:
        raise refuse("with GROUP BY, HAVING, DISTINCT, ORDER BY or LIMIT")
    if len(stmt.fields) != 1 or stmt.fields[0].is_wildcard:
        raise refuse("of more than one column")
    field = stmt.fields[0].expr
    aggs = [x for x in ast.walk_expr(field) if isinstance(x, ast.AggFunc)]
    if len(aggs) != 1 or aggs[0].name not in _NULL_ON_EMPTY + ("count",):
        raise refuse("whose value is not one aggregate")
    if aggs[0].name == "count":
        raise refuse("over COUNT (an empty group counts 0, which the"
                     " join that replaces the subquery cannot give)")
    from .builder import _find_in_schema
    if any(isinstance(x, ast.ColumnRef)
           and not _find_in_schema(sub_p.schema, x)
           and _find_in_schema(p.schema, x) for x in ast.walk_expr(field)):
        raise refuse("with a correlated column in its select list")
    if not _arithmetic_over(field, aggs[0]):
        raise refuse("whose value is not arithmetic over its aggregate")

    n = getattr(builder, "subq_decorrelated", 0) + 1
    builder.subq_decorrelated = n
    where = None
    for c in local:
        where = c if where is None else ast.BinaryOp("and", where, c)
    # the aggregate's parts are the derived table's columns and the
    # arithmetic over them moves into the comparison above the join; an
    # AVG is its SUM over its COUNT (NULL over no row, as the AVG), so
    # that the comparison sees the two exact parts of the quotient
    agg = aggs[0]
    parts = [agg] if agg.name != "avg" else [
        ast.AggFunc(name, list(agg.args), agg.distinct)
        for name in ("sum", "count")]
    derived = ast.SelectStmt(
        fields=[ast.SelectField(k, as_name=f"_subq{n}_k{i}")
                for i, (k, _) in enumerate(keys)]
        + [ast.SelectField(a, as_name=f"_subq{n}_a{j}")
           for j, a in enumerate(parts)],
        from_=stmt.from_, where=where, group_by=[k for k, _ in keys])
    try:
        sub_plan = builder.build_select(derived)
    except UnknownColumn as err:
        raise correlated_name_error(err, p.schema) from err
    join = LogicalJoin(JOIN_INNER, p, sub_plan)
    join.eq_conditions.extend(
        (outer, col)
        for (_, outer), col in zip(keys, sub_plan.schema.columns))
    got = sub_plan.schema.columns[len(keys):]
    value = got[0] if len(got) == 1 else new_function("/", list(got))
    rw = ExprRewriter(join.schema, builder, agg_mapper={id(agg): value})
    v = rw.rewrite(field)
    other = rw.rewrite(cmp.right if sub_left else cmp.left)
    cond = new_function(cmp.op, [v, other] if sub_left else [other, v])
    from ..ops import kernels
    kernels.stats_add("subq_decorrelated", 1)
    return LogicalSelection([fold_constants(cond)], join)


def build_in_join(builder, p: LogicalPlan, ie: ast.InExpr,
                  negated: bool) -> LogicalJoin:
    """``expr [NOT] IN (SELECT c ...)`` -> semi / null-aware anti join.
    The subquery builds as a normal SELECT (aggregation, HAVING, its own
    subqueries all compose); it must produce exactly one column."""
    from .builder import ExprRewriter, PlanError
    sub = builder.build_select(ie.items[0].select)
    if len(sub.schema.columns) != 1:
        raise PlanError("Operand should contain 1 column(s)")
    rw = ExprRewriter(p.schema, builder)
    outer = fold_constants(rw.rewrite(ie.expr))
    join = LogicalJoin(JOIN_ANTI if negated else JOIN_SEMI, p, sub)
    join.eq_conditions.append((outer, sub.schema.columns[0]))
    # NOT IN is null-aware; NOT EXISTS (below) is not — a NULL correlated
    # key simply never matches there
    join.null_aware = negated
    return join


def build_exists_join(builder, p: LogicalPlan, ex: ast.ExistsExpr,
                      negated: bool) -> LogicalJoin:
    """``[NOT] EXISTS (SELECT ...)`` -> semi / anti join, decorrelating
    equality conjuncts that reference the outer scope."""
    from .builder import ExprRewriter, PlanError
    stmt = ex.select
    tp = JOIN_ANTI if negated else JOIN_SEMI
    if stmt.limit is not None and stmt.limit[1] == 0:
        # LIMIT 0: the subquery is empty by construction
        return LogicalJoin(tp, p, LogicalTableDual(row_count=0))
    if stmt.group_by or stmt.having or stmt.distinct or _has_aggs(stmt):
        # aggregate-shaped EXISTS: build the full subquery plan and use
        # it as an (uncorrelated) cartesian build side; a correlated
        # column inside is refused by name
        from .builder import UnknownColumn
        try:
            sub = builder.build_select(stmt)
        except UnknownColumn as err:
            raise correlated_name_error(err, p.schema) from err
        return LogicalJoin(tp, p, sub)
    if stmt.from_ is None:
        # EXISTS (SELECT <exprs>): one constant row, always non-empty
        return LogicalJoin(tp, p, LogicalTableDual(row_count=1))
    sub_p = builder.build_table_refs(stmt.from_)
    corr: List[Expression] = []
    if stmt.where is not None:
        rw = ExprRewriter(sub_p.schema, builder, outer_schema=p.schema)
        local: List[Expression] = []
        for c in split_cnf(rw.rewrite(stmt.where)):
            cols = c.collect_columns()
            if all(sub_p.schema.contains(x) for x in cols):
                local.append(fold_constants(c))
            else:
                corr.append(c)
        if local:
            sub_p = LogicalSelection(local, sub_p)
    join = LogicalJoin(tp, p, sub_p)
    for c in corr:
        pair = _eq_pair(c, p.schema, sub_p.schema)
        if pair is not None:
            join.eq_conditions.append(pair)
        else:
            join.other_conditions.append(c)
    return join


def _has_aggs(stmt: ast.SelectStmt) -> bool:
    for f in stmt.fields:
        if f.expr is not None and ast.has_agg(f.expr):
            return True
    return False


def _eq_pair(c: Expression, outer_schema,
             inner_schema) -> Optional[Tuple[Expression, Expression]]:
    """``inner_expr = outer_expr`` (either order) -> (outer, inner) pair
    for the semi join's equi-keys; None when the conjunct is not such an
    equality (it stays an other_condition)."""
    if getattr(c, "name", "") != "=" or len(c.children()) != 2:
        return None
    a, b = c.children()
    ac, bc = a.collect_columns(), b.collect_columns()
    if not ac or not bc:
        return None
    a_outer = all(outer_schema.contains(x) for x in ac)
    b_outer = all(outer_schema.contains(x) for x in bc)
    a_inner = all(inner_schema.contains(x) for x in ac)
    b_inner = all(inner_schema.contains(x) for x in bc)
    if a_outer and b_inner:
        return a, b
    if b_outer and a_inner:
        return b, a
    return None

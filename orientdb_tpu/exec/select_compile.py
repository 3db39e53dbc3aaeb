"""SELECT → single-node MATCH rewrite: the TPU compilation of SELECT.

The reference plans SELECT with its own executor ([E] OSelectStatement →
OSelectExecutionPlanner → fetch-from-class + filter steps; SURVEY.md §1
layer 5, §2 "SQL execution planner"). This engine already compiles MATCH
node filters to device predicate scans with hull-restricted root
candidates, COUNT pushdown, columnar RETURN marshalling, and the
parameter-generic plan cache — and a class-target SELECT is exactly a
single-node MATCH:

    SELECT <proj> FROM C WHERE <pred> [GROUP/ORDER/SKIP/LIMIT]
      ≡ MATCH {class:C, as:s, where:(<pred>)} RETURN <proj'>

so instead of a second compiled executor the rewrite translates the
statement and reuses the whole MATCH machinery. Field references in
projections/ORDER BY/GROUP BY become ``s.field`` accesses; the WHERE
moves into the node filter verbatim (node-filter WHERE already evaluates
with record fields in scope). `expr_name` is shared between SELECT and
MATCH, so unaliased projection names match the oracle's exactly.

Projection-less ``SELECT FROM C`` returns *element* rows; the rewrite
flags ``element_alias`` so the solver unwraps the binding back into a
record row after ORDER/SKIP/LIMIT run.

A second target compiles: a breadth-first ``TRAVERSE`` counted by
``$depth`` (`rewrite_level_counts`), whose rewrite is a `LevelCounts`
for the whole-graph search of ``exec/tpu_engine.TpuLevelsSolver``.

Ineligible statements raise `Uncompilable`, and the engine front door
falls back to the oracle interpreter — exactly the fallback contract the
MATCH path uses for its own unsupported shapes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from orientdb_tpu.exec.oracle import expr_name
from orientdb_tpu.ops.predicates import Uncompilable
from orientdb_tpu.sql import ast as A

#: the binding alias the rewritten root node carries; double-underscore
#: keeps it clear of user aliases, and it is NOT a `$` context var
ALIAS = "__sel__"

#: top-level functions that implicitly operate on the current record
#: (graph accessors) — their meaning does not survive the rewrite
_GRAPH_FUNCS = frozenset(
    ["out", "in", "both", "oute", "ine", "bothe", "outv", "inv", "expand"]
)


def _rewrite_expr(e: A.Expression) -> A.Expression:
    """Record-relative references become accesses on the bound alias."""
    if isinstance(e, A.Identifier):
        return A.FieldAccess(A.Identifier(ALIAS), e.name)
    if isinstance(e, A.ContextVar):
        raise Uncompilable(f"context var ${e.name} in SELECT")
    if isinstance(e, A.FunctionCall) and e.name.lower() in _GRAPH_FUNCS:
        raise Uncompilable(f"graph function {e.name}() in SELECT")
    if dataclasses.is_dataclass(e) and not isinstance(e, type):
        changes = {}
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            if isinstance(v, A.Expression):
                nv = _rewrite_expr(v)
                if nv is not v:
                    changes[f.name] = nv
            elif isinstance(v, tuple):
                # recurse through NESTED tuples too — map literals hold
                # (key, Expression) pairs a flat scan would miss
                nv = _rewrite_tuple(v)
                if nv != v:
                    changes[f.name] = nv
        if changes:
            return dataclasses.replace(e, **changes)
    return e


def _rewrite_tuple(v: tuple) -> tuple:
    return tuple(
        _rewrite_expr(x)
        if isinstance(x, A.Expression)
        else _rewrite_tuple(x)
        if isinstance(x, tuple)
        else x
        for x in v
    )


@dataclasses.dataclass(frozen=True)
class LevelCounts(A.Statement):
    """``SELECT $depth, count(*) FROM (TRAVERSE both('<class>') FROM
    (<roots>) STRATEGY BREADTH_FIRST) GROUP BY $depth``, rewritten: one
    row a depth of a whole-graph search from the vertices ``roots``
    selects. ``columns`` are the projections in order, ``(name, "depth"
    | "count")``."""

    roots: A.SelectStatement
    edge_class: str
    columns: Tuple[Tuple[str, str], ...]

    is_idempotent = True


def _is_depth(e: A.Expression) -> bool:
    return isinstance(e, A.ContextVar) and e.name == "depth"


def _is_count_star(e: A.Expression) -> bool:
    return (
        isinstance(e, A.FunctionCall)
        and e.name.lower() == "count"
        and len(e.args) == 1
        and isinstance(e.args[0], A.Star)
    )


def rewrite_level_counts(stmt: A.SelectStatement) -> LevelCounts:
    """Translate a SELECT whose target is a compilable ``TRAVERSE``: the
    vertices by ``$depth``. What compiles is the search of one edge
    class walked both ways, breadth-first (so ``$depth`` is the least),
    whole (no ``MAXDEPTH``, ``WHILE`` or ``LIMIT``), from the vertices a
    plain ``SELECT FROM <class> [WHERE ...]`` admits, grouped by
    ``$depth`` and projected as ``$depth`` and ``count(*)``."""
    trav = stmt.target.query
    if trav.strategy != "BREADTH_FIRST":
        raise Uncompilable("$depth over a DEPTH_FIRST TRAVERSE is not the least depth")
    if trav.max_depth is not None or trav.while_cond is not None:
        raise Uncompilable("TRAVERSE MAXDEPTH/WHILE under a SELECT is not compiled")
    if trav.limit is not None:
        raise Uncompilable("TRAVERSE LIMIT slices in traversal order")
    field = trav.fields[0] if len(trav.fields) == 1 else None
    if not (
        isinstance(field, A.FunctionCall)
        and field.name.lower() == "both"
        and len(field.args) == 1
        and isinstance(field.args[0], A.Literal)
        and isinstance(field.args[0].value, str)
    ):
        raise Uncompilable("TRAVERSE under a SELECT compiles for both('<class>') only")
    roots = trav.target.query if isinstance(trav.target, A.SubQueryTarget) else None
    if not isinstance(roots, A.SelectStatement) or (
        roots.projections
        or roots.group_by
        or roots.order_by
        or roots.skip is not None
        or roots.limit is not None
        or roots.distinct
    ):
        raise Uncompilable("TRAVERSE roots are not a plain SELECT FROM <class> WHERE")
    rewrite_select(roots)  # a class scan the MATCH engine takes, or its refusal
    if stmt.where is not None or stmt.lets or stmt.unwind or stmt.distinct:
        raise Uncompilable("SELECT over a TRAVERSE compiles without WHERE/LET/UNWIND/DISTINCT")
    if stmt.order_by or stmt.skip is not None or stmt.limit is not None:
        raise Uncompilable("SELECT over a TRAVERSE compiles without ORDER BY/SKIP/LIMIT")
    if len(stmt.group_by) != 1 or not _is_depth(stmt.group_by[0]):
        raise Uncompilable("SELECT over a TRAVERSE compiles with GROUP BY $depth only")
    columns = []
    for i, p in enumerate(stmt.projections):
        kind = "depth" if _is_depth(p.expr) else "count" if _is_count_star(p.expr) else None
        if kind is None:
            raise Uncompilable(
                "SELECT over a TRAVERSE projects $depth and count(*) only"
            )
        columns.append((p.alias or expr_name(p.expr, i), kind))
    if not columns:
        raise Uncompilable("GROUP BY on whole-record SELECT")
    return LevelCounts(roots, field.args[0].value, tuple(columns))


def rewrite_select(stmt: A.SelectStatement):
    """Translate an eligible SELECT. A class-target one becomes the MATCH
    statement and the element alias (set when the SELECT returns whole
    records); one over a ``TRAVERSE`` becomes a `LevelCounts` (and no
    alias). Raises Uncompilable for shapes the compiled engine cannot
    honor with oracle parity."""
    if isinstance(stmt.target, A.SubQueryTarget) and isinstance(
        stmt.target.query, A.TraverseStatement
    ):
        return rewrite_level_counts(stmt), None
    if not isinstance(stmt.target, A.ClassTarget) or not stmt.target.polymorphic:
        raise Uncompilable("SELECT target is not a polymorphic class scan")
    if stmt.lets:
        raise Uncompilable("SELECT LET is not compiled")
    if stmt.unwind:
        raise Uncompilable("SELECT UNWIND is not compiled")

    element_alias: Optional[str] = None
    if not stmt.projections and stmt.group_by:
        # oracle semantics: grouping without projections yields empty
        # rows, not representative records — no MATCH equivalent
        raise Uncompilable("GROUP BY on whole-record SELECT")
    if stmt.projections:
        returns = tuple(
            A.Projection(
                _rewrite_expr(p.expr),
                # pin the oracle's SELECT column name so unaliased
                # projections keep identical keys after the rewrite
                p.alias or expr_name(p.expr, i),
            )
            for i, p in enumerate(stmt.projections)
        )
        if any(isinstance(p.expr, A.Star) for p in stmt.projections):
            raise Uncompilable("SELECT * projection is not compiled")
    else:
        # whole-record SELECT: bind the node and unwrap to element rows
        # after the finalize tail
        if stmt.distinct:
            raise Uncompilable("DISTINCT on whole-record SELECT")
        element_alias = ALIAS
        returns = (A.Projection(A.Identifier(ALIAS), ALIAS),)

    node = A.MatchFilter(
        alias=ALIAS, class_name=stmt.target.name, where=stmt.where
    )
    match = A.MatchStatement(
        paths=(A.MatchPath(first=node, items=()),),
        returns=returns,
        distinct=stmt.distinct,
        group_by=tuple(_rewrite_expr(g) for g in stmt.group_by),
        order_by=tuple(
            dataclasses.replace(
                o, expr=_rewrite_order_expr(o.expr, stmt, element_alias)
            )
            for o in stmt.order_by
        ),
        skip=stmt.skip,
        limit=stmt.limit,
    )
    return match, element_alias


def _rewrite_order_expr(
    e: A.Expression, stmt: A.SelectStatement, element_alias: Optional[str]
):
    """ORDER BY resolution differs by mode. In element mode every field
    rides on the bound record, so expressions rewrite to alias accesses
    like any other. In projection mode the MATCH finalize tail sees only
    the projected row (no record fallback, unlike oracle SELECT's
    ordering), so the expression is kept VERBATIM and every identifier in
    it must name a projected column — anything else is Uncompilable, not
    silently None-sorted."""
    if element_alias is not None:
        return _rewrite_expr(e)
    projected = {p.alias for p in stmt.projections if p.alias} | {
        expr_name(p.expr, i)
        for i, p in enumerate(stmt.projections)
        if p.alias is None
    }
    _check_order_resolvable(e, projected)
    return e


def _check_order_resolvable(e: A.Expression, projected) -> None:
    if isinstance(e, A.Identifier):
        if e.name not in projected:
            raise Uncompilable(f"ORDER BY non-projected field {e.name}")
        return
    if isinstance(e, A.ContextVar):
        raise Uncompilable(f"context var ${e.name} in ORDER BY")
    if isinstance(e, A.FunctionCall) and e.name.lower() in _GRAPH_FUNCS:
        raise Uncompilable(f"graph function {e.name}() in ORDER BY")
    if dataclasses.is_dataclass(e) and not isinstance(e, type):
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            if isinstance(v, A.Expression):
                _check_order_resolvable(v, projected)
            elif isinstance(v, tuple):
                _check_order_tuple(v, projected)


def _check_order_tuple(v: tuple, projected) -> None:
    for x in v:
        if isinstance(x, A.Expression):
            _check_order_resolvable(x, projected)
        elif isinstance(x, tuple):
            _check_order_tuple(x, projected)

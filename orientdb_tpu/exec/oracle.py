"""Pure-Python reference interpreter — the parity oracle.

Defines the *semantics* the TPU engine must reproduce, playing the role of
the reference's pull-based step executor ([E] core/.../sql/executor/ —
OSelectExecutionPlanner step chains, OMatchExecutionPlanner +
MatchEdgeTraverser per-record DFS, Depth/BreadthFirstTraverseStep;
SURVEY.md §3.2–§3.3). Deliberately simple and record-at-a-time: this is the
slow path OrientDB actually runs, and the reference the batched TPU
engine's answers are compared against.

MATCH semantics implemented here (the golden-corpus spec, mirroring
[E] OMatchStatementExecutionNewTest):
- one result row per distinct alias-binding combination, duplicates kept
  unless DISTINCT;
- aliases shared across comma-separated arms join; disjoint sub-patterns
  produce cartesian products;
- `while`/`maxDepth` arms iterate breadth of a DFS with a per-expansion
  visited set; depth 0 (the origin) is itself a candidate of the target
  alias — OrientDB's depth-0-includes-start behavior;
- the target `where` filters *emission* while `while` gates *traversal*;
- `optional:true` targets bind null when unmatched; NOT arms reject any
  binding for which the negated pattern is satisfiable;
- RETURN $matches / $paths give one row per match (named / all aliases);
  $elements / $pathElements flatten to one row per bound record.
"""

from __future__ import annotations

import itertools
from collections import deque
from typing import Deque
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

from orientdb_tpu.exec.eval import (
    EvalContext,
    EvalError,
    AGGREGATE_FUNCTIONS,
    as_list,
    compare,
    contains_aggregate,
    evaluate,
    get_prop,
    nav_edges,
    nav_vertices,
    resolve_links,
    truthy,
)
from orientdb_tpu.exec.result import Result, ResultSet
from orientdb_tpu.models.record import Document, Edge, Vertex, Direction
from orientdb_tpu.models.rid import RID
from orientdb_tpu.sql import ast as A


class ExecutionError(Exception):
    pass


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def expr_name(expr: A.Expression, index: int) -> str:
    """Deterministic column name for an unaliased projection ([E] the
    reference uses the expression's source text)."""
    if isinstance(expr, A.Identifier):
        return expr.name
    if isinstance(expr, A.FieldAccess):
        return expr.name
    if isinstance(expr, A.FunctionCall):
        return f"{expr.name}"
    if isinstance(expr, A.MethodCall):
        return expr.name
    if isinstance(expr, A.ContextVar):
        return f"${expr.name}"
    return f"_col{index}"


def resolve_target_rows(db, target: Optional[A.Target], ctx: EvalContext) -> Iterator:
    """FROM-target resolution → iterator of Documents / Results / values."""
    if target is None:
        yield Result(props={})
        return
    if isinstance(target, A.ClassTarget):
        cls = db.schema.get_class(target.name)
        if cls is None:
            raise ExecutionError(f"class '{target.name}' not found")
        yield from db.browse_class(cls.name, polymorphic=target.polymorphic)
        return
    if isinstance(target, A.ClusterTarget):
        if isinstance(target.name_or_id, int):
            yield from db.browse_cluster(target.name_or_id)
            return
        # cluster names are "<classname>" (first cluster) or "<classname>_N"
        name = str(target.name_or_id)
        cls = db.schema.get_class(name)
        if cls is None or not cls.cluster_ids:
            raise ExecutionError(f"cluster '{name}' not found")
        yield from db.browse_cluster(cls.cluster_ids[0])
        return
    if isinstance(target, A.RidTarget):
        for r in target.rids:
            doc = db.load(RID(r.cluster, r.position))
            if doc is not None:
                yield doc
        return
    if isinstance(target, A.IndexTarget):
        idx = db.indexes.get_index(target.name)
        if idx is None:
            raise ExecutionError(f"index '{target.name}' not found")
        keys = idx.keys()
        for k in keys:
            for rid in sorted(idx.get(k)):
                yield Result(props={"key": k, "rid": rid})
        return
    if isinstance(target, A.SubQueryTarget):
        for r in execute_statement(db, target.query, ctx.params, parent_ctx=ctx):
            yield r.element if r.is_element else r
        return
    if isinstance(target, A.ExpressionTarget):
        val = evaluate(ctx, target.expr)
        for item in as_list(resolve_links(ctx, val)):
            if item is not None:
                yield item
        return
    raise ExecutionError(f"unsupported target {target!r}")


def _row_ctx(db, row, params, parent_ctx) -> EvalContext:
    return EvalContext(db, current=row, params=params, parent=parent_ctx)


# ---------------------------------------------------------------------------
# index-driven candidate pruning ([E] the planner's index-vs-scan choice,
# SURVEY.md §3.2: "OSelectExecutionPlanner … index vs scan choice")
# ---------------------------------------------------------------------------


def _const_operand(expr: A.Expression, ctx: EvalContext):
    """(ok, value) for expressions that cannot reference the current row —
    literals, parameters, and their negations. Anything else is not a
    constant for index-probe purposes."""
    if isinstance(expr, A.Literal):
        return True, expr.value
    if isinstance(expr, A.Parameter):
        key = expr.name if expr.name is not None else expr.index
        if key in ctx.params:
            return True, ctx.params[key]
        return False, None
    if isinstance(expr, A.Unary) and expr.op in ("-", "+"):
        ok, v = _const_operand(expr.expr, ctx)
        if ok and isinstance(v, (int, float)) and not isinstance(v, bool):
            return True, (-v if expr.op == "-" else v)
        return False, None
    return False, None


_FLIP_OP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}


def _spatial_probe(db, class_name, fn, rhs, op, ctx):
    """Candidate RIDs for a ``distance(latF, lngF, <x>, <y>[, unit]) < r``
    conjunct via a SPATIAL grid index ([E] the lucene-spatial
    within-distance query; SURVEY.md §2 "Lucene"). Returns a SUPERSET —
    the caller still row-filters with the full WHERE — or None when the
    shape/index doesn't apply."""
    if (
        fn.name.lower() != "distance"
        or len(fn.args) < 4
        or op not in ("<", "<=")
        or db._indexes is None
    ):
        return None
    a0, a1 = fn.args[0], fn.args[1]
    if not (isinstance(a0, A.Identifier) and isinstance(a1, A.Identifier)):
        return None
    ok_r, r = _const_operand(rhs, ctx)
    ok_lat, latv = _const_operand(fn.args[2], ctx)
    ok_lng, lngv = _const_operand(fn.args[3], ctx)

    def num(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    if not (ok_r and ok_lat and ok_lng and num(r) and num(latv) and num(lngv)):
        return None
    if len(fn.args) > 4:
        from orientdb_tpu.utils.geo import MILE_UNITS, MILES_PER_KM

        u = fn.args[4]
        if not isinstance(u, A.Literal):
            return None
        unit = str(u.value).lower()
        if unit in MILE_UNITS:
            r = float(r) / MILES_PER_KM
        elif unit != "km":
            return None
    from orientdb_tpu.models.indexes import SpatialIndex

    cls = db.schema.get_class(class_name)
    if cls is None:
        return None
    for idx in db._indexes.all():
        if (
            isinstance(idx, SpatialIndex)
            and idx.fields == [a0.name, a1.name]
            and cls.is_subclass_of(idx.class_name)
        ):
            return idx.near(float(latv), float(lngv), float(r))
    return None


def index_lookup_rids(db, class_name: str, where: A.Expression, ctx: EvalContext):
    """RIDs satisfying ONE indexable conjunct of ``where``, or None when no
    single-field index applies. The caller still evaluates the FULL WHERE
    per row — the index is a pruning prefetch, so using it can only shrink
    the scanned set, never change results."""
    if isinstance(where, A.Binary) and where.op == "AND":
        left = index_lookup_rids(db, class_name, where.left, ctx)
        if left is not None:
            return left
        return index_lookup_rids(db, class_name, where.right, ctx)
    if db._indexes is None:
        return None

    def probe(lhs, rhs, op):
        if not isinstance(lhs, A.Identifier):
            return None
        idx = db._indexes.best_for(class_name, lhs.name)
        if idx is None:
            return None
        ok, v = _const_operand(rhs, ctx)
        if not ok or v is None:
            return None
        try:
            if op == "=":
                return set(idx.get(v))
            if not idx.range_capable:
                return None
            lo, hi = (v, None) if op in (">", ">=") else (None, v)
            out = set()
            for _k, rids in idx.range(
                lo=lo,
                hi=hi,
                lo_inclusive=(op != ">"),
                hi_inclusive=(op != "<"),
            ):
                out |= rids
            return out
        except TypeError:
            return None  # mixed-type keys: leave it to the row filter

    if isinstance(where, A.Binary) and where.op in _FLIP_OP:
        if isinstance(where.left, A.FunctionCall):
            return _spatial_probe(
                db, class_name, where.left, where.right, where.op, ctx
            )
        if isinstance(where.right, A.FunctionCall):
            return _spatial_probe(
                db,
                class_name,
                where.right,
                where.left,
                _FLIP_OP[where.op],
                ctx,
            )
        return probe(where.left, where.right, where.op) if isinstance(
            where.left, A.Identifier
        ) else probe(where.right, where.left, _FLIP_OP[where.op])
    if isinstance(where, A.Between) and isinstance(where.expr, A.Identifier):
        idx = db._indexes.best_for(class_name, where.expr.name)
        if idx is None or not idx.range_capable:
            return None
        ok_lo, lo = _const_operand(where.low, ctx)
        ok_hi, hi = _const_operand(where.high, ctx)
        if not (ok_lo and ok_hi) or lo is None or hi is None:
            return None
        try:
            out = set()
            for _k, rids in idx.range(lo=lo, hi=hi):
                out |= rids
            return out
        except TypeError:
            return None
    return None


def indexed_class_docs(db, class_name: str, polymorphic: bool, where, ctx):
    """Documents of ``class_name`` pruned through an index, or None → the
    caller scans. Disabled under an active transaction (indexes don't see
    the tx overlay)."""
    if db.tx is not None or where is None:
        return None
    cls = db.schema.get_class(class_name)
    if cls is None:
        return None
    rids = index_lookup_rids(db, cls.name, where, ctx)
    if rids is None:
        return None
    docs = []
    for rid in sorted(rids):
        d = db._load_raw(rid)
        if d is None:
            continue
        dcls = db.schema.get_class(d.class_name)
        if dcls is None or not dcls.is_subclass_of(cls.name):
            continue  # the index may span sibling subclasses
        if not polymorphic and d.class_name != cls.name:
            continue
        docs.append(d)
    return docs


def _skip_limit(rows: List, skip_expr, limit_expr, ctx) -> List:
    skip = int(evaluate(ctx, skip_expr)) if skip_expr is not None else 0
    limit = int(evaluate(ctx, limit_expr)) if limit_expr is not None else None
    if skip:
        rows = rows[skip:]
    if limit is not None and limit >= 0:
        rows = rows[:limit]
    return rows


def _sort_key_fn(vals: List):
    """Total order over heterogeneous projection values: None sorts first,
    then by (type-rank, value)."""

    def rank(v):
        if v is None:
            return (0, 0)
        if isinstance(v, bool):
            return (1, v)
        if isinstance(v, (int, float)):
            return (2, v)
        if isinstance(v, str):
            return (3, v)
        if isinstance(v, RID):
            return (4, (v.cluster, v.position))
        if isinstance(v, Document):
            return (4, (v.rid.cluster, v.rid.position))
        return (5, repr(v))

    return tuple(rank(v) for v in vals)


def _order_rows(
    rows: List[Result], order_by, db, params, parent_ctx, sources=None
) -> List[Result]:
    """Sort rows; an ORDER BY key may name a projection alias or (failing
    that) a field of the *source* record, as in the reference's executor."""
    if not order_by:
        return rows
    keyed = []
    for i, r in enumerate(rows):
        ctx = _row_ctx(db, r, params, parent_ctx)
        vals = []
        for item in order_by:
            v = evaluate(ctx, item.expr)
            if v is None and sources is not None and sources[i] is not None:
                sctx = _row_ctx(db, sources[i], params, parent_ctx)
                v = evaluate(sctx, item.expr)
            vals.append(v)
        keyed.append((vals, r))
    # stable multi-key sort: apply keys right-to-left
    for i in range(len(order_by) - 1, -1, -1):
        keyed.sort(
            key=lambda kv: _sort_key_fn([kv[0][i]]),
            reverse=not order_by[i].ascending,
        )
    return [r for _, r in keyed]


def _canonical(v) -> object:
    """Hashable canonical form for DISTINCT / GROUP BY keys."""
    if isinstance(v, Document):
        return ("rec", str(v.rid))
    if isinstance(v, RID):
        return ("rid", str(v))
    if isinstance(v, Result):
        return ("row", tuple(sorted((k, _canonical(v.get_property(k))) for k in v.property_names())))
    if isinstance(v, (list, tuple)):
        return ("list", tuple(_canonical(x) for x in v))
    if isinstance(v, set):
        return ("set", tuple(sorted(map(repr, v))))
    if isinstance(v, dict):
        return ("map", tuple(sorted((k, _canonical(x)) for k, x in v.items())))
    return v


# ---------------------------------------------------------------------------
# SELECT
# ---------------------------------------------------------------------------


class _Aggregator:
    __slots__ = ("fn", "count", "acc", "seen")

    def __init__(self, fn: str) -> None:
        self.fn = fn
        self.count = 0
        self.acc = None
        self.seen = False

    def add(self, value) -> None:
        if self.fn == "count":
            if value is not None:
                self.count += 1
            return
        if value is None:
            return
        if not self.seen:
            self.acc = value
            self.seen = True
            self.count = 1
            return
        self.count += 1
        if self.fn == "sum" or self.fn == "avg":
            self.acc = self.acc + value
        elif self.fn == "min":
            c = compare(value, self.acc)
            if c is not None and c < 0:
                self.acc = value
        elif self.fn == "max":
            c = compare(value, self.acc)
            if c is not None and c > 0:
                self.acc = value

    def result(self):
        if self.fn == "count":
            return self.count
        if not self.seen:
            return None
        if self.fn == "avg":
            return self.acc / self.count
        return self.acc


def _eval_with_aggregates(ctx: EvalContext, expr: A.Expression, aggs: Dict[int, _Aggregator]):
    """Evaluate a projection expression replacing aggregate calls with their
    accumulated results (aggs keyed by id of the FunctionCall node)."""
    if isinstance(expr, A.FunctionCall) and expr.name in AGGREGATE_FUNCTIONS:
        return aggs[id(expr)].result()
    if isinstance(expr, A.Binary):
        lv = _eval_with_aggregates(ctx, expr.left, aggs)
        rv = _eval_with_aggregates(ctx, expr.right, aggs)
        return evaluate(ctx, A.Binary(expr.op, A.Literal(lv), A.Literal(rv)))
    if isinstance(expr, A.Unary):
        v = _eval_with_aggregates(ctx, expr.expr, aggs)
        return evaluate(ctx, A.Unary(expr.op, A.Literal(v)))
    return evaluate(ctx, expr)


def _collect_aggregates(expr: A.Expression, out: List[A.FunctionCall]) -> None:
    if isinstance(expr, A.FunctionCall):
        if expr.name in AGGREGATE_FUNCTIONS:
            out.append(expr)
            return
        for a in expr.args:
            _collect_aggregates(a, out)
    elif isinstance(expr, A.Binary):
        _collect_aggregates(expr.left, out)
        _collect_aggregates(expr.right, out)
    elif isinstance(expr, A.Unary):
        _collect_aggregates(expr.expr, out)
    elif isinstance(expr, A.MethodCall):
        _collect_aggregates(expr.base, out)
    elif isinstance(expr, A.FieldAccess):
        _collect_aggregates(expr.base, out)


def execute_select(db, stmt: A.SelectStatement, params, parent_ctx=None) -> List[Result]:
    base_ctx = EvalContext(db, params=params, parent=parent_ctx)
    source = None
    if isinstance(stmt.target, A.ClassTarget) and db.schema.exists_class(
        stmt.target.name
    ):
        pruned = indexed_class_docs(
            db, stmt.target.name, stmt.target.polymorphic, stmt.where, base_ctx
        )
        if pruned is not None:
            source = iter(pruned)
    depths: Optional[Iterator] = None
    if isinstance(stmt.target, A.SubQueryTarget) and isinstance(
        stmt.target.query, A.TraverseStatement
    ):
        # [E] the results of a TRAVERSE carry their depth, and a SELECT
        # over one may project, filter and group by $depth
        found = execute_traverse(db, stmt.target.query, params, parent_ctx=base_ctx)
        source = iter([r.element for r in found])
        depths = iter([r.get_metadata("$depth") for r in found])
    if source is None:
        source = resolve_target_rows(db, stmt.target, base_ctx)

    # per-row context with LET variables
    def contexts() -> Iterator[Tuple[EvalContext, object]]:
        for row in source:
            ctx = _row_ctx(db, row, params, parent_ctx)
            if depths is not None:
                ctx.variables["depth"] = next(depths)
            for let in stmt.lets:
                if isinstance(let.value, A.Statement):
                    sub = execute_statement(db, let.value, params, parent_ctx=ctx)
                    ctx.variables[let.name] = [
                        r.element if r.is_element else r for r in sub
                    ]
                else:
                    ctx.variables[let.name] = evaluate(ctx, let.value)
            yield ctx, row

    filtered: List[Tuple[EvalContext, object]] = []
    for ctx, row in contexts():
        if stmt.where is None or truthy(evaluate(ctx, stmt.where)):
            filtered.append((ctx, row))

    aggregate_mode = bool(stmt.group_by) or any(
        contains_aggregate(p.expr) for p in stmt.projections
    )

    rows: List[Result]
    sources: Optional[List[object]]
    if aggregate_mode:
        rows = _aggregate_rows(db, stmt, filtered, params, parent_ctx)
        sources = None
    else:
        rows = _project_rows(db, stmt.projections, filtered)
        sources = (
            [row for _, row in filtered] if len(rows) == len(filtered) else None
        )

    if stmt.distinct:
        seen = set()
        deduped, dd_sources = [], []
        for i, r in enumerate(rows):
            key = _canonical(r)
            if key not in seen:
                seen.add(key)
                deduped.append(r)
                if sources is not None:
                    dd_sources.append(sources[i])
        rows = deduped
        sources = dd_sources if sources is not None else None

    for field in stmt.unwind:
        unwound: List[Result] = []
        unwound_sources: List[object] = []
        for i, r in enumerate(rows):
            src = sources[i] if sources is not None else None
            vals = as_list(r.get_property(field))
            expanded = vals if vals else [None]
            for v in expanded:
                rr = Result(props={k: r.get_property(k) for k in r.property_names()})
                rr.set_property(field, v)
                unwound.append(rr)
                unwound_sources.append(src)
        rows = unwound
        sources = unwound_sources

    rows = _order_rows(rows, stmt.order_by, db, params, parent_ctx, sources)
    rows = _skip_limit(rows, stmt.skip, stmt.limit, base_ctx)
    return rows


def _project_rows(db, projections, filtered) -> List[Result]:
    if not projections:
        return [
            (row if isinstance(row, Result) else Result(element=row))
            for _, row in filtered
        ]
    # single expand(...) projection flattens to element rows
    if len(projections) == 1 and isinstance(projections[0].expr, A.FunctionCall) and (
        projections[0].expr.name == "expand"
    ):
        inner = projections[0].expr.args[0]
        out = []
        for ctx, _row in filtered:
            val = evaluate(ctx, inner)
            for item in as_list(resolve_links(ctx, val)):
                if isinstance(item, Document):
                    out.append(Result(element=item))
                elif isinstance(item, Result):
                    out.append(item)
                elif item is not None:
                    out.append(Result(props={"value": item}))
        return out
    out = []
    for ctx, row in filtered:
        props: Dict[str, object] = {}
        for i, p in enumerate(projections):
            if isinstance(p.expr, A.Star):
                if isinstance(row, Document):
                    props.update(row.to_dict(include_meta=False))
                elif isinstance(row, Result):
                    for k in row.property_names():
                        props[k] = row.get_property(k)
                continue
            name = p.alias or expr_name(p.expr, i)
            props[name] = evaluate(ctx, p.expr)
        out.append(Result(props=props))
    return out


def _aggregate_rows(db, stmt, filtered, params, parent_ctx) -> List[Result]:
    # groups: key → (first_ctx, aggregators per projection)
    groups: Dict[object, Tuple[EvalContext, Dict[int, _Aggregator]]] = {}
    order: List[object] = []
    agg_nodes: List[A.FunctionCall] = []
    for p in stmt.projections:
        _collect_aggregates(p.expr, agg_nodes)

    for ctx, _row in filtered:
        key = tuple(_canonical(evaluate(ctx, g)) for g in stmt.group_by)
        if key not in groups:
            groups[key] = (ctx, {id(n): _Aggregator(n.name) for n in agg_nodes})
            order.append(key)
        _, aggs = groups[key]
        for node in agg_nodes:
            agg = aggs[id(node)]
            if len(node.args) == 1 and isinstance(node.args[0], A.Star):
                agg.count += 1
            else:
                agg.add(evaluate(ctx, node.args[0]) if node.args else None)

    out = []
    for key in order:
        ctx, aggs = groups[key]
        props = {}
        for i, p in enumerate(stmt.projections):
            name = p.alias or expr_name(p.expr, i)
            props[name] = _eval_with_aggregates(ctx, p.expr, aggs)
        out.append(Result(props=props))
    if not out and not stmt.group_by and agg_nodes:
        # aggregate over empty input still yields one row (count(*) = 0)
        props = {}
        empty_aggs = {id(n): _Aggregator(n.name) for n in agg_nodes}
        ctx = EvalContext(db, params=params, parent=parent_ctx)
        for i, p in enumerate(stmt.projections):
            name = p.alias or expr_name(p.expr, i)
            props[name] = _eval_with_aggregates(ctx, p.expr, empty_aggs)
        out.append(Result(props=props))
    return out


# ---------------------------------------------------------------------------
# MATCH
# ---------------------------------------------------------------------------


class PatternNode:
    """[E] PatternNode: one alias with its merged constraints."""

    __slots__ = ("alias", "filters", "anonymous", "optional", "is_edge_alias")

    def __init__(self, alias: str, anonymous: bool) -> None:
        self.alias = alias
        self.anonymous = anonymous
        self.filters: List[A.MatchFilter] = []
        self.optional = False
        self.is_edge_alias = False


class PatternEdge:
    """[E] PatternEdge: one path item connecting two aliases."""

    __slots__ = ("from_alias", "to_alias", "item", "negated_arm")

    def __init__(self, from_alias: str, to_alias: str, item: A.MatchPathItem, negated: bool):
        self.from_alias = from_alias
        self.to_alias = to_alias
        self.item = item
        self.negated_arm = negated


class Pattern:
    """[E] Pattern: nodes + edges, built from the MATCH AST."""

    def __init__(self) -> None:
        self.nodes: Dict[str, PatternNode] = {}
        self.edges: List[PatternEdge] = []
        self._anon = itertools.count()

    def node(self, flt: Optional[A.MatchFilter]) -> PatternNode:
        alias = flt.alias if flt is not None and flt.alias else None
        anonymous = alias is None
        if alias is None:
            alias = f"$anon{next(self._anon)}"
        n = self.nodes.get(alias)
        if n is None:
            n = self.nodes[alias] = PatternNode(alias, anonymous)
        if flt is not None:
            n.filters.append(flt)
            if flt.optional:
                n.optional = True
        return n


def build_pattern(stmt: A.MatchStatement) -> Tuple[Pattern, List[A.MatchPath]]:
    pattern = Pattern()
    not_paths: List[A.MatchPath] = []
    for path in stmt.paths:
        if path.negated:
            not_paths.append(path)
            # ensure shared aliases exist as nodes (without adding filters)
            continue
        prev = pattern.node(path.first)
        for item in path.items:
            tgt = pattern.node(item.target)
            if item.method and item.method.lower() in ("oute", "ine", "bothe") and (
                item.edge_filter is None
            ):
                # bare .outE(){as:e}: target alias binds the EDGE
                tgt.is_edge_alias = True
            pattern.edges.append(PatternEdge(prev.alias, tgt.alias, item, False))
            if item.edge_filter is not None and item.edge_filter.alias:
                en = pattern.node(A.MatchFilter(alias=item.edge_filter.alias))
                en.is_edge_alias = True
            prev = tgt
    return pattern, not_paths


_REVERSE_DIR = {"out": "in", "in": "out", "both": "both"}


def _expr_uses_bindings(expr, pattern_nodes: Dict[str, "PatternNode"]) -> bool:
    """True if a where-expression references other aliases ($matched,
    $currentMatch, or an alias name used as an identifier)."""
    if isinstance(expr, A.ContextVar):
        return expr.name in ("matched", "currentMatch")
    if isinstance(expr, A.Identifier):
        return expr.name in pattern_nodes
    if isinstance(expr, A.Binary):
        return _expr_uses_bindings(expr.left, pattern_nodes) or _expr_uses_bindings(
            expr.right, pattern_nodes
        )
    if isinstance(expr, A.Unary):
        return _expr_uses_bindings(expr.expr, pattern_nodes)
    if isinstance(expr, A.Between):
        return any(
            _expr_uses_bindings(e, pattern_nodes)
            for e in (expr.expr, expr.low, expr.high)
        )
    if isinstance(expr, (A.IsNull, A.IsDefined)):
        return _expr_uses_bindings(expr.expr, pattern_nodes)
    if isinstance(expr, A.FieldAccess):
        return _expr_uses_bindings(expr.base, pattern_nodes)
    if isinstance(expr, A.IndexAccess):
        return _expr_uses_bindings(expr.base, pattern_nodes) or _expr_uses_bindings(
            expr.index, pattern_nodes
        )
    if isinstance(expr, A.MethodCall):
        return _expr_uses_bindings(expr.base, pattern_nodes) or any(
            _expr_uses_bindings(a, pattern_nodes) for a in expr.args
        )
    if isinstance(expr, A.FunctionCall):
        return any(_expr_uses_bindings(a, pattern_nodes) for a in expr.args)
    if isinstance(expr, A.ListExpr):
        return any(_expr_uses_bindings(a, pattern_nodes) for a in expr.items)
    return False


class MatchInterpreter:
    """Per-record DFS, the [E] MatchEdgeTraverser analog."""

    def __init__(self, db, stmt: A.MatchStatement, params, parent_ctx=None) -> None:
        self.db = db
        self.stmt = stmt
        self.params = params
        self.parent_ctx = parent_ctx
        self.pattern, self.not_paths = build_pattern(stmt)
        # alias → binding-independent candidate list, computed once per query
        self._cand_cache: Dict[str, List[Document]] = {}

    # -- candidate sets ----------------------------------------------------

    def node_candidates(self, node: PatternNode) -> List[Document]:
        """Binding-independent candidate set for an alias, cached per query.
        Where-clauses that reference other bindings ($matched / alias names)
        are NOT applied here — callers re-check with
        `check_node(node, cand, bindings)` once bindings exist."""
        cached = self._cand_cache.get(node.alias)
        if cached is not None:
            return cached
        rid = None
        class_names = []
        for f in node.filters:
            if f.rid is not None:
                rid = RID(f.rid.cluster, f.rid.position)
            if f.class_name:
                class_names.append(f.class_name)
        if rid is not None:
            doc = self.db.load(rid)
            docs = [doc] if doc is not None else []
        elif class_names:
            # index-seeded when some filter's WHERE has an indexable
            # conjunct ([E] MatchPrefetchStep's index use, SURVEY.md §3.3);
            # check_node below still applies every filter in full
            docs = None
            if self.db.tx is None:
                ctx = EvalContext(self.db, params=self.params)
                for f in node.filters:
                    if f.where is None:
                        continue
                    seeded = indexed_class_docs(
                        self.db, class_names[0], True, f.where, ctx
                    )
                    if seeded is not None:
                        docs = [
                            d
                            for d in seeded
                            if all(self._doc_is_class(d, c) for c in class_names[1:])
                        ]
                        break
            if docs is None:
                # most selective: intersect by scanning the first and
                # checking all
                docs = [
                    d
                    for d in self.db.browse_class(class_names[0])
                    if all(self._doc_is_class(d, c) for c in class_names[1:])
                ]
        elif node.is_edge_alias:
            docs = list(self.db.browse_class("E"))
        else:
            docs = list(self.db.browse_class("V"))
        out = [d for d in docs if self.check_node(node, d, {}, prefilter=True)]
        self._cand_cache[node.alias] = out
        return out

    def estimate(self, node: PatternNode) -> int:
        """Candidate-set size estimate for greedy root/expansion ordering
        ([E] OMatchExecutionPlanner's index-aware estimates): class count
        scaled by a WHERE-selectivity prior — an equality on a
        unique-indexed field is a point lookup; plain equalities and
        ranges get blunt priors. Without this, a `where:(id = ?)` root is
        costed like a full class scan and the planner roots at the wrong
        alias (e.g. walking every Post's reply tree backwards instead of
        starting from the one matched Message)."""
        for f in node.filters:
            if f.rid is not None:
                return 1
        base = None
        cname = None
        for f in node.filters:
            if f.class_name:
                cls = self.db.schema.get_class(f.class_name)
                if cls is not None:
                    base = self.db.count_class(cls.name)
                    cname = cls.name
                    break
        if base is None:
            base = self.db.count_class("E" if node.is_edge_alias else "V") + 10**6
        sel = 1.0
        for f in node.filters:
            if f.where is not None:
                sel = min(sel, self._where_selectivity(cname, f.where))
        return max(1, int(base * sel))

    def _where_selectivity(self, cname: Optional[str], w) -> float:
        if isinstance(w, A.Binary):
            if w.op == "AND":
                return max(
                    1e-6,
                    self._where_selectivity(cname, w.left)
                    * self._where_selectivity(cname, w.right),
                )
            if w.op == "OR":
                return min(
                    1.0,
                    self._where_selectivity(cname, w.left)
                    + self._where_selectivity(cname, w.right),
                )
            if w.op == "=":
                fld = None
                if isinstance(w.left, A.Identifier):
                    fld = w.left.name
                elif isinstance(w.right, A.Identifier):
                    fld = w.right.name
                if fld and cname and self.db._indexes is not None:
                    idx = self.db._indexes.best_for(cname, fld)
                    if idx is not None and idx.unique:
                        return 1e-9  # point lookup
                return 0.01 if fld else 1.0
            if w.op in ("<", "<=", ">", ">="):
                return 0.3
            if w.op == "IN":
                return 0.05
        if isinstance(w, A.Between):
            return 0.2
        return 1.0

    def _doc_is_class(self, doc: Document, class_name: str) -> bool:
        cls = self.db.schema.get_class(doc.class_name)
        return cls is not None and cls.is_subclass_of(class_name)

    def check_node(
        self,
        node: PatternNode,
        doc: Document,
        bindings: Dict[str, object],
        prefilter: bool = False,
    ) -> bool:
        """With ``prefilter=True``, binding-dependent where-clauses are
        skipped (evaluating them with empty bindings would wrongly drop
        every candidate)."""
        for f in node.filters:
            if f.class_name and not self._doc_is_class(doc, f.class_name):
                return False
            if f.rid is not None and doc.rid != RID(f.rid.cluster, f.rid.position):
                return False
            if f.where is not None:
                if prefilter and _expr_uses_bindings(f.where, self.pattern.nodes):
                    continue
                ctx = self._where_ctx(doc, bindings)
                if not truthy(evaluate(ctx, f.where)):
                    return False
        return True

    def _where_ctx(self, doc, bindings, extra=None) -> EvalContext:
        variables = dict(bindings)
        variables["matched"] = {
            k: v for k, v in bindings.items() if not k.startswith("$anon")
        }
        variables["currentMatch"] = doc
        if extra:
            variables.update(extra)
        return EvalContext(
            self.db,
            current=doc,
            params=self.params,
            variables=variables,
            parent=self.parent_ctx,
        )

    # -- expansion ---------------------------------------------------------

    def expand(
        self,
        start: Document,
        item: A.MatchPathItem,
        bindings: Dict[str, object],
        reverse: bool = False,
    ) -> Iterator[Tuple[Document, Optional[Edge], int, List[Document]]]:
        """Yield (candidate, last_edge, depth, path) expanding one pattern
        edge from ``start``. ``reverse`` walks the arrow backwards (target
        alias was already bound)."""
        direction = item.direction
        method = (item.method or "").lower()
        if method in ("outv", "inv", "bothv"):
            # from a bound edge to its endpoint(s)
            if isinstance(start, Edge) and not reverse:
                if method == "outv":
                    yield start.from_vertex(), None, 1, [start]
                elif method == "inv":
                    yield start.to_vertex(), None, 1, [start]
                else:
                    yield start.from_vertex(), None, 1, [start]
                    yield start.to_vertex(), None, 1, [start]
            elif reverse and isinstance(start, Vertex):
                # reverse of outV: edges whose out is this vertex
                want = "out" if method == "outv" else "in"
                for e in start.edges(Direction.BOTH):
                    end = e.out_rid if want == "out" else e.in_rid
                    if end == start.rid:
                        yield e, None, 1, [start]
            return
        if reverse:
            direction = _REVERSE_DIR[direction]
        edge_dir = {"out": Direction.OUT, "in": Direction.IN, "both": Direction.BOTH}[
            direction
        ]
        edge_classes = item.edge_classes or (None,)
        binds_edge = method in ("oute", "ine", "bothe") and item.edge_filter is None
        while_cond = item.target.while_cond
        max_depth = item.target.max_depth
        if while_cond is None and max_depth is None:
            # single hop
            for ec in edge_classes:
                for edge in start.edges(edge_dir, ec) if isinstance(start, Vertex) else []:
                    if not self._edge_ok(edge, item, bindings):
                        continue
                    if binds_edge and not reverse:
                        yield edge, edge, 1, [start]
                        continue
                    other = self._other_end(edge, start, direction)
                    if other is not None:
                        yield other, edge, 1, [start, other]
            return
        # variable-depth: BFS with visited-at-enqueue; emit every reached
        # node including the origin at depth 0. BFS (not the reference's
        # per-record DFS) makes $depth the MINIMUM depth and the emitted
        # set independent of traversal order — DFS can reach a node first
        # through a long path and then refuse to expand it under
        # WHILE($depth<N), making results order-dependent on cyclic graphs.
        visited: Set[RID] = {start.rid}
        yield start, None, 0, [start]
        queue: Deque[Tuple[Document, int, List[Document]]] = deque([(start, 0, [start])])
        while queue:
            node, depth, path = queue.popleft()
            # gate traversal: while-condition at the current node
            if not self._while_ok(node, depth, while_cond, max_depth, bindings):
                continue
            for ec in edge_classes:
                if not isinstance(node, Vertex):
                    continue
                for edge in node.edges(edge_dir, ec):
                    if not self._edge_ok(edge, item, bindings):
                        continue
                    other = self._other_end(edge, node, direction)
                    if other is None or other.rid in visited:
                        continue
                    visited.add(other.rid)
                    npath = path + [other]
                    yield other, edge, depth + 1, npath
                    queue.append((other, depth + 1, npath))

    def _while_ok(self, node, depth, while_cond, max_depth, bindings) -> bool:
        if max_depth is not None and depth >= max_depth:
            return False
        if while_cond is not None:
            ctx = self._where_ctx(node, bindings, extra={"depth": depth})
            if not truthy(evaluate(ctx, while_cond)):
                return False
        elif max_depth is None:
            return False
        return True

    def _edge_ok(self, edge: Edge, item: A.MatchPathItem, bindings) -> bool:
        f = item.edge_filter
        if f is None:
            return True
        if f.class_name and not self._doc_is_class(edge, f.class_name):
            return False
        if f.where is not None:
            ctx = self._where_ctx(edge, bindings)
            if not truthy(evaluate(ctx, f.where)):
                return False
        return True

    def _other_end(self, edge: Edge, from_doc: Document, direction: str):
        if direction == "out":
            return self.db.load(edge.in_rid)
        if direction == "in":
            return self.db.load(edge.out_rid)
        other = edge.in_rid if edge.out_rid == from_doc.rid else edge.out_rid
        return self.db.load(other)

    # -- the solver --------------------------------------------------------

    def solve(self) -> Iterator[Dict[str, object]]:
        required = [e for e in self.pattern.edges if not self._edge_is_optional(e)]
        optionals = [e for e in self.pattern.edges if self._edge_is_optional(e)]
        isolated = self.enumerable_isolated(required, optionals)
        for bindings in self._solve_required(required, isolated, {}):
            for full in self._solve_optionals(optionals, bindings):
                if self._not_arms_ok(full):
                    yield full

    def enumerable_isolated(
        self, required: List[PatternEdge], optionals: List[PatternEdge]
    ) -> List[PatternNode]:
        """Nodes needing up-front candidate enumeration: not touched by any
        REQUIRED edge (isolated nodes, and the from-side of optional-only
        arms), but excluding
        - optional nodes (they bind null when unmatched),
        - filterless nodes created only for NOT-arm sharing,
        - aliases bound as a side effect of some arm's edge braces
          ({as:kn} between the dashes) — they bind when their arm runs,
        - targets of optional arms: enumerating a filtered target of an
          arm-optional probe would turn the left join into a cross product
          (the probe must *bind* it, nulling on no-match).

        This is the shared admission rule — the TPU planner replays it, so
        any edit here is an engine-parity change."""
        arm_bound = {
            e.item.edge_filter.alias
            for e in self.pattern.edges
            if e.item.edge_filter is not None and e.item.edge_filter.alias
        }
        opt_targets = {e.to_alias for e in optionals}
        return [
            n
            for n in self.pattern.nodes.values()
            if not any(
                e.from_alias == n.alias or e.to_alias == n.alias for e in required
            )
            and not n.optional
            and n.filters
            and n.alias not in arm_bound
            and n.alias not in opt_targets
        ]

    def _edge_is_optional(self, e: PatternEdge) -> bool:
        # node-level (reference semantics: an optional target binds null
        # when unmatched) or arm-level — `optional:true` inside the edge
        # braces marks just this arm as a left join, so a cyclic arm
        # between two required aliases can probe edge existence (the IS7
        # "knows" flag) without making either endpoint optional.
        return self.pattern.nodes[e.to_alias].optional or self._arm_optional(e)

    @staticmethod
    def _arm_optional(e: PatternEdge) -> bool:
        f = e.item.edge_filter
        return f is not None and f.optional

    def _solve_required(
        self,
        edges: List[PatternEdge],
        isolated: List[PatternNode],
        bindings: Dict[str, object],
    ) -> Iterator[Dict[str, object]]:
        if not edges:
            if not isolated:
                yield bindings
                return
            node, rest = isolated[0], isolated[1:]
            if node.alias in bindings:
                yield from self._solve_required(edges, rest, bindings)
                return
            for cand in self.node_candidates(node):
                if not self.check_node(node, cand, bindings):
                    continue
                nb = dict(bindings)
                nb[node.alias] = cand
                yield from self._solve_required(edges, rest, nb)
            return
        # pick the next edge: prefer both endpoints bound, then one bound;
        # otherwise start a new component at the smallest-estimate alias
        # ([E] OMatchExecutionPlanner's greedy smallest-first ordering)
        def edge_rank(e: PatternEdge):
            fb = e.from_alias in bindings
            tb = e.to_alias in bindings
            if fb and tb:
                return 0
            if fb:
                return 1
            if tb:
                return 2
            return 3

        edges_sorted = sorted(range(len(edges)), key=lambda i: edge_rank(edges[i]))
        best = edges_sorted[0]
        e = edges[best]
        rest = edges[:best] + edges[best + 1 :]
        fb = e.from_alias in bindings
        tb = e.to_alias in bindings
        if not fb and not tb:
            # new component: enumerate candidates for the cheaper endpoint
            # ([E] OMatchExecutionPlanner's smallest-first root choice)
            from_node = self.pattern.nodes[e.from_alias]
            to_node = self.pattern.nodes[e.to_alias]
            root = (
                from_node
                if self.estimate(from_node) <= self.estimate(to_node)
                else to_node
            )
            for cand in self.node_candidates(root):
                if not self.check_node(root, cand, bindings):
                    continue
                nb = dict(bindings)
                nb[root.alias] = cand
                yield from self._solve_required([e] + rest, isolated, nb)
            return
        yield from self._expand_edge(e, rest, isolated, bindings, solver=self._solve_required)

    def _expand_edge(
        self, e: PatternEdge, rest, isolated, bindings, solver
    ) -> Iterator[Dict[str, object]]:
        fb = e.from_alias in bindings
        tb = e.to_alias in bindings
        to_node = self.pattern.nodes[e.to_alias]
        from_node = self.pattern.nodes[e.from_alias]
        if fb:
            start = bindings[e.from_alias]
            if start is None:
                # optional upstream bound to null: propagate null
                nb = dict(bindings)
                nb.setdefault(e.to_alias, None)
                yield from solver(rest, isolated, nb)
                return
            for cand, edge, depth, path in self.expand(start, e.item, bindings):
                if tb:
                    bound = bindings[e.to_alias]
                    if bound is None or cand.rid != bound.rid:
                        continue
                elif not self.check_node(to_node, cand, bindings):
                    continue
                nb = dict(bindings)
                nb[e.to_alias] = cand
                self._bind_extras(nb, e.item, edge, depth, path)
                yield from solver(rest, isolated, nb)
        else:
            # reverse expansion: to is bound, from is not
            start = bindings[e.to_alias]
            if start is None:
                nb = dict(bindings)
                nb.setdefault(e.from_alias, None)
                yield from solver(rest, isolated, nb)
                return
            for cand, edge, depth, path in self.expand(
                start, e.item, bindings, reverse=True
            ):
                if not self.check_node(from_node, cand, bindings):
                    continue
                nb = dict(bindings)
                nb[e.from_alias] = cand
                self._bind_extras(nb, e.item, edge, depth, path)
                yield from solver(rest, isolated, nb)

    def _bind_extras(self, bindings, item: A.MatchPathItem, edge, depth, path) -> None:
        f = item.edge_filter
        if f is not None and f.alias and edge is not None:
            bindings[f.alias] = edge
        tgt = item.target
        if tgt.depth_alias:
            bindings[tgt.depth_alias] = depth
        if tgt.path_alias:
            bindings[tgt.path_alias] = list(path)

    def _solve_optionals(
        self, optionals: List[PatternEdge], bindings: Dict[str, object]
    ) -> Iterator[Dict[str, object]]:
        if not optionals:
            yield bindings
            return
        # process optional edges whose from-side is decided first
        e = None
        for i, cand_e in enumerate(optionals):
            if cand_e.from_alias in bindings or cand_e.to_alias in bindings:
                e = cand_e
                rest = optionals[:i] + optionals[i + 1 :]
                break
        if e is None:
            # fully detached optional arm: bind nulls
            nb = dict(bindings)
            for oe in optionals:
                nb.setdefault(oe.from_alias, None)
                nb.setdefault(oe.to_alias, None)
            yield nb
            return
        matched_any = False
        results = []
        for nb in self._expand_edge(
            e, rest, [], bindings, solver=lambda r, i, b: self._solve_optionals(r, b)
        ):
            matched_any = True
            results.append(nb)
        if matched_any:
            yield from iter(results)
        else:
            nb = dict(bindings)
            both_bound = e.from_alias in bindings and e.to_alias in bindings
            if not (both_bound and self._arm_optional(e)):
                # node-optional: the undecided endpoint binds null. An
                # arm-optional probe between two bound aliases must NOT
                # overwrite either endpoint — only its own extras null.
                nb[e.to_alias if e.from_alias in bindings else e.from_alias] = None
            f = e.item.edge_filter
            if f is not None and f.alias:
                nb[f.alias] = None
            if e.item.target.depth_alias:
                nb[e.item.target.depth_alias] = None
            if e.item.target.path_alias:
                nb[e.item.target.path_alias] = None
            yield from self._solve_optionals(rest, nb)

    def _not_arms_ok(self, bindings: Dict[str, object]) -> bool:
        for path in self.not_paths:
            if self._not_path_satisfiable(path, bindings):
                return False
        return True

    def _not_path_satisfiable(self, path: A.MatchPath, bindings) -> bool:
        # build a little sub-pattern for the NOT arm, sharing bound aliases
        sub = Pattern()
        prev = sub.node(path.first)
        for item in path.items:
            tgt = sub.node(item.target)
            sub.edges.append(PatternEdge(prev.alias, tgt.alias, item, True))
            prev = tgt
        saved_nodes = self.pattern.nodes
        saved_edges = self.pattern.edges
        # merge: nodes referenced by the NOT arm use the arm's filters; bound
        # aliases stay fixed through `bindings`
        merged = dict(sub.nodes)
        self.pattern = Pattern()
        self.pattern.nodes = merged
        self.pattern.edges = sub.edges
        try:
            start_bindings = {
                k: v for k, v in bindings.items() if k in merged and v is not None
            }
            for _ in self._solve_required(list(sub.edges), [], start_bindings):
                return True
            return False
        finally:
            self.pattern = Pattern()
            self.pattern.nodes = saved_nodes
            self.pattern.edges = saved_edges

    # -- RETURN ------------------------------------------------------------

    def rows(self) -> List[Result]:
        named = [
            n.alias for n in self.pattern.nodes.values() if not n.anonymous
        ]
        return match_rows_from_bindings(
            self.db, self.stmt, named, self.solve(), self.params, self.parent_ctx
        )


def match_rows_from_bindings(
    db, stmt: A.MatchStatement, named: List[str], bindings_iter, params, parent_ctx
) -> List[Result]:
    """RETURN/DISTINCT/UNWIND/ORDER/SKIP/LIMIT marshalling shared by the
    oracle interpreter and the TPU engine — both produce binding dicts
    (alias → Document/None), so result semantics are defined once here."""
    out: List[Result] = []
    returns = stmt.returns
    special = None
    if len(returns) == 1 and isinstance(returns[0].expr, A.ContextVar):
        cv = returns[0].expr.name.lower()
        if cv in ("matches", "paths", "elements", "pathelements"):
            special = cv
    aggregate_mode = bool(stmt.group_by) or any(
        contains_aggregate(p.expr) for p in returns
    )
    if aggregate_mode:
        sel = A.SelectStatement(
            projections=returns, target=None, group_by=stmt.group_by
        )
        filtered = []
        for bindings in bindings_iter:
            ctx = EvalContext(
                db,
                current=None,
                params=params,
                variables=_return_vars(bindings, named),
                parent=parent_ctx,
            )
            filtered.append((ctx, None))
        out = _aggregate_rows(db, sel, filtered, params, parent_ctx)
        out = _order_rows(out, stmt.order_by, db, params, parent_ctx)
        base_ctx = EvalContext(db, params=params, parent=parent_ctx)
        return _skip_limit(out, stmt.skip, stmt.limit, base_ctx)
    for bindings in bindings_iter:
        if special in ("matches", "paths"):
            aliases = (
                named
                if special == "matches"
                else [a for a in bindings if not _is_internal_alias(a, named)]
            )
            props = {a: bindings.get(a) for a in aliases}
            out.append(Result(props=props))
            continue
        if special in ("elements", "pathelements"):
            aliases = named if special == "elements" else list(bindings.keys())
            for a in aliases:
                v = bindings.get(a)
                if isinstance(v, Document):
                    out.append(Result(element=v))
            continue
        ctx = EvalContext(
            db,
            current=None,
            params=params,
            variables=_return_vars(bindings, named),
            parent=parent_ctx,
        )
        props = {}
        for i, p in enumerate(returns):
            name = p.alias or _match_proj_name(p.expr, i)
            props[name] = evaluate(ctx, p.expr)
        out.append(Result(props=props))

    return finalize_match_rows(db, stmt, out, params, parent_ctx)


def finalize_match_rows(
    db, stmt: A.MatchStatement, out: List[Result], params, parent_ctx
) -> List[Result]:
    """DISTINCT/UNWIND/ORDER/SKIP/LIMIT tail, shared with the TPU engine's
    columnar fast path (which builds `out` straight from device columns)."""
    if stmt.distinct:
        seen = set()
        deduped = []
        for r in out:
            key = _canonical(r)
            if key not in seen:
                seen.add(key)
                deduped.append(r)
        out = deduped
    for field in stmt.unwind:
        unwound = []
        for r in out:
            vals = as_list(r.get_property(field))
            if not vals:
                unwound.append(r)
            for v in vals:
                rr = Result(props={k: r.get_property(k) for k in r.property_names()})
                rr.set_property(field, v)
                unwound.append(rr)
        out = unwound
    out = _order_rows(out, stmt.order_by, db, params, parent_ctx)
    base_ctx = EvalContext(db, params=params, parent=parent_ctx)
    out = _skip_limit(out, stmt.skip, stmt.limit, base_ctx)
    return out


def _is_internal_alias(a: str, named: List[str]) -> bool:
    return a not in named and not a.startswith("$anon")


def _return_vars(bindings: Dict[str, object], named: List[str]) -> Dict[str, object]:
    variables = dict(bindings)
    variables["matched"] = {k: v for k, v in bindings.items() if k in named}
    variables["matches"] = variables["matched"]
    return variables


def _match_proj_name(expr: A.Expression, i: int) -> str:
    if isinstance(expr, A.Identifier):
        return expr.name
    if isinstance(expr, A.FieldAccess) and isinstance(expr.base, A.Identifier):
        return f"{expr.base.name}.{expr.name}"
    return expr_name(expr, i)


def execute_match(db, stmt: A.MatchStatement, params, parent_ctx=None) -> List[Result]:
    return MatchInterpreter(db, stmt, params, parent_ctx).rows()


# ---------------------------------------------------------------------------
# TRAVERSE
# ---------------------------------------------------------------------------


def _traverse_expand(db, doc: Document, fields: Sequence[A.Expression], ctx) -> List[Document]:
    """Records reachable in one step per the TRAVERSE projection list."""
    out: List[Document] = []
    if not fields or any(isinstance(f, A.Star) for f in fields):
        # '*' follows every link: vertex → incident edges; edge → endpoints;
        # plus any explicit link-valued fields
        if isinstance(doc, Vertex):
            out.extend(doc.edges(Direction.OUT))
            out.extend(doc.edges(Direction.IN))
        elif isinstance(doc, Edge):
            fv, tv = db.load(doc.out_rid), db.load(doc.in_rid)
            out.extend(d for d in (fv, tv) if d is not None)
        for name in doc.field_names():
            v = doc.get(name)
            for item in as_list(v):
                if isinstance(item, RID):
                    d = db.load(item)
                    if d is not None:
                        out.append(d)
                elif isinstance(item, Document):
                    out.append(item)
        return out
    for f in fields:
        if isinstance(f, A.FunctionCall):
            name = f.name.lower()
            classes = [evaluate(ctx.child(current=doc), a) for a in f.args]
            if name in ("out", "in", "both"):
                out.extend(nav_vertices(ctx.child(current=doc), doc, name, classes))
                continue
            if name in ("oute", "ine", "bothe"):
                out.extend(nav_edges(ctx.child(current=doc), doc, name[:-1], classes))
                continue
            if name == "any":
                out.extend(_traverse_expand(db, doc, (A.Star(),), ctx))
                continue
        if isinstance(f, A.Identifier):
            v = doc.get(f.name)
            for item in as_list(v):
                if isinstance(item, RID):
                    d = db.load(item)
                    if d is not None:
                        out.append(d)
                elif isinstance(item, Document):
                    out.append(item)
            continue
    return out


def execute_traverse(db, stmt: A.TraverseStatement, params, parent_ctx=None) -> List[Result]:
    base_ctx = EvalContext(db, params=params, parent=parent_ctx)
    roots: List[Document] = []
    for row in resolve_target_rows(db, stmt.target, base_ctx):
        if isinstance(row, Document):
            roots.append(row)
        elif isinstance(row, Result) and row.is_element:
            roots.append(row.element)  # type: ignore[arg-type]
    limit = int(evaluate(base_ctx, stmt.limit)) if stmt.limit is not None else None
    visited: Set[RID] = set()
    out: List[Result] = []
    depth_first = stmt.strategy == "DEPTH_FIRST"

    # frontier entries: (doc, depth)
    frontier: List[Tuple[Document, int]] = [(r, 0) for r in roots]
    if depth_first:
        frontier.reverse()  # stack pops from the end; keep root order

    def admit(doc: Document, depth: int) -> bool:
        if doc.rid in visited:
            return False
        if stmt.max_depth is not None and depth > stmt.max_depth:
            return False
        if stmt.while_cond is not None and depth > 0:
            ctx = EvalContext(
                db,
                current=doc,
                params=params,
                variables={"depth": depth},
                parent=parent_ctx,
            )
            if not truthy(evaluate(ctx, stmt.while_cond)):
                return False
        return True

    while frontier:
        if depth_first:
            doc, depth = frontier.pop()
        else:
            doc, depth = frontier.pop(0)
        if not admit(doc, depth):
            continue
        visited.add(doc.rid)
        res = Result(element=doc)
        # [E] a traverse result carries its depth: a SELECT over the
        # TRAVERSE reads it as $depth (execute_select)
        res.set_metadata("$depth", depth)
        out.append(res)
        if limit is not None and len(out) >= limit:
            break
        children = _traverse_expand(db, doc, stmt.fields, base_ctx)
        entries = [(c, depth + 1) for c in children if c.rid not in visited]
        if depth_first:
            frontier.extend(reversed(entries))
        else:
            frontier.extend(entries)
    return out


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def execute_statement(db, stmt: A.Statement, params, parent_ctx=None) -> List[Result]:
    if isinstance(stmt, A.SelectStatement):
        return execute_select(db, stmt, params, parent_ctx)
    if isinstance(stmt, A.MatchStatement):
        return execute_match(db, stmt, params, parent_ctx)
    if isinstance(stmt, A.TraverseStatement):
        return execute_traverse(db, stmt, params, parent_ctx)
    from orientdb_tpu.exec import dml

    return dml.execute(db, stmt, params, parent_ctx)

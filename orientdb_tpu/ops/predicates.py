"""Columnar predicate compiler: WHERE AST → fused device masks.

The reference evaluates `WHERE` as an interpreted expression tree per
candidate record inside the MATCH hot loop ([E] OExpression eval inside
MatchEdgeTraverser, SURVEY.md §3.3). Here the same AST compiles once per
query into a closure over device property columns; applied to a whole
frontier it is a handful of vectorized compares/selects that XLA fuses into
the expansion gathers ("edge-property WHERE predicates fused in" — the
north star).

Semantics contract: must agree with `orientdb_tpu/exec/eval.py` on the
columnar subset — parity tests replay the golden corpus through both
engines. Key OrientDB null rules preserved:
  - any comparison with null is false (only IS NULL sees nulls);
  - `!=` additionally needs both sides non-null;
  - AND/OR collapse null to false; NOT(null) is true;
  - type-mismatched `=` is false, `<` family is false, while `!=` of two
    non-null incomparable values is true (values_equal falls back to
    Python `==`).

String columns are dictionary-encoded with a *sorted* dictionary, so:
  - ordered compares against a literal become int32 compares versus the
    literal's bisect rank;
  - LIKE / MATCHES / CONTAINSTEXT are evaluated host-side over the (small)
    dictionary and pushed to device as a boolean code-membership table.

Anything outside the subset raises `Uncompilable`; the engine front door
falls back to the oracle interpreter, keeping behavior total.
"""

from __future__ import annotations

import bisect
import re
from typing import Callable, Dict, Optional, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from orientdb_tpu.exec.eval import like_match
from orientdb_tpu.ops import csr as K
from orientdb_tpu.ops.device_graph import DeviceColumn
from orientdb_tpu.sql import ast as A


class Uncompilable(Exception):
    """Expression outside the columnar subset; caller falls back."""


def split_params(params: Dict) -> Tuple[Dict[object, str], Dict[object, object]]:
    """Partition query parameters into *dynamic* (numeric — become jit
    arguments of the cached plan, so one compiled plan serves every value)
    and *static* (everything else — baked into the compiled predicates, so
    their values join the plan-cache key). int32 range is the TPU-native
    integer width; out-of-range ints stay static and hit `_const_val`'s
    range gate (→ oracle fallback)."""
    dyn: Dict[object, str] = {}
    static: Dict[object, object] = {}
    for k, v in params.items():
        if isinstance(v, bool):
            dyn[k] = "bool"
        elif isinstance(v, int) and -(2**31) < v < 2**31:
            dyn[k] = "int"
        elif isinstance(v, float):
            dyn[k] = "float"
        else:
            static[k] = v
    return dyn, static


class ParamBox:
    """Mutable parameter environment shared by a solver's compiled
    predicate closures.

    Recording runs read the concrete values from ``current``; a cached
    plan's replay swaps traced jit-argument scalars into ``current`` for
    the duration of the trace, making every numeric parameter a runtime
    input of ONE compiled executable instead of a compile-time constant
    (the [E] OExecutionPlanCache caches per *statement*, not per binding
    set — this is the TPU-native equivalent)."""

    def __init__(self, params: Dict) -> None:
        self.initial = dict(params)
        self.current = dict(params)
        self.dynamic, self.static = split_params(params)
        #: dynamic keys actually referenced by some compiled predicate
        self.used: Dict[object, str] = {}

    def __contains__(self, k) -> bool:
        return k in self.initial

    def set_current(self, values: Dict) -> None:
        self.current = {**self.initial, **values}

    def reset(self) -> None:
        self.current = dict(self.initial)


class ColumnScope:
    """Resolves bare field names for one predicate scope (a vertex alias or
    an edge class' property columns).

    With ``binding_columns`` set (a second, vertex-property scope) the
    compiler also accepts ``alias.prop`` references for aliases in
    ``visible_aliases``: they emit per-slot gathers through the alias'
    binding column, which the caller provides at evaluation time via
    ``env["bindings"][alias]`` (an int32 vertex-index array aligned with
    the mask slots). This is how edge/node WHERE clauses that reference
    earlier MATCH bindings compile ([E] the reference evaluates them
    per-candidate inside MatchEdgeTraverser with the binding context)."""

    def __init__(
        self,
        columns: Dict[str, DeviceColumn],
        non_columnar: Set[str],
        reserved: Set[str] = frozenset(),
        binding_columns: Optional[Dict[str, DeviceColumn]] = None,
        binding_non_columnar: Set[str] = frozenset(),
        visible_aliases: Set[str] = frozenset(),
    ) -> None:
        self.columns = columns
        self.non_columnar = non_columnar
        #: names that are MATCH aliases / variables → binding-dependent
        self.reserved = reserved
        self.binding_columns = binding_columns
        self.binding_non_columnar = binding_non_columnar
        self.visible_aliases = visible_aliases
        #: set True by the compiler when any binding reference compiled —
        #: callers must then pass env["bindings"] at evaluation time
        self.uses_bindings = False

    def resolve(self, name: str) -> Optional[DeviceColumn]:
        if name in self.reserved:
            raise Uncompilable(f"identifier {name!r} is a bound alias/variable")
        if name.startswith("@") or name.startswith("$"):
            raise Uncompilable(f"meta field {name!r} not columnar")
        if name in self.columns:
            return self.columns[name]
        if name in self.non_columnar:
            raise Uncompilable(f"property {name!r} has no columnar encoding")
        return None  # never present → null column

    def resolve_binding(self, alias: str, prop: str) -> Optional[DeviceColumn]:
        """Column for ``alias.prop`` where alias is a visible bound alias;
        raises Uncompilable when ineligible."""
        if self.binding_columns is None or alias not in self.visible_aliases:
            raise Uncompilable(f"alias {alias!r} not visible to this predicate")
        if prop.startswith("@") or prop.startswith("$"):
            raise Uncompilable(f"meta field {prop!r} not columnar")
        if prop in self.binding_columns:
            self.uses_bindings = True
            return self.binding_columns[prop]
        if prop in self.binding_non_columnar:
            raise Uncompilable(f"property {prop!r} has no columnar encoding")
        self.uses_bindings = True
        return None  # never present → null column


# A value node: kind + emit(idx, env) -> (values, present). kind one of
# 'int' 'float' 'bool' 'str' 'null'. For 'str', `dictionary` carries the
# sorted host dictionary. A bool node: emit(idx, env) -> mask.
class _Val:
    __slots__ = ("kind", "emit", "dictionary", "column")

    def __init__(self, kind: str, emit, dictionary=None, column=None):
        self.kind = kind
        self.emit = emit
        self.dictionary = dictionary
        #: source DeviceColumn for 'str' column reads — carries the
        #: delta maintainer's dict_unsorted flag (O(1) sortedness check)
        self.column = column


BoolFn = Callable[[jnp.ndarray, dict], jnp.ndarray]


def _const_val(v) -> _Val:
    if v is None:
        return _Val("null", lambda idx, env: (jnp.zeros(idx.shape, jnp.int32), jnp.zeros(idx.shape, bool)))
    if isinstance(v, bool):
        return _Val("bool", lambda idx, env, v=v: (
            jnp.full(idx.shape, int(v), jnp.int32), jnp.ones(idx.shape, bool)))
    if isinstance(v, int):
        if not (-(2**31) < v < 2**31):
            # float32 demotion would lose precision vs the oracle's exact
            # integer compare near the boundary — fall back instead
            raise Uncompilable(f"integer literal {v} outside int32 range")
        return _Val("int", lambda idx, env, v=v: (
            jnp.full(idx.shape, v, jnp.int32), jnp.ones(idx.shape, bool)))
    if isinstance(v, float):
        return _Val("float", lambda idx, env, v=v: (
            jnp.full(idx.shape, v, jnp.float32), jnp.ones(idx.shape, bool)))
    if isinstance(v, str):
        # literal strings stay host-side; comparisons handle them specially
        return _Val("strlit", lambda idx, env: None, dictionary=v)
    raise Uncompilable(f"literal {v!r} not columnar")


def _column_val(col: DeviceColumn) -> _Val:
    def emit(idx, env, col=col):
        n = col.values.shape[0]
        if n == 0:
            return (jnp.zeros(idx.shape, col.values.dtype), jnp.zeros(idx.shape, bool))
        K.count_read(idx)
        if isinstance(idx, K.IndexRange):
            # a contiguous range is sliced, not gathered; its padding
            # reads absent, as `ok` makes it below
            return (
                K.take_range(col.values, idx, 0),
                K.take_range(col.present, idx, False),
            )
        ok = idx >= 0
        ci = jnp.clip(idx, 0, n - 1)
        return (
            jnp.take(col.values, ci),
            jnp.take(col.present, ci) & ok,
        )

    return _Val(col.kind, emit, dictionary=col.dictionary, column=col)


def _binding_val(alias: str, col: DeviceColumn) -> _Val:
    """``alias.prop``: the per-slot vertex index comes from
    env["bindings"][alias] (same length as the mask slots), then the
    property gathers through it."""

    def emit(idx, env, alias=alias, col=col):
        rows = env["bindings"][alias]
        n = col.values.shape[0]
        if n == 0:
            return (jnp.zeros(rows.shape, col.values.dtype), jnp.zeros(rows.shape, bool))
        K.count_read(rows)
        ok = rows >= 0
        ci = jnp.clip(rows, 0, n - 1)
        return (
            jnp.take(col.values, ci),
            jnp.take(col.present, ci) & ok,
        )

    return _Val(col.kind, emit, dictionary=col.dictionary, column=col)


_NUMERIC = ("int", "float", "bool")


def _promote(a: _Val, b: _Val):
    """Numeric promotion for arithmetic/compare: int32 unless any float."""
    return "float" if "float" in (a.kind, b.kind) else "int"


def _as_dtype(vals, present, kind):
    if kind == "float":
        return vals.astype(jnp.float32), present
    return vals.astype(jnp.int32), present


class Compiler:
    def __init__(self, scope: ColumnScope, params: Dict, allow_depth: bool = False):
        self.scope = scope
        self.params = params
        self.allow_depth = allow_depth
        #: set when a dynamic parameter compiled to a read of the box:
        #: the predicate's mask then differs from replay to replay
        self.uses_params = False

    # -- entry -------------------------------------------------------------

    def compile_bool(self, expr: A.Expression) -> BoolFn:
        return self._bool(expr)

    # -- value nodes -------------------------------------------------------

    def _value(self, expr: A.Expression) -> _Val:
        if isinstance(expr, A.Literal):
            return _const_val(expr.value)
        if isinstance(expr, A.Parameter):
            key = expr.name if expr.name is not None else expr.index
            if key not in self.params:
                sig = f":{expr.name}" if expr.name is not None else f"?{expr.index}"
                raise Uncompilable(f"missing parameter {sig}")
            return self._param_val(key)
        if isinstance(expr, A.Identifier):
            col = self.scope.resolve(expr.name)
            if col is None:
                return _const_val(None)
            return _column_val(col)
        if (
            isinstance(expr, A.FieldAccess)
            and isinstance(expr.base, A.Identifier)
            and self.scope.binding_columns is not None
            and expr.base.name in self.scope.visible_aliases
        ):
            alias = expr.base.name
            col = self.scope.resolve_binding(alias, expr.name)
            if col is None:
                return _const_val(None)
            return _binding_val(alias, col)
        if isinstance(expr, A.ContextVar):
            if expr.name == "depth" and self.allow_depth:
                return _Val(
                    "int",
                    lambda idx, env: (
                        jnp.full(idx.shape, env["depth"], jnp.int32),
                        jnp.ones(idx.shape, bool),
                    ),
                )
            raise Uncompilable(f"context var ${expr.name} not columnar")
        if isinstance(expr, A.Unary):
            if expr.op in ("-", "+"):
                v = self._value(expr.expr)
                if v.kind not in _NUMERIC:
                    raise Uncompilable("unary minus on non-numeric")
                if expr.op == "+":
                    return v

                def emit(idx, env, v=v):
                    vals, pres = v.emit(idx, env)
                    return -vals, pres

                return _Val("int" if v.kind in ("int", "bool") else "float", emit)
            raise Uncompilable(f"unary {expr.op} is boolean")
        if isinstance(expr, A.Binary) and expr.op in ("+", "-", "*", "/", "%"):
            return self._arith(expr)
        if (
            isinstance(expr, A.FunctionCall)
            and expr.name.lower() == "distance"
        ):
            return self._distance(expr)
        raise Uncompilable(f"expression {type(expr).__name__} not columnar")

    def _distance(self, expr: A.FunctionCall) -> _Val:
        """Device haversine ([E] OSQLFunctionDistance): spatial predicates
        like ``distance(lat, lng, :x, :y) < r`` evaluate over the float
        columns on device — all V distances in one fused elementwise pass
        instead of a per-row host loop."""
        from orientdb_tpu.utils.geo import (
            EARTH_RADIUS_KM,
            MILE_UNITS,
            MILES_PER_KM,
        )

        if len(expr.args) not in (4, 5):
            raise Uncompilable("distance() takes 4 args (+ optional unit)")
        scale = 1.0
        if len(expr.args) == 5:
            u = expr.args[4]
            if not isinstance(u, A.Literal) or str(u.value).lower() not in (
                MILE_UNITS | {"km"}
            ):
                raise Uncompilable("distance() unit must be a literal")
            if str(u.value).lower() != "km":
                scale = MILES_PER_KM
        vals = [self._value(a) for a in expr.args[:4]]
        for v in vals:
            if v.kind == "null":
                return _const_val(None)
            # bool is numeric to arithmetic but the host oracle's
            # distance() rejects it (returns null) — match by falling back
            if v.kind not in ("int", "float"):
                raise Uncompilable("non-numeric distance() operand")

        def emit(idx, env, vals=vals, scale=scale):
            rads = []
            pres = jnp.ones(idx.shape, bool)
            for v in vals:
                vv, vp = _as_dtype(*v.emit(idx, env), "float")
                rads.append(jnp.deg2rad(vv))
                pres = pres & vp
            lat1, lon1, lat2, lon2 = rads
            h = (
                jnp.sin((lat2 - lat1) / 2.0) ** 2
                + jnp.cos(lat1) * jnp.cos(lat2) * jnp.sin((lon2 - lon1) / 2.0) ** 2
            )
            d = (
                2.0
                * EARTH_RADIUS_KM
                * jnp.arcsin(jnp.sqrt(jnp.clip(h, 0.0, 1.0)))
                * scale
            )
            return d, pres

        return _Val("float", emit)

    def _param_val(self, key) -> _Val:
        """A parameter reference: dynamic numerics read the box's current
        value (a concrete number while recording, a traced jit argument on
        replay); everything else bakes as a constant."""
        box = self.params
        if not isinstance(box, ParamBox) or key not in box.dynamic:
            v = box.initial[key] if isinstance(box, ParamBox) else box[key]
            return _const_val(v)
        kind = box.dynamic[key]
        box.used[key] = kind
        self.uses_params = True
        dtype = jnp.float32 if kind == "float" else jnp.int32

        def emit(idx, env, box=box, key=key, dtype=dtype):
            v = jnp.asarray(box.current[key]).astype(dtype)
            return (
                jnp.broadcast_to(v, idx.shape),
                jnp.ones(idx.shape, bool),
            )

        return _Val(kind, emit)

    def _arith(self, expr: A.Binary) -> _Val:
        a = self._value(expr.left)
        b = self._value(expr.right)
        if a.kind in ("strlit", "str") or b.kind in ("strlit", "str"):
            raise Uncompilable("string arithmetic not columnar")
        if a.kind == "null" or b.kind == "null":
            return _const_val(None)
        if a.kind not in _NUMERIC or b.kind not in _NUMERIC:
            raise Uncompilable("non-numeric arithmetic")
        op = expr.op
        kind = _promote(a, b)
        if op == "/":
            kind = "float"  # exact-int division equals float division numerically

        def emit(idx, env, a=a, b=b, op=op, kind=kind):
            av, ap = _as_dtype(*a.emit(idx, env), kind)
            bv, bp = _as_dtype(*b.emit(idx, env), kind)
            pres = ap & bp
            if op == "+":
                out = av + bv
            elif op == "-":
                out = av - bv
            elif op == "*":
                out = av * bv
            elif op == "/":
                pres = pres & (bv != 0)
                out = av / jnp.where(bv != 0, bv, 1)
            else:  # %
                pres = pres & (bv != 0)
                out = jnp.mod(av, jnp.where(bv != 0, bv, 1))
            return out, pres

        return _Val(kind, emit)

    # -- boolean nodes -----------------------------------------------------

    def _bool(self, expr: A.Expression) -> BoolFn:
        if isinstance(expr, A.Binary):
            op = expr.op
            if op == "AND":
                l, r = self._bool(expr.left), self._bool(expr.right)
                return lambda idx, env: l(idx, env) & r(idx, env)
            if op == "OR":
                l, r = self._bool(expr.left), self._bool(expr.right)
                return lambda idx, env: l(idx, env) | r(idx, env)
            if op in ("=", "!=", "<", "<=", ">", ">="):
                return self._compare(op, expr.left, expr.right)
            if op in ("LIKE", "MATCHES", "CONTAINSTEXT"):
                return self._string_table_op(op, expr.left, expr.right)
            if op == "IN":
                return self._in(expr.left, expr.right)
            raise Uncompilable(f"operator {op} not columnar")
        if isinstance(expr, A.Unary) and expr.op == "NOT":
            inner = self._bool(expr.expr)
            return lambda idx, env: ~inner(idx, env)
        if isinstance(expr, A.Between):
            ge = self._compare(">=", expr.expr, expr.low)
            le = self._compare("<=", expr.expr, expr.high)
            return lambda idx, env: ge(idx, env) & le(idx, env)
        if isinstance(expr, A.IsNull):
            v = self._value(expr.expr)
            if v.kind == "strlit":
                raise Uncompilable("IS NULL on string literal")
            neg = expr.negated

            def isnull(idx, env, v=v, neg=neg):
                if v.kind == "null":
                    pres = jnp.zeros(idx.shape, bool)
                else:
                    _, pres = v.emit(idx, env)
                return pres if neg else ~pres

            return isnull
        if isinstance(expr, A.Literal) and isinstance(expr.value, bool):
            b = expr.value
            return lambda idx, env: jnp.full(idx.shape, b, bool)
        # truthiness of a bare value (where:(flag))
        try:
            v = self._value(expr)
        except Uncompilable:
            raise
        return self._truthy(v)

    def _truthy(self, v: _Val) -> BoolFn:
        if v.kind == "null":
            return lambda idx, env: jnp.zeros(idx.shape, bool)
        if v.kind == "strlit":
            b = bool(v.dictionary)
            return lambda idx, env: jnp.full(idx.shape, b, bool)
        if v.kind == "str":
            # non-empty string is truthy: host-eval over the dictionary
            table = np.array([bool(s) for s in (v.dictionary or [])], bool)
            return self._code_table_mask(v, table)

        def fn(idx, env, v=v):
            vals, pres = v.emit(idx, env)
            return pres & (vals != 0)

        return fn

    def _code_table_mask(self, v: _Val, table: np.ndarray) -> BoolFn:
        dev = jnp.asarray(table) if table.size else jnp.zeros(1, bool)

        def fn(idx, env, v=v, dev=dev, empty=not table.size):
            vals, pres = v.emit(idx, env)
            if empty:
                return jnp.zeros(idx.shape, bool)
            code = jnp.clip(vals, 0, dev.shape[0] - 1)
            return pres & jnp.take(dev, code)

        return fn

    def _string_table_op(self, op: str, left: A.Expression, right: A.Expression) -> BoolFn:
        lv = self._value(left)
        rv = self._value(right)
        if rv.kind != "strlit":
            raise Uncompilable(f"{op} needs a literal pattern")
        pat = rv.dictionary
        if lv.kind == "null":
            return lambda idx, env: jnp.zeros(idx.shape, bool)
        if lv.kind == "strlit":
            # literal op literal: host constant (oracle semantics)
            s = lv.dictionary
            if op == "LIKE":
                res = like_match(s, pat)
            elif op == "MATCHES":
                res = re.fullmatch(pat, s) is not None
            else:
                res = pat in s
            return lambda idx, env, res=res: jnp.full(idx.shape, res, bool)
        if lv.kind != "str":
            return lambda idx, env: jnp.zeros(idx.shape, bool)  # non-str LIKE → false
        d = lv.dictionary or []
        if op == "LIKE":
            table = np.array([like_match(s, pat) for s in d], bool)
        elif op == "MATCHES":
            table = np.array([re.fullmatch(pat, s) is not None for s in d], bool)
        else:  # CONTAINSTEXT
            table = np.array([pat in s for s in d], bool)
        return self._code_table_mask(lv, table)

    def _in(self, left: A.Expression, right: A.Expression) -> BoolFn:
        if not isinstance(right, A.ListExpr):
            raise Uncompilable("IN needs a literal list")
        eqs = [self._compare("=", left, item) for item in right.items]
        if not eqs:
            return lambda idx, env: jnp.zeros(idx.shape, bool)

        def fn(idx, env, eqs=eqs):
            m = eqs[0](idx, env)
            for e in eqs[1:]:
                m = m | e(idx, env)
            return m

        return fn

    # -- comparisons -------------------------------------------------------

    def _compare(self, op: str, left: A.Expression, right: A.Expression) -> BoolFn:
        a = self._value(left)
        b = self._value(right)
        # null on either side: every compare false (incl. !=)
        if a.kind == "null" or b.kind == "null":
            return lambda idx, env: jnp.zeros(idx.shape, bool)
        # string literal vs string literal: host constant
        if a.kind == "strlit" and b.kind == "strlit":
            res = _host_cmp(op, a.dictionary, b.dictionary)
            return lambda idx, env, res=res: jnp.full(idx.shape, res, bool)
        # string column vs literal (either side)
        if a.kind == "str" and b.kind == "strlit":
            return self._cmp_str_lit(op, a, b.dictionary)
        if a.kind == "strlit" and b.kind == "str":
            return self._cmp_str_lit(_flip(op), b, a.dictionary)
        # type-mismatch across order classes
        a_num = a.kind in _NUMERIC
        b_num = b.kind in _NUMERIC
        a_str = a.kind == "str"
        b_str = b.kind in ("str", "strlit")
        if (a_num and b_str) or (a_str and b_num) or (a.kind == "strlit" and b_num):
            if op == "!=":
                # non-null incomparables are "not equal" (values_equal fallback)
                def fn(idx, env, a=a, b=b):
                    ap = _presence(a, idx, env)
                    bp = _presence(b, idx, env)
                    return ap & bp

                return fn
            return lambda idx, env: jnp.zeros(idx.shape, bool)
        if a_str and b.kind == "str":
            if a.dictionary is not None and a.dictionary is b.dictionary:
                if op not in ("=", "!=") and not _dict_sorted(a):
                    raise Uncompilable(
                        "ordered string compare on a delta-appended "
                        "dictionary (compaction re-sorts)"
                    )
                # same sorted dictionary (same property column on both
                # sides): code rank order == lexicographic order, so the
                # codes compare directly as ints (codes compare by
                # identity for =/!=, which appended dictionaries keep)
                a = _Val("int", a.emit)
                b = _Val("int", b.emit)
                a_num = b_num = True
            else:
                raise Uncompilable("string column vs string column compare")
        # numeric vs numeric (bool included)
        if not (a_num and b_num):
            raise Uncompilable(f"cannot compare {a.kind} with {b.kind}")
        ordered_ok = True
        if ("bool" in (a.kind, b.kind)) and a.kind != b.kind and op not in ("=", "!="):
            # compare() yields None for bool vs non-bool → ordered ops false
            ordered_ok = False
        kind = _promote(a, b)

        def fn(idx, env, a=a, b=b, op=op, kind=kind, ordered_ok=ordered_ok):
            av, ap = _as_dtype(*a.emit(idx, env), kind)
            bv, bp = _as_dtype(*b.emit(idx, env), kind)
            pres = ap & bp
            if op not in ("=", "!=") and not ordered_ok:
                return jnp.zeros(idx.shape, bool)
            if op == "=":
                c = av == bv
            elif op == "!=":
                c = av != bv
            elif op == "<":
                c = av < bv
            elif op == "<=":
                c = av <= bv
            elif op == ">":
                c = av > bv
            else:
                c = av >= bv
            return pres & c

        return fn

    def _cmp_str_lit(self, op: str, col: _Val, lit: str) -> BoolFn:
        d: Sequence[str] = col.dictionary or []
        if not _dict_sorted(col):
            # the delta maintainer (storage/deltas) APPENDED new strings:
            # codes no longer rank-ordered, so bisect is wrong. Equality
            # still compiles (exact code lookup); ordered compares fall
            # back to the oracle until compaction re-sorts.
            if op not in ("=", "!="):
                raise Uncompilable(
                    "ordered string compare on a delta-appended "
                    "dictionary (compaction re-sorts)"
                )
            lookup = (
                col.column.dict_lookup if col.column is not None else None
            )
            if lookup is not None:
                # the maintainer's value→code map: O(1) vs the O(n)
                # dictionary rescan, on the path every dict append
                # makes hot (appends bump plan_gen → re-record)
                exact_u: Optional[int] = lookup.get(lit)
            else:  # defensive: column-less _Vals are never delta-appended
                try:
                    exact_u = list(d).index(lit)
                except ValueError:
                    exact_u = None

            def ufn(idx, env, col=col, op=op, exact=exact_u):
                vals, pres = col.emit(idx, env)
                if op == "=":
                    if exact is None:
                        return jnp.zeros(idx.shape, bool)
                    return pres & (vals == exact)
                if exact is None:
                    return pres
                return pres & (vals != exact)

            return ufn
        lo = bisect.bisect_left(d, lit)
        hi = bisect.bisect_right(d, lit)
        exact = lo if (lo < len(d) and d[lo] == lit) else None

        def fn(idx, env, col=col, op=op, exact=exact, lo=lo, hi=hi):
            vals, pres = col.emit(idx, env)
            if op == "=":
                if exact is None:
                    return jnp.zeros(idx.shape, bool)
                return pres & (vals == exact)
            if op == "!=":
                if exact is None:
                    return pres
                return pres & (vals != exact)
            if op == "<":
                return pres & (vals < lo)
            if op == "<=":
                return pres & (vals < hi)
            if op == ">":
                return pres & (vals >= hi)
            return pres & (vals >= lo)  # >=

        return fn


def _presence(v: _Val, idx, env) -> jnp.ndarray:
    if v.kind == "strlit":
        return jnp.ones(idx.shape, bool)
    if v.kind == "null":
        return jnp.zeros(idx.shape, bool)
    _, pres = v.emit(idx, env)
    return pres


def _dict_sorted(v: _Val) -> bool:
    """True while the column dictionary's code order is lexicographic —
    the build-time invariant ordered compares rely on. The delta
    maintainer appends new strings at the tail, breaking it until
    compaction; it flags the host column (``dict_unsorted``), so a
    column-backed value answers in O(1). Only a _Val with no column
    attribution pays the O(n) scan (defensive: snapshot builds always
    sort, so untracked dictionaries are sorted in practice)."""
    col = v.column
    if col is not None:
        return not col.dict_unsorted
    d = v.dictionary or []
    return all(d[i] <= d[i + 1] for i in range(len(d) - 1))


def _flip(op: str) -> str:
    return {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "=", "!=": "!="}[op]


def _host_cmp(op: str, a: str, b: str) -> bool:
    return {
        "=": a == b,
        "!=": a != b,
        "<": a < b,
        "<=": a <= b,
        ">": a > b,
        ">=": a >= b,
    }[op]


def compile_predicate(
    expr: A.Expression,
    scope: ColumnScope,
    params: Dict,
    allow_depth: bool = False,
) -> BoolFn:
    """Compile a WHERE AST into `fn(idx_array, env) -> bool mask`.

    ``fn.uses_params`` says whether the mask reads a dynamic parameter of
    a `ParamBox` (what ``box.used`` gained here, or had already): a
    literal and a static parameter are constants of the compiled plan.

    Raises Uncompilable outside the columnar subset."""
    c = Compiler(scope, params, allow_depth=allow_depth)
    fn = c.compile_bool(expr)
    fn.uses_params = c.uses_params
    return fn

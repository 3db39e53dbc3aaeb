"""jaxlint — device-boundary & recompile hygiene for traced JAX code.

The perf this reproduction chases is won or lost at the JAX trace
boundary, and nothing static watched it: a host materialization inside
a jitted replay stalls the device pipeline every dispatch, an impure
call bakes a trace-time value into the executable forever, and an
un-memoized ``jax.jit(...)`` in method scope recompiles on every call
— all silent until a measured run regresses. This pass discovers the
**traced regions** (functions decorated ``@jax.jit`` /
``@partial(jax.jit, ...)``, functions and lambdas passed to
``jax.jit`` / ``jax.vmap`` / ``shard_map``, plus their same-module
call closure through typed receivers) and checks:

inside traced regions —

- **host sync**: ``jax.device_get`` / ``block_until_ready()`` and the
  rest of locklint's blocking-call vocabulary (``urlopen``, socket
  I/O, ``sleep``) execute at trace time and serialize the device
  pipeline;
- **host materialization on traced values** (trace-root functions,
  whose parameters ARE tracers): ``np.asarray(x)``, ``x.item()`` /
  ``x.tolist()``, ``float(x)`` / ``int(x)`` / ``bool(x)``, and
  ``if`` / ``while`` on tracer-valued expressions — a concretization
  error at best, a silent constant at worst. Values reached through
  ``.shape`` / ``.dtype`` / ``.ndim`` / ``len()`` are static and
  exempt; parameters named in ``static_argnames`` are host values by
  contract and exempt (a *direct* parameter gating control flow gets
  the "add it to static_argnames" advice);
- **impure side effects**: ``time.*`` / ``random.*`` calls,
  ``metrics.incr``/``gauge``/``observe``, span helpers
  (``span``/``_span``/``timed``), and lock acquisition — these run
  once at trace time and never again, which is almost never what the
  author meant;
- **config reads**: ``config.<key>`` inside a traced region freezes
  the value into the executable — an operator retuning the declared
  key (configlint's table) changes nothing until a recompile. Read it
  before the jit boundary and pass it in;
- **full-capacity all_gather**: ``all_gather`` of a non-scalar buffer
  in a region that also tracks a device count (a reduction-assigned
  name) gathers whole capacity blocks when the count already bounds
  the live prefix — O(S·cap) collective bytes where a packed-segment
  psum merge ships O(total). Gathering the counts themselves
  (``all_gather(tot)`` with ``tot = counts.sum()``) is the cheap
  extent exchange and stays clean — exactly the
  ``mesh_graph.expand_gather`` ring-merge contract.

outside traced regions — recompile hazards:

- **un-memoized jit construction**: ``jax.jit(...)`` built in
  function/method scope gets a fresh compile cache per call unless
  the result lands on ``self``/a module attribute or a cache mapping
  (assignment flow through a local is followed; a bare ``return
  jax.jit(...)`` needs a justified suppression when every caller
  memoizes, the ``tpu_engine._page_fn`` shape);
- **array-valued static_argnames**: a call passing a list/tuple/array
  for a static argument recompiles per distinct value (hashability
  aside) — statics are for small scalars.

Suppress a deliberate site with ``# lint: allow(jaxlint)`` plus a
justification comment. The runtime twin is
:mod:`orientdb_tpu.analysis.deviceguard`, which fails tier-1 tests on
implicit transfers/re-records and cross-checks its observations
against this pass's findings.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from orientdb_tpu.analysis import configlint
from orientdb_tpu.analysis.core import Finding, Module, SourceTree, register
from orientdb_tpu.analysis.locklint import _blocking_callee, _lock_name
from orientdb_tpu.analysis.typeres import TypeTable

#: jax transforms whose function argument becomes a traced region
TRACE_WRAPPERS = frozenset({"jit", "vmap", "pmap", "shard_map"})

#: attribute reads that yield STATIC (host) values on a tracer
STATIC_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "aval"})

#: receiver names whose method calls are impure under trace
IMPURE_MODULES = frozenset({"time", "random"})
#: metrics-registry style receivers: metrics.incr(...) under trace
#: runs once at trace time (the counter silently stops counting)
IMPURE_METRIC_ATTRS = frozenset({"incr", "gauge", "observe"})
#: span/timing helpers called by bare name
IMPURE_SPAN_NAMES = frozenset({"span", "_span", "timed"})

#: host-materialization callables on traced values
HOST_COERCIONS = frozenset({"float", "int", "bool", "complex"})
HOST_METHODS = frozenset({"item", "tolist"})

#: reductions that produce a device COUNT / live-extent scalar — an
#: all_gather of one of these is the cheap "exchange the extents"
#: pattern; an all_gather of anything else in a function that also
#: tracks such a count is gathering a full capacity block whose live
#: prefix the count already bounds (the pre-ISSUE-13 expand_gather)
REDUCTION_CALLS = frozenset(
    {"sum", "max", "min", "any", "all", "prod", "count_nonzero", "mask_count"}
)


def _reduction_rooted(e: ast.expr) -> bool:
    """True when an expression bottoms out in a reduction call after
    unwrapping slicing / reshape / astype / [None]-style lifts."""
    while True:
        if isinstance(e, ast.Subscript):
            e = e.value
            continue
        if isinstance(e, ast.Call):
            name = _callee_name(e.func)
            if name in ("reshape", "astype") and isinstance(
                e.func, ast.Attribute
            ):
                e = e.func.value
                continue
            return name in REDUCTION_CALLS
        return False


def _callee_name(f: ast.expr) -> Optional[str]:
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _is_trace_wrapper(call: ast.Call) -> Optional[str]:
    """'jit'/'vmap'/'pmap'/'shard_map' when this call wraps a function
    into a traced region, else None."""
    name = _callee_name(call.func)
    if name in TRACE_WRAPPERS:
        return name
    return None


def _jit_decorator(dec: ast.expr) -> Optional[ast.Call]:
    """The ``partial(jax.jit, ...)``/``jax.jit`` call of a jit
    decorator (to read static_argnames from), or a sentinel Call-less
    marker; None when the decorator is not a jit."""
    if isinstance(dec, ast.Attribute) and dec.attr == "jit":
        return ast.Call(func=dec, args=[], keywords=[])
    if isinstance(dec, ast.Name) and dec.id == "jit":
        return ast.Call(func=dec, args=[], keywords=[])
    if isinstance(dec, ast.Call):
        head = _callee_name(dec.func)
        if head == "jit":
            return dec
        if head == "partial" and dec.args:
            inner = _callee_name(dec.args[0])
            if inner == "jit":
                return dec
    return None


def _static_argnames(call: Optional[ast.Call]) -> Set[str]:
    out: Set[str] = set()
    if call is None:
        return out
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    out.add(n.value)
    return out


class _Region:
    """One traced function: the def/lambda node, whether it is a trace
    ROOT (its parameters are tracers), and the root's static args."""

    __slots__ = ("node", "root", "statics", "why")

    def __init__(self, node: ast.AST, root: bool, statics: Set[str],
                 why: str) -> None:
        self.node = node
        self.root = root
        self.statics = statics
        self.why = why


class _ModuleScan:
    """Per-module discovery: function tables, traced roots, closure."""

    def __init__(self, mod: Module, types: TypeTable) -> None:
        self.mod = mod
        self.types = types
        self.modname = mod.path.rsplit("/", 1)[-1][:-3]
        #: top-level function name -> node
        self.module_funcs: Dict[str, ast.AST] = {}
        #: (class, method) -> node
        self.methods: Dict[Tuple[str, str], ast.AST] = {}
        #: def/lambda node -> enclosing class name (None for module)
        self.owner: Dict[ast.AST, Optional[str]] = {}
        #: def/lambda node -> enclosing function node (for local defs)
        self.parent_fn: Dict[ast.AST, Optional[ast.AST]] = {}
        self.regions: Dict[ast.AST, _Region] = {}

    # -- indexing ------------------------------------------------------------

    def index(self) -> None:
        tree = self.mod.tree
        assert tree is not None

        def visit(node, classname, fn):
            for c in ast.iter_child_nodes(node):
                if isinstance(c, ast.ClassDef):
                    visit(c, c.name, fn)
                elif isinstance(
                    c, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                ):
                    self.owner[c] = classname
                    self.parent_fn[c] = fn
                    if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        if classname is not None and fn is None:
                            self.methods.setdefault((classname, c.name), c)
                        elif classname is None and fn is None:
                            self.module_funcs.setdefault(c.name, c)
                    visit(c, classname, c)
                else:
                    visit(c, classname, fn)

        visit(tree, None, None)

    # -- root discovery ------------------------------------------------------

    def find_roots(self) -> None:
        tree = self.mod.tree
        assert tree is not None
        # decorated defs
        for node in self.owner:
            for dec in getattr(node, "decorator_list", ()):
                call = _jit_decorator(dec)
                if call is not None:
                    self._add(
                        node, root=True,
                        statics=_static_argnames(call),
                        why=f"decorated @jit (line {node.lineno})",
                    )
        # functions passed to jit/vmap/shard_map
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            wrapper = _is_trace_wrapper(node)
            if wrapper is None or not node.args:
                continue
            statics = _static_argnames(node)
            for target in self._resolve_fn(node.args[0], node):
                self._add(
                    target, root=True, statics=statics,
                    why=f"passed to {wrapper} (line {node.lineno})",
                )

    def _enclosing(self, node: ast.AST) -> Tuple[Optional[str], Optional[ast.AST]]:
        """(class name, function) lexically enclosing an arbitrary
        node — found by scanning the owner maps for the nearest def
        whose span contains the node."""
        best = None
        for fn in self.owner:
            if (
                fn.lineno <= node.lineno
                and getattr(fn, "end_lineno", fn.lineno)
                >= getattr(node, "end_lineno", node.lineno)
            ):
                if best is None or fn.lineno > best.lineno:
                    best = fn
        if best is None:
            return None, None
        return self.owner.get(best), best

    def _resolve_fn(self, expr: ast.expr, site: ast.AST) -> List[ast.AST]:
        """Function node(s) an expression passed to a trace wrapper
        denotes: a lambda, a nested jit/vmap call, ``self.m``, a local
        or module-level def, or a local alias of self-methods
        (``replay = self._a if c else self._b``)."""
        if isinstance(expr, ast.Lambda):
            return [expr]
        if isinstance(expr, ast.Call):
            if _is_trace_wrapper(expr) and expr.args:
                return self._resolve_fn(expr.args[0], site)
            return []
        classname, fn = self._enclosing(site)
        if (
            isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "self"
            and classname is not None
        ):
            m = self.methods.get((classname, expr.attr))
            return [m] if m is not None else []
        if isinstance(expr, ast.Name):
            # a local def or alias in the enclosing function CHAIN
            # (`replay = self._a if c else self._b` one def up from the
            # background `work()` that jits it)
            scope = fn
            while scope is not None:
                for n in ast.walk(scope):
                    if (
                        isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and n.name == expr.id
                    ):
                        return [n]
                out: List[ast.AST] = []
                for n in ast.walk(scope):
                    if (
                        isinstance(n, ast.Assign)
                        and any(
                            isinstance(t, ast.Name) and t.id == expr.id
                            for t in n.targets
                        )
                    ):
                        for leaf in ast.walk(n.value):
                            if (
                                isinstance(leaf, ast.Attribute)
                                and isinstance(leaf.value, ast.Name)
                                and leaf.value.id == "self"
                                and classname is not None
                            ):
                                m = self.methods.get((classname, leaf.attr))
                                if m is not None:
                                    out.append(m)
                if out:
                    return out
                scope = self.parent_fn.get(scope)
            m2 = self.module_funcs.get(expr.id)
            return [m2] if m2 is not None else []
        return []

    def _add(self, node: ast.AST, root: bool, statics: Set[str],
             why: str) -> None:
        existing = self.regions.get(node)
        if existing is not None:
            existing.statics |= statics
            existing.root = existing.root or root
            return
        self.regions[node] = _Region(node, root, statics, why)

    # -- closure -------------------------------------------------------------

    def close_over_calls(self) -> None:
        """Extend the region set through same-module calls: bare names
        (module functions), ``self.m()``, and typed receivers whose
        class lives in this module. Closure members get the impurity /
        sync / config checks but not the taint checks (their
        parameters' tracer-ness is unknown)."""
        work = list(self.regions)
        seen: Set[ast.AST] = set(work)
        while work:
            fn = work.pop()
            region = self.regions[fn]
            classname = self.owner.get(fn)
            env = self.types.local_env(fn)
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            local_defs = {
                n.name: n
                for n in ast.walk(fn)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                and n is not fn
            }
            for stmt in body:
                for node in ast.walk(stmt):
                    if not isinstance(node, ast.Call):
                        continue
                    targets = self._call_targets(node, classname, env)
                    if (
                        not targets
                        and isinstance(node.func, ast.Name)
                        and node.func.id in local_defs
                    ):
                        targets = [local_defs[node.func.id]]
                    for target in targets:
                        if target in seen:
                            continue
                        seen.add(target)
                        root_why = region.why
                        if not root_why.startswith("reached from"):
                            root_why = f"reached from {root_why}"
                        self._add(
                            target, root=False, statics=set(),
                            why=root_why,
                        )
                        work.append(target)

    def _call_targets(
        self,
        call: ast.Call,
        classname: Optional[str],
        env: Dict[str, str],
    ) -> List[ast.AST]:
        f = call.func
        if isinstance(f, ast.Name):
            m = self.module_funcs.get(f.id)
            return [m] if m is not None else []
        if isinstance(f, ast.Attribute):
            if isinstance(f.value, ast.Name) and f.value.id == "self":
                if classname is not None:
                    m = self.methods.get((classname, f.attr))
                    return [m] if m is not None else []
                return []
            owner = self.types.resolve(f.value, classname, env)
            if owner is not None:
                m = self.methods.get((owner, f.attr))
                return [m] if m is not None else []
        return []


# ---------------------------------------------------------------------------
# inside-region checks
# ---------------------------------------------------------------------------


class _RegionChecker:
    def __init__(self, scan: _ModuleScan, region: _Region,
                 aliases: Set[str]) -> None:
        self.scan = scan
        self.region = region
        self.aliases = aliases  # config-singleton local names
        self.findings: List[Finding] = []
        self.path = scan.mod.path
        node = region.node
        self.taint: Set[str] = set()
        if region.root:
            args = getattr(node, "args", None)
            if args is not None:
                for a in (
                    list(args.args)
                    + list(args.posonlyargs)
                    + list(args.kwonlyargs)
                ):
                    if a.arg != "self" and a.arg not in region.statics:
                        self.taint.add(a.arg)
        self.params = set(self.taint)
        # names assigned from a reduction call anywhere in the region:
        # the device counts that track a buffer's live extent. Plain
        # Name targets ONLY — `buf[i] = x.sum()` must not whitelist the
        # buffer (gathering THAT is the pattern the rule catches)
        self.reduced_names: Set[str] = set()
        for n in ast.walk(node):
            if isinstance(n, ast.Assign) and _reduction_rooted(n.value):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        self.reduced_names.add(t.id)

    def _flag(self, node: ast.AST, message: str) -> None:
        self.findings.append(
            Finding("jaxlint", self.path, node.lineno, message)
        )

    def _tainted(self, e: ast.expr) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.taint
        if isinstance(e, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops
        ):
            # `x is None` tests pytree STRUCTURE, not the tracer's
            # value — identity never concretizes
            return False
        if isinstance(e, ast.Attribute):
            if e.attr in STATIC_ATTRS:
                return False  # x.shape / x.dtype are host values
            return self._tainted(e.value)
        if isinstance(e, ast.Call):
            name = _callee_name(e.func)
            if name == "len":
                return False  # len(tracer) is static
            if name in ("range", "enumerate", "zip"):
                return any(self._tainted(a) for a in e.args)
            return any(self._tainted(a) for a in e.args) or any(
                kw.value is not None and self._tainted(kw.value)
                for kw in e.keywords
            ) or self._tainted(e.func)
        for child in ast.iter_child_nodes(e):
            if isinstance(child, ast.expr) and self._tainted(child):
                return True
        return False

    def run(self) -> List[Finding]:
        node = self.region.node
        body = node.body if isinstance(node.body, list) else [node.body]
        for stmt in body:
            self._walk(stmt)
        return self.findings

    def _walk(self, node: ast.AST) -> None:
        # taint propagation through simple assignments, in program order
        if self.region.root:
            if isinstance(node, ast.Assign):
                if self._tainted(node.value):
                    for t in node.targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                self.taint.add(n.id)
            elif isinstance(node, ast.AugAssign):
                if self._tainted(node.value) and isinstance(
                    node.target, ast.Name
                ):
                    self.taint.add(node.target.id)
            elif isinstance(node, (ast.If, ast.While)):
                if self._tainted(node.test):
                    direct = next(
                        (
                            n.id
                            for n in ast.walk(node.test)
                            if isinstance(n, ast.Name) and n.id in self.params
                        ),
                        None,
                    )
                    kind = "while" if isinstance(node, ast.While) else "if"
                    if direct is not None:
                        self._flag(
                            node,
                            f"`{kind}` on traced argument {direct!r} "
                            f"inside a traced region ({self.region.why})"
                            " — Python control flow needs a host value;"
                            " add it to static_argnames (recompiles per"
                            " value) or rewrite with jnp.where/lax.cond",
                        )
                    else:
                        self._flag(
                            node,
                            f"`{kind}` on a tracer-valued expression "
                            f"inside a traced region ({self.region.why})"
                            " — this concretizes the tracer; use "
                            "jnp.where/lax.cond or hoist the decision "
                            "outside the jit boundary",
                        )
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                ce = item.context_expr
                if _lock_name(ce) is not None:
                    self._flag(
                        ce,
                        "lock acquired inside a traced region "
                        f"({self.region.why}) — the acquire runs once "
                        "at trace time and guards nothing at runtime; "
                        "move locking outside the traced function",
                    )
        if isinstance(node, ast.Call):
            self._check_call(node)
        if isinstance(node, ast.Attribute) and not isinstance(
            node.ctx, ast.Store
        ):
            if (
                isinstance(node.value, ast.Name)
                and node.value.id in self.aliases
            ):
                self._flag(
                    node,
                    f"config.{node.attr} read inside a traced region "
                    f"({self.region.why}) — the value bakes into the "
                    "executable at trace time and retuning the key "
                    "changes nothing; read it before the jit boundary "
                    "and pass it in",
                )
        for c in ast.iter_child_nodes(node):
            if isinstance(
                c, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                # a def nested in a traced fn is traced when called —
                # the closure pass visits it if it is ever invoked;
                # skipping here avoids double walks
                continue
            self._walk(c)

    def _check_call(self, call: ast.Call) -> None:
        f = call.func
        name = _callee_name(f)
        if name == "all_gather" and call.args:
            arg = call.args[0]
            scalarish = _reduction_rooted(arg) or (
                isinstance(arg, ast.Name) and arg.id in self.reduced_names
            )
            if not scalarish and self.reduced_names:
                self._flag(
                    call,
                    "full-capacity all_gather of a buffer whose live "
                    "extent is tracked by a device count "
                    f"({', '.join(sorted(self.reduced_names))}) inside "
                    f"a traced region ({self.region.why}) — every shard "
                    "ships its whole capacity block when only the live "
                    "prefix matters; scatter the packed segment at its "
                    "extent offset and psum-merge it instead "
                    "(mesh_graph.expand_gather's ring merge)",
                )
        blocking = _blocking_callee(call)
        if blocking in ("block_until_ready", "device_get"):
            self._flag(
                call,
                f"{blocking}() inside a traced region "
                f"({self.region.why}) — host synchronization under "
                "trace stalls the pipeline (and happens only at trace "
                "time); sync belongs to the fetch path",
            )
        elif blocking is not None:
            self._flag(
                call,
                f"blocking call {blocking}() inside a traced region "
                f"({self.region.why}) — executes once at trace time "
                "and never per dispatch; hoist it out of the traced "
                "function",
            )
        if (
            blocking is None
            and isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Name)
        ):
            recv = f.value.id
            if recv in IMPURE_MODULES:
                self._flag(
                    call,
                    f"{recv}.{f.attr}() inside a traced region "
                    f"({self.region.why}) — impure call runs once at "
                    "trace time and its result is baked in as a "
                    "constant",
                )
            elif recv == "metrics" and f.attr in IMPURE_METRIC_ATTRS:
                self._flag(
                    call,
                    f"metrics.{f.attr}() inside a traced region "
                    f"({self.region.why}) — records once at trace "
                    "time, then never again; count at the dispatch "
                    "site instead",
                )
        if isinstance(f, ast.Name) and f.id in IMPURE_SPAN_NAMES:
            self._flag(
                call,
                f"{f.id}() inside a traced region ({self.region.why}) "
                "— the span measures XLA tracing once, not the work; "
                "time the dispatch, not the trace",
            )
        # taint-gated host materialization (roots only)
        if not self.region.root:
            return
        if name == "asarray" and isinstance(f, ast.Attribute):
            base = f.value
            if (
                isinstance(base, ast.Name)
                and base.id in ("np", "numpy")
                and call.args
                and self._tainted(call.args[0])
            ):
                self._flag(
                    call,
                    "np.asarray() on a traced value inside a traced "
                    f"region ({self.region.why}) — forces a host "
                    "round-trip per call (or fails to trace); keep the "
                    "value on device (jnp) until the fetch path",
                )
        if (
            isinstance(f, ast.Name)
            and f.id in HOST_COERCIONS
            and call.args
            and self._tainted(call.args[0])
        ):
            self._flag(
                call,
                f"{f.id}() coercion of a traced value inside a traced "
                f"region ({self.region.why}) — concretizes the tracer "
                "(host sync); use jnp casts or hoist the value",
            )
        if (
            isinstance(f, ast.Attribute)
            and f.attr in HOST_METHODS
            and self._tainted(f.value)
        ):
            self._flag(
                call,
                f".{f.attr}() on a traced value inside a traced region "
                f"({self.region.why}) — device→host materialization "
                "per element; fetch once via the profiled fetch path",
            )


# ---------------------------------------------------------------------------
# outside-region checks (recompile hazards)
# ---------------------------------------------------------------------------


def _is_jit_construction(call: ast.Call) -> bool:
    name = _callee_name(call.func)
    if name != "jit":
        return False
    # plain `jit(...)`/`jax.jit(...)`; `partial(jax.jit, ...)` builds a
    # decorator, handled by the decorator path
    return True


def _shallow_nodes(fn: ast.AST):
    """Every node lexically inside ``fn`` but NOT inside a nested
    def/lambda (those bodies get their own per-function walk)."""
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    stack = list(body)
    while stack:
        n = stack.pop()
        yield n
        for c in ast.iter_child_nodes(n):
            if isinstance(
                c, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            stack.append(c)


def _unmemoized_jit_findings(
    scan: _ModuleScan,
) -> List[Finding]:
    out: List[Finding] = []
    for fn in scan.owner:
        if isinstance(fn, ast.Lambda):
            continue
        # every jax.jit(...) construction whose nearest enclosing
        # function is `fn` (shallow walk: nested defs report for
        # themselves)
        sites: List[Tuple[ast.Call, Optional[str]]] = []  # (call, local)
        stored_locals: Set[str] = set()
        for node in _shallow_nodes(fn):
            if isinstance(node, ast.Assign):
                v = node.value
                is_jit = isinstance(v, ast.Call) and _is_jit_construction(v)
                for t in node.targets:
                    if isinstance(t, (ast.Attribute, ast.Subscript)):
                        # self.x = fn / cache[key] = fn memoizes a local
                        if isinstance(v, ast.Name):
                            stored_locals.add(v.id)
                        if is_jit:
                            is_jit = False  # directly memoized
                    elif isinstance(t, ast.Name) and is_jit:
                        sites.append((v, t.id))
                        is_jit = False
                if is_jit:
                    sites.append((v, None))
            elif isinstance(node, (ast.Return, ast.Expr)):
                v = node.value
                if isinstance(v, ast.Call) and _is_jit_construction(v):
                    sites.append((v, None))
        for call, local in sites:
            if local is not None and local in stored_locals:
                continue  # flows into self.<attr>/cache[...] later
            out.append(
                Finding(
                    "jaxlint", scan.mod.path, call.lineno,
                    "jax.jit(...) constructed in function scope "
                    "without memoization — every call builds a fresh "
                    "executable cache and recompiles; cache the jitted "
                    "fn on self/module (or allow() with a note that "
                    "callers memoize)",
                )
            )
    return out


def _array_static_findings(scan: _ModuleScan) -> List[Finding]:
    """Call sites passing list/tuple/array expressions for a
    static_argnames argument of a same-module jitted function."""
    out: List[Finding] = []
    statics_by_name: Dict[str, Set[str]] = {}
    for region in scan.regions.values():
        if not region.root or not region.statics:
            continue
        fname = getattr(region.node, "name", None)
        if fname:
            statics_by_name.setdefault(fname, set()).update(region.statics)
    if not statics_by_name:
        return out
    tree = scan.mod.tree
    assert tree is not None
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _callee_name(node.func)
        statics = statics_by_name.get(fname or "")
        if not statics:
            continue
        for kw in node.keywords:
            if kw.arg in statics and _arrayish(kw.value):
                out.append(
                    Finding(
                        "jaxlint", scan.mod.path, kw.value.lineno,
                        f"array-valued static argument {kw.arg!r} to "
                        f"jitted {fname}() — static_argnames hash by "
                        "value, so every distinct array recompiles; "
                        "statics are for small scalars, pass arrays "
                        "as traced operands",
                    )
                )
    return out


def _arrayish(e: ast.expr) -> bool:
    if isinstance(e, (ast.List, ast.Tuple)):
        return True
    if isinstance(e, ast.Call):
        name = _callee_name(e.func)
        if name in ("array", "asarray", "arange", "zeros", "ones", "full"):
            return True
    return False


# ---------------------------------------------------------------------------
# the registered pass
# ---------------------------------------------------------------------------


@register(
    "jaxlint",
    "device-boundary hygiene: host sync / impurity / config reads "
    "inside traced regions; recompile hazards outside",
)
def run_jaxlint(tree: SourceTree) -> Iterable[Finding]:
    findings: List[Finding] = []
    types = TypeTable.build(tree)
    for mod in tree.modules:
        if mod.tree is None:
            continue
        scan = _ModuleScan(mod, types)
        scan.index()
        scan.find_roots()
        if scan.regions:
            scan.close_over_calls()
            aliases = configlint._config_aliases(mod.tree)
            seen: Set[Tuple[int, str]] = set()
            for region in scan.regions.values():
                for f in _RegionChecker(scan, region, aliases).run():
                    # key on (line, rule head) only: a function can be
                    # both a root and in another root's closure, and
                    # the provenance suffix must not double-report it
                    key = (f.line, f.message.split("(")[0])
                    if key not in seen:
                        seen.add(key)
                        findings.append(f)
            findings.extend(_array_static_findings(scan))
        # recompile hazards do not need a resolvable traced region —
        # jax.jit(<unresolvable>) in method scope is still a fresh
        # compile cache per call
        findings.extend(_unmemoized_jit_findings(scan))
    return findings

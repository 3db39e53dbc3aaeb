"""Runtime lock-order sanitizer — the dynamic half of race detection.

locklint derives a STATIC lock-nesting graph; nothing verified that the
orders it derives are the orders threads actually take at runtime (or
that its lexical lock recognition sees every lock that matters). This
module is a TSan-lite: while active it wraps ``threading.Lock`` /
``threading.RLock`` construction in recording proxies and, per thread,
tracks the acquisition stack:

- every acquisition made while other locks are held adds an edge to
  the **dynamic lock-order graph**, with the acquiring stack captured
  the first time each edge is seen;
- an edge that closes a cycle is a **violation**: two real threads
  took the same locks in opposite orders — the pytest plugin FAILS the
  test that observed it, printing both witness stacks;
- a lock **held longer than a threshold** (default
  ``ORIENTTPU_SANITIZER_BLOCK_MS`` = 200 ms — a blocking call executed
  under the lock, the runtime analog of locklint's blocking-under-lock
  finding) is flagged in the session report;
- at session end the dynamic edges are **cross-checked against
  locklint's static graph**: a dynamic edge the static pass missed is
  a locklint gap and is reported (never silently tolerated), and the
  edge set is dumped to ``SANITIZER_EDGES.json`` with the
  dynamic-vs-static coverage ratio.

Lock identity mirrors locklint's node ids: the construction site's
source line names the attribute (``self._lock = threading.Lock()`` in
class C of module m → ``m.C._lock``), so the two graphs share a
namespace. Locks constructed inside ``threading.py`` itself (Condition
/ Event internals) are left raw — zero overhead and zero noise.

pytest integration (``tests/conftest.py`` delegates here; the module
also works standalone via ``-p orientdb_tpu.analysis.sanitizer``):
recording activates for the concurrency-heavy suites in
:data:`SANITIZED_SUITES` and idles elsewhere. ``ORIENTTPU_SANITIZER=0``
disables the plugin entirely (local runs chasing an unrelated failure).
"""

from __future__ import annotations

import _thread
import linecache
import os
import re
import sys
import threading
import time
import traceback
from typing import Dict, List, Optional, Tuple

#: test-module stems the plugin records through (the suites that
#: actually interleave threads: 2PC + chaos, replication under faults,
#: CDC pumps, and the dedicated concurrency suite)
SANITIZED_SUITES = frozenset(
    {
        "test_concurrency",
        "test_partial_failure",
        "test_replication_chaos",
        "test_cdc",
    }
)

_ORIG_LOCK = threading.Lock
_ORIG_RLOCK = threading.RLock
_THREADING_FILE = getattr(threading, "__file__", "<threading>")

_ASSIGN_RE = re.compile(
    r"(self\.)?([A-Za-z_]\w*)\s*(?::[^=]+?)?=\s*[\w.]*?R?Lock\("
)
_SETDEFAULT_RE = re.compile(r"""setdefault\(\s*['"]([A-Za-z_]\w*)['"]""")


def _node_from_frame(frame) -> Tuple[str, str]:
    """(node id, creation file) for a lock constructed in ``frame`` —
    same namespace as locklint's graph nodes."""
    fn = frame.f_code.co_filename
    base = os.path.basename(fn)
    mod = base[:-3] if base.endswith(".py") else base
    src = linecache.getline(fn, frame.f_lineno)
    m = _ASSIGN_RE.search(src)
    if m:
        attr = m.group(2)
        if m.group(1):
            self_obj = frame.f_locals.get("self")
            if self_obj is not None:
                return f"{mod}.{type(self_obj).__name__}.{attr}", fn
            return f"*.{attr}", fn
        return f"{mod}.{attr}", fn
    m = _SETDEFAULT_RE.search(src)
    if m:
        return f"*.{m.group(1)}", fn
    return f"{mod}.<anon:{frame.f_lineno}>", fn


def _stack_summary(limit: int = 14) -> List[str]:
    """Compact acquisition stack, sanitizer frames dropped."""
    here = os.path.abspath(__file__)
    out = []
    for f in traceback.extract_stack()[:-1]:
        if os.path.abspath(f.filename) == here:
            continue
        out.append(f"{f.filename}:{f.lineno} in {f.name}")
    return out[-limit:]


class _Held:
    __slots__ = ("lock_id", "node", "path", "t0", "count")

    def __init__(
        self, lock_id: int, node: str, path: str, t0: float
    ) -> None:
        self.lock_id = lock_id
        self.node = node
        self.path = path
        self.t0 = t0
        self.count = 1


class LockOrderSanitizer:
    """Process-wide recorder. ``install()`` swaps the ``threading``
    factories (idempotent); ``active`` gates recording so proxies
    created once keep a cheap fast path outside sanitized suites."""

    def __init__(self) -> None:
        self.installed = False
        self.active = False
        self._mu = _thread.allocate_lock()  # raw: never itself recorded
        self._tls = threading.local()
        #: (a, b) -> {"thread", "stack", "paths"} — first witness wins
        self.edges: Dict[Tuple[str, str], Dict] = {}
        self.violations: List[Dict] = []
        self.long_holds: List[Dict] = []
        self._cycle_reported: set = set()
        self._cc_cache = None
        #: module-level raw locks that predate install() (import-closure
        #: holes the dynamic graph cannot see) — reported, not silent
        self.preinstall_raw: List[str] = []
        self.threshold_s = (
            float(os.environ.get("ORIENTTPU_SANITIZER_BLOCK_MS", "200"))
            / 1000.0
        )

    # -- lifecycle -----------------------------------------------------------

    def install(self) -> None:
        if not self.installed:
            threading.Lock = _lock_factory  # type: ignore[misc]
            threading.RLock = _rlock_factory  # type: ignore[misc]
            self.installed = True

    def uninstall(self) -> None:
        if self.installed:
            threading.Lock = _ORIG_LOCK  # type: ignore[misc]
            threading.RLock = _ORIG_RLOCK  # type: ignore[misc]
            self.installed = False

    def _stack(self) -> List[_Held]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    # -- recording -----------------------------------------------------------

    def on_acquired(self, lock: "_SanLock") -> None:
        st = self._stack()
        lid = id(lock)
        for fr in st:
            if fr.lock_id == lid:  # reentrant RLock re-acquire
                fr.count += 1
                return
        if self.active:
            for fr in st:
                if fr.node != lock.node:
                    self._note_edge(fr, lock)
        st.append(_Held(lid, lock.node, lock.path, time.monotonic()))

    def on_released(self, lock: "_SanLock") -> None:
        st = self._stack()
        lid = id(lock)
        for i in range(len(st) - 1, -1, -1):
            if st[i].lock_id == lid:
                st[i].count -= 1
                if st[i].count == 0:
                    fr = st.pop(i)
                    dt = time.monotonic() - fr.t0
                    if self.active and dt > self.threshold_s:
                        self._note_long_hold(fr.node, dt)
                return

    def forget(self, lock: "_SanLock") -> int:
        """Condition.wait() releasing an RLock wholesale: drop the
        frame, return its recursion count for the restore."""
        st = self._stack()
        lid = id(lock)
        for i in range(len(st) - 1, -1, -1):
            if st[i].lock_id == lid:
                return st.pop(i).count
        return 0

    def restore(self, lock: "_SanLock", count: int) -> None:
        if count <= 0:
            return
        # re-acquiring after wait() re-runs order checks: waking up
        # while the thread still holds OTHER locks is a real order
        self.on_acquired(lock)
        st = self._stack()
        for fr in st:
            if fr.lock_id == id(lock):
                fr.count = count
                return

    def _note_edge(self, held: _Held, lock: "_SanLock") -> None:
        a, b = held.node, lock.node
        with self._mu:
            if (a, b) in self.edges:
                return
            self.edges[(a, b)] = {
                "thread": threading.current_thread().name,
                "stack": _stack_summary(),
                "paths": (held.path, lock.path),
            }
            cycle = self._find_path(b, a)
        if cycle is not None:
            self._report_cycle(a, b, cycle)

    def _find_path(self, src: str, dst: str) -> Optional[List[str]]:
        """BFS path src → dst over recorded edges (caller holds _mu)."""
        prev: Dict[str, str] = {src: src}
        frontier = [src]
        while frontier:
            nxt = []
            for n in frontier:
                for (x, y) in self.edges:
                    if x == n and y not in prev:
                        prev[y] = n
                        if y == dst:
                            path = [y]
                            while path[-1] != src:
                                path.append(prev[path[-1]])
                            return list(reversed(path))
                        nxt.append(y)
            frontier = nxt
        return None

    def _report_cycle(self, a: str, b: str, path: List[str]) -> None:
        key = frozenset([a, b])
        with self._mu:
            if key in self._cycle_reported:
                return
            self._cycle_reported.add(key)
            fwd = self.edges.get((a, b), {})
            rev = self.edges.get((path[0], path[1])) if len(path) > 1 else None
        self.violations.append(
            {
                "kind": "lock-order-cycle",
                "cycle": [a] + path,
                "edge": (a, b),
                "edge_stack": fwd.get("stack", []),
                "edge_thread": fwd.get("thread", "?"),
                "reverse_edge": (path[0], path[1])
                if len(path) > 1
                else (b, a),
                "reverse_stack": (rev or {}).get("stack", []),
                "reverse_thread": (rev or {}).get("thread", "?"),
            }
        )

    def _note_long_hold(self, node: str, dt: float) -> None:
        with self._mu:
            if len(self.long_holds) >= 50:
                return
            self.long_holds.append(
                {
                    "node": node,
                    "held_ms": round(dt * 1000.0, 1),
                    "released_at": _stack_summary(limit=8),
                    "thread": threading.current_thread().name,
                }
            )

    # -- reporting -----------------------------------------------------------

    def format_violation(self, v: Dict) -> str:
        lines = [
            "lock-order cycle observed at runtime: "
            + " -> ".join(v["cycle"]),
            f"  edge {v['edge'][0]} -> {v['edge'][1]} "
            f"(thread {v['edge_thread']}) acquired at:",
        ]
        lines += [f"    {s}" for s in v["edge_stack"]] or ["    <?>"]
        lines.append(
            f"  reverse edge {v['reverse_edge'][0]} -> "
            f"{v['reverse_edge'][1]} (thread {v['reverse_thread']}) "
            "acquired at:"
        )
        lines += [f"    {s}" for s in v["reverse_stack"]] or ["    <?>"]
        lines.append(
            "  two threads taking these locks in opposite orders "
            "deadlock; pick one global order"
        )
        return "\n".join(lines)

    def repo_edges(self) -> Dict[Tuple[str, str], Dict]:
        """Dynamic edges whose locks were both constructed inside the
        package (test-fixture locks are out of cross-check scope)."""
        out = {}
        for (a, b), w in self.edges.items():
            pa, pb = w.get("paths", ("", ""))
            if "orientdb_tpu" in pa.replace(os.sep, "/") and (
                "orientdb_tpu" in pb.replace(os.sep, "/")
            ):
                out[(a, b)] = w
        return out

    @staticmethod
    def _node_match(static_node: str, dyn_node: str) -> bool:
        """One endpoint of a static edge vs a dynamic node: exact id,
        or an attribute-tail match when EITHER side is a ``*.attr``
        wildcard (locklint collapses non-self locks; the dynamic namer
        collapses setdefault-created ones). A fully-qualified static
        node must match exactly — a mere attribute-name coincidence
        between two different holders is NOT coverage."""
        if static_node == dyn_node:
            return True
        st = static_node.rsplit(".", 1)[-1]
        dt = dyn_node.rsplit(".", 1)[-1]
        if st != dt:
            return False
        return static_node == f"*.{st}" or dyn_node == f"*.{dt}"

    def cross_check(self) -> Dict:
        """Dynamic-vs-static edge comparison. A dynamic edge is covered
        when the static graph has it (per-endpoint :meth:`_node_match`).
        Uncovered edges are locklint gaps. Memoized per edge-set size:
        the session-end dump and the terminal summary both call this,
        and the full-repo AST parse behind lock_graph must not run
        twice for a frozen edge set."""
        cached = getattr(self, "_cc_cache", None)
        if cached is not None and cached[0] == len(self.edges):
            return cached[1]
        from orientdb_tpu.analysis.core import SourceTree
        from orientdb_tpu.analysis.locklint import lock_graph

        static_edges, _ = lock_graph(SourceTree.from_repo())
        dyn = self.repo_edges()
        with self._mu:
            sources = {a for a, _b in self.edges}
        covered, gaps, leaf_gaps = [], [], []
        for (a, b), w in sorted(dyn.items()):
            if any(
                self._node_match(x, a) and self._node_match(y, b)
                for x, y in static_edges
            ):
                covered.append((a, b))
            elif b not in sources:
                # the target never acquired onward in this session: a
                # LEAF lock (tracer/metrics/feed internals) — no cycle
                # can close through it, so it is summarized, not listed
                leaf_gaps.append((a, b))
            else:
                gaps.append({"edge": (a, b), "thread": w["thread"],
                             "stack": w["stack"][-4:]})
        total = len(dyn)
        out = {
            "dynamic_edges": total,
            "covered": len(covered),
            "coverage": round(len(covered) / total, 3) if total else None,
            "gaps": gaps,
            "leaf_gaps": len(leaf_gaps),
            "static_edges": len(static_edges),
        }
        self._cc_cache = (len(self.edges), out)
        return out

    def dump_edges(self, path: str) -> None:
        """Persist the session's dynamic graph + cross-check
        (atomic rewrite)."""
        import json

        from orientdb_tpu.storage.durability import atomic_write

        doc = {
            "edges": [
                {"from": a, "to": b, "thread": w["thread"]}
                for (a, b), w in sorted(self.edges.items())
            ],
            "repo_edges": [
                {"from": a, "to": b}
                for (a, b) in sorted(self.repo_edges())
            ],
            "cross_check": {
                k: v
                for k, v in self.cross_check().items()
                if k != "gaps"
            },
            "violations": len(self.violations),
            "long_holds": self.long_holds,
        }
        atomic_write(
            path, json.dumps(doc, indent=1, sort_keys=True).encode()
        )


#: the process-wide sanitizer every proxy reports to
sanitizer = LockOrderSanitizer()


class _SanLock:
    """Recording proxy over a raw lock, reporting to the sanitizer it
    was created under (the module singleton in production; unit tests
    construct isolated instances). Fast path when inactive: one
    attribute check, then straight through."""

    _is_rlock = False

    def __init__(self, san, inner, node: str, path: str) -> None:
        self._san = san
        self._inner = inner
        self.node = node
        self.path = path

    def acquire(self, blocking: bool = True, timeout: float = -1):
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._san.on_acquired(self)
        return ok

    def release(self) -> None:
        self._san.on_released(self)
        self._inner.release()

    def __enter__(self):
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __getattr__(self, name: str):
        # stdlib integration points (e.g. _at_fork_reinit registered by
        # concurrent.futures at import) reach the raw lock; anything the
        # raw lock lacks raises AttributeError exactly as before, so
        # Condition's hasattr-probing fallbacks behave identically
        return getattr(self._inner, name)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<sanitized {self.node} of {self._inner!r}>"


class _SanRLock(_SanLock):
    _is_rlock = True

    # Condition(lock) integration: wait() must release/restore through
    # the proxy or the hold stack would go stale while the thread
    # blocks in wait (false long-holds, phantom edges)

    def _release_save(self):
        n = self._san.forget(self)
        return (self._inner._release_save(), n)

    def _acquire_restore(self, state) -> None:
        saved, n = state
        self._inner._acquire_restore(saved)
        self._san.restore(self, n)

    def _is_owned(self) -> bool:
        return self._inner._is_owned()


def _lock_factory():
    inner = _ORIG_LOCK()
    frame = sys._getframe(1)
    if frame.f_code.co_filename == _THREADING_FILE:
        return inner  # Condition/Event internals stay raw
    node, path = _node_from_frame(frame)
    return _SanLock(sanitizer, inner, node, path)


def _rlock_factory():
    inner = _ORIG_RLOCK()
    frame = sys._getframe(1)
    if frame.f_code.co_filename == _THREADING_FILE:
        return inner
    node, path = _node_from_frame(frame)
    return _SanRLock(sanitizer, inner, node, path)


# -- pytest plugin -----------------------------------------------------------


def enabled() -> bool:
    """ORIENTTPU_SANITIZER=0 turns the plugin off (local debugging of
    an unrelated failure should not pay the wrapper or risk a
    sanitizer-first failure)."""
    return os.environ.get("ORIENTTPU_SANITIZER", "1") != "0"


def edges_path() -> Optional[str]:
    """Where the session's edge dump lands (ORIENTTPU_SANITIZER_EDGES
    overrides; '0'/'off' disables the dump)."""
    p = os.environ.get("ORIENTTPU_SANITIZER_EDGES")
    if p in ("0", "off"):
        return None
    if p:
        return p
    from orientdb_tpu.analysis.core import repo_root

    return os.path.join(repo_root(), "SANITIZER_EDGES.json")


def plugin_configure() -> None:
    """Install the recording factories at conftest-import time, before
    (almost) any product module is imported, so module-level locks —
    ``_TRACE_LOCK``, registry singletons — are proxies too. Recording
    stays gated per-suite via ``active``; an installed-but-inactive
    proxy costs ~1µs of hold-stack bookkeeping per acquire.

    "Almost": importing THIS module pulls in ``orientdb_tpu/__init__``
    and its closure (models.database, utils.*) first. None of those
    define module-level locks today; rather than trust that silently,
    the already-imported package modules are scanned for raw lock
    attributes and any hit is reported in the terminal summary — an
    invisible-to-the-graph lock is a coverage hole, not a secret."""
    if not enabled():
        return
    raw_types = (type(_ORIG_LOCK()), type(_ORIG_RLOCK()))
    for name, mod in list(sys.modules.items()):
        if not name.startswith("orientdb_tpu"):
            continue
        for attr, val in list(getattr(mod, "__dict__", {}).items()):
            if isinstance(val, raw_types):
                sanitizer.preinstall_raw.append(f"{name}.{attr}")
    sanitizer.install()


def _item_stem(item) -> str:
    return os.path.basename(str(item.fspath)).rsplit(".", 1)[0]


def plugin_runtest_setup(item) -> None:
    if not enabled():
        return
    if _item_stem(item) in SANITIZED_SUITES:
        sanitizer.install()
        sanitizer.active = True
    else:
        sanitizer.active = False


def plugin_runtest_teardown(item) -> None:
    if not enabled():
        return
    n = getattr(plugin_runtest_teardown, "_seen", 0)
    fresh = sanitizer.violations[n:]
    plugin_runtest_teardown._seen = len(sanitizer.violations)  # type: ignore[attr-defined]
    if fresh:
        import pytest

        pytest.fail(
            "\n\n".join(sanitizer.format_violation(v) for v in fresh),
            pytrace=False,
        )


def plugin_sessionfinish() -> None:
    if not enabled():
        return
    sanitizer.active = False
    sanitizer.uninstall()
    p = edges_path()
    if p is not None and sanitizer.edges:
        try:
            sanitizer.dump_edges(p)
        except Exception:  # pragma: no cover - best-effort artifact
            pass


def plugin_terminal_summary(terminalreporter) -> None:
    if not enabled() or not sanitizer.edges:
        return
    tr = terminalreporter
    try:
        chk = sanitizer.cross_check()
    except Exception:  # pragma: no cover - stripped source tree
        return
    tr.write_sep("-", "lock-order sanitizer")
    tr.write_line(
        f"dynamic edges: {len(sanitizer.edges)} "
        f"({chk['dynamic_edges']} in-package, "
        f"{chk['covered']} covered by locklint's static graph"
        + (
            f", coverage {chk['coverage']:.0%})"
            if chk["coverage"] is not None
            else ")"
        )
    )
    for g in chk["gaps"]:
        # a dynamic edge the static pass missed is a locklint gap —
        # reported every run, never silently tolerated
        tr.write_line(
            f"  LOCKLINT GAP: {g['edge'][0]} -> {g['edge'][1]} "
            f"(thread {g['thread']}) — static graph has no such edge"
        )
    if chk["leaf_gaps"]:
        tr.write_line(
            f"  ({chk['leaf_gaps']} further uncovered edge(s) into "
            "leaf locks — no onward acquisition, cycle-incapable; "
            "full list in the edge dump)"
        )
    for name in sanitizer.preinstall_raw:
        tr.write_line(
            f"  PRE-INSTALL RAW LOCK: {name} — created before the "
            "factories installed; invisible to the dynamic graph"
        )
    for h in sanitizer.long_holds[:10]:
        tr.write_line(
            f"  LONG HOLD: {h['node']} held {h['held_ms']}ms by "
            f"{h['thread']} — blocking work under a lock"
        )
    if sanitizer.violations:
        tr.write_line(
            f"  {len(sanitizer.violations)} lock-order cycle(s) "
            "observed (reported as test failures)"
        )


# standalone plugin hooks (-p orientdb_tpu.analysis.sanitizer)


def pytest_configure(config):  # pragma: no cover - via subprocess
    plugin_configure()


def pytest_runtest_setup(item):  # pragma: no cover - exercised via subprocess
    plugin_runtest_setup(item)


def pytest_runtest_teardown(item):  # pragma: no cover - via subprocess
    plugin_runtest_teardown(item)


def pytest_sessionfinish(session, exitstatus):  # pragma: no cover
    plugin_sessionfinish()


def pytest_terminal_summary(terminalreporter):  # pragma: no cover
    plugin_terminal_summary(terminalreporter)

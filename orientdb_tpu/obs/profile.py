"""Continuous profiling: span-tree self-time folding + process telemetry.

Two always-on planes that turn the raw obs primitives into aggregate
evidence:

- **SpanProfileAggregator** — a tracer listener that folds every
  finished LOCAL span tree into a cumulative per-stage *self-time*
  profile (flamegraph-style ``{name, self_ms, total_ms, count,
  children}``). Self time is a span's duration minus its children's —
  the number that says WHERE wall clock goes (e.g. ``query`` →
  ``tpu.step`` hops vs marshalling) without double counting. Governed
  by the same ``config.stats_sample_rate`` knob as the stats table;
  folding costs one dict merge per span, cheap enough to leave on.
- **gauge providers** — callables run at every registry scrape
  (``registry.snapshot_all``) that refresh memory/process gauges in the
  existing registry: RSS, thread count, uptime, live jax buffer bytes,
  snapshot column/adjacency bytes, and WAL segment bytes per attached
  database (``register_server_telemetry`` wires a server's databases
  in at startup).

Spans that continue a REMOTE trace (propagation) fold when their local
outermost span exits; a trace whose root lives on another node
contributes its local subtree only — per-stage profiles are about this
process's execution stages.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from orientdb_tpu.obs.stats import sampled
from orientdb_tpu.utils.config import config

_START_TS = time.time()


# ---------------------------------------------------------------------------
# span-profile aggregation
# ---------------------------------------------------------------------------


class _Node:
    __slots__ = ("name", "count", "self_us", "total_us", "children")

    def __init__(self, name: str) -> None:
        self.name = name
        self.count = 0
        self.self_us = 0.0
        self.total_us = 0.0
        self.children: Dict[str, "_Node"] = {}

    def to_dict(self) -> Dict[str, object]:
        return {
            "name": self.name,
            "count": self.count,
            "self_ms": round(self.self_us / 1000.0, 3),
            "total_ms": round(self.total_us / 1000.0, 3),
            "children": [
                c.to_dict()
                for c in sorted(
                    self.children.values(),
                    key=lambda n: n.total_us,
                    reverse=True,
                )
            ],
        }


class SpanProfileAggregator:
    """Accumulates finished span trees into one cumulative profile.

    Spans arrive in finish order (children before parents); they are
    parked per (trace id, THREAD) and folded when that thread's span
    stack empties — at that point every descendant recorded by the
    thread is present. Keying by thread matters: a force-joined trace
    (an in-process replica apply joining the write's trace) finishes
    spans of ONE trace on several threads, and a trace-only key would
    let the first idle thread consume another thread's still-open
    subtree — misattributing children as roots and double-counting the
    parent's self time. Unfinished traces age out of the bounded
    pending map.
    """

    _PENDING_MAX = 256
    _SAMPLED_OUT = ()  # sentinel: trace sampled out, drop its spans

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pending: Dict[str, object] = {}
        self._pending_order: deque = deque()
        self._root = _Node("")
        self._traces = 0

    # -- ingestion (tracer listener) ----------------------------------------

    def on_span(self, sp) -> None:
        """Tracer listener: called once per finished span, on the span's
        own thread (so the thread-local span stack tells us whether this
        was the outermost)."""
        from orientdb_tpu.obs.trace import current_span

        if config.stats_sample_rate <= 0:  # plane disabled: no lock,
            return  # no pending bookkeeping
        key = (sp.trace_id, threading.get_ident())
        with self._lock:
            rec = self._pending.get(key)
            if rec is None:
                rec = [] if sampled() else self._SAMPLED_OUT
                self._pending[key] = rec
                self._pending_order.append(key)
                while len(self._pending_order) > self._PENDING_MAX:
                    old = self._pending_order.popleft()
                    self._pending.pop(old, None)
            if rec is not self._SAMPLED_OUT and isinstance(rec, list):
                rec.append(
                    (sp.span_id, sp.parent_id, sp.name, sp.duration_us or 0.0)
                )
        # outermost on this thread: every descendant THIS thread
        # recorded for the trace has finished
        if current_span() is None:
            self._fold(key)

    def _fold(self, key) -> None:
        with self._lock:
            rec = self._pending.pop(key, None)
            if rec is None:
                return
            # drop the order entry for sampled-out traces too, or stale
            # ids eat the eviction window and evict LIVE traces
            try:
                self._pending_order.remove(key)
            except ValueError:
                pass
            if not rec or rec is self._SAMPLED_OUT:
                return
            by_id = {sid: (sid, pid, name, dur) for sid, pid, name, dur in rec}
            kids: Dict[Optional[str], List] = {}
            for sid, pid, name, dur in rec:
                parent = pid if pid in by_id else None
                kids.setdefault(parent, []).append((sid, name, dur))

            def merge(node: _Node, sid: str, name: str, dur: float) -> None:
                child = node.children.get(name)
                if child is None:
                    child = node.children[name] = _Node(name)
                child.count += 1
                child.total_us += dur
                child_dur = 0.0
                for csid, cname, cdur in kids.get(sid, ()):
                    child_dur += cdur
                    merge(child, csid, cname, cdur)
                child.self_us += max(dur - child_dur, 0.0)

            for sid, name, dur in kids.get(None, ()):
                merge(self._root, sid, name, dur)
            self._traces += 1

    # -- reading ------------------------------------------------------------

    def profile(self) -> Dict[str, object]:
        """The cumulative flamegraph-style profile."""
        with self._lock:
            return {
                "traces": self._traces,
                "pending": len(self._pending),
                "stages": self._root.to_dict()["children"],
            }

    def flat(self, k: int = 20) -> List[Dict[str, object]]:
        """Top-``k`` stages by cumulative SELF time, flattened across
        the tree (the console's ``STATS PROFILE`` view)."""
        agg: Dict[str, Dict[str, float]] = {}

        def walk(node: _Node) -> None:
            for c in node.children.values():
                a = agg.setdefault(
                    c.name, {"count": 0, "self_us": 0.0, "total_us": 0.0}
                )
                a["count"] += c.count
                a["self_us"] += c.self_us
                a["total_us"] += c.total_us
                walk(c)

        with self._lock:
            walk(self._root)
        rows = [
            {
                "name": name,
                "count": int(a["count"]),
                "self_ms": round(a["self_us"] / 1000.0, 3),
                "total_ms": round(a["total_us"] / 1000.0, 3),
            }
            for name, a in agg.items()
        ]
        rows.sort(key=lambda r: r["self_ms"], reverse=True)
        return rows[: max(k, 0)]

    def reset(self) -> None:
        with self._lock:
            self._pending.clear()
            self._pending_order.clear()
            self._root = _Node("")
            self._traces = 0


#: the process-wide aggregator, registered as a tracer listener on
#: import (obs/__init__ imports this module, and every stats consumer
#: imports through the package)
profiler = SpanProfileAggregator()


def _install() -> None:
    from orientdb_tpu.obs.trace import tracer

    tracer.add_listener(profiler.on_span)


_install()


# ---------------------------------------------------------------------------
# memory / process telemetry gauge providers
# ---------------------------------------------------------------------------

_providers: List[Callable[[], None]] = []
_providers_lock = threading.Lock()


def register_gauge_provider(fn: Callable[[], None]) -> None:
    """Register a callable run at every registry scrape to refresh
    gauges; exceptions are swallowed (telemetry must never fail a
    scrape)."""
    with _providers_lock:
        if fn not in _providers:
            _providers.append(fn)


def unregister_gauge_provider(fn: Callable[[], None]) -> None:
    with _providers_lock:
        try:
            _providers.remove(fn)
        except ValueError:
            pass


def run_gauge_providers() -> None:
    """Run every provider, timing each into the counter
    ``obs.provider_us.<its __name__>``: providers run inside every
    scrape and every watchdog tick, on the serving process's
    interpreter, and one that grows is found by name."""
    from orientdb_tpu.utils.metrics import metrics

    with _providers_lock:
        fns = list(_providers)
    spent: Dict[str, int] = {}
    for fn in fns:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            pass
        key = f"obs.provider_us.{getattr(fn, '__name__', 'provider')}"
        spent[key] = spent.get(key, 0) + round(
            (time.perf_counter() - t0) * 1e6
        )
    metrics.incr_many(spent)


def _rss_bytes() -> Optional[int]:
    try:  # /proc is the live number; getrusage's maxrss is a peak
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        try:
            import resource

            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            return None


def process_telemetry() -> None:
    """RSS / thread count / uptime / live jax buffer bytes — the
    default provider, registered at import."""
    from orientdb_tpu.utils.metrics import metrics

    rss = _rss_bytes()
    if rss is not None:
        metrics.gauge("proc.rss_bytes", rss)
    metrics.gauge("proc.threads", threading.active_count())
    metrics.gauge("proc.uptime_s", round(time.time() - _START_TS, 3))
    try:
        import jax

        arrs = jax.live_arrays()
        metrics.gauge(
            "jax.live_buffer_bytes",
            sum(int(getattr(a, "nbytes", 0)) for a in arrs),
        )
        metrics.gauge("jax.live_buffer_count", len(arrs))
    except Exception:
        pass


register_gauge_provider(process_telemetry)


def _snapshot_bytes(db) -> Dict[str, int]:
    """Host-side snapshot memory by category for one database: vertex
    property columns, adjacency (CSR arrays), edge property columns."""
    out = {"columns": 0, "adjacency": 0, "edge_columns": 0}
    snap = db.current_snapshot()
    if snap is None:
        return out
    for col in snap.v_columns.values():
        for arr in (getattr(col, "values", None), getattr(col, "present", None)):
            if arr is not None:
                out["columns"] += int(getattr(arr, "nbytes", 0))
    for dec in snap.edge_classes.values():
        for name in ("indptr_out", "indptr_in", "dst", "src", "edge_id_in"):
            arr = getattr(dec, name, None)
            if arr is not None:
                out["adjacency"] += int(getattr(arr, "nbytes", 0))
        for col in getattr(dec, "columns", {}).values():
            for arr in (
                getattr(col, "values", None),
                getattr(col, "present", None),
            ):
                if arr is not None:
                    out["edge_columns"] += int(getattr(arr, "nbytes", 0))
    return out


def _wal_bytes(db) -> int:
    """Live WAL file plus archived ``wal-*.log`` segments next to it."""
    wal = getattr(db, "_wal", None)
    path = getattr(wal, "path", None)
    if not path:
        return 0
    total = 0
    try:
        if os.path.exists(path):
            total += os.path.getsize(path)
        d = os.path.dirname(os.path.abspath(path))
        for f in os.listdir(d):
            if f.startswith("wal-") and f.endswith(".log"):
                total += os.path.getsize(os.path.join(d, f))
    except OSError:
        pass
    return total


def database_telemetry(dbs_fn: Callable[[], List]) -> Callable[[], None]:
    """Build a provider publishing per-process totals over ``dbs_fn()``:
    snapshot column/adjacency bytes and WAL segment bytes."""

    def provider() -> None:
        from orientdb_tpu.utils.metrics import metrics

        cols = adj = ecols = wal = 0
        for db in dbs_fn():
            b = _snapshot_bytes(db)
            cols += b["columns"]
            adj += b["adjacency"]
            ecols += b["edge_columns"]
            wal += _wal_bytes(db)
        metrics.gauge("snapshot.column_bytes", cols)
        metrics.gauge("snapshot.adjacency_bytes", adj)
        metrics.gauge("snapshot.edge_column_bytes", ecols)
        metrics.gauge("wal.segment_bytes", wal)

    # the name run_gauge_providers times it under
    provider.__name__ = "database_telemetry"
    return provider


def register_server_telemetry(server) -> Callable[[], None]:
    """Wire a server's databases into the scrape-time telemetry; returns
    the provider (callers keep it to unregister at shutdown). The
    provider holds the server WEAKLY: a server abandoned without
    shutdown() (crash-restart tests) must not be pinned — with its
    multi-GB snapshots — for process lifetime; a dead ref unregisters
    itself on the next scrape."""
    import weakref

    ref = weakref.ref(server)

    def dbs() -> List:
        srv = ref()
        if srv is None:
            unregister_gauge_provider(provider)
            return []
        return list(getattr(srv, "databases", {}).values())

    provider = database_telemetry(dbs)
    register_gauge_provider(provider)
    return provider

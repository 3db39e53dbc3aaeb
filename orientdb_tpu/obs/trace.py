"""Structured tracing: per-query trace IDs and lightweight spans.

Analog of the reference's per-command profiling chain ([E]
OProfiler.startChrono/stopChrono around command execution; SURVEY.md
§5.1), redesigned as explicit spans: every query gets a trace id, and
the layers it crosses (engine dispatch, TPU-engine stages, tx commit,
WAL append, replication apply) each contribute a named span with wall
duration and free-form attributes.

Spans nest through a thread-local stack — a span opened while another
is active becomes its child and inherits the trace id — and finished
spans land in a process-wide bounded ring (:data:`tracer`), cheap
enough to leave on permanently. PROFILE and tests read the ring back
by trace id; nothing is ever written to disk here.

Two things outlive the ring. Every finished span folds into the
metrics registry as ``span.<name>.us`` (whole microseconds) and
``span.<name>.n``, so a window's spans are counter deltas however
many requests it held (the ring keeps 4 096). And where ``jax`` is
already imported, a span is also a ``jax.profiler.TraceAnnotation``:
in a profiler trace it lies on its own thread's host line, on the
device operations' clock. A process that never imported JAX (the
remote client) never does on a span's account.

Two clocks no span can hold are read here too, and merged into every
``metrics.snapshot()``: the CPU time of the serving threads by role
(:data:`roles`, ``thread.<role>.cpu_us``), and the garbage collector's
pauses (:data:`gc_clock`, ``gc.pause_us``, ``gc.collections.gen<N>``,
``gc.pause_us.gen<N>``), a collection of generation 1 or 2 also an
annotation ``gc.gen<N>`` on the trace's clock while a server runs.

Usage::

    with span("tx.commit", creates=3) as sp:
        ...
        sp.set("rows", n)

    tracer.spans(trace_id=sp.trace_id)   # finished spans, oldest first
"""

from __future__ import annotations

import _thread
import gc
import itertools
import sys
import threading
import time
import uuid
from collections import deque
from typing import Dict, List, Optional

from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics

_ids = itertools.count(1)
#: process-unique id prefix: trace/span ids cross process boundaries
#: now (obs/propagation ships them to other nodes, and the debug
#: bundle groups by trace id), so two processes drawing from their own
#: counters must never mint the same id
_PROC = uuid.uuid4().hex[:8]
_local = threading.local()


#: ``jax.profiler.TraceAnnotation`` once JAX is loaded, else None
_annotation = None


def _find_annotation():
    """Look for the profiler's annotation class without importing JAX:
    the client and the load generator never load it and must stay so.
    ``getattr`` twice, because another thread may be half way through
    ``import jax`` (the watchdog's first tick is, in a server whose
    first query has not run yet)."""
    global _annotation
    jax = sys.modules.get("jax")
    _annotation = getattr(
        getattr(jax, "profiler", None), "TraceAnnotation", None
    )
    return _annotation


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current_trace_id() -> Optional[str]:
    """The active trace id on this thread, or None outside any span."""
    st = _stack()
    return st[-1].trace_id if st else None


def current_span() -> Optional["span"]:
    """The innermost active span on this thread, or None. Propagation
    (obs/propagation.py) reads it to build the outbound context."""
    st = _stack()
    return st[-1] if st else None


class span:
    """Context manager recording one span into the process tracer.

    A root span (no active parent on this thread) mints a fresh trace
    id; nested spans inherit it. Attributes passed as kwargs (or set
    later via :meth:`set`) must be JSON-friendly scalars — they travel
    into PROFILE output verbatim.
    """

    __slots__ = (
        "name",
        "attrs",
        "trace_id",
        "span_id",
        "parent_id",
        "start_ts",
        "duration_us",
        "error",
        "_t0",
        "_ann",
    )

    def __init__(self, name: str, **attrs) -> None:
        self.name = name
        self.attrs: Dict[str, object] = dict(attrs)
        self.trace_id: Optional[str] = None
        self.span_id: Optional[str] = None
        self.parent_id: Optional[str] = None
        self.start_ts: Optional[float] = None
        self.duration_us: Optional[float] = None
        self.error: Optional[str] = None
        self._t0 = 0.0
        self._ann = None

    def set(self, key: str, value) -> None:
        self.attrs[key] = value

    def __enter__(self) -> "span":
        st = _stack()
        parent = st[-1] if st else None
        if parent is not None:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        else:
            self.trace_id = f"t{_PROC}{next(_ids):08x}"
        self.span_id = f"s{_PROC}{next(_ids):08x}"
        self.start_ts = time.time()
        st.append(self)
        annotation = _annotation or _find_annotation()
        if annotation is not None:
            self._ann = annotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, _tb):
        self.duration_us = round(
            (time.perf_counter() - self._t0) * 1e6, 1
        )
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, _tb)
            self._ann = None
        if exc_type is not None:
            self.error = f"{exc_type.__name__}: {exc}"
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        else:  # unbalanced exit (thread reuse): drop without corrupting
            try:
                st.remove(self)
            except ValueError:
                pass
        tracer.record(self)
        return False

    def to_dict(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "name": self.name,
            "start_ts": self.start_ts,
            "duration_us": self.duration_us,
        }
        if self.parent_id is not None:
            out["parent_id"] = self.parent_id
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.error is not None:
            out["error"] = self.error
        return out


#: distinct span names that get counters of their own; any further
#: name shares ``span._other``. The catalog holds ~50, but a name can
#: come off the wire (``binary.<op>`` is the client's word), and the
#: registry must not grow by what a client sends
_FOLD_NAMES_MAX = 256


class Tracer:
    """Process-wide bounded ring of finished spans (thread-safe)."""

    def __init__(self, capacity: int) -> None:
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=max(capacity, 16))
        #: span names with counters of their own (the fold, below)
        self._folded: set = set()
        #: finished-span listeners (obs/profile's aggregator); called
        #: OUTSIDE the ring lock, on the finishing span's own thread
        self._listeners: list = []

    def add_listener(self, fn) -> None:
        if fn not in self._listeners:
            self._listeners.append(fn)

    def remove_listener(self, fn) -> None:
        try:
            self._listeners.remove(fn)
        except ValueError:
            pass

    def record(self, sp: span) -> None:
        name = sp.name
        with self._lock:
            self._spans.append(sp)
            if name not in self._folded:
                if len(self._folded) < _FOLD_NAMES_MAX:
                    self._folded.add(name)
                else:
                    name = "_other"
        metrics.incr_many(
            {
                f"span.{name}.us": round(sp.duration_us or 0.0),
                f"span.{name}.n": 1,
            }
        )
        for fn in self._listeners:
            try:
                fn(sp)
            except Exception:  # a listener must never fail a span exit
                pass

    def spans(
        self,
        trace_id: Optional[str] = None,
        name: Optional[str] = None,
    ) -> List[span]:
        """Finished spans, oldest first, optionally filtered."""
        with self._lock:
            items = list(self._spans)
        if trace_id is not None:
            items = [s for s in items if s.trace_id == trace_id]
        if name is not None:
            items = [s for s in items if s.name == name]
        return items

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()


#: the process-wide span ring (sized by config.trace_capacity)
tracer = Tracer(config.trace_capacity)


# -- the host's own time: CPU by thread role, and the collector's pauses ------


class RoleClocks:
    """CPU time of the serving threads, summed by role.

    A serving thread declares its role once, at the top of its loop
    (``session``, ``lane``, ``watchdog``), and retires in the loop's
    ``finally``. Nothing is stamped per request: :meth:`counters`, which
    ``metrics.snapshot()`` calls, reads each live thread's CPU clock
    (``time.pthread_getcpuclockid``) and adds what retired threads
    left, so ``thread.<role>.cpu_us`` never falls and a window's delta
    holds while sessions come and go. A thread that ends without
    retiring (a pool's worker) keeps the last reading a snapshot took.
    The clock counts the thread's time in the kernel too (a
    ``sendall``), never its waits."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        #: thread ident -> [role, CPU clock id, ns at declare, last ns]
        self._live: Dict[int, list] = {}
        #: role -> ns its ended threads spent
        self._retired: Dict[str, int] = {}

    def declare(self, role: str) -> None:
        getclock = getattr(time, "pthread_getcpuclockid", None)
        if getclock is None:  # no per-thread clock on this platform
            return
        ident = threading.get_ident()
        clock = getclock(ident)
        base = time.thread_time_ns()
        with self._lock:
            old = self._live.get(ident)
            if old is not None:
                # this thread's former role, or a thread gone without
                # retiring whose id this one was given
                ended = base if old[1] == clock else old[3]
                self._retired[old[0]] += ended - old[2]
            self._retired.setdefault(role, 0)
            self._live[ident] = [role, clock, base, base]

    def retire(self) -> None:
        ns = time.thread_time_ns()
        with self._lock:
            entry = self._live.pop(threading.get_ident(), None)
            if entry is not None:
                role, _clock, base, last = entry
                self._retired[role] += max(ns, last) - base

    def counters(self) -> Dict[str, int]:
        with self._lock:
            total = dict(self._retired)
            for ident, entry in list(self._live.items()):
                role, clock, base, last = entry
                try:
                    ns = time.clock_gettime_ns(clock)
                except OSError:  # the thread is gone
                    ns = -1
                if ns < last:  # gone, or its id reused: keep what was read
                    self._retired[role] += last - base
                    del self._live[ident]
                    ns = last
                else:
                    entry[3] = ns
                total[role] += ns - base
        return {f"thread.{role}.cpu_us": ns // 1000 for role, ns in total.items()}


#: the serving threads' CPU clocks (server/binary_server, server/coalesce,
#: obs/watchdog declare into it)
roles = RoleClocks()


class GcClock:
    """Every garbage collection, counted and timed, while a server runs.

    ``Server.startup`` installs one ``gc.callbacks`` hook and
    ``shutdown`` removes it (counted, for several servers in one
    process); nothing is installed at import, so the remote client and
    the load generator pay nothing. At each collection's ``stop`` the
    hook adds its pause to ``gc.pause_us`` and ``gc.pause_us.gen<N>``
    and one to ``gc.collections.gen<N>``. A collection of generation 1
    or 2 is also a ``TraceAnnotation`` named ``gc.gen<N>``, from
    ``start`` to ``stop``: it nests inside whatever frame allocated,
    so in a profiler trace it is the shortest host event over the
    pause. Generation 0 runs many times a second, each well under a
    millisecond, and is counted only.

    The totals are this object's own and ``metrics.snapshot()`` reads
    them: a collection starts inside any frame, one that holds the
    registry's lock included, so the hook takes no lock but its own, a
    raw re-entrant one that the lock sanitizer does not wrap."""

    def __init__(self) -> None:
        self._mu = _thread.RLock()
        self._users = 0
        self._totals: Dict[str, int] = {}
        #: this thread's collection in progress: (start ns, annotation)
        self._local = threading.local()

    def install(self) -> None:
        with self._mu:
            self._users += 1
            if self._users == 1:
                gc.callbacks.append(self._hook)

    def uninstall(self) -> None:
        with self._mu:
            if self._users == 0:
                return
            self._users -= 1
            if self._users == 0 and self._hook in gc.callbacks:
                gc.callbacks.remove(self._hook)

    def _hook(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        local = self._local
        if phase == "start":
            ann = None
            if gen:
                annotation = _annotation or _find_annotation()
                if annotation is not None:
                    ann = annotation(f"gc.gen{gen}")
                    ann.__enter__()
            local.open = (time.perf_counter_ns(), ann)
            return
        t0, ann = getattr(local, "open", None) or (None, None)
        if t0 is None:  # installed between this collection's start and stop
            return
        us = (time.perf_counter_ns() - t0) // 1000
        local.open = None
        if ann is not None:
            ann.__exit__(None, None, None)
        with self._mu:
            t = self._totals
            for name, n in (
                ("gc.pause_us", us),
                (f"gc.pause_us.gen{gen}", us),
                (f"gc.collections.gen{gen}", 1),
            ):
                t[name] = t.get(name, 0) + n

    def counters(self) -> Dict[str, int]:
        with self._mu:
            return dict(self._totals)


#: the collector's pauses (installed by server/server.Server.startup)
gc_clock = GcClock()
metrics.add_source(roles.counters)
metrics.add_source(gc_clock.counters)

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

Usage::

    with span("tx.commit", creates=3) as sp:
        ...
        sp.set("rows", n)

    tracer.spans(trace_id=sp.trace_id)   # finished spans, oldest first
"""

from __future__ import annotations

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

"""Process-wide metrics registry.

Analog of the reference's ``OProfiler``/``OAbstractProfiler`` ([E]
core/.../common/profiler/; SURVEY.md §5.1/§5.5): named counters and
duration stats, exported over the HTTP server's ``/metrics`` endpoint
(the JMX/`/profiler` analog) and readable in-process for tests.

Two primitive kinds, both thread-safe:
- counters   — ``incr("query.tpu")``; ``incr_many({...})`` moves
  several under one lock
- durations  — ``observe("query.tpu.dispatch", seconds)`` keeping
  count/total/max so rates and tails are recoverable.

A *source* (``add_source``) is a function whose counters ``snapshot``
merges in: totals another module keeps and reads only when asked, as
``obs/trace`` keeps the serving threads' CPU clocks and the
collector's pauses.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Mapping


class MetricsRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._durations: Dict[str, Dict[str, float]] = {}
        self._gauges: Dict[str, float] = {}
        self._sources: List[Callable[[], Mapping[str, int]]] = []

    def add_source(self, fn: Callable[[], Mapping[str, int]]) -> None:
        """Register once; ``reset`` keeps it (it holds no data here)."""
        if fn not in self._sources:
            self._sources.append(fn)

    def incr(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def incr_many(self, deltas: Mapping[str, int]) -> None:
        """Several counters under one lock: what a fold site (a span
        exit, a critpath commit) pays per request."""
        with self._lock:
            counters = self._counters
            for name, n in deltas.items():
                counters[name] = counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        """Set an instantaneous value (e.g. per-device HBM bytes)."""
        with self._lock:
            self._gauges[name] = value

    def gauge_value(self, name: str) -> float:
        with self._lock:
            return self._gauges.get(name, 0.0)

    def drop_gauge(self, name: str) -> None:
        """Remove a gauge so the series goes ABSENT in the exposition —
        the honest shape for "no current data" (a window-derived gauge
        whose window emptied must not keep exporting its last value as
        if it were live)."""
        with self._lock:
            self._gauges.pop(name, None)

    def observe(self, name: str, seconds: float) -> None:
        with self._lock:
            d = self._durations.get(name)
            if d is None:
                d = self._durations[name] = {"count": 0, "total_s": 0.0, "max_s": 0.0}
            d["count"] += 1
            d["total_s"] += seconds
            d["max_s"] = max(d["max_s"], seconds)

    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            snap = {
                "counters": dict(self._counters),
                "durations": {k: dict(v) for k, v in self._durations.items()},
                "gauges": dict(self._gauges),
            }
        for fn in self._sources:  # outside the lock: a source has its own
            snap["counters"].update(fn())
        return snap

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._durations.clear()
            self._gauges.clear()


#: the process-wide instance (the reference's OProfiler is a singleton too)
metrics = MetricsRegistry()


class timed:
    """Context manager: ``with timed("query.tpu.dispatch"): ...``"""

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self):
        import time

        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import time

        metrics.observe(self.name, time.perf_counter() - self._t0)
        return False

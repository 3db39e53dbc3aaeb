"""Health watchdog: the thread that drives the alert lifecycle.

`obs/alerts.py` is a pure evaluator — something has to tick it. The
:class:`HealthWatchdog` runs with a :class:`~orientdb_tpu.server.server.Server`
(started in ``Server.startup``, stopped in ``shutdown``, mirroring
``Cluster``'s probe thread) and every ``config.watchdog_interval_s``
seconds evaluates the built-in rule catalog over this server's
databases and cluster. Evaluation happens ONLY here (and in explicit
:meth:`tick` calls from tests) — the query hot path never pays
for it; the PR-4-style overhead guard in ``tests/test_alerts.py``
asserts that.

Each tick's rule evaluation runs under a ``watchdog.tick`` span (the
scrub before it under ``scrub.sweep``), so the watchdog's own cost
shows up in the profile plane like any other stage and, through the
span fold (``obs/trace``), as the counters ``span.watchdog.tick.us``
and ``span.scrub.sweep.us`` beside ``watchdog.ticks``. The thread's
CPU time is ``thread.watchdog.cpu_us`` (``obs/trace.roles``).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from orientdb_tpu.obs.alerts import engine
from orientdb_tpu.obs.trace import roles, span
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import get_logger
from orientdb_tpu.utils.metrics import metrics

log = get_logger("watchdog")


class HealthWatchdog:
    """Periodic alert-rule evaluation over one server's state."""

    def __init__(self, server, interval: Optional[float] = None) -> None:
        self.server = server
        #: None = read config.watchdog_interval_s live per tick (the
        #: slowlog convention: retune without restarting)
        self.interval = interval
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle (Server.startup/shutdown) --------------------------------

    def start(self) -> "HealthWatchdog":
        with self._lock:
            # under the lock: two concurrent start() calls must not
            # each observe None and spawn duplicate tick loops
            if self._thread is None:
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._loop, name="health-watchdog", daemon=True
                )
                self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        with self._lock:
            t = self._thread
            self._thread = None
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5)

    def _loop(self) -> None:
        roles.declare("watchdog")
        try:
            while not self._stop.is_set():
                try:
                    self.tick()
                except Exception:  # pragma: no cover - the loop must live
                    log.exception("watchdog tick failed")
                self._stop.wait(
                    self.interval
                    if self.interval is not None
                    else config.watchdog_interval_s
                )
        finally:
            roles.retire()

    # -- one evaluation round -----------------------------------------------

    def tick(self) -> Dict[str, int]:
        """Evaluate every rule once over this server's state. Safe to
        call without the thread running (tests drive the lifecycle
        deterministically this way)."""
        srv = self.server
        dbs = list(getattr(srv, "databases", {}).values())
        cluster = getattr(srv, "cluster", None)
        if config.scrub_enabled and dbs:
            # one budgeted device-state scrub rotation per tick — the
            # continuous-correctness sweep rides the same cadence as
            # rule evaluation (storage/scrub; never raises into the
            # tick, repairs loudly via the scrub_corruption rule)
            from orientdb_tpu.storage.scrub import scrubber

            scrubber.sweep_all(dbs)
        metrics.incr("watchdog.ticks")
        with span("watchdog.tick") as sp:
            out = engine.evaluate(dbs=dbs, cluster=cluster)
            sp.set("fired", out["fired"])
            sp.set("resolved", out["resolved"])
        if out["fired"] or out["resolved"]:
            log.warning(
                "watchdog: %d alert(s) fired, %d resolved this tick",
                out["fired"],
                out["resolved"],
            )
        return out

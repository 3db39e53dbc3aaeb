"""Continuous cross-client micro-batching — fingerprint-keyed lanes.

The compiled engine's batched dispatch (`exec/engine.
execute_query_batch` → `tpu_engine.dispatch_many`) pays one device
round trip for a whole batch, but only a client shipping an explicit
``query_batch`` frame could reach it — every other remote session's
query paid a full round trip alone. This module forms the batches the
clients no longer have to:

- **dispatch lanes**: sessions submit single queries; each lands in a
  per-database lane keyed by the query's FINGERPRINT (``obs/stats``:
  literals folded, case/whitespace normalized — the same id the stats
  table and slowlog join on). A drain therefore produces a HOMOGENEOUS
  micro-batch that replays ONE compiled plan (`tpu_engine.
  dispatch_lane`) instead of a mixed bag re-planned per item; two
  different shapes can never share a micro-batch.
- **adaptive collection window**: each lane learns its recent
  inter-arrival gap and device-time-per-batch (EWMAs) and waits only
  when co-riders are actually likely — sequential lone-client traffic
  (consecutive solo drains) pays ZERO added latency, and the window is
  hard-capped at ``config.coalesce_window_max_ms`` so a single query's
  p50 is bounded by one micro-batch window, never by batch greed. The
  old fixed ``OTPU_COALESCE_WINDOW_MS`` knob is gone; a constructor
  ``window_ms`` (tests) still forces a fixed window.
- **device-resident parameter rings**: each lane owns a
  ``tpu_engine.ParamRing`` — the stacked dynamic-arg pytree of a lane
  dispatch is ``jax.device_put`` once per distinct value set and
  REUSED in place, so steady-state dispatch of repeating parameters
  ships ~zero host bytes (the deviceguard plane proves the path makes
  no implicit transfers).
- **double-buffered dispatch**: the lane worker dispatches micro-batch
  N+1 (forming it and staging its parameters into the other ring slot)
  BEFORE collecting batch N's results, so batch formation and upload
  overlap the device execution in front of them. While a batch's fetch
  blocks, new arrivals queue behind it and drain as the next batch —
  continuous batching, no idle device between drains. The worker's
  two halves of a turn are spans on its own thread: ``lane.stage``
  (plan pick, dynamic args, ring or ``device_put``, launch) and
  ``lane.finish`` (fetch, materialize, ``to_dicts``, deliver).

Per-item isolation: statements that cannot ride a batch
(non-idempotent, EXPLAIN, parse errors, active tx) execute directly on
the submitting thread. A batch-level failure (one member's error
classes the whole call) re-runs per item on a DETACHED fallback thread
— each session gets ITS error or rows, and the lane's drain loop stays
hot instead of stalling every follower behind the poisoned cohort.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

from orientdb_tpu.obs.propagation import continue_trace, current_context
from orientdb_tpu.obs.registry import obs
from orientdb_tpu.obs.trace import roles, span
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import get_logger
from orientdb_tpu.utils.metrics import metrics

log = get_logger("coalesce")

#: consecutive single-item drains after which a lane stops windowing —
#: the traffic is sequential (one client awaiting each result), so a
#: collection wait only taxes that client; overlap re-arms it the
#: moment a drain catches more than one rider
_SOLO_OFF = 3


class _Item:
    __slots__ = (
        "sql",
        "params",
        "event",
        "rows",
        "engine",
        "error",
        "ctx",
        "t_enq",
        "epoch",
        "segs",
    )

    def __init__(self, sql: str, params) -> None:
        self.sql = sql
        self.params = params
        self.event = threading.Event()
        self.rows: Optional[List[dict]] = None
        self.engine: Optional[str] = None
        self.error: Optional[Exception] = None
        #: the submitter's trace context: the lane's dispatch span
        #: CONTINUES the first rider's trace (obs/propagation)
        self.ctx: Optional[Dict] = None
        self.t_enq: float = 0.0
        #: this item's amortized critical-path decomposition, built on
        #: the lane worker and merged into the SUBMITTER's request
        #: record when it wakes (obs/critpath.merge)
        self.segs: Optional[Dict[str, float]] = None
        #: db.mutation_epoch at ADMISSION: the lane dispatch refuses to
        #: serve this item from a snapshot older than every write that
        #: completed before the item was submitted (epoch keying — a
        #: lane window formed pre-write cannot serve post-write queries
        #: stale results)
        self.epoch: int = 0


def _first_ctx(batch: List[_Item]) -> Optional[Dict]:
    """The trace context the lane worker's spans continue: the first
    rider's that has one."""
    return next((i.ctx for i in batch if i.ctx), None)


class _Lane:
    """One fingerprint's dispatch lane: a bounded queue drained by a
    dedicated worker into homogeneous micro-batches."""

    def __init__(self, coal: "QueryCoalescer", db, fid: str) -> None:
        self.coal = coal
        self.db = db
        self.fid = fid
        self._cond = threading.Condition()
        self._pending: List[_Item] = []
        self._stop = False
        self._last_arrival: Optional[float] = None
        self._gap_ewma: Optional[float] = None  # arrival gap, seconds
        self._exec_ewma: Optional[float] = None  # batch execute wall, s
        self._solo_drains = _SOLO_OFF  # start windowless: no tax on firsts
        self._last_window = 0.0  # last adaptive window chosen (gauges)
        #: items the worker is currently executing (this drain + the
        #: double-buffered in-flight batch): the death guard must fail
        #: these too, not only the still-queued ones
        self._active: List[_Item] = []
        #: opaque engine staging state — exec/engine keeps the lane's
        #: device-resident ParamRing here, so this module stays jax-free
        self._ring_state: Dict = {}
        self._thread = threading.Thread(
            target=self._run,
            name=f"coalesce-{db.name}:{fid[:8]}",
            daemon=True,
        )
        self._thread.start()

    # -- producer side -------------------------------------------------------

    def submit(self, item: _Item) -> bool:
        """False when the lane is retiring — the item was NOT queued
        (the coalescer builds a fresh lane or goes direct): an append
        after the final drain would park the session until timeout."""
        now = time.monotonic()
        with self._cond:
            if self._stop:
                return False
            if self._last_arrival is not None:
                gap = now - self._last_arrival
                self._gap_ewma = (
                    gap
                    if self._gap_ewma is None
                    else 0.8 * self._gap_ewma + 0.2 * gap
                )
                if (
                    self._exec_ewma is not None
                    and gap < self._exec_ewma
                ):
                    # arrivals outpace service: genuine overlap, even
                    # if windowless drains keep catching singletons (a
                    # 2-client ping-pong never queues two at once) —
                    # re-arm the window so co-riders can merge
                    self._solo_drains = 0
            self._last_arrival = now
            item.t_enq = now
            self._pending.append(item)
            self._cond.notify()
            return True

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify()

    def last_arrival_ts(self) -> float:
        return self._last_arrival or 0.0

    # -- adaptive window -----------------------------------------------------

    def _window_s(self) -> float:
        """The collection window for the NEXT drain (caller holds
        ``_cond``). A fixed coalescer-level window (tests, back-compat)
        wins; otherwise: no wait while traffic looks sequential or
        arrivals are sparser than the cap, else wait about one batch's
        device time (co-riders accumulate while the device would be
        busy anyway), floored at two arrival gaps and hard-capped."""
        fixed = self.coal.window_s
        if fixed > 0.0:
            return fixed
        if self._solo_drains >= _SOLO_OFF:
            return 0.0
        cap = max(0.0, float(config.coalesce_window_max_ms)) / 1000.0
        if cap <= 0.0 or self._gap_ewma is None or self._gap_ewma > cap:
            return 0.0
        want = (
            self._exec_ewma
            if self._exec_ewma is not None
            else 2.0 * self._gap_ewma
        )
        return min(cap, max(want, 2.0 * self._gap_ewma))

    # -- worker side ---------------------------------------------------------

    def _run(self) -> None:
        roles.declare("lane")
        try:
            self._run_loop()
        except BaseException as e:
            # a dying worker must not wedge its fingerprint: fail the
            # queued items LOUDLY, retire, and let the next submit
            # build a fresh lane (already-delivered items are fine)
            with self._cond:
                self._stop = True
                orphans = self._pending + [
                    i for i in self._active if not i.event.is_set()
                ]
                self._pending = []
                self._active = []
            for item in orphans:
                item.error = RuntimeError(
                    f"coalesce lane worker died: {type(e).__name__}: {e}"
                )
                item.event.set()
            self.coal._drop_lane(self)
            raise
        finally:
            roles.retire()

    def _run_loop(self) -> None:
        inflight: Optional[Tuple[List[_Item], object, float]] = None
        while True:
            batch = self._collect(block=inflight is None)
            with self._cond:
                self._active = list(batch) + (
                    list(inflight[0]) if inflight else []
                )
            handle = None
            t0 = 0.0
            if batch:
                metrics.incr("coalesce.batches")
                metrics.incr("coalesce.items", len(batch))
                if len(batch) > 1:
                    metrics.incr("coalesce.grouped", len(batch))
                obs.observe_size("coalesce.batch_size", float(len(batch)))
                t0 = time.monotonic()
                # dispatch N+1 BEFORE collecting N (double buffering):
                # the new batch's params stage into the ring's other
                # slot and its Execute queues behind N's on device
                with continue_trace(
                    "lane.stage", _first_ctx(batch), n=len(batch)
                ):
                    handle = self._dispatch(batch)
            if inflight is not None:
                with continue_trace(
                    "lane.finish",
                    _first_ctx(inflight[0]),
                    n=len(inflight[0]),
                ):
                    self._finish(*inflight)
                inflight = None
            if batch:
                if handle is not None:
                    inflight = (batch, handle, t0)
                else:
                    self._execute_generic(batch, t0)
            if inflight is None:
                with self._cond:
                    done = self._stop and not self._pending
                if done:
                    self.coal._drop_lane(self)
                    return

    def _collect(self, block: bool) -> List[_Item]:
        """Drain up to ``coalesce_max_batch`` items. ``block=False``
        (an in-flight batch is executing — ITS fetch is the real wait)
        returns whatever is queued right now, window-free: continuous
        batching forms the next batch from the backlog that built up
        behind the device."""
        cap = max(1, int(config.coalesce_max_batch))
        with self._cond:
            if block and not self._pending and not self._stop:
                self._wait_locked()
            if block and self._pending and not self._stop:
                self._window_wait_locked()
            batch = self._pending[:cap]
            del self._pending[:cap]
            if len(batch) > 1:
                self._solo_drains = 0
            elif batch:
                self._solo_drains += 1
            depth, window = len(self._pending), self._last_window
        self.coal._note_drain(self, depth, window)
        return batch

    def _wait_locked(self) -> None:
        """Idle wait for traffic; a lane idle past
        ``coalesce_lane_idle_s`` retires its worker (a fresh submit
        builds a new lane)."""
        idle_s = max(0.0, float(config.coalesce_lane_idle_s))
        deadline = time.monotonic() + idle_s if idle_s > 0 else None
        while not self._pending and not self._stop:
            left = None if deadline is None else deadline - time.monotonic()
            if left is not None and left <= 0:
                self._stop = True
                return
            self._cond.wait(left if left is not None else 1.0)

    def _window_wait_locked(self) -> None:
        """Hold the drain for the adaptive window so co-riders can
        join. Followers' notify() wakes the wait early, so loop until
        the DEADLINE — otherwise the window degrades to
        wait-for-one-follower. A full batch drains immediately."""
        w = self._window_s()
        self._last_window = w
        if w <= 0.0:
            return
        cap = max(1, int(config.coalesce_max_batch))
        deadline = time.monotonic() + w
        while not self._stop and len(self._pending) < cap:
            left = deadline - time.monotonic()
            if left <= 0:
                return
            self._cond.wait(left)

    def _dispatch(self, batch: List[_Item]):
        """Non-blocking lane dispatch (`exec/engine.dispatch_lane_batch`
        — one cached plan, ring-staged params). None routes the batch
        to the generic blocking path (first execution, oracle shapes,
        group executable still compiling)."""
        from orientdb_tpu.exec.engine import dispatch_lane_batch

        try:
            return dispatch_lane_batch(
                self.db,
                [i.sql for i in batch],
                [i.params for i in batch],
                ring_state=self._ring_state,
                # flight-recorder context (obs/timeline): when the
                # first rider entered the lane, and the collection
                # window that formed this micro-batch
                enqueue_ts=min(i.t_enq for i in batch),
                window_s=self._last_window,
                # epoch keying: the snapshot must cover every rider's
                # admission epoch or the batch takes the generic path
                min_epoch=max(i.epoch for i in batch),
            )
        except Exception:
            # eligibility probing must never kill the drain loop; the
            # generic path will execute (and surface) this batch
            log.exception("lane dispatch probe failed; using generic path")
            return None

    def _finish(self, batch: List[_Item], handle, t0: float) -> None:
        """Collect a double-buffered dispatch: fetch, marshal, deliver.
        The span continues the FIRST submitter's trace — the dispatch
        is theirs; co-riders join via their own coalesce.lane spans."""
        ctx = _first_ctx(batch)
        try:
            waits = [max(0.0, t0 - i.t_enq) for i in batch]
            with continue_trace(
                "coalesce.dispatch",
                ctx,
                lane=self.fid,
                n=len(batch),
                mode="lane",
            ):
                results = handle.collect(queue_waits=waits)
            item_segs = getattr(handle, "item_segs", None) or []
            for k, (item, rs) in enumerate(zip(batch, results)):
                t_m = time.monotonic()
                item.rows = rs.to_dicts()
                segs = dict(item_segs[k]) if k < len(item_segs) else {}
                segs["marshal"] = (
                    segs.get("marshal", 0.0) + time.monotonic() - t_m
                )
                item.segs = segs
                item.engine = rs.engine
            for item in batch:
                item.event.set()
            self._observe_exec(time.monotonic() - t0)
        except Exception:
            metrics.incr("coalesce.batch_fallback")
            self._fallback_async(batch)

    def _execute_generic(self, batch: List[_Item], t0: float) -> None:
        """The blocking batch path (records first executions, serves
        oracle shapes). A batch-level failure falls back per item OFF
        this thread — head-of-line isolation: the drain loop keeps
        forming and dispatching micro-batches while the poisoned
        cohort sorts itself out on a fallback thread."""
        import orientdb_tpu.obs.critpath as CP
        import orientdb_tpu.obs.stats as S
        from orientdb_tpu.exec.engine import execute_query_batch

        ctx = _first_ctx(batch)
        n = max(len(batch), 1)
        try:
            # worker-side harvest record: execute_query_batch's front
            # door JOINS it (never commits), so its fold lands the whole
            # batch's device/transfer/plan/host split here for the per-
            # item amortization below
            harvest = (
                CP.CritPath("lane") if config.critpath_enabled else None
            )
            with continue_trace(
                "coalesce.dispatch",
                ctx,
                lane=self.fid,
                n=len(batch),
                mode="batch",
            ):
                with CP.active(harvest):
                    results = execute_query_batch(
                        self.db,
                        [i.sql for i in batch],
                        [i.params for i in batch],
                    )
            per_segs = (
                {k: v / n for k, v in harvest.segs.items()}
                if harvest is not None
                else {}
            )
            # materialize INSIDE the try: a lazily-raising result (an
            # oracle row stream erroring in to_dicts) must route to the
            # per-item fallback, never escape and kill the drain loop
            for item, rs in zip(batch, results):
                t_m = time.monotonic()
                item.rows = rs.to_dicts()
                segs = dict(per_segs)
                segs["queue"] = (
                    segs.get("queue", 0.0) + max(0.0, t0 - item.t_enq)
                )
                segs["marshal"] = (
                    segs.get("marshal", 0.0) + time.monotonic() - t_m
                )
                item.segs = segs
                item.engine = rs.engine
                S.stats.record_queue(item.sql, max(0.0, t0 - item.t_enq))
        except Exception:
            metrics.incr("coalesce.batch_fallback")
            self._fallback_async(batch)
            return
        for item in batch:
            item.event.set()
        self._observe_exec(time.monotonic() - t0)

    def _fallback_async(self, batch: List[_Item]) -> None:
        threading.Thread(
            target=self._fallback_run,
            args=(batch,),
            name=f"coalesce-fb-{self.db.name}",
            daemon=True,
        ).start()

    def _fallback_run(self, batch: List[_Item]) -> None:
        """Per-item re-run of a failed batch: each session gets ITS
        error and the innocent members still get results. Bounded by
        the coalescer-wide semaphore so a poison storm cannot spawn
        unbounded threads."""
        with self.coal._fb_sem:
            for item in batch:
                try:
                    rs = self.db.query(item.sql, item.params)
                    item.rows = rs.to_dicts()
                    item.engine = rs.engine
                except Exception as e:
                    item.error = e
                finally:
                    item.event.set()

    def _observe_exec(self, dur_s: float) -> None:
        with self._cond:
            self._exec_ewma = (
                dur_s
                if self._exec_ewma is None
                else 0.7 * self._exec_ewma + 0.3 * dur_s
            )


class QueryCoalescer:
    """Server-wide registry of per-database, per-fingerprint lanes."""

    def __init__(self, window_ms: Optional[float] = None) -> None:
        #: fixed collection window override (seconds). 0 = adaptive
        #: per-lane windows (the default); tests and the old API set a
        #: fixed one to make grouping deterministic on loaded runners.
        self.window_s = (float(window_ms) / 1000.0) if window_ms else 0.0
        #: id(db) → {fingerprint id → lane}
        self._lanes: Dict[int, Dict[str, _Lane]] = {}
        self._lock = threading.Lock()
        self._stopped = False
        #: bounds concurrent per-item fallback threads (poison storms)
        self._fb_sem = threading.BoundedSemaphore(4)
        #: per-lane drain gauges folded into ONE process gauge each —
        #: 64 lanes overwriting a flat gauge would export whichever
        #: lane drained last; publish the SUM of backlogs and the MAX
        #: window instead (leaf lock: never held while taking others)
        self._gauge_lock = threading.Lock()
        self._depths: Dict[int, int] = {}
        self._windows: Dict[int, float] = {}
        # evicted databases, held WEAKLY: a submit racing evict() must
        # not resurrect a lane for a dropped db (which would pin it
        # forever), and weak refs mean an id() reused after GC cannot
        # false-positive — the tombstone dies with the object
        import weakref

        self._evicted = weakref.WeakSet()

    # -- lane registry -------------------------------------------------------

    def _lane(self, db, fid: str) -> Optional[_Lane]:
        key = id(db)
        lanes = self._lanes.get(key)
        if lanes is not None:
            lane = lanes.get(fid)
            if lane is not None:
                return lane
        victims: List[_Lane] = []
        with self._lock:
            if self._stopped or db in self._evicted:
                return None  # shutdown/evict raced this: go direct
            lanes = self._lanes.setdefault(key, {})
            lane = lanes.get(fid)
            if lane is None:
                cap = max(1, int(config.coalesce_lanes_max))
                while len(lanes) >= cap:
                    # reap the longest-idle lane: its worker drains any
                    # queued items and retires
                    victim = min(
                        lanes.values(), key=_Lane.last_arrival_ts
                    )
                    lanes.pop(victim.fid, None)
                    victims.append(victim)
                lane = lanes[fid] = _Lane(self, db, fid)
            total = sum(len(d) for d in self._lanes.values())
        metrics.gauge("coalesce.lanes", float(total))
        for v in victims:  # outside the registry lock (takes lane conds)
            v.stop()
        return lane

    def _note_drain(self, lane: _Lane, depth: int, window_s: float) -> None:
        """Fold one lane's drain observation into the aggregate
        gauges: total queued backlog across lanes, worst adaptive
        window currently in force."""
        with self._gauge_lock:
            self._depths[id(lane)] = depth
            self._windows[id(lane)] = window_s
            depth_total = sum(self._depths.values())
            window_max = max(self._windows.values())
        metrics.gauge("coalesce.lane_depth", float(depth_total))
        metrics.gauge("coalesce.window_ms", round(window_max * 1000.0, 3))

    def _forget_gauges(self, lane: _Lane) -> None:
        with self._gauge_lock:
            self._depths.pop(id(lane), None)
            self._windows.pop(id(lane), None)

    def _drop_lane(self, lane: _Lane) -> None:
        """Remove a retired lane from the registry (identity-checked: a
        replacement lane under the same key must survive)."""
        with self._lock:
            lanes = self._lanes.get(id(lane.db))
            if lanes is not None and lanes.get(lane.fid) is lane:
                lanes.pop(lane.fid)
                if not lanes:
                    self._lanes.pop(id(lane.db), None)
            total = sum(len(d) for d in self._lanes.values())
        self._forget_gauges(lane)
        metrics.gauge("coalesce.lanes", float(total))

    def evict(self, db) -> None:
        """Stop and drop the database's lanes (drop_database /
        attach-replace): lane worker threads and their strong db
        references must not outlive the database's registration."""
        with self._lock:
            self._evicted.add(db)
            lanes = self._lanes.pop(id(db), None)
        for lane in (lanes or {}).values():
            self._forget_gauges(lane)
            lane.stop()

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
            all_lanes = [
                lane
                for lanes in self._lanes.values()
                for lane in lanes.values()
            ]
            self._lanes = {}
        for lane in all_lanes:
            self._forget_gauges(lane)
            lane.stop()

    # -- submission ----------------------------------------------------------

    @staticmethod
    def _coalescable(db, sql: str) -> bool:
        """Only idempotent, non-EXPLAIN statements outside a tx ride a
        lane; everything else executes directly on the caller."""
        if db.tx is not None:
            return False
        try:
            from orientdb_tpu.exec.engine import parse_cached
            from orientdb_tpu.sql import ast as A

            stmt = parse_cached(sql)
            return stmt.is_idempotent and not isinstance(
                stmt, A.ExplainStatement
            )
        except Exception:
            return False  # parse errors surface on the direct path

    def submit(
        self, db, sql: str, params, timeout: float = 120.0
    ) -> Tuple[List[dict], Optional[str]]:
        """Execute ``sql`` through the database's lane for its
        fingerprint; blocks until the result is ready. Returns
        ``(rows, engine)``."""
        if not self._coalescable(db, sql):
            rs = db.query(sql, params)
            return rs.to_dicts(), rs.engine
        # materialized-view fast path (exec/views): a CDC-valid resident
        # result beats any micro-batch — served before lane formation,
        # so hot fingerprints cost neither a window nor a dispatch
        from orientdb_tpu.exec.engine import _normalize_params
        from orientdb_tpu.exec.views import views_for

        vm = views_for(db) if db.tx is None else None
        if vm is not None:
            view = vm.lookup(sql, _normalize_params(params), None, False)
            if view is not None:
                return (
                    [
                        r if isinstance(r, dict) else r.to_dict()
                        for r in view.rows
                    ],
                    view.engine,
                )
        from orientdb_tpu.obs.stats import fingerprint_cached

        fid = fingerprint_cached(sql).fid
        item = _Item(sql, params)
        item.ctx = current_context()
        item.epoch = db.mutation_epoch
        with span("coalesce.lane", lane=fid) as sp:
            queued = False
            for _attempt in (0, 1):
                lane = self._lane(db, fid)
                if lane is None:
                    break
                if lane.submit(item):
                    queued = True
                    break
                # the lane retired between lookup and submit: drop it
                # and retry once with a fresh one
                self._drop_lane(lane)
            if not queued:
                # shutdown/evict raced the submit: serve the query
                # directly rather than park the session until timeout
                rs = db.query(sql, params)
                return rs.to_dicts(), rs.engine
            if not item.event.wait(timeout):
                raise TimeoutError(f"coalesced query timed out: {sql[:80]}")
            sp.set("engine", item.engine)
        if item.error is not None:
            raise item.error
        # fold the lane-built decomposition into THIS session's request
        # record — the amortized segments are sub-intervals of the wait
        # the submitter just paid, so its segment sum tracks its wall
        import orientdb_tpu.obs.critpath as CP

        CP.merge(item.segs)
        return item.rows or [], item.engine

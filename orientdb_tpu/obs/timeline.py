"""Dispatch flight recorder: per-dispatch lifecycle timelines with
overlap accounting and Chrome-trace/Perfetto export.

The perf arc (PRs 12–13) is now an *overlap* story — double-buffered
lane dispatch, device-resident parameter rings, speculative page
prefetch, collectives issued ahead of local expansion — but nothing
measured whether any of that overlap actually happens: the gauges count
events, not *concurrency*. This module records every dispatch's
lifecycle as timestamped events in a bounded ring and derives the
numbers the counters cannot express:

- **flight recorder** — each dispatch (compiled single, vmapped group,
  coalesce lane drain, sharded mesh, oracle) contributes ONE
  :class:`DispatchRecord`: monotonic-timestamped lifecycle events
  (``enqueue → lane_window → plan_resolve → param_upload｜ring_hit →
  device_dispatch → compute_done → transfer_start/done →
  result_delivered``), device-busy and transfer intervals (from
  ``exec/tpu_engine._fetch_profiled`` / ``_finish_pending`` /
  ``parallel/sharded.fetch_sharded``), and correlation ids (query
  fingerprint from the PR-4 stats plane, trace id from ``obs/trace``).
  Recording rides the ``config.stats_sample_rate`` sampling decision
  and thread-local hooks exactly like ``obs/stats`` — a sampled-out
  query costs one comparison per hook, and the tier-1 overhead guard
  pins the whole plane under 1.35x.
- **overlap accounting** (:meth:`FlightRecorder.overlap`) — the
  derived metrics: *device-idle fraction* (1 − merged "device" time
  over the window span; a record's "device" interval is the HOST's
  wait for the result in ``block_until_ready``, so this is the share
  of the wall in which no host thread waited on the device, not a
  device clock's reading), *transfer-hidden fraction* (bytes whose copy
  interval overlapped device compute vs serialized after it — the
  number that proves or refutes the PR-13 prefetch and PR-12 double
  buffer), *lane queue/window vs service decomposition*, and *ring
  upload-avoidance savings*; globally, per dispatch path, and for the
  hottest fingerprints.
- **export** — :meth:`FlightRecorder.chrome_trace` renders the window
  as Chrome-trace JSON (the ``traceEvents`` array form Perfetto and
  ``chrome://tracing`` load directly), served admin-only at ``GET
  /debug/timeline``, bundled as the debug bundle's ``timeline``
  section, and printed by the console ``TIMELINE [n]`` verb. Scrape
  surfaces: ``orienttpu_overlap_*`` gauges in ``/metrics`` (and the
  member-labeled ``/cluster/metrics`` fan-in) refresh from a bounded
  recent window at scrape time, and the ``overlap_regression`` alert
  rule (obs/alerts) watches the device-idle fraction against its
  online EWMA baseline.

All timestamps are ``time.monotonic()`` seconds (the coalesce lanes'
enqueue clock), so intervals from different threads compare directly;
``chrome_trace`` rescales to microseconds.
"""

from __future__ import annotations

import itertools
import threading
import time
from bisect import bisect_right
from collections import deque
from typing import Dict, List, Optional, Tuple

from orientdb_tpu.utils.config import config

#: the lifecycle vocabulary (README "Dispatch timeline" documents each);
#: compute_done / transfer_start / transfer_done are stamped by
#: :func:`add_phase` alongside the intervals that carry their bytes
EVENTS = (
    "enqueue",          # item entered its coalesce lane (lane path)
    "lane_window",      # lane collection window closed, batch formed
    "plan_resolve",     # cached plan picked (variants.pick)
    "param_upload",     # dynamic args uploaded host→device
    "ring_hit",         # dynamic args served from the device ring
    "prefetch_start",   # speculative result-page copy started
    "kernel_build",     # mesh shard_map kernel built (sharded path)
    "device_dispatch",  # replay enqueued on device
    "compute_done",     # the host's wait for the result returned (no
                        # device clock: the interval it closes is the
                        # host's wait, not the kernels' time)
    "transfer_start",   # blocking device→host drain began
    "transfer_done",    # bytes on host
    "result_delivered", # record committed (rows marshalled)
    "device_fault",     # classified device fault crossed this dispatch
                        # (exec/devicefault; marks carry the kind count)
)

#: dispatch path labels (``note_path`` refines; "lane" is sticky — a
#: lane drain that group-dispatches is still the coalesce path)
PATHS = ("single", "batch", "group", "lane", "sharded", "oracle")


class DispatchRecord:
    """One dispatch's flight record. Owned by the dispatching thread
    until :meth:`FlightRecorder.commit` publishes it into the ring —
    no locking on the hot path."""

    __slots__ = (
        "seq",
        "path",
        "_fid",
        "sql",
        "trace_id",
        "n",
        "t0",
        "t_done",
        "events",
        "device",
        "transfers",
        "marks",
    )

    def __init__(
        self,
        seq: int,
        path: str,
        sql: Optional[str],
        trace_id: Optional[str],
        n: int,
    ) -> None:
        self.seq = seq
        self.path = path
        #: fingerprint resolution is DEFERRED to read time: begin()
        #: keeps only the SQL text so the hot path never pays the
        #: normalization LRU — readers are bounded by the ring
        self._fid: Optional[str] = None
        self.sql = sql
        self.trace_id = trace_id
        self.n = n
        self.t0 = time.monotonic()
        self.t_done: Optional[float] = None
        #: [(event name, monotonic ts)]
        self.events: List[Tuple[str, float]] = []
        #: "device" intervals [(t_start, t_end)]: the HOST's wait for
        #: the dispatch's result, fetch wave by fetch wave — device
        #: execution plus whatever was queued on the device ahead of
        #: it. Device time proper is a profiler trace's (PERF.md)
        self.device: List[Tuple[float, float]] = []
        #: transfer intervals [(t_start, t_end, nbytes, kind)] — kind
        #: "fetch" (blocking drain) or "prefetch" (copy started at
        #: dispatch time, i.e. hidden behind compute by construction)
        self.transfers: List[Tuple[float, float, int, str]] = []
        #: free-form counters/annotations (ring hits, window_s, ...)
        self.marks: Dict[str, object] = {}

    def add_event(self, name: str, ts: Optional[float] = None) -> None:
        self.events.append((name, time.monotonic() if ts is None else ts))

    def bump(self, key: str, by: int = 1) -> None:
        self.marks[key] = int(self.marks.get(key, 0)) + by

    @property
    def fid(self) -> Optional[str]:
        """The stats-plane fingerprint id (resolved lazily from the
        SQL captured at begin; cached on the record)."""
        if self._fid is None and self.sql:
            from orientdb_tpu.obs.stats import fingerprint_cached

            self._fid = fingerprint_cached(self.sql).fid
        return self._fid

    def span(self) -> Tuple[float, float]:
        """(first, last) timestamp this record covers."""
        ts = [self.t0]
        ts.extend(t for _n, t in self.events)
        ts.extend(t for pair in self.device for t in pair)
        ts.extend(t for t, t1, _b, _k in self.transfers for t in (t, t1))
        if self.t_done is not None:
            ts.append(self.t_done)
        return min(ts), max(ts)

    def to_dict(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "seq": self.seq,
            "path": self.path,
            "fingerprint": self.fid,
            "trace_id": self.trace_id,
            "n": self.n,
            "t0": round(self.t0, 6),
            "events": [(n, round(t, 6)) for n, t in self.events],
            "device": [
                (round(a, 6), round(b, 6)) for a, b in self.device
            ],
            "transfers": [
                (round(a, 6), round(b, 6), nb, k)
                for a, b, nb, k in self.transfers
            ],
        }
        if self.t_done is not None:
            out["t_done"] = round(self.t_done, 6)
        if self.marks:
            out["marks"] = dict(self.marks)
        return out


# -- thread-local active record (the obs/stats accumulator pattern) ----------

_local = threading.local()


def _rec_stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current() -> Optional[DispatchRecord]:
    st = getattr(_local, "stack", None)
    return st[-1] if st else None


class active:
    """Make ``rec`` the thread's active record for the block — the
    hot-path hooks below write to whatever is active. ``active(None)``
    is a no-op, so call sites need no sampling branch."""

    __slots__ = ("rec",)

    def __init__(self, rec: Optional[DispatchRecord]) -> None:
        self.rec = rec

    def __enter__(self) -> Optional[DispatchRecord]:
        if self.rec is not None:
            _rec_stack().append(self.rec)
        return self.rec

    def __exit__(self, *exc) -> None:
        if self.rec is None:
            return
        st = _rec_stack()
        if st and st[-1] is self.rec:
            st.pop()
        else:  # unbalanced (should not happen): drop without corrupting
            try:
                st.remove(self.rec)
            except ValueError:
                pass


# -- the recorder ------------------------------------------------------------


def _merge_intervals(
    ivs: List[Tuple[float, float]]
) -> List[Tuple[float, float]]:
    """Union of possibly-overlapping intervals, sorted."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv for iv in ivs if iv[1] > iv[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _overlap_s(
    a0: float,
    a1: float,
    merged: List[Tuple[float, float]],
    ends: List[float],
) -> float:
    """Seconds of ``[a0, a1]`` covered by the merged interval union.
    ``ends`` are the union's interval ends (ascending, as the disjoint
    sorted intervals are): the walk starts by bisection at the first
    interval that reaches past ``a0`` — the ones before it cover
    nothing of ``[a0, a1]`` — so a pass over a full ring is records x
    log intervals, not records x intervals: it runs inside every
    watchdog tick, on the serving process's interpreter."""
    total = 0.0
    k, n = bisect_right(ends, a0), len(merged)
    while k < n:
        b0, b1 = merged[k]
        if b0 >= a1:
            break
        lo, hi = max(a0, b0), min(a1, b1)
        if hi > lo:
            total += hi - lo
        k += 1
    return total


class FlightRecorder:
    """Process-wide bounded ring of committed dispatch records."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        self._lock = threading.Lock()
        self._ring: deque = deque()
        #: lock-free sequence (itertools.count is atomic in CPython) —
        #: begin() is on the per-query hot path
        self._seq = itertools.count(1)
        #: None = read config.timeline_capacity live per commit (the
        #: slowlog convention: retune without restarting)
        self._capacity = capacity

    def _cap(self) -> int:
        return int(
            self._capacity
            if self._capacity is not None
            else config.timeline_capacity
        )

    # -- record lifecycle ---------------------------------------------------

    def begin(
        self,
        path: str,
        sql: Optional[str] = None,
        n: int = 1,
    ) -> Optional[DispatchRecord]:
        """Open a record for one dispatch, or None when the recorder is
        disabled (capacity <= 0) or the dispatch sampled out — every
        later hook then no-ops at one thread-local read.

        Sampling rides the stats plane's decision, not an independent
        draw: for per-query dispatches (no ``sql`` passed) an ACTIVE
        stats accumulator is the sampled-in marker, so under
        ``stats_sample_rate < 1`` the timeline covers exactly the same
        query subset as stats/slowlog/traces — a trace id found in the
        slowlog always joins a timeline record. Detached dispatches
        (lane drains, the in-frame batch front door — their worker
        threads carry no per-query accumulator) pass their ``sql`` and
        draw a decision at the same rate. The fingerprint derives
        lazily (at read time) from the SQL; the trace id is the
        thread's active span's."""
        if self._cap() <= 0:
            return None
        from orientdb_tpu.obs.stats import current_acc, sampled
        from orientdb_tpu.obs.trace import current_trace_id

        if sql is None:
            acc = current_acc()
            if acc is None:
                return None  # the stats plane sampled this query out
            sql = acc.sql or None
        elif not sampled():
            return None
        return DispatchRecord(
            next(self._seq), path, sql, current_trace_id(), n
        )

    def commit(self, rec: Optional[DispatchRecord]) -> None:
        """Stamp ``result_delivered`` and publish the record. A record
        that is never committed (an errored or ineligible dispatch)
        simply never enters the ring."""
        if rec is None:
            return
        rec.t_done = time.monotonic()
        rec.add_event("result_delivered", rec.t_done)
        cap = self._cap()
        if cap <= 0:
            return
        with self._lock:
            self._ring.append(rec)
            while len(self._ring) > cap:
                self._ring.popleft()

    # -- reading ------------------------------------------------------------

    def _window(
        self, window_s: Optional[float]
    ) -> List[DispatchRecord]:
        with self._lock:
            recs = list(self._ring)
        if window_s is None or window_s <= 0 or not recs:
            return recs
        floor = time.monotonic() - window_s
        return [r for r in recs if (r.t_done or r.t0) >= floor]

    def records(
        self,
        window_s: Optional[float] = None,
        limit: Optional[int] = None,
    ) -> List[Dict]:
        recs = self._window(window_s)
        if limit is not None:
            recs = recs[-limit:] if limit > 0 else []
        return [r.to_dict() for r in recs]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()

    # -- overlap accounting -------------------------------------------------

    def overlap(
        self,
        window_s: Optional[float] = None,
        top_fingerprints: int = 8,
    ) -> Dict[str, object]:
        """The derived-metrics pass over the (bounded) recent window.

        Runs under a ``timeline.overlap`` span — the accounting itself
        is an observable stage (it ticks at every scrape via the gauge
        provider and at watchdog evaluation)."""
        from orientdb_tpu.obs.trace import span

        with span("timeline.overlap"):
            return self._overlap(self._window(window_s), top_fingerprints)

    @staticmethod
    def _overlap(
        recs: List[DispatchRecord], top_fingerprints: int
    ) -> Dict[str, object]:
        out: Dict[str, object] = {"records": len(recs)}
        if not recs:
            return out
        spans = [r.span() for r in recs]
        lo = min(s[0] for s in spans)
        hi = max(s[1] for s in spans)
        span_s = max(hi - lo, 1e-9)
        busy = _merge_intervals(
            [iv for r in recs for iv in r.device]
        )
        busy_ends = [b for _a, b in busy]
        busy_s = sum(b - a for a, b in busy)
        out["span_s"] = round(span_s, 6)
        out["device_busy_s"] = round(busy_s, 6)
        # device-idle fraction BETWEEN dispatches: of the window span,
        # how much had no host thread waiting on a result — an upper
        # bound on how idle the device was, from the host's side
        out["device_idle_fraction"] = round(
            max(0.0, 1.0 - busy_s / span_s), 6
        )
        # transfer-hidden split: a transfer interval's bytes count as
        # hidden in proportion to its overlap with device-busy time;
        # a zero-length "prefetch" interval (copy landed before the
        # drain even looked) is hidden by construction
        t_bytes = h_bytes = 0
        pf_bytes = 0
        for r in recs:
            for a, b, nb, kind in r.transfers:
                t_bytes += nb
                if kind == "prefetch":
                    pf_bytes += nb
                if b > a:
                    ov = _overlap_s(a, b, busy, busy_ends)
                    h_bytes += int(nb * ov / (b - a))
                elif kind == "prefetch":
                    h_bytes += nb
        out["transfer"] = {
            "bytes": t_bytes,
            "hidden_bytes": h_bytes,
            "serialized_bytes": t_bytes - h_bytes,
            "prefetch_bytes": pf_bytes,
            "transfer_hidden_fraction": (
                round(h_bytes / t_bytes, 6) if t_bytes else 0.0
            ),
        }
        # ring upload-avoidance savings (PR-12 parameter rings)
        hits = sum(int(r.marks.get("ring_hits", 0)) for r in recs)
        ups = sum(int(r.marks.get("ring_uploads", 0)) for r in recs)
        out["ring"] = {
            "hits": hits,
            "uploads": ups,
            "bytes_uploaded": sum(
                int(r.marks.get("ring_bytes", 0)) for r in recs
            ),
            "hit_fraction": (
                round(hits / (hits + ups), 6) if (hits + ups) else 0.0
            ),
        }
        out["prefetch"] = {
            "starts": sum(
                int(r.marks.get("prefetch_starts", 0)) for r in recs
            ),
            "hits": sum(
                int(r.marks.get("prefetch_hits", 0)) for r in recs
            ),
            "misses": sum(
                int(r.marks.get("prefetch_misses", 0)) for r in recs
            ),
        }
        # lane decomposition: time queued in the lane (enqueue →
        # device_dispatch), the collection window in force, and the
        # service time (device_dispatch → result_delivered)
        lane_q: List[float] = []
        lane_w: List[float] = []
        lane_s: List[float] = []
        paths: Dict[str, int] = {}
        for r in recs:
            paths[r.path] = paths.get(r.path, 0) + 1
            if r.path != "lane":
                continue
            ev = dict(r.events)
            dd = ev.get("device_dispatch")
            enq = ev.get("enqueue")
            if enq is not None and dd is not None:
                lane_q.append(max(0.0, dd - enq))
            if dd is not None and r.t_done is not None:
                lane_s.append(max(0.0, r.t_done - dd))
            w = r.marks.get("window_s")
            if w is not None:
                lane_w.append(float(w))

        def _mean_ms(xs: List[float]) -> Optional[float]:
            return round(sum(xs) / len(xs) * 1000.0, 3) if xs else None

        out["paths"] = paths
        if paths.get("lane"):
            out["lane"] = {
                "dispatches": paths["lane"],
                "queue_ms_mean": _mean_ms(lane_q),
                "window_ms_mean": _mean_ms(lane_w),
                "service_ms_mean": _mean_ms(lane_s),
            }
        # per-fingerprint: dispatches, device/transfer cost, its own
        # hidden fraction, and idle time between its dispatches
        by_fid: Dict[str, List[DispatchRecord]] = {}
        for r in recs:
            if r.fid is not None:
                by_fid.setdefault(r.fid, []).append(r)
        tops = sorted(
            by_fid.items(), key=lambda kv: -len(kv[1])
        )[: max(top_fingerprints, 0)]
        fps: Dict[str, Dict] = {}
        for fid, rs in tops:
            fb = _merge_intervals([iv for r in rs for iv in r.device])
            fb_s = sum(b - a for a, b in fb)
            f_lo = min(r.span()[0] for r in rs)
            f_hi = max(r.span()[1] for r in rs)
            f_span = max(f_hi - f_lo, 1e-9)
            tb = hb = 0
            for r in rs:
                for a, b, nb, kind in r.transfers:
                    tb += nb
                    if b > a:
                        ov = _overlap_s(a, b, busy, busy_ends)
                        hb += int(nb * ov / (b - a))
                    elif kind == "prefetch":
                        hb += nb
            fps[fid] = {
                "dispatches": len(rs),
                "device_s": round(fb_s, 6),
                "idle_fraction": round(
                    max(0.0, 1.0 - fb_s / f_span), 6
                ),
                "transfer_bytes": tb,
                "transfer_hidden_fraction": (
                    round(hb / tb, 6) if tb else 0.0
                ),
            }
        if fps:
            out["fingerprints"] = fps
        return out

    # -- Chrome-trace / Perfetto export -------------------------------------

    def chrome_trace(
        self, window_s: Optional[float] = None
    ) -> Dict[str, object]:
        """The window as Chrome-trace JSON (``traceEvents`` array form)
        — loadable by Perfetto (ui.perfetto.dev) and chrome://tracing.
        One lane (tid) per dispatch path plus its device and transfer
        sub-lanes; lifecycle events render as instants, device/transfer
        intervals and whole dispatches as complete ("X") slices."""
        from orientdb_tpu.obs.trace import span

        with span("timeline.export") as sp:
            recs = self._window(window_s)
            sp.set("records", len(recs))
            events: List[Dict] = [
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": 1,
                    "tid": 0,
                    "args": {"name": "orienttpu dispatch"},
                }
            ]
            tids: Dict[str, int] = {}

            def tid(lane: str) -> int:
                t = tids.get(lane)
                if t is None:
                    t = tids[lane] = len(tids) + 1
                    events.append(
                        {
                            "name": "thread_name",
                            "ph": "M",
                            "pid": 1,
                            "tid": t,
                            "args": {"name": lane},
                        }
                    )
                return t

            def us(t: float) -> float:
                return round(t * 1e6, 1)

            for r in recs:
                lo, hi = r.span()
                args = {
                    "seq": r.seq,
                    "fingerprint": r.fid,
                    "trace_id": r.trace_id,
                    "n": r.n,
                }
                if r.marks:
                    args.update(r.marks)
                events.append(
                    {
                        "name": f"{r.path} dispatch",
                        "cat": r.path,
                        "ph": "X",
                        "ts": us(lo),
                        "dur": max(round((hi - lo) * 1e6, 1), 1.0),
                        "pid": 1,
                        "tid": tid(r.path),
                        "args": args,
                    }
                )
                for name, t in r.events:
                    events.append(
                        {
                            "name": name,
                            "cat": r.path,
                            "ph": "i",
                            "s": "t",
                            "ts": us(t),
                            "pid": 1,
                            "tid": tid(r.path),
                            "args": {"seq": r.seq},
                        }
                    )
                for a, b in r.device:
                    events.append(
                        {
                            "name": "device",
                            "cat": r.path,
                            "ph": "X",
                            "ts": us(a),
                            "dur": max(round((b - a) * 1e6, 1), 1.0),
                            "pid": 1,
                            "tid": tid(f"{r.path}:device"),
                            "args": {"seq": r.seq},
                        }
                    )
                for a, b, nb, kind in r.transfers:
                    events.append(
                        {
                            "name": kind,
                            "cat": r.path,
                            "ph": "X",
                            "ts": us(a),
                            "dur": max(round((b - a) * 1e6, 1), 1.0),
                            "pid": 1,
                            "tid": tid(f"{r.path}:transfer"),
                            "args": {"seq": r.seq, "bytes": nb},
                        }
                    )
            return {
                "traceEvents": events,
                "displayTimeUnit": "ms",
                "otherData": {
                    "generator": "orientdb-tpu dispatch flight recorder",
                    "overlap": self._overlap(recs, 8),
                },
            }


#: the process-wide recorder (mirrors stats/tracer/alert singletons)
recorder = FlightRecorder()


# -- hot-path hooks (no-ops when no record is active) ------------------------


def mark(name: str, ts: Optional[float] = None) -> None:
    rec = current()
    if rec is not None:
        rec.add_event(name, ts)


def note_path(path: str) -> None:
    """Refine the active record's dispatch path from a deeper layer
    (``dispatch_many`` → group, a mesh plan's dispatch → sharded).
    "lane" is sticky: a lane drain that group-dispatches is still the
    coalesce path — the lane IS the story."""
    rec = current()
    if rec is not None and rec.path != "lane":
        rec.path = path


def note(key: str, value) -> None:
    rec = current()
    if rec is not None:
        rec.marks[key] = value


def add_phase(device_s: float, transfer_s: float, nbytes: int) -> None:
    """Called next to ``obs.stats.add_device`` with a fetch wave's
    device-sync/transfer split: anchors the intervals at *now* (the
    hook runs right after the wave ends), stamping the
    compute_done/transfer_start/transfer_done lifecycle events."""
    rec = current()
    if rec is None:
        return
    now = time.monotonic()
    t_mid = now - max(transfer_s, 0.0)
    if device_s > 0.0:
        rec.device.append((t_mid - device_s, t_mid))
    rec.add_event("compute_done", t_mid)
    if transfer_s > 0.0 or nbytes:
        rec.transfers.append((t_mid, now, int(nbytes), "fetch"))
        rec.add_event("transfer_start", t_mid)
        rec.add_event("transfer_done", now)


def add_transfer(
    t_start: float, t_end: float, nbytes: int, kind: str = "fetch"
) -> None:
    rec = current()
    if rec is not None:
        rec.transfers.append((t_start, t_end, int(nbytes), kind))


def note_fault(kind: str) -> None:
    """A classified device fault (exec/devicefault) crossed the active
    dispatch: stamp the lifecycle event and bump the per-kind mark so
    the flight recorder shows WHERE the ladder engaged."""
    rec = current()
    if rec is not None:
        rec.add_event("device_fault")
        rec.bump(f"device_fault.{kind}")


def note_ring(hit: bool, nbytes: int = 0) -> None:
    """ParamRing.stage outcome: a staged-slot reuse (zero host bytes)
    or a fresh explicit upload."""
    rec = current()
    if rec is None:
        return
    if hit:
        rec.bump("ring_hits")
        rec.add_event("ring_hit")
    else:
        rec.bump("ring_uploads")
        rec.bump("ring_bytes", int(nbytes))
        rec.add_event("param_upload")


def note_prefetch_start() -> None:
    rec = current()
    if rec is None:
        return
    now = time.monotonic()
    rec.bump("prefetch_starts")
    rec.marks["prefetch_start_ts"] = now
    rec.add_event("prefetch_start", now)


def note_prefetch(hit: bool, nbytes: int = 0) -> None:
    """Page-election outcome. A HIT means the elected page's copy has
    been in flight since dispatch — record that transfer as spanning
    dispatch → election, i.e. overlapped with the device work in front
    of it (kind "prefetch"), which is exactly the hidden-bytes claim
    the accounting pass scores."""
    rec = current()
    if rec is None:
        return
    if hit:
        rec.bump("prefetch_hits")
        now = time.monotonic()
        start = float(
            rec.marks.get("prefetch_start_ts") or rec.t0
        )
        rec.transfers.append((start, now, int(nbytes), "prefetch"))
    else:
        rec.bump("prefetch_misses")


# -- scrape-time gauges ------------------------------------------------------


#: publish_overlap_gauges recompute floor: the overlap pass over a full
#: ring costs ~10ms of host time, and the provider runs inside EVERY
#: registry.snapshot_all() — a fast-ticking watchdog (tests tick at
#: 50Hz; production every few seconds) must not pay it per tick. 250ms
#: keeps /metrics effectively live while bounding the cost at any rate.
_PUBLISH_MIN_INTERVAL_S = 0.25
_publish_last_ts = 0.0


def publish_overlap_gauges() -> None:
    """Refresh the ``orienttpu_overlap_*`` gauges from a bounded recent
    window (``config.timeline_window_s``). Registered as a scrape-time
    gauge provider (obs/profile), so ``/metrics``, the member-labeled
    ``/cluster/metrics`` fan-in, and every alert-engine snapshot carry
    them without any hot-path cost. Recomputes at most once per
    ``_PUBLISH_MIN_INTERVAL_S`` (consumers in between read the prior
    gauge values — a racy double recompute is harmless)."""
    from orientdb_tpu.utils.metrics import metrics

    global _publish_last_ts
    now = time.monotonic()
    if now - _publish_last_ts < _PUBLISH_MIN_INTERVAL_S:
        return
    _publish_last_ts = now
    # span-FREE accounting: this provider runs inside EVERY
    # registry.snapshot_all() (scrapes, watchdog ticks, bundles) — a
    # span here would stamp the tracer ring on every scrape and poison
    # the alert plane's newest-span exemplar fallback. The explicit
    # surfaces (overlap()/chrome_trace()) keep their cataloged spans.
    rep = recorder._overlap(
        recorder._window(config.timeline_window_s), 8
    )
    metrics.gauge("overlap.window_records", float(rep.get("records", 0)))
    if not rep.get("records"):
        # window emptied (traffic stopped): DROP the fraction gauges
        # rather than freeze their last values — a scrape must never
        # read a stale idle fraction as live data (0.0 would fabricate
        # "fully busy"; absence is the honest shape)
        metrics.drop_gauge("overlap.device_idle_fraction")
        metrics.drop_gauge("overlap.transfer_hidden_fraction")
        metrics.drop_gauge("overlap.ring_hit_fraction")
        return
    metrics.gauge(
        "overlap.device_idle_fraction",
        float(rep.get("device_idle_fraction", 0.0)),
    )
    tr = rep.get("transfer") or {}
    metrics.gauge(
        "overlap.transfer_hidden_fraction",
        float(tr.get("transfer_hidden_fraction", 0.0)),
    )
    ring = rep.get("ring") or {}
    metrics.gauge(
        "overlap.ring_hit_fraction", float(ring.get("hit_fraction", 0.0))
    )


def _register_provider() -> None:
    from orientdb_tpu.obs.profile import register_gauge_provider

    register_gauge_provider(publish_overlap_gauges)


_register_provider()

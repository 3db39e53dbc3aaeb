"""Critical-path attribution: per-request latency decomposition.

The flight recorder (obs/timeline) can say *that* a dispatch was slow;
this plane says *which segment of the request's life* grew. Every
sampled request becomes a waterfall of named segments — admission →
parse → queue (lane window) → plan_resolve → param_upload|ring_hit →
device_compute|host_compute → result_transfer → marshal → flush — by
joining the existing per-query accumulator (obs/stats ``_Acc``: device,
transfer, queue, compile attribution) with stamps threaded through the
previously unstamped edges: admission entry (server/admission), request
parse and response marshal/flush (server/binary_server,
server/http_server), the oracle interpreter (exec/engine), retry sleep
in the device-fault ladder (exec/devicefault), and lane collection
(server/coalesce — per-item segments ride the items back to their
submitting sessions).

Aggregation (all at :func:`commit`, never mid-request):

- a bounded ring of recent decompositions (``critpath_capacity``);
- process counters ``critpath.<segment>_us`` and ``critpath.requests``
  (``utils/metrics``), so a window's decomposition is a counter delta;
- per-fingerprint cumulative segment columns riding the PR-4 stats
  table (:meth:`obs.stats.QueryStats.record_segments`);
- per-``SloClass`` cumulative breakdowns with a dominant-bottleneck
  rollup (class membership installed by :func:`register_slo_classes`
  from ``obs/slo``; unmapped fingerprints aggregate as
  ``unclassified``);
- a per-fingerprint sliding window feeding :meth:`CritPathPlane.blame`
  — the ``latency_regression`` alert's blame annotation: diff the
  recent window's mean breakdown against the older history and name
  the segment(s) that grew, with the worst recent request's trace id
  as exemplar.

Surfaces: ``GET /stats/critpath``, the debug bundle's ``critpath``
section, and the console's ``CRITPATH [k]``.

Accounting invariant: :func:`commit` folds any unattributed residual
(request wall minus the stamped segments) into ``host_compute``, so a
decomposition's segment sum always equals the measured wall latency —
nothing hides between segments. Segments stamped from worker threads
(lane device/transfer shares) are amortized sub-intervals of the
submitter's wait, so the residual stays non-negative in practice.

``critpathlint`` (orientdb_tpu/analysis) fails the build when a
``segment(...)``/``add_segment(...)`` stamp site names something not in
:data:`SEGMENT_CATALOG`, or a catalog entry has no stamp site left.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Dict, Iterable, List, Optional

from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics

#: segment name -> what it measures. The decomposition vocabulary in
#: one place: ``critpathlint`` cross-checks every literal stamp site
#: against this dict, and the README's segment-catalog table renders
#: from the same entries — the two planes cannot drift.
SEGMENT_CATALOG: Dict[str, str] = {
    "admission": "admission-control pressure check and shed wait "
    "(server/admission.db_pressure)",
    "parse": "request envelope/frame parse on the wire listener "
    "(binary frame JSON decode, HTTP body decode)",
    "queue": "time parked before execution: coalesce lane queue + "
    "collection window, batch queue waits",
    "plan_resolve": "statement parse/plan/compile resolution before "
    "dispatch (recording executions ARE the compile cost)",
    "param_upload": "host->device parameter staging (jax.device_put "
    "of the dynamic args; a ParamRing miss)",
    "ring_hit": "device-resident ParamRing slot match — parameters "
    "reused in place, ~zero host bytes shipped",
    "device_compute": "the host's wait for the device's result (the "
    "dispatch's sync share of the profiled fetch waves): device "
    "execution plus whatever ran on the device ahead of it",
    "host_compute": "host-side execution: the oracle interpreter, "
    "plus any request wall time no other segment claimed",
    "result_transfer": "device->host result fetch (the profiled "
    "transfer share)",
    "fault_retry": "device-fault ladder overhead: retry backoff sleep "
    "and failed attempts before the one that succeeded",
    "marshal": "result materialization/serialization (rows to dicts, "
    "response JSON encode)",
    "flush": "response frame/body write to the socket",
}

#: fingerprint windows kept for blame (LRU past this)
_FID_WINDOWS_MAX = 256

#: minimum per-fingerprint history before blame will diff windows
_BLAME_MIN_HISTORY = 8

#: absolute per-segment growth floor (seconds) below which a diff is
#: jitter, not blame — mirrors the alert plane's _MAD_FLOOR_S scale
_BLAME_FLOOR_S = 5e-4


class CritPath:
    """One sampled request's decomposition under construction."""

    __slots__ = ("kind", "sql", "trace_id", "t0", "ts", "wall_s",
                 "segs", "error", "stats_recorded")

    def __init__(self, kind: str, sql: Optional[str] = None) -> None:
        self.kind = kind
        self.sql = sql
        self.trace_id: Optional[str] = None
        self.t0 = time.monotonic()
        self.ts = 0.0  # stamped at commit (off the begin hot path)
        self.wall_s = 0.0
        self.segs: Dict[str, float] = {}
        self.error = False
        #: True when the execution path already wrote this request's
        #: (amortized) segment columns into the stats table — commit
        #: must not overwrite them with the full-batch split
        self.stats_recorded = False

    def add(self, name: str, seconds: float) -> None:
        if seconds > 0.0:
            self.segs[name] = self.segs.get(name, 0.0) + seconds

    #: held-record stamp: same contract as the module-level
    #: add_segment (critpathlint treats both spellings as stamp
    #: sites), minus the thread-local lookup a caller that already
    #: owns the record would pay for nothing
    add_segment = add

    def total(self) -> float:
        return sum(self.segs.values())

    def to_dict(self) -> Dict[str, object]:
        return {
            "kind": self.kind,
            "sql": self.sql,
            "trace_id": self.trace_id,
            "ts": round(self.ts, 3),
            "wall_ms": round(self.wall_s * 1000.0, 3),
            "segments_ms": {
                k: round(v * 1000.0, 3)
                for k, v in sorted(
                    self.segs.items(), key=lambda kv: -kv[1]
                )
            },
            "error": self.error,
        }


# -- thread-local record stack (mirrors timeline's active-record idiom) ------

_local = threading.local()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current() -> Optional[CritPath]:
    st = getattr(_local, "stack", None)
    return st[-1] if st else None


class active:
    """Make ``cp`` the thread's stamping target for a block. Pushing
    None is a no-op pair, so sampled-out paths stay branch-free."""

    __slots__ = ("cp",)

    def __init__(self, cp: Optional[CritPath]) -> None:
        self.cp = cp

    def __enter__(self) -> Optional[CritPath]:
        if self.cp is not None:
            _stack().append(self.cp)
        return self.cp

    def __exit__(self, *exc) -> None:
        if self.cp is not None:
            st = _stack()
            if st and st[-1] is self.cp:
                st.pop()
            else:  # unbalanced (should not happen): drop, don't corrupt
                try:
                    st.remove(self.cp)
                except ValueError:
                    pass


def begin_request(kind: str, sql: Optional[str] = None) -> Optional[CritPath]:
    """Open a decomposition for one request, or None when the plane is
    disabled or the request sampled out. Sampling rides the stats
    plane's rate (``stats_sample_rate``), so a committed decomposition
    joins the same query subset as stats/slowlog/traces."""
    from orientdb_tpu.obs.stats import sampled

    if not config.critpath_enabled or not sampled():
        return None
    cp = CritPath(kind, sql)
    from orientdb_tpu.obs.trace import current_trace_id

    cp.trace_id = current_trace_id()
    return cp


class segment:
    """Time a block into the thread's active record: ``with
    segment("parse"): ...``. No active record (sampled out, or a
    client-side caller of a shared helper) costs one thread-local
    read."""

    __slots__ = ("name", "_cp", "_t0")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "segment":
        self._cp = current()
        if self._cp is not None:
            self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        cp = self._cp
        if cp is not None:
            cp.add(self.name, time.monotonic() - self._t0)
            if cp.trace_id is None:
                from orientdb_tpu.obs.trace import current_trace_id

                cp.trace_id = current_trace_id()


def add_segment(name: str, seconds: float) -> None:
    """Fold measured seconds into the active record's segment — the
    non-context-manager stamp for sites that already hold a duration
    (the device-fault ladder's retry overhead, ring staging)."""
    cp = current()
    if cp is not None and seconds > 0.0:
        cp.add(name, seconds)
        if cp.trace_id is None:
            from orientdb_tpu.obs.trace import current_trace_id

            cp.trace_id = current_trace_id()


def merge(segs: Optional[Dict[str, float]]) -> None:
    """Fold a worker-thread-built segment dict into the active record —
    how a coalesce lane item's amortized decomposition (built on the
    lane worker) reaches its submitting session's request record."""
    cp = current()
    if cp is None or not segs:
        return
    for k, v in segs.items():
        cp.add(k, v)
    if cp.trace_id is None:
        from orientdb_tpu.obs.trace import current_trace_id

        cp.trace_id = current_trace_id()


def note_sql(sql: Optional[str]) -> None:
    """Attach the statement to a record opened before the SQL was known
    (the wire listeners open the record at frame arrival)."""
    cp = current()
    if cp is not None and sql and cp.sql is None:
        cp.sql = sql


class request:
    """Open-or-join front-door helper: when a record is already active
    on this thread (the wire listener opened it), yield that record and
    leave its lifecycle to the opener; otherwise begin + activate a new
    one and commit it on exit — embedded/bench callers of the engine
    front doors get attribution without a server in front."""

    __slots__ = ("kind", "sql", "_cp", "_owned")

    def __init__(self, kind: str, sql: Optional[str] = None) -> None:
        self.kind = kind
        self.sql = sql
        self._owned = False

    def __enter__(self) -> Optional[CritPath]:
        cp = current()
        if cp is not None:
            if self.sql and cp.sql is None:
                cp.sql = self.sql
            self._cp = cp
            return cp
        cp = begin_request(self.kind, self.sql)
        self._cp = cp
        if cp is not None:
            self._owned = True
            _stack().append(cp)
        return cp

    def __exit__(self, exc_type, *exc) -> None:
        if not self._owned:
            return
        cp = self._cp
        st = _stack()
        if st and st[-1] is cp:
            st.pop()
        else:
            try:
                st.remove(cp)
            except ValueError:
                pass
        if exc_type is not None:
            cp.error = True
        commit(cp)


def fold_query(
    cp: Optional[CritPath],
    duration_s: float,
    acc,
    stamped_before: float,
) -> None:
    """Map one finished engine execution onto catalog segments: the
    stats accumulator carries the profiled device/transfer/queue/
    compile attribution; whatever the engine window's wall clock holds
    beyond those AND beyond segments stamped during the window
    (``fault_retry``, the oracle's ``host_compute``) is host execution.
    ``stamped_before`` is ``cp.total()`` at engine entry, so nested
    front doors never double-claim each other's stamps."""
    if cp is None:
        return
    # stamp the held record directly — the caller owns cp, so the
    # thread-local current() lookup the module-level add_segment pays
    # is pure overhead here (commit's fallback covers the trace id)
    if acc is not None:
        cp.add_segment("queue", acc.queue_s)
        cp.add_segment("plan_resolve", acc.compile_s)
        cp.add_segment("device_compute", acc.device_s)
        cp.add_segment("result_transfer", acc.transfer_s)
    stamped_in_window = cp.total() - stamped_before
    cp.add_segment("host_compute", duration_s - stamped_in_window)


class _FidWindow:
    """One fingerprint's recent decompositions — the blame evidence."""

    __slots__ = ("text", "hist", "count", "wall_s", "segs")

    def __init__(self, text: str) -> None:
        self.text = text
        #: (wall_s, segs, trace_id), newest last
        self.hist: deque = deque(maxlen=128)
        self.count = 0
        self.wall_s = 0.0
        self.segs: Dict[str, float] = {}


class _ClassAgg:
    __slots__ = ("count", "wall_s", "segs")

    def __init__(self) -> None:
        self.count = 0
        self.wall_s = 0.0
        self.segs: Dict[str, float] = {}


def _dominant(segs: Dict[str, float]) -> Optional[str]:
    return max(segs, key=segs.get) if segs else None


class CritPathPlane:
    """Process-wide aggregation: ring + per-fid blame windows +
    per-SLO-class cumulative breakdowns. Written only at
    :meth:`commit` (one short lock per sampled request), read by the
    HTTP/console/bundle surfaces and the alert plane's blame hook."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        self._lock = threading.Lock()
        self._ring: deque = deque()
        #: None = read config.critpath_capacity live per commit
        self._capacity = capacity
        self._by_fid: "OrderedDict[str, _FidWindow]" = OrderedDict()
        self._class_of: Dict[str, str] = {}
        self._by_class: Dict[str, _ClassAgg] = {}
        self._committed = 0
        self._totals: Dict[str, float] = {}

    def _cap(self) -> int:
        return (
            self._capacity
            if self._capacity is not None
            else int(config.critpath_capacity)
        )

    # -- write side ----------------------------------------------------------

    def commit(self, cp: Optional[CritPath]) -> None:
        """Seal one record: stamp wall, fold the unattributed residual
        into ``host_compute`` (the segment sum == wall invariant), and
        aggregate. A record never committed (an abandoned pipelined
        frame) simply never enters any surface."""
        if cp is None:
            return
        cp.wall_s = time.monotonic() - cp.t0
        cp.ts = time.time()  # deferred from begin: one clock read here
        residual = cp.wall_s - cp.total()
        if residual > 0.0:
            add = cp.segs.get("host_compute", 0.0) + residual
            cp.segs["host_compute"] = add
        if cp.trace_id is None:
            from orientdb_tpu.obs.trace import current_trace_id

            cp.trace_id = current_trace_id()
        fid = text = None
        if cp.sql:
            from orientdb_tpu.obs.stats import fingerprint_cached, stats

            fp = fingerprint_cached(cp.sql)
            fid, text = fp.fid, fp.text
            # per-fingerprint cumulative segment columns ride the PR-4
            # stats accumulator table (sampling already decided at
            # begin_request — record_segments must not thin it again)
            if not cp.stats_recorded:
                stats.record_segments(cp.sql, cp.segs)
        # the same numbers as window counters (whole microseconds): a
        # reader of utils.metrics sees the decomposition of any window
        # by difference, with no handle on this plane
        fold = {
            f"critpath.{k}_us": round(v * 1e6)
            for k, v in cp.segs.items()
        }
        fold["critpath.requests"] = 1
        metrics.incr_many(fold)
        cap = self._cap()
        with self._lock:
            self._committed += 1
            for k, v in cp.segs.items():
                self._totals[k] = self._totals.get(k, 0.0) + v
            if cap > 0:
                # store the record itself; recent() renders at read
                # time so the hot path skips the dict build entirely
                self._ring.append(cp)
                while len(self._ring) > cap:
                    self._ring.popleft()
            cls = "unclassified"
            if fid is not None:
                w = self._by_fid.get(fid)
                if w is None:
                    while len(self._by_fid) >= _FID_WINDOWS_MAX:
                        self._by_fid.popitem(last=False)
                    w = self._by_fid[fid] = _FidWindow(text or "")
                else:
                    self._by_fid.move_to_end(fid)
                w.hist.append((cp.wall_s, dict(cp.segs), cp.trace_id))
                w.count += 1
                w.wall_s += cp.wall_s
                for k, v in cp.segs.items():
                    w.segs[k] = w.segs.get(k, 0.0) + v
                cls = self._class_of.get(fid, "unclassified")
            agg = self._by_class.get(cls)
            if agg is None:
                agg = self._by_class[cls] = _ClassAgg()
            agg.count += 1
            agg.wall_s += cp.wall_s
            for k, v in cp.segs.items():
                agg.segs[k] = agg.segs.get(k, 0.0) + v

    def register_classes(self, mapping: Dict[str, str]) -> None:
        """Install fingerprint -> SloClass-name membership (called by
        ``obs/slo`` when a spec begins; later registrations win)."""
        with self._lock:
            self._class_of.update(mapping)

    # -- blame (the latency_regression annotation) ---------------------------

    def blame(self, fid: str) -> Optional[Dict[str, object]]:
        """Diff the fingerprint's recent window against its older
        history: which segment(s) grew, and the worst recent request's
        trace id as exemplar. None when the history is too thin to
        split into baseline + current windows."""
        with self._lock:
            w = self._by_fid.get(fid)
            items = list(w.hist) if w is not None else []
        if len(items) < _BLAME_MIN_HISTORY:
            return None
        cut = max(4, len(items) // 4)
        recent, older = items[-cut:], items[:-cut]
        if not older:
            return None

        def _mean_segs(rows) -> Dict[str, float]:
            out: Dict[str, float] = {}
            for _wall, segs, _tid in rows:
                for k, v in segs.items():
                    out[k] = out.get(k, 0.0) + v
            return {k: v / len(rows) for k, v in out.items()}

        cur = _mean_segs(recent)
        base = _mean_segs(older)
        ratio = max(float(config.critpath_blame_ratio), 0.0)
        grown: List[Dict[str, float]] = []
        for seg in sorted(set(cur) | set(base)):
            c, b = cur.get(seg, 0.0), base.get(seg, 0.0)
            if c - b > max(b * ratio, _BLAME_FLOOR_S):
                grown.append(
                    {
                        "segment": seg,
                        "base_ms": round(b * 1000.0, 3),
                        "cur_ms": round(c * 1000.0, 3),
                        "delta_ms": round((c - b) * 1000.0, 3),
                    }
                )
        if not grown:
            return None
        grown.sort(key=lambda g: -g["delta_ms"])
        worst = max(
            recent, key=lambda row: row[0]
        )  # (wall, segs, trace) — worst wall carries the exemplar
        return {
            "segments": grown,
            "top": grown[0]["segment"],
            "trace_id": worst[2],
        }

    # -- read side -----------------------------------------------------------

    def totals(self) -> Dict[str, float]:
        """Cumulative seconds per segment across every committed
        record — the bench headline differences two of these around a
        timed block for its per-segment extras."""
        with self._lock:
            return dict(self._totals)

    def recent(self, k: int = 50) -> List[Dict]:
        with self._lock:
            items = list(self._ring)
        return [c.to_dict() for c in items[-max(k, 0):][::-1]]

    def report(self, k: int = 20) -> Dict[str, object]:
        """The ``GET /stats/critpath`` document: per-class rollups with
        dominant bottleneck, top fingerprints by cumulative wall, and
        the most recent decompositions."""
        with self._lock:
            classes = {
                name: {
                    "requests": agg.count,
                    "wall_ms_mean": round(
                        agg.wall_s * 1000.0 / agg.count, 3
                    ) if agg.count else 0.0,
                    "segments_ms_mean": {
                        s: round(v * 1000.0 / agg.count, 3)
                        for s, v in sorted(
                            agg.segs.items(), key=lambda kv: -kv[1]
                        )
                    } if agg.count else {},
                    "dominant": _dominant(agg.segs),
                }
                for name, agg in self._by_class.items()
            }
            fids = [
                {
                    "fingerprint": fid,
                    "query": w.text,
                    "requests": w.count,
                    "wall_ms_mean": round(
                        w.wall_s * 1000.0 / w.count, 3
                    ) if w.count else 0.0,
                    "segments_ms_mean": {
                        s: round(v * 1000.0 / w.count, 3)
                        for s, v in sorted(
                            w.segs.items(), key=lambda kv: -kv[1]
                        )
                    } if w.count else {},
                    "dominant": _dominant(w.segs),
                    "wall_s_total": w.wall_s,
                }
                for fid, w in self._by_fid.items()
            ]
            committed = self._committed
        fids.sort(key=lambda r: -r.pop("wall_s_total"))
        return {
            "ts": round(time.time(), 3),
            "enabled": bool(config.critpath_enabled),
            "requests": committed,
            "segment_catalog": dict(SEGMENT_CATALOG),
            "by_class": classes,
            "fingerprints": fids[: max(k, 0)],
            "recent": self.recent(min(max(k, 0), 20)),
        }

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._by_fid.clear()
            self._by_class.clear()
            self._class_of.clear()
            self._committed = 0
            self._totals.clear()


#: the process-wide plane (mirrors stats/tracer/recorder singletons)
plane = CritPathPlane()


def commit(cp: Optional[CritPath]) -> None:
    plane.commit(cp)


def register_slo_classes(classes: Iterable) -> None:
    """Map every SloClass's fingerprints to its name for the per-class
    rollup (``obs/slo`` calls this when a spec's run begins)."""
    mapping: Dict[str, str] = {}
    for cls in classes:
        try:
            for fid in cls.fids():
                mapping[fid] = cls.name
        except Exception:  # a malformed class must not kill the run
            continue
    if mapping:
        plane.register_classes(mapping)

"""Device fault domain: contain, degrade, and recover from device-side
failures on every dispatch path.

PR 3 hardened every *host-side* channel (chaos points, retries,
breakers, admission shedding) but the device itself stayed a single
point of failure: an XLA runtime error, a device OOM, or a failed D2H
transfer mid-dispatch escaped as an unclassified exception — no retry,
no degradation, no quarantine. This module closes that hole with one
**escalation ladder** wrapped around every dispatch path (compiled
single, vmapped group, coalesce lanes, sharded mesh, tiered prefetch,
delta apply — compaction is this ladder's *actuator*, reached through
the overlay poison machinery):

1. **classify** — every exception crossing a device boundary becomes
   ``oom`` / ``transient`` / ``persistent`` (``device.fault.*``
   counters; ``SimulatedCrash``, the engines' own control-flow
   exceptions and trace-time program errors pass through untouched);
2. **retry** — transients re-dispatch under the PR-3
   :class:`~orientdb_tpu.parallel.resilience.RetryPolicy` (bounded
   attempts + budget);
3. **relieve** — an OOM actuates memory-pressure relief before its
   retry, memledger-guided by owner taxonomy: evict tier-pool blocks
   (PR 16), poison the delta overlay so the maintainer compacts its
   slabs (PR 15), and drop the coalesce lanes' device param rings
   (PR 12);
4. **quarantine** — a plan whose faults survive the retries is
   quarantined by stats-plane fingerprint: the engine front doors
   route it to the oracle (riding the coalesce poison-fallback
   machinery) for a TTL, then admit ONE probe; a clean probe
   re-admits, a failed one doubles the TTL;
5. **shed** — when relief leaves the memledger total above the
   headroom fraction of ``tier_hbm_cap_bytes`` (or an OOM survives
   relief), the admission plane (``server/admission.db_pressure``)
   sheds writes with 503 + Retry-After for ``devicefault_shed_s`` —
   the server degrades loudly instead of OOM-crashing.

Injectable end to end: the ``tpu.dispatch`` / ``tpu.transfer`` /
``tpu.oom`` chaos points cross inside the wrapped sections, so a
seeded :class:`~orientdb_tpu.chaos.faults.FaultPlan` drives the whole
ladder deterministically in tests. Observable end to end: the
``devicefault.escalate`` span, the ``device_fault_storm`` alert rule,
quarantine state in ``/cluster/health`` and the debug bundle, and fault
events on the flight-recorder timeline.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from orientdb_tpu.chaos.faults import fault
from orientdb_tpu.ops.predicates import Uncompilable
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import get_logger
from orientdb_tpu.utils.metrics import metrics

log = get_logger("devicefault")

#: classification kinds (the ``device.fault.<kind>`` counter suffixes)
OOM = "oom"
TRANSIENT = "transient"
PERSISTENT = "persistent"
#: parity conviction (exec/audit): the plan ran fine but served rows
#: the shadow oracle disagrees with — wrong answers, not crashes
PARITY = "parity"
#: not a device fault at all: an error raised while TRACING or LOWERING
#: the program (a bug in the program). The guard re-raises it as is —
#: never retried, quarantined or served from the oracle.
PROGRAM = "program"


class DeviceFaultError(OSError):
    """A classified device-side failure.

    OSError on purpose: the PR-3 retry surfaces (client failover, the
    guard's own policy) already treat OSError as the retryable family.
    ``retry_after`` is set when the quarantine/shed machinery knows how
    long degraded mode lasts — the binary server forwards it as a
    503-style hint and :class:`client.remote.DeviceTransientError`
    honors it."""

    def __init__(
        self, msg: str, kind: str = TRANSIENT,
        retry_after: Optional[float] = None,
    ) -> None:
        super().__init__(msg)
        self.kind = kind
        self.retry_after = retry_after


class DeviceOomError(DeviceFaultError):
    """Device memory exhaustion (classified ``oom``)."""

    def __init__(self, msg: str, retry_after: Optional[float] = None):
        super().__init__(msg, kind=OOM, retry_after=retry_after)


class _PersistentFault(DeviceFaultError):
    """Internal: a fault classified persistent — retrying cannot help,
    the policy gives up immediately and escalation quarantines."""


class DeviceQuarantined(Uncompilable):
    """Raised out of a guarded dispatch path when the ladder exhausted
    its rungs. Subclasses ``Uncompilable`` deliberately: every engine
    front door already converts that into a per-statement oracle
    fallback, and the coalesce lanes' batch-failure machinery re-runs
    members through those front doors — so degraded mode rides the
    existing poison-fallback plumbing instead of a parallel one."""

    def __init__(self, msg: str, retry_after: Optional[float] = None):
        super().__init__(msg)
        self.retry_after = retry_after


# -- classification ----------------------------------------------------------

#: message fragments (lowercased) that mark device memory exhaustion —
#: XLA's RESOURCE_EXHAUSTED family plus the chaos point's own name, so
#: a plain ``error`` rule at ``tpu.oom`` classifies without a custom
#: error factory
_OOM_MARKERS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "allocat",
    "hbm",
    "tpu.oom",
)

#: fragments that mark a *structurally* broken executable — retrying
#: the same program cannot succeed, so the ladder skips straight to
#: quarantine
_PERSISTENT_MARKERS = (
    "invalid_argument",
    "invalid argument",
    "unimplemented",
    "failed_precondition",
)


#: what JAX raises while tracing or lowering (``jax.errors.JAXTypeError``
#: is a ``TypeError``); the runtime's own errors are ``RuntimeError``s
_TRACE_ERRORS = (TypeError, ValueError, NotImplementedError)


def classify(exc: BaseException) -> str:
    """``oom`` / ``persistent`` / ``transient`` / ``program`` for an
    exception caught at a device dispatch/fetch boundary. An exception
    that carries XLA status text is classified by it; otherwise a
    Python ``TypeError`` / ``ValueError`` / ``NotImplementedError`` was
    raised while tracing or lowering and is a bug in the program
    (``program``: the guard re-raises it untouched). Anything else that
    crossed the boundary is device-side, and the default is
    ``transient``: retry is the cheapest rung, and a persistent
    conviction also arrives via retry exhaustion."""
    if isinstance(exc, DeviceFaultError):
        return exc.kind
    msg = f"{type(exc).__name__}: {exc}".lower()
    if any(m in msg for m in _OOM_MARKERS):
        return OOM
    if any(m in msg for m in _PERSISTENT_MARKERS):
        return PERSISTENT
    if isinstance(exc, _TRACE_ERRORS):
        return PROGRAM
    return TRANSIENT


# -- quarantine entries ------------------------------------------------------


class _Quarantine:
    __slots__ = (
        "fid", "sql", "kind", "reason", "since", "until", "strikes",
        "probe_ts",
    )

    def __init__(self, fid, sql, kind, reason, now, ttl) -> None:
        self.fid = fid
        self.sql = sql
        self.kind = kind
        self.reason = reason
        self.since = now
        self.until = now + ttl
        self.strikes = 1
        #: monotonic ts of the in-flight probe (None = no probe out);
        #: a probe that never reports back expires after one TTL so a
        #: lost probe cannot wedge the entry in quarantine forever
        self.probe_ts: Optional[float] = None

    def row(self, now: float) -> Dict:
        return {
            "fingerprint": self.fid,
            "sql": (self.sql or "")[:120],
            "kind": self.kind,
            "reason": self.reason[:200],
            "age_s": round(now - self.since, 3),
            "ttl_s": round(max(0.0, self.until - now), 3),
            "strikes": self.strikes,
            "probing": self.probe_ts is not None,
        }


# -- the domain --------------------------------------------------------------


class DeviceFaultDomain:
    """Process-wide device fault state (mirrors ``metrics``/``stats``):
    the guard (:meth:`run`), the quarantine registry the engine front
    doors consult (:meth:`admit`), and the admission-plane shed latch
    (:meth:`shed_state`)."""

    def __init__(self) -> None:
        self._mu = threading.RLock()
        self._q: Dict[int, _Quarantine] = {}
        #: classified fault counts by kind (process lifetime)
        self._faults: Dict[str, int] = {}
        self._reliefs: Dict[str, int] = {}
        self._retries = 0
        self._quarantines = 0
        self._readmitted = 0
        self._oracle_served = 0
        self._probes = 0
        self._sheds = 0
        self._shed_until = 0.0
        self._shed_reason: Optional[str] = None

    # -- admission (engine front doors) --------------------------------------

    def _fid(self, sql: Optional[str]) -> Optional[int]:
        if not sql:
            return None
        from orientdb_tpu.obs.stats import fingerprint_cached

        return fingerprint_cached(sql).fid

    def admit(self, sql: Optional[str]) -> Optional[str]:
        """Gate one statement's compiled dispatch: ``None`` = clear,
        ``"quarantined"`` = serve the oracle, ``"probe"`` = THIS call
        holds the re-admission probe (report back via
        :meth:`note_success`, or the next fault re-quarantines). The
        no-quarantine fast path is one attribute read."""
        if not self._q:
            return None
        fid = self._fid(sql)
        if fid is None:
            return None
        now = time.monotonic()
        with self._mu:
            e = self._q.get(fid)
            if e is None:
                return None
            if now < e.until or (
                e.probe_ts is not None
                and now - e.probe_ts < self._ttl()
            ):
                # still serving time, or another probe is in flight
                self._oracle_served += 1
                metrics.incr("device.fault.quarantine.oracle")
                return "quarantined"
            e.probe_ts = now
            self._probes += 1
            metrics.incr("device.fault.probe")
            return "probe"

    def note_success(self, sql: Optional[str]) -> None:
        """A probe dispatch completed cleanly: re-admit the plan."""
        if not self._q:
            return
        fid = self._fid(sql)
        with self._mu:
            e = self._q.get(fid) if fid is not None else None
            if e is None or e.probe_ts is None:
                return
            del self._q[fid]
            self._readmitted += 1
        metrics.incr("device.fault.readmitted")
        metrics.gauge("device.fault.quarantined", float(len(self._q)))
        log.info("device fault quarantine lifted (probe ok): %s", sql)

    # -- the guard -----------------------------------------------------------

    def run(
        self,
        fn: Callable,
        *,
        db=None,
        sql: Optional[str] = None,
        stage: str = "dispatch",
        passthrough: Tuple[type, ...] = (),
        tier=None,
    ):
        """Run one device dispatch/fetch section under the escalation
        ladder. ``passthrough`` names the caller's control-flow
        exceptions (``ScheduleOverflow``); ``Uncompilable`` and
        ``SimulatedCrash`` always pass through, and so does a trace-time
        program error (``classify`` → ``program``). Exhaustion raises
        :class:`DeviceQuarantined` (an ``Uncompilable``) — zero
        unclassified device exceptions escape."""
        import time as _time

        from orientdb_tpu.parallel.resilience import (
            RetryBudgetExceeded,
            RetryPolicy,
        )

        give_up = (Uncompilable,) + tuple(passthrough)
        relief_done: List[str] = []
        # fault_retry attribution: retry backoff sleep + failed attempts
        # must not masquerade as device-compute growth in the critical-
        # path blame diff, so everything run() spends beyond the single
        # SUCCESSFUL attempt is stamped as its own segment
        t_run0 = _time.perf_counter()
        last_attempt_s = [0.0]
        n_attempts = [0]

        def _attempt():
            n_attempts[0] += 1
            t_a = _time.perf_counter()
            try:
                out = fn()
                last_attempt_s[0] = _time.perf_counter() - t_a
                return out
            except give_up:
                raise
            except Exception as e:
                # SimulatedCrash is a BaseException: it unwinds through
                # here untouched, like a real SIGKILL would
                kind = classify(e)
                if kind == PROGRAM:
                    raise
                self._record_fault(kind, stage, e)
                if kind == OOM and not relief_done:
                    # relief BEFORE the retry, once per guarded section
                    relief_done.extend(self.relieve(db, tier=tier))
                if kind == PERSISTENT:
                    raise _PersistentFault(
                        f"{stage}: {type(e).__name__}: {e}", kind=kind
                    ) from e
                with self._mu:
                    self._retries += 1
                raise DeviceFaultError(
                    f"{stage}: {type(e).__name__}: {e}", kind=kind
                ) from e

        policy = RetryPolicy(
            attempts=max(1, int(config.devicefault_retry_attempts)),
            base_s=0.01,
            cap_s=0.25,
            budget_s=float(config.devicefault_retry_budget_s),
        )
        try:
            out = policy.call(
                _attempt,
                retry_on=(DeviceFaultError,),
                give_up_on=give_up + (_PersistentFault,),
            )
        except give_up:
            raise
        except (_PersistentFault, RetryBudgetExceeded) as e:
            import orientdb_tpu.obs.critpath as _CP

            # exhaustion: the whole guarded section was retry churn
            _CP.add_segment(
                "fault_retry", _time.perf_counter() - t_run0
            )
            cause = e if isinstance(e, DeviceFaultError) else e.__cause__
            kind = cause.kind if isinstance(
                cause, DeviceFaultError
            ) else TRANSIENT
            self._escalate(kind, cause, db=db, sql=sql, stage=stage,
                           relief_done=relief_done)
        else:
            if n_attempts[0] > 1:
                overhead = (
                    _time.perf_counter() - t_run0
                ) - last_attempt_s[0]
                if overhead > 0.0:
                    import orientdb_tpu.obs.critpath as _CP

                    _CP.add_segment("fault_retry", overhead)
            if sql and self._q:
                self.note_success(sql)
            return out

    def _record_fault(self, kind: str, stage: str, exc) -> None:
        with self._mu:
            self._faults[kind] = self._faults.get(kind, 0) + 1
        metrics.incr(f"device.fault.{kind}")
        metrics.incr("device.fault.total")
        from orientdb_tpu.obs.timeline import note_fault

        note_fault(kind)
        log.warning(
            "device fault (%s) at %s: %s: %s",
            kind, stage, type(exc).__name__, exc,
        )

    def _escalate(
        self, kind, cause, *, db, sql, stage, relief_done
    ) -> None:
        """Retries exhausted (or the fault is persistent): quarantine
        the fingerprint, arm the shed latch when memory stayed tight,
        and degrade to the oracle. Always raises."""
        from orientdb_tpu.obs.trace import span

        ttl = self._ttl()
        with span(
            "devicefault.escalate", stage=stage, kind=kind,
            relief=",".join(relief_done) or None,
        ):
            retry_after = ttl
            if sql is not None:
                retry_after = self._quarantine(sql, kind, str(cause))
            if kind == OOM:
                # the device said OOM and relief + retry did not clear
                # it: degrade admission loudly instead of OOM-crashing
                self._arm_shed(f"device OOM survived relief at {stage}")
            elif self._ledger_over_headroom():
                self._arm_shed("memledger total over headroom fraction")
        raise DeviceQuarantined(
            f"device fault domain: {kind} fault at {stage} exhausted "
            f"retries ({cause}); serving oracle",
            retry_after=retry_after,
        ) from cause

    def quarantine_parity(self, sql: str, reason: str) -> float:
        """Parity-divergence conviction (exec/audit): the compiled plan
        executed cleanly but served rows the shadow oracle disagrees
        with. Quarantine its fingerprint so the engine front doors
        serve degraded-but-correct oracle traffic; the existing probe
        machinery re-admits after a clean (re-audited) trial. Returns
        the TTL, like :meth:`_quarantine`."""
        return self._quarantine(sql, PARITY, reason)

    def parity_quarantined(self) -> int:
        """Active quarantine entries convicted by the parity auditor
        (the ``parity_divergence`` alert rule's active-state signal)."""
        with self._mu:
            return sum(1 for e in self._q.values() if e.kind == PARITY)

    # -- quarantine ----------------------------------------------------------

    def _ttl(self) -> float:
        return max(0.1, float(config.devicefault_quarantine_ttl_s))

    def _quarantine(self, sql: str, kind: str, reason: str) -> float:
        """Register/extend the fingerprint's quarantine; returns the
        TTL the caller advertises as Retry-After."""
        fid = self._fid(sql)
        if fid is None:
            return self._ttl()
        now = time.monotonic()
        ttl = self._ttl()
        with self._mu:
            e = self._q.get(fid)
            if e is None:
                self._q[fid] = _Quarantine(fid, sql, kind, reason, now, ttl)
            else:
                # a failed probe (or a second path convicting the same
                # plan): strike and back off the TTL exponentially
                e.strikes += 1
                e.kind = kind
                e.reason = reason
                e.probe_ts = None
                ttl = ttl * min(2 ** (e.strikes - 1), 8)
                e.until = now + ttl
            self._quarantines += 1
        metrics.incr("device.fault.quarantine")
        metrics.gauge("device.fault.quarantined", float(len(self._q)))
        log.warning(
            "plan quarantined (%s, ttl %.1fs): %s", kind, ttl, sql
        )
        return ttl

    # -- relief --------------------------------------------------------------

    def relieve(self, db=None, tier=None) -> List[str]:
        """Actuate memory-pressure relief, memledger-guided: the owner
        taxonomy (PR 17) says where the bytes are, the PR-16 tier pool
        / PR-15 delta plane / PR-12 param rings are the actuators.
        Returns the actions taken (also counted as
        ``device.fault.relief.<action>``)."""
        from orientdb_tpu.obs.memledger import memledger

        totals = memledger.totals()
        actions: List[str] = []
        # actuate in descending attributed-bytes order so the relief
        # chases where the ledger says the memory actually is; rings
        # and transient pages are always worth dropping (cheap, purely
        # a cache)
        candidates = sorted(
            ("tier_pool", "delta_slab"),
            key=lambda k: totals.get(k, 0),
            reverse=True,
        )
        # each actuator independently guarded: relief runs UNDER a
        # faulting dispatch — a second failure here must degrade the
        # relief, never replace the classified fault being handled
        for kind in candidates:
            try:
                if kind == "tier_pool":
                    t = tier
                    if t is None and db is not None:
                        snap = db.current_snapshot()
                        t = getattr(snap, "_tier", None)
                    if t is not None and self._evict_tier(t):
                        actions.append("tier_evict")
                elif kind == "delta_slab" and totals.get(kind, 0) > 0:
                    if db is not None and self._poison_overlay(db):
                        actions.append("delta_compact")
            except Exception as e:  # noqa: BLE001 - relief best-effort
                log.warning("relief actuator %s failed: %s", kind, e)
        try:
            if self._drop_rings():
                actions.append("ring_drop")
        except Exception as e:  # noqa: BLE001 - relief best-effort
            log.warning("relief actuator ring_drop failed: %s", e)
        for a in actions:
            with self._mu:
                self._reliefs[a] = self._reliefs.get(a, 0) + 1
            metrics.incr(f"device.fault.relief.{a}")
        memledger.note_event(
            "devicefault_relief",
            ",".join(actions) if actions else "no actuator available",
        )
        log.warning("device fault relief actuated: %s", actions or "none")
        return actions

    @staticmethod
    def _evict_tier(tier, max_blocks: int = 8) -> bool:
        """Evict up to ``max_blocks`` resident, unpinned LRU blocks.
        Pool pages are recycled (not freed) — the relief is working-set
        pressure off the pinned hot set, and the observable signal the
        acceptance tests assert (``tier.evictions``)."""
        evicted = 0
        with tier.lock:
            for part in tier.parts.values():
                resident = [
                    b for b in range(part.B)
                    if part.page_of[b] >= 0
                    and part.pins.get(b, 0) <= 0
                ]
                resident.sort(key=lambda b: part.lru.get(b, -1))
                for b in resident[:max_blocks - evicted]:
                    tier._evict(part, b)
                    evicted += 1
                if evicted >= max_blocks:
                    break
        return evicted > 0

    @staticmethod
    def _poison_overlay(db) -> bool:
        """Poison the delta overlay so the maintainer folds its slabs
        on the next catch-up — compaction rides the existing rebuild
        machinery rather than running on the faulting thread (which may
        hold dispatch leases the compaction swap would wait on)."""
        m = getattr(db, "_snapshot_maintainer", None)
        ov = m.overlay if m is not None else None
        if ov is None or ov.poisoned is not None:
            return False
        ov.poison("device fault relief: compact slabs")
        return True

    @staticmethod
    def _drop_rings() -> bool:
        from orientdb_tpu.exec import tpu_engine

        return tpu_engine.drop_param_rings() > 0

    def _ledger_over_headroom(self) -> bool:
        cap = int(config.tier_hbm_cap_bytes)
        frac = float(config.devicefault_headroom_fraction)
        if cap <= 0 or frac <= 0:
            return False
        from orientdb_tpu.obs.memledger import memledger

        return memledger.total_bytes() > cap * frac

    # -- admission shed ------------------------------------------------------

    def _arm_shed(self, reason: str) -> None:
        with self._mu:
            self._sheds += 1
            self._shed_reason = reason
            self._shed_until = time.monotonic() + max(
                0.1, float(config.devicefault_shed_s)
            )
        metrics.incr("device.fault.shed")
        metrics.gauge("device.fault.shedding", 1.0)
        log.warning("device fault admission shed armed: %s", reason)

    def shed_state(self) -> Tuple[Optional[str], float]:
        """(reason or None, Retry-After seconds) — consulted by
        ``server/admission.db_pressure``. The latch is a half-open
        window: after ``devicefault_shed_s`` it clears on its own, so
        a recovered device re-admits without an operator."""
        if self._shed_until <= 0.0:
            return None, 0.0
        now = time.monotonic()
        with self._mu:
            if now >= self._shed_until:
                if self._shed_reason is not None:
                    self._shed_reason = None
                    metrics.gauge("device.fault.shedding", 0.0)
                return None, 0.0
            return self._shed_reason, round(self._shed_until - now, 3)

    # -- views ---------------------------------------------------------------

    def fault_total(self) -> int:
        """Classified device faults this process lifetime (the
        ``device_fault_storm`` rule's rate source)."""
        with self._mu:
            return sum(self._faults.values())

    def snapshot(self) -> Dict:
        """The ``/cluster/health`` + debug-bundle block."""
        now = time.monotonic()
        shed_reason, shed_after = self.shed_state()
        with self._mu:
            return {
                "classified": dict(self._faults),
                "retries": self._retries,
                "reliefs": dict(self._reliefs),
                "quarantined": [e.row(now) for e in self._q.values()],
                "quarantines_total": self._quarantines,
                "readmitted": self._readmitted,
                "oracle_served": self._oracle_served,
                "probes": self._probes,
                "sheds": self._sheds,
                "shedding": shed_reason,
                "shed_retry_after_s": shed_after,
            }

    def reset(self) -> None:
        """Test isolation (mirrors ``metrics.reset``)."""
        with self._mu:
            self._q.clear()
            self._faults.clear()
            self._reliefs.clear()
            self._retries = 0
            self._quarantines = 0
            self._readmitted = 0
            self._oracle_served = 0
            self._probes = 0
            self._sheds = 0
            self._shed_until = 0.0
            self._shed_reason = None


#: the process-wide domain (mirrors metrics/stats/tracer singletons)
domain = DeviceFaultDomain()


# -- chaos crossings ---------------------------------------------------------


def dispatch_point() -> None:
    """Cross the device-dispatch chaos points. ``tpu.oom`` first so a
    plan targeting it fires before a generic ``tpu.dispatch`` rule —
    its injected error carries the point name and classifies ``oom``
    without a custom error factory."""
    with fault.point("tpu.oom"):
        pass
    with fault.point("tpu.dispatch"):
        pass


def transfer_point() -> None:
    """Cross the device-transfer chaos points (H2D uploads and the
    blocking D2H result drains)."""
    with fault.point("tpu.oom"):
        pass
    with fault.point("tpu.transfer"):
        pass

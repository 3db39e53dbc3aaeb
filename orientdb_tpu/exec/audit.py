"""Sampled shadow-oracle parity auditor.

The north star is 50x MATCH throughput **at result-set parity**, but
until this module parity was asserted only by tests and the dry run —
never in production serving. PRs 15-18 stacked mutable device
state under every cached plan (delta slab scatters, tier paging, epoch
compaction swaps, OOM-relief evictions), so a single mis-applied patch
could serve wrong rows at full speed with zero signal. This module
makes the parity claim continuously verified:

- at ``audit_sample_rate``, the engine front doors (query/command,
  query_batch, the coalesce lanes' harvest) capture a served compiled
  result together with an epoch lease on the snapshot it was computed
  against (``GraphSnapshot.retain`` — the PR-15 lease keeps the
  compared epoch's device state alive until the audit retires);
- a bounded background worker re-executes the statement on the pure
  Python oracle and compares canonical result digests — the SAME
  canonicalization every other parity check uses (``exec/result``
  helpers), so the parity definitions cannot drift;
- a divergence emits a structured, replayable divergence record
  (fingerprint, trace id, epoch, row-level diff sample), bumps
  ``parity.diverged``, and convicts the fingerprint through the PR-18
  quarantine ladder (``devicefault.domain.quarantine_parity``) so the
  oracle serves degraded-but-correct traffic until a clean probe
  re-admits; the ``parity_divergence`` alert rule fires with the
  divergent request's trace id as exemplar.

Shadow execution is strictly off the serving thread: the submit fast
path is one config read, one sampling roll, an epoch capture, and a
non-blocking queue put (drops count ``parity.audit_dropped`` when the
queue is full). A store mutation between capture and shadow execution
invalidates the compare (the oracle reads the LIVE host store) — those
audits retire as ``parity.audit_stale`` instead of false divergences.

Deterministically provable: the ``audit.mismatch`` chaos point
(:func:`corrupt_point`, crossed by ``exec/engine._run`` after every
compiled execute) corrupts the SERVED rows — never the oracle's — so a
seeded :class:`~orientdb_tpu.chaos.faults.FaultPlan` drives detect →
quarantine → alert → re-admission end to end in tests.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from orientdb_tpu.chaos.faults import FaultError, fault
from orientdb_tpu.exec.result import (
    ColumnarRows,
    Result,
    result_digest,
    rows_diff_sample,
)
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import get_logger
from orientdb_tpu.utils.metrics import metrics

log = get_logger("audit")


def _to_dicts(rows) -> List[Dict]:
    """Plain-dict rows from a raw row container (the ``_rows`` of a
    ResultSet: a list of Result or a ColumnarRows) WITHOUT consuming
    any caller-visible stream."""
    if isinstance(rows, ColumnarRows):
        return rows.to_dicts()
    return [r.to_dict() if isinstance(r, Result) else dict(r) for r in rows]


class _Capture:
    """One sampled serving-path result awaiting shadow execution."""

    __slots__ = (
        "db", "sql", "params", "rows", "trace_id", "epoch", "snap",
        "ts",
    )

    def __init__(self, db, sql, params, rows, trace_id, epoch, snap):
        self.db = db
        self.sql = sql
        self.params = params
        self.rows = rows
        self.trace_id = trace_id
        self.epoch = epoch
        self.snap = snap
        self.ts = time.time()


class ParityAuditor:
    """Process-wide auditor (mirrors the metrics/stats singletons): a
    bounded queue + one daemon worker."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        # SimpleQueue (C-implemented) keeps the serving-thread put at
        # sub-µs; the bound is enforced by a qsize() check in
        # maybe_submit (approximate under races — a shed valve, not an
        # invariant)
        self._q: "queue.SimpleQueue[_Capture]" = queue.SimpleQueue()
        self._qmax = max(1, int(config.audit_queue_max))
        self._worker: Optional[threading.Thread] = None
        self._inflight = 0
        self._retired = 0
        self._submitted = 0
        self._audited = 0
        self._diverged = 0
        self._dropped = 0
        self._stale = 0
        self._errors = 0
        self._divergences: deque = deque()
        #: coalesced epoch leases: id(snap) -> [snap, refcount]. Every
        #: in-flight capture of the same snapshot shares ONE real
        #: ``retain()`` — the retain/ledger bookkeeping is the dominant
        #: serving-thread cost at high sample rates, and a thousand
        #: one-query leases tell the hbm_epoch_leak scan nothing a
        #: single audit-plane lease doesn't
        self._leases: Dict[int, list] = {}

    # -- serving-thread side -------------------------------------------------

    def maybe_submit(
        self, db, sql: str, params, rs, trace_id, sampled_in: bool
    ) -> bool:
        """The front-door hook: enqueue a shadow audit for a COMPILED
        result when the auditor's sampling roll admits it. Rides the
        PR-4 stats decision (``sampled_in`` = the query's stats
        accumulator ran, or the always-captured batch paths) so stats /
        slowlog / timeline / audit cover the same query subset. Never
        blocks and never raises into the serving path."""
        rate = config.audit_sample_rate
        if rate <= 0 or not sampled_in:
            return False
        if getattr(rs, "engine", None) != "tpu":
            return False
        rows = getattr(rs, "_rows", None)
        if rows is None or not hasattr(rows, "__len__"):
            return False
        from orientdb_tpu.obs.stats import sampled

        if not sampled(rate):
            return False
        try:
            snap = db.current_snapshot()
            cap = _Capture(
                db, sql, params, rows, trace_id, db.mutation_epoch, snap
            )
            with self._mu:
                if snap is not None:
                    # epoch lease: the compared epoch's device state
                    # stays alive until the audit retires (dropped in
                    # _release); captures of the same snapshot share
                    # one refcounted retain
                    sid = id(snap)
                    e = self._leases.get(sid)
                    if e is None:
                        snap.retain()
                        self._leases[sid] = [snap, 1]
                    else:
                        e[1] += 1
                self._submitted += 1
            if self._q.qsize() >= self._qmax:
                self._release(cap)
                with self._mu:
                    self._submitted -= 1
                    self._dropped += 1
                metrics.incr("parity.audit_dropped")
                return False
            self._q.put(cap)
            self._ensure_worker()
            return True
        except Exception:  # the audit plane must never fail a query
            log.exception("parity audit submit failed")
            return False

    # -- worker side ---------------------------------------------------------

    def _ensure_worker(self) -> None:
        w = self._worker
        if w is not None and w.is_alive():
            return
        with self._mu:
            if self._worker is not None and self._worker.is_alive():
                return
            self._worker = threading.Thread(
                target=self._loop, name="parity-audit", daemon=True
            )
            self._worker.start()

    def _loop(self) -> None:
        while True:
            cap = self._q.get()
            with self._mu:
                self._inflight += 1
            try:
                self._audit_one(cap)
            except Exception:
                with self._mu:
                    self._errors += 1
                log.exception("parity audit failed: %s", cap.sql)
            finally:
                self._release(cap)
                with self._mu:
                    self._inflight -= 1
                    self._retired += 1

    def _release(self, cap: _Capture) -> None:
        snap = cap.snap
        if snap is None:
            return
        cap.snap = None
        real = None
        with self._mu:
            e = self._leases.get(id(snap))
            if e is not None:
                e[1] -= 1
                if e[1] <= 0:
                    del self._leases[id(snap)]
                    real = e[0]
        if real is not None:
            try:
                real.release()
            except Exception:
                log.exception("audit epoch lease release failed")

    def _audit_one(self, cap: _Capture) -> None:
        from orientdb_tpu.obs.trace import span

        with span("audit.shadow", sql=cap.sql[:120]) as sp:
            if cap.db.mutation_epoch != cap.epoch:
                # the oracle reads the LIVE host store; a write landed
                # since capture, so the compare is no longer valid at
                # the captured epoch — retire without a verdict
                with self._mu:
                    self._stale += 1
                metrics.incr("parity.audit_stale")
                sp.set("verdict", "stale")
                return
            from orientdb_tpu.exec.engine import parse_cached
            from orientdb_tpu.exec.oracle import execute_statement

            served = _to_dicts(cap.rows)
            oracle_rows = execute_statement(
                cap.db, parse_cached(cap.sql), cap.params or {}
            )
            oracle = _to_dicts(oracle_rows)
            d_served = result_digest(served)
            d_oracle = result_digest(oracle)
            with self._mu:
                self._audited += 1
            metrics.incr("parity.audited")
            if d_served == d_oracle:
                sp.set("verdict", "parity")
                return
            sp.set("verdict", "diverged")
            self._diverge(cap, served, oracle, d_served, d_oracle)

    def _diverge(self, cap, served, oracle, d_served, d_oracle) -> None:
        from orientdb_tpu.exec.devicefault import domain as _fault_domain
        from orientdb_tpu.obs.stats import fingerprint_cached

        rec = {
            "fingerprint": fingerprint_cached(cap.sql).fid,
            "sql": cap.sql[:200],
            "trace_id": cap.trace_id,
            "epoch": cap.epoch,
            "digest_served": d_served,
            "digest_oracle": d_oracle,
            "rows_served": len(served),
            "rows_oracle": len(oracle),
            "diff": rows_diff_sample(
                served, oracle, limit=max(1, int(config.audit_diff_rows))
            ),
            "ts": round(time.time(), 3),
        }
        with self._mu:
            self._diverged += 1
            self._divergences.append(rec)
            capacity = max(1, int(config.audit_history_capacity))
            while len(self._divergences) > capacity:
                self._divergences.popleft()
        metrics.incr("parity.diverged")
        # quarantine the fingerprint through the PR-18 ladder: the
        # front doors serve the oracle (degraded but correct) until a
        # clean probe — which this auditor re-audits — re-admits
        _fault_domain.quarantine_parity(
            cap.sql,
            f"parity divergence: served {d_served} != oracle {d_oracle} "
            f"at epoch {cap.epoch}",
        )
        log.error(
            "PARITY DIVERGENCE (epoch %s, trace %s): %s — served %s "
            "(%d rows) vs oracle %s (%d rows)",
            cap.epoch, cap.trace_id, cap.sql[:120], d_served,
            len(served), d_oracle, len(oracle),
        )

    # -- views ---------------------------------------------------------------

    def flush(self, timeout_s: float = 5.0) -> bool:
        """Drain every queued audit (tests settle on it): True when
        every submitted capture has retired — exact accounting, immune
        to the dequeue-to-inflight handoff window."""
        deadline = time.monotonic() + timeout_s
        with self._mu:
            drained = self._retired >= self._submitted
        if not drained:
            self._ensure_worker()
        while True:
            with self._mu:
                if self._retired >= self._submitted:
                    return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.005)

    def divergences(self) -> List[Dict]:
        """Replayable divergence records, oldest first."""
        with self._mu:
            return list(self._divergences)

    def last_divergence_trace(self) -> Optional[str]:
        with self._mu:
            for rec in reversed(self._divergences):
                if rec.get("trace_id"):
                    return rec["trace_id"]
        return None

    def snapshot(self) -> Dict:
        with self._mu:
            return {
                "submitted": self._submitted,
                "audited": self._audited,
                "diverged": self._diverged,
                "dropped": self._dropped,
                "stale": self._stale,
                "errors": self._errors,
                "queued": self._q.qsize(),
                "divergences": list(self._divergences),
            }

    def reset(self) -> None:
        """Test isolation (mirrors ``metrics.reset``)."""
        self.flush(timeout_s=1.0)
        with self._mu:
            self._retired = 0
            self._submitted = 0
            self._audited = 0
            self._diverged = 0
            self._dropped = 0
            self._stale = 0
            self._errors = 0
            self._divergences.clear()


#: the process-wide auditor (mirrors metrics/stats/tracer singletons)
auditor = ParityAuditor()


# -- chaos crossing ----------------------------------------------------------


def corrupt_point(rows):
    """The ``audit.mismatch`` chaos crossing: an armed plan's ``error``
    rule here deterministically corrupts the SERVED compiled rows —
    never the oracle's — so the auditor's digest compare must diverge.
    Crossed by ``exec/engine._run`` after every compiled execute."""
    try:
        with fault.point("audit.mismatch"):
            return rows
    except FaultError:
        metrics.incr("parity.chaos_corrupted")
        if hasattr(rows, "__len__") and len(rows) > 0:
            return rows[1:]  # drop the first served row
        return [Result(props={"__corrupt__": True})]

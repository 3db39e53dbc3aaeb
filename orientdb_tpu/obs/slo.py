"""SLO verdicts: a declarative spec per query class, judged from the
existing obs planes.

The alert plane (obs/alerts) watches the process continuously; this
module answers a different question — **did one bounded run of
production-shaped traffic hold its SLOs?** A :class:`SloSpec` names
query classes (each a set of SQL shapes, joined to the PR-4 stats
table by fingerprint) with per-class targets (p50/p99 latency ceilings
estimated from the ``QueryStats`` histograms via
``obs.stats.estimate_quantile``, a minimum success rate) plus run-wide
policy (no alert left *firing*, error-budget burn within
``slo_max_burn`` of the ``alert_slo_error_rate`` budget). Nothing here
re-times queries: every signal is read from the stats/alerts planes
the serving path already feeds.

Evaluation is **windowed**: :meth:`SloEngine.begin` snapshots the
relevant fingerprints' histograms, :meth:`SloEngine.finish` differences
against them — so one run is judged on ITS traffic, not the process's
cumulative history. The result is one machine-readable report
(``verdict`` pass/fail with every failure naming its rule and key),
served by ``GET /slo`` and console ``SLO``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, List, Optional

from orientdb_tpu.obs.stats import (
    QUANTILE_FIELDS,
    estimate_quantile,
    fingerprint_cached,
    stats,
)
from orientdb_tpu.obs.trace import span
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import get_logger
from orientdb_tpu.utils.metrics import metrics

log = get_logger("slo")

#: verdict failure rules — the vocabulary every failure entry's
#: ``rule`` field draws from (the report's operator-facing index;
#: README "Traffic simulator & SLO verdicts" documents each)
FAILURE_RULES: Dict[str, str] = {
    "p50_latency": "a class's windowed p50 exceeds its p50_ms target",
    "p99_latency": "a class's windowed p99 exceeds its p99_ms target",
    "availability": "a class's windowed success rate is below its "
    "availability target",
    "no_traffic": "a class saw fewer calls than its min_calls floor — "
    "a silently dropped workload must not read as healthy",
    "alert_firing": "an alert was still FIRING at evaluation time "
    "(the run must end recovered, not mid-incident)",
    "error_budget_burn": "the run's overall error rate burned the "
    "alert_slo_error_rate budget beyond slo_max_burn",
}


class SloClass:
    """One query class: the SQL shapes that belong to it (parameter and
    literal spellings both — they fingerprint differently) plus its
    targets. ``None`` targets inherit the ``slo_*`` config defaults; an
    explicit 0/negative target disables that check."""

    __slots__ = ("name", "sqls", "p50_ms", "p99_ms", "availability",
                 "min_calls")

    def __init__(
        self,
        name: str,
        sqls: Iterable[str],
        p50_ms: Optional[float] = None,
        p99_ms: Optional[float] = None,
        availability: Optional[float] = None,
        min_calls: int = 1,
    ) -> None:
        self.name = name
        self.sqls = tuple(sqls)
        self.p50_ms = config.slo_p50_ms if p50_ms is None else p50_ms
        self.p99_ms = config.slo_p99_ms if p99_ms is None else p99_ms
        self.availability = (
            config.slo_availability if availability is None else availability
        )
        self.min_calls = min_calls

    def fids(self) -> List[str]:
        return sorted({fingerprint_cached(s).fid for s in self.sqls})

    def to_dict(self) -> Dict[str, object]:
        return {
            "name": self.name,
            "fingerprints": self.fids(),
            "p50_ms": self.p50_ms,
            "p99_ms": self.p99_ms,
            "availability": self.availability,
            "min_calls": self.min_calls,
        }


class SloSpec:
    """The declarative spec one run is judged against."""

    __slots__ = ("classes", "require_no_firing", "max_burn",
                 "error_budget")

    def __init__(
        self,
        classes: Iterable[SloClass],
        require_no_firing: bool = True,
        max_burn: Optional[float] = None,
        error_budget: Optional[float] = None,
    ) -> None:
        self.classes = list(classes)
        self.require_no_firing = require_no_firing
        self.max_burn = config.slo_max_burn if max_burn is None else max_burn
        self.error_budget = (
            config.alert_slo_error_rate
            if error_budget is None
            else error_budget
        )

    def to_dict(self) -> Dict[str, object]:
        return {
            "classes": [c.to_dict() for c in self.classes],
            "require_no_firing": self.require_no_firing,
            "max_burn": self.max_burn,
            "error_budget": self.error_budget,
        }


class SloRun:
    """One armed evaluation window: the spec plus the begin-time
    histogram snapshot :meth:`SloEngine.finish` differences against."""

    __slots__ = ("spec", "t0", "base")

    def __init__(self, spec: SloSpec, base: Dict[str, Dict]) -> None:
        self.spec = spec
        self.t0 = time.time()
        self.base = base


def _delta(cur: Dict, base: Optional[Dict]) -> Dict:
    """Windowed per-fingerprint stats: current minus the begin-time
    snapshot (a fingerprint absent at begin contributes whole)."""
    if base is None:
        return {
            "calls": cur["calls"],
            "errors": cur["errors"],
            "total_s": cur["total_s"],
            "max_s": cur["max_s"],
            "buckets": list(cur["buckets"]),
        }
    return {
        "calls": cur["calls"] - base["calls"],
        "errors": cur["errors"] - base["errors"],
        "total_s": cur["total_s"] - base["total_s"],
        # max_s is cumulative (no windowed max exists) — it only ever
        # OVER-bounds the overflow bucket's interpolation ceiling
        "max_s": cur["max_s"],
        "buckets": [
            c - b for c, b in zip(cur["buckets"], base["buckets"])
        ],
    }


class SloEngine:
    """Windowed SLO evaluation + the last report (the ``GET /slo``
    document). Process-wide singleton (:data:`engine`), mirroring the
    stats/alerts singletons."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._last: Optional[Dict] = None

    # -- run lifecycle -------------------------------------------------------

    def begin(self, spec: SloSpec) -> SloRun:
        """Arm one evaluation window: snapshot every spec fingerprint's
        histogram so :meth:`finish` scores only this run's traffic.
        Also installs the spec's class membership into the critical-path
        plane so its per-SloClass breakdowns roll up by the same
        names."""
        from orientdb_tpu.obs.critpath import register_slo_classes

        register_slo_classes(spec.classes)
        fids = [f for c in spec.classes for f in c.fids()]
        return SloRun(spec, stats.histogram_snapshot(fids))

    def finish(
        self, run: SloRun, extra: Optional[Dict] = None
    ) -> Dict[str, object]:
        """Judge the window: per-class quantiles/availability from the
        stats-table deltas, run-wide alert + burn policy from the alert
        engine. Returns (and stores) the machine-readable report;
        ``extra`` merges driver-side context (schedule digest, chaos
        summary) into it verbatim."""
        from orientdb_tpu.obs.alerts import engine as alert_engine

        with span("slo.evaluate", classes=len(run.spec.classes)):
            report = self._evaluate(run, alert_engine)
        if extra:
            report.update(extra)
        with self._mu:
            self._last = report
        metrics.gauge("slo.passed", 1 if report["verdict"] == "pass" else 0)
        metrics.gauge("slo.burn", report["burn"])
        metrics.gauge("slo.failures", len(report["failures"]))
        if report["verdict"] != "pass":
            log.warning(
                "SLO verdict FAIL: %s",
                "; ".join(
                    f"{f['rule']}({f['key']})" for f in report["failures"]
                ),
            )
        return report

    def _evaluate(self, run: SloRun, alert_engine) -> Dict[str, object]:
        spec = run.spec
        failures: List[Dict] = []

        def fail(rule: str, key: str, value, threshold, detail: str):
            failures.append(
                {
                    "rule": rule,
                    "key": key,
                    "value": round(float(value), 6),
                    "threshold": round(float(threshold), 6),
                    "detail": detail,
                }
            )

        classes: List[Dict] = []
        total_calls = total_errors = 0
        cur = stats.histogram_snapshot(
            [f for c in spec.classes for f in c.fids()]
        )
        for cls in spec.classes:
            agg = None
            for fid in cls.fids():
                if fid not in cur:
                    continue
                d = _delta(cur[fid], run.base.get(fid))
                if agg is None:
                    agg = d
                else:
                    agg["calls"] += d["calls"]
                    agg["errors"] += d["errors"]
                    agg["total_s"] += d["total_s"]
                    agg["max_s"] = max(agg["max_s"], d["max_s"])
                    agg["buckets"] = [
                        a + b for a, b in zip(agg["buckets"], d["buckets"])
                    ]
            calls = agg["calls"] if agg else 0
            errors = agg["errors"] if agg else 0
            row: Dict[str, object] = {
                "class": cls.name,
                "calls": calls,
                "errors": errors,
                "targets": {
                    "p50_ms": cls.p50_ms,
                    "p99_ms": cls.p99_ms,
                    "availability": cls.availability,
                },
            }
            if calls < cls.min_calls:
                fail(
                    "no_traffic", cls.name, calls, cls.min_calls,
                    f"class {cls.name} saw {calls} calls "
                    f"(< min_calls {cls.min_calls})",
                )
                classes.append(row)
                continue
            total_calls += calls
            total_errors += errors
            for field, q in QUANTILE_FIELDS:
                row[field] = round(
                    estimate_quantile(agg["buckets"], q, agg["max_s"])
                    * 1000.0,
                    3,
                )
            row["error_rate"] = round(errors / calls, 6)
            ok_rate = 1.0 - errors / calls
            if cls.availability > 0 and ok_rate < cls.availability:
                fail(
                    "availability", cls.name, ok_rate, cls.availability,
                    f"class {cls.name}: success rate {ok_rate:.4f} < "
                    f"target {cls.availability:.4f} "
                    f"({errors}/{calls} errors)",
                )
            for rule, field, target in (
                ("p50_latency", "p50_ms", cls.p50_ms),
                ("p99_latency", "p99_ms", cls.p99_ms),
            ):
                if target > 0 and row[field] > target:
                    fail(
                        rule, cls.name, row[field], target,
                        f"class {cls.name}: {field} {row[field]:.1f} ms "
                        f"> target {target:g} ms",
                    )
            classes.append(row)

        firing = [
            a for a in alert_engine.active() if a["state"] == "firing"
        ]
        if spec.require_no_firing:
            for a in firing:
                fail(
                    "alert_firing", a["rule"], a["value"], a["threshold"],
                    f"alert {a['rule']}({a['key']}) still firing: "
                    f"{a['detail']}",
                )
        burn = 0.0
        if total_calls > 0 and spec.error_budget > 0:
            burn = (total_errors / total_calls) / spec.error_budget
            if spec.max_burn > 0 and burn > spec.max_burn:
                fail(
                    "error_budget_burn", "run", burn, spec.max_burn,
                    f"run error rate {total_errors / total_calls:.4f} "
                    f"burns the {spec.error_budget:g} budget at "
                    f"{burn:.2f}x (> {spec.max_burn:g}x)",
                )
        return {
            "ts": round(time.time(), 3),
            "window_s": round(time.time() - run.t0, 3),
            "verdict": "fail" if failures else "pass",
            "failures": failures,
            "burn": round(burn, 4),
            "calls": total_calls,
            "errors": total_errors,
            "classes": classes,
            "alerts_firing": [a["rule"] for a in firing],
            "spec": spec.to_dict(),
        }

    # -- reading (scrape-time) ----------------------------------------------

    def report(self) -> Dict[str, object]:
        """The ``GET /slo`` document: the last run's report, or an
        explicit empty marker (never a fabricated pass)."""
        with self._mu:
            if self._last is not None:
                return dict(self._last)
        return {
            "ts": round(time.time(), 3),
            "verdict": "none",
            "detail": "no SLO run recorded in this process "
            "(workloads.driver.TrafficSim produces one)",
        }

    def reset(self) -> None:
        with self._mu:
            self._last = None


#: the process-wide engine (the stats/alerts singleton convention)
engine = SloEngine()

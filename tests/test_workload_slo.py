"""Traffic simulator & SLO verdicts (ISSUE 11): seeded schedule and
verdict determinism, the windowed SLO engine (per-class quantiles from
stats-histogram deltas, alert/burn policy, failures naming their
rule/key), per-fingerprint latency quantiles + ``?by=p99``, the
closed-loop chaos scenario end-to-end (real cluster, HTTP + binary
sessions, CDC consumers, replica kill/restart, breaker trip, settle,
verdict), and the `GET /slo`/console `SLO` surfaces."""

import base64
import io
import json
import os
import urllib.request

import pytest

from orientdb_tpu.chaos.faults import FaultPlan, fault
from orientdb_tpu.obs.alerts import engine as alert_engine
from orientdb_tpu.obs.slo import (
    FAILURE_RULES,
    SloClass,
    SloSpec,
    engine as slo_engine,
)
from orientdb_tpu.obs.stats import (
    QueryStats,
    estimate_quantile,
    stats,
)
from orientdb_tpu.utils.config import config
from orientdb_tpu.workloads.driver import (
    TX2PC_SQL,
    TrafficSim,
    _inline,
    build_schedule,
    default_chaos_plan,
    default_slo_spec,
    schedule_digest,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    from orientdb_tpu.parallel.resilience import reset_breakers

    monkeypatch.setattr(config, "watchdog_enabled", False)
    alert_engine.reset()
    slo_engine.reset()
    yield
    fault.disarm()
    alert_engine.reset()
    slo_engine.reset()
    reset_breakers()


def _get(url, password="pw", raw=False):
    cred = base64.b64encode(f"admin:{password}".encode()).decode()
    req = urllib.request.Request(
        url, headers={"Authorization": f"Basic {cred}"}
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        body = r.read()
    return body.decode() if raw else json.loads(body)


# ---------------------------------------------------------------------------
# schedule determinism
# ---------------------------------------------------------------------------


class TestSchedule:
    def test_same_seed_same_schedule(self):
        a = build_schedule(7, 4, 20, 0.2, 100, 300, "Ann")
        b = build_schedule(7, 4, 20, 0.2, 100, 300, "Ann")
        assert a == b
        assert schedule_digest(a) == schedule_digest(b)
        c = build_schedule(8, 4, 20, 0.2, 100, 300, "Ann")
        assert schedule_digest(c) != schedule_digest(a)

    def test_mix_respects_update_ratio(self):
        sched = build_schedule(3, 2, 50, 0.0, 100, 300)
        kinds = {op.kind for ops in sched for op in ops}
        assert not kinds & {"insert", "update", "tx2pc"}
        sched = build_schedule(3, 2, 50, 1.0, 100, 300)
        kinds = {op.kind for ops in sched for op in ops}
        assert kinds <= {"insert", "update", "tx2pc"}
        # the embedded 2PC path runs on session 0 only
        assert not any(
            op.kind == "tx2pc" for op in sched[1]
        )

    def test_inline_renders_literals(self):
        out = _inline(
            "MATCH {where:(id = :personId AND n = :person)} "
            "RETURN :firstName",
            {"personId": 5, "person": 7, "firstName": "O'Brien"},
        )
        assert ":personId" not in out and ":person" not in out
        assert "id = 5" in out and "n = 7" in out
        assert "'O\\'Brien'" in out


# ---------------------------------------------------------------------------
# histogram quantiles
# ---------------------------------------------------------------------------


class TestQuantiles:
    def test_estimate_quantile_interpolates(self):
        # buckets for _LAT_BUCKETS = (.001,.005,.025,.1,.5,2.5,10) + inf
        buckets = [10, 0, 0, 0, 0, 0, 0, 0]
        assert estimate_quantile(buckets, 0.5) == pytest.approx(0.0005)
        buckets = [5, 5, 0, 0, 0, 0, 0, 0]
        p50 = estimate_quantile(buckets, 0.5)
        p99 = estimate_quantile(buckets, 0.99)
        assert 0.0 < p50 <= 0.001 < p99 <= 0.005
        assert estimate_quantile([0] * 8, 0.99) == 0.0

    def test_overflow_bucket_bounded_by_max(self):
        buckets = [0, 0, 0, 0, 0, 0, 0, 4]
        v = estimate_quantile(buckets, 0.99, max_s=12.0)
        assert 10.0 <= v <= 12.0

    def test_entry_rows_carry_quantiles_and_sort_aliases(self):
        qs = QueryStats(capacity=16)
        for i in range(20):
            qs.record_external("SELECT FROM Fast", 0.0004, engine="t")
        for i in range(20):
            qs.record_external("SELECT FROM Slow", 0.3, engine="t")
        rows = qs.top(10, by="p99")
        assert rows[0]["query"].endswith("Slow")
        for r in rows:
            assert {"p50_ms", "p95_ms", "p99_ms"} <= set(r)
            assert r["p50_ms"] <= r["p95_ms"] <= r["p99_ms"]
        assert rows[0]["p99_ms"] > rows[1]["p99_ms"]


# ---------------------------------------------------------------------------
# the SLO engine (windowed evaluation, verdicts, failure naming)
# ---------------------------------------------------------------------------


def _spec_one(name, sql, **kw):
    kw.setdefault("availability", 0.99)
    return SloSpec([SloClass(name, [sql], **kw)])


class TestSloEngine:
    def test_window_excludes_prior_traffic(self):
        sql = "SELECT FROM WindowedShape"
        for _ in range(50):
            stats.record_external(sql, 5.0, engine="t")  # ancient, slow
        spec = _spec_one("W", sql, p50_ms=100.0, p99_ms=8000.0)
        run = slo_engine.begin(spec)
        for _ in range(10):
            stats.record_external(sql, 0.0004, engine="t")
        report = slo_engine.finish(run)
        (row,) = report["classes"]
        assert row["calls"] == 10 and row["errors"] == 0
        assert row["p50_ms"] < 1.0  # the 5 s history is outside the window
        assert report["verdict"] == "pass" and report["failures"] == []
        assert report["burn"] == 0.0

    def test_p99_breach_fails_naming_rule_and_class(self):
        sql = "SELECT FROM SlowShape"
        spec = _spec_one("SLOW", sql, p99_ms=1.0)
        run = slo_engine.begin(spec)
        for _ in range(5):
            stats.record_external(sql, 0.4, engine="t")
        report = slo_engine.finish(run)
        assert report["verdict"] == "fail"
        rules = {(f["rule"], f["key"]) for f in report["failures"]}
        assert ("p99_latency", "SLOW") in rules
        assert all(f["rule"] in FAILURE_RULES for f in report["failures"])

    def test_availability_and_burn_failures(self):
        sql = "SELECT FROM FlakyShape"
        spec = SloSpec(
            [SloClass("FLAKY", [sql], availability=0.9)],
            error_budget=0.01,
            max_burn=1.0,
        )
        run = slo_engine.begin(spec)
        for i in range(10):
            stats.record_external(
                sql, 0.001, engine="t",
                error=ValueError("x") if i < 5 else None,
            )
        report = slo_engine.finish(run)
        rules = {(f["rule"], f["key"]) for f in report["failures"]}
        assert ("availability", "FLAKY") in rules
        assert ("error_budget_burn", "run") in rules
        assert report["burn"] == pytest.approx(50.0)

    def test_no_traffic_fails(self):
        spec = _spec_one("GHOST", "SELECT FROM NeverRuns2")
        report = slo_engine.finish(slo_engine.begin(spec))
        assert report["verdict"] == "fail"
        assert {(f["rule"], f["key"]) for f in report["failures"]} == {
            ("no_traffic", "GHOST")
        }

    def test_firing_alert_fails_verdict(self, monkeypatch):
        monkeypatch.setattr(config, "alert_pending_ticks", 1)
        monkeypatch.setattr(config, "alert_rss_bytes", 1)
        sql = "SELECT FROM HealthyShape"
        spec = _spec_one("H", sql)
        run = slo_engine.begin(spec)
        stats.record_external(sql, 0.001, engine="t")
        alert_engine.evaluate()  # rss_watermark fires immediately
        report = slo_engine.finish(run)
        assert report["verdict"] == "fail"
        rules = {(f["rule"], f["key"]) for f in report["failures"]}
        assert ("alert_firing", "rss_watermark") in rules
        assert "rss_watermark" in report["alerts_firing"]

    def test_report_marker_then_last_report(self):
        assert slo_engine.report()["verdict"] == "none"
        sql = "SELECT FROM ReportShape"
        run = slo_engine.begin(_spec_one("R", sql))
        stats.record_external(sql, 0.001, engine="t")
        first = slo_engine.finish(run, extra={"schedule_digest": "abc"})
        served = slo_engine.report()
        assert served["verdict"] == first["verdict"]
        assert served["schedule_digest"] == "abc"


# ---------------------------------------------------------------------------
# the closed-loop simulator end-to-end
# ---------------------------------------------------------------------------


def _tiny_sim(seed, **kw):
    kw.setdefault("persons", 50)
    kw.setdefault("sessions", 3)
    kw.setdefault("ops_per_session", 8)
    kw.setdefault("update_ratio", 0.25)
    kw.setdefault("replica_outage", None)
    kw.setdefault("settle_s", 5.0)
    kw.setdefault("tick_s", 0.05)
    return TrafficSim(seed=seed, **kw)


class TestTrafficSimEndToEnd:
    def test_same_seed_same_schedule_and_verdict(self):
        r1 = _tiny_sim(5).run()
        digest1, verdict1 = r1["schedule_digest"], r1["slo"]["verdict"]
        slo_engine.reset()
        alert_engine.reset()
        r2 = _tiny_sim(5).run()
        assert r2["schedule_digest"] == digest1
        assert r2["slo"]["verdict"] == verdict1 == "pass"
        assert sum(r1["ops"].values()) == 3 * 8
        # GET-able afterwards: the last report is the run's report
        assert slo_engine.report()["schedule_digest"] == digest1

    def test_chaos_run_recovers_and_passes(self):
        seed = 11
        sim = _tiny_sim(
            seed,
            sessions=4,
            ops_per_session=12,
            update_ratio=0.3,
            chaos=default_chaos_plan(seed),
            replica_outage=(0.3, 0.6),
            settle_s=12.0,
        )
        r = sim.run()
        assert r["chaos"]["fired"] > 0
        assert r["settle"]["settled"] is True
        assert r["cdc"]["consumers"] == 2 and r["cdc"]["events"] > 0
        assert r["ops"].get("tx2pc", 0) >= 1
        # both read transports ran: every class the schedule drew got
        # judged, none as no_traffic
        assert r["slo"]["verdict"] == "pass", r["slo"]["failures"]
        judged = {c["class"] for c in r["slo"]["classes"]}
        assert judged == set(r["ops"])

    def test_injected_unresolved_alert_fails_verdict(self, monkeypatch):
        monkeypatch.setattr(config, "alert_pending_ticks", 1)
        monkeypatch.setattr(config, "alert_rss_bytes", 1)
        r = _tiny_sim(5, settle_s=0.3).run()
        assert r["settle"]["settled"] is False
        assert r["slo"]["verdict"] == "fail"
        rules = {(f["rule"], f["key"]) for f in r["slo"]["failures"]}
        assert ("alert_firing", "rss_watermark") in rules

    def test_p99_breach_fails_naming_the_class(self):
        # judge only the tx2pc class, with an impossible p99 target
        spec = SloSpec(
            [
                SloClass(
                    "tx2pc", [TX2PC_SQL],
                    p50_ms=0.0, p99_ms=0.0001, availability=0.0,
                )
            ]
        )
        r = _tiny_sim(
            11, sessions=2, ops_per_session=12, update_ratio=0.5,
            spec=spec,
        ).run()
        assert r["ops"].get("tx2pc", 0) >= 1
        assert r["slo"]["verdict"] == "fail"
        rules = {(f["rule"], f["key"]) for f in r["slo"]["failures"]}
        assert ("p99_latency", "tx2pc") in rules


# ---------------------------------------------------------------------------
# surfaces: GET /slo, GET /stats/queries?by=p99, console SLO
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_http_slo_and_stats_by_p99(self):
        from orientdb_tpu.server.server import Server

        srv = Server(admin_password="pw").startup()
        try:
            url = f"http://127.0.0.1:{srv.http_port}"
            doc = _get(f"{url}/slo")
            assert doc["verdict"] == "none"
            sql_fast = "SELECT FROM SurfFast"
            sql_slow = "SELECT FROM SurfSlow"
            run = slo_engine.begin(
                _spec_one("SURF", sql_fast, p99_ms=8000.0)
            )
            for _ in range(8):
                stats.record_external(sql_fast, 0.0004, engine="t")
                stats.record_external(sql_slow, 0.3, engine="t")
            report = slo_engine.finish(run)
            doc = _get(f"{url}/slo")
            assert doc["verdict"] == report["verdict"] == "pass"
            assert doc["classes"][0]["class"] == "SURF"
            # ?by=p99 aliases p99_ms and ranks the slow shape first
            doc = _get(f"{url}/stats/queries?by=p99&k=200")
            assert doc["by"] == "p99_ms"
            rows = [
                r for r in doc["queries"]
                if r["query"] in (sql_fast, sql_slow)
            ]
            assert rows and rows[0]["query"] == sql_slow
            assert rows[0]["p99_ms"] >= rows[-1]["p99_ms"]
        finally:
            srv.shutdown()

    def test_console_slo_verb(self):
        from orientdb_tpu.tools.console import Console

        buf = io.StringIO()
        Console(stdout=buf).onecmd("SLO")
        assert "no SLO run recorded" in buf.getvalue()
        sql = "SELECT FROM ConsoleShape"
        run = slo_engine.begin(_spec_one("CON", sql, p99_ms=0.0001))
        stats.record_external(sql, 0.2, engine="t")
        slo_engine.finish(run)
        buf = io.StringIO()
        Console(stdout=buf).onecmd("SLO")
        out = buf.getvalue()
        assert "verdict: FAIL" in out
        assert "p99_latency(CON)" in out
        # STATS QUERIES prints the quantile columns
        buf = io.StringIO()
        Console(stdout=buf).onecmd("STATS QUERIES 5")
        assert "p99 ms" in buf.getvalue()

"""SLO alerting & health watchdog (ISSUE 10): the rule engine's
pending → firing → resolved lifecycle driven end-to-end by chaos
faults (dropped replication pushes, a tripped circuit breaker) and
observed through every surface — `GET /alerts`, `/cluster/health`, the
debug bundle, and the console `ALERTS`/`HEALTH` verbs; the online
EWMA+MAD latency baseline and two-window burn-rate conditions;
trace-correlated structured logs and the bundle's bounded `logs` ring;
and the hot-path overhead guard."""

import base64
import io
import json
import logging
import os
import time
import urllib.error
import urllib.request

import pytest

from orientdb_tpu.chaos.faults import FaultPlan, fault
from orientdb_tpu.obs.alerts import (
    RULE_CATALOG,
    AlertEngine,
    engine,
    render_alerts_prometheus,
)
from orientdb_tpu.obs.promlint import lint_exposition
from orientdb_tpu.obs.trace import span, tracer
from orientdb_tpu.obs.watchdog import HealthWatchdog
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import JsonFormatter, get_logger, log_ring

@pytest.fixture(autouse=True)
def _clean_alert_state():
    from orientdb_tpu.parallel.resilience import reset_breakers

    engine.reset()
    yield
    fault.disarm()
    engine.reset()
    reset_breakers()


def _get(url, user="admin", password="pw", raw=False):
    cred = base64.b64encode(f"{user}:{password}".encode()).decode()
    req = urllib.request.Request(
        url, headers={"Authorization": f"Basic {cred}"}
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        body = r.read()
        ctype = r.headers.get("Content-Type", "")
    return (body.decode(), ctype) if raw else json.loads(body)


def _alert(doc, rule):
    """The first active alert for ``rule`` in a GET /alerts payload."""
    return next((a for a in doc["alerts"] if a["rule"] == rule), None)


class TestEngineLifecycle:
    def test_threshold_rule_pending_firing_resolved(self, monkeypatch):
        """rss_watermark (always breachable at threshold 1) walks the
        whole lifecycle: pending after one breaching tick, firing after
        alert_pending_ticks, resolved into the history ring when the
        signal clears."""
        monkeypatch.setattr(config, "alert_pending_ticks", 2)
        monkeypatch.setattr(config, "alert_rss_bytes", 1)
        engine.evaluate()
        (a,) = [x for x in engine.active() if x["rule"] == "rss_watermark"]
        assert a["state"] == "pending"
        engine.evaluate()
        (a,) = [x for x in engine.active() if x["rule"] == "rss_watermark"]
        assert a["state"] == "firing"
        assert a["value"] > a["threshold"]
        monkeypatch.setattr(config, "alert_rss_bytes", 1 << 60)
        engine.evaluate()
        assert not [
            x for x in engine.active() if x["rule"] == "rss_watermark"
        ]
        hist = [x for x in engine.history() if x["rule"] == "rss_watermark"]
        assert hist and hist[0]["state"] == "resolved"
        assert hist[0]["resolved_ts"] >= hist[0]["since_ts"]
        s = engine.summary()
        assert s["fired_total"] == 1 and s["resolved_total"] == 1
        assert s["rules"] == len(RULE_CATALOG)

    def test_pending_that_clears_never_fires(self, monkeypatch):
        monkeypatch.setattr(config, "alert_pending_ticks", 3)
        monkeypatch.setattr(config, "alert_rss_bytes", 1)
        engine.evaluate()
        monkeypatch.setattr(config, "alert_rss_bytes", 1 << 60)
        engine.evaluate()
        assert engine.summary()["fired_total"] == 0
        assert engine.history() == []

    def test_firing_alert_captures_span_exemplar(self, monkeypatch):
        """A firing alert with neither a slowlog match nor a span
        family (rss_watermark declares none) still links a valid trace:
        the newest span in the ring."""
        monkeypatch.setattr(config, "alert_pending_ticks", 1)
        monkeypatch.setattr(config, "alert_rss_bytes", 1)
        with span("query") as sp:
            pass
        engine.evaluate()
        (a,) = [x for x in engine.active() if x["rule"] == "rss_watermark"]
        assert a["state"] == "firing"
        assert a["exemplar_trace_id"] == sp.trace_id

    def test_history_ring_is_bounded(self, monkeypatch):
        monkeypatch.setattr(config, "alert_pending_ticks", 1)
        monkeypatch.setattr(config, "alert_history_capacity", 3)
        for _ in range(5):
            monkeypatch.setattr(config, "alert_rss_bytes", 1)
            engine.evaluate()
            monkeypatch.setattr(config, "alert_rss_bytes", 1 << 60)
            engine.evaluate()
        assert len(engine.history()) == 3

    def test_export_and_prometheus_are_catalog_complete(self):
        engine.evaluate()
        ex = engine.export()
        assert set(ex) == set(RULE_CATALOG)
        assert all(
            set(v) == {"firing", "pending"} for v in ex.values()
        )
        text = render_alerts_prometheus()
        assert lint_exposition(text) == []
        for rule in RULE_CATALOG:
            assert f'orienttpu_alert_firing{{rule="{rule}"}}' in text

    def test_snapshot_all_carries_alerts_and_stays_promlint_clean(self):
        from orientdb_tpu.obs.registry import (
            render_prometheus,
            render_prometheus_multi,
            snapshot_all,
        )

        snap = snapshot_all()
        assert set(snap["alerts"]) == set(RULE_CATALOG)
        assert lint_exposition(render_prometheus()) == []
        # JSON round trip (the /cluster/metrics fan-in path) + labels
        rt = json.loads(json.dumps(snap))
        text = render_prometheus_multi({"m1": rt, "m2": rt})
        assert lint_exposition(text) == []
        assert 'orienttpu_alert_firing{rule="breaker_open",member="m1"}' in text


class TestLatencyBaselineAndBurn:
    def _snap(self, qs):
        return {
            "counters": {},
            "gauges": {},
            "durations": {},
            "histograms": {},
            "query_stats": qs,
            "alerts": {},
        }

    def test_latency_regression_against_online_baseline(
        self, monkeypatch
    ):
        """Four 10ms-mean ticks warm the EWMA+MAD baseline; a 200ms
        tick breaches it; the exemplar joins the worst matching slowlog
        entry by fingerprint."""
        from orientdb_tpu.obs.slowlog import slowlog

        monkeypatch.setattr(config, "alert_pending_ticks", 1)
        monkeypatch.setattr(config, "alert_latency_min_calls", 5)
        monkeypatch.setattr(config, "alert_latency_mads", 6.0)
        monkeypatch.setattr(config, "slow_query_ms", 1.0)
        eng = AlertEngine()
        calls, total = 0, 0.0
        for _ in range(4):
            calls += 10
            total += 10 * 0.010
            eng.evaluate(snap=self._snap({"fp1": {
                "calls": calls, "total_s": round(total, 6), "errors": 0,
            }}))
        assert not [
            a for a in eng.active() if a["rule"] == "latency_regression"
        ]
        slowlog.record(
            "SELECT 1", 0.2, "tpu", trace_id="texemplar1",
            fingerprint="fp1",
        )
        calls += 10
        total += 10 * 0.200
        eng.evaluate(snap=self._snap({"fp1": {
            "calls": calls, "total_s": round(total, 6), "errors": 0,
        }}))
        (a,) = [
            a for a in eng.active() if a["rule"] == "latency_regression"
        ]
        assert a["state"] == "firing" and a["key"] == "fp1"
        assert a["exemplar_trace_id"] == "texemplar1"
        assert eng.summary()["baselines"] == 1
        slowlog.clear()

    def test_sustained_regression_fires_through_the_pending_dwell(
        self, monkeypatch
    ):
        """A breaching tick must NOT fold into its own baseline: with
        alert_pending_ticks=2 (the default dwell) a sustained 20x step
        still reaches firing on the second breaching tick — the EWMA
        cannot learn the regression out from under the pending alert."""
        monkeypatch.setattr(config, "alert_pending_ticks", 2)
        monkeypatch.setattr(config, "alert_latency_min_calls", 5)
        monkeypatch.setattr(config, "alert_latency_mads", 6.0)
        eng = AlertEngine()
        calls, total = 0, 0.0
        for _ in range(4):
            calls += 10
            total += 10 * 0.010
            eng.evaluate(snap=self._snap({"fp1": {
                "calls": calls, "total_s": round(total, 6), "errors": 0,
            }}))
        for want_state in ("pending", "firing"):
            calls += 10
            total += 10 * 0.200
            eng.evaluate(snap=self._snap({"fp1": {
                "calls": calls, "total_s": round(total, 6), "errors": 0,
            }}))
            (a,) = [
                x for x in eng.active()
                if x["rule"] == "latency_regression"
            ]
            assert a["state"] == want_state

    def test_two_window_burn_rate(self, monkeypatch):
        monkeypatch.setattr(config, "alert_pending_ticks", 1)
        monkeypatch.setattr(config, "alert_slo_error_rate", 0.01)
        monkeypatch.setattr(config, "alert_burn_factor", 2.0)
        eng = AlertEngine()
        # seed a base sample OLDER than the long window so the history
        # genuinely spans both windows
        eng._burn_samples.append((time.time() - 700.0, 100, 0))
        eng.evaluate(snap=self._snap({"fp1": {
            "calls": 200, "total_s": 2.0, "errors": 50,
        }}))
        (a,) = [
            a for a in eng.active() if a["rule"] == "error_burn_rate"
        ]
        assert a["state"] == "firing"
        # healthy traffic resolves it
        eng.evaluate(snap=self._snap({"fp1": {
            "calls": 20200, "total_s": 3.0, "errors": 50,
        }}))
        assert not [
            a for a in eng.active() if a["rule"] == "error_burn_rate"
        ]

    def test_young_history_cannot_page_the_burn_rule(self, monkeypatch):
        """Until the sample history SPANS the long window, the burn
        rule stays silent: a transient blip right after startup must
        not read as a long-window burn (the exact page the two-window
        condition exists to absorb)."""
        monkeypatch.setattr(config, "alert_pending_ticks", 1)
        monkeypatch.setattr(config, "alert_slo_error_rate", 0.01)
        monkeypatch.setattr(config, "alert_burn_factor", 2.0)
        eng = AlertEngine()
        eng.evaluate(snap=self._snap({"fp1": {
            "calls": 100, "total_s": 1.0, "errors": 0,
        }}))
        eng.evaluate(snap=self._snap({"fp1": {
            "calls": 200, "total_s": 2.0, "errors": 90,
        }}))
        assert not [
            a for a in eng.active() if a["rule"] == "error_burn_rate"
        ]

    def test_concurrent_evaluations_serialize(self):
        """Several in-process servers each tick the shared engine —
        whole ticks serialize under the evaluation lock, so N threads
        hammering evaluate() never corrupt the learning state."""
        import threading

        eng = AlertEngine()
        snap = self._snap({"fp1": {
            "calls": 10, "total_s": 0.1, "errors": 0,
        }})
        errs = []

        def hammer():
            try:
                for _ in range(50):
                    eng.evaluate(snap=snap)
            except Exception as e:  # pragma: no cover - the assert
                errs.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert eng.summary()["ticks"] == 200


def wait_for(cond, timeout=20.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


@pytest.fixture()
def quorum_pair(monkeypatch):
    """Primary + one replica under majority quorum, watchdog threads
    disabled (ticks are driven manually for determinism), puller
    interval long enough that pulls cannot heal mid-assertion."""
    from orientdb_tpu.parallel.cluster import Cluster
    from orientdb_tpu.server.server import Server

    monkeypatch.setattr(config, "watchdog_enabled", False)
    servers = [Server(admin_password="pw") for _ in range(2)]
    for s in servers:
        s.startup()
    pdb = servers[0].create_database("adb")
    cl = Cluster(
        "adb", user="admin", password="pw", interval=30.0,
        down_after=10_000, write_quorum="majority", quorum_timeout=0.5,
    )
    cl.set_primary("n0", servers[0], pdb)
    cl.add_replica("n1", servers[1])
    cl.start()
    pdb.schema.create_vertex_class("P")
    # sync the replica once so the fault window starts from lag 0
    cl.members["n1"].puller.pull_once()
    yield cl, servers, pdb
    cl.stop()
    for s in servers:
        try:
            s.shutdown()
        except Exception:
            pass


class TestChaosAlertsEndToEnd:
    def test_repl_push_drops_fire_lag_and_breaker_alerts(
        self, quorum_pair, monkeypatch
    ):
        """The acceptance path: a FaultPlan dropping every repl.push
        starves the replica (lag builds AND the repl:<url> breaker
        trips), a replication-lag alert and a breaker-open alert each
        walk pending → firing with a valid exemplar trace id — visible
        through GET /alerts, /cluster/health, the debug bundle, and
        console ALERTS — and return to resolved once the fault clears
        and the replica catches up."""
        from orientdb_tpu.parallel.resilience import breaker_snapshot

        cl, servers, pdb = quorum_pair
        monkeypatch.setattr(config, "alert_pending_ticks", 2)
        monkeypatch.setattr(config, "alert_repl_lag_entries", 2)
        url = f"http://127.0.0.1:{servers[0].http_port}"
        wd = HealthWatchdog(servers[0])  # manual ticks, no thread

        plan = FaultPlan(seed=7).at("repl.push", "drop", times=None)
        fault.arm(plan)
        try:
            for i in range(6):
                try:
                    pdb.new_vertex("P", uid=i)
                except Exception:
                    pass  # quorum unreachable by design
        finally:
            fault.disarm()
        assert plan.fired("repl.push") >= 5
        assert any(
            b["state"] == "open" for b in breaker_snapshot().values()
        ), "dropped pushes should have tripped the repl breaker"

        wd.tick()
        doc = _get(f"{url}/alerts")
        lag, br = _alert(doc, "replication_lag"), _alert(doc, "breaker_open")
        assert lag is not None and lag["state"] == "pending"
        assert br is not None and br["state"] == "pending"
        wd.tick()
        doc = _get(f"{url}/alerts")
        lag, br = _alert(doc, "replication_lag"), _alert(doc, "breaker_open")
        assert lag["state"] == "firing" and br["state"] == "firing"
        # valid exemplars: real trace ids from the tracer ring, joining
        # the alert into the trace plane
        ring_tids = {s.trace_id for s in tracer.spans()}
        assert lag["exemplar_trace_id"] in ring_tids
        assert br["exemplar_trace_id"] in ring_tids
        assert lag["key"] == "n1" and lag["value"] > 2

        # every surface shows the firing alerts
        health = _get(f"{url}/cluster/health")
        firing = {
            a["rule"]
            for a in health["alerts"]["active"]
            if a["state"] == "firing"
        }
        assert {"replication_lag", "breaker_open"} <= firing
        bundle = _get(f"{url}/debug/bundle")
        assert {
            a["rule"]
            for a in bundle["alerts"]["active"]
            if a["state"] == "firing"
        } >= {"replication_lag", "breaker_open"}
        from orientdb_tpu.tools.console import Console

        buf = io.StringIO()
        Console(stdout=buf).onecmd("ALERTS")
        out = buf.getvalue()
        assert "replication_lag" in out and "breaker_open" in out
        assert "firing" in out
        buf = io.StringIO()
        Console(stdout=buf).onecmd("HEALTH")
        assert "firing=2" in buf.getvalue()
        # prometheus state gauges flip to 1
        text, _ = _get(f"{url}/alerts?format=prometheus", raw=True)
        assert 'orienttpu_alert_firing{rule="replication_lag"} 1' in text
        assert lint_exposition(text) == []

        # clear the fault: replica catches up, breaker closes
        cl.members["n1"].puller.pull_once()
        for name, b in breaker_snapshot().items():
            if b["state"] == "open":
                from orientdb_tpu.parallel.resilience import breaker

                brk = breaker(name)
                brk.reset_s = 0.01
                time.sleep(0.02)
                brk.call(lambda: 1)  # half-open probe succeeds
        wd.tick()
        doc = _get(f"{url}/alerts")
        assert _alert(doc, "replication_lag") is None
        assert _alert(doc, "breaker_open") is None
        resolved = {h["rule"] for h in doc["history"]}
        assert {"replication_lag", "breaker_open"} <= resolved
        for h in doc["history"]:
            assert h["state"] == "resolved"


class TestLogCorrelation:
    def test_log_records_carry_active_trace_ids(self, monkeypatch):
        monkeypatch.setattr(config, "log_ring_capacity", 64)
        log = get_logger("alerttest")
        log_ring.clear()
        with span("query") as sp:
            log.warning("inside the span")
        log.warning("outside any span")
        entries = log_ring.entries()
        inside = next(e for e in entries if "inside" in e["msg"])
        outside = next(e for e in entries if "outside" in e["msg"])
        assert inside["trace_id"] == sp.trace_id
        assert inside["span_id"] == sp.span_id
        assert outside["trace_id"] is None
        log_ring.clear()

    def test_json_formatter_emits_structured_lines_with_trace(self):
        logger = logging.getLogger("orientdb_tpu.jsontest")
        buf = io.StringIO()
        h = logging.StreamHandler(buf)
        h.setFormatter(JsonFormatter())
        logger.addHandler(h)
        try:
            with span("query") as sp:
                logger.warning("structured %s", "line")
        finally:
            logger.removeHandler(h)
        doc = json.loads(buf.getvalue().strip())
        assert doc["msg"] == "structured line"
        assert doc["level"] == "WARNING"
        assert doc["trace_id"] == sp.trace_id
        assert doc["span_id"] == sp.span_id

    def test_default_text_format_is_unchanged(self):
        """ORIENTTPU_LOG_FORMAT unset keeps the classic text format on
        the root stream handler — existing log-format assertions stay
        green."""
        from orientdb_tpu.utils.logging import _FORMAT

        assert os.environ.get("ORIENTTPU_LOG_FORMAT", "") == ""
        fmts = [
            getattr(getattr(h, "formatter", None), "_fmt", None)
            for h in logging.getLogger().handlers
        ]
        assert not any(
            isinstance(h.formatter, JsonFormatter)
            for h in logging.getLogger().handlers
            if h.formatter is not None
        )
        assert _FORMAT == "%(asctime)s %(levelname)s [%(name)s] %(message)s"
        # a formatter is only set once basicConfig ran with our format
        assert any(f == _FORMAT for f in fmts if f)

    def test_ring_is_bounded_and_feeds_the_bundle(self, monkeypatch):
        from orientdb_tpu.obs.bundle import debug_bundle

        monkeypatch.setattr(config, "log_ring_capacity", 5)
        log = get_logger("ringtest")
        log_ring.clear()
        for i in range(20):
            log.warning("ring entry %d", i)
        entries = log_ring.entries()
        assert len(entries) == 5
        assert entries[0]["msg"] == "ring entry 19"  # most recent first
        b = debug_bundle()
        assert [e["msg"] for e in b["logs"]] == [
            e["msg"] for e in entries
        ]
        log_ring.clear()

    def test_bundle_logs_are_admin_only(self, monkeypatch):
        """The logs ring ships only inside /debug/bundle, which already
        requires the admin grant — a reader gets 403, never the logs."""
        from orientdb_tpu.server.server import Server

        monkeypatch.setattr(config, "watchdog_enabled", False)
        srv = Server(admin_password="pw").startup()
        try:
            url = f"http://127.0.0.1:{srv.http_port}"
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{url}/debug/bundle", user="reader", password="reader")
            assert ei.value.code == 403
            assert "logs" in _get(f"{url}/debug/bundle")
        finally:
            srv.shutdown()


class TestWatchdogLifecycleAndOverhead:
    def test_watchdog_starts_and_stops_with_server(self, monkeypatch):
        from orientdb_tpu.server.server import Server

        monkeypatch.setattr(config, "watchdog_enabled", True)
        monkeypatch.setattr(config, "watchdog_interval_s", 0.02)
        srv = Server(admin_password="pw").startup()
        try:
            assert srv._watchdog is not None
            assert wait_for(lambda: engine.summary()["ticks"] >= 2)
            # the tick span is cataloged and recorded
            assert tracer.spans(name="watchdog.tick")
        finally:
            srv.shutdown()
        assert srv._watchdog is None
        ticks = engine.summary()["ticks"]
        time.sleep(0.1)
        assert engine.summary()["ticks"] == ticks  # loop really stopped

    def test_disabled_watchdog_never_starts(self, monkeypatch):
        from orientdb_tpu.server.server import Server

        monkeypatch.setattr(config, "watchdog_enabled", False)
        srv = Server(admin_password="pw").startup()
        try:
            assert srv._watchdog is None
            assert engine.summary()["ticks"] == 0
        finally:
            srv.shutdown()

    def test_watchdog_overhead_off_the_query_hot_path(self, monkeypatch):
        """The PR-4-style guard: a query loop beside a fast-ticking
        watchdog. Rule evaluation rides the tick thread, never the
        query path, and what it costs is read where it is spent: the
        program's own counters (``watchdog.ticks``, and the span fold's
        ``span.watchdog.tick.us``) over the loop's wall. Best of 3,
        generous threshold: this asserts the mechanism, not the
        microbenchmark (two wall-clock loops side by side are a coin
        toss under six xdist workers on one host)."""
        import threading

        from orientdb_tpu.models.database import Database
        from orientdb_tpu.models.schema import PropertyType
        from orientdb_tpu.obs.stats import stats as _qstats
        from orientdb_tpu.obs.trace import tracer
        from orientdb_tpu.utils.metrics import metrics as _metrics

        # earlier tests in this file bloat the process-global stats
        # table / metric registry / alert state, and every tick
        # snapshots ALL of it on the tick thread. Reset so the guard
        # measures the watchdog mechanism, not the suite's accumulated
        # registry.
        _qstats.reset()
        _metrics.reset()
        engine.reset()

        db = Database("wd_overhead")
        P = db.schema.create_vertex_class("P")
        P.create_property("age", PropertyType.LONG)
        for i in range(10):
            db.new_vertex("P", uid=i, age=20 + i)
        q = "SELECT count(*) AS n FROM P WHERE age > 25"
        n = 200

        def loop(seconds=0.0):
            """At least ``n`` queries, and then until ``seconds``."""
            t0 = time.perf_counter()
            done = 0
            while done < n or time.perf_counter() - t0 < seconds:
                db.query(q).to_dicts()
                done += 1
            return time.perf_counter() - t0, done

        class _Host:  # duck-typed server: databases + no cluster
            databases = {"wd_overhead": db}
            cluster = None

        loop()  # warm parse/plan caches
        # 10 Hz is 50x the production tick rate and lands >5 ticks
        # per measured loop. A tick's span is wall time on a thread
        # that has to win the interpreter from the loop for every
        # stretch of it (~1-2 ms of work reads as ~7 ms, 20-odd on a
        # crowded host: a share of 6 to 20 %), and the share is held
        # under the 35 % the guard has always allowed
        wd = HealthWatchdog(_Host(), interval=0.1)
        wd.tick()  # the first evaluation imports what it reads

        tick_threads = set()

        def on_span(sp):
            if sp.name == "watchdog.tick":
                tick_threads.add(threading.current_thread().name)

        def counters():
            c = _metrics.snapshot()["counters"]
            return (
                c.get("watchdog.ticks", 0),
                c.get("span.watchdog.tick.us", 0),
                c.get("span.watchdog.tick.n", 0),
            )

        ticks, shares, queries = [], [], []
        tracer.add_listener(on_span)
        try:
            for _ in range(3):
                wd.start()
                try:
                    t0, us0, n0 = counters()
                    wall, done = loop(1.0)
                    t1, us1, n1 = counters()
                finally:
                    wd.stop()
                # one tick may still be open across either reading
                assert abs((t1 - t0) - (n1 - n0)) <= 1
                ticks.append(t1 - t0)
                shares.append((us1 - us0) / 1e6 / wall)
                queries.append(done)
        finally:
            tracer.remove_listener(on_span)
        assert engine.summary()["ticks"] > 0  # it really was ticking
        assert max(ticks) >= 5, ticks
        assert tick_threads == {"health-watchdog"}, tick_threads
        assert min(shares) < 0.35, (
            f"watchdog ticks held {min(shares):.0%} of the loop's wall "
            f"({ticks} ticks over {queries} queries)"
        )

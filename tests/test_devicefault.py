"""Device fault domain (exec/devicefault, ISSUE 18): classification,
every rung of the escalation ladder (retry → relief → quarantine →
probe re-admission → admission shed), the engine front-door quarantine
gates, crash integrity (SimulatedCrash propagates through every new
wrapper), the seeded FaultPlan end-to-end recovery story, and the
observability surfaces (bundle block, alert rule, device lint)."""

import threading
import time

import pytest

from orientdb_tpu.chaos import FaultPlan, SimulatedCrash, fault
from orientdb_tpu.chaos.faults import POINTS
from orientdb_tpu.exec import devicefault
from orientdb_tpu.exec.devicefault import (
    OOM,
    PERSISTENT,
    PROGRAM,
    TRANSIENT,
    DeviceFaultError,
    DeviceOomError,
    DeviceQuarantined,
    classify,
    domain,
)
from orientdb_tpu.ops.predicates import Uncompilable
from orientdb_tpu.storage.ingest import generate_demodb
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics


SQL = (
    "MATCH {class:Profiles, as:p, where:(uid = :u)}"
    "-HasFriend->{as:f} RETURN count(*) AS n"
)


@pytest.fixture(autouse=True)
def _clean_domain(monkeypatch):
    # materialized views would serve a hot fingerprint without touching
    # the device — the chaos crossings these tests drive would never
    # fire (exec/views admission is call-count gated)
    monkeypatch.setattr(config, "view_min_calls", 10**9)
    fault.disarm()
    domain.reset()
    yield
    fault.disarm()
    domain.reset()


@pytest.fixture(scope="module")
def db():
    d = generate_demodb(n_profiles=300, avg_friends=4, seed=18)
    attach_fresh_snapshot(d)
    return d


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


def _warm(db):
    """Record + compile SQL so the chaos crossings sit on the replay
    dispatch path (not the recording one)."""
    from orientdb_tpu.exec.tpu_engine import drain_warmups

    for u in (0, 3):
        db.query(SQL, params={"u": u}, engine="tpu", strict=True)
    drain_warmups()


class TestClassification:
    def test_oom_markers(self):
        assert classify(RuntimeError(
            "RESOURCE_EXHAUSTED: out of memory allocating 4096 bytes"
        )) == OOM
        assert classify(RuntimeError("failed to allocate HBM")) == OOM
        assert classify(DeviceOomError("boom")) == OOM

    def test_chaos_point_name_classifies_oom(self):
        """A plain `error` rule at tpu.oom needs no custom exception:
        the injected message carries the point name."""
        plan = FaultPlan(seed=1).at("tpu.oom", "error", times=1)
        with fault.armed(plan):
            with pytest.raises(Exception) as ei:
                devicefault.dispatch_point()
        assert classify(ei.value) == OOM

    def test_persistent_markers(self):
        assert classify(ValueError(
            "INVALID_ARGUMENT: dot dimension mismatch"
        )) == PERSISTENT
        assert classify(RuntimeError("UNIMPLEMENTED: no kernel")) == (
            PERSISTENT
        )

    def test_default_transient(self):
        assert classify(RuntimeError("connection reset")) == TRANSIENT
        assert classify(
            DeviceFaultError("x", kind=TRANSIENT)
        ) == TRANSIENT

    def test_trace_time_errors_are_program_bugs(self):
        """What JAX raises while tracing or lowering is a bug in the
        program, not a device fault — unless it carries XLA status
        text, which classifies as before."""
        import jax
        import jax.numpy as jnp

        with pytest.raises(TypeError) as ei:
            jax.jit(
                lambda x: jax.lax.cond(
                    x > 0, lambda: jnp.int8(1), lambda: jnp.int32(1)
                )
            )(1)
        assert classify(ei.value) == PROGRAM
        assert classify(ValueError("incompatible shapes")) == PROGRAM
        assert classify(NotImplementedError("no rule")) == PROGRAM
        assert classify(ValueError("INVALID_ARGUMENT: x")) == PERSISTENT
        assert classify(TypeError("RESOURCE_EXHAUSTED: hbm")) == OOM

    def test_new_points_in_catalog(self):
        assert {"tpu.dispatch", "tpu.transfer", "tpu.oom"} <= POINTS


class TestGuard:
    def test_transient_retries_then_succeeds(self):
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient device blip")
            return 42

        assert domain.run(fn, stage="t") == 42
        s = domain.snapshot()
        assert s["classified"].get("transient") == 1
        assert s["retries"] == 1
        assert s["quarantines_total"] == 0

    def test_trace_time_typeerror_raises_to_caller(self):
        """A TypeError raised while tracing inside a guarded dispatch
        reaches the caller as itself: no retry, no fault record, no
        quarantine, no DeviceQuarantined("... serving oracle")."""
        import jax
        import jax.numpy as jnp

        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            return jax.jit(
                lambda x: jax.lax.cond(
                    x > 0, lambda: jnp.zeros(3), lambda: jnp.zeros(4)
                )
            )(1)

        sql = "SELECT 1 FROM TraceBug"
        with pytest.raises(TypeError) as ei:
            domain.run(fn, sql=sql, stage="t")
        assert not isinstance(ei.value, Uncompilable)
        assert calls["n"] == 1
        s = domain.snapshot()
        assert s["classified"] == {} and s["retries"] == 0
        assert s["quarantines_total"] == 0 and domain.admit(sql) is None

    def test_persistent_skips_retry_and_quarantines(self):
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            raise ValueError("INVALID_ARGUMENT: broken program")

        with pytest.raises(DeviceQuarantined) as ei:
            domain.run(fn, sql="SELECT 1 FROM Broken", stage="t")
        assert calls["n"] == 1, "persistent faults must not retry"
        assert ei.value.retry_after is not None
        assert isinstance(ei.value, Uncompilable)
        assert domain.admit("SELECT 1 FROM Broken") == "quarantined"
        (row,) = domain.snapshot()["quarantined"]
        assert row["kind"] == PERSISTENT and row["strikes"] == 1

    def test_retry_exhaustion_quarantines(self, monkeypatch):
        monkeypatch.setattr(config, "devicefault_retry_attempts", 2)
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            raise RuntimeError("always flaky")

        with pytest.raises(DeviceQuarantined):
            domain.run(fn, sql="SELECT 2 FROM Flaky", stage="t")
        assert calls["n"] == 2
        assert domain.admit("SELECT 2 FROM Flaky") == "quarantined"

    def test_uncompilable_and_passthrough_bypass_the_ladder(self):
        class Overflow(Exception):
            pass

        with pytest.raises(Uncompilable):
            domain.run(lambda: (_ for _ in ()).throw(
                Uncompilable("not my problem")
            ))
        with pytest.raises(Overflow):
            domain.run(
                lambda: (_ for _ in ()).throw(Overflow()),
                passthrough=(Overflow,),
            )
        assert domain.snapshot()["classified"] == {}

    def test_simulated_crash_propagates(self):
        def fn():
            raise SimulatedCrash("kill -9")

        with pytest.raises(SimulatedCrash):
            domain.run(fn, sql="SELECT 3 FROM Crash", stage="t")
        # a crash is not a device fault: nothing classified, nothing
        # quarantined — restart-recovery tests own this path
        s = domain.snapshot()
        assert s["classified"] == {} and s["quarantined"] == []

    def test_oom_relieves_once_before_retry(self, monkeypatch):
        relieved = []
        monkeypatch.setattr(
            domain, "relieve",
            lambda db=None, tier=None: relieved.append(1) or ["x"],
        )
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("RESOURCE_EXHAUSTED: oom")
            return "ok"

        assert domain.run(fn, stage="t") == "ok"
        assert len(relieved) == 1, "relief actuates once per section"
        assert domain.snapshot()["classified"]["oom"] == 2

    def test_success_with_sql_clears_probe(self, monkeypatch):
        monkeypatch.setattr(
            config, "devicefault_quarantine_ttl_s", 0.15
        )
        sql = "SELECT 4 FROM Probe"
        with pytest.raises(DeviceQuarantined):
            domain.run(
                lambda: (_ for _ in ()).throw(
                    ValueError("INVALID_ARGUMENT: x")
                ),
                sql=sql,
            )
        time.sleep(0.2)
        assert domain.admit(sql) == "probe"
        assert domain.run(lambda: "fine", sql=sql) == "fine"
        assert domain.admit(sql) is None
        assert domain.snapshot()["readmitted"] == 1


class TestQuarantine:
    def _convict(self, sql):
        with pytest.raises(DeviceQuarantined):
            domain.run(
                lambda: (_ for _ in ()).throw(
                    ValueError("INVALID_ARGUMENT: x")
                ),
                sql=sql,
            )

    def test_ttl_probe_and_single_probe_window(self, monkeypatch):
        monkeypatch.setattr(
            config, "devicefault_quarantine_ttl_s", 0.15
        )
        sql = "SELECT 5 FROM Q"
        self._convict(sql)
        assert domain.admit(sql) == "quarantined"
        time.sleep(0.2)
        assert domain.admit(sql) == "probe"
        # a second caller while the probe is out keeps serving oracle
        assert domain.admit(sql) == "quarantined"
        domain.note_success(sql)
        assert domain.admit(sql) is None

    def test_failed_probe_strikes_and_doubles_ttl(self, monkeypatch):
        monkeypatch.setattr(
            config, "devicefault_quarantine_ttl_s", 0.15
        )
        sql = "SELECT 6 FROM Q"
        self._convict(sql)
        time.sleep(0.2)
        assert domain.admit(sql) == "probe"
        self._convict(sql)  # the probe dispatch faulted again
        (row,) = domain.snapshot()["quarantined"]
        assert row["strikes"] == 2
        assert row["ttl_s"] > 0.15 * 1.5  # exponential backoff

    def test_unfingerprinted_sections_never_quarantine(self):
        with pytest.raises(DeviceQuarantined):
            domain.run(
                lambda: (_ for _ in ()).throw(
                    ValueError("INVALID_ARGUMENT: x")
                ),
                sql=None,
            )
        assert domain.snapshot()["quarantined"] == []


class TestRelief:
    def test_tier_eviction_is_lru_and_skips_pinned(self):
        class _Part:
            def __init__(self):
                self.B = 4
                self.page_of = [0, 1, -1, 2]
                self.pins = {1: 1}  # pinned: in-flight footprint
                self.lru = {0: 2.0, 1: 1.0, 3: 0.5}

        class _Tier:
            def __init__(self):
                self.lock = threading.RLock()
                self.parts = {"E": _Part()}
                self.evicted = []

            def _evict(self, part, b):
                self.evicted.append(b)
                part.page_of[b] = -1

        t = _Tier()
        actions = domain.relieve(tier=t)
        assert "tier_evict" in actions
        assert t.evicted == [3, 0], "LRU order, pinned block skipped"

    def test_overlay_poison_is_idempotent(self):
        class _Overlay:
            poisoned = None

            def poison(self, reason):
                self.poisoned = reason

        class _Maint:
            def __init__(self):
                self.overlay = _Overlay()

        class _Db:
            def __init__(self):
                self._snapshot_maintainer = _Maint()

        d = _Db()
        assert domain._poison_overlay(d) is True
        assert "compact" in d._snapshot_maintainer.overlay.poisoned
        assert domain._poison_overlay(d) is False  # already poisoned

    def test_relief_failures_never_replace_the_fault(self):
        class _BadTier:
            @property
            def lock(self):
                raise RuntimeError("tier is on fire")

        # the classified OOM must still surface as DeviceQuarantined,
        # not the relief actuator's own failure
        with pytest.raises(DeviceQuarantined) as ei:
            domain.run(
                lambda: (_ for _ in ()).throw(
                    RuntimeError("RESOURCE_EXHAUSTED: oom")
                ),
                sql="SELECT 7 FROM R",
                tier=_BadTier(),
            )
        assert "oom" in str(ei.value)


class TestShed:
    def test_oom_escalation_arms_then_self_clears(self, monkeypatch):
        monkeypatch.setattr(config, "devicefault_shed_s", 0.2)
        monkeypatch.setattr(config, "devicefault_retry_attempts", 1)
        with pytest.raises(DeviceQuarantined):
            domain.run(
                lambda: (_ for _ in ()).throw(
                    RuntimeError("out of memory")
                ),
                sql="SELECT 8 FROM S",
            )
        reason, after = domain.shed_state()
        assert reason is not None and after > 0
        from orientdb_tpu.server.admission import db_pressure

        shed, retry = db_pressure(object())
        assert shed is not None and shed.startswith(
            "device memory pressure"
        )
        assert retry >= after - 0.05
        time.sleep(0.25)
        assert domain.shed_state() == (None, 0.0)
        assert db_pressure(object())[0] is None

    def test_headroom_arms_shed_on_non_oom_escalation(
        self, monkeypatch
    ):
        monkeypatch.setattr(config, "devicefault_retry_attempts", 1)
        monkeypatch.setattr(
            domain, "_ledger_over_headroom", lambda: True
        )
        with pytest.raises(DeviceQuarantined):
            domain.run(
                lambda: (_ for _ in ()).throw(
                    RuntimeError("transient-looking")
                ),
                sql="SELECT 9 FROM S",
            )
        reason, _after = domain.shed_state()
        assert reason == "memledger total over headroom fraction"

    def test_client_maps_device_503(self):
        from orientdb_tpu.client.remote import (
            DeviceTransientError,
            RemoteDatabase,
            ServerOverloadedError,
        )

        rd = RemoteDatabase.__new__(RemoteDatabase)
        rd._call = lambda req: {
            "ok": False, "code": 503, "device": True,
            "retry_after": 1.5, "error": "device fault",
        }
        with pytest.raises(DeviceTransientError) as ei:
            rd._checked({"op": "query"})
        assert ei.value.retry_after == 1.5
        rd._call = lambda req: {
            "ok": False, "code": 503, "error": "overloaded",
        }
        with pytest.raises(ServerOverloadedError):
            rd._checked({"op": "query"})


class TestEngineIntegration:
    def test_transient_dispatch_blip_is_invisible(self, db):
        _warm(db)
        want = db.query(
            SQL, params={"u": 0}, engine="oracle"
        ).to_dicts()
        plan = FaultPlan(seed=3).at("tpu.dispatch", "error", times=1)
        with fault.armed(plan):
            rs = db.query(SQL, params={"u": 0}, engine="tpu", strict=True)
        assert rs.to_dicts() == want and rs.engine == "tpu"
        assert plan.fired()
        assert domain.snapshot()["retries"] >= 1

    def test_crash_propagates_through_execute(self, db):
        _warm(db)
        plan = FaultPlan(seed=4).at("tpu.dispatch", "crash", times=1)
        with fault.armed(plan):
            with pytest.raises(SimulatedCrash):
                db.query(SQL, params={"u": 0}, engine="tpu", strict=True)
        assert domain.snapshot()["classified"] == {}

    def test_batch_quarantine_keeps_per_item_contract(
        self, db, monkeypatch
    ):
        monkeypatch.setattr(config, "devicefault_retry_attempts", 1)
        _warm(db)
        plist = [{"u": i} for i in range(3)]
        want = [
            db.query(SQL, params=p, engine="oracle").to_dicts()
            for p in plist
        ]
        plan = FaultPlan(seed=5).at("tpu.dispatch", "error", times=50)
        with fault.armed(plan):
            got = [
                rs.to_dicts()
                for rs in db.query_batch(
                    [SQL] * 3, params_list=plist, engine="tpu"
                )
            ]
        assert got == want, "every item still answered (oracle parity)"

    def test_full_ladder_end_to_end(self, db, monkeypatch):
        """The acceptance scenario: a seeded FaultPlan injecting
        tpu.oom + tpu.dispatch mid-traffic drives retry → relief →
        quarantine → oracle parity → shed → probe re-admission back to
        a clean compiled path; zero unclassified device exceptions
        escape."""
        monkeypatch.setattr(
            config, "devicefault_quarantine_ttl_s", 0.3
        )
        monkeypatch.setattr(config, "devicefault_shed_s", 0.3)
        monkeypatch.setattr(config, "devicefault_retry_attempts", 2)
        relieved = []
        real_relieve = devicefault.DeviceFaultDomain.relieve
        monkeypatch.setattr(
            domain, "relieve",
            lambda db=None, tier=None: (
                relieved.append(1),
                real_relieve(domain, db, tier=tier),
            )[1],
        )
        _warm(db)
        want = db.query(
            SQL, params={"u": 1}, engine="oracle"
        ).to_dicts()

        # phase 1 — transient blip: retried away, query unharmed
        p1 = FaultPlan(seed=18).at("tpu.dispatch", "error", times=1)
        with fault.armed(p1):
            rs = db.query(SQL, params={"u": 1}, engine="tpu")
        assert rs.to_dicts() == want and rs.engine == "tpu"
        assert p1.fired()

        # phase 2 — sustained OOM: relief fires, retries exhaust,
        # the plan quarantines, the shed latch arms — and the query
        # STILL answers correctly from the oracle
        p2 = FaultPlan(seed=18).at("tpu.oom", "error", times=50)
        with fault.armed(p2):
            rs = db.query(SQL, params={"u": 1}, engine="tpu")
            assert rs.to_dicts() == want and rs.engine == "oracle"
            assert relieved, "OOM must actuate relief before retrying"
            assert domain.snapshot()["quarantines_total"] >= 1
            reason, _after = domain.shed_state()
            assert reason is not None  # admission is shedding
            from orientdb_tpu.server.admission import db_pressure

            assert db_pressure(object())[0] is not None

            # phase 3 — while quarantined, the gate never reaches the
            # device (the armed plan would fire): straight to oracle
            rs = db.query(SQL, params={"u": 1}, engine="tpu")
            assert rs.to_dicts() == want and rs.engine == "oracle"
            assert domain.snapshot()["oracle_served"] >= 1

        # phase 4 — fault cleared + TTL served: one probe re-admits
        # the plan and traffic is back on the compiled path
        time.sleep(0.4)
        rs = db.query(SQL, params={"u": 1}, engine="tpu")
        assert rs.to_dicts() == want and rs.engine == "tpu"
        s = domain.snapshot()
        assert s["readmitted"] >= 1 and s["quarantined"] == []
        assert s["classified"].get("oom", 0) >= 1
        assert s["classified"].get("transient", 0) >= 1
        time.sleep(0.35)
        assert domain.shed_state() == (None, 0.0)
        # recovered steady state: one more clean compiled round trip
        rs = db.query(SQL, params={"u": 1}, engine="tpu", strict=True)
        assert rs.to_dicts() == want and rs.engine == "tpu"


class TestLanePath:
    SQL1 = "SELECT count(*) AS c FROM Profiles WHERE uid < 40"

    def test_lane_quarantine_falls_back_and_recovers(
        self, db, monkeypatch
    ):
        monkeypatch.setattr(config, "devicefault_retry_attempts", 1)
        monkeypatch.setattr(
            config, "devicefault_quarantine_ttl_s", 0.3
        )
        from orientdb_tpu.server.coalesce import QueryCoalescer
        from orientdb_tpu.exec.tpu_engine import drain_warmups

        db.query(self.SQL1, engine="tpu", strict=True)
        drain_warmups()
        want = db.query(self.SQL1, engine="oracle").to_dicts()
        co = QueryCoalescer()
        try:
            plan = FaultPlan(seed=6).at(
                "tpu.dispatch", "error", times=50
            )
            with fault.armed(plan):
                rows, _e = co.submit(db, self.SQL1, None)
                assert rows == want, "lane fault degraded, not failed"
            # lane stays alive; after the TTL the probe re-admits
            time.sleep(0.4)
            rows, _e = co.submit(db, self.SQL1, None)
            assert rows == want
        finally:
            co.stop()

    def test_crash_propagates_through_lane_collect(self, db):
        import orientdb_tpu.exec.engine as E
        from orientdb_tpu.exec.tpu_engine import drain_warmups

        db.query(self.SQL1, engine="tpu", strict=True)
        drain_warmups()
        h = E.dispatch_lane_batch(db, [self.SQL1], [None])
        if h is None:
            pytest.skip("lane fast path did not engage")
        # the crash lands on the blocking collect-side transfer
        plan = FaultPlan(seed=7).at("tpu.transfer", "crash", times=1)
        with fault.armed(plan):
            with pytest.raises(SimulatedCrash):
                h.collect()


class TestSurfaces:
    def _convict(self, sql):
        with pytest.raises(DeviceQuarantined):
            domain.run(
                lambda: (_ for _ in ()).throw(
                    ValueError("INVALID_ARGUMENT: x")
                ),
                sql=sql,
            )

    def test_bundle_shows_the_domain_snapshot(self):
        self._convict("SELECT 10 FROM V")
        from orientdb_tpu.obs.bundle import debug_bundle

        b = debug_bundle()
        assert b["device_faults"]["quarantines_total"] >= 1
        (row,) = b["device_faults"]["quarantined"]
        assert row["kind"] == "persistent" and row["sql"]

        # the bundle shows the plane's own snapshot: classified counts
        # and the quarantine ledger, nothing summarised beside it
        assert sum(b["device_faults"]["classified"].values()) >= 1
        assert b["device_faults"] == domain.snapshot()

    def test_device_fault_storm_alert(self, monkeypatch):
        from orientdb_tpu.obs.alerts import RULE_CATALOG, AlertEngine

        assert "device_fault_storm" in RULE_CATALOG
        monkeypatch.setattr(config, "alert_pending_ticks", 1)
        monkeypatch.setattr(
            config, "alert_device_faults_per_min", 10.0
        )
        snap = {
            "counters": {}, "gauges": {}, "durations": {},
            "histograms": {}, "query_stats": {}, "alerts": {},
        }
        eng = AlertEngine()
        eng.evaluate(snap=dict(snap))  # establishes the prev sample
        for _ in range(30):
            calls = {"n": 0}

            def fn():
                calls["n"] += 1
                if calls["n"] == 1:
                    raise RuntimeError("blip")
                return None

            domain.run(fn, stage="storm")
        eng.evaluate(snap=dict(snap))
        (a,) = [
            a for a in eng.active()
            if a["rule"] == "device_fault_storm"
        ]
        assert a["state"] == "firing"

    def test_fault_events_reach_the_flight_recorder(self, db):
        import orientdb_tpu.obs.timeline as TL

        _warm(db)
        plan = FaultPlan(seed=8).at("tpu.dispatch", "error", times=1)
        with fault.armed(plan):
            db.query(SQL, params={"u": 2}, engine="tpu", strict=True)
        recs = TL.recorder.records(window_s=60.0, limit=20)
        assert any(
            ev[0] == "device_fault"
            for r in recs
            for ev in r.get("events", [])
        ), "the classified fault must stamp the dispatch's record"


class TestDeviceLint:
    def test_unrouted_device_call_flags(self):
        from orientdb_tpu.chaos.iolint import lint_device_source

        bad = "def up(x):\n    return jax.device_put(x)\n"
        probs = lint_device_source(bad, "exec/foo.py")
        assert len(probs) == 1 and "device boundary" in probs[0]

    def test_routed_and_out_of_plane_sources_pass(self):
        from orientdb_tpu.chaos.iolint import lint_device_source

        ok = (
            "def up(x):\n"
            "    devicefault.transfer_point()\n"
            "    return jax.device_put(x)\n"
        )
        assert lint_device_source(ok, "exec/foo.py") == []
        bad = "def up(x):\n    return jax.device_put(x)\n"
        # host-side storage modules are not device planes
        assert lint_device_source(bad, "storage/wal.py") == []

    def test_repo_tree_is_device_clean(self):
        """The shipped tree itself holds the invariant: every raw
        device call in the device planes routes or is DEVICE_EXEMPT."""
        import os

        from orientdb_tpu.chaos.iolint import (
            DEVICE_SCAN_DIRS,
            lint_device_source,
        )

        import orientdb_tpu

        root = os.path.dirname(os.path.abspath(orientdb_tpu.__file__))
        problems = []
        for d in DEVICE_SCAN_DIRS:
            base = os.path.join(root, d)
            for dirpath, _dirs, files in os.walk(base):
                for f in sorted(files):
                    if not f.endswith(".py"):
                        continue
                    path = os.path.join(dirpath, f)
                    rel = os.path.relpath(path, root).replace(
                        os.sep, "/"
                    )
                    with open(path, "r", encoding="utf-8") as fh:
                        problems += lint_device_source(fh.read(), rel)
        assert problems == []

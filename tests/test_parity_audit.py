"""Continuous correctness plane, auditor half (ISSUE 20): the sampled
shadow-oracle parity auditor (exec/audit) driven end to end — the
shared canonicalization helpers (exec/result) both parity planes use;
clean audits riding the stats sampling decision through the query,
batch, and lane front doors; the seeded ``audit.mismatch`` chaos proof
(detect → replayable divergence record → PR-18 parity quarantine →
``parity_divergence`` alert pending → firing with the divergent
request's trace id as exemplar → TTL probe re-admission → resolve);
stale-epoch invalidation; and queue backpressure. What a request pays
the auditor is counted in tests/test_plane_overhead.py."""

import time

import pytest

from orientdb_tpu.chaos.faults import POINTS, FaultPlan, fault
from orientdb_tpu.exec import audit
from orientdb_tpu.exec.audit import ParityAuditor, auditor
from orientdb_tpu.exec.devicefault import domain
from orientdb_tpu.exec.result import (
    canonical_rows,
    result_digest,
    rows_diff_sample,
)
from orientdb_tpu.obs.alerts import RULE_CATALOG, engine as alert_engine
from orientdb_tpu.obs.spanlint import SPAN_CATALOG
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics

MATCH_ROWS = (
    "MATCH {class:Profiles, as:p}-HasFriend->{as:f} "
    "RETURN p.name AS p, f.name AS f"
)
MATCH_COUNT = (
    "MATCH {class:Profiles, as:p}-HasFriend->{as:f} "
    "RETURN count(*) AS n"
)


def canon(rows):
    return sorted(str(sorted(r.items())) for r in rows)


@pytest.fixture(autouse=True)
def _clean_audit_state():
    fault.disarm()
    auditor.reset()
    domain.reset()
    alert_engine.reset()
    yield
    fault.disarm()
    auditor.reset()
    domain.reset()
    alert_engine.reset()


@pytest.fixture
def compiled_db(social_db):
    """social_db with a fresh snapshot attached (compiled dispatch)."""
    from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

    attach_fresh_snapshot(social_db)
    yield social_db
    social_db.detach_snapshot()


# ---------------------------------------------------------------------------
# the shared canonicalization (exec/result) — THE parity definition
# ---------------------------------------------------------------------------


class TestCanonicalization:
    def test_canonical_rows_is_order_insensitive(self):
        a = [{"x": 1, "y": "a"}, {"x": 2, "y": "b"}]
        b = [{"y": "b", "x": 2}, {"y": "a", "x": 1}]
        assert canonical_rows(a) == canonical_rows(b)
        assert result_digest(a) == result_digest(b)

    def test_digest_detects_any_divergence(self):
        base = [{"n": i} for i in range(5)]
        assert result_digest(base) != result_digest(base[1:])
        mutated = [dict(r) for r in base]
        mutated[3]["n"] = 99
        assert result_digest(base) != result_digest(mutated)

    def test_digest_multiset_semantics(self):
        # duplicated rows are NOT collapsed — row multiplicity is part
        # of result-set parity
        assert result_digest([{"n": 1}, {"n": 1}]) != result_digest(
            [{"n": 1}]
        )

    def test_mixed_type_rows_fall_back_deterministically(self):
        rows = [{"v": 1}, {"v": "one"}]
        assert result_digest(rows) == result_digest(list(reversed(rows)))

    def test_rows_diff_sample_names_both_sides(self):
        served = [{"n": 1}, {"n": 2}]
        oracle = [{"n": 1}, {"n": 3}]
        d = rows_diff_sample(served, oracle, limit=5)
        assert len(d["only_served"]) == 1 and "2" in d["only_served"][0]
        assert len(d["only_oracle"]) == 1 and "3" in d["only_oracle"][0]
        # limit bounds the sample, not the verdict
        wide = rows_diff_sample([{"n": i} for i in range(50)], [], limit=3)
        assert len(wide["only_served"]) == 3


# ---------------------------------------------------------------------------
# catalogs: the new spans / rules / chaos points are registered
# ---------------------------------------------------------------------------


class TestCatalogs:
    def test_span_catalog_has_correctness_plane_stages(self):
        for name in ("audit.shadow", "scrub.sweep", "scrub.repair"):
            assert name in SPAN_CATALOG

    def test_rule_catalog_has_correctness_rules(self):
        assert "parity_divergence" in RULE_CATALOG
        assert "scrub_corruption" in RULE_CATALOG

    def test_chaos_points_registered(self):
        assert "audit.mismatch" in POINTS
        assert "scrub.flip" in POINTS


# ---------------------------------------------------------------------------
# clean audits through every front door
# ---------------------------------------------------------------------------


class TestCleanAudits:
    def test_compiled_queries_audit_clean(self, compiled_db, monkeypatch):
        monkeypatch.setattr(config, "audit_sample_rate", 1.0)
        db = compiled_db
        for sql in (MATCH_ROWS, MATCH_COUNT):
            rs = db.query(sql, engine="tpu", strict=True)
            assert rs.engine == "tpu"
            rs.to_dicts()
        assert auditor.flush(timeout_s=10.0)
        s = auditor.snapshot()
        assert s["submitted"] >= 2
        assert s["audited"] >= 2
        assert s["diverged"] == 0
        assert domain.parity_quarantined() == 0
        assert metrics.snapshot()["counters"].get("parity.audited", 0) >= 2

    def test_batch_door_audits_every_member(self, compiled_db, monkeypatch):
        monkeypatch.setattr(config, "audit_sample_rate", 1.0)
        out = compiled_db.query_batch([MATCH_COUNT, MATCH_ROWS])
        assert [rs.engine for rs in out] == ["tpu", "tpu"]
        assert auditor.flush(timeout_s=10.0)
        s = auditor.snapshot()
        assert s["submitted"] >= 2 and s["diverged"] == 0

    def test_oracle_results_are_not_audited(self, social_db, monkeypatch):
        monkeypatch.setattr(config, "audit_sample_rate", 1.0)
        social_db.query(MATCH_ROWS, engine="oracle").to_dicts()
        assert auditor.snapshot()["submitted"] == 0

    def test_zero_rate_disables_the_plane(self, compiled_db, monkeypatch):
        monkeypatch.setattr(config, "audit_sample_rate", 0.0)
        compiled_db.query(MATCH_ROWS, engine="tpu", strict=True).to_dicts()
        assert auditor.snapshot()["submitted"] == 0


# ---------------------------------------------------------------------------
# stale-epoch invalidation + queue backpressure
# ---------------------------------------------------------------------------


class TestAuditRetirement:
    def test_mutation_between_capture_and_shadow_retires_stale(
        self, social_db
    ):
        """The oracle reads the LIVE store, so a write after capture
        invalidates the compare — the audit must retire as stale, not
        as a false divergence."""
        db = social_db
        cap = audit._Capture(
            db, MATCH_COUNT, {}, [], "t-stale", db.mutation_epoch, None
        )
        db.new_vertex("Profiles", name="zed", age=50, uid=99)
        assert db.mutation_epoch != cap.epoch
        auditor._audit_one(cap)
        s = auditor.snapshot()
        assert s["stale"] == 1
        assert s["audited"] == 0 and s["diverged"] == 0

    def test_full_queue_drops_without_blocking(self, social_db, monkeypatch):
        monkeypatch.setattr(config, "audit_sample_rate", 1.0)
        monkeypatch.setattr(config, "audit_queue_max", 1)
        a = ParityAuditor()
        monkeypatch.setattr(a, "_ensure_worker", lambda: None)

        class _RS:
            engine = "tpu"
            _rows = [{"n": 1}]

        assert a.maybe_submit(social_db, MATCH_COUNT, {}, _RS(), "t1", True)
        assert not a.maybe_submit(
            social_db, MATCH_COUNT, {}, _RS(), "t2", True
        )
        s = a.snapshot()
        assert s["submitted"] == 1 and s["dropped"] == 1


# ---------------------------------------------------------------------------
# the seeded end-to-end proof: detect → quarantine → alert → re-admit
# ---------------------------------------------------------------------------


class TestDivergenceEndToEnd:
    def test_mismatch_detect_quarantine_alert_readmit(
        self, compiled_db, monkeypatch
    ):
        db = compiled_db
        monkeypatch.setattr(config, "audit_sample_rate", 1.0)
        monkeypatch.setattr(config, "alert_pending_ticks", 2)
        # short TTL so the probe re-admission leg runs in-test
        monkeypatch.setattr(config, "devicefault_quarantine_ttl_s", 0.2)
        # the stats table is the process's: where an earlier test file on
        # this worker ran this statement 8 times (view_min_calls), the
        # corrupted answer below would be kept as a materialized view and
        # served again to the probe in step 5 (exec/views takes what was
        # served; ROADMAP C12)
        from orientdb_tpu.obs.stats import stats

        stats.reset()

        oracle_rows = db.query(MATCH_ROWS, engine="oracle").to_dicts()
        assert len(oracle_rows) == 6
        # warm the compiled plan, then reset so the counters below are
        # exactly the faulted execution's
        db.query(MATCH_ROWS, engine="tpu", strict=True).to_dicts()
        assert auditor.flush(timeout_s=10.0)
        auditor.reset()

        # 1. a seeded plan corrupts the SERVED rows of one compiled
        # execution (never the oracle's)
        plan = FaultPlan(seed=7).at("audit.mismatch", "error", times=1)
        with fault.armed(plan):
            rs = db.query(MATCH_ROWS, engine="tpu", strict=True)
            assert rs.engine == "tpu"
            served = rs.to_dicts()
            assert auditor.flush(timeout_s=10.0)
        assert len(served) == len(oracle_rows) - 1  # corruption was served

        # 2. the auditor detected it and produced a replayable record
        s = auditor.snapshot()
        assert s["diverged"] == 1
        rec = auditor.divergences()[-1]
        assert rec["sql"].startswith("MATCH")
        assert rec["trace_id"]
        assert rec["digest_served"] != rec["digest_oracle"]
        assert rec["rows_served"] == 5 and rec["rows_oracle"] == 6
        assert rec["diff"]["only_oracle"]  # the dropped row, by value
        assert rec["fingerprint"]

        # 3. the fingerprint is quarantined: compiled dispatch serves
        # the oracle — degraded but CORRECT
        assert domain.parity_quarantined() == 1
        rs2 = db.query(MATCH_ROWS, engine="tpu")
        assert rs2.engine == "oracle"
        assert canon(rs2.to_dicts()) == canon(oracle_rows)

        # 4. the parity_divergence alert walks pending → firing with
        # the divergent request's trace id as exemplar
        alert_engine.evaluate(dbs=[db])
        a = next(
            x for x in alert_engine.active()
            if x["rule"] == "parity_divergence"
        )
        assert a["state"] == "pending"
        alert_engine.evaluate(dbs=[db])
        a = next(
            x for x in alert_engine.active()
            if x["rule"] == "parity_divergence"
        )
        assert a["state"] == "firing"
        assert a["exemplar_trace_id"] == rec["trace_id"]

        # 5. after the TTL a probe dispatch runs compiled, clean, and
        # re-admits the fingerprint
        time.sleep(0.25)
        rs3 = db.query(MATCH_ROWS, engine="tpu", strict=True)
        assert rs3.engine == "tpu"
        assert canon(rs3.to_dicts()) == canon(oracle_rows)
        assert domain.parity_quarantined() == 0
        assert auditor.flush(timeout_s=10.0)
        assert auditor.snapshot()["diverged"] == 1  # the probe was clean

        # 6. the alert resolves and lands in history
        alert_engine.evaluate(dbs=[db])
        assert not [
            x for x in alert_engine.active()
            if x["rule"] == "parity_divergence"
        ]
        assert any(
            h["rule"] == "parity_divergence"
            for h in alert_engine.history()
        )

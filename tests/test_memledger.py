"""Device-memory ledger (obs/memledger): attributed HBM accounting
with epoch-leak detection — the ISSUE 17 plane end to end.

Covers:

- ledger unit behavior: exact byte totals under register / upsert /
  unregister / drop_owner, peak + watermark tracking, the
  ``memledger_enabled=False`` no-op;
- serving-path wiring: attaching a snapshot attributes its arrays,
  querying attributes plan constants, detaching frees the owner;
- reconciliation against ``jax.live_arrays()`` (structure in-process;
  the within-tolerance acceptance runs in a clean subprocess where no
  other suite module holds device arrays);
- the leak-injection regression (satellite): a ``retain()`` with no
  ``release()`` turns into a stale lease, ``hbm_epoch_leak`` walks
  pending → firing with the retaining span's trace id as exemplar,
  and the release resolves it;
- the ``hbm_headroom`` rule off injected ``tier.cap_bytes`` /
  ``hbm.ledger_bytes`` gauges;
- refusal telemetry (satellite): ``tier.refusals`` dotted counters +
  the last-refusal record, including the real tiered+overlay path;
- surfaces: ``GET /debug/memory`` (admin-only), the bundle ``memory``
  section, console ``MEMORY``, scrape gauges + promlint-clean
  exposition.

What a request pays the ledger is counted in
tests/test_plane_overhead.py.
"""

import io
import json
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from orientdb_tpu.obs.alerts import engine
from orientdb_tpu.obs.memledger import (
    OWNER_KINDS,
    ledger_telemetry,
    memledger,
)
from orientdb_tpu.obs.trace import span
from orientdb_tpu.storage.ingest import generate_demodb
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics

COUNT_2HOP = (
    "MATCH {class:Profiles, as:p, where:(uid = :u)}"
    "-HasFriend->{as:f}-HasFriend->{as:g} RETURN count(*) AS n"
)


@pytest.fixture(autouse=True)
def _clean_ledger():
    """The ledger and the alert plane are process singletons; every
    test here starts from empty state and leaves none behind (a stale
    lease left over would fire hbm_epoch_leak in someone else's
    watchdog tick)."""
    memledger.reset()
    engine.reset()
    yield
    memledger.reset()
    engine.reset()


def _get(url, user="admin", password="pw"):
    import base64
    import urllib.request

    cred = base64.b64encode(f"{user}:{password}".encode()).decode()
    req = urllib.request.Request(
        url, headers={"Authorization": f"Basic {cred}"}
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


# ---------------------------------------------------------------------------
# ledger unit behavior
# ---------------------------------------------------------------------------


class TestLedgerUnit:
    def test_register_upsert_unregister_exact_totals(self):
        a = jnp.zeros((32, 32), dtype=jnp.int32)
        memledger.register("snapshot", "o1", "own", arr=a)
        assert memledger.totals()["snapshot"] == a.nbytes
        # upsert: same identity, new bytes — totals move, not double
        b = jnp.zeros((64, 32), dtype=jnp.int32)
        memledger.register("snapshot", "o1", "own", arr=b)
        assert memledger.totals()["snapshot"] == b.nbytes
        assert memledger.entry_count() == 1
        memledger.register("param_ring", "r1", "slot:0", nbytes=512, pinned=True)
        assert memledger.pinned_bytes() == 512
        assert memledger.total_bytes() == b.nbytes + 512
        memledger.unregister("snapshot", "o1", "own")
        assert memledger.totals()["snapshot"] == 0
        # unregistering a never-registered identity is a no-op
        memledger.unregister("snapshot", "o1", "own")
        assert memledger.total_bytes() == 512

    def test_drop_owner_and_peaks_survive_frees(self):
        for i in range(4):
            memledger.register(
                "tier_pool", "pool:a", f"page:{i}", nbytes=1000
            )
        memledger.register("tier_pool", "pool:b", "page:0", nbytes=7)
        peak = memledger.peak_total()
        assert peak == 4007
        freed = memledger.drop_owner("tier_pool", "pool:a")
        assert freed == 4000
        assert memledger.totals()["tier_pool"] == 7
        # peaks are high-water marks: frees never lower them
        assert memledger.peak_total() == peak
        assert memledger.peaks()["tier_pool"] == 4007
        assert memledger.watermarks(), "registrations left no watermark"

    def test_disabled_ledger_is_a_noop(self, monkeypatch):
        monkeypatch.setattr(config, "memledger_enabled", False)
        memledger.register("snapshot", "o", "k", nbytes=100)
        memledger.lease_acquired(object())
        assert memledger.total_bytes() == 0
        assert memledger.lease_count() == 0


# ---------------------------------------------------------------------------
# serving-path wiring: attach / query / detach
# ---------------------------------------------------------------------------


class TestWiring:
    def test_snapshot_attach_query_detach_lifecycle(self):
        db = generate_demodb(n_profiles=60, avg_friends=4, seed=11)
        snap = attach_fresh_snapshot(db)
        try:
            rows = db.query(
                COUNT_2HOP, params={"u": 3}, engine="tpu", strict=True
            ).to_dicts()
            assert rows
            # upload is lazy (column_prune): the first dispatch put the
            # CSR on device, and the put registered it
            assert memledger.totals()["snapshot"] > 0, (
                "device upload registered nothing — DeviceGraph._put "
                "wiring is gone"
            )
        finally:
            db.detach_snapshot()
        # _free_device dropped every entry attributed through the graph
        assert memledger.totals()["snapshot"] == 0, (
            "detach left snapshot bytes in the ledger: drop_graph is "
            "not wired into _free_device"
        )
        assert memledger.totals()["plan_const"] == 0
        assert snap is not None  # keep the ref alive through the test

    def test_reconcile_accounting_is_consistent(self):
        """In-process structural check (other suite modules may hold
        live arrays the ledger never saw, so ``ok`` is asserted only
        in the clean-subprocess test below): matched + untracked sum
        to live bytes, and everything this test registered matches."""
        db = generate_demodb(n_profiles=40, avg_friends=3, seed=5)
        db_snap = attach_fresh_snapshot(db)
        try:
            assert db_snap is not None
            rec = memledger.reconcile()
            assert rec["matched_bytes"] >= memledger.totals()["snapshot"]
            assert rec["untracked_bytes"] == max(
                0,
                rec["live_bytes"]
                - rec["matched_bytes"]
                - rec["alias_bytes"],
            )
            assert rec["tracked_dead_bytes"] == 0, rec["tracked_dead"]
            assert memledger.report(reconcile=False)["reconcile"] == rec
        finally:
            db.detach_snapshot()

    def test_dead_transient_entries_self_heal_as_reclaimed(self):
        a = jnp.zeros((16, 16), dtype=jnp.int32)
        nb = a.nbytes
        memledger.register("result_page", "plan:x", "page", arr=a)
        del a  # the page died without an unregister (normal for results)
        rec = memledger.reconcile()
        assert rec["reclaimed_bytes"] == nb
        assert memledger.totals()["result_page"] == 0
        assert rec["tracked_dead_bytes"] == 0

    def test_dead_persistent_entry_is_a_leak_candidate(self):
        a = jnp.zeros((16, 16), dtype=jnp.int32)
        nb = a.nbytes
        memledger.register("snapshot", "snap:leaky", "own", arr=a)
        del a  # a snapshot array dying WITHOUT drop_graph is a leak
        rec = memledger.reconcile()
        assert rec["tracked_dead_bytes"] == nb
        (row,) = rec["tracked_dead"]
        assert row["owner"] == "snap:leaky" and row["bytes"] == nb

    @pytest.mark.slow
    def test_clean_process_reconciles_within_tolerance(self, tmp_path):
        """The acceptance check proper: in a process where the ledger
        saw every upload, attributed bytes reconcile against
        jax.live_arrays() within memledger_tolerance."""
        script = (
            "import json\n"
            "from orientdb_tpu.storage.ingest import generate_demodb\n"
            "from orientdb_tpu.storage.snapshot import attach_fresh_snapshot\n"
            "from orientdb_tpu.obs.memledger import memledger\n"
            "db = generate_demodb(n_profiles=80, avg_friends=4, seed=7)\n"
            "snap = attach_fresh_snapshot(db)\n"
            "db.query(\n"
            "    'MATCH {class:Profiles, as:p, where:(uid = :u)}'\n"
            "    '-HasFriend->{as:f} RETURN count(*) AS n',\n"
            "    params={'u': 2}, engine='tpu', strict=True)\n"
            "print(json.dumps(memledger.reconcile()))\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True,
            text=True,
            timeout=180,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
            cwd="/root/repo",
        )
        assert out.returncode == 0, out.stderr[-2000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["ok"], rec
        assert rec["live_bytes"] > 0 and rec["matched_bytes"] > 0


# ---------------------------------------------------------------------------
# epoch-leak injection (satellite): lease -> stale -> alert -> resolve
# ---------------------------------------------------------------------------


class TestEpochLeak:
    def test_injected_leak_fires_with_trace_exemplar(self, monkeypatch):
        monkeypatch.setattr(config, "memledger_leak_s", 0.05)
        monkeypatch.setattr(config, "alert_pending_ticks", 2)
        db = generate_demodb(n_profiles=30, avg_friends=3, seed=9)
        snap = attach_fresh_snapshot(db)
        try:
            with span("query") as sp:
                snap.retain()  # the injected leak: no release()
            leaked_trace = sp.trace_id
            time.sleep(0.12)
            stale = memledger.stale_leases()
            assert stale and stale[0]["trace_id"] == leaked_trace
            # reconciliation-side visibility of the same state
            rep = memledger.report(reconcile=False)
            assert rep["leases"]["outstanding"] >= 1
            assert rep["leases"]["stale"]
            engine.evaluate()
            (a,) = [
                x for x in engine.active() if x["rule"] == "hbm_epoch_leak"
            ]
            assert a["state"] == "pending"
            engine.evaluate()
            (a,) = [
                x for x in engine.active() if x["rule"] == "hbm_epoch_leak"
            ]
            assert a["state"] == "firing"
            assert a["exemplar_trace_id"] == leaked_trace, (
                "the firing alert must carry the RETAINING lease's "
                "trace id, not a nearby span"
            )
            snap.release()
            engine.evaluate()
            assert not [
                x for x in engine.active() if x["rule"] == "hbm_epoch_leak"
            ]
            hist = [
                x
                for x in engine.history()
                if x["rule"] == "hbm_epoch_leak"
            ]
            assert hist and hist[0]["state"] == "resolved"
        finally:
            db.detach_snapshot()

    def test_balanced_retain_release_never_goes_stale(self, monkeypatch):
        monkeypatch.setattr(config, "memledger_leak_s", 0.05)
        db = generate_demodb(n_profiles=30, avg_friends=3, seed=9)
        snap = attach_fresh_snapshot(db)
        try:
            snap.retain()
            snap.release()
            time.sleep(0.12)
            assert memledger.stale_leases() == []
            engine.evaluate()
            assert not [
                x for x in engine.active() if x["rule"] == "hbm_epoch_leak"
            ]
        finally:
            db.detach_snapshot()


class TestHeadroomRule:
    @staticmethod
    def _snap(gauges):
        return {
            "counters": {},
            "gauges": gauges,
            "durations": {},
            "histograms": {},
            "query_stats": {},
            "alerts": {},
        }

    def test_headroom_lifecycle_against_config_cap(self, monkeypatch):
        """The rule arms off the CONFIG cap, never the published
        ``tier.cap_bytes`` gauge — gauges are process-global and
        outlive a detached tier, and a stale tiny cap must not keep
        firing this rule for the rest of the process."""
        monkeypatch.setattr(config, "alert_pending_ticks", 2)
        monkeypatch.setattr(config, "memledger_headroom_fraction", 0.9)
        monkeypatch.setattr(config, "tier_hbm_cap_bytes", 1000)
        hot = self._snap({"hbm.ledger_bytes": 950.0})
        engine.evaluate(snap=hot)
        engine.evaluate(snap=hot)
        (a,) = [x for x in engine.active() if x["rule"] == "hbm_headroom"]
        assert a["state"] == "firing"
        assert a["value"] == 950.0 and a["threshold"] == 900.0
        cool = self._snap({"hbm.ledger_bytes": 100.0})
        engine.evaluate(snap=cool)
        assert not [
            x for x in engine.active() if x["rule"] == "hbm_headroom"
        ]

    def test_stale_cap_gauge_does_not_arm_the_rule(self, monkeypatch):
        """Regression: a leftover ``tier.cap_bytes`` gauge from a
        detached tier (config cap back at 0) must not fire."""
        monkeypatch.setattr(config, "tier_hbm_cap_bytes", 0)
        stale = self._snap(
            {"tier.cap_bytes": 1000.0, "hbm.ledger_bytes": 1e12}
        )
        engine.evaluate(snap=stale)
        engine.evaluate(snap=stale)
        assert not [
            x for x in engine.active() if x["rule"] == "hbm_headroom"
        ]

    def test_no_cap_no_rule(self, monkeypatch):
        monkeypatch.setattr(config, "tier_hbm_cap_bytes", 0)
        engine.evaluate(
            snap=self._snap({"hbm.ledger_bytes": 1e12})
        )
        assert not [
            x for x in engine.active() if x["rule"] == "hbm_headroom"
        ]


# ---------------------------------------------------------------------------
# refusal telemetry (satellite)
# ---------------------------------------------------------------------------


class TestRefusals:
    def test_counters_and_last_refusal(self):
        c0 = metrics.counter("tier.refusals")
        m0 = metrics.counter("tier.refusals.mesh")
        memledger.note_refusal("mesh", "tiered snapshot on a mesh")
        memledger.note_refusal("overlay", "deltas on a tiered snapshot")
        memledger.note_refusal("mesh", "again")
        assert metrics.counter("tier.refusals") == c0 + 3
        assert metrics.counter("tier.refusals.mesh") == m0 + 2
        rep = memledger.report(reconcile=False)["refusals"]
        assert rep["counts"] == {"mesh": 2, "overlay": 1}
        assert rep["last"]["reason"] == "mesh"
        assert rep["last"]["detail"] == "again"

    def test_real_tiered_overlay_refusal_is_counted(self, monkeypatch):
        """The real path: delta maintenance on a tiered snapshot is
        refused with reason=overlay, and the refusal lands in the
        ledger alongside the raised ValueError."""
        from orientdb_tpu.storage import tiering
        from orientdb_tpu.storage.deltas import pad_for_deltas

        monkeypatch.setattr(config, "view_min_calls", 1 << 30)
        monkeypatch.setattr(config, "tier_block_edges", 32)
        db = generate_demodb(n_profiles=120, avg_friends=5, seed=3)
        snap = attach_fresh_snapshot(db)
        adj = tiering.adjacency_bytes(snap)
        db.detach_snapshot()
        monkeypatch.setattr(config, "tier_hbm_cap_bytes", max(1, adj // 2))
        snap = attach_fresh_snapshot(db)
        try:
            assert getattr(snap, "_tier", None) is not None
            o0 = metrics.counter("tier.refusals.overlay")
            with pytest.raises(ValueError, match="tiered"):
                pad_for_deltas(snap)
            assert metrics.counter("tier.refusals.overlay") == o0 + 1
            last = memledger.report(reconcile=False)["refusals"]["last"]
            assert last["reason"] == "overlay"
        finally:
            db.detach_snapshot()


# ---------------------------------------------------------------------------
# surfaces: gauges, /debug/memory, bundle, console
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_gauges_ride_snapshot_and_exposition(self):
        from orientdb_tpu.obs.promlint import lint_exposition
        from orientdb_tpu.obs.registry import (
            render_prometheus,
            snapshot_all,
        )

        a = jnp.zeros((32, 32), dtype=jnp.int32)
        memledger.register("snapshot", "o", "own", arr=a)
        snap = snapshot_all()
        gauges = snap["gauges"]
        assert gauges.get("hbm.ledger_bytes") == float(a.nbytes)
        assert gauges.get("hbm.owner.snapshot_bytes") == float(a.nbytes)
        assert "hbm.ledger_entries" in gauges
        assert "hbm.leak_leases" in gauges
        text = render_prometheus()
        assert "orienttpu_hbm_ledger_bytes" in text
        assert "orienttpu_hbm_owner_snapshot_bytes" in text
        assert lint_exposition(text) == [], lint_exposition(text)

    def test_disabled_ledger_publishes_no_gauges(self, monkeypatch):
        monkeypatch.setattr(config, "memledger_enabled", False)
        metrics.drop_gauge("hbm.ledger_bytes")
        ledger_telemetry()
        assert "hbm.ledger_bytes" not in metrics.snapshot()["gauges"]

    def test_debug_memory_endpoint_and_auth(self):
        import urllib.error

        from orientdb_tpu.server.server import Server

        db = generate_demodb(n_profiles=60, avg_friends=4, seed=13)
        db_snap = attach_fresh_snapshot(db)
        assert db_snap is not None
        # mixed traffic before the scrape: tpu + oracle
        for u in (1, 7):
            db.query(
                COUNT_2HOP, params={"u": u}, engine="tpu", strict=True
            )
            db.query(COUNT_2HOP, params={"u": u}, engine="oracle")
        memledger.note_refusal("mesh", "surface test")
        srv = Server(admin_password="pw").startup()
        try:
            url = f"http://127.0.0.1:{srv.http_port}"
            doc = _get(f"{url}/debug/memory")
            assert set(doc["owners"]) == set(OWNER_KINDS)
            assert doc["owners"]["snapshot"]["bytes"] > 0
            assert doc["total_bytes"] > 0
            rec = doc["reconcile"]
            assert rec is not None and "untracked_bytes" in rec
            assert doc["refusals"]["last"]["reason"] == "mesh"
            assert "stale" in doc["leases"]
            # ?reconcile=0 serves the cached verdict without a pass
            doc2 = _get(f"{url}/debug/memory?reconcile=0")
            assert doc2["reconcile"]["ts"] == rec["ts"]
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(
                    f"{url}/debug/memory",
                    user="reader",
                    password="reader",
                )
            assert ei.value.code in (401, 403)
        finally:
            srv.shutdown()
            db.detach_snapshot()

    def test_bundle_carries_memory_section(self):
        from orientdb_tpu.obs.bundle import debug_bundle

        memledger.register("snapshot", "o", "own", nbytes=64)
        b = debug_bundle()
        assert "memory" in b
        assert b["memory"]["total_bytes"] >= 64
        assert "reconcile" in b["memory"]

    def test_console_memory_verb(self):
        from orientdb_tpu.tools.console import Console

        a = jnp.zeros((16, 16), dtype=jnp.int32)
        memledger.register("snapshot", "o", "own", arr=a)
        memledger.note_refusal("mesh", "console test")
        out = io.StringIO()
        c = Console(stdout=out)
        c.onecmd("MEMORY")
        text = out.getvalue()
        assert "snapshot" in text and "total" in text
        assert "reconcile:" in text and "leases:" in text
        assert "refusals:" in text
        out2 = io.StringIO()
        Console(stdout=out2).onecmd("MEMORY WATERMARK")
        assert "MiB" in out2.getvalue()

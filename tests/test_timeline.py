"""Dispatch flight recorder (obs/timeline): per-dispatch lifecycle
rings, overlap accounting (device-idle / transfer-hidden / ring
savings / lane decomposition), Chrome-trace export over every dispatch
path, the tpu.page_prefetch.* counter contract (PR 13), and the
overlap_regression alert rule. What a request pays the recorder is
counted in tests/test_plane_overhead.py."""

import json
import time

import numpy as np
import pytest

import orientdb_tpu.obs.timeline as TL
from orientdb_tpu.models.database import Database
from orientdb_tpu.obs.timeline import DispatchRecord, FlightRecorder
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics


def canon(rows):
    return sorted(
        tuple(sorted((k, str(v)) for k, v in r.items())) for r in rows
    )


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


def make_graph(name, n=60):
    db = Database(name)
    db.schema.create_vertex_class("P")
    db.schema.create_edge_class("K")
    vs = [db.new_vertex("P", n=i) for i in range(n)]
    for i in range(n - 1):
        db.new_edge("K", vs[i], vs[i + 1])
    return db


def _rec(seq=1, path="single", fid=None, t0=1000.0):
    r = DispatchRecord(seq, path, None, None, 1)
    r._fid = fid  # synthetic records pin the id, no SQL to derive from
    r.t0 = t0
    r.events = []
    return r


# ---------------------------------------------------------------------------
# recorder unit behavior
# ---------------------------------------------------------------------------


class TestRecorder:
    def test_ring_is_bounded_and_resettable(self):
        rec = FlightRecorder(capacity=4)
        for _ in range(10):
            rec.commit(rec.begin("single", sql="SELECT 1"))
        assert len(rec) == 4
        seqs = [r["seq"] for r in rec.records()]
        assert seqs == sorted(seqs)[-4:]  # newest survive
        rec.reset()
        assert len(rec) == 0

    def test_capacity_zero_disables_recording(self, monkeypatch):
        monkeypatch.setattr(config, "timeline_capacity", 0)
        assert TL.recorder.begin("single", sql="SELECT 1") is None

    def test_detached_dispatch_sampled_out_returns_none(
        self, monkeypatch
    ):
        monkeypatch.setattr(config, "stats_sample_rate", 0.0)
        assert TL.recorder.begin("lane", sql="SELECT 1") is None

    def test_per_query_recording_rides_the_stats_decision(self):
        """The join contract: a per-query dispatch records IFF the
        stats plane sampled the query in (its accumulator is active on
        this thread) — under stats_sample_rate < 1 the timeline covers
        exactly the subset slowlog/stats/traces cover, so a slowlog
        trace id always joins a timeline record."""
        import orientdb_tpu.obs.stats as S

        # no accumulator on this thread -> the stats plane sampled the
        # query out (or there is no query) -> no record, regardless of
        # any independent draw
        assert S.current_acc() is None
        assert TL.recorder.begin("single") is None
        acc = S.stats.begin("SELECT 9 FROM P")
        try:
            r = TL.recorder.begin("single")
            assert r is not None
            assert r.sql == "SELECT 9 FROM P"
            assert r.fid == S.fingerprint_cached("SELECT 9 FROM P").fid
        finally:
            S.stats.finish(acc, 0.0, engine="?")

    def test_hooks_are_noops_without_active_record(self):
        # no exception, no state: the hot path outside a dispatch
        TL.mark("device_dispatch")
        TL.add_phase(0.1, 0.1, 100)
        TL.note_ring(True)
        TL.note_prefetch(True, 10)
        TL.note_path("sharded")
        assert TL.current() is None

    def test_active_none_is_noop_and_nests(self):
        with TL.active(None):
            assert TL.current() is None
        rec = FlightRecorder(capacity=8)
        r = rec.begin("single", sql="SELECT 1")
        with TL.active(r):
            assert TL.current() is r
            TL.mark("device_dispatch")
        assert TL.current() is None
        assert [n for n, _t in r.events] == ["device_dispatch"]

    def test_note_path_refines_but_lane_is_sticky(self):
        rec = FlightRecorder(capacity=8)
        r = rec.begin("single", sql="SELECT 1")
        with TL.active(r):
            TL.note_path("sharded")
        assert r.path == "sharded"
        r2 = rec.begin("lane", sql="SELECT 1")
        with TL.active(r2):
            TL.note_path("group")
        assert r2.path == "lane"

    def test_commit_stamps_result_delivered_and_window_filter(self):
        rec = FlightRecorder(capacity=8)
        r = rec.begin("oracle", sql="SELECT 1")
        rec.commit(r)
        assert r.events[-1][0] == "result_delivered"
        assert rec.records(window_s=60.0), "fresh record inside window"
        r.t_done = time.monotonic() - 999.0
        assert not rec.records(window_s=60.0)

    def test_uncommitted_record_never_rings(self):
        rec = FlightRecorder(capacity=8)
        r = rec.begin("single", sql="SELECT 1")
        assert r is not None and len(rec) == 0
        rec.commit(None)  # no-op
        assert len(rec) == 0


# ---------------------------------------------------------------------------
# overlap accounting (synthetic records, exact numbers)
# ---------------------------------------------------------------------------


class TestOverlapAccounting:
    def test_device_idle_fraction_from_merged_busy_intervals(self):
        a = _rec(1, t0=1000.0)
        a.device = [(1000.0, 1001.0)]
        a.t_done = 1001.0
        b = _rec(2, t0=1002.0)
        b.device = [(1002.0, 1003.0)]
        b.t_done = 1003.0
        rep = FlightRecorder._overlap([a, b], 8)
        # span 1000..1003 = 3 s, busy 2 s -> idle 1/3
        assert rep["span_s"] == pytest.approx(3.0)
        assert rep["device_busy_s"] == pytest.approx(2.0)
        assert rep["device_idle_fraction"] == pytest.approx(1 / 3, abs=1e-6)

    def test_overlapping_device_intervals_merge_not_doublecount(self):
        a = _rec(1, t0=1000.0)
        a.device = [(1000.0, 1002.0), (1001.0, 1003.0)]
        a.t_done = 1003.0
        rep = FlightRecorder._overlap([a], 8)
        assert rep["device_busy_s"] == pytest.approx(3.0)
        assert rep["device_idle_fraction"] == pytest.approx(0.0)

    def test_transfer_hidden_fraction_prorates_by_overlap(self):
        a = _rec(1, t0=1000.0)
        a.device = [(1000.0, 1002.0)]
        a.t_done = 1004.0
        # fully inside busy -> hidden; fully outside -> serialized;
        # half inside -> half the bytes hidden
        a.transfers = [
            (1000.5, 1001.5, 1000, "fetch"),
            (1002.5, 1003.5, 1000, "fetch"),
            (1001.5, 1002.5, 1000, "fetch"),
        ]
        rep = FlightRecorder._overlap([a], 8)
        tr = rep["transfer"]
        assert tr["bytes"] == 3000
        assert tr["hidden_bytes"] == 1500
        assert tr["transfer_hidden_fraction"] == pytest.approx(0.5)

    def test_zero_length_prefetch_transfer_counts_hidden(self):
        a = _rec(1, t0=1000.0)
        a.t_done = 1001.0
        a.transfers = [(1000.5, 1000.5, 512, "prefetch")]
        rep = FlightRecorder._overlap([a], 8)
        assert rep["transfer"]["hidden_bytes"] == 512
        assert rep["transfer"]["prefetch_bytes"] == 512

    def test_ring_and_prefetch_marks_aggregate(self):
        a = _rec(1, path="lane")
        a.t_done = 1001.0
        a.marks = {
            "ring_hits": 3,
            "ring_uploads": 1,
            "ring_bytes": 256,
            "prefetch_starts": 2,
            "prefetch_hits": 1,
            "prefetch_misses": 1,
        }
        rep = FlightRecorder._overlap([a], 8)
        assert rep["ring"] == {
            "hits": 3,
            "uploads": 1,
            "bytes_uploaded": 256,
            "hit_fraction": 0.75,
        }
        assert rep["prefetch"] == {"starts": 2, "hits": 1, "misses": 1}

    def test_lane_queue_window_service_decomposition(self):
        a = _rec(1, path="lane", t0=1000.0)
        a.events = [("enqueue", 999.9), ("device_dispatch", 1000.0)]
        a.marks = {"window_s": 0.005}
        a.t_done = 1000.05
        rep = FlightRecorder._overlap([a], 8)
        lane = rep["lane"]
        assert lane["dispatches"] == 1
        assert lane["queue_ms_mean"] == pytest.approx(100.0, rel=0.01)
        assert lane["window_ms_mean"] == pytest.approx(5.0)
        assert lane["service_ms_mean"] == pytest.approx(50.0, rel=0.01)

    def test_per_fingerprint_rollup(self):
        a = _rec(1, fid="f1", t0=1000.0)
        a.device = [(1000.0, 1001.0)]
        a.t_done = 1001.0
        b = _rec(2, fid="f1", t0=1001.0)
        b.device = [(1003.0, 1004.0)]
        b.t_done = 1004.0
        rep = FlightRecorder._overlap([a, b], 8)
        fp = rep["fingerprints"]["f1"]
        assert fp["dispatches"] == 2
        assert fp["device_s"] == pytest.approx(2.0)
        # f1's own span 1000..1004, busy 2 -> idle 0.5
        assert fp["idle_fraction"] == pytest.approx(0.5)

    def test_empty_window_reports_zero_records(self):
        rep = FlightRecorder._overlap([], 8)
        assert rep == {"records": 0}


# ---------------------------------------------------------------------------
# real dispatch paths land in the ring
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traffic_db():
    db = make_graph("tl_traffic")
    attach_fresh_snapshot(db)
    return db


COUNT_SQL = (
    "MATCH {class:P, as:a, where:(n < 40)}-K->{as:b} "
    "RETURN count(*) AS n"
)
PARAM_SQL = "SELECT count(*) AS c FROM P WHERE n < :k"


class TestDispatchPathsRecorded:
    def test_single_group_oracle_paths(self, traffic_db):
        TL.recorder.reset()
        traffic_db.query(COUNT_SQL, engine="tpu", strict=True)
        traffic_db.query(COUNT_SQL, engine="tpu", strict=True)
        traffic_db.query(COUNT_SQL, engine="oracle")
        from orientdb_tpu.exec.tpu_engine import drain_warmups

        drain_warmups()
        deadline = time.time() + 30
        while time.time() < deadline:
            traffic_db.query_batch([PARAM_SQL] * 8, [{"k": 17}] * 8)
            drain_warmups()
            paths = {r["path"] for r in TL.recorder.records()}
            if "group" in paths:
                break
        recs = TL.recorder.records()
        paths = {r["path"] for r in recs}
        assert {"single", "oracle", "group"} <= paths, paths
        # the SECOND single query replayed the cached plan: full
        # lifecycle (the first, recording execution, legitimately has
        # no plan_resolve — the eager solve IS the plan)
        singles = [r for r in recs if r["path"] == "single"]
        replay = next(
            r
            for r in singles
            if "plan_resolve" in [n for n, _t in r["events"]]
        )
        names = [n for n, _t in replay["events"]]
        assert "device_dispatch" in names
        assert names[-1] == "result_delivered"
        assert replay["fingerprint"], "stats-plane fingerprint missing"
        assert replay["trace_id"], "trace correlation missing"

    def test_lane_path_records_enqueue_ring_and_window(self, traffic_db):
        import orientdb_tpu.exec.engine as E
        from orientdb_tpu.exec.tpu_engine import drain_warmups

        traffic_db.query(PARAM_SQL, {"k": 17}, engine="tpu", strict=True)
        drain_warmups()
        TL.recorder.reset()
        sqls, plist = [PARAM_SQL] * 4, [{"k": 17}] * 4
        h = None
        deadline = time.time() + 30
        while h is None and time.time() < deadline:
            h = E.dispatch_lane_batch(
                traffic_db,
                sqls,
                plist,
                ring_state=(rs := {}),
                enqueue_ts=time.monotonic() - 0.01,
                window_s=0.002,
            )
            if h is None:
                drain_warmups()
        assert h is not None
        h.collect()
        # repeat with the same ring -> staged-slot reuse marks
        h2 = E.dispatch_lane_batch(
            traffic_db,
            sqls,
            plist,
            ring_state=rs,
            enqueue_ts=time.monotonic() - 0.01,
            window_s=0.002,
        )
        assert h2 is not None
        h2.collect()
        lanes = [
            r for r in TL.recorder.records() if r["path"] == "lane"
        ]
        assert lanes, "lane dispatches produced no flight records"
        names = [n for n, _t in lanes[-1]["events"]]
        assert "enqueue" in names
        assert "lane_window" in names
        assert "plan_resolve" in names
        assert lanes[-1]["marks"]["window_s"] == pytest.approx(0.002)
        assert any(
            r.get("marks", {}).get("ring_hits") for r in lanes
        ), "steady-state lane repeat recorded no ring hit"
        rep = TL.recorder.overlap()
        assert rep["lane"]["dispatches"] >= 2
        assert rep["lane"]["queue_ms_mean"] >= 5.0

    def test_sharded_path_recorded(self):
        from orientdb_tpu.parallel.sharded import make_mesh

        db = make_graph("tl_sharded", n=40)
        attach_fresh_snapshot(db, mesh=make_mesh(2, replicas=1))
        sql = (
            "MATCH {class:P, as:a, where:(n < 10)}-K->{as:b} "
            "RETURN a.n AS a, b.n AS b"
        )
        TL.recorder.reset()
        expected = canon(db.query(sql, engine="oracle").to_dicts())
        got = canon(
            db.query(sql, engine="tpu", strict=True).to_dicts()
        )
        assert got == expected
        # the replay dispatches through the mesh plan -> "sharded"
        got2 = canon(
            db.query(sql, engine="tpu", strict=True).to_dicts()
        )
        assert got2 == expected
        paths = {r["path"] for r in TL.recorder.records()}
        assert "sharded" in paths, paths
        db.detach_snapshot()


# ---------------------------------------------------------------------------
# page-prefetch counters (PR 13) + hidden-transfer proof
# ---------------------------------------------------------------------------


class TestPagePrefetchCounters:
    @pytest.fixture(scope="class")
    def page_db(self):
        # > _PAGE_MIN result rows so the replay emits a REAL pow2 page
        # ladder (1024, 2048, ... full) instead of one full-width page
        db = make_graph("tl_pages", n=3000)
        attach_fresh_snapshot(db)
        return db

    SQL = (
        "MATCH {class:P, as:a, where:(n < :lim)}-K->{as:b} "
        "RETURN a.n AS a, b.n AS b"
    )

    def test_hit_miss_accounting_and_hidden_transfer(
        self, page_db, monkeypatch
    ):
        """Elected-page SHAPE MATCH (same parameter twice) counts a
        prefetch hit; a parameter that elects a different ladder page
        counts a miss; and the hit's bytes land as an OVERLAPPED
        (hidden) transfer in the flight record — the dispatch-time
        copy rode behind the device wave (the acceptance criterion:
        transfer-hidden > 0 on the prefetch path)."""
        # keep the plan off the fused direct-fetch shortcut: the
        # ladder (and with it the prefetch) only exists on the paged
        # protocol
        monkeypatch.setattr(config, "result_direct_bytes", 1024)
        from orientdb_tpu.exec.tpu_engine import drain_warmups

        big, small = {"lim": 2500}, {"lim": 40}
        oracle = canon(
            page_db.query(self.SQL, big, engine="oracle").to_dicts()
        )
        got = canon(
            page_db.query(
                self.SQL, big, engine="tpu", strict=True
            ).to_dicts()
        )
        assert got == oracle
        drain_warmups()
        TL.recorder.reset()

        def batch(params):
            # 2 same-plan items (< group minimum): the per-query
            # dispatch + page election path
            rss = page_db.query_batch(
                [self.SQL] * 2, [dict(params)] * 2,
                engine="tpu", strict=True,
            )
            assert all(len(rs.to_dicts()) > 0 for rs in rss)

        c0 = metrics.snapshot()["counters"]
        batch(big)   # election #1: sets the guess
        batch(big)   # same shape -> dispatch-time prefetch HIT
        c1 = metrics.snapshot()["counters"]
        assert c1.get("tpu.page_prefetch.start", 0) > c0.get(
            "tpu.page_prefetch.start", 0
        ), "dispatch never started a speculative page copy"
        hits0 = c0.get("tpu.page_prefetch.hit", 0)
        assert c1.get("tpu.page_prefetch.hit", 0) > hits0, (
            "repeat election did not count a prefetch hit"
        )
        batch(small)  # different ladder page -> MISS
        c2 = metrics.snapshot()["counters"]
        assert c2.get("tpu.page_prefetch.miss", 0) > c1.get(
            "tpu.page_prefetch.miss", 0
        ), "page-shape mismatch did not count a prefetch miss"
        # the hit's transfer is on the timeline as prefetch-kind and
        # the overlap pass scores hidden bytes > 0
        recs = TL.recorder.records()
        pf = [
            t
            for r in recs
            for t in r.get("transfers", [])
            if t[3] == "prefetch"
        ]
        assert pf, "prefetch hit left no prefetch transfer interval"
        assert any(t[2] > 0 for t in pf)
        rep = TL.recorder.overlap()
        assert rep["prefetch"]["hits"] >= 1
        assert rep["prefetch"]["misses"] >= 1
        assert rep["transfer"]["hidden_bytes"] > 0, (
            "prefetch-path transfer did not score as hidden"
        )


# ---------------------------------------------------------------------------
# surfaces: HTTP endpoint, bundle, console, gauges, exposition
# ---------------------------------------------------------------------------


def _get(url, user="admin", password="pw", raw=False):
    import base64
    import urllib.request

    cred = base64.b64encode(f"{user}:{password}".encode()).decode()
    req = urllib.request.Request(
        url, headers={"Authorization": f"Basic {cred}"}
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        body = r.read()
    return body.decode() if raw else json.loads(body)


class TestSurfaces:
    def test_debug_timeline_serves_valid_chrome_trace_for_mixed_run(
        self, traffic_db, monkeypatch
    ):
        """The acceptance artifact: a mixed run (lane-coalesced, group,
        and sharded dispatches in one process) exports as valid
        Chrome-trace JSON from GET /debug/timeline — every event
        carries the required keys, and all three paths appear."""
        import orientdb_tpu.exec.engine as E
        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.parallel.sharded import make_mesh
        from orientdb_tpu.server.server import Server

        monkeypatch.setattr(config, "watchdog_enabled", False)
        TL.recorder.reset()
        # group dispatches (+ records the plans)
        traffic_db.query(PARAM_SQL, {"k": 9}, engine="tpu", strict=True)
        drain_warmups()
        deadline = time.time() + 30
        while time.time() < deadline:
            traffic_db.query_batch([PARAM_SQL] * 8, [{"k": 9}] * 8)
            drain_warmups()
            if "group" in {
                r["path"] for r in TL.recorder.records()
            }:
                break
        # lane-coalesced dispatches (the engine lane front door the
        # server coalescer drives)
        h = None
        deadline = time.time() + 30
        while h is None and time.time() < deadline:
            h = E.dispatch_lane_batch(
                traffic_db,
                [PARAM_SQL] * 4,
                [{"k": 9}] * 4,
                ring_state={},
                enqueue_ts=time.monotonic(),
                window_s=0.001,
            )
            if h is None:
                drain_warmups()
        assert h is not None
        h.collect()
        # sharded dispatches
        sdb = make_graph("tl_mixed_sharded", n=40)
        attach_fresh_snapshot(sdb, mesh=make_mesh(2, replicas=1))
        ssql = (
            "MATCH {class:P, as:a, where:(n < 8)}-K->{as:b} "
            "RETURN a.n AS a, b.n AS b"
        )
        sdb.query(ssql, engine="tpu", strict=True)
        sdb.query(ssql, engine="tpu", strict=True)
        srv = Server(admin_password="pw").startup()
        try:
            url = f"http://127.0.0.1:{srv.http_port}"
            doc = _get(f"{url}/debug/timeline")
            assert isinstance(doc["traceEvents"], list)
            assert doc["traceEvents"], "empty trace"
            for e in doc["traceEvents"]:
                assert e["ph"] in ("X", "M", "i"), e
                assert isinstance(e["pid"], int)
                assert isinstance(e["tid"], int)
                assert "name" in e
                if e["ph"] != "M":
                    assert isinstance(e["ts"], (int, float))
                if e["ph"] == "X":
                    assert e["dur"] >= 0
            cats = {
                e.get("cat") for e in doc["traceEvents"] if "cat" in e
            }
            assert {"lane", "group", "sharded"} <= cats, cats
            ov = doc["otherData"]["overlap"]
            assert ov["records"] > 0
            assert "device_idle_fraction" in ov
            # ?format=json serves raw records + the overlap report
            raw = _get(f"{url}/debug/timeline?format=json")
            assert raw["overlap"]["records"] > 0
            assert raw["records"]
        finally:
            srv.shutdown()
            sdb.detach_snapshot()

    def test_debug_timeline_is_admin_only(self, monkeypatch):
        import urllib.error

        from orientdb_tpu.server.server import Server

        monkeypatch.setattr(config, "watchdog_enabled", False)
        srv = Server(admin_password="pw").startup()
        try:
            url = f"http://127.0.0.1:{srv.http_port}"
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(
                    f"{url}/debug/timeline",
                    user="reader",
                    password="reader",
                )
            assert ei.value.code in (401, 403)
        finally:
            srv.shutdown()

    def test_bundle_carries_timeline_section(self, traffic_db):
        from orientdb_tpu.obs.bundle import debug_bundle

        traffic_db.query(COUNT_SQL, engine="tpu", strict=True)
        b = debug_bundle(dbs=[traffic_db])
        assert "timeline" in b
        assert "overlap" in b["timeline"]
        assert isinstance(b["timeline"]["records"], list)

    def test_overlap_gauges_ride_snapshot_and_exposition(
        self, traffic_db
    ):
        from orientdb_tpu.obs.promlint import lint_exposition
        from orientdb_tpu.obs.registry import (
            render_prometheus,
            snapshot_all,
        )

        traffic_db.query(COUNT_SQL, engine="tpu", strict=True)
        snap = snapshot_all()
        gauges = snap["gauges"]
        assert gauges.get("overlap.window_records", 0) > 0
        assert "overlap.device_idle_fraction" in gauges
        assert "overlap.transfer_hidden_fraction" in gauges
        text = render_prometheus()
        assert "orienttpu_overlap_device_idle_fraction" in text
        assert lint_exposition(text) == [], lint_exposition(text)

    def test_console_timeline_verb(self, traffic_db):
        import io

        from orientdb_tpu.tools.console import Console

        traffic_db.query(COUNT_SQL, engine="tpu", strict=True)
        out = io.StringIO()
        c = Console(stdout=out)
        c.onecmd("TIMELINE 5")
        text = out.getvalue()
        assert "dispatches over" in text
        assert "device idle" in text
        assert "transfer hidden" in text


# ---------------------------------------------------------------------------
# overlap_regression alert rule
# ---------------------------------------------------------------------------


class TestOverlapRegressionRule:
    @staticmethod
    def _snap(idle, records=100.0):
        return {
            "gauges": {
                "overlap.device_idle_fraction": idle,
                "overlap.window_records": records,
            },
            "query_stats": {},
        }

    def test_idle_regression_walks_pending_to_firing_to_resolved(
        self, monkeypatch
    ):
        from orientdb_tpu.obs.alerts import AlertEngine

        monkeypatch.setattr(config, "alert_pending_ticks", 2)
        eng = AlertEngine()
        for _ in range(4):  # learn the baseline at 0.2 idle
            eng.evaluate(snap=self._snap(0.2))
        assert not [
            a for a in eng.active() if a["rule"] == "overlap_regression"
        ]
        eng.evaluate(snap=self._snap(0.9))
        (a,) = [
            a for a in eng.active() if a["rule"] == "overlap_regression"
        ]
        assert a["state"] == "pending"
        assert a["key"] == "device_idle"
        eng.evaluate(snap=self._snap(0.9))
        (a,) = [
            a for a in eng.active() if a["rule"] == "overlap_regression"
        ]
        assert a["state"] == "firing"
        assert "device-idle fraction" in a["detail"]
        # signal clears -> resolved into history
        eng.evaluate(snap=self._snap(0.2))
        assert not [
            a for a in eng.active() if a["rule"] == "overlap_regression"
        ]
        assert any(
            h["rule"] == "overlap_regression" for h in eng.history()
        )

    def test_breaching_tick_does_not_teach_its_own_baseline(
        self, monkeypatch
    ):
        """The latency-rule discipline: a sustained idle step must stay
        breaching tick after tick — folding it into the EWMA would let
        it normalize itself before the pending dwell elapses."""
        from orientdb_tpu.obs.alerts import AlertEngine

        monkeypatch.setattr(config, "alert_pending_ticks", 4)
        eng = AlertEngine()
        for _ in range(4):
            eng.evaluate(snap=self._snap(0.1))
        for _ in range(4):
            eng.evaluate(snap=self._snap(0.95))
        (a,) = [
            a for a in eng.active() if a["rule"] == "overlap_regression"
        ]
        assert a["state"] == "firing"

    def test_min_records_gates_thin_windows(self, monkeypatch):
        from orientdb_tpu.obs.alerts import AlertEngine

        monkeypatch.setattr(config, "alert_overlap_min_records", 16)
        eng = AlertEngine()
        for _ in range(4):
            eng.evaluate(snap=self._snap(0.1))
        eng.evaluate(snap=self._snap(0.99, records=5.0))
        assert not [
            a for a in eng.active() if a["rule"] == "overlap_regression"
        ]

    def test_rule_is_cataloged(self):
        from orientdb_tpu.obs.alerts import BUILTIN_RULES, RULE_CATALOG

        assert "overlap_regression" in RULE_CATALOG
        assert any(
            r.name == "overlap_regression" for r in BUILTIN_RULES
        )

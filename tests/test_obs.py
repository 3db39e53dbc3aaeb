"""The obs/ subsystem: tracing spans, Prometheus exposition, slow-query
log, and crash-safe evidence streaming (ISSUE 1 tentpole)."""

import io
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from orientdb_tpu.obs.evidence import EvidenceSink, read_evidence
from orientdb_tpu.obs.registry import obs, render_prometheus
from orientdb_tpu.obs.slowlog import slowlog
from orientdb_tpu.obs.trace import current_trace_id, span, tracer
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def db():
    from orientdb_tpu.storage.ingest import generate_demodb
    from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

    d = generate_demodb(n_profiles=100, avg_friends=4, seed=5)
    attach_fresh_snapshot(d)
    return d


class TestTrace:
    def test_span_nesting_inherits_trace_id(self):
        assert current_trace_id() is None
        with span("outer", k=1) as outer:
            tid = current_trace_id()
            assert tid == outer.trace_id
            with span("inner") as inner:
                assert inner.trace_id == tid
                assert inner.parent_id == outer.span_id
        assert current_trace_id() is None
        got = tracer.spans(trace_id=tid)
        assert [s.name for s in got] == ["inner", "outer"]
        assert all(s.duration_us is not None for s in got)
        assert got[1].attrs["k"] == 1

    def test_span_records_error(self):
        with pytest.raises(ValueError):
            with span("boom") as sp:
                raise ValueError("nope")
        assert "ValueError" in tracer.spans(trace_id=sp.trace_id)[0].error

    def test_query_gets_a_root_span(self, db):
        tracer.reset()
        db.query(
            "MATCH {class:Profiles, as:p}-HasFriend->{as:f} "
            "RETURN count(*) AS n",
            engine="tpu",
            strict=True,
        )
        roots = [s for s in tracer.spans(name="query")]
        assert roots and roots[-1].attrs.get("engine") == "tpu"


class TestProfileSpans:
    def test_profiled_match_shows_per_hop_stage_timings(self, db):
        q = (
            "MATCH {class:Profiles, as:p, where:(age > 40)}"
            "-HasFriend->{as:f}-HasFriend->{as:g, where:(age < 30)} "
            "RETURN count(*) AS n"
        )
        db.query(q, engine="tpu", strict=True)  # record
        phases = db.query(f"PROFILE {q}").to_dicts()[0]["tpuPhases"]
        assert phases["traceId"]
        spans = phases["spans"]
        assert all(s["trace_id"] == phases["traceId"] for s in spans)
        steps = [s for s in spans if s["name"] == "tpu.step"]
        # root seed + two PatternEdge hops, each with a wall duration
        assert len(steps) >= 3
        assert sum("EXPAND" in s["attrs"]["step"] for s in steps) >= 2
        for s in steps:
            assert s["duration_us"] is not None
        # table-building steps also report the frontier they produced
        assert any("frontier_rows" in s["attrs"] for s in steps)
        names = {s["name"] for s in spans}
        assert "tpu.marshal" in names

    def test_frontier_histogram_observed(self, db):
        db.query(
            "MATCH {class:Profiles, as:p}-HasFriend->{as:f} "
            "RETURN count(*) AS n",
            params=None,
            engine="tpu",
            strict=True,
        )
        # the recording solve observed its frontier sizes
        assert obs.histogram("tpu.frontier_rows").snapshot()["count"] >= 1


class TestExposition:
    def test_prometheus_text_after_match_tx_and_replicated_write(
        self, monkeypatch
    ):
        """The acceptance path: a MATCH query, a tx commit, and a
        replicated write all leave their marks in one /metrics scrape
        (Prometheus text format)."""
        from orientdb_tpu.parallel.replication import (
            ReplicaPuller,
            enable_replication_source,
        )
        from orientdb_tpu.models.database import Database
        from orientdb_tpu.server.server import Server
        from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

        srv = Server(admin_password="pw")
        d = srv.create_database("obsx")
        enable_replication_source(d)  # arms a WAL: writes append + fsync path
        d.schema.create_vertex_class("P")
        d.schema.create_edge_class("K")
        a = d.new_vertex("P", uid=1)
        b = d.new_vertex("P", uid=2)
        d.new_edge("K", a, b)
        # tx commit
        d.begin()
        d.new_vertex("P", uid=3)
        d.commit()
        # MATCH on the compiled engine, twice through the result cache
        # so the cache-hit-rate counters have both sides
        monkeypatch.setattr(config, "command_cache_enabled", True)
        attach_fresh_snapshot(d)
        q = "MATCH {class:P, as:p}-K->{as:q} RETURN count(*) AS n"
        rows = d.query(q, engine="tpu", strict=True).to_dicts()
        assert rows == [{"n": 1}]
        assert d.query(q, engine="tpu", strict=True).to_dicts() == rows
        srv.startup()
        try:
            # replicated write: a replica pulls the WAL stream over HTTP
            rep = ReplicaPuller(
                f"http://127.0.0.1:{srv.http_port}",
                "obsx",
                Database("obsx_replica"),
                user="admin",
                password="pw",
            )
            assert rep.pull_once() > 0
            assert rep.db.count_class("P") == 3
            import base64

            cred = base64.b64encode(b"admin:pw").decode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.http_port}/metrics",
                headers={"Authorization": f"Basic {cred}"},
            )
            with urllib.request.urlopen(req, timeout=5) as r:
                ctype = r.headers["Content-Type"]
                text = r.read().decode()
        finally:
            srv.shutdown()
        assert ctype.startswith("text/plain")
        # query / tx / WAL / replication / cache families, typed
        for needle in (
            "# TYPE orienttpu_query_tpu_total counter",
            "orienttpu_tx_commit_total",
            "orienttpu_wal_append_total",
            "# TYPE orienttpu_wal_append_s histogram",
            "orienttpu_wal_append_s_bucket{le=",
            "orienttpu_replication_applied_total",
            "orienttpu_replication_lag_entries",
            "orienttpu_plan_cache_miss_total",
            "orienttpu_command_cache_hit_total",
            "orienttpu_query_latency_s_bucket{le=",
        ):
            assert needle in text, f"missing {needle!r} in exposition"

    def test_render_covers_gauges_and_durations(self):
        metrics.gauge("obs.test_gauge", 2.5)
        with pytest.raises(ZeroDivisionError):
            from orientdb_tpu.utils.metrics import timed

            with timed("obs.test_duration_s"):
                1 / 0
        text = render_prometheus()
        assert "# TYPE orienttpu_obs_test_gauge gauge" in text
        assert "orienttpu_obs_test_gauge 2.5" in text
        assert "orienttpu_obs_test_duration_s_count" in text
        assert "orienttpu_obs_test_duration_s_max" in text


class TestSlowlog:
    def test_threshold_and_console_surface(self, db, monkeypatch):
        monkeypatch.setattr(config, "slow_query_ms", 0.0001)
        slowlog.clear()
        db.query("SELECT name FROM Profiles WHERE uid = 1")
        entries = slowlog.entries()
        assert entries, "query over threshold must be recorded"
        assert entries[0]["ms"] > 0
        assert entries[0]["trace_id"]
        assert "SELECT" in entries[0]["sql"]
        # surfaced in the console
        from orientdb_tpu.tools.console import Console

        buf = io.StringIO()
        c = Console(stdout=buf)
        c.onecmd("SLOWLOG")
        assert "SELECT" in buf.getvalue()
        c.onecmd("SLOWLOG CLEAR")
        assert slowlog.entries() == []

    def test_zero_disables(self, db, monkeypatch):
        monkeypatch.setattr(config, "slow_query_ms", 0.0)
        slowlog.clear()
        db.query("SELECT name FROM Profiles WHERE uid = 2")
        assert slowlog.entries() == []


class TestEvidence:
    def test_sink_roundtrip_and_torn_tail(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        sink = EvidenceSink(p)
        sink.emit("a", {"x": 1})
        sink.emit("b", {"y": [1, 2]})
        sink.close()
        # a torn final line (process died mid-write) is skipped
        with open(p, "a") as f:
            f.write('{"seq": 3, "block": "c", "da')
        recs = read_evidence(p)
        assert [r["block"] for r in recs] == ["a", "b"]
        assert [r["seq"] for r in recs] == [1, 2]
        assert recs[1]["data"] == {"y": [1, 2]}
        assert all("elapsed_s" in r for r in recs)

    def test_bench_evidence_survives_sigkill(self, tmp_path):
        """A process that streams records through the sink and is
        SIGKILLed mid-stream (no atexit handler, no final flush) leaves
        every finished record on disk, in order and whole."""
        ev = str(tmp_path / "ev.jsonl")
        script = (
            "import sys, time\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "from orientdb_tpu.obs.evidence import EvidenceSink\n"
            f"sink = EvidenceSink({ev!r})\n"
            "i = 0\n"
            "while True:\n"
            "    i += 1\n"
            "    sink.emit('block%d' % i, {'i': i, 'pad': 'x' * 512})\n"
            "    time.sleep(0.01)\n"
        )
        proc = subprocess.Popen(
            [sys.executable, "-c", script],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        try:
            deadline = time.time() + 120
            while time.time() < deadline and proc.poll() is None:
                if len(read_evidence(ev)) >= 5:
                    break
                time.sleep(0.05)
            proc.kill()
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        recs = read_evidence(ev)
        assert len(recs) >= 5, f"no finished record on disk: {recs}"
        assert [r["seq"] for r in recs] == list(range(1, len(recs) + 1))
        for r in recs:
            assert r["block"] == f"block{r['seq']}"
            assert r["data"] == {"i": r["seq"], "pad": "x" * 512}
        # at most the record being written at the kill is torn: every
        # line before it parses as it stands
        with open(ev) as f:
            lines = [ln for ln in f.read().splitlines() if ln]
        assert len(lines) - len(recs) in (0, 1)
        assert [json.loads(ln)["seq"] for ln in lines[: len(recs)]] == [
            r["seq"] for r in recs
        ]

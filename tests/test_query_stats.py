"""The query-statistics & continuous-profiling plane (obs/stats,
obs/profile, obs/spanlint): fingerprint stability, per-fingerprint cost
accounting through the engine front door (including cached executions),
the slowlog ↔ stats ↔ trace join, the /stats endpoints, the
/cluster/metrics fan-in, the span-name catalog lint (tier-1), and the
sampling knob. What a request pays the stats plane is counted in
tests/test_plane_overhead.py."""

import io
import json
import time
import urllib.request

import pytest

from orientdb_tpu.obs.profile import SpanProfileAggregator, profiler
from orientdb_tpu.obs.slowlog import slowlog
from orientdb_tpu.obs.spanlint import SPAN_CATALOG, lint_spans
from orientdb_tpu.obs.stats import (
    QueryStats,
    fingerprint,
    fingerprint_cached,
    stats,
)
from orientdb_tpu.utils.config import config

@pytest.fixture(autouse=True)
def _clean_stats():
    stats.reset()
    profiler.reset()
    yield
    stats.reset()
    profiler.reset()


class TestFingerprint:
    def test_literal_variants_collapse(self):
        a = fingerprint("SELECT FROM P WHERE age > 40")
        b = fingerprint("SELECT FROM P WHERE age > 99")
        c = fingerprint("SELECT FROM P WHERE age > 'x'")
        assert a.fid == b.fid == c.fid
        assert "?" in a.text and "40" not in a.text

    def test_in_list_collapses_regardless_of_arity(self):
        a = fingerprint("SELECT FROM P WHERE uid IN [1, 2]")
        b = fingerprint("SELECT FROM P WHERE uid IN [1,2,3,4,5,6,7]")
        c = fingerprint("SELECT FROM P WHERE uid IN ['a']")
        assert a.fid == b.fid == c.fid
        assert "[?]" in a.text

    def test_negative_literal_lists_collapse_too(self):
        a = fingerprint("SELECT FROM P WHERE uid IN [-1, -2]")
        b = fingerprint("SELECT FROM P WHERE uid IN [-1,-2,-3]")
        c = fingerprint("SELECT FROM P WHERE uid IN [1, 2]")
        assert a.fid == b.fid == c.fid

    def test_whitespace_and_case_fold(self):
        a = fingerprint("select  from   Profiles  where AGE > 1")
        b = fingerprint("SELECT FROM profiles WHERE age > 2")
        assert a.fid == b.fid

    def test_display_text_keeps_identifier_spelling(self):
        fp = fingerprint("SELECT FROM Profiles WHERE Age > 1")
        assert "Profiles" in fp.text and "Age" in fp.text

    def test_distinct_shapes_do_not_collapse(self):
        one_hop = fingerprint(
            "MATCH {class:P, as:p}-K->{as:f} RETURN count(*) AS n"
        )
        two_hop = fingerprint(
            "MATCH {class:P, as:p}-K->{as:f}-K->{as:g} "
            "RETURN count(*) AS n"
        )
        proj_a = fingerprint("SELECT name FROM P")
        proj_b = fingerprint("SELECT age FROM P")
        fids = {one_hop.fid, two_hop.fid, proj_a.fid, proj_b.fid}
        assert len(fids) == 4

    def test_unlexable_input_still_gets_a_stable_id(self):
        a = fingerprint("%% not sql at  all %%")
        b = fingerprint("%%  not   sql at all %%")
        assert a.fid == b.fid  # whitespace-collapse fallback

    def test_cached_path_agrees_with_uncached(self):
        q = "SELECT FROM P WHERE uid = 7"
        assert fingerprint_cached(q) == fingerprint(q)


class TestStatsTable:
    def test_engine_front_door_counts_calls_rows_and_shapes(self, social_db):
        q = "SELECT name FROM Profiles WHERE age > 1"
        for _ in range(3):
            social_db.query(q).to_dicts()
        social_db.query("SELECT name FROM Profiles WHERE age > 99").to_dicts()
        row = stats.get(fingerprint(q).fid)
        assert row is not None
        # the age>99 variant is the SAME shape: 4 calls on one entry
        assert row["calls"] == 4
        assert row["rows_returned"] >= 5  # 3 full scans + 1 empty
        assert row["total_s"] > 0 and row["mean_ms"] > 0
        assert sum(row["latency_buckets"].values()) == 4
        assert "oracle" in row["engines"]

    def test_errors_are_counted_per_fingerprint(self, social_db):
        q = "SELECT bogus_function(name) FROM Profiles WHERE age > 0"
        fid = fingerprint(q).fid
        for _ in range(2):
            with pytest.raises(Exception):
                social_db.query(q)
        row = stats.get(fid)
        assert row is not None
        assert row["calls"] == 2 and row["errors"] == 2

    def test_cached_executions_still_count(self, social_db, monkeypatch):
        monkeypatch.setattr(config, "command_cache_enabled", True)
        q = "SELECT name FROM Profiles WHERE age > 2"
        social_db.query(q).to_dicts()
        social_db.query(q).to_dicts()
        social_db.query(q).to_dicts()
        row = stats.get(fingerprint(q).fid)
        assert row["calls"] == 3
        assert row["result_cache_hits"] == 2

    def test_sampling_zero_disables_accounting(self, social_db, monkeypatch):
        monkeypatch.setattr(config, "stats_sample_rate", 0.0)
        social_db.query("SELECT name FROM Profiles WHERE age > 3").to_dicts()
        assert len(stats) == 0

    def test_capacity_is_lru_bounded(self):
        small = QueryStats(capacity=4)
        for i in range(10):
            # distinct identifiers → distinct fingerprints
            small.record_external(f"SELECT col{i} FROM P", 0.001, "oracle")
        assert len(small) == 4
        # the most recent shapes survived
        assert small.get(fingerprint("SELECT col9 FROM P").fid) is not None
        assert small.get(fingerprint("SELECT col0 FROM P").fid) is None

    def test_capacity_config_is_read_live(self, monkeypatch):
        t = QueryStats()  # no explicit capacity: config governs
        monkeypatch.setattr(config, "query_stats_capacity", 2)
        for i in range(5):
            t.record_external(f"SELECT liv{i} FROM P", 0.001, "oracle")
        assert len(t) == 2
        monkeypatch.setattr(config, "query_stats_capacity", 4)
        for i in range(5, 8):
            t.record_external(f"SELECT liv{i} FROM P", 0.001, "oracle")
        assert len(t) == 4  # retuned without restarting

    def test_batch_statements_are_counted_per_shape(self, social_db):
        q1 = "SELECT name FROM Profiles WHERE age > 1"
        q2 = "SELECT age FROM Profiles WHERE age > 1"
        social_db.query_batch([q1, q2, q1])
        assert stats.get(fingerprint(q1).fid)["calls"] == 2
        assert stats.get(fingerprint(q2).fid)["calls"] == 1

    def test_top_sorts_by_requested_column(self):
        t = QueryStats(capacity=16)
        t.record_external("SELECT a FROM P", 0.5, "oracle")
        for _ in range(5):
            t.record_external("SELECT b FROM P", 0.001, "oracle")
        by_calls = t.top(2, by="calls")
        assert by_calls[0]["query"].startswith("SELECT b")
        by_total = t.top(2, by="total_s")
        assert by_total[0]["query"].startswith("SELECT a")
        # unknown column falls back instead of raising
        assert t.top(1, by="nope")[0]["query"].startswith("SELECT a")


class TestSlowlogJoin:
    def test_slowlog_entry_carries_the_stats_fingerprint(
        self, social_db, monkeypatch
    ):
        monkeypatch.setattr(config, "slow_query_ms", 0.0001)
        slowlog.clear()
        q = "SELECT name FROM Profiles WHERE age > 4"
        social_db.query(q).to_dicts()
        fid = fingerprint(q).fid
        entries = [e for e in slowlog.entries() if e["sql"] == q]
        assert entries and entries[0]["fingerprint"] == fid
        assert stats.get(fid) is not None  # the id joins both planes
        # console SLOWLOG prints the pivot id
        from orientdb_tpu.tools.console import Console

        buf = io.StringIO()
        c = Console(stdout=buf)
        c.onecmd("SLOWLOG")
        assert f"fp={fid}" in buf.getvalue()

    def test_console_stats_verbs(self, social_db):
        social_db.query("SELECT name FROM Profiles WHERE age > 5").to_dicts()
        from orientdb_tpu.tools.console import Console

        buf = io.StringIO()
        c = Console(stdout=buf)
        c.onecmd("STATS QUERIES 5")
        out = buf.getvalue()
        assert "fingerprint" in out and "SELECT" in out
        buf2 = io.StringIO()
        Console(stdout=buf2).onecmd("STATS PROFILE")
        assert "query" in buf2.getvalue()  # the folded front-door stage
        buf3 = io.StringIO()
        Console(stdout=buf3).onecmd("STATS RESET")
        assert "reset" in buf3.getvalue()
        assert len(stats) == 0


class TestProfileAggregator:
    def test_span_tree_folds_into_self_time(self):
        agg = SpanProfileAggregator()
        from orientdb_tpu.obs.trace import tracer, span

        tracer.add_listener(agg.on_span)
        try:
            with span("query"):
                with span("tpu.step"):
                    time.sleep(0.002)
                with span("tpu.step"):
                    time.sleep(0.002)
        finally:
            tracer.remove_listener(agg.on_span)
        prof = agg.profile()
        assert prof["traces"] == 1
        (root,) = [s for s in prof["stages"] if s["name"] == "query"]
        (step,) = [c for c in root["children"] if c["name"] == "tpu.step"]
        assert step["count"] == 2
        assert step["total_ms"] >= 4.0
        # parent self-time excludes the children's time. Tolerance: the
        # three values are independently rounded to 3 decimals, so the
        # identity can be off by up to 1.5 ulp (0.0015) — a 0.001 bound
        # flakes exactly at the rounding boundary (e.g. 0.1 vs
        # 5.137-5.038+0.001 = 0.09999...)
        assert root["self_ms"] <= root["total_ms"] - step["total_ms"] + 0.002
        flat = agg.flat(5)
        assert {r["name"] for r in flat} == {"query", "tpu.step"}

    def test_foreign_trace_contributes_local_subtree_only(self):
        agg = SpanProfileAggregator()
        from orientdb_tpu.obs.propagation import continue_trace
        from orientdb_tpu.obs.trace import tracer

        tracer.add_listener(agg.on_span)
        try:
            # a remote parent we never see locally
            with continue_trace(
                "replication.apply_entry",
                {"trace_id": "t" * 16, "span_id": "f" * 16},
            ):
                pass
        finally:
            tracer.remove_listener(agg.on_span)
        prof = agg.profile()
        names = [s["name"] for s in prof["stages"]]
        assert names == ["replication.apply_entry"]

    def test_force_joined_thread_does_not_steal_the_open_subtree(self):
        """Spans of ONE trace finishing on several threads (in-process
        replica apply force-joining the write's trace): the apply
        thread going idle must fold only ITS spans — not consume the
        write thread's still-open subtree, which would misattribute
        children as roots and double-count the parent's self time."""
        import threading

        agg = SpanProfileAggregator()
        from orientdb_tpu.obs.propagation import continue_trace
        from orientdb_tpu.obs.trace import span, tracer

        tracer.add_listener(agg.on_span)
        try:
            with span("command") as sp:
                with span("tx.commit"):
                    time.sleep(0.002)

                def apply_entry():
                    with continue_trace(
                        "replication.apply_entry",
                        {"trace_id": sp.trace_id, "span_id": sp.span_id},
                        force=True,
                    ):
                        pass

                t = threading.Thread(target=apply_entry)
                t.start()
                t.join()  # the apply thread went idle mid-command
        finally:
            tracer.remove_listener(agg.on_span)
        prof = agg.profile()
        top = {s["name"]: s for s in prof["stages"]}
        # the command tree stayed intact on its own thread…
        assert "command" in top and "tx.commit" not in top
        (commit,) = [
            c for c in top["command"]["children"] if c["name"] == "tx.commit"
        ]
        assert commit["count"] == 1
        # …self time excludes the child, i.e. no double counting. The
        # margin must absorb THREE independent 3-decimal roundings
        # (self/total/child are each rounded ±0.0005 ms in profile()) —
        # a real double count would err by the WHOLE child duration
        # (~2.5 ms), so 0.005 keeps the assertion meaningful without
        # the rounding coin toss that flaked full-suite runs.
        assert (
            top["command"]["self_ms"]
            <= top["command"]["total_ms"] - commit["total_ms"] + 0.005
        )
        # the apply thread's local subtree folded separately
        assert "replication.apply_entry" in top

    def test_rate_zero_disables_the_plane_entirely(self, monkeypatch):
        monkeypatch.setattr(config, "stats_sample_rate", 0.0)
        agg = SpanProfileAggregator()
        from orientdb_tpu.obs.trace import span, tracer

        tracer.add_listener(agg.on_span)
        try:
            with span("query"):
                pass
        finally:
            tracer.remove_listener(agg.on_span)
        # no lock-side bookkeeping at all, not just an empty profile
        assert agg._pending == {} and len(agg._pending_order) == 0
        assert agg.profile()["traces"] == 0

    def test_sampled_out_traces_do_not_leak_the_eviction_window(
        self, monkeypatch
    ):
        import orientdb_tpu.obs.profile as profile_mod

        monkeypatch.setattr(config, "stats_sample_rate", 0.5)
        monkeypatch.setattr(profile_mod, "sampled", lambda rate=None: False)
        agg = SpanProfileAggregator()
        from orientdb_tpu.obs.trace import span, tracer

        tracer.add_listener(agg.on_span)
        try:
            for _ in range(5):
                with span("query"):
                    pass
        finally:
            tracer.remove_listener(agg.on_span)
        # folded sampled-out traces release their order slot too
        assert agg._pending == {} and len(agg._pending_order) == 0


class TestSpanlint:
    """Back-compat shim: the canonical gate is
    tests/test_analysis.py (the lint now runs as the ``spanlint`` pass
    of orientdb_tpu/analysis); these names keep collecting."""

    def test_tree_is_clean(self):
        assert lint_spans() == []

    def test_uncataloged_span_name_is_flagged(self, tmp_path):
        pkg = tmp_path / "orientdb_tpu"
        pkg.mkdir()
        (pkg / "x.py").write_text('span("replication.aply")\n')
        problems = lint_spans(str(tmp_path))
        assert any("replication.aply" in p for p in problems)

    def test_stale_catalog_entry_is_flagged(self, tmp_path, monkeypatch):
        monkeypatch.setitem(
            SPAN_CATALOG, "ghost.stage", "never emitted anywhere"
        )
        pkg = tmp_path / "orientdb_tpu"
        pkg.mkdir()
        (pkg / "x.py").write_text('span("query")\n')
        problems = lint_spans(str(tmp_path))
        assert any("ghost.stage" in p for p in problems)


class TestSurfaces:
    def test_stats_endpoints_and_exposition(self, social_db):
        """GET /stats/queries (json top-K + promlint-clean prometheus)
        and GET /stats/profile on a live server."""
        import base64

        from orientdb_tpu.obs.promlint import lint_exposition
        from orientdb_tpu.server.server import Server

        q = "SELECT name FROM Profiles WHERE age > 6"
        social_db.query(q).to_dicts()
        fid = fingerprint(q).fid
        srv = Server(admin_password="pw")
        srv.attach_database(social_db)
        srv.startup()
        try:
            cred = base64.b64encode(b"admin:pw").decode()

            def get(path):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{srv.http_port}{path}",
                    headers={"Authorization": f"Basic {cred}"},
                )
                with urllib.request.urlopen(req, timeout=5) as r:
                    return r.read().decode()

            j = json.loads(get("/stats/queries?k=5&by=calls"))
            assert j["by"] == "calls"
            assert fid in {r["fingerprint"] for r in j["queries"]}
            prom = get("/stats/queries?format=prometheus")
            assert lint_exposition(prom) == []
            assert f'fingerprint="{fid}"' in prom
            assert "orienttpu_query_calls_total" in prom
            prof = json.loads(get("/stats/profile"))
            assert prof["traces"] >= 1
            # memory/process telemetry gauges ride the /metrics scrape
            full = get("/metrics")
            assert lint_exposition(full) == []
            assert "orienttpu_proc_rss_bytes" in full
            assert "orienttpu_proc_threads" in full
            assert "orienttpu_snapshot_column_bytes" in full
            assert "orienttpu_wal_segment_bytes" in full
        finally:
            srv.shutdown()

    def test_cluster_fan_in_labels_member_and_fingerprint(self, social_db):
        from orientdb_tpu.obs.promlint import lint_exposition
        from orientdb_tpu.obs.registry import (
            render_prometheus_multi,
            snapshot_all,
        )

        q = "SELECT name FROM Profiles WHERE age > 7"
        social_db.query(q).to_dicts()
        fid = fingerprint(q).fid
        snap = snapshot_all()
        assert fid in snap["query_stats"]
        multi = render_prometheus_multi({"node0": snap, "node1": snap})
        assert lint_exposition(multi) == []
        assert (
            f'orienttpu_query_calls_total{{fingerprint="{fid}",'
            f'member="node0"}}' in multi
        )
        assert f'member="node1"' in multi

    def test_debug_bundle_carries_stats_and_profile(self, social_db):
        from orientdb_tpu.obs.bundle import debug_bundle

        q = "SELECT name FROM Profiles WHERE age > 8"
        social_db.query(q).to_dicts()
        fid = fingerprint(q).fid
        b = debug_bundle(dbs=[social_db])
        assert fid in {r["fingerprint"] for r in b["query_stats"]}
        assert b["profile"]["traces"] >= 1
        stages = {s["name"] for s in b["profile"]["stages"]}
        assert "query" in stages

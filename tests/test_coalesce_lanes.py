"""Continuous cross-client micro-batching (server/coalesce.py):
fingerprint-keyed dispatch lanes, adaptive collection windows,
device-resident parameter rings, double-buffered dispatch, and
head-of-line isolation of poisoned batches.

This module is in the deviceguard GUARDED_SUITES: every test runs
under ``jax.transfer_guard`` (an implicit host↔device transfer on the
lane dispatch path fails the test that made it) and a same-shape plan
re-record anywhere in the module fails its observing test — the
acceptance bar "steady-state lane dispatch: zero implicit transfers,
zero recompiles" is enforced here, not just benched.
"""

import threading
import time
import urllib.parse

import numpy as np
import pytest

from orientdb_tpu.models.database import Database
from orientdb_tpu.server.coalesce import QueryCoalescer, _Lane, _SOLO_OFF
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics


def canon(rows):
    return sorted(
        tuple(sorted((k, str(v)) for k, v in r.items())) for r in rows
    )


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


def make_graph(name="lanes", n=60):
    db = Database(name)
    db.schema.create_vertex_class("P")
    db.schema.create_edge_class("K")
    vs = [db.new_vertex("P", n=i) for i in range(n)]
    for i in range(n - 1):
        db.new_edge("K", vs[i], vs[i + 1])
    return db


@pytest.fixture(scope="module")
def snap_db():
    db = make_graph("lanes_snap")
    attach_fresh_snapshot(db)
    return db


COUNT_SQL = "MATCH {class:P, as:a, where:(n < 40)}-K->{as:b} RETURN count(*) AS n"
PARAM_SQL = "SELECT count(*) AS c FROM P WHERE n < :k"


def submit_concurrently(co, db, jobs, timeout=60.0):
    """Submit [(sql, params), ...] from one thread each behind a
    barrier; returns ({idx: (rows, engine)}, {idx: error})."""
    results, errors = {}, {}
    start = threading.Barrier(len(jobs))

    def run(i, sql, params):
        try:
            start.wait(timeout=timeout)
            results[i] = co.submit(db, sql, params)
        except Exception as e:  # noqa: BLE001 - surfaced by assertions
            errors[i] = e

    ts = [
        threading.Thread(target=run, args=(i, s, p), daemon=True)
        for i, (s, p) in enumerate(jobs)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout)
    return results, errors


class TestLaneAssignment:
    def test_lane_key_is_the_fingerprint_and_deterministic(self):
        """Same-shape statements (different literals) share ONE lane;
        the lane key is the stats plane's fingerprint id, so assignment
        is deterministic across coalescer instances and processes."""
        from orientdb_tpu.obs.stats import fingerprint_cached

        s1 = "SELECT name FROM P WHERE n = 1"
        s2 = "SELECT name FROM P WHERE n = 2"
        assert fingerprint_cached(s1).fid == fingerprint_cached(s2).fid
        db = make_graph("lanes_key", n=5)
        co = QueryCoalescer(window_ms=5)
        try:
            co.submit(db, s1, None)
            co.submit(db, s2, None)
            lanes = co._lanes.get(id(db), {})
            assert set(lanes) == {fingerprint_cached(s1).fid}
        finally:
            co.stop()

    def test_two_shapes_never_share_a_micro_batch(self, monkeypatch):
        """Fingerprint isolation: concurrent traffic of two shapes must
        produce only homogeneous batches — one fingerprint per drain."""
        from orientdb_tpu.obs.stats import fingerprint_cached

        import orientdb_tpu.exec.engine as E

        seen = []
        real = E.execute_query_batch

        def recording(db, sqls, params_list=None, **kw):
            seen.append(list(sqls))
            return real(db, sqls, params_list, **kw)

        monkeypatch.setattr(E, "execute_query_batch", recording)
        db = make_graph("lanes_iso", n=10)  # no snapshot: generic path
        co = QueryCoalescer(window_ms=30)
        try:
            jobs = []
            for i in range(8):
                jobs.append(("SELECT count(*) AS c FROM P", None))
                jobs.append((f"SELECT name FROM P WHERE n = {i}", None))
            results, errors = submit_concurrently(co, db, jobs)
            assert not errors, errors
            assert len(results) == len(jobs)
        finally:
            co.stop()
        assert seen, "no batches drained"
        for batch in seen:
            fids = {fingerprint_cached(s).fid for s in batch}
            assert len(fids) == 1, f"mixed-shape micro-batch: {batch}"

    def test_grouping_actually_happens_in_one_lane(self):
        db = make_graph("lanes_grp", n=10)
        co = QueryCoalescer(window_ms=30)
        before = _counter("coalesce.grouped")
        try:
            jobs = [("SELECT count(*) AS c FROM P", None)] * 6
            results, errors = submit_concurrently(co, db, jobs)
            assert not errors, errors
            assert all(r[0] == [{"c": 10}] for r in results.values())
        finally:
            co.stop()
        assert _counter("coalesce.grouped") > before


class TestAdaptiveWindow:
    def test_window_rules(self):
        """The learned window: zero for sequential traffic, zero when
        arrivals are sparser than the cap, ~exec-EWMA otherwise, always
        bounded by coalesce_window_max_ms; a coalescer-level fixed
        window (tests/back-compat) overrides adaptivity."""
        db = make_graph("lanes_win", n=3)
        co = QueryCoalescer()
        lane = _Lane(co, db, "deadbeefdeadbeef")
        try:
            cap_s = config.coalesce_window_max_ms / 1000.0
            # fresh lane: solo counter starts at the off threshold, so
            # lone clients never wait
            assert lane._window_s() == 0.0
            lane._solo_drains = 0
            # no arrival evidence yet -> no wait
            lane._gap_ewma = None
            assert lane._window_s() == 0.0
            # arrivals sparser than the cap -> waiting buys nothing
            lane._gap_ewma = cap_s * 10
            assert lane._window_s() == 0.0
            # dense arrivals + slow batches -> window, capped
            lane._gap_ewma = cap_s / 50
            lane._exec_ewma = cap_s * 100
            assert lane._window_s() == pytest.approx(cap_s)
            # dense arrivals + fast batches -> window ~ exec time
            lane._exec_ewma = cap_s / 2
            assert 0.0 < lane._window_s() <= cap_s
            # solo streak re-disarms the window
            lane._solo_drains = _SOLO_OFF
            assert lane._window_s() == 0.0
            # fixed override wins over everything
            co.window_s = 0.017
            assert lane._window_s() == 0.017
        finally:
            lane.stop()
            co.stop()

    def test_single_query_pays_no_window_when_sequential(self):
        """A lone client's sequential singles drain immediately: every
        drain is solo, so the adaptive window stays off."""
        db = make_graph("lanes_solo", n=5)
        co = QueryCoalescer()  # adaptive
        try:
            for _ in range(5):
                rows, _e = co.submit(db, "SELECT count(*) AS c FROM P", None)
                assert rows == [{"c": 5}]
            lanes = co._lanes.get(id(db), {})
            assert len(lanes) == 1
            lane = next(iter(lanes.values()))
            with lane._cond:
                assert lane._window_s() == 0.0
        finally:
            co.stop()


class TestParamRing:
    def test_ring_reuses_staged_buffer_for_repeated_values(self):
        from orientdb_tpu.exec.tpu_engine import ParamRing

        ring = ParamRing()
        host1 = {"k": np.asarray([1, 2, 3], np.int32)}
        before_up = _counter("tpu.param_ring.upload")
        before_hit = _counter("tpu.param_ring.hit")
        d1 = ring.stage(dict(host1))
        d2 = ring.stage({"k": np.asarray([1, 2, 3], np.int32)})
        assert d2 is d1, "repeated value set must reuse the staged slot"
        d3 = ring.stage({"k": np.asarray([9, 9, 9], np.int32)})
        assert d3 is not d1
        # double buffering: the second distinct set lands in the OTHER
        # slot, so the first stays valid (an in-flight dispatch may
        # still read it) and a third repeat of set 1 hits again
        d4 = ring.stage({"k": np.asarray([1, 2, 3], np.int32)})
        assert d4 is d1
        assert _counter("tpu.param_ring.upload") - before_up == 2
        assert _counter("tpu.param_ring.hit") - before_hit == 2

    def test_ring_distinguishes_shapes_and_keys(self):
        from orientdb_tpu.exec.tpu_engine import ParamRing

        ring = ParamRing()
        a = ring.stage({"k": np.asarray([1, 2], np.int32)})
        b = ring.stage({"k": np.asarray([1, 2, 3], np.int32)})
        c = ring.stage({"j": np.asarray([1, 2], np.int32)})
        assert a is not b and b is not c

    def test_lane_dispatch_rides_the_ring_with_zero_uploads_on_repeat(
        self, snap_db
    ):
        """Steady state: a lane re-dispatching the same parameter set
        stages NOTHING — the device-resident buffers serve every
        dispatch (and the module-level transfer guard proves no
        implicit transfer sneaks in instead)."""
        import orientdb_tpu.exec.engine as E
        from orientdb_tpu.exec.tpu_engine import drain_warmups

        sqls = [PARAM_SQL] * 4
        plist = [{"k": 17}] * 4
        # record + warm the plan and the vmapped group executable
        snap_db.query(PARAM_SQL, {"k": 17}, engine="tpu", strict=True)
        drain_warmups()
        ring_state = {}
        h = None
        deadline = time.time() + 30
        while h is None and time.time() < deadline:
            h = E.dispatch_lane_batch(
                snap_db, sqls, plist, ring_state=ring_state
            )
            if h is None:  # group executable still compiling
                drain_warmups()
        assert h is not None, "lane fast path never became available"
        first = h.collect()
        assert all(rs.to_dicts() == [{"c": 17}] for rs in first)
        up0 = _counter("tpu.param_ring.upload")
        hit0 = _counter("tpu.param_ring.hit")
        for _ in range(3):
            h = E.dispatch_lane_batch(
                snap_db, sqls, plist, ring_state=ring_state
            )
            assert h is not None
            outs = h.collect(queue_waits=[0.01] * 4)
            assert all(r.to_dicts() == [{"c": 17}] for r in outs)
        assert _counter("tpu.param_ring.upload") == up0, (
            "steady-state lane dispatch re-uploaded parameters"
        )
        assert _counter("tpu.param_ring.hit") - hit0 >= 3
        # amortized device/transfer attribution reaches the stats
        # table (review fix: _finish_pending feeds add_device, which
        # the lane's stats.capture() splits across members)
        import orientdb_tpu.obs.stats as S

        row = S.stats.get(S.fingerprint_cached(PARAM_SQL).fid)
        assert row is not None
        assert row["bytes_fetched"] > 0, (
            "lane path lost device/transfer attribution"
        )
        assert row["queue_s"] > 0.0


class TestLaneCorrectness:
    def test_lane_results_match_oracle_count_and_rows(self, snap_db):
        """Concurrent same-shape singles through the lanes return
        exactly the oracle's rows — for the count pushdown shape AND a
        row-returning shape (the rows-group replay path)."""
        rows_sql = (
            "MATCH {class:P, as:a, where:(n < 6)}-K->{as:b} "
            "RETURN a.n AS a, b.n AS b"
        )
        expected = {
            COUNT_SQL: canon(
                snap_db.query(COUNT_SQL, engine="oracle").to_dicts()
            ),
            rows_sql: canon(
                snap_db.query(rows_sql, engine="oracle").to_dicts()
            ),
        }
        co = QueryCoalescer(window_ms=20)
        try:
            for sql in (COUNT_SQL, rows_sql):
                co.submit(snap_db, sql, None)  # record the plan
            from orientdb_tpu.exec.tpu_engine import drain_warmups

            drain_warmups()
            jobs = [(COUNT_SQL, None), (rows_sql, None)] * 6
            results, errors = submit_concurrently(co, snap_db, jobs)
            assert not errors, errors
            for i, (sql, _p) in enumerate(jobs):
                assert canon(results[i][0]) == expected[sql], sql
        finally:
            co.stop()

    def test_varying_params_in_one_lane_return_per_item_results(
        self, snap_db
    ):
        co = QueryCoalescer(window_ms=20)
        try:
            co.submit(snap_db, PARAM_SQL, {"k": 3})
            from orientdb_tpu.exec.tpu_engine import drain_warmups

            drain_warmups()
            jobs = [(PARAM_SQL, {"k": 3 + i}) for i in range(8)]
            results, errors = submit_concurrently(co, snap_db, jobs)
            assert not errors, errors
            for i in range(8):
                assert results[i][0] == [{"c": 3 + i}]
        finally:
            co.stop()


class TestMixedLiteralsOneLane:
    def test_mixed_literal_items_each_get_their_own_result(self, snap_db):
        """Lanes fold literals into one fingerprint, but a compiled
        plan bakes its recording literals — a drain mixing 'n < 10'
        and 'n < 20' must NOT replay item[0]'s plan for everyone
        (review fix: dispatch_lane bails to the generic path when any
        item's plan-cache key differs)."""
        sql10 = "SELECT count(*) AS c FROM P WHERE n < 10"
        sql20 = "SELECT count(*) AS c FROM P WHERE n < 20"
        from orientdb_tpu.obs.stats import fingerprint_cached

        assert (
            fingerprint_cached(sql10).fid == fingerprint_cached(sql20).fid
        ), "precondition: the two literals share a lane"
        co = QueryCoalescer(window_ms=30)
        try:
            co.submit(snap_db, sql10, None)  # record + cache sql10's plan
            from orientdb_tpu.exec.tpu_engine import drain_warmups

            drain_warmups()
            for _ in range(3):
                jobs = [(sql10, None), (sql20, None)] * 4
                results, errors = submit_concurrently(co, snap_db, jobs)
                assert not errors, errors
                for i, (sql, _p) in enumerate(jobs):
                    want = 10 if sql is sql10 else 20
                    assert results[i][0] == [{"c": want}], (
                        f"item got another literal's result: {sql}"
                    )
        finally:
            co.stop()

    def test_dispatch_lane_rejects_mixed_cache_keys(self, snap_db):
        import orientdb_tpu.exec.engine as E
        from orientdb_tpu.exec.tpu_engine import drain_warmups

        sql10 = "SELECT count(*) AS c FROM P WHERE n < 10"
        sql20 = "SELECT count(*) AS c FROM P WHERE n < 20"
        snap_db.query(sql10, engine="tpu", strict=True)
        drain_warmups()
        h = E.dispatch_lane_batch(snap_db, [sql10, sql20], [None, None])
        assert h is None, "mixed-literal batch took the single-plan path"


class TestLaneSurvivesBadResults:
    def test_lazily_failing_result_routes_to_fallback(self, monkeypatch):
        """A ResultSet that raises during to_dicts() (lazy row stream)
        must hit the per-item fallback like any batch failure — not
        escape _execute_generic and kill the drain loop."""
        import orientdb_tpu.exec.engine as E

        class _Lazy:
            engine = "oracle"

            def to_dicts(self):
                raise RuntimeError("lazy row stream error")

        calls = {"n": 0}
        real = E.execute_query_batch

        def flaky(db, sqls, params_list=None, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                return [_Lazy() for _ in sqls]
            return real(db, sqls, params_list, **kw)

        monkeypatch.setattr(E, "execute_query_batch", flaky)
        db = make_graph("lanes_lazy", n=4)
        co = QueryCoalescer()
        try:
            rows, _e = co.submit(db, "SELECT count(*) AS c FROM P", None)
            assert rows == [{"c": 4}]  # fallback served the item
            # the lane worker is still alive and serving
            rows, _e = co.submit(db, "SELECT count(*) AS c FROM P", None)
            assert rows == [{"c": 4}]
        finally:
            co.stop()

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"
    )
    def test_worker_death_fails_items_loudly_and_lane_rebuilds(
        self, monkeypatch
    ):
        """A BaseException escaping the drain loop (SimulatedCrash
        through except-Exception recovery) must fail queued items with
        an error — not leave them parked until timeout — and the dead
        lane must drop from the registry so the next submit rebuilds."""
        import orientdb_tpu.exec.engine as E
        from orientdb_tpu.chaos import SimulatedCrash

        calls = {"n": 0}
        real = E.execute_query_batch

        def crashing(db, sqls, params_list=None, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise SimulatedCrash("worker dies mid-drain")
            return real(db, sqls, params_list, **kw)

        monkeypatch.setattr(E, "execute_query_batch", crashing)
        db = make_graph("lanes_crash", n=4)
        co = QueryCoalescer()
        try:
            with pytest.raises(Exception):
                co.submit(db, "SELECT count(*) AS c FROM P", None, timeout=20)
            # the fingerprint is not wedged: a fresh submit succeeds
            rows, _e = co.submit(db, "SELECT count(*) AS c FROM P", None)
            assert rows == [{"c": 4}]
        finally:
            co.stop()


class TestHeadOfLineIsolation:
    def test_poisoned_batch_falls_back_off_thread_and_lane_stays_hot(
        self, monkeypatch
    ):
        """One bad query among 63 good ones: the batch-level failure is
        isolated per item on a DETACHED fallback thread — the poisoned
        item gets ITS error, the 63 innocents get rows, and the lane's
        drain loop keeps serving new queries WHILE the fallback is
        still stuck on the poison."""
        import orientdb_tpu.exec.engine as E

        POISON = "99991"
        real_batch = E.execute_query_batch

        def failing_batch(db, sqls, params_list=None, **kw):
            if any(POISON in s for s in sqls):
                raise RuntimeError("batch classed by poison member")
            return real_batch(db, sqls, params_list, **kw)

        monkeypatch.setattr(E, "execute_query_batch", failing_batch)

        gate = threading.Event()
        entered_poison = threading.Event()
        real_query = Database.query

        def blocking_query(self, sql, params=None, **kw):
            if POISON in sql:
                entered_poison.set()
                gate.wait(10)
                raise ValueError("poison item")
            return real_query(self, sql, params, **kw)

        monkeypatch.setattr(Database, "query", blocking_query)

        db = make_graph("lanes_hol", n=8)  # no snapshot: generic path
        co = QueryCoalescer(window_ms=60)
        fb_before = _counter("coalesce.batch_fallback")
        try:
            # 63 good + 1 poison, ALL one fingerprint (literals fold)
            jobs = [
                (f"SELECT count(*) AS c FROM P WHERE n != {10000 + i}", None)
                for i in range(63)
            ]
            jobs.insert(31, (f"SELECT count(*) AS c FROM P WHERE n != {POISON}", None))
            results, errors = submit_concurrently(co, db, jobs, timeout=90.0)
            # the poison member is parked on `gate` inside the fallback
            # thread by now (or the whole cohort already drained in >1
            # batches — then at least the poisoned batch is parked)
            assert entered_poison.wait(15), "fallback never reached poison"
            # drain loop must still be alive: a FRESH query through the
            # same lane completes while the fallback is stuck
            t0 = time.monotonic()
            rows, _e = co.submit(
                db, "SELECT count(*) AS c FROM P WHERE n != 77", None
            )
            assert rows == [{"c": 8}]
            assert time.monotonic() - t0 < 5.0, (
                "drain loop stalled behind the poisoned cohort"
            )
            gate.set()
            # now everyone settles: 63 innocents with rows, poison with
            # its own error
            deadline = time.time() + 30
            while len(results) + len(errors) < 64 and time.time() < deadline:
                time.sleep(0.05)
            assert len(results) + len(errors) == 64
            assert len(errors) == 1, errors
            (poison_err,) = errors.values()
            assert isinstance(poison_err, ValueError)
            assert all(
                r[0] == [{"c": 8}] for r in results.values()
            ), "an innocent batch member lost its rows"
        finally:
            gate.set()
            co.stop()
        assert _counter("coalesce.batch_fallback") > fb_before


class TestChaosBinSend:
    def test_coalesced_query_under_bin_send_fault(self):
        """A dropped response frame (bin.send chaos) fails only the
        session it hit: the coalescer and the server stay healthy and
        the next session's coalesced query answers normally."""
        from orientdb_tpu.chaos import FaultPlan, fault
        from orientdb_tpu.client.remote import connect
        from orientdb_tpu.server import Server

        srv = Server(admin_password="pw")
        db = srv.create_database("chaoslane")
        db.schema.create_vertex_class("P")
        db.new_vertex("P", n=1)
        srv.startup()
        try:
            url = f"remote:127.0.0.1:{srv.binary_port}/chaoslane"
            with connect(url, "admin", "pw") as rdb:
                assert rdb.query("SELECT count(*) AS c FROM P").to_dicts() == [
                    {"c": 1}
                ]
            items_before = _counter("coalesce.items")
            plan = FaultPlan(seed=11).at("bin.send", "error", times=1)
            fault.arm(plan)
            try:
                with pytest.raises(Exception):
                    with connect(url, "admin", "pw") as rdb:
                        rdb.query("SELECT count(*) AS c FROM P")
            finally:
                fault.disarm()
            # the lane executed the query even though the reply frame
            # died on the wire; a fresh session works immediately
            with connect(url, "admin", "pw") as rdb:
                assert rdb.query("SELECT count(*) AS c FROM P").to_dicts() == [
                    {"c": 1}
                ]
            assert _counter("coalesce.items") > items_before
        finally:
            fault.disarm()
            srv.shutdown()


class TestObservability:
    def test_queue_wait_lands_in_the_stats_table(self):
        import orientdb_tpu.obs.stats as S

        sql = "SELECT count(*) AS c FROM P WHERE n >= 0"
        fid = S.fingerprint_cached(sql).fid
        db = make_graph("lanes_obs", n=4)
        co = QueryCoalescer(window_ms=40)  # guarantees measurable waits
        try:
            jobs = [(sql, None)] * 4
            results, errors = submit_concurrently(co, db, jobs)
            assert not errors, errors
        finally:
            co.stop()
        row = S.stats.get(fid)
        assert row is not None
        assert row["queue_s"] > 0.0, "queue wait was not attributed"
        # the new column is exported like every scalar field
        assert any(f == "queue_s" for f, _m, _t in S.EXPORT_FIELDS)

    def test_dispatch_span_continues_the_submitters_trace(self):
        from orientdb_tpu.obs.trace import span, tracer

        db = make_graph("lanes_span", n=3)
        co = QueryCoalescer()
        try:
            with span("test.client") as root:
                co.submit(db, "SELECT count(*) AS c FROM P", None)
            got = tracer.spans(trace_id=root.trace_id)
            names = [s.name for s in got]
            assert "coalesce.lane" in names, names
            # the lane worker's dispatch span adopted the submitter's
            # trace id even though it ran on a different thread
            assert "coalesce.dispatch" in names, names
            disp = [s for s in got if s.name == "coalesce.dispatch"][-1]
            assert disp.attrs.get("n") == 1
            assert disp.attrs.get("lane")
        finally:
            co.stop()

    def test_lane_gauges_and_batch_size_histogram(self):
        from orientdb_tpu.obs.registry import obs

        db = make_graph("lanes_gauge", n=3)
        co = QueryCoalescer(window_ms=10)
        try:
            jobs = [("SELECT count(*) AS c FROM P", None)] * 3
            results, errors = submit_concurrently(co, db, jobs)
            assert not errors, errors
        finally:
            co.stop()
        gauges = metrics.snapshot()["gauges"]
        assert "coalesce.lanes" in gauges
        assert "coalesce.lane_depth" in gauges
        assert "coalesce.window_ms" in gauges
        hist = obs.snapshot().get("coalesce.batch_size")
        assert hist is not None and hist["count"] >= 1

    def test_idle_lane_retires_its_worker(self, monkeypatch):
        monkeypatch.setattr(config, "coalesce_lane_idle_s", 0.2)
        db = make_graph("lanes_idle", n=3)
        co = QueryCoalescer()
        try:
            co.submit(db, "SELECT count(*) AS c FROM P", None)
            assert co._lanes.get(id(db))
            deadline = time.time() + 10
            while co._lanes.get(id(db)) and time.time() < deadline:
                time.sleep(0.05)
            assert not co._lanes.get(id(db)), "idle lane never retired"
            # and the lane rebuilds transparently on the next submit
            rows, _e = co.submit(db, "SELECT count(*) AS c FROM P", None)
            assert rows == [{"c": 3}]
        finally:
            co.stop()

    def test_lane_cap_reaps_longest_idle_lane(self, monkeypatch):
        monkeypatch.setattr(config, "coalesce_lanes_max", 2)
        db = make_graph("lanes_cap", n=3)
        co = QueryCoalescer()
        try:
            co.submit(db, "SELECT count(*) AS c FROM P", None)
            co.submit(db, "SELECT name FROM P WHERE n = 1", None)
            co.submit(db, "SELECT n FROM P WHERE n < 2", None)
            assert len(co._lanes.get(id(db), {})) <= 2
        finally:
            co.stop()


class TestHttpLaneRoute:
    def test_http_query_verb_rides_the_coalescer(self):
        """The HTTP GET query verb submits to the same lanes the binary
        `query` op uses — no HTTP session pays a lone dispatch
        anymore."""
        import base64
        import json
        import urllib.request

        from orientdb_tpu.server import Server

        srv = Server(admin_password="pw")
        db = srv.create_database("httplane")
        db.schema.create_vertex_class("P")
        db.new_vertex("P", n=1)
        db.new_vertex("P", n=2)
        srv.startup()
        try:
            before = _counter("coalesce.items")
            sql = urllib.parse.quote("SELECT count(*) AS c FROM P", safe="")
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.http_port}/query/httplane/sql/{sql}"
            )
            req.add_header(
                "Authorization",
                "Basic " + base64.b64encode(b"admin:pw").decode(),
            )
            with urllib.request.urlopen(req) as resp:
                body = json.loads(resp.read())
            assert body["result"] == [{"c": 2}]
            assert _counter("coalesce.items") > before, (
                "HTTP query did not ride the coalescer"
            )
        finally:
            srv.shutdown()


class TestLaneEpochKeying:
    """Write-epoch correctness: a lane window formed pre-write must not
    serve post-write queries stale results (ISSUE 15 satellite)."""

    def test_items_carry_their_admission_epoch(self):
        db = make_graph("lanes_epoch0", n=10)
        attach_fresh_snapshot(db)
        co = QueryCoalescer()
        try:
            co.submit(db, COUNT_SQL, None)
            e0 = db.mutation_epoch
            db.new_vertex("P", n=99)
            assert db.mutation_epoch > e0
            # the NEXT submit stamps the post-write epoch; the lane
            # dispatch refuses any snapshot that does not cover it
            # (tpu_engine.dispatch_lane min_epoch gate)
            rows, _ = co.submit(db, COUNT_SQL, None)
            oracle = db.query(COUNT_SQL, engine="oracle").to_dicts()
            assert rows == oracle
        finally:
            co.stop()

    def test_lane_never_serves_post_write_queries_stale_results(self):
        """Interleave writes with coalesced reads on a delta-maintained
        snapshot: every read admitted after a write reflects it — the
        epoch-keyed lane either catches the snapshot up (delta apply)
        or routes to the generic path, never a stale replay."""
        from orientdb_tpu.storage.deltas import arm_delta_maintenance

        db = make_graph("lanes_epoch1", n=30)
        arm_delta_maintenance(db, spare_vertices=64, spare_edges=64)
        co = QueryCoalescer()
        try:
            rows, _ = co.submit(db, COUNT_SQL, None)
            anchors = [d for d in db.browse_class("P")][:5]
            for k in range(4):
                w = db.new_vertex("P", n=5 + k)  # n<40: a result row
                db.new_edge("K", anchors[k], w)
                rows, _ = co.submit(db, COUNT_SQL, None)
                oracle = db.query(COUNT_SQL, engine="oracle").to_dicts()
                assert rows == oracle, (
                    f"stale lane result after write {k}: "
                    f"{rows} vs {oracle}"
                )
        finally:
            co.stop()

    def test_dispatch_lane_min_epoch_gate(self, snap_db):
        from orientdb_tpu.exec import tpu_engine
        from orientdb_tpu.exec.engine import parse_cached

        db = snap_db
        db.query(COUNT_SQL, engine="tpu", strict=True)
        tpu_engine.drain_warmups()
        items = [(parse_cached(COUNT_SQL), {})]
        h = tpu_engine.dispatch_lane(db, items, min_epoch=db.mutation_epoch)
        if h is not None:
            h.collect()
        # an admission epoch beyond the snapshot's coverage must refuse
        assert (
            tpu_engine.dispatch_lane(
                db, items, min_epoch=db.mutation_epoch + 1
            )
            is None
        )

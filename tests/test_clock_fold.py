"""The program's own clocks as window counters (PR 27).

Spans (``obs/trace``) and critical-path segments (``obs/critpath``)
fold into ``utils/metrics`` counters at the points where they already
finish, so any window's share of them is a counter delta; the lane
worker's two halves of a turn are spans of their own; the watchdog's
tick and each gauge provider are timed by name; the overlap pass that
one of those providers runs is records x log intervals; and the
kernels of ``ops/csr`` carry their names into a lowered plan.
"""

import random
import subprocess
import sys
import threading
import time

import pytest

import orientdb_tpu.obs.critpath as CP
import orientdb_tpu.obs.timeline as TL
from orientdb_tpu.obs.timeline import DispatchRecord, FlightRecorder
from orientdb_tpu.obs.trace import span, tracer
from orientdb_tpu.utils.metrics import MetricsRegistry, metrics


def counters(prefix=""):
    return {
        k: v
        for k, v in metrics.snapshot()["counters"].items()
        if k.startswith(prefix)
    }


def moved(after, before):
    return {
        k: v - before.get(k, 0)
        for k, v in after.items()
        if v != before.get(k, 0)
    }


# -- utils/metrics.incr_many -------------------------------------------------


class TestIncrMany:
    def test_many_counters_move_under_one_call(self):
        reg = MetricsRegistry()
        reg.incr("a", 2)
        reg.incr_many({"a": 3, "b": 1, "c": 0})
        assert reg.counter("a") == 5 and reg.counter("b") == 1
        assert reg.snapshot()["counters"]["c"] == 0
        reg.incr_many({})
        assert reg.counter("a") == 5

    def test_no_update_is_lost_between_threads(self):
        reg = MetricsRegistry()
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            def work():
                for _ in range(2000):
                    reg.incr_many({"x.us": 3, "x.n": 1})
                    reg.incr("x.n")

            ts = [threading.Thread(target=work) for _ in range(16)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in ts)
        finally:
            sys.setswitchinterval(old)
        assert reg.counter("x.us") == 16 * 2000 * 3
        assert reg.counter("x.n") == 16 * 2000 * 2


# -- the span fold -----------------------------------------------------------


class TestSpanFold:
    def test_a_span_adds_its_own_duration_and_one_to_its_count(self):
        before = counters("span.fold.unit.")
        with span("fold.unit") as a:
            time.sleep(0.01)
        with span("fold.unit") as b:
            pass
        got = moved(counters("span.fold.unit."), before)
        assert got["span.fold.unit.n"] == 2
        want = a.duration_us + b.duration_us
        assert a.duration_us >= 10_000
        # whole microseconds, each span rounded on its own
        assert abs(got["span.fold.unit.us"] - want) <= 1.0

    def test_nested_spans_fold_each_under_its_own_name(self):
        before = counters("span.fold.")
        with span("fold.outer") as o:
            with span("fold.inner") as i:
                time.sleep(0.002)
        got = moved(counters("span.fold."), before)
        assert got["span.fold.outer.n"] == 1 and got["span.fold.inner.n"] == 1
        assert got["span.fold.outer.us"] >= got["span.fold.inner.us"] - 1
        assert abs(got["span.fold.inner.us"] - i.duration_us) <= 0.5
        assert abs(got["span.fold.outer.us"] - o.duration_us) <= 0.5

    def test_a_span_that_raises_folds_all_the_same(self):
        before = counters("span.fold.err.")
        with pytest.raises(ValueError):
            with span("fold.err"):
                raise ValueError("x")
        assert moved(counters("span.fold.err."), before)["span.fold.err.n"] == 1

    def test_a_listener_that_raises_fails_no_span_exit(self):
        def bad(_sp):
            raise RuntimeError("listener")

        tracer.add_listener(bad)
        try:
            before = counters("span.fold.listener.")
            with span("fold.listener") as sp:
                pass
            assert sp.duration_us is not None
            got = moved(counters("span.fold.listener."), before)
            assert got["span.fold.listener.n"] == 1
        finally:
            tracer.remove_listener(bad)

    def test_the_count_outlives_the_ring(self):
        n = tracer._spans.maxlen + 10
        before = counters("span.fold.ring.")
        for _ in range(n):
            with span("fold.ring"):
                pass
        assert moved(counters("span.fold.ring."), before)["span.fold.ring.n"] == n
        assert len(tracer.spans(name="fold.ring")) < n

    def test_names_off_the_wire_cannot_grow_the_registry(self):
        from orientdb_tpu.obs.trace import _FOLD_NAMES_MAX, Tracer

        t = Tracer(16)
        before = counters("span.")
        for k in range(_FOLD_NAMES_MAX + 40):
            sp = span(f"binary.op{k}")
            sp.duration_us = 2.0
            t.record(sp)
        got = moved(counters("span."), before)
        assert got["span._other.n"] == 40 and got["span._other.us"] == 80
        assert len([k for k in got if k.endswith(".n")]) == _FOLD_NAMES_MAX + 1
        # a name that has its counters keeps them past the cap
        sp = span("binary.op0")
        sp.duration_us = 1.0
        t.record(sp)
        assert moved(counters("span.binary.op0."), before)["span.binary.op0.n"] == 2

    def test_a_span_brings_no_jax_into_a_process_that_has_none(self):
        code = (
            "import sys\n"
            "from orientdb_tpu.obs.trace import span\n"
            "from orientdb_tpu.utils.metrics import metrics\n"
            "with span('client.side') as sp:\n"
            "    pass\n"
            "assert sp._ann is None\n"
            "assert metrics.counter('span.client.side.n') == 1\n"
            "print('jax' in sys.modules)\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "False"

    def test_with_jax_loaded_a_span_is_a_profiler_annotation_too(self):
        import jax  # noqa: F401  (the engine's process has it)

        with span("fold.annotated") as sp:
            assert sp._ann is not None
        assert sp._ann is None and sp.duration_us is not None


# -- the critpath fold -------------------------------------------------------


class TestCritpathFold:
    def test_the_folded_segments_sum_to_the_committed_walls(self, monkeypatch):
        from orientdb_tpu.utils.config import config

        monkeypatch.setattr(config, "critpath_enabled", True)
        monkeypatch.setattr(config, "stats_sample_rate", 1.0)
        plane = CP.CritPathPlane(capacity=64)
        before = counters("critpath.")
        walls = 0.0
        stamped = {"parse": 0.0, "queue": 0.0, "marshal": 0.0}
        rng = random.Random(27)
        for k in range(40):
            cp = CP.begin_request("binary", "SELECT 1")
            assert cp is not None
            # a request that began a while ago, partly stamped: commit
            # folds the rest into host_compute
            cp.t0 -= 0.010 + rng.random() * 0.010
            for seg in stamped:
                s = rng.random() * 0.002
                cp.add(seg, s)
                stamped[seg] += s
            plane.commit(cp)
            assert cp.total() == pytest.approx(cp.wall_s, abs=1e-9)
            walls += cp.wall_s
        got = moved(counters("critpath."), before)
        assert got.pop("critpath.requests") == 40
        assert set(got) == {
            "critpath.parse_us",
            "critpath.queue_us",
            "critpath.marshal_us",
            "critpath.host_compute_us",
        }
        # the plane's invariant, now visible in counters: each of 4
        # segments of 40 records was rounded to a whole microsecond
        assert abs(sum(got.values()) - walls * 1e6) <= 4 * 40 * 0.5 + 1
        for seg, s in stamped.items():
            assert abs(got[f"critpath.{seg}_us"] - s * 1e6) <= 40 * 0.5 + 1
        # and the same numbers as the plane's own totals
        for seg, s in plane.totals().items():
            assert abs(got[f"critpath.{seg}_us"] - s * 1e6) <= 40 * 0.5 + 1

    def test_no_record_no_fold(self):
        before = counters("critpath.")
        CP.CritPathPlane(capacity=4).commit(None)
        assert moved(counters("critpath."), before) == {}


# -- the tick, the providers, the lane worker's spans --------------------------


class TestTickAndProviders:
    def test_each_provider_is_timed_under_its_name(self):
        from orientdb_tpu.obs import profile

        def slow_provider():
            time.sleep(0.005)

        def broken_provider():
            raise RuntimeError("telemetry must never fail a scrape")

        profile.register_gauge_provider(slow_provider)
        profile.register_gauge_provider(broken_provider)
        try:
            before = counters("obs.provider_us.")
            profile.run_gauge_providers()
            got = moved(counters("obs.provider_us."), before)
        finally:
            profile.unregister_gauge_provider(slow_provider)
            profile.unregister_gauge_provider(broken_provider)
        assert got["obs.provider_us.slow_provider"] >= 5_000
        assert "obs.provider_us.broken_provider" in counters("obs.provider_us.")
        # the built-in ones, by the names PERF.md lists
        assert "obs.provider_us.process_telemetry" in got
        assert "obs.provider_us.publish_overlap_gauges" in counters(
            "obs.provider_us."
        )

    def test_a_servers_database_provider_has_a_name_of_its_own(self):
        from orientdb_tpu.obs.profile import database_telemetry

        assert database_telemetry(lambda: []).__name__ == "database_telemetry"

    def test_a_tick_counts_itself_and_its_evaluation_is_a_folded_span(self):
        from orientdb_tpu.obs.watchdog import HealthWatchdog

        class _Host:
            databases = {}
            cluster = None

        before = counters()
        HealthWatchdog(_Host()).tick()
        HealthWatchdog(_Host()).tick()
        got = moved(counters(), before)
        assert got["watchdog.ticks"] == 2
        assert got["span.watchdog.tick.n"] == 2
        assert got["span.watchdog.tick.us"] > 0

    def test_lint_spans_passes_with_the_lane_workers_spans_cataloged(self):
        from orientdb_tpu.obs.spanlint import SPAN_CATALOG, lint_spans

        assert {"lane.stage", "lane.finish"} <= set(SPAN_CATALOG)
        assert lint_spans() == []


class TestLaneWorkerSpans:
    def test_a_lane_batch_is_one_stage_and_one_finish_on_the_workers_thread(self):
        from orientdb_tpu.models.database import Database
        from orientdb_tpu.server.coalesce import QueryCoalescer
        from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

        db = Database("fold_lanes")
        db.schema.create_vertex_class("P")
        db.schema.create_edge_class("K")
        vs = [db.new_vertex("P", n=i) for i in range(12)]
        for i in range(11):
            db.new_edge("K", vs[i], vs[i + 1])
        attach_fresh_snapshot(db)
        sql = "MATCH {class:P, as:a, where:(n = :n)}-K->{as:b} RETURN b.n AS n"
        threads = {}

        def on_span(sp):
            if sp.name in ("lane.stage", "lane.finish", "coalesce.dispatch"):
                threads.setdefault(sp.name, set()).add(
                    threading.current_thread().name
                )

        from orientdb_tpu.exec.tpu_engine import drain_warmups

        co = QueryCoalescer()
        tracer.add_listener(on_span)
        try:
            before = counters()
            k = 0
            deadline = time.time() + 60
            with span("test.client") as root:
                # the first of a shape records on the blocking path and
                # starts the lane program's compile; once that is in,
                # a batch is launched ahead and finished a turn later
                while time.time() < deadline:
                    rows, _engine = co.submit(db, sql, {"n": k % 11})
                    assert rows == [{"n": k % 11 + 1}]
                    k += 1
                    drain_warmups()
                    if metrics.counter("span.lane.finish.n") - before.get(
                        "span.lane.finish.n", 0
                    ) >= 3:
                        break
            got = moved(counters(), before)
        finally:
            tracer.remove_listener(on_span)
            co.stop()
            drain_warmups()
            db.detach_snapshot()
        batches = got["coalesce.batches"]
        assert batches == k
        assert got["span.lane.stage.n"] == batches
        finished = got.get("span.lane.finish.n", 0)
        assert 3 <= finished <= batches, "the lane path never opened"
        assert got["span.coalesce.dispatch.n"] == batches
        assert 0 < got["tpu.fetch_wait_us"] <= got["span.lane.finish.us"]
        # on the lane worker's own thread, never the submitter's
        for name, seen in threads.items():
            assert all(t.startswith("coalesce-fold_lanes") for t in seen), (
                name,
                seen,
            )
        # and in the submitter's trace: stage and finish continue it,
        # coalesce.dispatch stays inside it as the child of lane.finish
        mine = tracer.spans(trace_id=root.trace_id)
        names = [s.name for s in mine]
        assert names.count("lane.stage") == batches
        fin = [s for s in mine if s.name == "lane.finish"]
        assert len(fin) == finished and all(s.attrs["n"] == 1 for s in fin)
        kids = [
            s
            for s in mine
            if s.name == "coalesce.dispatch"
            and s.parent_id in {f.span_id for f in fin}
        ]
        assert len(kids) == finished


# -- the overlap pass, bounded -------------------------------------------------


def _plain_overlap_s(a0, a1, merged, _ends=None):
    """The parent's ``_overlap_s`` (PR 26 and before): every call walks
    the merged list from its start. Kept as the plain reference."""
    total = 0.0
    for b0, b1 in merged:
        if b0 >= a1:
            break
        lo, hi = max(a0, b0), min(a1, b1)
        if hi > lo:
            total += hi - lo
    return total


def _lane_records(n, seed=27):
    """A full ring as a served rooted window leaves it: one lane record
    a batch, each with one device interval (the host's wait for the
    result) and one or two transfers, some hidden behind the next
    batch's interval, some in the gap, some straddling."""
    rng = random.Random(seed)
    recs = []
    t = 5000.0
    for k in range(n):
        r = DispatchRecord(k + 1, "lane", None, None, rng.randint(1, 8))
        r._fid = f"fid{k % 3}"
        r.t0 = t
        wait = 0.004 + rng.random() * 0.02
        d0 = t + 0.001 + rng.random() * 0.002
        r.device = [(d0, d0 + wait)]
        x0 = d0 + wait * rng.random() * 1.2
        r.transfers = [(x0, x0 + 0.0005 + rng.random() * 0.004, rng.randint(100, 60000), "fetch")]
        if k % 5 == 0:
            r.transfers.append((d0, d0, 512, "prefetch"))
        if k % 7 == 0:
            p0 = d0 - 0.001
            r.transfers.append((p0, p0 + wait, 4096, "prefetch"))
        r.events = [
            ("enqueue", t - rng.random() * 0.03),
            ("device_dispatch", d0),
            ("compute_done", d0 + wait),
        ]
        r.marks = {"ring_hits": 1, "window_s": 0.0}
        r.t_done = d0 + wait + 0.006
        recs.append(r)
        # batches overlap (double buffering) and sometimes leave a gap
        t = d0 + wait * (0.6 + rng.random() * 0.7)
    return recs


class TestOverlapBounded:
    @pytest.mark.parametrize("n", [1, 2, 17, 256])
    def test_same_report_as_the_plain_walk(self, n, monkeypatch):
        recs = _lane_records(n, seed=n)
        got = FlightRecorder._overlap(recs, 8)
        monkeypatch.setattr(TL, "_overlap_s", _plain_overlap_s)
        assert FlightRecorder._overlap(recs, 8) == got

    @pytest.mark.parametrize("seed", range(5))
    def test_overlap_s_agrees_to_the_last_digit(self, seed):
        rng = random.Random(seed)
        ivs = []
        t = 0.0
        for _ in range(300):
            t += rng.random()
            ivs.append((t, t + rng.random() * 1.5))
        merged = TL._merge_intervals(ivs)
        ends = [b for _a, b in merged]
        edges = [x for iv in merged for x in iv]
        for _ in range(2000):
            a0 = rng.choice(edges) if rng.random() < 0.3 else rng.random() * t
            a1 = a0 + rng.random() * 5
            assert TL._overlap_s(a0, a1, merged, ends) == _plain_overlap_s(
                a0, a1, merged
            )
        assert TL._overlap_s(-5.0, -1.0, merged, ends) == 0.0
        assert TL._overlap_s(t + 10, t + 11, merged, ends) == 0.0
        assert TL._overlap_s(0.0, 1.0, [], []) == 0.0

    def test_a_full_ring_takes_a_fraction_of_a_second_not_seconds(
        self, monkeypatch
    ):
        from orientdb_tpu.utils.config import config

        recs = _lane_records(int(config.timeline_capacity))
        assert len(recs) == 2048
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            got = FlightRecorder._overlap(recs, 8)
            best = min(best, time.perf_counter() - t0)
        # records x intervals takes 0.85 s on these records (1.13 s on
        # the serving host, PERF.md 6); bisection takes ~25 ms
        assert best < 0.3, f"{best:.3f}s over {len(recs)} records"
        assert got["records"] == 2048
        assert 0 < got["transfer"]["hidden_bytes"] < got["transfer"]["bytes"]
        assert len(got["fingerprints"]) == 3
        monkeypatch.setattr(TL, "_overlap_s", _plain_overlap_s)
        assert FlightRecorder._overlap(recs, 8) == got


# -- names for the kernels -----------------------------------------------------


class TestKernelScopes:
    @pytest.fixture(scope="class")
    def plans(self):
        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.models.database import Database
        from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

        db = Database("fold_scopes")
        db.schema.create_vertex_class("P")
        db.schema.create_edge_class("K")
        vs = [db.new_vertex("P", n=i) for i in range(40)]
        for i in range(39):
            db.new_edge("K", vs[i], vs[i + 1])
        snap = attach_fresh_snapshot(db)
        rows = "MATCH {class:P, as:a, where:(n < :k)}-K->{as:b} RETURN a.n AS a, b.n AS b"
        # the hop reads the parameter, so a replay lowers its weight pass
        # (one that reads none is the plan's, computed at the recording)
        count = (
            "MATCH {class:P, as:a, where:(n < :k)}-K->{as:b, where:(n <= :k)} "
            "RETURN count(*) AS c"
        )
        out = {}
        for key, sql in (("rows", rows), ("count", count)):
            known = set(getattr(snap, "_plan_cache", ()))
            db.query(sql, {"k": 7}, engine="tpu", strict=True).to_dicts()
            (new,) = set(snap._plan_cache) - known
            out[key] = snap._plan_cache[new].plans[0]
        drain_warmups()
        yield out
        db.detach_snapshot()

    @staticmethod
    def lowered(plan, fn=None):
        """The lowered text's ``csr.*`` scopes, and the compiled
        module's ``op_name`` metadata (whole paths: XLA composes the
        caller's name stack with a nested jit's own when it inlines)."""
        import re

        import jax

        fn = jax.jit(fn) if fn is not None else plan.jitted
        low = fn.lower(plan._arg_subset(), plan._dyn_args({"k": 7}))
        scopes = set(re.findall(r"csr\.[a-z_0-9]+(?=/)", low.as_text(debug_info=True)))
        names = set(re.findall(r'op_name="([^"]*)"', low.compile().as_text()))
        return scopes, names

    def test_a_row_plans_hlo_names_the_plan_entry_and_the_csr_kernels(self, plans):
        scopes, names = self.lowered(plans["rows"])
        assert {"csr.gather_expand", "csr.compact_indices", "csr.take_pad"} <= scopes
        assert any(
            n.startswith("jit(_replay)/match.replay/match.core/")
            and "/csr.gather_expand/" in n
            for n in names
        ), sorted(names)[:20]
        assert not any("match.replay_group" in n for n in names)

    def test_the_group_entry_has_a_name_of_its_own(self, plans):
        plan = plans["rows"]
        scopes, names = self.lowered(plan, plan._replay_group)
        assert "csr.gather_expand" in scopes
        assert any(
            "/match.replay_group/match.core/" in n and "/csr." in n for n in names
        ), sorted(names)[:20]

    def test_a_count_plans_weight_pass_is_named(self, plans):
        scopes, names = self.lowered(plans["count"])
        assert {"csr.indptr_segment_sum", "csr.value_cumsum"} <= scopes
        assert any(
            "/match.replay/match.core/count.weight_pass/" in n
            and "/csr.indptr_segment_sum/" in n
            for n in names
        ), sorted(names)[:20]

    @pytest.mark.parametrize(
        "name",
        [
            "degree_counts", "exclusive_cumsum", "gather_expand", "mask_cumsum",
            "value_cumsum", "compact_indices", "take_pad", "take_range", "mask_count",
            "indptr_segment_sum", "rows_to_bitmap", "bitmap_hop", "rows_with_matches",
        ],
    )
    def test_every_public_kernel_traces_under_its_own_name(self, name):
        import inspect

        from orientdb_tpu.ops import csr

        fn = getattr(csr, name)
        src = inspect.getsource(inspect.unwrap(fn))
        assert f'@jax.named_scope("csr.{name}")' in src
        # the scope sits inside the jit, so an eager call pays nothing
        if "jax.jit" in src:
            assert src.index("jax.jit") < src.index("jax.named_scope")

"""The benchmark's own tests (``python -m pytest benchmark/tests -q``).

No chip: tiny graphs on the CPU backend, seconds each. They hold what
later PRs lean on: a window whose order of shapes is independent of the
seed and that counts every request issued, references that agree with
the embedded engine, a reduction from trace to numbers checked on a
small recorded trace, a harness that takes new cells, configurations,
mixes and per-layer metrics as files, prints nothing off a TPU, and
calls a broken timed path and both controls ``correct: false``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmark import canon, loadgen, peaks, run, tracered, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
#: the kinds module of both accepted configurations, loaded as a run loads it
SNB = run.load_kinds({"name": "tests", "kinds": "snb_arrays"})
make_raw, attach, Reference, Measures = SNB.make_raw, SNB.attach, SNB.Reference, SNB.Measures
TINY = {"persons": 200, "avg_knows": 6, "msgs_per_person": 12, "supernodes": 2, "supernode_degree": 40}


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def all_mixes() -> dict:
    names = sorted(
        f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "traffic")) if f.endswith(".json")
    )
    return {n: traffic.load_json("traffic", n) for n in names}


# -- rule 1: a fixed cycle of shapes ----------------------------------------------


@pytest.mark.parametrize(
    "weights", [[3, 2, 1], [1], [1, 1], [3, 2, 1, 2], [5, 1], [2, 2, 3, 7]]
)
def test_block_has_exactly_the_weights_shares(weights):
    block = traffic.realise_block(weights)
    assert len(block) == sum(weights)
    assert [block.count(i) for i in range(len(weights))] == weights
    assert block == traffic.realise_block(list(weights))  # a pure function


def test_block_spreads_a_heavy_shape_evenly():
    # 3:2:1 -> the weight-3 shape never twice in a row within the block
    block = traffic.realise_block([3, 2, 1])
    assert block == [0, 1, 0, 2, 1, 0]
    assert all(not (a == b == 0) for a, b in zip(block, block[1:]))


@pytest.mark.parametrize("bad", [[], [0, 1], [1.5], [-1]])
def test_block_refuses_weights_that_are_not_whole(bad):
    with pytest.raises(ValueError):
        traffic.realise_block(bad)


@pytest.fixture(scope="module")
def tiny():
    raw = make_raw(TINY, 11)
    return raw, Measures(Reference(raw))


@pytest.mark.parametrize("mix_name", sorted(all_mixes()))
def test_two_seeds_same_shape_order_different_parameters(mix_name):
    mix = all_mixes()[mix_name]
    plans = []
    for seed in (7, 2**31 + 12345):
        raw = make_raw(TINY, seed)
        plans.append(traffic.build_plan(mix, Measures(Reference(raw)), seed, 64))
    a, b = plans
    assert a["block"] == b["block"] and a["offsets"] == b["offsets"]
    assert [s["sql"] for s in a["shapes"]] == [s["sql"] for s in b["shapes"]]
    assert any(
        sa["pool"]["rows"] != sb["pool"]["rows"]
        for sa, sb in zip(a["shapes"], b["shapes"])
    )


def test_every_seed_gets_the_same_sizes_in_another_order():
    a, b = make_raw(TINY, 3), make_raw(TINY, 2**31 + 3)
    assert (a.E, a.M, a.P) == (b.E, b.M, b.P)
    assert sorted(a.knows_deg) == sorted(b.knows_deg) and (a.knows_deg != b.knows_deg).any()
    for x, y in ((a.knows_dst, b.knows_dst), (a.creator, b.creator)):
        assert sorted(np.bincount(x, minlength=a.P)) == sorted(np.bincount(y, minlength=a.P))
        assert (x != y).mean() > 0.9
    assert a.knows_deg.max() == TINY["supernode_degree"]
    c = make_raw(TINY, 3)
    assert all((getattr(a, f) == getattr(c, f)).all() for f in ("knows_dst", "creator", "age"))


def test_same_seed_same_plan(tiny):
    _raw, measures = tiny
    mix = all_mixes()["rooted_16s"]
    assert traffic.build_plan(mix, measures, 11, 64) == traffic.build_plan(
        mix, Measures(Reference(make_raw(TINY, 11))), 11, 64
    )


def test_sessions_start_at_different_places():
    offsets = traffic.session_offsets(16, 6)
    assert len(offsets) == 16 and set(offsets) == set(range(6))
    assert traffic.session_offsets(4, 6) == [0, 1, 3, 4]


def test_a_pinned_mix_gives_every_shape_a_session_of_its_own(tiny):
    _raw, measures = tiny
    mix = all_mixes()["scan_4s"]
    plan = traffic.build_plan(mix, measures, 3, 32)
    assert plan["stride"] == 0 and plan["sessions"] == len(plan["shapes"]) == 4
    assert plan["shape_sessions"] == [[0], [1], [2], [3]]
    walking = traffic.build_plan({**mix, "walk": "block"}, measures, 3, 32)
    assert walking["stride"] == 1 and walking["shape_sessions"] == [[0, 1, 2, 3]] * 4
    with pytest.raises(ValueError):  # three sessions cannot hold four shapes
        traffic.build_plan({**mix, "sessions": 3}, measures, 3, 32)
    with pytest.raises(ValueError):
        traffic.build_plan({**mix, "walk": "sideways"}, measures, 3, 32)


def test_a_pinned_session_sends_its_own_shape_alone():
    sessions = stub_sessions([0.01, 0.01], stride=0, shapes=2)
    try:
        out = loadgen.run_window(sessions, 0.1, [0, 0])
        burst = loadgen.run_burst(sessions, 1, 1, [4, 6])
    finally:
        for s in sessions:
            s.run_args = None
            s.go.set()
    assert len(out["records"]) > 4 and all(r[1] == r[0] for r in out["records"])
    # a burst goes to the shape's own sessions and draws the pool's next tuples
    assert [(r[0], r[1], r[2]) for r in burst["records"]] == [(1, 1, 6)]


# -- rule 2: curated parameters ------------------------------------------------------


@pytest.mark.parametrize("measure", ["degree_both"])
def test_curated_roots_fall_inside_the_stated_band(tiny, measure):
    raw, measures = tiny
    shape = {
        "name": "s",
        "params": {"personId": {"root": measure, "band": [0.4, 0.6]}, "k": {"const": 3}},
    }
    pool = traffic.draw_pool(shape, measures, 5, 500)
    values = getattr(measures, measure)()
    lo, hi = np.quantile(values, [0.4, 0.6])
    roots = [r[0] for r in pool["rows"]]
    assert roots and all(lo <= values[p] <= hi for p in roots)
    assert len(set(roots)) == len(roots)  # no pair twice while the domain lasts
    assert values[roots[0]] == max(values[p] for p in roots)  # warm-up meets the largest
    hubs = set(np.argsort(-raw.knows_deg)[: TINY["supernodes"]].tolist())
    assert not hubs & set(roots)


def test_int_parameters_are_distinct_and_in_range(tiny):
    _raw, measures = tiny
    shape = {"name": "c", "params": {"d": {"int": [10, 500]}, "a": {"const": 40}}}
    rows = traffic.draw_pool(shape, measures, 1, 300)["rows"]
    assert len({tuple(r) for r in rows}) == len(rows) > 100
    assert all(10 <= r[0] <= 500 and r[1] == 40 for r in rows)


def test_the_pools_first_tuple_carries_the_lead_values(tiny):
    _raw, measures = tiny
    shape = {
        "name": "c",
        "params": {"d": {"int": [10, 500], "lead": 10}, "a": {"int": [1, 9], "lead": 9}},
    }
    for seed in (1, 2**31 + 2):
        rows = traffic.draw_pool(shape, measures, seed, 300)["rows"]
        assert rows[0] == [10, 9] and len({tuple(r) for r in rows}) == len(rows)


# -- rule 3: issue until the deadline, count everything issued -------------------------


class StubRemote:
    def __init__(self, delay: float) -> None:
        self.delay = delay

    def query(self, sql, params):
        time.sleep(self.delay)
        rs = argparse.Namespace(engine="tpu")
        rs.to_dicts = lambda: [{"n": 1}]
        return rs

    def close(self):
        pass


def stub_sessions(delays, stride=1, shapes=1):
    n = len(delays)
    plan = {
        "sessions": n,
        "seed": 0,
        "think_ms": [0.0, 0.0],
        "block": list(range(shapes)),
        "offsets": [s % shapes for s in range(n)],
        "stride": stride,
        "shape_sessions": [
            [s for s in range(n) if stride or s % shapes == i] for i in range(shapes)
        ],
        "shapes": [
            {
                "name": f"c{i}", "sql": "x", "columns": ["n"], "ordered": False,
                "pool": {"names": ["p"], "rows": [[k] for k in range(1000)]},
            }
            for i in range(shapes)
        ],
    }
    sessions = []
    for i, d in enumerate(delays):
        s = loadgen.Session(i, plan, lambda d=d: StubRemote(d))
        s.open()
        s.start()
        sessions.append(s)
    return sessions


def test_a_request_that_stalls_past_the_deadline_is_counted():
    sessions = stub_sessions([0.01, 0.01, 0.6])
    try:
        out = loadgen.run_window(sessions, 0.2, [0])
    finally:
        for s in sessions:
            s.run_args = None
            s.go.set()
    records = out["records"]
    slow = [r for r in records if r[0] == 2]
    assert len(slow) == 1 and slow[0][4] - slow[0][3] >= 0.6
    # issued before the deadline, answered after it: still in every metric
    assert slow[0][3] - out["t_start"] < 0.2 < slow[0][4] - out["t_start"]
    e2e = run.window_metrics(records)
    assert e2e["latency_p95_ms"] < 600 <= max((r[4] - r[3]) * 1000 for r in records)
    span = max(r[4] for r in records) - min(r[3] for r in records)
    assert span >= 0.6 and e2e["span_s"] == pytest.approx(span) and e2e["qps"] == pytest.approx(len(records) / span)
    # nobody issued after the deadline, and each session's pool indices are its own
    assert all(r[3] - out["t_start"] < 0.2 for r in records)
    assert all(r[2] % 3 == r[0] for r in records)
    assert len({(r[1], r[2]) for r in records}) == len(records)


def test_percentiles_are_measured_values():
    lat = sorted([10.0] * 50 + [100.0] * 50)
    assert run.percentile(lat, 0.50) == 10.0
    assert run.percentile(lat, 0.95) == 100.0
    assert run.percentile([5.0], 0.95) == 5.0
    with pytest.raises(ValueError):
        run.percentile([], 0.5)


def test_an_answer_from_another_engine_or_an_error_counts_as_failed():
    class Oracle(StubRemote):
        def query(self, sql, params):
            rs = super().query(sql, params)
            rs.engine = "oracle"
            return rs

    class Broken(StubRemote):
        def query(self, sql, params):
            raise ConnectionError("gone")

    shape = {"sql": "x", "columns": ["n"], "ordered": False}
    s = loadgen.Session(0, {"sessions": 1}, lambda: Oracle(0))
    s.open()
    assert s.request(shape, {})[0] == 1
    s = loadgen.Session(0, {"sessions": 1}, lambda: Broken(0))
    s.open()
    assert s.request(shape, {}) == (2, None) and "ConnectionError" in s.error


# -- the references against the embedded engine ---------------------------------------


def every_shape():
    seen = {}
    for mix in all_mixes().values():
        for s in mix["shapes"]:
            seen[s["reference"]] = s
    return seen


@pytest.fixture(scope="module")
def embedded():
    raw = make_raw(TINY, 2**31 + 5)
    db, _snap = attach(raw)
    yield raw, Reference(raw), db
    db.detach_snapshot()


@pytest.mark.parametrize("kind", sorted(every_shape()))
def test_reference_agrees_with_the_embedded_engine(embedded, kind):
    raw, ref, db = embedded
    shape = every_shape()[kind]
    rng = np.random.default_rng(1)
    for trial in range(8):
        draw = {
            "personId": 0 if trial == 0 else int(rng.integers(0, raw.P)),
            "maxAge": int(rng.integers(20, 70)),
            "minAge": int(rng.integers(20, 70)),
            "d": int(rng.integers(10_000, 20_000)),
            "minLen": int(rng.integers(0, 2000)),
        }
        params = {k: draw[k] for k in shape["params"]}
        rows = db.query(shape["sql"], params=params, engine="tpu", strict=True).to_dicts()
        got = canon.digest(canon.rows_of(rows, shape["columns"]), shape.get("ordered", False))
        want = canon.digest(ref.answer(kind, params), shape.get("ordered", False))
        assert got == want, (kind, params)


def test_every_reference_kind_has_a_byte_count(tiny):
    raw, _measures = tiny
    for kind in every_shape():
        assert SNB.least_bytes(kind, raw) > 0
    with pytest.raises(KeyError):
        SNB.least_bytes("no_such_kind", raw)
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")


def test_the_benchmark_imports_nothing_it_may_not():
    banned = ("import bench", "from bench ", "perfdiff", "workloads.driver", "workloads/driver")
    for dirpath, _dirs, files in os.walk(BENCH_DIR):
        if os.path.basename(dirpath) == "tests":
            continue
        for name in files:
            if name.endswith(".py"):
                text = open(os.path.join(dirpath, name)).read()
                assert not any(b in text for b in banned), name
    # a kinds module meets the program in ``attach`` alone: nothing of it
    # at the module's top level, nothing in the reference's side
    import ast
    import inspect

    for name in sorted(os.listdir(os.path.join(BENCH_DIR, "kinds"))):
        if not name.endswith(".py"):
            continue
        mod = run.load_kinds({"name": "tests", "kinds": name[:-3]})
        top = ast.parse(inspect.getsource(mod)).body
        imports = [n for n in top if isinstance(n, (ast.Import, ast.ImportFrom))]
        assert not any("orientdb_tpu" in ast.unparse(n) for n in imports), name
        for part in (mod.Reference, mod.Measures, mod.least_bytes, mod.make_raw, mod.stale):
            assert "import" not in inspect.getsource(part), (name, part)


# -- the trace reduction ------------------------------------------------------------------


def test_trace_reduction_on_a_synthetic_trace():
    ms = 1e6
    trace = {
        "planes": [
            {
                "name": "/device:TPU:0",
                "lines": [
                    {"name": "XLA Modules", "events": [["jit_replay", 0, 50 * ms]]},
                    {
                        "name": "XLA Ops",
                        "events": [
                            ["while.1", 10 * ms, 20 * ms],
                            ["fusion.2", 12 * ms, 5 * ms],
                            ["fusion.2", 20 * ms, 5 * ms],
                            ["fusion.3", 40 * ms, 10 * ms],
                        ],
                    },
                ],
            },
            {
                "name": "/host:CPU",
                "lines": [
                    {
                        "name": "python",
                        "events": [
                            ["np.asarray", 30 * ms, 9 * ms],
                            ["idle", 0 * ms, 9 * ms],
                            ["tail", 50 * ms, 50 * ms],
                        ],
                    }
                ],
            },
        ]
    }
    out = tracered.reduce(trace)
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.030)  # [10,30) and [40,50)
    ops = dict(out["device_ops"])
    assert ops["fusion.2"] == pytest.approx(0.010)
    assert ops["while.1"] == pytest.approx(0.010)  # self time: 20 - 2 x 5
    gaps = dict(out["idle_gaps"])
    assert gaps["host:np.asarray"] == pytest.approx(0.010)
    assert gaps["host:idle"] == pytest.approx(0.010)
    assert gaps["host:tail"] == pytest.approx(0.050)
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"])


def test_the_traced_window_lies_between_the_profilers_own_calls():
    """While ``stop_trace`` writes, the tracer holds the interpreter and the
    device starves: that stall is the profiler's, not the program's."""
    ms = 1e6
    trace = {
        "planes": [
            {
                "name": "/device:TPU:0",
                "lines": [
                    {
                        "name": "XLA Ops",
                        "events": [
                            ["fusion.1", 2 * ms, 2 * ms],  # before start_trace returned
                            ["fusion.2", 10 * ms, 20 * ms],
                            ["fusion.3", 55 * ms, 10 * ms],  # runs into stop_trace
                            ["fusion.4", 90 * ms, 5 * ms],  # under stop_trace
                        ],
                    }
                ],
            },
            {
                "name": "/host:CPU",
                "lines": [
                    {
                        "name": "python",
                        "events": [
                            ["$profiler.py:101 start_trace", 0, 5 * ms],
                            ["$coalesce.py:342 _finish", 5 * ms, 95 * ms],
                            ["$profiler.py:213 stop_trace", 60 * ms, 40 * ms],
                        ],
                    }
                ],
            },
        ]
    }
    out = tracered.reduce(trace)
    assert out["window_s"] == pytest.approx(0.055)  # [5, 60)
    assert out["busy_s"] == pytest.approx(0.025)  # [10,30) and [55,60)
    ops = dict(out["device_ops"])
    assert "fusion.1" not in ops and "fusion.4" not in ops
    assert ops["fusion.3"] == pytest.approx(0.005)
    assert sum(dict(out["idle_gaps"]).values()) == pytest.approx(0.030)


def test_trace_without_a_device_plane_reads_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": [{"name": "t", "events": [["x", 0, 5]]}]}]}
    out = tracered.reduce(trace)
    assert out["busy_s"] == 0 and out["chips"] == 0
    obs = {"trace": out, "requests_in_trace": 3, "least_bytes_in_trace": 10.0, "device_kind": "cpu"}
    for name in ("device_busy_ms_per_q", "hbm_roofline_share", "device_idle_share"):
        assert run.load_reader(name).read(obs) is None  # never a 0 share


RECORDED = os.path.join(HERE, "data", "v5e_trace_excerpt.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace in this tree")
def test_trace_reduction_on_the_recorded_v5e_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    out = tracered.reduce(rec["trace"])
    assert out["chips"] == 1
    for key, want in rec["expect"].items():
        assert out[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"][0][0] == rec["expect_top_op"]


# -- the layer metrics' readers ------------------------------------------------------------


def test_every_per_layer_metric_has_a_reader_file():
    bench = bench_json()
    for m in bench["per_layer"]:
        mod = run.load_reader(m["name"])
        assert callable(mod.read)
        doc = mod.__doc__
        assert m["source"] in doc and f"moves: {m['moves']}" in " ".join(doc.split())
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])


def test_readers_on_counter_deltas():
    obs = {
        "counters": {
            "coalesce.items": 40, "coalesce.batches": 10, "engine:tpu": 39, "engine:oracle": 1,
            "views.hit": 2, "plan_cache.miss": 1, "plan_cache.overflow_rerecord": 1,
            "plan_cache.group_compile": 3,
        },
        "requests": 40, "requests_in_trace": 0, "trace": None,
        "least_bytes_in_trace": 0.0, "device_kind": "cpu",
        "hbm_state_bytes": 2_000_000_000, "hbm_peak_bytes": 0,
    }
    read = lambda n: run.load_reader(n).read(obs)  # noqa: E731
    assert read("lane_batch_mean") == 4.0
    assert read("tpu_engine_share") == 97.5
    assert read("views_hit_share") == 5.0
    assert read("rerecords_per_kq") == 50.0
    assert read("compiles_in_window") == 3
    assert read("hbm_state_gb") == 2.0
    assert read("hbm_peak_gb") is None
    assert read("device_idle_share") is None
    obs["counters"] = {"tpu.lane_items": 9, "tpu.lane_dispatch": 3}
    assert read("lane_batch_mean") == 3.0 and read("tpu_engine_share") is None


# -- BENCHMARK.json and its files ------------------------------------------------------------


def test_benchmark_json_names_files_that_exist():
    bench = bench_json()
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and set(c["reduced"]) == set(body["reduced"])
        assert body["scale"]["persons"] == body["published"]["persons"]
        assert c["file"].startswith(bench["paths"][0] + "/")
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] == 1
        mix = traffic.load_json("traffic", w["traffic"])
        assert mix["name"] == w["traffic"] and len(w["why"]) <= 200
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    assert all(0.01 <= m["bound"] <= 0.25 for m in bench["end_to_end"])


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}"


def test_benchmark_json_keeps_to_the_contracts_limits():
    import re

    bench = bench_json()
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert len(bench["command"]) <= 32 and all(1 <= len(w) <= 200 for w in bench["command"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.fullmatch(NAME, c["name"]) and len(c["reduced"]) <= 16
        assert all(re.fullmatch(NAME, k) for k in c["reduced"])
        assert all(1 <= len(c[k]) <= 200 and "\n" not in c[k] for k in ("source", "why"))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(re.fullmatch(NAME, w[k]) for k in ("name", "config", "traffic"))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert 1 <= len(m["layer"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert re.fullmatch(NAME, m["name"]) and m["better"] in ("lower", "higher")
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
    for w in bench["workloads"]:
        e2e = {m["name"] for m in run.metrics_for(bench, "end_to_end", w["name"])}
        layer = run.metrics_for(bench, "per_layer", w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        # a per-layer metric is read only where the metric it moves is reported
        assert all(m["moves"] in e2e for m in layer), w["name"]
    for dirpath, _dirs, files in os.walk(BENCH_DIR):
        if "__pycache__" in dirpath:
            continue
        for name in files:
            assert re.fullmatch(r"[A-Za-z0-9_.\-]+", name), name


# -- one driven run: new files only, broken paths, both controls ---------------------------------


NEW_READER = '''"""requests_seen: source program_counter; moves: qps."""


def read(obs):
    return obs["requests"] or None
'''


@pytest.fixture(scope="module")
def plugged(tmp_path_factory):
    """A directory that ADDS one configuration, one mix and one per-layer
    metric as files beside copies of what is there, and a BENCHMARK.json
    (as a dict) that adds their entries: no existing file is edited."""
    root = str(tmp_path_factory.mktemp("bench"))
    for sub in ("configs", "traffic", "layer_metrics", "kinds"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(root, sub))
    with open(os.path.join(root, "configs", "tiny.json"), "w") as f:
        json.dump({"name": "tiny", "kinds": "snb_arrays", "scale": TINY}, f)
    with open(os.path.join(root, "configs", "heavy.json"), "w") as f:
        json.dump(
            {"name": "heavy", "kinds": "snb_arrays",
             "scale": {"persons": 120, "avg_knows": 8, "msgs_per_person": 400}}, f
        )
    mix = traffic.load_json("traffic", "rooted_16s")
    mix.update(name="pair_3s", sessions=3, pool_size=200)
    scans = {s["name"]: s for s in all_mixes()["scan_4s"]["shapes"]}
    mix["shapes"] = [
        dict(mix["shapes"][0], weight=1),
        dict(scans["creator_1hop"], weight=2),
        dict(scans["config5"], weight=2),
    ]
    with open(os.path.join(root, "traffic", "pair_3s.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "layer_metrics", "requests_seen.py"), "w") as f:
        f.write(NEW_READER)
    bench = bench_json()
    bench["configs"].append({"name": "tiny", "file": "x", "source": "x", "reduced": [], "why": "x"})
    bench["workloads"] += [
        {"name": "tiny_pair", "config": "tiny", "traffic": "pair_3s", "chips": 1, "why": "x"},
        {"name": "heavy_scan", "config": "heavy", "traffic": "scan_4s", "chips": 1, "why": "x"},
    ]
    bench["per_layer"].append(
        {"name": "requests_seen", "unit": "count", "better": "higher", "source": "program_counter",
         "layer": "wire", "moves": "qps", "workloads": ["tiny_pair"]}
    )
    return root, bench


def drive(plugged, workload="tiny_pair", trace=0, seed=2**31 + 77, control_name="none"):
    root, bench = plugged
    args = argparse.Namespace(
        workload=workload, seed=seed, seconds=1.0, trace=trace
    )
    return run.run_cell(args, bench, require_chip=False, root=root, control=control_name)


def test_a_driven_run_is_correct_and_counts_everything(plugged):
    res = drive(plugged)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == res["compared"]["answers_compared"]["value"] > 20
    assert list(res)[-1] == "compared"  # the numbers compared come last
    # both latencies list their cells, and this new one is not among them
    assert set(res["metrics"]) == {"qps", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["window"]["param_repeats"] >= 0  # 200 persons: the bands run out
    by_shape = res["window"]["by_shape"]
    assert set(by_shape) == {"friends", "creator_1hop", "config5"}
    assert by_shape["creator_1hop"]["n"] > by_shape["friends"]["n"]
    assert all(v["min_ms"] <= v["p50_ms"] <= v["p95_ms"] <= v["max_ms"] for v in by_shape.values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])


def test_a_traced_run_reports_the_new_metric_and_no_empty_share(plugged):
    res = drive(plugged, trace=1)
    assert res["correct"] is True
    m = res["metrics"]
    assert m["requests_seen"]["value"] == res["attempted"]
    assert m["tpu_engine_share"]["value"] == 100.0 and m["views_hit_share"]["value"] == 0.0
    assert m["lane_batch_mean"]["value"] >= 1.0
    # the CPU has no device plane: the trace's metrics are left out, not 0
    assert not {"device_busy_ms_per_q", "hbm_roofline_share", "device_idle_share"} & set(m)
    assert {"busy_s", "window_s"} <= set(res["device"]) and "breakdown" in res


def test_an_answer_altered_where_it_is_produced_is_not_correct(plugged, monkeypatch):
    import jax

    from orientdb_tpu.ops import csr

    sound = csr.indptr_segment_sum

    def off_by_one(*a, **kw):
        return sound(*a, **kw) + 1

    jax.clear_caches()
    monkeypatch.setattr(csr, "indptr_segment_sum", off_by_one)
    try:
        res = drive(plugged)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert res["correct"] is False
    assert res["compared"]["wrong_answers"]["value"] > 0


def test_the_stale_snapshot_control_is_not_correct(plugged):
    res = drive(plugged, control_name="stale_snapshot")
    assert res["correct"] is False and res["compared"]["wrong_answers"]["value"] > 0
    assert res["failed"] == 0  # the device answered; it answered another snapshot


def test_the_lower_precision_control_is_not_correct_once_sums_pass_256(plugged):
    res = drive(plugged, workload="heavy_scan", control_name="lower_precision")
    assert res["correct"] is False and res["compared"]["wrong_answers"]["value"] > 0
    sound = drive(plugged, workload="heavy_scan")
    assert sound["correct"] is True


def test_stale_snapshot_changes_one_in_a_thousand():
    raw = make_raw({**TINY, "persons": 5000}, 4)
    stale = SNB.stale(raw, 4)
    assert 0 < (stale.knows_dst != raw.knows_dst).sum() <= raw.E // 1000 + 1
    assert 0 < (stale.creator != raw.creator).sum() <= raw.M // 1000 + 1
    assert stale.age is raw.age


# -- no chip, no number ------------------------------------------------------------------------


def test_off_a_tpu_the_run_prints_no_metric():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "sf100_rooted_16s",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 2 and out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_an_unknown_workload_is_refused():
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "nope", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_the_load_generator_never_imports_jax():
    code = "import sys, benchmark.loadgen, orientdb_tpu.client.remote; print('jax' in sys.modules)"
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=60
    )
    assert out.stdout.strip() == "False", out.stderr


def test_no_thread_is_left_behind():
    assert not [t for t in threading.enumerate() if t.name.startswith("session-") and t.is_alive() and not t.daemon]

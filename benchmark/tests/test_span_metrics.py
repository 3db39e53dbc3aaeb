"""The six per-layer metrics that read the program's own clocks.

Each reader on a hand-made ``obs`` (the stated arithmetic; ``None`` where
its counters did not move, as on a parent commit that lacks the fold),
then one driven CPU run that shows a served window moving the counters
they read, together.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil

import pytest

from benchmark import run, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)

NAMES = (
    "wire_ms_per_q",
    "marshal_ms_per_q",
    "lane_wait_ms_per_q",
    "host_turn_ms_per_batch",
    "tick_stall_share",
    "tick_evaluate_ms",
)

#: a window of 100 requests in 20 batches over 10 s, with two ticks in it
MOVED = {
    "critpath.requests": 100,
    "critpath.parse_us": 3_000,
    "critpath.flush_us": 7_000,
    "critpath.marshal_us": 45_000,
    "critpath.queue_us": 2_500_000,
    "coalesce.batches": 20,
    "span.lane.stage.us": 30_000,
    "span.lane.finish.us": 400_000,
    "tpu.fetch_wait_us": 330_000,
    "span.watchdog.tick.us": 900_000,
    "span.watchdog.tick.n": 2,
    "span.scrub.sweep.us": 300_000,
    "span.scrub.sweep.n": 2,
}


def obs_of(counters: dict, span_s: float = 10.0) -> dict:
    return {"counters": dict(counters), "requests": 100, "window": {"span_s": span_s}}


def read(name: str, obs: dict):
    return run.load_reader(name).read(obs)


@pytest.mark.parametrize(
    "name, want",
    [
        ("wire_ms_per_q", (3_000 + 7_000) / 100 / 1000),
        ("marshal_ms_per_q", 45_000 / 100 / 1000),
        ("lane_wait_ms_per_q", 2_500_000 / 100 / 1000),
        ("host_turn_ms_per_batch", (30_000 + 400_000 - 330_000) / 20 / 1000),
        ("tick_stall_share", 100 * (900_000 + 300_000) / 1e6 / 10.0),
        ("tick_evaluate_ms", 900_000 / 2 / 1000),
    ],
)
def test_a_reader_does_the_stated_arithmetic(name, want):
    assert read(name, obs_of(MOVED)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    # the parent commit: the accepted counters move, none of the fold's
    parent = {"coalesce.batches": 20, "coalesce.items": 100, "engine:tpu": 100}
    assert read(name, obs_of(parent)) is None
    assert read(name, obs_of({})) is None


@pytest.mark.parametrize(
    "name, zeroed",
    [
        ("wire_ms_per_q", ["critpath.requests"]),
        ("marshal_ms_per_q", ["critpath.requests"]),
        ("lane_wait_ms_per_q", ["critpath.requests"]),
        ("host_turn_ms_per_batch", ["coalesce.batches"]),
        ("tick_evaluate_ms", ["span.watchdog.tick.n"]),
    ],
)
def test_a_zero_denominator_is_none_not_a_division(name, zeroed):
    counters = {k: v for k, v in MOVED.items() if k not in zeroed}
    assert read(name, obs_of(counters)) is None
    assert read(name, obs_of({**counters, **{k: 0 for k in zeroed}})) is None


def test_tick_stall_share_needs_a_window_and_a_tick():
    assert read("tick_stall_share", obs_of(MOVED, span_s=0.0)) is None
    no_tick = {k: v for k, v in MOVED.items() if not k.startswith("span.")}
    assert read("tick_stall_share", obs_of(no_tick)) is None
    # a sweep alone (rules off) is a stall all the same
    sweep = {"span.scrub.sweep.us": 500_000}
    assert read("tick_stall_share", obs_of(sweep)) == pytest.approx(5.0)


def test_a_segment_that_stamped_nothing_reads_zero_where_requests_moved():
    # one client to a lane: no wait; the metric is reported as 0, not left out
    counters = {k: v for k, v in MOVED.items() if k != "critpath.queue_us"}
    assert read("lane_wait_ms_per_q", obs_of(counters)) == 0.0


def test_host_turn_is_never_negative():
    # the wait is rounded to whole microseconds on its own, batch by batch
    counters = dict(MOVED, **{"tpu.fetch_wait_us": 430_007})
    assert read("host_turn_ms_per_batch", obs_of(counters)) == 0.0


def test_the_new_entries_are_in_benchmark_json_without_a_workloads_list():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(NAMES)
    old_layers = {m["layer"] for m in bench["per_layer"][:-6]}
    for name in NAMES:
        m = entries[name]
        assert "workloads" not in m and m["source"] == "program_span"
        assert m["better"] == "lower"
    assert {entries[n]["layer"] for n in NAMES[2:]} <= old_layers
    # every cell reports the metric they move
    for w in bench["workloads"]:
        e2e = {m["name"] for m in run.metrics_for(bench, "end_to_end", w["name"])}
        assert {entries[n]["moves"] for n in NAMES} <= e2e


def test_the_span_entries_are_found_by_name_and_list_no_workloads():
    """What the test above holds, with the six entries found by name
    wherever they stand in the list (``tests/test_benchmark_suite.py``
    expects the test above to fail since entries came after them)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(NAMES) <= set(entries)
    other_layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NAMES}
    for name in NAMES:
        m = entries[name]
        assert "workloads" not in m and m["source"] == "program_span"
        assert m["better"] == "lower"
    assert {entries[n]["layer"] for n in NAMES[2:]} <= other_layers
    # every cell reports the metric they move
    for w in bench["workloads"]:
        e2e = {m["name"] for m in run.metrics_for(bench, "end_to_end", w["name"])}
        assert {entries[n]["moves"] for n in NAMES} <= e2e


# -- one driven run: a served window moves the counters together ------------------------------

TINY = {"persons": 200, "avg_knows": 6, "msgs_per_person": 12, "supernodes": 2, "supernode_degree": 40}


@pytest.fixture(scope="module")
def plugged(tmp_path_factory):
    """Copies of what is there, plus a tiny configuration and the rooted
    mix with 4 sessions: no existing file is edited."""
    root = str(tmp_path_factory.mktemp("bench"))
    for sub in ("configs", "traffic", "layer_metrics", "kinds"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(root, sub))
    with open(os.path.join(root, "configs", "tiny.json"), "w") as f:
        json.dump({"name": "tiny", "kinds": "snb_arrays", "scale": TINY}, f)
    mix = traffic.load_json("traffic", "rooted_16s")
    mix.update(name="rooted_4s", sessions=4, pool_size=200)
    with open(os.path.join(root, "traffic", "rooted_4s.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "file": "x", "source": "x", "reduced": [], "why": "x"})
    bench["workloads"].append(
        {"name": "tiny_rooted", "config": "tiny", "traffic": "rooted_4s", "chips": 1, "why": "x"}
    )
    return root, bench


def test_a_served_window_moves_the_folded_counters_together(plugged, monkeypatch):
    root, bench = plugged
    seen = {}
    sound = run.delta

    def keep(after, before):
        seen["window"] = sound(after, before)  # the last call is the window's
        return seen["window"]

    monkeypatch.setattr(run, "delta", keep)
    args = argparse.Namespace(workload="tiny_rooted", seed=2**31 + 91, seconds=1.5, trace=1)
    res = run.run_cell(args, bench, require_chip=False, root=root)
    assert res["correct"] is True and res["failed"] == 0
    c = seen["window"]
    n = res["attempted"]
    assert n > 20
    # every request sampled: the plane committed one record a request
    # (the window's edges may hold one a session more or less)
    assert abs(c["critpath.requests"] - n) <= 4
    assert c["critpath.queue_us"] > 0 and c["critpath.marshal_us"] > 0
    assert c["critpath.parse_us"] > 0 and c["critpath.flush_us"] > 0
    # the lane worker's turns: one stage a batch, and one finish for each
    # that was launched ahead (the others ran on the blocking path)
    batches = c["coalesce.batches"]
    assert 0 < batches <= c["coalesce.items"] <= n + 4
    assert abs(c["span.lane.stage.n"] - batches) <= 1
    assert 0 < c["span.lane.finish.n"] <= batches + 1
    assert 0 < c["tpu.fetch_wait_us"] <= c["span.lane.finish.us"]
    # and the readers make finite numbers of them, in the result line
    m = res["metrics"]
    for name in ("wire_ms_per_q", "marshal_ms_per_q", "lane_wait_ms_per_q", "host_turn_ms_per_batch"):
        assert 0.0 <= m[name]["value"] < 1e4, name
    assert m["lane_wait_ms_per_q"]["value"] > 0.0
    # the segments are parts of the requests' walls, which the clients timed
    per_q = sum(v for k, v in c.items() if k.startswith("critpath.") and k.endswith("_us"))
    assert per_q / 1e6 <= res["window"]["span_s"] * 4 * 1.05

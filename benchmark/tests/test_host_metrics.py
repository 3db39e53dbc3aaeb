"""The five per-layer metrics that read the host's own clocks: each
serving thread's CPU time by role (``thread.<role>.cpu_us``) and the
garbage collector's pauses (``gc.*``), both kept by ``obs/trace`` and
merged into the program's counters at a snapshot.

Each reader on a hand-made ``obs``: the stated arithmetic on a counter
delta, ``None`` where nothing moved (a parent commit that lacks the
clocks), ``None`` on a zero denominator.
"""

from __future__ import annotations

import json
import os

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

NAMES = (
    "session_cpu_ms_per_q",
    "lane_cpu_ms_per_batch",
    "tick_cpu_share",
    "interp_cpu_ms_per_q",
    "gc_pause_share",
)

#: a window of 1 000 requests in 90 batches over 12 s
MOVED = {
    "critpath.requests": 1_000,
    "coalesce.batches": 90,
    "thread.session.cpu_us": 480_000,
    "thread.lane.cpu_us": 270_000,
    "thread.watchdog.cpu_us": 600_000,
    "gc.collections.gen0": 400,
    "gc.collections.gen1": 36,
    "gc.collections.gen2": 1,
    "gc.pause_us": 1_250_000,
    "gc.pause_us.gen0": 20_000,
    "gc.pause_us.gen1": 30_000,
    "gc.pause_us.gen2": 1_200_000,
}


def obs_of(counters: dict, span_s: float = 12.0) -> dict:
    return {"counters": dict(counters), "requests": 1_000, "window": {"span_s": span_s}}


def read(name: str, obs: dict):
    return run.load_reader(name).read(obs)


@pytest.mark.parametrize(
    "name, want",
    [
        ("session_cpu_ms_per_q", 480_000 / 1_000 / 1000),
        ("lane_cpu_ms_per_batch", 270_000 / 90 / 1000),
        ("tick_cpu_share", 100 * 600_000 / 1e6 / 12.0),
        ("interp_cpu_ms_per_q", (480_000 + 270_000 + 600_000) / 1_000 / 1000),
        ("gc_pause_share", 100 * 1_250_000 / 1e6 / 12.0),
    ],
)
def test_a_host_reader_does_the_stated_arithmetic(name, want):
    assert read(name, obs_of(MOVED)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", NAMES)
def test_a_host_reader_with_nothing_to_read_returns_none(name):
    # the parent commit: the accepted counters move, none of the clocks'
    parent = {
        "critpath.requests": 1_000,
        "critpath.parse_us": 3_000,
        "coalesce.batches": 90,
        "span.watchdog.tick.us": 900_000,
        "engine:tpu": 1_000,
    }
    assert read(name, obs_of(parent)) is None
    assert read(name, obs_of({})) is None


@pytest.mark.parametrize(
    "name, zeroed",
    [
        ("session_cpu_ms_per_q", ["critpath.requests"]),
        ("lane_cpu_ms_per_batch", ["coalesce.batches"]),
        ("interp_cpu_ms_per_q", ["critpath.requests"]),
        ("tick_cpu_share", ["thread.watchdog.cpu_us"]),
        ("gc_pause_share", [k for k in MOVED if k.startswith("gc.collections.")]),
    ],
)
def test_a_host_reader_with_a_zero_denominator_returns_none(name, zeroed):
    counters = {k: v for k, v in MOVED.items() if k not in zeroed}
    assert read(name, obs_of(counters)) is None
    assert read(name, obs_of({**counters, **{k: 0 for k in zeroed}})) is None


@pytest.mark.parametrize("name", ["tick_cpu_share", "gc_pause_share"])
def test_a_share_needs_a_window(name):
    assert read(name, obs_of(MOVED, span_s=0.0)) is None


def test_interp_cpu_sums_whichever_roles_moved():
    lanes_only = {"critpath.requests": 100, "thread.lane.cpu_us": 50_000}
    assert read("interp_cpu_ms_per_q", obs_of(lanes_only)) == pytest.approx(0.5)


def test_collections_that_round_to_no_pause_read_zero_not_none():
    # a window of young collections each under a microsecond: counted,
    # and the share is 0, not left out of the line
    young = {"gc.collections.gen0": 30}
    assert read("gc_pause_share", obs_of(young)) == 0.0


def test_the_host_entries_are_appended_last_without_a_workloads_list():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NAMES):] == list(NAMES)
    entries = {m["name"]: m for m in bench["per_layer"]}
    old_layers = {m["layer"] for m in bench["per_layer"][: -len(NAMES)]}
    for name in NAMES:
        m = entries[name]
        assert "workloads" not in m
        assert (m["source"], m["better"], m["moves"]) == ("program_counter", "lower", "qps")
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.py"))
    assert {entries[n]["layer"] for n in NAMES[:3]} <= old_layers
    assert {entries[n]["layer"] for n in NAMES[3:]} == {"host interpreter (all serving threads)"}
    # every cell reports the metric they move
    for w in bench["workloads"]:
        e2e = {m["name"] for m in run.metrics_for(bench, "end_to_end", w["name"])}
        assert "qps" in e2e

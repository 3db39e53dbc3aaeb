"""``benchmark/kinds/snb_paths.py`` (LDBC SNB Interactive complex read 13):
its reference against the embedded engine at a small size, the measure
over pairs, the byte count, and the planted fault coming out not correct
through ``run.run_cell`` on the real mix's statement. No chip."""

from __future__ import annotations

import argparse
import json
import os
import shutil

import numpy as np
import pytest

from benchmark import run, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)

SCALE = {"persons": 400, "avg_knows": 1, "msgs_per_person": 0, "supernodes": 2, "supernode_degree": 40}
SEED = 2**31 + 1301


@pytest.fixture(scope="module")
def kinds():
    cfg = traffic.load_json("configs", "snb-sf100-paths-1chip")
    return run.load_kinds(cfg)


@pytest.fixture(scope="module")
def raw(kinds):
    return kinds.make_raw(SCALE, SEED)


def plain_lens(raw, source: int) -> np.ndarray:
    """Distances from ``source`` by the simplest search there is."""
    nbrs = [[] for _ in range(raw.P)]
    src = np.repeat(np.arange(raw.P), raw.knows_deg)
    for a, b in zip(src.tolist(), raw.knows_dst.tolist()):
        nbrs[a].append(b)
        nbrs[b].append(a)
    dist = np.full(raw.P, -1)
    dist[source] = 0
    frontier = [source]
    while frontier:
        nxt = []
        for u in frontier:
            for w in nbrs[u]:
                if dist[w] < 0:
                    dist[w] = dist[u] + 1
                    nxt.append(w)
        frontier = nxt
    return dist


@pytest.mark.parametrize("push_limit", [0, 40, 10**9])
def test_the_reference_is_a_breadth_first_search(kinds, raw, push_limit):
    """Whatever share of the work the source's push and the targets' own
    searches take, the lengths are the plain search's; the graph has
    persons nothing reaches (avg_knows 1), so -1 is among them."""
    ref = kinds.Reference(raw)
    ref.push_limit = push_limit
    everyone = np.arange(raw.P)
    seen = set()
    for source in (0, 7, 123, 399):
        want = plain_lens(raw, source)
        got = ref.lens(source, everyone)
        assert (got == want).all(), np.flatnonzero(got != want)[:5]
        seen |= set(want.tolist())
    assert {-1, 0, 1, 2, 3, 4} <= seen
    assert ref.answer("shortest_path_len", {"person1Id": 7, "person2Id": 7}) == [(0,)]
    with pytest.raises(KeyError):
        ref.answer("friends_rows", {"personId": 1})


def test_the_measure_deals_pairs_from_the_data_alone(kinds, raw):
    ref = kinds.Reference(raw)
    values, pairs = kinds.Measures(ref).pair_distance(sources=12, targets=9)
    assert values.shape == (108,) and pairs.shape == (108, 2)
    assert (pairs[:, 0] != pairs[:, 1]).all()
    assert len({tuple(p) for p in pairs.tolist()}) == 108  # every pair once
    degree = ref.degree_both()
    lo, hi = np.quantile(degree, [0.4, 0.6])
    assert ((degree[pairs] >= lo) & (degree[pairs] <= hi)).all()
    for (a, b), d in zip(pairs.tolist(), values.tolist()):
        assert plain_lens(raw, a)[b] == d == ref.known[(a, b)]
    # a second reference of the same graph deals the same pairs
    again, pairs2 = kinds.Measures(kinds.Reference(raw)).pair_distance(sources=12, targets=9)
    assert (pairs2 == pairs).all() and (again == values).all()
    # through the generator: two names from one key, the longest pair first
    shape = {"name": "path_len", "params": {"person1Id,person2Id": {"root": "pair_distance", "band": [0, 1]}}}
    pool = traffic.draw_pool(shape, kinds.Measures(ref), SEED, 50)
    assert pool["names"] == ["person1Id", "person2Id"]
    assert len({tuple(r) for r in pool["rows"]}) == len(pool["rows"]) == 50
    first = ref.known[tuple(pool["rows"][0])]
    assert first == max(ref.known.values())


def test_least_bytes_is_a_function_of_the_sizes(kinds, raw):
    D = 2.0 * raw.E / raw.P
    want = 4 * (2 * (2 + D) + D * (2 + D)) + 4
    assert kinds.least_bytes("shortest_path_len", raw) == pytest.approx(want)
    full = kinds.make_raw({**SCALE, "persons": 800}, SEED)
    assert kinds.least_bytes("shortest_path_len", full) == pytest.approx(want, rel=0.2)
    with pytest.raises(KeyError):
        kinds.least_bytes("friends_rows", raw)


def test_the_planted_fault_moves_lengths(kinds, raw):
    ref, late = kinds.Reference(raw), kinds.Reference(kinds.stale(raw, SEED))
    assert late.raw.P == raw.P and late.raw.E < raw.E
    moved = sum(
        int((ref.lens(s, np.arange(raw.P)) != late.lens(s, np.arange(raw.P))).sum())
        for s in (3, 50, 311)
    )
    assert moved > 0.1 * 3 * raw.P


# -- through run.run_cell, from the real files and a small configuration ---------


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A benchmark root of the real kinds modules, the real mix with a
    smaller pool and three sessions, and a small configuration."""
    root = tmp_path_factory.mktemp("paths_root")
    for sub in ("kinds", "layer_metrics"):
        shutil.copytree(
            os.path.join(BENCH_DIR, sub), root / sub, ignore=shutil.ignore_patterns("__pycache__")
        )
    cfg = traffic.load_json("configs", "snb-sf100-paths-1chip")
    cfg["scale"] = SCALE
    mix = traffic.load_json("traffic", "ic13_16s")
    mix.update(sessions=3, pool_size=400)
    for sub, name, obj in (("configs", cfg["name"], cfg), ("traffic", mix["name"], mix)):
        os.makedirs(root / sub)
        with open(root / sub / (name + ".json"), "w") as f:
            json.dump(obj, f)
    return str(root)


def drive(root: str, control: str = "none", trace: int = 0) -> dict:
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    args = argparse.Namespace(workload="sf100_ic13_16s", seed=SEED, seconds=1.5, trace=trace)
    return run.run_cell(args, bench, require_chip=False, root=root, control=control)


def test_the_cell_is_correct_at_a_small_size_and_reports_its_metrics(small_root):
    res = drive(small_root, trace=1)
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert res["attempted"] == res["compared"]["answers_compared"]["value"] > 20
    assert res["window"]["param_repeats"] == 0
    got = res["metrics"]
    for name in ("bfs_levels_per_q", "bfs_edges_per_q", "bfs_overflow_share"):
        assert name in got, sorted(got)
    assert got["tpu_engine_share"]["value"] == 100.0
    assert got["rerecords_per_kq"]["value"] == 0.0 and got["compiles_in_window"]["value"] == 0.0
    assert got["bfs_levels_per_q"]["value"] >= 2.0
    assert set(drive(small_root)["metrics"]) == {"qps", "setup_s"}


def test_the_planted_fault_is_not_correct(small_root):
    res = drive(small_root, control="stale_snapshot")
    assert res["correct"] is False and res["compared"]["wrong_answers"]["value"] > 0
    assert res["failed"] == 0  # the device answered; it answered an older graph


def test_a_program_that_cannot_search_the_graph_is_refused_at_the_hand_over(
    kinds, raw, monkeypatch
):
    """The parent of PR 31 evaluates the function on the host over records
    this graph does not have: 324 of 331 answers read -1, exit code 0. The
    hand-over asks for one adjacent pair and ends such a run with an exit
    code instead."""
    from orientdb_tpu.exec import tpu_engine

    kinds.attach(raw)  # this program is handed the graph
    monkeypatch.setattr(tpu_engine.TpuMatchSolver, "_compile_path_lens", lambda self: {})
    with pytest.raises(SystemExit, match="share an edge"):
        kinds.attach(raw, "snb_of_a_parent")


# -- every cell's mix against its own configuration's kinds module ------------------
# ``test_benchmark_harness.py`` feeds every mix to ``snb_arrays``; beside a
# second kinds module three of its tests fail on ``ic13_16s`` (PERF.md 7).
# What they held is held here, cell by cell, with the module the cell names.

TINY = {"persons": 200, "avg_knows": 6, "msgs_per_person": 12, "supernodes": 2, "supernode_degree": 40}


def cells() -> dict:
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        return {w["name"]: w for w in json.load(f)["workloads"]}


def kinds_and_mix(cell: dict):
    cfg = traffic.load_json("configs", cell["config"])
    return run.load_kinds(cfg), traffic.load_json("traffic", cell["traffic"])


@pytest.mark.parametrize("cell", sorted(cells()))
def test_every_reference_kind_of_a_cell_has_a_byte_count_in_its_kinds_module(cell):
    kinds, mix = kinds_and_mix(cells()[cell])
    raw = kinds.make_raw(TINY, 11)
    for shape in mix["shapes"]:
        assert kinds.least_bytes(shape["reference"], raw) > 0
    with pytest.raises(KeyError):
        kinds.least_bytes("no_such_kind", raw)


@pytest.mark.parametrize("cell", sorted(cells()))
def test_two_seeds_give_a_cell_the_same_shape_order_and_other_parameters(cell):
    kinds, mix = kinds_and_mix(cells()[cell])
    plans = []
    for seed in (7, 2**31 + 12345):
        raw = kinds.make_raw(TINY, seed)
        plans.append(traffic.build_plan(mix, kinds.Measures(kinds.Reference(raw)), seed, 64))
    a, b = plans
    assert a["block"] == b["block"] and a["offsets"] == b["offsets"]
    assert [s["sql"] for s in a["shapes"]] == [s["sql"] for s in b["shapes"]]
    assert any(
        sa["pool"]["rows"] != sb["pool"]["rows"] for sa, sb in zip(a["shapes"], b["shapes"])
    )

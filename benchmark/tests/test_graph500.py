"""``benchmark/kinds/graph500.py`` (LDBC Graphalytics BFS on a Graph500
Kronecker graph): every seed the same graph under other labels, its
reference against the simplest search and against the embedded engine
through ``run.run_cell`` on the real mix's statement, the byte count,
the planted fault not correct, and the hand-over's question ending a
program that does not carry ``$depth`` out of a TRAVERSE. No chip."""

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
SEED = 2**31 + 2201


@pytest.fixture(scope="module")
def cfg():
    return traffic.load_json("configs", "graph500-22-1chip")


@pytest.fixture(scope="module")
def kinds(cfg):
    return run.load_kinds(cfg)


@pytest.fixture(scope="module")
def small(kinds):
    return kinds.SMALL


@pytest.fixture(scope="module")
def raw(kinds, small):
    return kinds.make_raw(small, SEED)


def plain_depths(raw, source: int) -> np.ndarray:
    """Depths from ``source`` by the simplest search there is."""
    nbrs = [[] for _ in range(raw.V)]
    for a, b in zip(raw.src.tolist(), raw.dst.tolist()):
        nbrs[a].append(b)
        nbrs[b].append(a)
    depth = np.full(raw.V, -1)
    depth[source] = 0
    frontier = [source]
    while frontier:
        nxt = []
        for u in frontier:
            for w in nbrs[u]:
                if depth[w] < 0:
                    depth[w] = depth[u] + 1
                    nxt.append(w)
        frontier = nxt
    return depth


def test_two_seeds_are_one_graph_under_other_labels(kinds, small, raw):
    other = kinds.make_raw(small, 7)
    assert (other.V, other.E) == (raw.V, raw.E) and raw.V <= 256
    assert (np.sort(other.degree) == np.sort(raw.degree)).all()
    assert (other.degree != raw.degree).any()  # another order
    again = kinds.make_raw(small, SEED)
    assert (again.src == raw.src).all() and (again.dst == raw.dst).all()


def test_the_graph_is_what_the_dataset_is(raw):
    """Self-loops dropped, each pair once in one direction, no vertex
    without an edge, the edge list in out-order."""
    assert (raw.src != raw.dst).all()
    lo, hi = np.minimum(raw.src, raw.dst), np.maximum(raw.src, raw.dst)
    assert np.unique(lo.astype(np.int64) * raw.V + hi).size == raw.E
    assert raw.degree.min() >= 1 and int(raw.degree.sum()) == 2 * raw.E
    key = raw.src.astype(np.int64) * raw.V + raw.dst
    assert (np.diff(key) > 0).all()
    # both directions are stored: the coin is a coin
    assert 0.3 < float((raw.src < raw.dst).mean()) < 0.7
    # a Kronecker graph's degrees are skewed: a hub far above the mean
    assert raw.degree.max() > 5 * raw.degree.mean()


@pytest.mark.parametrize("list_share", [0.0, 1 / 8, 10.0])
def test_the_reference_is_a_breadth_first_search(kinds, raw, list_share):
    """Whichever way a level is read (the frontier's lists, or the edge
    list once a direction), the depths are the plain search's."""
    ref = kinds.Reference(raw)
    ref.list_share = list_share
    for source in (0, 5, int(np.argmax(raw.degree)), int(np.argmin(raw.degree))):
        want = plain_depths(raw, source)
        assert (ref.depths(source) == want).all()
        rows = ref.answer("bfs_level_counts", {"source": source})
        assert rows == list(enumerate(np.bincount(want[want >= 0]).tolist()))
        assert rows[0] == (0, 1)
    assert ref.answer("bfs_level_counts", {"source": raw.V + 5}) == []
    with pytest.raises(KeyError):
        ref.answer("friends_rows", {"personId": 1})


def test_the_measure_is_the_undirected_degree(kinds, raw):
    ref = kinds.Reference(raw)
    degree = kinds.Measures(ref).degree()
    want = np.bincount(np.concatenate([raw.src, raw.dst]), minlength=raw.V)
    assert (degree == want).all() and degree.min() >= 1
    mix = traffic.load_json("traffic", "bfs_1s")
    pool = traffic.draw_pool(mix["shapes"][0], kinds.Measures(ref), SEED, 64)
    assert pool["names"] == ["source"] and len(pool["rows"]) == 64
    assert len({r[0] for r in pool["rows"]}) == 64  # no key twice
    assert degree[pool["rows"][0][0]] == degree.max()  # the hub records the plan


def test_least_bytes_is_a_function_of_the_sizes(kinds, raw):
    want = 8 * raw.E + 8 * (raw.V + 1) + raw.V / 4
    assert kinds.least_bytes("bfs_level_counts", raw) == pytest.approx(want)
    with pytest.raises(KeyError):
        kinds.least_bytes("friends_rows", raw)


def test_the_planted_fault_moves_counts(kinds, raw):
    late = kinds.stale(raw, SEED)
    assert late.V == raw.V and late.E < raw.E
    assert int((late.degree == 0).sum()) >= raw.V // 10
    ref, old = kinds.Reference(raw), kinds.Reference(late)
    moved = sum(
        ref.answer("bfs_level_counts", {"source": s})
        != old.answer("bfs_level_counts", {"source": s})
        for s in range(0, raw.V, 7)
    )
    assert moved == len(range(0, raw.V, 7))


# -- the configuration and the cell (what every cell is held to, planned at the
# module's SMALL, is in test_each_cell.py) ------------------------------------------


def test_the_configuration_is_the_dataset_whole(cfg):
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert entry["file"] == "benchmark/configs/graph500-22-1chip.json"
    assert entry["reduced"] == [] and cfg["reduced"] == {}  # nothing was cut
    assert cfg["architecture"] is None and cfg["kinds"] == "graph500"
    want = {"scale": 22, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}
    assert {k: cfg["scale"][k] for k in want} == want
    # the module's small scale is the dataset's own generator at scale 8
    assert run.load_kinds(cfg).SMALL == {**cfg["scale"], "scale": 8}
    assert cfg["published"]["dataset"] == "graph500-22"
    (cell,) = [w for w in bench["workloads"] if w["config"] == cfg["name"]]
    assert (cell["name"], cell["traffic"], cell["chips"]) == ("g500_s22_bfs_1s", "bfs_1s", 1)
    mix = traffic.load_json("traffic", "bfs_1s")
    (shape,) = mix["shapes"]
    assert (mix["sessions"], mix["think_ms"], mix["pool_size"]) == (1, 0, 64)
    assert shape["sql"] == run.load_kinds(cfg).STATEMENT and shape["ordered"] is False


# -- through run.run_cell, from the real files and a small configuration ---------


@pytest.fixture(scope="module")
def small_root(tmp_path_factory, cfg, small):
    """A benchmark root of the real kinds modules and readers, the real
    mix, and the real configuration at scale 8."""
    root = tmp_path_factory.mktemp("g500_root")
    for sub in ("kinds", "layer_metrics"):
        shutil.copytree(
            os.path.join(BENCH_DIR, sub), root / sub, ignore=shutil.ignore_patterns("__pycache__")
        )
    mix = traffic.load_json("traffic", "bfs_1s")
    for sub, name, obj in (
        ("configs", cfg["name"], {**cfg, "scale": small}),
        ("traffic", mix["name"], mix),
    ):
        os.makedirs(root / sub)
        with open(root / sub / (name + ".json"), "w") as f:
            json.dump(obj, f)
    return str(root)


def drive(root: str, control: str = "none", trace: int = 0) -> dict:
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    args = argparse.Namespace(workload="g500_s22_bfs_1s", seed=SEED, seconds=1.5, trace=trace)
    return run.run_cell(args, bench, require_chip=False, root=root, control=control)


def test_the_cell_is_correct_at_a_small_size_and_reports_its_metrics(small_root):
    res = drive(small_root, trace=1)
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert res["attempted"] == res["compared"]["answers_compared"]["value"] > 5
    got = res["metrics"]
    for name in ("trav_levels_per_q", "trav_dense_levels_per_q", "trav_edges_per_q"):
        assert name in got, sorted(got)
    assert "bfs_levels_per_q" not in got
    assert got["tpu_engine_share"]["value"] == 100.0
    assert got["rerecords_per_kq"]["value"] == 0.0 and got["compiles_in_window"]["value"] == 0.0
    assert got["lane_batch_mean"]["value"] == 1.0
    assert 2.0 <= got["trav_levels_per_q"]["value"] <= 8.0
    assert got["trav_dense_levels_per_q"]["value"] >= 1.0
    assert set(drive(small_root)["metrics"]) == {"qps", "setup_s"}


def test_the_planted_fault_is_not_correct(small_root):
    res = drive(small_root, control="stale_snapshot")
    assert res["correct"] is False and res["compared"]["wrong_answers"]["value"] > 0
    assert res["failed"] == 0  # the device answered; it answered an older graph


def test_the_readers_read_nothing_where_no_search_ran():
    for name in ("trav_levels_per_q", "trav_dense_levels_per_q", "trav_edges_per_q"):
        reader = run.load_reader(name)
        assert reader.read({"counters": {"bfs.queries": 9}}) is None
        counted = {
            "traverse.queries": 4,
            "traverse.levels": 26,
            "traverse.dense_levels": 8,
            "traverse.edges_scanned": 1000,
        }
        assert reader.read({"counters": counted}) in (6.5, 2.0, 250.0)


def test_a_program_that_answers_depth_none_is_ended_at_the_question(kinds, raw, monkeypatch):
    """The parent of PR 38 serves the statement from its oracle, which
    does not carry ``$depth`` out of the subquery: one row, ``depth``
    ``None``. The hand-over asks once, of 256 labels, and ends such a run
    with an exit code and both answers, before the large snapshot."""
    from orientdb_tpu.models.database import Database

    kinds.ask(raw.cfg)  # this program answers

    class Answer:
        def to_dicts(self):
            return [{"depth": None, "n": 37}]

    monkeypatch.setattr(Database, "query", lambda self, sql, params=None, **kw: Answer())
    with pytest.raises(SystemExit, match=r"\(0, 1\).*'depth': None"):
        kinds.attach(raw, "g500_of_a_parent")

"""A parameter keeps its type (``benchmark/traffic.draw_pool``).

(a) Every pool of an integer-only mix is what the integer harness drew:
``parent_draw_pool`` below is ``draw_pool`` copied verbatim from the
harness as it stood when it stacked every column into one int64 array,
and every mix under ``benchmark/traffic/`` is drawn by both, on its own
cell's kinds module at that module's ``SMALL``, on three seeds: the same
names, the same rows in the same order, the same Python types. (b) A
tuple measure's candidates may hold strings beside integers, in three
forms, and each column keeps its type. (c) A string parameter travels
the served path: a toy deployment under ``benchmark/tests/data/``
(``named_path``) reads a node's successor by ``(nodeId, name)`` through
``run.run_cell``, correct with every answer from the device, and not
correct with its planted fault. No chip.
"""

from __future__ import annotations

import argparse
import json
import os
import zlib
from typing import Dict

import numpy as np
import pytest

from benchmark import run, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
DATA = os.path.join(HERE, "data")


# -- the integer harness, verbatim -----------------------------------------------------


def band_members(values: np.ndarray, band) -> np.ndarray:
    """Indices whose value lies within the band's two quantiles of
    ``values`` (both ends included)."""
    q_lo, q_hi = float(band[0]), float(band[1])
    if not 0.0 <= q_lo <= q_hi <= 1.0:
        raise ValueError(f"band must be 0 <= lo <= hi <= 1: {band}")
    lo, hi = np.quantile(values, [q_lo, q_hi])
    return np.flatnonzero((values >= lo) & (values <= hi))


def parent_draw_pool(shape: dict, measures, seed: int, want: int) -> Dict:
    """Up to ``want`` distinct parameter tuples for one shape, as
    ``{"names": [...], "rows": [[...], ...]}``. ``measures`` is the kinds
    module's ``Measures`` over the reference. Seeded by ``seed`` and the
    shape's name, so that adding a shape to a mix moves no other shape's
    parameters.

    The first tuple is the one warm-up records the shape's plan with, and
    a plan keeps the buffer sizes of the answer it was recorded on
    (``exec/tpu_engine.SizeSchedule``): it carries each parameter's
    ``lead`` value and the band's largest root, the largest answer of the
    pool, so that every seed records the same sizes and no later request
    outgrows them."""
    rng = np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, zlib.crc32(shape["name"].encode())]
    )
    names = [n for key in shape["params"] for n in key.split(",")]
    cols = []
    for name, spec in shape["params"].items():
        if "const" in spec:
            cols.append(np.full(want, int(spec["const"]), np.int64))
        elif "int" in spec:
            lo, hi = spec["int"]
            col = rng.integers(int(lo), int(hi) + 1, want)
            if "lead" in spec:
                col[0] = int(spec["lead"])
            cols.append(col)
        elif "root" in spec:
            measure = getattr(measures, spec["root"], None)
            if measure is None or spec["root"].startswith("_"):
                raise KeyError(f"no root measure {spec['root']!r}")
            values, candidates = measure(), None
            if isinstance(values, tuple):
                values, candidates = values
            members = band_members(values, spec["band"])
            if members.size == 0:
                raise ValueError(f"{shape['name']}: empty band {spec}")
            picks = rng.permutation(members)
            # the band's largest member leads the pool
            top = int(np.argmax(values[picks]))
            picks[[0, top]] = picks[[top, 0]]
            reps = -(-want // picks.size)
            col = np.tile(picks, reps)[:want]
            if candidates is None:
                cols.append(col)
            else:
                cols.extend(np.asarray(candidates)[col].T)
        else:
            raise ValueError(f"{shape['name']}.{name}: unknown draw {spec}")
    rows = np.stack(cols, axis=1) if cols else np.zeros((want, 0), np.int64)
    # keep the first occurrence of every tuple, in drawn order
    _, first = np.unique(rows, axis=0, return_index=True)
    rows = rows[np.sort(first)]
    return {"names": names, "rows": rows.tolist()}


# -- (a) every integer pool as it was ----------------------------------------------------


def mixes_and_kinds() -> list:
    """Each mix under ``benchmark/traffic/`` with the kinds module of every
    configuration a cell runs it on."""
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    pairs = set()
    for w in bench["workloads"]:
        pairs.add((w["traffic"], traffic.load_json("configs", w["config"])["kinds"]))
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "traffic")) if f.endswith(".json")}
    assert on_disk == {m for m, _k in pairs}  # no mix without a cell
    return sorted(pairs)


def typed(rows: list) -> list:
    return [[(type(v), v) for v in r] for r in rows]


SEEDS = (7, 2**31 + 12345, 3000000019)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix_name, kinds_name", mixes_and_kinds())
def test_every_mix_draws_what_the_integer_harness_drew(mix_name, kinds_name, seed):
    kinds = run.load_kinds({"name": "pin", "kinds": kinds_name})
    mix = traffic.load_json("traffic", mix_name)
    measures = kinds.Measures(kinds.Reference(kinds.make_raw(kinds.SMALL, seed)))
    want = int(mix.get("pool_size", 20000))
    for shape in mix["shapes"]:
        new = traffic.draw_pool(shape, measures, seed, want)
        old = parent_draw_pool(shape, measures, seed, want)
        assert new["names"] == old["names"], shape["name"]
        assert typed(new["rows"]) == typed(old["rows"]), shape["name"]
        assert len(new["rows"]) > 1


def test_a_tuple_measure_is_among_the_mixes_held_to_the_integer_harness():
    tuple_measures = {
        spec["root"]
        for mix_name, _k in mixes_and_kinds()
        for shape in traffic.load_json("traffic", mix_name)["shapes"]
        for key, spec in shape["params"].items()
        if "," in key
    }
    assert "pair_distance" in tuple_measures


# -- (b) strings beside integers ---------------------------------------------------------------


class Named:
    """A tuple measure over (node, name): nodes 0..29, each with two names."""

    NAMES = ("Ann", "Bo", "Cy")

    def __init__(self, form: str) -> None:
        self.form = form

    def named(self):
        ids = np.repeat(np.arange(30), 2)
        names = [self.NAMES[(i + j) % 3] for i, j in zip(ids.tolist(), [0, 1] * 30)]
        if self.form == "tuples":
            candidates = list(zip(ids.tolist(), names))
        elif self.form == "columns":
            candidates = [ids.astype(np.int32), np.array(names)]
        else:  # numpy scalars in tuples
            candidates = [(np.int64(i), np.str_(n)) for i, n in zip(ids, names)]
        return ids.astype(np.float64), candidates


SHAPE = {
    "name": "s",
    "params": {"nodeId,name": {"root": "named", "band": [0.2, 0.8]}, "k": {"const": 3}},
}


@pytest.mark.parametrize("form", ["tuples", "columns", "numpy_scalars"])
def test_a_tuple_measure_keeps_an_int_and_a_str_side_by_side(form):
    pool = traffic.draw_pool(SHAPE, Named(form), 2**31 + 5, 80)
    assert pool["names"] == ["nodeId", "name", "k"]
    rows = pool["rows"]
    assert all([type(v) for v in r] == [int, str, int] for r in rows)
    lo, hi = np.quantile(np.repeat(np.arange(30), 2), [0.2, 0.8])
    assert all(lo <= i <= hi and n in Named.NAMES and k == 3 for i, n, k in rows)
    assert len({tuple(r) for r in rows}) == len(rows) == 2 * sum(lo <= i <= hi for i in range(30))
    assert rows[0][0] == max(r[0] for r in rows)  # the largest leads
    assert json.loads(json.dumps(pool)) == pool  # the plan crosses a pipe as JSON
    # the same draws whatever the form
    assert pool == traffic.draw_pool(SHAPE, Named("tuples"), 2**31 + 5, 80)


def test_a_string_constant_stays_a_string():
    shape = {"name": "c", "params": {"d": {"int": [1, 9], "lead": 9}, "tag": {"const": "x"}}}
    rows = traffic.draw_pool(shape, None, 4, 30)["rows"]
    assert rows[0] == [9, "x"] and all(type(t) is str for _d, t in rows)
    ints = {"name": "c", "params": {"d": shape["params"]["d"]}}
    assert rows == [[d, "x"] for (d,) in parent_draw_pool(ints, None, 4, 30)["rows"]]


class Bad:
    def __init__(self, candidates) -> None:
        self.candidates = candidates

    def named(self):
        return np.arange(4, dtype=np.float64), self.candidates


@pytest.mark.parametrize(
    "candidates, error",
    [
        # numpy would read every id as a string
        (np.array([[0, "a"], [1, "b"], [2, "c"], [3, "d"]]), TypeError),
        # a column of ints and strings mixed
        ([(0, "a"), (1, "b"), ("2", "c"), (3, "d")], TypeError),
        ([(0, "a"), (1, "b"), (2, 2.5), (3, "d")], TypeError),
        ([(0, "a"), (1, "b"), (2,), (3, "d")], ValueError),
        ([np.arange(4), np.array(list("abc"))], ValueError),
        ([np.arange(4)], ValueError),
    ],
    ids=["str_array", "mixed_column", "float", "short_tuple", "short_column", "one_column"],
)
def test_candidates_that_would_lose_a_type_or_a_value_are_refused(candidates, error):
    with pytest.raises(error):
        traffic.draw_pool(SHAPE, Bad(candidates), 1, 8)


# -- (c) a string parameter through the served path ---------------------------------------------

NAMED_BENCH = {
    "configs": [{"name": "named-path-64", "file": "x", "source": "x", "reduced": [], "why": "x"}],
    "workloads": [
        {"name": "named_hop", "config": "named-path-64", "traffic": "named_hop_3s", "chips": 1, "why": "x"}
    ],
    "end_to_end": [
        {"name": "qps", "unit": "queries/s", "better": "higher", "bound": 0.08, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock"},
    ],
    "per_layer": [
        {"name": "least_bytes_per_q", "unit": "B/query", "better": "lower", "source": "program_counter",
         "layer": "kernels", "moves": "qps"},
    ],
}
NAMED_SEED = 2**31 + 4501


@pytest.fixture(scope="module")
def named():
    kinds = run.load_kinds(traffic.load_json("configs", "named-path-64", DATA), DATA)
    raw = kinds.make_raw({"nodes": 64}, NAMED_SEED)
    return kinds, raw


def test_the_named_pool_holds_ints_and_strs_and_the_name_decides_the_answer(named):
    kinds, raw = named
    ref = kinds.Reference(raw)
    mix = traffic.load_json("traffic", "named_hop_3s", DATA)
    plan = traffic.build_plan(mix, kinds.Measures(ref), NAMED_SEED, mix["pool_size"])
    pool = plan["shapes"][0]["pool"]
    assert pool["names"] == ["nodeId", "name"]
    assert all(type(i) is int and type(n) is str for i, n in pool["rows"])
    answers = [ref.answer("next_named_rows", dict(zip(pool["names"], r))) for r in pool["rows"]]
    assert 0.3 < sum(1 for a in answers if a) / len(answers) < 0.7
    with pytest.raises(TypeError):  # an id that came as a string is no id
        ref.answer("next_named_rows", {"nodeId": str(pool["rows"][0][0]), "name": pool["rows"][0][1]})


def drive_named(control: str = "none", trace: int = 0) -> dict:
    args = argparse.Namespace(workload="named_hop", seed=NAMED_SEED, seconds=1.0, trace=trace)
    return run.run_cell(args, NAMED_BENCH, require_chip=False, root=DATA, control=control)


def test_a_string_parameter_is_served_and_compared_correct():
    res = drive_named()
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0  # every answer came from the engine "tpu"
    assert res["attempted"] == res["compared"]["answers_compared"]["value"] > 10
    assert res["compared"]["wrong_answers"]["value"] == 0
    assert res["compared"]["unanswered"]["value"] == 0


def test_the_named_deployments_planted_fault_is_not_correct():
    res = drive_named(control="stale_snapshot")
    assert res["correct"] is False and res["compared"]["wrong_answers"]["value"] > 0
    assert res["failed"] == 0  # the device answered; it answered the old names

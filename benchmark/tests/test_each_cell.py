"""Every cell of ``BENCHMARK.json`` against its own configuration's kinds
module, at the small scale that module states (``SMALL``,
``benchmark/kinds/README.md``). No chip.

A cell that a new deployment adds is held here with no edit: its mix is
planned on two seeds, every shape of it has an answer and a byte count,
and each reference kind agrees with the embedded engine on parameters
drawn from the cell's own pool. (``test_benchmark_harness.py`` feeds
every mix to ``snb_arrays`` and reads ``scale.persons``, and
``test_snb_paths.py`` hands every cell an SNB-shaped scale; where that
does not hold, ``tests/test_benchmark_suite.STALE_ASSUMPTIONS`` lists
them as expected failures. What they hold is held here for every cell.)
"""

from __future__ import annotations

import json
import os

import pytest

from benchmark import canon, peaks, run, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
SEEDS = (7, 2**31 + 12345)


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells() -> dict:
    return {w["name"]: w for w in bench_json()["workloads"]}


def kinds_of(cell: dict):
    return run.load_kinds(traffic.load_json("configs", cell["config"]))


def kinds_names() -> list:
    return sorted({traffic.load_json("configs", w["config"])["kinds"] for w in cells().values()})


def reference_kinds() -> dict:
    """``"<kinds module>:<reference kind>"`` -> a cell whose mix has a
    shape of that kind, and the shape."""
    out = {}
    for name, cell in sorted(cells().items()):
        module = traffic.load_json("configs", cell["config"])["kinds"]
        for shape in traffic.load_json("traffic", cell["traffic"])["shapes"]:
            out.setdefault(f"{module}:{shape['reference']}", (name, shape))
    return out


@pytest.mark.parametrize("kinds_name", kinds_names())
def test_every_kinds_module_with_a_cell_states_a_small_scale(kinds_name):
    kinds = run.load_kinds({"name": "small", "kinds": kinds_name})
    assert isinstance(getattr(kinds, "SMALL", None), dict), f"{kinds_name} states no SMALL"
    a, b = (kinds.make_raw(kinds.SMALL, seed) for seed in SEEDS)
    assert {k: getattr(v, "shape", v) for k, v in vars(a).items()} == {
        k: getattr(v, "shape", v) for k, v in vars(b).items()
    }  # every seed the same sizes


@pytest.mark.parametrize("cell", sorted(cells()))
def test_a_cells_mix_keeps_its_shapes_and_draws_other_parameters_on_another_seed(cell):
    kinds = kinds_of(cells()[cell])
    mix = traffic.load_json("traffic", cells()[cell]["traffic"])
    plans = [
        traffic.build_plan(
            mix, kinds.Measures(kinds.Reference(kinds.make_raw(kinds.SMALL, seed))), seed, 64
        )
        for seed in SEEDS
    ]
    a, b = plans
    assert a["block"] == b["block"] and a["offsets"] == b["offsets"]
    assert [s["sql"] for s in a["shapes"]] == [s["sql"] for s in b["shapes"]]
    assert any(
        sa["pool"]["rows"] != sb["pool"]["rows"] for sa, sb in zip(a["shapes"], b["shapes"])
    )


@pytest.mark.parametrize("cell", sorted(cells()))
def test_every_shape_of_a_cell_has_an_answer_and_a_byte_count_in_its_kinds_module(cell):
    kinds = kinds_of(cells()[cell])
    raw = kinds.make_raw(kinds.SMALL, 11)
    ref = kinds.Reference(raw)
    plan = traffic.build_plan(
        traffic.load_json("traffic", cells()[cell]["traffic"]), kinds.Measures(ref), 11, 8
    )
    for shape in plan["shapes"]:
        params = dict(zip(shape["pool"]["names"], shape["pool"]["rows"][0]))
        assert isinstance(ref.answer(shape["reference"], params), list), shape["name"]
        assert kinds.least_bytes(shape["reference"], raw) > 0, shape["name"]
    with pytest.raises(KeyError):
        kinds.least_bytes("no_such_kind", raw)
    with pytest.raises(KeyError):
        ref.answer("no_such_kind", {})


@pytest.fixture(scope="module")
def embedded():
    """One small deployment a kinds module, attached as a run attaches it."""
    held = {}

    def get(kinds_name: str):
        if kinds_name not in held:
            kinds = run.load_kinds({"name": "small", "kinds": kinds_name})
            raw = kinds.make_raw(kinds.SMALL, 2**31 + 5)
            db, _snap = kinds.attach(raw)
            held[kinds_name] = (kinds, raw, kinds.Reference(raw), db)
        return held[kinds_name]

    yield get
    for _kinds, _raw, _ref, db in held.values():
        db.detach_snapshot()


@pytest.mark.parametrize("key", sorted(reference_kinds()))
def test_a_reference_kind_agrees_with_the_embedded_engine_on_its_cells_pool(embedded, key):
    kinds_name, kind = key.split(":")
    cell, shape = reference_kinds()[key]
    kinds, _raw, ref, db = embedded(kinds_name)
    mix = dict(traffic.load_json("traffic", cells()[cell]["traffic"]), shapes=[shape])
    pool = traffic.build_plan(mix, kinds.Measures(ref), 2**31 + 5, 8)["shapes"][0]["pool"]
    ordered = shape.get("ordered", False)
    for row in pool["rows"]:
        params = dict(zip(pool["names"], row))
        rs = db.query(shape["sql"], params=params, engine="tpu", strict=True)
        got = canon.digest(canon.rows_of(rs.to_dicts(), shape["columns"]), ordered)
        assert got == canon.digest(ref.answer(kind, params), ordered), (kind, params)


def test_benchmark_json_names_files_that_exist_for_every_kind_of_configuration():
    bench = bench_json()
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and set(c["reduced"]) == set(body["reduced"])
        assert isinstance(body["scale"], dict) and run.load_kinds(body)
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        mix = traffic.load_json("traffic", w["traffic"])
        assert mix["name"] == w["traffic"] and len(w["why"]) <= 200
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    assert all(0.01 <= m["bound"] <= 0.25 for m in bench["end_to_end"])


def test_the_table_of_peaks_has_no_row_for_a_cpu():
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")

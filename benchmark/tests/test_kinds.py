"""Kinds modules: a deployment comes as files (``benchmark/kinds/README.md``).

(a) the pin: the arrays, the reference's answers and the byte counts of
``snb_arrays`` are bit for bit what ``benchmark/datagen.py``,
``benchmark/reference.py`` and ``peaks.least_bytes`` gave at the parent of
PR 30 (4ffc9f2), where the constants below were computed; (b) a toy
deployment of another kind runs through ``run.run_cell`` from files under
``benchmark/tests/data/`` alone, correct when sound and not correct with
its planted fault; (c) a configuration without ``kinds`` and a module
that lacks a name fail at load and say what is missing; (d) the seeded
pause before each send.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import zlib

import numpy as np
import pytest

from benchmark import loadgen, run, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
DATA = os.path.join(HERE, "data")

# -- (a) the pin ---------------------------------------------------------------------

SCALES = {
    "tiny": {"persons": 200, "avg_knows": 6, "msgs_per_person": 12, "supernodes": 2, "supernode_degree": 40},
    "tiny_knows": {"persons": 300, "avg_knows": 9, "msgs_per_person": 0, "supernodes": 3, "supernode_degree": 50},
}
PARAMS = {
    "config5_count": [{"minAge": 40, "d": 12000, "maxAge": 30}, {"minAge": 25, "d": 15500, "maxAge": 61}],
    "creator_1hop_count": [{"minLen": 0, "maxAge": 80}, {"minLen": 1200, "maxAge": 33}],
    "knows_1hop_count": [{"minAge": 30, "maxAge": 50}, {"minAge": 18, "maxAge": 79}],
    "knows_2hop_count": [{"minAge": 30, "maxAge": 50}, {"minAge": 61, "maxAge": 24}],
    "friends_rows": [{"personId": 0}, {"personId": 137}],
}
#: "<scale>:<seed>" -> sizes [P, M, E], per array [dtype, crc32 of its bytes], per kind the crc32 of
#: json.dumps(answer) on PARAMS' two sets, the crc32 of degree_both, per kind least_bytes; from the parent tree
PINNED = {'tiny:11': {'sizes': [200, 2400, 1260],
             'arrays': {'knows_deg': ['int64', 2991729614],
                        'knows_dst': ['int32', 997208869],
                        'knows_cdate': ['int32', 1289837632],
                        'creator': ['int32', 936010721],
                        'age': ['int32', 1428122659],
                        'length': ['int32', 2756478952]},
             'answers': {'config5_count': [1643318676, 2660996328],
                         'creator_1hop_count': [2669684277, 3710700910],
                         'knows_1hop_count': [987263094, 296740225],
                         'knows_2hop_count': [2431213961, 3190129237],
                         'friends_rows': [824780587, 1450662724]},
             'degree_both': 3735841419,
             'least_bytes': {'config5_count': 12484.0,
                             'creator_1hop_count': 20004.0,
                             'knows_1hop_count': 6644.0,
                             'knows_2hop_count': 12484.0,
                             'friends_rows': 217.60000000000002}},
 'tiny:2147483653': {'sizes': [200, 2400, 1260],
                     'arrays': {'knows_deg': ['int64', 3746129631],
                                'knows_dst': ['int32', 3063417142],
                                'knows_cdate': ['int32', 1828027182],
                                'creator': ['int32', 4159053572],
                                'age': ['int32', 3064509365],
                                'length': ['int32', 2348287121]},
                     'answers': {'config5_count': [2880891516, 2445860223],
                                 'creator_1hop_count': [2669684277, 1132405177],
                                 'knows_1hop_count': [2263444385, 854472712],
                                 'knows_2hop_count': [2431213961, 1177564988],
                                 'friends_rows': [2787515945, 3504479823]},
                     'degree_both': 2892917556,
                     'least_bytes': {'config5_count': 12484.0,
                                     'creator_1hop_count': 20004.0,
                                     'knows_1hop_count': 6644.0,
                                     'knows_2hop_count': 12484.0,
                                     'friends_rows': 217.60000000000002}},
 'tiny:3000000019': {'sizes': [200, 2400, 1260],
                     'arrays': {'knows_deg': ['int64', 805344772],
                                'knows_dst': ['int32', 3458574142],
                                'knows_cdate': ['int32', 2675822027],
                                'creator': ['int32', 1123080107],
                                'age': ['int32', 1705639554],
                                'length': ['int32', 1635047408]},
                     'answers': {'config5_count': [1478383766, 1504228968],
                                 'creator_1hop_count': [2669684277, 2033603677],
                                 'knows_1hop_count': [3560219253, 2507175253],
                                 'knows_2hop_count': [3784586602, 4270608473],
                                 'friends_rows': [2422667050, 3433264133]},
                     'degree_both': 2907898568,
                     'least_bytes': {'config5_count': 12484.0,
                                     'creator_1hop_count': 20004.0,
                                     'knows_1hop_count': 6644.0,
                                     'knows_2hop_count': 12484.0,
                                     'friends_rows': 217.60000000000002}},
 'tiny_knows:11': {'sizes': [300, 0, 2880],
                   'arrays': {'knows_deg': ['int64', 4137971381],
                              'knows_dst': ['int32', 3260879122],
                              'knows_cdate': ['int32', 2132370655],
                              'creator': ['int32', 0],
                              'age': ['int32', 2049201467],
                              'length': ['int32', 0]},
                   'answers': {'config5_count': [2873715364, 2873715364],
                               'creator_1hop_count': [2873715364, 2873715364],
                               'knows_1hop_count': [3248999004, 469743329],
                               'knows_2hop_count': [2348874574, 2259529295],
                               'friends_rows': [1545602997, 589957887]},
                   'degree_both': 2069088291,
                   'least_bytes': {'config5_count': 26644.0,
                                   'creator_1hop_count': 1204.0,
                                   'knows_1hop_count': 13924.0,
                                   'knows_2hop_count': 26644.0,
                                   'friends_rows': 323.2}},
 'tiny_knows:2147483653': {'sizes': [300, 0, 2880],
                           'arrays': {'knows_deg': ['int64', 2751275540],
                                      'knows_dst': ['int32', 3695187876],
                                      'knows_cdate': ['int32', 2812176671],
                                      'creator': ['int32', 0],
                                      'age': ['int32', 1941474125],
                                      'length': ['int32', 0]},
                           'answers': {'config5_count': [2873715364, 2873715364],
                                       'creator_1hop_count': [2873715364, 2873715364],
                                       'knows_1hop_count': [1835384892, 2701488093],
                                       'knows_2hop_count': [3483929844, 289488556],
                                       'friends_rows': [2991437341, 1763198864]},
                           'degree_both': 2201581514,
                           'least_bytes': {'config5_count': 26644.0,
                                           'creator_1hop_count': 1204.0,
                                           'knows_1hop_count': 13924.0,
                                           'knows_2hop_count': 26644.0,
                                           'friends_rows': 323.2}},
 'tiny_knows:3000000019': {'sizes': [300, 0, 2880],
                           'arrays': {'knows_deg': ['int64', 3353050536],
                                      'knows_dst': ['int32', 1046611521],
                                      'knows_cdate': ['int32', 4276609031],
                                      'creator': ['int32', 0],
                                      'age': ['int32', 1753460596],
                                      'length': ['int32', 0]},
                           'answers': {'config5_count': [2873715364, 2873715364],
                                       'creator_1hop_count': [2873715364, 2873715364],
                                       'knows_1hop_count': [1609836171, 1182152034],
                                       'knows_2hop_count': [2436421362, 902365135],
                                       'friends_rows': [768703043, 4008475877]},
                           'degree_both': 4210657034,
                           'least_bytes': {'config5_count': 26644.0,
                                           'creator_1hop_count': 1204.0,
                                           'knows_1hop_count': 13924.0,
                                           'knows_2hop_count': 26644.0,
                                           'friends_rows': 323.2}}}


def crc(a) -> int:
    return zlib.crc32(np.ascontiguousarray(a).tobytes())


@pytest.mark.parametrize("key", sorted(PINNED))
def test_snb_arrays_are_what_the_parent_made(key):
    scale, seed = key.split(":")
    want = PINNED[key]
    snb = run.load_kinds({"name": "pin", "kinds": "snb_arrays"})
    raw = snb.make_raw(SCALES[scale], int(seed))
    assert [raw.P, raw.M, raw.E] == want["sizes"]
    for name, (dtype, pinned) in want["arrays"].items():
        arr = getattr(raw, name)
        assert (str(arr.dtype), crc(arr)) == (dtype, pinned), name
    ref = snb.Reference(raw)
    for kind, pinned in want["answers"].items():
        got = [zlib.crc32(json.dumps(ref.answer(kind, p)).encode()) for p in PARAMS[kind]]
        assert got == pinned, kind
    assert crc(snb.Measures(ref).degree_both()) == want["degree_both"]
    assert {k: snb.least_bytes(k, raw) for k in PARAMS} == want["least_bytes"]


#: (mix, seed) -> the crc32 of each shape's pool of 500 on SCALES["tiny"], from the parent tree
PINNED_POOLS = {
    ("rooted_16s", 7): [3951818984],
    ("rooted_16s", 2**31 + 99): [1558378776],
    ("scan_4s", 7): [1705190676, 1104962724, 1026819088, 2875710016],
}


@pytest.mark.parametrize("mix_name, seed", sorted(PINNED_POOLS))
def test_the_parameter_pools_are_what_the_parent_drew(mix_name, seed):
    snb = run.load_kinds({"name": "pin", "kinds": "snb_arrays"})
    measures = snb.Measures(snb.Reference(snb.make_raw(SCALES["tiny"], seed)))
    plan = traffic.build_plan(traffic.load_json("traffic", mix_name), measures, seed, 500)
    got = [zlib.crc32(json.dumps(s["pool"]).encode()) for s in plan["shapes"]]
    assert got == PINNED_POOLS[(mix_name, seed)]


def snb_cells() -> list:
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [(w["name"], w["config"], w["traffic"]) for w in bench["workloads"]]
    return [c for c in cells if traffic.load_json("configs", c[1]).get("kinds") == "snb_arrays"]


@pytest.mark.parametrize("cell, config, mix_name", snb_cells())
def test_every_cell_finds_its_kinds_module_and_every_shape_an_answer_and_a_byte_count(cell, config, mix_name):
    """What ``tests/`` should hold too (PERF.md 7): each accepted configuration resolves
    its kinds module, and each reference a cell's mix names has an answer and a byte
    count there, on the configuration's own scale cut to 300 persons."""
    cfg = traffic.load_json("configs", config)
    kinds = run.load_kinds(cfg)
    assert all(hasattr(kinds, n) for n in run.KINDS_NAMES)
    scale = dict(cfg["scale"], persons=300, supernodes=min(2, cfg["scale"]["supernodes"]), supernode_degree=40)
    raw = kinds.make_raw(scale, 2**31 + 17)
    ref = kinds.Reference(raw)
    plan = traffic.build_plan(traffic.load_json("traffic", mix_name), kinds.Measures(ref), 2**31 + 17, 8)
    for shape in plan["shapes"]:
        params = dict(zip(shape["pool"]["names"], shape["pool"]["rows"][0]))
        assert isinstance(ref.answer(shape["reference"], params), list), (cell, shape["name"])
        assert kinds.least_bytes(shape["reference"], raw) > 0


# -- (b) a deployment of another kind, from files under tests/data alone ---------------

TOY_BENCH = {
    "configs": [{"name": "path-64", "file": "x", "source": "x", "reduced": [], "why": "x"}],
    "workloads": [{"name": "path_hop", "config": "path-64", "traffic": "hop_3s", "chips": 1, "why": "x"}],
    "end_to_end": [
        {"name": "qps", "unit": "queries/s", "better": "higher", "bound": 0.08, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock"},
    ],
    "per_layer": [
        {"name": "least_bytes_per_q", "unit": "B/query", "better": "lower", "source": "program_counter",
         "layer": "kernels", "moves": "qps"},
    ],
}


def tree(root: str) -> dict:
    """Every file under ``root`` with its size and change time."""
    out = {}
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            st = os.stat(os.path.join(dirpath, name))
            out[os.path.join(dirpath, name)] = (st.st_size, st.st_mtime_ns)
    return out


def drive_toy(control: str = "none", trace: int = 0) -> dict:
    args = argparse.Namespace(workload="path_hop", seed=2**31 + 301, seconds=1.0, trace=trace)
    return run.run_cell(args, TOY_BENCH, require_chip=False, root=DATA, control=control)


def test_a_deployment_of_a_new_kind_runs_from_new_files_alone():
    before = tree(BENCH_DIR)
    res = drive_toy(trace=1)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == res["compared"]["answers_compared"]["value"] > 10
    # the traced span's bytes are the toy module's own count
    assert res["metrics"]["least_bytes_per_q"]["value"] == 16.0
    assert set(drive_toy()["metrics"]) == {"qps", "setup_s"}
    # nothing of benchmark/ was written, and the toy's files are all under tests/data
    assert tree(BENCH_DIR) == before
    toy = run.load_kinds(traffic.load_json("configs", "path-64", DATA), DATA)
    assert toy.__file__.startswith(DATA + os.sep)
    assert not os.path.exists(os.path.join(BENCH_DIR, "kinds", "path_graph.py"))


def test_the_toy_deployments_planted_fault_is_not_correct():
    res = drive_toy(control="stale_snapshot")
    assert res["correct"] is False and res["compared"]["wrong_answers"]["value"] > 0
    assert res["failed"] == 0  # the device answered; it answered the chain turned round


def test_a_measure_over_pairs_curates_two_parameters_at_once():
    """What LDBC's curation of complex read 13 needs: pairs by distance."""

    class Pairs:
        def gap(self):
            pairs = np.array([(a, b) for a in range(30) for b in range(a + 1, 30)])
            return pairs[:, 1] - pairs[:, 0], pairs

    shape = {"name": "s", "params": {"a,b": {"root": "gap", "band": [0.4, 0.6]}, "k": {"const": 3}}}
    pool = traffic.draw_pool(shape, Pairs(), 2**31 + 5, 60)
    assert pool["names"] == ["a", "b", "k"]
    values, _ = Pairs().gap()
    lo, hi = np.quantile(values, [0.4, 0.6])
    rows = pool["rows"]
    assert rows and all(lo <= b - a <= hi and k == 3 for a, b, k in rows)
    assert len({tuple(r) for r in rows}) == len(rows)
    assert rows[0][1] - rows[0][0] == max(b - a for a, b, _k in rows)  # the largest leads
    assert pool == traffic.draw_pool(shape, Pairs(), 2**31 + 5, 60)


# -- (c) what is missing is named at load -------------------------------------------------


def test_a_configuration_that_names_no_kinds_module_fails_at_load():
    with pytest.raises(SystemExit, match="'bare'.*kinds"):
        run.load_kinds({"name": "bare", "scale": {}})


@pytest.mark.parametrize("name", run.KINDS_NAMES)
def test_a_kinds_module_that_lacks_a_name_fails_at_load(tmp_path, name):
    src = open(os.path.join(DATA, "kinds", "path_graph.py")).read()
    assert src.count(f"\ndef {name}(") + src.count(f"\nclass {name}:") == 1
    cut = src.replace(f"\ndef {name}(", "\ndef _gone(").replace(f"\nclass {name}:", "\nclass _Gone:")
    os.makedirs(tmp_path / "kinds")
    (tmp_path / "kinds" / "lacking.py").write_text(cut)
    with pytest.raises(SystemExit, match=f"lacking.py lacks {name}$"):
        run.load_kinds({"name": "c", "kinds": "lacking"}, str(tmp_path))


def test_a_kinds_module_that_is_not_there_fails_at_load():
    with pytest.raises(FileNotFoundError, match="no_such_kinds.py"):
        run.load_kinds({"name": "c", "kinds": "no_such_kinds"})


# -- (d) the seeded pause ------------------------------------------------------------------


def first_pauses(seed: int, session: int, think_ms=(0.5, 3.0), n: int = 50) -> list:
    return list(itertools.islice(loadgen.pauses(seed, session, list(think_ms)), n))


def test_a_seeds_pauses_are_fixed_and_differ_by_seed_and_session():
    big = 2**31 + 12345
    assert first_pauses(big, 3) == first_pauses(big, 3)
    assert first_pauses(big, 3) != first_pauses(big + 1, 3)
    assert first_pauses(big, 3) != first_pauses(big, 4)
    drawn = first_pauses(big, 0, n=2000)
    assert all(0.0005 <= p <= 0.003 for p in drawn)
    assert np.mean(drawn) == pytest.approx(0.00175, rel=0.05)


def test_think_ms_is_a_number_or_a_uniform_range():
    assert traffic.think_range(0) == [0.0, 0.0]
    assert traffic.think_range(1.5) == [1.5, 1.5]
    assert traffic.think_range({"uniform": [0.5, 3]}) == [0.5, 3.0]
    for bad in ({"uniform": [3, 1]}, -1, {"uniform": [-1, 2]}):
        with pytest.raises(ValueError):
            traffic.think_range(bad)


def mix(name: str) -> dict:
    return traffic.load_json("traffic", name)


def test_the_rooted_mix_pauses_and_the_scan_mix_does_not():
    assert traffic.think_range(mix("scan_4s")["think_ms"]) == [0.0, 0.0]
    lo, hi = traffic.think_range(mix("rooted_16s")["think_ms"])
    assert 0 <= lo < hi <= 8  # a fraction of the 16 ms a read takes


class Clock:
    """A remote that answers at once and a ``sleep`` that records."""

    def __init__(self):
        self.slept = []

    def query(self, sql, params):
        rs = argparse.Namespace(engine="tpu")
        rs.to_dicts = lambda: [{"n": 1}]
        return rs

    def close(self):
        pass


@pytest.mark.parametrize("think_ms, pausing", [([0.0, 0.0], False), ([0.2, 0.6], True)])
def test_a_session_pauses_before_every_send_and_only_where_the_mix_says(monkeypatch, think_ms, pausing):
    plan = {
        "sessions": 1, "seed": 2**31 + 9, "think_ms": think_ms, "block": [0], "offsets": [0], "stride": 1,
        "shapes": [{"name": "c", "sql": "x", "columns": ["n"], "ordered": False,
                    "pool": {"names": ["p"], "rows": [[k] for k in range(100)]}}],
    }
    remote = Clock()
    s = loadgen.Session(0, plan, lambda: remote)
    s.open()
    real_sleep = loadgen.time.sleep
    monkeypatch.setattr(loadgen.time, "sleep", lambda d: (remote.slept.append(d), real_sleep(d)))
    records = s.loop(loadgen.now(), 0.05, [0])
    monkeypatch.undo()
    assert len(records) > 3
    if pausing:
        want = first_pauses(plan["seed"], 0, think_ms, len(remote.slept))
        # one pause before each send, and one more that met the deadline
        assert remote.slept == want and len(remote.slept) == len(records) + 1
    else:
        assert remote.slept == []

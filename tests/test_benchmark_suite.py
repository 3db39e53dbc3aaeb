"""The shim that makes tier-1 collect ``benchmark/tests``.

The benchmark's own tests (the harness, the kinds modules, the span
metrics, each deployment's reference and planted fault) live under
``benchmark/tests/``, beside the files they test; the driver's tier-1
command collects ``tests/`` alone. ``tests/conftest.py`` collects every
file this module lists wherever this module is collected, under this
module's node id (one ``--dist loadfile`` worker runs them all, in turn),
so they count like any other test. They need no chip."""

import pathlib

ROOT = pathlib.Path(__file__).resolve().parent.parent


def benchmark_test_files() -> list:
    return sorted((ROOT / "benchmark" / "tests").glob("test_*.py"))


_H = "tests/test_benchmark_suite.py::test_benchmark_harness.py::"
_ONE_KIND = (
    "written when every mix under benchmark/traffic/ was an snb_arrays mix: "
    "it feeds each mix to snb_arrays' reference, measures and byte counts, "
    "and ic13_16s belongs to snb_paths (a PR that adds a deployment may edit "
    "no benchmark file; a benchmark PR has to load each mix's own kinds "
    "module here, PERF.md 7)"
)
_P = "tests/test_benchmark_suite.py::test_snb_paths.py::"
_SNB_SCALE = (
    "written when every configuration was cut from SNB: it reads "
    "scale.persons or hands every cell's kinds module an SNB-shaped scale "
    "(persons, avg_knows), and graph500-22-1chip's scale is the generator's "
    "(scale, edge_factor, a, b, c); benchmark/tests/test_graph500.py holds "
    "the same for that cell (a benchmark PR has to take each cell's small "
    "scale from its own module, PERF.md 7)"
)
#: tests of benchmark/tests that a second kinds module makes fail where it
#: adds nothing wrong: expected to fail, strictly, until a benchmark PR
#: repairs them (and then takes them off this list)
STALE_ASSUMPTIONS = {
    _H + "test_two_seeds_same_shape_order_different_parameters[ic13_16s]": _ONE_KIND,
    _H + "test_reference_agrees_with_the_embedded_engine[shortest_path_len]": _ONE_KIND,
    _H + "test_every_reference_kind_has_a_byte_count": _ONE_KIND,
    # PR 38's deployment, graph500 (bfs_1s; scan_2s is an snb_arrays mix
    # and needs no entry)
    _H + "test_two_seeds_same_shape_order_different_parameters[bfs_1s]": _ONE_KIND,
    _H + "test_reference_agrees_with_the_embedded_engine[bfs_level_counts]": _ONE_KIND,
    _H + "test_benchmark_json_names_files_that_exist": _SNB_SCALE,
    _P + "test_every_reference_kind_of_a_cell_has_a_byte_count_in_its_kinds_module"
    "[g500_s22_bfs_1s]": _SNB_SCALE,
    _P + "test_two_seeds_give_a_cell_the_same_shape_order_and_other_parameters"
    "[g500_s22_bfs_1s]": _SNB_SCALE,
    "tests/test_benchmark_suite.py::test_span_metrics.py::"
    "test_the_new_entries_are_in_benchmark_json_without_a_workloads_list": (
        "asserts that BENCHMARK.json's last six per-layer metrics are PR 27's, "
        "and new entries go at the end of the list (a benchmark PR has to "
        "find them by name, PERF.md 7)"
    ),
}


def test_the_benchmarks_tests_are_collected_with_this_file(request):
    files = benchmark_test_files()
    assert len(files) >= 4
    collected = {item.path for item in request.session.items}
    assert set(files) <= collected, sorted(set(files) - collected)
    ids = {item.nodeid for item in request.session.items}
    assert set(STALE_ASSUMPTIONS) <= ids, sorted(set(STALE_ASSUMPTIONS) - ids)

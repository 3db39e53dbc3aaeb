"""The bench regression gate (VERDICT r3 #1): any benched workload
dropping >15% vs a recorded round's JSON must fail the run — the round-3
LDBC IS3–IS7 45–65% regression shipped silently because nothing compared
rounds."""

import importlib.util
import os

spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py")
)
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)


def _run(value=100.0, is3=200.0, rows=20.0):
    return {
        "metric": "demodb_match_2hop_count_qps",
        "value": value,
        "extras": {
            "rows_1hop_batched_qps": rows,
            "ldbc_is": {"IS1": 100.0, "IS3": is3},
            "batch_size": 64,  # non-qps numbers are not gated
            "phase_split_ms_per_query": {"rows_1hop": {"device_ms": 20.0}},
        },
    }


def test_gate_passes_on_parity_and_improvement():
    assert bench.gate_regressions(_run(), _run()) == []
    assert bench.gate_regressions(_run(value=150, is3=500), _run()) == []


def test_gate_catches_is_style_regression():
    regs = bench.gate_regressions(_run(is3=98.0), _run(is3=268.0))
    assert regs == [("ldbc_is.IS3", 268.0, 98.0)]


def test_gate_catches_headline_regression():
    regs = bench.gate_regressions(_run(value=70.0), _run(value=100.0))
    assert ("headline", 100.0, 70.0) in regs


def test_gate_tolerates_within_15pct():
    assert bench.gate_regressions(_run(value=86.0), _run(value=100.0)) == []


def test_gate_reads_driver_wrapper_format():
    """BENCH_r*.json wraps the printed line under a "parsed" key."""
    prev = {"n": 3, "rc": 0, "parsed": _run(is3=268.0)}
    regs = bench.gate_regressions(_run(is3=98.0), prev)
    assert regs == [("ldbc_is.IS3", 268.0, 98.0)]


def test_gate_covers_round4_metric_families():
    """The sf10/sf100/skew/IC blocks' *_qps leaves are gated; byte and
    edge-count companions are not."""
    def run(ic=300.0, sf10=400.0, sf100=20.0, skew=100.0):
        return {
            "value": 500.0,
            "extras": {
                "ldbc_ic": {"IC1_qps": ic},
                "sf10": {"IS3_qps": sf10, "persons": 100000},
                "sf100_shape": {
                    "two_hop_count_qps": sf100,
                    "hbm_bytes": {"per_device_total": 10**9},
                    "edges": 8 * 10**7,
                },
                "degree_skew": {
                    "supernode_qps": skew,
                    "supernode_edges": 10**7,
                },
            },
        }

    assert bench.gate_regressions(run(), run()) == []
    regs = bench.gate_regressions(
        run(ic=90.0, sf10=100.0, sf100=5.0, skew=20.0), run()
    )
    assert {r[0] for r in regs} == {
        "ldbc_ic.IC1_qps",
        "sf10.IS3_qps",
        "sf100_shape.two_hop_count_qps",
        "degree_skew.supernode_qps",
    }
    # shrinking edge counts / byte gauges never gate
    prev = run()
    cur = run()
    cur["extras"]["sf100_shape"]["edges"] = 1
    cur["extras"]["sf100_shape"]["hbm_bytes"]["per_device_total"] = 1
    cur["extras"]["degree_skew"]["supernode_edges"] = 1
    assert bench.gate_regressions(cur, prev) == []


def test_gate_ignores_non_qps_and_missing_metrics():
    cur = _run()
    cur["extras"]["batch_size"] = 1  # changed but not a qps metric
    del cur["extras"]["rows_1hop_batched_qps"]  # missing in current: skip
    assert bench.gate_regressions(cur, _run()) == []


class TestDeviceMsGate:
    """The stable-signal gate (VERDICT r4 #6): device/host ms medians
    compare at ~0.85 — a regression q/s noise would hide must fail."""

    @staticmethod
    def _run(device=20.0, host=2.0, tiny=0.002):
        return {
            "value": 100.0,
            "extras": {
                "rows_1hop_batched_qps": 50.0,
                "phase_split_ms_per_query": {
                    "rows_1hop": {
                        "device_ms": device,
                        "host_ms": host,
                        "transfer_ms": 10.0,  # not gated (noisy)
                        "kb_per_query": 128.0,
                    },
                    "batched_2hop": {"device_ms": tiny, "host_ms": tiny},
                },
            },
        }

    def test_device_ms_growth_gates(self):
        regs = bench.gate_regressions(self._run(device=30.0), self._run())
        assert ("rows_1hop.device_ms", 20.0, 30.0) in regs

    def test_host_ms_growth_gates(self):
        regs = bench.gate_regressions(self._run(host=4.0), self._run())
        assert ("rows_1hop.host_ms", 2.0, 4.0) in regs

    def test_within_ms_tolerance_passes(self):
        # 20 -> 23 ms is within prev/0.85 = 23.5
        assert bench.gate_regressions(self._run(device=23.0), self._run()) == []

    def test_improvement_passes(self):
        assert bench.gate_regressions(self._run(device=5.0), self._run()) == []

    def test_sub_floor_values_never_gate(self):
        """Micro-ms COUNT workloads are pure jitter: 0.002 -> 0.2 must
        not gate (prev below the 0.5 ms floor)."""
        assert (
            bench.gate_regressions(self._run(tiny=0.2), self._run()) == []
        )

    def test_transfer_ms_is_not_gated(self):
        cur = self._run()
        cur["extras"]["phase_split_ms_per_query"]["rows_1hop"][
            "transfer_ms"
        ] = 99.0
        assert bench.gate_regressions(cur, self._run()) == []

    def test_a_44pct_qps_drop_now_caught_via_ms(self):
        """The r4 weakness: a 44% q/s drop passes the 0.55 q/s gate —
        but its device_ms growth fails the ms gate."""
        cur = self._run(device=36.0)
        cur["extras"]["rows_1hop_batched_qps"] = 28.0  # -44%: passes 0.55
        regs = bench.gate_regressions(cur, self._run(), tolerance=0.55)
        assert regs == [("rows_1hop.device_ms", 20.0, 36.0)]


class TestCompactLine:
    def test_fits_driver_capture_window(self):
        """The stdout line must survive the driver's ~2000-char tail
        capture (round 4's full line exceeded it and was recorded with
        parsed=null, losing every extra)."""
        import json

        from bench import LINE_BUDGET, compact_line

        # a representative fat result: every extras family populated
        out = {
            "metric": "demodb_match_2hop_count_qps",
            "value": 600.0,
            "unit": "queries/sec",
            "vs_baseline": 8000.0,
            "extras": {
                "batch_size": 64,
                "single_query_qps": 9.1,
                "rows_1hop_batched_qps": 58.2,
                "var_depth_while_batched_qps": 480.0,
                "traverse_bfs_batched_qps": 260.0,
                "select_count_batched_qps": 610.0,
                "remote": {
                    "single_qps": 8.5,
                    "batch_qps": 410.0,
                    "pipeline_qps": 120.0,
                    "clients": 4,
                    "extra_detail": list(range(50)),
                },
                "ldbc_is": {f"IS{i}": 100.0 + i for i in range(1, 8)},
                "ldbc_ic": {f"IC{i}": 200.0 + i for i in range(1, 4)},
                "sf10": {f"IS{i}": 300.0 + i for i in range(1, 8)},
                "sf100_shape": {"big": list(range(200))},
                "phase_split_ms_per_query": {
                    t: {"device_ms": 1.2, "transfer_ms": 3.4, "host_ms": 0.5}
                    for t in (
                        "single_2hop",
                        "batched_2hop",
                        "rows_1hop",
                        "rows_1hop_param",
                    )
                },
                "mesh_scaling": [{"S": s, "rows": 4096} for s in (2, 4, 8)],
            },
        }
        line = compact_line(out)
        assert len(line) <= LINE_BUDGET
        parsed = json.loads(line)
        # the required contract keys always survive
        for k in ("metric", "value", "unit", "vs_baseline"):
            assert k in parsed
        assert parsed["extras"]["detail_file"] == "BENCH_DETAIL.json"
        # the gate's stable signal rides along when it fits
        assert "phase_split_ms_per_query" in parsed["extras"]

    def test_gate_survives_null_parsed_wrapper(self):
        from bench import gate_regressions

        cur = {"value": 100.0, "extras": {"x_qps": 50.0}}
        prev_wrapper = {"n": 4, "rc": 0, "tail": "…", "parsed": None}
        # no numeric leaves in the wrapper: trivially no regressions,
        # and no crash on parsed=None
        assert gate_regressions(cur, prev_wrapper) == []

    def test_stable_signal_survives_longest(self):
        """phase_split (the device/host-ms gate signal) is the LAST
        extras family dropped when the line runs over budget."""
        import json

        from bench import compact_line

        out = {
            "metric": "m",
            "value": 1.0,
            "unit": "q/s",
            "vs_baseline": 1.0,
            "extras": {
                "ldbc_is": {f"IS{i}": float(i) for i in range(1, 8)},
                "remote": {"single_qps": 1.0, "batch_qps": 2.0},
                "phase_split_ms_per_query": {
                    "a": {"device_ms": 1.0, "host_ms": 2.0}
                },
            },
        }
        # a budget that can hold phase_split but not everything
        base = len(json.dumps({"metric": "m", "value": 1.0, "unit": "q/s",
                               "vs_baseline": 1.0}))
        line = compact_line(out, budget=base + 160)
        parsed = json.loads(line)
        assert "phase_split_ms_per_query" in parsed["extras"]
        assert "ldbc_is" not in parsed["extras"]

    def test_gate_prev_resolution_order(self, tmp_path):
        """A parsed=null driver record falls back to the round's
        committed BENCH_DETAIL.json — resolved BEFORE the current run
        overwrites it (self-comparison would never fail)."""
        import json

        from bench import _resolve_gate_prev

        wrapper = tmp_path / "BENCH_r04.json"
        wrapper.write_text(json.dumps({"n": 4, "tail": "x", "parsed": None}))
        # the fallback reads the ROUND-STAMPED detail file only — a
        # shared filename would be overwritten by every later run and
        # the gate would compare a run against itself
        detail = tmp_path / "BENCH_DETAIL_r04.json"
        detail.write_text(json.dumps({"value": 42.0, "extras": {"x_qps": 9.0}}))
        (tmp_path / "BENCH_DETAIL.json").write_text(
            json.dumps({"value": 1.0})
        )
        prev = _resolve_gate_prev(str(wrapper))
        assert prev["value"] == 42.0

"""The runtime transfer/compile guard (analysis/deviceguard):
jaxlint's dynamic twin. Unit tests for the knobs, site extraction,
jaxlint cross-check, and the dump round-trip; subprocess end-to-end
tests proving a seeded implicit-transfer mutation and a seeded
recompile mutation each FAIL their observing test with an actionable
message naming the offending site."""

import json
import os
import subprocess
import sys
import textwrap

from orientdb_tpu.analysis import deviceguard as dg_mod
from orientdb_tpu.analysis.deviceguard import DeviceGuard, _violation_site

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestKnobs:
    def test_mode_env_knob(self, monkeypatch):
        monkeypatch.delenv("ORIENTTPU_DEVICEGUARD", raising=False)
        assert dg_mod.mode() == "disallow"
        assert dg_mod.enabled()
        monkeypatch.setenv("ORIENTTPU_DEVICEGUARD", "log")
        assert dg_mod.mode() == "log"
        monkeypatch.setenv("ORIENTTPU_DEVICEGUARD", "0")
        assert dg_mod.mode() is None
        assert not dg_mod.enabled()
        monkeypatch.setenv("ORIENTTPU_DEVICEGUARD", "off")
        assert not dg_mod.enabled()

    def test_dump_path_env_knob(self, monkeypatch, tmp_path):
        monkeypatch.setenv("ORIENTTPU_DEVICEGUARD_DUMP", "0")
        assert dg_mod.dump_path() is None
        p = str(tmp_path / "dg.json")
        monkeypatch.setenv("ORIENTTPU_DEVICEGUARD_DUMP", p)
        assert dg_mod.dump_path() == p
        monkeypatch.delenv("ORIENTTPU_DEVICEGUARD_DUMP")
        assert dg_mod.dump_path().endswith("DEVICEGUARD.json")


class TestSiteExtraction:
    def test_innermost_package_frame_wins(self):
        code = compile(
            "def boom():\n    raise ValueError('x')\nboom()\n",
            os.path.join(REPO, "orientdb_tpu", "exec", "fake_site.py"),
            "exec",
        )
        try:
            exec(code, {})
        except ValueError as e:
            site = _violation_site(e)
        assert site == "orientdb_tpu/exec/fake_site.py:2"

    def test_fallback_to_outermost_non_package_frame(self):
        try:
            raise ValueError("y")
        except ValueError as e:
            site = _violation_site(e)
        assert site.endswith(f":{sys._getframe().f_lineno - 3}") or ":" in site


class TestCrossCheck:
    def test_flagged_site_covers_and_unflagged_site_gaps(self):
        guard = DeviceGuard()
        # tpu_engine.py carries a justified jaxlint suppression at the
        # _cap_of config read — a violation observed in that file is
        # "known to the static pass"; a models/ site is not
        guard.transfers = [
            {
                "test": "t1",
                "site": "orientdb_tpu/exec/tpu_engine.py:247",
                "error": "x",
            },
            {
                "test": "t2",
                "site": "orientdb_tpu/models/database.py:1",
                "error": "y",
            },
        ]
        chk = guard.cross_check()
        assert chk["observed"] == 2
        assert chk["static_covered"] == 1
        assert chk["coverage"] == 0.5
        assert len(chk["gaps"]) == 1
        assert chk["gaps"][0]["site"] == "orientdb_tpu/models/database.py:1"

    def test_no_observations_is_null_coverage(self):
        chk = DeviceGuard().cross_check()
        assert chk["observed"] == 0 and chk["coverage"] is None


class TestDumpRoundTrip:
    def test_dump_is_readable_by_bench(self, tmp_path):
        guard = DeviceGuard()
        guard.tests_guarded = 3
        guard.rerecords = [
            {"test": "t", "stmt": "MATCH ...", "site": "s"}
        ]
        guard.counter_deltas["plan_cache.hit"] = 7
        p = str(tmp_path / "DEVICEGUARD.json")
        guard.dump(p)
        doc = json.loads(open(p).read())
        assert doc["tests_guarded"] == 3
        assert doc["recompile_assertions"] == 2  # 3 tests, 1 offender
        # a reader finds the dump where ORIENTTPU_DEVICEGUARD_DUMP says
        # and gets every figure as plain JSON
        from orientdb_tpu.analysis.deviceguard import dump_path

        os.environ["ORIENTTPU_DEVICEGUARD_DUMP"] = p
        try:
            assert dump_path() == p
        finally:
            del os.environ["ORIENTTPU_DEVICEGUARD_DUMP"]
        assert doc["mode"] == "disallow"
        assert doc["transfers"] == []
        assert len(doc["rerecords"]) == 1
        assert doc["counters"]["plan_cache.hit"] == 7
        assert "coverage" in doc["cross_check"]


def _run_guarded_suite(tmp_path, body: str, env_extra=None):
    """Run `body` as a test file named like a guarded suite in a
    pytest subprocess with ONLY the standalone deviceguard plugin (no
    repo conftest), dumping to a per-run path."""
    test_file = tmp_path / "test_group_dispatch.py"
    test_file.write_text(body)
    dump = tmp_path / "DEVICEGUARD.json"
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            "ORIENTTPU_DEVICEGUARD_DUMP": str(dump),
            # keep the lock sanitizer out of the subprocess: this run
            # exercises the deviceguard plugin alone
            "ORIENTTPU_SANITIZER": "0",
        }
    )
    env.update(env_extra or {})
    proc = subprocess.run(
        [
            sys.executable, "-m", "pytest", str(test_file), "-q",
            "-p", "orientdb_tpu.analysis.deviceguard",
            "-p", "no:cacheprovider",
        ],
        cwd=str(tmp_path),
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    return proc, dump


_DB_PREAMBLE = """\
import numpy as np

from orientdb_tpu import Database, PropertyType
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

def _social():
    db = Database("dg")
    prof = db.schema.create_vertex_class("Profiles")
    prof.create_property("name", PropertyType.STRING)
    prof.create_property("age", PropertyType.LONG)
    db.schema.create_edge_class("HasFriend")
    vs = [
        db.new_vertex("Profiles", name=n, age=20 + i)
        for i, n in enumerate(["a", "b", "c"])
    ]
    db.new_edge("HasFriend", vs[0], vs[1])
    db.new_edge("HasFriend", vs[1], vs[2])
    attach_fresh_snapshot(db)
    return db

_SQL = (
    "MATCH {class:Profiles, as:p, where:(age > :a)}-HasFriend->"
    "{as:f} RETURN p.name AS p, f.name AS f"
)
"""


class TestPluginEndToEnd:
    def test_seeded_implicit_transfer_fails_the_observing_test(
        self, tmp_path
    ):
        """A device+host mixed op under the guard = the implicit-
        transfer mutation: the observing test fails with jax's
        disallowed-transfer error and the summary names the site."""
        proc, dump = _run_guarded_suite(
            tmp_path,
            textwrap.dedent(
                """
                import numpy as np
                import jax.numpy as jnp

                def test_mixed_host_device_math():
                    dev = jnp.arange(8)
                    host = np.arange(8)
                    total = (dev + host).sum()  # implicit h2d transfer
                    assert int(total) == 56
                """
            ),
        )
        assert proc.returncode != 0
        out = proc.stdout + proc.stderr
        assert "Disallowed host-to-device transfer" in out
        assert "IMPLICIT TRANSFER at" in out
        assert "test_group_dispatch.py" in out  # the offending site
        doc = json.loads(dump.read_text())
        assert len(doc["transfers"]) == 1
        assert "test_group_dispatch.py" in doc["transfers"][0]["site"]
        # observed-but-unflagged by jaxlint (a test file, not product
        # code) → reported as a jaxlint gap, never silently tolerated
        assert doc["cross_check"]["gaps"]

    def test_seeded_recompile_mutation_fails_the_observing_test(
        self, tmp_path
    ):
        """Break the plan cache (every lookup misses) and replay the
        SAME statement+parameters: the guard's re-record assertion
        fails the observing test, naming the statement."""
        proc, dump = _run_guarded_suite(
            tmp_path,
            _DB_PREAMBLE
            + textwrap.dedent(
                """
                import collections
                from orientdb_tpu.exec import tpu_engine

                def test_same_shape_replay(monkeypatch):
                    # seeded mutation: the plan cache forgets everything
                    monkeypatch.setattr(
                        tpu_engine, "_plan_cache",
                        lambda snap: collections.OrderedDict(),
                    )
                    db = _social()
                    r1 = db.query(_SQL, {"a": 20}, engine="tpu").to_dicts()
                    r2 = db.query(_SQL, {"a": 20}, engine="tpu").to_dicts()
                    assert r1 == r2
                """
            ),
        )
        assert proc.returncode != 0
        out = proc.stdout + proc.stderr
        assert "same-shape re-record" in out
        # the offending statement is named (its AST repr)
        assert "MatchStatement" in out and "Profiles" in out
        doc = json.loads(dump.read_text())
        assert len(doc["rerecords"]) >= 1
        assert doc["recompile_assertions"] == 0

    def test_clean_guarded_run_passes_and_dumps(self, tmp_path):
        """The same replay WITHOUT the mutation: plan-cache hit, no
        transfers, recompile assertion passes, counters recorded."""
        proc, dump = _run_guarded_suite(
            tmp_path,
            _DB_PREAMBLE
            + textwrap.dedent(
                """
                def test_same_shape_replay_hits_cache():
                    db = _social()
                    r1 = db.query(_SQL, {"a": 20}, engine="tpu").to_dicts()
                    r2 = db.query(_SQL, {"a": 20}, engine="tpu").to_dicts()
                    assert r1 == r2
                """
            ),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(dump.read_text())
        assert doc["tests_guarded"] == 1
        assert doc["transfers"] == [] and doc["rerecords"] == []
        assert doc["recompile_assertions"] == 1
        assert doc["counters"]["plan_cache.hit"] >= 1

    def test_log_mode_reports_rerecord_without_failing(self, tmp_path):
        """`log` is the first-run-on-a-new-backend posture: the seeded
        recompile mutation is OBSERVED (dump + summary) but the suite
        stays green."""
        proc, dump = _run_guarded_suite(
            tmp_path,
            _DB_PREAMBLE
            + textwrap.dedent(
                """
                import collections
                from orientdb_tpu.exec import tpu_engine

                def test_same_shape_replay(monkeypatch):
                    monkeypatch.setattr(
                        tpu_engine, "_plan_cache",
                        lambda snap: collections.OrderedDict(),
                    )
                    db = _social()
                    r1 = db.query(_SQL, {"a": 20}, engine="tpu").to_dicts()
                    r2 = db.query(_SQL, {"a": 20}, engine="tpu").to_dicts()
                    assert r1 == r2
                """
            ),
            env_extra={"ORIENTTPU_DEVICEGUARD": "log"},
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "SAME-SHAPE RE-RECORD" in proc.stdout
        doc = json.loads(dump.read_text())
        assert doc["mode"] == "log"
        assert len(doc["rerecords"]) >= 1

    def test_disabled_by_env_knob(self, tmp_path):
        """ORIENTTPU_DEVICEGUARD=0: the mixed-math test passes and no
        dump is written."""
        proc, dump = _run_guarded_suite(
            tmp_path,
            textwrap.dedent(
                """
                import numpy as np
                import jax.numpy as jnp

                def test_mixed_host_device_math():
                    assert int((jnp.arange(8) + np.arange(8)).sum()) == 56
                """
            ),
            env_extra={"ORIENTTPU_DEVICEGUARD": "0"},
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert not dump.exists()

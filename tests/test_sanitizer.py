"""The runtime lock-order sanitizer (analysis/sanitizer): mutation
tests proving the detector fires — a seeded ABBA acquisition must fail
with BOTH witness stacks — plus proxy/Condition integration, the
dynamic-vs-static locklint cross-check, the edge dump, and the
pytest-plugin end-to-end path (subprocess). What the proxies cost a
single-threaded workload is counted in tests/test_plane_overhead.py."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

from orientdb_tpu.analysis.sanitizer import (
    LockOrderSanitizer,
    _ORIG_LOCK,
    _ORIG_RLOCK,
    _SanLock,
    _SanRLock,
    sanitizer as global_sanitizer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "orientdb_tpu")


def _mk(san, node, rlock=False, path=None):
    """A recording proxy bound to an ISOLATED sanitizer instance (the
    unit tests never touch the module singleton's state)."""
    cls = _SanRLock if rlock else _SanLock
    inner = _ORIG_RLOCK() if rlock else _ORIG_LOCK()
    return cls(san, inner, node, path or os.path.join(PKG, "x.py"))


def _fresh():
    s = LockOrderSanitizer()
    s.active = True
    return s


class TestCycleDetection:
    def test_seeded_abba_fails_with_both_witness_stacks(self):
        """THE sanitizer mutation test: two threads take two locks in
        opposite orders; the violation carries both acquisition
        stacks, one per direction."""
        san = _fresh()
        a = _mk(san, "m.S._a_lock")
        b = _mk(san, "m.S._b_lock")

        def forward_order():
            with a:
                with b:
                    pass

        def reverse_order():
            with b:
                with a:
                    pass

        t = threading.Thread(target=forward_order, name="fwd")
        t.start()
        t.join()
        assert san.violations == []  # one direction alone is fine
        t = threading.Thread(target=reverse_order, name="rev")
        t.start()
        t.join()
        assert len(san.violations) == 1
        v = san.violations[0]
        assert set(v["cycle"]) == {"m.S._a_lock", "m.S._b_lock"}
        msg = san.format_violation(v)
        assert "lock-order cycle" in msg
        # both witness stacks, each naming its acquiring function
        assert msg.count("acquired at:") == 2
        assert "forward_order" in msg and "reverse_order" in msg
        assert "fwd" in msg and "rev" in msg

    def test_consistent_order_is_clean(self):
        san = _fresh()
        a = _mk(san, "m.S._a_lock")
        b = _mk(san, "m.S._b_lock")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert san.violations == []
        assert ("m.S._a_lock", "m.S._b_lock") in san.edges

    def test_three_lock_cycle_detected(self):
        san = _fresh()
        locks = {n: _mk(san, f"m.S._{n}_lock") for n in "abc"}

        def take(x, y):
            with locks[x]:
                with locks[y]:
                    pass

        take("a", "b")
        take("b", "c")
        assert san.violations == []
        take("c", "a")  # closes a->b->c->a
        assert len(san.violations) == 1
        assert len(san.violations[0]["cycle"]) >= 3

    def test_cycle_reported_once(self):
        san = _fresh()
        a = _mk(san, "m.S._a_lock")
        b = _mk(san, "m.S._b_lock")

        def ab():
            with a, b:
                pass

        def ba():
            with b, a:
                pass

        for fn in (ab, ba, ba, ab):
            t = threading.Thread(target=fn)
            t.start()
            t.join()
        assert len(san.violations) == 1

    def test_same_node_reacquire_is_not_an_edge(self):
        """Two locks sharing one node id (per-attribute abstraction,
        e.g. two Databases' _lock) must not self-edge."""
        san = _fresh()
        l1 = _mk(san, "m.S._lock")
        l2 = _mk(san, "m.S._lock")
        with l1:
            with l2:
                pass
        assert san.edges == {}

    def test_rlock_reentrancy_no_edge_no_double_pop(self):
        san = _fresh()
        r = _mk(san, "m.S._rlock", rlock=True)
        other = _mk(san, "m.S._other_lock")
        with r:
            with r:
                with other:
                    pass
        assert ("m.S._rlock", "m.S._other_lock") in san.edges
        assert san._stack() == []  # fully released


class TestProxyIntegration:
    def test_condition_wait_keeps_hold_stack_accurate(self):
        """Condition.wait() releases the lock through _release_save —
        the proxy must pop its frame or the blocked thread would show
        a phantom hold (false long-holds, phantom edges)."""
        san = _fresh()
        san.threshold_s = 0.15
        r = _mk(san, "m.S._cv_lock", rlock=True)
        cv = threading.Condition(r)
        woke = []

        def waiter():
            with cv:
                cv.wait(timeout=5)
                woke.append(True)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.3)  # wait() far past the long-hold threshold
        with cv:
            cv.notify_all()
        t.join()
        assert woke == [True]
        assert san._stack() == []
        # the time spent BLOCKED in wait() is not a "hold"
        assert san.long_holds == []

    def test_long_hold_flagged(self):
        san = _fresh()
        san.threshold_s = 0.05
        lk = _mk(san, "m.S._slow_lock")
        with lk:
            time.sleep(0.08)
        assert len(san.long_holds) == 1
        h = san.long_holds[0]
        assert h["node"] == "m.S._slow_lock"
        assert h["held_ms"] >= 50

    def test_inactive_is_silent_but_stack_stays_consistent(self):
        san = LockOrderSanitizer()  # active=False
        a = _mk(san, "m.S._a_lock")
        b = _mk(san, "m.S._b_lock")
        with a:
            with b:
                pass
        assert san.edges == {} and san.violations == []
        assert san._stack() == []

    def test_try_acquire_failure_records_nothing(self):
        san = _fresh()
        lk = _mk(san, "m.S._lock")
        with lk:
            got = []

            def try_it():
                got.append(lk.acquire(False))

            t = threading.Thread(target=try_it)
            t.start()
            t.join()
            assert got == [False]
        assert san._stack() == []

    def test_install_names_locks_from_the_creation_site(self, tmp_path):
        """End-to-end factory path: a module creating self._box_lock
        gets the locklint-namespaced node id mod.Class.attr."""
        mod = tmp_path / "sanmod_naming.py"
        mod.write_text(
            "import threading\n"
            "class Box:\n"
            "    def __init__(self):\n"
            "        self._box_lock = threading.Lock()\n"
            "_module_lock = threading.Lock()\n"
        )
        was_installed = global_sanitizer.installed
        spec = importlib.util.spec_from_file_location(
            "sanmod_naming", str(mod)
        )
        m = importlib.util.module_from_spec(spec)
        global_sanitizer.install()
        try:
            spec.loader.exec_module(m)
            box = m.Box()
        finally:
            if not was_installed:
                global_sanitizer.uninstall()
        assert box._box_lock.node == "sanmod_naming.Box._box_lock"
        assert m._module_lock.node == "sanmod_naming._module_lock"
        # condition/event internals stay RAW (no .node)
        ev = threading.Event()
        assert not hasattr(ev, "node")

    def test_uninstall_restores_factories(self):
        was_installed = global_sanitizer.installed
        global_sanitizer.install()
        global_sanitizer.uninstall()
        assert threading.Lock is _ORIG_LOCK
        assert threading.RLock is _ORIG_RLOCK
        if was_installed:  # leave the plugin state as we found it
            global_sanitizer.install()


class TestCrossCheck:
    def _with_edges(self, edges):
        san = LockOrderSanitizer()
        for (a, b) in edges:
            san.edges[(a, b)] = {
                "thread": "T",
                "stack": ["x"],
                "paths": (
                    os.path.join(PKG, "x.py"),
                    os.path.join(PKG, "y.py"),
                ),
            }
        return san

    def test_covered_gap_and_leaf_classification(self):
        san = self._with_edges(
            [
                # tails (_mu, _lock) exist in the real static graph
                # (twophase: self._mu then db._lock)
                ("twophase.TwoPhaseRegistry._mu", "database.Database._lock"),
                # fabricated: uncovered, target acquires onward → GAP
                ("m.A._zzq_lock", "m.B._zzr_lock"),
                # fabricated: uncovered, target never acquires → leaf
                ("m.B._zzr_lock", "m.C._zzs_lock"),
            ]
        )
        chk = san.cross_check()
        assert chk["dynamic_edges"] == 3
        assert chk["covered"] == 1
        assert chk["coverage"] == round(1 / 3, 3)
        gap_edges = [tuple(g["edge"]) for g in chk["gaps"]]
        assert gap_edges == [("m.A._zzq_lock", "m.B._zzr_lock")]
        assert chk["leaf_gaps"] == 1

    def test_pr7_gap_edge_is_now_statically_covered(self):
        """The cross-check's first real catch: the dynamic edge
        Cluster._lock -> Database._repl_lock was invisible to locklint
        because the lock is acquired through a non-self receiver
        (`m.db._repl_lock` in `_settled_lsn`, reached via `_elect`
        under the cluster lock). Typed-receiver resolution plus the
        self-method call closure must cover it now — a regression here
        reopens a known blind spot."""
        san = self._with_edges(
            [("cluster.Cluster._lock", "database.Database._repl_lock")]
        )
        chk = san.cross_check()
        assert chk["dynamic_edges"] == 1
        assert chk["covered"] == 1, chk["gaps"]
        assert chk["gaps"] == [] and chk["leaf_gaps"] == 0

    def test_typed_receiver_edge_exists_in_static_graph(self):
        """The static half of the same guarantee, independent of the
        cross-check's matching rules: locklint's graph contains the
        fully-qualified edge itself."""
        from orientdb_tpu.analysis.core import SourceTree
        from orientdb_tpu.analysis.locklint import lock_graph

        edges, _ = lock_graph(SourceTree.from_repo(REPO))
        assert (
            "cluster.Cluster._lock",
            "database.Database._repl_lock",
        ) in edges

    def test_out_of_package_locks_are_out_of_scope(self):
        san = LockOrderSanitizer()
        san.edges[("q.Queue.mutex", "f.Foo._lock")] = {
            "thread": "T",
            "stack": [],
            "paths": ("/usr/lib/python/queue.py", "/tmp/foo.py"),
        }
        assert san.repo_edges() == {}
        assert san.cross_check()["dynamic_edges"] == 0

    def test_dump_is_readable_by_bench(self, tmp_path):
        san = self._with_edges(
            [("twophase.TwoPhaseRegistry._mu", "database.Database._lock")]
        )
        san.long_holds.append(
            {"node": "n", "held_ms": 300.0, "released_at": [],
             "thread": "T"}
        )
        p = str(tmp_path / "edges.json")
        san.dump_edges(p)
        doc = json.loads(open(p).read())
        assert doc["edges"] == [
            {
                "from": "twophase.TwoPhaseRegistry._mu",
                "to": "database.Database._lock",
                "thread": "T",
            }
        ]
        assert doc["cross_check"]["coverage"] == 1.0
        # a reader finds the dump where ORIENTTPU_SANITIZER_EDGES says
        # and gets every figure as plain JSON
        from orientdb_tpu.analysis.sanitizer import edges_path

        os.environ["ORIENTTPU_SANITIZER_EDGES"] = p
        try:
            assert edges_path() == p
        finally:
            del os.environ["ORIENTTPU_SANITIZER_EDGES"]
        assert len(doc["repo_edges"]) == 1
        assert doc["violations"] == 0
        assert len(doc["long_holds"]) == 1
        assert set(doc["cross_check"]) >= {
            "dynamic_edges", "coverage", "static_edges",
        }


class TestPluginEndToEnd:
    def test_seeded_abba_fails_the_pytest_run(self, tmp_path):
        """The plugin half of the mutation test: a suite named like a
        sanitized module with a seeded ABBA must make pytest exit
        nonzero, printing the cycle with both stacks, and dump the
        session's dynamic edges."""
        (tmp_path / "test_concurrency.py").write_text(
            textwrap.dedent(
                """
                import threading

                def test_abba():
                    alpha_lock = threading.Lock()
                    beta_lock = threading.Lock()

                    def fwd():
                        with alpha_lock:
                            with beta_lock:
                                pass

                    def rev():
                        with beta_lock:
                            with alpha_lock:
                                pass

                    for fn in (fwd, rev):
                        t = threading.Thread(target=fn)
                        t.start()
                        t.join()
                """
            )
        )
        edges = tmp_path / "edges.json"
        env = dict(os.environ)
        env["ORIENTTPU_SANITIZER_EDGES"] = str(edges)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("PYTEST_ADDOPTS", None)
        proc = subprocess.run(
            [
                sys.executable, "-m", "pytest", "-q",
                "-p", "orientdb_tpu.analysis.sanitizer",
                "-p", "no:cacheprovider",
                "test_concurrency.py",
            ],
            cwd=str(tmp_path),
            env=env,
            capture_output=True,
            text=True,
            timeout=180,
        )
        assert proc.returncode != 0, proc.stdout + proc.stderr
        assert "lock-order cycle observed at runtime" in proc.stdout
        assert proc.stdout.count("acquired at:") >= 2
        assert "alpha_lock" in proc.stdout and "beta_lock" in proc.stdout
        doc = json.loads(edges.read_text())
        assert doc["violations"] == 1
        assert len(doc["edges"]) >= 2

    def test_disabled_by_env_knob(self, tmp_path):
        """ORIENTTPU_SANITIZER=0: the same seeded ABBA sails through
        (the local-debugging escape hatch)."""
        (tmp_path / "test_concurrency.py").write_text(
            textwrap.dedent(
                """
                import threading

                def test_abba():
                    a_lock = threading.Lock()
                    b_lock = threading.Lock()
                    with a_lock:
                        with b_lock:
                            pass
                    with b_lock:
                        with a_lock:
                            pass
                """
            )
        )
        env = dict(os.environ)
        env["ORIENTTPU_SANITIZER"] = "0"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("PYTEST_ADDOPTS", None)
        proc = subprocess.run(
            [
                sys.executable, "-m", "pytest", "-q",
                "-p", "orientdb_tpu.analysis.sanitizer",
                "-p", "no:cacheprovider",
                "test_concurrency.py",
            ],
            cwd=str(tmp_path),
            env=env,
            capture_output=True,
            text=True,
            timeout=180,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

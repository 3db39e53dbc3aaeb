"""The unified static-analysis subsystem (orientdb_tpu/analysis):
the tier-1 clean-tree gate over all six passes, one mutation test per
pass (a seeded violation each pass must report exactly), the
suppression machinery (incl. unused-suppression detection), and the
CLI. Replaces the scattered per-lint tests as the single entry point
(the old test names still collect via the legacy shims)."""

import json
import os
import subprocess
import sys

import pytest

from orientdb_tpu.analysis import core
from orientdb_tpu.analysis.core import Finding, SourceTree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

core.load_passes()


def run_pass(name, sources, readme=""):
    """One pass over a synthetic tree; returns that pass's findings
    and any suppression findings."""
    tree = SourceTree.from_sources(sources, readme=readme)
    rep = core.run(tree=tree, passes=[name])
    return rep.findings


class TestTreeIsClean:
    def test_all_passes_clean_over_the_whole_tree(self):
        """THE tier-1 gate: zero unsuppressed findings from any pass
        over orientdb_tpu/."""
        rep = core.run(root=REPO)
        assert rep.findings == [], "\n" + "\n".join(
            str(f) for f in rep.findings
        )
        # all ten passes actually ran
        assert set(rep.counts) >= {
            "locklint", "configlint", "exceptlint",
            "iolint", "spanlint", "promlint", "racelint", "jaxlint",
            "alertlint", "critpathlint",
        }

    def test_no_module_or_test_knows_the_old_benchmark(self):
        """Speed has one source, BENCHMARK.json + benchmark/, and the
        arrows point one way: nothing under orientdb_tpu/ or tests/
        names the deleted harness, its comparator, its span helper or
        one of its environment knobs."""
        import re

        gone = re.compile(r"bench\.py|perfdiff|_bench_span|\bBENCH_[A-Z]")
        hits = []
        for top in ("orientdb_tpu", "tests"):
            for dirpath, _dirs, names in os.walk(os.path.join(REPO, top)):
                for f in names:
                    if not f.endswith((".py", ".md", ".json", ".toml")):
                        continue
                    path = os.path.join(dirpath, f)
                    if os.path.samefile(path, __file__):
                        continue  # the pattern above
                    with open(path, encoding="utf-8") as fh:
                        for n, line in enumerate(fh, 1):
                            if gone.search(line):
                                hits.append(f"{path}:{n}: {line.strip()}")
        assert hits == [], "\n" + "\n".join(hits)


class TestFramework:
    def test_suppression_silences_and_counts(self):
        src = (
            "import time\n"
            "import threading\n"
            "_lock = threading.Lock()\n"
            "def f():\n"
            "    with _lock:\n"
            "        time.sleep(0.1)  # lint: allow(locklint)\n"
        )
        tree = SourceTree.from_sources({"orientdb_tpu/exec/x.py": src})
        rep = core.run(tree=tree, passes=["locklint"])
        assert rep.findings == []
        assert len(rep.suppressed) == 1
        assert rep.suppressed[0].pass_name == "locklint"

    def test_unused_suppression_is_itself_a_finding(self):
        src = "x = 1  # lint: allow(locklint)\n"
        tree = SourceTree.from_sources({"orientdb_tpu/exec/x.py": src})
        rep = core.run(tree=tree, passes=["locklint"])
        assert len(rep.findings) == 1
        f = rep.findings[0]
        assert f.pass_name == "suppression"
        assert "unused suppression" in f.message
        assert (f.path, f.line) == ("orientdb_tpu/exec/x.py", 1)

    def test_unknown_pass_in_suppression_flags(self):
        src = "x = 1  # lint: allow(nosuchpass)\n"
        tree = SourceTree.from_sources({"orientdb_tpu/exec/x.py": src})
        rep = core.run(tree=tree, passes=["locklint"])
        assert any(
            "unknown pass" in f.message for f in rep.findings
        )

    def test_repeated_pass_request_is_deduped(self):
        src = (
            "import threading, time\n"
            "_lock = threading.Lock()\n"
            "def f():\n"
            "    with _lock:\n"
            "        time.sleep(1)\n"
        )
        tree = SourceTree.from_sources({"orientdb_tpu/exec/x.py": src})
        rep = core.run(tree=tree, passes=["locklint", "locklint"])
        assert len(rep.findings) == 1
        assert rep.counts["locklint"] == 1

    def test_allow_suppression_itself_is_flagged(self):
        src = "x = 1  # lint: allow(suppression)\n"
        tree = SourceTree.from_sources({"orientdb_tpu/exec/x.py": src})
        rep = core.run(tree=tree, passes=["locklint"])
        assert len(rep.findings) == 1
        assert "cannot themselves" in rep.findings[0].message

    def test_suppression_syntax_in_strings_does_not_count(self):
        src = 'DOC = "example: # lint: allow(locklint)"\n'
        tree = SourceTree.from_sources({"orientdb_tpu/exec/x.py": src})
        rep = core.run(tree=tree, passes=["locklint"])
        assert rep.findings == []  # no stale-suppression finding

    def test_unparsable_module_is_a_finding(self):
        tree = SourceTree.from_sources(
            {"orientdb_tpu/exec/x.py": "def broken(:\n"}
        )
        rep = core.run(tree=tree, passes=["locklint"])
        assert any(f.pass_name == "parse" for f in rep.findings)

    def test_finding_str_is_clickable(self):
        f = Finding("locklint", "a/b.py", 7, "msg")
        assert str(f) == "a/b.py:7: [locklint] msg"


class TestLocklintMutations:
    def test_sleep_under_lock(self):
        src = (
            "import threading, time\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            time.sleep(1.0)\n"
        )
        fs = run_pass("locklint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1
        assert fs[0].pass_name == "locklint"
        assert fs[0].line == 7
        assert "sleep" in fs[0].message and "m.S._lock" in fs[0].message

    def test_socket_send_under_lock(self):
        src = (
            "import threading\n"
            "_send_lock = threading.Lock()\n"
            "def f(sock, data):\n"
            "    with _send_lock:\n"
            "        sock.sendall(data)\n"
        )
        fs = run_pass("locklint", {"orientdb_tpu/server/m.py": src})
        assert len(fs) == 1 and "sendall" in fs[0].message

    def test_lock_order_cycle(self):
        src = (
            "import threading\n"
            "a_lock = threading.Lock()\n"
            "b_lock = threading.Lock()\n"
            "def f():\n"
            "    with a_lock:\n"
            "        with b_lock:\n"
            "            pass\n"
            "def g():\n"
            "    with b_lock:\n"
            "        with a_lock:\n"
            "            pass\n"
        )
        fs = run_pass("locklint", {"orientdb_tpu/parallel/m.py": src})
        assert len(fs) == 1
        assert "lock-order cycle" in fs[0].message
        assert "m.a_lock" in fs[0].message
        assert "m.b_lock" in fs[0].message

    def test_consistent_order_is_clean(self):
        src = (
            "import threading\n"
            "a_lock = threading.Lock()\n"
            "b_lock = threading.Lock()\n"
            "def f():\n"
            "    with a_lock:\n"
            "        with b_lock:\n"
            "            pass\n"
            "def g():\n"
            "    with a_lock:\n"
            "        with b_lock:\n"
            "            pass\n"
        )
        assert run_pass(
            "locklint", {"orientdb_tpu/parallel/m.py": src}
        ) == []

    def test_nested_def_body_not_under_lock(self):
        """A callback defined under a lock runs later — no finding."""
        src = (
            "import threading, time\n"
            "_lock = threading.Lock()\n"
            "def f():\n"
            "    with _lock:\n"
            "        def cb():\n"
            "            time.sleep(1)\n"
            "        return cb\n"
        )
        assert run_pass(
            "locklint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_blocking_context_item_after_lock_in_one_with(self):
        """`with self._lock, urlopen(u):` blocks while holding the
        lock — later items of one with-statement see earlier items'
        acquisitions."""
        src = (
            "import threading\n"
            "from urllib.request import urlopen\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self, u):\n"
            "        with self._lock, urlopen(u) as r:\n"
            "            return r.read()\n"
        )
        fs = run_pass("locklint", {"orientdb_tpu/server/m.py": src})
        assert len(fs) == 1 and "urlopen" in fs[0].message

    def test_typed_receiver_lock_resolves_through_call_closure(self):
        """The PR 7 gap shape: a lock acquired through a TYPED non-self
        receiver (`m.db._repl_lock` with m: Member storing db:
        Database), one self-method call deep under the outer lock —
        the edge must land fully qualified in the graph."""
        from orientdb_tpu.analysis.locklint import lock_graph

        src = (
            "import threading\n"
            "class Database:\n"
            "    def __init__(self):\n"
            "        self._repl_lock = threading.Lock()\n"
            "class Member:\n"
            "    def __init__(self, db: Database):\n"
            "        self.db = db\n"
            "class Cluster:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def probe(self, m: Member):\n"
            "        with self._lock:\n"
            "            self._settle(m)\n"
            "    def _settle(self, m: Member):\n"
            "        with m.db._repl_lock:\n"
            "            pass\n"
        )
        tree = SourceTree.from_sources({"orientdb_tpu/parallel/m.py": src})
        edges, _ = lock_graph(tree)
        assert ("m.Cluster._lock", "m.Database._repl_lock") in edges

    def test_typed_local_binding_carries_across_statements(self):
        """`db = self.db` on one line, `with db._repl_lock:` on the
        next: the typed-local env must persist across the followed
        method's statements."""
        from orientdb_tpu.analysis.locklint import lock_graph

        src = (
            "import threading\n"
            "class Database:\n"
            "    def __init__(self):\n"
            "        self._repl_lock = threading.Lock()\n"
            "class Holder:\n"
            "    def __init__(self, db: Database):\n"
            "        self.db = db\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self._g()\n"
            "    def _g(self):\n"
            "        db = self.db\n"
            "        with db._repl_lock:\n"
            "            pass\n"
        )
        tree = SourceTree.from_sources({"orientdb_tpu/parallel/m.py": src})
        edges, _ = lock_graph(tree)
        assert ("m.Holder._lock", "m.Database._repl_lock") in edges

    def test_blocking_call_one_self_method_deep_flags(self):
        """The call closure also carries the blocking-call check: a
        sleep inside a *_locked helper invoked under the lock."""
        src = (
            "import threading, time\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self._work_locked()\n"
            "    def _work_locked(self):\n"
            "        time.sleep(1)\n"
        )
        fs = run_pass("locklint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1
        assert "sleep" in fs[0].message and fs[0].line == 9

    def test_untyped_receiver_keeps_wildcard_node(self):
        from orientdb_tpu.analysis.locklint import lock_graph

        src = (
            "import threading\n"
            "_g_lock = threading.Lock()\n"
            "def f(obj):\n"
            "    with _g_lock:\n"
            "        with obj._inner_lock:\n"
            "            pass\n"
        )
        tree = SourceTree.from_sources({"orientdb_tpu/exec/m.py": src})
        edges, _ = lock_graph(tree)
        assert ("m._g_lock", "*._inner_lock") in edges

    def test_sleep_outside_lock_is_clean(self):
        src = (
            "import threading, time\n"
            "_lock = threading.Lock()\n"
            "def f():\n"
            "    with _lock:\n"
            "        x = 1\n"
            "    time.sleep(0.1)\n"
        )
        assert run_pass(
            "locklint", {"orientdb_tpu/exec/m.py": src}
        ) == []


class TestRacelintMutations:
    """The static half of race detection: guard-consistency for
    self.<attr> rebinding in thread-crossing classes."""

    _MIXED = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.state = 0\n"
        "    def guarded(self):\n"
        "        with self._lock:\n"
        "            self.state = 1\n"
        "    def unguarded(self):\n"
        "        self.state = 2\n"
    )

    def test_mixed_guard_write_flags_at_the_lock_free_site(self):
        fs = run_pass("racelint", {"orientdb_tpu/exec/m.py": self._MIXED})
        assert len(fs) == 1
        f = fs[0]
        assert f.pass_name == "racelint"
        assert f.line == 10  # the LOCK-FREE write
        assert "mixed-guard" in f.message
        assert "m.S.state" in f.message
        assert "m.S._lock" in f.message
        assert "guarded()" in f.message and "unguarded()" in f.message

    def test_guard_inconsistent_two_locks(self):
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a_lock = threading.Lock()\n"
            "        self._b_lock = threading.Lock()\n"
            "        self.state = 0\n"
            "    def f(self):\n"
            "        with self._a_lock:\n"
            "            self.state = 1\n"
            "    def g(self):\n"
            "        with self._b_lock:\n"
            "            self.state = 2\n"
        )
        fs = run_pass("racelint", {"orientdb_tpu/parallel/m.py": src})
        assert len(fs) == 1
        assert "guard-inconsistent" in fs[0].message
        assert "m.S._a_lock" in fs[0].message
        assert "m.S._b_lock" in fs[0].message

    def test_pairwise_overlapping_guards_are_clean(self):
        """{L1,L2}, {L2,L3}, {L1,L3}: no single lock covers all three
        sites, but every PAIR shares one — all writes are serialized,
        so there is no race to report."""
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a_lock = threading.Lock()\n"
            "        self._b_lock = threading.Lock()\n"
            "        self._c_lock = threading.Lock()\n"
            "        self.state = 0\n"
            "    def f(self):\n"
            "        with self._a_lock, self._b_lock:\n"
            "            self.state = 1\n"
            "    def g(self):\n"
            "        with self._b_lock, self._c_lock:\n"
            "            self.state = 2\n"
            "    def h(self):\n"
            "        with self._a_lock, self._c_lock:\n"
            "            self.state = 3\n"
        )
        assert run_pass(
            "racelint", {"orientdb_tpu/parallel/m.py": src}
        ) == []

    def test_init_writes_are_exempt(self):
        """Construction happens-before publication: __init__'s
        lock-free writes never count against the guarded ones."""
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.state = 0\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self.state = 1\n"
        )
        assert run_pass(
            "racelint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_locked_suffix_methods_are_exempt(self):
        """*_locked methods document 'caller holds the lock'."""
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.state = 0\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self._f_locked()\n"
            "    def _f_locked(self):\n"
            "        self.state = 1\n"
            "    def g(self):\n"
            "        with self._lock:\n"
            "            self.state = 2\n"
        )
        assert run_pass(
            "racelint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_non_thread_crossing_class_is_clean(self):
        """No self-lock, no Thread subclass/target/submit: single-
        threaded staging objects stay out of scope."""
        src = (
            "class Loader:\n"
            "    def __init__(self, db):\n"
            "        self.db = db\n"
            "        self.items = []\n"
            "    def flush(self):\n"
            "        with self.db._lock:\n"
            "            self.items = []\n"
            "    def reset(self):\n"
            "        self.items = []\n"
        )
        assert run_pass(
            "racelint", {"orientdb_tpu/storage/m.py": src}
        ) == []

    def test_thread_target_marks_the_class_crossing(self):
        """A class whose method runs as a Thread target is checked
        even without a self-lock (guards can be module-level)."""
        src = (
            "import threading\n"
            "_mod_lock = threading.Lock()\n"
            "class Pump:\n"
            "    def __init__(self):\n"
            "        self.running = False\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._run).start()\n"
            "    def _run(self):\n"
            "        with _mod_lock:\n"
            "            self.running = True\n"
            "    def stop(self):\n"
            "        self.running = False\n"
        )
        fs = run_pass("racelint", {"orientdb_tpu/cdc/m.py": src})
        assert len(fs) == 1
        assert "m.Pump.running" in fs[0].message
        assert "Thread target" in fs[0].message

    def test_executor_submit_marks_the_class_crossing(self):
        src = (
            "import threading\n"
            "_mod_lock = threading.Lock()\n"
            "class Job:\n"
            "    def __init__(self, pool):\n"
            "        self.pool = pool\n"
            "        self.done = False\n"
            "    def kick(self):\n"
            "        self.pool.submit(self._work)\n"
            "    def _work(self):\n"
            "        with _mod_lock:\n"
            "            self.done = True\n"
            "    def reset(self):\n"
            "        self.done = False\n"
        )
        fs = run_pass("racelint", {"orientdb_tpu/server/m.py": src})
        assert len(fs) == 1
        assert "executor" in fs[0].message

    def test_bare_annotation_is_not_a_write(self):
        """`self.state: int` declares a type — no runtime store, no
        mixed-guard finding."""
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.state = 0\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self.state = 1\n"
            "    def g(self):\n"
            "        self.state: int\n"
            "    def h(self):\n"
            "        with self._lock:\n"
            "            self.state: int = 2\n"
        )
        assert run_pass(
            "racelint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_container_mutation_does_not_count(self):
        """self.d[k] = v mutates the dict, not the binding — out of
        scope by design (rebinding races only)."""
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.d = {}\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self.d = {}\n"
            "    def g(self, k, v):\n"
            "        self.d[k] = v\n"
        )
        assert run_pass(
            "racelint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_suppression_with_justification_silences(self):
        src = self._MIXED.replace(
            "        self.state = 2\n",
            "        self.state = 2  # lint: allow(racelint)\n",
        )
        tree = SourceTree.from_sources({"orientdb_tpu/exec/m.py": src})
        rep = core.run(tree=tree, passes=["racelint"])
        assert rep.findings == []
        assert len(rep.suppressed) == 1


class TestCliBaseline:
    """--baseline: snapshot findings, fail only on NEW ones."""

    def _tree(self, tmp_path, extra=""):
        d = tmp_path / "orientdb_tpu" / "exec"
        d.mkdir(parents=True, exist_ok=True)
        (d / "m.py").write_text(
            "import threading, time\n"
            "_lock = threading.Lock()\n"
            "def f():\n"
            "    with _lock:\n"
            "        time.sleep(1)\n" + extra
        )
        return str(tmp_path)

    def _main(self, *argv):
        from orientdb_tpu.analysis.__main__ import main

        return main(list(argv))

    def test_write_then_clean_compare_then_new_finding(
        self, tmp_path, capsys
    ):
        root = self._tree(tmp_path)
        snap = str(tmp_path / "snap.json")
        args = ("--root", root, "--pass", "locklint", "--baseline", snap)
        assert self._main(*args) == 0  # first run writes
        assert "baseline written" in capsys.readouterr().out
        assert self._main(*args) == 0  # same tree: carried, no new
        out = capsys.readouterr().out
        assert "0 new" in out
        root = self._tree(
            tmp_path,
            "def g(sock, data):\n"
            "    with _lock:\n"
            "        sock.sendall(data)\n",
        )
        assert self._main(*args) == 1  # NEW finding → fail
        out = capsys.readouterr().out
        assert "NEW:" in out and "sendall" in out
        # --write-baseline adopts, then compares clean again
        assert self._main(*args, "--write-baseline") == 0
        capsys.readouterr()
        assert self._main(*args) == 0

    def test_unrelated_edits_do_not_resurface_baselined_debt(
        self, tmp_path, capsys
    ):
        """Messages embed OTHER lines' numbers ("acquired line N");
        the comparison key must blank them or an inserted import above
        a baselined finding reports it as NEW."""
        root = self._tree(tmp_path)
        snap = str(tmp_path / "snap.json")
        args = ("--root", root, "--pass", "locklint", "--baseline", snap)
        assert self._main(*args) == 0  # adopt the sleep-under-lock
        capsys.readouterr()
        # shift every line down: the finding and its "acquired line"
        # reference both move, the debt itself is unchanged
        m = tmp_path / "orientdb_tpu" / "exec" / "m.py"
        m.write_text("import os  # noqa: shifts lines\n" + m.read_text())
        assert self._main(*args) == 0
        out = capsys.readouterr().out
        assert "0 new" in out and "0 fixed" in out

    def test_json_composes_with_baseline(self, tmp_path, capsys):
        """--json --baseline emits a machine-readable comparison (a CI
        piping stdout to json.load must not get the prose lines)."""
        root = self._tree(tmp_path)
        snap = str(tmp_path / "snap.json")
        args = (
            "--root", root, "--pass", "locklint",
            "--baseline", snap, "--json",
        )
        assert self._main(*args) == 0  # write
        doc = json.loads(capsys.readouterr().out)
        assert doc == {"written": True, "baselined": 1}
        assert self._main(*args) == 0  # compare
        doc = json.loads(capsys.readouterr().out)
        assert doc["ok"] is True and doc["new"] == []
        assert doc["carried"] == 1 and doc["baselined"] == 1
        self._tree(
            tmp_path,
            "def g(sock, data):\n"
            "    with _lock:\n"
            "        sock.sendall(data)\n",
        )
        assert self._main(*args) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["ok"] is False and len(doc["new"]) == 1
        assert doc["new"][0]["pass"] == "locklint"

    def test_fixed_findings_reported(self, tmp_path, capsys):
        root = self._tree(
            tmp_path,
            "def g(sock, data):\n"
            "    with _lock:\n"
            "        sock.sendall(data)\n",
        )
        snap = str(tmp_path / "snap.json")
        args = ("--root", root, "--pass", "locklint", "--baseline", snap)
        assert self._main(*args) == 0
        self._tree(tmp_path)  # rewrite without g(): one finding fixed
        assert self._main(*args) == 0
        out = capsys.readouterr().out
        assert "1 fixed" in out and "--write-baseline" in out


_MINI_CONFIG = (
    "class GlobalConfiguration:\n"
    "    foo: int = 1\n"
    "    bar: int = 2\n"
)


class TestConfiglintMutations:
    def test_undeclared_read(self):
        reader = (
            "from orientdb_tpu.utils.config import config\n"
            "x = config.foo\n"
            "y = config.bar\n"
            "z = config.mystery_knob\n"
        )
        fs = run_pass(
            "configlint",
            {
                "orientdb_tpu/utils/config.py": _MINI_CONFIG,
                "orientdb_tpu/exec/m.py": reader,
            },
            readme="foo bar",
        )
        assert len(fs) == 1
        assert "mystery_knob" in fs[0].message
        assert fs[0].path == "orientdb_tpu/exec/m.py"
        assert fs[0].line == 4

    def test_getattr_read_counts(self):
        reader = (
            "from orientdb_tpu.utils.config import config\n"
            "x = config.foo\n"
            'y = getattr(config, "nope", None)\n'
            "z = config.bar\n"
        )
        fs = run_pass(
            "configlint",
            {
                "orientdb_tpu/utils/config.py": _MINI_CONFIG,
                "orientdb_tpu/exec/m.py": reader,
            },
            readme="foo bar",
        )
        assert len(fs) == 1 and "nope" in fs[0].message

    def test_dead_key_flags(self):
        reader = (
            "from orientdb_tpu.utils.config import config\n"
            "x = config.foo\n"
        )
        fs = run_pass(
            "configlint",
            {
                "orientdb_tpu/utils/config.py": _MINI_CONFIG,
                "orientdb_tpu/exec/m.py": reader,
            },
            readme="foo bar",
        )
        assert len(fs) == 1
        assert "'bar' is never read" in fs[0].message
        assert fs[0].path == "orientdb_tpu/utils/config.py"

    def test_missing_readme_mention_flags(self):
        reader = (
            "from orientdb_tpu.utils.config import config\n"
            "x = config.foo\n"
            "y = config.bar\n"
        )
        fs = run_pass(
            "configlint",
            {
                "orientdb_tpu/utils/config.py": _MINI_CONFIG,
                "orientdb_tpu/exec/m.py": reader,
            },
            readme="only foo is documented",
        )
        assert len(fs) == 1
        assert "'bar'" in fs[0].message and "README" in fs[0].message

    def test_other_config_objects_ignored(self):
        """jax.config / self.config attribute reads are not the
        global config singleton."""
        reader = (
            "import jax\n"
            "from orientdb_tpu.utils.config import config\n"
            "x = config.foo\n"
            "y = config.bar\n"
            'jax.config.update("jax_platforms", "cpu")\n'
            "class E:\n"
            "    def g(self):\n"
            "        return self.config.get('loader')\n"
        )
        assert run_pass(
            "configlint",
            {
                "orientdb_tpu/utils/config.py": _MINI_CONFIG,
                "orientdb_tpu/exec/m.py": reader,
            },
            readme="foo bar",
        ) == []


class TestExceptlintMutations:
    def test_bare_except_flags(self):
        src = (
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except:\n"
            "        pass\n"
        )
        fs = run_pass("exceptlint", {"orientdb_tpu/obs/m.py": src})
        assert len(fs) == 1
        assert "bare except" in fs[0].message
        assert fs[0].line == 4

    def test_baseexception_swallow_flags_anywhere(self):
        src = (
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except BaseException:\n"
            "        return None\n"
        )
        fs = run_pass("exceptlint", {"orientdb_tpu/tools/m.py": src})
        assert len(fs) == 1 and "SimulatedCrash" in fs[0].message

    def test_baseexception_with_reraise_is_clean(self):
        src = (
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except BaseException:\n"
            "        cleanup()\n"
            "        raise\n"
        )
        assert run_pass(
            "exceptlint", {"orientdb_tpu/obs/m.py": src}
        ) == []

    def test_silent_except_exception_in_dispatch_path(self):
        src = (
            "def dispatch(req):\n"
            "    try:\n"
            "        handle(req)\n"
            "    except Exception:\n"
            "        pass\n"
        )
        fs = run_pass("exceptlint", {"orientdb_tpu/server/m.py": src})
        assert len(fs) == 1
        assert "discards the error" in fs[0].message

    def test_silent_tuple_except_in_dispatch_path_flags(self):
        src = (
            "def dispatch(req):\n"
            "    try:\n"
            "        handle(req)\n"
            "    except (Exception, OSError):\n"
            "        pass\n"
        )
        fs = run_pass("exceptlint", {"orientdb_tpu/server/m.py": src})
        assert len(fs) == 1
        assert "discards the error" in fs[0].message

    def test_silent_except_outside_dispatch_dirs_is_clean(self):
        src = (
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        pass\n"
        )
        assert run_pass(
            "exceptlint", {"orientdb_tpu/tools/m.py": src}
        ) == []

    def test_handled_except_exception_is_clean(self):
        src = (
            "def dispatch(req):\n"
            "    try:\n"
            "        handle(req)\n"
            "    except Exception:\n"
            "        metrics.incr('dispatch.error')\n"
        )
        assert run_pass(
            "exceptlint", {"orientdb_tpu/server/m.py": src}
        ) == []


class TestIolintMutation:
    def test_unrouted_io_flags(self):
        src = (
            "from urllib.request import urlopen\n"
            "def fetch(url):\n"
            "    return urlopen(url).read()\n"
        )
        fs = run_pass("iolint", {"orientdb_tpu/server/m.py": src})
        assert len(fs) == 1
        assert "fault.point" in fs[0].message
        assert fs[0].line == 2

    def test_routed_io_is_clean(self):
        src = (
            "from urllib.request import urlopen\n"
            "from orientdb_tpu.chaos import fault\n"
            "def fetch(url):\n"
            '    with fault.point("fwd.req"):\n'
            "        return urlopen(url).read()\n"
        )
        assert run_pass(
            "iolint", {"orientdb_tpu/server/m.py": src}
        ) == []


class TestSpanlintMutation:
    def test_missing_span_name_flags_exactly(self):
        from orientdb_tpu.obs.spanlint import SPAN_CATALOG

        # a module exercising every cataloged name (so no stale-entry
        # noise) plus ONE typo'd span
        lines = ["def span(name, **kw): pass"]
        for name in SPAN_CATALOG:
            lines.append(f"span({name!r})")
        lines.append('span("replication.aply")')  # the seeded typo
        src = "\n".join(lines) + "\n"
        fs = run_pass("spanlint", {"orientdb_tpu/obs/m.py": src})
        assert len(fs) == 1
        assert "replication.aply" in fs[0].message
        assert fs[0].line == len(lines)

    def test_stale_catalog_entry_flags(self):
        from orientdb_tpu.obs.spanlint import SPAN_CATALOG

        lines = ["def span(name, **kw): pass"]
        for name in sorted(SPAN_CATALOG)[1:]:  # drop one usage
            lines.append(f"span({name!r})")
        src = "\n".join(lines) + "\n"
        fs = run_pass("spanlint", {"orientdb_tpu/obs/m.py": src})
        dropped = sorted(SPAN_CATALOG)[0]
        assert len(fs) == 1
        assert dropped in fs[0].message
        assert "no call site" in fs[0].message


class TestCritpathlintMutation:
    """The tenth pass: every literal segment()/add_segment() stamp
    name is in SEGMENT_CATALOG (obs/critpath), stale entries flag —
    the spanlint contract applied to critical-path stamps."""

    def test_uncataloged_stamp_flags_exactly(self):
        from orientdb_tpu.obs.critpath import SEGMENT_CATALOG

        # a module exercising every cataloged name (so no stale-entry
        # noise) plus ONE typo'd stamp
        lines = ["def segment(name): pass"]
        for name in SEGMENT_CATALOG:
            lines.append(f"segment({name!r})")
        lines.append('segment("marshall")')  # the seeded typo
        src = "\n".join(lines) + "\n"
        fs = run_pass("critpathlint", {"orientdb_tpu/obs/m.py": src})
        assert len(fs) == 1
        assert "marshall" in fs[0].message
        assert fs[0].line == len(lines)

    def test_method_spelling_is_a_stamp_site(self):
        """cp.add_segment(...) counts the same as the module-level
        call — fold_query stamps the held record directly."""
        from orientdb_tpu.obs.critpath import SEGMENT_CATALOG

        lines = ["def segment(name): pass", "cp = object()"]
        names = sorted(SEGMENT_CATALOG)
        lines.append(f"segment({names[0]!r})")
        for name in names[1:]:
            lines.append(f"cp.add_segment({name!r}, 0.1)")
        src = "\n".join(lines) + "\n"
        fs = run_pass("critpathlint", {"orientdb_tpu/obs/m.py": src})
        assert fs == []

    def test_stale_catalog_entry_flags(self):
        from orientdb_tpu.obs.critpath import SEGMENT_CATALOG

        lines = ["def segment(name): pass"]
        for name in sorted(SEGMENT_CATALOG)[1:]:  # drop one usage
            lines.append(f"segment({name!r})")
        src = "\n".join(lines) + "\n"
        fs = run_pass("critpathlint", {"orientdb_tpu/obs/m.py": src})
        dropped = sorted(SEGMENT_CATALOG)[0]
        assert len(fs) == 1
        assert dropped in fs[0].message
        assert "stamped by no" in fs[0].message
        assert fs[0].path == "orientdb_tpu/obs/critpath.py"


class TestPromlintMutation:
    def test_bad_metric_name_flags(self):
        src = (
            "from orientdb_tpu.utils.metrics import metrics\n"
            'metrics.incr("Bad-Name")\n'
        )
        fs = run_pass("promlint", {"orientdb_tpu/obs/m.py": src})
        assert len(fs) == 1
        assert "Bad-Name" in fs[0].message
        assert fs[0].line == 2

    def test_dotted_lowercase_is_clean_and_dynamic_skipped(self):
        src = (
            "from orientdb_tpu.utils.metrics import metrics\n"
            'metrics.incr("tx2pc.abort_error")\n'
            'metrics.gauge(f"breaker.{name}.state", 1)\n'
        )
        assert run_pass(
            "promlint", {"orientdb_tpu/obs/m.py": src}
        ) == []

    def test_alert_gauge_site_is_checked(self):
        """The alert plane's summary-gauge helper (obs/alerts.
        alert_gauge) publishes into the same registry — its literal
        names obey the same grammar."""
        src = (
            "from orientdb_tpu.obs.alerts import alert_gauge\n"
            'alert_gauge("Bad-Alert-Gauge", 1)\n'
            'alert_gauge("alerts.firing", 2)\n'
        )
        fs = run_pass("promlint", {"orientdb_tpu/obs/m.py": src})
        assert len(fs) == 1
        assert "Bad-Alert-Gauge" in fs[0].message
        assert fs[0].line == 2


class TestAlertlintMutation:
    """The ninth pass: every literal _rule()/AlertRule() name is in
    RULE_CATALOG (obs/alerts), stale entries flag — the spanlint
    contract applied to alert-rule declarations."""

    def test_uncataloged_rule_name_flags(self):
        src = (
            "from orientdb_tpu.obs.alerts import _rule\n"
            '_rule("replication_laag", "critical", lambda e, c: ())\n'
        )
        fs = run_pass("alertlint", {"orientdb_tpu/obs/x.py": src})
        assert any(
            "replication_laag" in f.message and f.line == 2 for f in fs
        )

    def test_cataloged_rule_name_is_clean(self):
        src = (
            "from orientdb_tpu.obs.alerts import AlertRule\n"
            'AlertRule("replication_lag", "critical", lambda e, c: ())\n'
        )
        fs = run_pass("alertlint", {"orientdb_tpu/obs/x.py": src})
        assert not any("replication_lag" in f.message for f in fs)

    def test_stale_catalog_entry_flags_on_the_real_tree(
        self, monkeypatch
    ):
        from orientdb_tpu.obs import alerts

        monkeypatch.setitem(
            alerts.RULE_CATALOG, "ghost_rule", "never declared"
        )
        rep = core.run(root=REPO, passes=["alertlint"])
        assert len(rep.findings) == 1
        assert "ghost_rule" in rep.findings[0].message
        assert rep.findings[0].path == "orientdb_tpu/obs/alerts.py"


class TestJaxlintMutations:
    """Device-boundary & recompile hygiene: one seeded violation per
    sub-check, plus the negative spaces (statics, .shape, memoized
    jit) the pass must NOT flag."""

    def test_host_sync_under_trace(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    jax.device_get(x)\n"
            "    x.block_until_ready()\n"
            "    return x\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 2
        assert "device_get" in fs[0].message
        assert "block_until_ready" in fs[1].message
        assert "traced region" in fs[0].message

    def test_blocking_call_under_trace(self):
        src = (
            "import jax, time\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    time.sleep(0.1)\n"
            "    return x\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1
        assert "sleep" in fs[0].message and fs[0].line == 4

    def test_blocking_in_same_module_call_closure(self):
        """A helper the traced root calls is part of the region."""
        src = (
            "import jax, time\n"
            "def helper(x):\n"
            "    time.sleep(1)\n"
            "    return x\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return helper(x)\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1 and fs[0].line == 3

    def test_tracer_branch_direct_param_advises_static(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x, n):\n"
            "    if n > 2:\n"
            "        return x\n"
            "    return -x\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1
        assert fs[0].line == 4
        assert "static_argnames" in fs[0].message
        assert "'n'" in fs[0].message

    def test_tracer_branch_derived_value(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    y = x + 1\n"
            "    while y.sum() > 0:\n"
            "        y = y - 1\n"
            "    return y\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1
        assert "tracer-valued" in fs[0].message
        assert "`while`" in fs[0].message

    def test_static_argnames_param_is_exempt(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('n',))\n"
            "def f(x, n):\n"
            "    if n > 2:\n"
            "        return x\n"
            "    return -x\n"
        )
        assert run_pass(
            "jaxlint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_shape_branch_is_clean(self):
        """x.shape / len(x) are static host values, not tracers."""
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if x.shape[0] > 2 and len(x) > 1:\n"
            "        return x\n"
            "    if x is None:\n"
            "        return x\n"
            "    return -x\n"
        )
        assert run_pass(
            "jaxlint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_impure_time_and_metrics_under_trace(self):
        src = (
            "import jax, time\n"
            "from orientdb_tpu.utils.metrics import metrics\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    t = time.perf_counter()\n"
            "    metrics.incr('tpu.dispatch')\n"
            "    return x\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 2
        assert "time.perf_counter" in fs[0].message
        assert "baked in" in fs[0].message
        assert "metrics.incr" in fs[1].message

    def test_lock_acquisition_under_trace(self):
        src = (
            "import jax, threading\n"
            "_lock = threading.Lock()\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    with _lock:\n"
            "        return x\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1
        assert "lock acquired inside a traced region" in fs[0].message

    def test_config_read_under_trace(self):
        src = (
            "import jax\n"
            "from orientdb_tpu.utils.config import config\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x * config.schedule_headroom\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1
        assert "config.schedule_headroom" in fs[0].message
        assert "bakes into the executable" in fs[0].message

    def test_host_coercions_on_traced_values(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    a = np.asarray(x)\n"
            "    b = int(x)\n"
            "    c = x.sum().item()\n"
            "    return a, b, c\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        msgs = "\n".join(f.message for f in fs)
        assert len(fs) == 3
        assert "np.asarray" in msgs
        assert "int() coercion" in msgs
        assert ".item()" in msgs

    def test_lambda_passed_to_vmap_is_a_region(self):
        src = (
            "import jax, time\n"
            "def g(xs):\n"
            "    return jax.vmap(lambda x: x * time.time())(xs)\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1 and "time.time" in fs[0].message

    def test_shard_map_local_fn_is_a_region(self):
        src = (
            "from jax import shard_map\n"
            "from orientdb_tpu.utils.metrics import metrics\n"
            "def outer(mesh, data):\n"
            "    def local(x):\n"
            "        metrics.incr('hop')\n"
            "        return x\n"
            "    return shard_map(local, mesh=mesh)(data)\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/parallel/m.py": src})
        assert len(fs) == 1 and "metrics.incr" in fs[0].message

    def test_unmemoized_jit_in_function_scope(self):
        src = (
            "import jax\n"
            "def make(f):\n"
            "    return jax.jit(f)\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1
        assert "without memoization" in fs[0].message
        assert fs[0].line == 3

    def test_jit_memoized_on_self_is_clean(self):
        src = (
            "import jax\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.j = jax.jit(self._f)\n"
            "    def _f(self, x):\n"
            "        return x\n"
        )
        assert run_pass(
            "jaxlint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_jit_flowing_into_cache_is_clean(self):
        src = (
            "import jax\n"
            "class C:\n"
            "    def get(self, k):\n"
            "        fn = jax.jit(self._f)\n"
            "        self.cache[k] = fn\n"
            "        return fn\n"
            "    def _f(self, x):\n"
            "        return x\n"
        )
        assert run_pass(
            "jaxlint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_module_scope_jit_is_clean(self):
        src = (
            "import jax\n"
            "def _f(x):\n"
            "    return x\n"
            "f = jax.jit(_f)\n"
        )
        assert run_pass(
            "jaxlint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_array_valued_static_argument(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('sizes',))\n"
            "def f(x, sizes):\n"
            "    return x\n"
            "def g(x):\n"
            "    return f(x, sizes=[1, 2, 3])\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/exec/m.py": src})
        assert len(fs) == 1
        assert "array-valued static argument" in fs[0].message
        assert "'sizes'" in fs[0].message
        assert fs[0].line == 7

    def test_scalar_static_argument_is_clean(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('n',))\n"
            "def f(x, n):\n"
            "    return x\n"
            "def g(x):\n"
            "    return f(x, n=4)\n"
        )
        assert run_pass(
            "jaxlint", {"orientdb_tpu/exec/m.py": src}
        ) == []

    def test_full_capacity_all_gather_flags(self):
        """The exact pattern ISSUE 13 removed from expand_gather: a
        device count tracks the live extent, yet the whole cap block
        rides an all_gather."""
        src = (
            "import jax\n"
            "def kern(mesh):\n"
            "    def local(ind_l, srcs):\n"
            "        counts = degree_counts(ind_l, srcs)\n"
            "        tot = counts.sum()\n"
            "        blk = gather_expand(ind_l, srcs, tot)\n"
            "        return jax.lax.all_gather(blk, 'shards')\n"
            "    return shard_map(local, mesh=mesh)\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/parallel/m.py": src})
        assert any(
            "full-capacity all_gather" in f.message and f.line == 7
            for f in fs
        )
        assert any("tot" in f.message for f in fs)

    def test_subscript_store_does_not_whitelist_buffer(self):
        """`acc[i] = counts.sum()` stores a count INTO a buffer; an
        all_gather of that whole buffer is exactly the full-capacity
        pattern and must still flag (only plain Name targets become
        count names)."""
        src = (
            "import jax\n"
            "def kern(mesh):\n"
            "    def local(acc, counts):\n"
            "        tot = counts.sum()\n"
            "        acc[0] = counts.max()\n"
            "        return jax.lax.all_gather(acc, 'shards')\n"
            "    return shard_map(local, mesh=mesh)\n"
        )
        fs = run_pass("jaxlint", {"orientdb_tpu/parallel/m.py": src})
        assert any(
            "full-capacity all_gather" in f.message and f.line == 6
            for f in fs
        )

    def test_all_gather_of_device_count_is_clean(self):
        """Gathering the extents themselves (expand_totals' scalar
        exchange) must stay clean, including [None]/reshape lifts."""
        src = (
            "import jax\n"
            "def kern(mesh):\n"
            "    def local(ind_l, srcs):\n"
            "        counts = degree_counts(ind_l, srcs)\n"
            "        tot = counts.sum()[None]\n"
            "        g = jax.lax.all_gather(tot, 'shards').reshape(-1)\n"
            "        return g, jax.lax.all_gather(counts.max(), 'shards')\n"
            "    return shard_map(local, mesh=mesh)\n"
        )
        assert run_pass("jaxlint", {"orientdb_tpu/parallel/m.py": src}) == []

    def test_all_gather_without_tracked_count_is_clean(self):
        """No device count in the region → a block gather may be the
        genuine need; the rule targets the tracked-extent pattern."""
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return jax.lax.all_gather(x, 'shards')\n"
        )
        assert run_pass("jaxlint", {"orientdb_tpu/parallel/m.py": src}) == []

    def test_suppression_with_justification_silences(self):
        src = (
            "import jax, time\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    # deliberate: trace-time stamp for the test fixture\n"
            "    time.sleep(0.1)  # lint: allow(jaxlint)\n"
            "    return x\n"
        )
        tree = SourceTree.from_sources({"orientdb_tpu/exec/m.py": src})
        rep = core.run(tree=tree, passes=["jaxlint"])
        assert rep.findings == []
        assert len(rep.suppressed) == 1

    def test_unused_suppression_flags(self):
        src = "x = 1  # lint: allow(jaxlint)\n"
        tree = SourceTree.from_sources({"orientdb_tpu/exec/m.py": src})
        rep = core.run(tree=tree, passes=["jaxlint"])
        assert len(rep.findings) == 1
        assert "unused suppression" in rep.findings[0].message


class TestCli:
    def test_cli_json_clean_exit_zero(self):
        proc = subprocess.run(
            [sys.executable, "-m", "orientdb_tpu.analysis", "--json"],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["ok"] is True
        assert doc["findings"] == []
        for name in (
            "locklint", "configlint", "exceptlint",
            "iolint", "spanlint", "promlint", "racelint", "jaxlint",
            "critpathlint",
        ):
            assert doc["counts"][name] == 0

    def test_cli_list(self):
        proc = subprocess.run(
            [sys.executable, "-m", "orientdb_tpu.analysis", "--list"],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=60,
        )
        assert proc.returncode == 0
        for name in ("locklint", "configlint", "exceptlint"):
            assert name in proc.stdout

    def test_cli_pass_accepts_comma_separated_list(self):
        proc = subprocess.run(
            [
                sys.executable, "-m", "orientdb_tpu.analysis",
                "--json", "--pass", "jaxlint,locklint",
                "--pass", "promlint",
            ],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert set(doc["counts"]) == {"jaxlint", "locklint", "promlint"}

    def test_cli_comma_list_with_unknown_name_exit_2(self):
        proc = subprocess.run(
            [
                sys.executable, "-m", "orientdb_tpu.analysis",
                "--pass", "locklint,nosuchpass",
            ],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=60,
        )
        assert proc.returncode == 2
        assert "nosuchpass" in proc.stderr

    def test_cli_list_shows_docstring_descriptions(self):
        from orientdb_tpu.analysis.__main__ import pass_description

        proc = subprocess.run(
            [sys.executable, "-m", "orientdb_tpu.analysis", "--list"],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=60,
        )
        assert proc.returncode == 0
        for name in sorted(core.PASSES):
            desc = pass_description(name)
            assert desc  # non-empty for every pass
            assert desc in proc.stdout

    def test_every_pass_module_has_a_docstring(self):
        """--list pulls descriptions from module docstrings; a pass
        without one would list as its bare registry title."""
        import importlib

        for name, ap in sorted(core.PASSES.items()):
            mod = importlib.import_module(ap.fn.__module__)
            doc = (mod.__doc__ or "").strip()
            assert doc, f"pass {name} module {ap.fn.__module__} has no docstring"
            assert doc.splitlines()[0].strip(), name

    def test_cli_unknown_pass_exit_2(self):
        proc = subprocess.run(
            [
                sys.executable, "-m", "orientdb_tpu.analysis",
                "--pass", "nosuchpass",
            ],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=60,
        )
        assert proc.returncode == 2


class TestBackCompatShims:
    """The pre-framework entry points still work (old tests and any
    external callers keep collecting/passing)."""

    def test_iolint_shim(self):
        from orientdb_tpu.chaos.iolint import lint_package

        assert lint_package() == []

    def test_spanlint_shim(self):
        from orientdb_tpu.obs.spanlint import lint_spans

        assert lint_spans() == []

    def test_runtime_promlint_untouched(self):
        from orientdb_tpu.obs.promlint import lint_exposition

        assert lint_exposition("orienttpu_x_total 1\n") == []

"""Test bootstrap: force JAX onto CPU with 8 virtual devices BEFORE jax
imports anywhere, so sharded (mesh) tests run without TPU hardware —
the SURVEY.md §4 analog of OrientDB's `memory:` fake-backend strategy and
its multi-server-in-one-JVM distributed tests."""

import os

# overwrite, not setdefault: an environment that exports another
# JAX_PLATFORMS would put the whole unit suite on the accelerator — slow
# compiles and no 8-device mesh. jax.config.update works post-import as
# long as no backend has been initialized yet.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import tempfile

# dryrun evidence (obs/evidence) defaults to a repo-root JSONL for the
# driver; tests redirect it so suite runs never dirty the worktree
os.environ.setdefault(
    "ORIENTTPU_EVIDENCE",
    os.path.join(tempfile.gettempdir(), "orienttpu-test-evidence.jsonl"),
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from orientdb_tpu.analysis import deviceguard as _deviceguard  # noqa: E402
from orientdb_tpu.analysis import sanitizer as _sanitizer  # noqa: E402

# -- runtime lock-order sanitizer (analysis/sanitizer) -----------------------
# TSan-lite over the concurrency-heavy suites: records per-thread lock
# acquisition stacks, fails a test that exhibits a lock-order cycle
# (both witness stacks printed), flags long holds, and cross-checks the
# dynamic edges against locklint's static graph at session end.
# ORIENTTPU_SANITIZER=0 disables it locally.

# install the lock factories NOW, before any product module imports:
# module-level locks (_TRACE_LOCK, registry singletons) must be
# proxies for the dynamic graph to see them; recording stays off
# outside the sanitized suites
_sanitizer.plugin_configure()


# -- device transfer/compile guard (analysis/deviceguard) --------------------
# jaxlint's dynamic twin: the TPU suites run under jax.transfer_guard
# (implicit host<->device transfer fails the test that made it) with the
# engine's intentional fetch/recording paths allowlisted, and a
# same-shape re-record — the plan cache compiling an identical
# statement twice — fails the observing test. Session summary lands in
# DEVICEGUARD.json. ORIENTTPU_DEVICEGUARD=0 disables; =log warns only.


def pytest_runtest_setup(item):
    _sanitizer.plugin_runtest_setup(item)
    _deviceguard.plugin_runtest_setup(item)


def pytest_runtest_makereport(item, call):
    _deviceguard.plugin_runtest_makereport(item, call)


def pytest_runtest_teardown(item):
    _sanitizer.plugin_runtest_teardown(item)
    _deviceguard.plugin_runtest_teardown(item)


def pytest_sessionfinish(session, exitstatus):
    _sanitizer.plugin_sessionfinish()
    _deviceguard.plugin_sessionfinish()


def pytest_terminal_summary(terminalreporter):
    _sanitizer.plugin_terminal_summary(terminalreporter)
    _deviceguard.plugin_terminal_summary(terminalreporter)


@pytest.fixture
def db():
    from orientdb_tpu import Database

    return Database("testdb")


@pytest.fixture
def social_db():
    """A small demodb-shaped social graph used across test modules.

    Profiles: alice, bob, carol, dave, eve (ids 0..4)
    HasFriend (directed): alice->bob, alice->carol, bob->carol, carol->dave,
                          dave->eve, eve->alice
    Likes: alice->dave (weight 5), bob->eve (weight 1)
    """
    from orientdb_tpu import Database, PropertyType

    db = Database("social")
    prof = db.schema.create_vertex_class("Profiles")
    prof.create_property("name", PropertyType.STRING)
    prof.create_property("age", PropertyType.LONG)
    db.schema.create_edge_class("HasFriend")
    likes = db.schema.create_edge_class("Likes")
    likes.create_property("weight", PropertyType.LONG)

    names = ["alice", "bob", "carol", "dave", "eve"]
    ages = [30, 25, 35, 40, 28]
    vs = {
        n: db.new_vertex("Profiles", name=n, age=a, uid=i)
        for i, (n, a) in enumerate(zip(names, ages))
    }
    friend_pairs = [
        ("alice", "bob"),
        ("alice", "carol"),
        ("bob", "carol"),
        ("carol", "dave"),
        ("dave", "eve"),
        ("eve", "alice"),
    ]
    for a, b in friend_pairs:
        db.new_edge("HasFriend", vs[a], vs[b])
    db.new_edge("Likes", vs["alice"], vs["dave"], weight=5)
    db.new_edge("Likes", vs["bob"], vs["eve"], weight=1)
    db._test_vertices = vs  # convenience for assertions
    return db


# -- the benchmark's own tests (benchmark/tests) -----------------------------
# They live beside the benchmark, where a PR that adds a deployment adds
# its test as a new file; tier-1 collects ``tests/`` alone. The shim
# ``tests/test_benchmark_suite.py`` stands for them: where it is
# collected, every ``benchmark/tests/test_*.py`` is collected with it,
# under the shim's own node id, so that ``--dist loadfile`` gives them to
# ONE worker: their traced runs share ``.bench_trace/``, and two at once
# delete each other's trace. (Importing their tests into the shim's
# namespace would not do: two of the files define a fixture of one name.)


class _BenchmarkTests(pytest.File):
    def collect(self):
        from tests.test_benchmark_suite import benchmark_test_files

        for path in benchmark_test_files():
            yield pytest.Module.from_parent(
                self, path=path, nodeid=f"{self.nodeid}::{path.name}"
            )


def pytest_collect_file(file_path, parent):
    if file_path.name == "test_benchmark_suite.py":
        return _BenchmarkTests.from_parent(parent, path=file_path)
    return None


def pytest_collection_modifyitems(config, items):
    from tests.test_benchmark_suite import STALE_ASSUMPTIONS

    for item in items:
        why = STALE_ASSUMPTIONS.get(item.nodeid)
        if why is not None:
            item.add_marker(pytest.mark.xfail(reason=why, strict=True))

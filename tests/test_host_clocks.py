"""The host's own clocks: each serving thread's CPU time by role, and
every garbage collection's pause (``obs/trace.roles``,
``obs/trace.gc_clock``), read through ``metrics.snapshot()``.

No assertion compares two wall clocks: a thread's CPU clock is its own,
and what a test bounds is that clock against the thread's own
``time.thread_time_ns``, or a count.
"""

import gc
import threading
import time

import pytest

from orientdb_tpu.obs.trace import GcClock, RoleClocks, gc_clock, roles
from orientdb_tpu.utils.metrics import metrics


def _counters() -> dict:
    return metrics.snapshot()["counters"]


def _spin(cpu_s: float) -> None:
    """Burn this thread's own CPU clock for ``cpu_s`` (a wall-clock
    spin burns less where other processes share the cores)."""
    stop = time.thread_time_ns() + int(cpu_s * 1e9)
    while time.thread_time_ns() < stop:
        pass


def _moved(after: dict, before: dict, name: str) -> int:
    return after.get(name, 0) - before.get(name, 0)


class _Worker(threading.Thread):
    """A thread that declares a role, then, once told to go, spins or
    blocks and reports its own CPU time over that stretch; it retires
    once told to end."""

    def __init__(self, role: str, spin_s: float = 0.0, block_s: float = 0.0) -> None:
        super().__init__(daemon=True)
        self.role, self.spin_s, self.block_s = role, spin_s, block_s
        self.ready = threading.Event()
        self.go = threading.Event()
        self.done = threading.Event()
        self.end = threading.Event()
        self.own_ns = 0

    def run(self) -> None:
        roles.declare(self.role)
        try:
            self.ready.set()
            self.go.wait(10)
            t0 = time.thread_time_ns()
            _spin(self.spin_s)
            threading.Event().wait(self.block_s)
            self.own_ns = time.thread_time_ns() - t0
            self.done.set()
            self.end.wait(10)
        finally:
            roles.retire()


def test_a_spinning_lane_moves_its_role_by_its_own_cpu_time():
    w = _Worker("lane", spin_s=0.05)
    w.start()
    w.ready.wait(10)
    before = _counters()
    w.go.set()
    w.done.wait(10)
    after = _counters()
    w.end.set()
    w.join(10)
    moved_ns = _moved(after, before, "thread.lane.cpu_us") * 1000
    assert w.own_ns >= 50e6
    assert moved_ns >= 0.8 * w.own_ns, (moved_ns, w.own_ns)


def test_a_lane_blocked_on_an_event_moves_its_role_by_almost_nothing():
    w = _Worker("lane", block_s=0.3)
    w.start()
    w.ready.wait(10)
    before = _counters()
    w.go.set()
    w.done.wait(10)
    after = _counters()
    w.end.set()
    w.join(10)
    assert w.own_ns < 5e6
    assert _moved(after, before, "thread.lane.cpu_us") < 20_000


def test_the_counter_does_not_fall_when_the_thread_exits():
    w = _Worker("lane", spin_s=0.03)
    w.start()
    w.go.set()
    w.done.wait(10)
    alive = _counters()["thread.lane.cpu_us"]
    w.end.set()
    w.join(10)
    gone = _counters()["thread.lane.cpu_us"]
    assert gone >= alive
    assert _counters()["thread.lane.cpu_us"] >= gone


def test_a_thread_gone_without_retiring_keeps_its_last_reading():
    clocks = RoleClocks()
    seen = {}

    def pool_worker():  # a pool's worker: declared by an initializer only
        clocks.declare("session")
        _spin(0.03)
        seen["read"] = clocks.counters()["thread.session.cpu_us"]

    t = threading.Thread(target=pool_worker)
    t.start()
    t.join(10)
    time.sleep(0.05)  # the kernel reaps the thread's clock
    first = clocks.counters()["thread.session.cpu_us"]
    assert first >= seen["read"] >= 20_000
    assert clocks.counters()["thread.session.cpu_us"] == first
    assert clocks._live == {}


def test_a_thread_that_changes_its_role_moves_only_its_new_role_after():
    clocks = RoleClocks()
    clocks.declare("lane")
    _spin(0.02)
    clocks.declare("watchdog")
    lane = clocks.counters()["thread.lane.cpu_us"]
    _spin(0.02)
    c = clocks.counters()
    clocks.retire()
    assert c["thread.lane.cpu_us"] == lane >= 20_000
    assert c["thread.watchdog.cpu_us"] >= 20_000
    # the whole life of this thread before the first declare is in neither
    assert lane + c["thread.watchdog.cpu_us"] < time.thread_time_ns() // 1000


def test_metrics_reset_keeps_the_sources():
    roles.declare("lane")
    try:
        metrics.reset()
        assert "thread.lane.cpu_us" in _counters()
    finally:
        roles.retire()


@pytest.fixture
def hooked():
    gc_clock.install()
    yield
    gc_clock.uninstall()


def test_a_full_collection_moves_its_count_by_one_and_its_pause(hooked):
    before = _counters()
    gc.collect(2)
    after = _counters()
    assert _moved(after, before, "gc.collections.gen2") == 1
    assert _moved(after, before, "gc.pause_us") > 0
    assert _moved(after, before, "gc.pause_us.gen2") > 0
    assert _moved(after, before, "gc.pause_us") >= _moved(
        after, before, "gc.pause_us.gen2"
    )


def test_the_hook_is_installed_once_and_removed_with_its_last_user():
    clock = GcClock()  # not the process's: its totals are its own
    assert clock._hook not in gc.callbacks
    clock.install()
    clock.install()
    try:
        assert gc.callbacks.count(clock._hook) == 1
        gc.collect(1)
        clock.uninstall()
        assert clock._hook in gc.callbacks
    finally:
        clock.uninstall()
    clock.uninstall()  # one too many: nothing to remove
    assert clock._hook not in gc.callbacks
    counted = clock.counters()
    assert counted["gc.collections.gen1"] >= 1
    gc.collect(2)
    assert clock.counters() == counted


def test_a_full_collection_is_an_annotation_on_the_traces_host_line(
    hooked, tmp_path
):
    import jax

    from benchmark import tracered

    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    trace = tracered.load_xplane(tracered.find_xplane(str(tmp_path)))
    host = [
        e
        for p in trace["planes"]
        if p["name"].startswith("/host:")
        for ln in p["lines"]
        for e in ln["events"]
        if e[0] == "gc.gen2"
    ]
    assert host, [p["name"] for p in trace["planes"]]
    assert all(e[2] > 0 for e in host)


# -- a served window: sessions and a lane move their roles with the work --------

SQL = (
    "MATCH {class:Profiles, as:p, where:(uid = :u)}"
    "-HasFriend->{as:f} RETURN f.uid AS f"
)
SESSIONS = 4
PER_SESSION = 25


def test_a_served_window_moves_the_session_and_lane_clocks_together():
    from orientdb_tpu.client.remote import connect
    from orientdb_tpu.exec.tpu_engine import drain_warmups
    from orientdb_tpu.server import Server
    from orientdb_tpu.storage.ingest import generate_demodb
    from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

    db = generate_demodb(n_profiles=200, avg_friends=4, seed=41)
    attach_fresh_snapshot(db)
    srv = Server(admin_password="pw")
    srv.attach_database(db)
    users = gc_clock._users
    srv.startup()
    try:
        assert gc_clock._users == users + 1
        assert gc_clock._hook in gc.callbacks
        url = f"remote:127.0.0.1:{srv.binary_port}/{db.name}"
        clients = [connect(url, "admin", "pw") for _ in range(SESSIONS)]
        errors = []

        def session(rdb, k0):
            try:
                for k in range(PER_SESSION):
                    rdb.query(SQL, {"u": (k0 + k) % 200})
            except Exception as e:  # reported by the assertion below
                errors.append(e)

        def window():
            ts = [
                threading.Thread(target=session, args=(c, 50 * i))
                for i, c in enumerate(clients)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(120)

        window()  # warm: records, compiles
        drain_warmups()
        before = _counters()
        t0 = time.perf_counter()
        window()
        # a session commits its request's record after the reply is on
        # the wire: let the last ones land
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and _moved(
            _counters(), before, "critpath.requests"
        ) < SESSIONS * PER_SESSION:
            time.sleep(0.01)
        wall = time.perf_counter() - t0
        after = _counters()
        for c in clients:
            c.close()
    finally:
        srv.shutdown()
        db.detach_snapshot()
    assert not errors, errors[:3]
    assert gc_clock._users == users

    requests = _moved(after, before, "critpath.requests")
    batches = _moved(after, before, "coalesce.batches")
    session_us = _moved(after, before, "thread.session.cpu_us")
    lane_us = _moved(after, before, "thread.lane.cpu_us")
    assert requests >= SESSIONS * PER_SESSION
    assert 0 < batches <= requests
    assert session_us > 0 and lane_us > 0
    # no role's CPU exceeds its threads' wall: four sessions, one lane
    assert session_us <= SESSIONS * wall * 1e6
    assert lane_us <= 1 * wall * 1e6

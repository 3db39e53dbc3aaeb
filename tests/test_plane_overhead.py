"""What a request pays each observability plane, as a count.

A plane that is off should cost a request nothing and one that is on a
bounded amount. Both are counts, not times: under ``sys.setprofile``,
over a fixed warm loop of one statement on the test's own thread, the
Python calls a door call makes *into that plane's module*, and the
acquisitions of the plane's own locks. Two wall-clock loops side by
side say what else the machine was doing; a count says what the code
does, here and under six xdist workers alike. No assertion reads a
clock.

Planes ``stats``, ``timeline``, ``critpath``, ``memledger``, ``audit``
at the doors ``db.query``, ``db.query_batch`` and the lane door
(``exec/engine.dispatch_lane_batch(...).collect()``, no server, no
worker thread), plus the lock sanitizer on a single-threaded workload.
Each case asserts (a) with the plane switched off by its own setting
and the others as they ship, and (b) with that plane alone switched on
(beside the stats plane's sampling decision, which three of them ride).
Beside the planes, the metrics registry every one of them folds into:
its calls and lock acquisitions a door call, and no call at all into
the host's clocks (``obs/trace.RoleClocks``, ``GcClock``), which are
read at a snapshot and never on a request's path.

``READINGS`` holds what the code does today, a door call. The planes'
code is not this file's to change: several planes that are "off" still
make more than the one or two calls that read their setting (ROADMAP
C4 keeps the list), and their bound is what is there.
"""

import sys
import threading

import pytest

import orientdb_tpu.exec.engine as E
from orientdb_tpu.analysis.sanitizer import sanitizer as lock_sanitizer
from orientdb_tpu.chaos import fault
from orientdb_tpu.exec import audit
from orientdb_tpu.exec.devicefault import domain
from orientdb_tpu.exec.tpu_engine import drain_warmups
from orientdb_tpu.obs import critpath, memledger, stats, timeline
from orientdb_tpu.storage.ingest import generate_demodb
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics

SQL = (
    "MATCH {class:Profiles, as:p, where:(uid = :u)}"
    "-HasFriend->{as:f} RETURN count(*) AS n"
)
N = 20  # door calls in a counted loop
K = 4  # statements a call at the batch and lane doors

#: every plane off, each by its own setting
ALL_OFF = {
    "stats_sample_rate": 0.0,
    "timeline_capacity": 0,
    "critpath_enabled": False,
    "memledger_enabled": False,
    "audit_sample_rate": 0.0,
}
#: the planes as they ship (the auditor ships off)
AS_SHIPPED = {
    "stats_sample_rate": 1.0,
    "timeline_capacity": 2048,
    "critpath_enabled": True,
    "memledger_enabled": True,
    "audit_sample_rate": 0.0,
}
#: every plane on
ALL_ON = {**AS_SHIPPED, "audit_sample_rate": 1.0}

#: plane -> (its module, its own switch, its locks as (owner, attribute)
#: pairs, looked up when the case runs)
PLANES = {
    "stats": (
        "obs/stats.py",
        "stats_sample_rate",
        lambda: [(stats.stats, "_lock"), (stats, "_fp_lock")],
    ),
    "timeline": (
        "obs/timeline.py",
        "timeline_capacity",
        lambda: [(timeline.recorder, "_lock")],
    ),
    "critpath": (
        "obs/critpath.py",
        "critpath_enabled",
        lambda: [(critpath.plane, "_lock")],
    ),
    "memledger": (
        "obs/memledger.py",
        "memledger_enabled",
        lambda: [(memledger.memledger, "_lock")],
    ),
    "audit": (
        "exec/audit.py",
        "audit_sample_rate",
        lambda: [(audit.auditor, "_mu")],
    ),
}

#: (plane, door) -> a door call's Python calls into the plane's module
#: and acquisitions of its locks, with the plane off and with it on:
#: (off calls, off locks, on calls, on locks). This tree's own reading,
#: which is the parent's (PR 33 changed no plane), rounded up to whole
#: calls. A call of the batch and lane doors carries K statements.
READINGS = {
    ("stats", "query"): (11, 2, 16, 4),
    ("stats", "query_batch"): (25, 0, 40, 8),
    ("stats", "lane"): (17, 0, 31, 8),
    ("timeline", "query"): (14, 0, 25, 1),
    ("timeline", "query_batch"): (12, 0, 21, 1),
    ("timeline", "lane"): (17, 0, 32, 1),
    ("critpath", "query"): (8, 0, 23, 1),
    ("critpath", "query_batch"): (5, 0, 20, 1),
    ("critpath", "lane"): (5, 0, 9, 0),
    ("memledger", "query"): (2, 0, 2, 2),
    ("memledger", "query_batch"): (2, 0, 2, 2),
    ("memledger", "lane"): (3, 0, 5, 3),
    ("audit", "query"): (1, 0, 4, 1),
    ("audit", "query_batch"): (0, 0, 12, 4),
    ("audit", "lane"): (0, 0, 12, 4),
}
#: calls into analysis/sanitizer.py an insert, off and on
SANITIZER_READING = (0, 9)
#: door -> a door call's Python calls into utils/metrics.py and
#: acquisitions of the registry's lock, the planes as they ship. A fetch
#: observes no duration of its own: its wait and copy go to the stats
#: plane and the flight recorder, and on the lane path to the counter
#: ``tpu.fetch_wait_us``
REGISTRY_READINGS = {"query": (9, 6), "query_batch": (11, 8), "lane": (9, 6)}
#: the classes of obs/trace.py that keep the host's clocks
HOST_CLOCKS = ("RoleClocks.", "GcClock.")


def _bound(reading: int) -> float:
    """A reading plus a small margin (a tenth, at least one); nothing
    stays nothing."""
    return reading + max(1.0, reading / 10.0) if reading else 0.0


class _Calls:
    """``sys.setprofile`` hook: Python calls whose code lives in one
    file, by function name. The hook sees this thread only."""

    def __init__(self, suffix: str) -> None:
        self.suffix = suffix
        self.by_name = {}

    def __call__(self, frame, event, arg) -> None:
        if event == "call" and frame.f_code.co_filename.endswith(self.suffix):
            name = frame.f_code.co_name
            self.by_name[name] = self.by_name.get(name, 0) + 1

    @property
    def total(self) -> int:
        return sum(self.by_name.values())


class _CountedLock:
    """Stands in for a plane's lock and counts the acquisitions of the
    thread that made it (a plane's own worker takes the lock too)."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.thread = threading.get_ident()
        self.n = 0

    def acquire(self, *a, **kw):
        self.n += threading.get_ident() == self.thread
        return self.inner.acquire(*a, **kw)

    def release(self) -> None:
        self.inner.release()

    def __enter__(self):
        self.n += threading.get_ident() == self.thread
        return self.inner.__enter__()

    def __exit__(self, *exc):
        return self.inner.__exit__(*exc)


def _count(suffix: str, locks, run) -> tuple:
    """(calls into the module, lock acquisitions) over N door calls."""
    counted = []
    for owner, attr in locks:
        lock = _CountedLock(getattr(owner, attr))
        setattr(owner, attr, lock)
        counted.append((owner, attr, lock))
    run(0)  # the settings just changed: settle before counting
    for _o, _a, lock in counted:
        lock.n = 0
    calls = _Calls(suffix)
    sys.setprofile(calls)
    try:
        for i in range(N):
            run(i)
    finally:
        sys.setprofile(None)
        for owner, attr, lock in counted:
            setattr(owner, attr, lock.inner)
    return calls, sum(lock.n for _o, _a, lock in counted)


@pytest.fixture(scope="module")
def db():
    d = generate_demodb(n_profiles=300, avg_friends=4, seed=18)
    attach_fresh_snapshot(d)
    yield d
    drain_warmups()
    d.detach_snapshot()


def _params(i: int) -> dict:
    return {"u": i % 20}


@pytest.fixture(scope="module")
def doors(db):
    """door -> a function of the loop index that makes one door call;
    every door warm (recorded, compiled, its ring staged) on return."""
    ring = {}

    def query(i):
        db.query(SQL, params=_params(i), engine="tpu", strict=True).to_dicts()

    def query_batch(i):
        db.query_batch(
            [SQL] * K,
            [_params(i + k) for k in range(K)],
            engine="tpu",
            strict=True,
        )

    def lane(i):
        h = E.dispatch_lane_batch(
            db, [SQL] * K, [_params(i + k) for k in range(K)], ring_state=ring
        )
        assert h is not None, "the lane fast path does not apply"
        assert all(rs.engine == "tpu" for rs in h.collect())

    was = config.view_min_calls
    config.view_min_calls = 1 << 30
    try:
        for i in range(N):
            query(i)
        drain_warmups()
        for i in range(N):
            query_batch(i)
        drain_warmups()
        for _ in range(N):
            h = E.dispatch_lane_batch(
                db, [SQL] * K, [_params(k) for k in range(K)], ring_state=ring
            )
            if h is None:
                drain_warmups()  # the lane program was still compiling
            else:
                h.collect()
    finally:
        config.view_min_calls = was
    return {"query": query, "query_batch": query_batch, "lane": lane}


@pytest.fixture(autouse=True)
def _clean_planes(monkeypatch):
    # a materialized view would answer a hot fingerprint before any
    # door reached the device
    monkeypatch.setattr(config, "view_min_calls", 1 << 30)
    # a private auditor whose queue holds the whole loop: no capture is
    # dropped, so every audited call takes the same path
    monkeypatch.setattr(config, "audit_queue_max", 4 * N * K)
    private = audit.ParityAuditor()
    monkeypatch.setattr(audit, "auditor", private)
    fault.disarm()
    domain.reset()
    stats.stats.reset()
    critpath.plane.reset()
    timeline.recorder.reset()
    yield
    assert private.flush(timeout_s=60.0)
    assert private.snapshot()["diverged"] == 0
    stats.stats.reset()
    critpath.plane.reset()
    timeline.recorder.reset()


def _set(monkeypatch, settings: dict) -> None:
    for name, value in settings.items():
        monkeypatch.setattr(config, name, value)


@pytest.mark.parametrize("door", ["query", "query_batch", "lane"])
@pytest.mark.parametrize("plane", list(PLANES))
def test_a_door_call_pays_a_plane_a_counted_amount(
    doors, monkeypatch, plane, door
):
    suffix, switch, locks = PLANES[plane]
    run = doors[door]
    want_off, want_off_locks, want_on, want_on_locks = READINGS[plane, door]

    # (a) off by its own setting, the other planes as they ship
    _set(monkeypatch, {**AS_SHIPPED, switch: ALL_OFF[switch]})
    off, off_locks = _count(suffix, locks(), run)
    # (b) that plane alone, beside the sampling decision
    _set(
        monkeypatch,
        {**ALL_OFF, "stats_sample_rate": 1.0, switch: ALL_ON[switch]},
    )
    on, on_locks = _count(suffix, locks(), run)

    said = (
        f"{plane} at {door}, {N} door calls: off {off.total} calls "
        f"{off.by_name}, {off_locks} locks; on {on.total} calls "
        f"{on.by_name}, {on_locks} locks"
    )
    assert off.total <= _bound(want_off) * N, said
    assert off_locks <= _bound(want_off_locks) * N, said
    assert on.total <= _bound(want_on) * N, said
    assert on_locks <= _bound(want_on_locks) * N, said
    # the switch was really thrown: the plane on does what off did not
    assert on.total > off.total or on_locks > off_locks, said


class _QualCalls:
    """``sys.setprofile`` hook: calls into one file by qualified name."""

    def __init__(self, suffix: str) -> None:
        self.suffix = suffix
        self.names = set()

    def __call__(self, frame, event, arg) -> None:
        if event == "call" and frame.f_code.co_filename.endswith(self.suffix):
            self.names.add(frame.f_code.co_qualname)


@pytest.mark.parametrize("door", ["query", "query_batch", "lane"])
def test_a_door_call_pays_the_registry_a_counted_amount_and_no_host_clock(
    doors, monkeypatch, door
):
    _set(monkeypatch, AS_SHIPPED)
    run = doors[door]
    calls, locks = _count("utils/metrics.py", [(metrics, "_lock")], run)
    want_calls, want_locks = REGISTRY_READINGS[door]
    said = f"registry at {door}, {N} door calls: {calls.by_name}, {locks} locks"
    assert calls.total <= _bound(want_calls) * N, said
    assert locks <= _bound(want_locks) * N, said
    # one duration a call, ``tpu.host_s``
    assert calls.by_name.get("observe", 0) <= N, said
    clocks = _QualCalls("obs/trace.py")
    sys.setprofile(clocks)
    try:
        for i in range(N):
            run(i)
    finally:
        sys.setprofile(None)
    assert not {n for n in clocks.names if n.startswith(HOST_CLOCKS)}, clocks.names


def test_the_lock_sanitizer_pays_by_the_acquisition():
    """Single-threaded: with the factories uninstalled and recording
    off, locks made by the workload are raw and cost the sanitizer's
    module nothing; installed and recording, every acquisition is one
    proxy round (acquire, on_acquired, release, on_released, the hold
    stack) and an order edge where two locks nest."""
    from orientdb_tpu import Database

    ops = 50

    def workload():
        d = Database("san_overhead")
        d.schema.create_vertex_class("P")
        calls = _Calls("analysis/sanitizer.py")
        sys.setprofile(calls)
        try:
            for i in range(ops):
                d.new_vertex("P", uid=i)
        finally:
            sys.setprofile(None)
        return calls

    was_installed, was_active = lock_sanitizer.installed, lock_sanitizer.active
    try:
        lock_sanitizer.uninstall()
        lock_sanitizer.active = False
        off = workload()
        lock_sanitizer.install()
        lock_sanitizer.active = True
        on = workload()
    finally:
        lock_sanitizer.active = was_active
        if was_installed:
            lock_sanitizer.install()
        else:
            lock_sanitizer.uninstall()
    want_off, want_on = SANITIZER_READING
    said = (
        f"{ops} inserts: off {off.total} {off.by_name}; "
        f"on {on.total} {on.by_name}"
    )
    # off: only locks that were made while it was installed (module
    # singletons, under tier-1's conftest) still pass through a proxy,
    # and none of them records
    assert off.total <= _bound(want_off) * ops, said
    assert not {"_note_edge", "_note_long_hold"} & set(off.by_name), said
    assert on.total <= _bound(want_on) * ops, said
    assert on.total > off.total, said


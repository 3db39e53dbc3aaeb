"""Runtime transfer/compile guard — jaxlint's dynamic twin.

jaxlint reasons about the trace boundary statically; nothing verified
that the boundaries it blesses are the boundaries the runtime actually
crosses. This pytest plugin (the :mod:`analysis.sanitizer` pattern)
watches the TPU suites live:

- every test in :data:`GUARDED_SUITES` runs under
  ``jax.transfer_guard`` so an **implicit host↔device transfer** on a
  serving path fails the test that performed it — a silent round
  trip stalls the dispatch pipeline on every occurrence, and the
  PR 4 profiling counters only show it after the fact;
- the plan-compile entry point (``tpu_engine._record``) is wrapped:
  recording the SAME statement+parameters twice against the same
  snapshot within one test is a **same-shape re-record** — the plan
  cache failed, every query is paying the eager compile again — and
  the observing test FAILS with the statement named. The per-suite
  deltas of the PR 4 compile/recompile counters (``plan_cache.hit`` /
  ``.miss`` / ``.overflow_rerecord``) ride the session dump as
  evidence;
- known-intentional boundary crossings are **allowlisted** by
  wrapping, not by mode: ``tpu_engine._fetch_profiled`` (the profiled
  device→host fetch IS the transfer the engine means to make) and the
  eager recording itself (``_record`` mixes host and device by
  design — it is the compile, not the serving path);
- at session end the observed violation sites are **cross-checked
  against jaxlint's static findings**: an observed-but-unflagged site
  is a jaxlint gap and is reported (the sanitizer↔locklint
  convention), and the summary is dumped to ``DEVICEGUARD.json``.

``ORIENTTPU_DEVICEGUARD`` tunes the guard: ``disallow`` (default),
``log`` (warn, never fail — first runs on a new backend), ``0``/``off``
(plugin disabled). Works standalone via
``-p orientdb_tpu.analysis.deviceguard``.
"""

from __future__ import annotations

import os
import traceback
from typing import Dict, List, Optional, Tuple

#: test-module stems guarded by the transfer/compile guard — the
#: suites that exercise the TPU serving paths end to end
GUARDED_SUITES = frozenset(
    {
        "test_tpu_match",
        "test_select_compile",
        "test_sharded",
        "test_group_dispatch",
        "test_coalesce_lanes",
    }
)

#: counters summarized per session (the PR 4 compile/recompile plane)
_COUNTERS = (
    "plan_cache.hit",
    "plan_cache.miss",
    "plan_cache.overflow_rerecord",
    "plan_cache.aot_compile",
    "plan_cache.group_compile",
)


def mode() -> Optional[str]:
    """The transfer-guard level, or None when the plugin is disabled."""
    v = os.environ.get("ORIENTTPU_DEVICEGUARD", "disallow").lower()
    if v in ("0", "off", "false"):
        return None
    if v in ("log", "log_explicit"):
        return "log"
    return "disallow"


def enabled() -> bool:
    return mode() is not None


def dump_path() -> Optional[str]:
    """Where the session summary lands (ORIENTTPU_DEVICEGUARD_DUMP
    overrides; '0'/'off' disables the dump)."""
    p = os.environ.get("ORIENTTPU_DEVICEGUARD_DUMP")
    if p in ("0", "off"):
        return None
    if p:
        return p
    from orientdb_tpu.analysis.core import repo_root

    return os.path.join(repo_root(), "DEVICEGUARD.json")


class DeviceGuard:
    """Process-wide state: installed wrappers, per-test record keys,
    observed violations, counter deltas."""

    def __init__(self) -> None:
        self.installed = False
        self.active_item: Optional[str] = None
        self._ctx = None
        #: (id(snapshot), plan-cache key) recorded in the CURRENT test;
        #: the value keeps the snapshot alive so a GC'd snapshot's id
        #: cannot be recycled into a spurious collision mid-test
        self._recorded: Dict[Tuple, Tuple[str, object]] = {}
        self._cc_cache: Optional[Tuple[Tuple[int, int], Dict]] = None
        #: same-shape re-records observed: {"test", "stmt", "site"}
        self.rerecords: List[Dict] = []
        #: transfer violations observed: {"test", "site", "error"}
        self.transfers: List[Dict] = []
        self.tests_guarded = 0
        self._counter_base: Dict[str, int] = {}
        self.counter_deltas: Dict[str, int] = {k: 0 for k in _COUNTERS}
        self._pending_rerecord: List[Dict] = []

    # -- wrapper installation ------------------------------------------------

    def install(self) -> None:
        """Wrap the engine's compile entry and the intentional fetch
        path. Idempotent; imports tpu_engine lazily (the first guarded
        test pays it, non-TPU sessions never do)."""
        if self.installed:
            return
        self.installed = True
        import jax

        from orientdb_tpu.exec import tpu_engine

        orig_record = tpu_engine._record
        orig_fetch = tpu_engine._fetch_profiled
        guard = self

        def record_tracked(db, stmt, params):
            # the eager recording IS the compile: host/device mixing is
            # its job (allowlisted); but the SAME cacheable statement +
            # params recording twice against one snapshot means the
            # plan cache failed — a recompile on a same-shape replay
            key = None
            try:
                ck = tpu_engine._cache_key(stmt, params)
                if ck is not None:
                    snap = db.current_snapshot()
                    # the delta plane's plan generation joins the
                    # identity: a topology/dictionary structure bump
                    # (storage/deltas) legitimately clears the plan
                    # cache — recording again under a NEW generation is
                    # the designed recompile boundary, not a cache miss
                    ov = getattr(snap, "_overlay", None)
                    gen = ov.plan_gen if ov is not None else 0
                    key = (id(snap), gen, ck)
            except Exception:
                key = None
            if key is not None and guard.active_item is not None:
                prev = guard._recorded.get(key)
                if prev is not None:
                    guard._pending_rerecord.append(
                        {
                            "test": guard.active_item,
                            "stmt": str(stmt)[:200],
                            "site": "orientdb_tpu/exec/tpu_engine.py"
                            ":_record",
                        }
                    )
                else:
                    guard._recorded[key] = (str(stmt)[:200], snap)
            with jax.transfer_guard("allow"):
                return orig_record(db, stmt, params)

        def fetch_allowlisted(devs, split_sync=True):
            # the profiled fetch is the INTENTIONAL device→host path
            with jax.transfer_guard("allow"):
                return orig_fetch(devs, split_sync=split_sync)

        record_tracked._deviceguard_orig = orig_record  # type: ignore[attr-defined]
        fetch_allowlisted._deviceguard_orig = orig_fetch  # type: ignore[attr-defined]
        tpu_engine._record = record_tracked
        tpu_engine._fetch_profiled = fetch_allowlisted

    # -- per-test lifecycle --------------------------------------------------

    def begin(self, nodeid: str) -> None:
        import jax

        from orientdb_tpu.utils.metrics import metrics

        self.install()
        self.active_item = nodeid
        self.tests_guarded += 1
        self._recorded.clear()
        self._pending_rerecord = []
        self._counter_base = {k: metrics.counter(k) for k in _COUNTERS}
        self._ctx = jax.transfer_guard(mode())
        self._ctx.__enter__()

    def end(self) -> List[Dict]:
        """Close the guard; returns this test's re-record violations
        (caller fails the test)."""
        from orientdb_tpu.utils.metrics import metrics

        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None
        for k in _COUNTERS:
            self.counter_deltas[k] += metrics.counter(k) - (
                self._counter_base.get(k, 0)
            )
        self.active_item = None
        fresh, self._pending_rerecord = self._pending_rerecord, []
        self.rerecords.extend(fresh)
        return fresh

    def note_transfer(self, nodeid: str, exc: BaseException) -> None:
        site = _violation_site(exc)
        self.transfers.append(
            {
                "test": nodeid,
                "site": site,
                "error": str(exc).split("\n")[0][:300],
            }
        )

    # -- session reporting ---------------------------------------------------

    def cross_check(self) -> Dict:
        """Observed violation sites vs jaxlint's static findings: a
        site the static pass has no finding for (same file) is a
        jaxlint gap — reported, never silently tolerated. Memoized per
        observation count: the session-end dump and the terminal
        summary both call this, and the full-repo jaxlint run must not
        execute twice for a frozen violation set (the sanitizer
        cross_check convention)."""
        sig = (len(self.transfers), len(self.rerecords))
        if self._cc_cache is not None and self._cc_cache[0] == sig:
            return self._cc_cache[1]
        observed = []
        for v in self.transfers:
            observed.append(("transfer", v["site"], v["test"]))
        for v in self.rerecords:
            observed.append(("rerecord", v["site"], v["test"]))
        out: Dict = {
            "observed": len(observed),
            "static_covered": 0,
            "gaps": [],
            "coverage": None,
        }
        if not observed:
            self._cc_cache = (sig, out)
            return out
        try:
            from orientdb_tpu.analysis import core

            core.load_passes()
            rep = core.run(passes=["jaxlint"])
            flagged = {
                (f.path, f.line)
                for f in rep.findings + rep.suppressed
            }
            flagged_files = {p for p, _l in flagged}
        except Exception:  # pragma: no cover - stripped source tree
            self._cc_cache = (sig, out)
            return out
        covered = 0
        for kind, site, test in observed:
            path, _, line = site.partition(":")
            hit = (
                path,
                int(line) if line.split(":")[0].isdigit() else -1,
            ) in flagged or (kind == "transfer" and path in flagged_files)
            if hit:
                covered += 1
            else:
                out["gaps"].append(
                    {"kind": kind, "site": site, "test": test}
                )
        out["static_covered"] = covered
        out["coverage"] = round(covered / len(observed), 3)
        self._cc_cache = (sig, out)
        return out

    def dump(self, path: str) -> None:
        import json

        from orientdb_tpu.storage.durability import atomic_write

        doc = {
            "mode": mode(),
            "suites": sorted(GUARDED_SUITES),
            "tests_guarded": self.tests_guarded,
            "transfers": self.transfers,
            "rerecords": self.rerecords,
            "counters": dict(self.counter_deltas),
            # every guarded test that finished WITHOUT a same-shape
            # re-record is one passed recompile assertion
            "recompile_assertions": self.tests_guarded
            - len({v["test"] for v in self.rerecords}),
            "cross_check": self.cross_check(),
        }
        atomic_write(
            path, json.dumps(doc, indent=1, sort_keys=True).encode()
        )


def _violation_site(exc: BaseException) -> str:
    """repo-relative file:line of the innermost package frame in the
    violation's traceback (the offending call site); falls back to the
    innermost non-library frame (the test body itself) when the
    transfer happened outside the package."""
    pkg_best = None
    user_best = None
    for frame, lineno in traceback.walk_tb(exc.__traceback__):
        fn = frame.f_code.co_filename.replace(os.sep, "/")
        if "orientdb_tpu/" in fn:
            pkg_best = (
                f"orientdb_tpu/{fn.split('orientdb_tpu/', 1)[1]}:{lineno}"
            )
        elif "site-packages/" not in fn and not fn.startswith("<"):
            user_best = f"{fn}:{lineno}"
    return pkg_best or user_best or "?"


#: the process-wide guard every hook reports to
deviceguard = DeviceGuard()


# -- pytest plugin ------------------------------------------------------------


def _item_stem(item) -> str:
    return os.path.basename(str(item.fspath)).rsplit(".", 1)[0]


def plugin_runtest_setup(item) -> None:
    if not enabled():
        return
    if _item_stem(item) in GUARDED_SUITES:
        deviceguard.begin(item.nodeid)


def plugin_runtest_makereport(item, call) -> None:
    """Capture implicit-transfer failures during the call phase: the
    test already fails with jax's error; this records the SITE for the
    terminal summary and the jaxlint cross-check."""
    if not enabled() or call.when != "call" or call.excinfo is None:
        return
    if deviceguard.active_item != item.nodeid:
        return
    exc = call.excinfo.value
    msg = str(exc)
    if "Disallowed" in msg and "transfer" in msg:
        deviceguard.note_transfer(item.nodeid, exc)


def plugin_runtest_teardown(item) -> None:
    if not enabled():
        return
    if deviceguard.active_item != item.nodeid:
        return
    fresh = deviceguard.end()
    # `log` mode observes and reports but never fails — the documented
    # first-run-on-a-new-backend posture covers BOTH guard halves
    if fresh and mode() == "disallow":
        import pytest

        lines = [
            "same-shape re-record: the plan cache failed and the eager "
            "compile ran again for an identical statement+parameters —"
        ]
        for v in fresh:
            lines.append(f"  {v['stmt']}")
        lines.append(
            "  (recorded twice against one snapshot; a replay this "
            "shape should have served from the cached plan — see "
            "exec/tpu_engine._prepare)"
        )
        pytest.fail("\n".join(lines), pytrace=False)


def plugin_sessionfinish() -> None:
    if not enabled() or deviceguard.tests_guarded == 0:
        return
    p = dump_path()
    if p is not None:
        try:
            deviceguard.dump(p)
        except Exception:  # pragma: no cover - best-effort artifact
            pass


def plugin_terminal_summary(terminalreporter) -> None:
    if not enabled() or deviceguard.tests_guarded == 0:
        return
    tr = terminalreporter
    dg = deviceguard
    tr.write_sep("-", "device transfer/compile guard")
    tr.write_line(
        f"guarded {dg.tests_guarded} test(s) [{mode()}]: "
        f"{len(dg.transfers)} implicit transfer(s), "
        f"{len(dg.rerecords)} same-shape re-record(s); counters "
        + ", ".join(
            f"{k.split('.', 1)[1]}={v}"
            for k, v in sorted(dg.counter_deltas.items())
        )
    )
    for v in dg.transfers:
        tr.write_line(
            f"  IMPLICIT TRANSFER at {v['site']} ({v['test']}): "
            f"{v['error']}"
        )
    for v in dg.rerecords:
        tr.write_line(
            f"  SAME-SHAPE RE-RECORD in {v['test']}: {v['stmt']}"
        )
    chk = dg.cross_check()
    for g in chk["gaps"]:
        # an observed-but-unflagged site is a jaxlint gap — reported
        # every run, never silently tolerated
        tr.write_line(
            f"  JAXLINT GAP: {g['kind']} at {g['site']} — the static "
            "pass has no finding for this site"
        )


# standalone plugin hooks (-p orientdb_tpu.analysis.deviceguard)


def pytest_runtest_setup(item):  # pragma: no cover - via subprocess
    plugin_runtest_setup(item)


def pytest_runtest_makereport(item, call):  # pragma: no cover
    plugin_runtest_makereport(item, call)


def pytest_runtest_teardown(item):  # pragma: no cover - via subprocess
    plugin_runtest_teardown(item)


def pytest_sessionfinish(session, exitstatus):  # pragma: no cover
    plugin_sessionfinish()


def pytest_terminal_summary(terminalreporter):  # pragma: no cover
    plugin_terminal_summary(terminalreporter)

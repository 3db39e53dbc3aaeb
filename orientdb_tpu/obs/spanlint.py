"""AST lint: every span name literal in the codebase is cataloged.

The profile aggregator (``obs/profile.py``) groups stages by span NAME
and cross-node traces join on the names both sides emit — a typo'd
name in a new ``span("replication.aply")`` would silently split a
stage out of every profile and break trace joins, with no test to
notice. This lint (now the ``spanlint`` pass of ``orientdb_tpu/analysis``,
enforced tier-1 by ``tests/test_analysis.py``; ``lint_spans`` below
stays as a back-compat shim) makes that a build failure:

- every **string-literal** first argument of a ``span(...)`` /
  ``_span(...)`` / ``continue_trace(...)`` call under
  ``orientdb_tpu/`` must appear in :data:`SPAN_CATALOG`;
- every catalog entry must be used by at least one call site (a stale
  entry is dead documentation).

Dynamically named spans (f-strings like ``f"http.{verb}"``) cannot be
linted literal-by-literal; their families are documented in
:data:`DYNAMIC_FAMILIES` instead. Tests are exempt — ad-hoc span names
there are fixtures, not stages.

The catalog doubles as the span-name reference the README links.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Tuple

#: span name → what the stage covers. The profile aggregator's stage
#: names and the cross-node trace vocabulary, in one place.
SPAN_CATALOG: Dict[str, str] = {
    "query": "engine front door: one idempotent statement via query()",
    "command": "engine front door: one statement via command()",
    "query_batch": "batched front door: N statements, one dispatch wave",
    "profile": "EXPLAIN PROFILE execution of the inner statement",
    "tpu.load": "device-graph upload / fetch for a compiled execution",
    "tpu.solve": "compiled MATCH/TRAVERSE solve (recording execution)",
    "tpu.step": "one compiled plan step (root scan / expansion hop)",
    "tpu.marshal": "device results → host rows marshalling",
    "tpu.dispatch": "compiled replay dispatch (profile_execute)",
    "tpu.device": "device execution sync (profile_execute)",
    "tx.commit": "local transaction commit (MVCC checks + WAL append)",
    "tx2pc.coordinate": "2PC coordinator round (prepare + decide)",
    "tx2pc.participant.prepare": "2PC phase 1: validate + lock + stage",
    "tx2pc.participant.commit": "2PC phase 2: execute the staged batch",
    "tx2pc.participant.abort": "2PC abort: release the staged batch",
    "wal.append": "write-ahead-log append (+fsync when configured)",
    "replication.apply": "replica apply batch (push or pull)",
    "replication.apply_entry": "one WAL entry applied on a replica "
    "(joins the originating write's trace)",
    "forward.request": "non-owner → write-owner HTTP forward",
    "coalesce.lane": "cross-session micro-batching: one item's stay in "
    "its fingerprint lane, enqueue through result (submitter side)",
    "coalesce.dispatch": "one lane micro-batch executed on the lane "
    "worker (continues the first submitter's trace; lane/batch attrs)",
    "lane.stage": "the lane worker's first half of a turn, on its own "
    "thread: plan pick, dynamic args, ring slot or device_put, launch "
    "(server/coalesce; n = riders; continues the first rider's trace)",
    "lane.finish": "the lane worker's second half of a turn: the wait "
    "for the device (counter tpu.fetch_wait_us), result fetch, "
    "materialize, to_dicts, deliver; parent of coalesce.dispatch",
    "snapshot.delta.apply": "one CDC delta batch applied device-side "
    "to a maintained snapshot (storage/deltas: packed scatter "
    "segments, no re-upload)",
    "snapshot.compact": "epoch compaction: slabs folded back into a "
    "clean CSR (rebuild + optional content-addressed epoch persist)",
    "cdc.catchup": "changefeed catch-up read: WAL entries above a "
    "consumer's cursor decoded to events",
    "cdc.push": "one changefeed delivery (binary push frame or HTTP "
    "/changes long-poll response)",
    "watchdog.tick": "one health-watchdog alert-rule evaluation round "
    "(obs/watchdog; never on the query hot path)",
    "workload.run": "one closed-loop traffic-simulator run "
    "(workloads/driver.TrafficSim: sessions + chaos + settle)",
    "workload.session": "one simulated client session's closed-loop "
    "op sequence (HTTP or binary transport)",
    "slo.evaluate": "one SLO-verdict evaluation over a run window "
    "(obs/slo: stats-table deltas + alert state + burn policy)",
    "timeline.overlap": "one overlap-accounting pass over the flight "
    "recorder's recent window (obs/timeline: scrape-time gauges, "
    "the alert rule's signal)",
    "timeline.export": "Chrome-trace/Perfetto export of the flight "
    "recorder window (GET /debug/timeline, debug bundle)",
    "tier.prefetch": "tiered snapshot cold-block upload wave "
    "(storage/tiering: recording fault or dispatch footprint ensure; "
    "recorded as prefetch-kind transfers in the flight recorder)",
    "tier.evict": "tiered snapshot block eviction (owner row cleared, "
    "page recycled under tier_hbm_cap_bytes pressure)",
    "memledger.reconcile": "device-memory ledger reconciliation pass "
    "(obs/memledger: ledger totals diffed against jax.live_arrays — "
    "untracked = instrumentation gap, tracked-but-dead = leak "
    "candidate, dead transients pruned)",
    "devicefault.escalate": "device fault escalation (exec/devicefault: "
    "retries exhausted or persistent fault — quarantine + optional "
    "admission shed; attrs carry stage, kind, relief actions)",
    "audit.shadow": "one shadow-oracle parity audit (exec/audit: "
    "oracle re-execution + digest compare on the background worker; "
    "attrs carry the verdict — parity / diverged / stale)",
    "scrub.sweep": "one budgeted device-state scrub rotation "
    "(storage/scrub: device blocks fetched + re-hashed against "
    "host-truth checksums under scrub_budget_bytes)",
    "scrub.repair": "one scrub repair-ladder walk for a corrupt device "
    "key (storage/scrub: tier-block reload → overlay poison/compact → "
    "full snapshot re-upload; attrs carry the rung taken)",
}

#: dynamically named span families (f-string call sites the literal
#: lint cannot see) — documented here so the catalog stays the one
#: reference for every name shape in the ring
DYNAMIC_FAMILIES: Dict[str, str] = {
    "http.<verb>": "HTTP listener request (server/http_server._traced)",
    "binary.<op>": "binary-protocol op (server/binary_server)",
}

#: call names whose first positional string argument is a span name
SPAN_CALLS = frozenset({"span", "_span", "continue_trace"})


def _literal_span_names(tree: ast.Module) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        if not (isinstance(f, ast.Name) and f.id in SPAN_CALLS):
            continue
        if (
            n.args
            and isinstance(n.args[0], ast.Constant)
            and isinstance(n.args[0].value, str)
        ):
            out.append((n.lineno, n.args[0].value))
    return out


def lint_spans(root: str = None) -> List[str]:
    """Legacy entry point — now a thin shim over the framework pass
    (``orientdb_tpu.analysis``, pass ``spanlint``): shared discovery,
    per-line suppressions, and reporting. Returns problems (empty =
    every literal span name is cataloged and every catalog entry is
    live)."""
    from orientdb_tpu.analysis import core

    rep = core.run(passes=["spanlint"], root=root)
    # the old contract also reported unparsable modules
    return [
        str(f)
        for f in rep.findings
        if f.pass_name in ("spanlint", "parse")
    ]

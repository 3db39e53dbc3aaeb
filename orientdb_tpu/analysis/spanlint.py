"""spanlint — every literal span name is cataloged (migrated from
``obs/spanlint.py`` onto the shared framework).

The profile aggregator groups stages by span NAME and cross-node
traces join on the names both sides emit — a typo'd name in a new
``span("replication.aply")`` silently splits a stage out of every
profile with no test to notice. Every string-literal first argument
of a ``span``/``_span``/``continue_trace`` call must
appear in ``SPAN_CATALOG``, and every catalog entry must be used by
at least one call site (a stale entry is dead documentation).

The catalog itself (and the DYNAMIC_FAMILIES doc for f-string span
names) stays in ``obs/spanlint.py`` — it doubles as the README's
span-name reference; this module is the framework pass over it.
"""

from __future__ import annotations

from typing import Iterable, List, Set

from orientdb_tpu.analysis.core import Finding, SourceTree, register
from orientdb_tpu.obs.spanlint import SPAN_CATALOG, _literal_span_names


@register(
    "spanlint",
    "literal span names are in SPAN_CATALOG; no stale catalog entries",
)
def run_spanlint(tree: SourceTree) -> Iterable[Finding]:
    findings: List[Finding] = []
    used: Set[str] = set()
    for m in tree.modules:
        if m.tree is None:
            continue
        for lineno, name in _literal_span_names(m.tree):
            used.add(name)
            if name not in SPAN_CATALOG:
                findings.append(
                    Finding(
                        "spanlint", m.path, lineno,
                        f"span name {name!r} is not in SPAN_CATALOG "
                        "(obs/spanlint.py) — a typo here silently "
                        "splits profiles and breaks trace joins; add "
                        "the name with a description or fix the call "
                        "site",
                    )
                )
    for name in sorted(SPAN_CATALOG):
        if name not in used:
            findings.append(
                Finding(
                    "spanlint", "orientdb_tpu/obs/spanlint.py", 1,
                    f"SPAN_CATALOG entry {name!r} is used by no call "
                    "site — remove it or fix the spelling at the "
                    "call site",
                )
            )
    return findings

"""critpathlint — every critical-path segment stamp is cataloged.

The critical-path attribution plane (``obs/critpath.py``) aggregates,
renders, and documents decompositions by SEGMENT NAME: the
``GET /stats/critpath`` report, the blame annotation on
``latency_regression`` alerts and the README segment-catalog table
all join on it. A
``segment("marshall")`` typo would silently grow a segment no surface
documents and leave the cataloged name an empty column in every
breakdown — the exact failure mode spanlint/alertlint close for span
and rule names, so this pass applies the same contract to stamp sites:

- every **string-literal** first argument of a ``segment(...)`` /
  ``add_segment(...)`` call under ``orientdb_tpu/`` must appear in
  :data:`~orientdb_tpu.obs.critpath.SEGMENT_CATALOG`;
- every catalog entry must be stamped by at least one call site (a
  stale entry is dead documentation AND a permanently-zero blame
  candidate).

The catalog stays in ``obs/critpath.py`` (it doubles as the README's
segment reference); this module is the framework pass over it. Tests
are exempt — segment names there are fixtures.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Set, Tuple

from orientdb_tpu.analysis.core import Finding, SourceTree, register
from orientdb_tpu.obs.critpath import SEGMENT_CATALOG

#: call names whose first positional string argument is a segment name
STAMP_CALLS = frozenset({"segment", "add_segment"})


def _literal_segment_names(tree: ast.Module) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        name = (
            f.id
            if isinstance(f, ast.Name)
            else f.attr
            if isinstance(f, ast.Attribute)
            else None
        )
        if name not in STAMP_CALLS:
            continue
        if (
            n.args
            and isinstance(n.args[0], ast.Constant)
            and isinstance(n.args[0].value, str)
        ):
            out.append((n.lineno, n.args[0].value))
    return out


@register(
    "critpathlint",
    "literal critical-path segment names are in SEGMENT_CATALOG; no "
    "stale catalog entries",
)
def run_critpathlint(tree: SourceTree) -> Iterable[Finding]:
    findings: List[Finding] = []
    used: Set[str] = set()
    for m in tree.modules:
        if m.tree is None:
            continue
        for lineno, name in _literal_segment_names(m.tree):
            used.add(name)
            if name not in SEGMENT_CATALOG:
                findings.append(
                    Finding(
                        "critpathlint", m.path, lineno,
                        f"segment {name!r} is not in SEGMENT_CATALOG "
                        "(obs/critpath.py) — an uncataloged segment is "
                        "a column no surface documents; add the name "
                        "with a description or fix the stamp",
                    )
                )
    for name in sorted(SEGMENT_CATALOG):
        if name not in used:
            findings.append(
                Finding(
                    "critpathlint", "orientdb_tpu/obs/critpath.py", 1,
                    f"SEGMENT_CATALOG entry {name!r} is stamped by no "
                    "segment()/add_segment() call site — remove it or "
                    "fix the spelling at the stamp",
                )
            )
    return findings

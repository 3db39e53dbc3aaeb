"""The AST-walking framework every analysis pass shares.

One discovery walk (the package tree), one parse per
module, one suppression syntax, one report shape — a new invariant
check is a ~50-line registered function instead of another bespoke
walker with its own discovery and its own test plumbing.

Suppressions are per-line comments naming the pass::

    time.sleep(0.1)  # lint: allow(locklint)

A suppression that fires on nothing is itself a finding (pass
``suppression``) — stale allowances rot into blanket blindness
otherwise. Comments are found with :mod:`tokenize`, so the syntax
appearing inside a string/docstring (like the one above) never counts.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

_ALLOW_RE = re.compile(r"lint:\s*allow\(\s*([a-zA-Z0-9_\-\s,]+?)\s*\)")

#: pseudo-pass name for unused/unknown-suppression findings
SUPPRESSION_PASS = "suppression"


@dataclasses.dataclass(frozen=True)
class Finding:
    """One problem a pass found, anchored to a source line."""

    pass_name: str
    path: str  # repo-relative, posix separators
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.pass_name}] {self.message}"

    def to_dict(self) -> Dict[str, object]:
        return {
            "pass": self.pass_name,
            "path": self.path,
            "line": self.line,
            "message": self.message,
        }


class Module:
    """One source module: path, source, lazily-parsed AST, and the
    per-line suppression table."""

    def __init__(self, path: str, source: str) -> None:
        self.path = path
        self.source = source
        self.parse_error: Optional[SyntaxError] = None
        self._tree: Optional[ast.Module] = None
        self._suppressions: Optional[Dict[int, Set[str]]] = None

    @property
    def tree(self) -> Optional[ast.Module]:
        """The parsed AST, or None when the source does not parse
        (the run surfaces a ``parse`` finding instead of crashing)."""
        if self._tree is None and self.parse_error is None:
            try:
                self._tree = ast.parse(self.source, filename=self.path)
            except SyntaxError as e:
                self.parse_error = e
        return self._tree

    @property
    def suppressions(self) -> Dict[int, Set[str]]:
        """line → pass names allowed on that line."""
        if self._suppressions is None:
            out: Dict[int, Set[str]] = {}
            try:
                toks = tokenize.generate_tokens(
                    io.StringIO(self.source).readline
                )
                for tok in toks:
                    if tok.type != tokenize.COMMENT:
                        continue
                    m = _ALLOW_RE.search(tok.string)
                    if m is None:
                        continue
                    names = {
                        p.strip()
                        for p in m.group(1).split(",")
                        if p.strip()
                    }
                    out.setdefault(tok.start[0], set()).update(names)
            except (tokenize.TokenError, IndentationError, SyntaxError):
                pass  # unparsable source already reports via `parse`
            self._suppressions = out
        return self._suppressions


class SourceTree:
    """The module set one analysis run sees — the real repo
    (:meth:`from_repo`) or synthetic sources for mutation tests
    (:meth:`from_sources`)."""

    def __init__(
        self,
        modules: Sequence[Module],
        root: Optional[str] = None,
        readme: Optional[str] = None,
    ) -> None:
        self.modules = list(modules)
        self.root = root
        self._readme = readme
        self._by_path = {m.path: m for m in self.modules}

    @classmethod
    def from_repo(cls, root: Optional[str] = None) -> "SourceTree":
        """Every ``.py`` under ``orientdb_tpu/``."""
        if root is None:
            root = repo_root()
        files: List[str] = []
        pkg = os.path.join(root, "orientdb_tpu")
        for dirpath, dirs, names in os.walk(pkg):
            dirs.sort()
            for f in sorted(names):
                if f.endswith(".py"):
                    files.append(os.path.join(dirpath, f))
        mods = []
        for path in files:
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path, "r", encoding="utf-8") as fh:
                mods.append(Module(rel, fh.read()))
        return cls(mods, root=root)

    @classmethod
    def from_sources(
        cls, sources: Dict[str, str], readme: str = ""
    ) -> "SourceTree":
        """Synthetic tree for tests: ``{repo-relative path: source}``."""
        return cls(
            [Module(p, s) for p, s in sorted(sources.items())],
            readme=readme,
        )

    @property
    def readme(self) -> str:
        """README.md text ('' when absent — README checks skip)."""
        if self._readme is None:
            text = ""
            if self.root:
                p = os.path.join(self.root, "README.md")
                if os.path.exists(p):
                    with open(p, "r", encoding="utf-8") as fh:
                        text = fh.read()
            self._readme = text
        return self._readme

    def module(self, path: str) -> Optional[Module]:
        return self._by_path.get(path)

    def in_dirs(self, *dirs: str) -> List[Module]:
        """Modules under the named package subdirectories."""
        prefixes = tuple(f"orientdb_tpu/{d}/" for d in dirs)
        return [m for m in self.modules if m.path.startswith(prefixes)]


def repo_root() -> str:
    """The checkout root (parent of the ``orientdb_tpu`` package)."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


@dataclasses.dataclass(frozen=True)
class AnalysisPass:
    name: str
    title: str  # one-line description (--list, README)
    fn: Callable[[SourceTree], Iterable[Finding]]


#: name → pass; populated by the @register decorator at import
PASSES: Dict[str, AnalysisPass] = {}


def register(name: str, title: str):
    def deco(fn: Callable[[SourceTree], Iterable[Finding]]):
        PASSES[name] = AnalysisPass(name, title, fn)
        return fn

    return deco


def load_passes() -> None:
    """Import every pass module (idempotent) so PASSES is complete."""
    from orientdb_tpu.analysis import (  # noqa: F401
        alertlint,
        configlint,
        critpathlint,
        exceptlint,
        iolint,
        jaxlint,
        locklint,
        promlint,
        racelint,
        spanlint,
    )


@dataclasses.dataclass
class Report:
    """One analysis run: unsuppressed findings (the failures),
    suppressed ones (for --json visibility), per-pass counts."""

    findings: List[Finding]
    suppressed: List[Finding]
    counts: Dict[str, int]  # per pass, unsuppressed (zeros included)

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> Dict[str, object]:
        return {
            "ok": self.ok,
            "counts": dict(self.counts),
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
        }


def run(
    tree: Optional[SourceTree] = None,
    passes: Optional[Sequence[str]] = None,
    root: Optional[str] = None,
) -> Report:
    """Run the named passes (default: all) over the tree and fold in
    suppressions. Unknown pass names raise KeyError."""
    load_passes()
    if tree is None:
        tree = SourceTree.from_repo(root)
    # dedupe while preserving order (--pass is repeatable; running a
    # pass twice would double-report its findings)
    names = (
        sorted(PASSES) if passes is None else list(dict.fromkeys(passes))
    )
    raw: List[Finding] = []
    for n in names:
        raw.extend(PASSES[n].fn(tree))
    # a module that does not parse fails the run regardless of pass
    for m in tree.modules:
        m.tree  # force the parse attempt
        if m.parse_error is not None:
            raw.append(
                Finding(
                    "parse", m.path, m.parse_error.lineno or 1,
                    f"unparsable: {m.parse_error.msg}",
                )
            )
    fired: Set[Tuple[str, int, str]] = set()
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    for f in raw:
        mod = tree.module(f.path)
        allowed = mod.suppressions.get(f.line, set()) if mod else set()
        if f.pass_name in allowed:
            suppressed.append(f)
            fired.add((f.path, f.line, f.pass_name))
        else:
            findings.append(f)
    # unused / unknown suppressions (only for the passes that ran:
    # a single-pass run must not flag other passes' allowances)
    selected = set(names)
    for m in tree.modules:
        for line in sorted(m.suppressions):
            for p in sorted(m.suppressions[line]):
                if p == SUPPRESSION_PASS:
                    findings.append(
                        Finding(
                            SUPPRESSION_PASS, m.path, line,
                            "suppression findings cannot themselves "
                            "be suppressed — remove this allow()",
                        )
                    )
                elif p not in PASSES:
                    findings.append(
                        Finding(
                            SUPPRESSION_PASS, m.path, line,
                            f"suppression names unknown pass {p!r}",
                        )
                    )
                elif p in selected and (m.path, line, p) not in fired:
                    findings.append(
                        Finding(
                            SUPPRESSION_PASS, m.path, line,
                            f"unused suppression: no {p} finding on "
                            "this line — remove the stale allow()",
                        )
                    )
    findings.sort(key=lambda f: (f.path, f.line, f.pass_name))
    suppressed.sort(key=lambda f: (f.path, f.line, f.pass_name))
    counts = {n: 0 for n in names}
    for f in findings:
        counts[f.pass_name] = counts.get(f.pass_name, 0) + 1
    return Report(findings=findings, suppressed=suppressed, counts=counts)

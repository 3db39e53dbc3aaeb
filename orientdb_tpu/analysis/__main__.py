"""CLI: ``python -m orientdb_tpu.analysis [--json] [--pass NAME]
[--baseline PATH]``.

Exit status 0 when every pass is clean (no unsuppressed findings),
1 otherwise — the same gate ``tests/test_analysis.py`` enforces
tier-1.

``--baseline PATH`` is the adopt-in-a-dirty-tree mode CI wants: the
first run snapshots the current findings to PATH (exit 0 even when
findings exist — they are now the accepted debt); later runs compare
and exit 1 only on NEW findings, listing exactly those. Fixed findings
are reported so the snapshot can be re-tightened with
``--write-baseline``. Comparison keys are (pass, path, message) — line
numbers drift with every edit and would make the snapshot useless.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

from orientdb_tpu.analysis import core


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m orientdb_tpu.analysis",
        description="run the static-analysis passes over the tree",
    )
    p.add_argument(
        "--json", action="store_true",
        help="machine-readable report on stdout",
    )
    p.add_argument(
        "--list", action="store_true",
        help="list registered passes and exit",
    )
    p.add_argument(
        "--pass", dest="passes", action="append", metavar="NAME[,NAME]",
        help="run only these passes (repeatable and/or "
        "comma-separated; default: all)",
    )
    p.add_argument(
        "--root", default=None,
        help="repo root to scan (default: this checkout)",
    )
    p.add_argument(
        "--baseline", metavar="PATH", default=None,
        help="finding snapshot: written when PATH is missing, "
        "compared otherwise (exit 1 only on NEW findings)",
    )
    p.add_argument(
        "--write-baseline", action="store_true",
        help="rewrite the --baseline snapshot from this run",
    )
    args = p.parse_args(argv)
    core.load_passes()
    if args.list:
        for name in sorted(core.PASSES):
            print(f"{name:12s} {pass_description(name)}")
        return 0
    if args.passes:
        # `--pass a,b --pass c` and `--pass a --pass b` are the same
        args.passes = [
            n.strip()
            for chunk in args.passes
            for n in chunk.split(",")
            if n.strip()
        ]
        unknown = [n for n in args.passes if n not in core.PASSES]
        if unknown:
            print(f"unknown pass(es): {', '.join(unknown)}",
                  file=sys.stderr)
            return 2
    report = core.run(passes=args.passes, root=args.root)
    if args.baseline:
        return _baseline(report, args)
    if args.json:
        json.dump(report.to_dict(), sys.stdout, indent=1,
                  sort_keys=True)
        print()
    else:
        for f in report.findings:
            print(f)
        total = len(report.findings)
        counts = ", ".join(
            f"{n}={c}" for n, c in sorted(report.counts.items())
        )
        print(
            f"{'CLEAN' if report.ok else 'FAIL'}: {total} unsuppressed "
            f"finding(s) [{counts}] "
            f"({len(report.suppressed)} suppressed)"
        )
    return 0 if report.ok else 1


def pass_description(name: str) -> str:
    """One-line description of a registered pass, pulled from its
    module docstring (the source of truth a reader lands on) — every
    pass module must carry one (tier-1 asserts it)."""
    import importlib
    import sys as _sys

    fn = core.PASSES[name].fn
    mod = _sys.modules.get(fn.__module__) or importlib.import_module(
        fn.__module__
    )
    doc = (mod.__doc__ or "").strip()
    if doc:
        return doc.splitlines()[0].strip()
    return core.PASSES[name].title


_LINE_REF = re.compile(r"\bline \d+\b")


def _key(d) -> tuple:
    """(pass, path, message) with embedded line references blanked:
    several passes anchor their prose to other lines ("acquired line
    50"), which would drift on unrelated edits just like the excluded
    line field."""
    return (d["pass"], d["path"], _LINE_REF.sub("line ?", d["message"]))


def _baseline(report: "core.Report", args) -> int:
    cur = [f.to_dict() for f in report.findings]
    if args.write_baseline or not os.path.exists(args.baseline):
        from orientdb_tpu.storage.durability import atomic_write

        atomic_write(
            args.baseline,
            json.dumps(
                {"findings": cur}, indent=1, sort_keys=True
            ).encode(),
        )
        if args.json:
            json.dump(
                {"written": True, "baselined": len(cur)},
                sys.stdout, indent=1, sort_keys=True,
            )
            print()
        else:
            print(
                f"baseline written: {len(cur)} finding(s) -> "
                f"{args.baseline}"
            )
        return 0
    with open(args.baseline) as f:
        base = json.load(f).get("findings", [])
    # multisets: two same-message findings in one file must not hide
    # behind a single baselined one
    have = collections.Counter(_key(d) for d in base)
    new = []
    for d in cur:
        k = _key(d)
        if have[k] > 0:
            have[k] -= 1
        else:
            new.append(d)
    fixed = sum(have.values())
    if args.json:
        json.dump(
            {
                "ok": not new,
                "new": new,
                "fixed": fixed,
                "carried": len(cur) - len(new),
                "baselined": len(base),
            },
            sys.stdout, indent=1, sort_keys=True,
        )
        print()
        return 1 if new else 0
    for d in new:
        print(
            f"NEW: {d['path']}:{d['line']}: [{d['pass']}] {d['message']}"
        )
    print(
        f"baseline {args.baseline}: {len(new)} new, {fixed} fixed, "
        f"{len(cur) - len(new)} carried "
        f"({len(base)} baselined)"
        + (
            " — re-tighten with --write-baseline"
            if fixed and not new
            else ""
        )
    )
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())

"""Bench-artifact diff: compare two ``BENCH_DETAIL_r{N}.json`` rounds.

The bench trajectory so far is raw JSON files — judging round N against
round M meant eyeballing two trees. This tool makes the comparison a
command with a machine-readable verdict (the standalone twin of
``bench.py --gate``, which only gates the CURRENT run):

    python -m orientdb_tpu.tools.perfdiff BENCH_DETAIL_r12.json \
        BENCH_DETAIL_r14.json [--json] [--tol 0.55] [--ms-tol 0.85] \
        [--overlap-tol 0.2] [--hbm-tol 1.5]

Compared signals (the bench gate's two, plus overlap and peak HBM):

- **q/s leaves** — every ``*qps`` number under ``extras`` (and the
  ``ldbc_is`` per-query families) plus the headline ``value``; a drop
  below ``--tol`` × base is a regression (default 0.55: the wall
  clock is the noisy signal);
- **phase-split ms leaves** — ``device_ms``/``host_ms`` per workload;
  the STABLE signal (device time does not ride the host's clock), gated at
  ``--ms-tol`` (default 0.85), sub-0.5 ms bases skipped as jitter;
- **overlap metrics** (once both rounds carry them — the obs/timeline
  ``overlap`` blocks in ``concurrent_sessions``, per-shard
  ``mesh_scaling`` records, and the headline tier's
  ``headline_overlap`` block, ROADMAP item 4's named acceptance
  leaves): device-idle fraction RISING or transfer-hidden fraction
  FALLING by more than ``--overlap-tol`` absolute (default 0.2) is a
  regression — the overlap machinery stopped hiding work even if
  wall-clock noise masks it. The tolerance is ABSOLUTE (not a ratio)
  because the fractions live in [0, 1]: a 0.2 swing is one fifth of
  the whole scale, far past scheduler jitter (~0.02), while ratio
  gates on near-zero idle fractions would trip on noise;
- **critical-path segment leaves** (once both rounds carry the
  obs/critpath ``critpath`` extras block — per-workload per-segment ms
  from the headline tier): gated with the phase-split discipline —
  ``--ms-tol`` ratio (default 0.85: current must stay under
  base/0.85), sub-``ms_floor`` (0.5 ms) bases skipped as jitter — so
  a regression names the SEGMENT that grew, not just the workload;
- **peak-HBM leaves** (once both rounds carry the obs/memledger
  ``memory`` evidence record): the attributed device-memory peak and
  each owner kind's peak; growth past ``--hbm-tol`` × base (default
  1.5) is a regression — a perf win that silently costs half again as
  much HBM is not a win. Sub-64 KiB bases are skipped as allocator
  noise.

Output: one JSON document on stdout — ``verdict`` ("pass" |
"regression"), per-signal regression/improvement lists, and the
headline ratio. Exit code 0 = pass, 2 = regression (the bench gate's
convention), 1 = unreadable input. ``--json`` keeps stdout pure JSON;
without it a human summary also prints to stderr.

Accepts either the detail-artifact shape (``{"value", "extras": ...}``)
or a driver-recorded ``BENCH_r{N}.json`` wrapper (``{"parsed": ...}``).
"""

from __future__ import annotations

import json
import sys
from typing import Dict, Iterator, List, Optional, Tuple


def _load(path: str) -> Optional[Dict]:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"perfdiff: cannot read {path}: {e}", file=sys.stderr)
        return None
    if isinstance(doc, dict):
        doc = doc.get("parsed") or doc
    if not isinstance(doc, dict):
        print(f"perfdiff: {path} holds no result object", file=sys.stderr)
        return None
    return doc


def qps_leaves(d: Dict, prefix: str = "") -> Iterator[Tuple[str, float]]:
    """Every throughput leaf under an extras tree (the bench gate's
    walk: ``*qps`` keys anywhere, every numeric leaf under ldbc_is)."""
    for k, v in (d or {}).items():
        if isinstance(v, dict):
            yield from qps_leaves(v, f"{prefix}{k}.")
        elif isinstance(v, (int, float)) and (
            k.endswith("qps")
            or prefix.startswith("ldbc_is")
            or prefix.endswith("ldbc_is.")
        ):
            yield prefix + k, float(v)


def ms_leaves(d: Dict) -> Iterator[Tuple[str, float]]:
    for wl, split in (d or {}).items():
        if not isinstance(split, dict):
            continue
        for f in ("device_ms", "host_ms"):
            v = split.get(f)
            if isinstance(v, (int, float)):
                yield f"{wl}.{f}", float(v)


def overlap_leaves(extras: Dict) -> Iterator[Tuple[str, float]]:
    """(metric path, value) for every overlap fraction a round
    recorded: the concurrent_sessions block's and each mesh_scaling
    shard count's device-idle / transfer-hidden numbers."""

    def emit(tag: str, ov: Dict) -> Iterator[Tuple[str, float]]:
        if not isinstance(ov, dict) or not ov.get("records"):
            return
        idle = ov.get("device_idle_fraction")
        if isinstance(idle, (int, float)):
            yield f"{tag}.device_idle_fraction", float(idle)
        tr = ov.get("transfer")
        hidden = (
            tr.get("transfer_hidden_fraction")
            if isinstance(tr, dict)
            else ov.get("transfer_hidden_fraction")
        )
        if isinstance(hidden, (int, float)):
            yield f"{tag}.transfer_hidden_fraction", float(hidden)

    conc = (extras.get("concurrent_sessions") or {}).get("overlap")
    if conc:
        yield from emit("concurrent_sessions", conc)
    # the headline tier's own overlap block (ROADMAP item 4's named
    # acceptance leaves): device-idle / transfer-hidden over the
    # headline trio's dispatches
    head = extras.get("headline_overlap")
    if head:
        yield from emit("headline", head)
    for rec in extras.get("mesh_scaling") or []:
        if isinstance(rec, dict) and isinstance(rec.get("overlap"), dict):
            yield from emit(
                f"mesh_scaling.{rec.get('shards', '?')}", rec["overlap"]
            )


def segment_leaves(extras: Dict) -> Iterator[Tuple[str, float]]:
    """(metric path, ms) for the critical-path segment breakdown a
    round carried (the obs/critpath ``critpath`` extras block:
    ``{workload: {segment: ms_per_query}}``)."""
    for wl, segs in sorted((extras.get("critpath") or {}).items()):
        if not isinstance(segs, dict):
            continue
        for seg, v in sorted(segs.items()):
            if isinstance(v, (int, float)):
                yield f"critpath.{wl}.{seg}", float(v)


def hbm_leaves(extras: Dict) -> Iterator[Tuple[str, float]]:
    """(metric path, bytes) for the device-memory record a round
    carried (the obs/memledger ``memory`` evidence block): the
    attributed peak plus each owner kind's peak."""
    mem = extras.get("memory")
    if not isinstance(mem, dict):
        return
    v = mem.get("peak_bytes")
    if isinstance(v, (int, float)):
        yield "memory.peak_bytes", float(v)
    for kind, pv in sorted((mem.get("peak_by_owner") or {}).items()):
        if isinstance(pv, (int, float)):
            yield f"memory.peak.{kind}", float(pv)


def degraded_round(doc: Optional[Dict]) -> bool:
    """True when a round's evidence records degraded-mode dispatches —
    quarantine-driven oracle fallbacks, admission sheds, or plan
    quarantines from the device fault domain (the per-round
    ``device_faults`` evidence block, exec/devicefault) — or
    correctness-plane findings: shadow-oracle parity divergences or
    scrub repairs (the ``parity_audit`` block, exec/audit). A chaos or
    diverged round measures the ladder, not the fast path:
    ``bench._last_good_round`` skips these so one can never become the
    regression baseline."""
    ex = (doc or {}).get("extras") or {}
    df = ex.get("device_faults")
    if isinstance(df, dict) and any(
        int(df.get(k) or 0) > 0
        for k in ("oracle_served", "sheds", "quarantines")
    ):
        return True
    pa = ex.get("parity_audit")
    if isinstance(pa, dict) and any(
        int(pa.get(k) or 0) > 0
        for k in ("diverged", "scrub_corruptions", "scrub_repairs")
    ):
        return True
    return False


def diff(
    base: Dict,
    cur: Dict,
    tol: float = 0.55,
    ms_tol: float = 0.85,
    overlap_tol: float = 0.2,
    ms_floor: float = 0.5,
    hbm_tol: float = 1.5,
    hbm_floor: float = float(1 << 16),
) -> Dict:
    """The comparison document (pure function — tests drive it on
    synthetic rounds)."""
    b_ex, c_ex = base.get("extras") or {}, cur.get("extras") or {}
    b_q = dict(qps_leaves(b_ex))
    c_q = dict(qps_leaves(c_ex))
    b_q["headline"] = float(base.get("value") or 0.0)
    c_q["headline"] = float(cur.get("value") or 0.0)
    qps_reg: List[Dict] = []
    qps_imp: List[Dict] = []
    compared = 0
    for name, bv in sorted(b_q.items()):
        cv = c_q.get(name)
        if cv is None or bv <= 0:
            continue
        compared += 1
        row = {
            "metric": name,
            "base": bv,
            "cur": cv,
            "ratio": round(cv / bv, 3),
        }
        if cv < bv * tol:
            qps_reg.append(row)
        elif bv < cv * tol:  # the same envelope, in the other direction
            qps_imp.append(row)
    b_ms = dict(ms_leaves(b_ex.get("phase_split_ms_per_query") or {}))
    c_ms = dict(ms_leaves(c_ex.get("phase_split_ms_per_query") or {}))
    ms_reg: List[Dict] = []
    ms_imp: List[Dict] = []
    for name, bv in sorted(b_ms.items()):
        cv = c_ms.get(name)
        if cv is None or bv < ms_floor:
            continue
        compared += 1
        row = {
            "metric": name,
            "base": bv,
            "cur": cv,
            "ratio": round(cv / bv, 3),
        }
        if cv > bv / ms_tol:
            ms_reg.append(row)
        elif cv < bv * ms_tol:
            ms_imp.append(row)
    b_ov = dict(overlap_leaves(b_ex))
    c_ov = dict(overlap_leaves(c_ex))
    ov_reg: List[Dict] = []
    ov_deltas: Dict[str, Dict] = {}
    for name in sorted(set(b_ov) & set(c_ov)):
        bv, cv = b_ov[name], c_ov[name]
        delta = round(cv - bv, 4)
        ov_deltas[name] = {"base": bv, "cur": cv, "delta": delta}
        worse = (
            delta > overlap_tol
            if name.endswith("device_idle_fraction")
            else delta < -overlap_tol
        )
        if worse:
            ov_reg.append(
                {"metric": name, "base": bv, "cur": cv, "delta": delta}
            )
    b_seg = dict(segment_leaves(b_ex))
    c_seg = dict(segment_leaves(c_ex))
    seg_reg: List[Dict] = []
    seg_imp: List[Dict] = []
    for name, bv in sorted(b_seg.items()):
        cv = c_seg.get(name)
        if cv is None or bv < ms_floor:
            continue
        compared += 1
        row = {
            "metric": name,
            "base": bv,
            "cur": cv,
            "ratio": round(cv / bv, 3),
        }
        if cv > bv / ms_tol:
            seg_reg.append(row)
        elif cv < bv * ms_tol:
            seg_imp.append(row)
    b_hbm = dict(hbm_leaves(b_ex))
    c_hbm = dict(hbm_leaves(c_ex))
    hbm_reg: List[Dict] = []
    hbm_imp: List[Dict] = []
    for name, bv in sorted(b_hbm.items()):
        cv = c_hbm.get(name)
        if cv is None or bv < hbm_floor:
            continue
        compared += 1
        row = {
            "metric": name,
            "base": bv,
            "cur": cv,
            "ratio": round(cv / bv, 3),
        }
        if cv > bv * hbm_tol:
            hbm_reg.append(row)
        elif cv < bv / hbm_tol:
            hbm_imp.append(row)
    regressions = (
        [dict(r, kind="qps") for r in qps_reg]
        + [dict(r, kind="ms") for r in ms_reg]
        + [dict(r, kind="overlap") for r in ov_reg]
        + [dict(r, kind="segment") for r in seg_reg]
        + [dict(r, kind="hbm") for r in hbm_reg]
    )
    hb, hc = b_q["headline"], c_q["headline"]
    return {
        "headline": {
            "base": hb,
            "cur": hc,
            "ratio": round(hc / hb, 3) if hb else None,
        },
        "compared": compared,
        "qps": {"regressions": qps_reg, "improvements": qps_imp},
        "ms": {"regressions": ms_reg, "improvements": ms_imp},
        "overlap": {"deltas": ov_deltas, "regressions": ov_reg},
        "segments": {"regressions": seg_reg, "improvements": seg_imp},
        "hbm": {"regressions": hbm_reg, "improvements": hbm_imp},
        "regressions": regressions,
        "verdict": "regression" if regressions else "pass",
        "thresholds": {
            "tol": tol,
            "ms_tol": ms_tol,
            "overlap_tol": overlap_tol,
            "hbm_tol": hbm_tol,
        },
    }


def _human(rep: Dict, base_path: str, cur_path: str) -> None:
    h = rep["headline"]
    print(
        f"perfdiff {base_path} -> {cur_path}: headline "
        f"{h['base']} -> {h['cur']} "
        f"({h['ratio'] if h['ratio'] is not None else 'n/a'}x), "
        f"{rep['compared']} metrics compared",
        file=sys.stderr,
    )
    for r in rep["regressions"]:
        print(
            f"  REGRESSION [{r['kind']}] {r['metric']}: "
            f"{r['base']} -> {r['cur']}",
            file=sys.stderr,
        )
    for kind in ("qps", "ms", "segments", "hbm"):
        for r in rep[kind]["improvements"]:
            print(
                f"  improvement [{kind}] {r['metric']}: "
                f"{r['base']} -> {r['cur']}",
                file=sys.stderr,
            )
    print(f"verdict: {rep['verdict']}", file=sys.stderr)


_USAGE = (
    "usage: python -m orientdb_tpu.tools.perfdiff "
    "BASE_DETAIL.json CUR_DETAIL.json [--json] [--tol X] "
    "[--ms-tol X] [--overlap-tol X] [--hbm-tol X]"
)


def main(argv: List[str]) -> int:
    vals = {"tol": 0.55, "ms-tol": 0.85, "overlap-tol": 0.2, "hbm-tol": 1.5}
    pos: List[str] = []
    as_json = False
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--json":
            as_json = True
        elif a.startswith("--"):
            name, _, raw = a[2:].partition("=")
            if not raw and i + 1 < len(argv):
                i += 1
                raw = argv[i]
            if name not in vals:
                print(_USAGE, file=sys.stderr)
                return 1
            try:
                vals[name] = float(raw)
            except ValueError:
                print(_USAGE, file=sys.stderr)
                return 1
        else:
            pos.append(a)
        i += 1
    if len(pos) != 2:
        print(_USAGE, file=sys.stderr)
        return 1
    base = _load(pos[0])
    cur = _load(pos[1])
    if base is None or cur is None:
        return 1
    rep = diff(
        base,
        cur,
        tol=vals["tol"],
        ms_tol=vals["ms-tol"],
        overlap_tol=vals["overlap-tol"],
        hbm_tol=vals["hbm-tol"],
    )
    rep["base"] = pos[0]
    rep["cur"] = pos[1]
    if not as_json:
        _human(rep, pos[0], pos[1])
    print(json.dumps(rep, indent=1, sort_keys=True))
    return 2 if rep["verdict"] == "regression" else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

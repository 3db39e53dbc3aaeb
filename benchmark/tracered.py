"""From a profiler trace to numbers: device busy time as the union of
the intervals in which an operation ran, per-operation self time, and
the idle gaps, each attributed to what the host was doing in it.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain form
(``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns], ...]}]}]}``), ``reduce`` works on that form alone, so the
reduction is tested on a small recorded trace without a chip
(``benchmark/tests/data``).

What a v5e trace holds (looked at by hand, PR 26): a plane
``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` has one event per
executed HLO operation (named by its whole HLO text, which ``op_name``
cuts to the instruction and its result's shape; asynchronous copies sit
on a line of their own, ``Async XLA Ops``, and are not counted as busy)
and whose ``XLA Modules`` line has one per executed program; host
threads, the Python frames of the server among them, are lines of the
plane ``/host:CPU``. All planes share one clock. The traced window is
what lies between the profiler's own ``start_trace`` and ``stop_trace``
frames, where the trace has them.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: host events that are compilation (checked against the plan counters)
COMPILE_EVENT = re.compile(r"(?i)(backend_compile|XlaCompile|TpuCompile|compile_or_get_cached)")
HLO_TEXT = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])")
#: the profiler's own calls as the Python tracer names them: while they run
#: the tracer holds the interpreter and the server stands still (7.5 s of
#: ``stop_trace`` after 12 s of a 215 qps window, chip run, PR 26), so the
#: traced window is what lies between them
TRACER_START = re.compile(r"profiler\.py:\d+ start_trace$")
TRACER_STOP = re.compile(r"profiler\.py:\d+ stop_trace$")
#: how many of the longest gaps are attributed to host activity
GAPS_ATTRIBUTED = 400


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append(
                {
                    "name": line.name,
                    "events": [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events
                    ],
                }
            )
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(text: str) -> str:
    """``%fusion.4 = pred[1967163]{0:T(1024)} fusion(...)`` → ``fusion.4 pred[1967163]``."""
    m = HLO_TEXT.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:80]


def _union(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merged intervals of (starts, ends), ascending."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [s.size - 1]])
    return s[first], reach[last]


def _self_times(events: List[list]) -> Dict[str, float]:
    """Per name, duration minus what nested events cover (a ``while``
    holds its body's operations on the same line)."""
    out: Dict[str, float] = {}
    stack: list = []  # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= dur
        stack.append([name, end, dur])
    for done in stack:
        out[done[0]] = out.get(done[0], 0.0) + done[2]
    return out


def _device_lines(plane: dict) -> List[dict]:
    named = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
    if named:
        return named
    busiest = max(plane["lines"], key=lambda ln: len(ln["events"]), default=None)
    return [busiest] if busiest and busiest["events"] else []


def reduce(trace: dict) -> dict:
    """Busy and idle of the traced window, averaged over the chips that
    ran anything; the operations that took most device time; the longest
    idle gaps by what the host was doing."""
    t_lo, t_hi = float("inf"), float("-inf")
    for p in trace["planes"]:
        for ln in p["lines"]:
            for e in ln["events"]:
                t_lo = min(t_lo, e[1])
                t_hi = max(t_hi, e[1] + e[2])
    if t_hi < t_lo:
        raise ValueError("the trace holds no event")

    host = [
        e
        for p in trace["planes"]
        if p["name"].startswith("/host:")
        for ln in p["lines"]
        for e in ln["events"]
        if e[2] > 0
    ]
    t_lo = max([t_lo] + [e[1] + e[2] for e in host if TRACER_START.search(e[0])])
    t_hi = min([t_hi] + [e[1] for e in host if TRACER_STOP.search(e[0])])
    if t_hi <= t_lo:
        raise ValueError("the trace holds nothing between start_trace and stop_trace")
    window_ns = t_hi - t_lo
    h_start = np.array([e[1] for e in host], float)
    h_end = h_start + np.array([e[2] for e in host], float)

    busy_ns: List[float] = []
    op_self: Dict[str, float] = {}
    gap_by: Dict[str, float] = {}
    n_ops = 0
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        events = [
            [e[0], max(e[1], t_lo), min(e[1] + e[2], t_hi) - max(e[1], t_lo)]
            for ln in _device_lines(plane)
            for e in ln["events"]
            if e[1] < t_hi and e[1] + e[2] > t_lo
        ]
        if not events:
            continue
        n_ops += len(events)
        starts = np.array([e[1] for e in events], float)
        ends = starts + np.array([e[2] for e in events], float)
        ms, me = _union(starts, ends)
        busy_ns.append(float((me - ms).sum()))
        for name, secs in _self_times(events).items():
            name = op_name(name)
            op_self[name] = op_self.get(name, 0.0) + secs
        # gaps: before the first operation, between merged runs, after the last
        g_start = np.concatenate([[t_lo], me])
        g_end = np.concatenate([ms, [t_hi]])
        g_len = g_end - g_start
        longest = np.argsort(-g_len)[:GAPS_ATTRIBUTED]
        attributed = 0.0
        for j in longest:
            if g_len[j] <= 0:
                continue
            name = "unattributed"
            if h_start.size:
                overlap = np.minimum(h_end, g_end[j]) - np.maximum(h_start, g_start[j])
                # the innermost host span that covers most of the gap: an
                # outer frame (a whole request) would cover every gap alike
                covering = np.flatnonzero(overlap >= 0.5 * g_len[j])
                if covering.size:
                    k = int(covering[np.argmin((h_end - h_start)[covering])])
                else:
                    k = int(np.argmax(overlap))
                if overlap[k] > 0:
                    name = "host:" + host[k][0]
            gap_by[name] = gap_by.get(name, 0.0) + float(g_len[j])
            attributed += float(g_len[j])
        rest = float(g_len[g_len > 0].sum()) - attributed
        if rest > 0:
            gap_by["shorter gaps"] = gap_by.get("shorter gaps", 0.0) + rest
    chips = len(busy_ns)
    compiles = sum(1 for e in host if COMPILE_EVENT.search(e[0]))

    def top(d: Dict[str, float]) -> list:
        return [
            [k, v / 1e9 / max(chips, 1)]
            for k, v in sorted(d.items(), key=lambda kv: -kv[1])
        ]

    busy_s = (sum(busy_ns) / chips / 1e9) if chips else 0.0
    return {
        "busy_s": busy_s,
        "window_s": window_ns / 1e9,
        "chips": chips,
        "device_ops": top(op_self),
        "idle_gaps": top(gap_by),
        "compile_events": compiles,
        "summary": {
            "chips": chips,
            "device_events": n_ops,
            "host_events": len(host),
            "busy_s": round(busy_s, 4),
            "window_s": round(window_ns / 1e9, 4),
        },
    }

"""tick_stall_share — the watchdog's tick as a share of the window.

layer: watchdog tick (obs/watchdog, storage/scrub); source: program_span;
moves: qps. 100 × (Δ``span.watchdog.tick.us`` + Δ``span.scrub.sweep.us``)
/ 1e6 / the window's span: the rule evaluation and the scrub before it,
each a span of its own on the ``health-watchdog`` thread, folded into
counters at exit (``obs/trace``). The thread holds the interpreter for
most of both, and the lanes' threads wait (PERF.md 6)."""


def read(obs):
    c = obs["counters"]
    us = c.get("span.watchdog.tick.us", 0) + c.get("span.scrub.sweep.us", 0)
    span_s = obs["window"]["span_s"]
    if us <= 0 or span_s <= 0:
        return None
    return 100.0 * us / 1e6 / span_s

"""tick_cpu_share — the watchdog thread's CPU time as a share of the window.

layer: watchdog tick (obs/watchdog, storage/scrub); source: program_counter;
moves: qps. 100 × Δ``thread.watchdog.cpu_us`` / 1e6 / the window's span: the
CPU clock of the ``health-watchdog`` thread, which runs the scrub and the
alert rules (``obs/trace.roles``). Beside ``tick_stall_share``, the same
thread's spans on the wall, it says whether a tick holds the interpreter for
its whole wall time or waits inside it."""


def read(obs):
    c = obs["counters"]
    us = c.get("thread.watchdog.cpu_us", 0)
    span_s = obs["window"]["span_s"]
    if us <= 0 or span_s <= 0:
        return None
    return 100.0 * us / 1e6 / span_s

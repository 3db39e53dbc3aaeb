"""tick_evaluate_ms — one round of the alert rules, mean over the window.

layer: watchdog tick (obs/watchdog, storage/scrub); source: program_span;
moves: qps. Δ``span.watchdog.tick.us`` / Δ``span.watchdog.tick.n`` / 1000:
the ``watchdog.tick`` span is ``alerts.engine.evaluate`` alone (a
registry snapshot, which runs the gauge providers, then the rules); the
scrub has a span of its own and is in ``tick_stall_share``."""


def read(obs):
    c = obs["counters"]
    n = c.get("span.watchdog.tick.n", 0)
    if n <= 0:
        return None
    return c.get("span.watchdog.tick.us", 0) / n / 1000.0

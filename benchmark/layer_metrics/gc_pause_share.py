"""gc_pause_share — the garbage collector's pauses as a share of the window.

layer: host interpreter (all serving threads); source: program_counter;
moves: qps. 100 × Δ``gc.pause_us`` / 1e6 / the window's span: every
collection of the server's process, timed from its ``start`` to its ``stop``
by the ``gc.callbacks`` hook ``Server.startup`` installs (``obs/trace.gc_clock``;
``gc.collections.gen<N>`` and ``gc.pause_us.gen<N>`` split it by generation).
No thread runs Python during a collection. ``None`` where no collection was
counted: a program without the hook."""


def read(obs):
    c = obs["counters"]
    collections = sum(v for k, v in c.items() if k.startswith("gc.collections.gen"))
    span_s = obs["window"]["span_s"]
    if collections <= 0 or span_s <= 0:
        return None
    return 100.0 * c.get("gc.pause_us", 0) / 1e6 / span_s

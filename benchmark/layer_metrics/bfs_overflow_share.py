"""bfs_overflow_share — searches whose frontier outgrew its buffer.

layer: kernels (ops/csr); source: program_counter; moves: qps.
100 × Δ``bfs.overflow`` / Δ``bfs.queries`` over the window: searches
that needed a second chunk of a level or the dense levels, in percent.
Their answers are exact all the same; their batch pays for them. A
program without the counters reads nothing."""


def read(obs):
    c = obs["counters"]
    if c.get("bfs.queries", 0) <= 0:
        return None
    return 100.0 * c.get("bfs.overflow", 0) / c["bfs.queries"]

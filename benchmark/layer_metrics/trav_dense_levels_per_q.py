"""trav_dense_levels_per_q — levels a whole-graph search ran dense.

layer: kernels (ops/csr); source: program_counter; moves: qps.
Δ``traverse.dense_levels`` / Δ``traverse.queries`` over the window: the
levels whose frontier held more than one in sixteen of the graph's edge
ends (or outgrew the sparse step's buffer) and so read every edge once a
direction. A dense level costs several sparse ones, so a window's rate
follows this number. A program without the counters reads nothing."""


def read(obs):
    c = obs["counters"]
    if c.get("traverse.queries", 0) <= 0:
        return None
    return c.get("traverse.dense_levels", 0) / c["traverse.queries"]

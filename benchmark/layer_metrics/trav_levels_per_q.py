"""trav_levels_per_q — frontiers a whole-graph search expanded.

layer: kernels (ops/csr); source: program_counter; moves: qps.
Δ``traverse.levels`` / Δ``traverse.queries`` over the window: the levels
of a counted breadth-first TRAVERSE (``ops/csr.bfs_levels``), one a
depth that holds a vertex, from the device with the answer. A program
without the counters reads nothing."""


def read(obs):
    c = obs["counters"]
    if c.get("traverse.queries", 0) <= 0:
        return None
    return c.get("traverse.levels", 0) / c["traverse.queries"]

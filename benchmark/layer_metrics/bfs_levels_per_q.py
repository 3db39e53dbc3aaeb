"""bfs_levels_per_q — frontiers a shortest-path search expanded.

layer: kernels (ops/csr); source: program_counter; moves: qps.
Δ``bfs.levels`` / Δ``bfs.queries`` over the window: both ends' lists
count one each, then one per further level from either side, dense
levels among them (``ops/csr.bfs_pair_len``; counted at finish from what
the device returned with the answer). A program without the counters
reads nothing."""


def read(obs):
    c = obs["counters"]
    if c.get("bfs.queries", 0) <= 0:
        return None
    return c.get("bfs.levels", 0) / c["bfs.queries"]

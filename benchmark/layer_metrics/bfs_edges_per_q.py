"""bfs_edges_per_q — frontier edges a shortest-path search read.

layer: kernels (ops/csr); source: program_counter; moves: qps.
Δ``bfs.edges_expanded`` / Δ``bfs.queries`` over the window: the edges
behind every frontier the search expanded (a dense level reads the
whole edge list once a direction), from the device with the answer.
The buffer a level is read into is larger; ``device_busy_ms_per_q``
pays for the buffer, this counts the work. A program without the
counters reads nothing."""


def read(obs):
    c = obs["counters"]
    if c.get("bfs.queries", 0) <= 0:
        return None
    return c.get("bfs.edges_expanded", 0) / c["bfs.queries"]

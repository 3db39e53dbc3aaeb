"""trav_edges_per_q — edge ends a whole-graph search scanned.

layer: kernels (ops/csr); source: program_counter; moves: qps.
Δ``traverse.edges_scanned`` / Δ``traverse.queries`` over the window: 2E
a dense level, the frontier's own edge ends a sparse one. 2E is the
least a whole search can scan (every edge from both ends); what the
buffers of a sparse level hold beyond its ends is paid in
``device_busy_ms_per_q``, this counts the work. A program without the
counters reads nothing."""


def read(obs):
    c = obs["counters"]
    if c.get("traverse.queries", 0) <= 0:
        return None
    return c.get("traverse.edges_scanned", 0) / c["traverse.queries"]

"""views_hit_share — share of requests a materialised view answered.

layer: engine front doors (exec/views); source: program_counter;
moves: qps. Δ``views.hit`` over the window's requests, in
percent. These cells repeat no (statement, parameters) pair, so it
should read 0."""


def read(obs):
    if obs["requests"] <= 0:
        return None
    return 100.0 * obs["counters"].get("views.hit", 0) / obs["requests"]

"""rerecords_per_kq — plans recorded anew per thousand requests.

layer: plan record / replay (exec/tpu_engine); source: program_counter;
moves: qps. Δ(``plan_cache.miss`` +
``plan_cache.overflow_rerecord``) over the window."""


def read(obs):
    if obs["requests"] <= 0:
        return None
    c = obs["counters"]
    n = c.get("plan_cache.miss", 0) + c.get("plan_cache.overflow_rerecord", 0)
    return 1000.0 * n / obs["requests"]

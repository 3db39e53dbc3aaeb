"""compiles_in_window — programs compiled while the window was open.

layer: plan record / replay (exec/tpu_engine); source: program_counter;
moves: qps. Δ(``plan_cache.aot_compile`` +
``plan_cache.group_compile``) over the window, or the compile events the
profiler saw on the host in the traced span where those are more."""


def read(obs):
    c = obs["counters"]
    n = c.get("plan_cache.aot_compile", 0) + c.get("plan_cache.group_compile", 0)
    trace = obs.get("trace")
    if trace is not None:
        n = max(n, trace["compile_events"])
    return n

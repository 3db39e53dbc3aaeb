"""lane_cpu_ms_per_batch — the lane workers' CPU time, per batch.

layer: plan record / replay (exec/tpu_engine); source: program_counter;
moves: qps. Δ``thread.lane.cpu_us`` / Δ``coalesce.batches`` / 1000 over the
window: the CPU clocks of the ``server/coalesce`` lane workers, summed by
``obs/trace.roles`` and read at the window's open and close. Beside
``host_turn_ms_per_batch``, which times the worker's two spans on the wall,
it says how much of the turn the worker ran and how much it waited for the
interpreter."""


def read(obs):
    c = obs["counters"]
    batches = c.get("coalesce.batches", 0)
    us = c.get("thread.lane.cpu_us", 0)
    if batches <= 0 or us <= 0:
        return None
    return us / batches / 1000.0

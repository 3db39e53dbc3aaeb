"""lane_wait_ms_per_q — enqueue to the batch's dispatch, per request.

layer: coalescer lanes (server/coalesce); source: program_span;
moves: qps. Δ``critpath.queue_us`` / Δ``critpath.requests`` /
1000 over the window: the ``queue`` segment (``obs/critpath``), a rider's
stay in its lane from ``submit`` until the worker stages the batch it
rides. Service behind the batch in flight is not in it (that is
``device_compute``, a host wait, and deliberately no metric)."""


def read(obs):
    c = obs["counters"]
    n = c.get("critpath.requests", 0)
    if n <= 0:
        return None
    return c.get("critpath.queue_us", 0) / n / 1000.0

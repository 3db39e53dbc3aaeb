"""marshal_ms_per_q — rows to dicts and dicts to bytes, per request.

layer: result marshal (server/coalesce to_dicts, binary_server encode);
source: program_span; moves: qps. Δ``critpath.marshal_us`` /
Δ``critpath.requests`` / 1000 over the window: the lane worker's
``to_dicts`` of a rider's result and the session's JSON encode of the
reply, both stamped as the ``marshal`` segment (``obs/critpath``)."""


def read(obs):
    c = obs["counters"]
    n = c.get("critpath.requests", 0)
    if n <= 0:
        return None
    return c.get("critpath.marshal_us", 0) / n / 1000.0

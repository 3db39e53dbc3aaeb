"""device_busy_ms_per_q — device time a request costs.

layer: kernels (ops/csr); source: device_trace; moves: qps. The union
of the device-operation intervals in the profiler trace over the
requests answered in the traced span."""


def read(obs):
    trace = obs.get("trace")
    if trace is None or trace["busy_s"] <= 0 or obs["requests_in_trace"] <= 0:
        return None
    return 1000.0 * trace["busy_s"] / obs["requests_in_trace"]

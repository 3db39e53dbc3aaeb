"""device_idle_share — share of the traced span with no operation on
the device.

layer: device; source: device_trace; moves: qps. 1 − busy union over
the traced span, in percent."""


def read(obs):
    trace = obs.get("trace")
    if trace is None or trace["busy_s"] <= 0 or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

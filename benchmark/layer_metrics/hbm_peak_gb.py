"""hbm_peak_gb — peak bytes in use on the fullest device.

layer: device state (ops/device_graph); source: program_counter;
moves: qps. ``memory_stats()["peak_bytes_in_use"]`` after the window."""


def read(obs):
    if obs["hbm_peak_bytes"] <= 0:
        return None
    return obs["hbm_peak_bytes"] / 1e9

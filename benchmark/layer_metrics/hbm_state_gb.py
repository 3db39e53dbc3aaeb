"""hbm_state_gb — the graph's bytes on the fullest device.

layer: device state (ops/device_graph); source: program_counter;
moves: setup_s. ``memory_report()`` per-device bytes after warm-up (the
engine uploads a column when a plan first reads it)."""


def read(obs):
    if obs["hbm_state_bytes"] <= 0:
        return None
    return obs["hbm_state_bytes"] / 1e9

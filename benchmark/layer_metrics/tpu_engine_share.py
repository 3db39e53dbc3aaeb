"""tpu_engine_share — share of the window's calls the device answered.

layer: engine front doors (exec/engine); source: program_counter;
moves: qps. The stats plane's per-fingerprint call counts by engine,
window delta: ``tpu`` calls over all calls, in percent."""


def read(obs):
    calls = {
        k: v for k, v in obs["counters"].items() if k.startswith("engine:")
    }
    total = sum(calls.values())
    if total <= 0:
        return None
    return 100.0 * calls.get("engine:tpu", 0) / total

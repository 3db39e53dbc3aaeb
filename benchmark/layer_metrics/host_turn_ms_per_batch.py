"""host_turn_ms_per_batch — the lane worker's own work, per batch.

layer: plan record / replay (exec/tpu_engine); source: program_span;
moves: qps. (Δ``span.lane.stage.us`` + Δ``span.lane.finish.us`` −
Δ``tpu.fetch_wait_us``) / Δ``coalesce.batches`` / 1000 over the window:
the two spans of the worker's turn (plan pick, dynamic args, ring or
``device_put``, launch; then fetch, materialize, ``to_dicts``, deliver)
less the part of the second in which it only waits for the device
(``exec/tpu_engine._finish_pending``). What is left is what the host
adds between two batches. Never below 0: the wait is rounded on its own."""


def read(obs):
    c = obs["counters"]
    batches = c.get("coalesce.batches", 0)
    spans = c.get("span.lane.stage.us", 0) + c.get("span.lane.finish.us", 0)
    if batches <= 0 or spans <= 0:
        return None
    return max(0, spans - c.get("tpu.fetch_wait_us", 0)) / batches / 1000.0

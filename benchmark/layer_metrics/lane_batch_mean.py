"""lane_batch_mean — queries per batched device dispatch.

layer: coalescer lanes (server/coalesce); source: program_counter;
moves: qps. Δ``coalesce.items`` / Δ``coalesce.batches`` over the window;
where the served path moved only the engine's own pair, Δ``tpu.lane_items``
/ Δ``tpu.lane_dispatch``."""


def read(obs):
    c = obs["counters"]
    for items, batches in (
        ("coalesce.items", "coalesce.batches"),
        ("tpu.lane_items", "tpu.lane_dispatch"),
    ):
        if c.get(batches, 0) > 0:
            return c.get(items, 0) / c[batches]
    return None

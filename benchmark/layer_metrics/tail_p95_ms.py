"""tail_p95_ms — the 95th percentile of the traced run's own window.

layer: watchdog tick (obs/watchdog, storage/scrub); source: host_clock;
moves: qps. The load generator's send and last-byte times, nearest rank
over every request issued. For a cell that is saturated by construction
(a closed loop with no think time over one lane): there the tail swings
with the share of requests a watchdog tick catches (PERF.md 6) and is no
end-to-end metric."""


def read(obs):
    return obs["window"]["latency_p95_ms"]

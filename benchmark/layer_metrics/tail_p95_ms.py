"""tail_p95_ms — the 95th percentile of the traced run's own window.

layer: watchdog tick (obs/watchdog, storage/scrub); source: host_clock;
moves: qps. The load generator's send and last-byte times, nearest rank
over every request issued. No end-to-end metric in either kind of cell
(PERF.md 2): in one that is saturated by construction (a closed loop over
one lane) the tail swings with the share of requests a watchdog tick
catches; in one whose window holds some tens of requests (71 whole-graph
COUNTs: the 95th percentile is the 68th, three samples beyond it) one
cycle that a scrub sweep delays carries it."""


def read(obs):
    return obs["window"]["latency_p95_ms"]

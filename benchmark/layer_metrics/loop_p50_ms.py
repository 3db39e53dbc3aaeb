"""loop_p50_ms — the median latency of the traced run's own window.

layer: coalescer lanes (server/coalesce); source: host_clock; moves: qps.
The load generator's send and last-byte times, nearest rank over every
request issued. For a cell that is saturated by construction (a closed
loop whose pause is a fraction of a read): there Little's law ties the
median to the rate (sessions = qps x (latency + pause)), so the median is
``qps`` under a second name, read on the host's clock, and is no
end-to-end metric (PERF.md 2). What it adds to ``qps`` is how the
latency divides between the lane's batches."""


def read(obs):
    return obs["window"]["latency_p50_ms"]

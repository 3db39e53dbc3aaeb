"""interp_cpu_ms_per_q — every serving thread's CPU time, per request.

layer: host interpreter (all serving threads); source: program_counter;
moves: qps. (Δ``thread.session.cpu_us`` + Δ``thread.lane.cpu_us`` +
Δ``thread.watchdog.cpu_us``) / Δ``critpath.requests`` / 1000 over the window:
the CPU clocks of the session threads, the lane workers and the watchdog
(``obs/trace.roles``). Against 1000 / ``qps``, the wall a request has, it says
whether the one interpreter those threads share is saturated, or whether the
time goes to handing it from thread to thread."""

ROLES = ("session", "lane", "watchdog")


def read(obs):
    c = obs["counters"]
    n = c.get("critpath.requests", 0)
    us = sum(c.get(f"thread.{role}.cpu_us", 0) for role in ROLES)
    if n <= 0 or us <= 0:
        return None
    return us / n / 1000.0

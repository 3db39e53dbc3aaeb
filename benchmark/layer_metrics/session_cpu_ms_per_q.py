"""session_cpu_ms_per_q — the session threads' CPU time, per request.

layer: wire (server/binary_server, client/remote); source: program_counter;
moves: qps. Δ``thread.session.cpu_us`` / Δ``critpath.requests`` / 1000 over
the window: the CPU clocks of the binary server's session threads (and of a
pipelined session's pool), summed by ``obs/trace.roles`` and read at the
window's open and close, never stamped per request. Unlike ``wire_ms_per_q``
it holds no wait for the interpreter; it holds the kernel's side of a
``sendall`` and ``recv``, which is why it is given per request and not as a
share of the wall."""


def read(obs):
    c = obs["counters"]
    n = c.get("critpath.requests", 0)
    us = c.get("thread.session.cpu_us", 0)
    if n <= 0 or us <= 0:
        return None
    return us / n / 1000.0

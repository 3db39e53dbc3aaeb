"""wire_ms_per_q — the server's share of the wire, per request.

layer: wire (server/binary_server, client/remote); source: program_span;
moves: qps. (Δ``critpath.parse_us`` + Δ``critpath.flush_us``)
/ Δ``critpath.requests`` / 1000 over the window: the frame's decode and
the reply's ``sendall``, as ``obs/critpath`` stamps them on the session's
thread and folds them into counters at commit. The kernel's and the
client's side of the socket are not in it."""


def read(obs):
    c = obs["counters"]
    n = c.get("critpath.requests", 0)
    if n <= 0:
        return None
    return (c.get("critpath.parse_us", 0) + c.get("critpath.flush_us", 0)) / n / 1000.0

"""The load generator: a process of its own that never imports JAX.

The chip belongs to the server's process; this one only needs
``orientdb_tpu.client.remote``. Its session threads therefore share no
interpreter lock with the server, and a busy generator cannot be read
as a slow server.

Protocol, one JSON object per line: the parent writes
``{"op": "init", "url", "user", "password", "plan"}``, then any number
of ``{"op": "run", "seconds", "base": [per-shape pool offset]}`` or
``{"op": "burst", "shape", "k", "base"}`` and at last ``{"op": "quit"}``;
the generator answers each with one line. A run is a closed loop: every
session walks the plan's block from its own offset (or, pinned, stays
there), sends its next request when the last has answered (after the
mix's seeded pause, where it states one), STOPS ISSUING at ``seconds``
and returns when its last request has answered.
Every request issued is reported: none is dropped for being in flight.

Times are ``CLOCK_MONOTONIC`` seconds, the same clock in every process
of the machine.
"""

from __future__ import annotations

import json
import random
import sys
import threading
import time

from benchmark.canon import digest, rows_of


def now() -> float:
    return time.clock_gettime(time.CLOCK_MONOTONIC)


def pauses(seed: int, session: int, think_ms: list):
    """The pauses of one session before its sends, in seconds, without
    end: uniform between the mix's two ``think_ms``, a function of the
    seed and the session's index alone, so that every run of a seed
    pauses alike and no two sessions pause in step."""
    lo, hi = think_ms
    rng = random.Random(f"think:{int(seed)}:{int(session)}")
    while True:
        yield rng.uniform(lo, hi) / 1000.0


class Session(threading.Thread):
    """One client: a connection of its own and a closed loop."""

    def __init__(self, idx: int, plan: dict, connect) -> None:
        super().__init__(daemon=True, name=f"session-{idx}")
        self.idx = idx
        self.plan = plan
        self.connect = connect
        self.remote = None
        self.go = threading.Event()
        self.done = threading.Event()
        self.run_args = None
        self.records: list = []
        self.error = None

    def open(self) -> None:
        self.remote = self.connect()

    def request(self, shape: dict, params: dict) -> tuple:
        """(status, digest): status 0 answered by the device, 1 answered
        by another engine, 2 error or time-out."""
        try:
            rs = self.remote.query(shape["sql"], params)
        except Exception as e:  # the client's errors and time-outs all count
            self.error = f"{type(e).__name__}: {e}"[:300]
            try:
                self.remote.close()
                self.remote = self.connect()
            except Exception:
                pass
            return 2, None
        got = digest(
            rows_of(rs.to_dicts(), shape["columns"]), shape["ordered"]
        )
        return (0 if rs.engine == "tpu" else 1), got

    def one(self, i: int, k: int) -> list:
        """Send the request at index ``k`` of shape ``i``'s pool."""
        shape = self.plan["shapes"][i]
        pool = shape["pool"]
        row = pool["rows"][k % len(pool["rows"])]
        t_send = now()
        status, got = self.request(shape, dict(zip(pool["names"], row)))
        return [self.idx, i, k, t_send, now(), status, got]

    def loop(self, t_start: float, seconds: float, base: list) -> list:
        """The closed loop: walk the block from this session's offset by
        the plan's stride (0: stay there), pause before each send where
        the mix states a pause, issue until ``seconds`` after
        ``t_start``, wait for every answer."""
        plan = self.plan
        block = plan["block"]
        lo_hi = plan["think_ms"]
        think = pauses(plan["seed"], self.idx, lo_hi) if lo_hi[1] > 0 else None
        used = [0] * len(plan["shapes"])
        pos = plan["offsets"][self.idx]
        records = []
        while True:
            if think:
                time.sleep(next(think))
            if now() - t_start >= seconds:
                break
            i = block[pos % len(block)]
            pos += plan["stride"]
            k = base[i] + self.idx + used[i] * plan["sessions"]
            used[i] += 1
            records.append(self.one(i, k))
        return records

    def run(self) -> None:
        while True:
            self.go.wait()
            self.go.clear()
            if self.run_args is None:
                return
            kind, *rest = self.run_args
            self.records = (
                self.loop(*rest) if kind == "loop" else [self.one(*rest)]
            )
            self.done.set()


def run_window(sessions: list, seconds: float, base: list) -> dict:
    """One closed-loop run over all sessions. Sessions stop ISSUING at
    ``seconds``; the run ends when the last issued request has answered,
    and every request issued is in ``records``."""
    t_start = now()
    for s in sessions:
        s.run_args = ("loop", t_start, float(seconds), base)
        s.done.clear()
        s.go.set()
    for s in sessions:
        s.done.wait()
    return {
        "t_start": t_start,
        "records": [r for s in sessions for r in s.records],
        "last_error": next((s.error for s in sessions if s.error), None),
    }


def run_burst(sessions: list, shape: int, k: int, base: list) -> dict:
    """``k`` of the shape's sessions send one request of it at the same
    moment, the pool's next ``k`` tuples, so that the server's lane meets
    a batch of ``k`` (warm-up only)."""
    plan = sessions[0].plan
    chosen = [sessions[s] for s in plan["shape_sessions"][shape][:k]]
    for j, s in enumerate(chosen):
        s.run_args = ("one", shape, base[shape] + j)
        s.done.clear()
    for s in chosen:
        s.go.set()
    for s in chosen:
        s.done.wait()
    return {"records": [r for s in chosen for r in s.records]}


def serve(stdin, stdout) -> int:
    sessions: list = []
    for line in stdin:
        msg = json.loads(line)
        if msg["op"] == "init":
            from orientdb_tpu.client.remote import connect

            plan = msg["plan"]

            def opener(m=msg):
                return connect(m["url"], m["user"], m["password"])

            sessions = [
                Session(i, plan, opener) for i in range(plan["sessions"])
            ]
            for s in sessions:
                s.open()
                s.start()
            reply = {"ok": True, "jax_imported": "jax" in sys.modules}
        elif msg["op"] == "run":
            reply = {"ok": True, **run_window(sessions, msg["seconds"], msg["base"])}
        elif msg["op"] == "burst":
            reply = {
                "ok": True,
                **run_burst(sessions, msg["shape"], msg["k"], msg["base"]),
            }
        elif msg["op"] == "quit":
            break
        else:
            reply = {"ok": False, "error": f"unknown op {msg['op']!r}"}
        stdout.write(json.dumps(reply) + "\n")
        stdout.flush()
    for s in sessions:
        s.run_args = None
        s.go.set()
        if s.remote is not None:
            try:
                s.remote.close()
            except Exception:
                pass
    return 0


if __name__ == "__main__":
    sys.exit(serve(sys.stdin, sys.stdout))

"""One run of one cell of ``BENCHMARK.json``:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip. It loads the kinds module the cell's
configuration names (``benchmark/kinds/<module>.py``: generator, attach,
reference, root measures, byte counts), makes the cell's data from
``--seed``, attaches it to a ``server.server.Server`` on loopback,
starts the load generator (``loadgen``, a process of its own without
JAX), warms the cell's own statement shapes through the served path at
the cell's own concurrency until the plan counters stand still, and
opens the window. Everything before that is ``setup_s``.

The window (``run_window`` in ``loadgen``): sessions stop ISSUING at
``--seconds`` and the window closes when the last issued request has
answered. Every request issued is in ``attempted``, in ``qps`` and in
both percentiles. After the window the chip's peak memory is read, the
server stopped and the data detached; then every answer of the window
is compared with the kinds module's plain reference, which decides
``correct``.

The last line of standard output is the one JSON object the driver
reads. Off a TPU, or with fewer chips than the cell asks for, the run
prints no result and exits with code 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.clock_gettime(time.CLOCK_MONOTONIC)

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

from benchmark.canon import digest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: counters whose movement means a plan was recorded or compiled
COMPILE_COUNTERS = (
    "plan_cache.miss",
    "plan_cache.overflow_rerecord",
    "plan_cache.aot_compile",
    "plan_cache.group_compile",
    "plan_cache.aot_compile_error",
    "plan_cache.group_compile_error",
)
#: seconds of one warm-up round, and how many in a row must move nothing
WARM_ROUND_S = 2.0
WARM_QUIET_ROUNDS = 2
WARM_LIMIT_S = 900.0
#: the traced run profiles at most this much of the window's start
TRACE_SPAN_S = 12.0
PASSWORD = "benchmark"


def now() -> float:
    return time.clock_gettime(time.CLOCK_MONOTONIC)


def note(msg: str) -> None:
    print(f"[benchmark {now() - T_PROCESS:8.2f}s] {msg}", file=sys.stderr, flush=True)


# -- what the run reads of BENCHMARK.json -------------------------------------


def find_cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"benchmark: no workload {workload!r} in BENCHMARK.json")


def metrics_for(bench: dict, section: str, workload: str) -> list:
    return [
        m
        for m in bench[section]
        if "workloads" not in m or workload in m["workloads"]
    ]


def load_module(sub: str, name: str, root: str = HERE):
    """``benchmark/<sub>/<name>.py``, loaded by path: what belongs to one
    metric or one kind of deployment is a file that ``BENCHMARK.json`` or
    a configuration names, never an entry in a table here."""
    path = os.path.join(root, sub, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{sub}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: str = HERE):
    """``benchmark/layer_metrics/<name>.py``: one metric, one reader."""
    return load_module("layer_metrics", name, root)


#: what ``benchmark/kinds/<module>.py`` has to define (kinds/README.md)
KINDS_NAMES = ("make_raw", "attach", "Reference", "Measures", "least_bytes", "stale")


def load_kinds(cfg: dict, root: str = HERE):
    """The kinds module a configuration names, ``benchmark/kinds/<module>.py``:
    everything the benchmark knows about one kind of deployment. There is
    no default and no fallback."""
    if not cfg.get("kinds"):
        raise SystemExit(
            f"benchmark: configuration {cfg.get('name')!r} names no \"kinds\" module"
        )
    mod = load_module("kinds", cfg["kinds"], root)
    missing = [n for n in KINDS_NAMES if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"benchmark: kinds module {mod.__file__} lacks {', '.join(missing)}")
    return mod


# -- the program's counters, read and never written -----------------------------


def read_counters() -> dict:
    from orientdb_tpu.obs.stats import stats
    from orientdb_tpu.utils.metrics import metrics

    out = dict(metrics.snapshot().get("counters", {}))
    for row in stats.top(k=1 << 30):
        for engine, calls in row["engines"].items():
            key = f"engine:{engine}"
            out[key] = out.get(key, 0) + calls
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}


def warmups_in_flight() -> int:
    """Background plan compiles the engine has started and not finished
    (``exec/tpu_engine._AotWarmup``): the window may not open over one."""
    from orientdb_tpu.exec import tpu_engine

    holder = getattr(tpu_engine, "_AotWarmup", None)
    return len(getattr(holder, "_inflight", ()))


# -- the load generator's end of the pipe ----------------------------------------


class Generator:
    def __init__(self, url: str, plan: dict) -> None:
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.loadgen"],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            cwd=ROOT,
            env=env,
            text=True,
        )
        reply = self.call(
            {"op": "init", "url": url, "user": "admin", "password": PASSWORD, "plan": plan}
        )
        if reply.get("jax_imported"):
            raise SystemExit("benchmark: the load generator imported jax")

    def call(self, msg: dict) -> dict:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise SystemExit(
                f"benchmark: the load generator died (exit {self.proc.poll()})"
            )
        reply = json.loads(line)
        if not reply.get("ok"):
            raise SystemExit(f"benchmark: load generator: {reply}")
        return reply

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write('{"op": "quit"}\n')
                self.proc.stdin.flush()
                self.proc.stdin.close()
                self.proc.wait(timeout=20)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


def advance(base: list, records: list, sessions: int) -> list:
    """The pools' offsets after a run: past every index it drew."""
    out = list(base)
    for _s, i, k, *_rest in records:
        out[i] = max(out[i], k + 1)
    # whole strides, so that session s keeps drawing indices ≡ s
    return [b + (-b) % sessions for b in out]


# -- the run ----------------------------------------------------------------------


def percentile(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile of an ascending list: a value that was
    measured, never one interpolated between two clusters."""
    if not sorted_vals:
        raise ValueError("no samples")
    k = min(len(sorted_vals), max(1, math.ceil(q * len(sorted_vals))))
    return sorted_vals[k - 1]


def window_metrics(records: list) -> dict:
    """The end-to-end metrics of one window from the generator's records
    ``[session, shape, pool index, t_send, t_done, status, digest]``."""
    lat = sorted((r[4] - r[3]) * 1000.0 for r in records)
    span = max(r[4] for r in records) - min(r[3] for r in records)
    return {
        "span_s": span,
        "qps": len(records) / span,
        "latency_p50_ms": percentile(lat, 0.50),
        "latency_p95_ms": percentile(lat, 0.95),
    }


def shape_summary(records: list) -> dict:
    """One shape's requests of the window: how many, and where their
    latencies lie (rule 4: a percentile may not sit where two clusters
    meet, and this is where one looks)."""
    lat = sorted((r[4] - r[3]) * 1000.0 for r in records)
    if not lat:
        return {"n": 0}
    return {
        "n": len(lat),
        "min_ms": lat[0],
        "p50_ms": percentile(lat, 0.50),
        "p95_ms": percentile(lat, 0.95),
        "max_ms": lat[-1],
    }


def compare(plan: dict, records: list, ref) -> dict:
    """Every answer of the window against the reference. Returns the
    numbers compared, each beside its limit."""
    wrong, unanswered, compared, examples = 0, 0, 0, []
    cache: dict = {}
    for _s, i, k, _t0, _t1, status, got in records:
        if status == 2:
            unanswered += 1
            continue
        shape = plan["shapes"][i]
        pool = shape["pool"]
        row = pool["rows"][k % len(pool["rows"])]
        key = (i, tuple(row))
        if key not in cache:
            cache[key] = digest(
                ref.answer(shape["reference"], dict(zip(pool["names"], row))),
                shape["ordered"],
            )
        compared += 1
        if got != cache[key]:
            wrong += 1
            if len(examples) < 5:
                examples.append(
                    {"shape": shape["name"], "params": row, "got": got, "want": cache[key]}
                )
    return {
        "numbers": {
            "wrong_answers": {"value": wrong, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0},
            "answers_compared": {"value": compared, "at_least": 1},
        },
        "examples": examples,
        "correct": wrong == 0 and unanswered == 0 and compared >= 1,
    }


def run_cell(
    args, bench: dict, require_chip: bool = True, root: str = HERE, control: str = "none"
) -> dict:
    """Drive one run; returns the result object (``main`` prints it).
    ``control`` is for ``benchmark/control.py`` alone."""
    cell = find_cell(bench, args.workload)
    from benchmark.traffic import build_plan, load_json

    cfg = load_json("configs", cell["config"], root)
    mix = load_json("traffic", cell["traffic"], root)
    kinds = load_kinds(cfg, root)

    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < int(cell["chips"])):
        print(
            f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s); "
            f"JAX reports {[str(d) for d in devs]}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    dev = devs[0]

    from orientdb_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program goes to the cache, the small ones too: only the first
    # run of a cell in a checkout may compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    note(f"devices {[str(d) for d in devs]}; compile cache {cache_dir}")

    parts = {"import_s": now() - T_PROCESS}
    t = now()
    from benchmark import control as controls

    broken = contextlib.ExitStack()
    if control == "lower_precision":
        broken.enter_context(controls.lower_precision())
    raw = kinds.make_raw(cfg["scale"], args.seed)
    served = kinds.stale(raw, args.seed) if control == "stale_snapshot" else raw
    db, snap = kinds.attach(served)
    del served
    parts["build_s"] = now() - t
    t = now()
    ref = kinds.Reference(raw)
    plan = build_plan(
        mix, kinds.Measures(ref), args.seed, int(mix.get("pool_size", 20000))
    )
    parts["plan_s"] = now() - t
    note(
        f"data {({k: getattr(v, 'shape', v) for k, v in vars(raw).items()})}; "
        f"block {plan['block']}; pools {[len(s['pool']['rows']) for s in plan['shapes']]}"
    )

    from orientdb_tpu.server.server import Server

    srv = Server(admin_password=PASSWORD, http_port=0, binary_port=0)
    srv.attach_database(db)
    srv.startup()
    gen = None
    trace_dir = os.path.join(ROOT, ".bench_trace")
    try:
        t = now()
        gen = Generator(f"remote:127.0.0.1:{srv.binary_port}/{db.name}", plan)
        sessions = plan["sessions"]
        base = [0] * len(plan["shapes"])

        # warm-up: the cell's own shapes, at the cell's own concurrency.
        # First each shape alone in batches of 1, 2, 4 .. as many sessions
        # as can send it at one moment, so that the engine meets every lane
        # bucket it can meet in the window and starts that bucket's
        # background compile; then the mix itself in rounds, until a round
        # moves no plan counter and none is in flight.
        t_warm = now()

        def settle() -> None:
            while warmups_in_flight():
                if now() - t_warm > WARM_LIMIT_S:
                    raise SystemExit("benchmark: warm-up did not settle")
                time.sleep(0.05)

        for i, clients in enumerate(plan["shape_sessions"]):
            n = len(clients)
            for k in sorted({1 << b for b in range(n.bit_length())} | {n}):
                reply = gen.call({"op": "burst", "shape": i, "k": k, "base": base})
                base = advance(base, reply["records"], sessions)
                settle()
        note(f"warm bursts done: {delta(read_counters(), {})}")
        quiet, rounds = 0, 0
        while quiet < WARM_QUIET_ROUNDS:
            before = read_counters()
            reply = gen.call({"op": "run", "seconds": WARM_ROUND_S, "base": base})
            base = advance(base, reply["records"], sessions)
            settle()
            moved = {
                k: v
                for k, v in delta(read_counters(), before).items()
                if k in COMPILE_COUNTERS
            }
            shapes_seen = {r[1] for r in reply["records"] if r[5] == 0}
            rounds += 1
            settled = not moved and len(shapes_seen) == len(plan["shapes"])
            quiet = quiet + 1 if settled else 0
            note(f"warm round {rounds}: {len(reply['records'])} requests, moved {moved}")
        parts["warm_s"] = now() - t
        parts["warm_rounds"] = rounds

        state_bytes = max(
            snap._device_cache.memory_report()["per_device"].values(), default=0
        )

        # the window
        tracing = bool(args.trace)
        counters0 = read_counters()
        if tracing:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        t_open = now()
        setup_s = t_open - T_PROCESS
        if tracing:
            import threading

            t_trace = [t_open, None]

            def stop():
                t_trace[1] = now()  # stop_trace itself takes seconds to write
                jax.profiler.stop_trace()

            timer = threading.Timer(min(TRACE_SPAN_S, float(args.seconds)), stop)
            timer.start()
        reply = gen.call({"op": "run", "seconds": float(args.seconds), "base": base})
        if tracing:
            timer.join()
        records = reply["records"]
        counters = delta(read_counters(), counters0)
        # a background compile the window started and did not finish is a
        # compile in the window all the same
        late = warmups_in_flight()
        if late:
            counters["plan_cache.group_compile"] = (
                counters.get("plan_cache.group_compile", 0) + late
            )
            t_warm = now()
            settle()
        peak = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs[: int(cell["chips"])]
        )
        note(f"window: {len(records)} requests; last error {reply['last_error']}")
    finally:
        if gen is not None:
            gen.close()
        srv.shutdown()
        db.detach_snapshot()
        broken.close()

    if not records:
        raise SystemExit("benchmark: the window issued no request")
    e2e = window_metrics(records)
    e2e["setup_s"] = setup_s
    failed = sum(1 for r in records if r[5] != 0)

    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }
    result = {"correct": False, "attempted": len(records), "failed": failed}
    if tracing:
        from benchmark import tracered

        t = now()
        loaded = tracered.load_xplane(tracered.find_xplane(trace_dir))
        trace = tracered.reduce(loaded)
        del loaded
        shutil.rmtree(trace_dir, ignore_errors=True)
        note(f"trace read in {now() - t:.1f}s: {trace['summary']}")
        in_span = [r for r in records if t_trace[0] <= r[4] <= t_trace[1]]
        obs = {
            "counters": counters,
            "requests": len(records),
            "window": e2e,
            "requests_in_trace": len(in_span),
            "trace": trace,
            "least_bytes_in_trace": sum(
                kinds.least_bytes(plan["shapes"][r[1]]["reference"], raw)
                for r in in_span
            ),
            "device_kind": dev.device_kind,
            "hbm_state_bytes": state_bytes,
            "hbm_peak_bytes": peak,
        }
        metrics = {}
        for m in metrics_for(bench, "per_layer", args.workload):
            value = load_reader(m["name"], root).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_ops"][:10],
            "idle_gaps": trace["idle_gaps"][:10],
        }
    else:
        result["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in metrics_for(bench, "end_to_end", args.workload)
        }
    result["device"] = device
    result["setup_parts"] = {k: round(v, 3) for k, v in parts.items()}
    result["window"] = {
        "span_s": e2e["span_s"],
        "by_shape": {
            s["name"]: shape_summary([r for r in records if r[1] == i])
            for i, s in enumerate(plan["shapes"])
        },
        "param_repeats": len(records)
        - len({(r[1], r[2] % len(plan["shapes"][r[1]]["pool"]["rows"])) for r in records}),
        "last_error": reply["last_error"],
    }
    t = now()
    verdict = compare(plan, records, ref)
    note(f"reference compared in {now() - t:.1f}s")
    result["correct"] = verdict["correct"]
    result["compared"] = verdict["numbers"]
    if verdict["examples"]:
        print(json.dumps({"wrong_examples": verdict["examples"]}), file=sys.stderr)
    for name, n in verdict["numbers"].items():
        print(f"compared {name}: {json.dumps(n)}", file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    result = run_cell(args, bench)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

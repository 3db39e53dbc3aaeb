#!/usr/bin/env python
"""Chip smoke: the served MATCH path, once, on one TPU.

    python chip_smoke.py              # one chip: `served` + `scale`
    python chip_smoke.py --chips 4    # the sharded path and its one-chip
                                      # comparison, and no other phase

Phases (default run):

- ``served`` — an SNB SF1-shaped graph (10 000 persons) built through the
  record store, attached to a device snapshot and served by
  ``server.server.Server``; IS1–IS7 and a friend-of-friend COUNT go over
  HTTP and over the binary protocol, every answer is compared with
  ``engine="oracle"`` on the same database.
- ``scale`` — array-native device state at the scale tier's size
  (8 000 000 persons, ~80 M ``knows`` edges, planted supernodes): the
  1-hop/2-hop COUNT and the config-5 multi-pattern COUNT against the
  exact numpy references, all ``engine="tpu", strict=True``.

Before ``ok`` is printed the run proves from the metric counters that
the device did the work: every query counted as ``query.tpu``, none as a
fallback, an oracle answer, a views/command-cache hit or a device fault.

The last stdout line is one JSON object with the device as JAX reports
it. Off a TPU the script exits non-zero straight away and prints no
result; ``--rehearse`` lets the phases run anyway (tiny sizes on the
CPU, to find wrong paths before a chip call) but the run still exits
non-zero and never prints ``"ok": true``.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import sys
import time
import urllib.parse
import urllib.request

Q_1HOP = (
    "MATCH {class:Person, as:p, where:(age > 40)}"
    "-knows->{as:f, where:(age < 30)} RETURN count(*) AS n"
)
Q_2HOP = (
    "MATCH {class:Person, as:p, where:(age > 40)}-knows->{as:f}"
    "-knows->{as:g, where:(age < 30)} RETURN count(*) AS n"
)
Q_CONFIG5 = (
    "MATCH {class:Person, as:p, where:(age > 40)}"
    ".outE('knows'){where:(creationDate > :d)}"
    ".inV(){as:f, where:(age < 30)}, "
    "{class:Message, as:m}-hasCreator->{as:f} "
    "RETURN count(*) AS n"
)
CONFIG5_D = (12_000, 15_000, 18_500)
Q_FOF = (
    "MATCH {class:Person, as:p, where:(id = :personId)}"
    "-knows-{as:f}-knows-{as:ff, where:(id <> :personId)} "
    "RETURN count(*) AS n"
)
# var-depth arm over the sharded edge list (`sharded_bitmap_hop`)
Q_WHILE = (
    "MATCH {class:Person, as:p, where:(uid < :u)}"
    "-knows->{as:f, while:($depth < 2), where:(age < 30)} "
    "RETURN count(*) AS n"
)
WHILE_U = 4
#: distinct seeded ids each served shape is sent with
N_PARAM_SETS = 8
# row-returning expansion over the row-sharded CSR (`expand_gather`)
Q_ROWS = (
    "MATCH {class:Person, as:p, where:(uid < :u)}"
    "-knows->{as:f, where:(age < 30)} RETURN p.uid AS p, f.uid AS f"
)
ROWS_U = 50

#: counters that must not move while the phases run
ZERO_COUNTERS = (
    "query.tpu.fallback",
    "query.oracle",
    "device.fault.total",
    "views.hit",
    "command_cache.hit",
)
WATCHED = ("query.tpu",) + ZERO_COUNTERS


def say(tag: str, **fields) -> None:
    print(json.dumps({"phase": tag, **fields}, default=str), flush=True)


class Proof:
    """Counter deltas over the stretches in which the engine was sent
    queries (``add``), leaving out what the smoke itself asks of the
    oracle for parity (``rebase`` after it).

    ``query.tpu`` counts the embedded front door (``db.query``) only.
    The served path (coalescer lanes → ``execute_query_batch`` /
    ``dispatch_lane_batch``) moves neither it nor ``query.oracle``, so
    the nearest witness that exists is read as well: the stats plane's
    per-fingerprint call counts by engine (``engine:<name>`` below),
    which every path records."""

    def __init__(self) -> None:
        self.sent = 0
        self.sent_embedded = 0
        self.delta: dict = {}
        self.rebase()

    @staticmethod
    def _read() -> dict:
        from orientdb_tpu.obs.stats import stats
        from orientdb_tpu.utils.metrics import metrics

        out = {n: metrics.counter(n) for n in WATCHED}
        for row in stats.top(k=1 << 30):
            for engine, calls in row["engines"].items():
                key = f"engine:{engine}"
                out[key] = out.get(key, 0) + calls
        return out

    def rebase(self) -> None:
        self._base = self._read()

    def add(self, n_sent: int, embedded: bool = True) -> None:
        now = self._read()
        for k, v in now.items():
            self.delta[k] = self.delta.get(k, 0) + v - self._base.get(k, 0)
        self._base = now
        self.sent += n_sent
        if embedded:
            self.sent_embedded += n_sent

    def failures(self) -> list:
        d = self.delta
        bad = []
        if d.get("query.tpu", 0) != self.sent_embedded:
            bad.append(
                f"{self.sent_embedded} embedded queries sent but "
                f"query.tpu moved by {d.get('query.tpu', 0)}"
            )
        if d.get("engine:tpu", 0) != self.sent:
            bad.append(
                f"{self.sent} queries sent but the stats plane counts "
                f"{d.get('engine:tpu', 0)} answered by the tpu engine"
            )
        bad += [
            f"{k} moved by {v}"
            for k, v in sorted(d.items())
            if v and (
                k in ZERO_COUNTERS
                or (k.startswith("engine:") and k != "engine:tpu")
            )
        ]
        return bad


# -- served ------------------------------------------------------------------


def _http_query(port: int, password: str, db: str, sql: str) -> list:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query/{db}/sql/"
        + urllib.parse.quote(sql, safe="")
    )
    req.add_header(
        "Authorization",
        "Basic " + base64.b64encode(f"admin:{password}".encode()).decode(),
    )
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())["result"]


def _inline(sql: str, params: dict) -> str:
    """The HTTP GET route carries no parameters: inline them as literals."""
    for k, v in params.items():
        sql = sql.replace(f":{k}", str(int(v)))
    return sql


def phase_served(proof: Proof, seed: int, n_persons: int) -> None:
    import numpy as np

    from orientdb_tpu.client.remote import connect
    from orientdb_tpu.exec.result import canonical_rows
    from orientdb_tpu.server.server import Server
    from orientdb_tpu.storage.ingest import generate_ldbc_snb
    from orientdb_tpu.storage.snapshot import attach_fresh_snapshot
    from orientdb_tpu.workloads.ldbc import IS_QUERIES

    t0 = time.perf_counter()
    password = "smoke"
    srv = Server(admin_password=password, http_port=0, binary_port=0)
    db = srv.create_database("snb")
    generate_ldbc_snb(db, n_persons=n_persons, seed=seed)
    attach_fresh_snapshot(db)
    t_up = time.perf_counter()
    n_messages = db.count_class("Post") + db.count_class("Comment")
    rng = np.random.default_rng(seed)
    person_ids = rng.choice(n_persons, N_PARAM_SETS, replace=False).tolist()
    message_ids = rng.choice(n_messages, N_PARAM_SETS, replace=False).tolist()
    workload = []  # (name, sql, params)
    for name in sorted(IS_QUERIES):
        q = IS_QUERIES[name]
        key, ids = (
            ("personId", person_ids)
            if ":personId" in q
            else ("messageId", message_ids)
        )
        workload += [(name, q, {key: v}) for v in ids]
    workload += [("FOF", Q_FOF, {"personId": v}) for v in person_ids]

    served = []  # (name, sql, params, http rows, binary rows)
    first_s = {}
    srv.startup()
    try:
        with connect(
            f"remote:127.0.0.1:{srv.binary_port}/snb", "admin", password
        ) as remote:
            for name, q, params in workload:
                t_q = time.perf_counter()
                over_http = _http_query(
                    srv.http_port, password, "snb", _inline(q, params)
                )
                rs = remote.query(q, params)
                if rs.engine != "tpu":
                    raise SystemExit(
                        f"served: {name} {params} answered by engine "
                        f"{rs.engine!r} over the binary protocol"
                    )
                served.append((name, q, params, over_http, rs.to_dicts()))
                # the first query of each shape compiles its plan(s)
                first_s.setdefault(
                    name, round(time.perf_counter() - t_q, 3)
                )
    finally:
        srv.shutdown()
    t_done = time.perf_counter()
    # counted BEFORE the oracle parity calls below move query.oracle
    proof.add(2 * len(workload), embedded=False)

    n_rows = 0
    for name, q, params, over_http, over_binary in served:
        want = db.query(q, params=params, engine="oracle").to_dicts()
        # compare what a client sees: the oracle's rows through JSON too
        want = json.loads(json.dumps(want, default=str))
        for wire, got in (("http", over_http), ("binary", over_binary)):
            got = json.loads(json.dumps(got, default=str))
            same = (
                got == want
                if "ORDER BY" in q
                else canonical_rows(got) == canonical_rows(want)
            )
            if not same:
                raise SystemExit(
                    f"served: {name} {params} over {wire} differs from "
                    f"the oracle: got {got[:3]} want {want[:3]}"
                )
        n_rows += len(want)
    if n_rows == 0:
        raise SystemExit("served: no query returned a row")
    rep = db.current_snapshot()._device_cache.memory_report()
    say(
        "served",
        persons=n_persons,
        messages=n_messages,
        shapes=sorted({w[0] for w in workload}),
        parameter_sets_per_shape=N_PARAM_SETS,
        queries_http=len(workload),
        queries_binary=len(workload),
        oracle_parity="ok",
        rows_compared=n_rows,
        load_and_attach_s=round(t_up - t0, 3),
        traffic_s=round(t_done - t_up, 3),
        first_query_s_incl_compile=first_s,
        hbm_per_device_bytes=rep["per_device"],
    )
    db.detach_snapshot()
    proof.rebase()


# -- scale -------------------------------------------------------------------


def _count(db, sql: str, params=None) -> int:
    rows = db.query(sql, params=params, engine="tpu", strict=True).to_dicts()
    if len(rows) != 1 or set(rows[0]) != {"n"}:
        raise SystemExit(f"scale: unexpected COUNT result {rows!r}")
    return int(rows[0]["n"])


def _device_report(snap) -> dict:
    import jax

    rep = snap._device_cache.memory_report()
    return {
        "hbm_per_device_bytes": rep["per_device"],
        "memory_stats": jax.devices()[0].memory_stats(),
    }


def _scale_person_knows(seed: int, n_persons: int) -> int:
    import numpy as np

    from orientdb_tpu.storage.bigshape import (
        build_person_knows,
        numpy_1hop_count,
        numpy_2hop_count,
    )

    t0 = time.perf_counter()
    # the supernodes are the point: they push the COUNT pushdown's
    # per-edge weights far past 256, the largest integer bf16 holds
    # exactly — on the MXU-blocked prefix sum (ops/csr.value_cumsum)
    db, snap = build_person_knows(
        n_persons,
        avg_knows=10,
        seed=seed,
        supernodes=64,
        supernode_degree=min(20_000, max(300, n_persons // 4)),
    )
    t_built = time.perf_counter()
    age = snap.v_columns["age"].values
    src_m, mid, dst_m = age > 40, np.ones(age.shape[0], bool), age < 30
    want1 = numpy_1hop_count(snap, src_m, dst_m)
    want2 = numpy_2hop_count(snap, src_m, mid, dst_m)
    t_ref = time.perf_counter()
    try:
        got1 = _count(db, Q_1HOP)
        t_1 = time.perf_counter()
        got2 = _count(db, Q_2HOP)
        t_2 = time.perf_counter()
        report = _device_report(snap)
    finally:
        db.detach_snapshot()
    say(
        "scale.person_knows",
        persons=n_persons,
        edges=int(snap.edge_classes["knows"].num_edges),
        out_degree_max=int(snap.edge_classes["knows"].out_degree_max),
        one_hop={"got": got1, "want": want1},
        two_hop={"got": got2, "want": want2},
        build_s=round(t_built - t0, 3),
        numpy_reference_s=round(t_ref - t_built, 3),
        one_hop_s_incl_upload_compile=round(t_1 - t_ref, 3),
        two_hop_s_incl_compile=round(t_2 - t_1, 3),
        **report,
    )
    if (got1, got2) != (want1, want2):
        raise SystemExit(
            f"scale: COUNT differs from numpy at {n_persons} persons: "
            f"1-hop {got1} vs {want1}, 2-hop {got2} vs {want2}"
        )
    return 2


def _scale_config5(seed: int, n_persons: int) -> int:
    from orientdb_tpu.storage.bigshape import (
        build_snb_shape,
        numpy_config5_count,
    )

    t0 = time.perf_counter()
    db, snap = build_snb_shape(
        n_persons, msgs_per_person=2, avg_knows=10, seed=seed
    )
    t_built = time.perf_counter()
    answers, secs = {}, {}
    try:
        for d in CONFIG5_D:
            t_q = time.perf_counter()
            got = _count(db, Q_CONFIG5, {"d": d})
            secs[str(d)] = round(time.perf_counter() - t_q, 3)
            answers[str(d)] = {
                "got": got, "want": numpy_config5_count(snap, d)
            }
        report = _device_report(snap)
    finally:
        db.detach_snapshot()
    say(
        "scale.config5",
        persons=n_persons,
        vertices=int(snap.num_vertices),
        knows_edges=int(snap.edge_classes["knows"].num_edges),
        answers=answers,
        build_s=round(t_built - t0, 3),
        query_s_first_incl_upload_compile=secs,
        **report,
    )
    bad = {d: a for d, a in answers.items() if a["got"] != a["want"]}
    if bad:
        raise SystemExit(f"scale: config-5 differs from numpy: {bad}")
    return len(CONFIG5_D)


def _halving(proof: Proof, label: str, n_persons: int, run) -> int:
    """Run ``run(n)`` (returns the queries it sent); where the chip's
    compiler or HBM refuses the size, say so, halve and go again.
    Returns the size reached."""
    import jax

    from orientdb_tpu.exec import devicefault

    n = n_persons
    while True:
        try:
            proof.add(run(n))
            return n
        except Exception as e:
            # strict=True raises the ladder's DeviceQuarantined; the
            # device's own words are its cause
            cause = e.__cause__ if e.__cause__ is not None else e
            if n < 2 or devicefault.OOM not in (
                devicefault.classify(e), devicefault.classify(cause)
            ):
                raise
            say(
                f"{label}.refused",
                persons=n,
                refusal=f"{type(cause).__name__}: {cause}"[:2000],
                memory_stats=jax.devices()[0].memory_stats(),
                next_persons=n // 2,
            )
            # the refused size's faults and quarantine do not count
            # against the size that runs
            devicefault.domain.reset()
            proof.rebase()
            n //= 2


def phase_scale(proof: Proof, seed: int, n_persons: int) -> None:
    reached1 = _halving(
        proof,
        "scale.person_knows",
        n_persons,
        lambda n: _scale_person_knows(seed, n),
    )
    reached2 = _halving(
        proof,
        "scale.config5",
        n_persons,
        lambda n: _scale_config5(seed, n),
    )
    say(
        "scale",
        asked_persons=n_persons,
        reached_person_knows=reached1,
        reached_config5=reached2,
        numpy_parity="ok",
    )


# -- four chips ----------------------------------------------------------------


def _numpy_rows_1hop(snap, u: int) -> list:
    """Exact reference for ``Q_ROWS``: (p, f) for every ``knows`` edge
    p→f with uid(p) < u and age(f) < 30 (uid is the dense vertex id)."""
    knows = snap.edge_classes["knows"]
    age = snap.v_columns["age"].values
    out = []
    for p in range(u):
        nbrs = knows.dst[knows.indptr_out[p] : knows.indptr_out[p + 1]]
        out += [{"p": p, "f": int(f)} for f in nbrs if age[f] < 30]
    return out


def phase_sharded(proof: Proof, seed: int, n_persons: int) -> None:
    """The sharded path on a 4-device mesh — config-5 (COUNT pushdown:
    ``sharded_weight_pass``), a row-returning 1-hop (``expand_gather``)
    and a var-depth COUNT (``sharded_bitmap_hop``) — against numpy and
    against the one-chip answers computed in the same run."""
    import jax

    from orientdb_tpu.exec.result import canonical_rows
    from orientdb_tpu.parallel import mesh_graph
    from orientdb_tpu.parallel.sharded import make_mesh
    from orientdb_tpu.storage.bigshape import (
        build_snb_shape,
        numpy_config5_count,
    )

    t0 = time.perf_counter()
    db, snap = build_snb_shape(
        n_persons, msgs_per_person=2, avg_knows=10, seed=seed
    )
    t_built = time.perf_counter()
    queries = [
        (f"config5 d={d}", Q_CONFIG5, {"d": d}) for d in CONFIG5_D
    ] + [
        ("rows_1hop", Q_ROWS, {"u": ROWS_U}),
        ("while_count", Q_WHILE, {"u": WHILE_U}),
    ]
    numpy_want = {
        f"config5 d={d}": [{"n": numpy_config5_count(snap, d)}]
        for d in CONFIG5_D
    }
    numpy_want["rows_1hop"] = _numpy_rows_1hop(snap, ROWS_U)

    def run_all():
        return {
            label: canonical_rows(
                db.query(q, params=p, engine="tpu", strict=True).to_dicts()
            )
            for label, q, p in queries
        }

    try:
        one_chip = run_all()
        t_one = time.perf_counter()
        db.detach_snapshot()

        devs = jax.devices()[:4]
        if len(devs) != 4 or len({d.id for d in devs}) != 4:
            raise SystemExit(f"sharded: need 4 devices, have {devs}")
        kernels0 = {k[0] for k in mesh_graph._MESH_KERNEL_CACHE}
        db.attach_snapshot(snap, mesh=make_mesh(4, devices=devs))
        sharded = run_all()
        t_sh = time.perf_counter()
        dg = snap._device_cache
        placement = _shard_placement(dg, devs)
        rep = dg.memory_report()
        stats = {str(d.id): d.memory_stats() for d in devs}
        kernels = sorted(
            {k[0] for k in mesh_graph._MESH_KERNEL_CACHE} - kernels0
        )
    finally:
        db.detach_snapshot()

    def brief(rows):
        return rows[0][0][1] if len(rows) == 1 else f"{len(rows)} rows"

    say(
        "sharded",
        persons=n_persons,
        vertices=int(snap.num_vertices),
        knows_edges=int(snap.edge_classes["knows"].num_edges),
        mesh={"devices": [str(d) for d in devs]},
        answers={
            label: {
                "sharded": brief(sharded[label]),
                "one_chip": brief(one_chip[label]),
                **(
                    {"numpy": brief(canonical_rows(numpy_want[label]))}
                    if label in numpy_want
                    else {}
                ),
            }
            for label, _q, _p in queries
        },
        mesh_kernels_built=kernels,
        build_s=round(t_built - t0, 3),
        one_chip_s_incl_upload_compile=round(t_one - t_built, 3),
        sharded_s_incl_upload_compile=round(t_sh - t_one, 3),
        shard_placement=placement,
        hbm_per_device_bytes=rep["per_device"],
        memory_stats=stats,
    )
    for label, _q, _p in queries:
        if sharded[label] != one_chip[label]:
            raise SystemExit(
                f"sharded: {label} differs from the one-chip answer"
            )
        if label in numpy_want and sharded[label] != canonical_rows(
            numpy_want[label]
        ):
            raise SystemExit(f"sharded: {label} differs from numpy")
    missing = {"expand_gather", "bitmap_hop", "weight_pass"} - set(kernels)
    if missing:
        raise SystemExit(f"sharded: mesh kernels never built: {missing}")
    proof.add(2 * len(queries))


def _shard_placement(dg, devs) -> dict:
    """Where the sharded adjacency's bytes sit. Code that has only met
    virtual devices may put everything on the first one: every ``sh:``
    array must have a shard on each of the 4 devices, and each device
    about a quarter of the bytes."""
    per_dev = {d.id: 0 for d in devs}
    n_arrays = 0
    for key, arr in dg._arrays.items():
        if not key.startswith("sh:"):
            continue
        n_arrays += 1
        on = {s.device.id for s in arr.addressable_shards}
        if on != set(per_dev):
            raise SystemExit(
                f"sharded: {key} sits on devices {sorted(on)}, not on "
                f"{sorted(per_dev)}"
            )
        for s in arr.addressable_shards:
            per_dev[s.device.id] += int(s.data.nbytes)
    total = sum(per_dev.values())
    if n_arrays == 0 or total == 0:
        raise SystemExit("sharded: no sharded adjacency on the devices")
    shares = {str(d): round(b / total, 4) for d, b in per_dev.items()}
    if any(abs(s - 0.25) > 0.05 for s in shares.values()):
        raise SystemExit(f"sharded: uneven byte shares {shares}")
    return {
        "sharded_arrays": n_arrays,
        "bytes_per_device": {str(d): b for d, b in per_dev.items()},
        "share_per_device": shares,
    }


# -- main ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--snb-persons", type=int, default=10_000,
        help="size of the `served` graph (default: SNB SF1 shape)",
    )
    ap.add_argument(
        "--scale-persons", type=int, default=8_000_000,
        help="size of the `scale` / `--chips 4` graphs",
    )
    ap.add_argument(
        "--rehearse", action="store_true",
        help="run the phases off a TPU too (never prints ok: true there)",
    )
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(
            f"chip_smoke: no TPU: jax.devices()[0] is {devs[0]!r}",
            file=sys.stderr,
        )
        return 2
    if len(devs) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX reports "
            f"{len(devs)} device(s)",
            file=sys.stderr,
        )
        return 2

    from orientdb_tpu.exec import devicefault
    from orientdb_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    say(
        "start",
        jax=jax.__version__,
        devices=[str(d) for d in devs],
        seed=args.seed,
        chips=args.chips,
        compile_cache_dir=cache_dir,
        compile_cache_entries=cache_entries(),
    )

    proof = Proof()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(proof, args.seed, args.scale_persons)
    else:
        phase_served(proof, args.seed, args.snb_persons)
        phase_scale(proof, args.seed, args.scale_persons)

    # proof that the chip did the work
    fault = devicefault.domain.snapshot()
    say(
        "proof",
        queries_sent=proof.sent,
        queries_sent_embedded=proof.sent_embedded,
        counter_deltas=proof.delta,
        quarantined=fault["quarantined"],
        quarantines_total=fault["quarantines_total"],
        compile_cache_dir=cache_dir,
        compile_cache_entries=cache_entries(),
        total_s=round(time.perf_counter() - t0, 3),
    )
    bad = proof.failures()
    if bad or fault["quarantined"] or fault["quarantines_total"]:
        raise SystemExit(
            f"proof: the device did not do all the work: {bad}, "
            f"fault domain {fault}"
        )

    ok = on_tpu
    print(
        json.dumps(
            {
                "ok": ok,
                "device": {
                    "platform": devs[0].platform,
                    "kind": devs[0].device_kind,
                    "count": len(devs),
                },
            }
        ),
        flush=True,
    )
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())

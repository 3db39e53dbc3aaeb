"""Hermetic multichip dryrun — CPU-pinned sharded-MATCH parity check.

The driver validates the multi-chip sharding path by running
``__graft_entry__.dryrun_multichip(n)`` with N virtual devices. That check
is a pure *correctness* dryrun: it never needs the real TPU, and any
TPU-client state it touches (e.g. a libtpu client/terminal version skew
inside ``jax.device_put``) can only produce spurious failures. This module
therefore pins the **entire** JAX process to the CPU platform as its very
first act — before any backend can possibly initialize — and then runs the
full sharded execution body (`run_body`).

``__graft_entry__.dryrun_multichip`` runs this module in a fresh
subprocess with ``JAX_PLATFORMS=cpu`` set in the environment as well, so
even backend state created earlier in the *calling* process (e.g. the
driver compile-checking ``entry()`` on the real chip first) cannot leak in.

Reference analog: the multi-server-in-one-JVM distributed test pattern
(SURVEY.md §4) — prove the distributed plane without real cluster hardware.
"""

from __future__ import annotations

import os
import sys


def cpu_pinned_env(n_devices: int, base_env: dict) -> dict:
    """Env-var mutations pinning a JAX process to >= n_devices CPU devices.

    Keeps inherited XLA flags but forces OUR device count to be the
    winning (last) occurrence — XLA flag parsing is last-wins. Pure
    (returns a new dict); imports no jax, so safe to call from a parent
    process that must not initialize any backend.
    """
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    kept = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={n_devices}"]
    )
    return env


def pin_cpu(n_devices: int) -> None:
    """Pin this process to the CPU platform with >= n_devices devices.

    Must run before any JAX backend initializes. Uses both the env vars
    (read at first backend init) and `jax.config` updates (which win even
    when jax was imported early), so whichever path this interpreter
    took, the TPU client is never constructed.
    """
    os.environ.update(cpu_pinned_env(n_devices, os.environ))
    import jax

    jax.config.update("jax_platforms", "cpu")
    try:
        jax.config.update("jax_num_cpu_devices", n_devices)
    except Exception:
        pass  # backend already live (in-process test use) — count via XLA_FLAGS


# BASELINE-shaped query corpus: 1-hop with predicates; 2-hop COUNT via
# sharded psum weight passes; variable-depth WHILE via psum-OR bitmap hops;
# binding-referencing WHERE; NOT anti-join; parameter-generic replay;
# SELECT via the single-node-MATCH rewrite.
QUERIES = [
    (
        "MATCH {class:Profiles, as:p, where:(age > 40)}"
        "-HasFriend->{as:f, where:(age < 30)} RETURN p.uid AS p, f.uid AS f",
        None,
    ),
    (
        "MATCH {class:Profiles, as:p, where:(age > 40)}-HasFriend->{as:f}"
        "-HasFriend->{as:g, where:(age < 30)} RETURN count(*) AS n",
        None,
    ),
    (
        "MATCH {class:Profiles, as:p, where:(uid < 5)}-HasFriend->"
        "{as:f, while:($depth < 3)} RETURN p.uid AS p, f.uid AS f",
        None,
    ),
    (
        "MATCH {class:Profiles, as:p}-HasFriend->"
        "{as:f, where:(age < p.age)} RETURN p.uid AS p, f.uid AS f",
        None,
    ),
    (
        "MATCH {class:Profiles, as:p}-HasFriend->{as:f}, "
        "NOT {as:f}-HasFriend->{as:p} RETURN count(*) AS n",
        None,
    ),
    (
        "MATCH {class:Profiles, as:p, where:(uid < :lim)}"
        "-HasFriend->{as:f} RETURN p.uid AS p, f.uid AS f",
        {"lim": 9},
    ),
    (
        "SELECT name, age FROM Profiles WHERE age > 40 AND uid < :m",
        {"m": 40},
    ),
]


def run_body(n_devices: int) -> None:
    """Execute the sharded-MATCH parity corpus over an n-device mesh.

    Assumes devices are already provisioned (CPU-pinned via `pin_cpu`, or a
    test harness's forced-CPU conftest). Asserts record-run AND cached-plan
    sharded-replay parity against the oracle for every query shape.
    """
    from orientdb_tpu.parallel.sharded import make_mesh, provision_devices
    from orientdb_tpu.storage.ingest import generate_demodb
    from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

    devs = provision_devices(n_devices)
    assert all(d.platform == "cpu" for d in devs[:n_devices]), (
        "dryrun must never touch a non-CPU backend; got "
        + str({d.platform for d in devs[:n_devices]})
    )
    replicas = 2 if (n_devices >= 4 and n_devices % 2 == 0) else 1
    mesh = make_mesh(n_devices, replicas=replicas, devices=devs[:n_devices])
    db = generate_demodb(n_profiles=64, avg_friends=4, seed=1)
    attach_fresh_snapshot(db, mesh=mesh)

    def canon(rows):
        return sorted(tuple(sorted(r.items())) for r in rows)

    # crash-safe evidence (obs/evidence): a driver timeout mid-corpus
    # still leaves every completed query's parity verdict on disk. ORIENTTPU_EVIDENCE
    # overrides the path.
    import time as _time

    from orientdb_tpu.obs.evidence import evidence_sink

    sink = evidence_sink(
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
            "MULTICHIP_EVIDENCE.jsonl",
        )
    )
    for i, (sql, params) in enumerate(QUERIES):
        t0 = _time.perf_counter()
        recorded = canon(
            db.query(sql, params=params, engine="tpu", strict=True).to_dicts()
        )
        replayed = canon(
            db.query(sql, params=params, engine="tpu", strict=True).to_dicts()
        )
        oracle = canon(db.query(sql, params=params, engine="oracle").to_dicts())
        assert recorded == oracle, f"record-run parity broke: {sql}"
        assert replayed == oracle, f"sharded replay parity broke: {sql}"
        if sink is not None:
            sink.emit(
                "dryrun_query",
                {
                    "i": i,
                    "sql": sql[:80],
                    "rows": len(oracle),
                    "parity": "ok",
                    "s": round(_time.perf_counter() - t0, 3),
                },
            )

    # config-5 shape (BASELINE configs[4]): multi-class + EDGE property
    # column + multi-pattern edge-property WHERE, sharded on the same
    # mesh, against the exact numpy reference (array-native graph)
    from orientdb_tpu.storage.bigshape import (
        build_snb_shape,
        numpy_config5_count,
    )

    db5, snap5 = build_snb_shape(400, msgs_per_person=1, avg_knows=4, seed=7)
    snap5._mesh = mesh
    q5 = (
        "MATCH {class:Person, as:p, where:(age > 40)}"
        ".outE('knows'){where:(creationDate > :d)}"
        ".inV(){as:f, where:(age < 30)}, "
        "{class:Message, as:m}-hasCreator->{as:f} "
        "RETURN count(*) AS n"
    )
    for d in (12_000, 17_000):
        want = numpy_config5_count(snap5, d)
        got = db5.query(
            q5, params={"d": d}, engine="tpu", strict=True
        ).to_dicts()
        assert got == [{"n": want}], f"sharded config5 parity broke: d={d}"
    if sink is not None:
        sink.emit(
            "dryrun_done",
            {"mesh": dict(mesh.shape), "queries": len(QUERIES) + 1},
        )
    print(
        f"dryrun_multichip ok: mesh {dict(mesh.shape)}, "
        f"{len(QUERIES)} MATCH/SELECT queries + config5 edge-property-"
        "WHERE multi-pattern sharded-executed at oracle/numpy parity "
        "(platform=cpu, hermetic)"
    )


def main() -> int:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    pin_cpu(n)
    run_body(n)
    return 0


if __name__ == "__main__":
    sys.exit(main())

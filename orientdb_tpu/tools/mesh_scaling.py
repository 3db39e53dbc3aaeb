"""Shard-count scaling probe for the frontier-sparse sharded MATCH path.

Run as a subprocess per shard count (the CPU device count is fixed at
process start):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=S \
        python -m orientdb_tpu.tools.mesh_scaling S

or standalone across a sweep (each shard count in its own subprocess,
for bisection without a full bench round):

    python -m orientdb_tpu.tools.mesh_scaling --sweep 2,4,8 --json

Builds a demodb-shaped graph with one planted SUPERNODE (the §5.7 skew
case the merge design is judged on), runs a row-returning 1-hop MATCH
through the supernode on an S-shard mesh, and prints one JSON record per
shard count (the same record shape bench.py's ``mesh_scaling`` block
stores):

    {"shards": S, "merge_rows": N, "allgather_rows": M, "wall_s": T,
     "replay_s": R, "collective_kb": C, "frontier_occupancy": F,
     "empty_shard_skips": K, "kernel_builds": J, "result_rows": n,
     "overlap": {"records": d, "device_idle_fraction": i,
                 "transfer_hidden_fraction": h, "paths": {...}}}

``overlap`` is the flight recorder's verdict over the probe's own
dispatches (obs/timeline): how idle the devices sat between them and
how many transferred bytes hid behind compute.

``merge_rows`` is what the ring-compacted merge shipped per recording
(O(pow2 global total)); ``allgather_rows`` is what the pre-rework
all_gather-of-cap-blocks design would have shipped (O(S·pow2 local
max)). ``collective_kb`` counts the packed psum segment bytes per hop,
``frontier_occupancy`` is live expansion rows over dense slot rows
(how sparse the frontier the collectives no longer pay for), and
``empty_shard_skips`` counts shards whose gather/scatter was
cond-skipped outright. ``wall_s`` is the cold first query
(record + kernel compiles), ``replay_s`` the median sync-free replay —
the steady-state serving cost chips actually scale. ``kernel_builds``
reads the mesh.kernel_builds counter (memoized kernel wrappers built —
the trace-cache roots): revisiting a geometry must add zero (the
recompile-free contract tests/test_sharded.py pins)."""

from __future__ import annotations

import json
import sys
import time


def main(shards: int) -> None:
    from orientdb_tpu.parallel.sharded import make_mesh
    from orientdb_tpu.storage.ingest import generate_demodb
    from orientdb_tpu.storage.snapshot import attach_fresh_snapshot
    from orientdb_tpu.utils.metrics import metrics

    db = generate_demodb(n_profiles=2000, avg_friends=5, seed=11)
    # plant a supernode: profile 0 follows 1500 others — one shard's
    # local expansion max is ~1500 while the balanced share is ~10
    docs = {d["uid"]: d for d in db.browse_class("Profiles")}
    hub, n = docs[0], len(docs)
    for k in range(1, 1501):
        db.new_edge("HasFriend", hub, docs[k % (n - 1) + 1])
    mesh = make_mesh(shards, replicas=1)
    attach_fresh_snapshot(db, mesh=mesh)
    sql = (
        "MATCH {class:Profiles, as:p, where:(uid < 40)}"
        "-HasFriend->{as:f} RETURN p.uid AS p, f.uid AS f"
    )
    before = metrics.snapshot()["counters"]
    t0 = time.perf_counter()
    rows = db.query(sql, engine="tpu", strict=True).to_dicts()
    wall = time.perf_counter() - t0
    after = metrics.snapshot()["counters"]
    assert rows, "probe query returned nothing"

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    # steady state: the cached plan replays sync-free — the cost a
    # scaled-out serving fleet actually pays per query
    replays = []
    for _ in range(3):
        t1 = time.perf_counter()
        db.query(sql, engine="tpu", strict=True).to_dicts()
        replays.append(time.perf_counter() - t1)
    live = delta("mesh.frontier_live_rows")
    slots = delta("mesh.frontier_slot_rows")
    # overlap verdict for the probe's own dispatches (obs/timeline):
    # the sharded records land in THIS subprocess's flight recorder, so
    # the bench mesh_scaling block's per-S evidence carries device-idle
    # and transfer-hidden fractions next to the collective counters
    from orientdb_tpu.obs.timeline import recorder as _flight

    rep = _flight.overlap()
    overlap = {
        "records": rep.get("records", 0),
        "device_idle_fraction": rep.get("device_idle_fraction"),
        "transfer_hidden_fraction": (rep.get("transfer") or {}).get(
            "transfer_hidden_fraction"
        ),
        "paths": rep.get("paths", {}),
    }
    print(
        json.dumps(
            {
                "shards": shards,
                "merge_rows": delta("mesh.merge_rows"),
                "allgather_rows": delta("mesh.allgather_rows"),
                "wall_s": round(wall, 2),
                "replay_s": round(sorted(replays)[1], 3),
                "collective_kb": round(delta("mesh.collective_bytes") / 1024, 1),
                "frontier_occupancy": round(live / slots, 4) if slots else None,
                "empty_shard_skips": delta("mesh.empty_shard_skips"),
                "kernel_builds": delta("mesh.kernel_builds"),
                "result_rows": len(rows),
                "overlap": overlap,
            }
        )
    )


def sweep(shard_counts, as_json: bool) -> int:
    """Per-S subprocesses (the virtual CPU device count is pinned at
    process start) emitting the bench-block record shape — runnable
    standalone so a mesh regression bisects without a bench round. One
    hung or malformed shard count records an error and the sweep keeps
    going (the bench twin clamps the same way)."""
    from orientdb_tpu.tools.virtual_mesh import run_virtual_mesh_subprocess

    out = []
    rc = 0
    for S in shard_counts:
        res = run_virtual_mesh_subprocess(
            "orientdb_tpu.tools.mesh_scaling", [S], timeout=300, n_devices=S
        )
        res.setdefault("shards", S)
        if "error" in res:
            rc = 1
        out.append(res)
    if as_json:
        print(json.dumps(out))
    else:
        for rec in out:
            print(json.dumps(rec))
    return rc


if __name__ == "__main__":
    from orientdb_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    argv = sys.argv[1:]
    if "--sweep" in argv:
        i = argv.index("--sweep")
        try:
            counts = [int(s) for s in argv[i + 1].split(",") if s]
        except (IndexError, ValueError):
            print(
                "usage: python -m orientdb_tpu.tools.mesh_scaling "
                "--sweep 2,4,8 [--json]",
                file=sys.stderr,
            )
            sys.exit(2)
        sys.exit(sweep(counts, as_json="--json" in argv))
    try:
        shards = int(argv[0]) if argv else 8
    except ValueError:
        print(
            "usage: python -m orientdb_tpu.tools.mesh_scaling "
            "[SHARDS | --sweep 2,4,8 [--json]]",
            file=sys.stderr,
        )
        sys.exit(2)
    main(shards)

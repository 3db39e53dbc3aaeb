"""Sharded config-5 probe: the SNB-interactive-shaped graph executed on
a virtual S-device mesh (VERDICT r4 #2's "sharded sub-block").

Run as a subprocess so the CPU device count can be forced:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=S \
        python -m orientdb_tpu.tools.sharded_sf S N_PERSONS

Builds `storage.bigshape.build_snb_shape` (Person-knows with a
creationDate EDGE column + Message-hasCreator), shards it over the mesh
(adjacency + property columns row-sharded, O(E/S) per device —
`ops/device_graph.py`), checks the multi-pattern edge-property-WHERE
COUNT against the exact numpy reference, and prints ONE JSON line:

    {"shards": S, "persons": P, "knows_edges": E,
     "per_device_hbm": {...}, "config5_qps": Q, "wall_s": T}
"""

from __future__ import annotations

import json
import sys
import time

CONFIG5_SQL = (
    "MATCH {class:Person, as:p, where:(age > 40)}"
    ".outE('knows'){where:(creationDate > :d)}"
    ".inV(){as:f, where:(age < 30)}, "
    "{class:Message, as:m}-hasCreator->{as:f} "
    "RETURN count(*) AS n"
)


def main(shards: int, n_persons: int) -> None:
    from orientdb_tpu.ops.device_graph import device_graph
    from orientdb_tpu.parallel.sharded import make_mesh
    from orientdb_tpu.storage.bigshape import (
        build_snb_shape,
        numpy_config5_count,
    )

    db, snap = build_snb_shape(
        n_persons, msgs_per_person=2, avg_knows=10, seed=7
    )
    snap._mesh = make_mesh(shards, replicas=1)
    t0 = time.perf_counter()
    # parity gate (compiles the sharded plan as a side effect)
    d0 = 15_000
    got = db.query(
        CONFIG5_SQL, params={"d": d0}, engine="tpu", strict=True
    ).to_dicts()
    want = numpy_config5_count(snap, d0)
    if got != [{"n": want}]:
        print(
            json.dumps(
                {"shards": shards, "error": f"parity: {got} != {want}"}
            )
        )
        sys.exit(1)
    # timed replays across parameter values (plan is parameter-generic)
    n_queries = 8
    t1 = time.perf_counter()
    for i in range(n_queries):
        d = 12_000 + (i * 911) % 7000
        rows = db.query(
            CONFIG5_SQL, params={"d": d}, engine="tpu", strict=True
        ).to_dicts()
        assert rows and "n" in rows[0]
    dt = time.perf_counter() - t1
    rep = device_graph(snap).memory_report()
    print(
        json.dumps(
            {
                "shards": shards,
                "persons": int(n_persons),
                "knows_edges": int(snap.edge_classes["knows"].num_edges),
                "per_device_hbm": rep["per_device"],
                "config5_qps": round(n_queries / dt, 3),
                "wall_s": round(time.perf_counter() - t0, 1),
            }
        )
    )


if __name__ == "__main__":
    from orientdb_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(
        int(sys.argv[1]) if len(sys.argv) > 1 else 8,
        int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000,
    )

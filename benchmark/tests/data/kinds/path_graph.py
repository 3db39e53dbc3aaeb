"""The smallest kinds module (``benchmark/kinds/README.md``): a path
graph. ``Node`` vertices in one chain of ``next`` edges, whose order is
dealt by the seed; one kind of read (a node's successor), one root
measure (a node's place in the chain), one planted fault (the chain
reversed). ``benchmark/tests/test_kinds.py`` drives it through
``run.run_cell`` from the files under ``benchmark/tests/data/`` alone.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Raw:
    N: int
    order: np.ndarray  # int64[N]: the uids along the chain, first to last


def make_raw(scale: dict, seed: int) -> Raw:
    """Every seed gets the same chain of ``nodes`` in another order."""
    n = int(scale["nodes"])
    return Raw(N=n, order=np.random.default_rng(int(seed)).permutation(n))


def attach(raw: Raw, name: str = "path"):
    """The chain through the program's record API, snapshotted as the
    program snapshots any database. Returns ``(db, snap)``."""
    from orientdb_tpu.models.database import Database
    from orientdb_tpu.storage.snapshot import build_snapshot

    db = Database(name)
    db.schema.create_vertex_class("Node")
    db.schema.create_edge_class("next")
    nodes = {int(u): db.new_vertex("Node", uid=int(u)) for u in raw.order}
    for a, b in zip(raw.order[:-1], raw.order[1:]):
        db.new_edge("next", nodes[int(a)], nodes[int(b)])
    snap = build_snapshot(db)
    db.attach_snapshot(snap)
    return db, snap


def stale(raw: Raw, seed: int) -> Raw:
    """The chain as it stood before it was turned round: every ``next``
    edge points the other way."""
    return dataclasses.replace(raw, order=raw.order[::-1].copy())


class Reference:
    def __init__(self, raw: Raw) -> None:
        self.raw = raw
        self.place = np.empty(raw.N, np.int64)
        self.place[raw.order] = np.arange(raw.N)

    def next_rows(self, nodeId: int) -> list:
        at = int(self.place[nodeId]) + 1
        return [(int(self.raw.order[at]),)] if at < self.raw.N else []

    def answer(self, kind: str, params: dict) -> list:
        if kind != "next_rows":
            raise KeyError(f"no reference of kind {kind!r}")
        return self.next_rows(**{k: int(v) for k, v in params.items()})


class Measures:
    def __init__(self, ref: Reference) -> None:
        self.ref = ref

    def place(self) -> np.ndarray:
        return self.ref.place


def least_bytes(kind: str, raw: Raw) -> float:
    """Two int32 pointers, one neighbour id, one uid out."""
    if kind != "next_rows":
        raise KeyError(f"no byte count for reference kind {kind!r}")
    return 16.0

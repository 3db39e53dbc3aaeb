"""A path graph whose nodes bear names: the toy deployment in which a
parameter is a string beside an integer (``benchmark/tests/test_typed_params.py``
drives it through ``run.run_cell`` from the files under
``benchmark/tests/data/`` alone). ``Node`` vertices in one chain of
``next`` edges, as ``path_graph.py`` has them, each with a ``name`` from
a small dictionary; one kind of read (a node's successor, where the node
bears the name asked for), one measure over ``(nodeId, name)`` pairs,
one planted fault (every name moved one along the dictionary).
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: the dictionary of names: accents and an apostrophe ride the wire too
NAMES = ("Ada", "Bram", "Chloé", "Dmitri", "Eun-ji", "Farah", "O'Brien", "Zoë")


@dataclasses.dataclass
class Raw:
    N: int
    order: np.ndarray  # int64[N]: the uids along the chain, first to last
    name: np.ndarray  # int64[N]: each uid's name, an index into NAMES


def make_raw(scale: dict, seed: int) -> Raw:
    """Every seed gets the same chain and the same names, dealt in
    another order."""
    n = int(scale["nodes"])
    rng = np.random.default_rng(int(seed))
    return Raw(N=n, order=rng.permutation(n), name=rng.permutation(np.arange(n) % len(NAMES)))


def attach(raw: Raw, name: str = "named_path"):
    """The chain through the program's record API, snapshotted as the
    program snapshots any database. Returns ``(db, snap)``."""
    from orientdb_tpu.models.database import Database
    from orientdb_tpu.storage.snapshot import build_snapshot

    db = Database(name)
    db.schema.create_vertex_class("Node")
    db.schema.create_edge_class("next")
    nodes = {
        int(u): db.new_vertex("Node", uid=int(u), name=NAMES[int(raw.name[u])]) for u in raw.order
    }
    for a, b in zip(raw.order[:-1], raw.order[1:]):
        db.new_edge("next", nodes[int(a)], nodes[int(b)])
    snap = build_snapshot(db)
    db.attach_snapshot(snap)
    return db, snap


def stale(raw: Raw, seed: int) -> Raw:
    """The names as they stood before every node was renamed: each one
    the next in the dictionary."""
    return dataclasses.replace(raw, name=(raw.name + 1) % len(NAMES))


class Reference:
    def __init__(self, raw: Raw) -> None:
        self.raw = raw
        self.place = np.empty(raw.N, np.int64)
        self.place[raw.order] = np.arange(raw.N)

    def next_named_rows(self, nodeId: int, name: str) -> list:
        # the parameters arrive as the pool drew them: an int and a str
        if type(nodeId) is not int or type(name) is not str:
            raise TypeError(f"nodeId int and name str, not {nodeId!r}, {name!r}")
        if NAMES[int(self.raw.name[nodeId])] != name:
            return []
        at = int(self.place[nodeId]) + 1
        return [(int(self.raw.order[at]),)] if at < self.raw.N else []

    def answer(self, kind: str, params: dict) -> list:
        if kind != "next_named_rows":
            raise KeyError(f"no reference of kind {kind!r}")
        return self.next_named_rows(**params)


class Measures:
    def __init__(self, ref: Reference) -> None:
        self.ref = ref

    def named_place(self):
        """``(values, candidates)``: each node twice, with its own name
        and with the next one in the dictionary, as ``(nodeId, name)``
        tuples; the value is the node's place in the chain."""
        raw = self.ref.raw
        uids = np.repeat(np.arange(raw.N), 2)
        names = (raw.name[uids] + np.tile([0, 1], raw.N)) % len(NAMES)
        candidates = [(int(u), NAMES[int(k)]) for u, k in zip(uids, names)]
        return self.ref.place[uids], candidates


def least_bytes(kind: str, raw: Raw) -> float:
    """Two int32 pointers, one neighbour id, the name's code, one uid out."""
    if kind != "next_named_rows":
        raise KeyError(f"no byte count for reference kind {kind!r}")
    return 20.0

"""The benchmark's data: an SNB-shaped graph as plain numpy arrays, made
from ``--seed``, and its hand-over to the system under test.

``make_raw`` is the generator (the benchmark's own, so that no later PR
to the program changes the data a cell runs on). Its arrays are what the
numpy references in ``benchmark/reference.py`` read. ``attach`` lays the
same arrays out in the program's snapshot types and attaches them to a
schema-only ``Database``: the same columnar layout that
``storage/bigshape.build_snb_shape`` emits (persons first, messages
after them in one vertex index space; int32 CSR in both directions;
presence masks per class), which is the program's documented input
format for array-native graphs.

Distributions (``assumed`` in the configuration files): Poisson
``knows`` out-degrees with a few planted hubs, uniform targets, uniform
message creators (each drawn once per configuration and dealt out anew
by every seed, see ``make_raw``), uniform ``age`` 18-79, ``length`` 1-1999,
``creationDate`` 10 000-19 999 (days).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Raw:
    """One seeded graph. Vertex ids: persons ``[0, P)``, messages
    ``[P, P + M)``; ``uid`` is the vertex id."""

    P: int
    M: int
    knows_deg: np.ndarray  # int64[P] out-degree per person
    knows_dst: np.ndarray  # int32[E] target person, grouped by source
    knows_cdate: np.ndarray  # int32[E] knows.creationDate, same order
    creator: np.ndarray  # int32[M] creator person of message P + i
    age: np.ndarray  # int32[P]
    length: np.ndarray  # int32[M]

    @property
    def E(self) -> int:
        return int(self.knows_dst.shape[0])

    @property
    def V(self) -> int:
        return self.P + self.M


def _fixed_rng(cfg: dict) -> np.random.Generator:
    """The generator of what every seed shares: a function of the
    configuration's sizes alone."""
    key = [int(cfg[k]) for k in sorted(cfg) if isinstance(cfg[k], (int, float))]
    return np.random.default_rng([0x5EED] + key)


def make_raw(cfg: dict, seed: int) -> Raw:
    """The configuration's graph for one seed. ``cfg`` is the ``scale``
    object of a file under ``benchmark/configs/``.

    Every seed gets the same SIZES in another order: the multiset of
    out-degrees, the multiset of in-degrees and the multiset of messages
    per person are drawn once from the configuration; the seed decides
    which person gets which degree, which edge goes where, and every
    property. So E, V and the degree maxima, which are the shapes and the
    capacity buckets of the program's compiled plans, do not move with
    the seed, and no seed compiles what another seed has cached."""
    fixed = _fixed_rng(cfg)
    rng = np.random.default_rng(int(seed))
    P = int(cfg["persons"])
    M = P * int(cfg.get("msgs_per_person", 0))
    deg = fixed.poisson(float(cfg["avg_knows"]), P).astype(np.int64)
    hubs = int(cfg.get("supernodes", 0))
    if hubs > 0:
        deg[:hubs] = int(cfg["supernode_degree"])
    E = int(deg.sum())
    targets = fixed.integers(0, P, E, dtype=np.int32)  # fixes the in-degrees
    creators = fixed.integers(0, P, M, dtype=np.int32)
    # the seed: who holds which out-degree, who holds which in-degree (a
    # relabelling of the targets) and in which order the targets fall
    return Raw(
        P=P,
        M=M,
        knows_deg=rng.permutation(deg),
        knows_dst=rng.permutation(P).astype(np.int32)[rng.permutation(targets)],
        knows_cdate=rng.integers(10_000, 20_000, E, dtype=np.int32),
        creator=rng.permutation(P).astype(np.int32)[rng.permutation(creators)],
        age=rng.integers(18, 80, P, dtype=np.int32),
        length=rng.integers(1, 2000, M, dtype=np.int32),
    )


def _csr(name: str, V: int, edge_src: np.ndarray, dst: np.ndarray, P: int):
    """Both-direction int32 CSR (the layout ``storage/snapshot.EdgeClassCSR``
    documents) from an edge list grouped by source: ``edge_src`` ascending,
    ``dst`` the targets in the same order, every target a person (< P)."""
    from orientdb_tpu.storage.snapshot import EdgeClassCSR

    def indptr(counts):
        out = np.zeros(V + 1, np.int32)
        np.cumsum(counts, out=out[1 : counts.shape[0] + 1])
        out[counts.shape[0] + 1 :] = out[counts.shape[0]]
        return out

    csr = EdgeClassCSR(name)
    out_counts = np.bincount(edge_src, minlength=1)
    csr.indptr_out = indptr(out_counts)
    csr.dst = dst
    csr.out_degree_max = int(out_counts.max()) if dst.size else 0
    csr._edge_src = edge_src  # the program caches this; spare it the repeat
    order_in = np.argsort(dst, kind="stable").astype(np.int32)
    csr.src = edge_src[order_in]
    csr.edge_id_in = order_in
    in_counts = np.bincount(dst, minlength=P)
    csr.indptr_in = indptr(in_counts)
    csr.in_degree_max = int(in_counts.max()) if dst.size else 0
    csr.edge_rids = []
    return csr


def attach(raw: Raw, name: str = "snb"):
    """``raw`` as a schema-only ``Database`` with an attached snapshot.
    Returns ``(db, snap)``."""
    from orientdb_tpu.models.database import Database
    from orientdb_tpu.storage.snapshot import GraphSnapshot, PropertyColumn

    P, M, V = raw.P, raw.M, raw.V
    db = Database(name)
    db.schema.create_vertex_class("Person")
    db.schema.create_edge_class("knows")
    if M:
        db.schema.create_vertex_class("Message")
        db.schema.create_edge_class("hasCreator")

    knows = _csr(
        "knows",
        V,
        np.repeat(np.arange(P, dtype=np.int32), raw.knows_deg),
        raw.knows_dst,
        P,
    )
    knows.edge_columns = {
        "creationDate": PropertyColumn(
            "creationDate", "int", raw.knows_cdate, np.ones(raw.E, bool)
        )
    }

    snap = GraphSnapshot()
    snap.num_vertices = V
    cluster = {
        c: db.schema.get_class(c).cluster_ids[0]
        for c in (("Person", "Message") if M else ("Person",))
    }
    snap.v_cluster = np.full(V, cluster["Person"], np.int32)
    snap.v_position = np.arange(V, dtype=np.int32)
    if M:
        snap.v_cluster[P:] = cluster["Message"]
        snap.v_position[P:] -= P
    snap.rid_to_idx = {}

    classes = sorted(db.schema.classes(), key=lambda c: c.name)
    snap.class_names = [c.name for c in classes]
    snap.class_id_of = {c.name.lower(): i for i, c in enumerate(classes)}
    snap.v_class = np.full(V, snap.class_id_of["person"], np.int32)
    if M:
        snap.v_class[P:] = snap.class_id_of["message"]
    for c in classes:
        snap.class_closure[c.name.lower()] = np.array(
            sorted(
                snap.class_id_of[s.name.lower()]
                for s in c.subclasses(include_self=True)
            ),
            np.int32,
        )
    ranges = {"person": (0, P), "message": (P, V)}
    for c in classes:
        if c.is_vertex_type and not c.abstract:
            snap.class_vertex_range[c.name.lower()] = ranges.get(
                c.name.lower(), (0, 0)
            )

    is_person = np.zeros(V, bool)
    is_person[:P] = True
    age = np.zeros(V, np.int32)
    age[:P] = raw.age
    snap.v_columns = {
        "uid": PropertyColumn(
            "uid", "int", np.arange(V, dtype=np.int32), np.ones(V, bool)
        ),
        "age": PropertyColumn("age", "int", age, is_person),
    }
    snap.edge_classes["knows"] = knows
    if M:
        length = np.zeros(V, np.int32)
        length[P:] = raw.length
        snap.v_columns["length"] = PropertyColumn(
            "length", "int", length, ~is_person
        )
        snap.edge_classes["hasCreator"] = _csr(
            "hasCreator", V, np.arange(P, V, dtype=np.int32), raw.creator, P
        )
    for c in classes:
        if c.is_edge_type:
            snap.edge_closure[c.name.lower()] = sorted(
                s.name
                for s in c.subclasses(include_self=True)
                if s.name in snap.edge_classes
            )
    snap.epoch = db.mutation_epoch
    db.attach_snapshot(snap)
    return db, snap

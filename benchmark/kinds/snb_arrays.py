"""SNB-shaped graphs as plain arrays: everything the benchmark knows
about this kind of deployment (the contract is ``benchmark/kinds/README.md``).

**The data.** ``make_raw`` makes an SNB-shaped graph as plain numpy
arrays from ``--seed`` (the benchmark's own generator, so that no later
PR to the program changes the data a cell runs on). Its arrays are what
the numpy references below read. ``attach`` lays the same arrays out in
the program's snapshot types and attaches them to a schema-only
``Database``: the same columnar layout that
``storage/bigshape.build_snb_shape`` emits (persons first, messages
after them in one vertex index space; int32 CSR in both directions;
presence masks per class), which is the program's documented input
format for array-native graphs. No index is declared.

Distributions (``assumed`` in the configuration files): Poisson
``knows`` out-degrees with a few planted hubs, uniform targets, uniform
message creators (each drawn once per configuration and dealt out anew
by every seed, see ``make_raw``), uniform ``age`` 18-79, ``length`` 1-1999,
``creationDate`` 10 000-19 999 (days).

**The references.** Plain references: exact integer and row arithmetic
in numpy over the seeded arrays (``Raw``). ``Reference``, ``Measures``
and ``least_bytes`` import nothing of the program and read nothing the
program made.

One method per *kind* of statement; a traffic file names the kind of
each of its shapes (``"reference": "<kind>"``), so a new mix of these
kinds needs no code. Every method takes the request's parameters by
the names the statement uses and returns the rows a client must see, as
tuples in the column order the traffic file states.

The COUNT kinds are the arithmetic of ``storage/bigshape.numpy_*``
(PR 22 proved those against the chip at 8 M persons), written over the
raw edge list; the rooted kind is CSR slicing. ``-knows-`` walks an
edge both ways, and parallel edges count once each (``count(*)`` counts
paths, rows are a multiset).

**The planted fault.** ``stale``: the snapshot of one batch of updates
ago, which must make ``correct`` come out false.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np

#: a scale at which ``make_raw`` and the measures take well under a
#: second on a CPU: the benchmark's own tests plan every cell of this
#: module at it (``benchmark/kinds/README.md``)
SMALL = {"persons": 200, "avg_knows": 6, "msgs_per_person": 12, "supernodes": 2, "supernode_degree": 40}


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


def stale(raw: Raw, seed: int, share: float = 0.001) -> Raw:
    """``raw`` as it stood one batch of updates ago: one ``knows`` target
    and one message creator in a thousand differ from the data, where
    the configurations state reads of THE immutable snapshot, exact."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0x57A1E])
    out = dataclasses.replace(
        raw, knows_dst=raw.knows_dst.copy(), creator=raw.creator.copy()
    )
    for arr in (out.knows_dst, out.creator):
        if arr.size:
            n = max(1, int(arr.size * share))
            at = rng.choice(arr.size, n, replace=False)
            arr[at] = (arr[at] + 1 + rng.integers(0, raw.P - 1, n)) % raw.P
    return out


def _indptr(counts: np.ndarray) -> np.ndarray:
    out = np.zeros(counts.shape[0] + 1, np.int64)
    np.cumsum(counts, out=out[1:])
    return out


def _seg_sum(vals: np.ndarray, indptr: np.ndarray) -> np.ndarray:
    tot = np.zeros(vals.shape[0] + 1, np.int64)
    np.cumsum(vals, dtype=np.int64, out=tot[1:])
    return tot[indptr[1:]] - tot[indptr[:-1]]


class Reference:
    """The references of one graph. Derived arrays (the in-direction of
    ``knows``, messages per creator, the tables the scans sum
    over) are built once, on first use."""

    def __init__(self, raw: Raw) -> None:
        self.raw = raw
        self.out_ptr = _indptr(raw.knows_deg)
        self._lazy: dict = {}

    # -- derived arrays -----------------------------------------------------

    def _get(self, key: str, build):
        if key not in self._lazy:
            self._lazy[key] = build()
        return self._lazy[key]

    @property
    def edge_src(self) -> np.ndarray:
        return self._get(
            "edge_src",
            lambda: np.repeat(
                np.arange(self.raw.P, dtype=np.int32), self.raw.knows_deg
            ),
        )

    @property
    def knows_in(self):
        """(indptr, sources) of ``knows`` grouped by target."""

        def build():
            dst = self.raw.knows_dst
            order = np.argsort(dst, kind="stable")
            ptr = _indptr(np.bincount(dst, minlength=self.raw.P))
            return ptr, self.edge_src[order]

        return self._get("knows_in", build)

    @property
    def msg_count(self) -> np.ndarray:
        return self._get(
            "msg_count",
            lambda: np.bincount(
                self.raw.creator, minlength=self.raw.P
            ).astype(np.int64),
        )

    @property
    def len_age_table(self) -> np.ndarray:
        """``t[l, a]``: messages of length ``l`` whose creator is ``a``
        years old, so that a (minLen, maxAge) COUNT is a table sum."""

        def build():
            r = self.raw
            key = r.length.astype(np.int64) * 128 + r.age[r.creator]
            return np.bincount(key, minlength=2048 * 128).reshape(2048, 128)

        return self._get("len_age_table", build)

    def neighbours(self, p: int) -> np.ndarray:
        """Both directions of ``knows`` at ``p``: out-targets, then
        in-sources; a parallel or mutual edge appears once per edge."""
        ptr_in, src_in = self.knows_in
        return np.concatenate(
            [
                self.raw.knows_dst[self.out_ptr[p] : self.out_ptr[p + 1]],
                src_in[ptr_in[p] : ptr_in[p + 1]],
            ]
        ).astype(np.int64)

    def degree_both(self) -> np.ndarray:
        """Undirected degree per person (the 1-hop's result size)."""
        ptr_in, _ = self.knows_in
        return self.raw.knows_deg + np.diff(ptr_in)

    # -- whole-graph COUNT kinds ----------------------------------------------

    def config5_count(self, minAge: int, d: int, maxAge: int) -> list:
        """Σ over knows edges p→f with age(p) > minAge, creationDate > d,
        age(f) < maxAge, of the number of messages f created."""
        r = self.raw
        f = r.knows_dst
        w = ((r.age[f] < maxAge) & (r.knows_cdate > d)) * self.msg_count[f]
        per_src = _seg_sum(w, self.out_ptr)
        return [(int(per_src[r.age > minAge].sum()),)]

    def creator_1hop_count(self, minLen: int, maxAge: int) -> list:
        """Messages longer than minLen whose creator is under maxAge."""
        t = self.len_age_table
        return [(int(t[max(minLen + 1, 0) :, : max(maxAge, 0)].sum()),)]

    def knows_1hop_count(self, minAge: int, maxAge: int) -> list:
        r = self.raw
        w = _seg_sum(r.age[r.knows_dst] < maxAge, self.out_ptr)
        return [(int(w[r.age > minAge].sum()),)]

    def knows_2hop_count(self, minAge: int, maxAge: int) -> list:
        r = self.raw
        w2 = _seg_sum(r.age[r.knows_dst] < maxAge, self.out_ptr)
        w1 = _seg_sum(w2[r.knows_dst], self.out_ptr)
        return [(int(w1[r.age > minAge].sum()),)]

    # -- rooted kinds -----------------------------------------------------------

    def friends_rows(self, personId: int) -> list:
        """(uid, age) of every friend, either direction."""
        f = self.neighbours(personId)
        return list(zip(f.tolist(), self.raw.age[f].tolist()))

    def answer(self, kind: str, params: dict) -> list:
        fn = getattr(self, kind, None)
        if fn is None or kind.startswith("_"):
            raise KeyError(f"no reference of kind {kind!r}")
        return fn(**{k: int(v) for k, v in params.items()})


class Measures:
    """Per-person counts a root may be curated by, from the reference's
    arrays (never from the program's)."""

    def __init__(self, ref) -> None:
        self.ref = ref

    def degree_both(self) -> np.ndarray:
        return self.ref.degree_both()


def least_bytes(kind: str, raw: Raw) -> float:
    """Bytes the *query* needs for one request of a reference kind on a
    graph of ``P`` persons, ``M`` messages and ``E`` directed ``knows``
    edges: every int32 CSR array and column the statement must read,
    once, and every result value written, once. A function of the
    graph's sizes alone: never of what the present kernels move.

    Rooted kinds are reckoned at the graph's mean degrees
    (``d = E / P`` out, ``2 d`` both ways), which the curated roots stay
    near."""
    P, M, E = raw.P, raw.M, raw.E
    d = E / P
    w = 4  # every id, pointer and property column is int32
    table = {
        # knows: indptr, dst, creationDate; age of both ends; messages per
        # person = the in-direction pointers of hasCreator over persons
        "config5_count": w * (P + 2 * E + P + P) + w,
        # length of every message, its creator, the creators' ages
        "creator_1hop_count": w * (2 * M + P) + w,
        "knows_1hop_count": w * (P + E + P) + w,
        # the second hop's per-vertex weights must be complete before the
        # first hop sums them: the edge list is read twice
        "knows_2hop_count": w * (2 * P + 2 * E + P) + w,
        # two pointer pairs, 2d neighbour ids, their ages; 2 values a row out
        "friends_rows": w * (4 + 2 * d + 2 * d) + w * 4 * d,
    }
    if kind not in table:
        raise KeyError(f"no byte count for reference kind {kind!r}")
    return float(table[kind])

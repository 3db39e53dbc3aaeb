"""Graph500 Kronecker graphs searched breadth-first: everything the
benchmark knows about this kind of deployment (the contract is
``benchmark/kinds/README.md``).

LDBC Graphalytics, algorithm BFS: for a source vertex, the depth of
every vertex reachable from it, on the dataset ``graph500-<scale>``;
Graph500's kernel 2 is this search on this generator, one search at a
time from keys drawn uniformly among the vertices with an edge.

**The data.** ``make_raw``: the Kronecker edge list of Graph500's
reference generator (``2**scale`` labels, ``edge_factor`` edges a label,
initiator ``a, b, c``; bit by bit ``ii_bit = rand > a + b``, ``jj_bit =
rand > (c / (1 - a - b) if ii_bit else a / (a + b))``, float32 draws),
self-loops dropped, pairs made unordered and unique, vertices without an
edge dropped and the rest relabelled ``0..V-1``; each pair stored once,
as a directed ``Link`` whose direction is a coin kept with the pair. The
graph comes from the generator seed the configuration's ``scale`` keeps
(``graph_seed``): every ``--seed`` gets the same graph, so the same V, E
and degrees; ``--seed`` deals the permutation of the vertex labels and
nothing else.

**Its kind.** One: ``bfs_level_counts`` (``source`` -> one row a depth,
``(depth, n)``: the vertices at that depth, the source's own at 0; no
row where no vertex has the label).

**The reference.** ``Reference``: a level-synchronous search in numpy
over this module's own adjacency (both directions, built from ``raw``'s
edge list), new vertices by a boolean mask; imports nothing of the
program.

**Its measure.** ``degree``: the undirected degree of every vertex (all
at least 1), from the graph alone.

**The planted fault.** ``stale``: the snapshot of one batch of loads
ago, in which a seeded tenth of the vertices had no edges yet.
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

#: the mix's statement, and the hand-over's one question
STATEMENT = (
    "SELECT $depth AS depth, count(*) AS n "
    "FROM (TRAVERSE both('Link') FROM (SELECT FROM Node WHERE uid = :source) "
    "STRATEGY BREADTH_FIRST) GROUP BY $depth"
)
#: the scale of the graph the hand-over's question is asked of
QUESTION_SCALE = 8
#: edges a generator task draws at once (the tasks' streams are the
#: generator seed's children by task index, so the graph does not depend
#: on how many threads draw them)
GEN_CHUNK = 1 << 22
#: the share of vertices the planted fault leaves without edges
STALE_SHARE = 0.1
#: a scale at which ``make_raw`` and the measure take well under a second
#: on a CPU: the dataset's generator at scale 8 (``QUESTION_SCALE``); the
#: benchmark's own tests plan every cell of this module at it
#: (``benchmark/kinds/README.md``)
SMALL = {"scale": QUESTION_SCALE, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19, "graph_seed": 2022}


@dataclass
class Raw:
    """One seeded labelling of the configuration's graph: ``E`` directed
    ``Link`` edges ``src[e] -> dst[e]`` in out-order (ascending ``src``,
    then ``dst``), one a pair of neighbours; ``uid`` is the vertex id."""

    cfg: dict  # the generator's parameters: the configuration's ``scale``
    V: int
    src: np.ndarray  # int32[E]
    dst: np.ndarray  # int32[E]
    degree: np.ndarray  # int64[V] undirected

    @property
    def E(self) -> int:
        return int(self.dst.shape[0])


def _kronecker(cfg: dict, first: int, count: int) -> tuple:
    """``count`` edges of the configuration's generator, the chunk that
    starts at edge ``first``: Graph500's reference generator, bit by bit."""
    a, b, c = float(cfg["a"]), float(cfg["b"]), float(cfg["c"])
    ab = np.float32(a + b)
    c_norm, a_norm = np.float32(c / (1.0 - (a + b))), np.float32(a / (a + b))
    rng = np.random.default_rng([int(cfg["graph_seed"]), first // GEN_CHUNK])
    ii = np.zeros(count, np.int32)
    jj = np.zeros(count, np.int32)
    for bit in range(int(cfg["scale"])):
        ii_bit = rng.random(count, dtype=np.float32) > ab
        jj_bit = rng.random(count, dtype=np.float32) > np.where(ii_bit, c_norm, a_norm)
        ii += ii_bit.astype(np.int32) << bit
        jj += jj_bit.astype(np.int32) << bit
    return ii, jj


def _coin(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """The direction a pair is stored in: a bit of a hash of its two
    generator labels, so every copy of a pair falls the same way."""
    x = lo.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + hi.astype(np.uint64)
    x ^= x >> np.uint64(31)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    return ((x >> np.uint64(40)) & np.uint64(1)).astype(bool)


def make_raw(cfg: dict, seed: int) -> Raw:
    """The configuration's graph under one seed's labels. ``cfg`` is the
    ``scale`` object of a file under ``benchmark/configs/``.

    Every seed gets the same SIZES in another order: the pairs, their
    directions and so V, E and every degree come from ``graph_seed``; the
    seed permutes the labels of the vertices that have an edge."""
    N = 1 << int(cfg["scale"])
    M = int(cfg["edge_factor"]) * N
    firsts = range(0, M, GEN_CHUNK)
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        chunks = list(
            pool.map(lambda f: _kronecker(cfg, f, min(GEN_CHUNK, M - f)), firsts)
        )
    ii = np.concatenate([c[0] for c in chunks])
    jj = np.concatenate([c[1] for c in chunks])
    del chunks
    keep = ii != jj
    lo, hi = np.minimum(ii, jj)[keep], np.maximum(ii, jj)[keep]
    del ii, jj, keep
    # generator label -> this seed's label, over the labels with an edge
    present = np.zeros(N, bool)
    present[lo] = True
    present[hi] = True
    V = int(present.sum())
    label = np.full(N, -1, np.int64)
    label[present] = np.random.default_rng(int(seed)).permutation(V)
    flip = _coin(lo, hi)
    s = label[np.where(flip, hi, lo)]
    d = label[np.where(flip, lo, hi)]
    del lo, hi, flip, label, present
    # one sort: the pairs once each, in out-order
    key = np.unique(s * V + d)
    del s, d
    src = (key // V).astype(np.int32)
    dst = (key % V).astype(np.int32)
    degree = np.bincount(src, minlength=V) + np.bincount(dst, minlength=V)
    return Raw(cfg=dict(cfg), V=V, src=src, dst=dst, degree=degree.astype(np.int64))


def _in_order(dst: np.ndarray) -> np.ndarray:
    """``argsort(dst, kind="stable")`` as one sort of packed int64 keys
    (the target above the edge's position), which numpy sorts several
    times faster than it argsorts."""
    E = dst.shape[0]
    shift = max(int(E).bit_length(), 1)
    key = (dst.astype(np.int64) << shift) | np.arange(E, dtype=np.int64)
    key.sort()
    return (key & ((1 << shift) - 1)).astype(np.int32)


def _indptr(counts: np.ndarray, dtype) -> np.ndarray:
    out = np.zeros(counts.shape[0] + 1, dtype)
    np.cumsum(counts, out=out[1:])
    return out


def _handed_over(raw: Raw, name: str):
    """``raw`` as a schema-only ``Database`` with an attached snapshot in
    the program's documented array types (``storage/snapshot``: one
    vertex index space, int32 CSR in both directions, a presence mask a
    column), as ``snb_arrays.attach`` builds its own. No index is
    declared."""
    from orientdb_tpu.models.database import Database
    from orientdb_tpu.storage.snapshot import (
        EdgeClassCSR,
        GraphSnapshot,
        PropertyColumn,
    )

    V = raw.V
    db = Database(name)
    db.schema.create_vertex_class("Node")
    db.schema.create_edge_class("Link")

    link = EdgeClassCSR("Link")
    out_counts = np.bincount(raw.src, minlength=V)
    in_counts = np.bincount(raw.dst, minlength=V)
    link.indptr_out = _indptr(out_counts, np.int32)
    link.dst = raw.dst
    link.out_degree_max = int(out_counts.max()) if raw.E else 0
    link._edge_src = raw.src  # the program caches this; spare it the repeat
    order_in = _in_order(raw.dst)
    link.src = raw.src[order_in]
    link.edge_id_in = order_in
    link.indptr_in = _indptr(in_counts, np.int32)
    link.in_degree_max = int(in_counts.max()) if raw.E else 0
    link.edge_rids = []
    link.edge_columns = {}

    snap = GraphSnapshot()
    snap.num_vertices = V
    snap.v_cluster = np.full(V, db.schema.get_class("Node").cluster_ids[0], np.int32)
    snap.v_position = np.arange(V, dtype=np.int32)
    snap.rid_to_idx = {}
    classes = sorted(db.schema.classes(), key=lambda c: c.name)
    snap.class_names = [c.name for c in classes]
    snap.class_id_of = {c.name.lower(): i for i, c in enumerate(classes)}
    snap.v_class = np.full(V, snap.class_id_of["node"], np.int32)
    for c in classes:
        snap.class_closure[c.name.lower()] = np.array(
            sorted(
                snap.class_id_of[s.name.lower()]
                for s in c.subclasses(include_self=True)
            ),
            np.int32,
        )
        if c.is_vertex_type and not c.abstract:
            snap.class_vertex_range[c.name.lower()] = (
                (0, V) if c.name == "Node" else (0, 0)
            )
    snap.v_columns = {
        "uid": PropertyColumn(
            "uid", "int", np.arange(V, dtype=np.int32), np.ones(V, bool)
        )
    }
    snap.edge_classes["Link"] = link
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


def ask(cfg: dict) -> None:
    """The hand-over's one question, asked of a 256-label graph from the
    same generator handed over by the same code: the mix's statement
    from one root, compared with the reference's rows. A program that
    does not answer it does not run this configuration, and the run ends
    here with an exit code instead of a result line: before the large
    snapshot is built, so in seconds. What is asked is the answer, never
    how the program finds it."""
    small = make_raw({**cfg, "scale": QUESTION_SCALE}, 0)
    root = int(np.argmax(small.degree))
    want = Reference(small).answer("bfs_level_counts", {"source": root})
    db, _snap = _handed_over(small, "g500_question")
    try:
        got = db.query(STATEMENT, {"source": root}).to_dicts()
    finally:
        db.detach_snapshot()
    if sorted((r.get("depth"), r.get("n")) for r in got) != sorted(want):
        raise SystemExit(
            "benchmark: graph500 needs a program that counts a TRAVERSE by "
            f"$depth on this graph; from uid {root} the reference reads "
            f"{sorted(want)} and this one answers {got}"
        )


def attach(raw: Raw, name: str = "g500"):
    """The hand-over: the one question first (``ask``), then ``raw`` as a
    ``Database`` with its snapshot attached. Returns ``(db, snap)``."""
    ask(raw.cfg)
    return _handed_over(raw, name)


def stale(raw: Raw, seed: int, share: float = STALE_SHARE) -> Raw:
    """``raw`` as it stood one batch of loads ago: a seeded tenth of the
    vertices, the newest, had no edge yet, where the configuration
    states reads of THE immutable snapshot, every count exact. A search
    misses them and whatever only they lead to."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0x57A1E])
    late = np.zeros(raw.V, bool)
    late[rng.choice(raw.V, max(1, int(raw.V * share)), replace=False)] = True
    keep = ~(late[raw.src] | late[raw.dst])
    src, dst = raw.src[keep], raw.dst[keep]
    degree = np.bincount(src, minlength=raw.V) + np.bincount(dst, minlength=raw.V)
    return dataclasses.replace(raw, src=src, dst=dst, degree=degree.astype(np.int64))


class Reference:
    """The reference of one graph: ``Link`` walked both ways, as a
    vertex's out-targets (``raw``'s own order) and in-sources (this
    class's own grouping by target)."""

    #: a level whose frontier has more edge ends than this share of all
    #: reads the edge list once a direction instead of the frontier's lists
    list_share = 1 / 8

    def __init__(self, raw: Raw) -> None:
        self.raw = raw
        V = raw.V
        self.out_ptr = _indptr(np.bincount(raw.src, minlength=V), np.int64)
        self.in_ptr = _indptr(np.bincount(raw.dst, minlength=V), np.int64)
        self.in_src = raw.src[_in_order(raw.dst)]

    def _lists(self, ptr: np.ndarray, nbrs: np.ndarray, people: np.ndarray):
        lo = ptr[people]
        n = ptr[people + 1] - lo
        at = np.arange(int(n.sum())) - np.repeat(np.cumsum(n) - n, n)
        return nbrs[np.repeat(lo, n) + at]

    def depths(self, source: int) -> np.ndarray:
        """``int32[V]``: every vertex's depth from ``source``, -1 where
        there is no path."""
        raw = self.raw
        depth = np.full(raw.V, -1, np.int32)
        if not 0 <= source < raw.V:
            return depth
        depth[source] = 0
        frontier, level = np.array([source], np.int64), 0
        while frontier.size:
            reached = np.zeros(raw.V, bool)
            if raw.degree[frontier].sum() > self.list_share * 2 * raw.E:
                at = np.zeros(raw.V, bool)
                at[frontier] = True
                reached[raw.dst[at[raw.src]]] = True
                reached[raw.src[at[raw.dst]]] = True
            else:
                reached[self._lists(self.out_ptr, raw.dst, frontier)] = True
                reached[self._lists(self.in_ptr, self.in_src, frontier)] = True
            reached &= depth < 0
            frontier = np.flatnonzero(reached)
            level += 1
            depth[frontier] = level
        return depth

    def bfs_level_counts(self, source: int) -> list:
        depth = self.depths(source)
        return list(enumerate(np.bincount(depth[depth >= 0]).tolist()))

    def answer(self, kind: str, params: dict) -> list:
        if kind != "bfs_level_counts":
            raise KeyError(f"no reference of kind {kind!r}")
        return self.bfs_level_counts(**{k: int(v) for k, v in params.items()})


class Measures:
    """What a root may be curated by, from the graph alone."""

    def __init__(self, ref: Reference) -> None:
        self.ref = ref

    def degree(self) -> np.ndarray:
        return self.ref.raw.degree


def least_bytes(kind: str, raw: Raw) -> float:
    """Bytes the *query* needs for one search, a function of the graph's
    sizes alone and reckoned low on purpose: every edge's two ends once
    (int32), both pointer arrays once, a visited bit of every vertex
    read and written. What the present kernel moves (a level's pass over
    every edge, the prefix sums) is no part of it."""
    if kind != "bfs_level_counts":
        raise KeyError(f"no byte count for reference kind {kind!r}")
    return float(8 * raw.E + 8 * (raw.V + 1) + raw.V / 4)

"""Shortest paths over SNB's friendship graph: everything the benchmark
knows about this kind of deployment (the contract is
``benchmark/kinds/README.md``).

LDBC SNB Interactive complex read 13: given two persons, the length of
the shortest path between them over ``knows``, walked both ways; 0 for
the same person, -1 where there is none. The query reads ``Person`` and
``knows`` and nothing else, so the friendship graph is the part of a
scale factor it touches.

**The data.** The arrays of ``snb_arrays.py`` (its ``make_raw`` with no
messages, laid out and attached by its ``attach``): persons ``[0, P)``,
Poisson ``knows`` out-degrees with a few planted hubs, uniform targets,
every seed the same sizes in another order.

**Its kind.** One: ``shortest_path_len`` (``person1Id``, ``person2Id``
-> one row, one column, the length).

**The reference.** ``Reference``: a level-synchronous breadth-first
search in numpy over this module's own undirected CSR, importing nothing
of the program. From the first person it pushes whole levels while a
level has few edges behind it, which settles every person that near;
the second person is then searched from its own end, level by level,
until a level meets a person the first search has settled (the sum of
the two depths is the length, see ``Reference.lens``) or has nothing
left (-1). One source's push serves many targets, which is how the
measure below prices tens of thousands of pairs in seconds; what it
priced is remembered, so comparing a window looks its pairs up.

**Its measure.** ``pair_distance``: ``(values, candidates)`` over
*pairs*: a fixed set of ``(person1Id, person2Id)`` dealt from the data
(never from ``--seed``: the seed deals the graph and permutes the pool)
among the persons of the middle fifth of undirected degree, the two
different, every pair once, drawn uniformly and so at the graph's own
distribution of distances; the value is the pair's distance, so a band
``[0, 1]`` keeps every pair and the longest search leads the pool.

**The planted fault.** ``stale``: the snapshot of one batch of updates
ago, in which the newest tenth of the persons had no friendships yet.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import sys

import numpy as np


def _sibling(name: str):
    """``benchmark/kinds/<name>.py``, loaded by path as ``run.load_kinds``
    loads this file."""
    key = f"kinds_{name}"
    if key not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


_arrays = _sibling("snb_arrays")
Raw = _arrays.Raw
make_raw = _arrays.make_raw


#: the hand-over's one question (the mix's statement)
ADJACENT = (
    "MATCH {class:Person, as:a, where:(uid = :person1Id)}, "
    "{class:Person, as:b, where:(uid = :person2Id)} "
    "RETURN shortestPath(a, b, 'BOTH', 'knows').size() - 1 AS len"
)


def attach(raw: Raw, name: str = "snb"):
    """``snb_arrays.attach``, and one question the data answers: the two
    ends of a ``knows`` edge are one step apart.

    A program that cannot search an array-native graph still takes the
    statement (PR 31's parent binds the two persons on the device and
    evaluates ``shortestPath`` on the host over records, of which this
    graph has none: every length reads -1 at the rate of a rooted
    lookup). That is no run of this configuration, so the hand-over ends
    it at once with an exit code instead of a result line. What is asked
    is the answer, not how the program finds it."""
    db, snap = _arrays.attach(raw, name)
    src = np.repeat(np.arange(raw.P, dtype=np.int32), raw.knows_deg)
    edges = np.flatnonzero(src != raw.knows_dst)
    if edges.size:
        ends = {"person1Id": int(src[edges[0]]), "person2Id": int(raw.knows_dst[edges[0]])}
        got = db.query(ADJACENT, ends).to_dicts()
        if got != [{"len": 1}]:
            raise SystemExit(
                f"benchmark: snb_paths needs a program that searches this graph; "
                f"{ends} share an edge and this one answers {got}"
            )
    return db, snap


#: sources of the measure's pairs, and targets a source
PAIR_SOURCES = 256
PAIR_TARGETS = 128
#: the share of persons the planted fault leaves without friendships
STALE_SHARE = 0.1
#: a scale at which ``make_raw`` and ``pair_distance`` take well under a
#: second on a CPU: the benchmark's own tests plan every cell of this
#: module at it (``benchmark/kinds/README.md``); no messages, as IC13 reads none
SMALL = {"persons": 200, "avg_knows": 6, "msgs_per_person": 0, "supernodes": 2, "supernode_degree": 40}


def stale(raw: Raw, seed: int, share: float = STALE_SHARE) -> Raw:
    """``raw`` as it stood one batch of updates ago: a seeded tenth of
    the persons, the newest, had not made their first friendship, so no
    ``knows`` edge leaves or reaches them, where the configuration
    states reads of THE immutable snapshot, every length exact. A pair
    with such an end reads -1 and many a path through one grows longer.
    (One ``knows`` target in a thousand, ``snb_arrays.stale``, moves no
    distance here: a pair at distance 3 has a dozen paths.)"""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0x57A1E])
    late = np.zeros(raw.P, bool)
    late[rng.choice(raw.P, max(1, int(raw.P * share)), replace=False)] = True
    src = np.repeat(np.arange(raw.P, dtype=np.int32), raw.knows_deg)
    keep = ~(late[src] | late[raw.knows_dst])
    return dataclasses.replace(
        raw,
        knows_deg=np.bincount(src[keep], minlength=raw.P).astype(np.int64),
        knows_dst=raw.knows_dst[keep],
        knows_cdate=raw.knows_cdate[keep],
    )


def _indptr(counts: np.ndarray) -> np.ndarray:
    out = np.zeros(counts.shape[0] + 1, np.int64)
    np.cumsum(counts, out=out[1:])
    return out


class Reference:
    """The reference of one graph: ``knows`` walked both ways (a
    person's out-targets and in-sources; a parallel or mutual edge
    appears once per edge, which moves no distance)."""

    #: the first person's search pushes a level only while the level has
    #: at most this many edges behind it
    push_limit = 200_000

    def __init__(self, raw: Raw) -> None:
        self.raw = raw
        P = raw.P
        src = np.repeat(np.arange(P, dtype=np.int32), raw.knows_deg)
        order = np.argsort(raw.knows_dst, kind="stable")
        #: (pointers, neighbours) of knows by source and by target
        self.csrs = (
            (_indptr(raw.knows_deg), raw.knows_dst),
            (_indptr(np.bincount(raw.knows_dst, minlength=P)), src[order]),
        )
        #: lengths the measure has priced: (person1Id, person2Id) -> length
        self.known: dict = {}

    def degree_both(self) -> np.ndarray:
        return sum(np.diff(ptr) for ptr, _ in self.csrs)

    def _behind(self, people: np.ndarray) -> int:
        return int(sum((ptr[people + 1] - ptr[people]).sum() for ptr, _ in self.csrs))

    def _lists(self, people: np.ndarray):
        """The neighbour lists of ``people`` (out-targets, then
        in-sources), and which of ``people`` each entry belongs to."""
        nbrs, owners = [], []
        for ptr, arr in self.csrs:
            lo = ptr[people]
            n = ptr[people + 1] - lo
            at = np.arange(int(n.sum())) - np.repeat(np.cumsum(n) - n, n)
            nbrs.append(arr[np.repeat(lo, n) + at])
            owners.append(np.repeat(np.arange(people.shape[0]), n))
        return np.concatenate(nbrs).astype(np.int64), np.concatenate(owners)

    def lens(self, source: int, targets) -> np.ndarray:
        """The length of the shortest path from ``source`` to each of
        ``targets`` (-1: none).

        The source's search settles every person within ``depth`` steps
        (``near[v]`` its distance, -1 beyond). A target's own search then
        walks levels ``k = 0, 1, ..``: the first level holding a settled
        person ``v`` gives ``k + min near[v]``. No earlier level could:
        on a shortest path of length ``d`` the person ``min(depth, d)``
        steps from the source is settled and lies ``d - min(depth, d)``
        steps from the target, and a settled person nearer the target
        would make a shorter path."""
        P = self.raw.P
        targets = np.asarray(targets, np.int64)
        near = np.full(P, -1, np.int64)
        near[source] = 0
        frontier, depth = np.array([source], np.int64), 0
        while (
            frontier.size
            and (near[targets] < 0).any()
            and self._behind(frontier) <= self.push_limit
        ):
            reached = np.unique(self._lists(frontier)[0])
            frontier = reached[near[reached] < 0]
            depth += 1
            near[frontier] = depth
        out = near[targets].copy()
        if not frontier.size or (out >= 0).all():
            return out  # nothing more to settle, or nothing more to ask
        open_ = np.flatnonzero(out < 0)
        # level 1 of every open target at once, then one target at a time
        reached, owner = self._lists(targets[open_])
        best = np.full(open_.shape[0], np.iinfo(np.int64).max)
        hit = near[reached] >= 0
        np.minimum.at(best, owner[hit], near[reached][hit] + 1)
        found = best < np.iinfo(np.int64).max
        out[open_[found]] = best[found]
        for i in open_[~found]:
            out[i] = self._from_target(int(targets[i]), near)
        return out

    def _from_target(self, target: int, near: np.ndarray) -> int:
        seen = np.zeros(self.raw.P, bool)
        seen[target] = True
        frontier, k = np.array([target], np.int64), 0
        while frontier.size:
            settled = near[frontier]
            if (settled >= 0).any():
                return k + int(settled[settled >= 0].min())
            reached = np.unique(self._lists(frontier)[0])
            frontier = reached[~seen[reached]]
            seen[frontier] = True
            k += 1
        return -1

    def shortest_path_len(self, person1Id: int, person2Id: int) -> list:
        key = (person1Id, person2Id)
        if key not in self.known:
            self.known[key] = int(self.lens(person1Id, [person2Id])[0])
        return [(self.known[key],)]

    def answer(self, kind: str, params: dict) -> list:
        if kind != "shortest_path_len":
            raise KeyError(f"no reference of kind {kind!r}")
        return self.shortest_path_len(**{k: int(v) for k, v in params.items()})


class Measures:
    """What a pair of persons may be curated by, from the reference's
    arrays (never from the program's)."""

    def __init__(self, ref: Reference) -> None:
        self.ref = ref

    def pair_distance(self, sources: int = PAIR_SOURCES, targets: int = PAIR_TARGETS):
        """``(values, candidates)``: the distance of each of ``sources ×
        targets`` pairs of different persons from the middle fifth of
        undirected degree, every pair once. Which pairs is a function of
        the graph alone."""
        ref = self.ref
        degree = ref.degree_both()
        lo, hi = np.quantile(degree, [0.4, 0.6])
        band = np.flatnonzero((degree >= lo) & (degree <= hi))
        rng = np.random.default_rng([0x1C13, ref.raw.P, ref.raw.E])
        sources = min(sources, band.size)
        targets = min(targets, band.size - 1)
        values, pairs = [], []
        for s in rng.choice(band, sources, replace=False):
            others = band[band != s]
            ts = rng.choice(others, targets, replace=False)
            d = ref.lens(int(s), ts)
            values.append(d)
            pairs.append(np.stack([np.full(targets, s), ts], axis=1))
            ref.known.update(
                ((int(s), int(t)), int(x)) for t, x in zip(ts.tolist(), d.tolist())
            )
        return np.concatenate(values), np.concatenate(pairs)


def least_bytes(kind: str, raw: Raw) -> float:
    """Bytes the *query* needs for one request: a function of the
    graph's sizes alone, reckoned low on purpose at the mean undirected
    degree ``D = 2 E / P`` and the typical distance 3: both ends'
    pointer pairs and lists, and one end's neighbours' pointer pairs and
    lists, every value int32, and the length out. What the present
    kernel reads (hub lists, a whole chunk) is no part of it."""
    if kind != "shortest_path_len":
        raise KeyError(f"no byte count for reference kind {kind!r}")
    D = 2.0 * raw.E / raw.P
    return float(4 * (2 * (2 + D) + D * (2 + D)) + 4)

"""``shortestPath`` compiled (LDBC SNB Interactive complex read 13): the
device search ``ops/csr.bfs_pair_len`` against a plain search for every
pair of seeded graphs with several components, a hub, a long chain, self
pairs and unreachable pairs, at capacities that drive every stage of it;
and the statement through the engine: compiled = plain reference =
oracle, by single dispatch and by lane batches of 1, 3 and 16."""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orientdb_tpu.exec import tpu_engine
from orientdb_tpu.exec.tpu_engine import Uncompilable, drain_warmups
from orientdb_tpu.models.database import Database
from orientdb_tpu.ops import csr as K
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot
from orientdb_tpu.utils.metrics import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counters(prefix="bfs."):
    c = metrics.snapshot()["counters"]
    return {k: v for k, v in c.items() if k.startswith(prefix)}


def _delta(after, before):
    return {k: after.get(k, 0) - before.get(k, 0) for k in after}


def seeded_edges(seed: int, V: int = 48, chain: int = 14, hub: int = 15, E: int = 96):
    """``E`` directed edges over ``V`` vertices: random ones among the
    first vertices, a hub (0) of out-degree ``hub``, four vertices with
    no edge at all (components of their own), and a chain over the last
    ``chain`` vertices hung on vertex 3: always the same sizes, so one
    compiled program a capacity serves every seed."""
    rng = np.random.default_rng(seed)
    body = V - chain
    links = np.arange(body, V)
    fixed = np.concatenate(
        [
            np.stack([np.zeros(hub, int), rng.integers(1, body - 4, hub)], 1),
            np.stack([links[:-1], links[1:]], 1),
            [[3, body]],
        ]
    )
    rand = rng.integers(1, body - 4, (E - len(fixed), 2))
    edges = np.concatenate([fixed, rand])
    return edges[np.argsort(edges[:, 0], kind="stable")]


def csr_of(V: int, edges: np.ndarray):
    """(indptr_out, dst, edge_src, indptr_in, src) as ``EdgeClassCSR`` has them."""
    es, ed = edges[:, 0].astype(np.int32), edges[:, 1].astype(np.int32)
    ipo = np.zeros(V + 1, np.int32)
    np.cumsum(np.bincount(es, minlength=V), out=ipo[1:])
    ipi = np.zeros(V + 1, np.int32)
    np.cumsum(np.bincount(ed, minlength=V), out=ipi[1:])
    return ipo, ed, es, ipi, es[np.argsort(ed, kind="stable")]


def plain_len(V: int, edges: np.ndarray, s: int, t: int) -> int:
    """The simplest search there is, over edges walked both ways."""
    nbrs = [[] for _ in range(V)]
    for a, b in edges.tolist():
        nbrs[a].append(b)
        nbrs[b].append(a)
    dist = {s: 0}
    frontier = [s]
    while frontier and t not in dist:
        nxt = []
        for u in frontier:
            for w in nbrs[u]:
                if w not in dist:
                    dist[w] = dist[u] + 1
                    nxt.append(w)
        frontier = nxt
    return dist.get(t, -1)


#: (front, chunk); an expansion reads a quarter of ``chunk`` an iteration
CAPS = {
    "tiny_buffers": (2, 4),
    "a_hub_outgrows_its_list": (8, 16),
    "everything_fits": (64, 256),
    "a_list_fits_and_a_frontier_does_not": (64, 8),
    "one_iteration_reads_every_frontier": (64, 4096),
}


class TestKernel:
    @pytest.mark.parametrize("case", sorted(CAPS))
    def test_every_pair_of_a_seeded_graph(self, case):
        front, chunk = CAPS[case]
        V = 48
        search = jax.jit(
            jax.vmap(
                lambda g, s, t: K.bfs_pair_len(*g, s, t, front=front, chunk=chunk),
                in_axes=(None, 0, 0),
            )
        )
        s, t = (x.reshape(-1).astype(np.int32) for x in np.mgrid[0:V, 0:V])
        seen = {part: set() for part in K.BFS_PARTS}
        for seed in (1, 2, 3):
            edges = seeded_edges(seed, V)
            got = np.asarray(search(tuple(map(jnp.asarray, csr_of(V, edges))), s, t))
            want = np.array(
                [plain_len(V, edges, int(a), int(b)) for a, b in zip(s, t)]
            )
            assert (got[:, 0] == want).all(), [
                (int(a), int(b), g.tolist(), int(w))
                for a, b, g, w in zip(s, t, got, want)
                if g[0] != w
            ][:5]
            for j, part in enumerate(K.BFS_PARTS):
                seen[part] |= set(got[:, j].tolist())
        # self pairs, adjacent pairs, unreachable pairs, and a chain longer
        # than the four levels the sparse stages settle
        assert {-1, 0, 1, 2, 3, 4, 5, 9} <= seen["len"]
        assert max(seen["dense_levels"]) >= 9
        if chunk >= 256:
            assert seen["overflow"] == {0}
        else:
            assert seen["overflow"] == {0, 1}
        if case != "tiny_buffers":
            # some pairs were settled by the third and fourth level alone
            assert {0, 2, 3, 4} <= seen["levels"]

    def test_a_missing_end_and_an_empty_graph(self):
        g = tuple(map(jnp.asarray, csr_of(6, np.array([[0, 1], [1, 2]]))))
        for s, t, want in ((-1, 2, -1), (0, -1, -1), (0, 2, 2), (9, 1, -1)):
            got = K.bfs_pair_len(*g, jnp.int32(s), jnp.int32(t), front=8, chunk=8)
            assert int(got[0]) == want
        none = tuple(map(jnp.asarray, csr_of(4, np.zeros((0, 2), int))))
        for s, t, want in ((1, 1, 0), (1, 2, -1)):
            got = K.bfs_pair_len(*none, jnp.int32(s), jnp.int32(t), front=8, chunk=8)
            assert got.tolist() == [want, 0, 0, 0, 0]

    def test_buffers_come_from_the_snapshots_degrees(self):
        class Csr:
            pass

        c = Csr()
        # a star of 100 leaves on vertex 0, and 900 vertices without edges
        c.indptr_out = np.concatenate([[0], np.full(1000, 100)]).astype(np.int32)
        c.indptr_in = np.concatenate([[0, 0], np.arange(1, 101), np.full(899, 100)]).astype(np.int32)
        front, chunk = tpu_engine._bfs_caps(c)
        # 101 vertices with edges, 200 edge ends: mean degree ~2, a
        # neighbour's mean degree (100² + 100) / 200 = 50.5
        assert front == 8 and chunk == 128
        c.indptr_out = c.indptr_in = np.zeros(5, np.int32)
        assert tpu_engine._bfs_caps(c) == (8, 8)


# -- the statement, through the engine ---------------------------------------------

IC13 = (
    "MATCH {class:Person, as:a, where:(uid = :person1Id)}, "
    "{class:Person, as:b, where:(uid = :person2Id)} "
    "RETURN shortestPath(a, b, 'BOTH', 'knows').size() - 1 AS len"
)
V = 30


def _kinds():
    path = os.path.join(ROOT, "benchmark", "kinds", "snb_paths.py")
    spec = importlib.util.spec_from_file_location("kinds_snb_paths_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graph():
    """One seeded graph three ways: records for the oracle, their
    snapshot for the compiled plan, plain arrays for the reference."""
    edges = seeded_edges(7, V, chain=10, hub=20, E=60)
    db = Database("ic13")
    db.schema.create_vertex_class("Person")
    db.schema.create_edge_class("knows")
    db.schema.create_edge_class("likes")
    people = [db.new_vertex("Person", uid=i) for i in range(V)]
    for a, b in edges.tolist():
        db.new_edge("knows", people[a], people[b])
    db.new_edge("likes", people[1], people[V - 1])  # no path of knows
    attach_fresh_snapshot(db)
    kinds = _kinds()
    raw = kinds.Raw(
        P=V,
        M=0,
        knows_deg=np.bincount(edges[:, 0], minlength=V).astype(np.int64),
        knows_dst=edges[:, 1].astype(np.int32),
        knows_cdate=np.zeros(len(edges), np.int32),
        creator=np.zeros(0, np.int32),
        age=np.zeros(V, np.int32),
        length=np.zeros(0, np.int32),
    )
    yield db, edges, kinds.Reference(raw)
    drain_warmups()


def _lane(db, sqls, plist):
    import orientdb_tpu.exec.engine as E

    deadline = time.time() + 120
    while time.time() < deadline:
        h = E.dispatch_lane_batch(db, sqls, plist, ring_state={})
        if h is not None:
            return [rs.to_dicts() for rs in h.collect()]
        drain_warmups()  # the group executable was still compiling
    raise AssertionError("the lane fast path never became available")


class TestCompiled:
    def test_single_dispatch_every_pair_three_ways(self, graph):
        db, edges, ref = graph
        before = _counters()
        rerec = _counters("plan_cache.overflow").get("plan_cache.overflow_rerecord", 0)
        lens = []
        for a in range(V):
            for b in range(V):
                p = {"person1Id": a, "person2Id": b}
                got = db.query(IC13, p, engine="tpu", strict=True)
                assert got.engine == "tpu"
                want = plain_len(V, edges, a, b)
                assert got.to_dicts() == [{"len": want}], (a, b)
                assert ref.answer("shortest_path_len", p) == [(want,)]
                lens.append(want)
        for a, b in ((0, 0), (3, 20), (1, V - 1), (2, 5), (V - 10, V - 1)):
            p = {"person1Id": a, "person2Id": b}
            assert db.query(IC13, p, engine="oracle").to_dicts() == [
                {"len": plain_len(V, edges, a, b)}
            ]
        assert {-1, 0, 1, 2, 3, 4, 9} <= set(lens)
        moved = _delta(_counters(), before)
        assert moved["bfs.queries"] == V * V
        assert moved["bfs.unreachable"] == sum(1 for x in lens if x < 0)
        # the hub's 20 neighbours outgrow the list this graph's degrees
        # size (front 16): those searches ran dense levels, and were counted
        assert 0 < moved["bfs.overflow"] < V * V
        assert moved["bfs.levels"] >= 2 * (V * V - V)
        assert moved["bfs.edges_expanded"] > moved["bfs.queries"]
        # one recording served them all
        assert _counters("plan_cache.overflow").get("plan_cache.overflow_rerecord", 0) == rerec

    @pytest.mark.parametrize("lanes", [1, 3, 16])
    def test_lane_batches(self, graph, lanes):
        db, edges, _ref = graph
        rng = np.random.default_rng(lanes)
        pairs = [(0, 0), (V - 10, V - 1), (1, V - 1)] + rng.integers(0, V, (13, 2)).tolist()
        plist = [{"person1Id": int(a), "person2Id": int(b)} for a, b in pairs[:lanes]]
        db.query(IC13, plist[0], engine="tpu", strict=True)  # recorded
        drain_warmups()
        before = _counters()
        for _ in range(2):
            got = _lane(db, [IC13] * lanes, plist)
            want = [[{"len": plain_len(V, edges, p["person1Id"], p["person2Id"])}] for p in plist]
            assert got == want
        assert _delta(_counters(), before)["bfs.queries"] == 2 * lanes

    def test_a_person_nobody_is(self, graph):
        db, _edges, _ref = graph
        none = {"person1Id": 2, "person2Id": 99}
        assert db.query(IC13, none, engine="tpu", strict=True).to_dicts() == []
        assert db.query(IC13, none, engine="oracle").to_dicts() == []

    @pytest.mark.parametrize(
        "returns",
        [
            "shortestPath(a, b, 'BOTH', 'knows', {maxDepth: 2}).size() - 1 AS len",
            "shortestPath(a, b, 'OUT', 'knows').size() - 1 AS len",
            "shortestPath(a, b, 'IN', 'knows').size() - 1 AS len",
            "shortestPath(a, b, 'BOTH').size() - 1 AS len",
            "shortestPath(a, b, 'BOTH', 'E').size() - 1 AS len",
            "shortestPath(a, b, 'BOTH', 'knows') AS path",
            "shortestPath(a, b, 'BOTH', 'knows').size() AS n",
            "shortestPath(a, #9:0, 'BOTH', 'knows').size() - 1 AS len",
        ],
    )
    def test_other_shapes_are_the_oracles(self, graph, returns):
        db, _edges, _ref = graph
        sql = IC13[: IC13.index("RETURN")] + "RETURN " + returns
        p = {"person1Id": 3, "person2Id": 20}
        with pytest.raises(Uncompilable):
            db.query(sql, p, engine="tpu", strict=True)
        # and a batch that falls back says so in the counters
        c0 = metrics.snapshot()["counters"].get("query.tpu.fallback", 0)
        want = db.query(sql, p, engine="oracle").to_dicts()
        got = db.query_batch([sql], params_list=[p], engine="tpu")[0]
        assert got.to_dicts() == want
        assert got.engine == "oracle"
        assert metrics.snapshot()["counters"].get("query.tpu.fallback", 0) == c0 + 1

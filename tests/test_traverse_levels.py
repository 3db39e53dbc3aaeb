"""A whole-graph breadth-first ``TRAVERSE`` counted by ``$depth`` (LDBC
Graphalytics BFS), compiled: ``ops/csr.bfs_levels`` behind
``exec/tpu_engine.TpuLevelsSolver``, from a root given by parameter.

The statement on the compiled path (``engine="tpu"``, ``strict``)
against plain numpy and, on a record store, against the oracle (whose
``$depth`` outside a ``TRAVERSE`` this PR repaired); on a record-store
graph and on an array-native one; over a Kronecker graph from the
benchmark's generator, a path longer than the depth table, a hub whose
lists outgrow the sparse step's buffer, a root without edges, a ``uid``
nobody has and two components. One recording serves every root, the
level loop ends on the device, and the search's five counters are what
numpy says of the same search."""

import os

import jax
import numpy as np
import pytest

from orientdb_tpu.exec import select_compile, tpu_engine
from orientdb_tpu.exec.tpu_engine import Uncompilable, drain_warmups
from orientdb_tpu.models.database import Database
from orientdb_tpu.ops import csr as K
from orientdb_tpu.sql.parser import parse
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SQL = (
    "SELECT $depth AS depth, count(*) AS n "
    "FROM (TRAVERSE both('Link') FROM (SELECT FROM Node WHERE uid = :source) "
    "STRATEGY BREADTH_FIRST) GROUP BY $depth"
)


def graph500():
    from benchmark import run

    return run.load_module("kinds", "graph500")


def _counters(*prefixes):
    c = metrics.snapshot()["counters"]
    return {k: v for k, v in c.items() if k.startswith(prefixes)}


def _delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}


# -- the graphs: (V, edges[E, 2]) -------------------------------------------------


def kronecker():
    g = graph500()
    raw = g.make_raw(
        {"scale": 8, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19, "graph_seed": 7}, 3
    )
    return raw.V, np.stack([raw.src, raw.dst], 1)


def path(n: int = 200):
    """A path: more levels than the depth table's first size holds, all
    of them sparse."""
    at = np.arange(n - 1)
    # every other edge stored backwards: both directions are walked
    return n, np.where((at % 2 == 0)[:, None], np.stack([at, at + 1], 1), np.stack([at + 1, at], 1))


def hub(spokes: int = 600, rest: int = 2400):
    """Vertex 0 points at ``spokes`` leaves (all stored outwards), beside
    a ring of ``rest`` vertices with chords that makes E large enough for
    the hub's ends to pass the switch (at most 2E/16) and not one
    direction's buffer (E/16)."""
    leaves = np.arange(1, spokes + 1)
    ring = np.arange(spokes + 1, spokes + 1 + rest)
    edges = np.concatenate(
        [
            np.stack([np.zeros(spokes, int), leaves], 1),
            np.stack([ring, np.roll(ring, -1)], 1),
            np.stack([ring, np.roll(ring, -7)], 1),
            np.stack([ring, np.roll(ring, -31)], 1),
            [[1, spokes + 1]],  # the hub's component hangs on the ring
        ]
    )
    return spokes + 1 + rest, edges


def components():
    """Two components and a vertex without edges: a triangle with a
    tail (0..4), a path of 200 (5..204), and 205 alone."""
    n, p = path(200)
    small = np.array([[0, 1], [1, 2], [2, 0], [2, 3], [3, 4]])
    return 5 + n + 1, np.concatenate([small, p + 5])


GRAPHS = {"kronecker": kronecker, "path": path, "hub": hub, "components": components}


def roots_of(name: str, V: int):
    if name == "kronecker":
        return [0, 5, 77, V - 1, V + 40]  # the last: a uid nobody has
    if name == "path":
        return [0, 100, 199]
    if name == "hub":
        return [3, 0, 700]  # a leaf (the hub is its second level), the hub, the ring
    return [0, 4, 205, 5, 104, 999]


# -- plain numpy: the search, and what the kernel's counters must read -------------


def numpy_search(V: int, edges: np.ndarray, root: int):
    """``(rows, parts)``: the counts by depth, and ``K.LEVEL_PARTS`` as
    the kernel's switch (``K.levels_caps``) decides them."""
    E = len(edges)
    threshold, caps = K.levels_caps(E)
    s, d = edges[:, 0], edges[:, 1]
    deg_o, deg_i = np.bincount(s, minlength=V), np.bincount(d, minlength=V)
    depth = np.full(V, -1)
    parts = dict.fromkeys(K.LEVEL_PARTS, 0)
    if not 0 <= root < V:
        return [], parts
    depth[root] = 0
    frontier = np.zeros(V, bool)
    frontier[root] = True
    while frontier.any():
        ends_o, ends_i = int(deg_o[frontier].sum()), int(deg_i[frontier].sum())
        few = ends_o + ends_i <= threshold
        fits = max(ends_o, ends_i) <= caps[-1]
        parts["levels"] += 1
        if few and fits:
            parts["sparse_ends"] += ends_o + ends_i
        else:
            parts["dense_levels"] += 1
            parts["overflow"] += int(few)
        reached = np.zeros(V, bool)
        reached[d[frontier[s]]] = True
        reached[s[frontier[d]]] = True
        frontier = reached & (depth < 0)
        depth[frontier] = parts["levels"]
    parts["reached"] = int((depth >= 0).sum())
    return list(enumerate(np.bincount(depth[depth >= 0]).tolist())), parts


# -- the two stores ------------------------------------------------------------------


def record_store(V: int, edges: np.ndarray):
    db = Database("levels_records")
    db.schema.create_vertex_class("Node")
    db.schema.create_edge_class("Link")
    vs = [db.new_vertex("Node", uid=i) for i in range(V)]
    for a, b in edges.tolist():
        db.new_edge("Link", vs[a], vs[b])
    attach_fresh_snapshot(db)
    return db


def array_native(V: int, edges: np.ndarray):
    g = graph500()
    order = np.lexsort((edges[:, 1], edges[:, 0]))
    src, dst = edges[order, 0].astype(np.int32), edges[order, 1].astype(np.int32)
    degree = np.bincount(src, minlength=V) + np.bincount(dst, minlength=V)
    db, _snap = g._handed_over(g.Raw(cfg={}, V=V, src=src, dst=dst, degree=degree), "levels_arrays")
    return db


STORES = {"records": record_store, "arrays": array_native}


@pytest.fixture(scope="module", params=[(g, s) for g in GRAPHS for s in STORES], ids="-".join)
def served(request):
    name, store = request.param
    V, edges = GRAPHS[name]()
    was = config.view_min_calls
    config.view_min_calls = 1 << 30  # a view would answer before the device
    db = STORES[store](V, edges)
    yield name, store, V, edges, db
    config.view_min_calls = was
    drain_warmups()
    db.detach_snapshot()


def ask(db, root: int, engine: str = "tpu"):
    rs = db.query(SQL, params={"source": root}, engine=engine, strict=True)
    assert rs.engine == engine
    return [(r["depth"], r["n"]) for r in rs.to_dicts()]


def test_compiled_is_numpy_is_the_oracle_and_counts_what_numpy_counts(served):
    name, store, V, edges, db = served
    a_level = 2 * len(edges)
    for root in roots_of(name, V):
        want, parts = numpy_search(V, edges, root)
        before = _counters("traverse.", "query.tpu.fallback")
        assert ask(db, root) == want, (name, store, root)
        moved = _delta(_counters("traverse.", "query.tpu.fallback"), before)
        assert moved.pop("traverse.queries") == 1
        assert moved == {
            k: v
            for k, v in {
                "traverse.levels": parts["levels"],
                "traverse.dense_levels": parts["dense_levels"],
                "traverse.edges_scanned": parts["sparse_ends"] + a_level * parts["dense_levels"],
                "traverse.overflow": parts["overflow"],
                "traverse.reached": parts["reached"],
            }.items()
            if v
        }, (name, store, root)
        if store == "records":
            assert ask(db, root, engine="oracle") == want
    # what the cases are for
    if name == "kronecker":
        assert numpy_search(V, edges, 0)[1]["dense_levels"] >= 1  # past the switch
        assert numpy_search(V, edges, V + 40) == ([], dict.fromkeys(K.LEVEL_PARTS, 0))
    if name == "path":
        rows, parts = numpy_search(V, edges, 0)
        assert len(rows) == 200 > tpu_engine.LEVEL_SLOTS and parts["dense_levels"] == 0
    if name == "hub":
        # from a leaf the hub is the second frontier: its 601 ends pass the
        # switch, its 600 out-ends outgrow the buffer, and the level runs
        # dense; so do the 600 leaves after it, by their in-ends
        assert numpy_search(V, edges, 3)[1]["overflow"] == 2
    if name == "components":
        assert numpy_search(V, edges, 205)[0] == [(0, 1)]  # a root without edges
        assert numpy_search(V, edges, 4)[1]["reached"] == 5  # the other component unreached


def test_one_recording_serves_twenty_roots_and_compiles_once():
    V, edges = kronecker()
    db = array_native(V, edges)
    try:
        before = _counters("plan_cache.", "plan.traverse.")
        roots = list(range(0, V, max(V // 20, 1)))[:20]
        for root in roots:
            assert ask(db, root) == numpy_search(V, edges, root)[0]
        drain_warmups()
        moved = _delta(_counters("plan_cache.", "plan.traverse."), before)
        assert moved["plan_cache.miss"] == 1 and moved["plan_cache.hit"] == 19
        assert "plan_cache.overflow_rerecord" not in moved
        # lowered twice: probed abstractly, traced once by jit; never baked
        assert moved["plan.traverse.generic"] == 2 and "plan.traverse.baked" not in moved
        (variants,) = db.current_snapshot()._plan_cache.values()
        (plan,) = variants.plans
        assert isinstance(plan, tpu_engine._CompiledLevels)
        assert plan.jitted._cache_size() == 1  # no compile after the first
        assert plan.dyn_spec == {"source": "int"}  # the root is a jit argument
    finally:
        drain_warmups()
        db.detach_snapshot()


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


def test_the_level_loop_ends_on_the_device():
    """A replay's jaxpr holds one ``while`` (the levels) and no callback
    to the host; the roots' parameter is an argument of it."""
    V, edges = kronecker()
    db = array_native(V, edges)
    try:
        ask(db, 5)
        (variants,) = db.current_snapshot()._plan_cache.values()
        plan = variants.plans[0]
        dyn = plan._dyn_args({"source": 9})
        assert set(dyn) == {"source"}
        closed = jax.make_jaxpr(plan._replay)(plan._arg_subset(), dyn)
        names = _primitives(closed.jaxpr, [])
        assert names.count("while") == 1, sorted(set(names))
        assert "cond" in names  # the switch between the sparse steps and the dense one
        assert not [n for n in names if "callback" in n or "infeed" in n or "outfeed" in n]
    finally:
        drain_warmups()
        db.detach_snapshot()


def ring(n: int = 64):
    """A ring stored one way round: every vertex holds one edge each way."""
    at = np.arange(n)
    return n, np.stack([at, (at + 1) % n], 1)


@pytest.mark.parametrize("graph", ["kronecker", "ring"])
def test_the_dense_pass_sums_by_prefix_sums_on_every_graph(graph):
    """The level kernel is not handed the unit bit of the COUNT's weight
    pass (``ops/device_graph.unit_degree``): on a Kronecker graph, where
    the bit is unset, and on a ring, where it is set both ways, the dense
    pass lowers its two segment sums as one prefix sum and two boundary
    gathers each, and the search answers as numpy does."""
    from orientdb_tpu.ops.device_graph import device_graph

    V, edges = {"kronecker": kronecker, "ring": ring}[graph]()
    db = array_native(V, edges)
    try:
        assert ask(db, 5) == numpy_search(V, edges, 5)[0]
        dec = device_graph(db.current_snapshot()).edges["Link"]
        assert (dec.unit_out, dec.unit_in) == ((True, True) if graph == "ring" else (False, False))
        (variants,) = db.current_snapshot()._plan_cache.values()
        plan = variants.plans[0]
        closed = jax.make_jaxpr(plan._replay)(plan._arg_subset(), plan._dyn_args({"source": 9}))
        sums, todo = [], [closed.jaxpr]
        while todo:
            for eqn in todo.pop().eqns:
                for v in eqn.params.values():
                    for sub in v if isinstance(v, (list, tuple)) else (v,):
                        inner = getattr(sub, "jaxpr", sub)
                        if hasattr(inner, "eqns"):
                            todo.append(inner)
                if eqn.params.get("name") == "_segment_sum":
                    sums.append(_primitives(eqn.params["jaxpr"].jaxpr, []))
        assert len(sums) == 2
        for names in sums:
            assert names.count("cumsum") == 1 and names.count("gather") == 2, names
    finally:
        drain_warmups()
        db.detach_snapshot()


def test_a_search_past_the_depth_table_records_the_next_size_and_answers_whole():
    V, edges = components()
    db = array_native(V, edges)
    try:
        assert ask(db, 0) == numpy_search(V, edges, 0)[0]  # recorded on 3 levels
        (variants,) = db.current_snapshot()._plan_cache.values()
        assert variants.plans[0].solver.slots == tpu_engine.LEVEL_SLOTS
        before = _counters("plan_cache.", "traverse.queries")
        long = ask(db, 5)  # the path's end: 200 levels
        assert long == [(d, 1) for d in range(200)]
        moved = _delta(_counters("plan_cache.", "traverse.queries"), before)
        assert moved["plan_cache.overflow_rerecord"] == 1
        assert moved["traverse.queries"] == 1  # counted once an answer, not a try
        assert [p.solver.slots for p in variants.plans] == [256, 64]
        # both variants stay: the next long search and the next short one replay
        before = _counters("plan_cache.")
        assert ask(db, 204) == [(d, 1) for d in range(200)]
        assert ask(db, 5) == long and ask(db, 0) == numpy_search(V, edges, 0)[0]
        drain_warmups()
        assert "plan_cache.overflow_rerecord" not in _delta(_counters("plan_cache."), before)
    finally:
        drain_warmups()
        db.detach_snapshot()


def test_the_recording_itself_may_start_past_the_table():
    V, edges = path(200)
    db = array_native(V, edges)
    try:
        assert ask(db, 199) == [(d, 1) for d in range(200)]
        (variants,) = db.current_snapshot()._plan_cache.values()
        assert variants.plans[0].solver.slots == 256
    finally:
        drain_warmups()
        db.detach_snapshot()


def test_several_roots_are_one_search_from_all_of_them():
    """The roots are whatever the inner SELECT admits: a mask, so a
    predicate that admits several starts the search from all of them,
    as the oracle's FIFO does."""
    V, edges = components()
    db = record_store(V, edges)
    sql = SQL.replace("uid = :source", "uid >= :source AND uid < :upto")
    try:
        for p in ({"source": 4, "upto": 7}, {"source": 100, "upto": 103}):
            got = db.query(sql, params=p, engine="tpu", strict=True).to_dicts()
            assert got == db.query(sql, params=p, engine="oracle").to_dicts() and got
    finally:
        drain_warmups()
        db.detach_snapshot()


def test_an_indexed_root_is_seeded_from_the_index_and_stays_a_parameter():
    """Where ``uid`` carries an index the root is probed on the host and
    handed over as a seed array (`TpuMatchSolver._root_seeds`): still one
    recording for every root."""
    V, edges = components()
    db = Database("levels_indexed")
    db.schema.create_vertex_class("Node")
    db.schema.create_edge_class("Link")
    vs = [db.new_vertex("Node", uid=i) for i in range(V)]
    for a, b in edges.tolist():
        db.new_edge("Link", vs[a], vs[b])
    db.indexes.create_index("Node.uid", "Node", ["uid"], "UNIQUE")
    attach_fresh_snapshot(db)
    try:
        before = _counters("plan_cache.miss")
        for root in (0, 3, 205, 999):
            assert ask(db, root) == numpy_search(V, edges, root)[0]
        assert _delta(_counters("plan_cache.miss"), before) == {"plan_cache.miss": 1}
        (variants,) = db.current_snapshot()._plan_cache.values()
        assert list(variants.plans[0].seed_spec) == [select_compile.ALIAS]
    finally:
        drain_warmups()
        db.detach_snapshot()


# -- what compiles, and what stays refused by name ----------------------------------------


@pytest.mark.parametrize(
    "sql, why",
    [
        (SQL.replace("BREADTH_FIRST", "DEPTH_FIRST"), "DEPTH_FIRST"),
        (SQL.replace("both('Link')", "out('Link')"), r"both\('<class>'\) only"),
        (SQL.replace("both('Link')", "both()"), r"both\('<class>'\) only"),
        (SQL.replace("STRATEGY", "MAXDEPTH 3 STRATEGY"), "MAXDEPTH/WHILE"),
        (SQL.replace("(SELECT FROM Node WHERE uid = :source)", "Node"), "roots are not a plain SELECT"),
        (SQL.replace("uid = :source)", "uid = :source LIMIT 1)"), "roots are not a plain SELECT"),
        (SQL.replace(" GROUP BY $depth", ""), r"GROUP BY \$depth only"),
        (SQL.replace("count(*) AS n", "max(uid) AS n"), r"\$depth and count\(\*\) only"),
        (SQL.replace(") GROUP BY", ") WHERE $depth >= 1 GROUP BY"), "without WHERE"),
        ("SELECT $depth AS depth FROM Node", r"context var \$depth in SELECT"),
        ("SELECT uid FROM (SELECT FROM Node)", "not a polymorphic class scan"),
    ],
)
def test_what_the_rewrite_refuses_it_refuses_by_name(sql, why):
    with pytest.raises(Uncompilable, match=why):
        select_compile.rewrite_select(parse(sql))


def test_the_rewrite_admits_the_statement():
    levels, alias = select_compile.rewrite_select(parse(SQL))
    assert alias is None and isinstance(levels, select_compile.LevelCounts)
    assert levels.edge_class == "Link" and levels.columns == (("depth", "depth"), ("n", "count"))
    swapped, _ = select_compile.rewrite_select(
        parse(SQL.replace("$depth AS depth, count(*) AS n", "count(*), $depth"))
    )
    assert swapped.columns == (("count", "count"), ("$depth", "depth"))


def test_the_oracle_reads_depth_outside_the_traverse():
    """Upstream's traverse results carry their depth: a SELECT over the
    TRAVERSE projects, filters and groups by it."""
    V, edges = components()
    db = record_store(V, edges)
    try:
        inner = "(TRAVERSE both('Link') FROM (SELECT FROM Node WHERE uid = 0) STRATEGY BREADTH_FIRST)"
        rows = db.query(f"SELECT uid, $depth AS d FROM {inner}", engine="oracle").to_dicts()
        assert sorted((r["uid"], r["d"]) for r in rows) == [(0, 0), (1, 1), (2, 1), (3, 2), (4, 3)]
        far = db.query(f"SELECT uid FROM {inner} WHERE $depth >= 2", engine="oracle").to_dicts()
        assert sorted(r["uid"] for r in far) == [3, 4]
        # shapes the rewrite refuses are the oracle's, with $depth all the same
        auto = db.query(f"SELECT uid FROM {inner} WHERE $depth >= 2").to_dicts()
        assert sorted(r["uid"] for r in auto) == [3, 4]
    finally:
        drain_warmups()
        db.detach_snapshot()


def test_the_bare_traverse_keeps_its_baked_plan():
    V, edges = components()
    db = record_store(V, edges)
    try:
        before = _counters("plan.traverse.")
        sql = "TRAVERSE both('Link') FROM (SELECT FROM Node WHERE uid = :source) STRATEGY BREADTH_FIRST"
        rs = db.query(sql, params={"source": 0}, engine="tpu", strict=True)
        assert sorted(r["uid"] for r in rs.to_dicts()) == [0, 1, 2, 3, 4]
        drain_warmups()
        # lowered at its recording and at the trace of its replay; a second
        # root is a second plan
        assert _delta(_counters("plan.traverse."), before) == {"plan.traverse.baked": 2}
        before = _counters("plan_cache.miss")
        db.query(sql, params={"source": 5}, engine="tpu", strict=True)
        assert _delta(_counters("plan_cache.miss"), before) == {"plan_cache.miss": 1}
    finally:
        drain_warmups()
        db.detach_snapshot()


def test_the_caps_come_from_the_edge_count_alone():
    threshold, caps = K.levels_caps(64_155_735)
    assert threshold == 2 * 64_155_735 // 16 and caps[-1] >= threshold / 2
    assert all(c % 256 == 0 for c in caps) and list(caps) == sorted(set(caps))
    assert caps[-1] < threshold  # a direction's buffer is smaller than the switch
    assert K.levels_caps(0) == (0, (K.MIN_BUCKET,))

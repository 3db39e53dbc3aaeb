"""COUNT(*) aggregate pushdown parity.

Terminal chain expansions under a lone COUNT(*) collapse to segment-sum
weight passes (`TpuMatchSolver._apply_count_pushdown`) instead of
materializing binding tables; these tests pin result parity vs the oracle
across directions, edge predicates, reversed arrows, multi-hop chains,
self-loops, and confirm the optimization actually engages; and, where the
chain begins at the plan's only root, that the count folds that root as a
mask over its range (`_folds_root`) and every other plan keeps its rows;
and, over a hop of one edge a vertex, that a pass reads its far end's
columns from the plan's copies in edge order (`_FarEnds`).
"""

import pytest

from orientdb_tpu import Database, PropertyType
from orientdb_tpu.exec.engine import parse_cached
from orientdb_tpu.exec.tpu_engine import TpuMatchSolver
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot


def count(db, sql, engine):
    return db.query(sql, engine=engine, strict=(engine == "tpu")).to_dicts()[0]["n"]


def parity(db, sql):
    assert count(db, sql, "tpu") == count(db, sql, "oracle")


@pytest.fixture
def sdb(social_db):
    attach_fresh_snapshot(social_db)
    return social_db


@pytest.fixture
def loop_db():
    db = Database("loops")
    db.schema.create_vertex_class("N")
    db.schema.create_edge_class("L")
    vs = [db.new_vertex("N", uid=i) for i in range(4)]
    db.new_edge("L", vs[0], vs[0])  # self-loop
    db.new_edge("L", vs[0], vs[1])
    db.new_edge("L", vs[1], vs[2])
    db.new_edge("L", vs[2], vs[0])
    attach_fresh_snapshot(db)
    return db


class TestCountPushdownParity:
    def test_1hop(self, sdb):
        parity(sdb, "MATCH {class:Profiles, as:p}-HasFriend->{as:f} RETURN count(*) AS n")

    def test_2hop_chain(self, sdb):
        parity(
            sdb,
            "MATCH {class:Profiles, as:p, where:(age > 30)}-HasFriend->{as:f}"
            "-HasFriend->{as:g, where:(age < 35)} RETURN count(*) AS n",
        )

    def test_reversed_arrow(self, sdb):
        parity(sdb, "MATCH {class:Profiles, as:p}<-HasFriend-{as:f} RETURN count(*) AS n")

    def test_both_direction(self, sdb):
        parity(sdb, "MATCH {class:Profiles, as:p}-HasFriend-{as:f} RETURN count(*) AS n")

    def test_edge_where(self, sdb):
        parity(
            sdb,
            "MATCH {class:Profiles, as:p}-{class:Likes, where:(weight < 2)}->{as:f} "
            "RETURN count(*) AS n",
        )

    def test_edge_class_where_on_dst(self, sdb):
        parity(
            sdb,
            "MATCH {class:Profiles, as:p}-Likes->{as:f, where:(age > 30)} "
            "RETURN count(*) AS n",
        )

    def test_self_loop_both(self, loop_db):
        parity(loop_db, "MATCH {class:N, as:a}-L-{as:b} RETURN count(*) AS n")

    def test_self_loop_out(self, loop_db):
        parity(loop_db, "MATCH {class:N, as:a}-L->{as:b} RETURN count(*) AS n")

    def test_3hop_chain(self, loop_db):
        parity(
            loop_db,
            "MATCH {class:N, as:a}-L->{as:b}-L->{as:c}-L->{as:d} RETURN count(*) AS n",
        )

    def test_count_with_root_where_param(self, sdb):
        sql = "MATCH {class:Profiles, as:p, where:(age > :a)}-HasFriend->{as:f} RETURN count(*) AS n"
        t = sdb.query(sql, params={"a": 30}, engine="tpu", strict=True).to_dicts()
        o = sdb.query(sql, params={"a": 30}, engine="oracle").to_dicts()
        assert t == o


class TestPushdownEngages:
    def _steps(self, db, sql):
        solver = TpuMatchSolver(db, parse_cached(sql), {})
        return solver._count_pushdown_steps()

    def test_chain_fully_pushed(self, sdb):
        steps = self._steps(
            sdb,
            "MATCH {class:Profiles, as:p}-HasFriend->{as:f}"
            "-HasFriend->{as:g} RETURN count(*) AS n",
        )
        assert len(steps) == 2

    def test_row_return_not_pushed(self, sdb):
        steps = self._steps(
            sdb, "MATCH {class:Profiles, as:p}-HasFriend->{as:f} RETURN p.name, f.name"
        )
        assert steps == []

    def test_closing_edge_not_pushed(self, sdb):
        # triangle pattern: last edge closes back to a bound alias
        steps = self._steps(
            sdb,
            "MATCH {class:Profiles, as:p}-HasFriend->{as:f}"
            "-HasFriend->{as:g}-HasFriend->{as:p} RETURN count(*) AS n",
        )
        assert all(not s.close for s in steps)

    def test_shared_chain_alias_pushes_through(self, sdb):
        # f links two chain edges → the whole chain composes into weights
        sql = (
            "MATCH {class:Profiles, as:p}-HasFriend->{as:f}, "
            "{as:f}-Likes->{as:x} RETURN count(*) AS n"
        )
        steps = self._steps(sdb, sql)
        assert len(steps) >= 1
        parity(sdb, sql)

    def test_pushdown_count_matches_materialized(self, sdb):
        # force the non-pushdown path via a row query wrapped in count
        sql = "MATCH {class:Profiles, as:p}-HasFriend->{as:f} RETURN count(*) AS n"
        n_tpu = count(sdb, sql, "tpu")
        rows = sdb.query(
            "MATCH {class:Profiles, as:p}-HasFriend->{as:f} RETURN p.name, f.name",
            engine="tpu",
            strict=True,
        ).to_dicts()
        assert n_tpu == len(rows)


class TestVarDepthCountPushdown:
    """Terminal WHILE arms under a lone COUNT(*) aggregate by per-level
    popcounts instead of materializing binding rows
    (tpu_engine._var_count_step / _expand_var_depth(count_only=True))."""

    def test_parity_across_parameters(self, sdb):
        q = (
            "MATCH {class:Profiles, as:p, where:(uid < :k)}"
            "-HasFriend->{as:f, while:($depth < 3), where:(age < 30)} "
            "RETURN count(*) AS n"
        )
        for k in (0, 1, 3, 5):
            want = sdb.query(q, params={"k": k}, engine="oracle").to_dicts()
            got = sdb.query(q, params={"k": k}, engine="tpu", strict=True).to_dicts()
            assert got == want, k

    def test_pushdown_engaged_and_shapes_excluded(self, sdb):
        from orientdb_tpu.exec.tpu_engine import TpuMatchSolver
        from orientdb_tpu.sql.parser import parse

        eligible = parse(
            "MATCH {class:Profiles, as:p}"
            "-HasFriend->{as:f, while:($depth < 2)} RETURN count(*) AS n"
        )
        s = TpuMatchSolver(sdb, eligible, {})
        assert s._var_count_step() is not None

        # rows needed → no pushdown
        rows_stmt = parse(
            "MATCH {class:Profiles, as:p}"
            "-HasFriend->{as:f, while:($depth < 2)} RETURN f.name"
        )
        assert TpuMatchSolver(sdb, rows_stmt, {})._var_count_step() is None

        # dst participates in another arm → no pushdown
        shared = parse(
            "MATCH {class:Profiles, as:p}"
            "-HasFriend->{as:f, while:($depth < 2)}, "
            "{as:f}-Likes->{as:x} RETURN count(*) AS n"
        )
        assert TpuMatchSolver(sdb, shared, {})._var_count_step() is None

    def test_unbounded_while_parity(self, sdb):
        q = (
            "MATCH {class:Profiles, as:p, where:(uid = 0)}"
            "-HasFriend->{as:f, while:(true)} RETURN count(*) AS n"
        )
        want = sdb.query(q, engine="oracle").to_dicts()
        got = sdb.query(q, engine="tpu", strict=True).to_dicts()
        assert got == want

    def test_maxdepth_parity(self, sdb):
        q = (
            "MATCH {class:Profiles, as:p}"
            "-HasFriend->{as:f, maxDepth:2} RETURN count(*) AS n"
        )
        want = sdb.query(q, engine="oracle").to_dicts()
        got = sdb.query(q, engine="tpu", strict=True).to_dicts()
        assert got == want


# -- the weight pass's segment sums over an edge class's vertex hull ------------

V_SEG = 40  # vertices of the seeded CSRs below

#: name -> the [lo, hi) that holds every vertex with an edge
SEG_HULLS = {
    "at_the_start": (0, 12),
    "in_the_middle": (13, 29),
    "at_the_end": (31, V_SEG),
    "the_whole_universe": (0, V_SEG),
    "one_vertex": (20, 21),
    "empty": (0, 0),
}


def _seeded_csr(hull, dtype, seed, lanes=None, unit=False):
    """A pointer array whose degrees are zero outside ``hull`` (and at
    some vertices inside it, its two ends never; or, ``unit``, one at
    every vertex inside it), and values in its order: whole numbers, so
    a float32 prefix sum is exact."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lo, hi = hull
    deg = np.zeros(V_SEG, np.int64)
    if hi > lo and unit:
        deg[lo:hi] = 1
    elif hi > lo:
        deg[lo:hi] = rng.integers(0, 5, hi - lo)
        deg[lo] = deg[hi - 1] = 3
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    shape = (int(indptr[-1]),) if lanes is None else (lanes, int(indptr[-1]))
    return indptr, rng.integers(0, 9, shape).astype(dtype)


def _primitive_names(closed):
    """Every primitive of a closed jaxpr, nested jits' too."""
    out, todo = [], [closed.jaxpr]
    while todo:
        for e in todo.pop().eqns:
            out.append(e.primitive.name)
            todo += [
                getattr(p, "jaxpr", p)
                for p in e.params.values()
                if hasattr(getattr(p, "jaxpr", p), "eqns")
            ]
    return out


class TestSegmentSumOverAHull:
    @pytest.mark.parametrize("degrees", ["any", "one"])
    @pytest.mark.parametrize("lanes", [None, 3], ids=["one", "vmapped"])
    @pytest.mark.parametrize("dtype", ["int32", "float32"])
    @pytest.mark.parametrize("name", sorted(SEG_HULLS))
    def test_the_hull_form_is_the_full_form(self, name, dtype, lanes, degrees):
        """Over a hull whose vertices hold one edge each (``degrees``
        "one"), the slice form is the prefix-sum form too, and lowers no
        prefix sum and no gather."""
        import jax
        import numpy as np

        from orientdb_tpu.ops import csr as K
        from orientdb_tpu.ops.device_graph import unit_degree, vertex_hull
        from orientdb_tpu.utils.metrics import metrics

        hull = SEG_HULLS[name]
        unit = degrees == "one"
        indptr, vals = _seeded_csr(hull, dtype, seed=len(name), lanes=lanes, unit=unit)
        # the hull the attach finds is the one the case was built on, and
        # it is unit where every vertex of it holds one edge
        assert vertex_hull(indptr, np.arange(V_SEG + 1)) == hull
        assert unit_degree(indptr, hull) == (unit and hull[1] > hull[0])
        for out_size in (64, V_SEG):
            def seg(h, u=False):
                one = lambda v: K.indptr_segment_sum(v, indptr, out_size, h, u)
                return (jax.vmap(one) if lanes else one)

            got, full = np.asarray(seg(hull)(vals)), np.asarray(seg(None)(vals))
            assert got.dtype == full.dtype == np.dtype(dtype)
            assert got.shape == full.shape == vals.shape[:-1] + (out_size,)
            assert np.array_equal(got, full)
            rows = vals.reshape(lanes or 1, vals.shape[-1])
            want = np.zeros((rows.shape[0], out_size), dtype)
            for i in range(V_SEG):
                want[:, i] = rows[:, indptr[i] : indptr[i + 1]].sum(axis=1)
            assert np.array_equal(got.reshape(want.shape), want)
            if not unit:
                continue
            sliced = seg(hull, True)
            before = metrics.counter("plan.segsum.unit")
            names = _primitive_names(jax.make_jaxpr(sliced)(vals))
            assert metrics.counter("plan.segsum.unit") == before + 1
            assert not {"gather", "cumsum"} & set(names), names
            got = np.asarray(sliced(vals))
            assert got.dtype == full.dtype and np.array_equal(got, full)

    #: name -> the degrees of the hull (13, 29), by vertex, where they are
    #: not one
    NOT_UNIT = {
        "an_empty_segment_at_the_start": {13: 0},
        "two_edges_in_the_middle": {20: 2},
        "two_edges_at_the_end": {28: 2},
        "an_empty_and_a_doubled_segment_balance": {15: 0, 25: 2},
    }

    @pytest.mark.parametrize("case", sorted(NOT_UNIT))
    def test_one_segment_of_another_size_unsets_the_bit(self, case):
        import numpy as np

        from orientdb_tpu.ops.device_graph import unit_degree

        lo, hi = 13, 29
        deg = np.zeros(V_SEG, np.int64)
        deg[lo:hi] = 1
        for v, d in self.NOT_UNIT[case].items():
            deg[v] = d
        indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
        assert not unit_degree(indptr, (lo, hi))
        deg[list(self.NOT_UNIT[case])] = 1
        assert unit_degree(np.concatenate([[0], np.cumsum(deg)]), (lo, hi))

    def test_a_hull_snaps_outwards_to_the_class_boundaries(self):
        import numpy as np

        from orientdb_tpu.ops.device_graph import vertex_hull

        indptr, _ = _seeded_csr((13, 29), "int32", seed=0)
        bounds = np.asarray([0, 10, 30, V_SEG])
        assert vertex_hull(indptr, bounds) == (10, 30)
        assert vertex_hull(indptr, np.asarray([0, 13, 29, V_SEG])) == (13, 29)
        assert vertex_hull(indptr, np.asarray([0, V_SEG])) == (0, V_SEG)
        assert vertex_hull(np.zeros(V_SEG + 1, np.int32), bounds) == (0, 0)


SNB_COUNTS = {
    # the four statements of the benchmark's scan_4s mix, each as written
    # there and from its other end (the weight passes then walk the other
    # CSR of each edge class)
    "knows_1hop": (
        "MATCH {class:Person, as:p, where:(age > :minAge)}-knows->"
        "{as:f, where:(age < :maxAge)} RETURN count(*) AS n",
        "MATCH {class:Person, as:f, where:(age < :maxAge)}<-knows-"
        "{as:p, where:(age > :minAge)} RETURN count(*) AS n",
    ),
    "knows_2hop": (
        "MATCH {class:Person, as:p, where:(age > :minAge)}-knows->{as:f}-knows->"
        "{as:g, where:(age < :maxAge)} RETURN count(*) AS n",
        "MATCH {class:Person, as:g, where:(age < :maxAge)}<-knows-{as:f}<-knows-"
        "{as:p, where:(age > :minAge)} RETURN count(*) AS n",
    ),
    "creator_1hop": (
        "MATCH {class:Message, as:m, where:(length > :minLen)}-hasCreator->"
        "{as:p, where:(age < :maxAge)} RETURN count(*) AS n",
        "MATCH {class:Person, as:p, where:(age < :maxAge)}<-hasCreator-"
        "{as:m, where:(length > :minLen)} RETURN count(*) AS n",
    ),
    "config5": (
        "MATCH {class:Person, as:p, where:(age > 40)}.outE('knows')"
        "{where:(creationDate > :d)}.inV(){as:f, where:(age < 30)}, "
        "{class:Message, as:m}-hasCreator->{as:f} RETURN count(*) AS n",
        "MATCH {class:Message, as:m}-hasCreator->{as:f, where:(age < 30)}, "
        "{as:f}.inE('knows'){where:(creationDate > :d)}.outV()"
        "{as:p, where:(age > 40)} RETURN count(*) AS n",
    ),
}
SNB_PARAMS = {"minAge": 40, "maxAge": 30, "minLen": 900, "d": 14_000}


def _segsums():
    """(passes over a hull, passes over the universe) lowered so far."""
    from orientdb_tpu.utils.metrics import metrics

    c = metrics.snapshot()["counters"]
    return c.get("plan.segsum.hull", 0), c.get("plan.segsum.full", 0)


#: (statement, end) whose weight chain walks hasCreator from the messages,
#: one edge a message: that pass is a slice of its values
SLICED = {("creator_1hop", 0), ("config5", 1)}


@pytest.fixture(scope="module")
def snb_counts():
    import numpy as np

    from orientdb_tpu.exec.tpu_engine import drain_warmups
    from orientdb_tpu.storage import bigshape as B

    db, snap = B.build_snb_shape(400, msgs_per_person=3, avg_knows=5, seed=11)
    age = snap.v_columns["age"]
    old = (age.values > 40) & age.present
    young = (age.values < 30) & age.present
    hc = snap.edge_classes["hasCreator"]
    long_msg = snap.v_columns["length"].values > 900  # 0 on a person
    want = {
        "knows_1hop": B.numpy_1hop_count(snap, old, young),
        "knows_2hop": B.numpy_2hop_count(snap, old, age.present, young),
        # every message has the one edge, so hasCreator's dst lies in
        # message order
        "creator_1hop": int((long_msg[400:] & young[hc.dst]).sum()),
        "config5": B.numpy_config5_count(snap, 14_000),
    }
    yield db, snap, want
    drain_warmups()
    db.detach_snapshot()


class TestSnbCountsOverHulls:
    def test_the_hulls_are_the_classes_of_the_layout(self, snb_counts):
        from orientdb_tpu.ops.device_graph import device_graph

        _db, snap, _want = snb_counts
        edges = device_graph(snap).edges
        P, V = 400, 400 * 4
        assert (edges["knows"].hull_out, edges["knows"].hull_in) == ((0, P), (0, P))
        assert edges["hasCreator"].hull_out == (P, V)
        assert edges["hasCreator"].hull_in == (0, P)
        # one creator a message; a person writes none, one or several, and
        # knows has persons without a friend and persons with many
        assert edges["hasCreator"].unit_out and not edges["hasCreator"].unit_in
        assert not edges["knows"].unit_out and not edges["knows"].unit_in

    @pytest.mark.parametrize("end", [0, 1], ids=["as_written", "from_the_other_end"])
    @pytest.mark.parametrize("shape", sorted(SNB_COUNTS))
    def test_a_scan_count_is_the_numpy_count(self, snb_counts, shape, end):
        db, _snap, want = snb_counts
        assert want[shape] > 0, "a count of nothing tests nothing"
        names = ("plan.segsum.hull", "plan.segsum.full", "plan.segsum.unit")
        before = _counted(*names)
        got = db.query(
            SNB_COUNTS[shape][end], SNB_PARAMS, engine="tpu", strict=True
        ).to_dicts()
        moved = [b > a for a, b in zip(before, _counted(*names))]
        assert got == [{"n": want[shape]}]
        # the pushdown answered, and every pass of it ran over a class's
        # hull: no edge class of this layout spans the universe. A pass
        # from the messages over hasCreator is a slice, and creator_1hop
        # as written has no other
        sliced = (shape, end) in SLICED
        assert moved == [(shape, end) != ("creator_1hop", 0), False, sliced]


class TestAHullUnderDeltas:
    """A hull is read from the pointer array at attach. Writes land in
    the slab, outside it: the pushdown steps aside while the topology is
    dirty, and a compacted snapshot is a new device graph with hulls of
    its own. A recorded hull is never replayed over other arrays."""

    MSGS = "MATCH {class:Msg, as:m}-Wrote->{as:p, where:(age < :a)} RETURN count(*) AS n"
    BACK = "MATCH {class:Writer, as:p, where:(age < :a)}<-Wrote-{as:m} RETURN count(*) AS n"

    @pytest.mark.parametrize("sql", ["MSGS", "BACK"])
    def test_a_count_after_a_write_is_the_oracles(self, sql):
        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.ops.device_graph import device_graph
        from orientdb_tpu.storage.deltas import arm_delta_maintenance

        hulls_counted = lambda: _segsums()[0]
        db = Database(f"hull_deltas_{sql.lower()}")
        db.schema.create_vertex_class("Writer")
        db.schema.create_vertex_class("Msg")
        db.schema.create_edge_class("Wrote")
        writers = [db.new_vertex("Writer", age=20 + i) for i in range(6)]
        msgs = [db.new_vertex("Msg", length=i) for i in range(10)]
        for i, msg in enumerate(msgs):
            db.new_edge("Wrote", msg, writers[i % 6])
        m = arm_delta_maintenance(db, spare_vertices=16, spare_edges=16)
        q, a = getattr(self, sql), {"a": 24}
        ask = lambda engine: db.query(
            q, a, engine=engine, strict=(engine == "tpu")
        ).to_dicts()
        try:
            snap = db.current_snapshot(require_fresh=True)
            dec = device_graph(snap).edges["Wrote"]
            # the two classes lie one after the other, the slab behind both
            assert {dec.hull_out, dec.hull_in} == {(0, 6), (6, 16)}
            assert snap.num_vertices == 32
            # one edge a message, but the slab may give a message another:
            # a maintained snapshot never slices
            assert not dec.unit_out and not dec.unit_in
            before = hulls_counted()
            assert ask("tpu") == ask("oracle") == [{"n": 8}]
            assert hulls_counted() > before, "the pushdown did not answer"
            # that answer's plan is traced once more in the background
            # (the AOT warm-up), and a trace counts too: let it finish
            drain_warmups()
            # a message and its edge land in the slab, outside every hull
            db.new_edge("Wrote", db.new_vertex("Msg", length=99), writers[1])
            before = hulls_counted()
            assert ask("tpu") == ask("oracle") == [{"n": 9}]
            assert hulls_counted() == before, "a dirty topology took the pushdown"
            # compaction folds the slab in: new arrays, new hulls, a new plan
            m.compact("the hulls' test")
            snap2 = db.current_snapshot(require_fresh=True)
            dec2 = device_graph(snap2).edges["Wrote"]
            assert snap2 is not snap
            assert {dec2.hull_out, dec2.hull_in} == {(0, 6), (6, 17)}
            assert not dec2.unit_out and not dec2.unit_in
            before = hulls_counted()
            assert ask("tpu") == ask("oracle") == [{"n": 9}]
            assert hulls_counted() > before
            # a second creator for a message that has one: counted twice
            db.new_edge("Wrote", msgs[0], writers[2])
            assert ask("tpu") == ask("oracle") == [{"n": 10}]
            m.compact("a message with two edges")
            dec3 = device_graph(db.current_snapshot(require_fresh=True)).edges["Wrote"]
            assert not dec3.unit_out and not dec3.unit_in
            sliced = _counted("plan.segsum.unit")
            assert ask("tpu") == ask("oracle") == [{"n": 10}]
            assert _counted("plan.segsum.unit") == sliced
        finally:
            drain_warmups()
            db.detach_snapshot()


def _attach_plain(db, _monkeypatch):
    return attach_fresh_snapshot(db)


def _attach_maintained(db, _monkeypatch):
    from orientdb_tpu.storage.deltas import arm_delta_maintenance

    arm_delta_maintenance(db, spare_vertices=16, spare_edges=16)
    return db.current_snapshot(require_fresh=True)


def _attach_on_a_mesh(db, _monkeypatch):
    from orientdb_tpu.parallel.sharded import make_mesh

    return attach_fresh_snapshot(db, mesh=make_mesh(8))


def _attach_tiered(db, monkeypatch):
    from orientdb_tpu.storage import tiering
    from orientdb_tpu.utils.config import config

    monkeypatch.setattr(config, "tier_block_edges", 4)
    adj = tiering.adjacency_bytes(attach_fresh_snapshot(db))
    db.detach_snapshot()
    monkeypatch.setattr(config, "tier_hbm_cap_bytes", max(1, adj // 2))
    snap = attach_fresh_snapshot(db)
    assert snap._tier is not None
    return snap


class TestASliceOnlyWhereTheCsrCannotChange:
    """One edge a message, on four attachments of one graph: the unit bit
    is set on a plain snapshot alone. A delta-maintained one may give a
    message a second edge, a tier pages the edge arrays and a mesh shards
    them; there the pass runs as before, with the same answer."""

    MSGS = TestAHullUnderDeltas.MSGS
    ATTACH = {
        "plain": (_attach_plain, True),
        "delta_maintained": (_attach_maintained, False),
        "mesh_sharded": (_attach_on_a_mesh, False),
        "tiered": (_attach_tiered, False),
    }

    @pytest.mark.parametrize("kind", sorted(ATTACH))
    def test_the_bit_is_set_on_a_plain_snapshot_alone(self, monkeypatch, kind):
        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.ops.device_graph import device_graph

        attach, slices = self.ATTACH[kind]
        db = Database(f"slice_{kind}")
        db.schema.create_vertex_class("Writer")
        db.schema.create_vertex_class("Msg")
        db.schema.create_edge_class("Wrote")
        writers = [db.new_vertex("Writer", age=20 + i) for i in range(6)]
        for i in range(10):
            db.new_edge("Wrote", db.new_vertex("Msg", length=i), writers[i % 6])
        try:
            dec = device_graph(attach(db, monkeypatch)).edges["Wrote"]
            assert (dec.unit_out, dec.unit_in) == (slices, False)
            before = _counted("plan.segsum.unit")
            ask = lambda engine: db.query(
                self.MSGS, {"a": 24}, engine=engine, strict=(engine == "tpu")
            ).to_dicts()
            assert ask("tpu") == ask("oracle") == [{"n": 8}]
            assert (_counted("plan.segsum.unit") > before) == slices
        finally:
            drain_warmups()
            db.detach_snapshot()


@pytest.fixture(scope="module", params=[3, 2**31 + 5], ids=["seed_3", "seed_2e31_5"])
def snb_raw(request):
    """The benchmark's own SNB arrays at a small scale, attached as its
    hand-over attaches them, with its reference."""
    from benchmark import run
    from orientdb_tpu.exec.tpu_engine import drain_warmups

    S = run.load_module("kinds", "snb_arrays")
    scale = {"persons": 300, "avg_knows": 5, "msgs_per_person": 3}
    raw = S.make_raw({**scale, "supernodes": 0, "supernode_degree": 0}, request.param)
    db, _snap = S.attach(raw, f"snb_ref_{request.param}")
    yield db, S.Reference(raw)
    drain_warmups()
    db.detach_snapshot()


def _scan_4s_shapes():
    """name -> (statement, reference kind, two parameter sets: the pool's
    lead, and each range's upper end) of ``benchmark/traffic/scan_4s.json``."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "traffic", "scan_4s.json",
    )
    with open(path) as f:
        shapes = json.load(f)["shapes"]
    pick = lambda spec, end: spec["const"] if "const" in spec else (
        spec["lead"] if end is None else spec["int"][end]
    )
    return {
        s["name"]: (
            s["sql"],
            s["reference"],
            [{k: pick(v, end) for k, v in s["params"].items()} for end in (None, 1)],
        )
        for s in shapes
    }


SCAN_4S_SHAPES = _scan_4s_shapes()


class TestTheScanStatementsAreTheBenchmarksReference:
    @pytest.mark.parametrize("shape", sorted(SCAN_4S_SHAPES))
    def test_a_scan_answer_is_the_references(self, snb_raw, shape):
        db, ref = snb_raw
        sql, kind, params = SCAN_4S_SHAPES[shape]
        for p in params:
            want = ref.answer(kind, p)
            got = db.query(sql, p, engine="tpu", strict=True).to_dicts()
            assert [tuple(r.values()) for r in got] == want, (shape, p)
            assert want[0][0] > 0, "a count of nothing tests nothing"


# -- a COUNT folds its root as a mask over the root's range ---------------------


FOLD, ROWS = "plan.count.root_fold", "plan.count.root_rows"


def _counted(*names):
    """The named counters, read after every background trace finished
    (a plan's AOT warm-up lowers it once more, and a lowering counts)."""
    from orientdb_tpu.exec.tpu_engine import drain_warmups
    from orientdb_tpu.utils.metrics import metrics

    drain_warmups()
    return [metrics.counter(n) for n in names]


_rerecords = lambda: sum(
    _counted("plan_cache.miss", "plan_cache.overflow_rerecord")
)

#: a COUNT rooted at the messages, %s the root's alias (a name of its own
#: gives each case a plan of its own in the module's graph)
MSG_COUNT = (
    "MATCH {class:Message, as:%s, where:(length > :minLen)}-hasCreator->"
    "{as:p, where:(age < :maxAge)} RETURN count(*) AS n"
)
NONE, FEW, EVERY = 2500, 1950, 0  # lengths lie in [1, 2000)


def _msg_count(snap, p):
    P = int(snap.class_vertex_range["person"][1])
    hc = snap.edge_classes["hasCreator"]
    long_msg = snap.v_columns["length"].values[P:] > p["minLen"]
    return int((long_msg & (snap.v_columns["age"].values[hc.dst] < p["maxAge"])).sum())


def _hull_root_with_a_predicate(request):
    db, snap, _ = request.getfixturevalue("snb_counts")
    p = {"minLen": 900, "maxAge": 30}
    got = db.query(MSG_COUNT % "hull", p, engine="tpu", strict=True).to_dicts()
    return got, [{"n": _msg_count(snap, p)}]


def _root_with_no_predicate(request):
    db, snap, _ = request.getfixturevalue("snb_counts")
    sql = "MATCH {class:Person, as:bare}-knows->{as:f} RETURN count(*) AS n"
    got = db.query(sql, engine="tpu", strict=True).to_dicts()
    return got, [{"n": int(snap.edge_classes["knows"].num_edges)}]


def _slab_segment_after_a_write(request):
    """A property write leaves the topology clean, so the pushdown
    answers, and the root's range is the class hull and then the slab."""
    from orientdb_tpu.exec.tpu_engine import TpuMatchSolver, drain_warmups
    from orientdb_tpu.ops import csr as K
    from orientdb_tpu.storage.deltas import arm_delta_maintenance

    db = Database("fold_slab")
    db.schema.create_vertex_class("Writer")
    db.schema.create_vertex_class("Msg")
    db.schema.create_edge_class("Wrote")
    writers = [db.new_vertex("Writer", age=20 + i) for i in range(6)]
    msgs = [db.new_vertex("Msg", length=i) for i in range(10)]
    for i, m in enumerate(msgs):
        db.new_edge("Wrote", m, writers[i % 6])
    arm_delta_maintenance(db, spare_vertices=16, spare_edges=16)
    sql = (
        "MATCH {class:Msg, as:m, where:(length > :l)}-Wrote->"
        "{as:p, where:(age < :a)} RETURN count(*) AS n"
    )
    params = {"l": 4, "a": 24}
    ask = lambda engine: db.query(
        sql, params, engine=engine, strict=(engine == "tpu")
    ).to_dicts()
    try:
        assert ask("tpu") == ask("oracle") == [{"n": 4}]  # messages 6 to 9
        msgs[1].set("length", 50)
        db.save(msgs[1])
        snap = db.current_snapshot(require_fresh=True)
        assert not snap._overlay.topology_dirty
        idx, _mask = TpuMatchSolver(db, parse_cached(sql), params)._root_index("m")
        assert isinstance(idx, K.IndexRange) and idx.slab_size == 16
        assert ask("oracle") == [{"n": 5}]
        return ask("tpu"), [{"n": 5}]
    finally:
        drain_warmups()
        db.detach_snapshot()


def _root_seeded_from_an_index(request):
    """An indexed ``uid`` seeds the root from the host's index: the fold
    sums under the mask over the seed array, at most its capacity long."""
    from orientdb_tpu.exec.tpu_engine import drain_warmups

    db = Database("fold_seeded")
    person = db.schema.create_vertex_class("Person")
    person.create_property("uid", PropertyType.LONG)
    db.schema.create_edge_class("knows")
    vs = [db.new_vertex("Person", uid=i, age=20 + i) for i in range(30)]
    for i in range(29):
        db.new_edge("knows", vs[i], vs[i + 1])
        db.new_edge("knows", vs[i], vs[(i * 7 + 3) % 30])
    db.command("CREATE INDEX Person.uid ON Person (uid) UNIQUE")
    snap = attach_fresh_snapshot(db)
    sql = (
        "MATCH {class:Person, as:p, where:(uid = :u)}-knows->{as:f}-knows->"
        "{as:g, where:(age > :a)} RETURN count(*) AS n"
    )
    try:
        first = db.query(sql, {"u": 99, "a": 0}, engine="tpu", strict=True)
        assert first.to_dicts() == [{"n": 0}]  # recorded on no such person
        (variants,) = snap._plan_cache.values()
        assert variants.plans[0].seed_spec, "the root was not seeded"
        before = _rerecords()
        p = {"u": 4, "a": 25}
        got = db.query(sql, p, engine="tpu", strict=True).to_dicts()
        assert _rerecords() == before
        return got, db.query(sql, p, engine="oracle").to_dicts()
    finally:
        drain_warmups()
        db.detach_snapshot()


def _replayed_on_every_root(recorded_on, alias):
    def case(request):
        db, snap, _ = request.getfixturevalue("snb_counts")
        sql = MSG_COUNT % alias
        first = {"minLen": recorded_on, "maxAge": 60}
        got = db.query(sql, first, engine="tpu", strict=True).to_dicts()
        assert got == [{"n": _msg_count(snap, first)}]
        # before the fold the first recording skipped the pushdown for
        # want of a row, and both sized a buffer by their few candidates:
        # the widest parameters then forced a recording anew
        before = _rerecords()
        widest = {"minLen": EVERY, "maxAge": 60}
        got = db.query(sql, widest, engine="tpu", strict=True).to_dicts()
        assert _rerecords() == before, "the replay was recorded anew"
        return got, [{"n": _msg_count(snap, widest)}]

    return case


def _vmapped_group_of_lanes(request):
    import orientdb_tpu.obs.timeline as TL
    from orientdb_tpu.exec.tpu_engine import _GROUP_MIN

    db, snap, _ = request.getfixturevalue("snb_counts")
    sql = MSG_COUNT % "lane"
    plist = [
        {"minLen": l, "maxAge": a}
        for l, a in [(NONE, 60), (FEW, 60), (EVERY, 30), (900, 45), (EVERY, 60)]
    ]
    assert len(plist) >= _GROUP_MIN
    ask = lambda: db.query_batch(
        [sql] * len(plist), plist, engine="tpu", strict=True
    )
    ask()
    before = _rerecords()  # and the first group's program is compiled
    TL.recorder.reset()
    got = [rs.to_dicts()[0] for rs in ask()]
    assert [r["path"] for r in TL.recorder.records()] == ["group"]
    assert _rerecords() == before
    return got, [{"n": _msg_count(snap, p)} for p in plist]


def _path_length_of_two_seeded_roots(request):
    import numpy as np

    db, snap, _ = request.getfixturevalue("snb_counts")
    k = snap.edge_classes["knows"]
    a = int(np.flatnonzero(np.diff(k.indptr_out))[0])
    b = int(k.dst[k.indptr_out[a]])
    assert a != b
    sql = (
        "MATCH {class:Person, as:a, where:(uid = :person1Id)}, "
        "{class:Person, as:b, where:(uid = :person2Id)} "
        "RETURN shortestPath(a, b, 'BOTH', 'knows').size() - 1 AS len"
    )
    got = db.query(
        sql, {"person1Id": a, "person2Id": b}, engine="tpu", strict=True
    ).to_dicts()
    return got, [{"len": 1}]


def _on_the_social_graph(sql):
    def case(request):
        db = request.getfixturevalue("sdb")
        got = db.query(sql, engine="tpu", strict=True).to_dicts()
        return got, db.query(sql, engine="oracle").to_dicts()

    return case


#: case -> (what runs it, the counter its lowering moves: FOLD where the
#: root is folded, ROWS where the pushdown reads a table, None without one)
ROOT_CASES = {
    "hull_root_with_a_predicate": (_hull_root_with_a_predicate, FOLD),
    "root_with_no_predicate": (_root_with_no_predicate, FOLD),
    "slab_segment_after_a_write": (_slab_segment_after_a_write, FOLD),
    "root_seeded_from_an_index": (_root_seeded_from_an_index, FOLD),
    "recorded_on_no_root": (_replayed_on_every_root(NONE, "none"), FOLD),
    "recorded_on_few_roots": (_replayed_on_every_root(FEW, "few"), FOLD),
    "vmapped_group_of_lanes": (_vmapped_group_of_lanes, FOLD),
    # the plans that keep their rows
    "two_components": (
        _on_the_social_graph(
            "MATCH {class:Profiles, as:a, where:(uid < 2)}, "
            "{class:Profiles, as:p}-HasFriend->{as:f} RETURN count(*) AS n"
        ),
        None,
    ),
    "row_returning_statement": (
        _on_the_social_graph(
            "MATCH {class:Profiles, as:p, where:(age > 26)}-HasFriend->{as:f} "
            "RETURN p.name AS p, f.name AS f"
        ),
        None,
    ),
    "closing_edge": (
        _on_the_social_graph(
            "MATCH {class:Profiles, as:p}-HasFriend->{as:f}-HasFriend->{as:g}, "
            "{as:p}-HasFriend->{as:g} RETURN count(*) AS n"
        ),
        None,
    ),
    "path_length_of_two_seeded_roots": (_path_length_of_two_seeded_roots, None),
    "an_expansion_before_the_chain": (
        _on_the_social_graph(
            "MATCH {class:Profiles, as:p}.outE('HasFriend'){as:e}.inV(){as:f}"
            "-HasFriend->{as:g} RETURN count(*) AS n"
        ),
        ROWS,
    ),
}


class TestACountFoldsItsRoot:
    """Where a COUNT pushdown's chain begins at the plan's only other
    step, the root of its source, the count is the weight chain summed
    under the root's mask over the root's range
    (`TpuMatchSolver._folds_root`): no candidate is compacted, no size
    observed. Every other plan reads the table its prefix built."""

    @pytest.mark.parametrize("case", sorted(ROOT_CASES))
    def test_the_count_is_the_references(self, request, case):
        run, moves = ROOT_CASES[case]
        before = _counted(FOLD, ROWS)
        got, want = run(request)
        moved = [b > a for a, b in zip(before, _counted(FOLD, ROWS))]
        key = lambda rows: sorted(str(sorted(r.items())) for r in rows)
        assert key(got) == key(want), case
        assert any(v for r in want for v in r.values()), "nothing was counted"
        assert moved == [moves == FOLD, moves == ROWS]


# -- the passes of a weight chain that read no parameter are the plan's -----------


CONST, LIVE = "plan.count.pass_const", "plan.count.pass_live"


def _lowered(run):
    """``run()``, and the passes (taken from a plan, lowered) of the
    chains lowered meanwhile, every background trace finished."""
    before = _counted(CONST, LIVE)
    got = run()
    return got, tuple(b - a for a, b in zip(before, _counted(CONST, LIVE)))


def _dense_db(n):
    """Every vertex to every vertex, loops too: ``n ** (k + 1)`` chains
    of ``k`` hops."""
    db = Database(f"dense_{n}")
    db.schema.create_vertex_class("N")
    db.schema.create_edge_class("L")
    vs = [db.new_vertex("N", uid=i) for i in range(n)]
    for a in vs:
        for b in vs:
            db.new_edge("L", a, b)
    attach_fresh_snapshot(db)
    return db


class _Knows:
    """numpy over the module graph's knows edges: a hop is a join."""

    def __init__(self, snap):
        import numpy as np

        k = snap.edge_classes["knows"]
        self.src = np.repeat(np.arange(len(k.indptr_out) - 1), np.diff(k.indptr_out))
        self.dst = k.dst
        self.date = k.edge_columns["creationDate"].values
        age = snap.v_columns["age"]
        self.person = age.present  # a message has no age
        self.age = np.where(age.present, age.values, np.nan)  # compares false

    def hop1(self, root, end, edge=True):
        return int((root[self.src] & end[self.dst] & edge).sum())

    def hop2(self, root, mid, end):
        import numpy as np

        per_f = np.bincount(self.src, weights=end[self.dst], minlength=len(end))
        return int((root[self.src] * mid[self.dst] * per_f[self.dst]).sum())


class TestConstantPasses:
    """A pass of the weight chain whose edge filter and destination mask
    read no dynamic parameter, and whose incoming weights are constant,
    is evaluated once, at the recording, and kept on the plan
    (`TpuMatchSolver._const_passes`); a replay lowers the passes before
    it. Observed from the compiled predicates, not declared."""

    SWEEP = (13_000, 15_000, 17_000, 18_500, 12_500)

    def test_config5_recorded_once_is_the_numpy_count_on_every_cut(self, snb_counts):
        from orientdb_tpu.storage import bigshape as B

        db, snap, _ = snb_counts
        sql = SNB_COUNTS["config5"][0].replace("AS n", "AS swept")
        ask = lambda d: db.query(sql, {"d": d}, engine="tpu", strict=True).to_dicts()
        first, passes = _lowered(lambda: ask(12_000))
        assert first == [{"swept": B.numpy_config5_count(snap, 12_000)}]
        # the recording and its replay's one trace: the messages of each
        # person from the plan, the knows pass (it reads :d) lowered
        assert passes == (2, 2)
        before = _rerecords()
        counts = [ask(d)[0]["swept"] for d in self.SWEEP]
        assert counts == [B.numpy_config5_count(snap, d) for d in self.SWEEP]
        assert len(set(counts)) == len(counts) and min(counts) > 0
        assert _rerecords() == before, "a replay was recorded anew"

    def test_the_kept_weights_are_counted_as_device_bytes(self, snb_counts):
        """`memory_report` (the benchmark's ``hbm_state_gb``) and the
        ledger count what a plan keeps while the plan lives, and no
        longer."""
        import gc

        import jax.numpy as jnp

        from orientdb_tpu.obs.memledger import memledger
        from orientdb_tpu.ops.device_graph import device_graph

        db, snap, _ = snb_counts
        dg = device_graph(snap)
        kept = lambda: dg.memory_report()["per_device"]["plan_consts"]
        entries = lambda: {
            k: e.nbytes
            for (kind, _owner, k), e in memledger._entries.items()
            if kind == "plan_const" and k.startswith("plan:")
        }
        before, ledger = kept(), entries()
        sql = SNB_COUNTS["config5"][0].replace("AS n", "AS bytes")
        db.query(sql, {"d": 14_000}, engine="tpu", strict=True)
        # one int32 a person: the hull of hasCreator's targets
        assert kept() - before == 4 * 400
        (new,) = set(entries()) - set(ledger)
        assert entries()[new] == 4 * 400

        class Plan:  # whatever owns the array: a plan is collected the same
            pass

        plan, arr = Plan(), jnp.ones(25, jnp.int32)
        dg.adopt_plan_const(plan, "plan:of_the_test", arr)
        ident = f"plan:of_the_test:{id(plan):x}"
        assert kept() - before == 4 * 400 + 100 and entries()[ident] == 100
        del plan, arr
        gc.collect()
        assert kept() - before == 4 * 400 and ident not in entries()

    #: statement, the passes of one lowering (from the plan, lowered), and
    #: its count in numpy (`_Knows`) under parameters ``p``
    LAST_HOPS = {
        "a_parameter_is_live": (
            "MATCH {class:Person, as:p1, where:(age > :minAge)}-knows->"
            "{as:f, where:(age < :maxAge)} RETURN count(*) AS n",
            (0, 1),
            lambda g, p: g.hop1(g.age > p["minAge"], g.age < p["maxAge"]),
        ),
        "a_literal_is_constant": (
            "MATCH {class:Person, as:p2, where:(age > :minAge)}-knows->"
            "{as:f, where:(age < 30)} RETURN count(*) AS n",
            (1, 0),
            lambda g, p: g.hop1(g.age > p["minAge"], g.age < 30),
        ),
        "a_literal_behind_a_parameter_is_constant": (
            "MATCH {class:Person, as:p3, where:(age > :minAge)}-knows->"
            "{as:f, where:(age < :maxAge)}-knows->{as:g, where:(age > 50)} "
            "RETURN count(*) AS n",
            (1, 1),
            lambda g, p: g.hop2(g.age > p["minAge"], g.age < p["maxAge"], g.age > 50),
        ),
        "a_parameter_behind_a_literal_keeps_both_live": (
            "MATCH {class:Person, as:p4, where:(age > :minAge)}-knows->"
            "{as:f, where:(age < 30)}-knows->{as:g, where:(age > :minAge)} "
            "RETURN count(*) AS n",
            (0, 2),
            lambda g, p: g.hop2(g.age > p["minAge"], g.age < 30, g.age > p["minAge"]),
        ),
        "a_parameter_on_the_edge_is_live": (
            "MATCH {class:Person, as:p5, where:(age > :minAge)}"
            ".outE('knows'){where:(creationDate > :d)}.inV(){as:f} "
            "RETURN count(*) AS n",
            (0, 1),
            lambda g, p: g.hop1(g.age > p["minAge"], g.person, g.date > p["d"]),
        ),
        "a_literal_on_the_edge_is_constant": (
            "MATCH {class:Person, as:p6, where:(age > :minAge)}"
            ".outE('knows'){where:(creationDate > 14000)}.inV(){as:f} "
            "RETURN count(*) AS n",
            (1, 0),
            lambda g, p: g.hop1(g.age > p["minAge"], g.person, g.date > 14_000),
        ),
        "a_whole_chain_of_literals_is_constant": (
            "MATCH {class:Person, as:p7, where:(age > 40)}-knows->{as:f}-knows->"
            "{as:g, where:(age < 30)} RETURN count(*) AS n",
            (2, 0),
            lambda g, p: g.hop2(g.age > 40, g.person, g.age < 30),
        ),
    }

    @pytest.mark.parametrize("case", sorted(LAST_HOPS))
    def test_a_pass_is_constant_where_nothing_it_reads_is_a_parameter(
        self, snb_counts, case
    ):
        db, snap, _ = snb_counts
        sql, passes, count = self.LAST_HOPS[case]
        g = _Knows(snap)
        first = {"minAge": 40, "maxAge": 30, "d": 14_000}
        ask = lambda p: db.query(sql, p, engine="tpu", strict=True).to_dicts()
        got, lowered = _lowered(lambda: ask(first))
        assert got == [{"n": count(g, first)}] and got[0]["n"] > 0
        assert lowered == tuple(2 * n for n in passes)
        # the kept passes serve other parameters too
        other = {"minAge": 25, "maxAge": 60, "d": 16_000}
        before = _rerecords()
        assert ask(other) == [{"n": count(g, other)}]
        assert _rerecords() == before
        assert count(g, other) != count(g, first) or "> :" not in sql

    def test_a_static_parameter_is_a_constant_of_the_plan(self, sdb):
        """A string parameter is baked into the compiled predicate and
        joins the plan's key: each value records a plan of its own."""
        sql = (
            "MATCH {class:Profiles, as:p, where:(age > :a)}-HasFriend->"
            "{as:f, where:(name <> :who)} RETURN count(*) AS n"
        )
        for who in ("carol", "alice"):
            p = {"a": 20, "who": who}
            ask = lambda engine: sdb.query(
                sql, p, engine=engine, strict=(engine == "tpu")
            ).to_dicts()
            got, lowered = _lowered(lambda: ask("tpu"))
            assert got == ask("oracle") and got[0]["n"] in (4, 5)
            assert lowered == (2, 0)

    @pytest.mark.parametrize(
        "last_hop", ["(age < 24)", "(age < :a)"], ids=["literal", "parameter"]
    )
    def test_under_an_overlay_every_pass_is_live(self, last_hop):
        """A delta-maintained snapshot patches columns in place between
        two replays (`TestAHullUnderDeltas`' graph): nothing is kept,
        and a COUNT after a write to the property its last hop reads is
        the oracle's."""
        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.storage.deltas import arm_delta_maintenance

        db = Database(f"const_deltas_{'a' in last_hop[-3:]}")
        db.schema.create_vertex_class("Writer")
        db.schema.create_vertex_class("Msg")
        db.schema.create_edge_class("Wrote")
        writers = [db.new_vertex("Writer", age=20 + i) for i in range(6)]
        for i in range(10):
            db.new_edge("Wrote", db.new_vertex("Msg", length=i), writers[i % 6])
        arm_delta_maintenance(db, spare_vertices=16, spare_edges=16)
        sql = (
            "MATCH {class:Msg, as:m}-Wrote->{as:p, where:%s} RETURN count(*) AS n"
            % last_hop
        )
        ask = lambda engine: db.query(
            sql, {"a": 24}, engine=engine, strict=(engine == "tpu")
        ).to_dicts()
        try:
            got, lowered = _lowered(lambda: ask("tpu"))
            assert got == ask("oracle") == [{"n": 8}]
            assert lowered == (0, 2)
            # the last writer turns 21: two more messages count. A
            # property write leaves the topology clean, the plan replays
            writers[5].set("age", 21)
            db.save(writers[5])
            assert not db.current_snapshot(require_fresh=True)._overlay.topology_dirty
            before = _rerecords()
            got, lowered = _lowered(lambda: ask("tpu"))
            assert got == ask("oracle") == [{"n": 9}]
            assert lowered == (0, 0) and _rerecords() == before
        finally:
            drain_warmups()
            db.detach_snapshot()

    def test_a_group_of_config5_lanes_gets_each_lanes_own_count(self, snb_counts):
        import orientdb_tpu.obs.timeline as TL
        from orientdb_tpu.exec.tpu_engine import _GROUP_MIN
        from orientdb_tpu.storage import bigshape as B

        db, snap, _ = snb_counts
        sql = SNB_COUNTS["config5"][0].replace("AS n", "AS lane")
        plist = [{"d": d} for d in (12_000,) + self.SWEEP]
        assert len(plist) >= _GROUP_MIN
        ask = lambda: db.query_batch([sql] * len(plist), plist, engine="tpu", strict=True)
        ask()
        before = _rerecords()  # and the first group's program is compiled
        TL.recorder.reset()
        (got, (kept, live)) = _lowered(lambda: [rs.to_dicts()[0] for rs in ask()])
        assert [r["path"] for r in TL.recorder.records()] == ["group"]
        assert got == [{"lane": B.numpy_config5_count(snap, p["d"])} for p in plist]
        assert _rerecords() == before and (kept, live) == (0, 0)

    @pytest.mark.parametrize(
        "last_hop", ["{as:h}", "{as:h, where:(uid >= :u)}"], ids=["kept", "live"]
    )
    def test_the_float32_twin_still_refuses_a_chain_past_int32(self, last_hop):
        """40 ** 7 chains wrap int32 several times over. The twin runs
        the whole chain in float32 at the recording, kept passes too."""
        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.ops.predicates import Uncompilable

        db = _dense_db(40)
        hops = "".join("-L->{as:%s}" % a for a in "bcdef")
        sql = "MATCH {class:N, as:a}%s-L->%s RETURN count(*) AS n" % (hops, last_hop)
        try:
            assert sql.count("-L->") == 6
            with pytest.raises(Uncompilable, match="overflows int32"):
                db.query(sql, {"u": 0}, engine="tpu", strict=True)
            short = "MATCH {class:N, as:a}-L->{as:b}-L->%s RETURN count(*) AS n" % last_hop
            got, lowered = _lowered(
                lambda: db.query(short, {"u": 0}, engine="tpu", strict=True).to_dicts()
            )
            assert got == [{"n": 40**3}]
            assert lowered == ((4, 0) if last_hop == "{as:h}" else (0, 4))
        finally:
            drain_warmups()
            db.detach_snapshot()


# -- a unit hop reads its far end's columns from the plan, in edge order ---------


SLICED_ENDS, GATHERED_ENDS = "plan.count.ends_sliced", "plan.count.ends_gathered"


def _ends(run):
    """``run()``, and the passes (whose far mask read the plan's copies,
    that evaluated it at their ends) lowered meanwhile, every background
    trace finished."""
    before = _counted(SLICED_ENDS, GATHERED_ENDS)
    got = run()
    return got, tuple(b - a for a, b in zip(before, _counted(SLICED_ENDS, GATHERED_ENDS)))


def _authors_db(name, doubled=False):
    """Messages that each have one author (``Wrote``, stored from the
    message, with a weight ``w``) and one signature (``Signed``, stored
    from the author): both one edge a message. Every fourth writer has no
    age; ``Bot`` is a subclass of ``Writer``. ``doubled``: the first
    message has a second author."""
    db = Database(name)
    db.schema.create_vertex_class("Writer")
    db.schema.create_class("Bot", superclasses=("Writer",))
    db.schema.create_vertex_class("Msg")
    db.schema.create_edge_class("Wrote")
    db.schema.create_edge_class("Signed")
    authors = [
        db.new_vertex(
            "Writer", name=f"w{i}", score=i % 3, **({"age": 20 + i} if i % 4 else {})
        )
        for i in range(8)
    ] + [db.new_vertex("Bot", name=f"b{i % 5}", age=18 + i, score=i % 4) for i in range(16)]
    # fewer messages than bots: every plan below is rooted at the messages
    msgs = [db.new_vertex("Msg", length=i) for i in range(12)]
    for i, m in enumerate(msgs):
        a = authors[(5 * i) % len(authors)]
        db.new_edge("Wrote", m, a, w=i % 5)
        db.new_edge("Signed", a, m)
    if doubled:
        db.new_edge("Wrote", msgs[0], authors[1], w=2)
    db._authors = authors
    return db


#: whether a case's lowerings moved (ends_sliced, ends_gathered)
BY_COPIES, AT_THE_ENDS = (True, False), (False, True)
BY_AUTHOR = (
    "MATCH {class:Msg, as:m, where:(length > :l)}-Wrote->"
    "{as:p, where:(age < :a)} RETURN count(*) AS n"
)
BY_AUTHOR_PARAMS = [{"l": 3, "a": 24}, {"l": -1, "a": 30}]
ENDS_CASES = {
    # name: (attach, doubled, the statement, the parameters it is asked
    # with in turn, the counters its lowerings move)
    "creators_with_an_absent_age": (
        _attach_plain,
        False,
        "MATCH {class:Msg, as:m, where:(length > :l)}-Wrote->"
        "{as:p, where:(age IS NULL OR age > :a)} RETURN count(*) AS n",
        [{"l": 3, "a": 24}, {"l": -1, "a": 100}, {"l": 5, "a": 20}],
        BY_COPIES,
    ),
    "a_class_filter_and_two_columns": (
        _attach_plain,
        False,
        # two conjuncts at each end: the messages stay the root
        "MATCH {class:Msg, as:m, where:(length > :l AND length < 99)}-Wrote->"
        "{class:Bot, as:p, where:(age < :a AND score >= :s)} RETURN count(*) AS n",
        [{"l": 2, "a": 26, "s": 1}, {"l": 0, "a": 99, "s": 0}, {"l": 9, "a": 22, "s": 2}],
        BY_COPIES,
    ),
    "a_string_column": (
        _attach_plain,
        False,
        "MATCH {class:Msg, as:m, where:(length > :l)}-Wrote->"
        "{as:p, where:(name <> 'b2' AND age < :a)} RETURN count(*) AS n",
        [{"l": 1, "a": 35}, {"l": -1, "a": 99}, {"l": 4, "a": 24}],
        BY_COPIES,
    ),
    "a_parameter_free_mask_behind_a_live_edge_filter": (
        _attach_plain,
        False,
        "MATCH {class:Msg, as:m}.outE('Wrote'){where:(w > :x)}.inV()"
        "{as:p, where:(age < 24)} RETURN count(*) AS n",
        [{"x": 1}, {"x": -1}, {"x": 3}],
        BY_COPIES,
    ),
    "a_unit_hop_walked_in": (
        _attach_plain,
        False,
        "MATCH {class:Msg, as:m, where:(length > :l)}<-Signed-"
        "{as:p, where:(age < :a)} RETURN count(*) AS n",
        [{"l": 3, "a": 24}, {"l": 0, "a": 100}, {"l": 12, "a": 27}],
        BY_COPIES,
    ),
    # where the hop is not one edge a vertex, or the CSR may change under
    # the plan, the mask is evaluated at the ends as before
    "a_hull_where_one_vertex_holds_two_edges": (
        _attach_plain, True, BY_AUTHOR, BY_AUTHOR_PARAMS, AT_THE_ENDS,
    ),
    "delta_maintained": (
        _attach_maintained, False, BY_AUTHOR, BY_AUTHOR_PARAMS, AT_THE_ENDS,
    ),
    "mesh_sharded": (
        _attach_on_a_mesh, False, BY_AUTHOR, BY_AUTHOR_PARAMS, AT_THE_ENDS,
    ),
    # a tiered snapshot takes no pushdown at all
    "tiered": (
        _attach_tiered, False, BY_AUTHOR, BY_AUTHOR_PARAMS, (False, False),
    ),
}


class TestAUnitHopReadsItsEndsInEdgeOrder:
    """A weight pass over a hop whose hull holds one edge a vertex reads
    its destination's columns from copies the recording made in that
    hop's edge order (`tpu_engine._FarEnds`): every read a slice, the
    compare with the parameters in the replay. Observed from the
    snapshot's CSR (``unit_out`` / ``unit_in``), never declared."""

    def test_creator_1hop_swept_is_the_benchmarks_reference(self, snb_raw):
        db, ref = snb_raw
        sql, kind, _params = SCAN_4S_SHAPES["creator_1hop"]
        sql = sql.replace(" AS n", " AS swept")
        sweep = [
            {"minLen": l, "maxAge": a}
            for l in (200, 700, 1300, 1800)
            for a in (25, 33, 47, 60)
        ]
        ask = lambda p: [
            tuple(r.values())
            for r in db.query(sql, p, engine="tpu", strict=True).to_dicts()
        ]
        got, lowered = _ends(lambda: ask(sweep[0]))
        assert got == ref.answer(kind, sweep[0])
        # the recording and its replay's one trace
        assert lowered == (2, 0)
        before = _rerecords()
        got, lowered = _ends(lambda: [ask(p) for p in sweep[1:]])
        want = [ref.answer(kind, p) for p in sweep[1:]]
        assert got == want and len({w[0][0] for w in want}) > 8
        assert lowered == (0, 0) and _rerecords() == before

    @pytest.mark.parametrize("case", sorted(ENDS_CASES))
    def test_the_count_is_the_oracles(self, monkeypatch, case):
        from orientdb_tpu.exec.tpu_engine import drain_warmups

        attach, doubled, sql, plist, moves = ENDS_CASES[case]
        db = _authors_db(f"ends_{case}", doubled)
        ask = lambda engine: [
            db.query(sql, p, engine=engine, strict=(engine == "tpu")).to_dicts()
            for p in plist
        ]
        try:
            attach(db, monkeypatch)
            got, lowered = _ends(lambda: ask("tpu"))
            want = ask("oracle")
            assert got == want and len({r[0]["n"] for r in want}) == len(plist)
            assert tuple(n > 0 for n in lowered) == moves
            if case != "delta_maintained":
                return
            # a write to the column the mask reads leaves the topology
            # clean: the plan replays over the patched column
            db._authors[5].set("age", 40)
            db.save(db._authors[5])
            assert not db.current_snapshot(require_fresh=True)._overlay.topology_dirty
            before = _rerecords()
            got, lowered = _ends(lambda: ask("tpu"))
            assert got == ask("oracle") != want
            assert lowered == (0, 0) and _rerecords() == before
        finally:
            drain_warmups()
            db.detach_snapshot()

    def test_a_group_of_lanes_reads_one_set_of_copies(self, snb_counts):
        import orientdb_tpu.obs.timeline as TL
        from orientdb_tpu.exec.tpu_engine import _GROUP_MIN

        db, snap, _ = snb_counts
        sql = MSG_COUNT % "ends"
        plist = [
            {"minLen": l, "maxAge": a}
            for l, a in [(FEW, 60), (EVERY, 30), (900, 45), (EVERY, 60), (NONE, 60)]
        ]
        assert len(plist) >= _GROUP_MIN
        ask = lambda: [
            rs.to_dicts()[0]
            for rs in db.query_batch([sql] * len(plist), plist, engine="tpu", strict=True)
        ]
        want = [{"n": _msg_count(snap, p)} for p in plist]
        got, lowered = _ends(ask)
        assert got == want and len({w["n"] for w in want}) == len(plist)
        assert lowered[0] > 0 and lowered[1] == 0
        before = _rerecords()
        TL.recorder.reset()
        # the group's program, traced here if not before, slices too
        got, lowered = _ends(ask)
        assert [r["path"] for r in TL.recorder.records()] == ["group"]
        assert got == want and lowered[1] == 0 and _rerecords() == before

    def test_a_many_to_many_hop_evaluates_its_mask_at_its_ends(self, snb_counts):
        from orientdb_tpu.storage import bigshape as B

        db, snap, _ = snb_counts
        sql = SNB_COUNTS["knows_1hop"][0].replace("AS n", "AS ends")
        age = snap.v_columns["age"]
        sweep = [{"minAge": 40, "maxAge": 30}, {"minAge": 25, "maxAge": 60}]
        numpy = lambda p: B.numpy_1hop_count(
            snap,
            (age.values > p["minAge"]) & age.present,
            (age.values < p["maxAge"]) & age.present,
        )
        got, lowered = _ends(
            lambda: [db.query(sql, p, engine="tpu", strict=True).to_dicts() for p in sweep]
        )
        assert got == [[{"ends": numpy(p)}] for p in sweep]
        assert lowered[0] == 0 and lowered[1] > 0

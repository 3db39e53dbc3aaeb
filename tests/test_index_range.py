"""A contiguous index range is sliced, not gathered (``ops/csr.IndexRange``).

Parity of every reader over a range against the same reader over the
``int32`` array the range stands for; the lowering of the benchmark's
rooted statements (no gather as long as the root's hull bucket is left
in the replay's jaxpr); and the ``plan.read.range`` / ``plan.read.gather``
counters. Beside them, the COUNT pushdown's segment sums over an edge
class's vertex hull (``ops/device_graph.vertex_hull``): what their
replays gather, what ``plan.segsum.hull`` / ``plan.segsum.full`` count,
and that a seed does not move a hull; and a COUNT that folds its root
(``plan.count.root_fold``): the replays of the benchmark's four scan
statements compact nothing and gather through no candidate; and the
passes of a COUNT's weight chain that read no parameter
(``plan.count.pass_const``): config5's replay computes nothing as long
as ``hasCreator``, and the other five statements lower the gathers they
lowered before. No chip and no time in any of it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from orientdb_tpu.ops import csr as K
from orientdb_tpu.utils.metrics import metrics

N = 50  # the column's length

#: name -> (start, size, width, slab_start, slab_size)
RANGES = {
    "hull_at_the_start": (0, 12, 16, 0, 0),
    "hull_in_the_middle": (17, 9, 16, 0, 0),
    "hull_at_the_end": (38, 12, 16, 0, 0),
    "width_past_the_columns_end": (40, 10, 64, 0, 0),
    "size_0": (7, 0, 8, 0, 0),
    "whole_column_no_padding": (0, N, N, 0, 0),
    "hull_then_slab": (5, 9, 16, 44, 6),
    "slab_alone": (0, 0, 8, 44, 6),
    "segment_runs_past_the_column": (45, 10, 16, 0, 0),
    "segment_wholly_past_the_column": (60, 4, 8, 0, 0),
}

COLUMNS = {
    "int32": (np.arange(100, 100 + N, dtype=np.int32), jnp.int32(-1)),
    "bool": (np.arange(N) % 3 == 0, False),
    "float32": (np.linspace(0.5, 9.5, N).astype(np.float32), jnp.float32(-2.5)),
}


def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert np.array_equal(a, b), (a, b)


class TestTakeRange:
    @pytest.mark.parametrize("dtype", sorted(COLUMNS))
    @pytest.mark.parametrize("name", sorted(RANGES))
    def test_take_range_is_take_pad_over_the_materialised_index(self, name, dtype):
        values, fill = COLUMNS[dtype]
        values = jnp.asarray(values)
        rng = K.IndexRange(*RANGES[name])
        idx = rng.materialise()
        assert idx.shape == rng.shape == (rng.width,) and idx.dtype == jnp.int32
        want = K.take_pad(values, idx, fill)
        _same(K.take_range(values, rng, fill), want)
        # the public reader dispatches on the index's type alone
        _same(K.take_pad(values, rng, fill), want)

    @pytest.mark.parametrize("name", sorted(RANGES))
    def test_an_empty_column_reads_as_fill(self, name):
        rng = K.IndexRange(*RANGES[name])
        empty = jnp.zeros((0,), jnp.int32)
        _same(
            K.take_pad(empty, rng, jnp.int32(-1)),
            K.take_pad(empty, rng.materialise(), jnp.int32(-1)),
        )

    @pytest.mark.parametrize("name", sorted(RANGES))
    def test_materialise_is_the_array_the_engine_used_to_build(self, name):
        start, size, width, slo, slab = RANGES[name]
        pos = np.arange(width)
        old = np.where(
            pos < size,
            start + pos,
            np.where(pos < size + slab, slo + (pos - size), -1),
        ).astype(np.int32)
        _same(K.IndexRange(*RANGES[name]).materialise(), old)

    @pytest.mark.parametrize("name", sorted(RANGES))
    def test_index_at_is_take_pad_of_the_materialised_index(self, name):
        rng = K.IndexRange(*RANGES[name])
        pos = jnp.asarray(
            [-1, 0, 1, rng.size - 1, rng.size, rng.size + rng.slab_size - 1,
             rng.size + rng.slab_size, rng.width - 1, -1],
            jnp.int32,
        )
        pos = jnp.where(pos < rng.width, pos, -1)
        _same(K.index_at(rng, pos), K.take_pad(rng.materialise(), pos, jnp.int32(-1)))
        _same(K.index_at(rng.materialise(), pos), K.index_at(rng, pos))

    def test_as_index_leaves_an_array_alone(self):
        arr = jnp.asarray([3, -1, 7], jnp.int32)
        assert K.as_index(arr) is arr
        _same(K.as_index(K.IndexRange(2, 3, 4)), np.asarray([2, 3, 4, -1], np.int32))

    @pytest.mark.parametrize(
        "fields",
        [(-1, 2, 4), (0, 5, 4), (0, 2, 4, 0, 3), (0, 2, 4, -1, 1)],
    )
    def test_a_range_that_cannot_be_is_refused(self, fields):
        with pytest.raises(ValueError):
            K.IndexRange(*fields)

    def test_a_range_lowers_to_no_gather_and_an_array_to_one(self):
        values = jnp.arange(N, dtype=jnp.int32)
        rng = K.IndexRange(5, 9, 16, 44, 6)

        def prims(fn, *args):
            return {e.primitive.name for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)}

        assert "gather" not in prims(lambda v: K.take_pad(v, rng, -1), values)
        assert "gather" in prims(
            lambda v, i: K.take_pad(v, i, -1), values, rng.materialise()
        )


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = sub if hasattr(sub, "eqns") else getattr(sub, "jaxpr", None)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _gather_index_lengths(jaxpr):
    """The leading length of every gather's index operand."""
    return [
        int(e.invars[1].aval.shape[0])
        for e in _eqns(jaxpr)
        if e.primitive.name == "gather"
    ]


def _reads():
    c = metrics.snapshot()["counters"]
    return c.get("plan.read.range", 0), c.get("plan.read.gather", 0)


# -- node masks -----------------------------------------------------------------


@pytest.fixture(scope="module")
def mask_db():
    """Two vertex classes laid out one after the other, a subclass, a
    string and an int column with gaps."""
    from orientdb_tpu.models.database import Database
    from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

    db = Database("range_masks")
    db.schema.create_vertex_class("Animal")
    db.schema.create_class("Dog", superclasses=("Animal",))
    db.schema.create_vertex_class("Rock")
    db.schema.create_edge_class("Near")
    vs = []
    for i in range(9):
        vs.append(db.new_vertex("Animal", name=f"a{i % 4}", age=i))
    for i in range(7):
        props = {"name": f"d{i % 3}"}
        if i % 2:
            props["age"] = 10 + i  # age absent on every other dog
        vs.append(db.new_vertex("Dog", **props))
    for i in range(6):
        vs.append(db.new_vertex("Rock", age=3 * i))
    for i in range(len(vs) - 1):
        db.new_edge("Near", vs[i], vs[i + 1])
    attach_fresh_snapshot(db)
    db._vs = vs
    yield db
    db.detach_snapshot()


def _node_patterns(db):
    rid = db._vs[12].rid  # a dog with an age
    return {
        "bare_node": "{as:a}",
        "class_filter": "{class:Animal, as:a}",
        "subclass_filter": "{class:Dog, as:a}",
        "rid_filter": f"{{rid:{rid}, as:a}}",
        "where_int": "{as:a, where:(age > 4)}",
        "where_param": "{as:a, where:(age = :k)}",
        "where_string": "{as:a, where:(name = 'd1')}",
        "where_is_null": "{as:a, where:(age IS NULL)}",
        "class_and_where": "{class:Animal, as:a, where:(age < 13 AND name != 'a2')}",
        "class_rid_and_where": f"{{class:Dog, rid:{rid}, as:a, where:(age > 0)}}",
    }


NODE_CASES = [
    "bare_node", "class_filter", "subclass_filter", "rid_filter", "where_int",
    "where_param", "where_string", "where_is_null", "class_and_where",
    "class_rid_and_where",
]


class TestNodeMasksOverARange:
    @pytest.mark.parametrize("case", NODE_CASES)
    def test_a_mask_over_a_range_is_the_mask_over_its_array(self, mask_db, case):
        from orientdb_tpu.exec.engine import parse_cached
        from orientdb_tpu.exec.tpu_engine import TpuMatchSolver

        sql = f"MATCH {_node_patterns(mask_db)[case]}-Near->{{as:b}} RETURN b"
        solver = TpuMatchSolver(mask_db, parse_cached(sql), {"k": 11})
        mask = solver._node_masks["a"]
        V = solver.dg.num_vertices
        lo, hi = solver.snap.vertex_hull("Dog")
        assert 0 < lo < hi < V
        some_true = False
        for rng in (
            K.IndexRange(0, V, K.bucket(V)),  # the universe
            K.IndexRange(lo, hi - lo, K.bucket(hi - lo)),  # a class hull
            K.IndexRange(lo, hi - lo, 32, 0, 4),  # a hull and a second segment
            K.IndexRange(V - 3, 3, 8),  # the column's end, padded past it
            K.IndexRange(4, 0, 8),  # nothing
        ):
            got = np.asarray(mask(rng))
            _same(got, mask(rng.materialise()))
            some_true = some_true or bool(got.any())
        assert some_true, "the case admits nothing anywhere: it tests nothing"


# -- the lowering of the benchmark's rooted statements --------------------------

FRIENDS = (
    "MATCH {class:Person, as:p, where:(uid = :personId)}-knows-{as:f} "
    "RETURN f.uid AS personId, f.age AS age"
)
CREATOR_1HOP = (
    "MATCH {class:Message, as:m, where:(length > :minLen)}-hasCreator->"
    "{as:p, where:(age < :maxAge)} RETURN count(*) AS n"
)
LOWERED = {
    # statement, parameters, the root's class
    "friends": (FRIENDS, {"personId": 17}, "Person"),
    # a COUNT folds its root (TestACountsRootIsFolded): whether its
    # parameters admit few messages or every one, nothing is gathered
    # through the candidates, at the hull's bucket or at their capacity
    "creator_1hop": (CREATOR_1HOP, {"minLen": 1950, "maxAge": 60}, "Message"),
    "creator_1hop_widest": (
        CREATOR_1HOP.replace("as:m", "as:msg"),
        {"minLen": 0, "maxAge": 60},
        "Message",
    ),
}


@pytest.fixture(scope="module")
def snb():
    from orientdb_tpu.exec.tpu_engine import drain_warmups
    from orientdb_tpu.storage.bigshape import build_snb_shape

    db, snap = build_snb_shape(3000, msgs_per_person=3, avg_knows=6, seed=1)
    yield db, snap
    drain_warmups()
    db.detach_snapshot()


def _record(db, snap, sql, params):
    known = set(getattr(snap, "_plan_cache", ()))
    before = _reads()
    from orientdb_tpu.exec.tpu_engine import drain_warmups

    rows = db.query(sql, params, engine="tpu", strict=True).to_dicts()
    after = _reads()
    # the background warm-up traces the same solver: let it end before a
    # test traces the replay itself (two traces share one size schedule)
    drain_warmups()
    (new,) = set(snap._plan_cache) - known
    plan = snap._plan_cache[new].plans[0]
    return rows, plan, (after[0] - before[0], after[1] - before[1])


class TestLowering:
    @pytest.mark.parametrize("shape", sorted(LOWERED))
    def test_no_gather_as_long_as_the_roots_hull_bucket(self, snb, shape):
        db, snap = snb
        sql, params, root_class = LOWERED[shape]
        rows, plan, (ranged, gathered) = _record(db, snap, sql, params)
        assert rows and (shape != "friends" or len(rows) > 3)
        lo, hi = snap.vertex_hull(root_class)
        hull = K.bucket(hi - lo)
        assert hull >= 2048  # far from every other buffer of the plan
        jaxpr = jax.make_jaxpr(plan._replay)(
            plan._arg_subset(), plan._dyn_args(params)
        ).jaxpr
        lengths = _gather_index_lengths(jaxpr)
        assert hull not in lengths, sorted(set(lengths))
        if shape == "friends":
            # a rooted row read: nothing in it is sized by the class
            assert lengths, "a plan with no gather at all proves nothing"
            assert max(lengths) < hull // 8, sorted(set(lengths))
        else:
            # the creators' ages are the plan's, in message order: the
            # COUNT gathers nothing at all
            assert lengths == [], sorted(set(lengths))
            from orientdb_tpu.exec.tpu_engine import _cap_of

            length = snap.v_columns["length"].values[lo:hi]
            cap = _cap_of(int((length > params["minLen"]).sum()))
            assert (cap > hull) == (shape == "creator_1hop_widest")
            assert cap not in lengths, sorted(set(lengths))
        assert ranged > 0 and gathered > 0

    def test_the_vmapped_group_replay_has_no_hull_gather_either(self, snb):
        db, snap = snb
        sql, params, _ = LOWERED["friends"]
        rows, plan, _counts = _record(
            db, snap, sql.replace("AS age", "AS years"), params
        )
        replay = plan._replay_group if plan._rows_grouped() else plan._replay
        dyn = {k: jnp.stack([v] * 4) for k, v in plan._dyn_args(params).items()}
        jaxpr = jax.make_jaxpr(jax.vmap(replay, in_axes=(None, 0)))(
            plan._arg_subset(), dyn
        ).jaxpr
        lo, hi = snap.vertex_hull("Person")
        assert K.bucket(hi - lo) not in _gather_index_lengths(jaxpr)

    def test_a_seeded_root_counts_only_gathers(self):
        """An indexed ``uid`` seeds the root from the host index: its
        candidates are an array, and every read through them a gather."""
        from orientdb_tpu import Database, PropertyType
        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

        db = Database("range_seeded")
        person = db.schema.create_vertex_class("Person")
        person.create_property("uid", PropertyType.LONG)
        db.schema.create_edge_class("knows")
        vs = [db.new_vertex("Person", uid=i, age=20 + i) for i in range(30)]
        for i in range(29):
            db.new_edge("knows", vs[i], vs[i + 1])
        db.command("CREATE INDEX Person.uid ON Person (uid) UNIQUE")
        snap = attach_fresh_snapshot(db)
        try:
            rows, plan, (ranged, gathered) = _record(
                db, snap, FRIENDS, {"personId": 7}
            )
            assert sorted(r["personId"] for r in rows) == [6, 8]
            assert plan.seed_spec, "the root was not seeded from the index"
            assert ranged == 0 and gathered > 0
            # the same statement with no index scans the hull as a range
            db.command("DROP INDEX Person.uid")
            snap2 = attach_fresh_snapshot(db)
            rows2, plan2, (ranged2, _g) = _record(
                db, snap2, FRIENDS, {"personId": 7}
            )
            assert sorted(r["personId"] for r in rows2) == [6, 8]
            assert not plan2.seed_spec and ranged2 > 0
        finally:
            drain_warmups()
            db.detach_snapshot()


# -- a COUNT folds its root: no candidate is compacted or gathered through --------

KNOWS_1HOP = (
    "MATCH {class:Person, as:p, where:(age > :minAge)}-knows->"
    "{as:f, where:(age < :maxAge)} RETURN count(*) AS n"
)
KNOWS_2HOP = (
    "MATCH {class:Person, as:p, where:(age > :minAge)}-knows->{as:f}-knows->"
    "{as:g, where:(age < :maxAge)} RETURN count(*) AS n"
)
CONFIG5 = (
    "MATCH {class:Person, as:p, where:(age > :minAge)}.outE('knows')"
    "{where:(creationDate > :d)}.inV(){as:f, where:(age < :maxAge)}, "
    "{class:Message, as:m}-hasCreator->{as:f} RETURN count(*) AS n"
)
#: the four statements of the benchmark's scan_4s mix: statement, the
#: parameters that lead its pool (the widest), the root's class
SCAN_4S = {
    "config5": (CONFIG5, {"minAge": 40, "d": 12_000, "maxAge": 30}, "Person"),
    "creator_1hop": (CREATOR_1HOP, {"minLen": 200, "maxAge": 60}, "Message"),
    "knows_2hop": (KNOWS_2HOP, {"minAge": 20, "maxAge": 70}, "Person"),
    "knows_1hop": (KNOWS_1HOP, {"minAge": 20, "maxAge": 70}, "Person"),
}


def _folds():
    """(roots folded, tables read) by the COUNTs lowered so far, every
    background trace finished."""
    from orientdb_tpu.exec.tpu_engine import drain_warmups

    drain_warmups()
    return metrics.counter("plan.count.root_fold"), metrics.counter(
        "plan.count.root_rows"
    )


def _calls(jaxpr):
    """The names of the jitted functions a jaxpr calls, nested ones too."""
    return {
        e.params.get("name")
        for e in _eqns(jaxpr)
        if e.primitive.name in ("pjit", "jit")
    }


class TestACountsRootIsFolded:
    @pytest.mark.parametrize("shape", sorted(SCAN_4S))
    def test_a_scan_replay_compacts_and_gathers_no_root(self, snb, shape):
        from orientdb_tpu.exec.tpu_engine import _cap_of

        db, snap = snb
        sql, params, root_class = SCAN_4S[shape]
        fold0, rows0 = _folds()
        # a name of its own, so that the statement is recorded here
        rows, plan, _reads_counted = _record(
            db, snap, sql.replace("AS n", "AS folded"), params
        )
        assert rows[0]["folded"] > 0
        jaxpr = jax.make_jaxpr(plan._replay)(
            plan._arg_subset(), plan._dyn_args(params)
        ).jaxpr
        # one a lowering: the eager recording (its float32 twin folds
        # under the same count) and one trace of the replay, which the
        # background warm-up and this jaxpr share
        assert _folds() == (fold0 + 2, rows0)
        # no sort (jnp.nonzero's) and no prefix-sum-and-search compaction
        assert "sort" not in {e.primitive.name for e in _eqns(jaxpr)}
        called = _calls(jaxpr)
        assert "compact_indices" not in called and "_segment_sum" in called
        # and no gather through the root: neither as long as its hull's
        # bucket nor as its candidates' capacity would have been
        lo, hi = snap.vertex_hull(root_class)
        column, bound = ("age", "minAge") if root_class == "Person" else ("length", "minLen")
        admitted = int((snap.v_columns[column].values[lo:hi] > params[bound]).sum())
        assert admitted > (hi - lo) // 2, "the widest parameters admit most roots"
        lengths = _gather_index_lengths(jaxpr)
        # creator_1hop reads its creators' ages from the plan's copies
        # (TestAUnitHopReadsItsEndsInEdgeOrder) and gathers nothing
        assert bool(lengths) == (shape != "creator_1hop"), lengths
        assert not {K.bucket(hi - lo), _cap_of(admitted)} & set(lengths), sorted(
            set(lengths)
        )
        # nothing was sized by what the recording saw
        assert plan.solver.sched.values == [rows[0]["folded"]]


# -- the COUNT pushdown's segment sums run over the edge class's vertex hull ------

#: statement, its weight passes (one a hop)
SEGSUMS = {"knows_1hop": (KNOWS_1HOP, 1), "knows_2hop": (KNOWS_2HOP, 2)}
AGES = {"minAge": 40, "maxAge": 30}


def _segsums():
    c = metrics.snapshot()["counters"]
    return tuple(
        c.get(f"plan.segsum.{form}", 0) for form in ("hull", "full", "unit")
    )


def _segment_sum_gathers(jaxpr):
    """The index lengths of the gathers of each ``csr._segment_sum`` call."""
    return [
        _gather_index_lengths(e.params["jaxpr"].jaxpr)
        for e in _eqns(jaxpr)
        if e.primitive.name in ("pjit", "jit") and e.params.get("name") == "_segment_sum"
    ]


class TestSegmentSumsOverTheHull:
    @pytest.mark.parametrize("shape", sorted(SEGSUMS))
    def test_a_knows_count_gathers_no_boundary_past_the_persons(self, snb, shape):
        db, snap = snb
        sql, passes = SEGSUMS[shape]
        hull0, full0, unit0 = _segsums()
        rows, plan, _reads_counted = _record(db, snap, sql, AGES)
        assert rows[0]["n"] > 0
        lo, hi = snap.vertex_hull("Person")
        V = snap.num_vertices
        assert (lo, hi) == (0, 3000) and V == 12000
        jaxpr = jax.make_jaxpr(plan._replay)(
            plan._arg_subset(), plan._dyn_args(AGES)
        ).jaxpr
        hull1, full1, unit1 = _segsums()
        calls = _segment_sum_gathers(jaxpr)
        # two boundary gathers a pass, each as long as the person hull:
        # knows has persons without a friend and persons with many
        assert calls == [[hi - lo, hi - lo]] * passes, calls
        # and nothing in the whole replay is as wide as the vertex universe
        # (or its bucket): what is left are the [E] gathers over knows and
        # the root's candidates
        lengths = _gather_index_lengths(jaxpr)
        E = snap.edge_classes["knows"].num_edges
        assert E > K.bucket(V), "an [E] gather must be told apart from a [V] one"
        assert not [n for n in lengths if V <= n < E], sorted(set(lengths))
        # counted where Python lowers the pass: the eager recording (with
        # its float32 twin) and this trace, every one over a hull
        assert hull1 - hull0 >= 3 * passes and full1 == full0 and unit1 == unit0

    def test_a_hull_of_one_edge_a_vertex_is_sliced(self, snb):
        """creator_1hop walks hasCreator from the messages, one edge a
        message: its pass sums nothing, the replay reads the edges' values
        where they lie, and reads age and its presence at every edge's
        creator from the plan's copies in message order: no gather."""
        db, snap = snb
        p = {"minLen": 900, "maxAge": 30}
        before = _segsums()
        rows, plan, _reads_counted = _record(
            db, snap, CREATOR_1HOP.replace("AS n", "AS sliced"), p
        )
        assert rows[0]["sliced"] > 0
        dec = plan.solver.dg.edges["hasCreator"]
        assert dec.unit_out and dec.hull_out == snap.vertex_hull("Message")
        jaxpr = jax.make_jaxpr(plan._replay)(plan._arg_subset(), plan._dyn_args(p)).jaxpr
        # one pass a lowering, a slice: the eager recording, its float32
        # twin and the replay's trace
        hull, full, unit = np.subtract(_segsums(), before)
        assert (hull, full) == (0, 0) and unit >= 3
        assert _segment_sum_gathers(jaxpr) == [[]]
        assert _gather_index_lengths(jaxpr) == []
        assert "cumsum" not in {e.primitive.name for e in _eqns(jaxpr)}

    def test_a_class_that_spans_the_universe_counts_as_full(self):
        """A persons-only graph: the hull is the universe, and the pass is
        the program it was before hulls."""
        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.ops.device_graph import device_graph
        from orientdb_tpu.storage.bigshape import build_person_knows

        db, snap = build_person_knows(2000, avg_knows=6, seed=4)
        try:
            dec = device_graph(snap).edges["knows"]
            assert dec.hull_out == dec.hull_in == (0, 2000)
            hull0, full0, unit0 = _segsums()
            _rows, plan, _counted = _record(db, snap, KNOWS_1HOP, AGES)
            jaxpr = jax.make_jaxpr(plan._replay)(
                plan._arg_subset(), plan._dyn_args(AGES)
            ).jaxpr
            hull1, full1, unit1 = _segsums()
            assert _segment_sum_gathers(jaxpr) == [[2000, 2000]]
            assert full1 - full0 >= 3 and hull1 == hull0 and unit1 == unit0
        finally:
            drain_warmups()
            db.detach_snapshot()

    def test_two_seeds_of_one_scale_have_the_same_hulls(self):
        """A seed deals the same degrees in another order (as the
        benchmark's seeds do): the vertices with an edge move, the hull
        does not, and one compiled program serves both."""
        from orientdb_tpu.ops.device_graph import vertex_hull

        P, V = 300, 1000
        rng = np.random.default_rng(7)
        deg = rng.poisson(1.0, P)
        deg[0], deg[P - 1] = 0, 2
        graphs = []
        for order in (np.arange(P), np.arange(P)[::-1]):
            full = np.zeros(V, np.int64)
            full[:P] = deg[order]
            indptr = np.concatenate([[0], np.cumsum(full)]).astype(np.int32)
            graphs.append(
                (indptr, rng.integers(0, 5, int(indptr[-1])).astype(np.int32))
            )
        exact = [vertex_hull(ip, np.arange(V + 1)) for ip, _ in graphs]
        assert exact[0] != exact[1]
        snapped = [vertex_hull(ip, np.asarray([0, P, V])) for ip, _ in graphs]
        assert snapped == [(0, P), (0, P)]
        want = [K.indptr_segment_sum(vals, ip, 1024) for ip, vals in graphs]
        for hulls, programs in ((snapped, 1), (exact, 2)):
            compiled = K._segment_sum._cache_size()
            for (indptr, vals), hull, full in zip(graphs, hulls, want):
                _same(K.indptr_segment_sum(vals, indptr, 1024, hull), full)
            assert K._segment_sum._cache_size() - compiled == programs


# -- a COUNT's passes that read no parameter are the plan's, not the replay's -----

SHORTEST_PATH_LEN = (
    "MATCH {class:Person, as:a, where:(uid = :person1Id)}, "
    "{class:Person, as:b, where:(uid = :person2Id)} "
    "RETURN shortestPath(a, b, 'BOTH', 'knows').size() - 1 AS len"
)
#: the benchmark's six statements on the module's graph: statement,
#: parameters, the passes a lowering of its COUNT (takes from the plan,
#: lowers), and the index length of every gather of its replay, in order.
#: The lists of the knows statements and the rooted ones are the ones
#: lowered before any pass was kept (3 000 persons, 9 000 messages and
#: hasCreator edges, 17 927 knows edges); config5's lost [9000, 9000,
#: 3000, 3000] at its head: the reorder of an all-true edge mask, v_class
#: at every creator edge's message, and the two boundary gathers of the
#: pass's segment sum; creator_1hop's lost its two boundary gathers (one
#: edge a message: the segment sum is a slice), then age and its presence
#: at every edge's creator (the plan keeps both in message order)
KEPT = {
    "config5": (*SCAN_4S["config5"][:2], (1, 1), [17927, 17927, 3000, 3000]),
    "creator_1hop": (*SCAN_4S["creator_1hop"][:2], (0, 1), []),
    "knows_2hop": (
        *SCAN_4S["knows_2hop"][:2],
        (0, 2),
        [17927, 3000, 3000, 17927, 17927, 3000, 3000],
    ),
    "knows_1hop": (*SCAN_4S["knows_1hop"][:2], (0, 1), [17927, 3000, 3000]),
    "friends": (FRIENDS, {"personId": 17}, (0, 0), [8] * 18 + [32] * 10 + [40] * 2),
    "shortest_path_len": (
        SHORTEST_PATH_LEN,
        None,  # an adjacent pair, found in the graph
        (0, 0),
        [8] * 7 + [1] * 24 + [64] * 3 + [1] * 3 + [64, 64, 1, 64, 64, 64, 1, 1]
        + [17927, 17927, 1] + [8] * 7,
    ),
}


def _passes():
    """(passes taken from a plan, passes lowered) by the COUNTs lowered
    so far, every background trace finished."""
    from orientdb_tpu.exec.tpu_engine import drain_warmups

    drain_warmups()
    return np.array(
        [metrics.counter("plan.count.pass_const"), metrics.counter("plan.count.pass_live")]
    )


class TestConstantPassesAreThePlans:
    @pytest.mark.parametrize("shape", sorted(KEPT))
    def test_a_replay_lowers_the_passes_that_read_a_parameter(self, snb, shape):
        db, snap = snb
        sql, params, passes, gathers = KEPT[shape]
        if params is None:
            k = snap.edge_classes["knows"]
            a = int(np.flatnonzero(np.diff(k.indptr_out))[0])
            params = {"person1Id": a, "person2Id": int(k.dst[k.indptr_out[a]])}
        before = _passes()
        # a name of its own, so that the statement is recorded here
        for name in (" AS n", " AS age", " AS len"):
            sql = sql.replace(name, " AS kept")
        rows, plan, _reads_counted = _record(db, snap, sql, params)
        assert rows and all(v is not None for v in rows[0].values())
        jaxpr = jax.make_jaxpr(plan._replay)(
            plan._arg_subset(), plan._dyn_args(params)
        ).jaxpr
        # two lowerings: the eager recording (the float32 twin is not
        # counted) and one trace of the replay, which the background
        # warm-up and this jaxpr share
        assert tuple(_passes() - before) == tuple(2 * n for n in passes)
        assert _gather_index_lengths(jaxpr) == gathers
        ends = [f"{ENDS}:{n}" for n in ("p", "v")] if shape == "creator_1hop" else []
        assert sorted(plan.consts) == (["plan:count_w"] if passes[0] else ends)
        if shape != "config5":
            return
        # the kept weights are as long as the pass's hull, the persons,
        # and nothing the replay computes is as long as hasCreator (or
        # as the messages: both 9 000 here): no gather, no prefix sum
        hc = snap.edge_classes["hasCreator"].num_edges
        assert plan.consts["plan:count_w"].shape == (3000,)
        assert plan.consts["plan:count_w"].dtype == jnp.int32
        wide = [
            e.primitive.name
            for e in _eqns(jaxpr)
            if any(hc in getattr(v.aval, "shape", ()) for v in (*e.invars, *e.outvars))
        ]
        assert wide == [], wide
        assert "cumsum" in {e.primitive.name for e in _eqns(jaxpr)}  # knows' own

    def test_a_group_of_lanes_shares_the_kept_weights(self, snb):
        """The group replay vmaps over the parameters alone: the kept
        weights stay one array for all lanes, and no lane pays the pass."""
        db, snap = snb
        sql, params = KEPT["config5"][:2]
        rows, plan, _reads_counted = _record(
            db, snap, sql.replace(" AS n", " AS lanes"), params
        )
        args = plan._arg_subset()
        dyn = {k: jnp.stack([v] * 4) for k, v in plan._dyn_args(params).items()}
        closed = jax.make_jaxpr(jax.vmap(plan._replay, in_axes=(None, 0)))(args, dyn)
        kept = closed.jaxpr.invars[sorted(args).index("plan:count_w")]
        assert kept.aval.shape == (3000,)
        readers = [e for e in _eqns(closed.jaxpr) if kept in e.invars]
        assert readers and all(
            len(v.aval.shape) == 1 for e in readers for v in e.outvars
        )
        hc = snap.edge_classes["hasCreator"].num_edges
        assert not [
            e.primitive.name
            for e in _eqns(closed.jaxpr)
            if any(hc in getattr(v.aval, "shape", ()) for v in (*e.invars, *e.outvars))
        ]


# -- a unit hop's far end is read from the plan's copies, in edge order ---------

#: the copies' prefix in creator_1hop's plan: its destination, the class
#: and the direction of the hop
ENDS = "plan:ends:p:hasCreator:out:age"


class TestAUnitHopReadsItsEndsInEdgeOrder:
    def test_the_copies_are_unbatched_arguments_of_the_group_replay(self, snb):
        """Lanes differ in their parameters alone: the copies stay one
        array for every lane, each read a slice, and the lanes' compare
        is what is batched."""
        db, snap = snb
        sql, params = SCAN_4S["creator_1hop"][:2]
        rows, plan, _reads_counted = _record(
            db, snap, sql.replace(" AS n", " AS ends"), params
        )
        E = snap.edge_classes["hasCreator"].num_edges
        args = plan._arg_subset()
        dyn = {k: jnp.stack([v] * 4) for k, v in plan._dyn_args(params).items()}
        closed = jax.make_jaxpr(jax.vmap(plan._replay, in_axes=(None, 0)))(args, dyn)
        for name, dtype in (("v", jnp.int32), ("p", jnp.bool_)):
            kept = closed.jaxpr.invars[sorted(args).index(f"{ENDS}:{name}")]
            assert kept.aval.shape == (E,) and kept.aval.dtype == dtype
            readers = [e for e in _eqns(closed.jaxpr) if kept in e.invars]
            assert readers and all(
                v.aval.shape == (E,) for e in readers for v in e.outvars
            ), [e.primitive.name for e in readers]
        assert _gather_index_lengths(closed.jaxpr) == []

    def test_the_copies_are_device_bytes_while_the_plan_lives(self):
        """`memory_report` (the benchmark's ``hbm_state_gb``) counts the
        copies under ``plan_consts`` while their plan lives, and no
        longer once it is collected."""
        import gc

        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.ops.device_graph import device_graph
        from orientdb_tpu.storage.bigshape import build_snb_shape

        db, snap = build_snb_shape(300, msgs_per_person=3, avg_knows=4, seed=2)
        try:
            dg = device_graph(snap)
            kept = lambda: dg.memory_report()["per_device"]["plan_consts"]
            before = kept()
            sql, params = SCAN_4S["creator_1hop"][:2]
            db.query(sql, params, engine="tpu", strict=True)
            drain_warmups()
            E = snap.edge_classes["hasCreator"].num_edges
            assert E == 900
            # an int32 and a bool a message
            assert kept() - before == 5 * E
            snap._plan_cache.clear()
            gc.collect()
            assert kept() == before
        finally:
            drain_warmups()
            db.detach_snapshot()


# -- who materialises: a mesh-sharded graph --------------------------------------


class TestWhoKeepsTheGather:
    def test_a_mesh_sharded_graph_gets_the_array(self):
        from orientdb_tpu.exec.tpu_engine import _index_range

        class Graph:
            mesh_graph = None

        plain, sharded = Graph(), Graph()
        sharded.mesh_graph = object()
        assert isinstance(_index_range(plain, None, 3, 4, 8), K.IndexRange)
        for got in (
            _index_range(sharded, None, 3, 4, 8),
            _index_range(plain, object(), 3, 4, 8),  # a tiered snapshot
        ):
            _same(got, K.IndexRange(3, 4, 8).materialise())


# -- a delta-maintained snapshot: the slab is the hull's second segment ----------


class TestSlabSegment:
    ROWS = (
        "MATCH {class:Person, as:p, where:(age > :a)}-Knows->{as:q} "
        "RETURN p.name AS p, q.name AS q"
    )
    BARE = "MATCH {as:p, where:(age > :a)}-Knows->{as:q} RETURN p.name AS p, q.name AS q"
    COUNT = (
        "MATCH {class:Person, as:p, where:(age > :a)}-Knows->{as:q} "
        "RETURN count(*) AS n"
    )

    @pytest.mark.parametrize("sql", ["ROWS", "BARE", "COUNT"])
    def test_a_root_in_the_live_slab_is_found(self, sql):
        from orientdb_tpu.exec.tpu_engine import drain_warmups
        from orientdb_tpu.models.database import Database
        from orientdb_tpu.storage.deltas import arm_delta_maintenance

        db = Database(f"range_slab_{sql.lower()}")
        vs = [db.new_vertex("Person", name=f"p{i}", age=20 + i) for i in range(12)]
        for i in range(11):
            db.new_edge("Knows", vs[i], vs[i + 1])
        arm_delta_maintenance(db, spare_vertices=64, spare_edges=64)
        q = getattr(self, sql)
        try:
            # the two newcomers land in the append slab, outside the hull
            w = db.new_vertex("Person", name="w", age=77)
            x = db.new_vertex("Person", name="x", age=78)
            db.new_edge("Knows", w, vs[0])
            db.new_edge("Knows", x, w)
            db.delete(vs[9])  # a dead base vertex: class -1 inside the hull
            before = _reads()
            got = db.query(q, {"a": 28}, engine="tpu", strict=True).to_dicts()
            assert _reads()[0] > before[0]
            want = db.query(q, {"a": 28}, engine="oracle").to_dicts()
            key = lambda rows: sorted(str(sorted(r.items())) for r in rows)
            assert key(got) == key(want)
            if sql == "COUNT":
                assert got == [{"n": 3}]  # p10->p11, w->p0, x->w; p9 is gone
            else:
                assert {"p": "w", "q": "p0"} in got and {"p": "x", "q": "w"} in got
            snap = db.current_snapshot(require_fresh=True)
            assert snap.slab_vertex_range()[1] > snap.slab_vertex_range()[0]
        finally:
            drain_warmups()
            db.detach_snapshot()

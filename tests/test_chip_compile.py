"""Compiles for a DESCRIBED TPU v5e, at the sizes ``chip_smoke.py`` runs.

No chip is attached here: the TPU's compiler is asked to compile the main
path's kernels and whole plans at the scale tier's shapes (8 M persons,
~80 M ``knows`` edges) for a ``v5e:2x2`` topology, so what the chip's
compiler would refuse is found without chip time. Nothing runs and no
result or time is checked — a compile that passes is not a chip run.

The topology is described inside the module-scoped fixture below and
nowhere else: only one process at a time may load the TPU's library, so
nothing touches it at import, and all of these tests live in this one
file (the worker that gets the file loads the library once).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from orientdb_tpu.ops import csr
from orientdb_tpu.utils.config import config

PERSONS = 8_000_000
EDGES = 80_000_000
TINY = 2_000  # the graph a plan is recorded on before it is lowered big


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    devs = np.array(topo.devices[:4]).reshape(1, 4)
    return Mesh(devs, (config.mesh_replica_axis, config.mesh_shard_axis))


@pytest.fixture
def blocked_cumsum(monkeypatch):
    """The backend gate in ``value_cumsum`` reads the process's default
    backend (the CPU here): steer it onto the MXU-blocked branch the
    chip takes."""
    orig = csr.value_cumsum
    monkeypatch.setattr(
        csr, "value_cumsum", lambda v, force_blocked=False: orig(v, True)
    )


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    mem = compiled.memory_analysis()
    # one program must fit one chip's 16 GB next to nothing else
    assert (
        mem.argument_size_in_bytes
        + mem.temp_size_in_bytes
        + mem.output_size_in_bytes
    ) < 16e9, mem
    return compiled


def test_prefix_sums_at_80m_edges(one_chip):
    def spec(dtype):
        return jax.ShapeDtypeStruct((EDGES,), dtype, sharding=one_chip)

    _compile(lambda v: csr.value_cumsum(v, force_blocked=True), spec(jnp.int32))
    _compile(csr.mask_cumsum, spec(jnp.bool_))


def test_expand_and_compact_at_80m_edges(one_chip, blocked_cumsum):
    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    k, out = 1 << 20, 1 << 24
    _compile(
        lambda ip, nb, s, o, t: csr.gather_expand(ip, nb, s, o, t, out),
        spec((PERSONS + 1,)),
        spec((EDGES,)),
        spec((k,)),
        spec((k,)),
        spec(()),
    )
    # the selective regime (blocked prefix sum + binary searches)
    _compile(
        lambda m: csr.compact_indices(m, 1 << 16), spec((EDGES,), jnp.bool_)
    )


def test_pair_search_on_sf100s_friendship_graph_sixteen_lanes(
    one_chip, blocked_cumsum
):
    """``sf100_ic13_16s``'s program: 448 626 persons, 21 M ``knows``
    edges, the buffers ``_bfs_caps`` gives that graph, vmapped over a
    16-lane batch as the group replay does. Its dense level's
    temporaries are what fills the chip (3.3 GB; an axis of one under
    the lanes read 13.7 GB, and a bool scatter took the compiler half a
    minute where an int32 add takes a second)."""
    persons, edges = 448_626, 21_016_306

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def batch(ipo, dst, es, ipi, src, s, t):
        return jax.vmap(
            lambda a, b: csr.bfs_pair_len(
                ipo, dst, es, ipi, src, a, b, front=512, chunk=1 << 17
            )
        )(s, t)

    compiled = _compile(
        batch,
        spec((persons + 1,)),
        spec((edges,)),
        spec((edges,)),
        spec((persons + 1,)),
        spec((edges,)),
        spec((16,)),
        spec((16,)),
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


CREATOR_1HOP = (
    "MATCH {class:Message, as:m, where:(length > :minLen)}-hasCreator->"
    "{as:p, where:(age < :maxAge)} RETURN count(*) AS n"
)


@pytest.mark.parametrize(
    "shape", ["two_hop_count", "config5_count", "creator_1hop_count"]
)
def test_whole_count_plan_at_real_shapes(one_chip, blocked_cumsum, shape):
    """Record the plan on a tiny graph, then lower its whole jitted
    replay with every graph array at the scale tier's shape. The
    creator 1-hop's pass is a slice (one edge a message), and so are
    its reads of the creators' ages: the plan keeps them in message
    order and hands them to the replay as arguments."""
    import chip_smoke
    from orientdb_tpu.exec import tpu_engine
    from orientdb_tpu.storage import bigshape

    if shape == "two_hop_count":
        db, snap = bigshape.build_person_knows(
            TINY, avg_knows=10, seed=5, supernodes=4, supernode_degree=300
        )
        sql, params = chip_smoke.Q_2HOP, None
    else:
        db, snap = bigshape.build_snb_shape(
            TINY, msgs_per_person=2, avg_knows=10, seed=7
        )
        sql, params = (
            (chip_smoke.Q_CONFIG5, {"d": 15_000})
            if shape == "config5_count"
            else (CREATOR_1HOP, {"minLen": 200, "maxAge": 60})
        )
    try:
        db.query(sql, params=params, engine="tpu", strict=True)
        tpu_engine.drain_warmups()
        (variants,) = snap._plan_cache.values()
        plan = variants.plans[0]
        scale = PERSONS // TINY
        v_tiny = snap.num_vertices
        dims = {v_tiny: v_tiny * scale, v_tiny + 1: v_tiny * scale + 1}
        for ec in snap.edge_classes.values():
            dims[ec.num_edges] = ec.num_edges * scale

        def real(a):
            a = np.asarray(a) if not hasattr(a, "shape") else a
            return jax.ShapeDtypeStruct(
                tuple(dims.get(d, d) for d in a.shape),
                a.dtype,
                sharding=one_chip,
            )

        # what the recording kept for its replays (config5: the messages
        # of each person; the two hops of literals: the whole chain;
        # the creator 1-hop: age and its presence at every message's
        # creator, in message order) is as long as a vertex hull, and
        # grows with it
        sliced = shape == "creator_1hop_count"
        ends = "plan:ends:p:hasCreator:out:age"
        assert sorted(plan.consts) == (
            [f"{ends}:p", f"{ends}:v"] if sliced else ["plan:count_w"]
        )
        assert plan.solver.dg.edges["knows"].unit_out is False
        if "hasCreator" in plan.solver.dg.edges:
            assert plan.solver.dg.edges["hasCreator"].unit_out is True
        dims.update({c.shape[0]: c.shape[0] * scale for c in plan.consts.values()})
        arrays = {k: real(v) for k, v in plan._arg_subset().items()}
        assert set(plan.consts) <= set(arrays)
        # knows' edges, or (creator 1-hop) the 24 M persons and messages
        widest = 3 * PERSONS if sliced else EDGES
        assert max(s.shape[0] for s in arrays.values()) >= widest
        dyn = {k: real(v) for k, v in plan._dyn_args(params).items()}
        # the replay reads these sizes from host metadata
        dg = plan.solver.dg
        dg.num_vertices = dims[v_tiny]
        for dec in dg.edges.values():
            dec.num_edges = dims[dec.num_edges]
            dec.hull_out = tuple(b * scale for b in dec.hull_out)
            dec.hull_in = tuple(b * scale for b in dec.hull_in)
        _compile(plan._replay, arrays, dyn)
    finally:
        db.detach_snapshot()


def _sharded(mesh4, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(
        shape,
        dtype,
        sharding=NamedSharding(mesh4, P(config.mesh_shard_axis, None)),
    )


def _replicated(mesh4, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh4, P())
    )


def test_expand_gather_on_4_device_mesh(mesh4, blocked_cumsum):
    from orientdb_tpu.parallel import mesh_graph

    # snb shape on 4 shards: 24 M vertices → 6 M rows per shard; the
    # persons (and so all `knows` out-edges) sit in the first 8 M rows,
    # so the padded local edge width is shard 0's ~60 M
    rows, emax = 6_000_000, 60_000_000
    k, cap, cap_total = 1 << 20, 1 << 22, 1 << 23
    fn = mesh_graph._build_expand_gather(
        mesh4, config.mesh_shard_axis, cap, cap_total, True
    )
    compiled = _compile(
        fn,
        _sharded(mesh4, (4, rows + 1)),
        _sharded(mesh4, (4, emax)),
        _sharded(mesh4, (4, 1)),
        _sharded(mesh4, (4, 2)),
        _replicated(mesh4, (k,)),
    )
    # per device: a quarter of the sharded adjacency, not all of it
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 4 * (rows + 1 + emax + 3 + k) * 1.1
    assert "all-reduce" in compiled.as_text()


def test_sharded_bitmap_hop_on_4_device_mesh(mesh4):
    from orientdb_tpu.parallel import mesh_graph

    width, c, vb = EDGES // 4, 8, csr.bucket(3 * PERSONS)
    fn = mesh_graph._build_bitmap_hop(mesh4, config.mesh_shard_axis)
    compiled = _compile(
        fn,
        _sharded(mesh4, (4, width)),
        _sharded(mesh4, (4, width)),
        _sharded(mesh4, (4, width)),
        _replicated(mesh4, (EDGES,), jnp.bool_),
        _replicated(mesh4, (c, vb), jnp.bool_),
    )
    assert "all-reduce" in compiled.as_text()


def test_whole_graph_search_at_graph500_22(one_chip, blocked_cumsum):
    """``g500_s22_bfs_1s``'s program: the levels plan recorded on a
    256-label Kronecker graph, its whole jitted replay lowered at
    graph500-22's sizes (2.4 M vertices, 64.2 M ``Link`` edges): the
    level loop, the three sparse steps at the snapshot's buffer sizes
    and the dense pass's ``[E]`` gathers and prefix sums, beside ~1 GB of
    arguments."""
    from benchmark import run
    from orientdb_tpu.exec import tpu_engine

    V, E = 2_400_000, 64_200_000
    g = run.load_module("kinds", "graph500")
    raw = g.make_raw(
        {"scale": 8, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19, "graph_seed": 1}, 1
    )
    db, snap = g._handed_over(raw, "g500_compile")
    try:
        db.query(g.STATEMENT, params={"source": 3}, engine="tpu", strict=True)
        tpu_engine.drain_warmups()
        (variants,) = snap._plan_cache.values()
        plan = variants.plans[0]
        dims = {raw.V: V, raw.V + 1: V + 1, raw.E: E}

        def real(a):
            a = np.asarray(a) if not hasattr(a, "shape") else a
            return jax.ShapeDtypeStruct(
                tuple(dims.get(d, d) for d in a.shape), a.dtype, sharding=one_chip
            )

        arrays = {k: real(v) for k, v in plan._arg_subset().items()}
        assert sorted(s.shape[0] for s in arrays.values())[-2:] == [E, E]
        dyn = {k: real(v) for k, v in plan._dyn_args({"source": 5}).items()}
        # the replay reads these sizes from host metadata
        dg = plan.solver.dg
        dg.num_vertices = V
        snap.class_vertex_range["node"] = (0, V)
        dec = dg.edges["Link"]
        dec.num_edges, dec.hull_out, dec.hull_in = E, (0, V), (0, V)
        compiled = _compile(plan._replay, arrays, dyn)
        mem = compiled.memory_analysis()
        # two [E] int32 arrays and two pointer arrays in, a few [E]
        # temporaries of the dense pass beside them
        assert 0.5e9 < mem.argument_size_in_bytes < 0.6e9, mem
        assert mem.temp_size_in_bytes < 4e9, mem
    finally:
        db.detach_snapshot()

"""Mesh-sharded graph layout for the compiled MATCH engine.

The reference distributes a database by Hazelcast-replicating clusters to
server nodes ([E] OHazelcastPlugin / ODistributedStorage, SURVEY.md §2
"Distributed"); the TPU-native design instead **shards the adjacency
structure itself across the device mesh** and lets XLA collectives do the
merging:

- **out-CSR** row-sharded by source-vertex range ``[s·R, (s+1)·R)``:
  each shard holds a locally-rebased ``indptr`` and its slice of ``dst``;
- **in-CSR** row-sharded by destination-vertex range (reverse walks);
- the flat **edge list** (``edge_src``/``edge_dst``/``edge_id``) sliced
  into equal ranges for edge-parallel kernels (variable-depth bitmap hops,
  COUNT-pushdown segment sums).

Vertex and edge property columns are row-sharded too (vertex- /
edge-range ownership, `ops/device_graph.py`): per-device memory is
O(V/S + E/S), the SURVEY.md §7 SF100 per-chip budget. Property gathers
run in jit global view and XLA's SPMD partitioner inserts the
cross-shard collectives. Binding tables stay replicated (they are
query-sized, not graph-sized); each expansion step computes its shard's
local contribution under ``shard_map`` and the shards merge with
``all_gather`` (tables) or ``psum`` (bitmaps / weights) over ICI — the
SURVEY.md §5.7 frontier-merge design applied to the *real* engine.

All sharded buffers live in the owning ``DeviceGraph.arrays`` dict (keys
prefixed ``sh:``), placed with a ``NamedSharding`` over the mesh's
``shards`` axis, so compiled plans still receive ONE arg pytree shared by
every cached executable.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from orientdb_tpu.ops import csr as K
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.metrics import metrics


def _varying_zeros(shape, dtype, axes):
    """Zeros typed as varying over the manual mesh ``axes``: the skip
    branch of a per-shard ``lax.cond`` must carry the same varying axes
    as its live branch, and fresh zeros inside ``shard_map`` are typed
    unvarying."""
    return jax.lax.pcast(jnp.zeros(shape, dtype), axes, to="varying")


class ShardedEdgeArrays:
    """Host metadata for one edge class's sharded adjacency (the arrays
    themselves live in the DeviceGraph's flat dict)."""

    __slots__ = ("class_name", "prefix", "e_slice", "out_emax", "in_emax")

    def __init__(self, class_name: str, prefix: str):
        self.class_name = class_name
        self.prefix = prefix
        self.e_slice = 0  # edge-list slice width per shard
        self.out_emax = 0  # max local out-CSR edges across shards
        self.in_emax = 0


class MeshGraph:
    """Sharding context attached to a DeviceGraph."""

    def __init__(self, mesh: Mesh) -> None:
        if config.mesh_shard_axis not in mesh.shape:
            raise ValueError(
                f"mesh must have a {config.mesh_shard_axis!r} axis"
            )
        self.mesh = mesh
        self.n_shards = mesh.shape[config.mesh_shard_axis]
        self.rows_per_shard = 0
        self.edge: Dict[str, ShardedEdgeArrays] = {}

    def _spec(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(config.mesh_shard_axis, None))

    def build(self, dg) -> None:
        """Populate ``dg.arrays`` with sharded adjacency for every edge
        class of the snapshot behind ``dg``."""
        S = self.n_shards
        V = dg.num_vertices
        self.rows_per_shard = max(1, math.ceil(max(V, 1) / S))
        # shard row-ranges as a DEVICE OPERAND [S, 2] (lo, hi): the
        # expansion kernels read their range from this array instead of
        # baking `shard_id * rows_per_shard` as a trace constant, so an
        # elastic re-shard (same padded dims, moved boundaries) reuses
        # every cached executable
        R = self.rows_per_shard
        spans = np.stack(
            [
                np.arange(S, dtype=np.int32) * R,
                (np.arange(S, dtype=np.int32) + 1) * R,
            ],
            axis=1,
        )
        dg.arrays["sh:rowspan"] = jax.device_put(spans, self._spec())
        for name, dec in dg.edges.items():
            csr = dg.snap.edge_classes[name]
            sea = ShardedEdgeArrays(name, f"sh:{name}")
            self.edge[name] = sea
            self._put_csr(
                dg, sea, "out", csr.indptr_out, csr.dst, eid_map=None
            )
            self._put_csr(
                dg, sea, "in", csr.indptr_in, csr.src, eid_map=csr.edge_id_in
            )
            self._put_edge_list(dg, sea, csr)

    # -- layout builders -----------------------------------------------------

    def _shard_rows(self, indptr: np.ndarray):
        """Split a global CSR into per-shard locally-rebased rows."""
        S, R = self.n_shards, self.rows_per_shard
        V = indptr.shape[0] - 1
        ind_l = np.zeros((S, R + 1), np.int64)
        bases = np.zeros(S, np.int32)
        slices = []
        for s in range(S):
            r0 = min(s * R, V)
            r1 = min(r0 + R, V)
            seg = indptr[r0 : r1 + 1].astype(np.int64) - int(indptr[r0])
            ind_l[s, : seg.shape[0]] = seg
            if seg.shape[0] < R + 1:
                ind_l[s, seg.shape[0] :] = seg[-1] if seg.shape[0] else 0
            bases[s] = int(indptr[r0])
            slices.append((int(indptr[r0]), int(indptr[r1])))
        return ind_l.astype(np.int32), bases, slices

    def _put_csr(self, dg, sea, tag, indptr, nbrs, eid_map):
        spec = self._spec()
        S = self.n_shards
        ind_l, bases, slices = self._shard_rows(indptr)
        emax = max(1, max((b - a) for a, b in slices))
        nbr_l = np.full((S, emax), -1, np.int32)
        eid_l = np.full((S, emax), -1, np.int32) if eid_map is not None else None
        for s, (a, b) in enumerate(slices):
            nbr_l[s, : b - a] = nbrs[a:b]
            if eid_l is not None:
                eid_l[s, : b - a] = eid_map[a:b]
        p = sea.prefix
        dg.arrays[f"{p}:{tag}:indptr"] = jax.device_put(jnp.asarray(ind_l), spec)
        dg.arrays[f"{p}:{tag}:nbr"] = jax.device_put(jnp.asarray(nbr_l), spec)
        dg.arrays[f"{p}:{tag}:ebase"] = jax.device_put(
            jnp.asarray(bases[:, None]), spec
        )
        if eid_l is not None:
            dg.arrays[f"{p}:{tag}:eid"] = jax.device_put(jnp.asarray(eid_l), spec)
        if tag == "out":
            sea.out_emax = emax
        else:
            sea.in_emax = emax

    def _put_edge_list(self, dg, sea, csr):
        """Equal edge-range slices for edge-parallel kernels."""
        spec = self._spec()
        S = self.n_shards
        E = csr.num_edges
        W = max(1, math.ceil(max(E, 1) / S))
        sea.e_slice = W
        src_l = np.full((S, W), -1, np.int32)
        dst_l = np.full((S, W), -1, np.int32)
        eid_l = np.full((S, W), -1, np.int32)
        edge_src = csr.edge_src_np()
        for s in range(S):
            a, b = min(s * W, E), min((s + 1) * W, E)
            src_l[s, : b - a] = edge_src[a:b]
            dst_l[s, : b - a] = csr.dst[a:b]
            eid_l[s, : b - a] = np.arange(a, b, dtype=np.int32)
        p = sea.prefix
        dg.arrays[f"{p}:el:src"] = jax.device_put(jnp.asarray(src_l), spec)
        dg.arrays[f"{p}:el:dst"] = jax.device_put(jnp.asarray(dst_l), spec)
        dg.arrays[f"{p}:el:eid"] = jax.device_put(jnp.asarray(eid_l), spec)


# ---------------------------------------------------------------------------
# sharded execution kernels (called from TpuMatchSolver when a mesh is
# attached; all run under shard_map inside the solver's eager record run
# and inside the compiled replay's single jit alike)
#
# Every kernel is a MEMOIZED jax.jit keyed by (kernel, mesh, axis names,
# structural statics) — operand shapes (the padded dims) ride the jit's
# own shape cache, and shard row-ranges arrive as the `sh:rowspan`
# device operand. Before the memo, the eager recording executed each
# shard_map body primitive-by-primitive (a fresh SPMD program compile
# per primitive per call — 171 XLA compiles for ONE probe query);
# now a recording costs one cached Execute per kernel call, a
# shard sweep compiles each geometry once, and revisiting a geometry
# compiles NOTHING (the zero-retrace contract tests/test_sharded.py
# asserts via the mesh.kernel_builds counter — it counts memoized
# wrapper BUILDS, the trace-cache roots; operand shapes ride each
# build's jit cache, so with an unchanged workload a zero delta means
# no new executables either, which the tests additionally pin through
# build identity).
# ---------------------------------------------------------------------------

_MESH_KERNEL_CACHE: Dict[Tuple, object] = {}


def _mesh_kernel(name: str, mesh: Mesh, builder, *static):
    """Memoized jitted shard_map kernel for one (mesh, axes, statics)
    geometry. ``builder(mesh, shard_ax, *static)`` constructs the
    callable only on a miss."""
    ax = config.mesh_shard_axis
    key = (name, mesh, ax, config.mesh_replica_axis) + static
    fn = _MESH_KERNEL_CACHE.get(key)
    if fn is None:
        fn = jax.jit(builder(mesh, ax, *static))
        _MESH_KERNEL_CACHE[key] = fn
        # geometry-compile observability: the zero-retrace tests read
        # this counter's deltas; the flight record gets the event so a
        # compile-tainted dispatch is
        # distinguishable from a steady-state replay on the timeline
        metrics.incr("mesh.kernel_builds")
        from orientdb_tpu.obs.timeline import mark as _tl_mark

        _tl_mark("kernel_build")
    return fn


def _merge_dtype(mesh: Mesh):
    """psum element type for 0/1 bitmap contributions: int8 carries
    sums ≤ n_shards exactly up to 127 shards at a quarter of int32's
    ring bytes."""
    return (
        jnp.int8 if mesh.shape[config.mesh_shard_axis] <= 127 else jnp.int32
    )


def _build_expand_totals(mesh: Mesh, ax: str):
    def local(ind_l, span_l, srcs_rep):
        ind_l = ind_l[0]
        lo, hi = span_l[0, 0], span_l[0, 1]  # row-range device operand
        owned = (srcs_rep >= lo) & (srcs_rep < hi)
        ls = jnp.where(owned, srcs_rep - lo, -1)
        counts = K.degree_counts(ind_l, ls)
        tot = counts.sum()[None]
        return jax.lax.all_gather(tot, ax).reshape(-1)

    def kern(ind_sh, span_sh, srcs):
        return shard_map(
            local,
            mesh=mesh,
            in_specs=(P(ax, None), P(ax, None), P(None)),
            out_specs=P(None),
            # the output IS replicated (it is an all_gather over the
            # shard axis), but VMA's static inference marks all_gather
            # results as varying — unlike psum — so the check cannot
            # hold here; the psum-output kernels below run with it ON
            check_vma=False,
        )(ind_sh, span_sh, srcs)

    return kern


def expand_totals(mesh: Mesh, ind_sh, span_sh, srcs) -> jnp.ndarray:
    """Per-shard expansion totals [S] (replicated on every device).

    Each shard counts the out-degrees of the binding-table sources it
    owns (global ids inside its ``sh:rowspan`` row range); the result
    sizes the static expansion cap and the global total for the
    SizeSchedule. The gathered payload is one scalar per shard — the
    live extent — never a capacity block (jaxlint's full-capacity
    all_gather rule guards the distinction)."""
    return _mesh_kernel("expand_totals", mesh, _build_expand_totals)(
        ind_sh, span_sh, srcs
    )


def _build_expand_gather(
    mesh: Mesh, ax: str, cap: int, cap_total: int, is_out: bool
):
    def local(ind_l, nbr_l, extra_l, span_l, srcs_rep):
        ind_l, nbr_l, extra_l = ind_l[0], nbr_l[0], extra_l[0]
        sid = jax.lax.axis_index(ax)
        lo, hi = span_l[0, 0], span_l[0, 1]
        owned = (srcs_rep >= lo) & (srcs_rep < hi)
        ls = jnp.where(owned, srcs_rep - lo, -1)
        counts = K.degree_counts(ind_l, ls)
        tot = counts.sum()
        # the offset prefix is collective (every shard needs it), the
        # expansion itself is not: issue the scalar all_gather FIRST so
        # it flies while the local gather below runs
        all_tot = jax.lax.all_gather(tot, ax)
        my_off = jnp.cumsum(all_tot)[sid] - tot

        def expand(_):
            offsets = K.exclusive_cumsum(counts)
            row, epos, nbr = K.gather_expand(
                ind_l, nbr_l, ls, offsets, tot, cap
            )
            if is_out:
                eid = jnp.where(epos >= 0, epos + extra_l[0], -1)
            else:
                eid = K.take_pad(extra_l, epos, jnp.int32(-1))
            # gather_expand front-packs: rows [0, tot) are live. Scatter
            # them at this shard's exclusive offset in the global
            # segment (values shifted +1 so the zero identity becomes
            # the -1 padding after the merge).
            pos = jnp.arange(cap, dtype=jnp.int32)
            dest = jnp.where(pos < tot, pos + my_off, cap_total)
            z = jnp.zeros(cap_total, jnp.int32)
            return (
                z.at[dest].add(row + 1, mode="drop"),
                z.at[dest].add(eid + 1, mode="drop"),
                z.at[dest].add(nbr + 1, mode="drop"),
            )

        def skip(_):
            # frontier-sparse: a shard owning NO live sources skips its
            # gather/scatter entirely (the cond predicate varies per
            # shard; the branches carry no collective)
            z = _varying_zeros(cap_total, jnp.int32, ax)
            return z, z, z

        seg_row, seg_eid, seg_nbr = jax.lax.cond(
            tot > 0, expand, skip, jnp.int32(0)
        )
        # ONE fused ring reduce for the three packed segments: psum
        # merges the disjoint per-shard writes — O(pow2(global total))
        # bytes, never S·pow2(max local) capacity blocks
        m_row, m_eid, m_nbr = jax.lax.psum((seg_row, seg_eid, seg_nbr), ax)
        return m_row - 1, m_eid - 1, m_nbr - 1

    def kern(ind_sh, nbr_sh, extra_sh, span_sh, srcs):
        return shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(ax, None),
                P(ax, None),
                P(ax, None),
                P(ax, None),
                P(None),
            ),
            out_specs=(P(None), P(None), P(None)),
            check_vma=True,  # psum-merged outputs are provably replicated
        )(ind_sh, nbr_sh, extra_sh, span_sh, srcs)

    return kern


def expand_gather(
    mesh: Mesh,
    ind_sh,
    nbr_sh,
    extra_sh,
    span_sh,
    srcs,
    cap: int,
    cap_total: int,
    is_out: bool,
):
    """Sharded CSR expansion with a RING-compacted merge: every shard
    expands its owned sources into a static ``cap``-row local block,
    front-packs the live rows, scatters them at its global offset into a
    ``[cap_total]`` zero buffer, and the buffers merge with a ``psum``
    over the shard axis — XLA lowers it to the bandwidth-optimal ring
    reduce over ICI (SURVEY.md §5.7's ring exchange for binding-carrying
    expansions).

    vs the old ``all_gather`` of whole ``cap`` blocks, the merged
    segment is ``O(pow2(global total))`` instead of ``O(S·pow2(max
    local))``: under supernode skew (one shard's cap ≫ total/S) that is
    an up-to-S× saving in merge bytes and merged-table size. A shard
    whose local frontier slice is empty contributes a ``lax.cond``-
    skipped zero segment — its gather/scatter never runs.

    ``extra_sh`` is the per-shard global-edge-offset column (out-CSR:
    ``eid = local edge pos + base``) or the sharded ``edge_id_in`` map
    (in-CSR: local pos → out-order id); ``span_sh`` is the
    ``sh:rowspan`` row-range operand."""
    return _mesh_kernel(
        "expand_gather", mesh, _build_expand_gather, cap, cap_total, is_out
    )(ind_sh, nbr_sh, extra_sh, span_sh, srcs)


def _build_bitmap_hop(mesh: Mesh, ax: str):
    cdtype = _merge_dtype(mesh)

    def local(act_l, emit_l, eid_l, emask_rep, frontier_rep):
        act_l, emit_l, eid_l = act_l[0], emit_l[0], eid_l[0]
        em = K.take_pad(emask_rep, eid_l, False) & (act_l >= 0)

        def hop(_):
            return K.bitmap_hop(act_l, emit_l, em, frontier_rep).astype(
                cdtype
            )

        def skip(_):
            # frontier-sparse: dead frontier or mask-killed edge slice →
            # skip the [C, E_slice] gather and [C, vb] scatter entirely.
            # The predicate is deliberately gather-free (edge-list
            # slices see arbitrary sources, so per-shard frontier
            # locality does not exist here — the row-sharded BFS in
            # parallel/sharded.py owns that case).
            return _varying_zeros(frontier_rep.shape, cdtype, ax)

        contrib = jax.lax.cond(
            em.any() & frontier_rep.any(), hop, skip, jnp.int32(0)
        )
        # packed-dtype psum: int8 0/1 contributions, a quarter of the
        # old int32 all-reduce bytes per hop
        return jax.lax.psum(contrib, ax) > 0

    def kern(act_sh, emit_sh, eid_sh, emask_global, frontier):
        return shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(ax, None),
                P(ax, None),
                P(ax, None),
                P(None),
                P(None, None),
            ),
            out_specs=P(None, None),
            check_vma=True,
        )(act_sh, emit_sh, eid_sh, emask_global, frontier)

    return kern


def sharded_bitmap_hop(
    mesh: Mesh, act_sh, emit_sh, eid_sh, emask_global, frontier
) -> jnp.ndarray:
    """One variable-depth frontier hop over the sharded edge list: each
    shard scatter-ORs its edge slice's activations, and the [C, vb]
    bitmaps merge with a packed (int8) psum over the shards axis
    (SURVEY.md §5.7); a shard with no live activations cond-skips its
    scatter."""
    return _mesh_kernel("bitmap_hop", mesh, _build_bitmap_hop)(
        act_sh, emit_sh, eid_sh, emask_global, frontier
    )


def _build_weight_pass(mesh: Mesh, ax: str):
    def local(seg_l, emit_l, eid_l, emask_rep, ok_rep, w_rep):
        seg_l, emit_l, eid_l = seg_l[0], emit_l[0], eid_l[0]
        vb = w_rep.shape[0]

        def wpass(_):
            em = K.take_pad(emask_rep, eid_l, False) & (seg_l >= 0)
            ok = K.take_pad(ok_rep, emit_l, False)
            vals = (em & ok).astype(w_rep.dtype) * K.take_pad(
                w_rep, emit_l, jnp.zeros((), w_rep.dtype)
            )
            return jax.ops.segment_sum(
                vals, jnp.clip(seg_l, 0, vb - 1), num_segments=vb
            )

        def skip(_):
            # padding-only edge slice (E < S·W rounding): nothing to sum
            return _varying_zeros(vb, w_rep.dtype, ax)

        part = jax.lax.cond((seg_l >= 0).any(), wpass, skip, jnp.int32(0))
        return jax.lax.psum(part, ax)

    def kern(seg_sh, emit_sh, eid_sh, emask_global, dst_ok_global, w):
        return shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(ax, None),
                P(ax, None),
                P(ax, None),
                P(None),
                P(None),
                P(None),
            ),
            out_specs=P(None),
            check_vma=True,
        )(seg_sh, emit_sh, eid_sh, emask_global, dst_ok_global, w)

    return kern


def sharded_weight_pass(
    mesh: Mesh, seg_sh, emit_sh, eid_sh, emask_global, dst_ok_global, w
):
    """One COUNT-pushdown weight pass over the sharded edge list:
    ``new_w[v] = Σ_{local edges v→u} emask(e)·dst_ok(u)·w[u]`` per shard,
    psum-merged. ``dst_ok_global`` is the destination node-admission mask
    over the vertex universe (replicated); ``w`` [vb] carries the weights
    of the level below (all-ones for the last hop; its length IS vb)."""
    return _mesh_kernel("weight_pass", mesh, _build_weight_pass)(
        seg_sh, emit_sh, eid_sh, emask_global, dst_ok_global, w
    )

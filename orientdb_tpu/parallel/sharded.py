"""Sharded graph execution over a device mesh.

The reference scales out with Hazelcast replication and per-cluster server
ownership ([E] OHazelcastPlugin / ODistributedConfiguration, SURVEY.md §2
"Distributed"); the TPU-native design shards the **CSR by source-vertex
range across chips** and merges per-hop frontiers with XLA collectives over
ICI (SURVEY.md §5.7's ring-attention analog for deep traversal).

Mesh axes (the DP×TP analog for a graph engine):
  - ``replicas`` — independent query streams (each replica holds a block of
    the query batch; the data-parallel axis);
  - ``shards`` — CSR row ranges (each shard owns vertices
    [s·rows_per_shard, (s+1)·rows_per_shard) and their out-edges; the
    model-parallel axis).

Frontier-sparse schedule (the "invert the mesh" rework): the BFS state is
**vertex-sharded, never replicated** — each shard carries only its own
[Q, rows_per_shard] slice of the frontier and visited bitmaps, so the
per-hop collective is ONE ``psum_scatter`` of the hop's contribution
(the reduce half of the old psum all-reduce; the broadcast half is gone
because no shard ever needs the full [Q, V_pad] bitmap again). A shard
whose local frontier slice is empty skips its gather/scatter entirely
(``lax.cond`` on a device-side liveness scalar), the loop early-exits the
moment the global frontier drains (a scalar ``psum`` carried through a
``lax.while_loop`` — ``max_depth`` is a device operand, not a trace
constant), and the loop body is double-buffered: hop N's ring merge is
issued on the carried contribution slot BEFORE the local gather of the
next frontier consumes it, so XLA's async-collective scheduler can
overlap the merge with the expansion compute in front of it. The final
[Q, V] assembly happens HOST-side after the last hop (per-shard
``copy_to_host_async`` in :func:`fetch_sharded`) — the merge that used
to ride an all-gather inside every hop.

Recompile-free geometry: ``_BFS_STEP_CACHE`` keys executables by
(mesh, axis names) only — padded dims ride the jit cache's shape key,
and the scattered-state design removes shard row-range trace constants
from the BFS entirely (the engine-side expansion kernels in
``parallel/mesh_graph.py`` take their row spans as device operands for
the same reason) — so a shard sweep or an elastic re-shard back to a
previously-seen geometry never retraces.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from orientdb_tpu.storage.snapshot import GraphSnapshot
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import get_logger
from orientdb_tpu.utils.metrics import metrics

log = get_logger("sharded")



def provision_devices(n_devices: int) -> list:
    """Return >= n_devices JAX devices, self-provisioning virtual CPU
    devices when the default backend has fewer — for the CPU tests and
    ``tools/dryrun.py``. The result may be CPU devices even when the
    default backend is an accelerator; :func:`make_mesh` refuses that.

    `jax.config.update('jax_num_cpu_devices', n)` works after jax import
    as long as the CPU backend has not been initialized yet.
    """
    # Must run BEFORE any backend is initialized (any jax.devices() call
    # anywhere): once backends exist the update raises, and we can only
    # fall through to whatever device count is already live.
    try:
        jax.config.update("jax_num_cpu_devices", n_devices)
    except Exception:
        # backends already initialized: the update is rejected and we
        # fall through to whatever device count is live
        log.debug("jax_num_cpu_devices update rejected", exc_info=True)
    devs = jax.devices()
    if len(devs) >= n_devices:
        return devs
    cpus = jax.devices("cpu")
    if len(cpus) >= n_devices:
        return cpus
    raise ValueError(
        f"need {n_devices} devices, have {len(devs)} "
        f"(and only {len(cpus)} CPU devices could be provisioned)"
    )


def make_mesh(
    n_devices: Optional[int] = None,
    replicas: int = 1,
    devices: Optional[list] = None,
) -> Mesh:
    """1-D or 2-D mesh: (replicas, shards). `n_devices` defaults to all."""
    if devices is not None:
        devs = devices
        n = n_devices or len(devs)
        if n > len(devs):
            raise ValueError(
                f"need {n} devices but explicit list has {len(devs)}"
            )
    elif n_devices is not None:
        # provision BEFORE jax.devices(): initializing any backend blocks
        # the jax_num_cpu_devices update provision_devices relies on
        devs = provision_devices(n_devices)
        n = n_devices
        if jax.default_backend() != "cpu" and devs[0].platform == "cpu":
            # a mesh of virtual CPU devices would report a "sharded"
            # run that never touched the accelerator
            raise ValueError(
                f"need {n} {jax.default_backend()} devices, have "
                f"{len(jax.devices())}; refusing a CPU mesh on a "
                f"{jax.default_backend()} backend"
            )
    else:
        devs = jax.devices()
        n = len(devs)
    if n % replicas:
        raise ValueError(f"{n} devices not divisible into {replicas} replicas")
    arr = np.array(devs[:n]).reshape(replicas, n // replicas)
    return Mesh(arr, (config.mesh_replica_axis, config.mesh_shard_axis))


def fetch_sharded(arr) -> np.ndarray:
    """Host-side assembly of a fully-sharded device result: start every
    shard's device→host copy together (``copy_to_host_async`` per
    addressable shard), then assemble — the per-shard result-page merge
    moved OFF the hot loop, where it used to be the broadcast half of a
    per-hop all-reduce. The blocking wall records as one transfer
    interval on the active flight record (obs/timeline): the sharded
    path's drain is compute+copy fused (no extra sync is inserted just
    to split them), so it scores as hidden only where OTHER dispatches'
    device work overlapped it."""
    import time as _time

    t0 = _time.monotonic()
    shards = getattr(arr, "addressable_shards", None)
    if shards is not None:
        for sh in shards:
            fn = getattr(sh.data, "copy_to_host_async", None)
            if fn is not None:
                fn()
    out = np.asarray(arr)
    from orientdb_tpu.obs.timeline import add_transfer

    add_transfer(t0, _time.monotonic(), int(out.nbytes), "fetch")
    return out


class ShardedCSR:
    """One edge class's out-CSR, row-sharded by vertex range.

    Host layout: [n_shards, rows_per_shard+1] locally-rebased indptr and
    [n_shards, max_local_edges] destination arrays (-1 padded), placed with
    a NamedSharding so each device holds exactly its shard.
    """

    def __init__(self, mesh: Mesh, indptr: np.ndarray, dst: np.ndarray):
        self.mesh = mesh
        n_shards = mesh.shape[config.mesh_shard_axis]
        V = int(indptr.shape[0]) - 1
        rows = max(1, math.ceil(V / n_shards))
        V_pad = rows * n_shards
        self.num_vertices = V
        self.rows_per_shard = rows
        self.padded_vertices = V_pad
        ind_l = np.zeros((n_shards, rows + 1), np.int32)
        counts = []
        locals_ = []
        for s in range(n_shards):
            r0 = min(s * rows, V)
            r1 = min(r0 + rows, V)
            seg = indptr[r0 : r1 + 1] - indptr[r0]
            ind_l[s, : seg.shape[0]] = seg
            if seg.shape[0] < rows + 1:
                ind_l[s, seg.shape[0] :] = seg[-1] if seg.shape[0] else 0
            locals_.append(dst[indptr[r0] : indptr[r1]])
            counts.append(int(indptr[r1] - indptr[r0]))
        e_max = max(max(counts), 1)
        dst_l = np.full((n_shards, e_max), -1, np.int32)
        for s, seg in enumerate(locals_):
            dst_l[s, : seg.shape[0]] = seg
        shard_spec = NamedSharding(mesh, P(config.mesh_shard_axis, None))
        self.indptr = jax.device_put(jnp.asarray(ind_l), shard_spec)
        self.dst = jax.device_put(jnp.asarray(dst_l), shard_spec)

    @classmethod
    def from_snapshot(
        cls, snap: GraphSnapshot, mesh: Mesh, edge_class: str
    ) -> "ShardedCSR":
        csr = snap.edge_classes[edge_class]
        return cls(mesh, csr.indptr_out, csr.dst)


#: (mesh, axis names) → jitted BFS step. Padded dims (rows_per_shard,
#: v_pad, query block) key the jit's OWN shape cache, and max_depth is a
#: device operand — so a shard sweep revisiting a geometry, a re-shard,
#: or a depth change NEVER retraces (the deviceguard-visible contract;
#: tests/test_sharded.py asserts it). Meshes per process are few; the
#: cache is unbounded.
_BFS_STEP_CACHE: Dict[Tuple, object] = {}


def build_bfs_step(mesh: Mesh):
    """Compile the sharded multi-hop BFS step (the framework's
    `dryrun_multichip` "training step": DP over query replicas × TP over
    CSR shards, one psum_scatter ring merge per hop over ICI). Geometry
    rides operand shapes; depth rides a device operand."""
    from orientdb_tpu.parallel.mesh_graph import _merge_dtype, _varying_zeros

    # axis names are host-side trace constants: read them here, not
    # inside the traced closure (they also key the memo — a retuned
    # axis name must not serve a stale executable)
    shard_ax = config.mesh_shard_axis
    rep_ax = config.mesh_replica_axis
    key = (mesh, shard_ax, rep_ax)
    cached = _BFS_STEP_CACHE.get(key)
    if cached is not None:
        return cached
    S = mesh.shape[shard_ax]
    cdtype = _merge_dtype(mesh)
    metrics.incr("mesh.kernel_builds")

    def step(indptr_sh, dst_sh, roots, depth_cap):
        # roots: [Q, V_pad] bool — replica-sharded rows, SHARD-sharded
        # columns: the frontier/visited state lives scattered by vertex
        # range and is never replicated across shards
        def inner(indptr_l, dst_l, frontier0_l, cap):
            indptr_l = indptr_l[0]  # drop the size-1 sharded block dims
            dst_l = dst_l[0]
            R = indptr_l.shape[0] - 1
            v_pad = R * S
            Q = frontier0_l.shape[0]
            # loop-invariant edge geometry, hoisted out of the hop loop
            e_max = dst_l.shape[0]
            epos = jnp.arange(e_max, dtype=jnp.int32)
            src_local = jnp.clip(
                jnp.searchsorted(indptr_l, epos, side="right").astype(
                    jnp.int32
                )
                - 1,
                0,
                R - 1,
            )
            edge_live = (dst_l >= 0) & (epos < indptr_l[-1])
            dst_c = jnp.clip(dst_l, 0, v_pad - 1)

            def expand(frontier_l):
                # [Q, R] local frontier slice → [Q, v_pad] contribution:
                # edge active iff its (locally-owned) source is lit
                active = frontier_l[:, src_local] & edge_live[None, :]
                return (
                    jnp.zeros((Q, v_pad), cdtype)
                    .at[:, dst_c]
                    .max(active.astype(cdtype))
                )

            def contrib_of(frontier_l, go):
                # frontier-sparse: a shard whose local frontier slice is
                # empty — or a hop the depth cap will discard anyway —
                # skips its gather/scatter entirely. The frontier half
                # of the predicate varies per shard and the branches
                # carry no collective, so each device decides alone.
                return jax.lax.cond(
                    go & frontier_l.any(),
                    expand,
                    lambda _f: _varying_zeros(
                        (Q, v_pad), cdtype, (rep_ax, shard_ax)
                    ),
                    frontier_l,
                )

            live0 = jax.lax.psum(
                frontier0_l.any().astype(jnp.int32), shard_ax
            )
            contrib0 = contrib_of(frontier0_l, jnp.int32(0) < cap[0])

            def cond_fn(state):
                depth, live, _contrib, _visited = state
                return (depth < cap[0]) & (live > 0)

            def body(state):
                depth, _live, contrib, visited_l = state
                # hop N's ring merge is ISSUED here on the carried
                # (double-buffered) contribution slot, before the local
                # gather of the NEXT frontier at the bottom of the body
                # consumes its result — the reduce-scatter leaves each
                # shard exactly its own [Q, R] slice of the merged
                # frontier, so no broadcast half ever runs
                merged_l = jax.lax.psum_scatter(
                    contrib, shard_ax, scatter_dimension=1, tiled=True
                )
                nxt_l = (merged_l > 0) & ~visited_l
                # scalar liveness psum: independent of the expansion
                # below, so it overlaps the gather/scatter compute
                live = jax.lax.psum(
                    nxt_l.any().astype(jnp.int32), shard_ax
                )
                return (
                    depth + 1,
                    live,
                    contrib_of(nxt_l, depth + 1 < cap[0]),
                    visited_l | nxt_l,
                )

            _d, _l, _c, visited_l = jax.lax.while_loop(
                cond_fn,
                body,
                (jnp.int32(0), live0, contrib0, frontier0_l),
            )
            return visited_l

        return shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                P(shard_ax, None),
                P(shard_ax, None),
                P(rep_ax, shard_ax),
                P(None),
            ),
            out_specs=P(rep_ax, shard_ax),
            check_vma=True,
        )(indptr_sh, dst_sh, roots, depth_cap)

    fn = jax.jit(step)
    _BFS_STEP_CACHE[key] = fn
    return fn


def bfs_reachability(
    scsr: ShardedCSR, roots: np.ndarray, max_depth: int
) -> np.ndarray:
    """Multi-source BFS closure: roots [Q, V] bool → visited [Q, V] bool
    (roots included at depth 0, like TRAVERSE / MATCH-WHILE emit-origin
    semantics). ``max_depth`` is a device operand — sweeping it reuses
    one executable — and the loop exits early when the global frontier
    drains before the cap."""
    mesh = scsr.mesh
    Q = roots.shape[0]
    reps = mesh.shape[config.mesh_replica_axis]
    q_pad = max(1, math.ceil(Q / reps)) * reps
    fr = np.zeros((q_pad, scsr.padded_vertices), bool)
    fr[:Q, : roots.shape[1]] = roots
    fr_dev = jax.device_put(
        jnp.asarray(fr),
        NamedSharding(
            mesh, P(config.mesh_replica_axis, config.mesh_shard_axis)
        ),
    )
    cap_dev = jax.device_put(
        np.asarray([max_depth], np.int32),
        NamedSharding(mesh, P(None)),
    )
    step = build_bfs_step(mesh)
    visited = step(scsr.indptr, scsr.dst, fr_dev, cap_dev)
    return fetch_sharded(visited)[:Q, : scsr.num_vertices]

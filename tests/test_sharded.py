"""Sharded (mesh) execution vs host-computed ground truth.

The analog of the reference's multi-server-in-one-JVM distributed tests
([E] AbstractServerClusterTest, SURVEY.md §4): an 8-virtual-device CPU mesh
(conftest.py) stands in for a TPU slice; sharded BFS must agree with a
plain host BFS, and the sharded-vs-single-device check is the SURVEY §5.2
"sharded vs single-chip results" invariant.

ISSUE 13 additions — the frontier-sparse rework's contracts:
- shard-SWEEP result parity: the same MATCH over 2/4/8-shard meshes
  returns row sets identical to the unsharded engine (sorted canon);
- recompile-free shard geometry: revisiting a previously-seen geometry
  adds ZERO kernel builds (the mesh.kernel_builds counter pins it,
  with this suite running under the deviceguard transfer guard), and a
  max_depth change reuses the SAME executable (depth is an operand);
- frontier-sparse correctness: empty-shard cond-skips and the
  while_loop early exit cannot change reachability.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from orientdb_tpu.parallel.sharded import (
    _BFS_STEP_CACHE,
    ShardedCSR,
    bfs_reachability,
    make_mesh,
)
from orientdb_tpu.storage.ingest import generate_demodb
from orientdb_tpu.storage.snapshot import attach_fresh_snapshot, build_snapshot
from orientdb_tpu.utils.metrics import metrics


def host_bfs(indptr, dst, roots, max_depth):
    V = indptr.shape[0] - 1
    visited = np.zeros((roots.shape[0], V), bool)
    for q in range(roots.shape[0]):
        frontier = list(np.nonzero(roots[q])[0])
        visited[q, frontier] = True
        for _ in range(max_depth):
            nxt = []
            for u in frontier:
                for e in range(indptr[u], indptr[u + 1]):
                    v = dst[e]
                    if not visited[q, v]:
                        visited[q, v] = True
                        nxt.append(v)
            frontier = nxt
    return visited


@pytest.fixture(scope="module")
def demograph():
    db = generate_demodb(n_profiles=300, avg_friends=4, seed=3)
    snap = build_snapshot(db)
    csr = snap.edge_classes["HasFriend"]
    return snap, csr


@pytest.mark.parametrize("replicas", [1, 2, 4])
def test_sharded_bfs_matches_host(demograph, replicas):
    snap, csr = demograph
    mesh = make_mesh(8, replicas=replicas)
    scsr = ShardedCSR.from_snapshot(snap, mesh, "HasFriend")
    V = snap.num_vertices
    rng = np.random.default_rng(0)
    roots = np.zeros((5, V), bool)
    for q in range(5):
        roots[q, rng.choice(V, size=3, replace=False)] = True
    got = bfs_reachability(scsr, roots, max_depth=3)
    want = host_bfs(csr.indptr_out, csr.dst, roots, 3)
    assert (got == want).all()


def test_sharded_matches_single_device(demograph):
    snap, csr = demograph
    V = snap.num_vertices
    roots = np.zeros((2, V), bool)
    roots[0, 0] = True
    roots[1, V - 1] = True
    multi = bfs_reachability(
        ShardedCSR.from_snapshot(snap, make_mesh(8, replicas=2), "HasFriend"),
        roots,
        max_depth=4,
    )
    single = bfs_reachability(
        ShardedCSR.from_snapshot(snap, make_mesh(1), "HasFriend"),
        roots,
        max_depth=4,
    )
    assert (multi == single).all()


def test_empty_roots(demograph):
    snap, _ = demograph
    mesh = make_mesh(8)
    scsr = ShardedCSR.from_snapshot(snap, mesh, "HasFriend")
    roots = np.zeros((1, snap.num_vertices), bool)
    got = bfs_reachability(scsr, roots, max_depth=2)
    assert not got.any()


def test_early_exit_deep_cap_matches_host(demograph):
    """A depth cap far past convergence must return the full closure:
    the while_loop's liveness psum stops the loop when the frontier
    drains, and stopping early cannot drop reachable vertices."""
    snap, csr = demograph
    scsr = ShardedCSR.from_snapshot(snap, make_mesh(8), "HasFriend")
    roots = np.zeros((2, snap.num_vertices), bool)
    roots[0, 0] = True
    roots[1, 7] = True
    got = bfs_reachability(scsr, roots, max_depth=64)
    want = host_bfs(csr.indptr_out, csr.dst, roots, 64)
    assert (got == want).all()


def test_single_shard_roots_skip_parity(demograph):
    """Roots concentrated in ONE shard's row range (the supernode probe
    shape): every other shard cond-skips its gather/scatter on hop 1,
    and the result must still match the host BFS."""
    snap, csr = demograph
    scsr = ShardedCSR.from_snapshot(snap, make_mesh(8), "HasFriend")
    roots = np.zeros((3, snap.num_vertices), bool)
    # all roots inside shard 0's range [0, rows_per_shard)
    roots[0, 0] = roots[1, 1] = roots[2, 2] = True
    got = bfs_reachability(scsr, roots, max_depth=3)
    want = host_bfs(csr.indptr_out, csr.dst, roots, 3)
    assert (got == want).all()


def test_depth_is_operand_not_trace_constant(demograph):
    """One cached executable serves every max_depth: the step function
    is cache-identical across depths and a depth change adds zero
    kernel compiles."""
    snap, csr = demograph
    mesh = make_mesh(8)
    scsr = ShardedCSR.from_snapshot(snap, mesh, "HasFriend")
    roots = np.zeros((1, snap.num_vertices), bool)
    roots[0, 0] = True
    bfs_reachability(scsr, roots, max_depth=1)  # warm the geometry
    from orientdb_tpu.parallel.sharded import build_bfs_step

    step_a = build_bfs_step(mesh)
    before = metrics.counter("mesh.kernel_builds")
    for depth in (2, 3, 5):
        got = bfs_reachability(scsr, roots, max_depth=depth)
        want = host_bfs(csr.indptr_out, csr.dst, roots, depth)
        assert (got == want).all()
    assert build_bfs_step(mesh) is step_a
    assert metrics.counter("mesh.kernel_builds") == before


def test_bfs_geometry_revisit_is_cache_hit(demograph):
    """A shard sweep that RETURNS to a previously-built geometry finds
    its executable cached: the _BFS_STEP_CACHE keys (mesh, axes) and a
    fresh equal mesh over the same devices hashes to the same entry."""
    snap, _ = demograph
    roots = np.zeros((1, snap.num_vertices), bool)
    roots[0, 0] = True
    for s in (2, 4, 2):
        scsr = ShardedCSR.from_snapshot(snap, make_mesh(s), "HasFriend")
        bfs_reachability(scsr, roots, max_depth=2)
    size_after_sweep = len(_BFS_STEP_CACHE)
    before = metrics.counter("mesh.kernel_builds")
    scsr = ShardedCSR.from_snapshot(snap, make_mesh(2), "HasFriend")
    bfs_reachability(scsr, roots, max_depth=2)
    assert len(_BFS_STEP_CACHE) == size_after_sweep
    assert metrics.counter("mesh.kernel_builds") == before


# -- engine-level shard sweep (the deviceguard-observed contract) ------------


SWEEP_ROWS_SQL = (
    "MATCH {class:Profiles, as:p, where:(uid < 40)}-HasFriend->{as:f} "
    "RETURN p.uid AS p, f.uid AS f"
)
SWEEP_COUNT_SQL = (
    "MATCH {class:Profiles, as:p, where:(age > 40)}-HasFriend->{as:f}"
    "-HasFriend->{as:g, where:(age < 30)} RETURN count(*) AS n"
)


def canon(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


@pytest.fixture(scope="module")
def sweep_db():
    db = generate_demodb(n_profiles=200, avg_friends=4, seed=9)
    attach_fresh_snapshot(db)
    rows = canon(db.query(SWEEP_ROWS_SQL, engine="tpu", strict=True).to_dicts())
    count = db.query(SWEEP_COUNT_SQL, engine="tpu", strict=True).to_dicts()
    return db, rows, count


def _reattach(db, shards):
    from orientdb_tpu.exec.tpu_engine import drain_warmups

    # a background AOT warm-up still tracing the OLD snapshot's arrays
    # would KeyError when detach frees them — settle it first
    drain_warmups()
    db.detach_snapshot()
    attach_fresh_snapshot(db, mesh=make_mesh(shards, replicas=1))


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_shard_sweep_match_parity(sweep_db, shards):
    """2/4/8-shard MATCH row sets identical to unsharded, sorted canon —
    the result-parity half of the mesh acceptance gate."""
    db, want_rows, want_count = sweep_db
    _reattach(db, shards)
    got = canon(db.query(SWEEP_ROWS_SQL, engine="tpu", strict=True).to_dicts())
    assert got == want_rows
    assert (
        db.query(SWEEP_COUNT_SQL, engine="tpu", strict=True).to_dicts()
        == want_count
    )


def test_shard_geometry_revisit_zero_kernel_compiles(sweep_db):
    """Changing shard geometry and coming BACK must retrace nothing:
    the expansion kernels key on (mesh, axes, structural statics) with
    row ranges as device operands, so the revisit is a pure cache hit —
    observed via the mesh.kernel_builds counter while the deviceguard
    transfer guard watches the whole suite."""
    db, want_rows, _ = sweep_db
    for s in (2, 4):  # build both geometries once
        _reattach(db, s)
        db.query(SWEEP_ROWS_SQL, engine="tpu", strict=True).to_dicts()
    before = metrics.counter("mesh.kernel_builds")
    _reattach(db, 2)  # revisit: same geometry, fresh snapshot
    got = canon(db.query(SWEEP_ROWS_SQL, engine="tpu", strict=True).to_dicts())
    assert got == want_rows
    assert metrics.counter("mesh.kernel_builds") == before

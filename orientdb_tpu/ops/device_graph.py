"""Device-resident graph snapshot.

The HBM form of the columnar snapshot (`orientdb_tpu/storage/snapshot.py`):
every array `jax.device_put` once per snapshot epoch and cached, so repeated
queries over the same snapshot pay zero host↔device traffic for graph data —
the TPU-native answer to the reference's per-record page-cache reads on every
hop ([E] O2QCache / OPaginatedCluster.readRecord, SURVEY.md §3.2-3.3).

All arrays live in one flat ``DeviceGraph.arrays`` dict and are read through
lightweight proxies (`DeviceColumn`, `DeviceEdgeClass`). Compiled plans pass
that dict as a jit *argument* pytree — temporarily swapping in the tracer
dict during tracing — so the (potentially multi-GB) graph is shared across
every cached plan executable instead of being baked into each one as HLO
constants.
"""

from __future__ import annotations

import threading
import weakref
from contextlib import contextmanager
from typing import Dict, List, Optional, Set, Tuple

import jax.numpy as jnp
import numpy as np

from orientdb_tpu.chaos.faults import FaultError, fault
from orientdb_tpu.storage.snapshot import GraphSnapshot, PropertyColumn


class DeviceColumn:
    """A property column proxy: values + presence mask in ``graph.arrays``.

    `dictionary` (host-side) stays with the column so string predicates can
    be evaluated over the (small) dictionary on host and pushed to device as
    code-set membership masks.

    On a mesh, columns are row-sharded over the ``shards`` axis
    (``shard_pad`` rows per padded column, R per device) instead of
    replicated — per-device property memory is O(V/S) (vertices) or
    O(E/S) (edges), the SURVEY.md §7 SF100 per-chip budget. Predicate
    gathers read them in jit global view; XLA's SPMD partitioner inserts
    the cross-shard collectives (all_gather / all_to_all, its choice).
    """

    __slots__ = ("name", "kind", "dictionary", "_g", "_kv", "_kp", "_src")

    def __init__(
        self,
        col: PropertyColumn,
        g: "DeviceGraph",
        prefix: str,
        shard_pad: Optional[int] = None,
    ):
        self.name = col.name
        self.kind = col.kind
        self.dictionary = col.dictionary
        self._src = col
        self._g = g
        # LAZY upload (per-query property pruning, SURVEY.md §7's SF100
        # memory plan): the host arrays are registered but reach HBM only
        # when a compiled plan first reads the column — columns no query
        # references never cost device memory
        self._kv = g._put_lazy(f"{prefix}:v", col.values, shard_pad=shard_pad)
        self._kp = g._put_lazy(
            f"{prefix}:p", col.present, shard_pad=shard_pad
        )

    @property
    def dict_unsorted(self) -> bool:
        """Read through to the HOST column: the delta maintainer flips
        the flag when it appends a new string, which may happen long
        after this proxy was built (predicates check it per compile —
        O(1) where rescanning the dictionary is O(n))."""
        return self._src.dict_unsorted

    @property
    def dict_lookup(self):
        """Read through to the host column's exact value→code map (the
        delta maintainer keeps it current across appends) — equality
        compiles against a delta-appended dictionary in O(1)."""
        return self._src.dict_lookup

    @property
    def values(self):
        self._g.ensure_key(self._kv)
        return self._g.arrays[self._kv]

    @property
    def present(self):
        self._g.ensure_key(self._kp)
        return self._g.arrays[self._kp]


def vertex_hull(indptr: np.ndarray, bounds: np.ndarray) -> Tuple[int, int]:
    """``[lo, hi)`` holding every vertex with an edge in the CSR whose
    pointer array is ``indptr``: two binary searches over the monotone
    array, no pass over V. The pair is snapped OUTWARDS to ``bounds``
    (sorted; 0 and V are in it), the boundaries of the snapshot's
    vertex-class ranges: the hull is static in every program that reads
    it, and a hull that followed one seed's degrees by a vertex would
    compile anew for the next. No edges: the empty hull ``(0, 0)``."""
    E = int(indptr[-1])
    if E == 0:
        return (0, 0)
    lo = int(np.searchsorted(indptr, 0, "right")) - 1
    hi = int(np.searchsorted(indptr, E, "left"))
    return (
        int(bounds[np.searchsorted(bounds, lo, "right") - 1]),
        int(bounds[np.searchsorted(bounds, hi, "left")]),
    )


def unit_degree(indptr: np.ndarray, hull: Tuple[int, int]) -> bool:
    """Whether every vertex of ``hull`` (:func:`vertex_hull`'s) has
    exactly one edge in the CSR whose pointer array is ``indptr``, as a
    many-to-one relationship stored from its "many" side has: a segment
    sum over the hull is then its values in order
    (``ops/csr.indptr_segment_sum``). One pass over the hull; the empty
    hull is not unit."""
    lo, hi = hull
    if hi <= lo or int(indptr[hi]) - int(indptr[lo]) != hi - lo:
        return False
    return bool((np.diff(indptr[lo : hi + 1]) == 1).all())


class DeviceEdgeClass:
    """One edge class's CSR adjacency (both directions) in HBM.

    On a mesh-sharded graph the flat adjacency is NOT uploaded — every
    mesh execution path reads the ``sh:*`` shard-wise layout instead
    (`orientdb_tpu/parallel/mesh_graph.py`), and uploading both would
    leave per-device HBM at O(E·(1+1/S)) instead of O(E/S). Edge property
    columns are row-sharded by edge range on a mesh (O(E/S) per device);
    predicate gathers read them through XLA-inserted collectives."""

    __slots__ = (
        "class_name", "columns", "non_columnar", "num_edges", "hull_out",
        "hull_in", "unit_out", "unit_in", "_g", "_p",
    )

    def __init__(self, csr, g: "DeviceGraph") -> None:
        self.class_name = csr.class_name
        self._g = g
        p = self._p = f"e:{csr.class_name}"
        #: vertex hulls of the class, by direction: where the sources
        #: (out) and the targets (in) of its edges lie. A segment sum
        #: over the class visits these vertices and no others
        #: (``ops/csr.indptr_segment_sum``)
        self.hull_out = vertex_hull(csr.indptr_out, g.class_bounds)
        self.hull_in = vertex_hull(csr.indptr_in, g.class_bounds)
        #: whether each hull holds one edge a vertex (`unit_degree`).
        #: Static only where nothing patches, pages or shards the CSR
        #: under a recorded plan: never with a delta overlay or slab, a
        #: tier or a mesh
        fixed = (
            g.mesh_graph is None
            and getattr(g.snap, "_overlay", None) is None
            and getattr(g.snap, "_tier", None) is None
            and getattr(csr, "live", None) is None
        )
        self.unit_out = fixed and unit_degree(csr.indptr_out, self.hull_out)
        self.unit_in = fixed and unit_degree(csr.indptr_in, self.hull_in)
        if g.mesh_graph is None:
            # tiered snapshots (storage/tiering) page the four [E]
            # value arrays between a hot device pool and host-pinned
            # cold blocks — the flat uploads are what the HBM cap
            # exists to avoid. Indptrs stay resident (O(V), and every
            # paged gather sizes from them). Reading a skipped
            # property below raises KeyError by design: every consumer
            # is gated onto the paged kernels.
            tier = getattr(g.snap, "_tier", None)
            paged = tier is not None and tier.pages_dir(csr.class_name, "out")
            g._put(f"{p}:indptr_out", csr.indptr_out)
            g._put(f"{p}:indptr_in", csr.indptr_in)
            if not paged:
                g._put(f"{p}:dst", csr.dst)
                # per-edge source vertex in out-CSR order (bitmap-hop
                # kernels index edges directly instead of walking indptr)
                g._put(f"{p}:edge_src", csr.edge_src_np())
                g._put(f"{p}:src", csr.src)
                g._put(f"{p}:edge_id_in", csr.edge_id_in)
            if getattr(csr, "live", None) is not None:
                # delta-slab liveness (storage/deltas): spare slots and
                # tombstoned edges read False; the bitmap-hop and slab
                # expansion paths mask on it as a jit ARGUMENT, so
                # delta patches reach every cached plan
                g._put(f"{p}:live", csr.live)
            ov = getattr(g.snap, "_overlay", None)
            bk = getattr(ov, "bk", {}).get(csr.class_name) if ov else None
            if bk is not None:
                # bucketed slab index (storage/deltas): per-direction
                # endpoint-keyed tables of slab slots — patch-maintained
                # jit arguments like the live mask above
                g._put(f"bk:{csr.class_name}:out", bk["out"])
                g._put(f"bk:{csr.class_name}:in", bk["in"])
        e_pad = g._shard_pad_rows(int(csr.dst.shape[0]))
        self.columns: Dict[str, DeviceColumn] = {
            n: DeviceColumn(c, g, f"{p}:c:{n}", shard_pad=e_pad)
            for n, c in csr.edge_columns.items()
        }
        self.non_columnar: Set[str] = set(getattr(csr, "non_columnar", ()))
        self.num_edges = int(csr.dst.shape[0])

    @property
    def indptr_out(self):
        return self._g.arrays[f"{self._p}:indptr_out"]

    @property
    def dst(self):
        return self._g.arrays[f"{self._p}:dst"]

    @property
    def edge_src(self):
        return self._g.arrays[f"{self._p}:edge_src"]

    @property
    def indptr_in(self):
        return self._g.arrays[f"{self._p}:indptr_in"]

    @property
    def src(self):
        return self._g.arrays[f"{self._p}:src"]

    @property
    def edge_id_in(self):
        return self._g.arrays[f"{self._p}:edge_id_in"]


class _TouchTracker:
    """Recording-time view of the array store: logs every key read (the
    plan's future jit-arg subset) and faults lazy columns in on first
    read. Never reaches jax — dispatches always pass a plain dict."""

    __slots__ = ("_g", "log")

    def __init__(self, g: "DeviceGraph") -> None:
        self._g = g
        self.log: Set[str] = set()

    def __getitem__(self, key: str):
        self.log.add(key)
        g = self._g
        if key not in g._arrays:
            with g._pending_lock:
                spec = g._pending.pop(key, None)
            if spec is not None:
                arr, shard_pad, fill = spec
                g._put(key, arr, shard_pad=shard_pad, fill=fill)
        return g._arrays[key]

    def get(self, key: str, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def __contains__(self, key: str) -> bool:
        return key in self._g._arrays or key in self._g._pending

    def __iter__(self):
        return iter(self._g._arrays)

    def keys(self):
        return self._g._arrays.keys()

    def __len__(self) -> int:
        return len(self._g._arrays)


class DeviceGraph:
    """The full snapshot in HBM plus host metadata for planning/marshal.

    When the snapshot was attached with a device mesh, adjacency is
    additionally laid out shard-wise (`orientdb_tpu/parallel/mesh_graph.py`)
    and `self.mesh_graph` carries the sharding metadata; replicated arrays
    get an explicit fully-replicated NamedSharding so every jit argument
    agrees about the mesh."""

    def __init__(self, snap: GraphSnapshot) -> None:
        self.snap = snap
        self.num_vertices = snap.num_vertices
        self.mesh_graph = None
        self._replicated_spec = None
        mesh = getattr(snap, "_mesh", None)
        if mesh is not None:
            if getattr(snap, "_overlay", None) is not None:
                # the mesh layout re-partitions adjacency per shard and
                # does not upload the slab live masks — silently meshing
                # a delta-maintained snapshot would serve spare/dead
                # edges. Compact to a clean snapshot first.
                raise ValueError(
                    "delta-maintained snapshots are single-device; "
                    "compact before attaching a mesh"
                )
            from jax.sharding import NamedSharding, PartitionSpec
            from orientdb_tpu.parallel.mesh_graph import MeshGraph

            self.mesh_graph = MeshGraph(mesh)
            self._replicated_spec = NamedSharding(mesh, PartitionSpec())
        #: the single flat array store — compiled plans pass a per-plan
        #: KEY SUBSET of it as their jit-arg pytree (plans record the
        #: keys they touch, so lazily uploaded columns growing this dict
        #: never change any cached plan's pytree structure)
        self._arrays: Dict[str, jnp.ndarray] = {}
        #: device-memory ledger owner id (obs/memledger): every array
        #: this graph puts in HBM is attributed here; _free_device
        #: drops the whole owner in one call
        self._ledger_owner = (
            f"snap:{id(snap):x}:e{int(getattr(snap, 'epoch', 0) or 0)}"
        )
        #: host arrays registered but not yet uploaded (lazy columns):
        #: key -> (host_array, shard_pad, fill)
        self._pending: Dict[str, tuple] = {}
        self._pending_lock = threading.Lock()
        #: weak references to the arrays compiled plans keep beside the
        #: graph's own (`adopt_plan_const`); replaced, never mutated
        self._plan_consts: Dict[str, "weakref.ref"] = {}
        self._tls = threading.local()
        v_pad = self._shard_pad_rows(self.num_vertices)
        self._put("v_class", snap.v_class, shard_pad=v_pad, fill=-1)
        self.columns: Dict[str, DeviceColumn] = {
            n: DeviceColumn(c, self, f"v:{n}", shard_pad=v_pad)
            for n, c in snap.v_columns.items()
        }
        self.non_columnar: Set[str] = set(getattr(snap, "v_non_columnar", ()))
        tier = getattr(snap, "_tier", None)
        if tier is not None and self.mesh_graph is not None:
            # same composition rule as mesh + overlay below: the mesh
            # layout re-partitions adjacency shard-wise and knows
            # nothing of the hot/cold pools
            from orientdb_tpu.obs.memledger import memledger

            memledger.note_refusal(
                "mesh", "tiered snapshot built against a device mesh"
            )
            raise ValueError(
                "tiered snapshots are single-device; drop the mesh or "
                "raise tier_hbm_cap_bytes"
            )
        #: 0, V and every boundary of a concrete vertex class's range
        #: between them, sorted: what an edge class's hull snaps to
        self.class_bounds = np.unique(
            [0, self.num_vertices]
            + [b for r in snap.class_vertex_range.values() for b in r]
        )
        self.edges: Dict[str, DeviceEdgeClass] = {
            n: DeviceEdgeClass(c, self) for n, c in snap.edge_classes.items()
        }
        if tier is not None:
            # upload block indexes + pools, seed the hottest blocks
            # (storage/tiering); re-runs per DeviceGraph build, so a
            # _free_device → rebuild cycle re-establishes residency
            tier.install(self)
        # class-id sets stay OUTSIDE `arrays`: they are lazily created per
        # query, and growing the jit-arg pytree would change its structure
        # and silently retrace every cached plan. They are tiny (a few
        # int32s), so being baked into plan executables as constants is fine.
        self._class_ids: Dict[str, jnp.ndarray] = {}
        if self.mesh_graph is not None:
            self.mesh_graph.build(self)
        self.memory_report()  # publish hbm.* gauges for /metrics

    @property
    def arrays(self):
        """The array store — per-thread overridable.

        Compiled plans swap in the jit tracer pytree for the duration of a
        trace (``dg.arrays = tracers``). Replays are AOT-warmed on a
        background thread (`tpu_engine._CompiledPlan.ensure_compiled`), so
        that swap MUST be invisible to other threads: the override lives in
        thread-local storage, and assigning the canonical dict back clears
        it. Concurrent traces and eager solves on different threads each see
        their own view; `_put` writes to the canonical store directly so an
        active override can never swallow an upload.

        During a RECORDING (``start_touch_log``) this thread instead sees
        a tracking view that logs every key read — the recorded set
        becomes the plan's jit-arg subset — and faults lazy columns in
        on first read."""
        ov = getattr(self._tls, "override", None)
        if ov is not None:
            return ov
        trk = getattr(self._tls, "tracker", None)
        return self._arrays if trk is None else trk

    @arrays.setter
    def arrays(self, value) -> None:
        self._tls.override = None if value is self._arrays else value

    @contextmanager
    def bound(self, arrays):
        """This thread's view swapped to ``arrays`` (a trace's tracer
        pytree) and back to what it was: the canonical store, or a
        recording's tracking view, which a replay traced at its own
        recording (`tpu_engine._CompiledLevels.record`) must find again."""
        prev = getattr(self._tls, "override", None)
        self._tls.override = arrays
        try:
            yield
        finally:
            self._tls.override = prev

    # -- recording touch log (per-plan jit-arg subsets) ---------------------

    def start_touch_log(self) -> None:
        self._tls.tracker = _TouchTracker(self)

    def touched(self) -> frozenset:
        """The keys this thread's running touch log holds so far."""
        trk = getattr(self._tls, "tracker", None)
        return frozenset(trk.log) if trk is not None else frozenset()

    def stop_touch_log(self) -> frozenset:
        keys = self.touched()
        self._tls.tracker = None
        return keys

    @property
    def mesh(self):
        return self.mesh_graph.mesh if self.mesh_graph is not None else None

    def _shard_pad_rows(self, n: int) -> Optional[int]:
        """Padded row count making ``n`` divisible by the shard count
        (None when unsharded)."""
        if self.mesh_graph is None:
            return None
        S = self.mesh_graph.n_shards
        return max(1, -(-max(n, 1) // S)) * S

    def _put_lazy(
        self,
        key: str,
        arr,
        shard_pad: Optional[int] = None,
        fill: int = 0,
    ) -> str:
        """Register a host array for on-demand upload (`ensure_key`) —
        the per-query property-pruning path. ``column_prune=False``
        restores eager uploads."""
        from orientdb_tpu.utils.config import config as _cfg

        if not _cfg.column_prune:
            return self._put(key, arr, shard_pad=shard_pad, fill=fill)
        self._pending[key] = (arr, shard_pad, fill)
        return key

    def ensure_key(self, key: str) -> None:
        """Upload a lazily registered array if it has not reached the
        device yet; logs the touch when a recording is active. The
        upload runs INSIDE the pending lock so a concurrent delta patch
        (``apply_patches``) can never land between the pop and the
        device store and be lost."""
        trk = getattr(self._tls, "tracker", None)
        if trk is not None:
            trk.log.add(key)
        if key in self._pending:
            with self._pending_lock:
                spec = self._pending.pop(key, None)
                if spec is not None:
                    arr, shard_pad, fill = spec
                    self._put(key, arr, shard_pad=shard_pad, fill=fill)

    def apply_patches(self, patches: Dict[str, tuple]) -> int:
        """Scatter one delta batch into resident device arrays:
        ``{key: (indices, values)}`` applied as a functional
        ``arr.at[idx].set(vals)`` per key. Same shape in, same shape out
        — compiled plans take these arrays as jit ARGUMENTS, so every
        cached executable sees the patch with zero retrace and the
        upload is bounded by the delta (the packed index/value
        segments), never the graph. Keys still pending lazy upload are
        skipped: their HOST arrays were already patched in place by the
        maintainer, so the eventual upload carries the delta for free.
        Returns the host→device bytes shipped."""
        import jax

        nbytes = 0
        with self._pending_lock:
            for key, (idx, vals) in patches.items():
                cur = self._arrays.get(key)
                if cur is None:
                    continue  # lazy column not yet resident
                ia = np.asarray(idx, np.int32)
                va = np.asarray(vals).astype(cur.dtype)
                try:
                    # scrub.flip chaos crossing: corrupt the DEVICE-
                    # bound copy only — the maintainer already patched
                    # host truth, so the scrub sweep provably detects
                    with fault.point("scrub.flip"):
                        pass
                except FaultError:
                    from orientdb_tpu.storage.scrub import chaos_flip

                    va = chaos_flip(va)
                # bucket the segment to a pow2 length by REPEATING the
                # last (index, value) pair — a duplicate scatter of the
                # same value is idempotent, and the bucketed shape keeps
                # the .at[].set executable jit-cache-hot (per-delta
                # shapes recompiled XLA on every batch otherwise: ~3x
                # the whole read path's cost at bench shape)
                cap = 1 << max(0, int(ia.shape[0] - 1).bit_length())
                if cap > ia.shape[0]:
                    ia = np.concatenate(
                        [ia, np.full(cap - ia.shape[0], ia[-1], ia.dtype)]
                    )
                    va = np.concatenate(
                        [va, np.full(cap - va.shape[0], va[-1], va.dtype)]
                    )
                self._arrays[key] = cur.at[jax.device_put(ia)].set(
                    jax.device_put(va)
                )
                nbytes += int(ia.nbytes) + int(va.nbytes)
                # the overlay write produced a NEW device array under
                # the same key: refresh its ledger attribution in place
                from orientdb_tpu.obs.memledger import memledger

                memledger.register_graph_array(
                    self, key, self._arrays[key]
                )
                self._scrub_mark(key)
        return nbytes

    def _scrub_mark(self, key: str) -> None:
        """Host truth changed under ``key``: the scrubber re-hashes its
        cached checksum on the next sweep (storage/scrub)."""
        d = getattr(self, "_scrub_dirty", None)
        if d is None:
            d = self._scrub_dirty = set()
        d.add(key)

    def _put(
        self,
        key: str,
        arr,
        shard_pad: Optional[int] = None,
        fill: int = 0,
    ) -> str:
        import jax

        # an upload asked for under a trace (a plan probed abstractly,
        # `tpu_engine._CompiledLevels.probe`) is an upload all the same
        with jax.ensure_compile_time_eval():
            a = jnp.asarray(arr)
        if (
            self.mesh_graph is not None
            and shard_pad is not None
            and a.ndim == 1
            and a.shape[0] > 0
        ):
            # row-shard over the mesh's shard axis (vertex- or edge-range
            # ownership); padding rows carry `fill` and a False presence
            from jax.sharding import NamedSharding, PartitionSpec

            from orientdb_tpu.utils.config import config as _cfg

            if shard_pad > a.shape[0]:
                pad = jnp.full((shard_pad - a.shape[0],), fill, a.dtype)
                a = jnp.concatenate([a, pad])
            spec = NamedSharding(
                self.mesh_graph.mesh, PartitionSpec(_cfg.mesh_shard_axis)
            )
            self._arrays[key] = jax.device_put(a, spec)
            from orientdb_tpu.obs.memledger import memledger

            memledger.register_graph_array(self, key, self._arrays[key])
            self._scrub_mark(key)
            return key
        if self._replicated_spec is not None:
            a = jax.device_put(a, self._replicated_spec)
        self._arrays[key] = a
        from orientdb_tpu.obs.memledger import memledger

        memledger.register_graph_array(self, key, a)
        self._scrub_mark(key)
        return key

    @property
    def v_class(self):
        return self.arrays["v_class"]

    def memory_report(self) -> Dict[str, Dict[str, int]]:
        """Per-device graph-memory accounting by category (the SURVEY.md
        §5.5 HBM-occupancy observable): for each key group, logical bytes
        and per-device bytes (= the largest addressable shard, so a
        sharded array counts V/S-ish while a replicated one counts V).
        Published to the metrics registry as ``hbm.*`` gauges."""
        cats = {
            "adjacency": 0,
            "vertex_columns": 0,
            "edge_columns": 0,
            "plan_consts": 0,
            "other": 0,
        }
        logical = dict(cats)
        consts = [
            (k, a) for k, r in self._plan_consts.items() if (a := r()) is not None
        ]
        for key, arr in list(self._arrays.items()) + consts:
            if key.startswith("plan:"):
                cat = "plan_consts"
            elif key.startswith("sh:"):
                cat = "adjacency"
            elif key.startswith("t:") or key.startswith("bk:"):
                # tier pools/indexes (storage/tiering) and overlay slab
                # bucket tables are adjacency in paged/bucketed clothing
                cat = "adjacency"
            elif key == "v_class" or key.startswith("v:"):
                cat = "vertex_columns"
            elif key.startswith("e:") and ":c:" in key:
                cat = "edge_columns"
            elif key.startswith("e:"):
                cat = "adjacency"
            else:
                cat = "other"
            logical[cat] += int(arr.nbytes)
            try:
                per_dev = max(
                    int(s.data.nbytes) for s in arr.addressable_shards
                )
            except Exception:
                per_dev = int(arr.nbytes)
            cats[cat] += per_dev
        from orientdb_tpu.utils.metrics import metrics

        for cat, b in cats.items():
            metrics.gauge(f"hbm.per_device.{cat}_bytes", b)
        metrics.gauge("hbm.per_device.total_bytes", sum(cats.values()))
        # property pruning observables: columns registered but never
        # referenced by any compiled plan stay host-side
        pruned_bytes = sum(
            int(np.asarray(a).nbytes) for a, _sp, _f in self._pending.values()
        )
        metrics.gauge("hbm.pruned_column_bytes", pruned_bytes)
        metrics.gauge("hbm.pruned_column_arrays", len(self._pending))
        return {
            "per_device": cats,
            "logical": logical,
            "pruned_bytes": pruned_bytes,
            "pruned_arrays": len(self._pending),
        }

    def adopt_plan_const(self, plan, key: str, arr) -> None:
        """Account for a device array a compiled ``plan`` keeps beside
        the graph's own and hands its replays as a jit argument (a
        COUNT's constant weights, ``exec/tpu_engine``): `memory_report`
        counts it under ``plan_consts`` while it lives, and the ledger
        holds it as ``plan_const`` until the plan is collected."""
        from orientdb_tpu.obs.memledger import memledger

        ident = f"{key}:{id(plan):x}"
        live = {k: r for k, r in self._plan_consts.items() if r() is not None}
        live[ident] = weakref.ref(arr)
        self._plan_consts = live
        memledger.register("plan_const", self._ledger_owner, ident, arr=arr)
        weakref.finalize(
            plan, memledger.unregister, "plan_const", self._ledger_owner, ident
        )

    def class_ids(self, class_name: str) -> jnp.ndarray:
        key = class_name.lower()
        ids = self._class_ids.get(key)
        if ids is None:
            ids = self._class_ids[key] = jnp.asarray(
                self.snap.vertex_class_ids(class_name)
            )
            # baked into plan executables as constants — attributed so
            # the ledger's snapshot rollup covers the whole footprint
            from orientdb_tpu.obs.memledger import memledger

            memledger.register(
                "plan_const", self._ledger_owner, f"cls:{key}", arr=ids
            )
        return ids


_DG_BUILD_LOCK = threading.Lock()


def device_graph(snap: GraphSnapshot) -> DeviceGraph:
    """Build (or fetch the cached) device form of a snapshot.

    Construction is locked: a concurrent first-touch stampede would
    otherwise build SEVERAL DeviceGraphs for one snapshot (last writer
    wins) — wasted uploads, and threads left holding different
    instances, which breaks anything keyed on instance identity (the
    recording touch log that feeds per-plan jit-arg subsets)."""
    cached: Optional[DeviceGraph] = getattr(snap, "_device_cache", None)
    if cached is not None:
        return cached
    with _DG_BUILD_LOCK:
        cached = getattr(snap, "_device_cache", None)
        if cached is None:
            cached = snap._device_cache = DeviceGraph(snap)
    return cached

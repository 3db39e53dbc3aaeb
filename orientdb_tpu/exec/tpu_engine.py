"""Compiled TPU MATCH engine — batched binding-table execution.

The reference executes MATCH as a per-record interpreted DFS
([E] OMatchExecutionPlanner → MatchStep → MatchEdgeTraverser,
SURVEY.md §3.3): one RidBag walk, N document loads and an interpreted WHERE
per candidate edge. This engine replaces that hot loop wholesale:

- the pattern graph compiles to a **static plan** of steps (root scan,
  edge expansion, optional left-join) whose ordering replicates the
  oracle's greedy smallest-candidate-first choice ([E]
  OMatchExecutionPlanner's ordering) — the order is data-independent given
  host-side class counts, so the whole plan is known before launch;
- intermediate state is a **binding table**: one int32 device column per
  alias (dense vertex index, -1 = null), plus (class, edge-pos) column
  pairs for edge aliases and int32 columns for depth aliases;
- each pattern-edge hop is a batched CSR **count → scan → rank-search
  gather** (`orientdb_tpu/ops/csr.py`) with node/edge WHERE predicates
  applied as fused columnar masks (`orientdb_tpu/ops/predicates.py`);
- results marshal back through the SAME RETURN/DISTINCT/ORDER path as the
  oracle (`oracle.match_rows_from_bindings`), so result semantics are
  defined once and parity is structural.

Anything outside the compiled subset raises `Uncompilable` and the front
door falls back to the oracle — behavior stays total while the compiled
surface grows.
"""

from __future__ import annotations

import weakref
from collections import OrderedDict
from contextlib import contextmanager as _contextmanager
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from orientdb_tpu.exec import devicefault
from orientdb_tpu.exec.eval import EvalContext
from orientdb_tpu.exec.oracle import (
    MatchInterpreter,
    Pattern,
    PatternEdge,
    PatternNode,
    finalize_match_rows,
    match_rows_from_bindings,
    _expr_uses_bindings,
    _match_proj_name,
    _order_rows,
    _skip_limit,
    _REVERSE_DIR,
)
from orientdb_tpu.exec.result import ColumnarRows, Result
from orientdb_tpu.models.record import Document
from orientdb_tpu.models.rid import RID
from orientdb_tpu.ops import csr as K
from orientdb_tpu.ops.device_graph import DeviceGraph, device_graph
from orientdb_tpu.storage import tiering
from orientdb_tpu.ops.predicates import (
    ColumnScope,
    ParamBox,
    Uncompilable,
    compile_predicate,
    split_params,
)
from orientdb_tpu.sql import ast as A
from orientdb_tpu.utils.config import config
from orientdb_tpu.utils.logging import get_logger
from orientdb_tpu.utils.metrics import metrics, timed

log = get_logger("tpu_engine")


def _block_until_ready(d) -> None:
    """Device sync; host-resident numpy results (the CPU-backend fast
    paths) lack the method and need none."""
    fn = getattr(d, "block_until_ready", None)
    if fn is not None:
        fn()


def _copy_to_host_async(d) -> None:
    """Start an async device→host copy; host-resident numpy results
    lack the method and need none."""
    fn = getattr(d, "copy_to_host_async", None)
    if fn is not None:
        fn()


def _fetch_profiled(devs: List, split_sync: bool = True) -> List[np.ndarray]:
    """Fetch dispatched device results: the host's wait in the sync,
    its copy after it, and the bytes moved (`tpu.bytes_fetched`), the
    first two for the stats plane's per-fingerprint attribution and
    the flight recorder's intervals. Execution is
    in-order per device, so blocking on the LAST dispatched result
    covers the whole batch with one sync instead of N. ``split_sync=
    False`` skips the separate sync wave — a lone query must not pay an
    extra host↔device round trip just for the split; profile_execute
    decomposes singles instead."""
    import time as _time

    devicefault.transfer_point()
    t0 = _time.perf_counter()
    if split_sync and len(devs) > 1:
        _block_until_ready(devs[-1])
    t1 = _time.perf_counter()
    for d in devs:
        _copy_to_host_async(d)
    arrs = [np.asarray(d) for d in devs]
    t2 = _time.perf_counter()
    if devs:
        nbytes = sum(int(a.nbytes) for a in arrs)
        metrics.incr("tpu.bytes_fetched", nbytes)
        # per-fingerprint attribution (obs/stats): one thread-local add
        # when a query accumulator is active, a no-op otherwise
        from orientdb_tpu.obs.stats import add_device

        add_device(t1 - t0, t2 - t1, nbytes)
        # flight-recorder intervals (obs/timeline): same thread-local
        # discipline — the active dispatch record gets this wave's
        # device-busy and transfer intervals for overlap accounting
        from orientdb_tpu.obs.timeline import add_phase

        add_phase(t1 - t0, t2 - t1, nbytes)
    return arrs


#: smallest page (rows) a batched result fetch transfers; pow2 rounding
#: up from here bounds the distinct sliced shapes per buffer to log2(W)
_PAGE_MIN = 1024
#: rows-group page sizes round up to this so `group_page`'s jit cache
#: stays small (width/2048 distinct shapes at most) while waste stays
#: ≤ 2048 rows per group
_GROUP_PAGE_ROUND = 2048



# ---------------------------------------------------------------------------
# binding table
# ---------------------------------------------------------------------------


class Table:
    """Device binding table: padded columns + a host-known valid count."""

    def __init__(self, count: int = 1, width: int = 0) -> None:
        #: alias → int32 [B] dense vertex index (-1 null / padding)
        self.cols: Dict[str, jnp.ndarray] = {}
        #: edge alias → (class_idx int32 [B], edge_pos int32 [B])
        self.edge_cols: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]] = {}
        #: depth alias → int32 [B]
        self.depth_cols: Dict[str, jnp.ndarray] = {}
        self.count = count  # valid rows; starts at 1 (the empty binding)
        self.width = width  # bucketed column length (0 = no columns yet)
        #: device-side twin of `count` (threaded so COUNT(*) plans can fetch
        #: one scalar instead of the whole table); None until a step sets it
        self.count_dev = None
        #: device-side per-slot liveness (int32 1/0). On a recording run the
        #: first `count` slots are exactly the live ones, so None ≡
        #: arange(width) < count; a parameter-generic REPLAY can have live
        #: rows interleaved with recorded-size padding, and this mask is
        #: what lets materialization pick the true rows.
        self.valid = None

    @property
    def count_device(self):
        if self.count_dev is None:
            return jnp.int32(self.count)
        return self.count_dev

    @property
    def valid_device(self):
        if self.valid is not None:
            return self.valid
        pos = jnp.arange(max(self.width, 1), dtype=jnp.int32)
        return (pos < self.count_device).astype(jnp.int32)

    def empty(self) -> bool:
        return self.count == 0

    def has(self, alias: str) -> bool:
        return alias in self.cols or alias in self.edge_cols

    def gather(self, rows: jnp.ndarray) -> "Table":
        """New table selecting `rows` (padded with -1) from this one."""
        t = Table(count=self.count, width=int(rows.shape[0]))
        for a, c in self.cols.items():
            t.cols[a] = K.take_pad(c, rows, jnp.int32(-1))
        for a, (ci, pos) in self.edge_cols.items():
            t.edge_cols[a] = (
                K.take_pad(ci, rows, jnp.int32(-1)),
                K.take_pad(pos, rows, jnp.int32(-1)),
            )
        for a, c in self.depth_cols.items():
            t.depth_cols[a] = K.take_pad(c, rows, jnp.int32(-1))
        t.valid = K.take_pad(self.valid_device, rows, jnp.int32(0))
        return t


def _concat_tables(parts: List[Table], counts: List[int]) -> Table:
    """Concatenate gathered part-tables (same column sets) and re-bucket.

    Parts keep their FULL bucketed capacity (not just the recorded live
    prefix): a parameter-generic replay can have up to bucket(recorded)
    live rows per part, so slicing at the recorded count would silently
    truncate them. Liveness flows through the per-slot valid mask; the
    recorded host count is bookkeeping only."""
    total = sum(counts)
    # parts are already bucket-sized; their sum is deterministic given the
    # schedule, so no re-bucketing (it would only double the padding)
    cap = sum(p.width for p in parts)
    out = Table(count=total, width=max(cap, K.bucket(0)))
    if not parts:
        out.count = 0
        out.count_dev = jnp.int32(0)
        return out
    out.count_dev = parts[0].count_device
    for p in parts[1:]:
        out.count_dev = out.count_dev + p.count_device
    keys = parts[0].cols.keys()
    for a in keys:
        out.cols[a] = _pad_concat([p.cols[a] for p in parts], out.width)
    for a in parts[0].edge_cols.keys():
        ci = _pad_concat([p.edge_cols[a][0] for p in parts], out.width)
        ps = _pad_concat([p.edge_cols[a][1] for p in parts], out.width)
        out.edge_cols[a] = (ci, ps)
    for a in parts[0].depth_cols.keys():
        out.depth_cols[a] = _pad_concat(
            [p.depth_cols[a] for p in parts], out.width
        )
    out.valid = _pad_concat([p.valid_device for p in parts], out.width, pad=0)
    return out


def _pad_concat(segs: List[jnp.ndarray], width: int, pad: int = -1) -> jnp.ndarray:
    cat = jnp.concatenate(segs) if segs else jnp.zeros(0, jnp.int32)
    n = width - cat.shape[0]
    if n > 0:
        cat = jnp.concatenate([cat, jnp.full(n, pad, jnp.int32)])
    return cat


# ---------------------------------------------------------------------------
# size schedule (the compiled-plan-cache mechanism)
# ---------------------------------------------------------------------------


def _cap_of(n: int) -> int:
    """Replay-tolerant buffer capacity for an observed count: bucketed
    with ``config.schedule_headroom`` growth, so parameter-generic replays
    whose live sizes land within the headroom run without an overflow
    re-record."""
    if n <= 0:
        return K.bucket(0)
    # deliberate trace-time read: capacities are frozen per RECORDED
    # plan by design — retuning the headroom applies to the next
    # (re-)recording, never to a live executable
    return K.bucket(max(1, int(n * config.schedule_headroom)))  # lint: allow(jaxlint)


def _index_range(
    dg: DeviceGraph, tier, start: int, size: int, width: int,
    slab_start: int = 0, slab_size: int = 0,
):
    """The contiguous index range ``[start, start + size)`` (then the
    slab segment), ``-1``-padded to ``width``: a class hull, the vertex
    universe, an edge list's ids. It stays a ``K.IndexRange``, so every
    column read through it is a slice, where the columns are whole on
    one device; a mesh-sharded graph (columns row-sharded with their own
    padding) and a tiered snapshot get the ``int32`` array and gather as
    before."""
    rng = K.IndexRange(start, size, width, slab_start, slab_size)
    if dg.mesh_graph is not None or tier is not None:
        return rng.materialise()
    return rng


def _observe_compact(sched: "SizeSchedule", mask, min_capacity: int = 0):
    """Shared compaction protocol: surviving-row indices sized via the
    schedule (one blocking sync on the recording run, free on replay).
    Returns (indices, host count, device count)."""
    count_dev = K.mask_count(mask)
    count = sched.observe(count_dev, min_capacity=min_capacity)
    return (
        K.compact_indices(mask, max(min_capacity, _cap_of(count))),
        count,
        count_dev,
    )


class SizeSchedule:
    """Records every host-observed device scalar (frontier totals, compact
    counts) on the first execution; replays them sync-free afterwards.

    XLA needs static shapes, frontiers are dynamic — the first run pays one
    blocking device→host sync per observation to learn the shape schedule.
    Sizes are deterministic given (snapshot epoch, statement, params), so a
    replay under `jit` executes the whole multi-hop solve as a single
    device dispatch with zero syncs — the TPU-native analog of the
    reference's prepared-plan reuse ([E] OExecutionPlanCache).

    Parameter-generic replay: numeric parameters are jit ARGUMENTS, so a
    replay may see different live sizes than were recorded. Every non-free
    observation therefore accumulates a device-side ``overflow`` flag —
    live count exceeding the recorded bucket capacity (or any liveness
    where the recording saw zero and structurally skipped work) means the
    replay's buffers were too small and its result must be discarded; the
    caller re-records with the new parameters (buckets grow monotonically,
    so re-records converge). Live counts *under* the recorded capacity are
    handled exactly via the table's device valid mask + count."""

    def __init__(self) -> None:
        self.values: List[int] = []
        self.pos = 0
        self.recording = True
        self.overflow = None  # traced bool scalar during replay

    def observe(self, dev_scalar, free: bool = False, min_capacity: int = 0) -> int:
        """``free=True`` marks a value that sizes no buffer and gates no
        control flow (e.g. the COUNT(*) pushdown total) — exempt from the
        overflow check. ``min_capacity`` is the buffer floor the call site
        allocates even for a recorded zero (kept-empty parts): replays may
        fill it without flagging."""
        if self.recording:
            v = int(dev_scalar)
            self.values.append(v)
            return v
        v = self.values[self.pos]
        self.pos += 1
        if not free:
            cap = max(min_capacity, _cap_of(v) if v > 0 else 0)
            flag = dev_scalar > cap
            self.overflow = flag if self.overflow is None else (self.overflow | flag)
        return v

    def note_flag(self, dev_flag) -> None:
        """OR an externally computed device-side failure bit into the
        overflow surface (the tiered cold-miss flag: a replay whose
        frontier wandered onto a non-resident block must discard and
        re-record — the re-record faults the block in). No-op while
        recording: the recording run ensures residency eagerly."""
        if self.recording:
            return
        self.overflow = (
            dev_flag if self.overflow is None else (self.overflow | dev_flag)
        )

    def overflow_flag(self):
        if self.overflow is None:
            return jnp.zeros((), bool)
        return self.overflow

    def start_replay(self) -> None:
        self.recording = False
        self.pos = 0
        self.overflow = None


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------


class _SeedBox:
    """Index-seeded root candidates as replay inputs.

    ``spec`` (alias → padded capacity) is fixed at record time; ``current``
    holds the live arrays — concrete during recording, tracers during a
    replay trace (set by _CompiledPlan._replay from the dyn pytree)."""

    __slots__ = ("spec", "current")

    def __init__(self) -> None:
        self.spec: Dict[str, int] = {}
        self.current: Dict[str, object] = {}


def _eq_conjuncts(e):
    """Top-level `lhs = rhs` pairs of an AND tree."""
    if isinstance(e, A.Binary):
        if e.op == "AND":
            yield from _eq_conjuncts(e.left)
            yield from _eq_conjuncts(e.right)
        elif e.op == "=":
            yield e.left, e.right


def _path_len_call(e) -> Optional[A.FunctionCall]:
    """The ``shortestPath(...)`` call of ``shortestPath(...).size() - 1``
    (a path's rids less one: its number of edges, 0 from a vertex to
    itself, -1 where there is none), the one form of the function that
    compiles; None for any other expression."""
    if not (
        isinstance(e, A.Binary)
        and e.op == "-"
        and isinstance(e.right, A.Literal)
        and type(e.right.value) is int
        and e.right.value == 1
    ):
        return None
    m = e.left
    if not (isinstance(m, A.MethodCall) and m.name.lower() == "size" and not m.args):
        return None
    f = m.base
    if isinstance(f, A.FunctionCall) and f.name.lower() == "shortestpath":
        return f
    return None


def _calls_function(e, name: str) -> bool:
    """Whether expression ``e`` calls SQL function ``name`` anywhere."""
    if isinstance(e, A.FunctionCall) and e.name.lower() == name:
        return True
    if not hasattr(e, "__dataclass_fields__"):
        return False
    for f in e.__dataclass_fields__:
        v = getattr(e, f)
        for x in v if isinstance(v, tuple) else (v,):
            if isinstance(x, A.Expression) and _calls_function(x, name):
                return True
    return False


def _bfs_caps(csr) -> Tuple[int, int]:
    """``(front, chunk)`` of :func:`ops.csr.bfs_pair_len` for one edge
    class, from the snapshot's own degrees and not from a caller's
    first pair: an end's neighbour list gets four times the mean
    undirected degree ``D`` a direction, and a pair's expansion has room
    for ``D·m`` frontier edges, what the neighbours of a vertex have
    behind them (``m = Σdeg² / Σdeg``, the mean degree of a neighbour,
    which the hubs raise), each rounded up to a power of two."""
    deg = np.diff(csr.indptr_out).astype(np.int64) + np.diff(csr.indptr_in)
    ends = int(deg.sum())
    if ends == 0:
        return K.MIN_BUCKET, K.MIN_BUCKET
    mean = ends / int(np.count_nonzero(deg))
    of_a_neighbour = float((deg * deg).sum()) / ends
    front = min(K.bucket(int(4 * mean), K.MIN_BUCKET), 1 << 12)
    chunk = min(K.bucket(int(mean * of_a_neighbour), K.MIN_BUCKET), 1 << 20)
    return front, max(chunk, front)


class PlanStep:
    __slots__ = ("kind", "alias", "edge", "reverse", "close")

    def __init__(self, kind, alias=None, edge=None, reverse=False, close=False):
        self.kind = kind  # 'root' | 'expand' | 'optional'
        self.alias = alias
        self.edge: Optional[PatternEdge] = edge
        self.reverse = reverse
        self.close = close

    def describe(self) -> str:
        if self.kind == "root":
            return f"ROOT {self.alias}"
        e = self.edge
        arrow = "<-" if self.reverse else "->"
        return f"{self.kind.upper()} {e.from_alias}{arrow}{e.to_alias}"


def build_plan(pattern: Pattern, interp: MatchInterpreter) -> List[PlanStep]:
    """Static replay of the oracle's dynamic edge ordering (the bound-alias
    set evolves data-independently, so the greedy choice is a compile-time
    computation here; [E] OMatchExecutionPlanner does the analogous
    estimate-driven ordering once per query)."""
    steps: List[PlanStep] = []
    bound: set = set()
    required = [e for e in pattern.edges if not interp._edge_is_optional(e)]
    optionals = [e for e in pattern.edges if interp._edge_is_optional(e)]
    edges = list(required)
    while edges:
        def rank(e: PatternEdge) -> int:
            fb, tb = e.from_alias in bound, e.to_alias in bound
            if fb and tb:
                return 0
            if fb:
                return 1
            if tb:
                return 2
            return 3

        order = sorted(range(len(edges)), key=lambda i: rank(edges[i]))
        i = order[0]
        e = edges.pop(i)
        r = rank(e)
        if r == 3:
            fn, tn = pattern.nodes[e.from_alias], pattern.nodes[e.to_alias]
            root = fn if interp.estimate(fn) <= interp.estimate(tn) else tn
            steps.append(PlanStep("root", alias=root.alias))
            bound.add(root.alias)
            edges.insert(0, e)
            continue
        if r == 0:
            steps.append(PlanStep("expand", edge=e, close=True))
        elif r == 1:
            steps.append(PlanStep("expand", edge=e))
        else:
            steps.append(PlanStep("expand", edge=e, reverse=True))
        bound.add(e.from_alias)
        bound.add(e.to_alias)
        f = e.item.edge_filter
        if f is not None and f.alias:
            bound.add(f.alias)
    # isolated nodes: the shared admission rule lives in
    # MatchInterpreter.enumerable_isolated so both engines stay in lockstep
    for n in interp.enumerable_isolated(required, optionals):
        if n.alias in bound:
            continue
        if n.is_edge_alias:
            raise Uncompilable("unbound edge alias would scan all edges")
        steps.append(PlanStep("root", alias=n.alias))
        bound.add(n.alias)
    # optional edges: oracle picks (in list order) the first with a decided
    # endpoint; replay statically
    opts = list(optionals)
    while opts:
        pick = None
        for i, e in enumerate(opts):
            if e.from_alias in bound or e.to_alias in bound:
                pick = i
                break
        if pick is None:
            # fully detached optional arms bind null; no step needed (their
            # aliases marshal as None)
            for e in opts:
                bound.add(e.from_alias)
                bound.add(e.to_alias)
            break
        e = opts.pop(pick)
        fb = e.from_alias in bound
        tb = e.to_alias in bound
        steps.append(
            PlanStep("optional", edge=e, reverse=not fb, close=(fb and tb))
        )
        bound.add(e.from_alias)
        bound.add(e.to_alias)
    return steps


# ---------------------------------------------------------------------------
# shared bitmap-hop construction (variable-depth MATCH and TRAVERSE)
# ---------------------------------------------------------------------------


def _var_emit_mask(reached, node_mask_vec, bound_chunk, vb: int):
    """One var-depth level's emission mask: reached ∧ target node mask,
    restricted to the already-bound endpoint on cyclic (close) arms.
    Shared by the row-emitting and count-only paths so their semantics
    cannot drift."""
    emit = reached & node_mask_vec[None, :]
    if bound_chunk is not None:
        vcol = jnp.arange(vb, dtype=jnp.int32)
        emit = emit & (vcol[None, :] == bound_chunk[:, None])
    return emit


def build_bitmap_hops(dg: DeviceGraph, items, sched=None, tier=None,
                      touched=None) -> List:
    """Frontier-hop closures for ``(class, direction, emask)`` items.

    Each closure maps a ``[C, vb]`` frontier bitmap to the bitmap of
    vertices reached over that class+direction. Mesh-sharded graphs hop
    via the sharded edge-list slices with a psum-OR merge over the shards
    axis (SURVEY.md §5.7); single-device graphs scatter over the flat
    edge list. ``emask`` is an optional [E] per-edge prefilter in
    out-CSR order (fused edge WHERE).

    Tiered snapshots (``tier`` set, storage/tiering) hop over the paged
    pool instead of the flat edge list: the recording run faults every
    frontier-touched block resident (accumulating the plan's
    ``touched`` footprint), replays read the pools through ``dg.arrays``
    — jit arguments, so residency changes reach cached plans — and fold
    a device-side cold-miss bit into ``sched`` so an off-footprint
    replay re-records rather than dropping edges."""
    mg = dg.mesh_graph
    armed = getattr(dg.snap, "_overlay", None) is not None
    hops = []
    for cname, d, emask in items:
        dec = dg.edges[cname]
        if mg is None and tier is not None and tier.pages_dir(cname, d):

            def tiered_hop(fr, cname=cname, d=d, emask=emask):
                if sched is None or sched.recording:
                    tier.ensure_frontier(cname, d, fr, touched)
                arrays = dg.arrays
                out = tiering.paged_hop(arrays, cname, d, emask, fr)
                # computed on the recording run too (and discarded):
                # the touch log must see the miss path's keys or the
                # replay's jit-arg subset would lack them
                miss = tiering.paged_hop_miss(arrays, cname, d, fr)
                if sched is not None:
                    sched.note_flag(miss)
                return out

            hops.append(tiered_hop)
            continue
        m = emask if emask is not None else jnp.ones(dec.num_edges, bool)
        if mg is None:
            if armed:
                # delta-maintained edge list: slab slots (appended
                # edges) and tombstones flow through ONE liveness mask,
                # read via dg.arrays so replays take it as a jit
                # argument — a delta patch reaches every cached plan
                lv = dg.arrays[f"e:{cname}:live"]
                m = lv if emask is None else (emask & lv)
            if d == "out":
                a, em = dec.edge_src, dec.dst
            else:  # follow edges backwards: activate dst, emit src
                a, em = dec.dst, dec.edge_src
            hops.append(
                lambda fr, a=a, em=em, m=m: K.bitmap_hop(a, em, m, fr)
            )
        else:
            from orientdb_tpu.parallel.mesh_graph import sharded_bitmap_hop

            p = mg.edge[cname].prefix
            src_sh = dg.arrays[f"{p}:el:src"]
            dst_sh = dg.arrays[f"{p}:el:dst"]
            eid_sh = dg.arrays[f"{p}:el:eid"]
            a_sh, e_sh = (src_sh, dst_sh) if d == "out" else (dst_sh, src_sh)
            hops.append(
                lambda fr, a=a_sh, em=e_sh, i=eid_sh, m=m, mesh=mg.mesh: (
                    sharded_bitmap_hop(mesh, a, em, i, m, fr)
                )
            )
    return hops


# ---------------------------------------------------------------------------
# the solver
# ---------------------------------------------------------------------------


#: a plan's argument beside the graph's arrays: the constant tail of its
#: COUNT's weight chain (`TpuMatchSolver._pushdown_weights`). No graph
#: array has a key under ``plan:``
_COUNT_W = "plan:count_w"
#: the prefix of a plan's copies of a far end's columns (`_FarEnds`)
_ENDS = "plan:ends"


class _FarEnds:
    """The vertex columns a weight pass's destination mask reads, as the
    plan keeps them in the order of a hop whose hull holds one edge a
    vertex (`ops/device_graph.unit_degree`): position ``e`` holds the
    column at edge ``e``'s far end. The recording gathers each column
    once, as the mask first reads it, into ``solver.plan_consts``; a
    replay reads it back as a jit argument (``dg.arrays``), so the mask
    over ``K.IndexRange(0, E)`` slices where it gathered ``[E]`` wide.
    Quacks as the device graph to `TpuMatchSolver._compile_node`."""

    def __init__(self, solver: "TpuMatchSolver", prefix: str, emit) -> None:
        self.solver = solver
        self.prefix = prefix
        #: () -> the far end of every edge, in the hop's order
        self.emit = emit
        self.columns = {
            n: _EndColumn(self, c) for n, c in solver.dg.columns.items()
        }

    def array(self, name: str, source, fill) -> jnp.ndarray:
        key = f"{self.prefix}:{name}"
        s = self.solver
        if not s.sched.recording:
            return s.dg.arrays[key]
        if key not in s.plan_consts:
            s.plan_consts[key] = K.take_pad(source(), self.emit(), fill)
        return s.plan_consts[key]

    @property
    def v_class(self):
        return self.array("v_class", lambda: self.solver.dg.v_class, jnp.int32(-1))


class _EndColumn:
    """A `DeviceColumn` as `_FarEnds` keeps it: its own name, kind and
    host dictionary, its values and presence in the hop's edge order."""

    __slots__ = ("_ends", "_col", "name", "kind", "dictionary")

    def __init__(self, ends: _FarEnds, col) -> None:
        self._ends, self._col = ends, col
        self.name, self.kind, self.dictionary = col.name, col.kind, col.dictionary

    @property
    def values(self):
        return self._ends.array(f"{self.name}:v", lambda: self._col.values, 0)

    @property
    def present(self):
        return self._ends.array(f"{self.name}:p", lambda: self._col.present, False)

    @property
    def dict_unsorted(self) -> bool:
        return self._col.dict_unsorted

    @property
    def dict_lookup(self):
        return self._col.dict_lookup


class TpuMatchSolver:
    def __init__(
        self,
        db,
        stmt: A.MatchStatement,
        params: Dict,
        element_alias: Optional[str] = None,
    ) -> None:
        self.db = db
        self.stmt = stmt
        self.params = params
        #: set for rewritten whole-record SELECTs (select_compile): rows
        #: unwrap from {alias: doc} props back into element rows
        self.element_alias = element_alias
        # numeric parameters compile to reads of this box so one cached
        # plan replays for any value (predicates.ParamBox)
        self.param_box = ParamBox(params)
        #: compiled edge predicates, one a (class, WHERE, visible aliases)
        self._edge_where_fns: Dict[tuple, tuple] = {}
        #: device arrays the recording computed once for every replay
        #: (`_pushdown_weights`): the plan hands them to its replays as
        #: jit arguments beside the graph's (`_CompiledPlan._arg_subset`)
        self.plan_consts: Dict[str, jnp.ndarray] = {}
        #: destination masks over a unit hop's copies (`_ends_mask`)
        self._ends_masks: Dict[tuple, object] = {}
        snap = db.current_snapshot(require_fresh=True)
        if snap is None:
            raise Uncompilable("no fresh snapshot attached")
        self.snap = snap
        self.dg: DeviceGraph = device_graph(snap)
        #: delta-slab overlay (storage/deltas) when the snapshot is
        #: incrementally maintained; plans record its generation and
        #: overflow-fail when the structure moves under them
        self.overlay = getattr(snap, "_overlay", None)
        self.delta_gen = (
            self.overlay.plan_gen if self.overlay is not None else 0
        )
        #: hot/cold tier manager (storage/tiering) when the snapshot's
        #: adjacency exceeds the HBM cap; the recording run accumulates
        #: every faulted block into tier_touched — frozen at plan
        #: construction as the plan's dispatch-prefetch footprint
        self.tier = getattr(snap, "_tier", None)
        self.tier_touched: set = set()
        #: slab-scan capacity floor (host-read here, NOT inside the
        #: traced replay): recordings pre-allocate this many slab
        #: window/match slots even when the slab is near-empty, so a
        #: growing slab crosses far fewer pow2 buckets — each crossing
        #: is a full plan re-record (the r-mixed churn that collapsed
        #: read q/s under sustained writes)
        self._slab_floor = max(8, int(config.delta_slab_edge_slots) // 16)
        self.sched = SizeSchedule()
        # reuse the oracle's pattern build + estimates (host planning data)
        self.interp = MatchInterpreter(db, stmt, params)
        self.pattern = self.interp.pattern
        self.not_paths = self.interp.not_paths
        self.edge_class_list = sorted(self.dg.edges.keys())
        self.edge_class_idx = {n: i for i, n in enumerate(self.edge_class_list)}
        self._vertex_scope_cache: Optional[ColumnScope] = None
        self._check_supported()
        self.plan = build_plan(self.pattern, self.interp)
        #: RETURN items that are a compiled path length: projection
        #: expression -> (column prefix, from alias, to alias, edge class,
        #: the kernel's static arguments), see _compile_path_lens
        self._path_lens = self._compile_path_lens()
        # binding visibility: which (vertex) aliases are bound BEFORE each
        # alias' first bind / each step — this is the scope a
        # binding-referencing WHERE may see (mirrors the oracle, whose
        # check_node/edge-where run with the bindings accumulated so far)
        self._vertex_aliases = {
            a for a, n in self.pattern.nodes.items() if not n.is_edge_alias
        }
        self._alias_visible: Dict[str, set] = {}
        self._step_visible: Dict[int, set] = {}
        bound_so_far: set = set()
        for step in self.plan:
            if step.kind == "root":
                self._alias_visible.setdefault(step.alias, set())
                bound_so_far.add(step.alias)
                continue
            e = step.edge
            src = e.to_alias if step.reverse else e.from_alias
            dst = e.from_alias if step.reverse else e.to_alias
            vis = bound_so_far & self._vertex_aliases
            self._step_visible[id(step)] = vis
            self._alias_visible.setdefault(dst, vis)
            bound_so_far.add(src)
            bound_so_far.add(dst)
            f = e.item.edge_filter
            if f is not None and f.alias:
                bound_so_far.add(f.alias)
        # pre-compile all node/edge predicates (fail fast → fallback);
        # edge-alias nodes carry EDGE-scope filters, which the
        # edge-binding expansion compiles per concrete class itself
        self._node_masks: Dict[str, object] = {}
        for alias, node in self.pattern.nodes.items():
            if not node.is_edge_alias:
                self._node_masks[alias] = self._compile_node(node)
        # WHILE conditions compile with $depth as a per-level scalar
        self._while_fns: Dict[int, object] = {}
        for e in self.pattern.edges:
            w = e.item.target.while_cond
            if w is not None:
                self._while_fns[id(e)] = compile_predicate(
                    w, self._vertex_scope(), self.param_box, allow_depth=True
                )
        # NOT arms: per-path (aliases, admission masks, path items) for the
        # bitmap anti-join — compiled here so an unsupported arm fails
        # fast into the oracle fallback
        self._not_compiled = []
        for path in self.not_paths:
            sub = Pattern()
            prev = sub.node(path.first)
            aliases = [prev.alias]
            for it in path.items:
                aliases.append(sub.node(it.target).alias)
            masks = [self._compile_node(sub.nodes[a]) for a in aliases]
            self._not_compiled.append((aliases, masks, list(path.items)))
        # index-seeded roots ([E] the planner's index-vs-scan choice,
        # SURVEY.md §3.2): a root whose WHERE carries `field = :param` (or
        # a literal) over an indexed field seeds its candidates from the
        # host index — O(hits) instead of an O(|class|) hull scan, the
        # difference between V-independent and V-linear point lookups.
        # Seeds enter replays as jit inputs (see _SeedBox / _dyn_args).
        self.seed_box = _SeedBox()
        self._root_seeds: Dict[str, tuple] = {}
        if config.index_root_seed and self.db._indexes is not None:
            for st in self.plan:
                if st.kind == "root":
                    probe = self._root_seed_probe(st.alias)
                    if probe is not None:
                        self._root_seeds[st.alias] = probe

    def _root_seed_probe(self, alias: str):
        """(rhs expr, index) when the root's WHERE has an AND-conjunct
        `field = <param|literal>` over a single-field index covering the
        node's class; None otherwise."""
        node = self.pattern.nodes[alias]
        for f in node.filters:
            if not f.class_name or f.where is None:
                continue
            for lhs, rhs in _eq_conjuncts(f.where):
                if not isinstance(lhs, A.Identifier):
                    lhs, rhs = rhs, lhs
                if not isinstance(lhs, A.Identifier):
                    continue
                if not isinstance(rhs, (A.Parameter, A.Literal)):
                    continue
                idx = self.db._indexes.best_for(f.class_name, lhs.name)
                if idx is not None:
                    return (rhs, idx)
        return None

    def compute_seed(self, alias: str, params) -> np.ndarray:
        """Host-side index probe: snapshot vertex indices whose indexed
        field equals the (current) value — a SUPERSET filter input; the
        admission mask still applies the full node check."""
        rhs, index = self._root_seeds[alias]
        if isinstance(rhs, A.Parameter):
            key = rhs.name if rhs.name is not None else rhs.index
            value = (params or {}).get(key)
        else:
            value = rhs.value
        hits: List[int] = []
        if value is not None:
            for rid in index.get(value):
                i = self.snap.idx_of(rid)
                if i is not None:
                    hits.append(i)
        hits.sort()  # deterministic candidate order across replays
        return np.asarray(hits, np.int32)

    # -- compile-time gating ------------------------------------------------

    def _check_supported(self) -> None:
        if self.tier is not None:
            # tiered snapshots page the flat edge arrays out of HBM —
            # the method-form expansions (_expand_bind_edge /
            # _expand_edge_endpoint) still read them directly, so those
            # arms fall back to the oracle until they learn the paged
            # gather. Plain arrows, var-depth, NOT arms and TRAVERSE
            # all route through the paged kernels.
            for e in self.pattern.edges:
                if (e.item.method or "").lower() in (
                    "oute", "ine", "bothe", "outv", "inv", "bothv"
                ):
                    raise Uncompilable(
                        "method-form arm on a tiered snapshot"
                    )
        for path in self.not_paths:
            # NOT arms compile to a bitmap anti-join (see
            # _apply_not_path); the chain subset mirrors what that
            # machinery evaluates — no variable depth, methods, optional
            # flags, edge aliases, or binding references inside the arm
            flts = [path.first] + [it.target for it in path.items]
            for flt in flts:
                if flt is None:
                    continue
                if flt.while_cond is not None or flt.max_depth is not None:
                    raise Uncompilable("variable-depth NOT arm")
                if flt.optional or flt.depth_alias or flt.path_alias:
                    raise Uncompilable("optional/depth/path alias in NOT arm")
                if flt.where is not None and _expr_uses_bindings(
                    flt.where, self.pattern.nodes
                ):
                    raise Uncompilable("NOT-arm WHERE references bindings")
            for it in path.items:
                if (it.method or "").lower() in (
                    "outv", "inv", "bothv", "oute", "ine", "bothe"
                ):
                    raise Uncompilable("method form in NOT arm")
                f = it.edge_filter
                if f is not None and f.alias:
                    raise Uncompilable("edge alias in NOT arm")
                if f is not None and f.where is not None and _expr_uses_bindings(
                    f.where, self.pattern.nodes
                ):
                    raise Uncompilable("NOT-arm edge WHERE references bindings")
        reserved = set(self.pattern.nodes.keys())
        for e in self.pattern.edges:
            item = e.item
            m = (item.method or "").lower()
            if m in ("oute", "ine", "bothe") and item.edge_filter is None:
                # bare edge-binding arm (.outE(){as:e}) — compiled by
                # _expand_bind_edge; an edge target with a rid filter has
                # no device analog, and variable depth on an edge binding
                # has no compiled form
                if any(f.rid is not None for f in self.pattern.nodes[e.to_alias].filters):
                    raise Uncompilable("rid filter on an edge-binding target")
                if (
                    item.target.while_cond is not None
                    or item.target.max_depth is not None
                ):
                    raise Uncompilable("variable-depth edge-binding arm")
            if m in ("outv", "inv", "bothv") and (
                item.target.while_cond is not None
                or item.target.max_depth is not None
            ):
                raise Uncompilable("variable-depth endpoint arm")
            var_depth = (
                item.target.while_cond is not None
                or item.target.max_depth is not None
            )
            if item.target.path_alias:
                raise Uncompilable("pathAlias not compiled (per-path state)")
            if item.negated:
                raise Uncompilable("negated path item")
            f = item.edge_filter
            if var_depth:
                # variable-depth arms evaluate masks vertex-wise (no
                # per-row env), so binding references stay interpreted
                if f is not None and f.where is not None and _expr_uses_bindings(
                    f.where, self.pattern.nodes
                ):
                    raise Uncompilable("edge WHERE references bindings (WHILE arm)")
                if item.target.where is not None and _expr_uses_bindings(
                    item.target.where, self.pattern.nodes
                ):
                    raise Uncompilable("node WHERE references bindings (WHILE arm)")
                if f is not None and f.alias:
                    raise Uncompilable(
                        "edge alias on a WHILE arrow (discovery-edge binding)"
                    )
                w = item.target.while_cond
                if w is not None and _expr_uses_bindings(w, self.pattern.nodes):
                    raise Uncompilable("WHILE condition references bindings")
        # edge-alias nodes are fine when bound by an edge-filter alias or
        # as the target of a bare edge-binding arm (.outE(){as:e}); a bare
        # edge-alias root is not
        edge_filter_aliases = {
            e.item.edge_filter.alias
            for e in self.pattern.edges
            if e.item.edge_filter is not None and e.item.edge_filter.alias
        }
        edge_bind_targets = {
            e.to_alias
            for e in self.pattern.edges
            if (e.item.method or "").lower() in ("oute", "ine", "bothe")
            and e.item.edge_filter is None
        }
        for node in self.pattern.nodes.values():
            if (
                node.is_edge_alias
                and node.alias not in edge_filter_aliases
                and node.alias not in edge_bind_targets
            ):
                raise Uncompilable("edge-alias pattern nodes not compiled yet")

    def _compile_path_lens(self) -> Dict:
        """The statement's ``shortestPath(a, b, 'BOTH', class).size() - 1``
        RETURN items as device searches between two bound vertex aliases
        over one concrete edge class, walked both ways. Any other use of
        the function (the path itself, ``maxDepth``, one direction only,
        several edge classes or none named, an end that is no vertex
        alias) is the oracle's."""
        out: Dict = {}
        for p in self.stmt.returns:
            call = _path_len_call(p.expr)
            if call is None:
                if _calls_function(p.expr, "shortestpath"):
                    raise Uncompilable(
                        "shortestPath compiles as shortestPath(a, b, "
                        "direction, class).size() - 1 only"
                    )
                continue
            args = call.args
            if not 2 <= len(args) <= 4:
                raise Uncompilable("shortestPath with maxDepth/options")
            ends = []
            for a in args[:2]:
                node = (
                    self.pattern.nodes.get(a.name)
                    if isinstance(a, A.Identifier)
                    else None
                )
                if node is None or node.is_edge_alias:
                    raise Uncompilable("shortestPath end is no vertex alias")
                ends.append(a.name)
            lits = [a.value if isinstance(a, A.Literal) else a for a in args[2:]]
            direction = (lits[0] if lits else None) or "BOTH"
            if not isinstance(direction, str) or direction.upper() != "BOTH":
                raise Uncompilable("shortestPath compiles for 'BOTH' only")
            ecls = lits[1] if len(lits) > 1 else None
            if not isinstance(ecls, str):
                raise Uncompilable("shortestPath over every edge class")
            concrete = self.snap.edge_closure.get(ecls.lower(), [])
            if len(concrete) != 1 or concrete[0] not in self.dg.edges:
                raise Uncompilable(
                    f"shortestPath over {len(concrete)} concrete edge classes"
                )
            if (
                self.overlay is not None
                or self.tier is not None
                or self.dg.mesh_graph is not None
            ):
                raise Uncompilable(
                    "shortestPath on a delta-maintained, tiered or sharded graph"
                )
            front, chunk = _bfs_caps(self.snap.edge_classes[concrete[0]])
            out[p.expr] = (
                f"$path{len(out)}",
                ends[0],
                ends[1],
                concrete[0],
                dict(front=front, chunk=chunk),
            )
        return out

    def _apply_path_lens(self, table: Table) -> Table:
        """One search a live row and compiled path length: the rows are
        packed to as many pairs as the recording had rows (a pair costs a
        whole search, so none is spent on padding; a replay with more
        live rows overflows and records anew), searched under ``vmap``,
        and what each search returned (``K.BFS_PARTS``) is laid back
        into the table as int32 columns ``<prefix>.<part>``."""
        width = table.width
        pairs = max(table.count, 1)
        keep = K.compact_indices(table.valid_device[:width].astype(bool), pairs)
        self.sched.note_flag(table.count_device > pairs)
        back = jnp.where(keep >= 0, keep, width)
        for prefix, a, b, ecls, static in self._path_lens.values():
            dec = self.dg.edges[ecls]
            graph = (dec.indptr_out, dec.dst, dec.edge_src, dec.indptr_in, dec.src)
            ends = (
                K.take_pad(table.cols[a], keep, jnp.int32(-1)),
                K.take_pad(table.cols[b], keep, jnp.int32(-1)),
            )
            if pairs == 1:
                # the one pair as scalars: the lanes' vmap of the group
                # replay then searches the lanes' pairs together, and no
                # axis of one lies under it (the chip pads a
                # [lanes, 1, E] temporary to eight times its size)
                found = K.bfs_pair_len(*graph, ends[0][0], ends[1][0], **static)[None]
            else:
                found = jax.vmap(
                    lambda s, t: K.bfs_pair_len(*graph, s, t, **static)
                )(*ends)
            for j, part in enumerate(K.BFS_PARTS):
                table.depth_cols[f"{prefix}.{part}"] = (
                    jnp.full(width, -1, jnp.int32)
                    .at[back]
                    .set(found[:, j], mode="drop")
                )
        return table

    def _count_path_lens(self, table: Table) -> None:
        """The searches' counters, once an answer, from what the device
        returned with it: ``bfs.queries``, ``bfs.levels``,
        ``bfs.edges_expanded`` (a dense level reads every edge once a
        direction), ``bfs.overflow``, ``bfs.unreachable``."""
        sel = self._live_rows(table)
        for prefix, _a, _b, ecls, _static in self._path_lens.values():
            if f"{prefix}.len" not in table.depth_cols:
                continue  # the recording had no row to search
            col = {
                part: np.asarray(table.depth_cols[f"{prefix}.{part}"])[sel]
                for part in K.BFS_PARTS
            }
            a_level = 2 * self.dg.edges[ecls].num_edges
            metrics.incr("bfs.queries", int(col["len"].size))
            metrics.incr("bfs.levels", int(col["levels"].sum()))
            metrics.incr(
                "bfs.edges_expanded",
                int(col["edges"].sum()) + a_level * int(col["dense_levels"].sum()),
            )
            metrics.incr("bfs.overflow", int(col["overflow"].sum()))
            metrics.incr("bfs.unreachable", int((col["len"] < 0).sum()))

    # -- predicate compilation ---------------------------------------------

    def _vertex_scope(self) -> ColumnScope:
        if self._vertex_scope_cache is None:
            self._vertex_scope_cache = ColumnScope(
                self.dg.columns,
                self.dg.non_columnar,
                reserved=set(self.pattern.nodes.keys()),
            )
        return self._vertex_scope_cache

    def _compile_node(self, node: PatternNode, ends: Optional[_FarEnds] = None):
        """Node admission mask: fn(idx) -> bool mask over vertex ids,
        ``idx`` an int32 array or a ``K.IndexRange`` (whose column reads
        are slices). With ``ends`` (no rid filter, no binding) the mask
        reads that hop's copies of the columns, and ``idx`` is a position
        in its edge order.

        Mirrors oracle.check_node: class closure ∧ rid ∧ WHERE. A WHERE
        referencing earlier bindings (``alias.prop``) compiles against the
        alias-visibility set at this node's first bind; the mask then
        needs env["bindings"] at evaluation (``mask.uses_bindings``).
        ``mask.uses_params`` says whether its WHERE reads a dynamic
        parameter: without one the mask is the same on every replay."""
        graph = self.dg if ends is None else ends
        parts = []
        uses_bindings = False
        has_class = any(f.class_name for f in node.filters)
        if self.overlay is not None and not has_class:
            # delta-maintained universe: spare slab rows and deleted
            # vertices carry class -1 — a class filter excludes them via
            # isin, but a bare node needs an explicit liveness conjunct
            parts.append(
                lambda idx, env: K.take_pad(
                    self.dg.v_class, idx, jnp.int32(-1)
                )
                >= 0
            )
        for f in node.filters:
            if f.class_name:
                ids = self.dg.class_ids(f.class_name)
                parts.append(self._class_mask_fn(ids, graph))
            if f.rid is not None:
                want = self.snap.idx_of(RID(f.rid.cluster, f.rid.position))
                wi = -2 if want is None else want  # -2 matches nothing (≠ -1 pad)
                parts.append(lambda idx, env, wi=wi: K.as_index(idx) == wi)
            if f.where is not None:
                if _expr_uses_bindings(f.where, self.pattern.nodes):
                    scope = ColumnScope(
                        self.dg.columns,
                        self.dg.non_columnar,
                        reserved=set(self.pattern.nodes.keys()),
                        binding_columns=self.dg.columns,
                        binding_non_columnar=self.dg.non_columnar,
                        visible_aliases=self._alias_visible.get(
                            node.alias, set()
                        ),
                    )
                    fn = compile_predicate(f.where, scope, self.param_box)
                    uses_bindings = uses_bindings or scope.uses_bindings
                else:
                    scope = self._vertex_scope() if ends is None else ColumnScope(
                        ends.columns,
                        self.dg.non_columnar,
                        reserved=set(self.pattern.nodes.keys()),
                    )
                    fn = compile_predicate(f.where, scope, self.param_box)
                parts.append(fn)

        def mask(idx, env=None, parts=parts):
            env = env or {}
            m = K.as_index(idx) >= 0
            for p in parts:
                m = m & p(idx, env)
            return m

        mask.uses_bindings = uses_bindings
        mask.uses_params = any(getattr(p, "uses_params", False) for p in parts)
        return mask

    def _class_mask_fn(self, ids: jnp.ndarray, graph):
        def fn(idx, env, ids=ids):
            cls = K.take_pad(graph.v_class, idx, jnp.int32(-1))
            if ids.shape[0] == 0:
                return jnp.zeros(idx.shape, bool)
            return jnp.isin(cls, ids)

        return fn

    def _edge_where(
        self, concrete: str, where: A.Expression, visible: Optional[set] = None
    ):
        """Edge-property predicate over edge ids; with ``visible`` given,
        ``alias.prop`` references to those (vertex) aliases compile too —
        the returned fn then carries ``uses_bindings`` and needs
        env["bindings"] arrays aligned with its idx slots. Compiled once
        a solver: every lowering of a pass asks for it again, and
        ``uses_params`` (`compile_predicate`) is asked before any."""
        key = (concrete, id(where), frozenset(visible or ()))
        hit = self._edge_where_fns.get(key)
        if hit is not None:
            return hit[1]
        dec = self.dg.edges[concrete]
        scope = ColumnScope(
            dec.columns,
            dec.non_columnar,
            reserved=set(self.pattern.nodes.keys()),
            binding_columns=self.dg.columns if visible else None,
            binding_non_columnar=self.dg.non_columnar,
            visible_aliases=visible or set(),
        )
        fn = compile_predicate(where, scope, self.param_box)
        fn.uses_bindings = scope.uses_bindings
        # the expression rides along: its id is the key while it lives
        self._edge_where_fns[key] = (where, fn)
        return fn

    # -- execution ----------------------------------------------------------

    @staticmethod
    def _binding_env(table: Table, row: jnp.ndarray, visible: set) -> Dict:
        """env for binding-referencing predicates: per-slot vertex-index
        arrays for each visible alias, aligned with ``row`` (the source
        binding-table row per expansion slot; pass None for identity
        row mapping on width-aligned masks)."""
        def col(a):
            if a not in table.cols:
                shape = row.shape if row is not None else (table.width or 1,)
                return jnp.full(shape, -1, jnp.int32)
            if row is None:
                return table.cols[a]
            return K.take_pad(table.cols[a], row, jnp.int32(-1))

        return {"bindings": {a: col(a) for a in visible}}

    def _compact(self, mask):
        return _observe_compact(self.sched, mask)

    def _expand_csr(self, indptr, nbrs, srcs):
        counts = K.degree_counts(indptr, srcs)
        offsets = K.exclusive_cumsum(counts)
        total_dev = counts.sum()
        total = self.sched.observe(total_dev)
        row, edge_pos, nbr = K.gather_expand(
            indptr, nbrs, srcs, offsets, total_dev, _cap_of(total)
        )
        if self.overlay is not None:
            # delta-tombstoned base edges keep their CSR slot but carry
            # a -1 endpoint: turn those slots into padding so the dead
            # edge can never bind (matches gather_expand's own padding)
            dead = nbr < 0
            row = jnp.where(dead, -1, row)
            edge_pos = jnp.where(dead, -1, edge_pos)
        return row, edge_pos, nbr, total

    def _expand_paged(self, dec, d: str, srcs, part):
        """CSR expansion over a tiered (paged) partition: row/edge_pos
        come from the resident indptr exactly as the flat path; the
        neighbor (and, reverse, the out-order edge id) gather from the
        hot pool through the block→page indirection. The recording run
        faults every touched block resident first (and logs it into the
        plan's tier footprint); replays fold the device-side cold-miss
        bit into the overflow surface instead of syncing."""
        if self.sched.recording:
            # eager run inside the allowlisted _record boundary: the
            # host read of the frontier is the intentional fault path
            self.tier.ensure_vertices(
                dec.class_name, d, np.asarray(srcs), self.tier_touched
            )
        arrays = self.dg.arrays
        indptr = arrays[
            f"e:{dec.class_name}:indptr_{'out' if d == 'out' else 'in'}"
        ]
        counts = K.degree_counts(indptr, srcs)
        offsets = K.exclusive_cumsum(counts)
        total_dev = counts.sum()
        total = self.sched.observe(total_dev)
        row, eid, nbr, miss = tiering.paged_expand(
            arrays, dec.class_name, d, srcs, offsets, total_dev,
            _cap_of(total), part.Wp,
        )
        self.sched.note_flag(miss)
        return row, eid, nbr, total

    def _expand_slab(self, dec, d: str, srcs):
        """Append-slab expansion for one (class, direction): scan the
        slab tail of the padded edge list for live edges whose active
        endpoint is in ``srcs``. The scan window is sized by the
        OBSERVED used-slot count (SizeSchedule), so replays overflow —
        and re-record with a wider window — when the slab outgrows the
        recording; compaction folds the slab away entirely."""
        ov = self.overlay
        base = ov.edge_base(dec.class_name)
        cap = dec.num_edges
        if cap <= base:
            return None
        if (
            dec.class_name in getattr(ov, "bk", {})
            and dec.class_name not in ov.bucket_overflow
        ):
            # O(touched buckets) path — falls back to the window scan
            # below only when a bucket overflowed (plan_gen bumps then,
            # so recorded plans never switch paths mid-replay)
            return self._expand_slab_bucketed(dec, d, srcs, base)
        arrays = self.dg.arrays
        p = f"e:{dec.class_name}"
        tail_src = arrays[f"{p}:edge_src"][base:cap]
        tail_dst = arrays[f"{p}:dst"][base:cap]
        tail_live = arrays[f"{p}:live"][base:cap]
        # used slots are append-only: edge_src >= 0 marks them even
        # after a tombstone (live=False), so the window bound survives
        # deletes. The _slab_floor keeps both buckets generous: a slab
        # filling write-by-write must not re-record the plan at every
        # pow2 crossing.
        floor = min(cap - base, self._slab_floor)
        used = self.sched.observe(
            jnp.sum((tail_src >= 0).astype(jnp.int32)),
            min_capacity=floor,
        )
        W = min(cap - base, max(_cap_of(max(used, 1)), floor))
        a = tail_src[:W] if d == "out" else tail_dst[:W]
        e = tail_dst[:W] if d == "out" else tail_src[:W]
        m = (
            (a[None, :] == srcs[:, None])
            & tail_live[:W][None, :]
            & (srcs >= 0)[:, None]
        )
        total_dev = m.sum(dtype=jnp.int32)
        total = self.sched.observe(total_dev, min_capacity=floor)
        out = max(_cap_of(max(total, 1)), floor)
        idx = K.compact_indices(m.reshape(-1), out)
        ok = idx >= 0
        row = jnp.where(ok, idx // W, -1).astype(jnp.int32)
        j = jnp.where(ok, idx % W, 0).astype(jnp.int32)
        eid = jnp.where(ok, base + j, -1).astype(jnp.int32)
        nbr = jnp.where(ok, jnp.take(e, j), -1).astype(jnp.int32)
        return row, eid, nbr, total

    def _expand_slab_bucketed(self, dec, d: str, srcs, base: int):
        """Bucket-indexed slab expansion: probe each active endpoint's
        BK-slot bucket instead of scanning the whole used window —
        O(rows × BK) work per expansion however full the slab gets
        (the r15 scan was O(rows × used slots): ~2× read cost at
        500-edge occupancy). Same contract as the scan: (row, global
        edge id, neighbor, host total)."""
        ov = self.overlay
        NB, BK = ov.bk_nb, ov.bk_bk
        arrays = self.dg.arrays
        p = f"e:{dec.class_name}"
        tab = arrays[f"bk:{dec.class_name}:{'out' if d == 'out' else 'in'}"]
        own_a = arrays[f"{p}:{'edge_src' if d == 'out' else 'dst'}"]
        nbr_a = arrays[f"{p}:{'dst' if d == 'out' else 'edge_src'}"]
        live = arrays[f"{p}:live"]
        # int32 two's complement: -1 & (NB-1) is a valid (masked) bucket
        b = srcs & jnp.int32(NB - 1)
        slots = b[:, None] * BK + jnp.arange(BK, dtype=jnp.int32)[None, :]
        rel = jnp.take(tab, slots)  # [R, BK] relative slab slots
        ok = (rel >= 0) & (srcs >= 0)[:, None]
        abs_ = base + jnp.clip(rel, 0)
        m = (
            ok
            & (jnp.take(own_a, abs_) == srcs[:, None])
            & jnp.take(live, abs_)
        )
        floor = min(dec.num_edges - base, self._slab_floor)
        total = self.sched.observe(m.sum(dtype=jnp.int32), min_capacity=floor)
        out = max(_cap_of(max(total, 1)), floor)
        idx = K.compact_indices(m.reshape(-1), out)
        okk = idx >= 0
        row = jnp.where(okk, idx // BK, -1).astype(jnp.int32)
        rel_sel = jnp.take(rel.reshape(-1), jnp.clip(idx, 0))
        eid = jnp.where(okk, base + rel_sel, -1).astype(jnp.int32)
        nbr = jnp.where(
            okk, jnp.take(nbr_a, jnp.clip(base + rel_sel, 0)), -1
        ).astype(jnp.int32)
        return row, eid, nbr, total

    def _expand_one_dir_chunked(self, dec, d: str, srcs):
        """Expansion slabs for one (class, direction): usually ONE
        ``(row, eid, nbr, total)``, but when the output would exceed
        config.max_expansion_cap rows, the binding table splits into
        contiguous row ranges expanded separately — intermediate buffers
        stay bounded however large the fan-out (the SURVEY.md §7
        binding-table-blowup mitigation). The chunk count derives from
        the RECORDED total, so replays keep the structure; per-chunk
        observes catch parameter-driven growth."""
        mg = self.dg.mesh_graph
        cap = max(1, config.max_expansion_cap)
        if mg is not None:
            return [self._expand_one_dir(dec, d, srcs)]
        if d == "out":
            indptr = dec.indptr_out
        else:
            indptr = dec.indptr_in
        counts = K.degree_counts(indptr, srcs)
        total = self.sched.observe(counts.sum(), free=True)
        n_chunks = max(1, -(-_cap_of(total) // cap))
        if n_chunks == 1:
            return [self._expand_one_dir(dec, d, srcs)]
        width = int(srcs.shape[0])
        step = -(-width // n_chunks)
        slabs = []
        for a in range(0, width, step):
            sub = srcs[a : a + step]
            row, eid, nbr, t = self._expand_one_dir(dec, d, sub)
            row = jnp.where(row >= 0, row + a, row)  # local → table rows
            slabs.append((row, eid, nbr, t))
        return slabs

    def _expand_one_dir(self, dec, d: str, srcs):
        """One (edge class, direction) expansion → (row, global edge id,
        neighbor, host total), on the single-device or mesh-sharded path."""
        mg = self.dg.mesh_graph
        if mg is None:
            if self.tier is not None:
                part = self.tier.parts.get((dec.class_name, d))
                if part is not None:
                    return self._expand_paged(dec, d, srcs, part)
            if d == "out":
                indptr, nbrs = dec.indptr_out, dec.dst
            else:
                indptr, nbrs = dec.indptr_in, dec.src
            row, edge_pos, nbr, total = self._expand_csr(indptr, nbrs, srcs)
            if d == "out":
                eid = edge_pos
            else:
                eid = K.take_pad(dec.edge_id_in, edge_pos, jnp.int32(-1))
            if self.overlay is not None and self.overlay.topology_dirty:
                # append-slab edges live outside the base CSR: merge the
                # slab scan's slots in (padding interleaves — downstream
                # masks key on row >= 0, not prefix contiguity)
                slab = self._expand_slab(dec, d, srcs)
                if slab is not None:
                    row = jnp.concatenate([row, slab[0]])
                    eid = jnp.concatenate([eid, slab[1]])
                    nbr = jnp.concatenate([nbr, slab[2]])
                    total = total + slab[3]
            return row, eid, nbr, total
        from orientdb_tpu.parallel.mesh_graph import expand_gather, expand_totals

        arrays = self.dg.arrays
        p = mg.edge[dec.class_name].prefix
        ind_sh = arrays[f"{p}:{d}:indptr"]
        nbr_sh = arrays[f"{p}:{d}:nbr"]
        span_sh = arrays["sh:rowspan"]
        extra_sh = (
            arrays[f"{p}:out:ebase"] if d == "out" else arrays[f"{p}:in:eid"]
        )
        tots = expand_totals(mg.mesh, ind_sh, span_sh, srcs)
        total = self.sched.observe(tots.sum())
        max_local = self.sched.observe(tots.max())
        cap = _cap_of(max(max_local, 1))
        # merged segment sized by the GLOBAL total, not S x local max:
        # the ring-compacted merge in expand_gather keeps skewed shards
        # (supernodes) from inflating every shard's block
        cap_total = _cap_of(max(total, 1))
        if self.sched.recording:
            # merge-traffic observability: rows actually merged vs what the old
            # all_gather-of-blocks design would have shipped, per-hop
            # collective bytes (3 packed int32 psum segments), live-
            # frontier occupancy of the expansion slots, and how many
            # shards cond-skipped their gather/scatter outright
            S = mg.mesh.devices.size // (
                mg.mesh.shape.get(config.mesh_replica_axis, 1)
            )
            metrics.incr("mesh.merge_rows", cap_total)
            metrics.incr("mesh.allgather_rows", S * cap)
            metrics.incr("mesh.collective_bytes", 12 * cap_total)
            metrics.incr("mesh.frontier_live_rows", total)
            metrics.incr("mesh.frontier_slot_rows", S * cap)
            # recording runs inside the allowlisted _record boundary,
            # so this tiny [S] fetch is an intentional transfer
            metrics.incr(
                "mesh.empty_shard_skips", int((np.asarray(tots) == 0).sum())
            )
        row, eid, nbr = expand_gather(
            mg.mesh,
            ind_sh,
            nbr_sh,
            extra_sh,
            span_sh,
            srcs,
            cap,
            cap_total,
            is_out=(d == "out"),
        )
        return row, eid, nbr, total

    def solve_table(self) -> Table:
        pushdown = self._count_pushdown_steps()
        var_count = None if pushdown else self._var_count_step()
        if pushdown:
            steps = self.plan[: len(self.plan) - len(pushdown)]
        elif var_count is not None:
            steps = self.plan[:-1]
        else:
            steps = self.plan
        from contextlib import nullcontext

        from orientdb_tpu.obs.registry import obs as _obs
        from orientdb_tpu.obs.trace import span as _span

        # spans/histograms only on the eager RECORDING execution: replay
        # re-traces this body under jax.jit (compile time, recorded-size
        # padding) — observing there would record tracing artifacts as if
        # they were query execution
        rec = self.sched.recording
        if pushdown and self._folds_root(steps, pushdown):
            return self._apply_count_pushdown(None, pushdown, root=steps[0])
        table = Table(count=1, width=0)
        for step in steps:
            if table.empty():
                # required-edge pipeline already empty → no rows; optional
                # steps cannot resurrect rows
                return table
            # one span per plan step (root seed / PatternEdge hop): the
            # per-hop stage timings PROFILE surfaces; frontier sizes feed
            # the tpu.frontier_rows histogram on /metrics
            sp = _span("tpu.step", step=step.describe()) if rec else None
            with sp if sp is not None else nullcontext():
                if step.kind == "root":
                    table = self._root(table, step.alias)
                elif step.kind == "expand":
                    table = self._expand(table, step, optional=False)
                else:
                    table = self._expand(table, step, optional=True)
                if sp is not None:
                    sp.set("frontier_rows", table.count)
            if rec:
                _obs.observe_size("tpu.frontier_rows", table.count)
        if self._not_compiled and not table.empty():
            with _span("tpu.step", step="NOT anti-join") if rec else (
                nullcontext()
            ):
                table = self._apply_not_paths(table)
        if self._path_lens and not table.empty():
            table = self._apply_path_lens(table)
        if pushdown and not table.empty():
            return self._apply_count_pushdown(table, pushdown)
        if var_count is not None and not table.empty():
            return self._expand_var_depth(
                table, var_count, optional=False, count_only=True
            )
        return table

    # -- COUNT(*) aggregate pushdown ----------------------------------------

    # -- NOT patterns: bitmap anti-join -------------------------------------

    def _apply_not_paths(self, table: Table) -> Table:
        """Reject rows for which any NOT arm is satisfiable — the [E]
        NOT-pattern filter of OMatchStatement, evaluated as a chunked
        bitmap chain: candidates for the arm's first position (one-hot of
        the shared binding, or its admission mask over all vertices), one
        frontier hop per arm item, target masks/bindings ANDed in; a row
        with any survivor at the chain's end matched the NOT arm."""
        for aliases, masks, items in self._not_compiled:
            if table.empty():
                return table
            table = self._apply_not_path(table, aliases, masks, items)
        return table

    def _apply_not_path(self, table: Table, aliases, masks, items) -> Table:
        width = table.width or 1
        V = self.dg.num_vertices
        vb = K.bucket(max(V, 1))
        univ = _index_range(self.dg, self.tier, 0, V, vb)
        node_vecs = [m(univ) for m in masks]
        hops_per_item = []
        for it in items:
            hop_items = []
            f = it.edge_filter
            for cname in self._resolve_edge_classes(it):
                dec = self.dg.edges[cname]
                emask = None
                if f is not None and f.where is not None:
                    eids = _index_range(
                        self.dg, self.tier, 0, dec.num_edges, dec.num_edges
                    )
                    emask = self._edge_where(cname, f.where)(eids, {})
                dirs = ("out", "in") if it.direction == "both" else (it.direction,)
                for d in dirs:
                    hop_items.append((cname, d, emask))
            hops_per_item.append(
                build_bitmap_hops(
                    self.dg, hop_items, sched=self.sched, tier=self.tier,
                    touched=self.tier_touched,
                )
            )
        vcol = jnp.arange(vb, dtype=jnp.int32)
        valid_dev = table.valid_device
        exists_chunks = []
        C = self._var_chunk_rows(width, vb)
        for cs in range(0, width, C):
            chunk_rows = jnp.arange(cs, cs + C, dtype=jnp.int32)
            in_range = jnp.where(chunk_rows < valid_dev.shape[0], chunk_rows, -1)
            chunk_valid = K.take_pad(valid_dev, in_range, jnp.int32(0)) > 0
            chunk_rows = jnp.where(chunk_valid, chunk_rows, -1)
            a0 = aliases[0]
            if a0 in table.cols:
                src = K.take_pad(table.cols[a0], chunk_rows, jnp.int32(-1))
                cur = K.rows_to_bitmap(src, vb) & node_vecs[0][None, :]
            else:
                cur = node_vecs[0][None, :] & chunk_valid[:, None]
            for k, hops in enumerate(hops_per_item):
                nxt = jnp.zeros_like(cur)
                for hop in hops:
                    nxt = nxt | hop(cur)
                nxt = nxt & node_vecs[k + 1][None, :]
                tgt = aliases[k + 1]
                if tgt in table.cols:
                    bound = K.take_pad(
                        table.cols[tgt], chunk_rows, jnp.int32(-2)
                    )
                    nxt = nxt & (vcol[None, :] == bound[:, None])
                cur = nxt
            exists_chunks.append(cur.any(axis=1))
        exists = jnp.concatenate(exists_chunks)[:width]
        keep_mask = valid_dev[:width].astype(bool) & ~exists
        keep, kn, kn_dev = self._compact(keep_mask)
        t = table.gather(keep)
        t.count = kn
        t.count_dev = kn_dev
        return t

    def _count_pushdown_steps(self) -> List[PlanStep]:
        """Longest plan suffix of terminal chain expansions a lone COUNT(*)
        can aggregate without materializing binding tables.

        The reference counts MATCH results by draining the full traverser
        chain row by row ([E] the MatchStep pipeline under a COUNT
        projection); here every terminal hop collapses to one O(E)
        segment-sum pass — ``w_k[v] = Σ_{edges v→u} emask(e)·mask(u)·
        w_{k+1}[u]`` (a sparse matvec over the edge list) — and the count
        is ``w_1`` summed over the chain's sources: ``Σ_rows w_1[src]``
        over the rows of the table the plan's prefix built, or, where
        that prefix is the source's root step alone (`_folds_root`),
        ``Σ_{v in the root's range} mask(v)·w_1[v]`` with no table at
        all (`_apply_count_pushdown`). This keeps the per-query device
        program at O(E + V) instead of O(result rows), which is what
        makes batched COUNT throughput independent of fan-out.
        """
        if self.count_only_name() is None or self.stmt.group_by or self._not_compiled:
            return []
        if self.overlay is not None and self.overlay.topology_dirty:
            # the weight chain sums degrees off the base CSR indptr:
            # slab edges would be missed and tombstoned edges counted.
            # Dirty-topology plans take the full (slab-aware) solve;
            # compaction restores the pushdown on the next recording.
            return []
        if self.tier is not None:
            # the weight passes read the flat [E] arrays directly —
            # paged out on a tiered snapshot. The frontier solve (paged
            # gather + bitmap hops) covers COUNT correctly, just
            # without the pushdown's O(E+V) collapse.
            return []
        suffix: List[PlanStep] = []
        # alias usage counts over all edges (from/to + edge-filter aliases)
        for step in reversed(self.plan):
            if step.kind != "expand" or step.close:
                break
            e = step.edge
            item = e.item
            if (
                item.target.while_cond is not None
                or item.target.max_depth is not None
                or item.target.depth_alias
                or (item.edge_filter is not None and item.edge_filter.alias)
            ):
                break
            # binding-referencing predicates need per-row env — the
            # pushdown's vertex-wise weight passes cannot provide one
            if (
                item.edge_filter is not None
                and item.edge_filter.where is not None
                and _expr_uses_bindings(item.edge_filter.where, self.pattern.nodes)
            ):
                break
            mm = (item.method or "").lower()
            if (mm in ("oute", "ine", "bothe") and item.edge_filter is None) or mm in (
                "outv", "inv", "bothv"
            ):
                break  # edge-binding / endpoint arms have no weight pass
            dst_alias = e.from_alias if step.reverse else e.to_alias
            if getattr(self._node_masks[dst_alias], "uses_bindings", False):
                break
            # dst must be terminal: referenced by no OTHER edge than this one
            # and (for non-last suffix members) only as the src of the next
            # pushdown step — checked by walking backwards: the "next" step
            # is already in `suffix`, and its src is this dst.
            used_elsewhere = False
            for e2 in self.pattern.edges:
                if e2 is e:
                    continue
                in_suffix_head = suffix and e2 is suffix[0].edge
                touches = dst_alias in (e2.from_alias, e2.to_alias)
                f2 = e2.item.edge_filter
                if f2 is not None and f2.alias == dst_alias:
                    used_elsewhere = True
                if touches and not in_suffix_head:
                    used_elsewhere = True
            if used_elsewhere:
                break
            if suffix:
                nxt = suffix[0]
                nxt_src = (
                    nxt.edge.to_alias if nxt.reverse else nxt.edge.from_alias
                )
                if nxt_src != dst_alias:
                    break
            suffix.insert(0, step)
        return suffix

    def _var_count_step(self) -> Optional[PlanStep]:
        """The plan's final step, when it is a terminal var-depth (WHILE /
        maxDepth) expansion a lone COUNT(*) can aggregate by per-level
        popcounts (`_expand_var_depth(count_only=True)`) — the var-depth
        sibling of `_count_pushdown_steps`, which stops at WHILE arms."""
        if (
            self.count_only_name() is None
            or self.stmt.group_by
            or self._not_compiled
            or not self.plan
        ):
            return None
        step = self.plan[-1]
        if step.kind != "expand" or step.close:
            return None  # optional arms contribute unmatched rows too
        e = step.edge
        item = e.item
        if item.target.while_cond is None and item.target.max_depth is None:
            return None  # fixed expansion — the weight pushdown covers it
        f = item.edge_filter
        if f is not None and f.alias:
            return None
        dst_alias = e.from_alias if step.reverse else e.to_alias
        if getattr(self._node_masks[dst_alias], "uses_bindings", False):
            return None
        for e2 in self.pattern.edges:
            if e2 is e:
                continue
            if dst_alias in (e2.from_alias, e2.to_alias):
                return None  # dst participates elsewhere: rows needed
            f2 = e2.item.edge_filter
            if f2 is not None and f2.alias == dst_alias:
                return None
        return step

    @staticmethod
    def _pushdown_source(steps: List[PlanStep]) -> str:
        first = steps[0]
        return first.edge.to_alias if first.reverse else first.edge.from_alias

    def _folds_root(self, prefix: List[PlanStep], steps: List[PlanStep]) -> bool:
        """Whether the plan's ``prefix`` (what precedes the pushdown's
        ``steps``) is exactly the root step of the pushdown's source: the
        count then needs no row of that root, only its mask (see
        `_apply_count_pushdown`). A cartesian with an earlier table, an
        expansion before the chain and a path length all leave a longer
        prefix or a table to read, and keep the rows."""
        return (
            len(prefix) == 1
            and prefix[0].kind == "root"
            and prefix[0].alias == self._pushdown_source(steps)
            and not self._path_lens
        )

    def _apply_count_pushdown(
        self,
        table: Optional[Table],
        steps: List[PlanStep],
        root: Optional[PlanStep] = None,
    ) -> Table:
        """The count of a chain of pushed-down ``steps``: the weight
        chain's ``w_1`` (`_pushdown_weights`) summed over its sources.

        With ``table`` the sources are the rows the plan's prefix bound:
        ``Σ_rows w_1[src]``, a gather through the source column. With
        ``root`` (the prefix is that root step alone, `_folds_root`) the
        root is folded and never materialised: its candidates are a
        contiguous range under a mask (or an index's seeds under one,
        `_root_index`), and the same sum, term for term, is
        ``Σ_idx mask(idx)·w_1[idx]``: two slices, a ``where`` and a
        reduction. No compaction, no table column and no observed size,
        so nothing a replay with other parameters can overflow. Counted
        where Python lowers it (a recording, and each trace of a replay):
        ``plan.count.root_fold`` / ``plan.count.root_rows``."""
        if root is not None:
            metrics.incr("plan.count.root_fold")
            srcs, mask = self._folded_root(root)
        else:
            metrics.incr("plan.count.root_rows")
            src_alias = self._pushdown_source(steps)
            srcs, mask = table.cols.get(src_alias), None
            if srcs is None:
                raise Uncompilable(
                    f"alias {src_alias} not bound before expansion"
                )

        def summed(w, zero):
            per_src = K.take_pad(w, srcs, zero)
            if mask is not None:
                per_src = jnp.where(mask, per_src, zero)
            return per_src.sum()

        w = self._pushdown_weights(steps, jnp.int32)
        total_dev = summed(w, jnp.int32(0))
        if self.sched.recording:
            # int32 overflow guard (x64 is disabled on TPU): a float32 twin
            # of the whole weight chain detects wraps anywhere in the
            # segment sums — float32 is inexact above 2^24 but its ~1e-7
            # relative error is far below the mismatch a wrap produces.
            # Record-time only: the snapshot is immutable, so replay sees
            # the same data.
            wf = self._pushdown_weights(steps, jnp.float32)
            approx = float(summed(wf, jnp.float32(0)))
            exact = int(total_dev)
            if not (
                0 <= approx < 2**31 * 0.99
                and abs(approx - exact) <= max(1e-3 * approx, 1.0)
            ):
                raise Uncompilable(
                    f"COUNT pushdown overflows int32 (≈{approx:.6g} vs {exact})"
                )
        # free observe: the count IS the device scalar result — it sizes no
        # buffer and gates no control flow, so it must not trip overflow
        total = self.sched.observe(total_dev, free=True)
        t = Table(count=int(total), width=0)
        t.count_dev = total_dev
        return t

    def _pass_shape(self, step: PlanStep):
        """A pushed-down step's weight pass: ``(dst alias, edge classes,
        directions)``, the directions as the pass walks them."""
        e = step.edge
        direction = e.item.direction
        if step.reverse:
            direction = _REVERSE_DIR[direction]
        return (
            e.from_alias if step.reverse else e.to_alias,
            self._resolve_edge_classes(e.item),
            ("out", "in") if direction == "both" else (direction,),
        )

    def _const_passes(self, steps: List[PlanStep]) -> int:
        """How many passes at the END of a COUNT's weight chain give the
        same weights on every replay: the pass after the last hop starts
        from all-ones, and a pass whose edge filter and destination mask
        read no dynamic parameter (``uses_params``, observed while the
        predicate compiled; a literal and a static parameter are
        constants of the plan) turns constant weights into constant
        weights. Only over what cannot change under the plan: a
        delta-maintained snapshot patches columns in place between two
        replays, and a mesh's pass has a sharding of its own: there
        every pass stays live (a tiered snapshot has no pushdown)."""
        if self.overlay is not None or self.dg.mesh_graph is not None:
            return 0
        n = 0
        for step in reversed(steps):
            dst, classes, _dirs = self._pass_shape(step)
            f = step.edge.item.edge_filter
            if self._node_masks[dst].uses_params or (
                f is not None
                and f.where is not None
                and any(self._edge_where(c, f.where).uses_params for c in classes)
            ):
                break
            n += 1
        return n

    def _pass_hull(self, step: PlanStep) -> Tuple[int, int]:
        """``[lo, hi)`` outside which a pass's weights are zero: its
        segment sums land on the vertex hulls of its edge classes
        (`ops/device_graph.vertex_hull`), this is their union."""
        _dst, classes, dirs = self._pass_shape(step)
        hulls = [
            dec.hull_out if d == "out" else dec.hull_in
            for dec in (self.dg.edges[c] for c in classes)
            if dec.num_edges
            for d in dirs
        ]
        if not hulls:
            return (0, 0)
        return min(lo for lo, _ in hulls), max(hi for _, hi in hulls)

    def _pushdown_weights(self, steps: List[PlanStep], dtype) -> jnp.ndarray:
        """``w_1`` of the chain of pushed-down ``steps``, built pass by
        pass from the last hop to the first.

        The int32 chain's constant tail (`_const_passes`) is evaluated
        once, at the recording, and kept on the solver trimmed to its
        vertex hull (`plan_consts`); a replay reads it as a jit argument
        (``dg.arrays``, see `_CompiledPlan._arg_subset`), pads it where
        it is read and lowers only the passes before it. The float32
        twin of the overflow guard is recording-only and stays whole.
        Counted where Python lowers the int32 chain (a recording, and
        each trace of a replay): ``plan.count.pass_const`` a pass taken
        from the plan, ``plan.count.pass_live`` a pass lowered."""
        V = self.dg.num_vertices
        vb = K.bucket(max(V, 1))
        mg = self.dg.mesh_graph
        # vertex universe for [vb]-wide node-mask precomputes: the mesh
        # path always needs it; the single-device path uses it whenever
        # the edge list outnumbers the vertices — evaluating a node
        # predicate per EDGE emit re-gathers every referenced column
        # [E]-wide per hop (2-3 extra 80M-row gathers per pass at SF100
        # shape), where a [vb] precompute plus one bool gather does it
        univ = _index_range(self.dg, self.tier, 0, V, vb)
        from contextlib import nullcontext

        from orientdb_tpu.obs.trace import span as _span

        # recording-only spans, like solve_table: replays re-trace this
        # under jax.jit, where a span would time XLA tracing, not work
        rec = self.sched.recording

        def chain(part, w, ends=False):
            for step in reversed(part):
                # one span per PatternEdge hop: the COUNT pushdown fuses all
                # hops into one weight chain, so the honest per-hop timing is
                # each hop's weight-pass build/dispatch
                with _span(
                    "tpu.step", step=step.describe(), stage="count-pushdown"
                ) if rec else nullcontext():
                    w = self._pushdown_weight_step(
                        step, w, univ, mg, vb, dtype, ends
                    )
            return w

        kept = self._const_passes(steps)
        live, const = steps[: len(steps) - kept], steps[len(steps) - kept :]
        w = None  # None ≡ all-ones (the implicit weight after the last hop)
        if const and dtype != jnp.int32:
            w = chain(const, None)  # the float32 twin lowers every pass
        elif const:
            lo, hi = self._pass_hull(const[0])
            if rec:
                self.plan_consts[_COUNT_W] = chain(const, None)[lo:hi]
            # the recording reads what a replay will: a hull that missed
            # a vertex would fail the float32 twin's comparison there
            tail = (self.plan_consts if rec else self.dg.arrays)[_COUNT_W]
            w = jnp.pad(tail, (lo, vb - hi))
        if dtype == jnp.int32:
            metrics.incr_many(
                {
                    "plan.count.pass_const": len(const),
                    "plan.count.pass_live": len(live),
                }
            )
        return chain(live, w, ends=True)

    def _ends_mask(self, alias: str, cname: str, d: str):
        """``alias``'s admission mask over the copies of its columns in
        the order of ``cname`` walked ``d`` (`_FarEnds`), its argument a
        position in that order; ``None`` where a rid filter compares the
        vertex ids themselves. Compiled once a solver."""
        key = (alias, cname, d)
        if key not in self._ends_masks:
            node = self.pattern.nodes[alias]
            dec = self.dg.edges[cname]
            ends = _FarEnds(
                self,
                f"{_ENDS}:{alias}:{cname}:{d}",
                lambda: dec.dst if d == "out" else dec.src,
            )
            self._ends_masks[key] = (
                None
                if any(f.rid is not None for f in node.filters)
                else self._compile_node(node, ends)
            )
        return self._ends_masks[key]

    @jax.named_scope("count.weight_pass")
    def _pushdown_weight_step(self, step, w, univ, mg, vb, dtype, ends=False):
        """One pass of the weight chain: ``w`` pulled back over the
        step's edges and summed by source vertex. With ``ends`` (a pass
        the int32 chain lowers on every replay, and its float32 twin) a
        walk whose hull holds one edge a vertex reads its destination's
        columns from the plan's copies in edge order (`_ends_mask`).
        Counted where Python lowers the int32 chain:
        ``plan.count.ends_sliced`` a pass whose every walk read copies,
        ``plan.count.ends_gathered`` one that evaluated its mask at its
        ends."""
        dst_alias, classes, dirs = self._pass_shape(step)
        node_mask = self._node_masks[dst_alias]
        # the [vb]-wide precompute only pays for itself where a consumer
        # exists: the mesh path always reads it, the single-device path
        # only for classes whose edge list outnumbers the vertices —
        # otherwise the eager recording would evaluate it for nothing
        ok_vec = (
            node_mask(univ)
            if mg is not None
            or any(self.dg.edges[c].num_edges >= vb for c in classes)
            else None
        )
        f = step.edge.item.edge_filter
        new_w = jnp.zeros(vb, dtype)
        sliced: List[bool] = []
        for cname in classes:
            dec = self.dg.edges[cname]
            E = dec.num_edges
            if E == 0:
                continue
            eids = _index_range(self.dg, self.tier, 0, E, E)
            emask = (
                self._edge_where(cname, f.where)(eids, {})
                if (f is not None and f.where is not None)
                else jnp.ones(E, bool)
            )
            for d in dirs:
                # scanning the full out-CSR edge list covers both
                # directions: eid == position for either walk
                if mg is not None:
                    from orientdb_tpu.parallel.mesh_graph import (
                        sharded_weight_pass,
                    )

                    p = mg.edge[cname].prefix
                    src_sh = self.dg.arrays[f"{p}:el:src"]
                    dst_sh = self.dg.arrays[f"{p}:el:dst"]
                    eid_sh = self.dg.arrays[f"{p}:el:eid"]
                    seg_sh, emit_sh = (
                        (src_sh, dst_sh) if d == "out" else (dst_sh, src_sh)
                    )
                    new_w = new_w + sharded_weight_pass(
                        mg.mesh,
                        seg_sh,
                        emit_sh,
                        eid_sh,
                        emask,
                        ok_vec,
                        w if w is not None else jnp.ones(vb, dtype),
                    )
                    continue
                # both CSR orders exist in HBM, so either direction
                # sums via cumsum+boundary-gather (indptr_segment_sum;
                # a slice where the hull holds one edge a vertex)
                # instead of the ~7x-costlier TPU scatter-add; the
                # in-direction reorders the out-order edge mask
                # through the in-CSR's edge-id map first
                if d == "out":
                    emit, ip, hull = dec.dst, dec.indptr_out, dec.hull_out
                    unit, em = dec.unit_out, emask
                else:
                    emit, ip, hull = dec.src, dec.indptr_in, dec.hull_in
                    unit, em = dec.unit_in, jnp.take(emask, dec.edge_id_in)
                far = self._ends_mask(dst_alias, cname, d) if ends and unit else None
                sliced.append(far is not None)
                if far is not None:
                    # one edge a vertex: position e of this order is the
                    # hull's vertex lo + e, and its far end's columns are
                    # the plan's in this order, each read a slice
                    contrib = em & far(K.IndexRange(0, E, E))
                elif E >= vb:
                    # [vb] mask precompute + one bool gather beats
                    # re-evaluating the predicate's column gathers
                    # [E]-wide (see _pushdown_weights)
                    contrib = em & K.take_pad(ok_vec, emit, False)
                else:
                    contrib = em & node_mask(emit)
                vals = contrib.astype(dtype)
                if w is not None:
                    vals = vals * K.take_pad(w, emit, dtype(0))
                new_w = new_w + K.indptr_segment_sum(vals, ip, vb, hull, unit)
        if dtype == jnp.int32:
            metrics.incr(
                "plan.count.ends_sliced"
                if sliced and all(sliced)
                else "plan.count.ends_gathered"
            )
        return new_w

    def _folded_root(self, root: PlanStep):
        """`_root_index` of a root step a COUNT folds, under the
        ``tpu.step`` span and frontier histogram `solve_table` gives a
        materialised root at the recording (where the mask's count is a
        free sync), so PROFILE keeps its line for the step."""
        if not self.sched.recording:
            return self._root_index(root.alias)
        from orientdb_tpu.obs.registry import obs as _obs
        from orientdb_tpu.obs.trace import span as _span

        with _span("tpu.step", step=root.describe(), stage="count-fold") as sp:
            idx, mask = self._root_index(root.alias)
            n = int(K.mask_count(mask))
            sp.set("frontier_rows", n)
        _obs.observe_size("tpu.frontier_rows", n)
        return idx, mask

    def _root_candidates(self, alias: str):
        """The root's admitted vertices, compacted: the indices of
        `_root_index` where its mask holds, sized by the observed count.
        Returns (candidates, host count, device count)."""
        idx, mask = self._root_index(alias)
        cand, n, n_dev = self._compact(mask)
        return K.index_at(idx, cand), n, n_dev

    def _root_index(self, alias: str):
        """Candidate scan for a root alias, restricted to the dense-index
        HULL of its class filters' polymorphic closures — the snapshot
        lays each concrete class out contiguously, so a `{class:Person}`
        root scans |Person|-ish slots instead of all V (the device analog
        of [E] FetchFromClassExecutionStep iterating only the class's
        clusters). Admission masks still run in full (the hull can
        contain foreign vertices). Returns ``(idx, mask)``: the scanned
        indices (a ``K.IndexRange``, or the seed array of an index-seeded
        root) and the admission mask over them."""
        node = self.pattern.nodes[alias]
        if alias in self._root_seeds:
            if self.sched.recording:
                hits = self.compute_seed(alias, self.params)
                cap = max(_cap_of(len(hits)), K.bucket(1))
                self.seed_box.spec[alias] = cap
                arr = np.full(cap, -1, np.int32)
                arr[: len(hits)] = hits
                idx = jnp.asarray(arr)
            else:
                idx = self.seed_box.current[alias]  # [cap] replay input
            return idx, self._node_masks[alias](idx) & (idx >= 0)
        V = self.dg.num_vertices
        start, end = 0, V
        has_class = False
        for f in node.filters:
            if f.class_name:
                has_class = True
                lo, hi = self.snap.vertex_hull(f.class_name)
                start, end = max(start, lo), min(end, hi)
        size = max(end - start, 0)
        # delta-maintained snapshots: inserted vertices land in the
        # append slab OUTSIDE every class hull — scan it as a second
        # segment (class masks stay exact; classless hulls already end
        # at the padded universe and need no extra segment)
        slo, shi = (
            self.snap.slab_vertex_range() if has_class else (0, 0)
        )
        slab = max(shi - slo, 0)
        idx = _index_range(
            self.dg, self.tier, start, size,
            K.bucket(max(size + slab, 1)), slo if slab else 0, slab,
        )
        return idx, self._node_masks[alias](idx)

    def _root(self, table: Table, alias: str) -> Table:
        cand, n, n_dev = self._root_candidates(alias)
        if table.width == 0 and not table.cols:
            t = Table(count=n, width=int(cand.shape[0]))
            t.cols[alias] = cand
            t.count_dev = n_dev
            t.valid = (cand >= 0).astype(jnp.int32)
            return t
        # cartesian product with the existing table. Live rows may be
        # scattered among bucket padding (parts keep full capacity), but
        # the pairing below indexes a contiguous prefix — compact first.
        live = table.valid_device[: table.width].astype(bool)
        keep, packed_n, packed_dev = self._compact(live)
        table = table.gather(keep)
        table.count = packed_n
        table.count_dev = packed_dev
        # The pairing stride is the RECORDED new_n, so a parameter-generic
        # replay is only valid when both cardinalities match the recording
        # exactly — require it (single-component patterns, i.e. everything
        # without a cartesian, stay fully parameter-generic).
        old_n, new_n = table.count, n
        old_dev = table.count_device
        sched = self.sched
        if not sched.recording:
            flag = (old_dev != old_n) | (n_dev != new_n)
            sched.overflow = (
                flag if sched.overflow is None else (sched.overflow | flag)
            )
        total = old_n * new_n
        width = K.bucket(max(total, 1))
        pos = jnp.arange(width, dtype=jnp.int32)
        valid = pos < total
        if new_n == 0:
            rows = jnp.full(width, -1, jnp.int32)
            t = table.gather(rows)
            t.count = 0
            t.count_dev = jnp.int32(0)
            t.cols[alias] = rows
            return t
        rows = jnp.where(valid, pos // new_n, -1)
        sel = jnp.where(valid, pos % new_n, -1)
        t = table.gather(rows)
        t.count = total
        t.count_dev = old_dev * n_dev
        t.cols[alias] = K.take_pad(cand, sel, jnp.int32(-1))
        return t

    def _resolve_edge_classes(self, item: A.MatchPathItem) -> List[str]:
        """Concrete edge classes for a path item, with the edge-filter's
        class restriction applied as a host-side subclass check."""
        names = item.edge_classes or (None,)
        concrete: List[str] = []
        for nm in names:
            concrete.extend(self.snap.concrete_edge_classes(nm))
        f = item.edge_filter
        if f is not None and f.class_name:
            keep = []
            for c in concrete:
                cls = self.db.schema.get_class(c)
                if cls is not None and cls.is_subclass_of(f.class_name):
                    keep.append(c)
            concrete = keep
        return concrete

    def _expand(self, table: Table, step: PlanStep, optional: bool) -> Table:
        e = step.edge
        item = e.item
        if item.target.while_cond is not None or item.target.max_depth is not None:
            return self._expand_var_depth(table, step, optional)
        m = (item.method or "").lower()
        if m in ("oute", "ine", "bothe") and item.edge_filter is None:
            return self._expand_bind_edge(table, step, optional)
        if m in ("outv", "inv", "bothv"):
            return self._expand_edge_endpoint(table, step, optional, m)
        direction = item.direction
        reverse = step.reverse
        if reverse:
            direction = _REVERSE_DIR[direction]
        src_alias = e.to_alias if reverse else e.from_alias
        dst_alias = e.from_alias if reverse else e.to_alias
        dst_node = self.pattern.nodes[dst_alias]
        srcs = table.cols.get(src_alias)
        if srcs is None:
            raise Uncompilable(f"alias {src_alias} not bound before expansion")
        concrete = self._resolve_edge_classes(item)
        f = item.edge_filter
        sub_dirs = ("out", "in") if direction == "both" else (direction,)
        parts: List[Table] = []
        counts: List[int] = []
        matched_any = jnp.zeros(table.width or 1, jnp.int32)
        visible = self._step_visible.get(id(step), set())
        node_mask = self._node_masks[dst_alias]
        node_uses = getattr(node_mask, "uses_bindings", False)
        for cname in concrete:
            dec = self.dg.edges[cname]
            where_fn = (
                self._edge_where(cname, f.where, visible)
                if (f is not None and f.where is not None)
                else None
            )
            edge_uses = where_fn is not None and getattr(
                where_fn, "uses_bindings", False
            )
            for d in sub_dirs:
                for row, eid, nbr, total in self._expand_one_dir_chunked(
                    dec, d, srcs
                ):
                    if total == 0:
                        continue
                    env = {}
                    if node_uses or edge_uses:
                        env = self._binding_env(table, row, visible)
                    mask = row >= 0
                    if where_fn is not None:
                        mask = mask & where_fn(eid, env)
                    # destination node admission; close steps skip a
                    # binding-referencing re-check (the oracle doesn't re-run
                    # node filters when closing onto an already-bound alias,
                    # and the visibility set at first bind differs)
                    if not (step.close and node_uses):
                        mask = mask & node_mask(nbr, env)
                    if step.close:
                        bound = K.take_pad(table.cols[dst_alias], row, jnp.int32(-2))
                        mask = mask & (nbr == bound)
                    if optional:
                        matched_any = matched_any + K.rows_with_matches(
                            row, mask, table.width or 1
                        )
                    keep, kn, kn_dev = self._compact(mask)
                    if kn == 0:
                        continue
                    krow = K.take_pad(row, keep, jnp.int32(-1))
                    part = table.gather(krow)
                    part.count = kn
                    part.count_dev = kn_dev
                    part.cols[dst_alias] = K.take_pad(nbr, keep, jnp.int32(-1))
                    ecls_idx = self.edge_class_idx[cname]
                    keid = K.take_pad(eid, keep, jnp.int32(-1))
                    self._bind_edge_alias(part, item, ecls_idx, keid)
                    if item.target.depth_alias:
                        part.depth_cols[item.target.depth_alias] = jnp.where(
                            part.cols[dst_alias] >= 0, 1, -1
                        )
                    parts.append(part)
                    counts.append(kn)
        if optional:
            # left-join: rows with zero matches keep their binding, dst=null.
            # Liveness comes from the device valid mask, not the recorded
            # host count — a parameter-generic replay can have live rows
            # anywhere under the recorded capacity.
            matched = matched_any[: table.width] > 0 if table.width else matched_any[:0]
            valid_rows = table.valid_device[: table.width].astype(bool)
            unmatched = valid_rows & ~matched
            ukeep, un, un_dev = self._compact(unmatched)
            if un > 0:
                upart = table.gather(ukeep)
                upart.count = un
                upart.count_dev = un_dev
                null_col = jnp.full(upart.width, -1, jnp.int32)
                arm_opt = item.edge_filter is not None and item.edge_filter.optional
                if step.close and arm_opt:
                    # arm-optional probe between two bound aliases: both
                    # endpoints survive; only the edge alias binds null
                    pass
                elif step.close:
                    # oracle: null src uses setdefault (keeps the bound dst);
                    # non-null src with no match explicitly nulls it
                    src_g = K.take_pad(srcs, ukeep, jnp.int32(-1))
                    upart.cols[dst_alias] = jnp.where(
                        src_g < 0, upart.cols[dst_alias], -1
                    )
                else:
                    upart.cols[dst_alias] = null_col
                self._bind_edge_alias(upart, item, -1, null_col)
                if item.target.depth_alias:
                    upart.depth_cols[item.target.depth_alias] = null_col
                parts.append(upart)
                counts.append(un)
        if not parts:
            # preserve column structure for downstream steps
            t = table.gather(jnp.full(K.bucket(1), -1, jnp.int32))
            t.count = 0
            t.count_dev = jnp.int32(0)
            t.cols[dst_alias] = jnp.full(t.width, -1, jnp.int32)
            self._bind_edge_alias(t, item, -1, jnp.full(t.width, -1, jnp.int32))
            if item.target.depth_alias:
                t.depth_cols[item.target.depth_alias] = jnp.full(
                    t.width, -1, jnp.int32
                )
            return t
        return _concat_tables(parts, counts)

    # -- method-form arms ---------------------------------------------------

    def _expand_bind_edge(self, table: Table, step: PlanStep, optional: bool) -> Table:
        """Bare ``.outE('EC'){as:e}``: the target alias binds the EDGE
        ([E] MatchFieldTraverser's edge-step). Expansion slots carry the
        global edge id; target-filter class/where apply to the edge."""
        e = step.edge
        item = e.item
        if step.reverse:
            raise Uncompilable("reverse edge-binding arm")
        # mesh path: _expand_one_dir shards the expansion transparently
        # (global edge ids out), edge-property WHERE reads row-sharded
        # columns in jit global view — nothing here is single-chip-only
        src_alias, dst_alias = e.from_alias, e.to_alias
        srcs = table.cols.get(src_alias)
        if srcs is None:
            raise Uncompilable(f"alias {src_alias} not bound before expansion")
        dst_node = self.pattern.nodes[dst_alias]
        tgt_classes = [f.class_name for f in dst_node.filters if f.class_name]
        tgt_wheres = [f.where for f in dst_node.filters if f.where is not None]
        concrete = self._resolve_edge_classes(item)
        for tc in tgt_classes:
            concrete = [
                c
                for c in concrete
                if (cl := self.db.schema.get_class(c)) is not None
                and cl.is_subclass_of(tc)
            ]
        visible = self._step_visible.get(id(step), set())
        sub_dirs = (
            ("out", "in") if item.direction == "both" else (item.direction,)
        )
        parts: List[Table] = []
        counts: List[int] = []
        width = table.width or 1
        matched_any = jnp.zeros(width, jnp.int32)
        for cname in concrete:
            dec = self.dg.edges[cname]
            where_fns = [self._edge_where(cname, w, visible) for w in tgt_wheres]
            uses = any(getattr(f, "uses_bindings", False) for f in where_fns)
            for d in sub_dirs:
                row, eid, nbr, total = self._expand_one_dir(dec, d, srcs)
                if total == 0:
                    continue
                env = {}
                if uses:
                    env = self._binding_env(table, row, visible)
                mask = (row >= 0) & (eid >= 0)
                for fn in where_fns:
                    mask = mask & fn(eid, env)
                ecls_idx = self.edge_class_idx[cname]
                if step.close:
                    bci, beid = table.edge_cols[dst_alias]
                    mask = mask & (
                        K.take_pad(bci, row, jnp.int32(-2)) == ecls_idx
                    ) & (K.take_pad(beid, row, jnp.int32(-2)) == eid)
                if optional:
                    matched_any = matched_any + K.rows_with_matches(
                        row, mask, width
                    )
                keep, kn, kn_dev = self._compact(mask)
                if kn == 0:
                    continue
                krow = K.take_pad(row, keep, jnp.int32(-1))
                part = table.gather(krow)
                part.count = kn
                part.count_dev = kn_dev
                keid = K.take_pad(eid, keep, jnp.int32(-1))
                part.edge_cols[dst_alias] = (
                    jnp.where(keid >= 0, ecls_idx, -1),
                    keid,
                )
                parts.append(part)
                counts.append(kn)
        if optional:
            matched = matched_any[:width] > 0
            unmatched = table.valid_device[:width].astype(bool) & ~matched
            ukeep, un, un_dev = self._compact(unmatched)
            if un > 0:
                upart = table.gather(ukeep)
                upart.count = un
                upart.count_dev = un_dev
                null_col = jnp.full(upart.width, -1, jnp.int32)
                if not step.close:
                    upart.edge_cols[dst_alias] = (null_col, null_col)
                parts.append(upart)
                counts.append(un)
        if not parts:
            t = table.gather(jnp.full(K.bucket(1), -1, jnp.int32))
            t.count = 0
            t.count_dev = jnp.int32(0)
            null_col = jnp.full(t.width, -1, jnp.int32)
            t.edge_cols[dst_alias] = (null_col, null_col)
            return t
        return _concat_tables(parts, counts)

    def _expand_edge_endpoint(
        self, table: Table, step: PlanStep, optional: bool, m: str
    ) -> Table:
        """``.outV()/.inV()/.bothV()`` from a bound edge alias to its
        endpoint vertex: a 1:1 (or 1:2 for bothV) per-row gather through
        the edge-id columns — no fan-out expansion."""
        e = step.edge
        item = e.item
        if step.reverse:
            raise Uncompilable("reverse endpoint arm")
        src_alias, dst_alias = e.from_alias, e.to_alias
        ecols = table.edge_cols.get(src_alias)
        if ecols is None:
            raise Uncompilable(f"edge alias {src_alias} not bound before endpoint step")
        ci, eid = ecols
        width = table.width or 1
        node_mask = self._node_masks[dst_alias]
        node_uses = getattr(node_mask, "uses_bindings", False)
        env = {}
        if node_uses:
            visible = self._step_visible.get(id(step), set())
            env = self._binding_env(table, None, visible)
        kinds = {"outv": ("src",), "inv": ("dst",), "bothv": ("src", "dst")}[m]
        live = table.valid_device[:width].astype(bool)
        parts: List[Table] = []
        counts: List[int] = []
        matched_any = jnp.zeros(width, bool)
        mg = self.dg.mesh_graph
        for kind in kinds:
            cand = jnp.full(width, -1, jnp.int32)
            for k, cname in enumerate(self.edge_class_list):
                dec = self.dg.edges[cname]
                if dec.num_edges == 0:
                    continue
                if mg is None:
                    arr = dec.edge_src if kind == "src" else dec.dst
                else:
                    # mesh: the flat per-edge endpoint arrays are not
                    # uploaded; the shard-blocked edge list IS the flat
                    # array row-blocked by shard (mesh_graph upload), so
                    # a global-view reshape recovers endpoint-by-global-
                    # eid gathers (XLA inserts the collectives)
                    p = mg.edge[cname].prefix
                    key = f"{p}:el:src" if kind == "src" else f"{p}:el:dst"
                    arr = self.dg.arrays[key].reshape(-1)
                g = K.take_pad(arr, jnp.where(ci == k, eid, -1), jnp.int32(-1))
                cand = jnp.where(ci == k, g, cand)
            mask = live & (cand >= 0) & node_mask(cand, env)
            if step.close:
                mask = mask & (cand == table.cols[dst_alias])
            matched_any = matched_any | mask
            keep, kn, kn_dev = self._compact(mask)
            if kn == 0:
                continue
            part = table.gather(keep)
            part.count = kn
            part.count_dev = kn_dev
            part.cols[dst_alias] = K.take_pad(cand, keep, jnp.int32(-1))
            parts.append(part)
            counts.append(kn)
        if optional:
            unmatched = live & ~matched_any
            ukeep, un, un_dev = self._compact(unmatched)
            if un > 0:
                upart = table.gather(ukeep)
                upart.count = un
                upart.count_dev = un_dev
                if not step.close:
                    upart.cols[dst_alias] = jnp.full(upart.width, -1, jnp.int32)
                parts.append(upart)
                counts.append(un)
        if not parts:
            t = table.gather(jnp.full(K.bucket(1), -1, jnp.int32))
            t.count = 0
            t.count_dev = jnp.int32(0)
            t.cols[dst_alias] = jnp.full(t.width, -1, jnp.int32)
            return t
        return _concat_tables(parts, counts)

    # -- variable-depth (WHILE / maxDepth) expansion ------------------------

    _VAR_DEPTH_CHUNK = 256

    @staticmethod
    def _var_chunk_rows(width: int, vb: int) -> int:
        """Rows per frontier-bitmap chunk: no wider than the (bucketed)
        binding table — a point lookup walks 8-row bitmaps, not 256 — and
        capped so one [rows, bucket(V)] bool chunk stays inside
        config.var_depth_bitmap_budget bytes at SF100-scale V."""
        budget_rows = max(1, config.var_depth_bitmap_budget // max(vb, 1))
        return max(1, min(TpuMatchSolver._VAR_DEPTH_CHUNK, width, budget_rows))

    def _expand_var_depth(
        self,
        table: Table,
        step: PlanStep,
        optional: bool,
        count_only: bool = False,
    ) -> Table:
        """Breadth-wise frontier iteration with per-row visited bitmaps —
        the SURVEY §5.7 design for the reference's per-record WHILE-DFS
        ([E] OWhileMatchPathItem): emit the origin at depth 0, then one
        bitmap hop per level, gating expansion with the WHILE mask at the
        level's $depth and stopping at maxDepth / frontier exhaustion.
        Depths are minimum-discovery depths (the oracle's BFS semantics).

        ``count_only`` is the var-depth COUNT pushdown (`_var_count_step`):
        a terminal WHILE arm under a lone COUNT(*) contributes
        popcount(level emission) per level instead of materialized binding
        rows — no compactions, no gathers, no per-level size observes, and
        the result table is just the device scalar.
        """
        e = step.edge
        item = e.item
        direction = item.direction
        reverse = step.reverse
        if reverse:
            direction = _REVERSE_DIR[direction]
        src_alias = e.to_alias if reverse else e.from_alias
        dst_alias = e.from_alias if reverse else e.to_alias
        srcs = table.cols.get(src_alias)
        if srcs is None:
            raise Uncompilable(f"alias {src_alias} not bound before expansion")
        max_depth = item.target.max_depth
        while_fn = self._while_fns.get(id(e))
        depth_alias = item.target.depth_alias
        V = self.dg.num_vertices
        vb = K.bucket(max(V, 1))
        univ = _index_range(self.dg, self.tier, 0, V, vb)
        node_mask_vec = self._node_masks[dst_alias](univ)  # [vb]
        # per-(class, dir) edge hop closures; edge WHERE fused as edge masks
        f = item.edge_filter
        items = []
        for cname in self._resolve_edge_classes(item):
            dec = self.dg.edges[cname]
            emask = None
            if f is not None and f.where is not None:
                eids = _index_range(
                    self.dg, self.tier, 0, dec.num_edges, dec.num_edges
                )
                emask = self._edge_where(cname, f.where)(eids, {})
            for d in ("out", "in") if direction == "both" else (direction,):
                items.append((cname, d, emask))
        hops = build_bitmap_hops(
            self.dg, items, sched=self.sched, tier=self.tier,
            touched=self.tier_touched,
        )
        parts: List[Table] = []
        counts: List[int] = []
        width = table.width or 1
        matched_chunks = []
        total_dev = jnp.int32(0)  # count_only accumulators (+ f32 twin
        totalf_dev = jnp.float32(0.0)  # for the int32 wrap guard)
        C = self._var_chunk_rows(width, vb)
        # chunk over the bucketed WIDTH (not the recorded count): on a
        # parameter-generic replay live rows can occupy any slot under the
        # recorded capacity, and the per-slot valid mask (not a host count)
        # decides liveness
        valid_dev = table.valid_device
        for cs in range(0, width, C):
            chunk_rows = jnp.arange(cs, cs + C, dtype=jnp.int32)
            # take_pad clips (rather than fills) indices past the end, so
            # out-of-width slots must be sent negative explicitly
            in_range = jnp.where(chunk_rows < valid_dev.shape[0], chunk_rows, -1)
            chunk_valid = K.take_pad(valid_dev, in_range, jnp.int32(0)) > 0
            chunk_rows = jnp.where(chunk_valid, chunk_rows, -1)
            src_chunk = K.take_pad(srcs, chunk_rows, jnp.int32(-1))
            roots = K.rows_to_bitmap(src_chunk, vb)
            bound_chunk = None
            if step.close:
                bound_chunk = K.take_pad(
                    table.cols[dst_alias], chunk_rows, jnp.int32(-2)
                )
            matched = jnp.zeros(C, bool)

            def emit_level(reached, depth):
                nonlocal total_dev, totalf_dev
                if not count_only:
                    return self._emit_var_level(
                        table, reached, node_mask_vec, bound_chunk, cs,
                        depth, dst_alias, depth_alias, vb, parts, counts,
                    )
                emit = _var_emit_mask(reached, node_mask_vec, bound_chunk, vb)
                total_dev = total_dev + jnp.sum(emit, dtype=jnp.int32)
                totalf_dev = totalf_dev + jnp.sum(emit, dtype=jnp.float32)
                return matched  # unused in count mode (never optional)

            visited = roots
            frontier = roots
            depth = 0
            # emit the origin at depth 0
            matched = matched | emit_level(roots, depth)
            # level loop with PADDED trailing levels: recording runs
            # `var_depth_pad_levels` extra (empty) levels past frontier
            # exhaustion and keeps min-capacity emissions at every level,
            # so a replay whose walk is up to `pad` levels deeper — depth
            # varies with the query parameter — executes in place instead
            # of re-recording. The alive observes are free (the loop's
            # trip count replays from the schedule); the post-loop
            # structural observe flags replays needing even deeper walks.
            pad = max(1, config.var_depth_pad_levels)
            empty_streak = 0
            ended_by_bound = False
            while True:
                if max_depth is not None and depth >= max_depth:
                    ended_by_bound = True
                    break
                expandable = frontier
                if while_fn is not None:
                    gate = while_fn(univ, {"depth": depth})
                    expandable = expandable & gate[None, :]
                nxt = jnp.zeros_like(frontier)
                for hop in hops:
                    nxt = nxt | hop(expandable)
                nxt = nxt & ~visited
                alive = self.sched.observe(K.mask_count(nxt), free=True)
                empty_streak = empty_streak + 1 if alive == 0 else 0
                visited = visited | nxt
                depth += 1
                matched = matched | emit_level(nxt, depth)
                frontier = nxt
                if empty_streak >= pad:
                    break
                if depth > V:  # safety: no graph has longer shortest paths
                    ended_by_bound = True
                    break
            if not ended_by_bound:
                # exhaustion-ended: a replay still alive here needs more
                # levels than recorded+pad → overflow (recorded value is 0)
                self.sched.observe(K.mask_count(frontier))
            matched_chunks.append(matched)
        if count_only:
            if self.sched.recording:
                approx = float(totalf_dev)
                exact = int(total_dev)
                if not (
                    0 <= approx < 2**31 * 0.99
                    and abs(approx - exact) <= max(1e-3 * approx, 1.0)
                ):
                    raise Uncompilable(
                        f"var-depth COUNT overflows int32 (≈{approx:.6g})"
                    )
            # free observe: the count IS the result (see _apply_count_pushdown)
            total = self.sched.observe(total_dev, free=True)
            t = Table(count=int(total), width=0)
            t.count_dev = total_dev
            return t
        if optional:
            matched_all = jnp.concatenate(matched_chunks)[:width]
            if matched_all.shape[0] < width:
                matched_all = jnp.concatenate(
                    [
                        matched_all,
                        jnp.zeros(width - matched_all.shape[0], bool),
                    ]
                )
            unmatched = valid_dev[:width].astype(bool) & ~matched_all
            ukeep, un, un_dev = self._compact(unmatched)
            if un > 0:
                upart = table.gather(ukeep)
                upart.count = un
                upart.count_dev = un_dev
                null_col = jnp.full(upart.width, -1, jnp.int32)
                arm_opt = item.edge_filter is not None and item.edge_filter.optional
                if step.close and arm_opt:
                    pass  # arm-optional probe: endpoints survive (see _expand)
                elif step.close:
                    src_g = K.take_pad(srcs, ukeep, jnp.int32(-1))
                    upart.cols[dst_alias] = jnp.where(
                        src_g < 0, upart.cols[dst_alias], -1
                    )
                else:
                    upart.cols[dst_alias] = null_col
                if depth_alias:
                    upart.depth_cols[depth_alias] = null_col
                parts.append(upart)
                counts.append(un)
        if not parts:
            t = table.gather(jnp.full(K.bucket(1), -1, jnp.int32))
            t.count = 0
            t.count_dev = jnp.int32(0)
            t.cols[dst_alias] = jnp.full(t.width, -1, jnp.int32)
            if depth_alias:
                t.depth_cols[depth_alias] = jnp.full(t.width, -1, jnp.int32)
            return t
        return _concat_tables(parts, counts)

    def _emit_var_level(
        self,
        table: Table,
        reached: jnp.ndarray,
        node_mask_vec: jnp.ndarray,
        bound_chunk,
        cs: int,
        depth: int,
        dst_alias: str,
        depth_alias,
        vb: int,
        parts: List[Table],
        counts: List[int],
    ) -> jnp.ndarray:
        """Emit one BFS level's (row, vertex, depth) bindings; returns the
        per-chunk-row matched mask (for OPTIONAL bookkeeping).

        Levels whose recorded emission is EMPTY still append a
        min-capacity part: parameter-generic replays can emit up to that
        capacity at any level (incl. the padded post-exhaustion ones)
        without re-recording."""
        emit = _var_emit_mask(reached, node_mask_vec, bound_chunk, vb)
        matched = emit.any(axis=1)
        flat = emit.reshape(-1)
        keep, kn, kn_dev = _observe_compact(self.sched, flat, min_capacity=K.bucket(0))
        ok = keep >= 0
        c = jnp.where(ok, keep // vb, -1)
        v = jnp.where(ok, keep % vb, -1)
        rowid = jnp.where(ok, cs + c, -1)
        part = table.gather(rowid)
        part.count = kn
        part.count_dev = kn_dev
        part.cols[dst_alias] = v
        if depth_alias:
            part.depth_cols[depth_alias] = jnp.where(ok, depth, -1)
        parts.append(part)
        counts.append(kn)
        return matched

    def _bind_edge_alias(self, part: Table, item: A.MatchPathItem, ecls_idx, eid):
        f = item.edge_filter
        if f is not None and f.alias:
            if isinstance(ecls_idx, int):
                ci = jnp.where(eid >= 0, ecls_idx, -1)
            else:
                ci = ecls_idx
            part.edge_cols[f.alias] = (ci, eid)

    # -- marshalling --------------------------------------------------------

    @staticmethod
    def _live_rows(table: Table):
        """Row selector for marshalling: tables carry live rows scattered
        among bucket padding (the valid mask is authoritative); tables
        without a mask are contiguous-prefix (host-rebuilt ones)."""
        if table.valid is None:
            return slice(0, table.count)
        return np.flatnonzero(np.asarray(table.valid) > 0)

    def bindings_from_table(self, table: Table) -> List[Dict[str, object]]:
        sel = self._live_rows(table)
        cols = {a: np.asarray(c)[sel] for a, c in table.cols.items()}
        ecols = {
            a: (np.asarray(ci)[sel], np.asarray(pos)[sel])
            for a, (ci, pos) in table.edge_cols.items()
        }
        dcols = {a: np.asarray(c)[sel] for a, c in table.depth_cols.items()}
        n = next(iter(cols.values())).shape[0] if cols else (
            next(iter(ecols.values()))[0].shape[0] if ecols else table.count
        )
        # aliases that never hit a table column (fully detached optional
        # arms) marshal as None
        missing = [
            a
            for a in self.pattern.nodes
            if a not in cols and a not in ecols
        ]
        out: List[Dict[str, object]] = []
        doc_cache: Dict[int, object] = {}
        edge_cache: Dict[Tuple[int, int], object] = {}
        for i in range(n):
            b: Dict[str, object] = {}
            for a, arr in cols.items():
                v = int(arr[i])
                if v < 0:
                    b[a] = None
                else:
                    doc = doc_cache.get(v)
                    if doc is None:
                        doc = self.db.load(self.snap.rid_of(v))
                        doc_cache[v] = doc
                    b[a] = doc
            for a, (ci, pos) in ecols.items():
                c, p = int(ci[i]), int(pos[i])
                if c < 0 or p < 0:
                    b[a] = None
                else:
                    ed = edge_cache.get((c, p))
                    if ed is None:
                        rid = self.snap.edge_classes[self.edge_class_list[c]].edge_rids[p]
                        ed = self.db.load(rid)
                        edge_cache[(c, p)] = ed
                    b[a] = ed
            for a, arr in dcols.items():
                v = int(arr[i])
                b[a] = None if v < 0 else v
            for a in missing:
                b[a] = None
            out.append(b)
        return out

    def rows_from_table(self, table: Table, params: Optional[Dict] = None) -> List[Result]:
        params = self.params if params is None else params
        if self._path_lens:
            self._count_path_lens(table)
        fast = self._fast_rows(table, params)
        if fast is not None:
            return fast
        named = [
            n.alias for n in self.pattern.nodes.values() if not n.anonymous
        ]
        rows = match_rows_from_bindings(
            self.db,
            self.stmt,
            named,
            self.bindings_from_table(table),
            params,
            None,
        )
        if self.element_alias is not None:
            # rewritten whole-record SELECT: the finalize tail (ORDER/
            # SKIP/LIMIT) ran on the props rows; unwrap to element rows
            rows = [
                Result(element=r.get_property(self.element_alias))
                for r in rows
            ]
        return rows

    # -- columnar fast RETURN path -----------------------------------------

    def count_only_name(self) -> Optional[str]:
        """Projection name when RETURN is a lone COUNT(*) (no grouping)."""
        stmt = self.stmt
        if stmt.group_by or stmt.unwind:
            return None
        r = stmt.returns
        if (
            len(r) == 1
            and isinstance(r[0].expr, A.FunctionCall)
            and r[0].expr.name.lower() == "count"
            and len(r[0].expr.args) == 1
            and isinstance(r[0].expr.args[0], A.Star)
        ):
            from orientdb_tpu.exec.oracle import expr_name

            return r[0].alias or expr_name(r[0].expr, 0)
        return None

    def finalize_count(
        self, name: str, count: int, params: Optional[Dict] = None
    ) -> List[Result]:
        # aggregate path applies only ORDER/SKIP/LIMIT (no DISTINCT)
        params = self.params if params is None else params
        out = [Result(props={name: count})]
        out = _order_rows(out, self.stmt.order_by, self.db, params, None)
        base_ctx = EvalContext(self.db, params=params)
        return _skip_limit(out, self.stmt.skip, self.stmt.limit, base_ctx)

    def _fast_rows(
        self, table: Table, params: Optional[Dict] = None
    ) -> Optional[List[Result]]:
        """Build result rows straight from device columns when RETURN is a
        count(*) or plain columnar projections — skipping per-row Document
        loads entirely (the [E] OResultInternal marshalling cost the north
        star calls out). Returns None when ineligible (shared slow path)."""
        stmt = self.stmt
        if stmt.group_by or stmt.unwind:
            return None
        returns = stmt.returns
        if len(returns) == 1 and isinstance(returns[0].expr, A.ContextVar):
            return None  # $matches/$paths/$elements need Documents
        # lone COUNT(*) → O(1): the table's valid row count
        name = self.count_only_name()
        if name is not None:
            return self.finalize_count(name, table.count, params)
        # plain columnar projections: alias.prop / depth aliases
        from orientdb_tpu.exec.eval import contains_aggregate

        if any(contains_aggregate(p.expr) for p in returns):
            return None
        plans = []  # (name, values np | None, present np | None, decode)
        sel = self._live_rows(table)
        n = table.count if isinstance(sel, slice) else int(sel.shape[0])
        for i, p in enumerate(returns):
            e = p.expr
            name = p.alias or _match_proj_name(e, i)
            if isinstance(e, A.Identifier) and e.name in table.depth_cols:
                arr = np.asarray(table.depth_cols[e.name])[sel]
                plans.append((name, arr, arr >= 0, None))
                continue
            if self._path_lens and e in self._path_lens:
                found = table.depth_cols.get(self._path_lens[e][0] + ".len")
                if found is None:  # the recording had no row to search
                    return None
                arr = np.asarray(found)[sel]
                plans.append((name, arr, np.ones(arr.shape, bool), None))
                continue
            if (
                isinstance(e, A.FieldAccess)
                and isinstance(e.base, A.Identifier)
                and e.base.name in table.cols
            ):
                prop = e.name
                if prop in self.dg.non_columnar or prop.startswith("@"):
                    return None
                idx = np.asarray(table.cols[e.base.name])[sel]
                col = self.snap.v_columns.get(prop)
                if col is None:
                    plans.append((name, None, None, None))  # never present
                    continue
                ci = np.clip(idx, 0, max(len(col.values) - 1, 0))
                vals = col.values[ci]
                pres = col.present[ci] & (idx >= 0)
                plans.append((name, vals, pres, col))
                continue
            return None
        # vectorized decode: one object column per projection (no per-value
        # Python decode calls — this loop runs per result row otherwise)
        names = []
        obj_cols = []
        for name, vals, pres, col in plans:
            names.append(name)
            if vals is None:
                obj_cols.append(np.full(n, None, object))
                continue
            if col is None:  # depth alias: plain ints
                o = vals.astype(object)
            elif col.kind == "str":
                d = col.dict_array()
                o = d[np.clip(vals, 0, len(d) - 1)]
            elif col.kind == "bool":
                o = (vals != 0).astype(object)
            elif col.kind == "float":
                o = vals.astype(float).astype(object)
            else:
                o = vals.astype(object)
            o[~pres] = None
            obj_cols.append(o)
        if not (stmt.distinct or stmt.order_by or stmt.skip or stmt.limit):
            # (unwind already bailed at the top of this function)
            # finalize tail is identity → hand the columns over whole; the
            # ResultSet serializes them in bulk without per-row Results
            return ColumnarRows(names, [c.tolist() for c in obj_cols], n)
        out = [
            Result(props=dict(zip(names, vals_row)))
            for vals_row in zip(*obj_cols)
        ] if obj_cols else [Result(props={}) for _ in range(n)]
        return finalize_match_rows(self.db, stmt, out, params or self.params, None)


# ---------------------------------------------------------------------------
# TRAVERSE compilation
# ---------------------------------------------------------------------------


class TpuTraverseSolver:
    """Compiled TRAVERSE: bitmap-BFS levels over the device CSR.

    The reference walks TRAVERSE per-record with a visited set ([E]
    OTraverseStatement → Depth/BreadthFirstTraverseStep, SURVEY.md §1
    layer 5); here each level is ONE frontier bitmap hop over the whole
    graph (psum-OR merged across mesh shards when sharded), with
    MAXDEPTH / WHILE($depth, fields) applied as level masks.

    Semantics vs the oracle (`oracle.execute_traverse`):
    - BREADTH_FIRST pops FIFO, so every record is admitted at its minimum
      discovery depth — exactly what level-wise bitmap BFS computes; the
      result SET matches the oracle, while within-level order is vertex
      index order (the oracle's is parent-enumeration order; TRAVERSE
      order within a level is enumeration-defined in the reference too).
    - DEPTH_FIRST admits records at possibly non-minimal depths, so it
      compiles only when no MAXDEPTH/WHILE can observe the difference —
      then the result set is the plain reachability closure.
    - LIMIT slices in traversal order → always falls back to the oracle.

    Fields compile for out()/in()/both() with literal class names (or
    none); '*' / outE/inE/bothE / link fields emit edge documents and
    fall back.
    """

    def __init__(self, db, stmt: A.TraverseStatement, params: Dict) -> None:
        self.db = db
        self.stmt = stmt
        self.params = params or {}
        snap = db.current_snapshot(require_fresh=True)
        if snap is None:
            raise Uncompilable("no fresh snapshot attached")
        self.snap = snap
        self.dg: DeviceGraph = device_graph(snap)
        self.overlay = getattr(snap, "_overlay", None)
        self.delta_gen = (
            self.overlay.plan_gen if self.overlay is not None else 0
        )
        #: hot/cold tier manager (storage/tiering) when the snapshot's
        #: adjacency exceeds the HBM cap; the recording run accumulates
        #: every faulted block into tier_touched — frozen at plan
        #: construction as the plan's dispatch-prefetch footprint
        self.tier = getattr(snap, "_tier", None)
        self.tier_touched: set = set()
        #: TRAVERSE replays are fully static — the roots array is baked
        #: at record time and the schedule's overflow flag is dropped
        #: (sound on immutable snapshots, where replay inputs are
        #: identical by construction). On a delta-maintained snapshot
        #: the plan therefore pins the overlay's data version and
        #: re-records when ANY delta has landed since (dispatch checks).
        self.delta_data_version = (
            self.overlay.data_version if self.overlay is not None else 0
        )
        self.sched = SizeSchedule()
        if stmt.limit is not None:
            raise Uncompilable("TRAVERSE LIMIT slices in traversal order")
        if stmt.strategy == "DEPTH_FIRST" and (
            stmt.max_depth is not None or stmt.while_cond is not None
        ):
            raise Uncompilable(
                "DEPTH_FIRST with MAXDEPTH/WHILE admits at non-minimal depths"
            )
        self.hop_items = self._compile_fields(stmt.fields)
        self.while_fn = None
        if stmt.while_cond is not None:
            scope = ColumnScope(self.dg.columns, self.dg.non_columnar)
            self.while_fn = compile_predicate(
                stmt.while_cond, scope, self.params, allow_depth=True
            )
        self.roots = self._resolve_roots()

    def _compile_fields(self, fields) -> List[Tuple[str, str, None]]:
        dirs: List[Tuple[str, Optional[str]]] = []
        if not fields:
            raise Uncompilable("TRAVERSE * follows edges as records")
        for f in fields:
            if not isinstance(f, A.FunctionCall):
                raise Uncompilable("TRAVERSE field is not out()/in()/both()")
            name = f.name.lower()
            if name not in ("out", "in", "both"):
                raise Uncompilable(f"TRAVERSE {name}() emits non-vertex records")
            classes: List[Optional[str]] = []
            if not f.args:
                classes.append(None)
            for a in f.args:
                if not (isinstance(a, A.Literal) and isinstance(a.value, str)):
                    raise Uncompilable("non-literal edge class in TRAVERSE field")
                classes.append(a.value)
            for cls in classes:
                dirs.append((name, cls))
        items = []
        for direction, cls in dirs:
            for cname in self.snap.concrete_edge_classes(cls):
                for d in ("out", "in") if direction == "both" else (direction,):
                    items.append((cname, d, None))
        return items

    def _resolve_roots(self) -> np.ndarray:
        """Root record → dense vertex indices, via the oracle's target
        resolution (host-side; supports class / rid / subquery targets)."""
        from orientdb_tpu.exec.oracle import resolve_target_rows

        base_ctx = EvalContext(self.db, params=self.params)
        idxs: List[int] = []
        for row in resolve_target_rows(self.db, self.stmt.target, base_ctx):
            doc = row if isinstance(row, Document) else (
                row.element if isinstance(row, Result) else None
            )
            if doc is None:
                continue
            i = self.snap.idx_of(doc.rid)
            if i is None:
                raise Uncompilable("TRAVERSE root is not a snapshot vertex")
            idxs.append(i)
        # preserve first-occurrence order for depth-0 emission; BFS admits
        # each root once
        seen = set()
        uniq = [i for i in idxs if not (i in seen or seen.add(i))]
        return np.asarray(uniq, np.int32)

    def solve(self) -> Tuple[jnp.ndarray, int]:
        """Returns (emitted vertex indices [bucketed], emitted count),
        level by level (depth-0 roots first, then each BFS level)."""
        # deliberately at lowering: counts plans lowered, not dispatches
        metrics.incr("plan.traverse.baked")  # lint: allow(jaxlint)
        V = self.dg.num_vertices
        vb = K.bucket(max(V, 1))
        univ = _index_range(self.dg, self.tier, 0, V, vb)
        hops = build_bitmap_hops(
            self.dg, self.hop_items, sched=self.sched, tier=self.tier,
            touched=self.tier_touched,
        )
        # one logical traversal row: [1, vb] bitmap with every root set
        roots = jnp.zeros((1, vb), bool)
        if self.roots.shape[0]:
            roots = roots.at[0, jnp.asarray(self.roots)].set(True)
        visited = roots
        frontier = roots
        depth = 0
        # depth-0 emits the caller's root order (host-known), not index order
        parts: List[jnp.ndarray] = [jnp.asarray(self.roots)]
        counts: List[int] = [int(self.roots.shape[0])]
        max_depth = self.stmt.max_depth
        while True:
            if max_depth is not None and depth >= max_depth:
                break
            nxt = jnp.zeros_like(frontier)
            for hop in hops:
                nxt = nxt | hop(frontier)
            nxt = nxt & ~visited
            if self.while_fn is not None:
                gate = self.while_fn(univ, {"depth": depth + 1})
                nxt = nxt & gate[None, :]
            keep, kn, _dev = _observe_compact(self.sched, nxt.reshape(-1))
            if kn == 0:
                break
            visited = visited | nxt
            depth += 1
            parts.append(keep)
            counts.append(kn)
            frontier = nxt
            if depth > V:  # safety: no min-depth exceeds |V|
                break
        total = sum(counts)
        width = K.bucket(max(total, 1))
        idx = _pad_concat([p[:c] for p, c in zip(parts, counts)], width)
        return idx, total

    def rows_from(self, idx: np.ndarray, count: int) -> List[Result]:
        out: List[Result] = []
        for i in np.asarray(idx)[:count]:
            doc = self.db.load(self.snap.rid_of(int(i)))
            if doc is not None:
                out.append(Result(element=doc))
        return out


#: depths the table of a whole-graph search holds to begin with; a search
#: with more levels records anew with four times as many
LEVEL_SLOTS = 64


class TpuLevelsSolver(TpuMatchSolver):
    """The whole-graph breadth-first search behind ``SELECT $depth,
    count(*) FROM (TRAVERSE both('<class>') FROM (SELECT FROM <class>
    WHERE ...) STRATEGY BREADTH_FIRST) GROUP BY $depth``
    (`select_compile.LevelCounts`), generic in its roots.

    The roots are a MATCH root: the TRAVERSE's target rewrites to a
    single-node MATCH (`select_compile.rewrite_select`), whose admission
    mask over the class hull (`_root_index`) IS the search's first
    frontier, so a parameter in its WHERE is a dynamic jit argument and
    one recording serves every root; none, one or several vertices may
    pass. The levels, the depth of every vertex and the counts by depth
    are the device's (`ops/csr.bfs_levels`): nothing of a level crosses
    to the host, and no buffer is sized by an answer: V, E, the switch
    and the sparse step's buffers come from the snapshot
    (`ops/csr.levels_caps`), the depth table from `LEVEL_SLOTS`.

    Compiles for one concrete edge class walked both ways on a plain
    snapshot (not delta-maintained, tiered or mesh-sharded)."""

    def __init__(self, db, stmt, params: Dict) -> None:
        from orientdb_tpu.exec.select_compile import ALIAS, rewrite_select

        root_match, _ = rewrite_select(stmt.roots)
        super().__init__(db, root_match, params)
        self.levels = stmt
        self.root_alias = ALIAS
        concrete = self.snap.edge_closure.get(stmt.edge_class.lower(), [])
        if len(concrete) != 1 or concrete[0] not in self.dg.edges:
            raise Uncompilable(
                f"TRAVERSE levels over {len(concrete)} concrete edge classes"
            )
        if (
            self.overlay is not None
            or self.tier is not None
            or self.dg.mesh_graph is not None
        ):
            raise Uncompilable(
                "TRAVERSE levels on a delta-maintained, tiered or sharded graph"
            )
        self.edge_class = concrete[0]
        self.slots = LEVEL_SLOTS

    def first_frontier(self) -> jnp.ndarray:
        """``bool[V]``: the vertices the roots' SELECT admits."""
        V = self.dg.num_vertices
        idx, mask = self._root_index(self.root_alias)
        if isinstance(idx, K.IndexRange) and not idx.slab_size:
            return jnp.pad(
                mask[: idx.size], (idx.start, V - idx.start - idx.size)
            )
        at = jnp.where(mask, K.as_index(idx), V)
        return jnp.zeros(V, jnp.int32).at[at].add(1, mode="drop") > 0

    def solve_levels(self) -> jnp.ndarray:
        """The search as one int32 vector: `K.LEVEL_PARTS`, whether the
        depth table ran out, the vertices at each of its depths."""
        # deliberately at lowering: counts plans lowered, not dispatches
        metrics.incr("plan.traverse.generic")  # lint: allow(jaxlint)
        dec = self.dg.edges[self.edge_class]
        threshold, caps = K.levels_caps(dec.num_edges)
        _depth, counts, parts, more = K.bfs_levels(
            dec.indptr_out,
            dec.dst,
            dec.indptr_in,
            dec.src,
            self.first_frontier(),
            threshold=threshold,
            caps=caps,
            slots=self.slots,
            hull_out=dec.hull_out,
            hull_in=dec.hull_in,
        )
        return jnp.concatenate([parts, more.astype(jnp.int32)[None], counts])

    def rows_from_levels(self, out: np.ndarray) -> List[Result]:
        """One row a depth that holds a vertex, and the search's
        counters, once an answer, from what the device returned with it:
        ``traverse.queries``, ``traverse.levels``,
        ``traverse.dense_levels``, ``traverse.edges_scanned`` (2E a dense
        level, the frontier's ends a sparse one), ``traverse.overflow``,
        ``traverse.reached``."""
        n = len(K.LEVEL_PARTS)
        part = dict(zip(K.LEVEL_PARTS, (int(x) for x in out[:n])))
        a_level = 2 * self.dg.edges[self.edge_class].num_edges
        metrics.incr_many(
            {
                "traverse.queries": 1,
                "traverse.levels": part["levels"],
                "traverse.dense_levels": part["dense_levels"],
                "traverse.edges_scanned": part["sparse_ends"]
                + a_level * part["dense_levels"],
                "traverse.overflow": part["overflow"],
                "traverse.reached": part["reached"],
            }
        )
        counts = out[n + 1 :]
        return [
            Result(
                props={
                    name: d if kind == "depth" else int(counts[d])
                    for name, kind in self.levels.columns
                }
            )
            for d in np.flatnonzero(counts).tolist()
        ]


import threading as _threading

#: serializes TRACE-bearing work: a background warm-up tracing one plan
#: while the main thread eagerly records another shares lazily-populated
#: device-graph caches; concurrent first-touch of those can leak one
#: trace's values into the other. Compiled-plan DISPATCHES never trace
#: and never take this lock.
_TRACE_LOCK = _threading.RLock()


class _AotWarmup:
    """Background trace+compile of a replay's jitted function.

    A freshly recorded plan returns its rows from the eager recording run —
    its `jax.jit` replay has never been called, so the FIRST replay dispatch
    would absorb the whole trace+XLA-compile (~10 s for a deep var-depth
    plan), landing squarely in what callers think is the steady state.
    `ensure_compiled` moves that cost to record time on a daemon thread
    (tracing swaps `dg.arrays` thread-locally, so concurrent queries are
    unaffected); `dispatch` waits for a pending warm-up instead of
    duplicating the compile."""

    _aot_ready = None  # threading.Event while a warm-up is in flight

    #: all in-flight warm-up events (drain_warmups waits on these; each
    #: worker removes its own entry, so the list stays bounded)
    _inflight: "List" = []

    def _warm_call(self):  # pragma: no cover - overridden
        raise NotImplementedError

    def _arg_subset(self):
        """The plan's jit-arg pytree: only the graph arrays its
        recording touched (`_record`'s touch log). Keeps every cached
        plan's pytree structure stable while pruned columns upload
        lazily, and ships executables only what they read."""
        arrays = self.solver.dg.arrays
        keys = getattr(self, "arg_keys", None)
        if keys is None:
            # SNAPSHOT the dict: another thread may fault a pruned
            # column in (ensure_key -> _put) while jax flattens the
            # pytree on this one
            return dict(arrays)
        return {k: arrays[k] for k in keys}

    def _is_compiled(self) -> bool:
        try:
            return self.jitted._cache_size() > 0
        except Exception:
            return False

    def ensure_compiled(self) -> None:
        if self._aot_ready is not None or self._is_compiled():
            return
        import threading

        from orientdb_tpu.utils.metrics import metrics

        ev = threading.Event()
        self._aot_ready = ev
        _AotWarmup._inflight.append(ev)
        # keep the exit-time drain AHEAD of JAX's own teardown handlers:
        # atexit runs in reverse registration order and JAX registers
        # teardown lazily at first compile — re-registering on every
        # warm-up start keeps the drain first, so no trace is in flight
        # when the compile machinery is dismantled
        import atexit

        atexit.unregister(drain_warmups)
        atexit.register(drain_warmups)

        def work():
            # the warm-up CALLS the jitted replay (result discarded): JAX's
            # AOT `lower().compile()` does not seed the jit call cache, so
            # executing once is the only way to make the next dispatch hit
            try:
                for attempt in (0, 1):
                    try:
                        snap = getattr(
                            getattr(self, "solver", None), "snap", None
                        )
                        if (
                            snap is not None
                            and snap._device_cache is None
                        ):
                            # the snapshot's device graph was released
                            # (delta-plane compaction swap): the plan is
                            # dead, warming it would only KeyError
                            metrics.incr("plan_cache.aot_skip_released")
                            break
                        # the lock serializes TRACING (thread-local
                        # device-graph cache swaps); device execution
                        # is async, so wait for it after release
                        with _TRACE_LOCK:
                            res = self._warm_call()
                        jax.block_until_ready(res)
                        metrics.incr("plan_cache.aot_compile")
                        break
                    except ScheduleOverflow:
                        # stale delta generation (_check_delta_gen):
                        # the next dispatch re-records — nothing to warm
                        metrics.incr("plan_cache.aot_skip_stale")
                        break
                    except Exception:
                        if attempt:
                            # give up: the next dispatch compiles inline
                            # (slower but correct)
                            log.exception("background plan warm-up failed")
                            metrics.incr("plan_cache.aot_compile_error")
                        else:
                            import time as _t

                            _t.sleep(0.05)
            finally:
                ev.set()
                try:
                    _AotWarmup._inflight.remove(ev)
                except ValueError:
                    pass  # a concurrent drain already claimed it

        threading.Thread(target=work, daemon=True, name="plan-aot").start()

    def wait_compiled(self) -> None:
        ev = self._aot_ready
        if ev is not None:
            ev.wait()
            self._aot_ready = None


def drain_warmups() -> None:
    """Block until every in-flight background plan compile finishes.

    Benchmarks and tests call this between warm-up and measurement so AOT
    compile threads (which hold the GIL through long trace phases) don't
    steal host time from the timed section. Also registered atexit:
    killing a daemon thread inside an XLA compile at interpreter teardown
    aborts the process ("FATAL: exception not rethrown")."""
    pending, _AotWarmup._inflight = _AotWarmup._inflight, []
    for ev in pending:
        ev.wait()


import atexit  # noqa: E402  (registration belongs right next to the drain)

atexit.register(drain_warmups)


class _CompiledTraverse(_AotWarmup):
    """Replayable TRAVERSE plan (same dispatch/materialize protocol as
    `_CompiledPlan` so `execute_batch` treats both uniformly)."""

    def __init__(self, solver: TpuTraverseSolver, count: int) -> None:
        self.solver = solver
        self.count = count
        self.tier_footprint = frozenset(solver.tier_touched)
        self.jitted = jax.jit(self._replay)

    def _warm_call(self):
        # snapshot the canonical dict: the main thread may _put new keys
        # (lazy class-id/edge uploads) while jit flattens the pytree here
        return self.jitted(self._arg_subset())

    @jax.named_scope("traverse.replay")
    def _replay(self, arrays):
        dg = self.solver.dg
        saved = dg.arrays
        dg.arrays = arrays
        try:
            self.solver.sched.start_replay()
            idx, _n = self.solver.solve()
        finally:
            dg.arrays = saved
        return idx

    def dispatch(self, params: Optional[Dict] = None):
        # TRAVERSE plans bake parameter values (their full values join the
        # plan-cache key), so `params` is accepted for interface parity
        # with _CompiledPlan and ignored
        _check_traverse_static(self.solver)
        self.wait_compiled()
        tier = self.solver.tier
        if tier is not None:
            args = tier.prepare_dispatch(self.tier_footprint, self._arg_subset)
        else:
            args = self._arg_subset()
        return self.jitted(args)

    def batchable(self) -> bool:
        """TRAVERSE plans bake their parameters, so every batch item
        sharing this plan is the IDENTICAL program on identical inputs:
        the group path serves them all with ONE dispatch (the no-dyn
        shared-dispatch case of execute_batch's grouping)."""
        return (
            self.solver.dg.mesh_graph is None and self.solver.tier is None
        )

    def _dyn_args(self, params: Optional[Dict]) -> Dict:
        _check_delta_gen(self.solver)
        _check_traverse_static(self.solver)
        return {}  # no dynamic args: grouping uses the shared dispatch

    def materialize(self, dev, params: Optional[Dict] = None) -> List[Result]:
        tier = self.solver.tier
        if tier is not None:
            tier.release_footprint(self.tier_footprint)
        return self.solver.rows_from(np.asarray(dev), self.count)

    def rows(self, params: Optional[Dict] = None) -> List[Result]:
        arr = _fetch_profiled([self.dispatch()])[0]
        with timed("tpu.host_s"):
            return self.materialize(arr)


# ---------------------------------------------------------------------------
# compiled plan cache ([E] OExecutionPlanCache analog)
# ---------------------------------------------------------------------------


class ScheduleOverflow(Exception):
    """A parameter-generic replay's live sizes exceeded the recorded
    schedule's capacities; the result was discarded. Caller re-records."""


def _check_delta_gen(solver) -> None:
    """Fail a dispatch whose plan was recorded under an older delta
    structure (storage/deltas bumps the generation on the first
    topology delta and on dictionary appends, clearing the plan cache;
    this guards plan objects picked BEFORE the bump). The overflow
    surface routes the caller straight into the re-record path."""
    ov = getattr(solver, "overlay", None)
    if ov is not None and ov.plan_gen != solver.delta_gen:
        raise ScheduleOverflow(
            f"delta structure moved (gen {solver.delta_gen} -> "
            f"{ov.plan_gen})"
        )


def _check_traverse_static(solver) -> None:
    """TRAVERSE replays bake their host-resolved roots and drop the
    size schedule's overflow flag — sound only while replay inputs are
    identical to the recording (immutable snapshots). On a
    delta-maintained snapshot ANY applied event invalidates that
    assumption, so the dispatch re-records (MATCH keeps its full
    delta-aware replay; TRAVERSE pays an eager solve under writes)."""
    ov = getattr(solver, "overlay", None)
    if ov is not None and ov.data_version != solver.delta_data_version:
        raise ScheduleOverflow(
            "traverse recording is stale under delta maintenance "
            f"(data v{solver.delta_data_version} -> v{ov.data_version})"
        )


class _CompiledPlan(_AotWarmup):
    """A solver whose size schedule is learned: re-executions replay the
    whole solve as one jitted, sync-free device dispatch.

    Numeric query parameters are jit ARGUMENTS of the replay (see
    predicates.ParamBox), so ONE recorded plan serves every parameter
    value — the way the reference's [E] OExecutionPlanCache caches per
    statement. Because buffer sizes were recorded under the recording
    parameters, the replay returns (alongside the result) a device valid
    mask, the true row count, and an overflow flag; materialization uses
    the live mask/count, and an overflow raises ScheduleOverflow so the
    front door re-records with the new parameters (bucket capacities grow
    monotonically, so this converges).

    Execution is split into ``dispatch()`` (enqueue the device work —
    microseconds) and ``materialize()`` (device→host transfer + row
    marshalling). A transfer carries a fixed cost regardless of size, so
    ``execute_batch`` dispatches a whole batch, starts async host copies
    for every result, and only then materializes — overlapping N round
    trips into ~one."""

    def __init__(self, solver: TpuMatchSolver, table: Table) -> None:
        self.solver = solver
        self.v_names = sorted(table.cols)
        self.e_names = sorted(table.edge_cols)
        self.d_names = sorted(table.depth_cols)
        self.count = table.count
        self.width = table.width
        self.count_name = solver.count_only_name()
        self.fetch_limit = self._literal_fetch_limit(solver.stmt)
        #: result columns in the packed data stack (vertex + 2-per-edge
        #: + depth) — shared by direct_fetch and the group-lane budget
        self.ncols = (
            len(self.v_names) + 2 * len(self.e_names) + len(self.d_names)
        )
        #: small full buffers ship whole in the batch's first transfer
        #: wave — no meta-gated page election (see _replay's direct path)
        self.direct_fetch = (
            self.count_name is None
            and self.ncols > 0
            and self.width >= 2  # meta row needs [count, overflow] slots
            and 4 * self.width * self.ncols <= config.result_direct_bytes
        )
        #: page-ladder HBM budget, frozen per plan at construction —
        #: reading config inside _replay would bake it at trace time
        #: invisibly (jaxlint); freezing here makes the staleness
        #: boundary explicit: retuning applies from the next recording
        self.page_budget_bytes = int(config.result_page_budget_bytes)
        #: dynamic parameters the compiled predicates actually read
        self.dyn_spec = dict(solver.param_box.used)
        #: index-seeded root capacities (alias → padded length)
        self.seed_spec = dict(solver.seed_box.spec)
        #: (ladder index, fits16) the LAST materialization elected —
        #: dispatch() speculatively starts that page's device→host copy
        #: so the transfer rides behind the compute instead of waiting
        #: for the meta wave (the r04 rows-path 12 ms serialized tail)
        self._page_guess: Optional[Tuple[int, bool]] = None
        #: (B, rows, fits16) the last GROUP page election (group_page's
        #: cache key) — _group_dispatch prefetches the slice when its
        #: executable is already compiled
        self._group_page_guess: Optional[Tuple[int, int, bool]] = None
        #: data-stack shape the guess's page fn was compiled against:
        #: a prefetch only fires on an exact shape match, so the jit
        #: call is a guaranteed cache hit — a differently-sized batch
        #: must never absorb a synchronous XLA compile on the drain path
        self._group_page_shape: Optional[Tuple[int, ...]] = None
        #: tiered snapshots: the blocks the recording run faulted —
        #: every dispatch re-ensures them resident (pin + async
        #: prefetch) before grabbing its argument pytree
        self.tier_footprint = frozenset(solver.tier_touched)
        #: what the recording computed once for every replay
        #: (`TpuMatchSolver.plan_consts`): jit ARGUMENTS beside the
        #: graph's arrays, never closed-over constants of the executable
        self.consts = dict(solver.plan_consts)
        for key, arr in self.consts.items():
            solver.dg.adopt_plan_const(self, key, arr)
        self.jitted = jax.jit(self._replay)

    def _arg_subset(self):
        args = super()._arg_subset()
        args.update(self.consts)
        return args

    @_contextmanager
    def _bound(self, arrays, dyn):
        """The solver as a replay's trace sees it: the tracer pytree
        swapped into the device graph, so the graph buffers become jit
        ARGUMENTS (shared across every cached plan) rather than
        per-executable HLO constants; the dynamic parameter scalars in
        the param box and the seed arrays in the seed box likewise; the
        size schedule replaying."""
        solver = self.solver
        solver.param_box.set_current(dyn)
        solver.seed_box.current = {
            a: dyn[f"__seed__:{a}"] for a in self.seed_spec
        }
        try:
            with solver.dg.bound(arrays):
                solver.sched.start_replay()
                yield
        finally:
            solver.param_box.reset()
            solver.seed_box.current = {}

    @jax.named_scope("match.core")
    def _replay_core(self, arrays, dyn):
        """Shared replay body: run the recorded solve and front-pack the
        result columns. Returns ``(count_dev, overflow, data)`` where
        ``data`` is the [C, width] int32 column stack (None for
        count-only / column-less plans).

        The traced bodies carry name scopes (``match.replay`` or
        ``match.replay_group``, then ``match.core``, then the kernel's
        own ``csr.<name>``, ``ops/csr``): an operation's HLO ``op_name``
        says which plan entry and which kernel it came from. Trace time
        only."""
        solver = self.solver
        with self._bound(arrays, dyn):
            table = solver.solve_table()
        overflow = solver.sched.overflow_flag().astype(jnp.int32)
        count_dev = table.count_device.astype(jnp.int32)
        if self.count_name is not None or self.width == 0:
            return count_dev, overflow, None
        flat: List[jnp.ndarray] = [table.cols[a] for a in self.v_names]
        for a in self.e_names:
            flat.extend(table.edge_cols[a])
        flat.extend(table.depth_cols[a] for a in self.d_names)
        if not flat:  # no columns (e.g. fully-detached optional pattern)
            return count_dev, overflow, None
        width = flat[0].shape[0]
        # front-pack live rows ON DEVICE (stable), so the host needs only
        # the first `count` slots: the batch fetch path reads meta first
        # and then transfers just a page-rounded live prefix instead of
        # the whole capacity-padded buffer (at demodb scale the padded
        # stack was ~1 MB/query)
        perm = K.compact_indices(table.valid_device[:width], width)
        data = jnp.stack([K.take_pad(c, perm, -1) for c in flat])
        return count_dev, overflow, data

    @staticmethod
    def _fits16_flag(data, count_dev, width):
        """Runtime bit-width election flag: 1 when every live value fits
        int16 — decided per dispatch by a meta flag, not per plan."""
        live = jnp.arange(width, dtype=jnp.int32)[None, :] < count_dev
        masked = jnp.where(live, data, 0)
        return (
            (jnp.max(masked) < 32767) & (jnp.min(masked) > -32768)
        ).astype(jnp.int32)

    @jax.named_scope("match.replay_group")
    def _replay_group(self, arrays, dyn):
        """Group-mode replay for row-returning plans: ``(meta, data)``
        with the FULL int32 column stack and no page ladder — the group
        fetch elects ONE page for the whole lane stack after the meta
        wave (`group_page`), so the ladder's per-dispatch
        materialization cost is not paid B times."""
        count_dev, overflow, data = self._replay_core(arrays, dyn)
        if data is None:
            return jnp.stack([count_dev, overflow, jnp.int32(0)]), None
        width = data.shape[1]
        meta = jnp.stack(
            [count_dev, overflow, self._fits16_flag(data, count_dev, width)]
        )
        return meta, data

    @staticmethod
    def _page_round(W: int, need: int) -> int:
        """Rows of the compact group page covering ``need`` live rows:
        pow-of-_GROUP_PAGE_ROUND rounding, capped at the full width —
        ONE formula shared by the election and the speculative
        dispatch-time prefetch so their keys can never drift."""
        return min(W, -(-max(need, 1) // _GROUP_PAGE_ROUND) * _GROUP_PAGE_ROUND)

    @staticmethod
    def _page_fn(B: int, n: int, fits16: bool):
        # both callers memoize the result in _group_page_fns keyed
        # (B, n, fits16) — the construction itself never serves a batch
        if fits16:
            return jax.jit(lambda d: d[:B, :, :n].astype(jnp.int16))  # lint: allow(jaxlint)
        return jax.jit(lambda d: d[:B, :, :n])  # lint: allow(jaxlint)

    def _compile_page_async(self, key, data_dev) -> None:
        """Background trace+compile of one (B, n, fits16) page fn —
        serving batches must never absorb an XLA compile."""
        import threading

        flags = self.__dict__.setdefault("_page_compiling", set())
        if key in flags:
            return
        flags.add(key)
        cache = self.__dict__.setdefault("_group_page_fns", {})

        def work():
            try:
                B, n, f16 = key
                fn = self._page_fn(B, n, f16)
                jax.block_until_ready(fn(data_dev))
                cache[key] = fn
            except Exception:
                log.exception("group page compile failed: %s", key)
            finally:
                flags.discard(key)

        threading.Thread(target=work, daemon=True).start()

    def precompile_group_pages(self, data_dev) -> None:
        """Compile the pow2 page-fn ladder for a group's stacked data
        shape — called from the background group-compile thread so the
        first grouped serving batch finds its page fn ready."""
        Bb, _C, W = (int(s) for s in data_dev.shape)
        cache = self.__dict__.setdefault("_group_page_fns", {})
        n = _GROUP_PAGE_ROUND
        sizes = []
        while n < W:
            sizes.append(n)
            n *= 2
        sizes.append(W)
        for n in sizes:
            for f16 in (False, True):
                key = (Bb, n, f16)
                if key not in cache:
                    try:
                        fn = self._page_fn(Bb, n, f16)
                        jax.block_until_ready(fn(data_dev))
                        cache[key] = fn
                    except Exception:
                        log.exception(
                            "group page precompile failed: %s", key
                        )
                        return

    def group_page(self, data_dev, B: int, need: int, fits16: bool):
        """Elect the compact page for a whole group's stacked data:
        [Bb, C, width] → [B, C, n] (int16 when every lane's live values
        fit), as ONE Execute. NEVER compiles synchronously: an exact
        (B, n, fits16) hit serves directly; a miss kicks a background
        compile and serves this batch from the smallest precompiled
        fallback (the pow2 ladder built by `precompile_group_pages`),
        or the raw full int32 stack when nothing is ready yet."""
        n = self._page_round(int(data_dev.shape[2]), need)
        cache = self.__dict__.setdefault("_group_page_fns", {})
        fn = cache.get((B, n, fits16))
        if fn is not None:
            return fn(data_dev)
        self._compile_page_async((B, n, fits16), data_dev)
        best = None
        # snapshot: background compile threads insert into this dict
        for (b2, n2, f2), fn2 in list(cache.items()):
            if b2 >= B and n2 >= n and f2 == fits16:
                if best is None or (n2, b2) < best[0]:
                    best = ((n2, b2), fn2)
        if best is not None:
            return best[1](data_dev)
        return data_dev  # nothing compiled yet: ship the raw stack once

    @jax.named_scope("match.replay")
    def _replay(self, arrays, dyn):
        count_dev, overflow, data = self._replay_core(arrays, dyn)
        if data is None:
            # COUNT(*) plan (or column-less table): two scalars suffice
            return jnp.stack([count_dev, overflow, jnp.int32(0)]), None, None
        width = data.shape[1]
        if self.direct_fetch:
            # small buffer: ONE fused [C+1, width] array (data rows + a
            # trailing [count, overflow, ...] meta row) = ONE device
            # buffer and ONE host copy per query, started in the batch's
            # first transfer wave. Every buffer fetch carries a fixed
            # cost, so for few-KB results a single
            # fused copy beats the meta-then-elected-page protocol (the
            # round-3 LDBC IS regression); big buffers keep the election.
            meta_row = (
                jnp.zeros(width, jnp.int32)
                .at[0].set(count_dev)
                .at[1].set(overflow)
            )
            return jnp.concatenate([data, meta_row[None, :]], axis=0)
        # runtime bit-width election: when every live value fits int16
        # (vertex indices on small graphs usually do; edge positions on
        # big ones don't), the fetch ships the half-size copy — decided
        # per dispatch by a meta flag, not per plan, so it stays general
        meta = jnp.stack(
            [count_dev, overflow, self._fits16_flag(data, count_dev, width)]
        )
        # pre-materialized pow2 page prefixes (both dtypes): the batch
        # fetch picks the smallest page covering the live count and reads
        # an EXISTING device buffer — a per-query slice dispatch after the
        # meta wave costs more than the bytes it saves. The full ladder
        # costs ~3x the plain buffer in
        # device memory (prefix sums ≈ 2x per dtype), so it is emitted
        # only under a budget: wide plans (where a 64-deep batch of
        # tripled result buffers could pressure HBM) fall back to the
        # single full-width buffer per dtype — their transfers hide
        # behind device compute in the interleaved fetch anyway.
        C = int(data.shape[0])
        pages32, pages16 = [], []
        if 12 * width * C <= self.page_budget_bytes:
            p = _PAGE_MIN
            while p < width:
                pages32.append(data[:, :p])
                pages16.append(data[:, :p].astype(jnp.int16))
                p *= 2
        pages32.append(data)
        pages16.append(data.astype(jnp.int16))
        return meta, pages32, pages16

    def _dyn_args(self, params: Optional[Dict]) -> Dict:
        # host-side (numpy) values: the jit call transfers them, and
        # dispatch_many can stack B of them into ONE transfer per key
        _check_delta_gen(self.solver)
        params = params if params is not None else self.solver.params
        dyn = {}
        for k, kind in self.dyn_spec.items():
            v = params[k]
            dtype = np.float32 if kind == "float" else np.int32
            dyn[k] = np.asarray(int(v) if kind != "float" else v, dtype)
        for alias, cap in self.seed_spec.items():
            hits = self.solver.compute_seed(alias, params)
            if hits.shape[0] > cap:
                # more index hits than the recorded capacity: this
                # replay's buffers are too small — re-record (variants)
                raise ScheduleOverflow(f"root seed '{alias}' > {cap}")
            arr = np.full(cap, -1, np.int32)
            arr[: hits.shape[0]] = hits
            dyn[f"__seed__:{alias}"] = arr
        return dyn

    def _warm_call(self):
        # dict snapshot for the same flatten-vs-insert reason as traverse
        return self.jitted(self._arg_subset(), self._dyn_args(None))

    def dispatch(self, params: Optional[Dict] = None):
        """Enqueue the replay on device; returns the un-fetched result."""
        self.wait_compiled()
        import orientdb_tpu.obs.timeline as _TL

        if self.solver.dg.mesh_graph is not None:
            _TL.note_path("sharded")
        dyn = self._dyn_args(params)
        if dyn:
            # EXPLICIT host→device upload of the parameter scalars/seed
            # arrays: handing the jitted call raw numpy made the same
            # transfer implicitly on every dispatch — invisible to
            # profiling and flagged by the deviceguard transfer guard
            import time as _time

            import orientdb_tpu.obs.critpath as _CP

            _t_up = _time.perf_counter()
            dyn = jax.device_put(dyn)
            _CP.add_segment("param_upload", _time.perf_counter() - _t_up)
            _TL.mark("param_upload")
        tier = self.solver.tier
        if tier is not None:
            # footprint prefetch + pin + atomic arg-pytree grab under
            # the tier lock — a concurrent eviction can never hand this
            # dispatch a torn (pool, page_of) pair; materialize unpins
            args = tier.prepare_dispatch(self.tier_footprint, self._arg_subset)
        else:
            args = self._arg_subset()
        devicefault.dispatch_point()
        dev = self.jitted(args, dyn)
        _TL.mark("device_dispatch")
        self._prefetch_elected(dev)
        return dev

    def _prefetch_elected(self, dev) -> None:
        """Speculative result-page prefetch: start the device→host copy
        of the page the LAST materialization elected, at DISPATCH time.
        The D2H queues behind the producing compute, so the bytes move
        during the next dispatch's formation instead of serializing
        after the meta wave (r04 rows path: 20 ms device + 12 ms
        transfer back-to-back; steady state re-elects the same page, so
        the transfer hides). A wrong guess costs one redundant page
        copy — the election itself stays exact."""
        guess = self._page_guess
        if guess is None or not (isinstance(dev, tuple) and len(dev) == 3):
            return
        idx, f16 = guess
        pages = dev[2] if f16 else dev[1]
        if pages and 0 <= idx < len(pages):
            _copy_to_host_async(pages[idx])
            metrics.incr("tpu.page_prefetch.start")
            from orientdb_tpu.obs.memledger import memledger
            from orientdb_tpu.obs.timeline import note_prefetch_start

            memledger.register(
                "prefetched_page",
                f"plan:{id(self):x}",
                "spec_page",
                arr=pages[idx],
            )
            note_prefetch_start()

    def batchable(self) -> bool:
        """Eligible for the vmapped one-Execute group dispatch: count-only
        and direct-fetch plans (one small output buffer per lane), plus
        row-returning plans whose full int32 stack fits the per-lane
        budget (the group replays with NO page ladder and elects one
        compact page for the whole stack after the meta wave —
        `group_page`). Mesh plans keep per-query dispatch because
        vmap-over-shard_map is not exercised anywhere."""
        if self.solver.dg.mesh_graph is not None:
            return False
        if self.solver.tier is not None:
            # tiered dispatches pin/ensure their footprint per call —
            # the shared group lane would fuse different footprints
            return False
        if self.count_name is not None or self.width == 0 or self.direct_fetch:
            return True
        return 4 * self.width * self.ncols <= config.result_group_lane_bytes

    def _rows_grouped(self) -> bool:
        """True when group dispatch uses the (meta, data) rows-group
        replay rather than the single-buffer count/direct replay."""
        return not (
            self.count_name is not None or self.width == 0 or self.direct_fetch
        )

    def dispatch_many(self, dyns: List[Dict], ring: "ParamRing" = None):
        """ONE Execute for B same-plan replays: the replay vmapped over
        stacked dynamic args, padded to a pow2 lane bucket so the jit
        cache stays O(log B) per plan. ``ring`` (a coalesce lane's
        :class:`ParamRing`) keeps the stacked parameter pytree
        device-resident across dispatches: a repeated value set reuses
        the staged buffer and ships zero host bytes.

        Every Execute carries a fixed dispatch cost however small the
        program, which floors per-query dispatch; B stacked replays
        amortize it B-fold and fetch as ONE buffer.

        Returns None when this (plan, lane-bucket)'s vmapped executable
        is still compiling — compilation runs on a BACKGROUND thread
        (like the plan's own AOT warm-up) and the caller dispatches
        per-lane meanwhile, so a 10s+ vmapped XLA compile never lands in
        a serving batch. `drain_warmups()` blocks on these too."""
        self.wait_compiled()
        B = len(dyns)
        Bb = 1 << (B - 1).bit_length()
        cap = self._group_lane_cap()
        if Bb > cap and self._rows_grouped():
            # chunking would break the page ladder's (Bb, C, W) shape
            # contract; rows plans past the cap stay per-lane
            return None
        Bb = min(Bb, cap)
        nchunks = -(-B // Bb)  # oversized batches run capped chunks
        cache = self.__dict__.setdefault("_jitted_many", {})
        fn = cache.get(Bb)
        if fn is False:
            return None  # compile failed permanently: per-lane forever
        all_dyns = dyns + [dyns[-1]] * (nchunks * Bb - B)

        def _stack(c: int) -> Dict:
            # explicit upload (deviceguard): one device_put per chunk
            # instead of an implicit transfer inside the vmapped call
            host = {
                k: np.stack(
                    [
                        np.asarray(d[k])
                        for d in all_dyns[c * Bb : (c + 1) * Bb]
                    ]
                )
                for k in dyns[0]
            }
            if ring is not None:
                return ring.stage(host)
            return jax.device_put(host)

        if fn is None:
            self._compile_group_async(Bb, _stack(0))
            return None
        devicefault.dispatch_point()
        if nchunks == 1:
            return fn(self._arg_subset(), _stack(0))
        outs = [fn(self._arg_subset(), _stack(c)) for c in range(nchunks)]
        return jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *outs
        )

    def _group_lane_cap(self) -> int:
        """Max vmapped lanes per Execute for plans that READ EDGE
        STATE: the fused edge-predicate select materializes an O(E)
        int32 intermediate per lane, so an uncapped pow2 width on an
        80M-edge graph asks the compiler for lanes × 320 MB and OOMs —
        which costs a failed 20s+ compile AND drops the plan to
        per-lane forever. Cap so lanes × 4E fits
        config.group_hbm_budget_bytes, sized by the LARGEST edge class
        this plan's recording touched; edge-free plans (vertex-only
        counts/filters) keep unbounded width — they materialize no
        O(E) intermediate and live off group amortization."""
        dg = self.solver.dg
        keys = getattr(self, "arg_keys", None)
        if keys is None:
            classes = set(dg.edges)
        else:
            classes = {
                k.split(":", 2)[1] for k in keys if k.startswith("e:")
            }
        E = max(
            (
                dg.edges[c].num_edges
                for c in classes
                if c in dg.edges
            ),
            default=0,
        )
        if E <= 0:
            return 1 << 30
        cap = max(1, int(config.group_hbm_budget_bytes) // (4 * E))
        return 1 << (cap.bit_length() - 1)  # floor to pow2

    def _compile_group_async(self, Bb: int, stacked: Dict) -> None:
        import atexit
        import threading

        flags = self.__dict__.setdefault("_many_compiling", set())
        if Bb in flags:
            return
        flags.add(Bb)
        ev = threading.Event()
        _AotWarmup._inflight.append(ev)
        atexit.unregister(drain_warmups)
        atexit.register(drain_warmups)

        replay = (
            self._replay_group if self._rows_grouped() else self._replay
        )

        def work():
            # one retry for transient failures (runtime hiccup, resource
            # pressure) — the same discipline as ensure_compiled; only a
            # repeated failure writes the permanent per-lane sentinel so
            # a doomed compile isn't re-launched on every batch
            try:
                for attempt in (0, 1):
                    try:
                        fn = jax.jit(
                            jax.vmap(replay, in_axes=(None, 0))
                        )
                        # tracing completes when the call returns;
                        # the device-side wait runs lock-free
                        with _TRACE_LOCK:
                            res = fn(self._arg_subset(), stacked)
                        jax.block_until_ready(res)
                        if (
                            isinstance(res, tuple)
                            and len(res) == 2
                            and res[1] is not None
                        ):
                            # rows group: build the pow2 page-fn ladder
                            # NOW (still on this background thread) so
                            # serving batches never absorb a page compile
                            self.precompile_group_pages(res[1])
                        self._jitted_many[Bb] = fn
                        metrics.incr("plan_cache.group_compile")
                        break
                    except Exception:
                        if attempt:
                            log.exception(
                                "vmapped group compile failed twice "
                                "(plan stays per-lane)"
                            )
                            self._jitted_many[Bb] = False
                            metrics.incr("plan_cache.group_compile_error")
            finally:
                flags.discard(Bb)
                ev.set()
                try:
                    _AotWarmup._inflight.remove(ev)
                except ValueError:
                    pass

        threading.Thread(target=work, daemon=True).start()

    def materialize(self, fetched, params: Optional[Dict] = None) -> List[Result]:
        """Marshal rows from a dispatched `(meta, data)` pair.

        Accepts device results or pre-fetched numpy arrays; `data` may be
        a page-rounded live prefix of the full buffer (≥ `count` slots) —
        only the first `count` rows are read — and may arrive int16 when
        the dispatch's bit-width election shipped the half-size copy."""
        tier = self.solver.tier
        if tier is not None:
            # the dispatch that produced `fetched` has drained (we hold
            # its fetched buffers) — drop its footprint pins so
            # eviction stops preferring around these blocks. Runs
            # before the overflow raise: every dispatch path
            # materializes exactly once, success or overflow.
            tier.release_footprint(self.tier_footprint)
        if isinstance(fetched, tuple) and len(fetched) == 3:
            meta_dev, data_dev, _p16 = fetched  # raw dispatch triple
            if isinstance(data_dev, (list, tuple)):
                data_dev = data_dev[-1] if data_dev else None  # full page
        elif isinstance(fetched, tuple):
            meta_dev, data_dev = fetched
        else:
            meta_dev, data_dev = fetched, None
        meta = np.asarray(meta_dev)
        if meta.ndim == 2:
            # direct-fetch fused buffer: data rows + trailing meta row
            data_dev = meta[:-1]
            meta = meta[-1]
        count, overflow = int(meta[0]), int(meta[1])
        if overflow:
            raise ScheduleOverflow(str(self.solver.stmt))
        if self.count_name is not None:
            return self.solver.finalize_count(self.count_name, count, params)
        if data_dev is None:
            # column-less non-count table (degenerate): count empty rows
            t = Table(count=count, width=0)
            return self.solver.rows_from_table(t, params)
        data = np.asarray(data_dev)
        if data.dtype != np.int32:
            data = data.astype(np.int32)  # bit-width-elected fetch
        return self.solver.rows_from_table(
            self._table_from(data, self.fetch_rows_needed(count)), params
        )

    def rows(self, params: Optional[Dict] = None) -> List[Result]:
        dev = self.dispatch(params)
        if not isinstance(dev, tuple):  # direct-fetch fused buffer
            arr = _fetch_profiled([dev], split_sync=False)[0]
            with timed("tpu.host_s"):
                return self.materialize(arr, params)
        meta_dev, pages32, _p16 = dev
        if pages32:
            # the lone-query path always ships the full int32 page:
            # remember that election so the next dispatch prefetches it
            self._page_guess = (len(pages32) - 1, False)
        data_dev = pages32[-1] if pages32 else None
        devs = [meta_dev] if data_dev is None else [meta_dev, data_dev]
        arrs = _fetch_profiled(devs, split_sync=False)
        data = arrs[1] if len(arrs) > 1 else None
        with timed("tpu.host_s"):
            return self.materialize((arrs[0], data), params)

    def fetch_rows_needed(self, count: int) -> int:
        """How many live rows the host actually needs to marshal the
        result: `count`, or `skip+limit` when a literal LIMIT can be
        pushed into the transfer (no DISTINCT/UNWIND/ORDER/aggregate —
        those need every row before the cut)."""
        lim = self.fetch_limit
        return count if lim is None else min(count, lim)

    @staticmethod
    def _literal_fetch_limit(stmt) -> Optional[int]:
        """skip+limit as a plain int when LIMIT can cut the TRANSFER:
        row-per-binding results only — DISTINCT/UNWIND/ORDER/GROUP/
        aggregates and the $matches/$paths/$elements forms consume every
        row before the cut, and non-literal expressions would need a ctx."""
        from orientdb_tpu.exec.eval import contains_aggregate

        if not isinstance(stmt, A.MatchStatement):
            return None
        if stmt.distinct or stmt.unwind or stmt.order_by or stmt.group_by:
            return None
        if stmt.limit is None:
            return None
        if any(contains_aggregate(p.expr) for p in stmt.returns):
            return None
        if len(stmt.returns) == 1 and isinstance(stmt.returns[0].expr, A.ContextVar):
            return None
        def lit(e):
            if e is None:
                return 0
            if isinstance(e, A.Literal) and isinstance(e.value, int):
                return e.value
            return None
        limit, skip = lit(stmt.limit), lit(stmt.skip)
        if limit is None or skip is None or limit < 0:
            return None
        return skip + limit

    def _table_from(self, data: np.ndarray, count: int) -> Table:
        """Host table from the transferred live prefix: rows were
        front-packed (stable) on device, so the first `count` slots of
        every column are the live rows in expansion order."""
        n = min(count, data.shape[1])
        t = Table(count=n, width=n)
        i = 0
        for a in self.v_names:
            t.cols[a] = data[i][:n]
            i += 1
        for a in self.e_names:
            t.edge_cols[a] = (data[i][:n], data[i + 1][:n])
            i += 2
        for a in self.d_names:
            t.depth_cols[a] = data[i][:n]
            i += 1
        return t


class _CompiledLevels(_CompiledPlan):
    """A `TpuLevelsSolver` as a replayable plan, with `_CompiledPlan`'s
    dispatch protocol: the roots' parameters are dynamic jit arguments,
    the replay is one program whose level loop ends on the device, and
    its result is one small int32 vector (`solve_levels`).

    Nothing runs eagerly at its recording: `probe` traces the replay
    abstractly under the device graph's touch log (which arrays it
    reads, which parameters, which seeds) and `record` runs the jitted
    replay itself, with a longer depth table while the search runs out
    of depths. A replay that runs out raises `ScheduleOverflow`, and the
    front door records the next variant the same way."""

    def __init__(self, solver: TpuLevelsSolver) -> None:
        self.solver = solver
        # what the inherited dispatch reads: no constants of the plan, no
        # result page to prefetch; the specs are `probe`'s to fill
        self.consts: Dict = {}
        self._page_guess = None
        self.dyn_spec: Dict = {}
        self.seed_spec: Dict = {}
        self.jitted = jax.jit(self._replay)

    def probe(self) -> None:
        solver = self.solver
        jax.eval_shape(solver.solve_levels)
        self.dyn_spec = dict(solver.param_box.used)
        self.seed_spec = dict(solver.seed_box.spec)
        self.arg_keys = solver.dg.touched()

    def record(self, params: Optional[Dict]) -> List[Result]:
        while True:
            dyn = jax.device_put(self._dyn_args(params))
            devicefault.dispatch_point()
            dev = self.jitted(self._arg_subset(), dyn)
            devicefault.transfer_point()
            out = np.asarray(dev)
            if not out[len(K.LEVEL_PARTS)]:
                return self.solver.rows_from_levels(out)
            self.solver.slots *= 4
            self.jitted = jax.jit(self._replay)

    @jax.named_scope("traverse.levels")
    def _replay(self, arrays, dyn):
        with self._bound(arrays, dyn):
            return self.solver.solve_levels()

    def batchable(self) -> bool:
        return True

    def dispatch_many(self, dyns: List[Dict], ring: "ParamRing" = None):
        """A lane's searches one after another, stacked: each is the
        whole device's work, so there is nothing to share under a
        ``vmap`` and no second program to compile."""
        self.wait_compiled()
        args = self._arg_subset()
        devicefault.dispatch_point()
        return jnp.stack([self.jitted(args, jax.device_put(d)) for d in dyns])

    def materialize(self, fetched, params: Optional[Dict] = None) -> List[Result]:
        out = np.asarray(fetched)
        if out[len(K.LEVEL_PARTS)]:
            raise ScheduleOverflow(
                f"more than {self.solver.slots} levels: {self.solver.levels}"
            )
        return self.solver.rows_from_levels(out)

    def rows(self, params: Optional[Dict] = None) -> List[Result]:
        arr = _fetch_profiled([self.dispatch(params)], split_sync=False)[0]
        with timed("tpu.host_s"):
            return self.materialize(arr, params)


def _params_key(params) -> Optional[Tuple]:
    """Plan-cache key fragment: STATIC parameter values plus the
    names/kinds of dynamic (numeric) ones — dynamic values are jit
    arguments, so plans are shared across them."""
    dyn, static = split_params(params)
    try:
        t = (
            tuple(sorted((str(k), kind) for k, kind in dyn.items())),
            tuple(
                sorted((str(k), type(v).__name__, v) for k, v in static.items())
            ),
        )
        hash(t)
        return t
    except TypeError:
        return None  # unhashable param values → skip plan cache


# ---------------------------------------------------------------------------
# front door
# ---------------------------------------------------------------------------


def _plan_cache(snap) -> "OrderedDict":
    cache = getattr(snap, "_plan_cache", None)
    if cache is None:
        cache = snap._plan_cache = OrderedDict()
    return cache


def _all_values_key(params) -> Optional[Tuple]:
    """Every parameter value in the key (TRAVERSE plans bake values)."""
    try:
        t = tuple(
            sorted((str(k), type(v).__name__, v) for k, v in params.items())
        )
        hash(t)
        return t
    except TypeError:
        return None


def _cache_key(stmt, params) -> Optional[Tuple]:
    # MATCH and (rewritten) SELECT plans are parameter-generic; TRAVERSE
    # bakes parameter values into the plan
    pk = (
        _params_key(params)
        if isinstance(stmt, (A.MatchStatement, A.SelectStatement))
        else _all_values_key(params)
    )
    if pk is None:
        return None
    try:
        key = (stmt, pk)
        hash(key)
        return key
    except TypeError:  # statement holds an unhashable literal
        return None


#: SELECT→MATCH translation verdicts, keyed by statement (translation is
#: parameter-independent). Positive entries skip re-deriving the rewrite
#: on every cache-hit replay; negative entries (the Uncompilable reason)
#: make auto-routed workloads of permanently ineligible shapes (rid
#: lookups, SELECT *, LET) fail fast instead of re-rejecting per query.
_TRANSLATE_CACHE: "OrderedDict" = OrderedDict()
_TRANSLATE_CACHE_MAX = 512


def _translate(stmt):
    """SELECT compiles by rewriting to a single-node MATCH
    (select_compile); MATCH/TRAVERSE pass through."""
    if isinstance(stmt, A.SelectStatement):
        try:
            hashable = True
            verdict = _TRANSLATE_CACHE.get(stmt)
        except TypeError:  # statement holds an unhashable literal
            hashable = False
            verdict = None
        if verdict is not None:
            _TRANSLATE_CACHE.move_to_end(stmt)
            if isinstance(verdict, str):
                raise Uncompilable(verdict)
            return verdict
        from orientdb_tpu.exec.select_compile import rewrite_select

        try:
            out = rewrite_select(stmt)
        except Uncompilable as e:
            if hashable:
                _translate_remember(stmt, str(e))
            raise
        if hashable:
            _translate_remember(stmt, out)
        return out
    return stmt, None


def _translate_remember(stmt, verdict) -> None:
    while len(_TRANSLATE_CACHE) >= _TRANSLATE_CACHE_MAX:
        _TRANSLATE_CACHE.popitem(last=False)
    _TRANSLATE_CACHE[stmt] = verdict


def _record(db, stmt, params):
    """Recording first execution: eager solve with blocking size observes.
    Returns (plan, rows). Holds the trace lock: an eager solve must not
    interleave with a background warm-up's trace (see _TRACE_LOCK).

    The recording runs under the device graph's TOUCH LOG: every array
    key the solve reads becomes the plan's jit-arg subset
    (``arg_keys``), so lazily pruned columns uploading later never
    change a cached plan's pytree structure — and a plan ships only the
    graph arrays it actually uses to its executable."""
    from orientdb_tpu.obs.trace import span as _span

    stmt, element_alias = _translate(stmt)
    snap = db.current_snapshot(require_fresh=True)
    if snap is not None:
        # pin the buffers for the eager solve (see _snapshot_lease)
        snap.retain()
    try:
        return _record_leased(db, stmt, params, snap, element_alias)
    finally:
        if snap is not None:
            snap.release()


def _record_leased(db, stmt, params, snap, element_alias):
    from orientdb_tpu.obs.trace import span as _span

    with _span("tpu.load"):
        # snapshot → HBM upload (CSR + referenced columns); a warm cache
        # makes this span ~free, a cold one shows the real upload cost
        dg = device_graph(snap)
    with _TRACE_LOCK:
        dg.start_touch_log()
        try:
            if isinstance(stmt, A.MatchStatement):
                solver = TpuMatchSolver(
                    db, stmt, params, element_alias=element_alias
                )
                with _span("tpu.solve"):
                    table = solver.solve_table()
                with _span("tpu.marshal"):
                    rows = solver.rows_from_table(table)
                plan: object = _CompiledPlan(solver, table)
            elif not isinstance(stmt, A.TraverseStatement):
                # a SELECT over a TRAVERSE (select_compile.LevelCounts)
                plan = _CompiledLevels(TpuLevelsSolver(db, stmt, params))
                plan.probe()
                with _span("tpu.solve"):
                    rows = plan.record(params)
            else:
                tsolver = TpuTraverseSolver(db, stmt, params)
                with _span("tpu.solve"):
                    idx, total = tsolver.solve()
                with _span("tpu.marshal"):
                    rows = tsolver.rows_from(np.asarray(idx), total)
                plan = _CompiledTraverse(tsolver, total)
        finally:
            keys = dg.stop_touch_log()
        if plan.solver.dg is not dg:
            # a mutation re-attached the snapshot between our device_graph
            # fetch and the solver's own: the reads landed on a DIFFERENT
            # graph than the log watched — fall back to full-dict args
            # (correct, just unpruned) instead of poisoning the plan with
            # an empty subset
            plan.arg_keys = None
        else:
            # an empty log can only mean the reads bypassed this tracker
            # (unexpected): full-dict args are the safe fallback
            plan.arg_keys = keys if keys else None
        return plan, rows


def _prepare(db, stmt, params):
    """Plan-cache lookup, compiling (and executing) on miss.

    Returns ``(variants, None, None)`` on a cache hit — `variants` is the
    MRU-ordered list of schedule variants for this statement — or
    ``(None, rows, plan)`` when this call WAS the recording first
    execution (`plan` is the freshly cached plan with its background AOT
    warm-up started, or None when the statement was uncacheable)."""
    if not isinstance(
        stmt, (A.MatchStatement, A.TraverseStatement, A.SelectStatement)
    ):
        raise Uncompilable(f"{type(stmt).__name__} has no TPU compilation")
    if isinstance(stmt, A.SelectStatement):
        # fail fast on ineligible SELECT shapes BEFORE the miss metric —
        # the negative cache makes repeat rejections O(1)
        _translate(stmt)
    params = params or {}
    snap = db.current_snapshot(require_fresh=True)
    if snap is None:
        raise Uncompilable("no fresh snapshot attached")
    from orientdb_tpu.utils.metrics import metrics

    import orientdb_tpu.obs.stats as _stats

    cache = _plan_cache(snap)
    key = _cache_key(stmt, params)
    if key is not None:
        variants = cache.get(key)
        if variants is not None:
            cache.move_to_end(key)  # LRU: keep hot plans
            metrics.incr("plan_cache.hit")
            _stats.note_plan_cache(True)
            return variants, None, None
    metrics.incr("plan_cache.miss")
    _stats.note_plan_cache(False)
    # the eager recording execution IS the compile cost a caller absorbs
    # on a plan-cache miss: charge it to the query's fingerprint
    import time as _time

    _t0 = _time.perf_counter()
    plan_obj, rows = _record(db, stmt, params)
    _stats.add_compile(_time.perf_counter() - _t0)
    steps = getattr(getattr(plan_obj, "solver", None), "plan", None)
    if steps:
        _stats.note_plan(" -> ".join(s.describe() for s in steps))
    if key is not None and config.plan_cache_size > 0:
        while len(cache) >= config.plan_cache_size:
            cache.popitem(last=False)
        v = PlanVariants(plan_obj)
        v.remember(params, plan_obj)
        cache[key] = v
        # replay-compile off the critical path: rows came from the eager
        # recording, so the XLA compile would otherwise hit the NEXT caller
        plan_obj.ensure_compiled()
        return None, rows, plan_obj
    return None, rows, None


class PlanVariants:
    """Schedule variants for one cached statement, with a sticky
    per-parameter routing map: parameter populations whose live sizes
    cluster differently (e.g. shallow vs deep reply trees) each keep a
    fitting variant, and repeated parameter values dispatch straight to
    the variant that last served them — no retry round trips on the
    steady-state path."""

    __slots__ = ("plans", "by_param")

    _STICKY_MAX = 4096

    def __init__(self, first) -> None:
        self.plans = [first]
        self.by_param: Dict = {}

    @staticmethod
    def _pkey(params):
        try:
            t = tuple(sorted((str(k), str(v)) for k, v in (params or {}).items()))
            hash(t)
            return t
        except TypeError:
            return None

    def pick(self, params):
        plan = self.by_param.get(self._pkey(params))
        return plan if plan in self.plans else self.plans[0]

    def remember(self, params, plan) -> None:
        k = self._pkey(params)
        if k is None:
            return
        if len(self.by_param) >= self._STICKY_MAX:
            self.by_param.clear()
        self.by_param[k] = plan

    def add(self, plan) -> None:
        self.plans.insert(0, plan)
        del self.plans[max(1, config.plan_variants):]
        self.by_param = {
            k: p for k, p in self.by_param.items() if p in self.plans
        }


def _run_variants(
    db, stmt, params, variants: PlanVariants, tried=None, fresh=None
) -> List[Result]:
    """Walk the remaining variants after a miss; when every one overflows,
    record a NEW variant under these parameters. ``tried`` is the plan the
    caller already dispatched and saw overflow from; ``fresh`` (when given)
    collects newly recorded plans so a batch can block on their warm-ups."""
    for plan in list(variants.plans):
        if plan is tried:
            continue
        try:
            rows = plan.rows(params or {})
        except ScheduleOverflow:
            continue
        variants.remember(params, plan)
        return rows
    import time as _time

    import orientdb_tpu.obs.stats as _stats
    from orientdb_tpu.utils.metrics import metrics

    metrics.incr("plan_cache.overflow_rerecord")
    # recompile-due-to-shape: the replay's buffers were too small for
    # these parameters — charge the re-record to the fingerprint
    _t0 = _time.perf_counter()
    plan_obj, rows = _record(db, stmt, params)
    _stats.add_compile(_time.perf_counter() - _t0, rerecord=True)
    variants.add(plan_obj)
    variants.remember(params, plan_obj)
    plan_obj.ensure_compiled()
    if fresh is not None:
        fresh.append(plan_obj)
    return rows


@_contextmanager
def _snapshot_lease(db):
    """Pin the attached snapshot's device buffers for the duration of
    one dispatch: a delta-plane compaction swapping the snapshot
    mid-flight defers its buffer free until the lease drops
    (``GraphSnapshot.retain``/``release``) — the in-flight dispatch
    finishes on the epoch it was admitted under."""
    snap = db.current_snapshot()
    if snap is not None:
        snap.retain()
    try:
        yield snap
    finally:
        if snap is not None:
            snap.release()


def execute(db, stmt, params, sql: Optional[str] = None) -> List[Result]:
    import orientdb_tpu.obs.timeline as _TL

    # flight record for the compiled single-dispatch path (refined to
    # "sharded" by a mesh plan's dispatch); an Uncompilable/overflow
    # escape drops the record uncommitted — only real dispatches ring
    rec = _TL.recorder.begin("single")
    with _TL.active(rec):
        for _attempt in range(4):
            # recording first executions run eagerly on device — the
            # ladder guards them like replays (stage "record")
            variants, rows, _fresh = devicefault.domain.run(
                lambda: _prepare(db, stmt, params),
                db=db,
                sql=sql,
                stage="record",
                passthrough=(ScheduleOverflow,),
            )
            if variants is None:
                break
            plan = variants.pick(params)
            _TL.mark("plan_resolve")
            # pin the plan's snapshot across the dispatch: a delta-plane
            # compaction swapping snapshots mid-flight defers its buffer
            # free until this lease drops (epoch-gated dispatch). A swap
            # landing BETWEEN plan resolution and the pin has already
            # freed this plan's buffers — re-resolve against the new
            # snapshot (try_retain refuses the stale DeviceGraph)
            snap = plan.solver.snap
            if not snap.try_retain(plan.solver.dg):
                metrics.incr("tpu.lease_raced")
                continue
            try:
                # the device fault domain's escalation ladder wraps the
                # whole dispatch+fetch section; ScheduleOverflow is the
                # caller's control flow and passes through untouched
                rows = devicefault.domain.run(
                    lambda: plan.rows(params or {}),
                    db=db,
                    sql=sql,
                    stage="dispatch",
                    passthrough=(ScheduleOverflow,),
                )
                variants.remember(params, plan)
            except ScheduleOverflow:
                rows = devicefault.domain.run(
                    lambda: _run_variants(
                        db, stmt, params, variants, tried=plan
                    ),
                    db=db,
                    sql=sql,
                    stage="dispatch",
                )
            finally:
                snap.release()
            break
        else:
            # four consecutive compaction swaps inside the resolve→pin
            # window: degrade to the oracle rather than crash the query
            raise Uncompilable("snapshot compaction raced plan dispatch")
    _TL.recorder.commit(rec)
    return rows


#: minimum same-plan items in a batch before the vmapped group dispatch
#: pays for its extra compile (per plan per pow2 lane bucket)
_GROUP_MIN = 4


class ParamRing:
    """Device-resident parameter buffers for one dispatch lane.

    A lane's repeated dispatches stack the same dynamic-arg pytree
    shapes over and over — and under steady serving traffic, often the
    same VALUES (hot parameter sets, un-parameterized statements' seed
    arrays). Each distinct stacked value set is ``jax.device_put`` ONCE
    and then reused in place: a dispatch whose host stack matches a
    staged slot ships zero host bytes. Two slots double-buffer the
    ring — the upload for micro-batch N+1 lands in the other slot, so
    it can never overwrite the buffer an in-flight dispatch for batch
    N still reads. Buffers are reused rather than donated: donation
    would invalidate the slot after one Execute and forfeit the reuse
    that makes the steady state transfer-free.

    NOT thread-safe by design: a ring belongs to exactly one lane
    worker thread (the coalesce lane owns it for the plan's lifetime).
    The one cross-thread touch is :meth:`clear` (device fault relief
    dropping staged buffers): a racing ``stage`` at worst misses a hit
    and re-uploads — each slot write is a single list-item assignment.
    """

    __slots__ = ("_slots", "_next", "__weakref__")

    def __init__(self, depth: int = 2) -> None:
        self._slots: List = [None] * max(1, depth)
        self._next = 0
        _PARAM_RINGS.add(self)

    @staticmethod
    def _same(a: Dict, b: Dict) -> bool:
        if a.keys() != b.keys():
            return False
        return all(np.array_equal(a[k], b[k]) for k in a)

    def stage(self, host: Dict):
        """Device form of ``host`` (a dict of stacked numpy arrays):
        the staged copy when a slot's value set matches, a fresh
        explicit upload into the next slot otherwise."""
        import time as _time

        import orientdb_tpu.obs.critpath as _CP
        from orientdb_tpu.obs.timeline import note_ring

        _t0 = _time.perf_counter()
        for slot in self._slots:
            if slot is not None and self._same(slot[0], host):
                metrics.incr("tpu.param_ring.hit")
                note_ring(True)
                _CP.add_segment("ring_hit", _time.perf_counter() - _t0)
                return slot[1]
        devicefault.transfer_point()
        dev = jax.device_put(host)
        _CP.add_segment("param_upload", _time.perf_counter() - _t0)
        nbytes = sum(int(a.nbytes) for a in host.values())
        metrics.incr("tpu.param_ring.upload")
        metrics.incr("tpu.param_ring.bytes", nbytes)
        note_ring(False, nbytes)
        from orientdb_tpu.obs.memledger import memledger

        memledger.register(
            "param_ring",
            f"ring:{id(self):x}",
            f"slot:{self._next}",
            arr=next(iter(dev.values()), None) if dev else None,
            nbytes=nbytes,
            pinned=True,
        )
        self._slots[self._next] = (host, dev)
        self._next = (self._next + 1) % len(self._slots)
        return dev

    def clear(self) -> int:
        """Drop every staged device buffer (a pure cache: the next
        dispatch re-uploads). Returns slots dropped."""
        dropped = 0
        for i in range(len(self._slots)):
            if self._slots[i] is not None:
                self._slots[i] = None
                dropped += 1
        if dropped:
            from orientdb_tpu.obs.memledger import memledger

            memledger.drop_owner("param_ring", f"ring:{id(self):x}")
        return dropped


#: live ParamRings (weak — a reaped coalesce lane's ring just vanishes);
#: the device fault domain's relief drops their staged buffers
_PARAM_RINGS: "weakref.WeakSet" = weakref.WeakSet()


def drop_param_rings() -> int:
    """Device fault relief actuator: drop every lane's staged param
    buffers. Pure cache, so the only cost is re-upload on next use."""
    return sum(ring.clear() for ring in list(_PARAM_RINGS))


class _Group:
    """Stacked device result of a vmapped group dispatch; fetched to
    host ONCE and sliced per lane.

    Row-returning groups additionally carry the stacked [B, C, width]
    data buffer (``data_dev``, from the rows-group replay) or — for the
    no-dyn shared-dispatch case — the single dispatch's page ladder
    (``shared_pages``); the batch fetch elects ONE compact page for the
    whole group after the meta wave."""

    __slots__ = (
        "dev",
        "_np",
        "data_dev",
        "shared_pages",
        "data_np",
        "spec_key",
        "spec_dev",
    )

    def __init__(self, dev, data_dev=None, shared_pages=None) -> None:
        self.dev = dev
        self._np = None
        self.data_dev = data_dev
        self.shared_pages = shared_pages
        self.data_np = None  # host copy of the elected group page
        #: speculative page slice started at dispatch time (group_page
        #: key + device buffer); the election keeps it only on a match
        self.spec_key = None
        self.spec_dev = None

    def arr(self) -> np.ndarray:
        if self._np is None:
            self._np = np.asarray(self.dev)
        return self._np


class _Lane:
    """One lane of a group: `grp.arr()[k]` is this query's meta
    (count-only) or fused buffer slice (direct-fetch). ``k=None`` marks
    a shared single dispatch (no dynamic args — all lanes identical)."""

    __slots__ = ("grp", "k")

    def __init__(self, grp: "_Group", k: Optional[int]) -> None:
        self.grp = grp
        self.k = k

    def meta(self) -> np.ndarray:
        a = self.grp.arr()
        return a if self.k is None else a[self.k]

    def data(self) -> Optional[np.ndarray]:
        d = self.grp.data_np
        if d is None:
            return None
        return d if self.k is None or d.ndim == 2 else d[self.k]


def execute_batch(db, items, sqls: Optional[List[Optional[str]]] = None) -> List:
    """Execute ``[(stmt, params), ...]`` with one overlapped transfer phase.

    The single-chip DP axis (SURVEY.md §5 "replicas = independent query
    streams"): every cached plan dispatches back-to-back, async host
    copies start for all results, and only then does materialization
    block — so N queries cost ~one transfer round trip instead of N.
    Runs of the SAME plan (≥ _GROUP_MIN) collapse further into ONE
    vmapped Execute (`dispatch_many`), amortizing the fixed per-Execute
    cost across the whole group.

    Per-item failures (Uncompilable) are returned in-place as the exception
    instance so the engine front door can fall back per statement."""
    out: List = [None] * len(items)
    prepared = []  # (i, variants, plan, params)
    fresh = []
    # pin every prepared plan's snapshot across the dispatch + fetch
    # waves: a delta-plane compaction swapping snapshots mid-batch
    # defers the old buffers' free until the leases drop
    leases: Dict[int, object] = {}
    try:
        for i, (stmt, params) in enumerate(items):
            try:
                # recording first executions are device work too: the
                # ladder guard degrades an exhausted one per-item
                # (DeviceQuarantined IS an Uncompilable)
                variants, rows, plan_obj = devicefault.domain.run(
                    lambda: _prepare(db, stmt, params),
                    db=db,
                    sql=(sqls[i] if sqls else None),
                    stage="record",
                    passthrough=(ScheduleOverflow,),
                )
            except Uncompilable as e:
                out[i] = e
                continue
            if variants is None:
                out[i] = rows
                if plan_obj is not None:
                    fresh.append(plan_obj)
                continue
            for _attempt in range(4):
                # sticky routing: repeated parameter values dispatch
                # straight to the variant that last served them
                plan = variants.pick(params)
                snap = plan.solver.snap
                # a held lease keeps the snapshot's device cache pinned
                # (deferred free), so a second plan on the same snapshot
                # needs no re-check; a NEW lease must refuse a plan
                # whose DeviceGraph a compaction swap already freed
                if id(snap) in leases or snap.try_retain(plan.solver.dg):
                    leases.setdefault(id(snap), snap)
                    prepared.append((i, variants, plan, params))
                    break
                metrics.incr("tpu.lease_raced")
                try:
                    variants, rows, plan_obj = devicefault.domain.run(
                        lambda: _prepare(db, stmt, params),
                        db=db,
                        sql=(sqls[i] if sqls else None),
                        stage="record",
                        passthrough=(ScheduleOverflow,),
                    )
                except Uncompilable as e:
                    out[i] = e
                    break
                if variants is None:
                    out[i] = rows
                    if plan_obj is not None:
                        fresh.append(plan_obj)
                    break
            else:
                out[i] = Uncompilable(
                    "snapshot compaction raced plan dispatch"
                )
        if not prepared:
            for plan in fresh:
                plan.wait_compiled()
            return out
        try:
            # the escalation ladder wraps the whole dispatch+fetch wave;
            # a retry re-dispatches the prepared plans (reads are
            # idempotent and the leases stay held in the outer finally)
            return devicefault.domain.run(
                lambda: _execute_batch_leased(db, items, out, prepared, fresh),
                db=db,
                sql=(sqls[prepared[0][0]] if sqls else None),
                stage="batch",
                passthrough=(ScheduleOverflow,),
            )
        except devicefault.DeviceQuarantined as e:
            # exhaustion mid-wave: per-item contract — hand the not-yet
            # materialized items the Uncompilable so the front door
            # falls back per statement (completed slots keep their rows)
            for i in range(len(out)):
                if out[i] is None:
                    out[i] = e
            return out
    finally:
        for snap in leases.values():
            snap.release()


def _execute_batch_leased(db, items, out, prepared, fresh) -> List:
    groups: Dict[int, List[int]] = {}
    for j, (_i, _v, plan, _params) in enumerate(prepared):
        if getattr(plan, "batchable", None) is not None and plan.batchable():
            groups.setdefault(id(plan), []).append(j)
    grouped = {
        j for idxs in groups.values() if len(idxs) >= _GROUP_MIN for j in idxs
    }
    pending = []
    for j, (i, variants, plan, params) in enumerate(prepared):
        if j in grouped:
            continue  # dispatched below as a vmapped group
        stmt, _ = items[i]
        try:
            dev = plan.dispatch(params or {})
        except ScheduleOverflow:
            # seed capacity overflow surfaces at dispatch (host-side
            # index probe) — walk the variants now
            out[i] = _run_variants(
                db, stmt, params, variants, tried=plan, fresh=fresh
            )
            continue
        pending.append((i, variants, plan, dev))
    for idxs in groups.values():
        if len(idxs) < _GROUP_MIN:
            continue
        plan = prepared[idxs[0]][2]
        dyns, lanes = [], []
        for j in idxs:
            i, variants, _p, params = prepared[j]
            try:
                dyns.append(plan._dyn_args(params or {}))
                lanes.append(j)
            except ScheduleOverflow:
                out[i] = _run_variants(
                    db, items[i][0], params, variants, tried=plan, fresh=fresh
                )
        if not lanes:
            continue
        g = _group_dispatch(plan, dyns)
        if g is None:
            # vmapped executable still compiling in the background
            # (or permanently unavailable): serve per-lane, with the
            # same overflow walk as the singles path — a seed grown
            # since the group's _dyn_args probe must not fail the batch
            for j in lanes:
                i, variants, _p, params = prepared[j]
                try:
                    pending.append(
                        (i, variants, plan, plan.dispatch(params or {}))
                    )
                except ScheduleOverflow:
                    out[i] = _run_variants(
                        db, items[i][0], params, variants,
                        tried=plan, fresh=fresh,
                    )
            continue
        grp, ks = g
        for k, j in zip(ks, lanes):
            i, variants, _p, _params = prepared[j]
            pending.append((i, variants, plan, _Lane(grp, k)))
    _finish_pending(db, items, pending, out, fresh)
    # a batch returns replay-ready: block on warm-ups this call started so
    # plans recorded here don't leak their XLA compile into the next batch
    for plan in fresh:
        plan.wait_compiled()
    return out


def _group_dispatch(plan, dyns: List[Dict], ring: ParamRing = None):
    """Dispatch B same-plan replays as ONE group. Returns ``(grp, ks)``
    — ``ks[k]`` is each item's index into the stacked result, or None
    for the shared-single-dispatch case — or None while the vmapped
    executable is still compiling (callers dispatch per-lane instead).
    Shared by ``execute_batch``'s same-plan runs and the coalescer's
    lane drains (``dispatch_lane``)."""
    import orientdb_tpu.obs.timeline as _TL

    _TL.note_path("group")
    if not dyns[0]:
        # no dynamic args: every lane is the SAME program on the same
        # inputs — one plain dispatch serves the whole group
        try:
            dev = plan.dispatch({})
        except ScheduleOverflow:
            # a delta landed between the _dyn_args probe and this
            # dispatch (traverse static-replay guard): fall back to the
            # per-lane path, whose overflow handling re-records
            return None
        if isinstance(dev, tuple) and len(dev) == 3 and dev[1]:
            # rows plan: keep the single dispatch's page ladder so
            # the group elects one shared page after the meta wave
            grp = _Group(dev[0], shared_pages=(dev[1], dev[2]))
        else:
            grp = _Group(dev[0] if isinstance(dev, tuple) else dev)
        return grp, [None] * len(dyns)
    dev = plan.dispatch_many(dyns, ring=ring)
    if dev is None:
        return None
    _TL.mark("device_dispatch")
    if isinstance(dev, tuple) and len(dev) == 2 and dev[1] is not None:
        # rows-group replay: (meta stack, data stack)
        grp = _Group(dev[0], data_dev=dev[1])
        # speculative page prefetch: slice + start copying the page the
        # last batch elected while THIS batch's device work runs —
        # served only from an already-compiled page fn AND an exact
        # data-stack shape match (the fn's jit cache keys shapes), so a
        # guess can never absorb an XLA compile
        guess = plan._group_page_guess
        if guess is not None and plan._group_page_shape == tuple(
            dev[1].shape
        ):
            fn = plan.__dict__.get("_group_page_fns", {}).get(guess)
            if fn is not None:
                grp.spec_key = guess
                grp.spec_dev = fn(dev[1])
                _copy_to_host_async(grp.spec_dev)
                metrics.incr("tpu.page_prefetch.start")
                _TL.note_prefetch_start()
    else:
        grp = _Group(dev[0] if isinstance(dev, tuple) else dev)
    return grp, list(range(len(dyns)))


def _finish_pending(db, items, pending, out, fresh) -> None:
    """Fetch + materialize dispatched work: the overlapped meta wave,
    per-query/group page election, and host marshalling, with overflow
    fallbacks walked per item. ``pending`` holds ``(i, variants, plan,
    dev)`` rows dispatched by ``execute_batch`` or a coalesce lane
    (``LaneDispatch``); results land in ``out[i]``."""
    # wave 1: metas (tiny, overlapped) — traverse plans ship their whole
    # payload here since they have no meta/data split
    meta_devs, data_devs = [], []
    for _i, _v, _plan, dev in pending:
        if isinstance(dev, tuple):
            meta_devs.append(dev[0])
            data_devs.append(dev[1:])  # (data32, data16)
        else:
            meta_devs.append(dev)  # bare array, or a group _Lane
            data_devs.append(None)
    # interleaved fetch: the device executes the batch in dispatch order,
    # so each query's meta is read as IT lands (not after the whole batch
    # syncs) and its elected result page starts copying immediately —
    # page transfers overlap the device compute of later queries instead
    # of waiting behind it. Page choice: smallest pre-materialized pow2
    # prefix covering the live count (and a literal LIMIT cuts `need`
    # further); the meta's bit-width flag picks the int16 copy when live
    # values allow, halving the bytes again.
    import time as _time

    from orientdb_tpu.obs.timeline import (
        add_phase as _tl_add_phase,
        note_prefetch as _tl_note_prefetch,
    )
    from orientdb_tpu.obs.memledger import memledger as _ml

    pages_sel: List = [None] * len(pending)
    devicefault.transfer_point()
    seen_groups = set()
    for d in meta_devs:
        # direct-fetch plans ride this same wave: their dev IS the fused
        # single buffer (data + meta row), so one copy covers the query;
        # a group's stacked buffer starts ONE copy for all its lanes
        if isinstance(d, _Lane):
            if id(d.grp) in seen_groups:
                continue
            seen_groups.add(id(d.grp))
            d = d.grp.dev
        _copy_to_host_async(d)
    t0 = _time.perf_counter()
    metas: List = []
    for k, (_i, _v, plan, _dev) in enumerate(pending):
        md = meta_devs[k]
        meta = md.meta() if isinstance(md, _Lane) else np.asarray(md)
        metas.append(meta)
        pair = data_devs[k]
        if pair is None or not pair[0] or meta.ndim != 1 or int(meta[1]):
            continue  # count-only result, traverse payload, or overflow
        f16 = bool(int(meta[2]))
        pages = pair[1] if f16 else pair[0]
        need = plan.fetch_rows_needed(int(meta[0]))
        idx, d = next(
            (i, p) for i, p in enumerate(pages) if int(p.shape[1]) >= need
        )
        # election bookkeeping for the speculative dispatch-time
        # prefetch: a repeat election means the copy started with the
        # dispatch and this async call is a no-op
        if plan._page_guess is not None:
            hit = plan._page_guess == (idx, f16)
            metrics.incr(
                "tpu.page_prefetch.hit" if hit else "tpu.page_prefetch.miss"
            )
            _tl_note_prefetch(hit, int(d.nbytes) if hit else 0)
        plan._page_guess = (idx, f16)
        _copy_to_host_async(d)
        pages_sel[k] = d
        _ml.register("result_page", f"plan:{id(plan):x}", "page", arr=d)
    # rows groups: elect ONE compact page for each group's whole lane
    # stack — a single slice(+int16 cast) Execute and a single host
    # copy replace B per-query ladders (the rows-path floor was
    # per-query dispatch+meta overhead)
    grp_lane_metas: Dict[int, List[np.ndarray]] = {}
    grp_objs: Dict[int, Tuple[_Group, object]] = {}
    for k, (_i, _v, plan, dev) in enumerate(pending):
        if isinstance(dev, _Lane) and (
            dev.grp.data_dev is not None
            or dev.grp.shared_pages is not None
        ):
            grp_lane_metas.setdefault(id(dev.grp), []).append(metas[k])
            grp_objs[id(dev.grp)] = (dev.grp, plan)
    grp_fetch: List[Tuple[_Group, object]] = []
    for gid, lane_metas in grp_lane_metas.items():
        grp, plan = grp_objs[gid]
        needs, fits16 = [], True
        for m in lane_metas:
            if int(m[1]):
                continue  # overflow lane: re-dispatched later anyway
            needs.append(plan.fetch_rows_needed(int(m[0])))
            fits16 = fits16 and bool(int(m[2]))
        if not needs:
            continue
        need = max(max(needs), 1)
        if grp.shared_pages is not None:
            p32, p16 = grp.shared_pages
            pages = p16 if fits16 else p32
            idx, d = next(
                (i, p)
                for i, p in enumerate(pages)
                if int(p.shape[1]) >= need
            )
            # the shared dispatch rode plan.dispatch(): its ladder
            # prefetch reuses the per-query guess
            plan._page_guess = (idx, fits16)
        else:
            key = (
                len(lane_metas),
                plan._page_round(int(grp.data_dev.shape[2]), need),
                fits16,
            )
            if grp.spec_key is not None:
                hit = grp.spec_key == key
                metrics.incr(
                    "tpu.page_prefetch.hit" if hit else "tpu.page_prefetch.miss"
                )
                _tl_note_prefetch(
                    hit, int(grp.spec_dev.nbytes) if hit else 0
                )
            plan._group_page_guess = key
            plan._group_page_shape = tuple(grp.data_dev.shape)
            if grp.spec_key == key:
                d = grp.spec_dev  # copy already in flight since dispatch
            else:
                d = plan.group_page(
                    grp.data_dev, len(lane_metas), need, fits16
                )
        _copy_to_host_async(d)
        _ml.register("result_page", f"grp:{id(grp):x}", "page", arr=d)
        grp_fetch.append((grp, d))
    t1 = _time.perf_counter()
    datas: List = [None] * len(pending)
    nbytes = sum(int(m.nbytes) for m in metas)
    for k, d in enumerate(pages_sel):
        if d is not None:
            a = np.asarray(d)
            datas[k] = a
            nbytes += int(a.nbytes)
    for grp, d in grp_fetch:
        a = np.asarray(d)
        if a.dtype != np.int32:
            a = a.astype(np.int32)
        grp.data_np = a
        nbytes += int(d.nbytes)
    t2 = _time.perf_counter()
    if pending:
        # the part of the caller's turn (a lane worker's lane.finish
        # span) in which the host only waits for the device
        metrics.incr_many(
            {
                "tpu.fetch_wait_us": round((t2 - t0) * 1e6),
                "tpu.bytes_fetched": nbytes,
            }
        )
        # per-fingerprint attribution (obs/stats): a no-op without an
        # active accumulator (the query_batch front door deliberately
        # skips per-item device fiction), but the coalesce lane wraps
        # its collect in stats.capture() and splits this batch-level
        # split across its members
        from orientdb_tpu.obs.stats import add_device

        add_device(t1 - t0, t2 - t1, nbytes)
        _tl_add_phase(t1 - t0, t2 - t1, nbytes)
    overflowed = []
    with timed("tpu.host_s"):
        for k, ((i, variants, plan, dev), meta) in enumerate(
            zip(pending, metas)
        ):
            stmt, params = items[i]
            if isinstance(dev, _Lane) and dev.grp.data_np is not None:
                fetched = (meta, dev.data())  # rows-group lane
            elif isinstance(dev, tuple):
                fetched = (meta, datas[k])
            else:
                fetched = meta
            try:
                out[i] = plan.materialize(fetched, params or {})
                variants.remember(params, plan)
            except ScheduleOverflow:
                overflowed.append((i, variants, plan))
    # overflow fallbacks re-dispatch (and may re-record) whole plans —
    # outside the host-marshalling timer so the phase split stays honest.
    # A homogeneous batch overflows as a COHORT (e.g. a delta landed and
    # every lane's replay outgrew the recorded schedule): identical
    # (statement, params) items share one resolution's rows instead of
    # each paying a lone re-dispatch behind the fresh plan's compile —
    # measured 15x the fallback cost on the mixed read/write bench.
    resolved: Dict[Tuple, object] = {}
    for i, variants, plan in overflowed:
        stmt, params = items[i]
        pk = PlanVariants._pkey(params)
        rk = (id(variants), pk) if pk is not None else None
        if rk is not None and rk in resolved:
            out[i] = resolved[rk]
            continue
        rows = _run_variants(
            db, stmt, params, variants, tried=plan, fresh=fresh
        )
        out[i] = rows
        if rk is not None:
            resolved[rk] = rows


class LaneDispatch:
    """An in-flight homogeneous micro-batch: dispatched on device, not
    yet fetched. The coalescer's lane worker dispatches micro-batch N+1
    (staging its parameters into the lane's :class:`ParamRing`) BEFORE
    collecting batch N — double-buffered dispatch, so batch formation
    and parameter upload overlap the device execution in front of them
    instead of serializing behind it. Carries the dispatch's flight
    record (obs/timeline) across the dispatch→collect gap — the lane
    worker thread runs other work in between, so the record cannot
    stay thread-local."""

    __slots__ = ("db", "items", "pending", "rec", "lease", "sql")

    def __init__(self, db, items, pending, rec=None, lease=None, sql=None) -> None:
        self.db = db
        self.items = items
        self.pending = pending
        self.rec = rec
        #: retained snapshot pinning the dispatched buffers across the
        #: double-buffered dispatch→collect gap (epoch-gated dispatch:
        #: a compaction swap cannot free them while this batch flies)
        self.lease = lease
        #: the lane's fingerprint source text — the device fault
        #: domain's quarantine key if collect's fetch faults out
        self.sql = sql

    def collect(self) -> List:
        """Fetch + marshal the dispatched batch; returns per-item row
        lists in submission order (blocking — the device round trip
        this batch amortizes across its members)."""
        import orientdb_tpu.obs.timeline as _TL

        out: List = [None] * len(self.items)
        fresh: List = []
        try:
            with _TL.active(self.rec):
                # escalation-ladder guard on the fetch/marshal wave; an
                # exhausted fault raises DeviceQuarantined out of
                # collect(), which the coalescer's batch-failure path
                # catches and re-runs per item through the front door
                # (admit gate → oracle while quarantined)
                devicefault.domain.run(
                    lambda: _finish_pending(
                        self.db, self.items, self.pending, out, fresh
                    ),
                    db=self.db,
                    sql=self.sql,
                    stage="lane_collect",
                    passthrough=(ScheduleOverflow,),
                )
        finally:
            if self.lease is not None:
                self.lease.release()
                self.lease = None
        for plan in fresh:
            plan.wait_compiled()
        _TL.recorder.commit(self.rec)
        return out


def dispatch_lane(
    db,
    items,
    ring: ParamRing = None,
    sql: Optional[str] = None,
    enqueue_ts: Optional[float] = None,
    window_s: Optional[float] = None,
    min_epoch: Optional[int] = None,
):
    """Lane-aware dispatch entry: a fingerprint-keyed coalesce lane
    drains a HOMOGENEOUS micro-batch — every item the same statement
    shape — so ONE cached plan serves all of them, with the stacked
    dynamic args staged through the lane's device-resident ``ring``.

    Non-blocking: enqueues the replay(s) on device and returns a
    :class:`LaneDispatch` to collect later, or None when the fast path
    does not apply (no cached plan yet, sticky-variant split, seed
    overflow, vmapped executable still compiling) — the caller falls
    back to the generic batch path, which also handles the recording
    first execution."""
    if db.tx is not None or not items:
        return None
    stmt0, params0 = items[0]
    key = _cache_key(stmt0, params0)
    if key is None:
        return None
    snap = db.current_snapshot(require_fresh=True)
    if snap is None:
        return None
    if min_epoch is not None and db._snapshot_epoch < min_epoch:
        # coalesce-lane epoch keying: an item was admitted AFTER a
        # write this snapshot does not cover — a lane window formed
        # pre-write must not serve that item stale results. The generic
        # path re-resolves freshness (delta catch-up or oracle).
        return None
    cache = _plan_cache(snap)
    variants = cache.get(key)
    if variants is None:
        return None  # recording first execution: generic path records
    cache.move_to_end(key)
    plan = variants.pick(params0)
    if getattr(plan, "batchable", None) is None or not plan.batchable():
        return None
    import orientdb_tpu.obs.timeline as _TL

    # the lane drain's flight record: enqueue (first rider's lane
    # entry) and collection window come from the coalescer; it travels
    # on the LaneDispatch handle because collect() runs later, after
    # the worker double-buffers the next batch
    rec = _TL.recorder.begin("lane", sql=sql, n=len(items))
    if rec is not None:
        if enqueue_ts is not None:
            rec.add_event("enqueue", enqueue_ts)
        if window_s:
            rec.marks["window_s"] = float(window_s)
            rec.add_event("lane_window")
        rec.add_event("plan_resolve")
    dyns = []
    try:
        for stmt, params in items:
            if (stmt is not stmt0 or params is not params0) and _cache_key(
                stmt, params
            ) != key:
                # lanes fold LITERALS into one fingerprint, but plans
                # bake literals (and static params) into the recording:
                # a mixed-literal drain must not replay item[0]'s plan
                # for everyone — the generic path plans each item
                return None
            if variants.pick(params) is not plan:
                # sticky routing split the lane across variants: the
                # generic path groups each variant's run correctly
                return None
            dyns.append(plan._dyn_args(params or {}))
    except ScheduleOverflow:
        return None  # the variant walk belongs to the generic path
    lease = plan.solver.snap
    if not lease.try_retain(plan.solver.dg):
        # compaction swap freed this plan's buffers between resolution
        # and the pin: the generic path re-plans on the new snapshot
        metrics.incr("tpu.lease_raced")
        return None
    handed_off = False
    try:
        try:
            with _TL.active(rec):
                # escalation-ladder guard on the lane's group dispatch;
                # exhaustion degrades this drain to the generic path
                # (whose admit gate serves the quarantined plan from
                # the oracle) rather than failing the whole micro-batch
                g = devicefault.domain.run(
                    lambda: _group_dispatch(plan, dyns, ring=ring),
                    db=db,
                    sql=sql,
                    stage="lane",
                    passthrough=(ScheduleOverflow,),
                )
        except devicefault.DeviceQuarantined:
            return None
        if g is None:
            return None  # group executable still compiling: generic path
        handed_off = True
    finally:
        if not handed_off:
            lease.release()
    grp, ks = g
    pending = [(i, variants, plan, _Lane(grp, k)) for i, k in enumerate(ks)]
    metrics.incr("tpu.lane_dispatch")
    metrics.incr("tpu.lane_items", len(items))
    return LaneDispatch(db, items, pending, rec, lease=lease, sql=sql)


def explain_plan_steps(db, stmt) -> List[str]:
    """Plan description for EXPLAIN (the [E] prettyPrint analog)."""
    solver = TpuMatchSolver(db, stmt, {})
    return [s.describe() for s in solver.plan]


def profile_execute(db, stmt, params) -> Tuple[List[Result], Dict]:
    """Execute on the compiled path with per-phase wall timings — the
    observability PROFILE needs to attack dispatch overhead (SURVEY.md
    §5.1; the whole device solve is ONE fused dispatch, so phases — not
    per-step device kernels — are the honest breakdown).

    Also traces: the returned phases carry ``traceId`` and ``spans`` —
    per-hop TPU-engine stage spans (``tpu.load``/``tpu.step``/
    ``tpu.marshal``). A replay is one fused dispatch with no per-hop
    boundary, so PROFILE re-solves eagerly under the tracer to produce
    them; PROFILE is an explicitly-requested diagnostic, so paying one
    extra eager execution for real timings is the honest trade."""
    import time as _time

    from orientdb_tpu.obs.trace import span as _span, tracer as _tracer

    if db.tx is not None:
        # same guard as engine._run: the snapshot cannot see the tx overlay
        raise Uncompilable("active transaction on this thread")
    phases: Dict[str, object] = {}
    with _span("profile", statement=type(stmt).__name__) as root, (
        _snapshot_lease(db)
    ):
        t0 = _time.perf_counter()
        variants, rows, _fresh = _prepare(db, stmt, params)
        phases["prepareUs"] = round((_time.perf_counter() - t0) * 1e6, 1)
        if variants is None:
            # recording first execution: eager, one blocking sync per
            # observe — the per-hop spans came from solve_table just now
            phases["mode"] = "record"
        else:
            plan = variants.pick(params)
            phases["mode"] = "replay"
            phases["variants"] = len(variants.plans)
            t0 = _time.perf_counter()
            plan.wait_compiled()  # keep a pending AOT compile out of dispatchUs
            phases["compileWaitUs"] = round((_time.perf_counter() - t0) * 1e6, 1)
            t0 = _time.perf_counter()
            with _span("tpu.dispatch"):
                dev = devicefault.domain.run(
                    lambda: plan.dispatch(params or {}),
                    db=db,
                    stage="profile",
                    passthrough=(ScheduleOverflow,),
                )
            phases["dispatchUs"] = round((_time.perf_counter() - t0) * 1e6, 1)
            t0 = _time.perf_counter()
            with _span("tpu.device"):
                devicefault.domain.run(
                    lambda: (
                        devicefault.transfer_point(),
                        jax.block_until_ready(dev),
                    ),
                    db=db,
                    stage="profile",
                )
            phases["deviceUs"] = round((_time.perf_counter() - t0) * 1e6, 1)
            t0 = _time.perf_counter()
            with _span("tpu.marshal"):
                try:
                    rows = devicefault.domain.run(
                        lambda: plan.materialize(dev, params or {}),
                        db=db,
                        stage="profile",
                        passthrough=(ScheduleOverflow,),
                    )
                    variants.remember(params, plan)
                except ScheduleOverflow:
                    rows = _run_variants(db, stmt, params, variants, tried=plan)
                    phases["mode"] = "overflow-variant"
            phases["fetchMarshalUs"] = round(
                (_time.perf_counter() - t0) * 1e6, 1
            )
            solver = plan.solver
            sched = getattr(solver, "sched", None)
            if sched is not None:
                phases["scheduleObserves"] = len(sched.values)
                phases["scheduleSizes"] = sched.values[:32]
            steps = getattr(solver, "plan", None)
            if steps:
                phases["steps"] = [s.describe() for s in steps]
            # the replay has no per-hop boundaries: re-solve eagerly under
            # the tracer so the spans show real per-hop stage timings (a
            # levels plan records through its replay: nothing is eager)
            try:
                if not isinstance(plan, _CompiledLevels):
                    _record(db, stmt, params)
            except Exception as e:  # noqa: BLE001 - diagnostic only
                # rows are already computed; a failing diagnostic
                # re-solve must not fail the PROFILE itself
                phases["traceError"] = f"{type(e).__name__}: {e}"
    phases["traceId"] = root.trace_id
    phases["spans"] = [
        s.to_dict() for s in _tracer.spans(trace_id=root.trace_id)
    ]
    return rows, phases

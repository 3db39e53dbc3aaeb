"""Batched CSR frontier-expansion primitives.

This is the TPU replacement for the reference's per-record hot loop
([E] MatchStep.syncPull → MatchEdgeTraverser.next → ORidBag iteration →
per-RID document load, SURVEY.md §3.3): one `PatternEdge` hop over the whole
frontier becomes a **count → exclusive-scan → rank-search gather** over the
CSR arrays — a handful of fused XLA ops instead of millions of interpreted
iterator pulls.

Shape discipline (XLA wants static shapes): every kernel takes sizes that
are **bucketed to powers of two** (`bucket()`), padding rows carry src=-1
and are masked out, so the jit cache holds O(log n) specializations per
kernel instead of one per distinct frontier size.

Every kernel traces under ``jax.named_scope("csr.<its name>")`` (inside
its ``jit``, so an eager call pays nothing; ``take_pad``, which
dispatches on its index's type before any ``jit``, wears its scope
outside): the operations it becomes
carry ``.../csr.gather_expand/...`` in their HLO ``op_name`` metadata,
under the plan's own scope (``match.replay``, ``exec/tpu_engine``), and
a profiler trace can say which kernel a fusion came from. Trace time
only; the compiled program is the same.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from orientdb_tpu.utils.metrics import metrics

MIN_BUCKET = 8


def bucket(n: int, minimum: int = 0) -> int:
    """Round up to a power of two (≥ minimum, default
    config.min_expansion_cap) to bound the jit cache."""
    if minimum <= 0:
        from orientdb_tpu.utils.config import config

        minimum = max(1, config.min_expansion_cap)
    if n <= minimum:
        return minimum
    return 1 << (n - 1).bit_length()


@jax.jit
@jax.named_scope("csr.degree_counts")
def degree_counts(indptr: jnp.ndarray, srcs: jnp.ndarray) -> jnp.ndarray:
    """Per-source neighbor counts; padding (src=-1) counts 0."""
    valid = srcs >= 0
    s = jnp.where(valid, srcs, 0)
    return jnp.where(valid, jnp.take(indptr, s + 1) - jnp.take(indptr, s), 0)


@jax.jit
@jax.named_scope("csr.exclusive_cumsum")
def exclusive_cumsum(counts: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate(
        [jnp.zeros(1, counts.dtype), value_cumsum(counts)[:-1]]
    )


@partial(jax.jit, static_argnames=("out_size",))
@jax.named_scope("csr.gather_expand")
def gather_expand(
    indptr: jnp.ndarray,
    neighbors: jnp.ndarray,
    srcs: jnp.ndarray,
    offsets: jnp.ndarray,
    total: jnp.ndarray,
    out_size: int,
):
    """Expand every source's CSR slice into flat (row, edge_pos, neighbor).

    `offsets` is the exclusive cumsum of `degree_counts(indptr, srcs)` and
    `total` its sum (device scalar); `out_size` is a static bucket ≥ total.
    Returns int32 arrays of length `out_size`:
      row      — index into `srcs` this output came from (-1 on padding)
      edge_pos — position in CSR edge order (edge-property gathers use this)
      neighbor — the reached vertex (dst for out-CSR, src for in-CSR)
    """
    K = srcs.shape[0]
    pos = jnp.arange(out_size, dtype=jnp.int32)
    valid = pos < total
    # rank search via scatter+cumsum (binary-search gathers serialize badly
    # on TPU; a one-hot scatter then prefix-sum stays on the VPU): mark each
    # row's start offset, then row(pos) = #starts ≤ pos − 1. Zero-count rows
    # share an offset with their successor and never own a position.
    marks = jnp.zeros(out_size, jnp.int32).at[offsets].add(1, mode="drop")
    row = jnp.clip(value_cumsum(marks) - 1, 0, K - 1).astype(jnp.int32)
    src = jnp.take(srcs, row)
    s = jnp.clip(src, 0, indptr.shape[0] - 2)
    edge_pos = jnp.take(indptr, s) + (pos - jnp.take(offsets, row))
    if neighbors.shape[0]:
        edge_pos_c = jnp.clip(edge_pos, 0, neighbors.shape[0] - 1)
        nbr = jnp.take(neighbors, edge_pos_c)
    else:
        nbr = jnp.full((out_size,), -1, jnp.int32)
    row = jnp.where(valid, row, -1)
    edge_pos = jnp.where(valid, edge_pos, -1)
    nbr = jnp.where(valid, nbr, -1)
    return row, edge_pos, nbr


_CS_BLOCK = 256


@jax.named_scope("csr.mask_cumsum")
def mask_cumsum(mask: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of a boolean mask, MXU-shaped.

    XLA's plain cumsum lowers to log-depth reduce-window passes on a
    TPU; a [n/256, 256] reshape turns the intra-block
    scan into ONE triangular matmul on the systolic array (values ≤ 256
    are exact in f32), leaving only the block totals' own (recursively
    blocked) prefix sum for the offsets."""
    n = mask.shape[0]
    B = _CS_BLOCK
    if n < 2 * B or n % B:
        return jnp.cumsum(mask.astype(jnp.int32))
    # intra-block inclusive scans on the MXU (shared with value_cumsum)
    row_cs = _block_scan_f32(mask.astype(jnp.float32)).astype(jnp.int32)
    block_tot = row_cs[:, -1]
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), value_cumsum(block_tot)[:-1]]
    )
    return (row_cs + offs[:, None]).reshape(-1)


@jax.named_scope("csr.block_scan_f32")
def _block_scan_f32(vals_f32: jnp.ndarray) -> jnp.ndarray:
    """[n/B, B] per-block inclusive scans as ONE triangular matmul on
    the systolic array. Exact while every block-local partial stays
    under 2^24 (callers arrange that); cross-block offsets are the
    caller's job — f32 cannot carry graph-scale totals exactly.

    ``Precision.HIGHEST`` is what makes "exact" true on a TPU: at the
    default precision the MXU rounds f32 operands to bf16 (8 significant
    bits), so any input above 256 — a 16-bit half in ``value_cumsum``,
    or the second-level block totals of ``mask_cumsum`` past ~16 M
    elements — came back wrong on the v5e. The CPU computes f32 dots in
    f32 either way, which is why only a chip run could show it."""
    B = _CS_BLOCK
    rows = vals_f32.reshape(-1, B)
    tri = jnp.triu(jnp.ones((B, B), jnp.float32))
    return jnp.dot(rows, tri, precision=jax.lax.Precision.HIGHEST)


@jax.named_scope("csr.value_cumsum")
def value_cumsum(vals: jnp.ndarray, force_blocked: bool = False) -> jnp.ndarray:
    """Inclusive prefix sum of int32/f32 VALUES, MXU-shaped like
    :func:`mask_cumsum` — the COUNT-pushdown weight chain runs this
    over the whole edge list (80M rows at SF100 shape), where XLA's
    log-depth plain cumsum costs about twice the blocked form (PERF.md,
    PR 22).

    int32 stays EXACT on the f32 systolic array by scanning the low
    and high 16-bit halves separately: per-block partials are
    ≤ 256·2^16 < 2^24 (f32-exact), the halves recombine per block as
    ``hi·2^16 + lo`` in int32, and the cross-block offsets accumulate
    in int32 (recursively blocked) — exact for non-negative inputs
    whose total fits int32, which callers overflow-guard already (the
    pushdown's float-twin check). f32 inputs take the matmul path with
    f32 offsets (the overflow twin tolerates its ~1e-7 error); other
    dtypes, short inputs, and the padding tail fall back to plain
    cumsum; non-multiple lengths are zero-padded to a block boundary.

    The matmul path is gated to systolic backends at trace time: CPU's
    native cumsum is linear and memory-bound, so the [n/B, B]·[B, B]
    contraction would only add FLOPs there (backends are baked per
    executable anyway — the read is a trace-time constant by design,
    like the kernel platform itself)."""
    n = vals.shape[0]
    B = _CS_BLOCK
    if n < 2 * B or (jax.default_backend() == "cpu" and not force_blocked):
        return jnp.cumsum(vals)
    if n % B:
        pad = B - (n % B)
        return value_cumsum(jnp.pad(vals, (0, pad)), force_blocked)[:n]
    if vals.dtype == jnp.float32:
        row_cs = _block_scan_f32(vals)
        block_tot = row_cs[:, -1]
        offs = jnp.concatenate(
            [
                jnp.zeros(1, jnp.float32),
                value_cumsum(block_tot, force_blocked)[:-1],
            ]
        )
        return (row_cs + offs[:, None]).reshape(-1)
    if vals.dtype != jnp.int32:
        return jnp.cumsum(vals)
    lo = (vals & 0xFFFF).astype(jnp.float32)  # [0, 2^16)
    hi = (vals >> 16).astype(jnp.float32)  # arithmetic shift: sign rides hi
    row_cs = _block_scan_f32(hi).astype(jnp.int32) * jnp.int32(
        65536
    ) + _block_scan_f32(lo).astype(jnp.int32)
    block_tot = row_cs[:, -1]
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), value_cumsum(block_tot, force_blocked)[:-1]]
    )
    return (row_cs + offs[:, None]).reshape(-1)


@partial(jax.jit, static_argnames=("out_size",))
@jax.named_scope("csr.compact_indices")
def compact_indices(mask: jnp.ndarray, out_size: int) -> jnp.ndarray:
    """Indices of True entries (ascending), -1-padded to the static
    `out_size`.

    Two regimes, chosen statically by shape:
    - selective compactions (out_size ≪ n — point-lookup roots, sparse
      emissions): blocked prefix sum (see mask_cumsum) + out_size binary
      searches. jnp.nonzero here would pay XLA's full-width TPU sort
      (~28 ms per 1M elements — it dominated every compiled plan's
      device time at SF10 scale).
    - dense compactions (out_size comparable to n): nonzero's single
      sort beats out_size·log(n) gather-bound searches."""
    n = mask.shape[0]
    if n == 0:
        return jnp.full(out_size, -1, jnp.int32)
    if out_size * 8 > n:
        (idx,) = jnp.nonzero(mask, size=out_size, fill_value=-1)
        return idx.astype(jnp.int32)
    ranks = mask_cumsum(mask)
    wanted = jnp.arange(1, out_size + 1, dtype=jnp.int32)
    pos = jnp.searchsorted(ranks, wanted, side="left").astype(jnp.int32)
    ok = (pos < n) & (wanted <= ranks[-1])
    return jnp.where(ok, pos, -1)


@dataclass(frozen=True)
class IndexRange:
    """A contiguous index range carried AS a range: positions
    ``[0, size)`` are the indices ``start + pos``, positions
    ``[size, size + slab_size)`` the optional second segment
    ``slab_start + (pos - size)`` (the delta slab a root scan appends to
    its class hull), and the rest, up to the padded ``width``, is ``-1``
    padding.

    A column read through an ``int32`` index array is a gather, and a TPU
    gather of scalars is serial (~26 ns an element on the v5e: PERF.md
    §5); the same read through a range is a slice (:func:`take_range`).
    All fields are static, so the readers dispatch on the index's type
    alone: :func:`take_pad`, ``ops/predicates._column_val`` and the node
    masks of ``exec/tpu_engine``. A consumer that does not know ranges
    takes :func:`as_index`."""

    start: int
    size: int
    width: int
    slab_start: int = 0
    slab_size: int = 0

    def __post_init__(self):
        if min(self.start, self.size, self.slab_start, self.slab_size) < 0:
            raise ValueError(f"negative field in {self}")
        if self.size + self.slab_size > self.width:
            raise ValueError(f"{self} is longer than its width")

    @property
    def shape(self):
        return (self.width,)

    def segments(self):
        """The non-empty ``(start, size)`` segments, in position order."""
        segs = ((self.start, self.size), (self.slab_start, self.slab_size))
        return [(s, z) for s, z in segs if z]

    def materialise(self) -> jnp.ndarray:
        """The ``int32[width]`` index array this range stands for."""
        return _range_at(self, None)


@partial(jax.jit, static_argnames=("rng",))
@jax.named_scope("csr.range_at")
def _range_at(rng: IndexRange, pos) -> jnp.ndarray:
    """The range's indices at positions ``pos`` (``None``: at every
    position in turn); ``-1`` where ``pos`` is negative or in the
    padding: ``take_pad(materialise(), pos, -1)`` as arithmetic. One
    program, so an eager (recording) call holds one output and no
    ``pos``-sized temporaries."""
    if pos is None:
        pos = jnp.arange(rng.width, dtype=jnp.int32)
    idx = rng.start + pos
    if rng.slab_size:
        idx = jnp.where(pos < rng.size, idx, rng.slab_start + (pos - rng.size))
    live = rng.size + rng.slab_size
    return jnp.where((pos >= 0) & (pos < live), idx, -1).astype(jnp.int32)


def as_index(idx) -> jnp.ndarray:
    """``idx`` as the ``int32`` array every consumer understands."""
    return idx.materialise() if isinstance(idx, IndexRange) else idx


def index_at(idx, pos: jnp.ndarray) -> jnp.ndarray:
    """``idx[pos]`` where ``pos ≥ 0``, else ``-1``: arithmetic for a
    range, a gather for an array."""
    if isinstance(idx, IndexRange):
        return _range_at(idx, pos)
    return take_pad(idx, pos, jnp.int32(-1))


def count_read(idx) -> None:
    """Count one column read by how it lowers: ``plan.read.range`` (a
    slice) or ``plan.read.gather``. Counted where Python lowers the read
    (a plan's eager recording, and each trace of its replay); nothing of
    it is in the compiled program."""
    metrics.incr(
        "plan.read.range" if isinstance(idx, IndexRange) else "plan.read.gather"
    )


@partial(jax.jit, static_argnames=("rng",))
@jax.named_scope("csr.take_range")
def take_range(values: jnp.ndarray, rng: IndexRange, fill) -> jnp.ndarray:
    """``take_pad(values, rng.materialise(), fill)`` without the gather:
    each segment is a static slice, the padding is ``fill``. A segment
    that runs past the column's end reads the last element there, as
    ``take_pad``'s clip does; the padding is padded, never re-read."""
    n = values.shape[0]
    fill = jnp.asarray(fill, values.dtype)
    if n == 0:
        return jnp.full(rng.shape, fill, values.dtype)
    parts = []
    for s, z in rng.segments():
        lo, hi = min(s, n), min(s + z, n)
        if hi > lo:
            parts.append(jax.lax.slice(values, (lo,), (hi,)))
        if hi - lo < z:
            parts.append(jnp.broadcast_to(values[n - 1], (z - (hi - lo),)))
    pad = rng.width - rng.size - rng.slab_size
    if pad:
        parts.append(jnp.broadcast_to(fill, (pad,)))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


@jax.jit
def _gather_pad(values: jnp.ndarray, idx: jnp.ndarray, fill) -> jnp.ndarray:
    n = values.shape[0]
    if n == 0:
        return jnp.full(idx.shape, fill, values.dtype)
    ok = idx >= 0
    v = jnp.take(values, jnp.clip(idx, 0, n - 1))
    return jnp.where(ok, v, fill)


@jax.named_scope("csr.take_pad")
def take_pad(values: jnp.ndarray, idx, fill) -> jnp.ndarray:
    """`values[idx]` where idx ≥ 0, else `fill` (padding-safe gather).
    An :class:`IndexRange` is sliced, not gathered."""
    count_read(idx)
    if isinstance(idx, IndexRange):
        return take_range(values, idx, fill)
    return _gather_pad(values, idx, fill)


@jax.jit
@jax.named_scope("csr.mask_count")
def mask_count(mask: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(mask.astype(jnp.int32))


@partial(jax.jit, static_argnames=("out_size", "hull", "unit"))
def _segment_sum(
    vals: jnp.ndarray,
    indptr: jnp.ndarray,
    out_size: int,
    hull: Tuple[int, int],
    unit: bool = False,
) -> jnp.ndarray:
    lo, hi = hull
    if unit:
        # one edge a vertex, and none outside the hull: vertex lo + i
        # holds edge i
        seg = jax.lax.slice(vals, (0,), (hi - lo,))
    else:
        tot = jnp.concatenate([jnp.zeros(1, vals.dtype), value_cumsum(vals)])
        ends = jax.lax.slice(indptr, (lo,), (hi + 1,))
        seg = jnp.take(tot, ends[1:]) - jnp.take(tot, ends[:-1])
    seg = jnp.pad(seg, (lo, max(out_size - hi, 0)))
    return seg[:out_size]


@jax.named_scope("csr.indptr_segment_sum")
def indptr_segment_sum(
    vals: jnp.ndarray,
    indptr: jnp.ndarray,
    out_size: int,
    hull: Optional[Tuple[int, int]] = None,
    unit: bool = False,
) -> jnp.ndarray:
    """Segment sums of CSR-ordered values: cumsum + boundary gathers.

    When values are already ordered by segment (an edge list in CSR
    order), the per-vertex sum is a difference of prefix sums at the
    indptr boundaries — measured ~7x cheaper than the scatter-add
    `segment_sum` lowers to on TPU (2.8 ms vs 0.2+overhead ms at 200k
    rows), and it vmaps as a batched axis-wise scan instead of a
    batched scatter. The prefix sum itself runs MXU-blocked
    (:func:`value_cumsum`): at SF100 scale this cumsum over the 80M-row
    edge list was ~2 s/pass of XLA's log-depth reduce-window — the
    whole r04 two-hop COUNT cliff.

    ``hull = (lo, hi)`` (static; default: every segment) says that the
    segments outside ``[lo, hi)`` are empty, as
    ``ops/device_graph.vertex_hull`` finds it from the pointer array:
    only the hull's boundaries are gathered (a TPU gather of scalars is
    serial, 6-7 ns a boundary) and the other sums are the zeros of a
    static pad. ``unit`` (static; ``ops/device_graph.unit_degree``) says
    that every vertex of the hull holds exactly one edge: the sums are
    then ``vals`` itself, a static slice, and neither the prefix sum nor
    a boundary gather runs. Counted as :func:`count_read` counts, by how
    the pass lowers: ``plan.segsum.unit`` a slice, ``plan.segsum.hull``
    a hull narrower than the pointer array, else ``plan.segsum.full``.
    Result is zero-padded to the static `out_size`."""
    full = (0, indptr.shape[0] - 1)
    hull = full if hull is None else hull
    metrics.incr(
        "plan.segsum.unit"
        if unit
        else "plan.segsum.full" if hull == full else "plan.segsum.hull"
    )
    return _segment_sum(vals, indptr, out_size, hull, unit)


@partial(jax.jit, static_argnames=("vb",))
@jax.named_scope("csr.rows_to_bitmap")
def rows_to_bitmap(rows: jnp.ndarray, vb: int) -> jnp.ndarray:
    """[C] vertex ids (-1 = none) → [C, vb] one-hot frontier bitmap."""
    C = rows.shape[0]
    ok = rows >= 0
    r = jnp.clip(rows, 0, vb - 1)
    return jnp.zeros((C, vb), bool).at[jnp.arange(C), r].max(ok)


@jax.jit
@jax.named_scope("csr.bitmap_hop")
def bitmap_hop(
    act_idx: jnp.ndarray,
    emit_idx: jnp.ndarray,
    edge_mask: jnp.ndarray,
    frontier: jnp.ndarray,
) -> jnp.ndarray:
    """One frontier hop over an edge list as dense bitmaps.

    act_idx/emit_idx [E]: the edge endpoint that must be in the frontier
    and the endpoint reached (swap them to walk edges backwards);
    edge_mask [E] prefilters edges (fused edge-property WHERE);
    frontier [C, vb] per-row bitmaps. The scatter-OR is the SURVEY §5.7
    frontier-bitmap step of variable-depth traversal.
    """
    vb = frontier.shape[1]
    if act_idx.shape[0] == 0:
        return jnp.zeros_like(frontier)
    act = frontier[:, jnp.clip(act_idx, 0, vb - 1)] & edge_mask[None, :]
    emit_c = jnp.clip(emit_idx, 0, vb - 1)
    return jnp.zeros_like(frontier).at[:, emit_c].max(act)


@partial(jax.jit, static_argnames=("num_segments",))
@jax.named_scope("csr.rows_with_matches")
def rows_with_matches(rows: jnp.ndarray, mask: jnp.ndarray, num_segments: int):
    """Per-source-row match counts (OPTIONAL-arm left-join bookkeeping):
    scatter-add 1 for every surviving expansion into its origin row."""
    ok = mask & (rows >= 0)
    r = jnp.where(ok, rows, 0)
    return jax.ops.segment_sum(
        ok.astype(jnp.int32), r, num_segments=num_segments
    )



#: what one pair search returns, in the order of its int32 result
BFS_PARTS = ("len", "levels", "edges", "dense_levels", "overflow")
#: iterations that read one pair's ``chunk`` of frontier edges: a batch's
#: slot space is read a quarter of a pair's buffer at a time, so what a
#: batch costs follows the edges its pairs hold to within that quarter
#: and not the number of pairs that happened to ride together
BFS_STEPS_A_CHUNK = 4


def _bfs_pairs(
    indptr_out, dst, edge_src, indptr_in, src, s, t, *, front: int, chunk: int
):
    """:func:`bfs_pair_len` for ``B`` pairs at once (``s``, ``t``:
    ``int32[B]``; returns ``int32[B, 5]``). The pairs share one slot
    space: an expansion lays the frontier edges of every pair that still
    needs it end to end and reads them ``step`` slots an iteration
    (``chunk / BFS_STEPS_A_CHUNK``), so a batch costs what its pairs'
    frontiers hold together: nothing for a pair already settled, nothing
    for a lane that only pads the batch."""
    i32 = jnp.int32
    B = s.shape[0]
    V = indptr_out.shape[0] - 1
    E = dst.shape[0]
    step = max(chunk // BFS_STEPS_A_CHUNK, 1)
    s, t = s.astype(i32), t.astype(i32)
    ok = (s >= 0) & (t >= 0) & (s < V) & (t < V)
    same = ok & (s == t)
    if E == 0 or V <= 0:
        z = jnp.zeros(B, i32)
        return jnp.stack([jnp.where(same, 0, -1).astype(i32), z, z, z, z], 1)
    ends = jnp.clip(jnp.stack([s, t]), 0, V - 1)
    # one neighbour array for both CSRs: slot e is dst[e], slot E + e src[e]
    nbr_both = jnp.concatenate([dst, src])
    csrs = ((indptr_out, 0), (indptr_in, E))
    F = 2 * front  # one side's list: out-neighbours, then in-neighbours
    slot = jnp.arange(front, dtype=i32)
    lane_base = jnp.arange(B, dtype=i32) * V  # a pair's bitmap in [B * V]

    # bitmaps are built by int32 scatter-adds: the TPU compiler takes half a
    # minute over a bool scatter of this size and a second over an add
    def marked(idx):
        at = jnp.where(idx >= 0, lane_base[:, None] + idx, B * V)
        return jnp.zeros(B * V, i32).at[at.reshape(-1)].add(1, mode="drop") > 0

    def looked_up(bitmap, lane, idx):
        at = jnp.clip(lane, 0, B - 1) * V + jnp.clip(idx, 0, V - 1)
        return jnp.take(bitmap, at) & (idx >= 0)

    lists, n1, over, counts, starts = [], [], jnp.zeros(B, bool), [], []
    for k in (0, 1):
        v = ends[k]
        parts, n = [], jnp.zeros(B, i32)
        for ip, base in csrs:
            lo = jnp.take(ip, v)
            deg = jnp.take(ip, v + 1) - lo
            at = jnp.clip(base + lo[:, None] + slot[None, :], 0, 2 * E - 1)
            got = jnp.take(nbr_both, at)
            parts.append(jnp.where(slot[None, :] < deg[:, None], got, -1))
            n, over = n + deg, over | (deg > front)
        lst = jnp.concatenate(parts, axis=1)  # [B, F]
        lists.append(lst)
        n1.append(n)
        # the list's own edges, as 2 F virtual sources: every member's
        # out-slice, then every member's in-slice
        c = jnp.clip(lst, 0, V - 1)
        cnt, sta = [], []
        for ip, base in csrs:
            lo = jnp.take(ip, c)
            deg = jnp.take(ip, c + 1) - lo
            cnt.append(jnp.where(lst >= 0, deg, 0))
            sta.append(base + lo)
        counts.append(jnp.concatenate(cnt, axis=1))  # [B, 2 F]
        starts.append(jnp.concatenate(sta, axis=1))
    total = jnp.stack([c.sum(axis=1) for c in counts])  # [2, B]
    lane_of = jnp.arange(B, dtype=i32)
    vis = [
        marked(jnp.concatenate([lists[k], ends[k][:, None]], axis=1))
        for k in (0, 1)
    ]
    d1 = jnp.any(lists[0] == t[:, None], axis=1)
    d2 = jnp.any(looked_up(vis[0], lane_of[:, None], lists[1]), axis=1)
    cut = (n1[0] == 0) | (n1[1] == 0)  # an end nothing leads from, or to
    x = total[1] < total[0]  # the side with fewer edges behind its list

    def expansion(active, side_is_1, visit):
        """Read the frontier edges of the ``active`` pairs' lists
        (side 1's where ``side_is_1``), ``step`` slots an iteration;
        ``visit(state, lane, neighbour, first, last)`` folds one
        iteration in, ``first`` / ``last`` being each pair's slots in
        it."""
        cnt = jnp.where(side_is_1[:, None], counts[1], counts[0])
        cnt = jnp.where(active[:, None], cnt, 0).reshape(-1)
        sta = jnp.where(side_is_1[:, None], starts[1], starts[0]).reshape(-1)
        off = exclusive_cumsum(cnt)
        smo = sta - off
        whole = jnp.sum(cnt)
        lane_lo = off[:: 2 * F]
        lane_hi = lane_lo + cnt.reshape(B, -1).sum(axis=1)

        def cond(st):
            return st[0] * step < whole

        def body(st):
            c, state = st
            base = c * step
            rel = off - base
            at = jnp.where((rel > 0) & (rel < step), rel, step)
            begun = jnp.zeros(step, i32).at[at].add(1, mode="drop")
            row = jnp.sum(off <= base) - 1 + value_cumsum(begun)
            row = jnp.clip(row, 0, 2 * F * B - 1)
            pos = base + jnp.arange(step, dtype=i32)
            ep = jnp.take(smo, row) + pos
            nbr = jnp.take(nbr_both, jnp.clip(ep, 0, 2 * E - 1))
            nbr = jnp.where(pos < whole, nbr, -1)
            first = jnp.clip(lane_lo - base, 0, step)
            last = jnp.clip(lane_hi - base, 0, step)
            return c + 1, visit(state, row // (2 * F), nbr, first, last)

        return lambda state: jax.lax.while_loop(cond, body, (i32(0), state))[1]

    def meets(bitmap):
        """Per pair: does a neighbour this iteration read lie in the
        pair's ``bitmap``? The slots are in pair order, so a pair's hits
        are a difference of prefix sums."""

        def visit(found, lane, nbr, first, last):
            hit = looked_up(bitmap, lane, nbr).astype(i32)
            upto = jnp.concatenate([jnp.zeros(1, i32), value_cumsum(hit)])
            return found | (jnp.take(upto, last) > jnp.take(upto, first))

        return visit

    def marks(seen, lane, nbr, first, last):
        at = jnp.where(nbr >= 0, lane * V + nbr, B * V)
        return seen.at[at].add(1, mode="drop")

    none = jnp.zeros(B, bool)
    go3 = ok & ~same & ~over & ~cut & ~d1 & ~d2
    vis_y = jnp.where(
        jnp.repeat(x, V), vis[0], vis[1]
    )  # the bitmap of the side that is not expanded
    d3 = expansion(go3, x, meets(vis_y))(none)
    go4 = go3 & ~d3
    second = expansion(go4, x, marks)(jnp.zeros(B * V, i32))
    d4 = expansion(go4, ~x, meets(second > 0))(none)

    # the rest: whole-graph levels from s
    dense = ok & ~same & ~cut & (over | (go4 & ~d4))

    def hop(f):
        """One level over every edge, as :func:`bitmap_hop` walks it:
        a vertex is reached as often as frontier edges end there."""
        out = jnp.zeros(V, i32)
        out = out.at[dst].add(jnp.take(f, edge_src).astype(i32))
        out = out.at[edge_src].add(jnp.take(f, dst).astype(i32))
        return out > 0

    def levels_from(needed, a, b):
        def cond(st):
            f, _, _, found = st
            return needed & ~found & jnp.any(f)

        def body(st):
            f, seen, k, _ = st
            nxt = hop(f) & ~seen
            return nxt, seen | nxt, k + 1, jnp.take(nxt, b)

        start = jnp.zeros(V, bool).at[a].set(True)
        _, _, k, reached = jax.lax.while_loop(
            cond, body, (start, start, i32(0), jnp.zeros((), bool))
        )
        return k, reached

    k, reached = jax.vmap(levels_from)(dense, ends[0], ends[1])
    sparse = jnp.select([d1, d2, d3, d4], [1, 2, 3, 4], -1)
    length = jnp.where(dense, jnp.where(reached, k, -1), sparse)
    length = jnp.where(same, 0, jnp.where(ok & ~cut, length, -1))
    walked = ok & ~same
    tot_x = jnp.where(x, total[1], total[0])
    tot_y = jnp.where(x, total[0], total[1])
    levels = jnp.where(walked, 2 + go3.astype(i32) + go4.astype(i32) + k, 0)
    read = n1[0] + n1[1] + jnp.where(go3, tot_x, 0) + jnp.where(go4, tot_x + tot_y, 0)
    outgrew = walked & (
        over | (go3 & (tot_x > chunk)) | (go4 & (tot_y > chunk))
    )
    return jnp.stack(
        [
            length.astype(i32),
            levels,
            jnp.where(walked, read, 0),
            k,
            outgrew.astype(i32),
        ],
        axis=1,
    )


@lru_cache(maxsize=None)
def _pair_search(front: int, chunk: int):
    """The search of one pair whose ``vmap`` is the search of a batch:
    under ``vmap`` (the lanes of a group replay) the pairs of all lanes
    go through :func:`_bfs_pairs` together and share its slot space,
    where batching the one-pair program would give every lane, the
    padding lanes too, buffers of its own."""
    kw = dict(front=front, chunk=chunk)

    def plain(ipo, dst, edge_src, ipi, src, s, t):
        return _bfs_pairs(ipo, dst, edge_src, ipi, src, s[None], t[None], **kw)[0]

    one = jax.custom_batching.custom_vmap(plain)

    @one.def_vmap
    def many(axis_size, in_batched, *args):
        if axis_size * (args[0].shape[-1] - 1) >= 1 << 31:
            # the lanes' bitmaps past int32's reach: search lane by lane
            axes = [0 if b else None for b in in_batched]
            return jax.vmap(plain, axes)(*args), True
        s, t = (
            a if b else jnp.broadcast_to(a, (axis_size,))
            for a, b in zip(args[5:], in_batched[5:])
        )
        return _bfs_pairs(*args[:5], s, t, **kw), True

    return one


@partial(jax.jit, static_argnames=("front", "chunk"))
@jax.named_scope("csr.bfs_pair_len")
def bfs_pair_len(
    indptr_out: jnp.ndarray,
    dst: jnp.ndarray,
    edge_src: jnp.ndarray,
    indptr_in: jnp.ndarray,
    src: jnp.ndarray,
    s: jnp.ndarray,
    t: jnp.ndarray,
    *,
    front: int,
    chunk: int,
) -> jnp.ndarray:
    """Length of the shortest path from vertex ``s`` to vertex ``t`` over
    one edge class walked both ways (``BOTH``), as ``int32[5]`` in the order
    of :data:`BFS_PARTS`: the length (0 for ``s == t``, -1 where there is
    no path or an end is -1), the frontiers expanded, the frontier edges
    the sparse levels read, the dense levels run, and whether a frontier
    outgrew its buffer. One pair; under ``vmap`` the pairs of a batch
    are searched together (:func:`_pair_search`).

    A level-synchronous search from both ends whose loops end on the
    device. Its cost follows the frontier, not the graph:

    - both ends' neighbour lists are read as lists (``front`` slots a
      direction) and marked in one bitmap a side: lengths 1 and 2;
    - the side whose list has fewer edges behind it is expanded, a
      quarter of ``chunk`` frontier edges an iteration (count → scan →
      rank → gather, as :func:`gather_expand`, from a slot offset), and
      each neighbour is looked up in the other side's bitmap: length 3.
      A frontier with more than ``chunk`` edges is counted as outgrown
      and takes more iterations, never a wrong answer;
    - where that finds nothing, the same expansion marks the first
      side's second level and the other side's list is expanded against
      it: length 4;
    - what is left (five steps or more, no path, an end with more than
      ``front`` neighbours a direction) runs whole-graph levels from
      ``s``, a gather and a scatter over every edge a direction, until
      ``t`` is reached or the frontier is empty.

    Every stage is a ``lax.while_loop`` over the work that is left (the
    slots of the pairs that still need the stage; "this pair still needs
    a dense level"), so a batch pays for a stage only if one of its
    pairs does: a ``lax.cond`` under ``vmap`` would run both arms for
    every lane."""
    return _pair_search(front, chunk)(indptr_out, dst, edge_src, indptr_in, src, s, t)


#: what one whole-graph search returns beside its counts by depth, in the
#: order of its int32 result
LEVEL_PARTS = ("levels", "dense_levels", "sparse_ends", "overflow", "reached")
#: Beamer's switch: a level runs dense where its frontier holds more than
#: one in this many of the class's 2E edge ends (both directions)
LEVELS_DENSE_ONE_IN = 16
#: the sparse step's buffers as shares of the largest, smallest first: a
#: level takes the smallest that holds its frontier's ends a direction
LEVELS_TIERS = (64, 8, 1)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def levels_caps(num_edges: int) -> Tuple[int, Tuple[int, ...]]:
    """``(threshold, caps)`` of :func:`bfs_levels` for an edge class of
    ``num_edges`` edges, from that number alone (so from the snapshot,
    never from an answer): a level is sparse while its frontier holds at
    most ``threshold = 2E / 16`` edge ends; a sparse step expands each
    direction into a buffer of half that many slots (a pair's direction
    is as good as a coin's, so the ends split evenly), or into a smaller
    one of :data:`LEVELS_TIERS` where that holds them. Buffers are whole
    blocks of the prefix sum once they are long enough to be blocked."""
    threshold = 2 * num_edges // LEVELS_DENSE_ONE_IN
    top = -(-threshold // 2)
    caps = sorted(
        {
            _round_up(c, _CS_BLOCK if c >= 2 * _CS_BLOCK else MIN_BUCKET)
            for c in (max(-(-top // t), MIN_BUCKET) for t in LEVELS_TIERS)
        }
    )
    return threshold, tuple(caps)


@partial(
    jax.jit,
    static_argnames=("threshold", "caps", "slots", "hull_out", "hull_in"),
)
@jax.named_scope("csr.bfs_levels")
def bfs_levels(
    indptr_out: jnp.ndarray,
    dst: jnp.ndarray,
    indptr_in: jnp.ndarray,
    src: jnp.ndarray,
    start: jnp.ndarray,
    *,
    threshold: int,
    caps: Tuple[int, ...],
    slots: int,
    hull_out: Tuple[int, int],
    hull_in: Tuple[int, int],
):
    """A whole-graph breadth-first search over one edge class walked both
    ways, from the vertices of ``start`` (``bool[V]``; none, one or
    several). Returns ``(depth, counts, parts, more)``: ``int32[V]`` the
    depth of every vertex (-1 unreached), ``int32[slots]`` the vertices
    at each depth, ``int32[5]`` in the order of :data:`LEVEL_PARTS` (the
    frontiers expanded, how many of them dense, the edge ends the sparse
    ones held, the sparse ones that outgrew their buffer and ran dense,
    the vertices reached), and whether the search still had a frontier
    when the ``slots`` depths of the table were used up (the caller runs
    it again with a longer table; what it returned is then short).

    One ``lax.while_loop`` whose test is the frontier's population, so
    no level's count crosses to the host. A level's cost follows
    Beamer's switch (``threshold``, ``caps``: :func:`levels_caps`):

    - sparse, the frontier's ends a direction fit a buffer: each
      direction's lists are expanded (count → scan → rank → gather,
      :func:`gather_expand` over every vertex, the others with a count of
      0) into the smallest buffer of ``caps`` that holds them, and the
      ends are marked by an int32 scatter-add;
    - dense: one pass over the class in each stored order, the frontier
      bit gathered at ``src`` in in-order and at ``dst`` in out-order and
      summed by segment over the class's vertex hulls
      (:func:`_segment_sum`): a vertex is reached where an edge's other
      end is in the frontier. No scatter.

    Both mask by unreached. The counts by depth are one reduction over
    ``depth`` after the loop."""
    i32 = jnp.int32
    V = indptr_out.shape[0] - 1
    if V <= 0:
        z = jnp.zeros(0, i32)
        return z, jnp.zeros(slots, i32), jnp.zeros(5, i32), jnp.zeros((), bool)
    degrees = (indptr_out[1:] - indptr_out[:-1], indptr_in[1:] - indptr_in[:-1])
    verts = jnp.arange(V, dtype=i32)
    dirs = ((indptr_out, dst), (indptr_in, src))

    def sparse(cap):
        def step(frontier, counts):
            hit = jnp.zeros(V, i32)
            for (indptr, nbrs), cnt in zip(dirs, counts):
                _, _, nbr = gather_expand(
                    indptr, nbrs, verts, exclusive_cumsum(cnt), jnp.sum(cnt), cap
                )
                # -1 would wrap to the last vertex: V is out of range
                hit = hit.at[jnp.where(nbr >= 0, nbr, V)].add(1, mode="drop")
            return hit > 0

        return step

    def dense(frontier, _counts):
        f = frontier.astype(i32)
        hit = _segment_sum(jnp.take(f, src), indptr_in, V, hull_in)
        hit = hit + _segment_sum(jnp.take(f, dst), indptr_out, V, hull_out)
        return hit > 0

    steps = [sparse(c) for c in caps] + [dense]
    limits = jnp.asarray(caps, i32)

    def cond(st):
        _depth, frontier, level, *_ = st
        return jnp.any(frontier) & (level < slots)

    def body(st):
        depth, frontier, level, dense_n, sparse_ends, outgrew = st
        # the frontier's edge ends, a vertex and a direction
        counts = tuple(jnp.where(frontier, deg, 0) for deg in degrees)
        ends_o, ends_i = (jnp.sum(c) for c in counts)
        few = ends_o + ends_i <= threshold
        tier = jnp.sum(jnp.maximum(ends_o, ends_i) > limits)
        branch = jnp.where(few, tier, len(caps))
        ran_dense = branch == len(caps)
        nxt = jax.lax.switch(branch, steps, frontier, counts) & (depth < 0)
        return (
            jnp.where(nxt, level + 1, depth),
            nxt,
            level + 1,
            dense_n + ran_dense,
            sparse_ends + jnp.where(ran_dense, 0, ends_o + ends_i),
            outgrew + (few & ran_dense),
        )

    zero = i32(0)
    depth, frontier, level, dense_n, sparse_ends, outgrew = jax.lax.while_loop(
        cond,
        body,
        (jnp.where(start, 0, -1).astype(i32), start, zero, zero, zero, zero),
    )
    counts = jnp.sum(
        depth[:, None] == jnp.arange(slots, dtype=i32)[None, :], axis=0, dtype=i32
    )
    parts = jnp.stack(
        [level, dense_n, sparse_ends, outgrew, jnp.sum(depth >= 0, dtype=i32)]
    )
    return depth, counts, parts.astype(i32), jnp.any(frontier)

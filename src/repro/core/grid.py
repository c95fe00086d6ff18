"""Uniform ε-grid: the TPU-native replacement for the paper's hardware BVH.

The paper expands an ε-sphere around every point and lets RT cores build and
traverse a BVH (DESIGN.md §2). DBSCAN only ever issues *fixed*-radius
queries, so on TPU we specialize: bin points into a spatial-hash grid with
cell side ε. A query's candidates are exactly its own cell plus the 8 (2D) /
26 (3D) adjacent cells — a statically-shaped window, no traversal, no
divergence. The hash makes the table size independent of the data extent
(tiny ε over a large domain costs nothing, which is what makes the paper's
NGSIM case fast here too).

Build = quantize → hash → sort → rank (the analogue of the paper's "BVH
build" phase, and timed as such in the benchmarks). Exactness: the hash may
alias far-apart cells into one bucket; aliased candidates are eliminated by
the exact dist² ≤ ε² test in the sweep kernel — the same two-level
structure-prune / exact-refine split as the paper's Algorithm 2 line 6.

``plan_grid`` (host, numpy) fixes the static shape parameters per
(dataset, ε): table size H (pow2) and bucket capacity C = max occupancy, so
the jitted build can never drop a point. The (H, C) padded buffer is the
price of static shapes; plan warns when skew makes it pathological.

This module also provides the **cell-sorted CSR layout** (DESIGN.md §3) that
replaced the (H, C) table as the default engine: points reordered by Morton
cell code, with per-tile contiguous candidate slabs sized by actual local
occupancy — O(n) memory and O(n·window) work instead of O(H·C) and
O(n·27·C_max). See ``plan_csr_grid`` / ``build_csr_grid``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_HASH_K = (np.uint32(73856093), np.uint32(19349663), np.uint32(83492791))


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Static plan for one (dataset, ε). Hashable → safe as a jit static arg."""
    side: float           # cell side (≥ ε)
    origin: tuple         # (3,) domain min, for quantization precision
    table_size: int       # H, power of two
    capacity: int         # C, max points per bucket (measured at plan time)
    dims: int             # 2 or 3 (z ignored for 2D, stored as 0 like the paper)

    @property
    def n_offsets(self) -> int:
        return 9 if self.dims == 2 else 27


class Grid(NamedTuple):
    """Device-side grid buffers (a pytree)."""
    points: jnp.ndarray   # (H, C, 3) f32, padded with +BIG
    index: jnp.ndarray    # (H, C) int32 original point index, -1 padding
    valid: jnp.ndarray    # (H, C) bool
    order: jnp.ndarray    # (n,) int32 sort order (bucket-major)
    bucket: jnp.ndarray   # (n,) int32 bucket id per original point


BIG = 1e30
INT32_MAX = np.iinfo(np.int32).max


def _hash_cells(cx, cy, cz, table_size):
    """Classic spatial hash (Teschner et al.), uint32 wraparound semantics.

    Identical code runs in numpy (plan) and jnp (build) — both wrap uint32.
    """
    xp = jnp if isinstance(cx, jnp.ndarray) else np
    h = (cx.astype(xp.uint32) * _HASH_K[0]
         ^ cy.astype(xp.uint32) * _HASH_K[1]
         ^ cz.astype(xp.uint32) * _HASH_K[2])
    return (h & xp.uint32(table_size - 1)).astype(xp.int32)


def _quantize(points, spec: GridSpec):
    xp = jnp if isinstance(points, jnp.ndarray) else np
    inv = 1.0 / spec.side
    org = xp.asarray(spec.origin, dtype=points.dtype)
    c = xp.floor((points - org) * inv).astype(xp.int32)
    if spec.dims == 2:
        c = c.at[:, 2].set(0) if xp is jnp else _np_zero_z(c)
    return c


def _np_zero_z(c):
    c = c.copy()
    c[:, 2] = 0
    return c


def plan_grid(points_np: np.ndarray, eps: float, *, dims: int = 3,
              target_occupancy: float = 8.0, capacity_round: int = 8,
              max_table_size: int = 1 << 22) -> GridSpec:
    """Host-side planning pass: fixes H and C so the jitted build is exact.

    This is the analogue of OptiX sizing its BVH before the build; it is a
    single O(n) numpy pass (quantize + bincount).
    """
    n = len(points_np)
    origin = tuple(float(v) for v in points_np.min(axis=0))
    table_size = 1 << max(6, math.ceil(math.log2(max(n / target_occupancy, 1.0))))
    table_size = min(table_size, max_table_size)
    spec = GridSpec(side=float(eps), origin=origin, table_size=table_size,
                    capacity=0, dims=dims)
    c = _quantize(points_np.astype(np.float32), spec)
    h = _hash_cells(c[:, 0], c[:, 1], c[:, 2], table_size)
    occ = np.bincount(h, minlength=table_size)
    cap = int(occ.max()) if n else 1
    cap = max(capacity_round, ((cap + capacity_round - 1) // capacity_round)
              * capacity_round)
    if table_size * cap > 64 * max(n, 1):
        # Pathological skew: one bucket holds a large fraction of the data,
        # and every query pays its capacity. Irreducible candidate work for
        # exact DBSCAN (the paper's DenseBox-excluded regime) — we keep
        # going, but the caller should know the footprint and consider the
        # CSR engine (engine="grid"), whose memory stays O(n).
        warnings.warn(
            f"plan_grid: skewed occupancy — max bucket holds {occ.max()} of "
            f"{n} points, so the (H, C) table is ({table_size}, {cap}) = "
            f"{table_size * cap} slots ({table_size * cap / max(n, 1):.1f}x "
            f"the point count) and every query sweeps "
            f"{9 if dims == 2 else 27} x {cap} candidates; the cell-sorted "
            "CSR engine (engine='grid') avoids this blow-up",
            RuntimeWarning, stacklevel=2)
    return dataclasses.replace(spec, capacity=cap)


def build_grid(points: jnp.ndarray, spec: GridSpec) -> Grid:
    """Jitted grid build (sort-based). points (n, 3) f32."""
    n = points.shape[0]
    c = _quantize(points, spec)
    bucket = _hash_cells(c[:, 0], c[:, 1], c[:, 2], spec.table_size)
    order = jnp.argsort(bucket, stable=True).astype(jnp.int32)
    bsorted = bucket[order]
    # first slot of each bucket in the sorted array
    start = jnp.searchsorted(bsorted, jnp.arange(spec.table_size, dtype=bsorted.dtype),
                             side="left").astype(jnp.int32)
    rank = jnp.arange(n, dtype=jnp.int32) - start[bsorted]
    H, C = spec.table_size, spec.capacity
    gpoints = jnp.full((H, C, 3), BIG, jnp.float32)
    gindex = jnp.full((H, C), -1, jnp.int32)
    gvalid = jnp.zeros((H, C), bool)
    psorted = points[order]
    gpoints = gpoints.at[bsorted, rank].set(psorted, mode="drop")
    gindex = gindex.at[bsorted, rank].set(order, mode="drop")
    gvalid = gvalid.at[bsorted, rank].set(True, mode="drop")
    return Grid(points=gpoints, index=gindex, valid=gvalid, order=order,
                bucket=bucket)


# ---------------------------------------------------------------------------
# Cell-sorted CSR layout (DESIGN.md §3)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CSRGridSpec:
    """Static plan for the cell-sorted CSR engine. Hashable → jit-static.

    ``side`` may exceed ε when the extent saturates the Morton bit budget
    (coarser cells keep the ±1 window exact since side ≥ ε). The top cell
    index per axis is reserved for padding, so padded candidates can never
    enter a real query's window.
    """
    side: float           # cell side (≥ ε)
    origin: tuple         # (3,) domain min
    dims: int             # 2 or 3
    bits: int             # Morton bits per axis (15 for 2D, 10 for 3D)
    chunk: int            # queries per sweep tile
    block_k: int          # candidate block granularity (slab quantum)
    n: int                # real point count
    n_tiles: int          # T = ceil(n / chunk)
    slab: int             # per-tile slab capacity (elements, mult. block_k)
    n_cand: int           # padded sorted-candidate length (mult. block_k)

    @property
    def n_offsets(self) -> int:
        return 9 if self.dims == 2 else 27

    @property
    def max_real_cell(self) -> int:
        return (1 << self.bits) - 3


class CSRGrid(NamedTuple):
    """Device-side CSR grid buffers (a pytree). All layouts are *sorted*:
    position s holds the point with the s-th smallest Morton cell code."""
    order: jnp.ndarray    # (n,) int32: sorted position -> original index
    q_sorted: jnp.ndarray  # (T*chunk, 3) f32 sorted queries, edge-padded
    cands: jnp.ndarray    # (3, n_cand) f32 planar sorted candidates, +BIG pad
    starts: jnp.ndarray   # (T,) int32 slab starts (elements, mult. block_k)
    nblk: jnp.ndarray     # (T,) int32 live blocks per tile slab
    overflow: jnp.ndarray  # () bool: a tile's window outgrew the planned slab
    codes: jnp.ndarray    # (n,) int32 sorted Morton cell codes — the search
    #                       structure cross-corpus queries bisect (§10)


def csr_cells(points: jnp.ndarray, side: float, origin: tuple, dims: int,
              bits: int) -> jnp.ndarray:
    """Quantized cell coords, clipped to the real-cell range
    [0, 2^bits - 3]. The two top indices stay free: 2^bits - 2 for clipped
    window neighbors, 2^bits - 1 reserved for padding sentinels."""
    inv = 1.0 / side
    org = jnp.asarray(origin, points.dtype)
    c = jnp.floor((points - org) * inv).astype(jnp.int32)
    c = jnp.clip(c, 0, (1 << bits) - 3)
    if dims == 2:
        c = c.at[:, 2].set(0)
    return c


def _csr_window_codes(cells, dims: int, bits: int):
    """(W, m) int32 Morton codes of each cell's 9 (2-D) / 27 (3-D) window
    cells, clipped to the real-cell range plus the one free index above it
    (``2^bits - 2``, never occupied)."""
    from ..kernels import ref as _kref
    rng = (-1, 0, 1)
    offs = jnp.asarray([(dx, dy, dz) for dx in rng for dy in rng
                        for dz in (rng if dims == 3 else (0,))], jnp.int32)
    nb = jnp.clip(cells[None, :, :] + offs[:, None, :], 0, (1 << bits) - 2)
    w, m = nb.shape[:2]
    return _kref.morton_encode_ref(nb.reshape(w * m, 3),
                                   dims=dims).reshape(w, m)


def _csr_window_bounds(sorted_codes, cells, dims: int, bits: int):
    """Per query cell: [lo, hi) positions in the code-sorted corpus covering
    the occupied runs of all 9/27 window cells, by binary search. Empty
    window cells are excluded (their searchsorted insertion point would
    needlessly widen the slab).

    For queries that are *not* the corpus: cross-corpus queries (DESIGN.md
    §10) pass fresh query cells bisected against the frozen
    ``sorted_codes`` — the returned bounds have ``cells``'s length, not the
    corpus's. With m ≪ n queries, W·m small gathers beat any pass over the
    corpus; the self-join (m = n) uses :func:`_csr_self_bounds`.
    """
    n = sorted_codes.shape[0]
    m = cells.shape[0]
    lo = jnp.full((m,), n, jnp.int32)
    hi = jnp.zeros((m,), jnp.int32)
    for code in _csr_window_codes(cells, dims, bits):
        left = jnp.searchsorted(sorted_codes, code, side="left").astype(
            jnp.int32)
        right = jnp.searchsorted(sorted_codes, code, side="right").astype(
            jnp.int32)
        occupied = right > left
        lo = jnp.minimum(lo, jnp.where(occupied, left, n))
        hi = jnp.maximum(hi, jnp.where(occupied, right, 0))
    return lo, hi


def _csr_self_bounds(sorted_codes, sorted_cells, dims: int, bits: int):
    """The self-join's window bounds: :func:`_csr_window_bounds` of the
    corpus against itself, bit for bit, by one merge instead of 2·W binary
    searches (DESIGN.md §3.2).

    All W·n window codes (key ``2·code``) and the n corpus codes (key
    ``2·code + 1``; codes are below 2^30, so keys fit int32) are sorted
    together. A window key then precedes the corpus run of its own code:
    the corpus keys before it count ``left``, the corpus keys up to the end
    of its code's group count ``right``, and the cell is occupied iff
    ``right > left``. A second sort, on the origin index, returns the
    per-window bounds to (W, n) order for the min/max over each point's
    window. No binary search: two sorts and two scans of W·n + n keys.
    """
    n = sorted_codes.shape[0]
    wcodes = _csr_window_codes(sorted_cells, dims, bits)
    nw = wcodes.size
    keys = jnp.concatenate([wcodes.reshape(-1) * 2, sorted_codes * 2 + 1])
    src = jnp.arange(nw + n, dtype=jnp.int32)
    keys, src = jax.lax.sort((keys, src), num_keys=1, is_stable=False)
    in_corpus = keys & 1
    seen = jnp.cumsum(in_corpus)          # corpus keys up to here
    code = keys >> 1
    group_end = jnp.concatenate([code[1:] != code[:-1],
                                 jnp.ones((1,), bool)])
    # corpus keys up to the end of this key's code group: seen at the first
    # group end at or after here (seen never decreases)
    right = jax.lax.cummin(jnp.where(group_end, seen, n), reverse=True)
    left = seen - in_corpus
    occupied = right > left
    lo_w = jnp.where(occupied, left, n)
    hi_w = jnp.where(occupied, right, 0)
    # back to (W, n) order: a second sort, keyed on the origin index, runs
    # faster on the chip than a scatter of the same permutation
    _, lo_w, hi_w = jax.lax.sort((src, lo_w, hi_w), num_keys=1,
                                 is_stable=False)
    return (lo_w[:nw].reshape(wcodes.shape).min(axis=0),
            hi_w[:nw].reshape(wcodes.shape).max(axis=0))


@functools.partial(jax.jit,
                   static_argnames=("side", "origin", "dims", "bits"))
def _csr_layout(points, side: float, origin: tuple, dims: int, bits: int):
    """Shared sort-by-cell pass: the plan and the build run this one
    program, so the plan's slab capacity is valid for the build — the CSR
    analogue of plan_grid's exactness contract — and a build compiles it
    once per shape, at plan time."""
    from ..kernels import ref as _kref
    cells = csr_cells(points, side, origin, dims, bits)
    codes = _kref.morton_encode_ref(cells, dims=dims)
    order = jnp.argsort(codes).astype(jnp.int32)
    sorted_codes = codes[order]
    lo, hi = _csr_self_bounds(sorted_codes, cells[order], dims, bits)
    return order, points[order], lo, hi, sorted_codes


def tile_slabs(lo, hi, n: int, *, n_tiles: int, chunk: int, block_k: int,
               slab: int, n_cand: int):
    """Reduce per-query window bounds to per-tile slab (start, nblk).

    Queries beyond ``n`` are edge-repeated; callers with interleaved padding
    (the distributed engine) pre-mask pad entries to (lo=n, hi=0) so they
    drop out of the tile min/max. ``overflow`` fires when a tile's window
    outgrows the static ``slab`` capacity.
    """
    bk = block_k
    pad_idx = jnp.minimum(jnp.arange(n_tiles * chunk, dtype=jnp.int32),
                          max(n - 1, 0))
    lo_t = lo[pad_idx].reshape(n_tiles, chunk).min(axis=1)
    hi_t = hi[pad_idx].reshape(n_tiles, chunk).max(axis=1)
    start = jnp.clip((lo_t // bk) * bk, 0, n_cand - slab)
    need = hi_t - start
    overflow = jnp.any(need > slab)
    nblk = jnp.clip((need + bk - 1) // bk, 0, slab // bk)
    return start.astype(jnp.int32), nblk.astype(jnp.int32), overflow


def slab_payload_min(payload, starts, nblk, *, block_k: int,
                     max_blocks: int):
    """Per-tile min of ``payload`` over the tile's live slab blocks.

    payload (n_cand,) int32 — sorted-layout plane (INT32_MAX padding);
    returns (T,) int32. One block-granular reduce (reshape + min), then a
    sparse table of block-range minima (level k covers 2^k blocks) answers
    each tile's ``[start, start + nblk)`` range with two overlapping
    lookups — O(n_cand + n_blocks·log max_blocks + T), far below one sweep,
    and a program whose size does not grow with ``max_blocks`` (one
    unrolled gather per block compiles for minutes on the chip at 10⁶
    points). Used by the frontier round driver's live-tile test
    (DESIGN.md §11).
    """
    nb_tot = payload.shape[0] // block_k
    blk_min = payload.reshape(nb_tot, block_k).min(axis=1)
    max_span = max(min(max_blocks, nb_tot), 1)
    tab = [blk_min]
    while (1 << len(tab)) <= max_span:
        h = 1 << (len(tab) - 1)
        prev = tab[-1]
        tab.append(jnp.minimum(prev, jnp.concatenate(
            [prev[h:], jnp.full((h,), INT32_MAX, prev.dtype)])))
    tab = jnp.stack(tab)
    lo = jnp.clip(starts // block_k, 0, nb_tot - 1).astype(jnp.int32)
    span = jnp.clip(nblk, 1, max_span).astype(jnp.int32)
    k = 31 - jax.lax.clz(span)
    hi = jnp.clip(lo + span - (1 << k), 0, nb_tot - 1)
    out = jnp.minimum(tab[k, lo], tab[k, hi])
    return jnp.where(nblk > 0, out, INT32_MAX).astype(jnp.int32)


def slab_touched(flags, starts, nblk, n: int, *, block_k: int):
    """Per-tile "any flagged point in my slab" — the dirty-block test.

    flags (n,) bool in sorted layout; returns (T,) bool. Slabs are whole
    blocks (``starts`` is a multiple of ``block_k``), so one any-reduce per
    block and a prefix sum over the *blocks* give an O(T) two-gather range
    count per tile's contiguous slab ``[starts, starts + nblk·block_k)`` —
    no new data structure, the CSR plan's slab bounds are the ranges
    (DESIGN.md §11). The prefix sum runs over n / block_k entries, not n:
    a point-length one compiles for seconds on the chip.
    """
    nb_tot = -(-n // block_k)
    blk = jnp.zeros((nb_tot * block_k,), bool).at[:n].set(flags) \
        .reshape(nb_tot, block_k).any(axis=1)
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(blk.astype(jnp.int32))])
    lo = jnp.clip(starts // block_k, 0, nb_tot)
    hi = jnp.clip(starts // block_k + nblk, 0, nb_tot)
    return cum[hi] > cum[lo]


def compact_tiles(live):
    """Compact live tile ids to the front: (active (T,) int32, n_live ()).

    Entries at positions >= n_live repeat the last live id (0 when none),
    so a kernel walking ``active`` parks on resident blocks — the contract
    ``kernels/frontier_sweep.py`` documents.
    """
    T = live.shape[0]
    idx = jnp.arange(T, dtype=jnp.int32)
    n_live = live.sum().astype(jnp.int32)
    pos = jnp.cumsum(live.astype(jnp.int32)) - 1
    active = jnp.zeros((T,), jnp.int32).at[
        jnp.where(live, pos, T)].set(idx, mode="drop")
    park = active[jnp.clip(n_live - 1, 0, T - 1)]
    return jnp.where(idx < n_live, active, park), n_live


def plan_csr_grid(points, eps: float, *, dims: int = 3,
                  chunk: int = 256, block_k: int = 512,
                  margin_blocks: int = 1) -> CSRGridSpec:
    """Planning pass for the CSR engine.

    Runs the same sort-by-cell layout the device build runs and measures the
    worst per-tile slab extent, so the jitted build/sweep shapes are static
    yet sized by *actual* occupancy (one O(n log n) pass). ``side`` grows
    beyond ε only when the extent exceeds the Morton bit budget.
    ``points`` is a host or a device array; planning on the array the build
    will get lets the build reuse the layout program compiled here.
    """
    n = len(points)
    assert n >= 1, "plan_csr_grid needs at least one point"
    pts = np.asarray(points, np.float32)
    origin = tuple(float(v) for v in pts.min(axis=0))
    bits = 15 if dims == 2 else 10
    ext = float((pts.max(axis=0) - pts.min(axis=0))[:dims].max())
    side = float(eps)
    max_cells = (1 << bits) - 2
    if math.floor(ext / side) + 1 > max_cells:
        side = ext / (max_cells - 1) * (1 + 1e-5)
    _, _, lo, hi, _ = _csr_layout(jnp.asarray(points, jnp.float32), side,
                                  origin, dims, bits)
    lo, hi = np.asarray(lo), np.asarray(hi)
    T = max(1, -(-n // chunk))
    pad_idx = np.minimum(np.arange(T * chunk), n - 1)
    lo_t = lo[pad_idx].reshape(T, chunk).min(axis=1)
    hi_t = hi[pad_idx].reshape(T, chunk).max(axis=1)
    need = int((hi_t - (lo_t // block_k) * block_k).max())
    slab = -(-max(need, 1) // block_k) * block_k + margin_blocks * block_k
    n_cand = max(-(-n // block_k) * block_k, slab)
    return CSRGridSpec(side=side, origin=origin, dims=dims, bits=bits,
                       chunk=chunk, block_k=block_k, n=n, n_tiles=T,
                       slab=slab, n_cand=n_cand)


def build_csr_grid(points: jnp.ndarray, spec: CSRGridSpec) -> CSRGrid:
    """CSR build: sort by cell code (the plan's own layout program, already
    compiled for this shape when the spec was planned here), then derive
    per-tile slabs in a second program.

    The ``overflow`` flag guards the plan/build parity contract (it fires
    only if device quantization disagrees with the host plan beyond the
    slab margin — callers should assert it is False once per build).
    """
    return _csr_pack(*_csr_layout(points, spec.side, spec.origin, spec.dims,
                                  spec.bits), spec=spec)


@functools.partial(jax.jit, static_argnames=("spec",))
def _csr_pack(order, spoints, lo, hi, codes, spec: CSRGridSpec) -> CSRGrid:
    n = order.shape[0]
    starts, nblk, overflow = tile_slabs(
        lo, hi, n, n_tiles=spec.n_tiles, chunk=spec.chunk,
        block_k=spec.block_k, slab=spec.slab, n_cand=spec.n_cand)
    pad_idx = jnp.minimum(jnp.arange(spec.n_tiles * spec.chunk,
                                     dtype=jnp.int32), n - 1)
    q_sorted = spoints[pad_idx]
    cands = jnp.full((spec.n_cand, 3), BIG, jnp.float32).at[:n].set(spoints)
    return CSRGrid(order=order, q_sorted=q_sorted, cands=cands.T,
                   starts=starts, nblk=nblk, overflow=overflow, codes=codes)


def neighbor_buckets(points: jnp.ndarray, spec: GridSpec) -> tuple:
    """Per-point candidate window: bucket ids of the 9/27 adjacent cells.

    Returns (buckets (n, OFF) int32, cell_valid (n, OFF) bool) where
    duplicated bucket ids within a row (hash aliasing of distinct offsets)
    are masked out to avoid double counting.
    """
    c = _quantize(points, spec)
    rng = (-1, 0, 1)
    offs = [(dx, dy, dz) for dx in rng for dy in rng
            for dz in (rng if spec.dims == 3 else (0,))]
    offs = jnp.asarray(offs, jnp.int32)  # (OFF, 3)
    cells = c[:, None, :] + offs[None, :, :]  # (n, OFF, 3)
    b = _hash_cells(cells[..., 0], cells[..., 1], cells[..., 2], spec.table_size)
    # mask duplicate buckets within each row (sort, compare to predecessor)
    srt = jnp.sort(b, axis=1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros((b.shape[0], 1), bool), srt[:, 1:] == srt[:, :-1]], axis=1)
    # map duplicate-ness back: a bucket value is kept exactly once per row
    # (the first occurrence in sorted order); we recompute per original slot:
    # slot is a duplicate iff some earlier slot (in sorted tie order) has the
    # same value. Implement via argsort inverse.
    sidx = jnp.argsort(b, axis=1, stable=True)
    inv = jnp.argsort(sidx, axis=1, stable=True)
    dup = jnp.take_along_axis(dup_sorted, inv, axis=1)
    return b, ~dup

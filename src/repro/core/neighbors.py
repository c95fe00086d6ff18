"""Neighbor-search engines: the RT-FindNeighbor primitive, TPU edition.

An *engine* answers the paper's fused sweep query (DESIGN.md §2):

    sweep(state, core, root) -> (counts, minroot)

    counts[i]  = |{ j : ‖p_i − p_j‖² ≤ ε² }|          (self included)
    minroot[i] = min{ root[j] : j ε-neighbor of i, core[j] }  (INT_MAX if none)

Engines (all dispatched through the capability registry in
``repro.core.engines`` — one table, no per-call-site ``if engine ==``
chains):

  * ``brute``     — tiled all-pairs sweep (Pallas ``pairwise_sweep``). O(n²)
    work at roofline VPU efficiency; right answer below ~10⁵ points.
  * ``grid``      — cell-sorted CSR ε-grid (DESIGN.md §3; Pallas
    ``csr_sweep`` inner loop): points reordered by Morton cell code, query
    tiles sweep contiguous candidate slabs sized by actual local occupancy.
    O(n · window) work, O(n) memory. The default.
  * ``grid-hash`` — capacity-padded spatial-hash ε-grid (the previous
    default; Pallas ``gathered_sweep`` inner loop). O(n · 27 · C_max) work
    and O(H · C) memory — retained for comparison benchmarks and as a
    fallback where the CSR plan's Morton bit budget is too coarse.
  * ``bvh``       — LBVH with *wavefront* traversal (DESIGN.md §9; Pallas
    ``bvh_sweep`` level kernel, ``repro.core.bvh``): a level-compacted
    (query, node) work queue instead of per-query stacks, so traversal cost
    tracks total overlap work rather than the worst query. Sorted-layout
    fast path over the Morton-ordered leaves.
  * ``bvh-stack`` — LBVH with lockstep per-query stack traversal (the
    mechanical port of the paper's structure; FDBSCAN baseline and
    divergence benchmark).

All sweep functions are pure in their ``state`` pytree so they can be jitted
once and reused across DBSCAN rounds; factories are cached so repeated runs
(the paper's multi-run use case, §VI-B) do not recompile. Engines that
expose ``sweep_sorted`` (payloads already in sorted layout: CSR grid,
wavefront BVH) let the DBSCAN round driver stay in sorted order across
hooking rounds (DESIGN.md §5); engines that expose ``neighbors`` back the
``find_neighbors`` library op (DESIGN.md §6).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..kernels import ops
from . import engines
from . import grid as grid_mod
from .engines import Engine, make_engine  # re-export (public API)  # noqa: F401

INT_MAX = jnp.iinfo(jnp.int32).max
BIG = grid_mod.BIG

# Canonical bound on every overflow → double-slab-and-retrace loop (serve
# assign/ingest, distributed restarts): a slab doubles at most this many
# times before the caller must raise a CapacityError naming the final
# capacity instead of regrowing again. log2(n_cand/slab) doublings always
# suffice structurally; the cap exists so a pathological query
# distribution (or a fault-injected overflow flag) terminates with a
# diagnosable error rather than an unbounded recompile storm.
MAX_SLAB_REGROW = 8


class GridState(NamedTuple):
    grid: grid_mod.Grid
    buckets: jnp.ndarray             # (n, OFF) int32
    cell_valid: jnp.ndarray          # (n, OFF) bool
    points: jnp.ndarray              # (n, 3) f32 (original order)


def infer_dims(points_np: np.ndarray) -> int:
    """Data dimensionality: the column count, except for the paper's 3-col
    convention where 2D data rides in (n, 3) arrays with z = 0."""
    d = points_np.shape[1]
    if d != 3:
        return d
    return 2 if np.all(points_np[:, 2] == 0) else 3


def _pad0(x, n_pad, value):
    pad = n_pad - x.shape[0]
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=value)


def _topk_neighbor_ids(hit, cand_idx, k_max: int):
    """Shared tail of every neighbor-list body: ascending ids of the hits,
    -1 padded to ``k_max`` columns, plus exact per-row counts."""
    key = jnp.where(hit, cand_idx, INT_MAX)
    if key.shape[1] < k_max:
        key = jnp.pad(key, ((0, 0), (0, k_max - key.shape[1])),
                      constant_values=INT_MAX)
    key = jnp.sort(key, axis=1)[:, :k_max]
    cnt = hit.sum(axis=1).astype(jnp.int32)
    return jnp.where(key == INT_MAX, -1, key).astype(jnp.int32), cnt


@functools.lru_cache(maxsize=64)
def _grid_sweep_fn(spec: grid_mod.GridSpec, eps2: float, chunk: int,
                   backend: str | None):
    off = spec.n_offsets
    cap = spec.capacity

    @jax.jit
    def sweep(state: GridState, core, root):
        g = state.grid
        gcore = g.valid & core[g.index]
        groot = root[g.index]
        n = state.points.shape[0]
        n_pad = ((n + chunk - 1) // chunk) * chunk
        q = _pad0(state.points, n_pad, BIG).reshape(-1, chunk, 3)
        bkt = _pad0(state.buckets, n_pad, 0).reshape(-1, chunk, off)
        cv = _pad0(state.cell_valid, n_pad, False).reshape(-1, chunk, off)

        def body(args):
            qq, bb, vv = args
            cand = g.points[bb].reshape(chunk, off * cap, 3)
            val = (g.valid[bb] & vv[..., None]).reshape(chunk, off * cap)
            cc = gcore[bb].reshape(chunk, off * cap)
            rr = groot[bb].reshape(chunk, off * cap)
            return ops.gathered_sweep(qq, cand, val, cc, rr,
                                      jnp.float32(eps2), backend=backend)

        counts, minroot = jax.lax.map(body, (q, bkt, cv))
        return counts.reshape(-1)[:n], minroot.reshape(-1)[:n]

    return sweep


@functools.lru_cache(maxsize=64)
def _grid_hash_neighbors_fn(spec: grid_mod.GridSpec, eps2: float, chunk: int):
    """Neighbor lists from the hash grid's gathered candidate windows."""
    off, cap = spec.n_offsets, spec.capacity

    @functools.partial(jax.jit, static_argnames=("k_max",))
    def neighbors(state: GridState, k_max: int):
        g = state.grid
        n = state.points.shape[0]
        n_pad = ((n + chunk - 1) // chunk) * chunk
        q = _pad0(state.points, n_pad, BIG).reshape(-1, chunk, 3)
        bkt = _pad0(state.buckets, n_pad, 0).reshape(-1, chunk, off)
        cv = _pad0(state.cell_valid, n_pad, False).reshape(-1, chunk, off)

        def body(args):
            qq, bb, vv = args
            cand = g.points[bb].reshape(chunk, off * cap, 3)
            val = (g.valid[bb] & vv[..., None]).reshape(chunk, off * cap)
            idx = g.index[bb].reshape(chunk, off * cap)
            d2 = sum((qq[:, None, k] - cand[:, :, k]) ** 2 for k in range(3))
            return _topk_neighbor_ids((d2 <= eps2) & val, idx, k_max)

        idx, cnt = jax.lax.map(body, (q, bkt, cv))
        return idx.reshape(-1, k_max)[:n], cnt.reshape(-1)[:n]

    return neighbors


@functools.lru_cache(maxsize=64)
def _csr_sweep_fns(spec: grid_mod.CSRGridSpec, eps2: float,
                   backend: str | None):
    """Sweep pair for the cell-sorted CSR engine: the standard contract
    (original order / original root ids) and the sorted-layout fast path."""
    n = spec.n

    def _call(state: grid_mod.CSRGrid, croot_sorted):
        croot_pad = jnp.full((spec.n_cand,), INT_MAX, jnp.int32) \
            .at[:n].set(croot_sorted)
        counts_p, minroot_p = ops.csr_sweep(
            state.q_sorted, state.cands, croot_pad, state.starts, state.nblk,
            jnp.float32(eps2), slab=spec.slab, backend=backend,
            block_q=spec.chunk, block_k=spec.block_k)
        return counts_p[:n], minroot_p[:n]

    @jax.jit
    def sweep(state: grid_mod.CSRGrid, core, root):
        order = state.order
        croot_s = ops.fuse_core_root(core[order], root[order])
        counts_s, minroot_s = _call(state, croot_s)
        counts = jnp.zeros((n,), jnp.int32).at[order].set(counts_s)
        minroot = jnp.full((n,), INT_MAX, jnp.int32).at[order].set(minroot_s)
        return counts, minroot

    @jax.jit
    def sweep_sorted(state: grid_mod.CSRGrid, croot_sorted):
        return _call(state, croot_sorted)

    @jax.jit
    def sweep_counts(state: grid_mod.CSRGrid):
        counts_p = ops.csr_sweep_counts(
            state.q_sorted, state.cands, state.starts, state.nblk,
            jnp.float32(eps2), slab=spec.slab, backend=backend,
            block_q=spec.chunk, block_k=spec.block_k)
        return counts_p[:n]

    return sweep, sweep_sorted, sweep_counts


@functools.lru_cache(maxsize=64)
def _csr_frontier_fns(spec: grid_mod.CSRGridSpec, eps2: float,
                      backend: str | None):
    """The ``sweep_frontier`` capability for the CSR engine (DESIGN.md §11).

    Tile liveness is the intersection of two independently hook-safe tests:

      * **pending** (dirty blocks): some candidate in the tile's slab
        changed payload since the tile was last swept — a sticky flag, so
        a tile parked by the seam test keeps remembering the change;
      * **live seam**: the slab's min core root is below some core query's
        root in the tile — the only configuration that can produce a
        *new* union (otherwise every hook target equals the query's own
        root and the scatter-min is a no-op).

    Parked tiles return INT32_MAX min-root rows; their hook step is then
    ``parent[root] min= root`` — exactly the no-op the full sweep would
    have produced — so the union-find trajectory (and every label and the
    round count) is bit-identical to the full re-sweep drivers.
    """
    n, bk, chunk = spec.n, spec.block_k, spec.chunk
    T = spec.n_tiles
    max_blocks = spec.slab // bk

    def _pad_payload(croot_sorted):
        return jnp.full((spec.n_cand,), INT_MAX, jnp.int32) \
            .at[:n].set(croot_sorted)

    def _pad_tile_rows(x, fill):
        return jnp.full((T * chunk,), fill, x.dtype).at[:n].set(x)

    def _compacted_to_sorted(minroot_c, active, n_live):
        # slot i's rows belong to tile active[i]; dead slots drop
        slot = jnp.arange(T, dtype=jnp.int32)
        dst0 = jnp.where(slot < n_live, active * chunk, T * chunk)
        dst = (dst0[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :])
        return jnp.full((n,), INT_MAX, jnp.int32).at[
            dst.reshape(-1)].set(minroot_c, mode="drop")

    @jax.jit
    def sweep(state: grid_mod.CSRGrid, croot_s, qroot_s, changed_s, pending):
        pending = pending | grid_mod.slab_touched(
            changed_s, state.starts, state.nblk, n, block_k=bk)
        croot_pad = _pad_payload(croot_s)
        slab_min = grid_mod.slab_payload_min(
            croot_pad, state.starts, state.nblk, block_k=bk,
            max_blocks=max_blocks)
        qmax = _pad_tile_rows(qroot_s, jnp.int32(-1)) \
            .reshape(T, chunk).max(axis=1)
        live = pending & (slab_min < qmax)
        active, n_live = grid_mod.compact_tiles(live)
        minroot_c = ops.frontier_sweep(
            state.q_sorted, state.cands, croot_pad, state.starts,
            state.nblk, active, n_live, jnp.float32(eps2), slab=spec.slab,
            backend=backend, block_q=chunk, block_k=bk)
        return (_compacted_to_sorted(minroot_c, active, n_live),
                pending & ~live, n_live)

    @jax.jit
    def border(state: grid_mod.CSRGrid, croot_s, core_s):
        # minroot is consumed only by non-core queries, and only slabs with
        # a core candidate can produce one != INT32_MAX
        croot_pad = _pad_payload(croot_s)
        slab_min = grid_mod.slab_payload_min(
            croot_pad, state.starts, state.nblk, block_k=bk,
            max_blocks=max_blocks)
        has_noncore = _pad_tile_rows(~core_s, False) \
            .reshape(T, chunk).any(axis=1)
        live = has_noncore & (slab_min < INT_MAX)
        active, n_live = grid_mod.compact_tiles(live)
        minroot_c = ops.frontier_sweep(
            state.q_sorted, state.cands, croot_pad, state.starts,
            state.nblk, active, n_live, jnp.float32(eps2), slab=spec.slab,
            backend=backend, block_q=chunk, block_k=bk)
        return _compacted_to_sorted(minroot_c, active, n_live)

    return engines.FrontierPlan(n_tiles=T, sweep=sweep, border=border)


@functools.lru_cache(maxsize=64)
def _csr_cross_query_fn(spec: grid_mod.CSRGridSpec, eps2: float,
                        backend: str | None, slab: int, block_q: int):
    """Cross-corpus query over a frozen CSR layout (DESIGN.md §10).

    The device program behind the ``query`` capability and the serving
    subsystem's ``assign``: quantize fresh queries with the *corpus* plan,
    Morton-sort them so tiles share window cells, bisect each query's 9/27
    window cells against the corpus's sorted codes, reduce to per-tile
    slabs, and run the ``cross_sweep`` kernel. Results are scattered back
    to request order before returning.

    The returned function is jitted per (query capacity, slab) — the shape
    bucketing layer above picks capacities from a small fixed set so a
    variable request stream reuses a warm cache. ``nq`` (the live query
    count within the padded batch) is a *dynamic* argument: partially
    filled buckets do not retrace.
    """
    from ..kernels import ref as _kref
    n_cand = spec.n_cand
    eff_slab = min(slab, n_cand)  # slab == n_cand covers any window

    @jax.jit
    def query(codes, cands, croot_sorted, q, nq):
        Qp = q.shape[0]
        n = codes.shape[0]
        valid = jnp.arange(Qp, dtype=jnp.int32) < nq
        qcells = grid_mod.csr_cells(q, spec.side, spec.origin, spec.dims,
                                    spec.bits)
        qcodes = _kref.morton_encode_ref(qcells, dims=spec.dims)
        # stable sort by code, padding keyed to the end of the batch
        qorder = jnp.argsort(jnp.where(valid, qcodes, INT_MAX)).astype(
            jnp.int32)
        valid_s = valid[qorder]
        lo, hi = grid_mod._csr_window_bounds(codes, qcells[qorder],
                                             spec.dims, spec.bits)
        # dead lanes drop out of the tile min/max (the tile_slabs contract)
        lo = jnp.where(valid_s, lo, n)
        hi = jnp.where(valid_s, hi, 0)
        starts, nblk, overflow = grid_mod.tile_slabs(
            lo, hi, Qp, n_tiles=Qp // block_q, chunk=block_q,
            block_k=spec.block_k, slab=eff_slab, n_cand=n_cand)
        counts_s, minroot_s, mind2_s = ops.cross_sweep(
            q[qorder], cands, croot_sorted, starts, nblk, jnp.float32(eps2),
            slab=eff_slab, backend=backend, block_q=block_q,
            block_k=spec.block_k)
        counts = jnp.zeros((Qp,), jnp.int32).at[qorder].set(counts_s)
        minroot = jnp.full((Qp,), INT_MAX, jnp.int32).at[qorder].set(
            minroot_s)
        mind2 = jnp.full((Qp,), jnp.inf, jnp.float32).at[qorder].set(mind2_s)
        return counts, minroot, mind2, overflow

    return query


@functools.lru_cache(maxsize=64)
def _csr_neighbors_fn(spec: grid_mod.CSRGridSpec, eps2: float):
    """Neighbor lists from the CSR engine's per-tile contiguous slabs."""
    n, slab, bk = spec.n, spec.slab, spec.block_k
    chunk = spec.chunk

    @functools.partial(jax.jit, static_argnames=("k_max",))
    def neighbors(state: grid_mod.CSRGrid, k_max: int):
        order = state.order
        # original id per sorted position; slab pads (≥ n) can never hit
        orig = jnp.full((spec.n_cand,), INT_MAX, jnp.int32).at[:n].set(order)
        live_blk = jnp.arange(slab, dtype=jnp.int32)

        def tile(args):
            qq, st, nb = args
            c = jax.lax.dynamic_slice(state.cands, (0, st), (3, slab))
            oidx = jax.lax.dynamic_slice(orig, (st,), (slab,))
            live = live_blk < nb * bk
            d2 = sum((qq[:, None, k] - c[None, k, :]) ** 2 for k in range(3))
            return _topk_neighbor_ids((d2 <= eps2) & live[None, :],
                                      oidx[None, :], k_max)

        idx_s, cnt_s = jax.lax.map(
            tile, (state.q_sorted.reshape(-1, chunk, 3), state.starts,
                   state.nblk))
        idx_s = idx_s.reshape(-1, k_max)[:n]
        cnt_s = cnt_s.reshape(-1)[:n]
        idx = jnp.full((n, k_max), -1, jnp.int32).at[order].set(idx_s)
        cnt = jnp.zeros((n,), jnp.int32).at[order].set(cnt_s)
        return idx, cnt

    return neighbors


@functools.lru_cache(maxsize=64)
def _brute_sweep_fn(eps2: float, chunk: int, backend: str | None):

    @jax.jit
    def sweep(points, core, root):
        n = points.shape[0]
        n_pad = ((n + chunk - 1) // chunk) * chunk
        q = _pad0(points, n_pad, BIG).reshape(-1, chunk, points.shape[1])

        def body(qq):
            return ops.pairwise_sweep(qq, points, core, root,
                                      jnp.float32(eps2), backend=backend)

        counts, minroot = jax.lax.map(body, q)
        return counts.reshape(-1)[:n], minroot.reshape(-1)[:n]

    return sweep


@functools.lru_cache(maxsize=64)
def _brute_neighbors_fn(eps2: float, chunk: int):

    @functools.partial(jax.jit, static_argnames=("k_max",))
    def neighbors(points, k_max: int):
        n = points.shape[0]
        n_pad = ((n + chunk - 1) // chunk) * chunk
        q = _pad0(points, n_pad, BIG).reshape(-1, chunk, points.shape[1])
        cand_idx = jnp.arange(n, dtype=jnp.int32)[None, :]

        def body(qq):
            d2 = sum((qq[:, None, k] - points[None, :, k]) ** 2
                     for k in range(points.shape[1]))
            return _topk_neighbor_ids(d2 <= eps2, cand_idx, k_max)

        idx, cnt = jax.lax.map(body, q)
        return idx.reshape(-1, k_max)[:n], cnt.reshape(-1)[:n]

    return neighbors


# --- registry builders (one per engine; the only dispatch table) -----------


def _build_brute(points, eps, *, backend=None, chunk=2048, dims=None,
                 spec=None):
    eps2 = float(eps) ** 2
    return Engine("brute", points, _brute_sweep_fn(eps2, chunk, backend),
                  neighbors=_brute_neighbors_fn(eps2, chunk))


def _build_csr(points, eps, *, backend=None, chunk=2048, dims=None,
               spec=None):
    eps2 = float(eps) ** 2
    with obs.span("engine.plan", n=len(points)) as sp:
        pts_np = np.asarray(points)
        if dims is None:
            dims = infer_dims(pts_np)
        sp.set_metadata(dims=dims)
        if spec is None:
            spec = grid_mod.plan_csr_grid(points, float(eps), dims=dims)
    with obs.span("engine.layout", slab=spec.slab):
        g = grid_mod.build_csr_grid(points, spec)
        overflowed = bool(g.overflow)
    if overflowed:
        raise ValueError(
            "CSR grid build overflowed the planned slab capacity "
            f"(slab={spec.slab}) — the spec was planned for different "
            "data; re-plan with plan_csr_grid on this dataset")
    fn, fn_sorted, fn_counts = _csr_sweep_fns(spec, eps2, backend)

    def query(state, q, nq, croot_sorted, *, slab=None, block_q=256):
        """Cross-corpus queries against this engine's frozen layout: q
        (Qp, 3) padded queries (Qp multiple of block_q), nq live count,
        croot_sorted (n_cand,) payload in sorted layout."""
        fn_q = _csr_cross_query_fn(spec, eps2, backend,
                                   spec.slab if slab is None else slab,
                                   block_q)
        return fn_q(state.codes, state.cands, croot_sorted, q, nq)

    return Engine("grid", g, fn, meta=spec, sweep_sorted=fn_sorted,
                  order=g.order, neighbors=_csr_neighbors_fn(spec, eps2),
                  query=query, sweep_counts=fn_counts,
                  sweep_frontier=_csr_frontier_fns(spec, eps2, backend))


def _build_grid_hash(points, eps, *, backend=None, chunk=2048, dims=None,
                     spec=None):
    eps2 = float(eps) ** 2
    pts_np = np.asarray(points)
    if dims is None:
        dims = infer_dims(pts_np)
    if spec is None:
        spec = grid_mod.plan_grid(pts_np, float(eps), dims=dims)
    g = build_grid_jit(points, spec)
    buckets, cell_valid = neighbor_buckets_jit(points, spec)
    state = GridState(grid=g, buckets=buckets, cell_valid=cell_valid,
                      points=points)
    return Engine("grid-hash", state, _grid_sweep_fn(spec, eps2, chunk,
                                                     backend),
                  meta=spec, neighbors=_grid_hash_neighbors_fn(spec, eps2,
                                                               chunk))


engines.register_engine(
    "brute", _build_brute,
    doc="tiled all-pairs sweep (exact, O(n²) compute)",
    capabilities=("neighbors",))
engines.register_engine(
    "grid", _build_csr,
    doc="cell-sorted CSR ε-grid; sorted-layout fast path (the default)",
    capabilities=("neighbors", "sweep_sorted", "query", "sweep_counts",
                  "sweep_frontier"))
engines.register_engine(
    "grid-hash", _build_grid_hash,
    doc="capacity-padded spatial-hash ε-grid (comparison baseline)",
    capabilities=("neighbors",))


build_grid_jit = jax.jit(grid_mod.build_grid, static_argnames=("spec",))
neighbor_buckets_jit = jax.jit(grid_mod.neighbor_buckets,
                               static_argnames=("spec",))


def find_neighbors(points, eps: float, k_max: int, *, engine: str = "grid",
                   backend: str | None = None, chunk: int = 2048):
    """Generic fixed-radius neighbor *lists* (library op, DESIGN.md §6).

    Dispatches through the engine registry — any engine advertising the
    ``neighbors`` capability works (``grid``, ``grid-hash``, ``brute``).
    Returns (idx (n, k_max) int32 padded with -1, counts (n,) int32).
    Neighbor indices are ascending; self is included. Overflow beyond
    ``k_max`` is truncated (counts still exact).
    """
    entry = engines.get_engine_spec(engine)
    if "neighbors" not in entry.capabilities:
        raise ValueError(
            f"engine {engine!r} does not provide the neighbor-list "
            "capability; use engine='grid', 'grid-hash' or 'brute'")
    eng = make_engine(points, eps, engine=engine, backend=backend,
                      chunk=chunk)
    return eng.neighbors(eng.state, k_max=k_max)

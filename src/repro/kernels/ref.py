"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each Pallas kernel must match its oracle
bit-for-bit on integer outputs and to float tolerance on float outputs, over
shape/dtype sweeps (see tests/test_kernels.py). They are also the CPU
execution path (there is no Mosaic backend on CPU).

Sweep payload convention (used by both DBSCAN stages, fused — see DESIGN.md):
  counts[i]   = |{ j : dist²(q_i, c_j) ≤ ε², c_j valid }|   (self included)
  minroot[i]  = min{ root[j] : dist²(q_i, c_j) ≤ ε², c_j valid, core[j] }
                (INT32_MAX if empty)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INT_MAX = jnp.iinfo(jnp.int32).max


def _dist2(q: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Squared euclidean distance, (..., D) vs (..., D) broadcast-safe.

    Math is always f32 regardless of storage dtype (bf16/f16 storage with f32
    compute is the kernel contract; the Pallas kernels cast the same way).
    The accumulation order (ascending coordinate) matches the kernels', so
    float results are bit-identical across backends.
    """
    acc = jnp.zeros(jnp.broadcast_shapes(q.shape[:-1], c.shape[:-1]),
                    jnp.float32)
    for k in range(q.shape[-1]):
        d = q[..., k].astype(jnp.float32) - c[..., k].astype(jnp.float32)
        acc = acc + d * d
    return acc


def pairwise_sweep_ref(queries: jnp.ndarray, cands: jnp.ndarray,
                       cand_valid: jnp.ndarray, cand_core: jnp.ndarray,
                       cand_root: jnp.ndarray, eps2: jnp.ndarray):
    """Brute-force sweep: every query against every candidate.

    queries    (nq, 3) float
    cands      (nc, 3) float
    cand_valid (nc,)  bool
    cand_core  (nc,)  bool
    cand_root  (nc,)  int32
    eps2       scalar float
    returns counts (nq,) int32, minroot (nq,) int32
    """
    d2 = _dist2(queries[:, None, :], cands[None, :, :])  # (nq, nc)
    hit = (d2 <= eps2) & cand_valid[None, :]
    counts = hit.sum(axis=1).astype(jnp.int32)
    root_or_max = jnp.where(hit & cand_core[None, :], cand_root[None, :], INT_MAX)
    minroot = root_or_max.min(axis=1).astype(jnp.int32)
    return counts, minroot


def gathered_sweep_ref(queries: jnp.ndarray, cands: jnp.ndarray,
                       cand_valid: jnp.ndarray, cand_core: jnp.ndarray,
                       cand_root: jnp.ndarray, eps2: jnp.ndarray):
    """Per-query pre-gathered candidate sweep (grid engine inner loop).

    queries    (b, 3) float
    cands      (b, k, 3) float — per-query candidate window
    cand_valid (b, k) bool
    cand_core  (b, k) bool
    cand_root  (b, k) int32
    returns counts (b,) int32, minroot (b,) int32
    """
    d2 = _dist2(queries[:, None, :], cands)  # (b, k)
    hit = (d2 <= eps2) & cand_valid
    counts = hit.sum(axis=1).astype(jnp.int32)
    root_or_max = jnp.where(hit & cand_core, cand_root, INT_MAX)
    minroot = root_or_max.min(axis=1).astype(jnp.int32)
    return counts, minroot


def csr_sweep_ref(queries: jnp.ndarray, cands_planar: jnp.ndarray,
                  croot: jnp.ndarray, starts_blk: jnp.ndarray,
                  nblk: jnp.ndarray, eps2: jnp.ndarray, *,
                  max_blocks: int, block_k: int):
    """Cell-sorted CSR slab sweep (DESIGN.md §3): query tile ``t`` sweeps the
    contiguous candidate slab ``[starts_blk[t]·block_k,
    (starts_blk[t]+nblk[t])·block_k)`` of the sorted candidate array.

    queries      (T·block_q, 3) float — sorted query tiles
    cands_planar (3, nc) float        — cell-sorted candidates (BIG-padded)
    croot        (1, nc) int32        — root if core else INT32_MAX
    starts_blk   (T,) int32           — slab start per tile (block_k units)
    nblk         (T,) int32           — slab block count per tile
    returns counts (T·block_q,) int32, minroot (T·block_q,) int32

    Semantics match the Pallas kernel exactly: only the ``nblk[t]`` live
    blocks of each tile's slab are visited (a ``while_loop`` with dynamic
    trip count — the oracle analogue of the kernel's ``j < nblk`` skip), so
    integer outputs are bit-identical across backends AND the work adapts to
    local occupancy on CPU too.
    """
    T = starts_blk.shape[0]
    block_q = queries.shape[0] // T

    def tile(args):
        qq, st, nb = args

        def cond(carry):
            b, _, _ = carry
            return b < nb

        def body(carry):
            b, counts, minroot = carry
            off = (st + b) * block_k
            c = jax.lax.dynamic_slice(cands_planar, (0, off), (3, block_k))
            r = jax.lax.dynamic_slice(croot, (0, off), (1, block_k))[0]
            d2 = _dist2(qq[:, None, :], jnp.moveaxis(c, 0, -1)[None, :, :])
            hit = d2 <= eps2
            counts = counts + hit.sum(axis=1).astype(jnp.int32)
            minroot = jnp.minimum(
                minroot, jnp.where(hit, r[None, :], INT_MAX).min(axis=1))
            return b + jnp.int32(1), counts, minroot.astype(jnp.int32)

        _, counts, minroot = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.zeros((block_q,), jnp.int32),
                         jnp.full((block_q,), INT_MAX, jnp.int32)))
        return counts, minroot

    counts, minroot = jax.lax.map(
        tile, (queries.reshape(T, block_q, 3), starts_blk, nblk))
    return counts.reshape(-1), minroot.reshape(-1)


def csr_sweep_counts_ref(queries: jnp.ndarray, cands_planar: jnp.ndarray,
                         starts_blk: jnp.ndarray, nblk: jnp.ndarray,
                         eps2: jnp.ndarray, *, max_blocks: int,
                         block_k: int):
    """Counts-only slab sweep (stage-1): :func:`csr_sweep_ref` without the
    payload plane or min-root accumulation. Counts are bit-identical to the
    full sweep's counts output."""
    T = starts_blk.shape[0]
    block_q = queries.shape[0] // T

    def tile(args):
        qq, st, nb = args

        def cond(carry):
            b, _ = carry
            return b < nb

        def body(carry):
            b, counts = carry
            off = (st + b) * block_k
            c = jax.lax.dynamic_slice(cands_planar, (0, off), (3, block_k))
            d2 = _dist2(qq[:, None, :], jnp.moveaxis(c, 0, -1)[None, :, :])
            counts = counts + (d2 <= eps2).sum(axis=1).astype(jnp.int32)
            return b + jnp.int32(1), counts

        _, counts = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.zeros((block_q,), jnp.int32)))
        return counts

    counts = jax.lax.map(tile, (queries.reshape(T, block_q, 3), starts_blk,
                                nblk))
    return counts.reshape(-1)


def frontier_sweep_ref(queries: jnp.ndarray, cands_planar: jnp.ndarray,
                       croot: jnp.ndarray, starts_blk: jnp.ndarray,
                       nblk: jnp.ndarray, active: jnp.ndarray,
                       n_active: jnp.ndarray, eps2: jnp.ndarray, *,
                       max_blocks: int, block_k: int):
    """Frontier-compacted slab sweep (DESIGN.md §11): output slot ``i``
    holds the min-root rows of query tile ``active[i]`` when
    ``i < n_active``, INT32_MAX otherwise. Parked slots run a zero-trip
    block walk, so CPU cost tracks the live frontier exactly like the
    kernel's parked grid steps.

    Semantics match the Pallas kernel exactly: a live slot visits the
    ``nblk[active[i]]`` blocks of its tile's slab in order, accumulating the
    same f32 distances — outputs are bit-identical across backends.
    """
    T = starts_blk.shape[0]
    block_q = queries.shape[0] // T
    queries = jnp.asarray(queries)
    starts_blk = jnp.asarray(starts_blk)   # indexed by traced slot ids
    nblk = jnp.asarray(nblk)
    na = jnp.asarray(n_active).reshape(())

    def slot(args):
        i, t = args
        qq = jax.lax.dynamic_slice(queries, (t * block_q, 0), (block_q, 3))
        st = starts_blk[t]
        nb = jnp.where(i < na, nblk[t], 0)

        def cond(carry):
            b, _ = carry
            return b < nb

        def body(carry):
            b, minroot = carry
            off = (st + b) * block_k
            c = jax.lax.dynamic_slice(cands_planar, (0, off), (3, block_k))
            r = jax.lax.dynamic_slice(croot, (0, off), (1, block_k))[0]
            d2 = _dist2(qq[:, None, :], jnp.moveaxis(c, 0, -1)[None, :, :])
            hit = d2 <= eps2
            minroot = jnp.minimum(
                minroot, jnp.where(hit, r[None, :], INT_MAX).min(axis=1))
            return b + jnp.int32(1), minroot.astype(jnp.int32)

        _, minroot = jax.lax.while_loop(
            cond, body, (jnp.int32(0),
                         jnp.full((block_q,), INT_MAX, jnp.int32)))
        return minroot

    minroot = jax.lax.map(
        slot, (jnp.arange(T, dtype=jnp.int32), active.astype(jnp.int32)))
    return minroot.reshape(-1)


def cross_sweep_ref(queries: jnp.ndarray, cands_planar: jnp.ndarray,
                    croot: jnp.ndarray, starts_blk: jnp.ndarray,
                    nblk: jnp.ndarray, eps2: jnp.ndarray, *,
                    max_blocks: int, block_k: int):
    """Cross-corpus CSR slab sweep (DESIGN.md §10): query tile ``t`` (fresh
    Morton-sorted points, not corpus members) sweeps the contiguous corpus
    slab ``[starts_blk[t]·block_k, (starts_blk[t]+nblk[t])·block_k)``.

    queries      (T·block_q, 3) float — sorted query tiles
    cands_planar (3, nc) float        — cell-sorted frozen corpus (BIG pad)
    croot        (1, nc) int32        — cluster label if core else INT32_MAX
    starts_blk   (T,) int32           — slab start per tile (block_k units)
    nblk         (T,) int32           — slab block count per tile
    returns counts (T·block_q,) int32   — corpus ε-neighbors (no self term),
            minroot (T·block_q,) int32  — min core label within ε (predict),
            mind2 (T·block_q,) float32  — min d² over core hits (+inf none)

    Semantics match the Pallas kernel exactly: only the ``nblk[t]`` live
    blocks of each tile's slab are visited, distances accumulate in f32 in
    the same coordinate order, and ``mind2`` is a min over identically
    computed values — so all three outputs (the float one included) are
    bit-identical across backends.
    """
    T = starts_blk.shape[0]
    block_q = queries.shape[0] // T
    INF = jnp.float32(jnp.inf)

    def tile(args):
        qq, st, nb = args

        def cond(carry):
            b, _, _, _ = carry
            return b < nb

        def body(carry):
            b, counts, minroot, mind2 = carry
            off = (st + b) * block_k
            c = jax.lax.dynamic_slice(cands_planar, (0, off), (3, block_k))
            r = jax.lax.dynamic_slice(croot, (0, off), (1, block_k))[0]
            d2 = _dist2(qq[:, None, :], jnp.moveaxis(c, 0, -1)[None, :, :])
            hit = d2 <= eps2
            core_hit = hit & (r[None, :] != INT_MAX)
            counts = counts + hit.sum(axis=1).astype(jnp.int32)
            minroot = jnp.minimum(
                minroot, jnp.where(core_hit, r[None, :], INT_MAX).min(axis=1))
            mind2 = jnp.minimum(
                mind2, jnp.where(core_hit, d2, INF).min(axis=1))
            return (b + jnp.int32(1), counts, minroot.astype(jnp.int32),
                    mind2.astype(jnp.float32))

        _, counts, minroot, mind2 = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.zeros((block_q,), jnp.int32),
                         jnp.full((block_q,), INT_MAX, jnp.int32),
                         jnp.full((block_q,), INF, jnp.float32)))
        return counts, minroot, mind2

    counts, minroot, mind2 = jax.lax.map(
        tile, (queries.reshape(T, block_q, 3), starts_blk, nblk))
    return counts.reshape(-1), minroot.reshape(-1), mind2.reshape(-1)


def bvh_batch_sweep_ref(queries: jnp.ndarray, dlo: jnp.ndarray,
                        dhi: jnp.ndarray, pt: jnp.ndarray,
                        croot: jnp.ndarray, nmin: jnp.ndarray,
                        leaf: jnp.ndarray, bound: jnp.ndarray,
                        eps2: jnp.ndarray, *, bf16_prune: bool,
                        prune_payload: bool):
    """Batched wavefront BVH expand step (DESIGN.md §9, §13): one
    breadth-first level of (query-block, child-node) entries, B queries per
    entry, through the two-phase test — pre-dilated (optionally bf16,
    outward-rounded) AABB prune, exact f32 sphere refine for leaves
    (Algorithm 2 line 6) — plus the early-termination payload prune.

    queries (E, B, D) float — B batched queries per entry
    dlo/dhi (E, D) float — pre-dilated prune box (bf16-valued if bf16 prune)
    pt      (E, D) float — leaf point (internal entries: don't-care)
    croot   (E,) int32 — leaf payload: root if core else INT32_MAX
    nmin    (E,) int32 — subtree min payload (payload mode only)
    leaf    (E,) int32 — 1 iff the child is a leaf
    bound   (E, B) int32 — per-column running min-root bound
    returns hit (E, B) int32 ∈ {0, 1} (leaf within ε, exact — independent of
            the prune dtype), minroot (E, B) int32 (croot if hit else
            INT32_MAX), push (E,) int32 (internal entry with ≥ 1 useful
            column: inside the prune box and — payload mode — whose subtree
            min payload can still lower the column's bound)
    """
    q = queries.astype(jnp.float32)                     # (E, B, D)
    if bf16_prune:
        qp = q.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        qp = q
    lo = dlo.astype(jnp.float32)[:, None, :]
    hi = dhi.astype(jnp.float32)[:, None, :]
    inside = jnp.all((qp >= lo) & (qp <= hi), axis=-1)  # (E, B)
    d2 = _dist2(q, pt.astype(jnp.float32)[:, None, :])
    lf = (leaf != 0)[:, None]
    hit = lf & (d2 <= eps2)
    minroot = jnp.where(hit, croot[:, None], INT_MAX).astype(jnp.int32)
    if prune_payload:
        useful = inside & (nmin[:, None] < bound)
    else:
        useful = inside
    push = (~lf[:, 0]) & jnp.any(useful, axis=1)
    return hit.astype(jnp.int32), minroot, push.astype(jnp.int32)


def morton_encode_ref(coords: jnp.ndarray, dims: int = 3) -> jnp.ndarray:
    """30-bit Morton (Z-order) code from quantized integer coords.

    coords (n, 3) int32 in [0, 1024) (10 bits/axis for 3D, 15 bits/axis 2D —
    z column ignored when dims == 2).
    """
    def expand3(x):  # 10 -> 30 bits, 2-bit gaps
        x = x & 0x3FF
        x = (x | (x << 16)) & 0x030000FF
        x = (x | (x << 8)) & 0x0300F00F
        x = (x | (x << 4)) & 0x030C30C3
        x = (x | (x << 2)) & 0x09249249
        return x

    def expand2(x):  # 15 -> 30 bits, 1-bit gaps
        x = x & 0x7FFF
        x = (x | (x << 8)) & 0x00FF00FF
        x = (x | (x << 4)) & 0x0F0F0F0F
        x = (x | (x << 2)) & 0x33333333
        x = (x | (x << 1)) & 0x55555555
        return x

    x, y, z = coords[:, 0], coords[:, 1], coords[:, 2]
    if dims == 2:
        return (expand2(x) | (expand2(y) << 1)).astype(jnp.int32)
    return (expand3(x) | (expand3(y) << 1) | (expand3(z) << 2)).astype(jnp.int32)

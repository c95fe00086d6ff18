"""Public, backend-dispatching wrappers for the Pallas kernels.

Backends:
  * ``kernel``    — compiled Pallas (Mosaic) — the TPU production path;
  * ``interpret`` — Pallas interpret mode — kernel-body semantics on CPU,
                    used for validation in this (CPU-only) container;
  * ``ref``       — the pure-jnp oracle (``ref.py``) — the CPU execution
                    path (no Mosaic backend on CPU).

Selection: explicit ``backend=`` argument, else ``$REPRO_KERNEL_BACKEND``,
else ``kernel`` on TPU / ``ref`` otherwise. Wrappers own all padding so the
kernels only ever see hardware-aligned shapes.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import ref as _ref
from .bvh_sweep import bvh_batch_sweep as _bvh_kernel
from .cross_sweep import cross_sweep as _cross_kernel
from .csr_sweep import csr_sweep as _csr_kernel
from .csr_sweep import csr_sweep_counts as _csr_counts_kernel
from .frontier_sweep import frontier_sweep as _frontier_kernel
from .gathered_sweep import gathered_sweep as _gathered_kernel
from .morton import morton_encode as _morton_kernel
from .pairwise_sweep import pairwise_sweep as _pairwise_kernel

INT_MAX = jnp.iinfo(jnp.int32).max
BIG = 1e30


def default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        return env
    return "kernel" if jax.devices()[0].platform == "tpu" else "ref"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_to(x, n, axis, value):
    pad = n - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def fuse_core_root(core, root):
    """Pre-fuse the core mask into the payload plane: root if core else MAX."""
    return jnp.where(core, root, INT_MAX).astype(jnp.int32)


def pairwise_sweep(queries, cands, core, root, eps2, *, backend=None,
                   block_q: int = 256, block_c: int = 512):
    """Brute ε-sweep. queries (nq,3), cands (nc,3), core/root (nc,).

    Returns counts (nq,) int32, minroot (nq,) int32.
    """
    backend = backend or default_backend()
    nq, nc = queries.shape[0], cands.shape[0]
    eps2 = jnp.asarray(eps2, jnp.float32)
    if backend == "ref":
        valid = jnp.ones((nc,), bool)
        return _ref.pairwise_sweep_ref(queries, cands, valid, core, root, eps2)
    nq_p = _round_up(max(nq, 1), block_q)
    nc_p = _round_up(max(nc, 1), block_c)
    q = _pad_to(queries.astype(jnp.float32), nq_p, 0, BIG)
    c = _pad_to(cands.astype(jnp.float32), nc_p, 0, BIG)
    croot = _pad_to(fuse_core_root(core, root), nc_p, 0, INT_MAX)[None, :]
    counts, minroot = _pairwise_kernel(
        q, c.T, croot, eps2, block_q=block_q, block_c=block_c,
        interpret=(backend == "interpret"))
    return counts[:nq], minroot[:nq]


def gathered_sweep(queries, cands, cand_valid, cand_core, cand_root, eps2, *,
                   backend=None, block_b: int = 128, block_k: int = 512):
    """Pre-gathered window ε-sweep. queries (b,3), cands (b,k,3), masks (b,k).

    Returns counts (b,) int32, minroot (b,) int32.
    """
    backend = backend or default_backend()
    b, k = cands.shape[0], cands.shape[1]
    eps2 = jnp.asarray(eps2, jnp.float32)
    if backend == "ref":
        return _ref.gathered_sweep_ref(
            queries, cands, cand_valid, cand_core, cand_root, eps2)
    b_p = _round_up(max(b, 1), block_b)
    k_p = _round_up(max(k, 1), block_k)
    cands = jnp.where(cand_valid[..., None], cands.astype(jnp.float32), BIG)
    q = _pad_to(queries.astype(jnp.float32), b_p, 0, BIG)
    c = _pad_to(_pad_to(cands, k_p, 1, BIG), b_p, 0, BIG)
    croot = jnp.where(cand_valid & cand_core, cand_root, INT_MAX).astype(jnp.int32)
    croot = _pad_to(_pad_to(croot, k_p, 1, INT_MAX), b_p, 0, INT_MAX)
    counts, minroot = _gathered_kernel(
        q, jnp.transpose(c, (2, 0, 1)), croot, eps2, block_b=block_b,
        block_k=block_k, interpret=(backend == "interpret"))
    return counts[:b], minroot[:b]


def csr_sweep(queries, cands_planar, croot, starts, nblk, eps2, *,
              slab: int, backend=None, block_q: int = 256,
              block_k: int = 512):
    """Cell-sorted CSR slab ε-sweep (grid engine inner loop, DESIGN.md §3).

    queries      (T·block_q, 3) — sorted query tiles (tile t = rows
                 [t·block_q, (t+1)·block_q))
    cands_planar (3, nc)        — cell-sorted candidates, nc multiple of
                 block_k, padded with +BIG
    croot        (nc,) int32    — root if core else INT32_MAX (sorted order)
    starts       (T,) int32     — per-tile slab start, in *elements*,
                 multiples of block_k, with starts + slab ≤ nc
    nblk         (T,) int32     — per-tile live block count (≤ slab/block_k)
    slab         static per-tile slab capacity (elements, mult. of block_k)

    Returns counts (T·block_q,) int32, minroot (T·block_q,) int32. Both
    backends count exactly the ``nblk`` live blocks of each tile's slab, so
    integer outputs are bit-identical.
    """
    backend = backend or default_backend()
    assert slab % block_k == 0 and queries.shape[0] % block_q == 0
    eps2 = jnp.asarray(eps2, jnp.float32)
    starts_blk = (starts // block_k).astype(jnp.int32)
    croot2 = croot.astype(jnp.int32)[None, :]
    max_blocks = slab // block_k
    if backend == "ref":
        return _ref.csr_sweep_ref(queries.astype(jnp.float32), cands_planar,
                                  croot2, starts_blk, nblk, eps2,
                                  max_blocks=max_blocks, block_k=block_k)
    return _csr_kernel(queries.astype(jnp.float32), cands_planar, croot2,
                       starts_blk, nblk, eps2, max_blocks=max_blocks,
                       block_q=block_q, block_k=block_k,
                       interpret=(backend == "interpret"))


def csr_sweep_counts(queries, cands_planar, starts, nblk, eps2, *,
                     slab: int, backend=None, block_q: int = 256,
                     block_k: int = 512):
    """Counts-only CSR slab sweep (stage-1 core identification).

    The static sibling of :func:`csr_sweep` for callers that discard the
    payload half: no ``croot`` input (one less block DMA per grid step), no
    ``minroot`` output, no min-root accumulation. Counts are bit-identical
    to the full sweep's counts across backends.
    """
    backend = backend or default_backend()
    assert slab % block_k == 0 and queries.shape[0] % block_q == 0
    eps2 = jnp.asarray(eps2, jnp.float32)
    starts_blk = (starts // block_k).astype(jnp.int32)
    max_blocks = slab // block_k
    if backend == "ref":
        return _ref.csr_sweep_counts_ref(
            queries.astype(jnp.float32), cands_planar, starts_blk, nblk,
            eps2, max_blocks=max_blocks, block_k=block_k)
    return _csr_counts_kernel(
        queries.astype(jnp.float32), cands_planar, starts_blk, nblk, eps2,
        max_blocks=max_blocks, block_q=block_q, block_k=block_k,
        interpret=(backend == "interpret"))


def frontier_sweep(queries, cands_planar, croot, starts, nblk, active,
                   n_active, eps2, *, slab: int, backend=None,
                   block_q: int = 256, block_k: int = 512):
    """Frontier-compacted CSR slab ε-sweep (stage-2 rounds, DESIGN.md §11).

    ``csr_sweep`` restricted to an active-tile index vector: slot ``i``
    sweeps tile ``active[i]`` when ``i < n_active`` and is parked (no DMA,
    no compute, INT32_MAX output) otherwise — cost tracks the live
    frontier, not the tile count. ``active`` entries at or past
    ``n_active`` must repeat the last live id (or 0 when none) so parked
    steps revisit resident blocks. Returns the *compacted* minroot
    (T·block_q,) int32; there is no counts output (hooking discards it).
    """
    backend = backend or default_backend()
    assert slab % block_k == 0 and queries.shape[0] % block_q == 0
    eps2 = jnp.asarray(eps2, jnp.float32)
    starts_blk = (starts // block_k).astype(jnp.int32)
    croot2 = croot.astype(jnp.int32)[None, :]
    max_blocks = slab // block_k
    n_active = jnp.asarray(n_active, jnp.int32).reshape(1)
    if backend == "ref":
        return _ref.frontier_sweep_ref(
            queries.astype(jnp.float32), cands_planar, croot2, starts_blk,
            nblk, active, n_active, eps2, max_blocks=max_blocks,
            block_k=block_k)
    return _frontier_kernel(
        queries.astype(jnp.float32), cands_planar, croot2, starts_blk, nblk,
        active, n_active, eps2, max_blocks=max_blocks, block_q=block_q,
        block_k=block_k, interpret=(backend == "interpret"))


def cross_sweep(queries, cands_planar, croot, starts, nblk, eps2, *,
                slab: int, backend=None, block_q: int = 256,
                block_k: int = 512):
    """Cross-corpus CSR slab ε-sweep (serving inner loop, DESIGN.md §10).

    The asymmetric sibling of ``csr_sweep``: Q fresh query points against an
    N-point frozen corpus in cell-sorted CSR layout. The payload plane holds
    cluster *labels* of core corpus points, so ``minroot`` is directly the
    DBSCAN-predict answer; ``mind2`` (min d² over the deciding core hits,
    +inf if none) rides along as an attachment confidence.

    queries      (T·block_q, 3) — Morton-sorted query tiles (tile t = rows
                 [t·block_q, (t+1)·block_q)); +BIG padding rows never hit
    cands_planar (3, nc)        — cell-sorted frozen corpus, nc multiple of
                 block_k, padded with +BIG
    croot        (nc,) int32    — cluster label if core else INT32_MAX
    starts       (T,) int32     — per-tile slab start, in *elements*,
                 multiples of block_k, with starts + slab ≤ nc
    nblk         (T,) int32     — per-tile live block count (≤ slab/block_k)
    slab         static per-tile slab capacity (elements, mult. of block_k)

    Returns counts (T·block_q,) int32, minroot (T·block_q,) int32, mind2
    (T·block_q,) float32. All three are bit-identical across backends (the
    float output included — both paths take mins over identically computed
    f32 distances).
    """
    backend = backend or default_backend()
    assert slab % block_k == 0 and queries.shape[0] % block_q == 0
    eps2 = jnp.asarray(eps2, jnp.float32)
    starts_blk = (starts // block_k).astype(jnp.int32)
    croot2 = croot.astype(jnp.int32)[None, :]
    max_blocks = slab // block_k
    if backend == "ref":
        return _ref.cross_sweep_ref(queries.astype(jnp.float32),
                                    cands_planar, croot2, starts_blk, nblk,
                                    eps2, max_blocks=max_blocks,
                                    block_k=block_k)
    return _cross_kernel(queries.astype(jnp.float32), cands_planar, croot2,
                         starts_blk, nblk, eps2, max_blocks=max_blocks,
                         block_q=block_q, block_k=block_k,
                         interpret=(backend == "interpret"))


def bvh_batch_sweep(queries, dlo, dhi, pt, croot, nmin, leaf, bound, eps2, *,
                    bf16_prune: bool = True, prune_payload: bool = False,
                    backend=None, block: int = 256):
    """Batched wavefront BVH expand step (one breadth-first traversal level
    of (query-block, node) entries — DESIGN.md §9, §13).

    queries (E, B, D) float, dlo/dhi/pt (E, D) float, croot/nmin/leaf (E,)
    int32, bound (E, B) int32 — see ``ref.bvh_batch_sweep_ref`` for exact
    semantics. The prune boxes arrive pre-dilated (and, when ``bf16_prune``,
    already outward-rounded to bf16 values); the sphere refine is exact f32
    regardless. Dead / padded entries are encoded geometrically (box lo
    +BIG / hi −BIG, leaf 0) so the kernel needs no validity plane; both
    backends agree bit-for-bit on all three outputs.
    Returns hit (E, B) int32, minroot (E, B) int32, push (E,) int32.
    """
    backend = backend or default_backend()
    e = queries.shape[0]
    eps2 = jnp.asarray(eps2, jnp.float32)
    kw = dict(bf16_prune=bf16_prune, prune_payload=prune_payload)
    if backend == "ref":
        return _ref.bvh_batch_sweep_ref(queries, dlo, dhi, pt, croot, nmin,
                                        leaf, bound, eps2, **kw)
    e_p = _round_up(max(e, 1), block)
    q = _pad_to(queries.astype(jnp.float32), e_p, 0, -BIG)
    lo = _pad_to(dlo.astype(jnp.float32), e_p, 0, BIG)
    hi = _pad_to(dhi.astype(jnp.float32), e_p, 0, -BIG)
    p = _pad_to(pt.astype(jnp.float32), e_p, 0, BIG)
    cr = _pad_to(croot.astype(jnp.int32), e_p, 0, INT_MAX)
    nm = _pad_to(nmin.astype(jnp.int32), e_p, 0, INT_MAX)
    lf = _pad_to(leaf.astype(jnp.int32), e_p, 0, 0)
    bd = _pad_to(bound.astype(jnp.int32), e_p, 0, jnp.iinfo(jnp.int32).min)
    scal = eps2.reshape(1, 1)
    hit, minroot, push = _bvh_kernel(
        jnp.transpose(q, (2, 1, 0)), lo.T, hi.T, p.T, cr[None, :],
        nm[None, :], lf[None, :], bd.T, scal, block=block,
        interpret=(backend == "interpret"), **kw)
    return hit.T[:e], minroot.T[:e], push[:e]


def morton_encode(coords, *, dims: int = 3, backend=None, block: int = 1024):
    """Morton codes from quantized int32 coords (n, 3) -> (n,) int32."""
    backend = backend or default_backend()
    n = coords.shape[0]
    if backend == "ref":
        return _ref.morton_encode_ref(coords, dims=dims)
    n_p = _round_up(max(n, 1), block)
    c = _pad_to(coords.astype(jnp.int32), n_p, 0, 0)
    codes = _morton_kernel(c.T, dims=dims, block=block,
                           interpret=(backend == "interpret"))
    return codes[:n]

"""Pallas TPU kernel: cell-sorted CSR slab ε-sweep (grid engine inner loop).

The CSR grid engine (DESIGN.md §3) reorders points by Morton cell code so
that every query tile's candidates form one *contiguous* slab of the sorted
array. This kernel sweeps query tile ``i`` against candidate blocks
``starts[i] .. starts[i] + nblk[i]`` of that slab — the per-tile block count
``nblk[i]`` reflects the tile's *actual* local occupancy, so a single dense
cell no longer inflates the work of every other tile (the grid-hash engine's
``27 × C_max`` worst-case window, which this kernel replaces).

Data-dependent slab starts are classic scalar-prefetch territory: the
``(T,)`` start/count arrays are prefetched to SMEM and consumed by the
BlockSpec index maps, so the pipeline DMAs exactly the blocks each tile
needs. Tiles revisit their first block for the padded tail of the grid
(``min(j, nblk-1)``) — Pallas skips the copy when the mapped block is
unchanged, so padding steps cost neither bandwidth nor VPU work (the
``j < nblk`` guard).

Layout matches ``pairwise_sweep``: queries row-major ``(nq, 3)``, candidates
coordinate-planar ``(3, nc)``, payload pre-fused (``croot = root if core
else INT32_MAX``). Padding: coords = +BIG, payload = INT32_MAX.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT_MAX = jnp.iinfo(jnp.int32).max


def _hit_mask(q_ref, c_ref, eps2):
    """ε² hit mask between a query block (bq, 3) and a planar candidate
    block (3, bk): f32 accumulation in fixed coordinate order — the exact
    arithmetic every slab kernel (and its oracle) must share for the
    cross-backend bit-identity contract to hold."""
    bq = q_ref.shape[0]
    bk = c_ref.shape[1]
    acc = jnp.zeros((bq, bk), jnp.float32)
    for k in range(3):
        d = q_ref[:, k : k + 1].astype(jnp.float32) - \
            c_ref[k : k + 1, :].astype(jnp.float32)
        acc = acc + d * d
    return acc <= eps2


def _kernel(starts_ref, nblk_ref, eps2_ref, q_ref, c_ref, croot_ref,
            counts_ref, minroot_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        minroot_ref[...] = jnp.full_like(minroot_ref, INT_MAX)

    @pl.when(j < nblk_ref[i])
    def _accumulate():
        hit = _hit_mask(q_ref, c_ref, eps2_ref[0])
        counts_ref[...] += jnp.sum(hit, axis=1, keepdims=True).astype(jnp.int32)
        root_tile = jnp.where(hit, croot_ref[...], INT_MAX)
        minroot_ref[...] = jnp.minimum(
            minroot_ref[...], jnp.min(root_tile, axis=1, keepdims=True)
        )


def _kernel_counts(starts_ref, nblk_ref, eps2_ref, q_ref, c_ref, counts_ref):
    """Counts-only body: no payload plane in, no min-root accumulation out.

    Stage-1 core identification discards ``minroot`` entirely, so this
    variant drops the ``croot`` input (one less block DMA per grid step)
    and the min-root reduce — the fused sweep reduced to the filter half.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    @pl.when(j < nblk_ref[i])
    def _accumulate():
        hit = _hit_mask(q_ref, c_ref, eps2_ref[0])
        counts_ref[...] += jnp.sum(hit, axis=1, keepdims=True).astype(jnp.int32)


def _slab_block(j, start, nblk):
    """Candidate block index for grid step (i, j): walk the tile's slab, then
    park on the last visited block so padded steps trigger no new DMA."""
    return start + jnp.minimum(j, jnp.maximum(nblk - 1, 0))


@functools.partial(jax.jit,
                   static_argnames=("max_blocks", "block_q", "block_k",
                                    "interpret"))
def csr_sweep_counts(queries, cands_planar, starts_blk, nblk, eps2, *,
                     max_blocks: int, block_q: int = 256, block_k: int = 512,
                     interpret: bool = False):
    """Counts-only slab sweep (stage-1 core identification).

    Same contract as :func:`csr_sweep` minus the payload: no ``croot``
    input, no ``minroot`` output. Returns counts (T·block_q,) int32.
    """
    nq = queries.shape[0]
    nc = cands_planar.shape[1]
    T = starts_blk.shape[0]
    assert nq == T * block_q and nc % block_k == 0, (nq, nc, T, block_q,
                                                     block_k)
    assert max_blocks * block_k <= nc, (max_blocks, block_k, nc)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T, max_blocks),
        in_specs=[
            pl.BlockSpec((block_q, 3), lambda i, j, st, nb, e: (i, 0)),
            pl.BlockSpec((3, block_k),
                         lambda i, j, st, nb, e:
                         (0, _slab_block(j, st[i], nb[i]))),
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda i, j, st, nb, e: (i, 0)),
        ],
    )
    (counts,) = pl.pallas_call(
        _kernel_counts,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nq, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(starts_blk.astype(jnp.int32), nblk.astype(jnp.int32),
      eps2.reshape(1).astype(jnp.float32), queries, cands_planar)
    return counts[:, 0]


@functools.partial(jax.jit,
                   static_argnames=("max_blocks", "block_q", "block_k",
                                    "interpret"))
def csr_sweep(queries, cands_planar, croot, starts_blk, nblk, eps2, *,
              max_blocks: int, block_q: int = 256, block_k: int = 512,
              interpret: bool = False):
    """Fused filter+payload over per-tile contiguous candidate slabs.

    queries      (T·block_q, 3) float — sorted query tiles
    cands_planar (3, nc) float        — cell-sorted candidates, nc mult. of
                                        block_k
    croot        (1, nc) int32        — root if core else INT32_MAX
    starts_blk   (T,) int32           — slab start per tile, in block_k units
    nblk         (T,) int32           — slab length per tile, in block_k
                                        units, each ≤ max_blocks
    eps2         (1,) float32
    max_blocks   static grid extent for the slab walk (plan-time slab
                 capacity ÷ block_k)
    Returns counts (T·block_q,) int32, minroot (T·block_q,) int32, both
    counted over exactly the ``nblk[i]`` blocks of each tile's slab.
    """
    nq = queries.shape[0]
    nc = cands_planar.shape[1]
    T = starts_blk.shape[0]
    assert nq == T * block_q and nc % block_k == 0, (nq, nc, T, block_q,
                                                     block_k)
    assert max_blocks * block_k <= nc, (max_blocks, block_k, nc)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T, max_blocks),
        in_specs=[
            pl.BlockSpec((block_q, 3), lambda i, j, st, nb, e: (i, 0)),
            pl.BlockSpec((3, block_k),
                         lambda i, j, st, nb, e:
                         (0, _slab_block(j, st[i], nb[i]))),
            pl.BlockSpec((1, block_k),
                         lambda i, j, st, nb, e:
                         (0, _slab_block(j, st[i], nb[i]))),
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda i, j, st, nb, e: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j, st, nb, e: (i, 0)),
        ],
    )
    counts, minroot = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(starts_blk.astype(jnp.int32), nblk.astype(jnp.int32),
      eps2.reshape(1).astype(jnp.float32), queries, cands_planar, croot)
    return counts[:, 0], minroot[:, 0]

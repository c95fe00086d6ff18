"""Pallas TPU kernel: cross-corpus CSR slab ε-sweep (serving inner loop).

Every other sweep kernel in this package is a *self-join*: n points queried
against themselves. Serving (DESIGN.md §10) asks the asymmetric question —
Q fresh query points against an N-point *frozen* corpus whose cell-sorted
CSR layout was built once at snapshot time. This kernel is that cross join:
query tile ``i`` (Morton-sorted queries, so nearby queries share window
cells) walks candidate blocks ``starts[i] .. starts[i] + nblk[i]`` of the
frozen corpus slab, exactly the scalar-prefetch idiom of ``csr_sweep`` —
the ``(T,)`` start/count arrays are prefetched to SMEM and consumed by the
BlockSpec index maps, so the pipeline DMAs only the blocks each tile needs,
and padded grid steps park on the previous block (no copy, no VPU work).

Differences from the self-join kernel, both serving-driven:

  * the payload plane carries the corpus *cluster label* of core points
    (``croot = label if core else INT32_MAX``), so ``minroot`` is directly
    the DBSCAN-predict answer (min label over ε-reachable core points);
  * a third output ``mind2`` — min squared distance over the core hits that
    decided ``minroot`` (+inf when none) — gives the caller an attachment
    confidence for free; it falls out of the same distance tile.

Layout matches ``csr_sweep``: queries row-major ``(T·block_q, 3)``,
candidates coordinate-planar ``(3, nc)``. Padding: coords +BIG (padded
queries can never hit finite corpus points), payload INT32_MAX.

Payload-id contract for sharded serving (DESIGN.md §15.3): the kernel
only ever *min-reduces* the payload plane, so callers may load it with
any label encoding whose order embeds the global one. The sharded tier
exploits this by carrying **shard-local dense ids** (the s-th smallest
global cluster label present in the shard is id s): because that remap
is monotone, per-shard ``minroot`` mapped back through the shard's label
table and min-merged across shards is bit-identical to a global
``minroot`` — no kernel change, just a different payload plane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT_MAX = jnp.iinfo(jnp.int32).max
INF = float("inf")  # plain float: jnp scalars would be captured consts


def _kernel(starts_ref, nblk_ref, eps2_ref, q_ref, c_ref, croot_ref,
            counts_ref, minroot_ref, mind2_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        minroot_ref[...] = jnp.full_like(minroot_ref, INT_MAX)
        mind2_ref[...] = jnp.full_like(mind2_ref, INF)

    @pl.when(j < nblk_ref[i])
    def _accumulate():
        eps2 = eps2_ref[0]
        bq = q_ref.shape[0]
        bk = c_ref.shape[1]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for k in range(3):
            d = q_ref[:, k : k + 1].astype(jnp.float32) - \
                c_ref[k : k + 1, :].astype(jnp.float32)
            acc = acc + d * d
        hit = acc <= eps2
        core = croot_ref[...] != INT_MAX

        counts_ref[...] += jnp.sum(hit, axis=1, keepdims=True).astype(jnp.int32)
        root_tile = jnp.where(hit & core, croot_ref[...], INT_MAX)
        minroot_ref[...] = jnp.minimum(
            minroot_ref[...], jnp.min(root_tile, axis=1, keepdims=True))
        d2_tile = jnp.where(hit & core, acc, INF)
        mind2_ref[...] = jnp.minimum(
            mind2_ref[...], jnp.min(d2_tile, axis=1, keepdims=True))


def _slab_block(j, start, nblk):
    """Candidate block for grid step (i, j): walk the tile's slab, then park
    on the last visited block so padded steps trigger no new DMA."""
    return start + jnp.minimum(j, jnp.maximum(nblk - 1, 0))


@functools.partial(jax.jit,
                   static_argnames=("max_blocks", "block_q", "block_k",
                                    "interpret"))
def cross_sweep(queries, cands_planar, croot, starts_blk, nblk, eps2, *,
                max_blocks: int, block_q: int = 256, block_k: int = 512,
                interpret: bool = False):
    """Cross-corpus filter+payload over per-tile contiguous candidate slabs.

    queries      (T·block_q, 3) float — Morton-sorted query tiles (fresh
                 points, NOT the corpus)
    cands_planar (3, nc) float        — cell-sorted frozen corpus, nc mult.
                 of block_k, +BIG padded
    croot        (1, nc) int32        — cluster label if core else INT32_MAX
    starts_blk   (T,) int32           — slab start per tile, in block_k units
    nblk         (T,) int32           — slab length per tile, in block_k
                                        units, each ≤ max_blocks
    eps2         (1,) float32
    max_blocks   static grid extent for the slab walk

    Returns counts (T·block_q,) int32  — ε-neighbors in the corpus (no self:
                                         queries are not corpus members),
            minroot (T·block_q,) int32 — min core label within ε (INT32_MAX
                                         if none): the predict answer,
            mind2 (T·block_q,) float32 — min d² over those core hits (+inf
                                         if none),
    all counted over exactly the ``nblk[i]`` blocks of each tile's slab.
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
            pl.BlockSpec((block_q, 1), lambda i, j, st, nb, e: (i, 0)),
        ],
    )
    counts, minroot, mind2 = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(starts_blk.astype(jnp.int32), nblk.astype(jnp.int32),
      eps2.reshape(1).astype(jnp.float32), queries, cands_planar, croot)
    return counts[:, 0], minroot[:, 0], mind2[:, 0]

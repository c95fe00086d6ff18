"""Pallas TPU kernel: frontier-compacted CSR slab ε-sweep (stage-2 rounds).

The frontier round driver (DESIGN.md §11) re-sweeps only the *live* query
tiles of the CSR grid each hooking round — the tiles that could still
produce a new union — and parks the rest. This kernel is ``csr_sweep``
restricted to an **active-tile index vector**: grid step ``(i, j)`` sweeps
query tile ``active[i]`` against candidate blocks ``starts[active[i]] ..
starts[active[i]] + nblk[active[i]]`` when ``i < n_active``, and does
nothing (no DMA, no VPU work) otherwise.

The dynamic trip count is the same tiled-expansion trick as the wavefront
BVH's level loop (``bvh_sweep``): the grid is sized by the static tile
count ``T``, but steps beyond the live count are *parked* — callers
pre-fill ``active[i >= n_active]`` with the last live tile id, so the
parked steps' BlockSpec index maps resolve to blocks already resident in
VMEM and Pallas skips the copy. Cost therefore tracks the live frontier,
not the tile capacity, exactly like the wavefront's per-level tiles.

Outputs are *compacted*: slot ``i`` of the output holds tile
``active[i]``'s min-root rows (slots ``>= n_active`` hold INT32_MAX); the
wrapper scatters them back to tile positions. Only ``minroot`` is computed
— stage-2 hooking discards counts, so the counts plane (input DMA +
row-sum) is dropped entirely.

Layout matches ``csr_sweep``: queries row-major ``(T·block_q, 3)``,
candidates coordinate-planar ``(3, nc)``, payload pre-fused
(``croot = root if core else INT32_MAX``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .csr_sweep import _hit_mask, _slab_block

INT_MAX = jnp.iinfo(jnp.int32).max


def _kernel(active_ref, na_ref, starts_ref, nblk_ref, eps2_ref, q_ref,
            c_ref, croot_ref, minroot_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    t = active_ref[i]

    @pl.when(j == 0)
    def _init():
        minroot_ref[...] = jnp.full_like(minroot_ref, INT_MAX)

    @pl.when(jnp.logical_and(i < na_ref[0], j < nblk_ref[t]))
    def _accumulate():
        hit = _hit_mask(q_ref, c_ref, eps2_ref[0])
        root_tile = jnp.where(hit, croot_ref[...], INT_MAX)
        minroot_ref[...] = jnp.minimum(
            minroot_ref[...], jnp.min(root_tile, axis=1, keepdims=True)
        )


@functools.partial(jax.jit,
                   static_argnames=("max_blocks", "block_q", "block_k",
                                    "interpret"))
def frontier_sweep(queries, cands_planar, croot, starts_blk, nblk, active,
                   n_active, eps2, *, max_blocks: int, block_q: int = 256,
                   block_k: int = 512, interpret: bool = False):
    """Min-root over per-tile slabs, restricted to the active tiles.

    queries      (T·block_q, 3) float — sorted query tiles
    cands_planar (3, nc) float        — cell-sorted candidates, nc mult. of
                                        block_k
    croot        (1, nc) int32        — root if core else INT32_MAX
    starts_blk   (T,) int32           — slab start per tile (block_k units)
    nblk         (T,) int32           — slab block count per tile
    active       (T,) int32           — live tile ids compacted to the
                 front; entries at positions >= n_active must repeat the
                 last live id (or 0 when none) so parked grid steps revisit
                 resident blocks instead of triggering DMAs
    n_active     (1,) int32           — live tile count
    eps2         (1,) float32
    max_blocks   static grid extent for the slab walk

    Returns minroot (T·block_q,) int32, *compacted*: rows
    ``[i·block_q, (i+1)·block_q)`` belong to tile ``active[i]`` for
    ``i < n_active`` and are INT32_MAX beyond.
    """
    nq = queries.shape[0]
    nc = cands_planar.shape[1]
    T = starts_blk.shape[0]
    assert nq == T * block_q and nc % block_k == 0, (nq, nc, T, block_q,
                                                     block_k)
    assert max_blocks * block_k <= nc, (max_blocks, block_k, nc)

    # Parked steps (i >= n_active) must map to the block already resident
    # from the last live slot's final step: act[i] repeats the last live
    # tile (the wrapper contract), and the j operand is pinned to the walk's
    # end so the parked (i, j) sequence never re-walks the slab — without
    # the pin, j resetting to 0 at the live->parked boundary would re-DMA
    # the whole slab once per parked slot on the compiled path.
    def _park_j(i, j, na):
        return jnp.where(i < na[0], j, max_blocks - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T, max_blocks),
        in_specs=[
            pl.BlockSpec((block_q, 3),
                         lambda i, j, act, na, st, nb, e: (act[i], 0)),
            pl.BlockSpec((3, block_k),
                         lambda i, j, act, na, st, nb, e:
                         (0, _slab_block(_park_j(i, j, na), st[act[i]],
                                         nb[act[i]]))),
            pl.BlockSpec((1, block_k),
                         lambda i, j, act, na, st, nb, e:
                         (0, _slab_block(_park_j(i, j, na), st[act[i]],
                                         nb[act[i]]))),
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1),
                         lambda i, j, act, na, st, nb, e: (i, 0)),
        ],
    )
    (minroot,) = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nq, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(active.astype(jnp.int32), n_active.astype(jnp.int32),
      starts_blk.astype(jnp.int32), nblk.astype(jnp.int32),
      eps2.reshape(1).astype(jnp.float32), queries, cands_planar, croot)
    return minroot[:, 0]

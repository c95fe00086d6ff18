"""Pallas TPU kernel: pre-gathered candidate ε-sweep (grid engine inner loop).

The grid engine (``repro.core.grid``) is the TPU adaptation of the paper's
hardware BVH: the spatial hash selects, per query point, a fixed-shape window
of candidate cells; XLA performs the HBM gather, and this kernel fuses the
exact distance filter + both DBSCAN payloads over the gathered window in
VMEM. This mirrors the paper's split (Algorithm 2): the *structure* prunes
(bounding volume hit), the *kernel* refines (exact sphere test, line 6).

Layout: candidates are coordinate-planar ``(3, b, k)`` so each coordinate
plane is a natural (BB, BK) VPU tile; queries are row-major ``(b, 3)`` so a
query coordinate is a (BB, 1) sublane vector. Padding: coords = +BIG,
payload = INT32_MAX (min-ignored), exactly as in ``pairwise_sweep``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT_MAX = jnp.iinfo(jnp.int32).max


def _kernel(eps2_ref, q_ref, c_ref, croot_ref, counts_ref, minroot_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        minroot_ref[...] = jnp.full_like(minroot_ref, INT_MAX)

    eps2 = eps2_ref[0, 0]
    bb = q_ref.shape[0]
    bk = c_ref.shape[2]
    acc = jnp.zeros((bb, bk), jnp.float32)
    for k in range(3):
        d = q_ref[:, k : k + 1].astype(jnp.float32) - c_ref[k].astype(jnp.float32)
        acc = acc + d * d
    hit = acc <= eps2

    counts_ref[...] += jnp.sum(hit, axis=1, keepdims=True).astype(jnp.int32)
    root_tile = jnp.where(hit, croot_ref[...], INT_MAX)
    minroot_ref[...] = jnp.minimum(
        minroot_ref[...], jnp.min(root_tile, axis=1, keepdims=True)
    )


@functools.partial(jax.jit, static_argnames=("block_b", "block_k", "interpret"))
def gathered_sweep(queries, cands_planar, croot, eps2, *, block_b: int = 128,
                   block_k: int = 512, interpret: bool = False):
    """Fused filter+payload over per-query candidate windows.

    queries      (b, 3) float    — b multiple of block_b
    cands_planar (3, b, k) float — k multiple of block_k
    croot        (b, k) int32    — root if core else INT32_MAX
    eps2         scalar float32
    Returns counts (b,) int32, minroot (b,) int32.
    """
    b = queries.shape[0]
    k = cands_planar.shape[2]
    assert b % block_b == 0 and k % block_k == 0, (b, k, block_b, block_k)
    grid = (b // block_b, k // block_k)

    counts, minroot = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_b, 3), lambda i, j: (i, 0)),
            pl.BlockSpec((3, block_b, block_k), lambda i, j: (0, i, j)),
            pl.BlockSpec((block_b, block_k), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(eps2.reshape(1, 1).astype(jnp.float32), queries, cands_planar, croot)
    return counts[:, 0], minroot[:, 0]

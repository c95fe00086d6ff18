"""Pallas TPU kernel: tiled brute-force ε-sweep (fused count + min-core-root).

This is the TPU-native analogue of the paper's RT-FindNeighbor primitive for
the brute engine: a (BI × BJ)-tiled pass over all (query, candidate) pairs
that never materializes the distance matrix in HBM. Because the coordinate
contraction axis is ≤ 3 (the paper's own RT-core dimensionality limit, which
we keep), the MXU is useless here (K=3 of 128 lanes); the kernel is a pure
VPU workload and the layout is chosen for the VPU:

  * queries are row-major ``(nq, 3)`` — a query coordinate column ``q[:, k]``
    is a natural (BI, 1) sublane vector;
  * candidates are **coordinate-planar** ``(3, nc)`` — a candidate coordinate
    row ``c[k, :]`` is a natural (1, BJ) lane vector;
  * the (BI, BJ) difference tile is then a single broadcast subtract per
    coordinate — three VPU FMAs total per tile, no transposes.

Padded candidates carry coords = +BIG so dist² > ε² masks them for free, and
payload root = INT32_MAX so the min-reduction ignores them. The core mask is
pre-fused into the payload (``croot = root if core else INT32_MAX``) so the
kernel carries a single int32 payload plane.

Outputs accumulate across the candidate grid axis (j revisits the same output
block; init at j == 0) — the standard Pallas reduction pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT_MAX = jnp.iinfo(jnp.int32).max
BIG = jnp.float32(1e30)


def _kernel(eps2_ref, q_ref, c_ref, croot_ref, counts_ref, minroot_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        minroot_ref[...] = jnp.full_like(minroot_ref, INT_MAX)

    eps2 = eps2_ref[0, 0]
    bi = q_ref.shape[0]
    bj = c_ref.shape[1]
    acc = jnp.zeros((bi, bj), jnp.float32)
    for k in range(3):  # unrolled coordinate-planar dx²+dy²+dz²
        d = q_ref[:, k : k + 1].astype(jnp.float32) - c_ref[k : k + 1, :].astype(
            jnp.float32
        )
        acc = acc + d * d
    hit = acc <= eps2

    counts_ref[...] += jnp.sum(hit, axis=1, keepdims=True).astype(jnp.int32)
    root_tile = jnp.where(hit, croot_ref[...], INT_MAX)  # (1,BJ) -> (BI,BJ)
    minroot_ref[...] = jnp.minimum(
        minroot_ref[...], jnp.min(root_tile, axis=1, keepdims=True)
    )


@functools.partial(jax.jit, static_argnames=("block_q", "block_c", "interpret"))
def pairwise_sweep(queries, cands_planar, croot, eps2, *, block_q: int = 256,
                   block_c: int = 512, interpret: bool = False):
    """Tiled ε-sweep.

    queries      (nq, 3) float   — nq must be a multiple of block_q
    cands_planar (3, nc) float   — nc must be a multiple of block_c
    croot        (1, nc) int32   — root if core else INT32_MAX (padded: INT32_MAX)
    eps2         (1, 1) float32
    Returns counts (nq,) int32, minroot (nq,) int32.
    """
    nq = queries.shape[0]
    nc = cands_planar.shape[1]
    assert nq % block_q == 0 and nc % block_c == 0, (nq, nc, block_q, block_c)
    grid = (nq // block_q, nc // block_c)

    counts, minroot = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_q, 3), lambda i, j: (i, 0)),
            pl.BlockSpec((3, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(eps2.reshape(1, 1).astype(jnp.float32), queries, cands_planar, croot)
    return counts[:, 0], minroot[:, 0]

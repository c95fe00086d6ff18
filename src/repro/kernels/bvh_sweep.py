"""Pallas TPU kernel: batched wavefront BVH expand step (DESIGN.md §9, §13).

One breadth-first traversal level of the LBVH. The host-side driver
(``repro.core.bvh.wavefront_sweep``) keeps a compacted work queue of
(query-block, node) *entries* — the software analogue of the RT core's ray
queue — and per level expands every live entry into its two children. Three
RT-kNNS-Unbound techniques are fused here:

  * **query batching** — each entry carries B consecutive Morton-sorted
    queries, so one AABB load amortizes over a (B, block) tile of tests
    instead of a single query: the frontier (and every gather / compaction
    scatter around this kernel) shrinks ~B× while the VPU math stays dense;
  * **two-phase prune / refine** — the prune pass compares against
    *pre-dilated*, outward-rounded bf16 boxes (built once per tree+ε in
    ``core/bvh.py``; queries are round-to-nearest cast in here), so box
    storage and gather traffic halve; survivors hit the exact f32 sphere
    refine (Algorithm 2 line 6), whose result never depends on the prune
    dtype — bf16 admits a superset of the f32-pruned visits by
    construction, so labels are bit-identical;
  * **early termination** — in payload mode a column (entry × query) is
    *useful* only while the subtree's min payload can still lower that
    query's running min-root bound; an entry whose every column is useless
    is not pushed, so resolved queries fall out of the next frontier.

Layout: coordinate-planar queries ``(D, B, E)``, per-entry planes ``(D, E)``
(boxes / leaf point) and ``(1, E)`` (payload / leaf flag), per-column bound
``(B, E)``. Dead entries are encoded geometrically (box lo = +BIG,
hi = −BIG, query = −BIG, payload = INT32_MAX) so no validity plane is
needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT_MAX = jnp.iinfo(jnp.int32).max


def _kernel(scal_ref, q_ref, dlo_ref, dhi_ref, pt_ref, croot_ref, nmin_ref,
            leaf_ref, bound_ref, hit_ref, minroot_ref, push_ref, *,
            dims: int, bf16_prune: bool, prune_payload: bool):
    eps2 = scal_ref[0, 0]
    nb, blk = bound_ref.shape
    inside = jnp.ones((nb, blk), jnp.bool_)
    d2 = jnp.zeros((nb, blk), jnp.float32)
    for k in range(dims):
        q = q_ref[k].astype(jnp.float32)                   # (B, blk)
        if bf16_prune:
            # RN cast vs the outward-rounded dilated box = conservative
            qp = q.astype(jnp.bfloat16).astype(jnp.float32)
        else:
            qp = q
        dlo = dlo_ref[k : k + 1, :].astype(jnp.float32)    # (1, blk)
        dhi = dhi_ref[k : k + 1, :].astype(jnp.float32)
        inside = inside & (qp >= dlo) & (qp <= dhi)
        d = q - pt_ref[k : k + 1, :].astype(jnp.float32)
        d2 = d2 + d * d
    leaf = leaf_ref[...] != 0                              # (1, blk)
    hit = leaf & (d2 <= eps2)                              # exact f32 refine
    hit_ref[...] = hit.astype(jnp.int32)
    minroot_ref[...] = jnp.where(hit, croot_ref[...], INT_MAX)
    if prune_payload:
        useful = inside & (nmin_ref[...] < bound_ref[...])
    else:
        useful = inside
    push_ref[...] = (jnp.logical_not(leaf)
                     & jnp.any(useful, axis=0, keepdims=True)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "bf16_prune",
                                             "prune_payload", "interpret"))
def bvh_batch_sweep(q_planar, dlo_planar, dhi_planar, pt_planar, croot, nmin,
                    leaf, bound, scal, *, block: int = 256,
                    bf16_prune: bool = True, prune_payload: bool = False,
                    interpret: bool = False):
    """Fused batched prune/refine over one frontier of (query-block, node)
    entries.

    q_planar    (D, B, E) float — B queries per entry, coordinate-planar
    dlo_planar  (D, E) float — pre-dilated prune box lo (bf16-valued when
                ``bf16_prune``; leaf entries use the dilated leaf box)
    dhi_planar  (D, E) float — pre-dilated prune box hi
    pt_planar   (D, E) float — leaf point (internal entries: don't-care)
    croot       (1, E) int32 — leaf payload: root if core else INT32_MAX
    nmin        (1, E) int32 — subtree min payload (payload mode only)
    leaf        (1, E) int32 — 1 iff the child is a leaf
    bound       (B, E) int32 — per-column running min-root bound
    scal        (1, 1) f32   — [ε²]
    E must be a multiple of ``block``. Returns hit (B, E) int32 ∈ {0, 1},
    minroot (B, E) int32, push (1, E) int32 ∈ {0, 1}.
    """
    dims, nb, f = q_planar.shape
    assert f % block == 0, (f, block)
    kern = functools.partial(_kernel, dims=dims, bf16_prune=bf16_prune,
                             prune_payload=prune_payload)
    hit, minroot, push = pl.pallas_call(
        kern,
        grid=(f // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((dims, nb, block), lambda i: (0, 0, i)),
            pl.BlockSpec((dims, block), lambda i: (0, i)),
            pl.BlockSpec((dims, block), lambda i: (0, i)),
            pl.BlockSpec((dims, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((nb, block), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((nb, block), lambda i: (0, i)),
            pl.BlockSpec((nb, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, f), jnp.int32),
            jax.ShapeDtypeStruct((nb, f), jnp.int32),
            jax.ShapeDtypeStruct((1, f), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(scal.astype(jnp.float32), q_planar, dlo_planar, dhi_planar, pt_planar,
      croot, nmin, leaf, bound)
    return hit, minroot, push[0]

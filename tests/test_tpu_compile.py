"""Compile every Pallas kernel of the main path for a TPU v5e chip.

Interpret mode runs the kernel bodies on the CPU but cannot see what the
chip's compiler refuses: unaligned tiling, fast-memory overuse, scalar
prefetch that overflows SMEM. These tests compile each kernel for one chip
of a *described* (not attached) ``v5e:2x2`` topology at the shapes
``chip_smoke.py`` drives, and require the kernel to lower to a Mosaic
``tpu_custom_call``. Nothing runs, so no result or time is checked.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and it keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bvh_sweep import bvh_batch_sweep
from repro.kernels.cross_sweep import cross_sweep
from repro.kernels.csr_sweep import csr_sweep, csr_sweep_counts
from repro.kernels.frontier_sweep import frontier_sweep
from repro.kernels.morton import morton_encode

BLOCK_Q, BLOCK_K = 256, 512
# (tiles, slab blocks, padded candidates) that plan_csr_grid gives for the
# smoke's corpora: roadnet2d at 434,874 points (ε 0.02) and taxi2d at
# 1,000,000 points (ε 0.08)
PLANS = {"roadnet2d-434874": (1699, 686, 435200),
         "taxi2d-1000000": (3907, 1329, 1000448)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text


def _slab_shapes(plan):
    T, _, nc = plan
    return [((T * BLOCK_Q, 3), jnp.float32), ((3, nc), jnp.float32)]


@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("kernel", ["csr_sweep", "csr_sweep_counts",
                                    "frontier_sweep", "cross_sweep"])
def test_slab_kernel_compiles(one_chip, kernel, plan):
    T, max_blocks, nc = PLANS[plan]
    tiles = ((T,), jnp.int32)
    croot = ((1, nc), jnp.int32)
    eps2 = ((), jnp.float32)
    shapes = {
        "csr_sweep": [croot, tiles, tiles, eps2],
        "csr_sweep_counts": [tiles, tiles, eps2],
        "frontier_sweep": [croot, tiles, tiles, tiles, ((1,), jnp.int32),
                           eps2],
        "cross_sweep": [croot, tiles, tiles, eps2],
    }[kernel]
    fn = {"csr_sweep": csr_sweep, "csr_sweep_counts": csr_sweep_counts,
          "frontier_sweep": frontier_sweep, "cross_sweep": cross_sweep}[kernel]
    _compile(fn, one_chip, *_slab_shapes(PLANS[plan]), *shapes,
             max_blocks=max_blocks, block_q=BLOCK_Q, block_k=BLOCK_K)


@pytest.mark.parametrize("prune_payload", [False, True])
@pytest.mark.parametrize("dims", [3, 6])
def test_bvh_batch_sweep_compiles(one_chip, dims, prune_payload):
    E, B = 65536, 8
    f32, i32 = jnp.float32, jnp.int32
    _compile(bvh_batch_sweep, one_chip,
             ((dims, B, E), f32), ((dims, E), f32), ((dims, E), f32),
             ((dims, E), f32), ((1, E), i32), ((1, E), i32), ((1, E), i32),
             ((B, E), i32), ((1, 1), f32),
             bf16_prune=True, prune_payload=prune_payload)


@pytest.mark.parametrize("dims", [2, 3])
def test_morton_encode_compiles(one_chip, dims):
    n = PLANS["taxi2d-1000000"][2]
    _compile(morton_encode, one_chip, ((3, n), jnp.int32), dims=dims)

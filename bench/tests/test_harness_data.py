"""The benchmark's copied generators are pinned: a change to them changes
the yardstick's data, and has to change these digests knowingly."""
import hashlib

import numpy as np
import pytest

import harness_paths  # noqa: F401
from bench import data

DIGESTS = {
    ("roadnet2d", 0, None): "c7af43d83e35c96f38e6e80dfc6006f7",
    ("roadnet2d", 0, 0): "212114786b9dfbc8e1a540e68d537aad",
    ("roadnet2d", 3000000001, None): "4716803fe1194bac050b6e4ec1aa8677",
    ("roadnet2d", 3000000001, 0): "68c7365e0512733965751e869048bb78",
    ("roadnet3d", 0, None): "c033af20aee6d52f08b433e9b25b08d5",
    ("roadnet3d", 0, 0): "b942d284206ffbf5fd415024b9f320db",
    ("roadnet3d", 3000000001, None): "5b2e240f9730e7fc91f8f47b326367d3",
    ("roadnet3d", 3000000001, 0): "976b860c4a173729669076921f5cfcec",
    ("taxi2d", 0, None): "5c3db9b837f13e58015cf8e277a6ac69",
    ("taxi2d", 0, 0): "eacc226c8d1c8e761c79459cb5477ce7",
    ("taxi2d", 3000000001, None): "031f184b1577f1e7a3a3d8953a5ef5bd",
    ("taxi2d", 3000000001, 0): "ea1ea53513cc58288ca397d3e2f9f6ea",
}


@pytest.mark.parametrize("key", sorted(DIGESTS, key=str))
def test_generator_digest(key):
    name, seed, world = key
    pts = data.load(name, 5000, seed, structure_seed=world)
    assert pts.shape == (5000, 3) and str(pts.dtype) == "float32"
    assert hashlib.sha256(pts.tobytes()).hexdigest()[:32] == DIGESTS[key]


def test_sub_seeds_are_distinct_and_fit_63_bits():
    seeds = {data.sub_seed(2**31 + 5, k) for k in range(100)}
    assert len(seeds) == 100 and max(seeds) < 2**63


def test_roadnet3d_is_roadnet2d_with_altitude():
    flat = data.load("roadnet2d", 3000, 7, structure_seed=0)
    pts = data.load("roadnet3d", 3000, 7, structure_seed=0)
    assert np.array_equal(pts[:, :2], flat[:, :2])
    z = pts[:, 2]
    assert z.min() > -5 * data.ALTITUDE_NOISE
    assert z.max() < data.RELIEF + 5 * data.ALTITUDE_NOISE
    assert z.std() > data.RELIEF / 10


@pytest.mark.parametrize("seed", [3, 2**31 + 99])
def test_riffle_keeps_each_cells_order(seed):
    """Every seed: the same points, each cell's points in the sample's own
    order, the cells interleaved differently."""
    base = data.load("roadnet3d", 4000, 0, structure_seed=0)
    cell = 0.5
    perm = data.riffle(base, cell, np.random.default_rng(seed))
    assert np.array_equal(np.sort(perm), np.arange(len(base)))
    key = [tuple(k) for k in
           np.floor((base - base.min(0)) * np.float32(1 / cell)).astype(int)]
    seen = {}
    for i in perm:
        assert seen.get(key[i], -1) < i  # rising within each cell
        seen[key[i]] = i
    other = data.riffle(base, cell, np.random.default_rng(seed + 1))
    assert not np.array_equal(perm, other)
    got = data.ordered("roadnet3d", 4000, 0, seed, cell=cell)
    assert sorted(map(tuple, got)) == sorted(map(tuple, data.load(
        "roadnet3d", 4000, data.sub_seed(0, 0), structure_seed=0)))

"""The benchmark's own copy of the seeded dataset stand-ins.

Copied from ``repro.data.synth`` (``roadnet2d``, ``taxi2d``) so that a change
to the program cannot change the data the benchmark measures it on;
``tests/test_harness_data.py`` pins the output by digest at two seeds.

* ``roadnet2d`` stands in for the UCI 3D Road Network (North Jutland): a
  random planar graph wandered by noisy walkers — long 1-D chains.
* ``taxi2d`` stands in for the Porto taxi GPS set: twelve dense urban hubs
  (70% of the points) plus route traffic between them.

Both return float32 ``(n, 3)`` with z = 0. ``structure_seed`` draws the
world (road-graph nodes, hub centres and widths) from its own stream, so
points drawn with different ``seed`` but one ``structure_seed`` share a
world; without it one stream is drawn through in order.

``roadnet3d`` is the benchmark's own: ``roadnet2d`` with the set's third
attribute, altitude, as a smooth terrain of the world's drawn under every
point.
"""
from __future__ import annotations

import numpy as np


def _as3(points2d: np.ndarray) -> np.ndarray:
    z = np.zeros((len(points2d), 1), np.float32)
    return np.concatenate([points2d.astype(np.float32), z], axis=1)


def _split_rng(seed: int, structure_seed):
    rng = np.random.default_rng(seed)
    rs = rng if structure_seed is None else np.random.default_rng(
        structure_seed)
    return rs, rng


def roadnet2d(n: int, seed: int = 0, structure_seed: int | None = None,
              structure_n: int | None = None) -> np.ndarray:
    rs, rng = _split_rng(seed, structure_seed)
    n_nodes = max(16, (n if structure_n is None else structure_n) // 2000)
    nodes = rs.uniform(0.0, 10.0, (n_nodes, 2))
    pts = np.empty((n, 2), np.float32)
    i = 0
    while i < n:
        a, b = rng.integers(0, n_nodes, 2)
        seg = rng.integers(20, 200)
        seg = min(seg, n - i)
        t = np.linspace(0, 1, seg)[:, None]
        line = nodes[a] * (1 - t) + nodes[b] * t
        line += rng.normal(0, 0.004, line.shape)
        pts[i:i + seg] = line
        i += seg
    return _as3(pts)


def taxi2d(n: int, seed: int = 0, structure_seed: int | None = None,
           structure_n: int | None = None) -> np.ndarray:
    rs, rng = _split_rng(seed, structure_seed)
    n_hubs = 12
    hubs = rs.uniform(0.0, 8.0, (n_hubs, 2))
    widths = rs.uniform(0.3, 1.0, (n_hubs,)) if structure_seed is not None \
        else None
    n_blob = int(n * 0.7)
    which = rng.integers(0, n_hubs, n_blob)
    if widths is None:
        widths_blob = rng.normal(0, 0.15, (n_blob, 2)) * \
            rng.uniform(0.3, 1.0, (n_hubs,))[which][:, None]
    else:
        widths_blob = rng.normal(0, 0.15, (n_blob, 2)) * \
            widths[which][:, None]
    blob = hubs[which] + widths_blob
    n_route = n - n_blob
    a = hubs[rng.integers(0, n_hubs, n_route)]
    b = hubs[rng.integers(0, n_hubs, n_route)]
    t = rng.uniform(0, 1, (n_route, 1))
    route = a * (1 - t) + b * t + rng.normal(0, 0.03, (n_route, 2))
    return _as3(np.concatenate([blob, route]))


# North Jutland spans about 190 km from west to east and rises about 140 m
# above the sea; the stand-in's 10 units stand for that span, so its relief
# is 140 m / 19 km per unit.
RELIEF = 140.0 / 19_000.0
TERRAIN_WAVES = 6          # plane waves summed into the terrain
WAVELENGTH = (1.0, 5.0)    # units: about 19 to 95 km
ALTITUDE_NOISE = 2.0 / 19_000.0  # about 2 m per point


def roadnet3d(n: int, seed: int = 0, structure_seed: int | None = None,
              structure_n: int | None = None) -> np.ndarray:
    """``roadnet2d`` with altitude: ``RELIEF`` times a terrain in [0, 1],
    the mean of ``TERRAIN_WAVES`` plane waves of the world (drawn from
    ``structure_seed``, else ``seed``), plus per-point noise."""
    pts = roadnet2d(n, seed, structure_seed, structure_n)
    world = seed if structure_seed is None else structure_seed
    rt = np.random.default_rng([int(world), 3])
    length = rt.uniform(*WAVELENGTH, TERRAIN_WAVES)
    angle = rt.uniform(0.0, 2 * np.pi, TERRAIN_WAVES)
    phase = rt.uniform(0.0, 2 * np.pi, TERRAIN_WAVES)
    wave = (2 * np.pi / length)[:, None] * np.stack([np.cos(angle),
                                                     np.sin(angle)], 1)
    xy = pts[:, :2].astype(np.float64)
    terrain = 0.5 + 0.5 * np.sin(xy @ wave.T + phase).mean(axis=1)
    noise = np.random.default_rng([int(seed), 4]).normal(0.0, ALTITUDE_NOISE,
                                                          n)
    pts[:, 2] = (RELIEF * terrain + noise).astype(np.float32)
    return pts


DATASETS = {"roadnet2d": roadnet2d, "roadnet3d": roadnet3d, "taxi2d": taxi2d}


def sub_seed(seed: int, *path: int) -> int:
    """A 63-bit seed derived from ``seed`` and a path of small integers."""
    ss = np.random.SeedSequence([int(seed), *path])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(1))


def load(name: str, n: int, seed: int, structure_seed: int | None = None
         ) -> np.ndarray:
    return DATASETS[name](n, seed, structure_seed=structure_seed)


def ordered(name: str, n: int, world_seed: int, seed: int,
            stream: int = 0, cell: float | None = None) -> np.ndarray:
    """One fixed sample of ``n`` points from the world ``world_seed`` (one
    per ``stream``), its rows shuffled by ``seed``: every seed gets the same
    points as a different input.

    With ``cell``, the shuffle keeps the sample's own order among the points
    of each cell of side ``cell`` (a grid from the sample's minimum, in
    float32), and interleaves the cells in the seed's order: see
    :func:`riffle`."""
    pts = load(name, n, sub_seed(world_seed, stream),
               structure_seed=world_seed)
    rng = np.random.default_rng(sub_seed(seed, stream))
    if cell is None:
        return pts[rng.permutation(n)]
    return pts[riffle(pts, cell, rng)]


def riffle(pts: np.ndarray, cell: float, rng: np.random.Generator
           ) -> np.ndarray:
    """A permutation of the rows of ``pts`` that is random across cells of
    side ``cell`` and keeps the rows' order inside each cell.

    Each row gets a random slot; each cell's slots, in ascending order,
    then go to its rows in their own order. A clustering that sorts points
    by cell (stably, as the CSR engine does) sees every cell's points in
    one order for every seed, so it does the same work, while each point's
    index, and with it every label id, changes with the seed."""
    n = len(pts)
    c = np.floor((pts - pts.min(axis=0)) * np.float32(1.0 / cell))
    key = np.unique(c.astype(np.int64), axis=0, return_inverse=True)[1]
    key = key.reshape(-1)
    slots = rng.permutation(n)
    perm = np.empty(n, np.int64)
    perm[slots[np.lexsort((slots, key))]] = np.lexsort((np.arange(n), key))
    return perm

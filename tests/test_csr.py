"""CSR grid engine: degenerate/skew inputs + layout invariants.

The acceptance bar (ISSUE 1): grid-csr labels must match the brute engine —
*identically*, since both resolve components to min-original-core-index —
across one-cell pileups, exact duplicates, ragged n, and 2D (z = 0) data.
"""
import warnings

import numpy as np
import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from repro.core import grid as grid_mod
from repro.core import neighbors as nb
from repro.core.dbscan import dbscan
from repro.data import synth
from repro.kernels import ref as kref

INT_MAX = np.iinfo(np.int32).max


def _assert_matches_brute(pts, eps, minpts, **kw):
    b = dbscan(pts, eps, minpts, engine="brute")
    g = dbscan(pts, eps, minpts, engine="grid", **kw)
    np.testing.assert_array_equal(np.asarray(g.core), np.asarray(b.core))
    np.testing.assert_array_equal(np.asarray(g.counts), np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(g.labels), np.asarray(b.labels))
    return g


def test_all_points_one_cell():
    # every point inside a single ε-cell: one giant slab, still exact
    pts = np.random.default_rng(0).normal(0, 0.005, (500, 3)) \
        .astype(np.float32)
    _assert_matches_brute(pts, 0.05, 4)


def test_exact_duplicate_points():
    rng = np.random.default_rng(1)
    base = rng.uniform(0, 1, (100, 3)).astype(np.float32)
    pts = np.concatenate([base, base, base[:40]])  # heavy duplication
    _assert_matches_brute(pts, 0.03, 3)


def test_n_not_multiple_of_chunk():
    # ragged tail tile: n deliberately not a multiple of the tile chunk
    for n in (1, 7, 255, 257, 1001):
        pts = synth.blobs(n, k=3, seed=n)
        _assert_matches_brute(pts, 0.08, 4)


def test_2d_z_zero():
    pts = synth.load("taxi2d", 600, seed=3)
    assert (pts[:, 2] == 0).all()
    g = _assert_matches_brute(pts, 0.1, 6)
    assert g.labels.shape == (600,)


def test_skewed_occupancy_matches_brute():
    pts = synth.load("skewed2d", 1500, seed=4)
    _assert_matches_brute(pts, 0.05, 8)


def test_host_loop_matches_device_loop():
    pts = synth.blobs(400, k=4, seed=5)
    d = dbscan(pts, 0.08, 5, engine="grid", hook_loop="device")
    h = dbscan(pts, 0.08, 5, engine="grid", hook_loop="host")
    np.testing.assert_array_equal(np.asarray(d.labels), np.asarray(h.labels))


def test_csr_build_no_overflow_and_permutation():
    pts = synth.load("roadnet2d", 900, seed=6)
    spec = grid_mod.plan_csr_grid(pts, 0.05, dims=2)
    g = grid_mod.build_csr_grid(jnp.asarray(pts), spec)
    assert not bool(g.overflow), "plan slab capacity violated at build"
    order = np.asarray(g.order)
    assert np.array_equal(np.sort(order), np.arange(len(pts)))
    # every tile's slab stays inside the padded candidate array
    starts, nblk = np.asarray(g.starts), np.asarray(g.nblk)
    assert (starts % spec.block_k == 0).all()
    assert (starts + nblk * spec.block_k <= spec.n_cand).all()
    assert (nblk * spec.block_k <= spec.slab).all()


def test_csr_memory_is_linear_under_skew():
    # the motivating property: the hash table blows up on skew, CSR does not
    pts = synth.load("skewed2d", 2000, seed=7)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        hspec = grid_mod.plan_grid(pts, 0.05, dims=2)
    cspec = grid_mod.plan_csr_grid(pts, 0.05, dims=2)
    assert hspec.table_size * hspec.capacity > 20 * len(pts)
    assert cspec.n_cand <= 2 * len(pts) + cspec.slab


def test_plan_grid_warns_on_skew():
    pts = synth.load("skewed2d", 2000, seed=8)
    with pytest.warns(RuntimeWarning, match="skewed occupancy"):
        grid_mod.plan_grid(pts, 0.05, dims=2)


def test_engine_reuse_and_precomputed_counts():
    pts = synth.blobs(500, k=3, seed=9)
    eng = nb.make_engine(pts, 0.08, engine="grid")
    r1 = dbscan(pts, 0.08, 6, eng=eng)
    r2 = dbscan(pts, 0.08, 12, eng=eng, precomputed_counts=r1.counts)
    direct = dbscan(pts, 0.08, 12, engine="grid")
    np.testing.assert_array_equal(np.asarray(r2.labels),
                                  np.asarray(direct.labels))


def test_csr_side_grows_when_extent_saturates_bits():
    # huge extent / tiny eps: the Morton bit budget forces coarser cells,
    # which must stay ≥ eps and keep results exact
    pts = synth.load("highway", 400, seed=10)  # x extent ~1000
    spec = grid_mod.plan_csr_grid(pts, 1e-3, dims=2)
    assert spec.side >= 1e-3
    _assert_matches_brute(pts, 1e-3, 3)


def _dup_cell(pts, k):
    # a dense cell: k exact copies of the first point
    return np.concatenate([pts, np.repeat(pts[:1], k, axis=0)])


_UNIFORM = np.random.default_rng(11)
SELF_BOUNDS_CASES = {
    # name: (points, eps, dims, quantize at eps without the plan's growth)
    "uniform-3d": (_UNIFORM.uniform(0, 1, (1001, 3)).astype(np.float32),
                   0.05, 3, False),
    "uniform-2d": (np.concatenate([_UNIFORM.uniform(0, 1, (257, 2)),
                                   np.zeros((257, 1))], 1)
                   .astype(np.float32), 0.05, 2, False),
    "skewed-2d-dense-duplicates": (
        _dup_cell(synth.load("skewed2d", 1500, seed=12), 400), 0.05, 2,
        False),
    "blobs-3d-dense-duplicates": (
        _dup_cell(synth.blobs(700, k=3, seed=13), 300), 0.08, 3, False),
    "one-point": (np.full((1, 3), 0.5, np.float32), 0.1, 3, False),
    "one-cell": (_UNIFORM.normal(0, 0.005, (500, 3)).astype(np.float32),
                 0.05, 3, False),
    "saturated-2d": (synth.load("highway", 600, seed=14), 1e-3, 2, False),
    "saturated-3d": (_UNIFORM.uniform(0, 1000, (900, 3)).astype(np.float32),
                     0.01, 3, False),
    # cells past the bit budget pile on the top real index, whose windows
    # reach the free index above it
    "top-index-3d": (_UNIFORM.uniform(0, 1000, (900, 3)).astype(np.float32),
                     0.5, 3, True),
}


@pytest.mark.parametrize("case", sorted(SELF_BOUNDS_CASES))
def test_self_bounds_merge_equals_bisect(case, monkeypatch):
    pts, eps, dims, at_eps = SELF_BOUNDS_CASES[case]
    spec = grid_mod.plan_csr_grid(pts, eps, dims=dims)
    side = eps if at_eps else spec.side
    cells = grid_mod.csr_cells(jnp.asarray(pts), side, spec.origin, dims,
                               spec.bits)
    if at_eps:
        assert (np.asarray(cells)[:, :dims] == (1 << spec.bits) - 3).any()
    codes = kref.morton_encode_ref(cells, dims=dims)
    order = jnp.argsort(codes)
    args = (codes[order], cells[order], dims, spec.bits)
    merged = grid_mod._csr_self_bounds(*args)
    bisect = grid_mod._csr_window_bounds(*args)
    for m, b in zip(merged, bisect):
        assert m.dtype == b.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(m), np.asarray(b))

    # the whole build, against one whose layout runs the bisect (op by op,
    # so the layout's compiled merge is not reused)
    g = grid_mod.build_csr_grid(jnp.asarray(pts), spec)
    monkeypatch.setattr(grid_mod, "_csr_self_bounds",
                        grid_mod._csr_window_bounds)
    monkeypatch.setattr(grid_mod, "_csr_layout",
                        grid_mod._csr_layout.__wrapped__)
    ref_spec = grid_mod.plan_csr_grid(pts, eps, dims=dims)
    ref = grid_mod.build_csr_grid(jnp.asarray(pts), ref_spec)
    assert ref_spec == spec
    assert not bool(g.overflow) and not bool(ref.overflow)
    for field in ("order", "starts", "nblk", "codes"):
        np.testing.assert_array_equal(np.asarray(getattr(g, field)),
                                      np.asarray(getattr(ref, field)))


def test_build_compiles_the_layout_once():
    # the plan and the build run one jitted layout program: a build on a new
    # shape compiles it (and its merge sorts) once, then the slab program
    names = []

    def on_compile(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        nb._build_csr(jnp.asarray(synth.blobs(613, k=3, seed=613)), 0.08)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert names == ["jit(_csr_layout)", "jit(_csr_pack)"]

"""BVH engines: label parity, wavefront invariants, stack-overflow guard.

The acceptance bar (ISSUE 2): wavefront-BVH labels must match the brute
engine *identically* (both resolve components to min-original-core-index)
across skew, exact duplicates, n = 2 and all-noise data — the same suite the
CSR engine passes (tests/test_csr.py) — and the stack engine must refuse to
build (rather than silently drop neighbors) when the tree could outgrow its
traversal stack.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.baselines import fdbscan
from repro.core import bvh as bvh_mod
from repro.core import engines
from repro.core import neighbors as nb
from repro.core.dbscan import dbscan
from repro.data import synth

INT_MAX = np.iinfo(np.int32).max
ENGINES = ["bvh", "bvh-stack"]


def _assert_matches_brute(pts, eps, minpts, engine, **kw):
    b = dbscan(pts, eps, minpts, engine="brute")
    g = dbscan(pts, eps, minpts, engine=engine, **kw)
    np.testing.assert_array_equal(np.asarray(g.core), np.asarray(b.core))
    np.testing.assert_array_equal(np.asarray(g.counts), np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(g.labels), np.asarray(b.labels))
    return g


@pytest.mark.parametrize("engine", ENGINES)
def test_skewed_occupancy_matches_brute(engine):
    pts = synth.load("skewed2d", 1500, seed=4)
    _assert_matches_brute(pts, 0.05, 8, engine)


@pytest.mark.parametrize("engine", ENGINES)
def test_exact_duplicate_points(engine):
    # heavy duplication → duplicate Morton keys (index-augmented splits)
    rng = np.random.default_rng(1)
    base = rng.uniform(0, 1, (100, 3)).astype(np.float32)
    pts = np.concatenate([base, base, base[:40]])
    _assert_matches_brute(pts, 0.03, 3, engine)


@pytest.mark.parametrize("engine", ENGINES)
def test_n_two(engine):
    # the smallest tree: one internal node, two leaves
    pts = np.array([[0.0, 0.0, 0.0], [0.05, 0.0, 0.0]], np.float32)
    res = _assert_matches_brute(pts, 0.1, 2, engine)
    assert np.asarray(res.labels).tolist() == [0, 0]
    far = np.array([[0.0, 0.0, 0.0], [9.0, 0.0, 0.0]], np.float32)
    res = _assert_matches_brute(far, 0.1, 2, engine)
    assert np.asarray(res.labels).tolist() == [-1, -1]


@pytest.mark.parametrize("engine", ENGINES)
def test_all_noise(engine):
    pts = synth.load("highway", 300, seed=6)
    res = _assert_matches_brute(pts, 1e-4, 5, engine)
    assert (np.asarray(res.labels) == -1).all()


def test_wavefront_capabilities():
    # the wavefront engine advertises the sorted-layout fast path; the
    # stack engine does not — the registry drives dispatch off this, never
    # off the name
    pts = synth.blobs(300, k=3, seed=0)
    wave = nb.make_engine(pts, 0.08, engine="bvh")
    stack = nb.make_engine(pts, 0.08, engine="bvh-stack")
    assert wave.sweep_sorted is not None
    assert wave.sweep_counts is not None
    assert wave.sweep_frontier is not None
    assert np.array_equal(np.sort(np.asarray(wave.order)), np.arange(300))
    assert stack.sweep_sorted is None
    assert wave.meta.capacity % wave.meta.tile == 0
    # terminate=False keeps the exact engine but drops the frontier plan
    # (its compaction *is* the termination bound)
    exact = nb.make_engine(pts, 0.08, engine="bvh", terminate=False)
    assert exact.sweep_frontier is None


def test_wavefront_host_loop_matches_device_loop():
    pts = synth.blobs(400, k=4, seed=5)
    d = dbscan(pts, 0.08, 5, engine="bvh", hook_loop="device")
    h = dbscan(pts, 0.08, 5, engine="bvh", hook_loop="host")
    np.testing.assert_array_equal(np.asarray(d.labels), np.asarray(h.labels))


def test_wavefront_spec_reuse():
    pts = synth.blobs(500, k=3, seed=9)
    eng = nb.make_engine(pts, 0.08, engine="bvh")
    reused = nb.make_engine(pts, 0.08, engine="bvh", spec=eng.meta)
    r1 = dbscan(pts, 0.08, 6, eng=reused)
    direct = dbscan(pts, 0.08, 6, engine="bvh")
    np.testing.assert_array_equal(np.asarray(r1.labels),
                                  np.asarray(direct.labels))
    with pytest.raises(ValueError, match="planned for"):
        nb.make_engine(pts[:100], 0.08, engine="bvh", spec=eng.meta)


def test_wavefront_overflow_flag_fires_when_capacity_too_small():
    # bypass calibration: a frontier far below the block count must raise
    # the overflow flag rather than silently dropping work
    pts = jnp.asarray(synth.blobs(600, k=2, seed=3), jnp.float32)
    bvh = bvh_mod.build_bvh(pts, dims=2)
    croot = jnp.full((600,), INT_MAX, jnp.int32)
    _, _, ovf, _ = bvh_mod.wavefront_sweep(bvh, pts, croot, eps=0.1,
                                           eps2=0.01, capacity=8)
    assert bool(ovf)
    _, _, ovf, hist = bvh_mod.wavefront_sweep(bvh, pts, croot, eps=0.1,
                                              eps2=0.01, capacity=1 << 16)
    assert not bool(ovf)
    hist = np.asarray(hist)
    assert hist[0] == -(-600 // 8)        # level 0 = one entry per block
    assert hist.max() <= 1 << 16


def test_stack_overflow_raises_at_build():
    # regression for the silent-overflow bug: pushes past the stack used to
    # overwrite the top slot and drop neighbors. A 256-leaf tree needs at
    # least log2(256) + 2 = 10 slots; a 4-slot stack must refuse to build.
    pts = synth.blobs(256, k=3, seed=7)
    with pytest.raises(RuntimeError, match="stack overflow"):
        nb.make_engine(pts, 0.08, engine="bvh-stack", stack=4)


def test_stack_exact_depth_bound_suffices():
    # the advertised minimum (max_leaf_depth + 1 = meta["depth"] + 1) must
    # actually suffice — build with exactly that many slots and stay exact
    pts = synth.blobs(256, k=3, seed=7)
    eng = nb.make_engine(pts, 0.08, engine="bvh-stack")
    need = eng.meta["depth"] + 1
    tight = nb.make_engine(pts, 0.08, engine="bvh-stack", stack=need)
    b = dbscan(pts, 0.08, 6, engine="brute")
    t = dbscan(pts, 0.08, 6, eng=tight)
    np.testing.assert_array_equal(np.asarray(t.labels), np.asarray(b.labels))


def test_fdbscan_early_stop_counts_are_clipped_exactly():
    # §VI-B early traversal termination: counting stops at minPts, so the
    # early counts equal min(true, something ≥ minPts) — i.e. they agree
    # with the true counts below minPts and saturate at ≥ minPts above it.
    pts = synth.blobs(400, k=3, seed=2)
    eps, mp = 0.08, 6
    true = np.asarray(dbscan(pts, eps, mp, engine="brute").counts)
    eng = bvh_mod.make_bvh_stack_engine(jnp.asarray(pts, jnp.float32), eps,
                                        early_stop=mp)
    n = len(pts)
    early, _ = eng.sweep(eng.state, jnp.zeros((n,), bool),
                         jnp.arange(n, dtype=jnp.int32))
    early = np.asarray(early)
    below = true < mp
    np.testing.assert_array_equal(early[below], true[below])
    assert (early[~below] >= mp).all()
    assert (early <= true).all()


def test_fdbscan_early_exit_labels_match_reference():
    pts = synth.load("skewed2d", 600, seed=8)
    eps, mp = 0.05, 8
    ref = dbscan(pts, eps, mp, engine="brute")
    ee = fdbscan.run(pts, eps, mp, early_exit=True)
    np.testing.assert_array_equal(np.asarray(ee.core), np.asarray(ref.core))
    np.testing.assert_array_equal(np.asarray(ee.labels),
                                  np.asarray(ref.labels))


@pytest.mark.parametrize("engine", ENGINES)
def test_dims6_parity(engine):
    # d > 3: Morton order degrades to a locality heuristic over the first
    # three coordinates, but boxes / spheres / payload ranges are fully
    # 6-dimensional — labels must stay bit-identical to brute
    pts = synth.blobs(500, k=4, dims=6, seed=11)
    assert pts.shape == (500, 6)
    _assert_matches_brute(pts, 0.35, 6, engine)


def test_bf16_prune_matches_f32_prune():
    # the bf16 prune boxes are ε-dilated then outward-rounded, so the bf16
    # pass admits a superset of the f32-pruned candidates and the exact f32
    # sphere refine decides identically — labels must never differ
    for dims, eps in [(2, 0.05), (6, 0.35)]:
        pts = synth.blobs(700, k=4, dims=dims, seed=13)
        e16 = nb.make_engine(pts, eps, engine="bvh", prune_dtype="bf16")
        e32 = nb.make_engine(pts, eps, engine="bvh", prune_dtype="f32")
        r16 = dbscan(pts, eps, 6, eng=e16)
        r32 = dbscan(pts, eps, 6, eng=e32)
        np.testing.assert_array_equal(np.asarray(r16.counts),
                                      np.asarray(r32.counts))
        np.testing.assert_array_equal(np.asarray(r16.labels),
                                      np.asarray(r32.labels))
        _assert_matches_brute(pts, eps, 6, "bvh")


def test_capacity_calibrated_from_measured_peak():
    # regression for the 4x-growth overshoot (ISSUE 7): the committed
    # BENCH row carried frontier_cap=1048576 for n=4096. Capacity must now
    # track the measured per-level peak: within one tile of it, and — on
    # any dataset big enough that the peak spans at least a tile — within
    # the 4x bound the issue gates on.
    pts = synth.load("skewed2d", 2048, seed=0)
    eng = nb.make_engine(pts, 0.05, engine="bvh")
    spec = eng.meta
    assert spec.peak > 0
    assert spec.capacity >= spec.peak          # must still fit every sweep
    assert spec.capacity <= max(spec.peak + spec.tile - 1, spec.tile)
    assert spec.peak >= spec.tile              # dataset large enough that…
    assert spec.capacity <= 4 * spec.peak      # …the issue's 4x gate binds
    # the probe telemetry the calibration consumed is reproducible
    levels = bvh_mod.wavefront_levels(eng)
    assert levels.max() == spec.peak
    assert levels[0] == -(-2048 // spec.batch)


def test_termination_returns_exactly_clipped_minroot():
    # the early-termination contract: with a per-query bound, the returned
    # minroot is *exactly* min(exact minroot, bound) — never one neighbor
    # short — and non-terminated payload sweeps stay exact
    rng = np.random.default_rng(17)
    pts = jnp.asarray(synth.blobs(800, k=5, seed=17), jnp.float32)
    bvh = bvh_mod.build_bvh(pts, dims=2)
    n = 800
    croot = jnp.asarray(
        np.where(rng.uniform(size=n) < 0.6,
                 rng.integers(0, n, n), INT_MAX).astype(np.int32))
    kw = dict(eps=0.05, eps2=0.05 ** 2, capacity=1 << 14)
    _, m_exact, ovf, _ = bvh_mod.wavefront_sweep(
        bvh, bvh.pts_sorted, croot, **kw)
    assert not bool(ovf)
    bound = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    _, m_term, _, _ = bvh_mod.wavefront_sweep(
        bvh, bvh.pts_sorted, croot, bound=bound, **kw)
    np.testing.assert_array_equal(
        np.asarray(m_term), np.minimum(np.asarray(m_exact),
                                       np.asarray(bound)))


def test_frontier_driver_matches_device_driver():
    # hook_loop="frontier" must be bit-identical in labels AND round count,
    # with per-round live-block telemetry bounded by the block count
    pts = synth.load("skewed2d", 1500, seed=4)
    d = dbscan(pts, 0.05, 8, engine="bvh", hook_loop="device")
    f = dbscan(pts, 0.05, 8, engine="bvh", hook_loop="frontier")
    np.testing.assert_array_equal(np.asarray(d.labels), np.asarray(f.labels))
    assert int(d.n_rounds) == int(f.n_rounds)
    tiles = np.asarray(f.frontier_tiles)
    eng = nb.make_engine(pts, 0.05, engine="bvh")
    live = tiles[: int(f.n_rounds)]
    assert (live >= 0).all() and live.max() <= eng.sweep_frontier.n_tiles
    assert (tiles[int(f.n_rounds):] == -1).all()


def test_registry_rejects_unknown_engine():
    pts = synth.blobs(64, k=2, seed=0)
    with pytest.raises(ValueError, match="unknown engine"):
        nb.make_engine(pts, 0.1, engine="octree")
    with pytest.raises(ValueError, match="unknown local_engine"):
        engines.get_local_engine("octree")
    for name in ("brute", "grid", "grid-hash", "bvh", "bvh-stack"):
        assert name in engines.available_engines()
    for name in ("brute", "grid", "csr", "bvh"):
        assert name in engines.available_local_engines()

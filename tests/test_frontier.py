"""Frontier-compacted hooking (DESIGN.md §11): parity + safety invariants.

The acceptance bar (ISSUE 5): the frontier round driver's labels must be
bit-identical to the device and host drivers AND to the brute engine across
the standard parity suite (skew, exact duplicates, n = 2, all-noise), with
the same round count; tile parking must be provably safe — a parked tile's
full re-sweep could only have produced no-op hooks — which the hypothesis
property checks directly against full sweeps on random instances.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import grid as grid_mod
from repro.core import neighbors as nb
from repro.core.dbscan import dbscan, _hook_step, _counts_stage1_fn
from repro.core.union_find import pointer_jump
from repro.data import synth

INT_MAX = np.iinfo(np.int32).max


def _parity(pts, eps, minpts):
    b = dbscan(pts, eps, minpts, engine="brute")
    d = dbscan(pts, eps, minpts, engine="grid", hook_loop="device")
    h = dbscan(pts, eps, minpts, engine="grid", hook_loop="host")
    f = dbscan(pts, eps, minpts, engine="grid", hook_loop="frontier")
    for other in (b, d, h):
        np.testing.assert_array_equal(np.asarray(f.labels),
                                      np.asarray(other.labels))
        np.testing.assert_array_equal(np.asarray(f.core),
                                      np.asarray(other.core))
        np.testing.assert_array_equal(np.asarray(f.counts),
                                      np.asarray(other.counts))
    assert int(f.n_rounds) == int(d.n_rounds) == int(h.n_rounds)
    return f


def test_skewed_occupancy_parity():
    pts = synth.load("skewed2d", 1500, seed=4)
    _parity(pts, 0.05, 8)


def test_skewed_deep_clump_parity():
    # small ε turns the dense clump into a multi-cell component (many
    # hooking rounds) while the background is all noise — the regime the
    # frontier driver is for; parity must hold exactly there
    pts = synth.load("skewed2d", 4096, seed=10)
    f = _parity(pts, 1e-4, 8)
    hist = np.asarray(f.frontier_tiles)
    hist = hist[hist >= 0]
    assert len(hist) == int(f.n_rounds)
    eng = nb.make_engine(pts, 1e-4, engine="grid")
    # the frontier must actually compact: later rounds sweep fewer tiles
    # than the tile count (the all-noise background parks)
    assert hist[-1] < eng.meta.n_tiles


def test_exact_duplicate_points_parity():
    rng = np.random.default_rng(1)
    base = rng.uniform(0, 1, (100, 3)).astype(np.float32)
    pts = np.concatenate([base, base, base[:40]])
    _parity(pts, 0.03, 3)


def test_n_two_parity():
    near = np.array([[0.0, 0.0, 0.0], [0.05, 0.0, 0.0]], np.float32)
    f = _parity(near, 0.1, 2)
    assert np.asarray(f.labels).tolist() == [0, 0]
    far = np.array([[0.0, 0.0, 0.0], [9.0, 0.0, 0.0]], np.float32)
    f = _parity(far, 0.1, 2)
    assert np.asarray(f.labels).tolist() == [-1, -1]


def test_all_noise_parity():
    pts = synth.load("highway", 300, seed=6)
    f = _parity(pts, 1e-4, 5)
    assert (np.asarray(f.labels) == -1).all()
    hist = np.asarray(f.frontier_tiles)
    # no cores anywhere -> no live seam -> zero tiles swept in the single
    # (immediately converged) round
    assert hist[0] == 0


def test_frontier_capability_gating():
    # engines without sweep_frontier fall back to the sorted/device driver
    # rather than failing — capability-gated, never name-gated
    pts = synth.blobs(300, k=3, seed=0)
    eng = nb.make_engine(pts, 0.08, engine="grid")
    assert eng.sweep_frontier is not None
    assert eng.sweep_counts is not None
    # the wavefront BVH advertises sweep_frontier since DESIGN.md §13.2;
    # its terminate=False ablation is the engine without the capability
    bvh = nb.make_engine(pts, 0.08, engine="bvh", terminate=False)
    assert bvh.sweep_frontier is None
    f = dbscan(pts, 0.08, 5, eng=bvh, hook_loop="frontier")
    d = dbscan(pts, 0.08, 5, eng=bvh, hook_loop="device")
    np.testing.assert_array_equal(np.asarray(f.labels), np.asarray(d.labels))
    assert f.frontier_tiles is None
    with pytest.raises(ValueError, match="unknown hook_loop"):
        dbscan(pts, 0.08, 5, eng=eng, hook_loop="fronteer")


def test_counts_only_stage1_matches_full_sweep():
    # the counts-only sweep (no payload plane) must reproduce the fused
    # sweep's counts bit-for-bit — it feeds core identification directly
    pts = synth.load("skewed2d", 2000, seed=3)
    eng = nb.make_engine(pts, 0.05, engine="grid")
    counts = _counts_stage1_fn(eng.sweep_counts)(eng.state, eng.order)
    ref = dbscan(pts, 0.05, 8, engine="brute").counts
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref))


@pytest.mark.parametrize("max_blocks", [1, 2, 5, 8, 13])
def test_slab_reductions_match_point_loop(max_blocks):
    # the frontier's per-tile slab min and dirty test must equal a plain
    # walk over each slab's points, including empty slabs (nblk = 0),
    # slabs ending at the last block, and a ragged last block (n < n_cand)
    bk, nb_tot, T = 4, 16, 64
    n = nb_tot * bk - 3
    rng = np.random.default_rng(max_blocks)
    payload = rng.integers(0, 1000, nb_tot * bk).astype(np.int32)
    payload[rng.random(payload.size) < 0.3] = INT_MAX
    flags = rng.random(n) < 0.02
    nblk = rng.integers(0, max_blocks + 1, T).astype(np.int32)
    starts = (rng.integers(0, nb_tot - max_blocks + 1, T) * bk).astype(
        np.int32)
    starts[:2] = (nb_tot - max_blocks) * bk
    nblk[:2] = max_blocks, 0
    slabs = [slice(s, s + m * bk) for s, m in zip(starts, nblk)]
    got = grid_mod.slab_payload_min(jnp.asarray(payload), jnp.asarray(starts),
                                    jnp.asarray(nblk), block_k=bk,
                                    max_blocks=max_blocks)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.array([payload[s].min(initial=INT_MAX) for s in slabs], np.int32))
    got = grid_mod.slab_touched(jnp.asarray(flags), jnp.asarray(starts),
                                jnp.asarray(nblk), n, block_k=bk)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.array([flags[s].any() for s in slabs]))


def test_no_host_sync_in_dbscan():
    # regression for the hidden host sync: the device drivers must return
    # n_rounds as a device scalar (converting with int() inside dbscan()
    # would block async dispatch on every call); the host loop — whose
    # whole point is a host-visible round boundary — returns a plain int
    pts = synth.blobs(300, k=3, seed=1)
    for hook_loop in ("device", "frontier"):
        res = dbscan(pts, 0.08, 5, engine="grid", hook_loop=hook_loop)
        assert isinstance(res.n_rounds, jax.Array), hook_loop
        assert res.n_rounds.dtype == jnp.int32
    res_b = dbscan(pts, 0.08, 5, engine="brute", hook_loop="device")
    assert isinstance(res_b.n_rounds, jax.Array)
    res_h = dbscan(pts, 0.08, 5, engine="grid", hook_loop="host")
    assert isinstance(res_h.n_rounds, int)
    # lazy conversion still works and agrees across drivers
    assert int(res.n_rounds) == res_h.n_rounds


# --- tile-parking safety (the hypothesis property) -------------------------
# hypothesis is an optional dev dependency; without it the same properties
# run over a handful of fixed seeds so the container's tier-1 pass still
# exercises them (module-level importorskip would skip the parity suite too)

try:
    from hypothesis import given, settings, strategies as st
    _HYP = True
except ImportError:  # pragma: no cover - exercised in the slim container
    _HYP = False


def _hyp_or_fixed(cases, seeds_only=False):
    if _HYP:
        if seeds_only:
            return lambda fn: settings(max_examples=8, deadline=None)(
                given(st.integers(0, 10_000))(fn))
        return lambda fn: settings(max_examples=8, deadline=None)(
            given(st.integers(0, 10_000),
                  st.sampled_from([0.03, 0.05, 0.08]),
                  st.integers(3, 8))(fn))
    if seeds_only:
        return pytest.mark.parametrize("seed", [c[0] for c in cases])
    return pytest.mark.parametrize("seed,eps,minpts", cases)


@_hyp_or_fixed([(0, 0.05, 5), (1, 0.08, 3), (2, 0.03, 8), (7, 0.08, 6)])
def test_parked_tiles_only_lose_noop_hooks(seed, eps, minpts):
    """A parked tile's full re-sweep can only produce no-op hooks.

    Replays the frontier driver's rounds next to full sweeps: in every
    round, every core query in a *non-live* tile must satisfy
    ``min(m_full, root) == root`` — i.e. the hook the full driver performs
    there is ``parent[root] min= root``, a no-op. This is the invariant
    that makes parking bit-identical; any marking scheme that misses a
    tile whose min-root would produce a real union violates it.
    """
    pts = synth.blobs(220, k=3, seed=seed)
    eng = nb.make_engine(pts, eps, engine="grid")
    spec = eng.meta
    n = spec.n
    counts = dbscan(pts, eps, minpts, eng=eng).counts
    core_s = jnp.asarray(counts >= minpts)[eng.order]
    frontier = eng.sweep_frontier

    parent = jnp.arange(n, dtype=jnp.int32)
    prev_croot = jnp.full((n,), -1, jnp.int32)
    pending = jnp.ones((frontier.n_tiles,), bool)
    for _ in range(64):
        root = pointer_jump(parent)
        croot = jnp.where(core_s, root, INT_MAX)
        qroot = jnp.where(core_s, root, -1)
        m_f, pending, _ = frontier.sweep(eng.state, croot, qroot,
                                         croot != prev_croot, pending)
        _, m_full = eng.sweep_sorted(eng.state, croot)
        # wherever the frontier parked (INT_MAX) the full sweep's hook
        # must be a no-op for core queries
        parked = np.asarray(m_f) == INT_MAX
        tgt_full = np.minimum(np.asarray(m_full), np.asarray(root))
        bad = parked & np.asarray(core_s) & (tgt_full < np.asarray(root))
        assert not bad.any(), (
            f"parked tile would have produced a real union at sorted "
            f"positions {np.nonzero(bad)[0][:10]}")
        prev_croot = croot
        parent, changed = _hook_step(root, m_f, core_s)
        if not bool(changed):
            break


@_hyp_or_fixed([(0,), (3,), (11,), (42,)], seeds_only=True)
def test_slab_touched_never_misses(seed):
    """``slab_touched`` must flag every tile whose slab holds a flagged
    point (the dirty-block half of the liveness test)."""
    rng = np.random.default_rng(seed)
    pts = synth.blobs(200, k=2, seed=seed)
    eng = nb.make_engine(pts, 0.08, engine="grid")
    spec = eng.meta
    n = spec.n
    flags = rng.uniform(size=n) < rng.uniform(0, 0.2)
    got = np.asarray(grid_mod.slab_touched(
        jnp.asarray(flags), eng.state.starts, eng.state.nblk, n,
        block_k=spec.block_k))
    starts = np.asarray(eng.state.starts)
    nblk = np.asarray(eng.state.nblk)
    for t in range(spec.n_tiles):
        lo, hi = starts[t], min(starts[t] + nblk[t] * spec.block_k, n)
        assert got[t] == bool(flags[lo:hi].any())

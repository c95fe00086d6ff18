"""System-level DBSCAN validation against the sequential Algorithm 1."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.baselines.brute import reference_dbscan
from repro.core import labels as L
from repro.core import neighbors as nb
from repro.core.dbscan import dbscan
from repro.data import synth

CASES = [
    ("blobs2", synth.blobs(350, k=3, seed=0), 0.08, 6),
    ("blobs3d", synth.blobs(300, k=4, dims=3, seed=1), 0.12, 5),
    ("roadnet", synth.load("roadnet2d", 400, seed=2), 0.03, 4),
    ("taxi", synth.load("taxi2d", 400, seed=3), 0.12, 8),
    ("iono", synth.load("iono3d", 350, seed=4), 3.0, 10),
    ("dense-empty", synth.load("highway", 300, seed=5), 0.001, 5),
]


@pytest.mark.parametrize("hook_loop", ["device", "host", "frontier"])
@pytest.mark.parametrize("engine", ["brute", "grid", "grid-hash", "bvh",
                                    "bvh-stack"])
@pytest.mark.parametrize("name,pts,eps,minpts", CASES,
                         ids=[c[0] for c in CASES])
def test_dbscan_equivalent_to_reference(engine, name, pts, eps, minpts,
                                        hook_loop):
    # every stage-2 driver, including frontier's fall-back on engines
    # without ``sweep_frontier``, must agree with Algorithm 1
    ref_labels, ref_core = reference_dbscan(pts, eps, minpts)
    res = dbscan(pts, eps, minpts, engine=engine, hook_loop=hook_loop)
    assert np.array_equal(np.asarray(res.core), ref_core)
    assert L.equivalent(np.asarray(res.labels), ref_labels, ref_core,
                        points=pts, eps=eps)


def test_all_noise_case():
    pts = synth.load("highway", 200, seed=6)
    res = dbscan(pts, 1e-4, 5, engine="grid")
    assert (np.asarray(res.labels) == -1).all()
    assert len(L.cluster_sizes(res.labels)) == 0


def test_single_cluster_case():
    pts = np.random.default_rng(0).normal(0, 0.01, (100, 3)).astype(np.float32)
    res = dbscan(pts, 0.5, 3, engine="grid")
    assert len(L.cluster_sizes(res.labels)) == 1
    assert (np.asarray(res.labels) == np.asarray(res.labels)[0]).all()


def test_precomputed_counts_reuse():
    # the paper's §VI-B re-run use case: saved counts skip stage 1
    pts = synth.blobs(300, k=3, seed=7)
    r1 = dbscan(pts, 0.08, 6, engine="grid")
    r2 = dbscan(pts, 0.08, 12, engine="grid", precomputed_counts=r1.counts)
    direct = dbscan(pts, 0.08, 12, engine="grid")
    assert np.array_equal(np.asarray(r2.labels), np.asarray(direct.labels))


def test_engine_reuse_across_minpts():
    pts = synth.blobs(300, k=3, seed=8)
    eng = nb.make_engine(pts, 0.08, engine="grid")
    for mp in (4, 8, 16):
        a = dbscan(pts, 0.08, mp, eng=eng)
        b = dbscan(pts, 0.08, mp, engine="grid")
        assert np.array_equal(np.asarray(a.labels), np.asarray(b.labels))


def test_compact_labels():
    raw = np.array([5, 5, -1, 9, 9, 9, 2])
    c = L.compact_labels(raw)
    assert c.tolist() == [1, 1, -1, 2, 2, 2, 0]
    assert L.cluster_sizes(raw).tolist() == [1, 2, 3]

"""Multi-device tests. Each case runs in a subprocess with
``xla_force_host_platform_device_count`` (the main pytest process must keep
exactly 1 device)."""
import os
import subprocess
import sys
import textwrap

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 600) -> str:
    script = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    import sys
    sys.path.insert(0, {ROOT + "/src"!r})
    import numpy as np
    import jax, jax.numpy as jnp
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_distributed_dbscan_matches_single_device():
    out = run_sub("""
    from repro.launch.mesh import make_mesh
    from repro.distributed.dbscan_dist import dbscan_distributed
    from repro.core.dbscan import dbscan
    from repro.data import synth

    mesh = make_mesh((8,), ("data",))
    pts = synth.blobs(4096, k=5, seed=11)
    eps, minpts = 0.07, 6
    d = dbscan_distributed(pts, eps, minpts, mesh)
    s = dbscan(pts, eps, minpts, engine="grid")

    def canon(x):
        x = np.asarray(x); out = np.full(len(x), -1); m = {}
        for i, v in enumerate(x):
            if v != -1: out[i] = m.setdefault(v, len(m))
        return out

    core_s = np.asarray(s.core)
    assert (np.asarray(d.core) == core_s).all(), "core mismatch"
    la, lb = canon(d.labels), canon(s.labels)
    assert ((la == -1) == (lb == -1)).all(), "noise mismatch"
    assert (la[core_s] == lb[core_s]).all(), "core partition mismatch"
    print("OK rounds=", d.n_rounds)
    """)
    assert "OK" in out


def test_distributed_dbscan_csr_engine():
    out = run_sub("""
    from repro.launch.mesh import make_mesh
    from repro.distributed.dbscan_dist import dbscan_distributed, DistConfig
    from repro.core.dbscan import dbscan
    from repro.data import synth

    mesh = make_mesh((4,), ("data",))
    pts = synth.blobs(2048, k=5, seed=11)
    eps, minpts = 0.07, 6
    d = dbscan_distributed(pts, eps, minpts, mesh,
                           cfg=DistConfig(local_engine="csr"))
    s = dbscan(pts, eps, minpts, engine="grid")

    def canon(x):
        x = np.asarray(x); out = np.full(len(x), -1); m = {}
        for i, v in enumerate(x):
            if v != -1: out[i] = m.setdefault(v, len(m))
        return out

    core_s = np.asarray(s.core)
    assert (np.asarray(d.core) == core_s).all(), "core mismatch"
    la, lb = canon(d.labels), canon(s.labels)
    assert ((la == -1) == (lb == -1)).all(), "noise mismatch"
    assert (la[core_s] == lb[core_s]).all(), "core partition mismatch"
    print("OK rounds=", d.n_rounds)
    """, devices=4)
    assert "OK" in out


def test_distributed_dbscan_bvh_engine():
    out = run_sub("""
    from repro.launch.mesh import make_mesh
    from repro.distributed.dbscan_dist import dbscan_distributed, DistConfig
    from repro.core.dbscan import dbscan
    from repro.data import synth

    mesh = make_mesh((4,), ("data",))
    pts = synth.blobs(2048, k=5, seed=11)
    eps, minpts = 0.07, 6
    d = dbscan_distributed(pts, eps, minpts, mesh,
                           cfg=DistConfig(local_engine="bvh"))
    s = dbscan(pts, eps, minpts, engine="grid")

    def canon(x):
        x = np.asarray(x); out = np.full(len(x), -1); m = {}
        for i, v in enumerate(x):
            if v != -1: out[i] = m.setdefault(v, len(m))
        return out

    core_s = np.asarray(s.core)
    assert (np.asarray(d.core) == core_s).all(), "core mismatch"
    la, lb = canon(d.labels), canon(s.labels)
    assert ((la == -1) == (lb == -1)).all(), "noise mismatch"
    assert (la[core_s] == lb[core_s]).all(), "core partition mismatch"
    print("OK rounds=", d.n_rounds)
    """, devices=4)
    assert "OK" in out


def test_distributed_dbscan_dense_empty():
    out = run_sub("""
    from repro.launch.mesh import make_mesh
    from repro.distributed.dbscan_dist import dbscan_distributed
    from repro.data import synth
    mesh = make_mesh((4,), ("data",))
    pts = synth.load("highway", 2048, seed=1)
    d = dbscan_distributed(pts, 1e-4, 5, mesh)
    assert (np.asarray(d.labels) == -1).all()
    print("OK")
    """, devices=4)
    assert "OK" in out


def test_sharded_tier_on_four_devices():
    # one shard per device: the placed tier must answer assign and fold
    # ingest exactly as the single-snapshot session does
    out = run_sub("""
    from repro import serve
    from repro.core.dbscan import dbscan
    from repro.data import synth

    eps, minpts = 0.08, 8
    pts = synth.load("taxi2d", 3000, seed=3)
    head, tail = pts[:2400], pts[2400:]
    tier = serve.ShardedTier.build(head, eps, minpts, n_shards=4,
                                   max_delta_frac=np.inf)
    sess = serve.ServeSession(serve.build_snapshot(head, eps, minpts),
                              max_delta_frac=np.inf)

    def placement():
        return [tuple(d.id for d in s.snapshot.cands.devices())
                for s in tier.sessions]

    def same_answers(seed):
        q = synth.load("taxi2d", 500, seed=seed, structure_seed=3)
        a, b = sess.assign(q), tier.assign(q)
        assert not b.partial
        for f in ("labels", "counts", "dist"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))

    assert sorted(placement()) == [(0,), (1,), (2,), (3,)], placement()
    same_answers(11)
    for i in range(0, len(tail), 150):
        tier.ingest(tail[i:i + 150])
        sess.ingest(tail[i:i + 150])
    tier.compact(force=True)
    sess.compact(force=True)
    assert sorted(placement()) == [(0,), (1,), (2,), (3,)], placement()
    ref = dbscan(pts, eps, minpts)
    np.testing.assert_array_equal(np.asarray(sess.snapshot.labels),
                                  np.asarray(ref.labels))
    lab = np.full(len(pts), -2, np.int64)
    for p in tier.parts:
        loc = np.asarray(p.snapshot.labels)
        lab[p.orig_index] = np.where(
            loc >= 0, p.label_table.astype(np.int64)[np.maximum(loc, 0)]
            if p.label_table.size else -1, -1)
    np.testing.assert_array_equal(lab, np.asarray(ref.labels))
    same_answers(12)
    tier.close()
    print("OK")
    """, devices=4)
    assert "OK" in out


def test_checkpoint_atomic_roundtrip(tmp_path):
    from repro.distributed import checkpoint as ckpt
    import jax.numpy as jnp
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 4))}}
    ckpt.save(str(tmp_path), 5, tree, meta={"note": "x"}, keep=2)
    ckpt.save(str(tmp_path), 10, tree, keep=2)
    ckpt.save(str(tmp_path), 15, tree, keep=2)
    # keep-K gc
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2
    assert ckpt.latest_step(str(tmp_path)) == 15
    restored, meta = ckpt.restore(str(tmp_path), tree)
    assert meta["step"] == 15
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.arange(10.0))

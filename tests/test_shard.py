"""Sharded serving tier (DESIGN.md §15): split/route/merge parity.

Acceptance bar (ISSUE 9): sharded ``assign`` and ingest-then-compact are
bit-identical to the single-snapshot path across the full parity suite;
queries on/within ε of a Morton range boundary route to both shards and
merge exactly; an all-points-in-one-shard degenerate split still serves;
per-shard checkpoint namespaces isolate keep-K GC and watermark pins;
delta-overflow sheds name the owning shard.
"""
import os
from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.core import grid
from repro.kernels import ref as kref
from repro.serve import shard as shard_mod
from repro.core.dbscan import dbscan
from repro.data import synth
from repro.distributed import checkpoint as ckpt

EPS, MINPTS = 0.05, 8


def _parity_cases():
    """Same suite as test_serve plus the line corpus used for boundary
    routing (skewed2d / duplicates / n=2 / all-noise — the ISSUE 9 gate)."""
    rng = np.random.default_rng(0)
    base = rng.uniform(0, 1, (80, 3)).astype(np.float32)
    dup = np.concatenate([base, base, base[:30]])
    spread = (rng.uniform(0, 100, (60, 3)) * np.array([1, 1, 0])) \
        .astype(np.float32)
    return {
        "skewed2d": synth.load("skewed2d", 1200, seed=4),
        "duplicates": dup,
        "n2": np.asarray([[0., 0., 0.], [0.01, 0., 0.]], np.float32),
        "all_noise": spread,
    }


def _domain_queries(pts, m, seed=5):
    rng = np.random.default_rng(seed)
    lo, hi = pts.min(0), pts.max(0)
    q = rng.uniform(lo - 2 * EPS, hi + 2 * EPS, (m, 3)).astype(np.float32)
    if np.all(pts[:, 2] == pts[0, 2]):
        q[:, 2] = pts[0, 2]
    return q


def _tier_global_labels(tier):
    """Reassemble the tier's canonical-order global labels/core from its
    shard-local parts — what the §15.3 remap tables are for."""
    n = sum(p.n for p in tier.parts)
    lab = np.full(n, -2, np.int64)
    core = np.zeros(n, bool)
    for p in tier.parts:
        loc = np.asarray(p.snapshot.labels)
        g = np.full(len(loc), -1, np.int64)
        if p.label_table.size:
            m = loc >= 0
            g[m] = p.label_table.astype(np.int64)[loc[m]]
        lab[p.orig_index] = g
        core[p.orig_index] = np.asarray(p.snapshot.core)
    assert (lab != -2).all(), "shard rows must partition the corpus"
    return lab, core


@pytest.mark.parametrize("name", list(_parity_cases()))
@pytest.mark.parametrize("k", [2, 3])
def test_sharded_assign_bit_identical(name, k):
    pts = _parity_cases()[name]
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    tier = serve.ShardedTier.from_snapshot(snap, n_shards=k)
    q = _domain_queries(pts, 137)
    r1 = serve.assign(snap, q)
    r2 = tier.assign(q)
    np.testing.assert_array_equal(r1.labels, r2.labels)
    np.testing.assert_array_equal(r1.counts, r2.counts)
    np.testing.assert_array_equal(r1.dist, r2.dist)  # bit-identical, no tol


@pytest.mark.parametrize("name", list(_parity_cases()))
def test_sharded_ingest_then_compact_bit_identical(name):
    pts = _parity_cases()[name]
    n = len(pts)
    half = max(n // 2, 1)
    tier = serve.ShardedTier.build(pts[:half], EPS, MINPTS, n_shards=3,
                                   max_delta_frac=np.inf)
    sess = serve.ServeSession(serve.build_snapshot(pts[:half], EPS, MINPTS),
                              max_delta_frac=np.inf)
    for i in range(half, n, 64):
        chunk = pts[i:i + 64]
        res = tier.ingest(chunk)
        assert res.labels.shape == (len(chunk),)
        sess.ingest(chunk)
    tier.compact(force=True)
    sess.compact(force=True)
    ref = dbscan(pts, EPS, MINPTS, engine="grid")
    lab, core = _tier_global_labels(tier)
    np.testing.assert_array_equal(lab, np.asarray(ref.labels))
    np.testing.assert_array_equal(core, np.asarray(ref.core))
    np.testing.assert_array_equal(lab, np.asarray(sess.snapshot.labels))
    q = _domain_queries(pts, 99, seed=7)
    r1 = sess.assign(q)
    r2 = tier.assign(q)
    np.testing.assert_array_equal(r1.labels, r2.labels)
    np.testing.assert_array_equal(r1.dist, r2.dist)


def _line_corpus(n=400):
    """A dense line along x (spacing ε/4 → every interior point is core):
    2D Morton code of (cx, 0) is monotone in cx, so sorted order is x
    order and the shard cut is a *spatial* boundary we can aim queries
    at."""
    x = np.arange(n, dtype=np.float32) * (EPS / 4)
    pts = np.zeros((n, 3), np.float32)
    pts[:, 0] = x
    return pts


def test_boundary_queries_route_to_both_shards_and_merge_exactly():
    pts = _line_corpus()
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    tier = serve.ShardedTier.from_snapshot(snap, n_shards=2)
    assert tier.n_shards == 2
    smap = tier.map
    cut_pos = int(smap.pos_cuts[1])
    # first corpus point of shard 1 in sorted (== x) order
    order = np.asarray(snap.order)
    b = np.asarray(snap.points)[order[cut_pos]]
    # on the boundary, and within ε each side of it
    q = np.stack([b,
                  b - [EPS * 0.5, 0, 0],
                  b + [EPS * 0.5, 0, 0],
                  b - [EPS * 0.99, 0, 0],
                  b + [EPS * 0.99, 0, 0]]).astype(np.float32)
    mask = smap.window_shards(q)
    assert mask.shape == (len(q), 2)
    # ε-dilation must make every boundary-straddling query see both sides
    assert mask[0].all(), "a query ON the cut must route to both shards"
    assert (mask.sum(axis=1) >= 1).all()
    assert mask[:, 0].any() and mask[:, 1].any()
    r1 = serve.assign(snap, q)
    r2 = tier.assign(q)
    np.testing.assert_array_equal(r1.labels, r2.labels)
    np.testing.assert_array_equal(r1.counts, r2.counts)
    np.testing.assert_array_equal(r1.dist, r2.dist)
    # the line is one cluster: the merged label must survive the split
    assert (r2.labels == r1.labels[0]).all() and r1.labels[0] >= 0


def test_degenerate_all_points_one_shard():
    # one Morton code total: every cut snaps to the same run boundary and
    # collapses — the tier degrades to a single shard but still serves
    pts = np.tile(np.asarray([[0.3, 0.4, 0.0]], np.float32), (50, 1))
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    tier = serve.ShardedTier.from_snapshot(snap, n_shards=4)
    assert tier.n_shards == 1
    assert (tier.map.owner_of(pts) == 0).all()
    q = np.asarray([[0.3, 0.4, 0.0], [5.0, 5.0, 0.0]], np.float32)
    r1 = serve.assign(snap, q)
    r2 = tier.assign(q)
    np.testing.assert_array_equal(r1.labels, r2.labels)
    np.testing.assert_array_equal(r1.counts, r2.counts)
    assert r2.labels[0] == 0 and r2.labels[1] == -1


def test_split_partitions_canonical_corpus():
    pts = synth.load("skewed2d", 800, seed=2)
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    smap, parts = serve.split_snapshot(snap, 3)
    rows = np.concatenate([p.orig_index for p in parts])
    assert sorted(rows.tolist()) == list(range(len(pts)))
    for p in parts:
        np.testing.assert_array_equal(np.asarray(p.snapshot.points),
                                      pts[p.orig_index])
        # label table is ascending (the monotone-remap invariant)
        assert (np.diff(p.label_table) > 0).all() \
            if p.label_table.size > 1 else True
    # ownership matches the split: each shard's points route home
    for p in parts:
        assert (smap.owner_of(pts[p.orig_index]) == p.shard_id).all()



# --- the route table against the window bisect it replaces ------------------

_ROUTE = {}


def _route_corpus(dims):
    """Blobs, and a tight island far out along x, in the grid's top real
    cell (``2^bits - 3``, the cell clipped queries land in); between them
    lies empty space. Returns (points, blob points, snapshot)."""
    if dims not in _ROUTE:
        blobs = synth.blobs(700, k=4, dims=dims, seed=3)
        top = (1 << (15 if dims == 2 else 10)) - 3
        org = blobs.min(0)
        island = org + np.random.default_rng(dims).uniform(0, 0.02, (40, 3))
        island[:, 0] += (top + 0.2) * EPS
        pts = np.concatenate([blobs, island]).astype(np.float32)
        if dims == 2:
            pts[:, 2] = 0
        _ROUTE[dims] = (pts, blobs, serve.build_snapshot(pts, EPS, MINPTS))
    return _ROUTE[dims]


def _device_codes(cells, dims):
    return np.asarray(kref.morton_encode_ref(
        jnp.asarray(cells.reshape(-1, 3), jnp.int32),
        dims=dims)).astype(np.int64).reshape(cells.shape[:-1])


def _device_cells(smap, q):
    return np.asarray(grid.csr_cells(jnp.asarray(q, jnp.float32), smap.side,
                                     smap.origin, smap.dims, smap.bits))


def _bisect_window_shards(smap, codes, q):
    """The route as the split used to answer it: each of the query's 9/27
    window cells (device cells, neighbours clipped to the engine's cap)
    bisected against the global sorted codes; an occupied run's start
    names its shard."""
    offs = np.asarray([(dx, dy, dz) for dx in (-1, 0, 1)
                       for dy in (-1, 0, 1)
                       for dz in ((-1, 0, 1) if smap.dims == 3 else (0,))])
    nbc = np.clip(_device_cells(smap, q)[None] + offs[:, None], 0,
                  (1 << smap.bits) - 2)
    if smap.dims == 2:
        nbc[..., 2] = 0
    wc = _device_codes(nbc, smap.dims)
    left = np.searchsorted(codes, wc, side="left")
    occ = np.searchsorted(codes, wc, side="right") > left
    sid = np.searchsorted(smap.pos_cuts, left, side="right") - 1
    mask = np.zeros((len(q), smap.n_shards), bool)
    oi, oj = np.nonzero(occ)
    mask[oj, sid[oi, oj]] = True
    return mask


def _route_queries(pts, blobs, snap, smap):
    """Random queries over and past the blobs and the island, far into
    empty space and clipped at both ends of the grid, points on and
    within ε of every cut, and points at exact multiples of the tier side
    from the origin with their float32 neighbours on either side."""
    rng = np.random.default_rng(11)
    island = pts[len(blobs):]
    qs = [rng.uniform(lo - 3 * EPS, hi + 3 * EPS, (300, 3))
          for lo, hi in ((blobs.min(0), blobs.max(0)),
                         (island.min(0), island.max(0)))]
    qs += [rng.uniform(-1e4, 1e4, (20, 3)),
           np.asarray([[1e30, 1e30, 1e30], [-1e30, -1e30, -1e30],
                       [1e30, island[0, 1], island[0, 2]]])]
    order = np.asarray(snap.order)
    steps = [0, 0.5, 0.99, 1.0, 1.01]
    for cut in smap.pos_cuts[1:-1]:
        b = np.asarray(snap.points)[order[cut]].astype(np.float64)
        for axis in range(smap.dims):
            for d in steps + [-v for v in steps[1:]]:
                qs.append((b + np.eye(3)[axis] * d * EPS)[None])
    org = np.asarray(smap.origin, np.float64)
    k = np.floor((pts[::7] - org) / smap.side)
    qs.append(org + (k + rng.integers(-1, 3, k.shape)) * smap.side)
    q = np.concatenate(qs).astype(np.float32)
    q = np.concatenate([q, np.nextafter(q, np.float32(np.inf)),
                        np.nextafter(q, np.float32(-np.inf))])
    if smap.dims == 2:
        q[:, 2] = 0
    return q


@pytest.mark.parametrize("k", [1, 2, 3, 4])
@pytest.mark.parametrize("dims", [2, 3])
def test_window_shards_equal_window_bisect(dims, k):
    pts, blobs, snap = _route_corpus(dims)
    smap, _ = serve.split_snapshot(snap, k)
    assert smap.dims == dims and smap.n_shards == k
    assert smap.side == EPS  # the island sits in the top real cell
    q = _route_queries(pts, blobs, snap, smap)
    got = smap.window_shards(q)
    want = _bisect_window_shards(smap, np.asarray(snap.codes, np.int64), q)
    np.testing.assert_array_equal(got, want)
    # the draw reaches every case: unrouted queries, and queries routed
    # to more than one shard when there is more than one
    assert (~want.any(axis=1)).any()
    assert (want.sum(axis=1) > 1).any() == (k > 1)


@pytest.mark.parametrize("k", [1, 2, 3, 4])
@pytest.mark.parametrize("dims", [2, 3])
def test_owner_of_equals_cut_bisect(dims, k):
    pts, blobs, snap = _route_corpus(dims)
    smap, _ = serve.split_snapshot(snap, k)
    q = _route_queries(pts, blobs, snap, smap)
    want = np.searchsorted(smap.cut_codes[1:-1],
                           _device_codes(_device_cells(smap, q), smap.dims),
                           side="right")
    np.testing.assert_array_equal(smap.owner_of(q), want)
    assert set(want.tolist()) == set(range(k))


@pytest.mark.parametrize("plan", ["snapshot", "awkward"])
@pytest.mark.parametrize("dims", [2, 3])
def test_tier_cells_equal_csr_cells(dims, plan):
    """The host cell rule is the device's bit for bit, on the default
    backend: the route's exactness rests on it."""
    _, _, snap = _route_corpus(dims)
    smap, _ = serve.split_snapshot(snap, 2)
    side, origin = smap.side, smap.origin
    if plan == "awkward":
        side, origin = 0.0137, (-0.3331, 0.2719, -1.1e-3)
    rng = np.random.default_rng(dims)
    org = np.asarray(origin, np.float64)
    k = rng.integers(-3, 1 << 11, (2000, 3)).astype(np.float64)
    edge = (org + k * side).astype(np.float32)
    q = np.concatenate([
        rng.uniform(-2.0, 3.0, (2000, 3)).astype(np.float32), edge,
        np.nextafter(edge, np.float32(np.inf)),
        np.nextafter(edge, np.float32(-np.inf)),
        np.asarray([[1e30, -1e30, 3e38], [-3e38, 0, 1e9]], np.float32)])
    bits = smap.bits
    want = np.asarray(grid.csr_cells(jnp.asarray(q), side, origin, dims,
                                     bits))
    np.testing.assert_array_equal(
        shard_mod.tier_cells(q, side, origin, dims, bits), want)


@pytest.mark.parametrize("dims", [2, 3])
def test_morton_cells_invert_the_encoder(dims):
    bits = 15 if dims == 2 else 10
    rng = np.random.default_rng(dims)
    cells = rng.integers(0, (1 << bits) - 1, (5000, 3))
    cells[:4] = [[0, 0, 0], [(1 << bits) - 2] * 3, [(1 << bits) - 3, 0, 1],
                 [1, (1 << bits) - 2, 0]]
    if dims == 2:
        cells[:, 2] = 0
    codes = _device_codes(cells, dims)
    # the route encodes host cells with the same reference, on numpy
    np.testing.assert_array_equal(kref.morton_encode_ref(cells, dims=dims),
                                  codes)
    np.testing.assert_array_equal(shard_mod.morton_cells(codes, dims), cells)

def test_resplit_rebuilds_the_route_table():
    """Every split brings its own table, the compaction's re-split too,
    and the tier reports its size."""
    pts, _, snap = _route_corpus(2)
    tier = serve.ShardedTier.from_snapshot(snap, n_shards=3)
    try:
        first = tier.map
        assert tier.health_report()["route_table_cells"] \
            == first.reach_keys.size > 0
        extra = pts[:64] + np.float32(3 * EPS)
        tier.ingest(extra)
        tier.compact(force=True)
        assert tier.map is not first
        fresh, _ = serve.split_snapshot(
            serve.build_snapshot(np.concatenate([pts, extra]), EPS, MINPTS),
            3)
        np.testing.assert_array_equal(tier.map.reach_keys, fresh.reach_keys)
        np.testing.assert_array_equal(tier.map.reach, fresh.reach)
        assert tier.health_report()["route_table_cells"] \
            == fresh.reach_keys.size
    finally:
        tier.close()

def test_route_notes_the_same_fanout_histogram():
    sched = serve.BucketScheduler()
    per_q = np.asarray([0, 1, 1, 4, 2, 1, 0, 4])
    sched.note_route(per_q)
    sched.note_route(per_q[:0])
    assert sched.routed == Counter(int(v) for v in per_q)

def test_overflow_shed_names_owning_shard():
    pts = synth.load("skewed2d", 600, seed=4)
    tier = serve.ShardedTier.build(pts, EPS, MINPTS, n_shards=2,
                                   delta_capacity=64,
                                   max_delta_frac=np.inf)
    rng = np.random.default_rng(3)
    chunk = pts[rng.integers(0, len(pts), 60)] + rng.normal(
        0, EPS / 10, (60, 3)).astype(np.float32)
    chunk[:, 2] = 0
    with serve.faults.inject("serve.compact", times=-1,
                             error=RuntimeError("injected rebuild fail")):
        tier.ingest(chunk)          # fills buffers
        with pytest.raises(serve.AdmissionError) as ei:
            for _ in range(8):      # overflow + broken compaction -> shed
                tier.ingest(chunk + rng.normal(0, EPS / 10, chunk.shape)
                            .astype(np.float32) * [1, 1, 0])
        assert "shard-" in str(ei.value)
        assert ei.value.details.get("session_id")
        assert ei.value.retry_after is not None


def test_single_session_shed_includes_session_id():
    pts = synth.load("skewed2d", 300, seed=4)
    sess = serve.ServeSession(serve.build_snapshot(pts, EPS, MINPTS),
                              session_id="shard-007", delta_capacity=32,
                              max_delta_frac=np.inf)
    chunk = pts[:30]
    with serve.faults.inject("serve.compact", times=-1,
                             error=RuntimeError("injected rebuild fail")):
        sess.ingest(chunk)
        with pytest.raises(serve.AdmissionError) as ei:
            sess.ingest(chunk)
    assert "shard-007" in str(ei.value)
    assert ei.value.details.get("session_id") == "shard-007"


def test_delegated_session_refuses_local_compact():
    pts = synth.load("skewed2d", 300, seed=4)
    tier = serve.ShardedTier.build(pts, EPS, MINPTS, n_shards=2)
    with pytest.raises(serve.ServeError, match="tier"):
        tier.sessions[0].compact()


def test_checkpoint_namespace_isolates_gc_and_pins(tmp_path):
    """Satellite 2 regression: shard A churning through keep-K steps can
    never GC shard B's pinned baseline — namespaces do not share a step
    listing."""
    root = str(tmp_path)
    tree = {"x": np.arange(4)}
    ckpt.save(root, 0, tree, keep=2, namespace="shard-001")  # B's baseline
    for s in range(12):  # A churns far past keep
        ckpt.save(root, s, tree, keep=2, namespace="shard-000")
    assert ckpt.available_steps(root, namespace="shard-000") == [10, 11]
    assert ckpt.available_steps(root, namespace="shard-001") == [0]
    # pins are namespace-local too: pinning B's step number in A's
    # sequence must not resurrect or retain anything in B
    ckpt.save(root, 12, tree, keep=1, pin=(0,), namespace="shard-000")
    assert 0 not in ckpt.available_steps(root, namespace="shard-000")[1:]
    assert ckpt.available_steps(root, namespace="shard-001") == [0]
    restored, _ = ckpt.restore(root, tree, namespace="shard-001")
    np.testing.assert_array_equal(restored["x"], tree["x"])
    # namespaces must be clean path components
    with pytest.raises(ValueError):
        ckpt.save(root, 0, tree, namespace="a/b")
    with pytest.raises(ValueError):
        ckpt.save(root, 0, tree, namespace="step_0000000001")


def test_tier_durable_publish_per_shard_namespaces(tmp_path):
    pts = synth.load("skewed2d", 500, seed=4)
    ckpt_root = str(tmp_path / "snap")
    wal_root = str(tmp_path / "wal")
    tier = serve.ShardedTier.build(
        pts, EPS, MINPTS, n_shards=2, max_delta_frac=np.inf,
        ckpt_root=ckpt_root, wal_root=wal_root, durability="none")
    try:
        assert tier.n_shards == 2
        for j in range(tier.n_shards):
            ns = f"shard-{j:03d}"
            # step-0 baseline published per shard at bring-up
            assert ckpt.available_steps(ckpt_root, namespace=ns) == [0]
            assert os.path.isdir(os.path.join(wal_root, ns))
        tier.ingest(pts[:100] + np.float32(EPS / 7))
        tier.compact(force=True)
        for j in range(tier.n_shards):
            ns = f"shard-{j:03d}"
            steps = ckpt.available_steps(ckpt_root, namespace=ns)
            assert steps[-1] >= 1  # compaction republished every shard
            offs = serve.published_wal_offsets(ckpt_root, namespace=ns)
            assert offs, "per-shard WAL watermark must be embedded"
            snap = serve.load_snapshot(ckpt_root, namespace=ns)
            assert snap.n == tier.parts[j].n
    finally:
        tier.close()


def test_replicas_round_robin_with_zero_new_traces():
    pts = synth.load("skewed2d", 600, seed=4)
    tier = serve.ShardedTier.build(pts, EPS, MINPTS, n_shards=2)
    tier.warmup(512)
    assert tier.replicate(0, copies=1) == 1
    tier.scheduler.reset_stats()
    q = _domain_queries(pts, 100, seed=11)
    for _ in range(4):
        tier.assign(q)
    # replicas share the shard's plan: same trace keys, zero recompiles
    assert tier.scheduler.recompiles == 0
    served = [k for k in tier.replica_served if k[0] == 0]
    assert len(set(served)) == 2, "round-robin must touch both copies"
    # routing telemetry: fan-out histogram is bounded by the shard count
    assert set(tier.scheduler.routed) <= {0, 1, 2}
    assert sum(tier.scheduler.routed.values()) == 4 * len(q)


def test_tier_degrades_instead_of_stalling():
    pts = synth.load("skewed2d", 500, seed=4)
    tier = serve.ShardedTier.build(pts, EPS, MINPTS, n_shards=2,
                                   max_delta_frac=0.05)
    q = _domain_queries(pts, 50, seed=13)
    with serve.faults.inject("serve.compact", times=-1,
                             error=RuntimeError("injected rebuild fail")):
        r = tier.ingest(pts[:64] + np.float32(EPS / 9))  # compaction due
        assert r.degraded and not r.compacted
        ra = tier.assign(q)
        assert ra.degraded and ra.staleness >= 0
        with pytest.raises(serve.CompactionError):
            tier.compact()
    tier.compact(force=True)
    ra = tier.assign(q)
    assert not ra.degraded and tier.n_delta == 0


# --- §16: shard failure domains ---------------------------------------------
# health-checked scatter legs, replica failover, hedging, partial gathers,
# quarantine + re-materialization (ISSUE 10). hypothesis is optional: the
# partial-merge property enumerates all shard subsets either way.

try:
    from hypothesis import given, settings, strategies as st
    _HYP = True
except ImportError:  # pragma: no cover - exercised in the slim container
    _HYP = False


def _aimed_queries(tier, shard_id, extra=0, seed=17):
    """Queries guaranteed to route to ``shard_id`` (its own corpus points)
    plus ``extra`` domain-wide ones."""
    own = np.asarray(tier.parts[shard_id].snapshot.points)[:8]
    if extra:
        pts = np.asarray(tier.parts[0].snapshot.points)
        return np.concatenate([own, _domain_queries(pts, extra, seed=seed)])
    return own


def test_assign_failover_to_replica_bit_identical():
    pts = synth.load("skewed2d", 600, seed=4)
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    tier = serve.ShardedTier.from_snapshot(snap, n_shards=2, hedge=False,
                                           auto_recover=False)
    try:
        tier.replicate(0, copies=1)
        tier.warmup(256)
        q = np.concatenate([_domain_queries(pts, 80, seed=29),
                            np.asarray(tier.parts[0].snapshot.points)[:8]])
        full = serve.assign(snap, q)
        tier.scheduler.reset_stats()
        with serve.faults.inject(
                "serve.shard.assign", times=-1, tag="shard-000/r0",
                error=serve.CapacityError("injected: r0 wedged")):
            for _ in range(8):
                r = tier.assign(q)
                # the surviving replica's answer is the same bits — failover
                # changes availability, never the merge
                assert not r.partial
                np.testing.assert_array_equal(r.labels, full.labels)
                np.testing.assert_array_equal(r.counts, full.counts)
                np.testing.assert_array_equal(r.dist, full.dist)
            assert tier.scheduler.failovers >= 1
            # three strikes on r0's turns -> quarantined; r1 carries the slot
            assert tier.health.state((0, 0)) == serve.DOWN
        assert tier.scheduler.recompiles == 0
        assert tier.replica_served.get((0, 1), 0) >= 4
        rep = tier.health_report()
        assert rep["targets"]["shard-000/r0"]["state"] == serve.DOWN
        assert rep["scheduler"]["failovers"] == tier.scheduler.failovers
    finally:
        tier.close()


def test_round_robin_skips_quarantined_replica():
    """Satellite 2: a down replica never stalls its slot's turn — the next
    live copy inherits it, and traffic keeps spreading over survivors."""
    pts = synth.load("skewed2d", 500, seed=4)
    tier = serve.ShardedTier.build(pts, EPS, MINPTS, n_shards=2,
                                   auto_recover=False)
    try:
        tier.replicate(0, copies=2)          # 3 serving copies of shard 0
        tier.warmup(256)
        tier.health.force_down((0, 1))
        tier.scheduler.reset_stats()
        tier.replica_served.clear()
        q = _aimed_queries(tier, 0)
        for _ in range(6):
            assert not tier.assign(q).partial
        served = {k: v for k, v in tier.replica_served.items()
                  if k[0] == 0}
        assert served.get((0, 1), 0) == 0    # quarantined copy never serves
        assert served.get((0, 0), 0) >= 1 and served.get((0, 2), 0) >= 1
        assert sum(served.values()) == 6     # no stalled turns
        rep = tier.health_report()
        assert rep["targets"]["shard-000/r1"]["state"] == serve.DOWN
        assert rep["targets"]["shard-000/r0"]["state"] == serve.HEALTHY
        assert rep["targets"]["shard-000/r1"]["served"] == 0
    finally:
        tier.close()


def test_hedged_suspect_leg_first_result_wins():
    pts = synth.load("skewed2d", 500, seed=4)
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    tier = serve.ShardedTier.from_snapshot(snap, n_shards=2,
                                           auto_recover=False)
    try:
        tier.replicate(0, copies=1)
        tier.warmup(256)
        q = _aimed_queries(tier, 0)
        full = serve.assign(snap, q)
        tier.scheduler.reset_stats()
        # one strike makes the turn-holder suspect; its leg is hedged to
        # the healthy copy and the first result wins
        tier.health.record_failure((0, 0))
        assert tier.health.state((0, 0)) == serve.SUSPECT
        r = tier.assign(q)
        assert tier.scheduler.hedges == 1
        assert r.shards[0].hedged and not r.shards[0].missing
        assert r.shards[0].replica in (0, 1)
        # replicas share the shard's buffers: the hedge buys latency,
        # never a different answer
        np.testing.assert_array_equal(r.labels, full.labels)
        np.testing.assert_array_equal(r.counts, full.counts)
        np.testing.assert_array_equal(r.dist, full.dist)
    finally:
        tier.close()


def test_retry_after_survives_tier_reraise():
    """Satellite 1: the router's wrapping must preserve the session's
    ``retry_after`` hint (and the error's type) — clients price their
    retry on it."""
    pts = synth.load("skewed2d", 400, seed=4)
    sleeps = []
    tier = serve.ShardedTier.build(pts, EPS, MINPTS, n_shards=2,
                                   auto_recover=False, sleep=sleeps.append)
    try:
        chunk = np.asarray(tier.parts[0].snapshot.points)[:8]
        with serve.faults.inject(
                "serve.shard.ingest", times=-1, tag="shard-000",
                error=serve.AdmissionError("downstream shed",
                                           retry_after=7.5)):
            with pytest.raises(serve.AdmissionError) as ei:
                tier.ingest(chunk)
        assert ei.value.retry_after == 7.5           # hint survives wrapping
        assert "shard-000" in str(ei.value)
        assert ei.value.details.get("session_id") == "shard-000"
        # the leg's jittered backoff floored every delay at the hint
        assert len(sleeps) == tier.leg_retries
        assert all(s >= 7.5 for s in sleeps)
        assert tier.health.state((0, 0)) == serve.DOWN   # strikes landed
        # assign side: allow_partial off re-raises type + hint intact
        tier.health = serve.HealthRegistry()
        tier.allow_partial = False
        q = _aimed_queries(tier, 1)
        with serve.faults.inject(
                "serve.shard.assign", times=-1, tag="shard-001",
                error=serve.CapacityError("slab wedged", retry_after=2.25)):
            with pytest.raises(serve.CapacityError) as ei2:
                tier.assign(q)
        assert ei2.value.retry_after == 2.25
        assert ei2.value.details.get("session_id") == "shard-001"
    finally:
        tier.close()


# --- partial gathers: the §16.3 restriction property ------------------------

_PARTIAL = {}


def _partial_setup():
    if not _PARTIAL:
        pts = synth.load("skewed2d", 600, seed=4)
        snap = serve.build_snapshot(pts, EPS, MINPTS)
        tier = serve.ShardedTier.from_snapshot(snap, n_shards=3,
                                               auto_recover=False)
        assert tier.n_shards == 3
        q = np.concatenate(
            [_domain_queries(pts, 60, seed=23)]
            + [np.asarray(p.snapshot.points)[:5] for p in tier.parts])
        _PARTIAL.update(tier=tier, snap=snap, q=q,
                        full=serve.assign(snap, q))
    return _PARTIAL["tier"], _PARTIAL["snap"], _PARTIAL["q"], \
        _PARTIAL["full"]


def _restricted_merge(tier, q, alive):
    """Reference §16.3 restriction: the full merge minus the missing
    shards' contributions, computed from per-shard single-snapshot
    assigns + the same monotone remap/merge the router runs."""
    mask = tier.map.window_shards(q)
    nq = len(q)
    counts = np.zeros(nq, np.int32)
    merged = np.full(nq, np.iinfo(np.int64).max, np.int64)
    dist = np.full(nq, np.inf, np.float32)
    for j in alive:
        idx = np.nonzero(mask[:, j])[0]
        if idx.size == 0:
            continue
        r = serve.assign(tier.parts[j].snapshot, q[idx])
        table = tier.parts[j].label_table.astype(np.int64)
        if table.size:
            glab = np.where(r.labels >= 0,
                            table[np.clip(r.labels, 0, None)],
                            np.iinfo(np.int64).max)
        else:
            glab = np.full(idx.size, np.iinfo(np.int64).max, np.int64)
        merged[idx] = np.minimum(merged[idx], glab)
        counts[idx] += r.counts
        dist[idx] = np.minimum(dist[idx], r.dist)
    labels = np.where(merged != np.iinfo(np.int64).max,
                      merged, -1).astype(np.int32)
    return labels, counts, dist


def _check_partial_subset(bits):
    tier, snap, q, full = _partial_setup()
    K = tier.n_shards
    alive = [j for j in range(K) if bits >> j & 1]
    tier.health = serve.HealthRegistry()    # fresh: forget previous downs
    for j in range(K):
        if j not in alive:
            tier.health.force_down((j, 0))
    r = tier.assign(q)
    ref_lab, ref_cnt, ref_dist = _restricted_merge(tier, q, alive)
    # the partial answer IS the restriction — exactly, not approximately
    np.testing.assert_array_equal(r.labels, ref_lab)
    np.testing.assert_array_equal(r.counts, ref_cnt)
    np.testing.assert_array_equal(r.dist, ref_dist)
    # degradation direction: a missing shard only LOSES neighbors
    assert (r.counts <= full.counts).all()
    mism = r.labels != full.labels
    assert ((r.labels[mism] == -1)
            | (r.labels[mism].astype(np.int64)
               > full.labels[mism])).all(), "partial merge invented a label"
    routed = tier.map.window_shards(q)
    missing_routed = any(routed[:, j].any()
                         for j in range(K) if j not in alive)
    assert r.partial == missing_routed
    if missing_routed:
        assert any(s.missing for s in r.shards.values())


if _HYP:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 7))
    def test_partial_merge_is_restriction(bits):
        _check_partial_subset(bits)
else:
    @pytest.mark.parametrize("bits", list(range(8)))
    def test_partial_merge_is_restriction(bits):
        _check_partial_subset(bits)


# --- kill matrix + chaos gate -----------------------------------------------

@pytest.mark.parametrize("site", ["assign", "probe", "rematerialize",
                                  "ingest"])
def test_shard_kill_matrix(site, tmp_path):
    """Kill shard 1 at each §16 site; recovery must converge back to
    bit-identical parity with the unsharded path."""
    pts = synth.load("skewed2d", 400, seed=4)
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    tier = serve.ShardedTier.from_snapshot(
        snap, n_shards=3, ckpt_root=str(tmp_path / "snap"),
        wal_root=str(tmp_path / "wal"), durability="none",
        auto_recover=False, max_delta_frac=np.inf,
        health=serve.HealthRegistry(probe_deadline_s=30.0))
    try:
        tier.warmup(256)
        j = 1
        sid = serve.target_tag(j, None)
        q = np.concatenate([_domain_queries(pts, 60, seed=31),
                            np.asarray(tier.parts[j].snapshot.points)[:8]])
        full = serve.assign(snap, q)
        chunk = np.asarray(tier.parts[j].snapshot.points)[:16]
        if site == "assign":
            with serve.faults.inject("serve.shard.assign", times=1,
                                     tag=sid, error=serve.faults.Kill("x")):
                r = tier.assign(q)
            assert r.partial and r.shards[j].missing
            assert tier.health.state((j, 0)) == serve.DOWN
        elif site == "probe":
            with serve.faults.inject("serve.shard.probe", times=1,
                                     tag=sid, error=serve.faults.Kill("x")):
                assert tier.probe(j) is False
            assert tier.health.state((j, 0)) == serve.DOWN
        elif site == "rematerialize":
            tier.health.force_down((j, 0))
            with serve.faults.inject("serve.shard.rematerialize", times=1,
                                     tag=sid, error=serve.faults.Kill("x")):
                assert tier.recover_shard(j) is False
            assert j in tier.quarantined     # still down: next attempt's job
        elif site == "ingest":
            with serve.faults.inject("serve.shard.ingest", times=1,
                                     tag=sid, error=serve.faults.Kill("x")):
                with pytest.raises(serve.AdmissionError) as ei:
                    tier.ingest(chunk, request_id="kill-chunk")
            assert ei.value.retry_after is not None
            assert j in tier.quarantined
            # a quarantined owner sheds follow-up writes pre-scatter
            with pytest.raises(serve.AdmissionError):
                tier.ingest(chunk, request_id="kill-chunk")
        assert tier.recover_shard(j) is True      # re-materialize + certify
        assert tier.quarantined == []
        assert tier.health.state((j, 0)) == serve.HEALTHY
        if site == "ingest":
            # the unacked chunk retries idempotently after recovery
            res = tier.ingest(chunk, request_id="kill-chunk")
            assert not res.deduped
            tier.compact(force=True)
            ref = dbscan(np.concatenate([pts, chunk]), EPS, MINPTS,
                         engine="grid")
            lab, _ = _tier_global_labels(tier)
            np.testing.assert_array_equal(lab, np.asarray(ref.labels))
        else:
            r2 = tier.assign(q)
            assert not r2.partial
            np.testing.assert_array_equal(r2.labels, full.labels)
            np.testing.assert_array_equal(r2.counts, full.counts)
            np.testing.assert_array_equal(r2.dist, full.dist)
            ref = dbscan(pts, EPS, MINPTS, engine="grid")
            lab, _ = _tier_global_labels(tier)
            np.testing.assert_array_equal(lab, np.asarray(ref.labels))
    finally:
        tier.close()


@pytest.mark.parametrize("k", [2, 3])
def test_chaos_gate_kill_replicas_one_by_one(k, tmp_path):
    """ISSUE 10 acceptance gate: kill shard 0's serving copies one by
    one — the tier keeps answering (failover, then flagged partials,
    zero post-warmup recompiles), the quarantined shard re-materializes
    from its checkpoint namespace, and post-recovery answers are
    bit-identical to the single-snapshot path and batch ``dbscan()``."""
    pts = synth.load("skewed2d", 500, seed=4)
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    tier = serve.ShardedTier.from_snapshot(
        snap, n_shards=k, ckpt_root=str(tmp_path / "snap"),
        wal_root=str(tmp_path / "wal"), durability="none",
        auto_recover=False,
        health=serve.HealthRegistry(probe_deadline_s=30.0))
    try:
        tier.replicate(0, copies=1)
        tier.warmup(256)
        q = np.concatenate([_domain_queries(pts, 80, seed=19),
                            np.asarray(tier.parts[0].snapshot.points)[:8]])
        full = serve.assign(snap, q)
        tier.scheduler.reset_stats()
        # kill the primary: its replica inherits the slot, same bits
        serve.faults.inject("serve.shard.assign", times=-1,
                            tag="shard-000/r0", error=serve.faults.Kill("a"))
        r = tier.assign(q)
        assert not r.partial
        np.testing.assert_array_equal(r.labels, full.labels)
        assert tier.health.state((0, 0)) == serve.DOWN
        # kill the replica too: the gather goes partial, flagged per-shard
        serve.faults.inject("serve.shard.assign", times=-1,
                            tag="shard-000/r1", error=serve.faults.Kill("b"))
        r = tier.assign(q)
        assert r.partial and r.degraded
        assert r.shards[0].missing and r.shards[0].state == serve.DOWN
        assert tier.quarantined == [0]
        assert (r.counts <= full.counts).all()
        mism = r.labels != full.labels
        assert ((r.labels[mism] == -1)
                | (r.labels[mism].astype(np.int64)
                   > full.labels[mism])).all()
        # the storm recompiled nothing: every surviving leg stayed on the
        # warmed bucket ladder
        assert tier.scheduler.recompiles == 0
        assert tier.scheduler.partials >= 1
        serve.faults.clear()
        # re-materialize from the shard's own checkpoint namespace
        assert tier.recover_shard(0) is True
        assert tier.quarantined == []
        r2 = tier.assign(q)
        assert not r2.partial
        np.testing.assert_array_equal(r2.labels, full.labels)
        np.testing.assert_array_equal(r2.counts, full.counts)
        np.testing.assert_array_equal(r2.dist, full.dist)
        ref = dbscan(pts, EPS, MINPTS, engine="grid")
        lab, core = _tier_global_labels(tier)
        np.testing.assert_array_equal(lab, np.asarray(ref.labels))
        np.testing.assert_array_equal(core, np.asarray(ref.core))
        assert tier.scheduler.recompiles == 0
    finally:
        serve.faults.clear()
        tier.close()

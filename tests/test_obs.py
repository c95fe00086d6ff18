"""The program's own trace spans (``repro/obs.py``), read back from a real
``jax.profiler`` trace on the CPU with ``ProfileData``: which spans one
assign, a forced regrow, a CSR build and a collection record, with what
args, nested how; and that tracing changes no answer."""
import gc
import glob
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs, serve
from repro.core import grid
from repro.core.dbscan import dbscan
from repro.data import synth
from repro.serve import faults, snapshot

EPS, MINPTS = 0.05, 8


class Span(NamedTuple):
    name: str          # without the ``repro.`` prefix
    start: float
    end: float
    args: dict
    thread: tuple      # (plane, line index): one host thread

    def holds(self, other: "Span") -> bool:
        return (self.thread == other.thread and self.start <= other.start
                and other.end <= self.end)


def _traced(tmp_path, fn):
    """``fn()`` under a profiler trace, and the ``repro.`` spans it left."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans += [Span(ev.name[len(obs.PREFIX):], ev.start_ns,
                           ev.start_ns + ev.duration_ns, dict(ev.stats),
                           (plane.name, i))
                      for ev in line.events
                      if ev.name.startswith(obs.PREFIX)]
    return out, sorted(spans, key=lambda s: s.start)


def _named(spans, name):
    return [s for s in spans if s.name == name]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def session():
    pts = synth.blobs(900, k=4, seed=1)
    sess = serve.ServeSession(serve.build_snapshot(pts, EPS, MINPTS))
    sess.assign(pts[:17])  # compile outside the traces
    return sess, pts


def test_assign_spans_nest_on_one_thread_and_share_req(session, tmp_path):
    sess, pts = session
    q = pts[100:117]
    _, spans = _traced(tmp_path, lambda: (sess.assign(q), sess.assign(q)))
    outer = _named(spans, "serve.assign")
    assert len(outer) == 2
    assert outer[0].args["req"] != outer[1].args["req"]
    for top in outer:
        req = top.args["req"]
        assert top.args["nq"] == 17
        mine = [s for s in spans if s.args.get("req") == req]
        assert sorted(s.name for s in mine) == [
            "serve.assign", "serve.finish", "serve.prepare", "serve.run"]
        assert all(top.holds(s) for s in mine)
        prep, run, fin = (_named(mine, n)[0] for n in
                          ("serve.prepare", "serve.run", "serve.finish"))
        assert prep.end <= run.start and run.end <= fin.start
        assert prep.args["nq"] == 17 and prep.args["bucket"] == 256
        assert run.args["slab"] == sess.snapshot.slab
        assert run.args["attempt"] == 0
    assert not _named(spans, "serve.regrow")


def test_forced_overflow_records_regrow_between_two_runs(tmp_path,
                                                         monkeypatch):
    # a skewed corpus at small ε, so the planned slab has room to double;
    # grown slabs stick per plan for the process, so start from none
    monkeypatch.setattr(snapshot, "_SLAB_CACHE", {})
    pts = synth.load("skewed2d", 2000, seed=17)
    sess = serve.ServeSession(serve.build_snapshot(pts, 0.005, MINPTS))
    slab0 = sess.snapshot.slab
    assert slab0 < sess.snapshot.spec.n_cand
    faults.inject("serve.assign.overflow", times=1)
    _, spans = _traced(tmp_path, lambda: sess.assign(pts[:16]))
    runs, regrow = _named(spans, "serve.run"), _named(spans, "serve.regrow")
    assert [r.args["attempt"] for r in runs] == [0, 1]
    assert len(regrow) == 1
    slab1 = min(2 * slab0, sess.snapshot.spec.n_cand)
    assert [r.args["slab"] for r in runs] == [slab0, slab1]
    assert regrow[0].args["slab"] == slab1
    assert runs[0].end <= regrow[0].start and regrow[0].end <= runs[1].start
    req = _named(spans, "serve.assign")[0].args["req"]
    assert {s.args["req"] for s in runs + regrow} == {req}


def test_dbscan_records_engine_build_holding_plan_and_layout(tmp_path):
    pts = synth.blobs(600, k=3, seed=2)
    r, spans = _traced(tmp_path, lambda: dbscan(pts, EPS, MINPTS))
    build, = _named(spans, "engine.build")
    plan, = _named(spans, "engine.plan")
    layout, = _named(spans, "engine.layout")
    assert build.args == {"engine": "grid", "n": 600}
    assert build.holds(plan) and build.holds(layout)
    assert plan.end <= layout.start
    assert plan.args == {"n": 600, "dims": 2}
    assert layout.args["slab"] > 0
    assert (np.asarray(r.labels) >= 0).any()


def test_generation_two_collection_is_a_span(tmp_path):
    _, spans = _traced(tmp_path, lambda: gc.collect())
    assert [s.args for s in _named(spans, "gc")] == [{"generation": 2}]


def test_tracing_changes_no_answer(session, tmp_path):
    sess, pts = session
    q = synth.blobs(300, k=4, seed=8)
    plain = sess.assign(q)
    clusters = dbscan(pts, EPS, MINPTS)
    (traced, traced_clusters), _ = _traced(
        tmp_path, lambda: (sess.assign(q), dbscan(pts, EPS, MINPTS)))
    np.testing.assert_array_equal(plain.labels, traced.labels)
    np.testing.assert_array_equal(plain.counts, traced.counts)
    np.testing.assert_array_equal(plain.dist, traced.dist)
    np.testing.assert_array_equal(np.asarray(clusters.labels),
                                  np.asarray(traced_clusters.labels))
    np.testing.assert_array_equal(np.asarray(clusters.core),
                                  np.asarray(traced_clusters.core))


@pytest.fixture(scope="module")
def tier():
    pts = synth.blobs(900, k=4, seed=1)
    t = serve.ShardedTier.from_snapshot(serve.build_snapshot(pts, EPS, MINPTS),
                                        n_shards=2)
    assert t.n_shards == 2
    t.assign(pts[::3])  # compile every shard's program outside the traces
    yield t, pts
    t.close()


def _window_hits(t, pts, q):
    """Queries with a corpus point in their ±1-cell window at the tier's
    quantization, counted from the device's cells by brute force."""
    smap = t.map

    def cells(x):
        return np.asarray(grid.csr_cells(jnp.asarray(x), smap.side,
                                         smap.origin, smap.dims, smap.bits))
    near = np.abs(cells(q)[:, None, :] - cells(pts)[None, :, :]).max(-1) <= 1
    return int(near.any(axis=1).sum())


def test_tier_assign_spans_hold_route_legs_and_merges(tier, tmp_path):
    t, pts = tier
    q = np.concatenate([pts[::3], pts[:7] + np.float32(50)])
    routed = t.map.window_shards(q)
    want = [j for j in range(t.n_shards) if routed[:, j].any()]
    assert len(want) == 2  # the request spans both shards
    _, spans = _traced(tmp_path, lambda: t.assign(q))
    top, = _named(spans, "tier.assign")
    req = top.args["req"]
    assert top.args["nq"] == len(q) and top.args["shards"] == 2
    mine = [s for s in spans if s.args.get("req") == req]
    # every span of the request shares req (a collection may add "gc")
    assert len(mine) == len([s for s in spans if s.name != "gc"])
    assert all(top.holds(s) for s in mine)
    route, = _named(mine, "tier.route")
    # the far queries route nowhere: hits counts the rest
    assert route.args["hits"] == _window_hits(t, pts, q) == len(q) - 7
    legs, merges = _named(mine, "tier.leg"), _named(mine, "tier.merge")
    assert [s.args["shard"] for s in legs] == want
    assert [s.args["shard"] for s in merges] == want
    assert all(s.args["replica"] == 0 for s in legs)
    assert [s.args["nq"] for s in legs] == [int(routed[:, j].sum())
                                            for j in want]
    # route, then each shard's leg and its merge, one after another
    order = [route] + [s for pair in zip(legs, merges) for s in pair]
    assert all(a.end <= b.start for a, b in zip(order, order[1:]))
    for leg in legs:
        inner = [s for s in mine if s.name.startswith("serve.")
                 and leg.holds(s)]
        assert sorted(s.name for s in inner) == [
            "serve.finish", "serve.prepare", "serve.run"]
        prep, = _named(inner, "serve.prepare")
        assert prep.args["nq"] == leg.args["nq"]


def test_tier_assign_ids_differ_between_requests(tier, tmp_path):
    t, pts = tier
    _, spans = _traced(tmp_path, lambda: (t.assign(pts[:40]),
                                          t.assign(pts[-40:])))
    reqs = [s.args["req"] for s in _named(spans, "tier.assign")]
    assert len(reqs) == 2 and reqs[0] != reqs[1]
    for s in spans:
        assert s.name == "gc" or s.args.get("req") in reqs, s


def test_tracing_changes_no_tier_answer(tier, tmp_path):
    t, _ = tier
    q = synth.blobs(300, k=4, seed=8)
    plain = t.assign(q)
    traced, _ = _traced(tmp_path, lambda: t.assign(q))
    for f in ("labels", "counts", "dist"):
        np.testing.assert_array_equal(getattr(plain, f), getattr(traced, f))
    assert not traced.partial

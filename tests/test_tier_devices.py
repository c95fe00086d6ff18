"""The sharded tier with one shard per device, on four virtual CPU devices.

The suite's own JAX sees one device, so the tier runs in a fresh process
started with ``xla_force_host_platform_device_count=4``. There
``ShardedTier.from_snapshot(n_shards=4)`` must put each shard on a device
of its own, answer bit for bit as single-snapshot ``assign`` and the
``backend="ref"`` oracle do (labels, counts, dist), both for a city-wide
taxi request that routes to all four shards and for queries at the
shards' Morton-range boundaries, and upload each leg's queries to the
device of the shard that serves it.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    sys.path.insert(0, {src!r})
    import numpy as np
    from repro import serve
    from repro.core import neighbors as nb
    from repro.data import synth

    EPS, MINPTS = 0.08, 8
    pts = synth.load("taxi2d", 4000, seed=3)
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    tier = serve.ShardedTier.from_snapshot(snap, n_shards=4)

    def ids(x):
        return sorted(d.id for d in x.devices())

    legs = []  # (snapshot's devices, queries' devices) of each program call
    real = nb._csr_cross_query_fn

    def spy(*a, **kw):
        fn = real(*a, **kw)

        def call(codes, cands, croot, q, nq):
            legs.append((ids(codes), ids(q)))
            return fn(codes, cands, croot, q, nq)
        return call

    # the queries at each cut: the last point of the shard before it and
    # the first of the shard after it, and both nudged by ε/2 across it
    order = np.asarray(snap.order)
    cut_rows = np.concatenate([order[[p - 1, p]]
                               for p in tier.map.pos_cuts[1:-1]])
    edge = pts[cut_rows]
    nudge = np.float32(EPS / 2) * np.array([1, 1, 0], np.float32)
    boundary = np.concatenate([edge, edge + nudge, edge - nudge])
    city = synth.load("taxi2d", 1024, seed=11, structure_seed=3)

    out = {{"placement": [ids(s.snapshot.codes) for s in tier.sessions],
            "devices": [ids(a) for s in tier.sessions
                        for a in (s.snapshot.points, s.snapshot.cands,
                                  s.snapshot.croot_sorted)],
            "cases": {{}}}}
    for name, q in (("city", city), ("boundary", boundary)):
        routed = tier.map.window_shards(q)
        nb._csr_cross_query_fn = spy
        legs.clear()
        got = tier.assign(q)
        nb._csr_cross_query_fn = real
        case = {{"routed": routed.sum(axis=1).tolist(),
                 "partial": bool(got.partial), "legs": list(legs)}}
        for tag, r in (("tier", got), ("single", serve.assign(snap, q)),
                       ("ref", serve.assign(snap, q, backend="ref"))):
            case[tag] = {{"labels": r.labels.tolist(),
                          "counts": r.counts.tolist(),
                          "dist": r.dist.view(np.int32).tolist()}}
        out["cases"][name] = case
    tier.close()
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_devices():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, "-c", SCRIPT.format(src=SRC)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_each_shard_on_a_device_of_its_own(four_devices):
    assert sorted(four_devices["placement"]) == [[0], [1], [2], [3]]
    # every buffer of a shard lives with its codes
    assert all(len(d) == 1 for d in four_devices["devices"])


def test_city_request_routes_to_all_four_shards(four_devices):
    case = four_devices["cases"]["city"]
    assert len(case["legs"]) == 4
    assert sorted(dev for dev, _ in case["legs"]) == [[0], [1], [2], [3]]


def test_boundary_queries_route_to_both_sides(four_devices):
    case = four_devices["cases"]["boundary"]
    assert max(case["routed"]) >= 2
    assert min(case["routed"]) >= 1


@pytest.mark.parametrize("case", ["city", "boundary"])
def test_each_leg_uploads_its_queries_to_its_shard(four_devices, case):
    legs = four_devices["cases"][case]["legs"]
    assert legs and all(snap == q for snap, q in legs), legs


@pytest.mark.parametrize("case", ["city", "boundary"])
@pytest.mark.parametrize("other", ["single", "ref"])
def test_tier_answers_bit_identical(four_devices, case, other):
    c = four_devices["cases"][case]
    assert not c["partial"]
    for field in ("labels", "counts", "dist"):
        np.testing.assert_array_equal(np.asarray(c["tier"][field]),
                                      np.asarray(c[other][field]),
                                      err_msg=f"{case} {other} {field}")

"""The four-chip tier cell, ``taxi-assign-bulk-4shard``, and its readers.

Rehearsed in a fresh process on four virtual CPU devices: traced, it
reports its per-layer metrics, the ``.tier`` ones read from the program's
``repro.tier.`` spans and the ``.assign`` ones from its legs'
``repro.serve.`` spans and the trace; the control comes out not correct,
and so does a run with one leg's contribution dropped from the merge (the
exchange between chips the check can catch) or with one answer altered.
Each reader is also checked on a small recorded four-device trace, where
a tree without the tier's spans (the program before them) reads nothing
of them and raises nothing. ``cross_sweep_ms.assign`` finds the kernel by
the chip's HLO name, which an interpret-mode rehearsal does not have, so
only the recorded trace checks it.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import harness_paths  # noqa: F401
from bench import run
from bench import trace as T

CELL = "taxi-assign-bulk-4shard"
SEED = "3141592653"
METRICS = run.ROOT / run.BENCH.name / "metrics"
METRICS_OF_CELL = [m["name"] for m in run.Cell(CELL).per_layer()]
TIER_SPANS = ["fanout.tier", "route_ms.tier", "legs_ms.tier",
              "merge_ms.tier", "leg_overlap.tier"]
ON_THE_CHIP_ONLY = {"cross_sweep_ms.assign"}


def _child(patch: str, *argv):
    """``run.main`` in a fresh process on four virtual CPU devices, after
    ``patch`` (Python source) has run there."""
    script = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = \\
            "--{run.DEVICE_COUNT_FLAG}=4 " + os.environ.get("XLA_FLAGS", "")
        sys.path[:0] = [{str(run.ROOT)!r}, {str(run.ROOT / "src")!r}]
        import numpy as np
        from repro.serve import router
    """) + textwrap.dedent(patch) + textwrap.dedent(f"""
        from bench import run
        sys.exit(run.main({["--workload", CELL, "--seed", SEED,
                            "--seconds", "1", "--rehearse", *argv]!r}))
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "REPRO_KERNEL_BACKEND": "interpret"}
    env["XLA_FLAGS"] = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                                if run.DEVICE_COUNT_FLAG not in f)
    p = subprocess.run([sys.executable, "-c", script], cwd=run.ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_traced_rehearsal_reports_the_tier_metrics():
    line = _child("", "--trace", "1")
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
    got = line["metrics"]
    assert set(TIER_SPANS) <= set(METRICS_OF_CELL)
    for name in set(METRICS_OF_CELL) - ON_THE_CHIP_ONLY:
        assert got[name]["value"] is not None and got[name]["value"] >= 0, \
            name
    assert got["fanout.tier"]["value"] > 1
    assert got["compiles.assign"]["value"] == 0
    assert {c: v["value"] for c, v in line["checks"].items()} == {
        "unanswered": 0, "partial": 0, "misplaced_shards": 0,
        "label_mismatch": 0, "count_mismatch": 0, "dist_mismatch": 0}


def test_control_is_not_correct():
    line = _child("", "--control")
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


LEG_DROPPED = """
    real = router.ShardedTier._assign_leg

    def leg(self, j, *a):
        r, status = real(self, j, *a)
        if r is not None and j == self.n_shards - 1:
            r = r._replace(labels=np.full_like(r.labels, -1),
                           counts=np.zeros_like(r.counts),
                           dist=np.full_like(r.dist, np.inf))
        return r, status

    router.ShardedTier._assign_leg = leg
"""
ANSWER_ALTERED = """
    real = router.ShardedTier.assign

    def assign(self, queries):
        r = real(self, queries)
        counts = r.counts.copy()
        counts[0] += 1
        return r._replace(counts=counts)

    router.ShardedTier.assign = assign
"""


@pytest.mark.parametrize("fault", [LEG_DROPPED, ANSWER_ALTERED],
                         ids=["leg_dropped", "answer_altered"])
def test_fault_is_not_correct(fault):
    line = _child(fault)
    assert line["correct"] is False, line["checks"]


# --- the readers on a recorded four-device trace ---------------------------

class _Ev:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _trace(host, ops_by_device):
    planes = [_Plane(f"/device:TPU:{d}", [_Line("XLA Ops", [
        _Ev(*op) for op in ops])]) for d, ops in enumerate(ops_by_device)]
    planes.append(_Plane("/host:CPU", [_Line("python3", [
        _Ev(*ev) for ev in host])]))
    return T.Trace.from_planes(iter(planes))


def _read(tr, counters=None):
    reading = run.Reading(tr, counters or {"xla_compiles": 0}, 2)
    return {n: run._reader(METRICS / f"{n}.py")(reading)
            for n in METRICS_OF_CELL}


def _leg(req, shard, start, dur):
    """A tier leg of 2 queries padded to 256 rows: its ``repro.tier.leg``
    span, and inside it the leg's ``serve.prepare`` (5 ns) and
    ``serve.finish`` (5 ns) under the tier request's id."""
    return [("repro.tier.leg", start, dur,
             [("req", req), ("shard", shard), ("replica", 0), ("nq", 2)]),
            ("repro.serve.prepare", start, 5,
             [("req", req), ("nq", 2), ("bucket", 256)]),
            ("repro.serve.finish", start + dur - 5, 5, [("req", req)])]


# Two requests in a 1000 ns window. Request 1 (100..500) routes to four
# shards whose legs run one after another, each device busy 50 ns in its
# own leg; request 2 (600..900) to two shards whose device work overlaps
# for 20 of its 60 busy ns (device 0: 650..690, device 1: 670..710).
HOST = [
    ("bench.window", 0, 1000), ("bench.entry", 100, 400),
    ("bench.entry", 600, 300),
    ("repro.tier.assign", 100, 400, [("req", 1), ("nq", 8), ("shards", 4)]),
    ("repro.tier.route", 105, 15, [("req", 1)]),
    *[ev for j in range(4) for ev in (
        *_leg(1, j, 120 + 90 * j, 70),
        ("repro.tier.merge", 190 + 90 * j, 10, [("req", 1), ("shard", j)]))],
    ("repro.tier.assign", 600, 300, [("req", 2), ("nq", 4), ("shards", 2)]),
    ("repro.tier.route", 605, 25, [("req", 2)]),
    *_leg(2, 0, 640, 80),
    *_leg(2, 1, 660, 60),
    ("repro.tier.merge", 720, 20, [("req", 2), ("shard", 0)]),
    ("repro.tier.merge", 740, 20, [("req", 2), ("shard", 1)]),
]
OPS = [[("%cross_sweep.3 = ...", 130 + 90 * d, 40),
        ("%fusion.1 = ...", 170 + 90 * d, 10)] for d in range(4)]
OPS[0] += [("%cross_sweep.3 = ...", 650, 40)]
OPS[1] += [("%cross_sweep.3 = ...", 670, 40)]


def test_readers_on_a_recorded_four_device_trace():
    got = _read(_trace(HOST, OPS))
    assert set(got) == set(METRICS_OF_CELL)
    assert got["compiles.assign"] == 0
    assert got["fanout.tier"] == 3.0
    assert got["route_ms.tier"] == pytest.approx(20e-6)
    assert got["legs_ms.tier"] == pytest.approx((280e-6 + 140e-6) / 2)
    assert got["merge_ms.tier"] == pytest.approx((40e-6 + 40e-6) / 2)
    # request 1: 200 busy ns over a union of 200; request 2: 80 over 60
    assert got["leg_overlap.tier"] == pytest.approx((1.0 + 80 / 60) / 2)
    assert got["cross_sweep_ms.assign"] == pytest.approx(
        (4 * 40 + 2 * 40) / 2 * 1e-6)
    busy = (4 * 50 + 40 + 40) / 4
    assert got["idle_share.assign"] == pytest.approx(100 * (1 - busy / 1000))
    # per request, the legs' spans summed: 4 and 2 legs of 5 ns each
    assert got["prepare_ms.assign"] == pytest.approx((20e-6 + 10e-6) / 2)
    assert got["finish_ms.assign"] == pytest.approx((20e-6 + 10e-6) / 2)
    assert got["pad_share.assign"] == pytest.approx(100 * (1 - 2 / 256))


def test_a_trace_without_tier_spans_reads_no_span_metric():
    """The program before the tier's spans: the readers of the spans give
    nothing and raise nothing; those of the trace still read."""
    host = [ev for ev in HOST if not ev[0].startswith("repro.tier.")]
    got = _read(_trace(host, OPS))
    for name in TIER_SPANS:
        assert got[name] is None, name
    assert got["cross_sweep_ms.assign"] > 0 and got["idle_share.assign"] > 0


def test_no_trace_reads_no_trace_metric():
    reading = run.Reading(None, {}, 0)
    for name in METRICS_OF_CELL:
        assert run._reader(METRICS / f"{name}.py")(reading) is None, name

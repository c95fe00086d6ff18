"""The trace reduction and the window arithmetic."""
import glob
import os

import numpy as np
import pytest

import harness_paths  # noqa: F401
from bench import load
from bench import trace as T
from bench.ops import assign, cluster


def test_merge_and_covered():
    m = T.merge([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert m == [(0, 3), (5, 9), (10, 11)]
    assert T.covered(m, 2, 6) == 2  # [2,3] and [5,6]
    assert T.covered(m, 4, 4.5) == 0
    assert T.covered(m, -1, 20) == 3 + 4 + 1


def test_kernel_name_and_self_time():
    assert T.kernel_name("%csr_sweep.10 = (s32[8]) custom-call(%x.1)") \
        == "csr_sweep"
    assert T.kernel_name("wrapped_sine") == "wrapped_sine"
    outer = T.Event("%while.3 = while(...)", 0, 100)
    inner = T.Event("%csr_sweep.1 = custom-call(%while.3)", 10, 70)
    last = T.Event("%fusion.2 = fusion()", 100, 130)
    got = {e.kernel: ns for e, ns in T.self_times([last, inner, outer])}
    assert got == {"while": 40, "csr_sweep": 60, "fusion": 30}


def _synthetic():
    spans = [T.Event("bench.window", 0, 1000), T.Event("bench.entry", 0, 600),
             T.Event("bench.wait", 600, 1000)]
    ops = [T.Event("%cross_sweep.1 = custom-call(%fusion.1)", 100, 300),
           T.Event("%fusion.1 = fusion()", 50, 120),
           T.Event("%copy.2 = copy()", 900, 950),
           T.Event("%early.1 = x()", -50, 20)]  # clipped to the window
    return T.Trace([ops], spans)


def test_busy_idle_kernel_time_and_gaps():
    tr = _synthetic()
    assert tr.window_s == pytest.approx(1000e-9)
    assert tr.busy_s == pytest.approx((20 + 250 + 50) * 1e-9)
    assert tr.busy_in(0, 600) == pytest.approx(270e-9)
    # the kernel's own name, not an operand that mentions it
    assert tr.op_s("cross_sweep") == pytest.approx(200e-9)
    assert tr.op_s("fusion") == pytest.approx(70e-9)
    gaps = tr.idle_gaps(3)
    # 300..900: its midpoint lies in both spans; the shorter one is named
    assert gaps[0] == ["wait", pytest.approx(600e-9)]
    assert [g[0] for g in gaps[1:]] == ["wait", "entry"]
    assert tr.top_ops(1) == [["cross_sweep", pytest.approx(200e-9)]]


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


def test_device_planes_from_a_one_pass_iterator():
    planes = [_Plane("/device:TPU:0", [_Line("XLA Ops", [
                  _Ev("%csr_sweep.3 = custom-call()", 10, 30)])]),
              _Plane("/host:CPU", [_Line("python3", [
                  _Ev("bench.window", 0, 100), _Ev("bench.entry", 5, 50),
                  _Ev("jit_x", 10, 5, [("hlo_op", "x")])])])]
    tr = T.Trace.from_planes(iter(planes))
    assert tr.busy_s == pytest.approx(30e-9)
    assert tr.op_s("csr_sweep") == pytest.approx(30e-9)
    assert len(tr.spans_named("entry")) == 1


@pytest.mark.parametrize("device_lines", [None, ["Steps"]])
def test_trace_without_device_ops_is_refused(device_lines):
    """No device plane, or one without its op line: host events with an
    ``hlo_op`` stat stand in only where a rehearsal asks for them."""
    host = _Plane("/host:CPU", [_Line("python3", [
        _Ev("bench.window", 0, 100),
        _Ev("jit_x", 10, 5, [("hlo_op", "x")])])])
    planes = [host]
    if device_lines is not None:
        planes.append(_Plane("/device:TPU:0", [
            _Line(n, [_Ev("%fusion.1 = fusion()", 10, 30)])
            for n in device_lines]))
    with pytest.raises(T.NoDeviceOps):
        T.Trace.from_planes(iter(planes))
    tr = T.Trace.from_planes(iter(planes), host_ops=True)
    assert tr.busy_s == pytest.approx(5e-9)


def test_trace_recorded_on_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x.T + 1.0)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.entry"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            import time
            time.sleep(0.02)
    jax.profiler.stop_trace()
    assert glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)
    with pytest.raises(T.NoDeviceOps):  # a CPU trace has no device plane
        T.Trace.load(str(tmp_path))
    tr = T.Trace.load(str(tmp_path), host_ops=True)
    assert len(tr.spans_named("entry")) == 3
    assert 0 < tr.busy_s < tr.window_s
    assert tr.op_s(r"dot.*") > 0
    assert tr.idle_gaps(1)[0][0] == "wait"
    assert tr.top_ops(10)


def test_rate_is_over_the_whole_window():
    outs = [None] * 3
    res = {"outs": outs, "elapsed_s": 12.0}
    # three clusterings in 12 s: 4 s each, not the median of pieces
    assert cluster.end_to_end(res) == {"cluster_s": 4.0}


def test_tail_and_rate_are_over_all_requests():
    """The rate is every point answered over all the window's seconds; a
    failed request answers none."""
    answers = [(np.zeros(k % 7 + 1),) for k in range(1000)]
    res = {"answers": answers, "elapsed_s": 20.0}
    points = sum(len(a[0]) for a in answers)
    assert assign.end_to_end(res) == {
        "assign_points_per_s": pytest.approx(points / 20.0)}
    answers[3] = None
    assert assign.end_to_end(res)["assign_points_per_s"] == pytest.approx(
        (points - 4) / 20.0)


def test_schedule_same_work_for_every_seed():
    tr = {"loop": "closed", "pass_requests": 128, "passes": 3,
          "sizes": {"dist": "loguniform", "min": 1, "max": 4096}}
    size = load.sizes(tr["sizes"], 128)
    assert np.all(np.diff(size) >= 0)
    assert size.min() == 1 and 3000 < size.max() <= 4096
    a = load.passes(tr, 1)
    b = load.passes(tr, 2**31 + 11)
    assert len(a) == len(b) == 3
    # every pass of every seed serves all its requests, in its own order
    assert all(np.array_equal(np.sort(p), np.arange(128)) for p in a + b)
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a[0], b[0])
    with pytest.raises(ValueError):
        load.passes({**tr, "loop": "open"}, 1)


def test_requests_are_the_same_for_every_seed():
    """The assign driver's passes: the same point sets for every seed, in
    the seed's order of requests and of rows."""
    cfg = {"dataset": "taxi2d", "world_seed": 0}
    tr = {"loop": "closed", "pass_requests": 16, "passes": 2,
          "sizes": {"dist": "loguniform", "min": 1, "max": 300}}
    a, b = assign._requests(cfg, tr, 5), assign._requests(cfg, tr, 2**31 + 5)

    def sets(passes):
        return [sorted(sorted(map(tuple, q)) for q in p) for p in passes]
    assert sets(a) == sets(b)
    assert [len(q) for q in a[0]] != [len(q) for q in b[0]]
    assert sets(a)[0] != sets(a)[1]  # each pass has points of its own

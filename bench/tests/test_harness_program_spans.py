"""The program's spans read from a trace's planes (``program_spans.py``):
their args, the numbers read from them, and idle gaps named by them."""
import json

import pytest

import harness_paths  # noqa: F401
from bench import program_spans as P
from bench import trace as T


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


def _planes(host, ops):
    return [_Plane("/device:TPU:0", [_Line("XLA Ops", [
                _Ev(name, s, d) for name, s, d in ops])]),
            _Plane("/host:CPU", [_Line("python3", [
                _Ev(*ev) for ev in host])])]


def _read(host, ops):
    planes = _planes(host, ops)
    tr = T.Trace.from_planes(iter(planes))
    return tr, P.program_spans(planes, tr)


# One assign request (req 7) in a 1000 ns window: prepare, a run of
# 200..520 whose device work (300..500) leaves 120 ns of it idle, finish.
ASSIGN = [
    ("bench.window", 0, 1000), ("bench.entry", 100, 800),
    ("repro.serve.assign", 110, 780, [("req", 7), ("nq", 17)]),
    ("repro.serve.prepare", 120, 80,
     [("req", 7), ("nq", 17), ("bucket", 256)]),
    ("repro.serve.run", 200, 320, [("req", 7), ("slab", 64), ("attempt", 0)]),
    ("repro.serve.finish", 520, 350, [("req", 7)]),
    ("repro.other", 2000, 10),  # outside the window
]
ASSIGN_OPS = [("%fusion.1 = fusion()", 300, 100),
              ("%cross_sweep.2 = custom-call()", 400, 100)]


def test_program_spans_keep_their_args_inside_the_window():
    _, spans = _read(ASSIGN, ASSIGN_OPS)
    assert [s.name for s in spans] == [
        "repro.serve.assign", "repro.serve.prepare", "repro.serve.run",
        "repro.serve.finish"]
    assert spans[1].args == {"req": 7, "nq": 17, "bucket": 256}
    assert spans[2].dur_ns == 320


def test_assign_numbers():
    tr, spans = _read(ASSIGN, ASSIGN_OPS)
    got = P.numbers(tr, spans)
    assert got["prepare_ms.assign"] == pytest.approx(80e-6)
    assert got["launch_ms.assign"] == pytest.approx(120e-6)  # 320 - 200
    assert got["finish_ms.assign"] == pytest.approx(350e-6)
    assert got["self_ms.assign"] == pytest.approx((780 - 750) * 1e-6)
    assert got["pad_share.assign"] == pytest.approx(100 * (1 - 17 / 256))
    assert got["plan_ms.batch"] is None and got["layout_ms.batch"] is None


def test_numbers_are_summed_per_request_then_medianed():
    """A regrown request has two runs: their idle time adds up."""
    host = [("bench.window", 0, 1000)]
    for req, start in ((1, 0), (2, 300), (3, 600)):
        host += [("repro.serve.run", start, 50, [("req", req)]),
                 ("repro.serve.run", start + 100, 50, [("req", req)])]
    tr, spans = _read(host, [("%f.1 = fusion()", 950, 10)])
    assert P.numbers(tr, spans)["launch_ms.assign"] == pytest.approx(100e-6)


def test_batch_numbers():
    host = [("bench.window", 0, 1000), ("bench.engines", 0, 900),
            ("repro.engine.build", 0, 900, [("engine", "grid"), ("n", 9)]),
            ("repro.engine.plan", 10, 190, [("n", 9), ("dims", 3)]),
            ("repro.engine.layout", 200, 700, [("slab", 64)])]
    tr, spans = _read(host, [("%fusion.1 = fusion()", 250, 600)])
    got = P.numbers(tr, spans)
    assert got["plan_ms.batch"] == pytest.approx(190e-6)
    assert got["layout_ms.batch"] == pytest.approx(600e-6)
    assert got["prepare_ms.assign"] is None


@pytest.mark.parametrize("name", [
    "plan_ms.batch", "layout_ms.batch", "prepare_ms.assign",
    "launch_ms.assign", "finish_ms.assign", "self_ms.assign",
    "pad_share.assign"])
def test_numbers_are_none_without_program_spans(name):
    tr, spans = _read([("bench.window", 0, 1000), ("bench.entry", 0, 900)],
                      ASSIGN_OPS)
    assert spans == []
    assert P.numbers(tr, spans)[name] is None


def test_gap_inside_a_run_is_named_by_it():
    tr, spans = _read(ASSIGN, ASSIGN_OPS)
    # 500..1000 (midpoint in finish) and 0..300 (midpoint in prepare)
    assert P.idle_gaps(tr, spans, 4) == [
        ["repro.serve.finish", pytest.approx(500e-9)],
        ["repro.serve.prepare", pytest.approx(300e-9)]]
    # a second request's run covers the midpoint and is the innermost
    host = ASSIGN + [("repro.serve.run", 600, 300, [("req", 8)])]
    tr, spans = _read(host, ASSIGN_OPS)
    assert P.idle_gaps(tr, spans, 1) == [
        ["repro.serve.run", pytest.approx(500e-9)]]


def test_gaps_without_program_spans_are_the_harness_gaps():
    host = [("bench.window", 0, 1000), ("bench.entry", 0, 600),
            ("bench.wait", 600, 400)]
    tr, spans = _read(host, ASSIGN_OPS + [("%copy.1 = copy()", 900, 50)])
    assert P.idle_gaps(tr, spans, 10) == tr.idle_gaps(10)


@pytest.mark.parametrize("workload, names", [
    ("roadnet-batch", ["plan_ms.batch", "layout_ms.batch"]),
    ("taxi-assign-mixed", ["prepare_ms.assign", "launch_ms.assign",
                           "finish_ms.assign", "self_ms.assign",
                           "pad_share.assign"])])
def test_rehearsal_prints_the_program_line_last(capsys, monkeypatch,
                                                workload, names):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    harness_load = T.Trace.load
    rc = P.main(["--workload", workload, "--seed", str(2**31 + 17),
                 "--seconds", "1", "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 0, err
    *_, result, last = out.strip().splitlines()
    assert json.loads(result)["correct"] is True
    got = json.loads(last)["program"]
    assert all(got[n] is not None and got[n] >= 0 for n in names), got
    assert any(g[0].startswith(P.PROGRAM_PREFIX) for g in got["idle_gaps"])
    assert T.Trace.load == harness_load

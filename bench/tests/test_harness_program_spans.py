"""The program's spans in a trace (``Trace.program``): their args, the
per-layer metrics read from them, and idle gaps named by them."""
import pytest

import harness_paths  # noqa: F401
from bench import run
from bench import trace as T

METRICS = run.ROOT / run.BENCH.name / "metrics"
PROGRAM = ["plan_ms.batch", "layout_ms.batch", "prepare_ms.assign",
           "launch_ms.assign", "finish_ms.assign", "self_ms.assign",
           "pad_share.assign"]
HARNESS = ["build_ms.batch", "compiles.assign", "compiles.batch",
           "cross_sweep_ms.assign", "host_ms.assign", "idle_share.assign",
           "idle_share.batch", "rounds.batch", "sweep_ms.batch"]


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
    return T.Trace.from_planes(iter(_planes(host, ops)))


def _metrics(tr, names=PROGRAM, counters=None):
    reading = run.Reading(tr, counters or {}, 1)
    return {n: run._reader(METRICS / f"{n}.py")(reading) for n in names}


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
    spans = _read(ASSIGN, ASSIGN_OPS).program
    assert [s.name for s in spans] == [
        "repro.serve.assign", "repro.serve.prepare", "repro.serve.run",
        "repro.serve.finish"]
    assert spans[1].args == {"req": 7, "nq": 17, "bucket": 256}
    assert spans[2].dur_ns == 320
    assert _read(ASSIGN, ASSIGN_OPS).program_named("serve.run") == [spans[2]]


def test_assign_numbers():
    got = _metrics(_read(ASSIGN, ASSIGN_OPS))
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
    tr = _read(host, [("%f.1 = fusion()", 950, 10)])
    assert _metrics(tr)["launch_ms.assign"] == pytest.approx(100e-6)


def test_batch_numbers():
    host = [("bench.window", 0, 1000), ("bench.engines", 0, 900),
            ("repro.engine.build", 0, 900, [("engine", "grid"), ("n", 9)]),
            ("repro.engine.plan", 10, 190, [("n", 9), ("dims", 3)]),
            ("repro.engine.layout", 200, 700, [("slab", 64)])]
    got = _metrics(_read(host, [("%fusion.1 = fusion()", 250, 600)]))
    assert got["plan_ms.batch"] == pytest.approx(190e-6)
    assert got["layout_ms.batch"] == pytest.approx(600e-6)
    assert got["prepare_ms.assign"] is None


@pytest.mark.parametrize("name", PROGRAM)
def test_numbers_are_none_without_program_spans(name):
    tr = _read([("bench.window", 0, 1000), ("bench.entry", 0, 900)],
               ASSIGN_OPS)
    assert tr.program == []
    assert _metrics(tr, [name])[name] is None


def test_gap_inside_a_run_is_named_by_it():
    tr = _read(ASSIGN, ASSIGN_OPS)
    # 500..1000 (midpoint in finish) and 0..300 (midpoint in prepare)
    assert tr.idle_gaps(4) == [
        ["repro.serve.finish", pytest.approx(500e-9)],
        ["repro.serve.prepare", pytest.approx(300e-9)]]
    # a second request's run covers the midpoint and is the innermost
    tr = _read(ASSIGN + [("repro.serve.run", 600, 300, [("req", 8)])],
               ASSIGN_OPS)
    assert tr.idle_gaps(1) == [["repro.serve.run", pytest.approx(500e-9)]]


def test_gaps_without_program_spans_are_the_harness_gaps():
    host = [("bench.window", 0, 1000), ("bench.entry", 0, 600),
            ("bench.wait", 600, 400)]
    tr = _read(host, ASSIGN_OPS + [("%copy.1 = copy()", 900, 50)])
    assert tr.program == []
    # 500..900 (midpoint in wait), 0..300 (in entry), 950..1000 (in wait)
    assert tr.idle_gaps(10) == [["wait", pytest.approx(400e-9)],
                                ["entry", pytest.approx(300e-9)],
                                ["wait", pytest.approx(50e-9)]]


@pytest.mark.parametrize("name", HARNESS)
def test_harness_readers_ignore_program_spans(name):
    """The readers of the harness's spans, device operations and counters
    read the same with the program's spans in the planes as without."""
    harness = [ev for ev in ASSIGN if ev[0].startswith(T.SPAN_PREFIX)]
    harness += [("bench.engines", 150, 300)]
    ops = ASSIGN_OPS + [("%csr_sweep.3 = custom-call()", 600, 50)]
    counters = {"xla_compiles": 2, "recompiles": 1, "n_rounds": 6}
    without = _read(harness, ops)
    program = ASSIGN + [("bench.engines", 150, 300),
                        ("repro.engine.plan", 160, 40, [("n", 9)])]
    assert len(_read(program, ops).program) == 5
    got = _metrics(_read(program, ops), [name], counters)[name]
    assert got is not None
    assert got == _metrics(without, [name], counters)[name]

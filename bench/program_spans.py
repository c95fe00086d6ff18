#!/usr/bin/env python3
"""The program's own spans in a traced window, beside the harness's numbers.

    python3 bench/program_spans.py --workload <name> --seed <n> --seconds <s>

runs the cell once through ``run.py`` with ``--trace 1``, as the benchmark
does, and prints after its result line one more JSON object,
``{"program": {...}}``: the numbers below, read from the same
``.xplane.pb``, and the ten longest idle gaps named by the innermost
program span covering each gap's midpoint, else as ``trace.py`` names them.

The program marks its work with ``jax.profiler.TraceAnnotation`` names that
start with ``PROGRAM_PREFIX`` (``repro/obs.py``); their args are stats on
the trace event. ``trace.py`` keeps only the harness's spans, so this
module reads the planes again. Each number is None where the trace holds
none of its spans, as for a program without them:

* ``plan_ms.batch``: mean ``repro.engine.plan`` (host download and plan);
* ``layout_ms.batch``: mean device-busy time inside ``repro.engine.layout``
  (the CSR build program and its overflow read);
* ``prepare_ms.assign``, ``finish_ms.assign``: medians over requests of
  ``repro.serve.prepare`` (validate, pad, upload) and ``repro.serve.finish``
  (three reads back, labels, the result);
* ``launch_ms.assign``: median over requests of the idle time inside the
  request's ``repro.serve.run`` spans (dispatch, wait for the first
  operation, the overflow read);
* ``self_ms.assign``: median over requests of ``repro.serve.assign`` less
  the request's other spans (validation, admission);
* ``pad_share.assign``: 100 (1 - sum of ``nq`` / sum of ``bucket``) over
  the ``repro.serve.prepare`` spans: the share of padded query rows.
"""
import dataclasses
import json
import pathlib
import statistics
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if not __package__:
    sys.path[0] = str(ROOT)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

from bench import trace as T  # noqa: E402

PROGRAM_PREFIX = "repro."


@dataclasses.dataclass(frozen=True)
class Span(T.Event):
    """A program span: a host event with its stats as ``args``."""
    args: dict


def program_spans(planes, tr: "T.Trace") -> list:
    """The host events of ``planes`` named ``PROGRAM_PREFIX...`` that
    overlap ``tr``'s window, with their stats as ``args``."""
    return sorted((Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats))
                   for p in planes if p.name.startswith("/host:")
                   for line in p.lines for ev in line.events
                   if ev.name.startswith(PROGRAM_PREFIX)
                   and ev.start_ns + ev.duration_ns > tr.start_ns
                   and ev.start_ns < tr.end_ns),
                  key=lambda s: s.start_ns)


def _named(spans, name):
    return [s for s in spans if s.name == PROGRAM_PREFIX + name]


def _idle_ms(tr, s) -> float:
    return (s.dur_ns * 1e-9 - tr.busy_in(s.start_ns, s.end_ns)) * 1e3


def _per_req(spans, name, value) -> dict:
    """Sum of ``value(span)`` per ``req`` over the spans named ``name``."""
    out: dict = {}
    for s in _named(spans, name):
        out[s.args.get("req")] = out.get(s.args.get("req"), 0.0) + value(s)
    return out


def _median(values):
    values = list(values)
    return statistics.median(values) if values else None


def _mean(values):
    values = list(values)
    return statistics.fmean(values) if values else None


def numbers(tr, spans) -> dict:
    """The module docstring's numbers for one window."""
    ms = lambda s: s.dur_ns * 1e-6  # noqa: E731
    inner = {}
    for name in ("serve.prepare", "serve.run", "serve.regrow",
                 "serve.finish"):
        for req, v in _per_req(spans, name, ms).items():
            inner[req] = inner.get(req, 0.0) + v
    prep = _named(spans, "serve.prepare")
    bucket = sum(s.args.get("bucket", 0) for s in prep)
    return {
        "plan_ms.batch": _mean(map(ms, _named(spans, "engine.plan"))),
        "layout_ms.batch": _mean(
            tr.busy_in(s.start_ns, s.end_ns) * 1e3
            for s in _named(spans, "engine.layout")),
        "prepare_ms.assign": _median(
            _per_req(spans, "serve.prepare", ms).values()),
        "launch_ms.assign": _median(_per_req(
            spans, "serve.run", lambda s: _idle_ms(tr, s)).values()),
        "finish_ms.assign": _median(
            _per_req(spans, "serve.finish", ms).values()),
        "self_ms.assign": _median(
            v - inner.get(req, 0.0) for req, v in
            _per_req(spans, "serve.assign", ms).items()),
        "pad_share.assign": 100.0 * (1.0 - sum(
            s.args.get("nq", 0) for s in prep) / bucket) if bucket else None,
    }


def idle_gaps(tr, spans, k: int = 10) -> list:
    """``trace.Trace.idle_gaps``, with a gap inside a program span named by
    the innermost one, in full (``repro.serve.run``)."""
    out = tr.idle_gaps(k)
    gaps = []
    for m in tr.busy:
        edges = [(tr.start_ns, tr.start_ns), *m, (tr.end_ns, tr.end_ns)]
        gaps += [(b[0] - a[1], a[1], b[0])
                 for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    for row, (_, s, e) in zip(out, sorted(gaps, reverse=True)[:k]):
        mid = (s + e) / 2
        inner = [sp for sp in spans if sp.start_ns <= mid <= sp.end_ns]
        if inner:
            row[0] = min(inner, key=lambda sp: sp.dur_ns).name
    return out


def main(argv=None) -> int:
    import glob
    import os
    from bench import run
    from jax.profiler import ProfileData
    found = {}

    def load(cls, log_dir, host_ops=False):
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        planes = list(ProfileData.from_file(
            max(paths, key=os.path.getmtime)).planes)
        tr = cls.from_planes(planes, host_ops=host_ops)
        spans = program_spans(planes, tr)
        found["program"] = {**numbers(tr, spans),
                            "idle_gaps": idle_gaps(tr, spans)}
        return tr

    harness_load, T.Trace.load = T.Trace.load, classmethod(load)
    argv = list(sys.argv[1:] if argv is None else argv)
    try:
        rc = run.main(argv + ["--trace", "1"])
    finally:
        T.Trace.load = harness_load
    if "program" in found:
        print(json.dumps(found), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

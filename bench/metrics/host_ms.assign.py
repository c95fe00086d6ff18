"""Median over requests of the host's share of one ``assign`` call: the
harness's per-request ``entry`` span minus the device-busy time inside it.
Layer: serving host path (``serve/assign.py``, ``serve/scheduler.py``).
Moves ``assign_points_per_s``."""

import statistics


def read(run):
    spans = run.trace.spans_named("entry") if run.trace else []
    if not spans:
        return None
    return statistics.median(
        s.dur_ns * 1e-6 - run.trace.busy_in(s.start_ns, s.end_ns) * 1e3
        for s in spans)

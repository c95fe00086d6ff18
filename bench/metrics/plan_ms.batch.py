"""Mean milliseconds of the CSR engine's host plan per build, from the
program's ``repro.engine.plan`` spans (``n``, ``dims``: the download of the
points, ``plan_csr_grid`` and the jitted layout it runs on the device).
Layer: engines (``core/engines.py``, ``core/neighbors.py``). Moves
``cluster_s``."""

import statistics


def read(run):
    spans = run.trace.program_named("engine.plan") if run.trace else []
    if not spans:
        return None
    return statistics.fmean(s.dur_ns for s in spans) * 1e-6

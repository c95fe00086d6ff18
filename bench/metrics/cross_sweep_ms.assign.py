"""Device milliseconds per request in the cross-corpus slab sweep kernel
(``cross_sweep``), summed from the trace by kernel name. Layer: kernels
(``kernels/cross_sweep.py``). Moves ``assign_points_per_s``."""

PATTERN = r"cross_sweep"


def read(run):
    n = len(run.trace.spans_named("entry")) if run.trace else 0
    s = run.trace.op_s(PATTERN) if n else 0.0
    return s / n * 1e3 if s > 0 else None

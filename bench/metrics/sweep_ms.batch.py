"""Device milliseconds per clustering in the CSR slab sweep kernels
(``csr_sweep``, ``csr_sweep_counts``), summed from the trace by kernel name. Layer:
kernels (``kernels/csr_sweep.py``). Moves ``cluster_s``."""

PATTERN = r"csr_sweep(_counts)?"


def read(run):
    n = len(run.trace.spans_named("entry")) if run.trace else 0
    s = run.trace.op_s(PATTERN) if n else 0.0
    return s / n * 1e3 if s > 0 else None

"""Mean device-busy milliseconds inside the program's ``repro.engine.layout``
spans (``slab``: ``build_csr_grid``, the layout run again and the slab
program ``_csr_pack``, then the ``overflow`` read), one per build. Layer:
engines (``core/grid.py``, ``core/neighbors.py``). Moves ``cluster_s``."""

import statistics


def read(run):
    spans = run.trace.program_named("engine.layout") if run.trace else []
    if not spans:
        return None
    return statistics.fmean(
        run.trace.busy_in(s.start_ns, s.end_ns) for s in spans) * 1e3

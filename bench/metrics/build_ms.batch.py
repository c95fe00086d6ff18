"""Mean milliseconds of one engine build (``make_engine``: host plan plus
device build, ended on the device) per clustering, from the harness's
``engines`` spans in the trace. Layer: engines (``core/grid.py``,
``core/neighbors.py``). Moves ``cluster_s``."""


def read(run):
    spans = run.trace.spans_named("engines") if run.trace else []
    if not spans:
        return None
    return sum(s.dur_ns for s in spans) / len(spans) * 1e-6

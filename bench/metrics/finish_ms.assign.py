"""Median over requests of the milliseconds in the program's
``repro.serve.finish`` spans (``req``: three reads back, the labels, the
result). Layer: serving host path (``serve/assign.py``). Moves
``assign_points_per_s``."""

import statistics


def read(run):
    per_req = run.trace.per_request(
        "serve.finish", lambda s: s.dur_ns * 1e-6) if run.trace else {}
    return statistics.median(per_req.values()) if per_req else None

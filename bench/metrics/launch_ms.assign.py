"""Median over requests of the idle milliseconds inside the program's
``repro.serve.run`` spans (``req``, ``slab``, ``attempt``: program lookup,
dispatch, the wait for the first device operation and after the last, the
``overflow`` read): each span's length less the device-busy time in it,
summed over a regrown request's runs. Layer: serving host path
(``serve/assign.py``, ``serve/scheduler.py``). Moves
``assign_points_per_s``."""

import statistics


def read(run):
    if not run.trace:
        return None
    per_req = run.trace.per_request(
        "serve.run", lambda s: (s.dur_ns * 1e-9 - run.trace.busy_in(
            s.start_ns, s.end_ns)) * 1e3)
    return statistics.median(per_req.values()) if per_req else None

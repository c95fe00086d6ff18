"""Median over requests of the milliseconds in the program's
``repro.tier.route`` spans (``req``: the host bisect of each query's
ε-window codes against the corpus's sorted codes, and the routing
telemetry). Layer: router (``serve/router.py``, ``serve/shard.py``).
Moves ``assign_points_per_s``."""

import statistics


def read(run):
    per_req = run.trace.per_request(
        "tier.route", lambda s: s.dur_ns * 1e-6) if run.trace else {}
    return statistics.median(per_req.values()) if per_req else None

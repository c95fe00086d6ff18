"""Median over requests of the milliseconds in the program's
``repro.tier.leg`` spans, summed per request (``req``, ``shard``,
``replica``, ``nq``: one serve attempt against one shard, its
``repro.serve.*`` spans inside). Layer: router (``serve/router.py``).
Moves ``assign_points_per_s``."""

import statistics


def read(run):
    per_req = run.trace.per_request(
        "tier.leg", lambda s: s.dur_ns * 1e-6) if run.trace else {}
    return statistics.median(per_req.values()) if per_req else None

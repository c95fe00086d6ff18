"""Median over requests of the milliseconds in the program's
``repro.tier.merge`` spans, summed per request (``req``, ``shard``: a
leg's label-table remap and its min and sum into the answer, on the
host). Layer: router (``serve/router.py``). Moves
``assign_points_per_s``."""

import statistics


def read(run):
    per_req = run.trace.per_request(
        "tier.merge", lambda s: s.dur_ns * 1e-6) if run.trace else {}
    return statistics.median(per_req.values()) if per_req else None

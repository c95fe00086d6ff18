"""Median over tier requests of how many devices work at once: inside each
of the program's ``repro.tier.assign`` spans, the device-busy seconds
summed over the devices over the seconds in which any device is busy. 1
means the shards' legs never overlap on the devices; 4 means all four
chips always work together. Requests with no device work are left out.
Layer: device. Moves ``assign_points_per_s``."""

import statistics

from bench.trace import covered, merge


def read(run):
    spans = run.trace.program_named("tier.assign") if run.trace else []
    if not spans:
        return None
    union = merge(iv for busy in run.trace.busy for iv in busy)
    ratios = []
    for s in spans:
        any_busy = covered(union, s.start_ns, s.end_ns)
        if any_busy > 0:
            ratios.append(sum(covered(busy, s.start_ns, s.end_ns)
                              for busy in run.trace.busy) / any_busy)
    return statistics.median(ratios) if ratios else None

"""Share of the traced assign window in which no operation ran on the
device: 1 - busy union / window. One caller sends back to back, so the
idle time is the host's: the serving host path and the harness between
requests. Layer: device. Moves ``assign_points_per_s``."""


def read(run):
    if not run.trace or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)

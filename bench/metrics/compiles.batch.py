"""XLA compiles inside a batch window: the backend-compile events the
harness's meter counts from the window's open to its close. Layer: entry
(``core/dbscan.py`` ``dbscan``). Moves ``cluster_s``."""


def read(run):
    return run.counters.get("xla_compiles")

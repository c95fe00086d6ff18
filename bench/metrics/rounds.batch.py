"""Stage-2 hooking rounds of a clustering (``DBSCANResult.n_rounds``).
Layer: round drivers (``core/dbscan.py``). Moves ``cluster_s``."""


def read(run):
    return run.counters.get("n_rounds")

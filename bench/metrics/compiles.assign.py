"""Compiles inside an assign window: XLA backend-compile events plus the
serving scheduler's own count of new trace keys (``recompiles``). Layer:
entry (``serve/ingest.py`` ``ServeSession.assign``). Moves
``assign_points_per_s``."""


def read(run):
    c = run.counters
    if "xla_compiles" not in c:
        return None
    return c["xla_compiles"] + c.get("recompiles", 0)

"""Median over requests of the self time of the program's
``repro.serve.assign`` span (``req``, ``nq``: validation and admission):
its milliseconds less those of the request's ``repro.serve.prepare``,
``run``, ``regrow`` and ``finish`` spans. Layer: entry
(``serve/ingest.py`` ``ServeSession.assign``). Moves
``assign_points_per_s``."""

import statistics

INNER = ("serve.prepare", "serve.run", "serve.regrow", "serve.finish")


def read(run):
    if not run.trace:
        return None
    ms = lambda s: s.dur_ns * 1e-6  # noqa: E731
    outer = run.trace.per_request("serve.assign", ms)
    if not outer:
        return None
    inner: dict = {}
    for name in INNER:
        for req, v in run.trace.per_request(name, ms).items():
            inner[req] = inner.get(req, 0.0) + v
    return statistics.median(v - inner.get(req, 0.0)
                             for req, v in outer.items())

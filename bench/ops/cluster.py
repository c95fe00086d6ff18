"""``op: cluster`` — a batch job that clusters its corpus, closed loop.

Set-up takes the configuration's corpus (one sample of its world,
``world_seed``: one road graph) in an order drawn from the seed that keeps
the sample's own order inside each ε-cell (``data.riffle``), and clusters
it once, which plans and compiles its programs. The window then re-clusters it from the host array,
back to back with one caller, through ``dbscan(points, eps, min_pts)`` with
the program's defaults; each call ends with its labels, core mask, counts
and round count on the host. ``cluster_s`` is the window's seconds over the
clusterings completed in it, the last one run to its end.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import data
from bench import reference as R


def _program(cfg):
    from repro.core import dbscan as mod

    def run(points):
        r = mod.dbscan(points, cfg["eps"], cfg["min_pts"])
        return (np.asarray(r.labels), np.asarray(r.core),
                np.asarray(r.counts), int(r.n_rounds))
    return run


def _control(cfg):
    """The reference on bfloat16 coordinates, in the program's place."""
    def run(points):
        c = R.dbscan(R.to_bf16(points), cfg["eps"], cfg["min_pts"],
                     cfg["dims"])
        return c.labels, c.core, c.counts, -1
    return run


def setup(ctx) -> dict:
    cfg = ctx.cfg
    points = data.ordered(cfg["dataset"], cfg["n_points"],
                          cfg["world_seed"], ctx.seed, cell=cfg["eps"])
    run = _control(cfg) if ctx.control else _program(cfg)
    run(points)
    return {"points": points, "run": run}


@contextlib.contextmanager
def _build_spans(ctx):
    """In a traced run, mark each engine build (``make_engine``, which
    returns once its state is ready on the device) as a span."""
    if not ctx.tracing or ctx.control:
        yield
        return
    from repro.core import neighbors as nb
    orig = nb.make_engine

    def make_engine(*args, **kwargs):
        with ctx.span("engines"):
            return orig(*args, **kwargs)

    nb.make_engine = make_engine
    try:
        yield
    finally:
        nb.make_engine = orig


def window(ctx, state) -> dict:
    outs = []
    with _build_spans(ctx):
        t0 = time.perf_counter()
        while True:
            with ctx.span("entry"):
                outs.append(state["run"](state["points"]))
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        elapsed = time.perf_counter() - t0
    return {"outs": outs, "elapsed_s": elapsed, "attempted": len(outs),
            "failed": 0, "counters": {"n_rounds": outs[-1][3]}}


def end_to_end(result) -> dict:
    return {"cluster_s": result["elapsed_s"] / len(result["outs"])}


def release(state) -> None:
    state.pop("run", None)


def check(ctx, state, result) -> dict:
    """Every clustering of the window against the reference: the most
    points whose label, core flag or count differ, over the clusterings."""
    cfg = ctx.cfg
    exact = "counts" in cfg["compare"]
    ref = R.dbscan(state["points"], cfg["eps"], cfg["min_pts"], cfg["dims"],
                   exact_counts=exact)
    worst = {"label_mismatch": 0, "core_mismatch": 0}
    if exact:
        worst["count_mismatch"] = 0
    for labels, core, counts, _ in result["outs"]:
        worst["label_mismatch"] = max(worst["label_mismatch"],
                                      int((labels != ref.labels).sum()))
        worst["core_mismatch"] = max(worst["core_mismatch"],
                                     int((core != ref.core).sum()))
        if exact:
            worst["count_mismatch"] = max(worst["count_mismatch"],
                                          int((counts != ref.counts).sum()))
    return worst

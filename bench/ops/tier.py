"""``op: tier`` — DBSCAN-predict requests against one clustered snapshot
served from Morton-range shards, one per chip, behind ``ShardedTier``; one
caller, closed loop.

The corpus, the requests and their order are those of ``op: assign``
(its helpers make them). Set-up clusters the corpus globally
(``build_snapshot``), splits it into ``n_shards`` shards, each placed on a
device of its own with no read replica (``ShardedTier.from_snapshot``),
and drops the global snapshot. It warms the tier with a stream of requests
from the same generator under a sub-seed of its own, pass after pass,
until a pass compiles nothing and regrows no slab on any shard.

The window serves whole passes back to back through ``ShardedTier.assign``
until ``--seconds`` have passed; the pass under way then runs to its end.
A request that raises, or whose answer is ``partial`` (a routed shard
contributed nothing), answers no points. ``assign_points_per_s`` is the
points answered over the window's seconds, as in ``op: assign``.

The check is ``op: assign``'s, point for point against the reference on
the unsplit corpus, plus the number of partial answers and of shards not
on a device of their own (``misplaced_shards``: fewer shards than the
configuration asks for count too).
"""
from __future__ import annotations

import time

import numpy as np

from bench import data
from bench import load
from bench.ops import assign as A


def _misplaced(tier, n_shards: int) -> int:
    """``n_shards`` less the number of devices that each hold one whole
    shard and nothing of another."""
    import jax
    placed = set()
    for sess in tier.sessions:
        devs = {d for leaf in jax.tree_util.tree_leaves(sess.snapshot)
                if isinstance(leaf, jax.Array) for d in leaf.devices()}
        if len(devs) == 1:
            placed |= devs
    return n_shards - len(placed)


def setup(ctx) -> dict:
    if ctx.control:
        return A.setup(ctx)
    cfg, tr, seed = ctx.cfg, ctx.traffic, ctx.seed
    corpus = data.ordered(cfg["dataset"], cfg["n_points"], cfg["world_seed"],
                          seed, cell=cfg["eps"])
    requests = A._requests(cfg, tr, seed)
    warm_rng = np.random.default_rng(data.sub_seed(seed, 3))
    warm = A._points(cfg, load.sizes(tr["sizes"], tr["warmup_requests"],
                                     warm_rng), seed, 4)
    from repro import serve
    tier = serve.ShardedTier.from_snapshot(
        serve.build_snapshot(corpus, cfg["eps"], cfg["min_pts"]),
        n_shards=cfg["n_shards"])
    sched = tier.scheduler
    for _ in range(8):
        mark, regrows = ctx.meter.mark(), sched.regrows
        for q in warm:
            tier.assign(q)
        if ctx.meter.since(mark)[2] == 0 and sched.regrows == regrows:
            break
    return {"corpus": corpus, "requests": requests, "tier": tier,
            "serve": tier.assign,
            "misplaced": _misplaced(tier, cfg["n_shards"])}


def window(ctx, state) -> dict:
    from repro.serve import ServeError
    requests, serve_fn = state["requests"], state["serve"]
    tier = state.get("tier")
    recompiles = tier.scheduler.recompiles if tier else 0
    served, answers, service = [], [], []
    partial = n_pass = 0
    t0 = time.perf_counter()
    while True:
        p = n_pass % len(requests)
        for i, q in enumerate(requests[p]):
            start = time.perf_counter()
            with ctx.span("entry"):
                try:
                    r = serve_fn(q)
                    if getattr(r, "partial", False):
                        partial += 1
                        answers.append(None)
                    else:
                        answers.append((r.labels, r.counts, r.dist))
                except ServeError:
                    answers.append(None)
            service.append(time.perf_counter() - start)
            served.append((p, i))
        n_pass += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    counters = {"recompiles": (tier.scheduler.recompiles - recompiles)
                if tier else 0,
                "shards": tier.n_shards if tier else 0,
                "passes": n_pass,
                "service_mean_ms": float(np.mean(service)) * 1e3}
    return {"served": served, "answers": answers, "elapsed_s": elapsed,
            "attempted": len(answers), "partial": partial,
            "failed": sum(a is None for a in answers), "counters": counters}


end_to_end = A.end_to_end


def release(state) -> None:
    tier = state.pop("tier", None)
    state.pop("serve", None)
    if tier is not None:
        tier.close()


def check(ctx, state, result) -> dict:
    """``op: assign``'s check of a sample of the answers, with the partial
    answers and the misplaced shards."""
    got = A.check(ctx, state, result)
    return {"unanswered": got.pop("unanswered"),
            "partial": int(result["partial"]),
            "misplaced_shards": int(state.get("misplaced", 0)), **got}

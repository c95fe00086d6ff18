"""``op: assign`` — DBSCAN-predict requests against a served snapshot, one
caller, closed loop.

Set-up takes the configuration's corpus (one sample of its world,
``world_seed``: one city's hubs and routes, from which the requests' points
come too) in an order drawn from the seed, builds a ``ServeSession`` over
``build_snapshot`` of it, and warms it with a stream from the same
generator under a sub-seed of its own, then with one request per shape
bucket until a pass over the buckets compiles nothing and regrows no slab.
It also makes the window's requests: ``passes`` passes of the same sizes,
each with points of its own drawn from the world, the same for every seed,
which orders each pass's requests and the rows inside each request
(``load.passes``).

The window serves whole passes back to back through
``ServeSession.assign``, one request at a time, until ``--seconds`` have
passed; the pass under way then runs to its end. A request that raises is
failed and answers no points. ``assign_points_per_s`` is the points
answered over the window's seconds: the rate one caller gets from the
server, every pass the same work.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import data
from bench import load
from bench import reference as R


def _points(cfg, sizes, seed: int, stream: int) -> list:
    """One list of request point sets of ``sizes``, from the cell's world."""
    total = int(np.sum(sizes))
    pool = data.ordered(cfg["dataset"], max(total, 1), cfg["world_seed"],
                        seed, stream)
    return np.split(pool[:total], np.cumsum(sizes)[:-1])


def _buckets(max_size: int, first: int = 256) -> list:
    out = [first]
    while out[-1] < max_size:
        out.append(out[-1] * 2)
    return out


def _requests(cfg, tr, seed: int) -> list:
    """Per pass, its requests in the order the seed gives them."""
    size = load.sizes(tr["sizes"], int(tr["pass_requests"]))
    orders = load.passes(tr, data.sub_seed(seed, 1))
    pool = data.load(cfg["dataset"], len(orders) * int(size.sum()),
                     data.sub_seed(cfg["world_seed"], 2),
                     structure_seed=cfg["world_seed"])
    per_pass = np.split(pool, len(orders))
    rng = np.random.default_rng(data.sub_seed(seed, 2))
    out = []
    for pts, order in zip(per_pass, orders):
        reqs = np.split(pts, np.cumsum(size)[:-1])
        out.append([reqs[k][rng.permutation(len(reqs[k]))] for k in order])
    return out


def setup(ctx) -> dict:
    cfg, tr, seed = ctx.cfg, ctx.traffic, ctx.seed
    corpus = data.ordered(cfg["dataset"], cfg["n_points"], cfg["world_seed"],
                          seed, cell=cfg["eps"])
    requests = _requests(cfg, tr, seed)
    warm_rng = np.random.default_rng(data.sub_seed(seed, 3))
    warm = _points(cfg, load.sizes(tr["sizes"], tr["warmup_requests"],
                                   warm_rng), seed, 4)
    state = {"corpus": corpus, "requests": requests}
    if ctx.control:
        low = R.to_bf16(corpus)
        ref = R.dbscan(low, cfg["eps"], cfg["min_pts"], cfg["dims"],
                       exact_counts=False)
        state["serve"] = lambda q: R.predict(low, ref, R.to_bf16(q),
                                             cfg["eps"])
        state["serve"](warm[0])
        return state
    from repro import serve
    snap = serve.build_snapshot(corpus, cfg["eps"], cfg["min_pts"])
    sess = serve.ServeSession(snap)
    state.update(session=sess, serve=sess.assign)
    for q in warm:
        sess.assign(q)
    ladder = _points(cfg, _buckets(int(tr["sizes"]["max"]),
                                   sess.scheduler.min_bucket), seed, 5)
    for _ in range(8):
        mark, regrows = ctx.meter.mark(), sess.scheduler.regrows
        for q in ladder:
            sess.assign(q)
        if ctx.meter.since(mark)[2] == 0 \
                and sess.scheduler.regrows == regrows:
            break
    return state


def window(ctx, state) -> dict:
    from repro.serve import ServeError
    requests, serve_fn = state["requests"], state["serve"]
    sess = state.get("session")
    recompiles = sess.scheduler.recompiles if sess else 0
    served, answers, service = [], [], []
    n_pass = 0
    t0 = time.perf_counter()
    while True:
        p = n_pass % len(requests)
        for i, q in enumerate(requests[p]):
            start = time.perf_counter()
            with ctx.span("entry"):
                try:
                    r = serve_fn(q)
                    answers.append((r.labels, r.counts, r.dist))
                except ServeError:
                    answers.append(None)
            service.append(time.perf_counter() - start)
            served.append((p, i))
        n_pass += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    counters = {"recompiles": (sess.scheduler.recompiles - recompiles)
                if sess else 0,
                "slab": sess.snapshot.slab if sess else 0,
                "plan_slab": sess.snapshot.spec.slab if sess else 0,
                "passes": n_pass,
                "service_mean_ms": float(np.mean(service)) * 1e3}
    return {"served": served, "answers": answers, "elapsed_s": elapsed,
            "attempted": len(answers),
            "failed": sum(a is None for a in answers), "counters": counters}


def end_to_end(result) -> dict:
    points = sum(len(a[0]) for a in result["answers"] if a is not None)
    return {"assign_points_per_s": points / result["elapsed_s"]}


def release(state) -> None:
    for k in ("session", "serve"):
        state.pop(k, None)


def check(ctx, state, result) -> dict:
    """A sample of the window's answers, drawn from the seed and holding
    its largest request, against the reference: points whose label, count
    or distance differ, and requests never answered."""
    cfg = ctx.cfg
    queries = [state["requests"][p][i] for p, i in result["served"]]
    sizes = np.array([len(q) for q in queries])
    order = np.random.default_rng(data.sub_seed(ctx.seed, 6)) \
        .permutation(len(sizes))
    take = np.cumsum(sizes[order]) <= ctx.traffic["check_points"]
    pick = set(order[take].tolist()) | {int(np.argmax(sizes))}
    pick = sorted(i for i in pick if result["answers"][i] is not None)
    ref = R.dbscan(state["corpus"], cfg["eps"], cfg["min_pts"], cfg["dims"],
                   exact_counts=False)
    want = R.predict(state["corpus"], ref,
                     np.concatenate([queries[i] for i in pick]), cfg["eps"])
    print(f"checked {len(want.labels)} points of {len(pick)} requests",
          file=sys.stderr)

    def differ(k, ref):
        got = np.concatenate([result["answers"][i][k] for i in pick])
        return int((got != ref).sum()) if got.shape == ref.shape \
            else len(ref)

    return {"unanswered": int(result["failed"]),
            "label_mismatch": differ(0, want.labels),
            "count_mismatch": differ(1, want.counts),
            "dist_mismatch": differ(2, want.dist)}

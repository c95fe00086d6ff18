"""Clustering driver CLI — the paper's workload end-to-end.

    PYTHONPATH=src python -m repro.launch.cluster --dataset taxi2d -n 100000 \
        --eps 0.08 --min-pts 16 [--engine grid|bvh|brute] [--distributed]

Prints cluster statistics and the build/sweep time breakdown (paper §V-D).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..core import engines
from ..core import labels as L
from ..core import neighbors as nb
from ..core.dbscan import dbscan
from ..data import synth
from . import compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="taxi2d",
                    choices=sorted(synth.DATASETS))
    ap.add_argument("-n", type=int, default=100_000)
    ap.add_argument("--eps", type=float, default=0.08)
    ap.add_argument("--min-pts", type=int, default=16)
    ap.add_argument("--engine", default="grid",
                    choices=list(engines.available_engines()))
    ap.add_argument("--distributed", action="store_true",
                    help="shard over all local devices (shard_map path)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    pts = synth.load(args.dataset, args.n, seed=args.seed)
    print(f"dataset={args.dataset} n={args.n} eps={args.eps} "
          f"minPts={args.min_pts} engine={args.engine}")

    if args.distributed:
        import jax
        from ..distributed.dbscan_dist import dbscan_distributed
        from .mesh import make_mesh
        d = jax.device_count()
        mesh = make_mesh((d,), ("data",))
        t0 = time.perf_counter()
        res = dbscan_distributed(pts, args.eps, args.min_pts, mesh)
        t_total = time.perf_counter() - t0
        t_build = 0.0
    else:
        t0 = time.perf_counter()
        eng = nb.make_engine(pts, args.eps, engine=args.engine)
        t_build = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = dbscan(pts, args.eps, args.min_pts, eng=eng)
        t_total = t_build + (time.perf_counter() - t0)

    sizes = L.cluster_sizes(res.labels)
    lab = np.asarray(res.labels)
    print(f"clusters: {len(sizes)}  core: {int(np.asarray(res.core).sum())}"
          f"  border: {int(((lab >= 0) & ~np.asarray(res.core)).sum())}"
          f"  noise: {int((lab == -1).sum())}")
    if len(sizes):
        print(f"largest clusters: {sorted(sizes.tolist(), reverse=True)[:8]}")
    print(f"stage-2 rounds: {res.n_rounds}")
    print(f"time: total={t_total:.3f}s build={t_build:.3f}s "
          f"(build {100 * t_build / max(t_total, 1e-9):.0f}% — paper §V-D)")
    return res


if __name__ == "__main__":
    main()

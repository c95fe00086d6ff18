#!/usr/bin/env python3
"""Drive the system's main paths once on a TPU, at the paper's dataset sizes,
and check every answer against an independent reference.

    python chip_smoke.py              # batch + serving phases, one chip
    python chip_smoke.py --chips 4    # multi-device phase, four chips

One chip:

* **batch** — ``roadnet2d`` at the UCI 3D Road Network size (434,874
  points, ε = 0.02, minPts = 8): the ``repro.launch.cluster`` CLI
  (grid engine), ``dbscan(hook_loop="frontier")`` and
  ``dbscan(engine="bvh")`` must give bit-identical labels; the grid labels
  must equal the pure-jnp ``ref`` oracle run on the chip; on a seeded
  4,096-point spatial subsample the labels must be DBSCAN-equivalent to
  the sequential brute-force reference.
* **serve** — a ``taxi2d`` corpus at Porto taxi GPS scale (1,000,000
  points, ε = 0.08, minPts = 16): ``build_snapshot``, then a
  ``ServeSession`` answering 4,096-point ``assign`` batches (bit-identical
  to the ``ref`` oracle, zero recompiles after warmup) and ingesting
  1,024-point chunks with ``max_delta_frac=0.01``, so the tenth chunk
  forces a compaction whose labels must equal batch ``dbscan()`` on corpus
  plus chunks.

Four chips (``--chips 4``): ``dbscan_distributed`` over a ``(4,)`` mesh
must agree with single-chip ``dbscan()`` (equal core masks, equal noise,
equal core partition), and a ``ShardedTier`` with one shard per chip must
answer ``assign`` bit-identically to the single-snapshot path.

Every phase prints the device, the resolved kernel backend, wall and XLA
compile time and the device's peak memory; the last line of standard output
is one JSON object, ``{"ok": ..., "device": {...}}``. Without a TPU the
script exits 2 before any work. ``--tiny`` is a CPU rehearsal at small
sizes (``JAX_PLATFORMS=cpu REPRO_KERNEL_BACKEND=interpret``, plus
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` with ``--chips 4``);
its numbers are CPU numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.monitoring
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro import serve  # noqa: E402
from repro.baselines.brute import reference_dbscan  # noqa: E402
from repro.core import labels as L  # noqa: E402
from repro.core.dbscan import dbscan  # noqa: E402
from repro.data import synth  # noqa: E402
from repro.distributed.dbscan_dist import (DistConfig,  # noqa: E402
                                           dbscan_distributed)
from repro.kernels import ops  # noqa: E402
from repro.launch import cluster, compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

FULL = dict(batch_n=434_874, serve_n=1_000_000, chunk=1024, qbatch=4096,
            n_assign=4, sub_n=4096)
TINY = dict(batch_n=3_000, serve_n=10_000, chunk=11, qbatch=256,
            n_assign=2, sub_n=512)
BATCH_EPS, BATCH_MINPTS = 0.02, 8      # benchmarks/figures.py, roadnet2d
SERVE_EPS, SERVE_MINPTS = 0.08, 16     # benchmarks/figures.py, taxi2d
N_CHUNKS = 10                          # chunks until the first compaction
DELTA_FRAC = 0.01


class Failed(Exception):
    """A check that did not hold."""


class Meter:
    """XLA backend compile time and count, summed from JAX's monitoring
    events; spans read differences of the running totals."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compile_s = 0.0
        self.n_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == self.EVENT:
            self.compile_s += secs
            self.n_compiles += 1

    def mark(self):
        return time.perf_counter(), self.compile_s, self.n_compiles

    def since(self, mark):
        t0, c0, k0 = mark
        return (time.perf_counter() - t0, self.compile_s - c0,
                self.n_compiles - k0)


def _peak(dev) -> str:
    stats = dev.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return str(stats["peak_bytes_in_use"])


class Phase:
    """Prints one phase's steps and checks; a failed check raises."""

    def __init__(self, name: str, meter: Meter, devices):
        self.name, self.meter, self.devices = name, meter, devices

    def log(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", flush=True)

    def step(self, what: str, fn):
        m = self.meter.mark()
        out = fn()
        jax.block_until_ready([x for x in jax.tree.leaves(out)
                               if hasattr(x, "block_until_ready")])
        wall, comp, k = self.meter.since(m)
        self.log(f"{what}: wall_s={wall!r} compile_s={comp!r} "
                 f"compiles={k}")
        return out

    def check(self, what: str, ok: bool, detail: str = "") -> None:
        self.log(f"check {what}: {'PASS' if ok else 'FAIL'}"
                 + (f" ({detail})" if detail else ""))
        if not ok:
            raise Failed(f"{self.name}: {what}")

    def run(self, body) -> bool:
        d0 = self.devices[0]
        self.log(f"device platform={d0.platform} kind={d0.device_kind!r} "
                 f"count={len(self.devices)} "
                 f"kernel_backend={ops.default_backend()}")
        m = self.meter.mark()
        ok = True
        try:
            body(self)
        except Exception:  # report the phase, keep running the others
            ok = False
            traceback.print_exc()
            sys.stdout.flush()
        wall, comp, k = self.meter.since(m)
        peaks = " ".join(f"dev{d.id}={_peak(d)}" for d in self.devices)
        self.log(f"{'PASS' if ok else 'FAIL'} wall_s={wall!r} "
                 f"compile_s={comp!r} compiles={k} "
                 f"peak_bytes_in_use: {peaks}")
        return ok


def _equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and bool(np.array_equal(a, b))


def _n_clusters(labels) -> int:
    lab = np.asarray(labels)
    return int(np.unique(lab[lab >= 0]).size)


def batch_phase(ph: Phase, size: dict, seed: int) -> None:
    n, eps, mp = size["batch_n"], BATCH_EPS, BATCH_MINPTS
    ph.log(f"roadnet2d n={n} eps={eps} minPts={mp} seed={seed}")
    argv = ["--dataset", "roadnet2d", "-n", str(n), "--eps", str(eps),
            "--min-pts", str(mp), "--engine", "grid", "--seed", str(seed)]
    grid = ph.step("grid engine via repro.launch.cluster",
                   lambda: cluster.main(argv))
    pts = synth.load("roadnet2d", n, seed=seed)
    front = ph.step("grid engine, hook_loop=frontier",
                    lambda: dbscan(pts, eps, mp, hook_loop="frontier"))
    bvh = ph.step("bvh engine (batch=8, bf16 prune, terminate)",
                  lambda: dbscan(pts, eps, mp, engine="bvh"))
    ph.log(f"clusters={_n_clusters(grid.labels)} "
           f"core={int(np.asarray(grid.core).sum())} "
           f"noise={int((np.asarray(grid.labels) == -1).sum())} "
           f"rounds grid={int(grid.n_rounds)} frontier={int(front.n_rounds)} "
           f"bvh={int(bvh.n_rounds)}")
    ph.check("labels grid == frontier == bvh",
             _equal(grid.labels, front.labels)
             and _equal(grid.labels, bvh.labels))
    ref = ph.step("grid engine, backend=ref (pure-jnp oracle)",
                  lambda: dbscan(pts, eps, mp, backend="ref"))
    ph.check("grid labels, core, counts == ref oracle",
             _equal(grid.labels, ref.labels) and _equal(grid.core, ref.core)
             and _equal(grid.counts, ref.counts))

    # a spatial subsample keeps the corpus density, so clusters survive it
    rng = np.random.default_rng(seed)
    centre = pts[rng.integers(n)]
    near = np.argpartition(((pts - centre) ** 2).sum(1), size["sub_n"])
    sub = pts[np.sort(near[:size["sub_n"]])]
    got = ph.step(f"grid engine on a {len(sub)}-point subsample",
                  lambda: dbscan(sub, eps, mp))
    want_lab, want_core = reference_dbscan(sub, eps, mp)
    ph.log(f"subsample clusters={_n_clusters(want_lab)} "
           f"core={int(want_core.sum())}")
    ph.check("subsample == brute-force reference (labels.equivalent)",
             _equal(got.core, want_core)
             and L.equivalent(got.labels, want_lab, want_core, sub, eps))


def serve_phase(ph: Phase, size: dict, seed: int) -> None:
    n, eps, mp = size["serve_n"], SERVE_EPS, SERVE_MINPTS
    ph.log(f"taxi2d corpus n={n} eps={eps} minPts={mp} seed={seed}")
    corpus = synth.load("taxi2d", n, seed=seed)

    def world(m, s):  # fresh points around the corpus's hubs
        return synth.load("taxi2d", m, seed=s, structure_seed=seed)

    snap = ph.step("build_snapshot",
                   lambda: serve.build_snapshot(corpus, eps, mp))
    ph.log(f"snapshot clusters={snap.n_clusters()} "
           f"slab={snap.spec.slab} tiles={snap.spec.n_tiles}")
    sess = serve.ServeSession(snap, max_delta_frac=DELTA_FRAC)
    queries = [world(size["qbatch"], seed + 100 + i)
               for i in range(size["n_assign"] + 1)]
    ph.step("assign warmup", lambda: sess.assign(queries[0]))
    sess.scheduler.reset_stats()
    m = ph.meter.mark()
    got = [sess.assign(q) for q in queries[1:]]
    wall, _, k = ph.meter.since(m)
    p50, p99 = sess.scheduler.latency_percentiles()
    ph.log(f"{len(got)} assign batches of {size['qbatch']}: wall_s={wall!r} "
           f"p50_s={p50!r} p99_s={p99!r} xla_compiles={k}")
    ph.check("zero recompiles after warmup",
             sess.scheduler.recompiles == 0 and k == 0,
             f"scheduler.recompiles={sess.scheduler.recompiles} "
             f"xla_compiles={k}")
    refs = [ph.step("assign, backend=ref",
                    lambda q=q: serve.assign(snap, q, backend="ref"))
            for q in queries[1:]]
    ph.check("assign labels, counts, dist == ref oracle",
             all(_equal(a.labels, b.labels) and _equal(a.counts, b.counts)
                 and _equal(a.dist, b.dist) for a, b in zip(got, refs)),
             f"labelled={sum(int((a.labels >= 0).sum()) for a in got)}"
             f"/{sum(len(a.labels) for a in got)}")

    chunks = [world(size["chunk"], seed + 1000 + i) for i in range(N_CHUNKS)]
    compacted = []
    for i, c in enumerate(chunks):
        r = ph.step(f"ingest chunk {i} ({len(c)} points)",
                    lambda c=c: sess.ingest(c))
        compacted.append(r.compacted)
    ph.check(f"only chunk {N_CHUNKS - 1} compacts",
             compacted == [False] * (N_CHUNKS - 1) + [True],
             f"compacted={compacted} n_compactions={sess.n_compactions}")
    allpts = np.concatenate([corpus] + chunks)
    batch = ph.step("batch dbscan on corpus + chunks",
                    lambda: dbscan(allpts, eps, mp))
    ph.check("compacted labels, core == batch dbscan()",
             _equal(sess.snapshot.labels, batch.labels)
             and _equal(sess.snapshot.core, batch.core),
             f"n={len(allpts)}")


def _canon(x):
    """Relabel by order of first appearance (noise stays -1)."""
    x = np.asarray(x)
    out = np.full(x.shape, -1, np.int64)
    keep = x != -1
    _, first, inv = np.unique(x[keep], return_index=True,
                              return_inverse=True)
    out[keep] = np.argsort(np.argsort(first))[inv]
    return out


def multi_phase(ph: Phase, size: dict, seed: int) -> None:
    n, eps, mp = size["serve_n"], SERVE_EPS, SERVE_MINPTS
    ph.log(f"taxi2d corpus n={n} eps={eps} minPts={mp} seed={seed}")
    corpus = synth.load("taxi2d", n, seed=seed)
    mesh = make_mesh((len(ph.devices),), ("data",))
    # the hash-grid local engine's bucket capacity cannot hold taxi hubs at
    # this ε; CSR slabs sized for the densest per-device window can
    cfg = DistConfig(local_engine="csr", csr_slab=1 << 18)
    dist = ph.step(f"dbscan_distributed on a {mesh.devices.shape} mesh "
                   f"(local_engine={cfg.local_engine}, "
                   f"csr_slab={cfg.csr_slab})",
                   lambda: dbscan_distributed(corpus, eps, mp, mesh, cfg=cfg))
    snap = ph.step("single-chip build_snapshot (dbscan)",
                   lambda: serve.build_snapshot(corpus, eps, mp))
    core = np.asarray(snap.core)
    ph.log(f"label rounds={dist.n_rounds} clusters={snap.n_clusters()} "
           f"labels bit-identical={_equal(dist.labels, snap.labels)}")
    ph.check("distributed core mask == single-chip",
             _equal(dist.core, core))
    ph.check("distributed noise == single-chip",
             _equal(np.asarray(dist.labels) == -1,
                    np.asarray(snap.labels) == -1))
    ph.check("distributed core partition == single-chip",
             _equal(_canon(np.asarray(dist.labels)[core]),
                    _canon(np.asarray(snap.labels)[core])))

    k = len(ph.devices)
    tier = ph.step(f"ShardedTier.build n_shards={k}",
                   lambda: serve.ShardedTier.build(corpus, eps, mp,
                                                   n_shards=k))
    try:
        placed = []
        for j, s in enumerate(tier.sessions):
            devs = sorted(d.id for d in s.snapshot.cands.devices())
            placed.append(tuple(devs))
            ph.log(f"shard {j}: n={s.snapshot.n} devices={devs}")
        ph.check("shards on distinct devices",
                 len(set(placed)) == len(placed) == k)
        queries = [synth.load("taxi2d", size["qbatch"], seed=seed + 100 + i,
                              structure_seed=seed)
                   for i in range(size["n_assign"])]
        got = [ph.step("tier assign", lambda q=q: tier.assign(q))
               for q in queries]
        want = [ph.step("single-snapshot assign",
                        lambda q=q: serve.assign(snap, q))
                for q in queries]
        ph.check("tier assign labels, counts, dist == single snapshot",
                 all(not a.partial and _equal(a.labels, b.labels)
                     and _equal(a.counts, b.counts)
                     and _equal(a.dist, b.dist)
                     for a, b in zip(got, want)))
    finally:
        tier.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: batch + serving phases; 4: the multi-device "
                         "phase only")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at small sizes (not a chip run)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_KERNEL_BACKEND") and not args.tiny:
        print("chip_smoke: REPRO_KERNEL_BACKEND is set; unset it so the "
              "kernels run compiled", file=sys.stderr)
        return 2
    devices = jax.devices()
    platform = devices[0].platform
    want_backend = "interpret" if args.tiny else "kernel"
    if platform != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU (JAX found {platform}); nothing run",
              file=sys.stderr)
        return 2
    if ops.default_backend() != want_backend:
        print(f"chip_smoke: kernel backend resolves to "
              f"{ops.default_backend()!r}, expected {want_backend!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:args.chips]
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    size = TINY if args.tiny else FULL
    meter = Meter()
    if args.chips == 4:
        phases = [("multi", multi_phase)]
    else:
        phases = [("batch", batch_phase), ("serve", serve_phase)]
    results = {}
    for name, body in phases:
        results[name] = Phase(name, meter, devices).run(
            lambda ph, body=body: body(ph, size, args.seed))
    ok = all(results.values())
    d0 = devices[0]
    print(json.dumps({"ok": ok, "phases": results,
                      "device": {"platform": d0.platform,
                                 "kind": d0.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

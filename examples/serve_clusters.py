"""Serve a clustered corpus online: freeze a snapshot, stream new points
through ingest (bounded delta + compaction), and answer new-point queries
with bucketed assign — the DBSCAN analog of serve_decode.py.

Run: PYTHONPATH=src python examples/serve_clusters.py

Durable mode (DESIGN.md §14, the recovery runbook in README.md):

    # log every ingest to a WAL, then die mid-ingest after 3 acked chunks
    python examples/serve_clusters.py --wal-dir /tmp/wal --kill-after 3

    # restart: replay the log onto the newest intact snapshot and verify
    # labels are bit-identical to batch dbscan() on the recovered points
    python examples/serve_clusters.py --wal-dir /tmp/wal --recover

The kill is a real ``SIGKILL`` the process sends itself at a durability
boundary (frame flushed, ack never delivered), so the recover run
demonstrates the full contract: every acked chunk survives, the in-flight
chunk is applied in full or not at all, and parity is exact.

Sharded mode (DESIGN.md §15): the same serve loop scattered over N
Morton-range shards behind the router —

    python examples/serve_clusters.py --shards 3

streams ingest through per-shard delta buffers, compacts at tier scope,
and verifies the reassembled shard-local labels are bit-identical to
batch ``dbscan()`` on everything ingested (exit 1 on mismatch).

Shard chaos (DESIGN.md §16): kill one shard mid-stream and watch the
tier degrade and recover —

    python examples/serve_clusters.py --shards 3 --kill-shard 1 --at 2

arms a ``Kill`` on shard 1's next ingest leg at chunk 2: the chunk sheds
UNACKED, the shard quarantines, queries keep answering (partial gathers,
flagged per shard), the shard re-materializes from its own checkpoint
namespace, the shed chunk retries idempotently, and the run exits
nonzero unless post-recovery labels are still bit-identical to batch
``dbscan()``.
"""
import sys, os, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import shutil
import signal
import tempfile

import numpy as np

from repro import serve
from repro.core.dbscan import dbscan
from repro.data import synth
from repro.data.pipeline import point_stream
from repro.launch import compile_cache

EPS, MINPTS = 0.08, 16
N_CORPUS, N_STREAM, CHUNK = 20_000, 4_000, 512


def batch_demo():
    # --- freeze a snapshot of a clustered corpus ----------------------------
    pts = synth.load("taxi2d", N_CORPUS, seed=0)
    t0 = time.perf_counter()
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    print(f"snapshot: n={snap.n} clusters={snap.n_clusters()} "
          f"built in {time.perf_counter() - t0:.2f}s")

    # --- stream new points through ingest -----------------------------------
    # seed=0 matches the corpus: the stream samples the SAME hub layout
    # (point_stream pins the dataset's global structure to its seed)
    sess = serve.ServeSession(snap, max_delta_frac=0.1)
    t0 = time.perf_counter()
    n_in = 0
    for chunk in point_stream("taxi2d", N_STREAM, CHUNK, seed=0):
        res = sess.ingest(chunk)
        n_in += len(chunk)
        tag = "compacted" if res.compacted else f"delta={res.n_delta}"
        print(f"  ingest {len(chunk)} pts ({tag}): "
              f"{(res.labels >= 0).mean():.0%} clustered")
    dt = time.perf_counter() - t0
    print(f"ingested {n_in} pts in {dt:.2f}s ({n_in / dt:.0f} pts/s, "
          f"{sess.n_compactions} compactions)")

    # --- answer assign queries at varying batch sizes ------------------------
    rng = np.random.default_rng(2)
    for b in sess.scheduler.buckets_upto(1024):    # warmup the bucket ladder
        sess.assign(rng.uniform(0, 8, (b, 3)).astype(np.float32) * [1, 1, 0])
    sess.scheduler.reset_stats()

    t0 = time.perf_counter()
    n_q = 0
    for _ in range(40):
        nq = int(rng.integers(1, 1024))
        q = (rng.uniform(0, 8, (nq, 3)) * [1, 1, 0]).astype(np.float32)
        r = sess.assign(q)
        n_q += nq
    dt = time.perf_counter() - t0
    p50, p99 = sess.scheduler.latency_percentiles()
    print(f"assigned {n_q} queries in {dt:.2f}s — {n_q / dt:.0f} QPS "
          f"sustained, p50={p50 * 1e3:.1f}ms p99={p99 * 1e3:.1f}ms, "
          f"recompiles after warmup: {sess.scheduler.recompiles}")
    print(f"last batch: {(r.labels >= 0).mean():.0%} joined a cluster, "
          f"median core distance "
          f"{np.nanmedian(np.where(np.isinf(r.dist), np.nan, r.dist)):.4f}")

    # --- resilience: keep serving through a broken compaction ---------------
    # (DESIGN.md §12) inject one rebuild failure, watch the session degrade
    # to the last published snapshot instead of going down, then recover
    with serve.faults.inject("serve.compact", times=-1,
                             error=RuntimeError("injected rebuild failure")):
        ri = sess.ingest(synth.load("taxi2d", 256, seed=3))  # compaction
        #          due, rebuild fails -> online labels, delta kept
        print(f"ingest under broken compaction: degraded={ri.degraded}, "
              f"delta={ri.n_delta}")
        try:
            sess.compact()
        except serve.CompactionError as e:
            print(f"compaction failed ({e.code}), retry_after="
                  f"{e.retry_after:.1f}s — still serving")
        r = sess.assign(q)                            # answers keep coming
        print(f"degraded={r.degraded} staleness={r.staleness} "
              f"(answers can't see the last {r.staleness} ingested points)")
    sess.compact(force=True)                          # operator-driven probe
    r = sess.assign(q)
    print(f"recovered: degraded={r.degraded} staleness={r.staleness}, "
          f"breaker={sess.breaker.state}, shed so far: "
          f"{sess.admission.shed}, slab regrows: {sess.scheduler.regrows}")


def _shard_chaos_recover(tier, shard_id, i, chunk, err):
    """The §16 failover + recovery runbook, narrated: the owner died
    mid-scatter (chunk UNACKED), queries keep answering partially, the
    shard re-materializes from its checkpoint namespace, and the shed
    chunk retries idempotently."""
    print(f"  [chaos] chunk {i} shed UNACKED: {err} "
          f"(retry_after={err.retry_after:.2f}s)")
    rep = tier.health_report()
    states = {t: row["state"] for t, row in rep["targets"].items()}
    print(f"  [chaos] health: quarantined={rep['quarantined']} "
          f"states={states}")
    # reads survive the death: the gather degrades to a flagged partial
    rng = np.random.default_rng(7)
    q = (rng.uniform(0, 8, (256, 3)) * [1, 1, 0]).astype(np.float32)
    rq = tier.assign(q)
    miss = sorted(j for j, s in (rq.shards or {}).items() if s.missing)
    print(f"  [chaos] assign during quarantine: partial={rq.partial} "
          f"missing shards={miss} (a missing shard only LOSES neighbors)")
    t0 = time.perf_counter()
    ok = tier.recover_shard(shard_id)
    print(f"  [chaos] re-materialized {serve.target_tag(shard_id, None)} "
          f"from its checkpoint namespace in "
          f"{time.perf_counter() - t0:.2f}s: probe-certified={ok}")
    if not ok:
        print("  [chaos] recovery failed — shard still quarantined")
        sys.exit(2)
    res = tier.ingest(chunk, request_id=f"stream-{i}")
    print(f"  [chaos] idempotent retry of chunk {i} after recovery: acked")
    return res


def sharded_demo(args):
    # --- split a clustered corpus across Morton-range shards ----------------
    pts = synth.load("taxi2d", args.n_corpus, seed=0)
    t0 = time.perf_counter()
    knobs, tmp = {}, None
    if args.kill_shard is not None:
        # the chaos run needs per-shard checkpoint namespaces to
        # re-materialize the victim from (§16.4)
        tmp = tempfile.mkdtemp(prefix="serve-tier-chaos-")
        knobs = dict(ckpt_root=os.path.join(tmp, "snap"),
                     wal_root=os.path.join(tmp, "wal"),
                     durability="none", auto_recover=False,
                     # the certifying probe may be the recovered plan's
                     # first-ever assign trace — on the ref backend that
                     # is compile time, not serving latency
                     health=serve.HealthRegistry(probe_deadline_s=60.0))
    tier = serve.ShardedTier.build(pts, EPS, MINPTS, n_shards=args.shards,
                                   **knobs)
    print(f"sharded tier: n={tier.n} shards={tier.n_shards} "
          f"sizes={[p.n for p in tier.parts]} "
          f"built in {time.perf_counter() - t0:.2f}s")
    if args.kill_shard is not None and not (
            0 <= args.kill_shard < tier.n_shards):
        print(f"--kill-shard {args.kill_shard} out of range "
              f"(tier has {tier.n_shards} shards)")
        sys.exit(2)

    # --- stream ingest through the router -----------------------------------
    # each chunk scatters to the shards owning its Morton codes; tier-scope
    # compaction rebuilds the global clustering and re-cuts the shards
    chunks = []
    t0 = time.perf_counter()
    for i, chunk in enumerate(point_stream("taxi2d", args.n_stream, CHUNK,
                                           seed=0)):
        if args.kill_shard is not None and i == args.at:
            victim = serve.target_tag(args.kill_shard, 0)
            serve.faults.inject("serve.shard.ingest", times=1, tag=victim,
                                error=serve.faults.Kill("chaos"))
            print(f"  [chaos] armed a kill on {victim}'s next ingest leg")
        try:
            res = tier.ingest(chunk, request_id=f"stream-{i}")
        except serve.AdmissionError as e:
            res = _shard_chaos_recover(tier, args.kill_shard, i, chunk, e)
        chunks.append(chunk)
        tag = "compacted" if res.compacted else f"delta={res.n_delta}"
        print(f"  ingest {len(chunk)} pts ({tag}): "
              f"{(res.labels >= 0).mean():.0%} clustered")
    n_in = sum(len(c) for c in chunks)
    dt = time.perf_counter() - t0
    print(f"ingested {n_in} pts in {dt:.2f}s ({n_in / dt:.0f} pts/s, "
          f"{tier.n_compactions} tier compactions)")
    if args.kill_shard is not None:
        if serve.faults.fired_count("serve.shard.ingest") == 0:
            print("chaos kill never fired — no chunk after --at routed to "
                  f"shard {args.kill_shard} (raise --n-stream or lower "
                  "--at); refusing to report a green chaos run")
            sys.exit(2)
        serve.faults.clear()
    # snapshot chaos counters before the QPS section resets the scheduler
    sch = tier.scheduler
    chaos_stats = dict(failovers=sch.failovers, partials=sch.partials,
                       probes=sch.probes)
    tier.compact(force=True)

    # --- scatter-gather assign: routed fan-out + zero recompiles ------------
    def stream(seed):
        rng = np.random.default_rng(seed)
        for _ in range(40):
            nq = int(rng.integers(1, 1024))
            yield (rng.uniform(0, 8, (nq, 3)) * [1, 1, 0]).astype(np.float32)

    for b in tier.scheduler.buckets_upto(1024):       # trace the ladder,
        tier.assign((np.zeros((b, 3))).astype(np.float32))
    for q in stream(2):                               # then prime the exact
        tier.assign(q)                                # stream (slab regrows
    tier.scheduler.reset_stats()                      # are data-dependent)
    t0 = time.perf_counter()
    n_q = 0
    for q in stream(2):
        r = tier.assign(q)
        n_q += len(q)
    dt = time.perf_counter() - t0
    hist = dict(sorted(tier.scheduler.routed.items()))
    print(f"assigned {n_q} queries in {dt:.2f}s — {n_q / dt:.0f} QPS, "
          f"shards-per-query histogram {hist}, "
          f"recompiles after warmup: {tier.scheduler.recompiles}")

    # --- parity: shard labels reassemble to the batch answer ----------------
    every = np.concatenate([pts] + chunks)
    full = dbscan(every, EPS, MINPTS, engine="grid")
    lab = np.full(len(every), -1, np.int64)
    for p in tier.parts:
        loc = np.asarray(p.snapshot.labels)
        g = np.full(len(loc), -1, np.int64)
        if p.label_table.size:
            m = loc >= 0
            g[m] = p.label_table.astype(np.int64)[loc[m]]
        lab[p.orig_index] = g
    ok = np.array_equal(lab, np.asarray(full.labels))
    verb = ("post-recovery parity" if args.kill_shard is not None
            else "parity")
    print(f"{verb} vs batch dbscan on {len(every)} pts across "
          f"{tier.n_shards} shards: "
          + ("OK — bit-identical" if ok else "MISMATCH"))
    if args.kill_shard is not None:
        print(f"chaos telemetry: failovers={chaos_stats['failovers']} "
              f"partials={chaos_stats['partials']} "
              f"probes={chaos_stats['probes']} "
              f"recompiles after warmup: {tier.scheduler.recompiles}")
    tier.close()
    if tmp is not None:
        shutil.rmtree(tmp, ignore_errors=True)
    sys.exit(0 if ok else 1)


def durable_demo(args):
    ckpt_dir = args.ckpt_dir or args.wal_dir.rstrip("/") + "-snap"

    if args.recover:
        t0 = time.perf_counter()
        sess = serve.ServeSession.recover(
            ckpt_dir, args.wal_dir, durability=args.durability,
            max_delta_frac=0.1)
        rep = sess.last_recovery
        print(f"recovered from step {rep.baseline_step} @ log offset "
              f"{rep.baseline_offset} in {time.perf_counter() - t0:.2f}s: "
              f"replayed {rep.replayed_chunks} chunks / "
              f"{rep.replayed_points} pts ({rep.skipped_aborted} aborted, "
              f"{rep.skipped_duplicates} duplicate, {rep.truncated_bytes}B "
              "torn tail dropped)")
        sess.compact(force=True)  # fold the replayed delta for the check
        rec_pts = np.asarray(sess.snapshot.points)
        full = dbscan(rec_pts, EPS, MINPTS, engine="grid")
        ok = (np.array_equal(np.asarray(sess.snapshot.labels),
                             np.asarray(full.labels))
              and np.array_equal(np.asarray(sess.snapshot.core),
                                 np.asarray(full.core)))
        print(f"parity vs batch dbscan on {len(rec_pts)} recovered pts: "
              + ("OK — bit-identical" if ok else "MISMATCH"))
        sess.wal.close()
        sys.exit(0 if ok else 1)

    pts = synth.load("taxi2d", args.n_corpus, seed=0)
    t0 = time.perf_counter()
    snap = serve.build_snapshot(pts, EPS, MINPTS)
    print(f"snapshot: n={snap.n} built in {time.perf_counter() - t0:.2f}s; "
          f"WAL at {args.wal_dir} (durability={args.durability}), "
          f"checkpoints at {ckpt_dir}")
    sess = serve.ServeSession(
        snap, max_delta_frac=0.1, ckpt_dir=ckpt_dir,
        wal=serve.WriteAheadLog(args.wal_dir, durability=args.durability))
    # the canonical crash window: the frame is flushed, the ack never
    # happens — recovery must apply the chunk in full (fsync mode fires at
    # the sync itself; other modes die right after the apply)
    kill_site = ("serve.wal.fsync" if args.durability == "fsync"
                 else "serve.ingest.label")
    acked = 0
    t0 = time.perf_counter()
    for i, chunk in enumerate(point_stream("taxi2d", args.n_stream, CHUNK,
                                           seed=0)):
        if args.kill_after is not None and acked == args.kill_after:
            serve.faults.inject(kill_site, times=1,
                                error=serve.faults.Kill(kill_site))
        try:
            res = sess.ingest(chunk, request_id=f"stream-{i}")
        except serve.faults.Kill:
            print(f"SIGKILL mid-ingest: {acked} chunks acked, chunk {i} "
                  "logged but never acknowledged", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
        acked += 1
        tag = "compacted" if res.compacted else f"delta={res.n_delta}"
        print(f"  acked {len(chunk)} pts ({tag}), durable @ log offset "
              f"{sess.wal.position}")
    dt = time.perf_counter() - t0
    print(f"clean run: {acked} chunks / {acked * CHUNK} pts acked in "
          f"{dt:.2f}s, {sess.n_compactions} compactions, "
          f"{sess.wal.n_rotations} segment rotations")
    sess.wal.close()


if __name__ == "__main__":
    ap = argparse.ArgumentParser(
        description="online clustering serve demo (see module docstring)")
    ap.add_argument("--wal-dir", default=None, metavar="DIR",
                    help="enable durable ingest: write-ahead log directory")
    ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                    help="snapshot checkpoint dir (default: <wal-dir>-snap)")
    ap.add_argument("--recover", action="store_true",
                    help="replay the WAL onto the newest intact snapshot "
                         "and verify batch parity (exit 1 on mismatch)")
    ap.add_argument("--kill-after", type=int, default=None, metavar="N",
                    help="SIGKILL self mid-ingest after N acked chunks")
    ap.add_argument("--durability", default="fsync",
                    choices=["fsync", "flush", "none"])
    ap.add_argument("--shards", type=int, default=None, metavar="N",
                    help="serve through a sharded tier of N Morton-range "
                         "shards and verify batch parity (exit 1 on "
                         "mismatch)")
    ap.add_argument("--kill-shard", type=int, default=None, metavar="J",
                    help="(with --shards) kill shard J's owner mid-stream: "
                         "the chunk sheds UNACKED, the shard quarantines "
                         "and re-materializes, and the run exits nonzero "
                         "unless post-recovery labels match batch dbscan")
    ap.add_argument("--at", type=int, default=2, metavar="K",
                    help="arm the --kill-shard fault at stream chunk K")
    ap.add_argument("--n-corpus", type=int, default=6_000)
    ap.add_argument("--n-stream", type=int, default=2_048)
    args = ap.parse_args()
    if args.kill_shard is not None and args.shards is None:
        ap.error("--kill-shard requires --shards")
    compile_cache.enable()
    if args.shards is not None:
        sharded_demo(args)
    elif args.wal_dir is None:
        batch_demo()  # the original smoke: no flags, no durability
    else:
        durable_demo(args)

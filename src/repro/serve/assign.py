"""Online DBSCAN-predict against a frozen snapshot (DESIGN.md §10).

``assign`` answers the serving question: for a batch of *new* points,
which cluster of the frozen corpus does each belong to? Semantics are the
standard DBSCAN predict rule, made deterministic the same way the batch
path is: a query joins the cluster of its minimum-label ε-reachable core
point; with no core point in range it is noise (−1). Border/noise corpus
points never attract queries (they don't define reachability), which is
why the snapshot's payload plane carries ``label if core else INT32_MAX``.

One call is one batched device program: validate (NaN/Inf/shape/dtype are
rejected *before* quantization — DESIGN.md §12.4), bucket-pad (scheduler),
quantize with the corpus plan, Morton-sort, bisect window bounds against
the frozen sorted codes, and run the ``cross_sweep`` kernel over per-tile
slabs. The per-tile slab capacity starts at the corpus plan's and regrows
(double, retrace, retry — the same overflow posture as the distributed
driver's capacities) in the rare case a query tile's window outgrows it;
the grown value sticks for the snapshot so steady-state serving never
regrows twice. The regrow loop is bounded (``max_regrow``, default the
engine-wide ``MAX_SLAB_REGROW``): exhaustion raises a structured
:class:`~repro.serve.resilience.CapacityError` naming the final slab
capacity, and every retry is surfaced in the scheduler's telemetry.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import jax
import numpy as np

from .. import obs
from ..core import neighbors as nb
from . import faults
from .resilience import next_slab, validate_points
from .scheduler import BucketScheduler
from .snapshot import ClusterSnapshot

INT_MAX = np.iinfo(np.int32).max


class AssignResult(NamedTuple):
    labels: np.ndarray   # (nq,) int32: joined cluster label, or -1 noise
    counts: np.ndarray   # (nq,) int32: ε-neighbors in the corpus
    dist: np.ndarray     # (nq,) f32: distance to the nearest deciding core
    #                      point (+inf for noise) — attachment confidence
    bucket: int          # padded batch size served (telemetry)
    staleness: int = 0   # delta points ingested but not visible to this
    #                      answer (the delta watermark; 0 = fully fresh)
    degraded: bool = False  # True when the serving session is running on
    #                      a circuit-broken (failing/stalled) compaction —
    #                      staleness is no longer bounded by the policy
    partial: bool = False   # sharded tier only: at least one routed shard
    #                      contributed nothing (quarantined / leg
    #                      exhausted). Its neighbors are MISSING, never
    #                      invented: the min/sum merge makes counts a
    #                      lower bound and labels/dist upper bounds of
    #                      the full answer (DESIGN.md §16.3)
    shards: dict | None = None  # sharded tier only: shard_id →
    #                      router.LegStatus (serving replica, per-shard
    #                      staleness/degraded, retries/failovers/hedged,
    #                      missing flag) for every shard the query batch
    #                      routed to


def assign(snapshot: ClusterSnapshot, queries, *,
           scheduler: BucketScheduler | None = None,
           block_q: int = 256, backend: str | None = None,
           max_regrow: int = nb.MAX_SLAB_REGROW,
           req: int | None = None) -> AssignResult:
    """Label ``queries`` (nq, 3) against the frozen ``snapshot``.

    Pass a shared ``scheduler`` from a serving loop to get bucketed shape
    reuse and latency/recompile telemetry across calls; without one an
    ephemeral scheduler still buckets (so one-off calls hit the same jit
    cache keys a loop would). ``req`` is the request id the call's spans
    carry (``repro.obs``); a fresh one when not given. The padded queries
    go to the snapshot's own device (a sharded tier's leg, its shard's).
    """
    sched = scheduler or BucketScheduler(min_bucket=block_q)
    req = obs.next_req() if req is None else req
    with obs.span("serve.prepare", req=req) as sp:
        q_np = validate_points(queries, name="queries")
        q_pad, nq = sched.pad(q_np)
        sp.set_metadata(nq=nq, bucket=q_pad.shape[0])
        if q_pad.shape[0] % block_q:
            raise ValueError(
                f"bucket {q_pad.shape[0]} not a multiple of "
                f"block_q={block_q}; set the scheduler's min_bucket to a "
                "multiple of block_q")
        q_dev = jax.device_put(q_pad, snapshot.codes.sharding)
    spec = snapshot.spec
    eps2 = float(snapshot.eps) ** 2

    slab = snapshot.slab
    t0 = time.perf_counter()

    def trace_key(s):
        # the full identity of one compiled cross-query program: plan +
        # shape bucket + slab + tile + backend — a scheduler shared across
        # snapshots must not conflate their traces
        return (spec, q_pad.shape[0], s, block_q, backend)

    for attempt in range(max_regrow + 1):
        with obs.span("serve.run", req=req, slab=slab, attempt=attempt):
            fn = nb._csr_cross_query_fn(spec, eps2, backend, slab, block_q)
            counts, minroot, mind2, overflow = fn(
                snapshot.codes, snapshot.cands, snapshot.croot_sorted,
                q_dev, np.int32(nq))
            jax.block_until_ready(counts)
            overflowed = bool(overflow)
        if not overflowed and not faults.fire("serve.assign.overflow"):
            break
        with obs.span("serve.regrow", req=req) as sp:
            # the overflowed attempt compiled
            sched.note_trace(trace_key(slab))
            sched.note_regrow()
            slab = next_slab(slab, spec.n_cand, attempt=attempt,
                             max_regrow=max_regrow, what="cross-query")
            sp.set_metadata(slab=slab)
            snapshot.note_slab(slab)
    sched.note_call(trace_key(slab), time.perf_counter() - t0)

    with obs.span("serve.finish", req=req):
        counts = np.asarray(counts)[:nq]
        minroot = np.asarray(minroot)[:nq]
        mind2 = np.asarray(mind2)[:nq]
        labels = np.where(minroot != INT_MAX, minroot, -1).astype(np.int32)
        return AssignResult(labels=labels, counts=counts,
                            dist=np.sqrt(mind2, dtype=np.float32),
                            bucket=q_pad.shape[0])

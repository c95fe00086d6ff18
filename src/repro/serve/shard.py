"""Morton-range corpus shards: one clustered snapshot split into
per-device serveable pieces (DESIGN.md §15).

The CSR corpus is already Morton-sorted, so range partitioning is a
*split*, not a rebuild: shard ``j`` is a contiguous run of sorted
positions, cut at count-balanced quantiles and then **snapped forward to
the end of the enclosing code run** so one cell code never spans two
shards. That snap is the routing exactness precondition: a query's
ε-dilated window cell is either empty in the global corpus or its whole
occupied run lies inside exactly one shard, so the split can tabulate,
once, which shards each cell's window reaches, and a query's route is
one lookup of its own cell (§15.2).
Snapping can collapse adjacent cuts (e.g. an all-duplicates corpus has
one code), in which case the effective shard count is smaller than
requested — never zero-point shards.

**Why shards are split from a global clustering instead of clustered
independently:** DBSCAN labels are a global connectivity property — core
status needs neighbor counts across the boundary and clusters span it.
Each shard therefore carries the *global* clustering's outputs sliced to
its rows (core flags, ε-counts) but re-labeled with **shard-local dense
ids**: the s-th smallest global cluster label present in the shard maps
to local id s. ``np.unique`` builds that table ascending, so the remap
is *monotone* — the ``cross_sweep`` scatter-min over shard-local payload
ids, mapped back through the table and min-merged across shards, picks
the same element a global scatter-min would, which is what makes the
router's gather bit-identical to the single-snapshot answer (§15.3, the
merge invariant the parity suite gates).

Each shard gets its *own* :class:`~repro.core.grid.CSRGridSpec` planned
from its local extent/occupancy (jit traces per plan, and a dense
shard's slab no longer sizes a sparse shard's sweep); routing, by
contrast, always quantizes with the **tier plan** — the global
snapshot's side/origin/bits — because ownership is defined over tier
codes.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from ..core import grid as grid_mod
from ..kernels import ref as kref
from .snapshot import ClusterSnapshot

INT_MAX = np.iinfo(np.int32).max


def target_tag(shard_id: int, replica: int | None = 0) -> str:
    """Canonical name of one serving target — ``shard-00j/rK`` (or the
    shard-scoped ``shard-00j`` when ``replica`` is None) — shared by
    health reports, fault-site tags, and error messages so a chaos test
    can address the exact copy it means to kill."""
    sid = f"shard-{shard_id:03d}"
    return sid if replica is None else f"{sid}/r{replica}"


def _window_offsets(dims: int) -> np.ndarray:
    rng = (-1, 0, 1)
    return np.asarray(
        [(dx, dy, dz) for dx in rng for dy in rng
         for dz in (rng if dims == 3 else (0,))], np.int32)


@dataclasses.dataclass(frozen=True)
class ShardPart:
    """One shard of a split snapshot (module docstring).

    ``snapshot`` is a fully self-contained :class:`ClusterSnapshot` —
    same pytree, same ``assign``/ingest machinery — except its ``labels``
    / ``croot_sorted`` payload plane carries shard-local dense ids;
    ``label_table`` maps them back to the global label space.
    """
    shard_id: int
    snapshot: ClusterSnapshot
    label_table: np.ndarray   # (n_local_clusters,) int32, ascending global
    #                           labels; local id s -> label_table[s]
    code_lo: int              # owned tier-code range [code_lo, code_hi)
    code_hi: int
    orig_index: np.ndarray    # (n_j,) int64: shard row -> global corpus row

    @property
    def n(self) -> int:
        return self.snapshot.n

    @property
    def probe_point(self) -> np.ndarray:
        """(1, 3) f32 heartbeat query: the shard's own first corpus point.
        Probing with a point the shard *owns* keeps the window non-empty
        (a real slab walk, not a trivially-empty one) and the 1-point
        batch pads to the scheduler's smallest bucket, which warmup has
        already traced — a probe can never recompile (§16.1)."""
        return np.asarray(self.snapshot.points[:1], np.float32)


def tier_cells(points_np, side: float, origin: tuple, dims: int,
               bits: int) -> np.ndarray:
    """(m, 3) int32 tier cells on the host: ``grid.csr_cells`` in numpy,
    operation for operation in float32 (subtract the float32 origin,
    multiply by ``float32(1 / side)``, floor, clip, zero z in 2-D), so a
    query lands in the cell the device quantizes it to. The clip runs
    before the integer cast: equal to XLA's saturating cast then clip for
    every finite input, where numpy's own cast would wrap."""
    p = np.asarray(points_np, np.float32)
    with np.errstate(over="ignore"):  # past float32's range: ±inf, clipped
        f = np.floor((p - np.asarray(origin, np.float32))
                     * np.float32(1.0 / side))
    c = np.clip(f, 0, (1 << bits) - 3).astype(np.int32)
    if dims == 2:
        c[:, 2] = 0
    return c


def morton_cells(codes: np.ndarray, dims: int) -> np.ndarray:
    """(m, 3) int64 cells of (m,) Morton codes, the inverse of
    ``ref.morton_encode_ref`` (z = 0 in 2-D)."""
    k = np.asarray(codes, np.int64)
    out = np.zeros((len(k), 3), np.int64)
    if dims == 2:
        out[:, 0], out[:, 1] = _compact2(k), _compact2(k >> 1)
    else:
        out[:, 0], out[:, 1], out[:, 2] = (_compact3(k), _compact3(k >> 1),
                                           _compact3(k >> 2))
    return out


def _compact2(x):
    x = x & 0x55555555
    x = (x | (x >> 1)) & 0x33333333
    x = (x | (x >> 2)) & 0x0F0F0F0F
    x = (x | (x >> 4)) & 0x00FF00FF
    return (x | (x >> 8)) & 0x7FFF


def _compact3(x):
    x = x & 0x09249249
    x = (x | (x >> 2)) & 0x030C30C3
    x = (x | (x >> 4)) & 0x0300F00F
    x = (x | (x >> 8)) & 0x030000FF
    return (x | (x >> 16)) & 0x3FF


def _reach_table(codes: np.ndarray, pos_cuts: np.ndarray, dims: int,
                 bits: int) -> tuple:
    """Which shards each query cell's window reaches (§15.2).

    A query in cell ``q`` can touch an occupied cell ``o`` only if
    ``o = q + off`` for a window offset ``off``, so every occupied ``o``
    adds its shard to the keys ``q = o - off`` that are real cells
    (``[0, 2^bits - 3]`` on every axis). That is exact against the
    clipped window the engine sweeps: a neighbour clipped to 0 is the
    query cell's own row, one clipped to ``2^bits - 2`` is never occupied.
    Returns the sorted unique keys (T,) int32 and a (T, K) bool reach;
    T is at most 9 (2-D) or 27 (3-D) times the occupied cells.
    """
    occ, first = np.unique(codes, return_index=True)
    # an occupied run never straddles a cut: its start position names
    # the one shard holding it
    sid = np.searchsorted(pos_cuts, first, side="right") - 1
    offs = _window_offsets(dims).astype(np.int64)
    q = morton_cells(occ, dims)[None, :, :] - offs[:, None, :]
    real = ((q >= 0) & (q <= (1 << bits) - 3)).all(axis=2)
    keys, inv = np.unique(kref.morton_encode_ref(q[real], dims=dims),
                          return_inverse=True)
    reach = np.zeros((len(keys), len(pos_cuts) - 1), bool)
    reach[inv, np.broadcast_to(sid, real.shape)[real]] = True
    return keys, reach


@dataclasses.dataclass(frozen=True)
class ShardMap:
    """Routing structure: tier quantization, snapped cuts and the reach
    table built from them (§15.2).

    Owns no shard data. Both routing questions reduce to one
    ``searchsorted`` of each point's own tier cell code:

    * **ingest** (``owner_of``): against the inner cut codes — cut ranges
      partition the whole code space, so every point has exactly one
      owning shard;
    * **query** (``window_shards``): against the reach table's keys,
      every cell whose ε-dilated window holds an occupied run, each with
      the shards owning those runs (:func:`_reach_table`) — an occupied
      run lies wholly inside one shard (cuts are snapped to code
      boundaries), and only shards owning occupied window runs can hold
      an ε-neighbor, so the routed set is exact, typically 1–2 shards.
    """
    side: float
    origin: tuple
    dims: int
    bits: int
    pos_cuts: np.ndarray    # (K+1,) int64 cut positions in sorted order
    cut_codes: np.ndarray   # (K+1,) int64: shard j owns [cut[j], cut[j+1])
    reach_keys: np.ndarray  # (T,) int32 sorted codes of reaching cells
    reach: np.ndarray       # (T, K) bool: key t's window holds shard j

    @property
    def n_shards(self) -> int:
        return len(self.pos_cuts) - 1

    def _codes(self, points_np) -> np.ndarray:
        # the kernels' reference encoder, on host arrays: numpy in and out
        cells = tier_cells(points_np, self.side, self.origin, self.dims,
                           self.bits)
        return kref.morton_encode_ref(cells, dims=self.dims)

    def owner_of(self, points_np) -> np.ndarray:
        """(m,) int32 owning shard per point — the ingest route."""
        return np.searchsorted(self.cut_codes[1:-1], self._codes(points_np),
                               side="right").astype(np.int32)

    def window_shards(self, points_np) -> np.ndarray:
        """(m, K) bool: shard j may hold an ε-neighbor of point i.

        The window is ``grid._csr_window_bounds``'s cell enumeration (±1
        per axis around the clipped tier cell, neighbors clipped to the
        engine's cap): every corpus point within ε of a query sits in one
        of these window cells — tier side ≥ ε, the same argument that
        makes the engine's window sweep exact — so a shard outside this
        mask cannot contribute a count, a minroot, or a mind2. A query
        whose cell is not in the table routes nowhere.
        """
        codes = self._codes(points_np)
        i = np.minimum(np.searchsorted(self.reach_keys, codes),
                       len(self.reach_keys) - 1)
        hit = self.reach_keys[i] == codes
        return self.reach[i] & hit[:, None]


def _build_part(shard_id: int, pts: np.ndarray, labels_global: np.ndarray,
                core: np.ndarray, counts: np.ndarray, rows: np.ndarray,
                code_lo: int, code_hi: int, tier_spec, eps: float,
                min_pts: int, engine: str) -> ShardPart:
    # shard-local dense labels: ascending table -> monotone remap (the
    # §15.3 merge invariant; module docstring)
    table = np.unique(labels_global[labels_global >= 0]).astype(np.int32)
    local = np.where(labels_global >= 0,
                     np.searchsorted(table, labels_global),
                     -1).astype(np.int32)
    spec_j = grid_mod.plan_csr_grid(pts, eps, dims=tier_spec.dims,
                                    chunk=tier_spec.chunk,
                                    block_k=tier_spec.block_k)
    pts_dev = jnp.asarray(pts, jnp.float32)
    g = grid_mod.build_csr_grid(pts_dev, spec_j)
    if bool(g.overflow):
        raise AssertionError(
            f"shard {shard_id} CSR build overflowed its planned slab — "
            "plan/build disagree on quantization")
    local_dev = jnp.asarray(local)
    core_dev = jnp.asarray(core)
    labels_s = local_dev[g.order]
    core_s = core_dev[g.order]
    croot_sorted = jnp.full((spec_j.n_cand,), INT_MAX, jnp.int32).at[
        :spec_j.n].set(jnp.where(core_s, labels_s, INT_MAX)
                       .astype(jnp.int32))
    snap = ClusterSnapshot(
        points=pts_dev, labels=local_dev, core=core_dev,
        counts=jnp.asarray(counts), order=g.order, cands=g.cands,
        codes=g.codes, croot_sorted=croot_sorted, spec=spec_j,
        engine=engine, eps=float(eps), min_pts=int(min_pts))
    return ShardPart(shard_id=shard_id, snapshot=snap, label_table=table,
                     code_lo=int(code_lo), code_hi=int(code_hi),
                     orig_index=rows)


def split_snapshot(snapshot: ClusterSnapshot,
                   n_shards: int) -> Tuple[ShardMap, list]:
    """Split a (globally clustered) snapshot into Morton-range shards.

    Returns ``(shard_map, [ShardPart, ...])``. Cuts are count-balanced
    quantiles of the sorted corpus, snapped forward to code-run
    boundaries; collapsed cuts are dropped, so ``len(parts)`` may be
    smaller than ``n_shards`` (and is never zero — every part holds at
    least one point). Shard rows keep ascending global-corpus order, so
    tier compaction can reassemble the canonical corpus order exactly.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    spec = snapshot.spec
    codes = np.asarray(snapshot.codes).astype(np.int64)
    order = np.asarray(snapshot.order).astype(np.int64)
    n = len(codes)
    k_req = min(max(1, int(n_shards)), n)
    pos_cuts = [0]
    for j in range(1, k_req):
        p = (j * n) // k_req
        # snap forward past the run of the code at the quantile position
        p = int(np.searchsorted(codes, codes[min(p, n - 1)], side="right"))
        if pos_cuts[-1] < p < n:
            pos_cuts.append(p)
    pos_cuts.append(n)
    pos_cuts = np.asarray(pos_cuts, np.int64)
    K = len(pos_cuts) - 1
    cut_codes = np.empty(K + 1, np.int64)
    cut_codes[0] = 0
    for j in range(1, K):
        cut_codes[j] = codes[pos_cuts[j]]
    cut_codes[K] = np.iinfo(np.int64).max

    labels_g = np.asarray(snapshot.labels)
    core_g = np.asarray(snapshot.core)
    counts_g = np.asarray(snapshot.counts)
    pts_g = np.asarray(snapshot.points)
    parts = []
    for j in range(K):
        rows = np.sort(order[pos_cuts[j]:pos_cuts[j + 1]])
        parts.append(_build_part(
            j, pts_g[rows], labels_g[rows], core_g[rows], counts_g[rows],
            rows, int(cut_codes[j]), int(cut_codes[j + 1]), spec,
            float(snapshot.eps), int(snapshot.min_pts), snapshot.engine))
    reach_keys, reach = _reach_table(codes, pos_cuts, spec.dims, spec.bits)
    smap = ShardMap(side=spec.side, origin=tuple(spec.origin),
                    dims=spec.dims, bits=spec.bits, pos_cuts=pos_cuts,
                    cut_codes=cut_codes, reach_keys=reach_keys, reach=reach)
    return smap, parts

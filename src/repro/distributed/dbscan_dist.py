"""Distributed DBSCAN over a device mesh (shard_map) — DESIGN.md §4.

Pipeline (all shapes static, masked; capacities are config with overflow
flags — production restarts with larger capacity on overflow, exactly like
regrowing a hash table):

  1. **Quantile slabs**: global histogram (psum) over the widest coordinate
     picks D−1 boundaries so each device owns ≈ n/D points.
  2. **Redistribution**: fixed-capacity ``all_to_all`` — each point packs
     (x, y, z, global_id) to its slab owner.
  3. **ε-halo exchange**: points within ε of a slab face go to that
     neighbor via ``ppermute`` (ghost zone) — the only data any neighbor
     ever needs, so communication is O(boundary), not O(volume).
  4. **Local sweep**: the paper's fused primitive (count + min-core-root)
     over owned ∪ halo candidates — exact, since every ε-neighbor of an
     owned point is owned or in the halo.
  5. **Local union-find**: hooking + pointer jumping on the local subgraph.
  6. **Cross-device label rounds**: halo labels are re-exchanged and each
     local component takes the min label over its members (segment-min);
     converges in O(slab-diameter of the cluster graph) rounds — clusters
     rarely span many ε-wide slabs, and each round is one tiny permute.
  7. Labels return to the original order via a masked scatter by global id.

Fault tolerance: every round's (labels, parent) is a single small array —
the driver checkpoints it; restart resumes at the label-round loop (the
structure is a cheap rebuild). Elastic: capacities are per-device-count
configs; a restart on fewer devices re-plans and re-partitions from the
input shard (distributed/elastic.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core import engines
from ..core.dbscan import DBSCANResult

INT_MAX = jnp.iinfo(jnp.int32).max
BIG = jnp.float32(1e30)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    send_factor: float = 4.0     # per-(src,dst) capacity = factor · n/D²
    halo_factor: float = 0.5     # halo capacity = factor · n/D
    hist_bins: int = 512
    max_label_rounds: int = 32
    query_chunk: int = 1024
    local_uf_rounds: int = 32
    # local sweep engine, resolved through the engine registry
    # (``engines.register_local_engine``): "csr" = cell-sorted CSR slabs
    # (DESIGN.md §3, O(n·local window) work, O(n) memory), "grid" = per-slab
    # hash grid (O(n·27·C) work), "bvh" = wavefront LBVH traversal
    # (DESIGN.md §9), "brute" = all-pairs tiles (O((n/D)²))
    local_engine: str = "grid"
    grid_capacity: int = 32      # points per hash bucket (regrows on overflow)
    grid_occupancy: int = 8      # target points per bucket → table size
    csr_chunk: int = 256         # CSR queries per sweep tile
    csr_block: int = 512         # CSR slab granularity (elements)
    csr_slab: int = 4096         # CSR per-tile slab capacity (regrows on
    #                              overflow, capped by the candidate count)
    bvh_frontier_factor: float = 8.0  # wavefront queue = factor · n_cand
    #                              entries (regrows on overflow)


def _sweep_local(queries, cands, croot, eps2, chunk):
    """Chunked fused sweep (counts, min-core-root) — local RT primitive."""
    nq = queries.shape[0]
    n_pad = ((nq + chunk - 1) // chunk) * chunk
    qp = jnp.pad(queries, ((0, n_pad - nq), (0, 0)), constant_values=BIG)

    def body(qq):
        d2 = sum((qq[:, None, k] - cands[None, :, k]) ** 2 for k in range(3))
        hit = d2 <= eps2
        counts = hit.sum(axis=1).astype(jnp.int32)
        mr = jnp.where(hit, croot[None, :], INT_MAX).min(axis=1)
        return counts, mr

    counts, mr = jax.lax.map(body, qp.reshape(-1, chunk, 3))
    return counts.reshape(-1)[:nq], mr.reshape(-1)[:nq].astype(jnp.int32)


def make_grid_sweep(cand_pts, eps: float, n_cand: int, cfg: DistConfig):
    """Per-slab hash-grid sweep (§Perf C1): build once over the candidate
    set, answer fused (counts, min-core-root) queries in O(q · 27·C).

    Returns (sweep(queries, croot) -> (counts, minroot), overflow flag).
    Padded candidates (coords BIG) are clamped to a far cell; any capacity
    overflow (incl. hash collisions with the far cell) raises the regrow
    flag — correctness is never silently lost.
    """
    from ..core import grid as grid_mod

    table = 1 << max(6, int(np.ceil(np.log2(max(
        n_cand / cfg.grid_occupancy, 1.0)))))
    spec = grid_mod.GridSpec(side=eps, origin=(0.0, 0.0, 0.0),
                             table_size=table, capacity=cfg.grid_capacity,
                             dims=3)
    real = cand_pts[:, 0] < 1e29
    # every padded point gets its OWN far cell (2·side apart), strictly
    # beyond the real data's extent so pad cells can never alias real cells
    real_max = jnp.max(jnp.where(real, cand_pts[:, 0], -jnp.inf))
    far = jnp.where(jnp.isfinite(real_max), real_max, 0.0) + 16.0 * eps
    idx = jnp.arange(n_cand, dtype=jnp.float32)
    pad_x = far + 2.0 * eps * idx
    pts_c = jnp.where(real[:, None], cand_pts,
                      jnp.stack([pad_x, jnp.zeros_like(pad_x),
                                 jnp.zeros_like(pad_x)], axis=1))
    grid = grid_mod.build_grid(pts_c, spec)
    placed_real = (grid.valid & (grid.points[..., 0] < far)).sum()
    overflow = placed_real < real.sum()
    gcroot_template = grid.index  # (H, C) original local indices, -1 pad
    eps2 = jnp.float32(eps * eps)
    off, cap = spec.n_offsets, spec.capacity

    def sweep(queries, croot):
        nq = queries.shape[0]
        chunk = min(cfg.query_chunk, nq)
        n_pad = ((nq + chunk - 1) // chunk) * chunk
        qp = jnp.pad(queries, ((0, n_pad - nq), (0, 0)), constant_values=BIG)
        bkt, cvalid = grid_mod.neighbor_buckets(qp, spec)
        gcroot = jnp.where(grid.valid, croot[jnp.clip(gcroot_template, 0)],
                           INT_MAX)

        def body(args):
            qq, bb, vv = args
            cand = grid.points[bb].reshape(chunk, off * cap, 3)
            rr = jnp.where(vv[..., None], gcroot[bb],
                           INT_MAX).reshape(chunk, off * cap)
            d2 = sum((qq[:, None, k] - cand[:, :, k]) ** 2 for k in range(3))
            hit = d2 <= eps2
            return (hit.sum(axis=1).astype(jnp.int32),
                    jnp.where(hit, rr, INT_MAX).min(axis=1))

        counts, mr = jax.lax.map(
            body, (qp.reshape(-1, chunk, 3),
                   bkt.reshape(-1, chunk, off),
                   cvalid.reshape(-1, chunk, off)))
        return counts.reshape(-1)[:nq], mr.reshape(-1)[:nq].astype(jnp.int32)

    return sweep, overflow


def make_csr_sweep(cand_pts, eps: float, n_cand: int, cfg: DistConfig):
    """Per-slab cell-sorted CSR sweep (DESIGN.md §3): sort the candidate set
    by Morton cell code once, then answer fused (counts, min-core-root)
    queries for *all* candidates against per-tile contiguous slabs sized by
    actual local occupancy.

    Unlike the host-planned single-device engine, the slab capacity here is
    config (``cfg.csr_slab``) — static shapes inside shard_map — with an
    overflow flag that triggers the driver's regrow-and-restart, exactly like
    the hash grid's bucket capacity. Padded candidates (coords BIG) sort to a
    reserved top Morton cell that no real query window can reach.

    Returns (sweep(croot) -> (counts, minroot) over all local candidate
    indices, overflow flag).
    """
    from ..core import grid as grid_mod
    from ..kernels import ops
    from ..kernels import ref as kref

    bits = 10
    eps2 = jnp.float32(eps * eps)
    real = cand_pts[:, 0] < 1e29
    lo3 = jnp.min(jnp.where(real[:, None], cand_pts, jnp.inf), axis=0)
    hi3 = jnp.max(jnp.where(real[:, None], cand_pts, -jnp.inf), axis=0)
    lo3 = jnp.where(jnp.isfinite(lo3), lo3, 0.0)
    hi3 = jnp.where(jnp.isfinite(hi3), hi3, 0.0)
    max_cells = (1 << bits) - 2
    # side grows past ε only when the extent saturates the Morton bit budget
    side = jnp.maximum(jnp.float32(eps),
                       jnp.max(hi3 - lo3) / (max_cells - 1) * (1 + 1e-5))
    cells = grid_mod.csr_cells(cand_pts, side, lo3, 3, bits)
    cells = jnp.where(real[:, None], cells, (1 << bits) - 1)  # pads→top cell
    codes = kref.morton_encode_ref(cells, dims=3)
    order = jnp.argsort(codes).astype(jnp.int32)
    spts = cand_pts[order]
    lo, hi = grid_mod._csr_window_bounds(codes[order], cells[order], 3, bits)
    # padded queries demand nothing (lo=n / hi=0 drop out of the tile
    # min/max; their top-cell window never matches an occupied run anyway)
    real_s = real[order]
    lo = jnp.where(real_s, lo, n_cand)
    hi = jnp.where(real_s, hi, 0)

    chunk, bk = cfg.csr_chunk, cfg.csr_block
    slab = min(-(-cfg.csr_slab // bk) * bk, -(-n_cand // bk) * bk)
    T = -(-n_cand // chunk)
    n_csr = max(-(-n_cand // bk) * bk, slab)
    start, nblk, overflow = grid_mod.tile_slabs(
        lo, hi, n_cand, n_tiles=T, chunk=chunk, block_k=bk, slab=slab,
        n_cand=n_csr)
    pad_q = jnp.minimum(jnp.arange(T * chunk, dtype=jnp.int32), n_cand - 1)
    q_sorted = spts[pad_q]
    cands = jnp.full((n_csr, 3), BIG, jnp.float32).at[:n_cand].set(spts)
    cands_planar = cands.T

    def sweep(croot):
        croot_pad = jnp.full((n_csr,), INT_MAX, jnp.int32) \
            .at[:n_cand].set(croot[order])
        counts_p, m_p = ops.csr_sweep(
            q_sorted, cands_planar, croot_pad, start, nblk,
            eps2, slab=slab, block_q=chunk, block_k=bk)
        counts = jnp.zeros((n_cand,), jnp.int32).at[order].set(
            counts_p[:n_cand])
        m = jnp.full((n_cand,), INT_MAX, jnp.int32).at[order].set(
            m_p[:n_cand])
        return counts, m

    return sweep, overflow


def make_bvh_wave_sweep(cand_pts, eps: float, n_cand: int, cfg: DistConfig):
    """Per-slab wavefront LBVH sweep (DESIGN.md §9): build the Karras tree
    over the candidate set once, then answer fused (counts, min-core-root)
    queries for all candidates by level-synchronous frontier traversal.

    The frontier capacity is config (``cfg.bvh_frontier_factor`` ·
    ``n_cand`` — static shapes inside shard_map) with an overflow flag that
    triggers the driver's regrow-and-restart, like every other local
    capacity. Traversal structure depends only on geometry, so one
    payload-free probe at build time certifies every later sweep. Padded
    candidates (+BIG) quantize to the top Morton cell (the build quantizes
    over the *real* extent) and, as queries, carry a −BIG sentinel so they
    fall out of the frontier at the first level.

    Returns (sweep(croot) -> (counts, minroot) over all local candidate
    indices, overflow flag).
    """
    from ..core import bvh as bvh_mod

    real = cand_pts[:, 0] < 1e29
    lo3 = jnp.min(jnp.where(real[:, None], cand_pts, jnp.inf), axis=0)
    hi3 = jnp.max(jnp.where(real[:, None], cand_pts, -jnp.inf), axis=0)
    lo3 = jnp.where(jnp.isfinite(lo3), lo3, 0.0)
    hi3 = jnp.where(jnp.isfinite(hi3), hi3, 0.0)
    bvh = bvh_mod.build_bvh(cand_pts, dims=3, lo=lo3, hi=hi3)
    capacity = -(-int(cfg.bvh_frontier_factor * n_cand) // 512) * 512
    queries = jnp.where(real[:, None], cand_pts, -BIG)
    kw = dict(eps=float(eps), eps2=float(eps) ** 2, capacity=capacity)
    _, _, overflow, _ = bvh_mod.wavefront_sweep(
        bvh, queries, jnp.full((n_cand,), INT_MAX, jnp.int32),
        stop_on_overflow=True, **kw)

    def sweep(croot):
        counts, m, _, _ = bvh_mod.wavefront_sweep(bvh, queries,
                                                  croot[bvh.order], **kw)
        return counts, m

    return sweep, overflow


# --- local-engine registry builders (DESIGN.md §9): each returns
# (sweep_all, sweep_own, overflow) where ``sweep_all(croot)`` answers the
# fused query for every local candidate and ``sweep_own`` for the owned
# prefix only. ---


def _local_brute(cand_pts, eps, n_cand, p_own, cfg):
    eps2 = jnp.float32(eps * eps)

    def sweep_all(croot):
        return _sweep_local(cand_pts, cand_pts, croot, eps2, cfg.query_chunk)

    def sweep_own(croot):
        return _sweep_local(cand_pts[:p_own], cand_pts, croot, eps2,
                            cfg.query_chunk)

    return sweep_all, sweep_own, jnp.bool_(False)


def _local_csr(cand_pts, eps, n_cand, p_own, cfg):
    sweep_all, overflow = make_csr_sweep(cand_pts, eps, n_cand, cfg)

    def sweep_own(croot):
        counts, m = sweep_all(croot)
        return counts[:p_own], m[:p_own]

    return sweep_all, sweep_own, overflow


def _local_grid(cand_pts, eps, n_cand, p_own, cfg):
    gsweep, overflow = make_grid_sweep(cand_pts, eps, n_cand, cfg)

    def sweep_all(croot):
        return gsweep(cand_pts, croot)

    def sweep_own(croot):
        return gsweep(cand_pts[:p_own], croot)

    return sweep_all, sweep_own, overflow


def _local_bvh(cand_pts, eps, n_cand, p_own, cfg):
    sweep_all, overflow = make_bvh_wave_sweep(cand_pts, eps, n_cand, cfg)

    def sweep_own(croot):
        counts, m = sweep_all(croot)
        return counts[:p_own], m[:p_own]

    return sweep_all, sweep_own, overflow


engines.register_local_engine("brute", _local_brute)
engines.register_local_engine("csr", _local_csr)
engines.register_local_engine("grid", _local_grid)
engines.register_local_engine("bvh", _local_bvh)


def _local_components(sweep_all, core, n_local, rounds):
    """Local-index union-find over the device's points (owned ∪ halo)."""
    croot0 = jnp.arange(n_local, dtype=jnp.int32)

    def round_body(state):
        parent, _, it = state
        root = _compress(parent)
        croot = jnp.where(core, root, INT_MAX)
        _, m = sweep_all(croot)
        tgt = jnp.minimum(jnp.where(core, m, root), root)
        p2 = root.at[root].min(tgt)
        p2 = _compress(p2)
        return p2, jnp.any(p2 != root), it + 1

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < rounds)

    parent, _, _ = jax.lax.while_loop(
        cond, round_body, (croot0, jnp.bool_(True), jnp.int32(0)))
    return _compress(parent)


def _compress(parent):
    def cond(st):
        p, ch = st
        return ch

    def body(st):
        p, _ = st
        p2 = p[p]
        return p2, jnp.any(p2 != p)

    p, _ = jax.lax.while_loop(cond, body, (parent, jnp.bool_(True)))
    return p


def _pack_by_dest(values, dest, n_dest, cap):
    """values (n, w), dest (n,) -> (n_dest, cap, w) padded buffer + overflow.

    Padding rows carry coords=BIG and payload id 0 (invalid); overflowing
    ranks are routed out of bounds (mode="drop") so they can never clobber
    a valid slot.
    """
    n = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    ds = dest[order]
    start = jnp.searchsorted(ds, jnp.arange(n_dest, dtype=ds.dtype))
    rank = jnp.arange(n, dtype=jnp.int32) - start[ds].astype(jnp.int32)
    fill = jnp.asarray([BIG] * (values.shape[1] - 1) + [0.0], values.dtype)
    buf = jnp.broadcast_to(fill, (n_dest, cap, values.shape[1]))
    ok = rank < cap
    buf = buf.at[ds, jnp.where(ok, rank, cap)].set(values[order], mode="drop")
    overflow = jnp.any(~ok)
    return buf, overflow


def _select_first_k(values, pred, k):
    """First-k rows of ``values`` where pred; invalid rows get coords=BIG
    and payload id 0 (so downstream validity checks see them as empty)."""
    key = jnp.where(pred, jnp.arange(pred.shape[0], dtype=jnp.int32), INT_MAX)
    order = jnp.argsort(key)[:k]
    sel = values[order]
    valid = key[order] != INT_MAX
    fill = jnp.asarray([BIG, BIG, BIG, 0.0], values.dtype)
    return jnp.where(valid[:, None], sel, fill)


def make_distributed_dbscan(mesh, axis_names, n: int, eps: float,
                            min_pts: int, cfg: DistConfig = DistConfig()):
    """Build a jitted distributed DBSCAN for fixed (n, ε, minPts, mesh).

    Returns fn(points (n,3)) -> (labels (n,) int32, core (n,) bool,
    overflow flag). Points must be sharded (or shardable) over
    ``axis_names`` on dim 0.
    """
    D = 1
    for a in axis_names:
        D *= mesh.shape[a]
    ax = axis_names if len(axis_names) > 1 else axis_names[0]
    n_local = n // D
    cap_send = max(8, int(cfg.send_factor * n / (D * D)))
    p_own = D * cap_send
    cap_halo = max(8, int(cfg.halo_factor * n / D))

    def impl(pts_local):
        pts_local = pts_local.reshape(n_local, 3)
        dev = jax.lax.axis_index(ax)
        gidx = dev * n_local + jnp.arange(n_local, dtype=jnp.int32)

        # ---- 1. quantile slab boundaries over the widest coordinate ----
        lo = jax.lax.pmin(pts_local.min(axis=0), ax)
        hi = jax.lax.pmax(pts_local.max(axis=0), ax)
        widest = jnp.argmax(hi - lo)
        c = jnp.take_along_axis(pts_local, widest[None, None].repeat(
            n_local, 0), axis=1)[:, 0]
        clo = lo[widest]
        chi = jnp.maximum(hi[widest], clo + 1e-6)
        b = cfg.hist_bins
        bin_of = jnp.clip(((c - clo) / (chi - clo) * b).astype(jnp.int32),
                          0, b - 1)
        hist = jnp.zeros((b,), jnp.int32).at[bin_of].add(1)
        hist = jax.lax.psum(hist, ax)
        cum = jnp.cumsum(hist)
        targets = (jnp.arange(1, D, dtype=jnp.float32) / D) * n
        cut_bins = jnp.searchsorted(cum.astype(jnp.float32), targets)
        cuts = clo + (cut_bins.astype(jnp.float32) + 1) / b * (chi - clo)

        # ---- 2. fixed-capacity all_to_all redistribution ----
        dest = jnp.searchsorted(cuts, c).astype(jnp.int32)
        payload = jnp.concatenate(
            [pts_local, gidx[:, None].astype(jnp.float32) + 1.0], axis=1)
        send, ovf1 = _pack_by_dest(payload, dest, D, cap_send)
        recv = jax.lax.all_to_all(send.reshape(D * cap_send, 4), ax, 0, 0,
                                  tiled=True)
        owned = recv.reshape(p_own, 4)
        own_valid = owned[:, 3] > 0
        own_pts = jnp.where(own_valid[:, None], owned[:, :3], BIG)
        own_gidx = (owned[:, 3] - 1.0).astype(jnp.int32)

        # ---- 3. ε-halo exchange with slab neighbors ----
        my_lo = jnp.where(dev > 0, cuts[jnp.maximum(dev - 1, 0)], -BIG)
        my_hi = jnp.where(dev < D - 1, cuts[jnp.minimum(dev, D - 2)], BIG)
        oc = jnp.take_along_axis(own_pts, widest[None, None].repeat(
            p_own, 0), axis=1)[:, 0]
        near_lo = own_valid & (oc <= my_lo + eps)
        near_hi = own_valid & (oc >= my_hi - eps)
        send_l = _select_first_k(owned, near_lo, cap_halo)
        send_r = _select_first_k(owned, near_hi, cap_halo)
        ovf2 = (near_lo.sum() > cap_halo) | (near_hi.sum() > cap_halo)
        perm_r = [(i, (i + 1) % D) for i in range(D)]
        perm_l = [(i, (i - 1) % D) for i in range(D)]
        halo_from_l = jax.lax.ppermute(send_r, ax, perm_r)  # left nbr's right face
        halo_from_r = jax.lax.ppermute(send_l, ax, perm_l)  # right nbr's left face
        halo = jnp.concatenate([halo_from_l, halo_from_r], axis=0)
        halo_valid = halo[:, 3] > 0
        halo_pts = jnp.where(halo_valid[:, None], halo[:, :3], BIG)

        cand_pts = jnp.concatenate([own_pts, halo_pts], axis=0)
        n_cand = cand_pts.shape[0]

        # local engine dispatch through the one registry table (DESIGN.md
        # §9): CSR slabs / hash grid / wavefront BVH / brute tiles.
        build_local = engines.get_local_engine(cfg.local_engine)
        sweep_all, sweep_own, ovf3 = build_local(cand_pts, eps, n_cand,
                                                 p_own, cfg)

        # ---- 4. stage 1: core identification (fused sweep) ----
        nocore = jnp.full((n_cand,), INT_MAX, jnp.int32)
        counts, _ = sweep_own(nocore)
        core_own = own_valid & (counts >= min_pts)

        # halo core flags come from their owners via the same permutes
        core_l = _select_core_flags(core_own, near_lo, cap_halo)
        core_r = _select_core_flags(core_own, near_hi, cap_halo)
        halo_core = jnp.concatenate([
            jax.lax.ppermute(core_r, ax, perm_r),
            jax.lax.ppermute(core_l, ax, perm_l)], axis=0)
        core_all = jnp.concatenate([core_own, halo_core & halo_valid])

        # ---- 5. local components over owned ∪ halo ----
        root_local = _local_components(sweep_all, core_all, n_cand,
                                       cfg.local_uf_rounds)

        # ---- 6. cross-device label rounds ----
        halo_gidx = (halo[:, 3] - 1.0).astype(jnp.int32)
        label = jnp.where(core_own, own_gidx, INT_MAX)

        def lbl_round(state):
            label, _, it = state
            lab_l = _select_labels(label, near_lo, cap_halo)
            lab_r = _select_labels(label, near_hi, cap_halo)
            halo_lab = jnp.concatenate([
                jax.lax.ppermute(lab_r, ax, perm_r),
                jax.lax.ppermute(lab_l, ax, perm_l)], axis=0)
            all_lab = jnp.concatenate([label, halo_lab])
            all_lab = jnp.where(core_all, all_lab, INT_MAX)
            seg_min = jnp.full((n_cand,), INT_MAX, jnp.int32) \
                .at[root_local].min(all_lab)
            new_all = jnp.where(core_all, seg_min[root_local], INT_MAX)
            new = new_all[:p_own]
            changed = jax.lax.psum(
                jnp.any(new != label).astype(jnp.int32), ax) > 0
            return new, changed, it + 1

        def lbl_cond(state):
            _, changed, it = state
            return jnp.logical_and(changed, it < cfg.max_label_rounds)

        label, _, rounds = jax.lax.while_loop(
            lbl_cond, lbl_round, (label, jnp.bool_(True), jnp.int32(0)))

        # ---- border attachment: min core-neighbor label ----
        lab_l = _select_labels(label, near_lo, cap_halo)
        lab_r = _select_labels(label, near_hi, cap_halo)
        halo_lab = jnp.concatenate([
            jax.lax.ppermute(lab_r, ax, perm_r),
            jax.lax.ppermute(lab_l, ax, perm_l)], axis=0)
        all_lab = jnp.concatenate([label, halo_lab])
        croot = jnp.where(core_all, all_lab, INT_MAX)
        _, m = sweep_own(croot)
        final = jnp.where(core_own, label,
                          jnp.where(m != INT_MAX, m, -1)).astype(jnp.int32)
        final = jnp.where(own_valid, final, -1)

        overflow = jax.lax.psum(
            (ovf1 | ovf2 | ovf3).astype(jnp.int32), ax) > 0

        # ---- 7. return to original order ----
        out_lab = jnp.full((n,), -1, jnp.int32).at[
            jnp.where(own_valid, own_gidx, n)].set(final, mode="drop")
        out_core = jnp.zeros((n,), bool).at[
            jnp.where(own_valid, own_gidx, n)].set(core_own, mode="drop")
        out_lab = jax.lax.psum(jnp.where(out_lab == -1, 0, out_lab + 1), ax) - 1
        out_core = jax.lax.psum(out_core.astype(jnp.int32), ax) > 0
        return out_lab, out_core, overflow, rounds

        # NOTE on step 7: each global slot is written by exactly one device
        # (-1 ↦ 0 elsewhere), so the psum is a segmented "select the owner".

    def _select_core_flags(core, pred, k):
        key = jnp.where(pred, jnp.arange(pred.shape[0], dtype=jnp.int32),
                        INT_MAX)
        order = jnp.argsort(key)[:k]
        valid = key[order] != INT_MAX
        return core[order] & valid

    def _select_labels(label, pred, k):
        key = jnp.where(pred, jnp.arange(pred.shape[0], dtype=jnp.int32),
                        INT_MAX)
        order = jnp.argsort(key)[:k]
        valid = key[order] != INT_MAX
        return jnp.where(valid, label[order], INT_MAX)

    spec = P(ax)
    fn = shard_map(impl, mesh=mesh, in_specs=(spec,),
                   out_specs=(P(), P(), P(), P()), check_vma=False)
    return jax.jit(fn)


def dbscan_distributed(points, eps: float, min_pts: int, mesh,
                       axis_names=("data",), cfg: DistConfig = DistConfig(),
                       max_regrows: int = 3):
    """Convenience driver. points (n,3) host array, n divisible by D.

    On capacity overflow the buffers are regrown (×2) and the run restarts —
    the production semantics for the static-shape/elastic trade-off (same
    pattern as regrowing the grid capacity, DESIGN.md §4).
    """
    points = jnp.asarray(points, jnp.float32)
    n = points.shape[0]
    for _ in range(max_regrows + 1):
        fn = make_distributed_dbscan(mesh, tuple(axis_names), n, eps,
                                     min_pts, cfg)
        labels, core, overflow, rounds = fn(points)
        if not bool(overflow):
            counts = jnp.zeros((n,), jnp.int32)  # counts live device-side
            return DBSCANResult(labels=labels, core=core, counts=counts,
                                n_rounds=int(rounds))
        cfg = dataclasses.replace(cfg, send_factor=cfg.send_factor * 2,
                                  halo_factor=cfg.halo_factor * 2,
                                  grid_capacity=cfg.grid_capacity * 2,
                                  csr_slab=cfg.csr_slab * 2,
                                  bvh_frontier_factor=cfg.bvh_frontier_factor * 2)
    raise RuntimeError(
        "distributed DBSCAN capacity overflow after regrows — data too "
        "skewed for the configured budget")

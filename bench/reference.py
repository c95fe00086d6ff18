"""Plain DBSCAN and DBSCAN-predict: the answers the benchmark checks against.

Independent of the program under test: it imports nothing of ``repro`` and
takes nothing the program made. It computes the semantics every
configuration states:

* ε-test: float32 squared distance accumulated in coordinate order,
  ``((dx*dx + dy*dy) + dz*dz) <= float32(eps**2)``, self included;
* core: at least ``min_pts`` ε-neighbours;
* cluster label: the smallest original index of a core point in the
  connected component of core points (core–core pairs within ε);
* border: a non-core point takes the smallest label among the core points
  within ε of it, and is noise (−1) without one;
* predict (``assign``): a query's count is over all corpus points within ε,
  its label the smallest label among core points within ε (−1 without),
  its distance the square root of the least squared distance to those core
  points (+inf without).

How: points are bucketed into square sub-cells of side
``h = eps * (1 + MARGIN) / K`` over the configuration's ``dims`` axes, with
``K = floor(2 * sqrt(dims)) + 1``. Two facts make the shortcuts exact,
with a relative margin of ``MARGIN`` that float32 rounding (~1e-7) cannot
cross:

* points in sub-cells that differ by at most 1 along every axis are within
  ``2 * h * sqrt(dims) < eps`` of each other, so they are always neighbours;
* points in sub-cells that differ by more than ``K`` along some axis are
  more than ``K * h > eps`` apart, so they never are.

Every pair that neither fact decides is tested with the float32 formula
above. Core points of one sub-cell are thus always connected; components
are taken over sub-cells (``scipy.sparse.csgraph``).

The host (numpy) finds the pairs; the ε-tests themselves run in
``jax.numpy`` on the default device, so that the reference rounds as that
device's float32 unit does (whether a multiply and an add are fused differs
between platforms, and flips a pair that lies within an ulp of ε).
``predict`` is a blocked brute force over the whole corpus there.
"""
from __future__ import annotations

import functools
import itertools
import math
from typing import NamedTuple

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

INT_MAX = np.iinfo(np.int32).max
MARGIN = 1e-4
PAIR_CHUNK = 1 << 22  # candidate pairs expanded and tested at once
MIN_CHUNK = 1 << 14   # smallest padded test batch (one compile per size)


def eps2_f32(eps: float) -> np.float32:
    return np.float32(float(eps) ** 2)


@functools.lru_cache(maxsize=None)
def _jitted(name: str):
    """The reference's two device programs, built once per process."""
    import jax
    import jax.numpy as jnp

    def d2(a, b):
        acc = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]),
                        jnp.float32)
        for k in range(3):
            d = a[..., k] - b[..., k]
            acc = acc + d * d
        return acc

    def hits(a, b, ia, ib, eps2):
        return d2(a[ia], b[ib]) <= eps2

    def predict(qb, c, lab, eps2):
        def body(carry, blk):
            cnt, best, d2min = carry
            cb, lb = blk
            acc = d2(qb[:, None, :], cb[None, :, :])
            hit = acc <= eps2
            core_hit = hit & (lb[None, :] != INT_MAX)
            cnt = cnt + hit.sum(axis=1, dtype=jnp.int32)
            best = jnp.minimum(best, jnp.where(core_hit, lb[None, :],
                                               INT_MAX).min(axis=1))
            d2min = jnp.minimum(d2min, jnp.where(core_hit, acc,
                                                 jnp.inf).min(axis=1))
            return (cnt, best, d2min), None

        n = qb.shape[0]
        init = (jnp.zeros(n, jnp.int32), jnp.full(n, INT_MAX, jnp.int32),
                jnp.full(n, jnp.inf, jnp.float32))
        block = qb.shape[0]
        out, _ = jax.lax.scan(body, init, (c.reshape(-1, block, 3),
                                           lab.reshape(-1, block)))
        return out

    return jax.jit({"hits": hits, "predict": predict}[name])


class _Tests:
    """ε-tests of index pairs between two point sets held on the device."""

    def __init__(self, eps: float, a: np.ndarray, b: np.ndarray):
        import jax.numpy as jnp
        self._jnp, self.eps2 = jnp, eps2_f32(eps)
        self.a = jnp.asarray(np.asarray(a, np.float32))
        self.b = jnp.asarray(np.asarray(b, np.float32))

    def __call__(self, ia: np.ndarray, ib: np.ndarray) -> np.ndarray:
        """Is ``a[ia[k]]`` within eps of ``b[ib[k]]``, for each k."""
        m = len(ia)
        size = MIN_CHUNK
        while size < m:
            size *= 2
        pa = np.zeros(size, np.int32)
        pb = np.zeros(size, np.int32)
        pa[:m], pb[:m] = ia, ib
        out = _jitted("hits")(self.a, self.b, self._jnp.asarray(pa),
                              self._jnp.asarray(pb), self.eps2)
        return np.asarray(out)[:m]


class Clustering(NamedTuple):
    labels: np.ndarray   # (n,) int32
    core: np.ndarray     # (n,) bool
    counts: np.ndarray   # (n,) int32; -1 where not computed


class _Cells:
    """Points sorted by sub-cell key, with per-sub-cell ranges."""

    def __init__(self, pts: np.ndarray, eps: float, dims: int):
        self.dims = dims
        self.K = int(math.floor(2 * math.sqrt(dims))) + 1
        h = float(eps) * (1 + MARGIN) / self.K
        x = pts[:, :dims].astype(np.float64)
        ci = np.floor((x - x.min(axis=0)) / h).astype(np.int64) + self.K + 1
        ext = ci.max(axis=0) + self.K + 2
        # row-major key, last axis fastest: a run of sub-cells along the
        # last axis is one contiguous key range
        self.strides = np.ones(dims, np.int64)
        for a in range(dims - 2, -1, -1):
            self.strides[a] = self.strides[a + 1] * ext[a + 1]
        key = ci @ self.strides
        self.order = np.argsort(key, kind="stable")
        self.key = key[self.order]            # sorted point keys
        self.ucell, self.start, self.size = np.unique(
            self.key, return_index=True, return_counts=True)
        self.cell_of = np.searchsorted(self.ucell, key)  # per original point

    def offsets(self, reach: int) -> np.ndarray:
        """Key offsets of all sub-cells within ``reach`` along every axis."""
        r = range(-reach, reach + 1)
        return np.array([np.dot(o, self.strides)
                         for o in itertools.product(r, repeat=self.dims)],
                        np.int64)

    def row_offsets(self) -> np.ndarray:
        """Key offsets of the rows (all axes but the last) of the reach-K
        block; each row spans last-axis offsets -K..K."""
        r = range(-self.K, self.K + 1)
        return np.array([np.dot(o, self.strides[:-1])
                         for o in itertools.product(r, repeat=self.dims - 1)],
                        np.int64)

    def find(self, keys: np.ndarray) -> np.ndarray:
        """Index into ``ucell`` of each key, or -1 where no such sub-cell."""
        i = np.searchsorted(self.ucell, keys)
        i = np.minimum(i, len(self.ucell) - 1)
        return np.where(self.ucell[i] == keys, i, -1)


def _pairs(lo: np.ndarray, hi: np.ndarray):
    """Expand ranges [lo_i, hi_i) into (i, j) pairs, in chunks."""
    lens = np.maximum(hi - lo, 0)
    i0 = 0
    n = len(lo)
    while i0 < n:
        csum = np.cumsum(lens[i0:])
        i1 = i0 + max(1, int(np.searchsorted(csum, PAIR_CHUNK, "right")))
        ln = lens[i0:i1]
        tot = int(ln.sum())
        if tot:
            qi = np.repeat(np.arange(i0, i1), ln)
            first = np.cumsum(ln) - ln
            cj = np.repeat(lo[i0:i1] - first, ln) + np.arange(tot)
            yield qi, cj
        i0 = i1


def _block_pairs(cells: _Cells, sorted_keys: np.ndarray, qkeys: np.ndarray):
    """(query index, index into ``sorted_keys``) for every query and every
    entry whose sub-cell lies in the query's reach-K block."""
    K = cells.K
    for row in cells.row_offsets():
        lo = np.searchsorted(sorted_keys, qkeys + row - K, "left")
        hi = np.searchsorted(sorted_keys, qkeys + row + K, "right")
        yield from _pairs(lo, hi)


def dbscan(points: np.ndarray, eps: float, min_pts: int, dims: int, *,
           exact_counts: bool = True) -> Clustering:
    """DBSCAN of ``points`` (n, 3) float32 by the semantics above.

    With ``exact_counts`` every count is computed; without it only the
    counts that decide core membership are, the others read -1.
    """
    pts = np.asarray(points, np.float32)
    n = len(pts)
    cells = _Cells(pts, eps, dims)
    test = _Tests(eps, pts, pts)
    pkey = cells.key[np.argsort(cells.order)]  # per original point

    # --- core: the 3^d block around a point is all within eps ---
    near = np.zeros(len(cells.ucell), np.int64)
    for off in cells.offsets(1):
        j = cells.find(cells.ucell + off)
        near += np.where(j >= 0, cells.size[np.maximum(j, 0)], 0)
    counts = np.full(n, -1, np.int64)
    todo = np.arange(n) if exact_counts else \
        np.flatnonzero(near[cells.cell_of] < min_pts)
    if len(todo):
        c = np.zeros(len(todo), np.int64)
        for qi, cj in _block_pairs(cells, cells.key, pkey[todo]):
            hit = test(todo[qi], cells.order[cj])
            c += np.bincount(qi[hit], minlength=len(todo))
        counts[todo] = c
    core = np.where(counts >= 0, counts >= min_pts,
                    near[cells.cell_of] >= min_pts)

    # --- components over sub-cells holding core points ---
    core_ids = np.flatnonzero(core)
    labels = np.full(n, -1, np.int64)
    if len(core_ids):
        ccell = cells.cell_of[core_ids]
        live = np.unique(ccell)                     # sub-cells with a core
        node = np.full(len(cells.ucell), -1, np.int64)
        node[live] = np.arange(len(live))
        ukey = cells.ucell[live]

        def neighbours(offs):
            src, dst = [], []
            for off in offs:
                if off == 0:
                    continue
                j = cells.find(ukey + off)
                ok = j >= 0
                ok[ok] = node[j[ok]] >= 0
                src.append(np.flatnonzero(ok))
                dst.append(node[j[ok]])
            return np.concatenate(src), np.concatenate(dst)

        def components(src, dst):
            m = coo_matrix((np.ones(len(src), np.int8), (src, dst)),
                           shape=(len(live), len(live)))
            return connected_components(m, directed=False)[1]

        s1, d1 = neighbours(cells.offsets(1))
        comp = components(s1, d1)
        near1 = set(cells.offsets(1).tolist())
        far = [o for o in cells.offsets(cells.K).tolist()
               if o not in near1 and o > 0]
        s2, d2 = neighbours(far)
        undecided = comp[s2] != comp[d2]
        s2, d2 = s2[undecided], d2[undecided]
        linked = _core_links(test, core_ids, ccell, live, s2, d2)
        comp = components(np.concatenate([s1, s2[linked]]),
                          np.concatenate([d1, d2[linked]]))
        root = comp[node[ccell]]
        comp_min = np.full(comp.max() + 1, INT_MAX, np.int64)
        np.minimum.at(comp_min, root, core_ids)
        labels[core_ids] = comp_min[root]

        # --- border attachment: smallest label of a core point in range ---
        border = np.flatnonzero(~core)
        if len(border):
            corder = np.argsort(pkey[core_ids], kind="stable")
            ckey = pkey[core_ids][corder]
            cidx = core_ids[corder]
            clab = labels[cidx]
            best = np.full(len(border), INT_MAX, np.int64)
            for qi, cj in _block_pairs(cells, ckey, pkey[border]):
                hit = test(border[qi], cidx[cj])
                np.minimum.at(best, qi[hit], clab[cj[hit]])
            labels[border] = np.where(best < INT_MAX, best, -1)
    return Clustering(labels=labels.astype(np.int32), core=core,
                      counts=counts.astype(np.int32))


def _core_links(test, core_ids, ccell, live, src, dst):
    """For each sub-cell pair (src, dst) (indices into ``live``): is some
    core point of one within eps of some core point of the other?"""
    linked = np.zeros(len(src), bool)
    if not len(src):
        return linked
    order = np.argsort(ccell, kind="stable")
    cidx = core_ids[order]
    first = np.searchsorted(ccell[order], live)
    size = np.searchsorted(ccell[order], live, "right") - first
    # expand (pair, point of src) then (.., point of dst)
    for pi, a in _pairs(first[src], first[src] + size[src]):
        b_lo, b_n = first[dst[pi]], size[dst[pi]]
        for qi, cj in _pairs(b_lo, b_lo + b_n):
            hit = test(cidx[a[qi]], cidx[cj])
            linked[pi[qi[hit]]] = True
    return linked


class Predictions(NamedTuple):
    labels: np.ndarray   # (q,) int32
    counts: np.ndarray   # (q,) int32
    dist: np.ndarray     # (q,) float32


def predict(corpus: np.ndarray, clustering: Clustering, queries: np.ndarray,
            eps: float, *, block: int = 4096) -> Predictions:
    """DBSCAN-predict of ``queries`` against a clustered ``corpus``: blocked
    brute force over every corpus point, in ``jax.numpy`` on the default
    device."""
    import jax.numpy as jnp

    eps2 = eps2_f32(eps)
    n, q = len(corpus), len(queries)
    npad = -(-n // block) * block
    qpad = -(-max(q, 1) // block) * block
    big = np.float32(1e30)
    c = np.full((npad, 3), big, np.float32)
    c[:n] = corpus
    lab = np.full(npad, INT_MAX, np.int32)
    lab[:n] = np.where(clustering.core, clustering.labels, INT_MAX)
    qq = np.full((qpad, 3), big, np.float32)
    qq[:q] = queries
    c_dev, lab_dev = jnp.asarray(c), jnp.asarray(lab)
    outs = [_jitted("predict")(jnp.asarray(qq[i:i + block]), c_dev, lab_dev,
                               eps2)
            for i in range(0, qpad, block)]
    cnt, best, d2min = (np.concatenate([np.asarray(o[k]) for o in outs])[:q]
                        for k in range(3))
    return Predictions(labels=np.where(best != INT_MAX, best, -1)
                       .astype(np.int32),
                       counts=cnt.astype(np.int32),
                       dist=np.sqrt(d2min, dtype=np.float32))


def to_bf16(points: np.ndarray) -> np.ndarray:
    """Coordinates rounded to bfloat16 and widened back: the control's
    storage precision, one step below the configurations' float32."""
    import ml_dtypes
    return np.asarray(points, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)

"""BVH wavefront level-kernel roofline model (DESIGN.md §13).

``bvh_level_report`` turns the calibrated per-level frontier sizes into
per-level bytes, FLOPs and launch counts; ``figures.roofline`` prints it
(``PYTHONPATH=src python -m benchmarks.run --only roofline``).
"""
from __future__ import annotations


# Static traffic/arithmetic model of one batched expand entry, used to turn
# the measured per-level frontier sizes into a per-level roofline row. The
# byte model charges what the level loop actually streams per (block, node)
# entry; boxes are charged at the *stored* prune precision (2 B for bf16)
# because halving box bandwidth is the point of the mixed-precision prune.

def _entry_bytes(batch: int, dims: int, prune_dtype: str) -> int:
    pb = 2 if prune_dtype == "bf16" else 4
    q = dims * batch * 4                 # query planar slab (f32)
    boxes = 2 * dims * pb                # dlo + dhi at stored precision
    pt = dims * 4                        # leaf point (f32)
    meta = 3 * 4                         # croot / nmin / leaf
    bound = batch * 4                    # per-query termination bound
    out = 2 * batch * 4 + 4              # hit + minroot + push
    return q + boxes + pt + meta + bound + out


def _entry_flops(batch: int, dims: int) -> int:
    # per (query, dim): 2 cmp (inside) + sub/mul/add (d2) = 5; plus the
    # per-query ε² compare, payload compare and hit/push reductions ≈ 4
    return batch * (5 * dims + 4)


def bvh_level_report(levels, *, batch: int, dims: int, tile: int,
                     prune_dtype: str = "bf16"):
    """Per-level bytes / FLOPs / intensity for the batched wavefront kernel.

    ``levels`` is the calibrated per-level frontier history (entries alive
    at the top of each level, ``repro.core.bvh.wavefront_levels``). One
    kernel launch covers ``tile`` entries, so launches = ceil(f / tile) —
    the launch-count row is the telemetry ROADMAP's "launch/DMA-bound"
    hypothesis needs."""
    eb = _entry_bytes(batch, dims, prune_dtype)
    ef = _entry_flops(batch, dims)
    rows = []
    for lvl, f in enumerate(int(x) for x in levels):
        rows.append({
            "level": lvl,
            "entries": f,
            "launches": -(-f // tile) if f else 0,
            "bytes": f * eb,
            "flops": f * ef,
            "intensity": ef / eb,
        })
    tot_b = sum(r["bytes"] for r in rows)
    tot_f = sum(r["flops"] for r in rows)
    total = {
        "levels": len(rows),
        "entries": sum(r["entries"] for r in rows),
        "launches": sum(r["launches"] for r in rows),
        "bytes": tot_b,
        "flops": tot_f,
        "intensity": tot_f / max(tot_b, 1),
    }
    return {"levels": rows, "total": total,
            "entry_bytes": eb, "entry_flops": ef}

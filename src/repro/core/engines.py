"""Capability-based neighbor-engine registry (DESIGN.md §9).

One dispatch table for every place that used to hand-roll an ``if engine ==``
chain: ``make_engine`` (single-device builds), ``find_neighbors`` (neighbor
lists), ``dbscan``'s round-driver selection, and the distributed driver's
``local_engine`` choice. An engine registers once, advertising what it can
do through the fields of the :class:`Engine` it builds:

  * ``sweep``        — the fused (counts, min-core-root) primitive every
                       engine must provide (DESIGN.md §2);
  * ``sweep_sorted`` + ``order`` — optional sorted-layout fast path; its
                       presence (not the engine's *name*) is what opts a run
                       into ``dbscan``'s on-device sorted hooking loop
                       (DESIGN.md §5);
  * ``neighbors``    — optional neighbor-*list* capability backing
                       ``find_neighbors`` (DESIGN.md §6);
  * ``query``        — optional cross-corpus query capability (DESIGN.md
                       §10): answer fresh points against the built (frozen)
                       structure — the serving subsystem refuses engines
                       whose ``EngineSpec.capabilities`` lack it *before*
                       paying for a build;
  * ``sweep_counts`` — optional counts-only stage-1 sweep in sorted layout
                       (skips the payload plane the stage discards). For
                       engines whose payload sweep early-terminates on the
                       payload (the wavefront BVH, DESIGN.md §13.2) this is
                       not merely an optimization: ``sweep_sorted`` counts
                       are *partial* under termination, so stage 1 must use
                       this exact traversal — ``dbscan`` auto-prefers it
                       whenever advertised;
  * ``sweep_frontier`` — optional frontier-compacted stage-2 rounds
                       (DESIGN.md §11): a :class:`FrontierPlan` that lets
                       ``dbscan(hook_loop="frontier")`` re-sweep only the
                       tiles that can still produce a union;
  * ``meta``         — the engine's static plan (GridSpec / CSRGridSpec /
                       WavefrontSpec), exposed for benchmarks and reuse.

Builders receive the normalized ``(points, eps)`` pair plus the standard
keyword surface (``backend``, ``chunk``, ``dims``, ``spec``) and any
engine-specific extras forwarded verbatim by :func:`make_engine`.

A second, smaller table serves the distributed driver: *local* engines
build per-shard sweeps inside ``shard_map`` from a candidate buffer and the
:class:`~repro.distributed.dbscan_dist.DistConfig` capacities (static
shapes, overflow-flag regrow) — see :func:`register_local_engine`.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from .. import obs


class FrontierPlan(NamedTuple):
    """The ``sweep_frontier`` capability (DESIGN.md §11): everything the
    frontier round driver needs to re-sweep only the live tiles of a
    hooking round.

    ``n_tiles`` sizes the driver's pending-tile carry; the two callables
    keep all layout knowledge (slab bounds, block math, compaction) inside
    the engine:

      * ``sweep(state, croot_s, qroot_s, changed_s, pending) ->
        (minroot, pending', n_live)`` — one frontier round: fold
        ``changed_s`` (payload changed since last round, sorted layout)
        into ``pending``, intersect with the live-seam test, sweep exactly
        the live tiles, clear them from ``pending``. Parked tiles return
        INT32_MAX rows (a provable no-op for the hook — §11).
      * ``border(state, croot_s, core_s) -> minroot`` — the final border-
        attachment sweep, restricted to tiles that have both a core
        candidate in the slab and a non-core query (the only consumers of
        ``minroot`` there).
    """
    n_tiles: int
    sweep: Callable
    border: Callable


class Engine(NamedTuple):
    """A built neighbor-search engine; fields double as capability flags."""
    name: str
    state: Any                       # pytree of device arrays
    sweep: Callable                  # (state, core, root) -> (counts, minroot)
    meta: Any = None                 # static plan (GridSpec / CSRGridSpec / …)
    sweep_sorted: Callable | None = None  # (state, croot_sorted) ->
    #                                  (counts, minroot), all in sorted layout
    order: Any = None                # (n,) sorted position -> original index
    neighbors: Callable | None = None  # (state, k_max=) -> (idx, counts)
    query: Callable | None = None    # cross-corpus queries (serving,
    #                                  DESIGN.md §10): (state, queries, nq,
    #                                  croot_sorted, slab=, block_q=) ->
    #                                  (counts, minroot, mind2, overflow)
    sweep_counts: Callable | None = None  # (state) -> counts, sorted layout:
    #                                  stage-1 core identification without
    #                                  the payload plane (counts-only mode)
    sweep_frontier: FrontierPlan | None = None  # frontier-compacted stage-2
    #                                  rounds (DESIGN.md §11); presence opts
    #                                  dbscan's hook_loop="frontier" in


class EngineSpec(NamedTuple):
    """Registry entry: how to build an engine, a one-line description, and
    the capabilities the built Engine will advertise (so callers can reject
    a mismatched engine *before* paying for its build)."""
    name: str
    build: Callable                  # (points, eps, **kw) -> Engine
    doc: str = ""
    capabilities: frozenset = frozenset()


_REGISTRY: dict[str, EngineSpec] = {}
_LOCAL_REGISTRY: dict[str, Callable] = {}


def register_engine(name: str, build_fn: Callable, *, doc: str = "",
                    capabilities=()) -> None:
    """Register (or re-register) a single-device engine builder."""
    _REGISTRY[name] = EngineSpec(name=name, build=build_fn, doc=doc,
                                 capabilities=frozenset(capabilities))


def register_local_engine(name: str, build_fn: Callable) -> None:
    """Register a distributed *local* engine builder with signature
    ``build(cand_pts, eps, n_cand, p_own, cfg) -> (sweep_all, sweep_own,
    overflow)`` where ``sweep_*(croot) -> (counts, minroot)`` answer the
    fused query for all local candidates / the owned prefix respectively,
    and ``overflow`` raises the driver's regrow-and-restart flag."""
    _LOCAL_REGISTRY[name] = build_fn


def _ensure_builtin() -> None:
    # The built-in providers register themselves at import; imported lazily
    # here (not at module top) so the registry module stays import-cycle
    # free — neighbors/bvh both import *us* for Engine.
    from . import bvh as _bvh            # noqa: F401  (bvh, bvh-stack)
    from . import neighbors as _nb       # noqa: F401  (brute, grid, grid-hash)
    from ..distributed import dbscan_dist as _dd  # noqa: F401 (local engines)


def get_engine_spec(name: str) -> EngineSpec:
    _ensure_builtin()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered engines: "
            f"{', '.join(available_engines())}") from None


def available_engines() -> tuple:
    _ensure_builtin()
    return tuple(sorted(_REGISTRY))


def get_local_engine(name: str) -> Callable:
    _ensure_builtin()
    try:
        return _LOCAL_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown local_engine {name!r}; registered local engines: "
            f"{', '.join(available_local_engines())}") from None


def available_local_engines() -> tuple:
    _ensure_builtin()
    return tuple(sorted(_LOCAL_REGISTRY))


def make_engine(points, eps: float, *, engine: str = "grid",
                backend: str | None = None, chunk: int = 2048,
                dims: int | None = None, spec=None, **extra) -> Engine:
    """Build an engine over ``points`` (n, 3) for radius ``eps``.

    The structure build (cell sort / grid hashing / BVH build + frontier
    calibration) happens here — this is the phase the paper's §V-D breaks
    out as "BVH build time"; a trace marks it as the ``repro.engine.build``
    span, apart from the sweeps. ``spec`` lets callers
    reuse a plan (GridSpec for ``grid-hash``, CSRGridSpec for ``grid``,
    WavefrontSpec for ``bvh``); a reused spec must come from the same
    dataset — builds raise if its capacities don't fit. ``chunk`` tiles the
    brute/grid-hash/bvh-stack query sweeps; the CSR engine's tile size is
    planned (``plan_csr_grid(chunk=...)`` via ``spec``). Engine-specific
    keywords (e.g. ``early_stop=`` / ``stack=`` for ``bvh-stack``) are
    forwarded to the builder.
    """
    entry = get_engine_spec(engine)
    with obs.span("engine.build", engine=engine, n=len(points)):
        points = jnp.asarray(points, jnp.float32)
        eng = entry.build(points, float(eps), backend=backend, chunk=chunk,
                          dims=dims, spec=spec, **extra)
        jax.block_until_ready(eng.state)
    return eng

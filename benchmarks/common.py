"""Shared benchmark machinery.

Where the numbers come from: on a TPU the kernels run compiled (backend
``kernel``) and ``seconds`` is chip wall time; on the CPU they run through
the pure-jnp ``ref`` oracles (or ``interpret``), which checks the *claims
structure* — which system wins where, how work grows, where build time
dominates — but says nothing about speed on the chip. The rows do not yet
name the device they ran on (ROADMAP A1). Every benchmark prints
``name,case,seconds,derived`` CSV rows and returns them.

Timing: one warmup call (compile + engine build), then ``repeats`` timed
runs, median reported. Engine *build* time is timed separately where the
figure calls for it (paper §V-D).
"""
from __future__ import annotations

import time
from typing import Callable, List

import jax


def timeit(fn: Callable, *, repeats: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(_leaves(fn()))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(_leaves(fn()))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _leaves(x):
    return [l for l in jax.tree.leaves(x) if hasattr(l, "block_until_ready")]


# When set (by benchmarks.run --json), every Reporter.row also appends a
# machine-readable record here; run.py dumps the list to BENCH_sweep.json so
# the perf trajectory is diffable across PRs.
JSON_SINK: list | None = None


class Reporter:
    def __init__(self, name: str):
        self.name = name
        self.rows: List[str] = []

    def row(self, case: str, seconds: float, derived: str = "",
            engine: str = ""):
        line = f"{self.name},{case},{seconds:.6f},{derived}"
        print(line, flush=True)
        self.rows.append(line)
        if JSON_SINK is not None:
            JSON_SINK.append({"name": self.name, "case": case,
                              "seconds": seconds, "derived": derived,
                              "engine": engine})

    def note(self, case: str, text: str):
        line = f"{self.name},{case},NA,{text}"
        print(line, flush=True)
        self.rows.append(line)
        if JSON_SINK is not None:
            JSON_SINK.append({"name": self.name, "case": case,
                              "seconds": None, "derived": text,
                              "engine": ""})

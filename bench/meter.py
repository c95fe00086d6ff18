"""Compile counting and peak device memory (copied from ``chip_smoke.py``).

``Meter`` sums XLA backend compile time and count from JAX's monitoring
events; ``peak_bytes`` reads the allocator's peak of one device.
"""
from __future__ import annotations

import time

import jax
import jax.monitoring


class Meter:
    """XLA backend compile time and count; spans read differences of the
    running totals."""
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

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def peak_bytes(devices) -> int | None:
    """The largest ``peak_bytes_in_use`` over ``devices``, or None where the
    backend does not report it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None

"""The program's own trace spans (``repro.<name>``).

Spans are ``jax.profiler.TraceAnnotation``s, so under a ``jax.profiler``
trace they land in the same ``.xplane.pb`` as the device operations and on
their clock; a span's keyword args become stats on its trace event. With
the profiler off a span costs about a microsecond. A span adds no host
sync, no device read and no copy: what it encloses is the code as it runs
untraced.

Every span of one ``assign`` shares a ``req`` id from :func:`next_req`.
Importing this module installs a ``gc.callbacks`` hook that marks each
generation-2 collection as a ``repro.gc`` span.
"""
from __future__ import annotations

import gc
import itertools

from jax.profiler import TraceAnnotation

PREFIX = "repro."

_req = itertools.count(1)


def span(name: str, **args) -> TraceAnnotation:
    """The span ``repro.<name>``; ``args`` become stats on its event (add
    more inside it with ``set_metadata``)."""
    return TraceAnnotation(PREFIX + name, **args)


def next_req() -> int:
    """A fresh request id."""
    return next(_req)


_gc_open: list = []


def _on_gc(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        sp = span("gc", generation=2)
        sp.__enter__()
        _gc_open.append(sp)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


gc.callbacks.append(_on_gc)

"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The harness records one run's window with ``jax.profiler`` and marks its own
host spans with ``jax.profiler.TraceAnnotation`` names that start with
``SPAN_PREFIX``; the program marks its own with names that start with
``PROGRAM_PREFIX`` (``repro/obs.py``), their args stats on the trace event.
From the trace this module takes:

* the window: the span named ``SPAN_PREFIX + "window"``;
* the harness's spans and, apart (``Trace.program``), the program's spans
  that overlap the window, each with its stats as ``args``;
* device operations: the events of each device plane's op line, clipped to
  the window. A device plane is one whose name starts with ``/device:``.
  A trace without such a line is refused (``NoDeviceOps``), except in a
  CPU rehearsal (``host_ops=True``), where the host events that carry an
  ``hlo_op`` stat stand in as one device;
* busy time: the union of a device's operation intervals, averaged over the
  devices; idle share is one minus busy over the window;
* kernel time: the summed durations of the operations whose kernel name
  matches a pattern. An operation's kernel name is its HLO instruction name
  without the leading ``%`` and the trailing ``.N`` (``%csr_sweep.10 =
  ...`` is ``csr_sweep``); Pallas kernels are named after their jitted
  wrapper there;
* the breakdown's operations by self time: an operation's duration less
  that of the operations nested in it on the same device (a ``while``
  holds its body's operations);
* idle gaps: the intervals inside the window in which no operation ran on a
  device, each attributed to the innermost span of either kind that covers
  its midpoint (what the host was doing).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "repro."
WINDOW = SPAN_PREFIX + "window"
OP_LINES = ("XLA Ops",)


_SUFFIX = re.compile(r"\.\d+$")


class NoDeviceOps(ValueError):
    """The trace holds no device plane with an op line: its numbers would
    not be device numbers."""


def kernel_name(name: str) -> str:
    """``%csr_sweep.10 = (s32[...]) custom-call(...)`` -> ``csr_sweep``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    @property
    def kernel(self) -> str:
        return kernel_name(self.name)


@dataclasses.dataclass(frozen=True)
class Span(Event):
    """A program span: a host event with its stats as ``args``."""
    args: dict


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list, start: float, end: float) -> float:
    """Length of ``[start, end]`` that the disjoint ``merged`` covers."""
    total = 0.0
    i = max(bisect.bisect_right(merged, (start, float("inf"))) - 1, 0)
    for s, e in merged[i:]:
        if s >= end:
            break
        total += max(0.0, min(e, end) - max(s, start))
    return total


def self_times(ops: list) -> list:
    """``(event, self ns)`` for each event of one device: its duration less
    the durations of the events directly nested in it."""
    out, stack = [], []
    for ev in sorted(ops, key=lambda o: (o.start_ns, -o.end_ns)):
        while stack and stack[-1][0].end_ns <= ev.start_ns:
            out.append(tuple(stack.pop()))
        if stack and ev.end_ns <= stack[-1][0].end_ns:
            stack[-1][1] -= ev.dur_ns
        stack.append([ev, ev.dur_ns])
    out.extend(tuple(s) for s in reversed(stack))
    return out


def _event(ev) -> Event:
    return Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)


def _overlaps(ev, start_ns: float, end_ns: float) -> bool:
    return ev.end_ns > start_ns and ev.start_ns < end_ns


class Trace:
    """One traced window: device operations per device, the harness's host
    spans and the program's."""

    def __init__(self, devices: list, spans: list, program: list = ()):
        wins = [s for s in spans if s.name == WINDOW]
        if not wins:
            raise ValueError(f"trace has no {WINDOW!r} span")
        w = max(wins, key=lambda s: s.dur_ns)
        self.start_ns, self.end_ns = w.start_ns, w.end_ns
        self.spans = [s for s in spans
                      if _overlaps(s, self.start_ns, self.end_ns)]
        self.program = sorted(
            (s for s in program if _overlaps(s, self.start_ns, self.end_ns)),
            key=lambda s: s.start_ns)
        self.devices = []
        for ops in devices:
            clipped = [dataclasses.replace(
                o, start_ns=max(o.start_ns, self.start_ns),
                end_ns=min(o.end_ns, self.end_ns))
                for o in ops if _overlaps(o, self.start_ns, self.end_ns)]
            self.devices.append(clipped)
        self.busy = [merge((o.start_ns, o.end_ns) for o in ops)
                     for ops in self.devices]

    @classmethod
    def from_planes(cls, planes, host_ops: bool = False) -> "Trace":
        """The trace of ``planes``. Without a device op line it raises
        ``NoDeviceOps``, unless ``host_ops`` lets host events stand in."""
        planes = list(planes)  # ProfileData gives a one-pass iterator
        devices, spans, program = [], [], []
        hosts = [p for p in planes if p.name.startswith("/host:")]
        for plane in planes:
            if plane.name.startswith("/device:"):
                lines = {ln.name: ln for ln in plane.lines}
                ops = [_event(ev) for name in OP_LINES if name in lines
                       for ev in lines[name].events]
                if ops:
                    devices.append(ops)
        for plane in hosts:
            for line in plane.lines:
                spans += [_event(ev) for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)]
                program += [Span(ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns, dict(ev.stats))
                            for ev in line.events
                            if ev.name.startswith(PROGRAM_PREFIX)]
        if not devices and not host_ops:
            names = [p.name for p in planes]
            raise NoDeviceOps(f"no {' or '.join(OP_LINES)!r} line on a "
                              f"/device: plane; planes: {names}")
        if not devices:
            ops = [_event(ev) for plane in hosts for line in plane.lines
                   for ev in line.events
                   if any(k == "hlo_op" for k, _ in ev.stats)]
            devices = [ops] if ops else []
        return cls(devices, spans, program)

    @classmethod
    def load(cls, log_dir: str, host_ops: bool = False) -> "Trace":
        """The newest ``.xplane.pb`` under ``log_dir`` (``host_ops`` as in
        :meth:`from_planes`)."""
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        path = max(paths, key=os.path.getmtime)
        return cls.from_planes(ProfileData.from_file(path).planes,
                               host_ops=host_ops)

    # --- window arithmetic -------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def busy_s(self) -> float:
        """Union of device-operation time, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(e - s for m in self.busy for s, e in m) \
            / len(self.busy) * 1e-9

    def busy_in(self, start_ns: float, end_ns: float) -> float:
        """Device-busy seconds inside ``[start_ns, end_ns]``, averaged over
        the devices."""
        if not self.busy:
            return 0.0
        return sum(covered(m, start_ns, end_ns) for m in self.busy) \
            / len(self.busy) * 1e-9

    def op_s(self, pattern: str) -> float:
        """Summed device seconds of the operations whose kernel name
        matches ``pattern`` in full."""
        rx = re.compile(pattern)
        return sum(o.dur_ns for ops in self.devices for o in ops
                   if rx.fullmatch(o.kernel)) * 1e-9

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == SPAN_PREFIX + name]

    def program_named(self, name: str) -> list:
        """The program's spans named ``PROGRAM_PREFIX + name``."""
        return [s for s in self.program if s.name == PROGRAM_PREFIX + name]

    def per_request(self, name: str, value) -> dict:
        """``{req: sum of value(span)}`` over the program's spans named
        ``name``, by their ``req`` arg: a regrown request has two runs."""
        out: dict = {}
        for s in self.program_named(name):
            req = s.args.get("req")
            out[req] = out.get(req, 0.0) + value(s)
        return out

    # --- breakdown -----------------------------------------------------------

    def top_ops(self, k: int = 10) -> list:
        """``[[kernel name, seconds], ...]``: the operations that took most
        device self time, summed by kernel name over the devices."""
        tot: dict = {}
        for ops in self.devices:
            for o, ns in self_times(ops):
                tot[o.kernel] = tot.get(o.kernel, 0.0) + ns * 1e-9
        return [[n, s] for n, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list:
        """``[[host span, seconds], ...]``: the longest intervals with no
        device operation, over all devices, named by the innermost span,
        the harness's (without its prefix) or the program's (in full),
        covering each gap's midpoint."""
        gaps = []
        for m in self.busy:
            edges = [(self.start_ns, self.start_ns), *m,
                     (self.end_ns, self.end_ns)]
            gaps += [(b[0] - a[1], a[1], b[0])
                     for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
        out = []
        for dur, s, e in sorted(gaps, reverse=True)[:k]:
            mid = (s + e) / 2
            inner = [sp for sp in (*self.spans, *self.program)
                     if sp.start_ns <= mid <= sp.end_ns]
            name = min(inner, key=lambda sp: sp.dur_ns).name \
                if inner else "untraced"
            out.append([name[len(SPAN_PREFIX):]
                        if name.startswith(SPAN_PREFIX) else name,
                        dur * 1e-9])
        return out

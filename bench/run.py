#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``configs/<name>.json``: the deployment,
its limits for the correctness check) and a traffic mix
(``traffic/<name>.json``: parameters for the generator in ``load.py``,
including ``op``, the driver in ``ops/<op>.py`` that serves it). In order,
a run:

1. refuses a machine without a TPU, or with fewer chips than the cell asks
   for, and a set ``REPRO_KERNEL_BACKEND``, which would put another path
   than the chip's kernels in the window (exit 3, no result line);
2. makes the data from ``--seed``, builds and warms the system under test
   (``setup_s`` runs from the start of this process to here);
3. measures for ``--seconds``;
4. reads the device's peak memory, drops the program's state, checks what
   the window produced against the plain reference (``reference.py``),
   and prints each compared number beside its limit as the last lines of
   standard error, then one JSON object as the last line of standard
   output.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the metrics are
its per-layer metrics, each read by ``metrics/<name>.py`` from the trace
(``trace.py``: device operations, the harness's ``bench.`` spans and, in
``trace.program``, the program's ``repro.`` spans with their args) and the
harness's counters.

``--rehearse`` runs the cell on the CPU with interpret-mode kernels at the
configuration's and traffic's ``rehearsal`` sizes; its numbers are not
device numbers, and only there may host events stand in for the device's
in a trace. A cell on ``chips`` > 1 rehearses on as many virtual CPU
devices: the run adds ``--xla_force_host_platform_device_count=<chips>``
to ``XLA_FLAGS`` (unless a count is set there already) before JAX starts,
so it rehearses in a fresh process; where JAX is already running with
fewer devices it exits 2. ``--control`` puts the reference, computed on
bfloat16 coordinates, in the program's place; its ``correct`` must come
out false.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if not __package__:  # run as a script: import the harness as ``bench``,
    sys.path[0] = str(ROOT)  # so that its modules shadow nothing
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

EXIT_USAGE = 2
EXIT_NO_DEVICE = 3
DEVICE_COUNT_FLAG = "xla_force_host_platform_device_count"


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reader(path: pathlib.Path):
    """The ``read`` function of a per-layer metric's file."""
    name = path.name[:-len(".py")]
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration and traffic
    files, found by name."""

    def __init__(self, name: str, rehearse: bool = False,
                 root: pathlib.Path = ROOT):
        self.bench = _load_json(root / "BENCHMARK.json")
        found = [w for w in self.bench["workloads"] if w["name"] == name]
        if not found:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.workload = found[0]
        entry = [c for c in self.bench["configs"]
                 if c["name"] == self.workload["config"]][0]
        self.cfg = _load_json(root / entry["file"])
        self.dir = root / BENCH.name
        self.traffic = _load_json(self.dir / "traffic"
                                  / f"{self.workload['traffic']}.json")
        if rehearse:
            self.cfg = {**self.cfg, **self.cfg.get("rehearsal", {})}
            self.traffic = {**self.traffic,
                            **self.traffic.get("rehearsal", {})}
        self.op = importlib.import_module(f"bench.ops.{self.traffic['op']}")

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if _applies(m, self.workload["name"])]

    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"]
                if _applies(m, self.workload["name"])]

    def reader(self, metric: str):
        """The reader of a per-layer metric, ``metrics/<metric>.py``."""
        return _reader(self.dir / "metrics" / f"{metric}.py")


class Context:
    """What a driver in ``ops/`` is given: the cell's files, the run's
    arguments, the compile meter, and spans for the trace."""

    def __init__(self, cell: Cell, seed: int, seconds: float, tracing: bool,
                 control: bool, meter):
        self.cfg, self.traffic = cell.cfg, cell.traffic
        self.seed, self.seconds = seed, seconds
        self.tracing, self.control, self.meter = tracing, control, meter
        self._window = None

    def span(self, name: str):
        if self._window is None:
            return contextlib.nullcontext()
        import jax
        from bench.trace import SPAN_PREFIX
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def start_trace(self, log_dir: str) -> None:
        import jax
        from bench.trace import WINDOW
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()

    def stop_trace(self) -> None:
        if self._window is not None:
            import jax
            self._window.__exit__(None, None, None)
            self._window = None
            jax.profiler.stop_trace()


class Reading:
    """What a per-layer reader in ``metrics/`` is given."""

    def __init__(self, trace, counters: dict, attempted: int):
        self.trace, self.counters, self.attempted = trace, counters, attempted


def _number(v):
    return v if v is None or math.isfinite(v) else None


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, interpret-mode kernels, rehearsal sizes")
    ap.add_argument("--control", action="store_true",
                    help="the bfloat16 reference in the program's place")
    return ap.parse_args(argv)


def main(argv=None, t_start: float = T_START) -> int:
    args = _parse(argv)
    try:
        cell = Cell(args.workload, rehearse=args.rehearse)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_USAGE
    chips = cell.workload["chips"]
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("REPRO_KERNEL_BACKEND", "interpret")
        flags = os.environ.get("XLA_FLAGS", "")
        if chips > 1 and DEVICE_COUNT_FLAG not in flags:
            os.environ["XLA_FLAGS"] = \
                f"{flags} --{DEVICE_COUNT_FLAG}={chips}".strip()
    elif os.environ.get("REPRO_KERNEL_BACKEND"):
        print("bench: REPRO_KERNEL_BACKEND is set "
              f"({os.environ['REPRO_KERNEL_BACKEND']!r}); the benchmark "
              "times the chip's kernels only; nothing run", file=sys.stderr)
        return EXIT_NO_DEVICE

    import jax
    from bench.meter import Meter, peak_bytes
    from bench.trace import Trace

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu" and not args.rehearse:
        print(f"bench: no TPU (JAX found {platform}); nothing run",
              file=sys.stderr)
        return EXIT_NO_DEVICE
    if len(devices) < chips and args.rehearse:
        print(f"bench: JAX is already running with {len(devices)} "
              f"device(s); a {chips}-chip cell rehearses in a fresh process",
              file=sys.stderr)
        return EXIT_USAGE
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return EXIT_NO_DEVICE
    if not args.rehearse:
        peaks = _load_json(BENCH / "peaks.json")["devices"]
        if kind not in peaks:
            print(f"bench: device kind {kind!r} is not in peaks.json",
                  file=sys.stderr)
            return EXIT_NO_DEVICE
        from repro.launch import compile_cache
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    meter = Meter()
    try:
        ctx = Context(cell, args.seed, args.seconds, bool(args.trace),
                      args.control, meter)
        state = cell.op.setup(ctx)
        setup_s = time.perf_counter() - t_start

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") \
            if args.trace else None
        if trace_dir:
            ctx.start_trace(trace_dir)
        mark = meter.mark()
        try:
            result = cell.op.window(ctx, state)
        finally:
            ctx.stop_trace()
        counters = {**result["counters"], "xla_compiles": meter.since(mark)[2]}
        peak = peak_bytes(devices[:chips])
        cell.op.release(state)
        gc.collect()
        trace = None
        if trace_dir:
            try:
                trace = Trace.load(trace_dir, host_ops=args.rehearse)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)

        checks = cell.op.check(ctx, state, result)
    finally:
        meter.close()
    limits = cell.cfg["limits"]
    correct = all(v <= limits[k] for k, v in checks.items())

    e2e = {"setup_s": setup_s, **cell.op.end_to_end(result)}
    metrics = {}
    if trace is None:
        for m in cell.end_to_end():
            v = _number(e2e.get(m["name"]))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        reading = Reading(trace, counters, result["attempted"])
        for m in cell.per_layer():
            v = cell.reader(m["name"])(reading)
            v = _number(None if v is None else float(v))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        out["breakdown"] = {"device_ops": trace.top_ops(10),
                            "idle_gaps": trace.idle_gaps(10)}
    out["checks"] = {k: {"value": v, "limit": limits[k]}
                     for k, v in checks.items()}

    for k, v in {**e2e, **counters}.items():
        print(f"{k} {v!r}", file=sys.stderr)
    for k, v in checks.items():
        print(f"{k} {v} limit {limits[k]}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

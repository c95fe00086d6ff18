"""Benchmark entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig6_size]
                                            [--json [PATH]]
                                            [--check-regress [PATH]]

Prints ``name,case,seconds,derived`` CSV. With ``--json`` the same rows
are also written as ``BENCH_sweep.json`` (per-case
name/seconds/derived/engine), so the perf trajectory is machine-readable
and diffable across PRs.

``--check-regress`` compares the fresh run against a committed
``BENCH_sweep.json`` and exits nonzero on regression, so CI can gate on
the perf trajectory instead of only recording it. Two checks per case
present in both runs:

  * wall-clock: fresh seconds must stay within ``--regress-tol`` × the
    committed seconds (machine-speed sensitive — loosen the tolerance on
    heterogeneous runners);
  * derived ``speedup*`` ratios: machine-independent, so they get the
    tighter ``--ratio-tol`` — a frontier/wavefront/CSR speedup collapsing
    is a regression even if absolute times moved.

A few headline ratios additionally carry an absolute floor (``ABS_FLOORS``)
that binds on the fresh run independent of the baseline — e.g. the
wavefront-vs-stack BVH traversal speedup must stay ≥ 3x.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


# Absolute floors for derived ratios, enforced by --check-regress on the
# FRESH run regardless of what the committed baseline says: a baseline
# regenerated on a bad run must not grandfather a collapsed ratio in. The
# wavefront-vs-stack traversal gap is the headline structural claim of the
# batched/terminating/mixed-precision rework (DESIGN.md §13).
ABS_FLOORS = {"speedup_vs_stack": 3.0}


def _derived_speedups(derived: str) -> dict:
    """Parse ``speedup*=<float>`` entries out of a derived CSV fragment."""
    out = {}
    for key, val in re.findall(r"(speedup[\w]*)=([0-9.eE+-]+)", derived or ""):
        try:
            out[key] = float(val)
        except ValueError:
            pass
    return out


def check_regress(fresh_rows: list, committed: list, *,
                  regress_tol: float, ratio_tol: float) -> list:
    """Compare fresh records against a committed BENCH_sweep.json's rows.

    Returns a list of human-readable regression strings (empty = pass).
    Only (name, case) pairs present in both runs are compared — a partial
    ``--only`` run checks just its own figures against the committed file.
    ``committed`` is the baseline's row list, loaded by the caller *before*
    any ``--json`` dump so one invocation can gate against the old file
    and then overwrite it.
    """
    base = {(r["name"], r["case"]): r for r in committed
            if r.get("seconds") is not None}
    problems = []
    matched = 0
    for row in fresh_rows:
        key = (row["name"], row["case"])
        # absolute floors bind on every fresh row carrying the ratio, even
        # when the baseline lacks the case (renames, fresh baselines)
        for k, v in _derived_speedups(row.get("derived", "")).items():
            if k in ABS_FLOORS and v < ABS_FLOORS[k]:
                problems.append(
                    f"{key[0]},{key[1]}: {k}={v:.2f} below absolute floor "
                    f"{ABS_FLOORS[k]:.2f}")
        ref = base.get(key)
        if ref is None or row.get("seconds") is None:
            continue
        matched += 1
        if row["seconds"] > ref["seconds"] * regress_tol:
            problems.append(
                f"{key[0]},{key[1]}: {row['seconds']:.4f}s vs committed "
                f"{ref['seconds']:.4f}s (tol x{regress_tol})")
        ref_sp = _derived_speedups(ref.get("derived", ""))
        new_sp = _derived_speedups(row.get("derived", ""))
        for k, v in ref_sp.items():
            if k in new_sp and new_sp[k] < v / ratio_tol:
                problems.append(
                    f"{key[0]},{key[1]}: {k}={new_sp[k]:.2f} vs committed "
                    f"{v:.2f} (tol /{ratio_tol})")
    if matched == 0:
        # an empty intersection gates nothing — renamed cases, a --full
        # run against a non-full baseline, or a stale committed file must
        # not pass as a green check
        problems.append(
            "no (name, case) pairs overlap between this run and the "
            "committed baseline — the regression check compared nothing "
            "(case names or sizes changed? regenerate the baseline)")
    return problems


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="run only these figures (comma-separated names)")
    ap.add_argument("--json", nargs="?", const="BENCH_sweep.json",
                    default=None, metavar="PATH",
                    help="also write per-case records to PATH "
                         "(default BENCH_sweep.json)")
    ap.add_argument("--check-regress", nargs="?", const="BENCH_sweep.json",
                    default=None, metavar="PATH", dest="check_regress",
                    help="compare this run against a committed "
                         "BENCH_sweep.json and exit nonzero on regression")
    ap.add_argument("--regress-tol", type=float, default=1.6,
                    help="wall-clock tolerance factor for --check-regress "
                         "(default 1.6; loosen across machine classes, or "
                         "pass 'inf' to gate on the machine-independent "
                         "speedup ratios only — what CI does)")
    ap.add_argument("--ratio-tol", type=float, default=1.5,
                    help="tolerance factor for derived speedup ratios "
                         "(machine-independent; default 1.5)")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    from . import common, figures

    compile_cache.enable()

    if args.json or args.check_regress:
        common.JSON_SINK = []

    # load the baseline BEFORE any figure runs or --json dump: the same
    # invocation may gate against the committed file and then overwrite it
    baseline_rows = None
    if args.check_regress:
        if os.path.exists(args.check_regress):
            with open(args.check_regress) as f:
                baseline_rows = json.load(f)["rows"]
        else:
            print(f"# no committed baseline at {args.check_regress}; "
                  "skipping regression check", flush=True)

    only = set(args.only.split(",")) if args.only else None
    if only:
        known = {f.__name__ for f in figures.ALL_FIGS}
        unknown = only - known
        if unknown:
            ap.error(f"unknown figure(s) {sorted(unknown)}; "
                     f"known: {sorted(known)}")

    print("name,case,seconds,derived")
    t0 = time.time()
    failed = []
    for fig in figures.ALL_FIGS:
        if only and fig.__name__ not in only:
            continue
        try:
            fig(full=args.full)
        except Exception as e:  # keep the harness going; report the failure
            print(f"{fig.__name__},ERROR,NA,{type(e).__name__}: {e}",
                  flush=True)
            failed.append(fig.__name__)
    print(f"# total {time.time() - t0:.1f}s", flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"total_seconds": time.time() - t0,
                       "full": args.full,
                       "rows": common.JSON_SINK}, f, indent=2)
        print(f"# wrote {len(common.JSON_SINK)} records to {args.json}",
              flush=True)

    if baseline_rows is not None:
        problems = check_regress(
            common.JSON_SINK, baseline_rows,
            regress_tol=args.regress_tol, ratio_tol=args.ratio_tol)
        if problems:
            print(f"# REGRESSIONS vs {args.check_regress}:", flush=True)
            for p in problems:
                print(f"#   {p}", flush=True)
            sys.exit(3)
        print(f"# regression check vs {args.check_regress}: OK", flush=True)

    if failed:
        # every row (incl. ERROR ones) has been printed/written above; a
        # nonzero exit makes failed acceptance asserts (e.g. bench_serve's
        # zero-recompile gate) actually fail CI instead of vanishing
        print(f"# FAILED: {','.join(failed)}", flush=True)
        sys.exit(2)


if __name__ == "__main__":
    main()

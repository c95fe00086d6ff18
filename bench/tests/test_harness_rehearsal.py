"""Each cell rehearsed on the CPU at its rehearsal size, in-process on one
chip and in a fresh process on virtual devices for more: the last line has
exactly the contract's keys, names the CPU device, and comes out correct;
without the rehearsal flag the harness refuses the CPU."""
import functools
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness_paths  # noqa: F401
from bench import run

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
CELLS = [w["name"] for w in
         run._load_json(run.ROOT / "BENCHMARK.json")["workloads"]]
SEED = str(2**31 + 17)
# per-layer metrics read from the program's spans: a rehearsal reports them
PROGRAM_METRICS = {"plan_ms.batch", "layout_ms.batch", "prepare_ms.assign",
                   "launch_ms.assign", "finish_ms.assign", "self_ms.assign",
                   "pad_share.assign"}


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")


def _run(capsys, *argv):
    rc = run.main(list(argv))
    out, err = capsys.readouterr()
    return rc, out, err


def _rehearse_in_a_fresh_process(root, workload, traced):
    """``run.py --rehearse`` as its own process from the checkout ``root``,
    with no device count of the caller's in ``XLA_FLAGS``."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "REPRO_KERNEL_BACKEND": "interpret"}
    flags = [f for f in env.pop("XLA_FLAGS", "").split()
             if run.DEVICE_COUNT_FLAG not in f]
    if flags:
        env["XLA_FLAGS"] = " ".join(flags)
    p = subprocess.run(
        [sys.executable, f"{run.BENCH.name}/run.py", "--workload", workload,
         "--seed", SEED, "--seconds", "1", "--trace", str(traced),
         "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    return p.returncode, p.stdout, p.stderr


def _check_result(rc, out, err, cell, traced):
    assert rc == 0, err
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == KEYS | ({"breakdown"} if traced else set())
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    if cell.workload["chips"] > 1:
        assert line["device"]["count"] == cell.workload["chips"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    want = cell.per_layer() if traced else cell.end_to_end()
    assert set(line["metrics"]) <= {m["name"] for m in want}
    if traced:
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] >= line["device"]["busy_s"]
        program = PROGRAM_METRICS & {m["name"] for m in want}
        assert program <= set(line["metrics"])
        assert all(line["metrics"][n]["value"] >= 0 for n in program)
        if program:
            assert any(g[0].startswith("repro.")
                       for g in line["breakdown"]["idle_gaps"])
    else:
        assert set(line["metrics"]) == {m["name"] for m in want}
    # the compared numbers come last on standard error, each beside its limit
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[0] for t in tail] == list(line["checks"])
    assert all(" limit " in t for t in tail)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("traced", [0, 1])
def test_rehearsal_result_line(capsys, workload, traced):
    """One-chip cells rehearse in-process; a cell on more chips in a fresh
    process, whose JAX starts with as many virtual devices."""
    cell = run.Cell(workload)
    if cell.workload["chips"] > 1:
        rc, out, err = _rehearse_in_a_fresh_process(run.ROOT, workload,
                                                    traced)
    else:
        rc, out, err = _run(capsys, "--workload", workload, "--seed", SEED,
                            "--seconds", "1", "--trace", str(traced),
                            "--rehearse")
    _check_result(rc, out, err, cell, traced)


FOUR_CHIPS = "taxi-assign-mixed-4chips"


@pytest.fixture
def four_chip_checkout(tmp_path):
    """A copy of the harness whose ``BENCHMARK.json`` has one more cell, on
    four chips, over a configuration and traffic mix already there."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": FOUR_CHIPS, "config": "taxi2d-1m", "traffic": "assign-mixed",
        "chips": 4, "why": "example"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(run.BENCH, tmp_path / run.BENCH.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(run.ROOT / "src")
    return tmp_path


@pytest.mark.parametrize("traced", [0, 1])
def test_four_chip_cell_rehearses_in_a_fresh_process(four_chip_checkout,
                                                     traced):
    rc, out, err = _rehearse_in_a_fresh_process(four_chip_checkout,
                                                FOUR_CHIPS, traced)
    cell = run.Cell(FOUR_CHIPS, root=four_chip_checkout)
    _check_result(rc, out, err, cell, traced)


def test_four_chip_cell_in_process_asks_for_a_fresh_one(
        capsys, monkeypatch, four_chip_checkout):
    """JAX already running on fewer devices: a usage error, not a missing
    chip, and no result. Outside a rehearsal the cell is refused as any
    other: a set ``REPRO_KERNEL_BACKEND``, then a machine without a TPU."""
    import jax
    assert len(jax.devices()) < 4  # the suite's JAX is up before the run
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    monkeypatch.setattr(run, "Cell",
                        functools.partial(run.Cell, root=four_chip_checkout))
    rc, out, err = _run(capsys, "--workload", FOUR_CHIPS, "--seed", SEED,
                        "--seconds", "1", "--rehearse")
    assert rc == run.EXIT_USAGE and out == ""
    assert "a 4-chip cell rehearses in a fresh process" in err
    rc, out, err = _run(capsys, "--workload", FOUR_CHIPS, "--seed", SEED,
                        "--seconds", "1")
    assert rc == run.EXIT_NO_DEVICE and out == ""
    assert "REPRO_KERNEL_BACKEND" in err
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    rc, out, err = _run(capsys, "--workload", FOUR_CHIPS, "--seed", SEED,
                        "--seconds", "1")
    assert rc == run.EXIT_NO_DEVICE and out == "" and "no TPU" in err


def test_refuses_a_machine_without_a_tpu(capsys, monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    rc, out, err = _run(capsys, "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1")
    assert rc == run.EXIT_NO_DEVICE and out == "" and "no TPU" in err


def test_unknown_workload(capsys):
    rc, out, _ = _run(capsys, "--workload", "no-such-cell", "--seed", "1",
                      "--seconds", "1")
    assert rc == run.EXIT_USAGE and out == ""


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the harness runs nothing."""
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / run.BENCH.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, f"{run.BENCH.name}/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("backend", ["ref", "interpret", "kernel"])
def test_refuses_a_kernel_backend_override(capsys, backend, monkeypatch):
    """Outside a rehearsal a set ``REPRO_KERNEL_BACKEND`` is refused before
    JAX looks for a device: the window would time another path."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    rc, out, err = _run(capsys, "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1")
    assert rc == run.EXIT_NO_DEVICE and out == ""
    assert "REPRO_KERNEL_BACKEND" in err

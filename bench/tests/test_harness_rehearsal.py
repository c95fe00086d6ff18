"""Each cell rehearsed in-process on the CPU at its rehearsal size: the
last line has exactly the contract's keys, names the CPU device, and comes
out correct; without the rehearsal flag the harness refuses the CPU."""
import json

import pytest

import harness_paths  # noqa: F401
from bench import run

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
CELLS = [w["name"] for w in
         run._load_json(run.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")


def _run(capsys, *argv):
    rc = run.main(list(argv))
    out, err = capsys.readouterr()
    return rc, out, err


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("traced", [0, 1])
def test_rehearsal_result_line(capsys, workload, traced):
    rc, out, err = _run(capsys, "--workload", workload, "--seed",
                        str(2**31 + 17), "--seconds", "1", "--trace",
                        str(traced), "--rehearse")
    assert rc == 0, err
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == KEYS | ({"breakdown"} if traced else set())
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert line["attempted"] >= 1 and line["failed"] == 0
    cell = run.Cell(workload)
    want = cell.per_layer() if traced else cell.end_to_end()
    assert set(line["metrics"]) <= {m["name"] for m in want}
    if traced:
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] >= line["device"]["busy_s"]
    else:
        assert set(line["metrics"]) == {m["name"] for m in want}
    # the compared numbers come last on standard error, each beside its limit
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[0] for t in tail] == list(line["checks"])
    assert all(" limit " in t for t in tail)


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
    import os
    import shutil
    import subprocess
    import sys
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

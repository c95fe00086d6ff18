"""The correctness check catches what it is there to catch.

* The control — the reference on bfloat16 coordinates, one precision step
  below the configurations' float32, in the program's place — comes out
  not correct in every cell.
* A run with the timed path broken underneath comes out not correct, for
  each fault a cell can have: a hooking step that returns its state
  unchanged, half of each batch left out, an answer altered where it is
  produced. (There is no exchange between chips to leave out: every cell
  runs on one chip.)
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness_paths  # noqa: F401
from bench import run
from repro.core import dbscan as dbscan_mod
from repro.serve import ingest

CELLS = ["roadnet-batch", "taxi-assign-mixed"]
DRIVERS = ("_sorted_driver_fn", "_frontier_driver_fn", "_device_loop_fn",
           "_round_fn")


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    yield
    for name in DRIVERS:  # drop traces made with a patched step
        getattr(dbscan_mod, name).cache_clear()
    jax.clear_caches()


def _line(capsys, workload, *extra):
    rc = run.main(["--workload", workload, "--seed", "1234567891",
                   "--seconds", "1", "--trace", "0", "--rehearse", *extra])
    out, err = capsys.readouterr()
    assert rc == 0, err
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(capsys, workload):
    line = _line(capsys, workload, "--control")
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _state_unchanged(monkeypatch):
    monkeypatch.setattr(dbscan_mod, "_hook_step",
                        lambda root, m, core: (root, jnp.any(root != root)))
    for name in DRIVERS:
        getattr(dbscan_mod, name).cache_clear()


def _half_left_out(monkeypatch):
    orig_dbscan, orig_assign = dbscan_mod.dbscan, ingest.assign

    def dbscan(points, eps, min_pts, **kw):
        h = len(points) // 2
        r = orig_dbscan(points[:h], eps, min_pts, **kw)
        pad = len(points) - h
        return r._replace(
            labels=jnp.concatenate([r.labels, jnp.full(pad, -1, jnp.int32)]),
            core=jnp.concatenate([r.core, jnp.zeros(pad, bool)]),
            counts=jnp.concatenate([r.counts, jnp.zeros(pad, jnp.int32)]))

    def assign(snapshot, queries, **kw):
        h = (len(queries) + 1) // 2
        r = orig_assign(snapshot, queries[:h], **kw)
        pad = len(queries) - h
        return r._replace(
            labels=np.concatenate([r.labels, np.full(pad, -1, np.int32)]),
            counts=np.concatenate([r.counts, np.zeros(pad, np.int32)]),
            dist=np.concatenate([r.dist, np.full(pad, np.inf, np.float32)]))

    monkeypatch.setattr(dbscan_mod, "dbscan", dbscan)
    monkeypatch.setattr(ingest, "assign", assign)


def _answer_altered(monkeypatch):
    orig_dbscan, orig_assign = dbscan_mod.dbscan, ingest.assign

    def dbscan(*a, **kw):
        r = orig_dbscan(*a, **kw)
        return r._replace(labels=r.labels.at[0].add(1))

    def assign(*a, **kw):
        r = orig_assign(*a, **kw)
        counts = r.counts.copy()
        counts[0] += 1
        return r._replace(counts=counts)

    monkeypatch.setattr(dbscan_mod, "dbscan", dbscan)
    monkeypatch.setattr(ingest, "assign", assign)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _answer_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_is_not_correct(capsys, monkeypatch, workload, fault):
    fault(monkeypatch)
    line = _line(capsys, workload)
    assert line["correct"] is False, line["checks"]

"""A configuration, a traffic mix and a per-layer metric added as new files
are found by name; no existing file of the harness needs an edit."""
import hashlib
import json
import shutil

import harness_paths  # noqa: F401
from bench import run


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    src = run.ROOT / run.BENCH.name
    dst = tmp_path / run.BENCH.name
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(src / sub, dst / sub)
    before = _digests(dst)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())

    cfg = json.loads((src / "configs" / "taxi2d-1m.json").read_text())
    cfg.update(name="taxi2d-250k", n_points=250_000)
    (dst / "configs" / "taxi2d-250k.json").write_text(json.dumps(cfg))
    traffic = json.loads((src / "traffic" / "assign-mixed.json").read_text())
    traffic.update(sizes={"dist": "loguniform", "min": 1, "max": 64})
    (dst / "traffic" / "assign-small.json").write_text(json.dumps(traffic))
    (dst / "metrics" / "requests.small.py").write_text(
        "def read(run):\n    return run.attempted\n")
    bench["configs"].append({
        "name": "taxi2d-250k", "source": "example",
        "file": f"{run.BENCH.name}/configs/taxi2d-250k.json",
        "reduced": ["n_points"], "why": "example"})
    bench["workloads"].append({
        "name": "taxi-assign-small", "config": "taxi2d-250k",
        "traffic": "assign-small", "chips": 1, "why": "example"})
    bench["per_layer"].append({
        "name": "requests.small", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry",
        "moves": "assign_points_per_s", "workloads": ["taxi-assign-small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.Cell("taxi-assign-small", root=tmp_path)
    assert cell.cfg["n_points"] == 250_000
    assert cell.traffic["sizes"]["max"] == 64
    assert cell.op.__name__ == "bench.ops.assign"
    assert [m["name"] for m in cell.per_layer()] == ["requests.small"]
    assert {m["name"] for m in cell.end_to_end()} == {"setup_s"}
    assert cell.reader("requests.small")(run.Reading(None, {}, 7)) == 7
    # the cells already there are found as before
    assert run.Cell("roadnet-batch", root=tmp_path).cfg["n_points"] == 434874
    new = {p for p in _digests(dst)} - set(before)
    assert {str(p) for p in new} == {"configs/taxi2d-250k.json",
                                     "traffic/assign-small.json",
                                     "metrics/requests.small.py"}
    assert {p: d for p, d in _digests(dst).items() if p in before} == before

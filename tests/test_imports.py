"""Import hygiene: every module of ``src/repro`` loads on its own, and no
file of the repo imports a module of the LLM stack that was deleted from it
(models, configs, trainer, dry-run, elastic restart, compressed
collectives)."""
import ast
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")

DELETED = ("repro.models", "repro.configs", "repro.train",
           "repro.launch.dryrun", "repro.launch.train",
           "repro.launch.hlo_costs", "repro.launch.analysis",
           "repro.distributed.elastic", "repro.distributed.collectives")
SCANNED = ("src", "bench", "benchmarks", "examples", "tests",
           "chip_smoke.py")


def _py_files(rel):
    path = os.path.join(ROOT, rel)
    if os.path.isfile(path):
        yield path
        return
    for d, _, names in os.walk(path):
        yield from (os.path.join(d, n) for n in sorted(names)
                    if n.endswith(".py"))


def _module_name(path):
    """Dotted name of ``path``: under ``src/`` for the package, else from
    the repo root (``bench.run``)."""
    base = SRC if path.startswith(SRC + os.sep) else ROOT
    parts = os.path.relpath(path, base)[:-len(".py")].split(os.sep)
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


def _imported(tree, module, is_pkg):
    """Absolute names each import statement of ``tree`` may load, with
    relative imports resolved against ``module``."""
    pkg = module.split(".") if is_pkg else module.split(".")[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                keep = len(pkg) - (node.level - 1)
                base = ".".join(pkg[:keep] + ([node.module]
                                              if node.module else []))
            else:
                base = node.module
            yield base
            yield from (f"{base}.{a.name}" for a in node.names)


def _embedded_code(tree):
    """Code held in string literals (the scripts tests run in a child
    process); strings that are not Python are skipped."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and "import" in node.value):
            try:
                yield ast.parse(textwrap.dedent(node.value))
            except SyntaxError:
                continue


def test_no_file_imports_the_deleted_llm_stack():
    hits = []
    for rel in SCANNED:
        for path in _py_files(rel):
            module = _module_name(path)
            is_pkg = path.endswith("__init__.py")
            tree = ast.parse(open(path).read(), filename=path)
            for t in (tree, *_embedded_code(tree)):
                for name in _imported(t, module, is_pkg):
                    if any(name == d or name.startswith(d + ".")
                           for d in DELETED):
                        hits.append(f"{os.path.relpath(path, ROOT)}: {name}")
    assert not hits, "imports of deleted modules:\n" + "\n".join(hits)


def test_every_repro_module_imports():
    # ``repro`` is a namespace package (no __init__.py), so walk the files
    modules = sorted(_module_name(p) for p in _py_files("src/repro"))
    assert "repro.core.dbscan" in modules and "repro.serve.router" in modules
    script = ("import importlib\n"
              f"for m in {modules!r}:\n"
              "    importlib.import_module(m)\n"
              f"print('OK', {len(modules)})\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split() == ["OK", str(len(modules))]

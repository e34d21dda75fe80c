"""The chip benchmark of the SpGEMM system: one cell per run of
``python3 bench/run.py``; see ``bench/harness.py`` and ``BENCHMARK.json``."""
import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(kind: str, name: str, root: str = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold ``.``/``-``):
    how paths, metrics and matrices are found by the name a file gives."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

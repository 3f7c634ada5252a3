"""Find the benchmark's files by the names ``BENCHMARK.json`` gives:
configurations, cells, drivers, per-layer readers and the trace
reduction are all loaded by path, so a later PR adds one by adding a
file."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def load_module(*relative: str):
    """Import ``benchmarks/<relative...>.py`` under a name of its own
    (``trace`` and the dotted metric names are no package names)."""
    path = os.path.join(BENCH_DIR, *relative) + ".py"
    if not os.path.exists(path):
        raise FileNotFoundError(f"the benchmark has no {path}")
    name = "znicz_bench_" + "_".join(relative).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*relative: str) -> dict:
    with open(os.path.join(BENCH_DIR, *relative)) as f:
        return json.load(f)


def benchmark_json() -> dict:
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)

"""Shared set-up of the benchmark's tests: a copy of the benchmark directory
with small configurations, where a test may add files of its own."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run  # noqa: E402


def small_bench(tmp_path, scale: int = 8) -> tuple[str, dict]:
    """A copy of bench/ whose configurations are cut to `scale`, and the
    repository's BENCHMARK.json."""
    d = str(tmp_path / "bench")
    shutil.copytree(BENCH, d, ignore=shutil.ignore_patterns("tests",
                                                            "__pycache__"))
    for name in os.listdir(os.path.join(d, "configs")):
        path = os.path.join(d, "configs", name)
        cfg = run.load_json(path)
        cfg["scale"] = scale
        with open(path, "w") as f:
            json.dump(cfg, f)
    return d, run.load_json(os.path.join(ROOT, "BENCHMARK.json"))

"""CPU rehearsal of every cell at a small scale, the Pallas kernels in
interpret mode, checked against the plain references; and the command's
refusal to run without a TPU."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from helpers import ROOT, run, small_bench

CELLS = [w["name"] for w in run.load_json(
    os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal_is_correct(tmp_path, workload):
    d, spec = small_bench(tmp_path)
    r = run.run_cell(spec, workload, 2**31 + 12345, 0.0, False,
                     bench_dir=d, rehearsal=True)
    assert r["correct"], r
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["metrics"] == {}, "a CPU rehearsal reports no device metric"
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("entry", ["pagerank", "sssp"])
def test_warmup_and_window_call_the_entry_from_one_stack(tmp_path,
                                                         monkeypatch, entry):
    """A Pallas kernel's compiled body keeps the Python stack it was traced
    from, and the compile cache's key covers it: the warm-up has to reach
    the program's entry through the same frames as the window's jobs."""
    import traceback
    from repro.core import algorithms
    workload = {"pagerank": "ga500-16.pagerank", "sssp": "g500-16.bfs"}[entry]
    orig, stacks = getattr(algorithms, entry), []

    def spy(*a, **kw):
        stacks.append([(f.filename, f.lineno, f.name)
                       for f in traceback.extract_stack()[:-1]])
        return orig(*a, **kw)
    monkeypatch.setattr(algorithms, entry, spy)
    d, spec = small_bench(tmp_path, scale=7)
    r = run.run_cell(spec, workload, 5, 0.0, False, bench_dir=d,
                     rehearsal=True, kernel_mode="ref")
    assert r["correct"] and r["attempted"] == 1
    assert len(stacks) == 2                       # the warm-up and one job
    assert stacks[0] == stacks[1]


def _command(cwd, workload):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_tpu():
    p = _command(ROOT, CELLS[0])
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(str(tmp_path), CELLS[0])
    assert p.returncode != 0
    assert p.stdout == ""

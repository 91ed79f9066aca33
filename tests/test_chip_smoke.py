"""chip_smoke.py off the chip: its CPU rehearsal (Pallas interpret mode)
must match the references, and without a TPU and without the rehearsal
option it must fail and print no result.  Each run is a subprocess: the
script owns its JAX process, and the 4-device phase needs XLA_FLAGS set
before JAX starts."""
import json
import os
import subprocess
import sys

import jax

from repro.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, tmp_path, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env_extra)
    proc = subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=300)
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    return proc, rows


def _phase(rows, name):
    return next(r for r in rows if r.get("phase") == name)


def test_cpu_rehearsal_matches_references(tmp_path):
    proc, rows = _run(["--cpu-rehearsal", "--scale", "8", "--max-scale", "8"],
                      tmp_path)
    assert proc.returncode == 0, proc.stderr
    pr, cc = _phase(rows, "pagerank"), _phase(rows, "cc")
    assert pr["supersteps"] == 10 and pr["max_rel_err"] <= 1e-4
    assert cc["label_mismatches"] == 0 and cc["supersteps"] >= 1
    for row in (pr, cc):
        assert (row["plan"], row["apply_plan"]) == ("fused", "fused_apply")
    assert _phase(rows, "scale")["reached"] == 8
    assert "ok" not in rows[-1] and rows[-1]["device"]["platform"] == "cpu"
    # the persistent cache went where JAX_COMPILATION_CACHE_DIR points
    assert _phase(rows, "setup")["compile_cache"] == str(tmp_path /
                                                          "jax_cache")
    assert os.listdir(tmp_path / "jax_cache")


def test_without_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    proc, rows = _run([], tmp_path)
    assert proc.returncode != 0
    assert rows == [] and '"ok"' not in proc.stdout


def test_four_device_spmd_rehearsal(tmp_path):
    flags = (os.environ.get("XLA_FLAGS", "")
             + " --xla_force_host_platform_device_count=4").strip()
    proc, rows = _run(["--cpu-rehearsal", "--chips", "4", "--scale", "8",
                       "--max-scale", "8"], tmp_path, XLA_FLAGS=flags)
    assert proc.returncode == 0, proc.stderr
    pr, cc = _phase(rows, "spmd_pagerank"), _phase(rows, "spmd_cc")
    assert pr["max_rel_err"] <= 1e-4 and cc["label_mismatches"] == 0
    for row in (pr, cc):
        assert row["shard_devices"] == [0, 1, 2, 3]
        assert row["shard_rows"] == [1] and row["all_to_all"]
    assert rows[-1]["device"]["count"] == 4 and "ok" not in rows[-1]


def test_compile_cache_directory(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        default = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

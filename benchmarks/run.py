"""Benchmark driver — one module per paper figure/table.

  PYTHONPATH=src python -m benchmarks.run            # quick (CPU-sized)
  PYTHONPATH=src python -m benchmarks.run --full     # Table-1-scaled graphs
  PYTHONPATH=src python -m benchmarks.run --only fig5_join_elim

Prints one CSV-ish line per measurement and writes reports/bench.json.
The dry-run/roofline numbers (launch package) are reported separately in
EXPERIMENTS.md; this file covers the paper's measured figures.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import time
import traceback

# The SpmdExchange fused-vs-unfused columns (op_micro, fig7) need >= 4
# devices; simulate host-platform devices unless the operator provided
# their own flags.  REPRO_NUM_DEVICES overrides the simulated count (it has
# no effect under an operator-supplied XLA_FLAGS or on real accelerators,
# where the platform owns the device count — modules that need more
# devices than exist skip gracefully instead).  Must happen before any
# benchmark module imports jax.
_ndev = os.environ.get("REPRO_NUM_DEVICES", "4")
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={_ndev}")

MODULES = [
    "fig4_incremental",
    "fig5_join_elim",
    "fig6_index_scan",
    "fig7_graph_parallel",
    "fig8_scaling",
    "fig9_partitioning",
    "fig10_pipeline",
    "op_micro",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-out", default="reports/bench.json")
    ap.add_argument("--superstep", action="store_true",
                    help="run ONLY the superstep fusion/overlap bench and "
                         "write its persisted trajectory (BENCH file)")
    ap.add_argument("--bench-out", default="BENCH_superstep.json",
                    help="trajectory path for --superstep")
    ap.add_argument("--working-set", default=None,
                    help="comma-separated working-set fractions for the "
                         "fig8_scaling §2.4 matrix (e.g. 1.0,0.5,0.25)")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.superstep:
        from benchmarks import superstep_bench
        rows = superstep_bench.run(quick=not args.full)
        for r in rows:
            print("  " + ", ".join(f"{k}={v}" for k, v in r.items()
                                   if k != "benchmark"))
        with open(args.bench_out, "w") as f:
            json.dump(superstep_bench.trajectory(rows), f, indent=1)
            f.write("\n")
        print(f"\n{len(rows)} superstep rows -> {args.bench_out}")
        return

    mods = [args.only] if args.only else MODULES
    all_rows = []
    failures = []
    for name in mods:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.perf_counter()
        kwargs = {}
        if name == "fig8_scaling" and args.working_set:
            kwargs["working_sets"] = tuple(
                float(x) for x in args.working_set.split(","))
        try:
            rows = mod.run(quick=not args.full, **kwargs)
        except Exception as e:  # keep the suite going; report at the end
            failures.append((name, e))
            print(f"[{name}] FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
            continue
        dt = time.perf_counter() - t0
        print(f"\n== {name} ({dt:.1f}s) " + "=" * max(1, 50 - len(name)))
        for r in rows:
            print("  " + ", ".join(f"{k}={v}" for k, v in r.items()
                                   if k != "benchmark"))
        all_rows.extend(rows)

    os.makedirs(os.path.dirname(args.json_out), exist_ok=True)
    with open(args.json_out, "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    print(f"\n{len(all_rows)} measurements -> {args.json_out}")
    if failures:
        raise SystemExit(
            "benchmark failures: " + ", ".join(n for n, _ in failures))


if __name__ == "__main__":
    main()

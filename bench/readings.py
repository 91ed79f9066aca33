#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process on the chip.

    python bench/readings.py --workload ga500-16.pagerank --seeds 1-12 \\
        --control-seeds 1-3 --jobs 1

For each seed: the graph with that seed's edge order, the traffic's inputs,
then `--jobs` jobs through the program's public entry, compared with the
reference exactly as a benchmark run compares them.  For each control seed:
the job kind's control (its reference one precision lower, or with one
guarantee broken) put in the program's place at the cell's own size, under
the same comparison.  One JSON line per seed; the benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--jobs", type=int, default=1)
    args = ap.parse_args(argv)

    spec = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from graphs import dataset
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("readings.py: no TPU", file=sys.stderr)
        return 2
    cell = run.Cell(spec, args.workload)
    ds = dataset(cell.cfg)
    for seed in seeds(args.seeds) if args.seeds else []:
        t = time.time()
        g = run.build_graph(cell, ds, seed)
        qs = cell.job.queries(ds, cell.traffic, seed)[:args.jobs]
        done = []
        for q in qs:
            leaf, steps = cell.job.run(g, q, cell.traffic, "auto")
            done.append((q, jax.block_until_ready(leaf), steps, 0.0, 0.0))
        layout = run.home_positions(g, ds)
        del g
        ok, failed, checks = run.check(cell, ds, layout, done)
        run.log({"reading": "program", "seed": seed, "correct": ok,
                 "jobs": len(done), "failed": failed, "seconds":
                 time.time() - t, "checks": checks})
    for seed in seeds(args.control_seeds) if args.control_seeds else []:
        qs = cell.job.queries(ds, cell.traffic, seed)[:args.jobs]
        results = [(q, *cell.job.control(ds, q, cell.traffic, seed))
                   for q in qs]
        values, failed = cell.job.compare(ds, cell.traffic, results)
        run.log({"reading": "control", "seed": seed, "failed": failed,
                 "jobs": len(qs),
                 "checks": {k: {"value": v,
                                "limit": cell.traffic["limits"][k]}
                            for k, v in values.items()}})
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A configuration, a traffic mix and a per-layer metric added as new files
only, with new entries in BENCHMARK.json, are found by name and run: no
file the benchmark already has is edited."""
from __future__ import annotations

import hashlib
import json
import os
import types

from helpers import run, small_bench


def _digest(d):
    out = {}
    for root, _, files in os.walk(d):
        for f in files:
            with open(os.path.join(root, f), "rb") as fh:
                out[os.path.relpath(os.path.join(root, f), d)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_files_only(tmp_path):
    d, spec = small_bench(tmp_path)
    before = _digest(d)

    cfg = run.load_json(os.path.join(d, "configs",
                                     "graphalytics-graph500-16.json"))
    cfg.update(name="graphalytics-graph500-7", scale=7, dataset_seed=3)
    with open(os.path.join(d, "configs", "graphalytics-graph500-7.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(d, "traffic", "pagerank-5.json"), "w") as f:
        json.dump({"job": "pagerank", "num_iters": 5, "reset": 0.15,
                   "limits": {"rank_rel_err": 1e-4, "supersteps_off": 0}}, f)
    with open(os.path.join(d, "layer_metrics", "kernels.share.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return 100.0 * sum(ctx.kernel_s.values()) / ctx.busy_s\n")

    cell_name = "ga500-7.pagerank5"
    spec["configs"].append({"name": "graphalytics-graph500-7",
                            "source": "test", "file": "x", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": cell_name,
                              "config": "graphalytics-graph500-7",
                              "traffic": "pagerank-5", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "kernels.share", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "kernels", "moves": "evps",
                              "workloads": [cell_name]})

    cell = run.Cell(spec, cell_name, d)
    assert cell.cfg["scale"] == 7 and cell.traffic["num_iters"] == 5
    assert "kernels.share" in cell.per_layer
    assert "kernels.share" not in run.Cell(spec, "ga500-16.pagerank",
                                           d).per_layer
    ctx = types.SimpleNamespace(kernel_s={"triplet": 1.0, "apply": 0.5},
                                busy_s=3.0)
    assert cell.per_layer["kernels.share"][1].read(ctx) == 50.0

    r = run.run_cell(spec, cell_name, 9, 0.0, False, bench_dir=d,
                     rehearsal=True, kernel_mode="ref")
    assert r["correct"], r["checks"]
    assert r["attempted"] == 1

    after = _digest(d)
    assert {k: v for k, v in after.items() if k in before} == before

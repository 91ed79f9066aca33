"""The program-span reduction (`progtrace.py`): on a hand-made trace whose
numbers are known, on events recorded from a TPU v5e trace (one scale-10
job of each cell, traced by `run.py --trace 1`'s own path), on a trace of
a program without spans, and through the readers on a CPU trace file."""
from __future__ import annotations

import gzip
import json
import os
import types

import pytest

from helpers import BENCH, run  # noqa: F401  (puts bench/ on sys.path)
import progtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = ["ga500-16.pagerank", "g500-16.bfs"]
NEW_READERS = ["driver.plan_ms_per_job", "driver.dispatch_ms_per_superstep",
               "triplet.useful_step_share",
               "operators.streams_ms_per_superstep"]
T = "/host:CPU#0"


def _span(name, a, b, /, **args):
    return [name, T, a, b, {"job": 1, **args}]


def _hand_made():
    """One job [0, 100], the harness, then a second job [110, 200] whose
    spans are left out (a program without them)."""
    spans = [
        _span("graphx.algorithm", 0, 100, name="pagerank"),
        _span("graphx.operator", 1, 9, op="mrTriplets"),
        _span("graphx.pregel", 10, 99, supersteps=2),
        _span("graphx.pregel.plan", 10, 20),
        _span("graphx.pregel.dispatch", 20, 40, first=1),
        _span("graphx.pregel.sync", 40, 60, chunks_live=3, chunks=10,
              grid_steps=40),
        _span("graphx.pregel.dispatch", 60, 64, first=0),
        _span("graphx.pregel.sync", 64, 90, chunks_live=1, chunks=10,
              grid_steps=40),
        ["graphx.other_thread", "/host:CPU#1", 0, 200, {}],
    ]
    ops = [[0, 5, 4, ""],                          # the operator's sweep
           [0, 42, 8, "graphx.triplet_streams"],   # [42, 50]
           [0, 50, 10, ""],                        # the kernel
           [0, 66, 20, "graphx.triplet_streams"],  # [66, 86]
           [0, 150, 10, "graphx.apply"]]           # the second job
    return {"devices": 1, "spans": spans, "jobs": [[0, 100], [110, 200]],
            "ops": ops}


def test_scope_of_an_op_name():
    assert progtrace.scope_of(
        "jit(pregel_superstep)/jit(fused_triplet)/graphx.triplet_streams/"
        "gather:") == "graphx.triplet_streams"
    assert progtrace.scope_of("jit(pregel_superstep)/graphx.view/"
                              "graphx.exchange/transpose:") == \
        "graphx.exchange"
    assert progtrace.scope_of("jit(broadcast_in_dim)/broadcast_in_dim:") == ""


def test_hand_made_trace():
    red = progtrace.reduce(_hand_made())
    assert red["jobs"] == 2
    assert red["plan_s"] == pytest.approx(10e-9)
    assert red["dispatch_warm_s"] == pytest.approx(4e-9)
    assert red["supersteps_counted"] == 2
    assert (red["chunks_live"], red["grid_steps"]) == (4, 80)
    assert red["streams_s"] == pytest.approx(28e-9)
    assert red["device_s_by_scope"] == pytest.approx(
        {"": 14e-9, "graphx.triplet_streams": 28e-9, "graphx.apply": 10e-9})
    idle = red["idle_by_span"]
    assert idle == pytest.approx({
        "graphx.algorithm": 3e-9,          # [0, 1], [9, 10], [99, 100]
        "graphx.operator": 4e-9,           # [1, 5]
        "graphx.pregel.plan": 10e-9,
        "graphx.pregel.dispatch first=1": 20e-9,
        "graphx.pregel.sync": 8e-9,        # [40, 42], [64, 66], [86, 90]
        "graphx.pregel.dispatch first=0": 4e-9,
        "graphx.pregel": 9e-9,             # [90, 99]
        progtrace.OUTSIDE_SPANS: 80e-9,    # [110, 150], [160, 200]
        progtrace.BETWEEN_JOBS: 10e-9})    # [100, 110]
    # the idle time adds up to the window less the busy time
    assert sum(idle.values()) == pytest.approx((200 - 52) * 1e-9)


def test_a_program_without_spans_reads_nothing(monkeypatch):
    ev = {"devices": 1, "spans": [], "jobs": [[0, 10]],
          "ops": [[0, 2, 3, ""]]}
    red = progtrace.reduce(ev)
    assert [red[k] for k in ("plan_s", "dispatch_warm_s", "chunks_live",
                             "grid_steps", "streams_s")] == [None] * 5
    assert red["idle_by_span"] == pytest.approx(
        {progtrace.OUTSIDE_SPANS: 7e-9})
    monkeypatch.setattr(progtrace, "reading", lambda ctx: red)
    ctx = types.SimpleNamespace(jobs=1, supersteps=1)
    for name in NEW_READERS:
        reader = run.load_module(os.path.join(BENCH, "layer_metrics",
                                              name + ".py"))
        assert reader.read(ctx) is None


def _recorded(workload):
    with gzip.open(os.path.join(DATA, f"{workload}.scale10.progtrace.json.gz"),
                   "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("workload", RECORDED)
def test_recorded_chip_trace(workload):
    ev = _recorded(workload)
    red = progtrace.reduce(ev)
    assert ev["devices"] == 1 and red["jobs"] == 1
    assert red["plan_s"] > 0 and red["dispatch_warm_s"] > 0
    assert red["supersteps_counted"] >= 2
    assert 0 < red["chunks_live"] <= red["grid_steps"]
    steps = red["grid_steps"] // red["supersteps_counted"]
    assert red["grid_steps"] == steps * red["supersteps_counted"]
    assert red["streams_s"] > 0
    idle = red["idle_by_span"]
    in_job = sum(v for k, v in idle.items() if k != progtrace.BETWEEN_JOBS)
    below_root = sum(v for k, v in idle.items()
                     if k.startswith("graphx.") and k != "graphx.algorithm")
    assert below_root >= 0.9 * in_job


def test_readers_find_the_newest_trace_file(tmp_path, monkeypatch):
    """A CPU trace on disk, where `run.py` leaves a chip's: the readers
    read it when it holds the window's jobs, and nothing otherwise."""
    import jax
    import numpy as np
    from repro.core import Graph, algorithms
    from repro.data import rmat, symmetrize
    gd = symmetrize(rmat(6, 4, seed=3))
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=2,
                         edge_values={"w": np.ones(gd.num_edges,
                                                   np.float32)})
    d = str(tmp_path / "trace" / "cell")
    with jax.profiler.trace(d):
        with jax.profiler.TraceAnnotation("bench.job", index=0):
            res = algorithms.pagerank(g, num_iters=3,
                                      kernel_mode="interpret")
            jax.block_until_ready(res.graph.vdata)
    monkeypatch.setattr(progtrace, "TRACE_GLOB", os.path.join(
        str(tmp_path), "trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    readers = {name: run.load_module(os.path.join(
        BENCH, "layer_metrics", name + ".py")) for name in NEW_READERS}
    ctx = types.SimpleNamespace(jobs=1, supersteps=3)
    values = {n: r.read(ctx) for n, r in readers.items()}
    assert values["driver.plan_ms_per_job"] > 0
    assert values["driver.dispatch_ms_per_superstep"] > 0
    assert 0 < values["triplet.useful_step_share"] <= 100
    # a CPU trace has no device plane, so no device op to read
    assert values["operators.streams_ms_per_superstep"] is None
    ctx.jobs = 2
    assert all(r.read(ctx) is None for r in readers.values())

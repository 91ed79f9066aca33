"""The two cells of the placed deployment and of WCC: CPU rehearsals at a
small scale (the four-chip cell on four simulated devices), the controls,
two faults planted in the placed path, and the readers of the collectives
on events recorded from a TPU v5e trace (one scale-10 job of the
four-chip cell on a v5e 2x2 host, traced by `run.py --trace 1`'s own
path)."""
from __future__ import annotations

import gzip
import importlib
import json
import os
import types

import jax
import numpy as np
import pytest

from helpers import BENCH, run, small_bench
import progtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PLACED = "ga500-17.pagerank.4chip"
WCC = "ga500-16.wcc"


def _reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics",
                                        name + ".py"))


def _rehearse(tmp_path, workload, seed=2**31 + 99, scale=8):
    d, spec = small_bench(tmp_path, scale=scale)
    return run.run_cell(spec, workload, seed, 0.0, False, bench_dir=d,
                        rehearsal=True, kernel_mode="ref")


def test_placed_cell_runs_on_four_devices(tmp_path, monkeypatch):
    from repro.core import algorithms
    seen = []
    orig = algorithms.pagerank

    def spy(g, **kw):
        seen.append((g.num_devices, type(g.ex).__name__,
                     sorted(s.device.id for s in g.vmask.addressable_shards)))
        return orig(g, **kw)
    monkeypatch.setattr(algorithms, "pagerank", spy)
    r = _rehearse(tmp_path, PLACED)
    assert r["correct"] and r["attempted"] >= 1, r
    assert r["device"]["count"] == 4
    assert seen and all(s == (4, "SpmdExchange", [0, 1, 2, 3])
                        for s in seen)


def test_placed_cell_places_the_graph_once(tmp_path):
    from repro.core import Graph
    from graphs import dataset
    d, spec = small_bench(tmp_path, scale=7)
    cell = run.Cell(spec, PLACED, d)
    g = run.build_graph(cell, dataset(cell.cfg), 1)
    a = cell.job.placed(g, 4)
    assert isinstance(a, Graph) and cell.job.placed(g, 4) is a
    assert a.mesh is not None and g.mesh is None


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_wcc_cell_is_correct(tmp_path, seed):
    r = _rehearse(tmp_path, WCC, seed)
    assert r["correct"] and r["checks"]["label_mismatches"]["value"] == 0, r


def _no_exchange(mp):
    from repro.core.exchange import SpmdExchange
    mp.setattr(SpmdExchange, "transpose", lambda self, x: x)


def _altered_answer(mp):
    pregel = importlib.import_module("repro.core.pregel")
    orig = pregel._superstep

    def step(g, *a, **kw):
        g2, live, metrics = orig(g, *a, **kw)
        vdata = jax.tree.map(lambda v: v.at[0, 0].add(1.0), g2.vdata)
        return g2.replace(vdata=vdata, view=g2.view), live, metrics
    mp.setattr(pregel, "_superstep", step)


@pytest.mark.parametrize("fault", ["no_exchange", "altered_answer"])
def test_planted_fault_in_the_placed_path_is_not_correct(tmp_path,
                                                         monkeypatch, fault):
    {"no_exchange": _no_exchange, "altered_answer": _altered_answer}[fault](
        monkeypatch)
    r = _rehearse(tmp_path, PLACED, seed=77, scale=7)
    assert r["correct"] is False, r["checks"]
    assert r["failed"] >= 1


@pytest.mark.parametrize("workload", [PLACED, WCC])
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 987654321])
def test_control_is_not_correct(tmp_path, workload, seed):
    """The control takes the program's place in the comparison: PageRank's
    reference in bfloat16, WCC's reference halted one superstep early."""
    from graphs import dataset
    d, spec = small_bench(tmp_path, scale=10)
    cell = run.Cell(spec, workload, d)
    ds = dataset(cell.cfg)
    qs = cell.job.queries(ds, cell.traffic, seed)
    results = [(q, *cell.job.control(ds, q, cell.traffic, seed)) for q in qs]
    values, failed = cell.job.compare(ds, cell.traffic, results)
    limits = cell.traffic["limits"]
    assert failed == len(qs)
    assert any(values[k] > limits[k] for k in values), values
    ref = [(q, *cell.job.reference(ds, q, cell.traffic)) for q in qs]
    if cell.traffic["job"] == "pagerank_placed":
        ref = [(q, v * ds.num_vertices, s) for q, v, s in ref]
    assert cell.job.compare(ds, cell.traffic, ref)[1] == 0


def test_wcc_reference_is_the_least_id_of_each_component(tmp_path):
    from graphs import dataset
    d, spec = small_bench(tmp_path, scale=9)
    cell = run.Cell(spec, WCC, d)
    ds = dataset(cell.cfg)
    labels, steps = cell.job.reference(ds, None, cell.traffic)
    parent = {int(v): int(v) for v in ds.vertices}

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x
    for s, t in zip(ds.src.tolist(), ds.dst.tolist()):
        a, b = find(s), find(t)
        if a != b:
            parent[max(a, b)] = min(a, b)
    want = np.array([find(int(v)) for v in ds.vertices], np.float64)
    assert np.array_equal(labels, want)
    assert steps >= 2
    assert cell.job.least_bytes(ds, None, cell.traffic) > 0


def _recorded():
    with gzip.open(os.path.join(DATA, f"{PLACED}.scale10.progtrace.json.gz"),
                   "rt") as f:
        return json.load(f)


@pytest.fixture
def recorded(monkeypatch):
    """The recorded events, with the readers pointed at them."""
    ev = _recorded()
    red = progtrace.reduce(ev)
    link = _reader("collective.link_share")
    monkeypatch.setattr(progtrace, "reading", lambda ctx: red)
    monkeypatch.setattr(progtrace, "newest_trace", lambda pattern: "trace")
    monkeypatch.setattr(link.os.path, "getmtime", lambda path: 0.0)
    monkeypatch.setattr(link, "crossing", lambda path, mtime: (
        len(ev["jobs"]), ev["devices"],
        link.window_crossing(ev["spans"], ev["jobs"])))
    steps = sum(s[4]["supersteps"] for s in ev["spans"]
                if s[0] == "graphx.pregel")
    ctx = types.SimpleNamespace(jobs=len(ev["jobs"]), supersteps=steps)
    return ev, red, link, ctx


def test_recorded_four_chip_trace(recorded):
    ev, red, link, ctx = recorded
    assert ev["devices"] == 4 and red["jobs"] == 1
    assert red["device_s_by_scope"].get("graphx.collective", 0) > 0
    syncs = [s for s in ev["spans"] if s[0] == progtrace.SYNC]
    assert syncs and all(s[4]["bytes_crossing"] > 0 for s in syncs)
    devices = {s[0]: s[4].get("devices") for s in ev["spans"]
               if s[0] in ("graphx.algorithm", "graphx.pregel")}
    assert devices == {"graphx.algorithm": 4, "graphx.pregel": 4}


def test_collective_readers_on_recorded_trace(recorded):
    ev, red, link, ctx = recorded
    ms = _reader("collective.ms_per_superstep").read(ctx)
    assert ms == pytest.approx(
        1e3 * red["device_s_by_scope"]["graphx.collective"] / ctx.supersteps)
    assert ms > 0
    share = link.read(ctx)
    (w0, _), (_, w1) = ev["jobs"][0], ev["jobs"][-1]
    total = sum(s[4]["bytes_crossing"] for s in ev["spans"]
                if s[0] == progtrace.SYNC and w0 <= s[2] and s[3] <= w1)
    assert total < sum(s[4]["bytes_crossing"] for s in ev["spans"]
                       if s[0] == progtrace.SYNC)   # the warm-up's left out
    assert share == pytest.approx(
        100 * total / 4 / 200e9 / red["device_s_by_scope"]["graphx.collective"])
    assert 0 < share < 100


def test_collective_readers_read_none_without_scope_or_counter(recorded,
                                                               monkeypatch):
    ev, red, link, ctx = recorded
    bare = dict(red, device_s_by_scope={"": 1.0})
    monkeypatch.setattr(progtrace, "reading", lambda ctx: bare)
    assert _reader("collective.ms_per_superstep").read(ctx) is None
    assert link.read(ctx) is None
    monkeypatch.setattr(progtrace, "reading", lambda ctx: red)
    monkeypatch.setattr(link, "crossing", lambda path, mtime: (
        len(ev["jobs"]), 4, None))
    assert link.read(ctx) is None


def test_crossing_reads_the_counter_from_a_trace_file(tmp_path):
    """`crossing` on a real trace file: a placed PageRank job on the CPU's
    four simulated devices (no TPU plane, so no chip is counted)."""
    import glob
    from repro.core import Graph, algorithms
    from repro.data import rmat, symmetrize
    gd = symmetrize(rmat(7, 4, seed=2))
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=4).place(
        jax.devices()[:4])
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.job"):
            res = algorithms.pagerank(g, num_iters=3, kernel_mode="ref",
                                      track_metrics=True)
            jax.block_until_ready(res.graph.vdata)
    path = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")[0]
    link = _reader("collective.link_share")
    jobs, chips, total = link.crossing(path, 0.0)
    assert (jobs, chips) == (1, 0)
    assert total == sum(int(m["fwd"].bytes_link_modeled
                            + m["back"].bytes_link_modeled)
                        for m in res.metrics) > 0

"""`correct` must come out false when the timed path is broken underneath
or the control stands in for the program.

Each fault is planted in the program for the duration of one rehearsal run
(the jnp oracle kernels, to keep the runs short), which then goes through
the harness's own window and check as a chip run would: a superstep that
returns its state unchanged; half of every sweep's edges left out; the
exchange between partitions left out; an answer altered where the
superstep produces it."""
from __future__ import annotations

import importlib

import numpy as np
import pytest

from helpers import run, small_bench

CELLS = ["ga500-16.pagerank", "g500-16.bfs"]


def _unchanged_state(mp):
    pregel = importlib.import_module("repro.core.pregel")
    orig = pregel._superstep

    def step(g, *a, **kw):
        _, live, metrics = orig(g, *a, **kw)
        return g, live, metrics
    mp.setattr(pregel, "_superstep", step)


def _half_the_edges(mp):
    import jax.numpy as jnp
    from repro.kernels import ops
    orig = ops.triplet

    def triplet(x, ev, src_slot, dst_slot, live, *a, **kw):
        half = (jnp.arange(live.shape[0]) % 2) == 0
        return orig(x, ev, src_slot, dst_slot, live & half, *a, **kw)
    mp.setattr(ops, "triplet", triplet)


def _no_exchange(mp):
    from repro.core.exchange import LocalExchange
    mp.setattr(LocalExchange, "transpose", lambda self, x: x)


def _altered_answer(mp):
    import jax
    pregel = importlib.import_module("repro.core.pregel")
    orig = pregel._superstep

    def step(g, *a, **kw):
        g2, live, metrics = orig(g, *a, **kw)
        vdata = jax.tree.map(lambda v: v.at[0, 0].add(1.0), g2.vdata)
        return g2.replace(vdata=vdata, view=g2.view), live, metrics
    mp.setattr(pregel, "_superstep", step)


FAULTS = {"unchanged_state": _unchanged_state,
          "half_the_edges": _half_the_edges,
          "no_exchange": _no_exchange,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, workload,
                                      fault):
    d, spec = small_bench(tmp_path, scale=7)
    FAULTS[fault](monkeypatch)
    r = run.run_cell(spec, workload, 77, 0.0, False, bench_dir=d,
                     rehearsal=True, kernel_mode="ref")
    assert r["correct"] is False, r["checks"]
    assert r["failed"] >= 1


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tmp_path, workload):
    d, spec = small_bench(tmp_path, scale=7)
    r = run.run_cell(spec, workload, 77, 0.0, False, bench_dir=d,
                     rehearsal=True, kernel_mode="ref")
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 987654321])
def test_control_is_not_correct(tmp_path, workload, seed):
    """The control takes the program's place in the comparison: PageRank's
    reference in bfloat16, BFS's reference halted one superstep early."""
    from graphs import dataset
    d, spec = small_bench(tmp_path, scale=10)
    cell = run.Cell(spec, workload, d)
    ds = dataset(cell.cfg)
    qs = cell.job.queries(ds, cell.traffic, seed)[:3]
    results = [(q, *cell.job.control(ds, q, cell.traffic, seed)) for q in qs]
    values, failed = cell.job.compare(ds, cell.traffic, results)
    limits = cell.traffic["limits"]
    assert failed == len(qs)
    assert any(values[k] > limits[k] for k in values), values
    # the same comparison passes the reference itself
    ref = [(q, *cell.job.reference(ds, q, cell.traffic)) for q in qs]
    if cell.traffic["job"] == "pagerank":
        ref = [(q, v * ds.num_vertices, s) for q, v, s in ref]
    else:
        ref = [(q, np.where(np.isinf(v), cell.job.UNREACHED, v), s)
               for q, v, s in ref]
    assert cell.job.compare(ds, cell.traffic, ref)[1] == 0

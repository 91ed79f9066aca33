"""Graphalytics PageRank through `repro.core.algorithms.pagerank`.

Semantics (LDBC Graphalytics specification, "PageRank"): PR_0(v) = 1/|V|,
then `num_iters` synchronous iterations of
    PR(v) = (1 - d)/|V| + d * (sum over in-edges (u, v) of PR(u)/outdeg(u)
                               + sum over dangling w of PR(w)/|V|),
with d = 1 - reset.  The program keeps ranks scaled by |V| (it starts every
vertex at 1.0), so its ranks are divided by |V| before the comparison.
"""
from __future__ import annotations

import numpy as np

LEAF = "pr"


def queries(ds, traffic: dict, seed: int) -> list:
    """Every job is the same job on the resident graph."""
    return [None]


def run(g, query, traffic: dict, kernel_mode: str):
    """One job through the program's public entry: (rank leaf, supersteps)."""
    from repro.core import algorithms
    res = algorithms.pagerank(g, num_iters=traffic["num_iters"],
                              reset=traffic["reset"], kernel_mode=kernel_mode)
    return res.graph.vdata[LEAF], res.supersteps


def reference(ds, query, traffic: dict) -> tuple[np.ndarray, int]:
    """float64 ranks by vertex position, and the supersteps the job takes."""
    n = ds.num_vertices
    s, d = ds.index_of(ds.src), ds.index_of(ds.dst)
    deg = np.bincount(s, minlength=n).astype(np.float64)
    damp = 1.0 - traffic["reset"]
    pr = np.full(n, 1.0 / n)
    for _ in range(traffic["num_iters"]):
        dangling = pr[deg == 0].sum()
        msg = np.bincount(d, weights=(pr / np.maximum(deg, 1))[s], minlength=n)
        pr = (1.0 - damp) / n + damp * (msg + dangling / n)
    return pr, traffic["num_iters"]


def control(ds, query, traffic: dict, seed: int) -> tuple[np.ndarray, int]:
    """The reference put in the program's place, one precision lower: ranks,
    degrees and messages held in bfloat16 on the device, in the program's
    scale, each sum accumulated in float32 in the run's edge order (as the
    MXU accumulates bfloat16 products)."""
    import jax
    import jax.numpy as jnp
    from graphs import edge_order
    o = edge_order(ds, seed)
    n = ds.num_vertices
    s_np = ds.index_of(ds.src[o])
    s = jnp.asarray(s_np, jnp.int32)
    d = jnp.asarray(ds.index_of(ds.dst[o]), jnp.int32)
    bf = jnp.bfloat16
    deg = jnp.asarray(np.maximum(np.bincount(s_np, minlength=n), 1), bf)
    reset = jnp.asarray(traffic["reset"], bf)

    @jax.jit
    def step(pr):
        msg = jax.ops.segment_sum((pr / deg)[s].astype(jnp.float32), d,
                                  num_segments=n)
        return (reset + (1 - reset) * msg.astype(bf)).astype(bf)

    pr = jnp.ones(n, bf)
    for _ in range(traffic["num_iters"]):
        pr = step(pr)
    return np.asarray(pr.astype(jnp.float32)), traffic["num_iters"]


def compare(ds, traffic: dict, results: list) -> tuple[dict, int]:
    """results: [(query, values by vertex position, supersteps)] of every job
    due in the window.  Returns ({check: value}, jobs that failed)."""
    want, steps = reference(ds, None, traffic)
    lim = traffic["limits"]
    worst_err, worst_off, bad = 0.0, 0, 0
    for _, got, n_steps in results:
        err = float(np.max(np.abs(got / ds.num_vertices - want) / want))
        if not np.isfinite(err):
            err = float("inf")
        off = abs(int(n_steps) - steps)
        worst_err, worst_off = max(worst_err, err), max(worst_off, off)
        bad += not (err <= lim["rank_rel_err"] and off <= lim["supersteps_off"])
    return {"rank_rel_err": worst_err, "supersteps_off": worst_off}, bad


def least_bytes(ds, query, traffic: dict) -> int:
    """Least HBM bytes one job's triplet sweeps must move: each active edge's
    endpoint ids (int32) and the weight its message reads (f32) once, each
    active source's read values once, each destination's aggregate once.
    The degree count sends from every edge and reads no value or weight;
    each of the num_iters supersteps has every vertex active and reads the
    source's rank and degree and the edge weight."""
    e, v = ds.src.size, ds.num_vertices
    degree_sweep = e * 8 + v * 4
    superstep = e * (8 + 4) + v * 8 + v * 4
    return degree_sweep + traffic["num_iters"] * superstep

"""Graph500 kernel 2 (breadth-first search) through
`repro.core.algorithms.sssp` with the default unit edge weights, so the
distances it returns are hop counts.

Semantics: every vertex gets its hop distance from the search key; a vertex
the search cannot reach keeps the program's "unreached" value (float32 max).
A query runs until a superstep changes no vertex, so it takes depth + 1
supersteps, where depth is the largest hop distance reached.
"""
from __future__ import annotations

import numpy as np

LEAF = "dist"
UNREACHED = float(np.finfo(np.float32).max)


def levels(ds, key_pos: int, csr=None) -> np.ndarray:
    """Hop distance of every vertex position from `key_pos` (-1 unreached),
    level-synchronous over the CSR."""
    indptr, nbr = ds.csr() if csr is None else csr
    dist = np.full(ds.num_vertices, -1, np.int64)
    dist[key_pos] = 0
    frontier = np.array([key_pos])
    level = 0
    while frontier.size:
        nb = _neighbours(indptr, nbr, frontier)
        nb = np.unique(nb[dist[nb] < 0])
        level += 1
        dist[nb] = level
        frontier = nb
    return dist


def _neighbours(indptr, nbr, frontier):
    starts = indptr[frontier]
    counts = indptr[frontier + 1] - starts
    offs = np.repeat(starts - np.cumsum(counts) + counts, counts)
    return nbr[offs + np.arange(counts.sum())]


def queries(ds, traffic: dict, seed: int) -> list:
    """Search keys drawn uniformly, without replacement, among vertices of
    degree >= min_degree (Graph500 kernel 2), from the traffic's key_seed
    and in drawn order.  They are the same for every run seed, as the graph
    is, so every run does the same work; the run's seed orders the edge
    list."""
    deg = np.diff(ds.csr()[0])
    cand = np.flatnonzero(deg >= traffic["min_degree"])
    rng = np.random.default_rng([traffic["key_seed"], 2])
    keys = rng.choice(cand, traffic["search_keys"], replace=False)
    return [int(ds.vertices[k]) for k in keys]


def run(g, key, traffic: dict, kernel_mode: str):
    """One query through the program's public entry: (dist leaf,
    supersteps)."""
    from repro.core import algorithms
    res = algorithms.sssp(g, key, kernel_mode=kernel_mode)
    return res.graph.vdata[LEAF], res.supersteps


def reference(ds, key, traffic: dict, csr=None) -> tuple[np.ndarray, int]:
    """Hop distances by vertex position (inf unreached) and supersteps."""
    lv = levels(ds, int(ds.index_of(np.array([key]))[0]), csr)
    dist = np.where(lv >= 0, lv.astype(np.float64), np.inf)
    return dist, int(lv.max()) + 1


def control(ds, key, traffic: dict, seed: int) -> tuple[np.ndarray, int]:
    """The reference with one guarantee broken: it halts one superstep
    early, so the last level is never reached.  (Hop counts are small
    integers, exact in bfloat16 and float8 too, so a lower precision breaks
    nothing here.)"""
    dist, steps = reference(ds, key, traffic)
    last = dist[np.isfinite(dist)].max()
    return np.where(dist == last, UNREACHED, dist), steps - 1


def compare(ds, traffic: dict, results: list) -> tuple[dict, int]:
    """results: [(key, distances by vertex position, supersteps)] of every
    query due in the window.  Returns ({check: value}, queries that
    failed)."""
    csr = ds.csr()
    lim = traffic["limits"]
    mism, worst_off, bad = 0, 0, 0
    for key, got, n_steps in results:
        want, steps = reference(ds, key, traffic, csr)
        got_unreached = got >= UNREACHED
        m = int(np.sum(np.where(np.isinf(want), ~got_unreached,
                                got_unreached | (got != want))))
        off = abs(int(n_steps) - steps)
        mism, worst_off = mism + m, max(worst_off, off)
        bad += not (m <= lim["dist_mismatches"]
                    and off <= lim["supersteps_off"])
    return {"dist_mismatches": mism, "supersteps_off": worst_off}, bad


def least_bytes(ds, key, traffic: dict) -> int:
    """Least HBM bytes one query's supersteps must move, from the
    reference's own frontiers: in superstep k the sources are the vertices
    at distance k - 1; each of their out-edges moves its endpoint ids
    (int32) and weight (f32) once, each source its distance (f32) once, and
    each destination reached its aggregate (f32) once.  The last superstep
    sends from the deepest level and changes nothing."""
    indptr, nbr = csr = ds.csr()
    lv = levels(ds, int(ds.index_of(np.array([key]))[0]), csr)
    total = 0
    for k in range(int(lv.max()) + 1):
        frontier = np.flatnonzero(lv == k)
        nb = _neighbours(indptr, nbr, frontier)
        total += nb.size * 12 + frontier.size * 4 + np.unique(nb).size * 4
    return int(total)

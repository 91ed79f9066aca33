"""Graphalytics weakly connected components through
`repro.core.algorithms.connected_components`.

Semantics (LDBC Graphalytics specification, "Weakly Connected
Components"): every vertex is labelled with its component; here the label
is the least vertex id in the component, which the program's min-label
propagation reaches.  The program starts every vertex at its own id and,
superstep by superstep, takes the least label among itself and its
neighbours, all vertices at once, until a superstep changes no label; that
last superstep counts.
"""
from __future__ import annotations

import numpy as np

LEAF = "cc"


def queries(ds, traffic: dict, seed: int) -> list:
    """Every job is the same job on the resident graph."""
    return [None]


def run(g, query, traffic: dict, kernel_mode: str):
    """One job through the program's public entry: (label leaf,
    supersteps)."""
    from repro.core import algorithms
    res = algorithms.connected_components(g, kernel_mode=kernel_mode)
    return res.graph.vdata[LEAF], res.supersteps


def _propagation(ds) -> list[np.ndarray]:
    """Labels by vertex position after each synchronous superstep of
    min-label propagation, from the vertex ids, up to the first superstep
    that changes nothing (included)."""
    s, d = ds.index_of(ds.src), ds.index_of(ds.dst)
    order = np.argsort(d, kind="stable")
    s, d = s[order], d[order]
    starts = np.flatnonzero(np.r_[True, d[1:] != d[:-1]])
    heads = d[starts]
    lab = ds.vertices.copy()
    out = []
    while True:
        new = lab.copy()
        new[heads] = np.minimum(lab[heads],
                                np.minimum.reduceat(lab[s], starts))
        out.append(new)
        if np.array_equal(new, lab):
            return out
        lab = new


def reference(ds, query, traffic: dict) -> tuple[np.ndarray, int]:
    """Least vertex id of each vertex's component, by vertex position, and
    the supersteps the job takes."""
    steps = _propagation(ds)
    return steps[-1].astype(np.float64), len(steps)


def control(ds, query, traffic: dict, seed: int) -> tuple[np.ndarray, int]:
    """The reference with one guarantee broken: it halts one superstep
    early, so the labels of the last superstep that changed any are never
    taken.  (Labels are vertex ids, exact in the program's int32; a lower
    precision would wrap them rather than round them.)"""
    steps = _propagation(ds)
    before = steps[-3] if len(steps) >= 3 else ds.vertices
    return before.astype(np.float64), len(steps) - 1


def compare(ds, traffic: dict, results: list) -> tuple[dict, int]:
    """results: [(query, labels by vertex position, supersteps)] of every
    job due in the window.  Returns ({check: value}, jobs that failed)."""
    want, steps = reference(ds, None, traffic)
    lim = traffic["limits"]
    mism, worst_off, bad = 0, 0, 0
    for _, got, n_steps in results:
        m = int(np.sum(got != want))
        off = abs(int(n_steps) - steps)
        mism, worst_off = mism + m, max(worst_off, off)
        bad += not (m <= lim["label_mismatches"]
                    and off <= lim["supersteps_off"])
    return {"label_mismatches": mism, "supersteps_off": worst_off}, bad


def least_bytes(ds, query, traffic: dict) -> int:
    """Least HBM bytes one job's triplet sweeps must move, from the
    reference's own frontiers: in the first superstep every vertex sends,
    later only those whose label changed in the superstep before; each of
    their out-edges moves its endpoint ids (int32) once, each source its
    label (int32) once, and each destination of an edge sent on its
    aggregate (int32) once."""
    s, d = ds.index_of(ds.src), ds.index_of(ds.dst)
    lab, sending = ds.vertices, np.ones(ds.num_vertices, bool)
    total = 0
    for new in _propagation(ds):
        edges = sending[s]
        total += (int(edges.sum()) * 8 + int(sending.sum()) * 4
                  + np.unique(d[edges]).size * 4)
        sending, lab = new != lab, new
    return total

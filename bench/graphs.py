"""The benchmark's own graph generator, kept here so that a change to the
program's `data/graphs.py` cannot move the yardstick.

Graph500 kernel 1 (graph500.org, "Graph500 benchmark specification"):
Kronecker (R-MAT) edge tuples with initiator a/b/c/d, 2**scale vertices and
edge_factor * 2**scale tuples, then a random relabelling of the vertices.
The edge generation is a copy of the program's `repro.data.graphs.rmat`
(one uniform draw per bit and tuple, quadrant by the cumulative a, b, c).

`dataset()` turns the tuples into the undirected graph that LDBC
Graphalytics' `graph500-XX` datasets and Graph500's BFS both traverse: both
directions of every tuple, duplicates and self-loops dropped.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Dataset:
    src: np.ndarray          # [E] int64, both directions of every edge
    dst: np.ndarray
    vertices: np.ndarray     # [V] int64 sorted ids of the graph's vertices

    @property
    def num_vertices(self) -> int:
        return int(self.vertices.size)

    @property
    def num_undirected_edges(self) -> int:
        return int(self.src.size // 2)

    def index_of(self, ids: np.ndarray) -> np.ndarray:
        """Position of each vertex id in `vertices`."""
        return np.searchsorted(self.vertices, ids)

    def csr(self) -> tuple[np.ndarray, np.ndarray]:
        """(indptr [V+1], neighbour indices [E]) over vertex positions; the
        edges are sorted by source, so the neighbour list is `dst`."""
        s = self.index_of(self.src)
        indptr = np.zeros(self.num_vertices + 1, np.int64)
        np.cumsum(np.bincount(s, minlength=self.num_vertices), out=indptr[1:])
        return indptr, self.index_of(self.dst)


def kronecker_tuples(scale: int, edge_factor: int, seed: int, *,
                     a: float, b: float, c: float):
    """Graph500 Kronecker tuples over [0, 2**scale), randomly relabelled."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        r = rng.random(m)
        go_right = (r >= a) & (r < a + b) | (r >= a + b + c)
        go_down = r >= a + b
        src |= go_down.astype(np.int64) << bit
        dst |= go_right.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[src], perm[dst]


def dataset(cfg: dict) -> Dataset:
    """The configuration's undirected graph: every edge in both directions,
    no duplicates, no self-loops.  With `keep_isolated` all 2**scale ids are
    vertices (Graph500); without it only ids with an edge are (Graphalytics
    lists no isolated vertex)."""
    n = 1 << cfg["scale"]
    s, d = kronecker_tuples(cfg["scale"], cfg["edge_factor"],
                            cfg["dataset_seed"], a=cfg["a"], b=cfg["b"],
                            c=cfg["c"])
    keep = s != d
    s, d = s[keep], d[keep]
    key = np.unique(np.concatenate([s * n + d, d * n + s]))
    src, dst = key // n, key % n
    if cfg["keep_isolated"]:
        vertices = np.arange(n, dtype=np.int64)
    else:
        vertices = np.unique(src)
    return Dataset(src=src, dst=dst, vertices=vertices)


def edge_order(ds: Dataset, seed: int) -> np.ndarray:
    """The order in which a run hands the edge list to the program: a
    permutation drawn from the run's seed.  Graph500 kernel 1 takes the edge
    list in random order; the graph, and every shape built from it, stays
    the same for every seed."""
    return np.random.default_rng([seed % 2**64, 1]).permutation(ds.src.size)

"""Each job kind's least-bytes count against a count by hand, on a tiny
graph: the path 0-1-2 with a leaf 3 on vertex 1, undirected."""
from __future__ import annotations

import numpy as np

from helpers import run  # noqa: F401  (puts bench/ on sys.path)
from graphs import Dataset


SRC = np.array([0, 1, 1, 1, 2, 3])
DST = np.array([1, 0, 2, 3, 1, 1])
TINY = Dataset(src=SRC, dst=DST, vertices=np.arange(4))


def _job(name):
    return run.load_module(f"{run.BENCH}/jobs/{name}.py")


def test_pagerank_least_bytes_by_hand():
    # degree sweep: 6 edges x 2 int32 ids + 4 degree aggregates
    # superstep: 6 edges x (2 ids + weight) + 4 sources x (rank, degree)
    #            + 4 rank aggregates
    degree = 6 * 8 + 4 * 4
    step = 6 * 12 + 4 * 8 + 4 * 4
    got = _job("pagerank").least_bytes(TINY, None, {"num_iters": 2})
    assert got == degree + 2 * step == 304


def test_bfs_least_bytes_by_hand():
    # from key 0: level 0 {0}, level 1 {1}, level 2 {2, 3}; 3 supersteps
    # k=1: edge 0->1, source 0, destination 1         12 + 4 + 4
    # k=2: edges 1->0,1->2,1->3, source 1, 3 dests     36 + 4 + 12
    # k=3: edges 2->1, 3->1, sources 2 and 3, dest 1   24 + 8 + 4
    assert _job("bfs").least_bytes(TINY, 0, {}) == 20 + 52 + 36


def test_bfs_reference_by_hand():
    dist, steps = _job("bfs").reference(TINY, 2, {})
    assert dist.tolist() == [2.0, 1.0, 0.0, 2.0] and steps == 3


def test_pagerank_reference_matches_the_closed_form_on_a_regular_graph():
    # a 4-cycle: every vertex has degree 2, so every rank stays 1/|V|
    cyc = Dataset(src=np.array([0, 0, 1, 1, 2, 2, 3, 3]),
                  dst=np.array([1, 3, 0, 2, 1, 3, 0, 2]),
                  vertices=np.arange(4))
    pr, steps = _job("pagerank").reference(
        cyc, None, {"num_iters": 10, "reset": 0.15})
    assert np.allclose(pr, 0.25, rtol=1e-12) and steps == 10


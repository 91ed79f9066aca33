"""Tier-1 analytics-pipeline smoke (fast lane).

Runs the paper's Fig. 10 analytics tail — the operator chain whose wire
traffic the graph-resident view (DESIGN.md §3.1) exists to eliminate — at
CI scale, warm vs cold, so an end-to-end pipeline regression (an operator
re-shipping a clean view, or a cached chain diverging from the cold one)
fails CI.
"""
import numpy as np

from repro.core import Graph, algorithms as alg
from repro.core import transport as transport_mod
from repro.data import rmat


def analytics_tail(graph, *, reuse: bool, thresh: float):
    """The pipeline's graph-analytics TAIL: rank-mass flow -> restrict to
    high-rank vertices -> rank-mass among them.  Three operator stages on
    the PageRank result, with per-stage `bytes_shipped` read off the
    graph's wire log (DESIGN.md §3.1).

    reuse=True chains on the graph as Pregel left it — the graph-resident
    view carries `deg` (and the visibility state) across every stage
    boundary, so only dirty leaves ship; reuse=False strips the view
    before each consumer, which is exactly what a unified engine WITHOUT
    cross-operator view maintenance pays.  The two variants must agree
    bit-exactly while reuse moves strictly fewer bytes."""
    strip = (lambda x: x) if reuse else (lambda x: x.replace(view=None))
    g = strip(graph)

    def send_mass(sv, ev, dv):
        return {"m": sv["pr"] / sv["deg"] * ev["w"]}

    stages, b_prev = [], float(g.bytes_shipped)
    transport_mod.SHIP_EVENTS.clear()
    mass, _, g, _ = g.mrTriplets(send_mass, "sum")
    b = float(g.bytes_shipped)
    stages.append(round(b - b_prev))
    b_prev = b
    g = strip(g).subgraph(vpred=lambda vid, v: v["pr"] > thresh)
    b = float(g.bytes_shipped)
    stages.append(round(b - b_prev))
    b_prev = b
    g = strip(g)
    top_mass, _, g, _ = g.mrTriplets(send_mass, "sum")
    stages.append(round(float(g.bytes_shipped) - b_prev))
    ships = len(transport_mod.SHIP_EVENTS)
    return mass, top_mass, g, {
        "stage_bytes_shipped": stages,
        "total_bytes_shipped": sum(stages),
        "route_ships": ships,
    }


def test_fig10_tail_view_reuse_smoke():
    gd = rmat(7, 5, seed=1)
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=4)
    res = alg.pagerank(g, num_iters=5, kernel_mode="ref")
    pr = np.asarray(res.graph.vdata["pr"])[np.asarray(res.graph.vmask)]
    thresh = float(np.median(pr))

    mass_w, top_w, gw, acct_w = analytics_tail(res.graph, reuse=True,
                                               thresh=thresh)
    mass_c, top_c, gc, acct_c = analytics_tail(res.graph, reuse=False,
                                               thresh=thresh)
    # caching changes ships, never values (f32 bit-exact)
    np.testing.assert_array_equal(np.asarray(mass_w["m"]),
                                  np.asarray(mass_c["m"]))
    np.testing.assert_array_equal(np.asarray(top_w["m"]),
                                  np.asarray(top_c["m"]))
    # ... and the reuse pipeline is strictly cheaper on the wire, with the
    # final stage free (everything it reads was just shipped)
    assert acct_w["total_bytes_shipped"] < acct_c["total_bytes_shipped"]
    assert acct_w["route_ships"] < acct_c["route_ships"]
    assert acct_w["stage_bytes_shipped"][-1] < \
        acct_c["stage_bytes_shipped"][-1]

"""Algorithm library vs oracles (paper §5.1 workloads at reduced scale)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import Graph, algorithms as alg, pregel_fused
from repro.data import rmat, symmetrize, chain, star


def graph_of(gd, p=4, weights=None):
    ev = {"w": weights if weights is not None
          else np.ones(gd.num_edges, np.float32)}
    return Graph.from_edges(gd.src, gd.dst, edge_values=ev, num_partitions=p)


# "interpret" runs the engine's Pallas kernels (interpret mode) through the
# same public path a chip runs compiled; "auto" picks the jnp oracle on CPU.
@pytest.mark.parametrize("seed,p,kernel_mode", [
    (0, 2, "auto"), pytest.param(1, 4, "auto", marks=pytest.mark.slow),
    pytest.param(2, 6, "auto", marks=pytest.mark.slow),
    (0, 2, "interpret")], ids=["0-2", "1-4", "2-6", "0-2-interpret"])
def test_pagerank_matches_reference(seed, p, kernel_mode):
    gd = rmat(6, 4, seed=seed)
    res = alg.pagerank(graph_of(gd, p), num_iters=15, kernel_mode=kernel_mode)
    vids, vals = res.graph.vertices_to_numpy()
    ref = alg.pagerank_reference(gd.src, gd.dst, gd.num_vertices, 15)
    np.testing.assert_allclose(vals["pr"], ref[vids], rtol=1e-4)


def test_pagerank_with_tolerance_converges_and_skips():
    gd = rmat(7, 4, seed=3)
    res = alg.pagerank(graph_of(gd), num_iters=50, tol=1e-4,
                       track_metrics=True)
    assert res.supersteps < 50
    live = [m["live_edges"] for m in res.metrics]
    assert live[-1] < live[0]  # active set shrinks (paper Fig. 6 behaviour)


@pytest.mark.parametrize("maker", [chain, star])
def test_cc_on_special_graphs(maker):
    gd = symmetrize(maker(30))
    res = alg.connected_components(graph_of(gd))
    _, vals = res.graph.vertices_to_numpy()
    assert set(np.asarray(vals["cc"]).tolist()) == {0}


def test_cc_matches_union_find():
    gd = symmetrize(rmat(6, 2, seed=5))
    res = alg.connected_components(graph_of(gd))
    vids, vals = res.graph.vertices_to_numpy()
    got = dict(zip(vids.tolist(), np.asarray(vals["cc"]).tolist()))
    want = alg.connected_components_reference(gd.src, gd.dst, vids)
    assert got == want


@pytest.mark.parametrize("kernel_mode", ["auto", "interpret"])
def test_cc_dispatches_fused_and_matches_reference(kernel_mode):
    """The int32 min-label Pregel loop rides the fused triplet kernel end to
    end (f32 staging is exact under the id-bound guard) and agrees with the
    union-find oracle EXACTLY — and with the unfused plan bit-for-bit."""
    gd = symmetrize(rmat(6, 3, seed=17))
    res = alg.connected_components(graph_of(gd), kernel_mode=kernel_mode,
                                   track_metrics=True)
    assert res.metrics[0]["plan"] == "fused"
    assert res.graph.vdata["cc"].dtype == jnp.int32
    vids, vals = res.graph.vertices_to_numpy()
    got = dict(zip(vids.tolist(), np.asarray(vals["cc"]).tolist()))
    want = alg.connected_components_reference(gd.src, gd.dst, vids)
    assert got == want
    # pure execution-strategy change: unfused run is identical
    res_u = alg.connected_components(graph_of(gd), kernel_mode="unfused",
                                     track_metrics=True)
    assert res_u.metrics[0]["plan"] == "unfused"
    assert res_u.supersteps == res.supersteps
    _, vals_u = res_u.graph.vertices_to_numpy()
    np.testing.assert_array_equal(np.asarray(vals["cc"]),
                                  np.asarray(vals_u["cc"]))


@pytest.mark.parametrize("kernel_mode", ["auto", "interpret"])
def test_sssp(kernel_mode):
    # weighted path 0 -> 1 -> 2 ... with weight 2 each
    n = 12
    src = np.arange(n - 1, dtype=np.int64)
    dst = src + 1
    w = np.full(n - 1, 2.0, np.float32)
    res = alg.sssp(graph_of(type("G", (), {
        "src": src, "dst": dst, "num_edges": n - 1,
        "num_vertices": n})(), weights=w), source=0,
        max_supersteps=n + 2, kernel_mode=kernel_mode)
    vids, vals = res.graph.vertices_to_numpy()
    for vid, d in zip(vids, vals["dist"]):
        assert d == 2.0 * vid


def test_label_propagation_two_cliques():
    # two dense cliques with one bridge; labels should settle per clique
    edges = []
    for a in range(5):
        for b in range(5):
            if a != b:
                edges.append((a, b))
                edges.append((a + 5, b + 5))
    edges.append((0, 5))
    src = np.array([e[0] for e in edges], np.int64)
    dst = np.array([e[1] for e in edges], np.int64)
    gd = type("G", (), {"src": src, "dst": dst, "num_edges": len(edges),
                        "num_vertices": 10})()
    g = graph_of(gd).mapV(lambda vid, v: {"label": (vid // 5).astype(jnp.int32)})
    res = alg.label_propagation(g, num_labels=2, num_iters=5)
    vids, vals = res.graph.vertices_to_numpy()
    labels = dict(zip(vids.tolist(), np.asarray(vals["label"]).tolist()))
    assert all(labels[v] == 0 for v in range(5))
    assert all(labels[v] == 1 for v in range(5, 10))


@pytest.mark.slow
def test_pregel_fused_equals_host_loop():
    gd = rmat(6, 4, seed=7)
    g = alg.attach_out_degree(graph_of(gd))
    g = g.mapV(lambda vid, v: {**v, "pr": jnp.float32(1.0)})

    def send(sv, ev, dv):
        return {"m": sv["pr"] / sv["deg"] * ev["w"]}

    def vprog(vid, v, msg):
        return {**v, "pr": 0.15 + 0.85 * msg["m"]}

    from repro.core import pregel
    host = pregel(g, vprog, send, "sum", default_msg={"m": jnp.float32(0.0)},
                  max_supersteps=5, skip_stale=None)
    fused_g, steps = pregel_fused(
        g, vprog, send, "sum", default_msg={"m": jnp.float32(0.0)},
        max_supersteps=5, skip_stale=None,
        changed_fn=lambda o, n: jnp.abs(o["pr"] - n["pr"]) > 0)  # run all 5
    np.testing.assert_allclose(np.asarray(host.graph.vdata["pr"]),
                               np.asarray(fused_g.vdata["pr"]), rtol=1e-5)


@pytest.mark.slow
def test_coarsen_listing7():
    """Contract edges within same 'domain' (vid // 4); Listing 7 pipeline."""
    gd = symmetrize(rmat(5, 3, seed=9))
    vids = np.arange(gd.num_vertices, dtype=np.int64)
    g = Graph.from_edges(
        gd.src, gd.dst, vertex_keys=vids,
        vertex_values={"x": np.ones(gd.num_vertices, np.float32),
                       "dom": (vids // 4).astype(np.int32)},
        default_vertex={"x": np.float32(0), "dom": np.int32(-1)},
        num_partitions=4)
    coarse = alg.coarsen(
        g, epred=lambda sv, ev, dv: sv["dom"] == dv["dom"], merge="sum")
    cvids, cvals = coarse.vertices_to_numpy()
    # super-vertex property = sum of member 'x' => total mass preserved
    assert float(np.sum(cvals["x"])) == float(gd.num_vertices)
    # no intra-domain edges remain
    es, ed, _ = coarse.edges_to_numpy()
    doms = dict(zip(cvids.tolist(), cvals["dom"].tolist()))
    assert len(cvids) < gd.num_vertices


@pytest.mark.slow
def test_triangle_count_matches_bruteforce():
    gd = symmetrize(rmat(5, 3, seed=11))
    g = graph_of(gd, p=4)
    per_v, total, _ = alg.triangle_count(g, n_ids=gd.num_vertices,
                                         kernel_mode="ref")
    want = alg.triangle_count_reference(gd.src, gd.dst, gd.num_vertices)
    assert int(round(float(total))) == want
    # per-vertex counts are consistent with the total
    np.testing.assert_allclose(float(np.asarray(per_v).sum()) / 3.0,
                               float(total), rtol=1e-6)


@pytest.mark.slow
def test_triangle_count_clique_and_star():
    # K4: 4 triangles; star: none
    edges = [(a, b) for a in range(4) for b in range(4) if a != b]
    src = np.array([e[0] for e in edges], np.int64)
    dst = np.array([e[1] for e in edges], np.int64)
    gd = type("G", (), {"src": src, "dst": dst, "num_edges": len(edges),
                        "num_vertices": 4})()
    _, total, _ = alg.triangle_count(graph_of(gd), n_ids=4, kernel_mode="ref")
    assert int(round(float(total))) == 4
    sd = symmetrize(star(16))
    _, t2, _ = alg.triangle_count(graph_of(sd), n_ids=16, kernel_mode="ref")
    assert int(round(float(t2))) == 0

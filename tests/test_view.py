"""Graph-resident incremental view maintenance (DESIGN.md §3.1).

Two properties are pinned down here:

  * SHIP COUNTS are static and minimal — an N-operator chain emits exactly
    the expected number of route collectives, and ZERO when the view is
    clean (the count is trace-time, asserted via the transport layer's
    ship-event log, so the same numbers hold inside jit);
  * caching changes ships, NEVER values — chain-differential suites run
    mapV -> mrTriplets -> subgraph -> mrTriplets warm vs cold and require
    bit-exact f32 agreement (the 4-device SPMD half of the matrix lives in
    tests/spmd_check.py).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import Graph, ShipMetrics, Col
from repro.core import transport as transport_mod
from repro.core import algorithms as alg
from repro.data import rmat


def build(seed=0, p=4, scale=6, ef=4):
    g = rmat(scale, ef, seed=seed)
    n = g.num_vertices
    vids = np.arange(n, dtype=np.int64)
    gr = Graph.from_edges(
        g.src, g.dst,
        vertex_keys=vids,
        vertex_values={"x": (vids % 17 + 1).astype(np.float32),
                       "y": (vids % 5).astype(np.float32)},
        default_vertex={"x": np.float32(0), "y": np.float32(0)},
        num_partitions=p)
    return gr, g


def ships_during(fn):
    """(result, [fwd ship events], [all ship events]) of one eager call."""
    transport_mod.SHIP_EVENTS.clear()
    out = fn()
    evs = list(transport_mod.SHIP_EVENTS)
    return out, [e for e in evs if e["label"] == "fwd"], evs


SEND_X = lambda sv, ev, dv: {"m": sv["x"] * ev["w"]}
SEND_XY = lambda sv, ev, dv: {"m": sv["x"] + sv["y"]}


# ---------------------------------------------------------------------------
# ship-count regressions
# ---------------------------------------------------------------------------
def test_clean_view_ships_zero():
    gr, _ = build()
    # cold: exactly one forward route ship; repeat on the RETURNED graph
    # -> the view is clean, zero forward collectives, identical values
    v1, e1, g2, m1 = gr.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    (res, fwd, evs) = ships_during(
        lambda: g2.mrTriplets(SEND_X, "sum", kernel_mode="ref"))
    v2, e2, g3, m2 = res
    assert m1["ships_fwd"] == 1 and m2["ships_fwd"] == 0
    assert len(fwd) == 0 and len(evs) == 1            # only the aggregate return
    np.testing.assert_array_equal(np.asarray(v1["m"]), np.asarray(v2["m"]))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    assert float(m2["fwd"].n_shipped) == 0


def test_dirty_leaf_ships_alone():
    """A mapV that rewrites `x` leaves `y` clean: the next consumer of BOTH
    leaves ships only x (1 collective, x-sized), not x+y."""
    gr, _ = build()
    _, _, g, m_cold = gr.mrTriplets(SEND_XY, "sum", kernel_mode="ref")
    assert m_cold["ships_fwd"] == 1
    g = g.mapV(lambda vid, v: {"x": v["x"] + 1.0, "y": v["y"]})
    (res, fwd, _) = ships_during(
        lambda: g.mrTriplets(SEND_XY, "sum", kernel_mode="ref"))
    _, _, _, m_warm = res
    assert m_warm["ships_fwd"] == 1 and len(fwd) == 1
    # x alone is half the leaf bytes of x+y (flags wire rides along, so
    # compare the static payload accounting)
    assert m_warm["fwd"].wire_bytes < m_cold["fwd"].wire_bytes
    # correctness vs a cold run of the same rewritten graph
    want, _, _, _ = g.replace(view=None).mrTriplets(SEND_XY, "sum",
                                                    kernel_mode="ref")
    got, _, _, _ = g.mrTriplets(SEND_XY, "sum", kernel_mode="ref")
    np.testing.assert_array_equal(np.asarray(got["m"]), np.asarray(want["m"]))


def test_changed_rows_narrow_the_ship():
    """`changed=` marks per-vertex rows: a transform touching ~1/7 of the
    vertices re-ships ~1/7 of the route entries."""
    gr, _ = build()
    _, _, g, _ = gr.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    touch = lambda vid, v: {"x": jnp.where(vid % 7 == 0, v["x"] + 1.0,
                                           v["x"]),
                            "y": v["y"]}
    g_all = g.mapV(touch)                      # conservative: all rows dirty
    g_diff = g.mapV(touch, changed="diff")     # value-diff: 1/7 of rows
    _, _, _, m_all = g_all.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    _, _, _, m_diff = g_diff.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    assert 0 < int(m_diff["fwd"].n_shipped) < int(m_all["fwd"].n_shipped)
    a, _, _, _ = g_all.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    b, _, _, _ = g_diff.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    np.testing.assert_array_equal(np.asarray(a["m"]), np.asarray(b["m"]))


def test_direction_widening_reuse():
    """§4.3 on the wire: with "src" filled and "both" needed, only the dst
    routes ship — strictly fewer bytes than the cold "both" ship, same
    values."""
    gr, _ = build()
    _, _, g, m_src = gr.mrTriplets(SEND_XY, "sum", kernel_mode="ref")
    assert m_src["need"] == "src"
    (res, fwd, _) = ships_during(
        lambda: g.mrTriplets(SEND_XY, "sum", kernel_mode="ref",
                             force_need="both"))
    _, _, _, m_widen = res
    assert m_widen["ships_fwd"] == 1 and len(fwd) == 1
    _, _, _, m_cold = gr.mrTriplets(SEND_XY, "sum", kernel_mode="ref",
                                    force_need="both")
    assert m_widen["fwd"].wire_bytes < m_cold["fwd"].wire_bytes
    a, _, _, _ = g.mrTriplets(SEND_XY, "sum", kernel_mode="ref",
                              force_need="both")
    b, _, _, _ = gr.mrTriplets(SEND_XY, "sum", kernel_mode="ref",
                               force_need="both")
    np.testing.assert_array_equal(np.asarray(a["m"]), np.asarray(b["m"]))


def test_subgraph_folds_into_one_ship():
    """subgraph(vpred, epred) on a cold graph: visibility + the epred-read
    properties ship in ONE routed collective (previously two full ships);
    a triplets() on the result reuses the just-shipped view outright."""
    gr, g = build()
    (sub, fwd, _) = ships_during(
        lambda: gr.subgraph(
            vpred=lambda vid, v: v["x"] > 3,
            epred=lambda sv, ev, dv: (sv["x"] < 10) & (dv["y"] >= 0)))
    assert len(fwd) == 1
    # triplets() on the result: everything it needs was just shipped
    (_, fwd2, evs2) = ships_during(lambda: sub.triplets())
    assert len(fwd2) == 0 and len(evs2) == 0
    # semantics unchanged (mirror of test_subgraph_consistency_invariant)
    xv = lambda vid: vid % 17 + 1          # build()'s x property
    es, ed, _ = sub.edges_to_numpy()
    want = sum(1 for s, d in zip(g.src, g.dst)
               if xv(s) > 3 and xv(d) > 3 and xv(s) < 10)
    assert len(es) == want
    for s, d in zip(es, ed):
        assert xv(s) > 3 and xv(d) > 3 and xv(s) < 10


def test_sparse_inner_join_ships_sparse():
    """The top-k-join story: an innerJoin hitting few vertices, marked with
    changed="diff", re-ships only the rows it rewrote."""
    gr, g = build()
    _, _, gw, _ = gr.mrTriplets(SEND_XY, "sum", kernel_mode="ref")
    keep = np.array([v for v in range(g.num_vertices) if v % 11 == 0],
                    np.int64)
    col = Col.from_numpy(keep.astype(np.int32),
                         {"b": np.full(len(keep), 100.0, np.float32)}, p=4)
    j = lambda v, o, hit: {"x": jnp.where(hit, v["x"] + o["b"], v["x"]),
                           "y": v["y"]}
    g_j = gw.innerJoin(col, j, changed="diff")
    assert not g_j.vmask_full
    (res, fwd, _) = ships_during(
        lambda: g_j.mrTriplets(SEND_XY, "sum", kernel_mode="ref"))
    _, _, _, m = res
    # x ships only the joined rows; y is clean and ships nothing
    assert int(m["fwd"].n_shipped) < int(np.asarray(gr.vmask).sum())
    # differential vs fully-cold
    want, we, _, _ = g_j.replace(view=None).mrTriplets(
        SEND_XY, "sum", kernel_mode="ref")
    got, ge, _, _ = g_j.mrTriplets(SEND_XY, "sum", kernel_mode="ref")
    np.testing.assert_array_equal(np.asarray(got["m"]), np.asarray(want["m"]))
    np.testing.assert_array_equal(np.asarray(ge), np.asarray(we))


# ---------------------------------------------------------------------------
# chain differentials: cached vs cold bit-exact (f32), fused and unfused
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kernel_mode", ["unfused", "ref"])
def test_chain_differential(kernel_mode):
    gr, _ = build()

    def chain(g, cold):
        strip = (lambda x: x.replace(view=None)) if cold else (lambda x: x)
        v1, e1, g, _ = g.mrTriplets(SEND_XY, "sum", kernel_mode=kernel_mode)
        g = strip(g).mapV(lambda vid, v: {"x": v["x"] * 2.0, "y": v["y"]})
        v2, e2, g, _ = g.mrTriplets(SEND_XY, "sum", kernel_mode=kernel_mode)
        g = strip(g).subgraph(vpred=lambda vid, v: v["x"] < 20.0)
        g = strip(g)
        v3, e3, g, _ = g.mrTriplets(SEND_XY, "sum", kernel_mode=kernel_mode)
        return (v1, v2, v3), (e1, e2, e3), g

    (vw, ew, gw) = chain(gr, cold=False)
    (vc, ec, gc) = chain(gr, cold=True)
    for a, b in zip(vw, vc):
        np.testing.assert_array_equal(np.asarray(a["m"]), np.asarray(b["m"]))
    for a, b in zip(ew, ec):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(gw.emask), np.asarray(gc.emask))
    # and the warm chain moved strictly fewer bytes
    assert float(gw.bytes_shipped) < float(gc.bytes_shipped)


def test_chain_under_jit():
    """The whole warm chain inside one jit: ship plans are static, so the
    clean-view zero-ship program traces and runs."""
    gr, _ = build()

    @jax.jit
    def warm(g):
        v1, _, g, _ = g.mrTriplets(SEND_XY, "sum", kernel_mode="ref")
        g = g.mapV(lambda vid, v: {"x": v["x"] * 2.0, "y": v["y"]})
        v2, _, g, _ = g.mrTriplets(SEND_XY, "sum", kernel_mode="ref")
        v3, _, g, _ = g.mrTriplets(SEND_XY, "sum", kernel_mode="ref")
        return v1["m"], v2["m"], v3["m"], g.bytes_shipped

    transport_mod.SHIP_EVENTS.clear()
    a1, a2, a3, bytes_w = warm(gr)
    fwd = [e for e in transport_mod.SHIP_EVENTS if e["label"] == "fwd"]
    assert len(fwd) == 2          # cold both-leaf ship + dirty-x ship; v3 free
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(a3))
    b1, _, _, _ = gr.mrTriplets(SEND_XY, "sum", kernel_mode="ref")
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(b1["m"]))


def test_reverse_remaps_not_invalidates():
    gr, _ = build()
    _, _, g, _ = gr.mrTriplets(SEND_X, "sum", kernel_mode="ref")   # need=src
    grev = g.reverse()
    assert grev.view is not None
    # the transposed graph's "dst" side is the original's "src": a consumer
    # aggregating toward src with a dst-reading UDF... simplest check: the
    # same-need consumer on the reverse ships the OTHER routes, and values
    # match the cold run.
    send_rev = lambda sv, ev, dv: {"m": dv["x"] * ev["w"]}   # reads dst = old src
    (res, fwd, _) = ships_during(
        lambda: grev.mrTriplets(send_rev, "sum", to="src", kernel_mode="ref"))
    got, _, _, _ = res
    assert len(fwd) == 0          # old "src" fill serves the new "dst" need
    want, _, _, _ = grev.replace(view=None).mrTriplets(
        send_rev, "sum", to="src", kernel_mode="ref")
    np.testing.assert_array_equal(np.asarray(got["m"]), np.asarray(want["m"]))


def test_skip_stale_on_clean_view_matches_cold():
    """Regression: a statically-clean refresh carries NO delta information,
    so skip_stale must see everything fresh — the warm chain computes the
    same full aggregates as the cold one (not silently-empty results)."""
    gr, _ = build()
    _, _, g, _ = gr.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    got, ge, _, m = g.mrTriplets(SEND_X, "sum", skip_stale="out",
                                 kernel_mode="ref")
    want, we, _, _ = g.replace(view=None).mrTriplets(
        SEND_X, "sum", skip_stale="out", kernel_mode="ref")
    assert int(m["live_edges"]) > 0
    np.testing.assert_array_equal(np.asarray(ge), np.asarray(we))
    np.testing.assert_array_equal(np.asarray(got["m"]), np.asarray(want["m"]))
    # need-None consumers (UDF reads no vertex data) must not inherit a
    # PREVIOUS consumer's refresh slots as their freshness set either
    g5 = g.mapV(lambda vid, v: {"x": jnp.where(vid % 5 == 0, v["x"] + 1.0,
                                               v["x"]), "y": v["y"]},
                changed="diff")
    _, _, g5, _ = g5.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    count = lambda sv, ev, dv: {"c": jnp.float32(1.0)}
    cw, cwe, _, cm = g5.mrTriplets(count, "sum", skip_stale="out",
                                   kernel_mode="ref")
    cc, cce, _, _ = g5.replace(view=None).mrTriplets(
        count, "sum", skip_stale="out", kernel_mode="ref")
    np.testing.assert_array_equal(np.asarray(cwe), np.asarray(cce))
    np.testing.assert_array_equal(np.asarray(cw["c"]), np.asarray(cc["c"]))

    # the explicit-cache contract is untouched: a caller that SAYS nothing
    # changed (active all-False) still gets the all-stale delta semantics
    from repro.core.mrtriplets import mr_triplets
    _, _, cache, _ = mr_triplets(gr, SEND_X, "sum", kernel_mode="ref")
    frozen = gr.replace(active=jnp.zeros_like(gr.active))
    _, fe, _, fm = mr_triplets(frozen, SEND_X, "sum", cache=cache,
                               skip_stale="out", kernel_mode="ref")
    assert int(fm["live_edges"]) == 0 and not bool(fe.any())


def test_changed_accepts_numpy_mask():
    gr, _ = build()
    _, _, g, _ = gr.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    rows = np.asarray(gr.s.home_vid) % 5 == 0
    g2 = g.mapV(lambda vid, v: {"x": jnp.where(vid % 5 == 0, v["x"] + 1.0,
                                               v["x"]),
                                "y": v["y"]},
                changed=rows)
    got, _, _, m = g2.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    assert 0 < int(m["fwd"].n_shipped) < int(np.asarray(gr.vmask).sum())
    want, _, _, _ = g2.replace(view=None).mrTriplets(SEND_X, "sum",
                                                     kernel_mode="ref")
    np.testing.assert_array_equal(np.asarray(got["m"]), np.asarray(want["m"]))


# ---------------------------------------------------------------------------
# Pregel hand-off: delta state survives exiting the loop
# ---------------------------------------------------------------------------
def test_pregel_exit_leaves_warm_view():
    gd = rmat(7, 5, seed=3)
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=4)
    res = alg.pagerank(g, num_iters=8, tol=1e-3, kernel_mode="ref",
                       track_metrics=True)
    gout = res.graph
    assert gout.view is not None
    # `deg` was shipped (need="src") during the loop and never rewritten by
    # vprog (passthrough analysis): a post-loop consumer reading deg via
    # the src side ships NOTHING.
    send_deg = lambda sv, ev, dv: {"m": sv["deg"]}
    (r, fwd, _) = ships_during(
        lambda: gout.mrTriplets(send_deg, "sum", kernel_mode="ref"))
    got, _, _, _ = r
    assert len(fwd) == 0
    want, _, _, _ = gout.replace(view=None).mrTriplets(
        send_deg, "sum", kernel_mode="ref")
    np.testing.assert_array_equal(np.asarray(got["m"]), np.asarray(want["m"]))
    # pipeline metrics surfaced in the pregel rows
    assert res.metrics[-1]["pipeline_ships"] >= res.supersteps
    assert res.metrics[-1]["pipeline_bytes_shipped"] > 0


def test_reentering_pagerank_recomputes_degrees():
    """Regression (stale-`deg` hazard): a warm PageRank result restricted
    by subgraph and ranked AGAIN must re-ship the freshly recomputed
    degree leaf — attach_out_degree overwrites `deg`, so its pre-existing
    clean mirror may NOT survive as passthrough."""
    gd = rmat(6, 4, seed=5)
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=4)
    warm = alg.pagerank(g, num_iters=4, kernel_mode="ref").graph
    # vertex restriction shrinks emask, so every surviving vertex's
    # out-degree genuinely changes — the stale-mirror hazard is live
    sub = warm.subgraph(vpred=lambda vid, v: vid % 3 != 0)
    # second ranking on the restricted warm graph vs the fully cold path
    pr_warm = alg.pagerank(sub, num_iters=4, kernel_mode="ref").graph
    pr_cold = alg.pagerank(sub.replace(view=None), num_iters=4,
                           kernel_mode="ref").graph
    np.testing.assert_array_equal(np.asarray(pr_warm.vdata["pr"]),
                                  np.asarray(pr_cold.vdata["pr"]))


def test_pregel_incremental_false_stays_cold():
    gd = rmat(6, 4, seed=1)
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=4)
    res = alg.pagerank(g, num_iters=3, kernel_mode="ref", incremental=False)
    assert res.graph.view is None


# ---------------------------------------------------------------------------
# metrics plumbing
# ---------------------------------------------------------------------------
def test_ship_metrics_merge():
    a = ShipMetrics(wire_bytes=100, effective_bytes=jnp.int32(10),
                    n_shipped=jnp.int32(3),
                    bytes_accounted=jnp.float32(50),
                    bytes_shipped=jnp.float32(80),
                    ragged=jnp.float32(1), route_active_max=jnp.int32(7),
                    route_width=16)
    b = ShipMetrics(wire_bytes=40, effective_bytes=jnp.int32(4),
                    n_shipped=jnp.int32(2),
                    bytes_accounted=jnp.float32(20),
                    bytes_shipped=jnp.float32(30),
                    ragged=jnp.float32(0), route_active_max=jnp.int32(9),
                    route_width=8)
    m = a.merge(b)
    assert m.wire_bytes == 140 and m.route_width == 16
    assert int(m.n_shipped) == 5 and float(m.bytes_shipped) == 110
    assert float(m.ragged) == 1 and int(m.route_active_max) == 9
    z = ShipMetrics.zero()
    mz = m.merge(z)
    assert mz.wire_bytes == 140 and float(mz.bytes_shipped) == 110


def test_wire_log_accumulates():
    gr, _ = build()
    assert float(gr.ships) == 0
    _, _, g1, _ = gr.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    _, _, g2, _ = g1.mrTriplets(SEND_X, "sum", kernel_mode="ref")
    assert float(g1.ships) == 2                       # fwd + back
    assert float(g2.ships) == 3                       # + back only (clean)
    assert 0 < float(g2.bytes_shipped)
    assert float(g2.bytes_shipped) >= float(g1.bytes_shipped)
    # mutators keep the log
    g3 = g2.mapV(lambda vid, v: {"x": v["x"], "y": v["y"] + 1})
    assert float(g3.ships) == 3


def test_keep_through_nested_exclude_dirties_stale_leaf():
    """Regression: `keep_through(exclude=…)` matched top-level keys only,
    so excluding a NESTED leaf — the natural (("stats", "deg"),) spelling —
    silently kept its stale mirror marked clean and the warm path read old
    values.  Entries now match as path prefixes; warm must equal cold."""
    from repro.core import view as view_mod

    g = rmat(5, 4, seed=3)
    vids = np.arange(g.num_vertices, dtype=np.int64)
    vv = {"x": (vids % 7 + 1).astype(np.float32),
          "stats": {"deg": (vids % 4).astype(np.float32)}}
    gr = Graph.from_edges(
        g.src, g.dst, vertex_keys=vids, vertex_values=vv,
        default_vertex={"x": np.float32(0),
                        "stats": {"deg": np.float32(0)}},
        num_partitions=4)

    send = lambda sv, ev, dv: {"m": sv["stats"]["deg"] + dv["x"]}
    _, _, warm, _ = gr.mrTriplets(send, "sum")      # view now filled

    # overwrite ONLY the nested leaf, certifying the rest passes through
    def bump_deg(gg):
        old = gg.vdata
        new = {"x": old["x"],
               "stats": {"deg": old["stats"]["deg"] + 10.0}}
        view = view_mod.view_after_rewrite(
            gg.view, old, new,
            view_mod.keep_through(old, exclude=(("stats", "deg"),)), None)
        return gg.replace(vdata=new, view=view)

    got, _, _, _ = bump_deg(warm).mrTriplets(send, "sum")   # warm: delta
    want, _, _, _ = bump_deg(gr).mrTriplets(send, "sum")    # cold: full
    np.testing.assert_array_equal(np.asarray(got["m"]),
                                  np.asarray(want["m"]))
    # whole-subtree exclusion and the old top-level spelling both still work
    km = view_mod.keep_through(warm.vdata, exclude=("stats",))
    assert [v for _, v in sorted(km.items(), key=str)] in (
        [True, False], [False, True])
    km2 = view_mod.keep_through(warm.vdata, exclude=("x",))
    assert sum(km2.values()) == len(km2) - 1


def test_ship_metrics_zero_matches_live_dtypes_under_x64():
    """Regression: `ShipMetrics.zero()` hardcoded int32 counters while a
    live ship's counters are `flags.sum()` — the default integer dtype,
    which is int64 under the x64 config.  A statically-clean refresh and a
    shipping refresh then presented different avals across lax.cond
    branches.  zero() must track the config."""
    with jax.enable_x64(True):
        flags = jnp.zeros((2, 4), bool)
        live = ShipMetrics(wire_bytes=0,
                           effective_bytes=flags.sum() * 4,
                           n_shipped=flags.sum(),
                           route_width=0)
        z = ShipMetrics.zero()
        assert ([x.dtype for x in jax.tree.leaves(live)]
                == [x.dtype for x in jax.tree.leaves(z)])
        # the aval-stability contract itself: both branches of a cond
        out = jax.lax.cond(flags.any(),
                           lambda: live, lambda: ShipMetrics.zero())
        assert int(out.n_shipped) == 0
    # and outside x64 the counters stay the default int32
    assert ShipMetrics.zero().n_shipped.dtype == jnp.zeros((), bool).sum().dtype


# ---------------------------------------------------------------------------
# §2.4 narrow-resident mirrors: encoded-in-HBM vs decode-at-materialization
# ---------------------------------------------------------------------------
def _with_resident(gr, codec):
    from repro.core import with_wire
    return gr.replace(ex=with_wire(gr.ex, codec, resident=True))


@pytest.mark.parametrize("kernel_mode", ["ref", "unfused"])
def test_narrow_resident_int_bit_exact(kernel_mode):
    """Exact-representable ints under a certified bound: the resident "int"
    mirror is a lossless cast, so encoded-resident equals the wire-only
    (decode-at-scatter) int8 path bit for bit — and the warm view's HBM
    footprint is strictly smaller."""
    from repro.core import with_wire
    from repro.core import wire as wire_mod

    gr, _ = build()
    g = gr.mapV(lambda vid, v: {"c": (vid % 100).astype(jnp.int32)})
    send = lambda sv, ev, dv: {"m": sv["c"]}
    want, ew, gw, _ = g.replace(ex=with_wire(g.ex, "int8")).mrTriplets(
        send, "max", kernel_mode=kernel_mode, payload_bound=100)
    got, eg, gres, _ = _with_resident(g, "int8").mrTriplets(
        send, "max", kernel_mode=kernel_mode, payload_bound=100)
    np.testing.assert_array_equal(np.asarray(ew), np.asarray(eg))
    np.testing.assert_array_equal(np.asarray(got["m"]), np.asarray(want["m"]))
    enc = [l for l in jax.tree.leaves(gres.view.mirror,
                                      is_leaf=wire_mod.is_resident)
           if wire_mod.is_resident(l)]
    assert enc and all(l.kind == "int" for l in enc)
    assert (wire_mod.resident_hbm_bytes(gres.view.mirror)
            < wire_mod.resident_hbm_bytes(gw.view.mirror))


@pytest.mark.parametrize("kernel_mode", ["ref", "unfused"])
def test_narrow_resident_f32_pagerank_norm_err(kernel_mode):
    """f32 PageRank under the scaled int8 codec: a SINGLE materialization
    is bit-exact (the resident mirror holds exactly the wire-quantized
    values), and each warm refresh may re-quantize a scatter-touched block
    against its new vertex-axis absmax — at most ONE quantization step
    (rel 1/(2*qmax) = 1/254) of drift per refresh, the §2.4 contract.  The
    iterated pin is therefore `iters/254` relative L2 vs the wire-only
    run; the resident view is ~4x narrower in HBM."""
    from repro.core import with_wire
    from repro.core import wire as wire_mod

    def rel_l2(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)

    gr, _ = build()
    # one materialization: zero drift, bit for bit
    r_wire1 = alg.pagerank(gr.replace(ex=with_wire(gr.ex, "int8")),
                           num_iters=1, kernel_mode=kernel_mode)
    r_res1 = alg.pagerank(_with_resident(gr, "int8"),
                          num_iters=1, kernel_mode=kernel_mode)
    np.testing.assert_array_equal(np.asarray(r_res1.graph.vdata["pr"]),
                                  np.asarray(r_wire1.graph.vdata["pr"]))

    iters = 5
    r_f32 = alg.pagerank(gr, num_iters=iters, kernel_mode=kernel_mode)
    r_wire = alg.pagerank(gr.replace(ex=with_wire(gr.ex, "int8")),
                          num_iters=iters, kernel_mode=kernel_mode)
    r_res = alg.pagerank(_with_resident(gr, "int8"),
                         num_iters=iters, kernel_mode=kernel_mode)
    pr_res = r_res.graph.vdata["pr"]
    assert rel_l2(pr_res, r_wire.graph.vdata["pr"]) <= iters / 254.0
    # distance to the f32 truth is quantization noise, not residency drift:
    # both int8 runs sit at the same (loose) distance from f32
    assert rel_l2(pr_res, r_f32.graph.vdata["pr"]) <= 5e-2
    mir_res = wire_mod.resident_hbm_bytes(r_res.graph.view.mirror)
    mir_wire = wire_mod.resident_hbm_bytes(r_wire.graph.view.mirror)
    assert mir_res <= 0.35 * mir_wire, (mir_res, mir_wire)


def test_resident_mirror_survives_rewrite_and_rewarms():
    """view_after_rewrite keeps surviving leaves' ResidentLeaf mirrors
    encoded; a warm->delta chain under the resident codec stays value-equal
    to the same chain run cold."""
    from repro.core import wire as wire_mod

    gr, _ = build()
    g8 = _with_resident(gr, "int8")
    send = lambda sv, ev, dv: {"m": sv["x"] + sv["y"]}
    _, _, warm, _ = g8.mrTriplets(send, "sum", kernel_mode="ref")
    enc = [l for l in jax.tree.leaves(warm.view.mirror,
                                      is_leaf=wire_mod.is_resident)
           if wire_mod.is_resident(l)]
    assert enc, "resident codec should encode the warm mirror"
    bump = lambda vid, v: {"x": v["x"] + 1.0, "y": v["y"]}
    got, _, after, _ = warm.mapV(bump).mrTriplets(send, "sum",
                                                  kernel_mode="ref")
    want, _, _, _ = warm.mapV(bump).replace(view=None).mrTriplets(
        send, "sum", kernel_mode="ref")
    np.testing.assert_array_equal(np.asarray(got["m"]), np.asarray(want["m"]))
    enc2 = [l for l in jax.tree.leaves(after.view.mirror,
                                       is_leaf=wire_mod.is_resident)
            if wire_mod.is_resident(l)]
    assert enc2, "delta refresh must re-encode, not silently widen"

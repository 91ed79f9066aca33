"""Fused-superstep + pipelined-exchange differentials (DESIGN.md §2.3.2,
§2.1.2).

The LocalExchange half of the overlap matrix: the fused apply (triplet
sweep + combine + vprog + changed-mask derivation in one program) and the
ring-pipelined mirror ship change SCHEDULES, never VALUES.  The 4-device
SpmdExchange half lives in tests/spmd_check.py section (l).
"""
import functools
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (Graph, LocalExchange, TransportPolicy,
                        algorithms as alg, with_wire)
from repro.core import transport as T
from repro.core.mrtriplets import FUSED_MINMAX_MAX_WIDTH, apply_plan_of
from repro.data import rmat, symmetrize

# the package re-exports the driver function under the submodule's name
pregel_mod = importlib.import_module("repro.core.pregel")

IMAX = jnp.int32(2**31 - 1)


def _cc_graph(seed=2, scale=6):
    gd = symmetrize(rmat(scale, 4, seed=seed))
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=4)
    return gd, g.mapV(lambda vid, v: {"cc": vid})


def _cc_send(sv, ev, dv):
    return {"m": sv["cc"]}


def _cc_vprog(vid, v, msg):
    return {"cc": jnp.minimum(v["cc"], msg["m"])}


def _run_cc(g, *, fuse_apply, transport=None):
    return pregel_mod.pregel(
        g, _cc_vprog, _cc_send, "min", default_msg={"m": IMAX},
        skip_stale="out", transport=transport, track_metrics=True,
        fuse_apply=fuse_apply, max_supersteps=20)


# --------------------------------------------------------------- fused apply
def test_fused_apply_cc_bit_exact_vs_unfused_and_oracle():
    """min gather fuses by default ("auto") and must be bit-for-bit the
    unfused two-program superstep — and both match the union-find oracle."""
    gd, g = _cc_graph()
    r_u = _run_cc(g, fuse_apply="unfused")
    r_f = _run_cc(g, fuse_apply="auto")
    assert r_u.metrics[0]["apply_plan"] == "unfused"
    assert r_f.metrics[0]["apply_plan"] == "fused_apply"
    np.testing.assert_array_equal(np.asarray(r_f.graph.vdata["cc"]),
                                  np.asarray(r_u.graph.vdata["cc"]))
    assert r_f.supersteps == r_u.supersteps
    mask = np.asarray(g.vmask)
    vids = np.asarray(g.s.home_vid)[mask]
    want = alg.connected_components_reference(gd.src, gd.dst, vids)
    got = dict(zip(vids.tolist(),
                   np.asarray(r_f.graph.vdata["cc"])[mask].tolist()))
    assert got == want


def test_fused_apply_sum_fuses_by_default():
    """f32 sums fuse under "auto" (PR-7 follow-up (b) landed): both the
    fused sweep and the unfused scatter-add accumulate in the SAME fixed
    order (ascending source partition, collision-free within a partition's
    apply tiles), so the fusion is bit-exact — not merely close."""
    gd = rmat(7, 6, seed=3)
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=4)
    g = alg.attach_out_degree(g, kernel_mode="ref")
    g = g.mapV(lambda vid, v: {"pr": jnp.float32(1.0),
                               "deg": jnp.maximum(v["deg"], 1.0)})

    def send(sv, ev, dv):
        return {"m": sv["pr"] / sv["deg"]}

    def vprog(vid, v, msg):
        return {"pr": 0.15 + 0.85 * msg["m"], "deg": v["deg"]}

    def changed(old, new):
        return jnp.abs(new["pr"] - old["pr"]).max() > 1e-2

    def run(fuse):
        return pregel_mod.pregel(
            g, vprog, send, "sum", default_msg={"m": jnp.float32(0.0)},
            skip_stale="out", changed_fn=changed, track_metrics=True,
            fuse_apply=fuse, max_supersteps=15)

    r_un = run("unfused")
    r_auto = run("auto")
    assert r_un.metrics[0]["apply_plan"] == "unfused"
    assert r_auto.metrics[0]["apply_plan"] == "fused_apply"
    np.testing.assert_array_equal(np.asarray(r_auto.graph.vdata["pr"]),
                                  np.asarray(r_un.graph.vdata["pr"]))
    assert r_auto.supersteps == r_un.supersteps


def test_apply_plan_width_eligibility():
    """min/max fusion rides the segmented-scan reduce, which caps the
    payload width; sum has no such cap.  Ineligible -> clean fallback."""
    _, g = _cc_graph()
    assert apply_plan_of(g, _cc_vprog, _cc_send, "min",
                         default_msg={"m": IMAX}) == "fused_apply"
    wide = FUSED_MINMAX_MAX_WIDTH + 8
    gw = g.mapV(lambda vid, v: {"x": jnp.zeros((wide,), jnp.float32)})

    def send(sv, ev, dv):
        return {"m": sv["x"]}

    def vp(vid, v, msg):
        return {"x": jnp.minimum(v["x"], msg["m"])}

    dm = {"m": jnp.float32(0.0)}        # defaults must be static scalars
    assert apply_plan_of(gw, vp, send, "min", default_msg=dm) == "unfused"
    assert apply_plan_of(gw, vp, send, "sum", default_msg=dm) == "fused_apply"
    # a non-scalar default is its own (clean) ineligibility
    wide_dm = {"m": jnp.zeros((wide,), jnp.float32)}
    assert apply_plan_of(gw, vp, send, "sum", default_msg=wide_dm) == "unfused"


def _subjaxprs(val):
    from jax.extend import core as jex
    if isinstance(val, jex.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jex.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _subjaxprs(v)


def _count_home_shaped(jaxpr, shape2) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if tuple(getattr(v.aval, "shape", ()))[:2] == shape2:
                n += 1
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                n += _count_home_shaped(sub, shape2)
    return n


def count_home_materializations(g: Graph, *, vprog, send_msg, gather,
                                default_msg, skip_stale, fuse_apply) -> int:
    """Number of home-vertex-shaped ([nl, v_blk, ...]) arrays one traced
    superstep materializes.  Traced with kernel_mode="interpret" so the
    fused sweeps stay single `pallas_call` equations — exactly what the
    compiled HLO keeps VMEM-resident instead of round-tripping to HBM."""
    fn = functools.partial(
        pregel_mod._superstep, vprog=vprog, send_msg=send_msg, gather=gather,
        default_msg=default_msg, skip_stale=skip_stale, changed_fn=None,
        kernel_mode="interpret", use_cache=True, fuse_apply=fuse_apply)
    jaxpr = jax.make_jaxpr(fn)(g)
    nl, v_blk = g.s.home_vid.shape
    return _count_home_shaped(jaxpr.jaxpr, (nl, v_blk))


def test_fused_materializes_fewer_home_arrays():
    """The §2.3.2 HBM claim: one traced superstep materializes strictly
    fewer home-vertex-shaped arrays when the apply half fuses."""
    _, g = _cc_graph()
    kw = dict(vprog=_cc_vprog, send_msg=_cc_send, gather="min",
              default_msg={"m": IMAX}, skip_stale="out")
    m_fused = count_home_materializations(g, fuse_apply="auto", **kw)
    m_unfused = count_home_materializations(g, fuse_apply="unfused", **kw)
    assert 0 < m_fused < m_unfused, (m_fused, m_unfused)


# ------------------------------------------------------------ ring pipeline
def test_ring_transpose_matches_transpose_local():
    """ring_transpose is a re-schedule of the same permutation: bit
    identical to transpose for any trailing shape; ppermute composes."""
    ex = LocalExchange(p=4)
    rng = np.random.default_rng(0)
    for shape in ((4, 4), (4, 4, 3), (4, 4, 2, 5)):
        x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        np.testing.assert_array_equal(np.asarray(ex.ring_transpose(x)),
                                      np.asarray(ex.transpose(x)))
    x = jnp.arange(8, dtype=jnp.int32).reshape(4, 2)
    np.testing.assert_array_equal(np.asarray(ex.ppermute(x, 1)),
                                  np.roll(np.asarray(x), 1, axis=0))
    y = x
    for _ in range(4):      # p unit hops walk the full ring back home
        y = ex.ppermute(y, 1)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_pipelined_pregel_quick_differential():
    """Fast-lane smoke cell of the matrix: dense f32, fused apply."""
    _, g = _cc_graph()
    r_ser = _run_cc(g, fuse_apply="auto", transport=T.DENSE)
    r_pipe = _run_cc(g, fuse_apply="auto",
                     transport=T.DENSE.replace(pipeline=True))
    np.testing.assert_array_equal(np.asarray(r_pipe.graph.vdata["cc"]),
                                  np.asarray(r_ser.graph.vdata["cc"]))
    assert r_pipe.supersteps == r_ser.supersteps


@pytest.mark.slow
def test_pipelined_pregel_bit_exact_matrix():
    """The full local matrix: fused/unfused apply x dense/ragged transport
    x f32/int8 wire — pipelined == serialized bit for bit, same superstep
    count (the changed mask drives convergence identically)."""
    _, g = _cc_graph()
    ragged_pol = TransportPolicy("ragged", capacity_frac=0.5, cap_rounding=8)
    for codec in ("f32", "int8"):
        gc = g if codec == "f32" else g.replace(
            ex=with_wire(g.ex, "int8", delta=True))
        for fuse in ("auto", "unfused"):
            for tp0 in (T.DENSE, ragged_pol):
                r_ser = _run_cc(gc, fuse_apply=fuse, transport=tp0)
                r_pipe = _run_cc(gc, fuse_apply=fuse,
                                 transport=tp0.replace(pipeline=True))
                np.testing.assert_array_equal(
                    np.asarray(r_pipe.graph.vdata["cc"]),
                    np.asarray(r_ser.graph.vdata["cc"]),
                    err_msg=f"{codec}/{fuse}/{tp0.kind}")
                assert r_pipe.supersteps == r_ser.supersteps


def test_warm_view_reentry_pipelined():
    """PR 5 re-entry: leave one loop with the incremental view riding the
    graph, continue under the pipelined schedule — the delta-shipping path
    stays bit-exact vs the serialized continuation."""
    _, g = _cc_graph()

    def phase(gg, n, tp):
        out = gg
        for _ in range(n):
            out, _, _ = pregel_mod._superstep(
                out, None, vprog=_cc_vprog, send_msg=_cc_send, gather="min",
                default_msg={"m": IMAX}, skip_stale="out", changed_fn=None,
                kernel_mode="auto", use_cache=True, transport=tp)
        return out

    res = {}
    for pipe in (False, True):
        tp = T.DENSE.replace(pipeline=pipe)
        mid = phase(g, 3, tp)
        assert mid.view is not None     # exits warm
        res[pipe] = np.asarray(phase(mid, 5, tp).vdata["cc"])
    np.testing.assert_array_equal(res[True], res[False])


# ----------------------------------------------------- adapt-policy hysteresis
def test_adapt_policy_oscillating_frontier_pins_tier():
    """A frontier occupancy oscillating around a 1/8 tier boundary must NOT
    flip-flop between two compiled programs: with `prev=` threaded (what
    pregel's driver does) the tier pins to the upper value; widening still
    applies immediately."""
    pol = TransportPolicy("auto", cap_rounding=8, enter_frac=0.95,
                          exit_frac=0.97)
    fracs = [0.26, 0.24] * 6            # tiers 0.375 / 0.25 without memory
    naive = {T.adapt_policy(pol, was_ragged=True, active_frac=0.05,
                            fwd_frac=f).capacity_frac for f in fracs}
    assert naive == {0.25, 0.375}       # two programs, one per superstep

    cur = T.adapt_policy(pol, was_ragged=False, active_frac=0.05,
                         fwd_frac=0.26)
    assert cur.kind == "ragged" and cur.capacity_frac == 0.375
    seen = {(cur.kind, cur.capacity_frac)}
    for f in fracs:
        cur = T.adapt_policy(pol, was_ragged=cur.kind == "ragged",
                             active_frac=0.05, fwd_frac=f, prev=cur)
        seen.add((cur.kind, cur.capacity_frac))
    assert seen == {("ragged", 0.375)}, seen
    # under-capacity is a wasted dense-fallback ship: growth is immediate
    cur = T.adapt_policy(pol, was_ragged=True, active_frac=0.05,
                         fwd_frac=0.6, prev=cur)
    assert cur.capacity_frac == T.frac_tier(0.6)


def test_pregel_recompiles_metric():
    """Host metrics count DISTINCT compiled transport plans; a dense-only
    run is exactly one program."""
    _, g = _cc_graph()
    auto = TransportPolicy("auto", cap_rounding=8, enter_frac=0.9,
                           exit_frac=0.95)
    r_d = _run_cc(g, fuse_apply="auto", transport=T.DENSE)
    assert r_d.metrics[-1]["recompiles"] == 1
    r_a = _run_cc(g, fuse_apply="auto", transport=auto)
    rec = r_a.metrics[-1]["recompiles"]
    kinds = {m["transport"] for m in r_a.metrics}
    assert "ragged" in kinds, kinds     # the plan actually adapted
    assert 2 <= rec <= len(r_a.metrics), rec

"""Partitioner + routing-table invariants (unit + property tests).

Property tests use hypothesis when it is installed; otherwise a minimal
stand-in replays each property over a fixed batch of numpy-seeded draws so
the invariants stay exercised on images without hypothesis."""
import functools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    class _S:
        def __init__(self, draw):
            self.draw = draw

        def map(self, f):
            return _S(lambda rng: f(self.draw(rng)))

    class st:  # noqa: N801 - mimics the hypothesis namespace
        @staticmethod
        def integers(lo, hi):
            return _S(lambda rng: int(rng.integers(lo, hi + 1)))

        @staticmethod
        def tuples(*els):
            return _S(lambda rng: tuple(e.draw(rng) for e in els))

        @staticmethod
        def lists(elem, min_size=0, max_size=10):
            def draw(rng):
                n = int(rng.integers(min_size, max_size + 1))
                return [elem.draw(rng) for _ in range(n)]
            return _S(draw)

        @staticmethod
        def sampled_from(seq):
            return _S(lambda rng: seq[int(rng.integers(len(seq)))])

    def settings(**_kw):
        return lambda f: f

    def given(*strats):
        def deco(f):
            def run():
                for seed in range(12):
                    rng = np.random.default_rng(seed)
                    f(*(s.draw(rng) for s in strats))
            run.__name__ = f.__name__
            run.__doc__ = f.__doc__
            return run
        return deco

from repro.core import partition as pm
from repro.data import rmat


def edges_strategy(max_v=64, max_e=200):
    return st.lists(
        st.tuples(st.integers(0, max_v - 1), st.integers(0, max_v - 1)),
        min_size=1, max_size=max_e).map(
            lambda es: (np.array([e[0] for e in es], np.int64),
                        np.array([e[1] for e in es], np.int64)))


@settings(max_examples=30, deadline=None)
@given(edges_strategy(), st.sampled_from([1, 2, 4, 6, 8]))
def test_every_edge_placed_exactly_once(edges, p):
    src, dst = edges
    s = pm.build_structure(src, dst, p)
    # edge_part/edge_row map every input edge to a unique live slab slot
    seen = set()
    for q, r in zip(s.edge_part, s.edge_row):
        assert s.edge_mask[q, r]
        assert (int(q), int(r)) not in seen
        seen.add((int(q), int(r)))
    assert len(seen) == int(s.edge_mask.sum()) == len(src)


@settings(max_examples=30, deadline=None)
@given(edges_strategy(), st.sampled_from([2, 4, 8]))
def test_slots_resolve_to_original_endpoints(edges, p):
    src, dst = edges
    s = pm.build_structure(src, dst, p)
    for e in range(len(src)):
        q, r = s.edge_part[e], s.edge_row[e]
        assert s.mirror_vid[q, s.src_slot[q, r]] == src[e]
        assert s.mirror_vid[q, s.dst_slot[q, r]] == dst[e]


@settings(max_examples=20, deadline=None)
@given(edges_strategy(), st.sampled_from([2, 4]),
       st.sampled_from(["src", "dst", "both"]))
def test_routing_tables_consistent(edges, p, need):
    """k-th entry of send[q,pe] and recv[pe,q] describe the same vertex."""
    src, dst = edges
    s = pm.build_structure(src, dst, p)
    send, recv, _ = s.routes[need]
    for q in range(p):
        for pe in range(p):
            for k in range(send.shape[2]):
                row = send[q, pe, k]
                slot = recv[pe, q, k]
                if row < 0:
                    assert slot == s.v_mir  # padding agrees
                    continue
                vid = s.home_vid[q, row]
                assert s.mirror_vid[pe, slot] == vid
                # and the vertex is homed where we think
                assert s.home_of(np.array([vid]))[0] == q


@settings(max_examples=20, deadline=None)
@given(edges_strategy(), st.sampled_from([2, 4]))
def test_need_sets_are_exact(edges, p):
    """'src' routes exactly the vertices appearing as a source in that
    partition — the join-elimination byte saving is real, not heuristic."""
    src, dst = edges
    s = pm.build_structure(src, dst, p)
    for pe in range(p):
        live = s.edge_mask[pe]
        srcs = {int(s.mirror_vid[pe, sl]) for sl in s.src_slot[pe][live]}
        shipped = set()
        send, recv, _ = s.routes["src"]
        for q in range(p):
            for k in range(send.shape[2]):
                if send[q, pe, k] >= 0:
                    shipped.add(int(s.home_vid[q, send[q, pe, k]]))
        assert shipped == srcs


def test_2d_cut_replication_bound():
    """Paper §4.2: 2D hash partitioning bounds replication by 2*sqrt(P)-1."""
    g = rmat(10, 8, seed=1)
    for p in (4, 16):
        s = pm.build_structure(g.src, g.dst, p, partitioner="2d")
        bound = 2 * np.sqrt(p) - 1
        assert s.stats.replication_factor <= bound + 1e-9, (
            s.stats.replication_factor, bound)


def test_2d_beats_random_on_powerlaw():
    """The reason vertex-cut exists: lower replication on skewed graphs."""
    g = rmat(10, 16, seed=2)
    r2d = pm.build_structure(g.src, g.dst, 16, partitioner="2d")
    rnd = pm.build_structure(g.src, g.dst, 16, partitioner="random")
    assert r2d.stats.replication_factor < rnd.stats.replication_factor


def test_home_partition_balanced():
    g = rmat(10, 4, seed=3)
    s = pm.build_structure(g.src, g.dst, 8)
    counts = s.home_mask.sum(axis=1)
    assert counts.max() / max(counts.mean(), 1) < 1.5


def test_isolated_vertices_get_homes():
    src = np.array([0, 1], np.int64)
    dst = np.array([1, 2], np.int64)
    s = pm.build_structure(src, dst, 2, vertex_ids=np.array([7, 9], np.int64))
    vids = set(s.home_vid[s.home_mask].tolist())
    assert {0, 1, 2, 7, 9} == vids


def test_rejects_bad_ids():
    with pytest.raises(ValueError):
        pm.build_structure(np.array([-1]), np.array([2]), 2)


# ---- hybrid cut (§4.2) --------------------------------------------------

def test_hybrid_threshold_is_argmin_of_sweep():
    """The chosen threshold minimises total mirrors over the sweep — in
    particular candidate 0 (pure 2D) and max_deg+1 (pure 1D) never beat it."""
    g = rmat(9, 8, seed=4)
    p = 4
    deg = pm._edge_source_degree(g.src)
    d1 = pm.edge_partition_1d(g.src, g.dst, p)
    d2 = pm.edge_partition_2d(g.src, g.dst, p)

    def mirrors(t):
        return pm._mirror_total(g.src, g.dst, np.where(deg < t, d1, d2), p)

    t = pm.choose_hybrid_threshold(g.src, g.dst, p)
    chosen = mirrors(t)
    for cand in {0, 1, 2, 4, 8, int(deg.max()) + 1, t}:
        assert chosen <= mirrors(cand), (t, cand)


@settings(max_examples=15, deadline=None)
@given(edges_strategy(), st.sampled_from([2, 4, 8]))
def test_hybrid_placement_monotone_in_threshold(edges, p):
    """Raising the threshold only moves MORE edges to the 1D side; each edge
    is always placed by exactly one of the two underlying cuts."""
    src, dst = edges
    deg = pm._edge_source_degree(src)
    d1 = pm.edge_partition_1d(src, dst, p)
    d2 = pm.edge_partition_2d(src, dst, p)
    prev = None
    for t in (0, 1, 2, 4, int(deg.max()) + 1):
        ep = pm.edge_partition_hybrid(src, dst, p, threshold=t)
        low = deg < t
        assert np.array_equal(ep[low], d1[low])
        assert np.array_equal(ep[~low], d2[~low])
        if prev is not None:
            assert low.sum() >= prev
        prev = low.sum()


@settings(max_examples=15, deadline=None)
@given(edges_strategy(), st.sampled_from([2, 4, 8]))
def test_hybrid_replication_never_worse_than_2d(edges, p):
    """Threshold 0 IS 2D and the sweep minimises mirrors, so the hybrid cut
    structurally cannot replicate more than the 2D cut."""
    src, dst = edges
    s2 = pm.build_structure(src, dst, p, partitioner="2d")
    sh = pm.build_structure(src, dst, p, partitioner="hybrid")
    assert (sh.stats.replication_factor
            <= s2.stats.replication_factor + 1e-9)


def test_hybrid_beats_2d_on_low_degree_tail():
    """A random recursive forest (parent -> child) has a long low-out-degree
    tail whose edges colocate under the 1D cut while every child keeps
    in-degree 1: the sweep must pick a nonzero threshold and strictly win."""
    rng = np.random.default_rng(7)
    n = 4096
    dst = np.arange(1, n, dtype=np.int64)
    src = rng.integers(0, np.arange(1, n), dtype=np.int64)
    s2 = pm.build_structure(src, dst, 4, partitioner="2d")
    sh = pm.build_structure(src, dst, 4, partitioner="hybrid")
    assert sh.stats.threshold > 0
    assert (sh.stats.replication_factor
            < s2.stats.replication_factor - 1e-6)


def test_hybrid_replication_bound_on_skewed_graph():
    """ISSUE 9 acceptance: on the skewed power-law graph the hybrid cut's
    replication is <= the 2D cut's at P=4."""
    g = rmat(11, 12, seed=2)  # twitter-sim: power-law R-MAT
    s2 = pm.build_structure(g.src, g.dst, 4, partitioner="2d")
    sh = pm.build_structure(g.src, g.dst, 4, partitioner="hybrid")
    assert (sh.stats.replication_factor
            <= s2.stats.replication_factor + 1e-9)


@settings(max_examples=15, deadline=None)
@given(edges_strategy(), st.sampled_from([2, 4]))
def test_place_vertex_rows_roundtrip(edges, p):
    """place_vertex_rows scatters by global id; reading back through
    local_row recovers exactly the written values, everything else fill."""
    src, dst = edges
    s = pm.build_structure(src, dst, p, partitioner="hybrid")
    vids = np.unique(np.concatenate([src, dst]))[::2]
    vals = (vids * 3 + 1).astype(np.int64)
    buf = pm.place_vertex_rows(s, vids, vals, fill=-5)
    part, row = s.local_row(vids)
    assert np.array_equal(buf[part, row], vals)
    assert np.array_equal(s.home_vid[part, row], vids)
    mask = np.zeros(buf.shape, bool)
    mask[part, row] = True
    assert (buf[~mask] == -5).all()


# ---- broadcast-set classification (§2.1.3) ------------------------------

def _deliveries(s, send, recv):
    """Set of (dest partition, vid) pairs a routed table delivers."""
    out = set()
    p, _, k = send.shape
    for q in range(p):
        for pe in range(p):
            for j in range(k):
                if send[q, pe, j] >= 0 and recv[pe, q, j] < s.v_mir:
                    out.add((pe, int(s.home_vid[q, send[q, pe, j]])))
    return out


def test_broadcast_split_covers_full_routes():
    """Broadcast deliveries + residual p2p deliveries == the full routes'
    deliveries, disjointly, for every need set; broadcast members really
    are replicated on >= bcast_min_repl partitions."""
    g = rmat(8, 8, seed=5)
    bmr = 2
    s = pm.build_structure(g.src, g.dst, 4, bcast_min_repl=bmr)
    bvids = s.bcast_vid[s.bcast_vid >= 0]
    assert s.stats.n_broadcast == bvids.size > 0
    assert (s.stats.replication_of(bvids.astype(np.int64)) >= bmr).all()
    # id-sorted per home partition, and bsend rows point at the right homes
    for q in range(s.num_partitions):
        bq = s.bcast_vid[q][s.bcast_vid[q] >= 0]
        assert np.array_equal(bq, np.sort(bq))
        assert np.array_equal(s.home_vid[q, s.bsend[q][s.bsend[q] >= 0]], bq)
    for need in ("src", "dst", "both"):
        full = _deliveries(s, *s.routes[need][:2])
        p2p = _deliveries(s, *s.p2p_routes[need][:2])
        bc = set()
        for q in range(s.num_partitions):
            for pe in range(s.num_partitions):
                for j in range(s.b_width):
                    if (s.bcast_vid[q, j] >= 0
                            and s.brecv[need][pe, q, j] < s.v_mir):
                        bc.add((pe, int(s.bcast_vid[q, j])))
        assert p2p.isdisjoint(bc)
        assert p2p | bc == full, need
        assert not {v for _, v in p2p} & set(bvids.tolist())


# ---- differential: values independent of placement + transport ----------
#
# The gather order is only canonical per PLACEMENT, so the bit-exactness
# contract is: (a) any order-independent gather ('min' - CC) is bit-exact
# across partitioners x transports x fused/unfused; (b) a float 'sum'
# (PageRank) is bit-exact across transports/lanes/fusion for a FIXED
# placement, and matches the numpy oracle to float32 tolerance across
# placements (different partitioners legally reassociate the sum).

def _home_dict(g, leaf):
    hv = np.asarray(g.s.home_vid)
    hm = np.asarray(g.s.home_mask)
    v = np.asarray(g.vdata[leaf])
    return {int(hv[p, j]): v[p, j]
            for p in range(hv.shape[0]) for j in np.nonzero(hm[p])[0]}


_PARTS = [("2d", {}), ("1d", {}), ("hybrid", {}),
          ("hybrid", {"bcast_min_repl": 2})]


def test_cc_bit_exact_across_partitioner_transport_fusion():
    from repro.core import transport as tm
    from repro.core.algorithms import (connected_components,
                                       connected_components_reference)
    from repro.core.graph import Graph
    from repro.data import symmetrize

    gd = symmetrize(rmat(7, 5, seed=1))
    base = None
    for part, kw in _PARTS:
        g0 = Graph.from_edges(gd.src, gd.dst, num_partitions=4,
                              partitioner=part, **kw)
        for tp, mode in ((tm.TransportPolicy(kind="dense"), "unfused"),
                         (tm.TransportPolicy(kind="auto"), "auto")):
            r = connected_components(g0, max_supersteps=30, transport=tp,
                                     kernel_mode=mode)
            labels = _home_dict(r.graph, "cc")
            if base is None:
                base = labels
                oracle = connected_components_reference(
                    gd.src, gd.dst, sorted(labels))
                assert {k: int(v) for k, v in labels.items()} == oracle
            assert labels == base, (part, kw, tp.kind, mode)


def test_pagerank_bit_exact_across_transports_within_partitioner():
    from repro.core import transport as tm
    from repro.core.algorithms import pagerank, pagerank_reference
    from repro.core.graph import Graph

    gd = rmat(7, 5, seed=1)
    n = int(max(gd.src.max(), gd.dst.max())) + 1
    ref = pagerank_reference(gd.src, gd.dst, n, num_iters=3)
    transports = (
        tm.TransportPolicy(kind="dense"),
        tm.TransportPolicy(kind="ragged", capacity_frac=1.0,
                           capacity_frac_back=1.0),
        tm.TransportPolicy(kind="ragged", capacity_frac=1.0,
                           capacity_frac_back=1.0,
                           capacity_fracs=(1.0,) * 4,
                           capacity_fracs_back=(1.0,) * 4),
        tm.TransportPolicy(kind="auto"),
    )
    for part, kw in _PARTS:
        g0 = Graph.from_edges(gd.src, gd.dst, num_partitions=4,
                              partitioner=part, **kw)
        fixed = None
        for tp in transports:
            r = pagerank(g0, num_iters=3, transport=tp)
            pr = _home_dict(r.graph, "pr")
            if fixed is None:
                fixed = pr
                got = np.array([pr[v] for v in sorted(pr)])
                np.testing.assert_allclose(
                    got, ref[sorted(pr)], rtol=2e-6,
                    err_msg=f"{part} {kw} vs oracle")
            # same placement -> the transport must not change a single bit
            assert set(pr) == set(fixed)
            for k in fixed:
                assert np.array_equal(fixed[k], pr[k]), (part, kw, tp.kind, k)

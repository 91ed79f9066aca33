"""Graph algorithm library composed from the narrow-waist operators (§3.3).

Everything here is built from mrTriplets / Pregel / subgraph / joins — no
algorithm touches the physical representation, which is the paper's point.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from . import trace
from .graph import Graph
from .pregel import pregel, pregel_fused, PregelResult
from .tree import vmap2

INF32 = jnp.float32(jnp.finfo(jnp.float32).max)
IMAX = jnp.int32(2**31 - 1)


# --------------------------------------------------------------------------
# PageRank (paper Listings 1/2; evaluation §5.1)
# --------------------------------------------------------------------------
def attach_out_degree(g: Graph, kernel_mode: str = "auto") -> Graph:
    """Degree count is the paper's 0-way-join mrTriplets (§4.5.2).

    View-preserving (§3.1): only the `deg` leaf is (re)computed — a warm
    graph entering PageRank from an operator chain keeps every OTHER
    mirror it already shipped.  `deg` itself is excluded from the
    passthrough certificate: a pre-existing deg property is overwritten
    here (and the overwrite can produce different values, e.g. after a
    subgraph restriction), so its mirror must go dirty, not stay clean."""
    from . import view as view_mod
    from .graph import _degree_msg
    with trace.span("graphx.operator", op="mrTriplets"):
        # the method call (not bare degrees()) keeps the graph lineage: the
        # degree aggregation's wire traffic lands in the pipeline wire log
        vals, exists, g, _ = g.mrTriplets(_degree_msg, "sum", to="src",
                                          kernel_mode=kernel_mode)
        deg = jnp.where(exists, vals["deg"], 0.0)
        old = g.vdata if isinstance(g.vdata, dict) else {"v": g.vdata}
        vdata = {**old, "deg": jnp.maximum(deg, 1.0)}
        view = view_mod.view_after_rewrite(
            g.view, old, vdata, view_mod.keep_through(old, exclude=("deg",)),
            None)
        return g.replace(vdata=vdata, view=view)


def _map_vertices(g: Graph, fn: Callable) -> Graph:
    """`g.mapV(fn)` under a `graphx.operator` span."""
    with trace.span("graphx.operator", op="mapV"):
        return g.mapV(fn)


@trace.algorithm
def pagerank(g: Graph, *, num_iters: int = 20, reset: float = 0.15,
             tol: float = 0.0, kernel_mode: str = "auto",
             incremental: bool = True, track_metrics: bool = False,
             force_need: str | None = None,
             transport=None) -> PregelResult:
    """PageRank via Pregel-on-GAS.  The send UDF reads ONLY the source
    attributes, so the jaxpr analyzer drops the dst side of the join —
    the paper's headline join-elimination example (Fig. 5).

    tol == 0  -> synchronous (static) PageRank: every vertex recomputes
                 `reset + (1-reset)·msgSum` each superstep (Listings 1/2).
    tol > 0   -> *delta* PageRank, the formulation GraphX itself uses for
                 convergence-tracked runs: messages carry rank CHANGES, so
                 skipStale (dropping edges whose source changed < tol) is
                 semantics-preserving under the commutative 'sum' gather —
                 a stale source contributes an already-applied delta of 0,
                 not a missing absolute rank."""
    g = attach_out_degree(g, kernel_mode)

    if tol <= 0.0:
        g = _map_vertices(g, lambda vid, v: {**v, "pr": jnp.float32(1.0)})

        def send(sv, ev, dv):
            return {"m": sv["pr"] / sv["deg"] * ev["w"]}

        def vprog(vid, v, msg):
            return {**v, "pr": reset + (1.0 - reset) * msg["m"]}

        return pregel(
            g, vprog, send, "sum", default_msg={"m": jnp.float32(0.0)},
            max_supersteps=num_iters, skip_stale=None,
            incremental=incremental, kernel_mode=kernel_mode,
            track_metrics=track_metrics, transport=transport)

    # delta formulation: pr0 = reset, delta0 = reset
    g = _map_vertices(g, lambda vid, v: {**v, "pr": jnp.float32(reset),
                                         "delta": jnp.float32(reset)})

    def send(sv, ev, dv):
        return {"m": sv["delta"] / sv["deg"] * ev["w"]}

    def vprog(vid, v, msg):
        new_pr = v["pr"] + (1.0 - reset) * msg["m"]
        return {**v, "pr": new_pr, "delta": new_pr - v["pr"]}

    changed_fn = lambda old, new: jnp.abs(new["pr"] - old["pr"]) > tol

    return pregel(
        g, vprog, send, "sum", default_msg={"m": jnp.float32(0.0)},
        max_supersteps=num_iters, skip_stale="out",
        incremental=incremental, changed_fn=changed_fn,
        kernel_mode=kernel_mode, track_metrics=track_metrics,
        transport=transport)


def pagerank_reference(src: np.ndarray, dst: np.ndarray, n: int,
                       num_iters: int = 20, reset: float = 0.15) -> np.ndarray:
    """Dense numpy oracle for tests (synchronous PR, uniform init 1.0)."""
    pr = np.ones(n, np.float64)
    deg = np.maximum(np.bincount(src, minlength=n), 1)
    for _ in range(num_iters):
        contrib = pr / deg
        msg = np.zeros(n, np.float64)
        np.add.at(msg, dst, contrib[src])
        pr = reset + (1 - reset) * msg
    return pr


# --------------------------------------------------------------------------
# Connected components (paper Listing 6; evaluation §5.1)
# --------------------------------------------------------------------------
@trace.algorithm
def connected_components(g: Graph, *, max_supersteps: int = 100,
                         kernel_mode: str = "auto", incremental: bool = True,
                         track_metrics: bool = False,
                         transport=None) -> PregelResult:
    """Min-id label diffusion.  Undirected semantics: each edge carries the
    lower id both ways, so we run two mrTriplets per superstep via a
    symmetric send on the doubled graph — here realised by 'min' gather over
    both directions using to='dst' on g and on g.reverse().

    For the canonical single-pass Pregel formulation we instead propagate
    src->dst on the symmetrised edge set; callers should pass a graph built
    with both (u,v) and (v,u) edges (data/graphs.py does this), matching how
    Giraph/GraphLab benchmark CC.
    """
    g = _map_vertices(g, lambda vid, v: {"cc": vid})

    def send(sv, ev, dv):
        return {"m": sv["cc"]}

    def vprog(vid, v, msg):
        return {"cc": jnp.minimum(v["cc"], msg["m"])}

    return pregel(
        g, vprog, send, "min", default_msg={"m": IMAX},
        max_supersteps=max_supersteps, skip_stale="out",
        incremental=incremental, kernel_mode=kernel_mode,
        track_metrics=track_metrics, transport=transport)


def connected_components_reference(src, dst, vids) -> dict[int, int]:
    """Union-find oracle."""
    parent = {int(v): int(v) for v in vids}

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for s, d in zip(src, dst):
        rs, rd = find(int(s)), find(int(d))
        if rs != rd:
            parent[max(rs, rd)] = min(rs, rd)
    return {v: find(int(v)) for v in parent}


# --------------------------------------------------------------------------
# Single-source shortest paths
# --------------------------------------------------------------------------
@trace.algorithm
def sssp(g: Graph, source: int, *, max_supersteps: int = 100,
         kernel_mode: str = "auto") -> PregelResult:
    g = _map_vertices(g, lambda vid, v: {
        "dist": jnp.where(vid == source, jnp.float32(0.0), INF32)})

    def send(sv, ev, dv):
        return {"m": sv["dist"] + ev["w"]}

    def vprog(vid, v, msg):
        return {"dist": jnp.minimum(v["dist"], msg["m"])}

    return pregel(g, vprog, send, "min", default_msg={"m": INF32},
                  max_supersteps=max_supersteps, skip_stale="out",
                  kernel_mode=kernel_mode)


# --------------------------------------------------------------------------
# Label propagation (K-label voting — associative formulation)
# --------------------------------------------------------------------------
@trace.algorithm
def label_propagation(g: Graph, num_labels: int, *, num_iters: int = 10,
                      kernel_mode: str = "auto") -> PregelResult:
    """Each vertex adopts the argmax of neighbour label votes.  Votes are
    one-hot vectors so the gather is a sum — associative, unlike the usual
    'mode' formulation."""
    k = num_labels

    def send(sv, ev, dv):
        return {"votes": jax.nn.one_hot(sv["label"] % k, k, dtype=jnp.float32)}

    def vprog(vid, v, msg):
        has_votes = msg["votes"].sum() > 0
        new = jnp.argmax(msg["votes"]).astype(jnp.int32)
        return {"label": jnp.where(has_votes, new, v["label"])}

    return pregel(g, vprog, send, "sum",
                  default_msg={"votes": jnp.zeros((k,), jnp.float32)},
                  max_supersteps=num_iters, skip_stale=None,
                  kernel_mode=kernel_mode)


# --------------------------------------------------------------------------
# Triangle counting — a genuinely 3-way-join workload (contrast with
# PageRank's join-eliminated 2-way; benchmark fodder for Fig. 5)
# --------------------------------------------------------------------------
@trace.algorithm
def triangle_count(g: Graph, *, n_ids: int | None = None,
                   kernel_mode: str = "auto"):
    """Triangles via the narrow waist, two mrTriplets passes.

    Phase 1 gathers each vertex's neighbour set as a bitset: every (deduped)
    edge contributes a DISTINCT one-hot bit to its destination, so the 'sum'
    gather IS bitwise-OR — no new reduce op needed.  Phase 2 maps each edge
    to |N(src) ∩ N(dst)| (popcount of the AND) and sums at the destination;
    on a symmetrised, self-loop-free graph each triangle is counted twice
    per corner, six times in total.

    Requires compact vertex ids in [0, n_ids).  Returns
    (per_vertex [P,V_blk] float32, total triangles, metrics).
    """
    n_ids = n_ids or g.s.num_vertices
    w = (n_ids + 31) // 32

    g1 = _map_vertices(g, lambda vid, v: {"vid": vid})

    def send_bits(sv, ev, dv):
        word = (sv["vid"] // 32).astype(jnp.int32)
        bit = jnp.left_shift(jnp.uint32(1),
                             (sv["vid"] % 32).astype(jnp.uint32))
        return {"bits": jnp.zeros((w,), jnp.uint32).at[word].set(bit)}

    with trace.span("graphx.operator", op="mrTriplets"):
        bits, exists, _, m1 = g1.mrTriplets(send_bits, "sum", to="dst",
                                            kernel_mode=kernel_mode)
    nbr = jnp.where(exists[..., None], bits["bits"], jnp.uint32(0))
    g2 = g1.replace(vdata={"bits": nbr})

    def send_common(sv, ev, dv):
        inter = jnp.bitwise_and(sv["bits"], dv["bits"])
        cnt = jax.lax.population_count(inter).sum().astype(jnp.float32)
        return {"c": cnt}

    with trace.span("graphx.operator", op="mrTriplets"):
        cnts, exists2, _, m2 = g2.mrTriplets(send_common, "sum", to="dst",
                                             kernel_mode=kernel_mode)
    per_vertex = jnp.where(exists2, cnts["c"], 0.0) / 2.0
    total = per_vertex.sum() / 3.0
    return per_vertex, total, {"phase1": m1, "phase2": m2}


def triangle_count_reference(src, dst, n: int) -> int:
    """Brute-force oracle on the symmetrised adjacency."""
    adj = [set() for _ in range(n)]
    for s, d in zip(src, dst):
        if s != d:
            adj[int(s)].add(int(d))
            adj[int(d)].add(int(s))
    total = 0
    for u in range(n):
        for v in adj[u]:
            if v > u:
                total += len((adj[u] & adj[v]) - {u, v})
    # each triangle counted once per edge (u<v) that closes it: 3 edges
    return total // 3


# --------------------------------------------------------------------------
# Coarsen (paper Listing 7) — the unified data-/graph-parallel pipeline
# --------------------------------------------------------------------------
@trace.algorithm
def coarsen(g: Graph, epred: Callable, merge: str = "sum",
            *, kernel_mode: str = "auto") -> Graph:
    """Collapse edges satisfying `epred`; vertices in the same contracted
    component merge into a super-vertex.  Follows Listing 7 exactly:
    subgraph -> connected components -> reduceByKey -> rebuild.

    The rebuild is a host-side pipeline stage (graphs are immutable; the
    paper's Graph constructor is also a bulk operation)."""
    # 1. restrict to contractable edges, 2. CC on the subgraph
    sub = g.subgraph(epred=epred)
    cc = connected_components(sub, kernel_mode=kernel_mode).graph

    # 3. map every vertex to its component (super-vertex id)
    vids, cvals = cc.vertices_to_numpy()
    comp = np.asarray(cvals["cc"])
    comp_of = dict(zip(vids.tolist(), comp.tolist()))

    # merge vertex properties by component (host reduceByKey)
    gvids, gvals = g.vertices_to_numpy()
    comp_ids = np.array([comp_of[int(v)] for v in gvids])

    def merge_leaf(leaf):
        leaf = np.asarray(leaf)
        out: dict[int, Any] = {}
        for cid, val in zip(comp_ids, leaf):
            if cid in out:
                if merge == "sum":
                    out[cid] = out[cid] + val
                elif merge == "min":
                    out[cid] = np.minimum(out[cid], val)
                elif merge == "max":
                    out[cid] = np.maximum(out[cid], val)
            else:
                out[cid] = val
        keys = np.array(sorted(out))
        return keys, np.stack([out[k] for k in keys])

    leaves, treedef = jax.tree.flatten(g.vdata)
    host_leaves = [np.asarray(l)[np.asarray(g.vmask)] for l in leaves]
    merged = [merge_leaf(l) for l in host_leaves]
    super_keys = merged[0][0]
    super_vals = jax.tree.unflatten(treedef, [m[1] for m in merged])

    # 4. re-link surviving edges between super-vertices
    esrc, edst, evals = g.edges_to_numpy()
    # edges NOT contracted: those in g but not in sub's restricted edge set
    sub_src, sub_dst, _ = sub.edges_to_numpy()
    contracted = set(zip(sub_src.tolist(), sub_dst.tolist()))
    keep = np.array([(s, d) not in contracted
                     for s, d in zip(esrc.tolist(), edst.tolist())])
    new_src = np.array([comp_of[int(s)] for s in esrc[keep]], np.int64)
    new_dst = np.array([comp_of[int(d)] for d in edst[keep]], np.int64)
    new_evals = jax.tree.map(lambda e: np.asarray(e)[keep], evals)
    # drop self-loops created by contraction
    nl = new_src != new_dst
    new_src, new_dst = new_src[nl], new_dst[nl]
    new_evals = jax.tree.map(lambda e: e[nl], new_evals)

    default_v = jax.tree.map(
        lambda a: np.zeros(np.asarray(a).shape[1:], np.asarray(a).dtype),
        super_vals)
    return Graph.from_edges(
        new_src, new_dst, edge_values=new_evals,
        vertex_keys=super_keys, vertex_values=super_vals,
        default_vertex=default_v,
        num_partitions=g.s.p, ex=g.ex)

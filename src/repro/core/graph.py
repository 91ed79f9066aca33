"""The property graph — GraphX's unified data model (paper §3.1) in JAX.

A `Graph` is an immutable pytree: structural index arrays (`StructArrays`,
shared across property updates — §4.3 index reuse is literal object sharing
here) plus vertex/edge property pytrees and the visibility bitmasks that make
`subgraph` a view instead of a rebuild.

Operator semantics follow Listing 4 of the paper:
  vertices/edges/triplets  — collection views
  mapV / mapE              — property transforms, structure (and indexes) reused
  leftJoin / innerJoin     — merge external vertex collections
  subgraph                 — bitmask-restricted view
  mrTriplets               — see repro.core.mrtriplets
Plus `degrees`, `reverse`, and host round-trips for pipeline stages that
rebuild structure (coarsen).

UDF conventions (all per-element; the engine vmaps):
  mapV:              f(vid, vval) -> vval'
  mapE/epred/mapmsg: f(src_vval, eval, dst_vval) -> ...
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from . import partition as part_mod
from .collections import Col
from . import trace
from .exchange import Exchange, LocalExchange, SpmdExchange
from .mrtriplets import metrics_across, mr_triplets
from .tree import elem_spec, gather_rows, tree_where, vmap2
from . import analysis
from . import view as view_mod
from .view import GraphView, WireLog
from ..utils.spmd import make_mesh, shard_map


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StructArrays:
    """Device-resident structural index (immutable, shared — §4.3)."""

    src_slot: jnp.ndarray
    dst_slot: jnp.ndarray
    src_perm: jnp.ndarray
    edge_mask: jnp.ndarray
    mirror_vid: jnp.ndarray
    home_vid: jnp.ndarray
    home_mask: jnp.ndarray
    routes: dict            # need -> (send_idx, recv_slot)
    # tiles[side]: per-partition [P, n_chunks, ...] chunk tables for the
    # fused triplet kernel (kernels/triplet.build_triplet_tiles).  Pytree
    # CHILDREN, so they shard with the graph: inside shard_map each device
    # carries exactly its own local tiling — what lets the fused plan run
    # under the SPMD executor.  None only for shape-spec dry-run structures.
    tiles: dict = None
    # broadcast lane (DESIGN.md §2.1.3), present only when build_structure
    # classified a broadcast set: bsend [P, B] home rows of each partition's
    # broadcast vertices (-1 pad), brecv[need] [P, P, B] receive-side mirror
    # slots (v_mir = drop), p2p_routes[need] the residual point-to-point
    # routes with the broadcast set removed.  Pytree children like routes,
    # so they shard with the graph under shard_map.
    bsend: jnp.ndarray = None
    brecv: dict = None
    p2p_routes: dict = None
    # static metadata
    p: int = dataclasses.field(default=0)
    e_blk: int = 0
    v_mir: int = 0
    v_blk: int = 0
    num_vertices: int = 0
    num_edges: int = 0
    max_vid: int = 0        # fused planner's int-staging guard (partition.py)
    b_width: int = 0        # static B of the broadcast lane (0 = no lane)

    def tree_flatten(self):
        children = (self.src_slot, self.dst_slot, self.src_perm,
                    self.edge_mask, self.mirror_vid, self.home_vid,
                    self.home_mask, self.routes, self.tiles,
                    self.bsend, self.brecv, self.p2p_routes)
        aux = (self.p, self.e_blk, self.v_mir, self.v_blk,
               self.num_vertices, self.num_edges, self.max_vid,
               self.b_width)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @staticmethod
    def from_host(s: part_mod.GraphStructure) -> "StructArrays":
        return StructArrays(
            src_slot=jnp.asarray(s.src_slot),
            dst_slot=jnp.asarray(s.dst_slot),
            src_perm=jnp.asarray(s.src_perm),
            edge_mask=jnp.asarray(s.edge_mask),
            mirror_vid=jnp.asarray(s.mirror_vid),
            home_vid=jnp.asarray(s.home_vid),
            home_mask=jnp.asarray(s.home_mask),
            routes={k: (jnp.asarray(v[0]), jnp.asarray(v[1]))
                    for k, v in s.routes.items()},
            tiles=(None if s.tiles is None else
                   {side: {k: jnp.asarray(v) for k, v in t.items()}
                    for side, t in s.tiles.items()}),
            bsend=None if s.bsend is None else jnp.asarray(s.bsend),
            brecv=(None if s.brecv is None else
                   {k: jnp.asarray(v) for k, v in s.brecv.items()}),
            p2p_routes=(None if s.p2p_routes is None else
                        {k: (jnp.asarray(v[0]), jnp.asarray(v[1]))
                         for k, v in s.p2p_routes.items()}),
            p=s.num_partitions, e_blk=s.e_blk, v_mir=s.v_mir,
            v_blk=s.v_blk, num_vertices=s.num_vertices,
            num_edges=s.num_edges, max_vid=s.max_vid,
            b_width=s.b_width)


PARTS_AXIS = "parts"
# shard_map specs of `per_partition`'s outputs: one row per partition, or
# one value already reduced over the devices
PARTS = PartitionSpec(PARTS_AXIS)
WHOLE = PartitionSpec()


def per_partition(fn: Callable, out_specs) -> Callable:
    """`fn(g, *args)` run on each device's partitions of a placed graph.

    For a graph that `Graph.place` put one partition per device, the call
    runs `fn` under `shard_map` over the graph's mesh: `fn` sees the local
    partition (leading axis 1) with the graph's `SpmdExchange`, and the
    other arguments replicated.  `out_specs` is a prefix of fn's output:
    `PARTS` where it holds one row per partition, `WHOLE` where `fn` has
    already reduced it over the devices (`g.ex.psum`, `metrics_across`).
    Graphs in the output come back placed.  For any other graph the call is
    `fn(g, *args)` itself, with nothing added to its program."""
    @functools.wraps(fn)
    def run(g, *args):
        if g.mesh is None:
            return fn(g, *args)

        def local(g, *args):
            return _with_mesh(fn(dataclasses.replace(g, mesh=None), *args),
                              None)
        out = shard_map(local, g.mesh, (PARTS,) + (WHOLE,) * len(args),
                        out_specs)(g, *args)
        return _with_mesh(out, g.mesh)
    return run


def _with_mesh(tree, mesh):
    """`tree` with every Graph in it carrying `mesh`."""
    return jax.tree.map(
        lambda x: (dataclasses.replace(x, mesh=mesh)
                   if isinstance(x, Graph) else x),
        tree, is_leaf=lambda x: isinstance(x, Graph))


def _degree_msg(sv, ev, dv):
    """Stable module-level UDF: fused-path caches (tile_fn, kernel compiles)
    key on the UDF's object identity, so per-call lambdas would defeat them."""
    return {"deg": jnp.float32(1.0)}


_TILE_SIDE_SWAP = {"dst": "src", "src": "dst",
                   "apply_dst": "apply_src", "apply_src": "apply_dst"}


def _swap_tile_sides(tiles):
    """reverse() relabeling of the tile-table dict: the triplet tables swap
    aggregation roles, and so do the apply-route tables (they follow their
    routes).  Key-based, so new table families survive a transpose instead of
    being silently dropped by a hand-written dict literal."""
    if tiles is None:
        return None
    return {_TILE_SIDE_SWAP.get(k, k): v for k, v in tiles.items()}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Graph:
    """Immutable distributed property graph G(P) = (V, E, P)."""

    s: StructArrays
    vdata: Any               # pytree [P, V_blk, ...]
    edata: Any               # pytree [P, E_blk, ...]
    vmask: jnp.ndarray       # [P, V_blk] visibility bitmask (subgraph view)
    emask: jnp.ndarray       # [P, E_blk]
    active: jnp.ndarray      # [P, V_blk] changed-since-last-ship (§4.5.1)
    # graph-resident replicated vertex view (DESIGN.md §3.1): the
    # materialized mirror + per-leaf dirty state that lets operator CHAINS
    # delta-ship, not just the Pregel loop.  None = cold (first consumer
    # pays a full ship).  Mutators mark dirtiness; consumers read through
    # `core.view.refresh_view`.
    view: GraphView = dataclasses.field(default=None)
    # pipeline-level wire-traffic accumulators ([nl]-shaped, see WireLog);
    # None = untracked (hand-rolled graphs).
    wire_log: WireLog = dataclasses.field(default=None)
    ex: Exchange = dataclasses.field(default=None)          # static
    host: part_mod.GraphStructure = dataclasses.field(default=None)  # static
    # STATIC "vmask == home_mask" certificate: True only for graphs whose
    # vmask is structurally the full home mask (set by from_edges, cleared
    # by subgraph/innerJoin).  Rides in the pytree aux, so it survives jit
    # tracing — unlike any check on the vmask values or object identity.
    # Defaults to False: hand-rolled Graphs safely take the general path.
    vmask_full: bool = dataclasses.field(default=False)     # static
    # the 1-D mesh ("parts") of a graph placed one partition per device by
    # `place`; None for a graph whose partitions share one device.
    mesh: jax.sharding.Mesh | None = dataclasses.field(default=None)  # static

    def tree_flatten(self):
        return ((self.s, self.vdata, self.edata, self.vmask, self.emask,
                 self.active, self.view, self.wire_log),
                (self.ex, self.host, self.vmask_full, self.mesh))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, ex=aux[0], host=aux[1], vmask_full=aux[2],
                   mesh=aux[3])

    # ------------------------------------------------------------ placement
    @property
    def num_devices(self) -> int:
        """Devices the partitions live on: 1 unless the graph is placed."""
        return 1 if self.mesh is None else self.mesh.size

    def place(self, devices) -> "Graph":
        """The graph with partition i on `devices[i]`, one per device
        (DESIGN.md §4.7).  Every array's leading partition axis is sharded
        over a 1-D mesh of the devices, named "parts", and the exchange
        becomes `SpmdExchange` with the graph's wire codec, so routes move
        by all-to-all between the devices.  `pregel` and the eager operators
        run the placed graph through `per_partition`: `algorithms.pagerank`,
        `connected_components` and `sssp` take it as they take any graph."""
        devices = list(devices)
        if self.mesh is not None:
            raise ValueError("the graph is placed already")
        if len(devices) != self.s.p:
            raise ValueError(f"place: {self.s.p} partitions need "
                             f"{self.s.p} devices, got {len(devices)}")
        with trace.span("graphx.place", devices=len(devices)):
            mesh = make_mesh((self.s.p,), (PARTS_AXIS,), devices=devices)
            g = dataclasses.replace(
                self, mesh=mesh,
                ex=SpmdExchange(p=self.s.p, axis_name=PARTS_AXIS,
                                wire=self.ex.wire))
            g = jax.device_put(g, NamedSharding(mesh, PARTS))
            jax.block_until_ready(g)
        return g

    def replace(self, **kw) -> "Graph":
        """dataclasses.replace with view hygiene: rewriting `vdata` or
        `vmask` WITHOUT saying what happened to the view invalidates it —
        the generic escape hatch must never leave a stale mirror marked
        clean.  The operator methods below always pass `view=` explicitly
        (that is the whole point: they know exactly what they dirtied)."""
        if ("vdata" in kw or "vmask" in kw) and "view" not in kw:
            kw["view"] = None
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------ pipeline wire metrics
    @property
    def ships(self):
        """Routed collectives this graph's lineage has executed (0 when
        untracked)."""
        return (jnp.float32(0) if self.wire_log is None
                else self.wire_log.ships.sum())

    @property
    def bytes_shipped(self):
        return (jnp.float32(0) if self.wire_log is None
                else self.wire_log.bytes_shipped.sum())

    @property
    def bytes_accounted(self):
        return (jnp.float32(0) if self.wire_log is None
                else self.wire_log.bytes_accounted.sum())

    def _after_refresh(self, view, m, n_ships: int) -> "Graph":
        """Attach a refreshed view + account its traffic in the wire log."""
        log = self.wire_log
        if log is not None and (n_ships or m is not None):
            log = log.add(n_ships,
                          m.bytes_shipped if m is not None else 0.0,
                          m.bytes_accounted if m is not None else 0.0)
        return self.replace(view=view, wire_log=log)

    # ------------------------------------------------------------- builders
    @staticmethod
    def from_edges(
        src: np.ndarray,
        dst: np.ndarray,
        *,
        edge_values: Any = None,          # pytree of np [E, ...]
        vertex_keys: np.ndarray | None = None,
        vertex_values: Any = None,        # pytree of np [Nv, ...]
        default_vertex: Any = 0.0,        # paper's defaultV
        merge_v: str = "last",            # paper's mergeV: last|sum|min|max
        num_partitions: int = 4,
        partitioner: str = "2d",
        hybrid_threshold: int | None = None,
        bcast_min_repl: int | None = None,
        ex: Exchange | None = None,
    ) -> "Graph":
        """The `Graph` operator (Listing 4): build a consistent property
        graph from edge and (optional) vertex collections.

        partitioner: "2d" | "1d" | "random" | "hybrid" (§4.2 — hybrid
        places low-out-degree sources 1D and hubs 2D; `hybrid_threshold`
        pins the degree cut, None sweeps for minimum replication).
        bcast_min_repl: vertices replicated on >= this many partitions ship
        through the broadcast lane (DESIGN.md §2.1.3); None disables it."""
        host = part_mod.build_structure(
            src, dst, num_partitions,
            vertex_ids=vertex_keys, partitioner=partitioner,
            hybrid_threshold=hybrid_threshold,
            bcast_min_repl=bcast_min_repl)
        p, v_blk, e_blk = host.num_partitions, host.v_blk, host.e_blk

        # ---- place edge properties in slab order
        if edge_values is None:
            edge_values = {"w": np.ones(len(src), np.float32)}

        def place_edge(leaf):
            leaf = np.asarray(leaf)
            buf = np.zeros((p, e_blk) + leaf.shape[1:], leaf.dtype)
            buf[host.edge_part, host.edge_row] = leaf
            return jnp.asarray(buf)

        edata = jax.tree.map(place_edge, edge_values)

        # ---- place vertex properties (mergeV + defaultV => consistency)
        if vertex_keys is None:
            vertex_keys = np.empty((0,), np.int64)
            vertex_values = jax.tree.map(
                lambda d: np.empty((0,) + np.shape(d), np.asarray(d).dtype),
                default_vertex)
        vk = np.asarray(vertex_keys, np.int64)
        vpart, vrow = host.local_row(vk)

        def place_vertex(leaf, dflt):
            leaf = np.asarray(leaf)
            dflt_arr = np.asarray(dflt)
            trailing = leaf.shape[1:] if leaf.size else dflt_arr.shape
            dtype = leaf.dtype if leaf.size else dflt_arr.dtype
            buf = np.empty((p, v_blk) + trailing, dtype)
            buf[...] = dflt_arr
            if merge_v == "last" or vk.size == 0:
                buf[vpart, vrow] = leaf
            elif merge_v == "sum":
                np.add.at(buf, (vpart, vrow), leaf)
            elif merge_v == "min":
                np.minimum.at(buf, (vpart, vrow), leaf)
            elif merge_v == "max":
                np.maximum.at(buf, (vpart, vrow), leaf)
            else:
                raise ValueError(f"merge_v={merge_v}")
            return jnp.asarray(buf)

        vdata = jax.tree.map(place_vertex, vertex_values, default_vertex)

        s = StructArrays.from_host(host)
        return Graph(
            s=s, vdata=vdata, edata=edata,
            vmask=s.home_mask,
            emask=s.edge_mask,
            active=jnp.asarray(host.home_mask),
            wire_log=WireLog.zeros(p),
            ex=ex or LocalExchange(p), host=host,
            vmask_full=True)

    # ------------------------------------------------------ collection views
    @property
    def vertex_ids(self) -> jnp.ndarray:
        return self.s.home_vid

    def vertices(self) -> Col:
        """Collection view of the visible vertices (§3.2)."""
        return Col(self.s.home_vid, self.vdata, self.vmask, self.ex)

    def edges(self):
        """(src_vid, dst_vid, edata, mask) in slab order."""
        svid = gather_rows({"x": self.s.mirror_vid}, self.s.src_slot)["x"]
        dvid = gather_rows({"x": self.s.mirror_vid}, self.s.dst_slot)["x"]
        return svid, dvid, self.edata, self.emask

    def triplets(self):
        """The three-way join (§3.2): per-edge (src_vid, dst_vid, src_vals,
        edata, dst_vals, mask).  Reads THROUGH the graph-resident view
        (§3.1): a warm graph — e.g. straight after `subgraph`, which just
        shipped both visibility and properties — gathers from the cached
        mirror without a single route collective; only dirty leaves /
        missing directions ship."""
        view, mirror, vis_m, _, _ = view_mod.refresh_view(
            self, "both", with_vis=not self.vmask_full)
        svid, dvid, edata, mask = self.edges()
        svals = gather_rows(mirror, self.s.src_slot)
        dvals = gather_rows(mirror, self.s.dst_slot)
        # visibility: both endpoints visible
        if self.vmask_full:
            vis = self.emask
        else:
            svis = gather_rows({"v": vis_m}, self.s.src_slot)["v"]
            dvis = gather_rows({"v": vis_m}, self.s.dst_slot)["v"]
            vis = svis & dvis
        return svid, dvid, svals, edata, dvals, mask & vis

    # ----------------------------------------------------------- transforms
    def mapV(self, f: Callable, *, changed=None) -> "Graph":
        """f(vid, vval) -> vval'; structure and indexes reused (§4.3).

        May change the vertex property TYPE (Graph[V,E] -> Graph[V2,E]), so
        the new values apply everywhere; hidden vertices stay hidden via the
        bitmask, not via stale data.

        View lifecycle (§3.1): the graph-resident mirror is NOT discarded —
        jaxpr analysis finds the leaves `f` provably passes through
        (`{**v, "pr": ...}` rewrites only `pr`) and only the rewritten
        leaves go dirty.  `changed` narrows the dirty ROWS: None marks all
        (conservative), "diff" value-compares old vs new per leaf, a
        callable `changed(old_vval, new_vval) -> bool` is the caller's
        per-vertex certificate — a transform touching 1% of vertices then
        re-ships 1%."""
        if self.mesh is not None:
            return _placed_map_vertices(f, changed)(self)
        new_vdata = vmap2(f)(self.s.home_vid, self.vdata)
        rewrites = analysis.analyze_rewrites(
            f, (jax.ShapeDtypeStruct((), self.s.home_vid.dtype),
                elem_spec(self.vdata)), 1)
        view = view_mod.view_after_rewrite(
            self.view, self.vdata, new_vdata, rewrites, changed)
        return self.replace(vdata=new_vdata, view=view)

    def mapE(self, f: Callable) -> "Graph":
        """f(src_vval, eval, dst_vval) -> eval'; join-eliminated shipping
        through the graph-resident view — only dirty/missing vertex leaves
        among those `f` reads are shipped (§3.1)."""
        vex, eex = elem_spec(self.vdata), elem_spec(self.edata)
        deps = analysis.analyze_message_fn(f, vex, eex, vex)
        need = ("both" if deps.uses_src and deps.uses_dst
                else "src" if deps.uses_src
                else "dst" if deps.uses_dst else None)
        view = self.view
        m, n_ships = None, 0
        if need is None:
            zeros = jax.tree.map(
                lambda x: jnp.zeros((self.s.p, self.s.e_blk) + x.shape[2:], x.dtype),
                self.vdata)
            svals = dvals = zeros
        else:
            leaf_mask = deps.read_leaf_mask(len(jax.tree.leaves(self.vdata)))
            view, mirror, _, m, n_ships = view_mod.refresh_view(
                self, need, leaf_mask=leaf_mask)
            svals = gather_rows(mirror, self.s.src_slot)
            dvals = gather_rows(mirror, self.s.dst_slot)
        g = self._after_refresh(view, m, n_ships)
        return g.replace(edata=vmap2(f)(svals, self.edata, dvals))

    def leftJoin(self, other: Col, f: Callable | None = None,
                 capacity: int | None = None, *, changed=None) -> "Graph":
        """Merge a vertex property collection into the graph (Listing 4).

        f(vval, other_val, found) -> vval'.  Default keeps a tuple.  Only the
        input collection is shuffled (§4.4): it is re-keyed to the vertex
        home partitioning and merge-joined against the sorted home index.

        The graph-resident view survives by leaf path: passthrough leaves
        stay clean, rewritten leaves go dirty (`changed` as in mapV — a
        sparse join with `changed="diff"` re-ships only the rows it hit),
        newly-joined leaves start cold."""
        joined, ovf = self._join_to_homes(other, capacity)
        ovals, found = joined
        if f is None:
            f = lambda v, o, hit: (v, o, hit)
        new = vmap2(f)(self.vdata, ovals, found)
        rewrites = analysis.analyze_rewrites(
            f, (elem_spec(self.vdata), elem_spec(ovals),
                jax.ShapeDtypeStruct((), jnp.bool_)), 0)
        view = view_mod.view_after_rewrite(
            self.view, self.vdata, new, rewrites, changed)
        return self.replace(vdata=new, view=view)

    def innerJoin(self, other: Col, f: Callable | None = None,
                  capacity: int | None = None, *, changed=None) -> "Graph":
        """leftJoin that also hides unmatched vertices via the bitmask.
        Dirties the visibility leaf only where a vertex actually
        disappeared; property leaves follow the leftJoin rules."""
        joined, ovf = self._join_to_homes(other, capacity)
        ovals, found = joined
        if f is None:
            f = lambda v, o, hit: (v, o)
        fn = lambda v, o, hit: f(v, o, hit)
        new = vmap2(fn)(self.vdata, ovals, found)
        rewrites = analysis.analyze_rewrites(
            fn, (elem_spec(self.vdata), elem_spec(ovals),
                 jax.ShapeDtypeStruct((), jnp.bool_)), 0)
        view = view_mod.view_after_rewrite(
            self.view, self.vdata, new, rewrites, changed)
        vmask = self.vmask & found
        if view is not None:
            view = view.mark_vis(self.vmask & ~found)
        return self.replace(vdata=new, vmask=vmask, view=view,
                            vmask_full=False)

    def _join_to_homes(self, other: Col, capacity: int | None):
        """Shuffle `other` by vid-home hash; merge-join on sorted home_vid."""
        from .collections import shuffle_by_key, KEY_PAD
        capacity = capacity or 2 * max(other.keys.shape[1], self.s.v_blk)
        k, v, m, ovf = shuffle_by_key(other.keys, other.values, other.mask,
                                      self.ex, capacity)
        order = jnp.argsort(jnp.where(m, k, KEY_PAD), axis=1, stable=True)
        ks = jnp.take_along_axis(k, order, axis=1)
        idx = jax.vmap(lambda srt, q: jnp.searchsorted(srt, q))(ks, self.s.home_vid)
        idx = jnp.clip(idx, 0, ks.shape[1] - 1)
        found = (jnp.take_along_axis(ks, idx, axis=1) == self.s.home_vid) \
            & self.s.home_mask

        def probe(leaf):
            srt = jnp.take_along_axis(
                leaf, order.reshape(order.shape + (1,) * (leaf.ndim - 2)), axis=1)
            return jnp.take_along_axis(
                srt, idx.reshape(idx.shape + (1,) * (leaf.ndim - 2)), axis=1)

        return (jax.tree.map(probe, v), found), ovf

    # ------------------------------------------------------------- restrict
    def subgraph(self, vpred: Callable | None = None,
                 epred: Callable | None = None) -> "Graph":
        """Bitmask-restricted view (§4.3): no structure rebuild, indexes
        shared; retained edges satisfy epred AND both endpoint vpreds.

        View lifecycle (§3.1): restricting visibility dirties ONLY the
        visibility leaf — and only at the rows whose bit actually flipped —
        so the follow-up ship is a delta.  The visibility refresh and the
        `epred` property refresh resolve through the same cache and FOLD
        into one routed collective when both are cold (previously two
        back-to-back full ships); `epred` additionally ships only the
        vertex leaves it reads, and a `triplets()` on the result reuses the
        just-shipped view outright."""
        vmask = self.vmask
        view = self.view
        if vpred is not None:
            vmask = vmask & vmap2(vpred)(self.s.home_vid, self.vdata)
            if view is not None:
                view = view.mark_vis(self.vmask ^ vmask)
        g = self.replace(vmask=vmask, view=view,
                         active=self.active & vmask,
                         vmask_full=self.vmask_full and vpred is None)

        # which vertex leaves does epred read?  (leaf-level join
        # elimination for the property half of the ship)
        nleaves = len(jax.tree.leaves(self.vdata))
        if epred is not None:
            vex, eex = elem_spec(self.vdata), elem_spec(self.edata)
            deps = analysis.analyze_message_fn(epred, vex, eex, vex)
            leaf_mask = deps.read_leaf_mask(nleaves)
        else:
            leaf_mask = (False,) * nleaves

        with_vis = not g.vmask_full
        if epred is None and not with_vis:
            return g     # nothing to restrict against

        view, mirror, vis_m, m, n_ships = view_mod.refresh_view(
            g, "both", leaf_mask=leaf_mask, with_vis=with_vis)
        emask = g.emask
        if with_vis:
            svis = gather_rows({"v": vis_m}, self.s.src_slot)["v"]
            dvis = gather_rows({"v": vis_m}, self.s.dst_slot)["v"]
            emask = emask & svis & dvis
        if epred is not None:
            svals = gather_rows(mirror, self.s.src_slot)
            dvals = gather_rows(mirror, self.s.dst_slot)
            emask = emask & vmap2(epred)(svals, self.edata, dvals)
        g = g._after_refresh(view, m, n_ships)
        return g.replace(emask=emask)

    def reverse(self) -> "Graph":
        """Transpose the graph: swap src/dst slots.  Edges were stored
        dst-sorted, so the *new* src side is already sorted (src_perm =
        identity); the src/dst routing tables swap roles, and so do the
        fused-kernel tile tables (the "dst" tiling of the transpose IS the
        "src" tiling of the original — same (out_block, in_block) grouping
        with the endpoint roles flipped)."""
        ident = jnp.broadcast_to(
            jnp.arange(self.s.e_blk, dtype=jnp.int32), self.s.src_perm.shape)

        def _swap_dirs(d):
            """Swap the src/dst roles of a need-keyed table dict (routes,
            brecv, p2p_routes) — the broadcast lane follows its routes."""
            if d is None:
                return None
            return {"src": d["dst"], "dst": d["src"], "both": d["both"]}

        s = dataclasses.replace(
            self.s, src_slot=self.s.dst_slot, dst_slot=self.s.src_slot,
            src_perm=ident,
            routes=_swap_dirs(self.s.routes),
            brecv=_swap_dirs(self.s.brecv),
            p2p_routes=_swap_dirs(self.s.p2p_routes),
            tiles=_swap_tile_sides(self.s.tiles))
        host = self.host
        if host is not None:
            # memoised: GraphStructure is identity-compared static jit
            # metadata, so reverse() must return the SAME transposed host
            # every time (and reverse().reverse() the original) or every
            # jitted caller recompiles per call.
            cached = getattr(host, "_reversed", None)
            if cached is None:
                cached = dataclasses.replace(
                    host, src_slot=host.dst_slot, dst_slot=host.src_slot,
                    src_perm=np.tile(np.arange(host.e_blk, dtype=np.int32),
                                     (host.num_partitions, 1)),
                    routes=_swap_dirs(host.routes),
                    brecv=_swap_dirs(host.brecv),
                    p2p_routes=_swap_dirs(host.p2p_routes),
                    tiles=_swap_tile_sides(host.tiles))
                cached._reversed = host
                host._reversed = cached
            host = cached
        # the view REMAPS rather than invalidates (§3.1): mirror slots and
        # values are direction-agnostic, only the "which routes are filled"
        # labels swap roles with the tables.
        view = None if self.view is None else self.view.remap_reverse()
        return self.replace(s=s, host=host, view=view)

    # ------------------------------------------------------------ mrTriplets
    def mrTriplets(self, map_fn: Callable, reduce: str = "sum", *,
                   to: str = "dst", skip_stale: str | None = None,
                   cache: GraphView | None = None, kernel_mode: str = "auto",
                   force_need: str | None = None,
                   payload_bound: int | None = None,
                   transport=None, transport_state=None,
                   epred: Callable | None = None):
        """See repro.core.mrtriplets.mr_triplets.

        Returns (values, exists, graph', metrics): unlike the low-level
        `mr_triplets` (which hands back the refreshed `GraphView`), the
        METHOD hands back the graph carrying that view — so operator
        chains compose naturally and the next consumer delta-ships:

            vals, ok, g, m = g.mrTriplets(send, "sum")   # full ship
            vals, ok, g, m = g.mrTriplets(send, "sum")   # zero fwd ships

        kernel_mode selects the physical execution strategy:
          "auto"      — fused triplet kernel when eligible (sum/min/max over
                        flat float or exactly-stageable int payloads; Pallas
                        on TPU, jnp oracle on CPU), unfused otherwise;
          "pallas" / "interpret" / "ref"
                      — force that execution backend (fused when eligible);
          "unfused"   — always take the gather -> vmap -> segment-sum path.

        CONVENTION for integer payloads (DESIGN.md §2.3.1): the fused plan
        stages them through f32 and admits signed 32-bit ints as ID-VALUED
        (labels/parents, bounded by the graph's max vertex id < 2^24) —
        that covers the property values AND the messages the UDF computes
        from them.  `payload_bound=` overrides that default with a caller-
        certified |value| bound (timestamps, counters, UDFs whose integer
        arithmetic amplifies ids): it gates BOTH the fused staging guard and
        the wire codec's lossless int8/int16 packing width (§2.1).  Payloads
        with no certifiable bound should pass kernel_mode="unfused" and a
        codec without int packing.  Unsigned 32-bit ints (bitsets) never
        fuse and never narrow.

        transport (core/transport.py, §2.1.1) picks HOW the exchange
        buffers move: None/"dense" (static all_to_all), "ragged"
        (capacity-bounded compaction of the active entries, overflow falls
        back dense), or "auto" (hysteresis on the psummed active fraction;
        transport_state carries the previous decision).  Transports change
        bytes, never values.

        On a placed graph (`place`) the call runs per partition under one
        jitted `shard_map`; its metrics hold the array entries summed over
        the devices (`metrics_across`).  `cache`, `transport_state` and
        `epred` are not supported there.
        """
        if self.mesh is not None:
            if (cache is not None or transport_state is not None
                    or epred is not None):
                raise NotImplementedError(
                    "mrTriplets on a placed graph takes no cache, "
                    "transport_state or epred")
            return _placed_mr_triplets(
                map_fn, reduce, to, skip_stale, kernel_mode, force_need,
                payload_bound, transport)(self)
        values, exists, view, metrics = mr_triplets(
            self, map_fn, reduce, to=to, skip_stale=skip_stale,
            cache=cache, kernel_mode=kernel_mode,
            force_need=force_need, payload_bound=payload_bound,
            transport=transport, transport_state=transport_state,
            epred=epred)
        g = self._after_refresh(view, metrics["fwd"].merge(metrics["back"]),
                                metrics.get("ships", 0))
        if "emask_pushed" in metrics:
            # the pushed-down predicate IS the subgraph restriction: the
            # result graph carries the combined edge mask a materialising
            # subgraph(epred) would have produced (emask is edge-level
            # state, so the vertex view survives this replace untouched).
            g = g.replace(emask=metrics["emask_pushed"])
        return values, exists, g, metrics

    def degrees(self, direction: str = "in", kernel_mode: str = "auto"):
        """Vertex degrees via a join-eliminated mrTriplets (the paper's
        0-way-join example, §4.5.2)."""
        to = "dst" if direction == "in" else "src"
        vals, exists, _, metrics = self.mrTriplets(
            _degree_msg, "sum", to=to, kernel_mode=kernel_mode)
        deg = jnp.where(exists, vals["deg"], 0.0)
        return deg, metrics

    # ----------------------------------------------------------------- host
    def vertices_to_numpy(self):
        vids = np.asarray(self.s.home_vid)
        mask = np.asarray(self.vmask)
        vals = jax.tree.map(lambda v: np.asarray(v)[mask], self.vdata)
        return vids[mask], vals

    def edges_to_numpy(self):
        svid, dvid, edata, mask = self.edges()
        m = np.asarray(mask)
        return (np.asarray(svid)[m], np.asarray(dvid)[m],
                jax.tree.map(lambda e: np.asarray(e)[m], edata))


@functools.lru_cache(maxsize=64)
def _placed_map_vertices(f, changed):
    """`g.mapV(f, changed=changed)` per partition of a placed graph, jitted
    once per (f, changed)."""
    def placed_mapV(g):
        return g.mapV(f, changed=changed)
    return jax.jit(per_partition(placed_mapV, PARTS))


@functools.lru_cache(maxsize=64)
def _placed_mr_triplets(map_fn, reduce, to, skip_stale, kernel_mode,
                        force_need, payload_bound, transport):
    """`g.mrTriplets(...)` per partition of a placed graph, jitted once per
    set of static arguments: (values, exists, graph', metrics summed over
    the devices)."""
    def placed_mrTriplets(g):
        values, exists, g2, metrics = g.mrTriplets(
            map_fn, reduce, to=to, skip_stale=skip_stale,
            kernel_mode=kernel_mode, force_need=force_need,
            payload_bound=payload_bound, transport=transport)
        return values, exists, g2, metrics_across(metrics, g.ex)
    return jax.jit(per_partition(placed_mrTriplets,
                                 (PARTS, PARTS, PARTS, WHOLE)))

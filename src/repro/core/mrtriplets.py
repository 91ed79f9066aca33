"""mrTriplets execution: the physical join + aggregation plan (paper §4.4–4.6).

Logical plan (paper §4.5): triplets = edges ⋈ vertices(src) ⋈ vertices(dst);
messages = map(triplets); result = reduceByKey(messages).  Physical plan here:

  1. *join elimination* (§4.5.2) — jaxpr analysis picks the routing table
     ("src" / "dst" / "both" / none) so un-referenced vertex sides are never
     shipped;
  2. *vertex shipping* — gather(route_send) → all_to_all → scatter(route_recv)
     materialises the replicated vertex view at the edge partitions (join
     site selection: vertices move to edges, never the reverse);
  3. *incremental view maintenance* (§4.5.1, graph-resident since PR 5 —
     DESIGN.md §3.1) — the ship runs THROUGH `core.view.refresh_view`
     against the graph's own `GraphView`: statically-clean leaves ship
     nothing, dirty leaves ship their dirty rows, missing directions ship
     their routes; stale mirror slots keep their previously materialised
     value.  An explicit `cache=` argument restores the legacy contract
     (g.active marks the changed rows for every shipped leaf);
  4. *edge-parallel map + local pre-aggregation* — messages are computed for
     live edges (`skipStale` masks edges whose relevant endpoint is stale,
     §4.6's index-scan at block granularity inside the Pallas kernel) and
     segment-reduced per partition BEFORE the wire (PowerGraph-style
     combiners: wire traffic is O(mirrors), never O(edges));
  5. *aggregate return* — partial aggregates ship back over the same routing
     table and combine at each vertex's home partition.

Every step reports both static wire bytes (what the collective moves) and
effective bytes (what incremental maintenance actually needed) — the
quantities plotted in paper Figures 4 and 5.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from . import analysis
from . import transport as transport_mod
from . import wire as wire_mod
from .exchange import Exchange
from .tree import (bmask, elem_spec, gather_rows, nbytes_of, scatter_rows,
                   tree_where, tree_zeros_like_elem, vmap2)
from ..kernels import ops as kops
from ..kernels.triplet import (DEFAULT_EDGE_BLOCK, DEFAULT_VERTEX_BLOCK,
                               SCALE_GROUP, flatten_tiles)

# Tile geometry of the fused triplet kernel (DESIGN.md §2.3) — shared with
# the build-time table construction in kernels/triplet.py via partition.py.
FUSED_EDGE_BLOCK = DEFAULT_EDGE_BLOCK
FUSED_VERTEX_BLOCK = DEFAULT_VERTEX_BLOCK
# min/max reduce runs the segmented-scan MXU path (kernels/triplet.py §2.3.1):
# log2(Eb) shift/select steps over the [Eb, Dm] tile plus one [Vb, Eb] matmul,
# so VMEM scales with Dm instead of Dm·[Eb, Vb] masks.  The cap now only
# bounds the scan tile itself — wider payloads fall back to the unfused plan.
FUSED_MINMAX_MAX_WIDTH = 64

_REDUCE_IDENTITY = {
    "sum": lambda dt: jnp.zeros((), dt),
    "min": lambda dt: jnp.array(jnp.finfo(dt).max if jnp.issubdtype(dt, jnp.floating)
                                else jnp.iinfo(dt).max, dt),
    "max": lambda dt: jnp.array(jnp.finfo(dt).min if jnp.issubdtype(dt, jnp.floating)
                                else jnp.iinfo(dt).min, dt),
}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ViewCache:
    """One ship's materialised view slice (§4.5.1) — the INTERNAL record
    `ship_to_mirrors` consumes and produces.  The graph-resident,
    per-leaf-tracked cache that operators carry between each other is
    `core.view.GraphView` (DESIGN.md §3.1), which drives this type."""

    mirror: Any           # pytree [P, V_mir, ...]
    filled: jnp.ndarray   # [P, V_mir] bool — slot has ever been shipped
    active: jnp.ndarray   # [P, V_mir] bool — slot changed in latest ship

    def tree_flatten(self):
        return (self.mirror, self.filled, self.active), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShipMetrics:
    wire_bytes: int                 # static bytes a dense collective moves
    effective_bytes: jnp.ndarray    # data actually needed (Fig 4 quantity)
    n_shipped: jnp.ndarray
    # codec-aware ACCOUNTED volume: what a zero-run-compressing transport
    # would move under active-set delta shipping (== wire_bytes without a
    # delta codec).  The §2.1 accounting contract — compare bytes_shipped.
    bytes_accounted: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0))
    # what the selected transport's collectives REALLY moved this ship:
    # dense = static payload (+ flags wire), ragged = compacted payload +
    # slot indices + counts (§2.1.1).
    bytes_shipped: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0))
    ragged: jnp.ndarray = dataclasses.field(       # 1.0 = ragged plan taken
        default_factory=lambda: jnp.float32(0))
    route_active_max: jnp.ndarray = dataclasses.field(  # per-dest occupancy
        default_factory=lambda: jnp.int32(0))
    route_width: int = 0            # static K of this ship's route
    # robustness counters (DESIGN.md §6): ragged->dense overflow fallbacks
    # taken, integrity-word failures, and routes degraded to a raw dense
    # ship after the retry also failed.  f32 like the byte fields so zero()
    # stays aval-stable across cond/while branches.
    overflow: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0))
    wire_faults: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0))
    degraded: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0))
    # per-DESTINATION occupancy fractions [P] from the routed transport
    # (TransportInfo.route_active_frac) — the vector the §2.1.3 per-dest
    # tier planner feeds on.  Scalar 0 when nothing shipped; merge's
    # elementwise maximum broadcasts it against live ships' vectors.
    route_active_frac: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0))
    # ring-lowered LINK traffic model (§2.1.3, PR-9 follow-up (a)):
    # `bytes_shipped` counts ORIGINATION bytes — what each chip hands the
    # collective.  On a ring, an all_to_all block stays on the wire for one
    # hop but the (P-1)/P of it addressed off-chip is all that leaves, and
    # an all-gathered block traverses P-1 links.  This field applies those
    # factors, so BENCH rows state what the interconnect really carries.
    bytes_link_modeled: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0))

    @property
    def bytes_on_wire(self) -> jnp.ndarray:
        """Backward-compat alias: the PR-3 accounting number."""
        return self.bytes_accounted

    @classmethod
    def zero(cls) -> "ShipMetrics":
        """The no-ship element: what a statically-clean view refresh (zero
        route collectives) reports, and merge()'s identity.  Count fields
        carry the dtype a live ship's `flags.sum()` produces (the default
        integer dtype, which follows the x64 config) — a clean and a
        shipping refresh must present identical avals across lax.cond /
        while-carry branches."""
        nz = jnp.zeros((), jax.dtypes.canonicalize_dtype(jnp.int64))
        return cls(0, nz, nz)

    def merge(self, other: "ShipMetrics") -> "ShipMetrics":
        """Combine the metrics of two route ships into one pipeline-level
        record: byte and count fields add; `ragged` and the per-route
        occupancy facts take the max (a merged record says "any ship
        compacted" / "the fullest route looked like this"), which is the
        conservative read for the host-side capacity planner."""
        return ShipMetrics(
            wire_bytes=self.wire_bytes + other.wire_bytes,
            effective_bytes=self.effective_bytes + other.effective_bytes,
            n_shipped=self.n_shipped + other.n_shipped,
            bytes_accounted=self.bytes_accounted + other.bytes_accounted,
            bytes_shipped=self.bytes_shipped + other.bytes_shipped,
            ragged=jnp.maximum(self.ragged, other.ragged),
            route_active_max=jnp.maximum(self.route_active_max,
                                         other.route_active_max),
            route_width=max(self.route_width, other.route_width),
            overflow=self.overflow + other.overflow,
            wire_faults=self.wire_faults + other.wire_faults,
            degraded=self.degraded + other.degraded,
            route_active_frac=jnp.maximum(self.route_active_frac,
                                          other.route_active_frac),
            bytes_link_modeled=(self.bytes_link_modeled
                                + other.bytes_link_modeled))

    def across(self, ex: Exchange) -> "ShipMetrics":
        """The record summed over the executors of `ex`: byte and count
        fields add, the plan and occupancy facts take the largest, as in
        `merge`.  The identity where one executor holds every partition."""
        add = ("effective_bytes", "n_shipped", "bytes_accounted",
               "bytes_shipped", "overflow", "wire_faults", "degraded",
               "bytes_link_modeled")
        return dataclasses.replace(self, **{
            f.name: (ex.psum if f.name in add else ex.pmax)(
                getattr(self, f.name))
            for f in dataclasses.fields(self)
            if f.name not in ("wire_bytes", "route_width")})

    def tree_flatten(self):
        return ((self.effective_bytes, self.n_shipped, self.bytes_accounted,
                 self.bytes_shipped, self.ragged, self.route_active_max,
                 self.overflow, self.wire_faults, self.degraded,
                 self.route_active_frac, self.bytes_link_modeled),
                (self.wire_bytes, self.route_width))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], *children[:6], route_width=aux[1],
                   overflow=children[6], wire_faults=children[7],
                   degraded=children[8], route_active_frac=children[9],
                   bytes_link_modeled=children[10])


def metrics_across(metrics: dict, ex: Exchange) -> dict:
    """The array entries of an mr_triplets metrics dict summed over the
    executors of `ex`, so that the host reads one value of each: counts and
    bytes add; the plan flags (`ragged`, `transport_state`) take the
    largest.  Static entries (names, counts known at trace time) and the
    per-partition `emask_pushed` are left out."""
    out = {}
    for k, v in metrics.items():
        if isinstance(v, (str, int)) or k == "emask_pushed":
            continue
        if isinstance(v, ShipMetrics):
            out[k] = v.across(ex)
        elif k in ("ragged", "transport_state"):
            out[k] = ex.pmax(v)
        else:
            out[k] = ex.psum(v)
    return out


def _route_ship(ex: Exchange, sendbuf: Any, flags: jnp.ndarray, *,
                bound: int | None, elem_bytes: int,
                transport: transport_mod.TransportPolicy = transport_mod.DENSE,
                prefer_ragged: jnp.ndarray | None = None,
                recvflags: jnp.ndarray | None = None,
                label: str = "fwd"):
    """Move one routed [nl, P, K, ...] buffer + its freshness flags through
    the selected transport and account it — the single home for the
    active-mask/payload_bound threading that ship_to_mirrors and
    ship_aggregates_home share (DESIGN.md §2.1.1).

    flags double as the wire's active set: the codec zero-substitutes and
    delta-accounts stale entries (§4.5.1 reaching the physical wire), and
    the ragged transport compacts exactly these entries.  Returns
    (recvbuf, recvflags, ShipMetrics); recvbuf entries outside recvflags
    are unspecified (zeros) and must be masked by the consumer."""
    codec = ex.codec
    transport_mod.record_ship(label, transport.kind,
                              f"K={flags.shape[-1]}")
    with jax.named_scope("graphx.exchange"):
        recvbuf, rflags, info = transport_mod.ship_transport(
            ex, sendbuf, flags, bound=bound, policy=transport,
            prefer_ragged=prefer_ragged, recvflags=recvflags)
    metrics = ShipMetrics(
        wire_bytes=wire_mod.static_wire_bytes(sendbuf, codec, bound),
        effective_bytes=flags.sum() * elem_bytes,
        n_shipped=flags.sum(),
        bytes_accounted=wire_mod.bytes_on_wire(sendbuf, codec, flags, bound),
        bytes_shipped=info.bytes_shipped,
        ragged=info.ragged,
        route_active_max=info.route_active_max,
        route_width=flags.shape[-1],
        overflow=jnp.asarray(info.overflow, jnp.float32),
        wire_faults=jnp.asarray(info.wire_faults, jnp.float32),
        degraded=jnp.asarray(info.degraded, jnp.float32),
        route_active_frac=jnp.asarray(info.route_active_frac, jnp.float32),
        # a2a on a ring: each chip's diagonal block never leaves it, so the
        # interconnect carries (P-1)/P of the origination bytes.
        bytes_link_modeled=jnp.asarray(
            info.bytes_shipped * (flags.shape[1] - 1) / max(flags.shape[1], 1),
            jnp.float32),
    )
    return recvbuf, rflags, metrics


def ship_to_mirrors(
    s,                      # StructArrays (duck-typed: routes, v_mir, p)
    values: Any,            # pytree [P, V_blk, ...]
    need: str,              # "src" | "dst" | "both"
    ex: Exchange,
    *,
    active: jnp.ndarray | None = None,   # [P, V_blk] bool — ship only these
    cache: ViewCache | None = None,
    bound: int | None = None,            # |value| bound for int wire packing
    transport: Any = None,               # dense|ragged|auto plan (§2.1.1)
    prefer_ragged: jnp.ndarray | None = None,
) -> tuple[ViewCache, ShipMetrics]:
    """Materialise the replicated vertex view for one need set.

    When the structure classified a BROADCAST SET (partition.build_structure
    with bcast_min_repl — DESIGN.md §2.1.3), the forward ship splits into
    two lanes: high-replication vertices move ONCE per source through the
    all-gather collective (`transport.allgather_ship`, scattered via the
    `brecv` tables), and the point-to-point lane runs over the RESIDUAL
    routes (`p2p_routes`, K shrunk by the hubs).  Both lanes write the same
    mirror slots the unified route would have — placement changes bytes,
    never values.  The aggregate RETURN (`ship_aggregates_home`) keeps the
    full routes: reductions cannot all-gather."""
    tp = transport_mod.resolve_transport(transport)
    use_bcast = (getattr(s, "brecv", None) is not None
                 and getattr(s, "p2p_routes", None) is not None)
    send_idx, recv_slot = (s.p2p_routes if use_bcast else s.routes)[need]
    # nl = partitions on this device (= P globally, 1 inside shard_map);
    # the middle axis is always the GLOBAL partner count.
    nl, p, k = send_idx.shape
    valid = send_idx >= 0
    safe_idx = jnp.maximum(send_idx, 0)
    elem_bytes = nbytes_of(jax.tree.map(lambda v: v[0, 0], values))

    # sender-side gather;  flags mark entries that must overwrite the view
    flags = valid if active is None else (
        valid & jax.vmap(lambda a, i: jnp.take(a, i, mode="clip"))(
            active, safe_idx.reshape(nl, -1)).reshape(nl, p, k))
    sendbuf = jax.tree.map(
        lambda v: jax.vmap(lambda vv, ii: jnp.take(vv, ii, axis=0, mode="clip"))(
            v, safe_idx.reshape(nl, -1)).reshape((nl, p, k) + v.shape[2:]),
        values)
    sendbuf = tree_where(flags, sendbuf, jax.tree.map(jnp.zeros_like, sendbuf))

    # full ship: the flag pattern is STRUCTURAL (route padding), already
    # known at the receiver as recv_slot validity — the dense path skips
    # the flags collective entirely (one of the two forward a2a buffers).
    # This holds with or without a cache: active=None means every valid
    # route entry is fresh (direction-widening ships into an existing view
    # are full ships over the new routes).
    structural = (recv_slot < s.v_mir) if active is None else None
    recvbuf, recvflags, metrics = _route_ship(
        ex, sendbuf, flags, bound=bound, elem_bytes=elem_bytes,
        transport=tp, prefer_ragged=prefer_ragged, recvflags=structural)

    # receiver-side INCREMENTAL scatter into mirror slots (slots are unique
    # per partition): only fresh entries write — idx routes stale/padded
    # entries out of range, so with a cache the previous superstep's mirror
    # is updated in place rather than rebuilt and re-selected (§4.5.1).
    idx = jnp.where(recvflags, recv_slot, s.v_mir).reshape(nl, -1)
    # a narrow-RESIDENT cache (§2.4) holds encoded leaves; the incremental
    # scatter needs full-precision rows, so decode here and re-encode once
    # after BOTH lanes have written.  Untouched scale blocks round-trip
    # value-exact (decode can only lower a block's absmax); blocks a fresh
    # row landed in re-quantize against the new absmax.
    init = (wire_mod.decode_tree(cache.mirror) if cache is not None
            else jax.tree.map(
        lambda l: jnp.zeros((nl, s.v_mir) + l.shape[3:], l.dtype), recvbuf))
    mirror = jax.tree.map(
        lambda b, leaf: scatter_rows(
            b, idx, leaf.reshape((nl, p * k) + leaf.shape[3:])),
        init, recvbuf)
    shipped = scatter_rows(jnp.zeros((nl, s.v_mir), bool), idx,
                           jnp.ones((nl, p * k), bool))

    if use_bcast:
        # ---- broadcast lane: one payload per SOURCE, delivered mesh-wide.
        bvalid = s.bsend >= 0                                  # [nl, B]
        bidx = jnp.maximum(s.bsend, 0)
        b = bvalid.shape[1]
        bflags = bvalid if active is None else (
            bvalid & jax.vmap(lambda a, i: jnp.take(a, i, mode="clip"))(
                active, bidx))
        btree = jax.tree.map(
            lambda v: jax.vmap(
                lambda vv, ii: jnp.take(vv, ii, axis=0, mode="clip"))(
                    v, bidx), values)
        btree = tree_where(bflags, btree,
                           jax.tree.map(jnp.zeros_like, btree))
        transport_mod.record_ship("fwd", "bcast", f"B={b}")
        recvb, rfb, binfo = transport_mod.allgather_ship(
            ex, btree, bflags, bound=bound, integrity=tp.integrity)
        # scatter each source's block through its brecv table; v_mir drops
        # rows this partition does not mirror (or that are stale).
        brecv = s.brecv[need]                                  # [nl, P, B]
        bscat = jnp.where(rfb & (brecv < s.v_mir), brecv,
                          s.v_mir).reshape(nl, -1)
        mirror = jax.tree.map(
            lambda m, leaf: scatter_rows(
                m, bscat, leaf.reshape((nl, p * b) + leaf.shape[3:])),
            mirror, recvb)
        bshipped = scatter_rows(jnp.zeros((nl, s.v_mir), bool), bscat,
                                jnp.ones((nl, p * b), bool))
        shipped = shipped | bshipped
        staged = jax.tree.map(lambda x: x[:, None], btree)
        bmetrics = ShipMetrics(
            wire_bytes=transport_mod.allgather_wire_bytes(
                staged, ex.codec, bound, p, flags_shipped=True),
            effective_bytes=(rfb & (brecv < s.v_mir)).sum() * elem_bytes,
            n_shipped=bflags.sum(),
            bytes_accounted=wire_mod.bytes_on_wire(
                staged, ex.codec, bflags[:, None], bound),
            bytes_shipped=binfo.bytes_shipped,
            # occupancy facts stay zero: the broadcast lane has no capacity
            # to plan, and its B must not distort the p2p tier planner.
            overflow=jnp.asarray(binfo.overflow, jnp.float32),
            wire_faults=jnp.asarray(binfo.wire_faults, jnp.float32),
            degraded=jnp.asarray(binfo.degraded, jnp.float32),
            # ring all-gather: every contributed block traverses P-1 links
            # (origination accounting understates link traffic by (P-1)x).
            bytes_link_modeled=jnp.asarray(
                binfo.bytes_shipped * max(p - 1, 0), jnp.float32))
        metrics = metrics.merge(bmetrics)

    codec = ex.codec
    if codec is not None and codec.resident:
        mirror = jax.tree.map(
            lambda l: (wire_mod.encode_resident(
                l, codec, wire_mod.resident_kind(l.dtype, codec, bound),
                bound=bound)
                if wire_mod.resident_kind(l.dtype, codec, bound) else l),
            mirror)
    filled = shipped if cache is None else (cache.filled | shipped)
    return ViewCache(mirror=mirror, filled=filled, active=shipped), metrics


def ship_aggregates_home(
    s,
    partial: Any,            # pytree [P, V_mir, ...] partial aggregates
    had_msg: jnp.ndarray,    # [P, V_mir] bool
    need: str,
    reduce: str,
    ex: Exchange,
    *,
    bound: int | None = None,
    transport: Any = None,               # dense|ragged|auto plan (§2.1.1)
    prefer_ragged: jnp.ndarray | None = None,
    combine: bool = True,
) -> tuple[Any, jnp.ndarray, ShipMetrics]:
    """Return partial aggregates to vertex homes and combine (reduce UDF is
    commutative-associative, §3.2, so cross-partition combining is a
    scatter-reduce).

    combine=False stops after the route collective and hands back the RAW
    routed buffer (recv [nl, P, K, ...], rflags [nl, P, K]) instead of the
    combined per-home values — the seam the fused superstep apply
    (kernels/superstep.py) consumes, performing the combine inside the same
    kernel as the vprog so aggregates never materialise per-home in HBM."""
    send_idx, recv_slot = s.routes[need]
    nl, p, k = send_idx.shape

    def gather_leaf(leaf):
        flat = jax.vmap(lambda t, i: jnp.take(t, i, axis=0, mode="clip"))(
            leaf, recv_slot.reshape(nl, -1))
        return flat.reshape((nl, p, k) + leaf.shape[2:])

    backbuf = jax.tree.map(gather_leaf, partial)
    backflags = jax.vmap(lambda t, i: jnp.take(t, i, mode="clip"))(
        had_msg, recv_slot.reshape(nl, -1)).reshape(nl, p, k)
    backflags &= recv_slot < s.v_mir

    # backflags as the wire's active set: positions the receiver will
    # discard (empty mirror slots holding the reduce identity, route
    # padding) are zero-substituted BEFORE the codec — an int32 identity
    # (2^31-1) would otherwise wrap a lossless int16 cast and a float
    # identity would blow up a quantization block's absmax.
    #
    # The int-packing bound certifies individual message VALUES; min/max
    # aggregates preserve it, but partial SUMS can exceed it — no lossless
    # narrowing on the return wire for sum reduces (float quantization is
    # value-adaptive and stays on).
    if reduce == "sum":
        bound = None
    recv, rflags, metrics = _route_ship(
        ex, backbuf, backflags, bound=bound,
        elem_bytes=nbytes_of(jax.tree.map(lambda v: v[0, 0], partial)),
        transport=transport_mod.resolve_transport(transport),
        prefer_ragged=prefer_ragged, label="back")
    if not combine:
        return recv, rflags, metrics

    v_blk = s.home_mask.shape[1]
    scatter_ops = {"sum": "add", "min": "min", "max": "max"}
    mode = scatter_ops[reduce]

    def combine_leaf(leaf):
        # narrow wire dtypes accumulate in f32 at the home partition
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            leaf = leaf.astype(jnp.float32)
        ident = _REDUCE_IDENTITY[reduce](leaf.dtype)
        if reduce == "sum" and jnp.issubdtype(leaf.dtype, jnp.floating):
            # FIXED-ORDER f32 sum (§2.4, PR-7 follow-up (b)): one source
            # partition's route entries target DISTINCT home rows, so each
            # [nl, pe] slab is a collision-free scatter-add; accumulating
            # slabs in ascending pe is a deterministic association that the
            # fused apply kernel reproduces exactly (its apply tiles never
            # mix source partitions within a chunk, and chunks visit a home
            # block in ascending pe).  This is what lets sums fuse by
            # default instead of opt-in.
            init = jnp.zeros((nl, v_blk) + leaf.shape[3:], leaf.dtype)
            out = init
            for pe in range(p):
                x = jnp.where(bmask(rflags[:, pe], leaf[:, pe]),
                              leaf[:, pe], 0)
                idx = jnp.where(rflags[:, pe], send_idx[:, pe], v_blk)
                out = jax.vmap(
                    lambda b, ii, xx: b.at[ii].add(xx, mode="drop"))(
                        out, idx, x)
            return out
        flat = leaf.reshape((nl, p * k) + leaf.shape[3:])
        flat = jnp.where(bmask(rflags.reshape(nl, -1), flat), flat, ident)
        init = jnp.full((nl, v_blk) + leaf.shape[3:], ident, leaf.dtype)
        idx = jnp.where(rflags, send_idx, v_blk).reshape(nl, -1)  # OOB drop
        return jax.vmap(lambda b, ii, x: getattr(b.at[ii], mode)(x, mode="drop"))(
            init, idx, flat)

    out = jax.tree.map(combine_leaf, recv)
    exists = jax.vmap(lambda b, ii, x: b.at[ii].max(x, mode="drop"))(
        jnp.zeros((nl, v_blk), jnp.int32),
        jnp.where(rflags, send_idx, v_blk).reshape(nl, -1),
        rflags.reshape(nl, -1).astype(jnp.int32)) > 0
    return out, exists, metrics


def _segment_aggregate(msgs: Any, ids: jnp.ndarray, valid: jnp.ndarray,
                       v_mir: int, reduce: str, kernel_mode: str):
    """Per-partition segment reduction of edge messages into mirror slots.

    msgs: pytree [nl, E, ...]; ids: [nl, E] slots (dst or src side); valid [nl,E].
    Flattens the local-partition axis into the segment space so one kernel
    call covers all local partitions (ids stay sorted within each block).
    """
    nl, e = ids.shape
    num_seg = nl * v_mir
    flat_ids = jnp.where(valid, ids + jnp.arange(nl, dtype=jnp.int32)[:, None] * v_mir,
                         num_seg).reshape(-1)

    def agg_leaf(leaf):
        flat = leaf.reshape(nl * e, -1)
        if reduce == "sum" and jnp.issubdtype(leaf.dtype, jnp.floating):
            out = kops.segment_sum(flat, flat_ids, num_seg, mode=kernel_mode)
        else:
            fill = jnp.where(bmask(valid, leaf), leaf, _REDUCE_IDENTITY[reduce](leaf.dtype))
            flat = fill.reshape(nl * e, -1)
            fn = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
                  "max": jax.ops.segment_max}[reduce]
            out = fn(flat, flat_ids.clip(0, num_seg), num_segments=num_seg + 1)[:num_seg]
        return out.reshape((nl, v_mir) + leaf.shape[2:])

    partial = jax.tree.map(agg_leaf, msgs)
    counts = jax.ops.segment_sum(valid.reshape(-1).astype(jnp.int32),
                                 flat_ids.clip(0, num_seg),
                                 num_segments=num_seg + 1)[:num_seg]
    had_msg = counts.reshape(nl, v_mir) > 0
    return partial, had_msg


# ---------------------------------------------------------------------------
# Fused triplet path (§4.6 executed inside one Pallas kernel, DESIGN.md §2.3)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _FusedPlan:
    """Static packing layout for the fused triplet kernel."""

    v_used: tuple[bool, ...]      # union: vdata leaves packed into the x matrix
    src_used: tuple[bool, ...]    # leaves the UDF reads through the SRC side
    dst_used: tuple[bool, ...]    # leaves the UDF reads through the DST side
    e_used: bool                  # whether the edge payload packs at all
    dm: int                       # TOTAL packed message width (all leaves)
    msg_widths: tuple[int, ...]   # per-leaf flattened column widths
    msg_shapes: tuple[tuple[int, ...], ...]   # per-leaf element shapes
    msg_dtypes: tuple[Any, ...]   # per-leaf dtypes (staging casts back)
    msg_treedef: Any


# f32 mantissa: integers round-trip the kernel's f32 staging exactly below
# this bound.
_INT_STAGE_BOUND = 1 << 24


def _fused_int_ok(dtype, bound: int) -> bool:
    """Can integer values of `dtype` ride the kernel's f32 staging exactly?

    Narrow ints (≤ 16 bits) are bounded by their own dtype.  Signed 32-bit
    ints are admitted when the payload's static |value| bound is below the
    24-bit mantissa bound.  The bound is either user-supplied
    (`payload_bound=` on mrTriplets/pregel — timestamps, counters, any
    value-range the caller can certify) or defaults to the graph's
    `max_vid`: the id-valued convention covering CC labels, LP labels, SSSP
    parents, every §3.3 integer payload.  Either way the bound must also
    cover the int MESSAGE leaves the UDF computes (a map like `label * 3`
    can escape a bound its inputs satisfy — such UDFs need a wider
    payload_bound or kernel_mode="unfused").  Unsigned 32-bit ints are NOT
    admitted: by convention they carry bit patterns (triangle counting's
    neighbourhood bitsets), which f32 staging would silently truncate."""
    info = np.iinfo(np.dtype(dtype))
    if info.bits <= 16:
        return True
    return info.bits <= 32 and info.kind == "i" and bound < _INT_STAGE_BOUND


def _fused_leaf_ok(spec, bound: int, reduce: str,
                   message: bool = False) -> bool:
    """The kernel packs flat payloads (rank ≤ 1) staged through f32.

    Floats always qualify (staging widens).  Integers qualify under the
    exact-round-trip guard (_fused_int_ok); integer MESSAGE leaves
    additionally require a value-preserving reduce — min/max never invent
    values, while f32-staged sums can escape the 24-bit mantissa even when
    every addend fits it."""
    if len(spec.shape) > 1:
        return False
    dt = spec.dtype
    if jnp.issubdtype(dt, jnp.floating):
        return True
    if jnp.issubdtype(dt, jnp.integer):
        if message and reduce == "sum":
            return False
        return _fused_int_ok(dt, bound)
    return False


def _derive_need(deps, force_need: str | None) -> str | None:
    """Which vertex side(s) the physical join must ship — the ONE place the
    need set is derived (mr_triplets, plan_of, and pregel's metrics must
    agree or reported plans drift from executed ones)."""
    if force_need is not None:
        return force_need
    return ("both" if (deps.uses_src and deps.uses_dst)
            else "src" if deps.uses_src
            else "dst" if deps.uses_dst else None)


def _union_need(a: str | None, b: str | None) -> str | None:
    """Union of two need sets (the ship for a fused subgraph+mrTriplets
    pair must cover both UDFs' reads)."""
    if a is None:
        return b
    if b is None or a == b:
        return a
    return "both"


def _plan_fused(g, map_fn, deps, need, reduce, force_need,
                vex, eex, payload_bound: int | None = None
                ) -> _FusedPlan | None:
    """Decide whether this mrTriplets can run fused; None -> unfused path.

    Eligibility: sum/min/max reduce; flat float-or-exact-int message leaves
    (multi-leaf messages column-pack into one kernel matrix); flat
    float-or-exact-int vertex/edge payloads on the sides the UDF reads; and
    device-resident tile tables on the structure (built at from_edges —
    absent only for shape-spec dry-run graphs).  The tables are per-partition
    pytree children, so the plan holds both under LocalExchange (nl == P)
    and inside shard_map (nl == 1, each device sweeps its own tiling).

    Integer staging is guarded by `payload_bound` when supplied, else by the
    graph's max_vid (the id-valued convention, §2.3.1)."""
    if reduce not in ("sum", "min", "max") or g.s.tiles is None:
        return None
    msg_spec = deps.msg_spec     # captured by the join-elimination trace
    if msg_spec is None:         # UDF untraceable -> no fused plan
        return None
    max_vid = (payload_bound if payload_bound is not None else g.s.max_vid)
    msg_leaves, msg_treedef = jax.tree.flatten(msg_spec)
    if not msg_leaves or not all(
            _fused_leaf_ok(m, max_vid, reduce, message=True)
            for m in msg_leaves):
        return None

    vleaves = jax.tree.leaves(vex)
    n = len(vleaves)
    if need is None:
        src_used = dst_used = (False,) * n
    elif (force_need is None and deps.src_leaves is not None
          and len(deps.src_leaves) == n):
        src_used, dst_used = deps.src_leaves, deps.dst_leaves
    else:  # forced join / unknown leaves: whole sides named by `need`
        src_used = (need in ("src", "both"),) * n
        dst_used = (need in ("dst", "both"),) * n
    v_used = tuple(su or du for su, du in zip(src_used, dst_used))
    if not all(_fused_leaf_ok(l, max_vid, reduce)
               for l, u in zip(vleaves, v_used) if u):
        return None

    eleaves = jax.tree.leaves(eex)
    e_used = bool(eleaves) and (deps.uses_edge or force_need is not None)
    if e_used and not all(_fused_leaf_ok(l, max_vid, reduce)
                          for l in eleaves):
        return None

    widths = tuple(int(np.prod(m.shape, dtype=np.int64)) if m.shape else 1
                   for m in msg_leaves)
    dm = sum(widths)
    if reduce != "sum" and dm > FUSED_MINMAX_MAX_WIDTH:
        return None
    return _FusedPlan(v_used=v_used, src_used=src_used, dst_used=dst_used,
                      e_used=e_used, dm=dm, msg_widths=widths,
                      msg_shapes=tuple(tuple(m.shape) for m in msg_leaves),
                      msg_dtypes=tuple(m.dtype for m in msg_leaves),
                      msg_treedef=msg_treedef)


@functools.lru_cache(maxsize=256)
def _make_tile_fn(map_fn, vspecs, vdef, especs, edef, plan: _FusedPlan):
    """Tile-level message function for the kernel: unpack the column-packed
    endpoint/edge matrices back into the UDF's pytrees, vmap the UDF over the
    edge axis, flatten the message leaf.  Pure jnp — traced into the kernel.

    Memoised on (UDF identity, specs, plan): the returned closure is a STATIC
    jit argument of kernels/triplet.fused_triplet, so handing back the same
    object for repeated eager calls is what lets the kernel's jit cache hit
    (a fresh closure per call would recompile every superstep)."""
    vleaves, eleaves = list(vspecs), list(especs)

    def unpack(mat, specs, packed, used, treedef):
        """Column offsets advance over the PACKED (union) leaves; a leaf is
        read from the matrix only if this SIDE uses it.  A side that reads
        nothing never touches `mat` — which is what lets fused_triplet
        stream a width-1 dummy tile for that side.

        Float leaves stay in the f32 staging dtype (deliberate upcast);
        integer leaves cast BACK to their declared dtype, so the UDF sees
        the same integer arithmetic as the unfused path — exact, because
        the planner's round-trip guard admitted the values."""
        out, off = [], 0
        for spec, p, u in zip(specs, packed, used):
            size = int(np.prod(spec.shape, dtype=np.int64)) if spec.shape else 1
            is_int = jnp.issubdtype(spec.dtype, jnp.integer)
            dt = spec.dtype if is_int else jnp.float32
            if p and u:
                col = mat[:, off:off + size]
                out.append(col.reshape((mat.shape[0],) + tuple(spec.shape))
                           .astype(dt))
            else:  # provably unread by the UDF (join elimination) -> zeros
                out.append(jnp.zeros((mat.shape[0],) + tuple(spec.shape), dt))
            if p:
                off += size
        return jax.tree.unflatten(treedef, out)

    e_packed = (plan.e_used,) * len(eleaves)

    def tile_fn(sv, ev, dv):
        s_tree = unpack(sv, vleaves, plan.v_used, plan.src_used, vdef)
        d_tree = unpack(dv, vleaves, plan.v_used, plan.dst_used, vdef)
        e_tree = unpack(ev, eleaves, e_packed, e_packed, edef)
        msg = jax.vmap(map_fn)(s_tree, e_tree, d_tree)
        # multi-leaf messages column-pack into one [Eb, dm] matrix; the
        # engine splits the kernel output back along plan.msg_widths.
        cols = [l.reshape(l.shape[0], -1).astype(jnp.float32)
                for l in jax.tree.leaves(msg)]
        return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=-1)

    return tile_fn


def _pack_cols(tree, used, nl: int, n: int) -> jnp.ndarray:
    """Column-pack the used leaves of a [nl, N, ...] pytree into [nl, N, D].

    Staging dtype: when EVERY packed leaf is bfloat16 (a narrow-wire mirror,
    §2.1) the packed matrix stays bf16 — the kernel and the jnp oracle both
    upcast tiles to f32 at the accumulator, so results are bit-identical to
    f32 staging while the packed matrix's HBM reads halve.  Any other mix
    stages through f32 (exact for the integer leaves the planner admitted)."""
    leaves = jax.tree.leaves(tree) if tree is not None else []
    cols = [l.reshape(nl, n, -1) for l, u in zip(leaves, used) if u]
    if not cols:
        return jnp.zeros((nl, n, 0), jnp.float32)
    stage = (jnp.bfloat16 if all(c.dtype == jnp.bfloat16 for c in cols)
             else jnp.float32)
    return jnp.concatenate([c.astype(stage) for c in cols], axis=-1)


def _pack_cols_encoded(tree, used, nl: int, n: int):
    """Column-pack narrow-RESIDENT leaves WITHOUT decoding (§2.4).

    Returns (payload [nl, n, D] in the shared narrow dtype, scale
    [nl, ceil(n/SCALE_GROUP), D] int8 exponents), or None when the used
    leaves cannot share one encoded staging matrix — not all resident,
    mixed payload dtypes, or a scale block that differs from the kernel's
    SCALE_GROUP — in which case the caller decodes on read.  "int"-kind
    leaves ride along with zero exponents (exp2(0) == 1, and their payload
    upcasts to f32 exactly under the plan's round-trip guard)."""
    if tree is None:
        return None
    leaves = jax.tree.leaves(tree, is_leaf=wire_mod.is_resident)
    sel = [l for l, u in zip(leaves, used) if u]
    if not sel or not all(wire_mod.is_resident(l) for l in sel):
        return None
    pdt = sel[0].payload.dtype
    if any(l.payload.dtype != pdt or l.block != SCALE_GROUP for l in sel):
        return None
    nb = max(-(-n // SCALE_GROUP), 1)
    pcols, scols = [], []
    for l in sel:
        pc = l.payload.reshape(nl, n, -1)
        pcols.append(pc)
        if l.scale is None:
            scols.append(jnp.zeros((nl, nb, pc.shape[-1]), jnp.int8))
        else:
            scols.append(l.scale.reshape(nl, nb, -1))
    return (jnp.concatenate(pcols, axis=-1),
            jnp.concatenate(scols, axis=-1))


def _fused_aggregate(g, mirror_tree, map_fn, live, to, reduce, kernel_mode,
                     plan: _FusedPlan, vex, eex):
    """Steps 4a-4c of the physical plan in one kernel sweep: gather both
    endpoint views, run the map UDF, segment-reduce into mirror slots.

    The chunk tables come from the structure itself (s.tiles — device-
    resident, per-partition, built once at from_edges): each partition's
    LOCAL tiling is mapped onto the stacked flat space by `flatten_tiles`
    with the partition's slot space padded to whole vertex blocks, so the
    SAME code serves LocalExchange (nl == P) and shard_map (nl == 1, every
    device sweeping its own slice of the tables).  The tables carry each
    chunk edge's slots (`rows`), which the kernel reads in place of the
    flat slots below; only the jnp oracle reads `fsrc`/`fdst`.  The edge
    payload is packed only where the plan reads it (`plan.e_used`).

    `mirror_tree` may hold narrow-RESIDENT leaves (§2.4): when every used
    leaf shares one encoded layout the kernel streams the NARROW payload
    plus its scale plane and dequantizes per tile in VMEM; otherwise the
    tree decodes on read here — ineligible mixes never error.

    Returns (partial, had_msg, chunks_live): the kernel's count of chunks
    with a live edge, None on the jnp oracle."""
    s = g.s
    nl = live.shape[0]
    vb = FUSED_VERTEX_BLOCK
    n_vb = max(-(-s.v_mir // vb), 1)
    v_pad = n_vb * vb            # per-partition slot space, block-aligned
    seg = nl * v_pad
    xscale = None
    enc = _pack_cols_encoded(mirror_tree, plan.v_used, nl, s.v_mir)
    if enc is not None:
        x, sc = enc
        n_sc = v_pad // SCALE_GROUP
        sc = jnp.pad(sc, ((0, 0), (0, n_sc - sc.shape[1]), (0, 0)))
        xscale = sc.reshape(nl * n_sc, sc.shape[-1])
    else:
        mirror_tree = wire_mod.decode_tree(mirror_tree)
        x = _pack_cols(mirror_tree, plan.v_used, nl, s.v_mir)
    x = jnp.pad(x, ((0, 0), (0, v_pad - s.v_mir), (0, 0)))
    x = x.reshape(seg, x.shape[-1])
    n_eleaves = len(jax.tree.leaves(g.edata))
    ev = _pack_cols(g.edata, (plan.e_used,) * n_eleaves, nl, s.e_blk)
    ev = ev.reshape(nl * s.e_blk, ev.shape[-1])
    off = (jnp.arange(nl, dtype=jnp.int32) * v_pad)[:, None]
    fsrc = (s.src_slot + off).reshape(-1)
    fdst = (s.dst_slot + off).reshape(-1)
    # the jnp oracle ignores the chunk tiling — skip the flattening work on
    # the default CPU path.
    tiles = (None if kops.resolve_mode(kernel_mode) == "ref"
             else flatten_tiles(s.tiles[to], e_blk=s.e_blk, n_vb=n_vb))
    tile_fn = _make_tile_fn(map_fn,
                            tuple(jax.tree.leaves(vex)), jax.tree.structure(vex),
                            tuple(jax.tree.leaves(eex)), jax.tree.structure(eex),
                            plan)
    out, cnt, chunks_live = kops.triplet(
        x, ev, fsrc, fdst, live.reshape(-1), tiles, tile_fn, seg, plan.dm,
        xscale=xscale, to=to, reduce=reduce, use_src=any(plan.src_used),
        use_dst=any(plan.dst_used), mode=kernel_mode,
        eb=FUSED_EDGE_BLOCK, vb=FUSED_VERTEX_BLOCK)
    out = out.reshape(nl, v_pad, plan.dm)[:, :s.v_mir]
    had_msg = cnt.reshape(nl, v_pad)[:, :s.v_mir] > 0
    # split the packed kernel columns back into the message leaves, casting
    # each out of the f32 staging into its own dtype.
    leaves, col = [], 0
    for width, shape, dtype in zip(plan.msg_widths, plan.msg_shapes,
                                   plan.msg_dtypes):
        leaf = out[..., col:col + width].reshape((nl, s.v_mir) + shape)
        col += width
        # empty slots hold the kernel's f32 identity (finfo extremes), which
        # must NOT ride the cast below: a narrow float would overflow to inf
        # and an int would wrap.  Park a safe 0 there first, cast, then
        # re-assert the ENGINE identity in the leaf's own dtype.
        leaf = jnp.where(bmask(had_msg, leaf), leaf, 0.0).astype(dtype)
        if reduce != "sum":
            leaf = jnp.where(bmask(had_msg, leaf), leaf,
                             _REDUCE_IDENTITY[reduce](dtype))
        leaves.append(leaf)
    partial = jax.tree.unflatten(plan.msg_treedef, leaves)
    return partial, had_msg, chunks_live


def mr_triplets(
    g,                               # Graph (duck-typed)
    map_fn: Callable,                # f(src_val, edge_val, dst_val) -> msg pytree
    reduce: str = "sum",
    *,
    to: str = "dst",                 # "dst" | "src"
    skip_stale: str | None = None,   # None | "out" | "in" | "both"
    cache: ViewCache | None = None,
    kernel_mode: str = "auto",
    force_need: str | None = None,   # override join elimination (benchmarks)
    payload_bound: int | None = None,
    transport: Any = None,           # dense|ragged|auto plan (§2.1.1)
    transport_state: jnp.ndarray | None = None,  # prev decision (hysteresis)
    epred: Callable | None = None,   # pushed-down subgraph predicate (§4.4)
    return_routed: bool = False,     # fused-apply seam: skip the home combine
):
    """Execute one mrTriplets. Returns (values, exists, view, metrics).

    return_routed=True stops the physical plan after the aggregate-return
    collective: `values` is then the ROUTED recv buffer [nl, P, K, ...] and
    `exists` its freshness flags [nl, P, K] — the fused superstep apply
    (core/pregel.py via kernels/superstep.py) combines them in-kernel.

    epred: a `subgraph(epred=…)` predicate LOWERED below this mrTriplets by
    the chain planner (core/planner.py, DESIGN.md §4.4).  Its vertex reads
    union into this call's need/leaf ship (one refresh, one fold of the
    visibility ship when the graph is vmask-restricted); the predicate is
    evaluated on the refreshed mirrors and masks the per-edge `live` bits,
    so a restricted sweep feeds the fused kernel's §4.6 whole-chunk
    skipping instead of paying a separate subgraph materialisation pass.
    The combined edge mask (visibility ∧ epred, BEFORE any skip_stale
    freshness narrowing) is returned as `metrics["emask_pushed"]` for the
    caller to install as the result graph's emask.

    values: pytree [P, V_blk, ...] aggregated at vertex homes;
    exists:  [P, V_blk] bool ("WHERE sum IS NOT null", §3.2);
    view:    the refreshed graph-resident `GraphView` (DESIGN.md §3.1) —
    attach it (`g.replace(view=...)`, or use the `Graph.mrTriplets` method
    which does) and the next consumer ships only dirty leaves / missing
    directions; `metrics["ships_fwd"]` is the STATIC number of forward
    route collectives this call emitted (0 on a clean view);
    `metrics["live_edges"]` counts the edges that send a message and, on
    the fused kernel, `metrics["chunks_live"]` the chunks it swept.

    cache: explicit view override restoring the legacy §4.5.1 loop
    contract — the supplied view plus `g.active` as the changed-row set
    for every shipped leaf (eager loops that mutate vdata via `replace()`
    and track changes themselves).  Without it, the graph's own `g.view`
    (per-leaf dirty state maintained by the operators) drives the ship,
    and a viewless graph full-ships.

    kernel_mode: "auto" (fused triplet kernel when eligible — Pallas on TPU,
    jnp oracle on CPU — else unfused), "pallas"/"interpret"/"ref" (force a
    backend, still fused when eligible), or "unfused" (always take the
    gather -> vmap -> segment-reduce path).

    payload_bound: static |value| bound certified by the caller for every
    integer payload and message this mrTriplets touches.  Drives BOTH the
    fused kernel's f32 staging guard (admits int32 under bound < 2^24) and
    the wire codec's lossless narrowing width (int8 under 127, int16 under
    32767).  Defaults to the graph's max_vid — the §2.3.1 id-valued
    convention.

    transport: how the exchange buffers MOVE (core/transport.py):
    None/"dense" keeps the static all_to_all, "ragged" compacts the active
    entries per destination (overflow falls back dense via lax.cond), and
    "auto" switches per superstep on the psummed active fraction with
    hysteresis — transport_state carries the previous superstep's decision
    (metrics["transport_state"]) so the band has memory.  Both physical
    plans and every transport agree bit-for-bit under a lossless codec:
    transports change bytes, never values.

    Fused-path caches key on `map_fn`'s OBJECT IDENTITY (like jax.jit):
    eager host loops should pass the same function object every call, not a
    lambda rebuilt per iteration, or the kernel recompiles each time.
    """
    s, ex = g.s, g.ex
    nl = g.vmask.shape[0]   # local partition count (1 inside shard_map)
    # wire-packing bound: an explicit payload_bound certifies EVERY signed
    # int payload.  The id-valued default (max_vid) only speaks for int32
    # ids — ints of <= 16 bits are bounded by their own dtype, nothing
    # tighter (same rule as _fused_int_ok) — so it is floored at int16's
    # own range: int32 still narrows to int16, narrower dtypes never
    # narrow on a default bound.  max_vid == 0 means "unknown" (shape-spec
    # dry-run structures) -> no narrowing.
    bound = (payload_bound if payload_bound is not None
             else (max(s.max_vid, np.iinfo(np.int16).max)
                   if s.max_vid > 0 else None))

    vex, eex = elem_spec(g.vdata), elem_spec(g.edata)
    deps = analysis.analyze_message_fn(map_fn, vex, eex, vex)
    need = _derive_need(deps, force_need)
    if force_need is not None:
        uses_src = uses_dst = True
        arity = 1 + (need in ("src", "both")) + (need in ("dst", "both"))
    else:
        uses_src, uses_dst = deps.uses_src, deps.uses_dst
        arity = deps.n_way

    # pushed-down subgraph predicate (§4.4): its vertex reads join this
    # call's ship — one refresh covers both UDFs.
    edeps = (analysis.analyze_message_fn(epred, vex, eex, vex)
             if epred is not None else None)
    if edeps is not None:
        need = _union_need(need, _derive_need(edeps, None))

    metrics: dict[str, Any] = {"join_arity": arity, "need": need or "none"}

    # property-level join elimination (beyond §4.5.2): ship only the vdata
    # LEAVES the UDF actually reads.  Unused leaves keep whatever the view
    # holds (zeros when never shipped); since the UDF provably ignores
    # them, XLA DCEs the gathers.
    flat_vals, vtreedef = jax.tree.flatten(g.vdata)
    leaf_mask = (None if force_need is not None
                 else deps.read_leaf_mask(len(flat_vals)))
    if edeps is not None and leaf_mask is not None:
        em = edeps.read_leaf_mask(len(flat_vals))
        leaf_mask = (None if em is None else
                     tuple(a or b for a, b in zip(leaf_mask, em)))
    if leaf_mask is not None and (all(leaf_mask) or not any(leaf_mask)):
        leaf_mask = None
    metrics["shipped_leaves"] = (0 if need is None else
                                 sum(leaf_mask) if leaf_mask
                                 else len(flat_vals))

    # view resolution (DESIGN.md §3.1): an explicit `cache=` restores the
    # legacy loop-internal contract (g.active marks the changed rows);
    # otherwise the GRAPH-RESIDENT view carries per-leaf dirty state across
    # operator boundaries, and a cold graph full-ships.
    from . import view as view_mod   # late import: view.py builds on us
    if cache is not None and not isinstance(cache, view_mod.GraphView) \
            and hasattr(cache, "view"):
        # a Graph was passed (Graph.mrTriplets returns one in the cache
        # position now): use the view it carries
        cache = cache.view
    legacy = cache is not None
    graph_view = getattr(g, "view", None)
    if not legacy and not view_mod.compatible(graph_view, g.vdata, nl,
                                              s.v_mir):
        graph_view = None

    # --- transport plan (§2.1.1): dense vs ragged for THIS superstep -------
    # The ragged plan only pays off for DELTA ships (a full ship has no
    # stale entries to skip), so when no requested leaf may be dirty the
    # plan is dense.  For "auto" the decision is the psummed dirty fraction
    # against the hysteresis band — traced, mesh-uniform, carried across
    # supersteps via transport_state (pregel_fused's while carry / pregel's
    # host loop).
    tp = transport_mod.resolve_transport(transport)
    ship_rows = (g.active if legacy
                 else view_mod.dirty_rows(graph_view, leaf_mask))
    prefer_ragged = None
    tstate_new = jnp.float32(0)
    if tp.kind == "auto":
        if ship_rows is None:
            tp = transport_mod.DENSE
        else:
            frac = (ex.psum(ship_rows.sum().astype(jnp.float32))
                    / jnp.float32(max(s.p * ship_rows.shape[1], 1)))
            prev = (transport_state if transport_state is not None
                    else jnp.float32(0))
            thresh = jnp.where(prev > 0.5, jnp.float32(tp.exit_frac),
                               jnp.float32(tp.enter_frac))
            prefer_ragged = frac <= thresh
            tstate_new = prefer_ragged.astype(jnp.float32)
    metrics["transport"] = tp.kind
    metrics["transport_state"] = tstate_new

    # --- 1/2/3: materialise the replicated view THROUGH the cache ----------
    # a pushed-down epred on a vmask-restricted graph folds the visibility
    # ship into this same refresh (what subgraph would have shipped alone).
    with_vis = epred is not None and not getattr(g, "vmask_full", False)
    ships_fwd = 0
    vis_mir = None
    if need is not None or with_vis:
        lm = leaf_mask if need is not None else (False,) * len(flat_vals)
        with jax.named_scope("graphx.view"):
            refreshed = view_mod.refresh_view(
                g, need or "both", leaf_mask=lm, with_vis=with_vis,
                bound=bound, transport=tp, prefer_ragged=prefer_ragged,
                legacy_cache=cache if legacy else None)
        view, mirror_tree, vis_mir, m_fwd, ships_fwd = refreshed
        metrics["fwd"] = m_fwd
        if need is None:
            # no vertex PROPERTY was read: this call carries no property
            # freshness information (the vis-only refresh above must not
            # leak its slot set into skip_stale) — same rule as below.
            view = view.replace(active=jnp.ones((nl, s.v_mir), bool))
    else:
        mirror_tree = None
        if legacy:
            view = cache
        else:
            # no vertex data read: NO delta information exists for this
            # call, so every slot counts as fresh — a PREVIOUS consumer's
            # refresh slots must not leak into skip_stale (same rule as
            # refresh_view's entries-empty path: warm and cold agree).
            view = (graph_view if graph_view is not None
                    else view_mod.empty_view(s, g.vdata, nl, ex.codec, bound))
            view = view.replace(active=jnp.ones((nl, s.v_mir), bool))
        metrics["fwd"] = ShipMetrics.zero()

    # --- 4: edge-parallel message computation -------------------------------

    # skipStale (§3.2 / §4.6): drop edges whose relevant endpoint did not
    # change since the last ship.  "out" skips stale sources, "in" stale
    # destinations, "both" requires either endpoint fresh.  Both physical
    # plans below mask the SAME per-edge live bits, so fused vs unfused is a
    # pure execution-strategy choice, never a semantics change.
    live = g.emask
    if epred is not None:
        # §4.4 predicate pushdown reaching the §4.6 index scan: restrict
        # the per-edge live bits by endpoint visibility and the predicate
        # BEFORE the sweep, so whole all-dead chunks are skipped by the
        # fused kernel instead of materialised by a separate subgraph pass.
        take_slot = jax.vmap(lambda a, i: jnp.take(a, i, mode="clip"))
        if with_vis:
            live = live & take_slot(vis_mir, s.src_slot) \
                        & take_slot(vis_mir, s.dst_slot)
        ezeros = tree_zeros_like_elem(g.vdata, (nl, s.e_blk))
        esv = (gather_rows(mirror_tree, s.src_slot)
               if edeps.uses_src else ezeros)
        edv = (gather_rows(mirror_tree, s.dst_slot)
               if edeps.uses_dst else ezeros)
        live = live & vmap2(epred)(esv, g.edata, edv)
        metrics["emask_pushed"] = live
    if skip_stale is not None:
        take_active = jax.vmap(lambda a, i: jnp.take(a, i, mode="clip"))
        src_fresh = take_active(view.active, s.src_slot)
        dst_fresh = take_active(view.active, s.dst_slot)
        fresh = {"out": src_fresh, "in": dst_fresh,
                 "both": src_fresh | dst_fresh}[skip_stale]
        live = live & fresh
    metrics["live_edges"] = live.sum()

    # physical plan selection: the fused triplet kernel performs the gather,
    # the map UDF, and the block-local segment reduction in one sweep with
    # §4.6 chunk skipping — under LocalExchange AND inside shard_map (the
    # tile tables shard with the graph).  Ineligible shapes (non-flat
    # payloads, ints outside the f32-staging guard, exotic reduces) take the
    # unfused path, as does kernel_mode="unfused".
    plan = None
    if kernel_mode != "unfused":
        plan = _plan_fused(g, map_fn, deps, need, reduce, force_need,
                           vex, eex, payload_bound)
    metrics["plan"] = "fused" if plan is not None else "unfused"

    if plan is not None:
        # hand the fused sweep the view's POSSIBLY-ENCODED mirror (§2.4):
        # narrow-resident leaves stage without a decode materialisation —
        # the kernel dequantizes per tile in VMEM.  The decoded mirror_tree
        # stays the source for epred / the unfused gather above.
        enc_tree = view.mirror if view is not None else mirror_tree
        partial, had_msg, chunks_live = _fused_aggregate(
            g, enc_tree, map_fn, live, to, reduce, kernel_mode, plan,
            vex, eex)
        if chunks_live is not None:
            # the §4.6 index scan at grid granularity: chunks the kernel
            # swept, out of its static grid of one step per chunk
            metrics["chunks_live"] = chunks_live
    else:
        zeros_elem = tree_zeros_like_elem(g.vdata, (nl, s.e_blk))
        svals = gather_rows(mirror_tree, s.src_slot) if uses_src else zeros_elem
        dvals = gather_rows(mirror_tree, s.dst_slot) if uses_dst else zeros_elem
        msgs = vmap2(map_fn)(svals, g.edata, dvals)
        sub_mode = "auto" if kernel_mode == "unfused" else kernel_mode

        # aggregation toward the requested side
        if to == "dst":
            ids = s.dst_slot
            agg_msgs, agg_valid = msgs, live
        else:  # "src": pre-sorted permutation keeps segment ids ordered
            perm = s.src_perm
            agg_msgs = jax.tree.map(
                lambda mm: jax.vmap(lambda x, i: jnp.take(x, i, axis=0))(mm, perm),
                msgs)
            ids = jax.vmap(lambda x, i: jnp.take(x, i))(s.src_slot, perm)
            agg_valid = jax.vmap(lambda x, i: jnp.take(x, i))(live, perm)

        partial, had_msg = _segment_aggregate(agg_msgs, ids, agg_valid,
                                              s.v_mir, reduce, sub_mode)

    # --- 5: return aggregates to vertex homes --------------------------------
    # Aggregates flow back along the routing table of the side they were
    # aggregated on (structural, independent of which sides were shipped).
    # the return route gets its own capacity fraction when the plan set one
    # (the aggregate wire's occupancy decouples from the forward wire's).
    tp_back = (tp if tp.capacity_frac_back is None
               else tp.replace(capacity_frac=tp.capacity_frac_back,
                               capacity_fracs=tp.capacity_fracs_back))
    values, exists, m_back = ship_aggregates_home(
        s, partial, had_msg, to, reduce, ex, bound=bound, transport=tp_back,
        prefer_ragged=prefer_ragged, combine=return_routed is False)
    metrics["back"] = m_back
    # static route-ship count of this call: forward view-refresh collectives
    # (0 on a clean view) + the aggregate return (always 1 — it carries the
    # results).  The quantity the ship-count regression tests pin down.
    metrics["ships_fwd"] = ships_fwd
    metrics["ships"] = ships_fwd + 1
    # the headline codec metrics: forward + return wire volume after
    # narrowing, quantization, and (with a delta codec) zero-block skipping
    # — bytes_on_wire is the §2.1 ACCOUNTING number, bytes_shipped what the
    # selected transport's collectives really moved (§2.1.1).
    metrics["bytes_on_wire"] = (metrics["fwd"].bytes_on_wire
                                + m_back.bytes_on_wire)
    metrics["bytes_shipped"] = (metrics["fwd"].bytes_shipped
                                + m_back.bytes_shipped)
    # ring-lowered realism (§2.1.1): bytes a P-stage ring actually puts on
    # physical links — (P-1)/P of an all_to_all payload, (P-1)x a broadcast.
    metrics["bytes_link_modeled"] = (metrics["fwd"].bytes_link_modeled
                                     + m_back.bytes_link_modeled)
    # per-route capacities mean EITHER wire may compact (the forward route
    # can stay dense past the break-even clamp while the return route
    # compacts, and vice versa) — "ragged" means any compaction happened.
    metrics["ragged"] = jnp.maximum(metrics["fwd"].ragged, m_back.ragged)
    # resident footprint of the mirror carry (§2.4): STATIC bytes the view
    # pytree keeps in HBM between calls — the `mirror_hbm_bytes` BENCH
    # quantity the narrow-resident codec shrinks.
    metrics["mirror_hbm_bytes"] = (
        wire_mod.resident_hbm_bytes(view.mirror) if view is not None else 0)

    return values, exists, view, metrics


# ---------------------------------------------------------------------------
# Fused superstep APPLY path (DESIGN.md §2.3.2): combine + vprog + changed
# mask in one kernel at the vertex homes.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _ApplyPlan:
    """Static packing layout for the fused superstep apply kernel."""

    dm: int                       # packed message width
    dv: int                       # packed vertex-state width
    msg_specs: tuple              # per-leaf combine-dtype ShapeDtypeStructs
    msg_treedef: Any
    v_specs: tuple                # per-leaf vdata ShapeDtypeStructs
    v_treedef: Any
    defaults: tuple               # per-msg-leaf static default scalars


def _plan_apply(g, vprog: Callable, send_msg: Callable, reduce: str,
                changed_fn: Callable | None, default_msg: Any,
                payload_bound: int | None) -> _ApplyPlan | None:
    """Decide whether the superstep's apply half can run fused; None ->
    unfused apply.

    Eligibility mirrors _plan_fused's staging rules on the ROUTED aggregate
    leaves (message dtypes through the wire) and adds the apply side's own:
    every vdata leaf flat and either f32 (the staging dtype — narrower
    floats would see different vprog arithmetic) or an exact-staging int;
    the vprog traceable with output specs identical to the input state (its
    integer OUTPUT values must honour the same payload_bound that admits
    its inputs — the §2.3.1 id-valued convention); default-message leaves
    static scalars (they substitute in their own dtype INSIDE the kernel,
    so CC's 2^31-1 identity never rides the f32 staging); and the apply
    route tables present on the structure (partition.build_structure,
    tiles["apply_*"])."""
    s = g.s
    if reduce not in ("sum", "min", "max"):
        return None
    if s.tiles is None or "apply_dst" not in s.tiles:
        return None
    vex, eex = elem_spec(g.vdata), elem_spec(g.edata)
    deps = analysis.analyze_message_fn(send_msg, vex, eex, vex)
    msg_spec = deps.msg_spec
    if msg_spec is None:
        return None
    bound = payload_bound if payload_bound is not None else s.max_vid
    msg_leaves, msg_treedef = jax.tree.flatten(msg_spec)
    if not msg_leaves or not all(
            _fused_leaf_ok(m, bound, reduce, message=True)
            for m in msg_leaves):
        return None
    vleaves, vdef = jax.tree.flatten(vex)
    if not vleaves:
        return None
    for leaf in vleaves:
        if len(leaf.shape) > 1:
            return None
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            if leaf.dtype != jnp.float32:
                return None
        elif jnp.issubdtype(leaf.dtype, jnp.integer):
            if not _fused_int_ok(leaf.dtype, bound):
                return None
        else:
            return None
    # dtypes the vprog actually sees after the combine: floats upcast to f32
    # (the unfused combine_leaf accumulates float leaves in f32), ints exact.
    mspecs = tuple(
        jax.ShapeDtypeStruct(m.shape,
                             jnp.float32 if jnp.issubdtype(m.dtype,
                                                           jnp.floating)
                             else m.dtype)
        for m in msg_leaves)
    try:
        dleaves, _ = jax.tree.flatten(default_msg)
    except Exception:
        return None
    if len(dleaves) != len(msg_leaves):
        return None
    defaults = []
    for d in dleaves:
        if isinstance(d, jax.core.Tracer):
            # default_msg built INSIDE a trace has no static value — the
            # kernel bakes defaults in as compile-time scalars, so decline
            # (the unfused path handles traced defaults fine).
            return None
        arr = np.asarray(d)
        if arr.ndim != 0:
            return None
        defaults.append(arr.item())
    vid_spec = jax.ShapeDtypeStruct((), s.home_vid.dtype)
    try:
        out = jax.eval_shape(vprog, vid_spec, vex,
                             jax.tree.unflatten(msg_treedef, list(mspecs)))
    except Exception:
        return None
    out_leaves, out_def = jax.tree.flatten(out)
    if out_def != vdef or any(
            tuple(o.shape) != tuple(v.shape) or o.dtype != v.dtype
            for o, v in zip(out_leaves, vleaves)):
        return None
    if changed_fn is not None:
        try:
            ch = jax.eval_shape(changed_fn, vex, vex)
        except Exception:
            return None
        if getattr(ch, "shape", None) != () or ch.dtype != jnp.bool_:
            return None
    widths_m = [int(np.prod(m.shape, dtype=np.int64)) if m.shape else 1
                for m in msg_leaves]
    widths_v = [int(np.prod(v.shape, dtype=np.int64)) if v.shape else 1
                for v in vleaves]
    dm = sum(widths_m)
    if reduce != "sum" and dm > FUSED_MINMAX_MAX_WIDTH:
        return None
    return _ApplyPlan(dm=dm, dv=sum(widths_v), msg_specs=mspecs,
                      msg_treedef=msg_treedef, v_specs=tuple(vleaves),
                      v_treedef=vdef, defaults=tuple(defaults))


@functools.lru_cache(maxsize=256)
def _make_apply_fn(vprog, changed_fn, plan: _ApplyPlan):
    """Packed apply closure for the fused superstep kernel: unpack state and
    combined messages from their column-packed staging matrices, substitute
    per-leaf defaults where no message arrived, vmap the vprog, select on
    visibility, derive the changed bit.  Shared VERBATIM by the kernel
    (kernels/superstep.py) and the oracle (ref.fused_apply) — the only
    difference between the two paths is how the combine lands.

    Memoised on (vprog, changed_fn, plan) identity: the closure is a STATIC
    jit argument of the kernel, so repeated supersteps must hand back the
    same object or every step recompiles."""
    mspecs, mdef = plan.msg_specs, plan.msg_treedef
    vspecs, vdef = plan.v_specs, plan.v_treedef
    defaults = plan.defaults

    def unpack(mat, specs):
        out, off = [], 0
        for spec in specs:
            size = (int(np.prod(spec.shape, dtype=np.int64))
                    if spec.shape else 1)
            col = mat[:, off:off + size]
            off += size
            dt = (spec.dtype if jnp.issubdtype(spec.dtype, jnp.integer)
                  else jnp.float32)
            out.append(col.reshape((mat.shape[0],) + tuple(spec.shape))
                       .astype(dt))
        return out

    def apply_fn(vid, vmask, xv, acc, exists):
        n = xv.shape[0]
        vm = vmask > 0.0                                       # [n, 1]
        v_tree = jax.tree.unflatten(vdef, unpack(xv, vspecs))
        # messages: park a safe 0 where no message arrived (the accumulator
        # holds the f32 reduce identity there — finfo extremes that would
        # wrap an int cast), cast into the combine dtype, then substitute
        # the per-leaf default in ITS OWN dtype.
        mleaves, off = [], 0
        for spec, dflt in zip(mspecs, defaults):
            size = (int(np.prod(spec.shape, dtype=np.int64))
                    if spec.shape else 1)
            col = acc[:, off:off + size]
            off += size
            e = jnp.broadcast_to(exists, col.shape)
            dt = (spec.dtype if jnp.issubdtype(spec.dtype, jnp.integer)
                  else jnp.float32)
            col = jnp.where(e, col, 0.0).astype(dt)
            col = jnp.where(e, col, jnp.asarray(dflt, dt))
            mleaves.append(col.reshape((n,) + tuple(spec.shape)))
        m_tree = jax.tree.unflatten(mdef, mleaves)
        new = jax.vmap(vprog)(vid[:, 0], v_tree, m_tree)
        cols = [l.reshape(n, -1).astype(jnp.float32)
                for l in jax.tree.leaves(new)]
        new_mat = cols[0] if len(cols) == 1 else jnp.concatenate(cols, -1)
        new_mat = jnp.where(vm, new_mat, xv)                   # visibility
        if changed_fn is None:
            # exact in the packed staging: every admitted leaf embeds
            # injectively in f32 (native f32, or ints under the mantissa
            # bound), so packed inequality == native tree_changed.
            changed = jnp.any(new_mat != xv, axis=1, keepdims=True)
        else:
            new_tree = jax.tree.unflatten(vdef, unpack(new_mat, vspecs))
            ch = jax.vmap(changed_fn)(v_tree, new_tree)
            changed = ch.reshape(n, 1)
        changed = jnp.logical_and(changed, vm)
        return new_mat, changed.astype(jnp.float32)

    return apply_fn


def fused_apply_home(g, recv: Any, rflags: jnp.ndarray, to: str, reduce: str,
                     plan: _ApplyPlan, vprog: Callable,
                     changed_fn: Callable | None, kernel_mode: str):
    """Home half of the fused superstep (§2.3.2): pack the ROUTED aggregate
    rows (ship_aggregates_home(combine=False) / mr_triplets(
    return_routed=True)) and the home vertex state, then combine + apply +
    changed-derive in one kernel sweep per home block.

    Returns (new_vdata pytree [nl, V_blk, ...], changed [nl, V_blk] bool)."""
    s = g.s
    send_idx, _ = s.routes[to]
    nl, p, k = send_idx.shape
    vb = FUSED_VERTEX_BLOCK
    v_blk = s.v_blk
    n_vb = max(-(-v_blk // vb), 1)
    v_pad = n_vb * vb

    # routed payload rows -> [nl·P·K, Dm] f32 staging (floats widen exactly;
    # ints are exact under the plan's round-trip guard)
    pay = jnp.concatenate(
        [l.reshape(nl, p * k, -1).astype(jnp.float32)
         for l in jax.tree.leaves(recv)],
        axis=-1).reshape(nl * p * k, plan.dm)
    # route padding has send_idx == -1 at exactly the rflags-false positions,
    # but mask explicitly: dead rows must never address a home slot.
    flags = rflags & (send_idx >= 0)
    off = (jnp.arange(nl, dtype=jnp.int32) * v_pad)[:, None, None]
    slot = (jnp.where(send_idx >= 0, send_idx, 0) + off).reshape(-1)
    live = flags.reshape(-1)

    x = _pack_cols(g.vdata, (True,) * len(plan.v_specs), nl, v_blk)
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, v_pad - v_blk), (0, 0)))
    x = x.reshape(nl * v_pad, plan.dv)
    vid = jnp.pad(s.home_vid, ((0, 0), (0, v_pad - v_blk))).reshape(-1)
    vmask = jnp.pad(g.vmask, ((0, 0), (0, v_pad - v_blk))).reshape(-1)

    tiles = (None if kops.resolve_mode(kernel_mode) == "ref"
             else flatten_tiles(s.tiles["apply_" + to], e_blk=p * k,
                                n_vb=n_vb))
    apply_fn = _make_apply_fn(vprog, changed_fn, plan)
    # groups/group_span pin the oracle's f32 sum order to the kernel's
    # (§2.4): rows lay out [nl, P, K], one source partition per K-span.
    new_mat, changed = kops.superstep_apply(
        pay, slot, live, tiles, x, vid, vmask, apply_fn,
        nl * v_pad, plan.dm, plan.dv, reduce=reduce, groups=p, group_span=k,
        mode=kernel_mode, eb=FUSED_EDGE_BLOCK, vb=FUSED_VERTEX_BLOCK)
    new_mat = new_mat.reshape(nl, v_pad, plan.dv)[:, :v_blk]
    changed = changed.reshape(nl, v_pad)[:, :v_blk] > 0

    # split the packed state back per leaf, casting ints out of f32 staging
    out, col = [], 0
    for spec in plan.v_specs:
        size = int(np.prod(spec.shape, dtype=np.int64)) if spec.shape else 1
        leaf = new_mat[..., col:col + size].reshape(
            (nl, v_blk) + tuple(spec.shape))
        col += size
        out.append(leaf.astype(spec.dtype))
    return jax.tree.unflatten(plan.v_treedef, out), changed


def apply_plan_of(g, vprog: Callable, send_msg: Callable,
                  reduce: str = "sum", *, changed_fn: Callable | None = None,
                  default_msg: Any = None, kernel_mode: str = "auto",
                  payload_bound: int | None = None) -> str:
    """The static apply-half plan decision WITHOUT executing a superstep:
    "fused_apply" | "unfused" — the §2.3.2 analogue of `plan_of` (a
    trace-time constant; drivers report it, they cannot read it back out of
    a jitted step)."""
    if kernel_mode == "unfused":
        return "unfused"
    plan = _plan_apply(g, vprog, send_msg, reduce, changed_fn, default_msg,
                       payload_bound)
    return "fused_apply" if plan is not None else "unfused"


def sweep_grid(s, to: str = "dst") -> tuple[int, int]:
    """(chunks, grid steps) of one fused triplet sweep toward `to` over all
    partitions of structure `s`, from its tile tables' shapes: the kernel's
    grid is 1-D over the chunks of the flat space that `_fused_aggregate`
    builds, one step per chunk, and a step works when its chunk is live."""
    p, n_chunks = s.tiles[to]["chunk_out"].shape
    return p * n_chunks, p * n_chunks


def plan_of(g, map_fn: Callable, reduce: str = "sum", *,
            kernel_mode: str = "auto", force_need: str | None = None,
            payload_bound: int | None = None) -> str:
    """The static physical-plan decision for this mrTriplets WITHOUT
    executing it: "fused" | "unfused".

    The decision is a trace-time constant, so it cannot cross a jit/shard_map
    boundary as a value — drivers (pregel's metrics, benchmarks) call this to
    report which plan their jitted supersteps took."""
    if kernel_mode == "unfused":
        return "unfused"
    vex, eex = elem_spec(g.vdata), elem_spec(g.edata)
    deps = analysis.analyze_message_fn(map_fn, vex, eex, vex)
    need = _derive_need(deps, force_need)
    plan = _plan_fused(g, map_fn, deps, need, reduce, force_need,
                       vex, eex, payload_bound)
    return "fused" if plan is not None else "unfused"

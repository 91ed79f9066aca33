"""Distributed graph representation: vertex-cut partitioning + routing tables.

This is the build-time half of GraphX §4.2.  Graphs are immutable (§3.1), so
we "can afford to construct indexes" (§4): everything here runs once in numpy
when a graph is constructed, producing a `GraphStructure` — a pytree of
static-shape device arrays that the iterative device-side operators
(mrTriplets, Pregel) consume.

Layout (P = number of partitions):

  Edge slabs (vertex-cut: edges partitioned, vertices replicated to mirrors):
    src_slot   [P, E_blk] int32   index into the partition's mirror table
    dst_slot   [P, E_blk] int32   (edges CLUSTERED by dst_slot — CSR analog —
                                   so message aggregation is a segment-sum
                                   over sorted segment ids)
    src_perm   [P, E_blk] int32   permutation that re-sorts edges by src_slot
                                   (for aggregation toward the source side)
    edge_mask  [P, E_blk] bool    validity (padding + `subgraph` restriction)

  Mirror tables (the "replicated vertex view", §4.5.1):
    mirror_vid  [P, V_mir] int32  global vertex id of each mirror slot (-1 pad)

  Vertex home partitions (hash partitioned by id, SORTED by id within the
  partition — the paper's hash index, realised as a searchsorted/merge-join
  index on TPU):
    home_vid   [P, V_blk] int32   sorted global ids (-1 padding at the tail
                                   sorts high via uint reinterpretation; we
                                   pad with INT32_MAX and mask)
    home_mask  [P, V_blk] bool

  Routing tables (§4.2 "join sites").  Three variants are precomputed, one
  per *need set*, so automatic join elimination (§4.5.2) ships strictly
  fewer bytes: "src" routes only vertices appearing as a source in the
  target edge partition, "dst" only destinations, "both" the union:
    route_send_idx [P, P, K] int32  send_idx[q, p, k]: local row in home
                                    partition q of the k-th vertex shipped to
                                    edge partition p  (-1 = padding)
    route_recv_slot[P, P, K] int32  recv_slot[p, q, k]: mirror slot in edge
                                    partition p where that vertex lands

Shipping vertices = gather(route_send_idx) → all_to_all → scatter(route_recv_slot).
Returning partial aggregates runs the same tables backwards.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from .hashing import hash_mod, hash_mod32

INT_PAD = np.int32(2**31 - 1)  # sorts after every real id


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class PartitionStats:
    """Replication statistics — used to property-test the O(|V|·sqrt(P)) bound."""

    num_vertices: int
    num_edges: int
    num_partitions: int
    total_mirrors: int
    # hybrid cut (§4.2): the chosen source-degree threshold — edges whose
    # source degree is < threshold placed 1D by source.  None = non-hybrid.
    threshold: int | None = None
    # broadcast-set classification (build_structure(bcast_min_repl=...)):
    bcast_min_repl: int | None = None
    n_broadcast: int = 0
    # per-vertex replication: replication[i] partitions hold a mirror of
    # vertex_ids[i] (sorted unique ids).  compare=False: numpy members must
    # stay out of the generated __eq__ (array comparison raises).
    vertex_ids: np.ndarray | None = dataclasses.field(
        default=None, compare=False, repr=False)
    replication: np.ndarray | None = dataclasses.field(
        default=None, compare=False, repr=False)

    @property
    def replication_factor(self) -> float:
        return self.total_mirrors / max(self.num_vertices, 1)

    def replication_of(self, vids: np.ndarray) -> np.ndarray:
        """Per-vertex mirror counts for the given global ids."""
        idx = np.searchsorted(self.vertex_ids, np.asarray(vids))
        return self.replication[idx]


@dataclasses.dataclass(eq=False)
class GraphStructure:
    """Static-shape device-ready index arrays for one partitioned graph.

    All members are numpy here; `repro.core.graph.Graph` converts to jnp and
    treats this as an immutable, shareable structural index (§4.3: index
    reuse across property updates).

    eq=False: this object rides in Graph's pytree METADATA (it is static),
    so jit compares it when matching cache entries — identity equality is
    both correct (structures are immutable and shared, §4.3) and required
    (field-wise numpy comparison raises).
    """

    num_partitions: int
    num_vertices: int
    num_edges: int
    e_blk: int
    v_mir: int
    v_blk: int
    k_route: int

    src_slot: np.ndarray      # [P, E_blk] int32
    dst_slot: np.ndarray      # [P, E_blk] int32
    src_perm: np.ndarray      # [P, E_blk] int32 (indices re-sorting by src)
    edge_mask: np.ndarray     # [P, E_blk] bool
    mirror_vid: np.ndarray    # [P, V_mir] int32
    home_vid: np.ndarray      # [P, V_blk] int32 sorted, INT_PAD padding
    home_mask: np.ndarray     # [P, V_blk] bool
    # routes[need] for need in {"src", "dst", "both"}:
    #   (route_send_idx [P,P,K], route_recv_slot [P,P,K], K)
    routes: dict = None  # type: ignore[assignment]
    # tiles[side] for side in {"dst", "src"}: per-partition chunk tables for
    # the fused triplet kernel (kernels/triplet.build_triplet_tiles: the
    # chunk order, block ids and each chunk edge's static slot rows), built
    # once here so they ship to the device as part of StructArrays and shard
    # with the graph — the fused path's §4.3 "index reuse" at kernel level.
    tiles: dict = None  # type: ignore[assignment]
    stats: PartitionStats = None  # type: ignore[assignment]
    # placement of the i-th INPUT edge: partition + row within the slab
    edge_part: np.ndarray = None  # [E] int32  # type: ignore[assignment]
    edge_row: np.ndarray = None   # [E] int32  # type: ignore[assignment]
    # broadcast lane (§2.1.3), present when build_structure classified a
    # broadcast set (bcast_min_repl): vertices replicated on >= that many
    # partitions ship ONCE per source via an all-gather-style collective
    # instead of one payload per (source, dest) route.
    #   bsend    [P, B] int32  home rows of partition q's broadcast vertices
    #                          (-1 pad), id-sorted per partition
    #   bcast_vid[P, B] int32  their global ids (-1 pad)
    #   brecv[need] [P, P, B]  mirror slot where source q's j-th broadcast
    #                          vertex lands at partition pe (v_mir = drop:
    #                          not mirrored there / not in this need set)
    #   p2p_routes[need]       residual point-to-point routes with the
    #                          broadcast set removed (same layout as routes)
    bsend: np.ndarray = None      # type: ignore[assignment]
    bcast_vid: np.ndarray = None  # type: ignore[assignment]
    brecv: dict = None            # type: ignore[assignment]
    p2p_routes: dict = None       # type: ignore[assignment]
    b_width: int = 0
    # largest global vertex id (static): the fused planner's integer-staging
    # guard — id-valued payloads round-trip f32 exactly iff max_vid < 2^24.
    max_vid: int = 0

    @property
    def route_send_idx(self) -> np.ndarray:   # back-compat: union route
        return self.routes["both"][0]

    @property
    def route_recv_slot(self) -> np.ndarray:
        return self.routes["both"][1]

    # ---- host-side lookups used by build + tests ------------------------
    def home_of(self, vids: np.ndarray) -> np.ndarray:
        return hash_mod32(vids, self.num_partitions)

    def local_row(self, vids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(partition, row) of each vertex id in its home partition."""
        part = self.home_of(vids)
        rows = np.empty_like(part)
        for q in np.unique(part):
            sel = part == q
            rows[sel] = np.searchsorted(self.home_vid[q], vids[sel])
        return part, rows


def place_vertex_rows(s: "GraphStructure", vids: np.ndarray,
                      values: np.ndarray, fill=0) -> np.ndarray:
    """Host-side scatter of per-id vertex facts into `s`'s home layout:
    a [P, V_blk, ...] buffer with `values[i]` at vertex `vids[i]`'s home
    row and `fill` elsewhere.  The elastic-restore placement path (§6):
    a snapshot keys vmask/active by GLOBAL id, a structure keys them by
    (partition, row) — this is the re-keying."""
    vids = np.asarray(vids, np.int64)
    values = np.asarray(values)
    part, row = s.local_row(vids)
    buf = np.full((s.num_partitions, s.v_blk) + values.shape[1:], fill,
                  values.dtype)
    buf[part, row] = values
    return buf


def edge_partition_2d(src: np.ndarray, dst: np.ndarray, p: int) -> np.ndarray:
    """2D hash partitioner (§4.2).

    Lays partitions on a ceil(sqrt(P)) grid; edge (s, d) goes to cell
    (h(s) mod R, h(d) mod C).  Each vertex's edges then touch at most
    R + C - 1 = O(sqrt(P)) partitions, giving the paper's O(n·sqrt(P))
    replication upper bound for mrTriplets communication.
    """
    r = int(np.floor(np.sqrt(p)))
    while p % r != 0:
        r -= 1
    c = p // r  # r*c == p exactly; grid as square as divisibility allows
    hs = hash_mod(src, r, salt=0x5EED)
    hd = hash_mod(dst, c, salt=0xF00D)
    return hs * c + hd


def edge_partition_1d(src: np.ndarray, dst: np.ndarray, p: int) -> np.ndarray:
    """Edge-cut style hash of the canonical endpoint (baseline partitioner)."""
    del dst
    return hash_mod(src, p, salt=0x5EED)


def random_partition(src: np.ndarray, dst: np.ndarray, p: int) -> np.ndarray:
    """Random edge placement — the paper's "default placement" baseline."""
    return hash_mod(src * np.int64(1315423911) + dst, p, salt=0xABCD)


def _edge_source_degree(src: np.ndarray) -> np.ndarray:
    """Per-EDGE out-degree of the edge's source vertex."""
    if src.size == 0:
        return np.zeros(0, np.int64)
    _, inv, cnt = np.unique(src, return_inverse=True, return_counts=True)
    return cnt[inv]


def _mirror_total(src: np.ndarray, dst: np.ndarray, epart: np.ndarray,
                  p: int) -> int:
    """Total mirrors (distinct (vertex, partition) pairs) of a placement."""
    key = (np.concatenate([src, dst]).astype(np.int64) * p
           + np.tile(np.asarray(epart, np.int64), 2))
    return int(np.unique(key).size)


def choose_hybrid_threshold(src: np.ndarray, dst: np.ndarray,
                            p: int) -> int:
    """Pick the hybrid cut's degree threshold by a log-spaced sweep that
    minimises total mirrors.  Threshold 0 (no edge below it) IS the pure 2D
    cut and is always a candidate, so the chosen hybrid placement never
    replicates more than 2D; max_degree+1 (every edge 1D) anchors the other
    end.  The sweep is O(candidates · E log E) in numpy at build time —
    graphs are immutable, so it runs once (§4)."""
    deg = _edge_source_degree(src)
    max_deg = int(deg.max()) if deg.size else 1
    cands, t = [0], 1
    while t <= max_deg:
        cands.append(t)
        t *= 2
    cands.append(max_deg + 1)
    d1 = edge_partition_1d(src, dst, p)
    d2 = edge_partition_2d(src, dst, p)
    best_t, best_m = 0, None
    for cand in cands:
        m = _mirror_total(src, dst, np.where(deg < cand, d1, d2), p)
        if best_m is None or m < best_m:
            best_t, best_m = int(cand), m
    return best_t


def edge_partition_hybrid(src: np.ndarray, dst: np.ndarray, p: int,
                          threshold: int | None = None) -> np.ndarray:
    """Degree-aware hybrid vertex cut (PowerGraph/PowerLyra-style, §4.2).

    Edges whose SOURCE degree is below `threshold` place 1D by source — the
    long low-degree tail then replicates ≈1 (all of a tail vertex's out-
    edges land together) — while high-degree sources fall through to the 2D
    cut, keeping hub replication bounded by the O(sqrt(P)) grid.  The 1D
    hash reuses the 2D row salt, so a tail source's partition is stable
    under threshold changes.  None picks the threshold by sweep."""
    if threshold is None:
        threshold = choose_hybrid_threshold(src, dst, p)
    deg = _edge_source_degree(src)
    return np.where(deg < threshold,
                    edge_partition_1d(src, dst, p),
                    edge_partition_2d(src, dst, p))


PARTITIONERS = {
    "2d": edge_partition_2d,
    "1d": edge_partition_1d,
    "random": random_partition,
    "hybrid": edge_partition_hybrid,
}


def build_structure(
    src: np.ndarray,
    dst: np.ndarray,
    num_partitions: int,
    *,
    vertex_ids: np.ndarray | None = None,
    partitioner: str = "2d",
    pad_multiple: int = 8,
    hybrid_threshold: int | None = None,
    bcast_min_repl: int | None = None,
) -> GraphStructure:
    """Partition the edge list and build every structural index.

    `vertex_ids` may include isolated vertices (present in the vertex
    collection but with no edges); they get home rows but no mirrors.

    partitioner="hybrid" takes the degree-aware cut (threshold from
    `hybrid_threshold`, or swept to minimise replication).  `bcast_min_repl`
    classifies vertices replicated on >= that many partitions into the
    BROADCAST SET: their mirror routes move to all-gather tables
    (bsend/brecv) and the point-to-point routes shrink to the remainder
    (p2p_routes) — the transport's broadcast lane (§2.1.3).  The full
    `routes` stay as built: the aggregate RETURN direction and the fused
    apply tables keep using them, so values never depend on the lane split.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if src.shape != dst.shape or src.ndim != 1:
        raise ValueError("src/dst must be 1-D arrays of equal length")
    p = int(num_partitions)
    n_edges = int(src.shape[0])

    all_vids = np.unique(np.concatenate([src, dst]))
    if vertex_ids is not None:
        all_vids = np.unique(np.concatenate([all_vids, np.asarray(vertex_ids, np.int64)]))
    if all_vids.size and (all_vids.min() < 0 or all_vids.max() >= INT_PAD):
        raise ValueError("vertex ids must fit int32 and be non-negative "
                         "(ingest with dictionary encoding first)")
    n_vertices = int(all_vids.size)

    # ---- home partitions (hash by id, sorted within partition) ----------
    home = hash_mod32(all_vids, p)
    v_blk = _round_up(max(int(np.max(np.bincount(home, minlength=p))) if n_vertices else 1, 1),
                      pad_multiple)
    home_vid = np.full((p, v_blk), INT_PAD, dtype=np.int32)
    home_mask = np.zeros((p, v_blk), dtype=bool)
    for q in range(p):
        mine = np.sort(all_vids[home == q]).astype(np.int32)
        home_vid[q, : mine.size] = mine
        home_mask[q, : mine.size] = True

    # ---- edge partitions + mirror tables ---------------------------------
    threshold = None
    if partitioner == "hybrid":
        threshold = (hybrid_threshold if hybrid_threshold is not None
                     else choose_hybrid_threshold(src, dst, p))
        epart = edge_partition_hybrid(src, dst, p, threshold=threshold)
    else:
        epart = PARTITIONERS[partitioner](src, dst, p)
    counts = np.bincount(epart, minlength=p)
    e_blk = _round_up(max(int(counts.max()) if n_edges else 1, 1), pad_multiple)

    mirrors: list[np.ndarray] = []
    for q in range(p):
        sel = epart == q
        mirrors.append(np.unique(np.concatenate([src[sel], dst[sel]])).astype(np.int32))
    v_mir = _round_up(max(max((m.size for m in mirrors), default=1), 1), pad_multiple)

    src_slot = np.zeros((p, e_blk), dtype=np.int32)
    dst_slot = np.zeros((p, e_blk), dtype=np.int32)
    src_perm = np.tile(np.arange(e_blk, dtype=np.int32), (p, 1))
    edge_mask = np.zeros((p, e_blk), dtype=bool)
    mirror_vid = np.full((p, v_mir), -1, dtype=np.int32)
    edge_part = np.zeros(n_edges, dtype=np.int32)
    edge_row = np.zeros(n_edges, dtype=np.int32)

    for q in range(p):
        sel = np.flatnonzero(epart == q)
        m = mirrors[q]
        mirror_vid[q, : m.size] = m
        s_loc = np.searchsorted(m, src[sel]).astype(np.int32)
        d_loc = np.searchsorted(m, dst[sel]).astype(np.int32)
        # cluster by destination slot (stable, keeps src runs cache-friendly)
        order = np.argsort(d_loc, kind="stable")
        s_loc, d_loc = s_loc[order], d_loc[order]
        n = sel.size
        src_slot[q, :n] = s_loc
        dst_slot[q, :n] = d_loc
        edge_mask[q, :n] = True
        edge_part[sel[order]] = q
        edge_row[sel[order]] = np.arange(n, dtype=np.int32)
        # padding edges point at an always-masked slot pattern: slot 0 is fine
        # because edge_mask gates them everywhere.
        perm = np.argsort(np.where(edge_mask[q], src_slot[q], INT_PAD), kind="stable")
        src_perm[q] = perm.astype(np.int32)

    # ---- routing tables (per need set, for join elimination §4.5.2) -------
    # For edge partition pe, mirror v is "src-needed" if it appears as the
    # source of some edge there, "dst-needed" likewise; the union is the
    # classic replicated view.  We emit one table per need set; shipping
    # with the narrower table is the physical realisation of the 3-way →
    # 2-way join rewrite.
    need_flags: dict[str, list[np.ndarray]] = {"src": [], "dst": [], "both": []}
    for q in range(p):
        sel = epart == q
        m = mirrors[q]
        is_src = np.isin(m, src[sel])
        is_dst = np.isin(m, dst[sel])
        need_flags["src"].append(is_src)
        need_flags["dst"].append(is_dst)
        need_flags["both"].append(is_src | is_dst)

    def build_route(flags: list[np.ndarray]):
        send_lists: list[list[np.ndarray]] = [[None] * p for _ in range(p)]  # type: ignore
        recv_lists: list[list[np.ndarray]] = [[None] * p for _ in range(p)]  # type: ignore
        k_route = 1
        for pe in range(p):
            m = mirrors[pe][flags[pe]]
            mslot = np.arange(mirrors[pe].size, dtype=np.int32)[flags[pe]]
            vhome = hash_mod32(m, p)
            for q in range(p):
                sel = vhome == q
                rows = np.searchsorted(home_vid[q], m[sel]).astype(np.int32)
                send_lists[q][pe] = rows
                recv_lists[pe][q] = mslot[sel]
                k_route = max(k_route, rows.size)
        k_route = _round_up(k_route, pad_multiple)
        send = np.full((p, p, k_route), -1, dtype=np.int32)
        recv = np.full((p, p, k_route), v_mir, dtype=np.int32)  # OOB pad
        for q in range(p):
            for pe in range(p):
                rows = send_lists[q][pe]
                slots = recv_lists[pe][q]
                send[q, pe, : rows.size] = rows
                recv[pe, q, : slots.size] = slots
        return send, recv, k_route

    routes = {need: build_route(flags) for need, flags in need_flags.items()}
    k_route = routes["both"][2]

    # ---- fused-kernel tile tables (one per aggregation side, §2.3) --------
    # Built eagerly with the rest of the structural index: graphs are
    # immutable, so the O(E log E) grouping runs once and the tables ride to
    # the device as per-partition arrays that shard with the graph.  Eager
    # and unconditional on purpose — kernel_mode is a per-CALL choice and
    # the tables must already be pytree children when the graph enters
    # shard_map, so there is no later point at which a lazy host build
    # could still reach every device.
    from ..kernels.triplet import (DEFAULT_VERTEX_BLOCK, build_chunk_tables,
                                   build_triplet_tiles)
    tiles = {
        "dst": build_triplet_tiles(dst_slot, src_slot, edge_mask, v_mir),
        "src": build_triplet_tiles(src_slot, dst_slot, edge_mask, v_mir),
    }
    # Route-chunk tables for the fused superstep APPLY kernel (§2.3.2): the
    # aggregate-return route's [P, P, K] send entries, grouped by destination
    # HOME-vertex block through the same chunk machinery — route entry (pe, j)
    # of partition q plays the "edge", its home row the aggregation slot.
    # Keyed by the aggregation side whose route carries the aggregates back.
    # The gather-side slot is keyed on the SOURCE partition pe (one fake
    # vertex block per pe): the kernel never gathers through it, but the
    # (out_block, in_block) chunk grouping then guarantees no chunk mixes
    # rows of two source partitions — one source partition's rows target
    # DISTINCT home rows, so every chunk's scatter-add is collision-free and
    # the ascending-chunk accumulation is a FIXED order, which is what lets
    # f32 sums fuse by default (§2.4, PR-7 follow-up (b)).
    for side in ("src", "dst"):
        k_side = routes[side][0].shape[2]
        send = routes[side][0].reshape(p, -1)
        pe_block = (np.arange(send.shape[1], dtype=np.int32) // k_side
                    * DEFAULT_VERTEX_BLOCK)
        in_slot = np.broadcast_to(pe_block, send.shape)
        tiles["apply_" + side] = build_chunk_tables(
            np.maximum(send, 0), in_slot, send >= 0,
            max(v_blk, p * DEFAULT_VERTEX_BLOCK))

    # ---- per-vertex replication + broadcast-set classification (§2.1.3) ---
    repl = np.zeros(max(n_vertices, 1), np.int32)
    for q in range(p):
        if mirrors[q].size:
            repl[np.searchsorted(all_vids, mirrors[q])] += 1

    bsend = bcast_vid = brecv = p2p_routes = None
    b_width = 0
    n_broadcast = 0
    if bcast_min_repl is not None and n_vertices:
        bvids = all_vids[repl[:n_vertices] >= int(bcast_min_repl)]
        n_broadcast = int(bvids.size)
        if n_broadcast:
            bhome = hash_mod32(bvids, p)
            b_width = _round_up(
                max(int(np.bincount(bhome, minlength=p).max()), 1),
                pad_multiple)
            bsend = np.full((p, b_width), -1, np.int32)
            bcast_vid = np.full((p, b_width), -1, np.int32)
            bq_of = {}
            for q in range(p):
                bq = bvids[bhome == q]            # id-sorted (bvids sorted)
                bq_of[q] = bq
                bsend[q, : bq.size] = np.searchsorted(
                    home_vid[q], bq).astype(np.int32)
                bcast_vid[q, : bq.size] = bq.astype(np.int32)
            brecv = {}
            for need, flags in need_flags.items():
                tbl = np.full((p, p, b_width), v_mir, np.int32)
                for pe in range(p):
                    m = mirrors[pe]
                    for q in range(p):
                        bq = bq_of[q]
                        if not (m.size and bq.size):
                            continue
                        pos = np.searchsorted(m, bq)
                        inb = pos < m.size
                        pos2 = np.where(inb, pos, 0)
                        ok = inb & (m[pos2] == bq) & flags[pe][pos2]
                        row = tbl[pe, q, : bq.size]
                        row[ok] = pos2[ok].astype(np.int32)
                brecv[need] = tbl
            # residual point-to-point routes: broadcast vertices excluded —
            # the byte win is that they stop appearing once per (src, dest)
            # route entry, so K shrinks with the hubs.
            p2p_routes = {
                need: build_route(
                    [f & ~np.isin(mirrors[pe], bvids)
                     for pe, f in enumerate(flags)])
                for need, flags in need_flags.items()}

    stats = PartitionStats(
        num_vertices=n_vertices,
        num_edges=n_edges,
        num_partitions=p,
        total_mirrors=int(sum(m.size for m in mirrors)),
        threshold=threshold,
        bcast_min_repl=bcast_min_repl,
        n_broadcast=n_broadcast,
        vertex_ids=all_vids,
        replication=repl[:n_vertices],
    )
    return GraphStructure(
        num_partitions=p,
        num_vertices=n_vertices,
        num_edges=n_edges,
        e_blk=e_blk,
        v_mir=v_mir,
        v_blk=v_blk,
        k_route=k_route,
        src_slot=src_slot,
        dst_slot=dst_slot,
        src_perm=src_perm,
        edge_mask=edge_mask,
        mirror_vid=mirror_vid,
        home_vid=home_vid,
        home_mask=home_mask,
        routes=routes,
        tiles=tiles,
        stats=stats,
        edge_part=edge_part,
        edge_row=edge_row,
        bsend=bsend,
        bcast_vid=bcast_vid,
        brecv=brecv,
        p2p_routes=p2p_routes,
        b_width=b_width,
        max_vid=int(all_vids.max()) if n_vertices else 0,
    )


def structure_spec(n_vertices: int, n_edges: int, p: int, *, pad_multiple: int = 128,
                   mirror_factor: float = 2.0) -> dict[str, Any]:
    """Shape-only structure descriptor for dry-runs (no real graph needed).

    Sizes follow the 2D-cut replication model: mirrors per partition
    ≈ min(V, (E/P) + 1) bounded by the sqrt(P) replication factor.
    """
    import math

    e_blk = _round_up(max(math.ceil(n_edges / p), 1), pad_multiple)
    v_blk = _round_up(max(math.ceil(n_vertices / p), 1), pad_multiple)
    repl = min(2 * math.sqrt(p) - 1, p)
    v_mir = _round_up(
        max(min(int(mirror_factor * n_vertices * repl / p), n_vertices, 2 * e_blk), 1),
        pad_multiple)
    k_route = _round_up(max(math.ceil(v_mir / p) * 2, 1), pad_multiple)
    return dict(num_partitions=p, e_blk=e_blk, v_blk=v_blk, v_mir=v_mir, k_route=k_route,
                num_vertices=n_vertices, num_edges=n_edges)

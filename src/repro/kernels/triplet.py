"""Pallas TPU kernel: the general fused mrTriplets sweep (DESIGN.md §2.3).

mrTriplets' hot loop is a three-way join (edges ⋈ vertices(src) ⋈
vertices(dst)) followed by a per-vertex reduction.  The unfused engine path
materialises the [E, D] message array in HBM between the gather and the
reduce; this kernel performs mirror-row gather (src and/or dst), the per-edge
message computation, and the block-local segment reduction in ONE kernel, so
the edge sweep never leaves VMEM:

    sv  = onehot_src @ x[src_tile]            # gather  = MXU matmul
    dv  = onehot_dst @ x[dst_tile]
    msg = tile_fn(sv, ev, dv)                 # the (vmapped) map UDF, traced
    out += onehot_outᵀ @ (msg · live)         # reduce 'sum' = MXU matmul
    out  = min/max(out, boundaryᵀ @ scan(msg))  # 'min'/'max' = segmented scan
                                                #   + one MXU matmul (§2.3.1)

Edges are re-sorted at build time into fixed-size chunks grouped by
(out_block, in_block) — the §4.2 clustered index — so each chunk touches one
aggregation-side tile and one gather-side tile; per-chunk scalars arrive via
scalar prefetch and *indirect* both vertex BlockSpecs (the Pallas analog of
GraphX's routing-table join-site lookup).  The same mirror matrix is passed
twice with different index maps, once per endpoint role.  The chunk-ordered
edge slots are part of those build-time tables (`rows`, both of an edge's
slots in one int32); a call gathers only what changes between calls, the
live bits and the edge payload, through the chunk order in one gather.

§4.6-style index scan: chunks with no live edge are skipped via `pl.when`
on a per-chunk any-live flag.  `live` is per-EDGE, so the skipping is a pure
optimisation — results are identical to the unfused path's edge-granular
skipStale masking, while whole stale tiles cost nothing.

The scalar SpMV kernel (kernels/spmv.py) is the degenerate instance of this
kernel: linear message, sum reduce, src-only gather.
"""
from __future__ import annotations

import functools
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Reduction identities — finite (finfo extremes, not ±inf) so they match the
# engine's _REDUCE_IDENTITY convention bit-for-bit on empty segments.
REDUCE_IDENTITY = {
    "sum": 0.0,
    "min": float(np.finfo(np.float32).max),
    "max": float(np.finfo(np.float32).min),
}


# Default tile geometry of the fused triplet kernel (DESIGN.md §2.3); the
# engine and the build-time table construction must agree on these, so they
# live next to the kernel.
DEFAULT_EDGE_BLOCK = 512
DEFAULT_VERTEX_BLOCK = 512

# Rows covered by one narrow-resident scale exponent (DESIGN.md §2.4).  Must
# match the wire codec's scale block: the engine only plans encoded staging
# when `codec.block == SCALE_GROUP`, so one [vb//SCALE_GROUP, D] scale tile
# dequantizes one [vb, D] payload tile with a static-shape broadcast.
SCALE_GROUP = 32


# A static edge row holds the aggregation-side slot in its high half-word and
# the gather-side slot in its low one, both relative to their chunk's block.
ROW_SHIFT = 16


# ----------------------------------------------------------------------------
# Build-time tiling metadata (numpy; structure is immutable so this runs once
# per (graph, aggregation side) at `build_structure` time).
# ----------------------------------------------------------------------------
def build_chunk_tables(
    out_slot: np.ndarray,     # [P, E_blk] (or [E]) aggregation-side slots
    in_slot: np.ndarray,      # [P, E_blk] (or [E]) gather-side slots
    edge_mask: np.ndarray,    # [P, E_blk] (or [E]) structural validity
    num_slots: int,           # LOCAL slot space size (v_mir), both sides
    *,
    eb: int = DEFAULT_EDGE_BLOCK,
    vb: int = DEFAULT_VERTEX_BLOCK,
) -> dict[str, np.ndarray]:
    """Per-partition tile tables: group each partition's structurally-live
    edges into eb-sized chunks sorted by (out_block, in_block), padded to a
    UNIFORM chunk count across partitions so the tables stack into regular
    [P, n_chunks, ...] arrays.

    Everything is partition-LOCAL — edge indices in [0, E_blk), block ids
    over the local slot space — so the tables are legal pytree children that
    shard with the graph: inside `shard_map` each device holds its own
    [1, n_chunks, ...] slice and `flatten_tiles` maps it onto the kernel's
    flat space with nl == 1.  1-D inputs are treated as a single partition.

    Returns numpy arrays:
      perm       [P, n_chunks, eb]  per-chunk edge gather lists
                                    (padding -> E_blk, locally OOB)
      chunk_out  [P, n_chunks]      LOCAL aggregation-side block ids
      chunk_in   [P, n_chunks]      LOCAL gather-side block ids
    """
    out_slot = np.atleast_2d(np.asarray(out_slot))
    in_slot = np.atleast_2d(np.asarray(in_slot))
    edge_mask = np.atleast_2d(np.asarray(edge_mask))
    p, e_blk = out_slot.shape
    if edge_mask.any():
        hi = max(int(out_slot[edge_mask].max()), int(in_slot[edge_mask].max()))
        if hi >= num_slots:
            raise ValueError(
                f"slot {hi} outside the declared slot space [0, {num_slots})")

    per_perm: list[list[np.ndarray]] = []
    per_out: list[list[int]] = []
    per_in: list[list[int]] = []
    for q in range(p):
        live = np.flatnonzero(edge_mask[q])
        ob = out_slot[q][live] // vb
        ib = in_slot[q][live] // vb
        # out-block major, in-block minor; WITHIN a chunk the edges sort by
        # aggregation slot — the invariant the segmented-scan min/max path
        # relies on (equal-slot runs are contiguous, padding at the tail).
        order = np.lexsort((out_slot[q][live], ib, ob))
        live = live[order]
        ob, ib = ob[order], ib[order]

        # split runs of identical (ob, ib) into eb-sized chunks
        perm_chunks: list[np.ndarray] = []
        couts: list[int] = []
        cins: list[int] = []
        if live.size:
            boundaries = np.flatnonzero(
                (np.diff(ob) != 0) | (np.diff(ib) != 0)) + 1
            for seg in np.split(np.arange(live.size), boundaries):
                for off in range(0, seg.size, eb):
                    chunk = live[seg[off:off + eb]]
                    pad = np.full(eb - chunk.size, e_blk, dtype=np.int64)
                    perm_chunks.append(np.concatenate([chunk, pad]))
                    couts.append(int(ob[seg[0]]))
                    cins.append(int(ib[seg[0]]))
        per_perm.append(perm_chunks)
        per_out.append(couts)
        per_in.append(cins)

    # pad every partition to the same chunk count; padding chunks are fully
    # OOB so their any-live flag is false and the kernel skips them.
    n_chunks = max(1, max(len(c) for c in per_out))
    perm = np.full((p, n_chunks, eb), e_blk, dtype=np.int32)
    chunk_out = np.zeros((p, n_chunks), dtype=np.int32)
    chunk_in = np.zeros((p, n_chunks), dtype=np.int32)
    for q in range(p):
        for c, (pc, co, ci) in enumerate(zip(per_perm[q], per_out[q],
                                             per_in[q])):
            perm[q, c] = pc
            chunk_out[q, c] = co
            chunk_in[q, c] = ci
    return dict(perm=perm, chunk_out=chunk_out, chunk_in=chunk_in)


def build_triplet_tiles(
    out_slot: np.ndarray,     # [P, E_blk] (or [E]) aggregation-side slots
    in_slot: np.ndarray,      # [P, E_blk] (or [E]) gather-side slots
    edge_mask: np.ndarray,    # [P, E_blk] (or [E]) structural validity
    num_slots: int,           # LOCAL slot space size (v_mir), both sides
    *,
    eb: int = DEFAULT_EDGE_BLOCK,
    vb: int = DEFAULT_VERTEX_BLOCK,
) -> dict[str, np.ndarray]:
    """The triplet sweep's tables: `build_chunk_tables` and the static half
    of the sweep's chunk-ordered streams, fixed for the structure, so that a
    call reads them instead of gathering the slots through `perm`:

      rows       [P, n_chunks, eb]  int32, each chunk edge's aggregation-
                                    side slot less its chunk's `chunk_out`
                                    block base, << ROW_SHIFT, | its
                                    gather-side slot less the `chunk_in`
                                    base; vb in both halves at padding
    """
    if not 0 < vb < 1 << (ROW_SHIFT - 1):
        raise ValueError(f"vb={vb} does not fit a half of a static edge row")
    t = build_chunk_tables(out_slot, in_slot, edge_mask, num_slots,
                           eb=eb, vb=vb)
    perm = t["perm"]
    p, e_blk = np.atleast_2d(np.asarray(out_slot)).shape
    flat = perm.reshape(p, -1)
    pad = perm >= e_blk
    rel = []
    for slot, block in ((out_slot, t["chunk_out"]), (in_slot, t["chunk_in"])):
        slot = np.atleast_2d(np.asarray(slot)).astype(np.int32)
        slot = np.concatenate([slot, np.zeros((p, 1), np.int32)], axis=1)
        r = (np.take_along_axis(slot, flat, axis=1).reshape(perm.shape)
             - block[:, :, None] * vb)
        rel.append(np.where(pad, vb, r))
    t["rows"] = (rel[0] << ROW_SHIFT | rel[1]).astype(np.int32)
    return t


def chunk_live_flags(tiles, live: jnp.ndarray, *, e_blk: int) -> jnp.ndarray:
    """Per-chunk any-live flags [P, n_chunks] for a per-edge live mask
    [P, E_blk] — exactly the `act` bits `fused_triplet` derives to drive
    `pl.when` whole-chunk skipping (§4.6).

    This is the measurement hook for predicate pushdown (core/planner.py):
    a subgraph restriction lowered into the mrTriplets live bits skips
    every chunk whose edges are all dead, and `1 - mean(flags)` is the
    fraction of the clustered edge index the sweep never touches (the
    fig6 'index scan' quantity at tile granularity).  Padding chunks
    count as skipped, matching the kernel."""
    perm = jnp.asarray(tiles["perm"])
    p, n_chunks, eb = perm.shape
    lp = jnp.concatenate([live, jnp.zeros((live.shape[0], 1), bool)], axis=1)
    cl = jax.vmap(lambda l, i: jnp.take(l, i, mode="clip"))(
        lp, jnp.minimum(perm, e_blk).reshape(p, -1)).reshape(p, n_chunks, eb)
    cl = cl & (perm < e_blk)
    return cl.any(axis=2)


def flatten_tiles(tiles, *, e_blk: int, n_vb: int) -> dict:
    """Map per-partition [P, n_chunks, ...] tile tables onto the kernel's
    flat stacked space: edge i of partition q -> q*e_blk + i, local block b
    of partition q -> q*n_vb + b (the caller pads each partition's slot
    space to n_vb*vb slots).  The block-relative `rows`, where the tables
    have them, need no offset: a flat slot minus its flat block base is the
    local slot minus the local base, since each partition spans exactly
    n_vb*vb slots; each chunk's row becomes a [1, eb] block.  Pure jnp on
    device arrays — traced, so it runs on each device's OWN [1, ...] slice
    inside `shard_map`."""
    perm = jnp.asarray(tiles["perm"])
    p, n_chunks, eb = perm.shape
    off_e = (jnp.arange(p, dtype=jnp.int32) * e_blk).reshape(p, 1, 1)
    flat_perm = jnp.where(perm >= e_blk, p * e_blk, perm + off_e)
    off_b = (jnp.arange(p, dtype=jnp.int32) * n_vb).reshape(p, 1)
    flat = dict(
        perm=flat_perm.reshape(p * n_chunks * eb),
        chunk_out=(jnp.asarray(tiles["chunk_out"]) + off_b).reshape(-1),
        chunk_in=(jnp.asarray(tiles["chunk_in"]) + off_b).reshape(-1))
    if "rows" in tiles:
        flat["rows"] = jnp.asarray(tiles["rows"]).reshape(p * n_chunks, 1, eb)
    return flat


def grid_chunk_out(chunk_out: jnp.ndarray, pad: jnp.ndarray) -> jnp.ndarray:
    """The output block of each step of `fused_triplet`'s 1-D grid [n_chunks]:
    `chunk_out` on every real chunk, and on a padding chunk (`pad`: every
    edge slot out of bounds) the block of the chunk before it.

    The TPU writes an output block back when the block index changes and
    does not read it in again, so along the grid the index must never go
    back to a block it left.  Real chunks are already in order (out-block
    major within a partition, partitions offset by `flatten_tiles`); only
    each partition's padding tail, whose `chunk_out` is 0, breaks it, and a
    running maximum with the padding at 0 restores it.  Padding chunks have
    no live edge, so the block they carry gains nothing."""
    return jax.lax.cummax(jnp.where(pad, 0, chunk_out).astype(jnp.int32),
                          axis=0)


# ----------------------------------------------------------------------------
# Kernel
# ----------------------------------------------------------------------------
# Every one-hot matmul runs at f32 contract precision: a gather or scatter
# through the MXU must land each value exactly (CC stages int32 labels
# through these; the chip's default bf16 pass would round them above 256).
HIGHEST = jax.lax.Precision.HIGHEST


def mxu_dot(a, b):
    """[M, K] @ [K, N] at f32 contract precision, f32 accumulate."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def chunk_rows(slots, live) -> jnp.ndarray:
    """Per-chunk edge scalars [n_chunks, len(slots)+1, eb] int32: each slot
    stream, then live, laid along lanes.  One block spans the array's minor
    two dimensions (legal under the TPU's (8, 128) tiling for any chunk size)
    and stays lane-dense in HBM; the kernels transpose it to per-edge columns
    in VMEM."""
    return jnp.stack(list(slots) + [live.astype(jnp.int32)], axis=1)


def segmented_reduce_mxu(vals, slot_col, slot_row, reduce: str, ident,
                         oh_t):
    """Block-local segment min/max via the segmented-scan trick (MXU path).

    vals     [Eb, Dm] f32, dead rows ALREADY substituted with `ident`
    slot_col [Eb, 1]  int32 output slots; equal-slot rows must be CONTIGUOUS
                      (build_triplet_tiles sorts each chunk by aggregation
                      slot, padding rows at the tail)
    slot_row [1, Eb]  the same slots laid along lanes
    oh_t     [Vb, Eb] f32 transposed one-hot of slot against the block's
                      rows (0 columns for OOB/padding slots)

    A Hillis–Steele segmented inclusive prefix scan (log2(Eb) static steps of
    sublane rotate + slot-guarded select, pure VPU elementwise on the
    [Eb, Dm] tile) leaves every segment's FULL reduction at its last row; the
    boundary one-hot then has exactly one nonzero per output row, so a single
    [Vb, Eb] @ [Eb, Dm] matmul lands the per-slot results on the MXU — exact,
    because each output element sums exactly one scanned term.
    """
    sel = jnp.minimum if reduce == "min" else jnp.maximum
    eb = vals.shape[0]
    row_id = jax.lax.broadcasted_iota(jnp.int32, (eb, 1), 0)
    acc = vals
    shift = 1
    while shift < eb:                                 # log2(Eb) static steps
        prev = pltpu.roll(acc, shift, 0)
        pseg = pltpu.roll(slot_col, shift, 0)
        same = jnp.logical_and(pseg == slot_col, row_id >= shift)
        acc = jnp.where(same, sel(acc, prev), acc)
        shift *= 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, eb), 1)
    nxt = pltpu.roll(slot_row, eb - 1, 1)             # slot of the next edge
    last = jnp.logical_or(slot_row != nxt, lane == eb - 1)   # segment ends
    oh_last = oh_t * last.astype(jnp.float32)         # ≤1 nonzero per row
    red = mxu_dot(oh_last, acc)                       # [Vb, Dm]
    present = jnp.sum(oh_last, axis=1, keepdims=True) > 0.0
    return jnp.where(present, red, ident)


def _gather(x_ref, sc_ref, slot, cols):
    """Gather vertex rows for each edge: one-hot [Eb, Vb] @ tile [Vb, D].

    With a narrow-RESIDENT tile (§2.4) the payload arrives encoded and
    `sc_ref` holds one E8M0 exponent row per SCALE_GROUP payload rows: the
    exponent is gathered at group granularity and applied after the payload
    gather.  Each one-hot row picks exactly one term, so this equals
    dequantizing the tile first (`wire.decode_resident`) bit for bit."""
    oh = (slot == cols).astype(jnp.float32)
    v = mxu_dot(oh, x_ref[...].astype(jnp.float32))
    if sc_ref is not None:
        sb = sc_ref.shape[0]
        grp = jax.lax.broadcasted_iota(jnp.int32, (slot.shape[0], sb), 1)
        oh_g = (slot // SCALE_GROUP == grp).astype(jnp.float32)
        v = v * jnp.exp2(mxu_dot(oh_g, sc_ref[...].astype(jnp.float32)))
    return v


def _make_kernel(tile_fn: Callable, reduce: str, to: str, use_src: bool,
                 use_dst: bool, have_scale: bool):
    ident = REDUCE_IDENTITY[reduce]

    def kernel(cout_ref, csrc_ref, cdst_ref, act_ref, slot_ref, edge_ref,
               *refs):
        refs = list(refs)
        xs_ref = refs.pop(0) if use_src else None
        xd_ref = refs.pop(0) if use_dst else None
        ss_ref = refs.pop(0) if have_scale and use_src else None
        ds_ref = refs.pop(0) if have_scale and use_dst else None
        out_ref, cnt_ref = refs
        c = pl.program_id(0)      # chunk; its output block is cout_ref[c]

        # the first chunk of each output block starts the resident block:
        # block ids never decrease along the grid, so a block's chunks are
        # consecutive steps and it is written back once, after its last
        @pl.when(jnp.logical_or(
            c == 0, cout_ref[c] != cout_ref[jnp.maximum(c - 1, 0)]))
        def _init():
            out_ref[...] = jnp.full_like(out_ref, ident)
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

        # chunk skip (§4.6): a chunk whose edges are all dead — masked,
        # skipStale, or padding — never touches the tile pair.
        @pl.when(act_ref[c] != 0)
        def _accumulate():
            vb = out_ref.shape[0]
            row = slot_ref[...]         # [1, Eb] static edge rows
            col = row.T                                          # [Eb, 1]
            out_row = row >> ROW_SHIFT                           # [1, Eb]
            out_col = col >> ROW_SHIFT           # aggregation-side slot
            in_col = col & ((1 << ROW_SHIFT) - 1)    # gather-side slot
            dyn = edge_ref[...]         # [1+De, Eb] live, then the payload
            dcol = dyn.T                                         # [Eb, 1+De]
            eb = col.shape[0]
            live = dcol[:, 0:1] > 0                              # [Eb, 1]
            src_col = out_col if to == "src" else in_col
            dst_col = out_col if to == "dst" else in_col
            cols = jax.lax.broadcasted_iota(jnp.int32, (eb, vb), 1)
            zero = jnp.zeros((eb, 1), jnp.float32)
            sv = (_gather(xs_ref, ss_ref, src_col, cols)
                  if use_src else zero)                          # [Eb, Dx]
            dv = (_gather(xd_ref, ds_ref, dst_col, cols)
                  if use_dst else zero)
            ev = dcol[:, 1:]                                     # [Eb, De]
            msgs = tile_fn(sv, ev, dv)                           # [Eb, Dm]

            rows = jax.lax.broadcasted_iota(jnp.int32, (vb, eb), 0)
            oh_t = (out_row == rows).astype(jnp.float32)         # [Vb, Eb]
            oh_live = oh_t * (dyn[0:1, :] > 0).astype(jnp.float32)
            cnt_ref[...] += jnp.sum(oh_live, axis=1, keepdims=True)
            if reduce == "sum":
                # dead rows (padding / masked / stale) gathered ZERO endpoint
                # values, so the UDF may have produced NaN/inf there (0/0 in
                # PageRank's pr/deg).  Mask by SUBSTITUTION before the matmul
                # — multiplying by the 0/1 one-hot would turn 0·NaN into NaN
                # and poison the whole output block.
                out_ref[...] += mxu_dot(oh_live, jnp.where(live, msgs, 0.0))
            else:
                sel = jnp.minimum if reduce == "min" else jnp.maximum
                # dead rows keep their REAL slots but carry the identity, so
                # they never perturb a segment's min/max; padding rows (slot
                # == vb) match no row of the one-hot.
                vals = jnp.where(live, msgs, ident)
                red = segmented_reduce_mxu(vals, out_col, out_row,
                                           reduce, ident, oh_t)
                out_ref[...] = sel(out_ref[...], red)            # [Vb, Dm]

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("tile_fn", "num_segments", "dm", "to", "reduce",
                     "use_src", "use_dst", "eb", "vb", "interpret"))
def fused_triplet(
    x: jnp.ndarray,           # [S, Dx] packed mirror matrix (any float dtype,
                              # or the encoded payload dtype when xscale set)
    ev: jnp.ndarray,          # [E, De] packed edge payload (De may be 0)
    live: jnp.ndarray,        # [E] bool — edge contributes a message
    tiles: dict,              # FLAT tables over the stacked slot/edge space:
                              # build_triplet_tiles(...) -> flatten_tiles(...),
                              # built toward `to`
    tile_fn: Callable,        # ([Eb,Dx],[Eb,De],[Eb,Dx]) -> [Eb,Dm] f32
    num_segments: int,        # = S
    dm: int,                  # message width
    *,
    xscale: jnp.ndarray | None = None,  # [S//SCALE_GROUP, Dx] E8M0 exponents
                              # (narrow-resident staging, §2.4) — row b scales
                              # payload rows [b*32, (b+1)*32)
    to: str = "dst",
    reduce: str = "sum",
    use_src: bool = True,
    use_dst: bool = True,
    eb: int = 512,
    vb: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """out[v] = reduce_{live e: out(e)=v} tile_fn(x[src(e)], ev[e], x[dst(e)])

    Each edge's endpoints are the slots its tile tables were built from
    (`rows`, with `to` naming the aggregation side): the slot streams are
    static, and only the live bits and the payload are gathered per call.

    use_src / use_dst: whether tile_fn reads that endpoint's values.  An
    unused side streams nothing: tile_fn receives a width-1 zero column in
    its place (PageRank reads only src) and must not touch it (the engine's
    side-aware unpack guarantees this).  A payload of width 0 reaches
    tile_fn as a width-1 zero column.

    The grid is 1-D over the flat chunks, one step each.  A step's output
    block is its chunk's `chunk_out`, resident in VMEM while consecutive
    chunks of that block accumulate into it (`grid_chunk_out`); a block no
    chunk maps to gets the identity and a count of 0 after the call.

    Returns (out [S, dm] f32 — reduce identity at empty slots,
             cnt [S] f32 — live message count per slot,
             chunks_live int32 — chunks with a live edge, the grid steps
             that do work out of n_chunks).
    """
    e = live.shape[0]
    perm = jnp.asarray(tiles["perm"])
    chunk_out = jnp.asarray(tiles["chunk_out"])
    chunk_in = jnp.asarray(tiles["chunk_in"])
    slot_rows = jnp.asarray(tiles["rows"])            # [n_chunks, 1, eb]
    n_chunks = chunk_out.shape[0]
    n_vb = max(-(-num_segments // vb), 1)
    v_pad = n_vb * vb

    # Tiles stream in the CALLER's staging dtype (f32, or bf16 when the
    # engine packed a narrow-wire mirror, §2.1) — the kernel body upcasts
    # each tile to f32 in VMEM, so narrow mirrors halve the vertex-tile
    # HBM/DMA traffic while the accumulator math is unchanged.
    xp = jnp.pad(x.reshape(x.shape[0], -1),
                 ((0, v_pad - x.shape[0]), (0, max(1 - x.shape[1], 0))))
    dx = xp.shape[1]

    # narrow-resident scale plane: one exponent row per SCALE_GROUP payload
    # rows, laid out [n_vb, vb//32, Dx] so one block spans the minor two
    # dimensions and follows the payload tile through the same index map.
    # Zero-exponent padding dequantizes as identity.
    have_scale = xscale is not None
    if have_scale:
        if vb % SCALE_GROUP:
            raise ValueError(
                f"xscale staging requires vb % {SCALE_GROUP} == 0, "
                f"got vb={vb}")
        sb = vb // SCALE_GROUP
        scp = jnp.pad(xscale.reshape(xscale.shape[0], -1),
                      ((0, n_vb * sb - xscale.shape[0]),
                       (0, max(1 - xscale.shape[1], 0))))
        scp = scp.reshape(n_vb, sb, scp.shape[1])
    # the chunk-ordered dynamic stream the grid reads: ONE gather through
    # `perm` of the live bit and the payload columns, staged in f32 (the
    # kernel reads the payload as f32, so the staging is exact), with a zero
    # payload column where there is none and a zero row at index e for the
    # padding slots.  Named so that a device trace can tell it from the
    # kernel.
    with jax.named_scope("graphx.triplet_streams"):
        packed = jnp.concatenate([live.astype(jnp.float32)[:, None],
                                  ev.reshape(e, -1).astype(jnp.float32)],
                                 axis=1)
        packed = jnp.pad(packed, ((0, 1), (0, max(2 - packed.shape[1], 0))))
        width = packed.shape[1]
        cedge = jnp.swapaxes(packed[perm].reshape(n_chunks, eb, width), 1, 2)
        act = (cedge[:, 0, :] > 0).any(axis=1).astype(jnp.int32)  # chunk skip
        # grid steps that do work: the live chunks, one grid step each
        chunks_live = act.sum()

    # endpoint roles resolved from the grouping
    chunk_src = chunk_out if to == "src" else chunk_in
    chunk_dst = chunk_out if to == "dst" else chunk_in
    # a chunk's edges fill it from the front, so a chunk whose first slot is
    # out of bounds is padding throughout
    pad = perm.reshape(n_chunks, eb)[:, 0] >= e
    cout = grid_chunk_out(chunk_out, pad)
    sq = pl.Squeezed()
    in_specs = [
        pl.BlockSpec((sq, 1, eb), lambda c, co_, cs_, cd_, a: (c, 0, 0)),
        pl.BlockSpec((sq, width, eb), lambda c, co_, cs_, cd_, a: (c, 0, 0)),
    ]
    operands = [slot_rows, cedge]
    if use_src:
        in_specs.append(pl.BlockSpec(
            (vb, dx), lambda c, co_, cs_, cd_, a: (cs_[c], 0)))
        operands.append(xp)
    if use_dst:
        in_specs.append(pl.BlockSpec(
            (vb, dx), lambda c, co_, cs_, cd_, a: (cd_[c], 0)))
        operands.append(xp)
    if have_scale and use_src:
        in_specs.append(pl.BlockSpec(
            (sq, sb, dx), lambda c, co_, cs_, cd_, a: (cs_[c], 0, 0)))
        operands.append(scp)
    if have_scale and use_dst:
        in_specs.append(pl.BlockSpec(
            (sq, sb, dx), lambda c, co_, cs_, cd_, a: (cd_[c], 0, 0)))
        operands.append(scp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                    # grid cout, src/dst + act
        grid=(n_chunks,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((vb, dm), lambda c, co_, cs_, cd_, a: (co_[c], 0)),
            pl.BlockSpec((vb, 1), lambda c, co_, cs_, cd_, a: (co_[c], 0)),
        ],
    )
    out, cnt = pl.pallas_call(
        _make_kernel(tile_fn, reduce, to, use_src, use_dst, have_scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((v_pad, dm), jnp.float32),
                   jax.ShapeDtypeStruct((v_pad, 1), jnp.float32)],
        # sequential: an output block accumulates across consecutive steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_triplet",
    )(cout, chunk_src, chunk_dst, act, *operands)
    # blocks no chunk maps to were never written: select, so that whatever
    # the buffer held there (NaN included) cannot leak into the result
    visited = jnp.zeros((n_vb,), bool).at[cout].set(True)
    vis = jnp.repeat(visited, vb)[:num_segments, None]
    out = jnp.where(vis, out[:num_segments], REDUCE_IDENTITY[reduce])
    cnt = jnp.where(vis, cnt[:num_segments], 0.0)
    return out, cnt[:, 0], chunks_live

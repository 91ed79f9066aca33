"""Pallas TPU kernel: CSR segment-sum — the mrTriplets aggregation hot-spot.

GraphX clusters edges by destination (§4.2, the CSR index); message
aggregation is then a segment reduction over sorted segment ids.  On TPU we
recast the reduction as a sequence of one-hot matmuls so it runs on the MXU:

    out[i·Vb : (i+1)·Vb]  +=  onehot(ids_j − i·Vb)ᵀ @ msgs_j

Grid = (num_vertex_blocks, num_edge_blocks), edge axis innermost so each
output block stays resident in VMEM across the whole edge sweep (revisiting
accumulation).  Two block-skip predicates implement the paper's index-scan /
skipStale optimisations (§4.6) at block granularity — TPUs cannot branch per
element, but skipping whole tiles is free:

  * band skip   — sorted ids mean edge block j only intersects a narrow band
                  of vertex blocks; [lo_j, hi_j) is precomputed and the tile
                  pair is skipped when it misses the band.
  * active skip — with incremental view maintenance most edge blocks have no
                  active source vertex late in the run; a per-block any-active
                  flag skips them.

VMEM budget per grid step (defaults Eb=512, Vb=512, D≤512, f32):
  msgs tile 512·D·4 ≤ 1 MiB, out tile 512·D·4 ≤ 1 MiB, ids 2 KiB — well
  under the ~16 MiB/core VMEM of v5e, and both matmul dims are multiples of
  128 (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .triplet import mxu_dot


def _kernel(lo_ref, hi_ref, act_ref, ids_ref, msgs_ref, out_ref):
    """One (vertex-block i, edge-block j) tile pair."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    # Zero the accumulator on the first edge step for this output block.
    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vb = out_ref.shape[0]
    lo = lo_ref[j]          # first segment id present in edge block j
    hi = hi_ref[j]          # last segment id present in edge block j
    active = act_ref[j]     # any active (non-masked) edge in block j?

    band_hit = jnp.logical_and(hi >= i * vb, lo < (i + 1) * vb)

    @pl.when(jnp.logical_and(band_hit, active != 0))
    def _accumulate():
        ids = ids_ref[...]                                   # [1, Eb] int32
        rows = jax.lax.broadcasted_iota(jnp.int32, (vb, ids.shape[1]), 0)
        onehot_t = (ids - i * vb == rows).astype(jnp.float32)  # [Vb, Eb]
        msgs = msgs_ref[...].astype(jnp.float32)             # [Eb, D]
        out_ref[...] += mxu_dot(onehot_t, msgs)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "edge_block", "vertex_block", "interpret"),
)
def segment_sum(
    msgs: jnp.ndarray,        # [E, D]
    seg_ids: jnp.ndarray,     # [E] int32, sorted ascending; pad with >= num_segments
    num_segments: int,
    *,
    edge_block: int = 512,
    vertex_block: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Segment-sum with f32 accumulation.  ids outside [0, num_segments) drop."""
    e, d = msgs.shape
    eb = min(edge_block, max(e, 8))
    vb = min(vertex_block, -(-max(num_segments, 8) // 8) * 8)

    # Pad E to a multiple of eb and V to a multiple of vb.
    e_pad = (-e) % eb
    v_out = num_segments + ((-num_segments) % vb)
    ids = jnp.concatenate([seg_ids, jnp.full((e_pad,), v_out, jnp.int32)]) if e_pad else seg_ids
    # Route dropped/padding ids to an out-of-band block we slice off at the end.
    ids = jnp.where((ids < 0) | (ids >= num_segments), v_out, ids).astype(jnp.int32)
    m = jnp.pad(msgs, ((0, e_pad), (0, 0))) if e_pad else msgs

    n_eb = (e + e_pad) // eb
    n_vb = v_out // vb + 1   # +1 out-of-band block swallowing padding ids

    # per-edge-block band and skip flags ride scalar prefetch (SMEM); ids are
    # laid along lanes, [n_eb, 1, eb], so each block spans the array's minor
    # two dimensions.
    ids3 = ids.reshape(n_eb, 1, eb)
    lo = ids3.min(axis=(1, 2)).astype(jnp.int32)
    hi = ids3.max(axis=(1, 2)).astype(jnp.int32)
    act = (ids3 < num_segments).any(axis=(1, 2)).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                                # lo, hi, active
        grid=(n_vb, n_eb),
        in_specs=[
            pl.BlockSpec((pl.Squeezed(), 1, eb),
                         lambda i, j, lo_, hi_, a_: (j, 0, 0)),   # ids
            pl.BlockSpec((eb, d), lambda i, j, lo_, hi_, a_: (j, 0)),  # msgs
        ],
        out_specs=pl.BlockSpec((vb, d), lambda i, j, lo_, hi_, a_: (i, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_vb * vb, d), jnp.float32),
        interpret=interpret,
    )(lo, hi, act, ids3, m)

    return out[:num_segments].astype(msgs.dtype)

"""SpMV — the degenerate scalar instance of the fused triplet kernel.

mrTriplets with a *linear* message (msg = w·x[src], reduce = sum) is SpMV.
Historically this module carried its own Pallas kernel; the general fused
triplet kernel (kernels/triplet.py, DESIGN.md §2.3) now subsumes it — the
one-hot-matmul gather/scatter strategy and the (dst_block, src_block) chunk
tiling both live there.  This wrapper keeps the established SpMV surface:

    out[v] = Σ_{e: dst(e)=v} w[e]·x[src(e)]

with `active_src_blocks` giving the historical BLOCK-granular skipStale
(§4.5.1/§4.6): every edge whose source block is stale is dropped, realised
as a per-edge live mask so the general kernel's chunk skip stays exact.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .triplet import build_triplet_tiles, flatten_tiles, fused_triplet


def _linear_message(sv, ev, dv):
    """msg = w · x[src] — the PageRank message, tile-level."""
    return sv * ev[:, :1]


# ----------------------------------------------------------------------------
# Build-time tiling metadata (numpy; graphs are immutable so this runs once).
# ----------------------------------------------------------------------------
def build_tiles(
    src_slot: np.ndarray,
    dst_slot: np.ndarray,
    edge_mask: np.ndarray,
    v_mir: int,
    *,
    eb: int = 512,
    vb: int = 512,
) -> dict[str, np.ndarray]:
    """Group edges into Eb-sized chunks sorted by (dst_block, src_block).

    The FLAT view of the per-partition build_triplet_tiles (dst is the
    aggregation side; single-partition callers get the identity
    flattening): `perm`, `chunk_out`, `chunk_in` and `rows`.
    """
    t = build_triplet_tiles(dst_slot, src_slot, edge_mask, v_mir, eb=eb, vb=vb)
    flat = flatten_tiles(t, e_blk=int(np.asarray(dst_slot).shape[-1]),
                         n_vb=max(-(-v_mir // vb), 1))
    return {k: np.asarray(v) for k, v in flat.items()}


def spmv(
    x: jnp.ndarray,           # [V_mir, D] mirror values
    w: jnp.ndarray,           # [E] edge weights (0 for masked edges)
    src_slot: jnp.ndarray,    # [E] int32, the slots build_tiles was given
    tiles: dict,              # from build_tiles
    active_src_blocks: jnp.ndarray | None,  # [n_src_blocks] bool or None
    v_mir: int,
    *,
    eb: int = 512,
    vb: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """out[v] = Σ_{e: dst(e)=v} w[e]·x[src(e)]  over live chunks. f32 out."""
    e = w.shape[0]
    if active_src_blocks is None:
        live = jnp.ones((e,), bool)
    else:                                            # skipStale at block level
        live = active_src_blocks[src_slot // vb]
    out, _, _ = fused_triplet(
        x, w[:, None], live, tiles, _linear_message,
        v_mir, x.shape[1], to="dst", reduce="sum", use_dst=False,
        eb=eb, vb=vb, interpret=interpret)
    return out

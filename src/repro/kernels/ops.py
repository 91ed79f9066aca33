"""jit'd public wrappers for the Pallas kernels.

Dispatch policy (one switch for the whole engine):
  * on TPU           -> compiled Pallas kernels,
  * on CPU (tests)   -> pure-jnp oracle from ref.py (fast) or the kernel in
                        interpret mode (exact kernel semantics; used by the
                        per-kernel sweep tests),
  * a `mode` other than "auto" pins one path (`kernel_mode` passes it).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from . import ref
from . import segment_sum as _segsum
from . import triplet as _triplet
from . import flash_attention as _flash

Mode = Literal["auto", "pallas", "interpret", "ref", "chunked"]


def _backend_is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(mode: Mode) -> str:
    if mode != "auto":
        return mode
    return "pallas" if _backend_is_tpu() else "ref"


# public: callers that prepare kernel-only inputs (e.g. chunk tilings) use
# this to skip the work when the mode resolves to the jnp oracle.
resolve_mode = _resolve


def segment_sum(msgs, seg_ids, num_segments: int, *, mode: Mode = "auto",
                edge_block: int = 512, vertex_block: int = 512):
    m = _resolve(mode)
    if m == "ref":
        return ref.segment_sum(msgs, seg_ids, num_segments)
    return _segsum.segment_sum(
        msgs, seg_ids, num_segments,
        edge_block=edge_block, vertex_block=vertex_block,
        interpret=(m == "interpret"))


build_triplet_tiles = _triplet.build_triplet_tiles
flatten_tiles = _triplet.flatten_tiles


def triplet(x, ev, src_slot, dst_slot, live, tiles, tile_fn,
            num_segments: int, dm: int, *, xscale=None, to: str = "dst",
            reduce: str = "sum", use_src: bool = True, use_dst: bool = True,
            mode: Mode = "auto", eb: int = 512, vb: int = 512):
    """General fused mrTriplets sweep: gather(src,dst) + map + segment-reduce
    in one pass.  `tiles` is the flat device-resident table dict
    (build_triplet_tiles -> flatten_tiles, built toward `to` from these
    slots); the jnp oracle ignores it (pass None), and the kernel reads the
    slots from its static `rows` instead of `src_slot`/`dst_slot`.
    `xscale` is the narrow-resident scale plane (§2.4): per-32-row E8M0
    exponents dequantizing an encoded `x` at the staging seam — in-VMEM on
    the kernel path, up-front on the oracle, bit-identical either way.
    Returns (out [S, dm] f32, cnt [S] f32, chunks_live): the kernel's count
    of chunks with a live edge, None on the oracle, which runs no grid."""
    m = _resolve(mode)
    if m == "ref":
        out, cnt = ref.fused_triplet(x, ev, src_slot, dst_slot, live, tile_fn,
                                     num_segments, xscale=xscale, to=to,
                                     reduce=reduce)
        return out, cnt, None
    return _triplet.fused_triplet(
        x, ev, live, tiles, tile_fn, num_segments, dm,
        xscale=xscale, to=to, reduce=reduce, use_src=use_src, use_dst=use_dst,
        eb=eb, vb=vb, interpret=(m == "interpret"))


def superstep_apply(payload, slot, live, tiles, x, vid, vmask, apply_fn,
                    num_slots: int, dm: int, dv: int, *,
                    reduce: str = "sum", groups: int | None = None,
                    group_span: int = 1, mode: Mode = "auto",
                    eb: int = 512, vb: int = 512):
    """Fused superstep apply half (§2.3.2): combine the routed aggregate rows
    into per-home-vertex totals, then run the engine's packed vprog/changed
    closure in the same sweep.  `tiles` is the flat apply-route table dict
    (tiles["apply_*"] -> flatten_tiles); the jnp oracle ignores it (pass
    None).  `groups`/`group_span` pin the fixed f32 sum accumulation order
    on the oracle (ascending source partition, each group collision-free);
    the kernel path gets the same order from the apply tile tables' pe-keyed
    in_slot grouping, so both are bit-identical to the unfused combine.
    Returns (new packed state [S, dv] f32, changed [S] f32 0/1)."""
    m = _resolve(mode)
    if m == "ref":
        return ref.fused_apply(payload, slot, live, x, vid, vmask, apply_fn,
                               num_slots, reduce=reduce, groups=groups,
                               group_span=group_span)
    from . import superstep as _superstep
    return _superstep.fused_apply(
        payload, slot, live, tiles, x, vid, vmask, apply_fn, num_slots,
        dm, dv, reduce=reduce, eb=eb, vb=vb, interpret=(m == "interpret"))


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    kv_offset: int = 0, mode: Mode = "auto",
                    block_q: int = 512, block_kv: int = 512):
    m = _resolve(mode)
    if m == "chunked":
        return ref.flash_attention_chunked(q, k, v, causal=causal,
                                           scale=scale, kv_offset=kv_offset,
                                           block_kv=max(block_kv, 1024))
    if m == "ref":
        return ref.flash_attention(q, k, v, causal=causal, scale=scale,
                                   kv_offset=kv_offset)
    return _flash.flash_attention(q, k, v, causal=causal, scale=scale,
                                  kv_offset=kv_offset,
                                  block_q=block_q, block_kv=block_kv,
                                  interpret=(m == "interpret"))


def mlstm_chunked(q, k, v, logi, logf, *, chunk: int = 128,
                  mode: Mode = "auto"):
    """Fused chunkwise mLSTM (state resident in VMEM across the sequence)."""
    m = _resolve(mode)
    if m == "ref":
        return ref.mlstm_chunked(q, k, v, logi, logf, chunk=chunk)
    from . import mlstm as _mlstm
    return _mlstm.mlstm_chunked(q, k, v, logi, logf, chunk=chunk,
                                interpret=(m == "interpret"))

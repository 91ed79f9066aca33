"""Pallas TPU kernel: the fused Pregel superstep APPLY half (DESIGN.md §2.3.2).

The triplet kernel (kernels/triplet.py) fuses gather + edge UDF + segment
reduce on the MIRROR side; after the aggregate-return route ships per-edge-
partition partials back to their home partitions, the unfused engine still
materialises four home-resident intermediates in HBM between operators:
combined messages, defaulted messages, the new vertex state, and the changed
mask.  This kernel runs the whole home half in ONE sweep per vertex block —

    acc  = combine(routed partials)           # scatter: MXU matmul ('sum')
                                              #   or segmented scan ('min'/'max')
    new  = vprog(vid, unpack(x), default-substituted unpack(acc))
    new  = where(vmask, new, x)               # visibility select
    chg  = changed(x, new) & vmask            # §4.5.1 changed mask, in-kernel

— so vertex state and aggregates stay VMEM-resident between the combine and
the apply, and the changed mask is derived from exactly the values written
(delta-correctness: the view's dirty tracking keys on this mask, §3.1).

Route entries play the role edges play in the triplet kernel: the apply tile
tables (partition.build_structure, tiles["apply_*"]) group each partition's
[P·K] aggregate-return rows into eb-chunks by destination home block through
the same `build_chunk_tables` machinery, so chunk skipping, scalar-prefetch
indirection, and the scan-sortedness invariant all carry over unchanged.

`apply_fn` is an engine-built closure (core/mrtriplets._make_apply_fn) that
owns per-leaf packing: unpack state/messages from the column-packed staging
matrices, substitute the per-leaf default message where no message arrived,
vmap the user vprog, select on visibility, and derive the changed bit.  The
oracle (ref.fused_apply) shares it verbatim.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .triplet import (DEFAULT_EDGE_BLOCK, DEFAULT_VERTEX_BLOCK,
                      REDUCE_IDENTITY, chunk_rows, mxu_dot,
                      segmented_reduce_mxu)


def _make_apply_kernel(apply_fn: Callable, reduce: str):
    ident = REDUCE_IDENTITY[reduce]

    def kernel(cout_ref, act_ref,
               edge_ref, pay_ref,
               xv_ref, vid_ref, vm_ref,
               newv_ref, chg_ref, acc_ref, cnt_ref):
        i = pl.program_id(0)      # home vertex block
        c = pl.program_id(1)      # route chunk
        n_chunks = pl.num_programs(1)

        @pl.when(c == 0)
        def _init():
            acc_ref[...] = jnp.full_like(acc_ref, ident)
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

        mine = cout_ref[c] == i
        @pl.when(jnp.logical_and(mine, act_ref[c] != 0))
        def _accumulate():
            vb = acc_ref.shape[0]
            row = edge_ref[...]         # [2, Eb] home slot, live
            col = row.T                                          # [Eb, 2]
            eb = col.shape[0]
            live = col[:, 1:2] > 0                               # [Eb, 1]
            pay = pay_ref[...].T                                 # [Eb, Dm]
            rows = jax.lax.broadcasted_iota(jnp.int32, (vb, eb), 0)
            oh_t = (row[0:1, :] == rows).astype(jnp.float32)     # [Vb, Eb]
            oh_live = oh_t * (row[1:2, :] > 0).astype(jnp.float32)
            cnt_ref[...] += jnp.sum(oh_live, axis=1, keepdims=True)
            if reduce == "sum":
                acc_ref[...] += mxu_dot(oh_live,                 # scatter-add
                                        jnp.where(live, pay, 0.0))
            else:
                sel = jnp.minimum if reduce == "min" else jnp.maximum
                vals = jnp.where(live, pay, ident)
                red = segmented_reduce_mxu(vals, col[:, 0:1], row[0:1, :],
                                           reduce, ident, oh_t)
                acc_ref[...] = sel(acc_ref[...], red)

        # the LAST chunk's visit to this block closes the combine; the apply
        # runs on the still-VMEM-resident accumulator and writes state +
        # changed mask in the same kernel invocation.
        @pl.when(c == n_chunks - 1)
        def _apply():
            exists = cnt_ref[...] > 0.0                          # [vb, 1]
            newv, changed = apply_fn(
                vid_ref[...], vm_ref[...],
                xv_ref[...].astype(jnp.float32), acc_ref[...], exists)
            newv_ref[...] = newv
            chg_ref[...] = changed

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("apply_fn", "num_slots", "dm", "dv", "reduce",
                     "eb", "vb", "interpret"))
def fused_apply(
    payload: jnp.ndarray,     # [R, Dm] routed aggregate rows (flat space)
    slot: jnp.ndarray,        # [R] int32 home slot per row (flat PADDED space)
    live: jnp.ndarray,        # [R] bool — row carries a real aggregate
    tiles: dict,              # FLAT apply tables (build_chunk_tables over the
                              # route -> flatten_tiles; in_slot unused)
    x: jnp.ndarray,           # [S, Dv] packed home vertex state
    vid: jnp.ndarray,         # [S] int32 home vertex ids
    vmask: jnp.ndarray,       # [S] home visibility mask
    apply_fn: Callable,       # engine closure, see module docstring
    num_slots: int,           # = S (per-partition slot spaces pre-padded to vb)
    dm: int,                  # packed message width
    dv: int,                  # packed vertex-state width
    *,
    reduce: str = "sum",
    eb: int = DEFAULT_EDGE_BLOCK,
    vb: int = DEFAULT_VERTEX_BLOCK,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Combine routed aggregates and apply the vprog in one Pallas sweep.

    Returns (new packed state [S, Dv] f32, changed [S] f32 0/1)."""
    r = slot.shape[0]
    perm = jnp.asarray(tiles["perm"])
    chunk_out = jnp.asarray(tiles["chunk_out"])
    n_chunks = chunk_out.shape[0]
    n_vb = max(-(-num_slots // vb), 1)
    v_pad = n_vb * vb
    dxv = max(dv, 1)

    xp = jnp.pad(x.reshape(x.shape[0], -1).astype(jnp.float32),
                 ((0, v_pad - x.shape[0]), (0, max(1 - x.shape[1], 0))))
    vidp = jnp.pad(vid.astype(jnp.int32), (0, v_pad - vid.shape[0]))[:, None]
    vmp = jnp.pad(vmask.astype(jnp.float32),
                  (0, v_pad - vmask.shape[0]))[:, None]
    payp = jnp.concatenate(
        [payload.reshape(r, -1).astype(jnp.float32),
         jnp.zeros((1, dm), jnp.float32)])
    sp = jnp.concatenate([slot.astype(jnp.int32), jnp.zeros((1,), jnp.int32)])
    lp = jnp.concatenate([live, jnp.zeros((1,), bool)])

    pc = perm.reshape(n_chunks, eb)
    oob = pc >= r
    cs = jnp.where(oob, vb, sp[pc] - (chunk_out * vb)[:, None]).astype(jnp.int32)
    clive = lp[pc] & ~oob
    cedge = chunk_rows([cs], clive)
    cpay = jnp.swapaxes(payp[pc], 1, 2)           # padding row -> zeros
    act = clive.any(axis=1).astype(jnp.int32)     # chunk skip flag (dynamic)

    sq = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # chunk_out + act
        grid=(n_vb, n_chunks),
        in_specs=[
            pl.BlockSpec((sq, 2, eb), lambda i, c, co_, a: (c, 0, 0)),
            pl.BlockSpec((sq, dm, eb), lambda i, c, co_, a: (c, 0, 0)),
            pl.BlockSpec((vb, dxv), lambda i, c, co_, a: (i, 0)),
            pl.BlockSpec((vb, 1), lambda i, c, co_, a: (i, 0)),
            pl.BlockSpec((vb, 1), lambda i, c, co_, a: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((vb, dxv), lambda i, c, co_, a: (i, 0)),
            pl.BlockSpec((vb, 1), lambda i, c, co_, a: (i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((vb, dm), jnp.float32),
                        pltpu.VMEM((vb, 1), jnp.float32)],
    )

    newv, chg = pl.pallas_call(
        _make_apply_kernel(apply_fn, reduce),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((v_pad, dxv), jnp.float32),
                   jax.ShapeDtypeStruct((v_pad, 1), jnp.float32)],
        interpret=interpret,
        name="fused_apply",
    )(chunk_out, act, cedge, cpay, xp, vidp, vmp)
    return newv[:num_slots], chg[:num_slots, 0]

"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contracts: tests sweep shapes/dtypes and assert
`assert_allclose(kernel(interpret=True), ref)`.  They are also the CPU
fallback paths used by the engine when no TPU is present.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_sum(msgs: jnp.ndarray, seg_ids: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    """Sum messages [E, D] into segments [V, D] by sorted-or-not seg_ids [E].

    seg_ids >= num_segments (or < 0) are dropped (padding convention).
    Accumulation in float32.
    """
    valid = (seg_ids >= 0) & (seg_ids < num_segments)
    safe = jnp.where(valid, seg_ids, 0)
    m = jnp.where(valid[:, None], msgs, 0).astype(jnp.float32)
    out = jax.ops.segment_sum(m, safe, num_segments=num_segments)
    return out.astype(msgs.dtype)


# The finite (finfo-extreme) identity convention shared with the kernel —
# single source of truth so oracle and kernel stay bit-identical on empty
# segments (triplet.py imports nothing back from this module).
from .triplet import REDUCE_IDENTITY as _TRIPLET_IDENTITY  # noqa: E402
from .triplet import SCALE_GROUP as _SCALE_GROUP  # noqa: E402


def _dequant_rows(xf: jnp.ndarray, xscale: jnp.ndarray) -> jnp.ndarray:
    """Apply per-SCALE_GROUP-row pow2 exponents to an (exactly upcast) f32
    staging matrix — the oracle's counterpart of the kernel's in-VMEM
    dequant (`triplet._gather`, which gathers payload and exponent per
    edge).  Same values, same multiply, so the two paths stay
    bit-identical (§2.4)."""
    s = xf.shape[0]
    sc = xscale.astype(jnp.float32).reshape(xscale.shape[0], -1)
    sp = jnp.repeat(sc, _SCALE_GROUP, axis=0)[:s]
    if sp.shape[1] != xf.shape[1]:          # width-padded staging column
        sp = jnp.pad(sp, ((0, 0), (0, xf.shape[1] - sp.shape[1])))
    return xf * jnp.exp2(sp)


def fused_triplet(
    x: jnp.ndarray,          # [S, Dx] packed mirror matrix
    ev: jnp.ndarray,         # [E, De] packed edge payload
    src_slot: jnp.ndarray,   # [E] int32 in [0, S)
    dst_slot: jnp.ndarray,   # [E] int32 in [0, S)
    live: jnp.ndarray,       # [E] bool
    tile_fn,                 # ([E,Dx],[E,De],[E,Dx]) -> [E,Dm] f32
    num_segments: int,
    *,
    xscale: jnp.ndarray | None = None,   # [ceil(S/32), Dx] E8M0 exponents
    to: str = "dst",
    reduce: str = "sum",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for kernels/triplet.fused_triplet — the general fused mrTriplets
    sweep (gather both endpoints, map, segment-reduce toward `to`) in plain
    jnp.  Operands follow the kernel's packing contract: `x`/`ev` are
    column-packed staging matrices — f32, or bf16 when the engine packed a
    narrow-wire mirror (§2.1); both the oracle and the kernel upcast to f32
    at the accumulator, so the two stagings are bit-identical.  Multi-leaf
    payloads concatenate; integers stage exactly under the engine's
    round-trip guard.  `tile_fn` returns the column-packed [*, Dm] message
    matrix that the engine splits back per leaf.  No chunk tables here —
    the oracle sweeps the flat edge space directly.  Empty segments hold
    the finite reduce identity; returns (out [S, Dm] f32, cnt [S] f32 live
    message counts)."""
    s = x.shape[0]
    xf = x.astype(jnp.float32).reshape(s, -1)
    if xf.shape[1] == 0:
        xf = jnp.zeros((s, 1), jnp.float32)
    if xscale is not None:
        xf = _dequant_rows(xf, xscale)
    evf = ev.astype(jnp.float32).reshape(ev.shape[0], -1)
    if evf.shape[1] == 0:
        evf = jnp.zeros((ev.shape[0], 1), jnp.float32)
    sv = xf[jnp.clip(src_slot, 0, s - 1)]
    dv = xf[jnp.clip(dst_slot, 0, s - 1)]
    msgs = tile_fn(sv, evf, dv)                                  # [E, Dm]

    ids = src_slot if to == "src" else dst_slot
    seg = jnp.where(live, ids, num_segments)                     # dead -> OOB
    ident = _TRIPLET_IDENTITY[reduce]
    cnt = jax.ops.segment_sum(live.astype(jnp.float32), seg,
                              num_segments=num_segments + 1)[:num_segments]
    if reduce == "sum":
        m = jnp.where(live[:, None], msgs, 0.0)
        out = jax.ops.segment_sum(m, seg,
                                  num_segments=num_segments + 1)[:num_segments]
    else:
        fn = jax.ops.segment_min if reduce == "min" else jax.ops.segment_max
        m = jnp.where(live[:, None], msgs, ident)
        out = fn(m, seg, num_segments=num_segments + 1)[:num_segments]
        out = jnp.where(cnt[:, None] > 0, out, ident)            # finite ident
    return out, cnt


def fused_apply(
    payload: jnp.ndarray,    # [R, Dm] f32 routed aggregate rows (flat space)
    slot: jnp.ndarray,       # [R] int32 HOME slot per row (flat padded space)
    live: jnp.ndarray,       # [R] bool — row carries a real aggregate
    x: jnp.ndarray,          # [S, Dv] packed home vertex state (f32 staging)
    vid: jnp.ndarray,        # [S] int32 home vertex ids
    vmask: jnp.ndarray,      # [S] home visibility mask (0/1)
    apply_fn,                # ([S,1]i32,[S,1]f32,[S,Dv],[S,Dm],[S,1]bool)
                             #   -> ([S,Dv] f32, [S,1] f32)
    num_slots: int,          # = S
    *,
    reduce: str = "sum",
    groups: int | None = None,   # fixed-order sum: number of source-partition
                                 # groups; row r belongs to (r//group_span)%groups
    group_span: int = 1,         # contiguous rows per group per home partition
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for kernels/superstep.fused_apply — the home half of a fused
    Pregel superstep (DESIGN.md §2.3.2): combine the routed per-partition
    aggregates into per-home-vertex totals, then run the (already vmapped,
    column-packed) vprog apply + changed-mask closure in the same sweep.
    `apply_fn` owns the engine's per-leaf unpack / default-message
    substitution / visibility select / changed derivation, so the oracle and
    kernel share it verbatim and differ only in how the combine lands.

    `groups`/`group_span` pin the FIXED accumulation order for f32 sums
    (§2.4, PR-7 follow-up (b)): the aggregate-return route lays rows out as
    [nl, P, K] so rows of one source partition (one group) never collide on
    a home slot — each group is a collision-free scatter, and accumulating
    groups in ascending order reproduces the kernel's ascending-chunk adds
    bit-for-bit.  With groups=None sums fall back to segment_sum (only safe
    when the caller tolerates reassociation).

    Returns (new packed state [S, Dv] f32, changed [S] f32 0/1)."""
    ident = _TRIPLET_IDENTITY[reduce]
    seg = jnp.where(live, slot, num_slots)                       # dead -> OOB
    cnt = jax.ops.segment_sum(live.astype(jnp.float32), seg,
                              num_segments=num_slots + 1)[:num_slots]
    if reduce == "sum" and groups is not None:
        r = payload.shape[0]
        m = jnp.where(live[:, None], payload, 0.0).astype(jnp.float32)
        gid = (jnp.arange(r) // group_span) % groups
        acc = jnp.zeros((num_slots + 1, payload.shape[1]), jnp.float32)
        for g in range(groups):
            sel = gid == g
            idx = jnp.where(sel, seg, num_slots)
            acc = acc.at[idx].add(jnp.where(sel[:, None], m, 0.0),
                                  mode="drop")
        acc = acc[:num_slots]
    elif reduce == "sum":
        m = jnp.where(live[:, None], payload, 0.0).astype(jnp.float32)
        acc = jax.ops.segment_sum(m, seg,
                                  num_segments=num_slots + 1)[:num_slots]
    else:
        fn = jax.ops.segment_min if reduce == "min" else jax.ops.segment_max
        m = jnp.where(live[:, None], payload.astype(jnp.float32), ident)
        acc = fn(m, seg, num_segments=num_slots + 1)[:num_slots]
        acc = jnp.where(cnt[:, None] > 0, acc, ident)
    new, chg = apply_fn(vid.astype(jnp.int32)[:, None],
                        vmask.astype(jnp.float32)[:, None],
                        x.astype(jnp.float32), acc, cnt[:, None] > 0)
    return new, chg[:, 0]


def flash_attention(
    q: jnp.ndarray,  # [B, Hq, Lq, Dh]
    k: jnp.ndarray,  # [B, Hkv, Lk, Dh]
    v: jnp.ndarray,  # [B, Hkv, Lk, Dh]
    *,
    causal: bool = True,
    scale: float | None = None,
    kv_offset: int = 0,
) -> jnp.ndarray:
    """Reference GQA attention (fp32 softmax).  kv_offset shifts the causal
    diagonal for decode/prefill-with-cache: query position i attends to
    kv positions <= i + kv_offset."""
    b, hq, lq, dh = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    g = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    qf = q.astype(jnp.float32).reshape(b, hkv, g, lq, dh)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf) * scale
    if causal:
        lk = k.shape[2]
        mask = jnp.arange(lq)[:, None] + kv_offset >= jnp.arange(lk)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vf)
    return out.reshape(b, hq, lq, dh).astype(q.dtype)


def flash_attention_chunked(
    q: jnp.ndarray,  # [B, Hq, Lq, Dh]
    k: jnp.ndarray,  # [B, Hkv, Lk, Dh]
    v: jnp.ndarray,  # [B, Hkv, Lk, Dh]
    *,
    causal: bool = True,
    scale: float | None = None,
    kv_offset: int = 0,
    block_kv: int = 2048,
) -> jnp.ndarray:
    """Streaming (online-softmax) attention in pure jnp — the XLA-level
    flash algorithm.

    Semantically identical to `flash_attention` above but NEVER materialises
    the [Lq, Lk] logits: a lax.scan over KV blocks carries running
    (max, denom, accumulator).  This is what the dry-run lowers for
    long-sequence cells — on TPU the Pallas kernel plays this role; on the
    CPU-backend SPMD compile this keeps both HBM traffic and residency
    linear in sequence length, and GSPMD shards the query axis cleanly.
    """
    b, hq, lq, dh = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    g = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    blk = min(block_kv, lk)
    nb = -(-lk // blk)
    pad = nb * blk - lk

    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, g, lq, dh)
    kp = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = kp.reshape(b, hkv, nb, blk, dh).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(b, hkv, nb, blk, dh).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(lq, dtype=jnp.int32) + kv_offset

    NEG = jnp.float32(-1e30)   # finite sentinel: exp(-inf - NEG) stays 0

    def body(carry, inp):
        m, l, acc = carry
        kb_, vb_, j0 = inp
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kb_)          # [b,h,g,lq,blk]
        k_pos = j0 + jnp.arange(blk, dtype=jnp.int32)
        valid = k_pos < lk
        if causal:
            valid = valid[None, :] & (q_pos[:, None] >= k_pos[None, :])
        else:
            valid = jnp.broadcast_to(valid[None, :], (lq, blk))
        s = jnp.where(valid[None, None, None], s, -jnp.inf)
        m2 = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m2[..., None])
        corr = jnp.exp(m - m2)
        l2 = l * corr + p.sum(axis=-1)
        acc2 = acc * corr[..., None] + jnp.einsum("bhgqk,bhkd->bhgqd", p, vb_)
        return (m2, l2, acc2), None

    m0 = jnp.full((b, hkv, g, lq), NEG, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, lq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, lq, dh), jnp.float32)
    j0s = jnp.arange(nb, dtype=jnp.int32) * blk
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, j0s))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, lq, dh).astype(q.dtype)


def mlstm_chunked(q, k, v, logi, logf, *, chunk: int = 128):
    """Oracle for kernels/mlstm.py — the chunkwise-parallel mLSTM scan in
    pure jnp (same math as models/recurrent.mlstm_block's core)."""
    b, h, l, dh = q.shape
    w = min(chunk, l)
    assert l % w == 0
    nc = l // w
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    cq = qf.reshape(b, h, nc, w, dh).transpose(2, 0, 1, 3, 4)
    ck = kf.reshape(b, h, nc, w, dh).transpose(2, 0, 1, 3, 4)
    cv = vf.reshape(b, h, nc, w, dh).transpose(2, 0, 1, 3, 4)
    cli = logi.astype(jnp.float32).reshape(b, h, nc, w).transpose(2, 0, 1, 3)
    clf = logf.astype(jnp.float32).reshape(b, h, nc, w).transpose(2, 0, 1, 3)

    def chunk_step(carry, inp):
        C, n = carry
        qc, kc, vc, lic, lfc = inp
        cum = jnp.cumsum(lfc, axis=-1)
        total = cum[..., -1:]
        dmat = cum[..., :, None] - cum[..., None, :] + lic[..., None, :]
        tri = jnp.tril(jnp.ones((w, w), bool))
        dmat = jnp.where(tri, dmat, -jnp.inf)
        m_row = jnp.maximum(jnp.max(dmat, axis=-1), cum)
        att = jnp.einsum("bhtk,bhsk->bhts", qc, kc) * jnp.exp(
            dmat - m_row[..., None])
        intra = jnp.einsum("bhts,bhsk->bhtk", att, vc)
        dec = jnp.exp(cum - m_row)
        inter = jnp.einsum("bhtk,bhkv->bhtv", qc * dec[..., None], C)
        num = intra + inter
        den = att.sum(axis=-1) + jnp.einsum("bhtk,bhk->bht",
                                            qc * dec[..., None], n)
        out = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_row))[..., None]
        wgt = jnp.exp(total - cum + lic)
        C2 = jnp.exp(total)[..., None] * C + jnp.einsum(
            "bhsk,bhsv->bhkv", kc * wgt[..., None], vc)
        n2 = jnp.exp(total) * n + jnp.einsum("bhsk,bhs->bhk", kc, wgt)
        return (C2, n2), out

    C0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    n0 = jnp.zeros((b, h, dh), jnp.float32)
    _, outs = jax.lax.scan(chunk_step, (C0, n0), (cq, ck, cv, cli, clf))
    return outs.transpose(1, 2, 0, 3, 4).reshape(b, h, l, dh)

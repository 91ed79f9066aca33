"""Per-kernel shape/dtype sweeps: pallas interpret mode vs ref.py oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.segment_sum import segment_sum
from repro.kernels import triplet as triplet_mod
from repro.kernels.flash_attention import flash_attention

RNG = np.random.default_rng(0)


# -------------------------------------------------------------- fused triplet
def _flat_graph(e, v, dx, de, seed=0, int_valued=True):
    """Random flat-slot-space triplet workload.  Integer-valued floats make
    f32 sums order-independent, so kernel-vs-oracle compares are EXACT."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    live = rng.random(e) > 0.3
    if int_valued:
        x = rng.integers(-4, 5, (v, dx)).astype(np.float32)
        ev = rng.integers(1, 4, (e, de)).astype(np.float32)
    else:
        x = rng.normal(size=(v, dx)).astype(np.float32)
        ev = rng.normal(size=(e, de)).astype(np.float32)
    return src, dst, live, x, ev


def _affine_msg(sv, evv, dv):
    return sv * evv[:, :1] + dv * evv[:, 1:2]


def _flat_tiles(out_s, in_s, mask, v, *, eb, vb):
    """Per-partition tables -> flat kernel operands (single partition)."""
    t = triplet_mod.build_triplet_tiles(out_s, in_s, mask, v, eb=eb, vb=vb)
    return triplet_mod.flatten_tiles(t, e_blk=int(np.shape(out_s)[-1]),
                                     n_vb=max(-(-v // vb), 1))


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
@pytest.mark.parametrize("to", ["dst", "src"])
@pytest.mark.parametrize("e,v,dx,eb,vb", [
    (400, 100, 3, 64, 32),
    pytest.param(1000, 256, 1, 128, 128, marks=pytest.mark.slow),
    (64, 16, 4, 32, 16)])
def test_triplet_kernel_matches_oracle(reduce, to, e, v, dx, eb, vb):
    src, dst, live, x, ev = _flat_graph(e, v, dx, 2, seed=e + dx)
    out_s, in_s = (dst, src) if to == "dst" else (src, dst)
    tiles = _flat_tiles(out_s, in_s, np.ones(e, bool), v, eb=eb, vb=vb)
    got, cnt, chunks_live = triplet_mod.fused_triplet(
        jnp.asarray(x), jnp.asarray(ev), jnp.asarray(live), tiles,
        _affine_msg, v, dx, to=to, reduce=reduce, eb=eb, vb=vb,
        interpret=True)
    want, cnt_want = ref.fused_triplet(
        jnp.asarray(x), jnp.asarray(ev), jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(live), _affine_msg, v, to=to, reduce=reduce)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the grid counter: chunks holding a live edge
    perm = np.asarray(tiles["perm"]).reshape(-1, eb)
    lp = np.append(np.asarray(live), False)
    assert int(chunks_live) == int(lp[np.minimum(perm, e)].any(axis=1).sum())


def test_triplet_kernel_dead_edges_and_empty_segments():
    e, v = 128, 32
    src, dst, _, x, ev = _flat_graph(e, v, 2, 2, seed=7)
    live = np.zeros(e, bool)                      # everything stale
    tiles = _flat_tiles(dst, src, np.ones(e, bool), v, eb=32, vb=16)
    for reduce in ("sum", "min", "max"):
        out, cnt, chunks_live = triplet_mod.fused_triplet(
            jnp.asarray(x), jnp.asarray(ev), jnp.asarray(live), tiles,
            _affine_msg, v, 2, reduce=reduce, eb=32, vb=16, interpret=True)
        assert float(np.asarray(cnt).sum()) == 0.0
        assert int(chunks_live) == 0              # every chunk skipped
        ident = triplet_mod.REDUCE_IDENTITY[reduce]
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full((v, 2), ident, np.float32))


def test_triplet_tiles_per_partition_flatten():
    """The tentpole contract: per-partition [P, n_chunks, ...] tables padded
    to a uniform chunk count, flattened onto the stacked block space, must
    reproduce P independent single-partition sweeps."""
    p, e_blk, v_mir, dx = 3, 96, 24, 2
    eb, vb = 32, 16
    n_vb = -(-v_mir // vb)
    v_pad = n_vb * vb
    rng = np.random.default_rng(42)
    src = rng.integers(0, v_mir, (p, e_blk)).astype(np.int32)
    dst = rng.integers(0, v_mir, (p, e_blk)).astype(np.int32)
    # partition 2 is almost empty -> exercises the uniform-chunk padding
    mask = rng.random((p, e_blk)) > 0.2
    mask[2, 4:] = False
    live = mask & (rng.random((p, e_blk)) > 0.3)
    x = rng.integers(-4, 5, (p, v_mir, dx)).astype(np.float32)
    ev = rng.integers(1, 4, (p, e_blk, 1)).astype(np.float32)

    tiles = triplet_mod.build_triplet_tiles(dst, src, mask, v_mir,
                                            eb=eb, vb=vb)
    assert tiles["perm"].shape[0] == p
    assert tiles["perm"].shape[1] == tiles["chunk_out"].shape[1]
    flat = triplet_mod.flatten_tiles(tiles, e_blk=e_blk, n_vb=n_vb)

    xpad = np.zeros((p, v_pad, dx), np.float32)
    xpad[:, :v_mir] = x
    off = (np.arange(p, dtype=np.int32) * v_pad)[:, None]
    msg = lambda sv, evv, dv: sv * evv[:, :1] + dv
    for reduce in ("sum", "min"):
        got, cnt, _ = triplet_mod.fused_triplet(
            jnp.asarray(xpad.reshape(p * v_pad, dx)),
            jnp.asarray(ev.reshape(-1, 1)), jnp.asarray(live.reshape(-1)),
            flat, msg, p * v_pad, dx, reduce=reduce, eb=eb, vb=vb,
            interpret=True)
        got = np.asarray(got).reshape(p, v_pad, dx)[:, :v_mir]
        cnt = np.asarray(cnt).reshape(p, v_pad)[:, :v_mir]
        for q in range(p):   # each partition == its own single-device sweep
            want, cwant = ref.fused_triplet(
                jnp.asarray(x[q]), jnp.asarray(ev[q]), jnp.asarray(src[q]),
                jnp.asarray(dst[q]), jnp.asarray(live[q]), msg, v_mir,
                reduce=reduce)
            np.testing.assert_array_equal(got[q], np.asarray(want))
            np.testing.assert_array_equal(cnt[q], np.asarray(cwant))


def _grid_order_case(to, seed=5):
    """Four partitions on the flat space, built so that the 1-D chunk grid
    meets every case its block order has to survive:

      partition 0: no aggregation slot in block 1 (a middle block no chunk
                   maps to), and 40 edges on one (out 2, in 0) block pair,
                   split over consecutive chunks of eb = 16;
      partition 1: no edge at all, so nothing but padding chunks;
      partition 2: edges, none of them live;
      partition 3: few edges, so a tail of padding chunks.
    """
    p, e_blk, v_mir, dx = 4, 96, 64, 2
    eb = vb = 16
    rng = np.random.default_rng(seed)
    out_s = rng.integers(0, v_mir, (p, e_blk)).astype(np.int32)
    in_s = rng.integers(0, v_mir, (p, e_blk)).astype(np.int32)
    out_s[0] = np.where(out_s[0] // vb == 1, out_s[0] + vb, out_s[0])
    out_s[0, :40] = rng.integers(2 * vb, 3 * vb, 40)
    in_s[0, :40] = rng.integers(0, vb, 40)
    mask = np.ones((p, e_blk), bool)
    mask[1] = False
    mask[3, 10:] = False
    live = mask & (rng.random((p, e_blk)) > 0.25)
    live[2] = False
    tiles = triplet_mod.build_triplet_tiles(out_s, in_s, mask, v_mir,
                                            eb=eb, vb=vb)
    co = tiles["chunk_out"]
    real = (tiles["perm"] < e_blk).any(axis=2)
    assert 1 not in co[0][real[0]]
    assert ((co[0] == 2) & (tiles["chunk_in"][0] == 0)).sum() >= 3
    assert not real[1].any() and not real[3][-1]

    off = (np.arange(p, dtype=np.int32) * v_mir)[:, None]
    out_f, in_f = (out_s + off).reshape(-1), (in_s + off).reshape(-1)
    src, dst = (in_f, out_f) if to == "dst" else (out_f, in_f)
    x = rng.integers(-4, 5, (p * v_mir, dx)).astype(np.float32)
    ev = rng.integers(1, 4, (p * e_blk, 2)).astype(np.float32)
    flat = triplet_mod.flatten_tiles(tiles, e_blk=e_blk, n_vb=v_mir // vb)
    return dict(x=x, ev=ev, src=src, dst=dst, live=live.reshape(-1),
                tiles=flat, v=p * v_mir, dx=dx, eb=eb, vb=vb)


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
@pytest.mark.parametrize("to", ["dst", "src"])
def test_triplet_chunk_grid_block_order(reduce, to):
    """The 1-D chunk grid over a multi-partition flat table: padding tails,
    a block no chunk maps to, a run split over consecutive chunks of one
    block, a partition with nothing live — bit-exact to the oracle."""
    k = _grid_order_case(to)
    args = [jnp.asarray(k[n]) for n in ("x", "ev", "src", "dst", "live")]
    got, cnt, _ = triplet_mod.fused_triplet(
        args[0], args[1], args[4], k["tiles"], _affine_msg, k["v"], k["dx"],
        to=to, reduce=reduce, eb=k["eb"], vb=k["vb"], interpret=True)
    want, cnt_want = ref.fused_triplet(*args, _affine_msg, k["v"], to=to,
                                       reduce=reduce)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the unvisited middle block of partition 0, and all of partition 1
    ident = triplet_mod.REDUCE_IDENTITY[reduce]
    for rows in (slice(16, 32), slice(64, 128)):
        np.testing.assert_array_equal(np.asarray(got)[rows],
                                      np.full((16 if rows.start == 16 else 64,
                                               k["dx"]), ident, np.float32))
        assert not np.asarray(cnt)[rows].any()


@pytest.mark.parametrize("source", ["dst", "src", "random"])
def test_grid_chunk_out_never_decreases(source):
    """The chip writes an output block back when the grid leaves it and
    never reads it in again, so the grid's block ids must never decrease;
    interpret mode re-reads a revisited block, so only this invariant
    guards the order on CPU.  On every real chunk they are its chunk_out."""
    if source == "random":   # uneven partitions, every one padded but one
        p, e_blk, v_mir = 5, 300, 100
        rng = np.random.default_rng(11)
        mask = rng.random((p, e_blk)) < np.linspace(0.1, 1.0, p)[:, None]
        tiles = triplet_mod.build_triplet_tiles(
            rng.integers(0, v_mir, (p, e_blk)),
            rng.integers(0, v_mir, (p, e_blk)), mask, v_mir, eb=16, vb=16)
        flat = triplet_mod.flatten_tiles(tiles, e_blk=e_blk,
                                         n_vb=-(-v_mir // 16))
        e = p * e_blk
    else:
        k = _grid_order_case(source)
        flat, e = k["tiles"], k["src"].size
    co = np.asarray(flat["chunk_out"])
    pad = (np.asarray(flat["perm"]).reshape(co.size, -1) >= e).all(axis=1)
    cout = np.asarray(triplet_mod.grid_chunk_out(jnp.asarray(co),
                                                 jnp.asarray(pad)))
    assert pad.any() and (~pad).any()
    assert (np.diff(co) < 0).any()        # the stored tables break the order
    assert (np.diff(cout) >= 0).all()
    np.testing.assert_array_equal(cout[~pad], co[~pad])


@pytest.mark.parametrize("p", [1, 4])
@pytest.mark.parametrize("to", ["dst", "src"])
def test_triplet_tile_rows_match_per_call_gather(to, p):
    """The static `rows` of the tile tables equal what the sweep used to
    gather through `perm` on every call: each chunk edge's aggregation-side
    and gather-side slot in the flat space, less its chunk's block base,
    and vb on padding, packed in the high and the low half-word.  With p == 4, partition 1 has no edge and partition 3
    few, so both end in whole padding chunks."""
    e_blk, v_mir, eb, vb = 150, 80, 32, 16
    n_vb = -(-v_mir // vb)
    rng = np.random.default_rng(13)
    src = rng.integers(0, v_mir, (p, e_blk)).astype(np.int32)
    dst = rng.integers(0, v_mir, (p, e_blk)).astype(np.int32)
    mask = rng.random((p, e_blk)) > 0.1
    if p == 4:
        mask[1] = False
        mask[3, 12:] = False
    out_s, in_s = (dst, src) if to == "dst" else (src, dst)
    tiles = triplet_mod.build_triplet_tiles(out_s, in_s, mask, v_mir,
                                            eb=eb, vb=vb)
    n_chunks = tiles["chunk_out"].shape[1]
    assert tiles["rows"].shape == (p, n_chunks, eb)
    assert tiles["rows"].dtype == np.int32
    # the apply tables are built without them
    assert "rows" not in triplet_mod.build_chunk_tables(
        out_s, in_s, mask, v_mir, eb=eb, vb=vb)
    flat = triplet_mod.flatten_tiles(tiles, e_blk=e_blk, n_vb=n_vb)

    # the per-call derivation the rows replace, on the flat space
    e = p * e_blk
    off = (np.arange(p) * n_vb * vb)[:, None]
    sp = np.append((src + off).reshape(-1), 0)
    dp = np.append((dst + off).reshape(-1), 0)
    perm = np.asarray(flat["perm"]).reshape(-1, eb)
    co, ci = np.asarray(flat["chunk_out"]), np.asarray(flat["chunk_in"])
    chunk_src = co if to == "src" else ci
    chunk_dst = co if to == "dst" else ci
    oob = perm >= e
    cs = np.where(oob, vb, sp[perm] - (chunk_src * vb)[:, None])
    cd = np.where(oob, vb, dp[perm] - (chunk_dst * vb)[:, None])
    rows = np.asarray(flat["rows"])
    assert rows.shape == (p * n_chunks, 1, eb)
    shift = triplet_mod.ROW_SHIFT
    np.testing.assert_array_equal(rows[:, 0] >> shift,
                                  cs if to == "src" else cd)
    np.testing.assert_array_equal(rows[:, 0] & ((1 << shift) - 1),
                                  cd if to == "src" else cs)
    assert oob.any(axis=1).all()            # every chunk is part padding
    if p == 4:                              # whole padding chunks
        assert oob.all(axis=1).sum() >= n_chunks + 1
        assert (rows[oob.all(axis=1)] == vb << shift | vb).all()


def _sweep_msg(use_src, use_dst, payload):
    """A message that reads exactly the sides the sweep streams."""
    def msg(sv, ev, dv):
        m = jnp.zeros((sv.shape[0], 1), jnp.float32)
        if use_src:
            m = m + sv[:, :1] * (ev[:, :1] if payload else 1.0)
        if use_dst:
            m = m + dv[:, 1:2] - (ev[:, 1:2] if payload else 0.0)
        return m
    return msg


@pytest.mark.parametrize("to", ["dst", "src"])
@pytest.mark.parametrize("reduce,use_src,use_dst,stage", [
    ("sum", True, False, "f32"),          # PageRank's shape
    ("sum", True, True, "f32"),
    ("min", True, False, "no_payload"),   # WCC's shape
    ("max", False, True, "f32"),
    ("min", True, True, "no_payload"),
    ("sum", True, True, "bf16"),
    ("max", True, True, "xscale"),
    ("sum", False, True, "xscale"),
])
def test_triplet_sweep_matches_oracle_bit_for_bit(to, reduce, use_src,
                                                  use_dst, stage):
    """The sweep on static slot rows and one dynamic gather, in interpret
    mode, against the oracle: every reduce, every endpoint combination, no
    edge payload, a bf16 mirror and a narrow-resident mirror with its scale
    plane; results, counts and live chunks equal bit for bit."""
    p, e_blk, v_mir, dx = 3, 120, 96, 2
    eb = vb = 32
    rng = np.random.default_rng(17)
    src = rng.integers(0, v_mir, (p, e_blk)).astype(np.int32)
    dst = rng.integers(0, v_mir, (p, e_blk)).astype(np.int32)
    mask = rng.random((p, e_blk)) > 0.2
    mask[1] = False                        # a partition with no edge
    mask[2, 20:] = False                   # and one ending in padding
    live = mask & (rng.random((p, e_blk)) > 0.3)
    out_s, in_s = (dst, src) if to == "dst" else (src, dst)
    tiles = triplet_mod.build_triplet_tiles(out_s, in_s, mask, v_mir,
                                            eb=eb, vb=vb)
    flat = triplet_mod.flatten_tiles(tiles, e_blk=e_blk, n_vb=v_mir // vb)
    off = (np.arange(p) * v_mir)[:, None]
    fsrc = jnp.asarray((src + off).reshape(-1))
    fdst = jnp.asarray((dst + off).reshape(-1))
    s = p * v_mir
    x = rng.integers(-4, 5, (s, dx)).astype(np.float32)
    xscale = None
    if stage == "bf16":
        x = jnp.asarray(x, jnp.bfloat16)
    elif stage == "xscale":
        x = jnp.asarray(x, jnp.int8)
        xscale = jnp.asarray(rng.integers(-2, 3, (s // 32, dx)), jnp.int8)
    payload = stage != "no_payload"
    ev = rng.integers(1, 4, (p * e_blk, 2 if payload else 0))
    ev, x = jnp.asarray(ev, jnp.float32), jnp.asarray(x)
    lv = jnp.asarray(live.reshape(-1))
    fn = _sweep_msg(use_src, use_dst, payload)
    got, cnt, chunks_live = triplet_mod.fused_triplet(
        x, ev, lv, flat, fn, s, 1, xscale=xscale, to=to, reduce=reduce,
        use_src=use_src, use_dst=use_dst, eb=eb, vb=vb, interpret=True)
    want, cnt_want = ref.fused_triplet(x, ev, fsrc, fdst, lv, fn, s,
                                       xscale=xscale, to=to, reduce=reduce)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    perm = np.asarray(flat["perm"]).reshape(-1, eb)
    lp = np.append(live.reshape(-1), False)
    assert int(chunks_live) == int(lp[perm].any(axis=1).sum()) > 0


def _build_engine_graph(seed=0, p=4, scale=6, ef=4, payload_dim=0):
    from repro.core import Graph
    from repro.data import rmat
    g = rmat(scale, ef, seed=seed)
    n = g.num_vertices
    rng = np.random.default_rng(seed)
    vids = np.arange(n, dtype=np.int64)
    vvals = {"x": (vids % 17 + 1).astype(np.float32)}
    dflt = {"x": np.float32(0)}
    if payload_dim:
        vvals["vec"] = rng.integers(-3, 4, (n, payload_dim)).astype(np.float32)
        dflt["vec"] = np.zeros(payload_dim, np.float32)
    return Graph.from_edges(
        g.src, g.dst,
        edge_values={"w": (np.arange(g.num_edges) % 5 + 1).astype(np.float32)},
        vertex_keys=vids, vertex_values=vvals, default_vertex=dflt,
        num_partitions=p), g


_NEED_FNS = {
    "src":  lambda sv, ev, dv: {"m": sv["x"] * ev["w"]},
    "dst":  lambda sv, ev, dv: {"m": dv["x"] + ev["w"]},
    "both": lambda sv, ev, dv: {"m": sv["x"] * ev["w"] + dv["x"]},
    "none": lambda sv, ev, dv: {"m": jnp.float32(1.0)},
}


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
@pytest.mark.parametrize("need", ["src", "dst", "both", "none"])
def test_fused_engine_matches_unfused(reduce, need):
    """The tentpole differential: the fused physical plan must be a pure
    execution-strategy change.  Integer-valued f32 payloads -> bit-for-bit."""
    from repro.core.mrtriplets import mr_triplets
    gr, _ = _build_engine_graph()
    f = _NEED_FNS[need]
    a, ea, _, ma = mr_triplets(gr, f, reduce, kernel_mode="unfused")
    b, eb_, _, mb = mr_triplets(gr, f, reduce, kernel_mode="ref")
    assert ma["plan"] == "unfused" and mb["plan"] == "fused"
    assert bool(jnp.all(ea == eb_))
    mask = np.asarray(ea)
    np.testing.assert_array_equal(np.asarray(a["m"])[mask],
                                  np.asarray(b["m"])[mask])


def _div_msg(sv, ev, dv):
    """PageRank-shaped message: divides by a gathered value.  On dead/padded
    edge rows the gather yields zeros, so this produces 0/0 = NaN there —
    the kernel must mask by substitution, not by multiplying the one-hot."""
    return {"m": sv["x"] / jnp.maximum(sv["x"], 0.0) * ev["w"]}


@pytest.mark.parametrize("reduce,need", [("sum", "both"), ("min", "src"),
                                         ("max", "dst"), ("sum", "div")])
def test_fused_engine_interpret_matches_unfused(reduce, need):
    """Same sweep through the actual Pallas kernel (interpret mode).  The
    'div' case produces NaN on zero-gathered dead rows (PageRank's pr/deg
    shape) and guards the substitution masking in the kernel."""
    from repro.core.mrtriplets import mr_triplets
    gr, _ = _build_engine_graph(scale=5, ef=3)
    f = _div_msg if need == "div" else _NEED_FNS[need]
    a, ea, _, _ = mr_triplets(gr, f, reduce, kernel_mode="unfused")
    c, ec, _, mc = mr_triplets(gr, f, reduce, kernel_mode="interpret")
    assert mc["plan"] == "fused"
    assert bool(jnp.all(ea == ec))
    mask = np.asarray(ea)
    got = np.asarray(c["m"])[mask]
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(np.asarray(a["m"])[mask], got)


def test_fused_engine_vector_payload_to_src():
    """Vector messages aggregate toward the SOURCE side, fused vs unfused."""
    from repro.core.mrtriplets import mr_triplets
    gr, _ = _build_engine_graph(payload_dim=4)
    f = lambda sv, ev, dv: {"m": sv["vec"] * ev["w"] + dv["vec"]}
    a, ea, _, _ = mr_triplets(gr, f, "sum", to="src", kernel_mode="unfused")
    b, eb_, _, mb = mr_triplets(gr, f, "sum", to="src", kernel_mode="ref")
    assert mb["plan"] == "fused"
    assert bool(jnp.all(ea == eb_))
    mask = np.asarray(ea)
    np.testing.assert_array_equal(np.asarray(a["m"])[mask],
                                  np.asarray(b["m"])[mask])


@pytest.mark.parametrize("skip_stale", ["out", "in", "both"])
def test_fused_skip_stale_matches_unfused(skip_stale):
    """skipStale masks per-edge live bits identically under both plans: the
    fused kernel's chunk skip is an optimisation, not a semantics change."""
    from repro.core.mrtriplets import mr_triplets
    gr, _ = _build_engine_graph()
    f = _NEED_FNS["src"]
    _, _, cache, _ = mr_triplets(gr, f, "sum", kernel_mode="ref")
    changed = (gr.s.home_vid % 5 == 0) & gr.vmask
    g2 = gr.replace(
        vdata={"x": jnp.where(changed, gr.vdata["x"] + 2.0, gr.vdata["x"])},
        active=changed)
    a, ea, _, ma = mr_triplets(g2, f, "sum", cache=cache,
                               skip_stale=skip_stale, kernel_mode="unfused")
    b, eb_, _, mb = mr_triplets(g2, f, "sum", cache=cache,
                                skip_stale=skip_stale, kernel_mode="ref")
    assert int(ma["live_edges"]) == int(mb["live_edges"])
    assert bool(jnp.all(ea == eb_))
    mask = np.asarray(ea)
    np.testing.assert_array_equal(np.asarray(a["m"])[mask],
                                  np.asarray(b["m"])[mask])


def test_fused_bf16_wire_within_tolerance():
    """bf16 wire dtype: fused upcasts the packed view to f32 before the map,
    the unfused path computes in bf16 — results agree within bf16 tolerance."""
    from repro.core import with_wire
    from repro.core.mrtriplets import mr_triplets
    gr, _ = _build_engine_graph()
    gr16 = gr.replace(ex=with_wire(gr.ex, "bf16"))
    f = _NEED_FNS["both"]
    a, ea, _, _ = mr_triplets(gr16, f, "sum", kernel_mode="unfused")
    b, eb_, _, mb = mr_triplets(gr16, f, "sum", kernel_mode="ref")
    assert mb["plan"] == "fused"
    assert bool(jnp.all(ea == eb_))
    mask = np.asarray(ea)
    np.testing.assert_allclose(np.asarray(a["m"], np.float32)[mask],
                               np.asarray(b["m"], np.float32)[mask],
                               rtol=2e-2, atol=1e-1)


def test_fused_bf16_payload_min_keeps_finite_identity():
    """Narrow (bf16) message dtype with min/max reduce: empty slots must hold
    the finite finfo(bf16) identity under BOTH plans — never inf from casting
    the kernel's f32 identity down."""
    from repro.core.mrtriplets import mr_triplets
    gr, _ = _build_engine_graph(scale=5, ef=3)
    gr = gr.mapV(lambda vid, v: {"x": v["x"].astype(jnp.bfloat16)})
    f = lambda sv, ev, dv: {"m": sv["x"]}
    for reduce in ("min", "max"):
        a, ea, _, _ = mr_triplets(gr, f, reduce, kernel_mode="unfused")
        b, eb_, _, mb = mr_triplets(gr, f, reduce, kernel_mode="ref")
        assert mb["plan"] == "fused"
        assert bool(jnp.all(ea == eb_))
        assert np.isfinite(np.asarray(b["m"], np.float32)).all()
        np.testing.assert_allclose(np.asarray(a["m"], np.float32),
                                   np.asarray(b["m"], np.float32),
                                   rtol=2e-2, atol=1e-1)


def test_fused_tile_fn_and_kernel_cache_reuse():
    """Repeated eager mrTriplets with the same UDF must reuse one compiled
    fused kernel (tile_fn is memoised; it is a static jit argument)."""
    from repro.core.mrtriplets import mr_triplets
    from repro.kernels.triplet import fused_triplet
    gr, _ = _build_engine_graph(scale=5, ef=3)
    f = _NEED_FNS["src"]
    before = fused_triplet._cache_size()
    for _ in range(3):
        mr_triplets(gr, f, "sum", kernel_mode="interpret")
    assert fused_triplet._cache_size() <= before + 1


def _build_int_graph(seed=3, p=4, scale=5, ef=3, dtype=np.int32,
                     extra_vid=None):
    from repro.core import Graph
    from repro.data import rmat
    g = rmat(scale, ef, seed=seed)
    vids = np.arange(g.num_vertices, dtype=np.int64)
    if extra_vid is not None:   # widen the id space past the staging bound
        vids = np.concatenate([vids, [extra_vid]])
    return Graph.from_edges(
        g.src, g.dst, vertex_keys=vids,
        vertex_values={"label": (vids % 7).astype(dtype)},
        default_vertex={"label": dtype(0)}, num_partitions=p)


@pytest.mark.parametrize("reduce", ["min", "max"])
def test_fused_engine_int32_payload(reduce):
    """int32 payloads ride the kernel via exact f32 staging (the CC
    min-label shape): fused vs unfused agree bit-for-bit and the output
    keeps the integer dtype."""
    from repro.core.mrtriplets import mr_triplets
    gr = _build_int_graph()
    f = lambda sv, ev, dv: {"m": sv["label"]}
    a, ea, _, ma = mr_triplets(gr, f, reduce, kernel_mode="unfused")
    b, eb_, _, mb = mr_triplets(gr, f, reduce, kernel_mode="ref")
    c, ec, _, mc = mr_triplets(gr, f, reduce, kernel_mode="interpret")
    assert ma["plan"] == "unfused" and mb["plan"] == "fused" \
        and mc["plan"] == "fused"
    assert b["m"].dtype == jnp.asarray(a["m"]).dtype == gr.vdata["label"].dtype
    assert bool(jnp.all(ea == eb_)) and bool(jnp.all(ea == ec))
    mask = np.asarray(ea)
    np.testing.assert_array_equal(np.asarray(a["m"])[mask],
                                  np.asarray(b["m"])[mask])
    np.testing.assert_array_equal(np.asarray(a["m"])[mask],
                                  np.asarray(c["m"])[mask])


@pytest.mark.parametrize("reduce", ["sum", "min"])
def test_fused_engine_multi_leaf_message(reduce):
    """Multi-leaf messages column-pack into one kernel matrix and split back
    exactly (per-leaf widths/dtypes)."""
    from repro.core.mrtriplets import mr_triplets
    gr, _ = _build_engine_graph(scale=5, ef=3, payload_dim=3)
    f = lambda sv, ev, dv: {"a": sv["x"] * ev["w"], "b": dv["vec"],
                            "c": sv["x"] + dv["x"]}
    a, ea, _, ma = mr_triplets(gr, f, reduce, kernel_mode="unfused")
    c, ec, _, mc = mr_triplets(gr, f, reduce, kernel_mode="interpret")
    assert ma["plan"] == "unfused" and mc["plan"] == "fused"
    assert bool(jnp.all(ea == ec))
    mask = np.asarray(ea)
    for k in ("a", "b", "c"):
        np.testing.assert_array_equal(np.asarray(a[k])[mask],
                                      np.asarray(c[k])[mask])


def test_fused_engine_mixed_int_float_leaves():
    """A message mixing an int32 leaf with a float leaf fuses for min/max
    and splits back into per-leaf dtypes."""
    from repro.core.mrtriplets import mr_triplets
    gr = _build_int_graph()
    gr = gr.mapV(lambda vid, v: {**v, "x": v["label"].astype(jnp.float32)
                                 * 1.5})
    f = lambda sv, ev, dv: {"lab": sv["label"], "x": sv["x"]}
    a, ea, _, ma = mr_triplets(gr, f, "min", kernel_mode="unfused")
    c, ec, _, mc = mr_triplets(gr, f, "min", kernel_mode="interpret")
    assert ma["plan"] == "unfused" and mc["plan"] == "fused"
    assert c["lab"].dtype == jnp.int32 and c["x"].dtype == jnp.float32
    mask = np.asarray(ea)
    np.testing.assert_array_equal(np.asarray(a["lab"])[mask],
                                  np.asarray(c["lab"])[mask])
    np.testing.assert_array_equal(np.asarray(a["x"])[mask],
                                  np.asarray(c["x"])[mask])


def test_fused_fallback_on_ineligible_payloads():
    """Shapes outside the staging guard stay unfused."""
    from repro.core.mrtriplets import mr_triplets
    gr = _build_int_graph()
    # int MESSAGE with sum reduce -> unfused (f32-staged sums can escape
    # the 24-bit mantissa even when every addend fits it)
    _, _, _, m1 = mr_triplets(gr, lambda sv, ev, dv: {"m": sv["label"]},
                              "sum", kernel_mode="auto")
    assert m1["plan"] == "unfused"
    # ...but an int INPUT feeding a float message sums fused (staging of
    # the id-bounded inputs is exact; the sum itself runs in f32 either way)
    _, _, _, m1b = mr_triplets(
        gr, lambda sv, ev, dv: {"m": sv["label"].astype(jnp.float32)},
        "sum", kernel_mode="auto")
    assert m1b["plan"] == "fused"
    # unsigned 32-bit payloads are bit patterns (triangle bitsets): unfused
    gru = _build_int_graph(dtype=np.uint32)
    _, _, _, m2 = mr_triplets(gru, lambda sv, ev, dv: {"m": sv["label"]},
                              "min", kernel_mode="auto")
    assert m2["plan"] == "unfused"
    # id space past the f32 mantissa bound -> int32 staging not exact
    grbig = _build_int_graph(extra_vid=(1 << 25))
    _, _, _, m3 = mr_triplets(grbig, lambda sv, ev, dv: {"m": sv["label"]},
                              "min", kernel_mode="auto")
    assert m3["plan"] == "unfused"
    # rank-2 message leaf -> unfused
    gr2, _ = _build_engine_graph(scale=5, ef=3)
    _, _, _, m4 = mr_triplets(
        gr2, lambda sv, ev, dv: {"m": jnp.zeros((2, 2)) + sv["x"]},
        "sum", kernel_mode="auto")
    assert m4["plan"] == "unfused"
    # min/max widths within the segmented-scan cap now fuse (the old
    # per-column VMEM unroll and its 16-wide limit are gone)...
    gr3, _ = _build_engine_graph(scale=5, ef=3, payload_dim=32)
    f3 = lambda sv, ev, dv: {"m": sv["vec"]}
    _, _, _, m5 = mr_triplets(gr3, f3, "min", kernel_mode="auto")
    assert m5["plan"] == "fused"
    # ...but past FUSED_MINMAX_MAX_WIDTH the scan's [Eb, Dm] VMEM working
    # set stops paying for itself -> unfused
    from repro.core.mrtriplets import FUSED_MINMAX_MAX_WIDTH
    gr4, _ = _build_engine_graph(scale=5, ef=3,
                                 payload_dim=FUSED_MINMAX_MAX_WIDTH + 8)
    _, _, _, m5w = mr_triplets(gr4, f3, "min", kernel_mode="auto")
    assert m5w["plan"] == "unfused"
    _, _, _, m6 = mr_triplets(gr4, f3, "sum", kernel_mode="auto")
    assert m6["plan"] == "fused"    # sum path has no width cap


# ---------------------------------------------------------------- segment_sum
@pytest.mark.parametrize("e,v,d", [(100, 30, 1), (1000, 300, 16),
                                   (513, 128, 8), (8, 4, 4),
                                   pytest.param(2048, 64, 128,
                                                marks=pytest.mark.slow)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_segment_sum_sweep(e, v, d, dtype):
    ids = np.sort(RNG.integers(0, v, e)).astype(np.int32)
    msgs = RNG.normal(size=(e, d)).astype(dtype)
    out = segment_sum(jnp.asarray(msgs), jnp.asarray(ids), v,
                      edge_block=128, vertex_block=128, interpret=True)
    want = ref.segment_sum(jnp.asarray(msgs), jnp.asarray(ids), v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_segment_sum_unsorted_and_oob():
    # unsorted ids + padding ids >= V must be dropped, not crash
    ids = RNG.permutation(np.concatenate(
        [RNG.integers(0, 20, 50), np.full(14, 99)])).astype(np.int32)
    msgs = RNG.normal(size=(64, 4)).astype(np.float32)
    out = segment_sum(jnp.asarray(msgs), jnp.asarray(ids), 20,
                      edge_block=16, vertex_block=16, interpret=True)
    want = ref.segment_sum(jnp.asarray(msgs), jnp.asarray(ids), 20)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


def test_segment_sum_empty_segments():
    ids = np.full(32, 7, np.int32)
    msgs = np.ones((32, 2), np.float32)
    out = segment_sum(jnp.asarray(msgs), jnp.asarray(ids), 16,
                      edge_block=8, vertex_block=8, interpret=True)
    assert float(out[7, 0]) == 32.0
    assert float(np.abs(np.asarray(out)).sum()) == 64.0


# ------------------------------------------------------- linear message sweep
def _linear_msg(sv, evv, dv):
    """msg = w · x[src]: PageRank's message, summed to dst."""
    return sv * evv[:, :1]


def _linear_sweep(x, w, src, dst, mask, live, v, *, eb, vb):
    tiles = _flat_tiles(dst, src, mask, v, eb=eb, vb=vb)
    out, _, _ = triplet_mod.fused_triplet(
        jnp.asarray(x), jnp.asarray(w)[:, None], jnp.asarray(live), tiles,
        _linear_msg, v, x.shape[1], to="dst", reduce="sum", use_dst=False,
        eb=eb, vb=vb, interpret=True)
    return np.asarray(out)


def _linear_reference(x, w, src, dst, v):
    want = np.zeros((v, x.shape[1]), np.float32)
    np.add.at(want, dst, w[:, None] * x[src])
    return want


@pytest.mark.parametrize("e,v,d,eb,vb", [
    (500, 100, 1, 128, 64), (2000, 500, 8, 256, 128), (64, 16, 4, 32, 16)])
def test_linear_message_sweep(e, v, d, eb, vb):
    """The fused sweep with a linear message is SpMV over the edge set."""
    src = RNG.integers(0, v, e).astype(np.int32)
    dst = RNG.integers(0, v, e).astype(np.int32)
    mask = RNG.random(e) > 0.15
    w = (RNG.normal(size=e) * mask).astype(np.float32)
    x = RNG.normal(size=(v, d)).astype(np.float32)
    out = _linear_sweep(x, w, src, dst, mask, np.ones(e, bool), v,
                        eb=eb, vb=vb)
    np.testing.assert_allclose(out, _linear_reference(x, w, src, dst, v),
                               rtol=1e-4, atol=1e-4)


def test_linear_message_stale_source_blocks():
    """skipStale at block level: edges out of stale source blocks are dead
    and contribute nothing."""
    v, e, vb = 128, 400, 32
    src = RNG.integers(0, v, e).astype(np.int32)
    dst = RNG.integers(0, v, e).astype(np.int32)
    w = np.ones(e, np.float32)
    x = RNG.normal(size=(v, 2)).astype(np.float32)
    active = np.zeros(-(-v // vb), bool)
    active[0] = True   # only sources in block 0 are fresh
    out = _linear_sweep(x, w, src, dst, np.ones(e, bool), active[src // vb],
                        v, eb=64, vb=vb)
    want = _linear_reference(x, w * (src < vb), src, dst, v)
    np.testing.assert_allclose(out, want, rtol=1e-4)


# ------------------------------------------------------------------ flash
@pytest.mark.parametrize("b,hq,hkv,lq,lk,dh,causal,off", [
    (2, 4, 2, 64, 64, 32, True, 0),
    (1, 8, 1, 100, 100, 64, True, 0),
    (1, 4, 4, 1, 300, 32, True, 299),
    (2, 2, 2, 48, 96, 16, True, 48),
    (1, 2, 1, 64, 64, 32, False, 0),
    (1, 2, 2, 40, 72, 128, False, 0),
])
@pytest.mark.slow
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_sweep(b, hq, hkv, lq, lk, dh, causal, off, dtype):
    q = RNG.normal(size=(b, hq, lq, dh)).astype(dtype)
    k = RNG.normal(size=(b, hkv, lk, dh)).astype(dtype)
    v = RNG.normal(size=(b, hkv, lk, dh)).astype(dtype)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, kv_offset=off,
                          block_q=32, block_kv=32, interpret=True)
    want = ref.flash_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=causal, kv_offset=off)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.slow
def test_flash_block_sizes_agree():
    q = RNG.normal(size=(1, 2, 128, 32)).astype(np.float32)
    k = RNG.normal(size=(1, 2, 128, 32)).astype(np.float32)
    v = RNG.normal(size=(1, 2, 128, 32)).astype(np.float32)
    outs = [np.asarray(flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
        block_q=bq, block_kv=bk, interpret=True))
        for bq, bk in ((16, 16), (32, 64), (128, 128))]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- chunked (jnp flash)
@pytest.mark.parametrize("b,hq,hkv,lq,lk,dh,causal,off", [
    (2, 4, 2, 64, 64, 32, True, 0),
    (1, 8, 1, 100, 300, 64, True, 200),
    (1, 2, 2, 48, 96, 16, False, 0),
    (2, 2, 1, 1, 257, 32, True, 256),
])
@pytest.mark.slow
def test_chunked_flash_matches_dense(b, hq, hkv, lq, lk, dh, causal, off):
    q = RNG.normal(size=(b, hq, lq, dh)).astype(np.float32)
    k = RNG.normal(size=(b, hkv, lk, dh)).astype(np.float32)
    v = RNG.normal(size=(b, hkv, lk, dh)).astype(np.float32)
    got = ref.flash_attention_chunked(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), causal=causal,
                                      kv_offset=off, block_kv=32)
    want = ref.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                               causal=causal, kv_offset=off)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------- mLSTM
@pytest.mark.parametrize("b,h,l,dh,chunk", [
    (1, 2, 64, 16, 16),
    (2, 1, 128, 32, 32),
    (1, 4, 96, 8, 48),
    (2, 2, 32, 64, 32),     # single chunk
])
@pytest.mark.slow
def test_mlstm_kernel_matches_ref(b, h, l, dh, chunk):
    from repro.kernels.mlstm import mlstm_chunked as kern
    q = RNG.normal(size=(b, h, l, dh)).astype(np.float32) * 0.5
    k = RNG.normal(size=(b, h, l, dh)).astype(np.float32) * 0.5
    v = RNG.normal(size=(b, h, l, dh)).astype(np.float32)
    logi = np.clip(RNG.normal(size=(b, h, l)), -8, 4).astype(np.float32)
    logf = (-np.abs(RNG.normal(size=(b, h, l))) * 0.2).astype(np.float32)
    got = kern(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
               jnp.asarray(logi), jnp.asarray(logf), chunk=chunk,
               interpret=True)
    want = ref.mlstm_chunked(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             jnp.asarray(logi), jnp.asarray(logf),
                             chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_mlstm_kernel_chunk_sizes_agree():
    from repro.kernels.mlstm import mlstm_chunked as kern
    b, h, l, dh = 1, 2, 128, 16
    q = RNG.normal(size=(b, h, l, dh)).astype(np.float32) * 0.3
    k = RNG.normal(size=(b, h, l, dh)).astype(np.float32) * 0.3
    v = RNG.normal(size=(b, h, l, dh)).astype(np.float32)
    logi = np.zeros((b, h, l), np.float32)
    logf = np.full((b, h, l), -0.1, np.float32)
    outs = [np.asarray(kern(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            jnp.asarray(logi), jnp.asarray(logf),
                            chunk=c, interpret=True)) for c in (16, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("codec", ["int8", "fp8_e4m3"])
@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_fused_encoded_staging_matches_decode_fallback(mode, codec):
    """§2.4 narrow-resident staging differential: when every used mirror
    leaf is a ResidentLeaf the fused sweep streams the NARROW payload plus
    its scale plane and dequantizes per tile (an exact exponent shift);
    the unfused path decodes the same mirror on read.  Both consume
    identical quantized values, so the two plans are bit-for-bit — the
    dequant itself is part of the differential (a missing scale plane
    shows up as pow2-scaled garbage, not tolerance noise)."""
    from repro.core import with_wire
    from repro.core import wire as wire_mod
    from repro.core.mrtriplets import mr_triplets
    gr, _ = _build_engine_graph()
    g8 = gr.replace(ex=with_wire(gr.ex, codec, resident=True))
    f = _NEED_FNS["both"]
    a, ea, va, ma = mr_triplets(g8, f, "sum", kernel_mode="unfused")
    b, eb_, vb_, mb = mr_triplets(g8, f, "sum", kernel_mode=mode)
    assert ma["plan"] == "unfused" and mb["plan"] == "fused"
    # the warm mirror really is encoded (kind "scaled" for the f32 leaf)
    enc = [l for l in jax.tree.leaves(vb_.mirror,
                                      is_leaf=wire_mod.is_resident)
           if wire_mod.is_resident(l)]
    assert enc and all(l.kind == "scaled" for l in enc)
    assert bool(jnp.all(ea == eb_))
    mask = np.asarray(ea)
    np.testing.assert_array_equal(np.asarray(a["m"])[mask],
                                  np.asarray(b["m"])[mask])


def test_fused_resident_int_kind_rides_with_zero_exponents(    ):
    """"int"-kind resident leaves (bounded int32 -> int8 cast) share the
    encoded staging matrix with zero exponents — exp2(0) == 1 and the
    payload upcasts exactly, so fused == unfused bit-for-bit."""
    from repro.core import with_wire
    from repro.core.mrtriplets import mr_triplets
    gr, _ = _build_engine_graph()
    g = gr.mapV(lambda vid, v: {"c": (vid % 50).astype(jnp.int32)})
    g8 = g.replace(ex=with_wire(g.ex, "int8", resident=True))
    f = lambda sv, ev, dv: {"m": sv["c"]}
    a, ea, _, _ = mr_triplets(g8, f, "max", kernel_mode="unfused",
                              payload_bound=50)
    b, eb_, _, mb = mr_triplets(g8, f, "max", kernel_mode="ref",
                                payload_bound=50)
    assert mb["plan"] == "fused"
    assert bool(jnp.all(ea == eb_))
    mask = np.asarray(ea)
    np.testing.assert_array_equal(np.asarray(a["m"])[mask],
                                  np.asarray(b["m"])[mask])

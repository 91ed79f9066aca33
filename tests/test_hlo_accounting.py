"""Unit tests for the trip-count-corrected HLO accounting (utils/hlo.py) —
the functions the roofline's honesty depends on."""
import numpy as np
import pytest

from repro.utils import hlo


def _lower_text(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dot_flops_simple_matmul():
    import jax.numpy as jnp
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 256), jnp.float32)
    txt = _lower_text(lambda x, y: x @ y, a, b)
    got = hlo.dot_flops(txt)["dot_flops"]
    want = 2 * 64 * 256 * 128
    assert got == pytest.approx(want, rel=0.01), (got, want)


def test_dot_flops_counts_scan_trip_count():
    """The raw cost model counts a While body once; ours multiplies by the
    known trip count."""
    import jax
    import jax.numpy as jnp
    w = jnp.ones((8, 32, 32), jnp.float32)   # 8 layers

    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        out, _ = jax.lax.scan(body, x, w)
        return out

    txt = _lower_text(f, jnp.ones((16, 32), jnp.float32), w)
    got = hlo.dot_flops(txt)["dot_flops"]
    want = 8 * 2 * 16 * 32 * 32
    assert got == pytest.approx(want, rel=0.05), (got, want)


def test_bytes_accessed_scan_dus_counted_at_slice_size():
    """Scan-carried stacked outputs must not count the whole buffer per
    iteration (XLA aliases dynamic-update-slice in place)."""
    import jax
    import jax.numpy as jnp

    def f(x):
        def body(c, _):
            c = c * 1.5
            return c, c
        _, ys = jax.lax.scan(body, x, None, length=1000)
        return ys

    txt = _lower_text(f, jnp.ones((128,), jnp.float32))
    got = hlo.bytes_accessed(txt)
    # real traffic ~ 1000 iters x (read 512B + write 512B + write slice 512B)
    # with fusion overhead; the broken estimator would charge
    # 1000 x 512KB (the whole [1000,128] buffer) ~ 5e8
    assert got < 5e7, got


def test_collective_bytes_empty_for_local_program():
    import jax.numpy as jnp
    txt = _lower_text(lambda x: x * 2, jnp.ones((16,), jnp.float32))
    assert hlo.collective_bytes(txt)["total_bytes"] == 0


def _perm_gathers(hlo_text: str, n_idx: int) -> int:
    """Gathers in an HLO module that read `n_idx` indices: the product of
    the output dimensions that are not the gathered slice's own."""
    import re
    count = 0
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* gather\([^)]*\), "
                         r"offset_dims=\{([\d,]*)\}", hlo_text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        offset = {int(d) for d in m.group(2).split(",") if d}
        batch = [d for i, d in enumerate(dims) if i not in offset]
        count += int(np.prod(batch)) == n_idx
    return count


@pytest.mark.parametrize("payload", [1, 0])
def test_fused_sweep_gathers_through_perm_once(payload):
    """The fused triplet sweep makes one gather of n_chunks·eb indices, of
    the live bits and the edge payload, or of the live bits alone where the
    payload has no column; its slot rows come from the tile tables."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import triplet

    p, e_blk, v_mir, eb, vb = 2, 300, 64, 32, 16
    rng = np.random.default_rng(0)
    tiles = triplet.build_triplet_tiles(
        rng.integers(0, v_mir, (p, e_blk)), rng.integers(0, v_mir, (p, e_blk)),
        np.ones((p, e_blk), bool), v_mir, eb=eb, vb=vb)
    flat = triplet.flatten_tiles(tiles, e_blk=e_blk, n_vb=v_mir // vb)
    n_idx = flat["perm"].size
    assert n_idx == flat["chunk_out"].size * eb > eb
    e = p * e_blk

    def sweep(x, ev, live, t):
        return triplet.fused_triplet(
            x, ev, live, t, lambda sv, ev, dv: sv[:, :1], p * v_mir, 1,
            use_dst=False, eb=eb, vb=vb, interpret=True)

    txt = jax.jit(sweep).lower(
        jnp.ones((p * v_mir, 2)), jnp.ones((e, payload)),
        jnp.ones((e,), bool), flat).as_text(dialect="hlo")
    assert _perm_gathers(txt, n_idx) == 1

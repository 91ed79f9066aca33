"""Ahead-of-time compiles of the main path's Pallas kernels for TPU v5e.

Interpret mode runs any block layout; the chip's compiler refuses unaligned
tiles, unsupported relayouts and VMEM overuse.  These tests compile each
kernel of the Pregel main path for a described (not attached) v5e chip at
its real block sizes, eb = vb = 512, with chunk and slot counts taken from a
scale-16 Graph500 R-MAT build over 4 partitions.  Nothing runs; each compile
takes a second or two.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.

The persistent compile cache that the chip benchmark turns on
(`repro.utils.compile_cache`) is checked here too: where its directory goes.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import segment_sum, superstep, triplet
from repro.utils import compile_cache

EB = VB = 512
P = 4
# scale-16 R-MAT (edge factor 16, seed 0), Graph.from_edges(num_partitions=4)
E_BLK, V_MIR, V_BLK, K_DST = 249_264, 27_136, 11_752, 4_376
TRIPLET_CHUNKS, APPLY_CHUNKS = 2_913, 92
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back what it writes to the persistent
    # cache, so keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _specs(sharding):
    """shape, dtype -> an argument of the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _tiles(spec, n_chunks):
    return {"perm": spec((n_chunks * EB,), jnp.int32),
            "chunk_out": spec((n_chunks,), jnp.int32),
            "chunk_in": spec((n_chunks,), jnp.int32),
            "rows": spec((n_chunks, 1, EB), jnp.int32)}


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    return compiled


def _pagerank_msg(sv, ev, dv):          # pr / deg * w, source side only
    return sv[:, :1] / sv[:, 1:2] * ev[:, :1]


def _cc_msg(sv, ev, dv):                # the source's label
    return sv[:, :1]


def _both_msg(sv, ev, dv):              # reads both endpoints
    return (sv[:, :1] + dv[:, 1:2]) * ev[:, :1]


@pytest.mark.parametrize("case", ["sum", "min", "sum_int8_xscale"])
def test_fused_triplet_compiles_for_v5e(one_chip, case):
    spec = _specs(one_chip)
    seg = P * -(-V_MIR // VB) * VB
    e = P * E_BLK
    scaled = case.endswith("xscale")
    x = spec((seg, 2), jnp.int8 if scaled else jnp.float32)
    xscale = (spec((seg // triplet.SCALE_GROUP, 2), jnp.int8) if scaled
              else None)
    tile_fn = {"sum": _pagerank_msg, "min": _cc_msg,
               "sum_int8_xscale": _both_msg}[case]
    reduce = "min" if case == "min" else "sum"

    def fn(x, ev, live, tiles, xscale):
        return triplet.fused_triplet(
            x, ev, live, tiles, tile_fn, seg, 1, xscale=xscale,
            reduce=reduce, use_src=True, use_dst=scaled, eb=EB, vb=VB)

    _compile(fn, x, spec((e, 0 if case == "min" else 1), jnp.float32),
             spec((e,), jnp.bool_), _tiles(spec, P * TRIPLET_CHUNKS), xscale)


def _apply_fn(reduce):
    def apply_fn(vid, vmask, xv, acc, exists):
        msg = jnp.where(exists, acc, 0.0)
        if reduce == "sum":
            new = 0.15 + 0.85 * msg
        else:
            new = jnp.minimum(xv, jnp.where(exists, acc, xv))
        new = jnp.where(vmask > 0.0, new, xv)
        return new, jnp.logical_and(new != xv, vmask > 0.0).astype(
            jnp.float32)
    return apply_fn


@pytest.mark.parametrize("reduce", ["sum", "min"])
def test_fused_apply_compiles_for_v5e(one_chip, reduce):
    spec = _specs(one_chip)
    slots = P * -(-V_BLK // VB) * VB
    r = P * P * K_DST
    apply_fn = _apply_fn(reduce)

    def fn(pay, slot, live, tiles, x, vid, vmask):
        return superstep.fused_apply(pay, slot, live, tiles, x, vid, vmask,
                                     apply_fn, slots, 1, 1, reduce=reduce,
                                     eb=EB, vb=VB)

    _compile(fn, spec((r, 1), jnp.float32), spec((r,), jnp.int32),
             spec((r,), jnp.bool_), _tiles(spec, P * APPLY_CHUNKS),
             spec((slots, 1), jnp.float32), spec((slots,), jnp.int32),
             spec((slots,), jnp.bool_))


def test_segment_sum_compiles_for_v5e(one_chip):
    spec = _specs(one_chip)
    e = P * E_BLK
    num_segments = P * V_MIR

    def fn(msgs, ids):
        return segment_sum.segment_sum(msgs, ids, num_segments)

    _compile(fn, spec((e, 1), jnp.float32), spec((e,), jnp.int32))


def test_superstep_names_for_v5e(one_chip):
    """The whole jitted PageRank superstep, compiled for the chip, keeps the
    names a device trace is read by: the module is named after the
    superstep, the two Pallas kernels are custom calls named `fused_triplet`
    and `fused_apply`, and the chunk-stream gathers carry the
    `graphx.triplet_streams` scope in their op names."""
    import re

    import numpy as np
    from repro.core import Graph, algorithms
    from repro.core.pregel import superstep_jit
    from repro.core.transport import DENSE
    from repro.data import rmat

    gd = rmat(8, 8, seed=0)
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=P,
                         edge_values={"w": np.ones(gd.num_edges, np.float32)})
    g = algorithms.attach_out_degree(g)
    g = g.mapV(lambda vid, v: {**v, "pr": jnp.float32(1.0)})
    step = superstep_jit(
        lambda vid, v, msg: {**v, "pr": 0.15 + 0.85 * msg["m"]},
        lambda sv, ev, dv: {"m": sv["pr"] / sv["deg"] * ev["w"]}, "sum",
        default_msg={"m": jnp.float32(0.0)}, skip_stale=None,
        changed_fn=None, kernel_mode="pallas", incremental=True,
        payload_bound=None, fuse_apply="auto")
    spec = _specs(one_chip)
    text = step.lower(jax.tree.map(lambda x: spec(x.shape, x.dtype), g),
                      transport=DENSE).compile().as_text()
    assert text.startswith("HloModule jit_pregel_superstep,")
    calls = set(re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]* custom-call\(",
                           text))
    assert {"fused_triplet", "fused_apply"} <= calls
    ops = re.findall(r'op_name="([^"]*)"', text)
    assert any("/graphx.triplet_streams/" in o for o in ops)


def test_compile_cache_directory(monkeypatch, tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        default = os.path.join(root, ".jax_cache")
        assert compile_cache.enable_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

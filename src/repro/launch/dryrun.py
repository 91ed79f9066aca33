"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, and extract the roofline inputs.

For each cell we build ShapeDtypeStruct stand-ins (zero allocation), attach
NamedShardings from the logical-axis rules, lower the jitted step, compile,
and record:
  * memory_analysis()  — per-device bytes (does it fit 16 GB v5e HBM?)
  * cost_analysis()    — HLO FLOPs + bytes accessed
  * collective bytes   — parsed from the compiled SPMD HLO (utils/hlo.py)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-1.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--graph]
  PYTHONPATH=src python -m repro.launch.dryrun --graph          # GraphX engine cell

Results accumulate in reports/dryrun.json (one entry per cell x mesh).
"""
# The first two executable statements MUST precede any other import — jax
# locks the device count at first backend initialisation.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import functools
import json
import time
import traceback

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs as C
from ..configs.base import SHAPES, shape_applicable
from ..models import transformer as T
from ..models import layers as L
from ..sharding import rules
from ..train import optimizer as opt_mod
from ..utils import hlo as hlo_utils
from .mesh import make_production_mesh, make_graph_mesh, mesh_axis_sizes

REPORT_PATH = "reports/dryrun.json"


def _cost_dict(compiled) -> dict:
    """compiled.cost_analysis() across jax generations (<=0.4 returns
    [dict], newer returns the dict)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        return cost[0] if cost else {}
    return cost


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins — never allocated)
# ---------------------------------------------------------------------------
def _batch_axes(mesh, batch: int):
    from ..models import perf
    sizes = mesh_axis_sizes(mesh)
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    # perf knob: archs too small to use tensor parallelism (xlstm-350m:
    # replicated weights after the head-divisibility guard) hand the model
    # axis to data parallelism instead — full-mesh DP.
    if perf.get("dp_over_model") and "model" in sizes:
        full = dp_axes + ("model",)
        n = int(np.prod([sizes[a] for a in full]))
        if batch % n == 0 and batch >= n:
            return full
    dp = int(np.prod([sizes[a] for a in dp_axes])) if dp_axes else 1
    if batch % dp == 0 and batch >= dp:
        return dp_axes
    if "data" in sizes and batch % sizes["data"] == 0:
        return ("data",)
    return ()


def input_specs(cfg, shape, mesh) -> dict:
    """ShapeDtypeStructs for one cell's step inputs (weak-type-correct,
    shardable, no device allocation)."""
    b, s = shape.global_batch, shape.seq_len
    ba = _batch_axes(mesh, b)
    bspec = P(ba if ba else None, None)

    def sds(shp, dtype, spec):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, spec))

    out = {}
    if shape.kind == "train":
        out["tokens"] = sds((b, s), jnp.int32, bspec)
        out["labels"] = sds((b, s), jnp.int32, bspec)
    elif shape.kind == "prefill":
        out["tokens"] = sds((b, s), jnp.int32, bspec)
    else:  # decode: one new token against a seq_len cache
        out["tokens"] = sds((b, 1), jnp.int32, bspec)

    if cfg.n_context_tokens:
        n_ctx = (s // cfg.frontend_downsample if cfg.is_encdec
                 else cfg.n_context_tokens)
        if shape.kind == "decode" and cfg.is_encdec:
            n_ctx = min(n_ctx, 8192)  # decode: encoder output bounded
        out["context"] = sds((b, n_ctx, cfg.d_model), jnp.float32,
                             P(ba if ba else None, None, None))
    return out


def _named_tree(mesh, spec_tree, shape_tree):
    return jax.tree.map(
        lambda sds_, spec: jax.ShapeDtypeStruct(
            sds_.shape, sds_.dtype, sharding=NamedSharding(mesh, spec)),
        shape_tree, spec_tree)


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------
def lower_cell(arch: str, shape_name: str, mesh, *, strategy: str | None = None,
               kernel_mode: str = "ref", extra_tags: dict | None = None,
               return_hlo: bool = False, perf_opts: dict | None = None):
    cfg = C.get(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "status": "skipped",
               "reason": reason}
        return (rec, "") if return_hlo else rec

    strategy = strategy or rules.default_strategy(cfg)
    sizes = mesh_axis_sizes(mesh)

    from ..models import perf
    import contextlib

    def perf_ctx():   # fresh context per use (generator CMs are single-shot)
        return (perf.options(mesh=mesh, **perf_opts) if perf_opts
                else contextlib.nullcontext())

    # parameter structure + shardings (eval_shape: no allocation)
    p_struct = jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    p_vals_struct, axes_tree = L.split_params(p_struct)
    pspecs = rules.param_specs(axes_tree, p_vals_struct, strategy, sizes)
    p_sds = _named_tree(mesh, pspecs, p_vals_struct)

    with perf_ctx():
        batch_sds = input_specs(cfg, shape, mesh)

    t0 = time.time()
    if shape.kind == "train":
        ospecs = opt_specs = rules.opt_state_specs(pspecs, p_vals_struct,
                                                    strategy, sizes)
        o_struct = jax.eval_shape(opt_mod.init, p_vals_struct)
        o_sds = opt_mod.OptState(
            m=_named_tree(mesh, ospecs, o_struct.m),
            v=_named_tree(mesh, opt_specs, o_struct.v),
            step=jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=NamedSharding(mesh, P())))
        ocfg = opt_mod.AdamWConfig()

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                functools.partial(T.loss_fn, cfg=cfg, mode=kernel_mode))(
                    params, batch)
            params, opt_state, metrics = opt_mod.update(
                ocfg, params, grads, opt_state)
            return params, opt_state, {"loss": loss, **metrics}

        with perf_ctx():
            lowered = jax.jit(train_step, donate_argnums=(0, 1)).lower(
                p_sds, o_sds, batch_sds)

    elif shape.kind == "prefill":
        def prefill_step(params, batch):
            return T.forward(params, batch, cfg, mode=kernel_mode, remat=False)
        with perf_ctx():
            lowered = jax.jit(prefill_step).lower(p_sds, batch_sds)

    else:  # decode
        st_struct = jax.eval_shape(
            functools.partial(T.init_decode_state, cfg,
                              shape.global_batch, shape.seq_len))
        st_spec_fn = rules.decode_state_spec_fn(sizes)
        st_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=NamedSharding(mesh, st_spec_fn(x))), st_struct)
        ctx_sds = batch_sds.pop("context", None)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32,
                                       sharding=NamedSharding(mesh, P()))

        def serve_step(params, state, tokens, pos, ctx=None):
            return T.decode_step(params, state, tokens, pos, cfg,
                                 cross_ctx=ctx, mode=kernel_mode)

        args = (p_sds, st_sds, batch_sds["tokens"], pos_sds)
        with perf_ctx():
            if ctx_sds is not None:
                lowered = jax.jit(serve_step, donate_argnums=(1,)).lower(
                    *args, ctx_sds)
            else:
                lowered = jax.jit(serve_step, donate_argnums=(1,)).lower(*args)

    compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled)
    txt = compiled.as_text()
    coll = hlo_utils.collective_bytes(txt)
    # Trip-count-corrected terms (see utils/hlo.py): XLA cost_analysis counts
    # While bodies once; scan-over-layers models undercount by ~n_layers.
    dots = hlo_utils.dot_flops(txt)
    bytes_tc = hlo_utils.bytes_accessed(txt)

    n_chips = int(np.prod(mesh.devices.shape))
    rec = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "mesh_axes": list(mesh.axis_names),
        "n_chips": n_chips,
        "strategy": strategy,
        "kind": shape.kind,
        "compile_seconds": round(compile_s, 1),
        "flops_per_chip": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_chip": float(cost.get("bytes accessed", 0.0)),
        "flops_per_chip_tc": float(max(dots["dot_flops"],
                                       cost.get("flops", 0.0))),
        "dot_count_tc": float(dots["dot_count"]),
        "bytes_accessed_per_chip_tc": float(max(bytes_tc,
                                                cost.get("bytes accessed", 0.0))),
        "collective_bytes_per_chip": int(coll.get("total_bytes", 0)),
        "collectives": {k: v for k, v in coll.items() if k != "total_bytes"},
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "param_count": int(sum(np.prod(x.shape)
                               for x in jax.tree.leaves(p_vals_struct))),
    }
    if extra_tags:
        rec.update(extra_tags)
    return (rec, txt) if return_hlo else rec


# ---------------------------------------------------------------------------
# GraphX engine cell (the paper's own workload on the production mesh)
# ---------------------------------------------------------------------------
def _graph_cell_sds(mesh, *, n_vertices: int, n_edges: int,
                    mirror_factor: float, ex, contrib_form: bool = False):
    """ShapeDtypeStruct stand-ins for one Twitter-scale graph cell
    (structure sized by the 2D-cut replication model) — the ONE place the
    cell's spec lives, shared by lower_graph_cell and profile_ships so the
    two lanes always lower the same program shape."""
    from ..core import partition as pm
    from ..core.graph import Graph, StructArrays

    sizes = mesh_axis_sizes(mesh)
    p = sizes["parts"]
    spec = pm.structure_spec(n_vertices, n_edges, p,
                             mirror_factor=mirror_factor)
    e_blk, v_blk, v_mir, k = (spec["e_blk"], spec["v_blk"], spec["v_mir"],
                              spec["k_route"])

    def sds(shp, dtype, pspec):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, pspec))

    pp = P("parts")
    s = StructArrays(
        src_slot=sds((p, e_blk), jnp.int32, pp),
        dst_slot=sds((p, e_blk), jnp.int32, pp),
        src_perm=sds((p, e_blk), jnp.int32, pp),
        edge_mask=sds((p, e_blk), jnp.bool_, pp),
        mirror_vid=sds((p, v_mir), jnp.int32, pp),
        home_vid=sds((p, v_blk), jnp.int32, pp),
        home_mask=sds((p, v_blk), jnp.bool_, pp),
        routes={need: (sds((p, p, k), jnp.int32, pp),
                       sds((p, p, k), jnp.int32, pp))
                for need in ("src", "dst", "both")},
        p=p, e_blk=e_blk, v_mir=v_mir, v_blk=v_blk,
        num_vertices=n_vertices, num_edges=n_edges)
    vdata_sds = {"pr": sds((p, v_blk), jnp.float32, pp),
                 "deg": sds((p, v_blk), jnp.float32, pp)}
    if contrib_form:
        vdata_sds["contrib"] = sds((p, v_blk), jnp.float32, pp)
    g_sds = Graph(
        s=s,
        vdata=vdata_sds,
        edata={"w": sds((p, e_blk), jnp.float32, pp)},
        vmask=sds((p, v_blk), jnp.bool_, pp),
        emask=sds((p, e_blk), jnp.bool_, pp),
        active=sds((p, v_blk), jnp.bool_, pp),
        ex=ex, host=None)
    return g_sds, spec


def lower_graph_cell(mesh, *, n_vertices=41_652_230, n_edges=1_468_365_182,
                     supersteps: int = 1, return_hlo: bool = False,
                     wire: str | None = None,
                     wire_delta: bool = False, mirror_factor: float = 2.0,
                     contrib_form: bool = False,
                     transport: str | None = None,
                     capacity_frac: float = 0.25,
                     integrity: bool = False):
    """PageRank superstep on a Twitter-scale graph (paper Table 1), SPMD over
    the flat parts axis.  Structure arrays are ShapeDtypeStructs sized by the
    2D-cut replication model.

    wire: codec name ("f32"/"bf16"/"int8"/"fp8_e4m3"/"fp8_e5m2") for the
    mirror exchange (DESIGN.md §2.1); wire_delta enables active-set delta
    accounting.

    integrity (DESIGN.md §6): lower the cell with the per-route integrity
    word + retry/degrade ladder enabled, so the dry-run report prices the
    checked wire — the word itself (one int32 per route) plus the verify
    psum, and the lax.cond retry/degrade branches the checked program
    keeps in the HLO.

    transport (DESIGN.md §2.1.1): "dense" (default), "ragged", or "auto".
    "ragged" lowers the PURE compacted-collective program (overflow
    fallback disabled — this is shape analysis, the lax.cond would keep a
    dense branch in the HLO and double-count collective bytes), with the
    static capacity = capacity_frac of the route width; "auto" keeps the
    runtime cond, so the reported collective bytes cover BOTH branches.
    Ragged/auto cells run at least 2 supersteps so the second ships against
    a cache (the incremental path the ragged plan exists for)."""
    from ..core import transport as transport_mod
    from ..core.exchange import SpmdExchange, with_wire
    from ..core.pregel import _superstep

    tpol = None
    if transport is not None and transport != "dense":
        tpol = transport_mod.resolve_transport(transport)
        # an explicit --capacity-frac is the operator's certification: lift
        # the break-even clamp so the requested fraction really lowers the
        # ragged program (otherwise a frac >= ragged_max_frac would
        # silently lower dense under a ragged label).
        tpol = tpol.replace(capacity_frac=capacity_frac, cap_rounding=32,
                            ragged_max_frac=1.0)
        if tpol.kind == "ragged":
            tpol = tpol.replace(fallback=False)
        supersteps = max(supersteps, 2)
    if integrity:
        tpol = (tpol if tpol is not None
                else transport_mod.DENSE).replace(integrity=True)

    p = mesh_axis_sizes(mesh)["parts"]
    ex = SpmdExchange(p=p, axis_name="parts")
    if wire is not None:
        ex = with_wire(ex, wire, delta=wire_delta or None)
    # contrib_form is PowerGraph-style pre-aggregation: the message reads
    # ONE home-computed property, so property-level join elimination ships
    # a single float per mirror instead of the whole struct.
    g_sds, spec = _graph_cell_sds(
        mesh, n_vertices=n_vertices, n_edges=n_edges,
        mirror_factor=mirror_factor, ex=ex, contrib_form=contrib_form)
    e_blk, v_mir, k = spec["e_blk"], spec["v_mir"], spec["k_route"]

    if contrib_form:
        def send(sv, ev, dv):
            return {"m": sv["contrib"] * ev["w"]}

        def vprog(vid, v, msg):
            pr = 0.15 + 0.85 * msg["m"]
            return {"pr": pr, "deg": v["deg"], "contrib": pr / v["deg"]}
    else:
        def send(sv, ev, dv):
            return {"m": sv["pr"] / sv["deg"] * ev["w"]}

        def vprog(vid, v, msg):
            return {"pr": 0.15 + 0.85 * msg["m"], "deg": v["deg"]}

    def pr_superstep(g):
        out = g
        for _ in range(supersteps):
            out, live, _ = _superstep(
                out, vprog=vprog, send_msg=send, gather="sum",
                default_msg={"m": jnp.float32(0.0)}, skip_stale=None,
                changed_fn=None, kernel_mode="ref", use_cache=True,
                transport=tpol)
        # the carried view/wire_log are loop-internal here: stripping them
        # keeps the cell's output signature identical to its input specs
        return out.replace(view=None), live

    in_specs = jax.tree.map(lambda x: P(*(("parts",) + (None,) * (len(x.shape) - 1))),
                            g_sds, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    out_specs = (in_specs, P())
    from ..utils.spmd import shard_map as _shard_map
    fn = jax.jit(_shard_map(pr_superstep, mesh, (in_specs,), out_specs))
    t0 = time.time()
    lowered = fn.lower(g_sds)
    compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled)
    txt = compiled.as_text()
    coll = hlo_utils.collective_bytes(txt)
    dots = hlo_utils.dot_flops(txt)
    bytes_tc = hlo_utils.bytes_accessed(txt)
    shape_tag = (f"twitter_{supersteps}step"
                 + (f"_{transport}{capacity_frac}"
                    if transport not in (None, "dense") else "")
                 + ("_chk" if integrity else ""))
    rec = {
        "arch": "graphx-pagerank", "shape": shape_tag,
        "status": "ok",
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "mesh_axes": list(mesh.axis_names),
        "n_chips": int(np.prod(mesh.devices.shape)),
        "strategy": "vertex-cut-2d", "kind": "graph",
        "compile_seconds": round(compile_s, 1),
        "flops_per_chip": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_chip": float(cost.get("bytes accessed", 0.0)),
        "flops_per_chip_tc": float(max(dots["dot_flops"],
                                       cost.get("flops", 0.0))),
        "bytes_accessed_per_chip_tc": float(max(bytes_tc,
                                                cost.get("bytes accessed", 0.0))),
        "collective_bytes_per_chip": int(coll.get("total_bytes", 0)),
        "collectives": {kk: v for kk, v in coll.items() if kk != "total_bytes"},
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "graph": {"vertices": n_vertices, "edges": n_edges,
                  "e_blk": e_blk, "v_mir": v_mir, "k_route": k,
                  "wire": (ex.codec.name if ex.codec is not None else "f32"),
                  "transport": transport or "dense",
                  "capacity_frac": capacity_frac if tpol else None,
                  "integrity": bool(integrity),
                  "supersteps": supersteps},
    }
    return (rec, txt) if return_hlo else rec


def _collective_op_count(hlo_text: str, kind: str) -> int:
    """Occurrences of one collective op kind in the compiled HLO (sync and
    async-start forms; -done halves are not double counted)."""
    return sum(line.count(f" {kind}(") + line.count(f" {kind}-start(")
               for line in hlo_text.splitlines())


def lower_graph_cell_partitioned(*, p: int = 4, partitioner: str = "2d",
                                 bcast_min_repl: int | None = None,
                                 scale: int = 9, edge_factor: int = 10,
                                 seed: int = 2, supersteps: int = 1,
                                 return_hlo: bool = False):
    """Lower a PageRank superstep from a REAL scaled-down R-MAT graph under
    the requested partitioner (DESIGN.md §4.2/§2.1.3).

    The SDS stand-in path (`lower_graph_cell`) models the 2D cut's shapes
    analytically; the hybrid cut's routing tables — the degree threshold,
    the broadcast-set split — depend on the actual degree distribution, so
    the partitioner sweep materializes a small graph and lowers the exact
    program shard_map deploys.  `bcast_min_repl` enables the §2.1.3
    broadcast lane; the record reports the per-kind collective bytes so
    callers can assert the lane lowers to a single all-gather.  The graph
    is placed one partition per device (`Graph.place`) and the program is
    `pregel`'s own jitted step for it: the `supersteps`-th, the ones before
    it run first (the first ships cold, later ones against a warm view)."""
    from ..core import Graph as GraphCls
    from ..core import algorithms as alg_mod
    from ..core.pregel import superstep_jit
    from ..data import rmat

    gd = rmat(scale, edge_factor, seed=seed)
    kw = {} if partitioner == "2d" else {"partitioner": partitioner}
    if bcast_min_repl:
        kw["bcast_min_repl"] = bcast_min_repl
    g = GraphCls.from_edges(gd.src, gd.dst, num_partitions=p, **kw)
    stats = g.host.stats
    g = g.place(jax.devices()[:p])
    g = alg_mod.attach_out_degree(g, kernel_mode="ref")
    g = g.mapV(lambda vid, v: {**v, "pr": jnp.float32(1.0)})

    def send(sv, ev, dv):
        return {"m": sv["pr"] / sv["deg"] * ev["w"]}

    def vprog(vid, v, msg):
        return {**v, "pr": 0.15 + 0.85 * msg["m"]}

    step = superstep_jit(vprog, send, "sum",
                         default_msg={"m": jnp.float32(0.0)},
                         skip_stale=None, changed_fn=None,
                         kernel_mode="ref", incremental=True,
                         payload_bound=None, fuse_apply="auto")
    for _ in range(supersteps - 1):
        g, _, _ = step(g)
    t0 = time.time()
    compiled = step.lower(g).compile()
    compile_s = time.time() - t0
    txt = compiled.as_text()
    coll = hlo_utils.collective_bytes(txt)
    tag = f"rmat{scale}x{edge_factor}_{partitioner}"
    if bcast_min_repl:
        tag += f"_bcast{bcast_min_repl}"
    rec = {
        "arch": "graphx-pagerank", "shape": tag, "status": "ok",
        "mesh": f"{p}", "mesh_axes": ["parts"], "n_chips": p,
        "strategy": f"vertex-cut-{partitioner}", "kind": "graph",
        "compile_seconds": round(compile_s, 1),
        "collective_bytes_per_chip": int(coll.get("total_bytes", 0)),
        "collectives": {k: v for k, v in coll.items() if k != "total_bytes"},
        "all_gather_ops": _collective_op_count(txt, "all-gather"),
        "all_to_all_ops": _collective_op_count(txt, "all-to-all"),
        "graph": {"vertices": g.s.num_vertices, "edges": g.s.num_edges,
                  "partitioner": partitioner,
                  "bcast_min_repl": bcast_min_repl,
                  "replication_factor": round(stats.replication_factor, 4),
                  "hybrid_threshold": stats.threshold,
                  "n_broadcast": stats.n_broadcast,
                  "supersteps": supersteps},
    }
    return (rec, txt) if return_hlo else rec


def check_bcast_single_allgather(*, p: int = 4,
                                 bcast_min_repl: int = 3) -> dict:
    """`--bcast-check` (DESIGN.md §2.1.3): the broadcast lane must lower to
    EXACTLY ONE all-gather per superstep — one collective shipping each
    broadcast-set payload once per source — while the p2p all_to_all
    shrinks because those routes left the point-to-point tables.  Asserted
    on the compiled HLO of the same real-graph cell with and without the
    lane (a 2D cell has no broadcast set, hence zero all-gathers)."""
    cells = {}
    for name, kw in (("2d-dense", {"partitioner": "2d"}),
                     ("hybrid", {"partitioner": "hybrid"}),
                     ("hybrid+bcast", {"partitioner": "hybrid",
                                       "bcast_min_repl": bcast_min_repl})):
        rec = lower_graph_cell_partitioned(p=p, supersteps=1, **kw)
        cells[name] = {
            "all_gather_ops": rec["all_gather_ops"],
            "all_gather_bytes": int(rec["collectives"].get("all-gather", 0)),
            "all_to_all_bytes": int(rec["collectives"].get("all-to-all", 0)),
            "n_broadcast": rec["graph"]["n_broadcast"],
        }
        print(f"  {name:13s} ag_ops={cells[name]['all_gather_ops']} "
              f"ag_bytes={cells[name]['all_gather_bytes']} "
              f"a2a_bytes={cells[name]['all_to_all_bytes']} "
              f"n_bcast={cells[name]['n_broadcast']}", flush=True)
    for name in ("2d-dense", "hybrid"):
        assert cells[name]["all_gather_ops"] == 0, (name, cells)
    bc = cells["hybrid+bcast"]
    assert bc["n_broadcast"] > 0, cells
    assert bc["all_gather_ops"] == 1, cells
    assert bc["all_gather_bytes"] > 0, cells
    # the broadcast vertices' routes LEFT the p2p tables, so the point-to-
    # point collective must carry strictly fewer bytes than the dense 2D cell
    assert bc["all_to_all_bytes"] < cells["2d-dense"]["all_to_all_bytes"], \
        cells
    return cells


def check_hbm_resident(*, p: int = 4, scale: int = 9, edge_factor: int = 10,
                       seed: int = 2, threshold: float = 0.35) -> dict:
    """`--hbm-check` (DESIGN.md §2.4): narrow-RESIDENT mirrors must shrink
    the view carry's HBM bytes to <= `threshold` of the f32 baseline on the
    twitter-sim R-MAT PageRank cell.  Checked twice:

      * CONCRETE — run one warm superstep per codec and measure the view
        mirror's static resident bytes (`wire.resident_hbm_bytes`): int8
        keeps a 1-byte payload + a 1/32-density scale plane per f32 leaf,
        so the ratio lands near 26%;
      * COMPILED — lower the same warm superstep (the view rides the
        graph's carry, in AND out) and read the argument/output buffer
        totals from the XLA memory analysis: the encoded mirror must
        shrink the compiled carry, not just the Python-side accounting.
    """
    import dataclasses as _dc
    from ..core import Graph as GraphCls
    from ..core import algorithms as alg_mod
    from ..core import wire as wire_cdc
    from ..core.exchange import LocalExchange, with_wire
    from ..core.pregel import _superstep
    from ..data import rmat

    gd = rmat(scale, edge_factor, seed=seed)
    base = GraphCls.from_edges(gd.src, gd.dst, num_partitions=p)
    base = alg_mod.attach_out_degree(base, kernel_mode="ref")
    base = base.mapV(lambda vid, v: {**v, "pr": jnp.float32(1.0)})

    def send(sv, ev, dv):
        return {"m": sv["pr"] / sv["deg"] * ev["w"]}

    def vprog(vid, v, msg):
        return {**v, "pr": 0.15 + 0.85 * msg["m"]}

    def step(gg):
        g2, live, _ = _superstep(
            gg, vprog=vprog, send_msg=send, gather="sum",
            default_msg={"m": jnp.float32(0.0)}, skip_stale=None,
            changed_fn=None, kernel_mode="ref", use_cache=True)
        return g2, live

    cells = {}
    for name in ("f32", "int8"):
        ex = LocalExchange(p=p)
        if name == "int8":
            ex = with_wire(ex, "int8", resident=True)
        # view=None: the codec owns the mirror's resident format, so each
        # cell starts cold rather than inheriting the build chain's plain
        # f32 view (values would be identical; the footprint would lie).
        g2, _ = step(_dc.replace(base, ex=ex, view=None))  # warm eagerly
        mem = jax.jit(step).lower(g2).compile().memory_analysis()
        cells[name] = {
            "mirror_hbm_bytes": wire_cdc.resident_hbm_bytes(g2.view.mirror),
            "hlo_argument_bytes": int(mem.argument_size_in_bytes),
            "hlo_output_bytes": int(mem.output_size_in_bytes),
        }
        print(f"  {name:5s} mirror={cells[name]['mirror_hbm_bytes']} "
              f"args={cells[name]['hlo_argument_bytes']} "
              f"out={cells[name]['hlo_output_bytes']}", flush=True)
    ratio = (cells["int8"]["mirror_hbm_bytes"]
             / max(cells["f32"]["mirror_hbm_bytes"], 1))
    cells["ratio"] = round(ratio, 4)
    cells["threshold"] = threshold
    assert ratio <= threshold, cells
    assert (cells["int8"]["hlo_argument_bytes"]
            < cells["f32"]["hlo_argument_bytes"]), cells
    assert (cells["int8"]["hlo_output_bytes"]
            < cells["f32"]["hlo_output_bytes"]), cells
    return cells


def check_ragged_tracks_active(mesh, *, mirror_factor: float = 2.0,
                               fracs=(0.25, 0.5)) -> dict:
    """Dry-run HLO check (DESIGN.md §2.1.1): the ragged PageRank cell's
    collective bytes must TRACK the active fraction — lowering the same
    2-superstep cell at two capacity fractions and dense must order as
    coll(frac_lo) < coll(frac_hi) < coll(dense), and the two ragged cells'
    per-unit-fraction prices must agree within 15% (measured: 0.03% — the
    fixed per-destination counts wire is the only non-proportional term)."""
    lo, hi = sorted(fracs)
    cells = {}
    for name, kw in (("dense", {}),
                     (f"ragged@{lo}", {"transport": "ragged",
                                       "capacity_frac": lo}),
                     (f"ragged@{hi}", {"transport": "ragged",
                                       "capacity_frac": hi})):
        rec = lower_graph_cell(mesh, supersteps=2, mirror_factor=mirror_factor,
                               **kw)
        cells[name] = rec["collective_bytes_per_chip"]
        print(f"  {name:12s} collective bytes/chip = {cells[name]:.3e}",
              flush=True)
    d, blo, bhi = cells["dense"], cells[f"ragged@{lo}"], cells[f"ragged@{hi}"]
    assert blo < bhi < d, cells
    # "track the active fraction" = the ragged cell's collective bytes are
    # PROPORTIONAL to the capacity fraction: every cap row ships payload +
    # slot index and nothing else, so bytes/frac is a constant unit price
    # (the fixed remainder — per-destination counts, psums — is noise).
    # Measured on the Twitter cell: 2.019e8 / 0.25 vs 4.037e8 / 0.5, equal
    # to 0.03%.  The unit price EXCEEDS the dense price (slot indices ride
    # along: int32 on an 8 B/entry payload -> ~1.5x), which is exactly why
    # capacity_for clamps ragged plans to ragged_max_frac of the route.
    unit_lo, unit_hi = blo / lo, bhi / hi
    assert abs(unit_lo - unit_hi) / unit_hi < 0.15, (cells, unit_lo, unit_hi)
    return cells


def profile_ships(mesh, *, n_vertices=41_652_230, n_edges=1_468_365_182,
                  mirror_factor: float = 2.0) -> dict:
    """`--profile-ships`: lower a canned operator CHAIN (mrTriplets -> mapV
    touching one leaf -> mrTriplets -> mrTriplets) twice — once reading
    through the graph-resident view (§3.1), once with the view stripped
    before every consumer — and report, per variant, the trace-time route
    ships plus the all_to_all op count and collective bytes in the compiled
    HLO.  A pipeline regression (an operator re-shipping a clean view)
    shows up as extra route ships / collective bytes in the reuse column,
    which is exactly what this check is wired into CI to catch."""
    from ..core import transport as transport_mod
    from ..core.exchange import SpmdExchange

    p = mesh_axis_sizes(mesh)["parts"]
    g_sds, _ = _graph_cell_sds(
        mesh, n_vertices=n_vertices, n_edges=n_edges,
        mirror_factor=mirror_factor,
        ex=SpmdExchange(p=p, axis_name="parts"))

    def send(sv, ev, dv):
        return {"m": sv["pr"] / sv["deg"] * ev["w"]}

    def chain(g, reuse: bool):
        import dataclasses as dc
        strip = (lambda x: x) if reuse else \
            (lambda x: dc.replace(x, view=None))
        v1, _, g, _ = g.mrTriplets(send, "sum", kernel_mode="ref")
        g = strip(g).mapV(lambda vid, v: {"pr": v["pr"] * 0.85,
                                          "deg": v["deg"]})
        v2, _, g, _ = g.mrTriplets(send, "sum", kernel_mode="ref")
        g = strip(g)
        v3, _, g, _ = g.mrTriplets(send, "sum", kernel_mode="ref")
        return v1["m"], v2["m"], v3["m"]

    in_specs = jax.tree.map(
        lambda x: P(*(("parts",) + (None,) * (len(x.shape) - 1))),
        g_sds, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    from ..utils.spmd import shard_map as _shard_map
    out = {}
    for name, reuse in (("view_reuse", True), ("cold", False)):
        fn = jax.jit(_shard_map(lambda g, _r=reuse: chain(g, _r), mesh,
                                (in_specs,), (P("parts"),) * 3))
        transport_mod.SHIP_EVENTS.clear()
        lowered = fn.lower(g_sds)
        ships = list(transport_mod.SHIP_EVENTS)
        txt = lowered.compile().as_text()
        coll = hlo_utils.collective_bytes(txt)
        out[name] = {
            "route_ships": len(ships),
            "route_ships_fwd": sum(1 for e in ships if e["label"] == "fwd"),
            "a2a_ops": txt.count("all-to-all"),
            "collective_bytes_per_chip": int(coll.get("total_bytes", 0)),
        }
        print(f"  {name:10s} route_ships={out[name]['route_ships']} "
              f"(fwd {out[name]['route_ships_fwd']}) "
              f"a2a_ops={out[name]['a2a_ops']} "
              f"coll_bytes/chip={out[name]['collective_bytes_per_chip']:.3e}",
              flush=True)
    r, c = out["view_reuse"], out["cold"]
    assert r["route_ships_fwd"] < c["route_ships_fwd"], out
    assert r["collective_bytes_per_chip"] < c["collective_bytes_per_chip"], \
        out
    return out


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def _load_report() -> list:
    try:
        with open(REPORT_PATH) as f:
            return json.load(f)
    except FileNotFoundError:
        return []


def _save_report(entries: list) -> None:
    os.makedirs(os.path.dirname(REPORT_PATH), exist_ok=True)
    with open(REPORT_PATH, "w") as f:
        json.dump(entries, f, indent=1)


def _upsert(entries: list, rec: dict) -> None:
    key = (rec["arch"], rec["shape"], rec.get("mesh"), rec.get("variant", ""))
    entries[:] = [e for e in entries
                  if (e["arch"], e["shape"], e.get("mesh"),
                      e.get("variant", "")) != key]
    entries.append(rec)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--graph", action="store_true",
                    help="lower the GraphX PageRank superstep instead")
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--variant", default="",
                    help="tag for perf-iteration variants in the report")
    ap.add_argument("--kernel-mode", default="ref")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--moe-pin", action="store_true")
    ap.add_argument("--moe-bf16", action="store_true")
    ap.add_argument("--moe-cap", type=float, default=None)
    ap.add_argument("--moe-groups", action="store_true")
    ap.add_argument("--wire", default=None,
                    choices=["f32", "bf16", "int8", "fp8_e4m3", "fp8_e5m2"],
                    help="graph cell: wire codec for the mirror exchange")
    ap.add_argument("--wire-delta", action="store_true",
                    help="graph cell: active-set delta shipping accounting")
    ap.add_argument("--transport", default=None,
                    choices=["dense", "ragged", "auto"],
                    help="graph cell: exchange transport (DESIGN.md §2.1.1)")
    ap.add_argument("--capacity-frac", type=float, default=0.25,
                    help="graph cell: ragged capacity as a route fraction")
    ap.add_argument("--integrity", action="store_true",
                    help="graph cell: enable the §6 wire-integrity word + "
                         "retry/degrade ladder in the lowered program")
    ap.add_argument("--partitioner", default=None,
                    choices=["2d", "1d", "random", "hybrid"],
                    help="graph cell: vertex-cut partitioner (§4.2); "
                         "non-2d lowers a real scaled-down R-MAT cell")
    ap.add_argument("--bcast-min-repl", type=int, default=None,
                    help="graph cell: broadcast-lane replication threshold "
                         "(§2.1.3); implies the real-graph lowering")
    ap.add_argument("--bcast-check", action="store_true",
                    help="graph cell: assert in the compiled HLO that the "
                         "broadcast lane lowers to exactly one all-gather")
    ap.add_argument("--hbm-check", action="store_true",
                    help="graph cell: assert narrow-resident int8 mirrors "
                         "shrink the view carry's HBM bytes (§2.4)")
    ap.add_argument("--ragged-check", action="store_true",
                    help="graph cell: lower dense + two ragged capacities "
                         "and assert collective bytes track the fraction")
    ap.add_argument("--profile-ships", action="store_true",
                    help="graph cell: lower a canned operator chain with "
                         "and without graph-resident view reuse and report "
                         "route ships + HLO collective bytes (§3.1)")
    ap.add_argument("--mirror-factor", type=float, default=2.0)
    ap.add_argument("--contrib-form", action="store_true")
    ap.add_argument("--state-bf16", action="store_true")
    ap.add_argument("--mlstm-chunk", type=int, default=None)
    ap.add_argument("--dp-over-model", action="store_true")
    ap.add_argument("--batch-shard", action="store_true",
                    help="constrain activations batch-sharded over the full mesh")
    args = ap.parse_args()

    popts = {}
    if args.seq_shard:
        popts["act_spec"] = ("data", "model", None)
    if args.moe_pin:
        popts["moe_dispatch_spec"] = ("model", None, None)
    if args.moe_bf16:
        popts["moe_payload_dtype"] = jnp.bfloat16
    if args.moe_cap is not None:
        popts["moe_capacity_factor"] = args.moe_cap
    if args.moe_groups:
        popts["moe_groups"] = True
    if args.state_bf16:
        popts["state_dtype"] = jnp.bfloat16
    if args.mlstm_chunk:
        popts["mlstm_chunk"] = args.mlstm_chunk
    if args.dp_over_model:
        popts["dp_over_model"] = True
    if args.batch_shard:
        popts["act_spec"] = (("data", "model"), None, None)

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(multi_pod=False),
                  make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    entries = _load_report()

    if args.graph:
        if args.bcast_check:
            cells = check_bcast_single_allgather(
                bcast_min_repl=args.bcast_min_repl or 3)
            print(json.dumps({"bcast_check": "ok", "cells": cells},
                             indent=1))
            return
        if args.partitioner not in (None, "2d") or args.bcast_min_repl:
            rec = lower_graph_cell_partitioned(
                partitioner=args.partitioner or "2d",
                bcast_min_repl=args.bcast_min_repl)
            if args.variant:
                rec["variant"] = args.variant
            print(json.dumps(rec, indent=1))
            _upsert(entries, rec)
            _save_report(entries)
            return
        if args.hbm_check:
            cells = check_hbm_resident()
            print(json.dumps({"hbm_check": "ok", "cells": cells}, indent=1))
            return
        if args.profile_ships:
            gmesh = make_graph_mesh(multi_pod=args.multi_pod)
            cells = profile_ships(gmesh, mirror_factor=args.mirror_factor)
            print(json.dumps({"profile_ships": "ok", "cells": cells},
                             indent=1))
            return
        if args.ragged_check:
            gmesh = make_graph_mesh(multi_pod=args.multi_pod)
            cells = check_ragged_tracks_active(
                gmesh, mirror_factor=args.mirror_factor)
            print(json.dumps({"ragged_check": "ok", "cells": cells},
                             indent=1))
            return
        for mp in ([False, True] if args.both_meshes else [args.multi_pod]):
            gmesh = make_graph_mesh(multi_pod=mp)
            rec = lower_graph_cell(
                gmesh, wire=args.wire, wire_delta=args.wire_delta,
                mirror_factor=args.mirror_factor,
                contrib_form=args.contrib_form,
                transport=args.transport,
                capacity_frac=args.capacity_frac,
                integrity=args.integrity)
            if args.variant:
                rec["variant"] = args.variant
            print(json.dumps(rec, indent=1))
            _upsert(entries, rec)
        _save_report(entries)
        return

    archs = C.all_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]

    for mesh in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"[{arch} x {shape} @ {'x'.join(map(str, mesh.devices.shape))}]"
                try:
                    rec = lower_cell(arch, shape, mesh,
                                     strategy=args.strategy,
                                     kernel_mode=args.kernel_mode,
                                     perf_opts=popts or None)
                    if args.variant:
                        rec["variant"] = args.variant
                    status = rec["status"]
                    extra = (f" flops/chip={rec.get('flops_per_chip', 0):.3g}"
                             f" compile={rec.get('compile_seconds', 0)}s"
                             if status == "ok" else f" ({rec.get('reason')})")
                    print(f"{tag} {status}{extra}", flush=True)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "x".join(map(str, mesh.devices.shape)),
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"{tag} ERROR {type(e).__name__}: {e}", flush=True)
                _upsert(entries, rec)
                _save_report(entries)


if __name__ == "__main__":
    main()

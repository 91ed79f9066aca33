#!/usr/bin/env python3
"""Run the graph engine's main path on a TPU and check what comes out.

    python chip_smoke.py                 # one chip: PageRank + CC, scale sweep
    python chip_smoke.py --chips 4       # the SPMD path on a 4-chip host only
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal --scale 8 \\
        --max-scale 8                    # CPU rehearsal, Pallas interpret mode

One chip: a Graph500-shaped R-MAT graph (a/b/c = .57/.19/.19, edge factor
16) is generated from --seed and built with `Graph.from_edges(...,
num_partitions=4)`.  Static PageRank (10 supersteps) runs through
`algorithms.pagerank` and is checked against `algorithms.pagerank_reference`;
connected components runs through `algorithms.connected_components` on the
symmetrised graph and is checked exactly against a numpy min-label fixpoint.
Both must run the fused Pallas plans, and the compiled superstep must hold
the kernels (`tpu_custom_call`).  The sweep starts at --scale and goes up one
scale at a time while one superstep stays under STEP_BUDGET_S seconds; its
last line says where and why it stopped.

--chips 4 runs only the SPMD executor: the same PageRank and CC supersteps
under `shard_map` with `SpmdExchange` across four devices, one partition
each, checked against the same references.

Every line but the last is one JSON object for one phase.  The last line is
`{"ok": true, "device": {...}}` and appears only when every check passed on
a TPU.  Without a TPU the script exits non-zero and prints no result; only
--cpu-rehearsal runs on the CPU, and it never reports a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

PARTITIONS = 4
PR_ITERS = 10
PR_RTOL = 1e-4
STEP_BUDGET_S = 10.0    # the sweep stops raising the scale past this
TIME_BUDGET_S = 1000.0  # the whole run stays inside this


def emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling inside a window:
    the union of its compile-event spans, so nested traces count once."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **_):
        if event in self.EVENTS:
            self.spans.append((start, end))

    def seconds(self, t0: float, t1: float) -> float:
        total, reach = 0.0, t0
        for a, b in sorted(self.spans):
            a, b = max(a, reach), min(b, t1)
            if b > a:
                total += b - a
                reach = b
        return total


def cc_reference(src, dst, n: int):
    """Undirected connected components as a numpy min-label fixpoint: every
    vertex takes the least label among itself and its in-neighbours, then
    jumps to its label's label, until nothing changes.  Edges must hold both
    directions (data.symmetrize)."""
    order = np.argsort(dst, kind="stable")
    s_sorted, d_sorted = src[order], dst[order]
    starts = np.flatnonzero(np.r_[True, d_sorted[1:] != d_sorted[:-1]])
    heads = d_sorted[starts]
    lab = np.arange(n, dtype=np.int64)
    while True:
        new = lab.copy()
        new[heads] = np.minimum(lab[heads],
                                np.minimum.reduceat(lab[s_sorted], starts))
        new = new[new]
        if np.array_equal(new, lab):
            return lab
        lab = new


def _visible(g, leaf):
    """(home vertex ids, values of `leaf`) over the graph's visible rows."""
    mask = np.asarray(g.vmask)
    return np.asarray(g.s.home_vid)[mask], np.asarray(g.vdata[leaf])[mask]


def check_pagerank(g, gd, row) -> None:
    """Max relative error of the ranks against algorithms'
    pagerank_reference, recorded in `row` and emitted before the check."""
    from repro.core import algorithms as alg
    want = alg.pagerank_reference(gd.src, gd.dst, gd.num_vertices,
                                  num_iters=PR_ITERS)
    vid, got = _visible(g, "pr")
    rel = float(np.max(np.abs(got - want[vid]) / np.abs(want[vid])))
    row.update(max_rel_err=rel, rtol=PR_RTOL)
    emit(row)
    if not rel <= PR_RTOL:
        raise AssertionError(f"{row['phase']}: max relative error {rel}")


def check_cc(g, sd, row) -> None:
    """CC labels must equal the numpy fixpoint's exactly."""
    want = cc_reference(sd.src, sd.dst, sd.num_vertices)
    vid, got = _visible(g, "cc")
    bad = int(np.sum(got != want[vid]))
    row.update(label_mismatches=bad,
               components=int(np.unique(want[vid]).size))
    emit(row)
    if bad:
        raise AssertionError(f"{row['phase']}: {bad} labels differ from the "
                             "reference")


def grid_counts(g) -> dict:
    """Static work of one superstep on one device: grid steps of the fused
    triplet sweep and of the fused apply, and the tile-table bytes."""
    from repro.kernels.triplet import DEFAULT_VERTEX_BLOCK as vb
    s, p = g.s, g.s.p
    n_chunks = int(s.tiles["dst"]["chunk_out"].shape[1])
    n_apply = int(s.tiles["apply_dst"]["chunk_out"].shape[1])
    return {
        "chunks_per_partition": n_chunks,
        "triplet_grid_steps": p * -(-s.v_mir // vb) * p * n_chunks,
        "apply_grid_steps": p * -(-s.v_blk // vb) * p * n_apply,
        "tile_table_bytes": int(sum(a.nbytes for t in s.tiles.values()
                                    for a in t.values())),
    }


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def step_program(res, transport):
    """Compile the superstep a pregel run used; report the kernels and the
    device memory the program needs."""
    compiled = res.step.lower(res.graph, transport=transport).compile()
    mem = compiled.memory_analysis()
    return {
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "program_bytes": None if mem is None else {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes},
    }


def run_algorithm(name, fn, graph, clock, transport, on_tpu, **kw):
    """One pregel-driven algorithm: wall time to a result on the device,
    split into the compile spans inside it and the rest."""
    t0 = time.time()
    res = fn(graph, track_metrics=True, **kw)
    jax.block_until_ready(res.graph.vdata)
    t1 = time.time()
    compile_s = clock.seconds(t0, t1)
    m = res.metrics[-1]
    ti = time.time()
    prog = step_program(res, transport)
    row = {"phase": name, "supersteps": res.supersteps,
           "compile_s": compile_s, "run_s": (t1 - t0) - compile_s,
           "superstep_s": ((t1 - t0) - compile_s) / max(res.supersteps, 1),
           "inspect_s": time.time() - ti,
           "plan": m["plan"], "apply_plan": m["apply_plan"], **prog,
           "peak_bytes_in_use": peak_bytes()}
    if m["plan"] != "fused" or m["apply_plan"] != "fused_apply":
        raise AssertionError(f"{name}: fused plans not taken: {row}")
    if on_tpu and not prog["tpu_custom_call"]:
        raise AssertionError(f"{name}: no Pallas kernel in the step: {row}")
    return res, row


def one_chip(args, kernel_mode, on_tpu):
    from repro.core import Graph, algorithms as alg
    from repro.core.transport import resolve_transport
    from repro.data import rmat, symmetrize

    clock = CompileClock()
    transport = resolve_transport(None)
    t_start = time.time()

    def build(scale):
        t0 = time.time()
        gd = rmat(scale, 16, seed=args.seed)
        t_gen = time.time() - t0
        t0 = time.time()
        g = Graph.from_edges(gd.src, gd.dst, num_partitions=PARTITIONS)
        jax.block_until_ready(g.s)
        counts = grid_counts(g)
        emit({"phase": "build", "graph": "pagerank", "scale": scale,
              "vertices": int(g.s.num_vertices), "edges": gd.num_edges,
              "partitions": PARTITIONS, "gen_s": t_gen,
              "build_structure_s": time.time() - t0, **counts})
        return gd, g, counts

    scale = args.scale
    gd, g, counts = build(scale)
    while True:
        res, row = run_algorithm("pagerank", alg.pagerank, g, clock,
                                 transport, on_tpu, num_iters=PR_ITERS,
                                 kernel_mode=kernel_mode)
        row["scale"] = scale
        check_pagerank(res.graph, gd, row)
        step_s, run_s = row["superstep_s"], row["run_s"]
        del g, res

        t0 = time.time()
        sd = symmetrize(gd)
        sg = Graph.from_edges(sd.src, sd.dst, num_partitions=PARTITIONS)
        jax.block_until_ready(sg.s)
        emit({"phase": "build", "graph": "cc", "scale": scale,
              "vertices": int(sg.s.num_vertices), "edges": sd.num_edges,
              "build_structure_s": time.time() - t0, **grid_counts(sg)})
        res, row = run_algorithm("cc", alg.connected_components, sg, clock,
                                 transport, on_tpu, kernel_mode=kernel_mode)
        row["scale"] = scale
        check_cc(res.graph, sd, row)
        step_s = max(step_s, row["superstep_s"])
        run_s += row["run_s"]
        del sg, res

        reached = {"reached": scale, "superstep_s": step_s,
                   "triplet_grid_steps": counts["triplet_grid_steps"],
                   "apply_grid_steps": counts["apply_grid_steps"],
                   "tile_table_bytes": counts["tile_table_bytes"]}
        if step_s > STEP_BUDGET_S:
            why = (f"one superstep took {step_s:.3f} s at scale {scale}, "
                   f"over the {STEP_BUDGET_S} s budget")
            break
        if scale == args.max_scale:
            why = f"--max-scale {args.max_scale} reached"
            break
        last_steps = counts["triplet_grid_steps"]
        gd, g, counts = build(scale + 1)
        growth = counts["triplet_grid_steps"] / last_steps
        left = TIME_BUDGET_S - (time.time() - t_start)
        if run_s * growth > left:
            why = (f"scale {scale + 1} has {growth:.1f}x the grid steps of "
                   f"scale {scale}, whose supersteps ran {run_s:.1f} s: "
                   f"more than the {left:.0f} s left of the run")
            break
        scale += 1
    emit({"phase": "scale", **reached, "stop": why,
          "note": ("the fused triplet grid is (vertex blocks x chunks) over "
                   "all partitions; every step fetches its chunk's edge "
                   "blocks even when pl.when skips the compute")})


def four_chips(args, kernel_mode, on_tpu):
    """PageRank and CC supersteps under jit(shard_map) with SpmdExchange:
    one partition per device, the host loop driving static supersteps."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS
    from repro.core import Graph, SpmdExchange, algorithms as alg
    from repro.core.pregel import _superstep
    from repro.data import rmat, symmetrize
    from repro.utils.spmd import make_mesh, shard_map

    mesh = make_mesh((PARTITIONS,), ("parts",),
                     devices=jax.devices()[:PARTITIONS])

    def spmd(g):
        g = dataclasses.replace(g, ex=SpmdExchange(p=PARTITIONS,
                                                   axis_name="parts"),
                                host=None)
        return jax.device_put(g, jax.tree.map(
            lambda x: NamedSharding(mesh, PS("parts")), g))

    def loop(g, name, max_steps, **kw):
        def body(gg):
            g2, live, _ = _superstep(gg, kernel_mode=kernel_mode,
                                     use_cache=True, **kw)
            return g2, jax.lax.psum(live, "parts")
        step = jax.jit(shard_map(body, mesh, (PS("parts"),),
                                 (PS("parts"), PS())))
        # one executable per graph structure (the view turns warm after the
        # first superstep), compiled ahead so the loop runs no compile
        programs, compile_s = {}, 0.0
        t0 = time.time()
        steps = 0
        for _ in range(max_steps):
            key = jax.tree.structure(g)
            if key not in programs:
                tc = time.time()
                programs[key] = step.lower(g).compile()
                compile_s += time.time() - tc
            g, live = programs[key](g)
            steps += 1
            if int(live) == 0:
                break
        jax.block_until_ready(g.vdata)
        hlo = programs[jax.tree.structure(g)].as_text()
        leaf = jax.tree.leaves(g.vdata)[0]
        shards = leaf.addressable_shards
        devices = sorted({s.device.id for s in shards})
        row = {"phase": name, "supersteps": steps, "compile_s": compile_s,
               "run_s": time.time() - t0 - compile_s,
               "shard_devices": devices,
               "shard_rows": sorted({s.data.shape[0] for s in shards}),
               "all_to_all": "all-to-all" in hlo,
               "tpu_custom_call": "tpu_custom_call" in hlo,
               "peak_bytes_in_use": peak_bytes()}
        if len(devices) != PARTITIONS or row["shard_rows"] != [1]:
            raise AssertionError(f"{name}: partitions not one per device: "
                                 f"{row}")
        if not row["all_to_all"]:
            raise AssertionError(f"{name}: no all-to-all in the step")
        if on_tpu and not row["tpu_custom_call"]:
            raise AssertionError(f"{name}: no Pallas kernel in the step")
        return g, row

    gd = rmat(args.scale, 16, seed=args.seed)
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=PARTITIONS)
    g = alg.attach_out_degree(g, kernel_mode).mapV(
        lambda vid, v: {**v, "pr": jnp.float32(1.0)})
    g, row = loop(
        spmd(g), "spmd_pagerank", PR_ITERS,
        vprog=lambda vid, v, msg: {**v, "pr": 0.15 + 0.85 * msg["m"]},
        send_msg=lambda sv, ev, dv: {"m": sv["pr"] / sv["deg"] * ev["w"]},
        gather="sum", default_msg={"m": jnp.float32(0.0)}, skip_stale=None,
        changed_fn=None)
    row["scale"] = args.scale
    check_pagerank(g, gd, row)

    sd = symmetrize(gd)
    sg = Graph.from_edges(sd.src, sd.dst, num_partitions=PARTITIONS)
    sg = sg.mapV(lambda vid, v: {"cc": vid})
    sg, row = loop(
        spmd(sg), "spmd_cc", 100,
        vprog=lambda vid, v, msg: {"cc": jnp.minimum(v["cc"], msg["m"])},
        send_msg=lambda sv, ev, dv: {"m": sv["cc"]}, gather="min",
        default_msg={"m": alg.IMAX}, skip_stale="out", changed_fn=None)
    row["scale"] = args.scale
    check_cc(sg, sd, row)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=16,
                    help="first R-MAT scale (2**scale vertices)")
    ap.add_argument("--max-scale", type=int, default=20)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU with the Pallas kernels in "
                         "interpret mode; never reports a TPU")
    args = ap.parse_args(argv)
    if args.scale > args.max_scale:
        ap.error("--scale must not exceed --max-scale")

    devices = jax.devices()
    platform = devices[0].platform
    on_tpu = platform == "tpu"
    if args.cpu_rehearsal == on_tpu:
        print(f"chip_smoke: JAX platform is {platform!r}; "
              + ("--cpu-rehearsal is for hosts without a TPU" if on_tpu
                 else "no TPU found (use --cpu-rehearsal on a CPU host)"),
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.kernels import ops
    from repro.utils.compile_cache import enable_compile_cache
    emit({"phase": "setup", "compile_cache": enable_compile_cache(),
          "platform": platform, "device_kind": devices[0].device_kind,
          "devices": len(devices), "jax": jax.__version__})
    kernel_mode = "auto" if on_tpu else "interpret"
    if on_tpu and ops.resolve_mode(kernel_mode) != "pallas":
        raise AssertionError("kernel_mode='auto' does not compile the "
                             "Pallas kernels on this TPU")
    if args.chips == 4:
        four_chips(args, kernel_mode, on_tpu)
    else:
        one_chip(args, kernel_mode, on_tpu)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if on_tpu:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    else:
        print(json.dumps({"rehearsal": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

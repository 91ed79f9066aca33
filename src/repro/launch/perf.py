"""Dry-run profiler for the perf hillclimb (§Perf methodology).

Given a compiled cell, attribute collective bytes and HBM traffic to the
JAX source operation (HLO metadata op_name), trip-count corrected — a
static profile read from the compiled program on simulated host devices.
Nothing here runs on a chip: its times are a model built from the
constants below, never a device measurement (bench/run.py is the chip
benchmark).

  PYTHONPATH=src python -m repro.launch.perf --arch arctic-480b \
      --shape prefill_32k [--variant seqshard] [--top 15]
"""
# Must precede any jax import (device count locks at first init).
import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import re
from collections import defaultdict

from ..utils import hlo as H

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

_META_RE = re.compile(r'op_name="([^"]*)"')


def _short_op_name(meta: str, depth: int = 4) -> str:
    """jit(train_step)/jvp()/while/body/closed_call/bld,dhk->bhlk/dot_general
    -> a stable, readable tail."""
    parts = [p for p in meta.split("/") if p not in ("jvp()",)]
    return "/".join(parts[-depth:])


def top_collectives(hlo_text: str, k: int = 15):
    comps = H._computations(hlo_text)
    mult = H._multipliers(comps)
    rows = defaultdict(lambda: [0.0, 0.0, ""])  # name -> [bytes, count, kind]
    for cname, body in comps.items():
        m_k = mult.get(cname, 1.0)
        for line in body.splitlines():
            line = line.strip()
            m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(",
                         line)
            if not m:
                continue
            shape_str, op = m.groups()
            kind = next((c for c in H._COLLECTIVES
                         if op == c or op.startswith(c + "-start")), None)
            if kind is None or op.endswith("-done"):
                continue
            nbytes = H._shape_bytes(shape_str) * (2 if kind == "all-reduce"
                                                  else 1)
            meta = _META_RE.search(line)
            name = _short_op_name(meta.group(1)) if meta else "?"
            key = f"{kind} :: {name}"
            rows[key][0] += nbytes * m_k
            rows[key][1] += m_k
            rows[key][2] = kind
    out = sorted(((v[0], v[1], kk) for kk, v in rows.items()), reverse=True)
    return out[:k]


def top_memory(hlo_text: str, k: int = 15):
    comps = H._computations(hlo_text)
    mult = H._multipliers(comps)
    instrs, shapes_by_comp, shapes_global = H._parse_instructions(comps)
    inner = set()
    for cname, name, out_shape, op, operands, line in instrs:
        if op.startswith("fusion") or op in ("reduce", "scatter", "sort",
                                             "map", "reduce-window",
                                             "select-and-scatter",
                                             "all-reduce", "reduce-scatter"):
            for m in re.finditer(r"(?:calls|to_apply)=%?([\w.\-]+)", line):
                inner.add(m.group(1))
    rows = defaultdict(lambda: [0.0, 0.0])
    for cname, name, out_shape, op, operands, line in instrs:
        if cname in inner or op in H._MEM_SKIP_OPS:
            continue
        m_k = mult.get(cname, 1.0)
        local = shapes_by_comp.get(cname, {})
        opnd_bytes = []
        for tok in operands.split(","):
            tok = tok.strip()
            if "[" in tok:
                opnd_bytes.append(H._shape_bytes(tok))
            elif tok.startswith("%"):
                opnd_bytes.append(H._shape_bytes(
                    local.get(tok[1:], shapes_global.get(tok[1:], ""))))
        nbytes = H._instr_traffic(op, line, H._shape_bytes(out_shape),
                                  opnd_bytes)
        meta = _META_RE.search(line)
        label = _short_op_name(meta.group(1)) if meta else op
        key = f"{op} :: {label}"
        rows[key][0] += nbytes * m_k
        rows[key][1] += m_k
    out = sorted(((v[0], v[1], kk) for kk, v in rows.items()), reverse=True)
    return out[:k]


def summarize(rec: dict, txt: str, top: int = 12) -> None:
    flops = rec.get("flops_per_chip_tc", rec.get("flops_per_chip", 0))
    mem = rec.get("bytes_accessed_per_chip_tc",
                  rec.get("bytes_accessed_per_chip", 0))
    coll = rec.get("collective_bytes_per_chip", 0)
    print(f"\n=== {rec.get('arch')} x {rec.get('shape')} @ {rec.get('mesh')} "
          f"[{rec.get('variant', 'baseline')}] ===")
    print(f" compute   {flops / PEAK_FLOPS:10.3f} s   ({flops:.3e} flop)")
    print(f" memory    {mem / HBM_BW:10.3f} s   ({mem:.3e} B)")
    print(f" collective{coll / LINK_BW:10.3f} s   ({coll:.3e} B)")
    hbm = rec.get("memory", {})
    if hbm:
        gb = (hbm["argument_bytes"] + hbm["temp_bytes"]) / rec["n_chips"] / 2**30
        print(f" residency {gb:10.1f} GB/chip {'OVER 16GB!' if gb > 16 else ''}")
    print("\n top collectives (bytes/chip, count):")
    for b, c, name in top_collectives(txt, top):
        print(f"  {b:12.3e}  x{c:<6.0f} {name[:110]}")
    print("\n top memory traffic (bytes/chip, count):")
    for b, c, name in top_memory(txt, top):
        print(f"  {b:12.3e}  x{c:<6.0f} {name[:110]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--graph", action="store_true")
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--kernel-mode", default="ref")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-sharded residual stream over 'model'")
    ap.add_argument("--moe-pin", action="store_true",
                    help="pin MoE dispatch buffers to the expert axis")
    ap.add_argument("--moe-bf16", action="store_true",
                    help="bf16 MoE dispatch/combine payloads")
    ap.add_argument("--moe-cap", type=float, default=None,
                    help="MoE capacity factor override")
    ap.add_argument("--moe-groups", action="store_true",
                    help="group-local (GShard-style) MoE routing")
    ap.add_argument("--wire", default=None,
                    choices=["f32", "bf16", "int8", "fp8_e4m3", "fp8_e5m2"],
                    help="graph cell: wire codec for the mirror exchange "
                         "(per-block scaled int8/fp8, DESIGN.md §2.1)")
    ap.add_argument("--wire-delta", action="store_true",
                    help="graph cell: active-set delta shipping accounting")
    ap.add_argument("--transport", default=None,
                    choices=["dense", "ragged", "auto"],
                    help="graph cell: exchange transport — 'ragged' lowers "
                         "the compacted collective (DESIGN.md §2.1.1)")
    ap.add_argument("--capacity-frac", type=float, default=0.25,
                    help="graph cell: ragged capacity as a route fraction")
    ap.add_argument("--partitioner", default=None,
                    choices=["2d", "1d", "random", "hybrid"],
                    help="graph cell: vertex-cut partitioner (DESIGN.md "
                         "§4.2); non-2d profiles a real scaled-down cell")
    ap.add_argument("--bcast-min-repl", type=int, default=None,
                    help="graph cell: §2.1.3 broadcast-lane replication "
                         "threshold (implies the real-graph lowering)")
    ap.add_argument("--mirror-factor", type=float, default=2.0)
    ap.add_argument("--dp-over-model", action="store_true")
    ap.add_argument("--batch-shard", action="store_true",
                    help="constrain activations batch-sharded over the full mesh")
    ap.add_argument("--mlstm-chunk", type=int, default=None)
    ap.add_argument("--remat-nothing", action="store_true")
    ap.add_argument("--contrib-form", action="store_true",
                    help="graph cell: ship a precomputed contrib property")
    args = ap.parse_args()

    from .mesh import make_production_mesh, make_graph_mesh
    from . import dryrun
    import jax.numpy as jnp

    if args.graph or args.arch.startswith("graphx"):
        if args.partitioner not in (None, "2d") or args.bcast_min_repl:
            rec, txt = dryrun.lower_graph_cell_partitioned(
                partitioner=args.partitioner or "2d",
                bcast_min_repl=args.bcast_min_repl, return_hlo=True)
            summarize(rec, txt, args.top)
            return
        mesh = make_graph_mesh(multi_pod=False)
        rec, txt = dryrun.lower_graph_cell(
            mesh, return_hlo=True,
            wire=args.wire, wire_delta=args.wire_delta,
            mirror_factor=args.mirror_factor,
            contrib_form=args.contrib_form,
            transport=args.transport,
            capacity_frac=args.capacity_frac)
    else:
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
        if args.dp_over_model:
            popts["dp_over_model"] = True
        if args.batch_shard:
            popts["act_spec"] = (("data", "model"), None, None)
        if args.mlstm_chunk:
            popts["mlstm_chunk"] = args.mlstm_chunk
        if args.remat_nothing:
            popts["remat_policy"] = "nothing"
        mesh = make_production_mesh(multi_pod=False)
        rec, txt = dryrun.lower_cell(args.arch, args.shape, mesh,
                                     strategy=args.strategy, return_hlo=True,
                                     kernel_mode=args.kernel_mode,
                                     perf_opts=popts or None)
    summarize(rec, txt, args.top)


if __name__ == "__main__":
    main()

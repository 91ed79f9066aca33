"""Superstep mega-fusion + overlap benchmark (DESIGN.md §2.3.2 / §2.1.2).

First compiled numbers for the fused-superstep + pipelined-exchange work:
for each workload x transport x codec x pipeline cell, one converged Pregel
run reporting

  * `bytes_per_chip`        — shipped collective bytes / P (deterministic:
                              static wire accounting, fixed seeds);
  * `overlap_efficiency`    — the fraction of exchange wire time the ring
                              pipeline hides behind compute ((P-1)/P once
                              the schedule decomposes into P independent
                              stages; 0 for the serialized all_to_all);
  * `step_time_modeled_s`   — per-superstep roofline: HBM time for the home
                              materializations + the UNHIDDEN fraction of
                              link time (launch.perf constants — a model,
                              not a chip measurement);
  * `materializations_*`    — home-shaped HBM array materializations per
                              superstep from the traced jaxpr, fused vs
                              unfused apply (the §2.3.2 claim: strictly
                              fewer when the apply half fuses);
  * `bytes_link_modeled`    — the same traffic lowered onto PHYSICAL links:
                              (P-1)/P of each all_to_all payload, (P-1)x a
                              broadcast (§2.1.1 ring model), per chip;
  * `mirror_hbm_bytes`      — static HBM footprint of the warm view's
                              resident mirrors (§2.4: the narrow-resident
                              codec keeps int8 payload + E8M0 exponents in
                              HBM instead of f32);
  * `seconds_measured`      — CPU wall time, informational only (NOT gated:
                              host timing noise).

The `working_set: 0.5` rows are the §2.4 out-of-core lane: the same
PageRank with half the home-vertex cells spilled to host DRAM between
supersteps.  They persist the modeled double-buffered streaming trajectory
(`stream_time_overlap_s` strictly under `stream_time_serial_s`) and assert
bit-exactness against the fully resident run before emitting the row.

`benchmarks/run.py --superstep` writes the deterministic rows to
BENCH_superstep.json (the committed perf trajectory); `benchmarks/perf_gate.py`
diffs a fresh file against the committed one in CI.
"""
from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

import importlib

from repro.core import Graph, TransportPolicy, with_wire
from repro.core import wire as wire_mod
from repro.core.transport import DENSE
from repro.data import rmat, symmetrize

# `repro.core.pregel` the MODULE — the package re-exports the same name as
# the driver function, which `import ... as` would resolve to instead
pregel_mod = importlib.import_module("repro.core.pregel")

# roofline constants live with the dry-run profiler; launch.perf only forces
# a 512-device host platform when XLA_FLAGS is still unset (run.py sets it)
from repro.launch.perf import HBM_BW, LINK_BW

P = 4


# ---------------------------------------------------------------------------
# home-materialization counting (the dry-run HLO evidence for §2.3.2)
# ---------------------------------------------------------------------------
def _subjaxprs(val):
    from jax.extend import core as jex
    if isinstance(val, jex.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jex.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _subjaxprs(v)


def _count_home_shaped(jaxpr, shape2) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if tuple(getattr(v.aval, "shape", ()))[:2] == shape2:
                n += 1
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                n += _count_home_shaped(sub, shape2)
    return n


def count_home_materializations(g: Graph, *, vprog, send_msg, gather,
                                default_msg, skip_stale, fuse_apply) -> int:
    """Number of home-vertex-shaped ([nl, v_blk, ...]) arrays one traced
    superstep materializes.  Traced with kernel_mode="interpret" so the
    fused sweeps stay single `pallas_call` equations — exactly what the
    compiled HLO keeps VMEM-resident instead of round-tripping to HBM."""
    fn = functools.partial(
        pregel_mod._superstep, vprog=vprog, send_msg=send_msg, gather=gather,
        default_msg=default_msg, skip_stale=skip_stale, changed_fn=None,
        kernel_mode="interpret", use_cache=True, fuse_apply=fuse_apply)
    jaxpr = jax.make_jaxpr(fn)(g)
    nl, v_blk = g.s.home_vid.shape
    return _count_home_shaped(jaxpr.jaxpr, (nl, v_blk))


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------
def _workloads(quick: bool):
    """name -> (graph builder fn(partitioner_kw) -> Graph, pregel kwargs,
    fuse_apply).  The builder re-partitions the SAME edge set so the
    partitioner row dimension compares placements, not graphs."""
    IMAX = jnp.int32(2**31 - 1)

    # CC: min gather, int32 labels — the fused apply's bit-exact default
    sgd = symmetrize(rmat(7 if quick else 11, 4, seed=4))

    def cc_build(pkw):
        cg = Graph.from_edges(sgd.src, sgd.dst, num_partitions=P, **pkw)
        return cg.mapV(lambda vid, v: {"cc": vid})

    def cc_send(sv, ev, dv):
        return {"m": sv["cc"]}

    def cc_vprog(vid, v, msg):
        return {"cc": jnp.minimum(v["cc"], msg["m"])}

    # delta PageRank: sum gather with a tolerance changed mask, so the
    # active set SHRINKS and auto transport has something to compact
    gd = rmat(8 if quick else 12, 6, seed=3)
    deg = np.maximum(np.bincount(
        gd.src, minlength=int(max(gd.src.max(), gd.dst.max())) + 1), 1)
    vids = np.arange(len(deg))

    def pr_build(pkw):
        pg = Graph.from_edges(gd.src, gd.dst, num_partitions=P,
                              vertex_keys=vids,
                              vertex_values={"deg": deg.astype(np.float32)},
                              default_vertex={"deg": np.float32(1)}, **pkw)
        return pg.mapV(lambda vid, v: {"pr": jnp.float32(1.0),
                                       "deg": v["deg"]})

    def pr_send(sv, ev, dv):
        return {"m": sv["pr"] / sv["deg"]}

    def pr_vprog(vid, v, msg):
        return {"pr": 0.15 + 0.85 * msg["m"], "deg": v["deg"]}

    def pr_changed(old, new):
        return jnp.abs(new["pr"] - old["pr"]).max() > 1e-2

    return {
        "cc": (cc_build, dict(vprog=cc_vprog, send_msg=cc_send, gather="min",
                              default_msg={"m": IMAX}, skip_stale="out"),
               "auto"),
        "pagerank_delta": (pr_build, dict(vprog=pr_vprog, send_msg=pr_send,
                                          gather="sum",
                                          default_msg={"m": jnp.float32(0.0)},
                                          skip_stale="out",
                                          changed_fn=pr_changed),
                           "auto"),
    }


# partitioner row dimension (§4.2/§2.1.3): "2d" is the full historical
# matrix; the hybrid cut and its broadcast lane ride as extra f32 cells
_PARTITIONER_KW = {
    "2d": {},
    "hybrid": {"partitioner": "hybrid"},
    "hybrid+bcast": {"partitioner": "hybrid", "bcast_min_repl": 3},
}


def run(quick: bool = True) -> list[dict]:
    if jax.device_count() < 1:   # pragma: no cover — defensive
        return []
    rows = []
    auto_tp = TransportPolicy("auto", cap_rounding=8, enter_frac=0.95,
                              exit_frac=0.97)
    for wname, (build, kw, fuse) in _workloads(quick).items():
        mat_kw = {k: kw[k] for k in ("vprog", "send_msg", "gather",
                                     "default_msg", "skip_stale")}
        # the historical matrix stays on the 2D cut; the hybrid cut and its
        # broadcast lane add f32 cells (the ISSUE-9 partitioner dimension)
        cells = [("2d", codec, transport, pipeline)
                 for codec in ("f32", "int8")
                 for transport in ("dense", "auto")
                 for pipeline in (False, True)]
        cells += [("hybrid", "f32", "auto", False),
                  ("hybrid+bcast", "f32", "dense", False),
                  ("hybrid+bcast", "f32", "auto", False)]
        graphs: dict[str, Graph] = {}
        mats: dict[str, tuple[int, int]] = {}

        for partitioner, codec, transport, pipeline in cells:
            if partitioner not in graphs:
                g = build(_PARTITIONER_KW[partitioner])
                graphs[partitioner] = g
                # the §2.3.2 HBM-materialization evidence, per placement
                # (the broadcast lane adds exchange ops, not home arrays —
                # but count what the trace actually holds)
                mats[partitioner] = (
                    count_home_materializations(
                        g, fuse_apply="unfused", **mat_kw),
                    count_home_materializations(g, fuse_apply=fuse, **mat_kw))
            g = graphs[partitioner]
            mats_unfused, mats_fused = mats[partitioner]
            nl, v_blk = g.s.home_vid.shape
            dv = sum(int(np.prod(l.shape[2:], dtype=np.int64)) if l.ndim > 2
                     else 1 for l in jax.tree.leaves(g.vdata))
            home_bytes = nl * v_blk * dv * 4

            # narrow codecs run NARROW-RESIDENT (§2.4): mirrors stay encoded
            # in HBM, so `mirror_hbm_bytes` states the footprint win the
            # codec buys between supersteps, not just on the wire
            gc = (g.replace(ex=with_wire(g.ex, codec, resident=True))
                  if codec != "f32" else g)
            tp = (auto_tp if transport == "auto"
                  else DENSE).replace(pipeline=pipeline)
            call_kw = dict(kw)
            vprog = call_kw.pop("vprog")
            send_msg = call_kw.pop("send_msg")
            gather = call_kw.pop("gather")
            call_kw.update(transport=tp, track_metrics=True,
                           fuse_apply=fuse, max_supersteps=30)

            def go():
                return pregel_mod.pregel(gc, vprog, send_msg, gather,
                                         **call_kw)

            jax.block_until_ready(
                jax.tree.leaves(go().graph.vdata))   # compile
            t0 = time.perf_counter()
            res = go()
            jax.block_until_ready(jax.tree.leaves(res.graph.vdata))
            sec = time.perf_counter() - t0
            n_steps = max(res.supersteps, 1)
            shipped = float(sum(m["bytes_shipped"]
                                for m in res.metrics))
            # ring-lowered realism (§2.1.1): bytes the P-stage ring puts on
            # PHYSICAL links — (P-1)/P of each all_to_all, (P-1)x broadcast
            link_modeled = float(sum(m["bytes_link_modeled"]
                                     for m in res.metrics))
            # §2.4 resident mirror footprint: static HBM bytes the warm
            # view carries BETWEEN supersteps (the narrow-resident codec's
            # headline shrink; re-derived here because the jitted step
            # strips static ints from its returned metrics)
            view = res.graph.view
            mirror_hbm = (int(wire_mod.resident_hbm_bytes(view.mirror))
                          if view is not None else 0)
            bytes_per_chip = shipped / P
            overlap = (P - 1) / P if pipeline else 0.0
            # per-superstep roofline: HBM writes of the home-shaped
            # materializations + the unhidden slice of link time
            t_hbm = mats_fused * home_bytes / HBM_BW
            t_link = (bytes_per_chip / n_steps) / LINK_BW
            step_time = t_hbm + (1.0 - overlap) * t_link
            rows.append({
                "benchmark": "superstep",
                "workload": wname,
                "partitioner": partitioner,
                "transport": transport,
                "codec": codec,
                "pipeline": pipeline,
                "working_set": 1.0,
                "supersteps": res.supersteps,
                "apply_plan": res.metrics[0]["apply_plan"],
                "plan": res.metrics[0]["plan"],
                "recompiles": int(res.metrics[-1]["recompiles"]),
                "replication_factor": round(
                    g.host.stats.replication_factor, 4),
                "bytes_per_chip": round(bytes_per_chip),
                "bytes_link_modeled": round(link_modeled / P),
                "mirror_hbm_bytes": mirror_hbm,
                "overlap_efficiency": overlap,
                "materializations_fused": mats_fused,
                "materializations_unfused": mats_unfused,
                "t_link_s": t_link,
                "step_time_modeled_s": step_time,
                "seconds_measured": round(sec, 4),
            })

        if wname != "pagerank_delta":
            continue
        # §2.4 out-of-core lane: the SAME PageRank on half the working set.
        # Cold home-vertex cells spill to host DRAM after every superstep
        # and stream back through the double-buffered prefetch ring; the
        # persisted evidence is (a) bit-exact results vs fully resident,
        # (b) a slimmer device carry, (c) the modeled overlap time strictly
        # under the serialized compute-then-stream time.
        g = graphs["2d"]
        call_kw = dict(kw)
        vprog = call_kw.pop("vprog")
        send_msg = call_kw.pop("send_msg")
        gather = call_kw.pop("gather")
        call_kw.update(transport=DENSE, track_metrics=True,
                       fuse_apply=fuse, max_supersteps=30)
        res_full = pregel_mod.pregel(g, vprog, send_msg, gather, **call_kw)
        t0 = time.perf_counter()
        res_ws = pregel_mod.pregel(g, vprog, send_msg, gather,
                                   working_set_frac=0.5, **call_kw)
        sec = time.perf_counter() - t0
        if res_ws.supersteps != res_full.supersteps:
            raise AssertionError(
                f"out-of-core changed convergence: {res_ws.supersteps} "
                f"vs {res_full.supersteps} supersteps")
        for a, b in zip(jax.tree.leaves(res_full.graph.vdata),
                        jax.tree.leaves(res_ws.graph.vdata)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise AssertionError(
                    "out-of-core PageRank diverged from the fully "
                    "resident run (must be bit-exact)")
        stream_b = float(sum(m["stream_bytes"] for m in res_ws.metrics))
        t_serial = float(sum(m["stream_time_serial"]
                             for m in res_ws.metrics))
        t_overlap = float(sum(m["stream_time_overlap"]
                              for m in res_ws.metrics))
        if not (stream_b > 0 and t_overlap < t_serial):
            raise AssertionError(
                f"prefetch ring hid nothing: streamed {stream_b} bytes, "
                f"overlap {t_overlap} vs serial {t_serial}")
        full_bytes = float(max(m["spill_host_bytes"] +
                               m["spill_resident_bytes"]
                               for m in res_ws.metrics))
        rows.append({
            "benchmark": "superstep",
            "workload": wname,
            "partitioner": "2d",
            "transport": "dense",
            "codec": "f32",
            "pipeline": False,
            "working_set": 0.5,
            "supersteps": res_ws.supersteps,
            "bitexact_vs_resident": True,
            "stream_bytes": round(stream_b),
            "stream_time_serial_s": t_serial,
            "stream_time_overlap_s": t_overlap,
            "prefetch_hidden_frac": round(1.0 - t_overlap / t_serial, 4),
            # slimmest device carry the loop ran with, as a fraction of the
            # full vdata footprint — the out-of-core headline
            "spill_resident_bytes": round(min(
                m["spill_resident_bytes"] for m in res_ws.metrics)),
            "spill_resident_frac": round(min(
                m["spill_resident_bytes"] for m in res_ws.metrics)
                / max(full_bytes, 1.0), 4),
            "seconds_measured": round(sec, 4),
        })
    return rows


# deterministic fields the perf gate diffs (direction: which way is WORSE)
GATED_FIELDS = {
    "bytes_per_chip": ("up", 0.02),
    "bytes_link_modeled": ("up", 0.02),
    "mirror_hbm_bytes": ("up", 0.0),
    "step_time_modeled_s": ("up", 0.05),
    "supersteps": ("up", 0.0),
    "recompiles": ("up", 0.0),
    "materializations_fused": ("up", 0.0),
    "overlap_efficiency": ("down", 0.0),
    # §2.4 out-of-core lane (only the working_set < 1 rows carry these)
    "stream_time_overlap_s": ("up", 0.05),
    "spill_resident_bytes": ("up", 0.0),
    "prefetch_hidden_frac": ("down", 0.02),
}
ROW_KEY = ("workload", "partitioner", "transport", "codec", "pipeline",
           "working_set")


def trajectory(rows: list[dict]) -> dict:
    """The persisted BENCH document (no timestamps: byte-reproducible)."""
    return {
        "schema": 1,
        "bench": "superstep",
        "model": {"HBM_BW": HBM_BW, "LINK_BW": LINK_BW, "P": P},
        "gated_fields": {k: {"worse": d, "tol": t}
                         for k, (d, t) in GATED_FIELDS.items()},
        "row_key": list(ROW_KEY),
        # how rows from docs that PREDATE a key field key under the wider
        # schema (perf_gate fills these when diffing against an older
        # committed trajectory)
        "row_key_defaults": {"partitioner": "2d", "working_set": 1.0},
        "rows": rows,
    }

"""Enhanced Pregel on the GAS decomposition (paper §3.3, Listing 5).

The loop per superstep:
    msgs   = g.mrTriplets(send_msg, gather, skipStale)   # scatter+gather
    vdata' = vprog(vid, vdata, msg_or_default)           # apply
    active = changed(vdata, vdata')                      # vote-to-halt
until no vertex changed (all voted to halt) or max_supersteps.

Differences from classic Pregel, following the paper:
  * message computation sees BOTH endpoint attributes (triplet view) and the
    jaxpr analyzer prunes whichever side the UDF ignores (§4.5.2);
  * change tracking drives both skipStale edge skipping and incremental
    replicated-view maintenance (§4.5.1) via the GRAPH-RESIDENT view
    (DESIGN.md §3.1): the loop inherits whatever the operator chain before
    it already shipped, vprog's changed mask is folded back per leaf
    (passthrough leaves never re-ship), and the result graph exits WARM —
    downstream operators keep delta-shipping;
  * vprog runs on every visible vertex each superstep with a default message
    where none arrived — exactly `g.leftJoin(msgs).mapV(vprog)` of Listing 5;
  * `kernel_mode` threads through to mrTriplets' physical-plan choice:
    "auto" runs the fused triplet kernel (DESIGN.md §2.3) whenever the
    send/gather pair is eligible (sum/min/max over flat float payloads),
    "unfused" pins the gather -> vmap -> segment-reduce plan.

Two drivers:
  * `pregel` — host loop, jitted superstep, per-step metrics (benchmarks);
  * `pregel_fused` — single `lax.while_loop` program (the dry-run artifact:
    the whole algorithm lowers to one XLA program on the production mesh).
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

_log = logging.getLogger(__name__)

from . import analysis
from . import trace
from . import transport as transport_mod
from . import view as view_mod
from .graph import PARTS, WHOLE, Graph, per_partition
from .mrtriplets import (_plan_apply, apply_plan_of, fused_apply_home,
                         metrics_across, mr_triplets)
from .tree import elem_spec, tree_changed, tree_where, vmap2


@dataclasses.dataclass
class PregelResult:
    graph: Graph
    supersteps: int
    metrics: list[dict]     # per-superstep engine metrics
    # the jitted superstep the run compiled: `step.lower(graph,
    # transport=...)` gives its program for inspection (HLO, memory).
    step: Callable | None = None


def _superstep(g: Graph, tstate=None, *, vprog, send_msg, gather,
               default_msg, skip_stale, changed_fn, kernel_mode, use_cache,
               payload_bound=None, transport=None, fuse_apply="auto"):
    """One BSP superstep.  The incremental view rides the GRAPH itself
    (§3.1): mr_triplets refreshes `g.view` (full ship when cold, per-leaf
    delta when warm — including a view inherited from operators BEFORE the
    loop), and vprog's §4.5.1 changed mask is fed straight back into it, so
    the delta state also survives EXITING the loop into whatever operator
    chain consumes the result.

    fuse_apply: "auto" runs the §2.3.2 fused superstep kernel (combine +
    vprog + changed mask in one Pallas sweep) whenever the vprog/message
    shapes are eligible — the fusion is bit-exact vs this unfused path for
    ALL reduces: 'min'/'max' combine order-independently, and 'sum' pins a
    FIXED accumulation order (ascending source partition; the apply tile
    tables and the jnp oracle group rows by source partition, each group
    collision-free) that both the fused kernel and the unfused scatter-add
    follow, so sums fuse by default too.  False / "unfused" pins this
    reference path; True / "always" is kept as an explicit pin."""
    gin = g if use_cache else g.replace(view=None)
    aplan = None
    if kernel_mode != "unfused" and fuse_apply not in (False, "unfused"):
        aplan = _plan_apply(g, vprog, send_msg, gather, changed_fn,
                            default_msg, payload_bound)
    msgs, exists, view, metrics = mr_triplets(
        gin, send_msg, gather, to="dst", skip_stale=skip_stale,
        kernel_mode=kernel_mode,
        payload_bound=payload_bound, transport=transport,
        transport_state=tstate, return_routed=aplan is not None)
    n_ships = metrics.get("ships", 0)
    # strip static (non-array) entries: they are not jit-returnable and are
    # re-derivable from the UDF analysis in the driver
    metrics = {k: v for k, v in metrics.items()
               if not isinstance(v, (str, int))}
    with jax.named_scope("graphx.apply"):
        if aplan is not None:
            # fused §2.3.2 path: `msgs` here is the RAW routed aggregate tree
            # (per-source-partition partials, not yet combined) — the kernel
            # combines them and runs vprog + changed derivation in one sweep,
            # so the combined messages / defaulted messages / changed mask
            # never materialise to HBM on the home side.
            new_vdata, changed = fused_apply_home(
                g, msgs, exists, "dst", gather, aplan, vprog, changed_fn,
                kernel_mode)
            msg_elem = jax.tree.unflatten(aplan.msg_treedef,
                                          list(aplan.msg_specs))
        else:
            msgs_or_default = tree_where(exists, msgs, jax.tree.map(
                lambda d, m: jnp.broadcast_to(jnp.asarray(d, m.dtype),
                                              m.shape),
                default_msg, msgs))
            new_vdata = vmap2(vprog)(g.s.home_vid, g.vdata, msgs_or_default)
            new_vdata = tree_where(g.vmask, new_vdata, g.vdata)
            if changed_fn is None:
                changed = tree_changed(new_vdata, g.vdata)
            else:
                changed = vmap2(changed_fn)(g.vdata, new_vdata)
            changed = changed & g.vmask
            msg_elem = elem_spec(msgs_or_default)
        live = changed.sum()
    if use_cache:
        # per-leaf dirty feed: leaves vprog provably passes through (jaxpr
        # analysis — delta PageRank's `deg`) stay CLEAN and never re-ship;
        # rewritten leaves go dirty exactly at the changed rows.  The
        # analysis is trace-time work: every driver jits this function
        # (pregel's step, pregel_fused, the shard_map harnesses), so it
        # runs per COMPILE, not per superstep.
        rewrites = analysis.analyze_rewrites(
            vprog, (jax.ShapeDtypeStruct((), g.s.home_vid.dtype),
                    elem_spec(g.vdata), msg_elem), 1)
        with jax.named_scope("graphx.view"):
            view = view_mod.view_after_rewrite(
                view, g.vdata, new_vdata, rewrites, changed)
    log = g.wire_log
    if log is not None:
        m = metrics["fwd"].merge(metrics["back"])
        log = log.add(n_ships, m.bytes_shipped, m.bytes_accounted)
    g2 = g.replace(vdata=new_vdata, active=changed,
                   view=view if use_cache else None, wire_log=log)
    return g2, live, metrics


def pregel(
    g: Graph,
    vprog: Callable,            # f(vid, vval, msg) -> vval'
    send_msg: Callable,         # f(src_vval, eval, dst_vval) -> msg pytree
    gather: str = "sum",
    *,
    default_msg: Any,
    max_supersteps: int = 50,
    skip_stale: str | None = "out",
    incremental: bool = True,
    changed_fn: Callable | None = None,
    kernel_mode: str = "auto",
    track_metrics: bool = False,
    payload_bound: int | None = None,
    transport: Any = None,
    fuse_apply: Any = "auto",
    checkpoint: Any = None,
    checkpoint_every: int | None = None,
    guard: Any = None,
    resume: bool = True,
    working_set_frac: float | None = None,
) -> PregelResult:
    """Host-driven BSP loop with a jitted superstep.

    working_set_frac: out-of-core vertex partitions (§2.4 / core/spill.py).
    A fraction in (0, 1] of the home-vertex cells stays device-resident
    between supersteps; the coldest cells (by active-set occupancy) spill
    to host DRAM after each step and stream back through a double-buffered
    prefetch ring before the next.  Values are bit-exact vs fully-resident
    (the jitted superstep always computes on the restored arrays); the
    per-step metrics gain the modeled streaming trajectory
    (`stream_time_serial` / `stream_time_overlap`, `spill_resident_bytes`).
    None (default) disables spilling; host-loop driver only.

    checkpoint: a directory path or `core.snapshot.SnapshotStore` enabling
    superstep checkpointing (§6): every `checkpoint_every` supersteps — and
    at the next boundary after `guard` (a `train.fault.PreemptionGuard`)
    reports a preemption, after which the loop exits — the full carry is
    snapshotted: the warm graph INCLUDING its view and dirty masks, the
    live count, and the concrete transport policy the next superstep would
    run with.  With `resume=True` (default) an existing snapshot in the
    store is restored before the loop starts, so re-running the same
    `pregel` call after a kill continues warm — delta shipping and the
    adaptive capacity schedule pick up where they left off, bit-exact with
    the uninterrupted run.

    fuse_apply: "auto" | True/"always" | False/"unfused" — see _superstep.

    payload_bound certifies a static |value| bound for integer payloads and
    messages (see mr_triplets) — it widens or narrows both the fused
    kernel's staging guard and the wire codec's lossless int width.  The
    per-superstep metrics carry `bytes_on_wire` (the §2.1 accounting
    number) and `bytes_shipped` (what the transport's collectives really
    moved): with a delta codec the changed mask the vote-to-halt loop
    already maintains reaches the physical wire, so converged regions stop
    paying bytes.

    transport: None/"dense" | "ragged" | "auto" | TransportPolicy
    (core/transport.py).  "auto" re-plans per superstep ON THE HOST: the
    hysteresis band on the observed active fraction picks dense vs ragged,
    and the ragged capacity tracks the previous superstep's route occupancy
    in cap_rounding-sized tiers — the jitted superstep takes the plan as
    static metadata, so each tier compiles once and shipped bytes shrink
    with the active set (the runtime lax.cond overflow fallback still
    guards every ragged step).  The per-superstep metrics record the
    decision next to `plan` ("transport", "transport_cap", "ragged").

    On a graph placed one partition per device (`Graph.place`) the step
    runs per partition and the loop reads values summed over the devices;
    `working_set_frac` and `checkpoint` are not supported there.

    Spans (core/trace.py): `graphx.pregel` over the call (`devices`, and
    `supersteps` set at exit), and in it `graphx.pregel.plan` before the
    loop, then per superstep `graphx.pregel.dispatch` (`first=1` where the
    call traced a program), `graphx.pregel.sync` (the host's reads of the
    step's results) and, where they run, `graphx.pregel.spill`, `.record`,
    `.adapt` and `.checkpoint`.  Only while a profiler trace runs does the
    sync span also read the fused triplet sweep's `chunks_live` and carry
    it beside the grid's static `chunks` and `grid_steps`, and, on a placed
    graph, `bytes_crossing`: the bytes the step's routes moved from one
    device to another (`ShipMetrics.bytes_link_modeled`, summed)."""

    if g.mesh is not None and (working_set_frac is not None
                               or checkpoint is not None):
        raise NotImplementedError(
            "pregel on a placed graph runs with every partition resident "
            "and without checkpoints: working_set_frac and checkpoint are "
            "not supported there")
    with trace.span("graphx.pregel", devices=g.num_devices) as whole:
        with trace.span("graphx.pregel.plan"):
            step = superstep_jit(
                vprog, send_msg, gather, default_msg=default_msg,
                skip_stale=skip_stale, changed_fn=changed_fn,
                kernel_mode=kernel_mode, incremental=incremental,
                payload_bound=payload_bound, fuse_apply=fuse_apply)

            # static join-elimination + physical-plan facts, derived once
            # from the INITIAL graph's specs (vprog may retype properties,
            # but every §3.3 algorithm keeps the message shape fixed across
            # supersteps)
            from .mrtriplets import _derive_need, plan_of, sweep_grid
            deps = analysis.analyze_message_fn(
                send_msg, elem_spec(g.vdata), elem_spec(g.edata),
                elem_spec(g.vdata))
            tp = transport_mod.resolve_transport(transport)
            fuse = (kernel_mode != "unfused"
                    and fuse_apply not in (False, "unfused"))
            static_info = {
                "join_arity": deps.n_way,
                "need": _derive_need(deps, None) or "none",
                "wire": (g.ex.codec.name if g.ex.codec is not None
                         else "f32"),
                "transport_policy": tp.kind,
                "plan": plan_of(g, send_msg, gather,
                                kernel_mode=kernel_mode,
                                payload_bound=payload_bound),
                "apply_plan": (apply_plan_of(
                    g, vprog, send_msg, gather, changed_fn=changed_fn,
                    default_msg=default_msg, kernel_mode=kernel_mode,
                    payload_bound=payload_bound) if fuse else "unfused")}
            # the fused triplet sweep's static grid, for the sync spans
            grid = (sweep_grid(g.s) if static_info["plan"] == "fused"
                    else None)

            # host-side transport re-planning ("auto"): superstep 0 is a
            # full ship (dense by construction), later plans come from
            # adapt_policy on the observed active fraction + route
            # occupancy of the step just run.
            cur_tp = transport_mod.DENSE if tp.kind == "auto" else tp

            # §6 superstep checkpointing: resolve the store and, on resume,
            # swap in the snapshotted carry BEFORE deriving anything from
            # the graph.
            store = None
            start = 0
            if checkpoint is not None:
                from . import snapshot as snapshot_mod
                store = (checkpoint
                         if isinstance(checkpoint, snapshot_mod.SnapshotStore)
                         else snapshot_mod.SnapshotStore(checkpoint))
                if resume and store.latest_step() is not None:
                    g, start, saved_tp, _live = snapshot_mod.restore_pregel(
                        store, g)
                    if saved_tp is not None:
                        # the snapshot stores the POST-adapt policy: the
                        # next superstep runs exactly the plan the killed
                        # run chose.
                        cur_tp = saved_tp

            # §2.4 out-of-core residency: the ring lives entirely in the
            # host loop (the jitted step never traces through it) — restore
            # before, spill after every superstep.
            ring = None
            if working_set_frac is not None and working_set_frac < 1.0:
                from . import spill as spill_mod
                ring = spill_mod.SpillRing(plan=spill_mod.plan_spill(
                    g, working_set_frac))

            n_visible = max(int(jnp.sum(g.vmask)), 1)
        # each DISTINCT static transport plan the jitted step has seen is
        # one XLA compile — the hysteresis in adapt_policy (prev=) exists to
        # keep this set small on oscillating frontiers.
        plans_seen = {cur_tp}

        all_metrics: list[dict] = []
        steps = 0
        for it in range(start, max_supersteps):
            if ring is not None:
                with trace.span("graphx.pregel.spill"):
                    g = ring.restore(g)    # prefetch ring drained: resident
            with trace.span("graphx.pregel.dispatch") as dispatch:
                programs = step._cache_size()
                g, live, metrics = step(g, transport=cur_tp)
                # first=1: this dispatch traced, lowered and compiled (or
                # loaded) a program — a plan's first, or the first after
                # the view's static state changed (the cold first ship)
                dispatch.set_metadata(
                    first=int(step._cache_size() > programs))
            steps += 1
            if ring is not None:
                with trace.span("graphx.pregel.spill"):
                    g = ring.spill(g)      # cold cells to host; carry slims
            fwd, back = metrics["fwd"], metrics["back"]
            with trace.span("graphx.pregel.sync") as sync:
                n_live = int(live)
                # §6 graceful-degradation accounting, surfaced every
                # superstep: overflow = ragged plan fell back to a dense
                # ship (bytes worse, values exact), wire_faults/degraded =
                # integrity-word failures retried / degraded to raw f32 for
                # the step.
                overflow_fallbacks = float(fwd.overflow + back.overflow)
                wire_faults = float(fwd.wire_faults + back.wire_faults)
                degraded_routes = float(fwd.degraded + back.degraded)
                # the grid counter is read only into a running trace: the
                # device has finished the step, so this adds one transfer
                if (grid is not None and "chunks_live" in metrics
                        and trace.active()):
                    sync.set_metadata(
                        chunks_live=int(metrics["chunks_live"]),
                        chunks=grid[0], grid_steps=grid[1])
                # bytes the step's routes moved from one chip to another
                if g.mesh is not None and trace.active():
                    sync.set_metadata(bytes_crossing=int(
                        fwd.bytes_link_modeled + back.bytes_link_modeled))
            if overflow_fallbacks:
                _log.warning(
                    "pregel superstep %d: ragged transport overflowed its "
                    "static capacity %d time(s); shipped dense this step "
                    "(values exact, bytes worse)", it,
                    int(overflow_fallbacks))
            if track_metrics:
                with trace.span("graphx.pregel.record"):
                    # scalars -> float; [P] vectors (per-destination
                    # occupancy, §2.1.3) -> plain lists so the dict stays
                    # JSON-able.
                    host_metrics = jax.tree.map(
                        lambda x: float(x) if jnp.ndim(x) == 0
                        else np.asarray(x).tolist(), metrics)
                    host_metrics.update(static_info)
                    host_metrics["transport"] = cur_tp.kind
                    host_metrics["transport_cap"] = cur_tp.cap or 0
                    host_metrics["transport_frac"] = (
                        cur_tp.capacity_frac if cur_tp.kind == "ragged"
                        else 0.0)
                    host_metrics["recompiles"] = len(plans_seen)
                    host_metrics["overflow_fallbacks"] = overflow_fallbacks
                    host_metrics["wire_faults"] = wire_faults
                    host_metrics["degraded_routes"] = degraded_routes
                    # pipeline-level accumulation (§3.1): the graph's wire
                    # log counts this loop's traffic on top of whatever the
                    # operator chain BEFORE it already shipped.
                    host_metrics["pipeline_ships"] = float(g.ships)
                    host_metrics["pipeline_bytes_shipped"] = float(
                        g.bytes_shipped)
                    if ring is not None:
                        # §2.4 modeled streaming trajectory: the rotation
                        # just run (this step's spill + the restore that
                        # preceded it).
                        host_metrics.update(ring.stream_times(g))
                        host_metrics["spill_resident_bytes"] = float(
                            ring.resident_bytes(g))
                        host_metrics["spill_host_bytes"] = float(
                            ring.host_bytes())
                    all_metrics.append(host_metrics)
            if n_live == 0:
                break
            if tp.kind == "auto":
                with trace.span("graphx.pregel.adapt"):
                    def _occ(m):
                        # per-DESTINATION occupancy vector when the
                        # transport surfaced one (§2.1.3 tier planning);
                        # scalar worst-route fraction otherwise.
                        v = np.asarray(m.route_active_frac)
                        if v.ndim == 1 and v.size > 1:
                            return tuple(float(x) for x in v)
                        return (int(m.route_active_max)
                                / max(m.route_width, 1))
                    cur_tp = transport_mod.adapt_policy(
                        tp, was_ragged=cur_tp.kind == "ragged",
                        active_frac=n_live / n_visible,
                        fwd_frac=_occ(fwd),
                        back_frac=_occ(back),
                        prev=cur_tp)
                plans_seen.add(cur_tp)
            if store is not None:
                # checkpoint AFTER adapt so the saved policy is the one the
                # next superstep would run; a preemption request (SIGTERM
                # via train.fault.PreemptionGuard) forces a snapshot at this
                # boundary and exits the loop.
                preempt = guard is not None and getattr(guard, "requested",
                                                        False)
                due = (checkpoint_every is not None
                       and (it + 1 - start) % checkpoint_every == 0)
                if due or preempt:
                    # snapshot the FULL graph: peek() merges the host store
                    # without draining the ring (§2.4 snapshot
                    # compatibility).
                    with trace.span("graphx.pregel.checkpoint"):
                        snapshot_mod.save_pregel(
                            store, it + 1,
                            ring.peek(g) if ring is not None else g,
                            cur_tp, live=n_live)
                    if preempt:
                        break
        if ring is not None:
            with trace.span("graphx.pregel.spill"):
                g = ring.materialize(g)    # exit fully resident
        whole.set_metadata(supersteps=steps)
    return PregelResult(graph=g, supersteps=steps, metrics=all_metrics,
                        step=step)


def superstep_jit(vprog: Callable, send_msg: Callable, gather: str, *,
                  default_msg: Any, skip_stale: str | None,
                  changed_fn: Callable | None, kernel_mode: str,
                  incremental: bool, payload_bound: int | None,
                  fuse_apply: Any) -> Callable:
    """`_superstep` over these UDFs as `pregel`'s jitted step:
    `step(g, transport=plan)`, the transport plan static.  The function is
    named so that its compiled module is `jit_pregel_superstep`.

    On a placed graph the step runs per partition under `shard_map`
    (`per_partition`), and `live` and the metrics come back summed over
    the devices; on any other graph it is `_superstep` alone."""
    def pregel_superstep(g, tstate=None, *, transport=None):
        def step(g, tstate):
            g2, live, metrics = _superstep(
                g, tstate, vprog=vprog, send_msg=send_msg, gather=gather,
                default_msg=default_msg, skip_stale=skip_stale,
                changed_fn=changed_fn, kernel_mode=kernel_mode,
                use_cache=incremental, payload_bound=payload_bound,
                transport=transport, fuse_apply=fuse_apply)
            return g2, g.ex.psum(live), metrics_across(metrics, g.ex)
        return per_partition(step, (PARTS, WHOLE, WHOLE))(g, tstate)
    return jax.jit(pregel_superstep, static_argnames=("transport",))


def pregel_fused(
    g: Graph,
    vprog: Callable,
    send_msg: Callable,
    gather: str = "sum",
    *,
    default_msg: Any,
    max_supersteps: int = 50,
    skip_stale: str | None = "out",
    incremental: bool = True,
    changed_fn: Callable | None = None,
    kernel_mode: str = "auto",
    payload_bound: int | None = None,
    transport: Any = None,
    fuse_apply: Any = "auto",
):
    """Entire Pregel run as one `lax.while_loop` XLA program.

    This is the artifact the multi-pod dry-run lowers: graph state threads
    through the loop carry, collectives appear inside the loop body, and the
    compiled HLO exposes the per-superstep collective schedule for the
    roofline analysis.

    transport: unlike the host driver, ONE XLA program cannot re-plan
    static capacities — an "auto" plan here keeps the policy's static
    capacity and switches dense<->ragged per superstep through the traced
    hysteresis `lax.cond` (the previous decision rides the loop carry).
    Not supported on a placed graph.
    """
    if g.mesh is not None:
        raise NotImplementedError(
            "pregel_fused on a placed graph: run pregel, whose step runs "
            "per partition")
    part = functools.partial(
        _superstep, vprog=vprog, send_msg=send_msg, gather=gather,
        default_msg=default_msg, skip_stale=skip_stale,
        changed_fn=changed_fn, kernel_mode=kernel_mode,
        use_cache=incremental, payload_bound=payload_bound,
        transport=transport_mod.resolve_transport(transport),
        fuse_apply=fuse_apply)

    # materialise the graph-resident view with one full ship so the carry
    # has static structure (the view rides INSIDE the graph now — §3.1)
    g0, live0, m0 = part(g, jnp.float32(0))

    def cond(carry):
        g_, live_, ts_, i_ = carry
        return jnp.logical_and(live_ > 0, i_ < max_supersteps)

    def body(carry):
        g_, live_, ts_, i_ = carry
        g2, live, m = part(g_, ts_)
        return (g2, live, m["transport_state"], i_ + 1)

    gN, _, _, steps = jax.lax.while_loop(
        cond, body, (g0, live0, m0["transport_state"], jnp.int32(1)))
    return gN, steps

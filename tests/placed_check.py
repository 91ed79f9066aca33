"""Subprocess helper: a graph placed one partition per device
(`Graph.place`) through the public entries, on 4 simulated host devices.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4 (the parent
test tests/test_placed.py sets this; it must be set before jax
initialises, hence a subprocess).  Prints one JSON object: every fact the
parent test asserts on, computed here.

  * `algorithms.pagerank`, `connected_components` and `sssp` on the placed
    graph against their numpy references and against the same graph
    unplaced (LocalExchange on one device); PageRank and WCC again through
    the Pallas sweep in interpret mode, placed against unplaced;
  * where the partitions live, the collectives in the step's program, and
    how many programs the jitted step compiled;
  * the spans and counters of a traced run: `graphx.place`, the `devices`
    tag, `bytes_crossing` on every sync span against the step's own
    metrics;
  * what a placed graph refuses (`working_set_frac`, `checkpoint`,
    `pregel_fused`), and the dry run's partitioner sweep, which lowers
    `pregel`'s step for a placed graph.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import glob  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import Graph, algorithms as alg  # noqa: E402
from repro.core.pregel import pregel, pregel_fused  # noqa: E402
from repro.data import rmat, symmetrize  # noqa: E402

P = 4
ITERS = 10


def visible(g, res, leaf):
    """{vertex id: value} of a result's leaf over the visible vertices."""
    m = np.asarray(g.vmask)
    return dict(zip(np.asarray(g.s.home_vid)[m].tolist(),
                    np.asarray(res.graph.vdata[leaf])[m].tolist()))


def hops(sd, key):
    """BFS hop distance of every vertex id from `key` (absent unreached)."""
    nbr: dict[int, list[int]] = {}
    for s, d in zip(sd.src.tolist(), sd.dst.tolist()):
        nbr.setdefault(s, []).append(d)
    dist, frontier = {key: 0}, [key]
    while frontier:
        nxt = []
        for u in frontier:
            for v in nbr.get(u, ()):
                if v not in dist:
                    dist[v] = dist[u] + 1
                    nxt.append(v)
        frontier = nxt
    return dist


def host_spans(logdir):
    """[(name, args)] of the trace's graphx.* host spans, in start order."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.start_ns, e.name, dict(e.stats)) for e in line.events
                    if e.name.startswith("graphx.")]
    return [(name, args) for _, name, args in sorted(out)]


def raises(fn) -> bool:
    try:
        fn()
    except NotImplementedError:
        return True
    return False


def main():
    assert jax.device_count() >= P, jax.device_count()
    out = {}
    devices = jax.devices()[:P]
    gd = symmetrize(rmat(8, 8, seed=3))
    n = int(max(gd.src.max(), gd.dst.max())) + 1
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=P)
    pg = g.place(devices)
    out["ex"] = type(pg.ex).__name__
    out["num_devices"] = pg.num_devices
    shards = pg.vmask.addressable_shards
    out["shard_devices"] = sorted(s.device.id for s in shards)
    out["shard_rows"] = sorted({s.data.shape[0] for s in shards})

    # ---- PageRank: reference, unplaced run, programs, collectives --------
    want = alg.pagerank_reference(gd.src, gd.dst, n, num_iters=ITERS)
    r0 = alg.pagerank(g, num_iters=ITERS, kernel_mode="ref")
    r1 = alg.pagerank(pg, num_iters=ITERS, kernel_mode="ref")
    got, base = visible(g, r1, "pr"), visible(g, r0, "pr")
    out["pr_rel_err_reference"] = max(abs(v - want[k]) / want[k]
                                      for k, v in got.items())
    out["pr_rel_err_unplaced"] = max(abs(v - base[k]) / abs(base[k])
                                     for k, v in got.items())
    out["pr_supersteps"] = [r0.supersteps, r1.supersteps]
    out["pr_programs"] = [r0.step._cache_size(), r1.step._cache_size()]
    out["pr_result_placed"] = r1.graph.mesh is not None and sorted(
        s.device.id for s in r1.graph.vdata["pr"].addressable_shards) == \
        out["shard_devices"]
    hlo = r1.step.lower(r1.graph).compile().as_text()
    out["step_all_to_all"] = "all-to-all" in hlo
    # the eager degree sweep on the placed graph
    d0, _ = g.degrees("out", kernel_mode="ref")
    d1, _ = pg.degrees("out", kernel_mode="ref")
    out["degrees_equal"] = bool(np.array_equal(np.asarray(d0),
                                               np.asarray(d1)))

    # ---- CC and SSSP: exact ----------------------------------------------
    vids = sorted(set(gd.src.tolist()) | set(gd.dst.tolist()))
    cc_want = alg.connected_components_reference(gd.src, gd.dst, vids)
    c1 = alg.connected_components(pg, kernel_mode="ref")
    out["cc_mismatches"] = sum(int(v) != cc_want[k]
                               for k, v in visible(g, c1, "cc").items())
    key = int(gd.src[0])
    dist = hops(gd, key)
    s1 = alg.sssp(pg, key, kernel_mode="ref")
    unreached = float(np.finfo(np.float32).max)
    out["sssp_mismatches"] = sum(
        (v != dist[k]) if k in dist else (v != unreached)
        for k, v in visible(g, s1, "dist").items())
    out["sssp_supersteps"] = s1.supersteps
    out["sssp_depth"] = max(dist.values())

    # ---- the Pallas sweep (interpret mode) on each device's own rows ------
    # the tile tables' static slot rows shard with the placed graph, so
    # each device sweeps its own [1, n_chunks, eb] slice
    sg, sp = g, pg
    out["rows_shard_shape"] = sorted(
        {tuple(s.data.shape) for s in
         sp.s.tiles["dst"]["rows"].addressable_shards})
    out["rows_full_shape"] = list(sg.s.tiles["dst"]["rows"].shape)
    k0 = alg.pagerank(sg, num_iters=3, kernel_mode="interpret")
    k1 = alg.pagerank(sp, num_iters=3, kernel_mode="interpret")
    a, b = visible(sg, k0, "pr"), visible(sg, k1, "pr")
    out["kernel_pr_rel_err_unplaced"] = max(abs(v - a[k]) / abs(a[k])
                                            for k, v in b.items())
    w0 = alg.connected_components(sg, kernel_mode="interpret")
    w1 = alg.connected_components(sp, kernel_mode="interpret")
    out["kernel_cc_mismatches_unplaced"] = sum(
        int(v) != int(u) for v, u in zip(
            visible(sg, w0, "cc").values(), visible(sg, w1, "cc").values()))
    out["kernel_cc_supersteps"] = [w0.supersteps, w1.supersteps]

    # ---- spans and counters of a traced run ------------------------------
    logdir = tempfile.mkdtemp()
    with jax.profiler.trace(logdir):
        tg = g.place(devices)
        tr = alg.pagerank(tg, num_iters=3, kernel_mode="ref",
                          track_metrics=True)
        jax.block_until_ready(tr.graph.vdata)
    spans = host_spans(logdir)
    out["span_devices"] = {name: args.get("devices") for name, args in spans
                           if name in ("graphx.place", "graphx.algorithm",
                                       "graphx.pregel")}
    out["sync_bytes_crossing"] = [args.get("bytes_crossing")
                                  for name, args in spans
                                  if name == "graphx.pregel.sync"]
    out["metrics_bytes_crossing"] = [int(m["fwd"].bytes_link_modeled
                                         + m["back"].bytes_link_modeled)
                                     for m in tr.metrics]
    out["metrics_bytes_shipped"] = [m["bytes_shipped"] for m in tr.metrics]

    # ---- what a placed graph refuses --------------------------------------
    send = lambda sv, ev, dv: {"m": sv["cc"]}             # noqa: E731
    vprog = lambda vid, v, m: {"cc": jnp.minimum(v["cc"], m["m"])}  # noqa
    cg = pg.mapV(lambda vid, v: {"cc": vid})
    kw = dict(default_msg={"m": alg.IMAX}, kernel_mode="ref")
    out["refuses"] = {
        "working_set_frac": raises(lambda: pregel(
            cg, vprog, send, "min", working_set_frac=0.5, **kw)),
        "checkpoint": raises(lambda: pregel(
            cg, vprog, send, "min", checkpoint=tempfile.mkdtemp(), **kw)),
        "pregel_fused": raises(lambda: pregel_fused(
            cg, vprog, send, "min", **kw)),
    }

    # ---- the dry run's partitioner sweep, through the placed step --------
    from repro.launch.dryrun import check_bcast_single_allgather
    out["bcast_check"] = check_bcast_single_allgather(p=P)

    print(json.dumps(out))


if __name__ == "__main__":
    main()

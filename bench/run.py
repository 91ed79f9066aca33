#!/usr/bin/env python3
"""Graph-analytics benchmark: one cell of BENCHMARK.json per run, on the chip.

    python bench/run.py --workload ga500-16.pagerank --seed 7 --seconds 10 \\
        --trace 0

A run generates the cell's graph with the benchmark's own generator, builds
it with `repro.core.Graph.from_edges`, draws the traffic's inputs from
--seed, warms up with one whole job, then runs jobs back to back through
the program's public entries (`repro.core.algorithms`) until --seconds
have passed, letting the job in flight finish.  After the window it
compares every job's answer with the plain numpy reference and prints one
JSON line last: `correct`, `attempted`, `failed`, `metrics`, `device`, and
the numbers compared beside their limits under `checks`.  With --trace 1
the warm-up and the window run under the JAX profiler and `metrics` holds
the cell's per-layer metrics, read from the trace.  Earlier lines, one JSON
object each, give the set-up split, compiles in the window, peak memory and
grid sizes.

Everything is found by name: a configuration in `configs/<config>.json`, a
traffic mix in `traffic/<traffic>.json`, whose `job` names a job kind in
`jobs/<job>.py`, an end-to-end metric's reader in `end_to_end/<name>.py`
and a per-layer metric's reader in `layer_metrics/<name>.py`.  Chip peaks
are in `peaks.json`, keyed by the device kind.

Without a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result.  `run_cell(..., rehearsal=True)` runs a cell on the
CPU with the Pallas kernels in interpret mode for the tests; it checks the
answers and reports no device metric.
"""
from __future__ import annotations

import time

T0 = time.time()  # process start, for setup_s

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, ".bench_out")

for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(row: dict) -> None:
    print(json.dumps(row), flush=True)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH).replace("/", "_")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of BENCHMARK.json with its configuration, traffic, job
    kind and metric readers, all loaded by name from `bench_dir`."""

    def __init__(self, spec: dict, workload: str, bench_dir: str = BENCH):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise SystemExit(f"run.py: no workload {workload!r} in "
                             "BENCHMARK.json")
        self.w = cells[workload]
        self.name = workload
        self.chips = int(self.w["chips"])
        self.cfg = load_json(os.path.join(bench_dir, "configs",
                                          self.w["config"] + ".json"))
        self.traffic = load_json(os.path.join(bench_dir, "traffic",
                                              self.w["traffic"] + ".json"))
        self.job = load_module(os.path.join(bench_dir, "jobs",
                                            self.traffic["job"] + ".py"))

        def mine(metrics):
            return [m for m in metrics
                    if workload in m.get("workloads", [workload])]

        self.end_to_end = {
            m["name"]: (m, load_module(os.path.join(
                bench_dir, "end_to_end", m["name"] + ".py")))
            for m in mine(spec["end_to_end"])}
        self.per_layer = {
            m["name"]: (m, load_module(os.path.join(
                bench_dir, "layer_metrics", m["name"] + ".py")))
            for m in mine(spec["per_layer"])}
        self.peaks_path = os.path.join(bench_dir, "peaks.json")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from the
    persistent cache) inside a window: the union of its compile-event spans,
    so nested traces count once.  Also counts the persistent-cache misses,
    which are the real XLA compiles."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.spans: list[tuple[float, float]] = []
        self.misses: list[float] = []
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, **_):
        if event in self.EVENTS:
            self.spans.append((start, end))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses.append(time.time())

    def seconds(self, t0: float, t1: float) -> float:
        total, reach = 0.0, t0
        for a, b in sorted(self.spans):
            a, b = max(a, reach), min(b, t1)
            if b > a:
                total += b - a
                reach = b
        return total

    def compiles(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.misses)


def grid_counts(g) -> dict:
    """Grid steps of one fused triplet sweep and one fused apply over all
    partitions, and the tile-table bytes (static, from the structure)."""
    from repro.kernels.triplet import DEFAULT_VERTEX_BLOCK as vb
    s, p = g.s, g.s.p
    n_chunks = int(s.tiles["dst"]["chunk_out"].shape[1])
    n_apply = int(s.tiles["apply_dst"]["chunk_out"].shape[1])
    return {"chunks_per_partition": n_chunks,
            "triplet_grid_steps": p * -(-s.v_mir // vb) * p * n_chunks,
            "apply_grid_steps": p * -(-s.v_blk // vb) * p * n_apply,
            "tile_table_bytes": int(sum(a.nbytes for t in s.tiles.values()
                                        for a in t.values()))}


def build_graph(cell: Cell, ds, seed: int):
    """The program's graph of the dataset, edge list in the seed's order."""
    import numpy as np
    from graphs import edge_order
    from repro.core import Graph
    o = edge_order(ds, seed)
    kw = {}
    if cell.cfg["keep_isolated"]:
        kw = dict(vertex_keys=ds.vertices,
                  vertex_values=np.zeros(ds.num_vertices, np.float32))
    return Graph.from_edges(ds.src[o], ds.dst[o],
                            num_partitions=cell.cfg["partitions"],
                            partitioner=cell.cfg["partitioner"], **kw)


def home_positions(g, ds):
    """(mask over the [P, V_blk] home layout, vertex position of each
    visible row): how to read a job's answer by vertex position."""
    import numpy as np
    mask = np.asarray(g.s.home_mask)
    vids = np.asarray(g.s.home_vid)[mask]
    return mask, ds.index_of(vids), vids


def window(cell: Cell, g, queries: list, seconds: float, kernel_mode: str):
    """The warm-up job on the last query, then jobs back to back until
    `seconds` have passed; the job in flight finishes.  Each job ends in
    block_until_ready.  Returns the warm-up's host end, the window's jobs'
    (query, answer leaf, supersteps, host start, host end), and the
    window's host start and end.

    The warm-up goes through the very call that the window's jobs go
    through: a Pallas kernel's compiled body keeps the Python call stack it
    was traced from, and that stack is part of the compile cache's key, so
    a warm-up called from another line compiles programs that the window
    cannot find."""
    import jax
    import devtrace
    done = []
    i = -1
    while True:
        q = queries[i % len(queries)]
        ts = time.time()
        with jax.profiler.TraceAnnotation(
                "bench.warmup" if i < 0 else devtrace.JOB_SPAN, index=i):
            leaf, steps = cell.job.run(g, q, cell.traffic, kernel_mode)
            jax.block_until_ready(leaf)
        te = time.time()
        if i < 0:
            del leaf
            t_setup = t_start = te
        else:
            done.append((q, leaf, steps, ts, te))
            if te - t_start >= seconds:
                return t_setup, done, t_start, te
        i += 1


def check(cell: Cell, ds, g_layout, done) -> tuple[bool, int, dict]:
    """Every job's answer against the reference, after the window.  A
    number that is not finite is reported as 1e300, so the line stays
    plain JSON."""
    import math
    import numpy as np
    mask, pos, vids = g_layout
    limits = cell.traffic["limits"]
    off = np.setxor1d(vids, ds.vertices).size
    if off:
        checks = {"vertices_off": {"value": int(off), "limit": 0}}
        return False, len(done), checks
    results = []
    for q, leaf, steps, _, _ in done:
        got = np.empty(ds.num_vertices, np.float64)
        got[pos] = np.asarray(leaf)[mask]
        results.append((q, got, steps))
    values, failed = cell.job.compare(ds, cell.traffic, results)
    checks = {k: {"value": v if math.isfinite(v) else 1e300,
                  "limit": limits[k]} for k, v in values.items()}
    ok = failed == 0 and all(c["value"] <= c["limit"]
                             for c in checks.values())
    return ok, failed, checks


def trace_context(cell, ds, red, done, t_start, t_end, clock, peaks):
    """What the per-layer readers read: the trace's reduction, the host's
    compile seconds in the window, and the traffic's supersteps and least
    bytes over the window's jobs."""
    return types.SimpleNamespace(
        window_s=red["window_s"], busy_s=red["busy_s"],
        kernel_s=red["kernel_s"], other_s=red["other_s"],
        host_window_s=t_end - t_start,
        compile_s=clock.seconds(t_start, t_end),
        supersteps=sum(d[2] for d in done), jobs=len(done),
        least_bytes=sum(cell.job.least_bytes(ds, d[0], cell.traffic)
                        for d in done),
        peaks=peaks)


class Profiled:
    """The JAX profiler around the warm-up and the window of a traced run,
    which go through one call (`window`); the reduction reads the window's
    `bench.job` spans only."""

    def __init__(self, cell: Cell):
        self.dir = os.path.join(OUT_DIR, "trace", cell.name)

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()

    def reduce(self, done, clock):
        """The trace's reduction over the window's jobs, with the host's
        compile spans moved onto the trace clock."""
        import glob
        import devtrace
        path = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        ev = devtrace.events(path)
        if len(ev["jobs"]) != len(done):
            raise AssertionError(f"trace holds {len(ev['jobs'])} job spans, "
                                 f"the window ran {len(done)} jobs")
        # host clock -> trace clock, from the job spans both clocks saw
        offs = sorted(tj[0] - d[3] * 1e9 for tj, d in zip(ev["jobs"], done))
        off = offs[len(offs) // 2]
        spans = [(a * 1e9 + off, b * 1e9 + off) for a, b in clock.spans]
        return devtrace.reduce(ev, spans)


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, bench_dir: str = BENCH, rehearsal: bool = False,
             kernel_mode: str | None = None, t0: float = T0) -> dict:
    import jax
    from graphs import dataset

    cell = Cell(spec, workload, bench_dir)
    devices = jax.devices()
    clock = CompileClock()
    if kernel_mode is None:
        kernel_mode = "interpret" if rehearsal else "auto"

    t = time.time()
    ds = dataset(cell.cfg)
    gen_s = time.time() - t
    t = time.time()
    g = build_graph(cell, ds, seed)
    jax.block_until_ready(g.s)
    build_s = time.time() - t
    t = time.time()
    queries = cell.job.queries(ds, cell.traffic, seed)
    inputs_s = time.time() - t
    profiled = contextlib.nullcontext()
    if trace and not rehearsal:
        peaks = load_json(cell.peaks_path).get(devices[0].device_kind)
        if peaks is None:
            raise SystemExit(f"run.py: no peaks for device kind "
                             f"{devices[0].device_kind!r} in peaks.json")
        profiled = Profiled(cell)
    t_warm = time.time()
    with profiled:
        t_setup, done, t_start, t_end = window(cell, g, queries, seconds,
                                               kernel_mode)
    log({"phase": "setup", "workload": workload, "seed": seed,
         "vertices": ds.num_vertices, "edges": ds.num_undirected_edges,
         "gen_s": gen_s, "build_s": build_s, "inputs_s": inputs_s,
         "warmup_job_s": t_setup - t_warm,
         "warmup_compile_s": clock.seconds(t_warm, t_setup),
         "warmup_xla_compiles": clock.compiles(t_warm, t_setup),
         "setup_s": t_setup - t0, "queries": len(queries),
         **grid_counts(g)})
    red = profiled.reduce(done, clock) if trace and not rehearsal else None
    stats = devices[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    log({"phase": "window", "jobs": len(done), "window_s": t_end - t_start,
         "job_s": [d[4] - d[3] for d in done],
         "supersteps": [d[2] for d in done],
         "compile_s": clock.seconds(t_start, t_end),
         "xla_compiles": clock.compiles(t_start, t_end),
         "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
         "bytes_limit": stats.get("bytes_limit")})

    layout = home_positions(g, ds)
    del g
    ok, failed, checks = check(cell, ds, layout, done)

    rec = types.SimpleNamespace(
        graph_size=ds.num_vertices + ds.num_undirected_edges,
        jobs=len(done), window_s=t_end - t_start, setup_s=t_setup - t0)
    metrics, breakdown = {}, None
    if red is None and not rehearsal:
        for name, (m, reader) in cell.end_to_end.items():
            metrics[name] = {"value": reader.read(rec), "unit": m["unit"]}
    elif red is not None:
        ctx = trace_context(cell, ds, red, done, t_start, t_end, clock, peaks)
        for name, (m, reader) in cell.per_layer.items():
            v = reader.read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": m["unit"]}
        breakdown = red["breakdown"]
        log({"phase": "trace", "window_s": red["window_s"],
             "busy_s": red["busy_s"], "kernel_s": red["kernel_s"],
             "other_s": red["other_s"], "idle_by_why": red["idle_by_why"],
             "supersteps": ctx.supersteps, "least_bytes": ctx.least_bytes})
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if red is not None:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    result = {"correct": ok, "attempted": len(done), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = Cell(spec, args.workload)
    # JAX's persistent compilation cache, at a fixed path inside the
    # checkout; every program is cached, so only a checkout's first run of
    # a cell compiles and the window loads what the warm-up compiled.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # libtpu logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: JAX platform is {devices[0].platform!r}, no TPU",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The engine's host spans and grid counter, read back from a profiler trace.

PageRank and SSSP run on a small graph with the Pallas kernels in interpret
mode under `jax.profiler.trace`; the `.xplane.pb` it writes is read with
`ProfileData`, as a trace of a chip run is.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import Graph, algorithms as alg, trace
from repro.data import rmat, symmetrize

PREGEL_PHASES = {"graphx.pregel.plan", "graphx.pregel.dispatch",
                 "graphx.pregel.sync"}


def _graph():
    gd = symmetrize(rmat(6, 4, seed=11))
    return Graph.from_edges(gd.src, gd.dst, num_partitions=2,
                            edge_values={"w": np.ones(gd.num_edges,
                                                      np.float32)})


def _spans(logdir):
    """graphx.* spans of the trace: (name, start, end, args, parent index),
    the parent being the innermost enclosing span on the same thread."""
    path = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = sorted(((e.start_ns, -e.duration_ns, e.name, dict(e.stats))
                          for e in line.events
                          if e.name.startswith("graphx.")),
                         key=lambda x: x[:2])
            stack = []
            for start, neg, name, args in evs:
                end = start - neg
                while stack and out[stack[-1]][2] < end:
                    stack.pop()
                out.append((name, start, end, args,
                            stack[-1] if stack else None))
                stack.append(len(out) - 1)
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    g = _graph()
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        pr = alg.pagerank(g, num_iters=3, kernel_mode="interpret")
        bfs = alg.sssp(g, int(np.asarray(g.s.home_vid)[0, 0]),
                       kernel_mode="interpret")
        jax.block_until_ready((pr.graph.vdata, bfs.graph.vdata))
    return g, pr, bfs, _spans(logdir)


def test_spans_nest_as_the_driver_runs(traced):
    _, pr, bfs, spans = traced
    roots = [s for s in spans if s[0] == "graphx.algorithm"]
    assert [r[3]["name"] for r in roots] == ["pagerank", "sssp"]
    assert all(r[4] is None for r in roots)
    jobs = [r[3]["job"] for r in roots]
    assert jobs[1] == jobs[0] + 1
    parent = lambda s: spans[s[4]]
    for s in spans:
        if s[0] != "graphx.algorithm":
            # every span inside a job names its job, as its root does
            root = s
            while root[4] is not None:
                root = parent(root)
            assert root[0] == "graphx.algorithm"
            assert s[3]["job"] == root[3]["job"]
    for job, res in zip(jobs, (pr, bfs)):
        mine = [s for s in spans if s[3].get("job") == job]
        pregel = [s for s in mine if s[0] == "graphx.pregel"]
        assert len(pregel) == 1
        assert parent(pregel[0])[0] == "graphx.algorithm"
        assert pregel[0][3]["supersteps"] == res.supersteps
        phases = [s for s in mine if s[0].startswith("graphx.pregel.")]
        assert {s[0] for s in phases} == PREGEL_PHASES
        assert all(parent(s) is pregel[0] for s in phases)
        names = [s[0] for s in phases]
        assert names.count("graphx.pregel.plan") == 1
        assert names.count("graphx.pregel.dispatch") == res.supersteps
        assert names.count("graphx.pregel.sync") == res.supersteps
        ops = [s for s in mine if s[0] == "graphx.operator"]
        assert all(parent(s)[0] == "graphx.algorithm" for s in ops)
        assert all(s[2] <= pregel[0][1] for s in ops)
        want = {"pagerank": ["mrTriplets", "mapV"], "sssp": ["mapV"]}
        assert [s[3]["op"] for s in ops] == want[
            [r for r in roots if r[3]["job"] == job][0][3]["name"]]


def test_first_dispatch_of_each_plan_is_tagged(traced):
    _, pr, bfs, spans = traced
    for root, res in zip((s for s in spans if s[0] == "graphx.algorithm"),
                         (pr, bfs)):
        first = [s[3]["first"] for s in spans
                 if s[0] == "graphx.pregel.dispatch"
                 and s[3]["job"] == root[3]["job"]]
        # one plan (dense transport) per call: its first dispatch compiles,
        # and so does each dispatch whose graph changed static state (the
        # cold view's first ship); every program the step holds was tagged
        assert first[0] == 1
        assert sum(first) == res.step._cache_size() < len(first)
        assert first[-1] == 0


def test_sync_spans_carry_the_grid_counter(traced):
    g, _, _, spans = traced
    from repro.core.mrtriplets import sweep_grid
    chunks, grid_steps = sweep_grid(g.s)
    # the kernel's grid is one step per chunk of the flat space
    assert grid_steps == chunks == g.s.tiles["dst"]["chunk_out"].size
    syncs = [s for s in spans if s[0] == "graphx.pregel.sync"]
    assert syncs
    for s in syncs:
        a = s[3]
        assert (a["chunks"], a["grid_steps"]) == (chunks, grid_steps)
        assert 0 <= a["chunks_live"] <= a["grid_steps"] == a["chunks"]
    # PageRank sweeps every chunk that holds an edge, every superstep
    live = [s[3]["chunks_live"] for s in syncs]
    assert max(live) > 0


def test_nothing_recorded_without_the_profiler(tmp_path, monkeypatch):
    g = _graph()
    assert not trace.active()
    counts = []
    span = trace.span

    class Recorder:
        def __init__(self, ann):
            self.ann = ann

        def set_metadata(self, **kw):
            counts.append(kw)
            self.ann.set_metadata(**kw)

    def recording_span(name, /, **args):
        cm = span(name, **args)

        class Wrapped:
            def __enter__(self):
                return Recorder(cm.__enter__())

            def __exit__(self, *exc):
                return cm.__exit__(*exc)
        return Wrapped()

    monkeypatch.setattr(trace, "span", recording_span)
    res = alg.sssp(g, int(np.asarray(g.s.home_vid)[0, 0]),
                   kernel_mode="interpret")
    # the loop read no counter from the device: it set only host facts
    assert {k for c in counts for k in c} == {"first", "supersteps"}
    assert len(counts) == res.supersteps + 1
    logdir = str(tmp_path)
    with jax.profiler.trace(logdir):
        jax.block_until_ready(jax.numpy.zeros(3) + 1)
    assert not [s for s in _spans(logdir)]


def test_span_arguments_and_job_numbers():
    with trace.span("graphx.test") as ann:
        assert isinstance(ann, jax.profiler.TraceAnnotation)

    @trace.algorithm
    def outer():
        return trace._job.get(), inner()

    @trace.algorithm
    def inner():
        return trace._job.get()

    a, b = outer()
    assert a == b                      # a nested call joins the outer job
    assert outer()[0] == a + 1         # each public call takes a new number
    assert trace._job.get() is None

"""Reduce the program's own spans and counters in a traced run's profile.

The engine records host spans named `graphx.*` in the JAX profiler's trace
(`repro.core.trace`), on the device operations' clock, and names parts of
its jitted programs with `jax.named_scope`, which reach the compiled ops'
names.  This module reads both from the `.xplane.pb` that a `--trace 1`
run of `run.py` wrote, in the two steps of `devtrace`:

`events(path)` keeps what the reduction needs: the host's `graphx.*` spans
with their arguments and thread, its `bench.job` spans, and every device
operation's interval with the innermost `graphx.*` scope of its op name,
all in nanoseconds on the trace's clock.  A device
operation's op name (`tf_op`, the HLO metadata's jit and scope path) is a
stat of its event metadata, which `ProfileData` does not show; `op_names`
reads it from the file's protobuf fields.

`reduce(ev)` turns those into the window's numbers: the driver's planning
and warm dispatch time, the triplet grid's steps that did work, the device
time by scope (the stream gathers' among it), and the device's idle time
split by the innermost program span over it.

`reading(ctx)` is what the per-layer readers call: the reduction of the
newest trace under `<checkout>/.bench_out/trace/`, parsed once per process,
or None when that trace does not hold the window's jobs.  A program
without the spans, counters or scope reads None for what it lacks.

    python bench/progtrace.py [path/to/host.xplane.pb]

prints the reduction of a trace (by default the newest) as JSON.
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import sys

import devtrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_GLOB = os.path.join(ROOT, ".bench_out", "trace", "*", "plugins",
                          "profile", "*", "*.xplane.pb")
PREFIX = "graphx."
ROOT_SPAN = "graphx.algorithm"
PLAN = "graphx.pregel.plan"
DISPATCH = "graphx.pregel.dispatch"
SYNC = "graphx.pregel.sync"
STREAMS_SCOPE = "graphx.triplet_streams"
# stats of a device operation's event metadata: its HLO op name (the
# jit and named_scope path) and the program it belongs to
OP_NAME_STAT = "tf_op"
PROGRAM_STAT = "program_id"
OUTSIDE_SPANS = "bench.job, outside graphx spans"
BETWEEN_JOBS = "harness between jobs"


def newest_trace(pattern: str) -> str | None:
    paths = glob.glob(pattern)
    return max(paths, key=os.path.getmtime) if paths else None


def scope_of(op_name: str) -> str:
    """The innermost `graphx.*` scope in an op name, or "" for none."""
    inner = [p for p in op_name.split("/") if p.startswith(PREFIX)]
    return inner[-1] if inner else ""


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, start: int = 0, end: int | None = None):
    """(field number, value) of one protobuf message in buf[start:end]:
    varints as ints, length-delimited fields as (start, end) offsets."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        else:                                   # fixed 64 or 32 bits
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        yield key >> 3, v


def op_names(path: str) -> dict:
    """{(device plane, program id, HLO text): op name} of every device
    operation's event metadata in an `.xplane.pb` (XSpace.planes = 1;
    XPlane name = 2, event_metadata = 4, stat_metadata = 5, both maps of
    key = 1, value = 2; XEventMetadata name = 2, stats = 5; XStatMetadata
    name = 2; XStat metadata_id = 1, int64 = 4, uint64 = 3, str = 5,
    ref = 7, a ref naming a stat metadata entry)."""
    with open(path, "rb") as f:
        buf = f.read()
    text = lambda v: buf[v[0]:v[1]].decode("utf-8", "replace")
    out = {}
    for field, plane in _fields(buf):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = text(v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                entry = dict(_fields(buf, *v))
                meta = dict(_fields(buf, *entry[2]))
                stat_names[entry[1]] = text(meta[2]) if 2 in meta else ""
        if not name.startswith(devtrace.DEVICE_PLANE):
            continue
        for v in events:
            md = list(_fields(buf, *dict(_fields(buf, *v))[2]))
            stats = {}
            for f, st in md:
                if f != 5:
                    continue
                st = dict(_fields(buf, *st))
                key = stat_names.get(st.get(1))
                if 5 in st:
                    stats[key] = text(st[5])
                elif 7 in st:
                    stats[key] = stat_names.get(st[7], "")
                elif 4 in st or 3 in st:
                    stats[key] = st.get(4, st.get(3))
            hlo = [text(x) for f, x in md if f == 2]
            if OP_NAME_STAT in stats and hlo:
                out[(name, stats.get(PROGRAM_STAT), hlo[0])] = \
                    stats[OP_NAME_STAT]
    return out


def _program_id(module: str) -> int | None:
    """`jit_pregel_superstep(9269020766353316105)` -> 9269020766353316105."""
    head, _, rest = module.partition("(")
    return int(rest.rstrip(")")) if rest.rstrip(")").isdigit() else None


def events(path: str) -> dict:
    """spans: [name, thread, start, end, args]; jobs: [start, end];
    ops: [device, start, duration, innermost graphx scope or ""]."""
    from jax.profiler import ProfileData
    names = op_names(path)
    pd = ProfileData.from_file(path)
    spans, jobs, ops, devices = [], [], [], 0
    for plane in pd.planes:
        if plane.name.startswith(devtrace.DEVICE_PLANE):
            dev = devices
            devices += 1
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           _program_id(e.name))
                          for e in lines.get(devtrace.MODULES_LINE, []))
            starts = [m[0] for m in mods]
            for e in lines.get(devtrace.OPS_LINE, []):
                start = int(e.start_ns)
                i = bisect.bisect_right(starts, start) - 1
                prog = mods[i][2] if i >= 0 and start < mods[i][1] else None
                op = names.get((plane.name, prog, e.name), "")
                ops.append([dev, start, int(e.duration_ns), scope_of(op)])
        elif plane.name.startswith("/host:"):
            for t, line in enumerate(plane.lines):
                thread = f"{plane.name}#{t}"
                for e in line.events:
                    start, end = int(e.start_ns), int(e.end_ns)
                    if e.name == devtrace.JOB_SPAN:
                        jobs.append([start, end])
                    elif e.name.startswith(PREFIX):
                        spans.append([e.name, thread, start, end,
                                      {k: v for k, v in e.stats}])
    jobs.sort()
    spans.sort(key=lambda s: (s[2], -s[3]))
    return {"devices": devices, "spans": spans, "jobs": jobs, "ops": ops}


def _label(span) -> str:
    name, args = span[0], span[4]
    return f"{name} first={args['first']}" if "first" in args else name


def _innermost(spans, w0: int, w1: int, cuts=()) -> list:
    """[(a, b, span or None)] tiling [w0, w1], cut at every span's ends and
    at `cuts`: the innermost span over each piece, spans being nested (the
    latest start among those covering it)."""
    cuts = sorted({w0, w1} | {x for s in spans for x in (s[2], s[3])
                              if w0 < x < w1}
                  | {x for x in cuts if w0 < x < w1})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [s for s in spans if s[2] <= a and b <= s[3]]
        out.append((a, b, max(cover, key=lambda s: (s[2], -s[3]))
                    if cover else None))
    return out


def idle_by_span(ev: dict) -> dict:
    """Device 0's idle time in the window, in seconds, by the innermost
    program span the host was in: a `graphx.*` name (dispatch spans with
    their `first` tag), `bench.job` outside any of them, or the harness
    between jobs."""
    w0, w1 = ev["jobs"][0][0], ev["jobs"][-1][1]
    busy = devtrace._union([(max(s, w0), min(s + d, w1))
                            for dev, s, d, _ in ev["ops"]
                            if dev == 0 and min(s + d, w1) > max(s, w0)])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    threads = {s[1] for s in ev["spans"] if s[0] == ROOT_SPAN}
    pieces = _innermost([s for s in ev["spans"] if s[1] in threads], w0, w1,
                        [x for job in ev["jobs"] for x in job])
    starts = [p[0] for p in pieces]
    out: dict[str, float] = {}
    for a, b in gaps:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(pieces) and pieces[i][0] < b:
            pa, pb, span = pieces[i]
            t = min(b, pb) - max(a, pa)
            if t > 0:
                if span is not None:
                    why = _label(span)
                elif devtrace._overlap(pa, pb, ev["jobs"]) > 0:
                    why = OUTSIDE_SPANS
                else:
                    why = BETWEEN_JOBS
                out[why] = out.get(why, 0.0) + t / 1e9
            i += 1
    return out


def reduce(ev: dict) -> dict:
    """The window's program numbers; each is None when the trace holds
    nothing to compute it from.  Window = first job start to last job end,
    as in `devtrace.reduce`."""
    if not ev["jobs"]:
        raise ValueError("trace holds no job span")
    w0, w1 = ev["jobs"][0][0], ev["jobs"][-1][1]
    inside = [s for s in ev["spans"] if w0 <= s[2] and s[3] <= w1]
    plans = [s[3] - s[2] for s in inside if s[0] == PLAN]
    warm = [s[3] - s[2] for s in inside
            if s[0] == DISPATCH and s[4].get("first") == 0]
    counted = [s[4] for s in inside if s[0] == SYNC and "chunks_live" in s[4]]
    by_scope: dict[str, float] = {}
    for _, s, d, scope in ev["ops"]:
        t = min(s + d, w1) - max(s, w0)
        if t > 0:
            by_scope[scope] = by_scope.get(scope, 0.0) + t / 1e9
    n = max(ev["devices"], 1)
    by_scope = {k: v / n for k, v in by_scope.items()}
    return {
        "jobs": len(ev["jobs"]),
        "plan_s": sum(plans) / 1e9 if plans else None,
        "dispatch_warm_s": sum(warm) / len(warm) / 1e9 if warm else None,
        "supersteps_counted": len(counted),
        "chunks_live": sum(a["chunks_live"] for a in counted)
        if counted else None,
        "grid_steps": sum(a["grid_steps"] for a in counted)
        if counted else None,
        "streams_s": by_scope.get(STREAMS_SCOPE),
        "device_s_by_scope": by_scope,
        "idle_by_span": idle_by_span(ev) if ev["devices"] else {},
    }


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float) -> dict:
    return reduce(events(path))


def reading(ctx) -> dict | None:
    """The reduction of the newest trace, if it holds the window's jobs."""
    path = newest_trace(TRACE_GLOB)
    if path is None:
        return None
    red = _reduced(path, os.path.getmtime(path))
    return red if red["jobs"] == ctx.jobs else None


if __name__ == "__main__":
    p = sys.argv[1] if len(sys.argv) > 1 else newest_trace(TRACE_GLOB)
    if p is None:
        sys.exit("progtrace.py: no trace under .bench_out/trace/")
    print(json.dumps(reduce(events(p)), indent=1))

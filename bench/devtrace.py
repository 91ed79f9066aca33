"""Reduce a JAX profiler trace to the benchmark's device numbers.

Two steps, kept apart so that the second can be checked on a small recorded
trace:

`events(path)` reads the `.xplane.pb` a traced run wrote and keeps only what
the reduction needs: every operation on a TPU device plane's "XLA Ops" line
(a display name, the kernel it is, start and duration) and the host's
`bench.job` spans, all in nanoseconds on the trace's own clock.

`reduce(ev, ...)` turns those events into the traced window's busy time,
each Pallas kernel's device time, the time of the other device operations,
the idle gaps labelled by what the host was doing, and the breakdown lists
that the result line carries.

On a TPU v5e the "XLA Ops" events carry no source metadata: an event's name
is the HLO instruction's text, and the two Pallas kernels' bodies are both
called `kernel`.  Each kernel is a `tpu_custom_call` named after the jitted
wrapper that launches it, `fused_triplet` (kernels/triplet.py) and
`fused_apply` (kernels/superstep.py), and that is how they are told apart.
A kernel that stops being found is reported as missing, never as zero.
"""
from __future__ import annotations

import bisect
import re

JOB_SPAN = "bench.job"
KERNEL_OPS = {"fused_triplet": "triplet", "fused_apply": "apply"}
KERNEL_NAMES = {"triplet": "triplet kernel (kernels/triplet.py)",
                "apply": "apply kernel (kernels/superstep.py)"}
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def instruction(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion`."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def kernel_of(name: str) -> str | None:
    """Which Pallas kernel an "XLA Ops" event is, from its instruction."""
    if "custom-call(" not in name:
        return None
    return KERNEL_OPS.get(instruction(name))


def module_name(name: str) -> str:
    """`jit__unknown(9269020766353316105)` -> `jit__unknown`."""
    return name.split("(", 1)[0]


def events(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, jobs, devices = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = devices
            devices += 1
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           module_name(e.name))
                          for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            for e in lines.get(OPS_LINE, []):
                start = int(e.start_ns)
                i = bisect.bisect_right(starts, start) - 1
                mod = mods[i][2] if i >= 0 and start < mods[i][1] else "?"
                ops.append([dev, f"{mod}:{instruction(e.name)}",
                            kernel_of(e.name), start, int(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == JOB_SPAN:
                        jobs.append([int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)])
    jobs.sort()
    return {"devices": devices, "ops": ops, "jobs": jobs}


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(a, b, spans) -> int:
    return sum(max(0, min(b, e) - max(a, s)) for s, e in spans)


def reduce(ev: dict, compile_spans_ns=(), top: int = 10) -> dict:
    """Window = first job start to last job end on the trace clock.
    compile_spans_ns: host compile spans already moved onto that clock.
    Returns seconds: window, busy (union of device ops, averaged over
    devices), per-kernel and other device time, and the breakdown lists."""
    if not ev["jobs"] or not ev["devices"]:
        raise ValueError("trace holds no job span or no TPU device plane")
    w0, w1 = ev["jobs"][0][0], ev["jobs"][-1][1]
    busy_ns, kernel_ns, other_ns, by_label = 0, {}, 0, {}
    gaps_dev0 = []
    for dev in range(ev["devices"]):
        ivs = []
        for d, label, tag, start, dur in ev["ops"]:
            a, b = max(start, w0), min(start + dur, w1)
            if d != dev or b <= a:
                continue
            ivs.append((a, b))
            if tag is None:
                other_ns += b - a
            else:
                kernel_ns[tag] = kernel_ns.get(tag, 0) + b - a
                label = KERNEL_NAMES[tag]
            by_label[label] = by_label.get(label, 0) + b - a
        merged = _union(ivs)
        busy_ns += sum(b - a for a, b in merged)
        if dev == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps_dev0 = [(edges[i], edges[i + 1])
                         for i in range(0, len(edges), 2)
                         if edges[i + 1] > edges[i]]
    n = ev["devices"]
    compile_spans = _union(list(compile_spans_ns))
    labelled = []
    for a, b in gaps_dev0:
        if 2 * _overlap(a, b, compile_spans) >= b - a:
            why = "compile: trace, lower, compile or cache load"
        elif 2 * _overlap(a, b, ev["jobs"]) >= b - a:
            why = "inside a job call, not compiling"
        else:
            why = "harness between jobs"
        labelled.append((why, (b - a) / 1e9))
    labelled.sort(key=lambda x: -x[1])
    ops_top = sorted(by_label.items(), key=lambda x: -x[1])[:top]
    idle_by_why = {}
    for why, s in labelled:
        idle_by_why[why] = idle_by_why.get(why, 0.0) + s
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "kernel_s": {k: v / n / 1e9 for k, v in kernel_ns.items()},
        "other_s": other_ns / n / 1e9,
        "idle_by_why": idle_by_why,
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in ops_top],
            "idle_gaps": [[why, s] for why, s in labelled[:top]],
        },
    }

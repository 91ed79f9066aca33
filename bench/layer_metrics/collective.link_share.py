"""The collectives' share of their roofline: the bytes the window's
supersteps moved from one chip to another, per chip, at the chip's
inter-chip bandwidth, over the collectives' device time per chip.

Bytes: the sum of `bytes_crossing` over the window's `graphx.pregel.sync`
spans (each superstep's routes, leaving out every partition's block to
itself, summed over the chips), over the trace's chips.  Time: the mean
device seconds per chip of the ops under the `graphx.collective` scope
(`progtrace`).  A program without the counter or the scope reads None."""
import functools
import os

import devtrace
import progtrace

SCOPE = "graphx.collective"
COUNTER = "bytes_crossing"
# TPU v5e: 1,600 Gbit/s of chip-to-chip interconnect per chip (Google
# Cloud documentation, "TPU v5e")
LINK_BYTES_PER_S = 1600e9 / 8


def window_crossing(spans: list, jobs: list) -> int | None:
    """The counter summed over the sync spans inside the window of jobs
    (spans and jobs as `progtrace.events` gives them), or None where no
    such span carries it."""
    if not jobs:
        return None
    w0, w1 = jobs[0][0], jobs[-1][1]
    counts = [s[4][COUNTER] for s in spans
              if s[0] == progtrace.SYNC and COUNTER in s[4]
              and w0 <= s[2] and s[3] <= w1]
    return sum(counts) if counts else None


@functools.lru_cache(maxsize=2)
def crossing(path: str, mtime: float) -> tuple[int, int, int | None]:
    """(jobs, chips, `window_crossing`) of a trace, read from its host
    spans alone (the device operations are `progtrace`'s to read)."""
    from jax.profiler import ProfileData
    chips, jobs, syncs = 0, [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(devtrace.DEVICE_PLANE):
            chips += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    a, b = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    if e.name == devtrace.JOB_SPAN:
                        jobs.append([a, b])
                    elif e.name == progtrace.SYNC:
                        syncs.append([e.name, "", a, b, dict(e.stats)])
    jobs.sort()
    return len(jobs), chips, window_crossing(syncs, jobs)


def read(ctx):
    red = progtrace.reading(ctx)
    path = progtrace.newest_trace(progtrace.TRACE_GLOB)
    if red is None or path is None:
        return None
    seconds = red["device_s_by_scope"].get(SCOPE)
    jobs, chips, total = crossing(path, os.path.getmtime(path))
    if jobs != ctx.jobs or not seconds or not total or not chips:
        return None
    return 100.0 * total / chips / LINK_BYTES_PER_S / seconds

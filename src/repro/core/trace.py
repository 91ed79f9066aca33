"""Host spans of the engine, recorded in the JAX profiler's own trace.

A span is a `jax.profiler.TraceAnnotation`: the profiler buffers it in
memory beside the device's operations and writes both when the trace stops,
so spans and device time share one clock and one file.  With no trace
running a span costs a few microseconds and records nothing.

Names start with `graphx.`.  A span's parent is the span that encloses it
on the same thread.  Each public algorithm call opens a job
(`graphx.algorithm`, the job's root) and takes the next number of a
per-process counter; every span opened inside it carries that number as
`job=<n>`.  Spans belong in host code only: inside a jitted function they
would time the trace, not the run.  Device-side names are
`jax.named_scope`s, which land in the compiled program's op names.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools

import jax

_jobs = itertools.count(1)
_job: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "graphx_job", default=None)


def active() -> bool:
    """Whether a profiler trace is recording spans now."""
    return jax.profiler.TraceAnnotation.is_enabled()


@contextlib.contextmanager
def span(name: str, /, **args):
    """Record `name` (which starts with `graphx.`) over the block, with
    `args` and the current job number as its arguments.  Yields the
    annotation: `set_metadata(**counts)` adds arguments known only later."""
    job = _job.get()
    if job is not None:
        args["job"] = job
    with jax.profiler.TraceAnnotation(name, **args) as ann:
        yield ann


def algorithm(fn):
    """Run a public algorithm as one job under a `graphx.algorithm` span
    named after it, tagged with the devices its graph (the first argument)
    lives on.  An algorithm called from inside another's job joins that
    job."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        outer = _job.get()
        token = _job.set(outer if outer is not None else next(_jobs))
        devices = getattr(args[0], "num_devices", 1) if args else 1
        try:
            with span("graphx.algorithm", name=fn.__name__,
                      devices=devices):
                return fn(*args, **kwargs)
        finally:
            _job.reset(token)
    return run

"""Device milliseconds per superstep of the fused apply kernel
(`kernels/superstep.py`)."""


def read(ctx):
    s = ctx.kernel_s.get("apply", 0.0)
    return 1e3 * s / ctx.supersteps if s > 0 else None

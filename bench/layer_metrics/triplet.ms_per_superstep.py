"""Device milliseconds per superstep of the fused triplet sweep kernel
(`kernels/triplet.py`)."""


def read(ctx):
    s = ctx.kernel_s.get("triplet", 0.0)
    return 1e3 * s / ctx.supersteps if s > 0 else None

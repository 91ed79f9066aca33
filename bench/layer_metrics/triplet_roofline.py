"""The triplet kernel's share of its roofline: the least time the chip
could take for the jobs' triplet sweeps, over the kernel's device time.
The sweeps do a few float operations per byte, so HBM bandwidth bounds
them: least time = the traffic's least bytes / the chip's HBM bytes/s."""


def read(ctx):
    s = ctx.kernel_s.get("triplet", 0.0)
    if s <= 0:
        return None
    return 100.0 * ctx.least_bytes / ctx.peaks["hbm_bytes_per_s"] / s

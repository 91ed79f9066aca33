"""Share of the traced window in which no operation ran on the device:
1 - (union of the "XLA Ops" intervals / window), averaged over chips."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)

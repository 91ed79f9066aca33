"""Device milliseconds per superstep of the operations outside the two
Pallas kernels: view refresh, route exchange, packing, eager operators."""


def read(ctx):
    return 1e3 * ctx.other_s / ctx.supersteps

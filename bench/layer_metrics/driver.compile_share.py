"""Share of the traced window in which JAX traced, lowered, compiled or
loaded a program from the persistent cache: the union of JAX's
`/jax/core/compile/*` monitoring spans inside the window (host clock).
Every `pregel()` call builds a new jitted step, so each job pays this."""


def read(ctx):
    return 100.0 * ctx.compile_s / ctx.host_window_s

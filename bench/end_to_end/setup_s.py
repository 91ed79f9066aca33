"""Seconds from process start to the end of the warm-up job: JAX start-up,
graph generation, `Graph.from_edges`, the traffic's inputs, and one whole
job that traces, lowers and compiles (or loads from the persistent cache)
every program the window runs."""


def read(run):
    return run.setup_s

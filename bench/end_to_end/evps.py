"""Graphalytics EVPS: (vertices + undirected edges) of the graph times the
jobs completed in the window, over the seconds from the window's start to
the end of its last job (every job ends in block_until_ready)."""


def read(run):
    return run.graph_size * run.jobs / run.window_s

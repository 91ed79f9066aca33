"""Share of the triplet kernel's grid steps that did work: the sum of
`chunks_live` (chunks with a live edge, each working in one vertex-block
row) over the sum of `grid_steps` (vertex blocks x chunks), both read
from the `graphx.pregel.sync` spans of the window's supersteps."""
import progtrace


def read(ctx):
    red = progtrace.reading(ctx)
    if red is None or not red["grid_steps"]:
        return None
    return 100.0 * red["chunks_live"] / red["grid_steps"]

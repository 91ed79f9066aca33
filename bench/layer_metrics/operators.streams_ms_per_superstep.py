"""Device milliseconds per superstep of the operations whose op name lies
under the `graphx.triplet_streams` scope: the gathers through the chunk
permutation that build the triplet kernel's input streams."""
import progtrace


def read(ctx):
    red = progtrace.reading(ctx)
    if red is None or red["streams_s"] is None:
        return None
    return 1e3 * red["streams_s"] / ctx.supersteps

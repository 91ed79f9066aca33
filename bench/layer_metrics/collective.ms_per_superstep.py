"""Device milliseconds per superstep of the operations whose op name lies
under the `graphx.collective` scope: the all-to-all, ppermute, all-gather
and psum lanes that `SpmdExchange` runs between the chips of a placed
graph, as the mean over the chips.  A program without the scope reads
None."""
import progtrace

SCOPE = "graphx.collective"


def read(ctx):
    red = progtrace.reading(ctx)
    if red is None or not red["device_s_by_scope"].get(SCOPE):
        return None
    return 1e3 * red["device_s_by_scope"][SCOPE] / ctx.supersteps

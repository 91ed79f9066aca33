"""Mean host milliseconds of a warm superstep dispatch: the
`graphx.pregel.dispatch` spans with `first=0` in the window, each the
host's enqueue of a step it has already compiled.  The previous step's
`int(live)` sync has drained the device, so it idles for this long."""
import progtrace


def read(ctx):
    red = progtrace.reading(ctx)
    if red is None or red["dispatch_warm_s"] is None:
        return None
    return 1e3 * red["dispatch_warm_s"]

"""Host milliseconds per job that `pregel` spends before its loop: the
`graphx.pregel.plan` spans in the window (UDF analysis, plan choice, the
visible-vertex sync, building the jitted step), over the jobs."""
import progtrace


def read(ctx):
    red = progtrace.reading(ctx)
    if red is None or red["plan_s"] is None:
        return None
    return 1e3 * red["plan_s"] / ctx.jobs

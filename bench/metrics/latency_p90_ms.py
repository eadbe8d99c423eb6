"""latency_p90_ms: 90th percentile, over every request due in the window,
of the time from when it was due on the open-loop schedule to its answer.
Host clock.  Nothing when a request failed (it missed every limit) or the
loop is closed."""
from bench.work import percentile


def read(ctx):
    if ctx.loop != "open" or len(ctx.served) != len(ctx.outcomes):
        return None
    return 1e3 * percentile([o.done - ctx.t0 - o.request.due
                             for o in ctx.outcomes], 90)

"""Share of the window in which no operation ran on the device (1 minus
the union of the trace's operation intervals over the window)."""


def read(ctx):
    return ctx.idle_share()

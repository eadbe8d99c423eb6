"""setup_s: seconds from process start to the first request of the
window (weights, compile, warm-up).  Host clock."""


def read(ctx):
    return ctx.setup_s

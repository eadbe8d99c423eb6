"""tokens_per_s: prompt plus served tokens of every request sent in the
window, over the time from the window's start to the last answer.  Host
clock."""


def read(ctx):
    if not ctx.served:
        return None
    return sum(len(o.request.prompt) + len(o.tokens)
               for o in ctx.served) / ctx.window_s

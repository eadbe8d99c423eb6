"""Device time of the engine's prefill program per execution."""


def read(ctx):
    return ctx.program_ms("prefill")

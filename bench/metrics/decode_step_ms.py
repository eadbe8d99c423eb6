"""Device time of the engine's decode program per execution."""


def read(ctx):
    return ctx.program_ms("decode")

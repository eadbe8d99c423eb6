"""Mean active slots per decode tick: the served tokens of the window over
the count of ``engine.decode`` spans.  Each served token of the attention
family comes from exactly one tick, the first one too (the engine
re-decodes the last prompt token to produce it)."""
from bench import spans


def read(ctx):
    t = spans.traced(ctx)
    if t is None:
        return None
    _, ticks = spans.total(t, spans.ENGINE_DECODE)
    if not ticks:
        return None
    return sum(len(o.tokens) for o in ctx.served) / ticks

"""Share of the window in which the first device was idle between two
operations while the pump's innermost span was the engine's
(``engine.*``: admitting, dispatching, sampling, retiring), in percent
(bench/spans.py: idle_by_span)."""
from bench import spans

ENGINE = [n for n in spans.PUMP if n.startswith("engine.")]


def read(ctx):
    return spans.idle_share(ctx, ENGINE)

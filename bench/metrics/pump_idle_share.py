"""Share of the window in which the first device was idle between two
operations while the pump's innermost span was its own loop
(``replica.step`` outside the engine, ``replica.idle`` waiting for work),
in percent (bench/spans.py: idle_by_span)."""
from bench import spans


def read(ctx):
    return spans.idle_share(ctx, (spans.REPLICA_STEP, spans.REPLICA_IDLE))

"""The router's share of a request: the time in ``router.request`` less
the time in ``replica.request`` (the pick, the in-process REST hop and the
routing around the replica's handler), over the count of
``router.request`` spans.  Read from the program's spans in the window's
trace (bench/spans.py)."""
from bench import spans


def read(ctx):
    t = spans.traced(ctx)
    if t is None:
        return None
    routed, n = spans.total(t, spans.ROUTER_REQUEST)
    handled, m = spans.total(t, spans.REPLICA_REQUEST)
    if not n or not m:
        return None
    return (routed - handled) / n / 1e6
